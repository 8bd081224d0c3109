"""How widely a metric's readings spread over one set of runs, as a share of
their median, and the bound that recorded spreads give.  No chip, no JAX.

Two distances, each also with the run farthest from the median left out
where that narrows it (the ledger's words for what the driver holds a cell
to): largest less smallest, and the distance between the quartiles as
Python's `statistics.quantiles(values, n=4)` gives them (the contract's
words; the ledger's figures are of this kind: 0.989583 tokens/s is 47.5
tokens in 48 s, and whole tokens have no half but between two of five).

The evidence of a cell is `benchmarks/evidence/<cell>.json`: every run of the
sets its bounds were set from, and the spreads the driver's ledger holds for
the cell.  `rule_bound` turns it into the least bound a metric may have;
`faults` holds BENCHMARK.json to it (tests/benchmarks/test_bench_bounds.py).

    python3 -m benchmarks.harness.spread runs chiprun_out/ev/A/*.out   # saved runs -> one set's entries and spreads
    python3 -m benchmarks.harness.spread evidence                      # each cell's spreads, rule and bound
"""

from __future__ import annotations

import json
import math
import re
import statistics
import sys
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from benchmarks.harness.spec import ROOT, SpecError, _in_cell, load_benchmark, load_json

STEP = 0.005  # a bound is a multiple of this
CEILING = 0.1  # the contract's largest bound, and its word for `setup_s`
# bound >= room x the largest spread of each kind.  The driver refuses a bound as
# too tight where its own runs' trimmed quartile distance is over half of it: 1.4 x
# that again; an untrimmed one is at least its trimmed twin, so twice it will do.
ROOMS = {"range_trimmed": 1.4, "quartile_trimmed": 2.8, "quartile": 2.0}
MIN_RUNS, MIN_SET = 12, 6


def range_spread(values: Sequence[float]) -> float:
    """Largest less smallest, as a share of the median."""
    return (max(values) - min(values)) / _median(values)


def quartile_spread(values: Sequence[float]) -> float:
    """Third quartile less first, as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / _median(values)


def without_farthest(values: Sequence[float]) -> List[float]:
    """The readings less the one farthest from their median (of several
    equally far, the first).  Fewer than three are returned whole: two
    readings left alone say nothing of a third."""
    values = list(values)
    if len(values) < 3:
        return values
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return values[:far] + values[far + 1:]


def trimmed(spread: Callable[[Sequence[float]], float], values: Sequence[float]) -> float:
    """`spread` of the set, or of the set without its farthest run where that
    is narrower."""
    return min(spread(values), spread(without_farthest(values)))


def _median(values: Sequence[float]) -> float:
    if len(values) < 2:
        raise ValueError("a spread needs two readings or more")
    mid = statistics.median(values)
    if mid == 0:
        raise ValueError("a spread is a share of the median, and the median is 0")
    return abs(mid)


def set_spreads(values: Sequence[float]) -> Dict[str, float]:
    return {
        "range": range_spread(values),
        "range_trimmed": trimmed(range_spread, values),
        "quartile": quartile_spread(values),
        "quartile_trimmed": trimmed(quartile_spread, values),
    }


# ---- saved runs

_CELL = re.compile(r"^\[bench\] cell (\S+): config \S+, traffic \S+, seed (\d+), ([\d.]+)s, trace (\d)")
_CHECK = re.compile(r"^\[bench\] check: largest \|logprob - reference\| ([\d.]+) \(mean ([\d.]+)\)")
_WINDOW = re.compile(r"^\[bench\] window ([\d.]+)s: (\d+) tokens, (\d+) gaps, (\d+) first tokens")
_OPENS = re.compile(r"^\[bench\] window opens: setup [\d.]+s, phases (\{.*\})$")
_READINGS = "[bench] client readings: "


def parse_run(text: str) -> dict:
    """One saved run (the standard output of `benchmarks.run`), read.  The
    last line is the result; the `[bench]` lines
    before it give the seed, the phases of set-up, the token counts, the
    check's errors and, in both kinds of run, the client's end-to-end readings."""
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines or not lines[-1].startswith("{"):
        raise ValueError("no result on the last line: the run did not reach its end")
    result = json.loads(lines[-1])
    run: dict = {
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"], "device": result["device"]["kind"],
        "memory_peak_bytes": result["device"]["memory_peak_bytes"],
    }
    readings: dict = {}
    for ln in lines[:-1]:
        if m := _CELL.match(ln):
            run.update(cell=m[1], seed=int(m[2]), seconds=float(m[3]), trace=int(m[4]))
        elif m := _CHECK.match(ln):
            run.update(check_max_err=float(m[1]), check_mean_err=float(m[2]))
        elif m := _WINDOW.match(ln):
            run.update(tokens=int(m[2]), first_tokens=int(m[4]))
        elif m := _OPENS.match(ln):
            run["setup_phases"] = json.loads(m[1])
        elif ln.startswith(_READINGS):
            readings = json.loads(ln[len(_READINGS):])
    if "seed" not in run or not readings:
        raise ValueError("no `[bench] cell` or `client readings` line: not a saved run")
    run["readings"] = readings
    run["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    return run


def end_to_end_of(run: dict, names: Iterable[str]) -> Dict[str, float]:
    """A run's end-to-end readings: the result's in an untraced run, the
    client's own (printed in both kinds) in a traced one."""
    src = run["readings"] if run.get("trace") else run["metrics"]
    return {n: src[n] for n in names if n in src}


def evidence_runs(runs: Sequence[dict], names: Sequence[str]) -> List[dict]:
    """One set's parsed runs as the entries of an evidence file.  A run whose
    set-up took over 1.5 x the set's median compiled (a warm set-up varies by
    a tenth at most, a compiling one takes 2.7 x and more)."""
    e2e = [end_to_end_of(r, names) for r in runs]
    warm = statistics.median(m["setup_s"] for m in e2e)
    out = []
    for r, m in zip(runs, e2e):
        entry = {k: r[k] for k in (
            "seed", "trace", "tokens", "first_tokens", "attempted", "failed", "correct",
            "device", "memory_peak_bytes", "check_max_err", "check_mean_err", "setup_phases")}
        entry.update(compiled=m["setup_s"] > 1.5 * warm, metrics=m)
        if r["trace"]:
            entry["per_layer"] = r["metrics"]
        out.append(entry)
    return out


# ---- evidence and the rule


def evidence_file(cell: str, root: Path = ROOT) -> Path:
    return root / "benchmarks" / "evidence" / f"{cell}.json"


def full_sets(evidence: dict) -> List[dict]:
    """The sets the rule reads: those of MIN_SET untraced runs or more.  A
    smaller one (a proof run of the final tree) is recorded and not read."""
    return [s for s in evidence["sets"]
            if sum(not r["trace"] for r in s["runs"]) >= MIN_SET]


def counted(runs: Sequence[dict], metric: str) -> List[float]:
    """The metric's readings in the untraced runs that count: for `setup_s` a
    run on a checkout that compiled does not (the driver keeps
    `first_setup_s` apart in the same way)."""
    return [
        r["metrics"][metric] for r in runs
        if not r["trace"] and metric in r["metrics"]
        and not (metric == "setup_s" and r["compiled"])
    ]


def metric_sets(evidence: dict, metric: str) -> Dict[str, List[float]]:
    """The metric's counted readings by full set, where a set has two."""
    sets = {s["name"]: counted(s["runs"], metric) for s in full_sets(evidence)}
    return {name: vals for name, vals in sets.items() if len(vals) >= 2}


def largest_spreads(evidence: dict, metric: str) -> Dict[str, float]:
    """The largest spread of each kind the rule reads, over the builder's
    full sets and the driver's recorded spreads alike (a record's `kind` says
    which distance the ledger's figure is, or is taken to be)."""
    out = {kind: 0.0 for kind in ROOMS}
    for vals in metric_sets(evidence, metric).values():
        spreads = set_spreads(vals)
        for kind in out:
            out[kind] = max(out[kind], spreads[kind])
    for d in evidence.get("driver", ()):
        if metric in d["spread"]:
            out[d["kind"]] = max(out[d["kind"]], d["spread"][metric])
    return out


def rule_bound(evidence: dict, metric: str) -> float:
    """The least bound the evidence allows: the smallest multiple of STEP
    that leaves every kind of spread its room, and never over the ceiling.
    `setup_s` stands at the ceiling by the contract's word; the driver judges
    it by its median alone, and its spreads are recorded to be read."""
    if metric == "setup_s":
        return CEILING
    need = max(ROOMS[kind] * value for kind, value in largest_spreads(evidence, metric).items())
    return min(CEILING, max(1, math.ceil(round(need / STEP, 9))) * STEP)


def faults(bench: dict, root: Path = ROOT) -> List[str]:
    """Every end-to-end bound against its cells' evidence; empty = sound."""
    out: List[str] = []
    for w in bench["workloads"]:
        cell = w["name"]
        try:
            ev = load_json(evidence_file(cell, root))
        except SpecError:
            out.append(f"cell {cell}: no evidence file {evidence_file(cell, root).name}")
            continue
        full = full_sets(ev)
        if len(full) < 2 or sum(not r["trace"] for s in full for r in s["runs"]) < MIN_RUNS:
            out.append(f"cell {cell}: the evidence holds fewer than {MIN_RUNS} untraced "
                       f"runs in sets of {MIN_SET}")
            continue
        for m in bench["end_to_end"]:
            if not _in_cell(m, cell):
                continue
            if len(metric_sets(ev, m["name"])) < 2:
                out.append(f"cell {cell}, {m['name']}: fewer than two sets read it")
                continue
            least = rule_bound(ev, m["name"])
            if not least - 1e-12 <= m["bound"] <= CEILING:
                out.append(f"cell {cell}, {m['name']}: bound {m['bound']} is outside "
                           f"[{least:g}, {CEILING}], what its evidence allows")
    return out


def least_bounds(bench: dict, root: Path = ROOT) -> Dict[str, float]:
    """For each end-to-end metric, the least bound that every cell reporting
    it allows: what BENCHMARK.json should say, since a bound the evidence
    does not force is not loosened."""
    out: Dict[str, float] = {}
    for w in bench["workloads"]:
        ev = load_json(evidence_file(w["name"], root))
        for m in bench["end_to_end"]:
            if _in_cell(m, w["name"]):
                out[m["name"]] = max(out.get(m["name"], 0.0), rule_bound(ev, m["name"]))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["runs"] and argv[1:]:
        names = [m["name"] for m in load_benchmark()["end_to_end"]]
        runs = evidence_runs([parse_run(Path(p).read_text()) for p in argv[1:]], names)
        print(json.dumps(runs, indent=1))
        for name in names:
            vals = counted(runs, name)
            if len(vals) >= 2:
                print(name, "n", len(vals), "median", statistics.median(vals),
                      json.dumps(set_spreads(vals)), file=sys.stderr)
        return 0
    if argv[:1] == ["evidence"]:
        bench = load_benchmark()
        for w in bench["workloads"]:
            ev = load_json(evidence_file(w["name"]))
            for m in bench["end_to_end"]:
                if not _in_cell(m, w["name"]):
                    continue
                print(w["name"], m["name"], "bound", m["bound"], "rule", rule_bound(ev, m["name"]),
                      json.dumps(largest_spreads(ev, m["name"])))
                for name, vals in metric_sets(ev, m["name"]).items():
                    print("   set", name, "n", len(vals), "median", statistics.median(vals),
                          json.dumps({k: round(v, 5) for k, v in set_spreads(vals).items()}))
        for f in faults(bench):
            print("FAULT", f)
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
