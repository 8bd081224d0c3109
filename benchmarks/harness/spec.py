"""BENCHMARK.json and the data files it names, found by name.

A cell names a configuration and a traffic mix; a per-layer metric names
its reader file.  Nothing here knows any particular cell: a later PR adds
`configs/<config>.json`, `traffic/<mix>.json`,
`layer_metrics/<metric>.json` and one entry in BENCHMARK.json.  One entry
a READING: a new cell joins the `workloads` list of every entry whose
reader reads there, and brings entries only for readings no entry has.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import List

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as exc:
        raise SpecError(f"missing file {path}") from exc


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<mix>.json
    end_to_end: List[dict]  # metric entries of BENCHMARK.json for this cell
    per_layer: List[dict]


def _in_cell(metric: dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def layer_metric_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "layer_metrics" / f"{name}.json"


def reading(metric: dict, bench_dir: Path = BENCH_DIR) -> tuple:
    """What an entry reads and which way it points: its reader file less
    `what`, with its arrow.  Two entries with one reading are one entry."""
    reader = load_json(layer_metric_file(metric["name"], bench_dir))
    reader.pop("what", None)
    return (json.dumps(reader, sort_keys=True),) + tuple(
        metric[k] for k in ("moves", "better", "unit", "source", "layer")
    )


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def resolve_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    bench_dir = root / "benchmarks"
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise SpecError(f"workload {name!r} names unknown config {entry['config']!r}")
    return Cell(
        name=name,
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        chips=int(entry["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, name)],
    )


def validate(bench: dict, root: Path = ROOT) -> List[str]:
    """The contract's rules a file can be checked against without a run.
    Returns the list of faults (empty = valid)."""
    faults: List[str] = []

    def name_ok(what: str, value: str) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            faults.append(f"{what}: bad name {value!r}")

    def line_ok(what: str, value: str) -> None:
        if not (isinstance(value, str) and 1 <= len(value) <= 200) or re.search(
            r"[\n\t]", value
        ):
            faults.append(f"{what}: not 1..200 characters on one line")

    want_keys = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}
    if set(bench) != want_keys:
        faults.append(f"top-level keys {sorted(bench)} != {sorted(want_keys)}")
        return faults
    if not 1 <= int(bench["run_seconds"]) <= 51:
        faults.append("run_seconds outside 1..51")
    for word in bench["command"]:
        line_ok("command", word)
    paths = bench["paths"]
    cfg_names, cfg_files = set(), set()
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok("config", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        for key in c["reduced"]:
            name_ok(f"config {c['name']} reduced", key)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            faults.append(f"config {c['name']}: file outside paths")
        if not (root / c["file"]).is_file():
            faults.append(f"config {c['name']}: no file {c['file']}")
        if c["name"] in cfg_names or c["file"] in cfg_files:
            faults.append(f"config {c['name']}: name or file used twice")
        cfg_names.add(c["name"])
        cfg_files.add(c["file"])
    cells, pairs = set(), set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')}: keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            name_ok(f"workload {key}", w[key])
        line_ok(f"workload {w['name']} why", w["why"])
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in cfg_names:
            faults.append(f"workload {w['name']}: unknown config {w['config']}")
        if not (root / "benchmarks" / "traffic" / f"{w['traffic']}.json").is_file():
            faults.append(f"workload {w['name']}: no traffic file {w['traffic']}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            faults.append(f"workload {w['name']}: name or pair used twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in bench["workloads"]}
    for c in cfg_names - used:
        faults.append(f"config {c}: used by no cell")
    if sum(w["chips"] == 4 for w in bench["workloads"]) > max(
        1, len(bench["workloads"]) // 4
    ):
        faults.append("too many four-chip cells")

    metric_names = set()
    readings: dict = {}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        faults.append("no setup_s")
    for kind, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in bench[kind]:
            if set(m) - {"workloads"} != keys:
                faults.append(f"{kind} {m.get('name')}: keys {sorted(m)}")
                continue
            name_ok(kind, m["name"])
            if not UNIT_RE.match(m["unit"]):
                faults.append(f"{kind} {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                faults.append(f"{kind} {m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                faults.append(f"{kind} {m['name']}: source {m['source']!r}")
            if m["name"] in metric_names:
                faults.append(f"metric {m['name']} named twice")
            metric_names.add(m["name"])
            for cell in m.get("workloads", ()):
                if cell not in cells:
                    faults.append(f"{kind} {m['name']}: unknown cell {cell}")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    faults.append(f"end_to_end {m['name']}: source {m['source']}")
                if not 0 < m["bound"] <= 0.1:
                    faults.append(f"end_to_end {m['name']}: bound {m['bound']}")
            else:
                line_ok(f"per_layer {m['name']} layer", m["layer"])
                if not layer_metric_file(m["name"], root / "benchmarks").is_file():
                    faults.append(f"per_layer {m['name']}: no reader file")
                else:
                    twin = readings.setdefault(reading(m, root / "benchmarks"), m["name"])
                    if twin != m["name"]:
                        faults.append(
                            f"per_layer {m['name']}: the reading {twin} already has; "
                            f"a cell joins that entry's workloads"
                        )
                moved = e2e.get(m["moves"])
                if moved is None:
                    faults.append(f"per_layer {m['name']}: moves unknown {m['moves']}")
                    continue
                for cell in m.get("workloads", cells):
                    if not _in_cell(moved, cell):
                        faults.append(
                            f"per_layer {m['name']}: {m['moves']} is not "
                            f"reported in cell {cell}"
                        )
    listed = {m.get("name") for m in bench["per_layer"]}
    for f in sorted((root / "benchmarks" / "layer_metrics").glob("*.json")):
        if f.stem not in listed:
            faults.append(f"reader file {f.name}: no per_layer entry")
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if _in_cell(m, cell)]
        if len(mine) < 2 or not any(m["name"] == "setup_s" for m in mine):
            faults.append(f"cell {cell}: needs setup_s and one more end-to-end metric")
        if not any(_in_cell(m, cell) for m in bench["per_layer"]):
            faults.append(f"cell {cell}: no per-layer metric")
    return faults


def peaks_for(device_kind: str) -> dict:
    table = load_json(Path(__file__).resolve().parent / "peaks.json")
    row = table["devices"].get(device_kind)
    if row is None:
        raise SpecError(
            f"device_kind {device_kind!r} is not in benchmarks/harness/peaks.json"
        )
    return row
