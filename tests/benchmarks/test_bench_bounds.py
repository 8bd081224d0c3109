"""The spread of a set of runs as the driver takes it, and every end-to-end
bound of BENCHMARK.json held to the evidence recorded for its cells
(benchmarks/evidence/<cell>.json): a bound cannot drift from what was
measured, and a cell cannot come without its measurements."""

import json
import shutil
import statistics

import pytest

from benchmarks.harness import spec, spread

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
PAIRS = [(w["name"], m["name"]) for w in BENCH["workloads"]
         for m in BENCH["end_to_end"] if spec._in_cell(m, w["name"])]
DATA = spec.BENCH_DIR / "harness" / "testdata"


# ---- the spread, on made-up sets


@pytest.mark.parametrize(
    "values,range_,range_trimmed",
    [
        ([100, 101, 102, 103, 104, 150], 50 / 102.5, 4 / 102),  # one far above
        ([50, 100, 101, 102, 103, 104], 54 / 101.5, 4 / 102),  # one far below
        ([7, 7, 7, 7, 7, 7], 0.0, 0.0),  # a timeline that repeats to the token
        ([100, 100, 100, 100, 150, 150], 0.5, 0.5),  # two far off: leaving one out narrows nothing
        ([1, 2, 3], 1.0, 0.4),  # 1 and 3 equally far: the first goes, and the median moves
        ([10, 12], 2 / 11, 2 / 11),  # fewer than three: nothing is left out
    ],
)
def test_range_spread_with_and_without_the_farthest_run(values, range_, range_trimmed):
    assert spread.range_spread(values) == pytest.approx(range_)
    assert spread.trimmed(spread.range_spread, values) == pytest.approx(range_trimmed)


@pytest.mark.parametrize(
    "values",
    [[27.6, 28.7, 28.3, 27.8, 29.1, 28.7], [1771.2, 1781.1, 1752.4, 1783.5, 1761.3, 1777.4],
     [5, 3, 9], [4, 4, 4, 4, 4, 9]],
)
def test_quartile_spread_is_pythons_quartiles_over_the_median(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert spread.quartile_spread(values) == pytest.approx((q3 - q1) / statistics.median(values))
    assert spread.trimmed(spread.quartile_spread, values) <= spread.quartile_spread(values)


def test_the_farthest_run_is_left_out_only_where_that_narrows():
    values = [1, 1, 1, 7, 8, 8]  # without its first run the quartiles lie farther apart
    assert spread.quartile_spread(spread.without_farthest(values)) > spread.quartile_spread(values)
    assert spread.trimmed(spread.quartile_spread, values) == spread.quartile_spread(values)


def test_the_ledgers_figures_are_quartile_distances_of_five_runs():
    """PR 26's refusal quotes 0.989583 tokens/s: 47.5 tokens in 48 s.  Whole
    tokens differ by whole tokens; a half comes from the mean of two, which is
    where the quartiles of five readings lie."""
    tokens = [1300, 1320, 1336.5, 1340, 1370, 1365]  # any six; 1300 is farthest
    five = spread.without_farthest(tokens)
    assert 1300 not in five and len(five) == 5
    q1, _, q3 = statistics.quantiles(five, n=4)
    s = sorted(five)
    assert q1 == (s[0] + s[1]) / 2 and q3 == (s[3] + s[4]) / 2


@pytest.mark.parametrize("values", [[], [3.0], [0, 0, 0], [-1, 0, 1]])
def test_a_spread_needs_two_readings_and_a_median_to_be_a_share_of(values):
    with pytest.raises(ValueError):
        spread.range_spread(values)
    with pytest.raises(ValueError):
        spread.set_spreads(values)


# ---- saved runs


@pytest.mark.parametrize("name,trace", [("run_untraced.out", 0), ("run_traced.out", 1)])
def test_parser_reads_a_saved_run(name, trace):
    run = spread.parse_run((DATA / name).read_text())
    assert run["cell"] == "qwen3moe-ragprompt-sat" and run["trace"] == trace
    assert run["seed"] > 2**31 and run["seconds"] == 48.0
    assert run["correct"] is True and run["failed"] == 0 and run["device"] == "TPU v5 lite"
    assert run["tokens"] == round(run["readings"]["output_tokens_per_s"] * 48)
    assert 0 < run["check_mean_err"] < run["check_max_err"] < 0.09
    e2e = spread.end_to_end_of(run, [m["name"] for m in BENCH["end_to_end"]])
    assert set(e2e) == {"output_tokens_per_s", "ttft_p50_ms", "setup_s"}
    if trace:  # the result holds the per-layer metrics; the client's line the end-to-end ones
        assert "compiles_in_window" in run["metrics"] and "setup_s" not in run["metrics"]
    else:
        assert e2e == run["metrics"]


def test_parser_refuses_a_run_that_did_not_reach_its_end():
    text = (DATA / "run_untraced.out").read_text()
    with pytest.raises(ValueError, match="did not reach its end"):
        spread.parse_run(text.rsplit("\n{", 1)[0])
    with pytest.raises(ValueError, match="not a saved run"):
        spread.parse_run(text.strip().splitlines()[-1])


# ---- every bound against its evidence


@pytest.mark.parametrize("cell,metric", PAIRS)
def test_bound_is_what_its_evidence_allows_and_no_looser(cell, metric):
    ev = spec.load_json(spread.evidence_file(cell))
    bound = next(m["bound"] for m in BENCH["end_to_end"] if m["name"] == metric)
    full = spread.full_sets(ev)
    assert len(full) >= 2
    assert sum(not r["trace"] for s in full for r in s["runs"]) >= spread.MIN_RUNS
    top = spread.largest_spreads(ev, metric)
    assert set(top) == set(spread.ROOMS)
    least = min(spread.CEILING, max(spread.ROOMS[kind] * top[kind] for kind in top))
    assert least <= bound <= spread.CEILING
    # the smallest multiple of STEP that does, over the cells that report the metric
    assert bound == pytest.approx(spread.least_bounds(BENCH)[metric])


def test_benchmark_json_has_no_fault_against_its_evidence():
    assert spread.faults(BENCH) == []


@pytest.mark.parametrize("cell", CELLS)
def test_evidence_records_sound_runs_of_one_device(cell):
    ev = spec.load_json(spread.evidence_file(cell))
    assert ev["cell"] == cell and ev["run_seconds"] == BENCH["run_seconds"]
    runs = [r for s in ev["sets"] for r in s["runs"]]
    assert {r["device"] for r in runs} == {ev["device"]}
    for s in ev["sets"]:
        assert s["commit"] and s["call"]
        untraced = [r["seed"] for r in s["runs"] if not r["trace"]]
        assert len(set(untraced)) == len(untraced)  # a set's runs have a seed each
        assert all(r["seed"] > 2**31 for r in s["runs"])  # as large as the driver's
    for r in runs:
        assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
        if r["trace"]:
            assert r["per_layer"]["compiles_in_window"] == 0
        else:
            assert set(r["metrics"]) == {m["name"] for m in BENCH["end_to_end"]
                                         if spec._in_cell(m, cell)}
    for d in ev["driver"]:
        assert d["pr"] and d["origin"] and d["spread"]


def test_the_first_cells_evidence_is_three_sets_of_six_and_the_ledgers_spreads():
    ev = spec.load_json(spread.evidence_file("qwen3moe-ragprompt-sat"))
    sets = spread.metric_sets(ev, "output_tokens_per_s")
    assert sum(len(v) >= 6 for v in sets.values()) >= 3 and sum(map(len, sets.values())) >= 18
    assert sum(r["trace"] for s in ev["sets"] for r in s["runs"]) >= 2
    assert {d["pr"] for d in ev["driver"]} >= {25, 26}
    # the trimmed spreads PR 26's refusal quotes count like the builder's own
    assert spread.largest_spreads(ev, "output_tokens_per_s")["quartile_trimmed"] >= 0.0355


# ---- in a temporary copy: what the check reports


def copy_with(tmp_path, edit):
    root = tmp_path / "copy"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks")
    bench = json.loads(json.dumps(BENCH))
    edit(bench, root)
    return bench, root


def test_a_bound_under_its_evidence_is_a_fault(tmp_path):
    def edit(bench, root):
        bench["end_to_end"][0]["bound"] = 0.01  # where it stood until PR 27

    bench, root = copy_with(tmp_path, edit)
    assert spec.validate(bench, root) == []  # the contract admits it
    got = spread.faults(bench, root)
    # one fault a cell whose evidence asks for more than 1 %, however many cells there are
    assert got and all("output_tokens_per_s: bound 0.01 is outside" in f for f in got)
    cells = [f.split(",")[0] for f in got]
    assert len(set(cells)) == len(cells) and set(cells) <= {f"cell {c}" for c in CELLS}
    assert "cell qwen3moe-ragprompt-sat" in cells  # the cell PR 26 was refused in


def test_a_cell_without_evidence_is_a_fault(tmp_path):
    def edit(bench, root):
        bench["workloads"].append(dict(bench["workloads"][0], name="another-cell",
                                       traffic="ragprompt-sat-16b"))
        shutil.copy(root / "benchmarks/traffic/ragprompt-sat-16.json",
                    root / "benchmarks/traffic/ragprompt-sat-16b.json")

    bench, root = copy_with(tmp_path, edit)
    assert spec.validate(bench, root) == []
    assert spread.faults(bench, root) == ["cell another-cell: no evidence file another-cell.json"]


def test_evidence_of_too_few_runs_is_a_fault(tmp_path):
    def edit(bench, root):
        path = spread.evidence_file(CELLS[0], root)
        ev = json.loads(path.read_text())
        ev["sets"] = ev["sets"][:1]
        path.write_text(json.dumps(ev))

    bench, root = copy_with(tmp_path, edit)
    assert spread.faults(bench, root) == [
        f"cell {CELLS[0]}: the evidence holds fewer than 12 untraced runs in sets of 6"]
