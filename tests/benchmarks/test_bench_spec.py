"""BENCHMARK.json against the contract's rules, and the promise that a later
PR adds a configuration, a mix, a cell with its evidence and a metric as files
plus one entry; a cell JOINS the entries whose readers read in it (its name
appended to their `workloads`, nothing else of them changed) and brings
entries only for readings no entry has."""

import json
import shutil

import pytest

from benchmarks.harness import readers, spec, spread

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_keeps_the_contracts_rules():
    assert spec.validate(BENCH) == []


@pytest.mark.parametrize("cell", CELLS)
def test_every_cells_files_resolve_by_name(cell):
    c = spec.resolve_cell(cell)
    assert c.config["model_type"] and c.config["serve"] and c.config["check"]
    assert c.traffic["clients"] >= 1 and c.traffic["prompt_tokens"]["dist"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in c.per_layer:
        reader = spec.load_json(spec.layer_metric_file(m["name"]))
        assert reader["reader"] in readers.READERS
        assert m["moves"] in names  # a cell reports what its layer metric moves


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCH["configs"]])
def test_a_reduced_key_is_never_a_width(cfg):
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg)
    for key in entry["reduced"]:
        # `vocab_size` counts the rows one chip of several holds, not a width
        assert key == "vocab_size" or not key.endswith(("_dim", "_rank", "_size"))
        assert key not in ("num_experts_per_tok", "head_dim")


@pytest.mark.parametrize(
    "bad",
    [
        {"name": "has space"}, {"name": "slash/name"}, {"unit": "tokens per second"},
        {"unit": "µs"}, {"better": "faster"}, {"source": "guess"}, {"bound": 0.2},
    ],
)
def test_validate_refuses_what_the_contract_refuses(bad):
    bench = json.loads(json.dumps(BENCH))
    bench["end_to_end"][0].update(bad)
    assert spec.validate(bench) != []


def test_a_later_pr_adds_config_mix_cell_and_metrics_as_files(tmp_path):
    """In a temporary copy: new files and one entry each, no edit to a file
    that was there, and the harness finds and reads them.  The new cell
    joins every entry the rag cell's readers read in (the same program over
    the same model family) by appending its name, and adds two entries for
    readings no entry has."""
    root = tmp_path / "copy"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks")
    bench = json.loads(json.dumps(BENCH))
    new = "qwen3moe-chat-greedy"

    cfg = spec.load_json(spec.BENCH_DIR / "configs" / "qwen3-30b-a3b-6l.json")
    cfg["num_hidden_layers"] = 4
    (root / "benchmarks/configs/qwen3-30b-a3b-4l.json").write_text(json.dumps(cfg))
    (root / "benchmarks/traffic/chat-greedy-8.json").write_text(json.dumps({
        "clients": 8, "requests_per_client": 10, "block": 5,
        "prompt_tokens": {"dist": "loguniform", "min": 128, "max": 1024},
        "answer_tokens": {"dist": "uniform", "min": 32, "max": 96},
        "sampling": {"temperature": 0.0}, "residual_life_start": True}))
    (root / "benchmarks/layer_metrics/preemptions_in_window.json").write_text(json.dumps(
        {"reader": "prom_delta", "family": "dnet_sched_preemptions_total", "stat": "sum"}))
    (root / "benchmarks/layer_metrics/fusion_time_pct.json").write_text(json.dumps(
        {"reader": "trace_share", "pattern": "^%?fusion", "of": "busy"}))
    bench["configs"].append({
        "name": "qwen3-30b-a3b-4l", "source": "https://example.org/config.json",
        "file": "benchmarks/configs/qwen3-30b-a3b-4l.json",
        "reduced": ["num_hidden_layers"], "why": "a test"})
    bench["workloads"].append({
        "name": new, "config": "qwen3-30b-a3b-4l",
        "traffic": "chat-greedy-8", "chips": 1, "why": "a test"})
    joined = [m["name"] for m in bench["per_layer"] if "qwen3moe-ragprompt-sat" in m.get("workloads", ())]
    assert len(joined) >= 30  # the scheduler's, the engine's, the pool's, the experts' readings
    for m in bench["per_layer"]:
        if m["name"] in joined:
            m["workloads"].append(new)  # the whole edit of an entry that was there
    for name, src in (("preemptions_in_window", "program_counter"),
                      ("fusion_time_pct", "device_trace")):
        bench["per_layer"].append({
            "name": name, "unit": "%" if "pct" in name else "count", "better": "lower",
            "source": src, "layer": "scheduler", "moves": "ttft_p50_ms",
            "workloads": [new]})
    # an entry that was there differs by the appended name alone; none went
    was = {m["name"]: m for m in BENCH["per_layer"]}
    now = {m["name"]: m for m in bench["per_layer"]}
    assert list(now)[:len(was)] == list(was) and len(now) == len(was) + 2
    for name, m in was.items():
        assert {k: v for k, v in now[name].items() if k != "workloads"} == {
            k: v for k, v in m.items() if k != "workloads"}
        assert now[name].get("workloads") == (
            m["workloads"] + [new] if name in joined else m.get("workloads"))
    # the cell's evidence, a data file too: two sets of six runs, steady enough
    # for the bounds the metrics already have
    runs = [{"seed": 2**31 + 6 * k + i, "trace": 0, "compiled": i == 0,
             "metrics": {"output_tokens_per_s": 400 + (i + k) % 3, "ttft_p50_ms": 250 + i % 2,
                         "setup_s": 600 if i == 0 else 120 + i}}
            for k in range(2) for i in range(6)]
    (root / "benchmarks/evidence/qwen3moe-chat-greedy.json").write_text(json.dumps({
        "cell": "qwen3moe-chat-greedy",
        "sets": [{"name": n, "runs": runs[6 * k:6 * k + 6]} for k, n in enumerate("AB")]}))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert spec.validate(bench, root) == []
    assert spread.faults(bench, root) == []
    cell = spec.resolve_cell(new, root)
    assert cell.config["num_hidden_layers"] == 4 and cell.traffic["clients"] == 8
    # what it reads: the entries with no list, the ones it joined, its own two
    reads = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    reads |= {*joined, "preemptions_in_window", "fusion_time_pct"}
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in bench["per_layer"] if m["name"] in reads]
    ev = readers.Evidence(
        client={}, memory={},
        scrapes=[{"dnet_sched_preemptions_total": 2.0}, {"dnet_sched_preemptions_total": 5.0}],
        trace={"devices": {"/device:TPU:0": [["%fusion.1 = f32[8]", 0, 30], ["%sort.2 = s32[8]", 40, 10]]},
               "host": []},
    )
    got = {
        m["name"]: readers.read(
            spec.load_json(spec.layer_metric_file(m["name"], root / "benchmarks")), ev)
        for m in cell.per_layer if m["name"] in ("preemptions_in_window", "fusion_time_pct")
    }
    assert got == {"preemptions_in_window": 3.0, "fusion_time_pct": pytest.approx(75.0)}
    # the traffic mix needs no new code either
    from benchmarks.harness import traffic

    plans = traffic.plan(cell.traffic, 1, 1000)
    assert len(plans) == 8 and all(len(mine) == 10 for mine in plans)


# ---- one entry a reading ----------------------------------------------------


def test_a_cell_that_copies_a_reading_under_its_own_name_is_refused(tmp_path):
    """What every cell did until PR 43: `itl_p50_ms.chat` with a copy of
    `itl_p50_ms`'s reader file.  `validate` names the entry to join (that
    the list as it stands has no such pair, and no reader file without an
    entry, is `validate(BENCH) == []` above)."""
    root = tmp_path / "copy"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks")
    bench = json.loads(json.dumps(BENCH))
    twin = dict(next(m for m in bench["per_layer"] if m["name"] == "itl_p50_ms"),
                name="itl_p50_ms.chat", workloads=[CELLS[0]])
    reader = spec.load_json(spec.layer_metric_file("itl_p50_ms"))
    (root / "benchmarks/layer_metrics/itl_p50_ms.chat.json").write_text(
        json.dumps(dict(reader, what="the same, said again")))
    bench["per_layer"].append(twin)
    assert spec.validate(bench, root) == [
        "per_layer itl_p50_ms.chat: the reading itl_p50_ms already has; "
        "a cell joins that entry's workloads"]
    # the same reading with the OTHER arrow is an entry of its own (sort_time_pct.ttft / .tokens)
    twin["moves"] = "ttft_p50_ms"
    assert spec.validate(bench, root) == []


def test_a_reader_file_without_an_entry_is_a_fault(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks")
    (root / "benchmarks/layer_metrics/left_behind.json").write_text('{"reader": "trace_idle"}')
    assert spec.validate(json.loads(json.dumps(BENCH)), root) == [
        "reader file left_behind.json: no per_layer entry"]
