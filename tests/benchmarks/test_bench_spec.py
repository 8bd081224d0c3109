"""BENCHMARK.json against the contract's rules, and the promise that a later
PR adds a configuration, a mix, a cell with its evidence and a metric as files
plus one entry."""

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
        assert not key.endswith(("_dim", "_rank", "_size"))
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
    that was there, and the harness finds and reads them."""
    root = tmp_path / "copy"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks")
    bench = json.loads(json.dumps(BENCH))

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
        "name": "qwen3moe-chat-greedy", "config": "qwen3-30b-a3b-4l",
        "traffic": "chat-greedy-8", "chips": 1, "why": "a test"})
    for name, src in (("preemptions_in_window", "program_counter"),
                      ("fusion_time_pct", "device_trace")):
        bench["per_layer"].append({
            "name": name, "unit": "%" if "pct" in name else "count", "better": "lower",
            "source": src, "layer": "scheduler", "moves": "ttft_p50_ms",
            "workloads": ["qwen3moe-chat-greedy"]})
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
    cell = spec.resolve_cell("qwen3moe-chat-greedy", root)
    assert cell.config["num_hidden_layers"] == 4 and cell.traffic["clients"] == 8
    assert {m["name"] for m in cell.per_layer} >= {"preemptions_in_window", "fusion_time_pct"}
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
