"""The tick-anatomy metrics (PR 24): each new `layer_metrics/*.json` reads
its value from a recorded exposition through `readers.read`, the extended
BENCHMARK.json keeps the contract's rules, a program without the families
(the parent commit) gives nothing and does not raise, and a traced
rehearsal prints every one of them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import prom, readers, spec

DATA = spec.BENCH_DIR / "harness" / "testdata"
CELL = "qwen3moe-ragprompt-sat"

# metric -> (value over the recorded window, source, layer, moves)
WANT = {
    "sched_queue_wait_mean_ms": (24000 / 80, "program_span", "scheduler", "ttft_p50_ms"),
    "prefill_wall_mean_ms": (200000 / 80, "program_span", "scheduler", "ttft_p50_ms"),
    "prefill_ticks_mean": (360 / 80, "program_span", "scheduler", "ttft_p50_ms"),
    "decode_deliver_wait_mean_ms": (120000 / 600, "program_span", "scheduler", "output_tokens_per_s"),
    "decode_prepare_mean_ms": (120 / 120, "program_span", "engine programs", "output_tokens_per_s"),
    "decode_readback_wait_mean_ms": (36000 / 60, "program_span", "engine programs", "output_tokens_per_s"),
    "prefill_adopt_mean_ms": (4800 / 80, "program_span", "engine programs", "ttft_p50_ms"),
    "decode_slot_steps_in_window": (7680, "program_counter", "engine programs", "output_tokens_per_s"),
    "decode_lane_steps_in_window": (1800, "program_counter", "engine programs", "output_tokens_per_s"),
    "decode_tokens_delivered_in_window": (360 + 177, "program_counter", "engine programs", "output_tokens_per_s"),
}


def evidence(scrapes):
    return readers.Evidence(client={}, scrapes=scrapes, trace=None, memory={})


def recorded():
    return [prom.parse((DATA / f"metrics_tick_{w}.txt").read_text()) for w in ("before", "after")]


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_new_metric_reads_its_value_from_the_recorded_exposition(metric):
    reader = spec.load_json(spec.layer_metric_file(metric))
    assert reader["reader"] == "prom_delta" and reader["what"]
    assert readers.read(reader, evidence(recorded())) == pytest.approx(WANT[metric][0])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_program_without_the_family_gives_nothing_and_does_not_raise(metric):
    """The parent commit has none of these families: the driver lays this
    PR's benchmark files over its checkout too, and the line leaves the
    metric out."""
    older = [prom.parse((DATA / f"metrics_{w}.txt").read_text()) for w in ("before", "after")]
    reader = spec.load_json(spec.layer_metric_file(metric))
    assert readers.read(reader, evidence(older)) is None


def test_the_extended_benchmark_keeps_the_contracts_rules():
    bench = spec.load_benchmark()
    assert spec.validate(bench) == []
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (_, source, layer, moves) in WANT.items():
        m = entries[name]
        assert (m["source"], m["layer"], m["moves"]) == (source, layer, moves)
        assert CELL in m["workloads"] and m["unit"]  # other cells have joined since
    # asked for by name: where an entry stands in the list is no one's business
    cell = spec.resolve_cell(CELL)
    assert set(WANT) <= {m["name"] for m in cell.per_layer}


def test_delivered_tokens_sum_over_every_source_and_lanes_fit_in_slots():
    before, after = recorded()
    by_source = {
        s: prom.delta(after, before, "dnet_decode_tokens_total", {"source": s})
        for s in ("dispatch", "buffer", "spec")
    }
    assert sum(by_source.values()) == WANT["decode_tokens_delivered_in_window"][0]
    assert WANT["decode_lane_steps_in_window"][0] <= WANT["decode_slot_steps_in_window"][0]


def test_a_traced_rehearsal_prints_every_new_metric():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed",
         str(2**31 + 24), "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert all(k.startswith("rehearsal.") for k in metrics)
    for name in WANT:
        assert metrics[f"rehearsal.{name}"]["value"] > 0, name
    # what the counts must satisfy on any timeline
    steps = {k: metrics[f"rehearsal.decode_{k}_steps_in_window"]["value"] for k in ("slot", "lane")}
    assert steps["lane"] <= steps["slot"]
    assert metrics["rehearsal.decode_tokens_delivered_in_window"]["value"] <= steps["lane"]
