"""`cmdaplus-mixedlen-sat` end to end on the CPU (`--rehearse`: tiny sizes,
interpreted kernels, a 24-token window under 30-120-token prompts): the
server loads a cohere2_moe checkpoint, pages its two kinds of layer apart,
passes the check and serves the window.  About a minute."""

import json
import os
import subprocess
import sys

from benchmarks.harness.spec import ROOT


def test_rehearsal_of_the_mixed_length_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", "cmdaplus-mixedlen-sat",
         "--seed", str(2**31 + 28), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in m)  # no CPU number under a device name
    for name in ("kv_full_blocks_used_peak_pct", "kv_window_blocks_used_peak_pct",
                 "moe_assignments_held_in_window", "moe_assignments_routed_in_window",
                 "decode_tokens_delivered_in_window", "prefill_ticks_mean",
                 # the host cost of the window tables: released and grown inside
                 # dnet.decode.prepare, committed by kind inside dnet.prefill.adopt
                 "decode_prepare_mean_ms", "prefill_adopt_mean_ms",
                 "sched_batch_tokens_mean", "decode_lane_steps_in_window"):
        assert m[f"rehearsal.{name}"] > 0, name
    for name in ("sched_queue_wait_mean_ms", "admit_wait_mean_ms",
                 "decode_deliver_wait_mean_ms"):
        assert f"rehearsal.{name}" in m, name
    assert "rehearsal.kv_window_blocks_released_in_window" in m
    held = m["rehearsal.moe_assignments_held_in_window"]
    assert held < m["rehearsal.moe_assignments_routed_in_window"]  # 4 of 8 experts held
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier and "-> ok" in earlier
    assert '"paged_attend"' in earlier and '"flash_prefill"' in earlier


def test_rehearsal_of_the_precision_control():
    """`benchmarks/precision_control.py` puts the int8 reading through the
    harness's own comparison: the same served answers against the reference
    over int8-rounded matrices err more than against the plain one.  (At the
    tiny size the limits are loose, so `separates` is false and the exit code
    1: the chip run is what has to separate, PERF.md.)"""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.precision_control", "--workload",
         "cmdaplus-mixedlen-sat", "--seed", str(2**31 + 29), "--prompts", "2", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == (0 if out["separates"] else 1), p.stderr[-2000:]
    assert out["prompts"] == 2 and out["workload"] == "cmdaplus-mixedlen-sat"
    assert out["int8_mean_err_min"] > 2 * out["bf16_mean_err_max"] > 0
    assert out["int8_max_err_min"] > out["bf16_max_err_max"]
