"""One cell end to end on the CPU (`--rehearse`: tiny sizes, interpreted
kernels), and the last line's contract."""

import json
import os
import subprocess
import sys

from benchmarks.harness.spec import ROOT

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_rehearsal_runs_a_cell_and_prints_the_contracts_last_line():
    p = run("--workload", "qwen3moe-ragprompt-sat", "--seed", str(2**31 + 11),
            "--seconds", "4", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == RESULT_KEYS  # nothing else on the last line
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    # labelled: no CPU number under a device metric's name
    assert all(k.startswith("rehearsal.") for k in result["metrics"])
    assert {"rehearsal.output_tokens_per_s", "rehearsal.setup_s"} <= set(result["metrics"])
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier
    assert "gaps" in earlier and "first tokens" in earlier  # the reading counts


def test_without_an_accelerator_there_is_no_result():
    p = run("--workload", "qwen3moe-ragprompt-sat", "--seed", "1", "--seconds", "1", "--trace", "0",
            timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
