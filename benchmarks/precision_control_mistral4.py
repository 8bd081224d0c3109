"""The check's lower reading for the Mistral-Small-4 configuration: `python
-m benchmarks.precision_control_mistral4 --workload mistral4-longctx-sat
--seed <n> --prompts <k>`, from the root of a checkout.
`precision_control_brumby.py`'s method and plumbing (the harness's own
comparison, unedited, on the run's own weights and the real server), with
the precision that lies below what the configuration serves:

- `int8`: the reference reads the checkpoint's matrices rounded to int8 a
  row and back (`precision_control.int8_rows`, 16384 rows at a time; the
  expert stacks [E, out, in] a row of each expert at a time); vectors (the
  norms) stay as they are.

One JSON line a prompt, then a last line with the extremes and `separates`:
every sound comparison ok and every int8 one NOT ok.  Exit code 0 only
then.  `--rehearse` runs it on the CPU at the config's tiny sizes, where the
limits are loose and `separates` is not expected: it proves the script.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

from benchmarks import precision_control_brumby as base
from benchmarks import run as bench_run
from benchmarks.precision_control_qwen3_next import int8_reference

CONTROLS = {"int8": int8_reference}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.precision_control_mistral4")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompts", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    from benchmarks.harness import spec

    cell = spec.resolve_cell(args.workload)
    bench_run.prepare_environment(cell, args.rehearse)
    jax = bench_run.configure_jax()
    bench_run.require_devices(jax, cell, args.rehearse)
    base.CONTROLS = CONTROLS  # the same orchestration, this model's control
    out = asyncio.run(base.control(args, cell))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    # the chip's runtime can hang in teardown; everything is already stopped
    os._exit(0 if out["separates"] else 1)


if __name__ == "__main__":
    main()
