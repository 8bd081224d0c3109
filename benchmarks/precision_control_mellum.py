"""The check's lower readings for the Mellum2 configuration: `python -m
benchmarks.precision_control_mellum --workload mellum2-repoctx-sat --seed <n>
--prompts <k>`, from the root of a checkout.
`precision_control_brumby.py`'s method and plumbing (the harness's own
comparison, unedited, on the run's own weights and the real server), with
the three readings that lie below what the configuration serves:

- `int8`: the reference reads the checkpoint's matrices rounded to int8 a
  row and back (`precision_control.int8_rows`, 16384 rows at a time);
- `full_by_sliding_table`: the reference rotates the FULL layers by the
  window kind's table (the default frequencies, no attention factor): what a
  program that read `rope_parameters` as one group would serve;
- `window_attends_everything`: the reference's window layers attend every
  key before the query: a program that lost the window.

One JSON line a prompt, then a last line with the extremes and `separates`:
every sound comparison ok and every lower one NOT ok.  Exit code 0 only
then.  Where seeded N(0, 0.02) weights make the softmax so flat that the
chip's check cannot see the second or the third (the configuration's
`check.reason` says which it saw), tests/test_mellum_parity.py carries them
in float32 with a peaked softmax.  `--rehearse` runs it on the CPU at the
config's tiny sizes, where the limits are loose and `separates` is not
expected: it proves the script.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

from benchmarks import precision_control_brumby as base
from benchmarks import run as bench_run
from benchmarks.precision_control_minicpm_sala import _logits_with

CONTROLS = {
    "int8": base.int8_reference,
    "full_by_sliding_table": _logits_with(full_table="sliding"),
    "window_attends_everything": _logits_with(window=0),
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.precision_control_mellum")
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
    base.CONTROLS = CONTROLS  # the same orchestration, this model's three controls
    out = asyncio.run(base.control(args, cell))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    # the chip's runtime can hang in teardown; everything is already stopped
    os._exit(0 if out["separates"] else 1)


if __name__ == "__main__":
    main()
