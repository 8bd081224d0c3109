"""The check's lower readings for the MiniCPM-SALA configuration: `python -m
benchmarks.precision_control_minicpm_sala --workload minicpmsala-deepdoc-sat
--seed <n> --prompts <k>`, from the root of a checkout.
`precision_control_brumby.py`'s method and plumbing (the harness's own
comparison, unedited, on the run's own weights and the real server), with
the three readings that lie below what the configuration serves:

- `int8`: the reference reads the checkpoint's matrices rounded to int8 a
  row and back (`precision_control.int8_rows`, 16384 rows at a time);
- `bf16_state`: the reference keeps each lightning layer's state `S` as a
  recurrence and rounds it to bfloat16 after every token (the configuration
  states float32), everything else float32
  (`reference/minicpm_sala.py: lightning_recurrent`);
- `nearest_blocks`: the reference's sparse layers take, past `dense_len`,
  the 31 NEAREST blocks before the window instead of the 31 best-scoring:
  a selection that ignores the index.

One JSON line a prompt, then a last line with the extremes and `separates`:
every sound comparison ok and every lower one NOT ok.  Exit code 0 only
then.  `--rehearse` runs it on the CPU at the config's tiny sizes, where the
limits are loose and `separates` is not expected: it proves the script.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import functools
import json
import os
import sys

from benchmarks import precision_control_brumby as base
from benchmarks import run as bench_run


def _logits_with(**control):
    @contextlib.contextmanager
    def patched(ref):
        """While open, the reference module `ref` computes with `control`."""
        plain = ref.logits
        ref.logits = functools.partial(plain, **control)
        try:
            yield
        finally:
            ref.logits = plain

    return patched


CONTROLS = {
    "int8": base.int8_reference,
    "bf16_state": _logits_with(round_state="bfloat16"),
    "nearest_blocks": _logits_with(nearest=True),
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.precision_control_minicpm_sala")
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
