"""The check's lower readings for the Qwen3-Next configuration: `python -m
benchmarks.precision_control_qwen3_next --workload qwen3next-longdoc-sat
--seed <n> --prompts <k>`, from the root of a checkout.
`precision_control_brumby.py`'s method and plumbing (the harness's own
comparison, unedited, on the run's own weights and the real server), with
the two precisions that lie below what the configuration serves:

- `int8`: the reference reads the checkpoint's matrices rounded to int8 a
  row and back (`precision_control.int8_rows`, 16384 rows at a time; the
  expert stacks [E, out, in] a row of each expert at a time);
- `bf16_state`: the reference rounds each delta-rule layer's state `S` to
  bfloat16 after every token (the configuration states float32),
  everything else float32 (`reference/qwen3_next.py: delta_rule`'s
  `round_state`).

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
from benchmarks.precision_control import int8_rows


def int8_any(a):
    """Per-row int8 for a matrix, an expert stack or a conv's [C, 1, K];
    vectors (norms, A_log, dt_bias) stay as they are."""
    import numpy as np

    if a.ndim == 2:
        return base.int8_rows_blocked(a)
    if a.ndim == 3:
        return np.stack([int8_rows(a[e]) for e in range(a.shape[0])])
    return a


@contextlib.contextmanager
def int8_reference(ref):
    """While open, the reference module `ref` reads int8-rounded matrices."""
    plain = ref.Tensors

    class Int8Tensors(plain):
        def get(self, name):
            return int8_any(super().get(name))

        def layer(self, i):
            return {k: int8_any(v) for k, v in super().layer(i).items()}

    ref.Tensors = Int8Tensors
    try:
        yield
    finally:
        ref.Tensors = plain


@contextlib.contextmanager
def bf16_state_reference(ref):
    """While open, the reference module `ref` keeps a bfloat16 state."""
    import jax.numpy as jnp

    plain = ref.logits
    ref.logits = functools.partial(plain, round_state=jnp.bfloat16)
    try:
        yield
    finally:
        ref.logits = plain


CONTROLS = {"int8": int8_reference, "bf16_state": bf16_state_reference}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.precision_control_qwen3_next")
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
    base.CONTROLS = CONTROLS  # the same orchestration, this model's two controls
    out = asyncio.run(base.control(args, cell))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    # the chip's runtime can hang in teardown; everything is already stopped
    os._exit(0 if out["separates"] else 1)


if __name__ == "__main__":
    main()
