#!/usr/bin/env python3
"""The causal prefill kernel alone, parent against change, at the cells'
geometries: `_flash_pallas` of this tree against the parent commit's
(`--parent`: a path to its `dnet_tpu/ops/flash_attention.py`; by default
`git show HEAD~:` of it, or HEAD's where the change is not committed yet).
Run it on the chip (`chiprun -- python3 scripts/flash_prefill_parity.py
--parent .chip_tree/parent/dnet_tpu/ops/flash_attention.py`: the copy there
holds no `.git`); `--interpret` drives the same script here on the CPU at
small shapes through the interpreted kernels (no times).

Parity: bf16 queries, keys and values at the lat, doc, rag and both mix
geometries (the staged row's full length, three chunk positions each: a
first chunk, a middle one, the one that ends the row), random where a row
attends, NaN from the first kv tile wholly past the chunk on and, for a
window, in the tiles wholly behind it.  The two outputs must be EQUAL BIT
FOR BIT: the change copies fewer tiles and folds the same ones in the same
order.

Times (the chip only): the median over `--iters` programs of `CALLS` calls
chained on the device, as ms a call; the operations and the bytes the
algorithm needs by benchmarks/kernel_costs.py (`prefill_pairs`,
`prefill_bytes` with a head of (Hd + Vd) / 2, which counts k and v rows of
different widths exactly), their share of the v5e's peaks by
`roofline_share`; the grid's steps before and after, and what a step that
went had cost.

One measurement for the next question, in a toy kernel of this script's
own: a [128, 128] x [128, 128] Mosaic dot with float32 operands at default
precision, at `highest`, and with bf16 operands, timed (eight independent
products a loop turn, operands resident in VMEM) and held to float64: is
the fold's float32 dot one MXU pass or six today.

Last stdout line: one JSON object."""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

KERNEL = "dnet_tpu/ops/flash_attention.py"
#: published peaks of one TPU v5e chip (on-chip-measurement guide, section 4)
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
CALLS = 6

#: name -> (T, S, H, KVH, Hd, Vd, window, positions)
CELLS = {
    "lat": (2048, 33792, 32, 32, 192, 128, 0, (0, 9216, 31744)),
    "doc": (2048, 33280, 16, 2, 256, 256, 0, (0, 9216, 31232)),
    "rag": (2048, 4096, 32, 4, 128, 128, 0, (0, 1024, 2048)),
    "mix_full": (256, 16512, 128, 8, 128, 128, 0, (0, 5632, 16256)),
    "mix_window": (256, 16512, 128, 8, 128, 128, 4096, (0, 5632, 16256)),
}
SMALL = {
    "lat": (256, 1024, 4, 4, 24, 16, 0, (0, 300, 768)),
    "doc": (256, 1024, 8, 2, 32, 32, 0, (0, 300, 768)),
    "mix_window": (128, 1024, 8, 2, 16, 16, 256, (0, 300, 896)),
}


def parent_module(path: str | None):
    """The parent commit's kernel module, loaded beside this tree's."""
    if path is None:
        for rev in ("HEAD", "HEAD~"):  # HEAD where the change is not committed yet
            src = subprocess.run(
                ["git", "show", f"{rev}:{KERNEL}"], cwd=REPO, capture_output=True, text=True
            )
            if src.returncode == 0 and "_live_tiles" not in src.stdout:
                break
        else:
            raise SystemExit("no parent kernel: pass --parent <path to its flash_attention.py>")
        out = REPO / "chiprun_out" / "flash_prefill_parity"
        out.mkdir(parents=True, exist_ok=True)
        path = str(out / "parent_flash_attention.py")
        Path(path).write_text(src.stdout)
    spec = importlib.util.spec_from_file_location("parent_flash_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R, J, STEPS = 32, 8, 256  # the toy: products a loop turn x turns x grid steps


def toy_dots(dtype, precision):
    """-> (the timed program: STEPS x R x J products [128, 128] x [128, 128]
    with both operands resident in VMEM, one product alone for its error)."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax import lax

    def dot(a, b):
        return lax.dot_general(
            a, b, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )

    def many(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        def turn(i, carry):
            b = b_ref[i]
            for j in range(J):
                o_ref[j] += dot(a_ref[j], b)
            return carry

        lax.fori_loop(0, R, turn, 0)

    def one(a_ref, b_ref, o_ref):
        o_ref[...] = dot(a_ref[...], b_ref[...])

    whole = lambda s: (0, 0, 0)  # noqa: E731
    timed = jax.jit(
        lambda a, b: pl.pallas_call(
            many, grid=(STEPS,),
            in_specs=[pl.BlockSpec((J, 128, 128), whole), pl.BlockSpec((R, 128, 128), whole)],
            out_specs=pl.BlockSpec((J, 128, 128), whole),
            out_shape=jax.ShapeDtypeStruct((J, 128, 128), jnp.float32), name="toy_dot",
        )(a.astype(dtype), b.astype(dtype))
    )
    alone = jax.jit(
        lambda a, b: pl.pallas_call(
            one, out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32), name="toy_dot_one",
        )(a.astype(dtype), b.astype(dtype))
    )
    return timed, alone


def dot_passes(iters: int) -> dict:
    """[128, 128] x [128, 128] in a Mosaic kernel, three ways."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.default_rng(42)
    a64 = rng.normal(size=(J, 128, 128))
    b64 = rng.normal(size=(R, 128, 128))
    want = a64[0] @ b64[0]
    a32, b32 = jnp.asarray(a64, jnp.float32), jnp.asarray(b64, jnp.float32)
    out = {}
    for name, dtype, precision in (
        ("f32_default", jnp.float32, None),
        ("f32_highest", jnp.float32, lax.Precision.HIGHEST),
        ("bf16_operands", jnp.bfloat16, None),
    ):
        timed, alone = toy_dots(dtype, precision)
        got = np.asarray(alone(a32[0], b32[0]), np.float64)
        times = []
        for _ in range(iters + 3):
            t0 = time.perf_counter()
            timed(a32, b32).block_until_ready()
            times.append(time.perf_counter() - t0)
        t = statistics.median(times[3:])
        dots = STEPS * R * J
        out[name] = {
            "max_err_vs_float64": float(np.max(np.abs(got - want))),
            "output_size": float(np.max(np.abs(want))),
            "ns_a_dot": t / dots * 1e9,
            "tflop_per_s": dots * 2 * 128**3 / t / 1e12,
        }
    base = out["bf16_operands"]["ns_a_dot"]
    for v in out.values():
        v["time_over_bf16"] = v["ns_a_dot"] / base
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--interpret", action="store_true")
    p.add_argument("--parent", default=None)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--cells", default="")
    args = p.parse_args()

    import os

    if args.interpret:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DNET_FLASH_INTERPRET"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import kernel_costs as costs
    from dnet_tpu.ops import flash_attention as change

    parent = parent_module(args.parent)
    dev = jax.devices()[0]
    if not args.interpret and dev.platform != "tpu":
        print("no TPU: run through chiprun, or pass --interpret", file=sys.stderr)
        return 3
    cells = SMALL if args.interpret else CELLS
    if args.cells:
        cells = {k: cells[k] for k in args.cells.split(",")}
    dtype = jnp.float32 if args.interpret else jnp.bfloat16
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "impl": "interpret" if args.interpret else "pallas", "cells": {},
    }
    ok = True
    for name, (T, S, H, KVH, Hd, Vd, window, positions) in cells.items():
        bq, bk = change._pick_tile(T, 128), change._pick_tile(S, 128)
        kw = dict(G=H // KVH, scale=Hd**-0.5, bq=bq, bk=bk, interpret=args.interpret, window=window)
        key = jax.random.split(jax.random.key(42), 3)
        # heads MERGED, as the models hand them over (a [.., heads, dim]
        # operand would be relaid out whole before every call)
        q = jax.random.normal(key[0], (1, T, H * Hd), jnp.float32).astype(dtype)
        k0 = jax.random.normal(key[1], (1, S, KVH * Hd), jnp.float32).astype(dtype)
        v0 = jax.random.normal(key[2], (1, S, KVH * Vd), jnp.float32).astype(dtype)
        sinks = jnp.full((H,), change.NEG_INF, jnp.float32)

        def call(mod, q, k, v, pos):
            return mod._flash_pallas(
                q.reshape(1, T, H, Hd), k.reshape(1, S, KVH, Hd), v.reshape(1, S, KVH, Vd),
                pos, sinks, **kw,
            )

        def chained(mod):
            def calls(q, k, v, pos):
                def body(q, _):
                    o = call(mod, q, k, v, pos)
                    # chain the calls: the next query depends on this output
                    more = jnp.pad(o, ((0, 0),) * 3 + ((0, Hd - Vd),)).reshape(q.shape)
                    return q + more * 1e-3, o[0, 0, 0, 0]

                return jax.lax.scan(body, q, None, length=CALLS)

            return jax.jit(calls)

        once = {
            side: jax.jit(functools.partial(call, mod))
            for side, mod in (("parent", parent), ("change", change))
        }
        many = {"parent": chained(parent), "change": chained(change)}
        for pos in positions:
            rows = jnp.arange(S)
            first = max(pos - window + 1, 0) if window else 0
            # NaN from the first tile wholly past the chunk on, and in the
            # tiles wholly behind the first row's window
            dead = (rows // bk > (pos + T - 1) // bk) | (rows // bk < first // bk)
            k = jnp.where(dead[None, :, None], jnp.nan, k0).astype(dtype)
            v = jnp.where(dead[None, :, None], jnp.nan, v0).astype(dtype)
            pos_arr = jnp.asarray([pos], jnp.int32)
            got = {s: np.asarray(f(q, k, v, pos_arr).astype(jnp.float32)) for s, f in once.items()}
            equal = bool(np.array_equal(got["parent"], got["change"]))
            finite = bool(np.isfinite(got["change"]).all())
            folded, skipped = change.flash_tiles(pos, T, S, window)
            groups = KVH // change._heads_per_step(KVH, H // KVH, Hd, Vd)
            line = {
                "bit_equal": equal, "finite": finite,
                "steps_parent": groups * (T // bq) * (S // bk),
                "steps_change": groups * (T // bq) * int(
                    change._kv_steps(pos, T, bq=bq, bk=bk, n_s=S // bk, window=window, xp=np)
                ),
                "tiles_folded": groups * folded, "tiles_skipped": groups * skipped,
            }
            ok = ok and equal and finite
            if not args.interpret:
                head = (Hd + Vd) // 2
                ops = costs.attention_ops(costs.prefill_pairs(pos, T, window), H, head)
                nbytes = costs.prefill_bytes(pos, T, H, KVH, head, window=window, q_tile=bq)
                line.update(flops=ops, bytes=nbytes)
                for side, fn in many.items():
                    times = []
                    for _ in range(args.iters + 3):
                        t0 = time.perf_counter()
                        _, o = fn(q, k, v, pos_arr)
                        o.block_until_ready()
                        times.append((time.perf_counter() - t0) / CALLS)
                    t = statistics.median(times[3:])
                    share = costs.roofline_share(ops, nbytes, t, PEAK_FLOPS, PEAK_BYTES)
                    line[side] = {
                        "ms_a_call": t * 1e3,
                        "memory_peak_share": nbytes / PEAK_BYTES / t,
                        "compute_peak_share": ops / PEAK_FLOPS / t,
                        "roofline_share": share["share"], "bound": share["bound"],
                    }
                gone = line["steps_parent"] - groups * folded
                saved = line["parent"]["ms_a_call"] - line["change"]["ms_a_call"]
                line["us_a_step_that_went"] = saved * 1e3 / gone if gone else None
                line["us_a_live_step"] = line["change"]["ms_a_call"] * 1e3 / (groups * folded)
                line["change_over_parent"] = line["change"]["ms_a_call"] / line["parent"]["ms_a_call"]
            out["cells"].setdefault(name, {})[str(pos)] = line
            print(json.dumps({name: {str(pos): line}}), file=sys.stderr, flush=True)
    out["ok"] = ok
    if not args.interpret:
        out["dot_passes"] = dot_passes(args.iters)
    dest = REPO / "chiprun_out" / "flash_prefill_parity"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / ("interpret.json" if args.interpret else "chip.json")).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
