"""Compile every Pallas kernel at one model's shapes and compare with jnp.

    python -m dnet_tpu.ops.kernel_check --model <dir> [--max-seq 4096]

The tier-1 tests run the kernels in interpret mode at toy shapes; this runs
them the way the process resolves them (`kernel_backend()`: Mosaic-compiled
on a TPU backend, interpret under DNET_FLASH_INTERPRET=1 on a CPU one) at
the head counts, head dim and cache length a real checkpoint serves with,
against the plain `attend` op in float32 at the highest matmul precision.
`chip_smoke.py` runs it as its *kernels* phase.  One JSON line per case,
then a summary line; the exit code is non-zero if any case is over
tolerance, if a kernel resolved to anything but the backend's
implementation, or if no Pallas kernel can run here at all.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.ops.attention import attend, causal_mask, sliding_window_mask
from dnet_tpu.ops.kernel_select import SELECTIONS, device_report, kernel_backend

# Tolerance on |got - want| / max(1, |want|) against the float32 reference.
# Inputs and outputs are bf16 (the serving dtype): rounding the output costs
# up to 2^-9 relative (2e-3), and each tile's p @ v runs on the MXU at
# default precision, which rounds the f32 probabilities to bf16 once more.
# 1e-2 leaves a few such roundings of headroom; a wrong mask, a missed tile
# or a bad rescale is off by 1e-1 or more.
TOLERANCE = 1e-2
# One-hot column gather/scatter selects values: nothing may change them.
TOLERANCE_SELECT = 0.0
# Column sum of squares over up to 1024 rows in f32, tile-by-tile versus in
# one pass: relative error from reassociation only.
TOLERANCE_NORMS_REL = 1e-5


def _reference(q, k, v, mask, sinks=None):
    with jax.default_matmul_precision("highest"):
        return attend(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), mask=mask, sinks=sinks,
        )


class Checker:
    def __init__(self, tolerance: float) -> None:
        self.tolerance = tolerance
        self.results: list = []

    def case(self, name: str, fn, want, tol=None, rel: bool = False) -> None:
        """Run `fn` (compile + execute), compare with `want`."""
        tol = self.tolerance if tol is None else tol
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(fn()), np.float32)
        dt = time.perf_counter() - t0
        want = np.asarray(want, np.float32)
        err = np.abs(got - want) / np.maximum(np.abs(want), 1e-30 if rel else 1.0)
        worst = float(np.max(err)) if np.all(np.isfinite(got)) else float("inf")
        row = {
            "case": name, "ok": bool(worst <= tol), "max_err": worst,
            "tolerance": tol, "seconds": round(dt, 2),
        }
        self.results.append(row)
        print(json.dumps(row), flush=True)


def _qkv(key, B, T, S, H, KVH, Hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (B, T, H, Hd), dtype),
        jax.random.normal(kk, (B, S, KVH, Hd), dtype),
        jax.random.normal(kv, (B, S, KVH, Hd), dtype),
    )


def check_serving_kernels(c: Checker, H, KVH, Hd, S, bt, dtype) -> None:
    """The three kernels on the serving paths, through their dispatchers."""
    from dnet_tpu.core.engine import bucket_length
    from dnet_tpu.ops.flash_attention import flash_attend_causal
    from dnet_tpu.ops.flash_decode import flash_decode_attend, flash_decode_eligible
    from dnet_tpu.ops.paged_attention import (
        _paged_emulate,
        paged_attend,
        paged_attend_impl,
    )

    key = jax.random.key(0)
    # flash prefill: every bucket a prompt can pad to, fresh (pos 0) ...
    T = bucket_length(1)
    while T <= S:
        q, k, v = _qkv(jax.random.fold_in(key, T), 1, T, S, H, KVH, Hd, dtype)
        c.case(
            f"flash_prefill T={T} pos=0",
            lambda: jax.jit(flash_attend_causal)(q, k, v, jnp.int32(0)),
            _reference(q, k, v, causal_mask(T, S, 0)),
        )
        T *= 2
    # ... and as a continuation chunk whose diagonal crosses kv tiles
    T = min(128, S // 4)
    pos = S // 2 + 3
    q, k, v = _qkv(jax.random.fold_in(key, 7), 1, T, S, H, KVH, Hd, dtype)
    c.case(
        f"flash_prefill T={T} pos={pos}",
        lambda: jax.jit(flash_attend_causal)(q, k, v, jnp.int32(pos)),
        _reference(q, k, v, causal_mask(T, S, pos)),
    )

    # ... and a window layer's chunk: tiles behind the window are skipped
    window = S // 4 + 5
    c.case(
        f"flash_prefill_window T={T} pos={pos} window={window}",
        lambda: jax.jit(
            lambda *a: flash_attend_causal(*a, window=window)
        )(q, k, v, jnp.int32(pos)),
        _reference(q, k, v, sliding_window_mask(T, S, pos, window)),
    )

    # flash decode: first slot, a tile edge either side, mid-tile, last slot
    q, k, v = _qkv(jax.random.fold_in(key, 11), 1, 1, S, H, KVH, Hd, dtype)
    if not flash_decode_eligible(q, k):
        raise RuntimeError(f"flash decode ineligible at q{q.shape} k{k.shape}")
    dec = jax.jit(flash_decode_attend)
    for pos in sorted({0, S // 16 - 1, S // 16, S // 2 + 5, S - 1}):
        c.case(
            f"flash_decode pos={pos}",
            lambda pos=pos: dec(q, k, v, jnp.int32(pos)),
            _reference(q, k, v, causal_mask(1, S, pos)),
        )

    # ragged paged attend: 8 slots of ragged lengths over a shuffled pool,
    # at a narrow and at the full-width page-table bucket
    slots = 8
    n_blocks = slots * S // bt
    kp, kv_, kn, kvn, kq, kperm = jax.random.split(jax.random.fold_in(key, 13), 6)
    k_pool = jax.random.normal(kp, (n_blocks, bt, KVH, Hd), dtype)
    v_pool = jax.random.normal(kv_, (n_blocks, bt, KVH, Hd), dtype)
    k_new = jax.random.normal(kn, (slots, KVH, Hd), dtype)
    v_new = jax.random.normal(kvn, (slots, KVH, Hd), dtype)
    q = jax.random.normal(kq, (slots, 1, H, Hd), dtype)
    perm = jax.random.permutation(kperm, n_blocks).astype(jnp.int32)
    impl = paged_attend_impl()
    for nb in sorted({max(S // bt // 16, 1), S // bt}):
        tables = perm[: slots * nb].reshape(slots, nb)
        width = nb * bt
        pos = jnp.minimum(
            jnp.asarray(
                [0, 1, bt - 1, bt, width // 2 + 3, width - bt, width - 2, width - 1],
                jnp.int32,
            ),
            width - 1,
        )
        with jax.default_matmul_precision("highest"):
            want = _paged_emulate(
                q.astype(jnp.float32), k_pool.astype(jnp.float32),
                v_pool.astype(jnp.float32), tables, pos,
                k_new.astype(jnp.float32), v_new.astype(jnp.float32), Hd**-0.5,
            )
        c.case(
            f"paged_attend slots={slots} nb={nb}",
            lambda tables=tables, pos=pos: jax.jit(
                lambda *a: paged_attend(*a, impl=impl)
            )(q, k_pool, v_pool, tables, pos, k_new, v_new),
            want,
        )
        # a window layer's tables: the blocks behind the window given back
        # (entry j backs logical block base + j), the bound cutting a block
        window = 2 * bt + 3
        base = jnp.maximum(pos - window + 1, 0) // bt
        with jax.default_matmul_precision("highest"):
            want = _paged_emulate(
                q.astype(jnp.float32), k_pool.astype(jnp.float32),
                v_pool.astype(jnp.float32), tables, pos,
                k_new.astype(jnp.float32), v_new.astype(jnp.float32), Hd**-0.5,
                window=window, base=base,
            )
        c.case(
            f"paged_attend_window slots={slots} nb={nb} window={window}",
            lambda tables=tables, pos=pos, base=base: jax.jit(
                lambda *a: paged_attend(*a, impl=impl, window=window, base=base)
            )(q, k_pool, v_pool, tables, pos, k_new, v_new),
            want,
        )


def check_variant_kernels(c: Checker, H, KVH, Hd, S, D, dtype, interpret: bool) -> None:
    """The variants off the llama serving paths: sinks, the rotating
    sliding-window ring, int8-KV tiles, the sp (acc, m, l) partials, and
    the hop codec's column kernels."""
    from dnet_tpu.compression.ops import _column_sq_norms_pallas, _pallas_matmul
    from dnet_tpu.core.kvcache import _quantize_q8
    from dnet_tpu.ops.flash_attention import flash_attend_causal
    from dnet_tpu.ops.flash_decode import NEG_INF, _decode_pallas, flash_decode_attend

    key = jax.random.key(1)
    G = H // KVH
    q, k, v = _qkv(key, 1, 1, S, H, KVH, Hd, dtype)
    sinks = jax.random.normal(jax.random.fold_in(key, 1), (H,), jnp.float32)
    pos = S // 2 + 5

    c.case(
        f"flash_decode sinks pos={pos}",
        lambda: jax.jit(flash_decode_attend)(q, k, v, jnp.int32(pos), sinks=sinks),
        _reference(q, k, v, causal_mask(1, S, pos), sinks=sinks),
    )
    T = min(128, S)
    qp = jax.random.normal(jax.random.fold_in(key, 2), (1, T, H, Hd), dtype)
    c.case(
        f"flash_prefill sinks T={T}",
        lambda: jax.jit(flash_attend_causal)(qp, k, v, jnp.int32(0), sinks=sinks),
        _reference(qp, k, v, causal_mask(T, S, 0), sinks=sinks),
    )

    # rotating ring: the whole cache is the ring (W = S slots), wrapped
    window = S - S // 8
    rpos = 2 * S + S // 3
    slot = np.arange(S)[None, :]
    k_abs = rpos - np.mod(rpos - slot, S)
    ring_mask = jnp.asarray((k_abs >= 0) & (k_abs > rpos - window))
    c.case(
        f"flash_decode rotating W={S} window={window} pos={rpos}",
        lambda: jax.jit(
            lambda q, k, v: flash_decode_attend(
                q, k, v, jnp.int32(rpos), window=window, rotating=True
            )
        )(q, k, v),
        _reference(q, k, v, ring_mask),
    )

    # int8-KV tiles: dequantized in VMEM == attend over the dequantized cache
    k8, ks = _quantize_q8(k.astype(jnp.float32))
    v8, vs = _quantize_q8(v.astype(jnp.float32))
    c.case(
        f"flash_decode int8 pos={pos}",
        lambda: jax.jit(
            lambda q, k8, v8, ks, vs: flash_decode_attend(
                q, k8, v8, jnp.int32(pos), k_scale=ks, v_scale=vs
            )
        )(q, k8, v8, ks, vs),
        _reference(
            q, k8.astype(jnp.float32) * ks, v8.astype(jnp.float32) * vs,
            causal_mask(1, S, pos),
        ),
    )

    # with_lse: two half-cache shards' partials merged like the sp combine
    half = S // 2
    sink0 = jnp.full((KVH, G), NEG_INF, jnp.float32)

    def sp_merged():
        parts = [
            _decode_pallas(
                q, k[:, r * half:(r + 1) * half], v[:, r * half:(r + 1) * half],
                jnp.asarray([pos, r * half], jnp.int32), sink0, G=G,
                scale=Hd**-0.5, bk=min(256, half), window=0, rotating=False,
                with_lse=True, interpret=interpret,
            )
            for r in range(2)
        ]
        (o0, m0, l0), (o1, m1, l1) = parts
        m = jnp.maximum(m0, m1)
        c0, c1 = jnp.exp(m0 - m), jnp.exp(m1 - m)
        o = o0 * c0.reshape(1, 1, H, 1) + o1 * c1.reshape(1, 1, H, 1)
        return o / (l0 * c0 + l1 * c1).reshape(1, 1, H, 1)

    c.case(
        f"flash_decode with_lse 2x{half} pos={pos}",
        jax.jit(sp_merged),
        _reference(q, k, v, causal_mask(1, S, pos)),
    )

    # hop codec on [rows, hidden] activations: per-column squared norms and
    # one-hot column gather/scatter
    for R in (16, 256, 1024):
        x = jax.random.normal(jax.random.fold_in(key, R), (R, D), dtype)
        xf = x.astype(jnp.float32)
        c.case(
            f"column_norms R={R} D={D}",
            lambda x=x, R=R: jax.jit(
                lambda x: _column_sq_norms_pallas(
                    x, row_tile=min(R, 256), interpret=interpret
                )
            )(x),
            jnp.sum(xf * xf, axis=0), tol=TOLERANCE_NORMS_REL, rel=True,
        )
    R, keep = 256, D // 2
    x = jax.random.normal(jax.random.fold_in(key, 3), (R, D), jnp.float32)
    idx = jnp.sort(jax.random.permutation(jax.random.fold_in(key, 4), D)[:keep])
    gather = (jnp.arange(D)[:, None] == idx[None, :]).astype(jnp.float32)
    c.case(
        f"column_select gather R={R} D={D} K={keep} f32",
        lambda: jax.jit(
            lambda x, g: _pallas_matmul(x, g, interpret=interpret)
        )(x, gather),
        jnp.take(x, idx, axis=1), tol=TOLERANCE_SELECT,
    )
    kept = jnp.take(x, idx, axis=1).astype(dtype)
    scatter = (idx[:, None] == jnp.arange(D)[None, :]).astype(jnp.float32)
    c.case(
        f"column_select scatter R={R} K={keep} D={D} {jnp.dtype(dtype).name}",
        lambda: jax.jit(
            lambda x, s: _pallas_matmul(x, s, interpret=interpret)
        )(kept, scatter),
        jnp.zeros((R, D), jnp.float32).at[:, idx].set(kept.astype(jnp.float32)),
        tol=TOLERANCE_SELECT,
    )


def main(argv=None) -> int:
    from dnet_tpu.config import configure_compile_cache, get_settings

    configure_compile_cache()
    p = argparse.ArgumentParser(prog="python -m dnet_tpu.ops.kernel_check")
    p.add_argument("--model", required=True, help="checkpoint dir (config.json)")
    p.add_argument("--max-seq", type=int, default=get_settings().api.max_seq_len)
    p.add_argument("--tolerance", type=float, default=TOLERANCE)
    args = p.parse_args(argv)

    cfg = json.loads((Path(args.model) / "config.json").read_text())
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    S = args.max_seq
    bt = get_settings().kv.block_tokens
    backend = kernel_backend()
    print(json.dumps({
        "device": device_report(), "backend": backend,
        "shapes": {"H": H, "KVH": KVH, "Hd": Hd, "S": S, "block_tokens": bt},
    }), flush=True)
    if backend is None:
        print("no Pallas kernel can run on this backend", file=sys.stderr)
        return 1

    c = Checker(args.tolerance)
    check_serving_kernels(c, H, KVH, Hd, S, bt, jnp.bfloat16)
    # the codec kernels tile the hidden dim by 128 lanes; a toy checkpoint's
    # narrower hidden is checked at the smallest width that does
    D = cfg["hidden_size"] if cfg["hidden_size"] % 128 == 0 else 256
    check_variant_kernels(
        c, H, KVH, Hd, S, D, jnp.bfloat16, backend == "interpret"
    )

    kernels = SELECTIONS.snapshot()
    failed = [r["case"] for r in c.results if not r["ok"]]
    # every attention dispatcher must have resolved to the backend's kernel
    # and to nothing else (the codec kernels were called below their
    # dispatchers, with the same interpret flag)
    strays = [
        f"{name}:{impl}={n}"
        for name in ("flash_prefill", "flash_decode", "paged_attend")
        for impl, n in kernels[name].items()
        if impl != "dense_shapes" and ((impl == backend) != (n > 0))
    ]
    print(json.dumps({
        "ok": not failed and not strays, "cases": len(c.results),
        "failed": failed, "strays": strays, "kernels": kernels,
    }), flush=True)
    return 1 if failed or strays else 0


if __name__ == "__main__":
    raise SystemExit(main())
