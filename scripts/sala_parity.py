#!/usr/bin/env python3
"""MiniCPM-SALA's five kernels at the published shapes (32 query / 2 KV
heads of 128; 32 lightning heads of 128 x 128 float32; pages of 128 tokens,
blocks of 64): each against its definition, and its time.  Run it on the
chip (`chiprun -- python3 scripts/sala_parity.py`); `--interpret` drives the
same script here on the CPU at a small shape through the interpreted
kernels (no times).

Parity: two chunks go through `lightning_chunk` and `--steps` tokens through `lightning_step` in a store of four lanes
(one idle) against `lightning_quadratic`; a chunk at the sequence's END goes
through `sparse_prefill` and then `--steps` tokens through `sparse_decode`
over a shuffled page table against `sparse_attend_dense` (float32: the
kernels ask for `highest`).

Times (the chip only; bf16 operands as served): each kernel as operations
and bytes by benchmarks/kernel_costs_sala.py over the median time of
`--iters` programs of `--chain` calls chained on the device, as a share of
the peak that bounds it (197 TFLOP/s, 819 GB/s: Google Cloud, "TPU v5e").
The decode kernels run 16 lanes at `--context` tokens each, the prefill
kernels a chunk of 2048 at position `--context`.

Last stdout line: one JSON object."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--interpret", action="store_true")
    p.add_argument("--tokens", type=int, default=12288)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--context", type=int, default=32768)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--chain", type=int, default=8)
    args = p.parse_args()

    import os

    if args.interpret:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmarks import kernel_costs_sala as costs
    from dnet_tpu.ops import lightning as L
    from dnet_tpu.ops import sparse_attention as SA

    dev = jax.devices()[0]
    if args.interpret:
        impl, H, KVH, Hd, LH, cfg = "interpret", 4, 2, 16, 4, SA.SparseConfig(
            kernel_size=4, kernel_stride=2, block_size=8, topk=6, init_blocks=1,
            window_size=16, dense_len=64)
        T, chunk, bt = 256, 32, 16
    else:
        if dev.platform != "tpu":
            print("no accelerator: run with --interpret here", file=sys.stderr)
            return 3
        impl, H, KVH, Hd, LH, cfg = "pallas", 32, 2, 128, 32, SA.SparseConfig()
        T, chunk, bt = args.tokens, 2048, 128
    steps = args.steps
    key = jax.random.key(50)
    out = {"device": dev.device_kind, "impl": impl}

    # ---- lightning: chunks, then steps in a store, against the quadratic form
    # (the quadratic form holds [heads, T, T] float32: two chunks of it here)
    TL = min(T, 2 * chunk)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (TL + steps, LH, Hd)) for i in range(3))
    want = L.lightning_quadratic(q, k, v)
    S = jnp.zeros((LH, Hd, Hd))
    worst = 0.0
    for a in range(0, TL, chunk):
        o, S = L.lightning_chunk(S, q[a:a + chunk], k[a:a + chunk], v[a:a + chunk], impl=impl)
        worst = max(worst, float(jnp.max(jnp.abs(o - want[a:a + chunk]))))
    out["lightning_chunk_err"] = worst
    store = jnp.zeros((2, 4, LH, Hd, Hd)).at[1, 2].set(S).at[1, 3].set(7.0)
    step = jax.jit(lambda st, qb, kb, vb: L.lightning_step(
        st, qb, kb, vb, jnp.array([0, 0, 1, 0]), jnp.int32(1), impl=impl), donate_argnums=(0,))
    worst = 0.0
    for t in range(TL, TL + steps):
        o, store = step(store, *(jnp.broadcast_to(a[t], (4, LH, Hd)) for a in (q, k, v)))
        worst = max(worst, float(jnp.max(jnp.abs(o[2] - want[t]))))
    out["lightning_step_err"] = worst
    out["lightning_idle_lane_untouched"] = bool(jnp.all(store[1, 3] == 7.0) & jnp.all(store[0] == 0))
    out["lightning_out_scale"] = float(jnp.mean(jnp.abs(want)))

    # ---- sparse: the last chunk of the sequence, then steps through a page table
    q, k, v = (jax.random.normal(jax.random.fold_in(key, 10 + i), (T + steps, h, Hd))
               for i, h in enumerate((H, KVH, KVH)))
    q = q * 3.0  # a peaked index: the choice matters
    S_row = -(-(T + steps) // bt) * bt
    pad = lambda a: jnp.pad(a, ((0, S_row - a.shape[0]), (0, 0), (0, 0)))
    kr, vr = pad(k), pad(v)
    p0 = T - chunk
    want = SA.sparse_attend_dense(q[p0:], kr, vr, p0, cfg)
    written = (jnp.arange(S_row) < T)[:, None, None]
    got = SA.sparse_prefill(q[p0:T], jnp.where(written, kr, 0), jnp.where(written, vr, 0),
                            jnp.int32(p0), cfg, impl=impl)
    out["sparse_prefill_err"] = float(jnp.max(jnp.abs(got - want[:chunk])))
    nb = S_row // bt
    W = KVH * Hd
    perm = np.random.RandomState(0).permutation(nb + 8)[:nb]
    table = jnp.asarray(np.stack([np.arange(nb), perm]).astype(np.int32))
    kc = SA.pooled_keys(jnp.where(written, kr, 0).reshape(S_row, W), cfg)
    rpb = bt // cfg.kernel_stride
    pool = {
        "k": jnp.zeros((2, nb + 8, bt, W)).at[1, perm].set(jnp.where(written, kr, 0).reshape(nb, bt, W)),
        "v": jnp.zeros((2, nb + 8, bt, W)).at[1, perm].set(jnp.where(written, vr, 0).reshape(nb, bt, W)),
        "kc": jnp.zeros((2, nb + 8, rpb, W)).at[1, perm].set(kc.reshape(nb, rpb, W)),
    }
    dec = jax.jit(lambda pool, qq, kn, vn, pos: SA.sparse_decode(
        pool, qq, kn, vn, table, pos, jnp.array([0, 1]), jnp.int32(1), cfg, impl=impl),
        donate_argnums=(0,))
    worst = 0.0
    for t in range(T, T + steps):
        o, pool = dec(pool, jnp.stack([q[t] * 0, q[t]])[:, None], jnp.stack([k[t] * 0, k[t]]),
                      jnp.stack([v[t] * 0, v[t]]), jnp.array([3, t]))
        worst = max(worst, float(jnp.max(jnp.abs(o[1, 0] - want[chunk + t - T]))))
    out["sparse_decode_err"] = worst
    out["sparse_out_scale"] = float(jnp.mean(jnp.abs(want)))

    if args.interpret:
        print(json.dumps(out))
        return 0

    # ---- times, bf16 as served
    def timed(fn, *xs, calls=args.chain):
        """Median seconds of ONE call: `calls` of them chained on the device."""
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*xs))
        ts = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            ts.append((time.perf_counter() - t0) / calls)
        return statistics.median(ts)

    def share(cost, seconds):
        least = max(cost["flops"] / PEAK_FLOPS, cost["bytes"] / PEAK_BYTES)
        bound = "compute" if cost["flops"] / PEAK_FLOPS > cost["bytes"] / PEAK_BYTES else "memory"
        return {"ms": seconds * 1e3, "flops": cost["flops"], "bytes": cost["bytes"],
                "roofline_pct": 100 * least / seconds, "bound": bound}

    BF = jnp.bfloat16
    lanes, ctx, C = 16, args.context, args.chain
    rnd = lambda i, shape: jax.random.normal(jax.random.fold_in(key, 100 + i), shape, BF)
    # lightning_step: 16 lanes, one layer of a two-layer store; 64 steps a
    # program, so that the store's copy in (it is not donated here) weighs 2 %
    st = jnp.zeros((2, lanes, 32, 128, 128), jnp.float32)
    qkv = [rnd(i, (lanes, 32, 128)) for i in range(3)]

    def steps_fn(st, q, k, v):
        def body(st, _):
            o, st = L.lightning_step(st, q, k, v, jnp.ones((lanes,), jnp.int32), jnp.int32(1), impl="pallas")
            return st, o[0, 0, 0]
        return lax.scan(body, st, None, length=8 * C)

    out["lightning_step"] = share(
        costs.lightning_step_cost(lanes, 32, 128), timed(steps_fn, st, *qkv, calls=8 * C))
    qkv = [rnd(3 + i, (2048, 32, 128)) for i in range(3)]

    def chunks_fn(S, q, k, v):
        def body(S, _):
            o, S = L.lightning_chunk(S * 0.5, q, k, v, impl="pallas")
            return S, o[0, 0, 0]
        return lax.scan(body, S, None, length=C)

    out["lightning_chunk"] = share(costs.lightning_chunk_cost(2048, 32, 128),
                                   timed(chunks_fn, jnp.zeros((32, 128, 128)), *qkv))
    # the sparse kernels at `ctx` tokens of context
    S_row = 66560
    nbp = S_row // 128
    kc = rnd(6, (lanes, S_row // 16, 2, 128))
    qd = rnd(7, (lanes, 1, 32, 128))
    pos = jnp.full((lanes,), ctx - 1, jnp.int32)

    def index_dec(q, kc):
        def body(c, _):
            r = SA.index_scores(q + c.astype(BF), kc, pos, SA.SparseConfig(), impl="pallas")
            return r[0, 0, 0, 0] * 0, r[0, 0, 0, 1]
        return lax.scan(body, jnp.float32(0), None, length=C)

    out["sparse_index.decode"] = share(
        costs.sparse_index_cost([ctx] * lanes, 32, 2, 128, shared=False), timed(index_dec, qd, kc))
    qp = rnd(8, (1, 2048, 32, 128))

    def index_pre(q, kc):
        def body(c, _):
            r = SA.index_scores(q + c.astype(BF), kc[:1], jnp.array([ctx]), SA.SparseConfig(), impl="pallas")
            return r[0, 0, 0, 0] * 0, r[0, 0, 0, 1]
        return lax.scan(body, jnp.float32(0), None, length=C)

    out["sparse_index.prefill"] = share(
        costs.sparse_index_cost(range(ctx + 1, ctx + 2049), 32, 2, 128, shared=True),
        timed(index_pre, qp, kc))
    kp, vp = rnd(9, (2, lanes * nbp, 128, 256)), rnd(10, (2, lanes * nbp, 128, 256))
    table = jnp.arange(lanes * nbp, dtype=jnp.int32).reshape(lanes, nbp)
    R = jax.random.uniform(jax.random.fold_in(key, 11), (lanes, 2, S_row // 64))
    chosen = SA.choose_blocks(R, jnp.broadcast_to(pos[:, None], (lanes, 2)), SA.SparseConfig())

    def read_dec(q, kp, vp):
        def body(c, _):
            o = SA.paged_attend_sparse(q + c.astype(BF), kp, vp, table, chosen, pos, jnp.int32(1),
                                       SA.SparseConfig(), impl="pallas")
            return o[0, 0, 0, 0].astype(jnp.float32) * 0, o[0, 0, 0, 1]
        return lax.scan(body, jnp.float32(0), None, length=C)

    out["paged_attend_sparse"] = share(
        costs.paged_attend_sparse_cost([ctx] * lanes, 32, 2, 128), timed(read_dec, qd, kp, vp))
    kr, vr = rnd(12, (S_row, 2, 128)), rnd(13, (S_row, 2, 128))
    t = ctx + jnp.arange(2048, dtype=jnp.int32)
    R = jax.random.uniform(jax.random.fold_in(key, 14), (2, 2048, S_row // 64))
    apart = SA.choose_blocks(R, jnp.broadcast_to(t, (2, 2048)), SA.SparseConfig())
    alike = SA.choose_blocks(jnp.broadcast_to(R[:, :1], R.shape), jnp.broadcast_to(t, (2, 2048)),
                             SA.SparseConfig())

    def read_pre(chosen):
        def fn(q, k, v):
            def body(c, _):
                o = SA.flash_prefill_sparse(q + c.astype(BF), k, v, jnp.int32(ctx), chosen,
                                            SA.SparseConfig(), impl="pallas")
                return o[0, 0, 0].astype(jnp.float32) * 0, o[0, 0, 1]
            return lax.scan(body, jnp.float32(0), None, length=C)
        return fn

    cost = costs.flash_prefill_sparse_cost(ctx, 2048, 32, 2, 128)
    # seeded queries choose apart (a q tile's union is every tile); queries
    # that choose alike leave a q tile 64-odd tiles of the row's hundreds
    out["flash_prefill_sparse.apart"] = share(cost, timed(read_pre(apart), qp[0], kr, vr))
    out["flash_prefill_sparse.alike"] = share(cost, timed(read_pre(alike), qp[0], kr, vr))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
