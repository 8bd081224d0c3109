#!/usr/bin/env python3
"""Power retention at the published head shape: the decode step, the chunked
prefill and the quadratic definition against each other, and the two
kernels' times.  Run it on the chip (`chiprun -- python3
scripts/retention_parity.py`); `--interpret` drives the same script here on
the CPU at a small head through the interpreted kernels (no times).

Parity: one sequence of `4 x chunk + ragged` tokens (log-gates uniform in
[-0.02, 0], so a key still weighs 1e-9 of itself 1000 tokens on and a state
dropped, decayed twice or handed to the wrong lane moves every later
output) goes through `retention_chunk` a chunk at a time, the last one
padded, then `--steps` tokens through `retention_step` in a store of four
lanes of which one idles and one runs another sequence; every output is
compared with `retention_quadratic` over the whole sequence.

Times (the chip only): the step over 16 lanes x 8 KV heads in one layer of
a two-layer store, and one 2048-token chunk over 8 KV heads x 5 query
heads, each as bytes or operations by benchmarks/kernel_costs_retention.py
over the median time of `--iters` launches.

Last stdout line: one JSON object."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--interpret", action="store_true")
    p.add_argument("--chunk", type=int, default=512)
    p.add_argument("--steps", type=int, default=48)
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()

    import os

    if args.interpret:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import kernel_costs_retention as costs
    from dnet_tpu.ops import retention as R

    dev = jax.devices()[0]
    if args.interpret:
        impl, Hd, chunk = "interpret", 16, 64
    else:
        if dev.platform != "tpu":
            print("no TPU: run through chiprun, or pass --interpret", file=sys.stderr)
            return 3
        impl, Hd, chunk = "pallas", 128, args.chunk
    KVH, G = 2, 5
    H = KVH * G
    ragged = chunk // 2 - 7
    T, n = 4 * chunk + ragged, args.steps
    key = jax.random.split(jax.random.key(32), 8)
    f32 = jnp.float32
    q = jax.random.normal(key[0], (T + n, H, Hd), f32)
    k = jax.random.normal(key[1], (T + n, KVH, Hd), f32)
    v = jax.random.normal(key[2], (T + n, KVH, Hd), f32)
    lg = -0.02 * jax.random.uniform(key[3], (T + n, KVH), f32)
    want = np.asarray(jax.jit(R.retention_quadratic)(q, k, v, lg))
    scale = float(np.max(np.abs(want)))

    # ---- prefill in chunks, the last one ragged (padded with garbage) ----
    chunk_fn = jax.jit(lambda st, *a: R.retention_chunk(st, *a, impl=impl))
    emu_fn = jax.jit(lambda st, *a: R.retention_chunk(st, *a, impl="emulate"))
    st = R.init_state((), KVH, Hd)
    st_emu = R.init_state((), KVH, Hd)
    outs = []
    for c0 in range(0, T, chunk):
        real = min(chunk, T - c0)
        pad = lambda a, fill: jnp.full((chunk,) + a.shape[1:], fill, a.dtype).at[:real].set(a[c0:c0 + real])
        a = (pad(q, 1.0), pad(k, 1.0), pad(v, 1.0), pad(lg, -1.0), jnp.arange(chunk) < real)
        o, st = chunk_fn(st, *a)
        _, st_emu = emu_fn(st_emu, *a)
        outs.append(np.asarray(o)[:real])
    got = np.concatenate(outs)
    chunk_err = float(np.max(np.abs(got - want[:T])))
    state_err = float(jnp.max(jnp.abs(st["S"] - st_emu["S"])) / jnp.max(jnp.abs(st_emu["S"])))

    # ---- hand over to the decode step: lane 2 of 4 in layer 1 of 2 -------
    B, L = 4, 2
    store = R.init_state((L, B), KVH, Hd)
    store = jax.tree.map(lambda s, e: s.at[1, 2].set(e), store, st)
    store = jax.tree.map(lambda s, e: s.at[1, 1].set(e), store, st)  # idles: must not move
    step_fn = jax.jit(
        lambda store, q, k, v, lg, act: R.retention_step(store, q, k, v, lg, act, 1, impl=impl),
        donate_argnums=(0,),
    )
    active = jnp.asarray([1, 0, 1, 0], jnp.int32)
    step_out = []
    for t in range(T, T + n):
        lane = lambda a, other: jnp.stack([other, a[t], a[t], other])
        o, store = step_fn(
            store, lane(q, q[t - T]), lane(k, k[t - T]), lane(v, v[t - T]),
            lane(lg, lg[t - T]), active,
        )
        step_out.append(np.asarray(o[2]))
    step_err = float(np.max(np.abs(np.stack(step_out) - want[T:])))
    idle_moved = float(jnp.max(jnp.abs(store["S"][1, 1] - st["S"])))
    other_layer = float(jnp.max(jnp.abs(store["S"][0])))
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "impl": impl, "head_dim": Hd, "tokens": T, "chunks": -(-T // chunk),
        "chunk": chunk, "ragged": ragged, "steps": n, "scale": scale,
        "chunk_vs_quadratic": chunk_err, "step_vs_quadratic": step_err,
        "state_pallas_vs_jnp_rel": state_err, "idle_lane_moved": idle_moved,
        "other_layer_touched": other_layer,
    }
    ok = (
        chunk_err <= 2e-3 * scale and step_err <= 2e-3 * scale and state_err <= 1e-4
        and idle_moved == 0.0 and other_layer == 0.0
    )
    # ---- bfloat16 operands, as the model hands them over -----------------
    # the kernels upcast in VMEM: against the definition over the same
    # rounded values only the output's own rounding may be left
    bf = jnp.bfloat16
    qb, kb, vb = (a[:chunk].astype(bf) for a in (q, k, v))
    want_b = np.asarray(R.retention_quadratic(qb, kb, vb, lg[:chunk]))
    o_b, st_b = chunk_fn(R.init_state((), KVH, Hd), qb, kb, vb, lg[:chunk], jnp.ones((chunk,), bool))
    bf16_chunk_err = float(np.max(np.abs(np.asarray(o_b, np.float32) - want_b)))
    store_b = jax.tree.map(lambda e: e[None, None], st_b)
    step_b = jax.jit(
        lambda store, q, k, v, lg: R.retention_step(
            store, q, k, v, lg, jnp.ones((1,), jnp.int32), 0, impl=impl),
        donate_argnums=(0,),
    )
    qn, kn, vn = (a[chunk:chunk + 1].astype(bf) for a in (q, k, v))
    o_s, _ = step_b(store_b, qn, kn, vn, lg[chunk:chunk + 1])
    want_s = np.asarray(R.retention_quadratic(
        jnp.concatenate([qb, qn]), jnp.concatenate([kb, kn]), jnp.concatenate([vb, vn]),
        lg[:chunk + 1]))[-1]
    bf16_step_err = float(np.max(np.abs(np.asarray(o_s[0]) - want_s)))
    out["bf16_chunk_vs_quadratic"] = bf16_chunk_err
    out["bf16_step_vs_quadratic"] = bf16_step_err
    ok = ok and bf16_chunk_err <= 2.0**-7 * scale and bf16_step_err <= 2e-3 * scale
    out["ok"] = bool(ok)

    # ---- times: the chip only -------------------------------------------
    if not args.interpret:
        B, KVH, G, L = 16, 8, 5, 2
        H = KVH * G
        store = jax.tree.map(
            lambda a: a + 0.01, R.init_state((L, B), KVH, Hd)
        )
        qs = jax.random.normal(key[4], (B, H, Hd), bf)
        ks = jax.random.normal(key[5], (B, KVH, Hd), bf)
        lgs = -0.5 * jnp.ones((B, KVH), f32)
        act = jnp.ones((B,), jnp.int32)
        step16 = jax.jit(
            lambda store: R.retention_step(store, qs, ks, ks, lgs, act, 1, impl="pallas"),
            donate_argnums=(0,),
        )
        times = []
        for _ in range(args.iters + 3):
            t0 = time.perf_counter()
            o, store = step16(store)
            o.block_until_ready()
            times.append(time.perf_counter() - t0)
        t_step = statistics.median(times[3:])
        need = costs.retention_step_cost(lanes=B, kv_heads=KVH, q_heads=H, head_dim=Hd)
        out["step"] = {
            "lanes": B, "kv_heads": KVH, "seconds": t_step, "bytes": need["bytes"],
            "gb_per_s": need["bytes"] / t_step / 1e9,
        }
        Tc = 2048
        qc = jax.random.normal(key[6], (Tc, H, Hd), bf)
        kc = jax.random.normal(key[7], (Tc, KVH, Hd), bf)
        lgc = -0.5 * jnp.ones((Tc, KVH), f32)
        st8 = R.init_state((), KVH, Hd)
        chunk8 = jax.jit(lambda st: R.retention_chunk(st, qc, kc, kc, lgc, impl="pallas"))
        times = []
        for _ in range(args.iters + 3):
            t0 = time.perf_counter()
            o, st8 = chunk8(st8)
            o.block_until_ready()
            times.append(time.perf_counter() - t0)
        t_chunk = statistics.median(times[3:])
        need = costs.retention_chunk_cost(tokens=Tc, kv_heads=KVH, q_heads=H, head_dim=Hd)
        out["chunk_kernel"] = {
            "tokens": Tc, "seconds": t_chunk, "flops": need["flops"],
            "tflop_per_s": need["flops"] / t_chunk / 1e12,
        }
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
