#!/usr/bin/env python3
"""The gated delta rule at the published head shape (32 value heads x 128 x
128, float32): the decode step, the chunked prefill and the token-by-token
recurrence against each other, and the two kernels' times.  Run it on the
chip (`chiprun -- python3 scripts/gdn_parity.py`); `--interpret` drives the
same script here on the CPU at a small head through the interpreted
kernels (no times).

Parity: one sequence of `4 x chunk + ragged` tokens with `g` uniform in
[-0.02, 0] (LONG memory, which the benchmark's check lacks: there g is
about -0.7 and a key fades in a few tokens; here a key still weighs 1e-9 of
itself 1000 tokens on, so a state dropped, decayed twice or handed to the
wrong lane moves every later output) goes through `gdn_chunk` a chunk of
2048 at a time, the last one padded, then `--steps` tokens through
`gdn_step` in a store of four lanes of which one idles and one runs another
sequence; every output is compared with `gdn_recurrence` over the whole
sequence.

Times (the chip only): the step over 16 lanes x 32 value heads in one
layer of a three-layer store, and one 2048-token chunk, each as bytes or
operations by benchmarks/kernel_costs_gdn.py over the median time of
`--iters` programs of 32 steps (8 chunks) chained on the device: a step is
about 0.1 ms, less than one launch from the host costs, and the l2 norms
and head-spreading around the kernel are in the time.

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
    p.add_argument("--chunk", type=int, default=2048)
    p.add_argument("--steps", type=int, default=48)
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()

    import os

    if args.interpret:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import kernel_costs_gdn as costs
    from dnet_tpu.ops import gated_delta as G

    dev = jax.devices()[0]
    if args.interpret:
        impl, HK, HV, Dk, Dv, chunk = "interpret", 2, 4, 16, 16, 128
    else:
        if dev.platform != "tpu":
            print("no TPU: run through chiprun, or pass --interpret", file=sys.stderr)
            return 3
        impl, HK, HV, Dk, Dv, chunk = "pallas", 16, 32, 128, 128, args.chunk
    ragged = chunk // 2 - 7
    T, n = 4 * chunk + ragged, args.steps
    key = jax.random.split(jax.random.key(37), 12)
    f32 = jnp.float32
    q = jax.random.normal(key[0], (T + n, HK, Dk), f32)
    k = jax.random.normal(key[1], (T + n, HK, Dk), f32)
    v = jax.random.normal(key[2], (T + n, HV, Dv), f32)
    g = -0.02 * jax.random.uniform(key[3], (T + n, HV), f32)
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (T + n, HV), f32))
    S0 = jnp.zeros((HV, Dk, Dv), f32)
    want, S_want = jax.jit(G.gdn_recurrence)(S0, q, k, v, g, beta)
    want = np.asarray(want)
    scale = float(np.max(np.abs(want)))

    # ---- prefill in chunks, the last one ragged (padded with garbage) ----
    chunk_fn = jax.jit(lambda S, *a: G.gdn_chunk(S, *a, impl=impl))
    emu_fn = jax.jit(lambda S, *a: G.gdn_chunk(S, *a, impl="emulate"))
    S, S_emu, outs = S0, S0, []
    for c0 in range(0, T, chunk):
        real = min(chunk, T - c0)
        pad = lambda a, fill: jnp.full((chunk,) + a.shape[1:], fill, a.dtype).at[:real].set(a[c0:c0 + real])
        a = (pad(q, 1.0), pad(k, 1.0), pad(v, 1.0), pad(g, -1.0), pad(beta, 0.9), jnp.arange(chunk) < real)
        o, S = chunk_fn(S, *a)
        _, S_emu = emu_fn(S_emu, *a)
        outs.append(np.asarray(o)[:real])
    got = np.concatenate(outs)
    chunk_err = float(np.max(np.abs(got - want[:T])))
    S_T = jax.jit(G.gdn_recurrence)(S0, q[:T], k[:T], v[:T], g[:T], beta[:T])[1]
    state_err = float(jnp.max(jnp.abs(S - S_T)) / jnp.max(jnp.abs(S_T)))
    state_vs_jnp = float(jnp.max(jnp.abs(S - S_emu)) / jnp.max(jnp.abs(S_emu)))

    # ---- hand over to the decode step: lane 2 of 4 in layer 1 of 2 -------
    B, L = 4, 2
    store = jnp.zeros((L, B, HV, Dk, Dv), f32).at[1, 2].set(S).at[1, 1].set(S)  # lane 1 idles
    step_fn = jax.jit(
        lambda store, q, k, v, g, b, act: G.gdn_step(store, q, k, v, g, b, act, 1, impl=impl),
        donate_argnums=(0,),
    )
    active = jnp.asarray([1, 0, 1, 0], jnp.int32)
    step_out = []
    for t in range(T, T + n):
        lane = lambda a: jnp.stack([a[t - T], a[t], a[t], a[t - T]])
        o, store = step_fn(store, lane(q), lane(k), lane(v), lane(g), lane(beta), active)
        step_out.append(np.asarray(o[2]))
    step_err = float(np.max(np.abs(np.stack(step_out) - want[T:])))
    idle_moved = float(jnp.max(jnp.abs(store[1, 1] - S)))
    other_layer = float(jnp.max(jnp.abs(store[0])))
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "impl": impl, "heads": [HK, HV], "head_dims": [Dk, Dv], "tokens": T,
        "chunks": -(-T // chunk), "chunk": chunk, "ragged": ragged, "steps": n,
        "scale": scale, "chunk_vs_recurrence": chunk_err, "step_vs_recurrence": step_err,
        "state_vs_recurrence_rel": state_err, "state_pallas_vs_jnp_rel": state_vs_jnp,
        "idle_lane_moved": idle_moved, "other_layer_touched": other_layer,
    }
    ok = (
        chunk_err <= 2e-3 * scale and step_err <= 2e-3 * scale and state_err <= 1e-3
        and idle_moved == 0.0 and other_layer == 0.0
    )
    out["ok"] = bool(ok)

    # ---- times: the chip only -------------------------------------------
    if not args.interpret:
        bf = jnp.bfloat16
        B, L = 16, 3
        store = jnp.full((L, B, HV, Dk, Dv), 0.01, f32)
        qs = jax.random.normal(key[5], (B, HK, Dk), bf)
        ks = jax.random.normal(key[6], (B, HK, Dk), bf)
        vs = jax.random.normal(key[7], (B, HV, Dv), bf)
        gs = -0.5 * jnp.ones((B, HV), f32)
        bs = 0.5 * jnp.ones((B, HV), f32)
        act = jnp.ones((B,), jnp.int32)
        REPS = 32  # launches chained inside ONE program: the host's dispatch is not in the time

        def steps(store):
            def body(store, _):
                o, store = G.gdn_step(store, qs, ks, vs, gs, bs, act, 1, impl="pallas")
                return store, o[0, 0, 0]

            return jax.lax.scan(body, store, None, length=REPS)

        step16 = jax.jit(steps, donate_argnums=(0,))
        times = []
        for _ in range(args.iters + 3):
            t0 = time.perf_counter()
            store, o = step16(store)
            o.block_until_ready()
            times.append((time.perf_counter() - t0) / REPS)
        t_step = statistics.median(times[3:])
        need = costs.gdn_step_cost(lanes=B, k_heads=HK, v_heads=HV, k_dim=Dk, v_dim=Dv)
        out["step"] = {
            "lanes": B, "seconds": t_step, "bytes": need["bytes"],
            "gb_per_s": need["bytes"] / t_step / 1e9,
        }
        Tc = 2048
        qc = jax.random.normal(key[8], (Tc, HK, Dk), bf)
        kc = jax.random.normal(key[9], (Tc, HK, Dk), bf)
        vc = jax.random.normal(key[10], (Tc, HV, Dv), bf)
        gc = -0.5 * jnp.ones((Tc, HV), f32)
        bc = 0.5 * jnp.ones((Tc, HV), f32)
        CHUNKS = 8

        def chunks(S):
            def body(S, _):
                o, S = G.gdn_chunk(S, qc, kc, vc, gc, bc, impl="pallas")
                return S, o[0, 0, 0]

            return jax.lax.scan(body, S, None, length=CHUNKS)

        chunk1 = jax.jit(chunks)
        S1 = S0
        times = []
        for _ in range(args.iters + 3):
            t0 = time.perf_counter()
            S1, o = chunk1(S1)
            o.block_until_ready()
            times.append((time.perf_counter() - t0) / CHUNKS)
        t_chunk = statistics.median(times[3:])
        need = costs.gdn_chunk_cost(tokens=Tc, k_heads=HK, v_heads=HV, k_dim=Dk, v_dim=Dv)
        out["chunk_kernel"] = {
            "tokens": Tc, "seconds": t_chunk, "flops": need["flops"],
            "tflop_per_s": need["flops"] / t_chunk / 1e12,
        }
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
