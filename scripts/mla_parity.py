#!/usr/bin/env python3
"""The absorbed latent-attention decode kernel alone, at the published
shape (32 heads over an entry of 256 + 64, kept in 384 lanes; float32):
`paged_attend_latent` against plain jnp, and its time.  Run it on the chip
(`chiprun -- python3 scripts/mla_parity.py`); `--interpret` drives the same
script here on the CPU at a small shape through the interpreted kernel (no
times).

Parity: a pool of two layers, lanes of RAGGED lengths up to `--tokens`
(one at a block's edge, one a single token, one IDLE at length 0, one at
the table's end), the current token's entry folded in the launch, the
layer taken by index.  The reference is the definition: every head's
softmax over `q . entry_j` for j <= pos, values the entries' first `rank`
lanes; and, for the absorb itself, the EXPANDED form (keys and values a
head made from the latents through W_kvb) must give the same outputs as
absorbed queries through the kernel and W_kvb[V] after it.

Times (the chip only): one step of 32 lanes in bf16 at the cell's
geometry (blocks of 128, a table of 264 entries), lengths log-uniform
4096-32768 (the cell's prompts), as bytes by benchmarks/kernel_costs_mla.py
over the median time of `--iters` programs of 6 layers' calls chained on
the device.

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
    p.add_argument("--tokens", type=int, default=33792)
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()

    import os

    if args.interpret:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import kernel_costs_mla as costs
    from dnet_tpu.ops import paged_attention as P

    dev = jax.devices()[0]
    if args.interpret:
        impl, H, r, rope, nope, vd, bt, S = "interpret", 4, 16, 8, 16, 16, 8, 256
    else:
        if dev.platform != "tpu":
            print("no TPU: run through chiprun, or pass --interpret", file=sys.stderr)
            return 3
        impl, H, r, rope, nope, vd, bt, S = "pallas", 32, 256, 64, 64, 128, 128, args.tokens
    W = -(-(r + rope) // 128) * 128
    nb = S // bt
    f32 = jnp.float32
    key = jax.random.split(jax.random.key(41), 8)
    # lanes: a block's edge, one token, idle, the table's end less one, ragged
    lens = [3 * bt, 1, 0, S - 1, S // 2 + 5, bt + 3, S // 3, 2 * bt - 1]
    B, L = len(lens), 2
    pos = jnp.asarray(lens, jnp.int32)
    N = B * nb + 1
    entries = jax.random.normal(key[0], (L, N, bt, r + rope), f32) * 0.5
    pool = jnp.pad(entries, ((0, 0), (0, 0), (0, 0), (0, W - r - rope)))
    # every lane its own blocks, in a shuffled order
    perm = np.random.default_rng(41).permutation(N - 1)[: B * nb].reshape(B, nb) + 1
    tables = jnp.asarray(perm, jnp.int32)
    w_kvb = jax.random.normal(key[1], (r, H, nope + vd), f32) * r**-0.5
    q_nope = jax.random.normal(key[2], (B, H, nope), f32)
    q_pe = jax.random.normal(key[3], (B, H, rope), f32)
    c_new = jnp.pad(
        jax.random.normal(key[4], (B, 1, r + rope), f32) * 0.5, ((0, 0), (0, 0), (0, W - r - rope))
    )
    sigma = (nope + rope) ** -0.5
    hp = jax.lax.Precision.HIGHEST
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, w_kvb[..., :nope], precision=hp)
    q_lat = jnp.concatenate([q_abs, q_pe, jnp.zeros((B, H, W - r - rope), f32)], -1) * sigma

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "impl": impl,
           "shape": {"heads": H, "rank": r, "rope": rope, "lanes": W, "block": bt,
                     "table": nb, "lengths": lens}}
    ok = True
    for layer in range(L):
        got = jax.jit(
            lambda q, pool, cn, layer: P.paged_attend_latent(
                q[:, None], pool, tables, pos, cn, r, layer, impl=impl
            )
        )(q_lat, pool, c_new, jnp.int32(layer))[:, 0]
        got = np.asarray(jnp.einsum("bhr,rhv->bhv", got, w_kvb[..., nope:], precision=hp))

        # the definition, EXPANDED: per-head keys and values from the
        # latents, a lane at a time (a lane's are 0.8 GB at the full shape)
        @jax.jit
        def expanded(pool_l):
            def lane(args):
                tbl, cn, p_, qn, qp = args
                view = pool_l[tbl].reshape(nb * bt, W)
                view = jax.lax.dynamic_update_slice(view, cn, (p_, 0))
                kv = jnp.einsum("sr,rhn->shn", view[:, :r], w_kvb, precision=hp)
                s = (
                    jnp.einsum("hn,shn->hs", qn, kv[..., :nope], precision=hp)
                    + jnp.einsum("hd,sd->hs", qp, view[:, r:r + rope], precision=hp)
                ) * sigma
                live = jnp.arange(nb * bt)[None, :] <= p_
                pr = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
                return jnp.einsum("hs,shv->hv", pr, kv[..., nope:], precision=hp)

            return jax.lax.map(lane, (tables, c_new, pos, q_nope, q_pe))

        want = np.asarray(expanded(pool[layer]))
        err = float(np.max(np.abs(got - want)))
        size = float(np.max(np.abs(want)))
        out[f"layer{layer}"] = {"max_err": err, "output_size": size}
        ok = ok and err <= 2e-4 * max(size, 1.0)
    out["ok"] = ok

    if not args.interpret:
        bf = jnp.bfloat16
        B2, L2, nb2 = 32, 6, 264
        rng = np.random.default_rng(7)
        lens2 = np.exp(rng.uniform(np.log(4096), np.log(32768), B2)).astype(np.int32)
        N2 = 8448
        # 4.98 GB: one random run of blocks, repeated (the time does not
        # care what the entries say)
        some = (jax.random.normal(key[5], (1, 64, bt, W), f32) * 0.5).astype(bf)
        pool2 = jnp.tile(some, (L2, N2 // 64, 1, 1))
        tables2 = jnp.asarray(rng.permutation(N2)[: B2 * nb2].reshape(B2, nb2), jnp.int32)
        q2 = jax.random.normal(key[6], (B2, 1, H, W), f32).astype(bf) * 0.05
        cn2 = jax.random.normal(key[7], (B2, 1, W), f32).astype(bf)
        pos2 = jnp.asarray(lens2)
        for table, sub in ((256, 16), (264, 16), (256, 8), (256, 4)):
            tb = tables2[:, :table]
            # table entries folded a grid step (a module constant, read at
            # trace time: swept here to show where it stands)
            P.LATENT_SUB_BLOCKS = sub
            P._latent_pallas.clear_cache()

            def layers(q, pool):
                def body(q, layer):
                    o = P.paged_attend_latent(q, pool, tb, pos2, cn2, r, layer, impl="pallas")
                    # chain the calls: the next query depends on this output
                    return q + jnp.pad(o, ((0, 0),) * 3 + ((0, W - r),)) * 1e-3, o[0, 0, 0, 0]

                return jax.lax.scan(body, q, jnp.arange(L2, dtype=jnp.int32))

            fn = jax.jit(layers)
            times = []
            for _ in range(args.iters + 3):
                t0 = time.perf_counter()
                _, o = fn(q2, pool2)
                o.block_until_ready()
                times.append((time.perf_counter() - t0) / L2)
            t = statistics.median(times[3:])
            live = int(np.minimum(lens2, table * bt).sum())
            need = costs.latent_decode_cost(live, B2, H, r, rope)
            out[f"step_table{table}_sub{sub}"] = {
                "lanes": B2, "live_tokens": live, "seconds_a_layer": t,
                "bytes": need["bytes"], "gb_per_s": need["bytes"] / t / 1e9,
                "flops": need["flops"], "tflop_per_s": need["flops"] / t / 1e12,
                "kept_gb_per_s": live * W * 2 / t / 1e9,
            }
        P.LATENT_SUB_BLOCKS = 16
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
