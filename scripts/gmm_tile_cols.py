#!/usr/bin/env python3
"""The grouped matmul's column tiles at sides the cap does not divide: the
dividing tile (`ops/moe.py: group_tile_cols`: 2304 -> 768) against the masked
cap (1024, megablox masks the remainder of the last of three steps), on the
chip, at Mellum2's expert shapes (PERF.md section 6, PR 54).

    chiprun -- python3 scripts/gmm_tile_cols.py

Times Pallas' megablox `gmm` alone on seeded bf16 operands: a 2048-token
chunk's 16384 sorted rows over 64 groups of 256 rows, the gate / up product
[16384, 2304] x [64, 2304, 896] (the side is K) and the down product
[16384, 896] x [64, 896, 2304] (the side is N), each at the dividing and at
the masked tiling, and checks that both give the same numbers.  One JSON
line.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from dnet_tpu.ops.moe import GROUP_TILE_COLS, GROUP_TILE_ROWS, group_tile_cols

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "no TPU here: a time comes only from a chip run"}))
        return 3
    M, G, D, F = 16384, 64, 2304, 896
    key = jax.random.key(54)
    sizes = jnp.full((G,), M // G, jnp.int32)
    out = {"device": jax.devices()[0].device_kind, "rows": M, "groups": G}
    for name, (K, N) in {"gate_up": (D, F), "down": (F, D)}.items():
        kx, kw = jax.random.split(jax.random.fold_in(key, K))
        xs = jax.random.normal(kx, (M, K), jnp.bfloat16)
        w = (jax.random.normal(kw, (G, K, N), jnp.float32) * 0.02).astype(jnp.bfloat16)
        results = {}
        for label, cols in (("dividing", group_tile_cols), ("masked", lambda s: min(s, GROUP_TILE_COLS))):
            tiling = (GROUP_TILE_ROWS, cols(K), cols(N))
            fn = jax.jit(lambda a, b, s, t=tiling: gmm(a, b, s, preferred_element_type=a.dtype, tiling=t))
            y = jax.block_until_ready(fn(xs, w, sizes))
            t0 = time.perf_counter()
            for _ in range(20):
                y = fn(xs, w, sizes)
            jax.block_until_ready(y)
            results[label] = {"tiling": list(tiling), "ms": (time.perf_counter() - t0) / 20 * 1e3}
            results[label]["y"] = y
        same = bool(jnp.all(results["dividing"].pop("y") == results["masked"].pop("y")))
        flops = 2 * M * K * N
        for r in results.values():
            r["tflops"] = flops / (r["ms"] * 1e-3) / 1e12
        out[name] = dict(results, equal_to_the_bit=same)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
