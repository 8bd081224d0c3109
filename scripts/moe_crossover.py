#!/usr/bin/env python3
"""Dense against grouped routed experts, by rows: the table behind
`ops/moe.py: RIDGE_ROWS` (PERF.md section 6, PR 31).

    chiprun -- python scripts/moe_crossover.py            # times, on the chip
    JAX_PLATFORMS=cpu python scripts/moe_crossover.py --compile-only

Times the program's own closures (`swiglu_expert_closures`' dense() and
`swiglu_grouped_closure`) on seeded bf16 weights at the two benchmark
configurations' expert shapes, and, for comparison only, the same grouped
closure over XLA's own lowering of `lax.ragged_dot` and over a 512-row
tile.  `--compile-only` compiles for a described v5e without a chip and
prints what the HLO says the grouped program does (FLOPs against the routed
and the dense count, the custom calls it holds); it measures nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

#: name -> (held experts, routed experts, offset, hidden, expert width, top-k)
SHAPES = {
    "qwen3-30b-a3b": (128, 128, 0, 2048, 768, 8),
    "command-a-plus-share": (16, 128, 16, 4096, 4096, 8),
}
ROWS = (128, 256, 512, 1024, 2048)


def build(shape, rows, variant, spec=None):
    """-> (jitted fn of (weights, flat, logits), argument shapes)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dnet_tpu.ops import kernel_select, moe

    held, routed, offset, d, f, k = SHAPES[shape]

    def fn(p, flat, logits):
        scores = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = lax.top_k(scores, k)
        top_idx = top_idx.astype(jnp.int32)
        if variant == "dense":
            return moe.swiglu_expert_closures(p, flat, scores, top_idx, top_w, None, offset)[1]()
        if variant == "grouped":
            return moe.swiglu_grouped_closure(p, flat, top_idx, top_w, offset)()
        # comparisons: the same closure with the tile, or the primitive, swapped
        mod, name, value = {
            "tile512": (moe, "GROUP_TILE_ROWS", 512),
            "xla_ragged_dot": (kernel_select, "kernel_backend", lambda: None),
        }[variant]
        real = getattr(mod, name)
        setattr(mod, name, value)
        try:
            return moe.swiglu_grouped_closure(p, flat, top_idx, top_w, offset)()
        finally:
            setattr(mod, name, real)

    def S(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, **({"sharding": spec} if spec else {}))

    args = (
        {"e_gate": S(held, d, f), "e_up": S(held, d, f), "e_down": S(held, f, d)},
        S(rows, d), S(rows, routed, dtype=jnp.float32),
    )
    return jax.jit(fn), args


def compile_only() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import re

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dnet_tpu.ops import kernel_select

    kernel_select.on_tpu = lambda: True  # the chip's branch, compiled for a described chip
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for shape, (held, routed, _off, d, f, k) in SHAPES.items():
        for variant in ("dense", "grouped"):
            fn, args = build(shape, 2048, variant, spec=one)
            c = fn.lower(*args).compile()
            text = c.as_text()
            calls = sorted(set(re.findall(r"%([A-Za-z0-9_.-]+) = [^\n]*custom-call\(", text)))
            print(json.dumps({
                "shape": shape, "rows": 2048, "variant": variant,
                "hlo_gflop": round(c.cost_analysis().get("flops", 0) / 1e9, 1),
                "routed_gflop": round(2048 * k * 3 * 2 * d * f * held / routed / 1e9, 1),
                "dense_gflop": round(2048 * held * 3 * 2 * d * f / 1e9, 1),
                "custom_calls": calls,
            }))
    return 0


def measure(out_path: Path, reps: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no chip: times come from the chip alone (--compile-only runs here)", file=sys.stderr)
        return 2
    table = []
    for shape, (held, routed, _off, d, f, _k) in SHAPES.items():
        keys = jax.random.split(jax.random.key(31), 5)
        p = {
            n: (jax.random.normal(kk, s, jnp.bfloat16) * 0.02)
            for n, kk, s in (("e_gate", keys[0], (held, d, f)), ("e_up", keys[1], (held, d, f)),
                             ("e_down", keys[2], (held, f, d)))
        }
        for rows in ROWS:
            flat = jax.random.normal(keys[3], (rows, d), jnp.bfloat16)
            logits = jax.random.normal(jax.random.fold_in(keys[4], rows), (rows, routed), jnp.float32)
            line = {"shape": shape, "rows": rows}
            outs = {}
            for variant in ("dense", "grouped", "xla_ragged_dot", "tile512"):
                try:
                    fn, _ = build(shape, rows, variant)
                    y = jax.block_until_ready(fn(p, flat, logits))
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        y = fn(p, flat, logits)
                    jax.block_until_ready(y)
                    line[variant + "_ms"] = round((time.perf_counter() - t0) / reps * 1e3, 4)
                    outs[variant] = np.asarray(y, np.float32)
                except Exception as exc:  # a variant the compiler refuses is a finding
                    line[variant + "_error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
            for variant, y in outs.items():
                if variant != "dense":
                    line[variant + "_max_abs_diff"] = float(np.abs(y - outs["dense"]).max())
            line["dense_out_abs_max"] = float(np.abs(outs["dense"]).max())
            print(json.dumps(line), flush=True)
            table.append(line)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(
        {"device": {"platform": dev.platform, "kind": dev.device_kind}, "reps": reps, "table": table},
        indent=1))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", type=Path, default=Path("chiprun_out/moe_crossover.json"))
    a = ap.parse_args()
    return compile_only() if a.compile_only else measure(a.out, a.reps)


if __name__ == "__main__":
    sys.exit(main())
