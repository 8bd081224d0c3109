#!/usr/bin/env python3
"""Dense against grouped routed experts, by rows: the table behind
`ops/moe.py: RIDGE_ROWS` (PERF.md section 6, PR 31) and, at a decode
step's rows, behind `SPARSE_SHARE` (PR 47).

    chiprun -- python scripts/moe_crossover.py            # times, on the chip
    chiprun -- python scripts/moe_crossover.py --steps    # the rows up to the ridge alone
    JAX_PLATFORMS=cpu python scripts/moe_crossover.py --compile-only

Times the program's own closures (`swiglu_expert_closures`' dense() and
`swiglu_grouped_closure`) on seeded bf16 weights at the benchmark
configurations' expert shapes, ONE LAYER of a stack of as many layers as
the cell holds, read the way the models' layer scans read it (the dense
einsum from the layer's slice, the grouped kernel from the stack in place
by the layer's index), and, for comparison only, the same grouped closure
over XLA's own lowering of `lax.ragged_dot` and over other tiles.  Beside
each row: the share of the held experts the rows are expected to choose
(`expected_share`), the share they did choose, and what `auto` picks.
`--compile-only` compiles for a described v5e without a chip and prints
what the HLO says the grouped program does (FLOPs against the routed and
the dense count, the custom calls it holds); it measures nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

#: name -> (held experts, routed experts, offset, hidden, expert width,
#: top-k, layers of the cell's stack, the cell's step rows)
SHAPES = {
    "qwen3-30b-a3b": (128, 128, 0, 2048, 768, 8, 6, 32),
    "command-a-plus-share": (16, 128, 16, 4096, 4096, 8, 4, 16),
    "qwen3-next-share": (256, 512, 0, 2048, 512, 10, 4, 16),
    "mistral4-share": (16, 128, 0, 4096, 2048, 4, 6, 32),
}
STEP_ROWS = (1, 16, 32)
#: every row count at or under the ridge that is timed (`--steps`): the
#: steps' and the padding buckets of a prompt's last chunk up to RIDGE_ROWS
UNDER_RIDGE_ROWS = STEP_ROWS + (48, 64, 128, 256)
ROWS = UNDER_RIDGE_ROWS + (512, 1024, 2048)
#: the grouped closure with a constant, or the primitive, swapped
SWAPS = {
    "tile512": ("moe", "GROUP_TILE_ROWS", 512),
    "rows16": ("moe", "GROUP_TILE_ROWS", 16),
    "cols512": ("moe", "GROUP_TILE_COLS", 512),
    "cols2048": ("moe", "GROUP_TILE_COLS", 2048),
    "xla_ragged_dot": ("kernel_select", "kernel_backend", lambda: None),
}


def build(shape, rows, variant, spec=None):
    """-> (jitted fn of (stacked weights, layer, flat, logits), argument shapes)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dnet_tpu.ops import kernel_select, moe

    held, routed, offset, d, f, k, layers, _ = SHAPES[shape]

    def fn(w, layer, flat, logits):
        scores = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = lax.top_k(scores, k)
        top_idx = top_idx.astype(jnp.int32)
        p = {n: w[n][layer] for n in moe.EXPERT_KEYS}  # the scan's slice
        if variant == "dense":
            return moe.swiglu_expert_closures(p, flat, scores, top_idx, top_w, None, offset)[1]()
        p["e_stack"] = (w, layer)
        if variant == "grouped":
            return moe.swiglu_grouped_closure(p, flat, top_idx, top_w, offset)()
        mod, name, value = SWAPS[variant]
        mod = {"moe": moe, "kernel_select": kernel_select}[mod]
        real = getattr(mod, name)
        setattr(mod, name, value)
        try:
            return moe.swiglu_grouped_closure(p, flat, top_idx, top_w, offset)()
        finally:
            setattr(mod, name, real)

    def S(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, **({"sharding": spec} if spec else {}))

    args = (
        {"e_gate": S(layers, held, d, f), "e_up": S(layers, held, d, f),
         "e_down": S(layers, held, f, d)},
        S(dtype=jnp.int32), S(rows, d), S(rows, routed, dtype=jnp.float32),
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
    for shape, (held, routed, _off, d, f, k, _layers, step) in SHAPES.items():
        for rows in (step, 2048):
            for variant in ("dense", "grouped"):
                fn, args = build(shape, rows, variant, spec=one)
                c = fn.lower(*args).compile()
                text = c.as_text()
                calls = sorted(set(re.findall(r"%([A-Za-z0-9_.-]+) = [^\n]*custom-call\(", text)))
                print(json.dumps({
                    "shape": shape, "rows": rows, "variant": variant,
                    "hlo_gflop": round(c.cost_analysis().get("flops", 0) / 1e9, 1),
                    "routed_gflop": round(rows * k * 3 * 2 * d * f * held / routed / 1e9, 1),
                    "dense_gflop": round(rows * held * 3 * 2 * d * f / 1e9, 1),
                    "custom_calls": calls,
                }))
    return 0


def measure(out_path: Path, reps: int, rows_list, seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dnet_tpu.ops import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no chip: times come from the chip alone (--compile-only runs here)", file=sys.stderr)
        return 2
    table = []
    for shape, (held, routed, off, d, f, k, layers, _step) in SHAPES.items():
        keys = jax.random.split(jax.random.key(seed), 5)
        w = {  # every layer of the stack the same: one is read
            n: jnp.stack([jax.random.normal(kk, s, jnp.bfloat16) * 0.02] * layers)
            for n, kk, s in (("e_gate", keys[0], (held, d, f)), ("e_up", keys[1], (held, d, f)),
                             ("e_down", keys[2], (held, f, d)))
        }
        layer = jnp.int32(1)
        layer_gb = 3 * held * d * f * 2 / 1e9
        for rows in rows_list:
            flat = jax.random.normal(keys[3], (rows, d), jnp.bfloat16)
            logits = jax.random.normal(jax.random.fold_in(keys[4], rows), (rows, routed), jnp.float32)
            chosen = np.unique(np.asarray(jax.lax.top_k(logits, k)[1]))
            line = {
                "shape": shape, "rows": rows,
                "expected_share": round(moe.expected_share(rows, k, routed), 4),
                "chosen_share": round(float(((chosen >= off) & (chosen < off + held)).sum()) / held, 4),
                "auto": moe.resolve_moe_impl(
                    "auto", rows, 1, True, moe.expected_share(rows, k, routed)
                ),
                "layer_gb": round(layer_gb, 3),
            }
            outs = {}
            variants = ["dense", "grouped", "xla_ragged_dot"]
            if rows in STEP_ROWS:
                variants += ["rows16", "cols512", "cols2048"]
            elif rows > moe.RIDGE_ROWS:
                variants += ["tile512"]
            for variant in variants:
                try:
                    fn, _ = build(shape, rows, variant)
                    y = jax.block_until_ready(fn(w, layer, flat, logits))
                    batches = []  # the median of five: one stall of the host is not a row
                    for _ in range(5):
                        t0 = time.perf_counter()
                        for _ in range(max(1, reps // 5)):
                            y = fn(w, layer, flat, logits)
                        jax.block_until_ready(y)
                        batches.append((time.perf_counter() - t0) / max(1, reps // 5) * 1e3)
                    line[variant + "_ms"] = round(sorted(batches)[2], 4)
                    line[variant + "_ms_max"] = round(max(batches), 4)
                    outs[variant] = np.asarray(y, np.float32)
                except Exception as exc:  # a variant the compiler refuses is a finding
                    line[variant + "_error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
            for variant, y in outs.items():
                if variant != "dense":
                    line[variant + "_max_abs_diff"] = float(np.abs(y - outs["dense"]).max())
            line["dense_out_abs_max"] = float(np.abs(outs["dense"]).max())
            print(json.dumps(line), flush=True)
            table.append(line)
        del w
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(
        {"device": {"platform": dev.platform, "kind": dev.device_kind}, "reps": reps,
         "seed": seed, "table": table},
        indent=1))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--steps", action="store_true", help="the rows at or under the ridge alone")
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--out", type=Path, default=Path("chiprun_out/moe_crossover.json"))
    a = ap.parse_args()
    if a.compile_only:
        return compile_only()
    return measure(a.out, a.reps, UNDER_RIDGE_ROWS if a.steps else ROWS, a.seed)


if __name__ == "__main__":
    sys.exit(main())
