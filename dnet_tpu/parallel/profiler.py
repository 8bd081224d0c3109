"""Device profiling: per-chip capability microbenchmarks.

The analog of distilp's profiler (reference §2.7): measures achieved matmul
FLOP/s, HBM read bandwidth, and host->device transfer rate, plus memory
capacities — the solver's per-device cost-model inputs.  It runs in the
process that owns the chips: a chip belongs to one process at a time, so a
child could never reach them while the shard holds them.
"""

from __future__ import annotations

import time

import numpy as np


def profile_device_quick(device=None) -> dict:
    import jax
    import jax.numpy as jnp

    dev = device or jax.devices()[0]

    # matmul FLOPs (bf16, MXU-shaped)
    N = 2048
    a = jnp.ones((N, N), dtype=jnp.bfloat16)
    b = jnp.ones((N, N), dtype=jnp.bfloat16)
    f = jax.jit(lambda x, y: x @ y)
    f(a, b).block_until_ready()
    t0 = time.perf_counter()
    iters = 8
    out = a
    for _ in range(iters):
        out = f(out, b)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    flops = 2 * N**3 * iters / dt

    # HBM read bandwidth: sum over a large array
    M = 64 * 1024 * 1024 // 2  # 64MB of bf16
    big = jnp.ones((M,), dtype=jnp.bfloat16)
    g = jax.jit(lambda x: jnp.sum(x, dtype=jnp.float32))
    g(big).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        g(big).block_until_ready()
    dt = time.perf_counter() - t0
    hbm_bw = M * 2 * iters / dt

    # host -> device transfer rate
    host = np.ones((32 * 1024 * 1024,), dtype=np.uint8)  # 32MB
    jax.device_put(host, dev).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(4):
        jax.device_put(host, dev).block_until_ready()
    h2d = host.nbytes * 4 / (time.perf_counter() - t0)

    mem = {}
    try:
        stats = dev.memory_stats() or {}
        mem = {
            "hbm_bytes": stats.get("bytes_limit", 0),
            "hbm_in_use": stats.get("bytes_in_use", 0),
        }
    except Exception:
        pass

    import psutil

    return {
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "platform": dev.platform,
        "flops_bf16": flops,
        "hbm_bw": hbm_bw,
        "host_to_hbm_bw": h2d,
        "host_ram_bytes": psutil.virtual_memory().total,
        # chips this host can put behind ONE ring node (mesh-backed shard,
        # parallel/shard_mesh.py); the solver aggregates the slice's
        # FLOPs/HBM through DeviceInfo.chip_count
        "local_device_count": jax.local_device_count(),
        **mem,
    }
