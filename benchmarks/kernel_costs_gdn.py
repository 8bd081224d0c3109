"""Operations and bytes of the two gated-delta-rule kernels, from their
shapes: what a roofline share divides the trace's kernel time into
(on-chip-measurement guide, section 4).  The twin of
`kernel_costs_retention.py` for the delta rule's `state` layers.

As there, no reader takes a peak (`harness/readers.py: READERS` is closed,
`harness/peaks.json` holds none), so no per-layer metric reads these: the
builder computes each kernel's share by hand from a traced run and writes
it into PERF.md section 5 with the peak's source.  The counts are of the
work the ALGORITHM needs: not the six passes a float32 matmul takes on the
MXU, not the idle lanes a step copies through, not the padding of a ragged
last chunk, and the solve counted as forward substitution (C^2 / 2 rows of
the right-hand side), not as the six-factor product the kernel multiplies
out.  A share worked out with them is therefore under what the kernel
achieves on the work it really does, never over.

Conventions: one multiply-add is 2 operations.  The decode step is bound
by memory: each active lane's state `S [Dk, Dv]` float32 is read once and
written once a value head and layer.  The prefill chunk is bound by
compute and, between chunks, by latency: a chunk of C tokens costs a value
head the two [C, C] products over Dk (K K^T, Q K^T), the solve against
[C, Dv + Dk], the [C, C] x [C, Dv] product, and three [C, Dk] x [Dk, Dv]
products against the state (the corrected values' read, the queries' read,
the state's update).

The full layer's two attention kernels at head size 256 are
`kernel_costs.py`'s functions at new arguments.
"""

from __future__ import annotations

CHUNK = 64  # tokens of one chunk (dnet_tpu/ops/gated_delta.py)


def state_entry_bytes(v_heads: int, k_dim: int, v_dim: int) -> int:
    """One lane's S in one layer, float32 (the conv tail is not the
    kernel's: `jax.numpy` moves it)."""
    return v_heads * k_dim * v_dim * 4


def gdn_step_cost(lanes: int, k_heads: int, v_heads: int, k_dim: int, v_dim: int) -> dict:
    """One decode step of `lanes` active lanes in one layer."""
    state = lanes * state_entry_bytes(v_heads, k_dim, v_dim)
    # q, k a key head and v, o a value head in bf16; g, beta float32
    io = lanes * (2 * k_heads * k_dim * 2 + 2 * v_heads * v_dim * 2 + 2 * v_heads * 4)
    # decay (1), S^T k (2), the rank-one correction (2), S^T q (2) an entry
    flops = lanes * v_heads * k_dim * v_dim * 7
    return {"bytes": 2 * state + io, "flops": flops}


def gdn_chunk_cost(tokens: int, k_heads: int, v_heads: int, k_dim: int, v_dim: int,
                   chunk: int = CHUNK) -> dict:
    """One prefill chunk of `tokens` real tokens of one sequence in one
    layer: whole chunks of `chunk` and a ragged last one."""
    flops = 0
    whole, rest = divmod(tokens, chunk)
    for c, n in ((chunk, whole), (rest, 1 if rest else 0)):
        pairs = c * (c + 1) // 2
        intra = 2 * 2 * k_dim * pairs  # K K^T and Q K^T, the triangle
        solve = 2 * pairs * (v_dim + k_dim)  # forward substitution
        mix = 2 * pairs * v_dim  # (Q K^T) x the corrected values
        state = 3 * 2 * c * k_dim * v_dim  # W S, Q S, K^T V_new
        flops += n * v_heads * (intra + solve + mix + state)
    state_bytes = 2 * state_entry_bytes(v_heads, k_dim, v_dim)  # in once, out once
    io = tokens * (2 * k_heads * k_dim * 2 + 2 * v_heads * v_dim * 2 + 2 * v_heads * 4)
    return {"flops": flops, "bytes": state_bytes + io}
