"""Operations and bytes of the two power-retention kernels, from their
shapes: what a roofline share divides the trace's kernel time into
(on-chip-measurement guide, section 4).  The twin of `kernel_costs.py` for
the `state` kind of layer.

As there, no reader takes a peak (`harness/readers.py: READERS` is closed,
`harness/peaks.json` holds none), so no per-layer metric reads these: the
builder computes each kernel's share by hand from a traced run and writes
it into PERF.md section 5 with the peak's source.  The counts are of the
work the ALGORITHM needs at degree 2, with the state at its published
size, `D = head_dim (head_dim + 1) / 2` rows (8256 at 128): not the 65 x
128 = 8320 rows the kernels' diagonal layout holds, not the six passes a
float32 matmul takes on the MXU, not the idle lanes a step copies through.
A share worked out with them is therefore a little under what the kernel
achieves on the bytes it really moves, never over.

Conventions: one multiply-add is 2 operations.  The decode step is bound
by memory: each active lane's state (S and z, float32) is read once and
written once a KV head and layer.  The prefill chunk is bound by compute:
a query-key pair inside a sub-chunk costs 2 * head_dim operations for the
score and 2 * head_dim for the weighted value, 4 * head_dim a pair and
query head; a token's read of the incoming state is a [D] x [D, head_dim]
product a query head, and its key's entry into the state the same a KV
head.
"""

from __future__ import annotations

SUB_CHUNK = 128  # tokens of one quadratic block (dnet_tpu/ops/retention.py)


def state_rows(head_dim: int) -> int:
    """D: the distinct entries of the symmetric square of a head."""
    return head_dim * (head_dim + 1) // 2


def state_entry_bytes(kv_heads: int, head_dim: int) -> int:
    """One lane's state in one layer: S [D, head_dim] and z [D], float32."""
    return kv_heads * state_rows(head_dim) * (head_dim + 1) * 4


def retention_step_cost(lanes: int, kv_heads: int, q_heads: int, head_dim: int) -> dict:
    """One decode step of `lanes` active lanes in one layer."""
    D = state_rows(head_dim)
    state = lanes * state_entry_bytes(kv_heads, head_dim)
    io = lanes * (2 * q_heads + 2 * kv_heads) * head_dim * 2  # q, o, k, v in bf16
    # the update (decay, outer product: 3 a state entry) and each query
    # head's read of S and z
    flops = lanes * (3 * kv_heads + 2 * q_heads) * D * (head_dim + 1)
    return {"bytes": 2 * state + io, "flops": flops}


def chunk_pairs(tokens: int, sub_chunk: int = SUB_CHUNK) -> int:
    """Query-key pairs the chunked form must form: the triangle inside
    each sub-chunk (everything older arrives through the state)."""
    whole, rest = divmod(tokens, sub_chunk)
    return whole * sub_chunk * (sub_chunk + 1) // 2 + rest * (rest + 1) // 2


def retention_chunk_cost(tokens: int, kv_heads: int, q_heads: int, head_dim: int,
                         sub_chunk: int = SUB_CHUNK) -> dict:
    """One prefill chunk of `tokens` real tokens of one sequence in one
    layer."""
    D = state_rows(head_dim)
    intra = 4 * head_dim * q_heads * chunk_pairs(tokens, sub_chunk)
    read = 2 * D * (head_dim + 1) * q_heads * tokens
    write = 2 * D * (head_dim + 1) * kv_heads * tokens
    state = 2 * state_entry_bytes(kv_heads, head_dim)  # in once, out once
    io = tokens * (2 * q_heads + 2 * kv_heads) * head_dim * 2
    return {"flops": intra + read + write, "bytes": state + io}
