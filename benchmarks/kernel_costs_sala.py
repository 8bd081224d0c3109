"""Operations and bytes of MiniCPM-SALA's five kernels, from their shapes:
what a roofline share divides the trace's kernel time into
(on-chip-measurement guide, section 4).  The twin of `kernel_costs_gdn.py`
for lightning attention's `state` layers and the block-sparse `full` ones.

As there, no reader takes a peak (`harness/readers.py: READERS` is closed),
so no per-layer metric reads these: the builder computes each kernel's share
by hand from a traced run and writes it into PERF.md section 5 with the
peak's source.  The counts are of the work the ALGORITHM needs: the blocks a
query CHOSE, not the blocks its lane holds and not the tiles a q tile's
queries chose between them; not the passes a float32 matmul takes on the
MXU, not the idle lanes a step copies through, not padding.  A share worked
out with them is therefore under what the kernel achieves on the work it
really does, never over.

Conventions: one multiply-add is 2 operations; bf16 activations and cache,
float32 state.  `ctx` is a query's context n = position + 1.
"""

from __future__ import annotations

SUB_CHUNK = 128  # tokens of one sub-chunk (dnet_tpu/ops/lightning.py)
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
          "dense_len": 8192}


def state_entry_bytes(heads: int, dim: int) -> int:
    """One lane's S in one lightning layer, float32."""
    return heads * dim * dim * 4


def lightning_step_cost(lanes: int, heads: int, dim: int) -> dict:
    """One decode step of `lanes` active lanes in one layer: bound by
    memory, an entry read once and written once."""
    state = lanes * state_entry_bytes(heads, dim)
    io = lanes * heads * dim * (3 * 2 + 4)  # q, k, v bf16 in; o float32 out
    # decay (1), the outer product's multiply-add (2), S^T q (2) an entry
    return {"bytes": 2 * state + io, "flops": lanes * heads * dim * dim * 5}


def lightning_chunk_cost(tokens: int, heads: int, dim: int, sub: int = SUB_CHUNK) -> dict:
    """One prefill chunk of `tokens` real tokens of one sequence in one
    layer: whole sub-chunks and a ragged last one."""
    flops = 0
    whole, rest = divmod(tokens, sub)
    for c, n in ((sub, whole), (rest, 1 if rest else 0)):
        pairs = c * (c + 1) // 2
        intra = 2 * 2 * dim * pairs  # Q K^T and A V, the triangle
        state = 2 * 2 * c * dim * dim  # Q S and K^T V
        flops += n * heads * (intra + state)
    io = tokens * heads * dim * (3 * 2 + 4)
    return {"flops": flops, "bytes": 2 * state_entry_bytes(heads, dim) + io}


def spans_complete(ctx: int, sp: dict = SPARSE) -> int:
    """Pooled keys the query of context `ctx` scores."""
    return max(0, (ctx - sp["kernel_size"]) // sp["kernel_stride"] + 1)


def blocks_attended(ctx: int, sp: dict = SPARSE) -> int:
    held = -(-ctx // sp["block_size"])
    return held if ctx <= sp["dense_len"] else sp["topk"]


def sparse_index_cost(ctxs, q_heads: int, kv_heads: int, dim: int, shared: bool,
                      sp: dict = SPARSE) -> dict:
    """The index's score kernel for queries of contexts `ctxs` in one layer:
    a query head's scores over the complete spans (2 dim a pair) and its
    softmax (about 5 a score).  `shared`: the queries are one sequence's (a
    prefill chunk: the pooled keys are read once, the longest context's);
    else a lane each (a decode step: each reads its own)."""
    rows = [spans_complete(n, sp) for n in ctxs]
    flops = sum(q_heads * m * (2 * dim + 5) for m in rows)
    read = (max(rows, default=0) if shared else sum(rows)) * kv_heads * dim * 2
    io = len(rows) * q_heads * dim * 2 + sum(rows) * kv_heads * 4  # q in, r out
    return {"flops": flops, "bytes": read + io}


def paged_attend_sparse_cost(ctxs, q_heads: int, kv_heads: int, dim: int,
                             sp: dict = SPARSE) -> dict:
    """One decode step's read in one layer, a lane a context: each KV head
    reads the keys and values of the blocks it chose, the last one up to
    the query's own token."""
    keys = [min(blocks_attended(n, sp) * sp["block_size"], n) for n in ctxs]
    kv = sum(keys) * kv_heads * dim * 2 * 2
    io = len(keys) * q_heads * dim * 2 * 2
    return {"flops": sum(keys) * q_heads * dim * 2 * 2, "bytes": kv + io}


def flash_prefill_sparse_cost(pos: int, tokens: int, q_heads: int, kv_heads: int,
                              dim: int, sp: dict = SPARSE) -> dict:
    """One prefill chunk's read in one layer: each query's scores and values
    over the keys of ITS chosen blocks (bound by compute).  Bytes: the
    least a chunk can read, q and o and, a KV head, the chunk's own keys
    and values plus one query's other chosen blocks (queries that choose
    apart read more: up to everything before them)."""
    keys = [min(blocks_attended(n, sp) * sp["block_size"], n)
            for n in range(pos + 1, pos + tokens + 1)]
    least = min(pos + tokens, tokens + sp["topk"] * sp["block_size"])
    kv = least * kv_heads * dim * 2 * 2
    io = tokens * q_heads * dim * 2 * 2
    return {"flops": sum(keys) * q_heads * dim * 2 * 2, "bytes": kv + io}
