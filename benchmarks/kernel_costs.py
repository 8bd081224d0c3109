"""Operations and bytes of the attention kernels a window layer and a full
layer run, from their shapes: what a roofline share divides the trace's
kernel time into (on-chip-measurement guide, section 4).

No reader in `harness/readers.py` takes a peak yet (READERS is a closed
table, `harness/peaks.json` holds none), so no per-layer metric reads
these: the builder computes each kernel's share by hand from a traced run
and writes it into PERF.md section 5 with the peak's source.  The counts
are of the work the algorithm NEEDS (keys inside the causal triangle and
the window), not of the tiles a kernel happens to touch.

Conventions: one multiply-add is 2 operations; a query-key pair costs
2 * head_dim operations for the score and 2 * head_dim for the weighted
value, so 4 * head_dim a pair and head.  Bytes are the K and V rows read
once per kv head and step (the G query heads of a kv head share the read:
that reuse is the point of folding the group), plus q in and o out.
"""

from __future__ import annotations


def attended_keys(pos: int, window: int = 0) -> int:
    """Keys the token at position `pos` attends, itself included: all
    pos + 1 of them, or the last `window`."""
    n = pos + 1
    return min(n, window) if window else n


def prefill_pairs(start: int, tokens: int, window: int = 0) -> int:
    """Query-key pairs of one prefill chunk: queries at positions
    start .. start + tokens - 1 of one sequence."""
    return sum(attended_keys(p, window) for p in range(start, start + tokens))


def prompt_pairs(tokens: int, window: int = 0) -> int:
    """Closed form of prefill_pairs(0, tokens, window): the triangle, less
    the part of it behind the window."""
    full = tokens * (tokens + 1) // 2
    if not window or tokens <= window:
        return full
    behind = tokens - window
    return full - behind * (behind + 1) // 2


def attention_ops(pairs: int, heads: int, head_dim: int) -> int:
    return 4 * head_dim * heads * pairs


def prefill_bytes(start: int, tokens: int, heads: int, kv_heads: int, head_dim: int,
                  window: int = 0, q_tile: int = 128, itemsize: int = 2) -> int:
    """What a flash prefill of one chunk must move: each q tile reads the
    K and V rows its rows attend (from the first key the tile's first row
    reaches to the last key its last row does), once per kv head; q in, o
    out."""
    rows = 0
    for t0 in range(start, start + tokens, q_tile):
        t1 = min(t0 + q_tile, start + tokens) - 1
        lo = max(t0 - window + 1, 0) if window else 0
        rows += t1 - lo + 1
    kv = 2 * rows * kv_heads * head_dim * itemsize
    qo = 2 * tokens * heads * head_dim * itemsize
    return kv + qo


def decode_bytes(pos: int, heads: int, kv_heads: int, head_dim: int,
                 window: int = 0, itemsize: int = 2) -> int:
    """One decode step of one sequence: every attended key's K and V row
    once per kv head, q in, o out."""
    kv = 2 * attended_keys(pos, window) * kv_heads * head_dim * itemsize
    return kv + 2 * heads * head_dim * itemsize


def roofline_share(ops: int, nbytes: int, seconds: float, peak_flops: float,
                   peak_bytes_per_s: float) -> dict:
    """The least time the chip could take over the time it took, and which
    peak bounds it."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bytes_per_s
    return {
        "share": max(t_ops, t_bytes) / seconds,
        "bound": "compute" if t_ops >= t_bytes else "memory",
        "achieved_flops": ops / seconds,
        "achieved_bytes_per_s": nbytes / seconds,
    }
