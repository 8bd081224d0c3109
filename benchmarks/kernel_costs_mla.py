"""Operations and bytes of latent attention's two device paths, from their
shapes: what a roofline share divides the trace's kernel time into
(on-chip-measurement guide, section 4).  The twin of `kernel_costs_gdn.py`
for a model whose cache entry is ONE latent row a token
(dnet_tpu/models/deepseek_v2.py: deepseek_v2, mistral4).

As there, no reader takes a peak (`harness/readers.py: READERS` is closed,
`harness/peaks.json` holds none), so no per-layer metric reads these: the
builder computes each kernel's share by hand from a traced run and writes
it into PERF.md section 5 with the peak's source.  The counts are of the
work the ALGORITHM needs: the entry as `[c | k_pe]` (not the zero lanes the
pool pads it to a multiple of 128 with, which the kernel does read), each
live entry read once a layer a step (not the clamped repeats of dead table
entries, which cost a grid step and no copy), the causal triangle of a
prefill chunk (not the whole tiles the kernel folds along the diagonal).  A
share worked out with them is therefore under what the kernel achieves on
the work it really does, never over.

Conventions: one multiply-add is 2 operations.  The decode kernel
(`paged_attend_latent`) is bound by memory: every live token's entry is
read once a layer, and against it each query head takes one dot product
over the entry's width (the scores) and one over its rank (the values).
The prefill chunk expands the row's latents `[0, p + T)` to per-head keys
and values (a matmul, bound by compute and by the write of what it makes)
and runs `flash_prefill` over them (`kernel_costs.py` has that kernel's
function; `prefill_attention_cost` here is the same count at this model's
asymmetric head).
"""

from __future__ import annotations


def latent_entry_bytes(rank: int, rope_dim: int, itemsize: int = 2) -> int:
    """One token's entry in one layer: the normalised latent and the
    rotated shared key (mistral4: (256 + 64) x 2 = 640)."""
    return (rank + rope_dim) * itemsize


def latent_decode_cost(live_tokens: int, lanes: int, heads: int, rank: int,
                       rope_dim: int, itemsize: int = 2) -> dict:
    """One decode step of `paged_attend_latent` in one layer: `live_tokens`
    entries over the `lanes` active lanes."""
    width = rank + rope_dim
    entries = live_tokens * latent_entry_bytes(rank, rope_dim, itemsize)
    # q in, o out a head a lane, and the current token's entry
    io = lanes * (heads * (width + rank) + width) * itemsize
    # scores over the width, values over the rank, a head a live token
    flops = 2 * heads * (width + rank) * live_tokens
    return {"bytes": entries + io, "flops": flops}


def expansion_cost(tokens: int, heads: int, rank: int, nope_dim: int, rope_dim: int,
                   v_dim: int, itemsize: int = 2) -> dict:
    """Per-head keys and values of `tokens` latents in one layer (a chunk
    at position p expands p + T): c x W_kvb, and the shared key copied to
    every head."""
    flops = 2 * tokens * rank * heads * (nope_dim + v_dim)
    read = tokens * (rank + rope_dim) * itemsize + rank * heads * (nope_dim + v_dim) * itemsize
    wrote = tokens * heads * (nope_dim + rope_dim + v_dim) * itemsize
    return {"flops": flops, "bytes": read + wrote}


def prefill_attention_cost(pos: int, tokens: int, heads: int, qk_dim: int, v_dim: int,
                           itemsize: int = 2) -> dict:
    """`flash_prefill` for one chunk of `tokens` rows at position `pos` in
    one layer, over EXPANDED keys and values: row i attends pos + i + 1
    keys."""
    pairs = tokens * pos + tokens * (tokens + 1) // 2
    flops = 2 * heads * (qk_dim + v_dim) * pairs
    keys = (pos + tokens) * heads * (qk_dim + v_dim) * itemsize
    io = tokens * heads * (qk_dim + v_dim) * itemsize
    return {"flops": flops, "bytes": keys + io}


def absorbed_prefill_cost(pos: int, tokens: int, heads: int, rank: int, rope_dim: int) -> dict:
    """What the chunk's attention would cost ABSORBED (not built: the
    count that decided): scores over rank + rope, values over rank."""
    pairs = tokens * pos + tokens * (tokens + 1) // 2
    return {"flops": 2 * heads * (2 * rank + rope_dim) * pairs}
