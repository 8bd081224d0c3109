"""Ring attention / sequence-parallel attention over a mesh axis.

The reference lists ">128K context" as unshipped roadmap (README.md:51,
SURVEY.md §2.8); on TPU this is a first-class design axis: shard the KV
sequence over the `sp` mesh axis and

- prefill: rotate KV blocks around the ring with `lax.ppermute`, folding
  each visiting block into an online-softmax accumulator (flash-attention
  combine) — O(S/sp) memory per chip, full-S attention, ICI-bandwidth hops
  (Ring Attention, Liu et al. 2023);
- decode: the single query is replicated; every rank computes a partial
  (m, l, o) against its local KV block and one log-sum-exp combine
  (pmax + psum) merges them — distributed flash-decoding.

Both are numerically exact vs dense attention (tests compare against
ops.attention.attend).  GQA layout matches attend(): q [B,T,H,Hd],
k/v [B,S_local,KVH,Hd].
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


NEG = -1e30


def _block_scores(q5, k, mask):
    """q5: [B,KVH,G,Tq,Hd] scaled f32; k: [B,S,KVH,Hd] -> [B,KVH,G,Tq,S]."""
    scores = jnp.einsum("bkgtd,bskd->bkgts", q5, k.astype(jnp.float32))
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, NEG)
    return scores


def _fold_block(q5, k, v, mask, m, l, o):
    """Online-softmax fold of one KV block into the (m, l, o) accumulator."""
    scores = _block_scores(q5, k, mask)  # [B,KVH,G,Tq,S]
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bkgts,bskd->bkgtd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def ring_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Full ring attention inside shard_map: q is THIS rank's query block,
    k/v THIS rank's KV block; blocks rotate `sp` times around the axis.

    q_positions [Tq], kv_positions [S_local]: absolute token positions
    (rotate with the KV so causal masking stays correct).
    `scale` overrides the Hd**-0.5 softmax scale (MLA YaRN mscale).
    Returns [B, Tq, H, Hd] in q.dtype.
    """
    SP = lax.psum(1, axis_name)
    B, Tq, H, Hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    q5 = (q.reshape(B, Tq, KVH, G, Hd).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
          * (Hd**-0.5 if scale is None else scale))  # [B,KVH,G,Tq,Hd]

    # accumulators become device-varying over the axis once folded with the
    # rank-local KV; mark them so the fori carry types line up
    m = lax.pcast(jnp.full((B, KVH, G, Tq), NEG, dtype=jnp.float32), axis_name, to="varying")
    l = lax.pcast(jnp.zeros((B, KVH, G, Tq), dtype=jnp.float32), axis_name, to="varying")
    o = lax.pcast(jnp.zeros((B, KVH, G, Tq, Hd), dtype=jnp.float32), axis_name, to="varying")

    perm = [(r, (r + 1) % SP) for r in range(SP)]

    def body(_, carry):
        k, v, kv_pos, m, l, o = carry
        mask = (
            kv_pos[None, :] <= q_positions[:, None] if causal else None
        )  # [Tq, S_local]
        m, l, o = _fold_block(q5, k, v, mask, m, l, o)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        kv_pos = lax.ppermute(kv_pos, axis_name, perm)
        return k, v, kv_pos, m, l, o

    k, v, kv_pos, m, l, o = lax.fori_loop(
        0, SP, body, (k, v, kv_positions, m, l, o)
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Hd).astype(q.dtype)


def sp_decode_attend(
    q: jnp.ndarray,
    k_local: jnp.ndarray,
    v_local: jnp.ndarray,
    valid_local: jnp.ndarray,
    axis_name: str,
    sinks: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Distributed flash-decoding: q [B,T,H,Hd] replicated over the axis,
    k/v [B,S_local,KVH,Hd] this rank's KV shard, valid_local [T, S_local]
    boolean attendability mask (causal + written-slot validity).

    One cross-device LSE combine (pmax + 2x psum) merges the partials.
    sinks [H]: GPT-OSS attention-sink logits — a virtual key absorbing
    probability mass, folded into the global softmax denominator exactly
    once (outside the psum).  `scale` overrides the Hd**-0.5 softmax scale
    (MLA YaRN mscale compensation must survive the sp path).
    """
    B, Tq, H, Hd = q.shape
    KVH = k_local.shape[2]
    G = H // KVH
    q5 = (q.reshape(B, Tq, KVH, G, Hd).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
          * (Hd**-0.5 if scale is None else scale))

    scores = _block_scores(q5, k_local, valid_local)
    m_loc = jnp.max(scores, axis=-1)  # [B,KVH,G,Tq]
    m_glob = lax.pmax(m_loc, axis_name)
    if sinks is not None:
        sink = sinks.astype(jnp.float32).reshape(KVH, G)[None, :, :, None]
        m_glob = jnp.maximum(m_glob, sink)
    p = jnp.exp(scores - m_glob[..., None])
    l_loc = jnp.sum(p, axis=-1)
    o_loc = jnp.einsum("bkgts,bskd->bkgtd", p, v_local.astype(jnp.float32))
    l_glob = lax.psum(l_loc, axis_name)
    o_glob = lax.psum(o_loc, axis_name)
    if sinks is not None:
        l_glob = l_glob + jnp.exp(jnp.broadcast_to(sink, m_glob.shape) - m_glob)
    out = o_glob / jnp.maximum(l_glob[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, v_local.shape[-1]).astype(q.dtype)
