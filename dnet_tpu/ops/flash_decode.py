"""Flash DECODE attention: split-K Pallas kernel for T=1 against a long cache.

The prefill kernel (ops/flash_attention.py) covers the big-T pass; decode is
the other half: every generated token attends ONE query
row against the whole preallocated cache, and at 128K context that read IS
the per-token cost.  The dense path (`ops.attention.attend`) pays it badly
three ways: it upcasts the full [S, Hd] K and V to f32, materializes [H, S]
scores + probs through HBM, and — because the cache is preallocated at
max_seq — reads ALL max_seq slots even when only `pos+1` are live.

This kernel streams the cache tile-by-tile with the online-softmax
(m, l, acc) accumulator in VMEM scratch (split-K over the KV axis: the TPU
grid runs KV tiles sequentially with a cross-tile merge, the sequential
sibling of GPU split-K flash-decoding), and uses SCALAR-PREFETCHED block
index maps to clamp dead tiles to the last live tile — Pallas elides the
HBM->VMEM copy when the block index repeats, so a request at pos=2K in a
128K cache reads ~2K slots, not 128K.

Variants:
  - GQA / MLA: all G query heads of a KV group fold per tile; V's head dim
    may differ from K's (deepseek MLA).
  - sinks: gpt_oss per-head sink logits folded once into the denominator.
  - rotating=True: the gpt_oss sliding-window ring buffer — per-slot
    absolute positions are reconstructed in-kernel (slot s holds the most
    recent position <= pos congruent to s mod W) and masked to the window.
  - with_lse: emit UNNORMALIZED (acc, m, l) partials for a cross-rank
    log-sum-exp combine — `sp_flash_decode_attend` composes the kernel with
    the sequence-parallel decode path (ops/ring_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dnet_tpu.ops.flash_attention import (
    _pick_tile,
    _under_manual_mesh,
    _vma_union,
)
from dnet_tpu.ops.kernel_select import SELECTIONS, kernel_backend

NEG_INF = -1e30


def _decode_kernel(scal_ref, q_ref, k_ref, v_ref, *rest,
                   bk: int, scale: float, n_s: int, window: int,
                   rotating: bool, with_lse: bool, quantized: bool,
                   KVH: int, Hd: int, Vd: int):
    """One (batch, kv-tile) fold of the online softmax, every kv head.

    scal_ref SMEM [2] = (pos, offset): pos is the query's absolute
    position, offset the absolute position of this cache shard's slot 0
    (nonzero only under sp).  Mosaic tiles a block's last two dims, so the
    cache tile arrives with heads merged into the lane dim — k_ref
    [1, bk, KVH*Hd], v_ref [1, bk, KVH*Vd] — and a kv head is a static lane
    slice; q_ref [1, KVH, G, Hd] holds each head's whole GQA group, so one
    cache tile read is amortized over all G query heads sharing it.

    quantized: the cache tiles arrive as int8 with per-(slot, head) f32
    scales (ks_ref/vs_ref [1, bk, KVH]) — dequantization happens here in
    VMEM, so the HBM traffic is the quantized bytes, not a full-cache f32
    materialization (the read_kv dense path's cost)."""
    import jax.experimental.pallas as pl

    if quantized:
        ks_ref, vs_ref, *rest = rest
    if with_lse:
        sink_ref, o_ref, m_out, l_out, m_ref, l_ref, acc_ref = rest
    else:
        sink_ref, o_ref, m_ref, l_ref, acc_ref = rest

    def tile(ref, scale_ref, kh, D):
        """Head kh's [bk, D] f32 slice of a (possibly int8) cache tile."""
        t = ref[0, :, kh * D:(kh + 1) * D].astype(jnp.float32)
        if quantized:
            t = t * scale_ref[0, :, kh:kh + 1]
        return t

    s = pl.program_id(1)
    pos = scal_ref[0]
    offset = scal_ref[1]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    W_ring = n_s * bk  # ring-buffer modulus = the cache's slot count
    if rotating:
        live = jnp.minimum(pos + 1, jnp.int32(W_ring))  # live ring slots
    else:
        live = pos + 1 - offset  # local slots this rank may attend

    @pl.when(s * bk < live)
    def _fold():
        slot = s * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if rotating:
            # slot holds the most recent absolute position <= pos congruent
            # to it mod the ring size (written BEFORE attending, so the
            # current token's own slot maps to pos itself); the attention
            # window then masks within the live ring.  (pos - slot) mod W
            # from the scalar pos mod W: the VPU has no vector remainder.
            back = lax.rem(pos, jnp.int32(W_ring)) - slot
            k_abs = pos - jnp.where(back < 0, back + W_ring, back)
            valid = (k_abs >= 0) & (k_abs > pos - jnp.int32(window))
        else:
            valid = offset + slot <= pos
        for kh in range(KVH):
            q = q_ref[0, kh].astype(jnp.float32) * scale  # [G, Hd]
            k = tile(k_ref, ks_ref if quantized else None, kh, Hd)
            scores = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, bk]
            scores = jnp.where(valid, scores, NEG_INF)
            m_prev = m_ref[kh]  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[kh] = l_ref[kh] * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p, tile(v_ref, vs_ref if quantized else None, kh, Vd),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, Vd]
            acc_ref[kh] = acc_ref[kh] * corr + pv
            m_ref[kh] = m_new

    @pl.when(s == n_s - 1)
    def _emit():
        if with_lse:
            # unnormalized partials: the sp combine folds ranks (and the
            # sink, exactly once) at the global level
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)
            m_out[0] = m_ref[...]
            l_out[0] = l_ref[...]
        else:
            sink = sink_ref[...]  # [KVH, G, 1]
            m_fin = jnp.maximum(m_ref[...], sink)
            corr = jnp.exp(m_ref[...] - m_fin)
            l_fin = l_ref[...] * corr + jnp.exp(sink - m_fin)
            o_ref[0] = (
                acc_ref[...] * corr / jnp.maximum(l_fin, 1e-30)
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("G", "scale", "bk", "window", "rotating", "with_lse",
                     "interpret", "vma", "scal_varying"),
)
def _decode_pallas(q, k, v, scalars, sinks, *, G: int, scale: float, bk: int,
                   window: int, rotating: bool, with_lse: bool,
                   interpret: bool, vma: tuple = (), k_scale=None,
                   v_scale=None, scal_varying: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, Hd = q.shape
    S = k.shape[1]
    Vd = v.shape[-1]
    KVH = H // G
    n_s = S // bk
    quantized = k_scale is not None

    def live_tile(scal):
        """Last tile holding any live slot (block indices clamp here so the
        pipeline never fetches dead tiles — repeated indices elide copies)."""
        if rotating:
            live = jnp.minimum(scal[0] + 1, jnp.int32(S))
        else:
            live = scal[0] + 1 - scal[1]
        return jnp.clip((live - 1) // bk, 0, n_s - 1)

    # sp: the scalars carry a device-varying offset (axis_index), and vma
    # tracking rejects data-dependent block index maps on varying values —
    # drop the dead-tile clamp (each rank's S/sp shard is mostly live under
    # long context) and read the scalars from SMEM instead.  With INVARIANT
    # scalars (tp/mesh-shard decode) the prefetch grid keeps the clamp and
    # just declares the outputs' vma.
    prefetch = not (vma and scal_varying)
    if not prefetch and quantized:
        raise ValueError("sp flash decode reads a dequantized shard")

    def kv_map(b, s, *scal):
        return (b, jnp.minimum(s, live_tile(scal[0])) if prefetch else s, 0)

    def whole4(b, s, *scal):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, KVH, G, Hd), whole4),
        pl.BlockSpec((1, bk, KVH * Hd), kv_map),
        pl.BlockSpec((1, bk, KVH * Vd), kv_map),
    ]
    operands = [
        q.reshape(B, KVH, G, Hd), k.reshape(B, S, KVH * Hd),
        v.reshape(B, S, KVH * Vd),
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk, KVH), kv_map)] * 2
        operands += [k_scale.reshape(B, S, KVH), v_scale.reshape(B, S, KVH)]
    in_specs.append(pl.BlockSpec((KVH, G, 1), lambda b, s, *scal: (0, 0, 0)))
    operands.append(sinks.reshape(KVH, G, 1))
    # inside shard_map the partials are device-varying over the sp axis;
    # check_vma demands the output declare it (vma=() outside shard_map)
    kw = {"vma": frozenset(vma)}
    out_specs = pl.BlockSpec((1, KVH, G, Vd), whole4)
    out_shape = jax.ShapeDtypeStruct((B, KVH, G, Vd), q.dtype, **kw)
    if with_lse:
        out_specs = (out_specs,) + (pl.BlockSpec((1, KVH, G, 1), whole4),) * 2
        out_shape = (
            jax.ShapeDtypeStruct((B, KVH, G, Vd), jnp.float32, **kw),
            jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32, **kw),
            jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32, **kw),
        )
    scratch = [
        pltpu.VMEM((KVH, G, 1), jnp.float32),
        pltpu.VMEM((KVH, G, 1), jnp.float32),
        pltpu.VMEM((KVH, G, Vd), jnp.float32),
    ]
    kernel = functools.partial(
        _decode_kernel, bk=bk, scale=scale, n_s=n_s, window=window,
        rotating=rotating, with_lse=with_lse, quantized=quantized, KVH=KVH,
        Hd=Hd, Vd=Vd,
    )
    if prefetch:
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, n_s), in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch,
            ),
            out_shape=out_shape, interpret=interpret, name="flash_decode",
        )(scalars, *operands)
    else:
        out = pl.pallas_call(
            kernel, grid=(B, n_s),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
            name="flash_decode_sp",
        )(scalars, *operands)
    if with_lse:
        o, m, l = out
        return o.reshape(B, T, H, Vd), m[..., 0], l[..., 0]
    return out.reshape(B, T, H, Vd)


def _decode_emulate(q, k, v, scalars, sinks, *, G: int, scale: float,
                    bk: int, window: int, rotating: bool, with_lse: bool,
                    k_scale=None, v_scale=None):
    """Plain-jnp twin of _decode_kernel: the SAME tile-by-tile online-
    softmax fold (f32, same operation order, same dead-tile gating), for
    executed coverage where pallas cannot run — interpret mode inside
    shard_map discharges the kernel to a jaxpr whose constants stay
    vma-invariant (r4 diagnosis).  CPU mesh tests, dryruns, and the sp
    composition's interpret path run this emulation; real TPU runs the
    kernel.  Dead tiles are gated exactly like the kernel's `tile_live`
    (an sp rank whose shard lies entirely past `pos` must emit m=NEG_INF,
    l=0 partials, which fold-all would corrupt)."""
    B, T, H, _ = q.shape
    S = k.shape[1]
    KVH = H // G
    n_s = S // bk
    Vd = v.shape[-1]
    quantized = k_scale is not None
    pos = scalars[0]
    offset = scalars[1]
    if rotating:
        live = jnp.minimum(pos + 1, jnp.int32(S))
    else:
        live = pos + 1 - offset
    qf = q[:, 0].reshape(B, KVH, G, -1).astype(jnp.float32) * scale

    def dequant(t, sc):
        t = t.astype(jnp.float32)
        return t * sc if quantized else t

    def fold(carry, s):
        m, l, acc = carry
        k_t = lax.dynamic_slice_in_dim(k, s * bk, bk, 1)
        v_t = lax.dynamic_slice_in_dim(v, s * bk, bk, 1)
        ks_t = lax.dynamic_slice_in_dim(k_scale, s * bk, bk, 1) if quantized else None
        vs_t = lax.dynamic_slice_in_dim(v_scale, s * bk, bk, 1) if quantized else None
        kf = dequant(k_t, ks_t)  # [B, bk, KVH, Hd]
        vf = dequant(v_t, vs_t)  # [B, bk, KVH, Vd]
        scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf)  # [B, KVH, G, bk]
        slot = s * bk + jnp.arange(bk)
        if rotating:
            k_abs = pos - jnp.mod(pos - slot, jnp.int32(S))
            valid = (k_abs >= 0) & (k_abs > pos - jnp.int32(window))
        else:
            k_abs = offset + slot
            valid = k_abs <= pos
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum("bkgs,bskd->bkgd", p, vf)
        live_t = s * bk < live
        return (
            jnp.where(live_t, m_new, m),
            jnp.where(live_t, l_new, l),
            jnp.where(live_t, acc_new, acc),
        ), None

    init = (
        jnp.full((B, KVH, G, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, KVH, G, 1), jnp.float32),
        jnp.zeros((B, KVH, G, Vd), jnp.float32),
    )
    # the fold's outputs are varying over the inputs' mesh axes; the scan
    # carry must enter with the same vma (fresh zeros are invariant)
    axes = _vma_union(q, k, v, scalars)
    if axes:
        init = lax.pcast(init, tuple(sorted(axes)), to="varying")
    (m, l, acc), _ = lax.scan(fold, init, jnp.arange(n_s))
    if with_lse:
        return (
            acc.reshape(B, 1, H, Vd),
            m[..., 0],
            l[..., 0],
        )
    sink = sinks.astype(jnp.float32).reshape(KVH, G)[None, :, :, None]
    m_fin = jnp.maximum(m, sink)
    corr = jnp.exp(m - m_fin)
    l_fin = l * corr + jnp.exp(sink - m_fin)
    out = acc * corr / jnp.maximum(l_fin, 1e-30)
    return out.reshape(B, 1, H, Vd).astype(q.dtype)


def _shape_ok(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    T, H = q.shape[1], q.shape[2]
    S, KVH = k.shape[1], k.shape[2]
    return T == 1 and H % KVH == 0 and S >= 8 and _pick_tile(S, 256) > 0


def _kernel_serves(q: jnp.ndarray, k: jnp.ndarray, ok: bool = True) -> bool:
    """Whether the split-K kernel serves this decode (booked as `dense`
    when not).  DNET_FLASH_DECODE=0 is the operator kill-switch."""
    from dnet_tpu.config import env_flag

    ok = (
        ok
        and env_flag("DNET_FLASH_DECODE", default=True)
        and _shape_ok(q, k)
        and kernel_backend() is not None
    )
    if not ok:
        SELECTIONS.record("flash_decode", "dense", (q.shape, k.shape))
    return ok


def flash_decode_eligible(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    """T=1, GQA-divisible heads, tileable cache length, TPU backend (or the
    DNET_FLASH_INTERPRET test override).  Inside shard_map (mesh ring /
    mesh-backed shard programs) the kernel runs with explicit output vma
    declarations — or the jnp tile-fold emulation under interpret mode.
    Packed-int4 caches (uint8 tiles) are NOT eligible: their nibble
    interleave along the lane dim has no Mosaic lowering, so they
    dequantize through read_kv and stream f32 tiles instead."""
    return _kernel_serves(q, k, k.dtype != jnp.uint8)


def sp_flash_eligible(q: jnp.ndarray, k_local: jnp.ndarray) -> bool:
    """Eligibility for the sequence-parallel composition, which runs INSIDE
    shard_map by construction: the split-K kernel with declared output vma
    on TPU, the jnp tile-fold emulation under DNET_FLASH_INTERPRET=1 (the
    LSE combine — pmax/psum — is the same code either way, so CPU mesh
    tests execute the composition's algebra)."""
    return _kernel_serves(q, k_local)


def flash_decode_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    pos,
    scale: Optional[float] = None,
    sinks: Optional[jnp.ndarray] = None,
    window: int = 0,
    rotating: bool = False,
    offset=None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token decode attention against the (full, preallocated) cache.

    q [B, 1, H, Hd]; k [B, S, KVH, Hd]; v [B, S, KVH, Vd].  Equals the
    dense `attend` with the causal mask at `pos` (linear caches) or the
    rotating sliding-window mask (rotating=True, window=W ring buffers,
    cache written BEFORE the call).  `offset`: absolute position of slot 0
    (sp shards).  With `k_scale`/`v_scale` ([B, S, KVH, 1] f32) the cache
    arrives as int8 and dequantizes tile-by-tile in VMEM, reading only the
    quantized bytes from HBM (the dense path materializes a full f32 cache
    copy through read_kv first).  Caller must check
    flash_decode_eligible."""
    B, T, H, Hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = Hd**-0.5 if scale is None else scale
    sink_arr = (
        jnp.full((KVH, G), NEG_INF, dtype=jnp.float32)
        if sinks is None
        else sinks.astype(jnp.float32).reshape(KVH, G)
    )
    scalars = jnp.stack(
        [jnp.asarray(pos, jnp.int32),
         jnp.asarray(0 if offset is None else offset, jnp.int32)]
    )
    common = dict(
        G=G, scale=float(scale), bk=_pick_tile(k.shape[1], 256),
        window=int(window), rotating=bool(rotating), with_lse=False,
        k_scale=k_scale, v_scale=v_scale,
    )
    backend = kernel_backend()
    manual = _under_manual_mesh()
    if manual and backend == "interpret":
        SELECTIONS.record("flash_decode", "emulate")
        return _decode_emulate(q, k, v, scalars, sink_arr, **common)
    SELECTIONS.record("flash_decode", backend)
    vma: frozenset = frozenset()
    if manual:
        scales = () if k_scale is None else (k_scale, v_scale)
        vma = _vma_union(q, k, v, scalars, sink_arr, *scales)
    return _decode_pallas(
        q, k, v, scalars, sink_arr, interpret=backend == "interpret",
        vma=tuple(sorted(vma)),
        scal_varying=bool(manual and _vma_union(scalars)), **common,
    )


def sp_flash_decode_attend(
    q: jnp.ndarray,
    k_local: jnp.ndarray,
    v_local: jnp.ndarray,
    pos,
    axis_name: str,
    sinks: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel flash decode: each rank runs the split-K kernel on
    its KV shard emitting UNNORMALIZED (acc, m, l) partials, then one
    log-sum-exp combine (pmax + 2x psum) merges ranks — the kernel-backed
    twin of `ops.ring_attention.sp_decode_attend` (same collectives, same
    sink algebra, tile reads instead of dense f32 score tensors)."""
    B, T, H, Hd = q.shape
    KVH = k_local.shape[2]
    G = H // KVH
    S_local = k_local.shape[1]
    scale = Hd**-0.5 if scale is None else scale
    offset = lax.axis_index(axis_name) * S_local
    scalars = jnp.stack(
        [jnp.asarray(pos, jnp.int32), jnp.asarray(offset, jnp.int32)]
    )
    sink_arr = jnp.full((KVH, G), NEG_INF, dtype=jnp.float32)
    common = dict(
        G=G, scale=float(scale), bk=_pick_tile(S_local, 256), window=0,
        rotating=False, with_lse=True,
    )
    if kernel_backend() == "interpret":
        # CPU mesh coverage: emulated per-rank partials, REAL collectives —
        # the LSE-combine algebra below executes unchanged
        SELECTIONS.record("flash_decode", "emulate")
        o, m, l = _decode_emulate(q, k_local, v_local, scalars, sink_arr, **common)
    else:
        SELECTIONS.record("flash_decode", "pallas")
        o, m, l = _decode_pallas(
            q, k_local, v_local, scalars, sink_arr, interpret=False,
            vma=(axis_name,), scal_varying=True, **common,
        )  # o [B,1,H,Vd] unnormalized f32; m/l [B,KVH,G]
    m_glob = lax.pmax(m, axis_name)
    if sinks is not None:
        sink = sinks.astype(jnp.float32).reshape(KVH, G)[None]
        m_glob = jnp.maximum(m_glob, sink)
    corr = jnp.exp(m - m_glob)  # [B, KVH, G]
    corr_h = corr.reshape(B, 1, H, 1)
    l_glob = lax.psum(l * corr, axis_name)
    o_glob = lax.psum(o * corr_h, axis_name)
    if sinks is not None:
        l_glob = l_glob + jnp.exp(jnp.broadcast_to(sink, m_glob.shape) - m_glob)
    out = o_glob / jnp.maximum(l_glob.reshape(B, 1, H, 1), 1e-30)
    return out.astype(q.dtype)
