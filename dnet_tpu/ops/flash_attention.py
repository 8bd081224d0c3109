"""Flash attention for causal prefill (Pallas on TPU, jnp fallback).

The dense `ops.attention.attend` materializes the full [B, KVH, G, T, S]
f32 score tensor — at long-context prefill that is the dominant HBM
cost (a 8K x 8K f32 score block is 256 MB per head-group) and the reason
chunked prefill exists.  This kernel streams KV tiles through VMEM with
the online-softmax accumulator (m, l, acc) in scratch, so memory is
O(T x Hd) regardless of S, and the MXU sees [bq, Hd] x [Hd, bk] tiles.

Reference analog: the compression subsystem's Metal kernels show the
reference's pattern of hand-written GPU kernels for hot ops
(src/dnet/compression/kernels.py); attention is the TPU hot op worth the
same treatment.  Scope: CAUSAL SELF-ATTENTION against a slot-addressed
cache — query row i attends keys [0, pos + i] — covering llama-family,
deepseek-MLA (V's head dim may differ from Q/K's), and gpt_oss
full-attention prefill (per-head sink logits folded into the softmax
denominator at emit).  A window layer (`window` > 0) adds the lower bound
— row i attends keys (pos + i - window, pos + i] — and skips the tiles
wholly behind it as it skips those above the diagonal; its custom call has
a name of its own.  Rotating ring-buffer windows and sp sharding stay
dense.

TPU grids run sequentially over the LAST axis, so the KV-tile axis comes
last and the scratch accumulator carries across its iterations.  That axis
counts from the q tile's FIRST live kv tile (`_live_tiles`) and ends with
the chunk's last one: `pos` is a scalar prefetch, the k and v index maps
clamp into the q tile's live range (a repeated block index is not copied),
and the grid's bound is the most tiles any q tile of this chunk folds.  A
tile above the causal diagonal or behind the window is neither copied nor,
past the chunk, stepped over; the staged row's length costs nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.ops.kernel_select import SELECTIONS, kernel_backend

NEG_INF = -1e30


#: the custom calls' names in a device trace, by the layer's kind
FLASH_NAME = "flash_prefill"
FLASH_WINDOW_NAME = "flash_prefill_window"
#: query heads one grid step holds at most (scratch is [heads, bq, ...] f32:
#: 32 heads x 128 rows is 6 MB, and 128 heads would not fit VMEM)
HEADS_PER_STEP = 32


def _live_tiles(pos, tq, *, bq: int, bk: int, n_s: int, window: int, xp=jnp):
    """-> (lo, hi): the first and the last kv tile holding a (query, key)
    pair that q tile `tq` of a chunk at `pos` attends.  The tile's LAST row
    attends keys <= pos + (tq + 1) * bq - 1, so a kv tile starting past
    that is masked for the whole q tile; with a window the tile's FIRST
    row attends keys > pos + tq * bq - window and every later row only
    later ones, so a kv tile ending at or behind that is too.  The index
    maps, the kernel's `live` predicate, the grid's bound and the host's
    count (`xp=np`) all read this."""
    hi = xp.minimum((pos + (tq + 1) * bq - 1) // bk, n_s - 1)
    lo = xp.maximum(pos + tq * bq - window + 1, 0) // bk if window else 0
    return lo, hi


def _kv_tile(pos, tq, s, *, xp=jnp, **geom):
    """The kv tile step `s` of q tile `tq` holds: its live tiles in order
    from the first, then the last one again (a repeated block index is not
    copied, and the body skips the step)."""
    lo, hi = _live_tiles(pos, tq, xp=xp, **geom)
    return xp.minimum(lo + s, hi)


def _kv_steps(pos, T: int, *, bq: int, bk: int, n_s: int, window: int, xp=jnp):
    """The kv axis' bound: the most tiles any q tile of the chunk folds.
    Without a window that is the last q tile's (they all start at tile 0);
    a window of `window` keys over `bq` rows spans at most
    (window + bq - 2) // bk + 2 tiles wherever it lies."""
    _, hi = _live_tiles(pos, T // bq - 1, bq=bq, bk=bk, n_s=n_s, window=window, xp=xp)
    return xp.minimum(hi + 1, (window + bq - 2) // bk + 2) if window else hi + 1


def flash_tiles(pos: int, T: int, S: int, window: int = 0):
    """Host twin of the kernel's grid for one chunk of `T` rows at `pos`
    against a row of `S` keys -> ((q tile, kv tile) pairs it folds, pairs
    of the whole [T / bq, S / bk] grid it neither copies nor steps over),
    or None where the shapes do not take the kernel."""
    if kernel_backend() is None or not _tiles_ok(T, S):
        return None
    bq, bk = _pick_tile(T, 128), _pick_tile(S, 128)
    geom = dict(bq=bq, bk=bk, n_s=S // bk, window=int(window or 0), xp=np)
    folded = 0
    for tq in range(T // bq):
        lo, hi = _live_tiles(int(pos), tq, **geom)
        folded += int(hi) - int(lo) + 1
    return folded, (T // bq) * (S // bk) - folded


def _flash_kernel(pos_ref, sink_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, bq: int, bk: int, scale: float, n_s: int,
                  KVH: int, G: int, Hd: int, Vd: int, window: int = 0):
    """One (batch, head-group, q-tile, kv-step) step of the online softmax,
    every head of the group (KVH kv heads and their G query heads each;
    all the heads when they fit one step).  Step j of a q tile holds its
    j-th live kv tile (`_kv_tile`); the steps left over once a q tile's
    live tiles are folded hold the last one again and do nothing.

    Mosaic tiles the last two dims of a block, so a block can take a head
    out of [.., heads, dim] only whole.  The operands therefore arrive with
    heads merged into the lane dim — q_ref [1, bq, H*Hd], k_ref
    [1, bk, KVH*Hd], v_ref [1, bk, KVH*Vd], o_ref [1, bq, H*Vd] — and a
    head is a static lane slice (MLA: Vd may differ from Hd).  Scratch per
    head: m/l [H, bq, 1] f32, acc [H, bq, Vd] f32; pos SMEM [1]; sink_ref
    SMEM [H] per-head sink logits (GPT-OSS: a virtual key that absorbs
    probability mass but contributes no value; NEG_INF = no sink, exp
    underflows to an exact no-op)."""
    import jax.experimental.pallas as pl

    hb = pl.program_id(1)
    tq = pl.program_id(2)
    step = pl.program_id(3)
    pos = pos_ref[0]
    lo, hi = _live_tiles(pos, tq, bq=bq, bk=bk, n_s=n_s, window=window)
    s = lo + step  # the kv tile this step holds, where it is live

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s <= hi)
    def _fold():
        q_pos = pos + tq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = s * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = k_pos <= q_pos
        if window:
            # a row whose window opens in a later tile folds only masked
            # scores here (m stays NEG_INF, p = 1): its first real score
            # rescales that by exp(NEG_INF - m) == 0.0, exactly
            keep = keep & (k_pos > q_pos - window)
        for kh in range(KVH):
            k = k_ref[0, :, kh * Hd:(kh + 1) * Hd].astype(jnp.float32)
            v = v_ref[0, :, kh * Vd:(kh + 1) * Vd].astype(jnp.float32)
            for h in range(kh * G, (kh + 1) * G):
                q = q_ref[0, :, h * Hd:(h + 1) * Hd].astype(jnp.float32) * scale
                scores = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bq, bk]
                scores = jnp.where(keep, scores, NEG_INF)
                m_prev = m_ref[h]  # [bq, 1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(scores, axis=1, keepdims=True)
                )
                p = jnp.exp(scores - m_new)  # [bq, bk]
                corr = jnp.exp(m_prev - m_new)  # [bq, 1]
                l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
                pv = lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [bq, Vd]
                acc_ref[h] = acc_ref[h] * corr + pv
                m_ref[h] = m_new

    @pl.when(step == pl.num_programs(3) - 1)
    def _emit():
        # fold the sink into the global softmax denominator exactly once
        # (same algebra as the dense op's virtual-key column)
        for h in range(KVH * G):
            sink = sink_ref[hb * KVH * G + h]
            m_fin = jnp.maximum(m_ref[h], sink)
            corr = jnp.exp(m_ref[h] - m_fin)
            l_fin = l_ref[h] * corr + jnp.exp(sink - m_fin)
            o_ref[0, :, h * Vd:(h + 1) * Vd] = (
                acc_ref[h] * corr / jnp.maximum(l_fin, 1e-30)
            ).astype(o_ref.dtype)


def _heads_per_step(KVH: int, G: int, Hd: int, Vd: int) -> int:
    """kv heads one grid step takes: all of them when their query heads
    fit HEADS_PER_STEP, else the largest divisor that does and keeps the
    blocks' lane widths whole multiples of 128."""
    if KVH * G <= HEADS_PER_STEP:
        return KVH
    for kb in range(KVH - 1, 0, -1):
        if (
            KVH % kb == 0
            and kb * G <= HEADS_PER_STEP
            and (kb * Hd) % 128 == 0
            and (kb * Vd) % 128 == 0
        ):
            return kb
    return KVH


@functools.partial(
    jax.jit,
    static_argnames=("G", "scale", "bq", "bk", "interpret", "vma", "window"),
)
def _flash_pallas(q, k, v, pos, sinks, *, G: int, scale: float, bq: int,
                  bk: int, interpret: bool, vma: tuple = (), window: int = 0):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, Hd = q.shape
    S, KVH = k.shape[1], k.shape[2]
    Vd = v.shape[-1]
    n_s = S // bk
    KB = _heads_per_step(KVH, G, Hd, Vd)

    geom = dict(bq=bq, bk=bk, n_s=n_s, window=window)

    # grid (batch, head-group, q-tile, kv-step); kv-step LAST so the
    # scratch accumulator carries across its (sequential) iterations, and
    # only as long as this chunk's q tiles have live kv tiles to fold
    kernel = functools.partial(
        _flash_kernel, scale=scale, KVH=KB, G=G, Hd=Hd, Vd=Vd, **geom
    )

    def q_map(b, hb, tq, s, pos_ref):
        return (b, tq, hb)

    def kv_map(b, hb, tq, s, pos_ref):
        return (b, _kv_tile(pos_ref[0], tq, s, **geom), hb)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # pos [1]
            grid=(B, KVH // KB, T // bq, _kv_steps(pos[0], T, **geom)),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # sinks [H]
                pl.BlockSpec((1, bq, KB * G * Hd), q_map),
                pl.BlockSpec((1, bk, KB * Hd), kv_map),
                pl.BlockSpec((1, bk, KB * Vd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, bq, KB * G * Vd), q_map),
            scratch_shapes=[
                pltpu.VMEM((KB * G, bq, 1), jnp.float32),
                pltpu.VMEM((KB * G, bq, 1), jnp.float32),
                pltpu.VMEM((KB * G, bq, Vd), jnp.float32),
            ],
        ),
        # inside shard_map the output is device-varying over the inputs'
        # mesh axes; check_vma requires the declaration
        out_shape=jax.ShapeDtypeStruct(
            (B, T, H * Vd), q.dtype, vma=frozenset(vma)
        ),
        interpret=interpret,
        # the trace tells window-layer attention from full-layer attention
        # by this name
        name=FLASH_WINDOW_NAME if window else FLASH_NAME,
    )(
        pos, sinks, q.reshape(B, T, H * Hd), k.reshape(B, S, KVH * Hd),
        v.reshape(B, S, KVH * Vd),
    )
    return out.reshape(B, T, H, Vd)


def _flash_emulate(q, k, v, pos, sinks, *, scale: float, bk: int,
                   window: int = 0):
    """Plain-jnp twin of _flash_kernel: the same tile-by-tile online-softmax
    fold (f32, same operation order), for executed coverage where pallas
    cannot run — interpret mode inside shard_map discharges the kernel to a
    jaxpr whose constants stay vma-invariant (r4 diagnosis), so CPU mesh
    tests and dryruns run this emulation; real TPU runs the kernel.

    Folding every kv tile (no above-diagonal skip) is exact: tile 0 always
    holds an attendable key (slot 0 is causal for every row when pos >= 0),
    so m is finite after the first fold and a fully-masked later tile
    contributes exp(NEG_INF - m) == 0.0 to l/acc and leaves m unchanged —
    a bitwise no-op in f32."""
    B, T, H, Hd = q.shape
    S, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    Vd = v.shape[-1]
    n_s = S // bk
    qf = q.reshape(B, T, KVH, G, Hd).astype(jnp.float32) * scale

    def fold(carry, s):
        m, l, acc = carry  # [B,KVH,G,T,1] x2, [B,KVH,G,T,Vd]
        k_t = lax.dynamic_slice_in_dim(k, s * bk, bk, 1).astype(jnp.float32)
        v_t = lax.dynamic_slice_in_dim(v, s * bk, bk, 1).astype(jnp.float32)
        scores = jnp.einsum("btkgd,bskd->bkgts", qf, k_t)  # [B,KVH,G,T,bk]
        q_pos = pos + jnp.arange(T)[:, None]
        k_pos = s * bk + jnp.arange(bk)[None, :]
        keep = k_pos <= q_pos
        if window:
            keep = keep & (k_pos > q_pos - window)
        scores = jnp.where(keep[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bkgts,bskd->bkgtd", p, v_t)
        return (m_new, l, acc), None

    init = (
        jnp.full((B, KVH, G, T, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, KVH, G, T, 1), jnp.float32),
        jnp.zeros((B, KVH, G, T, Vd), jnp.float32),
    )
    # the fold's outputs are varying over the inputs' mesh axes; the scan
    # carry must enter with the same vma (fresh zeros are invariant)
    axes = _vma_union(q, k, v, pos)
    if axes:
        init = lax.pcast(init, tuple(sorted(axes)), to="varying")
    (m, l, acc), _ = lax.scan(fold, init, jnp.arange(n_s))
    sink = sinks.astype(jnp.float32).reshape(KVH, G)[None, :, :, None, None]
    m_fin = jnp.maximum(m, sink)
    corr = jnp.exp(m - m_fin)
    l_fin = l * corr + jnp.exp(sink - m_fin)
    out = acc * corr / jnp.maximum(l_fin, 1e-30)  # [B,KVH,G,T,Vd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Vd).astype(q.dtype)


def _pick_tile(n: int, target: int) -> int:
    for t in (target, 128, 64, 32, 16, 8):
        if t <= n and n % t == 0:
            return t
    return 0


def _under_manual_mesh() -> bool:
    """True when tracing inside shard_map (mesh ring / mesh-shard programs).

    Inside shard_map the kernels still run: pallas_call outputs carry
    explicit vma declarations derived from the inputs' varying axes
    (`_vma_union`), and interpret mode — where pallas under shard_map is
    fundamentally broken (discharged-jaxpr constants stay vma-invariant) —
    runs the plain-jnp tile-fold emulation instead."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def _vma_union(*xs) -> frozenset:
    """Union of the inputs' varying mesh axes (shard_map vma) — what a
    pallas_call's outputs must declare under check_vma."""
    out: frozenset = frozenset()
    for x in xs:
        out |= jax.typeof(jnp.asarray(x)).vma
    return out


def _tiles_ok(T: int, S: int) -> bool:
    return T >= 8 and _pick_tile(T, 128) > 0 and _pick_tile(S, 128) > 0


def _shape_ok(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    return q.shape[2] % k.shape[2] == 0 and _tiles_ok(q.shape[1], k.shape[1])


def flash_eligible(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> bool:
    """Kernel preconditions: GQA-divisible heads, tileable T/S, and a TPU
    backend (or the test override forcing interpret mode).  V's head dim
    may differ from Q/K's (MLA).  Inside shard_map the kernel runs with
    explicit output vma (or the jnp emulation under interpret)."""
    return kernel_backend() is not None and _shape_ok(q, k)


def flash_attend_causal(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    pos,
    scale: Optional[float] = None,
    sinks: Optional[jnp.ndarray] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Causal prefill attention: query row i attends cache slots [0, pos+i],
    or with `window` > 0 (static) only (pos+i-window, pos+i].

    q [B, T, H, Hd]; k [B, S, KVH, Hd], v [B, S, KVH, Vd] (the full cache;
    slots past pos+T are excluded by causality).  Equals
    `attend(q, k, v, mask=causal_mask(T, S, pos), sinks=sinks)` — the
    Pallas kernel runs on TPU (or under DNET_FLASH_INTERPRET=1 for CPU
    tests), the dense op otherwise.  sinks [H]: per-head attention-sink
    logits (GPT-OSS).
    """
    B, T, H, Hd = q.shape
    S, KVH = k.shape[1], k.shape[2]
    scale = Hd**-0.5 if scale is None else scale

    window = int(window or 0)

    def dense():
        from dnet_tpu.ops.attention import (
            attend,
            causal_mask,
            sliding_window_mask,
        )

        mask = (
            sliding_window_mask(T, S, pos, window)
            if window
            else causal_mask(T, S, pos)
        )
        return attend(q, k, v, mask=mask, scale=scale, sinks=sinks)

    if T == 1 and window:
        # a window layer's decode over a slot-addressed cache: the masked
        # dense op (the served path decodes through ops/paged_attention.py)
        return dense()
    if T == 1:
        # decode: one query row against the (preallocated) cache — the
        # split-K sibling kernel streams only the LIVE tiles
        from dnet_tpu.ops.flash_decode import (
            flash_decode_attend,
            flash_decode_eligible,
        )

        if flash_decode_eligible(q, k):
            return flash_decode_attend(q, k, v, pos, scale=scale, sinks=sinks)
        return dense()  # booked under flash_decode by its eligibility check
    if not flash_eligible(q, k, v):
        SELECTIONS.record("flash_prefill", "dense", (q.shape, k.shape))
        return dense()
    backend = kernel_backend()
    sink_arr = (
        jnp.full((H,), NEG_INF, dtype=jnp.float32)
        if sinks is None
        else sinks.astype(jnp.float32)
    )
    manual = _under_manual_mesh()
    if manual and backend == "interpret":
        # CPU mesh tests: pallas-in-shard_map interpret is broken, the
        # jnp emulation executes the identical fold
        SELECTIONS.record("flash_prefill", "emulate")
        return _flash_emulate(
            q, k, v, pos, sink_arr, scale=float(scale), bk=_pick_tile(S, 128),
            window=window,
        )
    SELECTIONS.record("flash_prefill", backend)
    vma = _vma_union(q, k, v, pos, sink_arr) if manual else frozenset()
    return _flash_pallas(
        q, k, v, jnp.asarray([pos], dtype=jnp.int32), sink_arr, G=H // KVH,
        scale=float(scale), bq=_pick_tile(T, 128), bk=_pick_tile(S, 128),
        interpret=backend == "interpret", vma=tuple(sorted(vma)),
        window=window,
    )
