"""Ragged paged attention: decode attends the KV block pool IN PLACE.

The paged subsystem (kv/) keeps the cache as blocks of a shared pool
behind per-sequence page tables.  "Ragged Paged Attention" (PAPERS.md,
arxiv 2604.15464) names the TPU-native way to read it, which this module
implements: an attention program that consumes the pool-shaped
`[N_blocks, bt, KVH, Hd]` arrays and the `[slots, nb]` int32 page tables
DIRECTLY, so a contiguous per-slot view never exists.

The kernel is the split-K online-softmax fold of `ops/flash_decode.py`
with the page table as the scalar-prefetched block index map: grid
(slots, kv_heads, nb) walks each slot's logical blocks, the index map
resolves logical -> physical through the prefetched table, and indices
past a slot's live length clamp to its last live block — Pallas elides
the HBM->VMEM copy when the block index repeats, so a slot at pos=2K in
a 128K-capacity pool reads ~2K slots (the same dead-tile trick, applied
per-sequence instead of per-batch).  Ragged per-slot lengths cost
nothing: length is just each slot's own clamp horizon.  GQA folds all G
query heads of a kv head per tile, and the CURRENT token's k/v row —
not yet in the pool; the block append happens after the launch — is
folded analytically into the (m, l, acc) accumulator at the emit step,
exactly like flash_decode's sink logits.

Three implementations behind one dispatcher (`paged_attend`):

- ``pallas``     — the real kernel, and the only choice on a TPU backend.
- ``interpret``  — the same kernel under pl.pallas_call(interpret=True),
  so CPU tier-1 executes the actual kernel logic incl. the index-map
  clamping (DNET_FLASH_INTERPRET=1, the flash_decode convention).
- ``emulate``    — a plain-jnp twin for CPU backends, where interpret mode
  is too slow to serve: gather the table's blocks (already width-bounded
  by the caller's pow2 bucket), write the new row at `pos`, and run the
  shared dense `attend` — the same operation order as the dense slot
  cache, so greedy streams stay byte-identical, fused into the step
  program.

A model whose cache entry is ONE latent row a token (multi-head latent
attention, models/deepseek_v2.py) is attended ABSORBED by a kernel of its
own, `paged_attend_latent`: every query head scores against the same entry
`[c | k_pe]`, whose first `rank` lanes are also the value, so a block is
read from HBM ONCE and used for both (passing the latent to `paged_attend`
as keys and again as values would store and read it twice).  Same grid,
same clamping of dead table entries, same fold of the current token at the
emit step, same three implementations behind its dispatcher.

The caller (core/batch.py: kv_layout) owns eligibility via
`ragged_refusal`: a model whose attention threads the `attend_fn` hook
(supports_paged_attend: the llama family, cohere2_moe's two kinds, the
hybrid qwen3_next, the latent deepseek_v2 / mistral4) and unquantized pool
leaves.  Everything else serves dense slots.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_STATE
from dnet_tpu.ops.kernel_select import SELECTIONS, kernel_backend

NEG_INF = -1e30

#: static implementation choices for the dispatcher (trace-time constant)
PAGED_IMPLS = ("pallas", "interpret", "emulate")
#: the custom calls' names in a device trace, by the layer's kind
PAGED_NAME = "paged_attend"
PAGED_WINDOW_NAME = "paged_attend_window"
PAGED_LATENT_NAME = "paged_attend_latent"


def paged_attend_impl() -> str:
    """Resolve the implementation for this process: the real kernel on
    TPU, the interpret-mode kernel under the DNET_FLASH_INTERPRET test
    override (CPU tier-1 executes the true kernel logic), the jnp twin on
    any other CPU backend (fast enough to SERVE there)."""
    return kernel_backend() or "emulate"


def ragged_refusal(model, kv_quant_bits: int) -> Optional[str]:
    """Why decode cannot attend the block pool in place for this model and
    cache (None = it can); such an engine serves dense slots
    (core/batch.py: kv_layout)."""
    if not getattr(model, "supports_paged_attend", False):
        return (
            f"{model.config.model_type}: no paged-attend hook (its attention "
            "does not thread apply_window's attend_fn), so decode cannot read "
            "the pool in place"
        )
    if kv_quant_bits:
        return (
            f"quantized KV cache (bits={kv_quant_bits}): the kernel reads "
            "unquantized blocks"
        )
    kinds = set(getattr(model, "paged_kinds", None) or (KV_KIND_FULL,))
    if kinds == {KV_KIND_STATE}:
        # not the pool's to serve, and not dense slots' either: kv_layout
        # sends it to the state store before it asks here
        return "recurrent-state layers keep no blocks (the state store serves them)"
    if KV_KIND_FULL not in kinds:
        return "no full layer among the window layers"
    if KV_KIND_STATE in kinds and len(kinds) > 2:
        # the store that holds a lane of state beside a page table
        # (kv/store.py HybridStore) has the full kind's pool alone
        return "state layers beside full AND window layers (no store holds all three)"
    return None


def _paged_kernel(tbl_ref, pos_ref, base_ref, layer_ref, q_ref, k_ref, v_ref,
                  kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref, *, bt: int,
                  scale: float, nb: int, KVH: int, Hd: int, Vd: int,
                  window: int):
    """One (slot, table-entry) fold of the online softmax, every kv head.

    tbl_ref SMEM [slots, nb] page table, pos_ref SMEM [slots] live pool
    rows per slot (the new token's row arrives via kn/vn, folded at emit),
    base_ref SMEM [slots] the logical block index of each table's FIRST
    entry (0 for a table that keeps everything; a window layer's table has
    given back the blocks behind the window, kv/paged.py), layer_ref SMEM
    [1] the pool's layer (index maps only).
    Mosaic tiles a block's last two dims, so the pool block arrives with
    heads merged into the lane dim — k_ref [1, 1, bt, KVH*Hd], v_ref
    [1, 1, bt, KVH*Vd] — and a kv head is a static lane slice; q_ref
    [1, KVH, G, Hd] holds each head's whole GQA group, so one block read
    amortizes over all G query heads sharing it.  `window` > 0 (static)
    adds the lower bound: only keys at absolute positions > live - window
    score, and a block wholly behind that bound is neither folded nor
    copied (the index map clamps it like a block past the live length)."""
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    i = pl.program_id(1)
    live = pos_ref[b]
    first = (base_ref[b] + i) * bt  # absolute position of this block's row 0

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    in_range = first < live
    if window:
        # the new token sits at position `live` and attends keys at
        # positions > live - window: a block whose last row is at or
        # behind that bound holds nothing to score
        in_range = in_range & (first + bt - 1 > live - window)

    @pl.when(in_range)
    def _fold():
        # mid-block ragged edge: the last live block is only partially
        # full — rows at absolute positions >= live are stale pool content
        # (or a clamped repeat of an earlier block) and must not score;
        # the block the window's edge cuts is masked by absolute position
        slot = first + lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        valid = slot < live
        if window:
            valid = valid & (slot > live - window)
        for kh in range(KVH):
            q = q_ref[0, kh].astype(jnp.float32) * scale  # [G, Hd]
            k = k_ref[0, 0, :, kh * Hd:(kh + 1) * Hd].astype(jnp.float32)
            scores = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, bt]
            scores = jnp.where(valid, scores, NEG_INF)
            m_prev = m_ref[kh]  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[kh] = l_ref[kh] * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p, v_ref[0, 0, :, kh * Vd:(kh + 1) * Vd].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, Vd]
            acc_ref[kh] = acc_ref[kh] * corr + pv
            m_ref[kh] = m_new

    @pl.when(i == nb - 1)
    def _emit():
        # fold the CURRENT token's row (position == live, always attended
        # under the causal predicate) analytically — it reaches the pool
        # only after the launch, via the kv_append program
        q = q_ref[0].astype(jnp.float32) * scale  # [KVH, G, Hd]
        kn = kn_ref[0].astype(jnp.float32)  # [KVH, 1, Hd]
        vn = vn_ref[0].astype(jnp.float32)  # [KVH, 1, Vd]
        s_new = jnp.sum(q * kn, axis=2, keepdims=True)  # [KVH, G, 1]
        m_fin = jnp.maximum(m_ref[...], s_new)
        corr = jnp.exp(m_ref[...] - m_fin)
        p_new = jnp.exp(s_new - m_fin)
        l_fin = l_ref[...] * corr + p_new
        acc_fin = acc_ref[...] * corr + p_new * vn
        o_ref[0] = (acc_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("G", "scale", "bt", "interpret", "window"),
)
def _paged_pallas(q, k_pool, v_pool, tables, pos, k_new, v_new, base=None,
                  layer=None, *, G: int, scale: float, bt: int,
                  interpret: bool, window: int = 0):
    """k_pool/v_pool are ONE layer's [N, bt, KVH, Hd/Vd] slices, or with
    `layer` ([1] int32) a kind's whole stack [L, N, bt, KVH*Hd/Vd], heads
    already merged into the lane dim, which the index map takes the layer
    of.  `base` [B] int32: see _paged_kernel (None = 0)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, Hd = q.shape
    KVH = H // G
    if layer is None:
        N = k_pool.shape[0]
        k_pool = k_pool.reshape(1, N, bt, -1)
        v_pool = v_pool.reshape(1, N, bt, -1)
        layer = jnp.zeros((1,), jnp.int32)
    if base is None:
        base = jnp.zeros((B,), jnp.int32)
    Vd = v_pool.shape[-1] // KVH
    nb = tables.shape[1]

    def kv_map(b, i, tbl, pos, base, layer):
        """Table entries past a slot's live length clamp to its last live
        block, and entries wholly behind the window to the first block the
        window reaches: the pipeline re-fetches (elides) one block instead
        of streaming dead ones."""
        hi = jnp.clip((pos[b] - 1) // bt - base[b], 0, nb - 1)
        if window:
            lo = jnp.clip((pos[b] - window + 1) // bt - base[b], 0, hi)
            i = jnp.maximum(i, lo)
        return (layer[0], tbl[b, jnp.minimum(i, hi)], 0, 0)

    def whole4(b, i, tbl, pos, base, layer):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, KVH, G, Hd), whole4),
            pl.BlockSpec((1, 1, bt, KVH * Hd), kv_map),
            pl.BlockSpec((1, 1, bt, KVH * Vd), kv_map),
            pl.BlockSpec((1, KVH, 1, Hd), whole4),
            pl.BlockSpec((1, KVH, 1, Vd), whole4),
        ],
        out_specs=pl.BlockSpec((1, KVH, G, Vd), whole4),
        scratch_shapes=[
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, Vd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, bt=bt, scale=scale, nb=nb, KVH=KVH, Hd=Hd, Vd=Vd,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, Vd), q.dtype),
        interpret=interpret,
        # the trace tells window-layer attention from full-layer attention
        # by this name
        name=PAGED_WINDOW_NAME if window else PAGED_NAME,
    )(
        tables, pos, base, layer, q.reshape(B, KVH, G, Hd), k_pool, v_pool,
        k_new.reshape(B, KVH, 1, Hd), v_new.reshape(B, KVH, 1, Vd),
    )
    return out.reshape(B, T, H, Vd)


def _paged_emulate(q, k_pool, v_pool, tables, pos, k_new, v_new,
                   scale: float, window: int = 0, base=None):
    """Plain-jnp twin: gather each slot's blocks to a contiguous view
    (width already bounded by the caller's pow2 table bucket), write the
    new row at `pos` exactly like the dense path's write_kv, and attend
    with the causal-at-pos mask through the SAME dense `attend` the
    dense slot cache bottoms out in — one fused program.  CPU backends
    serve through this; interpret mode
    and TPU run the kernel.  View row j of slot b sits at absolute
    position base[b] * bt + j (`base` None = 0)."""
    from dnet_tpu.ops.attention import attend

    B, T, H, Hd = q.shape
    nb = tables.shape[1]
    bt = k_pool.shape[1]
    KVH = k_new.shape[1]
    S = nb * bt

    def view(pool):
        g = pool[tables]  # [B, nb, bt, ...]
        return g.reshape(B, S, KVH, -1)

    kc = view(k_pool)
    vc = view(v_pool)
    rel = pos if base is None else pos - base * bt
    write = jax.vmap(
        lambda c, r, p: jax.lax.dynamic_update_slice(c, r[None], (p, 0, 0))
    )
    kc = write(kc, k_new.astype(kc.dtype), rel)
    vc = write(vc, v_new.astype(vc.dtype), rel)
    slot = jnp.arange(S)[None, :]
    mask = slot <= rel[:, None]
    if window:
        mask = mask & (slot > rel[:, None] - window)
    return attend(q, kc, vc, mask=mask[:, None, :], scale=scale)


def paged_attend(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    pos: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    scale: Optional[float] = None,
    impl: str = "emulate",
    window: int = 0,
    base: Optional[jnp.ndarray] = None,
    layer=None,
) -> jnp.ndarray:
    """Single-token decode attention against the block pool, in place.

    q [B, 1, H, Hd]; k_pool/v_pool [N_blocks, bt, KVH, Hd/Vd] (ONE layer's
    pool slices); tables [B, nb] int32 page tables (entries past a slot's
    allocation are 0 — never read thanks to the live clamp); pos [B] int32
    live pool rows per slot; k_new/v_new [B, KVH, Hd/Vd] the current
    token's rows (position == pos, attended in-launch, appended to the
    pool by the caller afterwards).  Equals dense write-then-attend with
    the causal mask at pos.  `impl` is a trace-time constant — callers
    resolve it once via paged_attend_impl().

    A window layer (`window` > 0, static) attends keys at positions
    > pos - window only, and its table may have given back the blocks
    behind the window: `base` [B] int32 is the logical block index of each
    table's first entry (None = 0).  With `layer` (a traced index) the
    pools are a kind's whole stack, [L, N_blocks, bt, KVH*Hd/Vd] with the
    heads already merged into the lane dim (kv/store.py KindStore): the
    kernel indexes the layer itself, so no pool slice is copied."""
    B, T, H, Hd = q.shape
    KVH = k_new.shape[1]
    G = H // KVH
    scale = Hd**-0.5 if scale is None else float(scale)
    tables = tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    base = (
        jnp.zeros((B,), jnp.int32) if base is None else base.astype(jnp.int32)
    )
    if impl not in PAGED_IMPLS:
        raise ValueError(f"paged_attend impl {impl!r} not in {PAGED_IMPLS}")
    SELECTIONS.record("paged_attend", impl)
    if layer is None:
        bt = k_pool.shape[1]
    else:
        bt = k_pool.shape[2]
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
        if impl == "emulate":
            k_pool = lax.dynamic_index_in_dim(k_pool, layer[0], 0, keepdims=False)
            v_pool = lax.dynamic_index_in_dim(v_pool, layer[0], 0, keepdims=False)
    if impl == "emulate":
        return _paged_emulate(q, k_pool, v_pool, tables, pos, k_new, v_new,
                              scale, window=int(window), base=base)
    return _paged_pallas(
        q, k_pool, v_pool, tables, pos, k_new, v_new, base, layer,
        G=G, scale=scale, bt=bt, interpret=(impl == "interpret"),
        window=int(window),
    )


# ---- latent entries: absorbed multi-head latent attention -------------------


def _latent_kernel(tbl_ref, pos_ref, layer_ref, q_ref, *refs, bt: int, nb: int,
                   rank: int, n_sub: int):
    """One (slot, `n_sub` table entries) fold of the online softmax, every
    head against the SAME latent entries.

    q_ref [1, H, W] the absorbed queries `[W_kvb[K]^T q_nope | q_pe]`, the
    softmax scale (and a position-dependent one) already folded in;
    refs: `n_sub` views of the pool, each one block [1, 1, bt, W] of
    entries `[c | k_pe]` (consecutive table entries: a grid step costs the
    same whatever it moves, and one block of 128 entries is a tenth of a
    microsecond of HBM), then cn_ref [1, 1, W] the current token's entry,
    o_ref [1, H, rank] and the scratch m, l [H, 1], acc [H, rank] float32.
    A block scores through its whole width and is the value through its
    first `rank` lanes: read once, used twice.  Dots take the pool's type
    with float32 accumulation (a float32 pool multiplies at `highest`)."""
    import jax.experimental.pallas as pl

    c_refs, (cn_ref, o_ref, m_ref, l_ref, acc_ref) = refs[:n_sub], refs[n_sub:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    live = pos_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * n_sub * bt < live)
    def _fold():
        # ONE softmax update for the step's `n_sub` blocks: their score and
        # value products do not depend on each other (a chain of per-block
        # updates keeps one matrix unit busy loading 128-row tiles for 32
        # query rows: 0.68 us a block measured, PERF.md section 6, PR 41)
        q = q_ref[0]
        blks = [c_ref[0, 0] for c_ref in c_refs]  # [bt, W] each
        q = q.astype(blks[0].dtype)  # [H, W]
        exact = lax.Precision.HIGHEST if blks[0].dtype == jnp.float32 else None
        scores = jnp.concatenate(
            [
                lax.dot_general(
                    q, blk, (((1,), (1,)), ((), ())), precision=exact,
                    preferred_element_type=jnp.float32,
                )
                for blk in blks
            ],
            axis=1,
        )  # [H, n_sub * bt]
        # the last live block is partly full, and blocks past it are
        # clamped repeats: rows at or past `live` must not score
        slot = i * n_sub * bt + lax.broadcasted_iota(jnp.int32, (1, n_sub * bt), 1)
        scores = jnp.where(slot < live, scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = sum(
            lax.dot_general(
                p[:, j * bt:(j + 1) * bt].astype(blk.dtype), blk[:, :rank],
                (((1,), (0,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32,
            )
            for j, blk in enumerate(blks)
        )  # [H, rank]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(i == nb // n_sub - 1)
    def _emit():
        # the CURRENT token's entry (position == live, always attended)
        # reaches the pool only after the launch: folded here
        q = q_ref[0].astype(jnp.float32)  # [H, W]
        cn = cn_ref[0].astype(jnp.float32)  # [1, W]
        s_new = jnp.sum(q * cn, axis=1, keepdims=True)  # [H, 1]
        m_fin = jnp.maximum(m_ref[...], s_new)
        corr = jnp.exp(m_ref[...] - m_fin)
        p_new = jnp.exp(s_new - m_fin)
        l_fin = l_ref[...] * corr + p_new
        acc_fin = acc_ref[...] * corr + p_new * cn[:, :rank]
        o_ref[0] = (acc_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


#: table entries one grid step of the latent kernel folds at most
LATENT_SUB_BLOCKS = 16


@functools.partial(jax.jit, static_argnames=("rank", "bt", "interpret"))
def _latent_pallas(q, pool, tables, pos, c_new, layer, *, rank: int, bt: int,
                   interpret: bool):
    """q [B, H, W]; pool [L, N, bt, W] (a kind's whole stack: the index map
    takes the layer); tables [B, nb]; pos [B]; c_new [B, 1, W]; layer [1]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    nb = tables.shape[1]
    n_sub = next(n for n in (LATENT_SUB_BLOCKS, 4, 2, 1) if nb % n == 0)

    def sub_map(j):
        def c_map(b, i, tbl, pos, layer):
            """Entries past a slot's live length clamp to its last live
            block: the pipeline re-fetches (elides) one block instead of
            streaming dead ones."""
            hi = jnp.clip((pos[b] - 1) // bt, 0, nb - 1)
            return (layer[0], tbl[b, jnp.minimum(i * n_sub + j, hi)], 0, 0)

        return c_map

    def whole3(b, i, tbl, pos, layer):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb // n_sub),
        in_specs=[pl.BlockSpec((1, H, W), whole3)]
        + [pl.BlockSpec((1, 1, bt, W), sub_map(j)) for j in range(n_sub)]
        + [pl.BlockSpec((1, 1, W), whole3)],
        out_specs=pl.BlockSpec((1, H, rank), whole3),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, bt=bt, nb=nb, rank=rank, n_sub=n_sub
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        interpret=interpret,
        name=PAGED_LATENT_NAME,
    )(tables, pos, layer, q, *([pool] * n_sub), c_new)


def _latent_emulate(q, pool, tables, pos, c_new, rank: int):
    """Plain-jnp twin: gather each slot's blocks to a contiguous view,
    write the new entry at `pos` and attend through the shared dense
    `attend` with ONE kv head whose value is the entry's first `rank`
    lanes.  pool [N, bt, W] (one layer's)."""
    from dnet_tpu.ops.attention import attend

    B, H, W = q.shape
    nb, bt = tables.shape[1], pool.shape[1]
    S = nb * bt
    view = pool[tables].reshape(B, S, 1, W)
    view = jax.vmap(
        lambda c, r, p: jax.lax.dynamic_update_slice(c, r[None], (p, 0, 0))
    )(view, c_new.astype(view.dtype), pos)
    mask = jnp.arange(S)[None, :] <= pos[:, None]
    return attend(
        q[:, None], view, view[..., :rank], mask=mask[:, None, :], scale=1.0
    )[:, 0]


def paged_attend_latent(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    pos: jnp.ndarray,
    c_new: jnp.ndarray,
    rank: int,
    layer,
    impl: str = "emulate",
) -> jnp.ndarray:
    """Single-token ABSORBED latent attention against the block pool, in
    place.  q [B, 1, H, W]: each head's absorbed query `[W_kvb[K]^T q_nope
    | q_pe]` with every scale folded in (the kernel scores as is); pool
    [L, N_blocks, bt, W] the latent kind's whole stack of entries `[c |
    k_pe]`, `layer` (traced) the one to read; tables [B, nb] int32; pos
    [B] live pool rows per slot; c_new [B, 1, W] the current token's entry
    (position == pos: attended in the launch, appended by the caller
    afterwards).  Returns o_lat [B, 1, H, rank] = sum_j softmax_j(q .
    entry_j) entry_j[:rank]: the caller un-absorbs it through W_kvb[V]."""
    if impl not in PAGED_IMPLS:
        raise ValueError(f"paged_attend_latent impl {impl!r} not in {PAGED_IMPLS}")
    SELECTIONS.record(PAGED_LATENT_NAME, impl)
    tables = tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if impl == "emulate":
        one = lax.dynamic_index_in_dim(pool, layer[0], 0, keepdims=False)
        out = _latent_emulate(q[:, 0], one, tables, pos, c_new, rank)
    else:
        out = _latent_pallas(
            q[:, 0], pool, tables, pos, c_new, layer, rank=int(rank),
            bt=pool.shape[2], interpret=(impl == "interpret"),
        )
    return out[:, None]
