"""Block-sparse attention chosen by an index of pooled keys (InfLLM-V2 as
MiniCPM4 publishes it; models/minicpm_sala.py's `minicpm4` layers).

Past `dense_len` tokens of context a query attends `topk` blocks of
`block_size` tokens and no more.  For the query at position t (context
n = t + 1 > dense_len), a KV head at a time:

    c_j    = mean(k_(s j) .. k_(s j + K - 1))        every j with s j + K - 1 <= t
    p_h[j] = softmax_j(q_h . c_j / sqrt(Hd))          a query head
    r[j]   = sum of p_h[j] over the G heads of the KV group
    R[b]   = max r[j] over the spans j that touch block b

(K `kernel_size` 32, s `kernel_stride` 16: the spans touching block b of 64
tokens are j in [4b - 1, 4b + 3]).  Chosen: the first `init_blocks` blocks,
the `window_size / block_size` blocks ending at the query's own, and the
best-scoring of the blocks before those, `topk` in all, ties to the lower
index.  The heads of a group share the choice; the groups choose apart.
At or under `dense_len` a query attends everything before it.  The rule is
PER POSITION: a prompt's token at position 5000 attends densely whatever
the prompt's length, its token at 9000 sparsely.

The pooled keys `c_j` are an INDEX kept beside the keys: one row for
`kernel_stride` tokens, complete once the span it averages is, so a decode
step scores `n / 16` rows instead of reading `n` keys.

Three ops, each `pallas` / `interpret` / `emulate` behind one dispatcher
(the `paged_attend` convention), and the plain functions between them:

- `index_scores` (/health `sparse_index`): `r` for a tile of queries
  against one sequence's pooled keys: G matmuls, G masked softmaxes and
  their sum a grid step, the pooled keys of a KV head whole in VMEM.
- `block_scores`, `choose_blocks`: `R` and the choice, `jax.numpy`.  The
  best blocks are found by a threshold search over the scores' bits (31
  counting passes over the row, as core/sampler.py filter_keep finds the
  sampler's cut): nothing is sorted.
- `paged_attend_sparse`: a decode step's read.  A (lane, KV head) walks
  its OWN list of chosen blocks through the page table, eight blocks a
  grid step, and nothing else of the pool; the new token's row is already
  in the pool (the caller writes before it reads).  The pool's block may
  be any multiple of `block_size`: the kernel sees the pool as
  `[L, N * (bt / block_size), block_size, W]` and a chosen block as a
  sub-block of its page.
- `flash_prefill_sparse`: a prefill chunk's read.  The causal grid's
  tiles of 128 keys, each masked a query by that query's choice, and only
  the tiles some query of the q tile chose: a (KV head, q tile) walks its
  own compacted list of tiles, the grid's bound the longest list.

`sparse_attend_dense` is the definition (explicit masks, no kernel, no
index leaf): the tests hold every op to it.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from dnet_tpu.obs.phases import SCOPE_ATTN_INDEX as SCOPE_INDEX
from dnet_tpu.obs.phases import SCOPE_ATTN_SPARSE as SCOPE_SPARSE
from dnet_tpu.ops.kernel_select import SELECTIONS, kernel_backend

NEG_INF = -1e30
SPARSE_IMPLS = ("pallas", "interpret", "emulate")
#: the custom calls' names in a device trace
INDEX_NAME = "sparse_index"
DECODE_NAME = "paged_attend_sparse"
PREFILL_NAME = "flash_prefill_sparse"
#: chosen blocks one grid step of the decode kernel reads
STEP_BLOCKS = 8
#: keys of one tile of the prefill kernel (whole blocks)
PREFILL_TILE = 128
#: query rows of one tile of the index and prefill kernels
QUERY_TILE = 128
#: queries one call of the index and prefill kernels takes (the served chunk)
QUERY_SLAB = 2048
_LANES = 128


@dataclass(frozen=True)
class SparseConfig:
    """MiniCPM4's `sparse_config`, in tokens."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        s, K, b = self.kernel_stride, self.kernel_size, self.block_size
        if b & (b - 1) or b % s or K % s or self.window_size % b or self.dense_len % b:
            raise ValueError(f"sparse attention: sizes do not nest: {self}")
        if self.n_best < 0 or self.dense_len // b < self.topk:
            # past dense_len a query has at least topk blocks to choose
            raise ValueError(f"sparse attention: topk {self.topk} does not fit: {self}")

    @classmethod
    def from_hf(cls, d) -> "SparseConfig":
        d = dict(d or {})
        return cls(**{k: int(d[k]) for k in cls.__dataclass_fields__ if k in d})

    @property
    def rows_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def n_best(self) -> int:
        """Blocks chosen by score: topk less the forced ones."""
        return self.topk - self.init_blocks - self.window_blocks

    @property
    def list_blocks(self) -> int:
        """The most blocks a query attends: all of them up to dense_len."""
        return max(self.dense_len // self.block_size, self.topk)

    def blocks_attended(self, n: int) -> int:
        """Blocks the query of context `n` (position n - 1) attends."""
        held = -(-n // self.block_size)
        return held if n <= self.dense_len else self.topk


def sparse_impl() -> str:
    return kernel_backend() or "emulate"


def _check_impl(impl: str) -> None:
    if impl not in SPARSE_IMPLS:
        raise ValueError(f"sparse attention impl {impl!r} not in {SPARSE_IMPLS}")


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


# ---- the index: pooled keys, scores, the choice ------------------------------
def pooled_keys(k, cfg: SparseConfig):
    """k [S, W] (a sequence's keys by position; S a multiple of the
    stride) -> c [S / stride, W] in k's type: row j the mean of keys
    [stride j, stride j + kernel_size).  Rows whose span runs past the
    keys written so far hold a partial mean: the index masks them."""
    S, W = k.shape
    s = cfg.kernel_stride
    sums = jnp.sum(k.astype(jnp.float32).reshape(S // s, s, W), axis=1)
    acc = sums
    for i in range(1, cfg.kernel_size // s):
        acc = acc + jnp.pad(sums[i:], ((0, i), (0, 0)))
    return (acc / cfg.kernel_size).astype(k.dtype)


def _scores_emulate(q, kc, t, cfg: SparseConfig, scale: float):
    """q [A, KVH, G, M, Hd], kc [A, Nc, KVH, Hd], t [A, M] -> r [A, KVH, M, Nc]."""
    s = jnp.einsum(
        "akgmd,ajkd->akgmj", q.astype(jnp.float32), kc.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ) * scale
    j = jnp.arange(kc.shape[1])
    ok = (cfg.kernel_stride * j[None, None, :] + cfg.kernel_size - 1 <= t[:, :, None])
    ok = ok[:, None, None]  # [A, 1, 1, M, Nc]
    m = jnp.max(jnp.where(ok, s, NEG_INF), axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.sum(p, axis=2)


def _scores_kernel(t_ref, q_ref, kc_ref, r_ref, *, G: int, bm: int, Hd: int,
                   stride: int, span: int, scale: float, rows_are_heads: bool,
                   precision):
    """One (sequence, KV head, tile of queries): the G heads' softmaxes
    over the head's pooled keys, summed.

    q_ref [1, 1, G, bm, Hd] (or, `rows_are_heads`, [1, 1, 1, G, Hd]: one
    query, its heads down the rows), kc_ref [1, Nc, Hd] the KV head's
    pooled keys, r_ref [1, 1, bm, Nc]; t_ref SMEM [A]: the position of the
    sequence's first query row (row i of tile m sits at t + m bm + i)."""
    import jax.experimental.pallas as pl

    a, mt = pl.program_id(0), pl.program_id(2)
    kc = kc_ref[0]
    Nc = kc.shape[0]
    rows = G if rows_are_heads else bm
    j = lax.broadcasted_iota(jnp.int32, (rows, Nc), 1)
    t = t_ref[a] + mt * bm
    if not rows_are_heads:
        t = t + lax.broadcasted_iota(jnp.int32, (rows, Nc), 0)
    ok = stride * j + span - 1 <= t

    def probs(qh):
        s = lax.dot_general(
            qh, kc, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, Nc]
        m = jnp.max(jnp.where(ok, s, NEG_INF), axis=1, keepdims=True)
        p = jnp.where(ok, jnp.exp(s - m), 0.0)
        return p / jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)

    if rows_are_heads:
        r_ref[0, 0] = jnp.sum(probs(q_ref[0, 0, 0]), axis=0, keepdims=True)
    else:
        acc = probs(q_ref[0, 0, 0])
        for h in range(1, G):
            acc = acc + probs(q_ref[0, 0, h])
        r_ref[0, 0] = acc


def _scores_pallas(q, kc, t0, cfg: SparseConfig, scale: float, interpret: bool):
    """q [A, KVH, G, M, Hd], kc [A, Nc, KVH * Hd] (Nc a multiple of 128),
    t0 [A] -> r [A, KVH, M, Nc].  M == 1 (a decode step's query a lane)
    puts the heads down the rows of ONE matmul."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    A, KVH, G, M, Hd = q.shape
    Nc = kc.shape[1]
    heads = M == 1
    if heads:
        q = q.reshape(A, KVH, 1, G, Hd)
        bm, q_block = 1, (1, 1, 1, G, Hd)
    else:
        bm = _query_tile(M)
        q_block = (1, 1, G, bm, Hd)
    precision = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    kernel = functools.partial(
        _scores_kernel, G=G, bm=bm, Hd=Hd, stride=cfg.kernel_stride,
        span=cfg.kernel_size, scale=scale, rows_are_heads=heads, precision=precision,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(A, KVH, M // bm),
            in_specs=[
                pl.BlockSpec(q_block, lambda a, g, m, t: (a, g, 0, 0 if heads else m, 0)),
                pl.BlockSpec((1, Nc, Hd), lambda a, g, m, t: (a, 0, g)),
            ],
            out_specs=pl.BlockSpec((1, 1, bm, Nc), lambda a, g, m, t: (a, g, m, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((A, KVH, M, Nc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        interpret=interpret,
        name=INDEX_NAME,
    )(t0.astype(jnp.int32), q, kc)


def _query_tile(M: int) -> int:
    for t in (QUERY_TILE, 64, 32, 16, 8):
        if t <= M and M % t == 0:
            return t
    return M


def index_scores(q, kc, t0, cfg: SparseConfig, impl: str = "emulate"):
    """`r`: each query's softmaxes over its sequence's pooled keys, summed
    over the heads of a KV group.

    q [A, M, H, Hd]: A sequences of M consecutive queries, the first at
    position t0[a] (a prefill chunk: A = 1; a decode step: a lane a
    sequence, M = 1); kc [A, Nc, KVH, Hd] the sequences' pooled keys by
    row, complete or not (row j counts for the query at t only where
    stride j + kernel_size - 1 <= t).  Returns r [A, KVH, M, Nc] float32,
    0 at the rows that do not count."""
    _check_impl(impl)
    SELECTIONS.record(INDEX_NAME, impl)
    A, M, H, Hd = q.shape
    Nc, KVH = kc.shape[1], kc.shape[2]
    G = H // KVH
    scale = Hd**-0.5
    qg = jnp.moveaxis(q.reshape(A, M, KVH, G, Hd), 1, 3)  # [A, KVH, G, M, Hd]
    if impl == "emulate":
        t = t0[:, None] + jnp.arange(M)[None, :]
        return _scores_emulate(qg, kc, t, cfg, scale)
    pad = _pad_to(Nc, _LANES) - Nc
    kc = kc.reshape(A, Nc, KVH * Hd)
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0)))
    r = _scores_pallas(qg, kc, t0, cfg, scale, impl == "interpret")
    return r[..., :Nc] if pad else r


def block_scores(r, cfg: SparseConfig):
    """r [..., Nc] -> R [..., Nc / rows_per_block]: a block's score is the
    largest r among the spans that touch it, the one ending inside it from
    the block before included (j in [4b - 1, 4b + 3] at the published
    sizes: a span covers kernel_size / stride strides)."""
    rpb = cfg.rows_per_block
    reach = cfg.kernel_size // cfg.kernel_stride - 1  # spans starting before the block
    nb = r.shape[-1] // rpb
    r = r[..., : nb * rpb]
    R = jnp.max(r.reshape(*r.shape[:-1], nb, rpb), axis=-1)
    for i in range(1, reach + 1):
        before = jnp.pad(r[..., : nb * rpb - i], [(0, 0)] * (r.ndim - 1) + [(i, 0)])
        R = jnp.maximum(R, before.reshape(*r.shape[:-1], nb, rpb)[..., 0])
    return R


def choose_blocks(R, t, cfg: SparseConfig):
    """The blocks the query at position `t` attends, as a mask.

    R [..., nb] float32 block scores (>= 0), t [...] int32.  Context
    t + 1 <= dense_len: every block up to the query's own.  Past it:
    the first `init_blocks`, the window's blocks ending at the query's
    own, and the `n_best` best-scoring of the blocks between (ties to the
    lower index), found by a threshold search over the scores' bits."""
    nb = R.shape[-1]
    b = jnp.arange(nb, dtype=jnp.int32)
    t = t[..., None].astype(jnp.int32)
    qb = t // cfg.block_size
    causal = b <= qb
    first_window = qb - cfg.window_blocks + 1
    forced = (b < cfg.init_blocks) | (b >= first_window)
    cand = (b >= cfg.init_blocks) & (b < first_window)
    k = cfg.n_best
    # non-negative float32 order as their bits do; a block that is no
    # candidate stands below every score
    key = jnp.where(cand, lax.bitcast_convert_type(R.astype(jnp.float32), jnp.int32), -1)

    def count(where):
        return jnp.sum(where, axis=-1, keepdims=True, dtype=jnp.int32)

    def bit(i, th):
        trial = th | (jnp.int32(1) << (30 - i))
        return jnp.where(count(key >= trial) >= k, trial, th)

    # the k-th largest key: the largest threshold with k keys at or above it
    th = lax.fori_loop(0, 31, bit, jnp.zeros(key.shape[:-1] + (1,), jnp.int32))
    above = key > th
    equal = key == th
    room = k - count(above)
    best = above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= room))
    sparse = causal & (forced | (best & cand))
    return jnp.where(t + 1 <= cfg.dense_len, causal, sparse)


def compact(mask, width: int):
    """mask [..., n] bool -> (the indices of its True entries in order,
    [..., width] int32, padded by repeating the last one; their count
    [...]).  A cumulative sum and a scatter, no sort."""
    n = mask.shape[-1]
    lead = mask.shape[:-1]
    flat = mask.reshape(-1, n)
    cnt = jnp.sum(flat, axis=-1, dtype=jnp.int32)
    at = jnp.where(flat, jnp.cumsum(flat, axis=-1, dtype=jnp.int32) - 1, width)
    rows = jnp.arange(flat.shape[0])[:, None]
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), flat.shape)
    out = jnp.zeros((flat.shape[0], width), jnp.int32).at[rows, at].set(ids, mode="drop")
    last = jnp.take_along_axis(out, jnp.maximum(cnt - 1, 0)[:, None], axis=1)
    out = jnp.where(jnp.arange(width)[None, :] < cnt[:, None], out, last)
    return out.reshape(*lead, width), jnp.minimum(cnt, width).reshape(lead)


# ---- the definition ----------------------------------------------------------
def sparse_attend_dense(q, k, v, pos, cfg: SparseConfig, chosen=None):
    """q [T, H, Hd] at positions pos .. pos + T - 1 against k/v [S, KVH, Hd]
    (keys by position) -> o [T, H, Hd] float32: the equations by explicit
    masks.  `chosen` [KVH, T, nb] overrides the choice (the tests')."""
    T, H, Hd = q.shape
    S, KVH, _ = k.shape
    G = H // KVH
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    t = pos + jnp.arange(T)
    if chosen is None:
        kc = pooled_keys(kf.reshape(S, KVH * Hd), cfg).reshape(-1, KVH, Hd)
        r = index_scores(qf[None], kc[None], jnp.asarray([pos]), cfg, impl="emulate")[0]
        chosen = choose_blocks(block_scores(r, cfg), t[None, :], cfg)
    tok = jnp.arange(S)
    keep = jnp.take(chosen, tok // cfg.block_size, axis=-1) & (tok[None, None, :] <= t[None, :, None])
    s = jnp.einsum(
        "tkgd,skd->kgts", qf.reshape(T, KVH, G, Hd), kf, precision=lax.Precision.HIGHEST
    ) * Hd**-0.5
    s = jnp.where(keep[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, vf, precision=lax.Precision.HIGHEST)
    return o.reshape(T, H, Hd)


# ---- the decode step's read --------------------------------------------------
def _decode_kernel(phys_ref, sel_ref, cnt_ref, pos_ref, layer_ref, q_ref, *rest,
                   NB: int, bs: int, scale: float, precision):
    """One (lane, KV head, NB chosen blocks) fold of the online softmax.

    phys_ref / sel_ref SMEM [B, KVH, Wl]: the chosen blocks' rows in the
    pool's sub-block view and their logical indices; cnt_ref SMEM [B, KVH]
    how many are chosen; pos_ref SMEM [B] the query's position (its own
    row is in the pool).  q_ref [1, 1, G, Hd]; then NB key blocks and NB
    value blocks [1, 1, bs, Hd]; o_ref [1, 1, G, Hd]; scratch m, l [G, 1],
    acc [G, Hd]."""
    import jax.experimental.pallas as pl

    k_refs, v_refs = rest[:NB], rest[NB:2 * NB]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * NB:]
    b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = pos_ref[b]
    n = cnt_ref[b, h]
    q = q_ref[0, 0]
    for e in range(NB):
        at = i * NB + e

        @pl.when(at < n)
        def _fold(e=e, at=at):
            first = sel_ref[b, h, at] * bs
            tok = first + lax.broadcasted_iota(jnp.int32, (1, bs), 1)
            s = lax.dot_general(
                q, k_refs[e][0, 0], (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            ) * scale  # [G, bs]
            s = jnp.where(tok <= t, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
                p.astype(v_refs[e].dtype), v_refs[e][0, 0], (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_pallas(q, k_pool, v_pool, phys, sel, cnt, pos, layer, cfg: SparseConfig,
                   interpret: bool):
    """q [B, KVH, G, Hd]; pools [L, N, bt, KVH * Hd]; phys/sel [B, KVH, Wl];
    cnt [B, KVH]; pos [B]; layer [1]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    B, KVH, G, Hd = q.shape
    L, N, bt, W = k_pool.shape
    bs = cfg.block_size
    sub = bt // bs
    kp = k_pool.reshape(L, N * sub, bs, W)
    vp = v_pool.reshape(L, N * sub, bs, W)
    Wl = phys.shape[-1]
    NB = min(STEP_BLOCKS, Wl)
    steps = (jnp.max(cnt) + NB - 1) // NB

    def q_map(b, h, i, *_):
        return (b, h, 0, 0)

    def kv_map(e):
        def index(b, h, i, phys, sel, cnt, pos, layer):
            at = jnp.minimum(i * NB + e, cnt[b, h] - 1)
            return (layer[0], phys[b, h, jnp.maximum(at, 0)], 0, h)

        return index

    blocks = [pl.BlockSpec((1, 1, bs, Hd), kv_map(e)) for e in range(NB)]
    precision = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, NB=NB, bs=bs, scale=Hd**-0.5, precision=precision
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, KVH, steps),
            in_specs=[pl.BlockSpec((1, 1, G, Hd), q_map)] + blocks + blocks,
            out_specs=pl.BlockSpec((1, 1, G, Hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, Hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, Hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=DECODE_NAME,
    )(phys, sel, cnt, pos, layer, q, *([kp] * NB), *([vp] * NB))


def _decode_emulate(q, k_pool, v_pool, phys, sel, cnt, pos, layer, cfg: SparseConfig):
    B, KVH, G, Hd = q.shape
    L, N, bt, W = k_pool.shape
    bs = cfg.block_size
    sub = bt // bs
    Wl = phys.shape[-1]

    def view(pool):
        rows = pool.reshape(L * N * sub, bs, KVH, Hd)[layer[0] * N * sub + phys]
        # [B, KVH, Wl, bs, KVH, Hd]: a head reads its own lanes
        own = jnp.stack([rows[:, h, :, :, h] for h in range(KVH)], axis=1)
        return own.reshape(B, KVH, Wl * bs, Hd).astype(jnp.float32)

    kf, vf = view(k_pool), view(v_pool)
    tok = (sel[..., None] * bs + jnp.arange(bs)).reshape(B, KVH, Wl * bs)
    live = (jnp.arange(Wl)[None, None, :] < cnt[..., None])
    keep = jnp.repeat(live, bs, axis=-1) & (tok <= pos[:, None, None])
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32), kf,
                   precision=lax.Precision.HIGHEST) * Hd**-0.5
    s = jnp.where(keep[:, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", p, vf, precision=lax.Precision.HIGHEST).astype(q.dtype)


def paged_attend_sparse(q, k_pool, v_pool, tables, chosen, pos, layer,
                        cfg: SparseConfig, impl: str = "emulate"):
    """A decode step's attention over the blocks each (lane, KV head)
    chose, read through the page table, in place.

    q [B, 1, H, Hd]; k_pool / v_pool [L, N, bt, KVH * Hd] the full kind's
    stacks, the step's own row ALREADY written; tables [B, nb] int32;
    chosen [B, KVH, nb * bt / block_size] bool (`choose_blocks`); pos [B]
    the queries' positions; `layer` a traced index.  Returns
    [B, 1, H, Hd] in q's type."""
    _check_impl(impl)
    SELECTIONS.record(DECODE_NAME, impl)
    B, _, H, Hd = q.shape
    bt = k_pool.shape[2]
    KVH = k_pool.shape[3] // Hd
    sub = bt // cfg.block_size
    width = min(cfg.list_blocks, chosen.shape[-1])
    sel, cnt = compact(chosen, width)
    page = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None, :], (B, KVH, tables.shape[1])), sel // sub, axis=2
    )
    phys = page * sub + sel % sub
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    args = (
        q.reshape(B, KVH, H // KVH, Hd), k_pool, v_pool, phys.astype(jnp.int32), sel,
        cnt, pos.astype(jnp.int32), layer, cfg,
    )
    if impl == "emulate":
        out = _decode_emulate(*args)
    else:
        out = _decode_pallas(*args, impl == "interpret")
    return out.reshape(B, 1, H, Hd)


# ---- the prefill chunk's read ------------------------------------------------
def _prefill_kernel(pos_ref, tiles_ref, cnt_ref, q_ref, k_ref, v_ref, c_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, G: int, bq: int, bk: int, bs: int,
                    Hd: int, nq: int, n_tiles: int, scale: float, precision):
    """One (KV head, q tile, chosen kv tile) fold of the online softmax,
    the G heads of the group.

    tiles_ref SMEM [KVH * nq * n_tiles] the chosen tiles of each (head, q
    tile) in order, cnt_ref SMEM [KVH * nq] how many; q_ref [1, bq, G * Hd],
    k_ref/v_ref [1, bk, Hd], c_ref [1, bq, 128] the queries' choice over
    the 128 blocks this tile's lie among (1.0 chosen), o_ref [1, bq, G * Hd];
    scratch m, l [G, bq, 1], acc [G, bq, Hd]."""
    import jax.experimental.pallas as pl

    g, tq, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row = g * nq + tq

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < cnt_ref[row])
    def _fold():
        tile = tiles_ref[row * n_tiles + s]
        q_pos = pos_ref[0] + tq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = tile * bk + col <= q_pos
        # each query's choice of this tile's blocks: a column of the slab a
        # block, spread over the block's keys
        lane = lax.broadcasted_iota(jnp.int32, (bq, _LANES), 1)
        slab = c_ref[0].astype(jnp.float32)
        tb = bk // bs
        for n in range(tb):
            at = (tile * tb + n) % _LANES
            mine = jnp.sum(jnp.where(lane == at, slab, 0.0), axis=1, keepdims=True) > 0.5
            inside = (col >= n * bs) & (col < (n + 1) * bs)
            keep = keep & (mine | jnp.logical_not(inside))
        k = k_ref[0]
        v = v_ref[0]
        for h in range(G):
            sc = lax.dot_general(
                q_ref[0, :, h * Hd:(h + 1) * Hd], k, (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32,
            ) * scale  # [bq, bk]
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(s == pl.num_programs(2) - 1)
    def _emit():
        for h in range(G):
            o_ref[0, :, h * Hd:(h + 1) * Hd] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            ).astype(o_ref.dtype)


def _prefill_pallas(q, k, v, pos, chosen, cfg: SparseConfig, interpret: bool):
    """q [T, H, Hd], k/v [S, KVH, Hd], chosen [KVH, T, nb] bool."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    T, H, Hd = q.shape
    S, KVH, _ = k.shape
    G = H // KVH
    bs = cfg.block_size
    bq = _query_tile(T)
    bk = max(bs, PREFILL_TILE)
    tb = bk // bs
    nq, n_tiles = T // bq, S // bk
    nb = n_tiles * tb
    chosen = chosen[..., :nb]
    if chosen.shape[-1] < nb:
        chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, nb - chosen.shape[-1])))
    # the tiles some query of a q tile chose, compacted
    any_q = jnp.any(chosen.reshape(KVH, nq, bq, n_tiles, tb), axis=(2, 4))
    tiles, cnt = compact(any_q, n_tiles)
    slab = jnp.pad(
        chosen.astype(jnp.bfloat16), ((0, 0), (0, 0), (0, _pad_to(nb, _LANES) - nb))
    )
    precision = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None

    def q_map(g, tq, s, *_):
        return (0, tq, g)

    def kv_map(g, tq, s, pos, tiles, cnt):
        row = g * nq + tq
        at = jnp.maximum(jnp.minimum(s, cnt[row] - 1), 0)
        return (0, tiles[row * n_tiles + at], g)

    def c_map(g, tq, s, pos, tiles, cnt):
        row = g * nq + tq
        at = jnp.maximum(jnp.minimum(s, cnt[row] - 1), 0)
        return (g, tq, tiles[row * n_tiles + at] * tb // _LANES)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, G=G, bq=bq, bk=bk, bs=bs, Hd=Hd, nq=nq, n_tiles=n_tiles,
            scale=Hd**-0.5, precision=precision,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(KVH, nq, jnp.max(cnt)),
            in_specs=[
                pl.BlockSpec((1, bq, G * Hd), q_map),
                pl.BlockSpec((1, bk, Hd), kv_map),
                pl.BlockSpec((1, bk, Hd), kv_map),
                pl.BlockSpec((1, bq, _LANES), c_map),
            ],
            out_specs=pl.BlockSpec((1, bq, G * Hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((G, bq, 1), jnp.float32),
                pltpu.VMEM((G, bq, 1), jnp.float32),
                pltpu.VMEM((G, bq, Hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((1, T, H * Hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=PREFILL_NAME,
    )(
        pos, tiles.reshape(-1), cnt.reshape(-1), q.reshape(1, T, H * Hd),
        k.reshape(1, S, KVH * Hd), v.reshape(1, S, KVH * Hd), slab,
    )
    return out.reshape(T, H, Hd)


def flash_prefill_sparse(q, k, v, pos, chosen, cfg: SparseConfig, impl: str = "emulate"):
    """A prefill chunk's attention over each query's chosen blocks.

    q [T, H, Hd] at positions pos .. pos + T - 1; k/v [S, KVH, Hd] the
    sequence's staged row, the chunk's own keys written; chosen
    [KVH, T, >= S / block_size] bool (`choose_blocks`: no block past a
    query's own).  Returns [T, H, Hd] in q's type."""
    _check_impl(impl)
    SELECTIONS.record(PREFILL_NAME, impl)
    if impl == "emulate":
        return sparse_attend_dense(q, k, v, pos, cfg, chosen=chosen).astype(q.dtype)
    return _prefill_pallas(
        q, k, v, jnp.asarray(pos, jnp.int32).reshape(1), chosen, cfg, impl == "interpret"
    )


# ---- what a layer calls ------------------------------------------------------
def sparse_prefill(q, k_row, v_row, pos, cfg: SparseConfig, impl: str = "emulate"):
    """One sequence's prefill chunk through a sparse layer: the index over
    the staged row's pooled keys, the choice, the read.  q [T, H, Hd];
    k_row/v_row [S, KVH, Hd]; pos the chunk's first position (traced)."""
    T = q.shape[0]
    if T > QUERY_SLAB:
        # a chunk wider than the served one: a slab of queries at a time (the
        # kernels' scalar tables and the scores [KVH, T, S / 16] stay a slab's)
        n = -(-T // QUERY_SLAB)
        slabs = jnp.pad(q, ((0, n * QUERY_SLAB - T), (0, 0), (0, 0)))
        starts = jnp.asarray(pos, jnp.int32) + QUERY_SLAB * jnp.arange(n, dtype=jnp.int32)
        out = lax.map(
            lambda a: sparse_prefill(a[0], k_row, v_row, a[1], cfg, impl=impl),
            (slabs.reshape(n, QUERY_SLAB, *q.shape[1:]), starts),
        )
        return out.reshape(n * QUERY_SLAB, *q.shape[1:])[:T]
    S, KVH, Hd = k_row.shape
    t0 = jnp.asarray(pos, jnp.int32).reshape(1)
    t = t0 + jnp.arange(T, dtype=jnp.int32)
    with jax.named_scope(SCOPE_INDEX):
        if S <= cfg.dense_len:
            # no position of this row is past dense_len: nothing to score
            R = jnp.zeros((KVH, T, S // cfg.block_size), jnp.float32)
        else:
            kc = pooled_keys(k_row.reshape(S, KVH * Hd), cfg).reshape(1, -1, KVH, Hd)
            R = block_scores(index_scores(q[None], kc, t0, cfg, impl=impl)[0], cfg)
        chosen = choose_blocks(R, jnp.broadcast_to(t, (KVH, T)), cfg)
    with jax.named_scope(SCOPE_SPARSE):
        return flash_prefill_sparse(q, k_row, v_row, pos, chosen, cfg, impl=impl)


def sparse_decode(pool: dict, q, k_new, v_new, table, pos, active, layer,
                  cfg: SparseConfig, impl: str = "emulate"):
    """One decode token a lane through a sparse layer, in place on the
    full kind's stacks `pool` {"k", "v": [L, N, bt, W], "kc": [L, N,
    bt / stride, W]} (donated): the new row into the lane's block, the
    pooled key whose span the token completes into the index, then the
    index over the lane's pooled keys, the choice and the read.

    q [B, 1, H, Hd]; k_new/v_new [B, KVH, Hd]; table [B, nb]; pos [B] the
    tokens' positions; active [B]: an idle lane writes nothing; `layer` a
    traced index.  Returns (o [B, 1, H, Hd], the stacks)."""
    L, N, bt, W = pool["k"].shape
    B, nb = table.shape
    KVH, Hd = k_new.shape[1], k_new.shape[2]
    s, K = cfg.kernel_stride, cfg.kernel_size
    rpb = bt // s
    pos = pos.astype(jnp.int32)
    live = active.astype(bool)

    def page(p):  # the lanes' physical block holding position p [B, ...]
        return jnp.take_along_axis(table, jnp.clip(p // bt, 0, nb - 1), axis=1)

    # the new row into the lane's block: ONE plain row scatter into the
    # [L*N*bt, W] view (kv/store.py KindStore.append_in_program has the
    # why); an idle lane's row lands past the end and is dropped
    row = (layer * N + page(pos[:, None])[:, 0]) * bt + pos % bt
    row = jnp.where(live, row, L * N * bt)

    def write(p, r, at):
        flat = p.reshape(-1, W).at[at].set(r.reshape(-1, W).astype(p.dtype), mode="drop")
        return flat.reshape(p.shape)

    k_pool = write(pool["k"], k_new, row)
    v_pool = write(pool["v"], v_new, row)
    with jax.named_scope(SCOPE_INDEX):
        # the span this token completes, if it completes one: the mean of
        # the pool's last kernel_size keys, its own among them
        span = pos[:, None] - (K - 1) + jnp.arange(K, dtype=jnp.int32)[None, :]
        span = jnp.maximum(span, 0)
        rows = (layer * N + page(span)) * bt + span % bt
        mean = jnp.mean(k_pool.reshape(-1, W)[rows].astype(jnp.float32), axis=1)
        j = (pos - (K - 1)) // s
        done = live & (pos >= K - 1) & ((pos - (K - 1)) % s == 0)
        at = (layer * N + page(jnp.maximum(j, 0)[:, None] // rpb * bt)[:, 0]) * rpb + j % rpb
        kc_pool = write(pool["kc"], mean, jnp.where(done, at, L * N * rpb))
        # the lane's pooled keys through its table, by row
        mine = kc_pool.reshape(L * N, rpb * W)[layer * N + table]  # [B, nb, rpb * W]
        kc = mine.reshape(B, nb * rpb, KVH, Hd)
        R = block_scores(index_scores(q, kc, pos, cfg, impl=impl)[:, :, 0], cfg)
        chosen = choose_blocks(R, jnp.broadcast_to(pos[:, None], (B, KVH)), cfg)
    with jax.named_scope(SCOPE_SPARSE):
        o = paged_attend_sparse(q, k_pool, v_pool, table, chosen, pos, layer, cfg, impl=impl)
    return o, {"k": k_pool, "v": v_pool, "kc": kc_pool}
