"""Device half of the paged KV subsystem: pool-shaped cache arrays plus
the jitted programs that read and write them.

ONE pool layout: a kind's layers share `[L_kind, N_blocks, block_tokens,
heads*dim]` leaves, the heads merged into the lane dimension once, at
construction: the layout the ragged kernel reads (it takes the layer by
index), so nothing slices, reshapes or relayouts a pool per step or per
prompt.  WHICH leaves and how wide comes from the model
(`RingModel.pool_leaves`): keys and values `{"k", "v"}` of `KVH*Hd` for
every model but one whose cache is latent (models/deepseek_v2.py), which
keeps ONE leaf `{"c"}` a token, `[c | k_pe]` in zero-padded lanes up to a
multiple of 128, whatever its heads, attended absorbed (paged_attend_latent).  (A quantised
cache is never paged: `core/batch.py: kv_layout` sends
it to dense slots, so no pool carries scale leaves.)

The decode path (core/batch.py `_build_ragged`) attends the pool IN PLACE
and speaks to a store by KIND of layer (obs/phases.py KV_KINDS), knowing
no layout: `kinds`, `attend`, `append_rows` and `commit_staged` take and
return `{kind: ...}`.  `KindStore` is the pool
store: a pool a kind, `full` alone for most models (which the prefix cache
also reads), `window` beside it for a model that mixes window and full
layers; `StateStore` holds the third kind, `state`: one recurrent entry a
lane, no blocks, behind the same four names; `HybridStore` holds a `full`
pool (the same layout) AND `state` entries for a model that mixes the two,
so that one sequence sees one store.

Two programs move whole blocks between a staged dense row and the pool:

- **gather_row** (prefix restore) builds one sequence's contiguous
  `[L, 1, nb*bt, KVH, Hd]` row: one `pool[:, ids]` take per leaf, the
  heads split on the gathered row.  Table entries past the sequence clamp
  to block 0; their rows sit at positions the causal mask excludes, so
  exp() zeroes them EXACTLY.
- **commit_staged** / **commit_row** (adoption, prefix store) take a
  staged row's blocks, merge the heads on THOSE, and write them into the
  pool with `.at[:, phys].set`, the pool buffers DONATED so XLA updates in
  place (tests/test_pool_layout_v5e_compile.py holds the compiled programs
  to that: no copy as large as a pool leaf).  Widths are bucketed to powers
  of two (padding repeats the last block: duplicate writes of identical
  content are deterministic) so the compiled-program set stays bounded, the
  same discipline as the engines' chunk buckets.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.kv.paged import PagedKVConfig
from dnet_tpu.obs.jit import instrument_jit
from dnet_tpu.obs import metric
from dnet_tpu.obs.phases import (
    KV_KIND_FULL,
    KV_KIND_STATE,
    KV_KIND_WINDOW,
    KV_KINDS,
    SCOPE_ATTN_FULL,
    SCOPE_ATTN_WINDOW,
)

_STATE_SLOTS = metric("dnet_state_slots")
#: the state kind's traffic counters (bytes, tokens{phase=}), by the op
#: family the model's state layers run (`RingModel.state_family`)
_STATE_COUNTERS = {
    "retention": (
        metric("dnet_retention_state_bytes_total"),
        metric("dnet_retention_tokens_total"),
    ),
    "gdn": (
        metric("dnet_gdn_state_bytes_total"),
        metric("dnet_gdn_tokens_total"),
    ),
    "lightning": (
        metric("dnet_lightning_state_bytes_total"),
        metric("dnet_lightning_tokens_total"),
    ),
}


def _gdn_state_step(state, q, rows, layer, impl):
    from dnet_tpu.ops.gated_delta import gdn_decode

    gate = rows["gate"]
    return gdn_decode(
        state, q[:, 0], gate["conv_w"], gate["g"], gate["beta"],
        rows["active"], layer, impl=impl,
    )


def _lightning_state_step(state, q, rows, layer, impl):
    from dnet_tpu.ops.lightning import lightning_step

    o, S = lightning_step(
        state["S"], q[:, 0], rows["k"], rows["v"], rows["active"], layer, impl=impl
    )
    return o, {**state, "S": S}


#: a hybrid model's state layers' decode step, by the same family: (the
#: state kind's stacks, q [B, 1, ...], the step's rows, the layer's index
#: within the kind, impl) -> (o [B, ...], the stacks)
_STATE_STEPS = {"gdn": _gdn_state_step, "lightning": _lightning_state_step}


def _bucket_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _commit_blocks(p, rows, block_idx, phys):
    """Traced: blocks `block_idx` [K] of staged rows [L, S, KVH, Hd] into
    blocks `phys` [K] of the (donated) pool leaf `p` [L, N, bt, KVH*Hd].
    The row's blocks are taken FIRST and the heads merged on those (tens
    of MB at most); on this layout the update is one in-place scatter."""
    L, S = rows.shape[:2]
    blk = rows.reshape(L, S // p.shape[2], p.shape[2], -1)[:, block_idx]
    return p.at[:, phys].set(blk.astype(p.dtype))


class StateStore:
    """The store of a model whose layers keep a recurrent STATE and no keys
    (models that set every `paged_kinds` entry to `state`): per layer one
    entry a lane, `S [L, slots, KVH, R, Hd, Hd]` and `z [L, slots, KVH, R,
    Hd]` float32 (ops/retention.py), of one size whatever the sequence's
    length.  No blocks, no page table, no pool manager: a lane is all a
    sequence costs, so admission is by free lanes alone.

    Behind the by-kind interface the decode path speaks: `attend` is the
    whole of a layer's read, decay and update, in place on the (donated)
    stack the model's scan carries, so `append_rows` has nothing left to
    write but the stack the step handed back; `commit_staged` is adoption:
    one lane's entry overwritten with a prefilled session's."""

    kinds = (KV_KIND_STATE,)
    in_place = True

    def __init__(self, model, n_layers: int, slots: int) -> None:
        self.slots = slots
        self.kv = model.init_kv(n_layers, slots, 0)
        c = model.config
        from dnet_tpu.ops.retention import state_entry_bytes

        #: bytes of one lane's state over every layer
        self.entry_bytes = n_layers * state_entry_bytes(c.num_key_value_heads, c.head_dim)
        self.state_counters = _STATE_COUNTERS[model.state_family]
        _STATE_SLOTS.set(slots)

        @jax.named_scope("kv_scatter")
        def adopt(store, row, slot):
            """row leaves [L, 1, ...] -> store[:, slot]."""
            return jax.tree.map(
                lambda s, r: jax.lax.dynamic_update_slice_in_dim(
                    s, r.astype(s.dtype), slot, axis=1
                ),
                store, row,
            )

        self._adopt = instrument_jit(jax.jit(adopt, donate_argnums=(0,)), "kv_scatter")

    def attend(self, pool, kvs, q, rows, tables, pos, kind, layer, impl):
        """Traced: one layer's decode step over every lane.  `kvs` is the
        stack as the scan carries it (`pool`, the stack before the first
        layer, goes unread); rows carries the lanes' new k and v, their
        gates' logs and who is active.  Returns (o, the stack)."""
        from dnet_tpu.ops.retention import retention_step

        o, store = retention_step(
            kvs, q[:, 0], rows["k"], rows["v"], rows["gate"], rows["active"],
            layer, impl=impl,
        )
        return o[:, None], store

    def append_rows(self, rows: dict, phys: dict, off) -> None:
        self.kv = rows

    def commit_staged(self, kv_row: dict, blocks: dict) -> None:
        """blocks: {state: the lane} of one session's [L, 1, ...] entries."""
        self.kv = self._adopt(
            self.kv, kv_row, np.int32(blocks[KV_KIND_STATE])
        )


class KindStore:
    """THE block pool store: every model whose layers keep keys and values.
    Layers are of a KIND (`model.paged_kinds`; None = every layer `full`):
    each kind's layers share a pool `[L_kind, N_kind, block_tokens, KVH*Hd]`
    and a pool manager (kv/paged.py BlockPool) of their own, so a window
    layer's table can hold the blocks inside its window alone while a full
    layer's keeps everything.  Heads are merged into the lane dimension
    ONCE, here: the ragged kernel (ops/paged_attention.py) reads a block as
    `[bt, KVH*Hd]` and takes the layer by index, so no slice or relayout of
    a pool is made per step, and a write that names blocks or rows moves
    those alone (kept with the heads apart, `[L, N, bt, 4, 128]`, XLA tiles
    the pool T(4,128) and copies a WHOLE leaf to T(8,128) and back around
    every scatter).

    `self.kv` is `{kind: {"k": ..., "v": ...}}`; `self.layers[kind]` the
    local layer indices of the kind, in order.  The prefix cache
    (kv/prefix.py) reads and writes the `full` kind through `gather_row`
    and `commit_row`."""

    in_place = False

    def __init__(
        self, model, cfgs: dict, kv_dtype: str, window_width: int = 0,
        session_tokens: int = 0,
    ) -> None:
        self.cfgs = cfgs
        self.cfg = cfgs[KV_KIND_FULL]
        #: the most blocks one sequence's window table holds (0: unknown)
        self.window_width = int(window_width)
        self.block_tokens = bt = self.cfg.block_tokens
        self.layers = {}
        by_layer = model.paged_kinds or (KV_KIND_FULL,) * len(model.layers)
        for i, kind in enumerate(by_layer):
            self.layers[kind] = self.layers.get(kind, ()) + (i,)
        self.kinds = tuple(k for k in KV_KINDS if k in self.layers)
        if set(self.layers) != set(cfgs):
            raise ValueError(f"pool kinds {sorted(cfgs)} != layer kinds {sorted(self.layers)}")
        self.window = int(model.window) if KV_KIND_WINDOW in self.layers else 0
        #: a token's entry: {leaf: (heads, dim)}, the model's word
        #: (RingModel.pool_leaves): "k", "v" of (KVH, Hd), or one latent "c"
        from dnet_tpu.models.base import RingModel

        # (a stand-in that is no RingModel gets the default: k and v of KVH x Hd)
        own = getattr(model, "pool_leaves", None)
        self.leaves = leaves = dict(own() if own else RingModel.pool_leaves(model))
        #: > 0: the entry is latent, its first `latent_rank` lanes the value
        self.latent_rank = int(getattr(model, "latent_rank", 0))
        if session_tokens:
            # blocks are cut out of a staged SESSION row by absolute
            # position: the rows the engines gather into and commit from
            # must be slot-addressed [L, 1, max_seq, KVH, Hd] keys and
            # values.  A rotating window ring (slots are position MOD W) or
            # a cache laid out by kind would commit the wrong rows silently
            n = len(by_layer)
            probe = jax.eval_shape(lambda: model.init_kv(n, 1, session_tokens, kv_dtype))
            want = {leaf: (n, 1, session_tokens) + hd for leaf, hd in leaves.items()}
            shapes = jax.tree.map(jnp.shape, probe)
            if shapes != want:
                raise NotImplementedError(
                    f"paged KV needs slot-addressed session caches, {want}; "
                    f"got {shapes} (rotating ring buffers and per-kind layouts stay dense)"
                )
        dt = jnp.dtype(kv_dtype)
        self.kv = {
            kind: {
                leaf: jnp.zeros((len(idx), cfgs[kind].pool_blocks, bt, h * d), dt)
                for leaf, (h, d) in leaves.items()
            }
            for kind, idx in self.layers.items()
        }
        layers = self.layers

        @jax.named_scope("kv_scatter")
        def commit(pool, dense, block_idx, phys):
            """One staged sequence's blocks into the pools: dense leaves
            [L, 1, S, KVH, Hd]; per kind, logical block block_idx[kind][j]
            of that kind's layers -> pool block phys[kind][j]."""
            out = {}
            for kind, idx in layers.items():
                sel = jnp.asarray(idx, jnp.int32)

                def one(p, d, kind=kind, sel=sel):
                    return _commit_blocks(p, d[sel, 0], block_idx[kind], phys[kind])

                out[kind] = jax.tree.map(one, pool[kind], dense)
            return out

        @jax.named_scope("kv_gather")
        def gather(pool, ids):
            """The full kind's blocks ids [nb] -> one dense row
            [L, 1, nb*bt, KVH, Hd]: the heads split on the gathered row."""

            def one(p, heads):
                g = p[:, ids]  # [L, nb, bt, W]
                return g.reshape(g.shape[0], 1, g.shape[1] * bt, *heads)

            return {leaf: one(p, leaves[leaf]) for leaf, p in pool[KV_KIND_FULL].items()}

        # instrumented: a page-table geometry leak re-tracing these per
        # call shows as climbing dnet_jit_compiles_total{fn=kv_*}
        self._gather = instrument_jit(jax.jit(gather), "kv_gather")
        self._commit = instrument_jit(
            jax.jit(commit, donate_argnums=(0,)), "kv_scatter"
        )
        self._append = instrument_jit(
            jax.jit(self.append_in_program, donate_argnums=(0,)), "kv_append"
        )

    def attend(self, pool, kvs, q, rows, tables, pos, kind, layer, impl):
        """Traced: one layer's decode attention.  The model names the
        layer's index `layer` within its kind and, where there are two, the
        layer's `kind` (its index in KV_KINDS, riding the scan as data);
        the kernel takes the layer out of the kind's stack itself (`kvs`
        goes unread: the pool is never sliced).  Each kind's custom call
        has a name of its own (the trace tells them apart)."""
        from dnet_tpu.ops.paged_attention import paged_attend, paged_attend_latent

        if self.latent_rank:
            # ONE entry a token, shared by the heads: read once by the
            # absorbed kernel (the model's dnet.attn.latent scope is around)
            return paged_attend_latent(
                q, pool[KV_KIND_FULL]["c"], tables[KV_KIND_FULL], pos, rows["c"],
                self.latent_rank, layer, impl=impl,
            )

        def of(name, scope, **kw):
            @jax.named_scope(scope)
            def run():
                return paged_attend(
                    q, pool[name]["k"], pool[name]["v"], tables[name], pos,
                    rows["k"], rows["v"], impl=impl, layer=layer, **kw,
                )

            return run

        full = of(KV_KIND_FULL, SCOPE_ATTN_FULL)
        if KV_KIND_WINDOW not in self.layers:
            return full()
        return jax.lax.cond(
            kind == KV_KINDS.index(KV_KIND_WINDOW),
            of(KV_KIND_WINDOW, SCOPE_ATTN_WINDOW, window=self.window, base=tables["base"]),
            full,
        )

    def append_in_program(self, pool, rows, phys, off):
        """Traced: one new token row per slot into each kind's pool.  rows
        leaves [L, slots, KVH, Hd] (every layer, in model order); phys[kind]
        [slots] the physical block (== pool_blocks, past the block axis,
        drops the lane's write); off [slots] the row inside the block."""
        out = {}
        for kind, idx in self.layers.items():
            sel = jnp.asarray(idx, jnp.int32)

            def one(p, r, kind=kind, sel=sel):
                # ONE plain row scatter into the [Lk*N*bt, W] view (a
                # bitcast).  Indexed as p[:, phys, off] the update spans
                # the layer axis and XLA relayouts the WHOLE pool around
                # the scatter, in and out (the window kind: 2 x 440 MB each
                # way, every decode step)
                Lk, N, bt, W = p.shape
                at = phys[kind]
                row = (jnp.arange(Lk, dtype=jnp.int32)[:, None] * N + at[None]) * bt + off[None]
                row = jnp.where(at[None] < N, row, Lk * N * bt)  # past the end: dropped
                flat = p.reshape(Lk * N * bt, W).at[row.reshape(-1)].set(
                    r[sel].reshape(-1, W).astype(p.dtype), mode="drop"
                )
                return flat.reshape(p.shape)

            out[kind] = jax.tree.map(one, pool[kind], rows)
        return out

    def commit_staged(self, kv_row: dict, blocks: dict) -> None:
        """blocks: {kind: (logical block indices, physical blocks)} of one
        staged [L, 1, S, ...] row.  Widths pad by repeating the last pair (a
        duplicate write of identical content): the full kind's to a power
        of two, the window kind's to the ONE width a window table can
        reach, so the compiled programs are one a power of two and not one
        a pair of them (a prompt of 4224 tokens holds 32 window blocks, one
        of 4300 holds 33)."""
        block_idx, phys = {}, {}
        for kind in self.layers:
            lb, pb = blocks[kind]
            K = _bucket_pow2(max(len(lb), 1))
            if kind == KV_KIND_WINDOW and self.window_width:
                K = max(self.window_width, len(lb))
            lb = list(lb) + [lb[-1]] * (K - len(lb))
            pb = list(pb) + [pb[-1]] * (K - len(pb))
            block_idx[kind] = np.asarray(lb, np.int32)
            phys[kind] = np.asarray(pb, np.int32)
        self.kv = self._commit(self.kv, kv_row, block_idx, phys)

    def append_rows(self, rows: dict, phys: dict, off) -> None:
        """Ragged-decode block append: one new token row per slot, written
        in place (donated pool buffers).  rows leaves [L, slots, KVH, Hd]
        (the step program's stacked per-layer k/v outputs); phys[kind] and
        off [slots] int32 physical block + in-block offset; phys ==
        pool_blocks (out of range, NOT negative) = skip this lane."""
        self.kv = self._append(
            self.kv, rows,
            {k: np.asarray(v, dtype=np.int32) for k, v in phys.items()},
            np.asarray(off, dtype=np.int32),
        )

    # ---- the prefix cache's two calls: the full kind alone --------------
    def gather_row(self, blocks: List[int], width_tokens: int) -> dict:
        """One sequence's blocks as a [L, 1, width_tokens, KVH, Hd] dense
        row (padded with clamped block 0 beyond the table: rows the causal
        mask excludes)."""
        bt = self.block_tokens
        assert width_tokens % bt == 0
        ids = np.zeros(width_tokens // bt, dtype=np.int32)
        ids[: len(blocks)] = blocks
        return self._gather(self.kv, ids)

    def commit_row(
        self, kv_row: dict, logical_blocks: List[int], phys_blocks: List[int]
    ) -> None:
        """Persist blocks of a single-sequence dense row ([L, 1, S, ...]):
        logical block index i of the row -> pool block phys_blocks[i]."""
        if logical_blocks:
            self.commit_staged(kv_row, {KV_KIND_FULL: (logical_blocks, phys_blocks)})


class HybridStore:
    """ONE store for a model that mixes `state` layers with `full` layers
    (models/qwen3_next.py): a sequence holds a lane of recurrent state for
    the former and a table of blocks for the latter, at once.  Made of the
    two layouts that exist: the full kind's pool as `KindStore` keeps it
    (`[L_full, N, block_tokens, KVH*Hd]`, heads merged into the lanes, the
    kernel takes the layer by index), the state kind's entries as
    `StateStore` keeps them (`[L_state, slots, ...]`, a lane IS the
    address).  `self.kv` is `{"full": {"k", "v"}, "state": {...}}`.

    `in_place`: the whole store rides the decode step donated, as the
    carry of the model's scan.  A state layer's `attend` is its family's
    step (`model.state_family`: the delta rule's read, decay and correction,
    or lightning attention's); a full layer's is the kernel's read of
    the pool through the page tables AND the new row's write into the
    lane's block, so `append_rows` has nothing left to write.
    `commit_staged` is adoption: the staged row's blocks into the pool and
    the session's state entry over the lane's, in one program.

    The full kind's leaves are the MODEL's (`pool_leaves`, as KindStore's):
    keys and values, and for a model whose full layers attend a chosen
    subset of blocks (`model.sparse`, ops/sparse_attention.py) the index
    beside them: a leaf `kc` whose rows are not tokens but pooled keys,
    one for `pool_strides()["kc"]` tokens, `[L_full, N, block_tokens /
    stride, KVH*Hd]` under the same page table.  Adoption pools the staged
    row's keys into it; a decode step extends it when its token completes
    a span, in the same donated program as the row's write.

    Admission is by both: a free lane and the blocks of the `full` pool
    (core/batch.py, sched/policy.py).  A lane's blocks are never aliased
    into a prefix cache: the state beside them cannot be cut at a prefix."""

    in_place = True
    kinds = (KV_KIND_FULL, KV_KIND_STATE)

    def __init__(self, model, cfg: PagedKVConfig, slots: int, kv_dtype: str) -> None:
        self.cfg = cfg
        self.slots = slots
        self.block_tokens = bt = cfg.block_tokens
        self.layers = {}
        for i, kind in enumerate(model.paged_kinds):
            self.layers[kind] = self.layers.get(kind, ()) + (i,)
        if set(self.layers) != set(self.kinds):
            raise ValueError(f"layer kinds {sorted(self.layers)} != {sorted(self.kinds)}")
        from dnet_tpu.models.base import RingModel

        # (a stand-in that is no RingModel gets the default: k and v of KVH x Hd)
        own = getattr(model, "pool_leaves", None)
        self.leaves = leaves = dict(own() if own else RingModel.pool_leaves(model))
        #: tokens a row of a leaf stands for, where that is not one
        strides = dict(getattr(model, "pool_strides", dict)())
        #: the full layers' choice of blocks (None: they attend everything)
        self.sparse = sparse = getattr(model, "sparse", None)
        if sparse is not None and (bt % sparse.block_size or set(strides) != {"kc"}):
            raise ValueError(
                f"pool blocks of {bt} tokens do not hold whole blocks of "
                f"{sparse.block_size} (or the index leaf is not `kc`: {strides})"
            )
        dt = jnp.dtype(kv_dtype)
        n_full = len(self.layers[KV_KIND_FULL])
        # the model's own session layout, a lane where a sequence would be
        entries = model.init_kv(len(model.paged_kinds), slots, 0, kv_dtype)
        state_keys = tuple(k for k in entries if k not in ("k", "v"))
        self.kv = {
            KV_KIND_FULL: {
                leaf: jnp.zeros(
                    (n_full, cfg.pool_blocks, bt // strides.get(leaf, 1), h * d), dt
                )
                for leaf, (h, d) in leaves.items()
            },
            KV_KIND_STATE: {k: entries[k] for k in state_keys},
        }
        #: bytes of one lane's state over every state layer
        self.entry_bytes = sum(
            int(np.prod(v.shape[2:])) * v.shape[0] * v.dtype.itemsize
            for v in self.kv[KV_KIND_STATE].values()
        )
        self.state_counters = _STATE_COUNTERS[model.state_family]
        self._state_step = _STATE_STEPS[model.state_family]
        _STATE_SLOTS.set(slots)

        @jax.named_scope("kv_scatter")
        def commit(store, dense, block_idx, phys, slot):
            """One staged sequence into the store: dense {"k", "v":
            [L_full, 1, S, KVH, Hd], state leaves [L_state, 1, ...]}."""

            def blocks(p, d):
                return _commit_blocks(p, d[:, 0], block_idx, phys)

            def lane(s, r):
                return jax.lax.dynamic_update_slice_in_dim(s, r.astype(s.dtype), slot, axis=1)

            full = {
                leaf: blocks(store[KV_KIND_FULL][leaf], dense[leaf]) for leaf in ("k", "v")
            }
            if sparse is not None:
                # the index: the staged keys pooled, a layer at a time, cut
                # into the same blocks (rows of spans the prompt has not
                # completed hold partial means: the index masks them by
                # position, and the decode step that completes one rewrites it)
                from dnet_tpu.ops.sparse_attention import pooled_keys

                rows = dense["k"][:, 0]
                pooled = jax.vmap(
                    lambda r: pooled_keys(r.reshape(r.shape[0], -1), sparse)
                )(rows)
                full["kc"] = _commit_blocks(
                    store[KV_KIND_FULL]["kc"], pooled, block_idx, phys
                )
            return {
                KV_KIND_FULL: full,
                KV_KIND_STATE: {
                    k: lane(store[KV_KIND_STATE][k], dense[k]) for k in state_keys
                },
            }

        self._commit_both = instrument_jit(jax.jit(commit, donate_argnums=(0,)), "kv_scatter")

    def attend(self, pool, kvs, q, rows, tables, pos, kind, layer, impl):
        """Traced: one layer's decode step over every lane.  `kvs` is the
        store as the scan carries it (`pool`, the store before the first
        layer, goes unread); `kind` is the layer's, a static name; `layer`
        its (traced) index within the kind.  Returns (o, the store)."""
        active = rows["active"]
        if kind == KV_KIND_STATE:
            o, state = self._state_step(kvs[KV_KIND_STATE], q, rows, layer, impl)
            return o[:, None], {**kvs, KV_KIND_STATE: state}
        full = kvs[KV_KIND_FULL]
        table = tables[KV_KIND_FULL]
        if self.sparse is not None:
            # the row's write, the index's extension, the choice and the
            # read of the chosen blocks alone
            from dnet_tpu.ops.sparse_attention import sparse_decode

            out, full = sparse_decode(
                full, q, rows["k"], rows["v"], table, pos, active, layer,
                self.sparse, impl=impl,
            )
            return out, {**kvs, KV_KIND_FULL: full}
        from dnet_tpu.ops.paged_attention import paged_attend

        out = paged_attend(
            q, full["k"], full["v"], table, pos, rows["k"], rows["v"],
            impl=impl, layer=layer,
        )
        # the new row into the lane's block: ONE plain row scatter into the
        # [L*N*bt, W] view (KindStore.append_in_program has the why); an
        # idle lane's row lands past the end and is dropped
        bt = self.block_tokens
        Lf, N, _, W = full["k"].shape
        bidx = jnp.clip(pos // bt, 0, table.shape[1] - 1)
        phys = jnp.take_along_axis(table, bidx[:, None], axis=1)[:, 0]
        row = (layer * N + phys) * bt + pos % bt
        row = jnp.where(active, row, Lf * N * bt)

        def write(p, r):
            flat = p.reshape(Lf * N * bt, W).at[row].set(
                r.reshape(-1, W).astype(p.dtype), mode="drop"
            )
            return flat.reshape(p.shape)

        full = {"k": write(full["k"], rows["k"]), "v": write(full["v"], rows["v"])}
        return out, {**kvs, KV_KIND_FULL: full}

    def append_rows(self, rows: dict, phys: dict, off) -> None:
        self.kv = rows

    def commit_staged(self, kv_row: dict, blocks: dict) -> None:
        """blocks: {full: (logical block indices, physical blocks), state:
        the lane} of one session's staged row and entries.  The block list
        pads to a power of two by repeating the last pair (a duplicate
        write of identical content)."""
        lb, pb = blocks[KV_KIND_FULL]
        K = _bucket_pow2(max(len(lb), 1))
        lb = list(lb) + [lb[-1]] * (K - len(lb))
        pb = list(pb) + [pb[-1]] * (K - len(pb))
        self.kv = self._commit_both(
            self.kv, kv_row, np.asarray(lb, np.int32), np.asarray(pb, np.int32),
            np.int32(blocks[KV_KIND_STATE]),
        )
