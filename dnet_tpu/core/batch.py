"""Continuous batching: N session slots share one batched decode program.

The reference serves one in-flight sequence per nonce and leaves batching
absent (SURVEY.md §2.8 "Speculative / batching schedulers: absent");
`max_concurrent_requests` merely interleaves requests through one
single-sequence engine.  On TPU, batch-1 decode is weight-bound — the MXU
reads every weight to produce ONE token — so lanes 2..N of a batched matmul
are nearly free.  This engine turns concurrency into throughput:

- A request owns one of `slots` lanes from prefill to EOS.  Its KV cache
  is a page table over the shared block pool (kv/), which the decode step
  attends IN PLACE, wherever the model and the cache allow it; dense rows
  ([L, slots, S, ...]) serve the rest (`kv_layout` below decides).
- The decode step is `jax.vmap` of the SAME single-example forward+sample
  the LocalEngine uses (per-slot pos / sampling params / RNG key / active
  flag), jitted once — adding or finishing requests never recompiles.
- Inactive lanes compute garbage that is discarded: their `active=False`
  flag gates the KV write (kv_commit) and the repetition-count update, so
  slot state cannot be corrupted.  This trades a constant slot's worth of
  (weight-bound, ~free) FLOPs for a completely static program shape.
- Prefill runs per-request on the LocalEngine's B=1 bucket programs, then
  the session's KV row is inserted into the batched cache.

Per-slot sampling params are traced vectors, so mixed temperatures /
top-p's batch together (same property as core/sampler.py's traced scalars).
"""

from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.core.engine import LocalEngine, apply_whole, bucket_length, count_expert_rows
from dnet_tpu.core.sampler import (
    MAX_LOGIT_BIAS,
    MAX_TOP_LOGPROBS,
    SampleParams,
    SampleResult,
    encode_logit_bias,
    sample,
)
from dnet_tpu.core.types import DecodingParams, EngineCapabilityError
from dnet_tpu.kv import (
    BlockPool,
    HybridStore,
    KindStore,
    KVPoolExhausted,
    PagedKVConfig,
    PagedPrefixCache,
    PageTable,
    StateStore,
    window_blocks,
    window_first_block,
)
from dnet_tpu.kv.store import _bucket_pow2
from dnet_tpu.obs import get_recorder, metric, span
from dnet_tpu.obs.jit import instrument_jit
from dnet_tpu.obs.phases import (
    KV_KIND_FULL,
    KV_KIND_STATE,
    KV_KIND_WINDOW,
    SPAN_DECODE_LAUNCH,
    SPAN_DECODE_PREPARE,
    SPAN_DECODE_READBACK,
    SPAN_DECODE_UNPACK,
)
from dnet_tpu.ops.flash_attention import flash_tiles
from dnet_tpu.utils.logger import get_logger

log = get_logger()

_DECODE_STEP_MS = metric("dnet_decode_step_ms")
_DECODE_DISPATCHES = metric("dnet_decode_dispatch_total")
_DECODE_SLOT_STEPS = metric("dnet_decode_slot_steps_total")
_DECODE_LANE_STEPS = metric("dnet_decode_lane_steps_total")
_DECODE_TOKENS = metric("dnet_decode_tokens_total")
_DECODE_BUFFER_DROPPED = metric("dnet_decode_buffer_dropped_total")
_DECODE_CHAINED = metric("dnet_decode_chained_lanes_total")
_DECODE_SURPLUS = metric("dnet_decode_surplus_steps_total")
_MOE_ASSIGNMENTS = metric("dnet_moe_assignments_total")
_MLA_TOKENS = metric("dnet_mla_tokens_total")
_MLA_LATENT_BYTES = metric("dnet_mla_latent_bytes_total")
_MLA_EXPANDED = metric("dnet_mla_expanded_tokens_total")
_SPARSE_BLOCKS = metric("dnet_sparse_blocks_total")
_SPARSE_TOKENS = metric("dnet_sparse_tokens_total")
_SPARSE_INDEX_ROWS = metric("dnet_sparse_index_rows_total")
_FLASH_TILES = metric("dnet_flash_tiles_total")
_STATE_SLOTS_USED = metric("dnet_state_slots_used")


@dataclass
class DecodeFlight:
    """What `decode_launch` hands `decode_read`: the lanes answered on the
    host already, and the dispatch the device still owes an answer for."""

    #: answered from a buffer / a verify block
    out: Dict[str, SampleResult] = field(default_factory=dict)
    errors: Dict[str, str] = field(default_factory=dict)
    order: Dict[str, int] = field(default_factory=dict)  # nonce -> slot sent
    #: the step's results, ON THE DEVICE; None = nothing was sent
    src: Optional[SampleResult] = None
    moe: Any = None  # [held, elsewhere] summed by the dispatch, on the device
    host_s: float = 0.0  # host time of the launch half
    #: the launch half itself read the device (a verify block's acceptance
    #: counts): work enqueued after it did not overlap this dispatch
    blocked: bool = False
    #: lanes of `order` whose input token this dispatch took from the
    #: flight before it, on the device (`decode_launch(chain=)`)
    chained: frozenset = frozenset()

    def answered(self) -> Tuple[Dict[str, SampleResult], Dict[str, str]]:
        """What the launch half settled on the host (lanes answered from
        their buffer, lanes refused), handed out ONCE: a caller that keeps
        this flight in the air past its tick takes them now, and
        `decode_read` then returns the dispatch's rows alone."""
        out, errors = self.out, self.errors
        self.out, self.errors = {}, {}
        return out, errors


#: a lane's host token row where the step takes the lane's input from the
#: flight before it instead (`_chain_tokens`): no token id is negative
CHAINED = -1


def _chain_tokens(token, prev_token):
    """The step's input tokens [slots, 1], inside its program: a lane whose
    host row says CHAINED takes what the flight before sampled for it,
    which is still on the device; every other lane keeps its host token."""
    return jnp.where(token < 0, prev_token, token)


def takes_another(budgets, nonce) -> bool:
    """Will this lane's driver take a token AFTER the one it has asked
    for?  (`budgets`: nonce -> tokens it still accepts, that one included;
    requests end by `max_tokens`, so the driver knows.)  Only then may the
    lane step again before that token has been read."""
    return ((budgets or {}).get(nonce) or 1) >= 2


#: the most tokens past a lane's `pos` that a decode step's window table
#: covers before the blocks behind the window are given back: the step in
#: flight and the one chained to it (`_extend_window_tables`)
WINDOW_STEP_TOKENS = 2

KV_PAGED = "paged"  # page tables over the block pool, attended in place
KV_DENSE = "dense"  # [L, slots, max_seq, ...] rows, one a lane
KV_STATE = "state"  # one recurrent state entry a lane, updated in place
KV_HYBRID = "state+paged"  # a lane of state AND a page table, one sequence


def kv_layout(
    model, kv_quant_bits: int, spec_lookahead: int, max_seq: int
) -> Tuple[str, str]:
    """THE rule for a batched engine's KV cache: (KV_PAGED | KV_DENSE |
    KV_STATE | KV_HYBRID, why), read off `model.paged_kinds`.

    A model whose layers keep a recurrent state and no keys (every kind
    `state`) serves from the state store, one entry a lane, whatever was
    asked of the cache: there are no keys to page, quantize or rewind.  A
    model that MIXES state layers with key-value layers serves from the
    store that holds both (kv/store.py HybridStore): a lane of state and a
    page table for the same sequence; a state cannot be rewound, so
    speculation is off, and where the pool's geometry refuses max_seq the
    load fails (dense slots have no lane of state to offer).  Any other
    model: the paged pool attended in
    place, unless something the code can see
    rules it out: per-lane speculation was asked for (its verify blocks
    rewind a dense cache: an explicit request outranks a derived default),
    the kernel refuses the model or the cache
    (ops/paged_attention.ragged_refusal), or the pool's geometry refuses
    max_seq (PagedKVConfig.from_settings).  Dense slots serve those, under
    the same scheduler."""
    from dnet_tpu.ops.paged_attention import ragged_refusal

    kinds = set(getattr(model, "paged_kinds", None) or ())
    if kinds == {KV_KIND_STATE}:
        return KV_STATE, "one recurrent state entry a lane, updated in place"
    if KV_KIND_STATE in kinds:
        why = ragged_refusal(model, kv_quant_bits)
        if why is not None:
            raise EngineCapabilityError(
                f"{model.config.model_type} mixes state and key-value layers: {why}"
            )
        PagedKVConfig.from_settings(max_seq)  # a refusing geometry fails the load
        return KV_HYBRID, (
            "a lane of recurrent state and a page table over the block pool "
            "for the same sequence, both updated in place"
        )
    if spec_lookahead > 0:
        return KV_DENSE, "per-lane speculation needs the dense cache"
    why = ragged_refusal(model, kv_quant_bits)
    if why is not None:
        return KV_DENSE, why
    try:
        PagedKVConfig.from_settings(max_seq)
    except ValueError as exc:
        return KV_DENSE, str(exc)
    return KV_PAGED, "the block pool, attended in place through the page tables"


class BatchedEngine:
    """LocalEngine-compatible surface plus `decode_batch` for the scheduler.

    `kv_paged`: None derives the KV layout by `kv_layout`; False is the
    explicit dense engine (the tests' reference); True insists on the pool
    even where speculation was asked for (which it then switches off), and
    still falls back to dense slots where the pool is refused.  The
    prefix-cache capacity belongs to whichever layout serves: block
    aliasing over the pool, or the inner B=1 engine's snapshots."""

    token_result = staticmethod(LocalEngine.token_result)

    def __init__(self, model_dir: str | Path, slots: int = 8, **engine_kwargs):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        paged = engine_kwargs.pop("kv_paged", None)
        prefix_size = int(engine_kwargs.pop("prefix_cache_size", 0) or 0)
        self.eng = LocalEngine(model_dir, **engine_kwargs)
        self._init_state(slots, paged=paged, prefix_size=prefix_size)

    @classmethod
    def from_params(
        cls, config, window_params, edge_params, *, slots: int = 8, **kw
    ) -> "BatchedEngine":
        """Build around already-materialised params (the zero-egress bench
        path, mirroring LocalEngine.from_params)."""
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self = cls.__new__(cls)
        paged = kw.pop("kv_paged", None)
        self.eng = LocalEngine.from_params(config, window_params, edge_params, **kw)
        self._init_state(slots, paged=paged)
        return self

    def _init_state(
        self, slots: int, paged: Optional[bool] = None, prefix_size: int = 0
    ) -> None:
        # typed load-time refusals: the HTTP layer maps these to 422
        # (operator/config error).  A served load never gets here
        # (api/model_manager.py: serving_plan reads the same two facts).
        if self.eng.plan.streams_weights:
            raise EngineCapabilityError(
                "continuous batching needs resident weights (fit policy); "
                "weight streaming serves single-sequence"
            )
        if not self.eng.model.supports_kv_commit:
            # fail at load, not mid-stream on the first batched step
            raise EngineCapabilityError(
                f"continuous batching not supported for "
                f"{self.eng.config.model_type} (no gated KV writes yet)"
            )
        self.slots = slots
        self.max_seq = self.eng.max_seq
        self.config = self.eng.config
        self.model = self.eng.model
        self.weight_quant_bits = self.eng.weight_quant_bits
        # per-LANE speculative decoding (VERDICT r3 next #5): spec_lookahead
        # flows through engine_kwargs into the inner LocalEngine, whose B=1
        # prefill paths maintain the per-session history buffers we adopt
        self.spec_lookahead = self.eng.spec_lookahead
        if self.spec_lookahead > 0 and not self.eng.model.kv_rewindable(self.max_seq):
            log.warning(
                "speculative decoding needs a rewind-safe cache layout; "
                "%s uses rotating SWA buffers — disabled for this model",
                self.eng.config.model_type,
            )
            self.spec_lookahead = 0
        m = self.eng.model
        if paged is False:
            layout, why = KV_DENSE, "dense slots asked for"
        else:
            # an explicit True outranks the speculation it then switches off
            layout, why = kv_layout(
                m, self.eng.kv_quant_bits,
                self.spec_lookahead if paged is None else 0, self.max_seq,
            )
        # the books are kept by KIND of layer (obs/phases.py KV_KINDS): a
        # pool manager and per-slot tables a kind.  Every model has the
        # `full` kind — kv_pool and _tables are its entries, and what
        # admission and prefix sharing are functions of; a model with
        # WINDOW layers among full ones (model.paged_kinds) has the
        # `window` kind too, whose tables hold only the blocks inside the
        # window.  All empty under dense slots.
        # A model of the `state` kind has a store (kv_store) and NO pool:
        # kv_pool stays None, which is what admission, preemption and
        # prefix sharing read, so a lane is all its sequences cost.  A
        # model that MIXES state layers with full ones has both: a store
        # that is updated in place AND the full kind's pool and tables, so
        # a sequence costs a lane and its blocks, and both are admitted by.
        self.kv_pool: Optional[BlockPool] = None
        self.kv_store = None  # a store of kv/store.py, whatever the kinds
        #: why prefix sharing is off although it was asked for (/health)
        self.prefix_refusal: Optional[str] = None
        self.paged_prefix: Optional[PagedPrefixCache] = None
        self._kv_cfg: Optional[PagedKVConfig] = None
        self._tables: List[Optional[PageTable]] = [None] * slots
        self._adopt: Dict[str, Tuple[int, List[int], int]] = {}
        self.kv_pools: Dict[str, BlockPool] = {}
        self._kind_tables: Dict[str, List[Optional[PageTable]]] = {}
        self._window = 0
        if layout == KV_PAGED:
            self._init_pool(m, slots, prefix_size)
        elif layout == KV_HYBRID:
            self._init_hybrid_store(m, slots, prefix_size)
        elif layout == KV_STATE:
            self._init_state_store(m, slots, prefix_size)
        else:
            (log.info if paged is False else log.warning)(
                "KV cache: dense slots (%s)", why
            )
            if prefix_size > 0:
                from dnet_tpu.core.prefix_cache import PrefixCache

                self.eng.prefix_cache = PrefixCache(prefix_size)
        #: > 0: the pool's entry is latent (models/deepseek_v2.py); the bytes
        #: of ONE token's entry over every layer BY THE ALGORITHM (the lanes
        #: the pool pads it with are not in it), what dnet_mla_latent_bytes
        #: counts in
        self._latent_entry_bytes = (
            m.latent_dim * jnp.dtype(self.eng.kv_dtype).itemsize * len(m.layers)
            if getattr(self.kv_store, "latent_rank", 0) else 0
        )
        #: the full layers' choice of blocks (ops/sparse_attention.py
        #: SparseConfig; None: they attend everything) and how many there are
        self._sparse = getattr(self.kv_store, "sparse", None)
        self._sparse_layers = (
            len(self.kv_store.layers[KV_KIND_FULL]) if self._sparse is not None else 0
        )
        #: (kind, window) -> how many layers of it a prefill chunk attends
        #: through the flash kernel (dnet_flash_tiles_total)
        self._flash_layers = Counter(m.flash_layers())
        self.kv = (
            None
            if self.kv_store is not None
            else m.init_kv(
                len(m.layers), slots, self.max_seq, self.eng.kv_dtype,
                quant_bits=self.eng.kv_quant_bits,
            )
        )
        V = self.config.vocab_size
        self.counts = jnp.zeros((slots, V), dtype=jnp.int32)
        # what a step with no flight before it takes in the place of that
        # flight's tokens (no lane reads it): the same program either way
        self._no_token = jnp.zeros((slots, 1), dtype=jnp.int32)
        self.keys = jax.random.split(
            jax.random.key(int.from_bytes(__import__("os").urandom(4), "little")),
            slots,
        )
        self.pos = np.zeros(slots, dtype=np.int64)  # host-side per-slot length
        self.last_used = np.zeros(slots, dtype=np.float64)
        self.slot_of: Dict[str, int] = {}  # nonce -> slot
        self._free: List[int] = list(range(slots))
        # tokens computed and not yet handed to the driver (nonce -> FIFO):
        # a verify block's later rows, the token a late driver had not asked
        # for when its step was read; dropped with the session
        self._buffer: Dict[str, List[SampleResult]] = {}
        # per-nonce [blocks, emitted] acceptance stats (adaptive spec gate)
        self._spec_stats: Dict[str, List[int]] = {}
        self.hist = (
            jnp.zeros((slots, self.max_seq), dtype=jnp.int32)
            if self.spec_lookahead > 0
            else None
        )
        self._build()

    def _init_pool(self, m, slots: int, prefix_size: int) -> None:
        """The pool(s), their managers and the per-slot tables (KV_PAGED)."""
        windowed = KV_KIND_WINDOW in (m.paged_kinds or ())
        if windowed and prefix_size:
            # sharing a prefix's window blocks is not sound: the donor
            # gives them back as it advances
            log.warning(
                "paged prefix sharing is OFF for %s: window layers give "
                "blocks back, so a prefix entry cannot alias them; "
                "DNET_API_PREFIX_CACHE=%d is ignored",
                self.eng.config.model_type, prefix_size,
            )
            prefix_size = 0
        cfg = PagedKVConfig.from_settings(self.max_seq, slots=slots + prefix_size)
        cfgs, per_slot = {KV_KIND_FULL: cfg}, 0
        if windowed:
            # the window kind's pool is sized so that it can never be what
            # admission waits for: every slot may hold the most blocks a
            # window table ever has
            from dnet_tpu.config import get_settings

            step = max(get_settings().sched.prefill_chunk_cap(), WINDOW_STEP_TOKENS)
            per_slot = window_blocks(int(m.window), cfg.block_tokens, step)
            cfgs[KV_KIND_WINDOW] = PagedKVConfig(cfg.block_tokens, slots * per_slot)
        store = KindStore(
            m, cfgs, self.eng.kv_dtype, window_width=per_slot,
            session_tokens=self.max_seq,
        )
        self._kv_cfg = cfg
        self.kv_pool = BlockPool(cfg)
        self.kv_store = store
        self.kv_pools = {KV_KIND_FULL: self.kv_pool}
        self._kind_tables = {KV_KIND_FULL: self._tables}
        if windowed:
            wcfg = store.cfgs[KV_KIND_WINDOW]
            self._window = int(m.window)
            self.kv_pools[KV_KIND_WINDOW] = BlockPool(wcfg, kind=KV_KIND_WINDOW)
            self._kind_tables[KV_KIND_WINDOW] = [None] * slots
            log.info(
                "window layers page apart: %d blocks (%d a slot) for "
                "a window of %d tokens",
                wcfg.pool_blocks, wcfg.pool_blocks // slots, self._window,
            )
        if prefix_size > 0:
            self.paged_prefix = PagedPrefixCache(self.kv_pool, store, prefix_size)
        if self.spec_lookahead > 0:
            log.warning(
                "per-lane speculation disabled under paged KV "
                "(verify blocks rewind a dense cache)"
            )
            self.spec_lookahead = 0
        log.info(
            "paged KV on: %d blocks x %d tokens serving %d slots",
            cfg.pool_blocks, cfg.block_tokens, slots,
        )

    def _refuse_prefix_sharing(self, prefix_size: int) -> None:
        """A state holds the whole sequence folded together and cannot be
        cut at a prefix: sharing is refused, with its reason (/health)."""
        if prefix_size:
            self.prefix_refusal = (
                f"{self.eng.config.model_type} keeps a recurrent state, which "
                "cannot be cut at a prefix (snapshots at block edges are not "
                f"built): DNET_API_PREFIX_CACHE={prefix_size} is ignored"
            )
            log.warning("prefix sharing is OFF: %s", self.prefix_refusal)
        self.spec_lookahead = 0  # kv_rewindable is False: already warned

    def _init_hybrid_store(self, m, slots: int, prefix_size: int) -> None:
        """A lane of state AND a page table a sequence (KV_HYBRID): the
        `full` kind's pool, manager and tables as `_init_pool` keeps them,
        the `state` kind's entries a lane, in one store.  No prefix cache:
        a lane's blocks are never aliased, because the state beside them
        cannot be cut where the blocks can."""
        self._refuse_prefix_sharing(prefix_size)
        cfg = PagedKVConfig.from_settings(self.max_seq, slots=slots)
        self._kv_cfg = cfg
        self.kv_pool = BlockPool(cfg)
        self.kv_store = HybridStore(m, cfg, slots, self.eng.kv_dtype)
        self.kv_pools = {KV_KIND_FULL: self.kv_pool}
        self._kind_tables = {KV_KIND_FULL: self._tables}
        _STATE_SLOTS_USED.set(0)
        log.info(
            "hybrid store on: %d lanes x %.1f MB of state (%d layers) beside "
            "%d blocks x %d tokens (%d layers)",
            slots, self.kv_store.entry_bytes / 1e6,
            len(self.kv_store.layers[KV_KIND_STATE]), cfg.pool_blocks,
            cfg.block_tokens, len(self.kv_store.layers[KV_KIND_FULL]),
        )

    def _init_state_store(self, m, slots: int, prefix_size: int) -> None:
        """The state kind's store (KV_STATE): an entry a lane and nothing
        to manage."""
        self._refuse_prefix_sharing(prefix_size)
        if self.eng.kv_quant_bits:
            log.warning(
                "DNET_KV_BITS=%d is ignored: a state entry is float32",
                self.eng.kv_quant_bits,
            )
        self.kv_store = StateStore(m, len(m.layers), slots)
        _STATE_SLOTS_USED.set(0)
        log.info(
            "state store on: %d lanes x %.1f MB (%d layers), no blocks",
            slots, self.kv_store.entry_bytes / 1e6, len(m.layers),
        )

    # ---- program ------------------------------------------------------
    def _build(self) -> None:
        self._build_adopt()
        if self.kv_store is not None:
            self._build_ragged()
            return
        model = self.eng.model

        @jax.named_scope("batched_step")
        def one(wp, ep, token, kv, pos, active, sp, key, counts):
            """Single-example decode+sample; vmapped over the slot axis.
            kv leaves arrive batch-axis-stripped [L, S, ...]: re-add B=1."""
            kv = jax.tree.map(lambda a: a[:, None], kv)
            x = model.embed(ep, token[None, :])  # [1, 1, D]
            x, kv = model.apply_window(wp, x, kv, pos, kv_commit=active)
            x = model.normalize(ep, x[:, -1:])
            logits = model.lm_project(ep, x)[:, 0]  # [1, V]
            new_key, step_key = jax.random.split(key)
            res = sample(logits, sp, step_key, token_counts=counts[None])
            counts = counts.at[res.token[0]].add(jnp.where(active, 1, 0))
            kv = jax.tree.map(lambda a: a[:, 0], kv)
            # inactive lanes must not advance their RNG stream either, or a
            # seeded request's tokens would depend on unrelated traffic
            key = jax.random.wrap_key_data(
                jnp.where(
                    active, jax.random.key_data(new_key), jax.random.key_data(key)
                )
            )
            return res, kv, counts, key

        kv_axes = jax.tree.map(lambda _: 1, self.kv)
        sp_axes = SampleParams(0, 0, 0, 0, 0, 0, 0, 0)
        self._vmapped = jax.vmap(
            one,
            in_axes=(None, None, 0, kv_axes, 0, 0, sp_axes, 0, 0),
            out_axes=(0, kv_axes, 0, 0),
        )

        def step(wp, ep, token, kv, pos, active, sp, keys, counts, prev_token):
            return self._vmapped(
                wp, ep, _chain_tokens(token, prev_token), kv, pos, active, sp,
                keys, counts,
            )

        self._step = instrument_jit(
            jax.jit(step, donate_argnums=(3, 8)), "batched_step"
        )

        L = self.spec_lookahead
        if L > 0:
            from dnet_tpu.core.spec import accept_drafts, ngram_draft

            @jax.named_scope("batched_spec")
            def one_spec(wp, ep, token, hist, kv, pos, active):
                """One per-lane verify block (vmapped): commit the fed
                token, draft L by prompt-lookup against THIS lane's history,
                verify in one (L+1)-wide forward, emit the agreeing prefix.
                Lanes accept independently — the host advances each slot by
                its own emitted count (uneven progress is the point)."""
                hist0 = hist
                hist = jax.lax.dynamic_update_slice_in_dim(hist, token, pos, axis=0)
                drafts = ngram_draft(hist[None], pos + 1, L)[0]  # [L]
                hist = jax.lax.dynamic_update_slice_in_dim(
                    hist, drafts, pos + 1, axis=0
                )
                # non-speculating lanes ride along with garbage inputs; their
                # history must stay untouched (the hist twin of kv_commit)
                hist = jnp.where(active, hist, hist0)
                block = jnp.concatenate([token, drafts])[None, :]  # [1, L+1]
                kv = jax.tree.map(lambda a: a[:, None], kv)
                x = model.embed(ep, block)
                x, kv = model.apply_window(
                    wp, x, kv, pos, kv_commit=active, t_real=L + 1
                )
                x = model.normalize(ep, x)
                logits = model.lm_project(ep, x)[0]  # [L+1, V]
                preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                _, out = accept_drafts(preds[None], drafts[None])
                kv = jax.tree.map(lambda a: a[:, 0], kv)
                return out[0], hist, kv

            self._spec_vmapped = jax.vmap(
                one_spec,
                in_axes=(None, None, 0, 0, kv_axes, 0, 0),
                out_axes=(0, 0, kv_axes),
            )
            self._spec_step = instrument_jit(
                jax.jit(self._spec_vmapped, donate_argnums=(3, 4)),
                "batched_spec",
            )

    def _build_adopt(self) -> None:
        """The program that writes a prefilled session's sampling state
        into its lane (and, under dense slots, its KV row): everything
        `adopt_prefilled` moves that the store's own commit does not, in
        ONE launch, the engine's arrays donated.  `slot` is traced: one
        program whatever the lane."""

        @jax.named_scope("adopt_lane")
        def adopt_lane(kv, counts, keys, hist, slot, row, row_counts, key, row_hist):
            if kv is not None:
                kv = jax.tree.map(
                    lambda big, one: jax.lax.dynamic_update_slice_in_dim(
                        big, one.astype(big.dtype), slot, axis=1
                    ),
                    kv, row,
                )
            counts = counts.at[slot].set(row_counts[0])
            keys = keys.at[slot].set(key)
            if hist is not None:
                hist = hist.at[slot].set(row_hist[0])
            return kv, counts, keys, hist

        self._adopt_lane = instrument_jit(
            jax.jit(adopt_lane, donate_argnums=(0, 1, 2, 3)), "adopt_lane"
        )

    def _build_ragged(self) -> None:
        """The pool's decode program (ops/paged_attention.py): one step
        that reads the block pool IN PLACE — page tables and per-slot
        positions ride along as the kernel's scalar-prefetched block index
        map — and hands back each lane's new K/V rows, which the store's
        kv_append program block-appends behind it.  The per-slot
        forward is the SAME math as the vmapped dense program: the model's
        norm/rope/MLP stack runs unchanged (apply_window's attend_fn hook
        swaps only the cache write + attention read), and sampling vmaps
        the identical per-lane tail, so greedy streams are parity-testable
        against the dense engine byte for byte."""
        from dnet_tpu.ops.paged_attention import paged_attend_impl

        model = self.eng.model
        impl = paged_attend_impl()
        store = self.kv_store  # it knows the pools' layout, by kind
        sp_axes = SampleParams(0, 0, 0, 0, 0, 0, 0, 0)
        self._moe_reported = bool(getattr(model, "reports_moe_held", False))

        def one_sample(logits, active, sp, key, counts):
            """Per-lane sampling tail, identical to the vmapped `one()`:
            inactive lanes advance neither counts nor their RNG stream."""
            new_key, step_key = jax.random.split(key)
            res = sample(logits[None], sp, step_key, token_counts=counts[None])
            counts = counts.at[res.token[0]].add(jnp.where(active, 1, 0))
            key = jax.random.wrap_key_data(
                jnp.where(
                    active, jax.random.key_data(new_key), jax.random.key_data(key)
                )
            )
            return res, counts, key

        vsample = jax.vmap(one_sample, in_axes=(0, 0, sp_axes, 0, 0))

        @jax.named_scope("paged_attend")
        def ragged_step(wp, ep, token, pool, tables, pos, active, sp, keys,
                        counts, prev_token):
            """One batched decode step against the pool (READ-ONLY here):
            returns the sampled results plus the stacked per-layer new K/V
            rows for the kv_append program.  tables: each kind's
            [slots, nb] int32 (bucketed), and with window layers their
            tables' `base` (_table_ids); pos [slots] int32 live pool rows
            per slot.  prev_token: the tokens of the flight before, for
            the lanes chained to it."""
            token = _chain_tokens(token, prev_token)

            def attend_fn(q, k_new, v_new, kvs, kind=None, layer=None, gate=None):
                # the model names the layer's index within its kind (and
                # the kind, where it has two); the pool is closed over.  A
                # token's new rows go by the pool's leaves: keys and values,
                # or a latent model's ONE entry (v_new None)
                new = [a[:, 0] for a in (k_new, v_new) if a is not None]
                rows = dict(zip(getattr(store, "leaves", ("k", "v")), new))
                if store.in_place:
                    # the state kind: `kvs` is the stack the scan carries,
                    # and the step is the read AND the write, for the
                    # active lanes alone: attend returns (attn, the stack).
                    # A gate that is a dict is the layer's own affair (a
                    # state layer of a hybrid model), handed on as it is
                    if gate is not None and not isinstance(gate, dict):
                        gate = gate[:, 0]
                    rows.update(gate=gate, active=active)
                out = store.attend(pool, kvs, q, rows, tables, pos, kind, layer, impl)
                return out if store.in_place else (out, rows)

            x = model.embed(ep, token)  # [slots, 1, D]
            x, rows = apply_whole(
                model, wp, x, pool, pos[:, None], attend_fn=attend_fn
            )
            # a model with an expert share says how many of each lane's
            # chosen experts it holds (dnet_moe_assignments_total)
            held = rows.pop("moe_held", None)  # [L, slots, 1]
            # and, where it says so, WHICH of the held experts each lane
            # chose (dnet_moe_experts_visited_total)
            took = rows.pop("moe_chosen", None)  # [L, slots, 1, E] bool
            if held is None:
                moe = jnp.zeros((2,), jnp.int32)
            else:
                live = active.astype(jnp.int32)
                mine = jnp.sum(held[..., 0] * live[None, :])
                chosen = held.shape[0] * self.config.num_experts_per_tok * jnp.sum(live)
                books = [mine, chosen - mine]
                if took is not None:
                    # a third entry for the model that reports it alone: the
                    # others' step stays the program it was.  (Imported below
                    # every kernel's call site on purpose: a line added above
                    # one re-compiles it, PERF.md section 6, PR 30)
                    from dnet_tpu.ops.moe import experts_visited

                    books.append(experts_visited(took[:, :, 0], active))
                moe = jnp.stack(books).astype(jnp.int32)
            x = model.normalize(ep, x[:, -1:])
            logits = model.lm_project(ep, x)[:, 0]  # [slots, V]
            res, counts, keys = vsample(logits, active, sp, keys, counts)
            return res, rows, counts, keys, moe

        # a store updated in place rides the step donated (and comes back
        # as `rows`); a pool is read only, its new rows appended after
        donate = (3, 9) if store.in_place else (9,)
        self._ragged_step = instrument_jit(
            jax.jit(ragged_step, donate_argnums=donate), "paged_attend"
        )

    # ---- slot lifecycle ----------------------------------------------
    def alloc_slot(self, nonce: str) -> int:
        if nonce in self.slot_of:
            return self.slot_of[nonce]
        if not self._free:
            raise RuntimeError(f"no free batch slots (capacity {self.slots})")
        slot = self._free.pop(0)
        self.slot_of[nonce] = slot
        self.pos[slot] = 0
        self.last_used[slot] = time.time()
        self._book_lanes()
        return slot

    def _book_lanes(self) -> None:
        if self.kv_store is not None and self.kv_store.in_place:
            _STATE_SLOTS_USED.set(len(self.slot_of))

    @property
    def _block_tokens(self) -> int:
        """Tokens a block of the pool holds (1 where no pool is: the state
        kind's lanes have no blocks to offset into)."""
        return self._kv_cfg.block_tokens if self._kv_cfg is not None else 1

    def free_slot(self, nonce: str) -> None:
        dropped = self._buffer.pop(nonce, None)
        if dropped:
            # computed on the device, never delivered (warm-up's throwaway
            # session pops its own buffer and is not counted)
            _DECODE_BUFFER_DROPPED.inc(len(dropped))
        self._spec_stats.pop(nonce, None)
        stash = self._adopt.pop(nonce, None)
        if stash is not None and self.kv_pool is not None:
            # adopted-but-never-committed prefix references (cancel race)
            self.kv_pool.free_blocks(stash[1])
        slot = self.slot_of.pop(nonce, None)
        if slot is not None:
            if self.kv_pool is not None:
                # block-table release: the whole point of paging — a
                # finished request's blocks return to the free list (or
                # drop a refcount on shared prefix blocks)
                for kind, pool in self.kv_pools.items():
                    tables = self._kind_tables[kind]
                    tbl, tables[slot] = tables[slot], None
                    pool.release_table(tbl)
            # the lane's counts, key and history stay as they are: nothing
            # reads an inactive lane's, and adoption overwrites them whole
            self.pos[slot] = 0
            self._free.append(slot)
            self._book_lanes()

    def end_session(self, nonce: str) -> None:
        self.free_slot(nonce)
        self.eng.end_session(nonce)

    def reset(self) -> None:
        for nonce in list(self.slot_of):
            self.free_slot(nonce)
        self.eng.reset()

    def sweep_sessions(self, ttl_s: float = 600.0) -> int:
        now = time.time()
        dead = [
            n for n, s in self.slot_of.items()
            if now - self.last_used[s] > ttl_s
        ]
        for n in dead:
            self.free_slot(n)
        return len(dead) + self.eng.sweep_sessions()

    def close(self) -> None:
        self.reset()
        self.eng.close()

    @property
    def sessions(self):  # adapter compatibility (membership checks)
        return self.slot_of

    # ---- inference ----------------------------------------------------
    def seed_from_prefix(self, nonce, full_ids, seed=None) -> int:
        """Over the pool: a PrefixIndex hit resolves to SHARED refcounted
        blocks — the full blocks alias straight into this request's future
        page table (no copy); only the staging dense row for the inner
        B=1 prefill is gathered.  Dense slots defer to the inner engine's
        snapshot cache."""
        if self.kv_pool is None:
            return self.eng.seed_from_prefix(nonce, full_ids, seed)
        if self.paged_prefix is None or nonce in self.eng.sessions:
            return 0
        full = list(full_ids)
        hit = self.paged_prefix.lookup_blocks(full)
        if hit is None:
            return 0
        n, blocks, n_full = hit
        kv_row = self.kv_store.gather_row(blocks, self.max_seq)
        self.eng._restore_session(nonce, full, n, kv_row, seed)
        self._adopt[nonce] = (n, blocks, n_full)
        get_recorder().span(nonce, "prefix_cache_hit", 0.0, tokens=n)
        return n

    def store_prefix(self, nonce, full_ids) -> None:
        if self.kv_pool is None:
            return self.eng.store_prefix(nonce, full_ids)
        if self.paged_prefix is None:
            return
        full = list(full_ids)
        slot = self.slot_of.get(nonce)
        if (
            slot is not None
            and self._tables[slot] is not None
            and int(self.pos[slot]) == len(full)
        ):
            # adopted slot: snapshot by ALIASING the live table (zero copy)
            self.paged_prefix.store_blocks(
                full, len(full), self._tables[slot].blocks
            )
            return
        sess = self.eng.sessions.get(nonce)
        if sess is not None and sess.kv is not None and sess.pos == len(full):
            # still staging on the inner engine (chunked prefill): commit
            # tail blocks, dedup the parent prefix block-level
            self.paged_prefix.store(full, sess.kv)

    def reserve_slot(self, nonce) -> None:
        """Claim a batch slot BEFORE chunked prefill burns any compute
        (same fail-fast invariant as prefill_and_sample)."""
        self.alloc_slot(nonce)

    def prefill_chunk(self, nonce, ids, seed=None):
        """One prompt chunk on the B=1 bucket program (continuation when the
        session already exists); returns last-position logits.  The adapter
        interleaves these with batched decode steps so a long prompt never
        stalls active lanes for its whole prefill.  allow_store=False keeps
        partial-prompt snapshots out of the prefix cache (store_prefix
        snapshots the full prompt at the end)."""
        if self.kv_pool is not None:
            # admission per chunk: the slot-commit at adopt time is the
            # authoritative (all-or-nothing) alloc; this pre-check stops a
            # doomed long prompt from burning its remaining chunks
            sess = self.eng.sessions.get(nonce)
            pos = 0 if sess is None else int(sess.pos)
            # only the FULL aliased blocks survive into the commit's table;
            # a shared partial tail is COW-copied from fresh blocks, so it
            # must not be counted as already-held capacity
            n_full = self._adopt.get(nonce, (0, [], 0))[2]
            need = self._kv_cfg.blocks_for(min(pos + len(ids), self.max_seq))
            self.kv_pool.require(max(need - n_full, 0))
        if self.kv_store is not None and self.kv_store.in_place:
            # the state kind: the chunk takes the session's entry in and
            # hands it on; nothing of it to admit, the lane is already held
            _, state_tokens = self.kv_store.state_counters
            state_tokens.labels(phase="prefill").inc(len(ids))
        sess = self.eng.sessions.get(nonce)
        pos = 0 if sess is None else int(sess.pos)
        if self._sparse is not None:
            # the chunk's positions by the side of dense_len their context
            # lies on (position t has context t + 1)
            dense = min(max(self._sparse.dense_len - pos, 0), len(ids))
            _SPARSE_TOKENS.labels(mode="dense").inc(dense)
            _SPARSE_TOKENS.labels(mode="sparse").inc(len(ids) - dense)
        if self._latent_entry_bytes:
            # a latent model's chunk at position p attends keys and values
            # expanded from the row's latents [0, p + T), in every layer
            _MLA_TOKENS.labels(phase="prefill").inc(len(ids))
            _MLA_EXPANDED.inc((pos + len(ids)) * len(self.model.layers))
        # the chunk's padded rows against the staged row, as the engine
        # launches them: what the flash kernel's grid makes of each layer
        width = min(bucket_length(len(ids)), self.max_seq - pos)
        for (kind, window), layers in self._flash_layers.items():
            tiles = flash_tiles(pos, width, self.max_seq, window)
            if tiles is not None:
                _FLASH_TILES.labels(kind=kind, state="folded").inc(tiles[0] * layers)
                _FLASH_TILES.labels(kind=kind, state="skipped").inc(tiles[1] * layers)
        return self.eng.prefill(nonce, list(ids), seed, allow_store=False)

    def abandon_prefill(self, nonce) -> None:
        """Drop a half-prefilled request (cancelled mid-chunks)."""
        self.free_slot(nonce)
        self.eng.end_session(nonce)

    def adopt_prefilled(self, nonce, logits, decoding: DecodingParams) -> SampleResult:
        """Move a fully-prefilled session into this request's batch lane and
        sample its first token: program launches only, nothing dispatched
        eagerly and nothing read (the result stays on the device for the
        caller to read when it has enqueued everything else).

        Host work first: the pools' `alloc` raises KVPoolExhausted BEFORE
        anything is enqueued, with the session, its key and its counts
        untouched, so the caller may free blocks and call again.  Then the
        store's commit (kv/store.py), the sample (core/engine.py
        sample_with_counts) and the lane's sampling state (`_adopt_lane`)."""
        sess = self.eng.sessions[nonce]
        slot = self.alloc_slot(nonce)
        if self.kv_store is not None:
            self._commit_paged_slot(nonce, slot, sess)
        res = self.eng._sample_with_counts(sess, logits, decoding)
        dense_row = sess.kv if self.kv_store is None else None
        self.kv, self.counts, self.keys, self.hist = self._adopt_lane(
            self.kv, self.counts, self.keys, self.hist, np.int32(slot),
            dense_row, sess.counts, sess.key,
            # the inner LocalEngine's prefill paths committed the prompt to
            # the session history; adopt it for this lane's prompt-lookup
            sess.hist if self.hist is not None else None,
        )
        self.pos[slot] = sess.pos
        self.last_used[slot] = time.time()
        self.eng.end_session(nonce)  # B=1 cache row no longer needed
        return res

    def _commit_paged_slot(self, nonce: str, slot: int, sess) -> None:
        """Turn a staged B=1 prefill into this slot's page tables, one a
        kind.  Full kind: aliased prefix blocks stay in place, everything
        from the first non-shared block commits out of the staged dense row
        (which already merged shared-partial content with the new tokens —
        the COW copy).  Window kind (no prefix to alias: the sharing is
        off): only the blocks the next token's window still reaches — the
        blocks behind it are never allocated.  All or nothing.  State
        kind: the session's entry overwrites the lane's, whole."""
        if self.kv_pool is None:
            self.kv_store.commit_staged(sess.kv, {KV_KIND_STATE: slot})
            return
        cfg = self._kv_cfg
        n = int(sess.pos)
        nb = cfg.blocks_for(n)
        stash = self._adopt.pop(nonce, None)
        n_sh, blocks, n_full = stash if stash is not None else (0, [], 0)
        first = {KV_KIND_FULL: n_full}
        if self._window:
            first[KV_KIND_WINDOW] = window_first_block(n, self._window, cfg.block_tokens)
        own: Dict[str, List[int]] = {}
        try:
            for kind, pool in self.kv_pools.items():
                own[kind] = pool.alloc(nb - first[kind])
        except KVPoolExhausted:
            for kind, got in own.items():
                self.kv_pools[kind].free_blocks(got)
            if stash is not None:
                self._adopt[nonce] = stash  # abandon_prefill releases it
            raise
        staged = {kind: (list(range(first[kind], nb)), got) for kind, got in own.items()}
        if KV_KIND_STATE in self.kv_store.kinds:
            staged[KV_KIND_STATE] = slot  # the session's entry over the lane's
        self.kv_store.commit_staged(sess.kv, staged)
        if self._sparse is not None and n >= self._sparse.kernel_size:
            # the spans the prompt completed, pooled into the index leaf
            spans = (n - self._sparse.kernel_size) // self._sparse.kernel_stride + 1
            _SPARSE_INDEX_ROWS.inc(spans * self._sparse_layers)
        if stash is not None:
            if n_sh % cfg.block_tokens:
                # the request diverged mid-block: the shared tail block was
                # copied (via the staged row) instead of mutated in place
                self.kv_pool.count_cow()
            self.kv_pool.free_blocks(blocks[n_full:])  # transient refs
        for kind, pool in self.kv_pools.items():
            tables = self._kind_tables[kind]
            # a re-prefilled nonce keeps its slot: drop the superseded table
            pool.release_table(tables[slot])
            kept = list(blocks[:n_full]) if kind == KV_KIND_FULL else []
            tables[slot] = PageTable(
                blocks=kept + own[kind], shared_upto=len(kept),
                base=first[kind] - len(kept),
            )

    def _extend_window_tables(self, order, errors, active, ahead) -> None:
        """Before a step: every stepping lane's window table gives back
        the blocks wholly behind the window of the first step the host has
        NOT READ yet (`pos`: a step in flight still reads from there, so a
        chained lane's release lags its launch by that step), then grows
        to cover one more token past the `ahead` steps in flight: at most
        two tokens past `pos` (WINDOW_STEP_TOKENS).  (Inside
        SPAN_DECODE_PREPARE.)"""
        bt = self._kv_cfg.block_tokens
        pool = self.kv_pools[KV_KIND_WINDOW]
        for nonce, slot in list(order.items()):
            tbl = self._kind_tables[KV_KIND_WINDOW][slot]
            p0 = int(self.pos[slot])
            pool.release_behind(tbl, window_first_block(p0, self._window, bt))
            try:
                pool.ensure(tbl, p0 + int(ahead[slot]) + 1)
            except KVPoolExhausted as exc:  # the pool is sized against this
                self._refuse_lane(nonce, slot, str(exc), order, errors, active, ahead)

    def _refuse_lane(self, nonce, slot, why, order, errors, active, ahead) -> None:
        """A lane the pools cannot extend leaves the dispatch: with its
        typed error where it asked with a host token; silently where it
        was only being chained (its step in flight still answers it, and
        it asks again with that token: the refusal, if it stands, is told
        then, after preemption has had its turn)."""
        if not ahead[slot]:
            errors[nonce] = why
        active[slot] = False
        ahead[slot] = 0
        del order[nonce]

    def _paged_extend(self, order, errors, active, ahead) -> None:
        """Extend every stepping lane's page table to cover one more token
        (past the `ahead` steps it has in flight).  A lane the pool cannot
        cover fails ALONE, with the typed backpressure message."""
        for nonce, slot in list(order.items()):
            try:
                self.kv_pool.ensure(
                    self._tables[slot], int(self.pos[slot]) + int(ahead[slot]) + 1
                )
            except KVPoolExhausted as exc:
                self._refuse_lane(nonce, slot, str(exc), order, errors, active, ahead)

    def _table_ids(self, order: Dict[str, int]) -> Dict[str, np.ndarray]:
        """Each kind's [slots, nb] physical block ids (0-padded past each
        table; padded rows sit beyond every live pos, where the causal mask
        zeroes them exactly), and with window layers `base`, [slots]: the
        logical block a window table's first entry backs.

        nb is the pow2 BUCKET of the widest table among `order` (the
        dispatch's active nonce -> slot map), at most max_seq/bt: the
        kernel walks fewer (elided) grid steps, and the compiled-program
        set stays bounded — the same discipline as _bucket_pow2 commit
        widths (warm_chunks compiles the step at every bucket width).
        Frozen lanes' longer tables truncate harmlessly (their compute is
        garbage, their blocks are never written)."""
        widest = max(
            (
                len(self._tables[s].blocks)
                for s in order.values()
                if self._tables[s] is not None
            ),
            default=1,
        )
        nb = min(
            _bucket_pow2(max(widest, 1)), self.max_seq // self._kv_cfg.block_tokens
        )
        ids = np.zeros((self.slots, nb), dtype=np.int32)
        for slot, tbl in enumerate(self._tables):
            if tbl is not None and tbl.blocks:
                n = min(len(tbl.blocks), nb)
                ids[slot, :n] = tbl.blocks[:n]
        out = {KV_KIND_FULL: ids}
        if self._window:
            # the window kind: one static width (the most blocks a window
            # table ever holds), entry j backing logical block base + j
            nbw = self.kv_pools[KV_KIND_WINDOW].total // self.slots
            wids = np.zeros((self.slots, nbw), dtype=np.int32)
            base = np.zeros(self.slots, dtype=np.int32)
            for slot, tbl in enumerate(self._kind_tables[KV_KIND_WINDOW]):
                if tbl is not None and tbl.blocks:
                    wids[slot, : len(tbl.blocks)] = tbl.blocks
                    base[slot] = tbl.base
            out.update({KV_KIND_WINDOW: wids, "base": base})
        return out

    def prefill_and_sample(
        self, nonce: str, prompt_ids: Sequence[int], decoding: DecodingParams
    ) -> SampleResult:
        """Prefill on the B=1 bucket program, then move the session's KV row
        and sampling state into this request's batch slot."""
        self.alloc_slot(nonce)  # fail on a full pool BEFORE burning prefill
        if self.kv_pool is None:
            logits = self.eng.prefill(nonce, prompt_ids, decoding.seed)
            return self.adopt_prefilled(nonce, logits, decoding)
        full = list(prompt_ids)
        try:
            n = self.seed_from_prefix(nonce, full, decoding.seed)
            # admission: the POOL must cover the non-shared remainder
            # before any prefill compute burns (same fail-fast invariant
            # as the slot claim above) — a shortfall surfaces as the typed
            # backpressure error, never a mid-prefill crash.  Only FULL
            # aliased blocks count as held: the commit COW-copies a shared
            # partial tail from a fresh block.
            n_full = self._adopt.get(nonce, (0, [], 0))[2]
            need = self._kv_cfg.blocks_for(min(len(full), self.max_seq))
            self.kv_pool.require(max(need - n_full, 0))
            logits = self.eng.prefill(
                nonce, full[n:], decoding.seed, allow_store=False
            )
            res = self.adopt_prefilled(nonce, logits, decoding)
        except Exception:
            self.abandon_prefill(nonce)
            raise
        self.store_prefix(nonce, full)
        return res

    def decode_batch(
        self,
        requests: Dict[str, Tuple[int, DecodingParams]],
        budgets: Optional[Dict[str, Optional[int]]] = None,
    ) -> Tuple[Dict[str, SampleResult], Dict[str, str]]:
        """One batched decode step for every (nonce -> last token) request.
        Slots not in `requests` stay frozen (active=False gates their KV
        write and counts).  Returns (results, per-nonce errors): a request
        whose slot vanished (client disconnect race) or hit max_seq fails
        ALONE — it must never poison the rest of the batch.

        Two halves at one seam: `decode_launch` ENQUEUES and returns,
        `decode_read` blocks on the device.  This call runs them in a row,
        and a dispatch is ONE step for the lanes that asked.  `budgets`
        (nonce -> remaining tokens the driver will accept) never widens it:
        here it only says which greedy lanes of a speculating engine may
        verify a drafted block instead (`_pick_spec_lanes`), whose later
        rows wait in the lane's buffer and answer its next calls with no
        device work.  Its callers: the batched adapter (api/strategies.py)
        and the parity tests.

        The SERVED path (sched/step.py) never reads a step before the next
        is enqueued: it calls the halves itself and keeps one step in
        flight, `decode_launch(chain=)`."""
        return self.decode_read(self.decode_launch(requests, budgets))

    def decode_launch(
        self,
        requests: Dict[str, Tuple[int, DecodingParams]],
        budgets: Optional[Dict[str, Optional[int]]] = None,
        chain: Optional[DecodeFlight] = None,
    ) -> DecodeFlight:
        """The half of `decode_batch` that enqueues.  Nothing is fenced and
        nothing read (but a verify block, which reads its acceptance
        counts: `blocked`).  The flight says what this call sent to the
        device: `order`, the lanes of its one step, with `src` None where
        every lane was answered on the host.  The lanes' `pos` advance in
        `decode_read`.

        `chain` is the flight BEFORE this one, not read yet (an empty
        DecodeFlight where there is none): the caller keeps one step in
        flight ahead of the one it reads.  `requests` are then the lanes
        whose drivers have ASKED:

        - a lane in `chain` that will take a token after the one it is
          owed (`budgets[nonce] >= 2`) steps again at `pos + 1`, its input
          token taken from `chain`'s result on the device, inside the
          step's program (`_chain_tokens`); with a budget of 1 it only
          waits for `chain` to be read;
        - a lane not in `chain` (it has just been adopted, or resumed)
          steps from its host token, in the same dispatch;
        - a lane whose driver was late for the flight before last holds
          that token in its buffer: it is answered now (`answered()`),
          and steps on from that token if it will take another.

        A chained lane that ends at the token it is owed (a stop id, a
        cancel, a preemption: the host learns at the read) leaves a
        surplus step in the air, which `decode_read` drops."""
        t0 = time.perf_counter()
        flight = DecodeFlight()
        if not requests:
            return flight
        plan = None
        served = chain is not None  # one step in flight ahead of the read
        ahead_of = chain.order if served and chain.src is not None else {}
        with span(SPAN_DECODE_PREPARE):
            # buffered tokens (a verify block's later rows, a late
            # driver's) resolve first
            flight.out, requests = self._pop_buffered(
                requests, budgets if served else None
            )
            # per-lane speculation: greedy lanes with budget to spare verify
            # a drafted block instead of stepping once; they advance by
            # their OWN acceptance count (buffered), while the remaining
            # lanes take the plain batched step below — the two programs
            # touch disjoint lanes
            spec_reqs = {} if served else self._pick_spec_lanes(requests, budgets)
            if requests and not spec_reqs:
                plan = self._plan_dispatch(requests, budgets, flight.errors, ahead_of)
        if spec_reqs:
            spec_out = self._decode_spec_lanes(spec_reqs)
            flight.blocked = True
            _DECODE_TOKENS.labels(source="spec").inc(len(spec_out))
            flight.out.update(spec_out)
            requests = {n: r for n, r in requests.items() if n not in spec_reqs}
            if requests:
                with span(SPAN_DECODE_PREPARE):
                    plan = self._plan_dispatch(requests, None, flight.errors, {})
        if plan is not None:
            flight.order, dev, table_ids, flight.chained = plan
            prev_token = chain.src.token if flight.chained else self._no_token
            self._launch(flight, dev, table_ids, prev_token)
        flight.host_s = time.perf_counter() - t0
        return flight

    def _launch(self, flight: DecodeFlight, dev, table_ids, prev_token) -> None:
        with span(SPAN_DECODE_LAUNCH, lanes=len(flight.order)):
            if self.kv_store is not None:
                # the pool is attended IN PLACE through the page tables and
                # the new rows block-append, all inside the launch
                count_expert_rows(self.eng.model, self.slots, 1, whole=True)
                flight.src, flight.moe = self._dispatch_ragged(
                    flight.order, dev, table_ids, prev_token
                )
            else:
                # vmapped over the slots: each lane's experts see one row
                count_expert_rows(self.eng.model, 1, self.slots)
                token_d, pos_d, active_d, sp = dev
                out = self._step(
                    self.eng.window_params, self.eng.edge_params, token_d,
                    self.kv, pos_d, active_d, sp, self.keys, self.counts,
                    prev_token,
                )
                flight.src, self.kv, self.counts, self.keys = out

    def decode_read(
        self, flight: DecodeFlight, asked: Optional[Any] = None
    ) -> Tuple[Dict[str, SampleResult], Dict[str, str]]:
        """The half of `decode_batch` that reads: blocks until the device
        has finished the flight's dispatch, then hands each lane its row.

        A lane that LEFT between launch and read (preempted or ended while
        its step was in flight: `slot_of` no longer maps its nonce to the
        slot it was sent on) gets nothing: its `pos` is not advanced, its
        token is dropped, and whoever holds the slot now is not touched.
        Where the step was chained that is a SURPLUS step (the lane ended
        at the token before it, which the host had not read at the launch);
        what it wrote went with the lane: its blocks, state entry and
        sampling row are given back whole and overwritten whole by the
        next adoption, which is enqueued after it.

        `asked` (the served path): the lanes whose drivers have asked for
        this flight's token.  A lane of the flight that is not among them
        (its driver's turn was cut) keeps its token in the per-nonce
        buffer, where its next ask finds it (`_pop_buffered`)."""
        if flight.src is None:
            return flight.out, flight.errors
        t0 = time.perf_counter()
        src, lanes = flight.src, len(flight.order)
        # ONE packed device->host read per field per dispatch (the
        # pipelined engine's drain pattern), then host-side slicing: a
        # device gather a lane would cost a dispatch each.  The first read
        # blocks until the device has finished the dispatch.
        with span(SPAN_DECODE_READBACK):
            toks = np.asarray(src.token)
            lps = np.asarray(src.logprob)
            tts = np.asarray(src.top_tokens)
            tlps = np.asarray(src.top_logprobs)
            if self.kv_store is not None and self._moe_reported:
                # summed on the device by the dispatch just read: no sync
                mine, elsewhere, *visited = np.asarray(flight.moe)
                _MOE_ASSIGNMENTS.labels(held="yes").inc(int(mine))
                _MOE_ASSIGNMENTS.labels(held="no").inc(int(elsewhere))
                if visited:  # the model says which experts its lanes chose
                    metric("dnet_moe_experts_visited_total").inc(int(visited[0]))
        with span(SPAN_DECODE_UNPACK):
            now = time.time()
            out = flight.out
            delivered = surplus = live = 0
            sp = self._sparse
            stayed = chosen = resident = past_dense = spans = 0
            for nonce, slot in flight.order.items():
                if self.slot_of.get(nonce) != slot:
                    # the lane left with its step in flight
                    surplus += nonce in flight.chained
                    continue
                live += int(self.pos[slot])  # the entries its step attended
                if sp is not None:
                    # from the position the host has (no sync): the blocks
                    # the step's query holds and reads, whether its token
                    # completed a span of the index
                    n = int(self.pos[slot]) + 1
                    stayed += 1
                    resident += -(-n // sp.block_size)
                    chosen += sp.blocks_attended(n)
                    past_dense += n > sp.dense_len
                    spans += n >= sp.kernel_size and (n - sp.kernel_size) % sp.kernel_stride == 0
                self.pos[slot] += 1
                self.last_used[slot] = now
                row = SampleResult(
                    token=toks[slot], logprob=lps[slot],
                    top_tokens=tts[slot], top_logprobs=tlps[slot],
                )
                if asked is None or nonce in asked:
                    delivered += 1
                    out[nonce] = row
                else:
                    self._buffer.setdefault(nonce, []).append(row)
        # what the dispatch did: the device computed a step for every slot,
        # `lanes` of them asked for, and the drivers that had asked received
        # their token now (a late driver's waits in the buffer)
        _DECODE_DISPATCHES.inc()
        _DECODE_SLOT_STEPS.inc(self.slots)
        _DECODE_LANE_STEPS.inc(lanes)
        _DECODE_TOKENS.labels(source="dispatch").inc(delivered)
        _DECODE_CHAINED.inc(len(flight.chained))
        _DECODE_SURPLUS.inc(surplus)
        if self._latent_entry_bytes:
            # what the algorithm reads: every live token's ONE entry, once a
            # layer a step, from positions the host already has (no sync)
            _MLA_LATENT_BYTES.inc(live * self._latent_entry_bytes)
            _MLA_TOKENS.labels(phase="decode").inc(lanes)
        if sp is not None:
            layers = self._sparse_layers
            _SPARSE_BLOCKS.labels(state="chosen").inc(chosen * layers)
            _SPARSE_BLOCKS.labels(state="resident").inc(resident * layers)
            _SPARSE_TOKENS.labels(mode="sparse").inc(past_dense)
            _SPARSE_TOKENS.labels(mode="dense").inc(stayed - past_dense)
            _SPARSE_INDEX_ROWS.inc(spans * layers)
        if self.kv_store is not None and self.kv_store.in_place:
            # what the algorithm needs: each active lane's entry read and
            # written once a step, in every layer
            state_bytes, state_tokens = self.kv_store.state_counters
            state_bytes.inc(lanes * self.kv_store.entry_bytes * 2)
            state_tokens.labels(phase="decode").inc(lanes)
        # per-token share, observed tokens-served times: the family's
        # count stays == tokens across the local / chunked / speculative /
        # batched paths (LocalEngine's amortization convention), and the
        # sum stays == the two halves' host time, prepare through unpack
        # (what a caller enqueued between them is not in it)
        host_s = flight.host_s + time.perf_counter() - t0
        _DECODE_STEP_MS.observe_n(host_s * 1000.0 / lanes, lanes)
        return out, flight.errors

    def _pop_buffered(self, requests, budgets=None):
        """Answer every lane that still holds rows of an earlier dispatch
        with the next one; returns (results, remaining requests).  With
        `budgets` (the served path) a lane answered so steps on in this
        call, FROM the token it was just handed, where it will take
        another: its request stays, with that token."""
        out_buf: Dict[str, SampleResult] = {}
        now = time.time()
        for nonce in requests:
            buf = self._buffer.get(nonce)
            if buf:
                out_buf[nonce] = buf.pop(0)
                slot = self.slot_of.get(nonce)
                if slot is not None:
                    self.last_used[slot] = now
        if out_buf:
            _DECODE_TOKENS.labels(source="buffer").inc(len(out_buf))
            goes_on = {
                n: (int(np.asarray(out_buf[n].token).reshape(-1)[0]), requests[n][1])
                for n in out_buf
                if takes_another(budgets, n) and not self._buffer.get(n)
            }
            requests = {n: r for n, r in requests.items() if n not in out_buf}
            requests.update(goes_on)
        return out_buf, requests

    def _pick_spec_lanes(self, requests, budgets) -> Dict[str, Tuple[int, int, int]]:
        """Lanes that verify a drafted block this call: nonce -> (token,
        slot, budget)."""
        spec_reqs: Dict[str, Tuple[int, int, int]] = {}
        if self.spec_lookahead <= 0 or not budgets:
            return spec_reqs
        for nonce, (tok, dec) in requests.items():
            slot = self.slot_of.get(nonce)
            budget = budgets.get(nonce) or 1
            if (
                slot is not None
                and dec.temperature == 0.0
                and not dec.logprobs
                and dec.repetition_penalty == 1.0
                and not dec.logit_bias  # verify argmaxes are unbiased
                and budget > 1
                and self.pos[slot] + self.spec_lookahead + 1 <= self.max_seq
                and self._spec_worthwhile(nonce)
            ):
                spec_reqs[nonce] = (tok, slot, budget)
        return spec_reqs

    def _plan_dispatch(self, requests, budgets, errors, ahead_of):
        """Everything the host prepares for one dispatch: per-slot numpy
        parameter rows and the page-table extension.  Returns (order, the
        step's host arguments, table ids, the chained lanes) or None when
        no lane is left to step.

        `ahead_of` is the order (nonce -> slot) of a flight the host has
        not read yet: a lane in it steps at `pos + 1` from that flight's
        token (its host row says CHAINED), where its budget holds a token
        after the one it is owed; otherwise it is left out of this
        dispatch, and no error is told for it."""
        token = np.zeros((self.slots, 1), dtype=np.int32)
        active = np.zeros(self.slots, dtype=bool)
        pos = np.zeros(self.slots, dtype=np.int32)
        ahead = np.zeros(self.slots, dtype=np.int32)  # steps in flight, unread
        temp = np.zeros(self.slots, dtype=np.float32)
        top_p = np.ones(self.slots, dtype=np.float32)
        top_k = np.zeros(self.slots, dtype=np.int32)
        min_p = np.zeros(self.slots, dtype=np.float32)
        rep = np.ones(self.slots, dtype=np.float32)
        mtk = np.ones(self.slots, dtype=np.int32)
        b_ids = np.full((self.slots, MAX_LOGIT_BIAS), -1, dtype=np.int32)
        b_vals = np.zeros((self.slots, MAX_LOGIT_BIAS), dtype=np.float32)
        order: Dict[str, int] = {}
        for nonce, (tok, dec) in requests.items():
            slot = self.slot_of.get(nonce)
            if slot is None:
                errors[nonce] = f"request {nonce!r} has no batch slot (cancelled?)"
                continue
            chained = ahead_of.get(nonce) == slot
            if chained and not (
                takes_another(budgets, nonce) and self.pos[slot] + 2 <= self.max_seq
            ):
                continue  # it takes the token it is owed and no step more
            if self.pos[slot] >= self.max_seq:
                errors[nonce] = (
                    f"sequence length {self.pos[slot]} reached max_seq {self.max_seq}"
                )
                continue
            token[slot, 0] = CHAINED if chained else tok
            active[slot] = True
            ahead[slot] = int(chained)
            pos[slot] = self.pos[slot] + ahead[slot]
            temp[slot] = dec.temperature
            top_p[slot] = dec.top_p
            top_k[slot] = dec.top_k
            min_p[slot] = dec.min_p
            rep[slot] = dec.repetition_penalty
            mtk[slot] = dec.min_tokens_to_keep
            b_ids[slot], b_vals[slot] = encode_logit_bias(dec.logit_bias)
            order[nonce] = slot
        if not order:
            return None

        # host rows: they go into the jitted step as arguments, which
        # uploads them in the call (one eager device_put a row costs more
        # host time than the whole launch)
        sp = SampleParams(
            temperature=temp, top_p=top_p, top_k=top_k, min_p=min_p,
            repetition_penalty=rep, min_tokens_to_keep=mtk,
            bias_ids=b_ids, bias_vals=b_vals,
        )
        table_ids = None
        if self.kv_pool is not None:
            # block-table extension is admission: a lane the pool cannot
            # cover fails ALONE with the typed backpressure message
            self._paged_extend(order, errors, active, ahead)
            if order and self._window:
                self._extend_window_tables(order, errors, active, ahead)
            if not order:
                return None
            table_ids = self._table_ids(order)
        elif self.kv_store is not None:
            table_ids = {}  # the state kind: a lane IS the address
        chained = frozenset(n for n, s in order.items() if ahead[s])
        return order, (token, pos, active, sp), table_ids, chained

    def _dispatch_ragged(self, order: Dict[str, int], dev, tables, prev_token):
        """One decode step over the pool: the read-only paged_attend
        program, then the jitted kv_append block-append (a store updated
        in place has written inside the step).  All of it is the launch
        span.  Returns the results and the dispatch's [held, elsewhere]
        expert assignments, both on the device."""
        token_d, pos_d, active_d, sp = dev
        res, rows, self.counts, self.keys, moe = self._ragged_step(
            self.eng.window_params, self.eng.edge_params, token_d,
            self.kv_store.kv, tables, pos_d, active_d, sp, self.keys,
            self.counts, prev_token,
        )
        if self.kv_store.in_place:
            self.kv_store.append_rows(rows, {}, None)  # the step already wrote
            return res, moe
        bt = self._block_tokens
        # inactive-lane sentinel: past the block axis, never negative
        # (see KindStore.append_in_program)
        phys = {
            kind: np.full(self.slots, pool.total, dtype=np.int32)
            for kind, pool in self.kv_pools.items()
        }
        off = np.zeros(self.slots, dtype=np.int32)
        for _nonce, slot in order.items():
            p0 = int(pos_d[slot])  # the row this step writes, chained or not
            off[slot] = p0 % bt
            for kind, tables in self._kind_tables.items():
                tbl = tables[slot]
                phys[kind][slot] = tbl.blocks[p0 // bt - tbl.base]
        self.kv_store.append_rows(rows, phys, off)
        return res, moe

    # adaptive spec gate, same thresholds/semantics as LocalEngine's
    SPEC_WARMUP_BLOCKS = LocalEngine.SPEC_WARMUP_BLOCKS
    SPEC_MIN_TOKENS_PER_BLOCK = LocalEngine.SPEC_MIN_TOKENS_PER_BLOCK

    def _spec_worthwhile(self, nonce: str) -> bool:
        st = self._spec_stats.get(nonce)
        if st is None or st[0] < self.SPEC_WARMUP_BLOCKS:
            return True
        return st[1] / st[0] >= self.SPEC_MIN_TOKENS_PER_BLOCK

    def _decode_spec_lanes(
        self, spec_reqs: Dict[str, Tuple[int, int, int]]
    ) -> Dict[str, SampleResult]:
        """One vmapped verify block over the speculating lanes.  Each lane
        emits 1..L+1 tokens (its own acceptance); the first returns now and
        the rest buffer, so lanes genuinely advance unevenly."""
        token = np.zeros((self.slots, 1), dtype=np.int32)
        active = np.zeros(self.slots, dtype=bool)
        pos = np.zeros(self.slots, dtype=np.int32)
        for nonce, (tok, slot, _budget) in spec_reqs.items():
            token[slot, 0] = tok
            active[slot] = True
            pos[slot] = self.pos[slot]
        t_blk = time.perf_counter()
        out_block, self.hist, self.kv = self._spec_step(
            self.eng.window_params, self.eng.edge_params, jnp.asarray(token),
            self.hist, self.kv, jnp.asarray(pos), jnp.asarray(active),
        )
        out_h = np.asarray(out_block)  # [slots, L+1]; -1 past acceptance
        blk_ms = (time.perf_counter() - t_blk) * 1000.0
        now = time.time()
        zero_lp = np.zeros((1,), np.float32)
        zero_tt = np.zeros((1, MAX_TOP_LOGPROBS), np.int32)
        zero_tlp = np.zeros((1, MAX_TOP_LOGPROBS), np.float32)
        res: Dict[str, SampleResult] = {}
        total_emitted = 0
        for nonce, (_tok, slot, budget) in spec_reqs.items():
            emitted = min(int((out_h[slot] >= 0).sum()), budget)
            total_emitted += emitted
            rows = [
                SampleResult(
                    np.ascontiguousarray(out_h[slot, i : i + 1]).astype(np.int32),
                    zero_lp, zero_tt, zero_tlp,
                )
                for i in range(emitted)
            ]
            self.pos[slot] += emitted
            self.last_used[slot] = now
            st = self._spec_stats.setdefault(nonce, [0, 0])
            st[0] += 1
            st[1] += emitted
            res[nonce] = rows[0]
            if rows[1:]:
                self._buffer.setdefault(nonce, []).extend(rows[1:])
        # the verify block amortizes one dispatch over every accepted
        # token: per-token share, observed tokens-served times (the same
        # convention as the plain batched dispatch and LocalEngine's spec
        # path, keeping the family's count == tokens on every path)
        per_tok_ms = blk_ms / max(total_emitted, 1)
        _DECODE_STEP_MS.observe_n(per_tok_ms, total_emitted)
        return res

    def warm_chunks(self) -> None:
        """Compile the batched step (at every table bucket) and, where the
        engine speculates, the verify block up front with a throwaway
        session, so the FIRST request doesn't stall every concurrent lane
        on a multi-second compile (all lanes run on one compute executor).
        The name is the engines' common one (LocalEngine.warm_chunks)."""
        t0 = time.time()
        dec = DecodingParams(temperature=0.0)
        self.prefill_and_sample("__warm__", [0], dec)
        if self.spec_lookahead > 0:
            # the greedy warm request IS spec-eligible: the first budgeted
            # round below compiles the verify block; disable the gate stats
            # afterwards so warmup acceptance doesn't bias real requests
            self.decode_batch({"__warm__": (0, dec)}, budgets={"__warm__": 8})
            self._buffer.pop("__warm__", None)
            self._spec_stats.pop("__warm__", None)
        # sampled decoding is spec-ineligible, so this compiles the PLAIN
        # step even on spec-enabled engines
        dec_plain = DecodingParams(temperature=1.0) if self.spec_lookahead else dec
        self._warm_step(dec_plain)
        self.end_session("__warm__")
        widths = 1
        if self.kv_pool is not None:
            # a dispatch attends at the pow2 bucket of the widest ACTIVE
            # table (_table_ids): compile the step at every bucket width
            # now, with a throwaway session grown into each bucket, so the
            # first long-context request doesn't stall the whole batch
            # loop on a mid-flight width compile
            bt = self._kv_cfg.block_tokens
            nb_full = self.max_seq // bt
            # bucket ladder: pow2 widths, plus the clamped full width when
            # nb_full itself is not a power of two (dispatches clamp to it,
            # so it is a real compiled width too)
            half = 1
            while half < nb_full:
                w = min(half * 2, nb_full)
                # smallest prompt whose table lands in bucket w: one token
                # past `half` full blocks (half+1 blocks round up past half)
                n_tok = half * bt + 1
                if n_tok + 1 >= self.max_seq:
                    break
                try:
                    self.prefill_and_sample("__warm__", [0] * n_tok, dec_plain)
                except KVPoolExhausted:
                    # a pool this tight can never serve a table this wide,
                    # so the width can never be dispatched either
                    self.end_session("__warm__")
                    break
                self._warm_step(dec_plain)
                self.end_session("__warm__")
                widths += 1
                half = w
        log.info(
            "[PROFILE] warmed the batched step (%d table widths) in %.1fs",
            widths, time.time() - t0,
        )

    def _warm_step(self, dec: DecodingParams) -> None:
        """The warm session's step as both of its callers give it: with no
        flight before it, and chained to that one (the same program)."""
        reqs = {"__warm__": (0, dec)}
        first = self.decode_launch(reqs, chain=DecodeFlight())
        if self.pos[self.slot_of["__warm__"]] + 2 < self.max_seq:
            second = self.decode_launch(reqs, budgets={"__warm__": 2}, chain=first)
            self.decode_read(first)
            first = second
        self.decode_read(first)

    def generate(
        self,
        prompt_ids: Sequence[int],
        decoding: Optional[DecodingParams] = None,
        max_tokens: int = 256,
        eos_token_ids: Optional[set] = None,
        nonce: str = "batched",
    ):
        """Single-sequence convenience loop over the batched program (tests /
        parity with LocalEngine.generate)."""
        decoding = decoding or DecodingParams()
        eos = eos_token_ids or set()
        self.end_session(nonce)
        res = self.prefill_and_sample(nonce, prompt_ids, decoding)
        first = self.token_result(nonce, res, step=0, decoding=decoding)
        token = first.token_id
        yield first
        if token in eos:
            self.end_session(nonce)
            return
        for step in range(1, max_tokens):
            if self.pos[self.slot_of[nonce]] >= self.max_seq:
                break
            res_map, errs = self.decode_batch({nonce: (token, decoding)})
            if errs:
                raise RuntimeError(errs[nonce])
            res_row = res_map[nonce]
            token = int(res_row.token[0])
            yield self.token_result(nonce, res_row, step=step, decoding=decoding)
            if token in eos:
                break
        self.end_session(nonce)
