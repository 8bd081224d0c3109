"""Single-process inference engine: the minimum end-to-end slice.

Runs a model (or a shard's layer range) on the local JAX device(s):
prefill + token-by-token decode with preallocated KV, bucketed prompt
padding (static shapes -> no per-request recompiles), donated cache buffers
(XLA-level reuse standing in for the reference's memory pools,
src/dnet/core/memory/memory_pool.py), and per-nonce KV sessions with TTL
expiry (reference: src/dnet/shard/runtime.py:374-396).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.core.kvcache import init_cache
from dnet_tpu.core.sampler import (
    MAX_TOP_LOGPROBS,
    SamplePlan,
    SampleParams,
    SampleResult,
    pack_chunk_results,
    sample,
)
from dnet_tpu.core.types import DecodingParams, TokenResult
from dnet_tpu.models import ModelConfig, get_ring_model_cls
from dnet_tpu.obs import get_recorder, metric
from dnet_tpu.obs.jit import instrument_jit
from dnet_tpu.obs.phases import MOE_PATHS
from dnet_tpu.ops.moe import whole_batch
from dnet_tpu.utils.checkpoint import Checkpoint
from dnet_tpu.utils.logger import get_logger

log = get_logger()

_DECODE_STEP_MS = metric("dnet_decode_step_ms")
_PREFILL_MS = metric("dnet_prefill_ms")
_LAYER_MS = metric("dnet_layer_compute_ms")
_MOE_EXPERT_ROWS = metric("dnet_moe_expert_rows_total")


def count_expert_rows(model, rows: int, passes: int = 1, whole: bool = False) -> None:
    """Book `passes` launched programs of `rows` rows (`whole`: all the
    rows the program carries, as `apply_whole` traced it; else one lane of
    a program vmapped over the lanes) under the path their routed experts
    take (dnet_moe_expert_rows_total{path=}; nothing for a model without
    routed experts or a path chosen by name outside MOE_PATHS).  Host
    arithmetic on static shapes: no device sync."""
    path = model.moe_path(rows, whole)
    if path in MOE_PATHS:
        _MOE_EXPERT_ROWS.labels(path=path).inc(rows * passes)


def apply_whole(model, *args, **kwargs):
    """`model.apply_window` for a program that hands the model every row
    it carries in this one trace, none of them under `jax.vmap` (ops/moe.py:
    whole_batch): the routed experts may then go grouped under the ridge."""
    with whole_batch():
        return model.apply_window(*args, **kwargs)


def bucket_length(n: int, min_bucket: int = 16) -> int:
    b = min_bucket
    while b < n:
        b *= 2
    return b


@dataclass
class Session:
    """Per-nonce decode state."""

    nonce: str = ""  # owning request id (flight-recorder span key)
    kv: dict = None  # stacked [L, ...] cache (fit policy)
    kv_list: list = None  # per-layer [1, ...] caches (offload policies)
    pos: int = 0
    key: jax.Array = None
    counts: jax.Array = None  # [B, V] int32 seen-token counts (repetition penalty)
    last_used: float = field(default_factory=time.time)
    # chunk pipelining: last sampled token ON DEVICE (chains the next chunk
    # without a host round trip) + dispatched-but-unread chunk queue
    last_token: jax.Array = None  # [B, 1] int32
    pending: "deque" = field(default_factory=lambda: deque())
    # speculative decoding: device-resident committed-token history
    # (prompt + generated), indexed by position — hist[i] is the token FED
    # at position i (whose KV landed in slot i).  None unless the engine
    # was built with spec_lookahead > 0.
    hist: jax.Array = None  # [B, max_seq] int32
    # draft-MODEL speculation: the small model's own KV cache (None unless
    # the engine was built with draft_dir)
    dkv: dict = None
    # acceptance accounting: blocks run / tokens emitted, feeding the
    # adaptive spec-vs-chunk gate (spec_worthwhile)
    spec_blocks: int = 0
    spec_emitted: int = 0


class LocalEngine:
    """One process, one device (or data-parallel later): full hot path.

    layers=None means the full model (single-shard serving); a sub-range
    makes this engine a shard's compute core.
    """

    # class default so engine subclasses with their own __init__ (MeshEngine)
    # are spec-ineligible unless they opt in
    spec_lookahead = 0
    # this engine's programs hand the model every row they carry in one
    # trace (`apply_whole`); a subclass whose programs run under a mesh
    # axis or vmap lanes says False, and its rows are booked as they trace
    whole_batch_programs = True

    def __init__(
        self,
        model_dir: str | Path,
        layers: Optional[Sequence[int]] = None,
        batch: int = 1,
        max_seq: int = 2048,
        param_dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        kv_ttl_s: float = 600.0,
        shard_mode: bool = False,
        window_size: int = 0,
        residency_size: int = 0,
        repack_dir: Optional[str] = None,
        kv_quant_bits: int = 0,
        weight_quant_bits: int = 0,
        weight_quant_group: int = 0,
        prefix_cache_size: int = 0,
        spec_lookahead: int = 0,
        draft_dir: Optional[str | Path] = None,
    ):
        self.ckpt = Checkpoint(model_dir)
        self.config = ModelConfig.from_hf(self.ckpt.config)
        model_cls = get_ring_model_cls(self.config.model_type)
        all_layers = list(range(self.config.num_hidden_layers))
        self.model = model_cls(self.config, layers if layers is not None else all_layers)
        # a subclass that shards over a mesh (parallel/shard_mesh.py) built
        # its mesh first: the model's cache then shards by kv head
        self.model.on_mesh = getattr(self, "mesh", None) is not None
        self.batch = batch
        self.max_seq = max_seq
        self.param_dtype = jnp.dtype(param_dtype)
        self.kv_dtype = kv_dtype or param_dtype
        self.kv_quant_bits = kv_quant_bits
        self.weight_quant_bits = weight_quant_bits
        self.weight_quant_group = weight_quant_group
        if weight_quant_bits not in (0, 4, 8):
            raise NotImplementedError(
                "weight quantization supports 4 (packed int4) or 8 (int8) bits"
            )
        self.kv_ttl_s = kv_ttl_s
        # shard_mode: load only the edge weights this layer range needs
        # (reference: edge tensors loaded iff shard holds layer 0 / the last
        # layer, src/dnet/shard/runtime.py:262-286)
        self.shard_mode = shard_mode
        self.spec_lookahead = int(spec_lookahead)
        self.sessions: Dict[str, Session] = {}

        from dnet_tpu.core.weights import plan_policy

        self.plan = plan_policy(
            len(self.model.layers), window_size, residency_size
        )
        self._repack_dir = repack_dir
        self.weight_cache = None
        self._windows: list[list[int]] = []
        self.prefix_cache = None
        if prefix_cache_size > 0:
            if self.plan.streams_weights or shard_mode:
                log.warning(
                    "prefix cache requested but unsupported for %s engines; "
                    "disabled",
                    "weight-streaming" if self.plan.streams_weights else "shard",
                )
            else:
                from dnet_tpu.core.prefix_cache import PrefixCache

                self.prefix_cache = PrefixCache(prefix_cache_size)

        # observability sync knobs (reference core/observability.py:31-107:
        # forced mx.eval sync points; here block_until_ready fences): without
        # a fence, XLA async dispatch makes per-stage wall times unattributable
        from dnet_tpu.config import get_settings

        obs = get_settings().obs
        self._sync_per_layer = obs.sync_per_layer
        self._sync_every_n = obs.sync_stride()  # 0 = never, N >= 1 = every N

        # draft-MODEL speculation (r5, beyond both the reference and the
        # prompt-lookup drafts): a second, much smaller checkpoint drafts
        # spec_lookahead tokens autoregressively; the target verifies the
        # block in ONE forward.  Greedy-exactness is independent of draft
        # quality (only acceptance varies), so any same-vocab model works.
        self.draft = None
        if draft_dir is not None:
            if spec_lookahead <= 0:
                raise ValueError(
                    "draft_dir needs spec_lookahead > 0 (the draft model "
                    "exists only to draft verify blocks)"
                )
            self._load_draft(draft_dir)

        self._load_params()
        self._build_fns()

    def _load_draft(self, draft_dir: str | Path) -> None:
        ckpt = Checkpoint(draft_dir)
        cfg = ModelConfig.from_hf(ckpt.config)
        if cfg.vocab_size != self.config.vocab_size:
            raise ValueError(
                f"draft model vocab {cfg.vocab_size} != target vocab "
                f"{self.config.vocab_size}; speculation needs a shared "
                f"token space"
            )
        model_cls = get_ring_model_cls(cfg.model_type)
        dmodel = model_cls(cfg, list(range(cfg.num_hidden_layers)))
        if not dmodel.kv_rewindable(self.max_seq):
            raise ValueError(
                f"draft model {cfg.model_type} uses rotating SWA caches, "
                f"which cannot rewind after partial acceptance"
            )
        per_layer = [dmodel.map_layer(ckpt.load_layer_raw(a)) for a in dmodel.layers]
        window = self._cast(dmodel.stack_layers(per_layer))
        edge = self._cast(dmodel.map_edge(ckpt.load_edge_raw()))
        from types import SimpleNamespace

        self.draft = SimpleNamespace(
            model=dmodel, config=cfg, window=window, edge=edge
        )
        log.info(
            "draft model loaded: %s (%d layers) drafting for %s",
            cfg.model_type, cfg.num_hidden_layers, self.config.model_type,
        )

    @classmethod
    def from_params(
        cls,
        config: ModelConfig,
        window_params,
        edge_params,
        *,
        batch: int = 1,
        max_seq: int = 2048,
        param_dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        kv_quant_bits: int = 0,
        kv_ttl_s: float = 600.0,
        spec_lookahead: int = 0,
    ) -> "LocalEngine":
        """Build an engine around already-materialised parameters (no
        checkpoint on disk) — the zero-egress bench path: the serving hot
        loop is identical, only weight provenance differs."""
        from dnet_tpu.core.weights import plan_policy

        self = cls.__new__(cls)
        self.ckpt = None
        self.config = config
        model_cls = get_ring_model_cls(config.model_type)
        self.model = model_cls(config, list(range(config.num_hidden_layers)))
        self.batch = batch
        self.max_seq = max_seq
        self.param_dtype = jnp.dtype(param_dtype)
        self.kv_dtype = kv_dtype or param_dtype
        self.kv_quant_bits = kv_quant_bits
        self.weight_quant_bits = 0
        self.weight_quant_group = 0
        self.kv_ttl_s = kv_ttl_s
        self.shard_mode = False
        self.spec_lookahead = int(spec_lookahead)
        self.sessions = {}
        self.plan = plan_policy(len(self.model.layers), 0, 0)
        self._repack_dir = None
        self.weight_cache = None
        self._windows = []
        self.prefix_cache = None
        self.draft = None
        self.window_params = jax.tree.map(jnp.asarray, window_params)
        self.edge_params = jax.tree.map(jnp.asarray, edge_params)
        self._sync_per_layer = False
        self._sync_every_n = 0
        self._build_fns()
        return self

    # ---- loading ------------------------------------------------------
    def _cast(self, tree):
        def cast_leaf(a: np.ndarray):
            arr = jnp.asarray(a)
            if jnp.issubdtype(arr.dtype, jnp.floating):
                arr = arr.astype(self.param_dtype)
            return arr

        return jax.tree.map(cast_leaf, tree)

    def _load_params(self) -> None:
        t0 = time.perf_counter()
        m = self.model
        if self.weight_quant_bits and not m.supports_weight_quant:
            raise NotImplementedError(
                f"weight quantization not supported for {self.config.model_type}"
            )
        m.experts_quantized = bool(self.weight_quant_bits) and "e_gate" in m.quant_keys
        if self.plan.streams_weights:
            # offload / sliding_fit: layers stream host<->HBM via WeightCache;
            # quantized layers shrink the host->HBM transfer (the streaming
            # bottleneck) by the same 2x/4x as the resident case
            from dnet_tpu.core.weights import HostLayerStore, WeightCache

            store = HostLayerStore(
                self.ckpt,
                m,
                param_dtype=str(self.param_dtype),
                repack_dir=self._repack_dir,
                weight_quant_bits=self.weight_quant_bits,
                weight_quant_group=self.weight_quant_group,
            )
            self.weight_cache = WeightCache(store, max_resident=self.plan.residency)
            w = self.plan.window_size
            self._windows = [
                m.layers[i : i + w] for i in range(0, len(m.layers), w)
            ]
            self.window_params = None
            self.weight_cache.prefetch(self._windows[0])
        else:
            per_layer = [m.map_layer(self.ckpt.load_layer_raw(a)) for a in m.layers]
            stacked = m.stack_layers(per_layer)
            if self.weight_quant_bits:
                stacked = m.quantize_params(
                    stacked, self.weight_quant_bits, scale_dtype=self.param_dtype,
                    group_size=self.weight_quant_group,
                )
            self.window_params = self._cast(stacked)
        edge_raw = m.map_edge(self.ckpt.load_edge_raw())
        if self.shard_mode:
            tied = self.config.tie_word_embeddings
            if not (m.is_first or (m.is_last and tied)):
                edge_raw.pop("embed", None)
            if not m.is_last:
                edge_raw.pop("final_norm", None)
                edge_raw.pop("lm_head", None)
        # tied embeddings: lm_project reads edge["embed"] (reference handles
        # ties in load_weights, src/dnet/core/models/base.py:111-195)
        if self.weight_quant_bits:
            edge_raw = m.quantize_edge(
                edge_raw, self.weight_quant_bits, scale_dtype=self.param_dtype,
                group_size=self.weight_quant_group,
            )
        self.edge_params = self._cast(edge_raw)
        log.info(
            "[PROFILE] loaded %d layers (%s) in %.2fs",
            len(m.layers),
            self.config.model_type,
            time.perf_counter() - t0,
        )

    # ---- jitted step functions ---------------------------------------
    def _build_fns(self) -> None:
        model = self.model

        def full_logits(window_params, edge_params, tokens, kv, pos, last_idx):
            x = model.embed(edge_params, tokens)
            x, kv = apply_whole(model, window_params, x, kv, pos, t_real=last_idx + 1)
            x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
            x_last = model.normalize(edge_params, x_last)
            logits = model.lm_project(edge_params, x_last)
            return logits[:, 0], kv

        # donate kv (arg 3): each step reuses the cache buffers in place
        # (instrumented: dnet_jit_compiles_total{fn=} separates warmup
        # compiles from steady state in load reports)
        @jax.named_scope("local_prefill")
        def prefill_logits(window_params, edge_params, tokens, kv, pos, last_idx):
            return full_logits(window_params, edge_params, tokens, kv, pos, last_idx)

        self._forward = instrument_jit(
            jax.jit(prefill_logits, donate_argnums=(3,)), "local_prefill"
        )

        @jax.named_scope("new_session")
        def fresh_session(seed, with_kv):
            """A new session's device state in one program: the zeroed
            cache row (unless a prefix snapshot seeds it), the key of
            `jax.random.key(seed)`, zeroed counts (and spec history)."""
            kv = (
                model.init_kv(
                    len(model.layers), self.batch, self.max_seq,
                    self.kv_dtype, quant_bits=self.kv_quant_bits,
                )
                if with_kv
                else None
            )
            counts = jnp.zeros((self.batch, self.config.vocab_size), dtype=jnp.int32)
            hist = (
                jnp.zeros((self.batch, self.max_seq), dtype=jnp.int32)
                if self.spec_lookahead > 0
                else None
            )
            return kv, jax.random.key(seed), counts, hist

        self._fresh_session = instrument_jit(
            jax.jit(fresh_session, static_argnums=(1,)), "new_session"
        )

        @jax.named_scope("local_decode")
        def decode_and_sample(window_params, edge_params, token, kv, pos, sp, key, counts,
                              plan=None):
            logits, kv = full_logits(window_params, edge_params, token, kv, pos, 0)
            res = sample(logits, sp, key, token_counts=counts, plan=plan)
            counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
            return res, kv, counts

        self._decode = instrument_jit(
            jax.jit(decode_and_sample, static_argnums=(8,),
                    donate_argnums=(3, 7)),
            "local_decode",
        )

        @jax.named_scope("local_decode_chunk")
        def decode_chunk_fn(window_params, edge_params, token, kv, pos, sp, key, counts,
                            n_steps, plan=None):
            """n_steps decode iterations fused into ONE XLA program: the
            sampled token feeds back on-device, so the host pays one dispatch
            + one device->host read per CHUNK instead of per token.  Key
            evolution matches the per-step path exactly (split-before-sample),
            so chunked and unchunked decode produce identical streams for a
            given seed.

            Returns the per-step results PACKED into one f32 array (one
            device->host transfer per chunk — four separate array reads cost
            4 round trips, which dominates chunk latency on a remote-attached
            device), plus the last sampled token ON DEVICE so the next chunk
            can chain without a host round trip."""

            def body(carry, _):
                tok, kv, pos, key, counts = carry
                key, step_key = jax.random.split(key)
                logits, kv = full_logits(window_params, edge_params, tok, kv, pos, 0)
                res = sample(logits, sp, step_key, token_counts=counts, plan=plan)
                counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
                return (res.token[:, None], kv, pos + 1, key, counts), res

            (last_tok, kv, _, key, counts), results = jax.lax.scan(
                body, (token, kv, pos, key, counts), None, length=n_steps
            )
            packed = pack_chunk_results(results, plan is None or plan.logprobs)
            return packed, last_tok, kv, key, counts

        self._decode_chunk = instrument_jit(
            jax.jit(decode_chunk_fn, static_argnums=(8, 9),
                    donate_argnums=(3, 7)),
            "local_decode_chunk",
        )

        def hidden_step(window_params, x, kv, pos, t_real, kinds=None):
            return apply_whole(
                model, window_params, x, kv, pos, layer_kinds=kinds, t_real=t_real
            )

        # mid-shard path (no embed/head): used by the ring runtime and the
        # offload per-layer loop (kinds slices the mixed-attention array)
        self._hidden = jax.jit(hidden_step, donate_argnums=(2,))

        def hidden_round(window_params, x, kv, pos, t_real, lo, hi, kinds=None):
            """One ring ROUND: apply the [lo, hi) slice of this engine's
            stacked layers (static bounds -> one compiled program per round;
            XLA slices in place, no host-side weight copies)."""
            wp = jax.tree.map(lambda a: a[lo:hi], window_params)
            kv_r = jax.tree.map(lambda a: a[lo:hi], kv)
            x, kv_r = apply_whole(
                model, wp, x, kv_r, pos, layer_kinds=kinds, t_real=t_real
            )
            kv = jax.tree.map(lambda f, s: f.at[lo:hi].set(s), kv, kv_r)
            return x, kv

        self._hidden_round = jax.jit(
            hidden_round, static_argnums=(5, 6), donate_argnums=(2,)
        )

        def embed_window(window_params, edge_params, tokens, kv, pos, t_real):
            """First-shard path: embed + this shard's window, hidden out."""
            x = model.embed(edge_params, tokens)
            return apply_whole(model, window_params, x, kv, pos, t_real=t_real)

        self._embed_window = jax.jit(embed_window, donate_argnums=(3,))

        def hidden_tail(window_params, edge_params, x, kv, pos, last_idx, sp, key, counts):
            """Last-shard path: window + normalize + head + sample."""
            x, kv = apply_whole(model, window_params, x, kv, pos, t_real=last_idx + 1)
            x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
            x_last = model.normalize(edge_params, x_last)
            logits = model.lm_project(edge_params, x_last)[:, 0]
            res = sample(logits, sp, key, token_counts=counts)
            counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
            return res, kv, counts

        self._hidden_tail = jax.jit(hidden_tail, donate_argnums=(3, 8))

        L = self.spec_lookahead
        if L > 0:
            # one speculative verify step: draft L tokens from history, run
            # ONE forward over [tok, d_1..d_L], greedily accept the agreeing
            # prefix.  KV for all L+1 positions is written; the host-side
            # caller rewinds pos to the accepted count (core/spec.py)
            from dnet_tpu.core.spec import make_spec_step

            def window_pass(wp, x, kv, pos, t_real):
                return apply_whole(model, wp, x, kv, pos, t_real=t_real)

            self._spec_step = jax.jit(
                make_spec_step(model, window_pass, L), donate_argnums=(3, 4)
            )

        if L > 0 and self.draft is not None:
            # draft-MODEL verify block: L sequential small-model steps draft
            # the block on-device (the draft's own KV rides the session),
            # then the target verifies in one (L+1)-wide forward.  Rewind
            # discipline matches the ngram path: all drafted positions
            # write both caches; stale rows are never attended (causal
            # masks at the rewound pos) and are overwritten on reuse.
            from dnet_tpu.core.spec import accept_drafts

            dmodel = self.draft.model

            def draft_forward(dwp, dep, tok, dkv, p):
                x = dmodel.embed(dep, tok)
                x, dkv = apply_whole(dmodel, dwp, x, dkv, p, t_real=1)
                x = dmodel.normalize(dep, x)
                return dmodel.lm_project(dep, x)[:, 0], dkv

            def spec_step_draft(wp, ep, dwp, dep, tok, kv, dkv, pos):
                def body(carry, _):
                    t, dkv, p = carry
                    logits, dkv = draft_forward(dwp, dep, t, dkv, p)
                    nt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nt[:, None], dkv, p + 1), nt

                (_, dkv, _), drafts = jax.lax.scan(
                    body, (tok, dkv, pos), None, length=L
                )
                drafts = jnp.moveaxis(drafts, 0, 1)  # [B, L]
                block = jnp.concatenate([tok, drafts], axis=1)  # [B, L+1]
                x = model.embed(ep, block)
                x, kv = apply_whole(model, wp, x, kv, pos, t_real=L + 1)
                x = model.normalize(ep, x)
                logits = model.lm_project(ep, x)
                preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                _, out = accept_drafts(preds, drafts)
                return out, kv, dkv

            self._spec_step_draft = jax.jit(
                spec_step_draft, donate_argnums=(5, 6)
            )

            def draft_prefill(dwp, dep, tokens, dkv, pos, t_real):
                x = dmodel.embed(dep, tokens)
                _, dkv = apply_whole(dmodel, dwp, x, dkv, pos, t_real=t_real)
                return dkv

            self._draft_prefill = jax.jit(draft_prefill, donate_argnums=(3,))

    # ---- offload execution --------------------------------------------
    def run_layers(self, sess: "Session", x: jnp.ndarray, pos: int, t_real=None) -> jnp.ndarray:
        """Apply this engine's layers to x under the active policy.

        Fit: one fused scan over the resident stack.  Offload/sliding_fit:
        window-at-a-time — wait on the current window's prefetch, compute
        per-layer (one compiled program reused for every layer), prefetch
        the next window during compute, release+evict behind us, and wrap
        the prefetch to window 0 for the next token
        (reference offload.py:183-421)."""
        t_real = jnp.int32(x.shape[1] if t_real is None else t_real)
        if not self.plan.streams_weights:
            x, sess.kv = self._hidden(
                self.window_params, x, sess.kv, jnp.int32(pos), t_real
            )
            return x
        return self._stream_windows(sess, x, pos, t_real, self._windows, None)

    def _stream_windows(
        self, sess, x, pos, t_real, windows, prefetch_after
    ) -> jnp.ndarray:
        """Window-at-a-time weight-streaming loop; `prefetch_after` (a layer
        list) overrides the wrap-to-first prefetch — multi-round rings
        prefetch the NEXT round's window while other devices compute."""
        sliding = self.plan.name == "sliding_fit"
        for wi, window in enumerate(windows):
            if wi + 1 < len(windows):
                nxt = windows[wi + 1]
            else:
                nxt = prefetch_after if prefetch_after is not None else windows[0]
            if len(windows) > 1 or prefetch_after is not None:
                self.weight_cache.prefetch(nxt)
            for layer in window:
                p = self.weight_cache.get(layer)
                li = self.model.abs_to_local[layer]
                kinds = (
                    None
                    if self.model.layer_kinds is None
                    else self.model.layer_kinds[li : li + 1]
                )
                t0 = time.perf_counter() if self._sync_per_layer else 0.0
                x, sess.kv_list[li] = self._hidden(
                    p, x, sess.kv_list[li], jnp.int32(pos), t_real, kinds
                )
                if self._sync_per_layer:
                    x.block_until_ready()
                    dt_ms = (time.perf_counter() - t0) * 1000
                    _LAYER_MS.observe(dt_ms)
                    get_recorder().span(
                        sess.nonce, "layer_compute", dt_ms, layer=layer
                    )
                    log.info("[PROFILE] layer %d: %.2fms", layer, dt_ms)
                # unpin immediately so the residency budget can evict behind
                # us; sliding_fit (residency < window) delta-swaps eagerly
                self.weight_cache.release([layer])
                if sliding:
                    self.weight_cache.evict([layer])
            if (len(windows) > 1 or prefetch_after is not None) and not sliding:
                self.weight_cache.evict(window)  # make room for what's coming
        return x

    def apply_round(
        self,
        sess: "Session",
        x: jnp.ndarray,
        pos: int,
        run: Sequence[int],
        t_real=None,
        prefetch_next: Optional[Sequence[int]] = None,
    ) -> jnp.ndarray:
        """Apply ONE contiguous round (`run`) of this engine's layers — the
        k-round ring schedule (reference api/utils.py:62-131): a device's
        layers are dealt in k contiguous chunks and the activation visits it
        k times per token, so streamed weights prefetch while OTHER devices
        compute.  `prefetch_next` seeds the next round's first window."""
        m = self.model
        t_real = jnp.int32(x.shape[1] if t_real is None else t_real)
        if not self.plan.streams_weights:
            if (
                getattr(m, "pair_kinds", None)
                or getattr(m, "ring_phases", 1) > 1
                or getattr(m, "segmented_stack", False)
            ):
                raise NotImplementedError(
                    "multi-round rings need a flat layer stack (gpt_oss "
                    "paired / deepseek + mixed-qwen3_moe segmented layouts "
                    "pending)"
                )
            lo, hi = m.abs_to_local[run[0]], m.abs_to_local[run[-1]] + 1
            kinds = None if m.layer_kinds is None else m.layer_kinds[lo:hi]
            x, sess.kv = self._hidden_round(
                self.window_params, x, sess.kv, jnp.int32(pos), t_real, lo, hi,
                kinds,
            )
            return x
        w = self.plan.window_size or len(run)
        windows = [list(run[i : i + w]) for i in range(0, len(run), w)]
        return self._stream_windows(
            sess, x, pos, t_real, windows, list(prefetch_next or [])[:w] or None
        )

    # ---- sessions -----------------------------------------------------
    def new_session(
        self, nonce: str, seed: Optional[int] = None, kv=None, pos: int = 0
    ) -> Session:
        """kv/pos: seed the session from a prefix-cache snapshot instead of
        allocating + zero-filling a fresh cache it would immediately drop."""
        if seed is None:
            # fresh entropy per unseeded request — two users must not share a stream
            seed = int.from_bytes(__import__("os").urandom(4), "little")
        kv_list = None
        if kv is None and self.plan.streams_weights:
            kv_list = [
                init_cache(
                    self.model.kv_config(
                        1, self.batch, self.max_seq, self.kv_dtype,
                        quant_bits=self.kv_quant_bits,
                    )
                )
                for _ in self.model.layers
            ]
        # ONE program launch (a prompt's first chunk runs between a tick's
        # decode launch and its read): the seed goes in as a host scalar
        fresh_kv, key, counts, hist = self._fresh_session(
            np.uint32(seed & 0xFFFFFFFF), kv is None and kv_list is None
        )
        sess = Session(
            nonce=nonce,
            kv=fresh_kv if kv is None else kv,
            kv_list=kv_list,
            pos=pos,
            key=key,
            counts=counts,
            hist=hist,
            dkv=(
                self.draft.model.init_kv(
                    self.draft.config.num_hidden_layers, self.batch,
                    self.max_seq, self.kv_dtype,
                )
                if self.draft is not None
                else None
            ),
        )
        self.sessions[nonce] = sess
        return sess

    def end_session(self, nonce: str) -> None:
        self.sessions.pop(nonce, None)

    def sweep_sessions(self) -> int:
        now = time.time()
        dead = [n for n, s in self.sessions.items() if now - s.last_used > self.kv_ttl_s]
        for n in dead:
            del self.sessions[n]
        return len(dead)

    def reset(self) -> None:
        self.sessions.clear()

    def close(self) -> None:
        self.reset()
        if self.weight_cache is not None:
            self.weight_cache.shutdown()

    # ---- inference ----------------------------------------------------
    def prefill(
        self,
        nonce: str,
        prompt_ids: Sequence[int],
        seed: Optional[int] = None,
        allow_store: bool = True,
    ):
        """Run the prompt; returns logits at the last real position.

        Reusing a live session continues at sess.pos (chunked prefill).
        allow_store=False suppresses the inline prefix-cache snapshot (a
        chunked caller stores the FULL prompt itself at the end).
        """
        full_ids = list(prompt_ids)
        if not full_ids:
            raise ValueError("empty prompt")
        t_pf = time.perf_counter()
        sess = self.sessions.get(nonce)
        fresh = sess is None
        # validate against the FULL prompt before any session mutation: a
        # too-long prompt must not leave a half-restored session behind
        start = 0 if sess is None else sess.pos
        if start + len(full_ids) > self.max_seq:
            raise ValueError(
                f"prompt length {start + len(full_ids)} exceeds max_seq {self.max_seq}"
            )
        if sess is None:
            hit = (
                self.prefix_cache.lookup(full_ids)
                if self.prefix_cache is not None
                else None
            )
            if hit is not None:
                n, kv_copy = hit
                sess = self.new_session(nonce, seed, kv=kv_copy, pos=n)
                get_recorder().span(nonce, "prefix_cache_hit", 0.0, tokens=n)
                prompt_ids = full_ids[n:]  # >= 1 token left by construction
            else:
                sess = self.new_session(nonce, seed)
        else:
            fresh = sess.pos == 0  # explicit chunked continuation
        T = len(prompt_ids)
        self._commit_prompt_hist(sess, full_ids, prompt_ids)
        # the PADDED width must also fit — dynamic_update_slice would clamp
        # the start index and silently shift the whole KV write otherwise
        Tpad = min(bucket_length(T), self.max_seq - sess.pos)
        tokens = np.zeros((self.batch, Tpad), dtype=np.int32)
        tokens[:, :T] = np.asarray(prompt_ids, dtype=np.int32)
        count_expert_rows(self.model, self.batch * Tpad, whole=self.whole_batch_programs)
        if self.plan.streams_weights:
            x = self.model.embed(self.edge_params, jnp.asarray(tokens))
            x = self.run_layers(sess, x, sess.pos, t_real=T)
            x_last = jax.lax.dynamic_slice_in_dim(x, T - 1, 1, axis=1)
            x_last = self.model.normalize(self.edge_params, x_last)
            logits = self.model.lm_project(self.edge_params, x_last)[:, 0]
        else:
            logits, sess.kv = self._forward(
                self.window_params, self.edge_params, tokens, sess.kv,
                np.int32(sess.pos), np.int32(T - 1),
            )
        if self.draft is not None:
            if fresh and len(prompt_ids) != len(full_ids):
                # prefix-cache hit seeded only the TARGET's kv; the draft
                # (tiny) simply re-reads the whole prompt from position 0
                self._advance_draft(sess, full_ids, 0)
            else:
                self._advance_draft(sess, prompt_ids, sess.pos)
        # repetition penalty counts GENERATED tokens only (prompt tokens are
        # not seeded): the ring's sampling shard never sees prompt ids, so
        # both serving paths must share this definition to stay equivalent.
        sess.pos += T
        sess.last_used = time.time()
        if (
            self.prefix_cache is not None
            and allow_store
            and fresh
            and sess.pos == len(full_ids)
        ):
            # snapshot the full-prompt KV (copied: step fns donate their kv;
            # the cache itself skips prompts below its min_tokens threshold)
            self.prefix_cache.store(full_ids, sess.kv)
        # dispatch wall time (logits are still async); a synced number needs
        # the DNET_OBS_SYNC_* fences, same as the [PROFILE] lines always did
        dt_ms = (time.perf_counter() - t_pf) * 1000
        _PREFILL_MS.observe(dt_ms)
        get_recorder().span(nonce, "prefill", dt_ms, tokens=T)
        return logits

    def seed_from_prefix(
        self, nonce: str, full_ids: Sequence[int], seed: Optional[int] = None
    ) -> int:
        """Chunk-aware prefix-cache entry: seed a FRESH session from the
        longest cached prefix of the FULL prompt (a chunked prefill would
        otherwise only look up its first chunk).  Returns the cached token
        count (0 = no hit)."""
        if self.prefix_cache is None or nonce in self.sessions:
            return 0
        hit = self.prefix_cache.lookup(list(full_ids))
        if hit is None:
            return 0
        n, kv_copy = hit
        self._restore_session(nonce, full_ids, n, kv_copy, seed)
        return n

    def _restore_session(
        self, nonce: str, full_ids: Sequence[int], n: int, kv, seed
    ) -> "Session":
        """Seed a FRESH session from a restored n-token prefix: the session
        itself, the spec history (the follow-up prefill only writes its own
        remainder — without this, prompt-lookup drafts would match against
        zeros), and the draft model's context (its kv is not cached;
        re-reading the prefix through the tiny model is cheaper than
        caching a second kv family).  Shared by this engine's prefix path
        and the batched engine's paged block adoption."""
        sess = self.new_session(nonce, seed, kv=kv, pos=n)
        if sess.hist is not None:
            ids = jnp.asarray(
                np.broadcast_to(np.asarray(full_ids[:n], dtype=np.int32), (self.batch, n))
            )
            sess.hist = jax.lax.dynamic_update_slice_in_dim(sess.hist, ids, 0, axis=1)
        self._advance_draft(sess, list(full_ids[:n]), 0)
        return sess

    def _advance_draft(self, sess: "Session", ids: Sequence[int], pos0: int) -> None:
        """Run the draft model over `ids` at absolute position pos0 so its
        cache tracks the committed context (draft-model speculation)."""
        if self.draft is None or sess.dkv is None or not ids:
            return
        T = len(ids)
        Tpad = min(bucket_length(T), self.max_seq - pos0)
        tokens = np.zeros((self.batch, Tpad), dtype=np.int32)
        tokens[:, :T] = np.asarray(ids, dtype=np.int32)
        sess.dkv = self._draft_prefill(
            self.draft.window, self.draft.edge, jnp.asarray(tokens),
            sess.dkv, jnp.int32(pos0), jnp.int32(T),
        )

    def store_prefix(self, nonce: str, full_ids: Sequence[int]) -> None:
        """Snapshot a fully-prefilled session's KV under the full prompt
        (chunked-prefill counterpart of the inline store in prefill())."""
        sess = self.sessions.get(nonce)
        if (
            self.prefix_cache is not None
            and sess is not None
            and sess.kv is not None
            and sess.pos == len(full_ids)
        ):
            self.prefix_cache.store(list(full_ids), sess.kv)

    def hidden_states(self, prompt_ids: Sequence[int]) -> np.ndarray:
        """Final-norm'd hidden states for a prompt — the embeddings serving
        primitive (BEYOND the reference, which schemas /v1/embeddings but
        never serves it).  One forward over a throwaway session, no
        sampling; works under every weight policy via run_layers.  Returns
        float32 [T, D] (callers pool)."""
        ids = list(prompt_ids)
        if not ids:
            raise ValueError("empty embeddings input")
        if len(ids) > self.max_seq:
            raise ValueError(
                f"input length {len(ids)} exceeds max_seq {self.max_seq}"
            )
        T = len(ids)
        Tpad = min(bucket_length(T), self.max_seq)
        tokens = np.zeros((self.batch, Tpad), dtype=np.int32)
        tokens[:, :T] = np.asarray(ids, dtype=np.int32)
        nonce = "__embed__"
        self.end_session(nonce)
        sess = self.new_session(nonce, seed=0)
        try:
            x = self.model.embed(self.edge_params, jnp.asarray(tokens))
            x = self.run_layers(sess, x, 0, t_real=T)
            h = self.model.normalize(self.edge_params, x)
            return np.asarray(h[0, :T], dtype=np.float32)
        finally:
            self.end_session(nonce)

    def decode_step(self, nonce: str, token_id: int, decoding: DecodingParams) -> SampleResult:
        sess = self.sessions[nonce]
        if sess.pos >= self.max_seq:
            raise ValueError(
                f"sequence length {sess.pos} reached max_seq {self.max_seq}"
            )
        t_step = time.perf_counter()
        sess.key, step_key = jax.random.split(sess.key)
        sp = SampleParams.from_decoding(decoding)
        plan = SamplePlan.from_decoding(decoding)
        token = jnp.full((self.batch, 1), token_id, dtype=jnp.int32)
        if self.plan.streams_weights:
            x = self.model.embed(self.edge_params, token)
            x = self.run_layers(sess, x, sess.pos, t_real=1)
            x = self.model.normalize(self.edge_params, x)
            logits = self.model.lm_project(self.edge_params, x)[:, 0]
            res = sample(logits, sp, step_key, token_counts=sess.counts, plan=plan)
            sess.counts = sess.counts.at[:, int(res.token[0])].add(1)
        else:
            res, sess.kv, sess.counts = self._decode(
                self.window_params, self.edge_params, token, sess.kv,
                jnp.int32(sess.pos), sp, step_key, sess.counts, plan,
            )
        if self._sync_every_n and sess.pos % self._sync_every_n == 0:
            t0 = time.perf_counter()
            res.token.block_until_ready()
            drain_ms = (time.perf_counter() - t0) * 1000
            get_recorder().span(nonce, "decode_sync_drain", drain_ms,
                                step=sess.pos)
            log.info(
                "[PROFILE] decode step %d sync: %.2fms drain",
                sess.pos, drain_ms,
            )
        # dispatch wall (synced only when the fence above ran this step)
        _DECODE_STEP_MS.observe((time.perf_counter() - t_step) * 1000)
        sess.pos += 1
        sess.last_used = time.time()
        return res

    # ---- speculative decoding ----------------------------------------
    def _commit_prompt_hist(self, sess, full_ids, prompt_ids) -> None:
        """Commit the prompt to the spec history buffer; on a prefix-cache
        hit write the FULL prompt at 0 (the cached tokens were never fed
        through THIS session).  Shared by LocalEngine and MeshEngine
        prefill (same hist contract, two execution substrates)."""
        if self.spec_lookahead <= 0 or sess.hist is None:
            return
        n_cached = len(full_ids) - len(prompt_ids)
        ids = jnp.asarray(
            np.broadcast_to(
                np.asarray(full_ids, dtype=np.int32), (self.batch, len(full_ids))
            )
        )
        sess.hist = jax.lax.dynamic_update_slice_in_dim(
            sess.hist, ids, sess.pos - n_cached, axis=1
        )

    def spec_eligible(self, decoding: DecodingParams) -> bool:
        """Whether this engine + request pair may take the speculative path.

        Greedy only (spec emits raw argmaxes; sampled streams would need
        rejection sampling), no logprobs (the verify forward discards the
        softmax), no repetition penalty (counts are not threaded through the
        verify block), resident weights only (a streamed verify would re-read
        every window per block, erasing the win), batch 1 (acceptance length
        is per-lane), and a rewind-safe cache layout (rotating SWA ring
        buffers cannot rewind — core/spec.py)."""
        return (
            self.spec_lookahead > 0
            and self.batch == 1
            and not self.plan.streams_weights
            and self.model.kv_rewindable(self.max_seq)
            and decoding.temperature == 0.0
            and not decoding.logprobs
            and decoding.repetition_penalty == 1.0
            and not decoding.logit_bias  # verify argmaxes are unbiased
        )

    # adaptive gate thresholds: a spec block costs one (L+1)-wide forward +
    # one host sync per <=L+1 tokens; a decode chunk costs one forward per
    # token but only one sync per ~32.  Below ~1.5 tokens/block, chunks win.
    SPEC_WARMUP_BLOCKS = 4
    SPEC_MIN_TOKENS_PER_BLOCK = 1.5

    def spec_worthwhile(self, nonce: str) -> bool:
        """Per-session acceptance gate: after a warmup, sessions whose
        drafts rarely accept (non-repetitive output — prompt-lookup has
        nothing to look up) fall back to chunked decode rather than paying
        one dispatch + host sync per ~1 token, the exact gap chunking
        closed.  The callers re-check every block, so speculation stops the
        moment it stops paying; it does not resume within the session."""
        sess = self.sessions.get(nonce)
        if sess is None or sess.spec_blocks < self.SPEC_WARMUP_BLOCKS:
            return True
        return (
            sess.spec_emitted / sess.spec_blocks >= self.SPEC_MIN_TOKENS_PER_BLOCK
        )

    def decode_spec(
        self,
        nonce: str,
        token_id: Optional[int],
        decoding: DecodingParams,
        max_new: int,
    ) -> List[SampleResult]:
        """One speculative verify block: feed `token_id` (None chains from
        the device-resident last emitted token), draft spec_lookahead tokens
        by prompt-lookup, verify in ONE forward, emit the accepted prefix
        plus the first correction — 1..L+1 tokens per weight read.  Emission
        is clamped to `max_new`; sess.pos advances by exactly the emitted
        count (stale KV/history rows are overwritten by the next block)."""
        sess = self.sessions[nonce]
        L = self.spec_lookahead
        if sess.pos >= self.max_seq:
            raise ValueError(
                f"sequence length {sess.pos} reached max_seq {self.max_seq}"
            )
        budget = min(max_new, self.max_seq - sess.pos)
        if budget <= 1 or sess.pos + L + 1 > self.max_seq:
            # no room to speculate: one plain step keeps the stream moving
            tid = (
                token_id
                if token_id is not None
                else int(np.asarray(sess.last_token)[0, 0])
            )
            return [self.decode_step(nonce, tid, decoding)]
        t_blk = time.perf_counter()
        if token_id is None:
            if sess.last_token is None:
                raise RuntimeError("no device-resident token to chain from")
            tok = sess.last_token
        else:
            tok = jnp.full((self.batch, 1), token_id, dtype=jnp.int32)
        if self.draft is not None:
            out, sess.kv, sess.dkv = self._spec_step_draft(
                self.window_params, self.edge_params,
                self.draft.window, self.draft.edge,
                tok, sess.kv, sess.dkv, jnp.int32(sess.pos),
            )
        else:
            out, sess.hist, sess.kv = self._spec_step(
                self.window_params, self.edge_params, tok, sess.hist, sess.kv,
                jnp.int32(sess.pos),
            )
        out_h = np.asarray(out)  # [B, L+1]; blocks until the block finishes
        emitted = min(int((out_h[0] >= 0).sum()), budget)
        # the verify block amortizes one forward over `emitted` tokens:
        # record the per-token share so the histogram's count stays equal
        # to tokens served across the plain / chunked / speculative paths
        per_tok_ms = (time.perf_counter() - t_blk) * 1000 / max(emitted, 1)
        _DECODE_STEP_MS.observe_n(per_tok_ms, emitted)
        sess.pos += emitted
        sess.spec_blocks += 1
        sess.spec_emitted += emitted
        sess.last_used = time.time()
        sess.last_token = jnp.asarray(out_h[:, emitted - 1 : emitted])
        B = out_h.shape[0]
        zero_lp = np.zeros((B,), np.float32)
        zero_tt = np.zeros((B, MAX_TOP_LOGPROBS), np.int32)
        zero_tlp = np.zeros((B, MAX_TOP_LOGPROBS), np.float32)
        return [
            SampleResult(
                np.ascontiguousarray(out_h[:, i]).astype(np.int32),
                zero_lp, zero_tt, zero_tlp,
            )
            for i in range(emitted)
        ]

    # chunk widths tried largest-first: a fixed bucket set keeps the number
    # of compiled scan programs bounded (one per width actually used)
    DECODE_CHUNK_BUCKETS = (32, 16, 8, 4, 2)

    def decode_chunk_dispatch(
        self,
        nonce: str,
        token_id: Optional[int],
        decoding: DecodingParams,
        max_steps: int,
    ) -> int:
        """Dispatch (async) a fused chunk of up to `max_steps` decode steps.

        token_id None chains from the DEVICE-resident last token of the
        previously dispatched chunk — the host never has to read a token to
        keep the device busy, so result transfers overlap the next chunk's
        compute.  Returns the dispatched width (0 = not chunkable; caller
        falls back to decode_step).  Results are read by decode_chunk_read
        in dispatch order.
        """
        sess = self.sessions[nonce]
        if sess.pos >= self.max_seq:
            # full context is not an error HERE: the caller may be
            # speculating past a chunk that exactly filled the sequence —
            # returning 0 routes the next real step to decode_step, which
            # raises the definitive "reached max_seq" for the request
            return 0
        budget = min(max_steps, self.max_seq - sess.pos)
        K = next((b for b in self.DECODE_CHUNK_BUCKETS if b <= budget), 1)
        if K == 1 or self.plan.streams_weights:
            return 0
        if token_id is None:
            if sess.last_token is None:
                raise RuntimeError("no device-resident token to chain from")
            token = sess.last_token
        else:
            token = jnp.full((self.batch, 1), token_id, dtype=jnp.int32)
        sp = SampleParams.from_decoding(decoding)
        plan = SamplePlan.from_decoding(decoding)
        packed, sess.last_token, sess.kv, sess.key, sess.counts = self._decode_chunk(
            self.window_params, self.edge_params, token, sess.kv,
            jnp.int32(sess.pos), sp, sess.key, sess.counts, K, plan,
        )
        sess.pending.append((K, packed, plan))
        sess.pos += K
        sess.last_used = time.time()
        return K

    def pending_chunks(self, nonce: str) -> int:
        """Dispatched-but-unread chunk count (0 for unknown sessions)."""
        sess = self.sessions.get(nonce)
        return len(sess.pending) if sess is not None else 0

    def pending_width(self, nonce: str) -> int:
        """Total tokens in flight across dispatched-but-unread chunks."""
        sess = self.sessions.get(nonce)
        return sum(k for k, _, _ in sess.pending) if sess is not None else 0

    def decode_chunk_read(self, nonce: str) -> List[SampleResult]:
        """Read the oldest dispatched chunk: ONE device->host transfer for
        the packed [K, B, W] result block, split host-side."""
        sess = self.sessions[nonce]
        K, packed, plan = sess.pending.popleft()
        t0 = time.perf_counter()
        arr = np.asarray(packed)  # blocks until the chunk's program finishes
        # the blocking read amortizes the chunk: record the per-token share
        # (K observations keep the histogram's count == tokens served)
        per_tok_ms = (time.perf_counter() - t0) * 1000 / K
        _DECODE_STEP_MS.observe_n(per_tok_ms, K)
        toks = arr[..., 0].astype(np.int32)  # [K, B]
        if plan.logprobs:
            M = MAX_TOP_LOGPROBS
            lps = arr[..., 1]
            tt = arr[..., 2 : 2 + M].astype(np.int32)
            tlp = arr[..., 2 + M : 2 + 2 * M]
        else:
            B = arr.shape[1]
            lps = np.zeros((K, B), np.float32)
            tt = np.zeros((K, B, MAX_TOP_LOGPROBS), np.int32)
            tlp = np.zeros((K, B, MAX_TOP_LOGPROBS), np.float32)
        return [SampleResult(toks[i], lps[i], tt[i], tlp[i]) for i in range(K)]

    def decode_chunk(
        self,
        nonce: str,
        token_id: int,
        decoding: DecodingParams,
        max_steps: int,
    ) -> list[SampleResult]:
        """Up to `max_steps` decode steps in one on-device lax.scan
        (dispatch + read in one call; the pipelining adapter calls the two
        halves itself to overlap the read with the next chunk's compute).

        Returns one host-side SampleResult per generated token.  The caller
        owns EOS / stop-sequence checks: tokens past a stop are simply
        discarded with the session, exactly as the reference's driver
        discards its own overshoot (the KV rows they wrote die with the
        session).  Closes the per-token dispatch gap flagged in BASELINE.md
        (49 tok/s dispatched vs 208 fused).
        """
        if self.decode_chunk_dispatch(nonce, token_id, decoding, max_steps) == 0:
            return [self.decode_step(nonce, token_id, decoding)]
        return self.decode_chunk_read(nonce)

    # plans warmed ahead of traffic: greedy, unfiltered-sampled (the
    # OpenAI-default request: temperature 1, top_p 1), and filtered-sampled;
    # logprobs/penalty variants compile on first use
    WARM_DECODINGS = (
        DecodingParams(),  # greedy: temperature 0, no filters
        DecodingParams(temperature=1.0),  # API-default sampled, no filters
        DecodingParams(temperature=0.7, top_p=0.9),  # sampled + filters
        # bias=True is its own plan dimension: warm it so the first
        # logit_bias request doesn't stall mid-stream on the compile
        DecodingParams(logit_bias={0: 0.0}),
    )

    def warm_chunks(self) -> None:
        """Compile the decode-chunk programs (and the single-step decode)
        for the common sampling plans up front, so the first request's ramp
        never stalls mid-stream on a synchronous XLA compile.  SamplePlan is
        a static jit argument, so each warmed DecodingParams shape is its
        own program set."""
        if self.plan.streams_weights:
            return
        nonce = "__warm__"
        t0 = time.perf_counter()
        for dec in self.WARM_DECODINGS:
            self.end_session(nonce)
            try:
                self.prefill_and_sample(nonce, [0], dec)
                for b in self.DECODE_CHUNK_BUCKETS:
                    if self.sessions[nonce].pos + b < self.max_seq:
                        self.decode_chunk(nonce, 0, dec, b)
                self.decode_step(nonce, 0, dec)
            finally:
                self.end_session(nonce)
        if self.spec_lookahead > 0:
            # the verify block is the same compile class as the chunk scans;
            # pay it here, not on the first eligible request's first block
            self.end_session(nonce)
            try:
                self.prefill_and_sample(nonce, [0], DecodingParams(temperature=0.0))
                self.decode_spec(nonce, 0, DecodingParams(temperature=0.0), 2)
            finally:
                self.end_session(nonce)
        log.info(
            "[PROFILE] warmed decode-chunk programs (%d plans) in %.1fs",
            len(self.WARM_DECODINGS),
            time.perf_counter() - t0,
        )

    def generate(
        self,
        prompt_ids: Sequence[int],
        decoding: Optional[DecodingParams] = None,
        max_tokens: int = 256,
        eos_token_ids: Optional[set[int]] = None,
        nonce: str = "local",
    ) -> Iterator[TokenResult]:
        """Greedy/sampled autoregressive generation, yielding per-token results."""
        decoding = decoding or DecodingParams()
        eos = eos_token_ids or set()
        self.end_session(nonce)
        # session is created by prefill (which may seed it from the prefix
        # cache); the seed flows via prefill_and_sample
        res = self.prefill_and_sample(nonce, prompt_ids, decoding)
        sess = self.sessions[nonce]
        token = int(res.token[0])
        yield self.token_result(nonce, res, step=0, decoding=decoding)
        if token in eos:
            self.end_session(nonce)
            return

        use_spec = self.spec_eligible(decoding)
        step = 1
        while step < max_tokens:
            if sess.pos >= self.max_seq:
                break  # cache capacity reached: stop cleanly (finish_reason=length)
            if use_spec and self.spec_worthwhile(nonce):
                results = self.decode_spec(nonce, token, decoding, max_tokens - step)
            else:
                results = [self.decode_step(nonce, token, decoding)]
            stop = False
            for res in results:
                token = int(res.token[0])
                yield self.token_result(nonce, res, step=step, decoding=decoding)
                step += 1
                if token in eos:
                    stop = True
                    break
            if stop:
                break
        self.end_session(nonce)

    def _sample_with_counts(
        self, sess: "Session", logits, decoding: DecodingParams
    ) -> SampleResult:
        """A session's next token from `logits`: `sample_with_counts` below
        (shared by LocalEngine and MeshEngine) over the session's key and
        counts.  One program launch, nothing read."""
        res, sess.key, sess.counts = sample_with_counts(
            logits, decoding, sess.key, sess.counts
        )
        return res

    def prefill_and_sample(
        self, nonce: str, prompt_ids: Sequence[int], decoding: DecodingParams
    ) -> SampleResult:
        """Prefill the prompt and sample the first token."""
        logits = self.prefill(nonce, prompt_ids, decoding.seed)
        return self._sample_with_counts(self.sessions[nonce], logits, decoding)

    @staticmethod
    def token_result(nonce: str, res: SampleResult, step: int, decoding: DecodingParams) -> TokenResult:
        """A SampleResult as the driver's TokenResult.  Each field it needs
        is converted ONCE, whole, and indexed on the host: a device result
        (the adapters') costs one transfer a field and no program, a host
        result (the scheduler's, read on the compute thread) nothing.
        Indexing a device array would enqueue a slice program behind
        whatever the device has queued and wait it out."""
        top = None
        if decoding.logprobs and decoding.top_logprobs > 0:
            n = min(decoding.top_logprobs, res.top_tokens.shape[-1])
            top = list(
                zip(
                    np.asarray(res.top_tokens)[0, :n].tolist(),
                    np.asarray(res.top_logprobs)[0, :n].tolist(),
                )
            )
        return TokenResult(
            nonce=nonce,
            token_id=int(np.asarray(res.token)[0]),
            logprob=float(np.asarray(res.logprob)[0]) if decoding.logprobs else None,
            top_logprobs=top,
            step=step,
        )


def _split_sample_count(logits, sp, key, counts, plan):
    key, step_key = jax.random.split(key)
    res = sample(logits, sp, step_key, token_counts=counts, plan=plan)
    # per-lane counts, matching the jitted decode/chunk programs exactly:
    # penalty state must not depend on which dispatch path served a step
    counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
    return res, key, counts


_SAMPLE_WITH_COUNTS = instrument_jit(
    jax.jit(_split_sample_count, static_argnames=("plan",)), "sample_with_counts"
)


def sample_with_counts(logits, decoding: DecodingParams, key, counts):
    """THE place owning the key-split / sample / counts invariants outside
    the decode programs (a prompt's first token on every engine, the mesh
    engine's steps): (result, the advanced key, counts with the sampled
    token booked).  ONE jitted program a SamplePlan (the plan static, every
    knob traced, as in the decode programs), the request's SampleParams
    going in as a host-built pytree: nothing is dispatched eagerly and
    nothing is read."""
    return _SAMPLE_WITH_COUNTS(
        logits, SampleParams.from_decoding(decoding), key, counts,
        plan=SamplePlan.from_decoding(decoding),
    )
