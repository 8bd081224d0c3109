"""MeshEngine: serve a whole model on a pp x tp x dp mesh as ONE XLA program.

The flagship TPU-native serving path (SURVEY.md §7 stage 4): where the
reference runs N shard processes exchanging gRPC frames, chips of one slice
form a Mesh and every decode step — all pipeline stages, tensor-parallel
matmuls, the activation hops (`lax.ppermute` over ICI) and the final logits —
is a single jitted step.  Exposes the LocalEngine session surface
(prefill_and_sample / decode_step / sessions / token_result), so the API
node's LocalAdapter drives it unchanged.
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.core.engine import LocalEngine, Session, bucket_length
from dnet_tpu.core.kvcache import init_cache
from dnet_tpu.core.sampler import SampleResult
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.models import ModelConfig, get_ring_model_cls
from dnet_tpu.parallel.mesh import build_mesh
from dnet_tpu.parallel.ring import (
    make_ring_chunk_fn,
    make_ring_decode_fn,
    place_ring_state,
)
from dnet_tpu.utils.checkpoint import Checkpoint
from dnet_tpu.utils.logger import get_logger

log = get_logger()


class MeshEngine:
    """LocalEngine-compatible engine executing the pipelined ring in-slice.

    Session/sampling invariants are LocalEngine's own methods, borrowed via
    duck typing — one implementation, two execution substrates.
    """

    token_result = staticmethod(LocalEngine.token_result)
    prefill_and_sample = LocalEngine.prefill_and_sample
    _sample_with_counts = LocalEngine._sample_with_counts
    end_session = LocalEngine.end_session
    sweep_sessions = LocalEngine.sweep_sessions
    reset = LocalEngine.reset
    # chunked-scan decode: the ring chunk program (make_ring_chunk_fn) keeps
    # LocalEngine's (packed, last_token, kv, key, counts) contract, so the
    # dispatch/read/pipelining machinery is borrowed verbatim — one
    # implementation, two execution substrates
    DECODE_CHUNK_BUCKETS = LocalEngine.DECODE_CHUNK_BUCKETS
    decode_chunk_dispatch = LocalEngine.decode_chunk_dispatch
    decode_chunk_read = LocalEngine.decode_chunk_read
    decode_chunk = LocalEngine.decode_chunk
    pending_chunks = LocalEngine.pending_chunks
    pending_width = LocalEngine.pending_width
    WARM_DECODINGS = LocalEngine.WARM_DECODINGS
    warm_chunks = LocalEngine.warm_chunks
    # speculative decoding: the ring verify program (make_ring_spec_fn)
    # keeps LocalEngine's _spec_step contract, so the eligibility gates and
    # the whole decode_spec driver are borrowed unchanged
    spec_lookahead = 0
    spec_eligible = LocalEngine.spec_eligible
    spec_worthwhile = LocalEngine.spec_worthwhile
    SPEC_WARMUP_BLOCKS = LocalEngine.SPEC_WARMUP_BLOCKS
    SPEC_MIN_TOKENS_PER_BLOCK = LocalEngine.SPEC_MIN_TOKENS_PER_BLOCK
    decode_spec = LocalEngine.decode_spec
    _commit_prompt_hist = LocalEngine._commit_prompt_hist

    def __init__(
        self,
        model_dir: str | Path,
        pp: int = 0,
        tp: int = 1,
        dp: int = 1,
        sp: int = 1,
        batch: int = 1,
        max_seq: int = 2048,
        param_dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        kv_quant_bits: int = 0,
        kv_ttl_s: float = 600.0,
        devices: Optional[Sequence] = None,
        weight_quant_bits: int = 0,
        quant_group: int = 0,  # 0 = quantizer default; must divide in/tp
        prefix_cache_size: int = 0,
        spec_lookahead: int = 0,
    ):
        self.ckpt = Checkpoint(model_dir)
        self.config = ModelConfig.from_hf(self.ckpt.config)
        model_cls = get_ring_model_cls(self.config.model_type)
        self.model = model_cls(self.config, range(self.config.num_hidden_layers))
        self.model.on_mesh = True  # the cache shards by kv head (mesh.py kv_spec)
        L = self.config.num_hidden_layers
        # segmented models zero-pad their stacks to pp divisibility — per
        # segment for multi-lap rings (ring_phases > 1), chunk-aligned for
        # interleaved layouts (pp_pad_chunks, models/qwen3_moe.py r5) — so
        # L need not divide evenly
        segmented = (
            getattr(self.model, "ring_phases", 1) > 1
            or getattr(self.model, "pp_pad_chunks", False)
        )
        if pp <= 0:  # 0 = infer: use every remaining device for pipeline stages
            n_dev = len(list(devices) if devices is not None else jax.devices())
            pp = max(n_dev // (tp * dp * sp), 1)
            while pp > 1 and L % pp != 0 and not segmented:
                pp -= 1
        if L % pp != 0 and not segmented:
            raise ValueError(f"pp={pp} must divide num_layers={L}")
        if sp > 1 and max_seq % sp != 0:
            raise ValueError(f"sp={sp} must divide max_seq={max_seq}")
        self.mesh = build_mesh(pp=pp, tp=tp, dp=dp, sp=sp, devices=devices)
        self.pp, self.tp, self.dp, self.sp = pp, tp, dp, sp
        self.batch = batch * dp
        self.max_seq = max_seq
        self.param_dtype = jnp.dtype(param_dtype)
        self.kv_dtype = kv_dtype or param_dtype
        self.kv_quant_bits = kv_quant_bits
        self.weight_quant_bits = weight_quant_bits
        self.quant_group = quant_group
        if weight_quant_bits and not self.model.supports_weight_quant:
            raise NotImplementedError(
                f"weight quantization not supported for {self.config.model_type}"
            )
        self.kv_ttl_s = kv_ttl_s
        self.sessions: Dict[str, Session] = {}
        self.plan = type("plan", (), {"streams_weights": False, "name": "fit"})()
        # the borrowed decode_spec driver branches on self.draft (draft-MODEL
        # speculation is LocalEngine-only); without the attribute the first
        # verify block dies on AttributeError mid-stream
        self.draft = None
        self.prefix_cache = None
        if prefix_cache_size > 0:
            # snapshots stay mesh-sharded: restore is a copy with the same
            # NamedSharding, no host round-trip
            from dnet_tpu.core.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(prefix_cache_size)

        self._load_params()
        self._step = make_ring_decode_fn(self.model, self.mesh, self._host_window)
        self._decode_chunk = make_ring_chunk_fn(
            self.model, self.mesh, self._host_window
        )
        self.spec_lookahead = int(spec_lookahead)
        if self.spec_lookahead > 0:
            from dnet_tpu.parallel.ring import make_ring_spec_fn

            self._spec_step = make_ring_spec_fn(
                self.model, self.mesh, self._host_window, self.spec_lookahead
            )
        log.info(
            "MeshEngine: %s over mesh pp=%d tp=%d dp=%d sp=%d (%d devices)",
            self.config.model_type, pp, tp, dp, sp, pp * tp * dp * sp,
        )

    @classmethod
    def from_params(
        cls,
        config: ModelConfig,
        window_params,
        edge_params,
        *,
        pp: int = 0,
        tp: int = 1,
        dp: int = 1,
        sp: int = 1,
        batch: int = 1,
        max_seq: int = 2048,
        param_dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        kv_quant_bits: int = 0,
        kv_ttl_s: float = 600.0,
        devices: Optional[Sequence] = None,
    ) -> "MeshEngine":
        """Build a mesh engine around already-materialised (host) params —
        the zero-egress bench path (mirror of LocalEngine.from_params): the
        serving hot loop and shardings are identical, only weight
        provenance differs.  Params may already be quantized."""
        self = cls.__new__(cls)
        self.ckpt = None
        self.config = config
        model_cls = get_ring_model_cls(config.model_type)
        self.model = model_cls(config, range(config.num_hidden_layers))
        self.model.on_mesh = True
        L = config.num_hidden_layers
        segmented = (
            getattr(self.model, "ring_phases", 1) > 1
            or getattr(self.model, "pp_pad_chunks", False)
        )
        if pp <= 0:
            n_dev = len(list(devices) if devices is not None else jax.devices())
            pp = max(n_dev // (tp * dp * sp), 1)
            while pp > 1 and L % pp != 0 and not segmented:
                pp -= 1
        if L % pp != 0 and not segmented:
            raise ValueError(f"pp={pp} must divide num_layers={L}")
        self.mesh = build_mesh(pp=pp, tp=tp, dp=dp, sp=sp, devices=devices)
        self.pp, self.tp, self.dp, self.sp = pp, tp, dp, sp
        self.batch = batch * dp
        self.max_seq = max_seq
        self.param_dtype = jnp.dtype(param_dtype)
        self.kv_dtype = kv_dtype or param_dtype
        self.kv_quant_bits = kv_quant_bits
        # params may arrive pre-quantized: detect for honest introspection,
        # and run the same actionable divisibility check as __init__
        from dnet_tpu.ops.quant import is_quantized

        quantized = isinstance(window_params, dict) and any(
            isinstance(v, dict) and is_quantized(v)
            for v in window_params.values()
        )
        self.weight_quant_bits = 8 if quantized else 0
        self.quant_group = 0
        self.kv_ttl_s = kv_ttl_s
        self.sessions = {}
        self.plan = type("plan", (), {"streams_weights": False, "name": "fit"})()
        self.draft = None  # mesh spec drafts by prompt-lookup only
        self.prefix_cache = None
        if isinstance(window_params, dict):
            self._check_quant_sharding(window_params)
        m = self.model
        self._n_kv_layers = len(m.layers)
        self._host_window = window_params
        kv0 = m.init_kv(
            self._n_kv_layers, self.batch, self.max_seq, self.kv_dtype,
            quant_bits=self.kv_quant_bits, rotating=(self.sp == 1),
        )
        self.window_params, self.edge_params, self._kv_template = place_ring_state(
            window_params, edge_params, kv0, self.mesh
        )
        self._step = make_ring_decode_fn(self.model, self.mesh, self._host_window)
        self._decode_chunk = make_ring_chunk_fn(
            self.model, self.mesh, self._host_window
        )
        return self

    def _check_quant_sharding(self, stacked: dict) -> None:
        """Fail fast with an actionable message when the scale-group axis of
        an in-sharded (row-parallel) weight cannot split over tp — otherwise
        the error surfaces as an opaque NamedSharding divisibility failure
        deep in place_ring_state."""
        from dnet_tpu.ops.quant import is_quantized
        from dnet_tpu.parallel.mesh import _ROW_PARALLEL

        if self.tp <= 1:
            return
        for name, w in stacked.items():
            if name in _ROW_PARALLEL and is_quantized(w):
                g = w["s"].shape[-2]
                if g % self.tp != 0:
                    raise ValueError(
                        f"weight {name!r} has {g} dequant scale groups, not "
                        f"divisible by tp={self.tp}: pass quant_group=G with "
                        f"G dividing in/tp (e.g. DNET_API_WEIGHT_QUANT_GROUP)"
                    )

    # ---- loading ------------------------------------------------------
    def _load_params(self) -> None:
        t0 = time.perf_counter()
        m = self.model
        per_layer = [m.map_layer(self.ckpt.load_layer_raw(a)) for a in m.layers]
        stacked = m.stack_layers(per_layer)
        if self.weight_quant_bits:
            # quantize raw values; the TP/PP shardings apply unchanged to the
            # {"q"/"q4","s"} leaves (scales share the weight's axis layout),
            # and groups stay rank-local because quant_group divides in/tp
            stacked = m.quantize_params(
                stacked,
                self.weight_quant_bits,
                scale_dtype=self.param_dtype,
                group_size=self.quant_group,
            )
            self._check_quant_sharding(stacked)

        def cast(a):
            arr = np.asarray(a)
            if np.issubdtype(arr.dtype, np.floating):
                import ml_dtypes

                target = (
                    ml_dtypes.bfloat16
                    if self.param_dtype == jnp.bfloat16
                    else self.param_dtype
                )
                arr = arr.astype(target)
            return arr

        # segmented models: zero-pad each segment's layer axis to a pp
        # multiple (exact residual no-ops); the KV cache then holds the
        # padded layer count, laid out per-rank (dense rows then moe rows)
        self._n_kv_layers = len(m.layers)
        if (
            getattr(m, "ring_phases", 1) > 1
            or getattr(m, "pp_pad_chunks", False)
        ):
            stacked, self._n_kv_layers = m.pad_mesh_segments(stacked, self.pp)
        self._host_window = jax.tree.map(cast, stacked)
        edge_raw = m.map_edge(self.ckpt.load_edge_raw())
        if self.weight_quant_bits:
            edge_raw = m.quantize_edge(
                edge_raw, self.weight_quant_bits, scale_dtype=self.param_dtype,
                group_size=self.quant_group,
            )
        edge = jax.tree.map(cast, edge_raw)
        kv0 = m.init_kv(
            self._n_kv_layers, self.batch, self.max_seq, self.kv_dtype,
            quant_bits=self.kv_quant_bits, rotating=(self.sp == 1),
        )
        self.window_params, self.edge_params, self._kv_template = place_ring_state(
            self._host_window, edge, kv0, self.mesh
        )
        log.info(
            "[PROFILE] mesh-placed %d layers in %.2fs",
            len(m.layers), time.perf_counter() - t0,
        )

    # ---- sessions -----------------------------------------------------
    def new_session(
        self, nonce: str, seed: Optional[int] = None, kv=None, pos: int = 0
    ) -> Session:
        """kv/pos: seed from a prefix-cache snapshot (already mesh-sharded)
        instead of allocating + placing a zero cache it would drop."""
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        if kv is None:
            kv0 = self.model.init_kv(
                self._n_kv_layers, self.batch, self.max_seq, self.kv_dtype,
                quant_bits=self.kv_quant_bits, rotating=(self.sp == 1),
            )
            _, _, kv = place_ring_state({}, {}, kv0, self.mesh)
        sess = Session(
            nonce=nonce,
            kv=kv,
            pos=pos,
            key=jax.random.key(seed),
            counts=jnp.zeros((self.batch, self.config.vocab_size), dtype=jnp.int32),
            hist=(
                jnp.zeros((self.batch, self.max_seq), dtype=jnp.int32)
                if self.spec_lookahead > 0
                else None
            ),
        )
        self.sessions[nonce] = sess
        return sess

    def close(self) -> None:
        self.sessions.clear()

    # ---- inference ----------------------------------------------------
    def _forward_ring(self, sess: Session, tokens_np: np.ndarray, last_idx: int):
        logits, sess.kv = self._step(
            self.window_params, self.edge_params, jnp.asarray(tokens_np),
            sess.kv, jnp.int32(sess.pos), jnp.int32(last_idx),
        )
        return logits

    def prefill(self, nonce: str, prompt_ids: Sequence[int], seed: Optional[int] = None):
        full_ids = list(prompt_ids)
        if not full_ids:
            raise ValueError("empty prompt")
        sess = self.sessions.get(nonce)
        fresh = sess is None
        # validate against the FULL prompt BEFORE any session mutation: a
        # too-long prompt must not leave a half-restored session behind
        start = 0 if sess is None else sess.pos
        if start + len(full_ids) > self.max_seq:
            raise ValueError(
                f"prompt length {start + len(full_ids)} exceeds max_seq "
                f"{self.max_seq}"
            )
        if sess is None:
            hit = (
                self.prefix_cache.lookup(full_ids)
                if self.prefix_cache is not None
                else None
            )
            if hit is not None:
                n, kv_copy = hit  # snapshot keeps the template's sharding
                sess = self.new_session(nonce, seed, kv=kv_copy, pos=n)
                prompt_ids = full_ids[n:]  # >= 1 token left by construction
            else:
                sess = self.new_session(nonce, seed)
        self._commit_prompt_hist(sess, full_ids, prompt_ids)
        T = len(prompt_ids)
        Tpad = min(bucket_length(T), self.max_seq - sess.pos)
        tokens = np.zeros((self.batch, Tpad), dtype=np.int32)
        tokens[:, :T] = np.asarray(prompt_ids, dtype=np.int32)
        logits = self._forward_ring(sess, tokens, T - 1)
        sess.pos += T
        sess.last_used = time.time()
        if self.prefix_cache is not None and fresh and sess.pos == len(full_ids):
            self.prefix_cache.store(full_ids, sess.kv)
        return logits

    def decode_step(self, nonce: str, token_id: int, decoding: DecodingParams) -> SampleResult:
        sess = self.sessions[nonce]
        if sess.pos >= self.max_seq:
            raise ValueError(f"sequence length {sess.pos} reached max_seq {self.max_seq}")
        tokens = np.full((self.batch, 1), token_id, dtype=np.int32)
        logits = self._forward_ring(sess, tokens, 0)
        res = self._sample_with_counts(sess, logits, decoding)
        sess.pos += 1
        sess.last_used = time.time()
        return res

    def generate(self, prompt_ids, decoding=None, max_tokens=256, eos_token_ids=None, nonce="mesh"):
        """Same loop as LocalEngine.generate (shared via duck-typed surface)."""
        return LocalEngine.generate(
            self, prompt_ids, decoding, max_tokens, eos_token_ids, nonce
        )

    def hidden_states(self, prompt_ids: Sequence[int]) -> np.ndarray:
        """Embeddings primitive through the mesh ring (LocalEngine's
        contract: float32 [T, D] of final-norm'd hidden states).  The ring
        pass runs over a throwaway KV; the program compiles lazily on the
        first embeddings request."""
        ids = list(prompt_ids)
        if not ids:
            raise ValueError("empty embeddings input")
        if len(ids) > self.max_seq:
            raise ValueError(
                f"input length {len(ids)} exceeds max_seq {self.max_seq}"
            )
        if not hasattr(self, "_hidden_fn"):
            from dnet_tpu.parallel.ring import make_ring_hidden_fn

            self._hidden_fn = make_ring_hidden_fn(
                self.model, self.mesh, self._host_window
            )
            # throwaway KV operand, built ONCE: the hidden fn never donates
            # it and t_real masks its (stale) contents, so every embeddings
            # request reuses the same placed buffers
            kv0 = self.model.init_kv(
                self._n_kv_layers, self.batch, self.max_seq, self.kv_dtype,
                quant_bits=self.kv_quant_bits, rotating=(self.sp == 1),
            )
            _, _, self._hidden_kv = place_ring_state({}, {}, kv0, self.mesh)
        T = len(ids)
        Tpad = min(bucket_length(T), self.max_seq)
        tokens = np.zeros((self.batch, Tpad), dtype=np.int32)
        tokens[:, :T] = np.asarray(ids, dtype=np.int32)
        h, _ = self._hidden_fn(
            self.window_params, self.edge_params, jnp.asarray(tokens),
            self._hidden_kv, jnp.int32(0), jnp.int32(T - 1),
        )
        return np.asarray(h[0, :T], dtype=np.float32)
