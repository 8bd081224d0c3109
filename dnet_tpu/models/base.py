"""Layer-wise ring model abstraction.

The TPU analog of the reference's `BaseRingModel`
(src/dnet/core/models/base.py:19-109): a shard constructs a model over only
its *assigned* absolute layers and exposes edge ops (embed / normalize /
lm_project) plus windowed layer application.  Unlike the reference's
stateful mlx modules, everything here is functional: parameters are pytrees
of arrays, `apply_window` is a pure function scanned over layer-stacked
params, so it jits/shards/donates cleanly.

Parameter layout:
  params = {
    "embed":      {...}            # only on the shard holding layer 0
    "final_norm": {...}, "lm_head": {...}   # only on the last shard
    "windows":    {window_start: stacked-layer pytree}
  }
Stacked-layer pytrees have a leading layer axis so a window runs as one
`lax.scan` (MXU-friendly, one compiled program regardless of window size).
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.core.kvcache import KVConfig
from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_STATE, KV_KIND_WINDOW, SCOPE_LM_HEAD
from dnet_tpu.ops.quant import QUANTIZABLE


@dataclass
class ModelConfig:
    """Normalized HF config (config.json) subset shared across families."""

    model_type: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 8192
    attention_bias: bool = False
    mlp_bias: bool = False
    sliding_window: int = 0
    layer_types: Optional[List[str]] = None  # e.g. ["sliding_attention", "full_attention", ...]
    # MoE (gpt-oss / mixtral style)
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    # {layer type: (rope_theta, rope_scaling)} where `rope_parameters` is
    # nested by layer type (mellum); None for the one flat group
    rope_by_type: Optional[Dict[str, Tuple[float, Optional[dict]]]] = None
    # MLA (deepseek style) and other family-specific extras
    extra: dict = field(default_factory=dict)

    @classmethod
    def from_hf(cls, d: Dict[str, Any]) -> "ModelConfig":
        heads = d["num_attention_heads"]
        head_dim = d.get("head_dim") or d["hidden_size"] // heads
        # newer configs (mistral4) keep theta and the scaling in ONE group,
        # `rope_parameters`; it is read where a config has no `rope_scaling`
        rope = d.get("rope_parameters") or {}
        # or one group a LAYER TYPE (mellum): each type in `layer_types`
        # then has a theta and a scaling of its own (`rope_by_type`), and
        # the flat fields hold the first type's
        by_type = rope_parameters_by_type(rope, d.get("layer_types"))
        if by_type:
            rope = next(iter(by_type.values()))
        scaling = d.get("rope_scaling")
        if scaling is None and rope.get("rope_type", rope.get("type", "default")) != "default":
            scaling = rope
        if any(t != "sparse" for t in d.get("mlp_layer_types") or ()):
            raise NotImplementedError(
                "mlp_layer_types with an entry other than 'sparse': "
                f"{sorted(set(d['mlp_layer_types']))} (no published config has "
                "a dense layer among them to hold one against)"
            )
        return cls(
            model_type=d["model_type"],
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("intermediate_size", 4 * d["hidden_size"]),
            num_hidden_layers=d["num_hidden_layers"],
            num_attention_heads=heads,
            num_key_value_heads=d.get("num_key_value_heads", heads),
            head_dim=head_dim,
            # cohere2 names it layer_norm_eps and ships rms_norm_eps: null
            rms_norm_eps=d.get("rms_norm_eps") or d.get("layer_norm_eps") or 1e-5,
            rope_theta=d.get("rope_theta") or rope.get("rope_theta", 10000.0),
            rope_scaling=scaling,
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            attention_bias=d.get("attention_bias", False),
            mlp_bias=d.get("mlp_bias", False),
            sliding_window=d.get("sliding_window") or 0,
            layer_types=d.get("layer_types"),
            # mixtral/gpt_oss say num_local_experts; qwen3_moe says num_experts
            num_local_experts=d.get("num_local_experts", d.get("num_experts", 0)),
            num_experts_per_tok=d.get("num_experts_per_tok", 0),
            rope_by_type={
                t: (float(g.get("rope_theta", 10000.0)), _scaling_of(g))
                for t, g in by_type.items()
            } or None,
            extra=d,
        )


def _scaling_of(group: dict) -> Optional[dict]:
    """A rope group's scaling: the group itself, None for the default type."""
    return None if group.get("rope_type", group.get("type", "default")) == "default" else group


def rope_parameters_by_type(rope: dict, layer_types) -> Dict[str, dict]:
    """`rope_parameters` NESTED BY LAYER TYPE -> {type: its group}, for the
    types `layer_types` uses, in their order of first use; {} for a flat
    group (or none).  A type the layers use and the dict lacks is an error:
    read as a flat group it would lose theta and scaling without a word."""
    if not rope or not all(isinstance(v, dict) for v in rope.values()):
        return {}
    used = list(dict.fromkeys(layer_types or rope))
    missing = [t for t in used if t not in rope]
    if missing:
        raise ValueError(
            f"rope_parameters is keyed by layer type {sorted(rope)} and has "
            f"no entry for {missing}, which layer_types uses"
        )
    return {t: rope[t] for t in used}


class RingModel(abc.ABC):
    """A shard's view of a model: assigned layers + edge ops.

    Subclasses set `model_type` and implement the pure compute functions and
    the HF-name weight mapping.  Instances hold *no* parameters — params are
    passed to every call (functional style), so the weight-streaming policy
    owns residency.
    """

    model_type: str = ""
    # extension point: a future model whose matmuls can't route through
    # ops.quant.dq sets False and the engine fails fast.  Every current
    # family supports it.
    supports_weight_quant: bool = True
    # apply_window honors the kv_commit gate (required by the pipelined-ring
    # mesh program and continuous batching); deepseek_v2 doesn't yet
    supports_kv_commit: bool = True
    # apply_window accepts an `attend_fn` override replacing the cache
    # write + attention of every layer (ragged paged attention,
    # ops/paged_attention.py).  The caller's pool is never scanned over:
    # the llama-family stack threads attend_fn(q, k, v, None, layer=) with
    # the layer's index; a model whose layers are of two kinds
    # (cohere2_moe: window and full) sets `paged_kinds` and also passes
    # kind=, with layer= the index within the kind.  Models with bespoke
    # attention layouts (gpt_oss paired SWA rings) keep the dense-gather
    # decode path.
    supports_paged_attend: bool = False
    # > 0: a token's cache entry is ONE latent row shared by every head
    # (multi-head latent attention, models/deepseek_v2.py), whose first
    # `latent_rank` lanes are also the value: the pool attends it absorbed
    # (ops/paged_attention.py paged_attend_latent)
    latent_rank: int = 0
    # set by the engines that shard a model over a mesh (tp / sp / pp):
    # their caches shard by kv head (parallel/mesh.py kv_spec), which a
    # latent entry has none of
    on_mesh: bool = False
    # per local layer its kind (obs/phases.py KV_KINDS): `full` keeps
    # everything, a `window` layer's page table gives back the blocks
    # behind the window; None = all full
    paged_kinds: Optional[Tuple[str, ...]] = None
    # the op family of a model's `state` layers, which names the counters
    # their traffic is booked under (kv/store.py): "retention"
    # (dnet_retention_*) or "gdn" (dnet_gdn_*)
    state_family: str = "retention"
    # per-layer param names eligible for weight-only quantization (the big
    # matmuls; norms/biases/routers stay float).  Subclasses override.
    quant_keys: frozenset = frozenset(QUANTIZABLE)
    # the model rotates each layer type by its own table
    # (`ModelConfig.rope_by_type`); one that does not is refused a config
    # whose types differ, where it would rotate them all by the first
    rope_by_layer_type: bool = False
    # routed experts: None = the model has none; True = its experts have
    # the exact grouped closure beside the dense one (ops/moe.py:
    # swiglu_grouped_closure), False = dense closures only
    moe_grouped: Optional[bool] = None
    # the held experts are kept quantized: set at load by the engine whose
    # programs may go grouped under the ridge (LocalEngine._load_params),
    # read by the host (`moe_path`) and handed to the trace (`moe_apply`)
    experts_quantized: bool = False

    def __init__(self, config: ModelConfig, layers: Sequence[int]):
        self.config = config
        tables = list((config.rope_by_type or {}).values())
        if not self.rope_by_layer_type and any(t != tables[0] for t in tables):
            raise NotImplementedError(
                f"{self.model_type}: rope_parameters differ by layer type "
                f"({sorted(config.rope_by_type)}) and this model rotates "
                "every layer by one table"
            )
        self.layers = sorted(set(int(x) for x in layers))
        self.abs_to_local = {a: i for i, a in enumerate(self.layers)}
        self.is_first = 0 in self.abs_to_local
        self.is_last = (config.num_hidden_layers - 1) in self.abs_to_local
        # per-assigned-layer attention-kind array (models with mixed layer
        # kinds, e.g. gpt_oss SWA/full, set this; None = homogeneous)
        self.layer_kinds = None
        # MoE compute path knobs (ops/moe.py); engines/tests may override
        # the instance attributes after construction
        from dnet_tpu.config import get_settings

        cs = get_settings().compute
        self.moe_impl = cs.moe_impl
        self.moe_capacity_factor = cs.moe_capacity_factor

    def moe_path(self, rows: int, whole: Optional[bool] = None) -> Optional[str]:
        """The compute path this model's routed experts take in a one-rank
        program of `rows` rows: the rule `moe_apply` follows at trace time,
        asked on the host (None: no routed experts).  `whole`: the rows are
        all the program carries, handed to the model in one trace (None: as
        the trace around this call declares; ops/moe.py: whole_batch)."""
        if self.moe_grouped is None:
            return None
        from dnet_tpu.ops.moe import resolve_moe_impl, sparse_share, whole_batch_declared

        if whole is None:
            whole = whole_batch_declared()
        # the router's width: a share's `n_routed`, else the experts held
        n_routed = getattr(self, "n_routed", 0) or self.config.num_local_experts
        share = sparse_share(
            rows, self.config.num_experts_per_tok, n_routed, whole, self.experts_quantized
        )
        return resolve_moe_impl(self.moe_impl, rows, 1, self.moe_grouped, share)

    def flash_layers(self) -> Tuple[Tuple[str, int], ...]:
        """(kind, window) of each local layer whose prefill chunk attends
        its staged row through ops/flash_attention.py flash_attend_causal,
        asked on the host (dnet_flash_tiles_total is booked from it): a
        `state` layer keeps no keys, a `window` layer bounds them below."""
        kinds = self.paged_kinds or (KV_KIND_FULL,) * len(self.layers)
        return tuple(
            (k, int(getattr(self, "window", 0)) if k == KV_KIND_WINDOW else 0)
            for k in kinds
            if k != KV_KIND_STATE
        )

    # ---- pure compute -------------------------------------------------
    def embed(self, edge_params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
        """tokens [B, T] -> hidden [B, T, D] (maybe-quantized table)."""
        from dnet_tpu.ops.quant import embed_lookup

        return embed_lookup(edge_params["embed"]["weight"], tokens)

    @abc.abstractmethod
    def apply_window(
        self,
        window_params: dict,
        x: jnp.ndarray,
        kv: dict,
        pos: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        layer_kinds: Optional[jnp.ndarray] = None,
        tp_axis: Optional[str] = None,
        kv_commit=None,
        sp_axis: Optional[str] = None,
        t_real=None,
    ) -> Tuple[jnp.ndarray, dict]:
        """Apply a stacked window of layers. kv holds this window's slices.

        tp_axis: mesh axis name when running tensor-parallel inside
        shard_map (params are per-device slices; reductions psum over it).
        kv_commit: optional traced bool gating cache writes (pipeline ranks
        processing a not-their-turn copy pass False).
        t_real: number of REAL (non-padding) tokens in this chunk (traced);
        models with rotating ring-buffer caches must exclude bucket padding
        from writes, because padded positions would wrap around and destroy
        live rows.  None means every token is real.
        """

    @abc.abstractmethod
    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        """Final norm before the LM head."""

    @jax.named_scope(SCOPE_LM_HEAD)
    def lm_project(
        self, edge_params: dict, x: jnp.ndarray, out_dtype=None
    ) -> jnp.ndarray:
        """hidden [B, T, D] -> logits [B, T, V], in x's type or `out_dtype`
        (the matmul accumulates in float32 either way).

        The projection matrix is the single largest per-step HBM read at
        decode (O(hidden x vocab) — ~0.5 GB bf16 for Llama-1B); quantized
        edges (see quantize_edge) store it in [hidden, vocab] orientation so
        `dq` fuses the dequant into this matmul."""
        from dnet_tpu.ops.quant import dq, is_quantized

        if self.config.tie_word_embeddings:
            w = edge_params["embed"]["weight"]
            w = dq(w) if is_quantized(w) else w.T
        else:
            w = dq(edge_params["lm_head"]["weight"])
        return jnp.matmul(x, w, preferred_element_type=out_dtype)

    # ---- weight mapping ----------------------------------------------
    @abc.abstractmethod
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """HF per-layer tensors (prefix `model.layers.{i}.` stripped) -> our
        per-layer param dict (unstacked)."""

    def map_edge(self, raw: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """HF non-layer tensors -> {"embed", "final_norm", "lm_head"}.

        Standard HF naming is shared by every supported family; override
        only for exotic edge layouts."""
        out: Dict[str, Any] = {}
        if "model.embed_tokens.weight" in raw:
            out["embed"] = {"weight": raw["model.embed_tokens.weight"]}
        if "model.norm.weight" in raw:
            out["final_norm"] = {"weight": raw["model.norm.weight"]}
        if "lm_head.weight" in raw:
            out["lm_head"] = {"weight": np.ascontiguousarray(raw["lm_head.weight"].T)}
        return out

    # ---- cache construction ------------------------------------------
    def pool_leaves(self) -> Dict[str, Tuple[int, int]]:
        """The leaves of a token's entry in the block pool (kv/store.py
        KindStore), each as (heads, dim): the pool keeps a leaf with
        `heads * dim` lanes, and a staged session row keeps it as
        [L, 1, S, heads, dim].  Default: keys and values of KVH x Hd."""
        c = self.config
        heads = (c.num_key_value_heads, c.head_dim)
        return {"k": heads, "v": heads}

    def kv_config(
        self,
        n_layers: int,
        batch: int,
        max_seq: int,
        dtype: str = "bfloat16",
        quant_bits: int = 0,
    ) -> KVConfig:
        return KVConfig(
            n_layers=n_layers,
            batch=batch,
            max_seq=max_seq,
            n_kv_heads=self.config.num_key_value_heads,
            head_dim=self.config.head_dim,
            dtype=dtype,
            quant_bits=quant_bits,
        )

    def init_kv(
        self,
        n_layers: int,
        batch: int,
        max_seq: int,
        dtype: str = "bfloat16",
        quant_bits: int = 0,
        rotating: bool = True,
    ) -> dict:
        """Allocate the stacked KV cache matching this model's window layout.

        Default: one flat [L, B, S, ...] cache.  Models with per-kind cache
        shapes (gpt_oss paired SWA ring buffers) override; `rotating=False`
        forces full-length caches (sequence-parallel serving shards the S
        axis and needs uniform length)."""
        from dnet_tpu.core.kvcache import init_cache

        return init_cache(
            self.kv_config(n_layers, batch, max_seq, dtype, quant_bits=quant_bits)
        )

    # ---- helpers ------------------------------------------------------
    @staticmethod
    def stack_layers(per_layer: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
        """Stack N per-layer param dicts along a new leading axis.

        Models with heterogeneous layer structures (deepseek dense-vs-MoE)
        override this (and wrap_offload_layer) with a list layout.
        """
        if not per_layer:
            return {}
        keys = per_layer[0].keys()
        return {k: np.stack([p[k] for p in per_layer], axis=0) for k in keys}

    def quantize_params(self, stacked, bits: int, scale_dtype=None, group_size: int = 0):
        """Weight-only quantize a stacked param pytree (engine fit path).
        Default covers the flat stacked-dict layout; list-layout models
        override.  group_size=0 uses the quantizer default; tensor-parallel
        serving passes a size that divides the per-rank contraction dim."""
        from dnet_tpu.ops.quant import quantize_tree

        return quantize_tree(
            stacked, self.quant_keys, bits=bits, scale_dtype=scale_dtype,
            group_size=group_size,
        )

    def quantize_layer(self, mapped, bits: int, scale_dtype=None, group_size: int = 0):
        """Weight-only quantize ONE layer as `map_layer` gives it (the
        streaming store's path, core/weights.py): a flat dict whatever the
        model's stacked layout."""
        return RingModel.quantize_params(self, mapped, bits, scale_dtype, group_size)

    def quantize_edge(self, edge: Dict[str, Any], bits: int, scale_dtype=None,
                      group_size: int = 0) -> Dict[str, Any]:
        """Quantize the LM projection among the edge params.

        Only the O(hidden x vocab) projection matrix is worth quantizing —
        it is read in full every decode step, while the embedding gather
        reads O(tokens x hidden) and the norms are vectors.  Tied embeddings
        are re-laid out to the projection orientation [hidden, vocab]
        (groups along hidden, the contraction dim); `embed_lookup` gathers
        logical table rows as physical columns from that layout, so one
        quantized array serves both ops and the bf16 table is not kept.
        """
        from dnet_tpu.ops.quant import (
            DEFAULT_GROUP,
            DEFAULT_GROUP_Q4,
            is_quantized,
            quantize_weight_q4,
            quantize_weight_q8,
        )

        if bits not in (4, 8):
            raise NotImplementedError(f"weight quantization bits={bits} (4 or 8)")
        quant = quantize_weight_q4 if bits == 4 else quantize_weight_q8
        group_size = group_size or (DEFAULT_GROUP_Q4 if bits == 4 else DEFAULT_GROUP)
        out = dict(edge)
        if self.config.tie_word_embeddings and "embed" in out:
            # tied: lm_project always reads "embed", so quantize THAT (some
            # tied checkpoints still serialize an lm_head — never read; drop)
            out.pop("lm_head", None)
            if not is_quantized(out["embed"]["weight"]):
                w = np.ascontiguousarray(np.asarray(out["embed"]["weight"]).T)
                out["embed"] = {"weight": quant(w, group_size, scale_dtype)}
        elif "lm_head" in out and not is_quantized(out["lm_head"]["weight"]):
            w = np.asarray(out["lm_head"]["weight"])  # already [hidden, vocab]
            out["lm_head"] = {"weight": quant(w, group_size, scale_dtype)}
        return out

    def wrap_offload_layer(self, mapped: Dict[str, np.ndarray]):
        """Shape ONE layer's mapped host params as a single-layer window (the
        weight-streaming unit).  Default: add the leading stack axis (tree-
        mapped so quantized {"q"/"q4","s"} leaf dicts wrap too)."""
        import jax

        return jax.tree.map(lambda v: v[None], mapped)

    def kv_rewindable(self, max_seq: int) -> bool:
        """Whether stale cache rows past a rewound `pos` are harmless.

        Slot-addressed max_seq caches qualify (stale rows are never attended
        and get overwritten); rotating ring-buffer SWA caches do not — a
        wrap-around write evicts live rows, so speculative decoding must
        refuse (see core/spec.py's KV-rewind invariant)."""
        return True

    def local_window(self, start_abs: int, size: int) -> List[int]:
        """The contiguous run of assigned layers beginning at start_abs."""
        out = []
        a = start_abs
        while a in self.abs_to_local and len(out) < size:
            out.append(a)
            a += 1
        return out
