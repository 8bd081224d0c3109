"""DeepSeek-V2-family ring model: MLA attention + shared/routed MoE.

Reference analog: src/dnet/core/models/deepseek_v2.py (MLA-style model,
asymmetric head dims).  Architecture (matching transformers' DeepseekV2*):

- MLA: queries via optional LoRA (q_a -> norm -> q_b), KV via a compressed
  latent (kv_a -> norm -> kv_b) plus a SHARED per-token rope key (MQA-style);
  rope uses the interleaved/complex-pair convention; K caches nope+rope
  (qk_head_dim) while V caches v_head_dim — the KV cache is asymmetric.
- Layers < first_k_dense_replace use a dense swiglu MLP; the rest use MoE:
  softmax-then-topk routing (greedy or group-limited), routed_scaling_factor,
  plus always-on shared experts.
- Dense vs MoE layers have different param structures, so the window is TWO
  stacked segments ({"dense": ..., "moe": ...}), each applied with one
  lax.scan — compile time is layer-count-independent (two programs), and a
  contiguous layer range is always a dense prefix + moe suffix.  MoE expert
  compute is dense-weighted (exact numerics); `tp_axis` shards attention
  heads and the EXPERT dim (expert-parallel ranks) with psum seams.
- For the mesh ring (pp sharding), segments are zero-padded to pp
  divisibility (zero o/down projections make a padded layer an exact
  residual no-op) and the ring runs TWO laps (`ring_phases = 2`): every
  rank applies its dense slice on lap 0 and its moe slice on lap 1, so the
  global execution order stays all-dense-then-all-moe.  The KV cache is laid
  out per-rank (dense rows then moe rows), which is exactly the local
  slicing apply_window already uses.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.core.kvcache import KVConfig
from dnet_tpu.models.base import ModelConfig, RingModel
from dnet_tpu.obs.phases import SCOPE_ATTN, SCOPE_MOE
from dnet_tpu.models.segments import TwoSegmentStackMixin
from dnet_tpu.parallel.tp_collectives import tp_all_reduce
from dnet_tpu.ops.attention import cached_attend
from dnet_tpu.ops.norms import rms_norm
from dnet_tpu.ops.quant import dq
from dnet_tpu.ops.rope import apply_rope_interleaved, rope_frequencies


class DeepseekV2RingModel(TwoSegmentStackMixin, RingModel):
    model_type = "deepseek_v2"
    supports_kv_commit = True
    ring_phases = 2  # mesh ring: lap 0 = dense slices, lap 1 = moe slices
    moe_grouped = True
    quant_keys = frozenset(
        {"wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo",  # MLA projections
         "w_gate", "w_up", "w_down",  # dense mlp
         "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down"}  # MoE
    )  # router gate_w stays f32 (routing decisions are precision-sensitive)

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        x = config.extra
        self.q_lora_rank = x.get("q_lora_rank")
        self.qk_nope_head_dim = x.get("qk_nope_head_dim", 128)
        self.qk_rope_head_dim = x.get("qk_rope_head_dim", 64)
        self.kv_lora_rank = x.get("kv_lora_rank", 512)
        self.v_head_dim = x.get("v_head_dim", 128)
        self.qk_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        self.n_routed_experts = x.get("n_routed_experts", 0)
        self.n_shared_experts = x.get("n_shared_experts", 0)
        self.moe_intermediate_size = x.get("moe_intermediate_size", 0)
        self.first_k_dense_replace = x.get("first_k_dense_replace", 0)
        self.routed_scaling_factor = x.get("routed_scaling_factor", 1.0)
        self.topk_method = x.get("topk_method", "greedy")
        self.n_group = x.get("n_group", 1)
        self.topk_group = x.get("topk_group", 1)
        self.norm_topk_prob = x.get("norm_topk_prob", False)
        self.num_experts_per_tok = x.get("num_experts_per_tok", 0)

        inv_freq, self.rope_scale = rope_frequencies(
            self.qk_rope_head_dim,
            config.rope_theta,
            config.rope_scaling,
            config.max_position_embeddings,
        )
        self.inv_freq = jnp.asarray(inv_freq)

        # Original DeepSeek-V2 YaRN: softmax scale is compensated by
        # mscale(factor, mscale_all_dim)^2 (the model was TRAINED with this;
        # the transformers port drops it when mscale == mscale_all_dim, which
        # shrinks logits ~1.6x on real checkpoints).
        self.softmax_scale = self.qk_head_dim**-0.5
        rs = config.rope_scaling or {}
        if rs.get("rope_type", rs.get("type")) == "yarn":
            factor = rs.get("factor", 1.0)
            msc_all = rs.get("mscale_all_dim", 0)
            if msc_all and factor > 1:
                import math

                mscale = 0.1 * msc_all * math.log(factor) + 1.0
                self.softmax_scale = self.softmax_scale * mscale * mscale

    def is_moe_layer(self, abs_layer: int) -> bool:
        return self.n_routed_experts > 0 and abs_layer >= self.first_k_dense_replace

    # ---- cache: asymmetric dims --------------------------------------
    def kv_config(self, n_layers, batch, max_seq, dtype="bfloat16", quant_bits=0) -> KVConfig:
        return KVConfig(
            n_layers=n_layers,
            batch=batch,
            max_seq=max_seq,
            n_kv_heads=self.config.num_attention_heads,
            head_dim=self.qk_head_dim,
            dtype=dtype,
            v_head_dim=self.v_head_dim,
            quant_bits=quant_bits,
        )

    # ---- pure compute -------------------------------------------------
    @jax.named_scope(SCOPE_ATTN)
    def _attention(
        self, p, x, kvs, pos, mask, tp_axis=None, kv_commit=None, sp_axis=None
    ):
        cfg = self.config
        B, T, D = x.shape
        nope, rope_d, vd = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim

        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        if self.q_lora_rank is None:
            q = h @ dq(p["wq"])
        else:
            qa = rms_norm(h @ dq(p["wq_a"]), p["q_a_norm"], 1e-6)
            q = qa @ dq(p["wq_b"])
        # local head count from the (possibly tp-sharded) projection shape
        H = q.shape[-1] // self.qk_head_dim
        q = q.reshape(B, T, H, self.qk_head_dim)
        q_nope, q_pe = q[..., :nope], q[..., nope:]

        ckv = h @ dq(p["wkv_a"])  # [B, T, kv_lora + rope_d] (replicated)
        k_latent, k_pe = ckv[..., : self.kv_lora_rank], ckv[..., self.kv_lora_rank:]
        k_latent = rms_norm(k_latent, p["kv_a_norm"], 1e-6)
        kv = (k_latent @ dq(p["wkv_b"])).reshape(B, T, H, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        positions = pos + jnp.arange(T)
        q_pe = apply_rope_interleaved(q_pe, positions, self.inv_freq, self.rope_scale)
        k_pe = apply_rope_interleaved(
            k_pe[:, :, None, :], positions, self.inv_freq, self.rope_scale
        )  # [B, T, 1, rope_d] — shared across heads (MQA-style)
        k_pe = jnp.broadcast_to(k_pe, (B, T, H, rope_d))

        q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
        k_full = jnp.concatenate([k_nope, k_pe], axis=-1)

        # shared body incl. the sp path: with sp_axis the cache holds this
        # rank's sequence shard and attention runs as distributed
        # flash-decoding with an LSE combine (ops/ring_attention.py) —
        # MLA's asymmetric K/V head dims flow through unchanged.  mask=None
        # non-sp declares the plain causal predicate: prefill takes the
        # Pallas flash kernel on TPU (ops/flash_attention.py)
        attn, kvs = cached_attend(
            q_full, k_full, v, kvs, pos, mask,
            kv_commit=kv_commit, sp_axis=sp_axis, scale=self.softmax_scale,
            causal=mask is None,
        )
        out = attn.reshape(B, T, H * vd) @ dq(p["wo"])
        if tp_axis is not None:
            # out-proj all-reduce through the quantizable TP seam
            out = tp_all_reduce(out, tp_axis)
        return x + out, kvs

    def _dense_mlp(self, p_prefix: dict, h: jnp.ndarray) -> jnp.ndarray:
        gate = h @ dq(p_prefix["w_gate"])
        up = h @ dq(p_prefix["w_up"])
        return (jax.nn.silu(gate) * up) @ dq(p_prefix["w_down"])

    @jax.named_scope(SCOPE_MOE)
    def _moe(self, p, x, tp_axis=None):
        B, T, D = x.shape
        h = rms_norm(x, p["mlp_norm"], self.config.rms_norm_eps)
        flat = h.reshape(B * T, D)

        logits = flat.astype(jnp.float32) @ p["gate_w"].astype(jnp.float32)
        scores = jax.nn.softmax(logits, axis=-1)  # [N, E] f32 softmax over ALL
        k = self.num_experts_per_tok
        if self.topk_method == "group_limited_greedy":
            N, E = scores.shape
            g = self.n_group
            group_scores = scores.reshape(N, g, E // g).max(axis=-1)
            _, group_idx = lax.top_k(group_scores, self.topk_group)
            group_mask = jnp.zeros_like(group_scores).at[
                jnp.arange(N)[:, None], group_idx
            ].set(1.0)
            score_mask = jnp.repeat(group_mask, E // g, axis=1)
            masked = jnp.where(score_mask > 0, scores, 0.0)
            topk_w, topk_idx = lax.top_k(masked, k)
        else:  # greedy (DeepSeek-V2-Lite)
            topk_w, topk_idx = lax.top_k(scores, k)
        if self.norm_topk_prob:
            topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
        topk_w = topk_w * self.routed_scaling_factor

        from dnet_tpu.ops.moe import (
            moe_apply,
            swiglu_expert_closures,
            swiglu_grouped_closure,
        )

        topk_idx = topk_idx.astype(jnp.int32)
        effn, dense, E_local = swiglu_expert_closures(
            p, flat, scores, topk_idx, topk_w, tp_axis
        )
        routed, routed_partial = moe_apply(
            self.moe_impl, flat, topk_idx, topk_w, effn, E_local,
            self.moe_capacity_factor, k, tp_axis, dense,
            grouped_fn=swiglu_grouped_closure(p, flat, topk_idx, topk_w),
        )

        # shared experts are Megatron-split over tp (col/row), so their
        # partial output always reduces over tp; the routed partial joins
        # that psum except on the a2a path, which returns a full output
        shared = self._dense_mlp(
            {"w_gate": p["s_gate"], "w_up": p["s_up"], "w_down": p["s_down"]}, flat
        )
        if tp_axis is not None:
            if routed_partial:
                out = tp_all_reduce(routed.astype(flat.dtype) + shared, tp_axis)
            else:
                out = routed.astype(flat.dtype) + tp_all_reduce(shared, tp_axis)
        else:
            out = routed.astype(flat.dtype) + shared
        return x + out.reshape(B, T, D)

    def _layer(
        self, p: dict, x, kvs, pos, mask, tp_axis=None, kv_commit=None,
        sp_axis=None,
    ):
        x, kvs = self._attention(p, x, kvs, pos, mask, tp_axis, kv_commit, sp_axis)
        if "e_gate" in p:
            x = self._moe(p, x, tp_axis)
        else:
            h = rms_norm(x, p["mlp_norm"], self.config.rms_norm_eps)
            out = self._dense_mlp(p, h)
            if tp_axis is not None:
                # down-proj all-reduce through the quantizable TP seam
                out = tp_all_reduce(out, tp_axis)
            x = x + out
        return x, kvs

    def apply_window(
        self,
        window_params,
        x: jnp.ndarray,
        kv: dict,
        pos: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        layer_kinds: Optional[jnp.ndarray] = None,
        tp_axis: Optional[str] = None,
        kv_commit=None,
        sp_axis: Optional[str] = None,
        phase=None,
        t_real=None,  # full-length caches overwrite padding before reading
    ) -> Tuple[jnp.ndarray, dict]:
        """Two-segment scan: the window's dense prefix, then its moe suffix.

        `phase` (traced int, mesh ring only) selects ONE segment per call:
        the ring runs `ring_phases` laps so the global layer order stays
        all-dense-then-all-moe even though each pp rank holds a slice of
        both segments.  The segment machinery itself is shared with mixed
        qwen3_moe (models/segments.py).
        """
        # the causal predicate stays implicit (mask=None) under sp too:
        # cached_attend owns the rank-local sp mask (or the TPU split-K
        # flash-decode partials, which honor self.softmax_scale)
        return self._apply_segments(
            window_params, x, kv, pos, mask, tp_axis, kv_commit, sp_axis, phase
        )

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return rms_norm(x, edge_params["final_norm"]["weight"], self.config.rms_norm_eps)

    # ---- weight mapping ----------------------------------------------
    def stack_layers(self, per_layer: List[Dict[str, np.ndarray]]):
        """Two homogeneous stacked segments: the window's dense prefix and
        its moe suffix (a contiguous layer range is always dense-then-moe
        because dense layers come first globally)."""
        n_dense = sum(1 for a in self.layers if not self.is_moe_layer(a))
        out: Dict[str, Any] = {}
        if per_layer[:n_dense]:
            out["dense"] = RingModel.stack_layers(per_layer[:n_dense])
        if per_layer[n_dense:]:
            out["moe"] = RingModel.stack_layers(per_layer[n_dense:])
        return out

    # quantize_params / wrap_offload_layer / pad_mesh_segments come from
    # TwoSegmentStackMixin (shared with mixed qwen3_moe)

    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        def t(name):
            return np.ascontiguousarray(raw[name].T)

        p: Dict[str, np.ndarray] = {
            "attn_norm": raw["input_layernorm.weight"],
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "wkv_a": t("self_attn.kv_a_proj_with_mqa.weight"),
            "kv_a_norm": raw["self_attn.kv_a_layernorm.weight"],
            "wkv_b": t("self_attn.kv_b_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
        }
        if "self_attn.q_proj.weight" in raw:
            p["wq"] = t("self_attn.q_proj.weight")
        else:
            p["wq_a"] = t("self_attn.q_a_proj.weight")
            p["q_a_norm"] = raw["self_attn.q_a_layernorm.weight"]
            p["wq_b"] = t("self_attn.q_b_proj.weight")

        if "mlp.gate.weight" in raw:  # MoE layer
            p["gate_w"] = t("mlp.gate.weight")
            e_gate, e_up, e_down = [], [], []
            e = 0
            while f"mlp.experts.{e}.gate_proj.weight" in raw:
                e_gate.append(t(f"mlp.experts.{e}.gate_proj.weight"))
                e_up.append(t(f"mlp.experts.{e}.up_proj.weight"))
                e_down.append(t(f"mlp.experts.{e}.down_proj.weight"))
                e += 1
            p["e_gate"] = np.stack(e_gate)
            p["e_up"] = np.stack(e_up)
            p["e_down"] = np.stack(e_down)
            p["s_gate"] = t("mlp.shared_experts.gate_proj.weight")
            p["s_up"] = t("mlp.shared_experts.up_proj.weight")
            p["s_down"] = t("mlp.shared_experts.down_proj.weight")
        else:  # dense layer
            p["w_gate"] = t("mlp.gate_proj.weight")
            p["w_up"] = t("mlp.up_proj.weight")
            p["w_down"] = t("mlp.down_proj.weight")
        return p

