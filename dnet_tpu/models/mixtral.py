"""Mixtral-family ring model: Llama attention + top-k sparse MoE FFN.

BASELINE config 4 names Mixtral-8x7B; the reference's model envelope covers
the same class of dense-attention MoE decoders through its catalog
(src/dnet/api/catalog.py).  Architecture (matching transformers'
MixtralForCausalLM):

- Attention is exactly Llama's (GQA + RoPE + rms norms), so the whole
  attention half — including TP head sharding, KV quant, SWA-free caches,
  sp flash-decoding, and the spec-decode rewind invariant — is inherited
  from LlamaRingModel unchanged.
- Every layer's FFN is a sparse MoE: a router linear scores E experts,
  routing weights are softmax-over-ALL-logits then top-k then renormalized
  (transformers MixtralSparseMoeBlock), and each expert is a swiglu
  (w1=gate, w3=up, w2=down).  No shared experts.
- Expert compute routes through ops/moe.moe_apply like gpt_oss/deepseek:
  dense-weighted einsum by default (exact numerics), capacity dispatch or
  all_to_all expert parallelism over the tp axis when configured.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.parallel.tp_collectives import tp_all_reduce
from dnet_tpu.models.llama import LlamaRingModel
from dnet_tpu.obs.phases import SCOPE_MOE
from dnet_tpu.ops.norms import rms_norm


class MixtralRingModel(LlamaRingModel):
    model_type = "mixtral"
    quant_keys = frozenset(
        {"wq", "wk", "wv", "wo", "e_gate", "e_up", "e_down"}
    )  # router gate_w stays f32 (routing decisions are precision-sensitive)
    # renormalize the kept top-k weights; always on for mixtral, config-read
    # for qwen3_moe ("only diff with mixtral sparse moe block" per HF)
    norm_topk_prob = True
    moe_grouped = True

    @jax.named_scope(SCOPE_MOE)
    def _mlp_block(self, p: dict, x: jnp.ndarray, tp_axis=None) -> jnp.ndarray:
        B, T, D = x.shape
        h = rms_norm(x, p["mlp_norm"], self.config.rms_norm_eps)
        flat = h.reshape(B * T, D)

        # transformers MixtralSparseMoeBlock: softmax over ALL logits first,
        # then top-k, then renormalize the kept weights
        logits = flat.astype(jnp.float32) @ p["gate_w"].astype(jnp.float32)
        scores = jax.nn.softmax(logits, axis=-1)  # [N, E] f32
        k = self.config.num_experts_per_tok
        top_w, top_idx = lax.top_k(scores, k)
        if self.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        top_idx = top_idx.astype(jnp.int32)

        from dnet_tpu.ops.moe import (
            moe_apply,
            swiglu_expert_closures,
            swiglu_grouped_closure,
        )

        effn, dense, E_local = swiglu_expert_closures(
            p, flat, scores, top_idx, top_w, tp_axis
        )
        routed, routed_partial = moe_apply(
            self.moe_impl, flat, top_idx, top_w, effn, E_local,
            self.moe_capacity_factor, k, tp_axis, dense,
            grouped_fn=swiglu_grouped_closure(p, flat, top_idx, top_w),
            quantized=self.experts_quantized,
        )
        out = routed.astype(flat.dtype)
        if tp_axis is not None and routed_partial:
            # expert-combine all-reduce: the MoE twin of the dense
            # down-proj collective, routed through the quantizable seam
            out = tp_all_reduce(out, tp_axis)
        return x + out.reshape(B, T, D)

    # ---- weight mapping ----------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        def t(name: str) -> np.ndarray:
            return np.ascontiguousarray(raw[name].T)  # HF [out,in] -> (in,out)

        E = self.config.num_local_experts
        return {
            "attn_norm": raw["input_layernorm.weight"],
            "wq": t("self_attn.q_proj.weight"),
            "wk": t("self_attn.k_proj.weight"),
            "wv": t("self_attn.v_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "gate_w": t("block_sparse_moe.gate.weight"),  # [D, E] router
            # experts stacked on a leading E axis, (in, out)-oriented
            "e_gate": np.stack(
                [t(f"block_sparse_moe.experts.{e}.w1.weight") for e in range(E)]
            ),
            "e_up": np.stack(
                [t(f"block_sparse_moe.experts.{e}.w3.weight") for e in range(E)]
            ),
            "e_down": np.stack(
                [t(f"block_sparse_moe.experts.{e}.w2.weight") for e in range(E)]
            ),
        }
