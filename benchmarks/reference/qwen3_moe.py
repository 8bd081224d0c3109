"""Qwen3-MoE (Qwen3MoeForCausalLM) in plain float32 jax.numpy.

Follows transformers' modeling_qwen3_moe: pre-norm decoder; attention with
grouped KV heads, an RMS norm over each head's q and k BEFORE rotary
embedding (rotate-half convention, whole head), causal softmax; a sparse
MoE block: router softmax over ALL experts in float32, top-k, renormalised
when `norm_topk_prob`, each expert a SwiGLU.  Departures: none known.
Only all-MoE layouts (`decoder_sparse_step` 1, no `mlp_only_layers`).
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import (
    Tensors, causal_attention, f32, rms_norm, rotate_half, routed_experts,
)


def tensor_table(cfg: dict):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim") or D // H
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    assert cfg.get("decoder_sparse_step", 1) == 1 and not cfg.get("mlp_only_layers")
    edge = {
        "model.embed_tokens.weight": ((V, D), "w"),
        "model.norm.weight": ((D,), "norm"),
        "lm_head.weight": ((V, D), "w"),
    }

    def layer(i: int):
        return {
            "input_layernorm.weight": ((D,), "norm"),
            "post_attention_layernorm.weight": ((D,), "norm"),
            "self_attn.q_proj.weight": ((H * Hd, D), "w"),
            "self_attn.k_proj.weight": ((KVH * Hd, D), "w"),
            "self_attn.v_proj.weight": ((KVH * Hd, D), "w"),
            "self_attn.o_proj.weight": ((D, H * Hd), "w"),
            "self_attn.q_norm.weight": ((Hd,), "norm"),
            "self_attn.k_norm.weight": ((Hd,), "norm"),
            "mlp.gate.weight": ((E, D), "router"),
            "mlp.experts.*.gate_proj.weight": ((E, F, D), "w"),
            "mlp.experts.*.up_proj.weight": ((E, F, D), "w"),
            "mlp.experts.*.down_proj.weight": ((E, D, F), "w"),
        }

    return edge, layer


def _layer(cfg: dict):
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    k_top, renorm = cfg["num_experts_per_tok"], cfg.get("norm_topk_prob", False)

    def layer(x, p):
        T = x.shape[0]
        h = rms_norm(x, p["input_layernorm.weight"], eps)
        q = (h @ f32(p["self_attn.q_proj.weight"]).T).reshape(T, H, Hd)
        k = (h @ f32(p["self_attn.k_proj.weight"]).T).reshape(T, KVH, Hd)
        v = (h @ f32(p["self_attn.v_proj.weight"]).T).reshape(T, KVH, Hd)
        q = rms_norm(q, p["self_attn.q_norm.weight"], eps)
        k = rms_norm(k, p["self_attn.k_norm.weight"], eps)
        inv = 1.0 / theta ** (jnp.arange(0, Hd, 2, dtype=jnp.float32) / Hd)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        q = q * jnp.cos(ang) + rotate_half(q) * jnp.sin(ang)
        k = k * jnp.cos(ang) + rotate_half(k) * jnp.sin(ang)
        k = jnp.repeat(k, H // KVH, axis=1)
        v = jnp.repeat(v, H // KVH, axis=1)
        a = causal_attention(q, k, v, Hd**-0.5).reshape(T, H * Hd)
        x = x + a @ f32(p["self_attn.o_proj.weight"]).T

        h = rms_norm(x, p["post_attention_layernorm.weight"], eps)
        probs = jax.nn.softmax(h @ f32(p["mlp.gate.weight"]).T, axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, k_top)
        if renorm:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        return x + routed_experts(
            h, top_idx, top_w,
            p["mlp.experts.*.gate_proj.weight"],
            p["mlp.experts.*.up_proj.weight"],
            p["mlp.experts.*.down_proj.weight"],
        )

    return jax.jit(layer)


def logits(model_dir: Path, cfg: dict, ids, last: int) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions, weights
    upcast one layer at a time (the served bf16 copy stays on the device)."""
    tensors = Tensors(model_dir)
    layer = _layer(cfg)
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(tensors.get("model.embed_tokens.weight"))[np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, {k: jnp.asarray(v) for k, v in tensors.layer(i).items()})
        x = rms_norm(x[-last:], tensors.get("model.norm.weight"), cfg["rms_norm_eps"])
        return x @ f32(tensors.get("lm_head.weight")).T
