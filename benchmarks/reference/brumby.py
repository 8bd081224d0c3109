"""Brumby (Qwen3's decoder with gated power retention of degree 2 in place
of softmax attention) in plain float32 jax.numpy: the QUADRATIC form.

    u = RMSNorm(x)
    q = RoPE(RMSNorm_head(W_q u))     k = RoPE(RMSNorm_head(W_k u))     v = W_v u
    log g_t = logsigmoid(W_g u_t)                      one gate a KV head
    a[t,i] = (q_t . k_i)^2 / head_dim * exp(sum_{j=i+1..t} log g_j)      i <= t
    o_t    = sum_i a[t,i] v_i / (sum_i a[t,i] + 1e-6)
    h = x + W_o concat(o);   y = h + W_down(silu(W_gate RMSNorm(h)) * W_up RMSNorm(h))

Every pair of positions is formed: no state, no chunks, no kernel, nothing
of `dnet_tpu`.  The served path computes the same function as a recurrence
over a state (dnet_tpu/ops/retention.py); that the two agree is what the
check decides.  Source: Manifest AI, "Scaling Context Requires Rethinking
Attention" (arXiv:2507.04239) and "Symmetric Power Transformers" (2024);
what the catalog row does not give (degree, gate, eps, tensor names) is in
the configuration file's `assumed`.

It runs beside the server's 12.8 GB, so nothing large is whole at once:
attention goes by KV head and by blocks of query rows, the head by blocks
of the vocabulary, and weights are upcast where they are used.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import Tensors, f32, rms_norm, rotate_half

EPS = 1e-6
QUERY_ROWS = 1024  # rows of one block of the [rows, T] pair matrix
VOCAB_ROWS = 16384  # rows of the head upcast at once


def tensor_table(cfg: dict):
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim") or D // H
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
            "self_attn.g_proj.weight": ((KVH, D), "w"),
            "self_attn.o_proj.weight": ((D, H * Hd), "w"),
            "self_attn.q_norm.weight": ((Hd,), "norm"),
            "self_attn.k_norm.weight": ((Hd,), "norm"),
            "mlp.gate_proj.weight": ((F, D), "w"),
            "mlp.up_proj.weight": ((F, D), "w"),
            "mlp.down_proj.weight": ((D, F), "w"),
        }

    return edge, layer


def power_retention(q, k, v, log_g):
    """q [T, G, Hd] (the query heads of ONE KV head), k/v [T, Hd], log_g [T]
    -> [T, G, Hd]: the quadratic form, a block of query rows at a time."""
    T, G, Hd = q.shape
    cum = jnp.cumsum(log_g)
    out = []
    for r0 in range(0, T, QUERY_ROWS):
        rows = slice(r0, min(r0 + QUERY_ROWS, T))
        t = jnp.arange(T)[rows][:, None]
        i = jnp.arange(T)[None, :]
        causal = i <= t
        s = jnp.einsum("tgd,id->gti", q[rows], k)
        decay = jnp.exp(jnp.where(causal, cum[rows][:, None] - cum[None, :], 0.0))
        a = jnp.where(causal, s * s / Hd * decay, 0.0)
        num = jnp.einsum("gti,id->tgd", a, v)
        den = jnp.sum(a, axis=-1).T[..., None]
        out.append(num / (den + EPS))
    return jnp.concatenate(out, axis=0)


def _layer(cfg: dict):
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    G = H // KVH
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def attention(x, p):
        T = x.shape[0]
        u = rms_norm(x, p["input_layernorm.weight"], eps)
        q = (u @ f32(p["self_attn.q_proj.weight"]).T).reshape(T, H, Hd)
        k = (u @ f32(p["self_attn.k_proj.weight"]).T).reshape(T, KVH, Hd)
        v = (u @ f32(p["self_attn.v_proj.weight"]).T).reshape(T, KVH, Hd)
        log_g = jax.nn.log_sigmoid(u @ f32(p["self_attn.g_proj.weight"]).T)  # [T, KVH]
        q = rms_norm(q, p["self_attn.q_norm.weight"], eps)
        k = rms_norm(k, p["self_attn.k_norm.weight"], eps)
        inv = 1.0 / theta ** (jnp.arange(0, Hd, 2, dtype=jnp.float32) / Hd)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        q = q * jnp.cos(ang) + rotate_half(q) * jnp.sin(ang)
        k = k * jnp.cos(ang) + rotate_half(k) * jnp.sin(ang)
        q = q.reshape(T, KVH, G, Hd)
        heads = [
            power_retention(q[:, h], k[:, h], v[:, h], log_g[:, h])
            for h in range(KVH)
        ]
        o = jnp.stack(heads, axis=1).reshape(T, H * Hd)
        return x + o @ f32(p["self_attn.o_proj.weight"]).T

    # the MLP in two steps, so that its three matrices (1.07 GB in float32)
    # are never on the device together beside the server
    def mlp_up(x, p):
        h = rms_norm(x, p["post_attention_layernorm.weight"], eps)
        return jax.nn.silu(h @ f32(p["mlp.gate_proj.weight"]).T) * (
            h @ f32(p["mlp.up_proj.weight"]).T
        )

    def mlp_down(x, h, p):
        return x + h @ f32(p["mlp.down_proj.weight"]).T

    return jax.jit(attention), jax.jit(mlp_up), jax.jit(mlp_down)


def logits(model_dir: Path, cfg: dict, ids, last: int) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions."""
    tensors = Tensors(model_dir)
    attention, mlp_up, mlp_down = _layer(cfg)

    def only(p, *prefixes):
        return {k: jnp.asarray(v) for k, v in p.items() if k.startswith(prefixes)}

    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(tensors.get("model.embed_tokens.weight"))[np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            p = tensors.layer(i)
            x = attention(x, only(p, "input_layernorm", "self_attn"))
            h = mlp_up(x, only(p, "post_attention", "mlp.gate_proj", "mlp.up_proj"))
            x = mlp_down(x, h, only(p, "mlp.down_proj"))
        x = rms_norm(x[-last:], tensors.get("model.norm.weight"), cfg["rms_norm_eps"])
        head = tensors.get("lm_head.weight")
        return jnp.concatenate(
            [
                x @ f32(head[r0 : r0 + VOCAB_ROWS]).T
                for r0 in range(0, head.shape[0], VOCAB_ROWS)
            ],
            axis=-1,
        )
