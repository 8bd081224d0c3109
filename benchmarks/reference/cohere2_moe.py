"""Cohere2-MoE (Command A+ class, `model_type` cohere2_moe), language model
only, in plain float32 jax.numpy.

The layer, T tokens, x [T, D] (ISSUE 28 and the catalog row's `config` /
`described_as`; there is no network here, so no modeling file was read):

    h  = LayerNorm(x) = (x - mean(x)) / sqrt(var(x) + eps) * g      weight only, no bias
    q, k, v = h Wq, h Wk, h Wv          H / KVH / KVH heads of head_dim, no bias, no q/k norm
    window layer: q, k <- RoPE(theta, interleaved pairs, whole head); keys j, i - W < j <= i
    full layer:   no position embedding; keys j <= i
    a  = softmax(q k^T / sqrt(head_dim)) v Wo
    s  = sigmoid(h Wr) [T, R];  top-k by s;  w_e = s_e / sum of the chosen s
    m  = sum_e w_e SwiGLU_e(h)  +  (1/S) sum_{j<S} SwiGLU_shared_j(h)
    x' = x + a + m                                                  parallel block
    logits = logit_scale * LayerNorm_f(x_L) E^T                     tied embedding

The expert SHARE (model-configs guide, section 4): the checkpoint holds
`num_experts` experts, the range from `expert_offset` of the
`num_experts_routed` the router scores; routing, top-k and normalisation run
over all of them, and the sum over e runs over the held experts alone.  What
the absent experts would have added is left out, here as in the program.

Departures from a one-line-per-equation reading, none of which changes a
value beyond float32 rounding:
- attention is computed one KV head's group of query heads and one block
  of queries at a time (`common.causal_attention` would materialise
  H x T x T floats: 9.9 GB at 128 heads and 4.4k tokens);
- the experts are applied one at a time, each weighted by its routing
  weight (zero where it was not chosen), so that one float32 expert lies
  beside the served weights and not a layer of them;
- the tied embedding is applied a block of vocabulary rows at a time;
- "average" is read as the mean of the shared experts' outputs (1/S); the
  other reading, a mean over shared AND routed terms, is named under
  `assumed` in the configuration file.
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import Tensors, f32, swiglu

QUERY_BLOCK = 1024
VOCAB_BLOCK = 32768


def tensor_table(cfg: dict):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    E, F = cfg["num_experts"], cfg["intermediate_size"]
    R = cfg.get("num_experts_routed") or E
    assert cfg.get("expert_offset", 0) == 0, "a seeded checkpoint holds experts 0.."
    # a program that cannot serve this model_type fails HERE, in seconds, and
    # not after 9.5 GB of weights were made and written for it
    from dnet_tpu.models import get_ring_model_cls

    get_ring_model_cls(cfg["model_type"])
    assert not cfg.get("first_k_dense_replace") and cfg.get("tie_word_embeddings")
    edge = {
        "model.embed_tokens.weight": ((V, D), "w"),
        "model.norm.weight": ((D,), "norm"),
    }

    def layer(i: int):
        t = {
            "input_layernorm.weight": ((D,), "norm"),
            "self_attn.q_proj.weight": ((H * Hd, D), "w"),
            "self_attn.k_proj.weight": ((KVH * Hd, D), "w"),
            "self_attn.v_proj.weight": ((KVH * Hd, D), "w"),
            "self_attn.o_proj.weight": ((D, H * Hd), "w"),
            "mlp.gate.weight": ((R, D), "router"),
            "mlp.experts.*.gate_proj.weight": ((E, F, D), "w"),
            "mlp.experts.*.up_proj.weight": ((E, F, D), "w"),
            "mlp.experts.*.down_proj.weight": ((E, D, F), "w"),
        }
        for j in range(cfg.get("num_shared_experts", 0)):
            t[f"mlp.shared_experts.{j}.gate_proj.weight"] = ((F, D), "w")
            t[f"mlp.shared_experts.{j}.up_proj.weight"] = ((F, D), "w")
            t[f"mlp.shared_experts.{j}.down_proj.weight"] = ((D, F), "w")
        return t

    return edge, layer


def layer_norm(x, w, eps):
    x = x.astype(jnp.float32)
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) * f32(w)


def rope_interleaved(x, theta):
    """x [T, N, Hd]: pairs (x[2i], x[2i+1]) rotated by position * theta^(-2i/Hd)."""
    T, _, Hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, Hd, 2, dtype=jnp.float32) / Hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]  # [T, Hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, q0, scale, *, window: int):
    """q [Tq, G, Hd] (queries at positions q0..), k, v [T, Hd] of ONE kv
    head -> [Tq, G, Hd]."""
    s = jnp.einsum("qgd,kd->gqk", q, k) * scale
    qi = q0 + jnp.arange(q.shape[0])[:, None]
    kj = jnp.arange(k.shape[0])[None, :]
    keep = kj <= qi
    if window:
        keep = keep & (kj > qi - window)
    s = jnp.where(keep[None], s, -jnp.inf)
    return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), v)


def attention(q, k, v, scale, window: int):
    """q [T, H, Hd], k, v [T, KVH, Hd] -> [T, H, Hd], by kv head and block
    of queries."""
    T, H, Hd = q.shape
    KVH = k.shape[1]
    G = H // KVH
    heads = []
    for kh in range(KVH):
        rows = [
            _attend_block(
                q[q0:q0 + QUERY_BLOCK, kh * G:(kh + 1) * G], k[:, kh], v[:, kh],
                q0, scale, window=window,
            )
            for q0 in range(0, T, QUERY_BLOCK)
        ]
        heads.append(jnp.concatenate(rows, axis=0))
    return jnp.concatenate(heads, axis=1)


_swiglu = jax.jit(swiglu)
_project = jax.jit(lambda x, w: x @ f32(w).T)


def moe(h, p, cfg: dict):
    """The held experts' part plus the shared experts' term, [T, D]."""
    k_top = cfg["num_experts_per_tok"]
    offset = cfg.get("expert_offset", 0)
    logits = _project(h, p["mlp.gate.weight"])
    fn = cfg.get("expert_selection_fn", "sigmoid")
    scores = jax.nn.sigmoid(logits) if fn == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(scores, k_top)
    if cfg.get("norm_topk_prob", True):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    gates = p["mlp.experts.*.gate_proj.weight"]
    for e in range(gates.shape[0]):
        w_e = jnp.sum(jnp.where(top_idx == offset + e, top_w, 0.0), axis=-1)  # [T]
        out = out + w_e[:, None] * _swiglu(
            h, gates[e], p["mlp.experts.*.up_proj.weight"][e],
            p["mlp.experts.*.down_proj.weight"][e],
        )
    S = cfg.get("num_shared_experts", 0)
    average = cfg.get("shared_expert_combination_strategy", "average") == "average"
    for j in range(S):
        pre = f"mlp.shared_experts.{j}."
        y = _swiglu(h, p[pre + "gate_proj.weight"], p[pre + "up_proj.weight"],
                    p[pre + "down_proj.weight"])
        out = out + (y / S if average else y)
    return out


def layer_forward(x, p, cfg: dict, kind: str):
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    T = x.shape[0]
    h = layer_norm(x, p["input_layernorm.weight"], cfg["layer_norm_eps"])
    q = _project(h, p["self_attn.q_proj.weight"]).reshape(T, H, Hd)
    k = _project(h, p["self_attn.k_proj.weight"]).reshape(T, KVH, Hd)
    v = _project(h, p["self_attn.v_proj.weight"]).reshape(T, KVH, Hd)
    window = 0
    if kind == "sliding_attention":
        q, k = rope_interleaved(q, cfg["rope_theta"]), rope_interleaved(k, cfg["rope_theta"])
        window = int(cfg.get("sliding_window") or 0)  # none given: every key before
    a = attention(q, k, v, Hd**-0.5, window).reshape(T, H * Hd)
    return x + _project(a, p["self_attn.o_proj.weight"]) + moe(h, p, cfg)


def logits(model_dir: Path, cfg: dict, ids, last: int) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions; weights
    stay on the host in the checkpoint's type and go to the device, and to
    float32, a matrix at a time (the served copy fills most of the chip)."""
    tensors = Tensors(model_dir)
    kinds = cfg.get("layer_types") or ["full_attention"] * cfg["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        embed = tensors.get("model.embed_tokens.weight")
        x = f32(np.asarray(embed)[np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            x = layer_forward(x, tensors.layer(i), cfg, kinds[i])
        x = layer_norm(x[-last:], tensors.get("model.norm.weight"), cfg["layer_norm_eps"])
        # the tied embedding a block of rows at a time: whole, in float32, it
        # would be 4.3 GB at 262144 x 4096
        out = [_project(x, embed[r:r + VOCAB_BLOCK]) for r in range(0, embed.shape[0], VOCAB_BLOCK)]
        return jnp.concatenate(out, axis=-1) * cfg.get("logit_scale", 1.0)
