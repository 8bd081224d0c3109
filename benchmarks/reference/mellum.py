"""Mellum (Mellum2-12B-A2.5B class, `model_type` mellum) in plain float32
jax.numpy.

The layer, T tokens, x [T, D], `kind(l)` from `layer_types` (ISSUE 54 and
the catalog row's `config`; there is no network here, so no modeling file
was read):

    h  = RMSNorm(x; w_in, eps)
    q, k, v = h Wq, h Wk, h Wv              H / KVH / KVH heads of head_dim, no bias
    q, k = RMSNorm_head(q; qn), RMSNorm_head(k; kn)      per head (`qk_norm`, ASSUMED true)
    q, k = rope_kind(q), rope_kind(k)       half-split pairs, the whole head; cos and sin of
                                            pos * inv_freq_kind, times attention_factor_kind
    window layer: keys j with i - W < j <= i (the query's own position counts)
    full layer:   keys j <= i
    x  = x + softmax(q k^T / sqrt(head_dim)) v Wo
    h2 = RMSNorm(x; w_post, eps)
    p  = softmax(h2 Wr) over all E;  top-k;  w = p_chosen / sum(p_chosen)   (`norm_topk_prob`)
    x  = x + sum_e w_e SwiGLU_e(h2)
    logits = RMSNorm(x_L; w_f) W_head                                       untied head

Both tables are written out here from `rope_parameters`' numbers, which are
NESTED BY LAYER TYPE: `sliding_attention` {default, theta}: inv_freq_i =
theta^(-2i/head_dim), factor 1; `full_attention` {yarn, theta, factor,
original_max_position_embeddings, beta_fast, beta_slow, attention_factor}:
YaRN's static blend as transformers.modeling_rope_utils computes it (below),
cos and sin times `attention_factor`.

Departures from the published model: NO multi-token-prediction head (the
config has no key for it, so its shape cannot be written down).  Departures
from a one-line-per-equation reading, none of which changes a value beyond
float32 rounding: attention one KV head's group of query heads and one block
of queries at a time; the experts a dense loop over all E, one at a time,
each weighted by its routing weight (zero where it was not chosen); the
head a block of vocabulary rows at a time.

`logits(..., **control)` takes the lower readings of
benchmarks/precision_control_mellum.py and tests/test_mellum_parity.py:
`full_table="sliding"` (the full layers rotated by the window kind's table),
`attention_factor=1.0`, `window=<W'>` (0: the window layers attend
everything), `norm_topk_prob=False`, `qk_norm=False`.
"""

from __future__ import annotations

import functools
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import Tensors, f32, rms_norm, rotate_half, swiglu

QUERY_BLOCK = 512
VOCAB_BLOCK = 32768
SLIDING, FULL = "sliding_attention", "full_attention"


def tensor_table(cfg: dict):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    assert all(t == "sparse" for t in cfg.get("mlp_layer_types") or ())
    assert not cfg.get("tie_word_embeddings")
    # a program that cannot serve this model_type fails HERE, in seconds, and
    # not after 7.6 GB of weights were made and written for it
    from dnet_tpu.models import get_ring_model_cls

    get_ring_model_cls(cfg["model_type"])
    edge = {
        "model.embed_tokens.weight": ((V, D), "w"),
        "model.norm.weight": ((D,), "norm"),
        "lm_head.weight": ((V, D), "w"),
    }

    def layer(i: int):
        t = {
            "input_layernorm.weight": ((D,), "norm"),
            "post_attention_layernorm.weight": ((D,), "norm"),
            "self_attn.q_proj.weight": ((H * Hd, D), "w"),
            "self_attn.k_proj.weight": ((KVH * Hd, D), "w"),
            "self_attn.v_proj.weight": ((KVH * Hd, D), "w"),
            "self_attn.o_proj.weight": ((D, H * Hd), "w"),
            "mlp.gate.weight": ((E, D), "router"),
            "mlp.experts.*.gate_proj.weight": ((E, F, D), "w"),
            "mlp.experts.*.up_proj.weight": ((E, F, D), "w"),
            "mlp.experts.*.down_proj.weight": ((E, D, F), "w"),
        }
        if cfg.get("qk_norm", True):
            t["self_attn.q_norm.weight"] = ((Hd,), "norm")
            t["self_attn.k_norm.weight"] = ((Hd,), "norm")
        return t

    return edge, layer


def rope_table(group: dict, head_dim: int, max_positions: int):
    """One layer type's (inv_freq [head_dim / 2] float32, the factor on cos
    and sin), from its group of `rope_parameters`."""
    theta = float(group["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    kind = group.get("rope_type", "default")
    if kind == "default":
        return inv.astype(np.float32), 1.0
    assert kind == "yarn", kind
    factor = float(group["factor"])
    original = group.get("original_max_position_embeddings") or max_positions
    attention_factor = group.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0

    def correction_dim(rotations):  # the dimension that turns `rotations` times in `original`
        return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = correction_dim(group.get("beta_fast") or 32), correction_dim(group.get("beta_slow") or 1)
    if group.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    # dimensions under `low` keep their frequency (extrapolation), those over
    # `high` are slowed by `factor` (interpolation), a linear blend between
    blended = inv / factor * ramp + inv * (1 - ramp)
    return blended.astype(np.float32), float(attention_factor)


def rope(x, inv_freq, scale):
    """x [T, N, Hd] at positions 0..T-1: half-split pairs over the whole head."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return x * (jnp.cos(ang) * scale) + rotate_half(x) * (jnp.sin(ang) * scale)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, q0, scale, *, window: int):
    """q [Tq, G, Hd] (queries at positions q0..), k, v [T, Hd] of ONE kv
    head -> [Tq, G, Hd]; the window as an explicit mask."""
    s = jnp.einsum("qgd,kd->gqk", q, k) * scale
    qi = q0 + jnp.arange(q.shape[0])[:, None]
    kj = jnp.arange(k.shape[0])[None, :]
    keep = kj <= qi
    if window:
        keep = keep & (qi - kj < window)
    s = jnp.where(keep[None], s, -jnp.inf)
    return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), v)


def attention(q, k, v, scale, window: int):
    """q [T, H, Hd], k, v [T, KVH, Hd] -> [T, H, Hd], by kv head and block
    of queries."""
    T, H, _ = q.shape
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


def experts(h, p, cfg: dict):
    """sum_e w_e SwiGLU_e(h), every expert on every token."""
    probs = jax.nn.softmax(_project(h, p["mlp.gate.weight"]), axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    gates = p["mlp.experts.*.gate_proj.weight"]
    for e in range(gates.shape[0]):
        w_e = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=-1)  # [T]
        out = out + w_e[:, None] * _swiglu(
            h, gates[e], p["mlp.experts.*.up_proj.weight"][e],
            p["mlp.experts.*.down_proj.weight"][e],
        )
    return out


def layer_forward(x, p, cfg: dict, table, window: int):
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    T, eps = x.shape[0], cfg["rms_norm_eps"]
    h = rms_norm(x, p["input_layernorm.weight"], eps)
    q = _project(h, p["self_attn.q_proj.weight"]).reshape(T, H, Hd)
    k = _project(h, p["self_attn.k_proj.weight"]).reshape(T, KVH, Hd)
    v = _project(h, p["self_attn.v_proj.weight"]).reshape(T, KVH, Hd)
    if cfg.get("qk_norm", True):
        q = rms_norm(q, p["self_attn.q_norm.weight"], eps)
        k = rms_norm(k, p["self_attn.k_norm.weight"], eps)
    q, k = rope(q, *table), rope(k, *table)
    a = attention(q, k, v, Hd**-0.5, window).reshape(T, H * Hd)
    x = x + _project(a, p["self_attn.o_proj.weight"])
    return x + experts(rms_norm(x, p["post_attention_layernorm.weight"], eps), p, cfg)


def logits(model_dir: Path, cfg: dict, ids, last: int, *, full_table=None,
           attention_factor=None, window=None, norm_topk_prob=None, qk_norm=None) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions; weights
    stay on the host in the checkpoint's type and go to the device, and to
    float32, a matrix at a time (the served copy fills most of the chip).
    The keyword arguments are the lower readings named at the top."""
    tensors = Tensors(model_dir)
    kinds = cfg.get("layer_types") or [FULL] * cfg["num_hidden_layers"]
    groups = dict(cfg["rope_parameters"])
    if full_table == "sliding":
        groups[FULL] = groups[SLIDING]
    if attention_factor is not None:
        groups[FULL] = dict(groups[FULL], attention_factor=attention_factor)
    tables = {
        t: rope_table(groups[t], cfg["head_dim"], cfg["max_position_embeddings"])
        for t in set(kinds)
    }
    W = int(cfg.get("sliding_window") or 0) if window is None else int(window)
    cfg = dict(cfg)
    if norm_topk_prob is not None:
        cfg["norm_topk_prob"] = norm_topk_prob
    if qk_norm is not None:
        cfg["qk_norm"] = qk_norm
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(tensors.get("model.embed_tokens.weight"))[np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            x = layer_forward(
                x, tensors.layer(i), cfg, tables[kinds[i]], W if kinds[i] == SLIDING else 0
            )
        x = rms_norm(x[-last:], tensors.get("model.norm.weight"), cfg["rms_norm_eps"])
        head = tensors.get("lm_head.weight")
        out = [_project(x, head[r:r + VOCAB_BLOCK]) for r in range(0, head.shape[0], VOCAB_BLOCK)]
        return jnp.concatenate(out, axis=-1)
