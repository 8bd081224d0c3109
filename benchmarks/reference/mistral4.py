"""Mistral-Small-4 (`model_type` mistral4: multi-head latent attention, an
expert layer after every one) in plain float32 jax.numpy, NOT absorbed:
each head's keys and values are made from the latent and attention forms
every pair of positions.

Pre-norm residual, RMSNorm eps `rms_norm_eps`, no biases; u = RMSNorm(x):

    c_q = RMSNorm(W_qa u)  [q_lora_rank];   [q_nope_h | q_pe_h] = W_qb,h c_q,  h = 1..H
    [c_kv | k_pe] = W_kva u;   c = RMSNorm(c_kv);   k_pe = RoPE_i(k_pe), ONE key for all heads
    [k_nope_h | v_h] = W_kvb,h c;   q_pe_h = RoPE_i(q_pe_h)
    s_h(t, j) = sigma a(t) (q_nope_h(t) . k_nope_h(j) + q_pe_h(t) . k_pe(j)),  j <= t
    sigma = qk_head_dim^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    a(t) = 1 + beta ln(1 + floor(t / original_max_position_embeddings))
    o_h(t) = sum_j softmax_j(s_h(t, .)) v_h(j);   h1 = x + W_o concat_h o_h
    u2 = RMSNorm(h1);  p = softmax(W_r u2);  top-k;  w_e = p_e / sum_chosen p  (x routed_scaling_factor)
    y = h1 + sum_{e chosen} w_e SwiGLU_e(u2) + SwiGLU_shared(u2)

RoPE_i rotates INTERLEAVED pairs (x[2i], x[2i+1]) by `t inv_freq_i`, with
YaRN's frequencies: dimension i of `qk_rope_head_dim / 2` is divided by
`factor` where its wavelength is past `original_max_position_embeddings /
beta_slow` rotations, kept where it is under `/ beta_fast`, and blended
linearly between (`rope_parameters`); cos and sin are multiplied by
`mscale(factor, mscale) / mscale(factor, mscale_all_dim)`, 1 here.

No cache, no absorbed form, no kernel, nothing of `dnet_tpu`.  The served
path keeps ONE latent entry a token, expands it for a prefill chunk and
attends it absorbed in a decode step (dnet_tpu/models/deepseek_v2.py,
dnet_tpu/ops/paged_attention.py); that the two agree is what the check
decides.

The expert SHARE is the configuration's: `n_routed_experts` experts are
held, the range from `expert_offset` of the `num_experts_routed` the
router scores; routing, top-k and the normalisation run over all of them,
and what the absent experts would have added is left out, here as in the
program.  The shared expert is whole.  The vision tower of the published
checkpoint is not part of the language model's forward pass and is not here.

It runs beside the server's 13 GB, so nothing large is whole at once:
attention goes by blocks of query rows, experts by blocks of tokens and of
experts, the head by blocks of the vocabulary, and weights are upcast
where they are used.
"""

from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import Tensors, f32, rms_norm, swiglu

QUERY_ROWS = 256  # rows of one block of the attention's [H, rows, T] scores
TOKEN_ROWS = 1024  # tokens of one block of the expert layer
EXPERT_BLOCK = 4  # experts upcast at once
VOCAB_ROWS = 16384  # rows of the head upcast at once
LORA_EPS = 1e-6  # q_a_layernorm, kv_a_layernorm


def _dims(cfg: dict):
    return (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
    )


def tensor_table(cfg: dict):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, nope, rope_d, vd, q_rank, kv_rank = _dims(cfg)
    F = cfg["moe_intermediate_size"]
    Fs = F * cfg.get("n_shared_experts", 1)
    E = cfg["n_routed_experts"]  # held here
    R = cfg.get("num_experts_routed") or E
    if cfg.get("expert_offset", 0):
        raise NotImplementedError(
            "the seeded checkpoint numbers its experts from 0: expert_offset must be 0"
        )
    if cfg.get("first_k_dense_replace", 0):
        raise NotImplementedError("first_k_dense_replace > 0: every layer is an expert layer")
    edge = {
        "model.embed_tokens.weight": ((V, D), "w"),
        "model.norm.weight": ((D,), "norm"),
        "lm_head.weight": ((V, D), "w"),
    }
    layer = {
        "input_layernorm.weight": ((D,), "norm"),
        "post_attention_layernorm.weight": ((D,), "norm"),
        "self_attn.q_a_proj.weight": ((q_rank, D), "w"),
        "self_attn.q_a_layernorm.weight": ((q_rank,), "norm"),
        "self_attn.q_b_proj.weight": ((H * (nope + rope_d), q_rank), "w"),
        "self_attn.kv_a_proj_with_mqa.weight": ((kv_rank + rope_d, D), "w"),
        "self_attn.kv_a_layernorm.weight": ((kv_rank,), "norm"),
        "self_attn.kv_b_proj.weight": ((H * (nope + vd), kv_rank), "w"),
        "self_attn.o_proj.weight": ((D, H * vd), "w"),
        "mlp.gate.weight": ((R, D), "router"),
        "mlp.experts.*.gate_proj.weight": ((E, F, D), "w"),
        "mlp.experts.*.up_proj.weight": ((E, F, D), "w"),
        "mlp.experts.*.down_proj.weight": ((E, D, F), "w"),
        "mlp.shared_experts.gate_proj.weight": ((Fs, D), "w"),
        "mlp.shared_experts.up_proj.weight": ((Fs, D), "w"),
        "mlp.shared_experts.down_proj.weight": ((D, Fs), "w"),
    }
    return edge, lambda i: layer


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(cfg: dict):
    """(inv_freq [rope_d / 2], the cos/sin multiplier, sigma's m) from the
    configuration's `rope_parameters` (or `rope_scaling` + `rope_theta`)."""
    rp = dict(cfg.get("rope_parameters") or cfg.get("rope_scaling") or {})
    theta = float(rp.get("rope_theta") or cfg.get("rope_theta") or 10000.0)
    dim = cfg["qk_rope_head_dim"]
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", rp.get("type")) != "yarn":
        return inv.astype(np.float32), 1.0, 1.0
    factor = float(rp["factor"])
    old = rp.get("original_max_position_embeddings") or cfg["max_position_embeddings"]

    def correction(rotations):  # the dimension that makes `rotations` turns in `old` tokens
        return dim * math.log(old / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(rp.get("beta_fast") or 32)), 0)
    high = min(math.ceil(correction(rp.get("beta_slow") or 1)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    m, m_all = rp.get("mscale"), rp.get("mscale_all_dim")
    trig = _mscale(factor, m) / _mscale(factor, m_all) if m and m_all else _mscale(factor, 1.0)
    return inv.astype(np.float32), float(trig), _mscale(factor, m_all or 0)


def rope_interleaved(x, positions, inv, trig):
    """x [T, N, d]: pairs (x[2i], x[2i+1]) rotated by positions * inv[i]."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]  # [T, d/2]
    cos, sin = (jnp.cos(ang) * trig)[:, None, :], (jnp.sin(ang) * trig)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _attention_layer(cfg: dict):
    H, nope, rope_d, vd, _, kv_rank = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    inv, trig, m = yarn(cfg)
    sigma = (nope + rope_d) ** -0.5 * m * m
    rp = cfg.get("rope_parameters") or cfg.get("rope_scaling") or {}
    beta = float(rp.get("llama_4_scaling_beta") or 0.0)
    period = rp.get("original_max_position_embeddings") or cfg["max_position_embeddings"]

    @jax.jit
    def project(x, p):
        T = x.shape[0]
        pos = jnp.arange(T)
        u = rms_norm(x, p["input_layernorm.weight"], eps)
        c_q = rms_norm(u @ f32(p["self_attn.q_a_proj.weight"]).T,
                       p["self_attn.q_a_layernorm.weight"], LORA_EPS)
        q = (c_q @ f32(p["self_attn.q_b_proj.weight"]).T).reshape(T, H, nope + rope_d)
        ckv = u @ f32(p["self_attn.kv_a_proj_with_mqa.weight"]).T
        c = rms_norm(ckv[:, :kv_rank], p["self_attn.kv_a_layernorm.weight"], LORA_EPS)
        k_pe = rope_interleaved(ckv[:, None, kv_rank:], pos, inv, trig)  # [T, 1, rope_d]
        kv = (c @ f32(p["self_attn.kv_b_proj.weight"]).T).reshape(T, H, nope + vd)
        q_pe = rope_interleaved(q[..., nope:], pos, inv, trig)
        a = 1.0 + beta * jnp.log1p(jnp.floor(pos.astype(jnp.float32) / period))
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1) * (sigma * a)[:, None, None]
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (T, H, rope_d))], axis=-1)
        return q, k, kv[..., nope:]

    @jax.jit
    def rows_attend(q_rows, first, k, v):
        T = k.shape[0]
        s = jnp.einsum("thd,jhd->htj", q_rows, k)
        causal = jnp.arange(T)[None, :] <= first + jnp.arange(q_rows.shape[0])[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("htj,jhd->thd", jax.nn.softmax(s, axis=-1), v)

    def mixer(x, p):
        T = x.shape[0]
        q, k, v = project(x, p)
        out = [
            rows_attend(q[r0:r0 + QUERY_ROWS], r0, k, v)  # a block of query rows at a time
            for r0 in range(0, T, QUERY_ROWS)
        ]
        o = jnp.concatenate(out).reshape(T, H * vd)
        return x + o @ f32(p["self_attn.o_proj.weight"]).T

    return mixer


def _expert_layer(cfg: dict):
    top_k = cfg["num_experts_per_tok"]
    held = cfg["n_routed_experts"]
    offset = cfg.get("expert_offset", 0)
    eps = cfg["rms_norm_eps"]
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_method", "greedy") != "greedy":
        raise NotImplementedError("group-limited routing (n_group > 1)")

    @jax.jit
    def route(x, p):
        u = rms_norm(x, p["post_attention_layernorm.weight"], eps)
        scores = jax.nn.softmax(u @ f32(p["mlp.gate.weight"]).T, axis=-1)
        top_w, top_idx = jax.lax.top_k(scores, top_k)  # over every routed expert
        if cfg.get("norm_topk_prob", False):
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        top_w = top_w * cfg.get("routed_scaling_factor", 1.0)
        # each token's weight on every HELD expert (zero where not chosen)
        weight = jnp.zeros((x.shape[0], scores.shape[1]), jnp.float32)
        weight = weight.at[jnp.arange(x.shape[0])[:, None], top_idx].add(top_w)
        shared = swiglu(
            u, p["mlp.shared_experts.gate_proj.weight"],
            p["mlp.shared_experts.up_proj.weight"], p["mlp.shared_experts.down_proj.weight"],
        )
        return u, weight[:, offset:offset + held], shared

    @jax.jit
    def block(u, weight, e_gate, e_up, e_down):
        """Every expert of the block on every token, weighted by the routing."""
        h = jax.nn.silu(jnp.einsum("td,efd->tef", u, f32(e_gate))) * jnp.einsum(
            "td,efd->tef", u, f32(e_up)
        )
        return jnp.einsum("tef,edf,te->td", h, f32(e_down), weight)

    def apply(x, p):
        u, weight, shared = route(
            x, {k: jnp.asarray(v) for k, v in p.items() if ".experts." not in k}
        )
        routed = []
        for t0 in range(0, x.shape[0], TOKEN_ROWS):
            rows = slice(t0, t0 + TOKEN_ROWS)
            acc = 0.0
            for e0 in range(0, held, EXPERT_BLOCK):
                es = slice(e0, e0 + EXPERT_BLOCK)
                acc = acc + block(
                    u[rows], weight[rows, es],
                    *(jnp.asarray(p[f"mlp.experts.*.{n}_proj.weight"][es])
                      for n in ("gate", "up", "down")),
                )
            routed.append(acc)
        return x + jnp.concatenate(routed) + shared

    return apply


def logits(model_dir: Path, cfg: dict, ids, last: int) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions."""
    tensors = Tensors(model_dir)
    attention, experts = _attention_layer(cfg), _expert_layer(cfg)

    def only(p, *prefixes):
        return {k: jnp.asarray(v) for k, v in p.items() if k.startswith(prefixes)}

    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(tensors.get("model.embed_tokens.weight"))[np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            p = tensors.layer(i)
            x = attention(x, only(p, "input_layernorm", "self_attn"))
            x = experts(x, {k: v for k, v in p.items() if k.startswith(("post_attention", "mlp."))})
        x = rms_norm(x[-last:], tensors.get("model.norm.weight"), cfg["rms_norm_eps"])
        head = tensors.get("lm_head.weight")
        return jnp.concatenate(
            [
                x @ f32(head[r0 : r0 + VOCAB_ROWS]).T
                for r0 in range(0, head.shape[0], VOCAB_ROWS)
            ],
            axis=-1,
        )
