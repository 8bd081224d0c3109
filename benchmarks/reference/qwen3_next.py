"""Qwen3-Next (Gated DeltaNet layers and gated softmax attention 3 : 1, an
expert layer after each) in plain float32 jax.numpy: the RECURRENCE, token
by token, and quadratic attention.

Pre-norm residual, no biases.  `RMSNorm0` is the ZERO-CENTRED norm
`x / rms(x) * (1 + w)`, eps 1e-6 (input_layernorm, post_attention_layernorm,
the final norm, the q/k head norms); layer i is full attention where
`(i + 1) % full_attention_interval == 0`, else Gated DeltaNet; u = RMSNorm0(x).

    Gated DeltaNet (16 key heads, 32 value heads of 128; value head h uses key head h // 2)
      [q | k | v | z] = in_proj_qkvz u   (laid out by key head: 128 q, 128 k, 256 v, 256 z each)
      [b | a]         = in_proj_ba u     (by key head likewise: 2 b, 2 a)
      c_t = SiLU(sum_j w_conv[:, j] m_{t-3+j}),  m = concat(q, k, v), depthwise, causal, m_{<0} = 0
      q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(128);   k^ = k / sqrt(sum k^2 + 1e-6)
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias);  alpha = exp(g)
      S_t = alpha_t S_{t-1} + k^_t (x) [beta_t (v_t - (alpha_t S_{t-1})^T k^_t)]     S_0 = 0
      o_t = S_t^T q^_t;   y_t = (o_t / rms(o_t) * w_norm) * SiLU(z_t);   out = out_proj concat_h(y)
    Gated attention (16 query / 2 KV heads of 256, scale 1 / sqrt(256), causal)
      [q | gate] = q_proj u (per head: 256 q then 256 gate);  k = k_proj u;  v = v_proj u
      q = RoPE64(RMSNorm0(q));  k = RoPE64(RMSNorm0(k))   (rotate-half over the first 64 dims, theta 1e7)
      out = o_proj(softmax-attention(q, k, v) * sigmoid(gate))
    Expert layer (every layer):  h = x + mixer;  u2 = RMSNorm0(h)
      p = softmax(W_r u2);  top-10;  w_e = p_e / sum_chosen p;  routed = sum_e w_e SwiGLU_e(u2)
      y = h + routed + sigmoid(w_sg . u2) * SwiGLU_shared(u2)

The state is carried token by token under `lax.scan`; attention forms
every pair of positions.  No chunks, no cache, no kernel, nothing of
`dnet_tpu`.  The served path computes the delta rule in chunks of 64 and
steps a stored state (dnet_tpu/ops/gated_delta.py); that the two agree is
what the check decides.

The expert SHARE is the configuration's: `num_experts` experts are held,
the range from `expert_offset` of the `num_experts_routed` the router
scores; routing, top-k and the normalisation run over all of them, and
what the absent experts would have added is left out, here as in the
program.  The published checkpoint's multi-token-prediction module is not
part of the forward pass and is not here.

**The seeded weights** (benchmarks/harness/weights.py, which knows three
kinds): the zero-centred norm weights are of kind `w` (N(0, 0.02), so the
scale 1 + w is near 1); `linear_attn.norm.weight`, a plain weight, is of
kind `norm`; `A_log` and `dt_bias` are of kind `w` too, so exp(A_log) is
about 1, softplus(a + dt_bias) about log 2, and g about -0.7: a key fades
in a few tokens.  What that leaves the check blind to is in the
configuration's `check.reason`.

It runs beside the server's 10 GB, so nothing large is whole at once:
experts go by blocks of tokens and of experts, attention by blocks of query
rows, the head by blocks of the vocabulary, and weights are upcast where
they are used.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import Tensors, f32, rotate_half, swiglu

L2_EPS = 1e-6
QUERY_ROWS = 1024  # rows of one block of the attention's [rows, T] scores
TOKEN_ROWS = 1024  # tokens of one block of the expert layer
EXPERT_BLOCK = 32  # experts upcast at once
VOCAB_ROWS = 16384  # rows of the head upcast at once


def rms_norm0(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + f32(w))


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % int(cfg.get("full_attention_interval", 4)) == 0


def _dims(cfg: dict):
    HK, HV = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return HK, HV, Dk, Dv


def tensor_table(cfg: dict):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    HK, HV, Dk, Dv = _dims(cfg)
    K = cfg.get("linear_conv_kernel_dim", 4)
    F, Fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    E = cfg["num_experts"]  # held here
    R = cfg.get("num_experts_routed") or E
    if cfg.get("expert_offset", 0):
        raise NotImplementedError(
            "the seeded checkpoint numbers its experts from 0: expert_offset must be 0"
        )
    edge = {
        "model.embed_tokens.weight": ((V, D), "w"),
        "model.norm.weight": ((D,), "w"),  # zero-centred
        "lm_head.weight": ((V, D), "w"),
    }
    experts = {
        "input_layernorm.weight": ((D,), "w"),
        "post_attention_layernorm.weight": ((D,), "w"),
        "mlp.gate.weight": ((R, D), "router"),
        "mlp.experts.*.gate_proj.weight": ((E, F, D), "w"),
        "mlp.experts.*.up_proj.weight": ((E, F, D), "w"),
        "mlp.experts.*.down_proj.weight": ((E, D, F), "w"),
        "mlp.shared_expert.gate_proj.weight": ((Fs, D), "w"),
        "mlp.shared_expert.up_proj.weight": ((Fs, D), "w"),
        "mlp.shared_expert.down_proj.weight": ((D, Fs), "w"),
        "mlp.shared_expert_gate.weight": ((1, D), "w"),
    }
    key, value = HK * Dk, HV * Dv
    delta = {
        "linear_attn.in_proj_qkvz.weight": ((2 * key + 2 * value, D), "w"),
        "linear_attn.in_proj_ba.weight": ((2 * HV, D), "w"),
        "linear_attn.conv1d.weight": ((2 * key + value, 1, K), "w"),
        "linear_attn.A_log": ((HV,), "w"),
        "linear_attn.dt_bias": ((HV,), "w"),
        "linear_attn.norm.weight": ((Dv,), "norm"),
        "linear_attn.out_proj.weight": ((D, value), "w"),
    }
    attention = {
        "self_attn.q_proj.weight": ((2 * H * Hd, D), "w"),
        "self_attn.k_proj.weight": ((KVH * Hd, D), "w"),
        "self_attn.v_proj.weight": ((KVH * Hd, D), "w"),
        "self_attn.o_proj.weight": ((D, H * Hd), "w"),
        "self_attn.q_norm.weight": ((Hd,), "w"),
        "self_attn.k_norm.weight": ((Hd,), "w"),
    }

    def layer(i: int):
        return {**experts, **(attention if is_full_attention(cfg, i) else delta)}

    return edge, layer


def delta_rule(q, k, v, g, beta, round_state=None):
    """The recurrence, token by token.  q/k [T, HK, Dk] as the convolution
    left them, v [T, HV, Dv], g/beta [T, HV] -> o [T, HV, Dv].
    `round_state`: a type the state is rounded to after every token (the
    precision control's; None here)."""
    T, HK, Dk = q.shape
    HV, Dv = v.shape[1], v.shape[2]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * Dk**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q = jnp.repeat(q, HV // HK, axis=1)
    k = jnp.repeat(k, HV // HK, axis=1)

    def step(S, xs):
        q, k, v, g, b = xs
        Sd = S * jnp.exp(g)[:, None, None]
        u = b[:, None] * (v - jnp.einsum("hkv,hk->hv", Sd, k))
        S = Sd + k[:, :, None] * u[:, None, :]
        if round_state is not None:
            # reduce_precision, not a pair of casts: XLA may elide those
            fi = jnp.finfo(round_state)
            S = jax.lax.reduce_precision(S, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, q)

    _, o = jax.lax.scan(step, jnp.zeros((HV, Dk, Dv), jnp.float32), (q, k, v, g, beta))
    return o


def _delta_layer(cfg: dict, round_state=None):
    HK, HV, Dk, Dv = _dims(cfg)
    r = HV // HK
    K = cfg.get("linear_conv_kernel_dim", 4)
    eps = cfg["rms_norm_eps"]

    def mixer(x, p):
        T = x.shape[0]
        u = rms_norm0(x, p["input_layernorm.weight"], eps)
        qkvz = (u @ f32(p["linear_attn.in_proj_qkvz.weight"]).T).reshape(T, HK, -1)
        q, k, v, z = jnp.split(qkvz, [Dk, 2 * Dk, 2 * Dk + r * Dv], axis=-1)
        ba = (u @ f32(p["linear_attn.in_proj_ba.weight"]).T).reshape(T, HK, 2 * r)
        b, a = ba[..., :r].reshape(T, HV), ba[..., r:].reshape(T, HV)
        m = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1), v.reshape(T, -1)], axis=-1)
        w = f32(p["linear_attn.conv1d.weight"])[:, 0, :]  # [C, K]
        padded = jnp.concatenate([jnp.zeros((K - 1, m.shape[1]), jnp.float32), m])
        c = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)))
        key = HK * Dk
        q, k = c[:, :key].reshape(T, HK, Dk), c[:, key:2 * key].reshape(T, HK, Dk)
        v = c[:, 2 * key:].reshape(T, HV, Dv)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(f32(p["linear_attn.A_log"])) * jax.nn.softplus(
            a + f32(p["linear_attn.dt_bias"])
        )
        o = delta_rule(q, k, v, g, beta, round_state)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        y = o * f32(p["linear_attn.norm.weight"]) * jax.nn.silu(z.reshape(T, HV, Dv))
        return x + y.reshape(T, HV * Dv) @ f32(p["linear_attn.out_proj.weight"]).T

    return jax.jit(mixer)


def _attention_layer(cfg: dict):
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // KVH
    rot = int(Hd * cfg.get("partial_rotary_factor", 1.0))
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def rope(x, T):
        inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        xr, xp = x[..., :rot], x[..., rot:]
        xr = xr * jnp.cos(ang) + rotate_half(xr) * jnp.sin(ang)
        return jnp.concatenate([xr, xp], axis=-1)

    def mixer(x, p):
        T = x.shape[0]
        u = rms_norm0(x, p["input_layernorm.weight"], eps)
        qg = (u @ f32(p["self_attn.q_proj.weight"]).T).reshape(T, H, 2 * Hd)
        q, gate = qg[..., :Hd], qg[..., Hd:]
        k = (u @ f32(p["self_attn.k_proj.weight"]).T).reshape(T, KVH, Hd)
        v = (u @ f32(p["self_attn.v_proj.weight"]).T).reshape(T, KVH, Hd)
        q = rope(rms_norm0(q, p["self_attn.q_norm.weight"], eps), T)
        k = rope(rms_norm0(k, p["self_attn.k_norm.weight"], eps), T)
        q = q.reshape(T, KVH, G, Hd)
        out = []
        for r0 in range(0, T, QUERY_ROWS):  # a block of query rows at a time
            rows = slice(r0, min(r0 + QUERY_ROWS, T))
            s = jnp.einsum("tkgd,ikd->kgti", q[rows], k) * Hd**-0.5
            causal = jnp.arange(T)[None, :] <= jnp.arange(T)[rows][:, None]
            s = jnp.where(causal, s, -jnp.inf)
            out.append(jnp.einsum("kgti,ikd->tkgd", jax.nn.softmax(s, axis=-1), v))
        o = jnp.concatenate(out).reshape(T, H, Hd) * jax.nn.sigmoid(gate)
        return x + o.reshape(T, H * Hd) @ f32(p["self_attn.o_proj.weight"]).T

    return jax.jit(mixer)


def _expert_layer(cfg: dict):
    top_k = cfg["num_experts_per_tok"]
    held = cfg["num_experts"]
    offset = cfg.get("expert_offset", 0)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def route(x, p):
        u = rms_norm0(x, p["post_attention_layernorm.weight"], eps)
        scores = jax.nn.softmax(u @ f32(p["mlp.gate.weight"]).T, axis=-1)
        top_w, top_idx = jax.lax.top_k(scores, top_k)  # over every routed expert
        if cfg.get("norm_topk_prob", True):
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        # each token's weight on every HELD expert (zero where not chosen)
        weight = jnp.zeros((x.shape[0], scores.shape[1]), jnp.float32)
        weight = weight.at[jnp.arange(x.shape[0])[:, None], top_idx].add(top_w)
        shared = jax.nn.sigmoid(u @ f32(p["mlp.shared_expert_gate.weight"]).T) * swiglu(
            u, p["mlp.shared_expert.gate_proj.weight"],
            p["mlp.shared_expert.up_proj.weight"], p["mlp.shared_expert.down_proj.weight"],
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


def logits(model_dir: Path, cfg: dict, ids, last: int, round_state=None) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions."""
    tensors = Tensors(model_dir)
    delta, attention, experts = (
        _delta_layer(cfg, round_state), _attention_layer(cfg), _expert_layer(cfg)
    )

    def only(p, *prefixes):
        return {k: jnp.asarray(v) for k, v in p.items() if k.startswith(prefixes)}

    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(tensors.get("model.embed_tokens.weight"))[np.asarray(ids)])
        for i in range(cfg["num_hidden_layers"]):
            p = tensors.layer(i)
            if is_full_attention(cfg, i):
                x = attention(x, only(p, "input_layernorm", "self_attn"))
            else:
                x = delta(x, only(p, "input_layernorm", "linear_attn"))
            x = experts(x, {k: v for k, v in p.items() if k.startswith(("post_attention", "mlp."))})
        x = rms_norm0(x[-last:], tensors.get("model.norm.weight"), cfg["rms_norm_eps"])
        head = tensors.get("lm_head.weight")
        return jnp.concatenate(
            [
                x @ f32(head[r0 : r0 + VOCAB_ROWS]).T
                for r0 in range(0, head.shape[0], VOCAB_ROWS)
            ],
            axis=-1,
        )
