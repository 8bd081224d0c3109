"""MiniCPM-SALA (block-sparse softmax attention among lightning linear
attention, on MiniCPM's muP trunk) in plain float32 jax.numpy: the quadratic
lightning form and the selection by explicit masks.

    h0 = scale_emb * E[token]
    h += s * Mixer(RMSNorm(h));   n = RMSNorm(h);   h += s * W_down(silu(W_gate n) * W_up n)
    s = scale_depth / sqrt(num_hidden_layers)      of the config as served
    logits = W_head (RMSNorm(h_L) / (hidden_size / dim_model_base))

`lightning-attn`: q, k, v = W_q x, W_k x, W_v x as lightning_nh heads of
lightning_head_dim; RMSNorm a head on q and k, then RoPE over the whole head
(theta rope_theta, HF's half-split);

    o_t = sum_{i <= t} lam_h^(t - i) (q_t . k_i / sqrt(d)) v_i       lam_h = exp(-2^(-8 (h + 1) / H))

and the output is `W_o (RMSNorm_{H d}(concat_h o_t) * sigmoid(W_z x))`.

`minicpm4`: q of num_attention_heads, k and v of num_key_value_heads heads of
head_dim; RMSNorm a head on q and k; no position embedding; for the query at
position t (context n = t + 1), a KV head g at a time:

    n <= dense_len:  attend every i <= t
    else:  c_j = mean(k_(16 j) .. k_(16 j + 31))              every j with 16 j + 31 <= t
           p_h = softmax_j(q_h . c_j / sqrt(head_dim))        a query head of the group
           r[j] = sum_h p_h[j];   R[b] = max r[j], j in [4 b - 1, 4 b + 3]
           chosen = block 0, the 32 blocks ending at floor(t / 64), and the 31
           best-scoring of the blocks before those (a stable descending sort:
           ties to the lower index); softmax attention over the tokens i <= t
           of the chosen blocks

and the output is `W_o (o * sigmoid(W_g x))`.  Sizes from `sparse_config`
(the defaults are MiniCPM4's published ones; the catalog row has none).

Nothing of `dnet_tpu`: no state, no chunk, no kernel, no index leaf, no
threshold search.  DEPARTURES from the published code, each under the
configuration file's `assumed`: (1) the rule is PER POSITION, where HF
decides dense or sparse once a forward call by that call's length: the two
agree in every decode step and for every prompt of at most dense_len tokens,
and here positions under dense_len of a LONGER prompt attend everything
before them (more than HF gives them); (2) the normaliser of p_h runs over
the fine pooled keys exactly (the published kernel may take it from coarser
spans); (3) the slope table is ALiBi's, the same in every layer; (4) q and k
are normed before RoPE; (5) the lightning output norm runs over the whole
concatenation; (6) `mup_denominator` plays no part in inference; (7) tensor
names.

It runs beside the server's memory, so nothing large is whole at once:
attention goes by blocks of query rows, the MLP in two steps, the head by
blocks of the vocabulary, and weights are upcast where they are used.

`round_state` and `nearest` are the precision controls' doors
(benchmarks/precision_control_minicpm_sala.py): the lightning state kept as
a recurrence and rounded after every token; the selection taking the 31
NEAREST blocks before the window instead of the best.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import Tensors, f32, rms_norm, rotate_half

QUERY_ROWS = 256  # rows of one block of the [heads, rows, T] pair tensors
VOCAB_ROWS = 16384  # rows of the head upcast at once
SPARSE_DEFAULTS = {
    "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
    "init_blocks": 1, "window_size": 2048, "dense_len": 8192,
}
SPARSE = "minicpm4"  # the other mixer is `lightning-attn`


def tensor_table(cfg: dict):
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    LW = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    edge = {
        "model.embed_tokens.weight": ((V, D), "w"),
        "model.norm.weight": ((D,), "norm"),
        "lm_head.weight": ((V, D), "w"),
    }

    def layer(i: int):
        common = {
            "input_layernorm.weight": ((D,), "norm"),
            "post_attention_layernorm.weight": ((D,), "norm"),
            "mlp.gate_proj.weight": ((F, D), "w"),
            "mlp.up_proj.weight": ((F, D), "w"),
            "mlp.down_proj.weight": ((D, F), "w"),
        }
        if cfg["mixer_types"][i] == SPARSE:
            return {
                **common,
                "self_attn.q_proj.weight": ((H * Hd, D), "w"),
                "self_attn.k_proj.weight": ((KVH * Hd, D), "w"),
                "self_attn.v_proj.weight": ((KVH * Hd, D), "w"),
                "self_attn.o_gate.weight": ((H * Hd, D), "w"),
                "self_attn.o_proj.weight": ((D, H * Hd), "w"),
                "self_attn.q_norm.weight": ((Hd,), "norm"),
                "self_attn.k_norm.weight": ((Hd,), "norm"),
            }
        return {
            **common,
            "self_attn.q_proj.weight": ((LW, D), "w"),
            "self_attn.k_proj.weight": ((LW, D), "w"),
            "self_attn.v_proj.weight": ((LW, D), "w"),
            "self_attn.z_proj.weight": ((LW, D), "w"),
            "self_attn.o_proj.weight": ((D, LW), "w"),
            "self_attn.q_norm.weight": ((cfg["lightning_head_dim"],), "norm"),
            "self_attn.k_norm.weight": ((cfg["lightning_head_dim"],), "norm"),
            "self_attn.o_norm.weight": ((LW,), "norm"),
        }

    return edge, layer


def residual_scale(cfg: dict) -> float:
    return cfg["scale_depth"] / float(np.sqrt(cfg["num_hidden_layers"]))


# ---- lightning attention -----------------------------------------------------
def log_decay(H: int):
    return -(2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H))


def lightning_quadratic(q, k, v):
    """q/k/v [T, H, d] -> [T, H, d]: every pair, a block of rows at a time."""
    T, H, d = q.shape
    lg = log_decay(H)[:, None, None]
    out = []
    for r0 in range(0, T, QUERY_ROWS):
        rows = jnp.arange(T)[r0:r0 + QUERY_ROWS]
        gap = (rows[:, None] - jnp.arange(T)[None, :]).astype(jnp.float32)
        causal = gap >= 0
        s = jnp.einsum("thd,ihd->hti", q[r0:r0 + QUERY_ROWS], k) * d**-0.5
        a = jnp.where(causal, s * jnp.exp(lg * jnp.where(causal, gap, 0.0)), 0.0)
        out.append(jnp.einsum("hti,ihd->thd", a, v))
    return jnp.concatenate(out)


def lightning_recurrent(q, k, v, round_state):
    """The same function as a recurrence whose state is rounded to
    `round_state` after every token (the precision control's)."""
    T, H, d = q.shape
    lam = jnp.exp(log_decay(H))[:, None, None]

    def step(S, x):
        qt, kt, vt = x
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        S = S.astype(round_state).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", S, qt) * d**-0.5

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    return o


def _lightning_layer(cfg: dict, round_state):
    H, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def mixer(x, p):
        T = x.shape[0]
        u = rms_norm(x, p["input_layernorm.weight"], eps)
        q = (u @ f32(p["self_attn.q_proj.weight"]).T).reshape(T, H, d)
        k = (u @ f32(p["self_attn.k_proj.weight"]).T).reshape(T, H, d)
        v = (u @ f32(p["self_attn.v_proj.weight"]).T).reshape(T, H, d)
        gate = jax.nn.sigmoid(u @ f32(p["self_attn.z_proj.weight"]).T)
        q = rms_norm(q, p["self_attn.q_norm.weight"], eps)
        k = rms_norm(k, p["self_attn.k_norm.weight"], eps)
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        q = q * jnp.cos(ang) + rotate_half(q) * jnp.sin(ang)
        k = k * jnp.cos(ang) + rotate_half(k) * jnp.sin(ang)
        if round_state is None:
            o = lightning_quadratic(q, k, v)
        else:
            o = lightning_recurrent(q, k, v, round_state)
        o = rms_norm(o.reshape(T, H * d), p["self_attn.o_norm.weight"], eps) * gate
        return o @ f32(p["self_attn.o_proj.weight"]).T

    return jax.jit(mixer)


# ---- block-sparse attention ---------------------------------------------------
def chosen_blocks(q, c, t, sp: dict, nearest: bool):
    """q [R, G, Hd] the group's heads at positions t [R]; c [Nc, Hd] the KV
    head's pooled keys -> [R, nb] bool: the blocks each query attends."""
    K, s, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    rpb, wb = bs // s, sp["window_size"] // bs
    n_best = sp["topk"] - sp["init_blocks"] - wb
    Nc = c.shape[0]
    nb = Nc // rpb
    j = jnp.arange(Nc)
    complete = s * j[None, :] + K - 1 <= t[:, None]  # [R, Nc]
    scores = jnp.einsum("rgd,jd->rgj", q, c) * q.shape[-1] ** -0.5
    scores = jnp.where(complete[:, None, :], scores, -jnp.inf)
    p = jnp.where(complete[:, None, :], jax.nn.softmax(scores, axis=-1), 0.0)
    r = jnp.sum(p, axis=1)  # [R, Nc]: 0 where the span is not complete
    # the spans that touch block b: those starting in it, and those starting
    # up to kernel_size / stride - 1 strides before it
    reach = K // s - 1
    padded = jnp.pad(r, ((0, 0), (reach, 0)))
    touch = jnp.stack(
        [padded[:, i:i + nb * rpb:rpb] for i in range(rpb + reach)], axis=-1
    )  # [R, nb, rpb + reach]
    R = jnp.max(touch, axis=-1)
    b = jnp.arange(nb)[None, :]
    qb = (t // bs)[:, None]
    first_window = qb - wb + 1
    forced = (b < sp["init_blocks"]) | ((b >= first_window) & (b <= qb))
    cand = (b >= sp["init_blocks"]) & (b < first_window)
    if nearest:
        best = cand & (b >= first_window - n_best)
    else:
        order = jnp.argsort(jnp.where(cand, -R, jnp.inf), axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        best = cand & (rank < n_best)
    dense = (t + 1 <= sp["dense_len"])[:, None]
    return jnp.where(dense, b <= qb, forced | best)


def sparse_attention(q, k, v, sp: dict, nearest: bool):
    """q [T, H, Hd], k/v [T, KVH, Hd] -> [T, H, Hd]."""
    T, H, Hd = q.shape
    KVH = k.shape[1]
    G = H // KVH
    K, s, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    Tp = -(-T // bs) * bs
    # pooled keys by explicit means (a row whose span runs past T is never
    # complete for any query)
    kp = jnp.pad(k, ((0, Tp + K - T), (0, 0), (0, 0)))
    span = s * jnp.arange(Tp // s)[:, None] + jnp.arange(K)[None, :]  # [Nc, K]: row j's tokens
    c = jnp.mean(kp[span], axis=1)  # [Nc, KVH, Hd]
    tok_block = jnp.arange(T) // bs
    out = []
    for r0 in range(0, T, QUERY_ROWS):
        t = jnp.arange(T)[r0:r0 + QUERY_ROWS]
        qr = q[r0:r0 + QUERY_ROWS].reshape(-1, KVH, G, Hd)
        heads = []
        for g in range(KVH):
            chosen = chosen_blocks(qr[:, g], c[:, g], t, sp, nearest)  # [R, nb]
            keep = chosen[:, tok_block] & (jnp.arange(T)[None, :] <= t[:, None])
            sc = jnp.einsum("rgd,id->gri", qr[:, g], k[:, g]) * Hd**-0.5
            sc = jnp.where(keep[None], sc, -jnp.inf)
            heads.append(jnp.einsum("gri,id->rgd", jax.nn.softmax(sc, axis=-1), v[:, g]))
        out.append(jnp.stack(heads, axis=1).reshape(-1, H, Hd))
    return jnp.concatenate(out)


def _sparse_layer(cfg: dict, nearest: bool):
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    sp = {**SPARSE_DEFAULTS, **(cfg.get("sparse_config") or {})}

    def mixer(x, p):
        T = x.shape[0]
        u = rms_norm(x, p["input_layernorm.weight"], eps)
        q = (u @ f32(p["self_attn.q_proj.weight"]).T).reshape(T, H, Hd)
        k = (u @ f32(p["self_attn.k_proj.weight"]).T).reshape(T, KVH, Hd)
        v = (u @ f32(p["self_attn.v_proj.weight"]).T).reshape(T, KVH, Hd)
        gate = jax.nn.sigmoid(u @ f32(p["self_attn.o_gate.weight"]).T)
        q = rms_norm(q, p["self_attn.q_norm.weight"], eps)
        k = rms_norm(k, p["self_attn.k_norm.weight"], eps)
        o = sparse_attention(q, k, v, sp, nearest).reshape(T, H * Hd) * gate
        return o @ f32(p["self_attn.o_proj.weight"]).T

    return jax.jit(mixer)


def _mlp(cfg: dict):
    eps = cfg["rms_norm_eps"]

    # in two steps, so that the three matrices (0.8 GB in float32 at the
    # published width) are never on the device together beside the server
    @jax.jit
    def up(x, norm, w_gate, w_up):
        h = rms_norm(x, norm, eps)
        return jax.nn.silu(h @ f32(w_gate).T) * (h @ f32(w_up).T)

    @jax.jit
    def down(a, w_down):
        return a @ f32(w_down).T

    def apply(x, p):
        a = up(x, jnp.asarray(p["post_attention_layernorm.weight"]),
               jnp.asarray(p["mlp.gate_proj.weight"]), jnp.asarray(p["mlp.up_proj.weight"]))
        return down(a, jnp.asarray(p["mlp.down_proj.weight"]))

    return apply


def logits(model_dir: Path, cfg: dict, ids, last: int, round_state=None,
           nearest: bool = False) -> jax.Array:
    """[last, V] float32 logits of the sequence's last positions."""
    tensors = Tensors(model_dir)
    light, sparse, mlp = _lightning_layer(cfg, round_state), _sparse_layer(cfg, nearest), _mlp(cfg)
    s = residual_scale(cfg)

    def only(p, *prefixes):
        return {k: jnp.asarray(v) for k, v in p.items() if k.startswith(prefixes)}

    with jax.default_matmul_precision("highest"):
        x = cfg["scale_emb"] * f32(
            np.asarray(tensors.get("model.embed_tokens.weight"))[np.asarray(ids)]
        )
        for i in range(cfg["num_hidden_layers"]):
            p = tensors.layer(i)
            mixer = sparse if cfg["mixer_types"][i] == SPARSE else light
            x = x + s * mixer(x, only(p, "input_layernorm", "self_attn"))
            x = x + s * mlp(x, p)
        x = rms_norm(x[-last:], tensors.get("model.norm.weight"), cfg["rms_norm_eps"])
        x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
        head = tensors.get("lm_head.weight")
        return jnp.concatenate(
            [
                x @ f32(head[r0 : r0 + VOCAB_ROWS]).T
                for r0 in range(0, head.shape[0], VOCAB_ROWS)
            ],
            axis=-1,
        )
