"""Qwen3-Next ring model (`model_type` qwen3_next): Gated DeltaNet layers
and gated full attention in one stack, an expert layer after each.

The layer pattern repeats with period `full_attention_interval` (4): three
Gated DeltaNet layers, then one gated softmax-attention layer, every one
followed by a mixture-of-experts block (pre-norm residual, no biases;
every RMSNorm but the delta rule's output norm is ZERO-CENTRED,
ops/norms.py rms_norm0).  What each kind of layer computes:

- Gated DeltaNet (`linear_attn.*`; ops/gated_delta.py): `in_proj_qkvz` and
  `in_proj_ba` give q, k (16 key heads of 128), v, z (32 value heads of
  128) and a `b`, `a` a value head; q | k | v go through a causal
  depthwise convolution of 4 taps and SiLU, q and k are l2-normalised,
  `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` (float32),
  the delta rule reads and corrects a state `S [128, 128]` float32 a value
  head, and the output is `RMSNorm(o) * SiLU(z)` through `out_proj`.  A
  sequence's memory in such a layer is ONE entry of fixed size: `S` and
  the convolution's 3-column tail (the `state` kind, obs/phases.py).
- Gated attention (`self_attn.*`): `q_proj` gives each head 256 q and 256
  gate; q and k take a zero-centred per-head RMSNorm and a rotary over the
  FIRST `partial_rotary_factor` of the head (ops/rope.py), attention is
  causal softmax over everything before (the `full` kind: blocks of a
  pool), and the output is multiplied by `sigmoid(gate)` before `o_proj`.
- The expert block: a softmax router over `num_experts_routed` experts,
  top-k renormalised over the chosen ones (`norm_topk_prob`), plus ONE
  shared expert scaled by `sigmoid(w . x)`, a gate a token.  The SHARE
  comes from the config as cohere2_moe reads it: `num_experts` held, the
  range from `expert_offset` of `num_experts_routed`; routing runs over
  every routed expert and nothing stands in for the absent ones.

The two kinds of layer have different PARAMETERS, so the stack is a
`lax.scan` over PERIODS: `gdn [P, 3, ...]`, `attn [P, ...]`,
`moe [P, 4, ...]`.  A window of layers must be whole periods: a window
that starts or ends inside a period (an offloading shard's single-layer
windows, a ring stage cut off the period's edge) is refused at
construction.  Tensor or sequence parallelism, weight quantisation and the
multi-token-prediction module of the published checkpoint are not served.

`paged_kinds` is `(state, state, state, full) x P`: one sequence holds a
lane of state AND a block table, through kv/store.py HybridStore.  Under
its `attend_fn` the store rides the scan's carry and each layer's step
updates its own slice in place: `attend_fn(q, k, v, store, kind="full",
layer=)` for an attention layer, `attend_fn(m, None, None, store,
kind="state", layer=, gate={g, beta, conv_w})` for a delta-rule layer
(`m` the projections before the convolution).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.models.base import ModelConfig, RingModel
from dnet_tpu.obs.phases import (
    KV_KIND_FULL,
    KV_KIND_STATE,
    SCOPE_ATTN,
    SCOPE_ATTN_FULL,
    SCOPE_ATTN_STATE,
    SCOPE_MOE,
    SCOPE_MOE_SHARED,
)
from dnet_tpu.ops.attention import cached_attend
from dnet_tpu.ops.gated_delta import gdn_impl, gdn_prefill
from dnet_tpu.ops.moe import EXPERT_KEYS
from dnet_tpu.ops.norms import rms_norm, rms_norm0
from dnet_tpu.ops.rope import apply_rope, rope_frequencies


class Qwen3NextRingModel(RingModel):
    model_type = "qwen3_next"
    supports_paged_attend = True
    supports_weight_quant = False  # the period stacks are not quantize_tree's layout
    reports_moe_held = True
    moe_grouped = True
    state_family = "gdn"

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        x = config.extra
        self.period = int(x.get("full_attention_interval", 4))
        refused = [
            why for bad, why in (
                (config.attention_bias, "attention biases"),
                (x.get("mlp_only_layers"), "dense-MLP layers (mlp_only_layers)"),
                (int(x.get("decoder_sparse_step", 1)) != 1, "decoder_sparse_step != 1"),
                (config.rope_scaling, "rope_scaling"),
                (x.get("use_sliding_window"), "a sliding window"),
            ) if bad
        ]
        if refused:
            raise NotImplementedError(f"qwen3_next: not implemented: {', '.join(refused)}")
        P = self.period
        if (
            not self.layers
            or self.layers[0] % P
            or len(self.layers) % P
            or self.layers != list(range(self.layers[0], self.layers[0] + len(self.layers)))
        ):
            raise NotImplementedError(
                f"qwen3_next: layers {self.layers[:1]}..{self.layers[-1:]} are not "
                f"whole periods of {P} (its parameters stack by period: a "
                "window may not start or end inside one)"
            )
        self.n_periods = len(self.layers) // P
        self.eps = config.rms_norm_eps
        # the delta rule's shapes
        self.HK = int(x["linear_num_key_heads"])
        self.HV = int(x["linear_num_value_heads"])
        self.Dk = int(x["linear_key_head_dim"])
        self.Dv = int(x["linear_value_head_dim"])
        self.conv_k = int(x.get("linear_conv_kernel_dim", 4))
        self.conv_channels = 2 * self.HK * self.Dk + self.HV * self.Dv
        # the expert share: `num_experts` held of `num_experts_routed`
        self.n_held = config.num_local_experts
        self.n_routed = int(x.get("num_experts_routed") or self.n_held)
        self.expert_offset = int(x.get("expert_offset", 0))
        if not 0 <= self.expert_offset <= self.n_routed - self.n_held:
            raise ValueError(
                f"qwen3_next: experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_held}) lie outside the "
                f"router's {self.n_routed}"
            )
        self.norm_topk_prob = bool(x.get("norm_topk_prob", True))
        rot = int(config.head_dim * float(x.get("partial_rotary_factor", 1.0)))
        inv_freq, self.rope_scale = rope_frequencies(
            rot, config.rope_theta, None, config.max_position_embeddings
        )
        self.inv_freq = jnp.asarray(inv_freq)
        self.paged_kinds = ((KV_KIND_STATE,) * (P - 1) + (KV_KIND_FULL,)) * self.n_periods

    # ---- cache construction --------------------------------------------
    def init_kv(self, n_layers, batch, max_seq, dtype="bfloat16", quant_bits=0,
                rotating=True) -> dict:
        """The attention layers' slot-addressed rows and the delta-rule
        layers' entries side by side: {"k", "v": [P, B, max_seq, KVH, Hd],
        "S": [3P, B, HV, Dk, Dv] float32, "conv": [3P, B, 3, C]}."""
        if quant_bits:
            raise NotImplementedError("qwen3_next: a quantized KV cache")
        c = self.config
        P = n_layers // self.period
        n_state = P * (self.period - 1)
        dt = jnp.dtype(dtype)
        row = (P, batch, max_seq, c.num_key_value_heads, c.head_dim)
        return {
            "k": jnp.zeros(row, dt),
            "v": jnp.zeros(row, dt),
            "S": jnp.zeros((n_state, batch, self.HV, self.Dk, self.Dv), jnp.float32),
            "conv": jnp.zeros((n_state, batch, self.conv_k - 1, self.conv_channels), dt),
        }

    def kv_rewindable(self, max_seq: int) -> bool:
        return False  # a state that took a token cannot give it back

    # ---- the mixers -------------------------------------------------------
    def _delta(self, p, u, kvs, idx, t_real, kv_commit, attend_fn):
        """A Gated DeltaNet mixer.  `kvs`: this layer's entries {"S":
        [B, ...], "conv": [B, ...]}, or with `attend_fn` the store."""
        B, T, _ = u.shape
        m = u @ p["w_qkv"]  # [B, T, C]: q | k | v before the convolution
        z = (u @ p["w_z"]).reshape(B, T, self.HV, self.Dv)
        # the decay's and the correction's logits stay float32: g is summed
        # over the tokens a key survives
        b = jnp.matmul(u, p["w_b"], preferred_element_type=jnp.float32)
        a = jnp.matmul(u, p["w_a"], preferred_element_type=jnp.float32)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a + p["dt_bias"].astype(jnp.float32)
        )  # [B, T, HV]
        with jax.named_scope(SCOPE_ATTN_STATE):
            if attend_fn is not None:
                o, kvs = attend_fn(
                    m, None, None, kvs, kind=KV_KIND_STATE, layer=idx,
                    gate={"g": g[:, 0], "beta": beta[:, 0], "conv_w": p["conv_w"]},
                )
            else:
                o, kvs = self._delta_sessions(p, m, g, beta, kvs, t_real, kv_commit)
        o = o.reshape(B, T, self.HV, self.Dv)
        y = rms_norm(o, p["o_norm"], self.eps).astype(u.dtype)
        y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
        return y.reshape(B, T, self.HV * self.Dv) @ p["wo"], kvs

    def _delta_sessions(self, p, m, g, beta, kvs, t_real, kv_commit):
        """The chunked form over each sequence's own entry."""
        impl = gdn_impl()
        outs, new = [], []
        for b in range(m.shape[0]):
            o, st = gdn_prefill(
                {"S": kvs["S"][b], "conv": kvs["conv"][b]}, m[b], p["conv_w"],
                g[b], beta[b], t_real=t_real, impl=impl,
            )
            outs.append(o)
            new.append(st)
        new = jax.tree.map(lambda *xs: jnp.stack(xs), *new)
        if kv_commit is not None:
            new = jax.tree.map(lambda a, b: jnp.where(kv_commit, a, b), new, kvs)
        return jnp.stack(outs), new

    def _attention(self, p, u, kvs, pos, idx, mask, kv_commit, attend_fn):
        """A gated softmax-attention mixer.  `kvs`: this layer's cache
        slices {"k", "v": [B, S, KVH, Hd]}, or with `attend_fn` the store."""
        cfg = self.config
        B, T, _ = u.shape
        Hd = cfg.head_dim
        H = p["wq"].shape[-1] // Hd
        KVH = p["wk"].shape[-1] // Hd
        q = (u @ p["wq"]).reshape(B, T, H, Hd)
        gate = u @ p["w_qgate"]  # [B, T, H * Hd]
        k = (u @ p["wk"]).reshape(B, T, KVH, Hd)
        v = (u @ p["wv"]).reshape(B, T, KVH, Hd)
        q = rms_norm0(q, p["q_norm"], self.eps)
        k = rms_norm0(k, p["k_norm"], self.eps)
        positions = pos + jnp.arange(T)
        q = apply_rope(q, positions, self.inv_freq, self.rope_scale)
        k = apply_rope(k, positions, self.inv_freq, self.rope_scale)
        with jax.named_scope(SCOPE_ATTN_FULL):
            if attend_fn is not None:
                attn, kvs = attend_fn(q, k, v, kvs, kind=KV_KIND_FULL, layer=idx)
            else:
                attn, kvs = cached_attend(
                    q, k, v, kvs, pos, mask, kv_commit=kv_commit, causal=mask is None
                )
        attn = attn.reshape(B, T, H * Hd).astype(jnp.float32)
        out = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(u.dtype)
        return out @ p["wo"], kvs

    def _moe(self, p, x):
        """x + the held experts' part + the gated shared expert; and how
        many of each token's chosen experts are held here [B, T]."""
        from dnet_tpu.ops.moe import (
            held_assignments,
            moe_apply,
            swiglu_expert_closures,
            swiglu_grouped_closure,
        )

        B, T, D = x.shape
        h32 = rms_norm0(x.astype(jnp.float32), p["mlp_norm"], self.eps)
        flat = h32.astype(x.dtype).reshape(B * T, D)
        # the router reads the norm's float32 output: the tenth and the
        # eleventh expert lie close, and a flip moves a whole routed term
        logits = jnp.matmul(
            h32.reshape(B * T, D), p["gate_w"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        scores = jax.nn.softmax(logits, axis=-1)
        k = self.config.num_experts_per_tok
        top_w, top_idx = lax.top_k(scores, k)  # over every routed expert
        if self.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        top_idx = top_idx.astype(jnp.int32)
        effn, dense, E_local = swiglu_expert_closures(
            p, flat, scores, top_idx, top_w, None, offset=self.expert_offset
        )
        out, _ = moe_apply(
            self.moe_impl, flat, top_idx, top_w, effn, E_local,
            self.moe_capacity_factor, k, None, dense,
            offset=self.expert_offset, n_routed=self.n_routed,
            grouped_fn=swiglu_grouped_closure(
                p, flat, top_idx, top_w, offset=self.expert_offset
            ),
        )
        out = out.astype(jnp.float32)
        with jax.named_scope(SCOPE_MOE_SHARED):
            inner = jax.nn.silu(flat @ p["s_gate"]) * (flat @ p["s_up"])
            shared = jnp.matmul(inner, p["s_down"], preferred_element_type=jnp.float32)
            share = jax.nn.sigmoid(
                jnp.matmul(flat, p["sg_w"], preferred_element_type=jnp.float32)
            )  # [N, 1]
            out = out + shared * share
        held = held_assignments(top_idx, self.expert_offset, E_local)
        y = (x.astype(jnp.float32) + out.reshape(B, T, D)).astype(x.dtype)
        return y, held.reshape(B, T)

    # ---- one period -------------------------------------------------------
    def _period(self, pp, x, state, full, pos, period, mask, t_real, kv_commit,
                attend_fn, stacks):
        """`period - 1` delta-rule layers, then one attention layer, an
        expert block after each.  With `attend_fn`, `state` is the caller's
        store, handed from layer to layer (`full` unused); without, they
        are this period's cache slices ({"S", "conv"} [period - 1, B, ...]
        and {"k", "v"} [B, S, ...]).  Returns (x, state, full, how many of
        each token's chosen experts are held [period, B, T])."""
        n_state = self.period - 1
        held, new_state = [], []
        for j in range(self.period):
            is_full = j == n_state
            p = pp["attn"] if is_full else jax.tree.map(lambda a: a[j], pp["gdn"])
            with jax.named_scope(SCOPE_ATTN):
                u = rms_norm0(x, p["attn_norm"], self.eps)
                if attend_fn is not None and is_full:
                    a, state = self._attention(p, u, state, pos, period, None, None, attend_fn)
                elif attend_fn is not None:
                    a, state = self._delta(
                        p, u, state, period * n_state + j, None, None, attend_fn
                    )
                elif is_full:
                    a, full = self._attention(p, u, full, pos, None, mask, kv_commit, None)
                else:
                    a, entry = self._delta(
                        p, u, jax.tree.map(lambda a: a[j], state), None, t_real,
                        kv_commit, None,
                    )
                    new_state.append(entry)
                x = x + a
            mp = jax.tree.map(lambda a: a[j], pp["moe"])
            if stacks is not None:
                mp["e_stack"] = (stacks, period * self.period + j)
            with jax.named_scope(SCOPE_MOE):
                x, h = self._moe(mp, x)
            held.append(h)
        if attend_fn is None:
            state = jax.tree.map(lambda *xs: jnp.stack(xs), *new_state)
        return x, state, full, jnp.stack(held)

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
        attend_fn=None,
    ) -> Tuple[jnp.ndarray, dict]:
        if tp_axis is not None or sp_axis is not None:
            raise NotImplementedError(
                "qwen3_next under tensor or sequence parallelism (its share of "
                "a layer is the expert-parallel one, by config; a state entry "
                "is not sharded over a mesh axis)"
            )
        P = window_params["attn"]["wq"].shape[0]
        periods = jnp.arange(P, dtype=jnp.int32)
        stacks = None
        if self.moe_path(x.shape[0] * x.shape[1]) == "grouped":
            # the grouped kernel reads a layer's experts out of the stack in
            # place (ops/moe.py: grouped_matmul): the scan closes over the
            # stacks [P * period, E, ...] and hands on the layer's index
            stacks = {
                k: window_params["moe"][k].reshape(-1, *window_params["moe"][k].shape[2:])
                for k in EXPERT_KEYS
            }

        if attend_fn is not None:
            # the caller's store rides the carry: each layer's step updates
            # its own slice of the (donated) stacks in place
            def step(carry, per):
                xc, store = carry
                pp, period = per
                xc, store, _, held = self._period(
                    pp, xc, store, None, pos, period, None, None, None,
                    attend_fn, stacks,
                )
                return (xc, store), held

            (x, kv), held = lax.scan(step, (x, kv), (window_params, periods))
            return x, dict(kv, moe_held=held.reshape(-1, *held.shape[2:]))

        n_state = self.period - 1
        state = {
            k: kv[k].reshape(P, n_state, *kv[k].shape[1:]) for k in ("S", "conv")
        }
        full = {k: kv[k] for k in ("k", "v")}

        def body(xc, per):
            pp, st, fl, period = per
            xc, st, fl, _ = self._period(
                pp, xc, st, fl, pos, period, mask, t_real, kv_commit, None, stacks
            )
            return xc, (st, fl)

        x, (state, full) = lax.scan(body, x, (window_params, state, full, periods))
        out = {k: v.reshape(P * n_state, *v.shape[2:]) for k, v in state.items()}
        return x, {**full, **out}

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return rms_norm0(x, edge_params["final_norm"]["weight"], self.eps)

    def lm_project(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        # float32 logits: bfloat16 would round a logit near 5 by up to 0.016
        return super().lm_project(edge_params, x, out_dtype=jnp.float32)

    # ---- weight mapping ---------------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """One layer's HF tensors -> its parameters, tagged by kind under
        "mixer".  `in_proj_qkvz` and `in_proj_ba` are laid out by KEY head
        in the checkpoint (q, k, this head's values, their z; b, a): they
        are un-interleaved ONCE here, into q | k | v (the convolution's
        channel order), z, b and a.  `q_proj` holds each head's q and gate
        side by side: split here too.  Experts under their GLOBAL ids, so a
        share's checkpoint holds `mlp.experts.{expert_offset}` onwards."""

        def t(name: str) -> np.ndarray:
            return np.ascontiguousarray(raw[name].T)  # HF [out,in] -> (in,out)

        def stack(fmt: str, ids) -> np.ndarray:
            return np.stack([t(fmt.format(e)) for e in ids])

        held = range(self.expert_offset, self.expert_offset + self.n_held)
        moe = {
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "gate_w": t("mlp.gate.weight"),  # [D, routed experts]
            "e_gate": stack("mlp.experts.{}.gate_proj.weight", held),
            "e_up": stack("mlp.experts.{}.up_proj.weight", held),
            "e_down": stack("mlp.experts.{}.down_proj.weight", held),
            "s_gate": t("mlp.shared_expert.gate_proj.weight"),
            "s_up": t("mlp.shared_expert.up_proj.weight"),
            "s_down": t("mlp.shared_expert.down_proj.weight"),
            "sg_w": t("mlp.shared_expert_gate.weight"),  # [D, 1]
        }
        norm = raw["input_layernorm.weight"]
        if "linear_attn.in_proj_qkvz.weight" in raw:
            HK, HV, Dk, Dv = self.HK, self.HV, self.Dk, self.Dv
            r = HV // HK
            D = raw["linear_attn.in_proj_qkvz.weight"].shape[1]
            w = raw["linear_attn.in_proj_qkvz.weight"].reshape(HK, 2 * Dk + 2 * r * Dv, D)
            parts = np.split(w, [Dk, 2 * Dk, 2 * Dk + r * Dv], axis=1)
            wq, wk, wv, wz = (a.reshape(-1, D) for a in parts)
            ba = raw["linear_attn.in_proj_ba.weight"].reshape(HK, 2 * r, D)
            mixer = {
                "attn_norm": norm,
                "w_qkv": np.ascontiguousarray(np.concatenate([wq, wk, wv]).T),
                "w_z": np.ascontiguousarray(wz.T),
                "w_b": np.ascontiguousarray(ba[:, :r].reshape(-1, D).T),
                "w_a": np.ascontiguousarray(ba[:, r:].reshape(-1, D).T),
                # depthwise [C, 1, K] -> [K, C]
                "conv_w": np.ascontiguousarray(raw["linear_attn.conv1d.weight"][:, 0, :].T),
                "A_log": raw["linear_attn.A_log"],
                "dt_bias": raw["linear_attn.dt_bias"],
                "o_norm": raw["linear_attn.norm.weight"],
                "wo": t("linear_attn.out_proj.weight"),
            }
            return {"gdn": mixer, "moe": moe}
        Hd = self.config.head_dim
        wq = raw["self_attn.q_proj.weight"]
        D = wq.shape[1]
        wq = wq.reshape(-1, 2, Hd, D)  # per head: q, then gate
        mixer = {
            "attn_norm": norm,
            "wq": np.ascontiguousarray(wq[:, 0].reshape(-1, D).T),
            "w_qgate": np.ascontiguousarray(wq[:, 1].reshape(-1, D).T),
            "wk": t("self_attn.k_proj.weight"),
            "wv": t("self_attn.v_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
            "q_norm": raw["self_attn.q_norm.weight"],
            "k_norm": raw["self_attn.k_norm.weight"],
        }
        return {"attn": mixer, "moe": moe}

    def stack_layers(self, per_layer: List[dict]) -> dict:
        """Whole periods of mapped layers -> {"gdn": [P, period - 1, ...],
        "attn": [P, ...], "moe": [P, period, ...]}."""
        n = self.period
        if not per_layer or len(per_layer) % n:
            raise NotImplementedError(
                f"qwen3_next: {len(per_layer)} layers are not whole periods of {n}"
            )

        def stacked(dicts):
            return {k: np.stack([d[k] for d in dicts]) for k in dicts[0]}

        periods = [per_layer[i:i + n] for i in range(0, len(per_layer), n)]
        for pr in periods:
            if [("attn" in lay) for lay in pr] != [False] * (n - 1) + [True]:
                raise NotImplementedError(
                    "qwen3_next: a window that does not start on a period's edge"
                )
        return {
            "gdn": stacked([stacked([lay["gdn"] for lay in pr[:-1]]) for pr in periods]),
            "attn": stacked([pr[-1]["attn"] for pr in periods]),
            "moe": stacked([stacked([lay["moe"] for lay in pr]) for pr in periods]),
        }

    def wrap_offload_layer(self, mapped):
        raise NotImplementedError(
            "qwen3_next: weights stream a layer at a time, and its parameters "
            "stack by period of layers of two kinds (serve it resident)"
        )
