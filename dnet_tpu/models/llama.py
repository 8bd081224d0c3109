"""Llama-family ring model (Llama 2/3.x, Hermes, etc.).

TPU-first re-design of the reference's `LlamaRingModel`
(src/dnet/core/models/llama.py:41-117): layers are stacked along a leading
axis and applied with one `lax.scan` per window (one XLA program per window
size, MXU-sized matmuls), weights live as (in, out)-oriented matrices so the
hot path is `x @ W` with no transposes, and rotary tables are closed over as
constants.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.models.base import ModelConfig, RingModel
from dnet_tpu.obs.phases import SCOPE_ATTN
from dnet_tpu.ops.attention import cached_attend
from dnet_tpu.parallel.tp_collectives import tp_all_reduce
from dnet_tpu.ops.norms import rms_norm
from dnet_tpu.ops.quant import dq, lead_dim, out_dim
from dnet_tpu.ops.rope import apply_rope, rope_frequencies


class LlamaRingModel(RingModel):
    model_type = "llama"
    # the standard norm->qkv->rope->cached_attend->o-proj layer body: the
    # attention half swaps cleanly for the ragged paged program
    supports_paged_attend = True

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        inv_freq, self.rope_scale = rope_frequencies(
            config.head_dim,
            config.rope_theta,
            config.rope_scaling,
            config.max_position_embeddings,
        )
        self.inv_freq = jnp.asarray(inv_freq)

    # ---- pure compute (embed/lm_project inherited quant-aware) ---------
    def _qk_transform(self, p: dict, q: jnp.ndarray, k: jnp.ndarray):
        """Pre-RoPE q/k hook; identity for llama (qwen3 adds per-head norms)."""
        return q, k

    def _layer(self, p: dict, x: jnp.ndarray, kvs: dict, pos, mask, tp_axis=None, kv_commit=None, sp_axis=None, attend_fn=None):
        """One decoder layer.  Works on full params or tensor-parallel slices:
        local head counts come from the (possibly sharded) param shapes, and
        `tp_axis` inserts the two Megatron-style psums (after o-proj and
        down-proj) when running inside shard_map.  kv_commit (scalar bool)
        gates the cache write O(T)-cheaply — a pipeline rank processing a
        not-its-turn copy must not pollute its cache.  kvs is this layer's
        cache-slice dict (may carry int8/int4 quant scales).  sp_axis: the
        KV sequence axis is sharded over this mesh axis (ring attention /
        distributed flash-decoding); `mask` is then the [T, S_local]
        validity mask against this rank's shard."""
        cfg = self.config
        B, T, D = x.shape
        Hd = cfg.head_dim
        H = out_dim(p["wq"]) // Hd  # local heads (== cfg heads / tp)
        KVH = out_dim(p["wk"]) // Hd

        with jax.named_scope(SCOPE_ATTN):
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            # qkv biases are present only for families that ship them (qwen2);
            # the per-family param dict is homogeneous so `in p` is static
            q = h @ dq(p["wq"])
            k = h @ dq(p["wk"])
            v = h @ dq(p["wv"])
            if "bq" in p:
                q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
            q = q.reshape(B, T, H, Hd)
            k = k.reshape(B, T, KVH, Hd)
            v = v.reshape(B, T, KVH, Hd)
            q, k = self._qk_transform(p, q, k)  # subclass hook (qwen3 q/k norms)
            positions = pos + jnp.arange(T)
            q = apply_rope(q, positions, self.inv_freq, self.rope_scale)
            k = apply_rope(k, positions, self.inv_freq, self.rope_scale)
            if attend_fn is not None:
                # ragged paged attention (ops/paged_attention.py): the caller
                # owns both the cache write (block append) and the attention
                # read, and its pool; where a cache slice would ride, the scan
                # carries the layer's index (apply_window), and stacks what
                # the hook returns
                attn, kvs = attend_fn(q, k, v, None, layer=kvs)
            else:
                attn, kvs = cached_attend(
                    q, k, v, kvs, pos, mask, kv_commit=kv_commit, sp_axis=sp_axis,
                    causal=mask is None,
                )
            attn_out = attn.reshape(B, T, H * Hd) @ dq(p["wo"])
            if tp_axis is not None:
                # out-proj all-reduce: THE first of the two per-layer TP
                # collectives, routed through the quantizable seam (exact
                # psum for plain string axes, parallel/tp_collectives.py)
                attn_out = tp_all_reduce(attn_out, tp_axis)
            x = x + attn_out

        x = self._mlp_block(p, x, tp_axis)
        return x, kvs

    def _mlp_block(self, p: dict, x: jnp.ndarray, tp_axis=None) -> jnp.ndarray:
        """Post-attention FFN incl. the residual add; subclass hook (mixtral
        swaps in the sparse-MoE block)."""
        h = rms_norm(x, p["mlp_norm"], self.config.rms_norm_eps)
        gate = h @ dq(p["w_gate"])
        up = h @ dq(p["w_up"])
        mlp_out = (jax.nn.silu(gate) * up) @ dq(p["w_down"])
        if tp_axis is not None:
            # down-proj all-reduce: the second per-layer TP collective
            mlp_out = tp_all_reduce(mlp_out, tp_axis)
        return x + mlp_out

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
        t_real=None,  # full-length caches overwrite padding before reading
        attend_fn=None,
    ) -> Tuple[jnp.ndarray, dict]:
        # the causal predicate stays implicit (mask=None) under sp too:
        # cached_attend owns the rank-local sp mask (or the TPU split-K
        # flash-decode partials) — pre-building sp_causal_mask here would
        # make the kernel path unreachable

        def body(carry, per_layer):
            xc = carry
            p, kvs = per_layer
            xc, kvs = self._layer(
                p, xc, kvs, pos, mask, tp_axis=tp_axis, kv_commit=kv_commit,
                sp_axis=sp_axis, attend_fn=attend_fn,
            )
            return xc, kvs

        if attend_fn is not None:
            # the pool is the hook's own, closed over and never sliced: the
            # scan carries each layer's index in its place
            kv = jnp.arange(lead_dim(window_params["wq"]), dtype=jnp.int32)
        stacks = None
        if tp_axis is None and self.moe_path(x.shape[0] * x.shape[1]) == "grouped":
            from dnet_tpu.ops.moe import expert_stacks

            stacks = expert_stacks(window_params)
        if stacks is None:
            x, kv_out = lax.scan(body, x, (window_params, kv))
            return x, kv_out

        # routed experts grouped: the kernel reads each layer's experts out
        # of the stack in place (ops/moe.py: grouped_matmul), so the scan
        # closes over the stacks and carries the layer's index beside its
        # other parameters (their per-layer slices go unused, and away)
        def body_stacked(carry, per_layer):
            p, kvs, layer = per_layer
            return body(carry, ({**p, "e_stack": (stacks, layer)}, kvs))

        layers = jnp.arange(stacks["e_gate"].shape[0], dtype=jnp.int32)
        return lax.scan(body_stacked, x, (window_params, kv, layers))

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return rms_norm(x, edge_params["final_norm"]["weight"], self.config.rms_norm_eps)

    # ---- weight mapping ----------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        def t(name: str) -> np.ndarray:
            return np.ascontiguousarray(raw[name].T)  # HF [out,in] -> (in,out)

        out = {
            "attn_norm": raw["input_layernorm.weight"],
            "wq": t("self_attn.q_proj.weight"),
            "wk": t("self_attn.k_proj.weight"),
            "wv": t("self_attn.v_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "w_gate": t("mlp.gate_proj.weight"),
            "w_up": t("mlp.up_proj.weight"),
            "w_down": t("mlp.down_proj.weight"),
        }
        # keyed on checkpoint CONTENTS, not family: llama checkpoints with
        # attention_bias=true and qwen2/2.5 both ship qkv biases
        if "self_attn.q_proj.bias" in raw:
            out["bq"] = raw["self_attn.q_proj.bias"]
            out["bk"] = raw["self_attn.k_proj.bias"]
            out["bv"] = raw["self_attn.v_proj.bias"]
        return out

