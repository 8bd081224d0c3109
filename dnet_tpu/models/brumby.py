"""Brumby-family ring model: Qwen3's decoder with softmax attention replaced
by gated power retention of degree 2 (ops/retention.py).

Everything but the attention read is the Qwen3 skeleton (projections,
per-head q/k RMS norm, RoPE, SwiGLU, HF weight names) plus one bias-free
gate projection a layer, `self_attn.g_proj.weight` [KV heads, hidden]:
`log g = logsigmoid(W_g u)`, one gate a KV head.

A sequence's memory is no run of keys and values but ONE state entry a
layer (`S [KVH, R, Hd, Hd]` and `z [KVH, R, Hd]` float32, whatever the
length), so every layer is of the `state` kind (obs/phases.py): `init_kv`
returns state entries (a session's, `[L, B, ...]`, or the store's,
`[L, slots, ...]`), a prefill chunk takes the entry in and hands it on
(`retention_chunk`), and the batched decode step reads, decays, updates and
queries the store in place through `attend_fn(..., kind="state", layer=,
gate=)` (`retention_step`, kv/store.py StateStore).  A state cannot be cut
at a prefix or rewound: no prefix sharing, no speculation.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.models.qwen3 import Qwen3RingModel
from dnet_tpu.obs.phases import KV_KIND_STATE, SCOPE_ATTN, SCOPE_ATTN_STATE
from dnet_tpu.ops.norms import rms_norm
from dnet_tpu.ops.quant import dq, out_dim
from dnet_tpu.ops.retention import init_state, retention_chunk, retention_impl
from dnet_tpu.ops.rope import apply_rope


class BrumbyRingModel(Qwen3RingModel):
    model_type = "brumby"
    supports_paged_attend = True

    def __init__(self, config, layers):
        super().__init__(config, layers)
        self.paged_kinds = (KV_KIND_STATE,) * len(self.layers)

    # ---- the state stands where the cache stood ------------------------
    def init_kv(self, n_layers, batch, max_seq, dtype="bfloat16", quant_bits=0,
                rotating=True) -> dict:
        """State entries, float32 whatever `dtype` says, and no wider for a
        longer `max_seq`: {"S": [L, B, KVH, R, Hd, Hd], "z": [L, B, KVH, R, Hd]}."""
        c = self.config
        return init_state((n_layers, batch), c.num_key_value_heads, c.head_dim)

    def kv_rewindable(self, max_seq: int) -> bool:
        return False  # a state that took a token cannot give it back

    # ---- one layer ------------------------------------------------------
    def _layer(self, p, x, kvs, pos, layer, t_real=None, kv_commit=None, attend_fn=None):
        """`kvs`: this layer's entries {"S": [B, ...], "z": [B, ...]}, or
        with `attend_fn` whatever the hook carries (the store's stack)."""
        cfg = self.config
        B, T, D = x.shape
        Hd = cfg.head_dim
        H = out_dim(p["wq"]) // Hd
        KVH = out_dim(p["wk"]) // Hd
        with jax.named_scope(SCOPE_ATTN):
            h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
            q = (h @ dq(p["wq"])).reshape(B, T, H, Hd)
            k = (h @ dq(p["wk"])).reshape(B, T, KVH, Hd)
            v = (h @ dq(p["wv"])).reshape(B, T, KVH, Hd)
            # the gate's logit stays float32: its log is summed over the
            # tokens a key survives
            log_g = jax.nn.log_sigmoid(
                jnp.matmul(h, p["wg"], preferred_element_type=jnp.float32)
            )  # [B, T, KVH]
            q, k = self._qk_transform(p, q, k)
            positions = pos + jnp.arange(T)
            q = apply_rope(q, positions, self.inv_freq, self.rope_scale)
            k = apply_rope(k, positions, self.inv_freq, self.rope_scale)
            with jax.named_scope(SCOPE_ATTN_STATE):
                if attend_fn is not None:
                    attn, kvs = attend_fn(
                        q, k, v, kvs, kind=KV_KIND_STATE, layer=layer, gate=log_g
                    )
                else:
                    attn, kvs = self._retain(q, k, v, log_g, kvs, t_real, kv_commit)
            x = x + attn.astype(x.dtype).reshape(B, T, H * Hd) @ dq(p["wo"])
        return self._mlp_block(p, x), kvs

    def _retain(self, q, k, v, log_g, kvs, t_real, kv_commit):
        """The chunked form over each sequence's own entry.  One token (a
        decode step outside the store) goes through `jax.numpy`: the chunk
        kernel is a prefill kernel."""
        B, T = q.shape[:2]
        impl = "emulate" if T == 1 else retention_impl()
        valid = None if t_real is None else jnp.arange(T) < t_real
        outs, S, z = [], [], []
        for b in range(B):
            o, st = retention_chunk(
                {"S": kvs["S"][b], "z": kvs["z"][b]}, q[b], k[b], v[b], log_g[b],
                valid=valid, impl=impl,
            )
            outs.append(o)
            S.append(st["S"])
            z.append(st["z"])
        new = {"S": jnp.stack(S), "z": jnp.stack(z)}
        if kv_commit is not None:
            new = jax.tree.map(lambda a, b: jnp.where(kv_commit, a, b), new, kvs)
        return jnp.stack(outs), new

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
                "brumby: a state entry is not sharded over a mesh axis yet"
            )
        L = window_params["wq"].shape[0]
        layers = jnp.arange(L, dtype=jnp.int32)
        if attend_fn is not None:
            # the store rides the carry: each layer's step updates its own
            # slice of the (donated) stack in place
            def step(carry, per_layer):
                xc, store = carry
                p, layer = per_layer
                return self._layer(p, xc, store, pos, layer, attend_fn=attend_fn), None

            (x, kv), _ = lax.scan(step, (x, kv), (window_params, layers))
            return x, kv

        def body(xc, per_layer):
            p, kvs, layer = per_layer
            return self._layer(p, xc, kvs, pos, layer, t_real=t_real, kv_commit=kv_commit)

        return lax.scan(body, x, (window_params, kv, layers))

    # ---- weight mapping -------------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        params = super().map_layer(raw)
        params["wg"] = np.ascontiguousarray(raw["self_attn.g_proj.weight"].T)
        return params
