"""MiniCPM-SALA ring model (`model_type` minicpm_sala): block-sparse
softmax-attention layers among lightning linear-attention layers, a dense
SwiGLU after each, on MiniCPM's muP trunk.

`mixer_types` names each layer's mixer:

- `minicpm4` (ops/sparse_attention.py; InfLLM-V2 as MiniCPM4 publishes
  it): q of `num_attention_heads`, k and v of `num_key_value_heads`, an
  RMSNorm a head on q and k, NO position embedding, scale 1 / sqrt(Hd).
  Up to `dense_len` tokens of context a query attends everything before
  it; past that, `topk` blocks of `block_size` tokens: the first, the
  window's, and the best by an index of pooled keys, a KV head's choice
  shared by its query heads.  Output `W_o (o * sigmoid(W_g x))`.  Such a
  layer is of the `full` kind: blocks of a pool, and beside the keys a
  pooled-key leaf `kc`, one row for `kernel_stride` tokens.
- `lightning-attn` (ops/lightning.py): q, k, v of `lightning_nh` heads, an
  RMSNorm a head on q and k, then RoPE over the whole head, a state
  `S [D, D]` float32 a head that decays by a fixed factor a token.  Output
  `W_o (RMSNorm(concat o) * sigmoid(W_g x))`.  The `state` kind: one entry
  a lane, no blocks.

The trunk: `h0 = scale_emb E[token]`, a block adds `s` times its mixer's
and then its MLP's output with `s = scale_depth / sqrt(num_hidden_layers)`
of the config AS SERVED (HF computes it so: a checkpoint of 32 layers
loads with its own `s`, a depth cut serves its own), and the head reads
`RMSNorm(h) / (hidden_size / dim_model_base)`.

The two mixers have different PARAMETERS, so the stack is a `lax.scan`
over PERIODS of `mixer_types` (its smallest repeating unit: the whole list
where nothing repeats, one period of `num_hidden_layers` unrolled layers):
`sparse [P, ns, ...]`, `light [P, nl, ...]`, `mlp [P, period, ...]`.  A
window of layers must be whole periods.  Tensor or sequence parallelism
and weight quantisation are not served.

`paged_kinds` follows `mixer_types`: one sequence holds a lane of state AND
a block table, through kv/store.py HybridStore.  Under its `attend_fn` the
store rides the scan's carry: `attend_fn(q, k, v, store, kind="full",
layer=)` for a sparse layer (the store writes the row, extends the index,
chooses and reads), `attend_fn(q, k, v, store, kind="state", layer=)` for a
lightning layer.
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
    SCOPE_ATTN_STATE,
)
from dnet_tpu.ops.lightning import lightning_chunk, lightning_impl
from dnet_tpu.ops.norms import rms_norm
from dnet_tpu.ops.rope import apply_rope, rope_frequencies
from dnet_tpu.ops.sparse_attention import SparseConfig, sparse_impl, sparse_prefill

MIXER_SPARSE = "minicpm4"
MIXER_LIGHT = "lightning-attn"
#: tokens a prefill program carries through the stack at once (the
#: scheduler's default chunk); a wider program loops over slabs of it
PREFILL_SLAB = 2048


def _period_of(mixers: Tuple[str, ...]) -> int:
    """The smallest p that divides len(mixers) with mixers p-periodic."""
    n = len(mixers)
    return next(
        p for p in range(1, n + 1)
        if n % p == 0 and all(mixers[i] == mixers[i % p] for i in range(n))
    )


class MiniCPMSALARingModel(RingModel):
    model_type = "minicpm_sala"
    supports_paged_attend = True
    supports_weight_quant = False  # the period stacks are not quantize_tree's layout
    state_family = "lightning"

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        x = config.extra
        mixers = tuple(x["mixer_types"])
        if len(mixers) != config.num_hidden_layers or set(mixers) - {MIXER_SPARSE, MIXER_LIGHT}:
            raise ValueError(
                f"minicpm_sala: mixer_types must name {config.num_hidden_layers} layers, "
                f"each {MIXER_SPARSE} or {MIXER_LIGHT}"
            )
        refused = [
            why for bad, why in (
                (config.attention_bias, "attention biases"),
                (x.get("attn_use_rope", False), "RoPE in the sparse layers (attn_use_rope)"),
                (not x.get("lightning_use_rope", True), "lightning layers without RoPE"),
                (not x.get("qk_norm", True), "layers without the q / k norm"),
                (not x.get("use_output_gate", True) or not x.get("attn_use_output_gate", True),
                 "a mixer without its output gate"),
                (not x.get("use_output_norm", True), "lightning layers without the output norm"),
                (x.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)", "another lightning_scale"),
                (config.rope_scaling, "rope_scaling"),
            ) if bad
        ]
        if refused:
            raise NotImplementedError(f"minicpm_sala: not implemented: {', '.join(refused)}")
        self.mixers = mixers
        P = self.period = _period_of(mixers)
        if (
            not self.layers
            or self.layers[0] % P
            or len(self.layers) % P
            or self.layers != list(range(self.layers[0], self.layers[0] + len(self.layers)))
        ):
            raise NotImplementedError(
                f"minicpm_sala: layers {self.layers[:1]}..{self.layers[-1:]} are not "
                f"whole periods of {P} (its parameters stack by period of mixer_types)"
            )
        self.n_periods = len(self.layers) // P
        unit = mixers[:P]
        #: per layer of a period: (is it sparse, its index within its kind)
        self.unit = tuple(
            (m == MIXER_SPARSE, sum(1 for u in unit[:j] if u == m)) for j, m in enumerate(unit)
        )
        self.ns = sum(1 for m in unit if m == MIXER_SPARSE)
        self.nl = P - self.ns
        if not self.ns or not self.nl:
            raise NotImplementedError(
                "minicpm_sala: a model of one mixer alone (the hybrid store holds both kinds)"
            )
        self.eps = config.rms_norm_eps
        self.LH = int(x.get("lightning_nh", config.num_attention_heads))
        self.LD = int(x.get("lightning_head_dim", config.head_dim))
        if int(x.get("lightning_nkv", self.LH)) != self.LH:
            raise NotImplementedError("minicpm_sala: lightning_nkv != lightning_nh")
        self.sparse = SparseConfig.from_hf(x.get("sparse_config"))
        # muP: the embedding's, the residual branches' and the head's scalings
        self.scale_emb = float(x.get("scale_emb", 1.0))
        self.residual_scale = float(x.get("scale_depth", 1.0)) / float(
            np.sqrt(config.num_hidden_layers)
        )
        self.head_divisor = config.hidden_size / float(x.get("dim_model_base", config.hidden_size))
        inv_freq, self.rope_scale = rope_frequencies(
            self.LD, config.rope_theta, None, config.max_position_embeddings
        )
        self.inv_freq = jnp.asarray(inv_freq)
        self.paged_kinds = tuple(
            KV_KIND_FULL if self.mixers[a] == MIXER_SPARSE else KV_KIND_STATE
            for a in self.layers
        )

    # ---- cache construction --------------------------------------------
    def pool_leaves(self) -> Dict[str, Tuple[int, int]]:
        """Keys, values, and the pooled keys `kc`: the index, whose rows
        are not tokens (`pool_strides`)."""
        c = self.config
        heads = (c.num_key_value_heads, c.head_dim)
        return {"k": heads, "v": heads, "kc": heads}

    def pool_strides(self) -> Dict[str, int]:
        """Tokens a row of a pool leaf stands for, where that is not one."""
        return {"kc": self.sparse.kernel_stride}

    def init_kv(self, n_layers, batch, max_seq, dtype="bfloat16", quant_bits=0,
                rotating=True) -> dict:
        """The sparse layers' slot-addressed rows and the lightning layers'
        entries side by side: {"k", "v": [Ns, B, max_seq, KVH, Hd], "S":
        [Nl, B, LH, LD, LD] float32}.  (A staged row keeps no pooled keys:
        a chunk pools its row's keys as it goes, and adoption commits them.)"""
        if quant_bits:
            raise NotImplementedError("minicpm_sala: a quantized KV cache")
        c = self.config
        P = n_layers // self.period
        dt = jnp.dtype(dtype)
        row = (P * self.ns, batch, max_seq, c.num_key_value_heads, c.head_dim)
        return {
            "k": jnp.zeros(row, dt),
            "v": jnp.zeros(row, dt),
            "S": jnp.zeros((P * self.nl, batch, self.LH, self.LD, self.LD), jnp.float32),
        }

    def kv_rewindable(self, max_seq: int) -> bool:
        return False  # a state that took a token cannot give it back

    def flash_layers(self) -> Tuple[Tuple[str, int], ...]:
        return ()  # its full layers attend through ops/sparse_attention.py

    # ---- the trunk's edges -----------------------------------------------
    def embed(self, edge_params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
        x = super().embed(edge_params, tokens)
        return (x.astype(jnp.float32) * self.scale_emb).astype(x.dtype)

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        h = rms_norm(x, edge_params["final_norm"]["weight"], self.eps)
        return (h.astype(jnp.float32) / self.head_divisor).astype(x.dtype)

    def lm_project(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return super().lm_project(edge_params, x, out_dtype=jnp.float32)

    # ---- the mixers -------------------------------------------------------
    def _sparse_mixer(self, p, u, kvs, pos, idx, kv_commit, attend_fn):
        """A `minicpm4` mixer.  `kvs`: this layer's cache slices {"k", "v":
        [B, S, KVH, Hd]}, or with `attend_fn` the store."""
        cfg = self.config
        B, T, _ = u.shape
        Hd = cfg.head_dim
        q = (u @ p["wq"]).reshape(B, T, -1, Hd)
        k = (u @ p["wk"]).reshape(B, T, -1, Hd)
        v = (u @ p["wv"]).reshape(B, T, -1, Hd)
        gate = u @ p["w_og"]
        q = rms_norm(q, p["q_norm"], self.eps)
        k = rms_norm(k, p["k_norm"], self.eps)
        if attend_fn is not None:
            attn, kvs = attend_fn(q, k, v, kvs, kind=KV_KIND_FULL, layer=idx)
        else:
            from dnet_tpu.core.kvcache import write_kv

            kvs = write_kv(kvs, k, v, pos, kv_commit)
            # one token (a decode step outside the store) goes through
            # `jax.numpy`: the kernels are a chunk's
            impl = "emulate" if T < 8 else sparse_impl()
            attn = jnp.stack([
                sparse_prefill(q[b], kvs["k"][b], kvs["v"][b], pos, self.sparse, impl=impl)
                for b in range(B)
            ])
        attn = attn.reshape(B, T, -1).astype(jnp.float32)
        out = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(u.dtype)
        return out @ p["wo"], kvs

    def _light_mixer(self, p, u, kvs, pos, idx, t_real, kv_commit, attend_fn):
        """A `lightning-attn` mixer.  `kvs`: this layer's entries {"S":
        [B, LH, LD, LD]}, or with `attend_fn` the store."""
        B, T, _ = u.shape
        H, D = self.LH, self.LD
        q = (u @ p["wq"]).reshape(B, T, H, D)
        k = (u @ p["wk"]).reshape(B, T, H, D)
        v = (u @ p["wv"]).reshape(B, T, H, D)
        gate = u @ p["w_og"]
        q = rms_norm(q, p["q_norm"], self.eps)
        k = rms_norm(k, p["k_norm"], self.eps)
        positions = pos + jnp.arange(T)
        q = apply_rope(q, positions, self.inv_freq, self.rope_scale)
        k = apply_rope(k, positions, self.inv_freq, self.rope_scale)
        with jax.named_scope(SCOPE_ATTN_STATE):
            if attend_fn is not None:
                o, kvs = attend_fn(q, k, v, kvs, kind=KV_KIND_STATE, layer=idx)
            else:
                impl = "emulate" if T == 1 else lightning_impl()
                valid = None if t_real is None else jnp.arange(T) < t_real
                outs, new = [], []
                for b in range(B):
                    o_b, S_b = lightning_chunk(kvs["S"][b], q[b], k[b], v[b], valid=valid, impl=impl)
                    outs.append(o_b)
                    new.append(S_b)
                o, S = jnp.stack(outs), jnp.stack(new)
                if kv_commit is not None:
                    S = jnp.where(kv_commit, S, kvs["S"])
                kvs = {"S": S}
        y = rms_norm(o.reshape(B, T, H * D), p["o_norm"], self.eps).astype(u.dtype)
        y = (y.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(u.dtype)
        return y @ p["wo"], kvs

    def _mlp(self, p, x):
        h = rms_norm(x, p["mlp_norm"], self.eps)
        return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    def _add(self, x, branch):
        return (x.astype(jnp.float32) + self.residual_scale * branch.astype(jnp.float32)).astype(x.dtype)

    # ---- one period -------------------------------------------------------
    @staticmethod
    def _by_layer(window_params: dict) -> dict:
        """{"sparse": [P, ns, ...], ...} -> {"sparse": [P * ns, ...], ...}
        (a reshape of contiguous stacks: nothing moves)."""
        return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), window_params)

    def _period(self, flat, x, store, full, state, pos, period, t_real, kv_commit, attend_fn):
        """The period's layers in `mixer_types`' order, an MLP after each.
        `flat`: the window's parameters by layer within its kind
        (`_by_layer`), of which each layer takes its own with ONE dynamic
        index.  With `attend_fn`, `store` is the caller's, handed from layer
        to layer; without, `full` {"k", "v": [ns, B, S, ...]} and `state`
        {"S": [nl, B, ...]} are this period's cache slices."""

        def own(kind, n, at):
            return jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, period * n + at, 0, keepdims=False),
                flat[kind],
            )

        new_full, new_state = [], []
        for j, (is_sparse, at) in enumerate(self.unit):
            p = own("sparse", self.ns, at) if is_sparse else own("light", self.nl, at)
            with jax.named_scope(SCOPE_ATTN):
                u = rms_norm(x, p["attn_norm"], self.eps)
                if attend_fn is not None and is_sparse:
                    a, store = self._sparse_mixer(
                        p, u, store, pos, period * self.ns + at, None, attend_fn
                    )
                elif attend_fn is not None:
                    a, store = self._light_mixer(
                        p, u, store, pos, period * self.nl + at, None, None, attend_fn
                    )
                elif is_sparse:
                    a, kvs = self._sparse_mixer(
                        p, u, jax.tree.map(lambda c: c[at], full), pos, None, kv_commit, None
                    )
                    new_full.append(kvs)
                else:
                    a, kvs = self._light_mixer(
                        p, u, jax.tree.map(lambda c: c[at], state), pos, None, t_real,
                        kv_commit, None,
                    )
                    new_state.append(kvs)
                x = self._add(x, a)
            x = self._add(x, self._mlp(own("mlp", self.period, j), x))
        if attend_fn is None:
            full = jax.tree.map(lambda *xs: jnp.stack(xs), *new_full)
            state = jax.tree.map(lambda *xs: jnp.stack(xs), *new_state)
        return x, store, full, state

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
                "minicpm_sala under tensor or sequence parallelism (a KV head's "
                "choice of blocks and its index leaf are not sharded over a mesh "
                "axis, nor is a state entry)"
            )
        if mask is not None:
            raise NotImplementedError("minicpm_sala: a caller's mask (the rule is causal)")
        P = window_params["mlp"]["mlp_norm"].shape[0]
        periods = jnp.arange(P, dtype=jnp.int32)
        # the scans close over the stacks and carry the PERIOD's index: a
        # layer takes its matrices out of [P * n, ...] with one dynamic
        # index, which its matmul reads in place.  (Handed the period's
        # slice [n, ...] by the scan and indexed [j] after, XLA copies the
        # whole period's weights, 2.2 GB, every iteration: 29 % of this
        # model's busy time on the chip before this.)
        flat = self._by_layer(window_params)

        if attend_fn is not None:
            # the caller's store rides the carry: each layer's step updates
            # its own slice of the (donated) stacks in place
            def step(carry, period):
                xc, store = carry
                xc, store, _, _ = self._period(
                    flat, xc, store, None, None, pos, period, None, None, attend_fn
                )
                return (xc, store), None

            (x, kv), _ = lax.scan(step, (x, kv), periods)
            return x, kv

        full = {k: kv[k].reshape(P, self.ns, *kv[k].shape[1:]) for k in ("k", "v")}
        state = {"S": kv["S"].reshape(P, self.nl, *kv["S"].shape[1:])}

        def stack(xs, full, state, at, real):
            """The window's periods over the tokens `xs` at position `at`."""

            def body(xc, per):
                fl, st, period = per
                xc, _, fl, st = self._period(
                    flat, xc, None, fl, st, at, period, real, kv_commit, None
                )
                return xc, (fl, st)

            return lax.scan(body, xs, (full, state, periods))

        B, T, D = x.shape
        if T > PREFILL_SLAB and T % PREFILL_SLAB == 0:
            # a program wider than a tick's chunk (a one-shot prefill: the
            # load's warm-up of the step's table widths) goes through the
            # stack a SLAB of tokens at a time, the caches carried: what it
            # holds at once is a chunk's activations whatever its width
            n = T // PREFILL_SLAB
            slabs = jnp.moveaxis(x.reshape(B, n, PREFILL_SLAB, D), 1, 0)

            def slab(carry, per):
                xs, i = per
                real = None if t_real is None else jnp.clip(
                    t_real - i * PREFILL_SLAB, 0, PREFILL_SLAB
                )
                xs, carry = stack(xs, *carry, pos + i * PREFILL_SLAB, real)
                return carry, xs

            (full, state), out = lax.scan(
                slab, (full, state), (slabs, jnp.arange(n, dtype=jnp.int32))
            )
            x = jnp.moveaxis(out, 0, 1).reshape(B, T, D)
        else:
            x, (full, state) = stack(x, full, state, pos, t_real)
        out = {k: v.reshape(-1, *v.shape[2:]) for k, v in {**full, **state}.items()}
        return x, out

    # ---- weight mapping ---------------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """One layer's HF tensors -> its parameters, tagged by mixer (a
        lightning layer is known by its output norm)."""

        def t(name: str) -> np.ndarray:
            return np.ascontiguousarray(raw[name].T)  # HF [out,in] -> (in,out)

        mlp = {
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "w_gate": t("mlp.gate_proj.weight"),
            "w_up": t("mlp.up_proj.weight"),
            "w_down": t("mlp.down_proj.weight"),
        }
        mixer = {
            "attn_norm": raw["input_layernorm.weight"],
            "wq": t("self_attn.q_proj.weight"),
            "wk": t("self_attn.k_proj.weight"),
            "wv": t("self_attn.v_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
            "q_norm": raw["self_attn.q_norm.weight"],
            "k_norm": raw["self_attn.k_norm.weight"],
        }
        if "self_attn.o_norm.weight" in raw:
            mixer["w_og"] = t("self_attn.z_proj.weight")
            mixer["o_norm"] = raw["self_attn.o_norm.weight"]
            return {"light": mixer, "mlp": mlp}
        mixer["w_og"] = t("self_attn.o_gate.weight")
        return {"sparse": mixer, "mlp": mlp}

    def stack_layers(self, per_layer: List[dict]) -> dict:
        """Whole periods of mapped layers -> {"sparse": [P, ns, ...],
        "light": [P, nl, ...], "mlp": [P, period, ...]}."""
        n = self.period
        if not per_layer or len(per_layer) % n:
            raise NotImplementedError(
                f"minicpm_sala: {len(per_layer)} layers are not whole periods of {n}"
            )

        def stacked(dicts):
            return {k: np.stack([d[k] for d in dicts]) for k in dicts[0]}

        periods = [per_layer[i:i + n] for i in range(0, len(per_layer), n)]
        want = [is_sparse for is_sparse, _ in self.unit]
        for pr in periods:
            if [("sparse" in lay) for lay in pr] != want:
                raise NotImplementedError(
                    "minicpm_sala: the checkpoint's layers are not of the mixers "
                    "mixer_types names (or the window does not start on a period's edge)"
                )
        return {
            "sparse": stacked([stacked([l["sparse"] for l in pr if "sparse" in l]) for pr in periods]),
            "light": stacked([stacked([l["light"] for l in pr if "light" in l]) for pr in periods]),
            "mlp": stacked([stacked([l["mlp"] for l in pr]) for pr in periods]),
        }

    def wrap_offload_layer(self, mapped):
        raise NotImplementedError(
            "minicpm_sala: weights stream a layer at a time, and its parameters "
            "stack by period of layers of two kinds (serve it resident)"
        )
