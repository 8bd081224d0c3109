"""Cohere2-MoE ring model (Command A+ class, `model_type` cohere2_moe).

Language model only (a checkpoint's vision tower is not served).  What the
family asks of a decoder stack, every piece of it from `config.json`:

- a PARALLEL block: one mean-subtracting, weight-only LayerNorm feeds the
  attention AND the expert layer, and both are added to the residual
  (`use_parallel_block`);
- window and full layers mixed (`layer_types`, three `sliding_attention`
  to one `full_attention`): a window layer rotates q and k by interleaved
  ("gptj") pairs over the whole head and attends the last
  `sliding_window` keys; a full layer has NO position embedding and
  attends everything before it.  No q/k norm, no biases;
- an expert layer routed by SIGMOID scores (`expert_selection_fn`): top-k
  of the scores, normalised over the chosen ones (`norm_topk_prob`), plus
  `num_shared_experts` always-on experts whose outputs are averaged
  (`shared_expert_combination_strategy`), stored here as one SwiGLU of
  `num_shared_experts` x the width (a sum of SwiGLUs is the SwiGLU of the
  concatenated columns) scaled by 1 / `num_shared_experts`;
- a tied embedding, logits scaled by `logit_scale`.

The expert SHARE comes from the config too: `num_experts` experts are held
here, the contiguous range starting at `expert_offset` of the
`num_experts_routed` the router scores (both default to "all of them").
Routing, top-k and normalisation run over every routed expert; the layer
returns its own experts' part plus the shared experts' term (ops/moe.py).
Nothing stands in for the experts held elsewhere.

A projection whose output is split by head (q, k, v) is STORED heads-first,
`[L, heads, head_dim, D]`: HF's own `[out, in]` split by head, contracted
over its last axis straight to `[B, T, heads, head_dim]`.  The v5e compiler
keeps q head-major (the rope and the Mosaic call behind it want the heads
apart) and so wants the weight with D minor: stored `[L, D, heads*head_dim]`
every program slices the layer's matrix out of the stack and copies it into
that layout before each use (wq: 128 MB a layer, PERF.md section 6, PR 52).
The quantised form keeps `[L, D, out]` (ops/quant.py: groups along D, a
scale an output row) and its contraction: dequantisation writes a temporary
anyway.

One `lax.scan` over the stacked layers with the layer's kind riding as
data.  Over a slot-addressed cache both kinds read one flat
[L, B, S, ...] cache (the window is the kernel's lower bound,
ops/flash_attention.py); under the ragged paged pool the caller's
`attend_fn` is told the kind and the layer's index within its kind, since
each kind has a pool and block tables of its own (kv/store.py KindStore).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.models.base import ModelConfig, RingModel
from dnet_tpu.obs.phases import (
    KV_KIND_FULL,
    KV_KIND_WINDOW,
    KV_KINDS,
    SCOPE_ATTN,
    SCOPE_ATTN_FULL,
    SCOPE_ATTN_WINDOW,
    SCOPE_MOE,
    SCOPE_MOE_SHARED,
)
from dnet_tpu.ops.attention import attend, sliding_window_mask
from dnet_tpu.ops.norms import layer_norm
from dnet_tpu.ops.quant import dq, is_quantized, lead_dim
from dnet_tpu.ops.rope import apply_rope_interleaved, rope_frequencies

# a layer's kind rides the scan as data: its index in obs/phases.py KV_KINDS,
# the one encoding the pools, the gauges' labels and the kernels' names share
KIND_FULL, KIND_WINDOW = (KV_KINDS.index(k) for k in (KV_KIND_FULL, KV_KIND_WINDOW))
# the projections whose output is split by head: stored heads-first
BY_HEAD = ("wq", "wk", "wv")


class Cohere2MoeRingModel(RingModel):
    model_type = "cohere2_moe"
    supports_paged_attend = True
    #: under an `attend_fn` the scan stacks each lane's count of chosen
    #: experts held here as `moe_held` (dnet_moe_assignments_total)
    reports_moe_held = True
    moe_grouped = True
    quant_keys = frozenset(
        {"wq", "wk", "wv", "wo", "e_gate", "e_up", "e_down",
         "s_gate", "s_up", "s_down"}
    )  # the router stays float: routing decisions are precision-sensitive

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        x = config.extra
        refused = [
            why for bad, why in (
                (x.get("use_qk_norm"), "use_qk_norm"),
                (x.get("first_k_dense_replace"), "leading dense layers"),
                (not x.get("use_parallel_block", True), "a sequential block"),
                (x.get("position_embedding_type", "rope_gptj") != "rope_gptj",
                 f"position_embedding_type {x.get('position_embedding_type')!r}"),
                (x.get("rotary_pct", 1) != 1, "partial rotary"),
                (config.attention_bias, "attention biases"),
                (x.get("expert_selection_fn", "sigmoid") not in ("sigmoid", "softmax"),
                 f"expert_selection_fn {x.get('expert_selection_fn')!r}"),
                (x.get("shared_expert_combination_strategy", "average")
                 not in ("average", "sum"),
                 "shared_expert_combination_strategy "
                 f"{x.get('shared_expert_combination_strategy')!r}"),
            ) if bad
        ]
        if refused:
            raise NotImplementedError(
                f"cohere2_moe: not implemented: {', '.join(refused)}"
            )
        self.eps = float(x.get("layer_norm_eps") or 1e-5)
        self.logit_scale = float(x.get("logit_scale", 1.0))
        self.sigmoid = x.get("expert_selection_fn", "sigmoid") == "sigmoid"
        self.norm_topk_prob = bool(x.get("norm_topk_prob", True))
        self.n_shared = int(x.get("num_shared_experts", 0))
        self.shared_scale = (
            1.0 / self.n_shared
            if self.n_shared
            and x.get("shared_expert_combination_strategy", "average") == "average"
            else 1.0
        )
        # the share: `num_experts` held of `num_experts_routed`, from
        # `expert_offset` on (all of them unless the config says otherwise)
        self.n_held = config.num_local_experts
        self.n_routed = int(x.get("num_experts_routed") or self.n_held)
        self.expert_offset = int(x.get("expert_offset", 0))
        if not 0 <= self.expert_offset <= self.n_routed - self.n_held:
            raise ValueError(
                f"cohere2_moe: experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_held}) lie outside the "
                f"router's {self.n_routed}"
            )
        inv_freq, self.rope_scale = rope_frequencies(
            config.head_dim, config.rope_theta, config.rope_scaling,
            config.max_position_embeddings,
        )
        self.inv_freq = jnp.asarray(inv_freq)
        types = config.layer_types or ["full_attention"] * config.num_hidden_layers
        self.window = int(config.sliding_window or 0)
        # a `sliding_attention` layer rotates q and k; it is bounded below
        # where the config gives a window
        kinds = [
            KIND_WINDOW if types[a] == "sliding_attention" else KIND_FULL
            for a in self.layers
        ]
        self.layer_kinds = jnp.asarray(kinds, dtype=jnp.int32)
        #: each local layer's kind for the paged pool (kv/store.py): a kind
        #: has a pool and block tables of its own.  None where no layer
        #: has a window: one pool, as for any model of one kind
        self.paged_kinds = (
            tuple(KV_KINDS[k] for k in kinds)
            if self.window and KIND_WINDOW in kinds
            else None
        )
        # a layer's index within its kind, riding the scan beside the kind
        seen = {KIND_FULL: 0, KIND_WINDOW: 0}
        within = []
        for k in kinds:
            within.append(seen[k])
            seen[k] += 1
        self._kind_index = jnp.asarray(within, dtype=jnp.int32)

    # ---- pure compute --------------------------------------------------
    def _by_head(self, h, w):
        """h [B, T, D] through a projection split by head -> [B, T, heads,
        head_dim]: a float matrix is [heads, head_dim, D], a quantised one
        [D, heads*head_dim]."""
        if is_quantized(w):
            B, T, _ = h.shape
            return (h @ dq(w)).reshape(B, T, -1, self.config.head_dim)
        return jnp.einsum("btd,hkd->bthk", h, w)

    def _attention(self, p, h, kvs, pos, kind, idx, mask, kv_commit, attend_fn):
        B, T, _ = h.shape
        q, k, v = (self._by_head(h, p[name]) for name in BY_HEAD)
        H, Hd = q.shape[2:]
        # window layers rotate, full layers carry no position at all
        positions = pos + jnp.arange(T)
        is_win = kind == KIND_WINDOW
        q = jnp.where(
            is_win, apply_rope_interleaved(q, positions, self.inv_freq, self.rope_scale), q
        )
        k = jnp.where(
            is_win, apply_rope_interleaved(k, positions, self.inv_freq, self.rope_scale), k
        )
        if attend_fn is not None:
            attn, kvs = attend_fn(q, k, v, kvs, kind=kind, layer=idx)
        else:
            attn, kvs = self._cached_attend(q, k, v, kvs, pos, is_win, mask, kv_commit)
        out = jnp.matmul(
            attn.reshape(B, T, H * Hd), dq(p["wo"]),
            preferred_element_type=jnp.float32,
        )
        return out, kvs

    def _cached_attend(self, q, k, v, kvs, pos, is_win, mask, kv_commit):
        """Both kinds over one slot-addressed cache: write, then attend
        under the kind's predicate (the flash kernels where they run)."""
        from dnet_tpu.core.kvcache import read_kv, write_kv
        from dnet_tpu.ops.flash_attention import flash_attend_causal

        kvs = write_kv(kvs, k, v, pos, kv_commit)
        kc, vc = read_kv(kvs)
        W = self.window
        if mask is not None:  # a caller's mask composes with the kind's
            swa = mask & sliding_window_mask(q.shape[1], kc.shape[1], pos, W or kc.shape[1])
            return attend(q, kc, vc, mask=jnp.where(is_win, swa, mask)), kvs
        if not W:
            return flash_attend_causal(q, kc, vc, pos), kvs

        @jax.named_scope(SCOPE_ATTN_WINDOW)
        def win():
            return flash_attend_causal(q, kc, vc, pos, window=W)

        @jax.named_scope(SCOPE_ATTN_FULL)
        def full():
            return flash_attend_causal(q, kc, vc, pos)

        return lax.cond(is_win, win, full), kvs

    def _moe(self, p, h, h32):
        """-> (the held experts' part + the shared experts' term [B, T, D]
        float32, how many of each token's chosen experts are held here
        [B, T]).  The router reads the norm's float32 output `h32`, not
        its rounded copy `h`: with sigmoid scores near 1 the eighth and
        the ninth expert lie close, and a flip moves 1/8 of a token's
        routed term."""
        from dnet_tpu.ops.moe import (
            held_assignments,
            moe_apply,
            swiglu_expert_closures,
            swiglu_grouped_closure,
        )

        B, T, D = h.shape
        flat = h.reshape(B * T, D)
        logits = jnp.matmul(
            h32.reshape(B * T, D), p["gate_w"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        scores = jax.nn.sigmoid(logits) if self.sigmoid else jax.nn.softmax(logits, axis=-1)
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
            quantized=self.experts_quantized,
        )
        out = out.astype(jnp.float32)
        if self.n_shared:
            with jax.named_scope(SCOPE_MOE_SHARED):
                inner = jax.nn.silu(flat @ dq(p["s_gate"])) * (flat @ dq(p["s_up"]))
                shared = jnp.matmul(
                    inner, dq(p["s_down"]), preferred_element_type=jnp.float32
                )
                out = out + shared * self.shared_scale
        held = held_assignments(top_idx, self.expert_offset, E_local)
        return out.reshape(B, T, D), held.reshape(B, T)

    def _layer(self, p, x, kvs, pos, kind, idx, mask, kv_commit, attend_fn):
        # ONE norm feeds both halves; the three terms are summed in float32
        # and rounded once
        h32 = layer_norm(x.astype(jnp.float32), p["norm"], self.eps)
        h = h32.astype(x.dtype)
        with jax.named_scope(SCOPE_ATTN):
            a, kvs = self._attention(p, h, kvs, pos, kind, idx, mask, kv_commit, attend_fn)
        with jax.named_scope(SCOPE_MOE):
            m, held = self._moe(p, h, h32)
        return (x.astype(jnp.float32) + a + m).astype(x.dtype), kvs, held

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
                "cohere2_moe under tensor or sequence parallelism (its share "
                "of a layer is the expert-parallel one, by config)"
            )
        L = lead_dim(window_params["wq"])
        if layer_kinds is not None:
            kinds = layer_kinds
        elif L == self.layer_kinds.shape[0]:
            kinds = self.layer_kinds
        else:
            raise NotImplementedError(
                "cohere2_moe: a window of fewer layers than the model holds "
                "needs its kinds passed (layer_kinds)"
            )

        # routed experts grouped: the kernel reads each layer's experts out
        # of the stack in place (ops/moe.py: grouped_matmul), so the scan
        # closes over the stacks and carries the layer's index as well
        stacks = None
        if self.moe_path(x.shape[0] * x.shape[1]) == "grouped":
            from dnet_tpu.ops.moe import expert_stacks

            stacks = expert_stacks(window_params)
        tail = () if stacks is None else (jnp.arange(L, dtype=jnp.int32),)

        def stacked(p, layer):
            return {**p, "e_stack": (stacks, layer[0])} if layer else p

        if attend_fn is not None:
            # the caller owns cache write and attention read: `kv` is its
            # own (per-kind) affair, never scanned over, and each layer
            # hands it the kind and the layer's index within that kind (the
            # pools' kinds: where no layer has a window, all are one); the
            # scan stacks whatever the hook returns, plus the
            # held-assignment counts
            within = (
                jnp.arange(L, dtype=jnp.int32) if self.paged_kinds is None
                else self._kind_index
            )

            def body(xc, per):
                p, kind, idx, *layer = per
                xc, rows, held = self._layer(
                    stacked(p, layer), xc, kv, pos, kind, idx, None, None, attend_fn
                )
                return xc, dict(rows, moe_held=held)

            return lax.scan(body, x, (window_params, kinds, within, *tail))

        def body(xc, per):
            p, kvs, kind, *layer = per
            xc, kvs, _ = self._layer(
                stacked(p, layer), xc, kvs, pos, kind, None, mask, kv_commit, None
            )
            return xc, kvs

        return lax.scan(body, x, (window_params, kv, kinds, *tail))

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return layer_norm(x, edge_params["final_norm"]["weight"], self.eps)

    def lm_project(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        # float32 logits: bfloat16 would round a logit near 5 by up to 0.016
        logits = super().lm_project(edge_params, x, out_dtype=jnp.float32)
        return logits if self.logit_scale == 1.0 else logits * self.logit_scale

    # ---- weight mapping -------------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Tensor names of the experts, shared experts and router are the
        HF mixture-of-experts convention (the catalog gives none): experts
        under their GLOBAL ids, so a share's checkpoint holds
        `mlp.experts.{expert_offset}` onwards."""

        def t(name: str) -> np.ndarray:
            return np.ascontiguousarray(raw[name].T)  # HF [out,in] -> (in,out)

        def by_head(name: str) -> np.ndarray:
            w = raw[name]  # HF [heads*head_dim, D] as it lies, split by head
            return w.reshape(-1, self.config.head_dim, w.shape[-1])

        def stack(fmt: str, ids) -> np.ndarray:
            return np.stack([t(fmt.format(e)) for e in ids])

        held = range(self.expert_offset, self.expert_offset + self.n_held)
        p = {
            "norm": raw["input_layernorm.weight"],
            "wq": by_head("self_attn.q_proj.weight"),
            "wk": by_head("self_attn.k_proj.weight"),
            "wv": by_head("self_attn.v_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
            "gate_w": t("mlp.gate.weight"),  # [D, routed experts]
            "e_gate": stack("mlp.experts.{}.gate_proj.weight", held),
            "e_up": stack("mlp.experts.{}.up_proj.weight", held),
            "e_down": stack("mlp.experts.{}.down_proj.weight", held),
        }
        if self.n_shared:
            js = range(self.n_shared)
            cat = "mlp.shared_experts.{}."
            p["s_gate"] = np.concatenate(
                [t(cat.format(j) + "gate_proj.weight") for j in js], axis=1
            )
            p["s_up"] = np.concatenate(
                [t(cat.format(j) + "up_proj.weight") for j in js], axis=1
            )
            p["s_down"] = np.concatenate(
                [t(cat.format(j) + "down_proj.weight") for j in js], axis=0
            )
        return p

    # ---- weight-only quantisation ----------------------------------------
    def quantize_params(self, stacked, bits: int, scale_dtype=None, group_size: int = 0):
        """The heads-first leaves quantise in ops/quant.py's form, [.., D,
        out] with the groups along D: the numbers a [D, out] matrix gave."""

        def rows_last(w):  # [.., heads, head_dim, D] -> a view [.., D, heads*head_dim]
            w = np.asarray(w)
            return np.swapaxes(w.reshape(*w.shape[:-3], -1, w.shape[-1]), -1, -2)

        turned = {k: rows_last(v) if k in BY_HEAD else v for k, v in stacked.items()}
        return super().quantize_params(turned, bits, scale_dtype, group_size)

    quantize_layer = quantize_params  # one mapped layer is the same flat dict
