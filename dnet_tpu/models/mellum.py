"""Mellum ring model (Mellum2-12B-A2.5B class, `model_type` mellum).

The Qwen3-MoE block with layers of two KINDS, every piece of it from
`config.json`:

- a sequential pre-norm block: RMSNorm -> attention -> residual, RMSNorm ->
  experts -> residual;
- attention with grouped KV heads and (`qk_norm`, default true: the config
  has no key for it and the family always has them) a per-head RMSNorm of q
  and k BEFORE the rotation;
- window and full layers mixed (`layer_types`, three `sliding_attention` to
  one `full_attention`): a window layer attends the last `sliding_window`
  keys, the query's own among them; a full layer everything before it.
  EACH KIND ROTATES BY A TABLE OF ITS OWN (`rope_parameters` nested by layer
  type, `ModelConfig.rope_by_type`): the published window kind the default
  table, the full kind YaRN's blend with its `attention_factor` on cos and
  sin.  Half-split pairs over the whole head;
- every layer's FFN a sparse expert layer (`mlp_layer_types` all `sparse`;
  models/base.py refuses any other entry): softmax over all `num_experts`,
  top-`num_experts_per_tok`, renormalised (`norm_topk_prob`), each expert a
  SwiGLU of `moe_intermediate_size`; no shared expert.  Every expert is
  held: there is no share;
- an untied head.  The published multi-token-prediction head has no key in
  the config and is not served: decoding yields one token a step.

One `lax.scan` over the stacked layers with the layer's kind riding as
data, as models/cohere2_moe.py has it: the kind chooses the rotation's
table (a `jnp.where` over two [head_dim / 2] vectors) and, under the paged
pools, the caller's `attend_fn` is told the kind and the layer's index
within its kind (each kind has a pool and block tables of its own,
kv/store.py KindStore).  The routed experts go grouped out of closed-over
stacks with one dynamic index a layer (ops/moe.py: grouped_matmul).

SHARED WITH cohere2_moe: `_by_head` and `_cached_attend` are that class's
functions, bound here as they stand; the bookkeeping of kinds in
`__init__`, the scan of `apply_window` and `quantize_params` are COPIES
(that class's refuse its own config's keys, sum a parallel block and call
`super()` of their own class): a debt PERF.md section 7 names for a
`simplicity` PR.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.models.base import ModelConfig, RingModel
from dnet_tpu.models.cohere2_moe import (
    BY_HEAD,
    KIND_FULL,
    KIND_WINDOW,
    Cohere2MoeRingModel,
)
from dnet_tpu.obs.phases import KV_KINDS, SCOPE_ATTN, SCOPE_MOE
from dnet_tpu.ops.norms import rms_norm
from dnet_tpu.ops.quant import dq, lead_dim
from dnet_tpu.ops.rope import apply_rope, rope_frequencies

#: tokens a prefill program carries through the stack at once (the
#: scheduler's default chunk); a wider program loops over slabs of at most it
PREFILL_SLAB = 2048
#: `layer_types`' names for the two kinds
TYPE_WINDOW, TYPE_FULL = "sliding_attention", "full_attention"


class MellumRingModel(RingModel):
    model_type = "mellum"
    supports_paged_attend = True
    rope_by_layer_type = True
    #: under an `attend_fn` the scan stacks each lane's count of chosen
    #: experts held here as `moe_held` (all of them: nothing is held
    #: elsewhere) and WHICH it chose as `moe_chosen`
    #: (dnet_moe_assignments_total, dnet_moe_experts_visited_total)
    reports_moe_held = True
    moe_grouped = True
    quant_keys = frozenset({"wq", "wk", "wv", "wo", "e_gate", "e_up", "e_down"})

    _by_head = Cohere2MoeRingModel._by_head
    _cached_attend = Cohere2MoeRingModel._cached_attend

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        x = config.extra
        refused = [
            why for bad, why in (
                (config.attention_bias, "attention biases"),
                (x.get("mlp_only_layers"), "dense-MLP layers (mlp_only_layers)"),
                (int(x.get("decoder_sparse_step", 1)) != 1, "decoder_sparse_step != 1"),
                (x.get("num_shared_experts") or x.get("shared_expert_intermediate_size"),
                 "shared experts"),
            ) if bad
        ]
        if refused:
            raise NotImplementedError(f"mellum: not implemented: {', '.join(refused)}")
        self.eps = config.rms_norm_eps
        self.qk_norm = bool(x.get("qk_norm", True))
        self.norm_topk_prob = bool(x.get("norm_topk_prob", True))
        types = config.layer_types or [TYPE_FULL] * config.num_hidden_layers
        unknown = sorted(set(types) - {TYPE_WINDOW, TYPE_FULL})
        if unknown:
            raise NotImplementedError(f"mellum: layer_types {unknown}")
        # a table a kind: (inv_freq [head_dim / 2], the factor on cos and sin)
        flat = (config.rope_theta, config.rope_scaling)
        by_type = config.rope_by_type or {}
        tables = {
            kind: rope_frequencies(
                config.head_dim, *by_type.get(name, flat), config.max_position_embeddings
            )
            for kind, name in ((KIND_WINDOW, TYPE_WINDOW), (KIND_FULL, TYPE_FULL))
        }
        self.inv_freq = {k: jnp.asarray(t[0]) for k, t in tables.items()}
        self.rope_scale = {k: float(t[1]) for k, t in tables.items()}
        # ---- the kinds' bookkeeping: a copy of cohere2_moe's ------------
        self.window = int(config.sliding_window or 0)
        kinds = [KIND_WINDOW if types[a] == TYPE_WINDOW else KIND_FULL for a in self.layers]
        self.layer_kinds = jnp.asarray(kinds, dtype=jnp.int32)
        #: each local layer's kind for the paged pool (kv/store.py); None
        #: where no layer has a window: one pool, as for any model of one kind
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
    def _rotate(self, a, positions, is_win):
        """q or k by the kind's table: the choice is data (the kind rides
        the scan), two [head_dim / 2] vectors and two scalars to choose from."""
        inv = jnp.where(is_win, self.inv_freq[KIND_WINDOW], self.inv_freq[KIND_FULL])
        scale = jnp.where(is_win, self.rope_scale[KIND_WINDOW], self.rope_scale[KIND_FULL])
        return apply_rope(a, positions, inv, scale)

    def _attention(self, p, h, kvs, pos, kind, idx, mask, kv_commit, attend_fn):
        B, T, _ = h.shape
        q, k, v = (self._by_head(h, p[name]) for name in BY_HEAD)
        H, Hd = q.shape[2:]
        if self.qk_norm:
            q, k = rms_norm(q, p["q_norm"], self.eps), rms_norm(k, p["k_norm"], self.eps)
        positions = pos + jnp.arange(T)
        is_win = kind == KIND_WINDOW
        q, k = self._rotate(q, positions, is_win), self._rotate(k, positions, is_win)
        if attend_fn is not None:
            attn, kvs = attend_fn(q, k, v, kvs, kind=kind, layer=idx)
        else:
            attn, kvs = self._cached_attend(q, k, v, kvs, pos, is_win, mask, kv_commit)
        return attn.reshape(B, T, H * Hd) @ dq(p["wo"]), kvs

    def _moe(self, p, h):
        """-> (the experts' term [B, T, D], how many of each token's chosen
        experts are held here [B, T]: all of them, which of the held experts
        each token chose [B, T, E] bool).  The router reads float32 at the
        highest precision: with softmax scores near 1/64 the eighth and the
        ninth expert lie close, and a flip moves an eighth of the term."""
        from dnet_tpu.ops.moe import (
            held_assignments,
            moe_apply,
            swiglu_expert_closures,
            swiglu_grouped_closure,
        )

        B, T, D = h.shape
        flat = h.reshape(B * T, D)
        logits = jnp.matmul(
            flat.astype(jnp.float32), p["gate_w"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        scores = jax.nn.softmax(logits, axis=-1)  # over every expert, then top-k
        k = self.config.num_experts_per_tok
        top_w, top_idx = lax.top_k(scores, k)
        if self.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        top_idx = top_idx.astype(jnp.int32)
        effn, dense, E = swiglu_expert_closures(p, flat, scores, top_idx, top_w, None)
        out, _ = moe_apply(
            self.moe_impl, flat, top_idx, top_w, effn, E,
            self.moe_capacity_factor, k, None, dense,
            grouped_fn=swiglu_grouped_closure(p, flat, top_idx, top_w),
            quantized=self.experts_quantized,
        )
        held = held_assignments(top_idx, 0, E)
        chosen = jnp.any(top_idx[:, :, None] == jnp.arange(E, dtype=jnp.int32), axis=1)
        return (
            out.astype(h.dtype).reshape(B, T, D), held.reshape(B, T), chosen.reshape(B, T, E)
        )

    def _layer(self, p, x, kvs, pos, kind, idx, mask, kv_commit, attend_fn):
        with jax.named_scope(SCOPE_ATTN):
            h = rms_norm(x, p["attn_norm"], self.eps)
            a, kvs = self._attention(p, h, kvs, pos, kind, idx, mask, kv_commit, attend_fn)
            x = x + a.astype(x.dtype)
        with jax.named_scope(SCOPE_MOE):
            m, held, chosen = self._moe(p, rms_norm(x, p["mlp_norm"], self.eps))
        return x + m, kvs, held, chosen

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
            raise NotImplementedError("mellum under tensor or sequence parallelism")
        L = lead_dim(window_params["wq"])
        if layer_kinds is not None:
            kinds = layer_kinds
        elif L == self.layer_kinds.shape[0]:
            kinds = self.layer_kinds
        else:
            raise NotImplementedError(
                "mellum: a window of fewer layers than the model holds needs "
                "its kinds passed (layer_kinds)"
            )

        B, T, D = x.shape
        slab = T
        if attend_fn is None and mask is None:
            while slab > PREFILL_SLAB and slab % 2 == 0:
                slab //= 2
        # routed experts grouped: the kernel reads each layer's experts out
        # of the stack in place (ops/moe.py: grouped_matmul), so the scan
        # closes over the stacks and carries the layer's index as well
        stacks = None
        if self.moe_path(B * slab) == "grouped":
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
            # scan stacks whatever the hook returns, plus the experts' books
            within = (
                jnp.arange(L, dtype=jnp.int32) if self.paged_kinds is None
                else self._kind_index
            )

            def body(xc, per):
                p, kind, idx, *layer = per
                xc, rows, held, chosen = self._layer(
                    stacked(p, layer), xc, kv, pos, kind, idx, None, None, attend_fn
                )
                return xc, dict(rows, moe_held=held, moe_chosen=chosen)

            return lax.scan(body, x, (window_params, kinds, within, *tail))

        def stack(xc, kv, at):
            def body(xc, per):
                p, kvs, kind, *layer = per
                xc, kvs, _, _ = self._layer(
                    stacked(p, layer), xc, kvs, at, kind, None, mask, kv_commit, None
                )
                return xc, kvs

            return lax.scan(body, xc, (window_params, kv, kinds, *tail))

        if slab == T:
            return stack(x, kv, pos)
        # a program wider than a tick's chunk (a one-shot prefill: the load's
        # warm-up of the step's table widths, up to max_seq rows) goes
        # through the stack a SLAB of tokens at a time, the cache carried:
        # what it holds at once is a chunk's activations and sorted expert
        # rows whatever its width (65536 rows x top-8 x 2304 would be 2.4 GB
        # a gathered copy, beside a chip that serving fills to four fifths)
        slabs = jnp.moveaxis(x.reshape(B, T // slab, slab, D), 1, 0)

        def one(kv, per):
            xs, i = per
            xs, kv = stack(xs, kv, pos + i * slab)
            return kv, xs

        kv, out = lax.scan(one, kv, (slabs, jnp.arange(T // slab, dtype=jnp.int32)))
        return jnp.moveaxis(out, 0, 1).reshape(B, T, D), kv

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return rms_norm(x, edge_params["final_norm"]["weight"], self.eps)

    def lm_project(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        # float32 logits: bfloat16 would round a logit near 5 by up to 0.016
        return super().lm_project(edge_params, x, out_dtype=jnp.float32)

    # ---- weight mapping -------------------------------------------------
    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """HF tensor names by the qwen3_moe convention (the catalog gives
        none).  q, k and v are kept heads-first, HF's own [out, in] split
        by head (models/cohere2_moe.py says why)."""
        from concurrent.futures import ThreadPoolExecutor

        def t(name: str) -> np.ndarray:
            return np.ascontiguousarray(raw[name].T)  # HF [out,in] -> (in,out)

        def by_head(name: str) -> np.ndarray:
            w = raw[name]
            return w.reshape(-1, self.config.head_dim, w.shape[-1])

        def stack(fmt: str) -> np.ndarray:
            # each expert's [out, in] turned straight into its place in the
            # stack, a few at a time (numpy copies without the GIL): one copy
            # of a layer's 0.79 GB of experts, not a transpose and then a stack
            first = raw[fmt.format(0)]
            out = np.empty((self.config.num_local_experts, *first.shape[::-1]), first.dtype)

            def put(e: int) -> None:
                out[e] = raw[fmt.format(e)].T

            with ThreadPoolExecutor(4) as pool:
                list(pool.map(put, range(len(out))))
            return out

        p = {
            "attn_norm": raw["input_layernorm.weight"],
            "wq": by_head("self_attn.q_proj.weight"),
            "wk": by_head("self_attn.k_proj.weight"),
            "wv": by_head("self_attn.v_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "gate_w": t("mlp.gate.weight"),  # [D, E] router
            "e_gate": stack("mlp.experts.{}.gate_proj.weight"),
            "e_up": stack("mlp.experts.{}.up_proj.weight"),
            "e_down": stack("mlp.experts.{}.down_proj.weight"),
        }
        if self.qk_norm:
            p["q_norm"] = raw["self_attn.q_norm.weight"]
            p["k_norm"] = raw["self_attn.k_norm.weight"]
        return p

    # ---- weight-only quantisation (a copy of cohere2_moe's) --------------
    def quantize_params(self, stacked, bits: int, scale_dtype=None, group_size: int = 0):
        """The heads-first leaves quantise in ops/quant.py's form, [.., D,
        out] with the groups along D: the numbers a [D, out] matrix gave."""

        def rows_last(w):  # [.., heads, head_dim, D] -> a view [.., D, heads*head_dim]
            w = np.asarray(w)
            return np.swapaxes(w.reshape(*w.shape[:-3], -1, w.shape[-1]), -1, -2)

        turned = {k: rows_last(v) if k in BY_HEAD else v for k, v in stacked.items()}
        return super().quantize_params(turned, bits, scale_dtype, group_size)

    quantize_layer = quantize_params  # one mapped layer is the same flat dict
