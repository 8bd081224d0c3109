"""DeepSeek-V2-family ring model: MLA attention + shared/routed MoE.

Reference analog: src/dnet/core/models/deepseek_v2.py (MLA-style model,
asymmetric head dims).  Architecture (matching transformers' DeepseekV2*):

- MLA: queries via optional LoRA (q_a -> norm -> q_b), KV via a compressed
  latent (kv_a -> norm -> kv_b) plus a SHARED per-token rope key (MQA-style);
  rope uses the interleaved/complex-pair convention.
- THE CACHE IS THE LATENT: one entry a token a layer, `[c (kv_lora_rank) |
  k_pe (qk_rope_head_dim)]`, the normalised latent and the rotated shared
  key, whatever the number of heads (`init_kv`: {"c": [L, B, S, 1, r +
  rope, zero lanes up to a multiple of 128]}; `pool_leaves`: the block pool keeps the same one leaf).  A decode
  step attends it ABSORBED: `q_abs_h = W_kvb,h[K]^T q_nope_h`, scores
  `q_abs_h . c(j) + q_pe_h . k_pe(j)`, `o_lat_h = sum_j p c(j)`, `o_h =
  W_kvb,h[V] o_lat_h`: the served path through `attend_fn` (the pool, read
  in place by ops/paged_attention.py paged_attend_latent), a session row
  through the dense op.  A prefill chunk at position p EXPANDS the row's
  latents [0, p + T) to per-head keys and values and runs the causal flash
  kernel over those (by the count: absorbed attention is (r + rope + r) /
  (qk + v) = 2.25 x the attention FLOPs at the mistral4 widths, the
  expansion 2 (p + T) r H (nope + v) FLOPs a layer a chunk).  ONE RULE says
  which cache a model instance keeps (`init_kv`): the latent, unless an
  engine shards the model over a mesh (`on_mesh`: tp shards the cache by
  kv head, sp by sequence through the ring-attention kernels, neither of
  which a latent entry has) or the cache is quantised or built a layer at
  a time from `kv_config` (weight streaming): those keep the EXPANDED
  cache, K nope+rope (qk_head_dim) and V v_head_dim a head, asymmetric.
  `_attention` reads which one it was handed off the cache's leaves.
- `mistral4` (Mistral-Small-4) is the same block with three additions: its
  rope group is `rope_parameters`, the query is scaled by position, `a(t) =
  1 + beta ln(1 + floor(t / original_max_position_embeddings))`
  (`llama_4_scaling_beta`), and its logits are float32.
- An EXPERT SHARE, as cohere2_moe and qwen3_next read it: `n_routed_experts`
  experts held, the range from `expert_offset` of `num_experts_routed`;
  routing, top-k and the normalisation run over every routed expert, the
  absent experts' terms are left out, the shared experts are whole.
- Layers < first_k_dense_replace use a dense swiglu MLP; the rest use MoE:
  softmax-then-topk routing (greedy or group-limited), routed_scaling_factor,
  plus always-on shared experts.
- Dense vs MoE layers have different param structures, so the window is TWO
  stacked segments ({"dense": ..., "moe": ...}), each applied with one
  lax.scan — compile time is layer-count-independent (two programs), and a
  contiguous layer range is always a dense prefix + moe suffix.  MoE expert
  compute is dense-weighted (exact numerics); `tp_axis` shards attention
  heads and the EXPERT dim (expert-parallel ranks) with psum seams.
- For the mesh ring (pp sharding), segments are zero-padded to pp
  divisibility (zero o/down projections make a padded layer an exact
  residual no-op) and the ring runs TWO laps (`ring_phases = 2`): every
  rank applies its dense slice on lap 0 and its moe slice on lap 1, so the
  global execution order stays all-dense-then-all-moe.  The KV cache is laid
  out per-rank (dense rows then moe rows), which is exactly the local
  slicing apply_window already uses.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.core.kvcache import KVConfig
from dnet_tpu.models.base import ModelConfig, RingModel
from dnet_tpu.obs.phases import SCOPE_ATTN, SCOPE_ATTN_LATENT, SCOPE_MOE
from dnet_tpu.models.segments import TwoSegmentStackMixin
from dnet_tpu.parallel.tp_collectives import tp_all_reduce
from dnet_tpu.ops.attention import attend, cached_attend, causal_mask
from dnet_tpu.ops.norms import rms_norm
from dnet_tpu.ops.quant import dq
from dnet_tpu.ops.rope import apply_rope_interleaved, rope_frequencies


class DeepseekV2RingModel(TwoSegmentStackMixin, RingModel):
    model_type = "deepseek_v2"
    supports_kv_commit = True
    supports_paged_attend = True
    reports_moe_held = True
    ring_phases = 2  # mesh ring: lap 0 = dense slices, lap 1 = moe slices
    moe_grouped = True
    quant_keys = frozenset(
        {"wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo",  # MLA projections
         "w_gate", "w_up", "w_down",  # dense mlp
         "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down"}  # MoE
    )  # router gate_w stays f32 (routing decisions are precision-sensitive)

    def __init__(self, config: ModelConfig, layers):
        super().__init__(config, layers)
        x = config.extra
        self.q_lora_rank = x.get("q_lora_rank")
        self.qk_nope_head_dim = x.get("qk_nope_head_dim", 128)
        self.qk_rope_head_dim = x.get("qk_rope_head_dim", 64)
        self.kv_lora_rank = x.get("kv_lora_rank", 512)
        self.v_head_dim = x.get("v_head_dim", 128)
        self.qk_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        self.latent_rank = self.kv_lora_rank
        #: a token's cache entry by the algorithm: [c | k_pe]
        self.latent_dim = self.kv_lora_rank + self.qk_rope_head_dim
        #: ... and as it is KEPT: zero lanes up to a multiple of the chip's
        #: 128-lane tile.  Kept at 320 lanes XLA makes the block's ROWS the
        #: minor dimension (no padding) and copies the whole pool to the
        #: padded row-major tiling around every custom call (v5e, ahead of
        #: time: 4.98 GB of temporaries for a 4.15 GB pool); at 384 the
        #: default tiling is the kernel's and nothing is copied
        self.entry_dim = -(-self.latent_dim // 128) * 128
        # the expert share: `n_routed_experts` held of `num_experts_routed`
        self.n_routed_experts = x.get("n_routed_experts", 0)
        self.n_routed = int(x.get("num_experts_routed") or self.n_routed_experts)
        self.expert_offset = int(x.get("expert_offset", 0))
        if not 0 <= self.expert_offset <= self.n_routed - self.n_routed_experts:
            raise ValueError(
                f"{self.model_type}: experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_routed_experts}) lie outside "
                f"the router's {self.n_routed}"
            )
        self.n_shared_experts = x.get("n_shared_experts", 0)
        self.moe_intermediate_size = x.get("moe_intermediate_size", 0)
        self.first_k_dense_replace = x.get("first_k_dense_replace", 0)
        self.routed_scaling_factor = x.get("routed_scaling_factor", 1.0)
        self.topk_method = x.get("topk_method", "greedy")
        self.n_group = x.get("n_group", 1)
        self.topk_group = x.get("topk_group", 1)
        self.norm_topk_prob = x.get("norm_topk_prob", False)
        self.num_experts_per_tok = x.get("num_experts_per_tok", 0)

        inv_freq, self.rope_scale = rope_frequencies(
            self.qk_rope_head_dim,
            config.rope_theta,
            config.rope_scaling,
            config.max_position_embeddings,
        )
        self.inv_freq = jnp.asarray(inv_freq)

        # Original DeepSeek-V2 YaRN: softmax scale is compensated by
        # mscale(factor, mscale_all_dim)^2 (the model was TRAINED with this;
        # the transformers port drops it when mscale == mscale_all_dim, which
        # shrinks logits ~1.6x on real checkpoints).
        self.softmax_scale = self.qk_head_dim**-0.5
        rs = config.rope_scaling or {}
        if rs.get("rope_type", rs.get("type")) == "yarn":
            factor = rs.get("factor", 1.0)
            msc_all = rs.get("mscale_all_dim", 0)
            if msc_all and factor > 1:
                mscale = 0.1 * msc_all * math.log(factor) + 1.0
                self.softmax_scale = self.softmax_scale * mscale * mscale
        # the position-dependent query scale (Llama-4 / Ministral-3): 0 = none
        self.q_scale_beta = float(rs.get("llama_4_scaling_beta") or 0.0)
        self.q_scale_period = int(
            rs.get("original_max_position_embeddings") or config.max_position_embeddings
        )

    def is_moe_layer(self, abs_layer: int) -> bool:
        return self.n_routed_experts > 0 and abs_layer >= self.first_k_dense_replace

    # ---- cache: the latent, or per-head keys and values ---------------
    def kv_config(self, n_layers, batch, max_seq, dtype="bfloat16", quant_bits=0) -> KVConfig:
        """The EXPANDED cache (asymmetric dims): what a mesh engine shards
        by kv head, a quantised cache and weight streaming's per-layer
        caches keep."""
        return KVConfig(
            n_layers=n_layers,
            batch=batch,
            max_seq=max_seq,
            n_kv_heads=self.config.num_attention_heads,
            head_dim=self.qk_head_dim,
            dtype=dtype,
            v_head_dim=self.v_head_dim,
            quant_bits=quant_bits,
        )

    def init_kv(self, n_layers, batch, max_seq, dtype="bfloat16", quant_bits=0,
                rotating=True) -> dict:
        """THE rule: the latent cache {"c": [L, B, S, 1, entry_dim]}, one
        entry a token whatever the heads, unless the model is sharded over
        a mesh or the cache is quantised (the expanded one, `kv_config`)."""
        if self.on_mesh or quant_bits:
            return super().init_kv(n_layers, batch, max_seq, dtype, quant_bits, rotating)
        return {
            "c": jnp.zeros(
                (n_layers, batch, max_seq, 1, self.entry_dim), jnp.dtype(dtype)
            )
        }

    def pool_leaves(self):
        return {"c": (1, self.entry_dim)}

    # ---- pure compute -------------------------------------------------
    def _expand(self, c_all, w_kvb, pos, T):
        """Per-head keys and values from a session row's latents [0, pos +
        T), in blocks (rows past them stay zero, and sit where causality
        never looks): c_all [B, S, 1, r + rope], w_kvb [r, H, nope + v] ->
        k [B, S, H, qk], v [B, S, H, v]."""
        B, S = c_all.shape[:2]
        r, nope = self.kv_lora_rank, self.qk_nope_head_dim
        H = w_kvb.shape[1]
        E = math.gcd(S, 1024)  # rows a block: the largest power of two that divides S
        rows = c_all[:, :, 0]

        def body(i, kv):
            k, v = kv
            blk = lax.dynamic_slice_in_dim(rows, i * E, E, axis=1)  # [B, E, entry]
            kvb = jnp.einsum("ber,rhn->behn", blk[..., :r], w_kvb)
            k_pe = jnp.broadcast_to(
                blk[:, :, None, r:self.latent_dim], (B, E, H, self.qk_rope_head_dim)
            )
            kb = jnp.concatenate([kvb[..., :nope], k_pe], axis=-1).astype(k.dtype)
            k = lax.dynamic_update_slice_in_dim(k, kb.reshape(B, E, -1), i * E, axis=1)
            v = lax.dynamic_update_slice_in_dim(
                v, kvb[..., nope:].astype(v.dtype).reshape(B, E, -1), i * E, axis=1
            )
            return k, v

        # the heads merged into the lanes, as the flash kernel reads them:
        # its own merge of [.., H, dim] is then the inverse of the split
        # below and no copy (0.28 GB a leaf at 33k otherwise)
        dt = c_all.dtype
        k0 = jnp.zeros((B, S, H * self.qk_head_dim), dt)
        v0 = jnp.zeros((B, S, H * self.v_head_dim), dt)
        n = jnp.minimum((jnp.asarray(pos, jnp.int32) + T + E - 1) // E, S // E)
        k, v = lax.fori_loop(0, n, body, (k0, v0))
        return k.reshape(B, S, H, -1), v.reshape(B, S, H, -1)

    @jax.named_scope(SCOPE_ATTN)
    def _attention(
        self, p, x, kvs, pos, mask, tp_axis=None, kv_commit=None, sp_axis=None,
        attend_fn=None, layer=None,
    ):
        cfg = self.config
        B, T, D = x.shape
        nope, rope_d, vd = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        r = self.kv_lora_rank

        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        if self.q_lora_rank is None:
            q = h @ dq(p["wq"])
        else:
            qa = rms_norm(h @ dq(p["wq_a"]), p["q_a_norm"], 1e-6)
            q = qa @ dq(p["wq_b"])
        # local head count from the (possibly tp-sharded) projection shape
        H = q.shape[-1] // self.qk_head_dim
        q = q.reshape(B, T, H, self.qk_head_dim)
        q_nope, q_pe = q[..., :nope], q[..., nope:]

        ckv = h @ dq(p["wkv_a"])  # [B, T, kv_lora + rope_d] (replicated)
        k_latent, k_pe = ckv[..., :r], ckv[..., r:]
        k_latent = rms_norm(k_latent, p["kv_a_norm"], 1e-6)

        positions = pos + jnp.arange(T)
        q_pe = apply_rope_interleaved(q_pe, positions, self.inv_freq, self.rope_scale)
        k_pe = apply_rope_interleaved(
            k_pe[:, :, None, :], positions, self.inv_freq, self.rope_scale
        )  # [B, T, 1, rope_d] — shared across heads (MQA-style)
        # the query's scale: the softmax scale, and a(t) where the family
        # has one (float32: a(t) - 1 is a few percent)
        q_mul = None
        if self.q_scale_beta:
            a = 1.0 + self.q_scale_beta * jnp.log1p(
                jnp.floor(positions.astype(jnp.float32) / self.q_scale_period)
            )
            q_mul = a[..., None, None] if a.ndim == 2 else a[None, :, None, None]

        def scaled(t, extra=1.0):
            if q_mul is None and extra == 1.0:
                return t
            m = extra if q_mul is None else q_mul * extra
            return (t.astype(jnp.float32) * m).astype(t.dtype)

        latent = attend_fn is not None or "c" in kvs
        if not latent:
            # the EXPANDED cache (mesh, quantised, streamed: init_kv's rule)
            kv = (k_latent @ dq(p["wkv_b"])).reshape(B, T, H, nope + vd)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            q_full = scaled(jnp.concatenate([q_nope, q_pe], axis=-1))
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (B, T, H, rope_d))], axis=-1
            )
            # shared body incl. the sp path: with sp_axis the cache holds this
            # rank's sequence shard and attention runs as distributed
            # flash-decoding with an LSE combine (ops/ring_attention.py) —
            # MLA's asymmetric K/V head dims flow through unchanged.  mask=None
            # non-sp declares the plain causal predicate: prefill takes the
            # Pallas flash kernel on TPU (ops/flash_attention.py)
            attn, kvs = cached_attend(
                q_full, k_full, v, kvs, pos, mask,
                kv_commit=kv_commit, sp_axis=sp_axis, scale=self.softmax_scale,
                causal=mask is None,
            )
        else:
            w_kvb = dq(p["wkv_b"]).reshape(r, H, nope + vd)
            pad = [(0, 0)] * 3 + [(0, self.entry_dim - self.latent_dim)]  # zero lanes score 0
            entry = jnp.pad(jnp.concatenate([k_latent[:, :, None, :], k_pe], axis=-1), pad)
            with jax.named_scope(SCOPE_ATTN_LATENT):
                if attend_fn is None:
                    new = entry.astype(kvs["c"].dtype)
                    if kv_commit is not None:
                        old = lax.dynamic_slice(kvs["c"], (0, pos, 0, 0), new.shape)
                        new = jnp.where(kv_commit, new, old)
                    c_all = lax.dynamic_update_slice(kvs["c"], new, (0, pos, 0, 0))
                    kvs = {"c": c_all}
                if attend_fn is not None or T == 1 or mask is not None:
                    # ABSORBED: the heads' keys folded into the query, the
                    # entry attended as it is kept, the values un-folded
                    q_abs = jnp.einsum("bthn,rhn->bthr", q_nope, w_kvb[..., :nope])
                    q_lat = jnp.pad(jnp.concatenate([q_abs.astype(q.dtype), q_pe], axis=-1), pad)
                    if attend_fn is not None:
                        # sigma and a(t) ride in on the query: the kernel scores as is
                        o_lat, kvs = attend_fn(
                            scaled(q_lat, self.softmax_scale), entry, None, kvs, layer=layer
                        )
                    else:
                        o_lat = attend(
                            scaled(q_lat), c_all, c_all[..., :r],
                            mask=causal_mask(T, c_all.shape[1], pos) if mask is None else mask,
                            scale=self.softmax_scale,
                        )
                    attn = jnp.einsum("bthr,rhv->bthv", o_lat, w_kvb[..., nope:]).astype(x.dtype)
                else:
                    # a prefill chunk: keys and values EXPANDED from the
                    # row's latents, through the causal flash kernel
                    from dnet_tpu.ops.flash_attention import flash_attend_causal

                    k_all, v_all = self._expand(c_all, w_kvb, pos, T)
                    q_full = scaled(jnp.concatenate([q_nope, q_pe], axis=-1))
                    attn = flash_attend_causal(
                        q_full, k_all, v_all, pos, scale=self.softmax_scale
                    )
        out = attn.reshape(B, T, H * vd) @ dq(p["wo"])
        if tp_axis is not None:
            # out-proj all-reduce through the quantizable TP seam
            out = tp_all_reduce(out, tp_axis)
        return x + out, kvs

    def _dense_mlp(self, p_prefix: dict, h: jnp.ndarray) -> jnp.ndarray:
        gate = h @ dq(p_prefix["w_gate"])
        up = h @ dq(p_prefix["w_up"])
        return (jax.nn.silu(gate) * up) @ dq(p_prefix["w_down"])

    @jax.named_scope(SCOPE_MOE)
    def _moe(self, p, x, tp_axis=None):
        B, T, D = x.shape
        h = rms_norm(x, p["mlp_norm"], self.config.rms_norm_eps)
        flat = h.reshape(B * T, D)

        # float32 router at full precision: the k-th and the next expert
        # lie close, and a flip moves a whole routed term
        logits = jnp.matmul(
            flat.astype(jnp.float32), p["gate_w"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        scores = jax.nn.softmax(logits, axis=-1)  # [N, E] f32 softmax over ALL routed
        k = self.num_experts_per_tok
        if self.topk_method == "group_limited_greedy":
            N, E = scores.shape
            g = self.n_group
            group_scores = scores.reshape(N, g, E // g).max(axis=-1)
            _, group_idx = lax.top_k(group_scores, self.topk_group)
            group_mask = jnp.zeros_like(group_scores).at[
                jnp.arange(N)[:, None], group_idx
            ].set(1.0)
            score_mask = jnp.repeat(group_mask, E // g, axis=1)
            masked = jnp.where(score_mask > 0, scores, 0.0)
            topk_w, topk_idx = lax.top_k(masked, k)
        else:  # greedy (DeepSeek-V2-Lite)
            topk_w, topk_idx = lax.top_k(scores, k)
        if self.norm_topk_prob:
            topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
        topk_w = topk_w * self.routed_scaling_factor

        from dnet_tpu.ops.moe import (
            held_assignments,
            moe_apply,
            swiglu_expert_closures,
            swiglu_grouped_closure,
        )

        topk_idx = topk_idx.astype(jnp.int32)
        off = self.expert_offset
        effn, dense, E_local = swiglu_expert_closures(
            p, flat, scores, topk_idx, topk_w, tp_axis, offset=off
        )
        routed, routed_partial = moe_apply(
            self.moe_impl, flat, topk_idx, topk_w, effn, E_local,
            self.moe_capacity_factor, k, tp_axis, dense,
            offset=off, n_routed=self.n_routed,
            grouped_fn=swiglu_grouped_closure(p, flat, topk_idx, topk_w, offset=off),
            quantized=self.experts_quantized,
        )

        # shared experts are Megatron-split over tp (col/row), so their
        # partial output always reduces over tp; the routed partial joins
        # that psum except on the a2a path, which returns a full output
        shared = self._dense_mlp(
            {"w_gate": p["s_gate"], "w_up": p["s_up"], "w_down": p["s_down"]}, flat
        )
        if tp_axis is not None:
            if routed_partial:
                out = tp_all_reduce(routed.astype(flat.dtype) + shared, tp_axis)
            else:
                out = routed.astype(flat.dtype) + tp_all_reduce(shared, tp_axis)
        else:
            out = routed.astype(flat.dtype) + shared
        # how many of each token's chosen experts are held here [B, T]
        held = held_assignments(topk_idx, off, E_local).reshape(B, T)
        return x + out.reshape(B, T, D), held

    def _layer(
        self, p: dict, x, kvs, pos, mask, tp_axis=None, kv_commit=None,
        sp_axis=None, attend_fn=None, layer=None,
    ):
        x, kvs = self._attention(
            p, x, kvs, pos, mask, tp_axis, kv_commit, sp_axis, attend_fn, layer
        )
        if "e_gate" in p:
            x, held = self._moe(p, x, tp_axis)
            if attend_fn is not None:
                # the hook's rows ride the scan; the held counts beside them
                kvs = dict(kvs, moe_held=held)
        else:
            h = rms_norm(x, p["mlp_norm"], self.config.rms_norm_eps)
            out = self._dense_mlp(p, h)
            if tp_axis is not None:
                # down-proj all-reduce through the quantizable TP seam
                out = tp_all_reduce(out, tp_axis)
            x = x + out
        return x, kvs

    def apply_window(
        self,
        window_params,
        x: jnp.ndarray,
        kv: dict,
        pos: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        layer_kinds: Optional[jnp.ndarray] = None,
        tp_axis: Optional[str] = None,
        kv_commit=None,
        sp_axis: Optional[str] = None,
        phase=None,
        t_real=None,  # full-length caches overwrite padding before reading
        attend_fn=None,
    ) -> Tuple[jnp.ndarray, dict]:
        """Two-segment scan: the window's dense prefix, then its moe suffix.

        `phase` (traced int, mesh ring only) selects ONE segment per call:
        the ring runs `ring_phases` laps so the global layer order stays
        all-dense-then-all-moe even though each pp rank holds a slice of
        both segments.  The segment machinery itself is shared with mixed
        qwen3_moe (models/segments.py).
        """
        # the causal predicate stays implicit (mask=None) under sp too:
        # cached_attend owns the rank-local sp mask (or the TPU split-K
        # flash-decode partials, which honor self.softmax_scale)
        if attend_fn is not None:
            return self._apply_paged(window_params, x, pos, attend_fn)
        return self._apply_segments(
            window_params, x, kv, pos, mask, tp_axis, kv_commit, sp_axis, phase
        )

    def _moe_stacks(self, seg, rows: int, tp_axis=None):
        """The segment's expert stacks for the grouped kernel to read a
        layer out of in place (ops/moe.py: grouped_matmul), where a program
        of `rows` rows takes the grouped path; else None."""
        if tp_axis is not None or "e_gate" not in seg or self.moe_path(rows) != "grouped":
            return None
        from dnet_tpu.ops.moe import expert_stacks

        return expert_stacks(seg)

    def _scan_segment(self, seg, x, kv_seg, pos, mask, tp_axis, kv_commit, sp_axis):
        stacks = self._moe_stacks(seg, x.shape[0] * x.shape[1], tp_axis)
        if stacks is None:
            return super()._scan_segment(
                seg, x, kv_seg, pos, mask, tp_axis, kv_commit, sp_axis
            )

        def body(carry, per):
            p, kvs, layer = per
            return self._layer(
                {**p, "e_stack": (stacks, layer)}, carry, kvs, pos, mask,
                tp_axis=tp_axis, kv_commit=kv_commit, sp_axis=sp_axis,
            )

        layers = jnp.arange(stacks["e_gate"].shape[0], dtype=jnp.int32)
        return lax.scan(body, x, (seg, kv_seg, layers))

    def _apply_paged(self, window_params, x, pos, attend_fn):
        """The served decode step: the caller's hook owns cache write and
        attention read (the pool is its own, closed over, never scanned),
        and each layer hands it the latent entry and the layer's index.
        Returns (x, the hook's rows stacked by layer, plus `moe_held`
        [L_moe, B, T])."""
        outs, held, first = [], None, 0
        for name in ("dense", "moe"):
            seg = window_params.get(name)
            if seg is None:
                continue
            n = jax.tree.leaves(seg)[0].shape[0]
            stacks = self._moe_stacks(seg, x.shape[0] * x.shape[1])

            def body(xc, per, stacks=stacks, first=first):
                p, layer = per
                if stacks is not None:
                    p = {**p, "e_stack": (stacks, layer - first)}
                return self._layer(p, xc, None, pos, None, attend_fn=attend_fn, layer=layer)

            x, rows = lax.scan(
                body, x, (seg, first + jnp.arange(n, dtype=jnp.int32))
            )
            held = rows.pop("moe_held", held)
            outs.append(rows)
            first += n
        rows = outs[0] if len(outs) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs), *outs
        )
        return x, rows if held is None else dict(rows, moe_held=held)

    def normalize(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return rms_norm(x, edge_params["final_norm"]["weight"], self.config.rms_norm_eps)

    # ---- weight mapping ----------------------------------------------
    def stack_layers(self, per_layer: List[Dict[str, np.ndarray]]):
        """Two homogeneous stacked segments: the window's dense prefix and
        its moe suffix (a contiguous layer range is always dense-then-moe
        because dense layers come first globally)."""
        n_dense = sum(1 for a in self.layers if not self.is_moe_layer(a))
        out: Dict[str, Any] = {}
        if per_layer[:n_dense]:
            out["dense"] = RingModel.stack_layers(per_layer[:n_dense])
        if per_layer[n_dense:]:
            out["moe"] = RingModel.stack_layers(per_layer[n_dense:])
        return out

    # quantize_params / wrap_offload_layer / pad_mesh_segments come from
    # TwoSegmentStackMixin (shared with mixed qwen3_moe)

    def map_layer(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        def t(name):
            return np.ascontiguousarray(raw[name].T)

        p: Dict[str, np.ndarray] = {
            "attn_norm": raw["input_layernorm.weight"],
            "mlp_norm": raw["post_attention_layernorm.weight"],
            "wkv_a": t("self_attn.kv_a_proj_with_mqa.weight"),
            "kv_a_norm": raw["self_attn.kv_a_layernorm.weight"],
            "wkv_b": t("self_attn.kv_b_proj.weight"),
            "wo": t("self_attn.o_proj.weight"),
        }
        if "self_attn.q_proj.weight" in raw:
            p["wq"] = t("self_attn.q_proj.weight")
        else:
            p["wq_a"] = t("self_attn.q_a_proj.weight")
            p["q_a_norm"] = raw["self_attn.q_a_layernorm.weight"]
            p["wq_b"] = t("self_attn.q_b_proj.weight")

        if "mlp.gate.weight" in raw:  # MoE layer
            p["gate_w"] = t("mlp.gate.weight")  # [D, routed experts]
            # experts under their GLOBAL ids: a share's checkpoint holds
            # `mlp.experts.{expert_offset}` onwards
            held = range(self.expert_offset, self.expert_offset + self.n_routed_experts)
            for key, proj in (("e_gate", "gate"), ("e_up", "up"), ("e_down", "down")):
                p[key] = np.stack([t(f"mlp.experts.{e}.{proj}_proj.weight") for e in held])
            p["s_gate"] = t("mlp.shared_experts.gate_proj.weight")
            p["s_up"] = t("mlp.shared_experts.up_proj.weight")
            p["s_down"] = t("mlp.shared_experts.down_proj.weight")
        else:  # dense layer
            p["w_gate"] = t("mlp.gate_proj.weight")
            p["w_up"] = t("mlp.up_proj.weight")
            p["w_down"] = t("mlp.down_proj.weight")
        return p



class Mistral4RingModel(DeepseekV2RingModel):
    """Mistral-Small-4 (`model_type` mistral4): the latent-attention block
    and the deepseek-style expert layer above, with the rope group read
    from `rope_parameters` (models/base.py), the position-dependent query
    scale a(t) (`llama_4_scaling_beta`, read in __init__ from that group)
    and float32 logits.  The vision tower of the published checkpoint is no
    part of the language model's forward pass and is not served."""

    model_type = "mistral4"

    def lm_project(self, edge_params: dict, x: jnp.ndarray) -> jnp.ndarray:
        # float32 logits: bfloat16 would round a logit near 5 by up to 0.016
        return super().lm_project(edge_params, x, out_dtype=jnp.float32)
