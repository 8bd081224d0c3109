"""MeshShardEngine: one gRPC ring shard backed by a LOCAL device mesh.

Composes the two serving substrates (VERDICT r3 next #1): the process ring
(gRPC frames between hosts, shard/adapter.py) and the in-slice mesh
(shard_map + psum over ICI, parallel/ring.py).  Where the reference gives
every ring node exactly one accelerator (src/dnet/shard/adapters/ring.py:
410-450 — one process, one Metal device), a TPU host owns a 4-8 chip ICI
slice; this engine lets ONE ring shard drive that whole slice: its layer
window runs tensor-parallel (and optionally sequence-parallel) across the
local chips, while activations still hop host-to-host over gRPC/DCN.

The v5e-16 topology the seed aimed at becomes expressible:
4 hosts x 4 chips = a 4-shard gRPC ring where each shard is a tp=4 mesh.

Design: LocalEngine's shard step functions (_embed_window / _hidden /
_hidden_round / _hidden_tail, core/engine.py:279-407) are rebuilt as
shard_map programs over a pp=1 x tp x sp mesh.  Params place with the same
column/row-parallel rules as the full mesh ring (parallel/mesh.py), the KV
cache shards heads over tp (sequence over sp), and the models' existing
tp_axis/sp_axis seams provide the psums — no new model code.  Everything
else (sessions, sampling invariants, the ShardCompute hot loop) is
inherited unchanged: one implementation, three execution substrates.
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from dnet_tpu.core.engine import LocalEngine, Session
from dnet_tpu.core.sampler import pack_chunk_results, sample
from dnet_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
    build_mesh,
    kv_spec,
    window_param_specs,
)
from dnet_tpu.utils.logger import get_logger

log = get_logger()


class MeshShardEngine(LocalEngine):
    """LocalEngine shard-mode compute core over a host-local tp x sp mesh.

    Drop-in for LocalEngine inside ShardCompute: same jitted-fn surface,
    same Session contract, the window math runs SPMD over `devices`.
    """

    # every program here runs under the mesh's tp axis (ops/moe.py: under
    # the ridge such a program keeps the dense einsum)
    whole_batch_programs = False

    def __init__(
        self,
        model_dir: str | Path,
        layers: Sequence[int],
        tp: int = 1,
        sp: int = 1,
        devices: Optional[Sequence] = None,
        max_seq: int = 2048,
        param_dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        kv_ttl_s: float = 600.0,
        kv_quant_bits: int = 0,
        weight_quant_bits: int = 0,
        weight_quant_group: int = 0,
        window_size: int = 0,
        residency_size: int = 0,
        repack_dir: Optional[str] = None,
        spec_lookahead: int = 0,
    ) -> None:
        if tp * sp < 1:
            raise ValueError(f"mesh axes tp={tp} sp={sp} must be positive")
        if sp > 1 and max_seq % sp != 0:
            raise ValueError(f"sp={sp} must divide max_seq={max_seq}")
        self.tp, self.sp = tp, sp
        self.mesh = build_mesh(pp=1, tp=tp, dp=1, sp=sp, devices=devices)
        super().__init__(
            model_dir,
            layers=list(layers),
            max_seq=max_seq,
            param_dtype=param_dtype,
            kv_dtype=kv_dtype,
            kv_ttl_s=kv_ttl_s,
            shard_mode=True,
            window_size=window_size,
            residency_size=residency_size,
            repack_dir=repack_dir,
            kv_quant_bits=kv_quant_bits,
            weight_quant_bits=weight_quant_bits,
            weight_quant_group=weight_quant_group,
            spec_lookahead=spec_lookahead,
        )

    # quant scale-group divisibility: same fail-fast as the full mesh ring
    from dnet_tpu.parallel.engine import MeshEngine as _ME

    _check_quant_sharding = _ME._check_quant_sharding
    del _ME

    # ---- substrate hooks ----------------------------------------------
    # The mesh-specific choices — axis names, param/KV specs, placement —
    # are isolated here so parallel/tp.py's TpEngine (NamedSharding over a
    # ("batch", "model") mesh with the quantizable collective seam) can
    # subclass this engine and override ONLY these; every program builder
    # below is substrate-agnostic.

    def _tp_axis(self):
        """Axis object handed to apply_window's tp seam (a plain string =
        exact psum; parallel/tp_collectives.TpAxis = quantizable).  Kept
        even at tp=1: the size-1 psum certifies x over the axis for the
        replicated out_spec."""
        return AXIS_TP

    def _sp_axis(self):
        return AXIS_SP if self.sp > 1 else None

    def _certify_axes(self):
        """Size-1 mesh axes the window output must be marked varying over
        (and psum-certified back) so the scan carry types line up."""
        return (AXIS_PP, AXIS_DP)

    def _window_specs_of(self, tree):
        return window_param_specs(tree)

    def _kv_pspec(self):
        return kv_spec(self._sp_axis() is not None)

    def _place_window(self, host_tree):
        """Window params host -> mesh, PRE-SHARDED: each chip's slice is
        cast and uploaded individually (parallel/tp.py place_presharded),
        so neither the host cast buffer nor any device ever materializes
        the full stacked tensor — load peak is 1/tp per chip."""
        from dnet_tpu.parallel.tp import place_presharded

        return place_presharded(
            host_tree, self.mesh, self._window_specs_of(host_tree),
            cast=self._np_cast,
        )

    def _place_edge(self, host_edge):
        from dnet_tpu.parallel.mesh import replicate

        return replicate(jax.tree.map(self._np_cast, host_edge), self.mesh)

    def _place_kv(self, kv):
        from jax.sharding import NamedSharding

        spec = self._kv_pspec()
        return jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(self.mesh, spec)), kv
        )

    # ---- loading ------------------------------------------------------
    def _np_cast(self, a):
        """Cast on HOST (numpy + ml_dtypes): the stacked window must not
        transit a single device's HBM before mesh placement — the whole
        point of a mesh shard is a window larger than one chip.  Called
        per SLICE by the pre-sharded placement path, so the cast copy is
        slice-sized too."""
        arr = np.asarray(a)
        if np.issubdtype(arr.dtype, np.floating):
            import ml_dtypes

            target = (
                ml_dtypes.bfloat16
                if self.param_dtype == jnp.bfloat16
                else self.param_dtype
            )
            arr = arr.astype(target)
        return arr

    def _load_params(self) -> None:
        t0 = time.perf_counter()
        m = self.model
        if self.weight_quant_bits and not m.supports_weight_quant:
            raise NotImplementedError(
                f"weight quantization not supported for {self.config.model_type}"
            )
        if self.plan.streams_weights:
            # streaming x mesh (VERDICT r4 next #2): each window layer
            # streams host->mesh as tp/sp-SHARDED device_puts — the window
            # lives across the slice's pooled HBM, not one chip's.  The
            # host store and residency machinery are LocalEngine's
            # (core/weights.py); only the placement differs.
            # Ref prefetch pipeline analog:
            # /root/reference/src/dnet/shard/policies/offload.py:395-421
            from dnet_tpu.core.weights import HostLayerStore, WeightCache

            store = HostLayerStore(
                self.ckpt,
                m,
                param_dtype=str(self.param_dtype),
                repack_dir=self._repack_dir,
                weight_quant_bits=self.weight_quant_bits,
                weight_quant_group=self.weight_quant_group,
            )
            probe = store.layer_host(m.layers[0])
            if self.weight_quant_bits:
                self._check_quant_sharding(probe)
            self._window_specs = self._window_specs_of(probe)
            self.weight_cache = WeightCache(
                store,
                max_resident=self.plan.residency,
                put_fn=self._place_window,
            )
            w = self.plan.window_size
            self._windows = [
                m.layers[i : i + w] for i in range(0, len(m.layers), w)
            ]
            self.window_params = None
            self.weight_cache.prefetch(self._windows[0])
            self._load_edge(t0)
            return
        per_layer = [m.map_layer(self.ckpt.load_layer_raw(a)) for a in m.layers]
        stacked = m.stack_layers(per_layer)
        if self.weight_quant_bits:
            stacked = m.quantize_params(
                stacked, self.weight_quant_bits, scale_dtype=self.param_dtype,
                group_size=self.weight_quant_group,
            )
            self._check_quant_sharding(stacked)
        # pre-sharded placement: cast + upload happen per chip-slice, so
        # the full stacked window is never materialized post-cast on host
        # nor on any single chip (satellite fix: load peak 1/tp per chip)
        self._window_specs = self._window_specs_of(stacked)
        self.window_params = self._place_window(stacked)
        self._load_edge(t0)

    def _load_edge(self, t0: float) -> None:
        """Edge load/prune/quantize/place, shared by the resident and
        streaming branches (pruning identical to LocalEngine._load_params)."""
        m = self.model
        edge_raw = m.map_edge(self.ckpt.load_edge_raw())
        tied = self.config.tie_word_embeddings
        if not (m.is_first or (m.is_last and tied)):
            edge_raw.pop("embed", None)
        if not m.is_last:
            edge_raw.pop("final_norm", None)
            edge_raw.pop("lm_head", None)
        if self.weight_quant_bits:
            edge_raw = m.quantize_edge(
                edge_raw, self.weight_quant_bits, scale_dtype=self.param_dtype,
                group_size=self.weight_quant_group,
            )
        self.edge_params = self._place_edge(edge_raw)
        log.info(
            "[PROFILE] mesh-shard %s %d layers over tp=%d sp=%d in %.2fs",
            "streams" if self.plan.streams_weights else "placed",
            len(m.layers), self.tp, self.sp, time.perf_counter() - t0,
        )

    # ---- jitted step functions ---------------------------------------
    def _build_fns(self) -> None:
        model, mesh = self.model, self.mesh
        tp_axis = self._tp_axis()
        sp_axis = self._sp_axis()
        certify = self._certify_axes()
        has_kinds = getattr(model, "layer_kinds", None) is not None
        kinds_arr = model.layer_kinds if has_kinds else jnp.zeros((), jnp.int32)
        kvs = self._kv_pspec()
        in_specs = (self._window_specs, P(), kvs, P(), P(), P())
        out_specs = (P(), kvs)

        def window_core(wp, x, kv, pos, t_real, kinds):
            # tp collective seams + sp flash-decoding combines live in the
            # models (same seams the in-slice ring uses, parallel/ring.py);
            # pp=1 here — the PIPELINE is the gRPC ring outside this program.
            # x becomes device-varying over the size-1 certify axes once the
            # sharded params/kv touch it; mark it up front so the layer
            # scan's carry types line up.
            x = lax.pcast(x, certify, to="varying")
            x, kv = model.apply_window(
                wp, x, kv, pos,
                layer_kinds=kinds if has_kinds else None,
                tp_axis=tp_axis, sp_axis=sp_axis, t_real=t_real,
            )
            # the certify axes are size 1, so the psum is an identity that
            # just certifies x as replicated again for the P() out_spec
            x = jax.lax.psum(x, certify)
            return x, kv

        core = jax.shard_map(
            window_core, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )

        def hidden_step(window_params, x, kv, pos, t_real, kinds=None):
            k = kinds if kinds is not None else kinds_arr
            return core(window_params, x, kv, pos, t_real, k)

        self._hidden = jax.jit(hidden_step, donate_argnums=(2,))

        if self.plan.streams_weights:
            # streaming feeds _hidden SINGLE-layer trees whose structure can
            # vary layer to layer (two-segment models wrap each layer as
            # {"dense": ...} OR {"moe": ...}, models/segments.py:87-89), but
            # shard_map bakes in_specs at build time — so dispatch on the
            # incoming tree structure and build one program per structure
            # (same retrace-on-structure behavior LocalEngine streaming gets
            # from plain jit)
            progs: dict = {}

            def hidden_stream(window_params, x, kv, pos, t_real, kinds=None):
                key = jax.tree.structure(window_params)
                fn = progs.get(key)
                if fn is None:
                    seg_core = jax.shard_map(
                        window_core, mesh=mesh,
                        in_specs=(
                            self._window_specs_of(window_params),
                            P(), kvs, P(), P(), P(),
                        ),
                        out_specs=out_specs,
                    )

                    def step(wp, x, kv, pos, t_real, kinds=None, _c=seg_core):
                        k = kinds if kinds is not None else kinds_arr
                        return _c(wp, x, kv, pos, t_real, k)

                    fn = jax.jit(step, donate_argnums=(2,))
                    progs[key] = fn
                return fn(window_params, x, kv, pos, t_real, kinds)

            self._hidden = hidden_stream

        def hidden_round(window_params, x, kv, pos, t_real, lo, hi, kinds=None):
            """One ring ROUND (k-round schedule): static [lo, hi) slice of
            the stacked window — slicing runs OUTSIDE shard_map where the
            layer axis is pp=1-replicated, so XLA slices each device's
            local shard in place."""
            wp = jax.tree.map(lambda a: a[lo:hi], window_params)
            kv_r = jax.tree.map(lambda a: a[lo:hi], kv)
            k = kinds_arr[lo:hi] if has_kinds else kinds_arr
            x, kv_r = core(wp, x, kv_r, pos, t_real, k)
            kv = jax.tree.map(lambda f, s: f.at[lo:hi].set(s), kv, kv_r)
            return x, kv

        self._hidden_round = jax.jit(
            hidden_round, static_argnums=(5, 6), donate_argnums=(2,)
        )

        def embed_window(window_params, edge_params, tokens, kv, pos, t_real):
            x = model.embed(edge_params, tokens)
            return core(window_params, x, kv, pos, t_real, kinds_arr)

        self._embed_window = jax.jit(embed_window, donate_argnums=(3,))

        def hidden_tail(window_params, edge_params, x, kv, pos, last_idx, sp, key, counts):
            x, kv = core(window_params, x, kv, pos, last_idx + 1, kinds_arr)
            x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
            x_last = model.normalize(edge_params, x_last)
            logits = model.lm_project(edge_params, x_last)[:, 0]
            res = sample(logits, sp, key, token_counts=counts)
            counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
            return res, kv, counts

        self._hidden_tail = jax.jit(hidden_tail, donate_argnums=(3, 8))

        # full-model paths (prefill/decode_step/decode_chunk): only
        # meaningful when this shard holds every layer, but cheap to build
        # (jit traces lazily) and they make a single-host mesh shard a
        # complete LocalEngine substitute for tests and probes
        def full_logits(window_params, edge_params, tokens, kv, pos, last_idx):
            x = model.embed(edge_params, tokens)
            x, kv = core(window_params, x, kv, pos, last_idx + 1, kinds_arr)
            x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
            x_last = model.normalize(edge_params, x_last)
            logits = model.lm_project(edge_params, x_last)
            return logits[:, 0], kv

        self._forward = jax.jit(full_logits, donate_argnums=(3,))

        def decode_and_sample(window_params, edge_params, token, kv, pos, sp, key,
                              counts, plan=None):
            logits, kv = full_logits(window_params, edge_params, token, kv, pos, 0)
            res = sample(logits, sp, key, token_counts=counts, plan=plan)
            counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
            return res, kv, counts

        self._decode = jax.jit(
            decode_and_sample, static_argnums=(8,), donate_argnums=(3, 7)
        )

        def decode_chunk_fn(window_params, edge_params, token, kv, pos, sp, key,
                            counts, n_steps, plan=None):
            def body(carry, _):
                tok, kv, pos, key, counts = carry
                key, step_key = jax.random.split(key)
                logits, kv = full_logits(window_params, edge_params, tok, kv, pos, 0)
                res = sample(logits, sp, step_key, token_counts=counts, plan=plan)
                counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
                return (res.token[:, None], kv, pos + 1, key, counts), res

            (last_tok, kv, _, key, counts), results = jax.lax.scan(
                body, (token, kv, pos, key, counts), None, length=n_steps
            )
            packed = pack_chunk_results(results, plan is None or plan.logprobs)
            return packed, last_tok, kv, key, counts

        self._decode_chunk = jax.jit(
            decode_chunk_fn, static_argnums=(8, 9), donate_argnums=(3, 7)
        )

        L = self.spec_lookahead
        if L > 0:
            # engine-level speculation over the mesh (VERDICT r4 next #5):
            # the shared verify-block body (core/spec.py make_spec_step)
            # with the window pass routed through the shard_map core —
            # drafting/history stay host-shaped, the (L+1)-wide verify
            # forward runs SPMD.  Eligibility gates and the decode_spec
            # driver are inherited unchanged.
            from dnet_tpu.core.spec import make_spec_step

            def window_pass(wp, x, kv, pos, t_real):
                return core(wp, x, kv, pos, jnp.int32(t_real), kinds_arr)

            self._spec_step = jax.jit(
                make_spec_step(model, window_pass, L), donate_argnums=(3, 4)
            )

    # ---- batched lanes over the mesh (r5) ------------------------------
    def place_lane_kv(self, kv):
        """Lane-pool cache placement: [L, slots, S, KVH, Hd] with the same
        axis meanings as the B=1 cache — slots ride the (size-1) dp axis,
        heads shard over tp, sequence over sp."""
        return self._place_kv(kv)

    def build_lane_programs(self, kv_template) -> dict:
        """shard_map(vmap(...)) lane step programs: the per-lane window
        pass (per-lane pos + kv_commit gating) vmaps INSIDE the mesh
        program, so the tp psum seams batch over lanes; head projection +
        per-lane sampling run on the replicated output outside shard_map.
        Signatures match LanePool._build_local exactly — ShardCompute's
        batch-frame hot loop cannot tell the substrates apart."""
        from dnet_tpu.core.sampler import SampleParams
        from dnet_tpu.shard.lanes import lane_sampler

        model, mesh = self.model, self.mesh
        tp_axis = self._tp_axis()
        sp_axis = self._sp_axis()
        certify = self._certify_axes()
        has_kinds = getattr(model, "layer_kinds", None) is not None
        kinds_arr = model.layer_kinds if has_kinds else jnp.zeros((), jnp.int32)
        kvs = self._kv_pspec()
        kv_axes = jax.tree.map(lambda _: 1, kv_template)
        sample_one = lane_sampler(model)
        sp_axes = SampleParams(0, 0, 0, 0, 0, 0, 0, 0)

        def window_lanes(wp, x, kv, pos, active, kinds):
            def one(x_row, kv_row, p, a):
                kv1 = jax.tree.map(lambda t: t[:, None], kv_row)
                xo = lax.pcast(x_row[None], certify, to="varying")
                xo, kv1 = model.apply_window(
                    wp, xo, kv1, p,
                    layer_kinds=kinds if has_kinds else None,
                    tp_axis=tp_axis, sp_axis=sp_axis, kv_commit=a,
                )
                xo = jax.lax.psum(xo, certify)
                return xo[0], jax.tree.map(lambda t: t[:, 0], kv1)

            return jax.vmap(
                one, in_axes=(0, kv_axes, 0, 0), out_axes=(0, kv_axes)
            )(x, kv, pos, active)

        core = jax.shard_map(
            window_lanes, mesh=mesh,
            in_specs=(self._window_specs, P(), kvs, P(), P(), P()),
            out_specs=(P(), kvs),
        )

        def head(wp, ep, token, kv, pos, active):
            x = model.embed(ep, token)  # [slots, 1, D]
            return core(wp, x, kv, pos, active, kinds_arr)

        def mid(wp, x, kv, pos, active):
            return core(wp, x, kv, pos, active, kinds_arr)

        def tail(wp, ep, x, kv, pos, active, sp, keys, counts):
            x, kv = core(wp, x, kv, pos, active, kinds_arr)
            res, counts, keys = jax.vmap(
                sample_one, in_axes=(None, 0, 0, sp_axes, 0, 0)
            )(ep, x[:, None], active, sp, keys, counts)
            return res, kv, counts, keys

        def full(wp, ep, token, kv, pos, active, sp, keys, counts):
            x = model.embed(ep, token)
            x, kv = core(wp, x, kv, pos, active, kinds_arr)
            res, counts, keys = jax.vmap(
                sample_one, in_axes=(None, 0, 0, sp_axes, 0, 0)
            )(ep, x[:, None], active, sp, keys, counts)
            return res, kv, counts, keys

        return {
            "head": jax.jit(head, donate_argnums=(3,)),
            "mid": jax.jit(mid, donate_argnums=(2,)),
            "tail": jax.jit(tail, donate_argnums=(3, 8)),
            "full": jax.jit(full, donate_argnums=(3, 8)),
        }

    # ---- sessions -----------------------------------------------------
    def new_session(
        self, nonce: str, seed: Optional[int] = None, kv=None, pos: int = 0
    ) -> Session:
        """KV allocates directly with the mesh sharding (heads over tp,
        sequence over sp) so every step reuses the placed buffers in place
        — no per-step resharding.  rotating=False under sp: ring-attention
        shards the sequence axis, which a rotating SWA window would alias."""
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        kv_list = None
        if kv is None:
            if self.plan.streams_weights:
                # streaming: one mesh-placed cache per layer, matching the
                # per-layer _hidden invocations of _stream_windows
                from dnet_tpu.core.kvcache import init_cache

                kv_list = []
                for _ in self.model.layers:
                    kv0 = init_cache(
                        self.model.kv_config(
                            1, self.batch, self.max_seq, self.kv_dtype,
                            quant_bits=self.kv_quant_bits,
                        )
                    )
                    kv_list.append(self._place_kv(kv0))
            else:
                kv0 = self.model.init_kv(
                    len(self.model.layers), self.batch, self.max_seq,
                    self.kv_dtype, quant_bits=self.kv_quant_bits,
                    rotating=(self.sp == 1),
                )
                kv = self._place_kv(kv0)
        sess = Session(
            nonce=nonce,
            kv=kv,
            kv_list=kv_list,
            pos=pos,
            key=jax.random.key(seed),
            counts=jnp.zeros((self.batch, self.config.vocab_size), dtype=jnp.int32),
            hist=(
                jnp.zeros((self.batch, self.max_seq), dtype=jnp.int32)
                if self.spec_lookahead > 0
                else None
            ),
        )
        self.sessions[nonce] = sess
        return sess
