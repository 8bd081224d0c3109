"""Staggered-microbatch pipelined ring: every pp rank does real work.

The sequential ring program (parallel/ring.py) runs PP stage-steps per token
with one rank's activation real at a time — (PP-1)/PP of the slice idles.
This module fills the pipeline the classic way, compiled into ONE XLA
program: M >= PP sequence slots are staggered across the pp ranks so that at
every stage-step each rank computes a *different* sequence's stage, and the
hidden states rotate one hop over ICI (`lax.ppermute`).  One "rotation" (M
stage-steps, a single dispatch) enters one new token per slot, exits one
sampled token per slot, and keeps every chip busy the whole time — the
steady state promised by the reference's k-round round-robin schedule
(src/dnet/api/utils.py:62-131), reached here inside a single jitted program.

Schedule (global step t, M slots, PP stages):
  - the token entering at step t belongs to slot  n(t) = t mod M
  - rank r is working on the token that entered at step t - r,
    i.e. slot (t - r) mod M
  - rank PP-1 finishes the token that entered at t-(PP-1): exit slot
    e(t) = (t - PP + 1) mod M; its logits are sampled ON DEVICE and the
    token is written to the entry buffer, so slot e's next entry (step
    t+1 when M == PP) needs no host round-trip.

Sampling inside the rotation matches LocalEngine's per-step key evolution
(split-before-sample per generated token), so a seeded request produces the
identical stream through either engine.

KV: per-slot caches live in one array with the slot folded into the batch
axis ([L, M*B, S, KVH, Hd]); each stage-step slices its slot out, applies
the stage, and writes it back (the write is a dynamic_update_slice into the
donated carry).  Garbage produced by idle slots lands only in idle slots'
rows and is overwritten by the next prefill.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dnet_tpu.core.engine import sample_with_counts
from dnet_tpu.core.sampler import (
    MAX_LOGIT_BIAS,
    SampleParams,
    SampleResult,
    encode_logit_bias,
    sample,
)
from dnet_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
    kv_spec,
    window_param_specs,
)


def _bcast_from_rank(x, axis_name: str, rank: int):
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == rank, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


# ---- multi-lap schedule ----------------------------------------------------
# Segmented models (deepseek ring_phases=2) need each token to traverse the
# ring `phases` times — lap p applies every rank's slice of segment p, so the
# global layer order stays all-dense-then-all-moe.  The schedule generalizes
# the single-lap rotation: a token occupies the ring for PHI = phases*PP
# stage-steps; rank 0 takes a NEW entry only on steps whose arriving token
# has finished its last lap, which happens in bursts of PP consecutive steps
# every PHI (entry_open); entries cycle the M slots round-robin; the token
# entering at step te exits at te + PHI - 1.  phases=1 reduces to the r2
# schedule exactly: entry_open always, slot(t) = t mod M, exit latency PP-1.


def _entry_open(t: int, pp: int, phases: int) -> bool:
    return (t % (phases * pp)) < pp


def _entry_slot(t: int, pp: int, phases: int, m: int) -> int:
    """Slot fed by the entry at step t (valid only when _entry_open)."""
    phi = phases * pp
    return ((t // phi) * pp + (t % phi)) % m


def resolve_pp(n_dev: int, tp: int, sp: int, n_layers: int) -> int:
    """Infer pp from the device budget: every remaining device becomes a
    pipeline stage, decremented until it divides the layer count (the same
    fallback as MeshEngine's inference).  Shared by the engine and the
    serving manager's precheck so both always agree on the resolved pp."""
    pp = max(n_dev // (tp * sp), 1)
    while pp > 1 and n_layers % pp != 0:
        pp -= 1
    return pp


def make_rotation_fn(
    model, mesh: Mesh, window_params, n_slots: int, batch: int = 1,
    n_steps: Optional[int] = None,
):
    """Build the jitted rotation program over `n_steps` stage-steps
    (default M = one rotation; R*M fuses R rotations into ONE dispatch —
    the chunked pipelined path: sampled tokens re-enter their slot on
    device, so the host pays one dispatch + one packed read per R tokens
    per slot instead of per rotation).

    Returned signature:
      (window_params, edge_params, x_state[PP,B,1,D], kv, tokens[M,B],
       pos_vec[M], pos_state[PP], live_state[PP], phase_state[PP],
       entry_open[n_steps], enter_live[n_steps], entry_slot[n_steps],
       exit_valid[n_steps], exit_slot[n_steps], sp_stack, keys[M,2]u32,
       counts[M,B,V], t0)
      -> (results: SampleResult leaves stacked [n_steps,B,...] in EXIT-STEP
          order, x_state, kv, tokens, pos_vec, pos_state, live_state,
          phase_state, keys, counts)

    enter_live is PER STEP (index j), not per slot: a slot's capacity can
    flip mid-chunk, and the engine's host-side schedule simulation computes
    the exact per-step flag.

    A token's write position AND its liveness travel WITH its hidden state
    (pos_state / live_state are ppermuted alongside x), because the ring
    always holds one in-flight token per slot.  The live flag is the single
    source of truth for realness: KV only commits for live tokens (idle-slot
    garbage touches nothing), and exit-side state writes (entry token, key
    burn, counts) are gated on the exiting token's flag — a stale token from
    a re-assigned or idle slot can neither corrupt the fresh prefill's KV
    rows nor clobber the injected entry token.  The engine kills the flag of
    a slot's in-flight token at injection time (it knows which rank holds
    it — see PipelinedMeshEngine.prefill_and_sample's stale-kill scan).

    Segmented models (ring_phases > 1) run each token through `phases` laps:
    a per-token phase travels with the hidden state the same way, entries
    only open on steps whose arriving token has finished its last lap, and
    the per-step schedule (entry_open / entry_slot / exit_valid / exit_slot)
    is precomputed host-side from the closed-form multi-lap schedule
    (_entry_open/_entry_slot) and consumed by the scan.

    Data parallelism shards SLOTS over dp lanes: `n_slots` is the PER-LANE
    slot count and every lane runs this same schedule over its own slots
    (global slot = lane * M + local), so capacity scales linearly with dp
    while the compiled schedule stays lane-invariant.  All per-slot state
    (tokens/pos_vec/keys/counts/sp_stack and the kv slot-batch axis) is
    dp-sharded lane-major; only `enter_live` is genuinely per-lane data
    (which slots carry real requests) and arrives [dp, n_steps].  Sampling
    runs per lane on its own slots — dp-varying by construction, which is
    why no identity psum over dp appears anywhere (r3's dp=1 pin).
    """
    PP = mesh.shape[AXIS_PP]
    M, B = n_slots, batch
    phases = getattr(model, "ring_phases", 1)
    PHI = phases * PP  # stage-steps a token occupies the ring
    n_steps = M * phases if n_steps is None else n_steps
    has_kinds = getattr(model, "layer_kinds", None) is not None
    # sequence parallelism: each sp rank holds a shard of every slot's KV
    # sequence axis; decode attention runs as distributed flash-decoding
    # (the same kv_spec/sp_axis plumbing as the sequential mesh ring)
    sp_axis = AXIS_SP if mesh.shape.get(AXIS_SP, 1) > 1 else None

    x_spec = P(AXIS_PP, AXIS_DP)  # x_state [PP, DP*B, 1, D]
    in_specs = (
        window_param_specs(window_params),
        P(),  # edge params replicated
        x_spec,
        kv_spec(sp_axis is not None),  # [L, DP*M*B, S(/sp), KVH, Hd]
        P(AXIS_DP),  # tokens [DP*M, B]
        P(AXIS_DP),  # pos_vec [DP*M]
        P(AXIS_PP, AXIS_DP),  # pos_state [PP, DP]
        P(AXIS_PP, AXIS_DP),  # live_state [PP, DP] bool
        P(AXIS_PP, AXIS_DP),  # phase_state [PP, DP] int32 (lap of in-flight token)
        P(),  # entry_open [n_steps] bool (schedule: step takes an entry)
        P(AXIS_DP),  # enter_live [DP, n_steps] bool (per-lane real-entry flag)
        P(),  # entry_slot [n_steps] int32 (lane-local slot)
        P(),  # exit_valid [n_steps] bool (schedule: step finishes a token)
        P(),  # exit_slot [n_steps] int32 (lane-local slot)
        P(AXIS_DP),  # sp_stack (SampleParams leaves [DP*M])
        P(AXIS_DP),  # keys [DP*M, 2] uint32
        P(AXIS_DP),  # counts [DP*M, B, V]
        P(),  # t0 scalar
        P(AXIS_PP) if has_kinds else P(),
    )
    res_spec = SampleResult(
        P(None, AXIS_DP), P(None, AXIS_DP), P(None, AXIS_DP), P(None, AXIS_DP)
    )  # leaves [n_steps, DP*B, ...]: every lane emits its own exit row
    out_specs = (
        res_spec, x_spec, kv_spec(sp_axis is not None), P(AXIS_DP), P(AXIS_DP),
        P(AXIS_PP, AXIS_DP), P(AXIS_PP, AXIS_DP), P(AXIS_PP, AXIS_DP),
        P(AXIS_DP), P(AXIS_DP),
    )

    def spmd(window_params, edge_params, x_state, kv, tokens, pos_vec,
             pos_state, live_state, phase_state, entry_open, enter_live,
             entry_slot, exit_valid, exit_slot, sp_stack, keys, counts,
             t0, kinds):
        my_pp = lax.axis_index(AXIS_PP)
        x = x_state[0]  # local [B, 1, D], device-varying over pp (and dp)
        pos_x = pos_state[0, 0]  # this (pp, lane) rank's in-flight position
        live_x = live_state[0, 0]  # is this rank's in-flight token real?
        phase_x = phase_state[0, 0]  # this rank's in-flight token lap
        live_row = enter_live[0]  # this lane's per-step real-entry flags

        def step(carry, j):
            x, pos_x, live_x, phase_x, kv, tokens, pos_vec, keys, counts = carry
            t = t0 + j
            open_j = lax.dynamic_index_in_dim(entry_open, j, keepdims=False)
            n = lax.dynamic_index_in_dim(entry_slot, j, keepdims=False)
            e = lax.dynamic_index_in_dim(exit_slot, j, keepdims=False)
            evalid_j = lax.dynamic_index_in_dim(exit_valid, j, keepdims=False)

            # entry: on schedule-open steps rank 0 replaces its (just-
            # drained) hidden with the entering token's embedding; the
            # token's position is consumed from pos_vec NOW and rides along
            # with the hidden thereafter.  On closed steps the arriving
            # token continues its next lap untouched.
            take = (my_pp == 0) & open_j
            tok_in = lax.dynamic_index_in_dim(tokens, n, keepdims=False)  # [B]
            x_embed = model.embed(edge_params, tok_in[:, None])
            # tokens are dp-sharded, so the embedding is already dp-varying;
            # only the pp axis needs the explicit cast
            x_embed = lax.pcast(x_embed, AXIS_PP, to="varying")
            x_in = jnp.where(take, x_embed, x)
            pos_entry = lax.dynamic_index_in_dim(pos_vec, n, keepdims=False)
            pos_in = jnp.where(take, pos_entry, pos_x)
            live_entry = lax.dynamic_index_in_dim(live_row, j, keepdims=False)
            live_entry = lax.pcast(live_entry, AXIS_PP, to="varying")
            live_in = jnp.where(take, live_entry, live_x)
            phase_in = jnp.where(take, 0, phase_x)
            pos_vec = lax.dynamic_update_index_in_dim(
                pos_vec, jnp.where(open_j, pos_entry + 1, pos_entry), n, axis=0
            )

            # this rank's slot follows from its token's entry step:
            # te = t - rank - PP*lap; slot = entry_slot(te) (closed form).
            # Garbage tokens (cold ring) may compute an arbitrary slot — they
            # never commit KV, so their reads/writes are inert.
            te = t - my_pp - PP * phase_in
            k_idx = (te // PHI) * PP + jnp.mod(te, PHI)
            my_slot = jnp.mod(k_idx, M)

            # this rank's stage over its slot's KV slice; only live tokens
            # commit KV (stale/idle garbage writes nothing, anywhere)
            kv_slot = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, my_slot * B, B, axis=1), kv
            )
            extra = {"phase": phase_in} if phases > 1 else {}
            x_out, kv_slot = model.apply_window(
                window_params, x_in, kv_slot, pos_in,
                layer_kinds=kinds, tp_axis=AXIS_TP, kv_commit=live_in,
                sp_axis=sp_axis, **extra,
            )
            kv = jax.tree.map(
                lambda full, sl: lax.dynamic_update_slice_in_dim(
                    full, sl, my_slot * B, axis=1
                ),
                kv, kv_slot,
            )

            # exit: rank PP-1's x_out is the finished hidden of slot e
            x_last = model.normalize(edge_params, x_out)
            logits = model.lm_project(edge_params, x_last)[:, 0]  # [B, V]
            logits = _bcast_from_rank(logits, AXIS_PP, PP - 1)
            # no dp collective here: each lane samples its OWN slot's exit —
            # the sampling state (tokens/keys/counts) is dp-sharded, so
            # dp-varying logits are exactly right (r3's identity psum gone)

            # the exiting token's own live flag decides realness (bcast from
            # the last rank, where it resides this step); schedule steps that
            # finish no token (mid-lap arrivals) are never real
            real = (
                lax.psum(
                    jnp.where(my_pp == PP - 1, live_in.astype(jnp.int32), 0),
                    AXIS_PP,
                )
                > 0
            ) & evalid_j
            old_key = lax.dynamic_index_in_dim(keys, e, keepdims=False)
            key = jax.random.wrap_key_data(old_key)
            key, step_key = jax.random.split(key)
            sp_e = SampleParams(*(lax.dynamic_index_in_dim(a, e, keepdims=False)
                                  for a in sp_stack))
            counts_e = lax.dynamic_index_in_dim(counts, e, keepdims=False)
            res = sample(logits, sp_e, step_key, token_counts=counts_e)
            # stale exits (re-assigned slot, cold pipeline) must not touch
            # slot state: no key burn, no counts, no entry-token clobber
            counts_new = counts_e.at[jnp.arange(B), res.token].add(1)
            counts = lax.dynamic_update_index_in_dim(
                counts, jnp.where(real, counts_new, counts_e), e, axis=0
            )
            keys = lax.dynamic_update_index_in_dim(
                keys, jnp.where(real, jax.random.key_data(key), old_key), e, axis=0
            )
            tok_e = lax.dynamic_index_in_dim(tokens, e, keepdims=False)
            tokens = lax.dynamic_update_index_in_dim(
                tokens, jnp.where(real, res.token, tok_e), e, axis=0
            )

            # hand hidden states (and their position/liveness/lap) one hop
            # around; crossing the PP-1 -> 0 seam advances the lap counter
            perm = [(p, (p + 1) % PP) for p in range(PP)]
            x_next = lax.ppermute(x_out, AXIS_PP, perm)
            pos_next = lax.ppermute(pos_in, AXIS_PP, perm)
            live_next = lax.ppermute(live_in, AXIS_PP, perm)
            phase_next = lax.ppermute(
                phase_in + (my_pp == PP - 1).astype(jnp.int32), AXIS_PP, perm
            )
            return (x_next, pos_next, live_next, phase_next, kv, tokens,
                    pos_vec, keys, counts), res

        (x, pos_x, live_x, phase_x, kv, tokens, pos_vec, keys, counts), results = (
            lax.scan(
                step,
                (x, pos_x, live_x, phase_x, kv, tokens, pos_vec, keys, counts),
                jnp.arange(n_steps, dtype=jnp.int32),
            )
        )
        return (results, x[None], kv, tokens, pos_vec, pos_x[None, None],
                live_x[None, None], phase_x[None, None], keys, counts)

    fn = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    jitted = jax.jit(fn, donate_argnums=(2, 3, 4, 5, 6, 7, 8, 15, 16))
    kinds_arr = (
        model.layer_kinds if has_kinds else jnp.zeros((), dtype=jnp.int32)
    )

    def call(window_params, edge_params, x_state, kv, tokens, pos_vec,
             pos_state, live_state, phase_state, entry_open, enter_live,
             entry_slot, exit_valid, exit_slot, sp_stack, keys, counts, t0):
        return jitted(window_params, edge_params, x_state, kv, tokens, pos_vec,
                      pos_state, live_state, phase_state, entry_open,
                      enter_live, entry_slot, exit_valid, exit_slot, sp_stack,
                      keys, counts, jnp.int32(t0), kinds_arr)

    return call


def make_slot_prefill_fn(model, mesh: Mesh, window_params, n_slots: int, batch: int = 1):
    """Sequential ring pass (parallel/ring.py schedule) writing ONE slot's KV.

    (window_params, edge_params, tokens[B,T], kv, pos, last_idx, slot, lane)
      -> (logits[B,V], kv)

    `slot` is lane-local; `lane` selects the dp lane that owns the request —
    every lane traces the same pass (SPMD), but only the owning lane's
    kv_commit fires and only its logits survive the dp broadcast.
    """
    PP = mesh.shape[AXIS_PP]
    B = batch
    phases = getattr(model, "ring_phases", 1)
    has_kinds = getattr(model, "layer_kinds", None) is not None
    sp_axis = AXIS_SP if mesh.shape.get(AXIS_SP, 1) > 1 else None
    in_specs = (
        window_param_specs(window_params),
        P(),
        P(),  # tokens [B, T] replicated: every lane traces the same pass
        kv_spec(sp_axis is not None), P(), P(), P(), P(),
        P(AXIS_PP) if has_kinds else P(),
    )
    out_specs = (P(), kv_spec(sp_axis is not None))

    def spmd(window_params, edge_params, tokens, kv, pos, last_idx, slot, lane,
             kinds):
        my_pp = lax.axis_index(AXIS_PP)
        mine = lax.axis_index(AXIS_DP) == lane
        kv_slot = jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(a, slot * B, B, axis=1), kv
        )
        x = model.embed(edge_params, tokens)
        x = lax.pcast(x, (AXIS_PP, AXIS_DP), to="varying")

        def stage_iter(i, carry):
            x, kv_slot = carry
            # segmented models take `phases` laps (lap p applies every
            # rank's slice of segment p — parallel/ring.py's schedule)
            extra = {"phase": i // PP} if phases > 1 else {}
            x_new, kv_slot = model.apply_window(
                window_params, x, kv_slot, pos,
                layer_kinds=kinds, tp_axis=AXIS_TP,
                kv_commit=(jnp.mod(i, PP) == my_pp) & mine,
                sp_axis=sp_axis, t_real=last_idx + 1, **extra,
            )
            x_next = lax.ppermute(
                x_new, AXIS_PP, [(p, (p + 1) % PP) for p in range(PP)]
            )
            return (x_next, kv_slot)

        x, kv_slot = lax.fori_loop(0, phases * PP, stage_iter, (x, kv_slot))
        kv = jax.tree.map(
            lambda full, sl: lax.dynamic_update_slice_in_dim(
                full, sl, slot * B, axis=1
            ),
            kv, kv_slot,
        )
        x_last = lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        x_last = model.normalize(edge_params, x_last)
        logits = model.lm_project(edge_params, x_last)
        logits = _bcast_from_rank(logits, AXIS_PP, 0)
        # keep the owning lane's logits and replicate (bcast, not identity)
        logits = lax.psum(jnp.where(mine, logits, jnp.zeros_like(logits)), AXIS_DP)
        return logits[:, 0], kv

    fn = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    jitted = jax.jit(fn, donate_argnums=(3,))
    kinds_arr = (
        model.layer_kinds if has_kinds else jnp.zeros((), dtype=jnp.int32)
    )

    def call(window_params, edge_params, tokens, kv, pos, last_idx, slot, lane=0):
        return jitted(window_params, edge_params, tokens, kv, jnp.int32(pos),
                      jnp.int32(last_idx), jnp.int32(slot), jnp.int32(lane),
                      kinds_arr)

    return call


class PipelinedMeshEngine:
    """BatchedEngine-compatible surface over the rotation program.

    M slots serve up to M concurrent requests; `decode_batch` runs rotations
    until every pending request has a result (steady state: exactly one).
    Drop-in behind BatchedLocalAdapter — continuous batching ACROSS the
    pipeline, the scheduler the sequential mesh ring lacks
    (VERDICT.md "MeshEngine pipeline is (PP-1)/PP idle").
    """

    def __init__(
        self,
        model_dir,
        pp: int = 0,
        tp: int = 1,
        sp: int = 1,
        dp: int = 1,
        slots: int = 0,
        max_seq: int = 2048,
        param_dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        kv_quant_bits: int = 0,
        weight_quant_bits: int = 0,
        quant_group: int = 0,
        devices: Optional[Sequence] = None,
        prefix_cache_size: int = 0,
    ):
        import numpy as np

        from dnet_tpu.parallel.engine import MeshEngine

        # resolve pp before sizing the slot pool (shared helper: the serving
        # manager's precheck must agree with this engine's resolution)
        if pp <= 0:
            import json
            from pathlib import Path as _Path

            n_dev = len(list(devices) if devices is not None else jax.devices())
            L = json.loads(
                (_Path(model_dir) / "config.json").read_text()
            )["num_hidden_layers"]
            pp = resolve_pp(n_dev, tp * dp, sp, L)
        # dp shards SLOTS: dp lanes each run the same per-lane schedule over
        # M_local slots (global slot = lane * M_local + local) — capacity
        # scales linearly, the schedule stays lane-invariant
        self.dp = dp = max(dp, 1)
        self.n_slots = M = slots if slots > 0 else pp * dp
        if M % dp != 0:
            raise ValueError(f"slots={M} must be divisible by dp={dp}")
        self.m_local = M_local = M // dp
        if M_local < pp:
            raise ValueError(
                f"slots={M} gives {M_local} per dp lane; need >= pp={pp} "
                f"to fill the pipeline"
            )
        self.slot_batch = B = 1
        # the inner MeshEngine loads/shards params and builds the kv template
        # with batch = dp*M_local*B (lanes x slots folded into the batch axis,
        # lane-major so the dp sharding blocks align with global slot ids)
        self._inner = MeshEngine(
            model_dir, pp=pp, tp=tp, dp=dp, sp=sp, batch=M_local * B,
            max_seq=max_seq,
            param_dtype=param_dtype, kv_dtype=kv_dtype,
            kv_quant_bits=kv_quant_bits, weight_quant_bits=weight_quant_bits,
            quant_group=quant_group, devices=devices,
        )
        inner = self._inner
        if not inner.model.supports_kv_commit:
            raise NotImplementedError(
                f"pipelined serving not supported for "
                f"{inner.config.model_type} (no gated KV writes yet)"
            )
        self.config, self.model, self.mesh = inner.config, inner.model, inner.mesh
        self.pp, self.tp, self.sp = inner.pp, inner.tp, inner.sp
        # segmented models (deepseek ring_phases=2) take `phases` laps per
        # token: one rotation is M*phases stage-steps and still yields one
        # entry + one exit per slot (the multi-lap schedule's entry bursts
        # cycle the slots round-robin — see _entry_open/_entry_slot)
        self.phases = getattr(inner.model, "ring_phases", 1)
        self.max_seq = max_seq
        self.window_params, self.edge_params = inner.window_params, inner.edge_params

        # rotation programs cached per fused-rotation count R (R*M_local
        # stage steps per dispatch); R=1 built eagerly, larger on demand
        self._host_window_ref = inner._host_window
        self._rot_fns = {
            1: make_rotation_fn(
                self.model, self.mesh, inner._host_window, M_local, B
            )
        }
        self._prefill_fn = make_slot_prefill_fn(
            self.model, self.mesh, inner._host_window, M_local, B
        )

        from jax.sharding import NamedSharding

        D = self.config.hidden_size
        V = self.config.vocab_size
        lane_sh = NamedSharding(self.mesh, P(AXIS_DP))  # slot-major over lanes
        self.x_state = jax.device_put(
            jnp.zeros((self.pp, dp * B, 1, D), dtype=jnp.dtype(param_dtype)),
            NamedSharding(self.mesh, P(AXIS_PP, AXIS_DP)),
        )
        self.kv = inner._kv_template  # [L, dp*M_local*B, S, ...] mesh-sharded
        self.tokens = jax.device_put(jnp.zeros((M, B), dtype=jnp.int32), lane_sh)
        self.pos_vec = jax.device_put(jnp.zeros((M,), dtype=jnp.int32), lane_sh)
        pp_dp = NamedSharding(self.mesh, P(AXIS_PP, AXIS_DP))
        self.pos_state = jax.device_put(
            jnp.zeros((self.pp, dp), dtype=jnp.int32), pp_dp
        )
        self.live_state = jax.device_put(
            jnp.zeros((self.pp, dp), dtype=bool), pp_dp
        )
        self.phase_state = jax.device_put(
            jnp.zeros((self.pp, dp), dtype=jnp.int32), pp_dp
        )
        self.keys = jax.device_put(jnp.zeros((M, 2), dtype=jnp.uint32), lane_sh)
        self.counts = jax.device_put(
            jnp.zeros((M, B, V), dtype=jnp.int32), lane_sh
        )
        self.t0 = 0

        self.slot_of: Dict[str, int] = {}
        self._free = list(range(M))
        self.slot_pos = np.zeros(M, dtype=np.int64)  # host mirror of pos_vec
        self._dec: Dict[int, "DecodingParams"] = {}  # slot -> sampling params
        self._entries: Dict[int, list] = {i: [] for i in range(M)}  # entry steps
        self._buffer: Dict[str, list] = {}  # nonce -> ready SampleResults
        self._last_used: Dict[str, float] = {}  # nonce -> wall time (TTL sweep)
        self.prefix_cache = None
        if prefix_cache_size > 0:
            # snapshots are SLOT-ROW slices of the shared cache ([L, B, S,
            # ...], mesh-sharded): restore writes the rows back into
            # whichever slot the new request lands on
            from dnet_tpu.core.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(prefix_cache_size)
        # dispatched-but-unread rotation chunks: (deliveries [(j, nonce)],
        # stacked SampleResult device arrays) — reads drain in dispatch order,
        # overlapping the next chunk's compute
        self._pending_rot: list = []
        self._np = np

    token_result = None  # set after class body (LocalEngine staticmethod)

    @property
    def sessions(self):
        return self.slot_of

    # ---- slots --------------------------------------------------------
    def _alloc(self, nonce: str) -> int:
        if nonce in self.slot_of:
            return self.slot_of[nonce]
        if not self._free:
            raise RuntimeError(f"no free pipeline slots (capacity {self.n_slots})")
        slot = self._free.pop(0)
        self.slot_of[nonce] = slot
        self._entries[slot] = []
        self._buffer[nonce] = []
        self._last_used[nonce] = time.time()
        return slot

    def end_session(self, nonce: str) -> None:
        slot = self.slot_of.pop(nonce, None)
        self._buffer.pop(nonce, None)
        self._last_used.pop(nonce, None)
        if slot is not None:
            self._dec.pop(slot, None)
            self._entries[slot] = []
            self._free.append(slot)

    def reset(self) -> None:
        for nonce in list(self.slot_of):
            self.end_session(nonce)

    def close(self) -> None:
        self.reset()

    def sweep_sessions(self, ttl_s: float = 600.0) -> int:
        """Free slots whose nonce has been idle past the TTL — a client that
        disconnected without adapter cleanup must not pin a slot forever
        (at capacity, _alloc fails for every new request)."""
        now = time.time()
        dead = [
            n for n, t in self._last_used.items()
            if now - t > ttl_s and n in self.slot_of
        ]
        for n in dead:
            self.end_session(n)
        return len(dead)

    # ---- serving ------------------------------------------------------
    def prefill_and_sample(self, nonce, prompt_ids, decoding) -> SampleResult:
        from dnet_tpu.core.engine import bucket_length
        from dnet_tpu.core.types import DecodingParams  # noqa: F401

        np = self._np
        full_ids = list(prompt_ids)
        T_total = len(full_ids)
        if T_total == 0:
            raise ValueError("empty prompt")
        if T_total >= self.max_seq:
            raise ValueError(
                f"prompt length {T_total} exceeds max_seq {self.max_seq}"
            )
        slot = self._alloc(nonce)
        B = self.slot_batch
        base, rest = 0, full_ids
        if self.prefix_cache is not None:
            hit = self.prefix_cache.lookup(full_ids)
            if hit is not None:
                base, kv_row = hit  # >= 1 token left by construction
                self.kv = jax.tree.map(
                    lambda big, row: big.at[:, slot * B : (slot + 1) * B].set(
                        row.astype(big.dtype)
                    ),
                    self.kv, kv_row,
                )
                rest = full_ids[base:]
        T = len(rest)
        Tpad = min(bucket_length(T), self.max_seq - base)
        tokens = np.zeros((B, Tpad), dtype=np.int32)
        tokens[:, :T] = np.asarray(rest, dtype=np.int32)
        lane, local = divmod(slot, self.m_local)
        logits, self.kv = self._prefill_fn(
            self.window_params, self.edge_params, jnp.asarray(tokens),
            self.kv, base, T - 1, local, lane,
        )
        if self.prefix_cache is not None:
            self.prefix_cache.store(
                full_ids,
                jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, slot * B, B, axis=1),
                    self.kv,
                ),
            )
        seed = decoding.seed
        if seed is None:
            seed = int.from_bytes(__import__("os").urandom(4), "little")
        res, key, counts0 = sample_with_counts(
            logits, decoding, jax.random.key(seed),
            jnp.zeros((B, self.config.vocab_size), dtype=jnp.int32),
        )
        # inject: the sampled token is this slot's first pipeline entry
        self.tokens = self.tokens.at[slot].set(res.token)
        self.pos_vec = self.pos_vec.at[slot].set(T_total)
        self.keys = self.keys.at[slot].set(jax.random.key_data(key))
        self.counts = self.counts.at[slot].set(counts0)
        # kill the slot's stale in-flight token: between rotations, rank r
        # carries the token that entered at te = t0 - r - PP*lap (exactly one
        # lap makes te an entry-open step) — its live flag must not let old
        # garbage commit KV into the rows this prefill just wrote.  The
        # schedule is lane-local, so the match is against the LOCAL slot and
        # the kill lands on this lane's column of live_state.
        for r in range(self.pp):
            for p in range(self.phases):
                te = self.t0 - r - self.pp * p
                if (
                    te >= 0
                    and _entry_open(te, self.pp, self.phases)
                    and _entry_slot(te, self.pp, self.phases, self.m_local) == local
                ):
                    self.live_state = self.live_state.at[r, lane].set(False)
        self.slot_pos[slot] = T_total
        self._dec[slot] = decoding
        return res

    def _sp_stack(self) -> SampleParams:
        np = self._np
        M = self.n_slots
        temp = np.zeros(M, dtype=np.float32)
        top_p = np.ones(M, dtype=np.float32)
        top_k = np.zeros(M, dtype=np.int32)
        min_p = np.zeros(M, dtype=np.float32)
        rep = np.ones(M, dtype=np.float32)
        mtk = np.ones(M, dtype=np.int32)
        b_ids = np.full((M, MAX_LOGIT_BIAS), -1, dtype=np.int32)
        b_vals = np.zeros((M, MAX_LOGIT_BIAS), dtype=np.float32)
        for slot, dec in self._dec.items():
            temp[slot] = dec.temperature
            top_p[slot] = dec.top_p
            top_k[slot] = dec.top_k
            min_p[slot] = dec.min_p
            rep[slot] = dec.repetition_penalty
            mtk[slot] = dec.min_tokens_to_keep
            b_ids[slot], b_vals[slot] = encode_logit_bias(dec.logit_bias)
        return SampleParams(
            jnp.asarray(temp), jnp.asarray(top_p), jnp.asarray(top_k),
            jnp.asarray(min_p), jnp.asarray(rep), jnp.asarray(mtk),
            jnp.asarray(b_ids), jnp.asarray(b_vals),
        )

    # fused-rotation widths tried largest-first (one compiled program per
    # width actually used, same bounded-bucket discipline as
    # LocalEngine.DECODE_CHUNK_BUCKETS)
    ROTATION_BUCKETS = (8, 4, 2, 1)

    def _rot_fn(self, R: int):
        fn = self._rot_fns.get(R)
        if fn is None:
            fn = make_rotation_fn(
                self.model, self.mesh, self._host_window_ref,
                self.m_local, self.slot_batch,
                n_steps=R * self.m_local * self.phases,
            )
            self._rot_fns[R] = fn
        return fn

    def _dispatch_chunk(self, R: int) -> None:
        """Dispatch (async) R fused rotations: R*M*phases stage-steps, one
        XLA program, sampled tokens re-entering their slots on device.  The
        delivery schedule (which exit step belongs to which nonce) is
        simulated host-side at dispatch time — it depends only on the entry
        bookkeeping, never on token VALUES, so the packed results can be
        read later (overlapping the next chunk's compute)."""
        np = self._np
        M_local, PP, phases, DP = self.m_local, self.pp, self.phases, self.dp
        PHI = phases * PP
        nonce_of = {s: n for n, s in self.slot_of.items()}
        sim = {m: list(self._entries[m]) for m in range(self.n_slots)}
        pos_sim = self.slot_pos.copy()
        deliveries = []  # (step index j, lane, nonce at dispatch time)
        n_steps = R * M_local * phases
        entry_open = np.zeros(n_steps, dtype=bool)
        enter_live = np.zeros((DP, n_steps), dtype=bool)
        entry_slot = np.zeros(n_steps, dtype=np.int32)
        exit_valid = np.zeros(n_steps, dtype=bool)
        exit_slot = np.zeros(n_steps, dtype=np.int32)
        for j in range(n_steps):
            t = self.t0 + j
            te = t - (PHI - 1)  # exit latency: phases laps of PP hops
            if te >= 0 and _entry_open(te, PP, phases):
                e_local = _entry_slot(te, PP, phases, M_local)
                exit_valid[j] = True
                exit_slot[j] = e_local
                # every dp lane exits its own slot at this step
                for lane in range(DP):
                    g = lane * M_local + e_local
                    ent = sim[g]
                    if ent and ent[0] == te:
                        ent.pop(0)
                        if g in nonce_of:
                            deliveries.append((j, lane, nonce_of[g]))
            if _entry_open(t, PP, phases):
                n_local = _entry_slot(t, PP, phases, M_local)
                entry_open[j] = True
                entry_slot[j] = n_local
                # a live slot below capacity feeds one real token this step;
                # lane d's device consumes enter_live[d, j] in its scan
                for lane in range(DP):
                    g = lane * M_local + n_local
                    if g in nonce_of and pos_sim[g] < self.max_seq:
                        enter_live[lane, j] = True
                        sim[g].append(t)
                    # pos_vec advances unconditionally at the entry step
                    # (device mirrors this); gated KV commits make
                    # dead-slot writes inert
                    pos_sim[g] += 1
        (results, self.x_state, self.kv, self.tokens, self.pos_vec,
         self.pos_state, self.live_state, self.phase_state, self.keys,
         self.counts) = self._rot_fn(R)(
            self.window_params, self.edge_params, self.x_state, self.kv,
            self.tokens, self.pos_vec, self.pos_state, self.live_state,
            self.phase_state, jnp.asarray(entry_open), jnp.asarray(enter_live),
            jnp.asarray(entry_slot), jnp.asarray(exit_valid),
            jnp.asarray(exit_slot), self._sp_stack(), self.keys, self.counts,
            self.t0,
        )
        self._pending_rot.append((deliveries, results))
        self._entries = sim
        # pos_sim IS the device pos_vec mirror; for phases>1 with
        # n_slots % pp != 0 the entry bursts do NOT distribute exactly R
        # entries per slot per chunk, so a blanket += R would desync
        self.slot_pos = pos_sim
        self.t0 += n_steps

    def _drain_pending(self) -> None:
        """Read every dispatched-but-unread chunk (ONE packed device->host
        transfer per chunk) and route tokens to their nonce buffers.  A
        nonce that ended between dispatch and drain has no buffer entry —
        its tokens are dropped, exactly like LocalAdapter's aborted-chunk
        leftovers."""
        np = self._np
        B = self.slot_batch
        while self._pending_rot:
            deliveries, results = self._pending_rot.pop(0)
            toks = np.asarray(results.token)  # [n_steps, DP*B]
            lps = np.asarray(results.logprob)
            tts = np.asarray(results.top_tokens)
            tlps = np.asarray(results.top_logprobs)
            for j, lane, nonce in deliveries:
                if nonce in self._buffer:
                    sl = slice(lane * B, (lane + 1) * B)
                    self._buffer[nonce].append(
                        SampleResult(toks[j, sl], lps[j, sl], tts[j, sl], tlps[j, sl])
                    )

    def decode_batch(
        self, requests, budgets: Optional[Dict[str, Optional[int]]] = None
    ) -> Tuple[Dict[str, SampleResult], Dict[str, str]]:
        """One result per requested nonce; `budgets` (nonce -> remaining
        tokens the driver will accept, None = unknown) widens the dispatch:
        R fused rotations produce R tokens per slot in one program, the
        extras resolving later decode_batch calls instantly from the
        buffers.  Without budgets the behavior is the r2 one-rotation step.
        """
        errors: Dict[str, str] = {}
        order: Dict[str, int] = {}
        for nonce, (_tok, dec) in requests.items():
            slot = self.slot_of.get(nonce)
            if slot is None:
                errors[nonce] = f"request {nonce!r} has no pipeline slot (cancelled?)"
                continue
            self._dec[slot] = dec
            order[nonce] = slot
            self._last_used[nonce] = time.time()
        if not order:
            return {}, errors

        def can_progress(nonce: str) -> bool:
            """More tokens can still arrive: capacity to enter, in flight,
            or dispatched-but-unread."""
            slot = order[nonce]
            return (
                self.slot_pos[slot] < self.max_seq
                or bool(self._entries[slot])
                or bool(self._pending_rot)
            )

        def pick_R(missing) -> int:
            """Largest fused-rotation width no request would overshoot:
            bounded by the smallest remaining budget MINUS that nonce's
            in-flight ring entries (each will deliver a token before any new
            entry from this chunk does) and by seq capacity."""
            if not budgets:
                return 1
            cap = min(
                max((budgets.get(n) or 1) - len(self._entries[order[n]]), 1)
                for n in missing
            )
            cap = min(cap, *(int(self.max_seq - self.slot_pos[order[n]])
                             for n in missing))
            return next((b for b in self.ROTATION_BUCKETS if b <= cap), 1)

        # steady state: one rotation yields one token per active slot; a
        # freshly prefilled slot needs a second (its first entry is mid-ring)
        for _ in range(3):
            self._drain_pending()
            missing = [n for n in order if not self._buffer.get(n)]
            if not missing or not any(can_progress(n) for n in missing):
                break
            self._dispatch_chunk(pick_R(missing))
        self._drain_pending()
        out: Dict[str, SampleResult] = {}
        for nonce, slot in order.items():
            buf = self._buffer.get(nonce)
            if buf:
                # buffered tokens generated before capacity are still valid
                out[nonce] = buf.pop(0)
            elif self.slot_pos[slot] >= self.max_seq:
                errors[nonce] = (
                    f"sequence length {self.slot_pos[slot]} reached max_seq "
                    f"{self.max_seq}"
                )
                self.end_session(nonce)
            else:
                errors[nonce] = "pipeline produced no token (stall)"
        return out, errors

    def generate(self, prompt_ids, decoding=None, max_tokens=256,
                 eos_token_ids=None, nonce="pipelined"):
        from dnet_tpu.core.types import DecodingParams

        decoding = decoding or DecodingParams()
        eos = eos_token_ids or set()
        self.end_session(nonce)
        res = self.prefill_and_sample(nonce, prompt_ids, decoding)
        token = int(res.token[0])
        yield self.token_result(nonce, res, step=0, decoding=decoding)
        if token in eos:
            self.end_session(nonce)
            return
        for step in range(1, max_tokens):
            if self.slot_pos[self.slot_of[nonce]] >= self.max_seq:
                break
            res_map, errs = self.decode_batch(
                {nonce: (token, decoding)},
                budgets={nonce: max_tokens - step},
            )
            if errs:
                raise RuntimeError(errs[nonce])
            row = res_map[nonce]
            token = int(row.token[0])
            yield self.token_result(nonce, row, step=step, decoding=decoding)
            if token in eos:
                break
        self.end_session(nonce)


def _bind_token_result():
    from dnet_tpu.core.engine import LocalEngine

    PipelinedMeshEngine.token_result = staticmethod(LocalEngine.token_result)


_bind_token_result()
