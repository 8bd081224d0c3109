"""In-slice pipelined-ring execution: the whole ring in ONE XLA program.

The reference moves activations shard-to-shard with gRPC frames
(src/dnet/shard/adapters/ring.py:241-299).  When the "shards" are chips of
one TPU slice, the entire per-token pipeline compiles into a single
shard_map program: each pp-rank applies its contiguous stage of layers, and
the hidden state hops to the next rank with `lax.ppermute` over ICI — no
serialization, no host round-trips.  Tensor parallelism nests inside each
stage (psum seams in the model), data parallelism replicates the whole ring.

Pipelining model: for a single in-flight token the ring runs PP sequential
stage-steps (other ranks compute garbage that is masked out of KV); with S
concurrent sequences the same program reaches steady state where every rank
does real work every step (classic pipelined-ring round-robin, the analog of
the reference's k-round schedule, src/dnet/api/utils.py:62-131).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dnet_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
    kv_spec,
    window_param_specs,
)


def _ring_spmd(model, mesh: Mesh, window_params, full_logits: bool = False,
               hidden_out: bool = False):
    """Construct the shard_map'd single-token ring step (un-jitted) and its
    layer-kinds operand.  Shared by the per-step fn (make_ring_decode_fn),
    the chunked-scan fn (make_ring_chunk_fn), the speculative verify fn
    (make_ring_spec_fn, full_logits=True: every position projected), and
    the embeddings fn (make_ring_hidden_fn, hidden_out=True: final-norm'd
    hidden states instead of the lm projection)."""
    PP = mesh.shape[AXIS_PP]
    phases = getattr(model, "ring_phases", 1)
    # sequence parallelism: KV shards over sp; queries/hidden replicate and
    # attention runs as ring/flash-decoding with one LSE combine per layer
    sp_axis = AXIS_SP if mesh.shape.get(AXIS_SP, 1) > 1 else None

    # mixed-attention models (gpt_oss) carry a per-layer kind array that must
    # shard over pp alongside the layer-stacked params
    has_kinds = getattr(model, "layer_kinds", None) is not None
    in_specs = (
        window_param_specs(window_params),
        P(),  # edge params replicated
        P(AXIS_DP, None),  # tokens [B, T]
        kv_spec(sp_axis is not None),  # pytree prefix: every kv leaf (incl. scales)
        P(),  # pos scalar
        P(),  # last_idx scalar
        P(AXIS_PP) if has_kinds else P(),
    )
    logits_spec = (
        P(AXIS_DP, None, None) if (full_logits or hidden_out) else P(AXIS_DP, None)
    )
    out_specs = (logits_spec, kv_spec(sp_axis is not None))

    def spmd(window_params, edge_params, tokens, kv, pos, last_idx, kinds):
        my_pp = lax.axis_index(AXIS_PP)

        # Stage 0 embeds; everyone runs the embed (cheap) but only rank 0's
        # x is "real" at iteration 0.
        x = model.embed(edge_params, tokens)
        # x becomes device-varying over pp once layer-sharded params touch
        # it (over tp it stays value-invariant thanks to the psum seams);
        # mark the loop carry so the carry types line up.
        x = lax.pcast(x, AXIS_PP, to="varying")

        def stage_iter(i, carry):
            x, kv = carry
            # KV only commits on the rank whose turn it is (garbage copies
            # on other ranks must not pollute their caches); the gate is
            # O(T) inside the layer, not an O(S) whole-cache select.
            extra = {"phase": i // PP} if phases > 1 else {}
            x_new, kv = model.apply_window(
                window_params, x, kv, pos,
                layer_kinds=kinds, tp_axis=AXIS_TP,
                kv_commit=(jnp.mod(i, PP) == my_pp),
                sp_axis=sp_axis, t_real=last_idx + 1, **extra,
            )
            # hand the hidden state to the next pipeline rank (ICI hop)
            x_next = lax.ppermute(
                x_new, AXIS_PP, [(p, (p + 1) % PP) for p in range(PP)]
            )
            return (x_next, kv)

        x, kv = lax.fori_loop(0, phases * PP, stage_iter, (x, kv))
        # after PP hops the processed x is back on rank 0; ranks agree via
        # the ppermute ring, and rank 0 holds the final hidden state.
        if hidden_out:
            # embeddings path: every position's final-norm'd hidden state
            xs = model.normalize(edge_params, x)
            return _bcast_from_rank0(xs, AXIS_PP), kv
        if full_logits:
            # spec verify needs every position's argmax; T is tiny (L+1)
            xs = model.normalize(edge_params, x)
            logits = model.lm_project(edge_params, xs)  # [B, T, V]
            return _bcast_from_rank0(logits, AXIS_PP), kv
        x_last = lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        x_last = model.normalize(edge_params, x_last)
        logits = model.lm_project(edge_params, x_last)
        # Replicate rank 0's logits across pp (out_specs say logits are not
        # sharded over pp; only rank 0 holds the real value after the loop).
        logits = _bcast_from_rank0(logits, AXIS_PP)
        return logits[:, 0], kv

    fn = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    kinds_arr = model.layer_kinds if has_kinds else jnp.zeros((), dtype=jnp.int32)
    return fn, kinds_arr


def make_ring_decode_fn(model, mesh: Mesh, window_params, donate_kv: bool = True):
    """Build a jitted single-program ring decode step.

    Signature of the returned fn:
      (window_params, edge_params, tokens[B,1] int32, kv, pos) -> (logits[B,V], kv)

    window_params: stacked over ALL model layers [L, ...], sharded
      (pp shards the layer axis into contiguous stages, tp the head/ffn dims)
      — passed here only for spec construction (flat or segmented layout).

    Models with `ring_phases > 1` (deepseek: dense/moe segments) run that
    many laps around the ring, applying one segment per lap, so the global
    layer order is preserved even though each rank holds a slice of every
    segment.
    """
    fn, kinds_arr = _ring_spmd(model, mesh, window_params)
    donate = (3,) if donate_kv else ()
    jitted = jax.jit(fn, donate_argnums=donate)

    def call(window_params, edge_params, tokens, kv, pos, last_idx=None):
        if last_idx is None:
            last_idx = jnp.int32(tokens.shape[1] - 1)
        return jitted(window_params, edge_params, tokens, kv, pos, last_idx, kinds_arr)

    return call


def make_ring_chunk_fn(model, mesh: Mesh, window_params):
    """Chunked-scan mesh decode: K ring steps + on-device sampling fused
    into ONE XLA program (the multi-chip analog of LocalEngine's
    decode_chunk, core/engine.py — same packed-result, device-chained-token
    contract, so LocalEngine's dispatch/read methods drive it unchanged).

    Per-token the served mesh path previously paid one full program dispatch
    + one host read (parallel/engine.py r2, the dispatch gap VERDICT flagged);
    here the sampled token feeds the next ring step on-device and the host
    pays one dispatch + one packed transfer per K tokens.  Sampling sits
    OUTSIDE shard_map at the global-batch level, so key evolution and noise
    shapes match the per-step path exactly (chunked and unchunked streams
    are identical for a given seed)."""
    from dnet_tpu.core.sampler import pack_chunk_results, sample

    ring, kinds_arr = _ring_spmd(model, mesh, window_params)

    def chunk(window_params, edge_params, token, kv, pos, sp, key, counts,
              n_steps, plan=None):
        def body(carry, _):
            tok, kv, pos, key, counts = carry
            key, step_key = jax.random.split(key)
            logits, kv = ring(
                window_params, edge_params, tok, kv, pos, jnp.int32(0), kinds_arr
            )
            res = sample(logits, sp, step_key, token_counts=counts, plan=plan)
            counts = counts.at[jnp.arange(counts.shape[0]), res.token].add(1)
            return (res.token[:, None], kv, pos + 1, key, counts), res

        (last_tok, kv, _, key, counts), results = jax.lax.scan(
            body, (token, kv, pos, key, counts), None, length=n_steps
        )
        packed = pack_chunk_results(results, plan is None or plan.logprobs)
        return packed, last_tok, kv, key, counts

    return jax.jit(chunk, static_argnums=(8, 9), donate_argnums=(3, 7))


def make_ring_spec_fn(model, mesh: Mesh, window_params, lookahead: int):
    """Speculative verify block through the mesh ring: draft `lookahead`
    tokens by prompt-lookup, run ONE ring pass over the [tok, drafts]
    block (L+1 positions instead of 1 — the extra positions ride the same
    PP stage-steps and ICI hops), greedily accept the agreeing prefix.

    Keeps LocalEngine's `_spec_step` contract
    ((wp, ep, tok, hist, kv, pos) -> (out, hist, kv), out[:, i] == -1
    beyond the accepted prefix), so LocalEngine.decode_spec and the
    serving adapter's spec path drive the mesh engine unchanged.
    Drafting/acceptance run at the global-batch level outside shard_map,
    exactly like chunked sampling (make_ring_chunk_fn)."""
    from dnet_tpu.core.spec import accept_drafts, commit_history, ngram_draft

    ring_full, kinds_arr = _ring_spmd(model, mesh, window_params, full_logits=True)
    L = int(lookahead)

    def spec_step(window_params, edge_params, tok, hist, kv, pos):
        hist = commit_history(hist, pos, tok, jnp.int32(1))
        drafts = ngram_draft(hist, pos + 1, L)  # [B, L]
        hist = commit_history(hist, pos + 1, drafts, jnp.int32(L))
        block = jnp.concatenate([tok, drafts], axis=1)  # [B, L+1]
        logits, kv = ring_full(
            window_params, edge_params, block, kv, pos, jnp.int32(L), kinds_arr
        )
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        _, out = accept_drafts(preds, drafts)
        return out, hist, kv

    return jax.jit(spec_step, donate_argnums=(3, 4))


def make_ring_hidden_fn(model, mesh: Mesh, window_params):
    """One ring pass returning final-norm'd hidden states [B, T, D] —
    the embeddings primitive for mesh-served models (the twin of
    LocalEngine.hidden_states).  KV is a throwaway: not donated, caller
    discards it."""
    fn, kinds_arr = _ring_spmd(model, mesh, window_params, hidden_out=True)
    jitted = jax.jit(fn)

    def call(window_params, edge_params, tokens, kv, pos, last_idx):
        return jitted(
            window_params, edge_params, tokens, kv, pos, last_idx, kinds_arr
        )

    return call


def _bcast_from_rank0(x, axis_name: str):
    """Replicate rank 0's value across the axis (psum of masked value)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == 0, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def place_ring_state(window_params, edge_params, kv, mesh: Mesh):
    """Device_put params/caches with ring shardings (host -> mesh)."""
    from dnet_tpu.parallel.mesh import replicate, shard_window_params

    sp = mesh.shape[AXIS_SP] > 1
    wp = shard_window_params(window_params, mesh)
    ep = replicate(edge_params, mesh)
    kvp = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, kv_spec(sp))), kv
    )
    return wp, ep, kvp
