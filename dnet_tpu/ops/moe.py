"""Mixture-of-experts token dispatch: capacity routing + expert parallelism.

The reference computes GPT-OSS MoE experts densely per hosted layer — every
token multiplies every expert's weights and the router's scattered scores
mask the sum (src/dnet/core/models/gpt_oss.py:171-214); it has no expert
parallelism at all (SURVEY.md §2.8: "EP ... absent").  Dense compute wastes
an E/k factor of MXU FLOPs at prefill size.  This module is the TPU-first
redesign: capacity-based token dispatch (GShard/Switch semantics) so each
expert computes only the tokens routed to it, and a true expert-parallel
path where `lax.all_to_all` routes per-expert token buffers between ranks
over ICI.

Four interchangeable compute paths over the same routed-FFN semantics:

- dense       every token x every (local) expert; exact.  A pass costs the
              read of EVERY held expert's weights whatever the rows, so it
              is as cheap as anything only at or under the ridge
              (RIDGE_ROWS) and only where the rows choose most of the held
              experts anyway.
- grouped     the (token, slot) assignments sorted by expert, the rows
              gathered, and gate / up / down run as grouped matmuls with
              per-expert group sizes (`grouped_matmul`): exact, nothing
              dropped, work proportional to the rows and weights read only
              for the experts some row chose.  What `auto` picks on one
              rank above the ridge, and under it where the routing leaves
              enough held experts untouched (`resolve_moe_impl`: a decode
              step of 16 lanes x top-10 over 512 routed reads 0.27 of
              them).
- dispatch    scatter tokens into per-expert capacity buffers [E, C, D], run
              the FFN once over the buffers, gather back weighted by the
              router probs.  FLOPs drop from N*E*ffn to E*C*ffn ~= k*cf*N*ffn.
              Tokens routed beyond an expert's capacity are dropped (standard
              MoE capacity semantics); capacity_factor <= 0 selects the exact
              no-drop capacity C = N (tests / small shapes).
- a2a         expert parallelism over a mesh axis: tokens sharded over the
              axis, experts sharded over the same axis.  Each rank scatters
              its token slice into [E, C, D]; `all_to_all` hands each expert
              owner its buffers ([E/R, R*C, D]); local FFN; reverse
              `all_to_all`; local weighted gather.  The hop rides ICI inside
              the jitted program — no wire format, no serialization.

All shapes are static (capacity is a Python int), so every path jits and
scans cleanly.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Callable, Optional

import jax.numpy as jnp
from jax import lax



def expert_capacity(n_tokens: int, n_experts: int, k: int, factor: float) -> int:
    """Per-expert token capacity C (static).  factor <= 0 -> exact (C = n)."""
    if factor <= 0:
        return int(n_tokens)
    c = math.ceil(k * n_tokens * factor / n_experts)
    return max(1, min(int(n_tokens), c))


MOE_IMPLS = ("auto", "dense", "grouped", "dispatch", "a2a")

#: The ridge: a bf16 matmul on the v5e turns from memory- to compute-bound
#: at 197e12 FLOP/s / 819e9 B/s = 240 FLOP a byte of weights = 240 rows.
#: Above it the dense einsum pays E/k times the routed FLOPs and `auto` is
#: `grouped`.  At or under it a dense pass costs the read of every held
#: expert whatever the rows (qwen3-30b-a3b at 256 rows: 0.64 ms a down
#: projection measured, 0.49 ms to read its 403 MB: on the ridge), and a
#: grouped pass the read of the experts some row chose plus its sort,
#: gather and unsort: which is less depends on how many the rows choose
#: (SPARSE_SHARE).  Measured crossover by rows: PERF.md section 6, PR 31.
RIDGE_ROWS = 256

#: At or under the ridge `auto` is `grouped` where the routing is expected
#: to touch at most this share of the held experts (`expected_share`), else
#: `dense`.  From `scripts/moe_crossover.py --steps` (grouped / dense time
#: a layer over three seeds, rows 1-256 at the four MoE cells' expert
#: shapes; PERF.md section 6, PR 47) the ratio follows the share: 0.28-0.35
#: at 0.27 (16 x top-10 of 512, 256 held: 2.16 -> 0.70 ms), 0.46-0.76 at
#: 0.63 (32 x top-4 and 16 x top-8 of 128), 0.84-1.00 at 0.87 (32 x top-8
#: of 128: 1.64 -> 1.45), 1.00-1.04 at 0.92, and from 0.95 up the einsum
#: wins, by up to 11 % where every expert is read (128 x top-8 of 128:
#: 1.64 -> 1.82): the sort, gather and unsort buy nothing there.  Every row
#: at or under the constant ran at least 24 % faster grouped in every seed;
#: between it and the break-even near 0.9 the layer's gain is 0-24 %,
#: under what a cell resolves end to end (PERF.md section 7).
SPARSE_SHARE = 0.65


def expected_share(n_rows: int, k: int, n_routed: int) -> float:
    """The share of its held experts a program of `n_rows` rows reads when
    each row chooses `k` of `n_routed` experts uniformly: the chance that
    some assignment falls on a given expert.  Uniform routing is the upper
    bound (a skewed router touches fewer); 1.0 where the routing is not
    known."""
    if k <= 0 or n_routed <= 0:
        return 1.0
    return 1.0 - (1.0 - 1.0 / n_routed) ** (n_rows * k)


def resolve_moe_impl(
    impl: str, n_rows: int, ranks: int, grouped: bool, share: float = 1.0
) -> str:
    """The compute path for a program of `n_rows` rows, from static shapes
    (this runs at trace time, so each padding bucket compiles the path that
    fits it, and on the host to count rows by path: `moe_path`).

    `auto` on one rank with a grouped closure supplied (`grouped`): more
    rows than the ridge -> `grouped`; at or under it `grouped` where the
    rows are expected to choose at most SPARSE_SHARE of the held experts,
    else `dense`.  `share` is `expected_share` of the program's routing
    where it may go grouped under the ridge at all, and 1.0 (every expert:
    the einsum) where it may not or nothing is known: rows that are one
    lane's under a vmap (`whole_batch`), a tp axis, quantized experts (a
    dequantized layer would be materialised whole).  Both are exact.  On
    several ranks `auto` is `dense`; `dispatch` and `a2a` (capacity
    semantics, mesh paths) are only ever chosen by name.  `grouped` by name
    falls to `dense` where it cannot run (no closure, several ranks): same
    result.
    """
    if impl not in MOE_IMPLS:
        # fail fast: a typo'd DNET_COMPUTE_MOE_IMPL would otherwise fall
        # through every model branch into silent dense compute
        raise ValueError(f"unknown moe_impl {impl!r}; expected one of {MOE_IMPLS}")
    if impl in ("dense", "dispatch", "a2a"):
        return impl
    if not grouped or ranks > 1:
        return "dense"
    if impl == "grouped" or n_rows > RIDGE_ROWS or share <= SPARSE_SHARE:
        return "grouped"
    return "dense"


def sparse_share(n_rows: int, k: int, n_routed: int, whole: bool, quantized: bool) -> float:
    """The `share` `resolve_moe_impl` is told, by the trace (`moe_apply`)
    and by the host (`RingModel.moe_path`) alike."""
    return expected_share(n_rows, k, n_routed) if whole and not quantized else 1.0


def route_positions(top_idx: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Arrival index of each (token, slot) within its expert's queue.

    top_idx [N, k] int32 expert ids (entries >= n_experts are sentinels and
    get position 0 — callers drop them via the out-of-bounds expert index).
    Returns pos [N, k]: slot-major cumulative count, so a token's place in an
    expert buffer is deterministic in token order.
    """
    flat_e = top_idx.reshape(-1)
    onehot = flat_e[:, None] == jnp.arange(n_experts, dtype=flat_e.dtype)[None, :]
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    own = jnp.sum(pos * onehot, axis=1)
    return own.reshape(top_idx.shape)


def localize_topk(top_idx: jnp.ndarray, offset, n_local: int) -> jnp.ndarray:
    """Shift global expert ids into a rank's local range; non-local entries
    become the (out-of-bounds) sentinel n_local, so scatter/gather drop them.
    `offset` may be traced (lax.axis_index) — jnp.where keeps it jittable."""
    ok = (top_idx >= offset) & (top_idx < offset + n_local)
    return jnp.where(ok, top_idx - offset, n_local).astype(jnp.int32)


def scatter_to_experts(
    flat: jnp.ndarray, top_idx: jnp.ndarray, pos: jnp.ndarray, n_experts: int, capacity: int
) -> jnp.ndarray:
    """flat [N, D] -> per-expert buffers [E, C, D].  Slots whose expert id or
    queue position is out of bounds (non-local / over capacity) are dropped."""
    vals = jnp.broadcast_to(flat[:, None, :], (*top_idx.shape, flat.shape[-1]))
    buf = jnp.zeros((n_experts, capacity, flat.shape[-1]), flat.dtype)
    return buf.at[top_idx, pos].add(vals, mode="drop")


def gather_from_experts(
    ye: jnp.ndarray, top_idx: jnp.ndarray, pos: jnp.ndarray, top_w: jnp.ndarray
) -> jnp.ndarray:
    """ye [E, C, D] + router weights [N, k] -> combined [N, D]; dropped slots
    contribute zero (mode="fill")."""
    g = ye.at[top_idx, pos].get(mode="fill", fill_value=0)  # [N, k, D]
    return jnp.einsum("nkd,nk->nd", g, top_w.astype(ye.dtype))


def moe_dispatch(
    flat: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    ffn: Callable[[jnp.ndarray], jnp.ndarray],
    n_experts: int,
    capacity: int,
) -> jnp.ndarray:
    """Single-rank capacity dispatch: [N, D] -> [N, D].

    ffn maps per-expert buffers [E, C, D] -> [E, C, D] (row i uses expert
    i's weights; per-expert biases are added inside, so a dropped token
    simply contributes zero to the combine).
    """
    pos = route_positions(top_idx, n_experts)
    xe = scatter_to_experts(flat, top_idx, pos, n_experts, capacity)
    return gather_from_experts(ffn(xe), top_idx, pos, top_w)


def moe_dispatch_sharded(
    flat: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    ffn_local: Callable[[jnp.ndarray], jnp.ndarray],
    n_local: int,
    capacity: int,
    axis: str,
) -> jnp.ndarray:
    """Experts sharded over `axis`, tokens replicated: each rank dispatches
    only the slots routed into its expert slice and returns a PARTIAL output
    — the caller psums over `axis` (same seam as the dense path)."""
    offset = lax.axis_index(axis) * n_local
    local_idx = localize_topk(top_idx, offset, n_local)
    pos = route_positions(local_idx, n_local)
    xe = scatter_to_experts(flat, local_idx, pos, n_local, capacity)
    return gather_from_experts(ffn_local(xe), local_idx, pos, top_w)


_TRACING = threading.local()


@contextlib.contextmanager
def whole_batch():
    """Entered around a model call by a program that hands the model EVERY
    row it carries in one trace (the paged step and the prefill chunk of
    core/batch.py, `LocalEngine`'s programs): only there may a program at
    or under the ridge take the grouped path.  Unmarked, the rows a trace
    sees may be ONE lane's under `jax.vmap` over the lanes (the dense-slot
    engines and the ring's `LanePool` vmap a one-row program), where the
    batched dense einsum reads the weights once for all lanes and neither
    `lax.ragged_dot` nor a kernel with scalar prefetch batches over them;
    a trace cannot see a vmap around a `lax.scan` body, so the caller that
    knows says so, and one that says nothing keeps the einsum.  The host's
    twin: `RingModel.moe_path(rows, whole=True)`."""
    was = whole_batch_declared()
    _TRACING.whole = True
    try:
        yield
    finally:
        _TRACING.whole = was


def whole_batch_declared() -> bool:
    return getattr(_TRACING, "whole", False)


def moe_apply(
    impl: str,
    flat: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    ffn_local: Callable[[jnp.ndarray], jnp.ndarray],
    n_local: int,
    capacity_factor: float,
    k: int,
    tp_axis,
    dense_fn: Callable[[], jnp.ndarray],
    offset: int = 0,
    n_routed: int = 0,
    grouped_fn: Optional[Callable[[], jnp.ndarray]] = None,
    quantized: bool = False,
):
    """One MoE layer through the selected compute path (shared by every MoE
    model; the models supply only their ffn/dense closures and routing).

    `offset` / `n_routed`: the layer holds the contiguous share
    [offset, offset + held) of `n_routed` routed experts (expert
    parallelism's share of a layer, here without its exchange): top_idx
    ranges over all n_routed, and the result is the held experts' part
    alone.  A layer that holds every expert passes the whole range
    (offset 0, n_routed 0 = as many as it holds).  `grouped_fn`: the
    family's exact grouped-matmul closure (`swiglu_grouped_closure`), for
    the families that have one; `quantized`: the model's
    `experts_quantized`, which the host's `moe_path` reads too.

    Returns (out [N, D], partial): partial=True means the output is a
    per-rank partial sum the caller must psum over tp_axis (the Megatron
    seam both models join their other residual terms at).
    """
    ranks = 1 if tp_axis is None else lax.axis_size(tp_axis)
    n_experts = n_local * ranks  # tp ranks shard the (held) expert dim
    n_routed = n_routed or n_experts
    # under a tp axis (of one rank too) a program at or under the ridge
    # keeps the einsum: no caller that passes one declares `whole_batch`
    whole = whole_batch_declared() and tp_axis is None
    share = sparse_share(flat.shape[0], k, n_routed, whole, quantized)
    impl = resolve_moe_impl(impl, flat.shape[0], ranks, grouped_fn is not None, share)
    if tp_axis is not None and (offset or n_routed != n_experts):
        raise NotImplementedError(
            "an expert share under a tp axis (the share is the expert-"
            "parallel layout; tp inside it is not wired)"
        )
    if impl == "a2a" and tp_axis is not None:
        out = moe_a2a_replicated(
            flat, top_idx, top_w, ffn_local, n_experts, capacity_factor, k, tp_axis
        )
        return out, False
    if impl in ("dispatch", "a2a"):
        capacity = expert_capacity(flat.shape[0], n_routed, k, capacity_factor)
        if tp_axis is None:
            # the whole range maps onto itself; a share drops the slots
            # routed to experts held elsewhere
            local_idx = localize_topk(top_idx, offset, n_local)
            return moe_dispatch(flat, local_idx, top_w, ffn_local, n_local, capacity), False
        out = moe_dispatch_sharded(
            flat, top_idx, top_w, ffn_local, n_local, capacity, tp_axis
        )
        return out, True
    if impl == "grouped":
        return grouped_fn(), False
    return dense_fn(), tp_axis is not None


def moe_a2a_replicated(
    flat: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    ffn_local: Callable[[jnp.ndarray], jnp.ndarray],
    n_experts: int,
    capacity_factor: float,
    k: int,
    axis: str,
) -> jnp.ndarray:
    """a2a expert parallelism for AXIS-REPLICATED inputs (the Megatron seam
    both MoE models sit behind: x is replicated over the tp axis).

    Splits the token set across ranks (ceil-padded; padded rows carry the
    out-of-bounds sentinel expert id so they dispatch nowhere), runs moe_a2a
    on each rank's slice, and restores replication with a scatter+psum —
    psum output is axis-INVARIANT, so a lax.scan carry through this path
    keeps its axis typing (an all_gather would mark the carry varying).
    Returns the full [N, D] combined output, replicated over `axis`.
    """
    N, D = flat.shape
    R = lax.axis_size(axis)
    n = -(-N // R)
    pad = n * R - N
    if pad:
        flat_p = jnp.pad(flat, ((0, pad), (0, 0)))
        idx_p = jnp.pad(top_idx, ((0, pad), (0, 0)), constant_values=n_experts)
        w_p = jnp.pad(top_w, ((0, pad), (0, 0)))
    else:
        flat_p, idx_p, w_p = flat, top_idx, top_w
    i = lax.axis_index(axis)
    fl = lax.dynamic_slice_in_dim(flat_p, i * n, n)
    ti = lax.dynamic_slice_in_dim(idx_p, i * n, n)
    tw = lax.dynamic_slice_in_dim(w_p, i * n, n)
    C = expert_capacity(n, n_experts, k, capacity_factor)
    out = moe_a2a(fl, ti, tw, ffn_local, n_experts, C, axis)
    buf = jnp.zeros((n * R, out.shape[-1]), out.dtype)
    buf = lax.dynamic_update_slice_in_dim(buf, out, i * n, axis=0)
    return lax.psum(buf, axis)[:N]


def moe_a2a(
    flat: jnp.ndarray,
    top_idx: jnp.ndarray,
    top_w: jnp.ndarray,
    ffn_local: Callable[[jnp.ndarray], jnp.ndarray],
    n_experts: int,
    capacity: int,
    axis: str,
) -> jnp.ndarray:
    """Expert-parallel dispatch over `axis` (R ranks).

    Per rank: flat [n, D] is this rank's token slice, top_idx/top_w [n, k]
    its router output over the GLOBAL expert space, ffn_local computes the
    rank's E/R experts on buffers [E/R, R*C, D].  Capacity is per
    (rank, expert) pair.  Requires n_experts % R == 0.
    """
    pos = route_positions(top_idx, n_experts)
    xe = scatter_to_experts(flat, top_idx, pos, n_experts, capacity)
    # [E, C, D] -> [E/R, R*C, D]: chunk j of the expert axis goes to rank j
    xe = lax.all_to_all(xe, axis, split_axis=0, concat_axis=1, tiled=True)
    ye = ffn_local(xe)
    # [E/R, R*C, D] -> [E, C, D]: return each rank's slice of every buffer
    ye = lax.all_to_all(ye, axis, split_axis=1, concat_axis=0, tiled=True)
    return gather_from_experts(ye, top_idx, pos, top_w)


def held_assignments(top_idx: jnp.ndarray, offset: int, n_held: int) -> jnp.ndarray:
    """How many of each token's chosen experts lie in [offset, offset +
    n_held): [N, k] -> [N] int32 (dnet_moe_assignments_total{held="yes"})."""
    ok = (top_idx >= offset) & (top_idx < offset + n_held)
    return jnp.sum(ok.astype(jnp.int32), axis=-1)


def swiglu_expert_closures(p, flat, scores, top_idx, top_w, tp_axis, offset: int = 0):
    """The (effn, dense) closure pair shared by swiglu-expert MoE families
    (mixtral, deepseek's routed experts): p holds stacked {"e_gate",
    "e_up", "e_down"} expert weights, (in, out)-oriented on a leading
    local-expert axis.  effn computes per-expert buffers [E*, C*, D];
    dense() is the exact all-local-experts einsum masked by the scattered
    routing weights, returning this rank's PARTIAL sum under tp (caller
    psums at its residual seam).  `scores` is as wide as the ROUTER; the
    held experts are the range [offset, offset + E_local) of it (the whole
    range for a layer that holds every expert).
    """
    import jax

    from dnet_tpu.ops.quant import dq, lead_dim

    N = flat.shape[0]
    E_local = lead_dim(p["e_gate"])

    def effn(xe):  # per-expert buffers [E*, C*, D] -> [E*, C*, D]
        gate = jnp.einsum("ecd,edf->ecf", xe, dq(p["e_gate"]))
        up = jnp.einsum("ecd,edf->ecf", xe, dq(p["e_up"]))
        return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, dq(p["e_down"]))

    def dense():  # scattered weights mask the all-local-experts einsum
        weights = jnp.zeros_like(scores).at[
            jnp.arange(N)[:, None], top_idx
        ].set(top_w)  # [N, E] over the GLOBAL expert space
        gate = jnp.einsum("nd,edf->nef", flat, dq(p["e_gate"]))
        up = jnp.einsum("nd,edf->nef", flat, dq(p["e_up"]))
        inner = jax.nn.silu(gate) * up
        expert_out = jnp.einsum("nef,efd->ned", inner, dq(p["e_down"]))
        if tp_axis is not None:
            e_off = offset + lax.axis_index(tp_axis) * E_local
            w_local = lax.dynamic_slice_in_dim(weights, e_off, E_local, axis=1)
        else:
            w_local = weights[:, offset:offset + E_local]
        return jnp.einsum("ned,ne->nd", expert_out, w_local.astype(flat.dtype))

    return effn, dense, E_local


#: the most rows one grid step of the grouped matmul multiplies by one
#: expert's weights.  A step whose tile straddles experts is repeated for
#: each, so work and weight reads grow with the tile: with about 128 sorted
#: rows an expert (2048 rows, top-8 of 128) XLA's own lowering of
#: `lax.ragged_dot` (tile 512) took 6.2 ms a layer where this tile takes
#: 3.9 and the dense einsum 15.5 (PERF.md section 6, PR 31: the table).
GROUP_TILE_ROWS = 128

#: the fewest: one bf16 sublane tile
MIN_TILE_ROWS = 16

#: the most columns of an expert's weights one grid step reads (the k and
#: the n tile): a [1024, 1024] bf16 tile is 2 MB, two in flight.  At a
#: step's rows 512 is 2-50 % slower and 2048 within 1 % where it fits
#: VMEM (not for 4096-wide experts): PERF.md section 6, PR 47.
GROUP_TILE_COLS = 1024


def group_tile_rows(m: int) -> int:
    """The row tile for `m` sorted rows, from the shape alone: the largest
    of GROUP_TILE_ROWS, its halves and MIN_TILE_ROWS that divides `m` (a
    prefill program's rows: 128; a decode step of 16 lanes x top-10: 32),
    MIN_TILE_ROWS where none does (the rows are then padded to it).  Each
    visit multiplies one tile by one expert, so a step's few rows an
    expert also multiply less under a small tile."""
    tile = GROUP_TILE_ROWS
    while tile > MIN_TILE_ROWS and m % tile:
        tile //= 2
    return tile


def grouped_matmul(
    xs: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray, layer=None
) -> jnp.ndarray:
    """xs [M, K] rows sorted by group, w [G, K, N], sizes [G] -> [M, N]:
    row r of group g is xs[r] @ w[g]; rows past the last group are left
    undefined.  Operands' dtype out, float32 accumulation.

    With `layer` (a traced index) w is a STACK [L, G, K, N] of which that
    layer's groups are used.  A custom call cannot read a slice of its
    operand the way a fused dot does: handed `w[layer]`, XLA copies the
    layer's weights out of the stack before every call (qwen3-30b-a3b:
    1.2 GB a layer, twice the kernel's own time).  So the kernel takes the
    whole stack as [L*G, K, N] (a bitcast) with every other layer's groups
    empty, and empty groups are never visited: neither are the groups of
    this layer that no row chose, which is what a decode step gains.

    On a TPU (and in interpret mode under DNET_FLASH_INTERPRET=1) Pallas'
    megablox kernel at `group_tile_rows(M)`, the rows padded to the tile
    where it does not divide them (the padding lies past the last group);
    elsewhere `lax.ragged_dot`.  Not booked in `/health`'s `kernels`
    block: the experts' path is counted by rows
    (dnet_moe_expert_rows_total).
    """
    from dnet_tpu.ops.kernel_select import kernel_backend

    backend = kernel_backend()
    if backend is None:
        return lax.ragged_dot(xs, w if layer is None else w[layer], sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if layer is not None:
        n_layers, groups = w.shape[:2]
        w = w.reshape(n_layers * groups, *w.shape[2:])
        sizes = lax.dynamic_update_slice(
            jnp.zeros((n_layers * groups,), sizes.dtype), sizes, (layer * groups,)
        )
    m = xs.shape[0]
    tile = group_tile_rows(m)
    pad = -m % tile
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    out = gmm(
        xs, w, sizes, preferred_element_type=xs.dtype,
        tiling=(tile, group_tile_cols(w.shape[1]), group_tile_cols(w.shape[2])),
        interpret=backend == "interpret",
    )
    return out[:m] if pad else out


EXPERT_KEYS = ("e_gate", "e_up", "e_down")


def expert_stacks(window_params: dict) -> Optional[dict]:
    """The layer-stacked expert weights [L, E, ...] of a window, for a scan
    over its layers to close over when its experts go grouped (the body
    then hands them on as p["e_stack"] = (stacks, layer index), and
    `grouped_matmul` reads the layer out of the stack in place).  None
    where the window has no such weights or holds them quantized (a
    dequantized layer is a fresh array anyway)."""
    from dnet_tpu.ops.quant import is_quantized

    stacks = {k: window_params.get(k) for k in EXPERT_KEYS}
    if any(w is None or is_quantized(w) for w in stacks.values()):
        return None
    return stacks


def swiglu_grouped_closure(p, flat, top_idx, top_w, offset: int = 0):
    """The exact grouped-matmul twin of `swiglu_expert_closures`' dense():
    same weights, same result, work proportional to the rows (one rank).

    The N*k (token, slot) assignments are sorted by local expert id;
    assignments to experts this process does not hold sort into a tail that
    no group covers, is never computed and contributes zero (an expert
    share works like a whole layer).  Operands and outputs keep the
    weights' dtype with float32 accumulation, like the dense einsum; no
    capacity, nothing dropped.  Where the layer scan handed the stacks on
    (p["e_stack"], `expert_stacks`) the weights are read out of them.
    """
    import jax

    from dnet_tpu.ops.quant import dq, lead_dim

    N, k = top_idx.shape
    M = N * k
    E_local = lead_dim(p["e_gate"])

    def grouped():
        local = localize_topk(top_idx, offset, E_local).reshape(M)
        order = jnp.argsort(local, stable=True)  # sorted place -> assignment
        sizes = jnp.sum(
            local[:, None] == jnp.arange(E_local, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32,
        )
        xs = flat[order // k]  # [M, D], rows in expert order
        w, layer = p.get("e_stack") or ({n: dq(p[n]) for n in EXPERT_KEYS}, None)
        gate = grouped_matmul(xs, w["e_gate"], sizes, layer)
        up = grouped_matmul(xs, w["e_up"], sizes, layer)
        ys = grouped_matmul(jax.nn.silu(gate) * up, w["e_down"], sizes, layer)
        # rows past the last group are whatever the kernel left there
        held = jnp.arange(M) < jnp.sum(sizes)
        ys = jnp.where(held[:, None], ys, 0)
        place = jnp.zeros((M,), jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))
        y = ys[place].reshape(N, k, ys.shape[-1])
        return jnp.einsum("nkd,nk->nd", y, top_w.astype(y.dtype))

    return grouped


# Below the kernel's callers on purpose: a Mosaic kernel's serialized body
# carries the lines of its call stack, and a line added above `grouped_matmul`
# would re-compile every program that holds the kernel (PERF.md section 6,
# PR 30).


def group_tile_cols(width: int) -> int:
    """The k or n tile for a side of `width` columns, from the shape alone:
    the side itself where it fits GROUP_TILE_COLS, else the largest multiple
    of 128 under the cap that DIVIDES it (2304 -> 768, where the cap itself
    would run three steps for 2.25 tiles of work, the last one masked),
    else the cap (megablox masks the remainder).  512, 768, 896 and 1024
    are their own tile; 2048 and 4096 keep the cap."""
    if width <= GROUP_TILE_COLS:
        return width
    for tile in range(GROUP_TILE_COLS, 0, -128):
        if width % tile == 0:
            return tile
    return GROUP_TILE_COLS


def experts_visited(chosen: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """How many (layer, held expert) pairs some ACTIVE lane of a decode
    step chose: what the step's grouped matmuls read of the experts
    (dnet_moe_experts_visited_total).  chosen [L, lanes, E] bool, active
    [lanes] bool -> int32 scalar."""
    return jnp.sum(jnp.any(chosen & active[None, :, None], axis=1), dtype=jnp.int32)

