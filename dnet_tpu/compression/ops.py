"""Column sparsification ops (Pallas on TPU, jnp elsewhere).

Reference: src/dnet/compression/ops.py:104-190 (`column_sparsify_tensor`
dispatching hand-written Metal kernels) — the op zeroes the k columns with
the smallest L2 norms so the wire layer can ship only the kept columns.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from dnet_tpu.ops.kernel_select import SELECTIONS, on_tpu

_LANE = 128


def _norms_kernel(x_ref, out_ref):
    """Accumulate per-column sum of squares over row tiles.

    Grid: one program per row-tile; out is revisited by every program
    (TPU grid is sequential, so accumulation is safe)."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    xf = x_ref[:].astype(jnp.float32)
    partial = jnp.sum(xf * xf, axis=0, keepdims=True)  # [1, C_tile]

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += partial


def _column_sq_norms_pallas(
    x: jnp.ndarray, row_tile: int = 256, interpret: bool = False
) -> jnp.ndarray:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, C = x.shape
    if R % row_tile:
        raise ValueError(f"row_tile {row_tile} must divide {R} rows exactly")
    grid = (R // row_tile,)
    return pl.pallas_call(
        _norms_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, C), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((1, C), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.float32),
        interpret=interpret,
        name="column_norms",
    )(x)[0]


def column_l2_norms(x: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 norm per column of a 2D tensor [R, C] -> [C] f32.

    Pallas kernel on TPU when the shape tiles cleanly; jnp otherwise
    (XLA fuses that fine — the kernel exists for the DCN egress hot path
    where activations are large and lane-aligned).
    """
    R, C = x.shape
    row_tile = R if R <= 256 else 256
    # tail row-blocks would be silently skipped by the grid: only use the
    # kernel when the tiling divides exactly
    if on_tpu() and C % _LANE == 0 and R % 8 == 0 and R % row_tile == 0:
        SELECTIONS.record("column_norms", "pallas")
        return _column_sq_norms_pallas(x, row_tile=row_tile)
    SELECTIONS.record("column_norms", "dense", (x.shape,))
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=0)


def _matmul_kernel(a_ref, b_ref, o_ref):
    """Tiled matmul with accumulation over the contraction grid axis (TPU
    grids run sequentially, so revisiting o_ref is safe)."""
    import jax.experimental.pallas as pl

    d = pl.program_id(2)

    @pl.when(d == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    # HIGHEST: b is a one-hot selection, so the product must reproduce a's
    # values exactly — one bf16 MXU pass would round f32 activations
    o_ref[:] += jax.lax.dot_general(
        a_ref[:].astype(jnp.float32),
        b_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _tile(n: int, candidates) -> int:
    """Largest candidate tile that divides n exactly (grids must cover n —
    a floor-division remainder would silently skip rows)."""
    for c in candidates:
        if n % c == 0:
            return c
    return 0


def _pallas_matmul(a: jnp.ndarray, b: jnp.ndarray, interpret: bool = False):
    """a [R, D] @ b [D, K] on the MXU via Pallas (gather/scatter engine:
    b is a one-hot selection matrix, reference kernels.py k_gather_cols /
    k_scatter_from_compact)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = a.shape
    _, K = b.shape
    tr = _tile(R, (256, 128, 64, 32, 16, 8))
    td = _tile(D, (512, 256, 128))
    tk = _tile(K, (256, 128))
    if not (tr and td and tk):
        raise ValueError(f"[{R},{D}] @ [{D},{K}] does not tile exactly")
    grid = (R // tr, K // tk, D // td)
    out = pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, td), lambda i, k, d: (i, d), memory_space=pltpu.VMEM),
            pl.BlockSpec((td, tk), lambda i, k, d: (d, k), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tr, tk), lambda i, k, d: (i, k), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((R, K), jnp.float32),
        interpret=interpret,
        name="column_select",
    )(a, b)
    return out


def _pallas_selectable(rows: int, contraction: int, out: int) -> bool:
    ok = (
        on_tpu()
        and _tile(rows, (256, 128, 64, 32, 16, 8)) > 0
        and _tile(contraction, (512, 256, 128)) > 0
        and _tile(out, (256, 128)) > 0
    )
    SELECTIONS.record(
        "column_select", "pallas" if ok else "dense",
        ((rows, contraction), (contraction, out)),
    )
    return ok


def gather_columns(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """[R, D] -> [R, K]: select columns `idx` (MXU one-hot select on TPU —
    the analog of the reference's k_gather_cols Metal kernel; a plain
    O(R*K) take elsewhere)."""
    R, D = x.shape
    K = idx.shape[0]
    if _pallas_selectable(R, D, K):
        onehot = (jnp.arange(D)[:, None] == idx[None, :]).astype(jnp.float32)
        return _pallas_matmul(x, onehot).astype(x.dtype)
    return jnp.take(x, idx, axis=1)


def scatter_columns(kept: jnp.ndarray, idx: jnp.ndarray, D: int) -> jnp.ndarray:
    """[R, K] -> [R, D]: scatter kept columns back, zeros elsewhere
    (reference k_scatter_from_compact analog)."""
    R, K = kept.shape
    if _pallas_selectable(R, K, D):
        onehot = (idx[:, None] == jnp.arange(D)[None, :]).astype(jnp.float32)
        return _pallas_matmul(kept, onehot).astype(kept.dtype)
    return jnp.zeros((R, D), dtype=kept.dtype).at[:, idx].set(kept)


@functools.partial(jax.jit, static_argnames=("keep",))
def _topk_column_mask(norms: jnp.ndarray, keep: int) -> jnp.ndarray:
    C = norms.shape[0]
    _, idx = jax.lax.top_k(norms, keep)
    return jnp.zeros((C,), dtype=bool).at[idx].set(True)


# ---- wire-pipeline encode entry points ------------------------------------
#
# One jitted launch per hop codec, with the ACTIVATION BUFFER DONATED: the
# sliced hop activation is dead after the encode, so XLA reuses its buffer
# for the outputs and the compute thread's only serial cost is the dispatch.
# Every output stays on device — transport/wire_pipeline.py reads them back
# on the tx stage, off the compute thread (the overlap the wire pipeline
# exists for).  Kept columns come out in ascending column order (the wire
# bitmask convention decompress relies on).


@jax.named_scope("wire_encode")
def _wire_cast_impl(x2, wire_np_dtype):
    """Lossless hop codec: cast to the wire dtype on device."""
    return x2.astype(wire_np_dtype)


@jax.named_scope("wire_encode")
def _wire_sparse_impl(x2, keep):
    """sparse_v1 device half: (mask bool[D], kept [R, keep]) — top-k
    column selection by L2 norm, gathered in ascending column order."""
    norms = column_l2_norms(x2)
    _, idx = jax.lax.top_k(norms, keep)
    idx = jnp.sort(idx)
    mask = jnp.zeros(norms.shape, dtype=bool).at[idx].set(True)
    return mask, gather_columns(x2, idx)


def quantize_q8(kept: jnp.ndarray, gs: int):
    """THE affine-uint8 quant math, shared by the synchronous encoder
    (wire.compress_tensor) and the jitted wire-pipeline launch — one
    definition of the scale epsilon / clip bounds / padding scheme.

    kept [R, K] -> (codes uint8 [R, K], scale f32, bias f32).  gs > 0:
    per-(row, group-of-kept-columns) params, zero padding included (note
    jit-compiled reductions may differ from eager by 1 ulp in a scale, so
    the two paths are value-equivalent, not byte-identical).  gs == 0:
    ONE per-tensor scale/bias pair — the fallback for frames too small
    for group quant."""
    R, K = kept.shape
    if gs == 0:
        kf = kept.astype(jnp.float32)
        mn = jnp.min(kf)
        scale = jnp.maximum((jnp.max(kf) - mn) / 255.0, 1e-12)
        codes = jnp.clip(jnp.round((kf - mn) / scale), 0, 255).astype(jnp.uint8)
        return codes, scale.reshape(1), mn.reshape(1)
    G = -(-K // gs)
    pad = G * gs - K
    kf = jnp.pad(kept.astype(jnp.float32), ((0, 0), (0, pad))).reshape(R, G, gs)
    mn = jnp.min(kf, axis=-1)
    mx = jnp.max(kf, axis=-1)
    scale = jnp.maximum((mx - mn) / 255.0, 1e-12)
    codes = jnp.clip(
        jnp.round((kf - mn[..., None]) / scale[..., None]), 0, 255
    ).astype(jnp.uint8)
    return codes.reshape(R, G * gs)[:, :K], scale, mn


@jax.named_scope("wire_encode")
def _wire_q8_impl(x2, keep, gs, wire_np_dtype):
    """qsparse8_v1 device half: (mask, codes u8, scale f32, bias f32) —
    top-k column selection + the shared quantize_q8 math.
    wire_np_dtype only tags the dequantized output; it is threaded as a
    static arg so the (dtype-bearing) tag string can be built host-side
    without reading anything back."""
    del wire_np_dtype  # static: part of the cache key / dtype tag only
    norms = column_l2_norms(x2)
    _, idx = jax.lax.top_k(norms, keep)
    idx = jnp.sort(idx)
    mask = jnp.zeros(norms.shape, dtype=bool).at[idx].set(True)
    kept = gather_columns(x2, idx)
    codes, scale, bias = quantize_q8(kept, gs)
    return mask, codes, scale, bias


def _jitted_wire_encode(fn, *static):
    """Cached jit of one encode impl with the activation donated; wrapped
    by instrument_jit so a shape leak shows up on the compile dashboards
    instead of as a mystery per-hop latency cliff."""

    @functools.cache
    def build():
        from dnet_tpu.obs.jit import instrument_jit

        return instrument_jit(
            jax.jit(fn, static_argnames=static, donate_argnums=(0,)),
            "wire_encode",
        )

    return build


wire_cast = _jitted_wire_encode(_wire_cast_impl, "wire_np_dtype")
wire_sparse = _jitted_wire_encode(_wire_sparse_impl, "keep")
wire_q8 = _jitted_wire_encode(_wire_q8_impl, "keep", "gs", "wire_np_dtype")


def column_sparsify(x: jnp.ndarray, drop_frac: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero the `drop_frac` fraction of columns with smallest L2 norm.

    x: [R, C] (activations flattened to 2D, columns = features).
    Returns (sparsified x, keep mask [C] bool).
    """
    R, C = x.shape
    keep = max(int(round(C * (1.0 - drop_frac))), 1)
    norms = column_l2_norms(x)
    mask = _topk_column_mask(norms, keep)
    return jnp.where(mask[None, :], x, jnp.zeros_like(x)), mask
