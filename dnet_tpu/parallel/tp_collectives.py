"""Intra-shard tensor-parallel collectives: the quantizable psum seam.

ROADMAP item 3's TP half: when a ring shard's layer window runs
tensor-parallel over its host-local chips (parallel/tp.py), every layer
pays exactly two collectives — the attention out-proj all-reduce and the
MLP down-proj all-reduce.  The models used to call ``lax.psum`` directly
at those sites; they now route through :func:`tp_all_reduce`, which keeps
the exact psum for plain string axes (every existing mesh program is
byte-identical) and adds an int8 grouped-quantized mode for
:class:`TpAxis`-tagged axes — EQuARX-shaped (arxiv 2506.17615):

    quantize -> all_to_all (scatter chunks) -> dequant + exact local sum
    -> quantize -> all_gather (collect reduced chunks) -> dequant

so the interconnect carries 1-byte codes plus per-group scale/bias pairs
(the PR 14 qsparse8 affine math, compression/ops.py quantize_q8) instead
of 2-4 byte floats, at the cost of two quantization passes of error.
``DNET_TP_COLLECTIVE`` picks the mode: ``lossless`` (exact, the default
resolution on CPU / forced-host meshes so greedy SSE parity holds),
``q8``, or ``auto`` (q8 only on real accelerator meshes).

Everything traced here is pure (DL004): byte accounting and the
collective-latency probe live OUTSIDE the traced functions —
:func:`collective_bytes` is analytic (a pure function of shape/mode), and
engines book it per dispatch via :func:`observe_collective_bytes`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Varying -> Invariant gather: every chip ends up holding the same gathered
# value, and shard_map's vma check must be able to see that, because the
# callers' out_specs promise replication over the tp axis exactly as they do
# after lax.psum.  lax.all_gather types its result varying; jax 0.9.0 ships
# the invariant form but has not yet exported it from jax.lax.
from jax._src.lax.parallel import all_gather_invariant

MODE_LOSSLESS = "lossless"
MODE_Q8 = "q8"
MODE_AUTO = "auto"
TP_COLLECTIVE_MODES = (MODE_AUTO, MODE_LOSSLESS, MODE_Q8)

# f32 scale + f32 bias per quant group (compression/ops.py quantize_q8)
_GROUP_META_BYTES = 8


class TpAxis(str):
    """A mesh axis name carrying its collective mode.

    ``str`` subclass so every existing consumer of an axis name —
    ``lax.psum(x, axis)``, ``axis_size(axis)``, mesh lookups — keeps
    working unchanged; only :func:`tp_all_reduce` / :func:`tp_all_gather`
    look at the extra ``mode``/``group_size`` attributes.  A plain string
    axis means lossless, always.
    """

    mode: str
    group_size: int

    def __new__(
        cls, name: str, mode: str = MODE_LOSSLESS, group_size: int = 64
    ) -> "TpAxis":
        if mode not in (MODE_LOSSLESS, MODE_Q8):
            raise ValueError(
                f"TpAxis mode must be resolved to lossless|q8, got {mode!r} "
                f"(resolve 'auto' via resolve_collective_mode first)"
            )
        if mode == MODE_Q8 and group_size < 1:
            raise ValueError(f"q8 group_size must be >= 1, got {group_size}")
        self = super().__new__(cls, name)
        self.mode = mode
        self.group_size = int(group_size)
        return self


def resolve_collective_mode(mode: str = "", devices=None) -> str:
    """``auto``/empty -> a concrete mode for the given mesh devices.

    q8 only pays off when the collective crosses a real interconnect;
    on CPU (incl. the forced-host test meshes) auto stays lossless so
    greedy SSE streams are byte-identical out of the box — the same
    default-safety contract as the PR 14 ``DNET_WIRE_CODEC=auto`` hop
    resolution (lossy only where DCN is paid).
    """
    if not mode or mode == MODE_AUTO:
        from dnet_tpu.config import get_settings

        cfg_mode = get_settings().tp.tp_collective
        if cfg_mode and cfg_mode != MODE_AUTO:
            mode = cfg_mode
        else:
            devs = list(devices) if devices is not None else jax.devices()
            platform = devs[0].platform if devs else "cpu"
            mode = MODE_Q8 if platform in ("tpu", "gpu") else MODE_LOSSLESS
    if mode not in (MODE_LOSSLESS, MODE_Q8):
        raise ValueError(
            f"unknown TP collective mode {mode!r} "
            f"(expected one of {TP_COLLECTIVE_MODES})"
        )
    return mode


# ---- traced collective bodies (pure; run inside shard_map) ----------------


def _q8_quant_chunks(rows: jnp.ndarray, gs: int):
    """[R, chunk] f32 -> (codes u8 [R, chunk], scale f32 [R, G], bias f32
    [R, G]) with chunk % gs == 0 — the PR 14 qsparse8 affine math."""
    from dnet_tpu.compression.ops import quantize_q8

    return quantize_q8(rows, gs)


def _q8_dequant(codes: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
                gs: int) -> jnp.ndarray:
    """Inverse of _q8_quant_chunks over the last axis, grouped by gs."""
    *lead, K = codes.shape
    G = K // gs
    vals = codes.astype(jnp.float32).reshape(*lead, G, gs)
    vals = vals * scale[..., None] + bias[..., None]
    return vals.reshape(*lead, K)


def _chunk_len(n_elem: int, tp: int, gs: int) -> int:
    """Per-chip chunk length: a multiple of gs covering n_elem / tp."""
    return -(-n_elem // (tp * gs)) * gs


def _q8_all_reduce(x: jnp.ndarray, axis: str, gs: int) -> jnp.ndarray:
    """EQuARX-shaped grouped-int8 all-reduce over ``axis``.

    Phase 1: each chip quantizes its full partial sum once, an all_to_all
    scatters chunk j (codes + per-group scale/bias) to chip j, which
    dequantizes the tp incoming chunks and sums them EXACTLY in f32.
    Phase 2: the reduced chunk re-quantizes once and an all_gather
    collects every chip's chunk.  Two quant passes total, independent of
    tp — not a per-hop requant chain.
    """
    tp = lax.axis_size(axis)
    if tp == 1:
        return x
    shape = x.shape
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    S = flat.shape[0]
    chunk = _chunk_len(S, tp, gs)
    flat = jnp.pad(flat, (0, tp * chunk - S))
    part = flat.reshape(tp, chunk)  # row j = the chunk chip j will own
    codes, scale, bias = _q8_quant_chunks(part, gs)
    # scatter: after all_to_all, row i holds chip i's partial of MY chunk
    codes = lax.all_to_all(codes, axis, split_axis=0, concat_axis=0)
    scale = lax.all_to_all(scale, axis, split_axis=0, concat_axis=0)
    bias = lax.all_to_all(bias, axis, split_axis=0, concat_axis=0)
    reduced = jnp.sum(_q8_dequant(codes, scale, bias, gs), axis=0)  # [chunk]
    codes1, scale1, bias1 = _q8_quant_chunks(reduced[None], gs)
    codes1 = all_gather_invariant(codes1, axis)  # [tp, 1, chunk]
    scale1 = all_gather_invariant(scale1, axis)
    bias1 = all_gather_invariant(bias1, axis)
    full = _q8_dequant(codes1[:, 0], scale1[:, 0], bias1[:, 0], gs)
    return full.reshape(tp * chunk)[:S].reshape(shape).astype(orig_dtype)


def _q8_all_gather(x: jnp.ndarray, axis: str, gs: int) -> jnp.ndarray:
    """Grouped-int8 all-gather: quantize the local payload once, gather
    codes + scales, dequantize every chip's copy.  Stacks a new leading
    tp axis like ``lax.all_gather``."""
    tp = lax.axis_size(axis)
    if tp == 1:
        return x[None]
    shape = x.shape
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    S = flat.shape[0]
    K = -(-S // gs) * gs
    flat = jnp.pad(flat, (0, K - S))
    codes, scale, bias = _q8_quant_chunks(flat[None], gs)
    codes = all_gather_invariant(codes, axis)  # [tp, 1, K]
    scale = all_gather_invariant(scale, axis)
    bias = all_gather_invariant(bias, axis)
    full = _q8_dequant(codes[:, 0], scale[:, 0], bias[:, 0], gs)  # [tp, K]
    return full[:, :S].reshape((tp,) + shape).astype(orig_dtype)


def tp_all_reduce(x: jnp.ndarray, axis) -> jnp.ndarray:
    """THE per-layer collective seam: sum partial activations over the
    tensor-parallel mesh axis.

    ``axis`` is a mesh axis name; a plain string (or None) keeps the exact
    ``lax.psum`` every pre-TP mesh program compiled to — byte-identical.
    A :class:`TpAxis` tagged ``q8`` runs the grouped-int8 reduction.
    """
    if axis is None:
        return x
    if isinstance(axis, TpAxis) and axis.mode == MODE_Q8:
        return _q8_all_reduce(x, str(axis), axis.group_size)
    return lax.psum(x, axis)


def tp_all_gather(x: jnp.ndarray, axis) -> jnp.ndarray:
    """Collect per-chip shards over the tp axis (new leading axis).

    Lossless for plain string axes; grouped-int8 payloads for a
    :class:`TpAxis` tagged ``q8``.  The result is replicated over the axis
    (typed invariant), like :func:`tp_all_reduce`'s."""
    if axis is None:
        return x[None]
    if isinstance(axis, TpAxis) and axis.mode == MODE_Q8:
        return _q8_all_gather(x, str(axis), axis.group_size)
    return all_gather_invariant(x, axis)


# ---- host-side byte accounting + latency probe ----------------------------


def collective_bytes(
    op: str, mode: str, tp: int, n_elem: int, elem_bytes: int,
    group_size: int = 64,
) -> int:
    """Analytic interconnect bytes for ONE collective, summed over the
    mesh (ring-algorithm accounting): what the engines book into
    ``dnet_tp_collective_bytes_total`` per dispatch.  Pure shape math —
    exact for the implementations above, zero device syncs.

    all_reduce lossless: reduce-scatter + all-gather move the tensor
    twice minus the resident share: ``2 * (tp-1) * n * eb``.
    all_reduce q8: phase 1 all_to_all ships (tp-1) quantized chunks per
    chip, phase 2 all-gather forwards each chip's reduced chunk (tp-1)
    times: ``2 * tp * (tp-1) * (chunk + chunk/gs * 8)``.
    all_gather: the per-chip payload forwarded (tp-1) times, lossless
    floats vs int8 codes + group meta.
    """
    if tp <= 1 or n_elem <= 0:
        return 0
    gs = max(int(group_size), 1)
    if op == "all_reduce":
        if mode == MODE_Q8:
            chunk = _chunk_len(n_elem, tp, gs)
            payload = chunk + (chunk // gs) * _GROUP_META_BYTES
            return 2 * tp * (tp - 1) * payload
        return 2 * (tp - 1) * n_elem * elem_bytes
    if op == "all_gather":
        if mode == MODE_Q8:
            padded = -(-n_elem // gs) * gs
            payload = padded + (padded // gs) * _GROUP_META_BYTES
            return tp * (tp - 1) * payload
        return tp * (tp - 1) * n_elem * elem_bytes
    raise ValueError(f"unknown collective op {op!r}")


def observe_collective_bytes(op: str, nbytes: int) -> None:
    """Book one dispatched collective's analytic wire bytes (host side,
    after the launch — never inside traced code)."""
    if nbytes <= 0:
        return
    from dnet_tpu.obs import metric

    metric("dnet_tp_collective_bytes_total").labels(op=op).inc(nbytes)


def probe_collective_ms(
    mesh, axis, hidden: int, dtype, mode: str, group_size: int = 64,
    reps: int = 3,
) -> dict:
    """Load-time collective latency probe: time a standalone jitted
    all_reduce and all_gather of one hidden-frame-shaped tensor on the
    real mesh and observe the medians into ``dnet_tp_collective_ms{op=}``.
    Per-op timing cannot be carved out of the fused layer programs at
    serving time (one XLA computation), so the probe is the honest
    source for this family — the same calibration discipline as
    ``predicted_stage_s`` / ``probe_stage_time``.
    """
    import time

    from dnet_tpu.obs import metric
    from dnet_tpu.obs.jit import instrument_jit

    from jax.sharding import PartitionSpec as P

    tp_axis = TpAxis(axis, mode=mode, group_size=group_size)

    @jax.named_scope("tp_collective")
    def reduce_body(v):
        # mark the replicated probe tensor varying so the reduction is
        # legal under the vma checker
        return tp_all_reduce(lax.pcast(v, str(tp_axis), to="varying"), tp_axis)

    @jax.named_scope("tp_collective")
    def gather_body(v):
        return tp_all_gather(lax.pcast(v, str(tp_axis), to="varying"), tp_axis)

    spec = P()
    fns = {
        "all_reduce": instrument_jit(
            jax.jit(jax.shard_map(
                reduce_body, mesh=mesh, in_specs=(spec,), out_specs=spec,
            )),
            "tp_collective",
        ),
        "all_gather": instrument_jit(
            jax.jit(jax.shard_map(
                gather_body, mesh=mesh, in_specs=(spec,),
                out_specs=P(None),
            )),
            "tp_collective",
        ),
    }
    x = jnp.ones((1, 1, hidden), dtype=dtype)
    out = {}
    fam = metric("dnet_tp_collective_ms")
    for op, fn in fns.items():
        times = []
        for _ in range(reps + 1):
            t0 = time.perf_counter()
            fn(x).block_until_ready()  # dnetlint: disable=DL005 collective calibration probe: the sync IS the measurement
            times.append((time.perf_counter() - t0) * 1000.0)
        med = sorted(times[1:])[reps // 2]  # drop the compile, take median
        fam.labels(op=op).observe(med)
        out[op] = med
    return out
