"""Lightning linear attention: the recurrent step and the chunked prefill,
as Pallas kernels with their plain `jax.numpy` forms beside them.

A head keeps a state `S [D, D]` float32 that decays by a FIXED factor a
token (no gate is learned: head h of H forgets at ALiBi's slope,
`lam_h = exp(-2^(-8 (h + 1) / H))`, the same table in every layer) and
takes each key's outer product with its value:

    S_t = lam_h S_{t-1} + k_t v_t^T            o_t = S_t^T q_t / sqrt(D)

which is `o_t = sum_{i <= t} lam_h^(t - i) (q_t . k_i / sqrt(D)) v_i`: causal
linear attention under an exponential decay, no denominator.  q and k
arrive as the layer left them (normed, rotated: models/minicpm_sala.py).

Two ops, three implementations each behind one dispatcher (the
`paged_attend` convention): `pallas` on a TPU backend and nothing else
there, `interpret` (the same kernel, DNET_FLASH_INTERPRET=1 on the CPU),
`emulate` (the `jax.numpy` form, what a CPU backend serves through).

- `lightning_step`: one token a lane against the store's whole stack, in
  place: the kernel takes the layer by index and aliases the store, an
  idle lane's entry is copied through untouched.  Bound by memory: an entry
  is read and written once.
- `lightning_chunk`: T tokens of one sequence against its own entry, in
  sub-chunks of up to 128: inside a sub-chunk the quadratic form with the
  decay between its keys, plus q against the incoming state decayed from
  the sub-chunk's start; the state moves on by the sub-chunk's keys, each
  decayed to its end.  Padding neither decays the state nor adds a key.

`lightning_quadratic` is the definition (no state, no chunks): the tests
hold the two ops to it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnet_tpu.ops.gated_delta import _column  # [1, n] -> [n, 1] on the vector unit
from dnet_tpu.ops.kernel_select import SELECTIONS

LIGHTNING_IMPLS = ("pallas", "interpret", "emulate")
#: the custom calls' names in a device trace
STEP_NAME = "lightning_step"
CHUNK_NAME = "lightning_chunk"
#: tokens of one sub-chunk of the chunked form (a lane tile)
SUB_CHUNK = 128
#: heads one grid step of the decode kernel holds
STEP_HEADS = 8
_HI = lax.Precision.HIGHEST


def log_decay(n_heads: int) -> np.ndarray:
    """log lam_h [H] float32: minus ALiBi's slope of head h of `n_heads`."""
    h = np.arange(1, n_heads + 1, dtype=np.float64)
    return (-(2.0 ** (-8.0 * h / n_heads))).astype(np.float32)


def state_entry_bytes(n_heads: int, head_dim: int) -> int:
    """Bytes of one lane's state in one layer: S, float32."""
    return n_heads * head_dim * head_dim * 4


# ---- the definition ---------------------------------------------------------
def lightning_quadratic(q, k, v):
    """q/k/v [T, H, D] -> o [T, H, D] float32: every pair of positions."""
    T, H, D = q.shape
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("thd,ihd->hti", q, k, precision=_HI) * D**-0.5
    t = jnp.arange(T)
    gap = (t[:, None] - t[None, :]).astype(jnp.float32)  # t - i
    mask = gap >= 0
    dec = jnp.exp(jnp.asarray(log_decay(H))[:, None, None] * jnp.where(mask, gap, 0.0))
    a = jnp.where(mask, s * dec, 0.0)
    return jnp.einsum("hti,ihd->thd", a, v, precision=_HI)


# ---- the jax.numpy forms ----------------------------------------------------
def _step_emulate(S, q, k, v, active):
    """One token a lane.  S [B, H, D, D], q/k/v [B, H, D] float32 (q scaled)."""
    lam = jnp.exp(jnp.asarray(log_decay(S.shape[1])))
    S1 = lam[None, :, None, None] * S + k[..., :, None] * v[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S1, q, precision=_HI)
    return o, jnp.where(active.astype(bool)[:, None, None, None], S1, S)


def _chunk_inputs(q, k, v, valid):
    """Heads first, the tokens padded to whole sub-chunks, a padded key
    zeroed; `cum` [H, T] the inclusive running log-decay INSIDE each
    sub-chunk (padding adds none).  q carries the 1 / sqrt(D)."""
    T, H, D = q.shape
    C = SUB_CHUNK if T >= SUB_CHUNK else -(-T // 8) * 8
    n = -(-T // C)
    ok = jnp.ones((T,), bool) if valid is None else valid
    pad = n * C - T
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        ok = jnp.pad(ok, (0, pad))
    k = jnp.where(ok[:, None, None], k, jnp.zeros((), k.dtype))
    count = jnp.cumsum(ok.reshape(n, C).astype(jnp.float32), axis=1).reshape(n * C)
    cum = jnp.asarray(log_decay(H))[:, None] * count[None, :]  # [H, T]

    def heads_first(a):
        return jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # [H, T, D]

    return heads_first(q) * D**-0.5, heads_first(k), heads_first(v), cum, C, T


def _chunk_emulate(S, q, k, v, valid):
    """T tokens of one sequence.  S [H, D, D], q/k/v [T, H, D]."""
    qh, kh, vh, cum, C, T = _chunk_inputs(q, k, v, valid)
    H, Tp, D = qh.shape
    n = Tp // C
    mask = jnp.tril(jnp.ones((C, C), bool))

    def split(a):
        return jnp.moveaxis(a.reshape(H, n, C, *a.shape[2:]), 1, 0)

    def body(S, xs):
        q, k, v, c = xs  # [H, C, D] x3, [H, C]
        dec = jnp.exp(jnp.where(mask, c[:, :, None] - c[:, None, :], 0.0))
        a = jnp.where(mask, jnp.einsum("htd,hid->hti", q, k, precision=_HI) * dec, 0.0)
        o = jnp.einsum("hti,hid->htd", a, v, precision=_HI) + jnp.einsum(
            "htk,hkv->htv", q * jnp.exp(c)[..., None], S, precision=_HI
        )
        last = c[:, -1:]  # [H, 1]
        kd = k * jnp.exp(last - c)[..., None]
        S = S * jnp.exp(last)[..., None] + jnp.einsum("hik,hiv->hkv", kd, v, precision=_HI)
        return S, o

    S, o = lax.scan(body, S.astype(jnp.float32), (split(qh), split(kh), split(vh), split(cum)))
    o = jnp.moveaxis(o, 0, 1).reshape(H, Tp, D)[:, :T]
    return jnp.moveaxis(o, 0, 1), S


# ---- the kernels ------------------------------------------------------------
def _step_kernel(layer_ref, act_ref, s_ref, q_ref, k_ref, v_ref, a_ref,
                 s_out, o_ref, *, Ht: int, D: int):
    """One (lane, block of Ht heads): decay, the key's outer product, read.

    s_ref/s_out [1, 1, Ht, D, D]; q_ref/k_ref/v_ref [1, Ht, D] rows; a_ref
    [Ht, D] (lam_h spread along the lanes); o_ref [1, Ht, D]."""
    import jax.experimental.pallas as pl

    live = act_ref[pl.program_id(0)] > 0

    @pl.when(live)
    def _():
        for j in range(Ht):
            kc = _column(k_ref[0, j:j + 1, :], D)  # [D, 1]
            qc = _column(q_ref[0, j:j + 1, :], D)
            S1 = s_ref[0, 0, j] * a_ref[j:j + 1, :] + kc * v_ref[0, j:j + 1, :]
            s_out[0, 0, j] = S1
            o_ref[0, j:j + 1, :] = jnp.sum(S1 * qc, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _step_pallas(S, q, k, v, active, layer, interpret: bool):
    """S [L, B, H, D, D]: the store's stack, aliased; `layer` int32 [1]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    L, B, H, D, _ = S.shape
    Ht = STEP_HEADS if H % STEP_HEADS == 0 else H
    lam = jnp.broadcast_to(jnp.exp(jnp.asarray(log_decay(H)))[:, None], (H, D))

    def s_map(b, h, layer, act):
        return (layer[0], b, h, 0, 0)

    def row_map(b, h, *_):
        return (b, h, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // Ht),
        in_specs=[
            pl.BlockSpec((1, 1, Ht, D, D), s_map),
            pl.BlockSpec((1, Ht, D), row_map),
            pl.BlockSpec((1, Ht, D), row_map),
            pl.BlockSpec((1, Ht, D), row_map),
            pl.BlockSpec((Ht, D), lambda b, h, *_: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Ht, D, D), s_map),
            pl.BlockSpec((1, Ht, D), row_map),
        ],
    )
    S1, o = pl.pallas_call(
        functools.partial(_step_kernel, Ht=Ht, D=D),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
            jax.ShapeDtypeStruct((B, H, D), jnp.float32),
        ],
        # operands count the two prefetched scalars: S is 2
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=STEP_NAME,
    )(layer, active.astype(jnp.int32), S, q, k, v, lam)
    return o, S1


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, g_ref, s_in, o_ref, s_out, *, C: int):
    """One (head, sub-chunk of C tokens); the head's state stays in s_out
    across its sub-chunks.

    q_ref/k_ref/v_ref [1, C, D], kt_ref [1, D, C] (k transposed: the
    state's update is then a plain matmul), g_ref [1, 1, C] the running
    log-decay inside the sub-chunk, s_in/s_out [1, D, D], o_ref [1, C, D]."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out[...] = s_in[...]

    f32 = jnp.float32
    dot = functools.partial(lax.dot_general, precision=_HI, preferred_element_type=f32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    cr = g_ref[0]  # [1, C]
    cc = _column(cr, C)  # [C, 1]
    S = s_out[0]
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    a = jnp.where(j <= i, dot(q, k, nt) * jnp.exp(jnp.minimum(cc - cr, 0.0)), 0.0)
    o_ref[0] = dot(a, v, nn) + dot(q * jnp.exp(cc), S, nn)
    last = cr[:, C - 1:C]  # [1, 1]
    kd = kt_ref[0] * jnp.exp(last - cr)  # [D, C]
    s_out[0] = S * jnp.exp(last) + dot(kd, v, nn)


def _chunk_pallas(S, q, k, v, valid, interpret: bool):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    qh, kh, vh, cum, C, T = _chunk_inputs(q, k, v, valid)
    H, Tp, D = qh.shape
    o, S1 = pl.pallas_call(
        functools.partial(_chunk_kernel, C=C),
        grid=(H, Tp // C),
        in_specs=[
            pl.BlockSpec((1, C, D), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, C, D), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, D, C), lambda h, c: (h, 0, c)),
            pl.BlockSpec((1, C, D), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, C), lambda h, c: (h, 0, c)),
            pl.BlockSpec((1, D, D), lambda h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, D), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, D, D), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, Tp, D), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=CHUNK_NAME,
    )(qh, kh, jnp.swapaxes(kh, -1, -2), vh, cum[:, None, :], S.astype(jnp.float32))
    return jnp.moveaxis(o[:, :T], 0, 1), S1


# ---- the dispatchers --------------------------------------------------------
def lightning_impl() -> str:
    """The implementation for this process: the kernel on a TPU backend,
    the interpreted kernel under DNET_FLASH_INTERPRET=1, else `jax.numpy`."""
    from dnet_tpu.ops.kernel_select import kernel_backend

    return kernel_backend() or "emulate"


def _check_impl(impl: str) -> None:
    if impl not in LIGHTNING_IMPLS:
        raise ValueError(f"lightning impl {impl!r} not in {LIGHTNING_IMPLS}")


def lightning_step(S, q, k, v, active, layer, impl: str = "emulate"):
    """One decode token a lane against the store's stack, in place.

    S [L, B, H, D, D] float32 (donate it: the kernel aliases it, the
    `jax.numpy` form updates its layer's slice); q/k/v [B, H, D]; active
    [B]: an idle lane's entry neither decays nor takes a key; `layer` a
    traced index.  Returns (o [B, H, D] float32, the stack)."""
    _check_impl(impl)
    SELECTIONS.record(STEP_NAME, impl)
    D = q.shape[-1]
    q = q.astype(jnp.float32) * D**-0.5
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if impl == "emulate":
        S_l = lax.dynamic_index_in_dim(S, layer[0], 0, keepdims=False)
        o, S_l = _step_emulate(S_l, q, k, v, active)
        return o, lax.dynamic_update_index_in_dim(S, S_l, layer[0], 0)
    return _step_pallas(S, q, k, v, active, layer, impl == "interpret")


def lightning_chunk(S, q, k, v, valid=None, impl: str = "emulate"):
    """T tokens of ONE sequence against its own entry (one layer's):
    S [H, D, D] float32, q/k/v [T, H, D], valid [T] bool (padding past the
    real tokens leaves the state alone; its outputs are garbage).  Any T.
    Returns (o [T, H, D] float32, the state after the chunk)."""
    _check_impl(impl)
    SELECTIONS.record(CHUNK_NAME, impl)
    if impl == "emulate":
        return _chunk_emulate(S, q, k, v, valid)
    return _chunk_pallas(S, q, k, v, valid, impl == "interpret")
