"""Gated power retention of degree 2: the recurrent step and the chunked
prefill, as Pallas kernels with their plain `jax.numpy` forms beside them.

The layer (models/brumby.py) replaces softmax attention by

    a[t,i] = (q_t . k_i)^2 / Hd * exp(sum_{j=i+1..t} log g_j)      i <= t
    o_t    = sum_i a[t,i] v_i / (sum_i a[t,i] + eps)

one gate a KV head, a query head reading KV head h // G.  With `phi(x)` the
symmetric square of `x / Hd^(1/4)` this is a linear recurrence over a state
that does not grow with the sequence:

    S_t = g_t S_{t-1} + v_t phi(k_t)^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

**The layout of phi.**  The symmetric square of a Hd-wide vector has
Hd (Hd + 1) / 2 distinct entries (8256 at Hd 128).  They are laid out by
DIAGONAL so that every piece is a whole tile: row d of `phi(x)`,
d = 0 .. Hd/2, is `x * roll(x, d) * w_d`, entry a holding
`x_a x_{(a+d) mod Hd}`.  Row 0 is the squares (w 1); rows 1 .. Hd/2 - 1
hold every unordered pair at that distance once (w sqrt 2); row Hd/2 holds
each of its pairs twice, from a and from a + Hd/2, at w 1, which sums to
the same.  So `phi(x) . phi(y) = (x . y)^2 / Hd` exactly, the state is
`[R, Hd, Hd]` with R = Hd/2 + 1 (65 rows: 8320 entries of phi, the 8256
distinct ones and 64 held twice, the padding a kernel may add), and a
kernel forms a row of phi by one lane roll and never materialises phi for a
chunk.  A state entry is `S [R, Hd(v), Hd(k)]` float32 and `z [R, Hd]`.

Two ops, three implementations each behind one dispatcher (the
`paged_attend` convention): `pallas` on a TPU backend and nothing else
there, `interpret` (the same kernel, DNET_FLASH_INTERPRET=1 on the CPU),
`emulate` (the `jax.numpy` form, what a CPU backend serves through).

- `retention_step`: one token a lane against the store's whole stack, in
  place: the kernel takes the layer by index and aliases the store, an
  idle lane's entry is copied through untouched.  Bound by memory: an entry
  is read and written once.
- `retention_chunk`: T tokens of one sequence against its own entry, in
  sub-chunks of up to 128: inside a sub-chunk the quadratic form with the
  decay between its keys, plus phi(q) against the incoming state decayed
  from the sub-chunk's start; the state moves on by the sub-chunk's keys,
  each decayed to its end.  Bound by compute (the MXU).

`retention_quadratic` is the definition (no state, no chunks): the tests
and scripts/retention_parity.py hold the two ops to it.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dnet_tpu.ops.kernel_select import SELECTIONS

EPS = 1e-6
RETENTION_IMPLS = ("pallas", "interpret", "emulate")
#: the custom calls' names in a device trace
STEP_NAME = "retention_step"
CHUNK_NAME = "retention_chunk"
#: tokens of one sub-chunk of the chunked form (a lane tile)
SUB_CHUNK = 128
_HI = lax.Precision.HIGHEST


def phi_rows(head_dim: int) -> int:
    """R: rows of phi (and of a state entry) for a head of `head_dim`."""
    if head_dim % 2:
        raise ValueError(f"power retention needs an even head_dim, got {head_dim}")
    return head_dim // 2 + 1


def phi_weight(d: int, head_dim: int) -> float:
    return math.sqrt(2.0) if 0 < d < head_dim // 2 else 1.0


def state_entry_bytes(kv_heads: int, head_dim: int) -> int:
    """Bytes of one lane's state in one layer: S and z, float32."""
    R = phi_rows(head_dim)
    return kv_heads * R * head_dim * (head_dim + 1) * 4


def init_state(lead: Tuple[int, ...], kv_heads: int, head_dim: int) -> dict:
    R = phi_rows(head_dim)
    return {
        "S": jnp.zeros((*lead, kv_heads, R, head_dim, head_dim), jnp.float32),
        "z": jnp.zeros((*lead, kv_heads, R, head_dim), jnp.float32),
    }


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """[..., Hd] -> [..., R, Hd] float32, the 1/Hd^(1/4) inside."""
    Hd = x.shape[-1]
    x = x.astype(jnp.float32) * Hd**-0.25
    rows = [
        x * jnp.roll(x, -d, axis=-1) * phi_weight(d, Hd) for d in range(phi_rows(Hd))
    ]
    return jnp.stack(rows, axis=-2)


# ---- the definition -------------------------------------------------------
def retention_quadratic(q, k, v, log_g):
    """q [T, H, Hd], k/v [T, KVH, Hd], log_g [T, KVH] -> o [T, H, Hd]
    float32: every pair of positions, no state."""
    T, H, Hd = q.shape
    KVH = k.shape[1]
    G = H // KVH
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    cum = jnp.cumsum(log_g.astype(jnp.float32), axis=0)  # [T, KVH]
    s = jnp.einsum("tkgd,ikd->kgti", q.reshape(T, KVH, G, Hd), k, precision=_HI)
    decay = cum.T[:, :, None] - cum.T[:, None, :]  # [KVH, t, i]
    mask = jnp.tril(jnp.ones((T, T), bool))
    a = jnp.where(mask, s * s / Hd * jnp.exp(jnp.where(mask, decay, 0.0))[:, None], 0.0)
    num = jnp.einsum("kgti,ikd->tkgd", a, v, precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]  # [T, KVH, G, 1]
    return (num / (den + EPS)).reshape(T, H, Hd)


# ---- the jax.numpy forms --------------------------------------------------
def _step_emulate(S, z, q, k, v, log_g, active):
    """One token a lane.  S [B, KVH, R, Hd, Hd], z [B, KVH, R, Hd],
    q [B, H, Hd], k/v [B, KVH, Hd], log_g [B, KVH], active [B] bool."""
    B, H, Hd = q.shape
    KVH = k.shape[1]
    g = jnp.exp(log_g.astype(jnp.float32))
    pk = phi(k)  # [B, KVH, R, Hd]
    S1 = g[..., None, None, None] * S + v.astype(jnp.float32)[:, :, None, :, None] * pk[:, :, :, None, :]
    z1 = g[..., None, None] * z + pk
    pq = phi(q.reshape(B, KVH, H // KVH, Hd))  # [B, KVH, G, R, Hd]
    num = jnp.einsum("bkgra,bkrca->bkgc", pq, S1, precision=_HI)
    den = jnp.einsum("bkgra,bkra->bkg", pq, z1, precision=_HI)
    o = num / (den[..., None] + EPS)
    live = active.astype(bool)
    S1 = jnp.where(live[:, None, None, None, None], S1, S)
    z1 = jnp.where(live[:, None, None, None], z1, z)
    return o.reshape(B, H, Hd), S1, z1


def _chunk_inputs(q, k, v, log_g, valid):
    """Padding neither decays the state nor adds a key; the cumulative
    log-decay restarts at each sub-chunk."""
    T = q.shape[0]
    C = min(SUB_CHUNK, T)
    if T % C:
        raise ValueError(f"retention_chunk: {T} tokens are no multiple of {C}")
    lg = log_g.astype(jnp.float32)
    if valid is not None:
        lg = jnp.where(valid[:, None], lg, 0.0)
        k = jnp.where(valid[:, None, None], k, jnp.zeros((), k.dtype))
    cum = jnp.cumsum(lg.reshape(T // C, C, -1), axis=1).reshape(T, -1)  # [T, KVH]
    return k, cum, C


def _chunk_emulate(S, z, q, k, v, log_g, valid):
    """T tokens of one sequence.  S [KVH, R, Hd, Hd], z [KVH, R, Hd],
    q [T, H, Hd], k/v [T, KVH, Hd], log_g [T, KVH], valid [T] bool."""
    T, H, Hd = q.shape
    KVH = k.shape[1]
    G = H // KVH
    k, cum, C = _chunk_inputs(q, k, v, log_g, valid)
    n = T // C
    qs = q.astype(jnp.float32).reshape(n, C, KVH, G, Hd)
    ks = k.astype(jnp.float32).reshape(n, C, KVH, Hd)
    vs = v.astype(jnp.float32).reshape(n, C, KVH, Hd)
    cs = cum.reshape(n, C, KVH)
    mask = jnp.tril(jnp.ones((C, C), bool))

    def body(carry, xs):
        S, z = carry
        q, k, v, c = xs
        ct = c.T  # [KVH, C]
        s = jnp.einsum("tkgd,ikd->kgti", q, k, precision=_HI)
        dec = jnp.exp(jnp.where(mask, ct[:, :, None] - ct[:, None, :], 0.0))
        a = jnp.where(mask, s * s / Hd * dec[:, None], 0.0)  # [KVH, G, t, i]
        pq = phi(q) * jnp.exp(c)[:, :, None, None, None]  # [C, KVH, G, R, Hd]
        num = jnp.einsum("kgti,ikd->tkgd", a, v, precision=_HI) + jnp.einsum(
            "tkgra,krca->tkgc", pq, S, precision=_HI
        )
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1) + jnp.einsum(
            "tkgra,kra->tkg", pq, z, precision=_HI
        )
        last = c[-1]  # [KVH]
        pk = phi(k) * jnp.exp(last[None] - c)[:, :, None, None]  # [C, KVH, R, Hd]
        gC = jnp.exp(last)
        S = gC[:, None, None, None] * S + jnp.einsum("ikc,ikra->krca", v, pk, precision=_HI)
        z = gC[:, None, None] * z + jnp.sum(pk, axis=0)
        return (S, z), num / (den[..., None] + EPS)

    (S, z), o = lax.scan(body, (S, z), (qs, ks, vs, cs))
    return o.reshape(T, H, Hd), S, z


# ---- the kernels ----------------------------------------------------------
def _row_tile(R: int) -> int:
    """Rows of a state entry one grid step of the decode kernel holds."""
    return max(t for t in range(1, min(R, 16) + 1) if R % t == 0)


def _step_kernel(layer_ref, act_ref, s_ref, z_ref, pk_ref, pq_ref, v_ref, g_ref,
                 s_out, z_out, o_ref, acc_ref, den_ref, *, Rt: int, G: int, Hd: int):
    """One (lane, KV head, tile of Rt rows): S and z move on by the lane's
    key, the G query heads of the group read the rows as they leave.

    s_ref/s_out [1, 1, 1, Rt, Hd(v), Hd(k)], z_ref/z_out [1, 1, 1, 1, Rt, Hd],
    pk_ref [1, 1, 1, Rt, Hd] phi(k) row form, pq_ref [1, 1, 1, Rt, G, Hd],
    v_ref [1, 1, Hd, 1] (a column: it broadcasts along the key lanes once),
    g_ref [1, 1, 1, 1]; o_ref [1, 1, G, Hd, 1]; acc_ref [G, Hd, Hd] and
    den_ref [G, 1] carry the read across the tiles of one entry."""
    import jax.experimental.pallas as pl

    b, i = pl.program_id(0), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    live = act_ref[b] > 0

    @pl.when(live)
    def _():
        g = g_ref[0, 0]  # [1, 1]
        vb = jnp.broadcast_to(v_ref[0, 0], (Hd, Hd))
        zt = g * z_ref[0, 0, 0, 0] + pk_ref[0, 0, 0]  # [Rt, Hd]
        z_out[0, 0, 0, 0] = zt
        dz = jnp.zeros((G, Hd), jnp.float32)
        for r in range(Rt):
            pk = pk_ref[0, 0, 0, r:r + 1, :]
            s_new = g * s_ref[0, 0, 0, r] + vb * pk
            s_out[0, 0, 0, r] = s_new
            pq = pq_ref[0, 0, 0, r]  # [G, Hd]
            dz = dz + pq * zt[r:r + 1, :]
            for j in range(G):
                acc_ref[j] += pq[j:j + 1, :] * s_new
        den_ref[...] += jnp.sum(dz, axis=-1, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        for j in range(G):
            num = jnp.sum(acc_ref[j], axis=-1, keepdims=True)  # [Hd, 1]
            o_ref[0, 0, j] = num / (den_ref[j:j + 1, :] + EPS)


def _step_pallas(S, z, q, k, v, log_g, active, layer, interpret: bool):
    """S [L, B, KVH, R, Hd, Hd], z [L, B, KVH, R, Hd]: the store's stack,
    aliased; `layer` int32 [1]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    L, B, KVH, R, Hd, _ = S.shape
    H = q.shape[1]
    G = H // KVH
    Rt = _row_tile(R)
    nt = R // Rt
    pk = phi(k).reshape(B, KVH, nt, Rt, Hd)
    pq = phi(q.reshape(B, KVH, G, Hd))  # [B, KVH, G, R, Hd]
    pq = pq.transpose(0, 1, 3, 2, 4).reshape(B, KVH, nt, Rt, G, Hd)
    v_col = v.astype(jnp.float32)[..., None]  # [B, KVH, Hd, 1]
    g = jnp.exp(log_g.astype(jnp.float32))[..., None, None]  # [B, KVH, 1, 1]

    def s_map(b, h, i, layer, act):
        return (layer[0], b, h, i, 0, 0)

    def z_map(b, h, i, layer, act):
        return (layer[0], b, h, i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, nt),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Rt, Hd, Hd), s_map),
            pl.BlockSpec((1, 1, 1, 1, Rt, Hd), z_map),
            pl.BlockSpec((1, 1, 1, Rt, Hd), lambda b, h, i, *_: (b, h, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, Rt, G, Hd), lambda b, h, i, *_: (b, h, i, 0, 0, 0)),
            pl.BlockSpec((1, 1, Hd, 1), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, i, *_: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Rt, Hd, Hd), s_map),
            pl.BlockSpec((1, 1, 1, 1, Rt, Hd), z_map),
            pl.BlockSpec((1, 1, G, Hd, 1), lambda b, h, i, *_: (b, h, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, Hd, Hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    S1, z1, o = pl.pallas_call(
        functools.partial(_step_kernel, Rt=Rt, G=G, Hd=Hd),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
            jax.ShapeDtypeStruct((L, B, KVH, nt, Rt, Hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, G, Hd, 1), jnp.float32),
        ],
        # operands count the two prefetched scalars: S is 2, z is 3
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=STEP_NAME,
    )(
        layer, active.astype(jnp.int32), S, z.reshape(L, B, KVH, nt, Rt, Hd),
        pk, pq, v_col, g,
    )
    return o.reshape(B, H, Hd), S1, z1.reshape(z.shape)


def _chunk_kernel(q_ref, k_ref, v_ref, vt_ref, cc_ref, cr_ref, s_in, z_in,
                  o_ref, s_out, z_out, *, C: int, G: int, Hd: int, R: int):
    """One (KV head, sub-chunk of C tokens); the head's entry stays in
    s_out/z_out across its sub-chunks.

    q_ref [1, G, C, Hd], k_ref/v_ref [1, C, Hd], vt_ref [1, Hd, C] (v
    transposed: the state's update is then a plain matmul), cc_ref [1, C, 1]
    and cr_ref [1, 1, C] the inclusive cumulative log-decay inside the
    sub-chunk as a column and as a row, s_in/s_out [1, R, Hd, Hd],
    z_in/z_out [1, R, Hd], o_ref [1, G, C, Hd]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out[...] = s_in[...]
        z_out[...] = z_in[...]

    f32 = jnp.float32
    dot = functools.partial(lax.dot_general, precision=_HI, preferred_element_type=f32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    q = q_ref[0].reshape(G * C, Hd).astype(f32)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)
    cc, cr = cc_ref[0], cr_ref[0]  # [C, 1], [1, C]
    ccg = jnp.concatenate([cc] * G, axis=0)  # [G*C, 1]

    # inside the sub-chunk: the quadratic form
    s = dot(q, k, nt)  # [G*C, C]
    t_idx = lax.broadcasted_iota(jnp.int32, (G * C, C), 0) % C
    i_idx = lax.broadcasted_iota(jnp.int32, (G * C, C), 1)
    a = jnp.where(
        i_idx <= t_idx, s * s * (1.0 / Hd) * jnp.exp(jnp.minimum(ccg - cr, 0.0)), 0.0
    )
    num = dot(a, v, nn)  # [G*C, Hd]
    den = jnp.sum(a, axis=-1, keepdims=True)

    # against the incoming state, and the state's own move
    scale = Hd**-0.5
    qd = q * (jnp.exp(ccg) * scale)  # the decay from the sub-chunk's start
    last = cr[:, C - 1:C]  # [1, 1]
    kd = k * scale
    vtw = vt_ref[0].astype(f32) * jnp.exp(last - cr)  # [Hd, C]
    wk = jnp.exp(last - cc)  # [C, 1]
    gC = jnp.exp(last)
    accz = jnp.zeros((G * C, Hd), f32)
    for d in range(R):
        w = phi_weight(d, Hd)
        shift = (Hd - d) % Hd
        q_roll = q if shift == 0 else pltpu.roll(q, shift, 1)
        k_roll = k if shift == 0 else pltpu.roll(k, shift, 1)
        pq = qd * q_roll * w  # [G*C, Hd]
        pk = kd * k_roll * w  # [C, Hd]
        Sd = s_out[0, d]  # [Hd(v), Hd(k)]
        zd = z_out[0, d:d + 1, :]
        num = num + dot(pq, Sd, nt)
        accz = accz + pq * zd
        s_out[0, d] = gC * Sd + dot(vtw, pk, nn)
        z_out[0, d:d + 1, :] = gC * zd + jnp.sum(pk * wk, axis=0, keepdims=True)
    den = den + jnp.sum(accz, axis=-1, keepdims=True)
    o_ref[0] = (num / (den + EPS)).reshape(G, C, Hd).astype(o_ref.dtype)


def _chunk_pallas(S, z, q, k, v, log_g, valid, interpret: bool):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    T, H, Hd = q.shape
    KVH = k.shape[1]
    G = H // KVH
    R = S.shape[1]
    k, cum, C = _chunk_inputs(q, k, v, log_g, valid)
    qh = q.reshape(T, KVH, G, Hd).transpose(1, 2, 0, 3)  # [KVH, G, T, Hd]
    kh = k.transpose(1, 0, 2)  # [KVH, T, Hd]
    vh = v.transpose(1, 0, 2)
    vt = v.transpose(1, 2, 0)  # [KVH, Hd, T]
    cum = cum.T  # [KVH, T]
    state = 2 * 2 * (R * Hd * Hd + R * Hd) * 4  # in and out, double-buffered
    o, S1, z1 = pl.pallas_call(
        functools.partial(_chunk_kernel, C=C, G=G, Hd=Hd, R=R),
        grid=(KVH, T // C),
        in_specs=[
            pl.BlockSpec((1, G, C, Hd), lambda h, c: (h, 0, c, 0)),
            pl.BlockSpec((1, C, Hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, C, Hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, Hd, C), lambda h, c: (h, 0, c)),
            pl.BlockSpec((1, C, 1), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, C), lambda h, c: (h, 0, c)),
            pl.BlockSpec((1, R, Hd, Hd), lambda h, c: (h, 0, 0, 0)),
            pl.BlockSpec((1, R, Hd), lambda h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, G, C, Hd), lambda h, c: (h, 0, c, 0)),
            pl.BlockSpec((1, R, Hd, Hd), lambda h, c: (h, 0, 0, 0)),
            pl.BlockSpec((1, R, Hd), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((KVH, G, T, Hd), q.dtype),
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=state + 32 * 1024 * 1024,
        ),
        interpret=interpret,
        name=CHUNK_NAME,
    )(qh, kh, vh, vt, cum[:, :, None], cum[:, None, :], S, z)
    return o.transpose(2, 0, 1, 3).reshape(T, H, Hd), S1, z1


# ---- the dispatchers ------------------------------------------------------
def retention_impl() -> str:
    """The implementation for this process: the kernel on a TPU backend,
    the interpreted kernel under DNET_FLASH_INTERPRET=1, else `jax.numpy`."""
    from dnet_tpu.ops.kernel_select import kernel_backend

    return kernel_backend() or "emulate"


def _check_impl(impl: str) -> None:
    if impl not in RETENTION_IMPLS:
        raise ValueError(f"retention impl {impl!r} not in {RETENTION_IMPLS}")


def retention_step(store: dict, q, k, v, log_g, active, layer, impl: str = "emulate"):
    """One decode token a lane against the store's stack, in place.

    store {"S": [L, B, KVH, R, Hd, Hd], "z": [L, B, KVH, R, Hd]} float32
    (donate it: the kernel aliases it, the `jax.numpy` form updates its
    layer's slice); q [B, H, Hd], k/v [B, KVH, Hd], log_g [B, KVH] the
    log of each KV head's gate, active [B]: an idle lane's entry neither
    decays nor takes a key; `layer` a traced index.  Returns
    (o [B, H, Hd] float32, the store)."""
    _check_impl(impl)
    SELECTIONS.record(STEP_NAME, impl)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if impl == "emulate":
        S = lax.dynamic_index_in_dim(store["S"], layer[0], 0, keepdims=False)
        z = lax.dynamic_index_in_dim(store["z"], layer[0], 0, keepdims=False)
        o, S, z = _step_emulate(S, z, q, k, v, log_g, active)
        return o, {
            "S": lax.dynamic_update_index_in_dim(store["S"], S, layer[0], 0),
            "z": lax.dynamic_update_index_in_dim(store["z"], z, layer[0], 0),
        }
    o, S, z = _step_pallas(
        store["S"], store["z"], q, k, v, log_g, active, layer, impl == "interpret"
    )
    return o, {"S": S, "z": z}


def retention_chunk(state: dict, q, k, v, log_g, valid=None, impl: str = "emulate"):
    """T tokens of ONE sequence against its own entry (one layer's):
    state {"S": [KVH, R, Hd, Hd], "z": [KVH, R, Hd]}, q [T, H, Hd], k/v
    [T, KVH, Hd], log_g [T, KVH], valid [T] bool (padding past the real
    tokens leaves the state alone; its outputs are garbage).  T is a
    multiple of min(T, 128).  Returns (o [T, H, Hd] in q's type, the
    state after the chunk)."""
    _check_impl(impl)
    SELECTIONS.record(CHUNK_NAME, impl)
    if impl == "emulate":
        o, S, z = _chunk_emulate(state["S"], state["z"], q, k, v, log_g, valid)
        return o.astype(q.dtype), {"S": S, "z": z}
    o, S, z = _chunk_pallas(
        state["S"], state["z"], q, k, v, log_g, valid, impl == "interpret"
    )
    return o, {"S": S, "z": z}
