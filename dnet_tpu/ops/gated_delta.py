"""The gated delta rule (Gated DeltaNet): the recurrent step and the chunked
prefill, as Pallas kernels with their plain `jax.numpy` forms beside them,
and the causal depthwise convolution that feeds them.

A value head keeps a state `S [Dk, Dv]` float32 that is DECAYED and then
CORRECTED by what it already holds for the incoming key (unlike
ops/retention.py's additive state):

    q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(Dk)      k^ = k / sqrt(sum k^2 + 1e-6)
    S_t = a_t S_{t-1} + k^_t (x) [ b_t ( v_t - (a_t S_{t-1})^T k^_t ) ]
    o_t = S_t^T q^_t                    a_t = exp(g_t) in (0, 1],  b_t in (0, 1)

one `g` and one `b` a value head, value head h reading key head h // G
(G = value heads / key heads).  The l2 norms are part of the op: every
implementation gets the same normalised float32 q^ and k^.

Two ops, three implementations each behind one dispatcher (the
`paged_attend` convention): `pallas` on a TPU backend and nothing else
there, `interpret` (the same kernel, DNET_FLASH_INTERPRET=1 on the CPU),
`emulate` (the `jax.numpy` form, what a CPU backend serves through).

- `gdn_step`: one token a lane against the store's whole stack, in place:
  the kernel takes the layer by index and aliases the store, an idle
  lane's entry is copied through untouched.  Bound by memory: an entry is
  read and written once.
- `gdn_chunk`: T tokens of one sequence against its own entry, in chunks
  of 64.  Inside a chunk, with `G` the running sum of `g`, the corrected
  values are the solve of a unit lower triangular system,

      A[i,j] = b_i (k^_i . k^_j) exp(G_i - G_j)      j < i   (else 0)
      (I + A) U = diag(b) V        (I + A) W = diag(b exp(G)) K^

  done as the six-factor product `(I - A)(I + A^2)(I + A^4)...(I + A^32)`
  (A is nilpotent: A^64 = 0), which is all matmuls; then each chunk reads
  the incoming state decayed to each row and hands on the state decayed
  over the chunk plus its keys' corrected values.  It equals the
  recurrence exactly (tests/test_gated_delta.py).

`gdn_recurrence` is the definition (token by token): the tests and
scripts/gdn_parity.py hold the two ops to it.

The convolution (`causal_conv`, `conv_step`) is `jax.numpy`: 4
multiply-adds a channel, with the last 3 columns carried as the TAIL.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dnet_tpu.ops.kernel_select import SELECTIONS

L2_EPS = 1e-6
GDN_IMPLS = ("pallas", "interpret", "emulate")
#: the custom calls' names in a device trace
STEP_NAME = "gdn_step"
CHUNK_NAME = "gdn_chunk"
#: tokens of one chunk of the chunked form
CHUNK = 64
#: value heads one grid step of the decode kernel holds
STEP_HEADS = 8
_HI = lax.Precision.HIGHEST


def l2_normalise(q, k):
    """-> (q^, k^) float32: unit vectors, q^ scaled by 1 / sqrt(Dk)."""
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    Dk = q.shape[-1]
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * Dk**-0.5
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    return q, k


# ---- the convolution -------------------------------------------------------
def causal_conv(tail, m, w, t_real=None):
    """Depthwise causal convolution and SiLU over T tokens of one sequence.

    tail [K-1, C] the K-1 inputs before the chunk (zeros at a sequence's
    start), m [T, C], w [K, C] (tap j multiplies the input K-1-j tokens
    back), t_real the count of real tokens (the rest is bucket padding).
    Returns (c [T, C] in m's type, the tail after the real tokens)."""
    K = w.shape[0]
    T = m.shape[0]
    xs = jnp.concatenate([tail.astype(m.dtype), m], axis=0)  # [T + K - 1, C]
    acc = jnp.zeros(m.shape, jnp.float32)
    for j in range(K):
        acc = acc + xs[j:j + T].astype(jnp.float32) * w[j].astype(jnp.float32)
    at = T if t_real is None else t_real
    new_tail = lax.dynamic_slice_in_dim(xs, at, K - 1, axis=0)
    return jax.nn.silu(acc).astype(m.dtype), new_tail.astype(tail.dtype)


def conv_step(tails, m, w, active, layer):
    """One token a lane against the store's stack of tails, in place.

    tails [L, B, K-1, C], m [B, C], w [K, C], active [B], `layer` a traced
    index.  Returns (c [B, C] in m's type, the stack): an idle lane's tail
    is untouched."""
    tail = lax.dynamic_index_in_dim(tails, layer, 0, keepdims=False)  # [B, K-1, C]
    xs = jnp.concatenate([tail.astype(m.dtype), m[:, None]], axis=1)  # [B, K, C]
    acc = jnp.sum(xs.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1)
    new = jnp.where(active.astype(bool)[:, None, None], xs[:, 1:].astype(tails.dtype), tail)
    return (
        jax.nn.silu(acc).astype(m.dtype),
        lax.dynamic_update_index_in_dim(tails, new, layer, 0),
    )


# ---- the definition ---------------------------------------------------------
def gdn_recurrence(S, q, k, v, g, beta):
    """Token by token.  S [HV, Dk, Dv] float32, q/k [T, HK, Dk], v
    [T, HV, Dv], g/beta [T, HV] -> (o [T, HV, Dv] float32, S)."""
    HV = v.shape[1]
    G = HV // k.shape[1]
    q, k = l2_normalise(q, k)
    q = jnp.repeat(q, G, axis=1)
    k = jnp.repeat(k, G, axis=1)

    def step(S, xs):
        q, k, v, g, b = xs
        Sd = S * jnp.exp(g)[:, None, None]
        kv = jnp.einsum("hkv,hk->hv", Sd, k, precision=_HI)
        u = b[:, None] * (v - kv)
        S = Sd + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision=_HI)

    S, o = lax.scan(
        step, S.astype(jnp.float32),
        (q, k, v.astype(jnp.float32), g.astype(jnp.float32), beta.astype(jnp.float32)),
    )
    return o, S


# ---- the jax.numpy forms ----------------------------------------------------
def _step_emulate(S, q, k, v, alpha, beta, active):
    """One token a lane.  S [B, HV, Dk, Dv]; q/k [B, HV, Dk] normalised and
    spread over the value heads; v [B, HV, Dv]; alpha/beta [B, HV]."""
    Sd = S * alpha[..., None, None]
    kv = jnp.einsum("bhkv,bhk->bhv", Sd, k, precision=_HI)
    u = beta[..., None] * (v - kv)
    S1 = Sd + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S1, q, precision=_HI)
    return o, jnp.where(active.astype(bool)[:, None, None, None], S1, S)


def _neumann_inverse(A, mm):
    """(I + A)^-1 for a strictly lower triangular [..., C, C] with C <= 64:
    (I - A)(I + A^2)(I + A^4)(I + A^8)(I + A^16)(I + A^32), by the matmul
    `mm` (the kernel's and the `jax.numpy` form's alike)."""
    C = A.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    T = jnp.where(i == j, 1.0, 0.0) - A
    P = A
    n = 2
    while n < C:
        P = mm(P, P)
        T = T + mm(T, P)
        n *= 2
    return T


def _chunk_inputs(q, k, v, g, beta, valid):
    """Pads T to whole chunks; padding neither decays the state nor adds a
    key (g 0, beta 0).  Returns the per-head [H, n, C, ...] views and the
    in-chunk running sums of g."""
    T = q.shape[0]
    n = -(-T // CHUNK)
    pad = n * CHUNK - T
    g = g.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    if valid is not None:
        g = jnp.where(valid[:, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    q, k = l2_normalise(q, k)

    def heads_first(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape(n, CHUNK, *a.shape[1:])
        return jnp.moveaxis(a, 2, 0)  # [H, n, C, ...]

    G = jnp.cumsum(heads_first(g), axis=-1)  # [HV, n, C]
    return (heads_first(q), heads_first(k), heads_first(v.astype(jnp.float32)),
            G, heads_first(beta), T)


def _chunk_emulate(S, q, k, v, g, beta, valid):
    """The chunked form in `jax.numpy`, one scan over the chunks."""
    HV = v.shape[1]
    rep = HV // k.shape[1]
    qh, kh, vh, G, bh, T = _chunk_inputs(q, k, v, g, beta, valid)
    qh = jnp.repeat(qh, rep, axis=0)
    kh = jnp.repeat(kh, rep, axis=0)
    C = CHUNK
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    mm = functools.partial(jnp.matmul, precision=_HI)

    def body(S, xs):
        q, k, v, G, b = xs  # [HV, C, ...]
        D = jnp.exp(jnp.where(lower, G[:, :, None] - G[:, None, :], 0.0))
        kk = mm(k, jnp.swapaxes(k, -1, -2))
        A = jnp.where(strict, b[:, :, None] * kk * D, 0.0)
        Tm = _neumann_inverse(A, mm)
        eG = jnp.exp(G)[..., None]
        u = mm(Tm, v * b[..., None])
        w = mm(Tm, k * (b[..., None] * eG))
        v_new = u - mm(w, S)
        qk = jnp.where(lower, mm(q, jnp.swapaxes(k, -1, -2)) * D, 0.0)
        o = mm(q * eG, S) + mm(qk, v_new)
        last = G[:, -1]
        kd = k * jnp.exp(last[:, None] - G)[..., None]
        S = S * jnp.exp(last)[:, None, None] + mm(jnp.swapaxes(kd, -1, -2), v_new)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (qh, kh, vh, G, bh))
    S, o = lax.scan(body, S.astype(jnp.float32), xs)  # o [n, HV, C, Dv]
    o = jnp.moveaxis(o, 1, 0).reshape(HV, -1, o.shape[-1])[:, :T]
    return jnp.moveaxis(o, 0, 1), S


# ---- the kernels ------------------------------------------------------------
def _column(row, n: int):
    """[1, n] -> [n, 1] on the vector unit: the row spread down the
    sublanes, masked to the diagonal and summed along the lanes (exact)."""
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _step_kernel(layer_ref, act_ref, s_ref, q_ref, k_ref, v_ref, a_ref, b_ref,
                 s_out, o_ref, *, Ht: int, Dk: int):
    """One (lane, block of Ht value heads): decay, read S^T k^, rank-one
    correction, read S^T q^.

    s_ref/s_out [1, 1, Ht, Dk, Dv]; q_ref/k_ref [1, Ht, Dk] rows;
    v_ref/a_ref/b_ref [1, Ht, Dv] rows (alpha and beta spread along the
    lanes); o_ref [1, Ht, Dv]."""
    import jax.experimental.pallas as pl

    live = act_ref[pl.program_id(0)] > 0

    @pl.when(live)
    def _():
        for j in range(Ht):
            kc = _column(k_ref[0, j:j + 1, :], Dk)  # [Dk, 1]
            qc = _column(q_ref[0, j:j + 1, :], Dk)
            Sd = s_ref[0, 0, j] * a_ref[0, j:j + 1, :]
            kv = jnp.sum(Sd * kc, axis=0, keepdims=True)  # [1, Dv]
            u = b_ref[0, j:j + 1, :] * (v_ref[0, j:j + 1, :] - kv)
            S1 = Sd + kc * u
            s_out[0, 0, j] = S1
            o_ref[0, j:j + 1, :] = jnp.sum(S1 * qc, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _step_pallas(S, q, k, v, alpha, beta, active, layer, interpret: bool):
    """S [L, B, HV, Dk, Dv]: the store's stack, aliased; `layer` int32 [1]."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    L, B, HV, Dk, Dv = S.shape
    Ht = STEP_HEADS if HV % STEP_HEADS == 0 else HV

    def spread(a):  # a scalar a head, along the lanes of a row
        return jnp.broadcast_to(a[..., None], (B, HV, Dv))

    def s_map(b, h, layer, act):
        return (layer[0], b, h, 0, 0)

    def row_map(b, h, *_):
        return (b, h, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, HV // Ht),
        in_specs=[
            pl.BlockSpec((1, 1, Ht, Dk, Dv), s_map),
            pl.BlockSpec((1, Ht, Dk), row_map),
            pl.BlockSpec((1, Ht, Dk), row_map),
            pl.BlockSpec((1, Ht, Dv), row_map),
            pl.BlockSpec((1, Ht, Dv), row_map),
            pl.BlockSpec((1, Ht, Dv), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Ht, Dk, Dv), s_map),
            pl.BlockSpec((1, Ht, Dv), row_map),
        ],
    )
    S1, o = pl.pallas_call(
        functools.partial(_step_kernel, Ht=Ht, Dk=Dk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
            jax.ShapeDtypeStruct((B, HV, Dv), jnp.float32),
        ],
        # operands count the two prefetched scalars: S is 2
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=STEP_NAME,
    )(layer, active.astype(jnp.int32), S, q, k, v, spread(alpha), spread(beta))
    return o, S1


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, g_ref, b_ref, s_in, o_ref, s_out, *, C: int):
    """One (value head, chunk of C tokens); the head's state stays in s_out
    across its chunks.

    q_ref/k_ref [1, 1, C, Dk] (the head's KEY head), kt_ref [1, 1, Dk, C]
    (k transposed: the state's update is then a plain matmul), v_ref
    [1, 1, C, Dv], g_ref/b_ref [1, 1, 1, C] rows (the running sum of g inside the chunk,
    beta), s_in/s_out [1, Dk, Dv], o_ref [1, 1, C, Dv]."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_out[...] = s_in[...]

    f32 = jnp.float32
    dot = functools.partial(lax.dot_general, precision=_HI, preferred_element_type=f32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    Gr = g_ref[0, 0]  # [1, C]
    Gc = _column(Gr, C)  # [C, 1]
    bc = _column(b_ref[0, 0], C)
    S = s_out[0]

    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    D = jnp.exp(jnp.minimum(Gc - Gr, 0.0))  # exp(G_i - G_j), used where j <= i
    A = jnp.where(j < i, bc * dot(k, k, nt) * D, 0.0)
    Tm = _neumann_inverse(A, lambda a, b: dot(a, b, nn))
    eG = jnp.exp(Gc)
    u = dot(Tm, v * bc, nn)
    w = dot(Tm, k * (bc * eG), nn)
    v_new = u - dot(w, S, nn)
    qk = jnp.where(j <= i, dot(q, k, nt) * D, 0.0)
    o_ref[0, 0] = dot(q * eG, S, nn) + dot(qk, v_new, nn)
    last = Gr[:, C - 1:C]  # [1, 1]
    kd = kt_ref[0, 0] * jnp.exp(last - Gr)  # [Dk, C]
    s_out[0] = S * jnp.exp(last) + dot(kd, v_new, nn)


def _chunk_pallas(S, q, k, v, g, beta, valid, interpret: bool):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    HV, Dk, Dv = S.shape
    rep = HV // k.shape[1]
    qh, kh, vh, G, bh, T = _chunk_inputs(q, k, v, g, beta, valid)
    n = qh.shape[1]
    C = CHUNK
    o, S1 = pl.pallas_call(
        functools.partial(_chunk_kernel, C=C),
        grid=(HV, n),
        in_specs=[
            pl.BlockSpec((1, 1, C, Dk), lambda h, c: (h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, C, Dk), lambda h, c: (h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, Dk, C), lambda h, c: (h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, C, Dv), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, C), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, C), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, Dv), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((HV, n, C, Dv), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=CHUNK_NAME,
    )(qh, kh, jnp.swapaxes(kh, -1, -2), vh, G[:, :, None, :], bh[:, :, None, :], S.astype(jnp.float32))
    o = o.reshape(HV, n * C, Dv)[:, :T]
    return jnp.moveaxis(o, 0, 1), S1


# ---- the dispatchers --------------------------------------------------------
def gdn_impl() -> str:
    """The implementation for this process: the kernel on a TPU backend,
    the interpreted kernel under DNET_FLASH_INTERPRET=1, else `jax.numpy`."""
    from dnet_tpu.ops.kernel_select import kernel_backend

    return kernel_backend() or "emulate"


def _check_impl(impl: str) -> None:
    if impl not in GDN_IMPLS:
        raise ValueError(f"gated delta impl {impl!r} not in {GDN_IMPLS}")


def gdn_step(S, q, k, v, g, beta, active, layer, impl: str = "emulate"):
    """One decode token a lane against the store's stack, in place.

    S [L, B, HV, Dk, Dv] float32 (donate it: the kernel aliases it, the
    `jax.numpy` form updates its layer's slice); q/k [B, HK, Dk] as the
    convolution left them, v [B, HV, Dv], g/beta [B, HV] (float32: the log
    of the decay, the correction's strength), active [B]: an idle lane's
    entry neither decays nor takes a key; `layer` a traced index.  Returns
    (o [B, HV, Dv] float32, the stack)."""
    _check_impl(impl)
    SELECTIONS.record(STEP_NAME, impl)
    HV = v.shape[1]
    rep = HV // k.shape[1]
    q, k = l2_normalise(q, k)
    q = jnp.repeat(q, rep, axis=1)
    k = jnp.repeat(k, rep, axis=1)
    v = v.astype(jnp.float32)
    alpha = jnp.exp(g.astype(jnp.float32))
    beta = beta.astype(jnp.float32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if impl == "emulate":
        S_l = lax.dynamic_index_in_dim(S, layer[0], 0, keepdims=False)
        o, S_l = _step_emulate(S_l, q, k, v, alpha, beta, active)
        return o, lax.dynamic_update_index_in_dim(S, S_l, layer[0], 0)
    return _step_pallas(S, q, k, v, alpha, beta, active, layer, impl == "interpret")


def gdn_chunk(S, q, k, v, g, beta, valid=None, impl: str = "emulate"):
    """T tokens of ONE sequence against its own entry (one layer's):
    S [HV, Dk, Dv] float32, q/k [T, HK, Dk] as the convolution left them,
    v [T, HV, Dv], g/beta [T, HV], valid [T] bool (padding past the real
    tokens leaves the state alone; its outputs are garbage).  Any T: the
    last chunk may be ragged.  Returns (o [T, HV, Dv] float32, the state
    after the chunk)."""
    _check_impl(impl)
    SELECTIONS.record(CHUNK_NAME, impl)
    if impl == "emulate":
        return _chunk_emulate(S, q, k, v, g, beta, valid)
    return _chunk_pallas(S, q, k, v, g, beta, valid, impl == "interpret")


# ---- the mixer's core: convolution, split, delta rule ------------------------
def split_qkv(c, HV: int, Dk: int, Dv: int):
    """The convolved channels [..., 2 HK Dk + HV Dv], laid out q | k | v,
    -> q/k [..., HK, Dk], v [..., HV, Dv]."""
    key = (c.shape[-1] - HV * Dv) // 2
    lead = c.shape[:-1]
    return (
        c[..., :key].reshape(*lead, key // Dk, Dk),
        c[..., key:2 * key].reshape(*lead, key // Dk, Dk),
        c[..., 2 * key:].reshape(*lead, HV, Dv),
    )


def gdn_prefill(state: dict, m, conv_w, g, beta, t_real=None, impl: str = "emulate"):
    """T tokens of ONE sequence through one layer's convolution and delta
    rule.  state {"S": [HV, Dk, Dv] float32, "conv": [K-1, C]}, m [T, C]
    the projections before the convolution, g/beta [T, HV], t_real the
    count of real tokens.  Returns (o [T, HV, Dv] float32, the state after
    the real tokens).  One token (a decode step outside the store) goes
    token by token: the chunk kernel is a prefill kernel."""
    T = m.shape[0]
    HV, Dk, Dv = state["S"].shape
    c, tail = causal_conv(state["conv"], m, conv_w, t_real)
    q, k, v = split_qkv(c, HV, Dk, Dv)
    if T == 1:
        o, S = gdn_recurrence(state["S"], q, k, v, g, beta)
    else:
        valid = None if t_real is None else jnp.arange(T) < t_real
        o, S = gdn_chunk(state["S"], q, k, v, g, beta, valid=valid, impl=impl)
    return o, {"S": S, "conv": tail}


def gdn_decode(store: dict, m, conv_w, g, beta, active, layer, impl: str = "emulate"):
    """One decode token a lane through one layer's convolution and delta
    rule, in place on the store's stacks {"S": [L, B, HV, Dk, Dv], "conv":
    [L, B, K-1, C]}: m [B, C], g/beta [B, HV], active [B], `layer` a traced
    index.  Returns (o [B, HV, Dv] float32, the store)."""
    _, _, HV, Dk, Dv = store["S"].shape
    c, tails = conv_step(store["conv"], m, conv_w, active, layer)
    q, k, v = split_qkv(c, HV, Dk, Dv)
    o, S = gdn_step(store["S"], q, k, v, g, beta, active, layer, impl=impl)
    return o, {"S": S, "conv": tails}
