"""Lightning linear attention (ops/lightning.py): the decode step and the
chunked prefill against the quadratic form, at small sizes on the CPU, the
`jax.numpy` form and the interpreted kernel alike."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.ops import lightning as L

IMPLS = ("emulate", "interpret")
TOL = 5e-5


def qkv(T, H, D, seed=0):
    key = jax.random.key(seed)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (T, H, D)) for i in range(3))


def test_the_decay_is_alibis_slope_table():
    lg = L.log_decay(32)
    assert lg.dtype == np.float32 and lg.shape == (32,)
    assert np.allclose(lg[0], -(2.0 ** -0.25)) and np.allclose(lg[-1], -(2.0 ** -8))
    assert np.all(np.diff(lg) > 0)  # later heads remember longer
    assert L.state_entry_bytes(32, 128) == 32 * 128 * 128 * 4


def test_the_quadratic_form_is_the_recurrence():
    q, k, v = qkv(40, 3, 8)
    lam = np.exp(L.log_decay(3))
    S = np.zeros((3, 8, 8))
    want = []
    for t in range(40):
        S = lam[:, None, None] * S + np.einsum("hk,hv->hkv", k[t], v[t])
        want.append(np.einsum("hkv,hk->hv", S, q[t]) / np.sqrt(8))
    assert np.max(np.abs(np.stack(want) - L.lightning_quadratic(q, k, v))) < TOL


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("T,cuts", [(300, (200,)), (256, (128,)), (77, (16, 48)), (130, (1, 129))])
def test_chunks_carry_the_state_across_their_edges(impl, T, cuts):
    """A sequence in chunks of any length equals the quadratic form over
    the whole: sub-chunk edges (128), chunk edges, a chunk of one token."""
    q, k, v = qkv(T, 4, 16)
    want = L.lightning_quadratic(q, k, v)
    S = jnp.zeros((4, 16, 16))
    outs, edges = [], (0, *cuts, T)
    for a, b in zip(edges, edges[1:]):
        o, S = L.lightning_chunk(S, q[a:b], k[a:b], v[a:b], impl=impl)
        assert o.dtype == jnp.float32 and S.dtype == jnp.float32
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs) - want))) < TOL


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("real,padded", [(100, 128), (5, 16), (129, 256)])
def test_padding_neither_decays_the_state_nor_adds_a_key(impl, real, padded):
    q, k, v = qkv(real + 4, 4, 16, seed=1)
    want = L.lightning_quadratic(q, k, v)

    def pad(a):
        return jnp.pad(a[:real], ((0, padded - real), (0, 0), (0, 0)), constant_values=3.0)

    o, S = L.lightning_chunk(
        jnp.zeros((4, 16, 16)), pad(q), pad(k), pad(v), valid=jnp.arange(padded) < real, impl=impl
    )
    assert float(jnp.max(jnp.abs(o[:real] - want[:real]))) < TOL
    # the state handed on is the one after the REAL tokens: the next ones agree
    o2, _ = L.lightning_chunk(S, q[real:], k[real:], v[real:], impl=impl)
    assert float(jnp.max(jnp.abs(o2 - want[real:]))) < TOL


@pytest.mark.parametrize("impl", IMPLS)
def test_a_step_moves_its_lane_and_layer_alone(impl):
    """Three lanes, two layers: lane 1 of layer 1 steps after a chunk; the
    idle lanes' entries and the other layer are copied through untouched."""
    T, H, D = 50, 8, 16
    q, k, v = qkv(T + 3, H, D, seed=2)
    want = L.lightning_quadratic(q, k, v)
    _, S1 = L.lightning_chunk(jnp.zeros((H, D, D)), q[:T], k[:T], v[:T], impl=impl)
    store = jnp.zeros((2, 3, H, D, D)).at[1, 1].set(S1).at[1, 2].set(7.0).at[0].set(5.0)
    step = jax.jit(
        lambda S, qb, kb, vb: L.lightning_step(
            S, qb, kb, vb, jnp.array([0, 1, 0]), jnp.int32(1), impl=impl
        ),
        donate_argnums=(0,),
    )
    for t in range(T, T + 3):
        rows = [jnp.stack([a[t] * 0 + 9.0, a[t], a[t]]) for a in (q, k, v)]
        o, store = step(store, *rows)
        assert o.dtype == jnp.float32 and store.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(o[1] - want[t]))) < TOL
    assert bool(jnp.all(store[1, 2] == 7.0)) and bool(jnp.all(store[1, 0] == 0.0))
    assert bool(jnp.all(store[0] == 5.0))


def test_an_unknown_impl_is_refused():
    q, k, v = qkv(8, 2, 8)
    with pytest.raises(ValueError, match="lightning impl"):
        L.lightning_chunk(jnp.zeros((2, 8, 8)), q, k, v, impl="dense")


def test_the_dispatchers_book_their_choice():
    from dnet_tpu.ops.kernel_select import SELECTIONS

    before = SELECTIONS.snapshot()
    q, k, v = qkv(8, 2, 8)
    L.lightning_chunk(jnp.zeros((2, 8, 8)), q, k, v, impl="emulate")
    L.lightning_step(jnp.zeros((1, 1, 2, 8, 8)), q[:1], k[:1], v[:1], jnp.array([1]), 0, impl="emulate")
    after = SELECTIONS.snapshot()
    assert after["lightning_chunk"]["emulate"] == before["lightning_chunk"]["emulate"] + 1
    assert after["lightning_step"]["emulate"] == before["lightning_step"]["emulate"] + 1
