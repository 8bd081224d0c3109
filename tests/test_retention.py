"""Gated power retention (ops/retention.py): the decode step, the chunked
prefill and the quadratic definition agree in float32 at a small head
(`Hd` 16, so a state entry is 9 x 16 x 16) with log-gates in [-0.02, 0] over
four chunks and a ragged last one, an idle lane's entry is untouched, and
the interpreted kernels equal the `jax.numpy` forms.

The gates matter: with log g near 0 a key still weighs (0.99)^n of itself n
tokens on, so a state dropped, decayed twice or handed to the wrong lane a
hundred tokens back moves every later output by far more than rounding.
The last tests make exactly those three mistakes and see them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.ops import retention as R

HD, KVH, G = 16, 2, 4
H = KVH * G
CHUNK, RAGGED = 32, 13
T = 4 * CHUNK + RAGGED
TOL = 2e-4  # float32, outputs of size ~2 (measured 6e-7 .. 5e-5)
IMPLS = ("emulate", "interpret")


@pytest.fixture(autouse=True)
def small_sub_chunk(monkeypatch):
    """Sub-chunks of 16 tokens: a 32-token chunk crosses an inner edge too."""
    monkeypatch.setattr(R, "SUB_CHUNK", 16)


@pytest.fixture(scope="module")
def seq():
    key = jax.random.split(jax.random.key(32), 4)
    n = T + 24
    q = jax.random.normal(key[0], (n, H, HD))
    k = jax.random.normal(key[1], (n, KVH, HD))
    v = jax.random.normal(key[2], (n, KVH, HD))
    lg = -0.02 * jax.random.uniform(key[3], (n, KVH))
    return q, k, v, lg, np.asarray(R.retention_quadratic(q, k, v, lg))


def chunks(seq, impl, width=CHUNK, upto=T, mistake=None):
    """Prefill `upto` tokens a chunk at a time, the last padded with
    garbage.  Returns (outputs, the entry after)."""
    q, k, v, lg, _ = seq
    st, outs = R.init_state((), KVH, HD), []
    for c0 in range(0, upto, width):
        real = min(width, upto - c0)

        def pad(a, fill):
            return jnp.full((width,) + a.shape[1:], fill, a.dtype).at[:real].set(a[c0:c0 + real])

        if mistake == "dropped" and c0 == width:
            st = R.init_state((), KVH, HD)
        o, new = R.retention_chunk(
            st, pad(q, 1.0), pad(k, 1.0), pad(v, 1.0), pad(lg, -1.0),
            valid=jnp.arange(width) < real, impl=impl,
        )
        if mistake == "decayed_twice" and c0 == width:
            total = jnp.exp(jnp.sum(lg[c0:c0 + real], axis=0))  # [KVH]
            new = {
                "S": new["S"] - (1 - total)[:, None, None, None] * total[:, None, None, None] * st["S"],
                "z": new["z"] - (1 - total)[:, None, None] * total[:, None, None] * st["z"],
            }
        st = new
        outs.append(np.asarray(o)[:real])
    return np.concatenate(outs), st


def test_phi_is_the_symmetric_square():
    x, y = jax.random.normal(jax.random.key(1), (2, 5, HD))
    got = jnp.sum(R.phi(x) * R.phi(y), axis=(-1, -2))
    np.testing.assert_allclose(got, jnp.sum(x * y, -1) ** 2 / HD, rtol=1e-5)
    assert R.phi(x).shape == (5, R.phi_rows(HD), HD) and R.phi_rows(128) == 65
    # 8256 distinct entries and 64 held twice at the published head
    assert R.phi_rows(128) * 128 == 128 * 129 // 2 + 64
    assert R.state_entry_bytes(8, 128) == 8 * 65 * 128 * 129 * 4
    with pytest.raises(ValueError):
        R.phi_rows(15)


@pytest.mark.parametrize("impl", IMPLS)
def test_chunked_form_equals_the_quadratic_over_four_chunks_and_a_ragged_one(seq, impl):
    got, _ = chunks(seq, impl)
    assert np.max(np.abs(got - seq[4][:T])) < TOL


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("width", [16, 64])
def test_chunk_width_does_not_matter(seq, impl, width):
    got, st = chunks(seq, impl, width=width)
    assert np.max(np.abs(got - seq[4][:T])) < TOL
    _, st32 = chunks(seq, "emulate")
    np.testing.assert_allclose(st["S"], st32["S"], atol=2e-4)
    np.testing.assert_allclose(st["z"], st32["z"], atol=2e-4)


@pytest.mark.parametrize("impl", IMPLS)
def test_step_form_equals_the_quadratic_and_an_idle_lane_is_untouched(seq, impl):
    q, k, v, lg, want = seq
    B, L = 3, 2
    mark = jax.tree.map(lambda a: a + 0.5, R.init_state((), KVH, HD))
    store = R.init_state((L, B), KVH, HD)
    store = jax.tree.map(lambda s, m: s.at[1, 1].set(m).at[0].set(m), store, mark)
    outs = []
    for t in range(40):
        lane = lambda a: jnp.stack([a[t], a[(t + 5) % 40], a[t]])
        o, store = R.retention_step(
            store, lane(q), lane(k), lane(v), lane(lg), jnp.asarray([1, 0, 1]), 1, impl=impl
        )
        outs.append(np.asarray(o))
    outs = np.stack(outs)
    assert np.max(np.abs(outs[:, 0] - want[:40])) < TOL
    np.testing.assert_array_equal(outs[:, 0], outs[:, 2])  # the same tokens, two lanes
    for leaf in ("S", "z"):
        np.testing.assert_array_equal(store[leaf][1, 1], mark[leaf])  # idle: not decayed, no key
        np.testing.assert_array_equal(store[leaf][0], jnp.stack([mark[leaf]] * B))  # the other layer
        np.testing.assert_array_equal(store[leaf][1, 0], store[leaf][1, 2])


@pytest.mark.parametrize("impl", IMPLS)
def test_chunks_hand_the_state_over_to_the_step(seq, impl):
    q, k, v, lg, want = seq
    _, st = chunks(seq, impl)
    store = jax.tree.map(lambda e: jnp.zeros((1, 2) + e.shape).at[0, 1].set(e), st)
    for t in range(T, T + 24):
        lane = lambda a: jnp.stack([a[0], a[t]])
        o, store = R.retention_step(
            store, lane(q), lane(k), lane(v), lane(lg), jnp.asarray([0, 1]), 0, impl=impl
        )
        assert np.max(np.abs(np.asarray(o[1]) - want[t])) < TOL, t
    assert float(jnp.max(jnp.abs(store["S"][0, 0]))) == 0.0


def test_interpreted_kernels_equal_the_jnp_forms(seq):
    a, sa = chunks(seq, "emulate")
    b, sb = chunks(seq, "interpret")
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(sa["S"], sb["S"], atol=2e-5)
    np.testing.assert_allclose(sa["z"], sb["z"], atol=2e-5)


def test_padding_neither_decays_the_state_nor_adds_a_key(seq):
    q, k, v, lg, _ = seq
    st0 = jax.tree.map(lambda a: a + 0.25, R.init_state((), KVH, HD))
    _, st = R.retention_chunk(
        st0, q[:16], k[:16], v[:16], lg[:16] - 3.0, valid=jnp.zeros((16,), bool)
    )
    np.testing.assert_array_equal(st["S"], st0["S"])
    np.testing.assert_array_equal(st["z"], st0["z"])


@pytest.mark.parametrize("mistake", ["dropped", "decayed_twice"])
def test_a_state_dropped_or_decayed_twice_is_seen(seq, mistake):
    """The check has teeth: lose the first chunk's state before the second,
    or decay it over the second chunk twice, and the outputs from there on
    are off by hundreds of tolerances, a hundred tokens later still."""
    got, _ = chunks(seq, "emulate", mistake=mistake)
    err = np.max(np.abs(got - seq[4][:T]), axis=(1, 2))
    assert np.max(err[:CHUNK]) < TOL  # before the mistake nothing is wrong
    assert np.min(err[2 * CHUNK:2 * CHUNK + 8]) > 100 * TOL
    assert err[-1] > 20 * TOL  # and it does not fade within the sequence


def test_a_state_handed_to_the_wrong_lane_is_seen(seq):
    q, k, v, lg, want = seq
    _, st = chunks(seq, "emulate")
    _, other = chunks(seq, "emulate", upto=2 * CHUNK)
    # lane 0 should continue `st`; it is given lane 1's entry
    store = jax.tree.map(lambda a, b: jnp.stack([b, a])[None], st, other)
    lane = lambda a: jnp.stack([a[T], a[T]])
    o, _ = R.retention_step(store, lane(q), lane(k), lane(v), lane(lg), jnp.asarray([1, 1]), 0)
    assert np.max(np.abs(np.asarray(o[1]) - want[T])) < TOL  # the right entry
    assert np.max(np.abs(np.asarray(o[0]) - want[T])) > 100 * TOL  # the wrong one


def test_unknown_impl_and_ragged_width_are_refused(seq):
    q, k, v, lg, _ = seq
    st = R.init_state((), KVH, HD)
    with pytest.raises(ValueError, match="impl"):
        R.retention_chunk(st, q[:16], k[:16], v[:16], lg[:16], impl="fast")
    with pytest.raises(ValueError, match="multiple"):
        R.retention_chunk(st, q[:24], k[:24], v[:24], lg[:24])


def test_selections_are_booked_under_the_kernels_names():
    from dnet_tpu.ops.kernel_select import KERNELS, SELECTIONS

    assert {R.STEP_NAME, R.CHUNK_NAME} <= set(KERNELS)
    before = SELECTIONS.snapshot()
    st = R.init_state((), 1, 4)
    x = jnp.ones((4, 1, 4))
    R.retention_chunk(st, x, x, x, jnp.zeros((4, 1)), impl="emulate")
    store = R.init_state((1, 1), 1, 4)
    R.retention_step(store, x[0][None], x[0][None], x[0][None], jnp.zeros((1, 1)), jnp.ones((1,)), 0)
    after = SELECTIONS.snapshot()
    for name in (R.STEP_NAME, R.CHUNK_NAME):
        assert after[name]["emulate"] == before[name]["emulate"] + 1
