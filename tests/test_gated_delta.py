"""The gated delta rule (ops/gated_delta.py): the decode step, the chunked
prefill and the token-by-token recurrence agree in float32 at a small head
(2 key / 4 value heads of 16) with `g` in [-0.02, 0] over four chunks and a
ragged last one, the convolution's tail carried across a chunk edge equals
the uncut convolution, an idle lane's state and tail are untouched, and the
interpreted kernels equal the `jax.numpy` forms.

The decay matters: with g near 0 a key still weighs (0.99)^n of itself n
tokens on, so a state dropped, decayed twice or handed to the wrong lane a
hundred tokens back moves every later output by far more than rounding.
The last tests make exactly those mistakes and see them.  Partial rotary
and the zero-centred norm are held to hand-written values here too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.ops import gated_delta as G

HK, HV, DK, DV = 2, 4, 16, 16
CHUNK, RAGGED = 96, 29  # a prefill chunk of 96 tokens crosses a 64-token edge inside it
T = 4 * CHUNK + RAGGED
TOL = 2e-5  # float32, outputs of size ~0.6 (measured 1e-7 .. 7e-7)
IMPLS = ("emulate", "interpret")


@pytest.fixture(scope="module")
def seq():
    key = jax.random.split(jax.random.key(37), 5)
    n = T + 24
    q = jax.random.normal(key[0], (n, HK, DK))
    k = jax.random.normal(key[1], (n, HK, DK))
    v = jax.random.normal(key[2], (n, HV, DV))
    g = -0.02 * jax.random.uniform(key[3], (n, HV))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (n, HV)))
    o, _ = G.gdn_recurrence(jnp.zeros((HV, DK, DV)), q, k, v, g, beta)
    return q, k, v, g, beta, np.asarray(o)


def chunks(seq, impl, width=CHUNK, upto=T, mistake=None):
    """Prefill `upto` tokens a chunk at a time, the last padded with
    garbage.  Returns (outputs, the state after)."""
    q, k, v, g, beta, _ = seq
    S, outs = jnp.zeros((HV, DK, DV)), []
    for c0 in range(0, upto, width):
        real = min(width, upto - c0)

        def pad(a, fill):
            return jnp.full((width,) + a.shape[1:], fill, a.dtype).at[:real].set(a[c0:c0 + real])

        if mistake == "dropped" and c0 == width:
            S = jnp.zeros_like(S)
        if mistake == "decayed twice" and c0 == width:
            S = S * jnp.exp(jnp.sum(g[:width], axis=0))[:, None, None]
        o, new = G.gdn_chunk(
            S, pad(q, 1.0), pad(k, 1.0), pad(v, 1.0), pad(g, -1.0), pad(beta, 0.9),
            valid=jnp.arange(width) < real, impl=impl,
        )
        S = new
        outs.append(np.asarray(o)[:real])
    return np.concatenate(outs), S


@pytest.mark.parametrize("impl", IMPLS)
def test_the_chunked_form_equals_the_recurrence(seq, impl):
    got, _ = chunks(seq, impl)
    assert np.max(np.abs(got - seq[-1][:T])) < TOL


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("width", [16, 64, 200])
def test_any_chunk_width_gives_the_same_state(seq, impl, width):
    """A width under one chunk of 64, exactly one, and one that is no
    multiple of it: the state handed on is the recurrence's."""
    q, k, v, g, beta, _ = seq
    _, S = chunks(seq, impl, width=width, upto=200)
    _, want = G.gdn_recurrence(jnp.zeros((HV, DK, DV)), q[:200], k[:200], v[:200], g[:200], beta[:200])
    assert float(jnp.max(jnp.abs(S - want))) < TOL


def store_of(S, lanes=3, layers=2, at=(1, 2)):
    return jnp.zeros((layers, lanes, HV, DK, DV)).at[at].set(S)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_step_takes_over_from_the_chunks_and_an_idle_lane_is_untouched(seq, impl):
    q, k, v, g, beta, want = seq
    _, S = chunks(seq, impl)
    store = store_of(S).at[1, 1].set(S)  # lane 1 idles beside it
    active = jnp.asarray([1, 0, 1], jnp.int32)
    for t in range(T, T + 12):
        lane = lambda a: jnp.stack([a[t - T], a[t], a[t]])  # noqa: E731
        o, store = G.gdn_step(
            store, lane(q), lane(k), lane(v), lane(g), lane(beta), active, 1, impl=impl
        )
        assert np.max(np.abs(np.asarray(o[2]) - want[t])) < TOL
    assert float(jnp.max(jnp.abs(store[1, 1] - S))) == 0.0  # neither decayed nor written
    assert float(jnp.max(jnp.abs(store[0]))) == 0.0  # the other layer
    assert float(jnp.max(jnp.abs(store[1, 0]))) > 0  # the third lane ran its own sequence


def test_the_interpreted_kernels_equal_the_jax_numpy_forms(seq):
    q, k, v, g, beta, _ = seq
    S0 = jax.random.normal(jax.random.key(1), (HV, DK, DV)) * 0.1
    a = (q[:128], k[:128], v[:128], g[:128], beta[:128])
    (o_e, S_e), (o_i, S_i) = (G.gdn_chunk(S0, *a, impl=impl) for impl in IMPLS)
    assert float(jnp.max(jnp.abs(o_e - o_i))) < 1e-6 and float(jnp.max(jnp.abs(S_e - S_i))) < 1e-6
    store = store_of(S0, at=(0, 0))
    act = jnp.asarray([1, 1, 0], jnp.int32)
    b = tuple(x[:3] for x in (q, k, v, g, beta))
    (o_e, st_e), (o_i, st_i) = (G.gdn_step(store, *b, act, 0, impl=impl) for impl in IMPLS)
    assert float(jnp.max(jnp.abs(o_e[:2] - o_i[:2]))) < 1e-6
    assert float(jnp.max(jnp.abs(st_e - st_i))) < 1e-6
    with pytest.raises(ValueError, match="impl"):
        G.gdn_chunk(S0, *a, impl="mosaic")


@pytest.mark.parametrize("mistake", ["dropped", "decayed twice"])
def test_a_state_lost_or_decayed_twice_at_a_chunk_edge_is_seen(seq, mistake):
    got, _ = chunks(seq, "emulate", mistake=mistake)
    err = np.abs(got - seq[-1][:T]).max(axis=(1, 2))
    assert err[:CHUNK].max() < TOL  # before the edge: nothing yet
    assert err[CHUNK:CHUNK + 16].max() > 100 * TOL  # just after: far past rounding
    # a head of 16 holds 16 directions, and the correction overwrites them:
    # the trace fades within a chunk or two here (at 128 it lasts far longer)
    assert err[CHUNK + 16:CHUNK + 48].max() > 10 * TOL


def test_a_state_handed_to_the_wrong_lane_is_seen(seq):
    q, k, v, g, beta, want = seq
    _, S = chunks(seq, "emulate")
    store = store_of(S, at=(1, 1))  # one lane off
    lane = lambda a: jnp.stack([a[T]] * 3)  # noqa: E731
    o, _ = G.gdn_step(store, lane(q), lane(k), lane(v), lane(g), lane(beta),
                      jnp.ones((3,), jnp.int32), 1)
    assert np.max(np.abs(np.asarray(o[1]) - want[T])) < TOL
    assert np.max(np.abs(np.asarray(o[2]) - want[T])) > 100 * TOL


# ---- the convolution and its tail ------------------------------------------
def test_the_tail_carried_across_a_chunk_edge_equals_the_uncut_convolution():
    key = jax.random.split(jax.random.key(2), 2)
    C, K, n = 24, 4, 50
    m = jax.random.normal(key[0], (n, C))
    w = jax.random.normal(key[1], (K, C))
    zero = jnp.zeros((K - 1, C))
    whole, tail_whole = G.causal_conv(zero, m, w)
    # by hand: tap j multiplies the input K-1-j tokens back, nothing before token 0
    padded = np.concatenate([np.zeros((K - 1, C)), np.asarray(m)])
    hand = sum(padded[j:j + n] * np.asarray(w)[j] for j in range(K))
    assert np.max(np.abs(np.asarray(whole) - hand / (1 + np.exp(-hand)))) < 1e-5
    # cut at 17 (inside the reach of the taps), the second piece padded to 40
    a, tail = G.causal_conv(zero, m[:17], w)
    piece = jnp.concatenate([m[17:], jnp.full((7, C), 9.0)])
    b, tail2 = G.causal_conv(tail, piece, w, t_real=n - 17)
    assert float(jnp.max(jnp.abs(jnp.concatenate([a, b[:n - 17]]) - whole))) < 1e-6
    assert float(jnp.max(jnp.abs(tail2 - tail_whole))) == 0.0  # the padding left no trace
    assert float(jnp.max(jnp.abs(tail2 - m[-3:]))) == 0.0
    # a chunk shorter than the tail keeps what is left of the old tail
    _, t1 = G.causal_conv(tail, m[17:18], w)
    assert float(jnp.max(jnp.abs(t1 - m[15:18]))) == 0.0


def test_the_conv_step_continues_the_chunk_and_leaves_an_idle_lane_alone():
    key = jax.random.split(jax.random.key(3), 2)
    C, K, n = 24, 4, 20
    m = jax.random.normal(key[0], (n + 3, C))
    w = jax.random.normal(key[1], (K, C))
    whole, _ = G.causal_conv(jnp.zeros((K - 1, C)), m, w)
    _, tail = G.causal_conv(jnp.zeros((K - 1, C)), m[:n], w)
    tails = jnp.zeros((2, 3, K - 1, C)).at[1, 0].set(tail).at[1, 2].set(tail)
    active = jnp.asarray([1, 0, 0], jnp.int32)
    for t in range(n, n + 3):
        c, tails = G.conv_step(tails, jnp.stack([m[t]] * 3), w, active, 1)
        assert float(jnp.max(jnp.abs(c[0] - whole[t]))) < 1e-6
    assert float(jnp.max(jnp.abs(tails[1, 2] - tail))) == 0.0  # idle: untouched
    assert float(jnp.max(jnp.abs(tails[1, 1]))) == 0.0 and float(jnp.max(jnp.abs(tails[0]))) == 0.0
    assert float(jnp.max(jnp.abs(tails[1, 0] - m[-3:]))) == 0.0


def test_the_mixers_core_splits_the_channels_and_steps_what_it_prefilled():
    """gdn_prefill then gdn_decode over {S, conv} equals one long prefill."""
    key = jax.random.split(jax.random.key(4), 4)
    C = 2 * HK * DK + HV * DV
    n = 70
    m = jax.random.normal(key[0], (n + 2, C))
    w = jax.random.normal(key[1], (4, C)) * 0.5
    g = -0.02 * jax.random.uniform(key[2], (n + 2, HV))
    beta = jax.nn.sigmoid(jax.random.normal(key[3], (n + 2, HV)))
    zero = {"S": jnp.zeros((HV, DK, DV)), "conv": jnp.zeros((3, C))}
    want, _ = G.gdn_prefill(zero, m, w, g, beta)
    got, st = G.gdn_prefill(zero, jnp.concatenate([m[:n], m[:10]]), w,
                            jnp.concatenate([g[:n], g[:10]]), jnp.concatenate([beta[:n], beta[:10]]),
                            t_real=n)
    assert float(jnp.max(jnp.abs(got[:n] - want[:n]))) < TOL
    store = jax.tree.map(lambda a: a[None, None], st)
    for t in (n, n + 1):
        o, store = G.gdn_decode(store, m[t][None], w, g[t][None], beta[t][None],
                                jnp.ones((1,), jnp.int32), 0)
        assert float(jnp.max(jnp.abs(o[0] - want[t]))) < TOL
    q, k, v = G.split_qkv(m[0], HV, DK, DV)
    assert q.shape == (HK, DK) and k.shape == (HK, DK) and v.shape == (HV, DV)
    assert float(q[1, 0]) == float(m[0, DK]) and float(v[0, 0]) == float(m[0, 2 * HK * DK])


# ---- partial rotary and the zero-centred norm -------------------------------
def test_a_rotary_narrower_than_the_head_rotates_the_first_dims_alone():
    from dnet_tpu.ops.rope import apply_rope, rope_frequencies

    inv, scale = rope_frequencies(4, 100.0)  # 4 of 8 dims rotate
    assert scale == 1.0 and np.allclose(inv, [1.0, 0.1])
    x = jnp.arange(1, 9, dtype=jnp.float32).reshape(1, 1, 1, 8)
    out = np.asarray(apply_rope(x, jnp.asarray([3]), jnp.asarray(inv)))[0, 0, 0]
    c0, s0, c1, s1 = np.cos(3.0), np.sin(3.0), np.cos(0.3), np.sin(0.3)
    # half-split inside the rotary part: pairs (x0, x2) and (x1, x3)
    hand = [1 * c0 - 3 * s0, 2 * c1 - 4 * s1, 3 * c0 + 1 * s0, 4 * c1 + 2 * s1, 5, 6, 7, 8]
    assert np.allclose(out, hand, atol=1e-6)
    # the whole head: as it always was
    inv8, _ = rope_frequencies(8, 100.0)
    full = np.asarray(apply_rope(x, jnp.asarray([3]), jnp.asarray(inv8)))[0, 0, 0]
    assert not np.allclose(full[4:], [5, 6, 7, 8])


def test_the_zero_centred_norm_scales_by_one_plus_the_weight():
    from dnet_tpu.ops.norms import rms_norm, rms_norm0

    x = jnp.asarray([[3.0, -4.0]])
    rms = np.sqrt((9 + 16) / 2)
    w = jnp.asarray([0.0, 0.5])
    assert np.allclose(np.asarray(rms_norm0(x, w, 0.0)), [[3 / rms, -4 / rms * 1.5]], atol=1e-6)
    assert np.allclose(np.asarray(rms_norm0(x, w, 0.0)), np.asarray(rms_norm(x, 1.0 + w, 0.0)))
    assert rms_norm0(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16
