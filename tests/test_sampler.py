import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.core.sampler import (
    FULL_PLAN,
    SampleParams,
    apply_repetition_penalty,
    filter_keep,
    sample,
)
from dnet_tpu.core.types import DecodingParams

pytestmark = pytest.mark.core


def params(**kw):
    d = DecodingParams(**kw)
    return SampleParams.from_decoding(d)


def test_greedy():
    logits = jnp.asarray([[0.1, 3.0, -1.0, 2.0]])
    res = sample(logits, params(temperature=0.0), jax.random.key(0))
    assert int(res.token[0]) == 1
    # logprob is log_softmax at the token
    ref = jax.nn.log_softmax(logits)[0, 1]
    assert abs(float(res.logprob[0]) - float(ref)) < 1e-5


def test_top_k_restricts_support():
    logits = jnp.asarray([[5.0, 4.0, 3.0, 2.0, 1.0]])
    seen = set()
    for i in range(50):
        res = sample(logits, params(temperature=2.0, top_k=2), jax.random.key(i))
        seen.add(int(res.token[0]))
    assert seen <= {0, 1}
    assert len(seen) == 2  # with temp 2 both should appear


def test_top_p_restricts_support():
    # probs ~ [0.97, 0.01, ...] -> top_p=0.5 keeps only token 0
    logits = jnp.asarray([[10.0, 5.0, 4.0, 3.0, 2.0]])
    for i in range(20):
        res = sample(logits, params(temperature=1.0, top_p=0.5), jax.random.key(i))
        assert int(res.token[0]) == 0


def test_min_p_restricts_support():
    logits = jnp.asarray([[5.0, 5.0, 0.0, -5.0]])
    for i in range(30):
        res = sample(logits, params(temperature=1.0, min_p=0.5), jax.random.key(i))
        assert int(res.token[0]) in {0, 1}


def test_never_empty_support():
    # aggressive filters still sample rank-0
    logits = jnp.asarray([[1.0, 0.9, 0.8]])
    res = sample(logits, params(temperature=1.0, top_p=1e-9, top_k=1, min_p=1.0), jax.random.key(0))
    assert int(res.token[0]) == 0


def test_top_logprobs_sorted():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
    res = sample(logits, params(temperature=0.0, logprobs=True, top_logprobs=4), jax.random.key(0))
    ids = np.asarray(res.top_tokens[0])
    lps = np.asarray(res.top_logprobs[0])[:4]  # width is padded to 8 with -inf
    assert ids[0] == 3
    assert np.all(np.diff(lps) <= 1e-7)


def test_sampling_distribution_roughly_matches():
    logits = jnp.asarray([[np.log(0.7), np.log(0.2), np.log(0.1)]])
    counts = np.zeros(3)
    n = 400
    for i in range(n):
        res = sample(logits, params(temperature=1.0), jax.random.key(i))
        counts[int(res.token[0])] += 1
    freq = counts / n
    np.testing.assert_allclose(freq, [0.7, 0.2, 0.1], atol=0.08)


def test_repetition_penalty():
    logits = jnp.asarray([[2.0, -2.0, 1.0]])
    counts = jnp.asarray([[1, 1, 0]], dtype=jnp.int32)
    out = apply_repetition_penalty(logits, counts, jnp.float32(2.0))
    np.testing.assert_allclose(np.asarray(out[0]), [1.0, -4.0, 1.0])


def test_min_tokens_to_keep_overrides_filters():
    """Aggressive top-p/min-p must still leave min_tokens_to_keep candidates
    reachable (reference DecodingConfig.min_tokens_to_keep)."""
    import collections

    import jax
    import jax.numpy as jnp

    from dnet_tpu.core.sampler import SampleParams, sample
    from dnet_tpu.core.types import DecodingParams

    # one dominant logit: top_p=0.01 would keep ONLY it; mtk=3 must keep 3
    logits = jnp.asarray([[10.0, 9.9, 9.8, -50.0, -50.0]])
    seen = set()
    for i in range(40):
        sp = SampleParams.from_decoding(
            DecodingParams(temperature=1.0, top_p=0.01, min_tokens_to_keep=3)
        )
        res = sample(logits, sp, jax.random.key(i))
        seen.add(int(res.token[0]))
    assert seen == {0, 1, 2}, seen  # all three survivors sampled, no others

    # default mtk=1 keeps only the argmax under the same top_p
    seen1 = set()
    for i in range(20):
        sp = SampleParams.from_decoding(DecodingParams(temperature=1.0, top_p=0.01))
        res = sample(logits, sp, jax.random.key(i))
        seen1.add(int(res.token[0]))
    assert seen1 == {0}


# ---- logit_bias (OpenAI semantics; the reference never applies it) ----


def test_logit_bias_forces_token(rng):
    """+100 on a low-logit token dominates greedy argmax."""
    import jax

    from dnet_tpu.core.sampler import SamplePlan, SampleParams, sample

    logits = jnp.asarray(rng.normal(size=(1, 32)), jnp.float32)
    loser = int(jnp.argmin(logits[0]))
    d = DecodingParams(temperature=0.0, logit_bias={loser: 100.0})
    res = sample(
        logits, SampleParams.from_decoding(d), jax.random.key(0),
        plan=SamplePlan.from_decoding(d),
    )
    assert int(res.token[0]) == loser


def test_logit_bias_suppresses_token(rng):
    """-100 on the argmax bans it even under stochastic sampling."""
    import jax

    from dnet_tpu.core.sampler import SamplePlan, SampleParams, sample

    logits = jnp.asarray(rng.normal(size=(1, 32)), jnp.float32)
    winner = int(jnp.argmax(logits[0]))
    d = DecodingParams(temperature=1.0, logit_bias={winner: -100.0})
    sp = SampleParams.from_decoding(d)
    plan = SamplePlan.from_decoding(d)
    for seed in range(8):
        res = sample(logits, sp, jax.random.key(seed), plan=plan)
        assert int(res.token[0]) != winner


def test_logit_bias_absent_is_exact_noop(rng):
    """FULL_PLAN carries the bias machinery; empty bias must not perturb
    a single logit (padded ids scatter zeros)."""
    import jax

    from dnet_tpu.core.sampler import SampleParams, sample

    logits = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    d0 = DecodingParams(temperature=0.7, top_p=0.9, seed=3)
    key = jax.random.key(3)
    a = sample(logits, SampleParams.from_decoding(d0), key)
    b = sample(
        logits,
        SampleParams.from_decoding(
            DecodingParams(temperature=0.7, top_p=0.9, seed=3, logit_bias={})
        ),
        key,
    )
    assert (a.token == b.token).all()
    np.testing.assert_array_equal(np.asarray(a.logprob), np.asarray(b.logprob))


def test_logit_bias_cap():
    from dnet_tpu.core.sampler import MAX_LOGIT_BIAS, encode_logit_bias

    with np.testing.assert_raises(ValueError):
        encode_logit_bias({i: 1.0 for i in range(MAX_LOGIT_BIAS + 1)})


# ---- the filters from one sort: equivalence with the four-sort code ----
#
# `_four_sort_filters` is the filter code `sample()` ran before the filters
# became a prefix of one sorted row (sort, argsort, argsort of the argsort,
# and a vocabulary-sized gather through the ranks), kept here verbatim as the
# reference.  The one-sort code must keep the same set, ties included, and so
# draw the same token for the same key.


def _four_sort_filters(scaled, params):
    V = scaled.shape[-1]
    # One descending sort powers top-k, top-p and min-p.
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V] desc
    ranks = jnp.argsort(jnp.argsort(scaled, axis=-1)[:, ::-1], axis=-1)

    # top-k: keep ranks < k (k==0 -> keep all)
    k = jnp.where(params.top_k > 0, params.top_k, V)
    keep_topk = ranks < k

    # top-p over the sorted distribution: keep the smallest prefix
    # with cumsum >= top_p (always keep rank 0).
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(sorted_probs, axis=-1)
    prefix_keep_sorted = (cumprobs - sorted_probs) < params.top_p
    keep_topp = jnp.take_along_axis(prefix_keep_sorted, ranks, axis=-1)

    # min-p: probability >= min_p * max prob
    probs = jax.nn.softmax(scaled, axis=-1)
    pmax = jnp.max(probs, axis=-1, keepdims=True)
    keep_minp = probs >= params.min_p * pmax

    keep = keep_topk & keep_topp & keep_minp
    # never mask below min_tokens_to_keep candidates (>= 1: the
    # argmax always survives)
    keep_n = ranks < jnp.maximum(params.min_tokens_to_keep, 1)
    keep = keep | keep_n
    # (the reference's mask with top-p off, for the one stated difference)
    keep_no_topp = keep_topk & keep_minp | keep_n
    return keep, keep_no_topp


def _scale(logits, params):
    return logits.astype(jnp.float32) / jnp.maximum(params.temperature, 1e-6)


def _token_from(keep, scaled, logits, params, key):
    """`sample()`'s stochastic / greedy select over a given mask."""
    masked = jnp.where(keep, scaled, -jnp.inf)
    gumbel = jax.random.gumbel(key, masked.shape, dtype=jnp.float32)
    stochastic = jnp.argmax(masked + gumbel, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(params.temperature <= 0.0, greedy, stochastic).astype(jnp.int32)


@jax.jit
def _both(logits, sp, key):
    """One dispatch per case: masks and tokens of both codes, called with
    the whole batch and vmapped with B = 1 a lane (`core/batch.py`)."""
    # the token does not depend on the logprob outputs: leave their top_k out
    plan = FULL_PLAN._replace(logprobs=False)
    scaled = _scale(logits, sp)
    ref_keep, ref_keep_no_topp = _four_sort_filters(scaled, sp)
    keys = jax.random.split(key, logits.shape[0])
    return {
        "scaled": scaled,
        "ref_keep": ref_keep,
        "ref_keep_no_topp": ref_keep_no_topp,
        "new_keep": filter_keep(scaled, sp),
        "new_keep_lanes": jax.vmap(lambda row: filter_keep(row[None], sp)[0])(scaled),
        "ref_token": _token_from(ref_keep, scaled, logits, sp, key),
        "new_token": sample(logits, sp, key, plan=plan).token,
        "ref_token_lanes": jax.vmap(
            lambda keep, row, raw, k: _token_from(
                keep[None], row[None], raw[None], sp, k
            )[0]
        )(ref_keep, scaled, logits, keys),
        "new_token_lanes": jax.vmap(
            lambda raw, k: sample(raw[None], sp, k, plan=plan).token[0]
        )(logits, keys),
    }


@functools.lru_cache(maxsize=None)
def _rows(V, kind, B):
    """Seeded logits [B, V]: rows from flat to peaked; `bf16` rows are what
    `lm_project` hands the sampler (about 2**8 distinct values an octave, so
    most of a 151936-entry row are equal values)."""
    rng = np.random.default_rng([V, B, kind == "bf16"])
    x = rng.standard_normal((B, V)) * np.asarray([1.0, 2.0, 4.0, 0.5])[:B, None]
    x = jnp.asarray(x, jnp.float32)
    return x.astype(jnp.bfloat16) if kind == "bf16" else x


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
@pytest.mark.parametrize("min_tokens_to_keep", [1, 5])
@pytest.mark.parametrize("min_p", [0.0, 0.05])
@pytest.mark.parametrize("top_p", [0.1, 0.9, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 50])
@pytest.mark.parametrize("kind", ["bf16", "f32"])
@pytest.mark.parametrize("V", [97, 151936])
def test_one_sort_filters_equal_the_four_sort_code(
    V, kind, top_k, top_p, min_p, min_tokens_to_keep, temperature, B
):
    logits = _rows(V, kind, B)
    sp = params(
        temperature=temperature, top_k=top_k, top_p=top_p, min_p=min_p,
        min_tokens_to_keep=min_tokens_to_keep,
    )
    case = [V, B, top_k, int(top_p * 10), int(min_p * 100), min_tokens_to_keep,
            int(temperature * 10)]
    key = jax.random.key(int(np.random.default_rng(case).integers(2**31)))
    got = {name: np.asarray(a) for name, a in _both(logits, sp, key).items()}

    np.testing.assert_array_equal(got["new_keep_lanes"], got["new_keep"])
    if top_p < 1.0 or (got["new_keep"] == got["ref_keep"]).all():
        np.testing.assert_array_equal(got["new_keep"], got["ref_keep"])
    else:
        # the one stated difference: top_p = 1.0 is "off", yet the reference's
        # float cumsum reaches 1.0 before the row ends and `cumsum - p < 1.0`
        # drops far-tail entries (with holes where it wobbles); the one-sort
        # code keeps the whole row, and what the reference dropped weighs nothing
        np.testing.assert_array_equal(got["new_keep"], got["ref_keep_no_topp"])
        x = got["scaled"].astype(np.float64)
        p = np.exp(x - x.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        dropped = (p * (got["new_keep"] & ~got["ref_keep"])).sum(-1)
        assert (dropped < 1e-6).all(), dropped
    np.testing.assert_array_equal(got["new_token"], got["ref_token"])
    np.testing.assert_array_equal(got["new_token_lanes"], got["ref_token_lanes"])


def _primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_full_plan_sample_holds_one_sort_and_no_vocabulary_sized_gather():
    """Structure, not speed: the filters cost one `sort` (`top_k` is its own
    primitive and stays) and gather one element a row, however the row is
    batched (whole batch, or vmapped a lane at a time as `core/batch.py`
    does)."""
    B, V = 4, 1024
    sp = params(temperature=0.7, top_p=0.9)
    logits = jnp.zeros((B, V), jnp.bfloat16)
    counts = jnp.zeros((B, V), jnp.int32)
    keys = jax.random.split(jax.random.key(0), B)

    def whole(logits, counts, key):
        return sample(logits, sp, key, token_counts=counts)

    def lanes(logits, counts, keys):
        return jax.vmap(
            lambda row, c, k: sample(row[None], sp, k, token_counts=c[None])
        )(logits, counts, keys)

    for fn, args in ((whole, (logits, counts, keys[0])), (lanes, (logits, counts, keys))):
        eqns = list(_primitives(jax.make_jaxpr(fn)(*args).jaxpr))
        names = [e.primitive.name for e in eqns]
        assert names.count("sort") == 1, names
        assert "top_k" in names
        for e in eqns:
            if e.primitive.name == "gather":
                assert all(v.aval.size <= B for v in e.outvars), e
