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


# ---- the filters by threshold search: equivalence with the four-sort code ----
#
# `_four_sort_filters` is the filter code `sample()` ran before the filters
# became a prefix of the row's descending order (sort, argsort, argsort of the
# argsort, and a vocabulary-sized gather through the ranks), kept here verbatim
# as the reference.  The search sorts nothing and must keep the same set, ties
# included, and so draw the same token for the same key.


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


def _descending(scaled):
    """Every entry's probability, the count of entries ahead of it and their
    weight (its exclusive cumulative probability) along the row's descending
    order, equal values higher index first: float64 numpy, a real sort."""
    x = np.asarray(scaled, np.float64)
    with np.errstate(invalid="ignore"):  # a row of -inf beside the maximum
        p = np.exp(x - x.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ahead, mass = np.empty(x.shape, np.int64), np.empty_like(p)
    for b in range(x.shape[0]):
        order = np.lexsort((-np.arange(x.shape[1]), -x[b]))
        ahead[b, order] = np.arange(x.shape[1])
        mass[b, order] = np.cumsum(p[b, order]) - p[b, order]
    return p, ahead, mass


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
@pytest.mark.parametrize("min_tokens_to_keep", [1, 5])
@pytest.mark.parametrize("min_p", [0.0, 0.05])
@pytest.mark.parametrize("top_p", [0.1, 0.9, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 50])
@pytest.mark.parametrize("kind", ["bf16", "f32"])
@pytest.mark.parametrize("V", [97, 151936])
def test_threshold_search_filters_equal_the_four_sort_code(
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
    differ = got["new_keep"] != got["ref_keep"]
    if differ.any() and top_p < 1.0:
        # the one place two float32 evaluations of the same rule may part: a
        # masked reduce and a running cumsum round differently, so the prefix
        # may end an entry apart where the exclusive cumulative probability
        # equals top_p to within float32 summation error, and nowhere else
        # (everything decided by a count is bit-equal below)
        off = np.abs(_descending(got["scaled"])[2][differ] - top_p)
        assert (off < 1e-5).all(), off.max()
        for name in ("new_token", "new_token_lanes"):
            assert got["new_keep"][np.arange(B), got[name]].all()
        return
    if differ.any():
        # the one stated difference: top_p = 1.0 is "off", yet the reference's
        # float cumsum reaches 1.0 before the row ends and `cumsum - p < 1.0`
        # drops far-tail entries (with holes where it wobbles); the search
        # keeps the whole row, and what the reference dropped weighs nothing
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


def test_full_plan_sample_holds_no_sort_and_a_bounded_search():
    """Structure, not speed: the filters cost no `sort` (`top_k` is its own
    primitive and stays), gather no more than an element a row, and read the
    row in loops of static trip counts whose sum has a ceiling, however the
    row is batched (whole batch, or vmapped a lane at a time as
    `core/batch.py` does)."""
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
        assert "sort" not in names, names
        assert "top_k" in names
        for e in eqns:
            if e.primitive.name == "gather":
                assert all(v.aval.size <= B for v in e.outvars), e
        # a loop is a `scan` of static length (a `while` would hide its trip
        # count): 32 bits of the value and the index's, two bits a pass
        assert "while" not in names, names
        trips = sum(e.params["length"] for e in eqns if e.primitive.name == "scan")
        assert 0 < trips <= 32 // 2 + 10 // 2, trips


# ---- what the search adds: ties, bans, signs, edges, per-lane knobs ----


def _prefix_oracle(scaled, top_k=0, top_p=1.0, min_p=0.0, min_tokens_to_keep=1):
    """The rule itself: an entry is kept iff fewer than min(k, n_minp)
    entries stand ahead of it and they weigh less than top_p, or fewer than
    min_tokens_to_keep do."""
    p, ahead, mass = _descending(scaled)
    n_minp = (p >= min_p * p.max(-1, keepdims=True)).sum(-1, keepdims=True)
    most = np.minimum(top_k or p.shape[1], n_minp)
    by_mass = (mass < top_p) | (top_p >= 1.0)
    return ((ahead < most) & by_mass) | (ahead < max(min_tokens_to_keep, 1))


def _bf16(x):
    return jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)


def _normal(V, B=3, seed=0):
    return np.random.default_rng([V, seed]).standard_normal((B, V))


def _coarse(V):
    """What `lm_project` hands over, exaggerated: a few bf16 values a row,
    so a cut falls inside a group of equal values."""
    return _bf16(np.round(_normal(V) * 2) / 2)


def _banned(V):
    x = _normal(V, seed=1)
    x[:, np.random.default_rng(V).permutation(V)[: V - 40]] = -np.inf
    return x


def _tied_top(V):
    x = _normal(V, seed=2)
    x[:, [3, V // 2, V - 2]] = 9.0  # a group of three stands first
    return x


SEARCH_CASES = {
    # a cut inside a tie group keeps the group's HIGHER indices
    "all_equal_top_p": (lambda V: np.zeros((2, V)), dict(top_p=0.9)),
    "all_equal_top_k": (lambda V: np.full((2, V), -3.5), dict(top_k=7)),
    "bf16_nucleus_ends_mid_group": (_coarse, dict(top_p=0.5)),
    "bf16_top_k_ends_mid_group": (_coarse, dict(top_k=33)),
    # a banned token: -inf sorts last and weighs nothing
    "banned_top_p": (_banned, dict(top_p=0.9)),
    "banned_top_k_past_the_finite": (_banned, dict(top_k=50)),
    "banned_kept_whole": (_banned, dict()),
    # the key's two branches
    "negative_rows": (lambda V: -np.abs(_normal(V)) - 0.5, dict(top_p=0.8, min_p=0.01)),
    "mixed_signs_and_zeros": (
        lambda V: np.where(np.arange(V) % 5 == 0, 0.0, _normal(V) * 3), dict(top_p=0.7),
    ),
    "top_k_1": (_normal, dict(top_k=1)),
    "top_k_1_of_a_tied_top": (_tied_top, dict(top_k=1)),
    "min_tokens_over_the_group": (_tied_top, dict(top_p=0.01, min_tokens_to_keep=5)),
    "min_tokens_over_the_row": (_normal, dict(top_k=2, min_tokens_to_keep=10**6)),
    "min_p_alone": (lambda V: _bf16(_normal(V) * 2), dict(min_p=0.2)),
    # nothing filters: the cut is the row's last entry
    "kept_whole": (_normal, dict()),
    "kept_whole_bf16": (lambda V: _bf16(_normal(V)), dict(top_k=0, top_p=1.0)),
    # temperature ~ 0: every probability but the first is exactly 0
    "temperature_1e-6": (
        lambda V: _bf16(_normal(V)) / 1e-6, dict(top_p=0.9, min_tokens_to_keep=5),
    ),
    "temperature_1e-6_tied_top": (lambda V: _tied_top(V) / 1e-6, dict(top_p=0.9, top_k=2)),
}


@pytest.mark.parametrize("V", [97, 128, 4096])
@pytest.mark.parametrize("name", sorted(SEARCH_CASES))
def test_threshold_search_case(name, V):
    rows, knobs = SEARCH_CASES[name]
    scaled = jnp.asarray(rows(V), jnp.float32)
    sp = params(temperature=1.0, **knobs)
    want = _prefix_oracle(scaled, **knobs)
    whole = np.asarray(jax.jit(filter_keep)(scaled, sp))
    lanes = np.asarray(
        jax.jit(jax.vmap(lambda row: filter_keep(row[None], sp)[0]))(scaled)
    )
    np.testing.assert_array_equal(whole, want)
    np.testing.assert_array_equal(lanes, want)
    if knobs.get("top_p", 1.0) < 1.0:  # at 1.0 the reference wobbles (above)
        ref, _ = jax.jit(_four_sort_filters)(scaled, sp)
        np.testing.assert_array_equal(whole, np.asarray(ref))
    assert whole.any(-1).all()  # never an empty support
    if name.startswith("all_equal"):
        n = whole[0].sum()
        assert 0 < n < V and whole[0, V - n:].all()  # the higher indices
    if name.endswith("mid_group"):
        # the last kept value's group keeps its higher indices, and in
        # some row it is cut
        x, cut_rows = np.asarray(scaled), 0
        for b in range(x.shape[0]):
            inside = whole[b][x[b] == x[b][whole[b]].min()]
            assert inside[-inside.sum():].all(), (name, V, b)
            cut_rows += not inside.all()
        assert cut_rows, (name, V)
    if name.startswith("kept_whole") or name == "min_tokens_over_the_row":
        assert whole.all()


@pytest.mark.parametrize("V", [97, 4096])
def test_threshold_search_takes_per_lane_knobs(V):
    """`core/batch.py` vmaps a lane's row AND its knobs: each lane's mask is
    the one it gets alone."""
    lanes = [
        dict(top_k=1), dict(top_p=0.3), dict(min_p=0.1), dict(),
        dict(top_k=40, top_p=0.9, min_p=0.001), dict(top_p=0.05, min_tokens_to_keep=7),
    ]
    scaled = _bf16(_normal(V, B=len(lanes), seed=3) * 2)
    sps = [params(temperature=1.0, **knobs) for knobs in lanes]
    stacked = jax.tree.map(lambda *a: np.stack(a), *sps)
    got = np.asarray(
        jax.jit(jax.vmap(lambda row, sp: filter_keep(row[None], sp)[0]))(scaled, stacked)
    )
    for b, (knobs, sp) in enumerate(zip(lanes, sps)):
        np.testing.assert_array_equal(got[b], _prefix_oracle(scaled[b : b + 1], **knobs)[0])
        np.testing.assert_array_equal(got[b], np.asarray(filter_keep(scaled[b : b + 1], sp))[0])
    assert len({tuple(row) for row in got}) == len(lanes)  # the knobs bit
