"""The traffic generator: a pure function of (mix, seed)."""

import json
from collections import Counter

import pytest

from benchmarks.harness import traffic
from benchmarks.harness.spec import BENCH_DIR

MIXES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
BIG = 2**31 + 12345  # the driver's seeds exceed 31 bits


def mix(name):
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_plan_is_a_pure_function_of_the_seed(name):
    a = traffic.plan(mix(name), BIG, 1000)
    b = traffic.plan(mix(name), BIG, 1000)
    assert a == b
    assert a != traffic.plan(mix(name), BIG + 1, 1000)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_lengths_in_another_order(name):
    m = dict(mix(name), residual_life_start=False)

    def sizes(seed, field):
        return Counter(
            len(p.prompt_ids) if field == "p" else p.max_tokens
            for mine in traffic.plan(m, seed, 1000) for p in mine
        )

    assert sizes(1, "p") == sizes(BIG, "p")
    assert sizes(1, "a") == sizes(BIG, "a")
    lo, hi = m["prompt_tokens"]["min"], m["prompt_tokens"]["max"]
    assert all(lo <= n <= hi for n in sizes(1, "p"))


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_residual_life_start_is_a_pure_function_of_the_seed(seed):
    a, b = traffic.residual_shares(32, seed), traffic.residual_shares(32, seed)
    assert a == b
    assert sorted(a) == [(k + 1) / 32 for k in range(32)]  # (0, 1], evenly spread
    assert a != traffic.residual_shares(32, seed + 1)


def test_first_answers_are_cut_and_later_ones_are_not():
    m = mix("ragprompt-sat-16")
    cut = traffic.plan(m, 5, 1000)
    whole = traffic.plan(dict(m, residual_life_start=False), 5, 1000)
    firsts = [(c[0].max_tokens, w[0].max_tokens) for c, w in zip(cut, whole)]
    assert all(1 <= c <= w for c, w in firsts)
    assert sum(c for c, _ in firsts) < 0.7 * sum(w for _, w in firsts)
    assert all(c[1:] == w[1:] for c, w in zip(cut, whole))


def test_prompt_text_round_trips_through_the_word_tokenizer():
    from benchmarks.harness.weights import token_id

    ids = (5, 77, 151935)
    assert [token_id(w) for w in traffic.prompt_text(ids).split()] == list(ids)


@pytest.mark.parametrize("name", MIXES)
def test_every_block_deals_each_client_one_length_from_each_band(name):
    """Whatever the seed, a client's block holds one length of every band of
    the distribution, and the clients' blocks together are the quantile set:
    the order cannot move much work from one client or one block to another."""
    m = mix(name)
    clients, per, block = m["clients"], m["requests_per_client"], m["block"]
    for field in ("prompt_tokens", "answer_tokens"):
        q = traffic.quantiles(m[field], clients * block)
        bands = [q[k * clients:(k + 1) * clients] for k in range(block)]
        for seed in (1, BIG):
            dealt = traffic.deal(m[field], clients, per, block, traffic._rng(seed, field))
            for start in range(0, per - block + 1, block):
                hands = [mine[start:start + block] for mine in dealt]
                assert sorted(n for h in hands for n in h) == q
                for h in hands:
                    assert all(
                        any(band[0] <= n <= band[-1] for n in h) for band in bands
                    )


@pytest.mark.parametrize("name", MIXES)
def test_the_seed_changes_the_order_unless_the_mix_fixes_it(name):
    m = mix(name)

    def lengths(seed):
        return [[(len(p.prompt_ids), p.max_tokens) for p in mine]
                for mine in traffic.plan(m, seed, 1000)]

    def words(seed):
        return [p.prompt_ids for mine in traffic.plan(m, seed, 1000) for p in mine]

    assert words(1) != words(2)  # what the prompts say always follows the seed
    assert (lengths(1) == lengths(2)) is ("schedule_seed" in m)
    shuffled = dict(m)
    shuffled.pop("schedule_seed", None)
    assert traffic.plan(shuffled, 1, 1000) != traffic.plan(shuffled, 2, 1000)


def test_a_client_list_that_is_not_whole_blocks_is_cut():
    dist = {"dist": "uniform", "min": 10, "max": 50}
    dealt = traffic.deal(dist, 4, 5, 2, traffic._rng(3, "x"))
    assert [len(d) for d in dealt] == [5, 5, 5, 5]
