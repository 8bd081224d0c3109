"""A tick enqueues all of its device work before its first blocking read.

What the rule rests on, piece by piece (the order itself, with a fake
engine: tests/subsystems/test_wire_pipeline.py
test_execute_tick_launches_every_chunk_before_the_decode_read):

- `decode_batch` is `decode_launch` then `decode_read`, and a lane that
  leaves between the two keeps its position and gets no token;
- adoption is compiled: `sample_with_counts` is the eager `sample` to the
  token, the key and the counts, for every SamplePlan;
- what leaves the compute thread is host data: `TickResult` holds no
  `jax.Array`, and `token_result` reads a device and a host result alike;
- `dnet_sched_mixed_ticks_total` says how often the rule engages.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import metric
from tests.subsystems.test_tick_anatomy import paged_env  # noqa: F401  (fixture)


@pytest.fixture
def engine(tiny_llama_dir, paged_env):
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(tiny_llama_dir, slots=3, max_seq=64, param_dtype="float32")
    assert eng.kv_pool is not None
    yield eng
    eng.close()


GREEDY = DecodingParams(temperature=0.0)


def _prompt(n, salt):
    return [(7 * i + salt) % 200 + 3 for i in range(n)]


def _chunk(nonce, ids, decoding=GREEDY, victims=()):
    from dnet_tpu.sched.policy import PrefillChunk

    return PrefillChunk(
        nonce=nonce, ids=list(ids), start=0, end=len(ids), first=True, last=True,
        decoding=decoding, pending_step=0, seed=None, victims=list(victims),
    )


def _tok(res) -> int:
    return int(np.asarray(res.token)[0])


# ---- the two halves of decode_batch ----------------------------------------


def test_decode_batch_is_launch_then_read(engine):
    """Launch enqueues and advances nothing; read hands out the rows and
    advances the lanes: together they are decode_batch, token for token."""
    a, b = _prompt(11, 1), _prompt(19, 2)
    ta = _tok(engine.prefill_and_sample("a", a, GREEDY))
    tb = _tok(engine.prefill_and_sample("b", b, GREEDY))
    want, errs = engine.decode_batch({"a": (ta, GREEDY), "b": (tb, GREEDY)})
    assert not errs
    engine.reset()
    assert _tok(engine.prefill_and_sample("a", a, GREEDY)) == ta
    assert _tok(engine.prefill_and_sample("b", b, GREEDY)) == tb
    pos0 = engine.pos.copy()
    flight = engine.decode_launch({"a": (ta, GREEDY), "b": (tb, GREEDY)})
    assert sorted(flight.order) == ["a", "b"] and not flight.blocked
    assert isinstance(flight.src.token, jax.Array)  # still on the device
    assert (engine.pos == pos0).all()  # the read advances, not the launch
    got, errs = engine.decode_read(flight)
    assert not errs
    assert {n: _tok(r) for n, r in got.items()} == {n: _tok(r) for n, r in want.items()}
    sa, sb = engine.slot_of["a"], engine.slot_of["b"]
    assert engine.pos[sa] == pos0[sa] + 1 and engine.pos[sb] == pos0[sb] + 1


def test_a_lane_that_leaves_between_launch_and_read_keeps_its_pos(engine):
    """Preempted (or ended) with its step in flight: no token, `pos` not
    advanced, and the prompt that took the freed lane meanwhile is not
    touched by the read half."""
    a, b, c = _prompt(11, 1), _prompt(19, 2), _prompt(13, 3)
    ta = _tok(engine.prefill_and_sample("a", a, GREEDY))
    tb = _tok(engine.prefill_and_sample("b", b, GREEDY))
    # what an undisturbed "a" decodes, with "b" beside it
    want, _ = engine.decode_batch({"a": (ta, GREEDY), "b": (tb, GREEDY)})
    engine.reset()
    engine.prefill_and_sample("a", a, GREEDY)
    engine.prefill_and_sample("b", b, GREEDY)
    engine.prefill_and_sample("idle", _prompt(9, 5), GREEDY)  # no lane is spare
    slot_b = engine.slot_of["b"]
    delivered0 = metric("dnet_decode_tokens_total").labels(source="dispatch").value
    flight = engine.decode_launch({"a": (ta, GREEDY), "b": (tb, GREEDY)})
    engine.end_session("b")  # the victim leaves; its lane is free again
    first_c = engine.prefill_and_sample("c", c, GREEDY)  # and is taken
    assert engine.slot_of["c"] == slot_b
    assert engine.pos[slot_b] == len(c)
    out, errs = engine.decode_read(flight)
    assert not errs and set(out) == {"a"}  # b's token is dropped
    assert _tok(out["a"]) == _tok(want["a"])
    assert engine.pos[slot_b] == len(c)  # not advanced by b's step
    assert metric("dnet_decode_tokens_total").labels(source="dispatch").value == delivered0 + 1
    # the lane's new owner decodes as if b had never been in flight there
    nxt, _ = engine.decode_batch({"c": (_tok(first_c), GREEDY)})
    engine.reset()
    alone = engine.prefill_and_sample("c", c, GREEDY)
    assert _tok(alone) == _tok(first_c)
    nxt_alone, _ = engine.decode_batch({"c": (_tok(alone), GREEDY)})
    assert _tok(nxt["c"]) == _tok(nxt_alone["c"])


def test_a_victim_evicted_by_an_adoption_is_preempted_exactly_once():
    """Adopt-time starvation evicts a DECODING lane whose step is in
    flight and retries in the tick: the victim keeps its `pos`, gets no
    token, and is in `preempted` once."""
    from dnet_tpu.kv import KVPoolExhausted
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick
    from tests.subsystems.test_sched import FakeStepEngine
    from tests.subsystems.test_sched import _chunk as fake_chunk

    eng = FakeStepEngine()
    eng.occupy("keep", committed=5, blocks=1)
    slot_low = eng.occupy("low", committed=6, blocks=2)
    refused = []

    def adopt(nonce, logits, decoding):
        if not refused:  # the pools' alloc raises before anything is enqueued
            refused.append(nonce)
            raise KVPoolExhausted(2, 0, 8)
        return f"sample-{nonce}"

    eng.adopt_prefilled = adopt
    plan = TickPlan()
    plan.decode = {"keep": (1, DecodingParams()), "low": (2, DecodingParams())}
    plan.steps = {"keep": 4, "low": 3}
    plan.budgets = {"keep": 9, "low": 9}
    plan.ids = {"low": list(range(8))}
    plan.victims = ["low", "keep"]
    first = execute_tick(eng, plan)  # both lanes' step goes into flight
    assert sorted(first.flight.order) == ["keep", "low"] and not first.decode_results
    plan.prefills = [fake_chunk("urgent", victims=["low"])]
    res = execute_tick(eng, plan, follows=first)
    assert res.preempted == ["low"] and eng.ended == ["low"]
    assert res.adopted == {"urgent": "sample-urgent"}  # retried in the tick
    assert set(res.decode_results) == {"keep"}  # the in-flight token is dropped
    assert eng.pos[slot_low] == 6  # and the freed lane's position left alone
    assert list(res.flight.order) == ["keep"]  # nor is the victim chained
    assert not res.errors and not res.requeued


# ---- adoption is compiled ---------------------------------------------------

PLANS = {
    "greedy": DecodingParams(temperature=0.0),
    "sampled": DecodingParams(temperature=0.8),
    "filters": DecodingParams(temperature=0.7, top_p=0.9, top_k=40, min_p=0.02),
    "logprobs": DecodingParams(temperature=0.7, top_p=0.9, logprobs=True, top_logprobs=5),
    "penalty": DecodingParams(temperature=0.9, repetition_penalty=1.3),
    "bias": DecodingParams(temperature=0.0, logit_bias={5: 40.0, 17: -100.0}),
    "all": DecodingParams(
        temperature=0.6, top_p=0.8, top_k=30, logprobs=True, top_logprobs=20,
        repetition_penalty=1.2, logit_bias={3: 2.0},
    ),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_compiled_sample_with_counts_is_the_eager_sample(name):
    """One jitted program a SamplePlan gives the token, the key and the
    counts of the eager split / sample / counts on the same inputs."""
    from dnet_tpu.core.engine import sample_with_counts
    from dnet_tpu.core.sampler import SampleParams, SamplePlan, sample

    d = PLANS[name]
    V = 257
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(0.0, 3.0, (1, V)).astype(np.float32))
    counts = jnp.asarray((rng.random((1, V)) < 0.2).astype(np.int32))
    key = jax.random.key(1234)
    # the eager reference, as adoption ran it before it was compiled
    ref_key, step_key = jax.random.split(key)
    ref = sample(
        logits, SampleParams.from_decoding(d), step_key,
        token_counts=counts, plan=SamplePlan.from_decoding(d),
    )
    ref_counts = counts.at[jnp.arange(1), ref.token].add(1)
    compiles0 = metric("dnet_jit_compiles_total").labels(fn="sample_with_counts").value
    res, new_key, new_counts = sample_with_counts(logits, d, key, counts)
    assert int(res.token[0]) == int(ref.token[0])
    assert (jax.random.key_data(new_key) == jax.random.key_data(ref_key)).all()
    assert (np.asarray(new_counts) == np.asarray(ref_counts)).all()
    np.testing.assert_allclose(res.logprob, ref.logprob, atol=1e-5)
    assert (np.asarray(res.top_tokens) == np.asarray(ref.top_tokens)).all()
    np.testing.assert_allclose(res.top_logprobs, ref.top_logprobs, atol=1e-5)
    # every knob is traced: another temperature is the same program
    if not SamplePlan.from_decoding(d).greedy:
        after = metric("dnet_jit_compiles_total").labels(fn="sample_with_counts").value
        sample_with_counts(
            logits, dataclasses.replace(d, temperature=d.temperature + 0.1), key, counts
        )
        assert metric("dnet_jit_compiles_total").labels(fn="sample_with_counts").value == after
        assert after - compiles0 <= 1


def test_an_adoption_refused_by_the_pool_leaves_the_stream_untouched(engine):
    """The pools' alloc is host work and raises before the sample: a retry
    in the tick samples from the same key, once."""
    from dnet_tpu.kv import KVPoolExhausted

    d = DecodingParams(temperature=0.9, seed=7)
    ids = _prompt(21, 4)
    want = _tok(engine.prefill_and_sample("w", ids, d))
    engine.reset()
    engine.reserve_slot("x")
    logits = engine.prefill_chunk("x", ids, d.seed)
    held = engine.kv_pool.alloc(engine.kv_pool.free)  # nothing left to commit into
    key0 = jax.random.key_data(engine.eng.sessions["x"].key)
    with pytest.raises(KVPoolExhausted):
        engine.adopt_prefilled("x", logits, d)
    sess = engine.eng.sessions["x"]  # still staged, key and counts untouched
    assert (jax.random.key_data(sess.key) == key0).all()
    assert int(np.asarray(sess.counts).sum()) == 0
    engine.kv_pool.free_blocks(held)
    assert _tok(engine.adopt_prefilled("x", logits, d)) == want


# ---- what leaves the compute thread is host data ----------------------------


def test_tick_results_hold_no_device_array(engine):
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick

    d = DecodingParams(temperature=0.7, top_p=0.9, logprobs=True, top_logprobs=3)
    ta = _tok(engine.prefill_and_sample("a", _prompt(11, 1), d))
    plan = TickPlan()
    plan.decode = {"a": (ta, d)}
    plan.steps = {"a": 1}
    plan.budgets = {"a": 5}
    first = execute_tick(engine, plan)  # a's step goes into flight
    plan.prefills = [_chunk("b", _prompt(19, 2), d)]
    res = execute_tick(engine, plan, follows=first)
    assert not res.errors
    assert set(res.decode_results) == {"a"} and set(res.adopted) == {"b"}
    for leaf in jax.tree.leaves((res.decode_results, res.adopted)):
        assert isinstance(leaf, np.ndarray) and not isinstance(leaf, jax.Array)
    # and the loop's conversion reads them as they are
    tr = engine.token_result("b", res.adopted["b"], step=0, decoding=d)
    assert tr.token_id == _tok(res.adopted["b"]) and len(tr.top_logprobs) == 3


@pytest.mark.parametrize("logprobs", [False, True])
def test_token_result_reads_a_device_and_a_host_result_alike(logprobs):
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.sampler import MAX_TOP_LOGPROBS, SampleResult

    rng = np.random.default_rng(3)
    host = SampleResult(
        token=np.asarray([41], np.int32),
        logprob=np.asarray([-1.25], np.float32),
        top_tokens=rng.integers(0, 99, (1, MAX_TOP_LOGPROBS)).astype(np.int32),
        top_logprobs=np.sort(rng.normal(size=(1, MAX_TOP_LOGPROBS)).astype(np.float32))[:, ::-1],
    )
    device = SampleResult(*(jnp.asarray(x) for x in host))
    d = DecodingParams(logprobs=logprobs, top_logprobs=4 if logprobs else 0)
    a = LocalEngine.token_result("n", host, step=2, decoding=d)
    b = LocalEngine.token_result("n", device, step=2, decoding=d)
    assert a == b and a.token_id == 41 and a.step == 2
    assert (a.logprob is not None) == logprobs
    assert (len(a.top_logprobs) == 4) if logprobs else (a.top_logprobs is None)
    # a host result is converted by nothing that could reach the device
    with jax.transfer_guard("disallow"):
        assert LocalEngine.token_result("n", host, step=2, decoding=d) == a


# ---- the counter that says how often the rule engages -----------------------


def _mixed():
    fam = metric("dnet_sched_mixed_ticks_total")
    return {v: fam.labels(overlapped=v).value for v in ("yes", "no")}


def test_mixed_ticks_counter_counts_a_step_with_a_chunk(engine):
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick

    ta = _tok(engine.prefill_and_sample("a", _prompt(11, 1), GREEDY))
    before = _mixed()
    plan = TickPlan()  # a decode-only tick: nothing to overlap, nothing counted
    plan.decode = {"a": (ta, GREEDY)}
    plan.steps = {"a": 1}
    plan.budgets = {"a": 9}
    res = execute_tick(engine, plan)
    assert _mixed() == before
    plan.prefills = [_chunk("b", _prompt(19, 2))]
    plan.decode = plan.steps = plan.budgets = {}  # a prefill-only tick: neither
    res = execute_tick(engine, plan, follows=res)  # (it reads a's step: late)
    assert _mixed() == before and not res.decode_results
    plan = TickPlan()  # a step AND a chunk, the step enqueued behind the chunk
    plan.decode = {"a": (ta, GREEDY)}
    plan.steps = {"a": 1}
    plan.budgets = {"a": 9}
    plan.prefills = [_chunk("c", _prompt(13, 3))]
    res = execute_tick(engine, plan, follows=res)
    assert _mixed() == {"yes": before["yes"] + 1, "no": before["no"]}
    assert set(res.decode_results) == {"a"}  # the held token, at its next ask


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1, 2**31 + 7, 2**32 - 1, 2**32 + 3, 2**40 + 1])
def test_a_new_session_is_one_program_with_the_eager_key(engine, seed):
    """`new_session` launches ONE program (cache row, key, counts), and its
    key is `jax.random.key(seed)` for any whole-number seed."""
    inner = engine.eng
    sess = inner.new_session("fresh", seed)
    try:
        assert (jax.random.key_data(sess.key) == jax.random.key_data(jax.random.key(seed))).all()
        assert int(np.asarray(sess.counts).sum()) == 0 and sess.pos == 0
        assert all(not np.asarray(leaf).any() for leaf in jax.tree.leaves(sess.kv))
        row = inner.model.init_kv(
            len(inner.model.layers), 1, inner.max_seq, inner.kv_dtype,
            quant_bits=inner.kv_quant_bits,
        )
        assert jax.tree.map(lambda a: (a.shape, a.dtype), sess.kv) == jax.tree.map(
            lambda a: (a.shape, a.dtype), row)
        compiles = metric("dnet_jit_compiles_total").labels(fn="new_session").value
        inner.new_session("fresh2", seed + 1)  # the seed is traced: no recompile
        assert metric("dnet_jit_compiles_total").labels(fn="new_session").value == compiles
    finally:
        inner.end_session("fresh")
        inner.end_session("fresh2")
