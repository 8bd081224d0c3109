"""The third KIND of layer, `state` (kv/store.py StateStore, core/batch.py):
one recurrent entry a lane, no blocks.  A lane freed and reused starts from
zero, admission waits for a lane and never for blocks, prefix sharing is
refused with its reason, and `kv_layout` / `serving_plan` send a state-kind
model to the scheduler's lanes from what the model says, no setting."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from tests.fakes.checkpoints import make_tiny_brumby
from dnet_tpu.obs import metric
from dnet_tpu.obs.phases import KV_KIND_STATE, KV_KINDS

CONFIG = spec.BENCH_DIR / "configs" / "brumby-14b-8l.json"


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("state_store")
    return make_tiny_brumby(d), d


@pytest.fixture()
def engine(checkpoint):
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(checkpoint[1], slots=2, max_seq=128, param_dtype="float32")
    yield eng
    eng.close()


def decoding():
    from dnet_tpu.core.types import DecodingParams

    return DecodingParams(temperature=0.0)


def ids(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(i) for i in rng.integers(1, cfg["vocab_size"], size=n)]


def run(eng, nonce, prompt, steps=3):
    res = eng.prefill_and_sample(nonce, prompt, decoding())
    toks = [int(res.token[0])]
    for _ in range(steps):
        out, errs = eng.decode_batch({nonce: (toks[-1], decoding())})
        assert not errs
        toks.append(int(out[nonce].token[0]))
    return toks


def test_the_state_kind_has_no_series_among_the_block_families():
    assert KV_KIND_STATE not in KV_KINDS and KV_KINDS == ("full", "window")


def test_the_store_is_one_entry_a_lane_and_no_pool(engine, checkpoint):
    from dnet_tpu.kv import StateStore

    cfg = checkpoint[0]
    L, KVH, Hd = cfg["num_hidden_layers"], cfg["num_key_value_heads"], cfg["head_dim"]
    assert isinstance(engine.kv_store, StateStore) and engine.kv_store.kinds == (KV_KIND_STATE,)
    assert engine.kv_pool is None and engine.kv_pools == {} and engine.kv is None
    R = Hd // 2 + 1
    assert engine.kv_store.kv["S"].shape == (L, 2, KVH, R, Hd, Hd)
    assert engine.kv_store.kv["z"].shape == (L, 2, KVH, R, Hd)
    assert engine.kv_store.kv["S"].dtype == jnp.float32
    assert engine.kv_store.entry_bytes == L * KVH * R * Hd * (Hd + 1) * 4
    assert metric("dnet_state_slots").value == 2
    # the block families stay at their two kinds, untouched by this engine
    for kind in KV_KINDS:
        metric("dnet_kv_pool_blocks").labels(kind=kind)
    text = __import__("dnet_tpu.obs", fromlist=["get_registry"]).get_registry().expose()
    assert 'kind="state"' not in text


def test_a_lane_freed_and_reused_starts_from_zero(engine, checkpoint):
    cfg = checkpoint[0]
    a, b = ids(cfg, 40, 1), ids(cfg, 23, 2)
    fresh = run(engine, "b", b)
    engine.end_session("b")
    assert metric("dnet_state_slots_used").value == 0
    first = run(engine, "a", a)
    lane = engine.slot_of["a"]
    assert float(jnp.max(jnp.abs(engine.kv_store.kv["S"][:, lane]))) > 0
    engine.end_session("a")
    engine._free.sort(key=lambda s: s != lane)  # the next request takes a's lane
    again = run(engine, "b2", b)
    assert engine.slot_of["b2"] == lane
    assert again == fresh  # nothing of `a` is left in it
    assert first != fresh


def test_two_lanes_do_not_see_each_other_and_the_books_follow(engine, checkpoint):
    cfg = checkpoint[0]
    a, b = ids(cfg, 40, 1), ids(cfg, 23, 2)
    alone = run(engine, "a", a, steps=4)
    engine.end_session("a")
    bytes0 = metric("dnet_retention_state_bytes_total").value
    tok0 = metric("dnet_retention_tokens_total").labels(phase="decode").value
    ra = engine.prefill_and_sample("a", a, decoding())
    rb = engine.prefill_and_sample("b", b, decoding())
    assert metric("dnet_state_slots_used").value == 2
    ta, tb = [int(ra.token[0])], [int(rb.token[0])]
    for step in range(4):
        reqs = {"a": (ta[-1], decoding())}
        if step % 2 == 0:  # b idles every other step: its state must wait for it
            reqs["b"] = (tb[-1], decoding())
        out, errs = engine.decode_batch(reqs)
        assert not errs
        ta.append(int(out["a"].token[0]))
        if "b" in out:
            tb.append(int(out["b"].token[0]))
    assert ta == alone
    lane_steps = 4 + 2
    assert metric("dnet_retention_state_bytes_total").value - bytes0 == (
        lane_steps * engine.kv_store.entry_bytes * 2
    )
    assert metric("dnet_retention_tokens_total").labels(phase="decode").value - tok0 == lane_steps
    engine.end_session("a")
    assert tb == run(engine, "b_alone", b, steps=2)


def test_budgeted_steps_carry_the_store_in_place(engine, checkpoint):
    cfg = checkpoint[0]
    a = ids(cfg, 40, 1)
    want = run(engine, "a", a, steps=8)
    engine.end_session("a")
    res = engine.prefill_and_sample("a", a, decoding())
    toks = [int(res.token[0])]
    sent = metric("dnet_decode_dispatch_total")
    sent0 = sent.value
    for k in range(8):  # a budget rides along and never widens a dispatch
        out, errs = engine.decode_batch({"a": (toks[-1], decoding())}, budgets={"a": 8 - k})
        assert not errs and sent.value - sent0 == k + 1
        toks.append(int(out["a"].token[0]))
    assert toks == want


def test_admission_waits_for_a_lane_and_never_for_blocks(engine, checkpoint):
    from dnet_tpu.sched.policy import SchedulerPolicy
    from dnet_tpu.sched.queue import SchedQueue

    cfg = checkpoint[0]
    policy, queue = SchedulerPolicy(token_budget=256, prefill_chunk=128), SchedQueue()
    for i in range(3):  # three requests, two lanes; the longest prompt first
        r = queue.add(f"r{i}", ids(cfg, 100 - 30 * i, i), decoding())
        r.pending_step = 0
    assert all(policy.admissible(r, engine) for r in queue.waiting())  # no pool to ask
    plan = policy.plan(queue, engine)
    assert plan.admitted == ["r0", "r1"]  # a lane each, and no third lane
    assert [r.nonce for r in queue.waiting()] == ["r2"]
    # whatever their length: a sequence costs one entry
    assert engine.kv_store.kv["S"].shape[1] == engine.slots == 2
    # block starvation cannot occur: nothing for the tick to preempt over
    from dnet_tpu.sched.step import execute_tick

    before = metric("dnet_sched_preemptions_total").labels(reason="block_starvation").value
    res = execute_tick(engine, plan)
    assert not res.errors and not res.preempted and not res.requeued
    assert metric("dnet_sched_preemptions_total").labels(reason="block_starvation").value == before
    with pytest.raises(RuntimeError, match="no free batch slots"):
        engine.alloc_slot("r2")


def test_prefix_sharing_refuses_with_its_reason(checkpoint, monkeypatch):
    from dnet_tpu.core import batch
    from dnet_tpu.core.batch import BatchedEngine

    warned = []
    monkeypatch.setattr(batch.log, "warning", lambda msg, *a: warned.append(msg % a))
    eng = BatchedEngine(checkpoint[1], slots=2, max_seq=128, param_dtype="float32",
                        prefix_cache_size=4)
    try:
        assert eng.paged_prefix is None and eng.eng.prefix_cache is None
        assert "cannot be cut at a prefix" in eng.prefix_refusal
        assert "DNET_API_PREFIX_CACHE=4" in eng.prefix_refusal
        assert any("prefix sharing is OFF" in w for w in warned)  # loudly: the load's log
        prompt = ids(checkpoint[0], 40, 1)
        assert eng.seed_from_prefix("a", prompt) == 0
        first = run(eng, "a", prompt)
        eng.store_prefix("a", prompt)  # nothing to store into
        eng.end_session("a")
        assert run(eng, "a2", prompt) == first
    finally:
        eng.close()
    plain = BatchedEngine(checkpoint[1], slots=1, max_seq=64, param_dtype="float32")
    assert plain.prefix_refusal is None  # not asked for: nothing refused
    plain.close()


def test_kv_layout_and_the_serving_plan_decide_from_the_model(checkpoint):
    from dnet_tpu.api.model_manager import serving_plan
    from dnet_tpu.core.batch import KV_STATE, kv_layout
    from dnet_tpu.models import ModelConfig, get_ring_model_cls
    from dnet_tpu.ops.paged_attention import ragged_refusal

    cfg = ModelConfig.from_hf(checkpoint[0])
    model = get_ring_model_cls("brumby")(cfg, range(cfg.num_hidden_layers))
    assert set(model.paged_kinds) == {KV_KIND_STATE} and not model.kv_rewindable(128)
    # whatever was asked of the cache: there are no keys to quantize or rewind
    for bits, spec_lookahead in ((0, 0), (8, 0), (0, 4)):
        layout, why = kv_layout(model, bits, spec_lookahead, 4608)
        assert layout == KV_STATE and "state entry a lane" in why
    assert "state" in ragged_refusal(model, 0)  # the pool is not asked to serve it
    plan = serving_plan(
        model, mesh=None, batch_slots=16, streams_weights=False, kv_quant_bits=0,
        spec_lookahead=0, draft_dir=None, max_seq=4608,
    )
    assert (plan.engine, plan.adapter, plan.kv) == ("BatchedEngine", "SchedulerAdapter", KV_STATE)


def test_max_seq_bounds_positions_not_memory(checkpoint):
    from dnet_tpu.core.batch import BatchedEngine

    small = BatchedEngine(checkpoint[1], slots=1, max_seq=64, param_dtype="float32")
    large = BatchedEngine(checkpoint[1], slots=1, max_seq=512, param_dtype="float32")
    try:
        assert small.kv_store.kv["S"].shape == large.kv_store.kv["S"].shape
        res = small.prefill_and_sample("a", ids(checkpoint[0], 63), decoding())
        out, errs = small.decode_batch({"a": (int(res.token[0]), decoding())})
        assert not errs
        out, errs = small.decode_batch({"a": (int(out["a"].token[0]), decoding())})
        assert "max_seq" in errs["a"]  # RoPE positions end; no pool was exhausted
    finally:
        small.close()
        large.close()


def test_the_config_file_is_the_catalog_row_cut_in_depth_alone():
    full = json.loads(CONFIG.read_text())
    assert full["num_hidden_layers"] == 8 and full["assumed"]["published"] == {"num_hidden_layers": 40}
    assert full["serve"]["expect_health"]["used"] == ["retention_step", "retention_chunk"]
    dead = {"DNET_SCHED", "DNET_KV_PAGED", "DNET_KV_RAGGED"}
    assert not dead & set(full["serve"]["env"]) and not dead & set(full["rehearse"]["env"])
