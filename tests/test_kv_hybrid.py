"""ONE store for a model that mixes `state` layers with `full` layers
(kv/store.py HybridStore, core/batch.py, sched/): a sequence holds a lane
of recurrent state AND a table of blocks at once.  A freed lane starts from
zero state and returns its blocks, admission waits for whichever runs out
first, a preempted lane gives EVERYTHING back and prefills again from token
0 (it is never aliased into a prefix cache), a pool too small for its lanes
queues or recomputes and never corrupts or deadlocks, prefix sharing is
refused with its reason, and `kv_layout` / `serving_plan` decide all of it
from `model.paged_kinds`, no setting."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from dnet_tpu.obs import metric
from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_STATE
from tests.fakes.checkpoints import make_tiny_qwen3_next

CONFIG = spec.BENCH_DIR / "configs" / "qwen3-next-80b-a3b-4l-ep2.json"
BT = 8  # tokens a block in these tests


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("kv_hybrid")
    return make_tiny_qwen3_next(d), d


def build(checkpoint, monkeypatch, slots=2, pool_blocks=0, **kw):
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(BT))
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", str(pool_blocks))
    reset_settings_cache()
    return BatchedEngine(checkpoint[1], slots=slots, max_seq=128, param_dtype="float32", **kw)


@pytest.fixture()
def engine(checkpoint, monkeypatch):
    from dnet_tpu.config import reset_settings_cache

    eng = build(checkpoint, monkeypatch)
    yield eng
    eng.close()
    monkeypatch.undo()
    reset_settings_cache()


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


def test_the_store_is_a_lane_of_state_and_a_pool_of_blocks(engine, checkpoint):
    from dnet_tpu.kv import HybridStore

    cfg = checkpoint[0]
    st = engine.kv_store
    assert isinstance(st, HybridStore) and st.in_place
    assert st.kinds == (KV_KIND_FULL, KV_KIND_STATE) and engine.kv is None
    assert st.layers == {KV_KIND_STATE: (0, 1, 2), KV_KIND_FULL: (3,)}
    HV, Dk, Dv = (cfg[k] for k in ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    C = 2 * cfg["linear_num_key_heads"] * Dk + HV * Dv
    assert st.kv[KV_KIND_STATE]["S"].shape == (3, 2, HV, Dk, Dv)
    assert st.kv[KV_KIND_STATE]["S"].dtype == jnp.float32
    assert st.kv[KV_KIND_STATE]["conv"].shape == (3, 2, 3, C)
    width = cfg["num_key_value_heads"] * cfg["head_dim"]
    assert st.kv[KV_KIND_FULL]["k"].shape == (1, 2 * 128 // BT, BT, width)
    assert st.entry_bytes == 3 * (HV * Dk * Dv * 4 + 3 * C * 4)  # float32 activations here
    # BOTH books, of one store
    assert engine.kv_pool is engine.kv_pools[KV_KIND_FULL] and set(engine.kv_pools) == {KV_KIND_FULL}
    assert metric("dnet_state_slots").value == 2
    assert metric("dnet_kv_pool_blocks").labels(kind="full").value == 32


def test_a_freed_lane_starts_from_zero_state_and_returns_its_blocks(engine, checkpoint):
    cfg = checkpoint[0]
    a, b = ids(cfg, 40, 1), ids(cfg, 23, 2)
    pool = engine.kv_pool
    fresh = run(engine, "b", b)
    engine.end_session("b")
    assert metric("dnet_state_slots_used").value == 0 and pool.free == pool.total
    first = run(engine, "a", a)
    lane = engine.slot_of["a"]
    assert pool.total - pool.free == 6  # 43 tokens + the next in blocks of 8
    assert metric("dnet_kv_blocks_used").labels(kind="full").value == 6
    assert metric("dnet_state_slots_used").value == 1
    assert float(jnp.max(jnp.abs(engine.kv_store.kv[KV_KIND_STATE]["S"][:, lane]))) > 0
    engine.end_session("a")
    assert pool.free == pool.total and engine._tables[lane] is None
    engine._free.sort(key=lambda s: s != lane)  # the next request takes a's lane
    again = run(engine, "b2", b)
    assert engine.slot_of["b2"] == lane
    assert again == fresh  # nothing of `a` is left in it: state, tail or blocks
    assert first != fresh


def test_two_lanes_do_not_see_each_other_and_the_books_follow(engine, checkpoint):
    cfg = checkpoint[0]
    a, b = ids(cfg, 40, 1), ids(cfg, 23, 2)
    alone = run(engine, "a", a, steps=4)
    engine.end_session("a")
    bytes0 = metric("dnet_gdn_state_bytes_total").value
    tok0 = metric("dnet_gdn_tokens_total").labels(phase="decode").value
    ra = engine.prefill_and_sample("a", a, decoding())
    rb = engine.prefill_and_sample("b", b, decoding())
    assert metric("dnet_state_slots_used").value == 2
    ta, tb = [int(ra.token[0])], [int(rb.token[0])]
    for step in range(4):
        reqs = {"a": (ta[-1], decoding())}
        if step % 2 == 0:  # b idles every other step: its state and its blocks wait for it
            reqs["b"] = (tb[-1], decoding())
        out, errs = engine.decode_batch(reqs)
        assert not errs
        ta.append(int(out["a"].token[0]))
        if "b" in out:
            tb.append(int(out["b"].token[0]))
    assert ta == alone
    lane_steps = 4 + 2
    assert metric("dnet_gdn_state_bytes_total").value - bytes0 == (
        lane_steps * engine.kv_store.entry_bytes * 2
    )
    assert metric("dnet_gdn_tokens_total").labels(phase="decode").value - tok0 == lane_steps
    engine.end_session("a")
    assert tb == run(engine, "b_alone", b, steps=2)


def test_budgeted_steps_carry_the_store_in_place(engine, checkpoint):
    cfg = checkpoint[0]
    a = ids(cfg, 38, 1)  # the steps cross a block's edge (40)
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


def test_admission_waits_for_whichever_runs_out_first(checkpoint, monkeypatch):
    from dnet_tpu.sched.policy import SchedulerPolicy
    from dnet_tpu.sched.queue import SchedQueue

    cfg = checkpoint[0]
    policy = SchedulerPolicy(token_budget=256, prefill_chunk=128)
    # lanes run out first: three requests, two lanes, blocks to spare
    eng = build(checkpoint, monkeypatch, slots=2)
    queue = SchedQueue()
    for i in range(3):
        queue.add(f"r{i}", ids(cfg, 40 - 8 * i, i), decoding()).pending_step = 0
    assert all(policy.admissible(r, eng) for r in queue.waiting())
    plan = policy.plan(queue, eng)
    assert plan.admitted == ["r0", "r1"] and [r.nonce for r in queue.waiting()] == ["r2"]
    eng.close()
    # blocks run out first: three lanes, a pool of 12 blocks, prompts of 7 blocks
    eng = build(checkpoint, monkeypatch, slots=3, pool_blocks=12)
    queue = SchedQueue()
    for i in range(3):
        queue.add(f"r{i}", ids(cfg, 50, i), decoding()).pending_step = 0
    run(eng, "held", ids(cfg, 44, 9), steps=0)  # a resident holds 6 of the 12
    queue.add("held", ids(cfg, 44, 9), decoding()).state = "decoding"
    assert eng.kv_pool.free == 6
    assert not any(policy.admissible(r, eng) for r in queue.waiting())  # 7 > 6: and two lanes are free
    plan = policy.plan(queue, eng)
    assert plan.admitted == []  # it waits for blocks, with two lanes idle
    eng.end_session("held")
    assert all(policy.admissible(r, eng) for r in queue.waiting())
    eng.close()


def test_a_preempted_lane_gives_everything_back_and_is_never_aliased(checkpoint, monkeypatch):
    """The rule taken (sched/step.py): preempted by giving everything back
    and prefilling again from token 0."""
    from dnet_tpu.sched.step import _preempt

    cfg = checkpoint[0]
    eng = build(checkpoint, monkeypatch, slots=2, prefix_cache_size=4)
    try:
        assert eng.paged_prefix is None  # nothing to alias into, whatever was asked
        prompt = ids(cfg, 40, 1)
        whole = run(eng, "v", prompt, steps=5)
        eng.end_session("v")
        toks = run(eng, "v", prompt, steps=2)
        before = metric("dnet_sched_preemptions_total").labels(reason="block_starvation").value
        _preempt(eng, "v", prompt + toks)
        assert metric("dnet_sched_preemptions_total").labels(reason="block_starvation").value == before + 1
        assert "v" not in eng.slot_of and eng.kv_pool.free == eng.kv_pool.total
        assert metric("dnet_state_slots_used").value == 0
        assert metric("dnet_kv_prefix_shared_blocks_total").value == 0
        # the resume: the whole of what was confirmed, again from token 0
        assert eng.seed_from_prefix("v", prompt + toks[:-1]) == 0
        res = eng.prefill_and_sample("v", prompt + toks[:-1], decoding())
        out = [int(res.token[0])]
        for _ in range(3):
            step, errs = eng.decode_batch({"v": (out[-1], decoding())})
            assert not errs
            out.append(int(step["v"].token[0]))
        assert toks[:-1] + out == whole
    finally:
        eng.close()


async def _serve(model_dir, prompts, max_tokens, slots, deadlines=None):
    """The production stack: a plain load (serving_plan decides), the
    scheduler's own loop, admission, preemption and requeue."""
    import asyncio

    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager
    from dnet_tpu.api.schemas import ChatCompletionRequest
    from dnet_tpu.config import reset_settings_cache

    reset_settings_cache()
    inference = InferenceManager(adapter=None, request_timeout_s=300.0, max_concurrent=slots)
    manager = LocalModelManager(inference, max_seq=128, param_dtype="float32", batch_slots=slots)
    await manager.load_model(str(model_dir))
    assert manager.serving.adapter == "SchedulerAdapter" and manager.serving.kv == "state+paged"

    def req(content, deadline_s):
        body = {"model": "tiny", "messages": [{"role": "user", "content": content}],
                "max_tokens": max_tokens, "temperature": 0.0}
        if deadline_s is not None:
            body["deadline_s"] = deadline_s
        return ChatCompletionRequest.model_validate(body)

    try:
        deadlines = deadlines or [None] * len(prompts)
        outs = await asyncio.gather(*(
            inference.generate(req(p, dl)) for p, dl in zip(prompts, deadlines)
        ))
        return [o.choices[0].message.content for o in outs]
    finally:
        await manager.unload_model()


def test_a_pool_too_small_for_its_lanes_queues_or_recomputes_and_never_corrupts(checkpoint, monkeypatch):
    """Three lanes and a pool of 13 blocks: two prompts of four blocks are
    admitted and the third waits (for blocks, a lane idle); as the two
    decode to nine blocks each they outgrow the pool, and the less urgent is
    preempted: everything given back, prefilled again from token 0 once
    blocks free.  Every answer is the one the request gets alone."""
    import asyncio

    from dnet_tpu.config import reset_settings_cache

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(BT))
    monkeypatch.setenv("DNET_SCHED_SLOTS", "3")
    rng = np.random.default_rng(7)
    prompts = [" ".join(f"t{i}" for i in rng.integers(1, 512, size=22)) for _ in range(3)]
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "0")
    solo = [asyncio.run(_serve(checkpoint[1], [p], 40, 3))[0] for p in prompts]
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "13")
    before = metric("dnet_sched_preemptions_total").labels(reason="block_starvation").value
    got = asyncio.run(_serve(checkpoint[1], prompts, 40, 3, deadlines=[30.0, 60.0, 90.0]))
    reset_settings_cache()
    assert metric("dnet_sched_preemptions_total").labels(reason="block_starvation").value > before
    assert got == solo  # recomputed, never corrupted; and it ended: no deadlock
    assert metric("dnet_state_slots_used").value == 0
    assert metric("dnet_kv_blocks_used").labels(kind="full").value == 0


def test_prefix_sharing_refuses_with_its_reason(checkpoint, monkeypatch):
    from dnet_tpu.core import batch

    warned = []
    monkeypatch.setattr(batch.log, "warning", lambda msg, *a: warned.append(msg % a))
    eng = build(checkpoint, monkeypatch, prefix_cache_size=4)
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
    plain = build(checkpoint, monkeypatch)
    assert plain.prefix_refusal is None  # not asked for: nothing refused
    plain.close()


def test_kv_layout_has_one_rule_from_the_models_kinds(checkpoint):
    from dnet_tpu.api.model_manager import serving_plan
    from dnet_tpu.core.batch import KV_HYBRID, kv_layout
    from dnet_tpu.core.types import EngineCapabilityError
    from dnet_tpu.models import ModelConfig, get_ring_model_cls
    from dnet_tpu.ops.paged_attention import ragged_refusal

    cfg = ModelConfig.from_hf(checkpoint[0])
    model = get_ring_model_cls("qwen3_next")(cfg, range(cfg.num_hidden_layers))
    assert set(model.paged_kinds) == {KV_KIND_STATE, KV_KIND_FULL} and not model.kv_rewindable(128)
    assert ragged_refusal(model, 0) is None  # the store can serve it
    for spec_lookahead in (0, 4):  # a state cannot be rewound: speculation goes, the store stays
        layout, why = kv_layout(model, 0, spec_lookahead, 33280)
        assert layout == KV_HYBRID and "lane of recurrent state and a page table" in why
    with pytest.raises(EngineCapabilityError, match="quantized KV"):
        kv_layout(model, 8, 0, 33280)
    plan = serving_plan(
        model, mesh=None, batch_slots=16, streams_weights=False, kv_quant_bits=0,
        spec_lookahead=0, draft_dir=None, max_seq=33280,
    )
    assert (plan.engine, plan.adapter, plan.kv) == ("BatchedEngine", "SchedulerAdapter", KV_HYBRID)
    model.paged_kinds = ("state", "window", "full", "state")
    assert "no store holds all three" in ragged_refusal(model, 0)


def test_the_config_file_is_the_catalog_row_cut_in_depth_and_experts():
    full = json.loads(CONFIG.read_text())
    assert full["num_hidden_layers"] == 4 and full["num_experts"] == 256
    assert full["num_experts_routed"] == 512 and full["expert_offset"] == 0
    assert full["assumed"]["published"] == {"num_hidden_layers": 48, "num_experts": 512}
    assert full["serve"]["expect_health"]["used"] == [
        "gdn_step", "gdn_chunk", "paged_attend", "flash_prefill"]
    dead = {"DNET_SCHED", "DNET_KV_PAGED", "DNET_KV_RAGGED"}
    assert not dead & set(full["serve"]["env"]) and not dead & set(full["rehearse"]["env"])
