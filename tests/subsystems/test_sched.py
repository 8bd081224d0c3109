"""Iteration-level scheduler (dnet_tpu/sched/): tick packing,
deadline-ordered admission, block-starvation preemption/resume, and the
scheduler-vs-legacy SSE parity contract.

Unit tier drives SchedulerPolicy/SchedQueue over a fake engine (no model);
the end-to-end tier serves the REAL tiny model through InferenceManager /
ApiHTTPServer, as a plain load serves it, so the paged block pool,
preemption, and the byte-level SSE framing are all the production code
paths.  The legacy half of the parity tests is BatchedLocalAdapter,
constructed directly over the same engine (no load selects it any more).
"""

import asyncio
import re

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import metric
from dnet_tpu.sched.kinds import (
    STATE_DECODING,
    STATE_PREFILLING,
    STATE_WAITING,
)
from dnet_tpu.sched.policy import SchedulerPolicy
from dnet_tpu.sched.queue import SchedQueue

pytestmark = pytest.mark.api


# ---------------------------------------------------------------------------
# fakes: just enough engine surface for the loop-side policy (slots + pool)
# ---------------------------------------------------------------------------


class FakePool:
    def __init__(self, free: int) -> None:
        self.free = free

    def can_cover(self, n: int) -> bool:
        return n <= self.free


class FakeCfg:
    block_tokens = 8

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_tokens)


class FakeEngine:
    max_seq = 256

    def __init__(self, slots: int = 4, free_blocks=None) -> None:
        self.slots = slots
        self.kv_pool = FakePool(free_blocks) if free_blocks is not None else None
        self._kv_cfg = FakeCfg()


def _add(queue, nonce, n_prompt, deadline=None, step=0):
    req = queue.add(nonce, list(range(n_prompt)), DecodingParams(),
                    deadline_ts=deadline)
    req.pending_step = step
    return req


# ---------------------------------------------------------------------------
# policy: packing
# ---------------------------------------------------------------------------


def test_tick_packs_decode_first_then_prefill_remainder():
    """Budget 10: 2 decode lanes take 1 token each, the PREFILLING request
    gets only the 8 remaining — a long prompt cannot starve running
    streams."""
    q = SchedQueue()
    for n in ("d1", "d2"):
        r = _add(q, n, 4, step=3)
        r.state = STATE_DECODING
    p = _add(q, "p1", 64)
    p.state = STATE_PREFILLING
    p.prefilled = 0
    plan = SchedulerPolicy(token_budget=10, prefill_chunk=256).plan(
        q, FakeEngine()
    )
    assert set(plan.decode) == {"d1", "d2"}
    assert len(plan.prefills) == 1 and plan.prefills[0].nonce == "p1"
    assert plan.prefill_tokens == 8
    assert plan.prefills[0].end - plan.prefills[0].start == 8
    assert not plan.prefills[0].last


def test_prefill_segments_bounded_by_chunk():
    q = SchedQueue()
    p = _add(q, "p1", 100)
    p.state = STATE_PREFILLING
    plan = SchedulerPolicy(token_budget=1000, prefill_chunk=16).plan(
        q, FakeEngine()
    )
    seg = plan.prefills[0]
    assert seg.end - seg.start == 16
    # the final segment of a prompt is tagged `last` so the tick adopts it
    p.prefilled = 96
    plan2 = SchedulerPolicy(token_budget=1000, prefill_chunk=16).plan(
        q, FakeEngine()
    )
    assert plan2.prefills[0].last and plan2.prefills[0].end == 100


def test_a_prompt_longer_than_the_budget_is_two_chunks_under_the_default_cap():
    """No chunk setting: the cap is the token budget, so a 3000-token
    prompt under budget 2048 is two chunks (2048, 952), and a prompt the
    budget holds is one, the next prompt taking what is left."""
    q = SchedQueue()
    p = _add(q, "p1", 3000)
    p.state = STATE_PREFILLING
    eng = FakeEngine()
    eng.max_seq = 4096
    policy = SchedulerPolicy(token_budget=2048, prefill_chunk=2048)
    first = policy.plan(q, eng).prefills
    assert [(c.start, c.end, c.last) for c in first] == [(0, 2048, False)]
    p.prefilled = 2048
    second = policy.plan(q, eng).prefills
    assert [(c.start, c.end, c.last) for c in second] == [(2048, 3000, True)]
    p.prefilled = 0
    nxt = _add(q, "p2", 1500)
    nxt.state = STATE_PREFILLING
    p.ids = p.ids[:1900]
    both = policy.plan(q, eng).prefills
    assert [(c.nonce, c.end - c.start) for c in both] == [("p1", 1900), ("p2", 148)]


def test_an_explicit_prefill_chunk_still_caps():
    q = SchedQueue()
    p = _add(q, "p1", 3000)
    p.state = STATE_PREFILLING
    seg = SchedulerPolicy(token_budget=2048, prefill_chunk=256).plan(
        q, FakeEngine()
    ).prefills[0]
    assert seg.end - seg.start == 256


@pytest.mark.parametrize(
    "env, want",
    [
        ({}, (2048, 2048)),  # the default: the cap is the budget
        ({"DNET_SCHED_TOKEN_BUDGET": "256"}, (256, 256)),
        ({"DNET_SCHED_PREFILL_CHUNK": "16"}, (2048, 16)),  # given: it caps
        ({"DNET_SCHED_TOKEN_BUDGET": "16", "DNET_SCHED_PREFILL_CHUNK": "8"}, (16, 8)),
    ],
    ids=["default", "budget-256", "chunk-16", "both"],
)
def test_the_adapters_cap_is_the_setting_or_the_budget(env, want, monkeypatch):
    from dnet_tpu.config import get_settings, reset_settings_cache
    from dnet_tpu.sched.engine import SchedulerAdapter

    class Chunked(FakeEngine):
        def prefill_chunk(self, *a):  # the surface the adapter asks for
            raise NotImplementedError

    for k in ("DNET_SCHED_TOKEN_BUDGET", "DNET_SCHED_PREFILL_CHUNK"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reset_settings_cache()
    try:
        policy = SchedulerAdapter(Chunked()).policy
        assert (policy.token_budget, policy.prefill_chunk) == want
        assert get_settings().sched.prefill_chunk_cap() == want[1]
        given = SchedulerAdapter(Chunked(), token_budget=64, prefill_chunk=4).policy
        assert (given.token_budget, given.prefill_chunk) == (64, 4)
    finally:
        reset_settings_cache()


def test_decode_without_pending_step_not_dispatched():
    """A DECODING lane whose driver has not asked for the next token yet
    (SSE backpressure) stays parked: dispatching it would sample a token
    nobody awaits and desync the stream."""
    q = SchedQueue()
    r = _add(q, "d1", 4, step=1)
    r.state = STATE_DECODING
    idle = q.add("d2", [1, 2], DecodingParams())
    idle.state = STATE_DECODING  # pending_step stays None
    plan = SchedulerPolicy(64, 16).plan(q, FakeEngine())
    assert set(plan.decode) == {"d1"}
    # no paged pool -> no preemption possible -> no replay snapshots
    assert plan.ids == {}
    # under pool pressure the replay ids ride the plan: the prefix alias
    # of a preempted victim needs them
    starved = SchedulerPolicy(64, 16).plan(q, FakeEngine(free_blocks=0))
    assert set(starved.ids) == {"d1", "d2"}


# ---------------------------------------------------------------------------
# policy: admission
# ---------------------------------------------------------------------------


def test_admission_is_deadline_ordered_then_fifo():
    q = SchedQueue()
    _add(q, "late", 4, deadline=100.0)
    _add(q, "urgent", 4, deadline=5.0)
    _add(q, "none1", 4)   # no deadline sorts last...
    _add(q, "none2", 4)   # ...and FIFO among equals
    plan = SchedulerPolicy(64, 16).plan(q, FakeEngine(slots=8))
    assert plan.admitted == ["urgent", "late", "none1", "none2"]


def test_admission_respects_slot_pool():
    q = SchedQueue()
    for i in range(3):
        _add(q, f"w{i}", 4)
    d = _add(q, "run", 4, step=2)
    d.state = STATE_DECODING
    plan = SchedulerPolicy(64, 16).plan(q, FakeEngine(slots=2))
    assert plan.admitted == ["w0"]  # 2 slots - 1 running = 1 free


def test_admission_gated_by_free_blocks_with_failfast():
    """A pool that cannot cover the prompt blocks admission — unless
    nothing is running at all, where the top request goes through anyway
    so an oversized prompt fails fast with the typed error instead of
    queueing forever."""
    q = SchedQueue()
    _add(q, "w0", 64)  # needs 9 blocks (64+1 over block_tokens=8)
    d = _add(q, "run", 4, step=1)
    d.state = STATE_DECODING
    starved = FakeEngine(slots=4, free_blocks=2)
    plan = SchedulerPolicy(256, 256).plan(q, starved)
    assert plan.admitted == []
    assert q.get("w0").state == STATE_WAITING
    # drain the running lane -> fail-fast admission despite the tiny pool
    q.remove("run")
    plan2 = SchedulerPolicy(256, 256).plan(q, starved)
    assert plan2.admitted == ["w0"]


def test_preempted_request_waits_for_its_driver_step():
    """A preempted request whose next driver step has not arrived is not
    schedulable — its resume sample would have no future to resolve."""
    q = SchedQueue()
    r = _add(q, "pre", 8, step=4)
    r.state = STATE_DECODING
    q.requeue("pre", reason_preempt=True)
    r.pending_step = None  # the in-flight step resolved as an error/resume
    policy = SchedulerPolicy(64, 16)
    eng = FakeEngine()
    assert not policy.has_work(q, eng)
    assert policy.plan(q, eng).admitted == []
    r.pending_step = 5  # the driver's next send names the future
    assert policy.has_work(q, eng)
    assert policy.plan(q, eng).admitted == ["pre"]


# ---------------------------------------------------------------------------
# queue: priority bookkeeping
# ---------------------------------------------------------------------------


def test_victims_are_least_urgent_first():
    q = SchedQueue()
    for nonce, dl in (("a", 5.0), ("b", None), ("c", 50.0)):
        r = _add(q, nonce, 4, deadline=dl, step=1)
        r.state = STATE_DECODING
    # no-deadline (inf) evicts first, then the laxest deadline
    assert q.victims() == ["b", "c", "a"]


def test_requeue_preserves_arrival_priority():
    q = SchedQueue()
    first = _add(q, "first", 4, step=2)
    first.state = STATE_DECODING
    _add(q, "second", 4)
    q.requeue("first", reason_preempt=True)
    assert q.get("first").state == STATE_WAITING
    assert q.get("first").preemptions == 1
    assert q.get("first").prefilled == 0
    # still ahead of the later arrival: preemption cannot invert priority
    assert [r.nonce for r in q.waiting()] == ["first", "second"]


def test_queue_depth_gauges_track_states():
    q = SchedQueue()
    r = _add(q, "x", 4)
    gauges = {
        s: metric("dnet_sched_queue_depth").labels(state=s)
        for s in (STATE_WAITING, STATE_PREFILLING, STATE_DECODING)
    }
    assert gauges[STATE_WAITING].value >= 1
    r.state = STATE_DECODING
    q.sync_gauges()
    waiting_now = gauges[STATE_WAITING].value
    q.remove("x")
    assert gauges[STATE_DECODING].value <= waiting_now + 1  # removed


# ---------------------------------------------------------------------------
# end-to-end: the real tiny model through the production serving stack
# ---------------------------------------------------------------------------


def _req(content: str, max_tokens: int = 8, deadline_s=None):
    from dnet_tpu.api.schemas import ChatCompletionRequest

    body = {
        "model": "tiny",
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
    }
    if deadline_s is not None:
        body["deadline_s"] = deadline_s
    return ChatCompletionRequest.model_validate(body)


async def _load(inference, manager, model_dir, sched: bool, slots: int):
    """sched: a plain load (serving_plan: the scheduler over the pool).
    Legacy: the same engine under BatchedLocalAdapter, installed the way
    load_model's tail installs one."""
    if sched:
        await manager.load_model(str(model_dir))
        assert manager.serving.adapter == "SchedulerAdapter"
        return
    from dnet_tpu.api.strategies import BatchedLocalAdapter
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.utils.tokenizer import load_tokenizer

    manager.engine = BatchedEngine(
        model_dir, slots=max(slots, 8), max_seq=64, param_dtype="float32"
    )
    inference.adapter = BatchedLocalAdapter(manager.engine)
    await inference.adapter.start()
    inference.tokenizer = load_tokenizer(model_dir)
    inference.model_id = "tiny"


async def _serve_burst(model_dir, prompts, sched: bool, max_tokens=8,
                       slots=4, deadlines=None):
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager

    reset_settings_cache()
    inference = InferenceManager(
        adapter=None, request_timeout_s=120.0, max_concurrent=slots
    )
    manager = LocalModelManager(
        inference, max_seq=64, param_dtype="float32", batch_slots=slots
    )
    await _load(inference, manager, model_dir, sched, slots)
    try:
        deadlines = deadlines or [None] * len(prompts)
        outs = await asyncio.gather(*(
            inference.generate(_req(p, max_tokens, deadline_s=dl))
            for p, dl in zip(prompts, deadlines)
        ))
        return [o.choices[0].message.content for o in outs]
    finally:
        await manager.unload_model()


@pytest.mark.slow
def test_scheduler_legacy_parity_mixed_burst(tiny_llama_dir, monkeypatch):
    """The acceptance contract: a mixed burst (short/long prompts, more
    requests than slots) produces the SAME greedy texts through the
    scheduler as through the legacy adapter, over the paged pool."""
    prompts = ["Hi", "Hello there", "A quick brown fox", "x" * 30,
               "mid prompt here"]
    legacy = asyncio.run(_serve_burst(tiny_llama_dir, prompts, sched=False))
    sched = asyncio.run(_serve_burst(tiny_llama_dir, prompts, sched=True))
    assert sched == legacy


def _normalize_sse(raw: str) -> str:
    """Strip the only run-specific bytes an SSE stream carries: the
    chatcmpl-<nonce> response id and the created wall-clock stamp."""
    raw = re.sub(r'"id": ?"[^"]*"', '"id": "chatcmpl-X"', raw)
    return re.sub(r'"created": ?\d+', '"created": 0', raw)


@pytest.mark.http
def test_scheduler_legacy_sse_byte_parity(tiny_llama_dir, monkeypatch):
    """Same burst through the REAL HTTP server: the SSE byte streams are
    identical after normalizing response id + created timestamp — chunk
    boundaries, logprob-free deltas, finish reasons, usage, framing."""
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager

    prompts = ["Hi", "Hello there", "A quick brown fox", "tail"]

    async def streams(sched: bool):
        inference = InferenceManager(
            adapter=None, request_timeout_s=120.0, max_concurrent=4
        )
        manager = LocalModelManager(
            inference, max_seq=64, param_dtype="float32", batch_slots=4
        )
        server = ApiHTTPServer(inference, manager)
        client = TestClient(TestServer(server.app))
        await client.start_server()
        try:
            await _load(inference, manager, tiny_llama_dir, sched, 4)

            async def one(p):
                resp = await client.post(
                    "/v1/chat/completions",
                    json={
                        "model": "tiny",
                        "messages": [{"role": "user", "content": p}],
                        "max_tokens": 6,
                        "temperature": 0,
                        "stream": True,
                    },
                )
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/event-stream"
                )
                return (await resp.read()).decode()

            return await asyncio.gather(*(one(p) for p in prompts))
        finally:
            await client.close()
            await manager.unload_model()

    legacy = [_normalize_sse(s) for s in asyncio.run(streams(False))]
    sched = [_normalize_sse(s) for s in asyncio.run(streams(True))]
    assert sched == legacy
    for s in sched:  # and they are real streams, not error shortcuts
        events = [ln for ln in s.splitlines() if ln.startswith("data: ")]
        assert events[-1] == "data: [DONE]" and len(events) > 2


@pytest.mark.slow
def test_small_pool_queues_by_blocks_and_completes(tiny_llama_dir, monkeypatch):
    """A pool too small for two residents: admission-by-blocks holds the
    second request in WAITING until the first frees its blocks — both
    complete, and each with the exact greedy text of an uncontended run."""
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "10")
    monkeypatch.setenv("DNET_SCHED_SLOTS", "2")

    prompts = ["a" * 20, "b" * 20]
    # solo baselines: each request alone (no contention, same texts owed)
    solo = [
        asyncio.run(_serve_burst(tiny_llama_dir, [p], sched=True,
                                 max_tokens=10, slots=2))[0]
        for p in prompts
    ]
    # contended: the second request carries the tight deadline -> priority
    got = asyncio.run(_serve_burst(
        tiny_llama_dir, prompts, sched=True, max_tokens=10, slots=2,
        deadlines=[None, 30.0],
    ))
    reset_settings_cache()
    assert got == solo


# ---------------------------------------------------------------------------
# step execution: block-starvation preemption (deterministic, fake engine)
# ---------------------------------------------------------------------------


class _Table:
    def __init__(self, blocks):
        self.blocks = list(blocks)


class FakeStepEngine:
    """The exact surface execute_tick touches, with scriptable pool
    starvation.  `pos` is per-slot committed length, as on BatchedEngine."""

    max_seq = 256
    slots = 4

    def __init__(self, fail_prefill=(), pool_free=100):
        self.kv_pool = FakePool(pool_free)
        self.kv_pool.free = pool_free
        self._kv_cfg = FakeCfg()
        self.slot_of = {}
        self.pos = [0] * self.slots
        self._tables = [None] * self.slots

        class _Inner:
            sessions = {}

        self.eng = _Inner()
        self.fail_prefill = set(fail_prefill)
        self.stored = []
        self.ended = []

    def occupy(self, nonce, committed=8, blocks=1):
        slot = len(self.slot_of)
        self.slot_of[nonce] = slot
        self.pos[slot] = committed
        self._tables[slot] = _Table(range(blocks))
        return slot

    def reserve_slot(self, nonce):
        self.occupy(nonce, committed=0, blocks=0)

    def seed_from_prefix(self, nonce, ids, seed=None):
        return 0

    def prefill_chunk(self, nonce, ids, seed=None):
        from dnet_tpu.kv import KVPoolExhausted

        if nonce in self.fail_prefill:
            raise KVPoolExhausted(2, 0, 8)
        slot = self.slot_of[nonce]
        self.pos[slot] += len(ids)
        return "logits"

    def store_prefix(self, nonce, ids):
        self.stored.append(nonce)

    def adopt_prefilled(self, nonce, logits, decoding):
        return f"sample-{nonce}"

    def abandon_prefill(self, nonce):
        self.slot_of.pop(nonce, None)

    def end_session(self, nonce):
        self.ended.append(nonce)
        self.slot_of.pop(nonce, None)

    def decode_launch(self, requests, budgets=None, chain=None):
        """The enqueue half: the lanes the step was sent for.  As on
        BatchedEngine, a lane of `chain` (the step not read yet) steps
        again only with a budget of 2 or more."""
        from dnet_tpu.core.batch import DecodeFlight, takes_another

        ahead = chain.order if chain is not None and chain.src is not None else {}
        order = {
            n: self.slot_of[n] for n in requests
            if ahead.get(n) != self.slot_of[n] or takes_another(budgets, n)
        }
        if not order:
            return DecodeFlight()
        return DecodeFlight(
            order=order, src="on the device",
            chained=frozenset(n for n in order if n in ahead),
        )

    def decode_read(self, flight, asked=None):
        """The read half: as on BatchedEngine, a lane that left while its
        step was in flight gets nothing and its `pos` is not advanced."""
        out = {}
        for n, slot in flight.order.items():
            if self.slot_of.get(n) == slot:
                self.pos[slot] += 1
                if asked is None or n in asked:
                    out[n] = f"tok-{n}"
        return out, {}


def _chunk(nonce, n_ids=8, victims=(), last=True):
    from dnet_tpu.sched.policy import PrefillChunk

    return PrefillChunk(
        nonce=nonce, ids=list(range(n_ids)), start=0, end=n_ids,
        first=True, last=last, decoding=DecodingParams(),
        pending_step=0, seed=None, victims=list(victims),
    )


def test_prefill_starvation_evicts_lower_priority_victim():
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick

    eng = FakeStepEngine(fail_prefill={"urgent"})
    eng.occupy("low", committed=6, blocks=2)
    plan = TickPlan()
    plan.decode = {"low": (42, DecodingParams())}
    plan.steps = {"low": 3}
    plan.ids = {"low": list(range(8))}
    plan.victims = ["low"]
    plan.prefills = [_chunk("urgent", victims=["low"])]
    res = execute_tick(eng, plan)
    # the chunk evicted the victim before this tick's step was enqueued
    # (chunks go first), its prefix aliased: it is left out of the step,
    # its pending step rides the resume and the freed lane's position is
    # left alone; the urgent prefill keeps its staging
    assert "low" not in res.decode_results and res.flight is None
    assert res.preempted == ["low"]
    assert eng.pos[0] == 6
    assert eng.ended == ["low"] and eng.stored == ["low"]
    assert res.progress["urgent"] == 0  # staged work kept; retry next tick
    assert "urgent" not in res.errors
    v = metric("dnet_sched_preemptions_total").labels(
        reason="block_starvation"
    ).value
    assert v >= 1


def test_prefill_starvation_without_victim_requeues():
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick

    eng = FakeStepEngine(fail_prefill={"u"})
    eng.occupy("other", committed=6, blocks=2)  # equal/higher priority
    plan = TickPlan()
    plan.prefills = [_chunk("u")]  # no victims: nothing lower-priority
    res = execute_tick(eng, plan)
    assert res.requeued == ["u"]
    assert "u" not in eng.slot_of  # staged work given back
    assert eng.ended == []  # nobody was evicted


def test_prefill_starvation_alone_is_typed_error():
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick

    eng = FakeStepEngine(fail_prefill={"u"})
    plan = TickPlan()
    plan.prefills = [_chunk("u")]
    res = execute_tick(eng, plan)
    # alone in the engine: no one will ever free blocks for this prompt
    assert "exhausted" in res.errors["u"]
    assert res.requeued == []


def test_decode_starvation_evicts_least_urgent_lane():
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick

    eng = FakeStepEngine(pool_free=0)
    eng.occupy("high", committed=8, blocks=1)  # next token needs block 2
    eng.occupy("low", committed=8, blocks=1)
    plan = TickPlan()
    plan.decode = {
        "high": (1, DecodingParams()),
        "low": (2, DecodingParams()),
    }
    plan.steps = {"high": 5, "low": 5}
    plan.ids = {"high": list(range(8)), "low": list(range(8))}
    plan.victims = ["low", "high"]  # least urgent first
    res = execute_tick(eng, plan)
    assert res.preempted == ["low"]
    assert list(res.flight.order) == ["high"]  # the urgent lane still stepped
    del plan.decode["low"]  # requeued: WAITING, no lane
    plan.budgets = {"high": 3}
    nxt = execute_tick(eng, plan, follows=res)  # and the tick after reads it
    assert set(nxt.decode_results) == {"high"}
    assert nxt.flight.chained == {"high"} and not nxt.preempted


def test_starved_requeue_is_bounded_by_typed_error():
    """MAX_STARVED_REQUEUES consecutive give-backs surface the typed
    backpressure error instead of spinning forever."""
    from dnet_tpu.sched.engine import SchedulerAdapter
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import MAX_STARVED_REQUEUES, TickResult

    reset_settings_cache()
    adapter = SchedulerAdapter(FakeStepEngine())
    req = adapter.queue.add("n", [1, 2, 3], DecodingParams())
    req.pending_step = 0
    plan = TickPlan()
    for _ in range(MAX_STARVED_REQUEUES - 1):
        adapter._apply(plan, TickResult(requeued=["n"]))
        assert adapter.queue.get("n").state == STATE_WAITING
    assert adapter.queue.get("n").starved == MAX_STARVED_REQUEUES - 1
    adapter._apply(plan, TickResult(requeued=["n"]))
    assert adapter.queue.get("n") is None  # errored out, not requeued


# ---------------------------------------------------------------------------
# EngineCapabilityError -> 422 (satellite: DL008 mapping)
# ---------------------------------------------------------------------------


def test_engine_capability_error_is_typed_and_mapped():
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import (
        EngineCapabilityError,
        InferenceError,
        InferenceManager,
    )
    from dnet_tpu.api.model_manager import LocalModelManager

    assert issubclass(EngineCapabilityError, InferenceError)

    async def go():
        inference = InferenceManager(adapter=None, request_timeout_s=5.0)
        manager = LocalModelManager(inference, max_seq=64)

        async def refuse(*a, **k):
            raise EngineCapabilityError(
                "continuous batching needs resident weights (fit policy)"
            )

        manager.load_model = refuse
        server = ApiHTTPServer(inference, manager)
        client = TestClient(TestServer(server.app))
        await client.start_server()
        try:
            r = await client.post("/v1/load_model", json={"model": "m"})
            assert r.status == 422
            body = await r.json()
            assert "resident weights" in body["error"]["message"]
            assert body["error"]["type"] == "invalid_request_error"
        finally:
            await client.close()

    asyncio.run(go())


def test_batched_engine_raises_typed_capability_error(tiny_llama_dir):
    """core/batch.py satellite: the load-time refusal is the typed error
    (mapped to 422), no longer a bare NotImplementedError->500."""
    from dnet_tpu.api.inference import EngineCapabilityError
    from dnet_tpu.core.batch import BatchedEngine

    class NoCommit:
        supports_kv_commit = False

    eng = BatchedEngine.__new__(BatchedEngine)

    class _Plan:
        streams_weights = True

    class _Eng:
        plan = _Plan()
        model = NoCommit()

    eng.eng = _Eng()
    with pytest.raises(EngineCapabilityError):
        eng._init_state(slots=2)
