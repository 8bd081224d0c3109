"""Which lanes a tick's decode dispatch carries, and how wide it is.

On the served path (sched/step.py; sched/policy.py: plan) every dispatch is
ONE step that carries every lane that asked: a lane of the step in flight
is chained to it on the device, a lane that has just been adopted joins the
same dispatch with its host token, and nobody waits for anybody's buffer.
The engine's own half (core/batch.py: decode_batch, for the callers that
read every step before they ask for the next) sends one step a call too,
whatever the budgets say: the engine has no wider dispatch.  Streams are
bit-identical to serial stepping either way.
"""

import asyncio

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import metric, reset_obs
from dnet_tpu.sched.flight import get_tick_recorder
from dnet_tpu.sched.kinds import STATE_DECODING, STATE_PREFILLING, STATE_WAITING
from dnet_tpu.sched.policy import SchedulerPolicy
from dnet_tpu.sched.queue import SchedQueue

pytestmark = pytest.mark.api

CHUNK = 8  # prefill chunk and kv block, tokens


@pytest.fixture
def paged_env(monkeypatch):
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(CHUNK))
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    monkeypatch.setenv("DNET_OBS_ENABLED", "1")  # the tick-record ring
    reset_settings_cache()
    reset_obs()
    yield monkeypatch
    monkeypatch.undo()
    reset_settings_cache()
    reset_obs()


def _engine(model_dir, env, kv="paged", slots=4):
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(
        model_dir, slots=slots, max_seq=128, param_dtype="float32",
        kv_paged=None if kv == "paged" else False,
    )
    assert (eng.kv_pool is not None) is (kv == "paged")
    return eng


def _prompt(nonce: str, n: int):
    return [256] + [1 + (ord(nonce[0]) * 7 + 3 * j) % 250 for j in range(n - 1)]


def _decoding(nonce: str) -> DecodingParams:
    return DecodingParams(temperature=0.8, top_p=0.9, seed=ord(nonce[0]))


def _chunked_prefill(eng, nonce, ids, dec) -> int:
    """The scheduler's prefill surface, chunk by chunk (sched/step.py)."""
    eng.reserve_slot(nonce)
    eng.seed_from_prefix(nonce, ids, dec.seed)
    logits = None
    for i in range(0, len(ids), CHUNK):
        logits = eng.prefill_chunk(nonce, ids[i : i + CHUNK], dec.seed)
    eng.store_prefix(nonce, ids)
    return int(eng.adopt_prefilled(nonce, logits, dec).token[0])


def _serial_streams(model_dir, env, asks: dict) -> dict:
    """The same requests and seeds stepped through decode_batch with no
    budgets at all: one step a call, every unfinished lane in every call."""
    eng = _engine(model_dir, env)
    try:
        toks = {
            n: [_chunked_prefill(eng, n, _prompt(n, plen), _decoding(n))]
            for n, (plen, _ask) in asks.items()
        }
        while True:
            reqs = {
                n: (toks[n][-1], _decoding(n))
                for n, (_plen, ask) in asks.items() if len(toks[n]) < ask
            }
            if not reqs:
                return toks
            out, errs = eng.decode_batch(reqs)
            assert not errs, errs
            for n, row in out.items():
                toks[n].append(int(row.token[0]))
    finally:
        eng.close()


async def _client(adapter, got, nonce, plen, ask, after=None):
    """One driver: the API's own loop (send, await, echo), starting once
    `after` = (nonce, tokens) has been reached by another stream."""
    while after is not None and len(got.get(after[0], ())) < after[1]:
        await asyncio.sleep(0.001)
    dec = _decoding(nonce)
    send = _prompt(nonce, plen)
    got[nonce] = []
    for step in range(ask):
        await adapter.send_tokens(nonce, send, dec, step, budget=ask - step)
        res = await adapter.await_token(nonce, step, 120.0)
        assert not res.error, res.error
        got[nonce].append(res.token_id)
        send = [res.token_id]
    await adapter.reset_cache(nonce)


async def _serve(eng, clients):
    from dnet_tpu.sched.engine import SchedulerAdapter

    adapter = SchedulerAdapter(eng, token_budget=64, prefill_chunk=CHUNK)
    await adapter.start()
    got: dict = {}
    try:
        await asyncio.gather(*(_client(adapter, got, *c) for c in clients))
    finally:
        await adapter.shutdown()
    return got


def _tokens_by_source():
    tok = metric("dnet_decode_tokens_total")
    return {s: int(tok.labels(source=s).value) for s in ("dispatch", "buffer", "spec")}


def _decode_records():
    recs = [r.as_dict() for r in get_tick_recorder().records()]
    return [r for r in recs if r["decode_lanes"]]


def _dispatches() -> int:
    return int(metric("dnet_decode_dispatch_total").value)


def test_lanes_out_of_phase_behind_a_prompt_step_together_every_second_tick(tiny_llama_dir, paged_env):
    """Three lanes adopted on different ticks (1, 2 and 3 chunks of prompt)
    while a fourth prompt of 14 chunks is still prefilling: every dispatch
    is a single step that carries every lane that asked, and while the long
    prompt's chunks go on the ticks alternate (a chunk and a step, then a
    chunk and that step's read: the chunk is what the device runs across
    the host's turn, sched/step.py).  The streams are the serial ones."""
    asks = {"a": (8, 7), "b": (16, 7), "c": (24, 7), "d": (112, 1)}
    want = _serial_streams(tiny_llama_dir, paged_env, asks)
    reset_obs()
    eng = _engine(tiny_llama_dir, paged_env)
    try:
        got = asyncio.run(_serve(eng, [(n, *asks[n]) for n in asks]))
        slots = eng.slots
    finally:
        eng.close()
    assert got == want
    recs = [r.as_dict() for r in get_tick_recorder().records()]
    sent = [r for r in recs if r["dispatched_lanes"]]
    assert sent
    # the long prompt was still prefilling when each of these ticks began
    assert all(r["prefill_tokens"] for r in sent)
    # no tick sends a step behind a chunk while one is in flight
    assert not any(
        x["dispatched_lanes"] and y["dispatched_lanes"] for x, y in zip(recs, recs[1:])
    )
    # lanes joined as they were adopted, and once in they stepped together
    assert max(r["dispatched_lanes"] for r in sent) == 3
    assert sum(r["dispatched_lanes"] == 3 for r in sent) >= 4
    lane_steps = metric("dnet_decode_lane_steps_total").value
    slot_steps = metric("dnet_decode_slot_steps_total").value
    assert lane_steps == sum(r["dispatched_lanes"] for r in sent) == 3 * 6
    assert slot_steps == slots * len(sent)
    assert _dispatches() == len(sent)
    assert metric("dnet_decode_surplus_steps_total").value == 0
    src = _tokens_by_source()
    assert src["dispatch"] + src["buffer"] == 18 and src["spec"] == 0
    assert sum(r["decode_lanes"] for r in recs) == 18


def test_a_new_lane_joins_the_next_dispatch_and_nobody_waits_for_a_buffer(tiny_llama_dir, paged_env):
    """Two streams step together, each step chained to the one before; a
    third arrives: the tick after its adoption it is in the SAME dispatch as
    the other two, with its host token.  No step is launched for it alone."""
    asks = {"a": (8, 24), "b": (8, 24), "c": (8, 24)}
    want = _serial_streams(tiny_llama_dir, paged_env, asks)
    reset_obs()
    eng = _engine(tiny_llama_dir, paged_env)
    try:
        got = asyncio.run(_serve(eng, [
            ("a", *asks["a"]), ("b", *asks["b"]), ("c", *asks["c"], ("a", 3)),
        ]))
    finally:
        eng.close()
    assert got == want
    recs = [r.as_dict() for r in get_tick_recorder().records()]
    sent = [r["dispatched_lanes"] for r in recs if r["dispatched_lanes"]]
    assert _dispatches() == len(sent)
    joined = sent.index(3)  # c's first step, beside a and b
    assert set(sent[:joined]) <= {1, 2} and sent[joined - 1] == 2, sent
    # a and b end together one step after the other or so: until then no
    # dispatch carries fewer than all three (late drivers aside: none here)
    both_alive = sent[joined : joined + 15]
    assert both_alive == [3] * len(both_alive), sent
    assert metric("dnet_decode_lane_steps_total").value == 3 * 23
    assert metric("dnet_decode_chained_lanes_total").value >= 3 * 23 - 3 - 2
    src = _tokens_by_source()
    assert src["spec"] == 0 and src["dispatch"] + src["buffer"] == 3 * 23


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_decode_batch_offers_no_way_to_widen_a_dispatch(tiny_llama_dir, paged_env, kv):
    """The engine's half, without a scheduler: 16 calls under budgets as
    large as the widest chunk there used to be send 16 dispatches, one step
    each for the lanes that asked, and compile no program but the step's
    own (over the pool: at the table buckets the lanes grow into, and the
    append behind it).  Same stream as the unbudgeted calls give."""
    from dnet_tpu.obs.phases import JIT_FNS

    dec = DecodingParams(temperature=0.0)
    prompts = {"a": [256, 72, 101], "b": [256, 84, 104, 105]}
    compiles = metric("dnet_jit_compiles_total")
    steps_own = {"dense": {"batched_step"}, "paged": {"paged_attend", "kv_append"}}[kv]
    assert steps_own <= set(JIT_FNS)

    def run(budgeted: bool):
        eng = _engine(tiny_llama_dir, paged_env, kv=kv)
        try:
            last = {
                n: int(eng.prefill_and_sample(n, ids, dec).token[0])
                for n, ids in prompts.items()
            }
            got = {n: [t] for n, t in last.items()}
            sent0, lanes0 = _dispatches(), metric("dnet_decode_lane_steps_total").value
            compiled0 = {fn: compiles.labels(fn=fn).value for fn in JIT_FNS}
            for k in range(16):
                budgets = {n: 32 - k for n in last} if budgeted else None
                out, errs = eng.decode_batch(
                    {n: (t, dec) for n, t in last.items()}, budgets=budgets
                )
                assert not errs and set(out) == set(last)
                assert _dispatches() - sent0 == k + 1
                assert not eng._buffer  # nothing computed ahead of the asking
                for n in last:
                    last[n] = int(out[n].token[0])
                    got[n].append(last[n])
            assert metric("dnet_decode_lane_steps_total").value - lanes0 == 16 * 2
            moved = {fn for fn in JIT_FNS if compiles.labels(fn=fn).value != compiled0[fn]}
            assert moved <= steps_own, moved
            return got
        finally:
            eng.close()

    assert run(budgeted=True) == run(budgeted=False)


def _queue(states: dict) -> SchedQueue:
    q = SchedQueue()
    for nonce, (state, step) in states.items():
        r = q.add(nonce, list(range(6)), DecodingParams())
        r.state, r.pending_step, r.pending_budget = state, step, 9
        r.prefilled = 0
    return q


class _Slots:
    max_seq = 256

    def __init__(self, slots):
        self.slots = slots


@pytest.mark.parametrize(
    "others,slots",
    [
        ({}, 4),  # lanes alone
        ({"p": (STATE_PREFILLING, 0)}, 4),  # a prompt mid-prefill
        ({"w": (STATE_WAITING, 0)}, 4),  # admitted this tick: its chunk is in the plan
        ({"w": (STATE_WAITING, 0)}, 2),  # no slot free: it waits for admission
        ({"w": (STATE_WAITING, None)}, 4),  # preempted, its next step moments away
        ({"x": (STATE_DECODING, None)}, 4),  # a lane between steps has no budget to tell
    ],
)
def test_the_policy_hands_out_budgets_in_every_plan(others, slots):
    """`plan.budgets` says which lanes may be chained a step ahead, whether
    or not a prompt waits: nothing is fused on this path, so a budget holds
    nobody up."""
    q = _queue({"d1": (STATE_DECODING, 3), "d2": (STATE_DECODING, 5), **others})
    plan = SchedulerPolicy(token_budget=64, prefill_chunk=8).plan(q, _Slots(slots))
    assert set(plan.decode) == {"d1", "d2"}
    assert plan.budgets == {"d1": 9, "d2": 9}
