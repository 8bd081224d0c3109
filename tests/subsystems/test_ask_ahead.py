"""A driver asks for its next token before it delivers the one it has.

`InferenceManager._run` over the real SchedulerAdapter loop on the CPU, tiny
engine: the order of a token's work is DECIDE, ask, two hops of the event
loop, DELIVER (api/inference.py), so that every lane's delivery runs while
the compute thread has the next decode step and not between two steps.

- (a) the recorded order of a turn: every lane's `send_tokens(s + 1)`, then
  the tick's submit, then the chunks of step s, in both of the tick loop's
  states (parked on its kick, and with the kick already set);
- (b) the stream is the ask-at-the-top order's, to the token, the text, the
  logprob entry, the finish reason and the usage (`_plain_driver` below IS
  that order: the loop as it stood, kept here as the reference);
- (c) no ask after a lane's last token, and no surplus step the old order
  did not leave;
- (d) an ask ahead that raises surfaces at the step's own top, where the
  resume path owns it;
- (e) a client that goes away between ask and delivery frees its lane;
- (f) `dnet_api_driver_asks_total{order=}` counts every ask once.
"""

import asyncio
import time

import pytest

from dnet_tpu.api.inference import (
    DeadlineExceededError,
    InferenceManager,
    _holdback_len,
)
from dnet_tpu.api.schemas import ChatCompletionRequest
from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import get_registry, metric, reset_obs
from dnet_tpu.obs.phases import DRIVER_ASK_AHEAD, DRIVER_ASK_AT_STEP, DRIVER_ASK_ORDERS
from dnet_tpu.utils.tokenizer import ByteTokenizer, Detokenizer

pytestmark = pytest.mark.api

CHUNK = 8  # prefill chunk and kv block, tokens
LANES = ("a", "b", "c", "d")
TURN_S = 0.25  # the drivers' turn here: see paged_env


class LetterTokenizer(ByteTokenizer):
    """Every token is one letter, so every token is one chunk and a stop
    string of two letters is a match across two tokens."""

    def __init__(self, eos=()):
        self._eos = set(eos)

    def decode(self, ids):
        return "".join(chr(ord("a") + int(i) % 26) for i in ids if 0 <= int(i) < 256)

    @property
    def eos_token_ids(self):
        return self._eos or {self.eos_token_id}


@pytest.fixture
def paged_env(monkeypatch):
    from dnet_tpu.sched import engine as sched_engine

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(CHUNK))
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    # a loaded machine can stall the loop past the 2 ms bound, and a lane cut
    # from one turn would fall out of step with the others
    monkeypatch.setattr(sched_engine, "DRIVER_TURN_S", TURN_S)
    reset_settings_cache()
    reset_obs()
    yield monkeypatch
    monkeypatch.undo()
    reset_settings_cache()
    reset_obs()


@pytest.fixture
def engine(tiny_llama_dir, paged_env):
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(tiny_llama_dir, slots=6, max_seq=128, param_dtype="float32")
    assert eng.kv_pool is not None
    yield eng
    eng.close()


class _Kick(asyncio.Event):
    """The tick loop's kick, saying whether each wait found it set."""

    def __init__(self, events):
        super().__init__()
        self._events = events

    async def wait(self):
        self._events.append(("kick_wait", self.is_set()))
        return await super().wait()


def _spy_adapter(engine, events, on_send=None, on_token=None):
    """The real adapter, with its driver surface, its submit, its apply and
    its two waits written into `events` in the order the loop ran them.
    `on_send(nonce, step)` may raise in the send's place; `on_token(nonce)`
    runs as a token leaves for its driver."""
    from dnet_tpu.sched.engine import SchedulerAdapter
    from dnet_tpu.sched.step import execute_tick

    class Spy(SchedulerAdapter):
        async def start(self):
            await super().start()
            self._kick = _Kick(events)  # the loop's task has not run yet
            submit = self._executor.submit

            def spied(fn, *args, **kw):
                if fn is execute_tick:
                    events.append(("submit",))
                return submit(fn, *args, **kw)

            self._executor.submit = spied

        def _apply(self, plan, result):
            events.append(("apply",))
            super()._apply(plan, result)

        async def _drivers_turn(self):
            events.append(("drivers_turn", len(self._answering)))
            await super()._drivers_turn()
            events.append(("turn_over",))

        async def send_tokens(self, nonce, token_ids, decoding, step, budget=None):
            if on_send is not None:
                on_send(nonce, step)
            events.append(("send", nonce, step))
            await super().send_tokens(nonce, token_ids, decoding, step, budget=budget)

        async def await_token(self, nonce, step, timeout):
            result = await super().await_token(nonce, step, timeout)
            events.append(("token", nonce, step, result.token_id))
            if on_token is not None:
                on_token(nonce)
            return result

        async def reset_cache(self, nonce):
            events.append(("reset", nonce))
            await super().reset_cache(nonce)

    return Spy(engine, token_budget=64, prefill_chunk=CHUNK)


def _manager(adapter, tokenizer=None):
    inference = InferenceManager(adapter, request_timeout_s=120.0, max_concurrent=8)
    inference.tokenizer = tokenizer or LetterTokenizer()
    inference.model_id = "tiny"
    return inference


def _request(content, **kw):
    body = {
        "model": "tiny", "temperature": 0.0, "max_tokens": 12,
        "messages": [{"role": "user", "content": content}],
    }
    body.update(kw)
    return ChatCompletionRequest.model_validate(body)


# ---- (a) the order of a turn ---------------------------------------------------


async def _four_streams(engine, events, beside=None):
    """Four streamed requests on one loop, each chunk written into `events`
    as its consumer takes it; `beside(adapter, lane, n)` runs in the
    consumer after lane's n-th chunk."""
    adapter = _spy_adapter(engine, events)
    await adapter.start()
    inference = _manager(adapter)

    async def consume(lane):
        n = 0
        async for chunk in inference.generate_stream(_request(lane * 5, max_tokens=16)):
            if chunk.choices[0].delta.content:
                events.append(("chunk", lane, n))
                n += 1
                if beside is not None:
                    await beside(adapter, lane, n)

    try:
        await asyncio.gather(*(consume(lane) for lane in LANES))
    finally:
        await adapter.shutdown()


def _next(events, start, kind):
    """Position of the first `kind` event from `start` on (the end if none)."""
    return next(
        (i for i in range(start, len(events)) if events[i][0] == kind), len(events)
    )


def _assert_asks_then_submit_then_chunks(events, apply_at):
    """The turn that follows the apply at `apply_at`: every lane asks, the
    tick is submitted, and only then is a chunk of that apply's tokens
    delivered."""
    submit_at = _next(events, apply_at, "submit")
    inside = events[apply_at + 1:submit_at]
    asked = [ev[1] for ev in inside if ev[0] == "send"]
    assert len(asked) == len(set(asked)) == len(LANES), inside
    assert not [ev for ev in inside if ev[0] == "chunk"], inside
    behind = {ev[1] for ev in events[submit_at + 1:] if ev[0] == "chunk"}
    assert behind == set(LANES)


def _parked(events):
    """The tick loop's last move is its own wait on the kick, found clear
    (not a wait inside the drivers' turn, and no tick is out)."""
    moves = [
        ev for ev in events
        if ev[0] in ("submit", "apply", "kick_wait", "drivers_turn", "turn_over")
    ]
    return (
        len(moves) > 1
        and moves[-1] == ("kick_wait", False)
        and moves[-2][0] in ("apply", "turn_over")
    )


def test_parked_on_its_kick_the_loop_submits_before_any_delivery(engine):
    """After a step-only tick in a closed loop the tick loop is PARKED on
    its kick: it is two hops from its submit (the wake-up, then its own
    coalescing `sleep(0)`), and the drivers' two hops keep every delivery
    behind it.  The loop is brought to that state by holding the four
    consumers until the drivers' turn has lapsed and the loop found nothing
    to plan; it stays in it from then on."""
    events: list = []
    held = {"n": 0, "gate": None}

    async def beside(adapter, lane, n):
        if n != 4:
            return
        if held["gate"] is None:
            held["gate"] = asyncio.Event()
        held["n"] += 1
        if held["n"] == len(LANES):
            # every driver has asked for its next token (the ask precedes
            # the delivery) and none will ask again until its consumer
            # comes back: the tick runs, the turn lapses, the loop parks
            for _ in range(1000):
                if _parked(events):
                    break
                await asyncio.sleep(0.01)
            events.append(("gate",))
            held["gate"].set()
        await held["gate"].wait()

    asyncio.run(_four_streams(engine, events, beside))
    gate_at = events.index(("gate",))
    # the turns in which all four lanes were still going
    whole = [
        a for a in range(gate_at, len(events))
        if events[a] == ("apply",)
        and sum(ev[0] == "send" for ev in events[a + 1:_next(events, a, "submit")]) == len(LANES)
    ]
    assert len(whole) >= 6, events[gate_at:]
    for apply_at in whole[:-1]:
        # the state: the first wait after the apply finds the kick CLEAR and
        # is the loop's own park, not the drivers' turn
        after = [ev for ev in events[apply_at + 1:] if ev[0] in ("kick_wait", "drivers_turn")]
        assert after[0] == ("kick_wait", False) and after[1] == ("drivers_turn", 0), after[:3]
        _assert_asks_then_submit_then_chunks(events, apply_at)


def test_with_a_prompt_waiting_the_loop_submits_before_any_delivery(engine):
    """A fifth prompt arrives while a tick runs: the kick is set when the
    tick loop comes back, so it does not park; it takes its coalescing hop,
    finds the four lanes owing their answers and waits for them in the
    drivers' turn, ONE hop from its submit.  The turn still reads asks,
    submit, chunks."""
    events: list = []
    fifth = {}

    async def beside(adapter, lane, n):
        if lane == "a" and n == 5:
            # a driver played by hand (tests/subsystems/test_turnaround.py):
            # this chunk is delivered while the next tick is on the compute
            # thread, so the prompt is in the queue when that tick ends
            ids = [256] + [7 + 3 * j for j in range(4 * CHUNK - 1)]
            await adapter.send_tokens("fifth", ids, DecodingParams(temperature=0.0), 0, budget=1)
            fifth["task"] = asyncio.ensure_future(adapter.await_token("fifth", 0, 120.0))
        if lane == "a" and n == 12:
            await fifth["task"]
            await adapter.reset_cache("fifth")

    asyncio.run(_four_streams(engine, events, beside))
    sent = events.index(("send", "fifth", 0))
    before = max(i for i, ev in enumerate(events[:sent]) if ev[0] in ("submit", "apply"))
    assert events[before] == ("submit",), "the prompt must arrive while a tick runs"
    apply_at = _next(events, sent, "apply")
    after = [ev for ev in events[apply_at + 1:] if ev[0] in ("kick_wait", "drivers_turn")]
    assert after[0] == ("kick_wait", True) and after[1] == ("drivers_turn", 4), after[:3]
    _assert_asks_then_submit_then_chunks(events, apply_at)


# ---- (b) the same stream as the ask-at-the-top order ---------------------------


class TokenDeadline:
    """A deadline that expires once its request has been handed `after`
    tokens (the spy counts them): a clock both orders read alike."""

    def __init__(self, after):
        self.after, self.seen = after, 0
        self.t_deadline = time.time() + 3600.0

    @property
    def expired(self):
        return self.seen >= self.after

    def remaining(self):
        return 3600.0


async def _plain_driver(inference, adapter, req, deadline=None):
    """The driver's loop as it stood before the ask moved: send at the top
    of the iteration, await, then everything else.  Returns what a client
    would have seen: ([(content, [(entry text, logprob, [top texts])])],
    finish_reason, (prompt tokens, completion tokens), error)."""
    tok = inference.tokenizer
    prompt_ids = tok.encode(req.render_prompt(tok))
    decoding = inference._decoding(req)
    stop_seqs, eos = req.stop_sequences(), tok.eos_token_ids
    detok = Detokenizer(tok)
    max_new = min(req.completion_tokens_limit, adapter.max_seq() - len(prompt_ids))
    nonce = "plain"
    chunks, generated, finish, error = [], 0, "length", None
    pending, held, emitted_ahead, stopped_by_seq = "", [], 0, False

    def emit(content, entries):
        chunks.append((content, [_entry(e) for e in entries]))

    await adapter.reset_cache(nonce)
    try:
        send = list(prompt_ids)
        for step in range(max_new):
            if deadline is not None and deadline.expired:
                error = f"request deadline expired after {generated} token(s)"
                break
            await adapter.send_tokens(nonce, send, decoding, step, budget=max_new - step)
            result = await adapter.await_token(nonce, step, 120.0)
            assert not result.error, result.error
            generated += 1
            if result.token_id in eos:
                finish = "stop"
                break
            delta = detok.add(result.token_id)
            send = [result.token_id]
            if req.logprobs_enabled:
                held.append(inference._logprob_entry(result, delta))
            stopped = False
            if stop_seqs:
                pending += delta
                delta = ""
                for s in stop_seqs:
                    idx = pending.find(s)
                    if idx != -1:
                        pending = pending[:idx]
                        stopped = True
                        break
                if stopped:
                    delta, pending = pending, ""
                else:
                    hold = _holdback_len(pending, stop_seqs)
                    emit_upto = len(pending) - hold
                    delta, pending = pending[:emit_upto], pending[emit_upto:]
            if delta or stopped:
                kept = []
                if req.logprobs_enabled and held:
                    budget = emitted_ahead + len(delta)
                    while held and len(held[0].token) <= budget:
                        budget -= len(held[0].token)
                        kept.append(held.pop(0))
                    if stopped:
                        held, emitted_ahead = [], 0
                    else:
                        emitted_ahead = budget
                emit(delta, kept)
            if stopped:
                finish, stopped_by_seq = "stop", True
                break
        if error is None:
            tail = pending + detok.flush() if not stopped_by_seq else ""
            if tail or (held and not stopped_by_seq):
                emit(tail, held if req.logprobs_enabled and not stopped_by_seq else [])
    finally:
        await adapter.reset_cache(nonce)
    if error is not None:
        return chunks, None, None, error
    return chunks, finish, (len(prompt_ids), generated), None


def _entry(e):
    return (e.token, round(e.logprob, 4), [t.token for t in e.top_logprobs])


async def _served(inference, req):
    """The same, through `generate_stream`."""
    chunks, finish, usage, error = [], None, None, None
    try:
        async for chunk in inference.generate_stream(req):
            choice = chunk.choices[0]
            if choice.finish_reason:
                finish = choice.finish_reason
                usage = (chunk.usage.prompt_tokens, chunk.usage.completion_tokens)
            else:
                entries = choice.logprobs.content if choice.logprobs else []
                chunks.append((choice.delta.content or "", [_entry(e) for e in entries]))
    except DeadlineExceededError as exc:
        error = str(exc)
    return chunks, finish, usage, error


async def _read_the_step_in_flight(adapter):
    """A stream cut short leaves the step chained behind its last token in
    flight; the engine counts it as surplus when the NEXT tick reads it.
    One token for a lane of its own is such a tick, and chains nothing."""
    await adapter.send_tokens("flush", [256, 1, 2], DecodingParams(temperature=0.0), 0, budget=1)
    await adapter.await_token("flush", 0, 120.0)
    await adapter.reset_cache("flush")


def _ids_of(events, nonce):
    return [ev[3] for ev in events if ev[0] == "token" and ev[1] == nonce]


async def _both_orders(engine, case):
    """(plain, served, events, the served request's nonce, its surplus
    steps) of one case, one order after the other through one adapter.  A
    case is built from the greedy stream itself: the ids and letters of a
    plain run, and the first place where both are new."""
    events: list = []
    deadlines: dict = {}  # nonce -> the TokenDeadline its tokens move
    serving: dict = {}  # the deadline of the request about to be served

    def on_send(nonce, step):
        if step == 0 and serving and nonce != "plain":
            deadlines[nonce] = serving["deadline"]  # the nonce is the response id

    def on_token(nonce):
        if nonce in deadlines:
            deadlines[nonce].seen += 1

    adapter = _spy_adapter(engine, events, on_send=on_send, on_token=on_token)
    await adapter.start()
    try:
        probe = await _plain_driver(_manager(adapter), adapter, _request("probe me"))
        ids = _ids_of(events, "plain")
        text = "".join(c for c, _ in probe[0])
        assert len(ids) == len(text) == 12
        fresh = next(
            k for k in range(3, 10)
            if ids[k] not in ids[:k] and text[k:k + 2] not in text[:k + 1]
        )
        kw, tokenizer, after = case(ids, text, fresh)
        inference = _manager(adapter, tokenizer)
        req = _request("probe me", **kw)
        del events[:]
        if after:
            deadlines["plain"] = TokenDeadline(after)
        surplus0 = metric("dnet_decode_surplus_steps_total").value
        plain = await _plain_driver(inference, adapter, req, deadlines.get("plain"))
        await _read_the_step_in_flight(adapter)
        surplus1 = metric("dnet_decode_surplus_steps_total").value
        if after:
            serving["deadline"] = TokenDeadline(after)
            inference._deadline_for = lambda req: serving["deadline"]
        mark = len(events)
        served = await _served(inference, req)
        await _read_the_step_in_flight(adapter)
        surplus2 = metric("dnet_decode_surplus_steps_total").value
        nonce = next(ev[1] for ev in events[mark:] if ev[0] == "send")
        return plain, served, events, nonce, (surplus1 - surplus0, surplus2 - surplus1)
    finally:
        await adapter.shutdown()


CASES = {
    # (request fields, tokenizer, deadline after n tokens) from the probe
    "eos": lambda ids, text, k: ({}, LetterTokenizer(eos={ids[k]}), None),
    "max_tokens_1": lambda ids, text, k: ({"max_tokens": 1}, None, None),
    "length": lambda ids, text, k: ({"max_tokens": 9}, None, None),
    "stop_across_two_tokens": lambda ids, text, k: ({"stop": [text[k:k + 2]]}, None, None),
    "logprobs": lambda ids, text, k: ({"logprobs": True, "top_logprobs": 3}, None, None),
    "stop_with_logprobs": lambda ids, text, k: (
        {"stop": [text[k:k + 2] + "~", text[k + 1:k + 3]], "logprobs": True, "top_logprobs": 2},
        None, None,
    ),
    "deadline_mid_stream": lambda ids, text, k: ({}, None, 5),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_stream_is_the_ask_at_the_top_orders(engine, name):
    plain, served, events, nonce, _ = asyncio.run(_both_orders(engine, CASES[name]))
    assert _ids_of(events, nonce) == _ids_of(events, "plain")
    assert served == plain
    chunks, finish, usage, error = served
    text = "".join(c for c, _ in chunks)
    if name == "eos":
        assert finish == "stop" and usage[1] == len(text) + 1 < 12
    elif name == "max_tokens_1":
        assert finish == "length" and usage[1] == len(text) == 1
    elif name == "length":
        assert finish == "length" and usage[1] == len(text) == 9
    elif name == "stop_across_two_tokens":
        # the match's first letter was held back, then dropped with it
        assert finish == "stop" and usage[1] == len(text) + 2
    elif name == "logprobs":
        assert [e[0] for _, entries in chunks for e in entries] == list(text)
        assert all(len(e[2]) == 3 for _, entries in chunks for e in entries)
    elif name == "stop_with_logprobs":
        # the letter held back for the stop that never completed came out
        # with the one that did, two tokens late; the matched letters and
        # their entries were dropped
        assert finish == "stop" and usage[1] == len(text) + 2
        assert [e[0] for _, entries in chunks for e in entries] == list(text)
    else:
        assert finish is None and error == "request deadline expired after 5 token(s)"
        assert len(text) == 5


# ---- (c) nothing is asked for behind a lane's last token -----------------------


@pytest.mark.parametrize("name", ["length", "eos", "stop_across_two_tokens"])
def test_no_ask_follows_a_lanes_last_token(engine, name):
    """The ask ahead is not a step ahead: a lane that stops at step s (the
    length, an end-of-sequence id, a stop string) sends nothing for s + 1,
    and the engine drops as many surplus steps as under the old order (the
    one it had chained behind a stop it could not know of, none at the
    length)."""
    plain, served, events, nonce, (plain_surplus, surplus) = asyncio.run(
        _both_orders(engine, CASES[name])
    )
    tokens = _ids_of(events, nonce)
    sends = [ev[2] for ev in events if ev[0] == "send" and ev[1] == nonce]
    assert sends == list(range(len(tokens)))
    assert surplus == plain_surplus == (0 if name == "length" else 1)


# ---- (d) an ask ahead that raises ----------------------------------------------


def test_an_ask_ahead_that_raises_takes_the_resume_path(engine, paged_env):
    """The send for step 3 leaves at the end of DECIDE on token 2 and dies
    in the transport: token 2 is delivered all the same, the error is raised
    at step 3's own top, inside the `try` that owns resume, and the replay
    carries the stream on: the client reads what it would have read."""
    paged_env.setenv("DNET_RESILIENCE_RESUME", "1")
    paged_env.setenv("DNET_RESILIENCE_RESUME_DEADLINE_S", "2.0")
    reset_settings_cache()
    events: list = []
    torn = []

    def on_send(nonce, step):
        if step == 3 and "#r" not in nonce and not torn and nonce != "plain":
            torn.append(nonce)
            events.append(("torn", nonce))
            raise ConnectionResetError("stream torn past retry budget")

    async def run():
        adapter = _spy_adapter(engine, events, on_send=on_send)
        await adapter.start()
        try:
            inference = _manager(adapter)
            plain = await _plain_driver(inference, adapter, _request("resume me"))
            resumed0 = metric("dnet_request_resumed_total").value
            served = await _served(inference, _request("resume me"))
            return plain, served, metric("dnet_request_resumed_total").value - resumed0
        finally:
            await adapter.shutdown()

    plain, served, resumed = asyncio.run(run())
    assert served == plain and served[1] == "length" and served[2][1] == 12
    assert resumed == 1 and len(torn) == 1
    torn_at = events.index(("torn", torn[0]))
    replay_at = events.index(("send", torn[0] + "#r1", 0))
    # nothing was sent twice: the only send between the two is none at all
    assert not [ev for ev in events[torn_at:replay_at] if ev[0] == "send"]
    asks = metric("dnet_api_driver_asks_total")
    sent = [ev for ev in events if ev[0] == "send" and ev[1].startswith(torn[0])]
    # every send that left is counted once; the replay is the resume
    # controller's own (resilience/checkpoint.py), the torn one never left
    assert asks.labels(order=DRIVER_ASK_AHEAD).value == 10
    assert asks.labels(order=DRIVER_ASK_AT_STEP).value == 1
    assert len(sent) == 10 + 1 + 1


# ---- (e) a client that goes away between ask and delivery ----------------------


def test_a_client_gone_between_ask_and_delivery_frees_its_lane(engine):
    """The generator is closed at a chunk's `yield`: the ask for the next
    step has left, its token will never be delivered.  The lane is reset
    once, its slot is back in the free list, and the server serves on."""
    events: list = []

    async def run():
        adapter = _spy_adapter(engine, events)
        await adapter.start()
        try:
            inference = _manager(adapter)
            gen = inference.generate_stream(_request("leave early", max_tokens=30))
            taken = [await gen.__anext__() for _ in range(3)]
            nonce = taken[0].id
            resets = events.count(("reset", nonce))
            await gen.aclose()
            # the driver's own generator is closed by the loop's finalizer
            # hook, a turn or two after the one the HTTP layer closes
            for _ in range(200):
                if events.count(("reset", nonce)) > resets:
                    break
                await asyncio.sleep(0.01)
            await asyncio.gather(*list(inference._cancel_cleanups))
            held = dict(engine.slot_of)
            free = sorted(engine._free)
            queued = adapter.queue.get(nonce)
            again = await _served(inference, _request("leave early", max_tokens=4))
            return nonce, resets, held, free, queued, again
        finally:
            await adapter.shutdown()

    nonce, resets, held, free, queued, again = asyncio.run(run())
    sends = [ev[2] for ev in events if ev[0] == "send" and ev[1] == nonce]
    tokens = _ids_of(events, nonce)
    assert sends == [0, 1, 2, 3] and len(tokens) == 3  # asked for a token it never took
    assert events.count(("reset", nonce)) == resets + 1
    assert nonce not in held and free == list(range(engine.slots)) and queued is None
    assert again[1] == "length" and again[2][1] == 4


# ---- (f) the counter -----------------------------------------------------------


@pytest.mark.parametrize("order", DRIVER_ASK_ORDERS)
def test_the_asks_are_exposed_at_zero_from_the_start(order):
    reset_obs()
    assert f'dnet_api_driver_asks_total{{order="{order}"}} 0' in get_registry().expose()


def test_ahead_and_at_step_add_up_to_the_sends(engine):
    """One count a `resume.send`: a request of n tokens asks once at its
    first step and n - 1 times ahead."""
    events: list = []

    async def run():
        adapter = _spy_adapter(engine, events)
        await adapter.start()
        try:
            inference = _manager(adapter)
            return await asyncio.gather(
                _served(inference, _request("one", max_tokens=7)),
                _served(inference, _request("other", max_tokens=1)),
                _served(inference, _request("third", max_tokens=10)),
            )
        finally:
            await adapter.shutdown()

    asyncio.run(run())
    asks = metric("dnet_api_driver_asks_total")
    ahead = asks.labels(order=DRIVER_ASK_AHEAD).value
    at_step = asks.labels(order=DRIVER_ASK_AT_STEP).value
    assert at_step == 3 and ahead == 6 + 0 + 9
    assert ahead + at_step == len([ev for ev in events if ev[0] == "send"])
