"""The turn-around between two ticks, from inside the program.

Through the real SchedulerAdapter loop on the CPU, tiny engine, drivers that
are the API's own loop (send, await, echo):

- `dnet_sched_turnaround_ms{device=}`: the end of tick n to tick n+1's first
  device program enqueued, on the compute thread, and its segments as
  declared host spans (obs/phases.py): `dnet.turn.to_loop`, `dnet.sched.turn`
  (> `dnet.sched.apply`, `dnet.sched.drivers_turn`, `dnet.sched.plan`),
  `dnet.turn.to_thread`, then `dnet.decode.prepare` and `.launch`;
- what the turn leaves out: `dnet_sched_lanes_left_out_total`,
  `dnet_sched_drivers_turn_total{outcome=}`, `dnet_sched_answer_wait_ms`;
- the two spans held across awaits land in a profile's host plane with the
  durations the histogram saw, whatever else runs on the loop meanwhile.
"""

import asyncio

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import get_registry, metric, reset_obs, span
from dnet_tpu.obs.phases import (
    DRIVERS_TURN_OUTCOMES,
    HOST_SPANS,
    SPAN_SCHED_DRIVERS_TURN,
    SPAN_SCHED_TURN,
    SPAN_TURN_TO_LOOP,
    SPAN_TURN_TO_THREAD,
    TURN_DEVICE,
)

pytestmark = pytest.mark.api

CHUNK = 8  # prefill chunk and kv block, tokens
TURN_SPANS = (
    SPAN_SCHED_TURN, SPAN_SCHED_DRIVERS_TURN, SPAN_TURN_TO_LOOP, SPAN_TURN_TO_THREAD,
)


@pytest.fixture
def paged_env(monkeypatch):
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(CHUNK))
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    reset_settings_cache()
    reset_obs()
    yield monkeypatch
    monkeypatch.undo()
    reset_settings_cache()
    reset_obs()


@pytest.fixture
def engine(tiny_llama_dir, paged_env):
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(tiny_llama_dir, slots=4, max_seq=128, param_dtype="float32")
    assert eng.kv_pool is not None
    yield eng
    eng.close()


@pytest.fixture
def wide_turn(monkeypatch):
    """The drivers' turn at 20 ms instead of 2: a prompt driver on a loaded
    machine still answers inside it, so only a driver the test holds back
    is ever cut."""
    from dnet_tpu.sched import engine as sched_engine

    monkeypatch.setattr(sched_engine, "DRIVER_TURN_S", 0.02)


def _prompt(nonce: str, n: int = CHUNK):
    return [256] + [1 + (ord(nonce[0]) * 7 + 3 * j) % 250 for j in range(n - 1)]


class Gate:
    """Where the drivers of a test meet: `arrive` returns once `n` of them
    have; the last one in runs `then` first (a snapshot of the books, taken
    while every driver owes the scheduler an answer and no tick runs)."""

    def __init__(self, n, then=None):
        self.n, self.then, self.here, self.open = n, then, 0, asyncio.Event()

    async def arrive(self):
        self.here += 1
        if self.here == self.n:
            if self.then is not None:
                self.then()
            self.open.set()
        await self.open.wait()


async def _client(adapter, got, nonce, ask, gates=None, hold=None, plen=CHUNK):
    """One driver, with the budget the API's loop gives (the tokens it will
    still take): every lane is chained a step ahead until its last token.
    `gates` = {tokens received: Gate}; `hold(nonce, tokens)` is awaited
    after each token, before the echo."""
    dec = DecodingParams(temperature=0.0)
    send = _prompt(nonce, plen)
    got[nonce] = []
    for step in range(ask):
        await adapter.send_tokens(nonce, send, dec, step, budget=ask - step)
        res = await adapter.await_token(nonce, step, 120.0)
        assert not res.error, res.error
        got[nonce].append(res.token_id)
        send = [res.token_id]
        if gates and len(got[nonce]) in gates:
            await gates[len(got[nonce])].arrive()
        if hold is not None and step < ask - 1:
            await hold(nonce, len(got[nonce]))
    await adapter.reset_cache(nonce)


async def _serve(eng, clients, beside=None):
    """`clients` = [(nonce, ask, gates, hold[, plen])] through one adapter;
    `beside` is a coroutine function run on the same loop meanwhile."""
    from dnet_tpu.sched.engine import SchedulerAdapter

    adapter = SchedulerAdapter(eng, token_budget=64, prefill_chunk=CHUNK)
    await adapter.start()
    got: dict = {}
    other = asyncio.ensure_future(beside()) if beside is not None else None
    try:
        await asyncio.gather(*(_client(adapter, got, *c) for c in clients))
        await asyncio.sleep(0.02)  # the loop finds nothing to do, and parks
    finally:
        if other is not None:
            other.cancel()
            await asyncio.gather(other, return_exceptions=True)
        await adapter.shutdown()
    return got


def _books() -> dict:
    """(count, sum) of every span and of the turn-around's own families."""
    spans = metric("dnet_span_ms")
    out = {n: (spans.labels(span=n).count, spans.labels(span=n).sum) for n in HOST_SPANS}
    turn = metric("dnet_sched_turnaround_ms")
    for d in TURN_DEVICE:
        out[d] = (turn.labels(device=d).count, turn.labels(device=d).sum)
    wait = metric("dnet_sched_answer_wait_ms")
    out["answer_wait"] = (wait.count, wait.sum)
    out["left_out"] = (metric("dnet_sched_lanes_left_out_total").value, 0.0)
    for o in DRIVERS_TURN_OUTCOMES:
        out[o] = (metric("dnet_sched_drivers_turn_total").labels(outcome=o).value, 0.0)
    return out


def _moved(before: dict, after: dict = None) -> dict:
    after = after or _books()
    return {k: (after[k][0] - before[k][0], after[k][1] - before[k][1]) for k in after}


# ---- (f) declared, exposed at zero ------------------------------------------


@pytest.mark.parametrize("name", TURN_SPANS)
def test_the_turn_spans_are_declared_and_exposed_from_the_start(name):
    """tests/test_obs_span.py's exposure test is parametrised over
    HOST_SPANS, so it holds these four without being told: they are in it."""
    assert name in HOST_SPANS
    assert f'dnet_span_ms_count{{span="{name}"}}' in get_registry().expose()


@pytest.mark.parametrize(
    "series",
    [f'dnet_sched_turnaround_ms_count{{device="{d}"}}' for d in TURN_DEVICE]
    + [f'dnet_sched_drivers_turn_total{{outcome="{o}"}}' for o in DRIVERS_TURN_OUTCOMES]
    + ["dnet_sched_lanes_left_out_total", "dnet_sched_answer_wait_ms_count"],
)
def test_the_turn_families_are_exposed_at_zero_from_the_start(series):
    reset_obs()
    assert f"{series} 0" in get_registry().expose()


def test_the_orphan_families_are_gone():
    text = get_registry().expose()
    assert "dnet_transport_tx_frames_total" not in text
    assert "dnet_kv_sessions_evicted_total" not in text
    assert "dnet_request_errors_total" in text  # what an operator alerts on: kept


# ---- (a) the segments add up -------------------------------------------------


def test_the_segments_add_up_to_the_turn_and_to_the_turnaround(engine, wide_turn):
    """Three lanes, twenty decode-only ticks between two gates (the books
    are read while every driver owes an answer and no tick runs; a step is
    in flight all the while): apply + drivers_turn + plan is
    dnet.sched.turn, and to_loop + turn + to_thread + prepare + launch is
    the turn-around, within 10 % or 0.3 ms a turn.  None of them is
    drained: the step the tick before enqueued runs through each."""
    snaps = []
    names = ("a", "b", "c")
    first = Gate(len(names), lambda: snaps.append(_books()))
    last = Gate(len(names), lambda: snaps.append(_books()))
    asyncio.run(_serve(engine, [(n, 26, {4: first, 24: last}, None) for n in names]))
    m = _moved(*snaps)
    n = m["busy"][0]
    assert n == 20 and m["drained"][0] == 0  # every tick between the gates: a step sent, a step read
    assert m["dnet.tick"][0] == m[SPAN_SCHED_TURN][0] == m[SPAN_TURN_TO_THREAD][0] == n
    assert m["dnet.decode.launch"][0] == m["dnet.decode.prepare"][0] == n
    assert m["dnet.prefill.launch"][0] == 0
    # a decode-only tick hands every lane its token in _apply: drivers are owed
    assert m[SPAN_SCHED_DRIVERS_TURN][0] == n
    assert m["answered"][0] == n and m["timed_out"][0] == 0 and m["left_out"][0] == 0

    def close(a, b):
        return abs(a - b) <= max(0.1 * b, 0.3 * n)

    turn_ms = m[SPAN_SCHED_TURN][1]
    parts = sum(m[s][1] for s in ("dnet.sched.apply", SPAN_SCHED_DRIVERS_TURN, "dnet.sched.plan"))
    assert parts <= turn_ms + 0.3 and close(parts, turn_ms), (parts, turn_ms)
    whole = m["busy"][1]
    segments = sum(m[s][1] for s in (
        SPAN_TURN_TO_LOOP, SPAN_SCHED_TURN, SPAN_TURN_TO_THREAD,
        "dnet.decode.prepare", "dnet.decode.launch",
    ))
    assert close(segments, whole), (segments, whole, {k: v for k, v in m.items() if v[0]})
    # and the tick's wall time as the loop sees it holds both hops
    assert m[SPAN_TURN_TO_LOOP][1] + m[SPAN_TURN_TO_THREAD][1] < metric("dnet_sched_tick_ms").sum


# ---- (b) a driver held back, and a prompt one --------------------------------


def test_a_driver_held_back_is_left_out_and_cuts_the_turn(engine, wide_turn):
    """Driver `b` sits on each token until `a` has been given two more
    (longer than the turn's bound, 20 ms here: `a` only gets them from the
    ticks the turn is holding up): the turn after the tick that handed `b`
    its token is cut by the bound, the next plans' steps run without `b`,
    and the step `b` had in flight is read into its buffer, where its next
    ask finds the token.  One cut a token of `b`'s, and none for `a`."""
    moved = asyncio.Condition()
    seen = {"a": 0}

    async def note_a(nonce, tokens):
        async with moved:
            seen["a"] = tokens
            moved.notify_all()

    async def hold_b(nonce, tokens):
        async with moved:
            then = seen["a"]
            await moved.wait_for(lambda: seen["a"] >= then + 2)

    async def run():
        moved.__init__()  # bound to this loop
        return await _serve(engine, [("a", 40, None, note_a), ("b", 7, None, hold_b)])

    before = _books()
    got = asyncio.run(run())
    assert (len(got["a"]), len(got["b"])) == (40, 7)
    m = _moved(before)
    held = 6  # b's tokens that were followed by another ask
    assert m["timed_out"][0] == held
    assert m["left_out"][0] >= held  # the plans that went without b
    # the turn waited its whole bound for b each time, and b's way back
    # shows in the answer wait: six of them took longer than the bound
    assert m[SPAN_SCHED_DRIVERS_TURN][1] >= held * 20.0
    assert m["answer_wait"][1] >= held * 20.0
    assert m["answer_wait"][0] == 39 + 6
    tok = metric("dnet_decode_tokens_total")
    assert tok.labels(source="buffer").value >= 1  # a token that waited for b
    assert tok.labels(source="dispatch").value + tok.labels(source="buffer").value == 39 + 6


def test_prompt_drivers_are_neither_left_out_nor_cut(engine, wide_turn):
    before = _books()
    asyncio.run(_serve(engine, [(n, 9, None, None) for n in ("a", "b", "c")]))
    m = _moved(before)
    assert m["left_out"][0] == 0 and m["timed_out"][0] == 0
    assert m["answered"][0] >= 8  # every decode tick's turn, at least
    assert m["dnet.decode.launch"][0] == 8  # in phase: one step a tick for all three


# ---- (c) a parked server ----------------------------------------------------


def test_a_server_parked_with_nothing_to_do_observes_no_turnaround(engine):
    """Two requests, one after the other with the server parked in between:
    every tick but each request's first follows a tick, and the seconds of
    the park are in no histogram."""
    from dnet_tpu.sched.engine import SchedulerAdapter

    park_s = 0.4

    async def run():
        adapter = SchedulerAdapter(engine, token_budget=64, prefill_chunk=CHUNK)
        await adapter.start()
        got: dict = {}
        try:
            await _client(adapter, got, "w", 3)  # warm: every compile
            await asyncio.sleep(0.05)  # parked
            reset_obs()
            await _client(adapter, got, "a", 5)
            await asyncio.sleep(park_s)
            await _client(adapter, got, "b", 5)
            await asyncio.sleep(0.05)
        finally:
            await adapter.shutdown()

    asyncio.run(run())
    m = _moved({k: (0, 0.0) for k in _books()})
    ticks = m["dnet.tick"][0]
    # a prefill tick, a tick that sends step 1, three that send a step and
    # read one, a tick that reads the last: twice
    assert ticks == 2 * 6
    # a turn-around ends at an enqueue: none for a request's first tick
    # (it follows the park) nor for its last (it enqueues nothing)
    assert m["drained"][0] + m["busy"][0] == ticks - 4
    assert m["drained"][0] == 2  # the tick after a lone prompt's adoption was read
    assert m["busy"][0] == 2 * 3  # every other follows a step in flight
    assert m["drained"][1] + m["busy"][1] < park_s * 1000.0 / 2
    # the loop's share ends where it parks, and the hops are per tick
    assert m[SPAN_SCHED_TURN][0] == m[SPAN_TURN_TO_LOOP][0] == m[SPAN_TURN_TO_THREAD][0] == ticks
    assert m[SPAN_SCHED_TURN][1] < park_s * 1000.0 / 2


def test_a_tick_that_leaves_a_step_in_flight_is_followed_busy(engine):
    """A prompt of three chunks beside a decoding lane: every tick leaves a
    step in flight (and two of them a chunk as well), so every turn-around
    until the lane's last token finds the device busy."""
    got: dict = {}

    async def run():
        from dnet_tpu.sched.engine import SchedulerAdapter

        adapter = SchedulerAdapter(engine, token_budget=64, prefill_chunk=CHUNK)
        await adapter.start()
        try:
            await _client(adapter, got, "w", 3)
            await asyncio.gather(_client(adapter, got, "a", 12), late_prompt(adapter))
        finally:
            await adapter.shutdown()

    async def late_prompt(adapter):
        while len(got.get("a", ())) < 3:  # a decodes alone first
            await asyncio.sleep(0.001)
        before.update(_books())
        await _client(adapter, got, "p", 2, plen=3 * CHUNK)

    before: dict = {}
    asyncio.run(run())
    m = _moved(before)
    assert m["dnet.prefill.launch"][0] == 3 and m["dnet.prefill.adopt"][0] == 1
    assert m["busy"][0] >= 7 and m["drained"][0] == 0


def test_every_tick_but_the_last_enqueues_a_step_and_follows_one(engine):
    """Two lanes in phase, 18 tokens each: nothing is answered from a
    buffer, so every tick between the prompts' and the last
    enqueues one step; each of those turn-arounds but the first (it follows
    the adoptions' read) finds the step before still in flight, and their
    sum is the loop's share and the hops of the ticks between."""
    before = _books()
    asyncio.run(_serve(engine, [(n, 18, None, None) for n in ("a", "b")]))
    m = _moved(before)
    ticks, launches = m["dnet.tick"][0], m["dnet.decode.launch"][0]
    assert launches == 17 == metric("dnet_decode_dispatch_total").value
    assert launches + 2 <= ticks <= launches + 3  # the prompts' tick(s), the last read
    assert m["drained"][0] + m["busy"][0] == ticks - 2
    assert m["busy"][0] == launches - 1
    whole = m["drained"][1] + m["busy"][1]
    every_tick = sum(m[s][1] for s in (SPAN_TURN_TO_LOOP, SPAN_SCHED_TURN, SPAN_TURN_TO_THREAD))
    assert whole >= 0.8 * every_tick - 0.3 * ticks, (whole, every_tick)
    assert whole <= every_tick + m["dnet.tick"][1]


# ---- (d) one answer wait a decode token --------------------------------------


def test_answer_wait_counts_one_a_decode_token(engine):
    before = _books()
    asks = {"a": 9, "b": 5, "c": 7}
    got = asyncio.run(_serve(engine, [(n, k, None, None) for n, k in asks.items()]))
    assert {n: len(t) for n, t in got.items()} == asks
    m = _moved(before)
    decode_tokens = sum(k - 1 for k in asks.values())
    assert m["answer_wait"][0] == decode_tokens
    delivered = metric("dnet_decode_tokens_total")
    assert delivered.labels(source="dispatch").value == decode_tokens
    assert m["answer_wait"][1] > 0.0


# ---- (e) one clock with the device trace -------------------------------------


def test_the_held_spans_land_in_a_profile_with_the_histograms_durations(
    engine, tmp_path, monkeypatch
):
    """`dnet.sched.turn` and `dnet.sched.drivers_turn` are held across
    awaits while another coroutine opens and closes spans on the same
    thread: what benchmarks/harness/xplane.py load() returns under `host`
    holds each of them once an observation, for as long as the histogram
    saw, a turn around its `dnet.sched.apply` and its drivers' turn.

    Held to what a loaded machine keeps.  A span stamps its profiler event
    and its host clock in two calls each end, and the thread can lose the
    processor between them: the event is then longer than the observation
    by a scheduler's slice, on any one span.  So the two are paired by
    order and count, every event must ENCLOSE its observation, and three
    pairs of four must agree to a twentieth of the span; nothing is held
    to a sum or to a fixed half millisecond."""
    import jax

    from benchmarks.harness import xplane
    from dnet_tpu.obs.metrics import _HistogramChild

    observed: dict = {}  # histogram child -> its observations, in order
    observe_n = _HistogramChild.observe_n

    def recording(self, v, n):
        observed.setdefault(id(self), []).append(v)
        observe_n(self, v, n)

    monkeypatch.setattr(_HistogramChild, "observe_n", recording)
    inside = []

    async def beside():
        while True:  # an SSE writer, say: opens and closes on the loop thread
            with span("dnet.api.sse_flush") as s:
                pass
            inside.append(s)
            await asyncio.sleep(0.0003)

    async def hold(nonce, tokens):
        await asyncio.sleep(0.003)  # past the bound: the turn waits it out

    async def run():
        from dnet_tpu.sched.engine import SchedulerAdapter

        adapter = SchedulerAdapter(engine, token_budget=64, prefill_chunk=CHUNK)
        await adapter.start()
        got: dict = {}
        try:
            await _client(adapter, got, "w", 3)  # warm, and parked again
        finally:
            await adapter.shutdown()
        reset_obs()
        observed.clear()
        opts = jax.profiler.ProfileOptions()  # as benchmarks/run.py takes its slice
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            await _serve(engine, [(n, 8, None, hold) for n in ("a", "b")], beside)
        finally:
            jax.profiler.stop_trace()

    asyncio.run(run())
    host = xplane.load(xplane.find_xplane(tmp_path))["host"]
    by_name: dict = {}
    for name, start, dur in host:
        if name.startswith("dnet."):
            by_name.setdefault(name, []).append((start, dur))
    spans = metric("dnet_span_ms")
    for name in (SPAN_SCHED_TURN, SPAN_SCHED_DRIVERS_TURN, "dnet.sched.apply"):
        child = spans.labels(span=name)
        events = sorted(by_name.get(name, []))  # one thread, one at a time: by start
        seen = observed.get(id(child), [])
        assert len(events) == len(seen) == child.count > 0, (name, len(events), child.count)
        close = 0
        for (_, dur), ms in zip(events, seen):
            prof_ms = dur / 1e6
            assert prof_ms >= ms - 0.05, (name, prof_ms, ms)
            close += prof_ms - ms <= max(0.1, 0.05 * ms)
        assert 4 * close >= 3 * len(seen), (name, close, len(seen))
    assert len(inside) > 20 and len(by_name["dnet.api.sse_flush"]) == len(inside)
    turns = sorted(by_name[SPAN_SCHED_TURN])
    applies = sorted(by_name["dnet.sched.apply"])
    slack = 1_000  # ns: two stamps of one clock

    def holds(t0, td, x0, xd):
        return t0 <= x0 + slack and x0 + xd <= t0 + td + slack

    # every turn holds ONE apply, and every drivers' turn lies in one turn,
    # behind that turn's apply: by containment and order, not by distance
    apply_end = {}
    for t0, td in turns:
        inside_turn = [(a0, ad) for a0, ad in applies if holds(t0, td, a0, ad)]
        assert len(inside_turn) == 1, (t0, td, inside_turn)
        apply_end[t0] = sum(inside_turn[0])
    for d0, dd in by_name[SPAN_SCHED_DRIVERS_TURN]:
        around = [t0 for t0, td in turns if holds(t0, td, d0, dd)]
        assert len(around) == 1 and apply_end[around[0]] <= d0 + slack, (d0, dd, around)
    # the other coroutine's spans opened and closed INSIDE the waits
    waits = by_name[SPAN_SCHED_DRIVERS_TURN]
    within = sum(
        any(w0 < s0 and s0 + sd < w0 + wd for w0, wd in waits)
        for s0, sd in by_name["dnet.api.sse_flush"]
    )
    assert within >= 5
