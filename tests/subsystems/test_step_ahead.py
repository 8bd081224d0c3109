"""One decode step ahead: the tick that reads step n has already enqueued
step n+1 from step n's tokens on the device (sched/step.py; core/batch.py:
decode_launch chain=, decode_read asked=).

- every lane's stream through the scheduler is the serial one, on each of
  the four stores, with joiners mid-stream, unequal answers and seeded
  sampling: only who supplies a step's input token changes;
- a lane that ends at a token the host had not read when the next step was
  enqueued (a stop id, a cancel) leaves a surplus step: its token reaches
  nobody, `dnet_decode_surplus_steps_total` counts it, and the lane's next
  owner starts clean;
- a driver whose turn was cut finds the token it was late for in the
  engine's buffer at its next ask: none lost, none doubled;
- an engine that speculates chains nothing and keeps the serial order.

The order of a tick's enqueues (chunks, adoptions, chained step, read) is
held on a recording engine in tests/subsystems/test_wire_pipeline.py
test_execute_tick_launches_every_chunk_before_the_decode_read.
"""

import asyncio

import numpy as np
import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import metric, reset_obs

pytestmark = pytest.mark.api

CHUNK = 8  # prefill chunk and kv block, tokens
STORES = ("one_kind", "two_kinds", "state", "hybrid")


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


@pytest.fixture(scope="module")
def checkpoints(tmp_path_factory):
    """A tiny checkpoint a store: llama (one kind of blocks), cohere2_moe
    (window blocks beside full ones, a window of 24), brumby (a state entry
    a lane), qwen3_next (a lane of state AND a page table)."""
    from benchmarks.harness.weights import write_checkpoint
    from tests.benchmarks.test_bench_cohere2_moe import tiny_config
    from tests.fakes import checkpoints as fakes

    dirs = {name: tmp_path_factory.mktemp(f"ahead_{name}") for name in STORES}
    fakes.make_tiny_llama(dirs["one_kind"])
    write_checkpoint(dirs["two_kinds"], tiny_config(), seed=2**31 + 28, dtype="float32")
    fakes.make_tiny_brumby(dirs["state"])
    fakes.make_tiny_qwen3_next(dirs["hybrid"])
    return dirs


def _engine(model_dir, slots=3, **kw):
    from dnet_tpu.core.batch import BatchedEngine

    return BatchedEngine(model_dir, slots=slots, max_seq=128, param_dtype="float32", **kw)


def _prompt(nonce: str, n: int):
    return [256] + [1 + (ord(nonce[0]) * 7 + 3 * j) % 250 for j in range(n - 1)]


def _decoding(nonce: str) -> DecodingParams:
    return DecodingParams(temperature=0.7, top_p=0.9, seed=ord(nonce[0]), logprobs=True)


def _alone(eng, nonce, plen, ask, decoding=_decoding):
    """The request by itself through `decode_batch`, one step a call and
    each read before the next is asked for: [(token, logprob)]."""
    dec = decoding(nonce)
    res = eng.prefill_and_sample(nonce, _prompt(nonce, plen), dec)
    got = [(int(res.token[0]), float(res.logprob[0]))]
    while len(got) < ask:
        out, errs = eng.decode_batch({nonce: (got[-1][0], dec)})
        assert not errs, errs
        got.append((int(out[nonce].token[0]), float(out[nonce].logprob[0])))
    eng.end_session(nonce)
    return got


async def _client(adapter, got, nonce, plen, ask, *, after=None, hold=None,
                  stop_after=None, cancel_after=None, decoding=_decoding):
    """One driver: the API's own loop (send, await, echo), starting once
    `after` = (nonce, tokens) has been reached by another stream.
    `stop_after`: it ends there although its budget said more, the way a
    stop id ends a request (the host learns of it at the read);
    `cancel_after`: it asks for the next token first, and leaves the ask
    outstanding (a client that went away)."""
    while after is not None and len(got.get(after[0], ())) < after[1]:
        await asyncio.sleep(0.001)
    dec = decoding(nonce)
    send = _prompt(nonce, plen)
    got[nonce] = []
    for step in range(ask):
        await adapter.send_tokens(nonce, send, dec, step, budget=ask - step)
        res = await adapter.await_token(nonce, step, 120.0)
        assert not res.error, res.error
        got[nonce].append((res.token_id, res.logprob))
        send = [res.token_id]
        if len(got[nonce]) == stop_after:
            break
        if len(got[nonce]) == cancel_after:
            await adapter.send_tokens(nonce, send, dec, step + 1, budget=ask - step - 1)
            break
        if hold is not None and step < ask - 1:
            await hold(nonce, len(got[nonce]))
    await adapter.reset_cache(nonce)


async def _serve(eng, clients, beside=None):
    from dnet_tpu.sched.engine import SchedulerAdapter

    adapter = SchedulerAdapter(eng, token_budget=64, prefill_chunk=CHUNK)
    await adapter.start()
    got: dict = {}
    try:
        jobs = [_client(adapter, got, *c[:3], **(c[3] if len(c) > 3 else {})) for c in clients]
        if beside is not None:
            jobs.append(beside(adapter, got))
        await asyncio.gather(*jobs)
    finally:
        await adapter.shutdown()
    return got


def _same(got, want):
    assert [t for t, _ in got] == [t for t, _ in want]
    np.testing.assert_allclose([lp for _, lp in got], [lp for _, lp in want], atol=2e-5)


def _dispatches() -> int:
    return int(metric("dnet_decode_dispatch_total").value)


# ---- (a) the streams are the serial ones, on every store ---------------------


@pytest.mark.parametrize("store", STORES)
def test_every_lanes_stream_is_the_serial_one(checkpoints, paged_env, store):
    """Five requests on three lanes, answers of 5 to 21 tokens, prompts of
    one to five chunks (past the two-kind model's window of 24), two of
    them joining when a lane frees: tokens and log-probabilities equal the
    request stepped alone, read before every ask."""
    from dnet_tpu.kv import HybridStore, KindStore, StateStore

    asks = {"a": (8, 21), "b": (40, 5), "c": (19, 16), "d": (27, 9), "e": (8, 12)}
    eng = _engine(checkpoints[store])
    try:
        assert isinstance(eng.kv_store, {
            "one_kind": KindStore, "two_kinds": KindStore,
            "state": StateStore, "hybrid": HybridStore,
        }[store])
        assert bool(eng._window) is (store == "two_kinds")
        want = {n: _alone(eng, n, *asks[n]) for n in asks}
        reset_obs()
        got = asyncio.run(_serve(eng, [
            ("a", *asks["a"]), ("b", *asks["b"]), ("c", *asks["c"]),
            ("d", *asks["d"], {"after": ("b", 3)}), ("e", *asks["e"], {"after": ("c", 9)}),
        ]))
    finally:
        eng.close()
    for n in asks:
        _same(got[n], want[n])
    assert _dispatches() > 0
    lane_steps = metric("dnet_decode_lane_steps_total").value
    assert lane_steps == sum(ask - 1 for _, ask in asks.values())  # no surplus, none twice
    # every step but a lane's first (and a late driver's next) is chained
    assert metric("dnet_decode_chained_lanes_total").value >= lane_steps - len(asks) - 6
    assert metric("dnet_decode_surplus_steps_total").value == 0
    assert metric("dnet_decode_buffer_dropped_total").value == 0


# ---- (b) a lane that ends with a step in flight ------------------------------


@pytest.mark.parametrize("how", ["stop_id", "cancel"])
def test_a_surplus_step_reaches_nobody_and_the_lane_starts_clean(
    tiny_llama_dir, paged_env, how
):
    """`x` ends at its fourth token although its budget said twelve (a stop
    id), or is cancelled with its ask for the fifth outstanding: either way
    step n+1 was chained for it before the host knew.  The read drops that
    token and counts it; `keep` beside it is not disturbed; `z`, which
    takes the lane `x` freed, decodes as it does alone."""
    asks = {"keep": (8, 30), "x": (16, 12), "z": (11, 8)}
    eng = _engine(tiny_llama_dir, slots=2)
    try:
        want = {n: _alone(eng, n, *asks[n]) for n in asks}
        reset_obs()

        async def z_after_x(adapter, got):
            while "x" in eng.slot_of or len(got.get("x", ())) < 4:
                await asyncio.sleep(0.0005)
            await _client(adapter, got, "z", *asks["z"])

        end = {"stop_after": 4} if how == "stop_id" else {"cancel_after": 4}
        clients = [("keep", *asks["keep"]), ("x", *asks["x"], end)]
        got = asyncio.run(_serve(eng, clients, z_after_x))
    finally:
        eng.close()
    assert len(got["x"]) == 4  # nothing after the token it ended at
    _same(got["x"], want["x"][:4])
    _same(got["keep"], want["keep"])
    _same(got["z"], want["z"])  # the freed lane's next owner
    assert metric("dnet_decode_surplus_steps_total").value == 1
    tok = metric("dnet_decode_tokens_total")
    delivered = tok.labels(source="dispatch").value + tok.labels(source="buffer").value
    assert delivered == 29 + 3 + 7
    # the surplus step was computed (a lane-step) and handed to nobody
    assert metric("dnet_decode_lane_steps_total").value == delivered + 1


# ---- (c) a late driver -------------------------------------------------------


def test_a_late_driver_gets_the_held_token_at_its_next_ask(tiny_llama_dir, paged_env, monkeypatch):
    """`b` sits on every token past the drivers' turn (20 ms here) while
    `a` keeps the ticks coming: the step `b` had in flight is read without
    it, its token waits in the buffer and answers `b`'s next ask, and `b`
    steps on from it in the same tick.  Both streams are the serial ones:
    no token lost, none doubled."""
    from dnet_tpu.sched import engine as sched_engine

    monkeypatch.setattr(sched_engine, "DRIVER_TURN_S", 0.02)
    asks = {"a": (8, 60), "b": (16, 7)}
    eng = _engine(tiny_llama_dir, slots=2)
    try:
        want = {n: _alone(eng, n, *asks[n]) for n in asks}
        reset_obs()
        moved = asyncio.Condition()
        seen = {"a": 0}

        async def note_a(nonce, tokens):
            async with moved:
                seen["a"] = tokens
                moved.notify_all()

        async def hold_b(nonce, tokens):
            async with moved:
                then = seen["a"]
                await moved.wait_for(lambda: seen["a"] >= then + 3)

        async def run():
            moved.__init__()  # bound to this loop
            return await _serve(eng, [
                ("a", *asks["a"], {"hold": note_a}), ("b", *asks["b"], {"hold": hold_b}),
            ])

        got = asyncio.run(run())
    finally:
        eng.close()
    _same(got["a"], want["a"])
    _same(got["b"], want["b"])
    tok = metric("dnet_decode_tokens_total")
    held = tok.labels(source="buffer").value
    assert held >= 3  # most of b's six decode tokens waited for it
    assert tok.labels(source="dispatch").value + held == 59 + 6
    assert metric("dnet_decode_lane_steps_total").value == 59 + 6
    assert metric("dnet_decode_surplus_steps_total").value == 0
    assert metric("dnet_decode_buffer_dropped_total").value == 0
    assert metric("dnet_sched_drivers_turn_total").labels(outcome="timed_out").value >= 3


# ---- (e) an engine that speculates does not chain ----------------------------


def test_a_tick_of_an_engine_that_speculates_keeps_the_serial_order():
    """`spec_lookahead > 0`: the launch half reads the device, so the step
    goes first, is read in the same tick and leaves nothing in flight; its
    budgets (which lanes may verify a drafted block) ride along whether a
    chunk is in the plan or not: they widen no dispatch."""
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick
    from tests.subsystems.test_sched import FakeStepEngine, _chunk

    calls = []
    eng = FakeStepEngine()
    eng.spec_lookahead = 2
    eng.occupy("dec", committed=4, blocks=1)
    launch, read, prefill = eng.decode_launch, eng.decode_read, eng.prefill_chunk

    def decode_launch(requests, budgets=None, chain=None):
        calls.append(("launch", budgets, chain))
        return launch(requests, budgets=budgets, chain=chain)

    def decode_read(flight, **kw):
        calls.append(("read", sorted(flight.order), kw))
        return read(flight, **kw)

    eng.decode_launch, eng.decode_read = decode_launch, decode_read
    eng.prefill_chunk = lambda *a, **k: calls.append(("prefill",)) or prefill(*a, **k)
    plan = TickPlan()
    plan.decode = {"dec": (42, DecodingParams())}
    plan.steps = {"dec": 3}
    plan.budgets = {"dec": 9}
    res = execute_tick(eng, plan)
    assert calls == [("launch", {"dec": 9}, None), ("read", ["dec"], {"asked": None})]
    assert set(res.decode_results) == {"dec"} and res.flight is None
    del calls[:]
    plan.prefills = [_chunk("new", last=False)]
    res = execute_tick(eng, plan, follows=res)
    assert calls == [
        ("launch", {"dec": 9}, None), ("prefill",), ("read", ["dec"], {"asked": None}),
    ]
    assert set(res.decode_results) == {"dec"} and res.flight is None


def test_a_served_engine_that_speculates_chains_nothing(tiny_llama_dir, paged_env):
    """The same through the adapter: greedy streams as without speculation,
    and not one lane chained."""
    asks = {"a": (8, 14), "b": (16, 9)}

    def greedy(nonce):
        return DecodingParams(temperature=0.0)

    plain = _engine(tiny_llama_dir, slots=2, kv_paged=False)
    try:
        want = {n: _alone(plain, n, *asks[n], decoding=greedy) for n in asks}
    finally:
        plain.close()
    eng = _engine(tiny_llama_dir, slots=2, spec_lookahead=2)
    try:
        if eng.spec_lookahead <= 0:
            pytest.skip("model cache layout refuses speculation")
        reset_obs()
        got = asyncio.run(_serve(eng, [(n, *asks[n], {"decoding": greedy}) for n in asks]))
    finally:
        eng.close()
    for n in asks:
        assert [t for t, _ in got[n]] == [t for t, _ in want[n]]
    assert metric("dnet_decode_chained_lanes_total").value == 0
    assert metric("dnet_decode_surplus_steps_total").value == 0
