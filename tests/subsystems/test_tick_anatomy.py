"""Tick anatomy and request waits from inside the program.

Three layers, all always on and unfenced:

- `decode_batch`'s counters (core/batch.py): dispatches (one step each),
  slot-steps the device computed, lane-steps active lanes asked for,
  tokens the driver received by source.  A buffer hit is a late driver's
  token (or a verify block's later rows); the served path dispatches one
  step over all lanes, a step ahead of the one it reads
  (tests/subsystems/test_decode_phase.py), a lone stream too;
- the scheduler's stamps on SchedRequest (sched/engine.py): queue wait,
  prefill wall time and ticks, decode deliver wait, and the recorder's
  `sched_queue` / `prefill` spans that make a request's segment ledger add
  up to its time to first token under the scheduler;
- the host-span tree (obs/phases.py HOST_SPANS): children sum to no more
  than their parent.
"""

import asyncio

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import get_recorder, metric, reset_obs
from dnet_tpu.obs.critical_path import decompose
from dnet_tpu.obs.phases import DECODE_CHILD_SPANS
from dnet_tpu.sched.flight import get_tick_recorder

pytestmark = pytest.mark.api


def _counters():
    tok = metric("dnet_decode_tokens_total")
    out = {
        "sent": metric("dnet_decode_dispatch_total").value,
        "slot_steps": metric("dnet_decode_slot_steps_total").value,
        "lane_steps": metric("dnet_decode_lane_steps_total").value,
        "dropped": metric("dnet_decode_buffer_dropped_total").value,
    }
    out.update({s: tok.labels(source=s).value for s in ("dispatch", "buffer", "spec")})
    return out


def _moved(before):
    return {k: int(v - before[k]) for k, v in _counters().items() if v != before[k]}


@pytest.fixture
def paged_env(monkeypatch):
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    reset_settings_cache()
    yield monkeypatch
    monkeypatch.undo()
    reset_settings_cache()


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_decode_batch_counts_what_the_dispatch_did(tiny_llama_dir, paged_env, kv):
    """Four budgeted calls for 2 lanes on 4 slots (a dispatch each, one
    step wide), then a step read while one driver is late (its token waits
    in the buffer and answers its next ask with no device work), then a
    session that ends with a token still buffered: the counters say exactly
    that, on every KV path."""
    from dnet_tpu.core.batch import BatchedEngine, DecodeFlight

    eng = BatchedEngine(
        tiny_llama_dir, slots=4, max_seq=64, param_dtype="float32",
        kv_paged=None if kv == "paged" else False,
    )
    try:
        assert (eng.kv_pool is not None) is (kv == "paged")
        dec = DecodingParams(temperature=0.0)
        last = {}
        for n, ids in (("a", [256, 72, 101]), ("b", [256, 84, 104, 105])):
            last[n] = int(eng.prefill_and_sample(n, ids, dec).token[0])
        before = _counters()
        reqs = {n: (t, dec) for n, t in last.items()}
        for k in range(4):  # a budget never widens a dispatch
            out, errs = eng.decode_batch(reqs, budgets={"a": 5 - k, "b": 7 - k})
            assert not errs and set(out) == {"a", "b"}
            reqs = {n: (int(out[n].token[0]), dec) for n in out}
        # the device computed a step for every slot, two lanes asked for it
        m = _moved(before)
        assert m == {"sent": 4, "slot_steps": 4 * 4, "lane_steps": 4 * 2, "dispatch": 8}
        # useful over attempted: delivered tokens over slot-steps
        assert m["dispatch"] / m["slot_steps"] == 0.5
        # the served path's two halves: b's driver is late for this step
        flight = eng.decode_launch(reqs, chain=DecodeFlight())
        got, errs = eng.decode_read(flight, asked={"a"})
        assert not errs and set(got) == {"a"}
        assert _moved(before) == {
            "sent": 5, "slot_steps": 20, "lane_steps": 10, "dispatch": 9,
        }
        # its next ask finds the token: no device work
        out, errs = eng.decode_batch({"b": reqs["b"]})
        assert not errs and set(out) == {"b"}
        assert _moved(before) == {
            "sent": 5, "slot_steps": 20, "lane_steps": 10, "dispatch": 9, "buffer": 1,
        }
        # lane "a" alone
        _, errs = eng.decode_batch({"a": (int(got["a"].token[0]), dec)})
        assert not errs
        # a late driver's token that is never collected: dropped
        flight = eng.decode_launch({"b": (int(out["b"].token[0]), dec)}, chain=DecodeFlight())
        assert eng.decode_read(flight, asked=()) == ({}, {})
        eng.end_session("b")
        m = _moved(before)
        assert m["dropped"] == 1 and m["sent"] == 7
        assert m["slot_steps"] == 4 * m["sent"] and m["lane_steps"] == 8 + 2 + 1 + 1
        assert m["dispatch"] == 10 and m["buffer"] == 1
        assert "spec" not in m
    finally:
        eng.close()


def test_decode_batch_counts_spec_tokens_by_source(tiny_llama_dir):
    """A speculating lane's first token is `spec`, the rest of its accepted
    block come back as `buffer`; the step's counters stay out of it
    (another program computed those)."""
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(
        tiny_llama_dir, slots=2, max_seq=64, param_dtype="float32",
        spec_lookahead=2, kv_paged=False,
    )
    try:
        if eng.spec_lookahead == 0:
            pytest.skip("model cache layout refuses speculation")
        dec = DecodingParams(temperature=0.0)
        tok = int(eng.prefill_and_sample("s", [256, 72, 101, 108], dec).token[0])
        before = _counters()
        out, errs = eng.decode_batch({"s": (tok, dec)}, budgets={"s": 8})
        assert not errs
        m = _moved(before)
        assert m == {"spec": 1}  # no step went to the device
        buffered = len(eng._buffer.get("s", []))
        for _ in range(buffered):
            out, _ = eng.decode_batch({"s": (int(out["s"].token[0]), dec)},
                                      budgets={"s": 8})
        assert _moved(before).get("buffer", 0) == buffered
    finally:
        eng.close()


def _span(name):
    ch = metric("dnet_span_ms").labels(span=name)
    return ch.count, ch.sum


async def _serve_one(model_dir, prompt, max_tokens):
    """The production stack under the scheduler: warm-up request (every
    compile), books reset, then ONE measured request."""
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager
    from dnet_tpu.api.schemas import ChatCompletionRequest

    def req(content):
        return ChatCompletionRequest.model_validate({
            "model": "tiny", "max_tokens": max_tokens, "temperature": 0.0,
            "messages": [{"role": "user", "content": content}],
        })

    inference = InferenceManager(adapter=None, request_timeout_s=120.0, max_concurrent=2)
    manager = LocalModelManager(inference, max_seq=256, param_dtype="float32", batch_slots=2)
    await manager.load_model(str(model_dir))
    try:
        await inference.generate(req(prompt))
        await inference.generate(req(prompt[::-1]))
        reset_obs()
        out = await inference.generate(req(prompt))
        return out.id, out.usage, inference.adapter.engine.slots
    finally:
        await manager.unload_model()


def test_scheduler_rehearsal_waits_spans_and_tick_records(tiny_llama_dir, paged_env):
    """The scheduler over the paged pool (what a load derives), tiny model, one request:
    the recorder's admission_wait + sched_queue + prefill spans add up to
    the measured time to first token, `sched_queue` is emitted, the wait
    histograms hold one request's worth, the span tree is consistent, and
    /v1/debug/sched's records say which ticks reached the device."""
    paged_env.setenv("DNET_SCHED_PREFILL_CHUNK", "8")
    paged_env.setenv("DNET_OBS_ENABLED", "1")  # the tick-record ring
    reset_settings_cache()
    try:
        rid, usage, slots = asyncio.run(
            _serve_one(tiny_llama_dir, "the quick brown fox jumps over the dog " * 4, 9)
        )
        tl = get_recorder().timeline(rid)
        by_name = {}
        for s in tl["spans"]:
            by_name.setdefault(s["name"], []).append(s)

        # ---- a request's wait adds up to its time to first token
        assert len(by_name["sched_queue"]) == 1
        sched_prefill = [s for s in by_name["prefill"] if "chunks" in s.get("meta", {})]
        assert len(sched_prefill) == 1
        chunks = sched_prefill[0]["meta"]["chunks"]
        assert chunks == -(-usage.prompt_tokens // 8) >= 20
        ttft = by_name["ttft"][0]["dur_ms"]
        parts = (
            sum(s["dur_ms"] for s in by_name.get("admission_wait", []))
            + by_name["sched_queue"][0]["dur_ms"]
            + sched_prefill[0]["dur_ms"]
        )
        # within 5 %, or the few ms a loaded event loop takes to hand the
        # token to its driver (since PR 33 the whole wait is some 60 ms here)
        assert parts == pytest.approx(ttft, rel=0.05, abs=6.0), (parts, ttft)
        # the engine's per-chunk `prefill` spans (enqueue times) lie inside
        # the scheduler's: the ledger's prefill_compute is the real wall
        led = decompose(tl)["segments_ms"]
        assert led["prefill_compute"] == pytest.approx(sched_prefill[0]["dur_ms"], rel=0.02)
        assert led["sched_queue"] == pytest.approx(by_name["sched_queue"][0]["dur_ms"], abs=0.01)
        step0 = next(s for s in by_name["decode_step"] if s["meta"]["step"] == 0)
        assert led["sched_queue"] + led["prefill_compute"] >= min(
            0.95 * step0["dur_ms"], step0["dur_ms"] - 6.0)

        # ---- the always-on wait families hold this one request
        assert metric("dnet_sched_queue_wait_ms").count == 1
        assert metric("dnet_sched_prefill_wall_ms").count == 1
        assert metric("dnet_sched_prefill_wall_ms").sum == pytest.approx(
            sched_prefill[0]["dur_ms"], abs=0.01)
        assert metric("dnet_sched_prefill_ticks").sum == chunks
        decode_tokens = usage.completion_tokens - 1
        assert metric("dnet_sched_deliver_wait_ms").count == decode_tokens
        tok = metric("dnet_decode_tokens_total")
        delivered = tok.labels(source="dispatch").value + tok.labels(source="buffer").value
        assert delivered == decode_tokens

        # ---- the span tree: children sum to no more than the parent
        n_tick, tick_ms = _span("dnet.tick")
        assert n_tick == metric("dnet_sched_tick_ms").count > chunks
        assert tick_ms <= metric("dnet_sched_tick_ms").sum
        # dnet.tick.decode opens around the launch half (prepare, launch)
        # of every tick whose plan holds a lane that asked, and around the
        # read half (readback, unpack) of every tick that follows a step
        n_halves, dec_ms = _span("dnet.tick.decode")
        child_ms = sum(_span(n)[1] for n in DECODE_CHILD_SPANS)
        assert 0.8 * dec_ms <= child_ms <= dec_ms
        n_dec = _span("dnet.decode.prepare")[0]  # one per decode_launch call
        assert DECODE_CHILD_SPANS == tuple(
            f"dnet.decode.{s}" for s in ("prepare", "launch", "readback", "unpack"))
        n_disp = metric("dnet_decode_dispatch_total").value
        assert _span("dnet.decode.launch")[0] == _span("dnet.decode.readback")[0] == n_disp
        assert n_halves == n_dec + n_disp
        # a lone stream is never answered from a buffer:
        # one step a token, each but the first chained to the one before;
        # the last ask (a budget of 1) only reads
        assert n_disp == decode_tokens == n_dec - 1
        assert metric("dnet_decode_chained_lanes_total").value == decode_tokens - 1
        assert metric("dnet_decode_surplus_steps_total").value == 0
        assert tok.labels(source="buffer").value == 0
        n_pf, pf_ms = _span("dnet.tick.prefill")
        assert n_pf == _span("dnet.prefill.launch")[0] == chunks
        assert _span("dnet.prefill.adopt")[0] == 1
        assert _span("dnet.prefill.launch")[1] + _span("dnet.prefill.adopt")[1] <= pf_ms
        n_rb, rb_ms = _span("dnet.prefill.readback")
        assert n_rb == 1  # the one tick that adopted a prompt read its first token
        assert dec_ms + pf_ms + rb_ms <= tick_ms
        assert _span("dnet.sched.apply")[0] == n_tick <= _span("dnet.sched.plan")[0]
        assert _span("dnet.api.sse_flush")[0] == 0  # no HTTP layer in this stack

        # ---- tick records: which ticks reached the device
        recs = [r.as_dict() for r in get_tick_recorder().records()]
        assert len(recs) == n_tick
        decode_ticks = [r for r in recs if r["decode_lanes"]]
        assert len(decode_ticks) == decode_tokens  # the ticks that read a step
        reached = [r for r in recs if r["dispatched_lanes"]]  # the ticks that sent one
        assert len(reached) == n_disp
        assert all(r["dispatched_lanes"] == 1 for r in reached)
        assert all("chunk_r" not in r for r in recs)
        # the first sends and reads nothing, the last reads and sends nothing
        assert reached[0] not in decode_ticks and decode_ticks[-1] not in reached
        assert sum(r["decode_lanes"] for r in recs) == decode_tokens
        slot_steps = metric("dnet_decode_slot_steps_total").value
        assert slot_steps == slots * len(reached)
    finally:
        reset_obs()
