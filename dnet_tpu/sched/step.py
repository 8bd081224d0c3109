"""Tick execution: one TickPlan against the batched engine's internals.

``execute_tick`` runs ON THE COMPUTE THREAD (the adapter's single-worker
executor — the same ownership model as every other engine touch).  One
rule orders a tick: **enqueue all of its device work, then read**.
Nothing blocks on the device until everything the tick will ask of it is
queued, so the device never drains inside a tick:

1. the batched decode dispatch is LAUNCHED first (every running stream
   advances before any prompt token burns — decode latency is what the
   per-token SLO measures): ONE step that carries every decoding lane
   whenever a prompt waits (the plan then holds no budgets), a fused
   R-step chunk only for lanes in phase with nothing queued
   (core/batch.py: decode_launch); with block-starvation preemption
   resolved BEFORE the dispatch so a pool shortfall evicts the
   lowest-priority sequence instead of erroring an arbitrary lane; the
   dispatch attends the block pool in place through the page tables
   (ops/paged_attention.py), and this module's block accounting
   (_decode_need, preemption) is a function of blocks alone;
2. then every chunked-prefill segment of the plan is LAUNCHED on the
   engine's B=1 bucket programs, staged in the inner engine's dense row;
   a segment that completes its prompt has its adoption ENQUEUED too (the
   row's blocks commit into the pool, the first token is sampled and the
   lane's sampling state written: compiled programs, no eager op);
3. then the decode step's results are READ (core/batch.py: decode_read)
   and leave the tick at once through ``on_decode``: the drivers answer
   while the chunks run;
4. then the tick's first tokens are read, whole fields at a time.

Launch order is device order: decode step, chunks, adoptions.  The step
appends to the pool it donates and a chunk works in its session's staged
row, so nothing races.

Preemption keeps the paged prefix intact: the victim's live page table is
aliased into the PagedPrefixCache (zero copy, refcounted) before the slot
is released, so its eventual resume re-prefills only what the cache
cannot cover.  Victims holding engine-buffered fused-chunk tokens are
skipped — their device position is ahead of the driver-confirmed stream,
so their table cannot be snapshotted consistently.  A victim a CHUNK
evicts (step 2) has its decode step in flight: the read half drops that
step's token and leaves its position alone (its freed lane may already
be another prompt's), the alias covers what was committed before the
step, and its pending driver step rides the resume like that of a lane
evicted before the dispatch.

**A lane that holds recurrent state is never aliased** (a hybrid model's
store, kv/store.py HybridStore: a lane of state beside the page table).
Its blocks could be cut at a prefix, the state beside them cannot, and a
prefix entry that aliased the blocks would hand a later request keys
without the state that goes with them.  The rule taken: such a victim is
preempted by giving EVERYTHING back (its lane, its state, its blocks) and
its resume prefills again from token 0.  Parking the state aside is not
built (PERF.md section 7).

The executor only reads the plan (loop-side snapshots) and the engine; it
never touches the scheduler queue.  Results flow back as plain HOST data
in a :class:`TickResult` the loop applies: no device array leaves the
compute thread.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List

import jax

from dnet_tpu.kv import KVPoolExhausted
from dnet_tpu.obs import metric, observe_span, span
from dnet_tpu.obs.phases import (
    SPAN_PREFILL_ADOPT,
    SPAN_PREFILL_LAUNCH,
    SPAN_PREFILL_READBACK,
    SPAN_TICK,
    SPAN_TICK_DECODE,
    SPAN_TICK_PREFILL,
    SPAN_TURN_TO_THREAD,
    TURN_DEVICE_BUSY,
    TURN_DEVICE_DRAINED,
)
from dnet_tpu.sched.policy import PrefillChunk, TickPlan
from dnet_tpu.utils.logger import get_logger

log = get_logger()

_PREEMPTIONS = metric("dnet_sched_preemptions_total")
_MIXED_TICKS = metric("dnet_sched_mixed_ticks_total")
_TURNAROUND_MS = metric("dnet_sched_turnaround_ms")

#: consecutive starved requeues before a prefill surfaces the typed
#: backpressure error instead of waiting for blocks that may never free
MAX_STARVED_REQUEUES = 8


@dataclass
class TickResult:
    #: nonce -> SampleResult from the batched decode dispatch (host arrays)
    decode_results: Dict[str, object] = field(default_factory=dict)
    #: nonce -> SampleResult sampled at prefill completion (adopt); host
    #: arrays once execute_tick returns
    adopted: Dict[str, object] = field(default_factory=dict)
    #: nonce -> absolute staged-token position after this tick's chunk
    progress: Dict[str, int] = field(default_factory=dict)
    errors: Dict[str, str] = field(default_factory=dict)
    #: DECODING sequences evicted back to WAITING (block starvation)
    preempted: List[str] = field(default_factory=list)
    #: PREFILLING requests that gave their staged work back (starved /
    #: lost the slot race) and should retry from WAITING
    requeued: List[str] = field(default_factory=list)
    #: nonces whose decode result was already handed off mid-tick through
    #: execute_tick's on_decode — the loop-side apply must not resolve
    #: these a second time
    dispatched: List[str] = field(default_factory=list)
    prefill_tokens: int = 0
    #: lanes the tick's decode_batch call answered — from the dispatch it
    #: made OR from the engine's fused-chunk buffer (feeds
    #: dnet_sched_batch_tokens{kind="decode"})
    decode_lanes: int = 0
    #: lanes and fused width R of the dispatch that call sent to the
    #: device; both 0 when every lane was answered from the buffer
    dispatched_lanes: int = 0
    chunk_r: int = 0
    #: perf_counter when the decode read ended on the compute thread: the
    #: loop measures a decode token's wait for its future from here
    t_decode_done: float = 0.0
    #: perf_counter when this tick's FIRST device program was enqueued (0.0:
    #: it enqueued none): the turn-around since the tick before ends here
    t_launched: float = 0.0
    #: perf_counter when dnet.tick ended (execute_tick about to return)
    t_done: float = 0.0
    #: perf_counter since when the device has had nothing new from the host:
    #: the turn-around to the next enqueue starts here.  `t_done`, or, where
    #: this tick enqueued nothing (every lane answered from a fused
    #: dispatch's buffer), the `t_idle` of the tick before: the turn-around
    #: runs through such a tick, as the device's wait does
    t_idle: float = 0.0
    #: the tick's last device program was read before it returned (the step
    #: of a tick without chunks; an adoption, by dnet.prefill.readback), so
    #: the device has nothing to do until the next launch.  False where a
    #: chunk it enqueued may still run.  A tick that enqueued nothing hands
    #: on what the tick before left.
    drained: bool = True


def _launched(res: TickResult) -> None:
    """A device program of this tick has just been enqueued."""
    if not res.t_launched:
        res.t_launched = time.perf_counter()


def _decode_need(engine, nonces) -> int:
    """Fresh blocks the pool must cover for one decode step across these
    lanes (R=1 floor; the engine's own extension shrinks wider fused
    chunks down to it under pressure)."""
    cfg = engine._kv_cfg
    need = 0
    for n in nonces:
        slot = engine.slot_of.get(n)
        if slot is None:
            continue
        tbl = engine._tables[slot]
        have = len(tbl.blocks) if tbl is not None else 0
        need += max(cfg.blocks_for(int(engine.pos[slot]) + 1) - have, 0)
    return need


def _preempt(engine, nonce: str, ids: List[int]) -> None:
    """Evict one DECODING sequence: alias its committed KV into the prefix
    cache (paged prefix intact — resume re-prefills only the uncovered
    tail), then release its slot, blocks, and inner session.  A lane whose
    device position ran ahead of the driver-confirmed stream (engine-
    buffered fused-chunk tokens) skips the alias — store_prefix refuses
    the inconsistent snapshot — and its resume recomputes the dropped
    lookahead (greedy-deterministic, so the stream is unchanged)."""
    slot = engine.slot_of.get(nonce)
    store = getattr(engine, "kv_store", None)
    stateful = store is not None and store.in_place
    if slot is not None and ids and not stateful:
        committed = ids[: int(engine.pos[slot])]
        try:
            engine.store_prefix(nonce, committed)
        except Exception as exc:
            # losing the alias only costs the resume a re-prefill
            log.debug("preemption prefix store for %s skipped: %s", nonce, exc)
    engine.end_session(nonce)
    _PREEMPTIONS.labels(reason="block_starvation").inc()


def _preempt_for_decode(engine, plan: TickPlan, reqs: dict, res: TickResult) -> None:
    """Evict lowest-priority lanes until the pool covers this tick's
    decode extensions.  The most urgent lane is never evicted."""
    victims = [v for v in plan.victims if v in engine.slot_of]
    while len(victims) > 1 and reqs:
        need = _decode_need(engine, reqs)
        if need <= engine.kv_pool.free:
            return
        v = victims.pop(0)
        _preempt(engine, v, plan.ids.get(v, []))
        res.preempted.append(v)
        reqs.pop(v, None)


def _run_prefill_chunk(
    engine, plan: TickPlan, chunk: PrefillChunk, res: TickResult
) -> None:
    nonce = chunk.nonce
    if chunk.first:
        try:
            engine.reserve_slot(nonce)
        except RuntimeError as exc:
            if "no free batch slots" in str(exc):
                # the loop-side slot estimate lost a race (TTL sweep /
                # concurrent teardown): a clean retry, never a client error
                res.requeued.append(nonce)
                return
            raise
        engine.seed_from_prefix(nonce, chunk.ids, chunk.seed)
    sess = engine.eng.sessions.get(nonce)
    cur = int(sess.pos) if sess is not None else 0
    end = max(min(chunk.end, len(chunk.ids)), cur)
    piece = chunk.ids[cur:] if chunk.last else chunk.ids[cur:end]
    logits = None
    if piece:
        try:
            with span(SPAN_PREFILL_LAUNCH):
                logits = engine.prefill_chunk(nonce, piece, chunk.seed)
        except KVPoolExhausted as exc:
            _handle_prefill_starvation(engine, plan, chunk, res, cur, exc)
            return
        _launched(res)
        res.drained = False  # until something enqueued behind it is read
        res.prefill_tokens += len(piece)
    res.progress[nonce] = cur + len(piece)
    if not chunk.last:
        return
    while True:
        try:
            # enqueues only: the result stays on the device until the tick
            # has launched everything (execute_tick reads it last)
            with span(SPAN_PREFILL_ADOPT):
                engine.store_prefix(nonce, chunk.ids)
                sample = engine.adopt_prefilled(nonce, logits, chunk.decoding)
        except KVPoolExhausted as exc:
            victims = [
                v
                for v in chunk.victims
                if v in engine.slot_of and v not in res.preempted
            ]
            if victims:
                # evict and retry IN THIS TICK: end_session frees the
                # victim's blocks synchronously, and a next-tick retry is
                # impossible here — the chunks are fully committed, so a
                # re-driven tick would have no logits left to adopt from.
                # (The pools' alloc raised before adopt_prefilled enqueued
                # anything or touched the session's key and counts.)
                _preempt(engine, victims[0], plan.ids.get(victims[0], []))
                res.preempted.append(victims[0])
                continue
            _handle_prefill_starvation(engine, plan, chunk, res, cur, exc)
            return
        except Exception as exc:
            log.exception("scheduler prefill adopt failed for %s", nonce)
            engine.abandon_prefill(nonce)
            res.errors[nonce] = str(exc)
            return
        break
    res.adopted[nonce] = sample
    res.drained = True  # the tick reads it, after everything before it


def _handle_prefill_starvation(
    engine,
    plan: TickPlan,
    chunk: PrefillChunk,
    res: TickResult,
    cur: int,
    exc: KVPoolExhausted,
) -> None:
    """A prefill segment the pool refused before committing anything.

    With a strictly-lower-priority DECODING victim available: evict it
    (its blocks free now) and keep this request's staged session — the
    next tick retries the same segment against the refilled pool (safe
    here because the chunk pre-check raises before any KV commits; the
    adopt-time starvation retries in-tick instead, see the caller).  With
    no victim but other residents: give the staged work back and retry
    from WAITING once their blocks free (bounded by the loop's starved
    counter).  Alone: surface the typed backpressure error — nothing will
    ever free the blocks this prompt needs."""
    victims = [
        v
        for v in chunk.victims
        if v in engine.slot_of and v not in res.preempted
    ]
    if victims:
        v = victims[0]
        _preempt(engine, v, plan.ids.get(v, []))
        res.preempted.append(v)
        res.progress[chunk.nonce] = cur  # staged work kept; retry next tick
        return
    others = [n for n in engine.slot_of if n != chunk.nonce]
    engine.abandon_prefill(chunk.nonce)
    if others:
        res.requeued.append(chunk.nonce)
        return
    res.errors[chunk.nonce] = str(exc)


def execute_tick(
    engine, plan: TickPlan, on_decode=None, follows=None, t_submit=None
) -> TickResult:
    """One tick on the compute thread: launch the decode step, launch every
    chunk (and enqueue the adoption of a prompt it completes), read the
    step, read the first tokens (the module docstring has the why).

    ``follows`` is the result of the tick this one follows with no park in
    between (the loop says so, sched/engine.py), ``t_submit`` the loop's
    clock when it handed this tick to the executor.  From them the
    turn-around between the two ticks: ``dnet.turn.to_thread`` (the submit
    to this tick's start) and ``dnet_sched_turnaround_ms`` (the end of
    ``follows`` to this tick's FIRST device program enqueued, both read on
    this thread).  A tick that enqueues nothing observes none and hands the
    start on: the next tick's turn-around holds it whole.

    ``on_decode`` hands each decode result off the moment the step is read
    — while this tick's chunks are still running on the device — so decode
    futures resolve (and, on a ring, the next hop's frames launch) instead
    of barriering behind the tick's slowest segment.  Results handed off
    this way are also recorded in ``dispatched`` so the loop-side apply
    doesn't resolve them twice."""
    res = TickResult()
    if t_submit is not None:
        observe_span(
            SPAN_TURN_TO_THREAD, (time.perf_counter() - t_submit) * 1000.0
        )
    with span(SPAN_TICK, decode_lanes=len(plan.decode),
              prefill_chunks=len(plan.prefills)):
        _execute(engine, plan, on_decode, res)
    res.t_done = res.t_idle = time.perf_counter()
    if follows is not None:
        if res.t_launched:
            _TURNAROUND_MS.labels(
                device=TURN_DEVICE_DRAINED if follows.drained else TURN_DEVICE_BUSY
            ).observe((res.t_launched - follows.t_idle) * 1000.0)
        else:
            res.t_idle, res.drained = follows.t_idle, follows.drained
    return res


def _execute(engine, plan: TickPlan, on_decode, res: TickResult) -> None:
    reqs = dict(plan.decode)
    if reqs and getattr(engine, "kv_pool", None) is not None:
        _preempt_for_decode(engine, plan, reqs, res)
    flight = None
    if reqs:
        with span(SPAN_TICK_DECODE):
            flight = engine.decode_launch(reqs, budgets=plan.budgets or None)
        res.decode_lanes = len(reqs)
        res.chunk_r, res.dispatched_lanes = getattr(
            engine, "last_dispatch", (0, 0)
        )
        if res.chunk_r:
            _launched(res)  # dnet.decode.launch has just ended
    for chunk in plan.prefills:
        if chunk.nonce in res.preempted:
            continue
        try:
            with span(SPAN_TICK_PREFILL, tokens=chunk.end - chunk.start,
                      first=chunk.first, last=chunk.last):
                _run_prefill_chunk(engine, plan, chunk, res)
        except Exception as exc:
            log.exception("scheduler prefill chunk failed for %s", chunk.nonce)
            try:
                engine.abandon_prefill(chunk.nonce)
            except Exception as inner:
                log.debug("abandon_prefill after failure: %s", inner)
            res.errors[chunk.nonce] = str(exc)
    if flight is not None:
        if res.chunk_r and plan.prefills:
            # a step AND a chunk: did the chunks queue up behind the step
            # (yes), or had the launch half already waited the device out
            _MIXED_TICKS.labels(
                overlapped="no" if flight.blocked else "yes"
            ).inc()
        with span(SPAN_TICK_DECODE):
            out, errs = engine.decode_read(flight)
        res.t_decode_done = time.perf_counter()
        res.decode_results.update(out)
        res.errors.update(errs)
        if on_decode is not None:
            for nonce, sample in out.items():
                try:
                    on_decode(nonce, sample)
                    res.dispatched.append(nonce)
                except Exception:
                    # a failed early dispatch falls back to the barriered
                    # apply path — the result is still in decode_results
                    log.exception("early decode dispatch failed for %s", nonce)
    if res.adopted:
        # the first tokens, whole fields at a time: the one other place a
        # tick waits for the device, after everything is enqueued
        with span(SPAN_PREFILL_READBACK):
            # dnetlint: disable=DL005 the tick's designed read of its first tokens: every field's copy started at once, waited for after all of the tick's device work is enqueued
            res.adopted = jax.device_get(res.adopted)
