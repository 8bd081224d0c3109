"""Tick execution: one TickPlan against the batched engine's internals.

``execute_tick`` runs ON THE COMPUTE THREAD (the adapter's single-worker
executor — the same ownership model as every other engine touch).  Two
rules order a tick: **enqueue all of its device work, then read**, and
**a tick that reads a decode step has already enqueued what the device
does next**: the next step, chained to that step's tokens on the device,
or a waiting prompt's chunk.  So the device neither drains inside a tick
nor between two of them (the host's way from one tick to the next, the
drivers' answers included, runs while that program does).  A decode
dispatch is ONE step for the lanes that asked, which is all the engine
has: with nothing to wait for, R steps in one program only held a joining
lane and a waiting prompt behind them (PR 40, gen: 222.98 -> 348.02
tokens/s with one step in flight and no wider dispatch).

1. every chunked-prefill segment of the plan is LAUNCHED on the engine's
   B=1 bucket programs, staged in the inner engine's dense row; a segment
   that completes its prompt has its adoption ENQUEUED too (the row's
   blocks commit into the pool, the first token is sampled and the lane's
   sampling state written: compiled programs, no eager op).  Step n, which
   the tick before enqueued, is running meanwhile: a chunk waits for the
   rest of ONE step, never for two;
2. then, UNLESS this tick leaves a chunk running behind a step in flight,
   step n+1 is LAUNCHED (core/batch.py: decode_launch, chain=): a
   lane of step n whose driver has asked for n's token and will take
   another (``plan.budgets``) steps again from that token ON THE DEVICE; a
   lane that asked and is not in step n (just adopted, resumed) joins the
   same dispatch with its host token; a lane that takes its last token,
   or whose driver has not asked, is not chained.  Block-starvation
   preemption is resolved BEFORE the dispatch, so a pool shortfall evicts
   the lowest-priority sequence instead of erroring an arbitrary lane; the
   dispatch attends the block pool in place through the page tables
   (ops/paged_attention.py), and this module's block accounting
   (_decode_need, preemption) is a function of blocks alone;
3. then step n's results are READ (core/batch.py: decode_read) and leave
   the tick at once through ``on_decode``: the drivers answer while the
   chunks and step n+1 run.  A lane of step n whose driver was late keeps
   its token in the engine's buffer and is answered at its next ask;
4. then the tick's first tokens are read, whole fields at a time.

**A chunk takes the place of the step ahead.**  One program enqueued
behind the step being read is what carries the device across the host's
turn, and a chunk does that as well as a step (PR 33's finding).  So while
a long prompt prefills, ticks alternate: one enqueues its chunk and a step
for every lane that asked (none is in flight), the next enqueues its chunk
and only reads that step.  The lanes then step every second tick, all in
ONE dispatch, and a prompt's chunks do not each wait behind a step.  A
tick that ADOPTS a prompt waits its chunk out for the first token, so the
chunk carries nothing: it launches the step, behind the adoption.
Chaining a step behind every chunk as well was measured first (PERF.md
section 6, PR 40): the device is as busy either way, but in a closed loop
of long prompts every chunk tick then costs a chunk AND a step, and the
first token came 13 % later than before the change.

Launch order is device order: (step n, already running,) chunks,
adoptions, step n+1.  The step appends to the pool it donates and a chunk
works in its session's staged row, so nothing races; and whatever the host
gives back while a step is in flight (a block behind a window, the blocks
and the state of a lane that ended) is written again only by programs
enqueued after that step.

A chained lane that ENDED at step n's token, for a reason the host learns
only at the read (a stop id, a cancel), has a surplus step in flight: the
next read drops its token and leaves the position alone, exactly as for
any lane that left between launch and read.

An engine with per-lane speculation (``spec_lookahead > 0``, off by
default) reads the device in its launch half, so nothing can be hidden
behind it: its ticks keep the serial order (step launched first, chunks,
then THAT step read) and chain nothing.

Preemption keeps the paged prefix intact: the victim's live page table is
aliased into the PagedPrefixCache (zero copy, refcounted) before the slot
is released, so its eventual resume re-prefills only what the cache
cannot cover.  Victims holding engine-buffered tokens are skipped — their
device position is ahead of the driver-confirmed stream, so their table
cannot be snapshotted consistently.  A victim nearly always has a step in
flight now (step n, not read yet, whoever evicts it: the decode
extension's shortfall or a chunk's adoption): the read half drops that
step's token and leaves its position alone (its freed lane may already be
another prompt's), the alias covers what was committed before the step,
and its pending driver step rides the resume.

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

from dnet_tpu.core.batch import DecodeFlight, takes_another
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
    #: lanes this tick handed a token — from the step it read OR from the
    #: engine's buffer (feeds dnet_sched_batch_tokens{kind="decode"})
    decode_lanes: int = 0
    #: lanes of the step this tick ENQUEUED; 0 when it enqueued none
    dispatched_lanes: int = 0
    #: the step this tick enqueued and did not read (the engine's
    #: DecodeFlight: device arrays, the compute thread's alone; the loop
    #: only hands it to the next tick with ``follows``), or None
    flight: object = None
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
    #: this tick neither enqueued nor read anything (every lane answered
    #: from the buffer), the `t_idle` of the tick before: the turn-around
    #: runs through such a tick, as the device's wait does.  0.0: no
    #: turn-around starts here (the loop parked after this tick)
    t_idle: float = 0.0
    #: the tick's last device program was read before it returned (an
    #: adoption, by dnet.prefill.readback; the step before, where no step
    #: was enqueued behind it), so the device has nothing to do until the
    #: next launch.  False where a step or a chunk it enqueued may still
    #: run.  A tick that enqueued and read nothing hands on what the tick
    #: before left.
    drained: bool = True

    def after_park(self) -> "TickResult":
        """What the tick after a park follows: the step still in flight,
        and no turn-around (an idle server's seconds are none)."""
        return TickResult(flight=self.flight, drained=self.drained)


def _launched(res: TickResult) -> None:
    """A device program of this tick has just been enqueued."""
    if not res.t_launched:
        res.t_launched = time.perf_counter()


def _decode_need(engine, nonces, in_flight) -> int:
    """Fresh blocks the pool must cover for one decode step across these
    lanes: a lane of `in_flight` (nonce -> slot of the step not read yet)
    steps one position past it."""
    cfg = engine._kv_cfg
    need = 0
    for n in nonces:
        slot = engine.slot_of.get(n)
        if slot is None:
            continue
        tbl = engine._tables[slot]
        have = len(tbl.blocks) if tbl is not None else 0
        ahead = int(in_flight.get(n) == slot)
        need += max(cfg.blocks_for(int(engine.pos[slot]) + ahead + 1) - have, 0)
    return need


def _preempt(engine, nonce: str, ids: List[int]) -> None:
    """Evict one DECODING sequence: alias its committed KV into the prefix
    cache (paged prefix intact — resume re-prefills only the uncovered
    tail), then release its slot, blocks, and inner session.  A lane whose
    device position ran ahead of the driver-confirmed stream (an engine-
    buffered token its driver was late for) skips the alias — store_prefix
    refuses the inconsistent snapshot — and its resume recomputes the
    dropped token (greedy-deterministic, so the stream is unchanged).  A
    step the victim has in flight wrote past `pos`, which is read here
    before that step is: the alias holds what was committed before it."""
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


def _preempt_for_decode(
    engine, plan: TickPlan, reqs: dict, res: TickResult, in_flight: dict
) -> None:
    """Evict lowest-priority lanes until the pool covers this tick's
    decode extensions.  The most urgent lane is never evicted."""
    victims = [v for v in plan.victims if v in engine.slot_of]
    while len(victims) > 1 and reqs:
        # a lane of the step in flight steps again only where it will take
        # a token after the one it is owed
        stepping = [
            n for n in reqs if n not in in_flight or takes_another(plan.budgets, n)
        ]
        need = _decode_need(engine, stepping, in_flight)
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
    """One tick on the compute thread: launch every chunk (and enqueue the
    adoption of a prompt it completes), launch the next decode step chained
    to the one in flight, read the one in flight, read the first tokens
    (the module docstring has the why).

    ``follows`` is the result of the tick before: it holds the step that
    tick left in flight (``flight``), which this one reads.  ``t_submit``
    is the loop's clock when it handed this tick to the executor.  From
    them the turn-around between the two ticks: ``dnet.turn.to_thread``
    (the submit to this tick's start) and ``dnet_sched_turnaround_ms`` (the
    end of ``follows`` to this tick's FIRST device program enqueued, both
    read on this thread; none where the loop parked in between:
    ``TickResult.after_park``).  A tick that enqueues and reads nothing
    observes none and hands the start on: the next tick's turn-around
    holds it whole.

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
    prev = follows.flight if follows is not None else None
    with span(SPAN_TICK, decode_lanes=len(plan.decode),
              prefill_chunks=len(plan.prefills)):
        read_device = _execute(engine, plan, on_decode, res, prev)
    res.t_done = res.t_idle = time.perf_counter()
    if res.flight is not None:
        res.drained = False  # the step just enqueued runs on
    if follows is not None:
        if not res.t_launched and not read_device:
            res.t_idle, res.drained = follows.t_idle, follows.drained
        elif res.t_launched and follows.t_idle:
            _TURNAROUND_MS.labels(
                device=TURN_DEVICE_DRAINED if follows.drained else TURN_DEVICE_BUSY
            ).observe((res.t_launched - follows.t_idle) * 1000.0)
    return res


def _launch_step(engine, plan: TickPlan, reqs: dict, res: TickResult, chain):
    """Enqueue this tick's decode step for the lanes that asked; returns
    its flight, or None where nothing went to the device.  What the launch
    half settled on the host (a late driver's token out of the buffer, a
    lane the pool refused) is this tick's to hand out."""
    in_flight = chain.order if chain is not None else {}
    if reqs and getattr(engine, "kv_pool", None) is not None:
        _preempt_for_decode(engine, plan, reqs, res, in_flight)
    if not reqs:
        return None
    with span(SPAN_TICK_DECODE):
        flight = engine.decode_launch(
            reqs, budgets=plan.budgets or None, chain=chain
        )
    if flight.src is not None:
        res.dispatched_lanes = len(flight.order)
        _launched(res)  # dnet.decode.launch has just ended
    return flight


def _execute(engine, plan: TickPlan, on_decode, res: TickResult, prev) -> bool:
    """The tick's body; says whether it read a dispatch off the device."""
    reqs = dict(plan.decode)
    # an engine that speculates reads the device in its launch half: its
    # step goes first and is read in this tick, and nothing is chained
    serial = getattr(engine, "spec_lookahead", 0) > 0
    read = prev
    if serial:
        read = _launch_step(engine, plan, reqs, res, None)
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
    out: Dict[str, object] = {}
    # What keeps the device at work across the host's turn is ONE program
    # enqueued behind the step being read: the next step, or, where a prompt
    # waits, its chunk.  A tick that leaves a chunk running behind a step in
    # flight (`drained` is False: it adopted nothing, so it will not wait
    # the chunk out) launches no step of its own: the lanes take their next
    # step in the tick after, all of them in one dispatch, and the prompt's
    # chunks do not each wait behind a step (module docstring).
    holds = prev is not None and not res.drained
    if not serial and not holds:
        for nonce in res.preempted:
            reqs.pop(nonce, None)  # a chunk's adoption evicted it
        flight = _launch_step(
            engine, plan, reqs, res, prev if prev is not None else DecodeFlight()
        )
        if flight is not None:
            out, errs = flight.answered()
            res.errors.update(errs)
            if flight.src is not None:
                res.flight = flight
    if res.dispatched_lanes and plan.prefills:
        # a step AND a chunk: is the device kept busy from the one to the
        # other (yes), or had the launch half already waited it out
        _MIXED_TICKS.labels(
            overlapped="no" if read is not None and read.blocked else "yes"
        ).inc()
    if read is not None:
        with span(SPAN_TICK_DECODE):
            # a lane of that step whose driver has not asked keeps its token
            got, errs = engine.decode_read(
                read, asked=None if serial else plan.decode.keys()
            )
        res.t_decode_done = time.perf_counter()
        out.update(got)
        res.errors.update(errs)
    res.decode_lanes = len(out)
    res.decode_results.update(out)
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
    return read is not None and read.src is not None
