"""SchedulerAdapter: the iteration-level tick loop that serves every
BatchedEngine load (api/model_manager.py: serving_plan).

One adapter in place of the kick-coalescing BatchedLocalAdapter AND the
monolithic per-request prefill: every tick the policy packs a token
budget of chunked-prefill segments plus one decode step per running
sequence into a single :class:`~dnet_tpu.sched.policy.TickPlan`, the
compute thread executes it (``sched/step.py``), and the loop applies the
results to the per-request state machines (``sched/queue.py``).  The
driver protocol (``ApiAdapterBase``) is unchanged — InferenceManager and
the HTTP layer cannot tell this engine from the legacy ones, which is
what makes the byte-identical parity test possible.

Admission is a function of free paged-KV blocks and batch slots;
deadlines stamped by the admission controller order both admission and
preemption.  Preempted sequences return to WAITING with their paged
prefix aliased into the prefix cache and resume transparently — the
pending driver step rides along and resolves from the resume's adopt
sample.
"""

from __future__ import annotations

import asyncio
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from dnet_tpu.analysis.runtime import ownership as dsan
from dnet_tpu.api.strategies import (
    ApiAdapterBase,
    _embed_on_executor,
    _reap,
    _TokenFutures,
)
from dnet_tpu.core.types import DecodingParams, TokenResult
from dnet_tpu.obs import get_recorder, metric, obs_enabled, observe_span, span
from dnet_tpu.obs.events import log_event
from dnet_tpu.obs.phases import (
    DRIVERS_TURN_ANSWERED,
    DRIVERS_TURN_NONE,
    DRIVERS_TURN_TIMED_OUT,
    SPAN_SCHED_APPLY,
    SPAN_SCHED_DRIVERS_TURN,
    SPAN_SCHED_PLAN,
    SPAN_SCHED_TURN,
    SPAN_TURN_TO_LOOP,
)
from dnet_tpu.sched.flight import get_tick_recorder
from dnet_tpu.sched.kinds import QUEUE_STATES, STATE_DECODING
from dnet_tpu.sched.policy import SchedulerPolicy, TickPlan
from dnet_tpu.sched.queue import SchedQueue
from dnet_tpu.sched.step import MAX_STARVED_REQUEUES, TickResult, execute_tick
from dnet_tpu.utils.logger import get_logger

log = get_logger()

_TICK_MS = metric("dnet_sched_tick_ms")
_BATCH_TOKENS = metric("dnet_sched_batch_tokens")
_PREEMPTIONS = metric("dnet_sched_preemptions_total")
_QUEUE_WAIT_MS = metric("dnet_sched_queue_wait_ms")
_PREFILL_WALL_MS = metric("dnet_sched_prefill_wall_ms")
_PREFILL_TICKS = metric("dnet_sched_prefill_ticks")
_DELIVER_WAIT_MS = metric("dnet_sched_deliver_wait_ms")
_LANES_LEFT_OUT = metric("dnet_sched_lanes_left_out_total")
_DRIVERS_TURN = metric("dnet_sched_drivers_turn_total")
_ANSWER_WAIT_MS = metric("dnet_sched_answer_wait_ms")


#: how long a plan waits for the drivers the last tick handed a token to
#: ask for the next one: two or three turns of the event loop in practice
#: (future -> driver task -> send_tokens), so the bound only matters for a
#: driver that is held up (a client that does not read); that lane then
#: joins the tick after, and is not waited for again until its next token
DRIVER_TURN_S = 0.002


class SchedulerAdapter(ApiAdapterBase):
    """Iteration-level continuous batching over a batched engine.

    Needs the full chunked-prefill serving surface BatchedEngine exposes
    (``reserve_slot`` / ``seed_from_prefix`` / ``prefill_chunk`` /
    ``adopt_prefilled`` / ``decode_batch`` + slot lifecycle).  Engines
    without it (PipelinedMeshEngine prefills in one ring pass) keep the
    BatchedLocalAdapter."""

    SWEEP_INTERVAL_S = 60.0

    def __init__(self, engine, token_budget: Optional[int] = None,
                 prefill_chunk: Optional[int] = None) -> None:
        from dnet_tpu.config import get_settings

        sched = get_settings().sched
        if not hasattr(engine, "prefill_chunk"):
            raise TypeError(
                f"SchedulerAdapter needs the chunked-prefill engine "
                f"surface; {type(engine).__name__} does not expose it"
            )
        self.engine = engine
        self.policy = SchedulerPolicy(
            token_budget=token_budget or sched.sched_token_budget,
            prefill_chunk=prefill_chunk or sched.prefill_chunk_cap(),
        )
        self.queue = SchedQueue()
        self._futures = _TokenFutures()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._kick: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._sweep_task: Optional[asyncio.Task] = None
        # deadline stamped by the driver BEFORE step 0 arrives (the
        # set_deadline call precedes the first send); loop-owned,
        # declared in analysis/runtime/domains.py
        self._deadlines: Dict[str, float] = dsan.guard_dict(
            {}, dsan.loop_domain(), "SchedulerAdapter._deadlines"
        )
        # nonces handed a token by the tick just applied whose drivers have
        # not answered yet (next step or reset); loop-owned
        self._answering: set = dsan.guard_set(
            set(), dsan.loop_domain(), "SchedulerAdapter._answering"
        )
        # dnet.sched.drivers_turn, open while `_answering` waits its turn
        self._owed: Optional[span] = None

    # ---- lifecycle ----------------------------------------------------
    async def start(self) -> None:
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="compute"
        )
        self._kick = asyncio.Event()
        self._task = asyncio.ensure_future(self._tick_loop())
        self._sweep_task = asyncio.ensure_future(self._sweep_loop())

    async def shutdown(self) -> None:
        task, self._task = self._task, None
        await _reap(task, "scheduler tick loop")
        sweep, self._sweep_task = self._sweep_task, None
        await _reap(sweep, "session sweep")
        if self._executor:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None

    async def _sweep_loop(self) -> None:
        """Periodic TTL sweep (same contract as the legacy adapters): a
        client that vanished without reset_cache must not pin its slot —
        or its queue entry — forever."""
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(self.SWEEP_INTERVAL_S)
            if self._executor is None:
                return
            try:

                def _sweep_once():
                    # residency snapshot taken ON the compute thread, in
                    # the same executor task as the sweep: slot_of is
                    # compute-owned, and a tick running between sweep and
                    # a loop-side read could preempt a request that would
                    # then be removed as "swept" (its pending step lost)
                    n_swept = self.engine.sweep_sessions()
                    return n_swept, set(self.engine.slot_of)

                n, resident = await loop.run_in_executor(
                    self._executor, _sweep_once
                )
                # a swept DECODING session lost its engine residency: drop
                # the stale queue entry so its slot estimate frees too
                for req in list(self.queue.decoding()):
                    if req.nonce not in resident:
                        self.queue.remove(req.nonce)
                if n:
                    log.info("TTL sweep freed %d idle sessions", n)
                    self._wake()
            except Exception:
                log.exception("session sweep failed")

    # ---- driver surface -----------------------------------------------
    async def reset_cache(self, nonce: str) -> None:
        self.queue.remove(nonce)
        self._deadlines.pop(nonce, None)
        self._answering.discard(nonce)
        if self._executor is not None:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                self._executor, self.engine.end_session, nonce
            )
        self._futures.cancel_nonce(nonce)
        self._wake()  # a freed slot / freed blocks may unblock admission

    def set_deadline(self, nonce: str, deadline_ts: float) -> None:
        req = self.queue.get(nonce)
        if req is not None:
            req.deadline_ts = deadline_ts
        else:
            self._deadlines[nonce] = deadline_ts

    def max_seq(self) -> Optional[int]:
        return self.engine.max_seq

    async def embed(self, ids_list: List[List[int]]) -> List[List[float]]:
        inner = getattr(self.engine, "eng", None) or getattr(
            self.engine, "_inner", None
        )
        fn = getattr(inner, "hidden_states", None)
        if fn is None:
            raise NotImplementedError(
                f"embeddings unsupported on {type(self.engine).__name__}"
            )
        return await _embed_on_executor(fn, self._executor, ids_list)

    async def send_tokens(
        self,
        nonce: str,
        token_ids: List[int],
        decoding: DecodingParams,
        step: int,
        budget: Optional[int] = None,
    ) -> None:
        if self._executor is None or self._kick is None:
            raise RuntimeError("adapter not started")
        self._futures.expect(nonce, step)
        if step == 0:
            req = self.queue.add(
                nonce, list(token_ids), decoding,
                deadline_ts=self._deadlines.pop(nonce, None),
            )
            req.pending_step = 0
            req.pending_budget = budget
        else:
            req = self.queue.get(nonce)
            if req is None:
                # mid-generation loss (TTL sweep / reset race): fail fast
                # instead of silently re-prefilling from one token
                self._futures.resolve(
                    TokenResult(
                        nonce=nonce, token_id=-1, step=step,
                        error=f"session expired for request {nonce}",
                    )
                )
                return
            # the driver echoes the accepted token as this step's input:
            # appending here keeps `ids` the exact replay source
            req.ids.append(token_ids[-1])
            req.pending_step = step
            req.pending_budget = budget
            self._answering.discard(nonce)
            if req.t_token is not None:
                # the driver's whole way back, as the scheduler feels it
                _ANSWER_WAIT_MS.observe(
                    (time.perf_counter() - req.t_token) * 1000.0
                )
                req.t_token = None
        self._wake()

    async def await_token(
        self, nonce: str, step: int, timeout: float
    ) -> TokenResult:
        return await self._futures.wait(nonce, step, timeout)

    def resolve_token(self, result: TokenResult) -> None:
        self._futures.resolve(result)

    # ---- tick loop ----------------------------------------------------
    def _wake(self) -> None:
        if self._kick is not None:
            self._kick.set()

    async def _drivers_turn(self) -> None:
        """Before a plan: the lanes the last tick handed a token get their
        turn to ask for the next one, so the tick's ONE decode step carries
        every lane.  A token reaches its driver a turn or two of the loop
        after `_apply` resolved it; a plan made sooner (a prompt is waiting,
        so `has_work` says go at once) would leave those lanes out, and a
        lane left out of every other tick decodes at half the rate while
        the step costs the same.  Bounded: a driver that is held up misses
        this tick, no more.

        `_owed` is dnet.sched.drivers_turn, opened at `_apply`'s end where a
        driver was owed an answer: it covers the park until the first one
        asks again as well as the wait here, and closes here.  The outcome
        (dnet_sched_drivers_turn_total) is told from the same moment: all
        of them may have answered before this coroutine was even resumed."""
        owed, self._owed = self._owed, None
        deadline = time.perf_counter() + DRIVER_TURN_S
        while self._answering and (left := deadline - time.perf_counter()) > 0:
            self._kick.clear()  # every answer sets it (send_tokens, reset_cache)
            try:
                await asyncio.wait_for(self._kick.wait(), left)
            except asyncio.TimeoutError:
                break
        if owed is None:
            outcome = DRIVERS_TURN_NONE
        else:
            owed.close()
            outcome = (
                DRIVERS_TURN_TIMED_OUT if self._answering else DRIVERS_TURN_ANSWERED
            )
        _DRIVERS_TURN.labels(outcome=outcome).inc()
        self._answering.clear()

    async def _tick_loop(self) -> None:
        loop = asyncio.get_running_loop()
        # The turn-around between two ticks, as the loop sees it.  `last`
        # is the tick the next one follows: it holds the decode step that
        # tick left in flight, which the next one reads (once the loop has
        # parked with nothing to do it holds that alone: an idle server's
        # seconds are no turn-around; None after a tick that failed);
        # `turn` is dnet.sched.turn, open from the resume after `last` to
        # the submit of the next tick (or to the park).  Held across awaits,
        # like `_owed` (obs.span says why that is sound).
        last: Optional[TickResult] = None
        turn: Optional[span] = None
        while True:
            await self._kick.wait()
            self._kick.clear()
            await asyncio.sleep(0)  # coalesce: let concurrent senders enqueue
            await self._drivers_turn()
            plan = None
            # the WHOLE tick body is guarded: an exception escaping this
            # loop would kill the task silently and wedge every current
            # and future request behind a kick event nobody waits on
            try:
                with span(SPAN_SCHED_PLAN):
                    plan = self.policy.plan(self.queue, self.engine)
                if plan.empty():
                    if turn is not None and not len(self.queue):
                        # no lane and no prompt: the loop parks, and the
                        # tick that ends the park follows no turn-around
                        turn.close()
                        turn = None
                        last = last.after_park() if last is not None else None
                    continue
                if plan.decode:
                    # lanes whose drivers have not asked yet: the step
                    # runs without them and costs the same
                    _LANES_LEFT_OUT.inc(sum(
                        r.pending_step is None for r in self.queue.decoding()
                    ))
                t0 = time.perf_counter()
                self._stamp_chunks(plan, t0)
                on_decode = None
                if plan.prefills:
                    # decode results leave the compute thread the moment
                    # the step is read, so their futures resolve — and the
                    # drivers ask for the next token — while this tick's
                    # prefill chunks are still running on the device.
                    # call_soon_threadsafe is the sanctioned bridge
                    # (domains.BRIDGE_MODULES); FIFO loop ordering
                    # guarantees every early resolve runs before the
                    # executor future resumes _apply.  (The lambda runs on
                    # the compute thread the moment the read ends: its
                    # clock reading is where the token's wait for its
                    # future starts.)
                    on_decode = lambda nonce, sample: loop.call_soon_threadsafe(  # noqa: E731
                        self._dispatch_decode, plan, nonce, sample,
                        time.perf_counter(),
                    )
                if turn is not None:
                    turn.close()
                    turn = None
                result = await loop.run_in_executor(
                    self._executor, execute_tick, self.engine, plan,
                    on_decode, last, time.perf_counter(),
                )
                t1 = time.perf_counter()
                observe_span(SPAN_TURN_TO_LOOP, (t1 - result.t_done) * 1000.0)
                last, turn = result, span(SPAN_SCHED_TURN).open()
                tick_ms = (t1 - t0) * 1000.0
                _TICK_MS.observe(tick_ms)
                _BATCH_TOKENS.labels(kind="prefill").observe(
                    float(result.prefill_tokens)
                )
                _BATCH_TOKENS.labels(kind="decode").observe(
                    float(result.decode_lanes)
                )
                with span(SPAN_SCHED_APPLY):
                    self._apply(plan, result)
                if self._answering:
                    self._owed = span(SPAN_SCHED_DRIVERS_TURN).open()
                if obs_enabled():
                    self._record_tick(tick_ms, result)
                if self.policy.has_work(self.queue, self.engine):
                    self._wake()
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                log.exception("scheduler tick failed")
                if plan is not None:
                    self._fail_plan(plan, str(exc))
                else:
                    # planning itself failed — deterministic over the same
                    # queue, so it would fail every tick: error the pending
                    # futures instead of wedging them to their timeouts
                    self._futures.fail_all(str(exc))
                last = None  # a failed tick is no start of a turn-around
                continue

    def _record_tick(self, tick_ms: float, result: TickResult) -> None:
        """One TickRecord into the flight ring (sched/flight.py): the
        black-box row GET /v1/debug/sched and the trace export replay.
        Queue depths are read AFTER _apply so the record reflects the
        state the tick left behind (matching the synced gauges)."""
        get_tick_recorder().record(
            tick_ms=tick_ms,
            budget_tokens=self.policy.token_budget,
            prefill_tokens=result.prefill_tokens,
            decode_lanes=result.decode_lanes,
            dispatched_lanes=result.dispatched_lanes,
            preempted=len(result.preempted),
            requeued=len(result.requeued),
            errors=len(result.errors),
            queue_depths={
                state: len(self.queue.by_state(state))
                for state in QUEUE_STATES
            },
            # every kind of the pool together (obs/phases.py KV_KINDS)
            kv_blocks_used=int(metric("dnet_kv_blocks_used").total()),
            kv_blocks_free=int(metric("dnet_kv_blocks_free").total()),
            kv_pool_blocks=int(metric("dnet_kv_pool_blocks").total()),
        )

    def _stamp_chunks(self, plan: TickPlan, t0: float) -> None:
        """The tick starting at `t0` runs these prefill chunks: count them,
        and for a request's FIRST chunk close its scheduler wait (enqueue
        to here) into dnet_sched_queue_wait_ms and the recorder's
        `sched_queue` span.  Forced like the other per-request summary
        spans: the segment ledger of every request needs it."""
        for chunk in plan.prefills:
            req = self.queue.get(chunk.nonce)
            if req is None:
                continue
            req.prefill_chunks += 1
            if req.t_first_chunk is None:
                req.t_first_chunk = t0
                wait_ms = (t0 - req.t_enqueued) * 1000.0
                _QUEUE_WAIT_MS.observe(wait_ms)
                get_recorder().span(
                    chunk.nonce, "sched_queue", wait_ms, force=True
                )

    def _stamp_first_token(self, req) -> None:
        """A request's first token just resolved: its prefill's real wall
        time (first chunk's tick start to here, the decode dispatches it
        shared ticks with included) and the ticks it took."""
        if req.t_first_token is not None or req.t_first_chunk is None:
            return
        req.t_first_token = time.perf_counter()
        wall_ms = (req.t_first_token - req.t_first_chunk) * 1000.0
        _PREFILL_WALL_MS.observe(wall_ms)
        _PREFILL_TICKS.observe(float(req.prefill_chunks))
        get_recorder().span(
            req.nonce, "prefill", wall_ms, force=True,
            tokens=req.prompt_len, chunks=req.prefill_chunks,
        )

    def _dispatch_decode(
        self, plan: TickPlan, nonce: str, sample, t_done: float
    ) -> None:
        """Early decode resolution (a tick with prefill chunks): runs on
        the loop via call_soon_threadsafe while the tick's prefill chunks
        are still executing.  _apply later skips nonces listed in
        TickResult.dispatched, so a result resolves exactly once."""
        step = plan.steps.get(nonce)
        if step is None:
            return
        self._resolve_step(nonce, step, sample=sample)
        _DELIVER_WAIT_MS.observe((time.perf_counter() - t_done) * 1000.0)

    def _fail_plan(self, plan: TickPlan, error: str) -> None:
        """A tick that died wholesale (executor torn down mid-flight):
        every participating pending step gets the error result."""
        for nonce, step in plan.steps.items():
            self._resolve_step(nonce, step, error=error)
        for chunk in plan.prefills:
            self._resolve_step(chunk.nonce, chunk.pending_step, error=error)

    def _resolve_step(
        self, nonce: str, step: int, sample=None, error: Optional[str] = None
    ) -> None:
        req = self.queue.get(nonce)
        if error is not None:
            self._futures.resolve(
                TokenResult(nonce=nonce, token_id=-1, step=step, error=error)
            )
            self.queue.remove(nonce)
            return
        decoding = req.decoding if req is not None else DecodingParams()
        self._futures.resolve(
            self.engine.token_result(nonce, sample, step=step, decoding=decoding)
        )
        if req is not None and req.pending_step == step:
            req.pending_step = None
            req.pending_budget = None
            self._answering.add(nonce)
            req.t_token = time.perf_counter()

    def _apply(self, plan: TickPlan, result: TickResult) -> None:
        """The tick's results into the request state machines and the
        drivers' futures.  `result` holds host data only (the compute
        thread read it): nothing here touches a device array."""
        for nonce in result.preempted:
            self.queue.requeue(nonce, reason_preempt=True)
            log_event("preempted", rid=nonce, reason="policy")
        for nonce in result.requeued:
            req = self.queue.get(nonce)
            if req is None:
                continue
            if req.starved + 1 >= MAX_STARVED_REQUEUES:
                self._resolve_step(
                    nonce,
                    req.pending_step if req.pending_step is not None else 0,
                    error=(
                        "paged KV pool exhausted: prefill starved after "
                        f"{req.starved + 1} requeues"
                    ),
                )
                continue
            self.queue.requeue(nonce, reason_preempt=False)
            _PREEMPTIONS.labels(reason="starved_requeue").inc()
            log_event("preempted", rid=nonce, reason="starved_requeue")
        for nonce, pos in result.progress.items():
            req = self.queue.get(nonce)
            if req is not None and req.state not in (STATE_DECODING,):
                req.prefilled = pos
        for nonce, sample in result.adopted.items():
            req = self.queue.get(nonce)
            if req is None:
                continue
            req.state = STATE_DECODING
            req.prefilled = len(req.ids)
            req.starved = 0
            step = req.pending_step if req.pending_step is not None else 0
            self._resolve_step(nonce, step, sample=sample)
            self._stamp_first_token(req)
        dispatched = set(result.dispatched)
        for nonce, sample in result.decode_results.items():
            if nonce in dispatched:
                continue  # already resolved mid-tick (on_decode)
            step = plan.steps.get(nonce)
            if step is None:
                continue
            self._resolve_step(nonce, step, sample=sample)
            # readback ended on the compute thread -> future resolved here
            # (a tick without chunks: nothing ran in between)
            _DELIVER_WAIT_MS.observe(
                (time.perf_counter() - result.t_decode_done) * 1000.0
            )
        for nonce, msg in result.errors.items():
            step = plan.steps.get(nonce)
            if step is None:
                req = self.queue.get(nonce)
                step = (
                    req.pending_step
                    if req is not None and req.pending_step is not None
                    else 0
                )
            self._resolve_step(nonce, step, error=msg)
        self.queue.sync_gauges()
