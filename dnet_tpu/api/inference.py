"""The decode driver: chat request -> token loop -> SSE chunks.

Reference: src/dnet/api/inference.py:66-311 — template/encode, per-request
nonce, per-token send/await/detokenize loop, EOS + stop-sequence + length
stops, usage and profile metrics, and non-streaming aggregation.

The order of a token's work (`InferenceManager._run`): DECIDE, ask, two
hops, DELIVER.  A driver asks for its next token before it delivers the one
it has.  What the serving side needs from a driver before it plans the next
decode step is one bit (does the lane go on?), which the driver knows from
the token id, the length and the deadline, a few tens of microseconds after
it wakes; what the CLIENT needs (recorder, SLO tracker, detokenizer,
logprob entry, chunk, SSE flush) is 0.3 ms a lane and none of the
scheduler's business.  With the ask at the top of the next iteration, as it
used to stand, every lane's delivery ran on the event loop between two decode
steps and the chip waited for all of them (a tenth to a sixth of a slice on
the two fastest decoders: PERF.md section 6, PR 51).  So the ask leaves at
the end of DECIDE, the driver gives the loop its turn
(`_behind_the_ticks_submit`), the tick loop plans and hands the step to the
compute thread, and the deliveries run on the loop while that thread
prepares, enqueues and reads.  The adapter protocol is unchanged
(`send_tokens(step)` then `await_token(step)`): only WHEN the send leaves
moved.
"""

from __future__ import annotations

import asyncio
import time
from typing import AsyncIterator, Optional

from dnet_tpu.api.schemas import (
    ChatChoice,
    ChatChoiceDelta,
    ChatCompletionChunk,
    ChatCompletionRequest,
    ChatCompletionResponse,
    ChatMessage,
    ChatStreamChoice,
    ChoiceLogprobs,
    LogprobEntry,
    RequestMetrics,
    TopLogprob,
    Usage,
    new_request_id,
)
from dnet_tpu.admission.controller import (
    AdmissionController,
    AdmissionRejected,
    Deadline,
    deadline_expired,
    request_deadline,
)
from dnet_tpu.api.strategies import ApiAdapterBase
from dnet_tpu.core.types import (  # the two errors live there; re-exported
    DecodingParams,
    EngineCapabilityError,
    InferenceError,
)
from dnet_tpu.obs import critical_path, get_recorder, get_slo_tracker, metric
from dnet_tpu.obs.events import bind, log_event
from dnet_tpu.obs.phases import DRIVER_ASK_AHEAD, DRIVER_ASK_AT_STEP
from dnet_tpu.resilience.checkpoint import ResumableDecode
from dnet_tpu.resilience.policy import is_retryable
from dnet_tpu.utils.logger import get_logger
from dnet_tpu.utils.tokenizer import Detokenizer

log = get_logger()

_TTFT_MS = metric("dnet_ttft_ms")
_REQUESTS = metric("dnet_requests_total")
_REQUEST_ERRORS = metric("dnet_request_errors_total")
_TOKENS_TOTAL = metric("dnet_tokens_generated_total")
_CANCELS = metric("dnet_cancel_propagated_total")
_ASKS = metric("dnet_api_driver_asks_total")
_ASKS_AHEAD = _ASKS.labels(order=DRIVER_ASK_AHEAD)
_ASKS_AT_STEP = _ASKS.labels(order=DRIVER_ASK_AT_STEP)


class PromptTooLongError(InferenceError):
    """Maps to HTTP 400 (client error) rather than 500."""


class ServiceDegradedError(InferenceError):
    """Ring has DOWN shards: maps to HTTP 503 immediately (fast-fail
    instead of the reference's 300s token-future timeout)."""


class DeadlineExceededError(InferenceError):
    """The request's end-to-end deadline expired mid-flight: maps to
    HTTP 504 (api/http.py).  Raised by the driver's between-step check or
    classified from a shard's `deadline exceeded` error final."""


class BackpressureError(InferenceError):
    """A capacity limit refused the work (paged-KV pool exhausted, lane /
    batch-slot pools full): maps to HTTP 429 + Retry-After, never 500 —
    the client should back off and retry, nothing is broken."""


# capacity-exhaustion signatures that cross the compute/wire boundary as
# error STRINGS (TokenResult.error); the single choke point turning them
# back into typed backpressure
_BACKPRESSURE_MARKERS = (
    "paged KV pool exhausted",   # kv/paged.py KVPoolExhausted
    "no free lanes",             # shard/lanes.py lane-pool overflow
    "no free batch slots",       # core/batch.py slot-pool overflow
)


def classify_result_error(error: str) -> InferenceError:
    """Map a step's error string to the typed exception the HTTP layer
    translates into a status code (429 backpressure / 504 deadline /
    500 otherwise)."""
    if "deadline exceeded" in error:
        return DeadlineExceededError(error)
    if any(marker in error for marker in _BACKPRESSURE_MARKERS):
        return BackpressureError(error)
    return InferenceError(error)


def _event_status(exc: BaseException) -> int:
    """HTTP status a failed request's `request_complete` wide event will
    carry — the same mapping api/http.py `_map_inference_errors` applies,
    duplicated here because the event must be journaled where the request
    FINISHES (the driver), not where the response serializes."""
    if isinstance(exc, AdmissionRejected):
        return 503 if exc.reason == "draining" else 429
    if isinstance(exc, BackpressureError):
        return 429
    if isinstance(exc, DeadlineExceededError):
        return 504
    if isinstance(exc, PromptTooLongError):
        return 400
    if isinstance(exc, EngineCapabilityError):
        return 422
    if isinstance(exc, ServiceDegradedError):
        return 503
    return 500


def _kv_mode(engine) -> str:
    """The serving engine's KV layout, by core/batch.py's names."""
    store = getattr(engine, "kv_store", None)
    if getattr(engine, "kv_pool", None) is not None:
        return "state+paged" if getattr(store, "in_place", False) else "paged"
    return "state" if store is not None else "dense"


def _resolved_modes(adapter) -> dict:
    """The serving modes a postmortem reader wants next to a request's
    outcome: resolved wire codec, the serving engine's KV layout, TP
    degree, and whether the continuous-batching scheduler served it."""
    from dnet_tpu.config import get_settings

    s = get_settings()
    engine = getattr(adapter, "engine", None)
    return {
        "codec": s.wire.codec,
        "kv": _kv_mode(engine),
        "tp": int(s.tp.tp),
        "sched": type(adapter).__name__ == "SchedulerAdapter",
    }


def completion_logprobs(entries: list, offset0: int = 0):
    """Chat-style LogprobEntry list -> the OpenAI text_completion logprobs
    shape ({tokens, token_logprobs, top_logprobs, text_offset})."""
    from dnet_tpu.api.schemas import CompletionLogprobs

    out = CompletionLogprobs()
    offset = offset0
    for e in entries:
        out.tokens.append(e.token)
        out.token_logprobs.append(e.logprob)
        out.top_logprobs.append({t.token: t.logprob for t in e.top_logprobs})
        out.text_offset.append(offset)
        offset += len(e.token)
    return out


def _holdback_len(text: str, stop_seqs: list[str]) -> int:
    """Length of the longest suffix of `text` that is a proper prefix of any
    stop sequence (must be held back — the next token may complete a stop)."""
    hold = 0
    for s in stop_seqs:
        for k in range(min(len(s) - 1, len(text)), 0, -1):
            if text.endswith(s[:k]):
                hold = max(hold, k)
                break
    return hold


def _scan_for_stop(text: str, stop_seqs: list[str]) -> tuple[str, str, bool]:
    """Split the not-yet-emitted `text` of a request with stop sequences into
    (what may be emitted now, what is held back, whether a stop matched).
    A match discards itself and everything after it; without one, the
    longest suffix that could still grow into a stop stays held."""
    for s in stop_seqs:
        idx = text.find(s)
        if idx != -1:
            return text[:idx], "", True
    emit_upto = len(text) - _holdback_len(text, stop_seqs)
    return text[:emit_upto], text[emit_upto:], False


async def _behind_the_ticks_submit() -> None:
    """Give the event loop its turn, twice, between a driver's ask for its
    next token and the delivery of the one it has, so that the delivery
    queues BEHIND the tick loop's submit of the next decode step
    (sched/engine.py `_tick_loop`) and runs while the compute thread
    prepares, enqueues and reads.

    The two hops it stands behind, from the first ask of a turn to the
    submit, where the tick loop is PARKED on `_kick.wait()` after a
    step-only tick (`has_work` is False until a driver asks): (1) its
    wake-up; (2) its own coalescing `await asyncio.sleep(0)`, after which
    `_drivers_turn` finds every lane answered, plans and calls
    `run_in_executor` without suspending again.  The ready queue, N drivers
    woken by one tick (a = DECIDE and ask, y = after the first yield,
    b = DELIVER):
    `[d1a..dNa] -> [T1, d1y..dNy] -> [d1y..dNy, T2] -> [T2, d1b..dNb]`.

    The loop's other state is ONE hop from its submit: the kick is already
    set when the tick ends (a prompt waits; or the last turn waited in
    `_drivers_turn`, whose way out leaves the kick set by the answer that
    ended the wait), so `_kick.wait()` returns at once, the loop takes its
    `sleep(0)` while `_apply`'s resolutions reach the drivers, and then
    waits for their asks inside `_drivers_turn`; the last ask is its
    wake-up.  There the second yield costs a pass of the queue.  Either
    state keeps itself up in a closed loop.

    One yield is not enough for the parked state (the order would be asks,
    wake-up, ALL deliveries, plan).  Hang-free by construction: if the
    loop's hops ever change, deliveries fall back to between the steps,
    where they used to be, and tests/subsystems/test_ask_ahead.py says so
    (it holds both states); nothing else breaks."""
    await asyncio.sleep(0)
    await asyncio.sleep(0)


class InferenceManager:
    def __init__(
        self,
        adapter: ApiAdapterBase,
        request_timeout_s: float = 300.0,
        max_concurrent: int = 8,
        admission: Optional[AdmissionController] = None,
    ) -> None:
        self.adapter = adapter
        self.tokenizer = None  # set by ModelManager on load
        self.model_id: Optional[str] = None
        self.request_timeout_s = request_timeout_s
        self._max_concurrent = max_concurrent
        if admission is None:
            from dnet_tpu.config import get_settings

            adm = get_settings().admission
            admission = AdmissionController(
                max_concurrent,
                queue_depth=adm.admit_queue_depth,
                queue_timeout_s=adm.admit_queue_timeout_s,
            )
        # the admission-aware front end replacing the old raw semaphore:
        # bounded queue, deadline-aware shedding, Retry-After estimates,
        # and drain mode all live here (dnet_tpu/admission/)
        self.admission = admission
        self.failure_monitor = None  # RingFailureMonitor in ring mode
        # detached cancel-cleanup tasks (client-disconnect fan-out): strong
        # refs so the loop's weak task set cannot GC a reclaim mid-flight
        self._cancel_cleanups: set = set()

    def set_concurrency_limit(self, n: Optional[int]) -> None:
        """Re-cap request admission (ring lanes: the shard lane pools hold
        exactly `lanes` KV rows, so admitting more mid-decode requests than
        lanes would hard-fail the overflow instead of queueing it).  None
        restores the configured default.  Requests already admitted finish
        under the old cap; new arrivals use the new one."""
        self.admission.set_capacity(n)

    @property
    def ready(self) -> bool:
        return self.tokenizer is not None and self.model_id is not None

    def _decoding(self, req: ChatCompletionRequest) -> DecodingParams:
        return DecodingParams(
            temperature=req.temperature,
            top_p=req.top_p,
            top_k=req.top_k,
            min_p=req.min_p,
            repetition_penalty=req.repetition_penalty,
            min_tokens_to_keep=req.min_tokens_to_keep,
            logprobs=req.logprobs_enabled,
            top_logprobs=req.top_logprobs,
            seed=req.seed,
            logit_bias=req.logit_bias_ids(),
            # EOS ids ride along so ring decode grants can halt shard-side
            stop_token_ids=tuple(self.tokenizer.eos_token_ids)
            if self.tokenizer is not None
            else (),
        )

    def _logprob_entry(self, result, text: str) -> LogprobEntry:
        top = [
            TopLogprob(
                token=self.tokenizer.decode([tid]),
                logprob=lp,
                bytes=list(self.tokenizer.decode([tid]).encode("utf-8")),
            )
            for tid, lp in (result.top_logprobs or [])
        ]
        return LogprobEntry(
            token=text,
            logprob=result.logprob or 0.0,
            bytes=list(text.encode("utf-8")),
            top_logprobs=top,
        )

    def _degraded(self) -> bool:
        return self.failure_monitor is not None and self.failure_monitor.degraded

    def _bound_await(self, resume: ResumableDecode, deadline: Deadline) -> None:
        """Re-bound the token await for the step about to be asked for: a
        shard that hangs without dying must surface the 504 when the
        deadline passes, not after the frozen request timeout (remaining()
        shrinks every step)."""
        resume.timeout_s = min(
            self.request_timeout_s, max(deadline.remaining(), 0.001)
        )

    def _deadline_for(self, req) -> Optional[Deadline]:
        from dnet_tpu.config import get_settings

        return request_deadline(
            getattr(req, "deadline_s", None),
            get_settings().admission.request_deadline_s,
        )

    async def generate_stream(
        self, req: ChatCompletionRequest
    ) -> AsyncIterator[ChatCompletionChunk]:
        """Per-token chunks; final chunk carries finish_reason/usage/metrics.

        Admission happens on the consumer's FIRST `anext`: a shed request
        raises `AdmissionRejected` (429 + Retry-After upstream) before any
        chunk — the HTTP layer peeks the first chunk before committing to
        an SSE 200, so rejections keep real status codes."""
        if not self.ready:
            raise InferenceError("no model loaded")
        deadline = self._deadline_for(req)
        t_admit = time.perf_counter()
        try:
            async with self.admission.slot(deadline):
                # queued-at-the-gate time, measured here because the rid
                # does not exist yet: _run backdates it onto the timeline
                # as the admission_wait segment (obs/critical_path.py)
                admit_wait_ms = (time.perf_counter() - t_admit) * 1000.0
                async for chunk in self._run(
                    req, deadline, admit_wait_ms=admit_wait_ms
                ):
                    yield chunk
        except AdmissionRejected as rej:
            # shed at the gate, before a rid ever existed: still one
            # finished request, so it still owes its request_complete —
            # the only variant without a rid (nothing to correlate)
            log_event(
                "request_complete",
                status=_event_status(rej),
                finish_reason="shed",
                shed=True,
                shed_reason=rej.reason,
                tokens=0,
                total_ms=round((time.perf_counter() - t_admit) * 1000.0, 3),
            )
            raise

    async def _run(
        self,
        req: ChatCompletionRequest,
        deadline: Optional[Deadline] = None,
        admit_wait_ms: float = 0.0,
    ) -> AsyncIterator[ChatCompletionChunk]:
        """One request's token loop.  Each token of step s is handled in
        this order, and the order is the point (module docstring):

        1. DECIDE what must be known before the lane may step again, and
           nothing else: the error, the end-of-sequence ids, the resume
           checkpoint, the length, the deadline and the ring's health, and,
           ONLY where the request has stop sequences (read off the request,
           no setting), the detokenizer and the stop search, because a stop
           string is found in text;
        2. if the lane goes on, ask for step s + 1 (`t_step` is stamped
           here: the `decode_step` span and the SLO tracker measure ask to
           token) and give the loop two hops (`_behind_the_ticks_submit`);
           the next iteration's top does not send again.  An ask that
           raises is not sent twice: what it raised is raised at that top,
           inside the `try` that owns the resume path, after token s is
           delivered, which is where and when a failed send always
           surfaced;
        3. DELIVER: recorder, SLO tracker, counters, the detokenizer where
           DECIDE did not run it, the logprob entry, hold-back, the chunk's
           `yield` (and behind it the HTTP layer's serialisation and flush).

        A lane that stops at step s (eos, stop sequence, length, deadline)
        asks for nothing.  A client that goes away between the ask and the
        delivery closes this generator: `reset_cache` frees the lane and the
        engine drops the step in flight as a surplus step."""
        rid = new_request_id()
        nonce = rid
        # request-identity binding (obs/events.py): every log record and
        # wide event in this request's dynamic extent carries the rid
        # automatically.  Entered manually so the function stays flat; the
        # finally below always exits it (bind guards the cross-Context
        # reset a loop-finalized generator would otherwise trip).
        ctx = bind(rid=rid, node="api")
        ctx.__enter__()
        t_start = time.perf_counter()
        t_first: Optional[float] = None
        generated = 0
        finish_reason = "length"
        recorder = get_recorder()
        slo = get_slo_tracker()  # rolling windows behind /health + dnet_slo_*
        completed = False  # guards the one-per-request request_complete
        cleanup_detached = False
        resume = None  # built once the wire session is prepared
        prompt_ids: list = []
        try:
            if self._degraded():
                raise ServiceDegradedError(
                    f"ring degraded: shard(s) "
                    f"{self.failure_monitor.down_shards()} down"
                )
            tok = self.tokenizer
            prompt = req.render_prompt(tok)  # chat template or raw
            prompt_ids = tok.encode(prompt)
            decoding = self._decoding(req)
            stop_seqs = req.stop_sequences()
            eos = tok.eos_token_ids
            detok = Detokenizer(tok)
            max_new = req.completion_tokens_limit

            capacity = self.adapter.max_seq()
            if capacity is not None:
                if len(prompt_ids) >= capacity:
                    raise PromptTooLongError(
                        f"prompt is {len(prompt_ids)} tokens but the serving "
                        f"context is {capacity}"
                    )
                max_new = min(max_new, capacity - len(prompt_ids))

            recorder.begin(rid)  # flight-recorder timeline (rid == nonce)
            if admit_wait_ms > 0.0:
                # the wait happened BEFORE this timeline's origin: a
                # negative start offset keeps [0, e2e] the admitted window
                # while the segment ledger still carries the queued time
                # (and the sum still reconciles against the client-measured
                # E2E)
                recorder.span(
                    rid, "admission_wait", admit_wait_ms,
                    t_ms=-admit_wait_ms, force=True,
                )
            _REQUESTS.inc()
            pending = ""  # emitted-text buffer held back for stop-seq match
            held_entries: list = []  # logprob entries for held-back tokens
            emitted_ahead = 0  # emitted chars owned by the oldest held entry
            first_chunk = True  # first streamed delta carries role=assistant
            stopped_by_seq = False

            await self.adapter.reset_cache(nonce)
            if deadline is not None:
                # the deadline rides every activation frame header from
                # here: shards shed expired frames at dequeue (zero
                # compute), and the lane flusher sheds expired members
                # (api/ring.py)
                self.adapter.set_deadline(nonce, deadline.t_deadline)
            # resume controller: owns the wire nonce + step mapping so a
            # mid-decode shard failure can (behind DNET_RESILIENCE_RESUME=1)
            # checkpoint, wait out recovery, and replay prompt+generated on
            # the new topology without this generator — or the client —
            # noticing.  adapter is a GETTER: auto-recovery swaps it.
            resume = ResumableDecode(
                lambda: self.adapter,
                rid,
                prompt_ids,
                monitor=self.failure_monitor,
                timeout_s=self.request_timeout_s,
            )
            send_ids = list(prompt_ids)
            asked = False  # DECIDE on the token before already sent this step's ask
            ask_error: Optional[Exception] = None  # ... or tried, and this came of it
            t_step = 0.0  # stamped where a step's ask leaves
            for step in range(max_new):
                if not asked:
                    if deadline is not None:
                        if deadline.expired:
                            # between-step shed: the client's deadline
                            # passed, so every further token is work nobody
                            # is waiting for
                            deadline_expired("api_step")
                            raise DeadlineExceededError(
                                f"request deadline expired after "
                                f"{generated} token(s)"
                            )
                        self._bound_await(resume, deadline)
                    t_step = time.perf_counter()
                try:
                    if ask_error is not None:
                        exc, ask_error = ask_error, None
                        raise exc
                    if not asked:
                        # re-check per step: the monitor's one-shot
                        # fail_pending only covers futures pending at the
                        # DOWN transition; a request at a step boundary
                        # would otherwise hang the full timeout
                        if self._degraded():
                            raise ServiceDegradedError(
                                f"ring degraded: shard(s) "
                                f"{self.failure_monitor.down_shards()} down"
                            )
                        await resume.send(
                            send_ids, decoding, step, budget=max_new - step
                        )
                        _ASKS_AT_STEP.inc()
                    asked = False
                    result = await resume.await_token(step)
                    if result.error:
                        # typed: deadline / backpressure errors keep their
                        # HTTP semantics (504 / 429) across the wire
                        raise classify_result_error(result.error)
                except asyncio.CancelledError:
                    raise
                except Exception as exc:
                    # transparent resume: wait for auto-recovery, replay
                    # prompt + generated under a fresh nonce, and take the
                    # replay's sampled token as THIS step's result.
                    # Candidates: error tokens / degraded ring / await
                    # timeout, AND raw transport failures from the send
                    # path (a dead stream past its re-open budget raises
                    # ConnectionError or gRPC UNAVAILABLE here, not an
                    # error TokenResult).  Non-transient logic errors
                    # propagate.  None = resume disabled/exhausted —
                    # surface the failure as before (fast 503 /
                    # InferenceError).
                    if (
                        deadline is not None
                        and deadline.expired
                        and isinstance(exc, asyncio.TimeoutError)
                    ):
                        # the deadline-bounded await lapsed: this is the
                        # deadline expiring mid-step, not a generic hang
                        deadline_expired("api_step")
                        raise DeadlineExceededError(
                            f"request deadline expired awaiting step "
                            f"{step}"
                        ) from exc
                    if isinstance(
                        exc, (DeadlineExceededError, BackpressureError)
                    ):
                        # shed work is not failed work: replaying a request
                        # nobody waits for (or that capacity just refused)
                        # would recreate the very overload being shed
                        raise
                    if not (
                        isinstance(
                            exc, (InferenceError, asyncio.TimeoutError)
                        )
                        or is_retryable(exc)
                    ):
                        raise
                    result = await resume.try_resume(
                        exc, decoding, step, budget=max_new - step
                    )
                    if result is None:
                        raise
                # one span per emitted token: ask -> token resolved (grant /
                # chunk-buffered steps resolve in ~0ms, visibly so)
                t_token = time.perf_counter()
                step_ms = (t_token - t_step) * 1000

                # ---- DECIDE: does the lane step again?  Nothing else. ----
                token = result.token_id
                at_eos = token in eos
                piece: Optional[str] = None  # the token's own text
                delta = ""
                stopped = False
                if not at_eos:
                    send_ids = [token]
                    # checkpoint the accepted token: a later resume replays
                    # prompt + generated so far (EOS never extends context
                    # and never needs replaying)
                    resume.record(token)
                    if stop_seqs:
                        # a stop string is found in TEXT, so for such a
                        # request the detokenizer comes before the ask.
                        # Never emit text at or beyond a match, and hold
                        # back any suffix that could still become one.
                        piece = detok.add(token)
                        delta, pending, stopped = _scan_for_stop(
                            pending + piece, stop_seqs
                        )
                if (
                    not at_eos
                    and not stopped
                    and step + 1 < max_new
                    # what stands at the loop's top: a lane that would be
                    # shed or failed there is not asked for here, and the
                    # top raises as it always did, after this delivery
                    and not (deadline is not None and deadline.expired)
                    and not self._degraded()
                ):
                    if deadline is not None:
                        self._bound_await(resume, deadline)
                    t_step = time.perf_counter()
                    try:
                        await resume.send(
                            send_ids, decoding, step + 1,
                            budget=max_new - (step + 1),
                        )
                    except Exception as exc:
                        # nothing swallowed and nothing sent twice: it is
                        # raised at the step's own top, inside the `try`
                        # that owns the resume path, after this delivery
                        ask_error = exc
                    else:
                        asked = True
                        _ASKS_AHEAD.inc()
                        await _behind_the_ticks_submit()

                # ---- DELIVER: the books, the text, the chunk ----
                recorder.span(rid, "decode_step", step_ms, step=step)
                if step > 0:
                    # step 0 is the prefill pass — TTFT owns it; folding
                    # it into the decode window would read a long prompt
                    # as a decode-p95 SLO burn
                    slo.record_decode(step_ms)
                if t_first is None:
                    t_first = t_token
                    ttft_ms = (t_first - t_start) * 1000
                    _TTFT_MS.observe(ttft_ms)
                    slo.record_ttft(ttft_ms)
                    # force: summary spans must survive the per-request
                    # span cap on generations long enough to out-span it
                    recorder.span(rid, "ttft", ttft_ms, t_ms=0.0, force=True)
                generated += 1
                _TOKENS_TOTAL.inc()

                if at_eos:
                    finish_reason = "stop"
                    break

                if piece is None:
                    piece = delta = detok.add(token)
                # one logprob entry per generated token, carrying the
                # token's OWN text — holdback buffering must not smear one
                # token's logprob across text accumulated from several
                if req.logprobs_enabled:
                    held_entries.append(self._logprob_entry(result, piece))

                if delta or stopped:
                    logprobs = None
                    if req.logprobs_enabled and held_entries:
                        # flush only entries whose token text is FULLY
                        # emitted; an entry whose text straddles the
                        # holdback boundary stays held with its text (a
                        # later stop match must be able to discard it —
                        # flushing early would leave a logprob entry for
                        # text that never reaches the client)
                        budget = emitted_ahead + len(delta)
                        kept = []
                        while held_entries and len(held_entries[0].token) <= budget:
                            budget -= len(held_entries[0].token)
                            kept.append(held_entries.pop(0))
                        if stopped:
                            # entries for the matched stop text are
                            # discarded with it
                            held_entries = []
                            emitted_ahead = 0
                        else:
                            emitted_ahead = budget
                        if kept:
                            logprobs = ChoiceLogprobs(content=kept)
                    yield ChatCompletionChunk(
                        id=rid,
                        model=req.model,
                        choices=[
                            ChatStreamChoice(
                                # the FIRST delta carries the role, as the
                                # OpenAI stream protocol (and client) expect
                                delta=ChatChoiceDelta(
                                    role=("assistant" if first_chunk else None),
                                    content=delta,
                                ),
                                logprobs=logprobs,
                            )
                        ],
                    )
                    first_chunk = False
                if stopped:
                    finish_reason = "stop"
                    stopped_by_seq = True
                    break

            # On EOS/length the held-back text is real content — flush it
            # (with any logprob entries still held back with it).  Only a
            # stop-sequence match discards its own matched text.
            tail = pending + detok.flush() if not stopped_by_seq else ""
            if tail or (held_entries and not stopped_by_seq):
                logprobs = (
                    ChoiceLogprobs(content=held_entries)
                    if req.logprobs_enabled and held_entries and not stopped_by_seq
                    else None
                )
                yield ChatCompletionChunk(
                    id=rid,
                    model=req.model,
                    choices=[
                        ChatStreamChoice(
                            delta=ChatChoiceDelta(
                                role=("assistant" if first_chunk else None),
                                content=tail,
                            ),
                            logprobs=logprobs,
                        )
                    ],
                )
                first_chunk = False

            t_end = time.perf_counter()
            usage = Usage(
                prompt_tokens=len(prompt_ids),
                completion_tokens=generated,
                total_tokens=len(prompt_ids) + generated,
            )
            # the request span closes the timeline; RequestMetrics is a VIEW
            # over the recorded spans (ttft + per-step + this), not a second
            # hand-maintained set of stopwatch fields
            recorder.span(
                rid, "request", (t_end - t_start) * 1000, t_ms=0.0,
                tokens=generated, prompt_tokens=len(prompt_ids),
                finish_reason=finish_reason, force=True,
            )
            # the segment ledger feeds dnet_request_segment_ms for EVERY
            # request (aggregate attribution is a serving concern, not a
            # profile=true opt-in); the structured dict additionally rides
            # the final chunk when the client asked to profile
            ledger = critical_path.decompose(recorder.timeline(rid))
            critical_path.observe(ledger)
            # the canonical wide event: exactly ONE per finished request,
            # embedding the same ledger so status/tokens/total_ms reconcile
            # with dnet_request_segment_ms by construction
            log_event(
                "request_complete",
                status=200,
                finish_reason=finish_reason,
                shed=False,
                tokens=generated,
                prompt_tokens=len(prompt_ids),
                total_ms=round((t_end - t_start) * 1000.0, 3),
                modes=_resolved_modes(self.adapter),
                critical_path=ledger,
            )
            completed = True
            metrics = None
            if req.profile:
                metrics = RequestMetrics.from_timeline(recorder.timeline(rid))
                metrics.critical_path = ledger
            yield ChatCompletionChunk(
                id=rid,
                model=req.model,
                choices=[
                    ChatStreamChoice(
                        # a stream with zero content deltas (immediate EOS /
                        # whole output held back by a stop-seq) still owes
                        # the client the initial role chunk
                        delta=ChatChoiceDelta(
                            role=("assistant" if first_chunk else None)
                        ),
                        finish_reason=finish_reason,
                    )
                ],
                usage=usage,
                metrics=metrics,
            )
            slo.record_request(ok=True)
        except (GeneratorExit, asyncio.CancelledError):
            # the client went away (an SSE disconnect closes this
            # generator; a task cancel lands here too): fan the cancel out
            # through the ring NOW as a DETACHED task — the dying request
            # task must not be able to interrupt the reset_cache fan-out
            # that reclaims shard lanes and paged-KV blocks.  The
            # admission slot itself frees in generate_stream's
            # `async with` as this exception keeps propagating.
            _CANCELS.inc()
            if not completed:
                # still a finished request from the server's side: 499 is
                # the client-closed-request convention
                log_event(
                    "request_complete",
                    status=499,
                    finish_reason="cancelled",
                    shed=False,
                    tokens=generated,
                    prompt_tokens=len(prompt_ids),
                    total_ms=round(
                        (time.perf_counter() - t_start) * 1000.0, 3
                    ),
                    modes=_resolved_modes(self.adapter),
                )
                completed = True
            cleanup_detached = True
            if resume is not None:
                task = asyncio.ensure_future(resume.cleanup())
                self._cancel_cleanups.add(task)
                task.add_done_callback(self._cancel_cleanups.discard)
            raise
        except Exception as exc:
            # client disconnects / task cancels (BaseException) are not
            # server errors; InferenceError and friends are.  Shed work is
            # not FAILED work either (the PR 5 status-code contract): a 429
            # capacity refusal or 504 expired deadline must not burn the
            # availability SLO or the error counter — otherwise every
            # overload the admission layer survives correctly would read
            # as an outage, and the load harness's availability (which
            # also excludes shed) could never cross-validate against the
            # live gauge.  Shed volume stays visible through
            # dnet_admit_rejected_total / dnet_deadline_exceeded_total.
            shed = isinstance(exc, (BackpressureError, DeadlineExceededError))
            if not shed:
                _REQUEST_ERRORS.inc()
                slo.record_request(ok=False)
            if not completed:
                log_event(
                    "request_complete",
                    status=_event_status(exc),
                    finish_reason="shed" if shed else "error",
                    shed=shed,
                    shed_reason=(
                        "deadline"
                        if isinstance(exc, DeadlineExceededError)
                        else "backpressure" if shed else ""
                    ),
                    error=str(exc)[:200],
                    tokens=generated,
                    prompt_tokens=len(prompt_ids),
                    total_ms=round(
                        (time.perf_counter() - t_start) * 1000.0, 3
                    ),
                    modes=_resolved_modes(self.adapter),
                )
                completed = True
            raise
        finally:
            # guarded cleanup: reset_cache can itself raise when the ring
            # just died, which would mask the original error and crash the
            # SSE generator — the controller logs + swallows transport
            # errors on this path only
            try:
                if resume is not None and not cleanup_detached:
                    await resume.cleanup()
            finally:
                ctx.__exit__(None, None, None)

    async def embeddings(self, req) -> "EmbeddingsResponse":
        """Serve /v1/embeddings: mean-pooled final-hidden-state vectors
        (beyond the reference, which schemas the route but never serves
        it).  Accepts the full OpenAI input envelope — a string, a list of
        strings, a token list, or a batch of token lists — and the base64
        encoding_format.  Embeddings compete for the same compute as
        decode, so they pass the same admission controller — an
        embeddings burst is bounded, shed with 429s, and drained like
        everything else."""
        async with self.admission.slot(self._deadline_for(req)):
            return await self._embeddings(req)

    async def _embeddings(self, req) -> "EmbeddingsResponse":
        from dnet_tpu.api.schemas import (
            EmbeddingData,
            EmbeddingsResponse,
            EmbeddingsUsage,
        )

        raw = req.input
        if isinstance(raw, str):
            batches = [self.tokenizer.encode(raw)]
        elif raw and isinstance(raw[0], str):
            batches = [self.tokenizer.encode(s) for s in raw]
        elif raw and isinstance(raw[0], list):
            batches = [list(ids) for ids in raw]
        else:
            batches = [list(raw)]
        if any(not b for b in batches):
            raise ValueError("embeddings input contains an empty entry")
        vecs = await self.adapter.embed(batches)
        if req.encoding_format == "base64":
            import base64

            import numpy as np

            vecs = [
                base64.b64encode(
                    np.asarray(v, dtype=np.float32).tobytes()
                ).decode("ascii")
                for v in vecs
            ]
        data = [EmbeddingData(index=i, embedding=v) for i, v in enumerate(vecs)]
        n_tok = sum(len(b) for b in batches)
        return EmbeddingsResponse(
            data=data,
            model=self.model_id or req.model,
            usage=EmbeddingsUsage(prompt_tokens=n_tok, total_tokens=n_tok),
        )

    async def generate_completion(self, req) -> "CompletionResponse":
        """Legacy /v1/completions (non-streaming): aggregate the same decode
        stream into a text_completion object."""
        from dnet_tpu.api.schemas import CompletionChoice, CompletionResponse

        rid, text, logprob_entries, finish_reason, usage, metrics = (
            await self._collect(req)
        )
        offset0 = 0
        if req.echo:
            text = req.prompt_text() + text
            offset0 = len(req.prompt_text())
        return CompletionResponse(
            id=rid.replace("chatcmpl", "cmpl"),
            model=req.model,
            choices=[
                CompletionChoice(
                    text=text,
                    logprobs=completion_logprobs(logprob_entries, offset0)
                    if req.logprobs_enabled
                    else None,
                    finish_reason=finish_reason,
                )
            ],
            usage=usage,
            metrics=metrics,
        )

    async def _collect(self, req):
        """Drain the decode stream into (rid, text, logprob entries,
        finish_reason, usage, metrics) — shared by both non-streaming
        endpoints."""
        parts: list[str] = []
        logprob_entries: list[LogprobEntry] = []
        usage = Usage()
        metrics = None
        finish_reason = "stop"
        rid = new_request_id()
        async for chunk in self.generate_stream(req):
            rid = chunk.id
            for choice in chunk.choices:
                if choice.delta.content:
                    parts.append(choice.delta.content)
                if choice.logprobs:
                    logprob_entries.extend(choice.logprobs.content)
                if choice.finish_reason:
                    finish_reason = choice.finish_reason
            if chunk.usage:
                usage = chunk.usage
            if chunk.metrics:
                metrics = chunk.metrics
        return rid, "".join(parts), logprob_entries, finish_reason, usage, metrics

    async def generate(self, req: ChatCompletionRequest) -> ChatCompletionResponse:
        """Non-streaming: aggregate the stream (reference inference.py:255-311)."""
        rid, text, logprob_entries, finish_reason, usage, metrics = (
            await self._collect(req)
        )
        return ChatCompletionResponse(
            id=rid,
            model=req.model,
            choices=[
                ChatChoice(
                    message=ChatMessage(role="assistant", content=text),
                    logprobs=ChoiceLogprobs(content=logprob_entries) if req.logprobs_enabled else None,
                    finish_reason=finish_reason,
                )
            ],
            usage=usage,
            metrics=metrics,
        )
