"""Execution-strategy seam: how the API node reaches the compute.

`ApiAdapterBase` is the contract the decode driver speaks
(reference: src/dnet/api/strategies/base.py:7-54).  Implementations:

- `LocalAdapter` (here): single-process — the model runs in this process on
  the local JAX device(s); the "ring" is a thread-pool call.
- `RingApiAdapter` (dnet_tpu/api/ring.py, task of the two-role split):
  gRPC streaming to the first shard + token-callback futures.

Because both speak the same surface, InferenceManager and the HTTP layer are
identical for 1 chip and for a multi-host ring.
"""

from __future__ import annotations

import abc
import asyncio
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from dnet_tpu.analysis.runtime import ownership as dsan
from dnet_tpu.core.types import DecodingParams, TokenResult
from dnet_tpu.utils.logger import get_logger


async def _embed_on_executor(hidden_fn, executor, ids_list):
    """Mean-pool hidden_states per input on the adapter's compute executor
    (session bookkeeping must not race concurrent decode steps)."""
    import numpy as np

    loop = asyncio.get_running_loop()
    out: List[List[float]] = []
    for ids in ids_list:
        h = await loop.run_in_executor(executor, hidden_fn, ids)  # [T, D]
        out.append([float(v) for v in np.mean(h, axis=0)])
    return out

log = get_logger()

# bound on awaiting a cancelled background task at shutdown: a step wedged
# in run_in_executor defers cancellation until the executor job completes,
# which for a wedged device dispatch is never — shutdown must not hang on it
_REAP_TIMEOUT_S = 5.0


async def _reap(task: Optional["asyncio.Task"], what: str) -> None:
    """Cancel-and-await a background task, bounded: the dropped-cancellation
    fix (the runtime twin of DL003) without trading it for an unbounded
    shutdown hang.  On timeout the task is abandoned with a warning — the
    same contract as a compute thread that fails to join."""
    if not task:
        return
    task.cancel()
    try:
        await asyncio.wait_for(task, timeout=_REAP_TIMEOUT_S)
    except (asyncio.CancelledError, asyncio.TimeoutError):
        pass
    if not task.done():
        log.warning(
            "%s ignored cancellation for %.0fs at shutdown; abandoning it "
            "(likely wedged in an executor step)", what, _REAP_TIMEOUT_S,
        )


class ApiAdapterBase(abc.ABC):
    """Token-path adapter between the decode driver and the compute plane."""

    @abc.abstractmethod
    async def start(self) -> None: ...

    @abc.abstractmethod
    async def shutdown(self) -> None: ...

    @abc.abstractmethod
    async def reset_cache(self, nonce: str) -> None:
        """Drop per-nonce state (KV) wherever it lives."""

    @abc.abstractmethod
    async def send_tokens(
        self,
        nonce: str,
        token_ids: List[int],
        decoding: DecodingParams,
        step: int,
        budget: Optional[int] = None,
    ) -> None:
        """Inject tokens for one decode step (whole prompt on step 0).

        `budget` is the driver's remaining token allowance for the request —
        a hint adapters may use to fuse multiple decode steps into one device
        program (chunked decode) without overshooting max_tokens."""

    @abc.abstractmethod
    async def await_token(self, nonce: str, step: int, timeout: float) -> TokenResult:
        """Wait for the sampled token of a specific step to come back."""

    def resolve_token(self, result: TokenResult) -> None:
        """Called by the transport when a token arrives (default: no-op)."""

    def set_deadline(self, nonce: str, deadline_ts: float) -> None:
        """Register the request's absolute wall-clock deadline (epoch
        seconds).  Adapters that serialize frames stamp it into every
        frame header so downstream hops can shed expired work
        (dnet_tpu/admission/).  Local adapters need no stamp — the driver
        itself checks between steps — so the default is a no-op."""

    def fail_pending(self, error: str) -> None:
        """Fail every in-flight token wait with `error` (fast-fail on shard
        death — the failure monitor calls this instead of letting requests
        burn the full await_token timeout).  The default covers any adapter
        built on `_TokenFutures`; adapters with different bookkeeping
        override."""
        futures = getattr(self, "_futures", None)
        if isinstance(futures, _TokenFutures):
            futures.fail_all(error)

    def max_seq(self) -> Optional[int]:
        """Sequence capacity of the serving path, when known."""
        return None

    async def embed(self, ids_list: List[List[int]]) -> List[List[float]]:
        """Mean-pooled final-hidden-state embeddings, one vector per input
        (beyond the reference, which never serves /v1/embeddings).
        Default: unsupported — the gRPC ring's shards never ship hidden
        states back to the API node.  The local adapter serves it for
        Local AND Mesh engines (both expose hidden_states), the batched
        adapter via its inner engine."""
        raise NotImplementedError(
            f"embeddings unsupported on {type(self).__name__}"
        )


class _TokenFutures:
    """Per-nonce, step-keyed future map shared by adapter implementations.

    Futures are keyed by (nonce, step) so a late token from a timed-out step
    can never be delivered to a later step of the same request.  resolve()
    may be called from any thread; it never pops — the awaiting side owns
    cleanup (pop happens in await_token's finally), which closes the race
    where a fast compute thread resolved before await_token looked up the
    future.  Reference: RingApiAdapter.await_token/resolve_token
    (src/dnet/api/strategies/ring.py:198-209).
    """

    def __init__(self) -> None:
        self._futures: Dict[tuple[str, int], asyncio.Future] = {}

    def expect(self, nonce: str, step: int) -> asyncio.Future:
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._futures[(nonce, step)] = fut
        return fut

    def resolve(self, result: TokenResult) -> bool:
        fut = self._futures.get((result.nonce, result.step))
        if fut is None or fut.done():
            return False
        fut.get_loop().call_soon_threadsafe(
            lambda: fut.done() or fut.set_result(result)
        )
        return True

    async def wait(self, nonce: str, step: int, timeout: float) -> TokenResult:
        fut = self._futures.get((nonce, step))
        if fut is None:
            raise RuntimeError(f"no pending token for nonce {nonce} step {step}")
        try:
            return await asyncio.wait_for(fut, timeout)
        finally:
            self._futures.pop((nonce, step), None)

    def cancel_nonce(self, nonce: str) -> None:
        for key in [k for k in self._futures if k[0] == nonce]:
            fut = self._futures.pop(key)
            if not fut.done():
                fut.cancel()

    def fail_all(self, error: str) -> None:
        """Resolve every pending future with an error TokenResult (the
        awaiting side still owns the pop)."""
        for (nonce, step) in list(self._futures):
            self.resolve(
                TokenResult(nonce=nonce, token_id=-1, step=step, error=error)
            )


class BatchedLocalAdapter(ApiAdapterBase):
    """Continuous-batching strategy over a BatchedEngine.

    Decode steps from concurrent requests coalesce: send_tokens enqueues the
    step and a scheduler task drains everything pending into ONE batched
    engine call (core/batch.py).  While a batched step runs on the compute
    executor, newly arriving steps queue for the next round — classic
    continuous batching.  Prefills run between batched steps on the same
    executor (no KV races: one compute thread)."""

    PREFILL_CHUNK = 256  # prompt tokens per executor job (interleave grain)

    def __init__(self, engine) -> None:
        self.engine = engine  # BatchedEngine
        self._futures = _TokenFutures()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._pending: Dict[str, tuple] = {}  # nonce -> (token, decoding, step)
        self._kick: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._prefill_tasks: set = set()

    SWEEP_INTERVAL_S = 60.0

    async def start(self) -> None:
        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="compute")
        self._kick = asyncio.Event()
        self._task = asyncio.ensure_future(self._batch_loop())
        self._sweep_task = asyncio.ensure_future(self._sweep_loop())

    async def _sweep_loop(self) -> None:
        """Periodic TTL sweep on the compute thread: a client that vanished
        without reset_cache must not pin its slot forever (at capacity the
        pool would reject every new request)."""
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(self.SWEEP_INTERVAL_S)
            if self._executor is None:
                return
            try:
                n = await loop.run_in_executor(
                    self._executor, self.engine.sweep_sessions
                )
                if n:
                    log.info("TTL sweep freed %d idle sessions", n)
            except Exception:
                log.exception("session sweep failed")

    async def shutdown(self) -> None:
        # cancel AND await (bounded): a dropped cancellation leaves the
        # task to die unobserved at loop close — and a sweep mid-
        # run_in_executor would keep touching the engine after the
        # executor below is gone
        task, self._task = self._task, None
        await _reap(task, "batch loop")
        sweep, self._sweep_task = getattr(self, "_sweep_task", None), None
        await _reap(sweep, "session sweep")
        for t in list(self._prefill_tasks):
            t.cancel()
        self._prefill_tasks.clear()
        if self._executor:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None

    async def reset_cache(self, nonce: str) -> None:
        self._pending.pop(nonce, None)
        # slot state is owned by the compute thread: freeing it from the
        # event loop would race an in-flight batched step
        if self._executor is not None:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                self._executor, self.engine.end_session, nonce
            )
        self._futures.cancel_nonce(nonce)

    def max_seq(self) -> Optional[int]:
        return self.engine.max_seq

    async def embed(self, ids_list: List[List[int]]) -> List[List[float]]:
        # the inner engine produces the hidden states (BatchedEngine wraps a
        # LocalEngine as .eng, PipelinedMeshEngine a MeshEngine as ._inner);
        # the batched programs themselves only decode
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
            if hasattr(self.engine, "prefill_chunk"):
                # chunked prefill: one executor job per chunk, so queued
                # batched decode steps run BETWEEN chunks — a long prompt
                # stalls active lanes for at most one chunk's prefill.
                # (PipelinedMeshEngine has no prefill_chunk: its prefill is
                # a single ring pass, the single-shot fallback below.)
                task = asyncio.ensure_future(
                    self._prefill_chunked(nonce, list(token_ids), decoding, step)
                )
                self._prefill_tasks.add(task)
                task.add_done_callback(self._prefill_tasks.discard)
            else:
                loop = asyncio.get_running_loop()
                loop.run_in_executor(
                    self._executor, self._prefill, nonce, list(token_ids),
                    decoding, step,
                )
        elif nonce not in self.engine.sessions:
            # mid-generation session loss: fail fast instead of silently
            # re-prefilling from the single last sampled token
            self._futures.resolve(
                TokenResult(
                    nonce=nonce, token_id=-1,
                    error=f"session expired for request {nonce}", step=step,
                )
            )
        else:
            self._pending[nonce] = (token_ids[-1], decoding, step, budget)
            self._kick.set()

    def _prefill(self, nonce: str, ids: List[int], decoding: DecodingParams, step: int) -> None:
        try:
            res = self.engine.prefill_and_sample(nonce, ids, decoding)
            self._futures.resolve(
                self.engine.token_result(nonce, res, step=step, decoding=decoding)
            )
        except Exception as exc:
            log.exception("batched prefill failed")
            self._futures.resolve(
                TokenResult(nonce=nonce, token_id=-1, error=str(exc), step=step)
            )

    def _cancelled(self, nonce: str, step: int) -> bool:
        return (nonce, step) not in self._futures._futures

    async def _prefill_chunked(
        self, nonce: str, ids: List[int], decoding: DecodingParams, step: int
    ) -> None:
        loop = asyncio.get_running_loop()
        eng = self.engine
        try:
            # claim a batch slot BEFORE burning any prefill compute (a full
            # pool must fail instantly, not after the whole prompt)
            await loop.run_in_executor(self._executor, eng.reserve_slot, nonce)
            # prefix cache first: a chunked prefill must look up the FULL
            # prompt, then prefill only the uncached suffix
            n = await loop.run_in_executor(
                self._executor, eng.seed_from_prefix, nonce, ids, decoding.seed
            )
            rest = ids[n:]
            logits = None
            for i in range(0, len(rest), self.PREFILL_CHUNK):
                if self._cancelled(nonce, step):
                    await loop.run_in_executor(
                        self._executor, eng.abandon_prefill, nonce
                    )
                    return
                chunk = rest[i : i + self.PREFILL_CHUNK]
                logits = await loop.run_in_executor(
                    self._executor, eng.prefill_chunk, nonce, chunk, decoding.seed
                )
            await loop.run_in_executor(
                self._executor, eng.store_prefix, nonce, ids
            )
            if self._cancelled(nonce, step):
                await loop.run_in_executor(self._executor, eng.abandon_prefill, nonce)
                return
            res = await loop.run_in_executor(
                self._executor, eng.adopt_prefilled, nonce, logits, decoding
            )
            self._futures.resolve(
                eng.token_result(nonce, res, step=step, decoding=decoding)
            )
        except Exception as exc:
            log.exception("chunked batched prefill failed")
            try:
                await loop.run_in_executor(self._executor, eng.abandon_prefill, nonce)
            except Exception as exc:  # executor already shut down
                log.debug("abandon_prefill skipped for %s: %s", nonce, exc)
            self._futures.resolve(
                TokenResult(nonce=nonce, token_id=-1, error=str(exc), step=step)
            )

    async def _batch_loop(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            await self._kick.wait()
            self._kick.clear()
            await asyncio.sleep(0)  # coalesce: let concurrent senders enqueue
            pending, self._pending = self._pending, {}
            if not pending:
                continue
            await loop.run_in_executor(self._executor, self._batched_step, pending)

    async def await_token(self, nonce: str, step: int, timeout: float) -> TokenResult:
        return await self._futures.wait(nonce, step, timeout)

    def resolve_token(self, result: TokenResult) -> None:
        self._futures.resolve(result)

    def _batched_step(self, pending: Dict[str, tuple]) -> None:
        try:
            reqs = {n: (tok, dec) for n, (tok, dec, _step, _b) in pending.items()}
            # budgets widen the dispatch where the engine supports fused
            # multi-rotation chunks (PipelinedMeshEngine): extras buffer
            # engine-side and resolve later steps without a dispatch
            budgets = {n: b for n, (_t, _d, _s, b) in pending.items()}
            results, errors = self.engine.decode_batch(reqs, budgets=budgets)
        except Exception as exc:
            log.exception("batched decode step failed")
            for nonce, (_tok, _dec, step, _b) in pending.items():
                self._futures.resolve(
                    TokenResult(nonce=nonce, token_id=-1, error=str(exc), step=step)
                )
            return
        for nonce, res in results.items():
            _tok, dec, step, _b = pending[nonce]
            self._futures.resolve(
                self.engine.token_result(nonce, res, step=step, decoding=dec)
            )
        for nonce, msg in errors.items():
            _tok, _dec, step, _b = pending[nonce]
            self._futures.resolve(
                TokenResult(nonce=nonce, token_id=-1, error=msg, step=step)
            )


class LocalAdapter(ApiAdapterBase):
    """Single-process strategy: the engine *is* the ring.

    Compute runs on a dedicated single-thread executor (the analog of the
    shard's dedicated compute thread, src/dnet/shard/runtime.py:364-372), so
    the event loop never blocks on XLA.

    Decode steps are CHUNKED when the engine supports it: one engine call
    fuses up to `chunk_size` steps on-device (LocalEngine.decode_chunk) and
    the extra tokens are buffered here, resolving later send_tokens calls
    instantly — the driver's per-token protocol is unchanged, but the device
    round-trip cost is paid once per chunk.  Chunk width RAMPS 2 -> 4 -> ...
    -> chunk_size per request, so streaming clients see early tokens at
    per-token latency while long generations converge to fused throughput.
    """

    MAX_BUFFERED_NONCES = 64  # aborted-mid-chunk leftovers cap (leak bound)

    def __init__(self, engine, chunk_size: int = 32) -> None:
        self.engine = engine
        self.chunk_size = max(1, chunk_size)
        self._futures = _TokenFutures()
        self._executor: Optional[ThreadPoolExecutor] = None
        # nonce -> {step: TokenResult}; guarded by _buf_lock (compute thread
        # inserts, event loop consumes/clears).  The guarded-by contract is
        # declared in analysis/runtime/domains.py and enforced under
        # DNET_SAN=1; with it unset these are the plain dicts/lock.
        self._buf_lock = dsan.san_lock("LocalAdapter._buf_lock")
        _buf_dom = dsan.maybe_lock_domain(self._buf_lock)
        self._buffered: Dict[str, Dict[int, TokenResult]] = dsan.guard_dict(
            {}, _buf_dom, "LocalAdapter._buffered"
        )
        self._ramp: Dict[str, int] = dsan.guard_dict(
            {}, _buf_dom, "LocalAdapter._ramp"
        )  # nonce -> next chunk width

    SWEEP_INTERVAL_S = 60.0
    # same periodic TTL sweep as the batched adapter (one implementation)
    _sweep_loop = BatchedLocalAdapter._sweep_loop

    async def start(self) -> None:
        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="compute")
        self._sweep_task = asyncio.ensure_future(self._sweep_loop())

    async def shutdown(self) -> None:
        # same bounded dropped-cancellation fix as the batched adapter:
        # await the cancelled sweep so it cannot touch the engine past
        # executor teardown or die unobserved at loop close
        sweep, self._sweep_task = getattr(self, "_sweep_task", None), None
        await _reap(sweep, "session sweep")
        if self._executor:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None

    async def reset_cache(self, nonce: str) -> None:
        self.engine.end_session(nonce)
        self._futures.cancel_nonce(nonce)
        with self._buf_lock:
            self._buffered.pop(nonce, None)
            self._ramp.pop(nonce, None)

    def max_seq(self) -> Optional[int]:
        return self.engine.max_seq

    async def embed(self, ids_list: List[List[int]]) -> List[List[float]]:
        fn = getattr(self.engine, "hidden_states", None)
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
        if self._executor is None:
            raise RuntimeError("adapter not started")
        self._futures.expect(nonce, step)
        with self._buf_lock:
            entries = self._buffered.get(nonce)
            buffered = entries.pop(step, None) if entries else None
            if entries is not None and not entries:
                del self._buffered[nonce]  # drained: don't count toward the cap
        if buffered is not None:
            self._futures.resolve(buffered)
            return
        loop = asyncio.get_running_loop()
        loop.run_in_executor(
            self._executor,
            self._compute_step, nonce, list(token_ids), decoding, step, budget,
        )

    def _next_chunk_width(self, nonce: str, budget: Optional[int]) -> int:
        with self._buf_lock:
            width = self._ramp.get(nonce, min(2, self.chunk_size))
            self._ramp[nonce] = min(width * 2, self.chunk_size)
            if len(self._ramp) > self.MAX_BUFFERED_NONCES:
                # entries re-created by a compute step racing reset_cache
                # (aborted request) have no session and can be pruned
                live = self.engine.sessions
                for n in [n for n in self._ramp if n not in live]:
                    del self._ramp[n]
        # no budget => no chunking: a chunk must never overshoot max_tokens
        # by more than the driver is prepared to discard
        return min(width, budget) if budget is not None else 1

    def _chunked_results(
        self,
        eng,
        nonce: str,
        token_ids: List[int],
        decoding,
        budget: Optional[int],
    ):
        """Pipelined chunked decode: read the current chunk AFTER dispatching
        the next one, so the result transfer (and this thread's host work)
        overlaps the device computing ahead.  The next chunk chains from the
        device-resident last token — no host round trip feeds the device.

        Returns the current chunk's SampleResults, or None to fall back to
        per-token decode (engine without chunk support / width-1 budget).
        """
        if (
            budget is not None
            and budget > 1
            and getattr(eng, "spec_eligible", None) is not None
            and eng.spec_eligible(decoding)
            and eng.spec_worthwhile(nonce)
            and eng.pending_chunks(nonce) == 0
        ):
            # speculative path: one verify forward emits 1..L+1 greedy-exact
            # tokens; the per-token driver protocol is unchanged (extras are
            # buffered exactly like chunked results)
            return eng.decode_spec(nonce, token_ids[-1], decoding, budget)
        if not hasattr(eng, "decode_chunk_dispatch"):
            # legacy engines: one-shot chunk call, no pipelining
            chunk = self._next_chunk_width(nonce, budget)
            if chunk > 1 and hasattr(eng, "decode_chunk"):
                return eng.decode_chunk(nonce, token_ids[-1], decoding, chunk)
            return None
        if eng.pending_chunks(nonce) == 0:
            chunk = self._next_chunk_width(nonce, budget)
            if chunk <= 1:
                return None
            if eng.decode_chunk_dispatch(nonce, token_ids[-1], decoding, chunk) == 0:
                return None
        # speculate one chunk beyond the unread one while we block on the
        # read; EOS overshoot wastes at most that chunk's compute (its KV
        # rows die with the session, same as the in-chunk overshoot)
        if budget is not None and budget - eng.pending_width(nonce) > 1:
            nxt = self._next_chunk_width(nonce, budget - eng.pending_width(nonce))
            if nxt > 1:
                eng.decode_chunk_dispatch(nonce, None, decoding, nxt)
        return eng.decode_chunk_read(nonce)

    def _buffer_results(self, nonce: str, entries: Dict[int, TokenResult]) -> None:
        with self._buf_lock:
            self._buffered[nonce] = entries
            if len(self._buffered) > self.MAX_BUFFERED_NONCES:
                # leftovers of aborted requests (session already ended) are
                # the only entries that can accumulate — never evict a live
                # request's pending tokens, that would corrupt its stream
                live = self.engine.sessions
                for n in [n for n in self._buffered if n not in live]:
                    if len(self._buffered) <= self.MAX_BUFFERED_NONCES:
                        break
                    del self._buffered[n]

    def _compute_step(
        self,
        nonce: str,
        token_ids: List[int],
        decoding: DecodingParams,
        step: int,
        budget: Optional[int] = None,
    ) -> None:
        try:
            eng = self.engine
            if step == 0:
                res = eng.prefill_and_sample(nonce, token_ids, decoding)
            elif nonce not in eng.sessions:
                # mid-generation session loss (TTL sweep / reset race) is an
                # error: re-prefilling from the single last token would
                # silently continue with empty context
                raise RuntimeError(f"session expired for request {nonce}")
            else:
                results = self._chunked_results(eng, nonce, token_ids, decoding, budget)
                if results is None:
                    res = eng.decode_step(nonce, token_ids[-1], decoding)
                else:
                    if len(results) > 1:
                        self._buffer_results(
                            nonce,
                            {
                                step + i: eng.token_result(
                                    nonce, r, step=step + i, decoding=decoding
                                )
                                for i, r in enumerate(results[1:], start=1)
                            },
                        )
                    res = results[0]
            result = eng.token_result(nonce, res, step=step, decoding=decoding)
            self._futures.resolve(result)
        except Exception as exc:  # surfaced to await_token as an error result
            log.exception("local compute step failed")
            self._futures.resolve(
                TokenResult(nonce=nonce, token_id=-1, error=str(exc), step=step)
            )

    async def await_token(self, nonce: str, step: int, timeout: float) -> TokenResult:
        return await self._futures.wait(nonce, step, timeout)

    def resolve_token(self, result: TokenResult) -> None:
        self._futures.resolve(result)
