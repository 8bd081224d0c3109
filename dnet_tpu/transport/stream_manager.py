"""Per-nonce bidi-stream lifecycle over gRPC aio.

Faithful port of the reference's StreamManager semantics
(src/dnet/core/stream_manager.py:48-130): lazy stream open per nonce, a
background ACK-reader task per stream, backpressure ACKs temporarily
disabling the stream with backoff, and periodic idle sweeping.  The channel
layer is injectable (tests pass fakes; production passes grpc.aio).
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

from dnet_tpu.analysis.runtime import ownership as dsan
from dnet_tpu.obs import get_recorder, metric
from dnet_tpu.resilience import chaos
from dnet_tpu.resilience.policy import call_with_retry
from dnet_tpu.transport.protocol import ActivationFrame, StreamAck
from dnet_tpu.utils.logger import get_logger

log = get_logger()

_TX_BYTES = metric("dnet_transport_tx_bytes_total")
_BACKPRESSURE = metric("dnet_transport_backpressure_total")
_REOPENS = metric("dnet_stream_reopens_total")
_WIRE_BYTES = metric("dnet_wire_bytes_total")


@dataclass
class StreamContext:
    nonce: str
    call: object  # grpc aio stream-stream call
    ack_task: Optional[asyncio.Task] = None
    last_used: float = field(default_factory=time.monotonic)
    disabled_until: float = 0.0
    seq: int = 0

    @property
    def disabled(self) -> bool:
        return time.monotonic() < self.disabled_until


class StreamManager:
    """Owns outbound activation streams keyed by nonce."""

    def __init__(
        self,
        open_stream: Callable[[], object],
        backoff_s: float = 0.25,
        idle_timeout_s: float = 30.0,
        on_nack: Optional[Callable[[StreamAck], None]] = None,
    ) -> None:
        self._open_stream = open_stream  # () -> stream-stream call
        # loop-only by contract (declared in analysis/runtime/domains.py):
        # every touch happens in a coroutine; the asyncio.Lock below only
        # serializes coroutines, it cannot protect against a raw thread
        self._streams: Dict[str, StreamContext] = dsan.guard_dict(
            {}, dsan.loop_domain(), "StreamManager._streams"
        )
        self._backoff_s = backoff_s
        self._idle_timeout_s = idle_timeout_s
        self._lock = asyncio.Lock()
        # outright-rejection observer (non-backpressure NACK): the epoch
        # fence answers fenced frames with a NACK the sender must be able
        # to act on — without this hook a fenced request would hang its
        # full await timeout on a token that can never come
        self._on_nack = on_nack

    async def get_or_create(self, nonce: str) -> StreamContext:
        async with self._lock:
            ctx = self._streams.get(nonce)
            if ctx is None:
                call = self._open_stream()
                ctx = StreamContext(nonce=nonce, call=call)
                ctx.ack_task = asyncio.ensure_future(self._ack_reader(ctx))
                self._streams[nonce] = ctx
            ctx.last_used = time.monotonic()
            return ctx

    async def send(self, nonce: str, frame: ActivationFrame) -> None:
        """Send one frame, respecting backpressure disable windows.

        frame.seq is the caller's end-to-end step identity and is preserved
        (the token callback echoes it; rewriting here would desync futures
        when a stream is recreated mid-request).  ctx.seq only counts frames
        for diagnostics.

        A write failure (peer restarted, channel reset) drops the context
        and — under the send_activation retry policy — re-opens a fresh
        stream and re-sends THIS frame with its original seq; the shard
        side dedups on (nonce, seq, layer_id) in case the first write
        landed before the break was observed.  Retries exhausted (or a
        non-transient error) propagate to the caller as before.
        """
        async def _attempt() -> StreamContext:
            ctx = await self.get_or_create(nonce)
            while ctx.disabled:
                await asyncio.sleep(
                    max(ctx.disabled_until - time.monotonic(), 0.01)
                )
            ctx.seq += 1
            try:
                await chaos.inject_async("send_activation")
                await ctx.call.write(frame)
            except Exception:
                # dead stream: drop the context so the retry (or the next
                # frame) opens a fresh one instead of failing forever
                await self.end_stream(nonce)
                raise
            return ctx

        t0 = time.perf_counter()
        ctx = await call_with_retry(
            _attempt,
            method="send_activation",
            on_retry=lambda *_: _REOPENS.inc(),
        )
        ctx.last_used = time.monotonic()
        n_bytes = len(getattr(frame, "payload", b"") or b"")
        _TX_BYTES.inc(n_bytes)
        _WIRE_BYTES.labels(dir="tx").inc(n_bytes)
        # seq rides along so the Perfetto export (obs/trace.py) can pair
        # this send with the receiving node's transport_recv flow arrow
        get_recorder().span(
            nonce, "transport_send", (time.perf_counter() - t0) * 1000,
            bytes=n_bytes, seq=getattr(frame, "seq", None),
        )

    async def _ack_reader(self, ctx: StreamContext) -> None:
        """Consume ACKs; a backpressure ACK pauses the stream briefly
        (reference stream_manager.py:76-96)."""
        try:
            while True:
                ack = await ctx.call.read()
                if ack is None or ack is getattr(ctx.call, "EOF", None):
                    break
                if isinstance(ack, (bytes, bytearray)):
                    ack = StreamAck.from_bytes(bytes(ack))
                if ack.backpressure:
                    ctx.disabled_until = time.monotonic() + self._backoff_s
                    _BACKPRESSURE.inc()
                    get_recorder().span(
                        ctx.nonce, "backpressure_pause", self._backoff_s * 1000
                    )
                    log.warning(
                        "[PROFILE] stream %s backpressure, pausing %.2fs",
                        ctx.nonce,
                        self._backoff_s,
                    )
                elif not ack.ok:
                    log.warning("stream %s NACK seq=%d: %s", ctx.nonce, ack.seq, ack.message)
                    if self._on_nack is not None:
                        try:
                            self._on_nack(ack)
                        except Exception:
                            log.exception("on_nack handler failed")
        except asyncio.CancelledError:
            raise
        except Exception as exc:
            log.debug("ack reader for %s ended: %s", ctx.nonce, exc)

    async def end_stream(self, nonce: str) -> None:
        async with self._lock:
            ctx = self._streams.pop(nonce, None)
        if ctx is None:
            return
        if ctx.ack_task:
            ctx.ack_task.cancel()
        done = getattr(ctx.call, "done_writing", None)
        if done is not None:
            try:
                await done()
            except Exception as exc:
                # half-close on an already-broken stream: the stream is
                # gone either way, but leave a trace (DL007 contract)
                log.debug("done_writing failed for %s: %s", nonce, exc)

    async def cleanup_idle(self) -> int:
        """Close streams idle past the timeout; returns count closed."""
        now = time.monotonic()
        stale = [
            n
            for n, c in self._streams.items()
            if now - c.last_used > self._idle_timeout_s
        ]
        # stale streams are independent: half-close them all concurrently
        # (end_stream pops under the lock per nonce, so parallel ends on
        # distinct nonces cannot race each other)
        await asyncio.gather(*(self.end_stream(n) for n in stale))
        return len(stale)

    async def shutdown(self) -> None:
        await asyncio.gather(
            *(self.end_stream(n) for n in list(self._streams))
        )
