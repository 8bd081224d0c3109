"""Overlapped quantized wire pipeline (DNET_WIRE_PIPELINE=1).

The hop codec used to sit SERIALLY inside the shard compute thread: step N
computed, then the thread paid the full encode (device quant/sparsify +
D2H readback + byte packing) before step N+1 could start.  This module is
the machinery that takes it off that path, following EQuARX's
quantize-the-collective-and-overlap framing (arxiv 2506.17615):

- tx: the compute thread only LAUNCHES the on-device encode (jitted, with
  the activation buffer donated — compression/wire.py launch_encode) and
  wraps the pending device buffers in a :class:`PendingWirePayload`.  The
  adapter's egress worker finalizes it on the :class:`WireTxStage`'s
  dedicated executor thread — D2H readback + byte packing + gRPC send all
  happen while the compute thread is already inside the next step.

- backpressure: a bounded :class:`EncodeRing` of encode slots (depth 2 by
  default) couples compute speed to wire drain — the compute thread may
  run at most ``depth`` launched-but-unsent frames ahead; past that,
  ``acquire`` blocks until the tx stage releases a slot.

- rx: the symmetric half lives in ShardCompute.predecode — ingress
  launches H2D upload + on-device dequant for a QUEUED frame so frame
  N+1's decode overlaps frame N's compute; this module only owns the
  shared accounting.

- attribution: ``dnet_wire_encode_ms`` / ``dnet_wire_decode_ms`` split by
  where the time was spent, and :data:`overlap` folds every observation
  into ``dnet_wire_overlap_ratio`` = hidden codec ms / total codec ms
  (1.0 = the wire costs the compute thread nothing but dispatch).

Chaos points ``wire_encode`` / ``wire_decode`` sit inside the codec work
so fault tests can deterministically wedge the tx stage (delay) or fail a
frame's codec (error) — resilience/chaos.py grammar.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from dnet_tpu.analysis.runtime import ownership as dsan
from dnet_tpu.obs import metric
from dnet_tpu.resilience import chaos
from dnet_tpu.utils.logger import get_logger

log = get_logger()

_ENCODE_MS = metric("dnet_wire_encode_ms")
_DECODE_MS = metric("dnet_wire_decode_ms")
_OVERLAP = metric("dnet_wire_overlap_ratio")


def wire_pipeline_enabled() -> bool:
    """THE flag gate: DNET_WIRE_PIPELINE=1 (WireSettings.pipeline).  A raw
    env read (config.env_flag, the sanctioned DL006 escape hatch) backs
    the settings value so tests toggling os.environ after the settings
    cache warmed still see the flip."""
    from dnet_tpu.config import env_flag, get_settings

    if get_settings().wire.pipeline:
        return True
    return env_flag("DNET_WIRE_PIPELINE")


class _OverlapTracker:
    """Cumulative serial-vs-hidden codec milliseconds -> the overlap gauge.

    ``serial`` ms were paid ON the compute thread (launch dispatch, or the
    whole codec when the pipeline is off); ``hidden`` ms ran on the tx
    stage / at ingress, overlapped with compute.  The gauge is the hidden
    fraction — how much of the codec the pipeline actually took off the
    serial path.

    ``stall`` ms are encode-ring backpressure waits — the compute thread
    intentionally parked because the wire is the bottleneck.  Books-kept
    separately and EXCLUDED from the ratio: backpressure is the depth
    bound doing its job, not codec work on the serial path."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._serial_ms = 0.0
        self._hidden_ms = 0.0
        self._stall_ms = 0.0

    def add(self, serial_ms: float = 0.0, hidden_ms: float = 0.0,
            stall_ms: float = 0.0) -> None:
        with self._lock:
            self._serial_ms += serial_ms
            self._hidden_ms += hidden_ms
            self._stall_ms += stall_ms
            total = self._serial_ms + self._hidden_ms
            ratio = (self._hidden_ms / total) if total > 0 else 0.0
        _OVERLAP.set(round(ratio, 6))

    def snapshot(self) -> dict:
        with self._lock:
            total = self._serial_ms + self._hidden_ms
            return {
                "serial_ms": self._serial_ms,
                "hidden_ms": self._hidden_ms,
                "stall_ms": self._stall_ms,
                "ratio": (self._hidden_ms / total) if total > 0 else 0.0,
            }

    def reset(self) -> None:
        with self._lock:
            self._serial_ms = 0.0
            self._hidden_ms = 0.0
            self._stall_ms = 0.0
        _OVERLAP.set(0.0)


#: process-global overlap books (one wire per process; tests reset())
overlap = _OverlapTracker()


def observe_encode(ms: float, hidden: bool) -> None:
    _ENCODE_MS.observe(ms)
    overlap.add(hidden_ms=ms if hidden else 0.0,
                serial_ms=0.0 if hidden else ms)


def observe_decode(ms: float, hidden: bool) -> None:
    _DECODE_MS.observe(ms)
    overlap.add(hidden_ms=ms if hidden else 0.0,
                serial_ms=0.0 if hidden else ms)


class EncodeRing:
    """Bounded ring of in-flight encode slots — the pipeline's depth-2
    double buffer.  ``acquire`` runs on the compute thread BEFORE the
    encode launches; ``release`` runs on the tx stage after the readback.
    A full ring blocks the compute thread: that is the backpressure that
    keeps device memory bounded and couples compute to wire drain.

    ``acquire`` degrades rather than deadlocks: if no slot frees within
    ``max_wait_s`` (a wedged/failed tx stage), it returns False and the
    caller encodes synchronously — slower, never stuck."""

    #: seconds a full ring may block the compute thread before the caller
    #: falls back to the synchronous encode path
    MAX_WAIT_S = 10.0

    def __init__(self, depth: int = 2) -> None:
        self.depth = max(int(depth), 1)
        self._slots = threading.BoundedSemaphore(self.depth)
        # dsan ownership (analysis/runtime/domains.py): the in-flight
        # count is touched from the compute thread AND the tx executor —
        # guarded-by _lock is the only honest domain for it
        self._lock = dsan.san_lock("EncodeRing._lock")
        self._domain = dsan.maybe_lock_domain(self._lock)
        self._inflight = 0

    def acquire(self, max_wait_s: Optional[float] = None) -> bool:
        budget = self.MAX_WAIT_S if max_wait_s is None else max_wait_s
        if not self._slots.acquire(timeout=budget):
            log.warning(
                "encode ring full for %.1fs (tx stage wedged?); "
                "falling back to synchronous encode", budget,
            )
            return False
        with self._lock:
            dsan.check_access("EncodeRing._inflight", self._domain, "write")
            self._inflight += 1
        return True

    def release(self) -> None:
        with self._lock:
            dsan.check_access("EncodeRing._inflight", self._domain, "write")
            self._inflight -= 1
        self._slots.release()  # BoundedSemaphore: over-release raises

    @property
    def inflight(self) -> int:
        with self._lock:
            dsan.check_access("EncodeRing._inflight", self._domain, "read")
            return self._inflight


class PendingWirePayload:
    """A hop whose payload is still a set of device buffers.

    Rides ActivationMessage.data from the compute thread to the adapter's
    egress worker, which awaits :class:`WireTxStage`.finalize before
    building the gRPC frame.  ``dtype``/``shape`` are final at launch, so
    everything EXCEPT the bytes is already known.  ``finalize`` releases
    the encode-ring slot whatever happens — an encode failure must not
    leak ring capacity and wedge the compute thread forever."""

    __slots__ = ("encode", "ring")

    def __init__(self, encode, ring: Optional[EncodeRing] = None) -> None:
        self.encode = encode  # compression.wire.DeviceEncode
        self.ring = ring

    @property
    def dtype(self) -> str:
        return self.encode.dtype

    @property
    def shape(self) -> tuple:
        return self.encode.shape

    def finalize(self, hidden: bool = True) -> bytes:
        """The ONE finalize body: chaos gate, D2H readback, byte packing,
        ring-slot release whatever happens.  ``hidden=True`` is the tx
        stage (overlapped with compute); ``hidden=False`` attributes the
        time as serial — the compute-thread fallback when the ring is
        full or the probe consumes its own frame."""
        t0 = time.perf_counter()
        try:
            chaos.inject("wire_encode")
            return self.encode.finalize()
        finally:
            if self.ring is not None:
                self.ring.release()
            observe_encode((time.perf_counter() - t0) * 1000.0, hidden=hidden)

    def finalize_sync(self) -> bytes:
        """Compute-thread fallback: same bytes, attributed as serial."""
        return self.finalize(hidden=False)

    def discard(self) -> None:
        """Drop the pending encode WITHOUT reading it back (frame dropped
        before send: output-queue overflow, calibration probe teardown).
        Must still release the ring slot — a leaked slot wedges the
        compute thread behind a frame nobody will ever finalize."""
        ring, self.ring = self.ring, None
        if ring is not None:
            ring.release()


class WireTxStage:
    """The dedicated tx stage: finalizes pending encodes on its own
    single-thread executor so the event loop never blocks on a D2H
    readback and the compute thread never waits for byte packing.  One
    worker keeps per-stream frame order trivially (the egress worker
    awaits each finalize before sending)."""

    def __init__(self) -> None:
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="wire-tx"
        )
        # loop-owned in-flight map (seq -> pending), declared in
        # analysis/runtime/domains.py: the egress worker is the only
        # writer, and a second loop touching it would break frame order
        self._pending = dsan.guard_dict(
            {}, dsan.loop_domain(), "WireTxStage._pending"
        )
        self._seq = 0

    @property
    def inflight(self) -> int:
        return len(self._pending)

    async def finalize(
        self, pending: PendingWirePayload, nonce: str = "",
        seq: int = -1,
    ) -> bytes:
        import asyncio

        key = self._seq
        self._seq += 1
        self._pending[key] = pending
        t0 = time.perf_counter()
        cfut = self._executor.submit(pending.finalize)
        try:
            data = await asyncio.wrap_future(cfut)
            if nonce:
                # the tx-stage leg of the frame's story: executor queue
                # wait + D2H readback + byte packing, rendered on the
                # tx-stage thread track in the Perfetto export
                # (obs/trace.py) under the egress wire_encode umbrella
                from dnet_tpu.obs import get_recorder

                get_recorder().span(
                    nonce, "wire_tx_stage",
                    (time.perf_counter() - t0) * 1000.0,
                    seq=seq, bytes=len(data),
                )
            return data
        except asyncio.CancelledError:
            # egress task cancelled (shutdown) while the finalize was
            # still queued: it will never run, so the ring slot it holds
            # must be released here or the compute thread wedges behind
            # it.  A finalize that already STARTED completes on the
            # executor and releases the slot itself.
            if cfut.cancel() or cfut.cancelled():
                pending.discard()
            raise
        finally:
            self._pending.pop(key, None)

    def shutdown(self) -> None:
        self._executor.shutdown(wait=False, cancel_futures=True)
