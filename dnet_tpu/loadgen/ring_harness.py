"""In-process two-shard ring behind the real API serving stack.

The wire-pipeline proof harness: two ShardRuntimes (real compute threads,
real ShardCompute engines) wired into a ring by RingAdapters whose gRPC
channel layer is replaced with direct in-process calls — every frame still
crosses the full protocol surface (ActivationFrame bytes are built, codec
tags parsed, ACKs returned, epochs checked), only the sockets are gone.
On top sits the REAL RingApiAdapter + InferenceManager + ApiHTTPServer, so
an aiohttp client (loadgen, tests) exercises the identical admission/SSE/
driver path a remote deployment would.

Used by tests/subsystems/test_wire_pipeline.py (byte-identical SSE parity
legacy-vs-pipelined).  Per-edge frame accounting (`RingWireStats`) gives
the per-hop tx bytes:
hidden activation hops are the "inter-hop bytes" the qsparse8 codec is
supposed to shrink, token/continuation frames are counted separately so
they cannot dilute the ratio.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass, field
from typing import Dict, Optional

from dnet_tpu.transport.protocol import (
    ActivationFrame,
    Empty,
    HealthInfo,
    LatencyProbe,
    StreamAck,
)
from dnet_tpu.utils.logger import get_logger

log = get_logger()


@dataclass
class RingWireStats:
    """Per-edge frame accounting, split by payload kind."""

    hidden_bytes: Dict[str, int] = field(default_factory=dict)
    hidden_frames: Dict[str, int] = field(default_factory=dict)
    token_bytes: Dict[str, int] = field(default_factory=dict)
    token_frames: Dict[str, int] = field(default_factory=dict)
    by_codec: Dict[str, int] = field(default_factory=dict)

    def record(self, edge: str, frame: ActivationFrame) -> None:
        n = len(frame.payload or b"")
        if frame.dtype == "tokens":
            self.token_bytes[edge] = self.token_bytes.get(edge, 0) + n
            self.token_frames[edge] = self.token_frames.get(edge, 0) + 1
            return
        self.hidden_bytes[edge] = self.hidden_bytes.get(edge, 0) + n
        self.hidden_frames[edge] = self.hidden_frames.get(edge, 0) + 1
        codec = frame.codec or frame.dtype
        self.by_codec[codec] = self.by_codec.get(codec, 0) + n

    def as_dict(self) -> dict:
        return {
            "hidden_bytes": dict(self.hidden_bytes),
            "hidden_frames": dict(self.hidden_frames),
            "token_bytes": dict(self.token_bytes),
            "token_frames": dict(self.token_frames),
            "by_codec": dict(self.by_codec),
        }


class _InprocStreamCall:
    """Stands in for a grpc aio stream-stream call: write() delivers the
    frame straight into the receiving adapter's ingress and queues the
    returned ACK for the reader task."""

    def __init__(self, deliver) -> None:
        self._deliver = deliver  # async (frame) -> StreamAck
        self.acks: asyncio.Queue = asyncio.Queue()

    async def write(self, frame: ActivationFrame) -> None:
        ack = await self._deliver(frame)
        if isinstance(ack, StreamAck):
            await self.acks.put(ack)

    async def read(self):
        return await self.acks.get()

    async def done_writing(self) -> None:
        return None


class _InprocRingClient:
    """RingClient replacement: frames/resets land on the target adapter
    in-process (full protocol semantics, no sockets)."""

    def __init__(self, target_adapter, edge: str, stats: RingWireStats) -> None:
        self._adapter = target_adapter
        self._edge = edge
        self._stats = stats

    def open_stream(self) -> _InprocStreamCall:
        return _InprocStreamCall(self._deliver)

    async def _deliver(self, frame: ActivationFrame) -> StreamAck:
        self._stats.record(self._edge, frame)
        ok, msg = await self._adapter.ingress_frame(frame)
        return StreamAck(nonce=frame.nonce, seq=frame.seq, ok=ok, message=msg)

    async def send_activation(self, frame, timeout=10.0):
        return await self._deliver(frame)

    async def health_check(self, timeout=5.0):
        return HealthInfo(ok=True)

    async def reset_cache(self, nonce="", timeout=10.0, epoch=0):
        await self._adapter.reset_cache(nonce)
        return Empty()

    async def measure_latency(self, probe, timeout=30.0):
        return LatencyProbe(t_sent=probe.t_sent, payload=probe.payload)

    async def close(self):
        return None


class _InprocCallbackClient:
    """ApiCallbackClient replacement: the tail shard's SendToken resolves
    straight into the API adapter (what the gRPC servicer would do)."""

    def __init__(self, resolve) -> None:
        self._resolve = resolve

    async def send_token(self, payload, timeout=3.0):
        self._resolve(payload.to_result())
        return Empty()

    async def close(self):
        return None


class _RingManagerFacade:
    """The slice of the model-manager surface ApiHTTPServer touches for a
    pre-loaded in-process ring (health + model identity; load/unload are
    the harness's job, not the HTTP client's)."""

    def __init__(self, inference, ring: "InprocRing") -> None:
        self.inference = inference
        self._ring = ring

    @property
    def current_model_id(self) -> Optional[str]:
        return self.inference.model_id

    def is_model_available(self, model_id: str) -> bool:
        return model_id == self.inference.model_id

    async def load_model(self, model_id: str, max_seq: Optional[int] = None) -> float:
        raise RuntimeError(
            "the in-process ring harness pre-loads its model; "
            "use InprocRing.start()"
        )

    async def unload_model(self) -> None:
        return None


class InprocRing:
    """Two real shards + real ring/API adapters + the real HTTP app."""

    def __init__(
        self,
        model_dir: str,
        layers0=(0, 1),
        layers1=(2, 3),
        max_seq: int = 64,
        param_dtype: str = "float32",
        wire_codec: str = "",
        auto_steps: int = 16,
        max_concurrent: int = 8,
        request_timeout_s: float = 120.0,
        tp: int = 0,
        tp_collective: str = "",
    ) -> None:
        from dnet_tpu.shard.adapter import RingAdapter
        from dnet_tpu.shard.runtime import ShardRuntime

        self.model_dir = str(model_dir)
        self.layers0, self.layers1 = list(layers0), list(layers1)
        self.max_seq = max_seq
        self.param_dtype = param_dtype
        self.wire_codec = wire_codec
        # NamedSharding TP per shard (parallel/tp.py): each ShardCompute
        # drives `tp` forced-host devices; 1 pins today's single-chip
        # shards, 0 defers to the DNET_TP shard default.  tp_collective
        # pins the collective mode for BOTH shards ("" = the
        # DNET_TP_COLLECTIVE default resolution).
        self.tp = max(int(tp), 0)
        self.tp_collective = tp_collective
        self.auto_steps = auto_steps
        self.max_concurrent = max_concurrent
        self.request_timeout_s = request_timeout_s
        self.stats = RingWireStats()
        self.s0 = ShardRuntime("s0")
        self.s1 = ShardRuntime("s1")
        self.a0 = RingAdapter(
            self.s0,
            ring_client_factory=lambda addr: _InprocRingClient(
                self.a1, "s0->s1", self.stats
            ),
            callback_client_factory=lambda addr: _InprocCallbackClient(
                self._resolve_token
            ),
        )
        self.a1 = RingAdapter(
            self.s1,
            ring_client_factory=lambda addr: _InprocRingClient(
                self.a0, "s1->s0", self.stats
            ),
            callback_client_factory=lambda addr: _InprocCallbackClient(
                self._resolve_token
            ),
        )
        self.api = None  # RingApiAdapter, built in start()
        self.inference = None
        self.manager = None
        self.server = None

    def _resolve_token(self, result) -> None:
        if self.api is not None:
            self.api.resolve_token(result)

    async def start(self) -> None:
        from dnet_tpu.api.http import ApiHTTPServer
        from dnet_tpu.api.inference import InferenceManager
        from dnet_tpu.api.ring import RingApiAdapter
        from dnet_tpu.utils.tokenizer import load_tokenizer

        loop = asyncio.get_running_loop()
        self.s0.start(loop)
        self.s1.start(loop)
        await self.a0.start()
        await self.a1.start()
        await asyncio.gather(
            loop.run_in_executor(
                None,
                lambda: self.s0.load_model_core(
                    self.model_dir, self.layers0, max_seq=self.max_seq,
                    param_dtype=self.param_dtype, wire_codec=self.wire_codec,
                    tp_degree=self.tp, tp_collective=self.tp_collective,
                ),
            ),
            loop.run_in_executor(
                None,
                lambda: self.s1.load_model_core(
                    self.model_dir, self.layers1, max_seq=self.max_seq,
                    param_dtype=self.param_dtype, wire_codec=self.wire_codec,
                    tp_degree=self.tp, tp_collective=self.tp_collective,
                ),
            ),
        )
        # fully wired ring: tail -> head carries decode-grant continuations
        self.a0.configure_topology("s1:1")
        self.a1.configure_topology("s0:1")
        self.api = RingApiAdapter(
            head_addr="s0:1",
            callback_url="grpc://api:1",
            shard_grpc_addrs=["s0:1", "s1:1"],
            ring_client_factory=lambda addr: _InprocRingClient(
                self.a0, "api->s0", self.stats
            ),
            max_seq_len=self.max_seq,
            auto_steps=self.auto_steps,
        )
        await self.api.start()
        self.inference = InferenceManager(
            adapter=self.api,
            request_timeout_s=self.request_timeout_s,
            max_concurrent=self.max_concurrent,
        )
        self.inference.tokenizer = load_tokenizer(self.model_dir)
        self.inference.model_id = "inproc-ring"
        self.manager = _RingManagerFacade(self.inference, self)
        self.server = ApiHTTPServer(self.inference, self.manager)

    @property
    def app(self):
        return self.server.app

    async def stop(self) -> None:
        if self.api is not None:
            await self.api.shutdown()
        await self.a0.shutdown()
        await self.a1.shutdown()
        self.s0.stop()
        self.s1.stop()
        # free both engines (two per run adds up across parity runs)
        for rt in (self.s0, self.s1):
            if rt.compute is not None:
                rt.compute.engine.close()
                rt.compute = None
