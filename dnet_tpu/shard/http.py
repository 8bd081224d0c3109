"""Shard control-plane HTTP server (aiohttp).

Reference: src/dnet/shard/http_api.py:222-336 — /health, /load_model,
/unload_model, /measure_latency (gRPC probes to peers per payload size),
/profile (device microbench).  Plus the obs surface: `GET /metrics` (this
process's Prometheus exposition — transport rx bytes, token RPC latency,
snapshot-cache counters live HERE, not on the API node) and
`GET /v1/debug/timeline/{rid}` (this shard's recorded spans for a nonce).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import List, Optional

from aiohttp import web
from pydantic import BaseModel, Field, ValidationError

from dnet_tpu.utils.logger import get_logger

log = get_logger()


class NextNode(BaseModel):
    host: str
    grpc_port: int


class ShardLoadModelRequest(BaseModel):
    """Reference: ShardLoadModelRequest (src/dnet/shard/models.py:10-33)."""

    model_path: str
    layers: List[int]
    next_node: Optional[NextNode] = None
    window_size: int = 0
    residency_size: int = 0
    kv_bits: int = 0
    max_seq_len: int = 4096
    api_callback_address: str = ""
    param_dtype: str = "bfloat16"
    wire_dtype: str = "bfloat16"
    # hop codec for this shard's outgoing hidden frames ("lossless" |
    # "qsparse8"; "" = the shard's own DNET_WIRE_CODEC default).  The API
    # resolves "auto" per hop: qsparse8 when the next shard is on another
    # host, lossless for same-host/loopback hops (greedy SSE parity).
    wire_codec: str = ""
    weight_quant_bits: int = 0
    # host-local mesh axes for this shard's window (parallel/shard_mesh.py):
    # 0 = use the shard's own DNET_SHARD_MESH_* defaults; -1 tp = all chips
    mesh_tp: int = 0
    mesh_sp: int = 0
    # NamedSharding tensor parallelism (parallel/tp.py): the solver's
    # mesh-slice placement ships the shard's tp degree here; 0 = the
    # shard's own DNET_TP default, 1 = single-chip.  Mutually exclusive
    # with a >1 mesh_tp/mesh_sp (one TP substrate per shard).
    tp_degree: int = 0
    # ring speculation (head drafts / tail verifies, shard/compute.py);
    # the API only sets this on single-round rewind-safe rings
    spec_lookahead: int = 0
    # batched lanes (shard/lanes.py): >1 allocates a pooled KV cache so the
    # API may coalesce that many concurrent nonces into one ring pass
    lanes: int = 0
    # ring prefix caching (shard/compute.py): per-shard KV snapshot count;
    # the API keys every store/hit through the prompt frames
    prefix_cache: int = 0
    # topology epoch this load pins (dnet_tpu/membership/): the shard
    # rejects frames/RPCs carrying any other nonzero epoch afterwards
    epoch: int = 0


class UpdateTopologyRequest(BaseModel):
    """Delta reconfiguration (dnet_tpu/membership/): bump the epoch, drop
    per-request state, rewire the next pointer — WITHOUT re-reading
    weights.  The shard verifies it really holds `model_path` + `layers`
    (a restarted shard holds neither) and answers 409 so the API falls
    back to a full /load_model."""

    model_path: str
    layers: List[int]
    epoch: int = 0
    next_node: Optional[NextNode] = None


class MeasureLatencyRequest(BaseModel):
    peers: List[str]  # "host:grpc_port"
    payload_sizes: List[int] = Field(default_factory=lambda: [1024, 65536, 1048576])
    rounds: int = 3


class ShardHTTPServer:
    def __init__(self, shard) -> None:
        self.shard = shard  # Shard facade (runtime + adapter)
        self.app = web.Application(client_max_size=16 * 1024 * 1024)
        self.app.router.add_get("/health", self.health)
        self.app.router.add_get("/metrics", self.metrics)
        self.app.router.add_get(
            "/v1/debug/timeline/{rid}", self.debug_timeline
        )
        self.app.router.add_get("/v1/debug/events", self.debug_events)
        self.app.router.add_post("/load_model", self.load_model)
        self.app.router.add_post("/update_topology", self.update_topology)
        self.app.router.add_post("/unload_model", self.unload_model)
        self.app.router.add_post("/measure_latency", self.measure_latency)
        self.app.router.add_post("/profile", self.profile)
        self.app.router.add_post("/probe_stage", self.probe_stage)
        self.app.router.add_post("/cleanup_repacked", self.cleanup_repacked)
        self._runner: Optional[web.AppRunner] = None

    async def start(self, host: str, port: int) -> None:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        log.info("shard HTTP listening on %s:%d", host, port)

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()
            self._runner = None

    # ---- handlers -----------------------------------------------------
    async def metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition of this shard process's registry."""
        from dnet_tpu.obs.http import metrics_response

        return await metrics_response(request)

    async def debug_timeline(self, request: web.Request) -> web.Response:
        """This shard's recorded spans for one request nonce — the
        shard-side half (transport_recv, token_rpc, layer_compute, ...) of
        the timeline the API server exposes under the same path.  The 404
        shape follows this server's `{"status": "error"}` convention."""
        from dnet_tpu.obs.http import find_timeline

        rid = request.match_info["rid"]
        timeline = find_timeline(rid)
        if timeline is None:
            return web.json_response(
                {"status": "error",
                 "message": f"no recorded timeline for {rid!r}"},
                status=404,
            )
        return web.json_response(timeline)

    async def debug_events(self, request: web.Request) -> web.Response:
        """This shard's wide-event ring (obs/events.py), filtered by
        ?rid= / ?name= / ?last_s=.  `t_wall` is stamped at response build
        so the API's `?cluster=1` fetch doubles as the clock probe that
        rebases these events onto the driver's clock — the same trick the
        cluster timeline fetch uses."""
        import time as _time

        from dnet_tpu.obs.events import get_event_ring

        try:
            last_s = float(request.query.get("last_s", "") or 0.0)
        except ValueError:
            return web.json_response(
                {"status": "error", "message": "last_s must be a number"},
                status=400,
            )
        ring = get_event_ring()
        events = ring.query(
            rid=request.query.get("rid", "").strip(),
            name=request.query.get("name", "").strip(),
            last_s=last_s,
        )
        return web.json_response({
            "events": events,
            "dropped": ring.dropped,
            "t_wall": _time.time(),
        })

    async def health(self, request: web.Request) -> web.Response:
        rt = self.shard.runtime
        compute = rt.compute
        mesh = {}
        if compute is not None:
            eng = compute.engine
            mesh = {"mesh_tp": getattr(eng, "tp", 1), "mesh_sp": getattr(eng, "sp", 1)}
            from dnet_tpu.parallel.tp import TpEngine

            if isinstance(eng, TpEngine):
                mesh = {
                    "tp_degree": eng.tp,
                    "tp_collective": eng.collective_mode,
                }
            if compute.prefix_snaps is not None:
                mesh["prefix_cache"] = dict(compute.prefix_snaps.stats)
        from dnet_tpu.resilience.chaos import armed_summary

        chaos = armed_summary()
        if chaos is not None:
            mesh["chaos"] = chaos
        from dnet_tpu.ops.kernel_select import SELECTIONS, device_report

        return web.json_response(
            {
                "status": "ok",
                "role": "shard",
                "device": device_report(),
                "kernels": SELECTIONS.snapshot(),
                "shard_id": rt.shard_id,
                "model": rt.model_path or None,
                "layers": list(compute.layers) if compute else [],
                "queue_depth": rt.queue_depth,
                "epoch": rt.epoch,
                **mesh,
            }
        )

    async def load_model(self, request: web.Request) -> web.Response:
        try:
            req = ShardLoadModelRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return web.json_response(
                {"status": "error", "message": f"invalid request: {exc}"}, status=400
            )
        t0 = time.perf_counter()
        try:
            await self.shard.load_model(req)
        except FileNotFoundError as exc:
            return web.json_response(
                {"status": "error", "message": str(exc)}, status=404
            )
        except Exception as exc:
            log.exception("shard load_model failed")
            return web.json_response(
                {"status": "error", "message": str(exc)}, status=500
            )
        return web.json_response(
            {"status": "ok", "load_time_s": time.perf_counter() - t0}
        )

    async def update_topology(self, request: web.Request) -> web.Response:
        """Delta reload's cheap half: epoch bump + state drop + rewire for
        a shard whose layer range (and every other load parameter) is
        unchanged.  409 when this shard cannot prove it holds the expected
        model/layers — the API then ships a full /load_model instead."""
        try:
            req = UpdateTopologyRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return web.json_response(
                {"status": "error", "message": f"invalid request: {exc}"}, status=400
            )
        try:
            await self.shard.update_topology(req)
        except ValueError as exc:
            # holds nothing / wrong model / wrong layers: a delta update
            # would serve garbage — refuse so the caller full-loads
            return web.json_response(
                {"status": "error", "message": str(exc)}, status=409
            )
        except Exception as exc:
            log.exception("shard update_topology failed")
            return web.json_response(
                {"status": "error", "message": str(exc)}, status=500
            )
        return web.json_response(
            {"status": "ok", "epoch": self.shard.runtime.epoch}
        )

    async def unload_model(self, request: web.Request) -> web.Response:
        await self.shard.unload_model()
        return web.json_response({"status": "ok"})

    async def cleanup_repacked(self, request: web.Request) -> web.Response:
        """Delete repack caches: the current model's subtree when a model is
        loaded, otherwise the whole cache dir (reference
        shard/http_api.py:222-336 + utils/repack.py:220-313)."""
        import asyncio
        import shutil
        from pathlib import Path

        from dnet_tpu.config import get_settings

        rt = self.shard.runtime

        def cleanup():
            # under the model lock: a concurrent /load_model can't be mid-
            # construction (it holds the same lock), so the streams check and
            # the delete are atomic w.r.t. loads
            with rt._model_lock:
                compute = rt.compute
                if compute is not None and compute.engine.plan.streams_weights:
                    return None, 0  # refuse: live engine reads this cache
                base = Path(get_settings().shard.repack_dir).expanduser()
                target = base
                if rt.model_path:
                    target = base / Path(rt.model_path).name
                freed = 0
                if target.is_dir():
                    freed = sum(
                        f.stat().st_size for f in target.rglob("*") if f.is_file()
                    )
                    shutil.rmtree(target, ignore_errors=True)
                return str(target), freed

        loop = asyncio.get_running_loop()
        removed, freed = await loop.run_in_executor(None, cleanup)
        if removed is None:
            return web.json_response(
                {
                    "status": "error",
                    "message": "model is streaming from the repack cache; "
                    "POST /unload_model first",
                },
                status=409,
            )
        return web.json_response(
            {"status": "ok", "removed": removed, "freed_bytes": freed}
        )

    async def measure_latency(self, request: web.Request) -> web.Response:
        """Probe each peer over gRPC with increasing payloads; return
        median RTT seconds per (peer, size) (reference shard/http_api.py:85-204)."""
        try:
            req = MeasureLatencyRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return web.json_response(
                {"status": "error", "message": f"invalid request: {exc}"}, status=400
            )
        from dnet_tpu.obs.clock import ClockSync
        from dnet_tpu.transport.grpc_transport import RingClient
        from dnet_tpu.transport.protocol import LatencyProbe

        results = {}
        clocks = ClockSync()  # min-RTT offset per peer from the same probes
        for peer in req.peers:
            client = RingClient(peer)
            peer_res = {}
            try:
                for size in req.payload_sizes:
                    rtts = []
                    payload = b"\x00" * size
                    for _ in range(req.rounds):
                        t0 = time.perf_counter()
                        t0_wall = time.time()
                        try:
                            echo = await client.measure_latency(
                                LatencyProbe(t_sent=t0_wall, payload=payload)
                            )
                            rtts.append(time.perf_counter() - t0)
                            if getattr(echo, "t_remote", 0.0):
                                clocks.update(
                                    peer, t0_wall, echo.t_remote, time.time()
                                )
                        except Exception as exc:
                            log.warning("latency probe to %s failed: %s", peer, exc)
                    if rtts:
                        rtts.sort()
                        peer_res[str(size)] = rtts[len(rtts) // 2]
            finally:
                await client.close()
            results[peer] = peer_res
        offsets = {
            peer: {
                "offset_s": est.offset_s,
                "rtt_s": est.rtt_s,
            }
            for peer in req.peers
            if (est := clocks.estimate(peer)) is not None
        }
        return web.json_response(
            {"status": "ok", "latency": results, "clock_offsets": offsets}
        )

    async def probe_stage(self, request: web.Request) -> web.Response:
        """Measured seconds/token for this shard's loaded stage (solver
        calibration input; parallel/calibrate.py)."""
        rt = self.shard.runtime
        if rt.compute is None:
            return web.json_response(
                {"status": "error", "message": "no model loaded"}, status=409
            )
        try:
            steps = int(request.query.get("steps", "3"))
        except ValueError:
            return web.json_response(
                {"status": "error", "message": "steps must be an integer"},
                status=400,
            )
        loop = asyncio.get_running_loop()
        try:
            stage_s = await loop.run_in_executor(
                None, rt.compute.probe_stage_time, max(1, min(steps, 16))
            )
        except Exception as exc:
            log.exception("stage probe failed")
            return web.json_response(
                {"status": "error", "message": str(exc)}, status=500
            )
        return web.json_response({"status": "ok", "stage_time_s": stage_s})

    async def profile(self, request: web.Request) -> web.Response:
        """Device microbenchmark, in this process: it owns the chips."""
        from dnet_tpu.parallel.profiler import profile_device_quick

        loop = asyncio.get_running_loop()
        result = await loop.run_in_executor(None, profile_device_quick)
        return web.json_response({"status": "ok", "profile": result})
