"""API-node HTTP server (aiohttp): OpenAI-compatible /v1 routes.

Routes (reference: src/dnet/api/http_api.py:75-93):
  POST /v1/chat/completions    — SSE streaming + aggregate
  GET  /v1/models              — catalog + currently loaded model
  POST /v1/load_model          — load (single-process or fan-out)
  POST /v1/unload_model
  GET  /v1/topology            — current topology (ring mode)
  GET  /v1/devices             — discovered devices
  GET  /health                 — + rolling SLO status (degraded when burning)
  GET  /metrics                — Prometheus text exposition (dnet_tpu.obs)
  GET  /v1/cluster/metrics     — every node's /metrics federated (node labels)
  GET  /v1/debug/timeline/{rid} — one request's flight-recorder spans;
                                  ?cluster=1 stitches every shard's spans
                                  into one skew-corrected timeline; the
                                  response embeds the request's
                                  critical-path segment ledger
  GET  /v1/debug/sched          — scheduler tick flight-recorder ring
                                  (sched/flight.py; scheduler loads)
  GET  /v1/debug/trace/{rid}    — one request as Chrome trace-event /
                                  Perfetto JSON (?cluster=1 stitches)
  GET  /v1/debug/trace?last_s=N — serving-window Perfetto dump (every
                                  retained timeline + tick records +
                                  wide-event instants)
  GET  /v1/debug/events         — structured wide-event ring
                                  (obs/events.py); ?rid= / ?name= /
                                  ?last_s= filter, ?cluster=1 merges every
                                  shard's ring onto this node's clock
FastAPI is not available in this image; aiohttp's request handling + a thin
pydantic validation shim cover the same surface.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Optional

from aiohttp import web
from pydantic import ValidationError

from dnet_tpu.admission.controller import AdmissionRejected
from dnet_tpu.api.catalog import model_catalog
from dnet_tpu.api.inference import (
    BackpressureError,
    DeadlineExceededError,
    EngineCapabilityError,
    InferenceError,
    InferenceManager,
    PromptTooLongError,
    ServiceDegradedError,
)
from dnet_tpu.api.schemas import (
    ChatCompletionRequest,
    HealthResponse,
    LoadModelRequest,
    LoadModelResponse,
    ModelInfo,
    ModelList,
    UnloadModelResponse,
)
from dnet_tpu.utils.logger import get_logger

log = get_logger()


def _json_error(
    status: int,
    message: str,
    err_type: str = "invalid_request_error",
    retry_after_s: Optional[float] = None,
):
    headers = None
    if retry_after_s is not None:
        # Retry-After is integral seconds per RFC 9110; never advertise 0
        headers = {"Retry-After": str(max(1, round(retry_after_s)))}
    return web.json_response(
        {"error": {"message": message, "type": err_type}},
        status=status,
        headers=headers,
    )


class ApiHTTPServer:
    def __init__(
        self,
        inference: InferenceManager,
        model_manager,
        cluster_manager=None,
        fleet=None,
    ) -> None:
        self.inference = inference
        self.model_manager = model_manager
        self.cluster_manager = cluster_manager
        # DNET_FLEET>1: a FleetManager routes decode endpoints across
        # replicas; None (the default) keeps the single-ring path with
        # zero new code between request and stream
        self.fleet = fleet
        self.app = web.Application(client_max_size=64 * 1024 * 1024)
        self.app.router.add_post("/v1/chat/completions", self.chat_completions)
        self.app.router.add_post("/v1/completions", self.completions)
        self.app.router.add_post("/v1/embeddings", self.embeddings)
        self.app.router.add_get("/v1/models", self.list_models)
        self.app.router.add_post("/v1/load_model", self.load_model)
        self.app.router.add_post("/v1/unload_model", self.unload_model)
        self.app.router.add_post("/v1/prepare_topology", self.prepare_topology)
        self.app.router.add_post("/v1/prepare_topology_manual", self.prepare_topology_manual)
        self.app.router.add_get("/v1/topology", self.get_topology)
        self.app.router.add_post("/v1/calibrate", self.calibrate)
        self.app.router.add_get("/v1/devices", self.get_devices)
        self.app.router.add_get("/health", self.health)
        self.app.router.add_get("/metrics", self.metrics)
        self.app.router.add_get("/v1/cluster/metrics", self.cluster_metrics)
        self.app.router.add_get(
            "/v1/debug/timeline/{rid}", self.debug_timeline
        )
        self.app.router.add_get("/v1/debug/sched", self.debug_sched)
        self.app.router.add_get("/v1/debug/trace", self.debug_trace_window)
        self.app.router.add_get("/v1/debug/trace/{rid}", self.debug_trace)
        self.app.router.add_get("/v1/debug/events", self.debug_events)
        self.app.router.add_get("/v1/debug/fleet", self.debug_fleet)
        self._runner: Optional[web.AppRunner] = None
        # peers seen by earlier /v1/cluster/metrics scrapes: a peer that
        # leaves discovery must drop to scrape_ok 0, not freeze at 1
        self._scraped_peers: set = set()

    # ---- lifecycle ----------------------------------------------------
    async def start(self, host: str, port: int) -> None:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        log.info("API HTTP listening on %s:%d", host, port)

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()
            self._runner = None

    # ---- decode-endpoint scaffolding ---------------------------------
    def _gate(self):
        """Shared pre-admission checks for decode endpoints (None = pass)."""
        if self.fleet is not None:
            # fleet mode: any serving replica admits the request — the
            # router walks the candidates; only a fleet with NO serving
            # replica falls through to the single-ring diagnostics below
            # (which then describe the primary honestly)
            if any(
                h.serving and getattr(h.inference, "ready", False)
                for h in self.fleet.handles()
            ):
                return None
        admission = self.inference.admission
        if admission.draining:
            # drain window (SIGTERM): in-flight streams finish; new work
            # is told exactly when to come back
            return _json_error(
                503,
                "server is draining for shutdown",
                "service_unavailable",
                retry_after_s=admission.retry_after_s(),
            )
        if not self.inference.ready:
            return _json_error(400, "no model loaded; POST /v1/load_model first")
        monitor = self.inference.failure_monitor
        if monitor is not None and monitor.degraded:
            return _json_error(
                503,
                f"ring degraded: shard(s) {monitor.down_shards()} down",
                "service_unavailable",
            )
        return None

    async def _sse(self, request, req, reshape) -> web.StreamResponse:
        """Stream the decode chunks as SSE; `reshape(chunk) -> [json str]`.

        The FIRST chunk is awaited before the SSE response commits to a
        200: anything shed before the first token — admission rejection
        (429 + Retry-After), drain (503), expired deadline (504), prompt
        too long (400), prefill backpressure (429) — keeps its real HTTP
        status instead of dying inside a 200 stream.  Past the first
        chunk the status is sent; errors become in-band SSE events.

        The generator is ALWAYS closed on the way out: a client that
        disconnects mid-stream closes it (GeneratorExit), which fans
        cancel + reset_cache out through the ring (InferenceManager) and
        frees the admission slot immediately."""
        route_info: dict = {}
        if self.fleet is not None:
            gen = self.fleet.stream(req, route_info)
        else:
            gen = self.inference.generate_stream(req)
        try:
            try:
                first = await gen.__anext__()
            except StopAsyncIteration:
                first = None
            except Exception as exc:
                return self._map_inference_errors(exc)
            headers = {
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            }
            if route_info.get("replica"):
                # per-replica outcome attribution for loadgen/report: the
                # serving replica is decided by first-chunk time (fleet
                # routing fills route_info during admission)
                headers["x-dnet-replica"] = route_info["replica"]
            resp = web.StreamResponse(status=200, headers=headers)
            await resp.prepare(request)

            async def write_chunk(chunk) -> None:
                # serialize + flush, timed as the request's sse_flush
                # segment (obs/critical_path.py): the one leg of a
                # request's story that happens after the driver hands a
                # chunk back
                from dnet_tpu.obs import get_recorder, observe_span
                from dnet_tpu.obs.phases import SPAN_SSE_FLUSH

                t_w = time.perf_counter()
                for payload in reshape(chunk):
                    await resp.write(f"data: {payload}\n\n".encode())
                flush_ms = (time.perf_counter() - t_w) * 1000.0
                get_recorder().span(chunk.id, "sse_flush", flush_ms)
                # histogram only: this coroutine awaits, and a profiler
                # annotation must open and close without yielding its thread
                observe_span(SPAN_SSE_FLUSH, flush_ms)

            try:
                if first is not None:
                    await write_chunk(first)
                    async for chunk in gen:
                        await write_chunk(chunk)
                await resp.write(b"data: [DONE]\n\n")
            except PromptTooLongError as exc:
                err = json.dumps(
                    {"error": {"message": str(exc), "type": "invalid_request_error"}}
                )
                await resp.write(f"data: {err}\n\n".encode())
            except DeadlineExceededError as exc:
                err = json.dumps(
                    {"error": {"message": str(exc), "type": "deadline_exceeded"}}
                )
                await resp.write(f"data: {err}\n\n".encode())
            except BackpressureError as exc:
                # capacity shed mid-stream is not a server fault: keep the
                # status contract's semantics in the in-band event type
                err = json.dumps(
                    {"error": {"message": str(exc), "type": "rate_limit_exceeded"}}
                )
                await resp.write(f"data: {err}\n\n".encode())
            except InferenceError as exc:
                err = json.dumps({"error": {"message": str(exc), "type": "server_error"}})
                await resp.write(f"data: {err}\n\n".encode())
            except ConnectionResetError:
                log.info("client disconnected mid-stream")
            await resp.write_eof()
            return resp
        finally:
            # closing an already-finished generator is a no-op; closing an
            # abandoned one (disconnect / handler error) triggers the
            # cancel fan-out in InferenceManager._run
            await gen.aclose()

    def _map_inference_errors(self, exc: Exception):
        from dnet_tpu.fleet.router import FleetSheddingError

        if isinstance(exc, FleetSheddingError):
            # every fleet replica shed: same client contract as a single
            # ring's capacity shed — 429 with the soonest honest Retry-After
            return _json_error(
                429,
                str(exc),
                "rate_limit_exceeded",
                retry_after_s=exc.retry_after_s,
            )
        if isinstance(exc, AdmissionRejected):
            status = 503 if exc.reason == "draining" else 429
            return _json_error(
                status,
                str(exc),
                "service_unavailable" if status == 503 else "rate_limit_exceeded",
                retry_after_s=exc.retry_after_s,
            )
        if isinstance(exc, BackpressureError):
            return _json_error(
                429,
                str(exc),
                "rate_limit_exceeded",
                retry_after_s=self.inference.admission.retry_after_s(),
            )
        if isinstance(exc, DeadlineExceededError):
            return _json_error(504, str(exc), "deadline_exceeded")
        if isinstance(exc, PromptTooLongError):
            return _json_error(400, str(exc))
        if isinstance(exc, EngineCapabilityError):
            # the serving config asked this engine for something it cannot
            # do — a 4xx the operator fixes, not a server fault
            return _json_error(422, str(exc), "invalid_request_error")
        if isinstance(exc, ServiceDegradedError):
            return _json_error(503, str(exc), "service_unavailable")
        if isinstance(exc, ConnectionError):
            # transport-class failure before any chunk was written (a
            # broken channel, or an injected chaos fault at a pre-stream
            # point like `admit`): the request never started, so it is
            # retryable service unavailability — never a 500.  The chaos
            # campaign's status-code contract pins this.
            return _json_error(503, str(exc), "service_unavailable")
        if isinstance(exc, InferenceError):
            return _json_error(500, str(exc), "server_error")
        raise exc

    # ---- handlers -----------------------------------------------------
    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            req = ChatCompletionRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return _json_error(400, f"invalid request: {exc}")
        gate = self._gate()
        if gate is not None:
            return gate

        if req.stream:
            return await self._sse(
                request, req, lambda c: [c.model_dump_json(exclude_none=True)]
            )
        route_info: dict = {}
        try:
            if self.fleet is not None:
                result = await self.fleet.generate(req, route_info)
            else:
                result = await self.inference.generate(req)
        except Exception as exc:
            return self._map_inference_errors(exc)
        headers = (
            {"x-dnet-replica": route_info["replica"]}
            if route_info.get("replica")
            else None
        )
        return web.json_response(
            result.model_dump(exclude_none=True), headers=headers
        )

    async def completions(self, request: web.Request) -> web.StreamResponse:
        """Legacy /v1/completions: raw prompt, text_completion objects."""
        from dnet_tpu.api.inference import completion_logprobs
        from dnet_tpu.api.schemas import CompletionRequest

        try:
            req = CompletionRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return _json_error(400, f"invalid request: {exc}")
        gate = self._gate()
        if gate is not None:
            return gate

        if req.stream:
            state = {"first": True, "offset": len(req.prompt_text()) if req.echo else 0}

            def reshape(chunk):
                """Chat-style deltas -> completion chunks (echo emits the
                prompt before the first delta; logprobs use the completions
                shape)."""
                out = {
                    "id": chunk.id.replace("chatcmpl", "cmpl"),
                    "object": "text_completion",
                    "model": req.model,
                    "choices": [],
                }
                for c in chunk.choices:
                    text = c.delta.content or ""
                    if state["first"] and (text or c.finish_reason):
                        state["first"] = False
                        if req.echo:
                            text = req.prompt_text() + text
                    choice = {"index": 0, "text": text, "finish_reason": c.finish_reason}
                    if c.logprobs is not None:
                        lp = completion_logprobs(c.logprobs.content, state["offset"])
                        state["offset"] += sum(len(t) for t in lp.tokens)
                        choice["logprobs"] = lp.model_dump()
                    out["choices"].append(choice)
                if chunk.usage:
                    out["usage"] = chunk.usage.model_dump()
                return [json.dumps(out)]

            return await self._sse(request, req, reshape)
        route_info: dict = {}
        try:
            if self.fleet is not None:
                result = await self.fleet.generate(
                    req, route_info, method="generate_completion"
                )
            else:
                result = await self.inference.generate_completion(req)
        except Exception as exc:
            return self._map_inference_errors(exc)
        headers = (
            {"x-dnet-replica": route_info["replica"]}
            if route_info.get("replica")
            else None
        )
        return web.json_response(
            result.model_dump(exclude_none=True), headers=headers
        )

    async def embeddings(self, request: web.Request) -> web.Response:
        """Mean-pooled final-hidden-state embeddings (BEYOND the reference,
        whose embeddings schema exists in api/models.py with no serving
        path).  Local/batched/mesh strategies serve; the gRPC ring —
        where shards never ship hidden states to the API node — answers
        501."""
        from dnet_tpu.api.schemas import EmbeddingsRequest

        try:
            req = EmbeddingsRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return _json_error(400, f"invalid request: {exc}")
        gate = self._gate()
        if gate is not None:
            return gate
        try:
            result = await self.inference.embeddings(req)
        except NotImplementedError as exc:
            return _json_error(501, str(exc), "not_implemented")
        except ValueError as exc:
            return _json_error(400, str(exc))
        except Exception as exc:
            return self._map_inference_errors(exc)
        return web.json_response(result.model_dump())

    async def list_models(self, request: web.Request) -> web.Response:
        # quant-variant aliases listed alongside base ids (reference-style
        # per-variant catalog rows; `<id>:int8` resolves via resolve_variant)
        from dnet_tpu.api.catalog import expanded_catalog

        data = [ModelInfo(id=e.id) for e in expanded_catalog()]
        loaded = self.model_manager.current_model_id
        if loaded and all(m.id != loaded for m in data):
            data.append(ModelInfo(id=loaded))
        return web.json_response(ModelList(data=data).model_dump())

    async def load_model(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            req = LoadModelRequest.model_validate(body)
        except (json.JSONDecodeError, ValidationError) as exc:
            return _json_error(400, f"invalid request: {exc}")
        kwargs = {}
        if req.delta:
            # `delta` only reaches managers that speak it (the ring
            # manager); the single-process manager has no fan-out to diff
            import inspect

            params = inspect.signature(
                self.model_manager.load_model
            ).parameters
            if "delta" not in params:
                return _json_error(
                    400, "delta reload is only available in ring mode"
                )
            kwargs["delta"] = True
        try:
            dt = await self.model_manager.load_model(
                req.model, max_seq=req.max_seq_len, **kwargs
            )
        except FileNotFoundError as exc:
            return _json_error(404, str(exc), "model_not_found")
        except EngineCapabilityError as exc:
            # e.g. continuous batching requested over streamed weights or a
            # model without gated KV writes (core/batch.py): the config is
            # at fault, not the server — 422, with nothing half-loaded
            return _json_error(422, str(exc), "invalid_request_error")
        except Exception as exc:
            log.exception("load_model failed")
            return _json_error(500, f"load failed: {exc}", "server_error")
        return web.json_response(
            LoadModelResponse(model=req.model, load_time_s=dt).model_dump()
        )

    async def unload_model(self, request: web.Request) -> web.Response:
        await self.model_manager.unload_model()
        return web.json_response(UnloadModelResponse(message="unloaded").model_dump())

    async def prepare_topology(self, request: web.Request) -> web.Response:
        """Auto pipeline: discover -> profile -> solve (reference
        http_api.py:254-303)."""
        from dnet_tpu.api.schemas import PrepareTopologyRequest

        if self.cluster_manager is None:
            return _json_error(400, "not in ring mode (no discovery configured)")
        try:
            req = PrepareTopologyRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return _json_error(400, f"invalid request: {exc}")

        from dnet_tpu.api.model_manager import resolve_model_dir
        from dnet_tpu.parallel.solver import (
            model_profile_from_checkpoint,
            solve_topology,
        )

        model_dir = resolve_model_dir(
            req.model, getattr(self.model_manager, "models_dir", None)
        )
        if model_dir is None:
            return _json_error(404, f"model {req.model!r} not found locally", "model_not_found")

        devices = await self.cluster_manager.profile_cluster()
        if not devices:
            return _json_error(503, "no healthy shards discovered", "no_devices")
        # fold in measured stage-time ratios from earlier /v1/calibrate runs
        devices = self.cluster_manager.apply_stage_ratios(devices)
        try:
            profile = model_profile_from_checkpoint(
                model_dir,
                seq_len=req.seq_len,
                kv_bits=req.kv_bits,
                weight_quant_bits=getattr(
                    self.model_manager, "weight_quant_bits", 0
                ),
            )
            from dnet_tpu.config import get_settings

            topo = solve_topology(
                devices,
                profile,
                kv_bits=req.kv_bits,
                solver=get_settings().topology.solver,
                mip_gap=get_settings().topology.mip_gap,
            )
        except ValueError as exc:
            return _json_error(400, str(exc))
        topo.model = req.model
        # install (not assign): minting the membership epoch here is what
        # arms the zombie fence for the upcoming load fan-out
        self.cluster_manager.install_topology(topo)
        return web.json_response(
            {
                "status": "ok",
                "topology": {
                    "model": topo.model,
                    "num_layers": topo.num_layers,
                    "epoch": topo.epoch,
                    "solution": topo.solution,
                    "assignments": [
                        {
                            "instance": a.instance,
                            "layers": a.layers,
                            "next_instance": a.next_instance,
                            "window_size": a.window_size,
                            "residency_size": a.residency_size,
                            "mesh_tp": a.mesh_tp,
                            "mesh_sp": a.mesh_sp,
                        }
                        for a in topo.assignments
                    ],
                },
            }
        )

    async def prepare_topology_manual(self, request: web.Request) -> web.Response:
        """Manual layer assignment -> ring topology (reference
        http_api.py:305-403).  Requires ring mode (a cluster manager)."""
        from dnet_tpu.api.schemas import PrepareTopologyManualRequest

        if self.cluster_manager is None:
            return _json_error(400, "not in ring mode (no discovery configured)")
        try:
            req = PrepareTopologyManualRequest.model_validate(await request.json())
        except (json.JSONDecodeError, ValidationError) as exc:
            return _json_error(400, f"invalid request: {exc}")

        from dnet_tpu.api.model_manager import resolve_model_dir
        from dnet_tpu.api.ring_manager import build_manual_topology

        model_dir = resolve_model_dir(
            req.model, getattr(self.model_manager, "models_dir", None)
        )
        if model_dir is None:
            return _json_error(404, f"model {req.model!r} not found locally", "model_not_found")
        num_layers = json.loads((model_dir / "config.json").read_text())[
            "num_hidden_layers"
        ]
        devices = await self.cluster_manager.healthy_devices()
        try:
            topo = build_manual_topology(
                req.model,
                num_layers,
                [a.model_dump() for a in req.assignments],
                devices,
                kv_bits=req.kv_bits,
            )
        except ValueError as exc:
            return _json_error(400, str(exc))
        self.cluster_manager.install_topology(topo)
        return web.json_response(
            {
                "status": "ok",
                "topology": {
                    "model": topo.model,
                    "num_layers": topo.num_layers,
                    "epoch": topo.epoch,
                    "assignments": [
                        {
                            "instance": a.instance,
                            "layers": a.layers,
                            "next_instance": a.next_instance,
                            "mesh_tp": a.mesh_tp,
                            "mesh_sp": a.mesh_sp,
                        }
                        for a in topo.assignments
                    ],
                },
            }
        )

    async def calibrate(self, request: web.Request) -> web.Response:
        """Probe every loaded shard's measured stage time, compare with the
        solver's predictions, optionally store the ratios for future solves
        (body: {"steps": 3, "apply": false})."""
        if self.cluster_manager is None:
            return _json_error(400, "not in ring mode (no discovery configured)")
        try:
            body = await request.json() if request.can_read_body else {}
        except json.JSONDecodeError:
            body = {}
        if not isinstance(body, dict):
            return _json_error(400, "body must be a JSON object")
        try:
            steps = int(body.get("steps", 3) or 3)
        except (TypeError, ValueError):
            return _json_error(400, "steps must be an integer")
        if not 1 <= steps <= 16:
            return _json_error(400, "steps must be between 1 and 16")
        try:
            cals = await self.cluster_manager.calibrate_topology(steps=steps)
        except ValueError as exc:
            return _json_error(409, str(exc))
        if body.get("apply"):
            self.cluster_manager.store_stage_ratios(cals)
        from dnet_tpu.parallel.calibrate import max_rel_err

        return web.json_response(
            {
                "calibrations": [c.as_dict() for c in cals],
                "max_rel_err": max_rel_err(cals),
                "applied": bool(body.get("apply")),
            }
        )

    async def get_topology(self, request: web.Request) -> web.Response:
        if self.cluster_manager is None or getattr(self.cluster_manager, "current_topology", None) is None:
            return web.json_response({"topology": None})
        topo = self.cluster_manager.current_topology
        return web.json_response(
            {
                "topology": {
                    "model": topo.model,
                    "num_layers": topo.num_layers,
                    "kv_bits": topo.kv_bits,
                    "epoch": topo.epoch,
                    "assignments": [
                        {
                            "instance": a.instance,
                            "layers": a.layers,
                            "next_instance": a.next_instance,
                            "window_size": a.window_size,
                            "residency_size": a.residency_size,
                            "mesh_tp": a.mesh_tp,
                            "mesh_sp": a.mesh_sp,
                        }
                        for a in topo.assignments
                    ],
                    "solution": topo.solution,
                }
            }
        )

    async def get_devices(self, request: web.Request) -> web.Response:
        if self.cluster_manager is None:
            return web.json_response({"devices": []})
        devices = await self.cluster_manager.scan_devices()
        return web.json_response(
            {
                "devices": [
                    {
                        "instance": d.instance,
                        "host": d.host,
                        "http_port": d.http_port,
                        "grpc_port": d.grpc_port,
                        "is_manager": d.is_manager,
                        "slice_id": d.slice_id,
                        "chip_count": d.chip_count,
                    }
                    for d in devices
                ]
            }
        )

    async def health(self, request: web.Request) -> web.Response:
        from dnet_tpu.obs import get_slo_tracker
        from dnet_tpu.resilience.chaos import armed_summary

        body = HealthResponse(model=self.model_manager.current_model_id).model_dump()
        # armed chaos is ALWAYS visible here: an operator reading /health
        # during an incident must be able to tell injected faults from
        # real ones at a glance (absent when no chaos is armed)
        chaos = armed_summary()
        if chaos is not None:
            body["chaos"] = chaos
        # membership view: the installed topology's epoch and the fenced-out
        # (quarantined, still-probed) shards — a degraded-membership ring is
        # visible here and through the federation scrape at a glance
        if self.cluster_manager is not None:
            body["epoch"] = getattr(self.cluster_manager, "epoch", 0)
        else:
            # local mode computes in this process: name the device it runs
            # on and what each kernel dispatcher resolved to.  (A ring-mode
            # API node owns no chip and must not open one to answer this;
            # its shards report their own.)
            from dnet_tpu.ops.kernel_select import SELECTIONS, device_report

            body["device"] = device_report()
            body["kernels"] = SELECTIONS.snapshot()
            serving = getattr(self.model_manager, "serving", None)
            if serving is not None:
                # engine, adapter, KV layout and why (model_manager.py:
                # serving_plan): the path no setting selects
                body["serving"] = serving._asdict()
                engine = getattr(self.inference.adapter, "engine", None)
                refusal = getattr(engine, "prefix_refusal", None)
                if refusal is not None:
                    # prefix sharing was asked for and the cache's kind
                    # cannot give it (core/batch.py: _init_state_store)
                    body["serving"]["prefix_sharing"] = f"off: {refusal}"
        monitor = self.inference.failure_monitor
        quarantine = getattr(monitor, "quarantine", None)
        if quarantine is not None:
            # quarantined shards don't degrade `status` — the re-solved
            # ring serves fine, just below full capacity — but operators
            # (and the rejoin runbook) see exactly who is out and for how
            # long they've probed green
            body["quarantine"] = quarantine.snapshot()
        if monitor is not None and monitor.health:
            body["shards"] = monitor.snapshot()
            if monitor.degraded:
                body["status"] = "degraded"
        # rolling SLO windows (obs/slo.py): a burning SLO degrades /health
        # even while every shard is up — slow is its own kind of down
        slo = get_slo_tracker().snapshot()
        body["slo"] = slo
        if slo["burning"]:
            body["status"] = "degraded"
        # admission picture: queue/in-flight depths, and the drain state —
        # "draining" wins over "degraded" (load balancers must stop
        # routing here regardless of how healthy the ring looks)
        admission = self.inference.admission
        body["admission"] = {
            "active": admission.active,
            "queued": admission.queued,
            "capacity": admission.capacity,
        }
        if admission.draining:
            body["status"] = "draining"
            # the drain snapshot names the membership state too: a load
            # balancer pulling this node out should know whether the rest
            # of the ring it routes to is at full membership
            body["admission"]["epoch"] = body.get("epoch", 0)
            body["admission"]["quarantine"] = list(
                body.get("quarantine") or ()
            )
        # fleet view: per-replica health snapshots aggregated at the front
        # door.  Serving capacity below fleet size is "degraded" (some
        # replica is down/draining); zero serving replicas wins outright —
        # the single-ring fields above describe only the primary
        if self.fleet is not None:
            replicas = [h.snapshot() for h in self.fleet.handles()]
            serving = sum(
                1 for h in self.fleet.handles()
                if h.serving and getattr(h.inference, "ready", False)
            )
            body["fleet"] = {
                "size": len(replicas),
                "serving": serving,
                "replicas": replicas,
            }
            if serving == 0:
                body["status"] = "draining" if admission.draining else "degraded"
            elif serving < len(replicas):
                if body.get("status") == "ok":
                    body["status"] = "degraded"
            elif body.get("status") == "draining" and serving > 0:
                # the PRIMARY is draining but other replicas still serve:
                # the front door as a whole is degraded, not out
                body["status"] = "degraded"
        return web.json_response(body)

    async def metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition of the process-global registry."""
        from dnet_tpu.obs.http import metrics_response

        return await metrics_response(request)

    async def _fan_out_shards(self, fetch) -> tuple[list, list]:
        """Shared httpx fan-out over the discovered shards (cluster
        metrics + cluster timeline): one AsyncClient with the obs scrape
        timeout, `fetch(client, device)` per device gathered concurrently,
        None results (unreachable / not-found / malformed) dropped.
        Returns (devices, results)."""
        import httpx

        from dnet_tpu.config import get_settings

        devices = await self.cluster_manager.scan_devices()
        timeout = get_settings().obs.cluster_scrape_timeout_s
        async with httpx.AsyncClient(timeout=timeout) as client:
            results = await asyncio.gather(
                *(fetch(client, d) for d in devices)
            )
        return devices, [r for r in results if r is not None]

    async def cluster_metrics(self, request: web.Request) -> web.Response:
        """Federated exposition: every healthy shard's /metrics plus this
        process's registry, each sample re-labeled with `node="<id>"` and
        merged into one Prometheus v0.0.4 document (obs/federation.py).
        Unreachable shards are skipped — and visible as
        `dnet_federation_scrape_ok{node=...} 0` in the API section."""
        from dnet_tpu.obs import (
            CONTENT_TYPE_LATEST,
            get_registry,
            get_slo_tracker,
            metric,
        )
        from dnet_tpu.obs.federation import federate

        sections: list[tuple[str, str]] = []
        if self.cluster_manager is not None:
            import httpx

            scrape_ok = metric("dnet_federation_scrape_ok")

            async def fetch(client, d):
                url = f"http://{d.host}:{d.http_port}/metrics"
                try:
                    r = await client.get(url)
                    r.raise_for_status()
                except httpx.HTTPError as exc:
                    log.warning(
                        "cluster metrics scrape of %s failed: %s",
                        d.instance, exc,
                    )
                    scrape_ok.labels(peer=d.instance).set(0.0)
                    return None
                scrape_ok.labels(peer=d.instance).set(1.0)
                return (d.instance, r.text)

            devices, scraped = await self._fan_out_shards(fetch)
            # a peer that left discovery is no longer scraped at all:
            # zero its gauge so `scrape_ok == 1` means "seen THIS scrape"
            current = {d.instance for d in devices}
            for gone in self._scraped_peers - current:
                scrape_ok.labels(peer=gone).set(0.0)
            self._scraped_peers |= current
            sections.extend(scraped)
        # fleet mode: in-process replicas share this registry (the
        # replica-labeled dnet_fleet_* families are already in the api
        # section), but their admission pictures are per-replica state the
        # registry cannot carry — synthesize one section of replica-labeled
        # gauges so queue skew between replicas shows up in one scrape
        if self.fleet is not None:
            lines = [
                "# HELP dnet_fleet_admission_slots Per-replica admission "
                "occupancy at scrape time (fleet front door)",
                "# TYPE dnet_fleet_admission_slots gauge",
            ]
            for h in self.fleet.handles():
                snap = h.snapshot()
                for field in ("active", "queued", "capacity"):
                    lines.append(
                        f'dnet_fleet_admission_slots{{replica='
                        f'"{h.replica_id}",kind="{field}"}} '
                        f'{float(snap["admission"][field])}'
                    )
            sections.append(("fleet", "\n".join(lines) + "\n"))
        # the API section LAST-built but FIRST-emitted: exposing after the
        # scrapes lets this very response carry their scrape_ok outcomes
        get_slo_tracker().snapshot()
        sections.insert(0, ("api", get_registry().expose()))
        body, skipped = federate(sections)
        for line in skipped:
            log.warning("cluster metrics: dropped unparseable line %s", line)
        return web.Response(
            body=body.encode("utf-8"),
            headers={"Content-Type": CONTENT_TYPE_LATEST},
        )

    async def debug_timeline(self, request: web.Request) -> web.Response:
        """One completed (or in-flight) request's flight-recorder spans —
        rid is the response id (`chatcmpl-...` or the completions-endpoint
        `cmpl-...` form); the recorder keeps the most recent requests, so
        recent rids resolve and ancient ones 404.  With `?cluster=1` the
        response is the MERGED cluster timeline: every shard's spans for
        the rid are fetched over their HTTP servers, skew-corrected onto
        this node's clock, and interleaved with the API's own spans."""
        from dnet_tpu.obs.critical_path import critical_path_section
        from dnet_tpu.obs.http import find_timeline

        rid = request.match_info["rid"]
        timeline = find_timeline(rid)
        cluster = request.query.get("cluster", "").strip().lower()
        if cluster in ("1", "true", "yes", "on"):
            stitched = await self._stitched_timeline(rid, timeline)
            if stitched is None:
                return _json_error(
                    404, f"no recorded timeline for {rid!r} on any node",
                    "not_found",
                )
            stitched["critical_path"] = critical_path_section(stitched)
            return web.json_response(stitched)
        if timeline is None:
            return _json_error(404, f"no recorded timeline for {rid!r}",
                               "not_found")
        payload = dict(timeline)
        payload["critical_path"] = critical_path_section(timeline)
        return web.json_response(payload)

    async def _stitched_timeline(
        self, rid: str, local: Optional[dict]
    ) -> Optional[dict]:
        """Fetch + stitch the shard halves of one request's timeline
        (None when no node recorded anything for the rid).

        Each shard fetch doubles as the clock probe correcting it: the
        response's `t_wall` bracketed by this node's wall clock yields an
        NTP-midpoint offset (obs/clock.py), so span times land on the API
        clock with error bounded by half the fetch round trip."""
        from dnet_tpu.obs.clock import offset_from_probe, stitch_timelines

        # shards key spans by the internal nonce; resolve the public
        # `cmpl-...` alias the same way the local lookup does
        internal = (local or {}).get("rid") or (
            "chat" + rid if rid.startswith("cmpl-") else rid
        )
        remotes = []
        if self.cluster_manager is not None:
            import httpx

            async def fetch(client, d):
                url = (
                    f"http://{d.host}:{d.http_port}"
                    f"/v1/debug/timeline/{internal}"
                )
                t0 = time.time()
                try:
                    r = await client.get(url)
                    t1 = time.time()
                    if r.status_code == 404:
                        return None  # this shard saw no frame for rid
                    r.raise_for_status()
                    tl = r.json()
                except (httpx.HTTPError, ValueError) as exc:
                    log.warning(
                        "cluster timeline fetch from %s failed: %s",
                        d.instance, exc,
                    )
                    return None
                try:
                    est = offset_from_probe(t0, float(tl["t_wall"]), t1)
                    tl["t_unix"] = float(tl["t_unix"])
                    assert isinstance(tl["spans"], list)
                except (KeyError, TypeError, ValueError, AssertionError):
                    # a body we cannot place on our clock (or without
                    # spans) must not 500 the whole merged view
                    log.warning(
                        "cluster timeline from %s malformed; skipping",
                        d.instance,
                    )
                    return None
                return (d.instance, tl, est)

            _devices, remotes = await self._fan_out_shards(fetch)
        if local is None and not remotes:
            return None
        return stitch_timelines(local, remotes, rid=internal)

    async def debug_sched(self, request: web.Request) -> web.Response:
        """Scheduler tick flight-recorder ring (sched/flight.py): per-tick
        token-budget use/waste, prefill/decode split, queue depths by
        state, preemptions, and KV block-pool occupancy.  `?last=N` trims
        the record list to the most recent N ticks."""
        from dnet_tpu.sched.flight import get_tick_recorder

        snap = get_tick_recorder().snapshot()
        last = request.query.get("last", "").strip()
        if last:
            try:
                n = max(0, int(last))
            except ValueError:
                return _json_error(400, "last must be an integer")
            snap["records"] = snap["records"][-n:] if n else []
        return web.json_response(snap)

    async def debug_events(self, request: web.Request) -> web.Response:
        """Query the structured wide-event ring (obs/events.py):
        `?rid=` one request's events (resume segments join their base rid),
        `?name=` one vocabulary entry (400 on an unknown name — typos must
        be loud, not silently empty), `?last_s=N` a trailing window.
        `?cluster=1` additionally fetches every shard's ring — each fetch
        doubling as the clock probe that rebases the shard's `t_unix` onto
        this node's clock — and returns the merged, time-ordered set."""
        from dnet_tpu.obs.events import get_event_ring, merge_remote_events
        from dnet_tpu.obs.phases import EVENT_NAMES

        rid = request.query.get("rid", "").strip()
        name = request.query.get("name", "").strip()
        if name and name not in EVENT_NAMES:
            return _json_error(
                400,
                f"unknown event name {name!r} (one of {sorted(EVENT_NAMES)})",
            )
        last_raw = request.query.get("last_s", "").strip()
        try:
            last_s = float(last_raw) if last_raw else 0.0
        except ValueError:
            return _json_error(400, "last_s must be a number")
        ring = get_event_ring()
        events = ring.query(rid=rid, name=name, last_s=last_s)
        dropped = ring.dropped
        cluster = request.query.get("cluster", "").strip().lower()
        if cluster in ("1", "true", "yes", "on") and (
            self.cluster_manager is not None
        ):
            import httpx

            from dnet_tpu.obs.clock import offset_from_probe

            async def fetch(client, d):
                url = f"http://{d.host}:{d.http_port}/v1/debug/events"
                params = {}
                if rid:
                    params["rid"] = rid
                if name:
                    params["name"] = name
                if last_s:
                    params["last_s"] = str(last_s)
                t0 = time.time()
                try:
                    r = await client.get(url, params=params)
                    t1 = time.time()
                    r.raise_for_status()
                    body = r.json()
                    est = offset_from_probe(t0, float(body["t_wall"]), t1)
                    remote = body["events"]
                    assert isinstance(remote, list)
                except (httpx.HTTPError, ValueError, KeyError,
                        TypeError, AssertionError) as exc:
                    log.warning(
                        "cluster events fetch from %s failed: %s",
                        d.instance, exc,
                    )
                    return None
                return (d.instance, remote, est)

            _devices, remotes = await self._fan_out_shards(fetch)
            # shard drop counts stay shard-local (each ring reports its
            # own loss); the merged view reports only this node's
            events = merge_remote_events(events, remotes)
        return web.json_response({"events": events, "dropped": dropped})

    async def debug_fleet(self, request: web.Request) -> web.Response:
        """Fleet routing introspection: the affinity table, per-replica
        health/load snapshots, and the epoch clock — the operator's view
        of why requests land where they land.  `{"fleet": null}` outside
        fleet mode (DNET_FLEET unset/1), mirroring /v1/topology's shape."""
        if self.fleet is None:
            return web.json_response({"fleet": None})
        return web.json_response({"fleet": self.fleet.snapshot()})

    async def debug_trace(self, request: web.Request) -> web.Response:
        """One request as Chrome trace-event / Perfetto JSON
        (obs/trace.py).  `?cluster=1` stitches every shard's spans in
        first, so the export carries one process track per node with flow
        arrows following the rid across hops.  `?format=` accepts only
        `perfetto` (the sole format) — anything else is a 400 so a typo'd
        format is loud, not silently perfetto."""
        from dnet_tpu.obs.events import get_event_ring
        from dnet_tpu.obs.http import find_timeline
        from dnet_tpu.obs.trace import export_trace
        from dnet_tpu.sched.flight import get_tick_recorder

        fmt = request.query.get("format", "perfetto").strip().lower()
        if fmt not in ("perfetto", "chrome"):
            return _json_error(400, f"unknown trace format {fmt!r}")
        rid = request.match_info["rid"]
        timeline = find_timeline(rid)
        cluster = request.query.get("cluster", "").strip().lower()
        if cluster in ("1", "true", "yes", "on"):
            timeline = await self._stitched_timeline(rid, timeline)
        if timeline is None:
            return _json_error(404, f"no recorded timeline for {rid!r}",
                               "not_found")
        # log<->trace correlation: the request's wide events render as
        # instant markers on the same clock as its spans (resume-suffixed
        # rids resolve through the same alias as the timeline lookup)
        internal = timeline.get("rid") or rid
        return web.json_response(
            export_trace(
                [timeline],
                tick_records=get_tick_recorder().snapshot()["records"],
                wide_events=get_event_ring().query(rid=internal),
            )
        )

    async def debug_trace_window(self, request: web.Request) -> web.Response:
        """Serving-window Perfetto dump: every timeline the recorder still
        retains whose request began in the last `last_s` seconds (default
        DNET_OBS_TRACE_WINDOW_S), plus the tick-record counter tracks."""
        from dnet_tpu.config import get_settings
        from dnet_tpu.obs import get_recorder
        from dnet_tpu.obs.trace import export_trace
        from dnet_tpu.sched.flight import get_tick_recorder

        last_raw = request.query.get("last_s", "").strip()
        try:
            last_s = (
                float(last_raw) if last_raw
                else get_settings().obs.trace_window_s
            )
        except ValueError:
            return _json_error(400, "last_s must be a number")
        recorder = get_recorder()
        timelines = [
            tl
            for rid in recorder.request_ids_since(time.time() - last_s)
            if (tl := recorder.timeline(rid)) is not None
        ]
        from dnet_tpu.obs.events import get_event_ring

        return web.json_response(
            export_trace(
                timelines,
                tick_records=get_tick_recorder().snapshot()["records"],
                wide_events=get_event_ring().query(last_s=last_s),
            )
        )
