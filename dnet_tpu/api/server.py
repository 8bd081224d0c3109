"""API-node process wiring: managers + HTTP (+ gRPC in ring mode).

Reference: src/cli/api.py:42-166.
"""

from __future__ import annotations

import asyncio
import signal

from dnet_tpu.api.http import ApiHTTPServer
from dnet_tpu.api.inference import InferenceManager
from dnet_tpu.api.model_manager import LocalModelManager
from dnet_tpu.config import get_settings
from dnet_tpu.parallel.mesh import parse_mesh as _parse_mesh
from dnet_tpu.utils.logger import get_logger

log = get_logger()


async def serve_async(args) -> None:
    s = get_settings()
    # runtime sanitizer (DNET_SAN=1): loop-stall watchdog + task audit
    # over the whole serving lifetime; install() is a no-op (None) when
    # dsan is off
    from dnet_tpu.analysis.runtime import serving as dsan_serving

    san = dsan_serving.install(asyncio.get_running_loop())
    # fail fast on a malformed DNET_CHAOS (and bannerize an armed one)
    # before the server takes traffic — never mid-request
    from dnet_tpu.resilience.chaos import validate_startup

    validate_startup(role="api")
    wq = getattr(args, "weight_quant_bits", None)
    weight_quant_bits = s.api.weight_quant_bits if wq is None else wq
    batch_slots = getattr(args, "batch_slots", None) or s.api.batch_slots
    # with continuous batching, admission must not exceed the slot pool —
    # an over-admitted request would hard-fail on prefill instead of queueing
    max_concurrent = (
        min(s.api.max_concurrent_requests, batch_slots)
        if batch_slots > 1
        else s.api.max_concurrent_requests
    )
    inference = InferenceManager(
        adapter=None,
        request_timeout_s=s.api.request_timeout_s,
        max_concurrent=max_concurrent,
    )
    # Multi-process meshes are multi-CONTROLLER: every process must dispatch
    # the same programs in lockstep, which a request-driven HTTP server
    # cannot guarantee (a request arriving at one host would dispatch a
    # collective the others never enter).  Request-driven multi-host serving
    # is the gRPC shard ring (one dnet-shard per host); the distributed
    # join is for SPMD batch/offline execution (parallel/mesh.py).
    if s.mesh.num_processes > 1:
        raise SystemExit(
            "DNET_MESH_NUM_PROCESSES>1 with the HTTP API server would "
            "deadlock on the first request (multi-controller mesh, single "
            "dispatching host). Serve multi-host via the gRPC ring: run "
            "dnet-shard on every host and dnet-api with --hostfile/UDP "
            "discovery."
        )
    from dnet_tpu.parallel.mesh import ensure_distributed

    if ensure_distributed(s.mesh.coordinator, s.mesh.num_processes, s.mesh.process_id):
        log.info(
            "joined single-process distributed runtime (coordinator %s)",
            s.mesh.coordinator,
        )
    env_mesh = {"pp": s.mesh.pp, "tp": s.mesh.tp, "dp": s.mesh.dp, "sp": s.mesh.sp}
    env_mesh_active = s.mesh.pp > 0 or s.mesh.tp > 1 or s.mesh.dp > 1 or s.mesh.sp > 1
    mesh = _parse_mesh(getattr(args, "mesh", "")) or (
        env_mesh if env_mesh_active else None
    )
    model_manager = LocalModelManager(
        inference,
        models_dir=getattr(args, "models_dir", "") or s.api.models_dir,
        max_seq=s.api.max_seq_len,
        param_dtype=s.api.param_dtype,
        mesh=mesh,
        weight_quant_bits=weight_quant_bits,
        weight_quant_group=s.api.weight_quant_group,
        kv_bits=s.kv.bits,
        batch_slots=batch_slots,
        prefix_cache=s.api.prefix_cache,
        spec_lookahead=s.api.spec_lookahead,
    )

    cluster_manager = None
    grpc_server = None
    ring_discovery = None
    if getattr(args, "discovery", "none") == "udp" and not getattr(args, "hostfile", ""):
        from dnet_tpu.utils.p2p import UdpDiscovery

        ring_discovery = UdpDiscovery(
            "api", args.http_port, args.grpc_port, is_manager=True,
            udp_port=getattr(args, "udp_port", 58899),
            target_addr=getattr(args, "udp_target", "255.255.255.255"),
            cluster=getattr(args, "cluster", "default"),
        )
        log.info("UDP discovery active (manager)")
    if getattr(args, "hostfile", "") or ring_discovery is not None:
        from dnet_tpu.api.cluster import ClusterManager
        from dnet_tpu.api.ring import ApiTokenServicer
        from dnet_tpu.api.ring_manager import RingModelManager
        from dnet_tpu.transport.grpc_transport import (
            api_service_handlers,
            start_grpc_server,
        )
        from dnet_tpu.utils.hostfile import StaticDiscovery

        discovery = (
            ring_discovery
            if ring_discovery is not None
            else StaticDiscovery.from_hostfile(args.hostfile)
        )
        cluster_manager = ClusterManager(discovery)
        # callback address shards dial for SendToken: explicit override, else
        # the interface facing the shards (reference http_api.py:188-196); a
        # server bound to loopback can only be dialled there, whatever
        # discovery has heard so far (UDP discovery may know no peer yet)
        from dnet_tpu.utils.network import LOOPBACK_HOSTS, primary_ip

        callback_host = (
            args.host if args.host in LOOPBACK_HOSTS
            else primary_ip(d.host for d in discovery.peers())
        )
        callback_addr = s.api.callback_addr or f"{callback_host}:{args.grpc_port}"
        model_manager = RingModelManager(
            inference,
            cluster_manager,
            models_dir=getattr(args, "models_dir", "") or s.api.models_dir,
            api_callback_addr=callback_addr,
            max_seq=s.api.max_seq_len,
            param_dtype=s.api.param_dtype,
            weight_quant_bits=weight_quant_bits,
        )
        # token-callback receiver: shards resolve decode futures through here
        grpc_server = await start_grpc_server(
            args.host,
            args.grpc_port,
            api_service_handlers(
                ApiTokenServicer(
                    lambda r: inference.adapter.resolve_token(r)
                    if inference.adapter is not None
                    else log.warning("token for %s before model load", r.nonce)
                )
            ),
        )
        log.info(
            "ring mode: %d shard(s) via %s",
            len(discovery.peers()),
            "udp discovery" if ring_discovery is not None else "hostfile",
        )
        # failure detection + optional elastic recovery (the reference only
        # detects — SURVEY.md §5 flags the missing recovery as a gap)
        from dnet_tpu.api.failure import RingFailureMonitor

        monitor = RingFailureMonitor(
            cluster_manager,
            inference,
            model_manager=model_manager,
            interval_s=s.api.health_interval_s,
            fail_threshold=s.api.health_fail_threshold,
            auto_recover=getattr(args, "auto_recover", False),
        )
        inference.failure_monitor = monitor
        monitor.start()

    fleet = None
    if s.fleet.fleet > 1:
        # DNET_FLEET=N: the front door routes across N replicas.  The
        # stack built above becomes replica r0; additional replicas are
        # attached programmatically (the in-process ring harness /
        # bench_serve --fleet is the supported multi-replica deployment —
        # one OS process per extra ring is future work).  Unset/1 never
        # constructs the fleet layer: the single-ring path is untouched.
        from dnet_tpu.fleet import FleetManager

        fleet = FleetManager()
        fleet.add_replica("r0", inference)
        log.info(
            "fleet mode: DNET_FLEET=%d, primary registered as r0 "
            "(attach more replicas via FleetManager.add_replica)",
            s.fleet.fleet,
        )
    if cluster_manager is None:
        # local mode computes in this process: refuse a kernel override that
        # must not reach the chip now, not at the first traced request
        from dnet_tpu.ops.kernel_select import kernel_backend

        kernel_backend()
    http = ApiHTTPServer(inference, model_manager, cluster_manager, fleet=fleet)
    await http.start(args.host, args.http_port)

    preload = getattr(args, "model", "") or ""
    if preload:
        try:
            await model_manager.load_model(preload)
        except Exception:
            if cluster_manager is None:
                # local mode: this process IS the engine, and the operator
                # asked for this model — a server that stays up without it
                # (and would later exit 0) hides the failure
                log.exception("preload of %s failed", preload)
                await http.stop()
                raise SystemExit(1)
            # ring mode has no topology until the operator prepares one; a
            # failed preload must not kill the server
            log.exception("preload of %s failed; continuing without a model", preload)

    tui = None
    tui_task = None
    if getattr(args, "tui", False):
        from dnet_tpu.tui import DnetTUI

        tui = DnetTUI(role="api")
        tui.start_background()

        async def _feed_tui() -> None:
            while True:
                topo = getattr(cluster_manager, "current_topology", None)
                tui.update_status(
                    state="ready" if inference.ready else "no model",
                    mode="ring" if cluster_manager else ("mesh" if mesh else "local"),
                    shards=len(topo.assignments) if topo else 0,
                )
                if topo is not None:
                    layers = [l for a in topo.assignments for l in a.layers]
                else:
                    engine = getattr(model_manager, "engine", None)
                    layers = list(engine.model.layers) if engine is not None else []
                tui.update_model_info(inference.model_id, sorted(layers))
                await asyncio.sleep(1.0)

        tui_task = asyncio.ensure_future(_feed_tui())

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    log.info("dnet-api ready")
    await stop.wait()
    # graceful drain (SIGTERM/SIGINT): flip admission into drain mode —
    # /health reports "draining", new decode requests get 503 +
    # Retry-After, queued waiters shed — while the HTTP server stays up
    # so in-flight streams can finish, bounded by DNET_DRAIN_DEADLINE_S.
    # Only then do adapters/transports tear down.
    drain_s = s.admission.drain_deadline_s
    log.info(
        "shutdown signal: draining %d in-flight request(s) (bounded %.1fs)",
        inference.admission.active, drain_s,
    )
    inference.admission.begin_drain()
    if await inference.admission.wait_drained(drain_s):
        log.info("drain complete; shutting down")
    else:
        log.warning("drain deadline hit; shutting down with work in flight")
    if inference.failure_monitor is not None:
        await inference.failure_monitor.stop()
    if tui_task is not None:
        tui_task.cancel()
    if tui is not None:
        tui.stop()
    if ring_discovery is not None:
        ring_discovery.stop()
    await http.stop()
    if grpc_server is not None:
        await grpc_server.stop(grace=2)
    if inference.adapter is not None:
        await inference.adapter.shutdown()
    if san is not None:
        san.teardown(log)




def serve(args) -> None:
    asyncio.run(serve_async(args))
