"""Shard-node process wiring (reference: src/cli/shard.py:18-136).

Composes ShardRuntime + RingAdapter + gRPC + HTTP with ordered shutdown.
"""

from __future__ import annotations

import asyncio
import contextlib
import signal
import socket
from typing import Optional

from dnet_tpu.config import get_settings
from dnet_tpu.shard.adapter import RingAdapter
from dnet_tpu.shard.grpc_servicer import ShardRingServicer
from dnet_tpu.shard.http import ShardHTTPServer, ShardLoadModelRequest
from dnet_tpu.shard.runtime import ShardRuntime
from dnet_tpu.utils.logger import get_logger

log = get_logger()


class Shard:
    """Facade over runtime + adapter (reference: src/dnet/shard/shard.py)."""

    def __init__(self, shard_id: str, runtime: ShardRuntime, adapter: RingAdapter) -> None:
        self.shard_id = shard_id
        self.runtime = runtime
        self.adapter = adapter

    async def start(self) -> None:
        self.runtime.start(asyncio.get_running_loop())
        await self.adapter.start()

    async def stop(self) -> None:
        await self.adapter.shutdown()
        self.runtime.stop()

    async def load_model(self, req: ShardLoadModelRequest) -> None:
        from dnet_tpu.api.model_manager import resolve_model_dir

        model_dir = resolve_model_dir(
            req.model_path, get_settings().shard.models_dir
        )
        if model_dir is None:
            raise FileNotFoundError(f"model {req.model_path!r} not found on shard")
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None,
            lambda: self.runtime.load_model_core(
                str(model_dir),
                req.layers,
                max_seq=req.max_seq_len,
                param_dtype=req.param_dtype,
                wire_dtype=req.wire_dtype,
                wire_codec=req.wire_codec,
                window_size=req.window_size,
                residency_size=req.residency_size,
                kv_bits=req.kv_bits,
                weight_quant_bits=req.weight_quant_bits,
                # 0 = the shard's own deployment default (each host knows
                # its chip count better than the API node does); lanes
                # compose with either resolution (r5)
                mesh_tp=req.mesh_tp or get_settings().shard.mesh_tp,
                mesh_sp=req.mesh_sp or get_settings().shard.mesh_sp,
                # 0 = this shard's own DNET_TP default (ShardCompute
                # resolves); the solver's mesh-slice placement overrides
                tp_degree=req.tp_degree,
                spec_lookahead=req.spec_lookahead,
                lanes=req.lanes,
                prefix_cache=req.prefix_cache,
                epoch=req.epoch,
                # engine ignores it unless plan_policy chose a streaming
                # policy — no second copy of that decision here
                repack_dir=get_settings().shard.repack_dir,
            ),
        )
        next_addr = f"{req.next_node.host}:{req.next_node.grpc_port}" if req.next_node else ""
        self.adapter.configure_topology(next_addr)

    async def update_topology(self, req) -> None:
        """Delta reconfiguration (dnet_tpu/membership/): this shard's load
        parameters are unchanged in the new topology, so it keeps its
        weights and only (1) proves it actually holds what the API thinks
        it holds, (2) drops every per-request state (KV sessions, lanes,
        prefix snapshots, stream dedup keys), (3) pins the new epoch, and
        (4) rewires its next pointer.  Raises ValueError when the proof
        fails — the HTTP layer answers 409 and the API full-loads."""
        from dnet_tpu.api.model_manager import resolve_model_dir
        from dnet_tpu.resilience.chaos import inject_async

        # chaos point: a fault here is this shard unreachable for the
        # delta — the API's call_with_retry runs, and a persistent fault
        # ends in the full-reload fallback (the 409 path's twin)
        await inject_async("update_topology")
        compute = self.runtime.compute
        if compute is None:
            raise ValueError("no model loaded; cannot delta-update")
        model_dir = resolve_model_dir(
            req.model_path, get_settings().shard.models_dir
        )
        if model_dir is None or str(model_dir) != self.runtime.model_path:
            raise ValueError(
                f"loaded model {self.runtime.model_path!r} does not match "
                f"requested {req.model_path!r}"
            )
        if sorted(compute.layers) != sorted(req.layers):
            raise ValueError(
                f"loaded layers {sorted(compute.layers)} do not match "
                f"requested {sorted(req.layers)}"
            )
        # drop per-request state minted under the old epoch: stale lanes /
        # KV must not leak into the new ring, queued old-epoch frames must
        # not burn compute on results the fences will reject, and the old
        # next-hop streams (possibly pointed at a fenced-out shard) must
        # close
        await self.adapter.reset_topology()
        self.runtime.drain_ingress()
        compute.reset("")
        # pin the epoch off-loop: set_epoch takes _model_lock, and a
        # concurrent full reload holds that lock in an executor for the
        # whole multi-second weight read — acquiring it here on the loop
        # thread would stall every stream on this shard for the duration
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.runtime.set_epoch, req.epoch)
        next_addr = (
            f"{req.next_node.host}:{req.next_node.grpc_port}"
            if req.next_node
            else ""
        )
        self.adapter.configure_topology(next_addr)
        log.info(
            "shard %s delta-updated to epoch %d (next=%s, weights kept)",
            self.shard_id, req.epoch, next_addr or "<tail>",
        )

    async def unload_model(self) -> None:
        await self.adapter.reset_topology()
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.runtime.unload_model_core)


async def serve_async(args) -> None:
    s = get_settings()
    # runtime sanitizer (DNET_SAN=1): the shard is the hottest thread/loop
    # boundary (ShardRuntime's compute worker vs the event loop), so the
    # stall watchdog + task audit cover its whole serving lifetime too;
    # install() is a no-op (None) when dsan is off
    from dnet_tpu.analysis.runtime import serving as dsan_serving

    san = dsan_serving.install(asyncio.get_running_loop())
    # fail fast on a malformed DNET_CHAOS (and bannerize an armed one)
    # before any model state exists — never mid-request
    from dnet_tpu.resilience.chaos import validate_startup

    validate_startup(role="shard")
    # likewise refuse, now, a kernel override that must not reach the chip
    from dnet_tpu.ops.kernel_select import kernel_backend

    kernel_backend()
    shard_id = args.shard_name or f"shard-{socket.gethostname()}-{args.grpc_port}"
    runtime = ShardRuntime(shard_id, queue_size=args.queue_size)
    adapter = RingAdapter(
        runtime,
        stream_idle_s=s.transport.stream_idle_sweep_s,
        backoff_s=s.transport.stream_backoff_s,
    )
    shard = Shard(shard_id, runtime, adapter)

    from dnet_tpu.transport.grpc_transport import (
        ring_service_handlers,
        start_grpc_server,
    )

    await shard.start()
    grpc_server = await start_grpc_server(
        args.host, args.grpc_port, ring_service_handlers(ShardRingServicer(adapter, runtime))
    )
    http = ShardHTTPServer(shard)
    await http.start(args.host, args.http_port)

    discovery = None
    if getattr(args, "discovery", "none") == "udp":
        try:
            from dnet_tpu.utils.p2p import UdpDiscovery

            discovery = UdpDiscovery(
                shard_id, args.http_port, args.grpc_port,
                udp_port=getattr(args, "udp_port", 58899),
                target_addr=getattr(args, "udp_target", "255.255.255.255"),
                cluster=getattr(args, "cluster", "default"),
            )
            log.info("UDP discovery announcing as %s", shard_id)
        except Exception as exc:
            log.warning("UDP discovery unavailable (%s); hostfile mode only", exc)

    sweeper = asyncio.ensure_future(runtime.sweeper())

    tui = None
    tui_task = None
    if getattr(args, "tui", False):
        from dnet_tpu.tui import DnetTUI

        tui = DnetTUI(role="shard", title=shard_id)
        tui.start_background()

        async def _feed_tui() -> None:
            while True:
                compute = runtime.compute
                tui.update_status(
                    state="serving" if compute else "idle",
                    queue=runtime.queue_depth,
                )
                if compute is not None:
                    resident = (
                        compute.engine.weight_cache.resident_layers()
                        if compute.engine.weight_cache is not None
                        else list(compute.layers)
                    )
                    tui.update_model_info(runtime.model_path, list(compute.layers), resident)
                else:
                    tui.update_model_info(None, [])
                await asyncio.sleep(1.0)

        tui_task = asyncio.ensure_future(_feed_tui())

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    log.info("dnet-shard %s ready (grpc %d, http %d)", shard_id, args.grpc_port, args.http_port)
    await stop.wait()

    log.info("shard shutting down")
    # cancel AND await the periodic tasks (the runtime twin of DL003): a
    # dropped cancellation leaves them to die unobserved at loop close —
    # and a DS005 finding under DNET_SAN=1
    if tui_task is not None:
        tui_task.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await tui_task
    if tui is not None:
        tui.stop()
    if discovery is not None:
        discovery.stop()
    sweeper.cancel()
    with contextlib.suppress(asyncio.CancelledError):
        await sweeper
    await http.stop()
    await grpc_server.stop(grace=2)
    await shard.stop()
    if san is not None:
        san.teardown(log)


def serve(args) -> None:
    asyncio.run(serve_async(args))
