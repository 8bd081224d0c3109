"""Chaos-campaign scenarios: real serving stacks behind a loopback port.

Each scenario builds one REAL serving topology in-process — the same
stacks the subsystem tests pin — and exposes
the uniform surface the campaign runner (campaign.py) drives cells
through: a loopback HTTP base URL for the seeded workload, a resource
snapshot for the conservation audit, a quiesce barrier, and (where the
scenario has moving parts) a scripted `storm()` of membership/fleet
events the injected faults perturb.

Scenarios:

- ``local``       single-node stack behind admission + SSE: the scheduler
                  over the paged pool, as every local load is served
- ``sched``       the same stack under its old name (the campaign's
                  matrix and its golden file count it; ROADMAP D2)
- ``ring``        two-shard in-process ring (loadgen/ring_harness.py),
                  resume armed — the transport/compute fault surface
- ``ring_wire``   the same ring under DNET_WIRE_PIPELINE=1 (overlapped
                  encode/decode seams live)
- ``member``      three-shard ring + ClusterManager + RingModelManager +
                  RingFailureMonitor (HTTP fan-out served in-process):
                  loss -> epoch-fenced recovery (delta reconfig) ->
                  resume -> rejoin, per cell
- ``member_auto`` the same with decode-grant batching
                  (DNET_API_RING_AUTO_STEPS=8)
- ``fleet``       two single-node replicas behind FleetManager
- ``fleet_sched`` the same stack under its old name (as ``sched``)
- ``fleet_ring``  two in-process RINGS behind FleetManager — the composed
                  acceptance cell (replica dies mid-stream on top of
                  in-ring resume) runs here

No scenario opens a real network socket beyond the loopback HTTP port;
no pytest machinery is involved, so ``make chaos`` runs the identical
stacks CI's tier-1 smoke does.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import socket
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from dnet_tpu.utils.logger import get_logger

log = get_logger()

# Deep admission queue: campaign cells must queue (and surface chaos as
# the injected fault's OWN failure mode), not shed on burst arrival — a
# shed would alias every cell's outcome to 429.
_BASE_ENV = {
    "DNET_ADMIT_QUEUE_DEPTH": "64",
    "DNET_ADMIT_QUEUE_TIMEOUT_S": "30",
}

# Resume armed with fast retries: the ring scenarios recover from
# injected transport/compute faults within a cell's request budget
# (mirrors tests/subsystems/test_ring_membership.py's _ENV).
_RESUME_ENV = {
    "DNET_RESILIENCE_RESUME": "1",
    "DNET_RESILIENCE_RESUME_DEADLINE_S": "30",
    "DNET_RESILIENCE_MAX_RESUMES": "200",
    "DNET_RESILIENCE_RETRY_BASE_S": "0.001",
    "DNET_RESILIENCE_RETRY_MAX_S": "0.01",
    "DNET_API_RING_AUTO_STEPS": "0",
}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _wait(cond, timeout_s: float, what: str) -> None:
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout_s:
            raise TimeoutError(f"timed out waiting for {what}")
        await asyncio.sleep(0.02)


class _EnvScope:
    """Set env overrides + fresh settings/obs books for one scenario;
    restore the previous environment on exit."""

    def __init__(self, env: Dict[str, str]) -> None:
        self.env = dict(env)
        self._saved: Dict[str, Optional[str]] = {}

    def enter(self) -> None:
        from dnet_tpu.config import reset_settings_cache
        from dnet_tpu.obs import reset_obs

        for k, v in self.env.items():
            self._saved[k] = os.environ.get(k)
            os.environ[k] = v
        reset_settings_cache()
        reset_obs()

    def exit(self) -> None:
        from dnet_tpu.config import reset_settings_cache

        for k, old in self._saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        self._saved.clear()
        reset_settings_cache()


@dataclass
class ResourceSnapshot:
    """Post-quiesce books for the conservation audit (invariants.py
    family 2).  Every entry is (observed, expected-at-rest)."""

    pools: Dict[str, Tuple[int, int, int]] = field(default_factory=dict)
    # name -> (used, free, total); at rest used==0 and free==total
    admission: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    # name -> (active, queued); at rest (0, 0)
    lanes: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    # name -> (free, slots); at rest free==slots
    streams: Dict[str, int] = field(default_factory=dict)
    # name -> open per-nonce stream contexts; at rest 0


def _pool_entry(snap: ResourceSnapshot, name: str, engine) -> None:
    pool = getattr(engine, "kv_pool", None)
    if pool is not None:
        snap.pools[name] = (pool.used, pool.free, pool.total)


def _lane_entry(snap: ResourceSnapshot, name: str, compute) -> None:
    lp = getattr(compute, "lane_pool", None)
    if lp is not None:
        snap.lanes[name] = (len(lp._free), lp.slots)


def _stream_entry(snap: ResourceSnapshot, name: str, holder) -> None:
    sm = getattr(holder, "_streams", None)
    if sm is not None:
        snap.streams[name] = len(getattr(sm, "_streams", {}))


class Scenario:
    """One serving stack the campaign drives cells through."""

    name = ""
    parity = "bytes"  # bytes | content — how golden comparison is judged
    #: injection points this scenario meaningfully exercises
    points: Tuple[str, ...] = ()
    #: per-request client budget: the server must answer inside this or
    #: the cell records status 0 (a status-contract violation)
    client_timeout_s = 60.0

    def __init__(self, model_dir: str) -> None:
        self.model_dir = str(model_dir)
        self.base_url = ""
        self._scope: Optional[_EnvScope] = None
        self._session = None

    # -- lifecycle ------------------------------------------------------
    def extra_env(self) -> Dict[str, str]:
        return {}

    async def start(self) -> None:
        self._scope = _EnvScope({**_BASE_ENV, **self.extra_env()})
        self._scope.enter()
        try:
            await self._build()
        except BaseException:
            self._scope.exit()
            raise
        import aiohttp

        self._session = aiohttp.ClientSession(
            base_url=self.base_url,
            timeout=aiohttp.ClientTimeout(total=None),
        )

    async def stop(self) -> None:
        if self._session is not None:
            await self._session.close()
            self._session = None
        try:
            await self._teardown()
        finally:
            if self._scope is not None:
                self._scope.exit()
                self._scope = None

    async def _build(self) -> None:
        raise NotImplementedError

    async def _teardown(self) -> None:
        raise NotImplementedError

    # -- request surface ------------------------------------------------
    @property
    def model(self) -> str:
        return self.model_dir

    async def post_chat(
        self, body: dict, timeout_s: float = 60.0
    ) -> Tuple[int, Dict[str, str], bytes]:
        """One streaming chat request; returns (status, headers, raw SSE
        bytes).  Transport failures surface as status 0 (a violation:
        the server must answer, even under chaos)."""
        async def _go():
            async with self._session.post(
                "/v1/chat/completions", json=body
            ) as resp:
                raw = await resp.read()
                return resp.status, dict(resp.headers), raw

        try:
            return await asyncio.wait_for(_go(), timeout_s)
        except asyncio.TimeoutError:
            return 0, {}, b"client timeout"
        except Exception as exc:
            return 0, {}, f"transport failure: {exc}".encode()

    # -- campaign hooks -------------------------------------------------
    async def storm(self) -> None:
        """Scripted mid-cell event arc (membership/fleet scenarios);
        no-op for static stacks."""
        return None

    async def quiesce(self, timeout_s: float = 10.0) -> None:
        """Barrier: in-flight work drained (admission idle)."""
        for name, inference in self._inferences():
            adm = inference.admission
            # dnetlint: disable=DL024 a handful of admission books; the wait is one shared wall-clock, not N round trips
            await _wait(
                lambda a=adm: a.active == 0 and a.queued == 0,
                timeout_s, f"{name} admission idle",
            )

    async def heal(self, timeout_s: float = 10.0) -> bool:
        """Post-cell repair: True when the stack is ready for the next
        cell; False tells the campaign to rebuild the scenario."""
        return True

    def _inferences(self):
        """[(name, InferenceManager)] — every admission book in play."""
        raise NotImplementedError

    def resources(self) -> ResourceSnapshot:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# local / sched: the single-node stack
# ---------------------------------------------------------------------------


class LocalScenario(Scenario):
    name = "local"
    parity = "bytes"
    points = ("admit",)

    batch_slots = 2

    async def _build(self) -> None:
        from dnet_tpu.api.http import ApiHTTPServer
        from dnet_tpu.api.inference import InferenceManager
        from dnet_tpu.api.model_manager import LocalModelManager
        from dnet_tpu.config import get_settings

        api = get_settings().api
        self.inference = InferenceManager(
            adapter=None,
            request_timeout_s=api.request_timeout_s,
            max_concurrent=min(
                api.max_concurrent_requests, self.batch_slots
            ),
        )
        self.manager = LocalModelManager(
            self.inference,
            models_dir=api.models_dir,
            max_seq=64,
            param_dtype="float32",
            batch_slots=self.batch_slots,
        )
        await self.manager.load_model(self.model_dir, max_seq=64)
        self.server = ApiHTTPServer(self.inference, self.manager)
        port = _free_port()
        await self.server.start("127.0.0.1", port)
        self.base_url = f"http://127.0.0.1:{port}"

    async def _teardown(self) -> None:
        await self.server.stop()
        await self.manager.unload_model()

    def _inferences(self):
        return [("api", self.inference)]

    def resources(self) -> ResourceSnapshot:
        snap = ResourceSnapshot()
        adm = self.inference.admission
        snap.admission["api"] = (adm.active, adm.queued)
        _pool_entry(snap, "engine", getattr(self.manager, "engine", None))
        return snap


class SchedScenario(LocalScenario):
    name = "sched"


# ---------------------------------------------------------------------------
# ring / ring_wire: the two-shard in-process ring (loadgen/ring_harness.py)
# ---------------------------------------------------------------------------


class RingScenario(Scenario):
    name = "ring"
    parity = "bytes"
    points = (
        "send_activation", "token_cb", "shard_compute", "zombie_frame",
        "wire_encode", "wire_decode", "admit",
    )

    wire_pipeline = False

    def extra_env(self) -> Dict[str, str]:
        env = dict(_RESUME_ENV)
        if self.wire_pipeline:
            env["DNET_WIRE_PIPELINE"] = "1"
        return env

    async def _build(self) -> None:
        import json as _json
        from pathlib import Path

        from dnet_tpu.loadgen.ring_harness import InprocRing

        cfg = _json.loads(
            (Path(self.model_dir) / "config.json").read_text()
        )
        n_layers = int(cfg["num_hidden_layers"])
        half = max(n_layers // 2, 1)
        self.ring = InprocRing(
            self.model_dir,
            layers0=range(0, half),
            layers1=range(half, n_layers),
            max_seq=64,
            auto_steps=0,  # per-step frames: the fault surface is widest
            # a token the chaos ate outright (fenced frame, exhausted
            # callback retries) only reaches the resume machinery when
            # await_token times out — keep that bound tight so recovery
            # lands well inside the cell's client budget
            request_timeout_s=6.0,
        )
        await self.ring.start()
        port = _free_port()
        await self.ring.server.start("127.0.0.1", port)
        self.base_url = f"http://127.0.0.1:{port}"

    async def _teardown(self) -> None:
        await self.ring.server.stop()
        await self.ring.stop()

    @property
    def model(self) -> str:
        return "inproc-ring"

    def _inferences(self):
        return [("api", self.ring.inference)]

    async def heal(self, timeout_s: float = 20.0) -> bool:
        # a request the chaos wedged past every server-side timeout means
        # the stack cannot be trusted for the next cell: report unhealed
        # so the campaign rebuilds instead of letting the stuck admission
        # slot cascade violations forward
        try:
            await self.quiesce(timeout_s)
        except TimeoutError:
            return False
        return True

    def resources(self) -> ResourceSnapshot:
        snap = ResourceSnapshot()
        adm = self.ring.inference.admission
        snap.admission["api"] = (adm.active, adm.queued)
        for rt_name, rt, adapter in (
            ("s0", self.ring.s0, self.ring.a0),
            ("s1", self.ring.s1, self.ring.a1),
        ):
            if rt.compute is not None:
                _pool_entry(snap, rt_name, rt.compute.engine)
                _lane_entry(snap, rt_name, rt.compute)
            _stream_entry(snap, rt_name, adapter)
        _stream_entry(snap, "api", self.ring.api)
        return snap


class RingWireScenario(RingScenario):
    name = "ring_wire"
    wire_pipeline = True


# ---------------------------------------------------------------------------
# member / member_auto: the elastic-membership ring
# (port of tests/subsystems/test_ring_membership.py's harness)
# ---------------------------------------------------------------------------


class _MemberStreamCall:
    """grpc aio stream-stream stand-in: write() delivers into the target
    shard's ingress, the returned ACK queues for the reader."""

    def __init__(self, deliver) -> None:
        self._deliver = deliver
        self.acks: asyncio.Queue = asyncio.Queue()

    async def write(self, frame) -> None:
        ack = await self._deliver(frame)
        if ack is not None:
            await self.acks.put(ack)

    async def read(self):
        return await self.acks.get()

    async def done_writing(self) -> None:
        return None


class _MemberRingClient:
    """RingClient stand-in addressed by grpc addr; frames land on the
    addressed shard's adapter in-process."""

    def __init__(self, addr: str, deliver, reset=None) -> None:
        self.addr = addr
        self._deliver = deliver
        self._reset = reset

    def open_stream(self) -> _MemberStreamCall:
        return _MemberStreamCall(lambda f: self._deliver(self.addr, f))

    async def send_activation(self, frame, timeout=10.0):
        return await self._deliver(self.addr, frame)

    async def health_check(self, timeout=5.0):
        from dnet_tpu.transport.protocol import HealthInfo

        return HealthInfo(ok=True)

    async def reset_cache(self, nonce="", timeout=10.0, epoch=0):
        from dnet_tpu.transport.protocol import Empty

        # the API fans per-nonce resets over every shard client after a
        # request ends; without forwarding them the member shards leak a
        # stream context per request — exactly what conservation audits
        if self._reset is not None:
            await self._reset(self.addr, nonce)
        return Empty()

    async def measure_latency(self, probe, timeout=30.0):
        return probe

    async def close(self):
        return None


class _MemberProbeClient(_MemberRingClient):
    """The failure monitor's probe client: fails while its addr is in
    the scenario's dead set (a FlakyClient without the test import)."""

    def __init__(self, addr: str, dead: set) -> None:
        super().__init__(addr, deliver=None)
        self._dead = dead

    async def health_check(self, timeout=5.0):
        if self.addr in self._dead:
            raise ConnectionError(f"{self.addr} unreachable")
        return await super().health_check(timeout)


class _MemberCallbackClient:
    """ApiCallbackClient stand-in: token payloads land in the sink the
    pump task drains into the API adapter."""

    def __init__(self, addr: str, sink: list) -> None:
        self.addr = addr
        self._sink = sink

    async def send_token(self, payload, timeout=3.0):
        from dnet_tpu.transport.protocol import Empty

        self._sink.append(payload)
        return Empty()

    async def close(self):
        return None


class _MemberShards:
    """Three real shard runtimes + adapters behind the faked HTTP control
    plane the ring manager fans out over."""

    def __init__(self, model_dir: str, sink: list) -> None:
        from dnet_tpu.shard.adapter import RingAdapter
        from dnet_tpu.shard.runtime import ShardRuntime

        self.model_dir = str(model_dir)
        self.sink = sink
        self.loads: Dict[str, int] = {}
        self.updates: Dict[str, int] = {}
        self.shards: Dict[str, tuple] = {}
        for i in range(3):
            inst = f"s{i}"
            rt = ShardRuntime(inst)
            adapter = RingAdapter(
                rt,
                ring_client_factory=self.ring_factory,
                callback_client_factory=lambda addr: _MemberCallbackClient(
                    addr, self.sink
                ),
            )
            self.shards[inst] = (rt, adapter)
        self.by_grpc = {f"h{i}:{10 * (i + 1)}": f"s{i}" for i in range(3)}
        self.by_http = {f"h{i}:{i + 1}": f"s{i}" for i in range(3)}

    def ring_factory(self, addr: str) -> _MemberRingClient:
        return _MemberRingClient(addr, self.ingress_ack, self.reset)

    async def reset(self, addr: str, nonce: str) -> None:
        rt, adapter = self.shards[self.by_grpc[addr]]
        await adapter.reset_cache(nonce)

    async def ingress_ack(self, addr: str, frame):
        from dnet_tpu.transport.protocol import StreamAck

        rt, adapter = self.shards[self.by_grpc[addr]]
        ok, msg = await adapter.ingress_frame(frame)
        return StreamAck(
            nonce=frame.nonce, seq=frame.seq, ok=ok, message=msg
        )

    def devices(self) -> list:
        from dnet_tpu.core.types import DeviceInfo

        return [
            DeviceInfo(
                instance=f"s{i}", host=f"h{i}", http_port=i + 1,
                grpc_port=10 * (i + 1), flops_bf16=1e14, hbm_bw=8e11,
                host_to_hbm_bw=1e10, hbm_bytes=16 << 30,
            )
            for i in range(3)
        ]

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        for rt, adapter in self.shards.values():
            rt.start(loop)
            await adapter.start()  # dnetlint: disable=DL024 three in-process adapters at build time; startup order is part of the harness contract

    async def stop(self) -> None:
        for rt, adapter in self.shards.values():
            await adapter.shutdown()  # dnetlint: disable=DL024 teardown must be ordered (adapter before runtime) per shard
            rt.stop()
        for rt, _adapter in self.shards.values():
            if rt.compute is not None:
                rt.compute.engine.close()
                rt.compute = None

    async def handle_post(self, url: str, body: dict):
        """(status, body) for one ring-manager fan-out POST — the
        in-process twin of shard/http.py's control routes, chaos points
        included."""
        from dnet_tpu.resilience import chaos

        hostport, _, path = url.removeprefix("http://").partition("/")
        inst = self.by_http[hostport]
        rt, adapter = self.shards[inst]
        nxt = body.get("next_node") or {}
        next_addr = f"{nxt['host']}:{nxt['grpc_port']}" if nxt else ""
        if path == "load_model":
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                None,
                lambda: rt.load_model_core(
                    self.model_dir, body["layers"],
                    max_seq=body["max_seq_len"],
                    param_dtype=body["param_dtype"],
                    epoch=body["epoch"],
                ),
            )
            adapter.configure_topology(next_addr)
            self.loads[inst] = self.loads.get(inst, 0) + 1
            return 200, {"status": "ok"}
        if path == "update_topology":
            # same chaos point the real Shard.update_topology traverses:
            # an injected fault is this shard unreachable for the delta —
            # non-200 sends the manager down the full-load fallback
            try:
                await chaos.inject_async("update_topology")
            except chaos.ChaosError as exc:
                return 503, {"status": "error", "message": str(exc)}
            if rt.compute is None or sorted(rt.compute.layers) != sorted(
                body["layers"]
            ):
                return 409, {"status": "error", "message": "cannot prove"}
            await adapter.reset_topology()
            rt.drain_ingress()
            rt.compute.reset("")
            rt.set_epoch(body["epoch"])
            adapter.configure_topology(next_addr)
            self.updates[inst] = self.updates.get(inst, 0) + 1
            return 200, {"status": "ok", "epoch": rt.epoch}
        if path == "unload_model":
            return 200, {"status": "ok"}
        return 404, {"status": "error", "message": f"unexpected {url}"}


class _MemberHttpx:
    """Stands in for the httpx module inside api.ring_manager."""

    class HTTPError(Exception):
        pass

    class _Resp:
        def __init__(self, status_code: int, body: dict) -> None:
            import json as _json

            self.status_code = status_code
            self._body = body
            self.text = _json.dumps(body)

        def json(self):
            return self._body

    def __init__(self, cluster: _MemberShards) -> None:
        outer = self

        class AsyncClient:
            def __init__(self, timeout=None) -> None:
                pass

            async def __aenter__(self):
                return self

            async def __aexit__(self, *exc):
                return False

            async def post(self, url, json=None):
                status, body = await cluster.handle_post(url, json)
                return outer._Resp(status, body)

        self.AsyncClient = AsyncClient


def _member_solve(model_id: str, n_layers: int):
    """Deterministic mini-solver: contiguous layer runs over whichever
    shards are alive, front-loaded so s0's range is STABLE across 3<->2
    shard shapes (s0 always delta-reconfigs, the tail shard full-loads)."""

    def solve(devices, profile=None, **kw):
        from dnet_tpu.api.ring_manager import build_manual_topology

        insts = sorted({d.instance for d in devices})
        if not insts:
            raise ValueError("no devices to solve over")
        n = len(insts)
        base, extra = divmod(n_layers, n)
        sizes = [base + (1 if i < extra else 0) for i in range(n)]
        assignments, at = [], 0
        for inst, size in zip(insts, sizes):
            assignments.append(
                {"instance": inst, "layers": list(range(at, at + size))}
            )
            at += size
        return build_manual_topology(model_id, n_layers, assignments, devices)

    return solve


class MemberScenario(Scenario):
    name = "member"
    parity = "content"
    # storms re-solve topology and reload shard engines mid-cell; a
    # request that lands inside a recovery window legitimately waits for
    # it, so the member budget is wider than the static stacks'
    client_timeout_s = 120.0
    points = (
        "health_check", "rejoin", "update_topology", "shard_compute",
        "token_cb", "admit",
    )

    auto_steps = 0
    n_layers = 4

    def extra_env(self) -> Dict[str, str]:
        env = dict(_RESUME_ENV)
        env["DNET_API_RING_AUTO_STEPS"] = str(self.auto_steps)
        return env

    async def _build(self) -> None:
        from dnet_tpu.api.cluster import ClusterManager
        from dnet_tpu.api.failure import RingFailureMonitor
        from dnet_tpu.api.http import ApiHTTPServer
        from dnet_tpu.api.inference import InferenceManager
        from dnet_tpu.api import ring_manager as rm_mod
        from dnet_tpu.api.ring_manager import RingModelManager
        from dnet_tpu.parallel import solver as solver_mod

        self._dead: set = set()
        self.sink: list = []
        self.shards = _MemberShards(self.model_dir, self.sink)
        # seam swaps (restored in _teardown): the manager's HTTP fan-out
        # and the re-solver
        self._real_httpx = rm_mod.httpx
        rm_mod.httpx = _MemberHttpx(self.shards)
        self._real_solve = solver_mod.solve_topology
        solver_mod.solve_topology = _member_solve(
            self.model_dir, self.n_layers
        )
        await self.shards.start()
        self.cluster = ClusterManager(discovery=None)

        async def profiled():
            return self.shards.devices()

        self.cluster.profile_cluster = profiled
        self.inference = InferenceManager(
            adapter=None, request_timeout_s=30.0, max_concurrent=8
        )
        self.manager = RingModelManager(
            self.inference,
            self.cluster,
            api_callback_addr="api:1",
            max_seq=64,
            param_dtype="float32",
            ring_client_factory=self.shards.ring_factory,
        )
        topo = solver_mod.solve_topology(self.shards.devices(), None)
        self.cluster.install_topology(topo)
        await self.manager.load_model(self.model_dir)
        self._stop_pump = asyncio.Event()
        self._pump_task = asyncio.ensure_future(self._pump())
        self.monitor = RingFailureMonitor(
            self.cluster,
            self.inference,
            model_manager=self.manager,
            interval_s=0.02,
            fail_threshold=2,
            timeout_s=0.5,
            auto_recover=True,
            ring_client_factory=lambda addr: _MemberProbeClient(
                addr, self._dead
            ),
            rejoin=True,
            rejoin_stable_s=0.1,
        )
        self.inference.failure_monitor = self.monitor
        self.monitor.start()
        self.server = ApiHTTPServer(
            self.inference, self.manager, cluster_manager=self.cluster
        )
        port = _free_port()
        await self.server.start("127.0.0.1", port)
        self.base_url = f"http://127.0.0.1:{port}"

    async def _pump(self) -> None:
        seen = 0
        while not self._stop_pump.is_set():
            while seen < len(self.sink):
                payload = self.sink[seen]
                seen += 1
                if self.inference.adapter is not None:
                    self.inference.adapter.resolve_token(payload.to_result())
            await asyncio.sleep(0.005)

    async def _teardown(self) -> None:
        from dnet_tpu.api import ring_manager as rm_mod
        from dnet_tpu.parallel import solver as solver_mod

        with contextlib.suppress(Exception):
            await self.monitor.stop()
        self._stop_pump.set()
        with contextlib.suppress(asyncio.CancelledError):
            self._pump_task.cancel()
            await asyncio.gather(self._pump_task, return_exceptions=True)
        with contextlib.suppress(Exception):
            await self.server.stop()
        if self.inference.adapter is not None:
            with contextlib.suppress(Exception):
                await self.inference.adapter.shutdown()
        await self.shards.stop()
        rm_mod.httpx = self._real_httpx
        solver_mod.solve_topology = self._real_solve

    @property
    def model(self) -> str:
        return self.model_dir

    async def storm(self) -> None:
        """One loss -> recover -> rejoin arc: s2 drops off the ring, the
        monitor re-solves without it (delta reconfig for the stable-range
        shards), then s2 probes green and rejoins at the next epoch.
        Under chaos, any leg of the arc may stall — that is tolerated
        here (degradation is allowed; 5xx and leaks are not) and repaired
        by heal() after the cell's faults clear."""
        e0 = self.cluster.epoch
        self._dead.add("h2:30")
        with contextlib.suppress(TimeoutError):
            await _wait(
                lambda: self.cluster.epoch > e0, 8.0, "loss re-solve"
            )
        e1 = self.cluster.epoch
        self._dead.discard("h2:30")
        with contextlib.suppress(TimeoutError):
            await _wait(
                lambda: self.cluster.epoch > e1, 8.0, "rejoin re-solve"
            )

    async def heal(self, timeout_s: float = 15.0) -> bool:
        self._dead.clear()
        try:
            await _wait(
                lambda: not self.monitor.degraded, timeout_s,
                "monitor green",
            )
            await self.quiesce(timeout_s)
        except TimeoutError:
            return False
        return True

    def _inferences(self):
        return [("api", self.inference)]

    def resources(self) -> ResourceSnapshot:
        snap = ResourceSnapshot()
        adm = self.inference.admission
        snap.admission["api"] = (adm.active, adm.queued)
        for inst, (rt, adapter) in self.shards.shards.items():
            if rt.compute is not None:
                _pool_entry(snap, inst, rt.compute.engine)
                _lane_entry(snap, inst, rt.compute)
            _stream_entry(snap, inst, adapter)
        if self.inference.adapter is not None:
            _stream_entry(snap, "api", self.inference.adapter)
        return snap


class MemberAutoScenario(MemberScenario):
    name = "member_auto"
    auto_steps = 8


# ---------------------------------------------------------------------------
# fleet / fleet_sched: replicated single-node stacks behind FleetManager
# ---------------------------------------------------------------------------


class FleetScenario(Scenario):
    name = "fleet"
    parity = "content"
    points = ("fleet_dispatch", "admit")

    n_replicas = 2
    batch_slots = 2

    def extra_env(self) -> Dict[str, str]:
        return {"DNET_FLEET": str(self.n_replicas)}

    async def _build(self) -> None:
        from dnet_tpu.api.http import ApiHTTPServer
        from dnet_tpu.api.inference import InferenceManager
        from dnet_tpu.api.model_manager import LocalModelManager
        from dnet_tpu.config import get_settings
        from dnet_tpu.fleet import FleetManager

        api = get_settings().api
        self.replicas = []
        for _ in range(self.n_replicas):
            inference = InferenceManager(
                adapter=None,
                request_timeout_s=api.request_timeout_s,
                max_concurrent=min(
                    api.max_concurrent_requests, self.batch_slots
                ),
            )
            manager = LocalModelManager(
                inference,
                models_dir=api.models_dir,
                max_seq=64,
                param_dtype="float32",
                batch_slots=self.batch_slots,
            )
            # dnetlint: disable=DL024 two engine loads share one jit cache: the second is cheap only AFTER the first finishes
            await manager.load_model(self.model_dir, max_seq=64)
            self.replicas.append((inference, manager))
        self.fleet = FleetManager()
        for i, (inference, _mgr) in enumerate(self.replicas):
            self.fleet.add_replica(f"r{i}", inference)
        self.server = ApiHTTPServer(
            self.replicas[0][0], self.replicas[0][1], fleet=self.fleet
        )
        port = _free_port()
        await self.server.start("127.0.0.1", port)
        self.base_url = f"http://127.0.0.1:{port}"

    async def _teardown(self) -> None:
        await self.server.stop()
        for _inf, mgr in self.replicas:
            await mgr.unload_model()  # dnetlint: disable=DL024 serial teardown keeps device memory accounting exact

    def _inferences(self):
        return [
            (f"r{i}", inf) for i, (inf, _m) in enumerate(self.replicas)
        ]

    def resources(self) -> ResourceSnapshot:
        snap = ResourceSnapshot()
        for i, (inference, manager) in enumerate(self.replicas):
            adm = inference.admission
            snap.admission[f"r{i}"] = (adm.active, adm.queued)
            _pool_entry(snap, f"r{i}", getattr(manager, "engine", None))
        return snap


class FleetSchedScenario(FleetScenario):
    name = "fleet_sched"


# ---------------------------------------------------------------------------
# fleet_ring: two in-process rings behind the fleet front door — the
# composed acceptance cell (failover mid-stream on top of in-ring resume)
# ---------------------------------------------------------------------------


class FleetRingScenario(Scenario):
    name = "fleet_ring"
    parity = "content"
    points = ("fleet_dispatch", "send_activation", "shard_compute")

    n_replicas = 2

    def extra_env(self) -> Dict[str, str]:
        env = dict(_RESUME_ENV)
        env["DNET_FLEET"] = str(self.n_replicas)
        return env

    async def _build(self) -> None:
        import json as _json
        from pathlib import Path

        from dnet_tpu.api.http import ApiHTTPServer
        from dnet_tpu.fleet import FleetManager
        from dnet_tpu.loadgen.ring_harness import InprocRing

        cfg = _json.loads(
            (Path(self.model_dir) / "config.json").read_text()
        )
        n_layers = int(cfg["num_hidden_layers"])
        half = max(n_layers // 2, 1)
        self.rings = []
        for _ in range(self.n_replicas):
            ring = InprocRing(
                self.model_dir,
                layers0=range(0, half),
                layers1=range(half, n_layers),
                max_seq=64,
                auto_steps=0,
                request_timeout_s=6.0,  # see RingScenario
            )
            # dnetlint: disable=DL024 two engine loads share one jit cache: the second is cheap only AFTER the first finishes
            await ring.start()
            self.rings.append(ring)
        self.fleet = FleetManager()
        for i, ring in enumerate(self.rings):
            self.fleet.add_replica(f"r{i}", ring.inference)
        self.server = ApiHTTPServer(
            self.rings[0].inference, self.rings[0].manager, fleet=self.fleet
        )
        port = _free_port()
        await self.server.start("127.0.0.1", port)
        self.base_url = f"http://127.0.0.1:{port}"

    async def _teardown(self) -> None:
        await self.server.stop()
        for ring in self.rings:
            await ring.stop()  # dnetlint: disable=DL024 serial teardown keeps device memory accounting exact

    @property
    def model(self) -> str:
        return "inproc-ring"

    async def kill_serving_replica(self, delay_s: float = 0.25) -> str:
        """The composed cell's fleet event: after `delay_s`, mark whichever
        replica holds the in-flight stream dead — its stream must splice
        onto the survivor."""
        await asyncio.sleep(delay_s)
        victim = "r0"
        for i, ring in enumerate(self.rings):
            if ring.inference.admission.active > 0:
                victim = f"r{i}"
                break
        self.fleet.fail_replica(victim)
        return victim

    def _inferences(self):
        return [
            (f"r{i}", ring.inference) for i, ring in enumerate(self.rings)
        ]

    def resources(self) -> ResourceSnapshot:
        snap = ResourceSnapshot()
        for i, ring in enumerate(self.rings):
            adm = ring.inference.admission
            snap.admission[f"r{i}"] = (adm.active, adm.queued)
            for rt_name, rt, adapter in (
                (f"r{i}.s0", ring.s0, ring.a0),
                (f"r{i}.s1", ring.s1, ring.a1),
            ):
                if rt.compute is not None:
                    _pool_entry(snap, rt_name, rt.compute.engine)
                    _lane_entry(snap, rt_name, rt.compute)
                _stream_entry(snap, rt_name, adapter)
            _stream_entry(snap, f"r{i}.api", ring.api)
        return snap


#: name -> scenario class; the campaign matrix and the CLI resolve here
SCENARIOS: Dict[str, type] = {
    cls.name: cls
    for cls in (
        LocalScenario, SchedScenario, RingScenario, RingWireScenario,
        MemberScenario, MemberAutoScenario, FleetScenario,
        FleetSchedScenario, FleetRingScenario,
    )
}


def build_scenario(name: str, model_dir: str) -> Scenario:
    try:
        cls = SCENARIOS[name]
    except KeyError:
        raise ValueError(
            f"unknown scenario {name!r}; one of {', '.join(SCENARIOS)}"
        ) from None
    return cls(model_dir)
