#!/usr/bin/env python
"""Serving-grade load bench: open-loop traffic -> BENCH_SERVE_*.json.

The served-throughput gate ROADMAP item 5(b) calls for: where bench.py
measures one stream's device rate, this drives a SEEDED open-loop arrival
process of concurrent OpenAI-API streaming clients (dnet_tpu/loadgen/) and
reports what serving actually delivered — goodput over completed requests
only, TTFT/TPOT/E2E p50/p95/p99, the shed-rate breakdown by status and
admission reason, SLO attainment cross-validated against the live
`dnet_slo_*` gauges, and the decode-step phase / JIT-compile attribution
that says WHERE the time went.

Two targets:

- default: an IN-PROCESS single-node server over `--model` (CPU or
  whatever backend jax resolves) — the tier-1-reproducible smoke shape;
- `--base-url http://api:8080`: any live deployment, including a real
  multi-shard ring (the bench is then a pure client; phase attribution
  reflects whatever the target's /metrics expose).

Every knob also rides DNET_LOADGEN_* (config.LoadgenSettings); CLI flags
win.  The report lands in BENCH_SERVE_r<NN>.json (next free index) unless
--out names a path.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import re
import socket
import sys
from pathlib import Path


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="bench_serve", description=__doc__)
    p.add_argument("--model", default="",
                   help="checkpoint dir or catalog id (in-process mode); "
                   "for --base-url, the model name to put in request bodies")
    p.add_argument("--base-url", default="",
                   help="drive a live server instead of serving in-process")
    p.add_argument("--requests", type=int, default=None)
    p.add_argument("--rate", type=float, default=None, dest="rate_rps",
                   help="mean arrival rate (requests/s)")
    p.add_argument("--arrival", choices=["poisson", "fixed"], default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--buckets", default=None,
                   help="prompt:max_tokens,... length classes")
    p.add_argument("--weights", default=None, help="bucket weights")
    p.add_argument("--temperature", type=float, default=None)
    p.add_argument("--warmup-s", type=float, default=None,
                   help="exclude requests scheduled before this offset")
    p.add_argument("--timeout-s", type=float, default=None)
    p.add_argument("--slots", type=int, default=4,
                   help="in-process: continuous-batching slots (1 = local)")
    p.add_argument("--sched", action="store_true",
                   help="in-process: the scheduler's own sizing (every "
                   "in-process load is served by the scheduler, "
                   "dnet_tpu/sched/): lanes = --slots, admission at the "
                   "configured concurrency")
    p.add_argument("--ring-tp", action="store_true",
                   help="drive the workload over the in-process two-shard "
                   "ring THREE times — tp=1 baseline (r04's pipelined wire "
                   "config), tensor-parallel lossless, and q8 quantized "
                   "collectives — and emit one composite report with "
                   "meta.tp and collective-byte books per leg "
                   "(parallel/tp.py)")
    p.add_argument("--ring-inproc", action="store_true",
                   help="drive the workload over an in-process two-shard "
                   "ring TWICE — legacy serial wire vs the overlapped "
                   "qsparse8 pipeline (DNET_WIRE_PIPELINE=1) — and emit "
                   "one composite report with per-hop tx bytes and "
                   "encode/decode attribution (loadgen/ring_harness.py)")
    p.add_argument("--wire-pct", type=float, default=0.75,
                   help="ring-inproc: qsparse8 column-drop fraction for "
                   "the pipelined leg (DNET_WIRE_QSPARSE_PCT)")
    p.add_argument("--tp", type=int, default=0,
                   help="in-process ring legs: NamedSharding tensor-"
                   "parallel degree per shard (parallel/tp.py; 0 = the "
                   "DNET_TP default, 1 = single-chip).  Forced-host CPU "
                   "devices emulate the chips under tier-1.")
    p.add_argument("--tp-collective", default="",
                   help="ring-inproc: TP collective mode for every shard "
                   "(auto|lossless|q8; '' = DNET_TP_COLLECTIVE default)")
    p.add_argument("--fleet", type=int, default=0,
                   help="drive the workload through the fleet front door "
                   "(dnet_tpu/fleet/) THREE times — 1 replica, N replicas "
                   "behind the least-loaded prefix-affine router, and the "
                   "failover drill (kill r1 mid-burst; zero 5xx is the "
                   "bar) — and emit one composite report with per-replica "
                   "goodput and routing counters per leg")
    p.add_argument("--fleet-pace-ms", type=float, default=40.0,
                   help="fleet legs: emulated device-bound decode floor "
                   "(DNET_FLEET_DECODE_PACE_MS).  On a real TPU ring the "
                   "host WAITS on the device, so replicas scale across "
                   "hosts; co-hosted CPU replicas would just contend for "
                   "the same cores and show no scaling.  0 disables the "
                   "floor (raw CPU contention).")
    p.add_argument("--max-seq", type=int, default=1024)
    p.add_argument("--param-dtype", default="bfloat16")
    p.add_argument("--out", default="", help="report path (default: next "
                   "BENCH_SERVE_r<NN>.json)")
    p.add_argument("--no-rows", action="store_true",
                   help="omit per-request rows from the report")
    return p


def _spec_from(args):
    from dnet_tpu.config import get_settings
    from dnet_tpu.loadgen import WorkloadSpec, parse_buckets

    s = get_settings().loadgen

    def pick(cli, env):
        return env if cli is None else cli

    return WorkloadSpec(
        seed=pick(args.seed, s.seed),
        requests=pick(args.requests, s.requests),
        rate_rps=pick(args.rate_rps, s.rate_rps),
        arrival=pick(args.arrival, s.arrival),
        buckets=parse_buckets(
            pick(args.buckets, s.buckets), pick(args.weights, s.weights)
        ),
        temperature=pick(args.temperature, s.temperature),
        warmup_s=pick(args.warmup_s, s.warmup_s),
        timeout_s=pick(args.timeout_s, s.timeout_s),
    )


def _next_report_path() -> Path:
    used = set()
    for f in Path(".").glob("BENCH_SERVE_r*.json"):
        m = re.match(r"BENCH_SERVE_r(\d+)\.json$", f.name)
        if m:
            used.add(int(m.group(1)))
    n = 1
    while n in used:
        n += 1
    return Path(f"BENCH_SERVE_r{n:02d}.json")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _kv_mode(engine) -> str:
    """The KV layout the loaded engine RESOLVED to (a refusal falls back
    to dense slots, and the stamp must say which path the numbers
    measured); `ragged` is the pool attended in place, as the earlier
    records name it."""
    return "ragged" if getattr(engine, "kv_pool", None) is not None else "dense"


def _tp_mode(engine) -> dict:
    """meta.tp: the RESOLVED tensor-parallel shape of one engine (the
    meta.kv discipline — a clamped DNET_TP must stamp what actually
    served).  degree 1 = the pre-TP single-chip behavior."""
    from dnet_tpu.parallel.tp import TpEngine

    if isinstance(engine, TpEngine):
        return {"degree": engine.tp, "collective": engine.collective_mode}
    return {"degree": 1, "collective": "lossless"}


async def _run_remote(args, spec) -> dict:
    import aiohttp

    from dnet_tpu.loadgen import run_load

    # no session-level cap: the per-request budget (spec.timeout_s via
    # run_request's wait_for) owns the timeout; aiohttp's default
    # ClientTimeout(total=300) would silently override longer budgets
    async with aiohttp.ClientSession(
        base_url=args.base_url, timeout=aiohttp.ClientTimeout(total=None)
    ) as session:
        result = await run_load(
            session, spec, args.model or "default",
            include_rows=not args.no_rows,
            meta={"target": args.base_url, "mode": "remote"},
        )
    return result.report


async def _run_inprocess(args, spec) -> dict:
    """Single-node serving stack in this process (the bench.py-measured
    engines behind the REAL admission/SSE/driver path), driven over a
    loopback HTTP port so the client half is identical to remote mode."""
    import aiohttp

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager
    from dnet_tpu.config import get_settings
    from dnet_tpu.loadgen import run_load

    api = get_settings().api
    # legacy path: admission must not out-admit the engine's slot pool —
    # excess load then queues (and sheds with Retry-After) at the admission
    # layer instead of hard-failing against the batch-slot pool.  The
    # scheduler path queues and preempts INTERNALLY (WAITING is a real
    # state, admission is a function of free KV blocks), so it keeps the
    # configured concurrency and lets the tick loop do the pacing.
    max_concurrent = (
        api.max_concurrent_requests
        if args.sched
        else min(api.max_concurrent_requests, max(args.slots, 1))
    )
    inference = InferenceManager(
        adapter=None,
        request_timeout_s=api.request_timeout_s,
        max_concurrent=max_concurrent,
    )
    manager = LocalModelManager(
        inference,
        models_dir=api.models_dir,
        max_seq=args.max_seq,
        param_dtype=args.param_dtype,
        batch_slots=args.slots,
    )
    await manager.load_model(args.model, max_seq=args.max_seq)
    server = ApiHTTPServer(inference, manager)
    port = _free_port()
    await server.start("127.0.0.1", port)
    try:
        async with aiohttp.ClientSession(
            base_url=f"http://127.0.0.1:{port}",
            # per-request wait_for owns the budget (see remote mode)
            timeout=aiohttp.ClientTimeout(total=None),
        ) as session:
            result = await run_load(
                session, spec, args.model,
                include_rows=not args.no_rows,
                meta={
                    "mode": "in-process",
                    "engine": "sched" if manager.serving.adapter == "SchedulerAdapter" else "legacy",
                    "kv": _kv_mode(manager.engine),
                    "tp": _tp_mode(manager.engine),
                    "slots": args.slots,
                    "max_seq": args.max_seq,
                    "param_dtype": args.param_dtype,
                },
            )
    finally:
        await server.stop()
        await manager.unload_model()
    return result.report


async def _ring_leg(args, spec, *, pipeline: bool, codec: str,
                    tp: int = None, tp_collective: str = None) -> dict:
    """One ring run: fresh two-shard in-process ring, fresh obs books,
    the full loadgen client over a real loopback HTTP port.  Returns the
    loadgen report extended with the harness's per-hop wire accounting
    and the overlap tracker's serial/hidden split."""
    import os

    import aiohttp

    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.loadgen import run_load
    from dnet_tpu.loadgen.ring_harness import InprocRing
    from dnet_tpu.obs import metric, reset_obs
    from dnet_tpu.transport.wire_pipeline import overlap

    if pipeline:
        os.environ["DNET_WIRE_PIPELINE"] = "1"
    else:
        os.environ.pop("DNET_WIRE_PIPELINE", None)
    os.environ["DNET_WIRE_QSPARSE_PCT"] = str(args.wire_pct)
    reset_settings_cache()
    reset_obs()
    overlap.reset()

    cfg = json.loads(
        (Path(args.model).expanduser() / "config.json").read_text()
    )
    n_layers = int(cfg["num_hidden_layers"])
    half = max(n_layers // 2, 1)
    ring = InprocRing(
        args.model,
        layers0=range(0, half),
        layers1=range(half, n_layers),
        max_seq=args.max_seq,
        param_dtype=args.param_dtype,
        wire_codec=codec,
        tp=args.tp if tp is None else tp,
        tp_collective=(
            args.tp_collective if tp_collective is None else tp_collective
        ),
    )
    await ring.start()
    port = _free_port()
    await ring.server.start("127.0.0.1", port)
    try:
        async with aiohttp.ClientSession(
            base_url=f"http://127.0.0.1:{port}",
            timeout=aiohttp.ClientTimeout(total=None),
        ) as session:
            result = await run_load(
                session, spec, "inproc-ring",
                include_rows=not args.no_rows,
                meta={
                    "mode": "ring-inproc",
                    "wire": "pipelined" if pipeline else "legacy",
                    "codec": codec,
                    "qsparse_pct": args.wire_pct if codec == "qsparse8" else None,
                    "shards": 2,
                    "layers": [list(ring.layers0), list(ring.layers1)],
                    # the RESOLVED per-shard TP shape (parallel/tp.py):
                    # what actually served, not what --tp asked for
                    "tp": _tp_mode(ring.s0.compute.engine),
                    "max_seq": args.max_seq,
                    "param_dtype": args.param_dtype,
                },
            )
            # resolved TP shape, read while the engines are still alive
            # (ring.stop() frees them)
            tp_meta = _tp_mode(ring.s0.compute.engine)
    finally:
        await ring.server.stop()
        await ring.stop()
    report = result.report
    # TP collective books for this leg (obs was reset at leg start, so the
    # absolute values ARE the leg totals): the analytic per-dispatch
    # interconnect bytes plus the load-time latency probe medians
    coll_ms = metric("dnet_tp_collective_ms").labels(op="all_reduce")
    report["tp"] = {
        **tp_meta,
        "collective_bytes_all_reduce": metric(
            "dnet_tp_collective_bytes_total"
        ).labels(op="all_reduce").value,
        "collective_probe_ms_all_reduce": round(
            coll_ms.sum / coll_ms.count, 3
        ) if coll_ms.count else None,
    }
    wire = ring.stats.as_dict()
    ov = overlap.snapshot()
    hidden_frames = sum(wire["hidden_frames"].values()) or 1
    report["wire"] = {
        **wire,
        "encode_ms_count": metric("dnet_wire_encode_ms").count,
        "decode_ms_count": metric("dnet_wire_decode_ms").count,
        # THE overlap numbers: serial = codec ms paid on the compute
        # thread, hidden = codec ms overlapped with compute (tx stage /
        # ingress).  Per-hidden-frame serial ms ~0 is the acceptance bar.
        "codec_serial_ms": round(ov["serial_ms"], 3),
        "codec_hidden_ms": round(ov["hidden_ms"], 3),
        # compute-thread waits on the full encode ring: the depth bound
        # exerting backpressure (the wire IS the bottleneck on a toy-model
        # CPU ring), kept out of the serial/overlap books
        "codec_backpressure_stall_ms": round(ov["stall_ms"], 3),
        "codec_serial_ms_per_hidden_frame": round(
            ov["serial_ms"] / hidden_frames, 4
        ),
        "overlap_ratio": round(ov["ratio"], 4),
    }
    return report


async def _run_ring_inproc(args, spec) -> dict:
    """Legacy serial wire vs overlapped qsparse8 pipeline over the SAME
    seeded workload and the SAME two-shard in-process ring: one composite
    BENCH_SERVE record proving the wire got smaller AND free."""
    import os

    from dnet_tpu.config import reset_settings_cache

    # the ring serves B=1 per nonce through two compute threads — a 16rps
    # open-loop burst queues at admission rather than shedding, so every
    # leg completes 96/96 and the comparison is codec-only (recorded in
    # meta; the per-request budget still bounds every stream)
    admit_depth = str(spec.requests)
    admit_timeout = str(spec.timeout_s)
    os.environ["DNET_ADMIT_QUEUE_DEPTH"] = admit_depth
    os.environ["DNET_ADMIT_QUEUE_TIMEOUT_S"] = admit_timeout
    # three legs, one seeded workload: the status-quo wire, what the
    # qsparse8 codec would cost ON the serial path, and the pipeline
    # hiding it — the middle leg is what makes "serial codec time ~0" a
    # like-for-like claim instead of a lossless-vs-quantized pun
    try:
        legacy = await _ring_leg(args, spec, pipeline=False, codec="lossless")
        q8_serial = await _ring_leg(
            args, spec, pipeline=False, codec="qsparse8"
        )
        pipelined = await _ring_leg(args, spec, pipeline=True, codec="qsparse8")
    finally:
        # a failed leg must not leave bench-sized admission queues or the
        # wire overrides behind for whatever runs in this process next
        os.environ.pop("DNET_WIRE_PIPELINE", None)
        os.environ.pop("DNET_WIRE_QSPARSE_PCT", None)
        os.environ.pop("DNET_ADMIT_QUEUE_DEPTH", None)
        os.environ.pop("DNET_ADMIT_QUEUE_TIMEOUT_S", None)
        reset_settings_cache()
    lw, sw, pw = legacy["wire"], q8_serial["wire"], pipelined["wire"]
    l_hidden = sum(lw["hidden_bytes"].values())
    p_hidden = sum(pw["hidden_bytes"].values())
    sync_ms = sw["codec_serial_ms_per_hidden_frame"]
    piped_ms = pw["codec_serial_ms_per_hidden_frame"]
    return {
        "kind": "bench_serve_ring",
        "spec": legacy["spec"],
        "meta": {
            "mode": "ring-inproc",
            "model": args.model,
            "admit_queue_depth": admit_depth,
            "admit_queue_timeout_s": admit_timeout,
        },
        "legacy": legacy,
        "qsparse8_serial": q8_serial,
        "pipelined": pipelined,
        "comparison": {
            "hidden_hop_bytes_legacy": l_hidden,
            "hidden_hop_bytes_pipelined": p_hidden,
            "hidden_hop_bytes_ratio": round(l_hidden / max(p_hidden, 1), 2),
            # per-hidden-frame codec ms the COMPUTE THREAD paid
            "codec_serial_ms_per_frame_lossless": lw[
                "codec_serial_ms_per_hidden_frame"
            ],
            "codec_serial_ms_per_frame_qsparse8_serial": sync_ms,
            "codec_serial_ms_per_frame_qsparse8_pipelined": piped_ms,
            "serial_codec_hidden_fraction": round(
                1.0 - piped_ms / max(sync_ms, 1e-9), 4
            ),
            "overlap_ratio_pipelined": pw["overlap_ratio"],
            "goodput_tok_s_legacy": legacy["goodput"]["tok_s"],
            "goodput_tok_s_qsparse8_serial": q8_serial["goodput"]["tok_s"],
            "goodput_tok_s_pipelined": pipelined["goodput"]["tok_s"],
            "completed_legacy": legacy["requests"]["completed"],
            "completed_qsparse8_serial": q8_serial["requests"]["completed"],
            "completed_pipelined": pipelined["requests"]["completed"],
        },
    }


async def _fleet_leg(args, spec, n_replicas: int, *,
                     fail_after_s: float = None) -> dict:
    """One fleet run: N fresh single-node replicas (full InferenceManager
    + engine stacks over the SAME checkpoint), one FleetManager front
    door, one loopback HTTP port, fresh obs books.  `fail_after_s` arms
    the failover drill: a timer marks r1 dead mid-burst, and the router
    must re-admit its in-flight streams on a survivor with zero 5xx."""
    import os

    import aiohttp

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager
    from dnet_tpu.config import get_settings, reset_settings_cache
    from dnet_tpu.fleet import FleetManager
    from dnet_tpu.loadgen import run_load
    from dnet_tpu.obs import metric, reset_obs

    os.environ["DNET_FLEET"] = str(n_replicas)
    reset_settings_cache()
    reset_obs()
    api = get_settings().api
    replicas = []
    for _ in range(n_replicas):
        inference = InferenceManager(
            adapter=None,
            request_timeout_s=api.request_timeout_s,
            # legacy engine path: admission capacity == the slot pool
            # (see _run_inprocess)
            max_concurrent=min(
                api.max_concurrent_requests, max(args.slots, 1)
            ),
        )
        manager = LocalModelManager(
            inference,
            models_dir=api.models_dir,
            max_seq=args.max_seq,
            param_dtype=args.param_dtype,
            batch_slots=args.slots,
        )
        await manager.load_model(args.model, max_seq=args.max_seq)
        replicas.append((inference, manager))
    fleet = FleetManager()
    for i, (inference, _mgr) in enumerate(replicas):
        fleet.add_replica(f"r{i}", inference)
    server = ApiHTTPServer(replicas[0][0], replicas[0][1], fleet=fleet)
    port = _free_port()
    await server.start("127.0.0.1", port)
    killer = None
    if fail_after_s is not None:
        async def _kill() -> None:
            await asyncio.sleep(fail_after_s)
            fleet.fail_replica("r1")

        killer = asyncio.ensure_future(_kill())
    try:
        async with aiohttp.ClientSession(
            base_url=f"http://127.0.0.1:{port}",
            timeout=aiohttp.ClientTimeout(total=None),
        ) as session:
            result = await run_load(
                session, spec, args.model,
                include_rows=not args.no_rows,
                meta={
                    "mode": "fleet",
                    "replicas": n_replicas,
                    "failover_drill": fail_after_s is not None,
                    "slots": args.slots,
                    "max_seq": args.max_seq,
                    "param_dtype": args.param_dtype,
                },
            )
    finally:
        if killer is not None:
            killer.cancel()
        await server.stop()
        for _inf, mgr in replicas:
            await mgr.unload_model()
    report = result.report
    # leg-local routing books (obs was reset at leg start, so absolute
    # values ARE the leg totals) + the 5xx count the failover bar gates on
    report["fleet_leg"] = {
        "http_5xx": sum(
            1 for o in result.outcomes if 500 <= o.status < 600
        ),
        "failovers_total": int(metric("dnet_fleet_failovers_total").value),
        "affinity_hits_total": int(
            metric("dnet_fleet_affinity_hits_total").value
        ),
    }
    return report


async def _run_fleet(args, spec) -> dict:
    """Fleet front-door legs over the SAME seeded workload: one replica,
    N replicas behind the least-loaded prefix-affine router, then the
    mid-burst failover drill.

    Admission queues are pinned DEEP (every request queues rather than
    sheds, like the r04 ring legs), so each capacity leg drains the
    identical workload and the goodput ratio is pure serving-rate
    scaling: tokens over the wall-clock each fleet size needs to drain
    the burst.  Decode runs under the DNET_FLEET_DECODE_PACE_MS floor
    (--fleet-pace-ms): on real hardware the host waits on the device
    and replicas scale across hosts, so the floor — which overlaps
    across co-hosted replicas the way device time would — is what makes
    a single-box fleet bench measure routing, not CPU contention."""
    import os

    from dnet_tpu.config import reset_settings_cache

    n = max(args.fleet, 2)
    admit_depth = str(spec.requests)
    os.environ["DNET_ADMIT_QUEUE_DEPTH"] = admit_depth
    os.environ["DNET_ADMIT_QUEUE_TIMEOUT_S"] = str(spec.timeout_s)
    os.environ["DNET_FLEET_DECODE_PACE_MS"] = str(max(args.fleet_pace_ms, 0.0))
    try:
        one = await _fleet_leg(args, spec, 1)
        two = await _fleet_leg(args, spec, n)
        # kill r1 ~40% into the measured serving window of the healthy
        # N-replica leg: late enough that it holds in-flight streams,
        # early enough that the survivors serve meaningful post-failover
        # load before the burst drains
        two_serving = max(two["duration_s"] - spec.warmup_s, 0.0)
        fail_at = spec.warmup_s + 0.4 * two_serving
        failover = await _fleet_leg(args, spec, n, fail_after_s=fail_at)
    finally:
        for k in ("DNET_FLEET", "DNET_ADMIT_QUEUE_DEPTH",
                  "DNET_ADMIT_QUEUE_TIMEOUT_S", "DNET_FLEET_DECODE_PACE_MS"):
            os.environ.pop(k, None)
        reset_settings_cache()
    g1 = one["goodput"]["tok_s"]
    g2 = two["goodput"]["tok_s"]
    return {
        "kind": "bench_serve_fleet",
        "spec": one["spec"],
        "meta": {
            "mode": "fleet",
            "model": args.model,
            "replicas": n,
            "failover_at_s": round(fail_at, 3),
            "admit_queue_depth": admit_depth,
            "decode_pace_ms": max(args.fleet_pace_ms, 0.0),
        },
        "one_replica": one,
        "two_replica": two,
        "failover": failover,
        "comparison": {
            "goodput_tok_s_one": g1,
            "goodput_tok_s_two": g2,
            "goodput_ratio": round(g2 / max(g1, 1e-9), 3),
            "completed_one": one["requests"]["completed"],
            "completed_two": two["requests"]["completed"],
            "completed_failover": failover["requests"]["completed"],
            "ttft_p99_ms_one": one["latency_ms"]["ttft"]["p99_ms"],
            "ttft_p99_ms_two": two["latency_ms"]["ttft"]["p99_ms"],
            "tpot_p99_ms_one": one["latency_ms"]["tpot"]["p99_ms"],
            "tpot_p99_ms_two": two["latency_ms"]["tpot"]["p99_ms"],
            "failover_http_5xx": failover["fleet_leg"]["http_5xx"],
            "failovers_total": failover["fleet_leg"]["failovers_total"],
        },
    }


async def _run_ring_tp(args, spec) -> dict:
    """Hybrid TP x PP legs over the SAME seeded workload and the SAME
    two-shard in-process ring as r04: the tp=1 baseline (directly
    comparable to r04's pipelined leg — identical wire config), the
    tensor-parallel lossless leg (byte-identical streams, TP speedup
    bounded here by CPU chip emulation), and the q8 quantized-collective
    leg (strictly fewer interconnect bytes).  One composite record with
    meta.tp stamped per leg."""
    import os

    from dnet_tpu.config import reset_settings_cache

    tp = args.tp if args.tp > 0 else 4  # 0 = unset; an explicit 1 is honored
    admit_depth = str(spec.requests)
    admit_timeout = str(spec.timeout_s)
    os.environ["DNET_ADMIT_QUEUE_DEPTH"] = admit_depth
    os.environ["DNET_ADMIT_QUEUE_TIMEOUT_S"] = admit_timeout
    try:
        base = await _ring_leg(
            args, spec, pipeline=True, codec="qsparse8", tp=1,
            tp_collective="lossless",
        )
        tp_lossless = await _ring_leg(
            args, spec, pipeline=True, codec="qsparse8", tp=tp,
            tp_collective="lossless",
        )
        tp_q8 = await _ring_leg(
            args, spec, pipeline=True, codec="qsparse8", tp=tp,
            tp_collective="q8",
        )
    finally:
        os.environ.pop("DNET_WIRE_PIPELINE", None)
        os.environ.pop("DNET_WIRE_QSPARSE_PCT", None)
        os.environ.pop("DNET_ADMIT_QUEUE_DEPTH", None)
        os.environ.pop("DNET_ADMIT_QUEUE_TIMEOUT_S", None)
        reset_settings_cache()
    return {
        "kind": "bench_serve_ring_tp",
        "spec": base["spec"],
        "meta": {
            "mode": "ring-tp",
            "model": args.model,
            "tp": tp,
            "admit_queue_depth": admit_depth,
            "admit_queue_timeout_s": admit_timeout,
        },
        "tp1": base,
        "tp_lossless": tp_lossless,
        "tp_q8": tp_q8,
        "comparison": {
            "goodput_tok_s_tp1": base["goodput"]["tok_s"],
            "goodput_tok_s_tp_lossless": tp_lossless["goodput"]["tok_s"],
            "goodput_tok_s_tp_q8": tp_q8["goodput"]["tok_s"],
            "completed_tp1": base["requests"]["completed"],
            "completed_tp_lossless": tp_lossless["requests"]["completed"],
            "completed_tp_q8": tp_q8["requests"]["completed"],
            "collective_bytes_lossless": tp_lossless["tp"][
                "collective_bytes_all_reduce"
            ],
            "collective_bytes_q8": tp_q8["tp"][
                "collective_bytes_all_reduce"
            ],
        },
    }


def _summarize_ring_tp(report: dict) -> str:
    c = report["comparison"]
    return "\n".join([
        f"ring tp legs (tp={report['meta']['tp']}): goodput "
        f"{c['goodput_tok_s_tp1']}/{c['goodput_tok_s_tp_lossless']}/"
        f"{c['goodput_tok_s_tp_q8']} tok/s (tp1/lossless/q8), completed "
        f"{c['completed_tp1']}/{c['completed_tp_lossless']}/"
        f"{c['completed_tp_q8']}",
        f"collective bytes: lossless {c['collective_bytes_lossless']:.0f} "
        f"-> q8 {c['collective_bytes_q8']:.0f}",
    ])


def _summarize_fleet(report: dict) -> str:
    c = report["comparison"]
    fo = report["failover"]["fleet_leg"]
    return "\n".join([
        f"fleet legs ({report['meta']['replicas']} replicas): goodput "
        f"{c['goodput_tok_s_one']} -> {c['goodput_tok_s_two']} tok/s "
        f"({c['goodput_ratio']}x), completed {c['completed_one']} -> "
        f"{c['completed_two']}",
        f"ttft p99 ms: {c['ttft_p99_ms_one']} -> {c['ttft_p99_ms_two']}; "
        f"tpot p99 ms: {c['tpot_p99_ms_one']} -> {c['tpot_p99_ms_two']}",
        f"failover drill: {c['completed_failover']} completed, "
        f"{fo['http_5xx']} HTTP 5xx, {fo['failovers_total']} failover(s)",
    ])


def _summarize_ring(report: dict) -> str:
    c = report["comparison"]
    return "\n".join([
        f"ring wire: {c['hidden_hop_bytes_legacy']} -> "
        f"{c['hidden_hop_bytes_pipelined']} hidden-hop bytes "
        f"({c['hidden_hop_bytes_ratio']}x fewer)",
        f"serial codec ms/frame: lossless "
        f"{c['codec_serial_ms_per_frame_lossless']}, qsparse8 serial "
        f"{c['codec_serial_ms_per_frame_qsparse8_serial']} -> pipelined "
        f"{c['codec_serial_ms_per_frame_qsparse8_pipelined']} "
        f"({c['serial_codec_hidden_fraction']:.0%} off the compute thread; "
        f"overlap {c['overlap_ratio_pipelined']})",
        f"completed: {c['completed_legacy']}/"
        f"{c['completed_qsparse8_serial']}/{c['completed_pipelined']} "
        f"(legacy/q8-serial/pipelined); goodput "
        f"{c['goodput_tok_s_legacy']}/{c['goodput_tok_s_qsparse8_serial']}/"
        f"{c['goodput_tok_s_pipelined']} tok/s",
    ])


def _summarize(report: dict) -> str:
    if report.get("kind") == "bench_serve_fleet":
        return _summarize_fleet(report)
    if report.get("kind") == "bench_serve_ring_tp":
        return _summarize_ring_tp(report)
    if report.get("kind") == "bench_serve_ring":
        return _summarize_ring(report)
    r = report["requests"]
    g = report["goodput"]
    lat = report["latency_ms"]
    lines = [
        f"requests: {r['completed']}/{r['measured']} completed, "
        f"{r['shed']} shed ({r['shed_by_status']}), {r['failed']} failed",
        f"goodput: {g['tok_s']} tok/s ({g['tokens_out']} tokens over "
        f"{report['measured_window_s']}s)",
        f"ttft ms p50/p95/p99: {lat['ttft']['p50_ms']}/"
        f"{lat['ttft']['p95_ms']}/{lat['ttft']['p99_ms']}",
        f"tpot ms p50/p95/p99: {lat['tpot']['p50_ms']}/"
        f"{lat['tpot']['p95_ms']}/{lat['tpot']['p99_ms']}",
    ]
    pa = report.get("phase_attribution")
    if pa and pa["decode_step"]["count"]:
        parts = ", ".join(
            f"{ph}={v['sum_ms']:.0f}ms" for ph, v in pa["phases"].items()
        )
        lines.append(f"decode phases: {parts} (coverage {pa['coverage']})")
    slo = report.get("slo")
    if slo:
        lines.append(
            f"slo attained: {slo['attained']} (burning: {slo['burning']})"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    import os

    from dnet_tpu.config import configure_compile_cache

    configure_compile_cache()
    # the tick-record ring and the [PROFILE] lines are gated on obs; the
    # bench opts in for its own process (a remote target keeps its own
    # setting).  The span table (dnet_span_ms) is always on.
    os.environ.setdefault("DNET_OBS_ENABLED", "1")
    args = build_parser().parse_args(argv)
    if not args.base_url and not args.model:
        print("error: --model is required without --base-url",
              file=sys.stderr)
        return 2
    if args.sched:
        if args.base_url:
            print("error: --sched is an in-process knob; a remote target "
                  "sizes its own scheduler", file=sys.stderr)
            return 2
        # --slots governs the lane count (DNET_SCHED_SLOTS=0 would widen
        # the scheduler to max(slots, 8)); an explicit DNET_SCHED_SLOTS in
        # the environment still wins; before reset_settings_cache so
        # SchedSettings sees it
        os.environ.setdefault("DNET_SCHED_SLOTS", str(max(args.slots, 1)))
    from dnet_tpu.config import reset_settings_cache

    reset_settings_cache()
    spec = _spec_from(args)
    if args.fleet:
        if args.base_url:
            print("error: --fleet is an in-process mode", file=sys.stderr)
            return 2
        runner = _run_fleet
    elif args.ring_inproc or args.ring_tp:
        if args.base_url:
            print("error: --ring-inproc/--ring-tp are in-process modes",
                  file=sys.stderr)
            return 2
        runner = _run_ring_tp if args.ring_tp else _run_ring_inproc
    else:
        runner = _run_remote if args.base_url else _run_inprocess
    report = asyncio.run(runner(args, spec))
    out = Path(args.out) if args.out else _next_report_path()
    out.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    print(_summarize(report))
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
