"""One run of one cell: `python -m benchmarks.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`, from the root of a checkout.

A new process that holds the cell's chips: seeded weights, the real
`dnet-api` server (serve_async: HTTP, admission, engine) in this process on
loopback, warm-up, the correctness check, then the measured window.  The
last line of stdout is the result, one JSON object with the contract's keys
and nothing else; everything else goes on earlier lines.

`--rehearse` runs the same orchestration on the CPU at the config's tiny
`rehearse` sizes with interpreted kernels.  Its metrics carry the prefix
`rehearsal.`: no CPU number is ever printed under a device metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH_KEYS = ("assumed", "deployment", "serve", "check", "rehearse")


def say(*parts) -> None:
    """An earlier line: for people and logs, never the result."""
    print("[bench]", *parts, flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="python -m benchmarks.run", description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


def hf_config(cell_config: dict, rehearse: bool) -> dict:
    cfg = {k: v for k, v in cell_config.items() if k not in BENCH_KEYS}
    if rehearse:
        cfg.update(cell_config["rehearse"]["config"])
    return cfg


def prepare_environment(cell, rehearse: bool) -> None:
    """Settings the configuration file records, placed before the program or
    JAX is imported.  Only existing settings of the program: no new switch."""
    serve = cell.config["serve"]
    os.environ.update({k: str(v) for k, v in serve.get("env", {}).items()})
    os.environ.setdefault("DNET_DRAIN_DEADLINE_S", "2")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.update(
            {k: str(v) for k, v in cell.config["rehearse"].get("env", {}).items()}
        )
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell.chips}"
            ).strip()


def configure_jax():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path inside the checkout: the path is part of the cache key
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # cache the sub-second programs too: a server start compiles hundreds
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def require_devices(jax, cell, rehearse: bool) -> dict:
    from benchmarks.harness.spec import peaks_for

    devs = jax.devices()
    platform = devs[0].platform
    if rehearse:
        if platform != "cpu":
            raise SystemExit("a rehearsal runs on the CPU")
    else:
        if platform == "cpu":
            say("no accelerator: JAX sees only the CPU; no result")
            raise SystemExit(3)
        peaks_for(devs[0].device_kind)  # a device not in the table is an error
    if len(devs) < cell.chips:
        say(f"cell needs {cell.chips} chips, JAX sees {len(devs)}; no result")
        raise SystemExit(3)
    return {"platform": platform, "kind": devs[0].device_kind, "count": len(devs)}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def http_json(session, url: str):
    async with session.get(url) as resp:
        return await resp.json()


async def scrape(session, url: str) -> dict:
    from benchmarks.harness import prom

    async with session.get(url + "/metrics") as resp:
        return prom.parse(await resp.text())


def memory_now(jax) -> dict:
    """Bytes in use on the fullest chip right now, its limit, the sum over
    the chips, and the allocator's peak since the process began (which holds
    the correctness check's float32 reference too)."""
    out = {"in_use": 0, "limit": 0, "sum_in_use": 0, "process_peak": 0}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        used = stats.get("bytes_in_use", 0)
        out["sum_in_use"] += used
        out["process_peak"] = max(out["process_peak"], stats.get("peak_bytes_in_use", 0))
        if used >= out["in_use"]:
            out["in_use"], out["limit"] = used, stats.get("bytes_limit", 0)
    return out


def health_faults(health: dict, expect: dict) -> list:
    """Which engine and kernels actually served, against the config's word."""
    faults = []
    kernels = health.get("kernels") or {}
    for col in expect.get("zero", ()):
        hit = {k: v[col] for k, v in kernels.items() if v.get(col)}
        if hit:
            faults.append(f"kernels resolved as {col}: {hit}")
    for k in expect.get("used", ()):
        if not (kernels.get(k) or {}).get(expect.get("impl", "pallas")):
            faults.append(f"kernel {k} never ran as {expect.get('impl', 'pallas')}")
    return faults


async def run(args, cell, jax, device: dict) -> dict:
    import aiohttp

    from benchmarks.harness import check as check_mod
    from benchmarks.harness import readers, traffic, window, xplane
    from benchmarks.harness.client import LoadDriver, StreamRow, clock, request_body, stream_one
    from benchmarks.harness.spec import layer_metric_file, load_json
    from benchmarks.harness.weights import write_checkpoint

    rehearse = args.rehearse
    cfg = hf_config(cell.config, rehearse)
    serve = cell.config["serve"]
    mix = dict(cell.traffic)
    if rehearse:
        mix.update(cell.traffic.get("rehearse", {}))
    phases = {}

    # ---- weights: on the device from the seed, written for the real loader
    t = clock()
    tmp = Path(tempfile.mkdtemp(prefix="dnet-bench-"))
    model_dir = tmp / cell.config_name
    nbytes = write_checkpoint(model_dir, cfg, args.seed, serve.get("dtype", "bfloat16"))
    phases["weights_s"] = clock() - t
    say(f"weights: {nbytes} bytes in {phases['weights_s']:.1f}s -> {model_dir}")

    # ---- the server, in this process
    from dnet_tpu.api.server import serve_async

    port = free_port()
    url = f"http://127.0.0.1:{port}"
    server = asyncio.ensure_future(
        serve_async(
            SimpleNamespace(
                host="127.0.0.1", http_port=port, grpc_port=free_port(), hostfile="",
                model=str(model_dir), models_dir="", mesh=serve.get("mesh", ""),
                discovery="none", tui=False, weight_quant_bits=None,
                auto_recover=False, batch_slots=None,
            )
        )
    )
    t = clock()
    session = aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=None))
    result: dict = {}
    driver = None
    try:
        while True:
            if server.done():
                server.result()
                raise RuntimeError("the server stopped before it was ready")
            try:
                health = await http_json(session, url + "/health")
                if health.get("model"):
                    break
            except aiohttp.ClientError:
                pass
            await asyncio.sleep(0.25)
        phases["load_s"] = clock() - t
        say(f"server ready in {phases['load_s']:.1f}s (load + the program's own warm-up)")
        model = health["model"]
        # weights served in a narrower type than the config's would fit in
        # fewer bytes than the checkpoint has (no reading on the CPU)
        held = memory_now(jax)["sum_in_use"]
        say(f"server holds {held} bytes on its chips; the checkpoint has {nbytes} in "
            f"{serve.get('dtype', 'bfloat16')}")
        narrow = 0 < held < nbytes

        # ---- warm every prefill shape the mix will use, one by one
        t = clock()
        warm = list(mix.get("warm_prompt_tokens", ()))
        for i, n in enumerate(warm):
            asked = int(mix.get("warm_answer_tokens", 2)) if i == len(warm) - 1 else 2
            ids = tuple(1 + (j % (cfg["vocab_size"] - 1)) for j in range(n))
            p = traffic.Planned(0, i, ids, asked, i)
            row = StreamRow(0, i, asked, clock())
            await stream_one(session, url, request_body(p, model, mix.get("sampling", {})), row)
            if row.failed or not row.finished:
                raise RuntimeError(f"warm-up request of {n} tokens failed: {row.error}")
        phases["warm_s"] = clock() - t

        # ---- the correctness check: served path against the plain reference
        t = clock()
        chk = dict(cell.config["check"])
        if rehearse:
            chk.update(cell.config["rehearse"].get("check", {}))
        verdict = await check_mod.compare(url, model, model_dir, cfg, chk, args.seed)
        phases["check_s"] = clock() - t
        say(
            f"check: largest |logprob - reference| {verdict['max_err']:.5f} (mean "
            f"{verdict['mean_err']:.5f}) over {verdict['values']} values at "
            f"{verdict['positions']} positions, tolerance {verdict['tolerance']} (mean "
            f"{verdict['mean_tolerance']}) -> "
            f"{'ok' if verdict['ok'] else 'WRONG'}"
        )

        # ---- traffic: ramp until every lane is out of phase and warm
        plans = traffic.plan(mix, args.seed, cfg["vocab_size"])
        driver = LoadDriver(url, model, plans, mix)
        t = clock()
        await driver.start()
        ramp_limit = float(mix.get("ramp_limit_s", 240.0))
        while not driver.ramped():
            if clock() - t > ramp_limit:
                raise RuntimeError(f"traffic did not ramp within {ramp_limit}s")
            await asyncio.sleep(0.05)
        # open between two bursts of tokens rather than inside one, if one
        # ends soon; this decides where the window starts and nothing else
        quiet = float(mix.get("open_quiet_s", 0.0))
        give_up = clock() + float(mix.get("open_quiet_limit_s", 5.0))
        while clock() < give_up and any(
            r.stamps and clock() - r.stamps[-1] < quiet for r in driver.rows
        ):
            await asyncio.sleep(0.005)
        phases["ramp_s"] = clock() - t

        # ---- the window: (t0, t0 + seconds], whatever the tokens do
        t0 = clock()
        setup_s = t0 - T_START
        t1 = t0 + float(args.seconds)
        say(f"window opens: setup {setup_s:.1f}s, phases {json.dumps({k: round(v, 1) for k, v in phases.items()})}")
        scrapes, trace_dir, trace = [], None, None
        slice_s = min(float(mix.get("trace_slice_s", 4.0)), float(args.seconds)) if args.trace else 0.0
        memory = memory_now(jax)
        if args.trace:
            scrapes.append(await scrape(session, url))
        # each second: the chip's memory, and in a traced run the counters
        while clock() < t1 - slice_s - 1.0:
            await asyncio.sleep(min(1.0, max(t1 - slice_s - 1.0 - clock(), 0.01)))
            now = memory_now(jax)
            memory = max(memory, now, key=lambda m: m["in_use"])
            if args.trace:
                scrapes.append(await scrape(session, url))
        if args.trace:  # profile the window's last slice
            trace_dir = tmp / "trace"
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the Python tracer slows the host it measures
            opts.host_tracer_level = 2
            await asyncio.sleep(max(t1 - slice_s - clock(), 0))
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        await asyncio.sleep(max(t1 - clock(), 0))
        memory = max(memory, memory_now(jax), key=lambda m: m["in_use"])
        if args.trace:
            scrapes.append(await scrape(session, url))
            await asyncio.get_running_loop().run_in_executor(None, jax.profiler.stop_trace)
        await asyncio.sleep(0.05)  # tokens that arrived by t1 get their stamps
        health = await http_json(session, url + "/health")
        rows = list(driver.rows)
        await driver.stop()
        if driver.wrapped:
            say(f"{driver.wrapped} clients reached the end of their lists and began again: "
                f"their prompts repeat; give the mix a longer requests_per_client")

        # ---- reduce
        streams = [(r.due, list(r.stamps)) for r in rows]
        summary = window.summarize(streams, t0, t1)
        late = driver.lateness(t0, t1)
        summary["gen_lateness_p99_ms"] = window.nearest_rank(late, 0.99) * 1e3 if late else 0.0
        attempted = sum(1 for r in rows if r.sent and r.sent <= t1)
        failed = sum(1 for r in rows if r.failed)
        say(
            f"window {t1 - t0:.2f}s: {int(summary['tokens'])} tokens, "
            f"{int(summary['n_gaps'])} gaps, {int(summary['n_ttft'])} first tokens, "
            f"drift {summary['window_drift_pct']:.2f}%, attempted {attempted}, failed {failed}"
        )
        say("client readings:", json.dumps(dict(summary, setup_s=setup_s)))
        say(f"memory: {memory['in_use']} bytes in use at most on the fullest chip through the "
            f"window, limit {memory['limit']}; the process's peak, the check's float32 "
            f"reference included, {memory['process_peak']}")
        for r in rows:
            if r.failed:
                say(f"failed stream client {r.client} seq {r.seq}: {r.error or 'token count'} "
                    f"({len(r.stamps)}/{r.asked})")
        faults = health_faults(health, serve.get("expect_health", {}) if not rehearse
                               else cell.config["rehearse"].get("expect_health", {}))
        if narrow:
            faults.append("the chips hold fewer bytes than the checkpoint: weights are "
                          "not served in the config's type")
        for f in faults:
            say("fault:", f)
        say("kernels:", json.dumps({k: {c: v[c] for c in ("pallas", "interpret", "emulate", "dense")}
                                    for k, v in (health.get("kernels") or {}).items()
                                    if any(v.get(c) for c in ("pallas", "interpret", "emulate", "dense"))}))
        correct = bool(verdict["ok"] and failed == 0 and not faults and summary["tokens"] > 0)

        metrics = {}
        prefix = "rehearsal." if rehearse else ""
        dev = dict(device, memory_peak_bytes=memory["in_use"])
        if args.trace:
            try:
                trace = xplane.load(xplane.find_xplane(trace_dir))
            except FileNotFoundError as exc:
                say("trace:", exc)
            if trace is not None and trace["devices"]:
                dev["busy_s"] = xplane.busy_s(trace)
                dev["window_s"] = xplane.window_s(trace)
                result["breakdown"] = xplane.breakdown(trace)
                dump_trace_names(trace, cell.name)
            ev = readers.Evidence(client=summary, scrapes=scrapes, trace=trace, memory=memory)
            for m in cell.per_layer:
                value = readers.read(load_json(layer_metric_file(m["name"])), ev)
                if value is not None:
                    metrics[prefix + m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = dict(summary, setup_s=setup_s)
            for m in cell.end_to_end:
                if m["name"] in values:
                    metrics[prefix + m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        result.update(correct=correct, attempted=attempted, failed=failed,
                      metrics=metrics, device=dev)
        return result
    finally:
        if driver is not None:
            await driver.stop()
        await session.close()
        # the server's own graceful path: SIGTERM -> drain -> teardown
        if not server.done():
            os.kill(os.getpid(), signal.SIGTERM)
            try:
                await asyncio.wait_for(server, 20)
            except (asyncio.TimeoutError, Exception) as exc:  # noqa: BLE001
                say(f"server shutdown: {type(exc).__name__}: {exc}")
        shutil.rmtree(tmp, ignore_errors=True)


def dump_trace_names(trace: dict, cell: str) -> None:
    """The full op names by time, for whoever writes the next trace_share
    pattern: too long for the result line, so under chiprun_out/."""
    out = ROOT / "chiprun_out" / "bench"
    try:
        out.mkdir(parents=True, exist_ok=True)
        from benchmarks.harness import xplane

        totals = {}
        for ops in trace["devices"].values():
            for name, d in xplane.self_times(ops):
                totals[name] = totals.get(name, 0) + d
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:60]
        (out / f"{cell}.ops.json").write_text(
            json.dumps([[n[:400], d / 1e9] for n, d in top], indent=0)
        )
    except OSError as exc:
        say("could not write op names:", exc)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "dnet_tpu" / "__init__.py").is_file():
        say("no program here: this directory holds the benchmark alone; no result")
        return 2
    from benchmarks.harness.spec import load_benchmark, resolve_cell

    cell = resolve_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(load_benchmark()["run_seconds"])
    prepare_environment(cell, args.rehearse)
    jax = configure_jax()
    device = require_devices(jax, cell, args.rehearse)
    say(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}, "
        f"seed {args.seed}, {args.seconds}s, trace {args.trace}, device {device}"
        + (" -- REHEARSAL on the CPU, interpreted kernels, tiny sizes" if args.rehearse else ""))
    result = asyncio.run(run(args, cell, jax, device))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # the chip's runtime can hang in teardown; everything is already stopped
    os._exit(0)


if __name__ == "__main__":
    raise SystemExit(main())
