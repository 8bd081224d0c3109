#!/usr/bin/env python3
"""Prove, on demand, that dnet-tpu still starts and serves on the chip.

    python3 chip_smoke.py             # on a machine with a TPU; fails without one
    python3 chip_smoke.py --rehearse  # same orchestration, toy model, CPU

Drives the serving path once through the entry points a user calls, at the
full width and depth of Llama-3.2-1B (seeded random weights; there is no
network to fetch published ones), and checks what comes out by the repo's
own means.  Phases, each ONE child process that owns the chip alone and has
exited before the next starts (this parent never imports jax or dnet_tpu):

  device         what JAX reports: platform, device_kind, count
  model          seeded HF-format checkpoint on disk (reused when present)
  kernels        every Pallas kernel compiled by Mosaic at this model's
                 shapes and compared with its jnp twin
  serve          `python -m dnet_tpu.cli.api --model <dir>`, no DNET_* set
                 (the scheduler over the paged pool, attended in place):
                 greedy twice (identical), a long streamed prompt, sampled;
                 then 8 concurrent streams, after which the block pool's
                 books must balance
  mesh4          (>= 4 devices) --mesh pp=2,tp=2, the lone requests of
                 `serve`, plus MeshEngine's logits against LocalEngine's

Children run with JAX_PLATFORMS=tpu, so a missing chip is an error and never
a CPU run; only --rehearse reaches the CPU, and says so first and last.
The last line of stdout is the result, one JSON object with exactly these
keys: {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}},
the device as JAX reports it.  The line before it is the JSON summary
(phases, seconds, compile cache, ..., "claim": null), also written to
chiprun_out/chip_smoke/summary.json beside the children's logs.  The exit
code is non-zero if any phase failed; without an accelerator no result is
printed at all.  It is a smoke test, not a benchmark: the seconds it prints
are for budgeting the run, not for comparing commits.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE / "chiprun_out" / "chip_smoke"
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
REHEARSAL_BANNER = "REHEARSAL (cpu) — not a chip result"

#: what changes between the chip run and the rehearsal; the orchestration
#: below is the same code for both
CHIP = {
    "platform": "tpu",
    "config": "llama-3.2-1b",
    "model_dir": HERE / ".chip_smoke" / "model-llama-3.2-1b",
    "env": {"JAX_PLATFORMS": "tpu"},
    "max_seq": 4096,  # the servers' DNET_API_MAX_SEQ_LEN default
    "long_prompt_bytes": 1500,
    "sched_prompt_bytes": (32, 1024),
    "kernel_impl": "pallas",
}
REHEARSAL = {
    "platform": "cpu",
    "config": "tiny-llama",
    "model_dir": HERE / ".chip_smoke" / "model-tiny-llama",
    "env": {
        "JAX_PLATFORMS": "cpu",
        "DNET_FLASH_INTERPRET": "1",
        "DNET_API_MAX_SEQ_LEN": "256",
        # four host devices, so that mesh4 is rehearsed too
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    },
    "max_seq": 256,
    "long_prompt_bytes": 150,
    "sched_prompt_bytes": (16, 96),
    "kernel_impl": "interpret",
}


class PhaseFailed(Exception):
    pass


class Smoke:
    def __init__(self, mode: dict, kernel_tolerance, model_dir) -> None:
        self.mode = mode
        self.kernel_tolerance = kernel_tolerance
        self.model_dir = Path(model_dir) if model_dir else mode["model_dir"]
        self.t_start = time.monotonic()
        self.children: list = []
        self.phases: dict = {}
        self.device: dict = {}
        self.checkpoint_bytes = 0
        # children see this checkout's code, no DNET_* switch but the ones a
        # phase sets, and the platform this mode names
        env = {k: v for k, v in os.environ.items() if not k.startswith("DNET_")}
        env.update(mode["env"])
        env["PYTHONPATH"] = os.pathsep.join(
            [str(HERE)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        env["PYTHONUNBUFFERED"] = "1"
        self.env = env

    # ---- processes ----------------------------------------------------
    def remaining(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t_start)

    def spawn(self, argv, log_name: str, extra_env=None) -> subprocess.Popen:
        if any(p.poll() is None for p in self.children):
            raise PhaseFailed("a previous child is still running")
        log = open(OUT / log_name, "w")
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=HERE, env={**self.env, **(extra_env or {})},
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )
        log.close()
        self.children.append(proc)
        return proc

    def run(self, argv, log_name: str, timeout: float, extra_env=None) -> str:
        """Run one child to its end; returns its output."""
        proc = self.spawn(argv, log_name, extra_env)
        try:
            rc = proc.wait(timeout=max(min(timeout, self.remaining()), 1))
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise PhaseFailed(f"timed out; tail of {log_name}:\n{tail(log_name)}")
        if rc != 0:
            raise PhaseFailed(f"exit code {rc}; tail of {log_name}:\n{tail(log_name)}")
        return (OUT / log_name).read_text()

    @staticmethod
    def stop(proc: subprocess.Popen, sig=signal.SIGKILL) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, sig)
            except ProcessLookupError:
                pass
            proc.wait(timeout=30)

    def stop_all(self) -> None:
        for proc in self.children:
            self.stop(proc)

    # ---- phases -------------------------------------------------------
    def phase(self, name: str, fn) -> bool:
        t0 = time.monotonic()
        try:
            if self.remaining() <= 0:
                raise PhaseFailed(f"no time left of the {DEADLINE_S:.0f} s budget")
            detail = fn() or {}
            ok, why = True, ""
        except PhaseFailed as exc:
            ok, why, detail = False, str(exc), {}
        except Exception as exc:  # a bug here must still stop the children
            ok, why, detail = False, f"{type(exc).__name__}: {exc}", {}
        finally:
            self.stop_all()
        secs = round(time.monotonic() - t0, 1)
        self.phases[name] = {"ok": ok, "seconds": secs, **detail}
        print(f"[{'PASS' if ok else 'FAIL'}] {name:14s} {secs:7.1f} s", flush=True)
        for k, v in detail.items():
            print(f"         {k}: {json.dumps(v)}", flush=True)
        if not ok:
            print(f"         why: {why}", flush=True)
        return ok

    def probe_device(self) -> dict:
        out = self.run(
            ["-c", "import jax, json; d = jax.devices(); print(json.dumps("
                   "{'platform': d[0].platform, 'kind': d[0].device_kind, "
                   "'count': len(d)}))"],
            "device.log", 120,
        )
        seen = last_json(out)
        # exactly the three keys of the result line, as JAX reported them
        self.device = {"platform": str(seen["platform"]), "kind": str(seen["kind"]),
                       "count": int(seen["count"])}
        if self.device["platform"] != self.mode["platform"]:
            raise PhaseFailed(
                f"platform is {self.device['platform']!r}, "
                f"not {self.mode['platform']!r}"
            )
        return dict(self.device)

    def write_model(self) -> dict:
        out = self.run(
            ["-m", "dnet_tpu.utils.random_init", "--out", str(self.model_dir),
             "--config", self.mode["config"], "--seed", "0"],
            "model.log", 300,
        )
        info = last_json(out)
        self.checkpoint_bytes = info["safetensors_bytes"]
        return {"written": info["written"], "bytes": self.checkpoint_bytes}

    def check_kernels(self) -> dict:
        argv = ["-m", "dnet_tpu.ops.kernel_check", "--model", str(self.model_dir),
                "--max-seq", str(self.mode["max_seq"])]
        if self.kernel_tolerance is not None:
            argv += ["--tolerance", str(self.kernel_tolerance)]
        try:
            out = self.run(argv, "kernels.log", 500)
        except PhaseFailed as exc:
            over = [l for l in (OUT / "kernels.log").read_text().splitlines()
                    if l.startswith('{"case"') and '"ok": false' in l]
            raise PhaseFailed("\n".join(over[:8] + [str(exc)])) from None
        summary = last_json(out)
        rows = [json.loads(l) for l in out.splitlines() if l.startswith('{"case"')]
        return {
            "cases": summary["cases"],
            "worst": max(rows, key=lambda r: r["max_err"] / max(r["tolerance"], 1e-30))["case"],
            "compiled_by": self.mode["kernel_impl"],
        }

    def serve(self, name: str, extra_args, extra_env, drive, used_kernels) -> dict:
        """Start dnet-api, wait for the model, drive it, check /health, drain."""
        import httpx

        port = free_port()
        base = f"http://127.0.0.1:{port}"
        proc = self.spawn(
            ["-m", "dnet_tpu.cli.api", "--model", str(self.model_dir),
             "--host", "127.0.0.1", "--http-port", str(port), *extra_args],
            f"{name}.log", extra_env,
        )
        t0 = time.monotonic()
        with httpx.Client(base_url=base, timeout=300) as http:
            while True:
                if proc.poll() is not None:
                    raise PhaseFailed(
                        f"server exited {proc.returncode} before ready; tail of "
                        f"{name}.log:\n{tail(name + '.log')}"
                    )
                if self.remaining() <= 0:
                    raise PhaseFailed(f"not ready in time; tail:\n{tail(name + '.log')}")
                try:
                    r = http.get("/health", timeout=5)
                    if r.status_code == 200 and r.json().get("model"):
                        break
                except httpx.TransportError:
                    pass
                time.sleep(1)
            detail = {"ready_s": round(time.monotonic() - t0, 1)}
            t1 = time.monotonic()
            detail.update(drive(http) or {})
            detail["requests_s"] = round(time.monotonic() - t1, 1)
            health = http.get("/health").json()
            detail.update(self.check_health(health, used_kernels))
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise PhaseFailed("SIGTERM did not drain the server in 90 s")
        if rc != 0:
            raise PhaseFailed(f"server exited {rc} after SIGTERM; tail:\n{tail(name + '.log')}")
        log_text = (OUT / f"{name}.log").read_text()
        detail["checkpoint_io"] = (
            "native host store" if "native host store serves" in log_text
            else "python safetensors"
        )
        return detail

    def check_health(self, health: dict, used_kernels) -> dict:
        dev, kernels = health.get("device"), health.get("kernels")
        if not dev or not kernels:
            raise PhaseFailed(f"/health lacks device/kernels blocks: {sorted(health)}")
        if not str(health.get("model") or "").endswith(self.model_dir.name):
            raise PhaseFailed(f"/health names model {health.get('model')!r}")
        if dev["platform"] != self.mode["platform"]:
            raise PhaseFailed(f"server runs on {dev['platform']!r}")
        want = self.mode["kernel_impl"]
        if want == "pallas":
            # on the chip nothing but the Mosaic-compiled kernel may have run
            stray = {k: {i: v[i] for i in ("interpret", "emulate") if v[i]}
                     for k, v in kernels.items()}
            stray = {k: v for k, v in stray.items() if v}
            if stray:
                raise PhaseFailed(f"kernels resolved off the chip path: {stray}")
            in_use = sum(d["bytes_in_use"] for d in dev["devices"])
            if in_use < self.checkpoint_bytes:
                raise PhaseFailed(
                    f"devices hold {in_use} bytes, less than the checkpoint's "
                    f"{self.checkpoint_bytes}"
                )
        # off the chip, inside shard_map, the kernels' stand-in is `emulate`
        ran = {k: kernels[k][want] + (kernels[k]["emulate"] if want != "pallas" else 0)
               for k in used_kernels}
        missing = [k for k, n in ran.items() if n == 0]
        if missing:
            raise PhaseFailed(f"{want} never selected for {missing}: {kernels}")
        dense = {k: v["dense_shapes"] for k, v in kernels.items() if v["dense_shapes"]}
        return {
            "kernels": {k: {i: v[i] for i in ("pallas", "interpret", "emulate", "dense")}
                        for k, v in kernels.items() if any(
                            v[i] for i in ("pallas", "interpret", "emulate", "dense"))},
            "dense_shapes": dense,
            "bytes_in_use": [d["bytes_in_use"] for d in dev["devices"]],
        }

    # ---- traffic ------------------------------------------------------
    def chat(self, http, content: str, max_tokens: int, **kw) -> dict:
        r = http.post("/v1/chat/completions", json={
            "model": self.model_dir.name, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content": content}], **kw,
        })
        if r.status_code != 200:
            raise PhaseFailed(f"HTTP {r.status_code}: {r.text[:300]}")
        body = r.json()
        choice = body["choices"][0]
        if body["usage"]["completion_tokens"] < 1 or not choice.get("finish_reason"):
            raise PhaseFailed(f"empty completion: {json.dumps(body)[:300]}")
        return body

    def chat_stream(self, http, content: str, max_tokens: int, **kw) -> dict:
        """One SSE completion; returns {chunks, completion_tokens, finish_reason}."""
        chunks, finish, usage, done = 0, None, None, False
        with http.stream("POST", "/v1/chat/completions", json={
            "model": self.model_dir.name, "max_tokens": max_tokens, "stream": True,
            "messages": [{"role": "user", "content": content}], **kw,
        }) as r:
            if r.status_code != 200:
                raise PhaseFailed(f"HTTP {r.status_code}: {r.read()[:300]!r}")
            for line in r.iter_lines():
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    done = True
                    break
                chunk = json.loads(data)
                if chunk.get("error"):
                    raise PhaseFailed(f"stream error: {data[:300]}")
                chunks += 1
                for choice in chunk.get("choices") or []:
                    finish = choice.get("finish_reason") or finish
                usage = chunk.get("usage") or usage
        tokens = (usage or {}).get("completion_tokens", 0)
        if not done or not finish or tokens < 1:
            raise PhaseFailed(
                f"stream ended done={done} finish={finish} completion_tokens={tokens}"
            )
        return {"chunks": chunks, "completion_tokens": tokens,
                "prompt_tokens": usage["prompt_tokens"], "finish_reason": finish}

    def drive_default(self, http) -> dict:
        first = self.chat(http, "Name three uses of a systolic array.", 16, temperature=0.0)
        again = self.chat(http, "Name three uses of a systolic array.", 16, temperature=0.0)
        a, b = (x["choices"][0]["message"]["content"] for x in (first, again))
        if a != b or first["usage"] != again["usage"]:
            raise PhaseFailed(f"greedy completion not reproducible: {a!r} vs {b!r}")
        long = self.chat_stream(
            http, filler(self.mode["long_prompt_bytes"], seed=1), 24, temperature=0.0
        )
        sampled = self.chat(http, "Pick a number.", 16, temperature=0.8, seed=1234)
        return {
            "greedy_tokens": first["usage"]["completion_tokens"],
            "long_stream": long,
            "sampled_tokens": sampled["usage"]["completion_tokens"],
        }

    def drive_serve(self, http) -> dict:
        detail = self.drive_default(http)
        detail.update(self.drive_streams(http))
        return detail

    def drive_streams(self, http) -> dict:
        rng = random.Random(0)
        lo, hi = self.mode["sched_prompt_bytes"]
        jobs = [(filler(rng.randint(lo, hi), seed=i), rng.randint(16, 64))
                for i in range(8)]
        results: list = [None] * len(jobs)

        def one(i: int) -> None:
            try:
                results[i] = self.chat_stream(http, jobs[i][0], jobs[i][1], temperature=0.0)
            except Exception as exc:
                results[i] = exc

        threads = [threading.Thread(target=one, args=(i,)) for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(self.remaining(), 1))
        bad = [f"stream {i}: {r}" for i, r in enumerate(results)
               if not isinstance(r, dict)]
        if bad:
            raise PhaseFailed("; ".join(bad)[:600])
        gauges = {}
        for line in http.get("/metrics").text.splitlines():
            name, _, value = line.rpartition(" ")
            name = name.partition("{")[0]  # one series a kind of layer: summed
            if name in ("dnet_kv_blocks_used", "dnet_kv_blocks_free", "dnet_kv_pool_blocks"):
                key = name[len("dnet_kv_"):]
                gauges[key] = gauges.get(key, 0) + int(float(value))
        if (len(gauges) != 3 or gauges["pool_blocks"] <= 0
                or gauges["blocks_used"] + gauges["blocks_free"] != gauges["pool_blocks"]):
            raise PhaseFailed(f"block pool does not balance: {gauges}")
        return {
            "streams": len(jobs),
            "completion_tokens": sum(r["completion_tokens"] for r in results),
            "prompt_tokens": sum(r["prompt_tokens"] for r in results),
            "kv_pool": gauges,
        }

    def mesh4(self) -> dict:
        detail = self.serve(
            "mesh4", ["--mesh", "pp=2,tp=2"], None, self.drive_default,
            ("flash_prefill", "flash_decode"),
        )
        if any(b <= 0 for b in detail["bytes_in_use"][:4]) and self.mode["platform"] == "tpu":
            raise PhaseFailed(f"a device holds no weights: {detail['bytes_in_use']}")
        out = self.run(
            ["-m", "dnet_tpu.parallel.engine_check", "--model", str(self.model_dir),
             "--mesh", "pp=2,tp=2", "--max-seq", str(self.mode["max_seq"])],
            "mesh4-logits.log", 400,
        )
        detail["logits"] = last_json(out)
        return detail


def tail(log_name: str, lines: int = 25) -> str:
    try:
        text = (OUT / log_name).read_text(errors="replace")
    except OSError as exc:
        return f"<{exc}>"
    return "\n".join("    " + l[:300] for l in text.splitlines()[-lines:])


def last_json(text: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise PhaseFailed(f"child printed no JSON line: {text[-300:]!r}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def filler(n_bytes: int, seed: int) -> str:
    """Seeded ASCII prose of exactly n_bytes (ByteTokenizer: one token each)."""
    words = ("tile", "lane", "mesh", "ring", "shard", "block", "cache", "token",
             "head", "page", "chip", "core", "queue", "batch", "slot", "step")
    rng = random.Random(seed)
    out = ""
    while len(out) < n_bytes:
        out += rng.choice(words) + " "
    return out[:n_bytes]


def cache_report(env: dict) -> dict:
    path = Path(env.get("JAX_COMPILATION_CACHE_DIR") or HERE / ".jax_cache")
    files = [p for p in path.iterdir() if p.is_file()] if path.is_dir() else []
    return {"dir": str(path), "entries": len(files),
            "bytes": sum(p.stat().st_size for p in files)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse", action="store_true",
                   help="toy checkpoint on the CPU with interpreted kernels: "
                        "debugs this command, proves nothing about the chip")
    p.add_argument("--kernel-tolerance", type=float, default=None,
                   help="override the kernels phase's tolerance (0 forces it to fail)")
    p.add_argument("--model-dir", default="",
                   help="checkpoint directory (default: a fixed one under .chip_smoke/)")
    p.add_argument("--only", default="",
                   help="comma-separated phases to run after device and model "
                        "(e.g. mesh4, to spend four chips on that phase alone)")
    args = p.parse_args(argv)

    if not (HERE / "dnet_tpu" / "__init__.py").is_file():
        print(f"chip_smoke.py: no dnet_tpu package beside {HERE / 'chip_smoke.py'}; "
              "it drives the checkout it sits in", file=sys.stderr)
        return 2
    OUT.mkdir(parents=True, exist_ok=True)
    mode = REHEARSAL if args.rehearse else CHIP
    smoke = Smoke(mode, args.kernel_tolerance, args.model_dir)
    if args.rehearse:
        print(REHEARSAL_BANNER, flush=True)
    try:
        if not smoke.phase("device", smoke.probe_device):
            # no accelerator (or not the one this mode names): no result
            print("chip_smoke.py: JAX found no usable accelerator; nothing was "
                  "served", file=sys.stderr)
            return 1
        steps = [
            ("model", smoke.write_model),
            ("kernels", smoke.check_kernels),
            # flash_decode (the dense cache's kernel) is the kernels
            # phase's and mesh4's; a plain load decodes through paged_attend
            ("serve", lambda: smoke.serve(
                "serve", [], None, smoke.drive_serve,
                ("flash_prefill", "paged_attend"))),
        ]
        if smoke.device["count"] >= 4:
            steps.append(("mesh4", smoke.mesh4))
        only = {s for s in args.only.split(",") if s}
        unknown = only - {name for name, _ in steps}
        if unknown:
            print(f"chip_smoke.py: --only names no phase here: {sorted(unknown)}",
                  file=sys.stderr)
            return 2
        ok = True
        for name, fn in steps:
            if only and name != "model" and name not in only:
                continue
            ok = smoke.phase(name, fn) and ok
            if name == "model" and not ok:
                break  # nothing to serve
    finally:
        smoke.stop_all()
    cache = cache_report(smoke.env)
    total = round(time.monotonic() - smoke.t_start, 1)
    print(f"compile cache: {cache['dir']} ({cache['entries']} entries, "
          f"{cache['bytes'] >> 20} MiB); "
          f"total {total} s; logs in {OUT}", flush=True)
    failed = [n for n, ph in smoke.phases.items() if not ph["ok"]]
    if failed:
        print(f"FAILED: {', '.join(failed)}", flush=True)
    summary = json.dumps({
        "ok": ok, "rehearsal": args.rehearse, "device": smoke.device,
        "phases": smoke.phases, "compile_cache": cache, "seconds": total,
        "claim": None,
    })
    (OUT / "summary.json").write_text(summary + "\n")
    print(summary, flush=True)
    # the result line: these keys and no others (a rehearsal's says "cpu" and
    # sits between its banners)
    print(json.dumps({"ok": ok, "device": smoke.device}), flush=True)
    if args.rehearse:
        print(REHEARSAL_BANNER, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
