"""The check's lower reading: `python -m benchmarks.precision_control
--workload <name> --seed <n> --prompts <k>`, from the root of a checkout.

`harness/check.py: compare` decides `correct` from two limits.  Where a
limit lies is set from two readings (PERF.md): the largest a sound run gives,
and what the comparison gives when the reference is computed in the nearest
precision BELOW the configuration's, which has to come out not correct.  This
script takes both readings with the harness's own comparison, unedited: it
makes the run's weights from `--seed`, starts the real server on them as
`benchmarks/run.py` does, and for each of `--prompts` check prompts calls
`compare` twice: against the plain reference, and against the same reference
reading the checkpoint's matrices rounded to int8 a row (symmetric, the row's
largest magnitude / 127) and back.  bfloat16 is what the configurations
serve; int8 weights are the next precision down the program can serve
(`weight_quant_bits` 8).

One JSON line a prompt, then a last line with the extremes and `separates`:
every sound comparison ok and every int8 comparison NOT ok.  Exit code 0
only then.  `--rehearse` runs it on the CPU at the config's tiny sizes, where
the limits are loose and `separates` is not expected: it proves the script.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import functools
import json
import os
import shutil
import signal
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

from benchmarks import run as bench_run


@functools.lru_cache(maxsize=None)
def _roundtrip():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def roundtrip(w):
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
        q = jnp.clip(jnp.round(w / jnp.where(scale > 0, scale, 1.0)), -127, 127)
        return q * scale

    return roundtrip


def int8_rows(a):
    """A matrix (or a stack of them) rounded to int8 a row and back, in
    float32 on the host; vectors (norm weights) are left as they are."""
    import numpy as np

    if a.ndim < 2:
        return a
    if a.ndim == 2:
        return np.asarray(_roundtrip()(a))
    return np.stack([np.asarray(_roundtrip()(m)) for m in a])  # an expert at a time


@contextlib.contextmanager
def int8_reference(ref):
    """While open, the reference module `ref` reads int8-rounded matrices."""
    plain = ref.Tensors

    class Int8Tensors(plain):
        def get(self, name):
            return int8_rows(super().get(name))

        def layer(self, i):
            return {k: int8_rows(v) for k, v in super().layer(i).items()}

    ref.Tensors = Int8Tensors
    try:
        yield
    finally:
        ref.Tensors = plain


async def control(args, cell) -> dict:
    import aiohttp

    from benchmarks.harness import check
    from benchmarks.harness.weights import reference_module, write_checkpoint
    from dnet_tpu.api.server import serve_async

    cfg = bench_run.hf_config(cell.config, args.rehearse)
    serve = cell.config["serve"]
    chk = dict(cell.config["check"])
    if args.rehearse:
        chk.update(cell.config["rehearse"].get("check", {}))
    tmp = Path(tempfile.mkdtemp(prefix="dnet-bench-control-"))
    model_dir = tmp / cell.config_name
    write_checkpoint(model_dir, cfg, args.seed, serve.get("dtype", "bfloat16"))
    port = bench_run.free_port()
    url = f"http://127.0.0.1:{port}"
    server = asyncio.ensure_future(
        serve_async(
            SimpleNamespace(
                host="127.0.0.1", http_port=port, grpc_port=bench_run.free_port(),
                hostfile="", model=str(model_dir), models_dir="",
                mesh=serve.get("mesh", ""), discovery="none", tui=False,
                weight_quant_bits=None, auto_recover=False, batch_slots=None,
            )
        )
    )
    ref = reference_module(cfg["model_type"])
    rows = []
    try:
        async with aiohttp.ClientSession() as session:
            while True:
                if server.done():
                    server.result()
                    raise RuntimeError("the server stopped before it was ready")
                try:
                    health = await bench_run.http_json(session, url + "/health")
                    if health.get("model"):
                        break
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.25)
        model = health["model"]
        for j in range(args.prompts):
            prompt_seed = args.seed + 7919 * j  # j = 0: the run's own check prompt
            sound = await check.compare(url, model, model_dir, cfg, chk, prompt_seed)
            with int8_reference(ref):
                int8 = await check.compare(url, model, model_dir, cfg, chk, prompt_seed)
            row = {"weights_seed": args.seed, "prompt_seed": prompt_seed,
                   "bf16": sound, "int8": int8}
            bench_run.say("control:", json.dumps(row))
            rows.append(row)
    finally:
        # the server's own graceful path, as benchmarks/run.py takes it
        if not server.done():
            os.kill(os.getpid(), signal.SIGTERM)
            with contextlib.suppress(asyncio.TimeoutError, Exception):
                await asyncio.wait_for(server, 20)
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "workload": cell.name,
        "weights_seed": args.seed,
        "prompts": len(rows),
        "mean_tolerance": rows[0]["bf16"]["mean_tolerance"],
        "tolerance": rows[0]["bf16"]["tolerance"],
        "bf16_mean_err_max": max(r["bf16"]["mean_err"] for r in rows),
        "bf16_max_err_max": max(r["bf16"]["max_err"] for r in rows),
        "int8_mean_err_min": min(r["int8"]["mean_err"] for r in rows),
        "int8_max_err_min": min(r["int8"]["max_err"] for r in rows),
        "separates": all(r["bf16"]["ok"] and not r["int8"]["ok"] for r in rows),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.precision_control")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompts", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    from benchmarks.harness import spec

    cell = spec.resolve_cell(args.workload)
    bench_run.prepare_environment(cell, args.rehearse)
    jax = bench_run.configure_jax()
    bench_run.require_devices(jax, cell, args.rehearse)
    out = asyncio.run(control(args, cell))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    # the chip's runtime can hang in teardown; everything is already stopped
    os._exit(0 if out["separates"] else 1)


if __name__ == "__main__":
    main()
