"""The check's lower readings for a state-kind configuration: `python -m
benchmarks.precision_control_brumby --workload brumby-longgen-sat --seed <n>
--prompts <k>`, from the root of a checkout.  `precision_control.py`'s
method (the harness's own comparison, unedited, on the run's own weights and
the real server), with the two precisions that lie below what the
configuration serves:

- `int8`: the reference reads the checkpoint's matrices rounded to int8 a
  row and back (`precision_control.int8_rows`, as for the other cells; here
  16384 rows at a time: the 151936 x 5120 embedding in float32 does not fit
  on the chip beside this server);
- `bf16_state`: the reference computes each layer's retention as the
  RECURRENCE over a state that is rounded to bfloat16 after every token
  (state and normaliser; the configuration states float32), everything else
  float32.  The state here is the plain outer square `k k^T / sqrt(Hd)` times
  `v`, `[Hd, Hd, Hd]` a KV head: the symmetric layout's entries, each with
  the same relative rounding.  Nothing of `dnet_tpu` computes it.

One JSON line a prompt, then a last line with the extremes and `separates`:
every sound comparison ok and every lower one NOT ok.  Exit code 0 only
then.  `--rehearse` runs it on the CPU at the config's tiny sizes, where the
limits are loose and `separates` is not expected: it proves the script.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import shutil
import signal
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

from benchmarks import run as bench_run
from benchmarks.precision_control import int8_rows


def int8_rows_blocked(a, rows: int = 16384):
    import numpy as np

    if a.ndim != 2 or a.shape[0] <= rows:
        return int8_rows(a)
    return np.concatenate([int8_rows(a[r0:r0 + rows]) for r0 in range(0, a.shape[0], rows)])


@contextlib.contextmanager
def int8_reference(ref):
    """While open, the reference module `ref` reads int8-rounded matrices."""
    plain = ref.Tensors

    class Int8Tensors(plain):
        def get(self, name):
            return int8_rows_blocked(super().get(name))

        def layer(self, i):
            return {k: int8_rows_blocked(v) for k, v in super().layer(i).items()}

    ref.Tensors = Int8Tensors
    try:
        yield
    finally:
        ref.Tensors = plain


def power_retention_bf16_state(q, k, v, log_g):
    """`reference/brumby.py: power_retention`'s function as a recurrence whose
    state is kept in bfloat16: q [T, G, Hd], k/v [T, Hd], log_g [T]."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.brumby import EPS

    T, G, Hd = q.shape
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def step(carry, x):
        S, z = carry
        q_t, k_t, v_t, lg = x
        kk = jnp.outer(k_t, k_t) * Hd**-0.5
        g = jnp.exp(lg)
        S = bf(g * S + kk[:, :, None] * v_t[None, None, :])
        z = bf(g * z + kk)
        qq = jnp.einsum("ga,gb->gab", q_t, q_t) * Hd**-0.5
        num = jnp.einsum("gab,abc->gc", qq, S)
        den = jnp.einsum("gab,ab->g", qq, z)
        return (S, z), num / (den[:, None] + EPS)

    init = (jnp.zeros((Hd, Hd, Hd), jnp.float32), jnp.zeros((Hd, Hd), jnp.float32))
    return jax.lax.scan(step, init, (q, k, v, log_g))[1]


@contextlib.contextmanager
def bf16_state_reference(ref):
    """While open, the reference module `ref` keeps a bfloat16 state."""
    plain = ref.power_retention
    ref.power_retention = power_retention_bf16_state
    try:
        yield
    finally:
        ref.power_retention = plain


CONTROLS = {"int8": int8_reference, "bf16_state": bf16_state_reference}


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
            row = {"weights_seed": args.seed, "prompt_seed": prompt_seed}
            row["sound"] = await check.compare(url, model, model_dir, cfg, chk, prompt_seed)
            for name, lower in CONTROLS.items():
                with lower(ref):
                    row[name] = await check.compare(url, model, model_dir, cfg, chk, prompt_seed)
            bench_run.say("control:", json.dumps(row))
            rows.append(row)
    finally:
        # the server's own graceful path, as benchmarks/run.py takes it
        if not server.done():
            os.kill(os.getpid(), signal.SIGTERM)
            with contextlib.suppress(asyncio.TimeoutError, Exception):
                await asyncio.wait_for(server, 20)
        shutil.rmtree(tmp, ignore_errors=True)
    out = {
        "workload": cell.name,
        "weights_seed": args.seed,
        "prompts": len(rows),
        "mean_tolerance": rows[0]["sound"]["mean_tolerance"],
        "tolerance": rows[0]["sound"]["tolerance"],
        "sound_mean_err_max": max(r["sound"]["mean_err"] for r in rows),
        "sound_max_err_max": max(r["sound"]["max_err"] for r in rows),
    }
    for name in CONTROLS:
        out[f"{name}_mean_err_min"] = min(r[name]["mean_err"] for r in rows)
        out[f"{name}_max_err_min"] = min(r[name]["max_err"] for r in rows)
    out["separates"] = all(
        r["sound"]["ok"] and not any(r[name]["ok"] for name in CONTROLS) for r in rows
    )
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.precision_control_brumby")
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
