"""What the first run on the chip established, kept true on the CPU.

The compile cache can be placed from outside, `/health` says which device
and which kernel implementations a process runs, nothing on a TPU backend
may fall back to interpret mode, a failed local preload is fatal, the
benchmark knows no default chip, and `chip_smoke.py` refuses to produce a
result without a chip (the two subprocess-heavy checks are marked slow).
"""

import asyncio
import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent


# ---- compile cache --------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the suite's
    own cache placement (conftest) must survive these tests."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_env_var_wins_and_option_is_untouched(
    monkeypatch, config_updates
):
    from dnet_tpu.config import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert configure_compile_cache() == "/some/dir"
    assert config_updates == []


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
    monkeypatch, config_updates
):
    from dnet_tpu.config import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = configure_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert configure_compile_cache() == first  # never a temp name or a pid
    assert config_updates == [("jax_compilation_cache_dir", first)] * 2


# ---- kernel selection -----------------------------------------------------

def test_interpret_override_is_an_error_on_a_tpu_backend(monkeypatch):
    from dnet_tpu.ops import kernel_select

    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    assert kernel_select.kernel_backend() == "interpret"  # the CPU test override
    monkeypatch.setattr(kernel_select, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="DNET_FLASH_INTERPRET"):
        kernel_select.kernel_backend()
    monkeypatch.delenv("DNET_FLASH_INTERPRET")
    assert kernel_select.kernel_backend() == "pallas"
    # and there the paged dispatcher cannot name the jnp twin
    from dnet_tpu.ops.paged_attention import paged_attend_impl

    assert paged_attend_impl() == "pallas"


def test_dispatchers_book_what_they_selected(monkeypatch, rng):
    import jax.numpy as jnp

    from dnet_tpu.ops.flash_attention import flash_attend_causal
    from dnet_tpu.ops.kernel_select import KERNELS, SELECTIONS

    SELECTIONS.reset()
    q = jnp.asarray(rng.normal(size=(1, 16, 4, 16)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    monkeypatch.delenv("DNET_FLASH_INTERPRET", raising=False)
    flash_attend_causal(q, kv, kv, 0)  # CPU, no override: the dense op
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    flash_attend_causal(q, kv, kv, 0)
    flash_attend_causal(q[:, :1], kv, kv, 5)  # T=1 routes to the decode kernel
    snap = SELECTIONS.snapshot()
    assert set(snap) == set(KERNELS)
    assert snap["flash_prefill"]["dense"] == 1
    assert snap["flash_prefill"]["dense_shapes"] == [[[1, 16, 4, 16], [1, 32, 2, 16]]]
    assert snap["flash_prefill"]["interpret"] == 1
    assert snap["flash_decode"]["interpret"] == 1
    assert snap["paged_attend"] == {
        "pallas": 0, "interpret": 0, "emulate": 0, "dense": 0, "dense_shapes": [],
    }


# ---- /health --------------------------------------------------------------

def _assert_device_and_kernels(body: dict) -> None:
    from dnet_tpu.ops.kernel_select import IMPLS, KERNELS

    dev = body["device"]
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.devices())
    assert dev["device_kind"] == jax.devices()[0].device_kind
    assert [d["id"] for d in dev["devices"]] == [d.id for d in jax.devices()]
    assert all(d["bytes_in_use"] >= 0 for d in dev["devices"])
    assert set(body["kernels"]) == set(KERNELS)
    for counts in body["kernels"].values():
        assert set(counts) == set(IMPLS) | {"dense_shapes"}


def test_api_health_names_device_and_kernels():
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager

    async def go():
        inference = InferenceManager(adapter=None, request_timeout_s=30.0)
        manager = LocalModelManager(inference, max_seq=64, param_dtype="float32")
        client = TestClient(TestServer(ApiHTTPServer(inference, manager).app))
        await client.start_server()
        try:
            return await (await client.get("/health")).json()
        finally:
            await client.close()

    _assert_device_and_kernels(asyncio.run(go()))


def test_shard_health_names_device_and_kernels():
    from dnet_tpu.shard.http import ShardHTTPServer
    from dnet_tpu.shard.runtime import ShardRuntime

    class _Shard:
        runtime = ShardRuntime("s0")

    body = json.loads(asyncio.run(ShardHTTPServer(_Shard()).health(None)).text)
    _assert_device_and_kernels(body)


# ---- entry points ---------------------------------------------------------

def test_local_preload_failure_exits_nonzero(tmp_path):
    """A local-mode `--model` that cannot load must not leave a server up
    that would later exit 0 (ring mode keeps serving without a model)."""
    proc = subprocess.run(
        [sys.executable, "-m", "dnet_tpu.cli.api", "--model",
         str(tmp_path / "no-such-checkpoint"), "--host", "127.0.0.1",
         "--http-port", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "preload of" in proc.stderr + proc.stdout


# ---- chip_smoke.py --------------------------------------------------------

def _smoke(*args, env=None, timeout=900):
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env={"PATH": "/usr/bin:/bin", **(env or {})},
    )


def test_chip_smoke_imports_neither_jax_nor_the_package():
    import ast

    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"jax", "jaxlib", "dnet_tpu", "numpy"}, imported


@pytest.mark.slow
def test_chip_smoke_without_a_chip_serves_nothing_and_fails(tmp_path):
    """Whatever JAX_PLATFORMS says: the children are held to the TPU."""
    try:
        jax.devices("tpu")
        pytest.skip("this machine has a TPU")
    except RuntimeError:
        pass
    for platforms in ("cpu", ""):
        proc = _smoke(env={"JAX_PLATFORMS": platforms}, timeout=300)
        assert proc.returncode != 0
        assert "[FAIL] device" in proc.stdout
        assert "] serve" not in proc.stdout  # no request was served
        assert not proc.stdout.rstrip().splitlines()[-1].startswith("{")  # no result
    # and alone, without the program beside it, it fails too
    lonely = tmp_path / "chip_smoke.py"
    lonely.write_text((REPO / "chip_smoke.py").read_text())
    proc = subprocess.run(
        [sys.executable, str(lonely)], cwd=tmp_path, capture_output=True,
        text=True, timeout=60, env={"PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0 and not proc.stdout.strip()


@pytest.mark.slow
def test_chip_smoke_rehearsal_end_to_end():
    proc = _smoke("--rehearse")
    lines = proc.stdout.rstrip().splitlines()
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert lines[0] == lines[-1] == "REHEARSAL (cpu) — not a chip result"
    # the result line holds exactly the contract's keys; the summary precedes it
    assert json.loads(lines[-2]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 4},
    }
    summary = json.loads(lines[-3])
    assert summary["ok"] and summary["rehearsal"] and summary["claim"] is None
    assert summary["device"]["platform"] == "cpu"
    assert list(summary["phases"]) == [
        "device", "model", "kernels", "serve", "mesh4",
    ]
    assert all(ph["ok"] for ph in summary["phases"].values())
    # a phase forced to fail makes the exit code non-zero
    forced = _smoke("--rehearse", "--only", "kernels", "--kernel-tolerance", "0")
    assert forced.returncode != 0 and "[FAIL] kernels" in forced.stdout
    forced = _smoke("--rehearse", "--only", "kernels", "--model-dir", "/dev/null/ckpt")
    assert forced.returncode != 0 and "[FAIL] model" in forced.stdout
