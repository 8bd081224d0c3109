"""Test scaffold: force an 8-device virtual CPU mesh before jax imports.

All unit/subsystem tests run on CPU with 8 virtual devices so multi-chip
sharding (pp/tp/dp/sp over a Mesh) is exercised without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Persistent compilation cache: the suite's dominant cost is XLA compiles
# (hundreds of tiny programs, recompiled identically every run).  With the
# cache warm, repeat runs skip nearly all of them; CI restores the directory
# between jobs (.github/workflows/ci.yml).  Same rule as
# dnet_tpu.config.configure_compile_cache: JAX_COMPILATION_CACHE_DIR wins
# (JAX reads it), else a fixed directory.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), ".jax_cache"),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

import contextlib  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--real-model",
        default="",
        help="HF repo id for the real-checkpoint integration test "
        "(tests/integration/test_real_model.py); requires network",
    )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def tiny_llama_dir(tmp_path_factory):
    """Session-scoped tiny random-weight Llama checkpoint."""
    from tests.fakes.checkpoints import make_tiny_llama

    d = tmp_path_factory.mktemp("tiny_llama")
    make_tiny_llama(d)
    return d


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@contextlib.contextmanager
def spawn_api_server(model_dir, env=None, ready_timeout_s: int = 180):
    """Spawn a real `dnet_tpu.cli.api` subprocess serving `model_dir` and
    yield its base URL once the preloaded model is serveable (/health turns
    200 before the startup load completes, so readiness requires the model
    field).  Shared by the integration/compat tiers — one place for the
    port pick, readiness protocol, and kill-falls-back teardown."""
    import socket
    import subprocess
    import sys
    import time

    import httpx

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dnet_tpu.cli.api",
            "--model", str(model_dir), "--http-port", str(port),
        ],
        env={
            "JAX_PLATFORMS": "cpu",
            "DNET_API_MAX_SEQ_LEN": "128",
            **os.environ,
            **(env or {}),
        },
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    base = f"http://127.0.0.1:{port}"
    try:
        for _ in range(ready_timeout_s):
            try:
                r = httpx.get(base + "/health", timeout=2)
                if r.status_code == 200 and r.json().get("model"):
                    break
            except Exception:
                pass
            time.sleep(1)
        else:
            raise RuntimeError("server did not become ready with a model")
        yield base
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
