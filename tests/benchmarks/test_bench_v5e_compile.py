"""Ahead-of-time v5e compiles of the kernels the two configurations' cells
run, at their real head shapes.  Mosaic runs here without a chip; a compile
that passes proves the kernel is accepted, not that it is right or fast.

ONE file, topology inside a fixture (on-chip-measurement guide, section 2):
only the worker given this file loads the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compile_for(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF = jnp.bfloat16


@pytest.mark.parametrize("slots,table_blocks", [(32, 8), (32, 64), (32, 256)])
def test_paged_attention_qwen3_30b_a3b(one_chip, no_cache, slots, table_blocks):
    """32 query / 4 KV heads of 128; 16-token blocks; the 32-slot pool."""
    from dnet_tpu.ops.paged_attention import _paged_pallas

    H, KVH, Hd, bt, N = 32, 4, 128, 16, 8192
    fn = functools.partial(_paged_pallas, G=H // KVH, scale=Hd**-0.5, bt=bt, interpret=False)
    compile_for(
        fn, one_chip,
        ((slots, 1, H, Hd), BF), ((N, bt, KVH, Hd), BF), ((N, bt, KVH, Hd), BF),
        ((slots, table_blocks), jnp.int32), ((slots,), jnp.int32),
        ((slots, KVH, Hd), BF), ((slots, KVH, Hd), BF),
    )


@pytest.mark.parametrize(
    "name,T,S,H,KVH,Hd,Vd",
    [
        ("qwen3-chunk256", 256, 4096, 32, 4, 128, 128),
        ("qwen3-chunk64", 64, 4096, 32, 4, 128, 128),
    ],
)
def test_flash_prefill(one_chip, no_cache, name, T, S, H, KVH, Hd, Vd):
    from dnet_tpu.ops.flash_attention import _flash_pallas, _pick_tile

    fn = functools.partial(
        _flash_pallas, G=H // KVH, scale=Hd**-0.5, bq=_pick_tile(T, 128),
        bk=_pick_tile(S, 128), interpret=False,
    )
    compile_for(
        fn, one_chip,
        ((1, T, H, Hd), BF), ((1, S, KVH, Hd), BF), ((1, S, KVH, Vd), BF),
        ((1,), jnp.int32), ((H,), jnp.float32),
    )
