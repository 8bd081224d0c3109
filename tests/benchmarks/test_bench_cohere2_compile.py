"""Ahead-of-time v5e compiles of the attention kernels of
`cmdaplus-mixedlen-sat` at its real head shapes: 8 KV heads x 16 query
heads each, 128 wide, window 4096, 128-token blocks, 16 slots.  Mosaic runs
here without a chip; a compile that passes proves the kernel is accepted
(tiling, fast memory), not that it is right or fast.

The topology is described inside a fixture (on-chip-measurement guide,
section 2).  `test_bench_v5e_compile.py`, which may not be edited, does the
same: where the two files land on different workers and only one process
may load the TPU library, this file's tests skip.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compile_for(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    return compiled.as_text()


BF, I32 = jnp.bfloat16, jnp.int32
H, KVH, Hd, BT, SLOTS = 128, 8, 128, 128, 16


@pytest.mark.parametrize(
    "window,layers,blocks,table,name",
    [
        (4096, 3, 16 * 35, 35, "paged_attend_window"),  # the window kind: its static width
        (0, 1, 1408, 129, "paged_attend"),  # the full kind at 16k, the fused chunks' width
        (0, 1, 1408, 16, "paged_attend"),  # ... and at a bucket of a single step
    ],
)
def test_paged_attention_by_kind(one_chip, no_cache, window, layers, blocks, table, name):
    from dnet_tpu.ops.paged_attention import _paged_pallas

    fn = functools.partial(_paged_pallas, G=H // KVH, scale=Hd**-0.5, bt=BT,
                           interpret=False, window=window)
    pool = ((layers, blocks, BT, KVH * Hd), BF)  # a kind's stack, heads merged
    text = compile_for(
        fn, one_chip,
        ((SLOTS, 1, H, Hd), BF), pool, pool, ((SLOTS, table), I32), ((SLOTS,), I32),
        ((SLOTS, KVH, Hd), BF), ((SLOTS, KVH, Hd), BF), ((SLOTS,), I32), ((1,), I32),
    )
    assert "tpu_custom_call" in text and f"{name}" in text
    if not window:
        assert "paged_attend_window" not in text  # the trace tells the kinds apart by name


@pytest.mark.parametrize("window", [4096, 0])
@pytest.mark.parametrize("T", [256, 32])
def test_flash_prefill_by_kind(one_chip, no_cache, window, T):
    """A 256-token chunk (and a last chunk's bucket) against the 16512-slot
    staging row, 128 query heads: 16 heads a grid step, not all 128."""
    from dnet_tpu.ops.flash_attention import _flash_pallas, _heads_per_step, _pick_tile

    S = 16512
    assert _heads_per_step(KVH, H // KVH, Hd, Hd) == 2  # 32 query heads a step
    assert _heads_per_step(4, 8, 128, 128) == 4  # qwen3-30b-a3b: every head, as before
    fn = functools.partial(
        _flash_pallas, G=H // KVH, scale=Hd**-0.5, bq=_pick_tile(T, 128),
        bk=_pick_tile(S, 128), interpret=False, window=window,
    )
    text = compile_for(
        fn, one_chip,
        ((1, T, H, Hd), BF), ((1, S, KVH, Hd), BF), ((1, S, KVH, Hd), BF),
        ((1,), I32), ((H,), jnp.float32),
    )
    assert "tpu_custom_call" in text
    assert ("flash_prefill_window" in text) == bool(window)
