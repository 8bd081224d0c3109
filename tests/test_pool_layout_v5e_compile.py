"""The pool's layout, held to what the v5e compiler makes of it: the
programs that write and read a block pool, at the geometry of the cells
that run them, compiled ahead of time for the chip, must hold NO `copy`
whose result is as large as a pool leaf.

A pool kept with the heads apart, `[L, N, bt, 4, 128]`, is tiled T(4,128)
and copied WHOLE to T(8,128) and back around every scatter (four 805 MB
copies a prompt in the rag cell, 10 % of its busy time), and a row write
indexed `p[:, block, row]` relayouts the pool around itself (PR 29's
finding in the mix cell); `[L, N, bt, KVH*Hd]`, taken by layer index and
written through the flat view, needs neither.  A compile that passes
proves the layout is accepted as it is, not that the programs are right:
tests/test_paged_kv.py and tests/test_kv_kinds.py hold them to the dense
path.

ONE file, topology inside a fixture (on-chip-measurement guide, section 2):
only the worker given this file loads the TPU library.
"""

import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dnet_tpu.kv import KindStore, PagedKVConfig
from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_WINDOW, KV_KINDS

BF = jnp.bfloat16


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
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _abstract(args, one_chip, pool_at=None, pool_blocks=0):
    """A call's arguments as shapes on the described chip; the pool, the
    argument at `pool_at`, grows to `pool_blocks` blocks."""

    def one(a, blocks=0):
        shape = tuple(np.shape(a))
        if blocks:
            shape = (shape[0], blocks) + shape[2:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    out = list(jax.tree.map(one, tuple(args)))
    if pool_at is not None:
        out[pool_at] = jax.tree.map(lambda a: one(a, pool_blocks), args[pool_at])
    return out


def pool_sized_copies(program, args, leaf_elems: int, must_hold: str = ""):
    """The compiled program's `copy` instructions with at least a pool
    leaf's element count."""
    text = program.trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert must_hold in text
    found = []
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?copy[.\w-]*\s*=\s*\w+\[([\d,]+)\]", text, re.M):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) >= leaf_elems:
            found.append(m.group(0).strip())
    return found


def test_the_reader_sees_the_copies_of_the_layout_that_went(one_chip, no_cache):
    """The assertion below is only as good as what it can see: the same
    commit on the heads-apart pool of the rag cell's geometry shows both
    of its relayouts."""
    L, N, bt, KVH, Hd, S, K = 6, 8192, 16, 4, 128, 4096, 256

    def commit(p, d, block_idx, phys):
        return p.at[:, phys].set(d[:, 0].reshape(L, S // bt, bt, KVH, Hd)[:, block_idx])

    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in (
            ((L, N, bt, KVH, Hd), BF), ((L, 1, S, KVH, Hd), BF),
            ((K,), jnp.int32), ((K,), jnp.int32),
        )
    ]
    found = pool_sized_copies(jax.jit(commit, donate_argnums=(0,)), args, L * N * bt * KVH * Hd)
    assert len(found) == 2, found


def test_one_kind_step_commit_and_append_at_the_rag_geometry(
    one_chip, no_cache, tmp_path, monkeypatch
):
    """The engine's OWN programs (the 32-slot decode step over llama's
    scan, the staged row's commit, the row append, the prefix restore's
    gather), taken with the arguments a served prompt gives them and grown
    to the rag cell's pool: 6 layers, 8192 blocks of 16, 4 x 128."""
    from tests.fakes.checkpoints import make_tiny_llama

    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.ops import paged_attention

    L, N, bt, KVH, Hd, slots, max_seq = 6, 8192, 16, 4, 128, 32, 4096
    make_tiny_llama(
        tmp_path, {"num_hidden_layers": L, "num_attention_heads": 32,
                   "num_key_value_heads": KVH, "head_dim": Hd},
    )
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(bt))
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "8")  # here; N on the described chip
    reset_settings_cache()
    eng = BatchedEngine(
        tmp_path, slots=slots, max_seq=max_seq, param_dtype="bfloat16",
        kv_dtype="bfloat16", kv_paged=True, prefix_cache_size=1,
    )
    try:
        store = eng.kv_store
        assert isinstance(store, KindStore) and store.kinds == (KV_KIND_FULL,)
        assert store.kv[KV_KIND_FULL]["k"].shape == (L, 8, bt, KVH * Hd)
        seen, programs = {}, {}

        def spy(obj, name):
            fn = programs[name] = getattr(obj, name)

            def run(*args):
                seen.setdefault(name, args)
                return fn(*args)

            setattr(obj, name, run)

        for obj, name in ((eng, "_ragged_step"), (store, "_commit"), (store, "_append"),
                          (store, "_gather")):
            spy(obj, name)
        dec = DecodingParams(temperature=0.0)
        res = eng.prefill_and_sample("a", list(range(300, 320)), dec)
        out, errs = eng.decode_batch({"a": (int(res.token[0]), dec)})
        assert not errs
        store.gather_row([1, 2], max_seq)
        assert set(seen) == {"_ragged_step", "_commit", "_append", "_gather"}
        # the step as the chip compiles it: the Mosaic kernel, the table
        # as wide as max_seq, every argument on the described chip
        monkeypatch.setattr(paged_attention, "paged_attend_impl", lambda: "pallas")
        eng._build_ragged()
        step = _abstract(seen["_ragged_step"], one_chip, 3, N)
        step[4] = {KV_KIND_FULL: jax.ShapeDtypeStruct((slots, max_seq // bt), jnp.int32,
                                                      sharding=one_chip)}
        commit = _abstract(seen["_commit"], one_chip, 0, N)
        wide = jax.ShapeDtypeStruct((max_seq // bt,), jnp.int32, sharding=one_chip)
        commit[2], commit[3] = {KV_KIND_FULL: wide}, {KV_KIND_FULL: wide}
        leaf = L * N * bt * KVH * Hd
        # the step reads the pool through the Mosaic kernel, nothing else
        assert pool_sized_copies(eng._ragged_step, step, leaf, "tpu_custom_call") == []
        for program, args in (
            (programs["_commit"], commit),
            (programs["_append"], _abstract(seen["_append"], one_chip, 0, N)),
            (programs["_gather"], _abstract(seen["_gather"], one_chip, 0, N)),
        ):
            assert pool_sized_copies(program, args, leaf) == []
    finally:
        eng.close()
        reset_settings_cache()


def test_two_kind_commit_append_and_attention_at_the_mix_geometry(one_chip, no_cache):
    """A window pool beside a full one (the mix cell: 3 window layers and 1
    full, 8 x 128, 16 slots, a window of 4096 in 272 blocks a slot): the
    commit, the row append (PR 29's finding) and a step's attention by
    kind followed by its append, in one program."""
    bt, KVH, Hd, slots, S = 16, 8, 128, 16, 16384
    per_slot, n_full = 272, 16 * (S // bt)
    model = SimpleNamespace(
        paged_kinds=(KV_KIND_WINDOW,) * 3 + (KV_KIND_FULL,), window=4096,
        config=SimpleNamespace(num_key_value_heads=KVH, head_dim=Hd),
    )
    tiny = {KV_KIND_FULL: PagedKVConfig(bt, 4), KV_KIND_WINDOW: PagedKVConfig(bt, 4)}
    store = KindStore(model, tiny, "bfloat16", window_width=per_slot)
    blocks = {KV_KIND_FULL: n_full, KV_KIND_WINDOW: slots * per_slot}

    def a(shape, dtype=BF):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = {
        kind: {leaf: a((len(store.layers[kind]), blocks[kind], bt, KVH * Hd)) for leaf in "kv"}
        for kind in store.kinds
    }
    row = {leaf: a((4, 1, S, KVH, Hd)) for leaf in "kv"}
    width = {KV_KIND_FULL: S // bt, KV_KIND_WINDOW: per_slot}
    idx = {kind: a((width[kind],), jnp.int32) for kind in store.kinds}
    rows = {leaf: a((4, slots, KVH, Hd)) for leaf in "kv"}
    phys = {kind: a((slots,), jnp.int32) for kind in store.kinds}
    tables = {kind: a((slots, width[kind]), jnp.int32) for kind in store.kinds}
    tables["base"] = a((slots,), jnp.int32)
    kinds = jnp.asarray([KV_KINDS.index(k) for k in model.paged_kinds], jnp.int32)
    within = jnp.asarray([0, 1, 2, 0], jnp.int32)

    def step(pool, q, rows, tables, pos, phys, off):
        def layer(x, per):
            kind, i, k, v = per
            o = store.attend(pool, None, q + x, {"k": k, "v": v}, tables, pos, kind, i, "pallas")
            return jnp.mean(o).astype(x.dtype), None

        x, _ = jax.lax.scan(layer, jnp.zeros((), BF), (kinds, within, rows["k"], rows["v"]))
        return x, store.append_in_program(pool, rows, phys, off)

    smallest = 3 * blocks[KV_KIND_WINDOW] * bt * KVH * Hd  # the window kind's leaf
    off = a((slots,), jnp.int32)
    for program, args in (
        (store._commit, (pool, row, idx, idx)),
        (store._append, (pool, rows, phys, off)),
    ):
        assert pool_sized_copies(program, args, smallest) == []
    step_args = (pool, a((slots, 1, 64, Hd)), rows, tables, off, phys, off)
    assert pool_sized_copies(
        jax.jit(step, donate_argnums=(0,)), step_args, smallest, "tpu_custom_call"
    ) == []


@pytest.mark.parametrize(
    "T,S,H,KVH,Hd,Vd,window",
    [
        (2048, 33792, 32, 32, 192, 128, 0),  # lat: expanded latents, G = 1, Vd != Hd
        (2048, 33280, 16, 2, 256, 256, 0),  # doc: gated attention at head 256
        (2048, 4096, 32, 4, 128, 128, 0),  # rag
        (256, 16512, 128, 8, 128, 128, 0),  # mix, its full layer: four head groups
        (256, 16512, 128, 8, 128, 128, 4096),  # mix, a window layer
    ],
    ids=["lat", "doc", "rag", "mix_full", "mix_window"],
)
def test_flash_prefill_takes_its_position_as_a_prefetch_and_a_grid_bound(
    one_chip, no_cache, T, S, H, KVH, Hd, Vd, window
):
    """The causal prefill kernel at the five geometries the cells run it
    at: Mosaic accepts `pos` as a scalar prefetch that the k / v index maps
    read, and a key axis whose bound is computed from it (a dynamic grid
    dimension), before any chip call."""
    from dnet_tpu.ops.flash_attention import FLASH_NAME, FLASH_WINDOW_NAME, _flash_pallas

    def a(shape, dtype=BF):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, k, v, pos, sinks):
        return _flash_pallas(
            q.reshape(1, T, H, Hd), k.reshape(1, S, KVH, Hd), v.reshape(1, S, KVH, Vd),
            pos, sinks, G=H // KVH, scale=Hd**-0.5, bq=128, bk=128, interpret=False,
            window=window,
        )

    args = (
        a((1, T, H * Hd)), a((1, S, KVH * Hd)), a((1, S, KVH * Vd)), a((1,), jnp.int32),
        a((H,), jnp.float32),
    )
    text = jax.jit(call).trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
    name = FLASH_WINDOW_NAME if window else FLASH_NAME
    calls = re.findall(rf"%{name}[.\w]* = .*custom-call\((.*?)\), custom_call_target", text)
    assert len(calls) == 1, text[:2000]
    # the grid's bound rides in front of pos, sinks, q, k and v
    assert len(calls[0].split(", ")) == 6
    # merged heads in, merged heads out: no copy as large as the row
    assert not re.search(rf"copy\([^)]*\[1,{S},", text)
