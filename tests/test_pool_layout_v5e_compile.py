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

The same reading for a WEIGHT's layout (PR 52): a projection split by head,
stored `[L, D, heads*head_dim]`, is sliced whole out of its stack and copied
into the layout the contraction wants (D minor, heads apart) in every layer
of every program; stored `[L, heads, head_dim, D]` it is read where it lies.
The mix cell's chunk and step programs hold no such slice and no such copy.

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


def _qwen3_next_params(cfg: dict, periods: int, dtype):
    """`models/qwen3_next.py`'s window parameters as shapes, from a config's
    numbers (held to the loader's own tree at the tiny size below)."""
    D, Hd = cfg["hidden_size"], cfg["head_dim"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    HK, HV = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv, taps = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    E, F, R = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["num_experts_routed"]
    Fs, C = cfg["shared_expert_intermediate_size"], 2 * HK * Dk + HV * Dv
    tree = {
        "attn": {"attn_norm": (D,), "k_norm": (Hd,), "q_norm": (Hd,), "w_qgate": (D, H * Hd),
                 "wk": (D, KVH * Hd), "wo": (H * Hd, D), "wq": (D, H * Hd), "wv": (D, KVH * Hd)},
        "gdn": {"A_log": (HV,), "attn_norm": (D,), "conv_w": (taps, C), "dt_bias": (HV,),
                "o_norm": (Dv,), "w_a": (D, HV), "w_b": (D, HV), "w_qkv": (D, C),
                "w_z": (D, HV * Dv), "wo": (HV * Dv, D)},
        "moe": {"e_down": (E, F, D), "e_gate": (E, D, F), "e_up": (E, D, F), "gate_w": (D, R),
                "mlp_norm": (D,), "s_down": (Fs, D), "s_gate": (D, Fs), "s_up": (D, Fs),
                "sg_w": (D, 1)},
    }
    lead = {"attn": (periods,), "gdn": (periods, 3), "moe": (periods, 4)}
    return {
        group: {n: jax.ShapeDtypeStruct(lead[group] + s, dtype) for n, s in leaves.items()}
        for group, leaves in tree.items()
    }


def test_the_doc_cells_decode_step_reads_its_experts_through_the_grouped_kernel(
    one_chip, no_cache, tmp_path, monkeypatch
):
    """The doc cell's step (16 lanes x top-10 over 512 routed, 256 held of
    2048 x 512 in each of 4 layers: 69 of 256 chosen a layer) as the v5e
    compiles `apply_window` for it, the store's kernels stubbed out: the
    routed experts are `gmm` custom calls over the WHOLE stack in place,
    no dot runs over the 256 held experts and no expert stack (1.6 GB a
    layer) is copied or sliced out for a kernel."""
    import json
    from pathlib import Path

    from tests.fakes.checkpoints import make_tiny_qwen3_next

    from dnet_tpu.core.engine import LocalEngine, apply_whole
    from dnet_tpu.models import get_ring_model_cls
    from dnet_tpu.models.base import ModelConfig
    from dnet_tpu.obs.phases import KV_KIND_STATE
    from dnet_tpu.ops import kernel_select

    # the shapes' formula is the loader's tree, at the size a loader can run
    tiny = make_tiny_qwen3_next(tmp_path)
    eng = LocalEngine(tmp_path, max_seq=64, param_dtype="float32")
    assert jax.tree.map(lambda a: a.shape, eng.window_params) == jax.tree.map(
        lambda a: a.shape, _qwen3_next_params(tiny, 1, jnp.float32)
    )
    eng.close()

    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmarks/configs/qwen3-next-80b-a3b-4l-ep2.json").read_text())
    model = get_ring_model_cls("qwen3_next")(ModelConfig.from_hf(cfg), range(4))
    lanes, E, D, F = 16, cfg["num_experts"], cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert (E, D, F, model.n_routed) == (256, 2048, 512, 512)
    assert model.moe_path(lanes, whole=True) == "grouped"
    assert model.moe_path(64, whole=True) == model.moe_path(lanes) == "dense"

    def attend(q, k, v, store, kind, layer, gate=None):
        """The store's hook, without its kernels: something of the mixer's
        output shape that depends on its input."""
        n = model.HV * model.Dv if kind == KV_KIND_STATE else q.shape[2] * q.shape[3]
        return q.reshape(lanes, 1, -1)[..., :n], store

    def step(params, x, pos):  # the paged step's call (core/batch.py: ragged_step)
        return apply_whole(model, params, x, {}, pos, attend_fn=attend)[0]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    args = on_chip((
        _qwen3_next_params(cfg, 1, BF), jax.ShapeDtypeStruct((lanes, 1, D), BF),
        jax.ShapeDtypeStruct((lanes, 1), jnp.int32),
    ))
    monkeypatch.setattr(kernel_select, "on_tpu", lambda: True)  # the chip's branch
    text = jax.jit(step).trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
    # gate, up and down of each of the period's four layers: twelve
    # kernels, each handed all 4 x 256 experts of the stack as its groups;
    # a layer's three share ONE set of group metadata (XLA merges the
    # three `make_group_metadata` of `gmm`)
    calls = re.findall(
        r"%gmm[.\w]* = [^\n]*custom-call\(([^\n]*?)\), custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", text,
    )
    assert len(calls) == 12, len(calls)
    stacks = (f"bf16[{4 * E},{D},{F}]", f"bf16[{4 * E},{F},{D}]")
    assert all(any(st in shapes for st in stacks) for _, shapes in calls), calls[0]
    metadata = [tuple(operands.split(", ")[:4]) for operands, _ in calls]
    assert len(set(metadata)) == 4 and all(metadata.count(m) == 3 for m in metadata)
    # nothing multiplies, copies or slices out a layer's 256 experts
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.-]+) = \w+\[([\d,]+)\][^\n]*? (\w[\w-]*)\(", text, re.M
    ):
        name, dims, op = m.group(1), [int(d) for d in m.group(2).split(",")], m.group(3)
        assert int(np.prod(dims)) < E * D * F or op in ("parameter", "bitcast", "get-tuple-element"), (
            name, dims, op
        )
    # .. nor names one layer's share of the stack at all (the dense einsum's
    # fusions take `[256, 2048, 512]` slices of it)
    assert not re.search(rf"bf16\[(1,)?{E},({D},{F}|{F},{D})\]", text)
    model.moe_impl = "dense"
    dense = (  # a new function: the trace of `step` above is cached
        jax.jit(lambda *a: step(*a)).trace(*args).lower(lowering_platforms=("tpu",))
        .compile().as_text()
    )
    assert "%gmm" not in dense and re.search(rf"bf16\[(1,)?{E},({D},{F}|{F},{D})\]", dense)


def materialised(text: str):
    """(name, dims, op) of every instruction of a compiled module that
    writes its result to memory: those of the entry, the loops' bodies and
    the branches, not those inside a fusion's computation."""
    fused = set(re.findall(r"fusion\([^\n]*?calls=%([\w.-]+)", text))
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(
            r"^\s*(?:ROOT\s+)?%?([\w.-]+) = \w+\[([\d,]+)\][^\n]*? (\w[\w-]*)\(", line
        )
        if m and comp not in fused:
            yield m.group(1), [int(d) for d in m.group(2).split(",")], m.group(3)


def weight_relayouts(text: str, D: int, counts) -> list:
    """The `copy` instructions and the slice fusions whose result is one
    layer's projection matrix: one of `counts` elements with the hidden
    width among its dimensions (a chunk's q, `[1, 256, 128, 128]`, has
    `wk`'s count and is no weight)."""
    return [
        (name, dims) for name, dims, op in materialised(text)
        if int(np.prod(dims)) in counts and D in dims
        and (op == "copy" or "slice" in name or "slice" in op)
    ]


MIX = dict(L=4, D=4096, H=128, KVH=8, Hd=128, E=16, R=128, F=4096, V=32768, shared=4,
           slots=16, bt=128, chunk=256, max_seq=16512, full_blocks=448, window_blocks=35)


def _cohere2_moe_params(c: dict, dtype, heads_first: bool = True) -> dict:
    """`models/cohere2_moe.py`'s window parameters as shapes, from a
    geometry's numbers (held to the loader's own tree below)."""
    L, D, H, KVH, Hd, E, F = (c[k] for k in ("L", "D", "H", "KVH", "Hd", "E", "F"))
    Fs = c["shared"] * F

    def by_head(n):
        return (L, n, Hd, D) if heads_first else (L, D, n * Hd)

    tree = {
        "norm": (L, D), "wq": by_head(H), "wk": by_head(KVH), "wv": by_head(KVH),
        "wo": (L, H * Hd, D), "gate_w": (L, D, c["R"]),
        "e_gate": (L, E, D, F), "e_up": (L, E, D, F), "e_down": (L, E, F, D),
        "s_gate": (L, D, Fs), "s_up": (L, D, Fs), "s_down": (L, Fs, D),
    }
    return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in tree.items()}


@pytest.fixture(scope="module")
def mix_engine(tmp_path_factory):
    """The mix cell's engine with its heads, experts, slots, blocks, window
    and `max_seq`, and a hidden width, an expert width and a vocabulary a
    CPU can hold (64, 32, 512): after one served prompt and one step, the
    engine, the two programs' arguments and the narrow geometry."""
    from benchmarks.harness import spec
    from benchmarks.harness.weights import write_checkpoint

    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    full = spec.load_json(spec.BENCH_DIR / "configs" / "command-a-plus-4l-ep8.json")
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    narrow = dict(MIX, D=64, F=32, V=512)
    cfg.update(hidden_size=narrow["D"], intermediate_size=narrow["F"], vocab_size=narrow["V"])
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["num_experts"], cfg["num_experts_routed"], cfg["num_shared_experts"]) == tuple(
        MIX[k] for k in ("H", "KVH", "Hd", "E", "R", "shared"))
    model_dir = tmp_path_factory.mktemp("mix_geometry")
    write_checkpoint(model_dir, cfg, seed=2**31 + 52, dtype="bfloat16")
    env = pytest.MonkeyPatch()
    serve = full["serve"]["env"]
    for name in ("DNET_KV_BLOCK_TOKENS", "DNET_SCHED_TOKEN_BUDGET"):
        env.setenv(name, serve[name])
    env.setenv("DNET_KV_POOL_BLOCKS", "8")  # here; the cell's 448 on the described chip
    reset_settings_cache()
    eng = BatchedEngine(
        model_dir, slots=MIX["slots"], max_seq=MIX["max_seq"], param_dtype="bfloat16",
        kv_dtype="bfloat16", kv_paged=True,
    )
    try:
        seen = {}

        def spy(obj, name):
            fn = getattr(obj, name)

            def run(*args):
                seen.setdefault(name, args)
                return fn(*args)

            setattr(obj, name, run)

        spy(eng, "_ragged_step")
        spy(eng.eng, "_forward")  # the chunk program: a prompt's rows against its staged row
        dec = DecodingParams(temperature=0.0)
        res = eng.prefill_and_sample("a", list(range(300, 320)), dec)
        _, errs = eng.decode_batch({"a": (int(res.token[0]), dec)})
        assert not errs and set(seen) == {"_ragged_step", "_forward"}
        # the shapes' formula is the loader's tree
        assert jax.tree.map(lambda a: a.shape, eng.eng.window_params) == jax.tree.map(
            lambda a: a.shape, _cohere2_moe_params(narrow, BF)
        )
        yield eng, seen
    finally:
        eng.close()
        env.undo()
        reset_settings_cache()


def _mix_programs(eng, seen, one_chip, monkeypatch, heads_first=True):
    """The engine's chunk program and its 16-slot step as the chip compiles
    them (the Mosaic kernels, the grouped experts), every argument a served
    prompt gave them on the described chip and grown to the cell's widths."""
    from dnet_tpu.ops import kernel_select, paged_attention

    c = MIX
    monkeypatch.setattr(kernel_select, "on_tpu", lambda: True)
    monkeypatch.setattr(paged_attention, "paged_attend_impl", lambda: "pallas")
    eng._build_ragged()
    eng.eng._build_fns()

    def a(shape, dtype=BF):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: a(np.shape(x), x.dtype), tree)

    wp = on_chip(_cohere2_moe_params(c, BF, heads_first))
    ep = {"embed": {"weight": a((c["V"], c["D"]))}, "final_norm": {"weight": a((c["D"],))}}
    assert jax.tree.structure(ep) == jax.tree.structure(seen["_forward"][1])
    chunk = [wp, ep, a((1, c["chunk"]), jnp.int32), *on_chip(seen["_forward"][3:])]
    assert chunk[3]["k"].shape == (c["L"], 1, c["max_seq"], c["KVH"], c["Hd"])
    step = [wp, ep, *on_chip(seen["_ragged_step"][2:])]
    assert step[2].shape == (c["slots"], 1)
    row = c["KVH"] * c["Hd"]
    step[3] = {
        KV_KIND_FULL: {n: a((1, c["full_blocks"], c["bt"], row)) for n in "kv"},
        KV_KIND_WINDOW: {
            n: a((3, c["slots"] * c["window_blocks"], c["bt"], row)) for n in "kv"
        },
    }
    assert jax.tree.map(lambda x: x.shape[2:], step[3]) == jax.tree.map(
        lambda x: x.shape[2:], seen["_ragged_step"][3])
    assert seen["_ragged_step"][4][KV_KIND_WINDOW].shape == (c["slots"], c["window_blocks"])
    step[4] = dict(step[4], **{KV_KIND_FULL: a((c["slots"], c["max_seq"] // c["bt"]), jnp.int32)})
    step[9] = a((c["slots"], c["V"]), jnp.int32)  # the sampler's counts, a vocabulary wide
    return {"chunk": (eng.eng._forward, chunk, ("flash_prefill", "flash_prefill_window")),
            "step": (eng._ragged_step, step, ("paged_attend", "paged_attend_window", "gmm"))}


def _compiled(program, args) -> str:
    return program.trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()


@pytest.mark.parametrize("which", ["chunk", "step"])
def test_the_mix_cells_programs_read_a_projection_where_it_lies(
    one_chip, no_cache, mix_engine, monkeypatch, which
):
    """The engine's OWN chunk program (256 tokens against the staged row of
    16512) and its 16-slot step over both pools, at the mix cell's widths
    (4 layers window x 3 + full, hidden 4096, 128 / 8 heads of 128, 16 held
    experts of 128 routed, 128-token blocks): NO `copy` and no slice fusion
    with the element count of a layer's `wq` (4096 x 16384) or of its `wk`
    (4096 x 1024).  Stored `[L, D, heads*head_dim]` each layer of each
    program made both, of each of the three (15 % of the cell's busy time:
    ledger, PR 51, mix, `breakdown`)."""
    eng, seen = mix_engine
    program, args, kernels = _mix_programs(eng, seen, one_chip, monkeypatch)[which]
    text = _compiled(program, args)
    assert "tpu_custom_call" in text and all(f"%{k}" in text for k in kernels)
    c = MIX
    counts = {c["D"] * c["H"] * c["Hd"], c["D"] * c["KVH"] * c["Hd"]}
    assert weight_relayouts(text, c["D"], counts) == []


def test_the_reader_sees_the_relayouts_of_the_projection_layout_that_went(
    one_chip, no_cache, mix_engine, monkeypatch
):
    """The assertion above is only as good as what it can see: the same
    programs with the same contraction over `[L, D, heads*head_dim]` stacks
    (the model's projection put back as it was) slice each of the three
    matrices out of its stack and copy it, under the names the ledger's
    `breakdown` of the mix cell carried."""
    eng, seen = mix_engine
    Hd = MIX["Hd"]

    def as_it_was(h, w):
        return (h @ w).reshape(*h.shape[:2], -1, Hd)

    monkeypatch.setattr(eng.model, "_by_head", as_it_was)
    programs = _mix_programs(eng, seen, one_chip, monkeypatch, heads_first=False)
    c = MIX
    wq, wk = c["D"] * c["H"] * c["Hd"], c["D"] * c["KVH"] * c["Hd"]
    for which, pairs_of_wk in (("chunk", 2), ("step", 1)):
        program, args, _ = programs[which]
        found = weight_relayouts(_compiled(program, args), c["D"], {wq, wk})
        big = [name for name, dims in found if int(np.prod(dims)) == wq]
        small = [name for name, dims in found if int(np.prod(dims)) == wk]
        assert len(big) == 2 and len(small) == 2 * pairs_of_wk, found
        for names in (big, small):
            assert sum(n.startswith("copy") for n in names) == len(names) // 2, found
            assert sum("dynamic-slice_fusion" in n for n in names) == len(names) // 2, found
