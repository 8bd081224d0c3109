"""The Mellum cell's OWN programs, compiled ahead of time for the v5e at the
cell's widths (beside tests/test_pool_layout_v5e_compile.py, whose fixtures
and readers these are): the 2048-token chunk against the staged row of
66560, the 16-slot step over both pools, AND the load's warm-up programs,
which prefill a table width's worth of tokens in ONE program (core/batch.py:
warm_chunks): 65536 and 66560 rows.  PR 50's first chip call died in such a
program (a 1.06 MB scalar table in 1 MB of SMEM); here a 65536-row program
that gathered its routed experts' rows at once would hold 2.4 GB a copy of
them beside a chip that serving fills to four fifths, which is why
models/mellum.py carries a wide program through the stack a slab at a time.

Eight layers (sliding x 3, full) x 2, hidden 2304, 32 / 4 heads of 128, 64
experts of 896 top-8, vocabulary 98304, 128-token blocks, window 1024: the
published widths.  A compile that passes proves the compiler takes the
programs and what they hold at once, not that they are right:
tests/test_mellum_parity.py holds them to the reference.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_WINDOW
from tests.test_pool_layout_v5e_compile import (  # noqa: F401  (fixtures)
    BF,
    _compiled,
    no_cache,
    one_chip,
)

CELL = dict(L=8, D=2304, H=32, KVH=4, Hd=128, E=64, F=896, V=98304, bt=128, slots=16,
            max_seq=66560, chunk=2048, window=1024, window_blocks=25, full_blocks=8320)


def _mellum_params(c, dtype):
    L, D, H, KVH, Hd, E, F = (c[k] for k in ("L", "D", "H", "KVH", "Hd", "E", "F"))
    tree = {
        "attn_norm": (L, D), "wq": (L, H, Hd, D), "wk": (L, KVH, Hd, D), "wv": (L, KVH, Hd, D),
        "wo": (L, H * Hd, D), "q_norm": (L, Hd), "k_norm": (L, Hd), "mlp_norm": (L, D),
        "gate_w": (L, D, E), "e_gate": (L, E, D, F), "e_up": (L, E, D, F), "e_down": (L, E, F, D),
    }
    return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in tree.items()}


@pytest.fixture(scope="module")
def cell_engine(tmp_path_factory):
    """The cell's engine with its heads, experts, slots, blocks, window and
    a hidden width, an expert width and a vocabulary a CPU can hold (64, 32,
    512), and a `max_seq` of 4096 (the widths below are the cell's own):
    after one served prompt and one step, the engine and the two programs'
    arguments."""
    from benchmarks.harness import spec
    from benchmarks.harness.weights import write_checkpoint

    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    full = spec.load_json(spec.BENCH_DIR / "configs" / "mellum2-12b-a2.5b-8l.json")
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    c = CELL
    assert (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["vocab_size"], cfg["sliding_window"]) == tuple(
        c[k] for k in ("L", "D", "H", "KVH", "Hd", "E", "F", "V", "window"))
    narrow = dict(c, D=64, F=32, V=512)
    cfg.update(hidden_size=narrow["D"], moe_intermediate_size=narrow["F"], vocab_size=narrow["V"])
    model_dir = tmp_path_factory.mktemp("mellum_geometry")
    write_checkpoint(model_dir, cfg, seed=2**31 + 54, dtype="bfloat16")
    env = pytest.MonkeyPatch()
    env.setenv("DNET_KV_BLOCK_TOKENS", full["serve"]["env"]["DNET_KV_BLOCK_TOKENS"])
    env.setenv("DNET_KV_POOL_BLOCKS", "8")  # here; the cell's 8320 on the described chip
    reset_settings_cache()
    eng = BatchedEngine(
        model_dir, slots=c["slots"], max_seq=4096, param_dtype="bfloat16",
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
        spy(eng.eng, "_forward")
        dec = DecodingParams(temperature=0.0)
        res = eng.prefill_and_sample("a", list(range(300, 320)), dec)
        _, errs = eng.decode_batch({"a": (int(res.token[0]), dec)})
        assert not errs and set(seen) == {"_ragged_step", "_forward"}
        # the shapes' formula is the loader's tree
        assert jax.tree.map(lambda a: a.shape, eng.eng.window_params) == jax.tree.map(
            lambda a: a.shape, _mellum_params(narrow, BF)
        )
        # the window kind's table is as wide as the cell's
        assert seen["_ragged_step"][4][KV_KIND_WINDOW].shape == (c["slots"], c["window_blocks"])
        yield eng, seen
    finally:
        eng.close()
        env.undo()
        reset_settings_cache()


def _cell_programs(eng, seen, one_chip, monkeypatch):
    from dnet_tpu.ops import kernel_select, paged_attention

    c = CELL
    monkeypatch.setattr(kernel_select, "on_tpu", lambda: True)
    monkeypatch.setattr(paged_attention, "paged_attend_impl", lambda: "pallas")
    eng._build_ragged()
    eng.eng._build_fns()

    def a(shape, dtype=BF):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: a(np.shape(x), x.dtype), tree)

    wp = on_chip(_mellum_params(c, BF))
    ep = {"embed": {"weight": a((c["V"], c["D"]))}, "final_norm": {"weight": a((c["D"],))},
          "lm_head": {"weight": a((c["D"], c["V"]))}}
    assert jax.tree.structure(ep) == jax.tree.structure(seen["_forward"][1])
    staged = {n: a((c["L"], 1, c["max_seq"], c["KVH"], c["Hd"])) for n in "kv"}
    assert jax.tree.structure(staged) == jax.tree.structure(seen["_forward"][3])

    def prefill(rows):
        return [wp, ep, a((1, rows), jnp.int32), staged, *on_chip(seen["_forward"][4:])]

    step = [wp, ep, *on_chip(seen["_ragged_step"][2:])]
    assert step[2].shape == (c["slots"], 1)
    row = c["KVH"] * c["Hd"]
    step[3] = {
        KV_KIND_FULL: {n: a((2, c["full_blocks"], c["bt"], row)) for n in "kv"},
        KV_KIND_WINDOW: {
            n: a((6, c["slots"] * c["window_blocks"], c["bt"], row)) for n in "kv"
        },
    }
    assert jax.tree.map(lambda x: x.shape[2:], step[3]) == jax.tree.map(
        lambda x: x.shape[2:], seen["_ragged_step"][3])
    step[4] = dict(step[4], **{KV_KIND_FULL: a((c["slots"], c["max_seq"] // c["bt"]), jnp.int32)})
    step[9] = a((c["slots"], c["V"]), jnp.int32)  # the sampler's counts, a vocabulary wide
    flash = ("flash_prefill", "flash_prefill_window", "gmm")
    return {
        "chunk": (eng.eng._forward, prefill(c["chunk"]), flash),
        "warm_65536": (eng.eng._forward, prefill(65536), flash),
        "warm_66560": (eng.eng._forward, prefill(c["max_seq"]), flash),
        # the step's 16 rows x top-8 are expected to choose 0.87 of the 64
        # experts, over ops/moe.py SPARSE_SHARE: `auto` keeps the einsum
        "step": (eng._ragged_step, step, ("paged_attend", "paged_attend_window")),
    }


def largest_temporary(text: str) -> int:
    """The most bytes any one instruction of the compiled program yields,
    its parameters apart (they are the weights, the pools and the staged
    row): what the program holds at once beside them is a few of these."""
    size = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1, "f16": 2}
    worst = (0, "")
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(\w+)\[([\d,]*)\][^\s]*\s+([\w-]+)\(", text, re.M
    ):
        name, dtype, dims, op = m.groups()
        # a view is not a buffer: the kernel's [L*E, K, N] reading of a stack
        if op in ("parameter", "bitcast", "get-tuple-element") or dtype not in size:
            continue
        n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        worst = max(worst, (n * size[dtype], f"{name} = {op} {dtype}[{dims}]"))
    return worst


@pytest.mark.parametrize("which", ["chunk", "warm_65536", "warm_66560", "step"])
def test_the_cells_programs_compile_for_the_v5e_at_the_published_widths(
    one_chip, no_cache, cell_engine, monkeypatch, which
):
    """Each holds the Mosaic kernels of BOTH kinds (the prefill programs the
    grouped matmul too; the step's experts stay the dense einsum), and
    nothing it makes on the way is as large as 1.2 GB: the staged row
    (1.09 GB a leaf pair, 0.55 GB a leaf) is the largest thing a prefill
    program touches, whatever its width."""
    eng, seen = cell_engine
    program, args, kernels = _cell_programs(eng, seen, one_chip, monkeypatch)[which]
    text = _compiled(program, args)
    assert "tpu_custom_call" in text and all(f"%{k}" in text for k in kernels), which
    assert ("%gmm" in text) == (which != "step")
    assert largest_temporary(text)[0] < 1.2e9, largest_temporary(text)
