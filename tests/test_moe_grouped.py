"""The exact grouped-matmul path of the routed experts (ops/moe.py:
`swiglu_grouped_closure`) against the dense einsum, the rule that chooses
between them from static shapes (`resolve_moe_impl`), the host-side counter
of rows by path, and wide prefill chunks through the engines.

Everything is float32 on the CPU at tiny sizes with `moe_impl="grouped"`
forced, or a chunk wide enough (more than RIDGE_ROWS padded rows) for
`auto` to choose it.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.ops.moe import (
    RIDGE_ROWS,
    moe_apply,
    resolve_moe_impl,
    swiglu_expert_closures,
    swiglu_grouped_closure,
)

pytestmark = pytest.mark.core

#: name -> (routed experts, held, offset, top-k, scores, renormalise, scale)
FAMILIES = {
    "qwen3_moe": (8, 8, 0, 2, "softmax", True, 1.0),
    "mixtral": (4, 4, 0, 2, "softmax", True, 1.0),
    "deepseek_v2": (8, 8, 0, 3, "softmax", False, 2.5),
    "cmdaplus-share": (128, 16, 16, 8, "sigmoid", True, 1.0),
}
ROUTINGS = ("random", "one-expert-takes-all", "an-expert-takes-none", "padded-rows")


def _case(family, routing, n=37, d=32, f=16):
    n_routed, held, offset, k, fn, renorm, scale = FAMILIES[family]
    rng = np.random.default_rng(zlib.crc32(f"{family}/{routing}".encode()))
    p = {
        name: jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.3
        for name, shape in (
            ("e_gate", (held, d, f)), ("e_up", (held, d, f)), ("e_down", (held, f, d)),
        )
    }
    flat = rng.standard_normal((n, d)).astype(np.float32)
    logits = rng.standard_normal((n, n_routed)).astype(np.float32)
    if routing == "one-expert-takes-all":
        logits[:, offset + 1] += 50.0  # a held expert is in every token's choice
    elif routing == "an-expert-takes-none":
        logits[:, offset] -= 50.0  # the first held expert is in nobody's
    elif routing == "padded-rows":
        flat[n - 11:] = 0.0  # what a bucket's padding embeds and norms to
        logits[n - 11:] = 0.0
    logits = jnp.asarray(logits)
    scores = jax.nn.sigmoid(logits) if fn == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(scores, k)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return p, jnp.asarray(flat), scores, top_idx.astype(jnp.int32), top_w * scale, offset, n_routed


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("family", FAMILIES)
def test_grouped_equals_dense(family, routing):
    p, flat, scores, top_idx, top_w, offset, n_routed = _case(family, routing)
    effn, dense, held = swiglu_expert_closures(p, flat, scores, top_idx, top_w, None, offset=offset)
    grouped = swiglu_grouped_closure(p, flat, top_idx, top_w, offset=offset)
    want = np.asarray(dense())
    got, partial = jax.jit(
        lambda: moe_apply("grouped", flat, top_idx, top_w, effn, held, 0.0, top_idx.shape[1],
                          None, dense, offset=offset, n_routed=n_routed, grouped_fn=grouped)
    )()
    assert not partial
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    here = np.asarray((top_idx >= offset) & (top_idx < offset + held))
    if routing == "one-expert-takes-all":
        assert here[:, :].any(axis=1).all()
    if routing == "an-expert-takes-none":
        assert not np.asarray(top_idx == offset).any()
    # a token none of whose experts is held here gets exactly nothing from
    # the routed term (the share's block then adds the shared experts alone)
    nowhere = ~here.any(axis=1)
    if family == "cmdaplus-share" and routing == "random":
        assert nowhere.any()
    assert not np.asarray(got)[nowhere].any()


@pytest.mark.parametrize("family", ["qwen3_moe", "cmdaplus-share"])
def test_the_chips_kernel_interpreted_equals_dense(family, monkeypatch):
    """On the chip the groups go through Pallas' megablox kernel; here its
    interpret mode (DNET_FLASH_INTERPRET=1, a row count the tile divides)
    runs the same kernel logic: tiles shared by experts, an empty group,
    and for the share a tail no group covers."""
    from dnet_tpu.ops import moe

    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    k = FAMILIES[family][3]
    p, flat, scores, top_idx, top_w, offset, n_routed = _case(
        family, "an-expert-takes-none", n=2 * moe.GROUP_TILE_ROWS // k, d=128, f=128
    )
    calls = []
    real = moe.lax.ragged_dot
    monkeypatch.setattr(moe.lax, "ragged_dot", lambda *a, **kw: calls.append(1) or real(*a, **kw))
    effn, dense, held = swiglu_expert_closures(p, flat, scores, top_idx, top_w, None, offset=offset)
    got = swiglu_grouped_closure(p, flat, top_idx, top_w, offset=offset)()
    assert not calls  # the kernel ran, not its twin
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense()), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("stacked", [False, True], ids=["one-layer", "stack"])
@pytest.mark.parametrize("m, tile", [(160, 32), (128, 128), (320, 64), (48, 16), (10, 16), (4, 16)])
def test_a_steps_rows_reach_the_kernel(m, tile, stacked, monkeypatch):
    """A decode step's few sorted rows (16 lanes x top-10 = 160; a single
    stream's 10 or 4) go through the kernel on the chip, not its twin:
    the row tile is read off the row count, rows no tile divides are
    padded past the last group, and the groups nobody chose (most, at a
    step) are never visited.  Here the kernel interpreted, against
    `lax.ragged_dot`."""
    import jax.experimental.pallas.ops.tpu.megablox as megablox

    from dnet_tpu.ops import moe

    assert moe.group_tile_rows(m) == tile
    rng = np.random.default_rng(m)
    groups, K, N = 24, 128, 256
    # most groups empty, and a tail of rows that no group covers
    sizes = np.zeros(groups, np.int32)
    chosen = rng.choice(groups, size=5, replace=False)
    sizes[chosen] = rng.multinomial(m - max(1, m // 5), np.ones(5) / 5)
    xs = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, groups, K, N)), jnp.float32) * 0.1
    want = np.asarray(jax.lax.ragged_dot(xs, w[1], jnp.asarray(sizes)))

    tiles, real_gmm = [], megablox.gmm
    monkeypatch.setattr(
        megablox, "gmm", lambda *a, **kw: tiles.append(kw["tiling"][0]) or real_gmm(*a, **kw)
    )
    twin = []
    real = moe.lax.ragged_dot
    monkeypatch.setattr(moe.lax, "ragged_dot", lambda *a, **kw: twin.append(1) or real(*a, **kw))
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    if stacked:
        got = jax.jit(lambda layer: moe.grouped_matmul(xs, w, jnp.asarray(sizes), layer))(jnp.int32(1))
    else:
        got = moe.grouped_matmul(xs, w[1], jnp.asarray(sizes))
    assert tiles == [tile] and not twin and got.shape == (m, N)
    covered = int(sizes.sum())
    assert 0 < covered < m
    np.testing.assert_allclose(np.asarray(got)[:covered], want[:covered], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernel", ["twin", "interpret"])
def test_a_layer_is_read_out_of_the_stack_in_place(kernel, monkeypatch):
    """What llama's layer scan hands on when the experts go grouped:
    p["e_stack"] = (the window's stacked weights, the layer's index, traced).
    The kernel takes the whole stack with every other layer's groups empty;
    its twin indexes the stack.  Each layer's answer is its own."""
    from dnet_tpu.ops import moe

    if kernel == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    layers = [_case("qwen3_moe", r, n=64, d=128, f=128) for r in ROUTINGS[:3]]
    _, flat, scores, top_idx, top_w, _, _ = layers[1]
    stacks = moe.expert_stacks(
        {n: jnp.stack([lay[0][n] for lay in layers]) for n in moe.EXPERT_KEYS}
    )

    @jax.jit
    def at(layer):
        p = {"e_gate": stacks["e_gate"][0], "e_stack": (stacks, layer)}
        return swiglu_grouped_closure(p, flat, top_idx, top_w)()

    for i, lay in enumerate(layers):
        want = swiglu_expert_closures(lay[0], flat, scores, top_idx, top_w, None)[1]()
        np.testing.assert_allclose(
            np.asarray(at(jnp.int32(i))), np.asarray(want), atol=5e-5, rtol=5e-5
        )
    quantized = {n: {"q": stacks[n], "s": stacks[n][..., :1, :]} for n in moe.EXPERT_KEYS}
    assert moe.expert_stacks(quantized) is None and moe.expert_stacks({"w_gate": 1}) is None


def test_a_forced_dense_program_never_calls_the_grouped_closure():
    p, flat, scores, top_idx, top_w, offset, n_routed = _case("qwen3_moe", "random")
    effn, dense, held = swiglu_expert_closures(p, flat, scores, top_idx, top_w, None)

    def boom():
        raise AssertionError("grouped closure traced under dense")

    for impl in ("dense", "auto"):  # 37 rows x top-2 of 8: under the ridge, every expert chosen
        out, _ = moe_apply(impl, flat, top_idx, top_w, effn, held, 0.0, 2, None, dense,
                           grouped_fn=boom)
        assert np.array_equal(np.asarray(out), np.asarray(dense()))


#: a cell's step: (rows, top-k, routed experts), PERF.md section 4
DOC, LAT, MIX, RAG = (16, 10, 512), (32, 4, 128), (16, 8, 128), (32, 8, 128)


@pytest.mark.parametrize(
    "impl, rows, ranks, closure, want",
    [
        ("auto", RIDGE_ROWS, 1, True, "dense"),  # on the ridge: the weight read either way
        ("auto", RIDGE_ROWS + 1, 1, True, "grouped"),
        ("auto", 2048, 1, True, "grouped"),
        ("auto", 32, 1, True, "dense"),  # a decode step that chooses 0.86 of the held
        ("auto", RIDGE_ROWS + 1, 1, False, "dense"),  # gpt_oss: no closure
        ("auto", 4096, 4, True, "dense"),  # under a tp axis auto is dense
        ("auto", 4096, 4, False, "dense"),
        ("grouped", 8, 1, True, "grouped"),  # by name: tests force it small
        ("grouped", 8, 1, False, "dense"),  # nothing to run: same result
        ("grouped", 4096, 2, True, "dense"),
        ("dense", 10_000, 1, True, "dense"),  # by name wins
        ("dispatch", 8, 1, True, "dispatch"),
        ("a2a", 8, 4, True, "a2a"),
    ],
)
def test_resolve_moe_impl(impl, rows, ranks, closure, want):
    """By rows, at the rag cell's routing (top-8 of 128)."""
    from dnet_tpu.ops.moe import expected_share

    assert resolve_moe_impl(impl, rows, ranks, closure, expected_share(rows, 8, 128)) == want
    if rows > RIDGE_ROWS:  # above the ridge the share is not asked
        assert resolve_moe_impl(impl, rows, ranks, closure) == want


@pytest.mark.parametrize(
    "step, share, want",
    [
        (DOC, 0.27, "grouped"),  # 16 lanes x top-10 over 512: 69 of 256 held a layer
        (LAT, 0.63, "grouped"),  # measured 1.16 -> 0.59 ms a layer: admitted
        (MIX, 0.63, "grouped"),  # 2.19 -> 1.52 (cohere2_moe hands its stack on too)
        (RAG, 0.87, "dense"),  # 1.64 -> 1.45: within a skewed step's sort and gather
        ((16, 4, 128), 0.39, "grouped"),  # lat: a prompt's last chunk in the 16-row bucket
        ((1, 10, 512), 0.02, "grouped"),  # a single stream (LocalEngine)
        ((1, 4, 128), 0.03, "grouped"),
        ((1, 8, 128), 0.06, "grouped"),
        ((1, 2, 8), 0.23, "grouped"),  # mixtral's 8 experts: one row ..
        ((4, 2, 8), 0.66, "dense"),  # .. but not four
        ((5, 2, 8), 0.74, "dense"),  # a speculative L + 1 rows there
        ((64, 10, 512), 0.71, "dense"),  # a prompt's ragged last chunk in doc
        ((16, 0, 0), 1.0, "dense"),  # routing not known: every expert
    ],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v),
)
def test_under_the_ridge_the_share_of_held_experts_chosen_decides(step, share, want):
    from dnet_tpu.ops.moe import SPARSE_SHARE, expected_share, sparse_share

    rows, k, n_routed = step
    assert expected_share(rows, k, n_routed) == pytest.approx(share, abs=0.01)
    assert (share <= SPARSE_SHARE) == (want == "grouped")
    told = sparse_share(rows, k, n_routed, whole=True, quantized=False)
    assert told == expected_share(rows, k, n_routed)
    assert resolve_moe_impl("auto", rows, 1, True, told) == want
    # what keeps the einsum whatever the share: no closure, several ranks,
    # and a program that may not go grouped there, which is told 1.0: rows
    # that are one lane's under a vmap (not declared whole), quantized
    # experts (dq would materialise every one); a name wins
    assert resolve_moe_impl("auto", rows, 1, False, told) == "dense"
    assert resolve_moe_impl("auto", rows, 2, True, told) == "dense"
    for whole, quantized in ((False, False), (True, True), (False, True)):
        assert sparse_share(rows, k, n_routed, whole, quantized) == 1.0
    assert resolve_moe_impl("auto", rows, 1, True, 1.0) == "dense"
    assert resolve_moe_impl("auto", rows, 1, True) == "dense"
    assert resolve_moe_impl("dense", rows, 1, True, told) == "dense"
    assert resolve_moe_impl("grouped", rows, 1, True, 1.0) == "grouped"


def test_quantized_experts_stay_grouped_above_the_ridge_as_before():
    assert resolve_moe_impl("auto", RIDGE_ROWS + 1, 1, True, 1.0) == "grouped"


def _model_like(step, held, **over):
    """What `RingModel.moe_path` reads off a model, at a cell's routing."""
    from types import SimpleNamespace

    _, k, n_routed = step
    fields = dict(
        moe_grouped=True, moe_impl="auto", experts_quantized=False, n_routed=n_routed,
        config=SimpleNamespace(num_experts_per_tok=k, num_local_experts=held),
    )
    return SimpleNamespace(**{**fields, **over})


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("rows", [1, 16, 32, RIDGE_ROWS, 2048])
@pytest.mark.parametrize(
    "step, held", [(DOC, 256), (LAT, 16), (MIX, 16), (RAG, 128)], ids=["doc", "lat", "mix", "rag"]
)
def test_the_hosts_moe_path_is_the_traced_programs(step, held, rows, quantized):
    """The counter of rows by path is booked on the host by the rule the
    program was traced under: `RingModel.moe_path` and `moe_apply` agree
    at every cell's routing, at its step's rows and around them, for a
    program that declares its rows whole and for one that does not."""
    import contextlib

    from dnet_tpu.models.base import RingModel
    from dnet_tpu.ops.moe import whole_batch

    _, k, n_routed = step
    flat = jnp.zeros((rows, 8))
    idx = jnp.zeros((rows, k), jnp.int32)

    def traced(held, **kw):
        out, partial = moe_apply(
            "auto", flat, idx, idx.astype(jnp.float32), None, held, 0.0, k, None,
            lambda: "dense", grouped_fn=lambda: "grouped", **kw,
        )
        assert not partial
        return out

    like = _model_like(step, held, experts_quantized=quantized)
    every = _model_like(step, n_routed, n_routed=0)  # a layer that holds every expert
    for whole in (False, True):
        with whole_batch() if whole else contextlib.nullcontext():
            share = traced(held, n_routed=n_routed, quantized=quantized)
            assert RingModel.moe_path(like, rows) == share  # as the trace around declares
            assert RingModel.moe_path(every, rows) == traced(n_routed)
        assert RingModel.moe_path(like, rows, whole) == share
        if rows > RIDGE_ROWS:
            assert share == "grouped"
        elif not whole or quantized:  # the parent's rule: one lane's rows may be these
            assert share == "dense"
        elif rows == step[0]:
            assert share == ("dense" if step is RAG else "grouped")
    assert RingModel.moe_path(_model_like(step, held, moe_grouped=None), rows, True) is None


@pytest.mark.parametrize("rows, want", [(1, 0.0), (32, 0.0), (RIDGE_ROWS + 1, 1.0)])
def test_under_a_tp_axis_of_one_rank_the_ridge_alone_decides(rows, want):
    """`parallel/pipelined.py` and `parallel/ring.py` always pass their tp
    axis: on a mesh with tp = 1 a program above the ridge goes grouped as
    before PR 47, and one at or under it keeps the einsum whatever its
    share and whatever is declared around it."""
    from dnet_tpu.ops.moe import whole_batch

    flat = jnp.zeros((1, rows, 8))
    idx = jnp.zeros((rows, 10), jnp.int32)

    def one(x):
        out, _ = moe_apply(
            "auto", x, idx, idx.astype(jnp.float32), None, 256, 0.0, 10, "tp",
            lambda: jnp.zeros(()), grouped_fn=lambda: jnp.ones(()),
        )
        return out

    with whole_batch():
        assert float(jax.vmap(one, axis_name="tp")(flat)[0]) == want


def test_resolve_moe_impl_refuses_an_unknown_name():
    with pytest.raises(ValueError, match="unknown moe_impl"):
        resolve_moe_impl("groupped", 8, 1, True)


def test_auto_is_the_default_and_the_ridge_is_256():
    from dnet_tpu.config import ComputeSettings

    assert ComputeSettings().moe_impl == "auto" and RIDGE_ROWS == 256


# ---- through the engines ----------------------------------------------


def _tiny(family, d):
    from tests.fakes import checkpoints as ck

    if family == "cmdaplus-share":
        from benchmarks.harness.weights import write_checkpoint
        from tests.benchmarks.test_bench_cohere2_moe import tiny_config

        cfg = tiny_config(max_position_embeddings=1024)
        write_checkpoint(d, cfg, seed=2**31 + 31, dtype="float32")
        return cfg
    make = {"qwen3_moe": ck.make_tiny_qwen3_moe, "mixtral": ck.make_tiny_mixtral,
            "deepseek_v2": ck.make_tiny_deepseek_v2, "qwen3_next": ck.make_tiny_qwen3_next,
            "mistral4": ck.make_tiny_mistral4}[family]
    return make(d)


@pytest.fixture(scope="module")
def tiny_dirs(tmp_path_factory):
    made = {}

    def get(family):
        if family not in made:
            d = tmp_path_factory.mktemp(f"grouped_{family.replace('-', '_')}")
            made[family] = (_tiny(family, d), d)
        return made[family]

    return get


def _prefill_logits(model_dir, impl, ids, max_seq=128):
    """A fresh engine an impl: the path branches at trace time."""
    from dnet_tpu.core.engine import LocalEngine

    eng = LocalEngine(model_dir, max_seq=max_seq, param_dtype="float32")
    eng.model.moe_impl = impl
    out = np.asarray(eng.prefill("n", ids), np.float32)
    eng.close()
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_engine_prefill_grouped_matches_dense(family, tiny_dirs):
    cfg, d = tiny_dirs(family)
    rng = np.random.default_rng(5)
    ids = [int(i) for i in rng.integers(1, cfg["vocab_size"], size=45)]  # 19 padded rows
    dense = _prefill_logits(d, "dense", ids)
    grouped = _prefill_logits(d, "grouped", ids)
    np.testing.assert_allclose(grouped, dense, atol=1e-4, rtol=1e-4)


def test_gpt_oss_stays_dense_under_every_exact_name(tmp_path):
    from dnet_tpu.models import get_ring_model_cls
    from tests.fakes.checkpoints import make_tiny_gpt_oss

    make_tiny_gpt_oss(tmp_path)
    assert get_ring_model_cls("gpt_oss").moe_grouped is False
    ids = list(range(3, 40))
    np.testing.assert_array_equal(
        _prefill_logits(tmp_path, "grouped", ids), _prefill_logits(tmp_path, "dense", ids)
    )


def test_a_model_without_routed_experts_books_no_rows(tmp_path):
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.obs import metric
    from tests.fakes.checkpoints import make_tiny_llama

    make_tiny_llama(tmp_path)
    fam = metric("dnet_moe_expert_rows_total")
    before = fam.total()
    eng = LocalEngine(tmp_path, max_seq=64, param_dtype="float32")
    assert eng.model.moe_path(2048) is None
    eng.prefill("n", list(range(3, 30)))
    eng.close()
    assert fam.total() == before


@pytest.fixture
def paged_env(monkeypatch):
    from dnet_tpu.config import reset_settings_cache

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    yield monkeypatch
    reset_settings_cache()


def _rows(path):
    from dnet_tpu.obs import metric

    return metric("dnet_moe_expert_rows_total").labels(path=path).value


@pytest.mark.parametrize("chunks", [1, 2, "1-interpreted"])
def test_wide_chunks_give_the_logits_of_one_whole_prefill(chunks, tiny_dirs, paged_env):
    """`auto`, nothing forced: a 300-token prompt is one 512-row program
    (grouped) or a 288-row one and a 12-token rest (grouped, then dense);
    either way the logits are those of the dense engine's whole prefill,
    and the rows are booked under the path each program took."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    if chunks == "1-interpreted":  # the chip's kernels, interpreted
        from dnet_tpu.config import reset_settings_cache

        paged_env.setenv("DNET_FLASH_INTERPRET", "1")
        reset_settings_cache()
        chunks = 1
    cfg, d = tiny_dirs("qwen3_moe")
    rng = np.random.default_rng(11)
    ids = [int(i) for i in rng.integers(1, cfg["vocab_size"], size=300)]
    want = _prefill_logits(d, "dense", ids, max_seq=1024)
    eng = BatchedEngine(d, slots=3, max_seq=1024, param_dtype="float32")
    assert eng.kv_pool is not None and eng.eng.model.moe_impl == "auto"
    g0, d0 = _rows("grouped"), _rows("dense")
    eng.reserve_slot("a")
    cuts = [300] if chunks == 1 else [288, 300]
    start = 0
    for end in cuts:
        logits = eng.prefill_chunk("a", ids[start:end])
        start = end
    np.testing.assert_allclose(np.asarray(logits, np.float32), want, atol=1e-4, rtol=1e-4)
    assert _rows("grouped") - g0 == 512 and _rows("dense") - d0 == (0 if chunks == 1 else 16)
    dec = DecodingParams(temperature=0.0)
    res = eng.adopt_prefilled("a", logits, dec)
    tok = eng.token_result("a", res, step=0, decoding=dec).token_id
    out, errs = eng.decode_batch({"a": (tok, dec)})
    assert not errs and "a" in out
    # the decode step is a `slots`-row program: dense, and booked so
    assert _rows("grouped") - g0 == 512 and _rows("dense") - d0 == (3 if chunks == 1 else 19)
    eng.close()


def _served_tokens(d, impl, prompts, steps, kernels=False):
    """Greedy tokens of `prompts` decoded side by side through the paged
    engine, one lane each; and the rows its steps booked by path."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    eng = BatchedEngine(d, slots=len(prompts), max_seq=128, param_dtype="float32")
    eng.eng.model.moe_impl = impl
    dec = DecodingParams(temperature=0.0)
    last, got = {}, {n: [] for n in prompts}
    for n, ids in prompts.items():
        last[n] = int(eng.prefill_and_sample(n, ids, dec).token[0])
    g0, d0 = _rows("grouped"), _rows("dense")
    for s in range(1, steps + 1):
        out, errs = eng.decode_batch({n: (last[n], dec) for n in prompts})
        assert not errs
        for n in prompts:
            last[n] = eng.token_result(n, out[n], step=s, decoding=dec).token_id
            got[n].append(last[n])
    booked = (_rows("grouped") - g0, _rows("dense") - d0)
    eng.close()
    return got, booked


@pytest.mark.parametrize(
    "family, kernels",
    [("qwen3_next", False), ("qwen3_next", True), ("cmdaplus-share", False), ("mistral4", False)],
    ids=["qwen3_next", "qwen3_next-interpreted", "cmdaplus-share", "mistral4"],
)
def test_a_paged_decode_step_reads_only_the_chosen_experts_and_keeps_its_tokens(
    family, kernels, tiny_dirs, paged_env
):
    """`auto`, nothing forced: two lanes of a share (16 held of 32 routed,
    top-4; 16 of 128, top-8; 4 of 8, top-2) choose under half of the held
    experts, so the step's routed experts go grouped: out of the stack in
    place in all three families' scans, through the hybrid store, the
    pools by kind and the latent pool.  The tokens are the dense run's and
    every step's rows are booked grouped."""
    if kernels:  # the chip's kernels, interpreted: the step's 8 rows padded to a tile
        from dnet_tpu.config import reset_settings_cache

        paged_env.setenv("DNET_FLASH_INTERPRET", "1")
        reset_settings_cache()
    cfg, d = tiny_dirs(family)
    rng = np.random.default_rng(47)
    prompts = {
        n: [int(i) for i in rng.integers(1, cfg["vocab_size"], size=size)]
        for n, size in (("a", 21), ("b", 13))
    }
    want, (g, dn) = _served_tokens(d, "dense", prompts, steps=6)
    assert g == 0 and dn == 2 * 6
    from dnet_tpu.ops import moe

    layers, real = [], moe.grouped_matmul
    paged_env.setattr(
        moe, "grouped_matmul", lambda xs, w, sizes, layer=None: layers.append(layer)
        or real(xs, w, sizes, layer),
    )
    got, (g, dn) = _served_tokens(d, "auto", prompts, steps=6)
    assert got == want
    assert g == 2 * 6 and dn == 0
    # the layer scan handed the kernel the stack and the layer's index
    assert layers and all(layer is not None for layer in layers)


def test_lanes_vmapped_over_a_one_row_program_keep_the_einsum(tiny_dirs, monkeypatch):
    """The dense-slot engine vmaps a ONE-row program over its lanes: alone
    that row would choose 0.44 of the 4 experts and go grouped (as
    LocalEngine's single stream does), but no grouped closure batches over
    lanes, and the batched einsum reads the weights once for all of them.
    Only a program that declares its rows whole (`ops/moe.py: whole_batch`)
    goes grouped under the ridge; this one says nothing, traces no grouped
    closure, and the host books the same."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.ops import moe

    cfg, d = tiny_dirs("qwen3_moe")
    eng = BatchedEngine(d, slots=2, max_seq=64, param_dtype="float32", kv_paged=False)
    model = eng.eng.model
    assert model.moe_path(1, whole=True) == "grouped"
    assert model.moe_path(1) == model.moe_path(1, whole=False) == "dense"
    dec = DecodingParams(temperature=0.0)
    tok = int(eng.prefill_and_sample("a", list(range(3, 20)), dec).token[0])
    calls, real = [], moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda *a, **kw: calls.append(1) or real(*a, **kw))
    g0, d0 = _rows("grouped"), _rows("dense")
    out, errs = eng.decode_batch({"a": (tok, dec)})
    assert not errs and (_rows("grouped") - g0, _rows("dense") - d0) == (0, 2)
    assert not calls
    eng.close()
    # .. and the declaration is the trace's: it ends with the call it is around
    flat, idx = jnp.zeros((1, 8)), jnp.zeros((1, 2), jnp.int32)
    args = ("auto", flat, idx, idx.astype(jnp.float32), None, 4, 0.0, 2, None, lambda: "dense")
    with moe.whole_batch():
        assert moe.whole_batch_declared()
        assert moe_apply(*args, grouped_fn=lambda: "grouped")[0] == "grouped"
    assert not moe.whole_batch_declared()
    assert moe_apply(*args, grouped_fn=lambda: "grouped")[0] == "dense"


def test_a_window_model_takes_a_chunk_wider_than_256_past_its_window(tiny_dirs, paged_env):
    """Budget 2048 (the default) and no chunk setting: the cap is the
    budget, the window kind's pool is sized for it, and a 300-token prompt
    (window 24) prefilled as ONE chunk, its experts grouped, then decoded
    through the pools by kind keeps the reference's log-probabilities."""
    from dnet_tpu.config import get_settings
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.kv.paged import window_blocks
    from dnet_tpu.obs.phases import KV_KIND_WINDOW
    from tests.benchmarks.test_bench_cohere2_moe import TOL, decoding, prompt, worst_error

    cfg, d = tiny_dirs("cmdaplus-share")
    assert get_settings().sched.prefill_chunk_cap() == 2048 > 256 > cfg["sliding_window"]
    eng = BatchedEngine(d, slots=2, max_seq=512, param_dtype="float32")
    wpool = eng.kv_pools[KV_KIND_WINDOW]
    assert wpool.total == 2 * window_blocks(cfg["sliding_window"], 8, 2048)
    dec, ids = decoding(), prompt(cfg, n=300)
    g0 = _rows("grouped")
    eng.reserve_slot("a")
    logits = eng.prefill_chunk("a", ids)
    assert _rows("grouped") - g0 == 512
    res = eng.adopt_prefilled("a", logits, dec)
    got = [eng.token_result("a", res, step=0, decoding=dec)]
    for s in range(1, 5):
        out, errs = eng.decode_batch({"a": (got[-1].token_id, dec)})
        assert not errs
        got.append(eng.token_result("a", out["a"], step=s, decoding=dec))
    assert worst_error(cfg, d, ids, got) < TOL
    eng.close()
