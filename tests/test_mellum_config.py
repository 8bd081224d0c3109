"""What Mellum2 forced outside its own model file, held by arithmetic and
small shapes, no device: `rope_parameters` nested by layer type
(models/base.py), the grouped matmul's column tiles from the shape
(ops/moe.py), and the two-kind store's tables at a window NARROWER than a
prefill chunk (window 1024, blocks of 128, chunks of 2048: kv/paged.py).
"""

import copy
import json
from pathlib import Path

import numpy as np
import pytest

from dnet_tpu.models.base import ModelConfig, rope_parameters_by_type

ROOT = Path(__file__).resolve().parents[1]
BENCH_KEYS = ("assumed", "deployment", "serve", "check", "rehearse")
STANDING = ("brumby-14b-8l", "command-a-plus-4l-ep8", "minicpm-sala-8l",
            "mistral-small-4-119b-6l-ep8", "qwen3-30b-a3b-6l", "qwen3-next-80b-a3b-4l-ep2")
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
DEFAULT = {"rope_type": "default", "rope_theta": 500000}


def hf(name: str) -> dict:
    full = json.loads((ROOT / "benchmarks" / "configs" / f"{name}.json").read_text())
    return {k: v for k, v in full.items() if k not in BENCH_KEYS}


def mellum(**over) -> dict:
    return {**hf("mellum2-12b-a2.5b-8l"), **over}


# ---- rope_parameters -------------------------------------------------------
def before_this_pr(d: dict):
    """(rope_theta, rope_scaling) as `ModelConfig.from_hf` read them at the
    parent commit: `rope_parameters` as ONE flat group."""
    rope = d.get("rope_parameters") or {}
    scaling = d.get("rope_scaling")
    if scaling is None and rope.get("rope_type", rope.get("type", "default")) != "default":
        scaling = rope
    return d.get("rope_theta") or rope.get("rope_theta", 10000.0), scaling


@pytest.mark.parametrize("name", STANDING)
def test_a_standing_configs_model_config_is_what_it_was(name):
    d = hf(name)
    for cfg in (d, {**d, **json.loads(
            (ROOT / "benchmarks" / "configs" / f"{name}.json").read_text())["rehearse"]["config"]}):
        mc = ModelConfig.from_hf(cfg)
        assert (mc.rope_theta, mc.rope_scaling) == before_this_pr(cfg)
        assert mc.rope_by_type is None  # a flat group, or none: nothing by type
        assert mc == ModelConfig.from_hf(copy.deepcopy(cfg))


@pytest.mark.parametrize("rope", [
    None, {}, {"rope_theta": 50000, "rope_type": "default"},
    {"rope_type": "yarn", "rope_theta": 1e6, "factor": 4, "original_max_position_embeddings": 4096},
    {"type": "llama3", "rope_theta": 5e5, "factor": 8},
], ids=["none", "empty", "flat-default", "flat-yarn", "flat-llama3"])
def test_a_flat_group_reads_as_it_did(rope):
    d = {"model_type": "llama", "vocab_size": 32, "hidden_size": 16, "num_hidden_layers": 2,
         "num_attention_heads": 2, "layer_types": ["full_attention"] * 2}
    if rope is not None:
        d["rope_parameters"] = rope
    mc = ModelConfig.from_hf(d)
    assert (mc.rope_theta, mc.rope_scaling) == before_this_pr(d) and mc.rope_by_type is None


def test_a_group_nested_by_layer_type_yields_a_table_a_type():
    mc = ModelConfig.from_hf(mellum())
    assert mc.rope_by_type == {
        "sliding_attention": (500000.0, None), "full_attention": (500000.0, YARN)}
    # the flat fields hold the first type's: read as ONE flat group this
    # config had no rope_type, took "default", and theta fell to 10000.0
    assert mc.rope_theta == 500000 and mc.rope_scaling is None
    assert before_this_pr(mellum()) == (10000.0, None)


@pytest.mark.parametrize("order", [("sliding_attention", "full_attention"),
                                   ("full_attention", "sliding_attention")])
def test_the_types_come_in_the_order_the_layers_use_them(order):
    by = rope_parameters_by_type({"full_attention": YARN, "sliding_attention": DEFAULT},
                                 [order[0]] * 3 + [order[1]])
    assert tuple(by) == order


def test_a_type_the_layers_use_and_the_group_lacks_is_refused():
    d = mellum(rope_parameters={"full_attention": YARN})
    with pytest.raises(ValueError, match="sliding_attention"):
        ModelConfig.from_hf(d)


@pytest.mark.parametrize("types", [["sparse", "dense"], ["dense"] * 8, ["sparse"] * 7 + ["moe"]])
def test_an_mlp_layer_type_other_than_sparse_is_refused(types):
    with pytest.raises(NotImplementedError, match="mlp_layer_types"):
        ModelConfig.from_hf(mellum(mlp_layer_types=types))


def test_a_model_of_one_table_is_refused_a_config_whose_types_differ():
    from dnet_tpu.models import get_ring_model_cls

    d = mellum(model_type="qwen3_moe")
    with pytest.raises(NotImplementedError, match="differ by layer type"):
        get_ring_model_cls("qwen3_moe")(ModelConfig.from_hf(d), range(8))
    same = mellum(model_type="qwen3_moe",
                  rope_parameters={"full_attention": DEFAULT, "sliding_attention": DEFAULT})
    get_ring_model_cls("qwen3_moe")(ModelConfig.from_hf(same), range(8))  # equal tables: fine


def test_the_models_two_tables_are_the_rows():
    from dnet_tpu.models import get_ring_model_cls
    from dnet_tpu.models.cohere2_moe import KIND_FULL, KIND_WINDOW

    from benchmarks.reference.mellum import rope_table

    model = get_ring_model_cls("mellum")(ModelConfig.from_hf(mellum()), range(8))
    assert model.rope_scale == {KIND_WINDOW: 1.0, KIND_FULL: 1.2772588722239782}
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(np.asarray(model.inv_freq[KIND_WINDOW]), plain, rtol=1e-6)
    full = np.asarray(model.inv_freq[KIND_FULL])
    # the fastest dimensions keep their frequency, the slowest are slowed x 16
    np.testing.assert_allclose(full[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(full[-8:], plain[-8:] / 16, rtol=1e-6)
    # the reference writes both out from the row's numbers, apart from the program
    for kind, group in ((KIND_WINDOW, DEFAULT), (KIND_FULL, YARN)):
        inv, scale = rope_table(group, 128, 131072)
        np.testing.assert_allclose(np.asarray(model.inv_freq[kind]), inv, rtol=1e-6)
        assert scale == model.rope_scale[kind]


@pytest.mark.parametrize("qk_norm", [True, False])
def test_qk_norm_is_honoured_both_ways(qk_norm, tmp_path):
    from tests.fakes.checkpoints import make_tiny_mellum

    from dnet_tpu.core.engine import LocalEngine

    make_tiny_mellum(tmp_path, qk_norm=qk_norm)
    eng = LocalEngine(tmp_path, max_seq=32, param_dtype="float32")
    assert eng.model.qk_norm is qk_norm
    assert ("q_norm" in eng.window_params) is qk_norm


def test_a_window_of_fewer_layers_needs_its_kinds():
    import jax.numpy as jnp

    from dnet_tpu.models import get_ring_model_cls

    model = get_ring_model_cls("mellum")(ModelConfig.from_hf(mellum()), range(8))
    with pytest.raises(NotImplementedError, match="layer_kinds"):
        model.apply_window({"wq": jnp.zeros((2, 1, 1, 1))}, jnp.zeros((1, 1, 1)), {}, 0)


# ---- the grouped matmul's column tiles ---------------------------------------
@pytest.mark.parametrize("width,tile", [
    (512, 512), (768, 768), (1024, 1024), (2048, 1024), (4096, 1024),  # today's widths: today's tiles
    (2304, 768), (896, 896),  # Mellum2's: the tile divides the side
    (32, 32), (64, 64), (7168, 1024), (1100, 1024),  # tiny sides whole; no divisor: the cap, masked
])
def test_a_column_tile_comes_from_the_shape(width, tile):
    from dnet_tpu.ops.moe import GROUP_TILE_COLS, group_tile_cols

    assert group_tile_cols(width) == tile
    if width in (512, 768, 1024, 2048, 4096):
        assert tile == min(width, GROUP_TILE_COLS)  # what every standing cell's program had


def test_every_standing_configs_expert_sides_keep_their_tile():
    from dnet_tpu.ops.moe import GROUP_TILE_COLS, group_tile_cols

    sides = set()
    for name in STANDING:
        d = hf(name)
        if not d.get("num_experts") and not d.get("n_routed_experts"):
            continue
        sides |= {d["hidden_size"], d.get("moe_intermediate_size") or d["intermediate_size"]}
    assert sides == {512, 768, 2048, 4096}
    assert all(group_tile_cols(s) == min(s, GROUP_TILE_COLS) for s in sides)


def test_the_grouped_matmul_at_mellums_sides_is_the_ragged_dot(monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dnet_tpu.ops.moe import grouped_matmul

    K, N, G, M = 2304, 896, 4, 64
    kx, kw = jax.random.split(jax.random.key(54))
    xs = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (G, K, N), jnp.float32) * 0.02
    sizes = jnp.asarray([16, 0, 32, 16], jnp.int32)
    want = lax.ragged_dot(xs, w, sizes)
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    from dnet_tpu.ops import kernel_select

    assert kernel_select.kernel_backend() == "interpret"
    got = grouped_matmul(xs, w, sizes)  # tiles (16, 768, 896): three k steps, one n step
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    down = grouped_matmul(got, jnp.swapaxes(w, 1, 2), sizes)  # [M, 896] x [G, 896, 2304]
    np.testing.assert_allclose(
        np.asarray(down), np.asarray(lax.ragged_dot(want, jnp.swapaxes(w, 1, 2), sizes)),
        rtol=2e-4, atol=2e-4,
    )


# ---- the two-kind store at window 1024 < chunk 2048 ---------------------------
W, BT, CHUNK = 1024, 128, 2048


def test_a_lanes_window_table_holds_twenty_five_blocks_at_most():
    from dnet_tpu.kv import window_blocks

    assert window_blocks(W, BT, CHUNK) == 25
    # nothing says window >= chunk: a wider window only adds its own blocks
    assert window_blocks(4096, BT, 256) == 35 and window_blocks(W, BT, 2) == 10


@pytest.mark.parametrize("n", [1, 1023, 1024, 1025, 2048, 12000, 16384, 35500, 65536])
def test_adoption_takes_the_blocks_the_next_token_still_reaches(n):
    """After a prompt of n tokens the window kind's table holds the blocks
    from `window_first_block` to the prompt's last: nine at most, of the
    twenty-five a lane may hold."""
    from dnet_tpu.kv import PagedKVConfig, window_first_block

    first = window_first_block(n, W, BT)
    nb = PagedKVConfig(BT, 4096).blocks_for(n)
    live = nb - first
    assert 1 <= live <= 9
    # the next token, at position n, attends keys n - 1023 .. n: the first of them is held
    assert first * BT <= max(n - W + 1, 0) < (first + 1) * BT
    if n >= W + BT:
        assert live >= 8  # the window's own eight blocks, and the edge


@pytest.mark.parametrize("prompt,steps", [(12000, 48), (16384, 1024), (65536, 1024), (1030, 300)])
def test_decode_gives_back_behind_the_window_and_never_outgrows_the_lane(prompt, steps):
    """release_behind and ensure, step by step as `_extend_window_tables`
    calls them (two tokens ahead at most): the table never holds more than
    the lane's twenty-five blocks, every key the step attends is held, and
    the pool's books balance."""
    from dnet_tpu.kv import BlockPool, PagedKVConfig, PageTable, window_blocks, window_first_block
    from dnet_tpu.obs.phases import KV_KIND_WINDOW

    per_lane = window_blocks(W, BT, CHUNK)
    pool = BlockPool(PagedKVConfig(BT, per_lane), kind=KV_KIND_WINDOW)  # ONE lane's share
    first = window_first_block(prompt, W, BT)
    nb = pool.cfg.blocks_for(prompt)
    tbl = PageTable(blocks=pool.alloc(nb - first), base=first)
    most, released = len(tbl.blocks), 0
    for pos in range(prompt, prompt + steps):
        released += pool.release_behind(tbl, window_first_block(pos, W, BT))
        pool.ensure(tbl, pos + 2)  # the step in flight and the one chained to it
        most = max(most, len(tbl.blocks))
        lo, hi = max(pos - W + 1, 0), pos  # the keys the token at `pos` attends
        assert tbl.base * BT <= lo and hi < (tbl.base + len(tbl.blocks)) * BT
    assert most <= 10 < per_lane
    assert released == window_first_block(prompt + steps - 1, W, BT) - first
    pool.check_conservation([tbl.blocks])
    assert pool.release_table(tbl) > 0 and pool.free == pool.total and tbl.base == 0


def test_a_lane_used_again_after_a_longer_one_starts_from_its_own_base():
    from dnet_tpu.kv import BlockPool, PagedKVConfig, PageTable, window_blocks, window_first_block
    from dnet_tpu.obs.phases import KV_KIND_WINDOW

    pool = BlockPool(PagedKVConfig(BT, 2 * window_blocks(W, BT, CHUNK)), kind=KV_KIND_WINDOW)
    held = {}
    for n in (65536, 16384):
        first = window_first_block(n, W, BT)
        tbl = PageTable(blocks=pool.alloc(pool.cfg.blocks_for(n) - first), base=first)
        held[n] = (tbl.base, len(tbl.blocks))
        pool.release_table(tbl)
        assert pool.free == pool.total
    assert held[65536] == (504, 8) and held[16384] == (120, 8)
