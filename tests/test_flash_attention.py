"""Pallas flash-attention prefill kernel vs the dense op (interpret mode)."""

import numpy as np
import pytest
import jax.numpy as jnp

from dnet_tpu.ops.attention import attend, causal_mask

pytestmark = pytest.mark.core


@pytest.fixture(autouse=True)
def _force_kernel(monkeypatch):
    # run the REAL kernel via the pallas interpreter on CPU
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


@pytest.mark.parametrize(
    "B,T,H,KVH,Hd,S,pos",
    [
        (1, 16, 4, 4, 16, 32, 0),  # MHA, fresh cache
        (2, 32, 4, 2, 16, 64, 8),  # GQA, continued session
        (1, 8, 8, 2, 32, 8, 0),  # T == S, 4x grouping
        (1, 64, 2, 1, 16, 256, 96),  # long cache, late chunk (MQA)
    ],
)
def test_matches_dense_causal(rng, B, T, H, KVH, Hd, S, pos):
    from dnet_tpu.ops.flash_attention import flash_attend_causal, flash_eligible

    q = _rand(rng, B, T, H, Hd)
    k = _rand(rng, B, S, KVH, Hd)
    v = _rand(rng, B, S, KVH, Hd)
    assert flash_eligible(q, k, v)
    ref = attend(q, k, v, mask=causal_mask(T, S, pos))
    out = flash_attend_causal(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_asymmetric_v_head_dim(rng):
    """MLA layout: K caches qk_head_dim but V caches v_head_dim."""
    from dnet_tpu.ops.flash_attention import flash_attend_causal, flash_eligible

    q = _rand(rng, 1, 16, 4, 24)  # qk head dim 24
    k = _rand(rng, 1, 32, 4, 24)
    v = _rand(rng, 1, 32, 4, 16)  # v head dim 16
    assert flash_eligible(q, k, v)
    ref = attend(q, k, v, mask=causal_mask(16, 32, 2))
    out = flash_attend_causal(q, k, v, 2)
    assert out.shape == (1, 16, 4, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_attention_sinks(rng):
    """GPT-OSS sinks: a virtual key absorbing softmax mass, folded into
    the flash denominator exactly once at emit."""
    from dnet_tpu.ops.flash_attention import flash_attend_causal

    q = _rand(rng, 1, 16, 4, 16)
    k = _rand(rng, 1, 32, 2, 16)
    v = _rand(rng, 1, 32, 2, 16)
    sinks = jnp.asarray(np.linspace(-1.0, 2.0, 4), jnp.float32)
    ref = attend(q, k, v, mask=causal_mask(16, 32, 4), sinks=sinks)
    out = flash_attend_causal(q, k, v, 4, sinks=sinks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_custom_scale(rng):
    from dnet_tpu.ops.flash_attention import flash_attend_causal

    q, k, v = _rand(rng, 1, 16, 2, 16), _rand(rng, 1, 32, 2, 16), _rand(rng, 1, 32, 2, 16)
    scale = 0.33
    ref = attend(q, k, v, mask=causal_mask(16, 32, 4), scale=scale)
    out = flash_attend_causal(q, k, v, 4, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_garbage_slots_never_attended(rng):
    """Cache slots past pos+T must not influence the output (they hold
    stale garbage between sessions)."""
    from dnet_tpu.ops.flash_attention import flash_attend_causal

    T, S, pos = 8, 64, 4
    q = _rand(rng, 1, T, 2, 16)
    k = _rand(rng, 1, S, 2, 16)
    v = _rand(rng, 1, S, 2, 16)
    out = flash_attend_causal(q, k, v, pos)
    k2 = k.at[:, pos + T:].set(1e4)  # poison unreachable slots
    v2 = v.at[:, pos + T:].set(-1e4)
    out2 = flash_attend_causal(q, k2, v2, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=0, rtol=0)


def test_decode_width_falls_back(rng, monkeypatch):
    """T=1 is not prefill-eligible (flash_eligible False) — it routes to
    the split-K decode kernel (ops/flash_decode.py) — and stays
    causal-exact either way."""
    from dnet_tpu.ops.flash_attention import flash_attend_causal, flash_eligible

    q, k, v = _rand(rng, 1, 1, 2, 16), _rand(rng, 1, 32, 2, 16), _rand(rng, 1, 32, 2, 16)
    assert not flash_eligible(q, k, v)
    ref = attend(q, k, v, mask=causal_mask(1, 32, 7))
    out = flash_attend_causal(q, k, v, 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_bf16_inputs(rng):
    from dnet_tpu.ops.flash_attention import flash_attend_causal

    q = _rand(rng, 1, 16, 2, 16).astype(jnp.bfloat16)
    k = _rand(rng, 1, 32, 2, 16).astype(jnp.bfloat16)
    v = _rand(rng, 1, 32, 2, 16).astype(jnp.bfloat16)
    ref = attend(q, k, v, mask=causal_mask(16, 32, 0))
    out = flash_attend_causal(q, k, v, 0)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )


# ---- the key axis is bounded by the chunk's position ----------------------


def _tile_geom(T, S, window):
    from dnet_tpu.ops.flash_attention import _pick_tile

    bq, bk = _pick_tile(T, 128), _pick_tile(S, 128)
    return dict(bq=bq, bk=bk, n_s=S // bk, window=window, xp=np)


def _live_by_the_mask(pos, T, S, window, bq, bk):
    """[T / bq, S / bk] bool: the tile pair holds a (query, key) pair the
    mask keeps, by enumerating the mask a q tile at a time."""
    live = []
    k_pos = np.arange(S)[None, :]
    for t0 in range(0, T, bq):
        q_pos = pos + t0 + np.arange(bq)[:, None]
        keep = k_pos <= q_pos
        if window:
            keep &= k_pos > q_pos - window
        live.append(keep.reshape(bq, S // bk, bk).any(axis=(0, 2)))
    return np.stack(live)


@pytest.mark.parametrize("tight", [False, True], ids=["row33792", "row_ends_with_chunk"])
@pytest.mark.parametrize("window", [0, 512, 4096])
@pytest.mark.parametrize("T", [128, 256, 2048])
@pytest.mark.parametrize("pos", [0, 128, 300, 9216])
def test_the_live_range_is_the_masks_and_no_dead_tile_is_copied(pos, T, window, tight):
    """The helper the index maps, the body and the grid's bound all read,
    against a brute-force enumeration of the mask: every tile that holds
    an unmasked pair is in the q tile's range and no other is; walking the
    k / v index map over the grid's steps yields each live tile once, in
    order, and nothing else (a repeated index is not copied); the host's
    count is the mask's."""
    from dnet_tpu.ops.flash_attention import (
        _kv_steps,
        _kv_tile,
        _live_tiles,
        flash_tiles,
    )

    S = -(-(pos + T) // 128) * 128 if tight else 33792
    geom = _tile_geom(T, S, window)
    bq, bk = geom["bq"], geom["bk"]
    want = _live_by_the_mask(pos, T, S, window, bq, bk)
    steps = int(_kv_steps(pos, T, **geom))
    most = 0
    for tq in range(T // bq):
        lo, hi = (int(x) for x in _live_tiles(pos, tq, **geom))
        assert list(np.flatnonzero(want[tq])) == list(range(lo, hi + 1)), (tq, lo, hi)
        walked = [int(_kv_tile(pos, tq, s, **geom)) for s in range(steps)]
        copied = [t for i, t in enumerate(walked) if i == 0 or t != walked[i - 1]]
        assert copied == list(range(lo, hi + 1)), (tq, walked)
        most = max(most, hi - lo + 1)
    # the bound holds every q tile's live tiles; without a window it is
    # exactly the most any q tile folds
    assert steps >= most
    if not window:
        assert steps == most
    else:
        assert steps <= (window + bq - 2) // bk + 2
    folded, skipped = flash_tiles(pos, T, S, window)
    assert folded == int(want.sum())
    assert folded + skipped == (T // bq) * (S // bk)


def test_the_hosts_count_at_the_lat_cells_mean_chunk():
    """A 2048-row chunk at 9216 of a 33792-row staged row: 16 q tiles of
    72 + 1 .. 72 + 16 live tiles, of 264 each in the old grid."""
    from dnet_tpu.ops.flash_attention import flash_tiles

    folded, skipped = flash_tiles(9216, 2048, 33792)
    assert folded == sum(73 + i for i in range(16)) == 1288
    assert skipped == 16 * 264 - 1288
    assert 0.6 < skipped / (folded + skipped) < 0.75
    assert flash_tiles(0, 1, 4096) is None  # a decode row: not this kernel


def _poison(k, v, pos, T, window, bk):
    """NaN in every key row no query row attends (past pos + T; behind the
    first row's window), and in every value row of a tile that holds no
    attended row at all; 1e4 in the unattended value rows of a tile that
    also holds attended ones (their weight is an exact 0.0, and
    0.0 * NaN is not)."""
    S = k.shape[1]
    rows = np.arange(S)
    first = max(pos - window + 1, 0) if window else 0
    dead = (rows >= pos + T) | (rows < first)
    tile = rows // bk
    dead_tile = (tile > (pos + T - 1) // bk) | (tile < first // bk)
    k = jnp.where(dead[None, :, None, None], jnp.nan, k)
    v = jnp.where(dead[None, :, None, None], 1e4, v)
    return k, jnp.where(dead_tile[None, :, None, None], jnp.nan, v)


@pytest.mark.parametrize(
    "T,S,H,KVH,Hd,Vd,pos,window,sinks",
    [
        (256, 1024, 4, 4, 24, 16, 300, 0, False),  # the lat geometry in small: G = 1, Vd != Hd
        (256, 1024, 4, 4, 24, 16, 0, 0, False),  # a first chunk
        (128, 1024, 4, 4, 24, 16, 896, 0, False),  # the chunk ends the row
        (256, 1024, 8, 2, 16, 16, 300, 0, True),  # GQA with sinks, pos not a multiple of bk
        (256, 1024, 8, 2, 16, 16, 300, 256, False),  # a window inside the chunk's reach
        (128, 1024, 8, 2, 16, 16, 640, 200, True),  # a window that is no multiple of bk, sinks
        (32, 160, 4, 2, 16, 16, 40, 24, False),  # tiles of 32: S takes no 128
    ],
)
def test_garbage_in_dead_tiles_changes_no_bit(rng, T, S, H, KVH, Hd, Vd, pos, window, sinks):
    """NaN in every row past pos + T (and behind a window) leaves the
    output as it is, bit for bit: a tile clamped one too far would show.
    The clean output is the dense op's to rounding."""
    from dnet_tpu.ops.attention import sliding_window_mask
    from dnet_tpu.ops.flash_attention import _pick_tile, flash_attend_causal

    q, k, v = _rand(rng, 1, T, H, Hd), _rand(rng, 1, S, KVH, Hd), _rand(rng, 1, S, KVH, Vd)
    sk = jnp.asarray(np.linspace(-1.0, 2.0, H), jnp.float32) if sinks else None
    bk = _pick_tile(S, 128)
    clean = flash_attend_causal(q, k, v, pos, sinks=sk, window=window)
    k2, v2 = _poison(k, v, pos, T, window, bk)
    if pos + T < S:  # (a chunk that ends the row leaves nothing past it)
        assert bool(jnp.isnan(k2).any()) and bool(jnp.isnan(v2).any())
    got = flash_attend_causal(q, k2, v2, pos, sinks=sk, window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    mask = sliding_window_mask(T, S, pos, window) if window else causal_mask(T, S, pos)
    ref = attend(q, k, v, mask=mask, sinks=sk)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _tiles_booked():
    from dnet_tpu.obs import metric
    from dnet_tpu.obs.phases import FLASH_TILE_STATES, KV_KINDS

    fam = metric("dnet_flash_tiles_total")
    return {
        (kind, state): fam.labels(kind=kind, state=state).value
        for kind in KV_KINDS for state in FLASH_TILE_STATES
    }


def test_a_chunk_books_its_tiles_once_a_layer(tmp_path, monkeypatch):
    """dnet_flash_tiles_total: prefill_chunk books, for each layer that
    attends through the kernel, the chunk's padded rows against the staged
    row by the kernel's own range; nothing where no kernel runs."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.ops.flash_attention import flash_tiles
    from tests.fakes.checkpoints import make_tiny_llama

    cfg = make_tiny_llama(tmp_path)
    L = cfg["num_hidden_layers"]
    eng = BatchedEngine(tmp_path, slots=2, max_seq=256, param_dtype="float32")
    try:
        before = _tiles_booked()
        eng.reserve_slot("a")
        ids = list(range(3, 3 + 150))  # four chunks of 32 and a ragged one, padded to 32
        for i in range(0, len(ids), 32):
            eng.prefill_chunk("a", ids[i:i + 32])
        after = _tiles_booked()
        moved = {k: after[k] - before[k] for k in after}
        # a 256-row staged row is two kv tiles of 128; a 32-row chunk is
        # one q tile: the chunks at 0 .. 96 fold the first alone, the one
        # at 128 both
        assert [flash_tiles(p, 32, 256) for p in (0, 96, 128)] == [(1, 1), (1, 1), (2, 0)]
        assert moved == {
            ("full", "folded"): 6 * L, ("full", "skipped"): 4 * L,
            ("window", "folded"): 0, ("window", "skipped"): 0,
        }
        # no kernel, nothing to engage
        monkeypatch.delenv("DNET_FLASH_INTERPRET")
        eng.reserve_slot("b")
        eng.prefill_chunk("b", ids[:32])
        assert _tiles_booked() == after
    finally:
        eng.close()


def test_which_layers_attend_through_the_kernel_is_the_models_to_say():
    """`RingModel.flash_layers`: a state layer keeps no keys, a window
    layer bounds them below by the model's window."""
    from types import SimpleNamespace

    from dnet_tpu.models.base import RingModel

    def layers(**kw):
        return RingModel.flash_layers(SimpleNamespace(layers=[0, 1, 2, 3], **kw))

    assert layers(paged_kinds=None) == (("full", 0),) * 4
    assert layers(paged_kinds=("state",) * 4) == ()
    assert layers(paged_kinds=("state", "state", "state", "full")) == (("full", 0),)
    assert layers(paged_kinds=("window",) * 3 + ("full",), window=4096) == (
        ("window", 4096),) * 3 + (("full", 0),)
