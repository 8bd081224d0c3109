"""The window's lower bound inside the two attention kernels, run in
interpret mode against the dense masked op: flash prefill (head groups
smaller than the model's heads included) and the ragged paged decode over
a kind's stacked pool, whose tables begin at a `base` block."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dnet_tpu.ops.attention import attend, causal_mask, sliding_window_mask


def rnd(i, shape):
    return jax.random.normal(jax.random.fold_in(jax.random.key(7), i), shape, jnp.float32)


@pytest.mark.parametrize("window", [0, 40, 128, 300])
@pytest.mark.parametrize(
    "H,KVH,Hd", [(8, 2, 16), (64, 4, 128)], ids=["all-heads-a-step", "two-kv-heads-a-step"]
)
def test_flash_prefill_window_matches_the_masked_dense_op(monkeypatch, window, H, KVH, Hd):
    from dnet_tpu.ops import flash_attention as fa

    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    if H == 64:
        assert fa._heads_per_step(KVH, H // KVH, Hd, Hd) == 2
    T, S, pos = 128, 512, 250
    q, k, v = rnd(1, (1, T, H, Hd)), rnd(2, (1, S, KVH, Hd)), rnd(3, (1, S, KVH, Hd))
    got = fa.flash_attend_causal(q, k, v, pos, window=window)
    mask = sliding_window_mask(T, S, pos, window) if window else causal_mask(T, S, pos)
    want = attend(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if window:  # and the bound does something
        assert float(jnp.max(jnp.abs(want - attend(q, k, v, mask=causal_mask(T, S, pos))))) > 1e-3


@pytest.mark.parametrize("impl", ["interpret", "emulate"])
@pytest.mark.parametrize("window", [0, 24, 64])
def test_paged_attend_window_over_a_kinds_stack(impl, window):
    """Tables that gave back the blocks behind the window (`base`), a pool
    with a leading layer axis and merged heads, ragged positions."""
    from dnet_tpu.kv import window_first_block
    from dnet_tpu.ops.paged_attention import paged_attend

    B, H, KVH, Hd, bt, L, N, nb = 5, 8, 2, 16, 8, 3, 64, 12
    pos = np.asarray([3, 24, 61, 70, 88])
    base = np.asarray([window_first_block(int(p), window, bt) if window else 0 for p in pos])
    rs = np.random.RandomState(0)
    tables = np.zeros((B, nb), np.int32)
    for b in range(B):
        need = -(-(int(pos[b]) + 1) // bt) - base[b]
        assert need <= nb
        tables[b, :need] = rs.choice(N, size=need, replace=False)
    kp, vp = rnd(4, (L, N, bt, KVH * Hd)), rnd(5, (L, N, bt, KVH * Hd))
    q, kn, vn = rnd(6, (B, 1, H, Hd)), rnd(7, (B, KVH, Hd)), rnd(8, (B, KVH, Hd))
    layer = 1
    got = paged_attend(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(pos, jnp.int32), kn, vn, impl=impl,
        window=window, base=jnp.asarray(base, jnp.int32), layer=jnp.int32(layer),
    )
    for b in range(B):
        p = int(pos[b])
        lo = max(p - window + 1, 0) if window else 0
        rows = [(tables[b, j // bt - base[b]], j % bt) for j in range(lo, p)]
        K = [kp[layer, blk, r].reshape(KVH, Hd) for blk, r in rows] + [kn[b]]
        V = [vp[layer, blk, r].reshape(KVH, Hd) for blk, r in rows] + [vn[b]]
        want = attend(q[b:b + 1], jnp.stack(K)[None], jnp.stack(V)[None])
        np.testing.assert_allclose(np.asarray(got[b:b + 1]), np.asarray(want), atol=2e-5)


def test_the_kinds_custom_calls_have_names_of_their_own():
    from dnet_tpu.ops import flash_attention as fa
    from dnet_tpu.ops import paged_attention as pa

    assert (pa.PAGED_NAME, pa.PAGED_WINDOW_NAME) == ("paged_attend", "paged_attend_window")
    assert (fa.FLASH_NAME, fa.FLASH_WINDOW_NAME) == ("flash_prefill", "flash_prefill_window")
