"""Block-sparse attention by an index of pooled keys
(ops/sparse_attention.py) at small sizes on the CPU: the index, the choice
and both reads against the definition by explicit masks, which is itself
held to a query-by-query numpy form written from the equations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.ops import sparse_attention as SA

IMPLS = ("emulate", "interpret")
CFG = SA.SparseConfig(
    kernel_size=4, kernel_stride=2, block_size=8, topk=6, init_blocks=1,
    window_size=16, dense_len=64,
)
TOL = 2e-5
S, H, KVH, HD = 256, 4, 2, 16


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    # tiles of two blocks, so that 256 keys are 16 tiles a q tile can skip
    monkeypatch.setattr(SA, "PREFILL_TILE", 16)


def qkv(seed=0, scale=2.0):
    key = jax.random.key(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (S, H, HD)) * scale
    k = jax.random.normal(jax.random.fold_in(key, 1), (S, KVH, HD))
    v = jax.random.normal(jax.random.fold_in(key, 2), (S, KVH, HD))
    return q, k, v


def by_hand(q, k, v, t, cfg=CFG):
    """The query at position t, a KV head at a time, from the equations:
    (the output [H, Hd], the chosen blocks a KV head)."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    G = H // KVH
    K, s, bs = cfg.kernel_size, cfg.kernel_stride, cfg.block_size
    out, picked = np.zeros((H, HD)), []
    for g in range(KVH):
        qb = t // bs
        if t + 1 <= cfg.dense_len:
            blocks = list(range(qb + 1))
        else:
            js = [j for j in range(S // s) if s * j + K - 1 <= t]
            c = np.stack([k[s * j:s * j + K, g].mean(0) for j in js])
            r = np.zeros(len(js))
            for h in range(g * G, (g + 1) * G):
                sc = c @ q[t, h] / np.sqrt(HD)
                p = np.exp(sc - sc.max())
                r += p / p.sum()
            rpb, reach = bs // s, K // s - 1
            first_window = qb - cfg.window_blocks + 1
            cand = list(range(cfg.init_blocks, first_window))
            R = {
                b: max(r[j] for j in range(rpb * b - reach, rpb * b + rpb) if 0 <= j < len(js))
                for b in cand
            }
            best = sorted(cand, key=lambda b: (-R[b], b))[: cfg.n_best]
            blocks = sorted(set(range(cfg.init_blocks)) | set(best) | set(range(first_window, qb + 1)))
        picked.append(blocks)
        toks = [i for b in blocks for i in range(b * bs, (b + 1) * bs) if i <= t]
        for h in range(g * G, (g + 1) * G):
            sc = k[toks, g] @ q[t, h] / np.sqrt(HD)
            p = np.exp(sc - sc.max())
            out[h] = (p / p.sum()) @ v[toks, g]
    return out, picked


def test_the_config_refuses_sizes_that_do_not_nest():
    assert SA.SparseConfig().n_best == 31 and SA.SparseConfig().list_blocks == 128
    assert SA.SparseConfig().blocks_attended(8192) == 128 and SA.SparseConfig().blocks_attended(8193) == 64
    with pytest.raises(ValueError, match="do not nest"):
        SA.SparseConfig(block_size=48)
    with pytest.raises(ValueError, match="does not fit"):
        SA.SparseConfig(topk=200)
    assert SA.SparseConfig.from_hf({"topk": 48, "unknown": 1}).topk == 48


@pytest.mark.parametrize("t", [0, 7, 40, 63, 64, 65, 100, 127, 200, 255])
def test_the_definition_is_the_equations(t):
    """dense up to context 64, sparse past it; 63 / 64 is the edge."""
    q, k, v = qkv()
    want, picked = by_hand(q, k, v, t)
    got = SA.sparse_attend_dense(q[t:t + 1], k, v, t, CFG)[0]
    assert np.max(np.abs(np.asarray(got) - want)) < TOL
    assert all(len(b) == (CFG.topk if t >= 64 else t // 8 + 1) for b in picked)


def test_pooled_keys_are_means_of_overlapping_spans():
    _, k, _ = qkv()
    c = SA.pooled_keys(k.reshape(S, KVH * HD), CFG)
    assert c.shape == (S // 2, KVH * HD)
    for j in (0, 1, 37, S // 2 - 2):
        assert np.allclose(c[j], np.asarray(k.reshape(S, -1))[2 * j:2 * j + 4].mean(0), atol=1e-6)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("form", ["chunk", "lanes"])
def test_index_scores_sum_the_heads_softmaxes_over_complete_spans(impl, form):
    q, k, _ = qkv(seed=3)
    kc = SA.pooled_keys(k.reshape(S, -1), CFG).reshape(1, S // 2, KVH, HD)
    if form == "chunk":  # 32 consecutive queries of one sequence
        qq, t0 = q[None, 96:128], jnp.array([96])
    else:  # three lanes, one query each
        qq, t0 = q[jnp.array([2, 130, 255])][:, None], jnp.array([2, 130, 255])
        kc = jnp.broadcast_to(kc, (3, *kc.shape[1:]))
    r = SA.index_scores(qq, kc, t0, CFG, impl=impl)
    want = SA.index_scores(qq, kc, t0, CFG, impl="emulate")
    assert r.shape == want.shape == (qq.shape[0], KVH, qq.shape[1], S // 2)
    assert float(jnp.max(jnp.abs(r - want))) < 1e-5
    t = np.asarray(t0)[:, None] + np.arange(qq.shape[1])[None, :]
    complete = 2 * np.arange(S // 2)[None, None, :] + 3 <= t[:, :, None]
    assert np.all(np.asarray(r)[:, 0][~complete] == 0.0)  # an incomplete span scores nothing
    sums = np.asarray(r).sum(-1)
    assert np.allclose(sums[np.broadcast_to((t >= 3)[:, None], sums.shape)], H // KVH, atol=1e-4)


def test_block_scores_take_the_spans_that_touch_a_block():
    r = jnp.zeros((1, 32)).at[0, 7].set(0.5).at[0, 9].set(0.25).at[0, 3].set(0.125)
    R = np.asarray(SA.block_scores(r, CFG))[0]  # blocks of 4 spans; a span reaches 1 back
    assert R.shape == (8,)
    assert R[0] == 0.125 and R[1] == 0.5 and R[2] == 0.5 and R[3] == 0.0  # span 7 ends in block 2


def test_the_choice_breaks_ties_to_the_lower_index():
    nb = 32
    t = jnp.array([nb * 8 - 1])  # the last position: qb 31, window blocks 30-31, candidates 1-29
    R = jnp.zeros((1, nb)).at[0, 20].set(0.5).at[0, 5].set(0.25).at[0, 9].set(0.25).at[0, 14].set(0.25)
    chosen = np.flatnonzero(np.asarray(SA.choose_blocks(R, t, CFG))[0])
    assert list(chosen) == [0, 5, 9, 20, 30, 31]  # 20, then the two lowest of the tied three
    flat = np.flatnonzero(np.asarray(SA.choose_blocks(jnp.zeros((1, nb)), t, CFG))[0])
    assert list(flat) == [0, 1, 2, 3, 30, 31]  # all tied at 0: the lowest three
    dense = np.flatnonzero(np.asarray(SA.choose_blocks(R, jnp.array([63]), CFG))[0])
    assert list(dense) == list(range(8))  # context 64: everything up to the query's block


def test_compact_lists_in_order_and_pads_with_the_last():
    mask = jnp.array([[0, 1, 0, 1, 1, 0], [1, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]], bool)
    ids, cnt = SA.compact(mask, 4)
    assert np.asarray(ids).tolist() == [[1, 3, 4, 4], [0, 0, 0, 0], [0, 0, 0, 0]]
    assert np.asarray(cnt).tolist() == [3, 1, 0]


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [32, 40])
def test_prefill_in_chunks_equals_the_definition(impl, chunk):
    """160 tokens: dense_len (64) is crossed INSIDE a chunk, spans complete
    at chunk edges, stale rows past the chunk are in the row."""
    q, k, v = qkv(seed=1)
    n = 160
    want = SA.sparse_attend_dense(q[:n], k, v, 0, CFG)
    outs = []
    for p in range(0, n, chunk):
        written = (jnp.arange(S) < p + chunk)[:, None, None]
        o = SA.sparse_prefill(
            q[p:p + chunk], jnp.where(written, k, 7.0), jnp.where(written, v, 7.0),
            jnp.int32(p), CFG, impl=impl,
        )
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs) - want))) < TOL


def test_a_q_tile_walks_only_the_tiles_its_queries_chose():
    """32 queries that ask the same thing choose nearly the same blocks:
    their q tile's list leaves most of the 16 tiles out, and the kernel
    that never copies those equals the definition.  (Queries that differ,
    as seeded weights make them, choose apart and their union is every
    tile: the kernel then walks the whole causal grid.)"""
    q, k, v = qkv(seed=1)
    same = jnp.broadcast_to(q[240], (32, H, HD))
    t = jnp.arange(224, 256)
    kc = SA.pooled_keys(k.reshape(S, -1), CFG).reshape(1, -1, KVH, HD)
    R = SA.block_scores(SA.index_scores(same[None], kc, jnp.array([224]), CFG)[0], CFG)
    chosen = SA.choose_blocks(R, jnp.broadcast_to(t, (KVH, 32)), CFG)
    tiles = np.asarray(jnp.any(chosen.reshape(KVH, 32, 16, 2), axis=(1, 3)))
    assert tiles.sum(-1).max() <= 8  # of 16
    assert tiles[:, 0].all() and tiles[:, 15].all()  # the first block's and the queries' own
    got = SA.flash_prefill_sparse(same, k, v, jnp.int32(224), chosen, CFG, impl="interpret")
    want = SA.sparse_attend_dense(same, k, v, 224, CFG, chosen=chosen)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("start,steps", [(160, 8), (58, 12)])
def test_decode_through_the_page_table_equals_the_definition(impl, start, steps):
    """Two lanes over pages of 16 tokens (two blocks a page) in a shuffled
    table; lane 1 steps from `start`, lane 0 idles.  (160, 8): four spans
    complete on the way.  (58, 12): dense_len is crossed inside the answer."""
    q, k, v = qkv(seed=2)
    want = SA.sparse_attend_dense(q[: start + steps], k, v, 0, CFG)
    bt, N, L, W, nb = 16, 40, 2, KVH * HD, 16
    perm = np.random.RandomState(0).permutation(N)[:nb]
    table = jnp.asarray(np.stack([np.arange(nb), perm]).astype(np.int32))
    held = (jnp.arange(S) < start)[:, None]
    kr, vr = jnp.where(held, k.reshape(S, W), 0.0), jnp.where(held, v.reshape(S, W), 0.0)
    kc = SA.pooled_keys(kr, CFG)
    pool = {"k": jnp.zeros((L, N, bt, W)), "v": jnp.zeros((L, N, bt, W)),
            "kc": jnp.zeros((L, N, bt // 2, W))}
    for lb in range(nb):  # adoption: the prompt's blocks and their pooled keys, layer 1
        rows = slice(lb * bt, (lb + 1) * bt)
        pool["k"] = pool["k"].at[1, perm[lb]].set(kr[rows])
        pool["v"] = pool["v"].at[1, perm[lb]].set(vr[rows])
        pool["kc"] = pool["kc"].at[1, perm[lb]].set(kc[lb * 8:(lb + 1) * 8])
    step = jax.jit(
        lambda pool, qq, kn, vn, pos: SA.sparse_decode(
            pool, qq, kn, vn, table, pos, jnp.array([0, 1]), jnp.int32(1), CFG, impl=impl
        ),
        donate_argnums=(0,),
    )
    for t in range(start, start + steps):
        o, pool = step(
            pool, jnp.stack([q[t] * 0, q[t]])[:, None], jnp.stack([k[t] * 0 + 5, k[t]]),
            jnp.stack([v[t] * 0 + 5, v[t]]), jnp.array([3, t]),
        )
        assert float(jnp.max(jnp.abs(o[1, 0] - want[t]))) < TOL, t
    assert bool(jnp.all(pool["k"][0] == 0)) and bool(jnp.all(pool["kc"][0] == 0))  # the other layer
    assert bool(jnp.all(pool["k"][1, 0] == 0))  # the idle lane's page took no row
    # the spans the answer completed are in the index, the mean of their keys
    full = SA.pooled_keys(k.reshape(S, W), CFG)
    for t in range(start, start + steps):
        if t >= 3 and (t - 3) % 2 == 0:
            j = (t - 3) // 2
            got = pool["kc"][1, perm[j // 8], j % 8]
            assert float(jnp.max(jnp.abs(got - full[j]))) < 1e-6, j
