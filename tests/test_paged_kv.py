"""Paged KV subsystem: allocator invariants, COW/sharing, backpressure,
and paged-vs-dense engine parity (ISSUE 3 acceptance)."""

from types import SimpleNamespace

import numpy as np
import pytest

from dnet_tpu.core.types import DecodingParams
from dnet_tpu.kv import (
    BlockPool,
    KindStore,
    KVPoolExhausted,
    PagedKVConfig,
    PagedPrefixCache,
    PageTable,
)
from dnet_tpu.obs import metric, reset_obs

pytestmark = pytest.mark.core


def make_pool(bt=4, blocks=8):
    return BlockPool(PagedKVConfig(block_tokens=bt, pool_blocks=blocks))


# ---- allocator unit ------------------------------------------------------


def test_alloc_free_refcount_invariants():
    pool = make_pool(bt=4, blocks=8)
    a = pool.alloc(3)
    assert pool.used == 3 and pool.free == 5
    sh = pool.share(a[:2])
    assert pool.used == 3  # shared blocks count ONCE
    assert all(pool.refcount(b) == 2 for b in sh)
    pool.check_conservation([a, sh])
    assert pool.free_blocks(sh) == 0  # refs drop, nothing freed yet
    assert pool.free_blocks(a) == 3
    assert pool.used == 0 and pool.free == 8
    pool.check_conservation([])


def test_alloc_is_all_or_nothing_and_typed():
    pool = make_pool(bt=4, blocks=4)
    pool.alloc(3)
    before = pool.free
    with pytest.raises(KVPoolExhausted) as ei:
        pool.alloc(2)
    assert pool.free == before  # no partial allocation
    assert ei.value.need == 2 and ei.value.total == 4
    pool.check_conservation()


def test_ensure_grows_table_by_token_count():
    pool = make_pool(bt=4, blocks=8)
    t = PageTable()
    assert len(pool.ensure(t, 1)) == 1
    assert pool.ensure(t, 4) == []  # still covered by one block
    assert len(pool.ensure(t, 9)) == 2  # 3 blocks for 9 tokens
    assert len(t.blocks) == 3
    pool.release_table(t)
    assert pool.used == 0


def test_cow_allocates_and_counts():
    reset_obs()
    pool = make_pool(bt=4, blocks=4)
    (orig,) = pool.alloc(1)
    pool.share([orig])
    new = pool.cow(orig)
    assert new != orig
    assert pool.refcount(orig) == 1 and pool.refcount(new) == 1
    assert metric("dnet_kv_cow_copies_total").value == 1


def test_gauges_track_pool_state():
    reset_obs()
    pool = make_pool(bt=4, blocks=6)
    a = pool.alloc(2)
    assert metric("dnet_kv_blocks_used").labels(kind="full").value == 2
    assert metric("dnet_kv_blocks_free").labels(kind="full").value == 4
    assert metric("dnet_kv_pool_blocks").labels(kind="full").value == 6
    pool.free_blocks(a)
    assert metric("dnet_kv_blocks_used").labels(kind="full").value == 0
    with pytest.raises(KVPoolExhausted):
        pool.require(7)
    assert metric("dnet_kv_admission_rejected_total").value == 1


# ---- device store + paged prefix cache ----------------------------------


class _FlatKVModel:
    """Minimal init_kv provider with the flat [L, B, S, KVH, Hd] layout:
    two layers, all of the full kind."""

    paged_kinds = None
    layers = range(2)
    config = SimpleNamespace(num_key_value_heads=2, head_dim=4)

    def init_kv(self, n_layers, batch, max_seq, dtype="float32",
                quant_bits=0, rotating=True):
        from dnet_tpu.core.kvcache import KVConfig, init_cache

        return init_cache(
            KVConfig(n_layers, batch, max_seq, n_kv_heads=2, head_dim=4,
                     dtype=dtype, quant_bits=quant_bits)
        )


def _store(model, cfg, **kw):
    return KindStore(model, {"full": cfg}, "float32", **kw)


def _row(model, n_layers, seq, fill):
    import jax

    kv = model.init_kv(n_layers, 1, seq)
    return jax.tree.map(lambda a: a + fill, kv)


def test_store_commit_gather_roundtrip():
    """commit_row -> gather_row: a staged row's blocks land where the table
    says and come back in order; a second commit rewrites one block only."""
    cfg = PagedKVConfig(block_tokens=4, pool_blocks=8)
    model = _FlatKVModel()
    store = _store(model, cfg)
    row = _row(model, 2, 16, 7.0)  # [2, 1, 16, 2, 4] all 7s
    store.commit_row(row, [0, 1, 2, 3], [5, 6, 1, 2])
    dense = store.gather_row([5, 6, 1, 2], 16)
    np.testing.assert_array_equal(np.asarray(dense["k"]), np.asarray(row["k"]))
    # commit a mutated block 2 and re-gather (3 blocks: a padded width)
    import jax

    dense2 = jax.tree.map(lambda a: a * 2, dense)
    store.commit_row(dense2, [2], [1])
    out = store.gather_row([5, 6, 1], 16)
    np.testing.assert_array_equal(
        np.asarray(out["k"][:, :, 8:12]), np.asarray(row["k"][:, :, 8:12]) * 2
    )
    np.testing.assert_array_equal(
        np.asarray(out["k"][:, :, :8]), np.asarray(row["k"][:, :, :8])
    )
    store.commit_row(dense2, [], [])  # nothing to write: no program runs


@pytest.mark.parametrize("impl", ["emulate", "interpret"])
def test_commit_gather_and_attend_by_layer_match_dense_attention(impl):
    """The merged layout end to end, float32: staged rows of ragged lengths
    (a partial last block; a three-block commit padded to four by repeating
    its last pair) are committed to scattered blocks, gathered back equal,
    and `paged_attend(layer=)` over the pool AS IT IS KEPT, every layer's
    stack passed whole, equals dense attention over each row with the new
    token written at its position."""
    import jax
    import jax.numpy as jnp

    from dnet_tpu.ops.attention import attend
    from dnet_tpu.ops.paged_attention import paged_attend

    L, KVH, Hd, H, bt, S = 3, 2, 16, 4, 8, 32

    class Model(_FlatKVModel):
        layers = range(L)
        config = SimpleNamespace(num_key_value_heads=KVH, head_dim=Hd)

    cfg = PagedKVConfig(block_tokens=bt, pool_blocks=16)
    store = _store(Model(), cfg)
    assert store.kv["full"]["k"].shape == (L, 16, bt, KVH * Hd)
    rng = np.random.default_rng(38)
    lengths = [5, 19, 24]  # live rows before the step: 1, 3 (padded to 4) and 3 blocks
    phys = [[9], [4, 13, 2], [7, 0, 11]]
    rows = []
    for n, blocks in zip(lengths, phys):
        row = {leaf: jnp.asarray(rng.normal(size=(L, 1, S, KVH, Hd)).astype(np.float32))
               for leaf in ("k", "v")}
        rows.append(row)
        store.commit_row(row, list(range(len(blocks))), blocks)
        back = store.gather_row(blocks, S)
        live = len(blocks) * bt
        for leaf in ("k", "v"):
            assert back[leaf].shape == (L, 1, S, KVH, Hd)
            np.testing.assert_array_equal(
                np.asarray(back[leaf][:, :, :live]), np.asarray(row[leaf][:, :, :live])
            )
    # the pad repeated the pair (2 -> 2): no other block was touched
    np.testing.assert_array_equal(np.asarray(store.kv["full"]["k"][:, 1]), 0.0)
    tables = np.zeros((3, S // bt), np.int32)
    for b, blocks in enumerate(phys):
        tables[b, : len(blocks)] = blocks
    pos = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, H, Hd)).astype(np.float32))
    k_new, v_new = (jnp.asarray(rng.normal(size=(3, KVH, Hd)).astype(np.float32)) for _ in "kv")
    pool = store.kv["full"]
    for layer in range(L):
        got = paged_attend(
            q, pool["k"], pool["v"], jnp.asarray(tables), pos, k_new, v_new,
            impl=impl, layer=jnp.int32(layer),
        )
        for b, n in enumerate(lengths):
            k = rows[b]["k"][layer].at[0, n].set(k_new[b])
            v = rows[b]["v"][layer].at[0, n].set(v_new[b])
            want = attend(q[b : b + 1], k, v, mask=(jnp.arange(S) <= n)[None, :])
            np.testing.assert_allclose(
                np.asarray(got[b]), np.asarray(want[0]), rtol=1e-5, atol=1e-5
            )


def test_paged_prefix_store_dedups_blocks():
    reset_obs()
    cfg = PagedKVConfig(block_tokens=4, pool_blocks=16)
    model = _FlatKVModel()
    pool = BlockPool(cfg)
    store = _store(model, cfg)
    cache = PagedPrefixCache(pool, store, capacity=4, min_tokens=4)
    base = list(range(100, 108))  # 8 tokens = 2 full blocks
    cache.store(base, _row(model, 2, 16, 1.0))
    used_after_first = pool.used  # 2 blocks
    assert used_after_first == 2
    # the grown-history turn: first 8 tokens shared, 4 new
    cache.store(base + [1, 2, 3, 4], _row(model, 2, 16, 2.0))
    assert pool.used == used_after_first + 1  # tail block only
    assert metric("dnet_kv_prefix_shared_blocks_total").value == 2
    # a hit hands out the entry's blocks (one reference each), which
    # gather into a private dense row
    hit = cache.lookup_blocks(base + [1, 2, 3, 4, 9])
    assert hit is not None
    n, blocks, n_full = hit
    assert (n, n_full) == (12, 3)
    kv_row = store.gather_row(blocks, 16)
    assert kv_row["k"].shape[2] == 16
    assert float(kv_row["k"][0, 0, 8, 0, 0]) == 2.0  # the grown turn's tail
    pool.free_blocks(blocks)
    pool.check_conservation()
    cache.clear()
    assert pool.used == 0


def test_paged_prefix_eviction_releases_blocks():
    cfg = PagedKVConfig(block_tokens=4, pool_blocks=16)
    model = _FlatKVModel()
    pool = BlockPool(cfg)
    store = _store(model, cfg)
    cache = PagedPrefixCache(pool, store, capacity=2, min_tokens=4)
    for base in (10, 20, 30):  # third store evicts the first (LRU)
        cache.store([base + i for i in range(8)], _row(model, 2, 16, 1.0))
    assert pool.used == 4  # two live entries x 2 blocks
    pool.check_conservation()


# ---- engine integration (paged vs dense parity + acceptance) -------------


@pytest.fixture
def paged_env(monkeypatch):
    """Small blocks so tiny prompts span several; settings cache reset
    around the env mutation (repo test idiom)."""
    from dnet_tpu.config import reset_settings_cache

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    yield
    reset_settings_cache()


@pytest.fixture(scope="module")
def dense_ref(tiny_llama_dir):
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(
        tiny_llama_dir, slots=4, max_seq=64, param_dtype="float32",
        kv_paged=False,
    )
    yield eng
    eng.close()


def _paged_engine(tiny_llama_dir, **kw):
    from dnet_tpu.core.batch import BatchedEngine

    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("param_dtype", "float32")
    return BatchedEngine(tiny_llama_dir, kv_paged=True, **kw)


PROMPTS = {
    "va": [256, 72, 101],                      # short
    "vb": [256, 84, 104, 105, 110, 3, 9, 12, 44, 7, 81],  # spans 2 blocks
    "vc": list(range(300, 318)),               # spans 3 blocks
}


def _interleaved_greedy(eng, prompts, steps):
    dec = DecodingParams(temperature=0.0)
    last, got = {}, {}
    for n, ids in prompts.items():
        eng.end_session(n)
        res = eng.prefill_and_sample(n, ids, dec)
        last[n] = int(res.token[0])
        got[n] = [last[n]]
    for _ in range(steps - 1):
        out, errs = eng.decode_batch({n: (last[n], dec) for n in prompts})
        assert not errs
        for n, res in out.items():
            last[n] = int(res.token[0])
            got[n].append(last[n])
    for n in prompts:
        eng.end_session(n)
    return got


def test_paged_matches_dense_streams(tiny_llama_dir, dense_ref, paged_env):
    """>= 3 concurrent variable-length sessions: byte-identical greedy
    token streams to the dense path, peak block usage strictly below the
    dense-equivalent block count (acceptance criterion)."""
    reset_obs()
    want = _interleaved_greedy(dense_ref, PROMPTS, 6)
    eng = _paged_engine(tiny_llama_dir)
    try:
        assert eng.kv_pool is not None and eng.kv is None
        got = _interleaved_greedy(eng, PROMPTS, 6)
        assert got == want
        bt = eng._kv_cfg.block_tokens
        dense_equiv_blocks = eng.slots * (eng.max_seq // bt)
        assert 0 < eng.kv_pool.peak_used < dense_equiv_blocks
        assert eng.kv_pool.used == 0  # every table released
        eng.kv_pool.check_conservation()
    finally:
        eng.close()


def test_paged_chunked_decode_matches_dense(tiny_llama_dir, dense_ref, paged_env):
    """A budget never changes the stream, over the pool as on dense slots:
    every call's dispatch carries one step, across a block's edge (16)."""
    dec = DecodingParams(temperature=0.0)
    sent = metric("dnet_decode_dispatch_total")
    slot_steps = metric("dnet_decode_slot_steps_total")

    def run(eng):
        eng.end_session("ck")
        res = eng.prefill_and_sample("ck", PROMPTS["vb"], dec)
        toks = [int(res.token[0])]
        while len(toks) < 12:
            before = sent.value, slot_steps.value
            out, errs = eng.decode_batch(
                {"ck": (toks[-1], dec)}, budgets={"ck": 12 - len(toks)}
            )
            assert not errs
            assert (sent.value, slot_steps.value) == (before[0] + 1, before[1] + eng.slots)
            toks.append(int(out["ck"].token[0]))
        eng.end_session("ck")
        return toks

    want = run(dense_ref)
    eng = _paged_engine(tiny_llama_dir)
    try:
        assert run(eng) == want
        eng.kv_pool.check_conservation()
    finally:
        eng.close()


@pytest.mark.parametrize("family", ["llama", "qwen3", "qwen3_moe"])
def test_one_kind_pool_matches_dense_slots_by_family(family, tmp_path, paged_env):
    """Every llama-family model of one kind serves from the one pool layout:
    interleaved greedy streams byte-identical to dense slots, and a second
    turn that aliases the first's full blocks and diverges INSIDE the third
    (COW) stays byte-identical too."""
    from dnet_tpu.core.batch import BatchedEngine

    from tests.fakes import checkpoints

    reset_obs()
    d = tmp_path
    getattr(checkpoints, f"make_tiny_{family}")(d)  # qwen3_moe: every layer routed
    kw = dict(slots=4, max_seq=64, param_dtype="float32")
    dense = BatchedEngine(d, kv_paged=False, **kw)
    eng = BatchedEngine(d, kv_paged=True, prefix_cache_size=4, **kw)
    try:
        assert isinstance(eng.kv_store, KindStore) and eng.kv is None
        pool = eng.kv_store.kv["full"]["k"]
        c = eng.config
        assert pool.shape[0] == c.num_hidden_layers and pool.ndim == 4
        assert pool.shape[2:] == (8, c.num_key_value_heads * c.head_dim)
        eng.paged_prefix.min_tokens = 8
        assert _interleaved_greedy(eng, PROMPTS, 6) == _interleaved_greedy(dense, PROMPTS, 6)
        dec = DecodingParams(temperature=0.0)
        base = list(range(60, 80))  # 20 tokens: 2 full blocks + 4 in a third

        def turns(e):
            got = []
            for nonce, ids in (("t1", base), ("t2", base + [7, 2])):
                res = e.prefill_and_sample(nonce, ids, dec)
                toks = [int(res.token[0])]
                for _ in range(5):
                    out, errs = e.decode_batch({nonce: (toks[-1], dec)})
                    assert not errs
                    toks.append(int(out[nonce].token[0]))
                got.append(toks)
            for nonce in ("t1", "t2"):
                e.end_session(nonce)
            return got

        assert turns(eng) == turns(dense)
        assert metric("dnet_kv_prefix_shared_blocks_total").value >= 2
        assert metric("dnet_kv_cow_copies_total").value >= 1
        eng.paged_prefix.clear()
        eng.kv_pool.check_conservation()
        assert eng.kv_pool.used == 0
    finally:
        eng.close()
        dense.close()


def test_prefix_sharing_pair_aliases_blocks(tiny_llama_dir, paged_env):
    """A prefix-sharing pair reports shared blocks > 0 and fewer unique
    blocks than two unshared sessions would pin (acceptance criterion)."""
    reset_obs()
    eng = _paged_engine(tiny_llama_dir, prefix_cache_size=4)
    try:
        eng.paged_prefix.min_tokens = 8
        dec = DecodingParams(temperature=0.0)
        base = list(range(260, 276))  # 16 tokens = 2 full blocks of 8
        eng.prefill_and_sample("p1", base, dec)  # stores on completion
        used_single = eng.kv_pool.used
        eng.prefill_and_sample("p2", base + [1, 2, 3], dec)  # hit: aliases
        shared = metric("dnet_kv_prefix_shared_blocks_total").value
        assert shared > 0
        # p2 pinned only its non-shared tail, not a full copy of the prefix
        unshared_equiv = used_single + eng._kv_cfg.blocks_for(len(base) + 3)
        assert eng.kv_pool.used < unshared_equiv
        # both sessions decode fine after the COW split
        out, errs = eng.decode_batch({"p1": (5, dec), "p2": (5, dec)})
        assert not errs and set(out) == {"p1", "p2"}
        eng.end_session("p1")
        eng.end_session("p2")
        eng.kv_pool.check_conservation()
    finally:
        eng.close()


def test_cow_on_mid_block_divergence(tiny_llama_dir, dense_ref, paged_env):
    """A prompt diverging INSIDE a shared block must COW that block: the
    sharer's stream stays byte-identical to dense, the original's partial
    block is never mutated, and the copy is counted."""
    reset_obs()
    eng = _paged_engine(tiny_llama_dir, prefix_cache_size=4)
    try:
        eng.paged_prefix.min_tokens = 8
        dec = DecodingParams(temperature=0.0)
        base = list(range(260, 280))  # 20 tokens: 2 full blocks + 4 in a 3rd
        grown = base + [7, 2]

        def stream(e, nonce, ids, steps):
            res = e.prefill_and_sample(nonce, ids, dec)
            toks = [int(res.token[0])]
            for _ in range(steps - 1):
                out, errs = e.decode_batch({nonce: (toks[-1], dec)})
                assert not errs
                toks.append(int(out[nonce].token[0]))
            return toks

        want_base = stream(dense_ref, "cb", base, 6)
        want_grown = stream(dense_ref, "cg", grown, 6)
        dense_ref.end_session("cb")
        dense_ref.end_session("cg")

        got_base = [stream(eng, "b", base, 1)[0]]
        # adoption shares 2 full blocks, COWs the partial third
        got_grown = stream(eng, "g", grown, 6)
        assert got_grown == want_grown
        assert metric("dnet_kv_cow_copies_total").value >= 1
        assert metric("dnet_kv_prefix_shared_blocks_total").value >= 2
        # the original keeps decoding out of its UN-mutated partial block
        for _ in range(5):
            out, errs = eng.decode_batch({"b": (got_base[-1], dec)})
            assert not errs
            got_base.append(int(out["b"].token[0]))
        assert got_base == want_base
        eng.end_session("b")
        eng.end_session("g")
        eng.kv_pool.check_conservation()
    finally:
        eng.close()


def test_pool_exhaustion_is_typed_backpressure(tiny_llama_dir, paged_env, monkeypatch):
    """Admission fails with KVPoolExhausted before burning prefill; decode
    extension fails the starved lane ALONE, and freed sessions re-admit."""
    from dnet_tpu.config import reset_settings_cache

    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "3")
    reset_settings_cache()
    reset_obs()
    eng = _paged_engine(tiny_llama_dir, slots=3)
    try:
        dec = DecodingParams(temperature=0.0)
        t1 = eng.prefill_and_sample("e1", list(range(100, 108)), dec)  # 1 blk
        eng.prefill_and_sample("e2", list(range(200, 216)), dec)  # 2 blks
        # pool is now full: admission refuses a third prompt cleanly
        with pytest.raises(KVPoolExhausted):
            eng.prefill_and_sample("e3", list(range(50, 66)), dec)
        assert "e3" not in eng.slot_of  # failed admission left no residue
        # e1 sits at pos 8 (block boundary): its next step needs a block
        # the pool doesn't have — IT fails, with the typed message
        out, errs = eng.decode_batch({"e1": (int(t1.token[0]), dec)})
        assert "e1" in errs and "exhausted" in errs["e1"]
        assert not out
        # freeing e2 returns blocks; e1 proceeds
        eng.end_session("e2")
        out, errs = eng.decode_batch({"e1": (int(t1.token[0]), dec)})
        assert not errs and "e1" in out
        eng.end_session("e1")
        eng.kv_pool.check_conservation()
    finally:
        eng.close()
        reset_settings_cache()


def test_rotating_swa_model_refused_and_falls_back(tmp_path, paged_env):
    """gpt_oss rotating ring buffers are NOT block-addressable: the store
    guard must probe the SESSION layout (the pool probe alone flattens it)
    and the engine must fall back to dense slots instead of committing
    mod-W rows under absolute-position block geometry."""
    from tests.fakes.checkpoints import make_tiny_gpt_oss

    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    d = tmp_path / "gpt_oss"
    cfg_d = make_tiny_gpt_oss(d)
    cfg = ModelConfig.from_hf(cfg_d)
    model = get_ring_model_cls("gpt_oss")(cfg, range(cfg.num_hidden_layers))
    with pytest.raises(NotImplementedError):
        _store(model, PagedKVConfig(block_tokens=8, pool_blocks=8), session_tokens=64)
    eng = BatchedEngine(
        d, slots=2, max_seq=64, param_dtype="float32", kv_paged=True
    )
    try:
        assert eng.kv_pool is None and eng.kv is not None  # dense fallback
    finally:
        eng.close()


def test_explicit_dense_overrides_the_derived_pool(tiny_llama_dir):
    """kv_paged=False is the dense engine whatever kv_layout would derive
    (this model and cache take the pool: the engine built with no argument
    beside it shows so), and the prefix capacity goes to the inner engine's
    snapshot cache."""
    from dnet_tpu.core.batch import BatchedEngine

    kw = dict(slots=2, max_seq=64, param_dtype="float32", prefix_cache_size=4)
    eng = BatchedEngine(tiny_llama_dir, kv_paged=False, **kw)
    derived = BatchedEngine(tiny_llama_dir, **kw)
    try:
        assert eng.kv_pool is None and eng.kv is not None
        assert eng.eng.prefix_cache is not None and eng.paged_prefix is None
        assert derived.kv_pool is not None and derived.kv is None
        assert derived.eng.prefix_cache is None and derived.paged_prefix is not None
    finally:
        eng.close()
        derived.close()


def test_paged_fallback_keeps_dense_prefix_cache(tiny_llama_dir, monkeypatch):
    """When paged init fails (block size not dividing max_seq), the engine
    must fall back to dense slots WITH the configured prefix cache — not
    silently drop it."""
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "48")  # does not divide 64
    reset_settings_cache()
    eng = BatchedEngine(
        tiny_llama_dir, slots=2, max_seq=64, param_dtype="float32",
        kv_paged=True, prefix_cache_size=4,
    )
    try:
        assert eng.kv_pool is None and eng.kv is not None
        assert eng.eng.prefix_cache is not None
    finally:
        eng.close()
        reset_settings_cache()


def test_a_lane_the_pool_cannot_extend_fails_alone_while_the_others_step(
    tiny_llama_dir, paged_env, monkeypatch
):
    """One pass over the lanes, one token each: the lane that finds no block
    is refused ALONE, with the typed message and nothing hoarded, while the
    lane before it (which took the last block) and the lane after it (which
    needs none) take their step.  A budget changes none of it."""
    from dnet_tpu.config import reset_settings_cache

    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "4")
    reset_settings_cache()
    eng = _paged_engine(tiny_llama_dir, slots=3)
    try:
        dec = DecodingParams(temperature=0.0)
        last = {}
        # r1, r2: one full block each (bt=8), pos at the edge; r3 has room
        for n, k in (("r1", 8), ("r2", 8), ("r3", 5)):
            res = eng.prefill_and_sample(n, list(range(100, 100 + k)), dec)
            last[n] = int(res.token[0])
        assert eng.kv_pool.free == 1
        out, errs = eng.decode_batch(
            {n: (t, dec) for n, t in last.items()}, budgets=dict.fromkeys(last, 16)
        )
        assert set(out) == {"r1", "r3"}
        assert errs == {"r2": "paged KV pool exhausted: need 1 block(s), 0 free of 4"}
        assert eng.kv_pool.free == 0
        pos = {n: int(eng.pos[eng.slot_of[n]]) for n in last}
        assert pos == {"r1": 9, "r2": 8, "r3": 6}
        assert [len(eng._tables[eng.slot_of[n]].blocks) for n in last] == [2, 1, 1]
        # the refused lane lost nothing: a block comes back and it steps
        eng.end_session("r3")
        out, errs = eng.decode_batch({"r2": (last["r2"], dec)})
        assert not errs and set(out) == {"r2"}
        eng.end_session("r1")
        eng.end_session("r2")
        eng.kv_pool.check_conservation()
    finally:
        eng.close()
        reset_settings_cache()


def test_sweep_returns_blocks_to_free_list(tiny_llama_dir, paged_env):
    eng = _paged_engine(tiny_llama_dir)
    try:
        dec = DecodingParams(temperature=0.0)
        eng.prefill_and_sample("s1", list(range(100, 110)), dec)
        eng.prefill_and_sample("s2", list(range(200, 220)), dec)
        assert eng.kv_pool.used > 0
        eng.last_used[:] = 0.0  # everything looks ancient
        assert eng.sweep_sessions(ttl_s=1.0) >= 2
        assert eng.kv_pool.used == 0 and eng.kv_pool.free == eng.kv_pool.total
        eng.kv_pool.check_conservation([])
    finally:
        eng.close()
