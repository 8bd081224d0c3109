"""The paged pool's books by KIND of layer (kv/paged.py, kv/store.py):
full layers keep every block, window layers give back the blocks behind
the window, and each kind's accounting is exact on its own."""

import jax.numpy as jnp
import pytest

from dnet_tpu.kv import (
    BlockPool,
    KVPoolExhausted,
    PagedKVConfig,
    PageTable,
    window_blocks,
    window_first_block,
)
from dnet_tpu.obs import metric
from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_WINDOW


def gauges(kind):
    return tuple(
        int(metric(f).labels(kind=kind).value)
        for f in ("dnet_kv_blocks_used", "dnet_kv_blocks_free", "dnet_kv_pool_blocks")
    )


def test_window_first_block_is_the_first_a_next_token_reaches():
    # the token at position n attends keys > n - W
    assert window_first_block(10, 24, 8) == 0  # everything is inside
    assert window_first_block(24, 24, 8) == 0  # key 1 is the first: block 0
    assert window_first_block(31, 24, 8) == 1  # first key 8: block 1
    assert window_first_block(4500, 4096, 128) == 3
    assert window_first_block(16384, 4096, 128) == (16384 - 4095) // 128


@pytest.mark.parametrize("window,bt,step,want", [(4096, 128, 256, 35), (24, 8, 16, 6), (24, 8, 256, 36)])
def test_window_blocks_covers_window_step_and_edges(window, bt, step, want):
    assert window_blocks(window, bt, step) == want
    # the most a table holds before a dispatch of `step` tokens at any pos
    for pos in range(0, 4 * window, 7):
        held = -(-(pos + step) // bt) - window_first_block(pos, window, bt)
        assert held <= want


@pytest.mark.parametrize(
    "window,bt,cap,want",
    [(4096, 128, 256, 35), (24, 8, 16, 6), (24, 8, 1, 5)],
    ids=["the_mix_cell", "the_tiny_cell", "a_cap_under_the_step"],
)
def test_the_engine_sizes_the_window_pool_by_the_prefill_cap(monkeypatch, window, bt, cap, want):
    """`BatchedEngine._init_pool`'s own arithmetic: a slot's share of the
    window kind's pool covers the window and the widest prefill chunk
    (DNET_SCHED_PREFILL_CHUNK, else the tick's token budget), and never
    less than the two tokens a decode step may add past `pos` (the step in
    flight and the one chained to it)."""
    from types import SimpleNamespace

    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import WINDOW_STEP_TOKENS, BatchedEngine
    from dnet_tpu.kv import KindStore

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(bt))
    monkeypatch.setenv("DNET_SCHED_TOKEN_BUDGET", str(cap))
    monkeypatch.delenv("DNET_SCHED_PREFILL_CHUNK", raising=False)
    reset_settings_cache()
    try:
        slots = 3
        model = SimpleNamespace(
            paged_kinds=(KV_KIND_WINDOW,) * 3 + (KV_KIND_FULL,), window=window,
            config=SimpleNamespace(num_key_value_heads=2, head_dim=4, model_type="stub"),
            # a slot-addressed session row, as the pool cuts blocks out of
            init_kv=lambda n, b, s, dt: {
                leaf: jnp.zeros((n, b, s, 2, 4), dt) for leaf in ("k", "v")
            },
        )
        eng = BatchedEngine.__new__(BatchedEngine)
        eng.max_seq, eng.spec_lookahead, eng._tables = 2 * bt * 32, 0, [None] * slots
        eng.eng = SimpleNamespace(kv_dtype="float32", config=model.config)
        eng._init_pool(model, slots, 0)
        assert isinstance(eng.kv_store, KindStore) and eng._window == window
        wpool = eng.kv_pools[KV_KIND_WINDOW]
        assert wpool.total == slots * want
        assert want == window_blocks(window, bt, max(cap, WINDOW_STEP_TOKENS))
        assert eng.kv_store.kv[KV_KIND_WINDOW]["k"].shape[:3] == (3, slots * want, bt)
        # a decode step's table: from the window's first block at `pos`
        # through the chained step's row at pos + 1, wherever pos stands
        for pos in range(0, 3 * window, max(window // 37, 1)):
            held = (pos + 1) // bt - window_first_block(pos, window, bt) + 1
            assert held <= want, (pos, held)
    finally:
        reset_settings_cache()


def test_each_kind_keeps_exact_books_and_window_blocks_come_back():
    full = BlockPool(PagedKVConfig(block_tokens=8, pool_blocks=32))
    win = BlockPool(PagedKVConfig(block_tokens=8, pool_blocks=12), kind=KV_KIND_WINDOW)
    W, bt = 24, 8
    assert gauges(KV_KIND_FULL) == (0, 32, 32) and gauges(KV_KIND_WINDOW) == (0, 12, 12)
    released0 = metric("dnet_kv_window_blocks_released_total").value

    n = 61  # a prompt longer than the window
    first = window_first_block(n, W, bt)
    tf = PageTable(blocks=full.alloc(full.cfg.blocks_for(n)))
    tw = PageTable(blocks=win.alloc(win.cfg.blocks_for(n) - first), base=first)
    assert len(tf.blocks) == 8 and (tw.base, len(tw.blocks)) == (4, 4)
    for pos in range(n, n + 40):  # decode: release behind, then grow
        win.release_behind(tw, window_first_block(pos, W, bt))
        win.ensure(tw, pos + 1)
        full.ensure(tf, pos + 1)
        assert tw.base * bt <= pos - W + 1 < (tw.base + 1) * bt  # holds the window's first key
        assert (tw.base + len(tw.blocks)) * bt > pos  # and the new token's row
        assert len(tw.blocks) <= window_blocks(W, bt, 1)
        for pool in (full, win):
            pool.check_conservation()
            assert pool.used + pool.free == pool.total
        assert gauges(KV_KIND_WINDOW) == (win.used, win.free, 12)
        assert gauges(KV_KIND_FULL) == (full.used, full.free, 32)
    assert len(tf.blocks) == 13 and win.used == len(tw.blocks) <= 5
    assert metric("dnet_kv_window_blocks_released_total").value - released0 == tw.base - first > 0
    win.release_table(tw)
    full.release_table(tf)
    assert (tw.base, win.used, full.used) == (0, 0, 0)


def test_release_behind_never_frees_past_the_table():
    pool = BlockPool(PagedKVConfig(block_tokens=8, pool_blocks=4), kind=KV_KIND_WINDOW)
    t = PageTable(blocks=pool.alloc(2), base=3)
    assert pool.release_behind(t, 2) == 0 and t.base == 3  # nothing is behind
    assert pool.release_behind(t, 9) == 2 and (t.base, t.blocks) == (5, [])
    pool.check_conservation()
    assert pool.free == 4
    pool.ensure(t, 6 * 8)  # grows from its base, not from zero
    assert len(t.blocks) == 1 and pool.used == 1


def test_admission_counts_a_prompts_need_by_kind():
    """can_cover by kind: the full kind needs the whole prompt, the window
    kind at most what one slot's table ever holds."""
    from types import SimpleNamespace

    from dnet_tpu.sched.policy import SchedulerPolicy

    bt, W, slots = 8, 24, 2
    per_slot = window_blocks(W, bt, 16)
    full = BlockPool(PagedKVConfig(bt, 20))
    win = BlockPool(PagedKVConfig(bt, slots * per_slot), kind=KV_KIND_WINDOW)
    engine = SimpleNamespace(kv_pool=full, kv_pools={KV_KIND_FULL: full, KV_KIND_WINDOW: win},
                             _kv_cfg=full.cfg, max_seq=256, slots=slots)
    req = lambda n: SimpleNamespace(ids=list(range(n)))  # noqa: E731
    assert SchedulerPolicy.admissible(req(100), engine)  # 13 full blocks, 6 window
    assert not SchedulerPolicy.admissible(req(200), engine)  # 26 full blocks > 20
    held = win.alloc(win.total - per_slot + 1)  # less than one slot's share left
    assert not SchedulerPolicy.admissible(req(100), engine)
    assert SchedulerPolicy.admissible(req(30), engine)  # a short prompt needs 4
    win.free_blocks(held)
    with pytest.raises(KVPoolExhausted):
        win.alloc(win.total + 1)
    del engine.kv_pools[KV_KIND_WINDOW]  # a model of one kind: the full pool alone
    assert SchedulerPolicy.admissible(req(100), engine)


def test_a_staged_rows_commit_has_one_window_width_whatever_the_prompt():
    """The commit program is compiled once a power of two of the FULL kind's
    blocks: the window kind's list always has the one width a window table
    can reach, so two prompts of nearly one length (32 or 33 window blocks)
    never ask for a program the warm-up did not make."""
    from types import SimpleNamespace

    from dnet_tpu.kv import KindStore

    bt = 8
    model = SimpleNamespace(
        paged_kinds=(KV_KIND_WINDOW,) * 3 + (KV_KIND_FULL,), window=24,
        config=SimpleNamespace(num_key_value_heads=2, head_dim=4),
    )
    cfgs = {KV_KIND_FULL: PagedKVConfig(bt, 64), KV_KIND_WINDOW: PagedKVConfig(bt, 12)}
    store = KindStore(model, cfgs, "float32", window_width=6)
    assert store.layers == {KV_KIND_WINDOW: (0, 1, 2), KV_KIND_FULL: (3,)}
    seen = []
    store._commit = lambda kv, row, idx, phys: seen.append(
        {k: (int(idx[k].shape[0]), int(phys[k].shape[0])) for k in idx}) or kv
    for n_full, n_win in ((5, 4), (5, 5), (7, 3), (9, 4)):
        store.commit_staged({}, {KV_KIND_FULL: (list(range(n_full)), list(range(n_full))),
                              KV_KIND_WINDOW: (list(range(n_win)), list(range(n_win)))})
    assert [s[KV_KIND_WINDOW] for s in seen] == [(6, 6)] * 4
    assert [s[KV_KIND_FULL] for s in seen] == [(8, 8), (8, 8), (8, 8), (16, 16)]


@pytest.mark.parametrize("dropped", [(), (1,), (0, 2)])
def test_a_steps_rows_land_where_indexing_by_layer_block_and_row_puts_them(dropped):
    """`KindStore.append_in_program` writes one row a slot through the pool's
    [L*N*bt, W] view (so the compiler need not relayout the pool around the
    write); it must put every row exactly where `pool[:, phys, off]` does,
    layer by layer and kind by kind, and drop a lane whose block is past
    the pool's end."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    import numpy as np

    from dnet_tpu.kv import KindStore

    bt, slots = 8, 3
    model = SimpleNamespace(
        paged_kinds=(KV_KIND_WINDOW, KV_KIND_FULL, KV_KIND_WINDOW, KV_KIND_WINDOW), window=24,
        config=SimpleNamespace(num_key_value_heads=2, head_dim=4),
    )
    cfgs = {KV_KIND_FULL: PagedKVConfig(bt, 10), KV_KIND_WINDOW: PagedKVConfig(bt, 6)}
    store = KindStore(model, cfgs, "float32")
    rng = np.random.default_rng(7)
    pool = {
        kind: {leaf: jnp.asarray(rng.normal(size=p.shape).astype(np.float32)) for leaf, p in leaves.items()}
        for kind, leaves in store.kv.items()
    }
    rows = {leaf: jnp.asarray(rng.normal(size=(4, slots, 2, 4)).astype(np.float32)) for leaf in ("k", "v")}
    phys = {KV_KIND_FULL: np.array([9, 0, 4], np.int32), KV_KIND_WINDOW: np.array([5, 2, 0], np.int32)}
    for s in dropped:  # an inactive lane: past the block axis of every kind
        for kind in phys:
            phys[kind][s] = cfgs[kind].pool_blocks
    off = np.array([0, 7, 3], np.int32)
    got = store.append_in_program(pool, rows, {k: jnp.asarray(v) for k, v in phys.items()}, jnp.asarray(off))
    for kind, idx in store.layers.items():
        for leaf in ("k", "v"):
            want = np.array(pool[kind][leaf])
            for j, layer in enumerate(idx):
                for s in range(slots):
                    if s not in dropped:
                        want[j, phys[kind][s], off[s]] = np.asarray(rows[leaf])[layer, s].reshape(-1)
            np.testing.assert_array_equal(np.asarray(got[kind][leaf]), want)


def test_a_model_of_two_layer_types_and_no_window_pages_as_one_kind(tmp_path, monkeypatch):
    """cohere2_moe without `sliding_window`: its layers still differ (three
    of four rotate), but every one keeps everything, so `paged_kinds` is
    None and the one pool's `full` kind holds them all, taken by each
    layer's index in the MODEL (not within its rope kind).  Greedy streams
    equal dense slots' byte for byte."""
    from benchmarks.harness import spec
    from benchmarks.harness.weights import write_checkpoint
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.kv import KindStore

    full = spec.load_json(spec.BENCH_DIR / "configs" / "command-a-plus-4l-ep8.json")
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    cfg.update(full["rehearse"]["config"], sliding_window=None)
    write_checkpoint(tmp_path, cfg, seed=2**31 + 38, dtype="float32")
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    dec = DecodingParams(temperature=0.0)
    prompts = {"a": list(range(5, 30)), "b": list(range(40, 51))}

    def streams(eng):
        try:
            last = {n: int(eng.prefill_and_sample(n, ids, dec).token[0]) for n, ids in prompts.items()}
            got = {n: [t] for n, t in last.items()}
            for _ in range(5):
                out, errs = eng.decode_batch({n: (got[n][-1], dec) for n in prompts})
                assert not errs
                for n, res in out.items():
                    got[n].append(int(res.token[0]))
            return got
        finally:
            eng.close()

    try:
        kw = dict(slots=2, max_seq=64, param_dtype="float32")
        paged = BatchedEngine(tmp_path, **kw)
        assert paged.model.paged_kinds is None and isinstance(paged.kv_store, KindStore)
        assert paged.kv_store.kinds == (KV_KIND_FULL,)
        assert paged.kv_store.kv[KV_KIND_FULL]["k"].shape[0] == cfg["num_hidden_layers"]
        assert streams(paged) == streams(BatchedEngine(tmp_path, kv_paged=False, **kw))
    finally:
        reset_settings_cache()
