"""kv/store.py HybridStore serves two state families and takes the full
kind's leaves from the model: the doc cell's model (`qwen3_next`: the gated
delta rule beside keys and values) keeps the store it had, to the shape,
the counter and the name of every program it compiles; `minicpm_sala`
(lightning attention beside keys, values AND an index of pooled keys) gets
its own."""

import logging
import re

import jax
import numpy as np
import pytest

from tests.fakes.checkpoints import make_tiny_minicpm_sala, make_tiny_qwen3_next

#: what the parent commit (dd2830f, PR 48) builds and compiles for the tiny
#: qwen3_next through three chunks of 32, an adoption and two decode steps
#: (recorded from a `git archive` of it by the same steps as below)
PARENT_LEAVES = {
    "full": {"k": ((1, 48, 8, 32), "float32"), "v": ((1, 48, 8, 32), "float32")},
    "state": {"S": ((3, 3, 4, 16, 16), "float32"), "conv": ((3, 3, 3, 128), "float32")},
}
PARENT_MODULES = {
    "jit(_split_sample_count)", "jit(_threefry_fold_in)", "jit(_threefry_seed)",
    "jit(_threefry_split)", "jit(adopt_lane)", "jit(broadcast_in_dim)", "jit(commit)",
    "jit(convert_element_type)", "jit(dynamic_slice)", "jit(fresh_session)", "jit(make)",
    "jit(prefill_logits)", "jit(ragged_step)", "jit(squeeze)",
}


class Compiled(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = set()

    def emit(self, record):
        m = re.match(r"Compiling (\S+)", record.getMessage())
        if m:
            self.names.add(m.group(1))


@pytest.fixture()
def engine_of(monkeypatch, tmp_path):
    from dnet_tpu.config import reset_settings_cache

    made = []

    def make(maker, block_tokens, **kw):
        monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(block_tokens))
        reset_settings_cache()
        from dnet_tpu.core.batch import BatchedEngine

        cfg = maker(tmp_path)
        eng = BatchedEngine(tmp_path, slots=3, param_dtype="float32", **kw)
        made.append(eng)
        return cfg, eng

    yield make
    for eng in made:
        eng.close()
    reset_settings_cache()


def serve(eng, cfg, n, chunk=32, steps=2):
    from dnet_tpu.core.types import DecodingParams

    dec = DecodingParams(temperature=0.0)
    ids = [int(i) for i in np.random.default_rng(0).integers(1, cfg["vocab_size"], size=n)]
    eng.reserve_slot("a")
    for i in range(0, n, chunk):
        logits = eng.prefill_chunk("a", ids[i:i + chunk])
    tok = int(eng.adopt_prefilled("a", logits, dec).token[0])
    for _ in range(steps):
        out, errs = eng.decode_batch({"a": (tok, dec)})
        assert not errs
        tok = int(out["a"].token[0])
    return ids


def test_the_doc_cells_store_and_programs_are_the_parents(engine_of):
    seen = Compiled()
    logger = logging.getLogger("jax")
    level = logger.level
    jax.config.update("jax_log_compiles", True)
    logger.addHandler(seen)
    logger.setLevel(logging.DEBUG)
    try:
        cfg, eng = engine_of(make_tiny_qwen3_next, 8, max_seq=128)
        serve(eng, cfg, 77)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(seen)
        logger.setLevel(level)
    store = eng.kv_store
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), store.kv)
    assert got == PARENT_LEAVES
    assert store.kinds == ("full", "state") and store.entry_bytes == 16896
    assert [c.name for c in store.state_counters] == [
        "dnet_gdn_state_bytes_total", "dnet_gdn_tokens_total"]
    assert store.sparse is None and list(store.leaves) == ["k", "v"]
    # (a warm process's cache may spare a compile; none may be NEW)
    assert seen.names <= PARENT_MODULES, seen.names - PARENT_MODULES


def test_the_sparse_models_store_keeps_an_index_leaf_beside_the_keys(engine_of):
    from dnet_tpu.kv import HybridStore
    from dnet_tpu.obs import metric

    cfg, eng = engine_of(make_tiny_minicpm_sala, 16, max_seq=256)
    store = eng.kv_store
    assert isinstance(store, HybridStore) and store.kinds == ("full", "state")
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), store.kv)
    pages = store.cfg.pool_blocks
    assert got == {
        # two sparse layers: pages of 16 tokens, 2 KV heads x 16; the index
        # keeps a row for every 2 tokens (8 a page)
        "full": {"k": ((2, pages, 16, 32), "float32"), "v": ((2, pages, 16, 32), "float32"),
                 "kc": ((2, pages, 8, 32), "float32")},
        # six lightning layers: S [4, 16, 16] float32 a lane
        "state": {"S": ((6, 3, 4, 16, 16), "float32")},
    }
    assert list(store.leaves) == ["k", "v", "kc"] and store.sparse.block_size == 8
    assert store.entry_bytes == 6 * 4 * 16 * 16 * 4
    assert [c.name for c in store.state_counters] == [
        "dnet_lightning_state_bytes_total", "dnet_lightning_tokens_total"]
    rows = metric("dnet_sparse_index_rows_total")
    modes = metric("dnet_sparse_tokens_total")
    blocks = metric("dnet_sparse_blocks_total")
    tokens = metric("dnet_lightning_tokens_total")
    before = (rows.value, modes.labels(mode="dense").value, modes.labels(mode="sparse").value,
              blocks.labels(state="chosen").value, blocks.labels(state="resident").value,
              tokens.labels(phase="prefill").value, tokens.labels(phase="decode").value)
    ids = serve(eng, cfg, 150, steps=4)  # positions 150 .. 153
    after = (rows.value, modes.labels(mode="dense").value, modes.labels(mode="sparse").value,
             blocks.labels(state="chosen").value, blocks.labels(state="resident").value,
             tokens.labels(phase="prefill").value, tokens.labels(phase="decode").value)
    d = [b - a for a, b in zip(before, after)]
    # adoption pools the 74 spans 150 tokens complete ((150 - 4) // 2 + 1), the
    # steps at 151 and 153 complete two more; two sparse layers each
    assert d[0] == 2 * (74 + 2)
    assert d[1] == 64 and d[2] == (150 - 64) + 4  # by the side of dense_len
    assert d[3] == 2 * 4 * 6 and d[4] == 2 * (19 + 19 + 20 + 20)  # 6 chosen of 19-20 held
    assert d[5] == 150 and d[6] == 4
    # the index holds the mean of the keys it spans, committed AND extended
    slot = eng.slot_of["a"]
    table = eng._tables[slot].blocks
    k = np.asarray(store.kv["full"]["k"])
    kc = np.asarray(store.kv["full"]["kc"])
    flat = np.concatenate([k[0, b] for b in table])  # layer 0's keys by position
    for j in (0, 36, 73, 74, 75):  # committed (73 the last), then decode's two
        got = kc[0, table[j // 8], j % 8]
        assert np.allclose(got, flat[2 * j:2 * j + 4].mean(0), atol=1e-6), j
    assert len(ids) == 150 and len(table) == -(-154 // 16)


def test_a_pool_block_must_hold_whole_sparse_blocks(engine_of):
    with pytest.raises(ValueError, match="do not hold whole blocks"):
        engine_of(make_tiny_minicpm_sala, 4, max_seq=256)


@pytest.mark.parametrize("family,maker,kernels", [
    ("gdn", make_tiny_qwen3_next, {"gdn_step", "paged_attend"}),
    ("lightning", make_tiny_minicpm_sala, {"lightning_step", "sparse_index", "paged_attend_sparse"}),
])
def test_the_state_layers_step_is_their_familys(engine_of, family, maker, kernels):
    from dnet_tpu.kv.store import _STATE_COUNTERS, _STATE_STEPS
    from dnet_tpu.ops.kernel_select import SELECTIONS

    assert set(_STATE_STEPS) == {"gdn", "lightning"} and set(_STATE_STEPS) < set(_STATE_COUNTERS)
    before = SELECTIONS.snapshot()
    cfg, eng = engine_of(maker, 16, max_seq=128)
    assert eng.model.state_family == family and eng.kv_store._state_step is _STATE_STEPS[family]
    serve(eng, cfg, 40, steps=1)
    after = SELECTIONS.snapshot()
    ran = {k for k in after if sum(after[k][c] - before[k][c] for c in ("emulate", "interpret"))}
    assert kernels <= ran
    other = {"gdn_step", "lightning_step", "paged_attend_sparse"} - kernels
    assert not (other & ran)
