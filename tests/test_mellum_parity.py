"""Mellum on the served path against the plain reference, float32 on seeded
weights at a tiny size (two periods of (sliding x 3, full), hidden 64, 8 / 2
heads of 16, 8 experts top-2, a window of 8, YaRN x16 over an original
length of 32 on the full kind): WINDOW < CHUNK < PROMPT.  Prefill in chunks
of 16 or 32 through the staged row (a chunk's own first rows leave the
window inside the chunk), adoption into a table a kind (the window kind's
holds the blocks the next token still reaches), then decode through both
pools with another lane busy beside it, the window's blocks given back on
the way.  The reference (benchmarks/reference/mellum.py) is one pass over
the whole sequence: no chunks, no cache, the window an explicit mask, both
rotary tables written out from the config's numbers.  Log-probabilities are
compared, not tokens.

Then what the comparison is worth: with `wq` scaled up the softmax is peaked,
and a reference that rotates the full layers by the window kind's table,
drops YaRN's attention factor, has a window one key wider or narrower, lets
the window layers attend everything, does not renormalise the chosen
experts' weights, or leaves out the q / k norms is FAR off the same served
answers.
"""

import jax
import numpy as np
import pytest

from benchmarks.harness.weights import reference_module
from tests.fakes.checkpoints import make_tiny_mellum

TOL = 1e-5  # nat, float32 both sides (measured 1e-6)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("mellum_parity")
    return make_tiny_mellum(d), d


def prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(i) for i in rng.integers(1, cfg["vocab_size"], size=n)]


def decoding():
    from dnet_tpu.core.types import DecodingParams

    return DecodingParams(temperature=0.0, logprobs=True, top_logprobs=20)


def worst_error(cfg, model_dir, ids, got, **control):
    seq = ids + [r.token_id for r in got[:-1]]
    ref = reference_module(cfg["model_type"])
    want = np.asarray(
        jax.nn.log_softmax(ref.logits(model_dir, cfg, seq, last=len(got), **control), axis=-1)
    )
    worst = 0.0
    for j, r in enumerate(got):
        for tid, lp in [(r.token_id, r.logprob), *r.top_logprobs]:
            worst = max(worst, abs(lp - want[j, tid]))
    return worst


def served(model_dir, cfg, ids, chunk, steps, monkeypatch, kernels, max_seq=256):
    """Chunked prefill, adoption, `steps` decode steps beside a busy lane."""
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.kv import KindStore
    from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_WINDOW

    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("DNET_SCHED_PREFILL_CHUNK", str(chunk))
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=3, max_seq=max_seq, param_dtype="float32")
        assert isinstance(eng.kv_store, KindStore)
        assert eng.kv_store.kinds == (KV_KIND_FULL, KV_KIND_WINDOW)
        dec, other = decoding(), prompt(cfg, 30, seed=5)
        o = eng.prefill_and_sample("other", other, dec)
        o_tok = int(o.token[0])
        eng.reserve_slot("a")
        for i in range(0, len(ids), chunk):
            logits = eng.prefill_chunk("a", ids[i:i + chunk])
        res = eng.adopt_prefilled("a", logits, dec)
        assert "a" not in eng.eng.sessions
        got = [eng.token_result("a", res, step=0, decoding=dec)]
        for step in range(1, steps):
            out, errs = eng.decode_batch({"a": (got[-1].token_id, dec), "other": (o_tok, dec)})
            assert not errs
            o_tok = int(out["other"].token[0])
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        eng.close()
        return got
    finally:
        reset_settings_cache()


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_decode_through_both_pools(checkpoint, monkeypatch, kernels, chunk):
    """150 tokens: past the tiny YaRN's original 32, eighteen windows, a
    ragged last chunk; twelve steps cross a block's edge, so the window's
    table gives a block back and takes one."""
    cfg, model_dir = checkpoint
    assert cfg["sliding_window"] < chunk < 150
    assert cfg["rope_parameters"]["full_attention"]["original_max_position_embeddings"] < 150
    ids = prompt(cfg, 150)
    got = served(model_dir, cfg, ids, chunk, 12, monkeypatch, kernels)
    assert worst_error(cfg, model_dir, ids, got) < TOL


def test_one_sequence_at_a_time_matches_the_reference(checkpoint):
    """LocalEngine: one program of 128 rows for 90 tokens, then six steps
    over the slot-addressed cache (both kinds in one flat cache, the window
    the kernel's lower bound)."""
    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    eng = LocalEngine(model_dir, max_seq=128, param_dtype="float32")
    ids = prompt(cfg, 90)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) < TOL


def test_a_wide_program_goes_through_the_stack_a_slab_at_a_time(checkpoint, monkeypatch):
    """A one-shot prefill wider than `PREFILL_SLAB` (the load's warm-up of
    the step's table widths): 100 tokens in a program of 128 rows, in four
    slabs of 32 with the staged row carried, equal to the whole."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.models import mellum

    cfg, model_dir = checkpoint
    monkeypatch.setattr(mellum, "PREFILL_SLAB", 32)
    eng = LocalEngine(model_dir, max_seq=160, param_dtype="float32")
    ids = prompt(cfg, 100, seed=2)
    got = list(eng.generate(ids, decoding(), max_tokens=4))
    assert worst_error(cfg, model_dir, ids, got) < TOL


def test_a_lane_is_used_again_after_a_longer_sequence(checkpoint, monkeypatch):
    """A lane that held 150 tokens' tables is given to a prompt of 40: the
    shorter one's answers are the reference's, whatever the longer left in
    the pools."""
    from dnet_tpu.config import reset_settings_cache

    cfg, model_dir = checkpoint
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=1, max_seq=256, param_dtype="float32")
        dec = decoding()
        for n, seed in ((150, 0), (40, 7)):
            ids = prompt(cfg, n, seed=seed)
            eng.reserve_slot("a")
            for i in range(0, n, 16):
                logits = eng.prefill_chunk("a", ids[i:i + 16])
            res = eng.adopt_prefilled("a", logits, dec)
            assert eng.slot_of["a"] == 0
            got = [eng.token_result("a", res, step=0, decoding=dec)]
            for step in range(1, 10):
                out, errs = eng.decode_batch({"a": (got[-1].token_id, dec)})
                assert not errs
                got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
            assert worst_error(cfg, model_dir, ids, got) < TOL, n
            eng.end_session("a")
        eng.close()
    finally:
        reset_settings_cache()


@pytest.fixture(scope="module")
def peaked(tmp_path_factory):
    """The tiny checkpoint with `wq` x 6 in every layer: the per-head norms
    keep q's size, so the scale goes on their weights too (x 6): scores six
    times as large, a softmax that picks keys."""
    from safetensors.numpy import load_file, save_file

    d = tmp_path_factory.mktemp("mellum_peaked")
    cfg = make_tiny_mellum(d)
    for f in sorted(d.glob("model-layer-*.safetensors")):
        t = load_file(str(f))
        for name in t:
            if name.endswith(("self_attn.q_norm.weight", "self_attn.q_proj.weight")):
                t[name] = t[name] * 6.0
        save_file(t, str(f))
    ids = prompt(cfg, 150, seed=9)
    return cfg, d, ids


@pytest.fixture(scope="module")
def peaked_answers(peaked):
    cfg, d, ids = peaked
    mp = pytest.MonkeyPatch()
    try:
        return served(d, cfg, ids, 16, 10, mp, "interpret")
    finally:
        mp.undo()


def test_the_peaked_checkpoint_is_served_to_float32_rounding(peaked, peaked_answers):
    cfg, d, ids = peaked
    assert worst_error(cfg, d, ids, peaked_answers) < 1e-4


@pytest.mark.parametrize(
    "control",
    [
        {"full_table": "sliding"},  # the full layers rotated by the window kind's table
        {"attention_factor": 1.0},  # YaRN without its factor on cos and sin
        {"window": 7},  # the window one key narrower
        {"window": 9},  # ... one key wider (the query's own position not counted)
        {"window": 0},  # the window layers attend everything
        {"norm_topk_prob": False},  # the chosen experts' weights as the softmax left them
        {"qk_norm": False},  # no per-head norms
    ],
    ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()),
)
def test_a_reference_that_reads_the_config_otherwise_is_far_off(peaked, peaked_answers, control):
    """Each reading the issue names must FAIL the comparison by a wide
    margin where the sound one passes at 1e-4 (measured: 0.02-0.5 nat at the
    plain tiny size already)."""
    cfg, d, ids = peaked
    assert worst_error(cfg, d, ids, peaked_answers, **control) > 5e-3
