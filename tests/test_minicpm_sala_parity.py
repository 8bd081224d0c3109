"""MiniCPM-SALA on the served path against the plain reference, float32 on
seeded weights at a tiny size (two periods of [minicpm4, lightning-attn x 3];
blocks of 8 tokens, 6 chosen past 64 tokens of context, pooled keys every 2
tokens over 4): prefill in chunks (the lightning state, the staged keys the
index pools and the block table cross chunk edges, `dense_len` is crossed
INSIDE the prompt), adoption into a lane AND a page table of the combined
store (the index leaf committed beside the keys), then decode through it
one step at a time with another lane busy beside it, spans completing on
the way.  The reference (benchmarks/reference/minicpm_sala.py) is the
quadratic lightning form and the selection by explicit masks over the whole
sequence: no chunks, no cache, no index leaf, a sort where the program
searches a threshold.  Logits and log-probabilities are compared, not
tokens."""

import jax
import numpy as np
import pytest

from benchmarks.harness.weights import reference_module
from tests.fakes.checkpoints import make_tiny_minicpm_sala

TOL = 2e-3  # nat, float32 both sides (measured 2e-6)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("minicpm_sala_parity")
    return make_tiny_minicpm_sala(d), d


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


def served(model_dir, cfg, ids, chunk, steps, monkeypatch, kernels):
    """Chunked prefill, adoption, `steps` decode steps beside a busy lane."""
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.kv import HybridStore

    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "16")
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=3, max_seq=256, param_dtype="float32")
        assert isinstance(eng.kv_store, HybridStore)
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


@pytest.mark.parametrize("slab", [2048, 32])
def test_one_sequence_at_a_time_matches_the_reference(checkpoint, monkeypatch, slab):
    """LocalEngine: the session holds the sparse layers' rows and the
    lightning layers' entries side by side; 90 tokens cross dense_len in ONE
    program of 128 rows, which at a slab of 32 goes through the stack in
    four slabs (the caches carried) and through the index in eight."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.models import minicpm_sala
    from dnet_tpu.ops import sparse_attention

    monkeypatch.setattr(minicpm_sala, "PREFILL_SLAB", slab)
    monkeypatch.setattr(sparse_attention, "QUERY_SLAB", slab // 2)
    cfg, model_dir = checkpoint
    eng = LocalEngine(model_dir, max_seq=128, param_dtype="float32")
    ids = prompt(cfg, 90)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) < TOL


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_decode_through_the_combined_store(checkpoint, monkeypatch, kernels, chunk):
    """150 tokens: dense_len (64) is crossed inside the prompt, the last
    chunk is ragged, and ten steps complete five spans of the index."""
    cfg, model_dir = checkpoint
    ids = prompt(cfg, 150)
    got = served(model_dir, cfg, ids, chunk, 10, monkeypatch, kernels)
    assert worst_error(cfg, model_dir, ids, got) < TOL


def test_dense_len_is_crossed_inside_an_answer(checkpoint, monkeypatch):
    """A prompt of 58 tokens decodes across context 64: the first steps
    attend everything, the later ones six blocks."""
    cfg, model_dir = checkpoint
    ids = prompt(cfg, 58, seed=3)
    got = served(model_dir, cfg, ids, 32, 14, monkeypatch, "interpret")
    assert worst_error(cfg, model_dir, ids, got) < TOL


def test_a_wrong_selection_fails_by_a_wide_margin(tmp_path, monkeypatch):
    """With q scaled up (its norm's weights x 8) the index's softmax is peaked
    and a query's answer lives in the blocks it scores best; with v and o
    scaled up (x 10 each) a sparse layer's output weighs in the logits: the
    served path still agrees with the reference to float32 rounding, and a
    reference that takes the NEAREST blocks instead of the best is far off
    (measured 0.18 nat against 1e-6).  What the selection is held by."""
    import json

    from safetensors.numpy import load_file, save_file

    cfg = make_tiny_minicpm_sala(tmp_path)
    scaled = {"self_attn.q_norm.weight": 8.0, "self_attn.v_proj.weight": 10.0,
              "self_attn.o_proj.weight": 10.0}
    for f in sorted(tmp_path.glob("model-layer-*.safetensors")):
        t = load_file(str(f))
        for name in t:
            _, _, layer, suffix = name.split(".", 3)
            if cfg["mixer_types"][int(layer)] == "minicpm4" and suffix in scaled:
                t[name] = t[name] * scaled[suffix]
        save_file(t, str(f))
    assert json.loads((tmp_path / "config.json").read_text())["model_type"] == "minicpm_sala"
    ids = prompt(cfg, 200, seed=9)
    got = served(tmp_path, cfg, ids, 32, 8, monkeypatch, "interpret")
    assert worst_error(cfg, tmp_path, ids, got) < TOL
    assert worst_error(cfg, tmp_path, ids, got, nearest=True) > 0.1


def test_the_chip_script_rehearses_here():
    """scripts/sala_parity.py --interpret: the five kernels against their
    definitions through the interpreted kernels, as the chip runs them."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, str(root / "scripts" / "sala_parity.py"), "--interpret", "--steps", "4"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["impl"] == "interpret" and out["lightning_idle_lane_untouched"] is True
    for k in ("lightning_chunk_err", "lightning_step_err", "sparse_prefill_err", "sparse_decode_err"):
        assert out[k] < 1e-4, (k, out[k])
