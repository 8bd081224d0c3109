"""Brumby on the served path against the plain reference, float32 on seeded
weights at a tiny size: prefill in chunks (the state crosses chunk edges and
a ragged last chunk), adoption into a lane of the `StateStore`, then decode
through it, one step at a time (alone too, under a budget), with another lane
busy beside it.
The reference (benchmarks/reference/brumby.py) is the QUADRATIC form over
the whole sequence: no state anywhere.  Logits and log-probabilities are
compared, not tokens."""

import jax
import numpy as np
import pytest

from benchmarks.harness.weights import reference_module
from tests.fakes.checkpoints import make_tiny_brumby

TOL = 2e-3  # nat, float32 both sides (measured 1e-6 .. 2e-5)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("brumby_parity")
    return make_tiny_brumby(d), d


def prompt(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(i) for i in rng.integers(1, cfg["vocab_size"], size=n)]


def decoding():
    from dnet_tpu.core.types import DecodingParams

    return DecodingParams(temperature=0.0, logprobs=True, top_logprobs=20)


def worst_error(cfg, model_dir, ids, got):
    seq = ids + [r.token_id for r in got[:-1]]
    ref = reference_module(cfg["model_type"])
    want = np.asarray(jax.nn.log_softmax(ref.logits(model_dir, cfg, seq, last=len(got)), axis=-1))
    worst = 0.0
    for j, r in enumerate(got):
        for tid, lp in [(r.token_id, r.logprob), *r.top_logprobs]:
            worst = max(worst, abs(lp - want[j, tid]))
    return worst


def test_one_sequence_at_a_time_matches_the_reference(checkpoint):
    """LocalEngine: the session holds the state where a cache row would be."""
    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    eng = LocalEngine(model_dir, max_seq=128, param_dtype="float32")
    ids = prompt(cfg, 61)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) < TOL


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_decode_through_the_state_store(checkpoint, monkeypatch, kernels, chunk):
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.kv import StateStore
    from dnet_tpu.obs import metric

    cfg, model_dir = checkpoint
    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=3, max_seq=128, param_dtype="float32")
        assert isinstance(eng.kv_store, StateStore)
        dec, ids, other = decoding(), prompt(cfg, 77), prompt(cfg, 30, seed=5)
        pre0 = metric("dnet_retention_tokens_total").labels(phase="prefill").value
        # another sequence holds a lane and steps beside ours
        o = eng.prefill_and_sample("other", other, dec)
        o_tok = int(o.token[0])
        eng.reserve_slot("a")
        for i in range(0, len(ids), chunk):  # 77 tokens: the last chunk is ragged
            logits = eng.prefill_chunk("a", ids[i:i + chunk])
        assert metric("dnet_retention_tokens_total").labels(phase="prefill").value - pre0 == 77
        res = eng.adopt_prefilled("a", logits, dec)
        assert "a" not in eng.eng.sessions  # the session's entry moved into the lane
        got = [eng.token_result("a", res, step=0, decoding=dec)]
        for step in range(1, 4):  # single steps, the other lane active
            out, errs = eng.decode_batch(
                {"a": (got[-1].token_id, dec), "other": (o_tok, dec)}
            )
            assert not errs
            o_tok = int(out["other"].token[0])
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        # four steps alone (the other lane idles), a budget riding along:
        # it never widens a dispatch
        sent = metric("dnet_decode_dispatch_total")
        sent0 = sent.value
        for step in range(4, 8):
            out, errs = eng.decode_batch(
                {"a": (got[-1].token_id, dec)}, budgets={"a": 8 - step}
            )
            assert not errs and sent.value - sent0 == step - 3
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        assert worst_error(cfg, model_dir, ids, got) < TOL
        eng.close()
    finally:
        reset_settings_cache()


def test_dense_slots_agree_with_the_state_store(checkpoint):
    """`kv_paged=False` (the tests' dense engine) keeps a state entry a slot
    inside the vmapped step: the same tokens, byte for byte."""
    from dnet_tpu.core.batch import BatchedEngine

    cfg, model_dir = checkpoint
    ids, dec = prompt(cfg, 50), decoding()
    streams = []
    for paged in (None, False):
        eng = BatchedEngine(model_dir, slots=2, max_seq=128, param_dtype="float32", kv_paged=paged)
        assert (eng.kv_store is None) == (paged is False)
        streams.append([r.token_id for r in eng.generate(ids, dec, max_tokens=6)])
        eng.close()
    assert streams[0] == streams[1]


def test_the_weight_map_takes_the_gate_projection(checkpoint):
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    cfg, _ = checkpoint
    mc = ModelConfig.from_hf(cfg)
    model = get_ring_model_cls("brumby")(mc, range(mc.num_hidden_layers))
    ref = reference_module("brumby")
    _, layer = ref.tensor_table(cfg)
    raw = {k: np.zeros(shape, np.float32) for k, (shape, _) in layer(0).items()}
    mapped = model.map_layer(raw)
    assert mapped["wg"].shape == (cfg["hidden_size"], cfg["num_key_value_heads"])
    assert {"q_norm", "k_norm", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"} <= set(mapped)
    assert "wg" not in model.quant_keys  # the gate stays float
    with pytest.raises(NotImplementedError, match="mesh axis"):
        model.apply_window({"wq": np.zeros((1, 4, 4))}, None, None, 0, tp_axis="model")
