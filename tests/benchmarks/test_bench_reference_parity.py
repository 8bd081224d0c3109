"""The plain references against the system at a tiny size on the CPU:
prefill, then decoding through the cache, compared as log-probabilities.

Tolerance 2e-3 nats: both sides run float32 on the CPU over the same
float32 weights, so what is left is summation order (measured 2e-5 .. 4e-4
here).  A dropped norm, a wrong rotary layout, a missing softmax scale or
shared expert moves log-probabilities by 1e-2 to 1, far outside it.  On the
chip the run's own check uses the tolerance of its configuration file.
"""

import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.harness.weights import reference_module, write_checkpoint

TOL = 2e-3


def tiny_config(model_type):
    assert model_type == "qwen3_moe"  # a config of another type brings its tiny sizes here
    full = spec.load_json(spec.BENCH_DIR / "configs" / "qwen3-30b-a3b-6l.json")
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    cfg.update(full["rehearse"]["config"])
    return cfg


@pytest.fixture(scope="module", params=[("qwen3_moe", 2**31 + 7), ("qwen3_moe", 11)],
                ids=lambda p: f"{p[0]}-seed{p[1]}")
def case(request, tmp_path_factory):
    model_type, seed = request.param
    cfg = tiny_config(model_type)
    d = tmp_path_factory.mktemp(f"bench_{model_type}")
    write_checkpoint(d, cfg, seed=seed, dtype="float32")
    from dnet_tpu.core.engine import LocalEngine

    return cfg, d, LocalEngine(d, max_seq=128, param_dtype="float32")


def test_prefill_then_decode_matches_the_reference(case):
    import jax

    from dnet_tpu.core.types import DecodingParams

    cfg, model_dir, engine = case
    rng = np.random.default_rng(0)
    ids = [int(i) for i in rng.integers(1, cfg["vocab_size"], size=21)]
    steps = 5
    dec = DecodingParams(temperature=0.0, logprobs=True, top_logprobs=20)
    got = list(engine.generate(ids, dec, max_tokens=steps))
    assert len(got) == steps
    seq = ids + [r.token_id for r in got[:-1]]
    ref = reference_module(cfg["model_type"])
    want = np.asarray(jax.nn.log_softmax(ref.logits(model_dir, cfg, seq, last=steps), axis=-1))
    worst = 0.0
    for j, r in enumerate(got):
        assert int(np.argmax(want[j])) == r.token_id  # float32 both sides: same token
        for tid, lp in [(r.token_id, r.logprob), *r.top_logprobs]:
            worst = max(worst, abs(lp - want[j, tid]))
    assert worst < TOL, worst


def test_the_reference_notices_a_wrong_weight(case):
    """The comparison has teeth: scaling one norm by 1.5 moves it far
    outside the tolerance."""
    import jax
    from safetensors.numpy import load_file, save_file

    cfg, model_dir, _ = case
    ref = reference_module(cfg["model_type"])
    ids = list(range(3, 20))
    before = np.asarray(jax.nn.log_softmax(ref.logits(model_dir, cfg, ids, last=2), -1))
    f = model_dir / "model-layer-001.safetensors"
    tensors = load_file(str(f))
    saved = dict(tensors)
    key = "model.layers.1.post_attention_layernorm.weight"
    tensors[key] = tensors[key] * 1.5
    save_file(tensors, str(f))
    try:
        after = np.asarray(jax.nn.log_softmax(ref.logits(model_dir, cfg, ids, last=2), -1))
    finally:
        save_file(saved, str(f))
    assert np.max(np.abs(after - before)) > 10 * TOL


def test_seeded_weights_are_a_pure_function_of_the_seed(tmp_path):
    from safetensors.numpy import load_file

    cfg = tiny_config("qwen3_moe")
    for name, seed in (("a", 2**31 + 7), ("b", 2**31 + 7), ("c", 2**31 + 8)):
        write_checkpoint(tmp_path / name, cfg, seed=seed, dtype="float32")
    a, b, c = (load_file(str(tmp_path / n / "model-layer-000.safetensors")) for n in "abc")
    key = "model.layers.0.mlp.experts.3.up_proj.weight"
    assert np.array_equal(a[key], b[key]) and not np.array_equal(a[key], c[key])
    assert abs(float(np.std(a[key])) - 0.02) < 0.004
