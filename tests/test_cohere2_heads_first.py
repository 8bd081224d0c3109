"""`cohere2_moe` stores a projection split by head heads-first: `wq`, `wk`,
`wv` are `[L, heads, head_dim, D]`, HF's own `[out, in]` split by head, and
contracted over D straight to `[B, T, heads, head_dim]` (what the layout is
for: tests/test_pool_layout_v5e_compile.py).  Here, on the CPU in float32 at
the rehearsal's widths: the loader does not transpose, the projection is
`h @ W.T` reshaped to the bit, and int8 / int4 weights keep the `[D, out]`
form, its numbers and its streams.
"""

import numpy as np
import pytest

from benchmarks.harness.weights import write_checkpoint
from tests.benchmarks.test_bench_cohere2_moe import decoding, prompt, tiny_config

from dnet_tpu.models.cohere2_moe import BY_HEAD as LEAVES

# each heads-first leaf and the HF projection it is read from
BY_HEAD = dict(zip(LEAVES, ("q_proj", "k_proj", "v_proj")))


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    cfg = tiny_config()
    d = tmp_path_factory.mktemp("cohere2_heads_first")
    write_checkpoint(d, cfg, seed=2**31 + 52, dtype="float32")
    return cfg, d


def _model_and_raw(cfg, d, layers=None):
    from dnet_tpu.utils.checkpoint import Checkpoint
    from dnet_tpu.models import get_ring_model_cls
    from dnet_tpu.models.base import ModelConfig

    layers = list(range(cfg["num_hidden_layers"])) if layers is None else layers
    model = get_ring_model_cls("cohere2_moe")(ModelConfig.from_hf(cfg), layers)
    ckpt = Checkpoint(d)  # the tensors are views of its mapping: it lives as long as they do
    return model, [ckpt.load_layer_raw(a) for a in layers], ckpt


def test_map_layer_keeps_hfs_orientation_split_by_head(checkpoint):
    cfg, d = checkpoint
    model, raws, _ckpt = _model_and_raw(cfg, d, [0])
    raw = raws[0]
    p = model.map_layer(raw)
    D, Hd = cfg["hidden_size"], cfg["head_dim"]
    heads = {"wq": cfg["num_attention_heads"], "wk": cfg["num_key_value_heads"],
             "wv": cfg["num_key_value_heads"]}
    for leaf, proj in BY_HEAD.items():
        w = raw[f"self_attn.{proj}.weight"]
        assert p[leaf].shape == (heads[leaf], Hd, D)
        assert np.shares_memory(p[leaf], w)  # a view of the tensor as read: no transpose
        assert np.array_equal(p[leaf].reshape(-1, D), w)
    # what is not split by head stays [in, out]
    assert p["wo"].shape == (heads["wq"] * Hd, D)
    assert np.array_equal(p["wo"], raw["self_attn.o_proj.weight"].T)


@pytest.mark.parametrize("B,T", [(1, 16), (4, 1)], ids=["chunk", "step"])
def test_the_projection_is_h_times_w_transposed_to_the_bit(checkpoint, B, T):
    import jax.numpy as jnp

    cfg, d = checkpoint
    model, raws, _ckpt = _model_and_raw(cfg, d, [0])
    p = model.map_layer(raws[0])
    D, Hd = cfg["hidden_size"], cfg["head_dim"]
    h = np.random.default_rng(B * T).standard_normal((B, T, D)).astype(np.float32)
    for leaf, proj in BY_HEAD.items():
        w = raws[0][f"self_attn.{proj}.weight"]
        want = (jnp.asarray(h) @ jnp.asarray(np.ascontiguousarray(w.T))).reshape(B, T, -1, Hd)
        got = model._by_head(jnp.asarray(h), jnp.asarray(p[leaf]))
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_stack_is_layers_heads_head_dim_hidden(checkpoint):
    from dnet_tpu.core.engine import LocalEngine

    cfg, d = checkpoint
    eng = LocalEngine(d, max_seq=64, param_dtype="float32")
    try:
        L, D, Hd = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["head_dim"]
        shapes = {k: eng.window_params[k].shape for k in ("wq", "wk", "wv", "wo")}
        assert shapes == {
            "wq": (L, cfg["num_attention_heads"], Hd, D),
            "wk": (L, cfg["num_key_value_heads"], Hd, D),
            "wv": (L, cfg["num_key_value_heads"], Hd, D),
            "wo": (L, cfg["num_attention_heads"] * Hd, D),
        }
    finally:
        eng.close()


def _old_form(model, raws, bits):
    """The quantised leaves as they were when the float form was `[L, D,
    out]`: ops/quant.py over the transposed HF matrices, stacked."""
    from dnet_tpu.ops.quant import quantize_tree

    stacked = {
        leaf: np.stack([np.ascontiguousarray(r[f"self_attn.{proj}.weight"].T) for r in raws])
        for leaf, proj in BY_HEAD.items()
    }
    return quantize_tree(stacked, model.quant_keys, bits=bits, scale_dtype=np.float32)


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_quantised_weights_keep_the_d_by_out_form_and_its_numbers(checkpoint, bits):
    """Loaded whole or streamed a window of layers at a time, the three
    leaves are bit for bit what `[D, out]` matrices quantised to: groups
    along D, a scale an output row."""
    from dnet_tpu.core.engine import LocalEngine

    cfg, d = checkpoint
    model, raws, _ckpt = _model_and_raw(cfg, d)
    want = _old_form(model, raws, bits)
    D = cfg["hidden_size"]
    whole = LocalEngine(d, max_seq=64, param_dtype="float32", weight_quant_bits=bits)
    streamed = LocalEngine(d, max_seq=64, param_dtype="float32", weight_quant_bits=bits,
                           window_size=2, residency_size=2)
    try:
        for leaf in BY_HEAD:
            got = whole.window_params[leaf]
            assert set(got) == set(want[leaf])
            rows = D // 2 if bits == 4 else D
            assert got["q4" if bits == 4 else "q"].shape[1] == rows
            layers = [streamed.weight_cache.store.layer_host(a)[leaf] for a in model.layers]
            for part in got:
                assert np.array_equal(np.asarray(got[part]), want[leaf][part]), (leaf, part)
                assert np.array_equal(
                    np.concatenate([np.asarray(w[part]) for w in layers]), want[leaf][part]
                )
    finally:
        whole.close()
        streamed.close()


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_a_quantised_model_serves_one_stream_whole_streamed_and_paged(
    checkpoint, bits, monkeypatch
):
    """The streams of the three ways to serve are one stream (as before
    the change: the contraction of a quantised leaf is the one it was), and
    it is the float model's up to the quantisation."""
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.engine import LocalEngine

    cfg, d = checkpoint
    ids, n = prompt(cfg), 8
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    kw = dict(max_seq=128, param_dtype="float32")
    streams = {}
    try:
        for name, more in (
            ("float", {}),
            ("whole", dict(weight_quant_bits=bits)),
            ("streamed", dict(weight_quant_bits=bits, window_size=2, residency_size=2)),
        ):
            eng = LocalEngine(d, **kw, **more)
            streams[name] = [
                (r.token_id, r.logprob) for r in eng.generate(ids, decoding(), max_tokens=n)
            ]
            eng.close()
        eng = BatchedEngine(d, slots=2, kv_paged=True, weight_quant_bits=bits, **kw)
        res = eng.prefill_and_sample("a", ids, decoding())
        paged = [(int(res.token[0]), float(res.logprob[0]))]
        while len(paged) < n:
            out, errs = eng.decode_batch({"a": (paged[-1][0], decoding())})
            assert not errs
            paged.append((int(out["a"].token[0]), float(out["a"].logprob[0])))
        eng.close()
    finally:
        reset_settings_cache()
    for other in (streams["streamed"], paged):
        assert [t for t, _ in other] == [t for t, _ in streams["whole"]]
        assert np.allclose([lp for _, lp in other], [lp for _, lp in streams["whole"]], atol=2e-4)
    # the float model's logprob of the same first token, within the quantisation's reach
    assert streams["whole"][0][0] == streams["float"][0][0]
    assert abs(streams["whole"][0][1] - streams["float"][0][1]) < (0.05 if bits == 8 else 0.5)
