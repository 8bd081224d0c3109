"""Qwen3-Next on the served path against the plain reference, float32 on
seeded weights at a tiny size (one period: three Gated DeltaNet layers and
one gated-attention layer, 16 experts held of 32, top-4): prefill in chunks
(the state, the convolution's tail and the block table cross chunk edges
and a ragged last chunk), adoption into a lane AND a page table of the
combined store, then decode through it, one step at a time (alone too), with
another lane busy beside it.  The reference (benchmarks/reference/
qwen3_next.py) is the token-by-token recurrence and quadratic attention
over the whole sequence: no chunks, no cache.  Logits and log-probabilities
are compared, not tokens.

And the SHARE test of the model-configs guide's section 4: the two shares'
routed parts plus the shared expert counted once add up to the uncut
reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.weights import reference_module, write_checkpoint
from tests.fakes.checkpoints import make_tiny_qwen3_next, tiny_qwen3_next_config

TOL = 2e-3  # nat, float32 both sides (measured 5e-7)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("qwen3_next_parity")
    return make_tiny_qwen3_next(d), d


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
    """LocalEngine: the session holds the attention layer's row and the
    delta-rule layers' entries side by side."""
    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    eng = LocalEngine(model_dir, max_seq=128, param_dtype="float32")
    ids = prompt(cfg, 61)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) < TOL


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_decode_through_the_combined_store(checkpoint, monkeypatch, kernels, chunk):
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.kv import HybridStore
    from dnet_tpu.obs import metric

    cfg, model_dir = checkpoint
    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=3, max_seq=128, param_dtype="float32")
        assert isinstance(eng.kv_store, HybridStore)
        dec, ids, other = decoding(), prompt(cfg, 77), prompt(cfg, 30, seed=5)
        pre0 = metric("dnet_gdn_tokens_total").labels(phase="prefill").value
        # another sequence holds a lane and a table, and steps beside ours
        o = eng.prefill_and_sample("other", other, dec)
        o_tok = int(o.token[0])
        eng.reserve_slot("a")
        for i in range(0, len(ids), chunk):  # 77 tokens: the last chunk is ragged
            logits = eng.prefill_chunk("a", ids[i:i + chunk])
        assert metric("dnet_gdn_tokens_total").labels(phase="prefill").value - pre0 == 77
        res = eng.adopt_prefilled("a", logits, dec)
        assert "a" not in eng.eng.sessions  # the session's row and entries moved into the store
        assert len(eng._tables[eng.slot_of["a"]].blocks) == 10  # 77 tokens in blocks of 8
        got = [eng.token_result("a", res, step=0, decoding=dec)]
        for step in range(1, 5):  # single steps over a block's edge (80), the other lane active
            out, errs = eng.decode_batch(
                {"a": (got[-1].token_id, dec), "other": (o_tok, dec)}
            )
            assert not errs
            o_tok = int(out["other"].token[0])
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        assert len(eng._tables[eng.slot_of["a"]].blocks) == 11
        # four steps alone (the other lane idles), a budget riding along:
        # it never widens a dispatch
        sent = metric("dnet_decode_dispatch_total")
        sent0 = sent.value
        for step in range(5, 9):
            out, errs = eng.decode_batch(
                {"a": (got[-1].token_id, dec)}, budgets={"a": 9 - step}
            )
            assert not errs and sent.value - sent0 == step - 4
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        assert worst_error(cfg, model_dir, ids, got) < TOL
        eng.close()
    finally:
        reset_settings_cache()


def test_dense_slots_agree_with_the_combined_store(checkpoint):
    """`kv_paged=False` (the tests' dense engine) keeps a row and an entry a
    slot inside the vmapped step: the same tokens, byte for byte."""
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


def test_the_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tmp_path):
    """Each share holds 16 of the 32 routed experts, routes over all 32 and
    returns its own experts' part plus the shared expert's term.  Summed,
    with the shared expert (what every chip computes alike) counted once,
    they are the uncut layer."""
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    whole = tiny_qwen3_next_config(num_experts=32, num_experts_routed=32)
    write_checkpoint(tmp_path, whole, seed=2**31 + 38, dtype="float32")
    ref = reference_module("qwen3_next")
    from benchmarks.reference.common import Tensors

    raw = Tensors(tmp_path).layer(0)
    per_expert = {}
    for k, v in raw.items():
        if ".experts.*." in k:
            for e in range(v.shape[0]):
                per_expert[k.replace(".experts.*.", f".experts.{e}.")] = v[e]
        else:
            per_expert[k] = v
    x = jax.random.normal(jax.random.key(3), (1, 24, whole["hidden_size"]))

    def moe_of(cfg):
        mc = ModelConfig.from_hf(cfg)
        model = get_ring_model_cls("qwen3_next")(mc, range(mc.num_hidden_layers))
        p = {k: jnp.asarray(v) for k, v in model.map_layer(per_expert)["moe"].items()}
        y, held = model._moe(p, x)
        return np.asarray(y - x)[0], np.asarray(held)[0], p

    full, held_all, p = moe_of(whole)
    assert (held_all == whole["num_experts_per_tok"]).all()
    lo, held_lo, _ = moe_of({**whole, "num_experts": 16, "expert_offset": 0})
    hi, held_hi, _ = moe_of({**whole, "num_experts": 16, "expert_offset": 16})
    assert ((held_lo + held_hi) == whole["num_experts_per_tok"]).all() and held_lo.min() < 4
    # the shared expert's term alone: a share of no routed weight at all
    flat = np.asarray(ref.rms_norm0(x[0], raw["post_attention_layernorm.weight"], whole["rms_norm_eps"]))
    sg = 1 / (1 + np.exp(-(flat @ np.asarray(raw["mlp.shared_expert_gate.weight"]).T)))
    inner = jax.nn.silu(flat @ np.asarray(p["s_gate"])) * (flat @ np.asarray(p["s_up"]))
    shared = np.asarray(inner @ np.asarray(p["s_down"])) * sg
    assert np.max(np.abs((lo + hi - shared) - full)) < 1e-5
    assert np.max(np.abs(shared)) > 1e-4  # it is there to be counted twice by mistake
    # and the uncut layer is the reference's
    layer = ref._expert_layer(whole)
    want = np.asarray(layer(x[0], raw) - x[0])
    assert np.max(np.abs(full - want)) < 1e-5


def test_the_weight_map_uninterleaves_the_projections(checkpoint):
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    cfg, _ = checkpoint
    mc = ModelConfig.from_hf(cfg)
    model = get_ring_model_cls("qwen3_next")(mc, range(mc.num_hidden_layers))
    assert model.paged_kinds == ("state", "state", "state", "full")
    ref = reference_module("qwen3_next")
    _, layer = ref.tensor_table(cfg)
    HK, HV, Dk, Dv = 2, 4, 16, 16
    D = cfg["hidden_size"]
    raw = {k: np.zeros(shape, np.float32) for k, (shape, _) in layer(0).items()}
    raw = {k.replace(".experts.*.", ".experts.0."): (v[0] if ".experts.*." in k else v)
           for k, v in raw.items()}
    for e in range(1, cfg["num_experts"]):
        for n in ("gate", "up", "down"):
            raw[f"mlp.experts.{e}.{n}_proj.weight"] = raw[f"mlp.experts.0.{n}_proj.weight"]
    # mark each output row of in_proj_qkvz by what it is: key head h holds
    # Dk q, Dk k, 2 Dv v, 2 Dv z in that order
    rows = []
    for h in range(HK):
        rows += [("q", h, i) for i in range(Dk)] + [("k", h, i) for i in range(Dk)]
        rows += [("v", h, i) for i in range(2 * Dv)] + [("z", h, i) for i in range(2 * Dv)]
    code = {"q": 1.0, "k": 2.0, "v": 3.0, "z": 4.0}
    w = np.zeros((len(rows), D), np.float32)
    for r, (what, h, i) in enumerate(rows):
        w[r, 0] = code[what] + 0.1 * h + 0.001 * i
    raw["linear_attn.in_proj_qkvz.weight"] = w
    ba = np.zeros((2 * HV, D), np.float32)
    ba[:, 0] = [5.0, 5.1, 6.0, 6.1, 5.2, 5.3, 6.2, 6.3]  # head 0: b b a a; head 1: b b a a
    raw["linear_attn.in_proj_ba.weight"] = ba
    mapped = model.map_layer(raw)
    g = mapped["gdn"]
    qkv = g["w_qkv"][0]  # [C]: q | k | v, each by head
    assert g["w_qkv"].shape == (D, 2 * HK * Dk + HV * Dv) and g["w_z"].shape == (D, HV * Dv)
    assert np.allclose(qkv[:Dk], 1.0 + 0.001 * np.arange(Dk))  # q of key head 0
    assert np.allclose(qkv[Dk:2 * Dk], 1.1 + 0.001 * np.arange(Dk))  # q of key head 1
    assert np.allclose(qkv[2 * Dk:3 * Dk], 2.0 + 0.001 * np.arange(Dk))  # k of key head 0
    assert np.allclose(qkv[4 * Dk:4 * Dk + 2 * Dv], 3.0 + 0.001 * np.arange(2 * Dv))  # v heads 0, 1
    assert np.allclose(g["w_z"][0][2 * Dv:], 4.1 + 0.001 * np.arange(2 * Dv))  # z heads 2, 3
    assert np.allclose(g["w_b"][0], [5.0, 5.1, 5.2, 5.3]) and np.allclose(g["w_a"][0], [6.0, 6.1, 6.2, 6.3])
    assert g["conv_w"].shape == (4, 2 * HK * Dk + HV * Dv)
    # the attention layer: each head's q and gate lie side by side in q_proj
    raw4 = {k: np.zeros(shape, np.float32) for k, (shape, _) in layer(3).items()}
    raw4 = {k.replace(".experts.*.", ".experts.0."): (v[0] if ".experts.*." in k else v)
            for k, v in raw4.items()}
    for e in range(1, cfg["num_experts"]):
        for n in ("gate", "up", "down"):
            raw4[f"mlp.experts.{e}.{n}_proj.weight"] = raw4[f"mlp.experts.0.{n}_proj.weight"]
    Hd, H = cfg["head_dim"], cfg["num_attention_heads"]
    wq = np.zeros((2 * H * Hd, D), np.float32)
    wq[:, 0] = np.tile(np.concatenate([np.full(Hd, 7.0), np.full(Hd, 8.0)]), H)
    raw4["self_attn.q_proj.weight"] = wq
    a = model.map_layer(raw4)["attn"]
    assert (a["wq"][0] == 7.0).all() and (a["w_qgate"][0] == 8.0).all()
    assert a["wq"].shape == (D, H * Hd)


def test_what_is_refused_is_said(checkpoint):
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    cfg, _ = checkpoint
    mc = ModelConfig.from_hf({**cfg, "num_hidden_layers": 8})
    cls = get_ring_model_cls("qwen3_next")
    assert cls(mc, range(4, 8)).paged_kinds == ("state", "state", "state", "full")
    for layers in (range(1, 5), range(0, 3), [0, 1, 2, 3, 5, 6, 7, 8]):
        with pytest.raises(NotImplementedError, match="whole periods"):
            cls(mc, layers)
    model = cls(mc, range(8))
    with pytest.raises(NotImplementedError, match="mesh axis"):
        model.apply_window({"attn": {"wq": np.zeros((1, 4, 4))}}, None, None, 0, tp_axis="model")
    with pytest.raises(NotImplementedError, match="stream"):
        model.wrap_offload_layer({})
    with pytest.raises(ValueError, match="outside the router"):
        cls(ModelConfig.from_hf({**cfg, "expert_offset": 20}), range(4))
