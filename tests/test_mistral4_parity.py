"""Mistral-Small-4 (`mistral4`: multi-head latent attention, an expert layer
after every one) on the served path against the plain reference, float32
on seeded weights at a tiny size (4 heads of 16 + 8 over a latent of 16,
two layers, 4 experts held of 8, top-2): prefill in chunks that EXPAND the
latent row, adoption into the pool of latent blocks, then decode through
it ABSORBED, one step at a time (alone too), with another lane busy beside
it.  The reference (benchmarks/reference/mistral4.py) makes every head's
keys and values from the latent and attends every pair of positions: not
absorbed, no cache.  Logits and log-probabilities are compared, not tokens.

`original_max_position_embeddings` is 32 here, so every sequence crosses
the boundaries where the query scale a(t) leaves 1 (32, 64, ...) and where
YaRN bends the frequencies, in prefill and in decode; the last tests leave
a(t) and sigma's m^2 out and see it.

And the SHARE test of the model-configs guide's section 4: the eight
shares' routed parts plus the shared expert counted once add up to the
uncut reference's layer."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.weights import reference_module, write_checkpoint
from tests.fakes.checkpoints import make_tiny_mistral4, tiny_mistral4_config

TOL = 2e-3  # nat, float32 both sides (measured 5e-7 .. 1e-6)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    d = tmp_path_factory.mktemp("mistral4_parity")
    return make_tiny_mistral4(d), d


@pytest.fixture()
def small_blocks(monkeypatch):
    from dnet_tpu.config import reset_settings_cache

    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    yield
    reset_settings_cache()


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


def test_the_config_is_read(checkpoint):
    """`rope_parameters` where a config has no `rope_scaling`; the latent
    entry and its kept width; the share."""
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    cfg, _ = checkpoint
    mc = ModelConfig.from_hf(cfg)
    assert mc.rope_scaling["rope_type"] == "yarn" and mc.rope_theta == 10000
    m = get_ring_model_cls("mistral4")(mc, range(mc.num_hidden_layers))
    assert (m.q_scale_beta, m.q_scale_period) == (0.1, 32)
    assert m.softmax_scale == pytest.approx(24**-0.5 * (0.1 * np.log(16) + 1) ** 2)
    assert (m.latent_rank, m.latent_dim, m.entry_dim) == (16, 24, 128)
    assert m.pool_leaves() == {"c": (1, 128)} and m.supports_paged_attend
    assert (m.n_routed_experts, m.n_routed, m.expert_offset) == (4, 8, 0)
    kv = jax.eval_shape(lambda: m.init_kv(2, 1, 64, "float32"))
    assert {k: v.shape for k, v in kv.items()} == {"c": (2, 1, 64, 1, 128)}
    # ONE rule: a model on a mesh, or a quantised cache, keeps the expanded one
    q8 = jax.eval_shape(lambda: m.init_kv(2, 1, 64, "float32", quant_bits=8))
    assert q8["k"].shape == (2, 1, 64, 4, 24) and q8["v"].shape == (2, 1, 64, 4, 16)
    m.on_mesh = True
    assert set(jax.eval_shape(lambda: m.init_kv(2, 1, 64, "float32"))) == {"k", "v"}


def test_one_sequence_at_a_time_matches_the_reference(checkpoint):
    """LocalEngine: the session's row is the latent; a prompt in one
    program (expanded), then steps over the row (absorbed, dense)."""
    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    eng = LocalEngine(model_dir, max_seq=128, param_dtype="float32")
    ids = prompt(cfg, 61)  # the steps cross position 64
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) < TOL


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_prefill_then_decode_through_the_latent_pool(
    checkpoint, monkeypatch, small_blocks, kernels, chunk
):
    from dnet_tpu.kv import KindStore
    from dnet_tpu.obs import metric

    cfg, model_dir = checkpoint
    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    from dnet_tpu.core.batch import BatchedEngine

    eng = BatchedEngine(model_dir, slots=3, max_seq=128, param_dtype="float32")
    assert isinstance(eng.kv_store, KindStore) and eng.kv_store.latent_rank == 16
    pool = eng.kv_store.kv["full"]
    assert {k: v.shape for k, v in pool.items()} == {"c": (2, 48, 8, 128)}
    dec, ids, other = decoding(), prompt(cfg, 77), prompt(cfg, 30, seed=5)
    pre0 = metric("dnet_mla_tokens_total").labels(phase="prefill").value
    exp0 = metric("dnet_mla_expanded_tokens_total").value
    byt0 = metric("dnet_mla_latent_bytes_total").value
    tiles = metric("dnet_flash_tiles_total")
    til0 = sum(tiles.labels(kind="full", state=s).value for s in ("folded", "skipped"))
    # another sequence holds a lane and a table, and steps beside ours
    o = eng.prefill_and_sample("other", other, dec)
    o_tok = int(o.token[0])
    eng.reserve_slot("a")
    for i in range(0, len(ids), chunk):  # 77 tokens: the last chunk is ragged
        logits = eng.prefill_chunk("a", ids[i:i + chunk])
    assert metric("dnet_mla_tokens_total").labels(phase="prefill").value - pre0 == 77
    ends = [min(i + chunk, 77) for i in range(0, 77, chunk)]
    assert metric("dnet_mla_expanded_tokens_total").value - exp0 == 2 * sum(ends)
    # each chunk is one q tile against the staged row's one kv tile, in
    # both layers, where a kernel runs to fold it
    til1 = sum(tiles.labels(kind="full", state=s).value for s in ("folded", "skipped"))
    assert til1 - til0 == (2 * len(ends) if kernels == "interpret" else 0)
    res = eng.adopt_prefilled("a", logits, dec)
    assert "a" not in eng.eng.sessions  # the session's latent row moved into the pool
    assert len(eng._tables[eng.slot_of["a"]].blocks) == 10  # 77 tokens in blocks of 8
    got = [eng.token_result("a", res, step=0, decoding=dec)]
    byt1 = metric("dnet_mla_latent_bytes_total").value
    for step in range(1, 5):  # single steps over a block's edge (80), the other lane active
        out, errs = eng.decode_batch({"a": (got[-1].token_id, dec), "other": (o_tok, dec)})
        assert not errs
        o_tok = int(out["other"].token[0])
        got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
    # live tokens x 2 layers x (16 + 8) x 4 bytes: what the algorithm reads
    live = sum(77 + k for k in range(4)) + sum(30 + k for k in range(4))
    assert metric("dnet_mla_latent_bytes_total").value - byt1 == live * 2 * 24 * 4
    assert byt1 == byt0  # a prefill books none
    assert len(eng._tables[eng.slot_of["a"]].blocks) == 11
    # four steps alone (the other lane idles), a budget riding along: it
    # never widens a dispatch
    sent = metric("dnet_decode_dispatch_total")
    sent0 = sent.value
    for step in range(5, 9):
        out, errs = eng.decode_batch({"a": (got[-1].token_id, dec)}, budgets={"a": 9 - step})
        assert not errs and sent.value - sent0 == step - 4
        got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
    assert worst_error(cfg, model_dir, ids, got) < TOL
    eng.close()


def test_dense_slots_agree_with_the_pool(checkpoint, small_blocks):
    """`kv_paged=False` (the tests' dense engine) keeps a latent row a slot
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


def test_a_preempted_lane_prefills_again_and_goes_on(checkpoint, small_blocks):
    """A lane gives its blocks back mid-stream and is prefilled again over
    prompt + what it had generated: the stream goes on as if nothing."""
    from dnet_tpu.core.batch import BatchedEngine

    cfg, model_dir = checkpoint
    eng = BatchedEngine(model_dir, slots=2, max_seq=128, param_dtype="float32")
    dec, ids = decoding(), prompt(cfg, 45, seed=2)
    res = eng.prefill_and_sample("a", ids, dec)
    got = [eng.token_result("a", res, step=0, decoding=dec)]
    for step in range(1, 4):
        out, _ = eng.decode_batch({"a": (got[-1].token_id, dec)})
        got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
    used = eng.kv_pool.used
    eng.free_slot("a")  # preempted: its blocks go back
    assert eng.kv_pool.used < used
    again = ids + [r.token_id for r in got[:-1]]
    res = eng.prefill_and_sample("a", again, dec)
    assert int(res.token[0]) == got[-1].token_id
    for step in range(4, 7):
        out, _ = eng.decode_batch({"a": (got[-1].token_id, dec)})
        got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
    assert worst_error(cfg, model_dir, ids, got) < TOL
    eng.kv_pool.check_conservation()
    eng.close()


def test_latent_blocks_alias_into_a_second_prompt(checkpoint, small_blocks):
    """Prefix sharing is left ON for a latent pool: a latent block is a
    `full`-kind block, aliased whole, gathered into the staged latent row,
    its partial tail copied on write."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.obs import metric

    cfg, model_dir = checkpoint
    eng = BatchedEngine(
        model_dir, slots=2, max_seq=128, param_dtype="float32", prefix_cache_size=4
    )
    assert eng.paged_prefix is not None and eng.prefix_refusal is None
    eng.paged_prefix.min_tokens = 8
    dec, base = decoding(), prompt(cfg, 44, seed=9)  # 5 full blocks + 4 tokens
    eng.prefill_and_sample("p1", base, dec)
    shared0 = metric("dnet_kv_prefix_shared_blocks_total").value
    grown = base + prompt(cfg, 9, seed=10)
    res = eng.prefill_and_sample("p2", grown, dec)
    assert metric("dnet_kv_prefix_shared_blocks_total").value - shared0 >= 5
    got = [eng.token_result("p2", res, step=0, decoding=dec)]
    for step in range(1, 4):
        out, errs = eng.decode_batch({"p2": (got[-1].token_id, dec)})
        assert not errs
        got.append(eng.token_result("p2", out["p2"], step=step, decoding=dec))
    assert worst_error(cfg, model_dir, grown, got) < TOL
    eng.end_session("p1")
    eng.end_session("p2")
    eng.kv_pool.check_conservation()
    eng.close()


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tmp_path):
    """Each share holds ONE of the 8 routed experts, routes over all 8 and
    returns its own expert's part plus the shared expert's term.  Summed,
    with the shared expert (what every chip computes alike) counted once,
    they are the uncut layer."""
    from benchmarks.reference.common import Tensors, swiglu
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    whole = tiny_mistral4_config(n_routed_experts=8, num_experts_routed=8)
    write_checkpoint(tmp_path, whole, seed=2**31 + 42, dtype="float32")
    ref = reference_module("mistral4")
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
        model = get_ring_model_cls("mistral4")(mc, range(mc.num_hidden_layers))
        p = {k: jnp.asarray(v) for k, v in model.map_layer(per_expert).items()}
        y, held = model._moe(p, x)
        return np.asarray(y - x)[0], np.asarray(held)[0]

    full, held_all = moe_of(whole)
    assert (held_all == whole["num_experts_per_tok"]).all()
    parts = [moe_of({**whole, "n_routed_experts": 1, "expert_offset": e}) for e in range(8)]
    assert (sum(h for _, h in parts) == whole["num_experts_per_tok"]).all()
    assert min(h.min() for _, h in parts) == 0  # no share holds every token's choice
    # the shared expert's term alone: a share of no routed weight at all
    from benchmarks.reference.common import rms_norm

    u = rms_norm(x[0], raw["post_attention_layernorm.weight"], whole["rms_norm_eps"])
    shared = np.asarray(swiglu(
        u, raw["mlp.shared_experts.gate_proj.weight"],
        raw["mlp.shared_experts.up_proj.weight"], raw["mlp.shared_experts.down_proj.weight"],
    ))
    assert np.max(np.abs(sum(y for y, _ in parts) - 7 * shared - full)) < 1e-5
    assert np.max(np.abs(shared)) > 1e-4  # it is there to be counted eight times by mistake
    # and the uncut layer is the reference's
    want = np.asarray(ref._expert_layer(whole)(x[0], raw) - x[0])
    assert np.max(np.abs(full - want)) < 1e-5


@pytest.mark.parametrize(
    "left_out", [{}, {"llama_4_scaling_beta": 0.0}, {"mscale_all_dim": 0}],
    ids=["nothing (the control)", "the query scale a(t)", "sigma's m squared"],
)
def test_a_scale_left_out_is_seen(checkpoint, tmp_path, left_out):
    """The same weights served WITHOUT a(t), or without m^2 in the softmax
    scale, read far outside the tolerance against the reference that has
    them.  With the harness's N(0, 0.02) weights the scores are small and
    softmax is nearly flat whatever scales them (which is why the cell's
    check can hardly tell); here `q_b_proj` is 64 times larger, for the
    served model and the reference alike."""
    from safetensors.numpy import load_file, save_file

    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    for f in model_dir.iterdir():
        if f.suffix == ".safetensors":
            tensors = load_file(str(f))
            for name in tensors:
                if name.endswith("self_attn.q_b_proj.weight"):
                    tensors[name] = tensors[name] * 64.0
            save_file(tensors, str(tmp_path / f.name))
        else:
            shutil.copy(f, tmp_path / f.name)
    served = json.loads((tmp_path / "config.json").read_text())
    served["rope_parameters"] = {**served["rope_parameters"], **left_out}
    (tmp_path / "config.json").write_text(json.dumps(served))
    eng = LocalEngine(tmp_path, max_seq=128, param_dtype="float32")
    ids = prompt(cfg, 61)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    err = worst_error(cfg, tmp_path, ids, got)
    assert (err < TOL) if not left_out else (err > 5 * TOL), err


def test_what_is_refused_is_said(checkpoint):
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    cfg, _ = checkpoint
    with pytest.raises(ValueError, match="lie outside the router's 8"):
        mc = ModelConfig.from_hf({**cfg, "expert_offset": 6})
        get_ring_model_cls("mistral4")(mc, range(mc.num_hidden_layers))
