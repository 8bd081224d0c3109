"""Command A+ (`cohere2_moe`): the plain reference against the system at a
tiny size on the CPU, with the window SHORTER than the prompt, so that the
window layers' tables give blocks back while the comparison runs; the
expert share against the uncut layer; the configuration against the
catalog's row; the kernels' operation and byte counts.

Tolerance 2e-3 nat on log-probabilities, as for the other reference: both
sides run float32 over the same float32 weights (measured 5e-7 here).  A
window off by one, no window, or the shared experts summed and not
averaged moves them by more than ten times that (asserted below).
"""

import json
import shutil

import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.harness.weights import reference_module, write_checkpoint
from dnet_tpu.obs.phases import KV_KIND_WINDOW

TOL = 2e-3
CONFIG = spec.BENCH_DIR / "configs" / "command-a-plus-4l-ep8.json"
BENCH_KEYS = ("assumed", "deployment", "serve", "check", "rehearse")


def tiny_config(**over):
    full = spec.load_json(CONFIG)
    cfg = {k: v for k, v in full.items() if k not in BENCH_KEYS}
    cfg.update(full["rehearse"]["config"])
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    cfg = tiny_config()
    d = tmp_path_factory.mktemp("bench_cohere2_moe")
    write_checkpoint(d, cfg, seed=2**31 + 28, dtype="float32")
    return cfg, d


def prompt(cfg, n=61):
    rng = np.random.default_rng(0)
    return [int(i) for i in rng.integers(1, cfg["vocab_size"], size=n)]


def worst_error(cfg, model_dir, ids, got):
    import jax

    seq = ids + [r.token_id for r in got[:-1]]
    ref = reference_module(cfg["model_type"])
    want = np.asarray(jax.nn.log_softmax(ref.logits(model_dir, cfg, seq, last=len(got)), axis=-1))
    worst = 0.0
    for j, r in enumerate(got):
        for tid, lp in [(r.token_id, r.logprob), *r.top_logprobs]:
            worst = max(worst, abs(lp - want[j, tid]))
    return worst


def decoding():
    from dnet_tpu.core.types import DecodingParams

    return DecodingParams(temperature=0.0, logprobs=True, top_logprobs=20)


def test_slot_addressed_prefill_then_decode_matches_the_reference(checkpoint):
    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    assert cfg["sliding_window"] < 61  # the window is shorter than the prompt
    eng = LocalEngine(model_dir, max_seq=128, param_dtype="float32")
    ids = prompt(cfg)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) < TOL


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
def test_chunked_prefill_then_paged_decode_matches_the_reference(checkpoint, monkeypatch, kernels):
    """The served path: prefill in 16-token chunks, then decode through the
    per-kind paged pools (one step at a time and one fused chunk), while
    the window layers' tables release blocks; accounting exact per kind."""
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.obs import metric

    cfg, model_dir = checkpoint
    for k, v in {"DNET_KV_BLOCK_TOKENS": "8", "DNET_SCHED_PREFILL_CHUNK": "16"}.items():
        monkeypatch.setenv(k, v)
    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=3, max_seq=128, param_dtype="float32")
        wpool, wtables = eng.kv_pools[KV_KIND_WINDOW], eng._kind_tables[KV_KIND_WINDOW]
        assert eng.kv_pool is not None and wpool.total == 3 * 6  # window 24 + step 16 in blocks of 8, + 1
        released0 = metric("dnet_kv_window_blocks_released_total").value
        dec, ids = decoding(), prompt(cfg)
        eng.reserve_slot("a")
        for i in range(0, len(ids), 16):
            logits = eng.prefill_chunk("a", ids[i:i + 16])
        res = eng.adopt_prefilled("a", logits, dec)
        slot = eng.slot_of["a"]
        # the window kind never took the blocks behind the window
        assert (wtables[slot].base, len(wtables[slot].blocks)) == (4, 4)
        assert len(eng._tables[slot].blocks) == 8
        got = [eng.token_result("a", res, step=0, decoding=dec)]
        for s in range(1, 14):
            out, errs = eng.decode_batch(
                {"a": (got[-1].token_id, dec)}, budgets={"a": 4} if s == 3 else None
            )
            assert not errs
            got.append(eng.token_result("a", out["a"], step=s, decoding=dec))
            for pool in eng.kv_pools.values():
                pool.check_conservation()
                assert pool.used + pool.free == pool.total
        assert metric("dnet_kv_window_blocks_released_total").value - released0 >= 2
        assert wtables[slot].base >= 6 and len(wtables[slot].blocks) <= 5
        held = metric("dnet_moe_assignments_total").labels(held="yes").value
        away = metric("dnet_moe_assignments_total").labels(held="no").value
        assert held > 0 and away > 0 and (held + away) % (4 * 2) == 0  # layers x top-k a token
        assert worst_error(cfg, model_dir, ids, got) < TOL
        eng.end_session("a")
        assert eng.kv_pool.used == 0 and wpool.used == 0
        eng.close()
    finally:
        reset_settings_cache()


@pytest.mark.parametrize(
    "wrong",
    [{"sliding_window": 23}, {"sliding_window": None},
     {"shared_expert_combination_strategy": "sum"}, {"logit_scale": 0.9}],
    ids=["window-off-by-one", "no-window", "shared-not-averaged", "logit-scale"],
)
def test_the_comparison_notices_a_served_side_that_is_wrong(checkpoint, tmp_path, wrong):
    from dnet_tpu.core.engine import LocalEngine

    cfg, model_dir = checkpoint
    served = tmp_path / "served"
    shutil.copytree(model_dir, served)
    (served / "config.json").write_text(json.dumps(dict(cfg, **wrong)))
    eng = LocalEngine(served, max_seq=128, param_dtype="float32")
    ids = prompt(cfg)
    got = list(eng.generate(ids, decoding(), max_tokens=6))
    assert worst_error(cfg, model_dir, ids, got) > 10 * TOL


def random_layer(cfg, seed=5):
    """One layer's raw HF tensors, float32."""
    ref = reference_module("cohere2_moe")
    _, layer = ref.tensor_table(cfg)
    rng = np.random.default_rng(seed)
    raw = {}
    for name, (shape, kind) in layer(0).items():
        x = rng.standard_normal(shape).astype(np.float32) * (0.2 if kind != "norm" else 0.1)
        x = x + 1.0 if kind == "norm" else x
        if ".experts.*." in name:
            for e in range(shape[0]):
                raw[name.replace(".experts.*.", f".experts.{e}.")] = x[e]
        else:
            raw[name] = x
    return raw


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Each of eight chips holds one of eight routed experts and both
    shared ones: the shares' routed parts plus the shared term counted
    once are the uncut reference's whole layer (model-configs guide,
    section 4), and each share equals the reference given that share."""
    import jax.numpy as jnp

    from dnet_tpu.models.base import ModelConfig
    from dnet_tpu.models.cohere2_moe import Cohere2MoeRingModel

    ref = reference_module("cohere2_moe")
    whole_cfg = tiny_config(num_experts=8, num_experts_routed=8)
    raw = random_layer(whole_cfg)
    stack = lambda fmt, ids: np.stack([raw[fmt.format(e)] for e in ids])  # noqa: E731

    def ref_params(ids):
        p = {k: v for k, v in raw.items() if ".experts." not in k or "shared" in k}
        for w in ("gate_proj", "up_proj", "down_proj"):
            fmt = "mlp.experts.{}." + w + ".weight"
            p[f"mlp.experts.*.{w}.weight"] = (
                stack(fmt, ids) if ids else np.zeros((0, *raw[fmt.format(0)].shape), np.float32)
            )
        return p

    h = jnp.asarray(np.random.default_rng(1).standard_normal((37, 64)), jnp.float32)
    whole = np.asarray(ref.moe(h, ref_params(range(8)), whole_cfg))
    shared = np.asarray(ref.moe(h, ref_params([]), whole_cfg))  # no expert held: the shared term
    assert np.abs(shared).max() > 1e-3 and np.abs(whole - shared).max() > 1e-3
    total, held = shared.copy(), 0
    for s in range(8):
        cfg = tiny_config(num_experts=1, num_experts_routed=8, expert_offset=s)
        model = Cohere2MoeRingModel(ModelConfig.from_hf(cfg), range(4))
        p = {k: jnp.asarray(v) for k, v in model.map_layer(raw).items()}
        out, n = model._moe(p, h[None], h[None])
        part = np.asarray(out[0])
        np.testing.assert_allclose(part, np.asarray(ref.moe(h, ref_params([s]), cfg)), atol=2e-5)
        total += part - shared
        held += int(n.sum())
    np.testing.assert_allclose(total, whole, atol=1e-4)
    assert held == 37 * 2  # every assignment is held by exactly one share


def test_a_layer_that_holds_every_expert_gives_what_it_gave_before():
    """qwen3_moe / mixtral go through the same closures with the whole
    range: bit-equal to the arithmetic the closures had before the share."""
    import jax
    import jax.numpy as jnp

    from dnet_tpu.ops.moe import moe_apply, swiglu_expert_closures

    rng = np.random.default_rng(3)
    N, D, F, E, k = 19, 32, 16, 8, 2
    p = {n: jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.3
         for n, s in (("e_gate", (E, D, F)), ("e_up", (E, D, F)), ("e_down", (E, F, D)))}
    flat = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    scores = jax.nn.softmax(jnp.asarray(rng.standard_normal((N, E)), jnp.float32), -1)
    top_w, top_idx = jax.lax.top_k(scores, k)
    effn, dense, E_local = swiglu_expert_closures(p, flat, scores, top_idx, top_w, None)
    weights = jnp.zeros_like(scores).at[jnp.arange(N)[:, None], top_idx].set(top_w)
    inner = jax.nn.silu(jnp.einsum("nd,edf->nef", flat, p["e_gate"])) * jnp.einsum(
        "nd,edf->nef", flat, p["e_up"])
    before = jnp.einsum("ned,ne->nd", jnp.einsum("nef,efd->ned", inner, p["e_down"]), weights)
    assert E_local == E and np.array_equal(np.asarray(dense()), np.asarray(before))
    out, partial = moe_apply("dense", flat, top_idx, top_w, effn, E, 0.0, k, None, dense)
    assert not partial and np.array_equal(np.asarray(out), np.asarray(before))
    exact, _ = moe_apply("dispatch", flat, top_idx, top_w, effn, E, 0.0, k, None, dense)
    np.testing.assert_allclose(np.asarray(exact), np.asarray(before), atol=1e-5)
    # a share through the dispatch path: the slots routed elsewhere drop out
    half = {n: v[2:6] for n, v in p.items()}
    effn_h, dense_h, _ = swiglu_expert_closures(half, flat, scores, top_idx, top_w, None, offset=2)
    part, _ = moe_apply("dispatch", flat, top_idx, top_w, effn_h, 4, 0.0, k, None, dense_h,
                        offset=2, n_routed=E)
    np.testing.assert_allclose(np.asarray(part), np.asarray(dense_h()), atol=1e-5)


def test_the_configuration_holds_every_number_of_the_catalogs_row():
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "command-a-plus-4l-ep8")
    cfg = spec.load_json(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types"}
    assert cfg["layer_types"] == row["config"]["layer_types"][:4]  # one whole period
    assert cfg["num_experts_routed"] == row["config"]["num_experts"] == 8 * cfg["num_experts"]
    # floors of the guide: a period and four layers, 8 routed experts, 1/8 vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    env = cfg["serve"]["env"]
    assert int(env["DNET_API_MAX_SEQ_LEN"]) % int(env["DNET_KV_BLOCK_TOKENS"]) == 0
    assert int(env["DNET_API_MAX_SEQ_LEN"]) >= 16384 + 48
    # the check's prompt is longer than the window, a chunk and a block
    assert cfg["check"]["prompt_tokens"] > cfg["sliding_window"] + 256 + int(env["DNET_KV_BLOCK_TOKENS"])


def test_the_mix_is_the_issues():
    from benchmarks.harness import traffic

    mix = spec.load_json(spec.BENCH_DIR / "traffic" / "mixedlen-sat-16.json")
    plans = traffic.plan(mix, 7, 32768)
    assert len(plans) == 16 and all(len(p) == 24 for p in plans)
    lens = sorted(len(r.prompt_ids) for p in plans for r in p[:4])
    assert 1024 <= lens[0] and lens[-1] <= 16384
    assert sum(n > 4096 for n in lens) == len(lens) // 2  # half past the window
    assert 5000 < sum(lens) / len(lens) < 6000
    for p in plans:  # every client's block holds one prompt of each band
        bands = sorted(int(np.log2(len(r.prompt_ids) / 1024)) for r in p[:4])
        assert bands == [0, 1, 2, 3]
    answers = [r.max_tokens for p in plans for r in p[1:]]
    assert 16 <= min(answers) and max(answers) <= 48
    assert max(mix["warm_prompt_tokens"]) == 16384 and mix["schedule_seed"]


def test_kernel_costs_count_the_keys_inside_triangle_and_window():
    from benchmarks import kernel_costs as kc

    W = 4096
    assert kc.attended_keys(0) == 1 and kc.attended_keys(9999, W) == W
    assert kc.prompt_pairs(16384) == 16384 * 16385 // 2
    for T in (100, W, W + 1, 16384):
        assert kc.prompt_pairs(T, W) == kc.prefill_pairs(0, T, W)
    chunks = sum(kc.prefill_pairs(s, 256, W) for s in range(0, 16384, 256))
    assert chunks == kc.prompt_pairs(16384, W)
    # ISSUE 28's arithmetic: a 16k prompt, 128 heads of 128: 8.8 TFLOP a full
    # layer, 3.85 a window layer (T x 4096 pairs less the first triangle)
    full = kc.attention_ops(kc.prompt_pairs(16384), 128, 128)
    win = kc.attention_ops(kc.prompt_pairs(16384, W), 128, 128)
    assert round(full / 1e12, 1) == 8.8 and round(win / 1e12, 2) == 3.85
    # bytes: a decode step reads each attended key's K and V row once per kv head
    assert kc.decode_bytes(16383, 128, 8, 128) == 2 * 16384 * 8 * 128 * 2 + 2 * 128 * 128 * 2
    assert kc.decode_bytes(16383, 128, 8, 128, W) == 2 * W * 8 * 128 * 2 + 2 * 128 * 128 * 2
    assert kc.prefill_bytes(8192, 256, 128, 8, 128, W) < kc.prefill_bytes(8192, 256, 128, 8, 128)
    r = kc.roofline_share(197e12, 1, 2.0, 197e12, 819e9)
    assert r["bound"] == "compute" and abs(r["share"] - 0.5) < 1e-9
    r = kc.roofline_share(1, 819e9, 4.0, 197e12, 819e9)
    assert r["bound"] == "memory" and abs(r["share"] - 0.25) < 1e-9
