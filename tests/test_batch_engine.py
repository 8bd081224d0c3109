"""Continuous batching: batched decode must match the single-sequence engine."""

import numpy as np
import pytest

from dnet_tpu.core.types import DecodingParams

pytestmark = pytest.mark.core


@pytest.fixture(scope="module")
def batched(tiny_llama_dir):
    from dnet_tpu.core.batch import BatchedEngine

    return BatchedEngine(tiny_llama_dir, slots=4, max_seq=64, param_dtype="float32")


@pytest.fixture(scope="module")
def local_ref(tiny_llama_dir):
    from dnet_tpu.core.engine import LocalEngine

    return LocalEngine(tiny_llama_dir, max_seq=64, param_dtype="float32")


def greedy_tokens(eng, ids, n, nonce):
    return [
        r.token_id
        for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=n, nonce=nonce)
    ]


def test_single_sequence_matches_local(batched, local_ref):
    ids = [256, 72, 101, 108]
    assert greedy_tokens(batched, ids, 6, "a") == greedy_tokens(local_ref, ids, 6, "a")


def test_interleaved_requests_match_serial(batched, local_ref):
    """Three prompts decoded in lockstep through the shared batched program
    produce the same greedy tokens as serial single-sequence decoding."""
    prompts = {
        "r0": [256, 72, 101],
        "r1": [256, 84, 104, 105, 110],
        "r2": [256, 65],
    }
    expected = {n: greedy_tokens(local_ref, ids, 5, n) for n, ids in prompts.items()}

    dec = DecodingParams(temperature=0.0)
    last = {}
    for n, ids in prompts.items():
        batched.end_session(n)
        res = batched.prefill_and_sample(n, ids, dec)
        last[n] = int(res.token[0])
    got = {n: [t] for n, t in last.items()}
    for _step in range(1, 5):
        results, errs = batched.decode_batch({n: (last[n], dec) for n in prompts})
        assert not errs
        for n, res in results.items():
            last[n] = int(res.token[0])
            got[n].append(last[n])
    for n in prompts:
        batched.end_session(n)
        assert got[n] == expected[n], n


def test_partial_batch_freezes_inactive(batched, local_ref):
    """A slot that skips a step must not advance or corrupt its KV."""
    dec = DecodingParams(temperature=0.0)
    ids_a, ids_b = [256, 72, 101], [256, 84, 104]
    expected_a = greedy_tokens(local_ref, ids_a, 4, "za")
    expected_b = greedy_tokens(local_ref, ids_b, 4, "zb")

    for n, ids in (("a2", ids_a), ("b2", ids_b)):
        batched.end_session(n)
    ra = batched.prefill_and_sample("a2", ids_a, dec)
    rb = batched.prefill_and_sample("b2", ids_b, dec)
    ta, tb = int(ra.token[0]), int(rb.token[0])
    got_a, got_b = [ta], [tb]
    # advance only a2 for two steps, then b2 catches up step by step
    for _ in range(2):
        ta = int(batched.decode_batch({"a2": (ta, dec)})[0]["a2"].token[0])
        got_a.append(ta)
    for _ in range(3):
        step_req = {"b2": (tb, dec)}
        if len(got_a) < 4:
            step_req["a2"] = (ta, dec)
        out, errs = batched.decode_batch(step_req)
        assert not errs
        tb = int(out["b2"].token[0])
        got_b.append(tb)
        if "a2" in out:
            ta = int(out["a2"].token[0])
            got_a.append(ta)
    batched.end_session("a2")
    batched.end_session("b2")
    assert got_a == expected_a
    assert got_b == expected_b


def test_slot_exhaustion_raises(batched):
    dec = DecodingParams(temperature=0.0)
    nonces = [f"fill{i}" for i in range(batched.slots)]
    for n in nonces:
        batched.prefill_and_sample(n, [256, 65], dec)
    with pytest.raises(RuntimeError, match="no free batch slots"):
        batched.prefill_and_sample("overflow", [256, 65], dec)
    for n in nonces:
        batched.end_session(n)


def test_mixed_sampling_params_batch_together(batched):
    """Greedy and hot-temperature requests share one batched step."""
    dec_greedy = DecodingParams(temperature=0.0)
    dec_hot = DecodingParams(temperature=1.5, top_p=0.9, seed=1)
    batched.end_session("g")
    batched.end_session("h")
    rg = batched.prefill_and_sample("g", [256, 72, 101], dec_greedy)
    rh = batched.prefill_and_sample("h", [256, 72, 101], dec_hot)
    out, errs = batched.decode_batch(
        {"g": (int(rg.token[0]), dec_greedy), "h": (int(rh.token[0]), dec_hot)}
    )
    assert not errs
    assert set(out) == {"g", "h"}
    assert all(0 <= int(r.token[0]) < batched.config.vocab_size for r in out.values())
    batched.end_session("g")
    batched.end_session("h")


def test_streaming_weights_rejected(tiny_llama_dir):
    from dnet_tpu.api.inference import EngineCapabilityError
    from dnet_tpu.core.batch import BatchedEngine

    # typed since the sched PR: api/http.py maps it to 422, not a 500
    with pytest.raises(EngineCapabilityError, match="resident weights"):
        BatchedEngine(
            tiny_llama_dir, slots=2, max_seq=64, param_dtype="float32",
            window_size=1, residency_size=1,
        )


def test_unknown_nonce_fails_alone(batched):
    """A cancelled request in the batch must not poison the others."""
    dec = DecodingParams(temperature=0.0)
    batched.end_session("ok")
    r = batched.prefill_and_sample("ok", [256, 72], dec)
    out, errs = batched.decode_batch(
        {"ok": (int(r.token[0]), dec), "ghost": (5, dec)}
    )
    assert "ok" in out and "ghost" in errs
    batched.end_session("ok")


def test_seeded_sampling_immune_to_other_traffic(tiny_llama_dir):
    """A seeded request's tokens must not depend on batched steps that ran
    without it (inactive lanes' RNG keys must not advance)."""
    from dnet_tpu.core.batch import BatchedEngine

    dec = DecodingParams(temperature=1.0, seed=42)
    other = DecodingParams(temperature=0.0)

    def run(noise_steps: int) -> list:
        eng = BatchedEngine(tiny_llama_dir, slots=4, max_seq=64, param_dtype="float32")
        rs = eng.prefill_and_sample("s", [256, 72, 101], dec)
        ts = int(rs.token[0])
        ro = eng.prefill_and_sample("o", [256, 65], other)
        to = int(ro.token[0])
        toks = [ts]
        for _ in range(noise_steps):  # steps that EXCLUDE the seeded request
            out, _ = eng.decode_batch({"o": (to, other)})
            to = int(out["o"].token[0])
        for _ in range(3):
            out, _ = eng.decode_batch({"s": (ts, dec)})
            ts = int(out["s"].token[0])
            toks.append(ts)
        eng.close()
        return toks

    assert run(0) == run(3)


def test_budget_chunks_match_serial_steps(tiny_llama_dir):
    """A budget never changes the stream and never widens a dispatch: every
    call sends ONE step for the lanes that asked, including a lane frozen
    midway and a seeded sampled lane."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.obs import metric

    sent = metric("dnet_decode_dispatch_total")
    lane_steps = metric("dnet_decode_lane_steps_total")

    dec = DecodingParams(temperature=0.0)
    hot = DecodingParams(temperature=1.0, seed=9)
    prompts = {"g": [256, 72, 101], "h": [256, 84, 104, 105]}

    def run(budgeted: bool):
        eng = BatchedEngine(tiny_llama_dir, slots=4, max_seq=64, param_dtype="float32")
        decs = {"g": dec, "h": hot}
        last = {
            n: int(eng.prefill_and_sample(n, ids, decs[n]).token[0])
            for n, ids in prompts.items()
        }
        got = {n: [t] for n, t in last.items()}
        for step in range(1, 9):
            reqs = {n: (last[n], decs[n]) for n in prompts}
            if step > 4:
                reqs.pop("g")  # g freezes; h keeps decoding
            budgets = {n: 9 - step for n in reqs} if budgeted else None
            before = sent.value, lane_steps.value
            out, errs = eng.decode_batch(reqs, budgets=budgets)
            assert not errs, errs
            assert (sent.value, lane_steps.value) == (before[0] + 1, before[1] + len(reqs))
            for n, r in out.items():
                last[n] = int(r.token[0])
                got[n].append(last[n])
        eng.close()
        return got

    assert run(budgeted=True) == run(budgeted=False)


def test_deepseek_accepted_at_load(tmp_path_factory):
    """DeepSeek-V2 gates its KV writes (supports_kv_commit) and threads the
    paged-attend hook: the batched engine accepts it and serves it from the
    block pool, whose entry is the LATENT (one leaf, kv_lora_rank +
    qk_rope_head_dim kept in 128 lanes), not the heads' keys and values
    (full behavior covered by tests/test_deepseek_mesh_batch.py and, for
    the block, tests/test_mistral4_parity.py)."""
    from tests.fakes.checkpoints import make_tiny_deepseek_v2
    from dnet_tpu.core.batch import KV_PAGED, BatchedEngine, kv_layout
    from dnet_tpu.kv import KindStore

    d = tmp_path_factory.mktemp("batch_dsv2")
    make_tiny_deepseek_v2(d)
    eng = BatchedEngine(d, slots=2, max_seq=32, param_dtype="float32")
    assert eng.model.supports_kv_commit and eng.model.supports_paged_attend
    assert kv_layout(eng.model, 0, 0, 32)[0] == KV_PAGED
    assert isinstance(eng.kv_store, KindStore) and eng.kv_pool is not None
    assert eng.kv_store.leaves == {"c": (1, 128)} and eng.kv_store.latent_rank == 24
    # a quantised cache is the expanded one, on dense slots
    assert kv_layout(eng.model, 8, 0, 32)[0] == "dense"
    eng.close()


def test_logit_bias_per_lane(tiny_llama_dir):
    """Two lanes with DIFFERENT biases in one batched step: each lane's
    forced token wins only on its own lane."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    eng = BatchedEngine(tiny_llama_dir, slots=2, max_seq=64, param_dtype="float32")
    da = DecodingParams(temperature=0.0, logit_bias={65: 100.0})
    db = DecodingParams(temperature=0.0, logit_bias={66: 100.0})
    eng.prefill_and_sample("a", [256, 72], da)
    eng.prefill_and_sample("b", [256, 73], db)
    results, errors = eng.decode_batch({"a": (65, da), "b": (66, db)})
    assert not errors
    assert int(results["a"].token[0]) == 65
    assert int(results["b"].token[0]) == 66
    eng.close()
