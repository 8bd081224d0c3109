"""Qwen3-Next-80B-A3B (`qwen3_next`) in the benchmark: the configuration
against the catalog's row, the plain reference against the system at the
rehearsal size, ahead-of-time v5e compiles of both delta-rule kernels and of
the two attention kernels at head size 256, the kernels' operation and byte
counts, and the cell's rehearsal end to end on the CPU.

Tolerance 2e-3 nat on log-probabilities: both sides run float32 over the
same float32 weights (measured 5e-7 here).

The topology is described inside a fixture (on-chip-measurement guide,
section 2), as in `test_bench_brumby.py`: where the files land on different
workers and only one process may load the TPU library, this file's compile
tests skip.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import kernel_costs_gdn as costs
from benchmarks.harness import spec
from benchmarks.harness.weights import reference_module, write_checkpoint

TOL = 2e-3
CELL = "qwen3next-longdoc-sat"
NAME = "qwen3-next-80b-a3b-4l-ep2"
CONFIG = spec.BENCH_DIR / "configs" / f"{NAME}.json"
BENCH_KEYS = ("assumed", "deployment", "serve", "check", "rehearse")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "shared_expert_intermediate_size", "head_dim", "linear_key_head_dim",
          "linear_value_head_dim", "linear_num_key_heads", "linear_num_value_heads",
          "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
          "linear_conv_kernel_dim", "partial_rotary_factor", "vocab_size")


def tiny_config():
    full = spec.load_json(CONFIG)
    cfg = {k: v for k, v in full.items() if k not in BENCH_KEYS}
    cfg.update(full["rehearse"]["config"])
    return cfg


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    cfg = tiny_config()
    d = tmp_path_factory.mktemp("bench_qwen3_next")
    write_checkpoint(d, cfg, seed=2**31 + 37, dtype="float32")
    return cfg, d


# ---- the configuration ----------------------------------------------------
def test_every_number_of_the_catalog_row_is_under_its_own_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    full = spec.load_json(CONFIG)
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert not any(k.endswith(("_size", "_dim", "_rank")) for k in entry["reduced"])
    differs = sorted(k for k, v in row["config"].items() if full.get(k, "absent") != v)
    assert differs == ["num_experts", "num_hidden_layers"]
    assert full["assumed"]["published"] == {
        k: row["config"][k] for k in ("num_hidden_layers", "num_experts")}
    assert all(full[k] == row["config"][k] for k in WIDTHS)  # no width is cut
    assert full["num_experts_routed"] == row["config"]["num_experts"]
    # the floors: a whole period and four layers, at least 8 experts, the whole vocabulary
    assert full["num_hidden_layers"] % full["full_attention_interval"] == 0
    assert full["num_hidden_layers"] >= 4 and full["num_experts"] >= 8


def test_the_cut_fits_the_chip_as_the_deployment_says():
    full = spec.load_json(CONFIG)
    D, V, F = full["hidden_size"], full["vocab_size"], full["moe_intermediate_size"]
    H, KVH, Hd = full["num_attention_heads"], full["num_key_value_heads"], full["head_dim"]
    HK, HV, Dk, Dv = (full[k] for k in ("linear_num_key_heads", "linear_num_value_heads",
                                        "linear_key_head_dim", "linear_value_head_dim"))
    E, L = full["num_experts"], full["num_hidden_layers"]
    expert = 3 * D * F
    assert round(expert * 2 / 1e6, 2) == 6.29 and round(E * expert * 2 / 1e9, 3) == 1.611
    key, value = HK * Dk, HV * Dv
    delta = D * (2 * key + 2 * value) + D * 2 * HV + value * D + (2 * key + value) * 4
    attn = D * 2 * H * Hd + 2 * D * KVH * Hd + H * Hd * D
    rest = D * full["num_experts_routed"] + 3 * D * full["shared_expert_intermediate_size"] + D
    assert round(delta / 1e6, 1) == 33.7 and round(attn / 1e6, 1) == 27.3
    assert round(rest / 1e6, 1) == 4.2
    weights = 2 * (L * E * expert + 3 * delta + attn + L * rest + 2 * V * D)
    assert round(weights / 1e9, 2) == 7.98
    lanes = int(full["serve"]["env"]["DNET_SCHED_SLOTS"])
    max_seq = int(full["serve"]["env"]["DNET_API_MAX_SEQ_LEN"])
    bt = int(full["serve"]["env"]["DNET_KV_BLOCK_TOKENS"])
    assert max_seq % bt == 0 and max_seq >= 32768 + 256
    pool = lanes * max_seq * KVH * Hd * 2 * 2  # one full layer, k and v, bf16
    assert round(pool / 1e9, 2) == 1.09
    state = lanes * 3 * (costs.state_entry_bytes(HV, Dk, Dv) + 3 * (2 * key + value) * 2)
    assert round(state / 1e9, 2) == 0.10
    assert 0.52 < (weights + pool + state) / 16.9e9 < 0.60  # over the floor of a quarter
    chk = full["check"]
    assert chk["prompt_tokens"] + chk["decode_steps"] <= max_seq
    assert chk["prompt_tokens"] > 2 * 2048  # three chunks at the default budget


def test_the_mix_is_a_data_file_for_the_generator_as_it_is():
    from benchmarks.harness import traffic

    cell = spec.resolve_cell(CELL)
    assert (cell.traffic_name, cell.chips, cell.config_name) == ("longdoc-sat-16", 1, NAME)
    mix = cell.traffic
    assert mix["schedule_seed"] == 37 and mix["requests_per_client"] == 48
    plans = traffic.plan(mix, 3000000019, cell.config["vocab_size"])
    assert len(plans) == 16 and all(len(p) == 48 for p in plans)
    lens = [len(r.prompt_ids) for p in plans for r in p]
    assert 2048 <= min(lens) and max(lens) <= 32768 and 9000 < sum(lens) / len(lens) < 12000
    for p in plans:  # one length from each band in every block of four
        for b in range(0, 48, 4):
            block = sorted(len(r.prompt_ids) for r in p[b:b + 4])
            assert block[0] < 4096 <= block[1] < 8192 <= block[2] < 16384 <= block[3]
    assert all(64 <= r.max_tokens <= 256 for p in plans for r in p[1:])
    again = traffic.plan(mix, 7, cell.config["vocab_size"])
    assert [len(r.prompt_ids) for r in again[3]] == [len(r.prompt_ids) for r in plans[3]]  # one order
    # every program width is warmed: the prefill buckets 16 .. 2048, every
    # power-of-two table and commit width up to the longest prompt's
    warm = mix["warm_prompt_tokens"]
    widths = {max(16, 1 << (n - 1).bit_length()) for n in warm if n <= 2048}
    assert widths == {16, 32, 64, 128, 256, 512, 1024, 2048}
    tables = {1 << (-(-n // 128) - 1).bit_length() for n in warm}
    assert tables == {1, 2, 4, 8, 16, 32, 64, 128, 256} and max(warm) == 32768


def test_the_new_per_layer_metrics_read_in_this_cell_alone():
    bench = spec.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    # asked for by name, wherever they stand: the five readings only this
    # model has (PR 43 gave every other reading of the cell to the entry the
    # other cells read it under)
    alone = ("gdn_step_time_pct", "gdn_chunk_time_pct", "pallas_time_pct.gdn",
             "gdn_state_bytes_in_window", "gdn_prefill_tokens_in_window")
    assert all(by[name]["workloads"] == [CELL] for name in alone)
    joined = ("state_slots_used_peak_pct", "kv_full_blocks_used_peak_pct", "attn_full_time_pct",
              "moe_grouped_time_pct", "moe_assignments_held_in_window", "moe_assignments_routed_in_window",
              "moe_grouped_rows_in_window", "moe_expert_rows_in_window", "sort_time_pct.tokens",
              "itl_p50_ms", "sched_tick_host_mean_ms", "sched_queue_wait_mean_ms",
              "sched_batch_tokens_mean", "prefill_wall_mean_ms", "prefill_ticks_mean",
              "prefill_adopt_mean_ms", "decode_prepare_mean_ms", "decode_readback_wait_mean_ms",
              "decode_deliver_wait_mean_ms", "decode_slot_steps_in_window",
              "decode_lane_steps_in_window", "decode_tokens_delivered_in_window",
              "admit_wait_mean_ms", "mixed_ticks_in_window", "mixed_ticks_overlapped_in_window")
    assert len(set(alone + joined)) == 30  # what PR 37 brought as thirty `.doc` entries
    mine = {m["name"] for m in spec.resolve_cell(CELL).per_layer}
    for name in alone + joined:
        assert CELL in by[name]["workloads"] and name in mine
        reader = spec.load_json(spec.layer_metric_file(name))
        assert reader["reader"] in ("prom_delta", "trace_share", "client")
    assert by["gdn_step_time_pct"]["moves"] == "output_tokens_per_s"
    assert by["gdn_chunk_time_pct"]["moves"] == "ttft_p50_ms"
    assert spec.validate(bench) == []


# ---- reference against system --------------------------------------------
def worst_error(cfg, model_dir, ids, got):
    seq = ids + [r.token_id for r in got[:-1]]
    ref = reference_module(cfg["model_type"])
    want = np.asarray(jax.nn.log_softmax(ref.logits(model_dir, cfg, seq, last=len(got)), axis=-1))
    return max(
        abs(lp - want[j, tid])
        for j, r in enumerate(got)
        for tid, lp in [(r.token_id, r.logprob), *r.top_logprobs]
    )


@pytest.mark.parametrize("kernels", ["emulate", "interpret"])
def test_the_system_matches_the_recurrent_reference_at_the_rehearsal_size(
        checkpoint, monkeypatch, kernels):
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.types import DecodingParams

    cfg, model_dir = checkpoint
    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    reset_settings_cache()
    try:
        from dnet_tpu.core.batch import BatchedEngine

        eng = BatchedEngine(model_dir, slots=2, max_seq=128, param_dtype="float32")
        dec = DecodingParams(temperature=0.0, logprobs=True, top_logprobs=20)
        rng = np.random.default_rng(1)
        ids = [int(i) for i in rng.integers(1, cfg["vocab_size"], size=70)]
        eng.reserve_slot("a")
        for i in range(0, len(ids), 32):
            logits = eng.prefill_chunk("a", ids[i:i + 32])
        got = [eng.token_result("a", eng.adopt_prefilled("a", logits, dec), step=0, decoding=dec)]
        for step in range(1, 5):
            out, errs = eng.decode_batch({"a": (got[-1].token_id, dec)})
            assert not errs
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        assert worst_error(cfg, model_dir, ids, got) < TOL
        eng.close()
    finally:
        reset_settings_cache()


def test_the_reference_is_the_recurrence_and_its_controls_round_what_they_say(checkpoint):
    from benchmarks import precision_control_qwen3_next as pc

    ref = reference_module("qwen3_next")
    key = jax.random.split(jax.random.key(3), 5)
    T, HK, HV, Dk, Dv = 50, 2, 4, 8, 8
    q, k = jax.random.normal(key[0], (2, T, HK, Dk))
    v = jax.random.normal(key[1], (T, HV, Dv))
    g = -0.05 * jax.random.uniform(key[2], (T, HV))
    beta = jax.nn.sigmoid(jax.random.normal(key[3], (T, HV)))
    want = np.asarray(ref.delta_rule(q, k, v, g, beta))
    # by hand, one head, three tokens
    qn = np.asarray(q / np.sqrt((np.asarray(q) ** 2).sum(-1, keepdims=True) + 1e-6) / np.sqrt(Dk))
    kn = np.asarray(k / np.sqrt((np.asarray(k) ** 2).sum(-1, keepdims=True) + 1e-6))
    S = np.zeros((Dk, Dv))
    for t in range(3):
        S = S * np.exp(float(g[t, 3]))
        u = float(beta[t, 3]) * (np.asarray(v[t, 3]) - S.T @ kn[t, 1])  # value head 3 reads key head 1
        S = S + np.outer(kn[t, 1], u)
        assert np.max(np.abs(S.T @ qn[t, 1] - want[t, 3])) < 1e-5
    bf16 = np.asarray(ref.delta_rule(q, k, v, g, beta, round_state=jnp.bfloat16))
    err = np.max(np.abs(bf16 - want))
    assert 1e-4 < err < 0.2  # rounded to bfloat16 after every token: seen, and not wild
    with pc.bf16_state_reference(ref):
        assert ref.logits.keywords == {"round_state": jnp.bfloat16}
    assert not hasattr(ref.logits, "keywords")
    a = np.asarray(jax.random.normal(key[4], (3, 5, 7)))
    r = pc.int8_any(a)
    assert r.shape == a.shape and 1e-4 < np.max(np.abs(r - a)) < 0.05
    assert pc.int8_any(a[0, 0]) is not None and (pc.int8_any(a[0, 0]) == a[0, 0]).all()  # vectors stay
    edge, layer = ref.tensor_table(tiny_config())
    assert "linear_attn.in_proj_qkvz.weight" in layer(0) and "self_attn.q_proj.weight" in layer(3)
    assert "self_attn.q_proj.weight" not in layer(2) and "lm_head.weight" in edge
    # the zero-centred weights and the decay's parameters are of kind `w`
    assert layer(0)["input_layernorm.weight"][1] == "w" and layer(0)["linear_attn.A_log"][1] == "w"
    assert layer(0)["linear_attn.norm.weight"][1] == "norm" and edge["model.norm.weight"][1] == "w"


# ---- the kernels' costs ---------------------------------------------------
def test_the_costs_are_the_issues_arithmetic():
    entry = costs.state_entry_bytes(32, 128, 128)
    assert round(entry / 1e6, 2) == 2.10  # a layer a sequence
    step = costs.gdn_step_cost(lanes=16, k_heads=16, v_heads=32, k_dim=128, v_dim=128)
    assert round(step["bytes"] / 1e6) == 68  # read and written, 16 lanes, one layer
    chunk = costs.gdn_chunk_cost(tokens=2048, k_heads=16, v_heads=32, k_dim=128, v_dim=128)
    assert 8e9 < chunk["flops"] < 16e9  # the issue's "about 15 GFLOP" counts the product form
    ragged = costs.gdn_chunk_cost(tokens=2048 + 10, k_heads=16, v_heads=32, k_dim=128, v_dim=128)
    assert 0 < ragged["flops"] - chunk["flops"] < chunk["flops"] / 32  # ten tokens of a 33rd chunk
    from benchmarks import kernel_costs as attn

    names = [n for n in dir(attn) if not n.startswith("_")]
    assert names  # the full layer's kernels at head 256 are these functions at new arguments


# ---- ahead-of-time compiles for the v5e ------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compile_for(fn, one_chip, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HK, HV, DK, DV, LANES, LAYERS = 16, 32, 128, 128, 16, 3


def test_the_decode_step_compiles_in_place_at_the_published_shape(one_chip, no_cache):
    from dnet_tpu.ops.gated_delta import gdn_step

    fn = lambda S, q, k, v, g, b, act, layer: gdn_step(S, q, k, v, g, b, act, layer, impl="pallas")
    c = compile_for(
        fn, one_chip, ((LAYERS, LANES, HV, DK, DV), F32), ((LANES, HK, DK), BF),
        ((LANES, HK, DK), BF), ((LANES, HV, DV), BF), ((LANES, HV), F32), ((LANES, HV), F32),
        ((LANES,), I32), ((), I32), donate=(0,),
    )
    assert "tpu_custom_call" in c.as_text() and "gdn_step" in c.as_text()
    mem = c.memory_analysis()
    state = LAYERS * LANES * HV * DK * DV * 4
    assert mem.alias_size_in_bytes == state  # the whole store, in place: no second copy
    assert mem.temp_size_in_bytes < 0.05 * state


@pytest.mark.parametrize("tokens", [16, 2048])
def test_the_prefill_chunk_compiles_at_the_published_shape(one_chip, no_cache, tokens):
    from dnet_tpu.ops.gated_delta import gdn_chunk

    fn = lambda S, q, k, v, g, b, valid: gdn_chunk(S, q, k, v, g, b, valid, impl="pallas")
    c = compile_for(
        fn, one_chip, ((HV, DK, DV), F32), ((tokens, HK, DK), BF), ((tokens, HK, DK), BF),
        ((tokens, HV, DV), BF), ((tokens, HV), F32), ((tokens, HV), F32), ((tokens,), jnp.bool_),
    )
    text = c.as_text()
    assert "tpu_custom_call" in text and "gdn_chunk" in text
    assert c.memory_analysis().temp_size_in_bytes < 100e6


def test_the_attention_kernels_compile_at_head_size_256(one_chip, no_cache):
    """16 query / 2 KV heads of 256: a block is [128, 512]."""
    from dnet_tpu.ops.flash_attention import _flash_pallas
    from dnet_tpu.ops.paged_attention import _paged_pallas

    paged = lambda q, kp, vp, tb, pos, kn, vn, layer: _paged_pallas(
        q, kp, vp, tb, pos, kn, vn, None, layer, G=8, scale=1 / 16.0, bt=128, interpret=False)
    c = compile_for(
        paged, one_chip, ((16, 1, 16, 256), BF), ((1, 4160, 128, 512), BF),
        ((1, 4160, 128, 512), BF), ((16, 260), I32), ((16,), I32), ((16, 2, 256), BF),
        ((16, 2, 256), BF), ((1,), I32),
    )
    assert "paged_attend" in c.as_text()
    flash = lambda q, k, v, pos, sinks: _flash_pallas(
        q, k, v, pos, sinks, G=8, scale=1 / 16.0, bq=128, bk=128, interpret=False)
    c = compile_for(
        flash, one_chip, ((1, 2048, 16, 256), BF), ((1, 33280, 2, 256), BF),
        ((1, 33280, 2, 256), BF), ((1,), I32), ((16,), F32),
    )
    assert "flash_prefill" in c.as_text()


# ---- the cell, end to end on the CPU ---------------------------------------
def test_rehearsal_of_the_long_document_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 37), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in m)  # no CPU number under a device name
    for name in ("state_slots_used_peak_pct", "kv_full_blocks_used_peak_pct",
                 "gdn_state_bytes_in_window", "gdn_prefill_tokens_in_window",
                 "moe_assignments_held_in_window", "moe_assignments_routed_in_window",
                 "moe_expert_rows_in_window", "decode_lane_steps_in_window",
                 "decode_tokens_delivered_in_window", "prefill_ticks_mean",
                 "prefill_adopt_mean_ms", "decode_prepare_mean_ms",
                 "sched_batch_tokens_mean", "itl_p50_ms", "sched_tick_host_mean_ms"):
        assert m[f"rehearsal.{name}"] > 0, name
    for name in ("sched_queue_wait_mean_ms", "admit_wait_mean_ms",
                 "decode_deliver_wait_mean_ms", "decode_readback_wait_mean_ms",
                 "decode_slot_steps_in_window", "prefill_wall_mean_ms",
                 "moe_grouped_rows_in_window", "mixed_ticks_in_window",
                 "mixed_ticks_overlapped_in_window"):
        assert f"rehearsal.{name}" in m, name
    # BOTH books of one store, live in one cell
    assert 0 < m["rehearsal.state_slots_used_peak_pct"] <= 100.0
    assert 0 < m["rehearsal.kv_full_blocks_used_peak_pct"] <= 100.0
    # bytes booked = lane steps x one entry x 2 (3 layers x (4 x 16 x 16 float32 S
    # + a 3 x 128 tail in the activations' bfloat16))
    entry = 3 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    # (whole entries; a scrape can fall between the two counters' increments at
    # either edge of the window: at most one 4-lane dispatch apart at each)
    booked, rest = divmod(m["rehearsal.gdn_state_bytes_in_window"], entry * 2)
    assert rest == 0 and abs(booked - m["rehearsal.decode_lane_steps_in_window"]) <= 2 * 4
    # 16 of 32 experts held: about half of the chosen ones, routing over all 32
    share = m["rehearsal.moe_assignments_held_in_window"] / m["rehearsal.moe_assignments_routed_in_window"]
    assert 0.3 < share < 0.7
    assert not any(k.endswith((".rag", ".mix", ".gen", ".doc", ".lat")) for k in m)  # no suffix names a cell
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier and "-> ok" in earlier
    assert '"gdn_step"' in earlier and '"gdn_chunk"' in earlier
