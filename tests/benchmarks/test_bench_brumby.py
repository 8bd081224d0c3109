"""Brumby-14B (`brumby`) in the benchmark: the configuration against the
catalog's row, the plain reference against the system at the rehearsal
size, ahead-of-time v5e compiles of both retention kernels at the published
head shape, the kernels' operation and byte counts, and the cell's
rehearsal end to end on the CPU.

Tolerance 2e-3 nat on log-probabilities: both sides run float32 over the
same float32 weights (measured 2e-5 here).

The topology is described inside a fixture (on-chip-measurement guide,
section 2), as in `test_bench_v5e_compile.py` and
`test_bench_cohere2_compile.py`, which may not be edited: where the files
land on different workers and only one process may load the TPU library,
this file's compile tests skip.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import kernel_costs_retention as costs
from benchmarks.harness import spec
from benchmarks.harness.weights import reference_module, write_checkpoint

TOL = 2e-3
CELL = "brumby-longgen-sat"
CONFIG = spec.BENCH_DIR / "configs" / "brumby-14b-8l.json"
BENCH_KEYS = ("assumed", "deployment", "serve", "check", "rehearse")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_config():
    full = spec.load_json(CONFIG)
    cfg = {k: v for k, v in full.items() if k not in BENCH_KEYS}
    cfg.update(full["rehearse"]["config"])
    return cfg


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    cfg = tiny_config()
    d = tmp_path_factory.mktemp("bench_brumby")
    write_checkpoint(d, cfg, seed=2**31 + 32, dtype="float32")
    return cfg, d


# ---- the configuration ----------------------------------------------------
def test_every_number_of_the_catalog_row_is_under_its_own_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Brumby-14B-Base")
    full = spec.load_json(CONFIG)
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == "brumby-14b-8l")
    assert entry["source"] == row["source_url"] and entry["reduced"] == ["num_hidden_layers"]
    differs = [k for k, v in row["config"].items() if full.get(k, "absent") != v]
    assert differs == ["num_hidden_layers"]
    assert full["assumed"]["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]


def test_the_cut_fits_the_chip_as_the_deployment_says():
    full = spec.load_json(CONFIG)
    D, F, V = full["hidden_size"], full["intermediate_size"], full["vocab_size"]
    H, KVH, Hd, L = (full[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim",
                                       "num_hidden_layers"))
    layer = 2 * D * H * Hd + 2 * D * KVH * Hd + D * KVH + 3 * D * F
    weights = 2 * (L * layer + 2 * V * D)
    assert round(weights / 1e9, 2) == 8.40
    lanes = int(full["serve"]["env"]["DNET_SCHED_SLOTS"])
    state = lanes * L * KVH * (Hd // 2 + 1) * Hd * (Hd + 1) * 4  # as the kernels lay it out
    assert round(state / 1e9, 2) == 4.40 and round(lanes * L * costs.state_entry_bytes(KVH, Hd) / 1e9, 2) == 4.36
    assert 0.74 < (weights + state) / 16.9e9 < 0.77  # over the floor of a quarter, by depth alone
    chk = full["check"]
    assert chk["prompt_tokens"] + chk["decode_steps"] <= int(full["serve"]["env"]["DNET_API_MAX_SEQ_LEN"])
    assert chk["prompt_tokens"] > 2 * 2048  # three chunks at the default budget


def test_the_mix_is_a_data_file_for_the_generator_as_it_is():
    from benchmarks.harness import traffic

    cell = spec.resolve_cell(CELL)
    assert (cell.traffic_name, cell.chips) == ("longgen-sat-16", 1)
    mix = cell.traffic
    plans = traffic.plan(mix, 3000000019, cell.config["vocab_size"])
    assert len(plans) == 16 and all(len(p) == 48 for p in plans)
    lens = [len(r.prompt_ids) for p in plans for r in p]
    assert 512 <= min(lens) and max(lens) <= 4096
    for p in plans:  # one length from each band in every block of four
        for b in range(0, 48, 4):
            block = sorted(len(r.prompt_ids) for r in p[b:b + 4])
            assert block[0] < 870 and block[3] > 2400
    again = traffic.plan(mix, 7, cell.config["vocab_size"])
    assert [len(r.prompt_ids) for r in again[3]] == [len(r.prompt_ids) for r in plans[3]]  # one order
    # every program width is warmed: the prefill buckets 16 .. 2048
    widths = {max(16, 1 << (n - 1).bit_length()) for n in mix["warm_prompt_tokens"] if n <= 2048}
    assert widths == {16, 32, 64, 128, 256, 512, 1024, 2048} and max(mix["warm_prompt_tokens"]) > 2048


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
def test_the_system_matches_the_quadratic_reference_at_the_rehearsal_size(
        checkpoint, monkeypatch, kernels):
    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.types import DecodingParams

    cfg, model_dir = checkpoint
    if kernels == "interpret":
        monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
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


def test_the_reference_is_the_quadratic_form_and_the_recurrence_agrees(checkpoint):
    """The reference keeps no state; the precision control's recurrence with
    its rounding taken out is the same function."""
    from benchmarks import precision_control_brumby as pc

    ref = reference_module("brumby")
    key = jax.random.split(jax.random.key(3), 4)
    T, G, Hd = 50, 3, 8
    q = jax.random.normal(key[0], (T, G, Hd))
    k, v = jax.random.normal(key[1], (2, T, Hd))
    lg = -jax.random.uniform(key[2], (T,))
    want = ref.power_retention(q, k, v, lg)
    bf16 = np.asarray(pc.power_retention_bf16_state(q, k, v, lg))
    err = np.max(np.abs(bf16 - np.asarray(want)))
    assert 1e-4 < err < 0.2  # rounded to bfloat16 after every token: seen, and not wild
    assert np.asarray(want).shape == (T, G, Hd)
    with pc.bf16_state_reference(ref):
        assert ref.power_retention is pc.power_retention_bf16_state
    assert ref.power_retention is not pc.power_retention_bf16_state
    edge, layer = ref.tensor_table(tiny_config())
    assert "self_attn.g_proj.weight" in layer(0) and "lm_head.weight" in edge


# ---- the kernels' costs ---------------------------------------------------
def test_the_costs_are_the_issues_arithmetic():
    assert costs.state_rows(128) == 8256
    entry = costs.state_entry_bytes(8, 128)
    assert round(entry / 1e6, 1) == 34.1  # a layer a sequence
    step = costs.retention_step_cost(lanes=16, kv_heads=8, q_heads=40, head_dim=128)
    assert round(step["bytes"] / 1e9, 2) == 1.09  # read and written, 16 lanes, one layer
    chunk = costs.retention_chunk_cost(tokens=2048, kv_heads=8, q_heads=40, head_dim=128)
    per_token = chunk["flops"] / 2048
    assert 100e6 < per_token < 106e6  # 48 heads' [D] x [D, 128] products and the pairs
    assert costs.chunk_pairs(300) == 2 * 128 * 129 // 2 + 44 * 45 // 2
    assert costs.retention_chunk_cost(128, 8, 40, 128)["bytes"] > 2 * entry


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
H, KVH, HD, LANES, LAYERS, ROWS = 40, 8, 128, 16, 8, 65


def test_the_decode_step_compiles_in_place_at_the_published_shape(one_chip, no_cache):
    from dnet_tpu.ops.retention import _step_pallas

    fn = lambda S, z, q, k, v, lg, act, layer: _step_pallas(S, z, q, k, v, lg, act, layer, False)
    c = compile_for(
        fn, one_chip,
        ((LAYERS, LANES, KVH, ROWS, HD, HD), F32), ((LAYERS, LANES, KVH, ROWS, HD), F32),
        ((LANES, H, HD), BF), ((LANES, KVH, HD), BF), ((LANES, KVH, HD), BF),
        ((LANES, KVH), F32), ((LANES,), I32), ((1,), I32), donate=(0, 1),
    )
    assert "tpu_custom_call" in c.as_text() and "retention_step" in c.as_text()
    mem = c.memory_analysis()
    state = LAYERS * LANES * KVH * ROWS * HD * (HD + 1) * 4
    assert mem.alias_size_in_bytes == state  # the whole store, in place: no second copy
    assert mem.temp_size_in_bytes < 0.05 * state


@pytest.mark.parametrize("tokens", [16, 2048])
def test_the_prefill_chunk_compiles_at_the_published_shape(one_chip, no_cache, tokens):
    from dnet_tpu.ops.retention import _chunk_pallas

    fn = lambda S, z, q, k, v, lg, valid: _chunk_pallas(S, z, q, k, v, lg, valid, False)
    c = compile_for(
        fn, one_chip,
        ((KVH, ROWS, HD, HD), F32), ((KVH, ROWS, HD), F32), ((tokens, H, HD), BF),
        ((tokens, KVH, HD), BF), ((tokens, KVH, HD), BF), ((tokens, KVH), F32),
        ((tokens,), jnp.bool_),
    )
    text = c.as_text()
    assert "tpu_custom_call" in text and "retention_chunk" in text
    # phi is never materialised for the chunk: [2048, 40, 8320] would be 2.7 GB
    assert c.memory_analysis().temp_size_in_bytes < 200e6


# ---- the cell, end to end on the CPU ---------------------------------------
def test_rehearsal_of_the_long_generation_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 32), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in m)  # no CPU number under a device name
    for name in ("state_slots_used_peak_pct", "retention_state_bytes_in_window",
                 "retention_prefill_tokens_in_window", "decode_lane_steps_in_window",
                 "decode_tokens_delivered_in_window", "prefill_ticks_mean",
                 "prefill_adopt_mean_ms", "decode_prepare_mean_ms",
                 "sched_batch_tokens_mean", "itl_p50_ms", "sched_tick_host_mean_ms"):
        assert m[f"rehearsal.{name}"] > 0, name
    for name in ("sched_queue_wait_mean_ms", "admit_wait_mean_ms",
                 "decode_deliver_wait_mean_ms", "decode_readback_wait_mean_ms",
                 "decode_slot_steps_in_window", "prefill_wall_mean_ms"):
        assert f"rehearsal.{name}" in m, name
    assert m["rehearsal.state_slots_used_peak_pct"] <= 100.0
    # bytes booked = lane steps x one entry x 2 (2 layers x 2 KV heads x 9 x 16 x 17 floats)
    entry = 2 * 2 * 9 * 16 * 17 * 4
    # the two counters move one after the other on the compute thread and a
    # scrape from the event loop can fall between them, at either edge of the
    # window: whole entries, and at most one 4-lane dispatch apart at each edge
    booked, rest = divmod(m["rehearsal.retention_state_bytes_in_window"], entry * 2)
    assert rest == 0 and abs(booked - m["rehearsal.decode_lane_steps_in_window"]) <= 2 * 4
    assert not any(k.endswith((".rag", ".mix", ".gen", ".doc", ".lat")) for k in m)  # no suffix names a cell
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier and "-> ok" in earlier
    assert '"retention_step"' in earlier and '"retention_chunk"' in earlier
