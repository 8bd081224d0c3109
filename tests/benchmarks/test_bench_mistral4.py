"""Mistral-Small-4-119B (`mistral4`) in the benchmark: the configuration
against the catalog's row, the plain reference against the system at the
rehearsal size, ahead-of-time v5e compiles of the absorbed decode kernel and
of the prefill attention at the cell's real shapes, the kernels' operation
and byte counts, and the cell's rehearsal end to end on the CPU.

Tolerance 2e-3 nat on log-probabilities: both sides run float32 over the
same float32 weights (measured 5e-7 here).

The topology is described inside a fixture (on-chip-measurement guide,
section 2), as in `test_bench_qwen3_next.py`: where the files land on
different workers and only one process may load the TPU library, this
file's compile tests skip.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import kernel_costs_mla as costs
from benchmarks.harness import spec
from benchmarks.harness.weights import reference_module, write_checkpoint

TOL = 2e-3
CELL = "mistral4-longctx-sat"
NAME = "mistral-small-4-119b-6l-ep8"
CONFIG = spec.BENCH_DIR / "configs" / f"{NAME}.json"
BENCH_KEYS = ("assumed", "deployment", "serve", "check", "rehearse")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "qk_head_dim", "v_head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "n_shared_experts", "vocab_size", "rope_parameters")


def tiny_config():
    full = spec.load_json(CONFIG)
    cfg = {k: v for k, v in full.items() if k not in BENCH_KEYS}
    cfg.update(full["rehearse"]["config"])
    return cfg


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    cfg = tiny_config()
    d = tmp_path_factory.mktemp("bench_mistral4")
    write_checkpoint(d, cfg, seed=2**31 + 41, dtype="float32")
    return cfg, d


# ---- the configuration ----------------------------------------------------
def test_every_number_of_the_catalog_row_is_under_its_own_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Mistral-Small-4-119B-2603")
    full = spec.load_json(CONFIG)
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert not any(k.endswith(("_size", "_dim", "_rank")) for k in entry["reduced"])
    differs = sorted(k for k, v in row["config"].items() if full.get(k, "absent") != v)
    assert differs == ["n_routed_experts", "num_hidden_layers"]
    assert full["assumed"]["published"] == {
        k: row["config"][k] for k in ("num_hidden_layers", "n_routed_experts")}
    assert all(full[k] == row["config"][k] for k in WIDTHS)  # no width is cut
    assert full["num_experts_routed"] == row["config"]["n_routed_experts"]
    assert all(k in full["assumed"] for k in full["assumed"]["keys"])
    # the floors: four layers (a period is one), at least 8 experts, the whole vocabulary
    assert full["num_hidden_layers"] >= 4 and full["n_routed_experts"] >= 8


def test_the_cut_fits_the_chip_as_the_deployment_says():
    full = spec.load_json(CONFIG)
    D, V, F = full["hidden_size"], full["vocab_size"], full["moe_intermediate_size"]
    H, r, rope = full["num_attention_heads"], full["kv_lora_rank"], full["qk_rope_head_dim"]
    nope, vd, q_rank = full["qk_nope_head_dim"], full["v_head_dim"], full["q_lora_rank"]
    E, L = full["n_routed_experts"], full["num_hidden_layers"]
    attn = D * q_rank + q_rank * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) + H * vd * D
    assert round(attn / 1e6, 2) == 28.05
    beside = attn + full["num_experts_routed"] * D + 3 * D * F
    assert round(beside / 1e6, 2) == 53.74 and round(beside * 2 / 1e6, 1) == 107.5
    expert = 3 * D * F
    assert round(expert * 2 / 1e6, 1) == 50.3 and round(E * expert * 2 / 1e9, 3) == 0.805
    assert round(128 * expert * 2 / 1e9, 2) == 6.44
    weights = 2 * (L * (E * expert + beside) + 2 * V * D)
    assert round(weights / 1e9, 2) == 7.62
    lanes = int(full["serve"]["env"]["DNET_SCHED_SLOTS"])
    max_seq = int(full["serve"]["env"]["DNET_API_MAX_SEQ_LEN"])
    bt = int(full["serve"]["env"]["DNET_KV_BLOCK_TOKENS"])
    assert (lanes, max_seq, bt) == (32, 33792, 128) and max_seq == 32768 + 1024
    entry = costs.latent_entry_bytes(r, rope)
    assert entry == 640 and 32 * (128 + 128) * 2 == 16384 and 16384 / entry == 25.6
    assert round(lanes * max_seq * L * entry / 1e9, 2) == 4.15  # by the algorithm
    kept = -(-(r + rope) // 128) * 128 * 2
    assert kept == 768 and round(lanes * max_seq * L * kept / 1e9, 2) == 4.98  # in HBM
    blocks = int(full["serve"]["env"].get("DNET_KV_POOL_BLOCKS", lanes * max_seq // bt))
    assert 0.25 < (weights + blocks * bt * L * kept) / 16.9e9 < 0.85  # over the floor of a quarter
    chk = full["check"]
    assert chk["prompt_tokens"] + chk["decode_steps"] <= max_seq
    assert chk["prompt_tokens"] > 4 * 2048 and chk["prompt_tokens"] > 8192  # five chunks, past a(t)'s edge


def test_the_mix_is_a_data_file_for_the_generator_as_it_is():
    from benchmarks.harness import traffic

    cell = spec.resolve_cell(CELL)
    assert (cell.traffic_name, cell.chips, cell.config_name) == ("longctx-sat-32", 1, NAME)
    mix = cell.traffic
    assert mix["schedule_seed"] == 41 and mix["requests_per_client"] == 12
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    plans = traffic.plan(mix, 3000000019, cell.config["vocab_size"])
    assert len(plans) == 32 and all(len(p) == 12 for p in plans)
    lens = [len(r.prompt_ids) for p in plans for r in p]
    assert 4096 <= min(lens) and max(lens) <= 32768 and 13000 < sum(lens) / len(lens) < 14500
    for p in plans:  # one length from each band in every block of four
        for b in range(0, 12, 4):
            block = sorted(len(r.prompt_ids) for r in p[b:b + 4])
            assert block[0] < 6889 <= block[1] < 11586 <= block[2] < 19484 <= block[3]
    assert all(256 <= r.max_tokens <= 1024 for p in plans for r in p[1:])
    again = traffic.plan(mix, 7, cell.config["vocab_size"])
    assert [len(r.prompt_ids) for r in again[3]] == [len(r.prompt_ids) for r in plans[3]]  # one order
    # every program width is warmed: the prefill buckets 16 .. 2048, every
    # power-of-two table and commit width up to the longest prompt's
    warm = mix["warm_prompt_tokens"]
    widths = {max(16, 1 << (n - 1).bit_length()) for n in warm if n <= 2048}
    assert widths == {16, 32, 64, 128, 256, 512, 1024, 2048}
    tables = {1 << (-(-n // 128) - 1).bit_length() for n in warm}
    assert tables == {1, 2, 4, 8, 16, 32, 64, 128, 256} and max(warm) == 32768


def test_the_new_per_layer_metrics_are_one_unbroken_run_read_in_this_cell_alone():
    """By name, wherever they stand in `per_layer`.  PR 41 could bring nine
    entries of the issue's two dozen: the list stood at the contract's 128.
    PR 43 made one entry a reading (66), so the cell reads the scheduler's
    and the engine's entries too, and the next configuration finds room."""
    bench = spec.load_benchmark()
    assert len(bench["per_layer"]) <= 128  # the contract's ceiling
    assert 128 - len(bench["per_layer"]) >= 48  # room for the next configuration's readings
    by = {m["name"]: m for m in bench["per_layer"]}
    alone = ("mla_decode_time_pct", "mla_prefill_time_pct", "mla_latent_bytes_in_window",
             "mla_prefill_tokens_in_window", "mla_expanded_tokens_in_window")
    assert all(by[name]["workloads"] == [CELL] for name in alone)
    joined = ("itl_p50_ms", "kv_full_blocks_used_peak_pct", "moe_assignments_held_in_window",
              "moe_assignments_routed_in_window")
    mine = {m["name"] for m in spec.resolve_cell(CELL).per_layer}
    for name in alone + joined:  # the nine of PR 41
        assert CELL in by[name]["workloads"] and name in mine
        reader = spec.load_json(spec.layer_metric_file(name))
        assert reader["reader"] in ("prom_delta", "trace_share", "client")
    assert by["mla_decode_time_pct"]["moves"] == "output_tokens_per_s"
    assert by["mla_prefill_time_pct"]["moves"] == "ttft_p50_ms"
    # a share of busy spent in a kernel the cell wants faster falls as the kernel gets faster
    assert by["mla_decode_time_pct"]["better"] == by["mla_prefill_time_pct"]["better"] == "lower"
    import re

    pattern = spec.load_json(spec.layer_metric_file("mla_decode_time_pct"))["pattern"]
    assert re.search(pattern, "%paged_attend_latent.3 = ") and not re.search(pattern, "%paged_attend.3 = ")
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
def test_the_system_matches_the_expanded_reference_at_the_rehearsal_size(
    checkpoint, monkeypatch, kernels
):
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
        assert eng.kv_pool is not None and eng.kv_store.latent_rank == cfg["kv_lora_rank"]
        dec = DecodingParams(temperature=0.0, logprobs=True, top_logprobs=20)
        rng = np.random.default_rng(4)
        ids = [int(i) for i in rng.integers(1, cfg["vocab_size"], size=70)]
        eng.reserve_slot("a")
        for i in range(0, 70, 32):  # three chunks, over a(t)'s edges at 32 and 64
            logits = eng.prefill_chunk("a", ids[i:i + 32])
        res = eng.adopt_prefilled("a", logits, dec)
        got = [eng.token_result("a", res, step=0, decoding=dec)]
        for step in range(1, 4):
            out, errs = eng.decode_batch({"a": (got[-1].token_id, dec)})
            assert not errs
            got.append(eng.token_result("a", out["a"], step=step, decoding=dec))
        assert worst_error(cfg, model_dir, ids, got) < TOL
        eng.close()
    finally:
        reset_settings_cache()


def test_the_reference_shares_nothing_with_the_program():
    src = (spec.BENCH_DIR / "reference" / "mistral4.py").read_text()
    assert "import dnet_tpu" not in src and "from dnet_tpu" not in src
    assert 'default_matmul_precision("highest")' in src


def test_the_costs_are_the_issues_arithmetic():
    assert costs.latent_entry_bytes(256, 64) == 640
    step = costs.latent_decode_cost(live_tokens=1000, lanes=1, heads=32, rank=256, rope_dim=64)
    assert step["flops"] == 2 * 32 * (320 + 256) * 1000  # a live token a lane
    assert step["bytes"] == 640 * 1000 + (32 * (320 + 256) + 320) * 2
    exp = costs.expansion_cost(33792, 32, 256, 64, 64, 128)
    assert exp["flops"] == 2 * 33792 * 256 * 6144
    assert round(33792 * 32 * (128 + 128) * 2 / 1e9, 2) == 0.55  # the transient keys and values at 33k
    att = costs.prefill_attention_cost(pos=8192, tokens=2048, heads=32, qk_dim=128, v_dim=128)
    absorbed = costs.absorbed_prefill_cost(pos=8192, tokens=2048, heads=32, rank=256, rope_dim=64)
    assert absorbed["flops"] / att["flops"] == 2.25


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
LANES, HEADS, LAYERS, BLOCKS, BT, MAX_SEQ = 32, 32, 6, 8448, 128, 33792


@pytest.mark.parametrize("table", [256, 264])
def test_the_decode_kernel_reads_the_pool_in_place_at_the_cells_shape(one_chip, no_cache, table):
    """The whole 4.98 GB stack goes in as it is kept: no temporary as large
    as a pool (kept at 320 lanes the compiler relayouts all of it)."""
    from dnet_tpu.ops.paged_attention import _latent_pallas

    fn = lambda q, pool, tb, pos, cn, layer: _latent_pallas(
        q, pool, tb, pos, cn, layer, rank=256, bt=BT, interpret=False)
    c = compile_for(
        fn, one_chip, ((LANES, HEADS, 384), BF), ((LAYERS, BLOCKS, BT, 384), BF),
        ((LANES, table), I32), ((LANES,), I32), ((LANES, 1, 384), BF), ((1,), I32),
    )
    text = c.as_text()
    assert "tpu_custom_call" in text and "paged_attend_latent" in text
    pool = LAYERS * BLOCKS * BT * 384 * 2
    assert c.memory_analysis().temp_size_in_bytes < 0.01 * pool


def test_the_prefill_attention_compiles_over_expanded_keys_at_the_cells_shape(one_chip, no_cache):
    """`flash_prefill` at 32 heads of 128 / 128 over 33792 keys, and the
    expansion that feeds it with the heads already merged: no copy of the
    expanded keys or values (0.28 GB a leaf)."""
    from dnet_tpu.models import ModelConfig, get_ring_model_cls

    full = spec.load_json(CONFIG)
    mc = ModelConfig.from_hf({k: v for k, v in full.items() if k not in BENCH_KEYS})
    model = get_ring_model_cls("mistral4")(mc, range(mc.num_hidden_layers))
    import dnet_tpu.ops.flash_attention as fa

    def chunk(q, c_all, w_kvb, pos):
        k, v = model._expand(c_all, w_kvb, pos[0], 2048)
        return fa._flash_pallas(
            q, k, v, pos, jnp.full((HEADS,), -1e30, F32), G=1, scale=model.softmax_scale,
            bq=128, bk=128, interpret=False, vma=(), window=0)

    c = compile_for(
        chunk, one_chip, ((1, 2048, HEADS, 128), BF), ((1, MAX_SEQ, 1, 384), BF),
        ((256, HEADS, 192), BF), ((1,), I32),
    )
    text = c.as_text()
    assert "flash_prefill" in text and "while" in text
    leaf = MAX_SEQ * HEADS * 128 * 2
    # the two expanded leaves and working room: not a third or fourth leaf
    assert c.memory_analysis().temp_size_in_bytes < 2.5 * leaf


# ---- the cell, end to end on the CPU ---------------------------------------
def test_rehearsal_of_the_long_context_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 41), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in m)  # no CPU number under a device name
    for name in ("mla_latent_bytes_in_window", "mla_prefill_tokens_in_window",
                 "mla_expanded_tokens_in_window", "kv_full_blocks_used_peak_pct",
                 "moe_assignments_held_in_window", "moe_assignments_routed_in_window",
                 "itl_p50_ms"):
        assert m[f"rehearsal.{name}"] > 0, name
    # the per-layer list is full at the contract's 128: the cell's other
    # layers are read by the metrics every cell reports
    for name in ("gen_lateness_p99_ms", "window_drift_pct", "compiles_in_window"):
        assert f"rehearsal.{name}" in m, name
    assert 0 < m["rehearsal.kv_full_blocks_used_peak_pct"] <= 100.0
    # bytes booked are whole entries of 2 layers x (16 + 8) x 2 bytes (bfloat16)
    assert m["rehearsal.mla_latent_bytes_in_window"] % (2 * 24 * 2) == 0
    # a prompt's latents are expanded once a chunk a layer: at least once each
    assert m["rehearsal.mla_expanded_tokens_in_window"] >= 2 * m["rehearsal.mla_prefill_tokens_in_window"]
    # 4 of 8 experts held: about half of the chosen ones, routing over all 8
    share = m["rehearsal.moe_assignments_held_in_window"] / m["rehearsal.moe_assignments_routed_in_window"]
    assert 0.3 < share < 0.7
    assert not any(k.endswith((".rag", ".mix", ".gen", ".doc", ".lat")) for k in m)  # no suffix names a cell
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier and "-> ok" in earlier
    assert '"paged_attend_latent"' in earlier and '"flash_prefill"' in earlier
