"""MiniCPM-SALA (`minicpm_sala`) in the benchmark: the configuration against
the catalog's row, the mix and the per-layer entries, the kernels' operation
and byte counts, ahead-of-time v5e compiles of the five new kernels at the
cell's real shapes (no temporary as large as a pool leaf), and the cell's
rehearsal end to end on the CPU.  (The reference against the system at the
rehearsal size: tests/test_minicpm_sala_parity.py.)

The topology is described inside a fixture (on-chip-measurement guide,
section 2), as in `test_bench_qwen3_next.py`.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import kernel_costs_sala as costs
from benchmarks.harness import spec
from benchmarks.harness.weights import reference_module

CELL = "minicpmsala-deepdoc-sat"
NAME = "minicpm-sala-8l"
CONFIG = spec.BENCH_DIR / "configs" / f"{NAME}.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("sparse_decode_time_pct", "sparse_index_time_pct", "lightning_step_time_pct",
       "sparse_prefill_time_pct", "lightning_chunk_time_pct", "sala_pallas_time_pct",
       "sparse_blocks_chosen_in_window", "sparse_blocks_resident_in_window",
       "lightning_state_bytes_in_window", "lightning_prefill_tokens_in_window")
KERNELS = ("sparse_index", "paged_attend_sparse", "flash_prefill_sparse",
           "lightning_step", "lightning_chunk")


# ---- the configuration ----------------------------------------------------
def test_every_number_of_the_catalog_row_is_under_its_own_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "MiniCPM-SALA")
    full = spec.load_json(CONFIG)
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    differs = sorted(k for k, v in row["config"].items() if full.get(k, "absent") != v)
    assert differs == ["mixer_types", "num_hidden_layers"]  # depth alone: no width, no head, no vocabulary
    assert full["assumed"]["published"] == {
        k: row["config"][k] for k in ("num_hidden_layers", "mixer_types")}
    assert full["num_hidden_layers"] == 8
    assert full["mixer_types"] == (["minicpm4"] + ["lightning-attn"] * 3) * 2
    # the published ratio, 8 sparse of 32, kept; the cut opens with a sparse layer as the model does
    assert row["config"]["mixer_types"].count("minicpm4") * 4 == len(row["config"]["mixer_types"])
    assert row["config"]["mixer_types"][0] == "minicpm4"


def test_what_the_row_does_not_give_is_listed_as_assumed():
    full = spec.load_json(CONFIG)
    assumed = full["assumed"]
    assert set(assumed["keys"]) <= set(assumed) and len(assumed["keys"]) == 12
    assert all(len(assumed[k]) > 40 for k in assumed["keys"])
    assert full["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert "per POSITION" in assumed["selection_per_position"]
    assert "pipeline" in full["deployment"].lower() and "no width" in full["deployment"].lower()
    # the program reads the same sizes the file states
    from dnet_tpu.ops.sparse_attention import SparseConfig

    sp = SparseConfig.from_hf(full["sparse_config"])
    assert (sp.n_best, sp.window_blocks, sp.rows_per_block, sp.list_blocks) == (31, 32, 4, 128)
    ref = reference_module("minicpm_sala")
    assert ref.SPARSE_DEFAULTS == full["sparse_config"] == {k: getattr(sp, k) for k in ref.SPARSE_DEFAULTS}


def test_the_cut_fits_the_chip_as_the_issue_reckons():
    full = spec.load_json(CONFIG)
    D, V, F = full["hidden_size"], full["vocab_size"], full["intermediate_size"]
    H, KVH, Hd = full["num_attention_heads"], full["num_key_value_heads"], full["head_dim"]
    LW = full["lightning_nh"] * full["lightning_head_dim"]
    mlp = 3 * D * F
    sparse = 3 * D * H * Hd + 2 * D * KVH * Hd + mlp
    light = 5 * D * LW + mlp
    assert round(mlp / 1e6, 1) == 201.3 and round(sparse / 1e6, 1) == 253.8
    assert round(light / 1e6, 1) == 285.2 and round(2 * V * D / 1e6, 1) == 601.7
    params = 2 * sparse + 6 * light + 2 * V * D
    assert round(params * 2 / 1e9, 2) == 5.64
    env = full["serve"]["env"]
    lanes, max_seq, bt = (int(env[k]) for k in (
        "DNET_SCHED_SLOTS", "DNET_API_MAX_SEQ_LEN", "DNET_KV_BLOCK_TOKENS"))
    assert (lanes, max_seq, bt) == (16, 65536 + 1024, 128) and max_seq % bt == 0
    assert bt % full["sparse_config"]["block_size"] == 0  # a chosen block is half a page
    token = KVH * Hd * 2 * 2 + KVH * Hd * 2 / full["sparse_config"]["kernel_stride"]
    assert token == 1056
    pool = lanes * max_seq * 2 * token
    state = lanes * 6 * costs.state_entry_bytes(full["lightning_nh"], full["lightning_head_dim"])
    assert round(pool / 1e9, 2) == 2.25 and round(state / 1e9, 2) == 0.20
    assert 0.45 < (params * 2 + pool + state) / 16.9e9 < 0.55  # over the floor of a quarter
    chk = full["check"]
    assert (chk["prompt_tokens"], chk["decode_steps"]) == (12000, 48)
    assert chk["prompt_tokens"] > full["sparse_config"]["dense_len"]  # the check chooses blocks
    assert -(-chk["prompt_tokens"] // 2048) == 6  # six chunks at the default budget
    health = full["serve"]["expect_health"]
    assert set(health["used"]) == set(KERNELS) and health["impl"] == "pallas"
    assert set(health["zero"]) == {"interpret", "emulate", "dense"}
    assert not any(k.startswith("DNET_") and k not in (
        "DNET_SCHED_SLOTS", "DNET_API_BATCH_SLOTS", "DNET_API_MAX_CONCURRENT_REQUESTS",
        "DNET_API_MAX_SEQ_LEN", "DNET_KV_BLOCK_TOKENS") for k in env)  # no new knob


def test_the_mix_is_a_data_file_for_the_generator_as_it_is():
    from benchmarks.harness import traffic

    cell = spec.resolve_cell(CELL)
    assert (cell.traffic_name, cell.chips, cell.config_name) == ("deepdoc-sat-16", 1, NAME)
    mix = cell.traffic
    assert mix["clients"] == 16 and mix["block"] == 4 and "schedule_seed" in mix
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 16384, "max": 65536}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9} and mix["residual_life_start"]
    plans = traffic.plan(mix, 3000000019, cell.config["vocab_size"])
    n = mix["requests_per_client"]
    assert len(plans) == 16 and all(len(p) == n for p in plans) and n % 4 == 0
    lens = [len(r.prompt_ids) for p in plans for r in p]
    assert 16384 <= min(lens) and max(lens) <= 65536 and 34000 < sum(lens) / len(lens) < 37000
    assert min(lens) > cell.config["sparse_config"]["dense_len"]  # every request chooses blocks
    for p in plans:  # one length from each band in every block of four
        for b in range(0, n, 4):
            block = sorted(len(r.prompt_ids) for r in p[b:b + 4])
            assert block[0] < 23171 <= block[1] < 32768 <= block[2] < 46341 <= block[3]
    assert all(256 <= r.max_tokens <= 1024 for p in plans for r in p[1:])
    again = traffic.plan(mix, 7, cell.config["vocab_size"])
    assert [len(r.prompt_ids) for r in again[3]] == [len(r.prompt_ids) for r in plans[3]]  # one order
    warm = mix["warm_prompt_tokens"]
    widths = {max(16, 1 << (w - 1).bit_length()) for w in warm if w <= 2048}
    assert widths == {16, 32, 64, 128, 256, 512, 1024, 2048} and max(warm) == 65536
    tables = {min(1 << (-(-w // 128) - 1).bit_length(), 520) for w in warm}
    assert tables >= {1, 2, 4, 8, 16, 32, 64, 128, 256, 512}


def test_the_cell_joins_the_standing_entries_and_brings_ten_of_its_own():
    bench = spec.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    assert all(by[name]["workloads"] == [CELL] for name in NEW)
    assert all(by[n]["better"] == "lower" for n in NEW if n.endswith("_time_pct") or n.startswith("sala_pallas"))
    moves = {n: by[n]["moves"] for n in NEW}
    assert moves["sparse_prefill_time_pct"] == moves["lightning_chunk_time_pct"] == "ttft_p50_ms"
    assert moves["lightning_prefill_tokens_in_window"] == "ttft_p50_ms"
    assert all(v == "output_tokens_per_s" for k, v in moves.items() if k not in (
        "sparse_prefill_time_pct", "lightning_chunk_time_pct", "lightning_prefill_tokens_in_window"))
    joined = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in NEW]
    assert len(joined) == 33 and {"kv_full_blocks_used_peak_pct", "state_slots_used_peak_pct",
                                   "attn_full_time_pct", "itl_p50_ms"} <= set(joined)
    for name in joined:  # appended at the end, nothing else of the entry touched
        assert by[name]["workloads"][-1] == CELL and len(by[name]["workloads"]) >= 2
    import re

    pattern = spec.load_json(spec.layer_metric_file("attn_full_time_pct"))["pattern"]
    assert re.search(pattern, "paged_attend_sparse.3") and re.search(pattern, "flash_prefill_sparse")
    mine = {m["name"] for m in spec.resolve_cell(CELL).per_layer}
    assert set(NEW) <= mine and set(joined) <= mine
    assert len(bench["per_layer"]) == 76 and len(bench["workloads"]) == 6 and len(bench["configs"]) == 6
    assert spec.validate(bench) == []


# ---- the kernels' costs ---------------------------------------------------
def test_the_costs_are_the_issues_arithmetic():
    assert round(costs.state_entry_bytes(32, 128) / 1e6, 2) == 2.10
    step = costs.lightning_step_cost(lanes=16, heads=32, dim=128)
    assert round(step["bytes"] / 1e6) == 68  # read and written, 16 lanes, one layer
    chunk = costs.lightning_chunk_cost(tokens=2048, heads=32, dim=128)
    ragged = costs.lightning_chunk_cost(tokens=2048 + 10, heads=32, dim=128)
    assert 5e9 < chunk["flops"] < 8e9 and 0 < ragged["flops"] - chunk["flops"] < chunk["flops"] / 100
    # 16 lanes at 35 k of context: the read is 64 blocks x 64 tokens x 1 KB a lane,
    # where a dense read would be the whole 35 k
    read = costs.paged_attend_sparse_cost([35000] * 16, 32, 2, 128)
    assert round(read["bytes"] / 1e6) == 67 and costs.blocks_attended(35000) == 64
    assert costs.blocks_attended(8192) == 128 and costs.blocks_attended(8193) == 64
    dense = 16 * 35000 * 2 * 128 * 2 * 2
    assert 8 < dense / read["bytes"] < 9
    assert costs.spans_complete(31) == 0 and costs.spans_complete(32) == 1 and costs.spans_complete(48) == 2
    index = costs.sparse_index_cost([35000] * 16, 32, 2, 128, shared=False)
    assert round(index["bytes"] / 1e6) == 18  # 16 lanes x 2186 pooled keys x 512 B, and r
    pre = costs.flash_prefill_sparse_cost(32768, 2048, 32, 2, 128)
    assert round(pre["flops"] / 1e9) == 137  # 2048 queries x 4096 keys x 32 heads x 128 x 4
    from dnet_tpu.ops.sparse_attention import SparseConfig

    sp = SparseConfig()
    assert all(sp.blocks_attended(n) == costs.blocks_attended(n) for n in (1, 64, 65, 8192, 8193, 60000))


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
H, KVH, HD, LANES, S, PAGES, BT = 32, 2, 128, 16, 66560, 8320, 128
LEAF = 2 * PAGES * BT * KVH * HD * 2  # the pool's k (or v) leaf: 1.09 GB


def test_the_lightning_step_compiles_in_place_at_the_published_shape(one_chip, no_cache):
    from dnet_tpu.ops.lightning import lightning_step

    fn = lambda S_, q, k, v, act, layer: lightning_step(S_, q, k, v, act, layer, impl="pallas")
    c = compile_for(
        fn, one_chip, ((6, LANES, 32, 128, 128), F32), ((LANES, 32, 128), BF),
        ((LANES, 32, 128), BF), ((LANES, 32, 128), BF), ((LANES,), I32), ((), I32), donate=(0,),
    )
    assert "tpu_custom_call" in c.as_text() and "lightning_step" in c.as_text()
    mem = c.memory_analysis()
    state = 6 * LANES * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes == state  # the whole store, in place: no second copy
    assert mem.temp_size_in_bytes < 0.05 * state


@pytest.mark.parametrize("tokens", [16, 2048])
def test_the_lightning_chunk_compiles_at_the_published_shape(one_chip, no_cache, tokens):
    from dnet_tpu.ops.lightning import lightning_chunk

    fn = lambda S_, q, k, v, valid: lightning_chunk(S_, q, k, v, valid, impl="pallas")
    c = compile_for(
        fn, one_chip, ((32, 128, 128), F32), ((tokens, 32, 128), BF), ((tokens, 32, 128), BF),
        ((tokens, 32, 128), BF), ((tokens,), jnp.bool_),
    )
    assert "lightning_chunk" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 100e6


def test_a_sparse_decode_step_compiles_in_place_with_no_copy_of_a_leaf(one_chip, no_cache):
    """The row's write, the index's extension, the scores, the choice and
    the read of the chosen blocks, over the cell's pool: 8320 pages of 128
    tokens, 520 a lane, two layers."""
    from dnet_tpu.ops.sparse_attention import SparseConfig, sparse_decode

    def fn(k, v, kc, q, kn, vn, table, pos, act, layer):
        return sparse_decode({"k": k, "v": v, "kc": kc}, q, kn, vn, table, pos, act, layer,
                             SparseConfig(), impl="pallas")

    pool = (2, PAGES, BT, KVH * HD)
    c = compile_for(
        fn, one_chip, (pool, BF), (pool, BF), ((2, PAGES, BT // 16, KVH * HD), BF),
        ((LANES, 1, H, HD), BF), ((LANES, KVH, HD), BF), ((LANES, KVH, HD), BF),
        ((LANES, 520), I32), ((LANES,), I32), ((LANES,), I32), ((), I32), donate=(0, 1, 2),
    )
    text = c.as_text()
    assert "sparse_index" in text and "paged_attend_sparse" in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * LEAF  # k and v (and the index) updated in place
    assert mem.temp_size_in_bytes < LEAF / 16  # the lanes' pooled keys gathered: 35 MB


def test_a_sparse_prefill_chunk_compiles_at_the_cells_longest_row(one_chip, no_cache):
    from dnet_tpu.ops.sparse_attention import SparseConfig, sparse_prefill

    fn = lambda q, k, v, pos: sparse_prefill(q, k, v, pos, SparseConfig(), impl="pallas")
    c = compile_for(
        fn, one_chip, ((2048, H, HD), BF), ((S, KVH, HD), BF), ((S, KVH, HD), BF), ((), I32),
    )
    text = c.as_text()
    assert "sparse_index" in text and "flash_prefill_sparse" in text
    assert c.memory_analysis().temp_size_in_bytes < LEAF / 4  # r [2, 2048, 4224] float32 and its kin


# ---- the cell, end to end on the CPU ---------------------------------------
def test_rehearsal_of_the_deep_document_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 43), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in m)  # no CPU number under a device name
    for name in ("state_slots_used_peak_pct", "kv_full_blocks_used_peak_pct",
                 "sparse_blocks_chosen_in_window", "sparse_blocks_resident_in_window",
                 "lightning_state_bytes_in_window", "lightning_prefill_tokens_in_window",
                 "decode_lane_steps_in_window", "decode_tokens_delivered_in_window",
                 "prefill_ticks_mean", "prefill_adopt_mean_ms", "decode_prepare_mean_ms",
                 "sched_batch_tokens_mean", "itl_p50_ms", "sched_tick_host_mean_ms"):
        assert m[f"rehearsal.{name}"] > 0, name
    # BOTH books of one store, live in one cell
    assert 0 < m["rehearsal.state_slots_used_peak_pct"] <= 100.0
    assert 0 < m["rehearsal.kv_full_blocks_used_peak_pct"] <= 100.0
    # a step past dense_len (64 tokens here) reads 6 of the blocks its lane holds
    assert m["rehearsal.sparse_blocks_chosen_in_window"] < m["rehearsal.sparse_blocks_resident_in_window"]
    # bytes booked = lane steps x one entry x 2 (6 layers x 4 x 16 x 16 float32); a scrape
    # can fall between the two counters' increments: at most one 4-lane dispatch apart an edge
    entry = 6 * 4 * 16 * 16 * 4
    booked, rest = divmod(m["rehearsal.lightning_state_bytes_in_window"], entry * 2)
    assert rest == 0 and abs(booked - m["rehearsal.decode_lane_steps_in_window"]) <= 2 * 4
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier and "-> ok" in earlier
    assert all(f'"{k}"' in earlier for k in KERNELS)
