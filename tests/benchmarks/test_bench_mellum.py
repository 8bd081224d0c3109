"""Mellum2-12B-A2.5B (`mellum`) in the benchmark: the configuration against
the catalog's row and its own arithmetic, the mix's plan, the per-layer
entries (the cell JOINS the standing ones and brings three of its own), and
the cell's rehearsal end to end on the CPU.  (The reference against the
system at the rehearsal size: tests/test_mellum_parity.py; ahead-of-time v5e
compiles of the cell's programs: tests/test_mellum_v5e_compile.py.)
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import spec

CELL = "mellum2-repoctx-sat"
NAME = "mellum2-12b-a2.5b-8l"
CONFIG = spec.BENCH_DIR / "configs" / f"{NAME}.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types"]
NEW = ("flash_tiles_window_folded_in_window", "flash_tiles_full_folded_in_window",
       "moe_experts_visited_in_window")
JOINED_BY_NAME = (
    "attn_full_time_pct", "attn_window_time_pct", "pallas_time_pct.attn",
    "kv_full_blocks_used_peak_pct", "kv_window_blocks_used_peak_pct",
    "kv_window_blocks_released_in_window", "moe_grouped_time_pct",
    "moe_assignments_held_in_window", "moe_assignments_routed_in_window",
    "moe_grouped_rows_in_window", "moe_expert_rows_in_window",
    "mixed_ticks_in_window", "mixed_ticks_overlapped_in_window",
)
ENV = ("DNET_SCHED_SLOTS", "DNET_API_BATCH_SLOTS", "DNET_API_MAX_CONCURRENT_REQUESTS",
       "DNET_API_MAX_SEQ_LEN", "DNET_KV_BLOCK_TOKENS",
       # the rag and mix cells' standing setting: the mix's own warm-up covers every served shape
       "DNET_API_WARM_ON_LOAD")


@pytest.fixture(scope="module")
def full():
    return spec.load_json(CONFIG)


@pytest.fixture(scope="module")
def row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    return next(r for r in map(json.loads, open(CATALOG))
                if r["name"] == "Mellum2-12B-A2.5B-Instruct")


# ---- the configuration ----------------------------------------------------
def test_the_entry_names_the_rows_source_and_the_three_cuts(row):
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == REDUCED and entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


def test_every_key_of_the_catalog_row_is_at_its_published_value_but_the_three(row, full):
    differs = sorted(k for k, v in row["config"].items() if full.get(k, "absent") != v)
    assert differs == sorted(REDUCED)  # depth alone: no width, no head, no expert, no vocabulary


@pytest.mark.parametrize("key", sorted(
    ["hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "vocab_size",
     "num_experts", "num_experts_per_tok", "moe_intermediate_size", "intermediate_size",
     "sliding_window", "max_position_embeddings", "rms_norm_eps", "norm_topk_prob",
     "rope_parameters", "tie_word_embeddings", "max_window_layers", "use_sliding_window"]))
def test_a_width_a_table_or_a_rule_is_as_published(row, full, key):
    assert full[key] == row["config"][key]


def test_the_cut_is_two_whole_periods(row, full):
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert row["config"]["layer_types"] == period * 7
    assert full["num_hidden_layers"] == 8 and full["layer_types"] == period * 2
    assert full["mlp_layer_types"] == ["sparse"] * 8
    assert full["assumed"]["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    # floors kept: a whole period, more than four layers, at least 8 experts, all of them
    assert full["num_hidden_layers"] > 4 and full["num_experts"] == 64 >= 8
    assert "num_experts_routed" not in full and "expert_offset" not in full  # no share


def test_what_the_row_does_not_give_is_listed_as_assumed(full):
    assumed = full["assumed"]
    assert assumed["keys"] == [
        "qk_norm", "router_order", "window_counts_own_position", "layer_types_govern",
        "yarn_truncate", "intermediate_size", "tensor_names", "mtp_head"]
    assert set(assumed["keys"]) <= set(assumed)
    assert all(len(assumed[k]) > 40 for k in assumed["keys"])
    assert full["qk_norm"] is True and "no shape and no cost" in assumed["qk_norm"]
    assert "DEPARTURE" in assumed["mtp_head"] and "one token a step" in assumed["mtp_head"]
    assert "pipeline" in full["deployment"].lower() and "no width cut" in full["deployment"].lower()
    assert "overstates" in full["deployment"]


def test_the_bytes_are_the_issues_reckoned_again(full):
    D, V, E, F = (full[k] for k in ("hidden_size", "vocab_size", "num_experts",
                                     "moe_intermediate_size"))
    H, KVH, Hd = full["num_attention_heads"], full["num_key_value_heads"], full["head_dim"]
    q, kv, o = D * H * Hd, 2 * D * KVH * Hd, H * Hd * D
    assert [round(x / 1e6, 2) for x in (q, kv, o)] == [9.44, 2.36, 9.44]
    expert, router = 3 * D * F, D * E
    assert round(expert / 1e6, 2) == 6.19 and round(E * expert / 1e6, 1) == 396.4
    layer = q + kv + o + E * expert + router
    assert round(layer / 1e6, 1) == 417.7 and round(layer * 2 / 1e6) == 835  # 417.74 M, 835.5 MB
    edge = 2 * V * D
    assert round(edge / 1e6, 1) == 453.0 and round(edge * 2 / 1e9, 3) == 0.906
    total = (8 * layer + edge) * 2
    assert round(total / 1e9, 2) == 7.59 and 0.44 < total / 16.9e9 < 0.46
    for figure in ("417.74 M", "452.98 M", "7.59 GB", "21.23 M", "396.36 M"):
        assert figure in full["deployment"], figure


def test_the_cache_is_the_issues_reckoned_again(full):
    from dnet_tpu.kv import window_blocks

    env = full["serve"]["env"]
    lanes, max_seq, bt = (int(env[k]) for k in (
        "DNET_SCHED_SLOTS", "DNET_API_MAX_SEQ_LEN", "DNET_KV_BLOCK_TOKENS"))
    assert (lanes, max_seq, bt) == (16, 65536 + 1024, 128) and max_seq % bt == 0
    token = full["num_key_value_heads"] * full["head_dim"] * 2 * 2
    assert token == 2048  # bytes a token a layer
    full_pool = lanes * max_seq * 2 * token
    assert lanes * max_seq // bt == 8320 and round(full_pool / 1e9, 2) == 4.36
    per_lane = window_blocks(full["sliding_window"], bt, 2048)
    assert per_lane == 25
    window_pool = lanes * per_lane * bt * 6 * token
    assert round(window_pool / 1e9, 2) == 0.63
    staged = 8 * max_seq * token
    assert round(staged / 1e9, 2) == 1.09
    held = 7.59e9 + full_pool + window_pool + staged
    assert 0.80 < held / 16.9e9 < 0.82  # far over the floor of a quarter
    assert set(env) == set(ENV)  # no new knob: the issue's five and the one two standing cells carry
    assert env["DNET_API_WARM_ON_LOAD"] == "0"


def test_the_check_and_the_health_are_the_issues(full):
    chk = full["check"]
    assert chk["prompt_tokens"] >= 12000 and chk["decode_steps"] == 48
    yarn = full["rope_parameters"]["full_attention"]
    assert chk["prompt_tokens"] > yarn["original_max_position_embeddings"]  # past YaRN's original
    assert chk["prompt_tokens"] // full["sliding_window"] >= 11  # eleven windows
    assert -(-chk["prompt_tokens"] // 2048) - 1 == 5  # five chunk edges
    assert 0 < chk["mean_tolerance"] < chk["tolerance"] and len(chk["reason"]) > 400
    health = full["serve"]["expect_health"]
    assert set(health["used"]) == {"paged_attend", "flash_prefill"} and health["impl"] == "pallas"
    assert set(health["zero"]) == {"interpret", "emulate", "dense"}


def test_the_rehearsal_is_tiny_and_keeps_window_under_chunk(full):
    r = full["rehearse"]
    cfg, env = r["config"], r["env"]
    assert (cfg["hidden_size"], cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["vocab_size"]) == (64, 8, 2, 8, 256)
    assert cfg["sliding_window"] < int(env["DNET_SCHED_PREFILL_CHUNK"]) == 16
    assert full["num_hidden_layers"] == 8  # two periods, as served
    yarn = cfg["rope_parameters"]["full_attention"]
    assert yarn["rope_type"] == "yarn" and yarn["original_max_position_embeddings"] < r["check"]["prompt_tokens"]


def test_the_program_reads_the_file_as_the_file_says(full):
    from dnet_tpu.models import get_ring_model_cls
    from dnet_tpu.models.base import ModelConfig

    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    mc = ModelConfig.from_hf(cfg)
    assert set(mc.rope_by_type) == {"sliding_attention", "full_attention"}
    model = get_ring_model_cls("mellum")(mc, range(8))
    assert model.paged_kinds == ("window", "window", "window", "full") * 2
    assert model.window == 1024 and model.qk_norm and model.norm_topk_prob
    assert model.flash_layers() == (("window", 1024),) * 3 + (("full", 0),) + (
        ("window", 1024),) * 3 + (("full", 0),)
    # a 16-lane step keeps the einsum (0.87 of the experts expected), a chunk goes grouped
    assert model.moe_path(16, whole=True) == "dense" and model.moe_path(2048, whole=True) == "grouped"


# ---- the mix ---------------------------------------------------------------
def test_the_mix_is_a_data_file_for_the_generator_as_it_is():
    from benchmarks.harness import traffic

    cell = spec.resolve_cell(CELL)
    assert (cell.traffic_name, cell.chips, cell.config_name) == ("repoctx-sat-16", 1, NAME)
    mix = cell.traffic
    assert mix["clients"] == 16 and mix["block"] == 4 and mix["requests_per_client"] == 12
    assert isinstance(mix["schedule_seed"], int)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "min": 16384, "max": 65536}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9} and mix["residual_life_start"]
    assert (mix["warm_ticks"], mix["open_quiet_s"], mix["open_quiet_limit_s"],
            mix["trace_slice_s"], mix["ramp_limit_s"]) == (1, 0.1, 5, 5, 360)
    deep = spec.load_json(spec.BENCH_DIR / "traffic" / "deepdoc-sat-16.json")
    own = ("schedule_seed", "warm_prompt_tokens")  # the order, and the warm-up (read below)
    assert {k: v for k, v in mix.items() if k not in own} == {
        k: v for k, v in deep.items() if k not in own}  # the shape PR 50's cell is steady on
    plans = traffic.plan(mix, 3000000019, cell.config["vocab_size"])
    n = mix["requests_per_client"]
    assert len(plans) == 16 and all(len(p) == n for p in plans) and n % 4 == 0
    lens = [len(r.prompt_ids) for p in plans for r in p]
    assert 16384 <= min(lens) and max(lens) <= 65536 and 34000 < sum(lens) / len(lens) < 37000
    yarn = cell.config["rope_parameters"]["full_attention"]
    assert min(lens) > yarn["original_max_position_embeddings"]  # every prompt past YaRN's original
    assert min(lens) >= 16 * cell.config["sliding_window"]  # ... and sixteen windows
    for p in plans:  # one length from each band in every block of four
        for b in range(0, n, 4):
            block = sorted(len(r.prompt_ids) for r in p[b:b + 4])
            assert block[0] < 23171 <= block[1] < 32768 <= block[2] < 46341 <= block[3]
    assert all(256 <= r.max_tokens <= 1024 for p in plans for r in p[1:])
    again = traffic.plan(mix, 7, cell.config["vocab_size"])
    assert [len(r.prompt_ids) for r in again[3]] == [len(r.prompt_ids) for r in plans[3]]  # one order
    # the warm-up is the mix's own (the cell's file sets DNET_API_WARM_ON_LOAD 0): alone on the
    # server a prompt goes through in whole chunks of 2048 and one remainder, so the list has to
    # send every chunk program (16 .. 2048 rows) and a step at every table width the mix's
    # prompts reach: 128 blocks (16384 tokens) and up, so 128, 256, 512 and the clamped 520
    warm = mix["warm_prompt_tokens"]
    pow2 = lambda n: 1 << (n - 1).bit_length()  # noqa: E731
    widths = {max(16, pow2(w % 2048)) for w in warm if w % 2048} | {2048}
    assert widths == {16, 32, 64, 128, 256, 512, 1024, 2048} and max(warm) == 65536
    tables = {min(pow2(-(-(w + 1) // 128)), 520) for w in warm}
    assert tables == {128, 256, 512, 520}
    assert min(tables) <= pow2(16384 // 128) and min(warm) > 2048


# ---- the entries ------------------------------------------------------------
def test_the_cell_joins_the_standing_entries_by_appending():
    bench = spec.load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    joined = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in NEW]
    assert set(JOINED_BY_NAME) <= set(joined) and len(joined) == 41
    for name in joined:  # appended at the end, nothing else of the entry touched
        assert by[name]["workloads"][-1] == CELL and len(by[name]["workloads"]) >= 2
    # every entry all six standing cells share has this one too
    standing = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for m in bench["per_layer"]:
        if set(standing) <= set(m.get("workloads", ())):
            assert CELL in m["workloads"], m["name"]
    mine = {m["name"] for m in spec.resolve_cell(CELL).per_layer}
    assert set(NEW) <= mine and set(joined) <= mine


@pytest.mark.parametrize("name", NEW)
def test_an_entry_of_its_own_is_a_data_file_for_a_reader_that_is_there(name):
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["better"] == "lower"
    assert entry["source"] == "program_counter" and set(entry) == {
        "name", "unit", "better", "source", "layer", "moves", "workloads"}
    reader = spec.load_json(spec.layer_metric_file(name))
    assert reader["reader"] == "prom_delta" and reader["stat"] == "sum" and len(reader["what"]) > 80
    if name.startswith("flash_tiles"):
        kind = "window" if "window_folded" in name else "full"
        assert reader["family"] == "dnet_flash_tiles_total"
        assert reader["labels"] == {"kind": kind, "state": "folded"}
        assert entry["moves"] == "ttft_p50_ms" and entry["layer"] == "kernels"
    else:
        assert reader["family"] == "dnet_moe_experts_visited_total" and "labels" not in reader
        assert entry["moves"] == "output_tokens_per_s" and entry["layer"] == "engine programs"
    # the family is the program's own
    from dnet_tpu.obs import metric

    assert metric(reader["family"]) is not None


def test_the_benchmark_is_whole_with_the_cell_in_it():
    bench = spec.load_benchmark()
    assert spec.validate(bench) == []
    assert len(bench["per_layer"]) <= 80 and len(bench["per_layer"]) == 79
    assert len(bench["workloads"]) >= 7 and len(bench["configs"]) >= 7
    assert [w["name"] for w in bench["workloads"]][6] == CELL  # at the end of the list as it stood
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(cell["why"]) <= 200 and "all experts held" in cell["why"]
    assert "YaRN" in cell["why"] and "overstates" in cell["why"]


def test_a_reader_finds_nothing_in_a_program_without_the_counter():
    """The parent has no dnet_moe_experts_visited_total: the reader returns
    nothing and does not raise, and the line leaves the metric out."""
    from benchmarks.harness import readers

    reader = spec.load_json(spec.layer_metric_file("moe_experts_visited_in_window"))
    scrape = {"dnet_moe_assignments_total{held=\"yes\"}": 5.0}
    ev = readers.Evidence(client={}, scrapes=[scrape, scrape], trace=None, memory={})
    assert readers.read(reader, ev) is None
    with_it = dict(scrape, dnet_moe_experts_visited_total=40.0)
    ev = readers.Evidence(client={}, scrapes=[scrape | {"dnet_moe_experts_visited_total": 8.0},
                                              with_it], trace=None, memory={})
    assert readers.read(reader, ev) == 32.0


# ---- the cell, end to end on the CPU ---------------------------------------
def test_rehearsal_of_the_repository_context_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 54), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(k.startswith("rehearsal.") for k in m)  # no CPU number under a device name
    for name in ("kv_full_blocks_used_peak_pct", "kv_window_blocks_used_peak_pct",
                 "kv_window_blocks_released_in_window", "moe_assignments_held_in_window",
                 "moe_expert_rows_in_window", "flash_tiles_window_folded_in_window",
                 "flash_tiles_full_folded_in_window", "moe_experts_visited_in_window",
                 "decode_lane_steps_in_window", "decode_tokens_delivered_in_window",
                 "prefill_ticks_mean", "sched_batch_tokens_mean", "itl_p50_ms"):
        assert m[f"rehearsal.{name}"] > 0, name
    # every expert is held: what the lanes chose is what this chip holds
    assert m["rehearsal.moe_assignments_held_in_window"] == m["rehearsal.moe_assignments_routed_in_window"]
    # visited <= dispatches x 8 layers x 8 experts, and <= the assignments themselves
    dispatches = m["rehearsal.decode_slot_steps_in_window"] / 4
    assert m["rehearsal.moe_experts_visited_in_window"] <= dispatches * 8 * 8 + 64
    assert m["rehearsal.moe_experts_visited_in_window"] <= m["rehearsal.moe_assignments_routed_in_window"] + 64
    earlier = "\n".join(lines[:-1])
    assert "REHEARSAL" in earlier and "check: largest" in earlier and "-> ok" in earlier
    assert '"paged_attend"' in earlier and '"flash_prefill"' in earlier
