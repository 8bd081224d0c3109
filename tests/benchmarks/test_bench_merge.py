"""The merge of PR 43: `per_layer` says each reading once.

Until PR 43 every new cell brought its own `.mix` / `.gen` / `.doc` / `.lat`
twin of a metric whose reader file, `moves`, `better`, unit and source were
the ones already there, and the list stood at the contract's 128 entries
for 66 readings.  RETIRED is every name that went, with the name that reads
it now, the cells the retired entry listed, the end-to-end metric it moved
and the PARENT's reader file less `what`, quoted: the new entry's reader is
that file, its arrow that arrow, and its `workloads` holds those cells.  One
name went for another reason: `turnaround_drained_mean_ms` read `null`
since PR 40 (no tick ends drained) and the mean over the `busy`
turn-arounds of the same family stands in its place.
"""

import pytest

from benchmarks.harness import spec

BENCH = spec.load_benchmark()
BY = {m["name"]: m for m in BENCH["per_layer"]}
RAG, MIX, GEN, DOC, LAT = (
    "qwen3moe-ragprompt-sat", "cmdaplus-mixedlen-sat", "brumby-longgen-sat",
    "qwen3next-longdoc-sat", "mistral4-longctx-sat",
)
TOKENS, TTFT = "output_tokens_per_s", "ttft_p50_ms"
CELL_SUFFIXES = (".rag", ".mix", ".gen", ".doc", ".lat")

# retired name: (new name, the retired entry's cells, what it moved, the parent's reader less `what`)
RETIRED = {
    "sched_tick_host_mean_ms.rag": ("sched_tick_host_mean_ms", (RAG,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_tick_ms", "stat": "mean"}),
    "sort_time_pct.rag": ("sort_time_pct.ttft", (RAG,), TTFT,
        {"reader": "trace_share", "pattern": "^%?sort[.\\s=]", "of": "busy"}),
    "pallas_time_pct.rag": ("pallas_time_pct.attn", (RAG,), TTFT,
        {"reader": "trace_share", "pattern": "custom-call.*tpu_custom_call|^%?[A-Za-z_0-9.]*(paged_attend|flash_prefill|flash_decode|pallas)", "of": "busy"}),
    "itl_rag_p50_ms": ("itl_p50_ms", (RAG,), TOKENS,
        {"reader": "client", "field": "itl_p50_ms"}),
    "sched_queue_wait_mean_ms.rag": ("sched_queue_wait_mean_ms", (RAG,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_queue_wait_ms", "stat": "mean"}),
    "prefill_wall_mean_ms.rag": ("prefill_wall_mean_ms", (RAG,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_wall_ms", "stat": "mean"}),
    "prefill_ticks_mean.rag": ("prefill_ticks_mean", (RAG,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_ticks", "stat": "mean"}),
    "prefill_adopt_mean_ms.rag": ("prefill_adopt_mean_ms", (RAG,), TTFT,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.prefill.adopt"}, "stat": "mean"}),
    "attn_window_time_pct.mix": ("attn_window_time_pct", (MIX,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*(paged_attend_window|flash_prefill_window)", "of": "busy"}),
    "attn_full_time_pct.mix": ("attn_full_time_pct", (MIX,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*(paged_attend|flash_prefill)(?!_window)", "of": "busy"}),
    "pallas_time_pct.mix": ("pallas_time_pct.attn", (MIX,), TTFT,
        {"reader": "trace_share", "pattern": "custom-call.*tpu_custom_call|^%?[A-Za-z_0-9.]*(paged_attend|flash_prefill|flash_decode|pallas)", "of": "busy"}),
    "sort_time_pct.mix": ("sort_time_pct.ttft", (MIX,), TTFT,
        {"reader": "trace_share", "pattern": "^%?sort[.\\s=]", "of": "busy"}),
    "kv_full_blocks_used_peak_pct.mix": ("kv_full_blocks_used_peak_pct", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_kv_blocks_used", "labels": {"kind": "full"}, "stat": "max_ratio_pct", "over": "dnet_kv_pool_blocks", "over_labels": {"kind": "full"}}),
    "kv_window_blocks_used_peak_pct.mix": ("kv_window_blocks_used_peak_pct", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_kv_blocks_used", "labels": {"kind": "window"}, "stat": "max_ratio_pct", "over": "dnet_kv_pool_blocks", "over_labels": {"kind": "window"}}),
    "kv_window_blocks_released_in_window.mix": ("kv_window_blocks_released_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_kv_window_blocks_released_total", "stat": "sum"}),
    "moe_assignments_held_in_window.mix": ("moe_assignments_held_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_assignments_total", "labels": {"held": "yes"}, "stat": "sum"}),
    "moe_assignments_routed_in_window.mix": ("moe_assignments_routed_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_assignments_total", "stat": "sum"}),
    "itl_p50_ms.mix": ("itl_p50_ms", (MIX,), TOKENS,
        {"reader": "client", "field": "itl_p50_ms"}),
    "sched_tick_host_mean_ms.mix": ("sched_tick_host_mean_ms", (MIX,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_tick_ms", "stat": "mean"}),
    "prefill_wall_mean_ms.mix": ("prefill_wall_mean_ms", (MIX,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_wall_ms", "stat": "mean"}),
    "prefill_ticks_mean.mix": ("prefill_ticks_mean", (MIX,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_ticks", "stat": "mean"}),
    "decode_readback_wait_mean_ms.mix": ("decode_readback_wait_mean_ms", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.decode.readback"}, "stat": "mean"}),
    "decode_slot_steps_in_window.mix": ("decode_slot_steps_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_slot_steps_total", "stat": "sum"}),
    "decode_tokens_delivered_in_window.mix": ("decode_tokens_delivered_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_tokens_total", "stat": "sum"}),
    "sched_queue_wait_mean_ms.mix": ("sched_queue_wait_mean_ms", (MIX,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_queue_wait_ms", "stat": "mean"}),
    "sched_batch_tokens_mean.mix": ("sched_batch_tokens_mean", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_batch_tokens", "stat": "mean"}),
    "admit_wait_mean_ms.mix": ("admit_wait_mean_ms", (MIX,), TTFT,
        {"reader": "prom_delta", "family": "dnet_admit_wait_ms", "stat": "mean"}),
    "decode_prepare_mean_ms.mix": ("decode_prepare_mean_ms", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.decode.prepare"}, "stat": "mean"}),
    "prefill_adopt_mean_ms.mix": ("prefill_adopt_mean_ms", (MIX,), TTFT,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.prefill.adopt"}, "stat": "mean"}),
    "decode_deliver_wait_mean_ms.mix": ("decode_deliver_wait_mean_ms", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_deliver_wait_ms", "stat": "mean"}),
    "decode_lane_steps_in_window.mix": ("decode_lane_steps_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_lane_steps_total", "stat": "sum"}),
    "moe_grouped_rows_in_window.rag": ("moe_grouped_rows_in_window", (RAG,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_expert_rows_total", "labels": {"path": "grouped"}, "stat": "sum"}),
    "moe_expert_rows_in_window.rag": ("moe_expert_rows_in_window", (RAG,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_expert_rows_total", "stat": "sum"}),
    "moe_grouped_time_pct.rag": ("moe_grouped_time_pct", (RAG,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?(gmm|ragged-dot)[.\\s=-]", "of": "busy"}),
    "retention_step_time_pct.gen": ("retention_step_time_pct", (GEN,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*retention_step", "of": "busy"}),
    "retention_chunk_time_pct.gen": ("retention_chunk_time_pct", (GEN,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*retention_chunk", "of": "busy"}),
    "state_slots_used_peak_pct.gen": ("state_slots_used_peak_pct", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_state_slots_used", "stat": "max_ratio_pct", "over": "dnet_state_slots"}),
    "retention_state_bytes_in_window.gen": ("retention_state_bytes_in_window", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_retention_state_bytes_total", "stat": "sum"}),
    "retention_prefill_tokens_in_window.gen": ("retention_prefill_tokens_in_window", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_retention_tokens_total", "stat": "sum", "labels": {"phase": "prefill"}}),
    "itl_p50_ms.gen": ("itl_p50_ms", (GEN,), TOKENS,
        {"reader": "client", "field": "itl_p50_ms"}),
    "sched_tick_host_mean_ms.gen": ("sched_tick_host_mean_ms", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_tick_ms", "stat": "mean"}),
    "sched_queue_wait_mean_ms.gen": ("sched_queue_wait_mean_ms", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_queue_wait_ms", "stat": "mean"}),
    "prefill_wall_mean_ms.gen": ("prefill_wall_mean_ms", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_wall_ms", "stat": "mean"}),
    "prefill_ticks_mean.gen": ("prefill_ticks_mean", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_ticks", "stat": "mean"}),
    "prefill_adopt_mean_ms.gen": ("prefill_adopt_mean_ms", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.prefill.adopt"}, "stat": "mean"}),
    "decode_prepare_mean_ms.gen": ("decode_prepare_mean_ms", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.decode.prepare"}, "stat": "mean"}),
    "decode_readback_wait_mean_ms.gen": ("decode_readback_wait_mean_ms", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.decode.readback"}, "stat": "mean"}),
    "decode_deliver_wait_mean_ms.gen": ("decode_deliver_wait_mean_ms", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_deliver_wait_ms", "stat": "mean"}),
    "decode_slot_steps_in_window.gen": ("decode_slot_steps_in_window", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_slot_steps_total", "stat": "sum"}),
    "decode_lane_steps_in_window.gen": ("decode_lane_steps_in_window", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_lane_steps_total", "stat": "sum"}),
    "decode_tokens_delivered_in_window.gen": ("decode_tokens_delivered_in_window", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_tokens_total", "stat": "sum"}),
    "sched_batch_tokens_mean.gen": ("sched_batch_tokens_mean", (GEN,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_batch_tokens", "stat": "mean"}),
    "admit_wait_mean_ms.gen": ("admit_wait_mean_ms", (GEN,), TTFT,
        {"reader": "prom_delta", "family": "dnet_admit_wait_ms", "stat": "mean"}),
    "pallas_time_pct.gen": ("pallas_time_pct.retention", (GEN,), TOKENS,
        {"reader": "trace_share", "pattern": "custom-call.*tpu_custom_call|^%?[A-Za-z_0-9.]*(retention_step|retention_chunk|paged_attend|flash_prefill|flash_decode|pallas)", "of": "busy"}),
    "sort_time_pct.gen": ("sort_time_pct.tokens", (GEN,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?sort[.\\s=]", "of": "busy"}),
    "mixed_ticks_overlapped_in_window.rag": ("mixed_ticks_overlapped_in_window", (RAG,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_mixed_ticks_total", "labels": {"overlapped": "yes"}, "stat": "sum"}),
    "mixed_ticks_in_window.rag": ("mixed_ticks_in_window", (RAG,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_mixed_ticks_total", "stat": "sum"}),
    "mixed_ticks_overlapped_in_window.mix": ("mixed_ticks_overlapped_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_mixed_ticks_total", "labels": {"overlapped": "yes"}, "stat": "sum"}),
    "mixed_ticks_in_window.mix": ("mixed_ticks_in_window", (MIX,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_mixed_ticks_total", "stat": "sum"}),
    "gdn_step_time_pct.doc": ("gdn_step_time_pct", (DOC,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*gdn_step", "of": "busy"}),
    "gdn_chunk_time_pct.doc": ("gdn_chunk_time_pct", (DOC,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*gdn_chunk", "of": "busy"}),
    "attn_full_time_pct.doc": ("attn_full_time_pct", (DOC,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*(paged_attend|flash_prefill)(?!_window)", "of": "busy"}),
    "moe_grouped_time_pct.doc": ("moe_grouped_time_pct", (DOC,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?(gmm|ragged-dot)[.\\s=-]", "of": "busy"}),
    "pallas_time_pct.doc": ("pallas_time_pct.gdn", (DOC,), TOKENS,
        {"reader": "trace_share", "pattern": "custom-call.*tpu_custom_call|^%?[A-Za-z_0-9.]*(gdn_step|gdn_chunk|paged_attend|flash_prefill|flash_decode|gmm|pallas)", "of": "busy"}),
    "sort_time_pct.doc": ("sort_time_pct.tokens", (DOC,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?sort[.\\s=]", "of": "busy"}),
    "moe_assignments_held_in_window.doc": ("moe_assignments_held_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_assignments_total", "labels": {"held": "yes"}, "stat": "sum"}),
    "moe_assignments_routed_in_window.doc": ("moe_assignments_routed_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_assignments_total", "stat": "sum"}),
    "moe_grouped_rows_in_window.doc": ("moe_grouped_rows_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_expert_rows_total", "labels": {"path": "grouped"}, "stat": "sum"}),
    "moe_expert_rows_in_window.doc": ("moe_expert_rows_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_expert_rows_total", "stat": "sum"}),
    "gdn_state_bytes_in_window.doc": ("gdn_state_bytes_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_gdn_state_bytes_total", "stat": "sum"}),
    "gdn_prefill_tokens_in_window.doc": ("gdn_prefill_tokens_in_window", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_gdn_tokens_total", "stat": "sum", "labels": {"phase": "prefill"}}),
    "state_slots_used_peak_pct.doc": ("state_slots_used_peak_pct", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_state_slots_used", "stat": "max_ratio_pct", "over": "dnet_state_slots"}),
    "kv_full_blocks_used_peak_pct.doc": ("kv_full_blocks_used_peak_pct", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_kv_blocks_used", "labels": {"kind": "full"}, "stat": "max_ratio_pct", "over": "dnet_kv_pool_blocks", "over_labels": {"kind": "full"}}),
    "itl_p50_ms.doc": ("itl_p50_ms", (DOC,), TOKENS,
        {"reader": "client", "field": "itl_p50_ms"}),
    "sched_tick_host_mean_ms.doc": ("sched_tick_host_mean_ms", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_tick_ms", "stat": "mean"}),
    "sched_queue_wait_mean_ms.doc": ("sched_queue_wait_mean_ms", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_queue_wait_ms", "stat": "mean"}),
    "sched_batch_tokens_mean.doc": ("sched_batch_tokens_mean", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_batch_tokens", "stat": "mean"}),
    "prefill_wall_mean_ms.doc": ("prefill_wall_mean_ms", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_wall_ms", "stat": "mean"}),
    "prefill_ticks_mean.doc": ("prefill_ticks_mean", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_sched_prefill_ticks", "stat": "mean"}),
    "prefill_adopt_mean_ms.doc": ("prefill_adopt_mean_ms", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.prefill.adopt"}, "stat": "mean"}),
    "decode_prepare_mean_ms.doc": ("decode_prepare_mean_ms", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.decode.prepare"}, "stat": "mean"}),
    "decode_readback_wait_mean_ms.doc": ("decode_readback_wait_mean_ms", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_span_ms", "labels": {"span": "dnet.decode.readback"}, "stat": "mean"}),
    "decode_deliver_wait_mean_ms.doc": ("decode_deliver_wait_mean_ms", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_deliver_wait_ms", "stat": "mean"}),
    "decode_slot_steps_in_window.doc": ("decode_slot_steps_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_slot_steps_total", "stat": "sum"}),
    "decode_lane_steps_in_window.doc": ("decode_lane_steps_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_lane_steps_total", "stat": "sum"}),
    "decode_tokens_delivered_in_window.doc": ("decode_tokens_delivered_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_decode_tokens_total", "stat": "sum"}),
    "admit_wait_mean_ms.doc": ("admit_wait_mean_ms", (DOC,), TTFT,
        {"reader": "prom_delta", "family": "dnet_admit_wait_ms", "stat": "mean"}),
    "mixed_ticks_in_window.doc": ("mixed_ticks_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_mixed_ticks_total", "stat": "sum"}),
    "mixed_ticks_overlapped_in_window.doc": ("mixed_ticks_overlapped_in_window", (DOC,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_mixed_ticks_total", "labels": {"overlapped": "yes"}, "stat": "sum"}),
    "pool_copy_time_pct.rag": ("pool_copy_time_pct", (RAG,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?copy[.\\s=]", "of": "busy"}),
    "attn_full_time_pct.rag": ("attn_full_time_pct", (RAG,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*(paged_attend|flash_prefill)(?!_window)", "of": "busy"}),
    "turnaround_drained_mean_ms": ("turnaround_busy_mean_ms", (GEN, DOC), TOKENS,
        {"reader": "prom_delta", "family": "dnet_sched_turnaround_ms", "labels": {"device": "drained"}, "stat": "mean"}),
    "mla_decode_time_pct.lat": ("mla_decode_time_pct", (LAT,), TOKENS,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*paged_attend_latent", "of": "busy"}),
    "mla_prefill_time_pct.lat": ("mla_prefill_time_pct", (LAT,), TTFT,
        {"reader": "trace_share", "pattern": "^%?[A-Za-z_0-9.]*flash_prefill(?!_window)", "of": "busy"}),
    "mla_latent_bytes_in_window.lat": ("mla_latent_bytes_in_window", (LAT,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_mla_latent_bytes_total", "stat": "sum"}),
    "mla_prefill_tokens_in_window.lat": ("mla_prefill_tokens_in_window", (LAT,), TTFT,
        {"reader": "prom_delta", "family": "dnet_mla_tokens_total", "stat": "sum", "labels": {"phase": "prefill"}}),
    "mla_expanded_tokens_in_window.lat": ("mla_expanded_tokens_in_window", (LAT,), TTFT,
        {"reader": "prom_delta", "family": "dnet_mla_expanded_tokens_total", "stat": "sum"}),
    "itl_p50_ms.lat": ("itl_p50_ms", (LAT,), TOKENS,
        {"reader": "client", "field": "itl_p50_ms"}),
    "kv_full_blocks_used_peak_pct.lat": ("kv_full_blocks_used_peak_pct", (LAT,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_kv_blocks_used", "labels": {"kind": "full"}, "stat": "max_ratio_pct", "over": "dnet_kv_pool_blocks", "over_labels": {"kind": "full"}}),
    "moe_assignments_held_in_window.lat": ("moe_assignments_held_in_window", (LAT,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_assignments_total", "labels": {"held": "yes"}, "stat": "sum"}),
    "moe_assignments_routed_in_window.lat": ("moe_assignments_routed_in_window", (LAT,), TOKENS,
        {"reader": "prom_delta", "family": "dnet_moe_assignments_total", "stat": "sum"}),
}
# one reading, two arrows: the suffix names the arrow (or the thing read), never a cell
TWO_ARROWS = {"sort_time_pct.ttft": TTFT, "sort_time_pct.tokens": TOKENS}


def test_the_table_holds_every_name_that_went():
    assert len(RETIRED) == 101 and len({new for new, *_ in RETIRED.values()}) == 47
    assert not set(RETIRED) & set(BY)  # none came back
    assert len(BENCH["per_layer"]) == len(BY) <= 80  # 128 before; 66 at PR 43


@pytest.mark.parametrize("old", sorted(RETIRED))
def test_a_retired_name_is_read_by_the_same_reader_under_its_new_name(old):
    new, cells, moves, parents_reader = RETIRED[old]
    entry = BY[new]
    reader = spec.load_json(spec.layer_metric_file(new))
    assert reader.pop("what")  # each says what it reads, and what a cell's twin said alone
    if old == "turnaround_drained_mean_ms":  # pointed at what took its place
        assert parents_reader["labels"] == {"device": "drained"}
        parents_reader = dict(parents_reader, labels={"device": "busy"})
    assert reader == parents_reader
    assert entry["moves"] == moves
    assert set(cells) <= set(entry["workloads"])
    for cell in cells:
        assert new in {m["name"] for m in spec.resolve_cell(cell).per_layer}


def test_no_two_entries_have_one_reader_and_one_arrow():
    """Over the whole list, so the next twin fails tier-1: reader files equal
    apart from `what` with equal `moves`, `better`, `unit`, `source`, `layer`."""
    keys = {}
    for m in BENCH["per_layer"]:
        keys.setdefault(spec.reading(m), []).append(m["name"])
    assert [names for names in keys.values() if len(names) > 1] == []
    assert spec.validate(BENCH) == []


def test_a_suffix_names_the_arrow_or_the_thing_read_never_a_cell():
    assert not [n for n in BY if n.endswith(CELL_SUFFIXES) or "_rag_" in n]
    for name, moves in TWO_ARROWS.items():
        assert BY[name]["moves"] == moves
    a, b = (spec.load_json(spec.layer_metric_file(n)) for n in TWO_ARROWS)
    assert {k: v for k, v in a.items() if k != "what"} == {k: v for k, v in b.items() if k != "what"}
    # pallas_time_pct: three patterns, each knowing other kernels by name
    patterns = {spec.load_json(spec.layer_metric_file(n))["pattern"]
                for n in BY if n.startswith("pallas_time_pct.")}
    assert len(patterns) == 3


@pytest.mark.parametrize("name", sorted(n for n in BY if n.split(".")[0].endswith("_time_pct")))
def test_a_kernels_share_of_busy_is_better_lower(name):
    """The README's arrow rule: a share of busy time spent in a kernel (or a
    kind of instruction) the cell wants faster FALLS as it gets faster; PR 42
    found `mla_prefill_time_pct` marked `higher` while it fell 63.7 -> 41.9
    with + 43 % tokens/s."""
    assert BY[name]["better"] == "lower" and BY[name]["unit"] == "%"
    assert spec.load_json(spec.layer_metric_file(name))["reader"] == "trace_share"


def test_no_entry_lists_a_cell_twice_and_lists_keep_the_cells_order():
    order = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        cells = m.get("workloads")
        if cells is not None:
            assert len(set(cells)) == len(cells) >= 1 and set(cells) <= set(order), m["name"]
