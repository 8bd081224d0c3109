"""Unified observability: metrics registry + per-request flight recorder.

One process-global `MetricsRegistry` (Prometheus text exposition at
`GET /metrics` on both the API and shard HTTP servers) and one
`FlightRecorder` (span timelines at `GET /v1/debug/timeline/{rid}`).
Instrumented modules fetch family handles by name via `metric()`; the
canonical family set below is registered on first access so `/metrics`
exposes every series — zero-valued — from process start, and so a typo'd
name fails loudly at import instead of silently creating a parallel series.

`span(name, **args)` is the ONE host-span primitive: a
`jax.profiler.TraceAnnotation` (next to free while no profiler session
runs; in the same `.xplane.pb` as the device ops, on the same clock, while
one does) plus one always-on `dnet_span_ms{span=}` observation.  Names are
declared in obs/phases.py HOST_SPANS.  It fences nothing.  `open()` /
`close()` are `with` for a span whose ends lie in two iterations of a loop.

`obs_enabled()` is the ONE truth for profile gating: the `[PROFILE]` log
filter (utils/logger.py) and any sampling decisions both consult it, so the
legacy `DNET_PROFILE` env and `DNET_OBS_ENABLED` (config.ObsSettings) can
never disagree.  The registry and recorder themselves are always on —
counters are near-free and the recorder is bounded — gating covers only the
log-line firehose, the tick-record ring and the per-layer sync fences.
"""

from __future__ import annotations

import threading
import time

from dnet_tpu.obs.metrics import (
    CONTENT_TYPE_LATEST,
    DEFAULT_MS_BUCKETS,
    METRIC_NAME_RE,
    MetricFamily,
    MetricsRegistry,
)
from dnet_tpu.obs.recorder import FlightRecorder

__all__ = [
    "CONTENT_TYPE_LATEST",
    "DEFAULT_MS_BUCKETS",
    "METRIC_NAME_RE",
    "FlightRecorder",
    "MetricFamily",
    "MetricsRegistry",
    "get_recorder",
    "get_registry",
    "get_slo_tracker",
    "metric",
    "obs_enabled",
    "observe_span",
    "reset_obs",
    "span",
]

_registry = MetricsRegistry()
_recorder = FlightRecorder()
_core_once = threading.Lock()
_core_done = False

# lane-depth / small-count histograms use power-of-two buckets, not ms
COUNT_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)

_CACHE_KINDS = ("prefix", "snapshot")

# request waits run to seconds under load: the default ms ladder tops out
# too early to tell one prefill chunk from ten
_WAIT_MS_BUCKETS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
                    2500.0, 5000.0, 10000.0, 30000.0)

# the turn-around between two ticks is milliseconds and its bound two: the
# ladder starts under one and is fine around DRIVER_TURN_S
_TURN_MS_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.0, 3.0, 5.0, 10.0, 25.0, 50.0,
                    100.0, 250.0, 1000.0, 5000.0)


def _register_core(reg: MetricsRegistry) -> None:
    """The canonical family set, pre-registered (and labeled children
    pre-touched) so exposition carries them at zero before first use."""
    reg.histogram(
        "dnet_decode_step_ms",
        "One decode dispatch's host wall time (enqueue through readback) "
        "shared evenly over the tokens it produced, observed once per "
        "token (ms)",
    )
    reg.histogram(
        "dnet_prefill_ms",
        "Host time to ENQUEUE one prefill forward (a prompt, or one chunk "
        "of it); the device runs it later, so this is not prefill "
        "latency (ms)",
    )
    reg.histogram(
        "dnet_ttft_ms", "Time to first token per request (ms)"
    )
    reg.histogram(
        "dnet_layer_compute_ms",
        "Per-layer compute wall time under DNET_OBS_SYNC_PER_LAYER (ms)",
    )
    reg.histogram(
        "dnet_token_rpc_ms",
        "Shard-to-API token callback RPC latency (ms)",
    )
    reg.histogram(
        "dnet_ring_hop_rtt_ms",
        "API-observed token frame send-to-resolve round trip (ms)",
    )
    reg.histogram(
        "dnet_lane_queue_wait_ms",
        "Decode-step wait in the lane coalescing queue (ms)",
    )
    reg.histogram(
        "dnet_lane_flush_depth",
        "Members per flushed multi-lane ring frame",
        buckets=COUNT_BUCKETS,
    )
    reg.counter(
        "dnet_transport_tx_bytes_total",
        "Activation/token frame payload bytes written to outbound streams",
    )
    reg.counter(
        "dnet_transport_rx_bytes_total",
        "Activation/token frame payload bytes admitted at ingress",
    )
    reg.counter(
        "dnet_transport_backpressure_total",
        "Backpressure ACKs that paused an outbound stream",
    )
    for name, help_text in (
        ("dnet_kv_cache_hits_total", "KV snapshot cache hits"),
        ("dnet_kv_cache_misses_total", "KV snapshot cache misses"),
        ("dnet_kv_cache_evictions_total", "KV snapshot cache LRU evictions"),
        ("dnet_kv_cache_stores_total", "KV snapshots stored"),
    ):
        fam = reg.counter(name, help_text, labelnames=("cache",))
        for kind in _CACHE_KINDS:
            fam.labels(cache=kind)  # pre-touch: expose at 0 from the start
    # paged KV pool (dnet_tpu/kv/paged.py): used + free == pool size at all
    # times (shared blocks count once in used; BlockPool.check_conservation)
    from dnet_tpu.obs.phases import (
        FLASH_TILE_STATES,
        KV_KINDS,
        MOE_HELD,
        MOE_PATHS,
        RETENTION_PHASES,
        SPARSE_BLOCK_STATES,
        SPARSE_MODES,
    )

    # the state kind (kv/store.py StateStore): one entry a lane, no blocks
    reg.gauge(
        "dnet_state_slots",
        "State entries the store of a model with recurrent-state layers "
        "holds: one a lane (0 for a model without such layers)",
    )
    reg.gauge(
        "dnet_state_slots_used",
        "State entries that belong to a live sequence right now",
    )
    reg.counter(
        "dnet_retention_state_bytes_total",
        "Bytes of recurrent state the batched decode dispatches read and "
        "wrote: active lanes x steps x layers x one entry x 2",
    )
    ret_fam = reg.counter(
        "dnet_retention_tokens_total",
        "Tokens that went through the state layers' retention op, by the "
        "program that carried them",
        labelnames=("phase",),
    )
    for phase in RETENTION_PHASES:
        ret_fam.labels(phase=phase)  # pre-touch: the lint checks these
    # the same two books for the gated delta rule's state layers
    # (ops/gated_delta.py; a hybrid model's, kv/store.py HybridStore)
    reg.counter(
        "dnet_gdn_state_bytes_total",
        "Bytes of gated-delta-rule state (S and the conv tail) the batched "
        "decode dispatches read and wrote: active lanes x steps x state "
        "layers x one entry x 2",
    )
    gdn_fam = reg.counter(
        "dnet_gdn_tokens_total",
        "Tokens that went through the state layers' gated delta rule, by "
        "the program that carried them",
        labelnames=("phase",),
    )
    for phase in RETENTION_PHASES:
        gdn_fam.labels(phase=phase)
    # and for lightning linear attention's (ops/lightning.py)
    reg.counter(
        "dnet_lightning_state_bytes_total",
        "Bytes of lightning-attention state the batched decode dispatches "
        "read and wrote: active lanes x steps x state layers x one entry x 2",
    )
    light_fam = reg.counter(
        "dnet_lightning_tokens_total",
        "Tokens that went through the state layers' lightning attention, by "
        "the program that carried them",
        labelnames=("phase",),
    )
    for phase in RETENTION_PHASES:
        light_fam.labels(phase=phase)
    # block-sparse attention over an index of pooled keys
    # (ops/sparse_attention.py): booked on the host from positions it has
    blocks_fam = reg.counter(
        "dnet_sparse_blocks_total",
        "Blocks of the sparse layers' block size the batched decode "
        "dispatches' active lanes read (chosen) and hold (resident), a "
        "sparse layer a step",
        labelnames=("state",),
    )
    for state in SPARSE_BLOCK_STATES:
        blocks_fam.labels(state=state)
    modes_fam = reg.counter(
        "dnet_sparse_tokens_total",
        "Query positions that went through the sparse layers on the served "
        "path (prefill chunks' real tokens and decode lanes), by the side "
        "of dense_len their context lies on",
        labelnames=("mode",),
    )
    for mode in SPARSE_MODES:
        modes_fam.labels(mode=mode)
    reg.counter(
        "dnet_sparse_index_rows_total",
        "Pooled keys written into the sparse layers' index leaf: the spans "
        "an adopted prompt completed and those decode tokens completed, a "
        "sparse layer each",
    )
    # a latent cache (multi-head latent attention, models/deepseek_v2.py):
    # the pool's books are the `full` kind's; these count what crosses it
    mla_fam = reg.counter(
        "dnet_mla_tokens_total",
        "Tokens that went through latent-attention layers on the served "
        "path, by the program that carried them (a prefill chunk's real "
        "tokens; a decode dispatch's lanes x steps)",
        labelnames=("phase",),
    )
    for phase in RETENTION_PHASES:
        mla_fam.labels(phase=phase)
    reg.counter(
        "dnet_mla_latent_bytes_total",
        "Bytes of latent cache entries the batched decode dispatches' "
        "absorbed attention reads, by the algorithm: live tokens of the "
        "active lanes x layers x one entry",
    )
    reg.counter(
        "dnet_mla_expanded_tokens_total",
        "Latent entries the prefill chunks expanded to per-head keys and "
        "values: position + chunk tokens, a chunk a layer",
    )
    flash_fam = reg.counter(
        "dnet_flash_tiles_total",
        "(q tile, kv tile) pairs of the grid a prefill chunk spans against "
        "its staged row, a layer that attends through the flash kernel, by "
        "the layer's kind and by whether the kernel folds the pair or "
        "neither copies nor steps over it",
        labelnames=("kind", "state"),
    )
    for kind in KV_KINDS:
        for state in FLASH_TILE_STATES:
            flash_fam.labels(kind=kind, state=state)
    for name, help_text in (
        ("dnet_kv_blocks_used",
         "Paged KV pool blocks currently allocated (refcount >= 1), by the "
         "kind of layer the pool serves (obs/phases.py KV_KINDS)"),
        ("dnet_kv_blocks_free",
         "Paged KV pool blocks on the free list, by kind"),
        ("dnet_kv_pool_blocks",
         "Paged KV pool total capacity in blocks, by kind"),
    ):
        fam = reg.gauge(name, help_text, labelnames=("kind",))
        for kind in KV_KINDS:
            fam.labels(kind=kind)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_kv_window_blocks_released_total",
        "Blocks a window layer's page table gave back because every row "
        "of them fell behind the window as the sequence advanced",
    )
    moe_fam = reg.counter(
        "dnet_moe_assignments_total",
        "(token, chosen expert) pairs of batched decode dispatches' active "
        "lanes, by whether this process holds the expert (an expert share "
        "routes over every expert and computes its own; models that do "
        "not report it leave this at 0)",
        labelnames=("held",),
    )
    for held in MOE_HELD:
        moe_fam.labels(held=held)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_moe_experts_visited_total",
        "(layer, held expert) pairs that at least one active lane of a "
        "batched decode dispatch chose: over the dispatches x the layers x "
        "the held experts, the share of its experts a step reads (models "
        "that do not report their lanes' choices leave this at 0)",
    )
    rows_fam = reg.counter(
        "dnet_moe_expert_rows_total",
        "Rows (padding included) of the prefill chunks and decode steps "
        "launched for a model with routed experts, by the compute path "
        "its experts take at that row count (grouped above the ridge, "
        "dense at or under it: ops/moe.py)",
        labelnames=("path",),
    )
    for path in MOE_PATHS:
        rows_fam.labels(path=path)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_kv_cow_copies_total",
        "Paged KV copy-on-write block copies (shared block diverged)",
    )
    reg.counter(
        "dnet_kv_prefix_shared_blocks_total",
        "Paged KV blocks shared by refcount aliasing instead of copying",
    )
    reg.counter(
        "dnet_kv_admission_rejected_total",
        "Paged KV admissions/extensions refused for lack of free blocks",
    )
    reg.counter("dnet_requests_total", "Decode requests started")
    reg.counter(
        "dnet_request_errors_total", "Decode requests failed with an error"
    )
    reg.counter(
        "dnet_tokens_generated_total", "Tokens emitted across all requests"
    )
    from dnet_tpu.obs.phases import DRIVER_ASK_ORDERS

    asks = reg.counter(
        "dnet_api_driver_asks_total",
        "send_tokens calls of the API driver, by when a step's ask left "
        "(obs/phases.py DRIVER_ASK_ORDERS: ahead = before the delivery of "
        "the token before it; api/inference.py _run)",
        labelnames=("order",),
    )
    for order in DRIVER_ASK_ORDERS:
        asks.labels(order=order)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_prefix_refill_total",
        "Ring prefix-cache misses transparently re-sent as full prefills",
    )
    # resilience (dnet_tpu/resilience/): retries, stream re-open, resume,
    # and the chaos harness that exercises all of them
    retries = reg.counter(
        "dnet_rpc_retries_total",
        "RPC attempts retried under the resilience backoff policy",
        labelnames=("method",),
    )
    for m in ("send_activation", "send_token", "reset_cache",
              "measure_latency", "load_model"):
        retries.labels(method=m)  # pre-touch: expose at 0 from the start
    reg.counter(
        "dnet_stream_reopens_total",
        "Broken activation streams re-opened with the in-flight frame "
        "re-sent",
    )
    reg.counter(
        "dnet_request_resumed_total",
        "Requests transparently resumed after a mid-decode failure",
    )
    reg.counter(
        "dnet_resume_replay_tokens_total",
        "Tokens (prompt + generated) replayed by request-resume prefills",
    )
    # admission / overload survival (dnet_tpu/admission/): bounded queue,
    # load shedding, end-to-end deadlines, drain.  Reason/stage label sets
    # are DECLARED in admission/reasons.py and cross-checked both ways by
    # the metrics lint (pass 6).
    reg.gauge(
        "dnet_admit_queue_depth",
        "Requests currently waiting in the bounded admission queue",
    )
    reg.gauge(
        "dnet_admit_inflight",
        "Requests currently holding an admission slot (executing)",
    )
    reg.counter(
        "dnet_admit_admitted_total",
        "Requests granted an admission slot",
    )
    reg.histogram(
        "dnet_admit_wait_ms",
        "Admission-queue wait before a slot was granted (ms)",
    )
    from dnet_tpu.admission.reasons import DEADLINE_STAGES, REJECT_REASONS

    rejected = reg.counter(
        "dnet_admit_rejected_total",
        "Requests shed at admission (reason per admission/reasons.py)",
        labelnames=("reason",),
    )
    for reason in REJECT_REASONS:
        rejected.labels(reason=reason)  # pre-touch: the lint checks these
    exceeded = reg.counter(
        "dnet_deadline_exceeded_total",
        "End-to-end request deadlines found expired, by pipeline stage",
        labelnames=("stage",),
    )
    for stage in DEADLINE_STAGES:
        exceeded.labels(stage=stage)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_cancel_propagated_total",
        "Client disconnects fanned out as cancel + reset_cache to the ring",
    )
    reg.gauge(
        "dnet_drain_state",
        "1 while the server is draining for shutdown (503 for new work)",
    )
    reg.counter(
        "dnet_shard_outq_dropped_total",
        "Shard output-queue frames dropped on overflow (error surfaced "
        "upstream in their place)",
    )
    # elastic ring membership (dnet_tpu/membership/): epoch fence +
    # recovery/rejoin accounting.  Kind/outcome label sets are DECLARED in
    # membership/epoch.py (a leaf module, like admission/reasons.py) and
    # cross-checked both ways by the metrics lint (pass 7).
    reg.gauge(
        "dnet_topology_epoch",
        "Ring topology epoch this process holds (API: minted; shard: "
        "pinned at load; 0 = unfenced)",
    )
    from dnet_tpu.membership.epoch import RECOVERY_OUTCOMES, STALE_EPOCH_KINDS

    stale = reg.counter(
        "dnet_stale_epoch_rejected_total",
        "Messages fenced out for carrying a dead topology epoch "
        "(kind per membership/epoch.py)",
        labelnames=("kind",),
    )
    for kind in STALE_EPOCH_KINDS:
        stale.labels(kind=kind)  # pre-touch: the lint checks these
    recovery = reg.counter(
        "dnet_recovery_total",
        "Ring recovery/rejoin rounds by outcome (membership/epoch.py)",
        labelnames=("outcome",),
    )
    for outcome in RECOVERY_OUTCOMES:
        recovery.labels(outcome=outcome)  # pre-touch: the lint checks these
    reg.histogram(
        "dnet_recovery_duration_seconds",
        "Wall time of one recovery/rejoin round (re-solve + reload)",
        buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
    )
    reg.counter(
        "dnet_shard_rejoins_total",
        "Quarantined shards re-admitted to the ring without operator action",
    )
    from dnet_tpu.resilience.chaos import INJECTION_POINTS

    chaos_fam = reg.counter(
        "dnet_chaos_injected_total",
        "Faults injected by the deterministic chaos harness",
        labelnames=("point",),
    )
    for point in INJECTION_POINTS:
        chaos_fam.labels(point=point)  # pre-touch: the lint checks these
    # labeled "peer", NOT "node": federation injects node="api" into every
    # API-section sample, and a node label here would collide with it
    reg.gauge(
        "dnet_federation_scrape_ok",
        "1 if the last /v1/cluster/metrics scrape of this peer succeeded",
        labelnames=("peer",),
    )
    reg.gauge(
        "dnet_slo_ttft_p95_ms",
        "Rolling-window TTFT p95 against the SLO target (ms)",
    )
    reg.gauge(
        "dnet_slo_decode_p95_ms",
        "Rolling-window decode-step p95 against the SLO target (ms)",
    )
    # p99 twins for load-report cross-validation (attainment logic stays
    # p95-based; these exist so loadgen tail percentiles have a live peer)
    reg.gauge(
        "dnet_slo_ttft_p99_ms",
        "Rolling-window TTFT p99 (informational; attainment is p95-based)",
    )
    reg.gauge(
        "dnet_slo_decode_p99_ms",
        "Rolling-window decode-step p99 (informational; attainment is "
        "p95-based)",
    )
    reg.gauge(
        "dnet_slo_availability",
        "Rolling-window request availability (1 - errors/requests)",
    )
    burning = reg.gauge(
        "dnet_slo_burning",
        "1 when the named SLO is violating its target over the window",
        labelnames=("slo",),
    )
    from dnet_tpu.obs.slo import SLO_KINDS

    for kind in SLO_KINDS:
        burning.labels(slo=kind)  # pre-touch: expose at 0 from the start
    # performance attribution (obs/phases.py, obs/jit.py): host spans, the
    # batched decode counters, jit compile tracking, device memory.
    # Span / source / fn / kind label sets are DECLARED in
    # obs/phases.py (a leaf module) and cross-checked both ways by the
    # metrics lint (pass 8).
    from dnet_tpu.obs.phases import (
        DECODE_TOKEN_SOURCES,
        DEVICE_MEM_KINDS,
        HOST_SPANS,
        JIT_FNS,
    )

    span_fam = reg.histogram(
        "dnet_span_ms",
        "Host-clock duration of one obs.span (obs/phases.py HOST_SPANS; "
        "never fenced: a launch span is an enqueue, a readback span is the "
        "host blocked on the device; self time = span less its children)",
        labelnames=("span",),
        buckets=(0.05, 0.25, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                 500.0, 1000.0, 2500.0, 5000.0),
    )
    for name in HOST_SPANS:
        span_fam.labels(span=name)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_decode_dispatch_total",
        "Batched decode dispatches that reached the device (one step each)",
    )
    reg.counter(
        "dnet_decode_slot_steps_total",
        "Slot-steps the device computed in batched decode dispatches "
        "(slots per dispatch, inactive slots included)",
    )
    reg.counter(
        "dnet_decode_lane_steps_total",
        "Slot-steps active lanes asked for in batched decode dispatches "
        "(dispatched lanes per dispatch)",
    )
    tokens_fam = reg.counter(
        "dnet_decode_tokens_total",
        "Tokens decode_batch handed to the driver, by where they came from "
        "(obs/phases.py DECODE_TOKEN_SOURCES)",
        labelnames=("source",),
    )
    for source in DECODE_TOKEN_SOURCES:
        tokens_fam.labels(source=source)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_decode_buffer_dropped_total",
        "Buffered tokens (a verify block's, a late driver's) thrown away "
        "when their session ended (computed on the device, never delivered)",
    )
    reg.counter(
        "dnet_decode_chained_lanes_total",
        "Lanes of a decode dispatch that took their input token from the "
        "flight before it, on the device (core/batch.py decode_launch "
        "chain=; counted at decode_read)",
    )
    reg.counter(
        "dnet_decode_surplus_steps_total",
        "Chained lane-steps whose token was dropped at the read: the lane "
        "had ended at the token before (a stop id, a cancel, a preemption)",
    )
    compiles = reg.counter(
        "dnet_jit_compiles_total",
        "Traced+compiled calls per instrumented jit entry point "
        "(obs/phases.py JIT_FNS)",
        labelnames=("fn",),
    )
    for fn in JIT_FNS:
        compiles.labels(fn=fn)  # pre-touch: the lint checks these
    reg.histogram(
        "dnet_jit_compile_ms",
        "Wall time of calls that compiled (trace + compile + first run)",
        buckets=(10.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                 10000.0, 30000.0, 60000.0),
    )
    mem = reg.gauge(
        "dnet_device_mem_bytes",
        "Backend device memory summed over local devices, where the PJRT "
        "backend reports stats (0 on CPU)",
        labelnames=("kind",),
    )
    for kind in DEVICE_MEM_KINDS:
        mem.labels(kind=kind)  # pre-touch: expose at 0 from the start
    # overlapped wire pipeline (transport/wire_pipeline.py,
    # DNET_WIRE_PIPELINE=1).  The dir label set is DECLARED in
    # obs/phases.py (leaf) and cross-checked both ways by the metrics
    # lint (pass 12).
    from dnet_tpu.obs.phases import WIRE_DIRS

    reg.histogram(
        "dnet_wire_encode_ms",
        "Hop-codec encode wall time per frame (D2H readback + byte "
        "packing; tx-stage time under the wire pipeline, compute-thread "
        "time without it)",
    )
    reg.histogram(
        "dnet_wire_decode_ms",
        "Hop-codec decode wall time per frame (H2D upload + on-device "
        "dequant dispatch; ingress time under the wire pipeline, "
        "compute-thread time without it)",
    )
    wire_bytes = reg.counter(
        "dnet_wire_bytes_total",
        "Activation/token frame payload bytes by wire direction "
        "(obs/phases.py WIRE_DIRS)",
        labelnames=("dir",),
    )
    for d in WIRE_DIRS:
        wire_bytes.labels(dir=d)  # pre-touch: the lint checks these
    reg.gauge(
        "dnet_wire_overlap_ratio",
        "Fraction of cumulative hop-codec time hidden off the compute "
        "thread (1.0 = codec fully overlapped with compute)",
    )
    # intra-shard tensor parallelism (parallel/tp.py, DNET_TP=N).  The op
    # label set is DECLARED in obs/phases.py TP_OPS (leaf) and
    # cross-checked both ways by the metrics lint (pass 13).
    from dnet_tpu.obs.phases import TP_OPS

    tp_ms = reg.histogram(
        "dnet_tp_collective_ms",
        "Intra-shard TP collective latency from the load-time calibration "
        "probe (per-op timing cannot be carved out of the fused layer "
        "programs at serving time)",
        labelnames=("op",),
    )
    tp_bytes = reg.counter(
        "dnet_tp_collective_bytes_total",
        "Analytic interconnect bytes dispatched per TP collective "
        "(ring-algorithm accounting, parallel/tp_collectives.py)",
        labelnames=("op",),
    )
    for op in TP_OPS:
        tp_ms.labels(op=op)  # pre-touch: the lint checks these
        tp_bytes.labels(op=op)  # pre-touch: the lint checks these
    reg.gauge(
        "dnet_tp_degree",
        "Resolved tensor-parallel degree of this process's serving engine "
        "(1 = single-chip, the pre-TP behavior)",
    )
    # runtime concurrency sanitizer (dnet_tpu/analysis/runtime/, DNET_SAN=1).
    # Check-code / thread label sets are DECLARED in
    # analysis/runtime/domains.py (a leaf module) and cross-checked both
    # ways by the metrics lint (pass 9).
    from dnet_tpu.analysis.runtime.domains import (
        RUNTIME_CHECK_CODES,
        ZOMBIE_THREAD_KINDS,
    )

    san_findings = reg.counter(
        "dnet_san_findings_total",
        "Runtime sanitizer (dsan) findings recorded, by DS check code",
        labelnames=("check",),
    )
    for code in RUNTIME_CHECK_CODES:
        san_findings.labels(check=code)  # pre-touch: the lint checks these
    zombies = reg.counter(
        "dnet_san_zombie_threads_total",
        "Worker threads that failed to join at stop() and were leaked as "
        "daemons (a wedged worker must be visible, not silent)",
        labelnames=("thread",),
    )
    for kind in ZOMBIE_THREAD_KINDS:
        zombies.labels(thread=kind)  # pre-touch: the lint checks these
    # iteration-level scheduler (dnet_tpu/sched/).  State /
    # kind / reason label sets are DECLARED in sched/kinds.py (a leaf
    # module, like admission/reasons.py) and cross-checked both ways by
    # the metrics lint (pass 10).
    from dnet_tpu.sched.kinds import (
        BATCH_KINDS,
        MIXED_TICK_OVERLAP,
        PREEMPT_REASONS,
        QUEUE_STATES,
    )

    reg.histogram(
        "dnet_sched_tick_ms",
        "One scheduler tick wall time: the mixed prefill+decode plan "
        "executed on the compute thread",
    )
    # where requests wait (sched/engine.py stamps on SchedRequest)
    reg.histogram(
        "dnet_sched_queue_wait_ms",
        "Scheduler wait of a request: step-0 enqueue to the start of the "
        "tick that runs its first prefill chunk (ms)",
        buckets=_WAIT_MS_BUCKETS,
    )
    reg.histogram(
        "dnet_sched_prefill_wall_ms",
        "Wall time from the start of a request's first prefill chunk to "
        "its first token resolved, the decode dispatches it shared ticks "
        "with included (ms)",
        buckets=_WAIT_MS_BUCKETS,
    )
    reg.histogram(
        "dnet_sched_prefill_ticks",
        "Prefill chunks (one per tick) a prompt took up to its first token",
        buckets=COUNT_BUCKETS,
    )
    reg.histogram(
        "dnet_sched_deliver_wait_ms",
        "A decode token's wait from the decode read ending on the compute "
        "thread to its future resolved on the event loop: a hop of the "
        "loop in a tick with prefill chunks (handed off at the read), the "
        "rest of the tick otherwise (ms)",
        buckets=_WAIT_MS_BUCKETS,
    )
    batch_fam = reg.histogram(
        "dnet_sched_batch_tokens",
        "Per-tick batch composition: prompt tokens chunk-prefilled and "
        "decode lanes stepped in the same tick (sched/kinds.py)",
        labelnames=("kind",),
        buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                 512.0, 1024.0, 2048.0),
    )
    for kind in BATCH_KINDS:
        batch_fam.labels(kind=kind)  # pre-touch: the lint checks these
    preempt = reg.counter(
        "dnet_sched_preemptions_total",
        "Sequences evicted back to WAITING by the scheduler "
        "(reason per sched/kinds.py)",
        labelnames=("reason",),
    )
    for reason in PREEMPT_REASONS:
        preempt.labels(reason=reason)  # pre-touch: the lint checks these
    mixed = reg.counter(
        "dnet_sched_mixed_ticks_total",
        "Ticks that carried a decode step AND a prefill chunk, by whether "
        "every chunk was enqueued before the step's blocking read "
        "(sched/step.py; overlapped per sched/kinds.py)",
        labelnames=("overlapped",),
    )
    for overlap in MIXED_TICK_OVERLAP:
        mixed.labels(overlapped=overlap)  # pre-touch: the lint checks these
    # the turn-around between two ticks (sched/step.py, sched/engine.py):
    # one number on the compute thread, its segments as host spans
    # (obs/phases.py: dnet.turn.*, dnet.sched.*), and what the turn left out
    from dnet_tpu.obs.phases import DRIVERS_TURN_OUTCOMES, TURN_DEVICE

    turnaround = reg.histogram(
        "dnet_sched_turnaround_ms",
        "End of tick n (execute_tick about to return) to tick n+1's first "
        "device program enqueued, both on the compute thread's clock, by "
        "what the device had to do meanwhile (obs/phases.py TURN_DEVICE: "
        "drained = tick n read everything it enqueued); runs through a "
        "tick that enqueues nothing (every lane answered from its "
        "buffer); not observed across a park with nothing to do (ms)",
        labelnames=("device",),
        buckets=_TURN_MS_BUCKETS,
    )
    for device in TURN_DEVICE:
        turnaround.labels(device=device)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_sched_lanes_left_out_total",
        "Requests that held a decoding lane but had not asked for their "
        "next token when a plan with a decode step was made: the step ran "
        "without them (sched/engine.py _tick_loop)",
    )
    turns = reg.counter(
        "dnet_sched_drivers_turn_total",
        "Waits for the drivers the last tick handed a token to, by how "
        "they ended (obs/phases.py DRIVERS_TURN_OUTCOMES; "
        "sched/engine.py _drivers_turn)",
        labelnames=("outcome",),
    )
    for outcome in DRIVERS_TURN_OUTCOMES:
        turns.labels(outcome=outcome)  # pre-touch: the lint checks these
    reg.histogram(
        "dnet_sched_answer_wait_ms",
        "A token's future resolved to the same request's next send_tokens, "
        "both on the event loop: the driver's way back to its ask as the "
        "scheduler feels it (the ask leaves before the token's delivery: "
        "api/inference.py _run), once per token asked for (ms)",
        buckets=_TURN_MS_BUCKETS,
    )
    depth = reg.gauge(
        "dnet_sched_queue_depth",
        "Requests resident in the scheduler queue, by live state "
        "(sched/kinds.py)",
        labelnames=("state",),
    )
    for state in QUEUE_STATES:
        depth.labels(state=state)  # pre-touch: the lint checks these
    # critical-path attribution (obs/critical_path.py): the exhaustive
    # per-request segment ledger.  The segment label set is DECLARED in
    # obs/phases.py REQUEST_SEGMENTS (leaf) and cross-checked both ways by
    # the metrics lint (pass DL028).
    from dnet_tpu.obs.phases import REQUEST_SEGMENTS

    seg_fam = reg.histogram(
        "dnet_request_segment_ms",
        "Per-request critical-path segment ledger: exhaustive, "
        "non-overlapping wall-time attribution of one request's recorded "
        "spans (obs/phases.py REQUEST_SEGMENTS; obs/critical_path.py)",
        labelnames=("segment",),
    )
    for seg in REQUEST_SEGMENTS:
        seg_fam.labels(segment=seg)  # pre-touch: the lint checks these
    # scheduler tick flight-recorder (sched/flight.py): the bounded
    # TickRecord ring behind GET /v1/debug/sched
    reg.counter(
        "dnet_sched_tick_records_total",
        "Scheduler ticks captured into the tick flight-recorder ring "
        "(sched/flight.py; bounded by DNET_OBS_TICK_RECORDS)",
    )
    # structured wide events (obs/events.py): the canonical event journal
    # behind GET /v1/debug/events.  The name vocabulary is DECLARED in
    # obs/phases.py EVENT_NAMES (leaf) and cross-checked both ways by the
    # metrics lint (pass DL030).
    from dnet_tpu.obs.phases import EVENT_NAMES

    events_fam = reg.counter(
        "dnet_events_total",
        "Structured wide events journaled by log_event "
        "(obs/phases.py EVENT_NAMES; obs/events.py)",
        labelnames=("name",),
    )
    for event_name in EVENT_NAMES:
        events_fam.labels(name=event_name)  # pre-touch: the lint checks these
    reg.histogram(
        "dnet_sched_tick_budget_used_ratio",
        "Fraction of the per-tick token budget the planned batch consumed "
        "(1.0 = saturated tick; sched/flight.py)",
        buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
    )
    # fleet routing (dnet_tpu/fleet/, DNET_FLEET=N): replica-state and
    # routing-reason label sets are DECLARED in fleet/states.py (leaf) and
    # cross-checked both ways by the metrics lint (pass DL031).  The
    # `replica` label is operator-assigned (r0, r1, ...) — dynamic, so no
    # pre-touch loop; the enum-valued families below get one.
    from dnet_tpu.fleet.states import REPLICA_STATES, ROUTE_REASONS

    reg.counter(
        "dnet_fleet_requests_total",
        "Requests the fleet front door dispatched, by serving replica "
        "(fleet/manager.py; replica ids are deployment-assigned)",
        labelnames=("replica",),
    )
    routed_fam = reg.counter(
        "dnet_fleet_routed_total",
        "Routing decisions by policy reason "
        "(fleet/states.py ROUTE_REASONS; fleet/router.py)",
        labelnames=("reason",),
    )
    for reason in ROUTE_REASONS:
        routed_fam.labels(reason=reason)  # pre-touch: the lint checks these
    reg.counter(
        "dnet_fleet_affinity_hits_total",
        "Requests routed by a sticky prefix-affinity entry to the replica "
        "holding their COW prefix blocks (fleet/router.py)",
    )
    reg.counter(
        "dnet_fleet_failovers_total",
        "In-flight requests migrated off a dead replica to a survivor "
        "via deterministic replay (fleet/manager.py)",
    )
    replicas_fam = reg.gauge(
        "dnet_fleet_replicas",
        "Fleet replicas by lifecycle state "
        "(fleet/states.py REPLICA_STATES; fleet/manager.py)",
        labelnames=("state",),
    )
    for state in REPLICA_STATES:
        replicas_fam.labels(state=state)  # pre-touch: the lint checks these


def _ensure_core() -> None:
    global _core_done
    if _core_done:
        return
    with _core_once:
        if not _core_done:
            _register_core(_registry)
            _core_done = True


def get_registry() -> MetricsRegistry:
    """The process-global registry (core families registered)."""
    _ensure_core()
    return _registry


def get_recorder() -> FlightRecorder:
    return _recorder


_slo_tracker = None
_slo_lock = threading.Lock()


def get_slo_tracker():
    """The process-global SLO tracker, built from ObsSettings targets on
    first access (lazy so tests can mutate the env, reset the settings
    cache, and reset_obs() to pick the new targets up)."""
    global _slo_tracker
    if _slo_tracker is None:
        with _slo_lock:
            if _slo_tracker is None:
                from dnet_tpu.config import get_settings
                from dnet_tpu.obs.slo import SloTracker

                obs = get_settings().obs
                _slo_tracker = SloTracker(
                    window_s=obs.slo_window_s,
                    ttft_p95_ms=obs.slo_ttft_p95_ms,
                    decode_p95_ms=obs.slo_decode_p95_ms,
                    availability=obs.slo_availability,
                )
    return _slo_tracker


def metric(name: str) -> MetricFamily:
    """Fetch a registered family by name; unknown names raise (catching
    typos at import time beats a silently separate series)."""
    _ensure_core()
    fam = _registry.get(name)
    if fam is None:
        raise KeyError(f"metric {name!r} is not registered; add it to "
                       f"dnet_tpu.obs._register_core")
    return fam


_trace_annotation = None


def _annotation_cls():
    """jax.profiler.TraceAnnotation, imported on first use: importing
    dnet_tpu.obs stays light, and opening an annotation starts no backend."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation


class span:
    """Host span: `with span("dnet.tick", decode_lanes=3): ...`.

    Opens a profiler TraceAnnotation (args become the event's stats) and,
    on exit, observes the host-clock duration into dnet_span_ms{span=name}.
    Always on, never fenced, never gated on obs_enabled().  Open and close
    it on ONE thread; code that awaits times itself and calls
    observe_span(), and so does an interval whose two ends lie on two
    threads.  `name` must be declared in obs/phases.py HOST_SPANS.

    Two spans ARE held across awaits, by design: `dnet.sched.turn` and
    `dnet.sched.drivers_turn` (sched/engine.py _tick_loop), so that the
    host plane of a profile says what the loop did while the device was
    drained between two ticks (benchmarks/harness/xplane.py names an idle
    gap by the span over it).  That is sound because the profiler keeps no
    stack: a TraceAnnotation is a TraceMe, which notes its start when it
    is entered and records ONE finished event (name, start, duration) when
    it is left, so whatever other coroutines open and close on the loop
    thread meanwhile become events of their own, inside it or across its
    edge, and move neither its start nor its duration.  What it costs is
    the reading: such a span's time is wall time of the loop thread, other
    coroutines' turns included, and a viewer draws an event that crosses
    its edge as overlapping.  Hence only these two, which exist to be
    wall time; tests/subsystems/test_turnaround.py holds a profile to it."""

    __slots__ = ("_child", "_ann", "_t0", "ms")

    def __init__(self, name: str, **args) -> None:
        self._child = _span_child(name)
        self._ann = _annotation_cls()(name, **args)
        self.ms = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1000.0
        self._ann.__exit__(*exc)
        self._child.observe(self.ms)

    def open(self) -> "span":
        return self.__enter__()

    def close(self) -> None:
        self.__exit__(None, None, None)


def observe_span(name: str, dur_ms: float) -> None:
    """Histogram half of span() alone, for code that times itself across
    an `await` (api/http.py write_chunk)."""
    _span_child(name).observe(dur_ms)


_span_children: dict = {}


def _span_child(name: str):
    child = _span_children.get(name)
    if child is None:
        from dnet_tpu.obs.phases import HOST_SPANS

        if name not in HOST_SPANS:
            raise ValueError(
                f"span name {name!r} is not declared in "
                f"dnet_tpu.obs.phases.HOST_SPANS"
            )
        child = _span_children[name] = metric("dnet_span_ms").labels(span=name)
    return child


def obs_enabled() -> bool:
    """Single profile-gating truth: DNET_OBS_ENABLED (ObsSettings) or the
    legacy DNET_PROFILE env, whichever is set (read via config.env_flag,
    the sanctioned DL006 escape hatch, so post-cache flips still gate)."""
    from dnet_tpu.config import env_flag, get_settings

    if get_settings().obs.enabled:
        return True
    return env_flag("DNET_PROFILE")


def reset_obs() -> None:
    """Zero metrics in place and drop recorded timelines (for tests).
    Family/child objects survive, so handles held by instrumented modules
    stay valid.  The SLO tracker is DROPPED, not zeroed — the next
    get_slo_tracker() re-reads targets from settings, so a test that
    changed DNET_OBS_SLO_* (and reset the settings cache) sees them."""
    global _slo_tracker
    _ensure_core()
    _registry.reset()
    _recorder.clear()
    # the scheduler tick ring is obs state too (captured under
    # obs_enabled, dumped by /v1/debug/sched): a test that resets the
    # books must not inherit a previous run's ticks.  Imported here, not
    # at module top: sched.flight itself imports dnet_tpu.obs.
    from dnet_tpu.sched.flight import get_tick_recorder

    get_tick_recorder().clear()
    # the wide-event journal is obs state too: drop ring + sink so the
    # next log_event re-reads DNET_OBS_EVENTS_* from fresh settings.
    # Late import: obs.events imports dnet_tpu.obs for metric().
    from dnet_tpu.obs.events import reset_events

    reset_events()
    with _slo_lock:
        _slo_tracker = None
