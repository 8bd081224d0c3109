"""Declared label sets for the performance-attribution metric families.

A LEAF module (like admission/reasons.py and membership/epoch.py): imported
by `dnet_tpu.obs` for pre-touching and by the metrics lint (pass 8), which
cross-checks the exposed label sets against these tuples BOTH directions —
a new host span or instrumented jit entry point cannot ship without its
series, and a renamed one cannot strand a stale label on dashboards.
"""

from __future__ import annotations

# dnet_span_ms{span=}: the host spans obs.span() opens (obs/__init__.py).
# Each is a jax.profiler.TraceAnnotation (on the profiler's clock, beside the
# device ops, while a profile is taken) and ONE always-on histogram
# observation of its host-clock duration.  No fence: a span says what the
# HOST did; device time is the device trace's to tell.  A span's self time is
# its duration less its children's.  The tree (parent > child):
#   dnet.tick                 sched/step.py execute_tick, compute thread
#     dnet.tick.decode        around engine.decode_launch (step n+1, after the
#                             tick's chunks are launched) and around
#                             engine.decode_read (step n, enqueued a tick ago)
#       dnet.decode.prepare     buffer pops, numpy rows, uploads, table ids
#       dnet.decode.launch      the jitted step's call (+ kv_append):
#                               an ENQUEUE (and a compile, if one happens)
#       dnet.decode.readback    the np.asarray reads: host blocked until the
#                               device finishes the dispatch
#       dnet.decode.unpack      SampleResult slicing
#     dnet.tick.prefill       one per prefill chunk, between the two halves
#       dnet.prefill.launch     engine.prefill_chunk: ENQUEUE only
#       dnet.prefill.adopt      store_prefix + adopt_prefilled: the slot
#                               commit, the first-token sample and the
#                               lane's sampling state, ENQUEUED (compiled
#                               programs; the pools' alloc is its host work)
#     dnet.prefill.readback   the tick's first tokens read, whole fields at
#                             a time: host blocked until the device has
#                             finished the tick's chunks and adoptions
#   dnet.sched.turn           event loop, sched/engine.py _tick_loop: the
#                             loop's whole share of the turn-around between
#                             two ticks, from its resume after tick n to the
#                             submit of tick n+1 (held across awaits:
#                             obs/__init__.py span says why that is sound)
#     dnet.sched.apply          SchedulerAdapter._apply
#     dnet.sched.drivers_turn   _apply's end to _drivers_turn's end: the park
#                               until the first driver asks again, and the
#                               bounded wait for the rest (held across awaits;
#                               only where a driver was owed an answer)
#     dnet.sched.plan           policy.plan
#   dnet.turn.to_loop         dnet.tick n's end (compute thread) to the
#                             loop's resume: crosses threads, histogram only
#   dnet.turn.to_thread       the submit (loop) to dnet.tick n+1's start
#                             (compute thread): histogram only
#   dnet.api.sse_flush        api/http.py write_chunk (awaits: histogram
#                             only, no annotation)
# to_loop + sched.turn + to_thread + decode.prepare + decode.launch is the
# turn-around dnet_sched_turnaround_ms observes on the compute thread.
SPAN_TICK = "dnet.tick"
SPAN_TICK_DECODE = "dnet.tick.decode"
SPAN_DECODE_PREPARE = "dnet.decode.prepare"
SPAN_DECODE_LAUNCH = "dnet.decode.launch"
SPAN_DECODE_READBACK = "dnet.decode.readback"
SPAN_DECODE_UNPACK = "dnet.decode.unpack"
SPAN_TICK_PREFILL = "dnet.tick.prefill"
SPAN_PREFILL_LAUNCH = "dnet.prefill.launch"
SPAN_PREFILL_ADOPT = "dnet.prefill.adopt"
SPAN_PREFILL_READBACK = "dnet.prefill.readback"
SPAN_SCHED_PLAN = "dnet.sched.plan"
SPAN_SCHED_APPLY = "dnet.sched.apply"
SPAN_SCHED_TURN = "dnet.sched.turn"
SPAN_SCHED_DRIVERS_TURN = "dnet.sched.drivers_turn"
SPAN_TURN_TO_LOOP = "dnet.turn.to_loop"
SPAN_TURN_TO_THREAD = "dnet.turn.to_thread"
SPAN_SSE_FLUSH = "dnet.api.sse_flush"
# the children of dnet.tick.decode, in dispatch order (loadgen/report.py's
# decode table and the reconciliation tests sum these against the parent)
DECODE_CHILD_SPANS = (
    SPAN_DECODE_PREPARE,
    SPAN_DECODE_LAUNCH,
    SPAN_DECODE_READBACK,
    SPAN_DECODE_UNPACK,
)
HOST_SPANS = (
    SPAN_TICK,
    SPAN_TICK_DECODE,
    *DECODE_CHILD_SPANS,
    SPAN_TICK_PREFILL,
    SPAN_PREFILL_LAUNCH,
    SPAN_PREFILL_ADOPT,
    SPAN_PREFILL_READBACK,
    SPAN_SCHED_PLAN,
    SPAN_SCHED_APPLY,
    SPAN_SSE_FLUSH,
    SPAN_SCHED_TURN,
    SPAN_SCHED_DRIVERS_TURN,
    SPAN_TURN_TO_LOOP,
    SPAN_TURN_TO_THREAD,
)

# dnet_sched_turnaround_ms{device=}: what the device was doing while the
# host turned from tick n to tick n+1 (sched/step.py).  `drained`: tick n
# ended in a blocking read of everything it enqueued (a decode-only tick;
# a tick whose last program was an adoption, read by dnet.prefill.readback),
# so the device has nothing until tick n+1's first launch.  `busy`: chunks
# tick n enqueued and did not read may still run.
TURN_DEVICE_DRAINED = "drained"
TURN_DEVICE_BUSY = "busy"
TURN_DEVICE = (TURN_DEVICE_DRAINED, TURN_DEVICE_BUSY)

# dnet_sched_drivers_turn_total{outcome=}: how the wait for the drivers
# the last tick handed a token to ended (sched/engine.py _drivers_turn):
# every one asked again inside DRIVER_TURN_S, the bound cut the wait (the
# lanes still out join the tick after), or nobody was owed an answer
DRIVERS_TURN_ANSWERED = "answered"
DRIVERS_TURN_TIMED_OUT = "timed_out"
DRIVERS_TURN_NONE = "none"
DRIVERS_TURN_OUTCOMES = (
    DRIVERS_TURN_ANSWERED, DRIVERS_TURN_TIMED_OUT, DRIVERS_TURN_NONE,
)

# dnet_api_driver_asks_total{order=}: when the API driver asked for a
# step's token (api/inference.py _run): `ahead`, at the end of DECIDE on the
# token before it, so before that token's delivery; `at_step`, at the top of
# the step's own iteration (a request's first ask; a step whose ask DECIDE
# held back because the ring was degraded, if it has recovered by then).
# An ask ahead that raises is counted under neither and is not sent again:
# what it raised surfaces at the step's top.  ahead / all is the share of
# asks that kept a delivery off the path between two decode steps.
DRIVER_ASK_AHEAD = "ahead"
DRIVER_ASK_AT_STEP = "at_step"
DRIVER_ASK_ORDERS = (DRIVER_ASK_AHEAD, DRIVER_ASK_AT_STEP)

# jax.named_scope names inside the traced programs, beside each jitted
# entry's own JIT_FNS name: metadata only (no instruction changes), so a
# device trace can be read by layer where the profiler carries op names
SCOPE_SAMPLE = "dnet.sample"
SCOPE_LM_HEAD = "dnet.lm_head"
SCOPE_MOE = "dnet.moe"
SCOPE_ATTN = "dnet.attn"
# inside dnet.attn / dnet.moe of a model with layers of two kinds and
# always-on experts (models/cohere2_moe.py)
SCOPE_ATTN_WINDOW = "dnet.attn.window"
SCOPE_ATTN_FULL = "dnet.attn.full"
SCOPE_MOE_SHARED = "dnet.moe.shared"
# inside dnet.attn of a model whose layers keep a recurrent state and no
# keys (models/brumby.py), or of one that mixes such layers with full ones
# (models/qwen3_next.py: the delta rule and its convolution here, the
# softmax layers under dnet.attn.full): the state's read, decay and update
SCOPE_ATTN_STATE = "dnet.attn.state"
# inside dnet.attn of a model whose cache entry is ONE latent row a token
# (multi-head latent attention, models/deepseek_v2.py): the absorb, the
# attention over the latents (the decode kernel paged_attend_latent; a
# prefill chunk's expansion and its flash kernel) and the un-absorb
SCOPE_ATTN_LATENT = "dnet.attn.latent"
# inside dnet.attn of a model whose full layers attend a CHOSEN subset of a
# sequence's blocks (ops/sparse_attention.py; models/minicpm_sala.py): the
# index (pooled keys to scores to the choice) and the read of the chosen
# blocks (the decode kernel paged_attend_sparse; a prefill chunk's masked
# tiles, flash_prefill_sparse)
SCOPE_ATTN_INDEX = "dnet.attn.index"
SCOPE_ATTN_SPARSE = "dnet.attn.sparse"
DEVICE_SCOPES = (
    SCOPE_SAMPLE, SCOPE_LM_HEAD, SCOPE_MOE, SCOPE_ATTN,
    SCOPE_ATTN_WINDOW, SCOPE_ATTN_FULL, SCOPE_MOE_SHARED, SCOPE_ATTN_STATE,
    SCOPE_ATTN_LATENT, SCOPE_ATTN_INDEX, SCOPE_ATTN_SPARSE,
)

# dnet_kv_blocks_used / _free / dnet_kv_pool_blocks {kind=}: the paged pool
# keeps books per KIND of layer (kv/paged.py).  A model whose layers all
# keep everything has the `full` kind alone; `window` layers hold only the
# blocks inside their window and give back the ones behind it
# (dnet_kv_window_blocks_released_total counts those).
KV_KIND_FULL = "full"
KV_KIND_WINDOW = "window"
KV_KINDS = (KV_KIND_FULL, KV_KIND_WINDOW)
# The third kind of layer keeps no blocks at all: one recurrent STATE entry
# a lane, of one size whatever the sequence's length (kv/store.py
# StateStore), so it has no pool, no page table and no series among the
# dnet_kv_blocks_* families: dnet_state_slots / dnet_state_slots_used are
# its books, and a lane is all a sequence costs.  A model that mixes state
# layers with full ones (kv/store.py HybridStore) keeps BOTH books: a lane
# and the `full` kind's blocks for the same sequence.
KV_KIND_STATE = "state"

# dnet_retention_tokens_total{phase=} / dnet_gdn_tokens_total{phase=} /
# dnet_mla_tokens_total{phase=}: tokens that went through a state layer's
# op (power retention; the gated delta rule) or a latent-attention layer,
# by the program that carried them (a prefill chunk's real tokens; a decode
# dispatch's lanes x steps)
RETENTION_PHASES = ("prefill", "decode")

# dnet_sparse_blocks_total{state=}: for each decode dispatch and sparse
# layer, the blocks (of the model's `block_size` tokens) its active lanes
# READ (`chosen`) against the blocks they HOLD (`resident`); 1 - chosen /
# resident is the share of the cache a step leaves unread
SPARSE_BLOCK_STATES = ("chosen", "resident")
# dnet_sparse_tokens_total{mode=}: query positions that went through a
# sparse layer on the served path, by the side of `dense_len` their context
# lies on: `dense` attends everything before it, `sparse` the chosen blocks
SPARSE_MODES = ("dense", "sparse")

# dnet_flash_tiles_total{kind=, state=}: (q tile, kv tile) pairs of the
# [T / bq, S / bk] grid a prefill chunk spans against its staged row, a
# layer that attends through ops/flash_attention.py (kind: KV_KINDS), by
# what the kernel makes of them: `folded` = copied and multiplied,
# `skipped` = above the causal diagonal, behind the window or past the
# chunk, so neither copied nor stepped over — booked on the host from the
# chunk's position by the kernel's own range (flash_tiles)
FLASH_TILE_STATES = ("folded", "skipped")

# dnet_moe_assignments_total{held=}: (token, chosen expert) pairs of the
# batched decode dispatches' active lanes, by whether the expert is one
# this process holds (an expert share, ops/moe.py) — summed on the device,
# read back with the step's tokens
MOE_HELD = ("yes", "no")

# dnet_moe_expert_rows_total{path=}: rows of the prefill chunks and decode
# steps launched for a model with routed experts, by the exact compute
# path the rule gives a program of that many rows (ops/moe.py:
# resolve_moe_impl through RingModel.moe_path) — counted on the host at
# the launch, from the program's static row count
MOE_PATHS = ("grouped", "dense")

# dnet_decode_tokens_total{source=}: where a token decode_batch handed the
# driver came from (core/batch.py)
#   dispatch — first row of the dispatch this call made
#   buffer   — a row an earlier dispatch left in the engine's buffer (a
#              verify block's later rows; under the scheduler, the token
#              a late driver had not asked for when its step was read):
#              no device work in this call
#   spec     — first row of a per-lane speculative verify block
DECODE_TOKEN_SOURCES = ("dispatch", "buffer", "spec")

# Instrumented jitted entry points (obs/jit.py instrument_jit): the `fn`
# label of dnet_jit_compiles_total.  Every instrument_jit call site must use
# one of these names — the lint fails a stray label either direction.
JIT_FNS = (
    "local_prefill",        # LocalEngine._forward (bucketed prefill)
    "new_session",          # LocalEngine._fresh_session: a session's zeroed
                            # cache row, key and counts in one program
    "local_decode",         # LocalEngine._decode (fused decode+sample)
    "local_decode_chunk",   # LocalEngine._decode_chunk (R-step scan)
    "sample_with_counts",   # core/engine.py sample_with_counts: key split,
                            # sample and counts outside the decode programs
                            # (a prompt's first token), one program a plan
    "batched_step",         # BatchedEngine._step (vmapped decode+sample)
    "batched_spec",         # BatchedEngine._spec_step (verify blocks)
    "adopt_lane",           # BatchedEngine._adopt_lane: a prefilled session's
                            # counts, key (hist, dense KV row) into its lane
    "kv_gather",            # pool store: page-table gather (prefix restore)
    "kv_scatter",           # pool store: a staged row's blocks into the pool
    "paged_attend",         # BatchedEngine's decode step attending the
                            # pool in place
    "kv_append",            # pool store: per-step block-append of new K/V rows
    "wire_encode",          # wire-pipeline hop encode launches (lossless
                            # cast / sparse / qsparse8 — compression/ops.py)
    "tp_window",            # TpEngine shard_map window/step programs over
                            # the ("batch", "model") mesh (parallel/tp.py)
    "tp_collective",        # standalone collective calibration probes
                            # (parallel/tp_collectives.py probe_collective_ms)
)

# dnet_request_segment_ms{segment=}: the exhaustive, non-overlapping
# critical-path segment ledger one request's recorded spans decompose into
# (obs/critical_path.py).  Every wall-clock millisecond between admission
# and the closing request span is attributed to EXACTLY one segment, so the
# per-request sums reconcile against measured E2E and the histogram's
# per-segment totals explain a serving window's p99 without hand-joining
# span families.  The metrics lint (pass DL028) cross-checks these against
# the exposed label set both ways.
#   admission_wait  — queued at the admission gate before a slot opened
#   sched_queue     — admitted but waiting on a scheduler/lane grant
#   prefill_compute — prompt prefill (local engine or replayed prefix)
#   decode_compute  — driver decode-step residual not claimed by a more
#                     specific segment below
#   wire_encode     — activation codec encode on the wire path
#   wire_tx         — writing frames to outbound streams
#   hop_rtt         — in-flight between nodes (send..ingress gap)
#   shard_compute   — shard-side layer compute
#   sse_flush       — serializing/flushing SSE chunks to the client
#   other           — recorded wall clock no span claims (gaps)
SEG_ADMISSION_WAIT = "admission_wait"
SEG_SCHED_QUEUE = "sched_queue"
SEG_PREFILL_COMPUTE = "prefill_compute"
SEG_DECODE_COMPUTE = "decode_compute"
SEG_WIRE_ENCODE = "wire_encode"
SEG_WIRE_TX = "wire_tx"
SEG_HOP_RTT = "hop_rtt"
SEG_SHARD_COMPUTE = "shard_compute"
SEG_SSE_FLUSH = "sse_flush"
SEG_OTHER = "other"
REQUEST_SEGMENTS = (
    SEG_ADMISSION_WAIT,
    SEG_SCHED_QUEUE,
    SEG_PREFILL_COMPUTE,
    SEG_DECODE_COMPUTE,
    SEG_WIRE_ENCODE,
    SEG_WIRE_TX,
    SEG_HOP_RTT,
    SEG_SHARD_COMPUTE,
    SEG_SSE_FLUSH,
    SEG_OTHER,
)

# dnet_wire_bytes_total{dir=}: activation/token payload bytes by wire
# direction (tx = written to outbound streams, rx = admitted at ingress).
# The metrics lint (pass 12) cross-checks these against the exposed label
# set both ways, the established leaf-enum pattern.
WIRE_DIRS = ("tx", "rx")

# dnet_device_mem_bytes{kind=}: backend memory stats summed over local
# devices, where the PJRT backend reports them (TPU/GPU; CPU returns none)
DEVICE_MEM_KINDS = ("in_use", "peak", "limit")

# dnet_tp_collective_ms{op=} / dnet_tp_collective_bytes_total{op=}: the two
# intra-shard tensor-parallel collective shapes the TP seam dispatches
# (parallel/tp_collectives.py).  The metrics lint (pass 13) cross-checks
# these against the exposed label sets both ways.
TP_OPS = ("all_reduce", "all_gather")

# dnet_events_total{name=}: the canonical wide-event vocabulary
# (obs/events.py log_event).  Every structured event a node journals uses
# one of these names — the metrics lint (pass DL030) cross-checks the
# exposed label set against this tuple both ways, so an event cannot ship
# without its counter series and a renamed one cannot strand a stale label.
#   request_complete — EXACTLY one per finished request (any outcome):
#                      status, shed/finish reason, token counts, resolved
#                      codec/kv/tp modes, and the critical-path segment
#                      ledger embedded
#   admitted         — admission granted a slot (queue wait attached)
#   shed             — admission rejected the request (reason attached)
#   preempted        — scheduler evicted a running sequence to WAITING
#   resumed          — a mid-decode failure was transparently replayed
#   recovery_round   — one auto-recovery re-solve round ended (outcome)
#   epoch_fenced     — a stale-epoch message was fenced out (kind)
#   routed           — the fleet front door chose a replica for a request
#                      (replica + routing reason attached — fleet/router.py)
#   failover         — in-flight work moved from a dead replica to a
#                      survivor mid-stream (victim/survivor attached)
EVENT_REQUEST_COMPLETE = "request_complete"
EVENT_ADMITTED = "admitted"
EVENT_SHED = "shed"
EVENT_PREEMPTED = "preempted"
EVENT_RESUMED = "resumed"
EVENT_RECOVERY_ROUND = "recovery_round"
EVENT_EPOCH_FENCED = "epoch_fenced"
EVENT_ROUTED = "routed"
EVENT_FAILOVER = "failover"
EVENT_NAMES = (
    EVENT_REQUEST_COMPLETE,
    EVENT_ADMITTED,
    EVENT_SHED,
    EVENT_PREEMPTED,
    EVENT_RESUMED,
    EVENT_RECOVERY_ROUND,
    EVENT_EPOCH_FENCED,
    EVENT_ROUTED,
    EVENT_FAILOVER,
)
