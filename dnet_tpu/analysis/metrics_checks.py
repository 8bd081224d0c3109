"""Metric/observability contract passes (DL010+), folded in from
``scripts/check_metrics_names.py`` (which remains as a thin CLI shim).

These are *runtime* checks (``requires_runtime = True``): they import the
live registry, exercise the paged-KV pool, and round-trip the federation
path — so they run from the full-repo suite (CLI and tier-1 wrapper), not
over synthetic fixture projects.

Pass catalog (the original scripts/check_metrics_names.py passes 1-8):

- DL010 registry      — every registered family name matches
  ``dnet_[a-z0-9_]+`` and carries a help string
- DL011 source-scan   — literal ``counter(/gauge(/histogram(`` calls in the
  tree conform even when registered lazily
- DL012 federation    — two-node relabel/merge round trip re-parses, one
  ``node`` label per sample, required families present
- DL013 paged-pool    — alloc/share/COW/release script keeps the block
  books balanced and the gauges honest
- DL014 chaos-points  — chaos injection points <-> pre-touched series, both
  directions
- DL015 admission     — reject-reason / deadline-stage labels <-> declared
  enums, both directions
- DL016 membership    — stale-epoch kinds / recovery outcomes <-> declared
  enums, both directions
- DL017 attribution   — host spans (series AND call sites) / decode
  dispatch widths / token sources / jit fns / device-mem kinds /
  turn-around device states / drivers-turn outcomes <-> declared enums,
  both directions
- DL018 sanitizer     — dsan check codes / zombie-thread kinds <->
  declared enums, both directions (pass 9)
- DL019 scheduler     — sched queue states / batch kinds / preemption
  reasons <-> declared enums, both directions (pass 10)
- DL020 jit-coverage  — instrument_jit call-site name literals <->
  obs/phases.py JIT_FNS, both directions (pass 11): a new jitted entry
  point cannot ship uninstrumented under a stray label, and a declared
  name cannot outlive its last call site (a stale series on the compile
  dashboards)
- DL026 wire          — wire-pipeline dir labels <-> obs/phases.py
  WIRE_DIRS both directions + the dnet_wire_* families required
  (pass 12; DL021-DL025 are the flow-sensitive tier, analysis/flow/)
- the TP collective op labels cross-checked against obs/phases.py TP_OPS
  both directions + the dnet_tp_* families required (pass 13)
- DL028 critical-path — request-segment labels <-> obs/phases.py
  REQUEST_SEGMENTS both directions, the attribution map + trace track
  routing (obs/critical_path.py, obs/trace.py) consistent with the
  declared segment enum, and the segment-histogram + tick-record
  families required (pass 14)
- DL030 events        — wide-event name labels <-> obs/phases.py
  EVENT_NAMES both directions (pass 15; DL029 is the static
  logging-hygiene check, checks_logging.py)
"""

from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import Iterable

from dnet_tpu.analysis.core import Check, Finding, Project

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:  # runnable via the scripts/ shim
    sys.path.insert(0, str(REPO))

# metric-registration calls with a literal name; help must be the next
# argument and a non-empty string literal
_CALL_RE = re.compile(
    r"""\.\s*(counter|gauge|histogram)\(\s*
        (?P<q>['"])(?P<name>[^'"]+)(?P=q)\s*,\s*
        (?P<rest>.{0,120})""",
    re.VERBOSE | re.DOTALL,
)
_HELP_RE = re.compile(r"""^(?P<q>['"])(?P<help>[^'"]*)""")

_SCAN_DIRS = ("dnet_tpu", "scripts")
_SCAN_FILES = ("bench.py",)


def _check_name(name: str, where: str, errors: list) -> None:
    from dnet_tpu.obs import METRIC_NAME_RE

    if not METRIC_NAME_RE.match(name):
        errors.append(
            f"{where}: metric name {name!r} does not match "
            f"{METRIC_NAME_RE.pattern}"
        )


def check_registry(errors: list) -> int:
    from dnet_tpu.obs import get_registry

    fams = get_registry().families()
    for name, fam in fams.items():
        _check_name(name, "registry", errors)
        if not fam.help.strip():
            errors.append(f"registry: metric {name} has an empty help string")
    return len(fams)


def _scan_paths() -> list:
    """The source tree both literal-scanning passes walk: the standalone
    entry points plus every .py under the scanned dirs — ONE definition,
    so the passes can never silently diverge on the file set."""
    files = [REPO / f for f in _SCAN_FILES]
    for d in _SCAN_DIRS:
        files.extend(sorted((REPO / d).rglob("*.py")))
    return files


def check_sources(errors: list) -> int:
    n = 0
    for path in _scan_paths():
        if not path.is_file():
            continue
        text = path.read_text()
        for m in _CALL_RE.finditer(text):
            name = m.group("name")
            if not name.startswith("dnet_"):
                continue  # not one of ours (e.g. a generic helper call)
            n += 1
            where = f"{path.relative_to(REPO)}"
            _check_name(name, where, errors)
            hm = _HELP_RE.match(m.group("rest").lstrip())
            if hm is None or not hm.group("help").strip():
                errors.append(
                    f"{where}: metric {name} registered without a literal "
                    f"non-empty help string"
                )
    return n


# families the cluster observability surface registers; their absence means
# a refactor silently dropped a series dashboards/alerts depend on
_REQUIRED_FAMILIES = (
    "dnet_slo_ttft_p95_ms",
    "dnet_slo_decode_p95_ms",
    "dnet_slo_availability",
    "dnet_slo_burning",
    "dnet_prefix_refill_total",
    "dnet_federation_scrape_ok",
    # paged KV pool (dnet_tpu/kv/) — capacity dashboards and the
    # backpressure alert depend on these
    "dnet_kv_blocks_used",
    "dnet_kv_blocks_free",
    "dnet_kv_pool_blocks",
    "dnet_kv_cow_copies_total",
    "dnet_kv_prefix_shared_blocks_total",
    "dnet_kv_admission_rejected_total",
    # resilience (dnet_tpu/resilience/) — the retry/resume dashboards and
    # the chaos-coverage lint (pass 5) depend on these
    "dnet_rpc_retries_total",
    "dnet_stream_reopens_total",
    "dnet_request_resumed_total",
    "dnet_resume_replay_tokens_total",
    "dnet_chaos_injected_total",
    # admission / overload survival (dnet_tpu/admission/) — the shed-rate
    # alert, drain runbook, and the label cross-check (pass 6) depend on
    # these
    "dnet_admit_queue_depth",
    "dnet_admit_inflight",
    "dnet_admit_admitted_total",
    "dnet_admit_wait_ms",
    "dnet_admit_rejected_total",
    "dnet_deadline_exceeded_total",
    "dnet_cancel_propagated_total",
    "dnet_drain_state",
    "dnet_shard_outq_dropped_total",
    # elastic ring membership (dnet_tpu/membership/) — the epoch-fence
    # dashboards, recovery alert, and the label cross-check (pass 7)
    # depend on these
    "dnet_topology_epoch",
    "dnet_stale_epoch_rejected_total",
    "dnet_recovery_total",
    "dnet_recovery_duration_seconds",
    "dnet_shard_rejoins_total",
    # performance attribution (obs/phases.py, obs/jit.py) — the loadgen
    # report's span/JIT/memory sections, the benchmark's per-layer readers
    # and the label cross-check (pass 8) depend on these
    "dnet_span_ms",
    "dnet_decode_dispatch_total",
    "dnet_decode_slot_steps_total",
    "dnet_decode_lane_steps_total",
    "dnet_decode_tokens_total",
    "dnet_decode_buffer_dropped_total",
    # the share of its held experts a decode step reads (core/batch.py;
    # benchmarks/layer_metrics/moe_experts_visited_in_window.json)
    "dnet_moe_experts_visited_total",
    "dnet_jit_compiles_total",
    "dnet_jit_compile_ms",
    "dnet_device_mem_bytes",
    "dnet_slo_ttft_p99_ms",
    "dnet_slo_decode_p99_ms",
    # runtime sanitizer (dnet_tpu/analysis/runtime/) — the dsan findings
    # dashboard and the zombie-thread alert (pass 9) depend on these
    "dnet_san_findings_total",
    "dnet_san_zombie_threads_total",
    # iteration-level scheduler (dnet_tpu/sched/) — the tick/composition
    # dashboards and the label cross-check (pass 10) depend on these
    "dnet_sched_tick_ms",
    "dnet_sched_batch_tokens",
    "dnet_sched_queue_wait_ms",
    "dnet_sched_prefill_wall_ms",
    "dnet_sched_prefill_ticks",
    "dnet_sched_deliver_wait_ms",
    "dnet_sched_preemptions_total",
    "dnet_sched_queue_depth",
    # overlapped wire pipeline (transport/wire_pipeline.py) — the per-hop
    # codec dashboards, the overlap gauge the BENCH_SERVE reports embed,
    # and the label cross-check (pass 12) depend on these
    "dnet_wire_encode_ms",
    "dnet_wire_decode_ms",
    "dnet_wire_bytes_total",
    "dnet_wire_overlap_ratio",
    # critical-path attribution + scheduler tick flight recorder
    # (obs/critical_path.py, sched/flight.py) — the per-request segment
    # ledgers, /v1/debug/sched, and the label cross-check (pass 14)
    # depend on these
    "dnet_request_segment_ms",
    "dnet_sched_tick_records_total",
    "dnet_sched_tick_budget_used_ratio",
    # structured wide events (obs/events.py) — the event-rate dashboards
    # and the vocabulary cross-check (pass 15) depend on this
    "dnet_events_total",
    # fleet routing (dnet_tpu/fleet/) — the per-replica traffic/failover
    # dashboards and the label cross-check (pass 16) depend on these
    "dnet_fleet_requests_total",
    "dnet_fleet_routed_total",
    "dnet_fleet_affinity_hits_total",
    "dnet_fleet_failovers_total",
    "dnet_fleet_replicas",
)


def check_federation(errors: list) -> int:
    """Pass 3: federate the live exposition with itself under two node ids
    and re-validate the merged document sample by sample."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.obs.federation import _SAMPLE_RE, _family_of, federate

    fams = get_registry().families()
    for req in _REQUIRED_FAMILIES:
        if req not in fams:
            errors.append(f"federation: required family {req} not registered")
    text = get_registry().expose()
    merged, skipped = federate([("api", text), ("shard-0", text)])
    for line in skipped:
        errors.append(f"federation: dropped unparseable line {line!r}")
    n = 0
    typed: set = set()
    for line in merged.splitlines():
        if line.startswith("# TYPE "):
            name = line.split()[2]
            if name in typed:
                errors.append(f"federation: duplicate TYPE for {name}")
            typed.add(name)
            continue
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"federation: emitted unparseable sample {line!r}")
            continue
        n += 1
        _check_name(_family_of(m.group("name")), "federation", errors)
        if line.count('node="') != 1:
            errors.append(
                f"federation: sample must carry exactly one node label: "
                f"{line!r}"
            )
    return n


def check_paged_conservation(errors: list) -> int:
    """Pass 4: exercise the paged KV pool through an alloc / share / COW /
    table-release / prefix-eviction script and assert the books balance at
    every step — used + free == pool (shared blocks counted once), the
    free list stays duplicate-free and disjoint, refcounts match holders,
    and the gauges report exactly what the pool says."""
    from dnet_tpu.kv import BlockPool, KVPoolExhausted, PagedKVConfig, PageTable
    from dnet_tpu.obs import metric

    pool = BlockPool(PagedKVConfig(block_tokens=8, pool_blocks=12))
    steps = 0

    def audit(holders):
        nonlocal steps
        steps += 1
        try:
            pool.check_conservation(holders)
        except AssertionError as exc:
            errors.append(f"paged-conservation step {steps}: {exc}")
            return
        used = metric("dnet_kv_blocks_used").labels(kind="full").value
        free = metric("dnet_kv_blocks_free").labels(kind="full").value
        if (used, free) != (pool.used, pool.free):
            errors.append(
                f"paged-conservation step {steps}: gauges ({used}, {free}) "
                f"!= pool ({pool.used}, {pool.free})"
            )

    t1, t2 = PageTable(), PageTable()
    entry = pool.alloc(2)  # a prefix entry's blocks
    audit([entry])
    pool.ensure(t1, 20)  # 3 blocks
    audit([entry, t1.blocks])
    t2.blocks.extend(pool.share(entry))  # adoption aliases the entry
    pool.ensure(t2, 30)  # grows past the shared run
    audit([entry, t1.blocks, entry, t2.blocks[2:]])
    old = t2.blocks[1]
    t2.blocks[1] = pool.cow(old)  # diverge mid-run
    audit([entry, t1.blocks, [entry[0]], t2.blocks[1:]])
    try:
        pool.alloc(pool.free + 1)
        errors.append("paged-conservation: overdraw did not raise")
    except KVPoolExhausted:
        pass
    audit([entry, t1.blocks, [entry[0]], t2.blocks[1:]])
    pool.release_table(t1)
    pool.release_table(t2)
    pool.free_blocks(entry)  # prefix eviction
    audit([])
    if pool.used != 0 or pool.free != pool.total:
        errors.append(
            f"paged-conservation: end state leaks ({pool.used} used, "
            f"{pool.free}/{pool.total} free)"
        )
    return steps


def check_chaos_points(errors: list) -> int:
    """Pass 5: every chaos injection point declared in
    dnet_tpu/resilience/chaos.py must have a pre-touched
    dnet_chaos_injected_total{point=} series — a new point cannot ship
    without its observability, and a renamed point cannot strand a stale
    label."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.resilience.chaos import INJECTION_POINTS

    text = get_registry().expose()
    n = 0
    for point in INJECTION_POINTS:
        n += 1
        if f'dnet_chaos_injected_total{{point="{point}"}}' not in text:
            errors.append(
                f"chaos: injection point {point!r} has no "
                f"dnet_chaos_injected_total label (pre-touch it in "
                f"dnet_tpu.obs._register_core)"
            )
    # reverse direction: no exposed point label without a declaration
    for m in re.finditer(
        r'dnet_chaos_injected_total\{point="([^"]+)"\}', text
    ):
        if m.group(1) not in INJECTION_POINTS:
            errors.append(
                f"chaos: exposed point label {m.group(1)!r} is not declared "
                f"in chaos.INJECTION_POINTS"
            )
    return n


def check_chaos_kinds(errors: list) -> int:
    """Pass 5b: grammar self-test — every declared chaos KIND must parse
    at every declared point (a kind added to the docs/campaign without a
    parser, or a parser branch dropped in a refactor, fails here, not in
    the middle of a chaos campaign)."""
    from dnet_tpu.resilience.chaos import INJECTION_POINTS, KINDS, ChaosInjector

    sample = {
        "error": "0.5", "error_at": "3+5", "delay": "10ms", "partition": "2+3",
    }
    n = 0
    for kind in KINDS:
        n += 1
        if kind not in sample:
            errors.append(
                f"chaos: kind {kind!r} has no grammar self-test sample "
                f"(add one to check_chaos_kinds)"
            )
            continue
        for point in INJECTION_POINTS:
            try:
                ChaosInjector(f"{point}:{kind}:{sample[kind]}", seed=1)
            except ValueError as exc:
                errors.append(
                    f"chaos: declared kind {kind!r} fails to parse at "
                    f"point {point!r}: {exc}"
                )
    return n


def _cross_check_labels(
    errors: list, text: str, family: str, label: str, declared, where: str
) -> int:
    """Exposed `family{label=...}` series must match `declared` EXACTLY in
    both directions: every declared value pre-touched, no stray label."""
    n = 0
    scope = where.split(".", 1)[0]
    for value in declared:
        n += 1
        if f'{family}{{{label}="{value}"}}' not in text:
            errors.append(
                f"{scope}: {where} value {value!r} has no {family} "
                f"series (pre-touch it in dnet_tpu.obs._register_core)"
            )
    for m in re.finditer(rf'{family}\{{{label}="([^"]+)"\}}', text):
        if m.group(1) not in declared:
            errors.append(
                f"{scope}: exposed {family} {label} label "
                f"{m.group(1)!r} is not declared in {where}"
            )
    return n


def check_admission_labels(errors: list) -> int:
    """Pass 6: the admission surface's labeled families must agree with
    the declared enums (dnet_tpu/admission/reasons.py) both ways — a new
    reject reason or deadline stage cannot ship without its series, and a
    renamed one cannot strand a stale label on dashboards."""
    from dnet_tpu.admission.reasons import DEADLINE_STAGES, REJECT_REASONS
    from dnet_tpu.obs import get_registry

    text = get_registry().expose()
    n = _cross_check_labels(
        errors, text, "dnet_admit_rejected_total", "reason",
        REJECT_REASONS, "admission.reasons.REJECT_REASONS",
    )
    n += _cross_check_labels(
        errors, text, "dnet_deadline_exceeded_total", "stage",
        DEADLINE_STAGES, "admission.reasons.DEADLINE_STAGES",
    )
    return n


def check_membership_labels(errors: list) -> int:
    """Pass 7: the membership surface's labeled families must agree with
    the declared enums (dnet_tpu/membership/epoch.py) both ways — a new
    stale-epoch kind or recovery outcome cannot ship without its series,
    and a renamed one cannot strand a stale label on dashboards.  Same
    pattern as passes 5-6."""
    from dnet_tpu.membership.epoch import RECOVERY_OUTCOMES, STALE_EPOCH_KINDS
    from dnet_tpu.obs import get_registry

    text = get_registry().expose()
    n = _cross_check_labels(
        errors, text, "dnet_stale_epoch_rejected_total", "kind",
        STALE_EPOCH_KINDS, "membership.epoch.STALE_EPOCH_KINDS",
    )
    n += _cross_check_labels(
        errors, text, "dnet_recovery_total", "outcome",
        RECOVERY_OUTCOMES, "membership.epoch.RECOVERY_OUTCOMES",
    )
    return n


def check_attribution_labels(errors: list) -> int:
    """Pass 8: the performance-attribution families must agree with the
    declared enums (dnet_tpu/obs/phases.py) both ways.  Histogram families
    expose per-label `_bucket`/`_sum`/`_count` series, so presence is
    checked on `_count` and strays on any exposition suffix.  Also: every
    `span("...")` / `observe_span("...")` literal in the tree names a
    declared host span and every declared span has a call site (by
    constant or literal)."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.obs import phases
    from dnet_tpu.obs.phases import (
        DECODE_TOKEN_SOURCES,
        DEVICE_MEM_KINDS,
        HOST_SPANS,
        JIT_FNS,
    )

    text = get_registry().expose()
    n = 0
    for name in HOST_SPANS:
        n += 1
        if f'dnet_span_ms_count{{span="{name}"}}' not in text:
            errors.append(
                f"attribution: obs.phases.HOST_SPANS value {name!r} has "
                f"no dnet_span_ms series (pre-touch it in "
                f"dnet_tpu.obs._register_core)"
            )
    for m in re.finditer(
        r'dnet_span_ms(?:_bucket|_sum|_count)\{span="([^"]+)"', text
    ):
        if m.group(1) not in HOST_SPANS:
            errors.append(
                f"attribution: exposed dnet_span_ms span label "
                f"{m.group(1)!r} is not declared in obs.phases.HOST_SPANS"
            )
    # call sites: span(SPAN_X, ...) by constant, or by literal
    const_of = {
        k: v for k, v in vars(phases).items()
        if k.startswith("SPAN_") and isinstance(v, str)
    }
    used = set()
    site_re = re.compile(
        r"""\b(?:span|observe_span)\(\s*(?:(?P<c>SPAN_[A-Z_]+)|"""
        r"""(?P<q>['"])(?P<lit>dnet\.[^'"]+)(?P=q))"""
    )
    for path in _scan_paths():
        if not path.is_file() or path.name == "metrics_checks.py":
            continue
        for m in site_re.finditer(path.read_text()):
            name = const_of.get(m.group("c")) if m.group("c") else m.group("lit")
            n += 1
            if name not in HOST_SPANS:
                errors.append(
                    f"attribution: {path.relative_to(REPO)} opens span "
                    f"{m.group(0)!r}, not declared in obs.phases.HOST_SPANS"
                )
            else:
                used.add(name)
    for name in HOST_SPANS:
        if name not in used:
            errors.append(
                f"attribution: obs.phases.HOST_SPANS declares {name!r} but "
                f"no span()/observe_span() call site opens it"
            )
    n += _cross_check_labels(
        errors, text, "dnet_decode_tokens_total", "source",
        DECODE_TOKEN_SOURCES, "obs.phases.DECODE_TOKEN_SOURCES",
    )
    from dnet_tpu.obs.phases import KV_KINDS, MOE_HELD, MOE_PATHS, RETENTION_PHASES

    # the block families carry the kinds that HAVE blocks; the state kind
    # (KV_KIND_STATE) keeps its books in dnet_state_slots*
    for fam in ("dnet_kv_blocks_used", "dnet_kv_blocks_free", "dnet_kv_pool_blocks"):
        n += _cross_check_labels(
            errors, text, fam, "kind", KV_KINDS, "obs.phases.KV_KINDS"
        )
    for fam in (
        "dnet_retention_tokens_total", "dnet_gdn_tokens_total", "dnet_mla_tokens_total",
        "dnet_lightning_tokens_total",
    ):
        n += _cross_check_labels(
            errors, text, fam, "phase", RETENTION_PHASES,
            "obs.phases.RETENTION_PHASES",
        )
    from dnet_tpu.obs.phases import SPARSE_BLOCK_STATES, SPARSE_MODES

    n += _cross_check_labels(
        errors, text, "dnet_sparse_blocks_total", "state", SPARSE_BLOCK_STATES,
        "obs.phases.SPARSE_BLOCK_STATES",
    )
    n += _cross_check_labels(
        errors, text, "dnet_sparse_tokens_total", "mode", SPARSE_MODES,
        "obs.phases.SPARSE_MODES",
    )
    n += _cross_check_labels(
        errors, text, "dnet_moe_assignments_total", "held", MOE_HELD,
        "obs.phases.MOE_HELD",
    )
    n += _cross_check_labels(
        errors, text, "dnet_moe_expert_rows_total", "path", MOE_PATHS,
        "obs.phases.MOE_PATHS",
    )
    from dnet_tpu.obs.phases import FLASH_TILE_STATES

    # two labels: the layer's kind x what the flash kernel makes of a tile
    want = {
        f'dnet_flash_tiles_total{{kind="{k}",state="{s}"}}'
        for k in KV_KINDS for s in FLASH_TILE_STATES
    }
    n += len(want)
    for series in sorted(want ^ set(re.findall(r"dnet_flash_tiles_total\{[^}]*\}", text))):
        errors.append(
            f"attribution: {series} is "
            + ("not exposed (pre-touch it in dnet_tpu.obs._register_core)"
               if series in want
               else "exposed, not declared in obs.phases KV_KINDS x FLASH_TILE_STATES")
        )
    from dnet_tpu.obs.phases import DRIVERS_TURN_OUTCOMES, TURN_DEVICE

    # the turn-around between two ticks: a histogram by what the device
    # had to do (every labeled child exposes a _count, so that series
    # stands for the family) and a counter by how the drivers' turn ended
    n += _cross_check_labels(
        errors, text, "dnet_sched_turnaround_ms_count", "device",
        TURN_DEVICE, "obs.phases.TURN_DEVICE",
    )
    n += _cross_check_labels(
        errors, text, "dnet_sched_drivers_turn_total", "outcome",
        DRIVERS_TURN_OUTCOMES, "obs.phases.DRIVERS_TURN_OUTCOMES",
    )
    from dnet_tpu.obs.phases import DRIVER_ASK_ORDERS

    n += _cross_check_labels(
        errors, text, "dnet_api_driver_asks_total", "order",
        DRIVER_ASK_ORDERS, "obs.phases.DRIVER_ASK_ORDERS",
    )
    n += _cross_check_labels(
        errors, text, "dnet_jit_compiles_total", "fn",
        JIT_FNS, "obs.phases.JIT_FNS",
    )
    n += _cross_check_labels(
        errors, text, "dnet_device_mem_bytes", "kind",
        DEVICE_MEM_KINDS, "obs.phases.DEVICE_MEM_KINDS",
    )
    return n


def check_san_labels(errors: list) -> int:
    """Pass 9: the runtime sanitizer's labeled families must agree with
    the declared enums (dnet_tpu/analysis/runtime/domains.py) both ways —
    a new DS check or zombie-able worker thread cannot ship without its
    series, and a renamed one cannot strand a stale label.  Same pattern
    as passes 5-8."""
    from dnet_tpu.analysis.runtime.domains import (
        RUNTIME_CHECK_CODES,
        ZOMBIE_THREAD_KINDS,
    )
    from dnet_tpu.obs import get_registry

    text = get_registry().expose()
    n = _cross_check_labels(
        errors, text, "dnet_san_findings_total", "check",
        RUNTIME_CHECK_CODES, "analysis.runtime.domains.RUNTIME_CHECK_CODES",
    )
    n += _cross_check_labels(
        errors, text, "dnet_san_zombie_threads_total", "thread",
        ZOMBIE_THREAD_KINDS, "analysis.runtime.domains.ZOMBIE_THREAD_KINDS",
    )
    return n


def check_sched_labels(errors: list) -> int:
    """Pass 10: the scheduler's labeled families must agree with the
    declared enums (dnet_tpu/sched/kinds.py) both ways — a new queue
    state, batch kind, or preemption reason cannot ship without its
    series, and a renamed one cannot strand a stale label.  The
    histogram family is checked on its exposition suffixes, like the
    attribution pass."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.sched.kinds import (
        BATCH_KINDS,
        MIXED_TICK_OVERLAP,
        PREEMPT_REASONS,
        QUEUE_STATES,
    )

    text = get_registry().expose()
    n = 0
    for kind in BATCH_KINDS:
        n += 1
        if f'dnet_sched_batch_tokens_count{{kind="{kind}"}}' not in text:
            errors.append(
                f"sched: sched.kinds.BATCH_KINDS value {kind!r} has no "
                f"dnet_sched_batch_tokens series (pre-touch it in "
                f"dnet_tpu.obs._register_core)"
            )
    for m in re.finditer(
        r'dnet_sched_batch_tokens(?:_bucket|_sum|_count)\{kind="([^"]+)"',
        text,
    ):
        if m.group(1) not in BATCH_KINDS:
            errors.append(
                f"sched: exposed dnet_sched_batch_tokens kind label "
                f"{m.group(1)!r} is not declared in sched.kinds.BATCH_KINDS"
            )
    n += _cross_check_labels(
        errors, text, "dnet_sched_preemptions_total", "reason",
        PREEMPT_REASONS, "sched.kinds.PREEMPT_REASONS",
    )
    n += _cross_check_labels(
        errors, text, "dnet_sched_queue_depth", "state",
        QUEUE_STATES, "sched.kinds.QUEUE_STATES",
    )
    n += _cross_check_labels(
        errors, text, "dnet_sched_mixed_ticks_total", "overlapped",
        MIXED_TICK_OVERLAP, "sched.kinds.MIXED_TICK_OVERLAP",
    )
    return n


def check_jit_instrumentation(errors: list) -> int:
    """Pass 11: every `instrument_jit(..., "name")` call site in the tree
    must use a name declared in obs/phases.py JIT_FNS, and every declared
    name must have at least one call site — both directions, resolved by
    AST so nested jax.jit(...) argument parens can't confuse a regex.  A
    non-literal name argument is itself a violation (the contract is
    lintable only over literals)."""
    import ast

    from dnet_tpu.obs.phases import JIT_FNS

    seen: dict = {}
    n = 0
    for path in _scan_paths():
        if not path.is_file():
            continue
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError as exc:
            errors.append(f"jit-coverage: {path.relative_to(REPO)} "
                          f"unparseable: {exc}")
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else getattr(fn, "id", "")
            if name != "instrument_jit":
                continue
            where = f"{path.relative_to(REPO)}:{node.lineno}"
            n += 1
            label = node.args[1] if len(node.args) > 1 else None
            if label is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        label = kw.value
            if not (isinstance(label, ast.Constant) and isinstance(label.value, str)):
                errors.append(
                    f"jit-coverage: {where} passes a non-literal jit fn "
                    f"name (the JIT_FNS contract is checked over literals)"
                )
                continue
            seen.setdefault(label.value, []).append(where)
            if label.value not in JIT_FNS:
                errors.append(
                    f"jit-coverage: {where} instruments undeclared jit fn "
                    f"{label.value!r} (declare it in obs.phases.JIT_FNS)"
                )
    for declared in JIT_FNS:
        if declared not in seen:
            errors.append(
                f"jit-coverage: obs.phases.JIT_FNS declares {declared!r} "
                f"but no instrument_jit call site uses it (remove the "
                f"stale label or restore its entry point)"
            )
    return n


def check_wire_labels(errors: list) -> int:
    """Pass 12: the wire pipeline's labeled family must agree with the
    declared dir enum (dnet_tpu/obs/phases.py WIRE_DIRS) both ways, and
    the dnet_wire_* families must exist — a renamed direction cannot
    strand a stale label, and a refactor cannot silently drop the series
    the BENCH_SERVE wire meta and overlap dashboards read."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.obs.phases import WIRE_DIRS

    text = get_registry().expose()
    n = _cross_check_labels(
        errors, text, "dnet_wire_bytes_total", "dir",
        WIRE_DIRS, "obs.phases.WIRE_DIRS",
    )
    fams = get_registry().families()
    for req in ("dnet_wire_encode_ms", "dnet_wire_decode_ms",
                "dnet_wire_overlap_ratio"):
        n += 1
        if req not in fams:
            errors.append(f"wire: required family {req} not registered")
    return n


def check_tp_labels(errors: list) -> int:
    """Pass 13: the TP collective families must agree with the declared
    op enum (dnet_tpu/obs/phases.py TP_OPS) both ways — a renamed or new
    collective op cannot strand a stale label or ship without its series
    — and the dnet_tp_* families the TP parity tests and BENCH_SERVE
    meta.tp read must exist."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.obs.phases import TP_OPS

    text = get_registry().expose()
    n = 0
    for op in TP_OPS:  # histogram children expose _bucket/_sum/_count
        n += 1
        if f'dnet_tp_collective_ms_count{{op="{op}"}}' not in text:
            errors.append(
                f"obs: obs.phases.TP_OPS value {op!r} has no "
                f"dnet_tp_collective_ms series (pre-touch it in "
                f"dnet_tpu.obs._register_core)"
            )
    for m in re.finditer(
        r'dnet_tp_collective_ms(?:_bucket|_sum|_count)\{op="([^"]+)"', text
    ):
        if m.group(1) not in TP_OPS:
            errors.append(
                f"obs: exposed dnet_tp_collective_ms op label "
                f"{m.group(1)!r} is not declared in obs.phases.TP_OPS"
            )
    n += _cross_check_labels(
        errors, text, "dnet_tp_collective_bytes_total", "op",
        TP_OPS, "obs.phases.TP_OPS",
    )
    fams = get_registry().families()
    n += 1
    if "dnet_tp_degree" not in fams:
        errors.append("tp: required family dnet_tp_degree not registered")
    return n


def check_request_segment_labels(errors: list) -> int:
    """Pass 14: the critical-path surface must stay self-consistent with
    the declared segment enum (obs/phases.py REQUEST_SEGMENTS), both
    directions:

    - every declared segment has a pre-touched dnet_request_segment_ms
      series, and no exposed segment label is undeclared;
    - every obs/critical_path.py SPAN_SEGMENTS target is a declared
      segment, and every declared segment except `other` (the residual
      bucket) is reachable from at least one span mapping — a segment no
      span can feed is a stale ledger row;
    - the Perfetto track routing (obs/trace.py) only names spans the
      attribution map knows (plus the instant-only flow-rx marker), its
      compute/tx sets are disjoint, and flow arrows only leave tx spans;
    - the tick flight recorder's queue-depth keys are exactly
      sched/kinds.py QUEUE_STATES, so /v1/debug/sched and the
      dnet_sched_queue_depth gauges tell the same story."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.obs import trace as obs_trace
    from dnet_tpu.obs.critical_path import SPAN_SEGMENTS
    from dnet_tpu.obs.phases import REQUEST_SEGMENTS, SEG_OTHER
    from dnet_tpu.sched.flight import TickFlightRecorder
    from dnet_tpu.sched.kinds import QUEUE_STATES

    text = get_registry().expose()
    n = 0
    for seg in REQUEST_SEGMENTS:
        n += 1
        if f'dnet_request_segment_ms_count{{segment="{seg}"}}' not in text:
            errors.append(
                f"critical-path: obs.phases.REQUEST_SEGMENTS value {seg!r} "
                f"has no dnet_request_segment_ms series (pre-touch it in "
                f"dnet_tpu.obs._register_core)"
            )
    for m in re.finditer(
        r'dnet_request_segment_ms(?:_bucket|_sum|_count)\{segment="([^"]+)"',
        text,
    ):
        if m.group(1) not in REQUEST_SEGMENTS:
            errors.append(
                f"critical-path: exposed dnet_request_segment_ms segment "
                f"label {m.group(1)!r} is not declared in "
                f"obs.phases.REQUEST_SEGMENTS"
            )

    mapped_targets = {seg for seg, _prio in SPAN_SEGMENTS.values()}
    for seg in mapped_targets:
        n += 1
        if seg not in REQUEST_SEGMENTS:
            errors.append(
                f"critical-path: SPAN_SEGMENTS maps to {seg!r}, which is "
                f"not declared in obs.phases.REQUEST_SEGMENTS"
            )
    for seg in REQUEST_SEGMENTS:
        if seg != SEG_OTHER and seg not in mapped_targets:
            errors.append(
                f"critical-path: declared segment {seg!r} is unreachable — "
                f"no obs/critical_path.py SPAN_SEGMENTS entry feeds it"
            )

    routed = obs_trace.COMPUTE_SPANS | obs_trace.TX_SPANS
    overlap_names = obs_trace.COMPUTE_SPANS & obs_trace.TX_SPANS
    if overlap_names:
        errors.append(
            f"critical-path: trace track sets overlap: {sorted(overlap_names)}"
        )
    known = set(SPAN_SEGMENTS) | {obs_trace.FLOW_RX_SPAN}
    for name in sorted(routed - known):
        errors.append(
            f"critical-path: obs/trace.py routes span {name!r} to a thread "
            f"track but obs/critical_path.py SPAN_SEGMENTS does not "
            f"attribute it"
        )
    n += len(routed)
    for name in sorted(obs_trace.FLOW_TX_SPANS - obs_trace.TX_SPANS):
        errors.append(
            f"critical-path: flow arrow source {name!r} is not on the "
            f"tx-stage track"
        )

    states = TickFlightRecorder().snapshot()["states"]
    n += 1
    if tuple(states) != tuple(QUEUE_STATES):
        errors.append(
            f"critical-path: tick-record states {states!r} != "
            f"sched.kinds.QUEUE_STATES {tuple(QUEUE_STATES)!r}"
        )
    return n


def check_event_labels(errors: list) -> int:
    """Pass 15: the wide-event vocabulary (obs/phases.py EVENT_NAMES) must
    agree with the dnet_events_total exposition both ways — a new event
    cannot ship without its pre-touched counter series, and a renamed one
    cannot strand a stale name label on dashboards.  log_event() itself
    asserts membership at emit time; this pass catches the drift BEFORE a
    process ever emits."""
    from dnet_tpu.obs import get_registry
    from dnet_tpu.obs.phases import EVENT_NAMES

    text = get_registry().expose()
    return _cross_check_labels(
        errors, text, "dnet_events_total", "name",
        EVENT_NAMES, "obs.phases.EVENT_NAMES",
    )


def check_fleet_labels(errors: list) -> int:
    """Pass 16: the fleet-routing surface must agree with the declared
    enums (fleet/states.py) both ways — a new replica state or routing
    reason cannot ship without its pre-touched series, and a renamed one
    cannot strand a stale label on dashboards.  The `replica` label of
    dnet_fleet_requests_total is deployment-assigned (r0, r1, ...) and
    intentionally NOT enum-checked."""
    from dnet_tpu.fleet.states import REPLICA_STATES, ROUTE_REASONS
    from dnet_tpu.obs import get_registry

    text = get_registry().expose()
    n = _cross_check_labels(
        errors, text, "dnet_fleet_replicas", "state",
        REPLICA_STATES, "fleet.states.REPLICA_STATES",
    )
    n += _cross_check_labels(
        errors, text, "dnet_fleet_routed_total", "reason",
        ROUTE_REASONS, "fleet.states.ROUTE_REASONS",
    )
    return n


def main() -> int:
    """The scripts/check_metrics_names.py CLI contract, verbatim: exit 0
    and the 'ok: ...' summary on clean, the FAIL lines and exit 1 on
    violations (tests/test_metrics_lint.py asserts this format)."""
    errors: list[str] = []
    n_reg = check_registry(errors)
    n_src = check_sources(errors)
    n_fed = check_federation(errors)
    n_pool = check_paged_conservation(errors)
    n_chaos = check_chaos_points(errors)
    n_kinds = check_chaos_kinds(errors)
    n_admit = check_admission_labels(errors)
    n_member = check_membership_labels(errors)
    n_attr = check_attribution_labels(errors)
    n_san = check_san_labels(errors)
    n_sched = check_sched_labels(errors)
    n_jit = check_jit_instrumentation(errors)
    n_wire = check_wire_labels(errors)
    n_tp = check_tp_labels(errors)
    n_seg = check_request_segment_labels(errors)
    n_evt = check_event_labels(errors)
    n_fleet = check_fleet_labels(errors)
    if errors:
        for e in errors:
            print(f"FAIL {e}")
        return 1
    print(f"ok: {n_reg} registered families, {n_src} source-literal "
          f"registrations, {n_fed} federated samples, {n_pool} paged-pool "
          f"audits, {n_chaos} chaos points, {n_kinds} chaos kinds, "
          f"{n_admit} admission labels, "
          f"{n_member} membership labels, {n_attr} attribution labels, "
          f"{n_san} sanitizer labels, {n_sched} scheduler labels, "
          f"{n_jit} jit call sites, {n_wire} wire labels, "
          f"{n_tp} tp labels, {n_seg} critical-path labels, "
          f"{n_evt} event labels, {n_fleet} fleet labels, all conform")
    return 0


# ---- framework wrappers ---------------------------------------------------


class _MetricsCheck(Check):
    """Adapter: one legacy errors-list pass -> one DL01x check."""

    requires_runtime = True
    severity = "error"
    pass_name = ""  # looked up in this module at run time

    def run_project(self, project: Project) -> Iterable[Finding]:
        errors: list = []
        fn = globals()[self.pass_name]
        try:
            fn(errors)
        except Exception as exc:  # a crashed pass is itself a finding
            yield self.finding(
                "dnet_tpu/analysis/metrics_checks.py", 0,
                f"{self.pass_name} crashed: {type(exc).__name__}: {exc}",
            )
            return
        for e in errors:
            yield self.finding("dnet_tpu/analysis/metrics_checks.py", 0, e)


class MetricRegistryNames(_MetricsCheck):
    code = "DL010"
    name = "metric-registry-names"
    description = "registered families match dnet_[a-z0-9_]+ with help text"
    pass_name = "check_registry"


class MetricSourceLiterals(_MetricsCheck):
    code = "DL011"
    name = "metric-source-literals"
    description = "literal counter/gauge/histogram registrations conform"
    pass_name = "check_sources"


class FederationRoundTrip(_MetricsCheck):
    code = "DL012"
    name = "federation-round-trip"
    description = "two-node relabel/merge re-parses; required families exist"
    pass_name = "check_federation"


class PagedPoolConservation(_MetricsCheck):
    code = "DL013"
    name = "paged-pool-conservation"
    description = "block books balance through alloc/share/COW/release"
    pass_name = "check_paged_conservation"


class ChaosPointCoverage(_MetricsCheck):
    code = "DL014"
    name = "chaos-point-coverage"
    description = "chaos injection points <-> pre-touched series, both ways"
    pass_name = "check_chaos_points"


class AdmissionLabelContract(_MetricsCheck):
    code = "DL015"
    name = "admission-label-contract"
    description = "reject/deadline labels <-> declared enums, both ways"
    pass_name = "check_admission_labels"


class MembershipLabelContract(_MetricsCheck):
    code = "DL016"
    name = "membership-label-contract"
    description = "epoch/recovery labels <-> declared enums, both ways"
    pass_name = "check_membership_labels"


class AttributionLabelContract(_MetricsCheck):
    code = "DL017"
    name = "attribution-label-contract"
    description = "phase/jit/mem labels <-> declared enums, both ways"
    pass_name = "check_attribution_labels"


class SanLabelContract(_MetricsCheck):
    code = "DL018"
    name = "san-label-contract"
    description = "dsan check/zombie labels <-> declared enums, both ways"
    pass_name = "check_san_labels"


class SchedLabelContract(_MetricsCheck):
    code = "DL019"
    name = "sched-label-contract"
    description = "sched state/kind/reason labels <-> declared enums, both ways"
    pass_name = "check_sched_labels"


class JitInstrumentationContract(_MetricsCheck):
    code = "DL020"
    name = "jit-instrumentation-contract"
    description = "instrument_jit call-site names <-> JIT_FNS, both ways"
    pass_name = "check_jit_instrumentation"


class WireLabelContract(_MetricsCheck):
    # DL021-DL025 belong to the flow-sensitive tier (analysis/flow/)
    code = "DL026"
    name = "wire-label-contract"
    description = "wire dir labels <-> WIRE_DIRS + dnet_wire_* families exist"
    pass_name = "check_wire_labels"


class TpLabelContract(_MetricsCheck):
    code = "DL027"
    name = "tp-label-contract"
    description = "tp collective op labels <-> TP_OPS + dnet_tp_* families exist"
    pass_name = "check_tp_labels"


class RequestSegmentContract(_MetricsCheck):
    code = "DL028"
    name = "request-segment-contract"
    description = (
        "segment labels <-> REQUEST_SEGMENTS + trace tracks consistent"
    )
    pass_name = "check_request_segment_labels"


class EventLabelContract(_MetricsCheck):
    # DL029 is the static logging-hygiene check (checks_logging.py)
    code = "DL030"
    name = "event-label-contract"
    description = "wide-event name labels <-> EVENT_NAMES, both ways"
    pass_name = "check_event_labels"


class FleetLabelContract(_MetricsCheck):
    code = "DL031"
    name = "fleet-label-contract"
    description = "fleet state/reason labels <-> declared enums, both ways"
    pass_name = "check_fleet_labels"


class ChaosKindGrammar(_MetricsCheck):
    code = "DL032"
    name = "chaos-kind-grammar"
    description = "every declared chaos kind parses at every point"
    pass_name = "check_chaos_kinds"


METRICS_CHECKS = [
    MetricRegistryNames(),
    MetricSourceLiterals(),
    FederationRoundTrip(),
    PagedPoolConservation(),
    ChaosPointCoverage(),
    AdmissionLabelContract(),
    MembershipLabelContract(),
    AttributionLabelContract(),
    SanLabelContract(),
    SchedLabelContract(),
    JitInstrumentationContract(),
    WireLabelContract(),
    TpLabelContract(),
    RequestSegmentContract(),
    EventLabelContract(),
    FleetLabelContract(),
    ChaosKindGrammar(),
]
