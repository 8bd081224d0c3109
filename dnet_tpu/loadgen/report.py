"""Load-run aggregation: outcome rows -> the BENCH_SERVE report.

Report semantics, pinned by tests/subsystems/test_loadgen.py:

- **Goodput counts 200-completed requests only.**  Shed rows (429/503/504)
  and failed rows contribute to the shed/failure breakdowns, never to
  goodput; requests scheduled inside the warmup window are excluded from
  every aggregate (they exist to absorb compiles and cache fills).
- **Percentiles are nearest-rank** over client-observed samples (TTFT,
  inter-token latency, E2E) — the same convention as obs/slo.py, so a
  report percentile and a live gauge are the same statistic over two
  vantage points.
- **Cross-validation, not duplication**: the report embeds the server's
  live `dnet_slo_*` values (and burn state) next to its own client-side
  numbers plus the relative gap, so a disagreement — a broken gauge, an
  unmeasured queue — is visible in the artifact itself.
- The decode-phase and JIT summaries are DELTAS of the server's
  `/metrics` exposition bracketing the run, so a long-lived server's
  history cannot pollute one run's attribution.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence

from dnet_tpu.loadgen.client import RequestOutcome
from dnet_tpu.loadgen.workload import WorkloadSpec
from dnet_tpu.obs.phases import (
    DECODE_CHILD_SPANS,
    DEVICE_MEM_KINDS,
    REQUEST_SEGMENTS,
)
from dnet_tpu.obs.slo import nearest_rank

# one Prometheus v0.0.4 sample line: name{labels} value  (labels optional)
_SAMPLE_RE = re.compile(
    r"^(?P<name>[A-Za-z_:][A-Za-z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})?\s+(?P<value>[^\s]+)\s*$"
)


def parse_prometheus(text: str) -> Dict[str, float]:
    """Exposition text -> {'name{labels}': value} (labels verbatim)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            continue
        try:
            value = float(m.group("value"))
        except ValueError:
            continue
        out[m.group("name") + (m.group("labels") or "")] = value
    return out


def metric_delta(
    after: Dict[str, float], before: Optional[Dict[str, float]], key: str
) -> float:
    """after[key] - before[key] (missing keys read as 0)."""
    return after.get(key, 0.0) - (before or {}).get(key, 0.0)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-quantile (0..1); 0.0 on an empty sample.  THE same
    implementation as the live `dnet_slo_*` windows (obs/slo.py
    nearest_rank), which is what makes slo.cross_check a like-for-like
    comparison."""
    return nearest_rank(list(values), q)


def _latency_summary(values: List[float]) -> dict:
    return {
        "n": len(values),
        "mean_ms": round(sum(values) / len(values), 3) if values else 0.0,
        "p50_ms": round(percentile(values, 0.50), 3),
        "p95_ms": round(percentile(values, 0.95), 3),
        "p99_ms": round(percentile(values, 0.99), 3),
    }


def _phase_summary(
    after: Dict[str, float], before: Optional[Dict[str, float]]
) -> dict:
    """The batched decode dispatch's host spans (dnet_span_ms, the children
    of dnet.tick.decode: obs/phases.py DECODE_CHILD_SPANS) + the parent
    dnet_decode_step_ms as deltas over the run: what the host did around a
    decode dispatch, and how much of the parent the spans account for
    (`coverage`).  Nothing is fenced: `launch` is an enqueue, `readback`
    is the host blocked until the device finished the dispatch."""
    phases = {}
    phase_sum = 0.0
    for name in DECODE_CHILD_SPANS:
        s = metric_delta(after, before, f'dnet_span_ms_sum{{span="{name}"}}')
        n = metric_delta(after, before, f'dnet_span_ms_count{{span="{name}"}}')
        phase_sum += s
        phases[name] = {
            "sum_ms": round(s, 3),
            "count": int(n),
            "mean_ms": round(s / n, 3) if n else 0.0,
        }
    parent_sum = metric_delta(after, before, "dnet_decode_step_ms_sum")
    parent_n = metric_delta(after, before, "dnet_decode_step_ms_count")
    return {
        "phases": phases,
        # count is TOKENS served (the family's per-token amortization
        # convention); the spans' counts are decode_batch calls
        "decode_step": {
            "sum_ms": round(parent_sum, 3),
            "count": int(parent_n),
        },
        # fraction of the dispatches' wall time the spans explain; above 1
        # by the prepare time of calls answered from the engine's buffer
        # alone, 0 off the batched path
        "coverage": round(phase_sum / parent_sum, 4) if parent_sum else 0.0,
    }


def _jit_summary(
    after: Dict[str, float], before: Optional[Dict[str, float]]
) -> dict:
    compiles: Dict[str, int] = {}
    for key, val in after.items():
        m = re.match(r'dnet_jit_compiles_total\{fn="([^"]+)"\}$', key)
        if m:
            d = val - (before or {}).get(key, 0.0)
            if d:
                compiles[m.group(1)] = int(d)
    return {
        "compiles": compiles,
        "compile_ms_sum": round(
            metric_delta(after, before, "dnet_jit_compile_ms_sum"), 1
        ),
        "compile_count": int(
            metric_delta(after, before, "dnet_jit_compile_ms_count")
        ),
    }


def _device_mem(after: Dict[str, float]) -> dict:
    return {
        kind: after.get(f'dnet_device_mem_bytes{{kind="{kind}"}}', 0.0)
        for kind in DEVICE_MEM_KINDS
    }


def _critical_path_summary(completed: List[RequestOutcome]) -> dict:
    """Aggregate the per-request segment ledgers (obs/critical_path.py)
    carried by profile=true final chunks: per-segment mean/p95 over the
    completed rows, plus which segment DOMINATED each request — the
    run-level answer to "where did the latency go"."""
    ledgers = [o.critical_path for o in completed if o.critical_path]
    segments = {}
    for seg in REQUEST_SEGMENTS:
        vals = [float((lg.get("segments_ms") or {}).get(seg, 0.0))
                for lg in ledgers]
        segments[seg] = {
            "mean_ms": round(sum(vals) / len(vals), 3) if vals else 0.0,
            "p95_ms": round(percentile(vals, 0.95), 3),
            "sum_ms": round(sum(vals), 3),
        }
    dominant: Dict[str, int] = {}
    for lg in ledgers:
        seg = lg.get("dominant") or "other"
        dominant[seg] = dominant.get(seg, 0) + 1
    coverages = [float(lg.get("coverage", 0.0)) for lg in ledgers]
    return {
        "requests": len(ledgers),
        "segments": segments,
        "dominant": dominant,
        "coverage_mean": (
            round(sum(coverages) / len(coverages), 4) if coverages else 0.0
        ),
    }


def _fleet_summary(
    measured: List[RequestOutcome],
    completed: List[RequestOutcome],
    shed: List[RequestOutcome],
    failed: List[RequestOutcome],
    window_s: float,
    after: Optional[Dict[str, float]],
    before: Optional[Dict[str, float]],
) -> Optional[dict]:
    """Per-replica breakdown of a fleet-routed run (ISSUE: fleet section).

    Rows are attributed via the `x-dnet-replica` header the front door
    stamps; the routing counters (`dnet_fleet_*`) ride next to them so a
    disagreement between header attribution and the router's own ledger
    is visible in the artifact.  Returns None when the run never touched
    a fleet (no row carries a replica and no fleet counter moved) so
    single-ring reports stay byte-identical.
    """
    replicas = sorted({o.replica for o in measured if o.replica})
    counters = {}
    if after is not None:
        for key in ("affinity_hits", "failovers"):
            d = metric_delta(after, before, f"dnet_fleet_{key}_total")
            if d:
                counters[key] = int(d)
        for reason in ("affinity", "least_loaded", "failover"):
            d = metric_delta(
                after, before,
                f'dnet_fleet_routed_total{{reason="{reason}"}}',
            )
            if d:
                counters.setdefault("routed_by_reason", {})[reason] = int(d)
    if not replicas and not counters:
        return None
    per_replica = {}
    for rid in replicas:
        mine = [o for o in completed if o.replica == rid]
        tokens = sum(o.tokens_out for o in mine)
        per_replica[rid] = {
            "completed": len(mine),
            "shed": sum(1 for o in shed if o.replica == rid),
            "failed": sum(1 for o in failed if o.replica == rid),
            "tokens_out": tokens,
            "tok_s": round(tokens / window_s, 2),
        }
    routed = sum(
        (counters.get("routed_by_reason") or {}).values()
    )
    hits = counters.get("affinity_hits", 0)
    return {
        "replicas": per_replica,
        "counters": counters,
        # fraction of routed requests served by their sticky replica —
        # the prefix-affinity effectiveness number for the bench gate
        "affinity_hit_rate": round(hits / routed, 4) if routed else 0.0,
    }


def _rel_gap(report_v: float, live_v: float) -> float:
    base = max(abs(live_v), 1e-9)
    return round((report_v - live_v) / base, 4)


def build_report(
    outcomes: Iterable[RequestOutcome],
    *,
    spec: WorkloadSpec,
    duration_s: float,
    health: Optional[dict] = None,
    metrics_before: Optional[Dict[str, float]] = None,
    metrics_after: Optional[Dict[str, float]] = None,
    include_rows: bool = True,
    meta: Optional[dict] = None,
) -> dict:
    rows = sorted(outcomes, key=lambda o: o.index)
    warmup = spec.warmup_s
    measured = [o for o in rows if o.t_sched_s >= warmup]
    completed = [o for o in measured if o.ok and o.status == 200]
    shed = [o for o in measured if o.shed]
    failed = [o for o in measured if not o.ok and not o.shed]

    shed_by_status: Dict[str, int] = {}
    shed_by_reason: Dict[str, int] = {}
    for o in shed:
        shed_by_status[str(o.status)] = shed_by_status.get(str(o.status), 0) + 1
        reason = o.shed_reason or "other"
        shed_by_reason[reason] = shed_by_reason.get(reason, 0) + 1

    window_s = max(duration_s - warmup, 1e-9)
    tokens_out = sum(o.tokens_out for o in completed)
    ttfts = [o.ttft_ms for o in completed]
    itls = [ms for o in completed for ms in o.itl_ms]
    e2es = [o.e2e_ms for o in completed]

    report = {
        "kind": "BENCH_SERVE",
        "spec": spec.as_dict(),
        "duration_s": round(duration_s, 3),
        "measured_window_s": round(window_s, 3),
        "requests": {
            "scheduled": len(rows),
            "measured": len(measured),
            "warmup_excluded": len(rows) - len(measured),
            "completed": len(completed),
            "shed": sum(shed_by_status.values()),
            "failed": len(failed),
            "shed_by_status": shed_by_status,
            "shed_by_reason": shed_by_reason,
            "shed_rate": round(len(shed) / len(measured), 4) if measured else 0.0,
            # server-assigned rids of failed rows: paste one into
            # /v1/debug/events?rid= or /v1/debug/timeline/{rid} for the
            # postmortem (shed-at-the-gate rows never got a rid)
            "failed_rids": [o.rid for o in failed if o.rid],
        },
        # goodput: tokens delivered by COMPLETED requests only, over the
        # measured window — shed and failed rows contribute nothing
        "goodput": {
            "tokens_out": tokens_out,
            "tok_s": round(tokens_out / window_s, 2),
            "requests_per_s": round(len(completed) / window_s, 3),
        },
        "latency_ms": {
            "ttft": _latency_summary(ttfts),
            "tpot": _latency_summary(itls),
            "e2e": _latency_summary(e2es),
        },
        "critical_path": _critical_path_summary(completed),
    }
    # client-observed availability over requests that were ADMITTED (shed
    # rows never enter the server's availability window either — admission
    # rejections happen before the SLO tracker sees the request)
    admitted = len(completed) + len(failed)
    report["availability"] = (
        round(len(completed) / admitted, 4) if admitted else 1.0
    )

    if health is not None and isinstance(health.get("slo"), dict) and measured:
        slo = health["slo"]
        live = {s["name"]: s for s in slo.get("slos", [])}
        cross = {}
        if "ttft_p95_ms" in live:
            lv = live["ttft_p95_ms"]["value"]
            cross["ttft_p95_ms"] = {
                "report": round(percentile(ttfts, 0.95), 3),
                "live": lv,
                "rel_gap": _rel_gap(percentile(ttfts, 0.95), lv),
            }
        if "decode_p95_ms" in live:
            lv = live["decode_p95_ms"]["value"]
            cross["decode_p95_ms"] = {
                # client-side peer of the server's decode-step window is
                # the inter-token latency
                "report": round(percentile(itls, 0.95), 3),
                "live": lv,
                "rel_gap": _rel_gap(percentile(itls, 0.95), lv),
            }
        if "availability" in live:
            lv = live["availability"]["value"]
            cross["availability"] = {
                "report": report["availability"],
                "live": lv,
                "rel_gap": _rel_gap(report["availability"], lv),
            }
        p99 = slo.get("p99") or {}
        report["slo"] = {
            "live": slo,
            "cross_check": cross,
            "live_p99": p99,
            "report_p99": {
                "ttft_ms": round(percentile(ttfts, 0.99), 3),
                "tpot_ms": round(percentile(itls, 0.99), 3),
            },
            "attained": not slo.get("burning"),
            "burning": slo.get("burning", []),
        }

    fleet = _fleet_summary(
        measured, completed, shed, failed, window_s,
        metrics_after, metrics_before,
    )
    if fleet is not None:
        report["fleet"] = fleet

    if metrics_after is not None:
        report["phase_attribution"] = _phase_summary(
            metrics_after, metrics_before
        )
        report["jit"] = _jit_summary(metrics_after, metrics_before)
        report["device_mem_bytes"] = _device_mem(metrics_after)
    if meta:
        report["meta"] = meta
    if include_rows:
        report["rows"] = [o.as_dict() for o in rows]
    return report
