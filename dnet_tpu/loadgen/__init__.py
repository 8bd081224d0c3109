"""Serving-grade load generation (ROADMAP item 5(b)).

An open-loop, seeded load harness for the OpenAI-compatible serving
surface: `workload` builds deterministic arrival/length schedules,
`client` drives one streaming request to one outcome row, `runner`
orchestrates the fan-out and brackets it with metric scrapes, and
`report` turns the rows into a machine-readable record (``kind``
``BENCH_SERVE``) that `compare` diffs.  The chaos campaign
(dnet_tpu/chaos/, scripts/chaos_campaign.py) drives it; speed on the chip
is `benchmarks/run.py`'s to measure, not this harness's.
"""

from dnet_tpu.loadgen.client import RequestOutcome, run_request
from dnet_tpu.loadgen.report import build_report, parse_prometheus, percentile
from dnet_tpu.loadgen.runner import LoadResult, run_load
from dnet_tpu.loadgen.workload import (
    Bucket,
    PlannedRequest,
    WorkloadSpec,
    parse_buckets,
    schedule,
)

__all__ = [
    "Bucket",
    "LoadResult",
    "PlannedRequest",
    "RequestOutcome",
    "WorkloadSpec",
    "build_report",
    "parse_buckets",
    "parse_prometheus",
    "percentile",
    "run_load",
    "run_request",
    "schedule",
]
