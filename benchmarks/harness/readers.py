"""The generic readers a per-layer metric file picks from.

A metric is `benchmarks/layer_metrics/<name>.json`:

  {"reader": "prom_delta", "family": "dnet_sched_tick_ms", "labels": {...},
   "stat": "mean" | "sum" | "max_ratio_pct", ...}
  {"reader": "trace_share", "pattern": "<regex over device-op names>",
   "of": "busy" | "window"}
  {"reader": "trace_idle"}
  {"reader": "client", "field": "<a key of the client summary>"}
  {"reader": "device_memory"}

A reader that finds nothing to read returns None and the harness leaves the
metric out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from benchmarks.harness import prom, xplane


@dataclass(frozen=True, kw_only=True)
class Evidence:
    """What one traced run gathered, for the readers to read."""

    client: Dict[str, float]  # window.summarize() + generator lateness
    scrapes: List[Dict[str, float]]  # /metrics samples: window open ... close
    trace: Optional[dict]  # xplane.load() of the traced slice
    memory: Dict[str, float]  # run.memory_now(): most bytes in use through the window


def prom_delta(spec: dict, ev: Evidence) -> Optional[float]:
    if len(ev.scrapes) < 2:
        return None
    before, after = ev.scrapes[0], ev.scrapes[-1]
    fam, labels, stat = spec["family"], spec.get("labels"), spec.get("stat", "sum")
    if stat == "sum":  # a counter's increase over the window
        if not any(k.split("{")[0] == fam for k in after):
            return None
        return prom.delta(after, before, fam, labels)
    if stat == "mean":  # a histogram's mean observation over the window
        n = prom.delta(after, before, fam + "_count", labels)
        if n <= 0:
            return None
        return prom.delta(after, before, fam + "_sum", labels) / n
    if stat == "max_ratio_pct":  # a gauge's peak through the window, over another
        den = prom.total(after, spec["over"], spec.get("over_labels"))
        if den <= 0:
            return None
        return max(prom.total(s, fam, labels) for s in ev.scrapes) / den * 100.0
    raise ValueError(f"prom_delta: unknown stat {stat!r}")


def trace_share(spec: dict, ev: Evidence) -> Optional[float]:
    if ev.trace is None or not ev.trace["devices"]:
        return None
    return xplane.share_pct(ev.trace, spec["pattern"], spec.get("of", "busy"))


def trace_idle(spec: dict, ev: Evidence) -> Optional[float]:
    if ev.trace is None or not ev.trace["devices"]:
        return None
    return xplane.idle_pct(ev.trace)


def client(spec: dict, ev: Evidence) -> Optional[float]:
    return ev.client.get(spec["field"])


def device_memory(spec: dict, ev: Evidence) -> Optional[float]:
    if not ev.memory.get("limit"):
        return None
    return ev.memory["in_use"] / ev.memory["limit"] * 100.0


READERS: Dict[str, Callable[[dict, Evidence], Optional[float]]] = {
    "prom_delta": prom_delta,
    "trace_share": trace_share,
    "trace_idle": trace_idle,
    "client": client,
    "device_memory": device_memory,
}


def read(spec: dict, ev: Evidence) -> Optional[float]:
    return READERS[spec["reader"]](spec, ev)
