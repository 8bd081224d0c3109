"""From a profiler trace to busy time, shares and the breakdown.

`load` turns an `.xplane.pb` (read with jax.profiler.ProfileData, nothing
else) into plain data; everything after works on that, so the reduction is
checked on a small recorded trace (testdata/) without a chip:

  {"devices": {plane_name: [[op_name, start_ns, dur_ns], ...]},   device ops
   "host":    [[span_name, start_ns, dur_ns], ...]}               host spans

Device ops are the events of each device plane's "XLA Ops" line: one event
per executed HLO instruction or custom call, named by its HLO text.  A
`while` or `conditional` event encloses the events of its body, so time is
attributed by SELF time (an event's duration less its direct children's):
self times of one device add up to its busy time, and no share counts an
instruction twice.
"""

from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"

Event = Tuple[str, int, int]


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: Path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events
                )
    return {"devices": devices, "host": host}


def load_recorded(path: Path) -> dict:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def union_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def span_ns(trace: dict) -> Tuple[int, int]:
    """First start and last end of any device op: the traced slice as the
    devices saw it."""
    starts = [s for ops in trace["devices"].values() for _, s, _ in ops]
    ends = [s + d for ops in trace["devices"].values() for _, s, d in ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def self_times(ops: Sequence[Event]) -> List[Tuple[str, int]]:
    """(name, self_ns) per event: its duration less that of the events
    nested directly inside it on the same line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ops[i][2] for i in range(len(ops))]
    stack: List[int] = []  # indices of the enclosing events, outermost first
    for i in order:
        _, s, d = ops[i]
        while stack and s >= ops[stack[-1]][1] + ops[stack[-1]][2]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(ops[i][0], max(self_ns[i], 0)) for i in range(len(ops))]


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, mean over the device planes."""
    per = [union_ns([(s, s + d) for _, s, d in ops]) for ops in trace["devices"].values()]
    return sum(per) / len(per) / 1e9


def window_s(trace: dict) -> float:
    t0, t1 = span_ns(trace)
    return (t1 - t0) / 1e9


def idle_pct(trace: dict) -> float:
    return (1.0 - busy_s(trace) / window_s(trace)) * 100.0


def share_pct(trace: dict, pattern: str, of: str = "busy") -> float:
    """Device self time in ops whose name matches `pattern`, as a share of
    busy time or of the traced window, mean over devices."""
    rx = re.compile(pattern)
    shares = []
    t0, t1 = span_ns(trace)
    for ops in trace["devices"].values():
        hit = sum(d for name, d in self_times(ops) if rx.search(name))
        base = union_ns([(s, s + d) for _, s, d in ops]) if of == "busy" else t1 - t0
        shares.append(hit / base if base else 0.0)
    return sum(shares) / len(shares) * 100.0


def short_name(name: str, width: int = 96) -> str:
    """An HLO event's name is its whole text; keep the head, on one line."""
    return re.sub(r"\s+", " ", name).strip()[:width]


def _covering_span(host: Sequence[Event], g0: int, g1: int) -> str:
    """The host span underneath an idle gap: the SHORTEST span that covers
    at least half of it (the most specific thing the host was doing), else
    the one that covers most."""
    best, best_len, most, most_cover = None, None, "__no_host_span__", 0
    for name, s, d in host:
        cover = min(g1, s + d) - max(g0, s)
        if cover <= 0:
            continue
        if 2 * cover >= g1 - g0 and (best_len is None or d < best_len):
            best, best_len = name, d
        if cover > most_cover:
            most, most_cover = name, cover
    return best if best is not None else most


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most self time (seconds, mean over
    devices) and the longest idle gaps of the first device, by the host
    span underneath."""
    n = len(trace["devices"])
    by_op: Dict[str, int] = {}
    for ops in trace["devices"].values():
        for name, d in self_times(ops):
            k = short_name(name)
            by_op[k] = by_op.get(k, 0) + d
    device_ops = [
        [k, v / n / 1e9] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    ]
    gaps: List[Tuple[int, int]] = []
    first = next(iter(trace["devices"].values()))
    merged = sorted((s, s + d) for _, s, d in first)
    end = merged[0][1] if merged else 0
    for s, e in merged[1:]:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:50]
    by_host: Dict[str, int] = {}
    for g0, g1 in gaps:
        by_host_name = short_name(_covering_span(trace["host"], g0, g1), 64)
        by_host[by_host_name] = by_host.get(by_host_name, 0) + (g1 - g0)
    idle_gaps = [
        [k, v / 1e9] for k, v in sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {"device_ops": device_ops, "idle_gaps": idle_gaps}
