"""Token-stamped window arithmetic.

Every number is taken from the arrival times of single tokens at the
client, never from completed requests: a request still in flight when the
window closes contributes the tokens and gaps it produced inside it, and
nothing waits for a drain.

A stream is (due, stamps): when the request was due to be sent, and the
arrival time of each of its tokens, all on one clock.  A window is
(t0, t1]: a token arriving exactly at t1 is inside, one at t0 is not.
The window is the run's own: t1 = t0 + --seconds, whatever the tokens do.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

Stream = Tuple[float, Sequence[float]]


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank quantile (copy of dnet_tpu/obs/slo.py: nearest_rank)."""
    if not 0.0 <= q <= 1.0:
        raise ValueError("q must be in [0, 1]")
    vals = sorted(values)
    if not vals:
        return 0.0
    rank = max(math.ceil(q * len(vals)), 1)
    return vals[rank - 1]


def tokens_in(streams: Sequence[Stream], t0: float, t1: float) -> int:
    return sum(1 for _, stamps in streams for t in stamps if t0 < t <= t1)


def gaps_in(streams: Sequence[Stream], t0: float, t1: float) -> List[float]:
    """Every interval between two consecutive tokens of one stream whose
    LATER token falls in the window (seconds)."""
    out = []
    for _, stamps in streams:
        for a, b in zip(stamps, stamps[1:]):
            if t0 < b <= t1:
                out.append(b - a)
    return out


def ttfts_in(streams: Sequence[Stream], t0: float, t1: float) -> List[float]:
    """First token minus due time, for every request whose first token
    falls in the window (seconds)."""
    return [
        stamps[0] - due
        for due, stamps in streams
        if stamps and t0 < stamps[0] <= t1
    ]


def drift_pct(streams: Sequence[Stream], t0: float, t1: float) -> float:
    """|tokens in the second half - first half| / half the total, in %.
    Large = the window is not in steady state, whatever its medians say."""
    mid = (t0 + t1) / 2
    first, second = tokens_in(streams, t0, mid), tokens_in(streams, mid, t1)
    total = first + second
    return 0.0 if total == 0 else abs(second - first) / (total / 2) * 100.0


def summarize(streams: Sequence[Stream], t0: float, t1: float) -> Dict[str, float]:
    """All client-side readings of one window, by name."""
    gaps = gaps_in(streams, t0, t1)
    ttfts = ttfts_in(streams, t0, t1)
    tokens = tokens_in(streams, t0, t1)
    out = {
        "tokens": float(tokens),
        "output_tokens_per_s": tokens / (t1 - t0),
        "n_gaps": float(len(gaps)),
        "n_ttft": float(len(ttfts)),
        "window_drift_pct": drift_pct(streams, t0, t1),
    }
    if gaps:
        out["itl_p50_ms"] = nearest_rank(gaps, 0.50) * 1e3
        out["itl_mean_ms"] = sum(gaps) / len(gaps) * 1e3
        if len(gaps) >= 200:  # a p95 needs ten readings beyond it
            out["itl_p95_ms"] = nearest_rank(gaps, 0.95) * 1e3
    if ttfts:
        out["ttft_p50_ms"] = nearest_rank(ttfts, 0.50) * 1e3
    return out
