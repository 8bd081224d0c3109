"""Prometheus text exposition: parser and window deltas.

The parser is a copy of dnet_tpu/loadgen/report.py: parse_prometheus (the
program may change its own; the yardstick may not)."""

from __future__ import annotations

import re
from typing import Dict, Optional

_SAMPLE_RE = re.compile(
    r"^(?P<name>[A-Za-z_:][A-Za-z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})?\s+(?P<value>[^\s]+)\s*$"
)
_LABEL_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Dict[str, float]:
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


def _matches(key: str, name: str, labels: Optional[Dict[str, str]]) -> bool:
    base, _, rest = key.partition("{")
    if base != name:
        return False
    if not labels:
        return True
    have = dict(_LABEL_RE.findall(rest))
    return all(have.get(k) == v for k, v in labels.items())


def total(sample: Dict[str, float], name: str, labels: Optional[Dict[str, str]] = None) -> float:
    """Sum of every series of `name` whose labels include `labels`."""
    return sum(v for k, v in sample.items() if _matches(k, name, labels))


def delta(after: Dict[str, float], before: Dict[str, float], name: str,
          labels: Optional[Dict[str, str]] = None) -> float:
    return total(after, name, labels) - total(before, name, labels)
