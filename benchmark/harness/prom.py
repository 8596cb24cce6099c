"""Read the Prometheus text the server's ``/metrics`` renders."""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

_SAMPLE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$')
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def parse(text: str) -> Dict[Key, float]:
    out: Dict[Key, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(3) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(4))
        except ValueError:
            continue
    return out


def value(samples: Dict[Key, float], metric: str,
          labels: Optional[dict] = None) -> Optional[float]:
    """Sum of the samples of ``metric`` whose labels include ``labels``;
    None when the metric is not in the scrape at all (a counter that
    never moved is not rendered)."""
    want = set((labels or {}).items())
    got = [v for (name, lab), v in samples.items()
           if name == metric and want <= set(lab)]
    return sum(got) if got else None


def delta(start: Dict[Key, float], end: Dict[Key, float], metric: str,
          labels: Optional[dict] = None) -> float:
    return (value(end, metric, labels) or 0.0) - (value(start, metric, labels) or 0.0)


def rows(samples: Dict[Key, float], metric: str) -> Dict[Tuple, float]:
    return {lab: v for (name, lab), v in samples.items() if name == metric}
