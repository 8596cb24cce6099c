"""Metrics from the program's own spans in the profiler's capture.

The program writes its seams into the profiler's trace
(``dynamo_tpu/telemetry/tracing.span``: ``sched.admit``,
``sched.decode.build|dispatch|sync|emit``, ``sched.yield``,
``sched.wait``, ``sync.fetch``, ``dispatch.<program>``, the frontend's
leaves; the table is in ``docs/observability.md``). They land on the
``/host:CPU`` plane on the device planes' clock, and ``harness/trace.py``
collects them with the runtime's own host events into
``DeviceTrace.host``. A program that writes no such span (a parent
commit from before them) gives every reader here nothing to read.
"""

from __future__ import annotations

import bisect
from typing import List, Tuple

from harness.rundata import RunData
from harness.stats import gaps_of

Interval = Tuple[float, float]


def _merged(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _spans(trace, prefixes) -> list:
    prefixes = tuple(prefixes)
    return [h for h in trace.host if h.name.startswith(prefixes)]


def idle_gaps_of(trace, device: int) -> List[Interval]:
    """The stretches of the capture in which no operation ran on
    ``device``: the idle time the device's idle-share metric counts."""
    t0, t1 = trace.window
    return gaps_of([(o.start, o.start + o.dur) for o in trace.ops[device]],
                   t0, t1)


def idle_covered_pct(trace, prefixes) -> float:
    """Share of the device's idle time (the chip that waits longest)
    that lies inside the union of the host spans named by ``prefixes``.

    The profiler writes a span when it *ends*: one still open when the
    capture stops (a prefill's sync of a quarter of a second, say) is
    never written, and nothing of the program is on record before its
    first span either. So the idle time is counted between the start of
    the first and the end of the last ``sched.*`` span, where the
    program's spans can be on record at all."""
    sched = _spans(trace, ("sched.",))
    lo = min(h.start for h in sched)
    hi = max(h.start + h.dur for h in sched)
    device = max(trace.devices, key=trace.idle_share)
    gaps = [(max(s, lo), min(e, hi)) for s, e in idle_gaps_of(trace, device)
            if min(e, hi) > max(s, lo)]
    idle = sum(e - s for s, e in gaps)
    if not idle:
        return 0.0
    cover = _merged([(h.start, h.start + h.dur)
                     for h in _spans(trace, prefixes)])
    return 100.0 * _overlap(gaps, cover) / idle


def sync_tails(trace, span: str) -> List[float]:
    """For each ``span`` event inside which a device operation ended:
    seconds from the end of the last such operation (any device) to the
    span's end. The device's result is ready at the first and the
    scheduler runs again at the second: the copy to the host and the
    hop back from the executor thread."""
    ends = sorted(o.start + o.dur for d in trace.devices for o in trace.ops[d])
    out = []
    for h in trace.host:
        if h.name != span:
            continue
        i = bisect.bisect_right(ends, h.start + h.dur)
        if i and ends[i - 1] >= h.start:
            out.append(h.start + h.dur - ends[i - 1])
    return out


def read(run: RunData, args: dict):
    trace = run.device_trace
    if trace is None:
        return None
    stat = args["stat"]
    if stat == "span_mean_ms":
        durs = [h.dur for h in trace.host if h.name == args["span"]]
        return (1e3 * sum(durs) / len(durs), len(durs)) if durs else None
    if stat == "sync_tail_mean_ms":
        tails = sync_tails(trace, args["span"])
        return (1e3 * sum(tails) / len(tails), len(tails)) if tails else None
    if stat == "idle_covered_pct":
        if not _spans(trace, ("sched.",)):
            return None       # a program that writes no spans of its own
        return idle_covered_pct(trace, args["spans"])
    raise ValueError(f"host_spans reader: unknown stat {stat!r}")
