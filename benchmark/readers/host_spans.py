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
import re
from typing import List, Optional, Tuple

from harness.rundata import RunData
from harness.stats import gaps_of
from harness.trace import COMPLETED, ENQUEUED

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


def plane_shift(trace) -> Tuple[float, Optional[float]]:
    """(late, room), in seconds: how late the capture's host plane runs
    against its device planes, and how well that is known.

    The two planes' clocks are set apart by the profiler, and not alike
    in every capture: the first capture a machine takes shows the host
    plane ~1 ms later than its next ones do (PR 36 saw tails 0.7-0.9 ms
    high in a machine's first capture; PR 58's twenty captures read the
    same tail in two groups, 1.5-1.8 and 2.4-2.8 ms, the higher in the
    first traced run of each chip call). Two of the runtime's host
    events name their execution (``harness/trace.py``: RUN_EVENTS), so
    each side has a bound that needs no guess at what belongs to what:

    - an execution cannot begin before the host has begun to enqueue
      it: ``late`` = the most by which one seems to (0 where none does,
      and where the capture has no such events). Every time read from
      a device event to a host event is too long by at least that;
    - the host cannot learn that an execution ended before it did:
      ``room`` = the least of (``CompleteCallbacks`` start - execution
      end) once ``late`` is taken off, None without such events. The
      planes' true offset lies within ``room`` of ``late``; under zero,
      no one shift puts both sides in order and the planes disagree
      within the capture."""
    host = {(h.name, h.run): h.start for h in trace.host if h.run}
    early, learnt = [], []
    for d in trace.devices:
        for m in trace.modules[d]:
            if (ENQUEUED, m.run) in host:
                early.append(m.start - host[ENQUEUED, m.run])
            if (COMPLETED, m.run) in host:
                learnt.append(host[COMPLETED, m.run] - (m.start + m.dur))
    late = max(0.0, -min(early)) if early else 0.0
    return late, (min(learnt) - late if learnt else None)


def step_ends(trace, program: str) -> List[float]:
    """For every execution in the capture (any device) of a program
    whose name matches ``program``, the end of the last operation inside
    it, sorted: when that step's result exists on the device, on the
    host plane's clock (later by ``plane_shift``'s ``late``), so that it
    can be held against a span. An execution without an operation of
    its own ends where its ``XLA Modules`` event does."""
    late, _ = plane_shift(trace)
    out = []
    for d in trace.devices:
        ops, i = trace.ops[d], 0
        for m in trace.modules[d]:           # both lists are sorted by start
            if not re.search(program, m.name):
                continue
            end = m.start + m.dur
            while i < len(ops) and ops[i].start < m.start:
                i += 1
            last = None
            while i < len(ops) and ops[i].start < end:
                last = max(last or 0.0, ops[i].start + ops[i].dur)
                i += 1
            out.append((end if last is None else last) + late)
    return sorted(out)


def step_end_inside(ends: List[float], start: float, end: float):
    """The last of ``ends`` (``step_ends``) inside [start, end], or None."""
    i = bisect.bisect_right(ends, end)
    return ends[i - 1] if i and ends[i - 1] >= start else None


def sync_tails(trace, span: str, program: str) -> List[float]:
    """For each ``span`` event inside which an execution of ``program``
    ended (``step_ends``): seconds from the end of the last such
    execution's last operation (any device, on the host plane's clock)
    to the span's end. The device's result is ready at the first and
    the scheduler runs again at the second: the copy to the host and
    the hop back from the executor thread.

    It is the execution that counts, not the operation: since PR 57 the
    next decode step is on the device while the host waits for this
    one, so an operation ends inside the wait every few microseconds,
    and all of them but the waited step's last belong to the step in
    flight, which ends after the wait does. A metric's file names the
    program the span waits for (``^jit_decode_`` for
    ``sched.decode.sync``): the small programs the host sends once a
    wait is over (a cast, a row set) end microseconds after they start,
    and on the late host plane they seem to end inside the wait that
    came before them."""
    ends = step_ends(trace, program)
    out = []
    for h in trace.host:
        if h.name != span:
            continue
        device_end = step_end_inside(ends, h.start, h.start + h.dur)
        if device_end is not None:
            out.append(h.start + h.dur - device_end)
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
        tails = sync_tails(trace, args["span"], args["program"])
        return (1e3 * sum(tails) / len(tails), len(tails)) if tails else None
    if stat == "idle_covered_pct":
        if not _spans(trace, ("sched.",)):
            return None       # a program that writes no spans of its own
        return idle_covered_pct(trace, args["spans"])
    raise ValueError(f"host_spans reader: unknown stat {stat!r}")
