"""The sync tail in its three parts (ISSUE 35).

The sync tail of ``readers/host_spans.py`` (``sync_tail_mean_ms``) is
one lump: end of the waited step's last operation on the device
(``host_spans.step_ends``: the last execution of the program the span
waits for that ended inside it; since PR 57 the next step is on the
device meanwhile, and its operations are not this step's) to end of
``sched.decode.sync``. Since PR 35 the program writes where the lump
divides, inside every wait for a device result (``Scheduler._fetch``):
under ``sync.fetch``, on the executor thread, ``sync.ready`` ends when
the tokens are on the host and ``sync.copy`` covers the arrays fetched
after them; what is left of the ``sched.*.sync`` span after
``sync.fetch`` has ended is the hop back to the scheduler's loop. So,
over the passes the lump is read from (the waited step ended inside
the span):

- ready = ``sync.ready`` end - end of the waited step's last operation
- copy  = duration of ``sync.copy`` (0 where only the tokens were fetched)
- hop   = ``sched.*.sync`` end - ``sync.fetch`` end

and ready + copy + hop is the lump, but for the microsecond between the
two inner spans. Only *ready* crosses from the device plane's clock to
the host plane's, which runs late by an amount that differs from
capture to capture: ``host_spans.plane_shift`` bounds it from both
sides by the runtime's own events, the step's end is taken later by
its ``late``, and the clock's slack is what is then left of the
tighter side: the least ready part, or the ``room`` between an
execution's end and the host's learning of it. The planes' offset is
known to within that much, and ready and the lump are good to it; under
zero they disagree within the capture and both are void (copy and hop,
differences on one plane, stand). ``counter_tail_ms`` is copy + hop
again from the program's counters, by the host's clock alone, over a
stretch of the capture.

``DeviceTrace.host`` holds a name, a start and a duration an event, so a
fetch is found in its pass by time: the loop waits for one fetch at a
time. A program that writes no such span or counter (a parent commit)
gives every reader here nothing to read.
"""

from __future__ import annotations

import bisect
from typing import List, NamedTuple

from harness import prom
from harness.rundata import RunData
from readers.host_spans import plane_shift, step_end_inside, step_ends

FETCH_SECONDS = "dynamo_scheduler_fetch_seconds_total"
FETCHES = "dynamo_scheduler_fetches_total"


class Pass(NamedTuple):
    device_end: float   # end of the waited step on the device (step_ends)
    ready_end: float    # sync.ready: the tokens are on the host
    copy_s: float       # sync.copy: the arrays after them
    fetch_end: float    # sync.fetch: the executor thread is done
    span_end: float     # sched.*.sync: the scheduler runs again

    @property
    def ready_s(self) -> float:
        return self.ready_end - self.device_end

    @property
    def hop_s(self) -> float:
        return self.span_end - self.fetch_end


def _inside(events, starts, lo: float, hi: float) -> list:
    """The events of a start-sorted list that lie within [lo, hi]."""
    i = bisect.bisect_left(starts, lo)
    out = []
    while i < len(events) and events[i].start <= hi:
        if events[i].start + events[i].dur <= hi:
            out.append(events[i])
        i += 1
    return out


def passes(trace, span: str, program: str) -> List[Pass]:
    """One entry for each ``span`` event inside which an execution of
    ``program`` ended (any device: the passes ``host_spans.sync_tails``
    reads, from the waited step's last operation and not the step in
    flight's) and which holds a ``sync.fetch`` written in its parts."""
    ends = step_ends(trace, program)
    by_name = {}
    for name in ("sync.fetch", "sync.ready", "sync.copy"):
        evs = sorted((h for h in trace.host if h.name == name),
                     key=lambda h: h.start)
        by_name[name] = (evs, [h.start for h in evs])
    out = []
    for h in trace.host:
        if h.name != span:
            continue
        end = h.start + h.dur
        device_end = step_end_inside(ends, h.start, end)
        if device_end is None:
            continue
        fetches = _inside(*by_name["sync.fetch"], h.start, end)
        if len(fetches) != 1:
            continue
        f = fetches[0]
        ready = _inside(*by_name["sync.ready"], f.start, f.start + f.dur)
        if len(ready) != 1:
            continue
        copies = _inside(*by_name["sync.copy"], f.start, f.start + f.dur)
        out.append(Pass(device_end, ready[0].start + ready[0].dur,
                        sum(c.dur for c in copies), f.start + f.dur, end))
    return out


def _counter_stretch(run: RunData):
    """The two /metrics samples the counters are read between: the first
    and the last taken inside the captured slice, so that the counters
    cover a stretch the capture's rows cover too (the seconds after a
    capture, while the profiler writes it out in the server's process,
    are not the program's: PERF.md section 6, PR 35); the window's two
    ends where the run has no slice or too few samples in it."""
    if run.trace_slice is not None:
        s0, s1 = run.trace_slice
        inside = [s for t, s in run.prom_samples if s0 <= t <= s1]
        if len(inside) >= 2:
            return inside[0], inside[-1]
    return run.prom_start, run.prom_end


def fetch_tail_ms(run: RunData, kind: str):
    """copy + hop a fetch, from the counters' deltas."""
    lo, hi = _counter_stretch(run)
    if not lo or not hi:
        return None
    n = prom.delta(lo, hi, FETCHES, {"kind": kind})
    if n <= 0:
        return None
    seconds = sum(prom.delta(lo, hi, FETCH_SECONDS,
                             {"kind": kind, "part": part})
                  for part in ("copy", "hop"))
    return 1e3 * seconds / n, int(n)


def read(run: RunData, args: dict):
    stat = args["stat"]
    if stat == "counter_tail_ms":
        return fetch_tail_ms(run, args["kind"])
    trace = run.device_trace
    if trace is None:
        return None
    found = passes(trace, args["span"], args["program"])
    if not found:
        return None
    n = len(found)
    if stat == "ready_mean_ms":
        return 1e3 * sum(p.ready_s for p in found) / n, n
    if stat == "copy_mean_ms":
        return 1e3 * sum(p.copy_s for p in found) / n, n
    if stat == "hop_mean_ms":
        return 1e3 * sum(p.hop_s for p in found) / n, n
    if stat == "clock_slack_min_ms":
        room = plane_shift(trace)[1]
        least = min(p.ready_s for p in found)
        return 1e3 * (least if room is None else min(least, room)), n
    raise ValueError(f"sync_parts reader: unknown stat {stat!r}")
