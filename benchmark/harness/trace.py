"""Reduce a profiler capture (``*.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else, by the process
that held the chip (after the window) or after it has exited: never by a
second process that loads the TPU's library beside it.

What a TPU capture looks like (seen by hand on the v5e, PR 22; a cut of
it is kept in ``benchmark/tests/data``): one plane per chip named
``/device:TPU:<n>``; in it the line ``XLA Modules`` has one event per
execution of a compiled program (``jit_<function>(<fingerprint>)``) and
the line ``XLA Ops`` one event per HLO operation, named by its whole HLO
line and nested (a ``while`` spans the operations of its body); the
line ``Async XLA Ops`` holds copies that run beside them and is left
out. Host threads are lines of the plane ``/host:CPU``. An execution
carries the stat ``run_id``, and so do two of the runtime's own host
events (seen on every capture of PR 58):
``DoEnqueueProgram`` (a ``pjrt-tpu-tasks`` thread hands the program to
the chip) and ``CompleteCallbacks`` (the host learns that it ended),
with the chip as ``device_ordinal``. They are the one place where an
event of the host plane and one of a device plane are the same thing
by name and not by time (``Event.run``).

- busy time of a device = the union of its ``XLA Ops`` intervals;
- a program's time = the durations of its ``XLA Modules`` events;
- an operation's own time = its duration minus the operations nested in
  it, so that a loop is not counted on top of its body.

``python -m harness.trace <file>`` prints what a capture holds: look at
one by hand before trusting a name.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from .stats import gaps_of, union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# the program's own spans that can hold an idle gap (docs/observability.md)
PROGRAM_SPANS = ("sched.", "sync.", "dispatch.")
# the runtime's host events that name the execution they belong to
ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"
RUN_EVENTS = (ENQUEUED, COMPLETED)


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds on the capture's clock
    dur: float
    own: float = 0.0  # dur minus nested events (ops only)
    detail: str = ""  # a longer name where the trace has one
    run: Optional[Tuple[int, int]] = None   # (chip, run_id): RUN_EVENTS, executions


@dataclasses.dataclass
class DeviceTrace:
    window: Tuple[float, float]          # first to last event of any plane
    devices: List[int]
    busy_s: Dict[int, float]             # per device: union of op intervals
    span: Dict[int, Tuple[float, float]]  # per device: first op start, last op end
    modules: Dict[int, List[Event]]      # per device: program executions
    ops: Dict[int, List[Event]]          # per device: HLO operations, own time set
    host: List[Event]                    # host-side spans (all threads)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def idle_share(self, device: int) -> float:
        return 1.0 - self.busy_s[device] / self.window_s


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO line,
    ``%fusion.7 = f32[...] fusion(...)``: the instruction's name is
    enough, and is what stays the same from run to run."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def _run_of(e, device: Optional[int]) -> Optional[Tuple[int, int]]:
    """(chip, run_id) of an execution (``device`` given) or of one of
    RUN_EVENTS; None where the event does not say."""
    stats = {k: v for k, v in e.stats if k in ("run_id", "device_ordinal")}
    if "run_id" not in stats:
        return None
    if device is None:
        device = stats.get("device_ordinal")
    return None if device is None else (int(device), int(stats["run_id"]))


def _events(line, device: Optional[int] = None) -> List[Event]:
    """A line's events by start. ``Event.run`` is read for every event
    of chip ``device``'s ``XLA Modules`` line and for RUN_EVENTS."""
    out = []
    for e in line.events:
        ev = Event(short_name(e.name), e.start_ns * 1e-9,
                   e.duration_ns * 1e-9, detail=e.name[:300])
        if device is not None or ev.name in RUN_EVENTS:
            ev.run = _run_of(e, device)
        out.append(ev)
    out.sort(key=lambda ev: (ev.start, -ev.dur))
    return out


def _set_own_time(events: List[Event]) -> None:
    """own = dur - time covered by directly nested events."""
    stack: List[Event] = []
    for ev in events:
        ev.own = ev.dur
        while stack and ev.start >= stack[-1].start + stack[-1].dur - 1e-12:
            stack.pop()
        if stack:
            stack[-1].own -= ev.dur
        stack.append(ev)
    for ev in events:
        ev.own = max(ev.own, 0.0)


def load(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo, hi = float("inf"), float("-inf")
    busy, span, modules, ops, host = {}, {}, {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = None
            if m and line.name in (OPS_LINE, MODULES_LINE):
                d = int(m.group(1))
                evs = _events(line, d if line.name == MODULES_LINE else None)
                if line.name == OPS_LINE:
                    _set_own_time(evs)
                    ops[d] = evs
                    busy[d] = union_length(
                        [(e.start, e.start + e.dur) for e in evs])
                    if evs:
                        span[d] = (evs[0].start,
                                   max(e.start + e.dur for e in evs))
                else:
                    modules[d] = evs
            elif plane.name == HOST_PLANE:
                evs = _events(line)
                host.extend(evs)
            if evs:
                lo = min(lo, evs[0].start)
                hi = max(hi, max(e.start + e.dur for e in evs))
    devices = sorted(ops)
    if not devices:
        raise ValueError(
            f"{path}: no '/device:TPU:<n>' plane with an '{OPS_LINE}' line: "
            "nothing ran on a TPU while this was captured")
    for d in devices:
        modules.setdefault(d, [])
    return DeviceTrace((lo, hi), devices, busy, span, modules, ops, host)


def top_ops(trace: DeviceTrace, device: int, n: int = 10) -> List[list]:
    """The n operations with most own time, under the trace's own names."""
    total: Dict[str, float] = {}
    for o in trace.ops[device]:
        total[o.name] = total.get(o.name, 0.0) + o.own
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _program(name: str) -> str:
    """``jit_step(5733744117576889536)`` -> ``jit_step(5733)``."""
    return re.sub(r"\((\d{4})\d*\)", r"(\1)", name)


def idle_gaps(trace: DeviceTrace, device: int, n: int = 5) -> List[list]:
    """The n longest stretches in which no operation ran on the device,
    each labelled by the programs that ran before and after it and by
    what the host was doing in it: the program's own span with most time
    inside the gap (``sched.*``, ``sync.*``, ``dispatch.*``:
    ``telemetry/tracing.span``) where one overlaps it, else the
    runtime's host event name with most time inside it. The runtime's
    names are summed over threads, and on four chips several threads
    write the same one, so they come second: they would outweigh the one
    scheduler span that holds the gap. ``python`` where the host was in
    no such span: the scheduler's own code, or a program from before the
    spans. The device planes of a capture run on for a tenth of a second
    after the host plane's last event, so gaps are looked for only where
    the host is on record: past that, every gap would read ``python``."""
    t0, t1 = trace.window
    if trace.host:
        t0 = max(t0, min(h.start for h in trace.host))
        t1 = min(t1, max(h.start + h.dur for h in trace.host))
    busy = [(o.start, o.start + o.dur) for o in trace.ops[device]]
    gaps = sorted(gaps_of(busy, t0, t1), key=lambda g: g[0] - g[1])[:n]
    # helper programs of a few microseconds (a dtype cast) say nothing
    mods = [m for m in trace.modules[device] if m.dur >= 1e-4]
    out = []
    for s, e in gaps:
        before = [m for m in mods if m.start + m.dur <= s + 1e-9]
        after = [m for m in mods if m.start >= e - 1e-9]
        own: Dict[str, float] = {}
        runtime: Dict[str, float] = {}
        for h in trace.host:
            c = min(e, h.start + h.dur) - max(s, h.start)
            if c > 0:
                inside = own if h.name.startswith(PROGRAM_SPANS) else runtime
                inside[h.name] = inside.get(h.name, 0.0) + c
        inside = own or runtime
        host = max(inside, key=inside.get) if inside else "python"
        if inside and inside[host] < 0.25 * (e - s):
            host = "python, then " + host
        label = (f"{_program(before[-1].name) if before else 'start'} -> "
                 f"{_program(after[0].name) if after else 'end'}: {host}")
        out.append([label, e - s])
    return out


def describe(path: str, limit: int = 25) -> str:
    """What a capture holds, for reading by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lines_out = [f"{path}: {os.path.getsize(path)} bytes"]
    for plane in data.planes:
        lines = list(plane.lines)
        lines_out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            total: Dict[str, List[float]] = {}
            keys = set()
            for e in evs:
                t = total.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns * 1e-6
            for k, v in evs[len(evs) // 2].stats:
                keys.add(f"{k}={str(v)[:90]!r}")
            lines_out.append(
                f"  LINE {line.name!r}: {len(evs)} events, "
                f"{len(total)} names; stats of one: {sorted(keys)[:8]}")
            for name, (cnt, ms) in sorted(
                    total.items(), key=lambda kv: -kv[1][1])[:limit]:
                lines_out.append(f"      {ms:10.3f} ms {cnt:7d} x  {name[:110]}")
    return "\n".join(lines_out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
