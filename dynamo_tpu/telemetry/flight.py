"""In-engine flight recorder + XLA compile observability.

The black box the aggregate gauges can't be: when a serving worker
stalls or dies, ``/metrics`` says *that* throughput went flat, not *why*.
The :class:`FlightRecorder` is a process-wide bounded ring of structured
engine events — scheduler admission/preemption/dispatch/drain/rollback,
allocator eviction/OOM, disagg commit/nack/poison/local-fallback, KV
router picks, XLA compiles — each stamped with monotonic time and the
request/trace id it belongs to. The ring is cheap enough to run always
(one dict build + deque append per event, no locks on the append path)
and bounded (default 4096 events, oldest evicted, evictions counted), so
the last N seconds of engine decisions are ALWAYS reconstructable — the
stall watchdog (telemetry/watchdog.py), ``GET /debug/flight``, and
SIGUSR2 all dump it.

The :class:`CompileTracker` is the recompile-storm detector: on TPU a
request shape missing the bucket ladder triggers a multi-ten-second XLA
compile on the hot path (docs/perf_tuning.md warns; nothing detected
it). Every compiled-program entry point in ``engine/model_runner.py``
runs through ``track(program, key)``: the first dispatch of a distinct
(program, shape-bucket) key is a compile — its wall time is recorded,
it lands in the flight ring, and it increments
``dynamo_engine_xla_compiles_total{program,phase}`` where phase is
``startup`` before ``mark_serving_started()`` and ``late`` after. A
nonzero late-compile rate IS the storm signal (warmup should have swept
every serving shape).
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import List, Optional

from .tracing import span

logger = logging.getLogger(__name__)

FLIGHT_DIR_ENV = "DYN_FLIGHT_DIR"
FLIGHT_EVENTS_ENV = "DYN_FLIGHT_EVENTS"
DEFAULT_CAPACITY = 4096


class FlightRecorder:
    """Bounded ring of structured engine events.

    Append is O(1) and lock-free on CPython (``deque.append`` with a
    ``maxlen`` is atomic under the GIL; the monotonic ``appended``
    counter makes the eviction count derivable without coordination), so
    recording from the scheduler loop, executor threads (compile
    tracking during warmup), and transfer callbacks never contends.
    ``snapshot()`` is the only reader and copies the ring atomically.
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get(FLIGHT_EVENTS_ENV, "")
                               or DEFAULT_CAPACITY)
            except ValueError:
                capacity = DEFAULT_CAPACITY
        self.capacity = max(16, capacity)
        self._ring: "collections.deque" = collections.deque(
            maxlen=self.capacity)
        self._seq = itertools.count()
        self.appended = 0  # lifetime events; dropped = appended - len(ring)

    @property
    def dropped(self) -> int:
        """Events evicted by the ring bound (oldest-first, like
        TraceRecorder's drop-and-count — except here the NEWEST survive:
        a flight recorder's job is the moments before the crash)."""
        return max(0, self.appended - len(self._ring))

    def record(self, kind: str, request_id: Optional[str] = None,
               trace_id: Optional[str] = None, **data) -> None:
        """Append one event. Never raises, never blocks, never touches
        the event loop — safe from any thread, any layer."""
        evt = {
            "seq": next(self._seq),
            "t": time.monotonic(),
            "wall": time.time(),
            "kind": kind,
        }
        if request_id is not None:
            evt["request_id"] = request_id
        if trace_id is not None and trace_id != request_id:
            evt["trace_id"] = trace_id
        if data:
            evt["data"] = data
        self.appended += 1
        self._ring.append(evt)

    def snapshot(self, request_id: Optional[str] = None,
                 n: Optional[int] = None) -> List[dict]:
        """Chronological copy of the ring, optionally filtered to one
        request id and/or capped to the most recent ``n``."""
        events = list(self._ring)  # atomic under the GIL
        if request_id is not None:
            events = [
                e for e in events
                if e.get("request_id") == request_id
                or e.get("trace_id") == request_id
            ]
        if n is not None:
            events = events[-n:]
        return events

    def clear(self) -> None:
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)


# the process-wide recorder every component records into by default;
# tests inject private recorders instead of resetting this one
_GLOBAL = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _GLOBAL


class CompileTracker:
    """Detects and times XLA/Mosaic compiles at the dispatch seam.

    jit compiles happen synchronously inside the first call with a new
    static shape, so the first dispatch of a distinct (program,
    shape-bucket key) IS the compile and its wall time is dominated by
    it. The tracker keeps the seen-key set (one lock, held only for the
    membership test — warmup runs in an executor thread while serving
    dispatches from the loop) and classifies each compile by phase:
    ``startup`` until ``mark_serving_started()``, ``late`` after. Late
    compiles are the recompile-storm signal and additionally log a
    warning with the offending shape key.
    """

    def __init__(self, flight: Optional[FlightRecorder] = None,
                 registry=None):
        from .registry import MetricsRegistry

        self.flight = flight if flight is not None else flight_recorder()
        # private registry by default; the scheduler / prefill worker
        # attach it so the compile series render in the engine's scrape
        self.registry = registry or MetricsRegistry()
        self._compiles = self.registry.counter(
            "dynamo_engine_xla_compiles_total",
            "Compiled-program builds, labelled program= and phase="
            "startup|late (late = after serving started: the "
            "recompile-storm signal — warmup should have swept every "
            "serving shape)",
        )
        self._duration = self.registry.histogram(
            "dynamo_engine_xla_compile_duration_seconds",
            "Wall time of each program compile (first dispatch of a "
            "distinct shape-bucket key), labelled program=",
        )
        self._lock = threading.Lock()
        self._seen: set = set()
        self._serving = False
        self.records: List[dict] = []  # every compile, for tests/debug
        self.late_compiles = 0
        # optional per-dispatch context hook (program name → context
        # manager): the engine installs ops.attention.route_program so
        # trace-time route records carry the program label
        self.dispatch_cm = None

    def mark_serving_started(self) -> None:
        """Compiles from now on are ``late`` — the engine is serving, so
        every further compile stalls a real request."""
        self._serving = True

    @property
    def serving(self) -> bool:
        return self._serving

    @contextmanager
    def track(self, program: str, key: str, **stats):
        """Wrap ONE dispatch of ``program`` at shape-bucket ``key``;
        records a compile iff this (program, key) was never dispatched.
        ``stats`` go onto the ``dispatch.<program>`` span beside ``key``
        (``arrays``: how many host arrays the call sends)."""
        hook = self.dispatch_cm
        # the profiler's trace gets the program's stable name: the
        # runtime's own host spans (PjitFunction, shard_args, the
        # transfers) nest inside it
        with span("dispatch." + program, key=key, **stats), (
                hook(program) if hook is not None else nullcontext()):
            with self._lock:
                first = (program, key) not in self._seen
                if first:
                    self._seen.add((program, key))
            if not first:
                yield False
                return
            yield from self._track_first(program, key)

    def _track_first(self, program: str, key: str):
        t0 = time.monotonic()
        try:
            yield True
        finally:
            dt = time.monotonic() - t0
            phase = "late" if self._serving else "startup"
            self._compiles.inc(program=program, phase=phase)
            self._duration.observe(dt, program=program)
            self.records.append({
                "program": program, "key": key, "phase": phase,
                "duration_s": dt,
            })
            self.flight.record(
                "xla.compile", program=program, key=key, phase=phase,
                duration_s=round(dt, 4),
            )
            if phase == "late":
                self.late_compiles += 1
                logger.warning(
                    "late XLA compile: program=%s key=%s took %.2fs on "
                    "the serving path — a request shape missed the "
                    "bucket ladder (see docs/perf_tuning.md)",
                    program, key, dt,
                )
