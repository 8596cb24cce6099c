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
every serving shape). What jax itself times inside such a first dispatch
(tracing, lowering, the load from the persistent cache, the compile) is
added to its record from ``jax.monitoring``'s events.

The :class:`StartupTimeline` is the engine's start as one ordered list
of marks on ``time.monotonic()``, from the package's import to the HTTP
service listening: ``dynamo_engine_startup_seconds{phase}``, the marks'
own gauge and the ``startup`` record are all written from that list.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import threading
import time
import weakref
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Tuple

from .. import T_IMPORT
from .tracing import span, span_breakdown

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


# --------------------------------------------------------------------------
# a first dispatch in its parts: what jax.monitoring says while one is open
# --------------------------------------------------------------------------

PARTS = ("trace", "lower", "load", "compile", "rest")
_PART_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RESULT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
UNTRACKED = "untracked"

# per thread (the target's and the draft's warm-up compile in two
# executor threads at once): ``open`` is (tracker, record) of the first
# dispatch open on this thread, ``closed`` the events that no later
# event has been found to hold
_tls = threading.local()
# one trace of a step program holds a few hundred small traces (every
# ``jnp`` call is a ``jit``), each kept until its holder closes
_CLOSED_MAX = 1 << 14
_listen_lock = threading.Lock()
_listening = False
# the tracker made last: an event with no open first dispatch on its
# thread (weight init's helper jits, a late compile outside ``track``)
# is counted there, under program="untracked" (``_phase_of``)
_latest: Optional["weakref.ref"] = None


def _self_seconds(duration: float) -> float:
    """``duration`` less the events that closed inside it on this thread.

    jax reports an event when it ends, with its duration. Events nest:
    an inner ``jit``'s trace lies inside its caller's, a hit's retrieval
    inside ``backend_compile_duration``, a lowering rule's own traces
    inside the lowering. An event that began after this one began lies
    inside it, so its seconds are taken off this one's and each second
    is counted once, under the innermost event that held it."""
    closed = _tls.__dict__.setdefault("closed", [])
    start = time.monotonic() - duration
    inner = 0.0
    while closed and closed[-1][0] >= start:
        inner += closed.pop()[1]
    closed.append((start, duration))
    if len(closed) > _CLOSED_MAX:   # outside a dispatch nothing resets it
        del closed[:_CLOSED_MAX // 2]
    return max(0.0, duration - inner)


def _on_duration(event: str, duration: float, **_kw) -> None:
    part = _PART_OF.get(event)
    if part is None:
        return
    seconds = _self_seconds(duration)
    opened = getattr(_tls, "open", None)
    if opened is not None:
        opened[1][part + "_s"] += seconds
        return
    tracker = _latest() if _latest is not None else None
    if tracker is not None:
        tracker._count_part(UNTRACKED, part, seconds)


def _on_event(event: str, **_kw) -> None:
    result = _CACHE_RESULT.get(event)
    if result is None:
        return
    opened = getattr(_tls, "open", None)
    if opened is not None:
        tracker, rec = opened
        if rec["cache"] != "miss":   # one program of several missed: a miss
            rec["cache"] = result
        tracker._count_cache(rec["program"], result)
        return
    tracker = _latest() if _latest is not None else None
    if tracker is not None:
        tracker._count_cache(UNTRACKED, result)


def _listen() -> None:
    """Register the two listeners, once a process. They fire only while
    jax traces, lowers or compiles: a dispatch on jit's fast path makes
    no event."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        try:
            from jax import monitoring
        except ImportError:   # a frontend alone: nothing compiles here
            return
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listening = True


# --------------------------------------------------------------------------
# the start-up timeline
# --------------------------------------------------------------------------

# the marks every engine of this process shares, stamped before any
# engine exists: the package's import (``dynamo_tpu/__init__.py``) and
# the backend coming up (``engine/device.check_serving_device``)
_PROCESS_MARKS: List[Tuple[str, float]] = [("import", T_IMPORT)]


def mark_process(name: str) -> None:
    """Stamp a mark of the process, once: the first call wins."""
    if all(n != name for n, _ in _PROCESS_MARKS):
        _PROCESS_MARKS.append((name, time.monotonic()))


class StartupTimeline:
    """One engine's start as ordered marks ``(name, t_monotonic)``, each
    stamped where its work ends: ``import``, ``backend``, ``model_card``,
    ``device_init``, ``weights``, ``kv_cache``, ``runner``, ``engine``,
    ``warmup``, ``scheduler``, ``listening`` (docs/observability.md).
    ``time.monotonic()`` is the clock of the request records'
    ``t0_monotonic`` and of a load generator on the same machine.

    A phase is the time from the mark before it to its own
    (``tracing.span_breakdown``), so the phases after ``import`` sum to
    the last mark less ``import``. ``mark`` writes the phase into
    ``seconds`` (``ModelRunner.startup_s``) and into
    ``dynamo_engine_startup_seconds{phase}``, and the mark's own time
    into ``dynamo_engine_startup_mark_monotonic_seconds{mark}``; a name
    is stamped once. ``within`` sets a stretch that lies inside or across
    the phases and is no term of their sum (``warmup_wait``, ``serve``).
    """

    def __init__(self, registry, programs: List[dict]):
        # the tracker's ``records``: every first dispatch, as it is added
        self.programs = programs
        self._seconds = registry.gauge(
            "dynamo_engine_startup_seconds",
            "Wall time of each start-up phase, set once: the time from "
            "the mark before it, phase=backend|model_card|device_init|"
            "weights|kv_cache|runner|engine|warmup|scheduler|listening; "
            "beside them warmup_wait (inside warmup: all but its first "
            "dispatches, which dynamo_engine_xla_compile_part_seconds_"
            "total splits) and serve (scheduler + listening)",
        )
        self._at = registry.gauge(
            "dynamo_engine_startup_mark_monotonic_seconds",
            "time.monotonic() at each start-up mark, set once: the clock "
            "of the request records' t0_monotonic, so mark=\"import\" is "
            "to this engine what process_start_time_seconds is to a "
            "process",
        )
        self.marks: List[Tuple[str, float]] = []
        self.seconds: Dict[str, float] = {}
        for name, t in _PROCESS_MARKS:
            self.mark(name, t)

    def mark(self, name: str, t: Optional[float] = None) -> None:
        """Stamp ``name`` now (or at ``t``), unless it is stamped."""
        if name in dict(self.marks):
            return
        t = time.monotonic() if t is None else t
        self.marks.append((name, t))
        self._at.set(t, mark=name)
        if len(self.marks) > 1:
            self.within(name, self.spans()[-1]["duration_s"])

    def within(self, name: str, seconds: float) -> None:
        self.seconds[name] = seconds
        self._seconds.set(seconds, phase=name)

    def spans(self) -> List[dict]:
        """The phases so far, each named by the mark that closes it."""
        return span_breakdown(self.marks, self.marks[-1][1])[:-1]

    def record(self) -> dict:
        """The ``startup`` record of the ``DYN_TRACE_JSONL`` sink and of
        the ``engine start-up:`` log line: the marks as spans from
        ``import`` on, and every first dispatch so far in its parts."""
        return {
            "request_id": "startup",
            "time": time.time(),
            "t0_monotonic": self.marks[0][1],
            "total_s": round(self.marks[-1][1] - self.marks[0][1], 6),
            "spans": self.spans(),
            "programs": list(self.programs),
        }


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

    While a first dispatch is open on a thread, the events
    ``jax.monitoring`` publishes on that thread are added to its record:
    ``trace_s`` (Python tracing), ``lower_s`` (jaxpr to MLIR, the Pallas
    kernels' lowering in it), ``load_s`` (the executable read from the
    persistent cache, on a hit), ``compile_s`` (the backend's compile
    less that load: about 0 on a hit) and ``rest_s``, what jax does not
    time (argument transfer, the executable onto the device, the
    enqueue). Each second is counted once, so the five sum to
    ``duration_s``. ``cache`` is ``hit``, ``miss``, or ``off`` where jax
    did not ask the cache (none configured, or a program too small).
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
        self._parts = self.registry.counter(
            "dynamo_engine_xla_compile_part_seconds_total",
            "Seconds of first dispatches by part=trace|lower|load|"
            "compile|rest (jax.monitoring's events while the dispatch "
            "was open; rest is what jax does not time), labelled "
            "program= and phase=startup|late; program=\"untracked\" "
            "holds events outside any tracked dispatch (phase=startup_"
            "untracked before serving started: no part of warm-up's sum)",
        )
        self._cache = self.registry.counter(
            "dynamo_engine_compile_cache_total",
            "Programs jax looked up in the persistent compilation "
            "cache, by result=hit|miss, labelled program= and phase=",
        )
        global _latest
        _latest = weakref.ref(self)
        _listen()
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

    @property
    def _phase(self) -> str:
        return "late" if self._serving else "startup"

    def _phase_of(self, program: str) -> str:
        """``phase=`` of the two series by part: a first dispatch's own.
        What compiled outside any dispatch before serving started (weight
        init's helper jits, an eager ``zeros`` between two dispatches of
        warm-up) is ``startup_untracked``: it is no first dispatch, and
        ``phase="startup"`` sums to warm-up's first dispatches alone."""
        if program == UNTRACKED and not self._serving:
            return "startup_untracked"
        return self._phase

    def _count_part(self, program: str, part: str, seconds: float) -> None:
        self._parts.inc(seconds, program=program, part=part,
                        phase=self._phase_of(program))

    def _count_cache(self, program: str, result: str) -> None:
        self._cache.inc(program=program, result=result,
                        phase=self._phase_of(program))

    def _track_first(self, program: str, key: str):
        rec = {"program": program, "key": key, "phase": "", "duration_s": 0.0,
               "trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0,
               "compile_s": 0.0, "cache": "off", "rest_s": 0.0}
        outer, _tls.open = getattr(_tls, "open", None), (self, rec)
        t0 = time.monotonic()
        try:
            yield True
        finally:
            dt = time.monotonic() - t0
            _tls.open = outer
            _tls.closed = []   # no later event holds what closed in here
            phase = rec["phase"] = self._phase
            rec["duration_s"] = dt
            named = sum(rec[p + "_s"] for p in PARTS[:-1])
            if named > dt:   # jax's clock against ours: never more than dt
                for p in PARTS[:-1]:
                    rec[p + "_s"] *= dt / named
                named = dt
            rec["rest_s"] = dt - named
            self._compiles.inc(program=program, phase=phase)
            self._duration.observe(dt, program=program)
            for p in PARTS:
                self._count_part(program, p, rec[p + "_s"])
            # a zero is a reading too: a warm start's misses
            for result in _CACHE_RESULT.values():
                self._cache.inc(0.0, program=program, result=result,
                                phase=phase)
            self.records.append(rec)
            parts = {p + "_s": round(rec[p + "_s"], 4) for p in PARTS}
            self.flight.record(
                "xla.compile", program=program, key=key, phase=phase,
                duration_s=round(dt, 4), cache=rec["cache"], **parts,
            )
            if phase == "late":
                self.late_compiles += 1
                logger.warning(
                    "late XLA compile: program=%s key=%s took %.2fs "
                    "(trace %.2f, lower %.2f, cache load %.2f, compile "
                    "%.2f, rest %.2f; cache %s) on the serving path — a "
                    "request shape missed the bucket ladder (see "
                    "docs/perf_tuning.md)",
                    program, key, dt, *(rec[p + "_s"] for p in PARTS),
                    rec["cache"],
                )
