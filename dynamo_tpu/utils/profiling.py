"""Device profiling hooks: jax.profiler capture, on demand.

The reference measures performance externally (genai-perf, perf.sh —
SURVEY.md §5 notes no in-repo profiler integration); on TPU the
first-class tool is the XLA profiler, so this framework wires it in as
part of the serving surface:

- ``enable_profiler_server(port)`` starts jax's profiler gRPC server —
  TensorBoard (or ``jax.profiler.trace_remote``) can then capture traces
  from a live worker, the standard remote-capture workflow.
- ``capture_trace(out_dir, seconds)`` records a trace window in-process
  (device activity, HLO annotations, the program's own host spans; the
  Python tracer is off) — the engine's HTTP service
  exposes it at ``GET /debug/profile`` when ``--profile-dir`` is set, so
  an operator can grab a trace of live traffic with one curl.

Both are thin wrappers so non-serving code (tests) can reuse the same
entry points.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import os
import threading
import time
import uuid

logger = logging.getLogger(__name__)

_server_started = False

# jax.profiler.trace is NOT reentrant: a second trace starting while one
# is active crashes mid-capture (and can corrupt the first capture's
# output). Every capture path — GET /debug/profile, an incident bundle's
# --incident-profile-s window — funnels through this
# process-wide lock; a loser gets CaptureBusyError (→ a clean 409 /
# "skipped" note) instead of a crash.
_capture_lock = threading.Lock()


class CaptureBusyError(RuntimeError):
    """Another profiler capture is already in flight in this process."""

# per-process capture counter: two captures in the same SECOND used to
# collide (strftime has second resolution) and exist_ok=True silently
# merged their trace files into one unreadable directory
_capture_seq = itertools.count()


def trace_dir_name() -> str:
    """Unique-per-capture directory name: timestamp (human ordering) +
    process-local counter (same-second captures in one process) + pid +
    random suffix (same-second captures across processes sharing the
    profile dir)."""
    return (
        time.strftime("trace-%Y%m%d-%H%M%S")
        + f"-{os.getpid()}-{next(_capture_seq):04d}-{uuid.uuid4().hex[:6]}"
    )


def enable_profiler_server(port: int) -> None:
    """Start the jax profiler gRPC server (idempotent; once per process)."""
    global _server_started
    if _server_started:
        return
    import jax

    jax.profiler.start_server(port)
    _server_started = True
    logger.info("jax profiler server on port %d (TensorBoard-capturable)", port)


def start_capture(trace_dir: str) -> None:
    """Start the profiler's trace into ``trace_dir`` and lay the
    program's clock on the capture's: the first event written is the
    span ``clock.mark`` with the stat ``monotonic_ns``, the value of
    ``time.monotonic_ns()`` at the span's start. Every
    ``time.monotonic()`` stamp of the program (the scheduler's fetch
    parts, the request trace's marks, a load generator's ``t0``) is then
    at ``mark.start + (stamp - monotonic_ns)`` on the capture's axis.
    The caller holds the capture lock and stops the trace."""
    import jax

    from ..telemetry.tracing import span

    # the Python tracer hooks every call of the scheduler's loop,
    # the thing an operator wants to see undisturbed (11 MB a second
    # of capture with it on; about 6 MB for 4 s without). The host
    # tracer keeps the program's own spans (telemetry/tracing.span)
    # and the runtime's, on the device planes' clock.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with span("clock.mark", monotonic_ns=time.monotonic_ns()):
        pass


def capture_trace(out_dir: str, seconds: float) -> str:
    """Record a profiler trace window; returns the trace directory.

    Blocking — run it in an executor from async code. Each capture lands
    in a timestamped subdirectory so consecutive captures never collide.
    Raises :class:`CaptureBusyError` when another capture holds the
    process-wide profiler lock (jax allows ONE active trace per process).
    """
    import jax

    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusyError(
            "a profiler capture is already in flight in this process")
    try:
        trace_dir = os.path.join(out_dir, trace_dir_name())
        # exist_ok=False on purpose: a collision must fail loudly instead
        # of silently merging two captures into one directory
        os.makedirs(trace_dir)
        start_capture(trace_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return trace_dir
    finally:
        _capture_lock.release()


async def capture_trace_async(out_dir: str, seconds: float) -> str:
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(None, capture_trace, out_dir, seconds)
