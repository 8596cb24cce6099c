"""Flight recorder + XLA compile observability (telemetry/flight.py),
the flightdump pretty-printer, and the profiler capture-dir fix.

The compile-storm acceptance test drives a REAL ModelRunner on CPU: two
request shapes missing the warmed bucket set after serving start must
produce exactly two ``late`` compile events — the recompile-storm
signal docs/perf_tuning.md warns about but nothing previously detected.
"""

import json
import os
import sys

import numpy as np
import pytest

from dynamo_tpu.telemetry.flight import (
    CompileTracker,
    FlightRecorder,
    flight_recorder,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# FlightRecorder: the ring itself
# --------------------------------------------------------------------------


def test_ring_is_bounded_and_counts_drops():
    fr = FlightRecorder(capacity=16)
    for i in range(50):
        fr.record("test.event", request_id=f"r{i}", i=i)
    assert len(fr) == 16
    assert fr.dropped == 34
    assert fr.appended == 50
    events = fr.snapshot()
    # newest survive (the moments before a crash are the valuable ones)
    assert [e["data"]["i"] for e in events] == list(range(34, 50))
    # chronological + monotonic stamps
    assert all(a["t"] <= b["t"] for a, b in zip(events, events[1:]))
    assert all(a["seq"] < b["seq"] for a, b in zip(events, events[1:]))


def test_snapshot_filters_by_request_and_trace_id():
    fr = FlightRecorder(capacity=64)
    fr.record("a", request_id="req-1")
    fr.record("b", request_id="req-2", trace_id="trace-x")
    fr.record("c")  # no id at all
    assert [e["kind"] for e in fr.snapshot(request_id="req-1")] == ["a"]
    # trace ids match too (the operator usually has the X-Request-Id)
    assert [e["kind"] for e in fr.snapshot(request_id="trace-x")] == ["b"]
    assert len(fr.snapshot()) == 3
    assert fr.snapshot(n=1)[-1]["kind"] == "c"


def test_global_recorder_is_a_singleton():
    assert flight_recorder() is flight_recorder()


def test_capacity_env_override(monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT_EVENTS", "128")
    assert FlightRecorder().capacity == 128
    monkeypatch.setenv("DYN_FLIGHT_EVENTS", "not-a-number")
    assert FlightRecorder().capacity == 4096  # default, not a crash


# --------------------------------------------------------------------------
# CompileTracker: first-dispatch-per-key detection + phase classification
# --------------------------------------------------------------------------


def test_compile_tracker_counts_first_dispatch_per_key_only():
    fr = FlightRecorder(capacity=64)
    tracker = CompileTracker(flight=fr)
    with tracker.track("prefill", "b2_s64") as first:
        assert first
    with tracker.track("prefill", "b2_s64") as first:
        assert not first
    with tracker.track("prefill", "b2_s128") as first:
        assert first
    assert [r["key"] for r in tracker.records] == ["b2_s64", "b2_s128"]
    assert all(r["phase"] == "startup" for r in tracker.records)
    assert tracker.late_compiles == 0

    tracker.mark_serving_started()
    with tracker.track("decode", "b2_s1"):
        pass
    assert tracker.records[-1]["phase"] == "late"
    assert tracker.late_compiles == 1
    # compile events land in the flight ring with their phase
    kinds = [e for e in fr.snapshot() if e["kind"] == "xla.compile"]
    assert len(kinds) == 3
    assert kinds[-1]["data"]["phase"] == "late"
    # and in the exposition, labelled program+phase
    text = tracker.registry.render()
    assert ('dynamo_engine_xla_compiles_total'
            '{phase="late",program="decode"} 1.0') in text
    assert ('dynamo_engine_xla_compiles_total'
            '{phase="startup",program="prefill"} 2.0') in text
    assert "dynamo_engine_xla_compile_duration_seconds_bucket" in text


def test_attention_route_counter_rides_the_dispatch_hook():
    """record_route() must attribute routes to the program whose tracked
    dispatch is on the stack (ops.attention.route_program installed as
    CompileTracker.dispatch_cm) — and, routes being TRACE-time facts,
    count once per compiled specialization, not once per step."""
    from dynamo_tpu.ops import attention as attn

    def count(program, route):
        key = (("program", program), ("route", route))
        return attn.ATTENTION_ROUTE_COUNTER.values.get(key, 0.0)

    tracker = CompileTracker(flight=FlightRecorder())
    tracker.dispatch_cm = attn.route_program

    base = count("decode", "sp_ring_kernel")
    for _ in range(3):  # repeat dispatches: only the first one traces
        with tracker.track("decode", "b2_s1") as first:
            if first:  # the dispatch seams record inside the trace
                attn.record_route("sp_ring_kernel")
    assert count("decode", "sp_ring_kernel") == base + 1
    # the tracked dispatches themselves stay startup-phase compiles
    assert all(r["phase"] == "startup" for r in tracker.records)
    # the hook restores its previous label on exit
    base_u = count("unknown", "xla")
    attn.record_route("xla")
    assert count("unknown", "xla") == base_u + 1

    # engine wiring: every runner installs the hook and registers the
    # singleton into its compile registry (the engine scrape), once
    runner, _ = _tiny_runner()
    assert runner.compiles.dispatch_cm is attn.route_program
    assert (attn.ATTENTION_ROUTE_COUNTER.name
            in runner.compiles.registry.names())
    runner2, _ = _tiny_runner()  # re-registration must not duplicate
    assert runner2.compiles.registry.names().count(
        attn.ATTENTION_ROUTE_COUNTER.name) <= 1


# --------------------------------------------------------------------------
# the compile-storm acceptance test: real runner, real compiles
# --------------------------------------------------------------------------


def _tiny_runner():
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.model_runner import ModelRunner

    cfg = EngineConfig(
        model=ModelConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=2, num_kv_heads=1,
        ),
        max_batch_size=2, max_model_len=128, kv_block_size=8,
        num_kv_blocks=32, dtype="float32",
    )
    return ModelRunner(cfg), cfg


def _dispatch(runner, b, s, w):
    import jax

    z2 = np.zeros((b, s), np.int32)
    runner.step(
        z2, z2, np.zeros((b, w), np.int32), np.full((b, s), -1, np.int32),
        np.ones(b, np.int32), np.zeros(b, np.int32),
        np.zeros(b, np.float32), np.zeros(b, np.int32),
        np.ones(b, np.float32), jax.random.PRNGKey(0),
    )


def test_compile_storm_two_unseen_buckets_after_serving_start():
    runner, cfg = _tiny_runner()
    fr = FlightRecorder(capacity=256)
    runner.compiles.flight = fr
    b = cfg.max_batch_size
    w = cfg.blocks_per_seq

    # "warmup": one prefill bucket compiled before serving starts
    _dispatch(runner, b, 64, w)
    assert [r["phase"] for r in runner.compiles.records] == ["startup"]

    runner.compiles.mark_serving_started()

    # the storm: two request shapes that missed the warmed ladder
    _dispatch(runner, b, 128, w)   # unseen prefill bucket
    _dispatch(runner, b, 1, w)     # unseen decode shape
    # …and a repeat of an already-compiled shape, which must NOT count
    _dispatch(runner, b, 64, w)

    late = [r for r in runner.compiles.records if r["phase"] == "late"]
    assert len(late) == 2, late
    assert {r["program"] for r in late} == {"prefill", "decode"}
    assert all(r["duration_s"] > 0 for r in late)
    ring_late = [
        e for e in fr.snapshot()
        if e["kind"] == "xla.compile" and e["data"]["phase"] == "late"
    ]
    assert len(ring_late) == 2
    text = runner.compiles.registry.render()
    assert ('dynamo_engine_xla_compiles_total'
            '{phase="late",program="prefill"} 1.0') in text
    assert ('dynamo_engine_xla_compiles_total'
            '{phase="late",program="decode"} 1.0') in text


def test_scheduler_attaches_compile_registry_and_marks_serving():
    """The engine scrape must carry the runner's compile series, and
    Scheduler.start() must flip the late-compile phase."""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.scheduler import Scheduler

    class RunnerStub:
        compiles = CompileTracker(flight=FlightRecorder())

        def gather_blocks_device(self, ids):  # host-tier hook, unused
            raise NotImplementedError

    cfg = EngineConfig(
        model=ModelConfig(vocab_size=64), max_batch_size=2,
        max_model_len=64, kv_block_size=8, num_kv_blocks=16,
    )
    sched = Scheduler(RunnerStub(), cfg, flight=FlightRecorder())
    assert "dynamo_engine_xla_compiles_total" in sched.registry.names()

    async def go():
        sched.start()
        assert RunnerStub.compiles.serving
        await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()


# --------------------------------------------------------------------------
# a first dispatch in its parts (ISSUE 50): jax.monitoring's events while
# the dispatch is open on this thread
# --------------------------------------------------------------------------

PARTS = ("trace_s", "lower_s", "load_s", "compile_s", "rest_s")


def _nested_program(scale):
    """A fresh ``jit`` that calls a fresh inner ``jit`` (no trace of
    either is cached), over a shape unique to ``scale``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        for _ in range(20):
            x = jnp.sin(x) * scale + jnp.cos(x)
        return x

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 2.0) + scale

    return outer


def _trace_events():
    """Every ``jaxpr_trace_duration`` jax publishes, as (fun_name, s)."""
    import jax

    seen = []

    def listener(event, duration, **kw):
        if event.endswith("jaxpr_trace_duration"):
            seen.append((kw.get("fun_name"), duration))

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen, listener


def test_first_dispatch_parts_sum_to_its_duration():
    import jax
    import jax.numpy as jnp

    tracker = CompileTracker(flight=FlightRecorder())
    outer = _nested_program(3.0)
    x = jnp.ones((16, 31))
    seen, listener = _trace_events()
    try:
        with tracker.track("nested", "k0") as first:
            assert first
            outer(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    (rec,) = tracker.records
    assert all(rec[p] >= 0 for p in PARTS), rec
    assert sum(rec[p] for p in PARTS) == pytest.approx(rec["duration_s"])
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["compile_s"] > 0
    # the inner jit's trace lies inside the outer's: counted once
    by_name = dict(seen)
    assert by_name["inner"] > 0 and by_name["outer"] > by_name["inner"]
    assert (0.95 * by_name["outer"] <= rec["trace_s"]
            < by_name["inner"] + by_name["outer"])
    # the same record feeds the flight event and the counters
    evt = tracker.flight.snapshot()[-1]["data"]
    assert evt["cache"] == rec["cache"]
    assert all(evt[p] == round(rec[p], 4) for p in PARTS)
    text = tracker.registry.render()
    for part in ("trace", "lower", "load", "compile", "rest"):
        assert ('dynamo_engine_xla_compile_part_seconds_total{part="%s",'
                'phase="startup",program="nested"}' % part) in text
    assert 'key=' not in text


@pytest.fixture
def compile_cache(tmp_path):
    """jax's persistent cache in a directory of this test's own."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", old[2])
    cc.reset_cache()


def test_first_dispatch_says_miss_then_hit(compile_cache):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((16, 37))

    def first_dispatch():
        tracker = CompileTracker(flight=FlightRecorder())
        with tracker.track("cached", "k0"):
            _nested_program(5.0)(x).block_until_ready()
        return tracker, tracker.records[0]

    tracker, cold = first_dispatch()
    assert cold["cache"] == "miss" and cold["compile_s"] > 0, cold
    assert cold["load_s"] == 0
    assert ('dynamo_engine_compile_cache_total{phase="startup",'
            'program="cached",result="miss"} 1.0') in tracker.registry.render()
    jax.clear_caches()
    tracker, warm = first_dispatch()
    assert warm["cache"] == "hit" and warm["load_s"] > 0, warm
    # the backend's timer holds the load and little else
    assert warm["compile_s"] < 0.25 * cold["compile_s"], (warm, cold)
    assert sum(warm[p] for p in PARTS) == pytest.approx(warm["duration_s"])
    assert ('dynamo_engine_compile_cache_total{phase="startup",'
            'program="cached",result="hit"} 1.0') in tracker.registry.render()


def test_no_cache_directory_reads_off():
    import jax
    import jax.numpy as jnp

    if jax.config.jax_compilation_cache_dir:
        pytest.skip("this process has a compile cache placed")
    tracker = CompileTracker(flight=FlightRecorder())
    with tracker.track("plain", "k0"):
        _nested_program(7.0)(jnp.ones((16, 41))).block_until_ready()
    assert tracker.records[0]["cache"] == "off"
    # a zero is a reading: neither result moved
    text = tracker.registry.render()
    for result in ("hit", "miss"):
        assert ('dynamo_engine_compile_cache_total{phase="startup",'
                'program="plain",result="%s"} 0.0' % result) in text


def test_two_threads_compiling_at_once_keep_their_events_apart(compile_cache):
    import threading

    import jax.numpy as jnp

    # made first: an eager ``ones`` of a new shape is a compile of its
    # own, outside any dispatch
    inputs = {(i, j): jnp.ones((16, 43 + 10 * i + j))
              for i in (0, 1) for j in range(i + 1)}
    trackers = [CompileTracker(flight=FlightRecorder()) for _ in range(2)]
    both_open = threading.Barrier(2)
    errors = []

    def compile_on(i):
        try:
            with trackers[i].track(f"thread{i}", "k0"):
                both_open.wait(timeout=30)
                for j in range(i + 1):   # thread 1 compiles two programs
                    _nested_program(11.0 + 10 * i + j)(
                        inputs[i, j]).block_until_ready()
        except Exception as e:   # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=compile_on, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    recs = [t.records[0] for t in trackers]
    for i, rec in enumerate(recs):
        assert rec["program"] == f"thread{i}"
        assert rec["lower_s"] > 0 and rec["compile_s"] > 0
        # its own events alone: never more than its own wall time
        assert sum(rec[p] for p in PARTS) == pytest.approx(rec["duration_s"])
        assert rec["rest_s"] >= 0
    # one program's miss on thread 0, two on thread 1, nothing outside
    for i, t in enumerate(trackers):
        text = t.registry.render()
        assert ('dynamo_engine_compile_cache_total{phase="startup",'
                'program="thread%d",result="miss"} %d.0' % (i, i + 1)) in text
        assert ',program="untracked"}' not in text


def test_events_outside_a_dispatch_are_untracked_and_a_seen_key_adds_nothing():
    import jax.numpy as jnp

    tracker = CompileTracker(flight=FlightRecorder())   # the tracker made last
    program = _nested_program(13.0)
    x = jnp.ones((16, 47))
    program(x).block_until_ready()            # compiles outside any track()
    text = tracker.registry.render()
    for part in ("trace", "lower", "compile"):
        line = ('dynamo_engine_xla_compile_part_seconds_total{part="%s",'
                'phase="startup_untracked",program="untracked"}' % part)
        assert line in text, text
    # no first dispatch, so no term of phase="startup", which sums to
    # warm-up's first dispatches
    assert 'phase="startup",' not in text
    assert not tracker.records
    with tracker.track("seen", "k0") as first:
        assert first
        program(x).block_until_ready()        # jit's fast path: no event
    before = tracker.registry.render()
    tracker.mark_serving_started()
    with tracker.track("seen", "k0") as first:
        assert not first
        _nested_program(17.0)(jnp.ones((16, 53))).block_until_ready()
    # the second dispatch of a seen key opens nothing: what compiled
    # inside it is a late compile outside track()
    assert len(tracker.records) == 1
    after = tracker.registry.render()
    assert ('dynamo_engine_xla_compile_part_seconds_total{part="compile",'
            'phase="late",program="untracked"}') in after
    seen_lines = [ln for ln in after.splitlines() if 'program="seen"' in ln]
    assert seen_lines == [ln for ln in before.splitlines()
                          if 'program="seen"' in ln]


def test_late_compile_log_line_says_its_parts(caplog):
    import logging

    import jax.numpy as jnp

    tracker = CompileTracker(flight=FlightRecorder())
    tracker.mark_serving_started()
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.telemetry.flight"):
        with tracker.track("late_one", "k0"):
            _nested_program(19.0)(jnp.ones((16, 59))).block_until_ready()
    (line,) = [r.getMessage() for r in caplog.records
               if "late XLA compile" in r.getMessage()]
    assert "program=late_one key=k0" in line
    for word in ("trace", "lower", "cache load", "compile", "rest", "cache "):
        assert word in line, line


# --------------------------------------------------------------------------
# satellite: profiler capture dirs can no longer collide
# --------------------------------------------------------------------------


def test_trace_dir_names_unique_within_one_second():
    from dynamo_tpu.utils.profiling import trace_dir_name

    # the old strftime-only name collided for any two captures in the
    # same second and exist_ok=True silently merged them
    names = {trace_dir_name() for _ in range(100)}
    assert len(names) == 100
    assert all(n.startswith("trace-") for n in names)
    assert all(f"-{os.getpid()}-" in n for n in names)


def test_capture_trace_rejects_collision(tmp_path, monkeypatch):
    """capture_trace must CREATE its directory (exist_ok=False): a name
    collision fails loudly instead of merging two captures."""
    from dynamo_tpu.utils import profiling

    monkeypatch.setattr(profiling, "trace_dir_name", lambda: "trace-fixed")
    made = profiling.capture_trace(str(tmp_path), 0.0)
    assert os.path.isdir(made)
    with pytest.raises(FileExistsError):
        profiling.capture_trace(str(tmp_path), 0.0)


# --------------------------------------------------------------------------
# satellite: scripts/flightdump.py renders artifacts readably
# --------------------------------------------------------------------------


def _sample_artifact():
    from dynamo_tpu.telemetry.watchdog import build_flight_artifact

    fr = FlightRecorder(capacity=32)
    fr.record("scheduler.admission", request_id="req-a", slot=0)
    fr.record("scheduler.burst_dispatch", rows=1, requests=["req-a"])
    fr.record("watchdog.trip", reason="decode_stall")
    return build_flight_artifact(reason="unit_test", flight=fr)


def test_flightdump_renders_event_table_and_stacks(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import flightdump
    finally:
        sys.path.pop(0)

    path = os.path.join(str(tmp_path), "artifact.json")
    with open(path, "w") as f:
        json.dump(_sample_artifact(), f, default=str)

    assert flightdump.main(["flightdump", path]) == 0
    out = capsys.readouterr().out
    assert "scheduler.admission" in out
    assert "req-a" in out
    assert "decode_stall" in out
    assert "--- thread" in out  # stack section
    assert "reason=unit_test" in out

    # per-request filtering: only req-a's events survive
    assert flightdump.main(
        ["flightdump", path, "--request", "req-a", "--no-stacks"]
    ) == 0
    out = capsys.readouterr().out
    assert "scheduler.admission" in out
    assert "watchdog.trip" not in out
    assert "--- thread" not in out

    # unreadable artifact is a clean exit-2, not a stack trace
    assert flightdump.main(
        ["flightdump", os.path.join(str(tmp_path), "missing.json")]
    ) == 2
