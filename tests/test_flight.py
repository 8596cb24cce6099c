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
