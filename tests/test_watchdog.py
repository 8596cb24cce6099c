"""Stall-watchdog contract (telemetry/watchdog.py).

No-false-positive half: an idle engine (empty queue) and a legitimately
long prefill/decode (slow-but-progressing host syncs, healthy remote
waits) must NOT trip. Detection half: a fake-runner decode loop
artificially wedged mid-burst (the host sync never returns — the
executor-side shape of a hung Mosaic compile or dead device) MUST trip
within the configured deadline, and the dumped artifact must carry the
wedged request's last flight events, all-thread stacks, the active
request table, and a metrics snapshot — on disk AND at
``GET /debug/flight``.
"""

import asyncio
import json
import os
import threading
import uuid

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext
from dynamo_tpu.telemetry.flight import FlightRecorder
from dynamo_tpu.telemetry.watchdog import StallWatchdog

from test_decode_pipeline import FakeRunner


# --------------------------------------------------------------------------
# probe-level unit contract
# --------------------------------------------------------------------------


def _probe(heartbeat=None, steps=0, depth=0, remote=0, active=0,
           stopping=False):
    import time

    hb = heartbeat if heartbeat is not None else time.monotonic()
    return {
        "heartbeat_t": hb, "steps": steps, "queue_depth": depth,
        "pending_remote": remote, "active": active, "stopping": stopping,
    }


def _run_watchdog(probe_fn, cycles=8, interval=0.03, stall=0.1, **kw):
    async def go():
        wd = StallWatchdog(
            probe_fn, interval_s=interval, stall_s=stall,
            flight=FlightRecorder(), **kw,
        ).start()
        await asyncio.sleep(interval * cycles + stall)
        await wd.stop()
        return wd

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


def test_idle_engine_with_stale_heartbeat_never_trips():
    # an idle loop parks on wake.wait(): heartbeat arbitrarily old, but
    # with NO pending work that is rest, not a stall
    wd = _run_watchdog(lambda: _probe(heartbeat=0.0))
    assert wd.trips == []
    assert wd.loop_lag_s < 1.0  # lag gauge sampled, loop healthy


def test_healthy_remote_prefill_wait_never_trips_no_throughput():
    # pending remote prefills poll on a fresh heartbeat with frozen
    # steps: the remote deadline machinery owns that wait, not us
    wd = _run_watchdog(lambda: _probe(steps=7, remote=3))
    assert wd.trips == []


def test_stale_heartbeat_with_pending_work_trips_decode_stall_once():
    wd = _run_watchdog(lambda: _probe(heartbeat=0.0, active=1, steps=4),
                       cycles=16)
    # edge-triggered: one persistent wedge = ONE trip, not one per cycle
    assert [t["reason"] for t in wd.trips] == ["decode_stall"]
    text = wd.registry.render()
    assert ('dynamo_watchdog_trips_total{reason="decode_stall"} 1.0'
            in text)
    assert "dynamo_runtime_event_loop_lag_seconds" in text
    # the trip landed in the flight ring too
    assert any(e["kind"] == "watchdog.trip" for e in wd.flight.snapshot())


def test_frozen_steps_with_queued_work_trips_no_throughput():
    # fresh heartbeat (the loop spins) but the dispatch counter never
    # moves while requests queue: starved admission. The sampler's check
    # is stepped on a clock of the test's own, twenty samples 30 ms
    # apart: on a loaded machine a real 30 ms sleep can outlast stall_s
    # and add an event_loop_lag trip that is the machine's, not the
    # probe's
    clock = {"now": 1000.0}
    wd = StallWatchdog(
        lambda: _probe(heartbeat=clock["now"], steps=42, depth=2),
        interval_s=0.03, stall_s=0.1, flight=FlightRecorder())

    async def go():
        for _ in range(20):
            clock["now"] += 0.03
            await wd._check(clock["now"])

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
    assert [t["reason"] for t in wd.trips] == ["no_throughput"]


def test_idle_gap_then_arrival_does_not_instantly_trip_no_throughput():
    """Steps frozen through a long idle period, then work arrives: the
    starvation clock must restart at arrival (it re-stamps while the
    queue is empty) — only a queue that STAYS starved past the deadline
    trips."""
    state = {"depth": 0}

    def probe():
        return _probe(steps=10, depth=state["depth"])

    async def go():
        wd = StallWatchdog(probe, interval_s=0.03, stall_s=0.15,
                           flight=FlightRecorder()).start()
        await asyncio.sleep(0.5)   # idle far beyond stall_s, steps frozen
        state["depth"] = 2         # burst of work arrives
        await asyncio.sleep(0.09)  # well under stall_s since arrival
        early = list(wd.trips)
        await asyncio.sleep(0.5)   # now genuinely starved
        await wd.stop()
        return early, list(wd.trips)

    loop = asyncio.new_event_loop()
    try:
        early, late = loop.run_until_complete(go())
    finally:
        loop.close()
    assert early == [], "tripped instantly on arrival after an idle gap"
    assert [t["reason"] for t in late] == ["no_throughput"]


def test_advancing_steps_never_trip():
    counter = {"steps": 0}

    def probe():
        counter["steps"] += 1  # every sample sees progress
        return _probe(steps=counter["steps"], depth=2, active=1)

    wd = _run_watchdog(probe, cycles=16)
    assert wd.trips == []


def test_stopping_engine_never_trips():
    wd = _run_watchdog(lambda: _probe(heartbeat=0.0, active=3,
                                      stopping=True))
    assert wd.trips == []


def test_flaky_probe_does_not_kill_the_watchdog():
    calls = {"n": 0}

    def probe():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("scrape race")
        return _probe(heartbeat=0.0, active=1)

    wd = _run_watchdog(probe, cycles=16)
    assert calls["n"] > 3  # survived the failures and kept sampling
    assert [t["reason"] for t in wd.trips] == ["decode_stall"]


# --------------------------------------------------------------------------
# scheduler-level: no false positives on real (fake-runner) engines
# --------------------------------------------------------------------------


class _SlowArray:
    """Device-array stand-in whose host sync takes ``delay`` seconds —
    runs in the scheduler's executor, so the loop stays free (the shape
    of a legitimately slow device)."""

    def __init__(self, arr, delay):
        self._arr = np.asarray(arr)
        self._delay = delay

    def __array__(self, dtype=None):
        import time

        time.sleep(self._delay)
        a = self._arr
        return a.astype(dtype) if dtype is not None else a

    def __getitem__(self, item):
        return _SlowArray(self._arr[item], self._delay)


class _WedgeableRunner(FakeRunner):
    """FakeRunner whose decode host-syncs can be slowed or wedged.

    ``sync_delay`` makes every decode sync take that long (legitimately
    slow). ``wedge_after`` wedges the Nth decode burst's sync on an
    Event that only the test releases — the executor-side shape of a
    hung compile / dead device, mid-burst."""

    def __init__(self, config, sync_delay=0.0, wedge_after=None):
        super().__init__(config)
        self.sync_delay = sync_delay
        self.wedge_after = wedge_after
        self.release = threading.Event()
        self.wedged = threading.Event()  # test observability

    def decode_burst(self, *args, **kw):
        out = super().decode_burst(*args, **kw)
        if (self.wedge_after is not None
                and self.burst_calls > self.wedge_after):
            runner = self

            class _Wedged(_SlowArray):
                def __array__(self, dtype=None):
                    runner.wedged.set()
                    runner.release.wait()
                    return super().__array__(dtype)

            return tuple(_Wedged(a, 0.0) for a in out)
        if self.sync_delay:
            return tuple(_SlowArray(a, self.sync_delay) for a in out)
        return out


def _request(prompt, max_tokens):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[],
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


def _config(**kw):
    kw.setdefault("num_kv_blocks", 64)
    kw.setdefault("max_model_len", 256)
    # fused bursts: idle-runner decode rides decode_burst, which is the
    # seam _WedgeableRunner slows/wedges
    kw.setdefault("multi_step_decode", 4)
    return EngineConfig(
        model=ModelConfig(vocab_size=512, hidden_size=32,
                          intermediate_size=64, num_layers=1, num_heads=2,
                          num_kv_heads=1),
        max_batch_size=4, kv_block_size=8, dtype="float32",
        enable_prefix_caching=False, **kw,
    )


async def _collect(er):
    toks = []
    while True:
        out = await er.out_queue.get()
        if out is None:
            return toks
        toks.extend(out.token_ids)


def test_idle_scheduler_never_trips():
    config = _config()

    async def go():
        runner = FakeRunner(config)
        sched = Scheduler(runner, config, flight=FlightRecorder())
        sched.start()
        wd = StallWatchdog(
            probe=sched.watchdog_probe, requests=sched.request_table,
            flight=sched.flight, interval_s=0.02, stall_s=0.1,
        ).start()
        await asyncio.sleep(0.5)  # way past the deadline, zero work
        trips = list(wd.trips)
        await wd.stop()
        await sched.stop()
        return trips

    loop = asyncio.new_event_loop()
    try:
        assert loop.run_until_complete(go()) == []
    finally:
        loop.close()


def test_long_prefill_and_slow_decode_do_not_trip():
    """Work that takes many times the stall deadline overall — a long
    chunked prefill + per-pass decode syncs slower than the sampling
    interval — must not trip: every pass re-stamps the heartbeat and
    advances the step counter."""
    # 120-token prompt at <=16 computed tokens/step: 8+ prefill chunks
    config = _config(max_prefill_tokens_per_step=16,
                     prefill_buckets=[16, 32, 64, 128, 256])

    async def go():
        runner = _WedgeableRunner(config, sync_delay=0.05)
        sched = Scheduler(runner, config, flight=FlightRecorder())
        sched.start()
        wd = StallWatchdog(
            probe=sched.watchdog_probe, requests=sched.request_table,
            flight=sched.flight, interval_s=0.02, stall_s=0.25,
        ).start()
        er = _request(list(range(1, 121)), 12)
        sched.add_request(er)
        toks = await _collect(er)  # total runtime >> stall_s
        trips = list(wd.trips)
        await wd.stop()
        await sched.stop()
        return toks, trips

    loop = asyncio.new_event_loop()
    try:
        toks, trips = loop.run_until_complete(go())
    finally:
        loop.close()
    assert len(toks) == 12
    assert trips == []


# --------------------------------------------------------------------------
# the wedge: trip + artifact, end to end (disk AND /debug/flight)
# --------------------------------------------------------------------------


def _drive_wedged_engine(tmp_path, stall_s=0.25):
    """Start a fake engine, wedge its 3rd decode burst mid-sync, let the
    watchdog trip, and return (trip list, artifact path, wedged request,
    scheduler, service port artifacts...). Shared by the disk and HTTP
    assertions."""
    config = _config()
    dump_dir = os.path.join(str(tmp_path), "flight")
    out = {}

    async def go():
        import aiohttp

        from dynamo_tpu.http.service import HttpService, ModelManager

        runner = _WedgeableRunner(config, wedge_after=2)
        flight = FlightRecorder()
        sched = Scheduler(runner, config, flight=flight)
        sched.start()
        wd = StallWatchdog(
            probe=sched.watchdog_probe, requests=sched.request_table,
            registry=sched.registry, flight=flight,
            interval_s=0.02, stall_s=stall_s, dump_dir=dump_dir,
        ).start()
        service = HttpService(ModelManager(), host="127.0.0.1", port=0)
        await service.start()

        er = _request([1, 17, 43], 64)
        sched.add_request(er)
        collector = asyncio.ensure_future(_collect(er))
        try:
            # the runner wedges its 3rd burst; the watchdog must trip
            # within its deadline + a few sampling intervals
            for _ in range(200):
                if wd.trips:
                    break
                await asyncio.sleep(0.05)
            out["trips"] = list(wd.trips)
            out["wedged"] = runner.wedged.is_set()
            out["request_id"] = er.request_id

            # the on-demand endpoint, while still wedged
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{service.port}/debug/flight"
                ) as r:
                    out["http_status"] = r.status
                    out["http_artifact"] = await r.json()
        finally:
            runner.release.set()  # un-wedge so everything drains
            await collector
            await wd.stop()
            await service.stop()
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
    return out, dump_dir


def test_wedged_decode_trips_and_dumps_artifact(tmp_path):
    out, dump_dir = _drive_wedged_engine(tmp_path)
    assert out["wedged"], "test is vacuous: the runner never wedged"
    reasons = [t["reason"] for t in out["trips"]]
    assert "decode_stall" in reasons, reasons
    rid = out["request_id"]

    # --- on-disk artifact ---
    files = sorted(os.listdir(dump_dir))
    assert files, "trip produced no artifact"
    with open(os.path.join(dump_dir, files[0])) as f:
        artifact = json.load(f)
    assert artifact["reason"] == "decode_stall"
    # the wedged request's last flight events are present
    mine = [e for e in artifact["events"] if e.get("request_id") == rid]
    assert any(e["kind"] == "scheduler.admission" for e in mine)
    dispatches = [
        e for e in artifact["events"]
        if e["kind"] == "scheduler.burst_dispatch"
        and rid in (e.get("data") or {}).get("requests", [])
    ]
    assert dispatches, "no dispatch event for the wedged request"
    # all-thread stacks include the executor thread stuck in the sync
    stacks = "\n".join(
        ln for th in artifact["threads"] for ln in th["stack"]
    )
    assert "__array__" in stacks
    # active request table names the wedged request as decoding
    table = artifact["sources"][0]["requests"]
    assert any(r["request_id"] == rid and r["state"] == "decoding"
               for r in table)
    # metrics snapshot rode along, including the trip counter itself
    metrics = artifact["sources"][0]["metrics"]
    assert "dynamo_watchdog_trips_total" in metrics
    assert "dynamo_scheduler_step_duration_seconds" in metrics

    # --- GET /debug/flight, served while wedged ---
    assert out["http_status"] == 200
    http_art = out["http_artifact"]
    assert any(e.get("request_id") == rid for e in http_art["events"])
    assert any("__array__" in ln for th in http_art["threads"]
               for ln in th["stack"])
    assert any(
        r["request_id"] == rid
        for src in http_art["sources"] for r in (src["requests"] or [])
    )


def test_wedge_recovers_cleanly_after_release(tmp_path):
    """After the wedge clears, the stream completes and the watchdog
    re-arms (condition cleared) without further trips."""
    out, _ = _drive_wedged_engine(tmp_path)
    # exactly one decode_stall for one persistent wedge
    assert [t["reason"] for t in out["trips"]].count("decode_stall") == 1
