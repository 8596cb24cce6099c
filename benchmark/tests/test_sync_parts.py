"""The sync tail in its parts (``readers/sync_parts.py``, ISSUE 35):
arithmetic on hand-made traces, then two cuts of traced v5e runs of
PR 35 that hold the parts (``data/v5e-sync-parts*.xplane.pb``; how each
was cut is in its ``.expected.json``): one whose planes agree, read as
it is and with its host plane shifted 2 ms early, and one whose planes
disagree as the profiler recorded them; a cut of PR 58 with a step in
flight; and two cuts of PR 58 that hold the runtime's own events, by
which a late host plane is seen: one cell's first and a later capture
of a machine."""

import dataclasses
import json
import os

import pytest

from harness import manifest, prom, trace
from harness.manifest import Cell
from harness.rundata import RunData, read_metric
from harness.trace import DeviceTrace, Event
from readers import host_spans, sync_parts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPAN = "sched.decode.sync"
PROGRAM = "^jit_decode_"
STATS = ("ready_mean_ms", "copy_mean_ms", "hop_mean_ms", "clock_slack_min_ms")
METRICS = {"step_sync_ready_ms": "ready_mean_ms",
           "step_sync_copy_ms": "copy_mean_ms",
           "step_sync_hop_ms": "hop_mean_ms",
           "trace_clock_slack_ms": "clock_slack_min_ms"}


def _trace(ops, host, window=(0.0, 10.0), devices=(0,)):
    """Every (start, end) of ``ops`` is one execution of the decode
    program with one operation inside it."""
    evs = [Event("fusion.1", s, e - s, own=e - s) for s, e in ops]
    mods = [Event("jit_decode_step(1)", s, e - s) for s, e in ops]
    return DeviceTrace(
        window=window, devices=list(devices),
        busy_s={d: sum(e - s for s, e in ops) for d in devices},
        span={d: (ops[0][0], ops[-1][1]) for d in devices},
        modules={d: list(mods) for d in devices},
        ops={d: list(evs) for d in devices},
        host=[Event(n, s, e - s) for n, s, e in host])


def _run(t, start=None, end=None):
    cell = Cell("c", 1, {}, "k", {}, "m", {"drain_s": 1}, [], [])
    return RunData(cell=cell, hf={}, serve={}, seconds=1.0, window=(0.0, 10.0),
                   setup_seconds=0.0, records=[], prom_start=start or {},
                   prom_end=end or {}, device_trace=t)


def _read(t, stat):
    return sync_parts.read(_run(t), {"stat": stat, "span": SPAN,
                                     "program": PROGRAM})


# three steps end at 1.0, 3.0 and 6.0 s; times in seconds, so a part of
# 0.1 reads 100 ms
OPS = [(0.0, 1.0), (2.0, 3.0), (5.0, 6.0)]


def _pass(sync, fetch, ready, copy=None, span=SPAN):
    """One pass's four host events from its (start, end) pairs."""
    out = [(span, *sync), ("sync.fetch", *fetch), ("sync.ready", *ready)]
    if copy is not None:
        out.append(("sync.copy", *copy))
    return out


HOST = (
    # step ends 1.0: ready 0.1, copy 0.2, hop 0.3 (tail 0.6)
    _pass((0.5, 1.6), (0.5, 1.3), (0.5, 1.1), (1.1, 1.3))
    # step ends 3.0: ready 0.3, only the tokens fetched, hop 0.1 (tail 0.4)
    + _pass((2.5, 3.4), (2.5, 3.3), (2.5, 3.3))
    # nothing ended inside this one (the device was done before it began)
    + _pass((3.6, 4.0), (3.6, 3.9), (3.6, 3.7), (3.7, 3.9))
    # step ends 6.0: ready 0.2, copy 0.1, hop 0.6 (tail 0.9)
    + _pass((5.5, 6.9), (5.5, 6.3), (5.5, 6.2), (6.2, 6.3))
    # a prefill's wait is another span's
    + _pass((7.0, 7.5), (7.0, 7.4), (7.0, 7.2), (7.2, 7.4),
            span="sched.prefill.sync"))


@pytest.mark.parametrize("stat, want, n", [
    ("ready_mean_ms", 1e3 * (0.1 + 0.3 + 0.2) / 3, 3),
    ("copy_mean_ms", 1e3 * (0.2 + 0.0 + 0.1) / 3, 3),
    ("hop_mean_ms", 1e3 * (0.3 + 0.1 + 0.6) / 3, 3),
    ("clock_slack_min_ms", 1e3 * 0.1, 3),
])
def test_parts_over_the_passes_the_lump_is_read_from(stat, want, n):
    got, samples = _read(_trace(OPS, HOST), stat)
    assert samples == n and got == pytest.approx(want)


def test_the_three_parts_are_the_lump():
    t = _trace(OPS, HOST)
    tail, n = host_spans.read(_run(t), {"stat": "sync_tail_mean_ms",
                                        "span": SPAN, "program": PROGRAM})
    parts = sum(_read(t, s)[0] for s in STATS[:3])
    assert n == 3 and parts == pytest.approx(tail)


def test_ready_runs_from_the_last_chip():
    t = _trace(OPS, HOST, devices=(0, 1))
    t.ops[1] = [Event("fusion.1", 0.0, 1.05, own=1.05)]   # chip 1 ends at 1.05
    t.modules[1] = [Event("jit_decode_step(1)", 0.0, 1.05)]
    got, _ = _read(t, "ready_mean_ms")
    assert got == pytest.approx(1e3 * (0.05 + 0.3 + 0.2) / 3)


def test_a_host_plane_that_runs_early_reads_a_negative_slack():
    """The same passes with every host event 0.15 s early: the tokens
    seem to reach the host before the device made them in the first
    pass (ready 0.1), and the check says by how much at least."""
    early = [(n, s - 0.15, e - 0.15) for n, s, e in HOST]
    t = _trace(OPS, early)
    got, n = _read(t, "clock_slack_min_ms")
    assert n == 3 and got == pytest.approx(1e3 * (0.1 - 0.15))
    # copy and hop are differences on one plane: the shift leaves them
    assert _read(t, "copy_mean_ms")[0] == pytest.approx(1e3 * 0.1)
    assert _read(t, "hop_mean_ms")[0] == pytest.approx(1e3 * 1.0 / 3)
    # a host plane that runs late only adds to the ready parts: without
    # the runtime's events that name an execution it cannot be seen
    late = [(n, s + 0.15, e + 0.15) for n, s, e in HOST]
    assert _read(_trace(OPS, late), "clock_slack_min_ms")[0] == pytest.approx(
        1e3 * (0.1 + 0.15))


def test_a_host_plane_that_runs_late_is_taken_off_the_ready_part():
    """With the runtime's events (each execution enqueued 0.01 s before
    it starts, its end learnt 0.02 s after): the host plane 0.15 s late
    is seen late by 0.14, every ready part is read 0.14 shorter, and
    the slack is the tighter side's room: 0.02 + 0.01, the time an
    enqueue and a callback take, within which the offset is not known.
    Copy and hop stand. On time, nothing is taken off."""
    def runs(t):
        for i, m in enumerate(t.modules[0]):
            m.run = (0, i)
            t.host.append(Event(trace.ENQUEUED, m.start - 0.01, 1e-4, run=m.run))
            t.host.append(Event(trace.COMPLETED, m.start + m.dur + 0.02, 1e-4,
                                run=m.run))
        return t

    on_time = runs(_trace(OPS, HOST))
    assert _read(on_time, "ready_mean_ms")[0] == pytest.approx(1e3 * 0.2)
    assert _read(on_time, "clock_slack_min_ms")[0] == pytest.approx(1e3 * 0.02)
    late = dataclasses.replace(on_time, host=[
        dataclasses.replace(h, start=h.start + 0.15) for h in on_time.host])
    assert _read(late, "ready_mean_ms") == (pytest.approx(1e3 * 0.21), 3)
    assert _read(late, "clock_slack_min_ms")[0] == pytest.approx(1e3 * 0.03)
    assert _read(late, "copy_mean_ms")[0] == pytest.approx(1e3 * 0.1)
    assert _read(late, "hop_mean_ms")[0] == pytest.approx(1e3 * 1.0 / 3)
    tail, n = host_spans.read(_run(late), {"stat": "sync_tail_mean_ms",
                                           "span": SPAN, "program": PROGRAM})
    assert n == 3 and tail == pytest.approx(1e3 * (0.6 + 0.4 + 0.9 + 0.03) / 3)


@pytest.mark.parametrize("stat", STATS)
def test_a_program_without_the_parts_gives_nothing_to_read(stat):
    """The parent of PR 35 writes ``sync.fetch`` alone: the line leaves
    the metric out, and nothing raises."""
    whole = [e for e in HOST if e[0] not in ("sync.ready", "sync.copy")]
    assert _read(_trace(OPS, whole), stat) is None
    run = _run(None)                              # an untraced run
    assert sync_parts.read(run, {"stat": stat, "span": SPAN,
                                 "program": PROGRAM}) is None


PROM = """\
dynamo_scheduler_fetches_total{kind="decode"} %(n)s
dynamo_scheduler_fetches_total{kind="prefill"} 7.0
dynamo_scheduler_fetch_seconds_total{kind="decode",part="ready_wait"} %(wait)s
dynamo_scheduler_fetch_seconds_total{kind="decode",part="copy"} %(copy)s
dynamo_scheduler_fetch_seconds_total{kind="decode",part="hop"} %(hop)s
dynamo_scheduler_fetch_seconds_total{kind="prefill",part="copy"} 5.0
dynamo_scheduler_fetch_seconds_total{kind="prefill",part="hop"} 5.0
"""


def test_copy_and_hop_a_fetch_from_the_counters():
    start = prom.parse(PROM % dict(n=100.0, wait=1.0, copy=0.05, hop=0.1))
    end = prom.parse(PROM % dict(n=300.0, wait=4.0, copy=0.25, hop=0.4))
    args = {"stat": "counter_tail_ms", "kind": "decode"}
    got, n = sync_parts.read(_run(None, start, end), args)
    assert n == 200 and got == pytest.approx(1e3 * (0.2 + 0.3) / 200)
    # a window without a decode fetch, a program from before the counters
    # and a run without the two samples: nothing to read, nothing raised
    assert sync_parts.read(_run(None, end, end), args) is None
    other = prom.parse("dynamo_scheduler_yield_seconds_total 3.0\n")
    assert sync_parts.read(_run(None, other, other), args) is None
    assert sync_parts.read(_run(None), args) is None


def test_the_counters_are_read_inside_the_captured_slice():
    """A traced run reads the counters between the first and the last
    /metrics sample taken inside the capture, the stretch the capture's
    rows cover; what the window's ends say is left aside."""
    def at(n, copy, hop):
        return prom.parse(PROM % dict(n=n, wait=0.0, copy=copy, hop=hop))

    run = _run(None, at(0.0, 0.0, 0.0), at(1000.0, 9.0, 9.0))
    run.trace_slice = (20.0, 24.0)
    run.prom_samples = [(19.5, at(400.0, 1.0, 1.0)), (20.5, at(440.0, 1.04, 1.02)),
                        (22.0, at(500.0, 1.1, 1.05)), (23.5, at(560.0, 1.16, 1.08)),
                        (24.5, at(600.0, 3.0, 3.0))]
    args = {"stat": "counter_tail_ms", "kind": "decode"}
    got, n = sync_parts.read(run, args)
    assert n == 120 and got == pytest.approx(1e3 * (0.12 + 0.06) / 120)
    run.prom_samples = run.prom_samples[:2]        # one sample inside: the window
    got, n = sync_parts.read(run, args)
    assert n == 1000 and got == pytest.approx(1e3 * 18.0 / 1000)


def test_an_unknown_stat_of_the_parts_is_an_error():
    with pytest.raises(ValueError, match="unknown stat"):
        _read(_trace(OPS, HOST), "nope")


@pytest.mark.parametrize("cell_name", [w["name"] for w in
                                       manifest.load_manifest()["workloads"]])
def test_every_cell_lists_the_five_metrics_of_the_scheduler(cell_name):
    """No ``workloads`` list: every cell that reports the gap reports
    them, from the manifest's entries and the metric's file alone, and
    each names the program the wait is for (the step in flight since
    PR 57 is another execution of it, and ends after the wait)."""
    cell = manifest.load_cell(cell_name)
    by_name = {m.name: m for m in cell.per_layer}
    assert "sync_hop_busy_share" not in by_name       # 0.0 in every cell: PR 58
    for name, stat in METRICS.items():
        m = by_name[name]
        assert (m.reader, m.args["stat"], m.args["span"], m.args["program"]) == (
            "sync_parts", stat, SPAN, PROGRAM)
        assert (m.unit, m.moves) == ("ms", "itl_p50_ms")
        assert m.better == ("higher" if name == "trace_clock_slack_ms"
                            else "lower")
    m = by_name["fetch_tail_ms_per_pass"]
    assert (m.reader, m.args, m.unit, m.better) == (
        "sync_parts", {"stat": "counter_tail_ms", "kind": "decode"}, "ms",
        "lower")
    t = _trace(OPS, HOST)
    run = dataclasses.replace(_run(t), cell=cell)
    got = {name: read_metric(by_name[name], run)[0] for name in METRICS}
    tail = read_metric(by_name["step_sync_tail_ms"], run)[0]
    assert by_name["step_sync_tail_ms"].args == {
        "stat": "sync_tail_mean_ms", "span": SPAN, "program": PROGRAM}
    assert (got["step_sync_ready_ms"] + got["step_sync_copy_ms"]
            + got["step_sync_hop_ms"]) == pytest.approx(tail)


# ---- the recorded cuts: five passes of falcon-h1-chat on the v5e ----

def _recorded(name):
    t = trace.load(os.path.join(DATA, name + ".xplane.pb"))
    with open(os.path.join(DATA, name + ".expected.json")) as f:
        return t, json.load(f)


@pytest.fixture(scope="module")
def recorded_parts():
    return _recorded("v5e-sync-parts")


@pytest.fixture(scope="module")
def recorded_parts_late_host():
    return _recorded("v5e-sync-parts-early")


def _mean(rows, key):
    return sum(r[key] for r in rows) / len(rows)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
@pytest.mark.parametrize("stat, key", [("ready_mean_ms", "ready_ms"),
                                       ("copy_mean_ms", "copy_ms"),
                                       ("hop_mean_ms", "hop_ms")])
def test_recorded_parts(recorded_parts, kind, stat, key):
    t, want = recorded_parts
    assert (len(t.ops[0]), len(t.modules[0]), len(t.host)) == (
        want["n_ops"], want["n_modules"], want["n_host"])
    got, n = sync_parts.read(_run(t), {"stat": stat,
                                       "span": "sched.%s.sync" % kind,
                                       "program": "^jit_%s_" % kind})
    assert n == len(want[kind]) > 0
    assert got == pytest.approx(_mean(want[kind], key), abs=1e-5)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_recorded_parts_are_the_recorded_lump(recorded_parts, kind):
    """ready + copy + hop against the accepted reader's tail over the
    same passes: apart by the two seams between the inner spans, some
    tens of microseconds while a capture runs."""
    t, want = recorded_parts
    args = {"span": "sched.%s.sync" % kind, "program": "^jit_%s_" % kind}
    tail, n = host_spans.read(_run(t), dict(args, stat="sync_tail_mean_ms"))
    assert n == len(want[kind])
    assert tail == pytest.approx(_mean(want[kind], "tail_ms"), abs=1e-5)
    by = {s: sync_parts.read(_run(t), dict(args, stat=s))[0] for s in STATS}
    # each part is what the issue expected to find, or is not: the hop is
    # the smallest, the copies cost as much as the transfer they follow
    assert 0.0 <= tail - sum(by[s] for s in STATS[:3]) < 0.05
    assert 1.0 < by["ready_mean_ms"] < 1.6 and 1.0 < by["copy_mean_ms"] < 2.0
    assert 0.1 < by["hop_mean_ms"] < 0.3
    assert by["clock_slack_min_ms"] == pytest.approx(
        min(r["ready_ms"] for r in want[kind]), abs=1e-5)
    assert by["clock_slack_min_ms"] > 1.0


def test_recorded_capture_with_its_host_plane_2_ms_early(recorded_parts):
    """What a capture whose host plane runs 2 ms early reads: the tokens
    on the host 0.6-0.8 ms before the device made them. The check is
    negative by that much, ready and the lump are 2 ms short, and copy
    and hop, differences on one plane, are what they were."""
    t, want = recorded_parts
    early = dataclasses.replace(t, host=[
        dataclasses.replace(h, start=h.start - 2e-3) for h in t.host])
    args = {"span": SPAN, "program": PROGRAM}
    slack, n = sync_parts.read(_run(early), dict(args, stat="clock_slack_min_ms"))
    assert n == len(want["decode"])
    assert slack == pytest.approx(
        min(r["ready_ms"] for r in want["decode"]) - 2.0, abs=1e-5)
    assert -1.0 < slack < -0.5
    ready, _ = sync_parts.read(_run(early), dict(args, stat="ready_mean_ms"))
    assert ready == pytest.approx(_mean(want["decode"], "ready_ms") - 2.0, abs=1e-5)
    for stat, key in (("copy_mean_ms", "copy_ms"), ("hop_mean_ms", "hop_ms")):
        got, _ = sync_parts.read(_run(early), dict(args, stat=stat))
        assert got == pytest.approx(_mean(want["decode"], key), abs=1e-5)


def test_recorded_capture_whose_host_plane_runs_late(recorded_parts_late_host,
                                                     recorded_parts):
    """The first traced run of the same chip call, as the profiler wrote
    it: its host plane runs about 1.4 ms late. Read from the last
    *operation* inside a wait (until PR 58), the next step's first
    operations seemed to end inside the wait that came before their
    dispatch, and three of five passes read a ready part of -1.4 ms.
    Read from the waited program's last *execution*, no pass does: each
    ready part is 2.6-2.8 ms where the capture whose planes agree reads
    1.2-1.4 (the cut holds none of the runtime's events by which a late
    host plane is seen, so its 1.4 ms stay in), copy and hop read what
    they read there, and the first wait drops out (its step began
    before the cut)."""
    t, want = recorded_parts_late_host
    agree, want_agree = recorded_parts
    assert (len(t.ops[0]), len(t.modules[0]), len(t.host)) == (
        want["n_ops"], want["n_modules"], want["n_host"])
    rows = want["decode_by_execution"]
    args = {"span": SPAN, "program": PROGRAM}
    slack, n = sync_parts.read(_run(t), dict(args, stat="clock_slack_min_ms"))
    assert n == len(rows) == 4
    assert slack == pytest.approx(min(r["ready_ms"] for r in rows), abs=1e-5)
    assert 2.5 < slack < 2.8
    tail, n = host_spans.read(_run(t), dict(args, stat="sync_tail_mean_ms"))
    assert n == 4 and tail == pytest.approx(_mean(rows, "tail_ms"), abs=1e-5)
    for stat, key, lo, hi in (("ready_mean_ms", "ready_ms", 2.6, 2.8),
                              ("copy_mean_ms", "copy_ms", 1.0, 2.0),
                              ("hop_mean_ms", "hop_ms", 0.1, 0.3)):
        got, _ = sync_parts.read(_run(t), dict(args, stat=stat))
        assert got == pytest.approx(_mean(rows, key), abs=1e-5)
        assert lo < got < hi
        if key != "ready_ms":
            assert abs(got - _mean(want_agree["decode"], key)) < 0.25
    assert host_spans.plane_shift(t) == (0.0, None)


# ---- a step in flight: six waits of phi3-chat on the v5e, PR 57's program ----

@pytest.fixture(scope="module")
def recorded_in_flight():
    return _recorded("v5e-step-in-flight")


@pytest.mark.parametrize("stat, key", [("ready_mean_ms", "ready_ms"),
                                       ("copy_mean_ms", "copy_ms"),
                                       ("hop_mean_ms", "hop_ms")])
def test_recorded_parts_with_a_step_in_flight(recorded_in_flight, stat, key):
    """Step k is on the device while the host waits for step k-1 (every
    dispatch of the cut carries ``ahead`` = 1): the parts are read from
    the end of the step waited for, as they were before PR 57."""
    t, want = recorded_in_flight
    assert (len(t.ops[0]), len(t.modules[0]), len(t.host)) == (
        want["n_ops"], want["n_modules"], want["n_host"])
    got, n = sync_parts.read(_run(t), {"stat": stat, "span": SPAN,
                                       "program": PROGRAM})
    assert n == len(want["decode"]) == 6
    assert got == pytest.approx(_mean(want["decode"], key), abs=1e-5)


def test_recorded_lump_with_a_step_in_flight(recorded_in_flight):
    """The tail is 2.3-2.7 ms a wait, its ready part 2.0-2.3, and the
    slack is positive: the capture's planes agree. By the last operation
    that ended inside the wait, the reading until PR 58 and the ledger's
    at PR 57, the same six waits gave a tail of 0.01-0.06 ms and a ready
    part under zero: an operation of the step in flight, microseconds
    before the wait's end."""
    t, want = recorded_in_flight
    args = {"span": SPAN, "program": PROGRAM}
    tail, n = host_spans.read(_run(t), dict(args, stat="sync_tail_mean_ms"))
    assert n == 6 and tail == pytest.approx(_mean(want["decode"], "tail_ms"),
                                            abs=1e-5)
    assert 2.3 < tail < 2.5
    by = {s: sync_parts.read(_run(t), dict(args, stat=s))[0] for s in STATS}
    assert 0.0 <= tail - sum(by[s] for s in STATS[:3]) < 0.05
    assert by["clock_slack_min_ms"] == pytest.approx(
        min(r["ready_ms"] for r in want["decode"]), abs=1e-5)
    assert 1.9 < by["clock_slack_min_ms"] < by["ready_mean_ms"] < 2.3
    was = want["decode_by_last_operation"]
    assert len(was) == 6 and max(r["ready_ms"] for r in was) < 0
    assert max(r["tail_ms"] for r in was) < 0.06
    # the waits are for the executions in their order: the last one of the
    # cut is the step in flight during the sixth wait, and no wait is its
    ends = host_spans.step_ends(t, PROGRAM)
    assert len(ends) == 7
    syncs = sorted((h for h in t.host if h.name == SPAN), key=lambda h: h.start)
    assert [host_spans.step_end_inside(ends, h.start, h.start + h.dur)
            for h in syncs] == ends[:6]


# ---- the runtime's own events: one cell's first and a later capture of
# a machine, sala-longdoc on the v5e, PR 57's program ----

CAPTURES = ("v5e-planes-first-capture", "v5e-planes-second-capture")


@pytest.fixture(scope="module", params=CAPTURES)
def recorded_planes(request):
    return _recorded(request.param)


def test_recorded_planes_are_apart_by_what_the_runtimes_events_say(
        recorded_planes):
    """One of the six executions began on an idle device, and before the
    runtime's event that enqueued it had begun: the host plane is late
    by at least that, 1.29 ms in the machine's first capture and 0.38 in
    a later one, and what is left between an execution's end and the
    host's learning of it is the room the offset is known within."""
    t, want = recorded_planes
    assert (len(t.ops[0]), len(t.modules[0]), len(t.host)) == (
        want["n_ops"], want["n_modules"], want["n_host"])
    assert all(m.run is not None for m in t.modules[0])
    by_name = {name: sum(h.name == name and h.run is not None for h in t.host)
               for name in trace.RUN_EVENTS}
    assert by_name == {trace.ENQUEUED: 6, trace.COMPLETED: 6}
    late, room = host_spans.plane_shift(t)
    assert 1e3 * late == pytest.approx(want["late_ms"], abs=1e-5)
    assert 1e3 * room == pytest.approx(want["room_ms"], abs=1e-5)
    assert sum(x < 0 for x in want["starts_before_its_enqueue_ms"]) == 1
    assert late > 0 and 0.4 < 1e3 * room < 0.6


@pytest.mark.parametrize("stat, key", [("ready_mean_ms", "ready_ms"),
                                       ("copy_mean_ms", "copy_ms"),
                                       ("hop_mean_ms", "hop_ms")])
def test_recorded_parts_on_a_late_host_plane(recorded_planes, stat, key):
    t, want = recorded_planes
    got, n = sync_parts.read(_run(t), {"stat": stat, "span": SPAN,
                                       "program": PROGRAM})
    assert n == len(want["decode"]) == 5
    assert got == pytest.approx(_mean(want["decode"], key), abs=1e-5)


def test_recorded_lump_on_a_late_host_plane(recorded_planes):
    t, want = recorded_planes
    args = {"span": SPAN, "program": PROGRAM}
    tail, n = host_spans.read(_run(t), dict(args, stat="sync_tail_mean_ms"))
    assert n == 5 and tail == pytest.approx(_mean(want["decode"], "tail_ms"),
                                            abs=1e-5)
    by = {s: sync_parts.read(_run(t), dict(args, stat=s))[0] for s in STATS}
    assert 0.0 <= tail - sum(by[s] for s in STATS[:3]) < 0.05
    # the slack is the tighter side: here the room, under the least ready
    assert by["clock_slack_min_ms"] == pytest.approx(want["room_ms"], abs=1e-5)
    assert by["clock_slack_min_ms"] < min(r["ready_ms"] for r in want["decode"])


def test_a_machines_first_capture_reads_what_its_later_ones_read():
    """As recorded the same cell's waits read in two groups, 2.4-2.5 ms
    in the machine's first capture and 1.6-1.8 in a later one (the first
    wait of each cut aside: the pass after a prompt's last chunk), the
    ready part 2.2-2.3 against 1.3-1.5. With each capture's own
    lateness taken off both read 1.1-1.4 and 0.9-1.1."""
    first, second = (_recorded(name)[1] for name in CAPTURES)
    for key, lo_1, hi_1, lo_2, hi_2, lo, hi in (
            ("tail_ms", 2.4, 2.55, 1.55, 1.8, 1.1, 1.4),
            ("ready_ms", 2.15, 2.3, 1.3, 1.55, 0.85, 1.15)):
        for rows, a, b in ((first["decode_as_recorded"][1:], lo_1, hi_1),
                           (second["decode_as_recorded"][1:], lo_2, hi_2),
                           (first["decode"][1:], lo, hi),
                           (second["decode"][1:], lo, hi)):
            assert all(a < r[key] < b for r in rows), (key, a, b, rows)
    assert first["late_ms"] - second["late_ms"] == pytest.approx(0.915, abs=1e-3)
