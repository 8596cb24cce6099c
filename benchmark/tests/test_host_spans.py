"""The readers of the program's own spans (``readers/host_spans.py``):
arithmetic on hand-made traces, then a cut of a traced v5e run of PR 23
that holds the spans (``data/v5e-spans.*``; how it was cut is in
``data/v5e-spans.expected.json``)."""

import dataclasses
import json
import os

import pytest

from harness import trace
from harness.manifest import Cell
from harness.rundata import RunData
from harness.trace import DeviceTrace, Event
from readers import host_spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ALL_SPANS = ["sched.", "sync.", "dispatch.", "http.", "pre.", "detok."]
SYNC, DECODE = "sched.decode.sync", "^jit_decode_"


EDGES = [("sched.admit", 0.0, 0.0), ("sched.admit", 10.0, 10.0)]


def _trace(ops, host, window=(0.0, 10.0), devices=(0,), edges=True):
    """A capture of ``window`` with the same operations on every device,
    each the one operation of an execution of the decode program;
    ``edges`` puts an empty ``sched.admit`` at both ends, so that the
    program's spans are on record over the whole window."""
    if edges and any(n.startswith("sched.") for n, _, _ in host):
        host = EDGES + list(host)
    evs = [Event("fusion.1", s, e - s, own=e - s) for s, e in ops]
    busy = sum(e - s for s, e in ops)
    return DeviceTrace(
        window=window, devices=list(devices),
        busy_s={d: busy for d in devices},
        span={d: (ops[0][0], ops[-1][1]) for d in devices},
        modules={d: [Event("jit_decode_step(1)", s, e - s) for s, e in ops]
                 for d in devices},
        ops={d: list(evs) for d in devices},
        host=[Event(n, s, e - s) for n, s, e in host])


def _run(t):
    cell = Cell("c", 1, {}, "k", {}, "m", {"drain_s": 1}, [], [])
    return RunData(cell=cell, hf={}, serve={}, seconds=1.0, window=(0.0, 10.0),
                   setup_seconds=0.0, records=[], prom_start={}, prom_end={},
                   device_trace=t)


# busy 0-1, 2-3, 5-6, 9-10: gaps 1-2, 3-5, 6-9 (6 s idle of 10)
OPS = [(0.0, 1.0), (2.0, 3.0), (5.0, 6.0), (9.0, 10.0)]


@pytest.mark.parametrize("host, spans, want", [
    # a gap wholly inside sched.wait, nothing else named
    ([("sched.wait", 0.9, 2.1)], ["sched.wait"], 100 * 1 / 6),
    # one gap half covered
    ([("sched.decode.build", 3.0, 4.0)], ALL_SPANS, 100 * 1 / 6),
    # an uncovered gap stays uncovered: spans over busy time count nothing
    ([("sched.decode.sync", 0.0, 1.0), ("sched.yield", 5.2, 5.8)], ALL_SPANS, 0.0),
    # overlapping and nested spans are a union, not a sum
    ([("sched.decode.dispatch", 3.0, 5.0), ("dispatch.decode", 3.5, 4.5),
      ("sched.yield", 6.0, 9.0), ("http.sse_write", 6.5, 7.0)], ALL_SPANS, 100 * 5 / 6),
    # every gap named: all of it
    ([("sched.admit", 1.0, 2.0), ("sched.decode.build", 3.0, 5.0),
      ("sched.wait", 6.0, 9.0)], ALL_SPANS, 100.0),
    # the runtime's own host events are not the program's spans
    ([("sched.admit", 0.0, 0.1), ("PjitFunction(decode_step)", 3.0, 5.0)], ALL_SPANS, 0.0),
])
def test_idle_share_covered_by_spans(host, spans, want):
    run = _run(_trace(OPS, host))
    got = host_spans.read(run, {"stat": "idle_covered_pct", "spans": spans})
    assert got == pytest.approx(want)


def test_idle_share_is_of_the_chip_that_waits_longest():
    t = _trace(OPS, [("sched.wait", 6.0, 9.0)], devices=(0, 1))
    t.ops[1] = t.ops[1][:3]                       # chip 1 idles from 6 to 10
    t.busy_s[1] = 3.0
    got = host_spans.read(_run(t), {"stat": "idle_covered_pct",
                                    "spans": ["sched.wait"]})
    assert got == pytest.approx(100 * 3 / 7)


def test_idle_time_counts_only_where_spans_can_be_on_record():
    """A span still open when the capture stops is never written: the
    idle time after the last sched.* span's end (and before the first
    one's start) is left out, not counted as unnamed."""
    host = [("sched.decode.build", 3.0, 4.0), ("sched.yield", 6.0, 7.5)]
    t = _trace(OPS, host, edges=False)
    # between 3.0 and 7.5: idle 3-5 and 6-7.5 = 3.5 s, named 1 + 1.5
    got = host_spans.read(_run(t), {"stat": "idle_covered_pct",
                                    "spans": ALL_SPANS})
    assert got == pytest.approx(100 * 2.5 / 3.5)


def test_no_work_share_is_zero_when_the_loop_never_waited():
    run = _run(_trace(OPS, [("sched.decode.build", 3.0, 4.0)]))
    assert host_spans.read(run, {"stat": "idle_covered_pct",
                                 "spans": ["sched.wait"]}) == 0.0


@pytest.mark.parametrize("args", [
    {"stat": "idle_covered_pct", "spans": ALL_SPANS},
    {"stat": "span_mean_ms", "span": "sched.decode.build"},
    {"stat": "sync_tail_mean_ms", "span": "sched.decode.sync",
     "program": "^jit_decode_"},
])
def test_a_program_without_spans_gives_nothing_to_read(args):
    """The parent commit of PR 23 writes none: the line leaves the
    metric out, and nothing raises."""
    run = _run(_trace(OPS, [("PjitFunction(step)", 3.0, 5.0),
                            ("np.asarray(jax.Array)", 0.0, 1.0)]))
    assert host_spans.read(run, args) is None
    run.device_trace = None                       # an untraced run
    assert host_spans.read(run, args) is None


def test_span_mean_is_over_the_events_of_that_name_only():
    host = [("sched.decode.build", 1.0, 1.002), ("sched.decode.build", 3.0, 3.004),
            ("sched.decode.build.x", 4.0, 5.0), ("sched.prefill.build", 4.0, 5.0)]
    ms, n = host_spans.read(_run(_trace(OPS, host)),
                            {"stat": "span_mean_ms", "span": "sched.decode.build"})
    assert n == 2 and ms == pytest.approx(3.0)


def test_sync_tail_runs_from_the_last_execution_that_ended_inside():
    host = [
        ("sched.decode.sync", 0.5, 1.25),    # step ends at 1.0: tail 0.25
        ("sched.decode.sync", 2.5, 3.5),     # step ends at 3.0: tail 0.5
        ("sched.decode.sync", 3.6, 4.0),     # nothing ended inside: left out
        ("sched.decode.sync", 4.5, 9.5),     # 6.0 is the last inside (10.0 is after)
        ("sched.prefill.sync", 5.5, 6.75),   # another span's
    ]
    t = _trace(OPS, host)
    assert host_spans.sync_tails(t, SYNC, DECODE) == pytest.approx(
        [0.25, 0.5, 3.5])
    ms, n = host_spans.read(_run(t), {"stat": "sync_tail_mean_ms",
                                      "span": SYNC, "program": DECODE})
    assert n == 3 and ms == pytest.approx(1e3 * (0.25 + 0.5 + 3.5) / 3)


def test_sync_tail_waits_for_the_last_chip():
    t = _trace(OPS, [("sched.decode.sync", 2.5, 3.5)], devices=(0, 1))
    t.ops[1] = [Event("fusion.1", 2.0, 1.25, own=1.25)]   # chip 1 ends at 3.25
    t.modules[1] = [Event("jit_decode_step(1)", 2.0, 1.25)]
    assert host_spans.sync_tails(t, SYNC, DECODE) == pytest.approx([0.25])


def _step_in_flight():
    """Since PR 57: step k is on the device while the host waits for
    step k-1. Steps of 1 s back to back from 0.0, ten operations each;
    the wait for step 1 (ends 1.0) runs 0.4-1.3 and the wait for step 2
    (ends 2.0) 1.6-2.25, and inside each the step in flight has already
    ended operations of its own."""
    steps = [(float(k), k + 1.0) for k in range(4)]
    t = _trace(steps, [("sched.decode.sync", 0.4, 1.3),
                       ("sched.decode.sync", 1.6, 2.25)])
    t.ops[0] = [Event("fusion.%d" % j, k + j / 10, 0.1, own=0.1)
                for k in range(4) for j in range(10)]
    return t


def test_sync_tail_with_a_step_in_flight_is_the_waited_steps():
    """The last operation that ended inside the first wait is the step
    in flight's third (ends 1.3: a tail of 0.0, what PR 57's ledger
    lines read); the last *execution* that ended inside it is the step
    waited for (1.0: 0.3)."""
    t = _step_in_flight()
    assert host_spans.sync_tails(t, SYNC, DECODE) == pytest.approx([0.3, 0.25])
    assert host_spans.step_ends(t, DECODE) == pytest.approx([1.0, 2.0, 3.0, 4.0])


def test_sync_tail_counts_only_the_program_the_span_waits_for():
    """A cast the host sent once the first wait was over, on a host
    plane that runs late: it seems to end inside the wait, and its name
    says that it is not the step. The metric's file names the program;
    a reader is not asked without one."""
    t = _step_in_flight()
    t.modules[0] = sorted(t.modules[0] + [Event("jit_convert_element_type(7)",
                                                1.25, 0.001)],
                          key=lambda m: m.start)
    assert host_spans.sync_tails(t, SYNC, "^jit_")[0] == pytest.approx(
        0.05, abs=2e-3)
    assert host_spans.sync_tails(t, SYNC, DECODE) == pytest.approx([0.3, 0.25])
    ms, n = host_spans.read(_run(t), {"stat": "sync_tail_mean_ms",
                                      "span": SYNC, "program": DECODE})
    assert n == 2 and ms == pytest.approx(1e3 * 0.275)
    with pytest.raises(KeyError, match="program"):
        host_spans.read(_run(t), {"stat": "sync_tail_mean_ms", "span": SYNC})


def _with_runs(t, enqueued, completed):
    """``t`` with its executions numbered and the runtime's two events an
    execution: its enqueue ``enqueued`` s before it starts and the
    host's learning of its end ``completed`` s after."""
    host = list(t.host)
    for d in t.devices:
        for i, m in enumerate(t.modules[d]):
            m.run = (d, i)
            host.append(Event(trace.ENQUEUED, m.start - enqueued, 1e-4, run=m.run))
            host.append(Event(trace.COMPLETED, m.start + m.dur + completed,
                              1e-4, run=m.run))
    return dataclasses.replace(t, host=host)


def test_a_late_host_plane_is_seen_by_the_runtimes_own_events():
    """An execution cannot begin before its own enqueue has: where one
    seems to, the host plane is late by at least that much, the step's
    end is taken that much later, and the tail is what it is on a host
    plane that runs on time, whichever way it was recorded."""
    on_time = _with_runs(_step_in_flight(), enqueued=0.01, completed=0.02)
    assert host_spans.plane_shift(on_time) == pytest.approx((0.0, 0.02))
    assert host_spans.sync_tails(on_time, SYNC, DECODE) == pytest.approx(
        [0.3, 0.25])
    late = dataclasses.replace(on_time, host=[
        dataclasses.replace(h, start=h.start + 0.1) for h in on_time.host])
    # 0.1 late, of which the enqueue's own 0.01 cannot be told from the
    # time an enqueue takes: seen late by 0.09, known to within 0.03
    assert host_spans.plane_shift(late) == pytest.approx((0.09, 0.03))
    assert host_spans.sync_tails(late, SYNC, DECODE) == pytest.approx(
        [0.31, 0.26])
    # an early host plane cannot be told from a quick one on this side;
    # the other side says it: the host learnt of an end before the end
    early = dataclasses.replace(on_time, host=[
        dataclasses.replace(h, start=h.start - 0.1) for h in on_time.host])
    assert host_spans.plane_shift(early) == pytest.approx((0.0, -0.08))
    # a capture without the runtime's events (the cuts of PR 23 and 35)
    assert host_spans.plane_shift(_step_in_flight()) == (0.0, None)


def test_unknown_stat_is_an_error():
    with pytest.raises(ValueError, match="unknown stat"):
        host_spans.read(_run(_trace(OPS, [])), {"stat": "nope"})


# ---- the recorded_spans cut: ten decode passes of phi3-chat on the v5e ----

@pytest.fixture(scope="module")
def recorded_spans():
    t = trace.load(os.path.join(DATA, "v5e-spans.xplane.pb"))
    with open(os.path.join(DATA, "v5e-spans.expected.json")) as f:
        return t, json.load(f)


@pytest.mark.parametrize("span", [
    "sched.admit", "sched.decode.build", "sched.decode.dispatch",
    "sched.decode.sync", "sched.decode.emit", "sched.yield", "sync.fetch",
    "dispatch.decode"])
def test_recorded_span_means(recorded_spans, span):
    t, want = recorded_spans
    ms, n = host_spans.read(_run(t), {"stat": "span_mean_ms", "span": span})
    assert [ms, n] == pytest.approx(want["span_mean_ms"][span], rel=1e-6)


def test_recorded_sync_tail(recorded_spans):
    t, want = recorded_spans
    ms, n = host_spans.read(_run(t), {"stat": "sync_tail_mean_ms",
                                      "span": SYNC, "program": DECODE})
    assert [ms, n] == pytest.approx(want["sync_tail_mean_ms"], rel=1e-6)
    # ready -> running again is a few milliseconds, not the whole fetch
    assert 1.0 < ms < 10.0


def test_recorded_idle_time_has_names(recorded_spans):
    t, want = recorded_spans
    assert len(t.ops[0]) == want["n_ops"]
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-6)
    idle = sum(e - s for s, e in host_spans.idle_gaps_of(t, 0))
    assert idle == pytest.approx(want["idle_s"], rel=1e-4)    # ns against ps
    named = host_spans.read(_run(t), {"stat": "idle_covered_pct",
                                      "spans": ALL_SPANS})
    assert named == pytest.approx(
        100 * want["idle_in_spans_s"] / want["idle_between_sched_spans_s"],
        rel=1e-4)
    assert named > 95.0
    assert host_spans.read(_run(t), {"stat": "idle_covered_pct",
                                     "spans": ["sched.wait"]}) == 0.0


def test_recorded_gaps_are_labelled_by_the_programs_spans(recorded_spans):
    t, _ = recorded_spans
    gaps = trace.idle_gaps(t, 0, 5)
    assert all(": sched." in label or ": dispatch." in label
               for label, _ in gaps), gaps
    assert all("jit_decode_step(" in label for label, _ in gaps), gaps


def test_a_gap_goes_to_the_programs_span_before_the_runtimes_threads():
    """On four chips several runtime threads write an event of one name
    inside a gap; summed, they outweigh the one scheduler span that
    holds it. The program's span is the label all the same."""
    runtime = [("PjitFunction(decode_step)", 3.1, 4.9)] * 4
    host = runtime + [("sched.decode.dispatch", 3.0, 5.0), ("dispatch.decode", 3.2, 4.8),
                      ("sched.yield", 6.0, 6.5)]
    gaps = trace.idle_gaps(_trace(OPS, host), 0, 3)
    assert [round(s, 6) for _, s in gaps] == [3.0, 2.0, 1.0]
    # 6-9: the span with most time inside covers a sixth of it
    assert gaps[0][0].endswith(": python, then sched.yield")
    assert gaps[1][0].endswith(": sched.decode.dispatch")
    assert gaps[2][0].endswith(": python")                      # 1-2: nothing on record
    # a program from before the spans keeps the runtime's label
    marks = [("ThreadpoolListener", 0.0, 0.0), ("ThreadpoolListener", 10.0, 10.0)]
    gaps = trace.idle_gaps(_trace(OPS, runtime + marks), 0, 3)
    assert gaps[1][0].endswith(": PjitFunction(decode_step)")
    # gaps are looked for where the host is on record: the device planes
    # run on after the host plane's last event, and nothing could name that
    gaps = trace.idle_gaps(_trace(OPS, host, edges=False), 0, 3)   # host 3.0-6.5
    assert [(label.split(": ")[1], round(s, 6)) for label, s in gaps] == [
        ("sched.decode.dispatch", 2.0), ("sched.yield", 0.5)]


def test_recorded_gaps_keep_the_programs_spans_under_more_runtime_threads(recorded_spans):
    """The recorded one-chip capture with every host event that is not
    the scheduler's written four times over (the runtime's, as the
    threads of a four-chip host write them, and the frontend's leaves):
    every gap is still labelled by the scheduler's span that holds it.
    Summed by name alone, they took the label."""
    t, _ = recorded_spans
    runtime = [h for h in t.host if not h.name.startswith(trace.PROGRAM_SPANS)]
    assert runtime
    crowded = dataclasses.replace(t, host=t.host + 3 * runtime)
    want = trace.idle_gaps(t, 0, 5)
    assert trace.idle_gaps(crowded, 0, 5) == want
    assert all(": sched." in label or ": dispatch." in label for label, _ in want), want
