"""The sync tail in its parts (``readers/sync_parts.py``, ISSUE 35):
arithmetic on hand-made traces, then two cuts of traced v5e runs of
PR 35 that hold the parts (``data/v5e-sync-parts*.xplane.pb``; how each
was cut is in its ``.expected.json``): one whose planes agree, read as
it is and with its host plane shifted 2 ms early, and one whose planes
disagree as the profiler recorded them."""

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
STATS = ("ready_mean_ms", "copy_mean_ms", "hop_mean_ms", "hop_frontend_pct",
         "clock_slack_min_ms")
METRICS = {"step_sync_ready_ms": "ready_mean_ms",
           "step_sync_copy_ms": "copy_mean_ms",
           "step_sync_hop_ms": "hop_mean_ms",
           "sync_hop_busy_share": "hop_frontend_pct",
           "trace_clock_slack_ms": "clock_slack_min_ms"}


def _trace(ops, host, window=(0.0, 10.0), devices=(0,)):
    evs = [Event("fusion.1", s, e - s, own=e - s) for s, e in ops]
    return DeviceTrace(
        window=window, devices=list(devices),
        busy_s={d: sum(e - s for s, e in ops) for d in devices},
        span={d: (ops[0][0], ops[-1][1]) for d in devices},
        modules={d: [] for d in devices}, ops={d: list(evs) for d in devices},
        host=[Event(n, s, e - s) for n, s, e in host])


def _run(t, start=None, end=None):
    cell = Cell("c", 1, {}, "k", {}, "m", {"drain_s": 1}, [], [])
    return RunData(cell=cell, hf={}, serve={}, seconds=1.0, window=(0.0, 10.0),
                   setup_seconds=0.0, records=[], prom_start=start or {},
                   prom_end=end or {}, device_trace=t)


def _read(t, stat):
    return sync_parts.read(_run(t), {"stat": stat, "span": SPAN})


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
    ("hop_frontend_pct", 0.0, 3),
])
def test_parts_over_the_passes_the_lump_is_read_from(stat, want, n):
    got, samples = _read(_trace(OPS, HOST), stat)
    assert samples == n and got == pytest.approx(want)


def test_the_three_parts_are_the_lump():
    t = _trace(OPS, HOST)
    tail, n = host_spans.read(_run(t), {"stat": "sync_tail_mean_ms",
                                        "span": SPAN})
    parts = sum(_read(t, s)[0] for s in STATS[:3])
    assert n == 3 and parts == pytest.approx(tail)


def test_ready_runs_from_the_last_chip():
    t = _trace(OPS, HOST, devices=(0, 1))
    t.ops[1] = [Event("fusion.1", 0.0, 1.05, own=1.05)]   # chip 1 ends at 1.05
    got, _ = _read(t, "ready_mean_ms")
    assert got == pytest.approx(1e3 * (0.05 + 0.3 + 0.2) / 3)


def test_hop_share_inside_the_frontends_leaves():
    """Hops: 1.3-1.6, 3.3-3.4, 6.3-6.9 (1.0 s). The union of the
    frontend's leaves covers 0.1 + 0.1 + 0.25 of it; the runtime's and
    the scheduler's own events count nothing."""
    host = HOST + [
        ("http.sse_write", 1.2, 1.4), ("detok.step", 1.35, 1.4),   # 1.3-1.4
        ("pre.tokenize", 3.0, 3.5),                                  # 3.3-3.4
        ("http.ingress", 6.5, 6.7), ("http.sse_write", 6.65, 6.75),  # 6.5-6.75
        ("PjitFunction(decode_step)", 6.3, 6.9), ("sched.admit", 6.3, 6.9)]
    got, n = _read(_trace(OPS, host), "hop_frontend_pct")
    assert n == 3 and got == pytest.approx(100 * 0.45 / 1.0)


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
    # a host plane that runs late only adds to the slack: it cannot be seen
    late = [(n, s + 0.15, e + 0.15) for n, s, e in HOST]
    assert _read(_trace(OPS, late), "clock_slack_min_ms")[0] == pytest.approx(
        1e3 * (0.1 + 0.15))


@pytest.mark.parametrize("stat", STATS)
def test_a_program_without_the_parts_gives_nothing_to_read(stat):
    """The parent of PR 35 writes ``sync.fetch`` alone: the line leaves
    the metric out, and nothing raises."""
    whole = [e for e in HOST if e[0] not in ("sync.ready", "sync.copy")]
    assert _read(_trace(OPS, whole), stat) is None
    run = _run(None)                              # an untraced run
    assert sync_parts.read(run, {"stat": stat, "span": SPAN}) is None


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
def test_every_cell_lists_the_six_metrics_of_the_scheduler(cell_name):
    """No ``workloads`` list: every cell that reports the gap reports
    them, from the manifest's entries and the metric's file alone."""
    cell = manifest.load_cell(cell_name)
    by_name = {m.name: m for m in cell.per_layer}
    for name, stat in METRICS.items():
        m = by_name[name]
        assert (m.reader, m.args["stat"], m.args["span"]) == (
            "sync_parts", stat, SPAN)
        assert (m.unit, m.moves) == ("%" if "share" in name else "ms",
                                     "itl_p50_ms")
        assert m.better == ("higher" if name == "trace_clock_slack_ms"
                            else "lower")
    m = by_name["fetch_tail_ms_per_pass"]
    assert (m.reader, m.args, m.unit, m.better) == (
        "sync_parts", {"stat": "counter_tail_ms", "kind": "decode"}, "ms",
        "lower")
    t = _trace(OPS, HOST)
    run = dataclasses.replace(_run(t), cell=cell)
    got = {name: read_metric(by_name[name], run)[0] for name in METRICS}
    tail = host_spans.read(run, {"stat": "sync_tail_mean_ms", "span": SPAN})[0]
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
                                       "span": "sched.%s.sync" % kind})
    assert n == len(want[kind]) > 0
    assert got == pytest.approx(_mean(want[kind], key), abs=1e-5)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_recorded_parts_are_the_recorded_lump(recorded_parts, kind):
    """ready + copy + hop against the accepted reader's tail over the
    same passes: apart by the two seams between the inner spans, some
    tens of microseconds while a capture runs."""
    t, want = recorded_parts
    span = "sched.%s.sync" % kind
    tail, n = host_spans.read(_run(t), {"stat": "sync_tail_mean_ms",
                                        "span": span})
    assert n == len(want[kind])
    assert tail == pytest.approx(_mean(want[kind], "tail_ms"), abs=1e-5)
    parts = sum(sync_parts.read(_run(t), {"stat": s, "span": span})[0]
                for s in STATS[:3])
    assert 0.0 <= tail - parts < 0.05
    # each part is what the issue expected to find, or is not: the hop is
    # the smallest, the copies cost as much as the transfer they follow
    by = {s: sync_parts.read(_run(t), {"stat": s, "span": span})[0]
          for s in STATS}
    assert 1.0 < by["ready_mean_ms"] < 1.6 and 1.0 < by["copy_mean_ms"] < 2.0
    assert 0.1 < by["hop_mean_ms"] < 0.3 and by["hop_frontend_pct"] == 0.0
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
    args = {"span": SPAN}
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


def test_recorded_capture_whose_planes_disagree(recorded_parts_late_host,
                                                recorded_parts):
    """The first traced run of the same chip call, as the profiler wrote
    it: its host plane runs about 1.4 ms late, the next step's first
    operations seem to end inside the wait that came before their
    dispatch, and the check reads negative. The lump reads 0.01-0.07 ms
    in those passes and 4.4 ms in the others (2.6-3.0 ms in the capture
    whose planes agree); copy and hop read what they read there."""
    t, want = recorded_parts_late_host
    agree, want_agree = recorded_parts
    assert (len(t.ops[0]), len(t.modules[0]), len(t.host)) == (
        want["n_ops"], want["n_modules"], want["n_host"])
    args = {"span": SPAN}
    slack, n = sync_parts.read(_run(t), dict(args, stat="clock_slack_min_ms"))
    assert n == len(want["decode"]) == 5
    assert slack == pytest.approx(min(r["ready_ms"] for r in want["decode"]),
                                  abs=1e-5)
    assert slack < -1.0
    tails = sorted(r["tail_ms"] for r in want["decode"])
    assert tails[2] < 0.1 and tails[3] > 4.0
    for stat, key, lo, hi in (("copy_mean_ms", "copy_ms", 1.0, 2.0),
                              ("hop_mean_ms", "hop_ms", 0.1, 0.3)):
        got, _ = sync_parts.read(_run(t), dict(args, stat=stat))
        assert got == pytest.approx(_mean(want["decode"], key), abs=1e-5)
        assert lo < got < hi
        assert abs(got - _mean(want_agree["decode"], key)) < 0.25
