"""The program's seams in the profiler's trace (ISSUE 23).

A tiny engine served over HTTP, and the scheduler over a fake runner on
every decode path, each under ONE real ``jax.profiler`` capture with the
benchmark's options (host tracer 2, Python tracer off). The CPU backend
writes ``TraceAnnotation`` spans to the ``/host:CPU`` plane as the TPU
backend does, so what these tests read is what ``benchmark/harness/
trace.py`` reads on the chip. A scenario runs once a module; the tests
are assertions on what it recorded.
"""

import asyncio
import glob
import json
import logging
import os
import re
import socket
import time

import pytest

from fixtures import make_model_dir

SCHED = ("sched.admit", "sched.prefill.build", "sched.prefill.dispatch",
         "sched.prefill.request", "sched.prefill.sync", "sched.prefill.emit",
         "sched.decode.build", "sched.decode.dispatch",
         "sched.decode.request", "sched.decode.sync", "sched.decode.emit",
         "sched.yield", "sched.wait")
CROSS_AWAIT = ("sched.prefill.sync", "sched.decode.sync", "sched.yield",
               "sched.wait")
FRONTEND = ("http.ingress", "pre.tokenize", "detok.step", "http.sse_write")
FETCH = ("sync.fetch", "sync.ready", "sync.copy")
PREFIX = ("the quick brown fox jumps over the lazy dog and keeps running "
          "through the quiet forest until the river bends ")


def _capture_start(trace_dir):
    """As ``capture_trace`` and GET /debug/profile start one: the
    benchmark's options, and ``clock.mark`` as the first event."""
    from dynamo_tpu.utils import profiling

    profiling.start_capture(trace_dir)


def _capture_stop(trace_dir):
    """Stop the capture; every host event as a dict, plus the planes'
    names."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for tid, line in enumerate(plane.lines):
            for e in line.events:
                events.append({
                    "name": e.name, "tid": tid, "start": e.start_ns,
                    "end": e.start_ns + e.duration_ns,
                    "stats": {k: v for k, v in e.stats},
                })
    return events


def _named(events, prefix):
    return sorted((e for e in events if e["name"].startswith(prefix)),
                  key=lambda e: e["start"])


# ---------------------------------------------------------------------
# the early request (ISSUE 36): a recording stand-in for every array a
# runner hands back, and one log of what the scheduler did with it
# ---------------------------------------------------------------------

class Recorded:
    """A device array's stand-in (as tests/test_kv_reuse.py's SlowD2H):
    it writes into the shared ``log`` when its copy to the host was
    requested and when it was read, and passes both on to the array it
    wraps, a jax one or the fake runner's numpy."""

    def __init__(self, arr, log):
        self.arr, self.log, self.nbytes = arr, log, arr.nbytes
        self.raw = arr      # what the fake runner's device reads

    def copy_to_host_async(self):
        self.log.append(("request", id(self)))
        start = getattr(self.arr, "copy_to_host_async", None)
        if start is not None:
            start()

    def is_ready(self):
        return getattr(self.arr, "is_ready", lambda: True)()

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        self.log.append(("read", id(self)))
        return np.asarray(self.arr)


# the outputs a scheduler may fetch, by the runner's method (``step``'s
# fifth are the prompt's rows, which the scheduler slices on the device)
OUTPUTS = {"step": (0, 1, 2, 3, 5), "decode_burst": (0, 1, 2, 3),
           "decode_burst_chained": (0, 1, 2, 3),
           "decode_burst_spec": (0, 1, 2)}


def _record(sched):
    """Wrap the outputs of ``sched.runner`` in ``Recorded`` and write
    the scheduler's own moments beside theirs: ("dispatch", method,
    ids, is it a decode step), ("turn",) where ``sched.yield`` is about to open, ("fetch",
    kind, ids) where ``_fetch`` is entered. Returns the log; the
    wrapped objects are kept alive in it so that no id is used twice."""
    log = []

    def wrap_method(name, which):
        real = getattr(sched.runner, name)

        def method(*a, **kw):
            fed = kw.get("prev_tokens")
            if isinstance(fed, Recorded):
                # the program reads the array itself, not the stand-in
                kw["prev_tokens"] = fed.arr
            out = list(real(*a, **kw))
            for i in which:
                out[i] = Recorded(out[i], log)
            # (the fourth: is it a decode step, one token a row?)
            log.append(("dispatch", name, [out[i] for i in which],
                        name == "step" and a[0].shape[1] == 1))
            return tuple(out)

        setattr(sched.runner, name, method)

    for name, which in OUTPUTS.items():
        if hasattr(sched.runner, name):
            wrap_method(name, which)
    turn, fetch = sched._frontend_turn, sched._fetch

    async def _frontend_turn():
        if not sched._turn_taken:
            log.append(("turn",))
        await turn()

    async def _fetch(loop, kind, arrays, *a, **kw):
        log.append(("fetch", kind, [id(x) for x in arrays]))
        return await fetch(loop, kind, arrays, *a, **kw)

    sched._frontend_turn, sched._fetch = _frontend_turn, _fetch
    return log


def _assert_requested_early(log, what):
    """Every array a fetch read had its copy requested exactly once,
    before the first read, and where the scheduler says: a decode
    step's or burst's at its own dispatch, with nothing of the
    scheduler's in between (``_decode_dispatch`` since ISSUE 57, the
    chain's bursts before it), so that a step read a pass later has it
    made already; a prefill chunk's and a verify step's where ``_fetch``
    is entered, before the frontend's turn. Returns (fetches, arrays
    fetched, arrays requested at a chained dispatch, fetches that the
    turn followed with nothing but their requests between, arrays of a
    decode step requested at its dispatch)."""
    made_by, at = {}, {}
    for i, entry in enumerate(log):
        if entry[0] == "dispatch":
            for x in entry[2]:
                made_by[id(x)] = (entry[1], i, len(entry[2]))
        elif entry[0] in ("request", "read"):
            at.setdefault((entry[0], entry[1]), []).append(i)
    fetches = [(i, e) for i, e in enumerate(log) if e[0] == "fetch"]
    arrays = chained = turned = stepped = 0
    for i, (_, kind, ids) in fetches:
        ids = [x for x in ids if x in made_by]   # not the sliced rows
        assert ids, (what, kind)
        inside = 0      # of this fetch's requests, those made in it
        for x in ids:
            arrays += 1
            requests, reads = at.get(("request", x)), at.get(("read", x))
            assert requests and len(requests) == 1, (what, kind, requests)
            assert reads and requests[0] < reads[0], (what, kind)
            method, made, n = made_by[x]
            if requests[0] < i:
                # at the dispatch: behind it at once, before the fetch
                assert made < requests[0] <= made + n, (what, method)
                assert kind == "decode", (what, method)
                if method in ("decode_burst_chained", "decode_burst_spec"):
                    chained += 1
                else:
                    stepped += 1
            else:
                # right behind the fetch's entry: the turn, where this
                # fetch takes it, opens after every request
                assert i < requests[0] <= i + len(ids), (what, method)
                assert method == "step", (what, method)
                inside += 1
        assert inside in (0, len(ids)), (what, kind)
        turned += log[i + inside + 1][0] == "turn"
    return len(fetches), arrays, chained, turned, stepped


# ---------------------------------------------------------------------
# scenario A: in=http out=jax at tiny widths, the default (synchronous)
# decode path, two requests that share a prefix
# ---------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _served_scenario(tmp):
    import aiohttp

    from dynamo_tpu.cli.run import build_engine, build_parser, run_http

    model_dir = make_model_dir(tmp, name="tiny-spans", context_length=256,
                               config_overrides={
                                   "hidden_size": 64, "intermediate_size": 128,
                                   "num_hidden_layers": 2,
                                   "num_attention_heads": 4,
                                   "num_key_value_heads": 2})
    extra = os.path.join(str(tmp), "extra.json")
    with open(extra, "w") as f:
        json.dump({"dtype": "float32", "prefill_buckets": [32, 64],
                   "max_prefill_batch": 1, "seed": 7}, f)
    port = _free_port()
    flags = build_parser().parse_args([
        "--model-path", model_dir, "--model-name", "tiny",
        "--allow-random-weights", "--http-host", "127.0.0.1",
        "--http-port", str(port), "--max-model-len", "128",
        "--max-batch-size", "4", "--num-kv-blocks", "96",
        "--kv-block-size", "8", "--extra-engine-args", extra])
    lines = []
    handler = logging.Handler()
    handler.emit = lambda r: lines.append(r.getMessage())
    serving_log = logging.getLogger("dynamo_tpu.engine.serving")
    serving_log.addHandler(handler)
    # setLevel, not an assignment to ``level``: a logger remembers what
    # ``isEnabledFor`` last said a level (``Logger._cache``), and only
    # setLevel forgets it. An engine that an earlier test of this worker
    # built has asked this logger about INFO under the root's WARNING;
    # with the level merely assigned, it went on answering no and the
    # lines below were never written (the driver's run of PR 56's tree)
    level = serving_log.level
    serving_log.setLevel(logging.INFO)
    try:
        engine, mdc = await build_engine("jax", flags)
    finally:
        serving_log.removeHandler(handler)
        serving_log.setLevel(level)
    task = asyncio.ensure_future(run_http(flags, engine, mdc))
    base = f"http://127.0.0.1:{port}"
    out = {"runner": engine.core_engine.runner, "serving_log": lines,
           "log": _record(engine.core_engine.scheduler)}

    async def complete(session, rid, prompt, n):
        chunks = 0
        async with session.post(
                f"{base}/v1/completions",
                json={"model": "tiny", "prompt": prompt, "max_tokens": n,
                      "temperature": 0, "stream": True,
                      "nvext": {"ignore_eos": True}},
                headers={"X-Request-Id": rid}) as r:
            assert r.status == 200, await r.text()
            async for line in r.content:
                chunks += line.startswith(b"data: {")
        return chunks

    async def metrics(session):
        async with session.get(f"{base}/metrics") as r:
            return await r.text()

    try:
        async with aiohttp.ClientSession() as session:
            for _ in range(200):
                try:
                    await metrics(session)
                    break
                except aiohttp.ClientError:
                    await asyncio.sleep(0.05)
            await complete(session, "warm", "hello there", 4)
            out["metrics_before"] = await metrics(session)
            del out["log"][:]
            trace_dir = os.path.join(str(tmp), "profile")
            _capture_start(trace_dir)
            t0 = time.monotonic()
            # the second request finds the first one's prefix cached
            await complete(session, "first", PREFIX + "alpha", 12)
            await complete(session, "second", PREFIX + "beta gamma", 12)
            await asyncio.sleep(0.05)   # an idle pass: sched.wait
            out["t"] = (t0, time.monotonic())
            out["events"] = _capture_stop(trace_dir)
            out["metrics_after"] = await metrics(session)
            async with session.get(f"{base}/debug/requests/second") as r:
                out["debug_second"] = await r.json()
            async with session.get(f"{base}/debug/requests") as r:
                out["debug_requests"] = await r.text()
    finally:
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        await engine.core_engine.close()
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    jsonl = os.path.join(str(tmp), "traces.jsonl")
    old = os.environ.get("DYN_TRACE_JSONL")
    os.environ["DYN_TRACE_JSONL"] = jsonl
    try:
        out = asyncio.run(asyncio.wait_for(_served_scenario(tmp), 300))
    finally:
        if old is None:
            os.environ.pop("DYN_TRACE_JSONL")
        else:
            os.environ["DYN_TRACE_JSONL"] = old
    deadline = time.monotonic() + 5.0   # the writer thread's last lines
    while time.monotonic() < deadline:
        try:
            with open(jsonl) as f:
                out["jsonl"] = {json.loads(line)["request_id"]: json.loads(line)
                                for line in f}
        except FileNotFoundError:
            out["jsonl"] = {}
        if "second" in out["jsonl"]:
            break
        time.sleep(0.05)
    return out


def _loop_tid(events):
    """The event loop's thread: the line that holds sched.admit."""
    return next(e["tid"] for e in events if e["name"] == "sched.admit")


@pytest.mark.parametrize("name", SCHED + FRONTEND + FETCH + (
    "dispatch.decode", "dispatch.prefill", "clock.mark"))
def test_every_span_of_the_table_is_in_the_capture(served, name):
    assert any(e["name"] == name for e in served["events"]), name


def test_span_names_are_a_fixed_set(served):
    ours = {e["name"] for e in served["events"]
            if e["name"].startswith(("sched.", "sync.", "dispatch.",
                                     "http.", "pre.", "detok.", "clock."))}
    allowed = set(SCHED + FRONTEND + FETCH) | {"clock.mark"}
    assert all(n in allowed or n.startswith("dispatch.") for n in ours), ours
    # ISSUE 35 added exactly these three to the set
    assert {"sync.ready", "sync.copy", "clock.mark"} <= ours
    # what varies is a stat, never part of the name
    assert all(n == n.lower() and " " not in n and not any(
        c.isdigit() for c in n) for n in ours), ours


def test_sched_spans_carry_their_pass_number(served):
    steps = [e["stats"].get("step") for e in _named(served["events"],
                                                    "sched.decode.build")]
    assert steps and all(isinstance(s, int) for s in steps)
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    d = next(e for e in served["events"] if e["name"] == "dispatch.decode")
    assert str(d["stats"]["key"]).startswith("b4_s1_w")


def test_sched_spans_do_not_overlap_on_the_loop_thread(served):
    tid = _loop_tid(served["events"])
    spans = [e for e in _named(served["events"], "sched.")
             if e["tid"] == tid]
    assert len(spans) > 40
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["start"], (a, b)


def test_no_span_wraps_a_whole_pass(served):
    """Every sched.* span of one pass carries that pass's number, and
    the first of a pass starts after the last of the pass before ended:
    nothing covers a pass from outside."""
    tid = _loop_tid(served["events"])
    by_pass = {}
    for e in _named(served["events"], "sched."):
        if e["tid"] == tid:
            by_pass.setdefault(e["stats"]["step"], []).append(e)
    assert len(by_pass) > 10
    ours = [e for e in served["events"] if e["tid"] == tid
            and e["name"].startswith(("sched.", "dispatch."))]
    for n, spans in by_pass.items():
        if len(spans) < 2:   # a pass the capture's edge cut
            continue
        lo, hi = spans[0]["start"], spans[-1]["end"]
        cover = [e for e in ours if e["start"] <= lo and e["end"] >= hi]
        assert not cover, (n, cover)


def test_leaf_spans_hold_no_await(served):
    """Nothing else of the program runs on the loop thread inside a leaf
    span; only the .sync spans, sched.yield and sched.wait cross an
    await, and what other tasks do meanwhile nests inside those."""
    tid = _loop_tid(served["events"])
    loop = [e for e in served["events"] if e["tid"] == tid and
            e["name"].startswith(("sched.", "http.", "pre.", "detok."))]
    leaves = [e for e in loop if e["name"].startswith("sched.")
              and e["name"] not in CROSS_AWAIT]
    others = [e for e in loop if not e["name"].startswith("sched.")]
    assert others
    for leaf in leaves:
        inside = [o["name"] for o in others
                  if leaf["start"] < o["start"] < leaf["end"]]
        assert not inside, (leaf["name"], inside)
    # and the frontend's work does land inside sched.yield / sched.*.sync
    crossing = [e for e in loop if e["name"] in CROSS_AWAIT]
    assert any(c["start"] <= o["start"] and o["end"] <= c["end"]
               for o in others for c in crossing)


def test_sched_spans_cover_the_loops_time(served):
    tid = _loop_tid(served["events"])
    spans = [e for e in _named(served["events"], "sched.")
             if e["tid"] == tid]
    total = spans[-1]["end"] - spans[0]["start"]
    covered = sum(e["end"] - e["start"] for e in spans)   # disjoint: above
    assert covered >= 0.95 * total, covered / total


def _passes(events, tid):
    """The loop thread's sched.* spans by pass number, in time order."""
    by_pass = {}
    for e in _named(events, "sched."):
        if e["tid"] == tid:
            by_pass.setdefault(e["stats"]["step"], []).append(e)
    return by_pass


def _assert_one_turn_a_pass(events, sync_path, what):
    """sched.yield once a pass that progressed and never twice; on a
    synchronous decode path right after the pass's last dispatch (and
    the request for its result's copy, ``sched.decode.request``) and
    before the wait for a result, with ``inflight=1`` (ISSUE 32). The
    wait is for the pass's own step, or, where the step runs ahead
    (ISSUE 57), for the step of the pass before, this pass's being left
    in flight; the first step of a run is waited for by no pass of its
    own, and that pass ends on its turn. Returns the passes whose turn
    came between a dispatch and a wait."""
    by_pass = _passes(events, _loop_tid(events))
    first, last = min(by_pass), max(by_pass)
    hidden = 0
    for n, spans in by_pass.items():
        if n in (first, last):     # a pass the capture's edge cut
            continue
        names = [e["name"] for e in spans]
        if "sched.wait" in names:
            assert "sched.yield" not in names, (what, n, names)
            continue
        assert names.count("sched.yield") == 1, (what, n, names)
        i = names.index("sched.yield")
        if sync_path and "sched.decode.dispatch" in names:
            # the request for the result's copy to the host is all
            # that stands between the dispatch and the turn
            assert names[i - 2:i] == ["sched.decode.dispatch",
                                      "sched.decode.request"], (what, names)
            assert names[i + 1:] in (["sched.decode.sync",
                                      "sched.decode.emit"], []), (what, names)
            assert spans[i]["stats"]["inflight"] == 1, (what, n)
            hidden += bool(names[i + 1:])
    return hidden


def test_the_turn_comes_once_a_pass_between_dispatch_and_sync(served):
    assert _assert_one_turn_a_pass(served["events"], True, "served") > 15


def test_tokens_are_written_while_the_next_step_is_in_flight(served):
    """The SSE writes of a decode pass's tokens lie inside the next
    pass's sched.yield, which starts after that pass's dispatch."""
    ev = served["events"]
    tid = _loop_tid(ev)
    turns = [e for e in _named(ev, "sched.yield")
             if e["tid"] == tid and e["stats"].get("inflight") == 1]
    writes = [e for e in _named(ev, "http.sse_write") if e["tid"] == tid]
    inside = [w for w in writes
              if any(t["start"] <= w["start"] and w["end"] <= t["end"]
                     for t in turns)]
    assert len(writes) > 15 and len(inside) >= 0.7 * len(writes), (
        len(inside), len(writes))
    dispatches = _named(ev, "sched.decode.dispatch")
    for t in turns:
        assert any(d["stats"]["step"] == t["stats"]["step"]
                   and d["end"] <= t["start"] for d in dispatches), t


def test_yield_counters_say_the_turn_was_hidden(served):
    total = _delta(served, "dynamo_scheduler_yield_seconds_total")
    hidden = _delta(served, "dynamo_scheduler_yield_inflight_seconds_total")
    assert 0 < hidden <= total
    # two requests of 12 tokens: nearly every turn follows a dispatch
    assert hidden >= 0.5 * total, (hidden, total)
    spans = sum(e["end"] - e["start"]
                for e in _named(served["events"], "sched.yield")) / 1e9
    assert spans <= total * 1.05 + 1e-3     # the counter times the span


def test_sync_fetch_is_on_an_executor_thread_inside_the_sync_span(served):
    tid = _loop_tid(served["events"])
    fetches = _named(served["events"], "sync.fetch")
    syncs = [e for e in served["events"]
             if e["name"] in ("sched.decode.sync", "sched.prefill.sync")]
    assert fetches and all(f["tid"] != tid for f in fetches)
    for f in fetches:
        assert any(s["start"] <= f["start"] and f["end"] <= s["end"]
                   for s in syncs), f


def _assert_fetch_parts(events, what):
    """Every ``sync.fetch`` holds ``sync.ready`` and then, where more
    than the tokens were fetched, ``sync.copy``: on its own executor
    thread, one after the other, inside the pass's ``sched.*.sync``; and
    no part lies outside a fetch (ISSUE 35). Returns (fetches, copies)."""
    tid = _loop_tid(events)
    syncs = [e for e in events
             if e["name"] in ("sched.decode.sync", "sched.prefill.sync")]
    parts = [e for e in events if e["name"] in ("sync.ready", "sync.copy")]
    fetches = _named(events, "sync.fetch")
    assert fetches, what
    copies = 0
    for f in fetches:
        assert f["tid"] != tid, (what, f)
        inside = sorted((e for e in parts if e["tid"] == f["tid"]
                         and f["start"] <= e["start"] and e["end"] <= f["end"]),
                        key=lambda e: e["start"])
        names = [e["name"] for e in inside]
        assert names in (["sync.ready"], ["sync.ready", "sync.copy"]), (
            what, names)
        assert inside[0]["stats"]["bytes"] > 0, (what, inside[0])
        if len(inside) == 2:
            assert inside[0]["end"] <= inside[1]["start"], (what, inside)
            assert inside[1]["stats"]["arrays"] >= 1, (what, inside[1])
            assert inside[1]["stats"]["bytes"] > 0, (what, inside[1])
            copies += 1
        holds = [s for s in syncs
                 if s["start"] <= f["start"] and f["end"] <= s["end"]]
        assert len(holds) == 1 and holds[0]["tid"] == tid, (what, f, holds)
    assert len(parts) == len(fetches) + copies, what
    return len(fetches), copies


def test_a_fetch_is_written_in_its_parts(served):
    """The default path fetches the tokens, then the log-probabilities
    and the two top-K arrays: three copies a fetch."""
    fetches, copies = _assert_fetch_parts(served["events"], "served")
    assert fetches > 20 and copies == fetches
    assert {e["stats"]["arrays"] for e in served["events"]
            if e["name"] == "sync.copy"} == {3}


def test_every_result_is_requested_at_its_dispatch(served):
    """ISSUE 36: the four arrays of a step are asked for straight after
    its dispatch (a decode step's there, a prefill chunk's where
    ``_fetch`` is entered) and before ``sched.yield`` opens; the executor
    thread's ``np.asarray`` finds the request made."""
    fetches, arrays, chained, turned, stepped = _assert_requested_early(
        served["log"], "served")
    assert fetches > 20 and arrays == 4 * fetches and chained == 0
    assert turned > 15 and stepped > 60
    stats = [e["stats"]["prefetched"]
             for e in _named(served["events"], "sync.fetch")]
    assert len(stats) == fetches and set(stats) == {4}


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_prefetched_counter_is_arrays_times_fetches(served, kind):
    label = '{kind="%s"}' % kind
    n = _delta(served, "dynamo_scheduler_fetches_total", label)
    assert n > 0
    assert _delta(served, "dynamo_scheduler_fetch_prefetched_total",
                  label) == 4 * n


def _prom_sum(text, name):
    """Every sample of ``name``, whatever its labels, summed."""
    return sum(float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
               if line.startswith((name + " ", name + "{")))


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_fetch_counters_count_every_wait(served, kind):
    label = 'kind="%s"' % kind
    n = _delta(served, "dynamo_scheduler_fetches_total", "{%s}" % label)
    spans = [e for e in served["events"]
             if e["name"] == "sched.%s.sync" % kind]
    assert n == len(spans) > 0
    for part in ("ready_wait", "copy", "hop"):
        v = _delta(served, "dynamo_scheduler_fetch_seconds_total",
                   '{%s,part="%s"}' % (label, part))
        assert v > 0, (kind, part)
    # copy by the host's clock encloses the sync.copy spans
    copy = _delta(served, "dynamo_scheduler_fetch_seconds_total",
                  '{%s,part="copy"}' % label)
    inside = sum(c["end"] - c["start"] for c in served["events"]
                 if c["name"] == "sync.copy" and any(
                     s["start"] <= c["start"] and c["end"] <= s["end"]
                     for s in spans)) / 1e9
    assert copy >= inside > 0


def test_fetch_parts_sum_to_the_host_sync_phase(served):
    """ready_wait + copy + hop is the host_sync phase: both are stamped
    at the same two moments, to within 0.1 ms a pass."""
    name = "dynamo_scheduler_fetch_seconds_total"
    parts = (_prom_sum(served["metrics_after"], name)
             - _prom_sum(served["metrics_before"], name))
    phase = _delta(served, "dynamo_scheduler_phase_duration_seconds_sum",
                   '{phase="host_sync"}')
    n = (_prom_sum(served["metrics_after"], "dynamo_scheduler_fetches_total")
         - _prom_sum(served["metrics_before"],
                     "dynamo_scheduler_fetches_total"))
    assert n > 20 and phase > 0
    assert abs(parts - phase) <= 1e-4 * n, (parts, phase, n)


def test_clock_mark_lays_the_programs_clock_on_the_captures(served):
    """``clock.mark`` is the capture's first event of the program and
    carries ``time.monotonic_ns()`` of its start: a monotonic stamp of
    the program is at mark.start + (stamp - monotonic_ns)."""
    ev = served["events"]
    marks = _named(ev, "clock.mark")
    assert len(marks) == 1
    mark = marks[0]
    ours = [e for e in ev if e["name"].startswith(
        ("sched.", "sync.", "dispatch.", "http.", "pre.", "detok."))]
    assert mark["start"] <= min(e["start"] for e in ours)

    def on_capture(t_monotonic):
        return mark["start"] + (t_monotonic * 1e9
                                - mark["stats"]["monotonic_ns"])

    t0, t1 = served["t"]              # taken right after the start, and
    assert 0 <= on_capture(t0) - mark["end"] < 50e6     # before the stop
    assert max(e["end"] for e in ours) <= on_capture(t1) + 1e6
    # a request's own record, laid on the capture: its first mark falls
    # inside that request's http.ingress span
    rec = served["jsonl"]["second"]
    at = on_capture(rec["t0_monotonic"])
    ingress = _named(ev, "http.ingress")
    assert any(i["start"] - 1e6 <= at <= i["end"] + 1e6 for i in ingress), (
        at, [(i["start"], i["end"]) for i in ingress])


def test_dispatch_span_nests_in_the_schedulers_and_holds_the_runtimes(served):
    ev = served["events"]
    disp = next(e for e in _named(ev, "dispatch.decode"))
    outer = [e for e in ev if e["name"] == "sched.decode.dispatch"
             and e["start"] <= disp["start"] and disp["end"] <= e["end"]]
    assert outer
    inner = {e["name"] for e in ev if e["tid"] == disp["tid"]
             and disp["start"] <= e["start"] and e["end"] <= disp["end"]}
    assert any(n.startswith("PjitFunction(") for n in inner), inner


def test_a_step_dispatch_says_how_many_host_arrays_it_sent(served):
    """One packed array a step (engine/step_inputs.py): the span's
    ``arrays`` stat is that mechanism's counter."""
    for name in ("dispatch.decode", "dispatch.prefill"):
        spans = _named(served["events"], name)
        assert spans
        assert {int(e["stats"]["arrays"]) for e in spans} == {1}, name


def test_compiled_programs_have_a_name_each(served):
    """What the TPU's ``XLA Modules`` line would show: the jitted
    functions' names, as the runtime's own host spans carry them."""
    names = {e["name"] for e in served["events"]}
    assert "PjitFunction(decode_step)" in names
    assert "PjitFunction(prefill_step)" in names
    assert "PjitFunction(step)" not in names
    runner = served["runner"]
    assert runner._decode_step.__name__ == "decode_step"
    assert runner._prefill_step.__name__ == "prefill_step"


def _prom(text, name, labels=""):
    for line in text.splitlines():
        if line.startswith(name + labels + " ") or (
                labels and line.startswith(name + "{")
                and all(p in line for p in labels.strip("{}").split(","))):
            return float(line.rsplit(" ", 1)[1])
    return None


def _delta(served, name, labels=""):
    return (_prom(served["metrics_after"], name, labels)
            - (_prom(served["metrics_before"], name, labels) or 0.0))


def test_prefix_counters_move_by_the_pairs_tokens(served):
    second = served["jsonl"]["second"]
    first = served["jsonl"]["first"]
    looked = _delta(served, "dynamo_kv_prefix_lookup_tokens_total")
    hit = _delta(served, "dynamo_kv_prefix_hit_tokens_total")
    assert looked == (first["cached_tokens"] + first["computed_tokens"]
                      + second["cached_tokens"] + second["computed_tokens"])
    assert hit == first["cached_tokens"] + second["cached_tokens"]
    # whole blocks of the shared prefix, and only for the second request
    assert first["cached_tokens"] == 0
    assert second["cached_tokens"] >= 16 and second["cached_tokens"] % 8 == 0
    assert second["computed_tokens"] > 0


def test_queue_wait_histogram_counts_each_admission(served):
    assert _delta(served, "dynamo_scheduler_queue_wait_seconds_count") == 2
    s = _delta(served, "dynamo_scheduler_queue_wait_seconds_sum")
    assert 0 <= s < served["t"][1] - served["t"][0]


@pytest.mark.parametrize("phase", ["device_init", "weights", "kv_cache",
                                   "warmup"])
def test_startup_gauge_has_each_phase(served, phase):
    v = _prom(served["metrics_after"], "dynamo_engine_startup_seconds",
              '{phase="%s"}' % phase)
    assert v is not None and v >= 0
    assert v == pytest.approx(served["runner"].startup_s[phase])
    assert _prom(served["metrics_after"], "dynamo_engine_xla_compiles_total",
                 '{phase="late"') is None   # warm-up swept every shape


# the start-up timeline (ISSUE 50): one list of marks from the package's
# import to the service listening, each phase the time from the mark
# before it
MARKS = ("import", "backend", "model_card", "device_init", "weights",
         "kv_cache", "runner", "engine", "warmup", "scheduler", "listening")
PARTS = ("trace", "lower", "load", "compile", "rest")


def _mark(served, name):
    return _prom(served["metrics_after"],
                 "dynamo_engine_startup_mark_monotonic_seconds",
                 '{mark="%s"}' % name)


def _phase(served, name):
    return _prom(served["metrics_after"], "dynamo_engine_startup_seconds",
                 '{phase="%s"}' % name)


def test_startup_marks_are_in_order(served):
    import dynamo_tpu

    marks = served["runner"].startup.marks
    assert tuple(name for name, _ in marks) == MARKS
    times = [t for _, t in marks]
    assert times == sorted(times)
    assert [_mark(served, name) for name in MARKS] == times
    # the program's earliest moment, on the clock of the request records
    assert times[0] == dynamo_tpu.T_IMPORT
    assert times[-1] < served["t"][0]


@pytest.mark.parametrize("phase", MARKS[1:] + ("warmup_wait", "serve"))
def test_startup_gauge_has_the_timelines_phases(served, phase):
    v = _phase(served, phase)
    assert v is not None and v >= 0
    assert v == pytest.approx(served["runner"].startup_s[phase])
    at = dict(served["runner"].startup.marks)
    if phase in at:   # the time from the mark before it
        before = MARKS[MARKS.index(phase) - 1]
        assert v == pytest.approx(at[phase] - at[before], abs=2e-6)


def test_startup_phases_sum_to_the_timeline(served):
    total = _mark(served, "listening") - _mark(served, "import")
    assert sum(_phase(served, p) for p in MARKS[1:]) == pytest.approx(
        total, abs=0.2)
    # serve lies across two phases and warmup_wait inside one: no terms
    assert _phase(served, "serve") == pytest.approx(
        _phase(served, "scheduler") + _phase(served, "listening"))
    assert _phase(served, "warmup_wait") <= _phase(served, "warmup")


def test_startup_record_is_in_the_jsonl_and_not_in_the_ring(served):
    import dynamo_tpu

    rec = served["jsonl"]["startup"]
    # inside the process's lifetime: its first mark is the import's
    assert rec["t0_monotonic"] == dynamo_tpu.T_IMPORT < time.monotonic()
    assert [s["name"] for s in rec["spans"]] == list(MARKS[1:])
    for s in rec["spans"]:
        assert s["duration_s"] == pytest.approx(
            served["runner"].startup_s[s["name"]])
    assert rec["total_s"] == pytest.approx(
        _mark(served, "listening") - _mark(served, "import"), abs=1e-5)
    assert {p["program"] for p in rec["programs"]} == {
        "decode", "prefill", "sample_row"}
    for p in rec["programs"]:
        assert "key" in p and p["phase"] == "startup"
        assert sum(p[part + "_s"] for part in PARTS) == pytest.approx(
            p["duration_s"])
    assert "second" in served["debug_requests"]
    assert "startup" not in served["debug_requests"]


def test_startup_is_logged_beside_the_device_line(served):
    lines = served["serving_log"]
    at = [i for i, ln in enumerate(lines) if ln.startswith("engine start-up: ")]
    assert len(at) == 1
    assert lines[at[0] - 1].startswith("engine device: ")
    rec = json.loads(lines[at[0]][len("engine start-up: "):])
    # the marks so far: the HTTP service adds ``listening``
    assert [s["name"] for s in rec["spans"]] == list(MARKS[1:-1])
    assert rec["request_id"] == "startup" and len(rec["programs"]) == 5


def test_warmup_is_its_first_dispatches_in_parts_and_the_wait(served):
    """Every tracked dispatch of start-up is one of warm-up's: the five
    parts over all programs and ``warmup_wait`` are ``warmup``."""
    text = served["metrics_after"]
    parts = sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("dynamo_engine_xla_compile_part_seconds_total{")
        and 'phase="startup"' in line)
    assert parts > 0
    assert parts + _phase(served, "warmup_wait") == pytest.approx(
        _phase(served, "warmup"), abs=1e-4)
    # what weight init's helper jits compiled is counted, apart
    assert _prom(text, "dynamo_engine_xla_compile_part_seconds_total",
                 '{part="compile",phase="startup_untracked",'
                 'program="untracked"}') > 0
    # no series carries a shape key
    assert 'key="' not in text


@pytest.mark.parametrize("field", ["cached_tokens", "computed_tokens",
                                   "preemptions", "decode_tokens",
                                   "t0_monotonic"])
def test_request_record_has_the_new_fields(served, field):
    for rec in (served["jsonl"]["second"], served["debug_second"]):
        assert field in rec, rec
    rec = served["jsonl"]["second"]
    assert rec["preemptions"] == 0
    assert rec["decode_tokens"] == 11          # 12 tokens, the first from prefill
    assert served["t"][0] <= rec["t0_monotonic"] <= served["t"][1]


# ---------------------------------------------------------------------
# scenario B: the scheduler's other decode paths, over the fake runner
# of tests/test_decode_pipeline.py (no compile, no model)
# ---------------------------------------------------------------------

PATHS = {
    "ahead": dict(depth=1, ahead=True),
    "sync": dict(depth=1),
    "burst": dict(depth=1, k=4),
    "chained": dict(depth=2),
    "chained_k4": dict(depth=2, k=4),
    "spec_sync": dict(depth=1, spec=True),
    "spec_chained": dict(depth=2, spec=True),
}


def _path_run(path, record):
    """One run of the scheduler over the fake runner on ``path``: the
    clients' streams, the scheduler, and the log of ``_record`` or, with
    ``record`` false, the fake's plain numpy results and no log."""
    import test_decode_pipeline as dp

    kw = dict(PATHS[path])
    kw_spec = kw.pop("spec", False)
    if kw_spec:
        # an 8-token vocabulary and a repetitive prompt, so that the
        # ngram proposer has matches and the verify path runs
        config = dp._spec_config(kw.pop("depth"))
        reqs = [dp._request([1, 2, 1, 2, 1, 2], 24)]
    else:
        # ``ahead``: the runner feeds a step its tokens on the device, so
        # the scheduler dispatches each decode step before it has read the
        # one before (the other paths' runner does not, and they run as
        # they ran before ISSUE 57)
        runner_cls = dp.FedRunner if kw.pop("ahead", False) else dp.FakeRunner
        config = dp._config(kw.pop("depth"), k=kw.pop("k", 1), **kw)
        reqs = [dp._request(p, 21) for p in dp.PROMPTS]
    box = {}

    def hooks(sched):
        box.update(sched=sched, log=_record(sched) if record else None)

    box["streams"] = dp._run(config, reqs, hooks=hooks,
                             **({} if kw_spec else {"runner_cls": runner_cls}))
    return box


@pytest.fixture(scope="module", params=sorted(PATHS))
def path_run(request, tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("path-" + request.param))
    _capture_start(trace_dir)
    box = {}
    try:
        box = _path_run(request.param, record=True)
    finally:
        box["events"] = _capture_stop(trace_dir)
    box["path"] = request.param
    return box


@pytest.fixture(scope="module")
def path_events(path_run):
    return path_run["path"], path_run["events"], path_run["sched"]


def test_every_decode_path_writes_the_same_names(path_events):
    path, events, sched = path_events
    names = {e["name"] for e in events}
    for want in ("sched.admit", "sched.prefill.build",
                 "sched.prefill.dispatch", "sched.prefill.sync",
                 "sched.prefill.emit", "sched.decode.build",
                 "sched.decode.dispatch", "sched.decode.sync",
                 "sched.decode.emit", "sync.fetch", "sched.yield"):
        assert want in names, (path, want)
    if path in ("chained", "chained_k4", "spec_chained"):
        assert sched.pipeline_bursts > 0, path
    if path.startswith("spec"):
        assert sched.spec_proposed > 0, path


def test_every_decode_path_writes_a_fetch_in_its_parts(path_events):
    """All six sites go through the one ``_fetch``: whichever path, a
    fetch is ``sync.ready`` then ``sync.copy`` on an executor thread
    inside the pass's ``sched.*.sync``; a fetch of the tokens alone
    (a synchronous verify step's greedy rows) has no copy."""
    path, events, sched = path_events
    fetches, copies = _assert_fetch_parts(events, path)
    assert fetches > 3 and copies >= 1, path
    if path == "spec_sync":
        assert copies < fetches, path
    else:
        assert copies == fetches, path
    # the capture covers the scheduler's whole life: each fetch counted
    assert sum(sched._fetches_ctr.values.values()) == fetches, path


def test_every_decode_path_splits_host_sync_into_its_parts(path_events):
    """ready_wait + copy + hop over the run is the host_sync phase's
    sum, to within 0.1 ms a pass, and each part is counted under the
    kind of the pass that waited."""
    path, _, sched = path_events
    parts = sched._fetch_ctr.values
    kinds = {dict(k)["kind"] for k in sched._fetches_ctr.values}
    assert kinds == {"decode", "prefill"}, path
    assert {(dict(k)["kind"], dict(k)["part"]) for k in parts} == {
        (kind, part) for kind in kinds
        for part in ("ready_wait", "copy", "hop")}, path
    assert all(v >= 0 for v in parts.values()), path
    n = sum(sched._fetches_ctr.values.values())
    phase = sched._phase_hist.sums[(("phase", "host_sync"),)]
    assert sched._phase_hist.totals[(("phase", "host_sync"),)] == n, path
    assert abs(sum(parts.values()) - phase) <= 1e-4 * n, path


def test_every_decode_path_keeps_sched_spans_apart(path_events):
    path, events, _ = path_events
    tid = _loop_tid(events)
    spans = [e for e in _named(events, "sched.") if e["tid"] == tid]
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["start"], (path, a, b)
    # the fake runner answers in microseconds, so the few lines between
    # two spans weigh far more than on a real engine (95 % there, above);
    # a seam left without a span would still show
    total = spans[-1]["end"] - spans[0]["start"]
    assert sum(e["end"] - e["start"] for e in spans) >= 0.75 * total, path


def test_every_decode_path_takes_one_turn_a_pass(path_events):
    path, events, _ = path_events
    sync_path = path in ("ahead", "sync", "burst", "spec_sync")
    hidden = _assert_one_turn_a_pass(events, sync_path, path)
    if sync_path:
        assert hidden > 3, path


def test_the_step_ahead_is_dispatched_before_the_step_before_is_read(
        path_run):
    """ISSUE 57, the spans of a pass in their new order: build(k),
    dispatch(k) with ``ahead=1``, request(k), yield, sync(k-1),
    emit(k-1). By the log, the arrays a decode fetch reads were made one
    dispatch of ``step`` before the newest; on every other path by the
    newest, and no dispatch says ``ahead``."""
    path, log = path_run["path"], path_run["log"]
    flags = [int(e["stats"]["ahead"])
             for e in _named(path_run["events"], "sched.decode.dispatch")
             if "ahead" in e["stats"]]
    made = {id(x): i for i, e in enumerate(log) if e[0] == "dispatch"
            for x in e[2]}
    steps = [i for i, e in enumerate(log) if e[0] == "dispatch" and e[3]]
    behind = []     # decode dispatches between a result's own and its fetch
    for i, e in enumerate(log):
        if e[0] == "fetch" and e[1] == "decode" and e[2][0] in made:
            behind.append(sum(made[e[2][0]] < j < i for j in steps))
    sched = path_run["sched"]
    ahead = sum(sched._ahead_ctr.values.values())
    if path != "ahead":
        assert not any(flags) and ahead == 0, path
        if not path.startswith("chained"):
            assert set(behind) == {0}, path
        return
    # all but the run's first step, and the steps after a pass in which a
    # prompt's last chunk was read first (none here: one prefill pass)
    assert sum(flags) == ahead == len(flags) - 1 > 15
    assert behind.count(1) == ahead and behind.count(0) == 1
    by_pass = _passes(path_run["events"], _loop_tid(path_run["events"]))
    full = [[e["name"] for e in spans] for spans in by_pass.values()
            if sum(e["name"] == "sched.decode.dispatch" for e in spans)
            and any(e["name"] == "sched.decode.sync" for e in spans)]
    assert len(full) >= ahead - 1
    for names in full:
        assert names[-6:] == [
            "sched.decode.build", "sched.decode.dispatch",
            "sched.decode.request", "sched.yield", "sched.decode.sync",
            "sched.decode.emit"], names
    assert not sched._sync_fallback_ctr.values
    assert sum(sched._ahead_discarded_ctr.values.values()) == 0


def _fetched(sched):
    """(fetches, arrays prefetched) over the scheduler's life."""
    return (sum(sched._fetches_ctr.values.values()),
            sum(sched._prefetched_ctr.values.values()))


def test_every_decode_path_requests_each_result_once_and_early(path_run):
    """ISSUE 36, over all six fetch sites: exactly one request an array,
    before the first read; a synchronous path's where ``_fetch`` is
    entered, before the turn; a chained burst's at the burst's dispatch,
    one or more bursts before its fetch."""
    path, sched = path_run["path"], path_run["sched"]
    fetches, arrays, chained, turned, stepped = _assert_requested_early(
        path_run["log"], path)
    assert (fetches, arrays) == _fetched(sched), path
    if path in ("chained", "chained_k4", "spec_chained"):
        assert chained > 0, path
    else:
        assert chained == 0 and turned > 3, path
    if path in ("ahead", "sync", "burst"):
        assert stepped > 12, path
    stats = [e["stats"]["prefetched"]
             for e in _named(path_run["events"], "sync.fetch")]
    assert len(stats) == fetches and sum(stats) == arrays, path


def test_every_decode_path_takes_a_plain_numpy_result(path_run):
    """A stand-in without ``copy_to_host_async`` (the fake runner's
    numpy, a test's array) passes through unasked and counts 0; the
    clients' tokens are the recorded run's."""
    plain = _path_run(path_run["path"], record=False)
    assert plain["streams"] == path_run["streams"]
    fetches, prefetched = _fetched(plain["sched"])
    assert fetches == _fetched(path_run["sched"])[0] > 3
    assert prefetched == 0
    assert set(plain["sched"]._prefetched_ctr.values) == set(
        plain["sched"]._fetches_ctr.values)


# ---------------------------------------------------------------------
# the scopes and the names are metadata: same compiled code
# ---------------------------------------------------------------------

def _lower_tiny_step(strip):
    """The tiny decode step lowered and compiled, with the named scopes
    as they are or made inert."""
    import contextlib

    import jax
    import numpy as np

    from dynamo_tpu.engine import model_runner as mr
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig

    real = jax.named_scope
    try:
        if strip:
            jax.named_scope = lambda name: contextlib.nullcontext()
        cfg = EngineConfig(
            model=ModelConfig(vocab_size=256, hidden_size=32,
                              intermediate_size=64, num_layers=2,
                              num_heads=2, num_kv_heads=1),
            max_batch_size=2, max_model_len=64, kv_block_size=8,
            num_kv_blocks=16, dtype="float32", allow_random_weights=True)
        r = mr.ModelRunner(cfg)
        b, w = 2, cfg.kv_width_buckets()[0]
        z2 = np.zeros((b, 1), np.int32)
        packed = mr.step_inputs.pack(
            z2, z2, np.zeros((b, w), np.int32), z2 - 1,
            keys=np.zeros((b, 2), np.uint32), want_top=False,
            context_lens=1, last_idx=0, top_k=0, temperature=0.0, top_p=1.0)
        lowered = r._decode_step.lower(
            r.params, r.kv_cache[0], r.kv_cache[1], *r.sample_state, packed,
            np.zeros(b, np.int32))
        return lowered.as_text(debug_info=True), lowered.compile()
    finally:
        jax.named_scope = real


def test_named_scopes_change_no_compiled_code():
    text, with_scopes = _lower_tiny_step(strip=False)
    bare_text, without = _lower_tiny_step(strip=True)
    for scope in ("embed", "attn", "mlp", "lm_head", "sampling"):
        assert re.search(rf'["/]{scope}/', text), scope
        assert not re.search(rf'["/]{scope}/', bare_text), scope
    assert with_scopes.cost_analysis() == without.cost_analysis()
    a, b = with_scopes.memory_analysis(), without.memory_analysis()
    for key in ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes"):
        assert getattr(a, key) == getattr(b, key), key

    def instructions(compiled):
        return sorted(re.findall(r" = \S+ ([\w\-]+)\(", compiled.as_text()))

    assert instructions(with_scopes) == instructions(without)


def test_span_helper_is_a_null_context_without_jax(monkeypatch):
    from dynamo_tpu.telemetry import tracing

    monkeypatch.setattr(tracing, "_annotation", False)
    with tracing.span("sched.admit", step=1):
        pass
