"""Where in a pass the loop hands itself to the frontend (ISSUE 32).

``sched.yield`` comes once a progressed pass, between the pass's last
dispatch and the wait for its result, so that the consumers of
``out_queue`` (detokenizer, SSE write) run while the device computes.
The scheduler runs here over the fake runner of
``tests/test_decode_pipeline.py``; where the order matters the runner's
results take a set time to be ready (``SlowRunner``), and the spans are
recorded by a stand-in for ``telemetry.tracing.span`` (the capture's own
view is in ``tests/test_trace_spans.py``).
"""

import asyncio
import contextlib
import time

import numpy as np
import pytest

import test_decode_pipeline as dp
from test_trace_spans import PATHS
from dynamo_tpu.engine import scheduler as scheduler_mod
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.protocols.common import OutputOptions, SamplingOptions

STEP_S = 0.04   # a device step of the slow runner

# of the decode paths tests/test_trace_spans.py enumerates: those that
# fetch a step's result a pass (their own step's before the next
# dispatch, or, on ``ahead``, the step before's behind it), and those
# that take a mixed batch (which is not eligible for speculation)
SYNC_PATHS = ("ahead", "sync", "burst", "spec_sync")
MIXED_PATHS = sorted(p for p in PATHS if not p.startswith("spec"))


class _Later:
    """A device array that is ready ``STEP_S`` after its dispatch:
    ``np.asarray`` blocks until then, as a fetch from the chip does."""

    def __init__(self, value, ready_at, log, n):
        self.value, self.ready_at, self.log, self.n = value, ready_at, log, n
        self.raw = value    # the device reads it without waiting

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.monotonic()))
        self.log.append(("ready", self.n, time.monotonic()))
        return np.asarray(self.value, dtype=dtype)

    def __getitem__(self, i):
        return _Later(self.value[i], self.ready_at, self.log, self.n)


class SlowRunner(dp.FakeRunner):
    """The fake runner, its decode results ready a set time after the
    dispatch. ``log`` holds ("dispatch" | "ready", n, monotonic)."""

    def __init__(self, config):
        super().__init__(config)
        self.log = []
        self.n = 0
        self.busy_until = 0.0

    def _later(self, outs):
        # one device: a step starts when the one before it has ended
        self.n += 1
        t = time.monotonic()
        self.log.append(("dispatch", self.n, t))
        self.busy_until = max(t, self.busy_until) + STEP_S
        return tuple(_Later(o, self.busy_until, self.log, self.n)
                     if isinstance(o, np.ndarray) else o for o in outs)

    def step(self, tokens, *a, **kw):
        outs = super().step(tokens, *a, **kw)
        # a prefill chunk answers at once: the decode steps are on trial
        return self._later(outs) if np.asarray(tokens).shape[1] == 1 \
            or kw.get("want_greedy") else outs

    def decode_burst(self, *a, **kw):
        return self._later(super().decode_burst(*a, **kw))


def _record_spans(monkeypatch):
    """Every span the scheduler opens, as (name, stats, t0, t1)."""
    spans = []

    @contextlib.contextmanager
    def span(name, **stats):
        t0 = time.monotonic()
        try:
            yield
        finally:
            spans.append((name, stats, t0, time.monotonic()))

    monkeypatch.setattr(scheduler_mod, "span", span)
    return spans


def _drive(config, reqs, runner_cls=dp.FakeRunner, on_output=None, hooks=None,
           settle_s=0.0):
    """The scheduler over ``runner_cls``; returns (what each request's
    consumer received, the scheduler)."""

    async def go():
        sched = Scheduler(runner_cls(config), config)
        if hooks:
            hooks(sched)
        sched.start()

        async def consume(er):
            got = []
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return got
                got.append((time.monotonic(), out))
                if on_output:
                    on_output(sched, er, out)
        try:
            for er in reqs:
                sched.add_request(er)
            got = await asyncio.gather(*(consume(er) for er in reqs))
            await asyncio.sleep(settle_s)
            return got, sched
        finally:
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


class SlowFedRunner(SlowRunner):
    feeds_tokens = True


def _path_runner(path, slow=False):
    """``ahead``: the runner that feeds a step its tokens on the device
    (the scheduler then runs one step ahead of the host)."""
    if PATHS[path].get("ahead"):
        return SlowFedRunner if slow else dp.FedRunner
    return SlowRunner if slow else dp.FakeRunner


def _path_config(path):
    kw = PATHS[path]
    if kw.get("spec"):
        return dp._spec_config(kw["depth"])
    return dp._config(kw["depth"], k=kw.get("k", 1))


def _path_requests(path, max_tokens=21):
    """What tests/test_trace_spans.py sends down each path: a repetitive
    prompt over the spec config's 8-token vocabulary, so that the ngram
    proposer has matches, else the three prompts of the fake runner."""
    if PATHS[path].get("spec"):
        return [dp._request([1, 2, 1, 2, 1, 2], max_tokens + 3)]
    return [dp._request(p, max_tokens) for p in dp.PROMPTS]


# ---------------------------------------------------------------------
# (1) token n reaches its consumer after dispatch n+1 and before result
#     n+1 is fetched
# ---------------------------------------------------------------------

@pytest.mark.parametrize("path", SYNC_PATHS)
def test_token_is_streamed_while_the_next_step_computes(path, monkeypatch):
    spans = _record_spans(monkeypatch)
    got, sched = _drive(_path_config(path), _path_requests(path),
                        runner_cls=_path_runner(path, slow=True))
    log = sched.runner.log
    dispatch = {n: t for kind, n, t in log if kind == "dispatch"}
    ready = {}
    for kind, n, t in log:
        if kind == "ready":
            ready.setdefault(n, t)          # the first array of the fetch
    assert len(dispatch) >= 5
    # each delivery of the first request, but those of the run's last
    # step (no dispatch follows them): the newest dispatch before it is
    # still computing
    hidden = 0
    for t_recv, out in got[0][1:]:
        n = max(k for k, t in dispatch.items() if t <= t_recv)
        if n == max(dispatch) and t_recv >= ready[n]:
            continue
        assert dispatch[n] + STEP_S <= ready[n], (path, n)
        # one step ahead, the device has a queue: the run's last step but
        # one is delivered a whole step after the newest dispatch, while
        # that step, queued behind it, computes
        still = ready[n] if path == "ahead" else dispatch[n] + STEP_S
        assert dispatch[n] <= t_recv < still, (path, n, t_recv - dispatch[n])
        hidden += 1
    assert hidden >= 3
    # and the turn that delivered them says so
    turns = [s for s in spans if s[0] == "sched.yield"]
    assert sum(s[1]["inflight"] for s in turns) >= 3
    for _, stats, t0, t1 in turns:
        if stats["inflight"]:
            n = max(k for k, t in dispatch.items() if t <= t0)
            assert t1 <= ready[n], (path, n)
    # sched.*.sync is time blocked on the device: it starts after the turn
    syncs = [s for s in spans if s[0] == "sched.decode.sync"]
    assert all(not (s[2] < t[2] < s[3]) for s in syncs for t in turns)


# ---------------------------------------------------------------------
# (2) a pass that dispatched nothing still yields; a cancelled request is
#     dropped within one pass
# ---------------------------------------------------------------------

def test_a_pass_that_dispatched_nothing_still_yields(monkeypatch):
    spans = _record_spans(monkeypatch)
    reaped = []

    def hooks(sched):
        # a remote prefill that completes with nothing to run locally:
        # the pass progresses (the reap) and dispatches nothing
        sched.pending_remote.append(object())

        def reap():
            reaped.append(sched.passes)
            sched.pending_remote.clear()
            return True

        sched._reap_remote = reap

    _, sched = _drive(dp._config(1, k=1), [], hooks=hooks, settle_s=0.05)
    assert reaped
    mine = [s for s in spans if s[1].get("step") == reaped[0]]
    assert [s[0] for s in mine] == ["sched.admit", "sched.yield"]
    assert mine[1][1]["inflight"] == 0
    total = sum(sched._yield_ctr.values.values())
    assert total > 0 and not sched._yield_inflight_ctr.values


@pytest.mark.parametrize("path", MIXED_PATHS)
def test_cancelled_request_is_dropped_within_one_pass(path):
    """A consumer that hangs up during the turn of pass p (its token of
    pass p-1 in hand) is reaped by the admit of pass p+1: one device
    step later at most, as before the move."""
    cancelled_at, finished_at = [], []

    def on_output(sched, er, out):
        if er is reqs[0] and not cancelled_at and len(out.token_ids) \
                and er.generated >= 4:
            er.ctx.stop_generating()
            cancelled_at.append(sched.passes)

    def hooks(sched):
        orig = sched._finish

        def finish(er, reason, **kw):
            if er is reqs[0]:
                finished_at.append((sched.passes, reason))
            return orig(er, reason, **kw)

        sched._finish = finish

    reqs = _path_requests(path, 40)
    got, _ = _drive(_path_config(path), reqs, on_output=on_output,
                    hooks=hooks, runner_cls=_path_runner(path))
    assert cancelled_at and finished_at
    (p_fin, reason), = finished_at
    assert reason == "cancelled"
    assert p_fin - cancelled_at[0] <= 1, (path, cancelled_at, finished_at)
    # the others ran to their end
    assert all(sum(len(o.token_ids) for _, o in g) == 40 for g in got[1:])


# ---------------------------------------------------------------------
# (3) a mixed batch streams what the parent commit streamed
# ---------------------------------------------------------------------

def _mixed_requests():
    """Five requests on four slots: greedy, sampled with a seed, one that
    wants log-probabilities (and alternatives), a stop string, a guided
    choice. The fifth waits for a slot, so admission joins a running
    batch."""
    stop = dp._stop_seq_request(dp.PROMPTS[1], 20, [[283, 12]])
    lp = dp._request([4, 8, 15], 12)
    lp.req.output_options = OutputOptions(logprobs=2)
    return [
        dp._request(dp.PROMPTS[0], 20),
        dp._request([3, 1, 4, 1, 5], 16, sampling=SamplingOptions(
            temperature=0.8, top_p=0.9, seed=1234)),
        lp,
        stop,
        dp._guided_request(dp.PROMPTS[2], 20, dp.CHOICES),
    ]


def _streams_of(got):
    out = []
    for deliveries in got:
        toks, lps, finish = [], [], None
        for _, o in deliveries:
            toks.extend(o.token_ids)
            for lp in o.logprobs or []:
                lps.append(round(float(lp.logprob), 4))
            finish = o.finish_reason or finish
        out.append([toks, lps, str(finish.value if hasattr(finish, "value")
                                   else finish)])
    return out


# what commit 58b906c (the parent of PR 32) streams for _mixed_requests(),
# on every one of MIXED_PATHS: written by running this file's
# _mixed_requests/_streams_of over that commit's scheduler
PARENT_STREAMS = [
    [[328, 288, 21, 213, 34, 330, 367, 127, 508, 116, 457, 297, 214, 158, 291,
      211, 176, 456, 381, 381],
     [], 'length'],
    [[88, 170, 245, 271, 466, 308, 239, 281, 76, 190, 489, 35, 454, 328, 483,
      45],
     [], 'length'],
    [[132, 452, 145, 57, 478, 366, 107, 355, 56, 24, 325, 397],
     [-0.6, -0.4, -0.5, -0.1, -0.2, -0.2, -0.2, -0.5, 0.0, -0.3, -0.3, -0.5],
      'length'],
    [[49, 370, 70, 31, 283, 12],
     [], 'stop'],
    [[7, 11, 13, 17, 100, 101, 102, 103, 104, 105, 106, 107],
     [], 'stop'],
]


@pytest.mark.parametrize("path", MIXED_PATHS)
def test_mixed_batch_streams_what_the_parent_streamed(path):
    got, sched = _drive(_path_config(path), _mixed_requests(),
                        runner_cls=_path_runner(path))
    assert _streams_of(got) == PARENT_STREAMS
    assert sched.allocator.used == 0
    if path == "ahead":
        # the guided row holds every pass it is in to the host's pace,
        # under its reason; once it has gone, the steps run ahead
        reasons = {dict(k)["reason"] for k in sched._sync_fallback_ctr.values}
        assert reasons == {"guided"}
        assert sum(sched._ahead_ctr.values.values()) > 0


# ---------------------------------------------------------------------
# (4) the two counters add up
# ---------------------------------------------------------------------

@pytest.mark.parametrize("path", sorted(PATHS))
def test_yield_counters_add_up(path):
    seen = []

    def on_output(sched, er, out):
        seen.append((sum(sched._yield_ctr.values.values()),
                     sum(sched._yield_inflight_ctr.values.values())))

    _, sched = _drive(_path_config(path), _path_requests(path),
                      on_output=on_output, runner_cls=_path_runner(path))
    assert len(seen) > 5
    for (t0, i0), (t1, i1) in zip(seen, seen[1:]):
        assert t1 >= t0 and i1 >= i0                # both monotone
    assert all(0.0 <= i <= t for t, i in seen)      # a part of the whole
    total, inflight = seen[-1]
    assert total > 0
    if path in SYNC_PATHS:
        # every decode pass takes its turn with a result pending (the
        # chain's do where a burst is still in flight when the pass ends;
        # the fake runner's are ready at once)
        assert inflight > 0.5 * total, (path, inflight, total)
    text = sched.registry.render()
    assert "dynamo_scheduler_yield_seconds_total" in text
    assert "dynamo_scheduler_yield_inflight_seconds_total" in text


# ---------------------------------------------------------------------
# (5) with a step in flight across passes, the turn is still one a pass
# ---------------------------------------------------------------------

def test_the_turn_is_taken_once_a_pass_with_a_step_in_flight(monkeypatch):
    """ISSUE 57: the decode step is left in flight at the end of its
    pass and read by the next, behind that pass's dispatch. Every pass
    that progressed still takes exactly one turn; a pass with a step in
    flight takes it with ``inflight=1``, between its dispatch and the
    wait for the step before; and a token is delivered while the device
    still has a step queued."""
    spans = _record_spans(monkeypatch)
    got, sched = _drive(_path_config("ahead"), _path_requests("ahead"),
                        runner_cls=SlowFedRunner)
    by_pass = {}
    for name, stats, t0, t1 in spans:
        if name.startswith("sched."):       # (not sync.fetch's parts)
            by_pass.setdefault(stats["step"], []).append(
                (name, stats, t0, t1))
    ahead = 0
    for n, mine in by_pass.items():
        names = [s[0] for s in mine]
        if "sched.wait" in names:
            assert "sched.yield" not in names, names
            continue
        assert names.count("sched.yield") == 1, (n, names)
        i = names.index("sched.yield")
        dispatch = [s for s in mine if s[0] == "sched.decode.dispatch"]
        if dispatch and dispatch[-1][1]["ahead"]:
            ahead += 1
            assert mine[i][1]["inflight"] == 1
            assert names[i - 2:] == [
                "sched.decode.dispatch", "sched.decode.request",
                "sched.yield", "sched.decode.sync", "sched.decode.emit"]
    assert ahead == sum(sched._ahead_ctr.values.values()) >= 15
    # the device never waited for the host between two such steps: each
    # was dispatched before the one before it was ready
    log = sched.runner.log
    dispatch = {n: t for kind, n, t in log if kind == "dispatch"}
    ready = {}
    for kind, n, t in log:
        if kind == "ready":
            ready.setdefault(n, t)
    queued = sum(dispatch[n] < ready[n - 1] for n in dispatch if n - 1 in ready)
    assert queued >= ahead - 1
    assert all(sum(len(o.token_ids) for _, o in g) == 21 for g in got)
