"""The persistent decode loop (EngineConfig.decode_pipeline_depth=2)
edge cases, driven by a deterministic fake runner.

The fake models exactly the carry semantics the chain relies on —
``next = f(prev_token, position)`` — so the synchronous and chained
schedulers must produce byte-identical streams through every edge:
rows frozen on device mid-burst, preemption forcing a barrier, and the
guided/spec/``n>1``/refused-batch fallbacks. The real-model differential
lives in tests/test_multi_step.py; this file isolates the SCHEDULER's
chain logic from the numerics.
"""

import asyncio
import uuid

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.engine.step_inputs import FED
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext


class FakeRunner:
    """Deterministic stand-in for ModelRunner.

    Token rule: the token after ``prev`` (sitting at ``pos``) is the
    bias-row argmax of ``-(|id - target|)`` with ``target = (prev * 7 +
    pos * 13 + 1) % vocab`` — a pure function of the carry and the
    slot's installed mask, so any scheduling (per-token, fused burst,
    chained, guided via bias rows OR via the device
    transition table, preempt + re-prefill resume) must reproduce the
    same stream. With a zero bias row the argmax IS ``target`` (the
    original rule); a guided mask steers it to the nearest allowed id
    identically on the host-mask path and the device-table path.
    """

    spec_burst_ready = True
    # ``step(prev_tokens=)`` is honoured, and ``FedRunner`` says so to the
    # scheduler (``ModelRunner.feeds_tokens``): the paths of this file
    # that predate the step ahead run as they ran
    feeds_tokens = False

    def __init__(self, config: EngineConfig):
        self.config = config
        self.v = config.model.vocab_size
        self.step_calls = 0
        self.burst_calls = 0
        self.chained_calls = 0
        self.spec_calls = 0
        self.bias = np.zeros((config.max_batch_size, self.v), np.float32)
        # test hook: force a stop-string suffix-hash candidate (the
        # device false-positive injection) — fn(slot, gen) -> bool
        self.force_stop_candidate = None

    def _advance(self, prev, pos):
        return (prev * 7 + pos * 13 + 1) % self.v

    def _tok(self, prev, pos, slot=None, extra_mask=None):
        """One sampled token: bias-aware argmax (mirrors sample())."""
        target = int((int(prev) * 7 + int(pos) * 13 + 1) % self.v)
        row = self.bias[slot] if slot is not None else None
        if (row is None or not row.any()) and extra_mask is None:
            return target
        logits = -np.abs(
            np.arange(self.v) - target
        ).astype(np.float64)
        if row is not None:
            logits = logits + row
        if extra_mask is not None:
            logits = logits + extra_mask
        return int(np.argmax(logits))

    # sampling-state writes mirror only the bias row (guided masks +
    # logit_bias); counts/seen are penalty bookkeeping the fake's
    # deterministic rule never consults
    def set_sample_row(self, slot, prompt_ids, generated_ids=(),
                       logit_bias=None, guided_mask=None):
        row = (
            np.asarray(guided_mask, np.float32).copy()
            if guided_mask is not None
            else np.zeros(self.v, np.float32)
        )
        for tid, b in (logit_bias or {}).items():
            tid = int(tid)
            if 0 <= tid < self.v:
                row[tid] += float(b)
        self.bias[slot] = row

    GUIDED_STATE_BUCKETS = (1, 64, 256, 1024)

    def guided_state_bucket(self, n_states):
        for s in self.GUIDED_STATE_BUCKETS:
            if n_states <= s:
                return s
        return self.GUIDED_STATE_BUCKETS[-1]

    def set_bias_row(self, slot, row):
        self.bias[slot] = np.asarray(row, np.float32).copy()

    def edit_bias_entries(self, slot, ids, vals):
        for t, val in zip(ids, vals):
            self.bias[slot][int(t)] = float(val)
        return True

    def step(self, tokens, positions, btab, slot_map, ctx_lens, last_idx,
             *args, sample_slots=None, want_greedy=False, prev_tokens=None,
             **kw):
        self.step_calls += 1
        tokens = np.asarray(tokens)
        if prev_tokens is not None:
            # the device reads its own array, whenever the host does: a
            # test's wrapper around it is looked through (``raw``)
            fed = np.asarray(getattr(prev_tokens, "raw", prev_tokens))
            tokens = np.where(tokens == FED, fed[:, None], tokens)
        assert (tokens >= 0).all(), "a fed row and no step to feed it"
        b = tokens.shape[0]
        rows = np.arange(b)
        last_idx = np.asarray(last_idx)
        prev = tokens[rows, last_idx]
        pos = np.asarray(positions)[rows, last_idx]
        slots = (np.asarray(sample_slots) if sample_slots is not None
                 else rows)
        nt = np.asarray([
            self._tok(prev[i], pos[i], slot=int(slots[i]))
            for i in range(b)
        ], np.int32)
        lps = (-(nt % 7) / 10.0).astype(np.float32)
        tv = np.zeros((b, 8), np.float32)
        ti = np.zeros((b, 8), np.int32)
        plps = np.zeros(tokens.shape, np.float32)
        # spec verify: per-position raw argmax (no bias — the real
        # verify reads raw logits), position-wise f(token_j, pos_j)
        if want_greedy:
            greedy = self._advance(
                tokens.astype(np.int64), np.asarray(positions)
            ).astype(np.int32)
        else:
            greedy = np.zeros(tokens.shape, np.int32)
        return nt, lps, tv, ti, plps, greedy

    def decode_burst(self, tokens0, positions0, btab, *args,
                     commit=None, want_top=False, **kw):
        self.burst_calls += 1
        K = max(1, self.config.multi_step_decode)
        prev = np.asarray(tokens0).astype(np.int64).copy()
        pos = np.asarray(positions0).astype(np.int64).copy()
        b = prev.shape[0]
        toks = np.zeros((K, b), np.int32)
        lps = np.zeros((K, b), np.float32)
        for s in range(K):
            prev = np.asarray([
                self._tok(prev[i], pos[i], slot=i) for i in range(b)
            ], np.int64)
            toks[s] = prev
            lps[s] = -(toks[s] % 7) / 10.0
            pos += 1
        tv = np.zeros((K, b, 8), np.float32)
        ti = np.zeros((K, b, 8), np.int32)
        return toks, lps, tv, ti

    # -- chained-path mirrors -------------------------------------------

    def _stop_candidate(self, ring_row, gen, min_new, hashes, lens, slot):
        from dynamo_tpu.engine.sampling import stop_seq_hash

        if self.force_stop_candidate is not None and \
                self.force_stop_candidate(slot, int(gen)):
            return True
        for h, ell in zip(hashes, lens):
            ell = int(ell)
            if ell > 0 and gen >= ell and gen >= min_new:
                if stop_seq_hash(ring_row[-ell:]) == int(h):
                    return True
        return False

    def decode_burst_chained(self, tokens0, positions0, gen0, done0, btab,
                             *args, commit=None, stop_ids=None,
                             min_new=None, max_new=None, ring0=None,
                             gstate0=None, stop_hash=None, stop_hlen=None,
                             gtable=None, want_top=False, **kw):
        """Host mirror of the device-finish burst: same token rule, plus
        the freeze semantics — finished rows stop advancing and emit -1
        pads; the carry (tokens/pos/gen/done/ring/gstate) feeds the next
        call. Guided rows mask through the transition table, stop-string
        rows through the rolling suffix hash, exactly like the device
        program."""
        self.chained_calls += 1
        K = max(1, self.config.multi_step_decode)
        prev = np.asarray(tokens0).astype(np.int64).copy()
        pos = np.asarray(positions0).astype(np.int64).copy()
        gen = np.asarray(gen0).astype(np.int64).copy()
        done = np.asarray(done0).astype(bool).copy()
        commit = np.asarray(commit).astype(bool)
        b = prev.shape[0]
        from dynamo_tpu.engine.sampling import SUFFIX_RING_W

        ring = (np.asarray(ring0, np.int64).copy() if ring0 is not None
                else np.full((b, SUFFIX_RING_W), -1, np.int64))
        gstate = (np.asarray(gstate0, np.int64).copy()
                  if gstate0 is not None else np.full(b, -1, np.int64))
        gtab = np.asarray(gtable) if gtable is not None else None
        hashes = (np.asarray(stop_hash) if stop_hash is not None
                  else np.zeros((b, 4), np.uint32))
        hlens = (np.asarray(stop_hlen) if stop_hlen is not None
                 else np.zeros((b, 4), np.int32))
        toks = np.full((K, b), -1, np.int32)
        lps = np.zeros((K, b), np.float32)
        max_len = self.config.max_model_len
        for s in range(K):
            live = commit & ~done
            nt = np.zeros(b, np.int64)
            for i in range(b):
                extra = None
                if gstate[i] >= 0 and gtab is not None:
                    extra = np.where(gtab[int(gstate[i])] < 0, -1e9, 0.0)
                nt[i] = self._tok(prev[i], pos[i], slot=i,
                                  extra_mask=extra)
            gen = gen + live.astype(np.int64)
            ring_n = np.concatenate([ring[:, 1:], nt[:, None]], axis=1)
            ring = np.where(live[:, None], ring_n, ring)
            hit = (nt[:, None] == np.asarray(stop_ids)).any(axis=1)
            hard = (
                ((gen >= min_new) & hit)
                | (gen >= max_new) | (pos + 2 >= max_len)
            )
            cand = np.asarray([
                live[i] and self._stop_candidate(
                    ring[i], gen[i], int(np.asarray(min_new)[i]),
                    hashes[i], hlens[i], i)
                for i in range(b)
            ], bool)
            gdone = np.zeros(b, bool)
            gnext = np.full(b, -1, np.int64)
            for i in range(b):
                if gstate[i] >= 0 and gtab is not None:
                    gnext[i] = int(gtab[int(gstate[i]), int(nt[i])])
                    gdone[i] = (not hard[i]) and gnext[i] <= 0
            newly = live & (hard | cand | gdone)
            toks[s] = np.where(live, nt, -1)
            lps[s] = np.where(live, -(nt % 7) / 10.0, 0.0)
            adv = live & ~newly
            prev = np.where(adv, nt, prev)
            pos = np.where(adv, pos + 1, pos)
            gstate = np.where(adv & (gstate >= 0), gnext, gstate)
            done = done | newly
        tv = np.zeros((K, b, 8), np.float32)
        ti = np.zeros((K, b, 8), np.int32)
        return toks, lps, tv, ti, (
            prev.astype(np.int32), pos.astype(np.int32),
            gen.astype(np.int32), done, ring.astype(np.int32),
            gstate.astype(np.int32),
        )

    def _ngram_from_ring(self, ring, m, k):
        w = len(ring)
        tail = ring[-m:]
        best = -1
        for s in range(w - m):
            win = ring[s:s + m]
            if (win == tail).all() and (win >= 0).all() \
                    and s + m + k <= w:
                best = s
        if best < 0:
            return [-1] * k
        return [int(t) if t >= 0 else -1
                for t in ring[best + m:best + m + k]]

    def decode_burst_spec(self, tokens0, positions0, gen0, done0, ring0,
                          gstate0, btab, *, commit, stop_ids, min_new,
                          max_new, stop_hash, stop_hlen, proposals=None):
        """Host mirror of the chained propose-verify round: ngram
        proposals from the ring, one-forward greedy verify, accepted
        prefix + correction committed with freeze semantics."""
        self.spec_calls += 1
        P = self.config.spec_ngram_tokens
        S = P + 1
        prev = np.asarray(tokens0).astype(np.int64).copy()
        pos = np.asarray(positions0).astype(np.int64).copy()
        gen = np.asarray(gen0).astype(np.int64).copy()
        done = np.asarray(done0).astype(bool).copy()
        ring = np.asarray(ring0, np.int64).copy()
        commit = np.asarray(commit).astype(bool)
        hashes = np.asarray(stop_hash)
        hlens = np.asarray(stop_hlen)
        b = prev.shape[0]
        max_len = self.config.max_model_len
        toks = np.full((S, b), -1, np.int32)
        nprop = np.zeros(b, np.int32)
        nacc = np.zeros(b, np.int32)
        for i in range(b):
            if not commit[i] or done[i]:
                continue
            props = (
                [int(t) for t in np.asarray(proposals)[i]]
                if proposals is not None
                else self._ngram_from_ring(
                    ring[i], self.config.spec_ngram_match, P)
            )
            nprop[i] = sum(1 for t in props if t >= 0)
            row = [int(prev[i])] + [t if t >= 0 else 0 for t in props]
            greedy = [
                int(self._advance(np.int64(row[j]), pos[i] + j))
                for j in range(S)
            ]
            acc = 0
            while acc < P and props[acc] >= 0 \
                    and greedy[acc] == props[acc]:
                acc += 1
            nacc[i] = acc  # raw verified proposals (sync-path semantics)
            for j in range(S):
                if done[i] or j > acc:
                    break
                t = greedy[j]
                gen[i] += 1
                ring[i] = np.concatenate([ring[i][1:], [t]])
                hit = t in set(int(x) for x in np.asarray(stop_ids)[i])
                hard = (
                    (gen[i] >= np.asarray(min_new)[i] and hit)
                    or gen[i] >= np.asarray(max_new)[i]
                    or pos[i] + 2 >= max_len
                )
                cand = self._stop_candidate(
                    ring[i], gen[i], int(np.asarray(min_new)[i]),
                    hashes[i], hlens[i], i)
                toks[j, i] = t
                if hard or cand:
                    done[i] = True
                else:
                    prev[i] = t
                    pos[i] += 1
        return toks, nprop, nacc, (
            prev.astype(np.int32), pos.astype(np.int32),
            gen.astype(np.int32), done, ring.astype(np.int32),
            np.asarray(gstate0, np.int32).copy(),
        )


def _config(depth, k=4, **kw):
    kw.setdefault("num_kv_blocks", 64)
    kw.setdefault("max_model_len", 128)
    return EngineConfig(
        model=ModelConfig(vocab_size=512, hidden_size=32,
                          intermediate_size=64, num_layers=1, num_heads=2,
                          num_kv_heads=1),
        max_batch_size=4, kv_block_size=8,
        dtype="float32", multi_step_decode=k, decode_pipeline_depth=depth,
        enable_prefix_caching=False, **kw,
    )


def _request(prompt, max_tokens, eos=None, sampling=None):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, ignore_eos=eos is None,
        ),
        sampling_options=sampling or SamplingOptions(temperature=0.0),
        eos_token_ids=list(eos or []),
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


class FedRunner(FakeRunner):
    """The fake runner behind a scheduler that runs its decode step one
    step ahead of the host."""

    feeds_tokens = True


def _run(config, requests, hooks=None, runner_cls=FakeRunner):
    """Drive the scheduler over a FakeRunner; returns (streams, sched)."""

    async def go():
        runner = runner_cls(config)
        sched = Scheduler(runner, config)
        if hooks:
            hooks(sched)
        sched.start()

        async def collect(er):
            toks, finish = [], None
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks, finish
                toks.extend(out.token_ids)
                if out.finish_reason is not None:
                    finish = out.finish_reason
        try:
            for er in requests:
                sched.add_request(er)
            return await asyncio.gather(*(collect(er) for er in requests))
        finally:
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


PROMPTS = ([1, 17, 43], [2, 5], [9, 9, 9, 9, 9])


def _streams(depth, max_tokens=21, eos=None, k=4, sched_out=None, **cfg_kw):
    config = _config(depth, k=k, **cfg_kw)
    reqs = [_request(p, max_tokens, eos=eos) for p in PROMPTS]
    captured = {}

    def grab(s):
        captured["sched"] = s

    out = _run(config, reqs, hooks=grab)
    if sched_out is not None:
        sched_out.update(captured)
    return out


def test_differential_greedy_streams_identical():
    """Depth-2 greedy decode must emit byte-identical streams vs sync —
    token ids, logprob carriers, and finish reasons."""
    box = {}
    want = _streams(1)
    got = _streams(2, sched_out=box)
    assert got == want
    assert box["sched"].pipeline_bursts > 0, "chain never engaged"
    assert not box["sched"]._chain and not box["sched"]._chain_members


def test_eos_on_last_step_of_burst_stream_identical():
    """EOS lands on the LAST step of a burst while later bursts are
    already queued behind it: the row freezes there, the stream (and
    finish reason) is identical to the sync path, and every block
    reserved ahead of it returns to the allocator."""
    # find the greedy continuation, then make its 6th token the eos: with
    # K=2 it is the last step of burst 3
    plain = _streams(1, max_tokens=24, k=2)
    eos = [plain[0][0][5]]
    want = _streams(1, max_tokens=24, eos=eos, k=2)
    assert want[0][1] == "eos" and len(want[0][0]) <= 6
    box = {}
    got = _streams(2, max_tokens=24, eos=eos, k=2, sched_out=box)
    assert got == want
    sched = box["sched"]
    assert sched.pipeline_bursts > 0
    assert sched.allocator.used == 0  # headroom + rollback leak nothing


def test_single_step_pipeline_identical():
    assert _streams(2, k=1) == _streams(1, k=1)


def test_preemption_closes_single_step_chain_and_stream_continues():
    """KV OOM under the K=1 chain must close it at a barrier before
    preemption — and the resumed streams still total max_tokens with the
    identical prefix, matching the unconstrained run."""
    want = _streams(1, max_tokens=24, k=1, num_kv_blocks=64)

    preempts = []

    def hook(sched):
        orig = sched._preempt

        def spy(er):
            # the chain must be fully reconciled when preemption runs
            assert not sched._chain, \
                "preempted with a burst still in flight"
            preempts.append(er.request_id)
            orig(er)

        sched._preempt = spy

    # (3 prompts + 24 new tokens) doesn't fit in 10 blocks even at the
    # sync path's one-position reservation, so the chain's OOM first
    # degrades to sync (barrier) and the sync path then preempts
    config = _config(2, k=1, num_kv_blocks=10)
    reqs = [_request(p, 24) for p in PROMPTS]
    box = {}

    def hooks(s):
        box["sched"] = s
        hook(s)

    got = _run(config, reqs, hooks=hooks)
    assert preempts, "test is vacuous: no preemption happened"
    assert box["sched"].pipeline_bursts > 0, "chain never engaged"
    assert got == want


def _pipeline_stays_cold(config, reqs):
    box = {}

    def grab(s):
        box["sched"] = s

    out = _run(config, reqs, hooks=grab)
    sched = box["sched"]
    assert sched.pipeline_bursts == 0, "chained dispatch on a sync-only shape"
    assert not sched._chain
    return out


def test_guided_requests_force_sync_path_when_table_disabled():
    """With guided_device_table off, guided rows keep the per-token host
    mask path (no chained burst) and the fallback counter names the
    reason."""
    config = _config(2, guided_device_table=False)
    sampling = SamplingOptions(
        temperature=0.0,
        guided_choice_token_ids=[[3, 4, 5], [3, 7]],
    )
    reqs = [_request([1, 2], 8, sampling=sampling)]
    out = _pipeline_stays_cold(config, reqs)
    assert out[0][1] is not None  # the request still completes


def _spec_config(depth, vocab=8, **kw):
    """Tiny-vocab spec config: an 8-token vocab makes the deterministic
    stream repetitive enough for ngram lookups to actually hit, so the
    acceptance path (not just the no-proposal round) is exercised."""
    kw.setdefault("num_kv_blocks", 64)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("spec_ngram_tokens", 2)
    kw.setdefault("spec_ngram_match", 2)
    return EngineConfig(
        model=ModelConfig(vocab_size=vocab, hidden_size=32,
                          intermediate_size=64, num_layers=1, num_heads=2,
                          num_kv_heads=1),
        max_batch_size=4, kv_block_size=8,
        dtype="float32", multi_step_decode=4, decode_pipeline_depth=depth,
        enable_prefix_caching=False, **kw,
    )


def test_spec_decode_chains_and_streams_identical():
    """Ngram speculation now runs INSIDE the chain (propose-verify
    rounds off the device carry): streams must match the sync spec path
    byte-for-byte (which itself matches plain greedy decode — proposals
    affect acceptance, never content), the spec program must actually
    run, chain length must exceed 1 (no per-round host barrier), and
    the round's acceptance accounting must ride back."""
    reqs = lambda: [_request([1, 2, 1, 2, 1, 2], 24)]  # noqa: E731
    want = _run(_spec_config(1), reqs())
    plain = _run(_spec_config(1, spec_ngram_tokens=0), reqs())
    assert want == plain  # greedy spec never changes content
    box = {}

    def grab(s):
        box["sched"] = s

    got = _run(_spec_config(2), reqs(), hooks=grab)
    assert got == want
    sched = box["sched"]
    assert sched.runner.spec_calls > 1, "spec chain never engaged"
    assert sched._last_chain_len > 1, "host barrier still per round"
    assert sched.allocator.used == 0
    # acceptance accounting rode back from the device: proposals were
    # made and at least one round accepted speculative tokens
    assert sum(sched._spec_accept_hist.totals.values()) > 0
    assert sched.spec_proposed > 0
    assert sched.spec_accepted > 0


def test_n_gt_1_forces_sync_path():
    # serving rejects n>1 today; the scheduler still guards in case a
    # future fan-out path feeds multi-choice requests straight in
    config = _config(2)
    reqs = [_request([1, 2, 3], 8,
                     sampling=SamplingOptions(temperature=0.0, n=2))]
    _pipeline_stays_cold(config, reqs)


def test_prefill_arrival_drains_then_resumes_pipeline():
    """A new admission mid-decode forces the sync path (runner no longer
    idle) and the pipeline re-engages afterwards — outputs unchanged."""
    config = _config(2)

    async def go():
        runner = FakeRunner(config)
        sched = Scheduler(runner, config)
        sched.start()

        async def collect(er):
            toks = []
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks
                toks.extend(out.token_ids)

        first = _request(PROMPTS[0], 30)
        sched.add_request(first)
        t1 = asyncio.ensure_future(collect(first))
        await asyncio.sleep(0.05)  # let the pipeline engage
        engaged = sched.pipeline_bursts
        late = _request(PROMPTS[1], 30)
        sched.add_request(late)
        t2 = asyncio.ensure_future(collect(late))
        out = [await t1, await t2]
        bursts = sched.pipeline_bursts
        await sched.stop()
        return engaged, bursts, out

    loop = asyncio.new_event_loop()
    try:
        engaged, bursts, got = loop.run_until_complete(go())
    finally:
        loop.close()
    assert engaged > 0, "pipeline never engaged before the late arrival"
    assert bursts > engaged, "pipeline never re-engaged after the drain"
    want = _streams(1, max_tokens=30)
    assert got[0] == want[0][0]
    assert got[1] == want[1][0]


# --------------------------------------------------------------------------
# device-resident finish detection — the persistent decode loop:
# chained bursts, frozen rows, async row drain
# --------------------------------------------------------------------------


def test_device_finish_differential_streams_identical():
    """Streams must be byte-identical chained (depth 2) vs sync (depth
    1) — token ids, logprob carriers, finish reasons — and the chained
    path must actually engage: bursts dispatched between host barriers
    > 1 (the host barrier is no longer per burst)."""
    want = _streams(1)
    sync_box, on_box = {}, {}
    assert _streams(1, sched_out=sync_box) == want
    on = _streams(2, sched_out=on_box)
    assert on == want
    assert sync_box["sched"].runner.chained_calls == 0
    sched = on_box["sched"]
    assert sched.runner.chained_calls > 1
    assert sched._last_chain_len > 1, "host barrier still per burst"
    assert not sched._chain and not sched._chain_members
    # every finish was detected on device (all rows are device-checkable)
    assert sum(sched._device_finished_ctr.values.values()) == len(PROMPTS)


def test_device_finish_eos_mid_burst_freezes_row():
    """EOS landing mid-burst: the row freezes ON DEVICE at exactly the
    stop token (no over-decode at all — nothing emits after it), the stream matches the sync path byte-for-byte,
    and the reserved headroom blocks all roll back."""
    plain = _streams(1, max_tokens=24)
    eos = [plain[0][0][5]]  # lands mid-burst at K=4
    want = _streams(1, max_tokens=24, eos=eos)
    assert want[0][1] == "eos" and len(want[0][0]) <= 6
    box = {}
    got = _streams(2, max_tokens=24, eos=eos, sched_out=box)
    assert got == want
    sched = box["sched"]
    assert sched.runner.chained_calls > 0
    assert sum(sched._device_finished_ctr.values.values()) >= 1
    assert sched.allocator.used == 0  # headroom + rollback leak nothing


def test_device_finish_max_tokens_at_burst_boundary():
    """max_tokens an exact multiple of K: the LENGTH finish lands on the
    last step of a burst — the device mask must freeze the row there
    and the stream must match the sync path."""
    for mt in (8, 12):  # K=4 boundaries
        want = _streams(1, max_tokens=mt)
        box = {}
        got = _streams(2, max_tokens=mt, sched_out=box)
        assert got == want
        assert all(len(toks) == mt and f == "length" for toks, f in got)
        assert box["sched"].runner.chained_calls > 0
        assert box["sched"].allocator.used == 0


def _refused_batch(case):
    """A batch with a row the chain cannot check, and that row's
    ``EngineRequest.classify_finish`` reason."""
    from dynamo_tpu.engine.sampling import STOP_SEQ_MAX_LEN

    eos17 = list(range(400, 417))  # one id past STOP_ID_WIDTH
    if case == "stop_ids_overflow":
        return case, [_request(p, 12, eos=eos17) for p in PROMPTS]
    if case == "stop_seqs_overflow":
        long_seq = list(range(300, 301 + STOP_SEQ_MAX_LEN))
        return case, [_stop_seq_request(p, 12, [long_seq]) for p in PROMPTS]
    if case == "stop_seqs_unavailable":
        # stop STRINGS with no canonical token sequences shipped: only
        # the backend's host-side post-check can see them
        return case, [_stop_seq_request(p, 12, [], stop=["never-matches"])
                      for p in PROMPTS]
    assert case == "mixed"
    return "stop_ids_overflow", [
        _request(PROMPTS[0], 12), _request(PROMPTS[1], 12, eos=eos17),
    ]


@pytest.mark.parametrize("case", [
    "stop_ids_overflow", "stop_seqs_overflow", "stop_seqs_unavailable",
    "mixed",
])
def test_depth2_refused_batch_decodes_sync(case):
    """Depth 2 means the chain and nothing else: a batch with a row the
    chain cannot check decodes on the synchronous path — never two
    bursts outstanding — with the depth-1 stream and the refusal counted
    under the row's reason."""
    reason, rs = _refused_batch(case)
    assert reason in {er.chain_fallback for er in rs}
    want = _run(_config(1), _refused_batch(case)[1])
    box = {"outstanding": 0, "peak": 0}

    def hooks(sched):
        box["sched"] = sched
        burst, synced = sched.runner.decode_burst, sched._observe_host_sync

        def dispatch(*a, **kw):
            box["outstanding"] += 1
            box["peak"] = max(box["peak"], box["outstanding"])
            return burst(*a, **kw)

        def sync(dt):
            box["outstanding"] = 0
            synced(dt)

        sched.runner.decode_burst = dispatch
        sched._observe_host_sync = sync

    got = _run(_config(2), rs, hooks=hooks)
    assert got == want
    sched = box["sched"]
    assert reason in _fallback_reasons(sched)
    assert sched.runner.chained_calls == 0, "chained an uncheckable row"
    assert sched.pipeline_bursts == 0
    assert box["peak"] == 1, "a burst was dispatched ahead of a host sync"


def test_preemption_kv_oom_drains_chain_before_membership_changes():
    """KV OOM mid-chain must run the chain barrier (every queued burst
    reconciled, membership compacted) before preemption touches any
    row — and the resumed streams still match the unconstrained run."""
    want = _streams(1, max_tokens=24, num_kv_blocks=64)

    preempts = []

    def hook(sched):
        orig = sched._preempt

        def spy(er):
            assert not sched._chain, "preempted with chained bursts in flight"
            assert not sched._chain_members, \
                "preempted before the chain membership barrier"
            preempts.append(er.request_id)
            orig(er)

        sched._preempt = spy

    config = _config(2, num_kv_blocks=10)
    reqs = [_request(p, 24) for p in PROMPTS]
    box = {}

    def hooks(s):
        box["sched"] = s
        hook(s)

    got = _run(config, reqs, hooks=hooks)
    assert preempts, "test is vacuous: no preemption happened"
    assert box["sched"].runner.chained_calls > 0, "chain never engaged"
    assert got == want


def test_device_finish_near_horizon_rows_stay_chained():
    """Rows near max_model_len do NOT fall back to sync: the device's
    LENGTH check (pos + 2 >=
    max_model_len — the in-scan mirror of _check_finish's context_len +
    1 bound) freezes them at exactly the horizon, headroom reservation
    caps at max_model_len - 1, and the streams still match the sync
    path byte-for-byte."""
    want = _streams(1, max_tokens=200, max_model_len=32)
    box = {}
    got = _streams(2, max_tokens=200, max_model_len=32, sched_out=box)
    assert got == want
    assert all(f == "length" for _, f in got)
    sched = box["sched"]
    assert sched.runner.chained_calls > 0, \
        "near-horizon rows forced sync"
    # every LENGTH finish at the horizon was detected on device
    assert sum(sched._device_finished_ctr.values.values()) == len(PROMPTS)
    assert sched.allocator.used == 0
    assert not sched._chain and not sched._chain_members


def test_late_drain_rolls_back_reserved_headroom():
    """The chain reserves block headroom against its own dispatch count,
    so a row finishing deep into a chain holds blocks covering positions
    it froze before reaching — the drain must roll that tail back into the allocator (rollback_tail observed with a
    shrinking keep) and leak nothing."""
    rollbacks = []

    def hook(sched):
        orig = sched.allocator.rollback_tail

        def spy(block_ids, keep):
            rollbacks.append((len(block_ids), keep))
            return orig(block_ids, keep)

        sched.allocator.rollback_tail = spy

    plain = _streams(1, max_tokens=24)
    eos = [plain[2][0][2]]  # row 2 stops early, deep headroom reserved
    config = _config(2, num_kv_blocks=64)
    reqs = [_request(p, 24, eos=eos) for p in PROMPTS]
    box = {}

    def hooks(s):
        box["sched"] = s
        hook(s)

    _run(config, reqs, hooks=hooks)
    sched = box["sched"]
    assert sched.runner.chained_calls > 0
    assert any(total > keep for total, keep in rollbacks), \
        "no over-reserved tail was ever rolled back"
    assert sched.allocator.used == 0


# --------------------------------------------------------------------------
# device-time attribution on the chained path (telemetry/device_time.py)
# --------------------------------------------------------------------------


def _run_chained(with_tracker):
    """Drive the persistent loop over a FakeRunner, spying on the host
    syncs (_observe_host_sync — every executor-side device sync passes
    through it). Returns (streams, sync_count, tracker_or_None)."""
    from dynamo_tpu.telemetry.device_time import DeviceTimeTracker

    config = _config(2)
    reqs = [_request(p, 21) for p in PROMPTS]
    syncs = []
    box = {}

    async def go():
        runner = FakeRunner(config)
        tracker = None
        if with_tracker:
            tracker = DeviceTimeTracker(
                param_bytes=1e9, kv_bytes_per_token=1e3, hbm_gbps=100.0,
            )
            runner.device_time = tracker
        sched = Scheduler(runner, config)
        box["sched"] = sched
        orig = sched._observe_host_sync

        def spy(dt):
            syncs.append(dt)
            orig(dt)

        sched._observe_host_sync = spy
        sched.start()

        async def collect(er):
            toks, finish = [], None
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks, finish
                toks.extend(out.token_ids)
                if out.finish_reason is not None:
                    finish = out.finish_reason
        try:
            for er in reqs:
                sched.add_request(er)
            return await asyncio.gather(*(collect(er) for er in reqs)), tracker
        finally:
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        streams, tracker = loop.run_until_complete(go())
    finally:
        loop.close()
    return streams, len(syncs), tracker


def test_device_time_chained_adds_no_host_syncs_and_attributes_bursts():
    """The device-time tracker measures off the async drain's EXISTING
    reconciliation seams: with it attached, the chained path performs
    exactly the same number of host syncs, the streams are byte-
    identical, and every chained burst lands as a decode_burst_df
    observation with nonzero busy time + a live roofline fraction."""
    base_streams, base_syncs, _ = _run_chained(with_tracker=False)
    streams, syncs, tracker = _run_chained(with_tracker=True)
    assert streams == base_streams
    assert syncs == base_syncs, "device-time tracking added a host sync"
    assert tracker is not None and tracker.observations > 0
    assert tracker.busy_s.get("decode", 0.0) > 0.0
    assert box_chained_calls(tracker) > 0
    text = tracker.registry.render()
    assert "dynamo_engine_device_time_seconds" in text
    assert "dynamo_engine_roofline_fraction" in text
    ((_, frac),) = tracker._roofline()
    assert frac > 0.0
    # the chained program is what got attributed (alongside the prefill)
    programs = {dict(k).get("program") for k in tracker._time_hist.counts}
    assert "decode_burst_df" in programs
    phases = {dict(k).get("phase") for k in tracker._time_hist.counts}
    assert phases <= {"decode", "prefill"}


def box_chained_calls(tracker):
    # decode tokens accumulated via the burst token accounting
    return tracker.decode_tokens


# --------------------------------------------------------------------------
# unrestricted persistent decode (ISSUE 13): guided / stop-string / n>1 /
# spec traffic inside the chain, with the sync-fallback ladder counted
# --------------------------------------------------------------------------


def _fallback_reasons(sched):
    return {dict(k).get("reason") for k in
            sched._sync_fallback_ctr.values}


def _guided_request(prompt, max_tokens, choice_ids):
    return _request(prompt, max_tokens, sampling=SamplingOptions(
        temperature=0.0, guided_choice_token_ids=choice_ids,
    ))


# two long choices sharing a 4-token prefix so the chain runs >1 burst
CHOICES = [
    [7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47],
    [7, 11, 13, 17, 100, 101, 102, 103, 104, 105, 106, 107],
]


def _precompile_guided_tables(sched):
    """Deterministic table availability for chain-engagement asserts:
    compile synchronously (the production path compiles in an executor
    and serves sync passes until the table lands)."""
    orig_reason = sched._guided_chain_reason

    def eager(er):
        key = sched._guided_table_key(er)
        if key not in sched._guided_tables:
            sched._guided_tables[key] = sched._compile_guided_table(er)
        return orig_reason(er)

    sched._guided_chain_reason = eager


def test_guided_choice_chains_byte_identical():
    """guided_choice rows now chain through the device transition
    table: streams byte-identical to the host-mask sync path, chain
    length > 1, the guided finish detected on device, zero leaked
    blocks."""
    config_sync = _config(1, k=2)
    want = _run(config_sync, [_guided_request([1, 2], 16, CHOICES)])
    assert want[0][1] == "stop" and list(want[0][0]) in CHOICES
    box = {}

    def hooks(s):
        box["sched"] = s
        _precompile_guided_tables(s)

    got = _run(_config(2, k=2), [_guided_request([1, 2], 16, CHOICES)],
               hooks=hooks)
    assert got == want
    sched = box["sched"]
    assert sched.runner.chained_calls > 1, "guided chain never engaged"
    assert sched._last_chain_len > 1
    assert sum(sched._device_finished_ctr.values.values()) == 1
    assert sched.allocator.used == 0


def test_guided_json_in_bound_chains_byte_identical():
    """An in-bound guided_json grammar (tiny enum schema over a toy
    piece table) chains through its compiled table and the stream
    matches the sync path byte-for-byte."""
    from dynamo_tpu.engine.guided import JsonConstraint, JsonGrammar

    v = 512
    pieces = [None] * v
    for i, ch in enumerate('"abcdefgh'):
        pieces[50 + i] = ch
    grammar = JsonGrammar(
        pieces, {"enum": ["abca", "abda", "aeee", "gh"]}
    )

    def reqs():
        er = _request([1, 2], 16)
        er.guided = JsonConstraint(grammar)
        return [er]

    want = _run(_config(1, k=2), reqs())
    assert want[0][1] == "stop" and len(want[0][0]) >= 4
    box = {}

    def hooks(s):
        box["sched"] = s
        _precompile_guided_tables(s)

    got = _run(_config(2, k=2), reqs(), hooks=hooks)
    assert got == want
    sched = box["sched"]
    assert sched.runner.chained_calls > 1, "guided-json chain never engaged"
    assert sched.allocator.used == 0


def test_guided_table_bound_falls_back_named():
    """A grammar whose reachable states exceed the bound keeps the sync
    path with reason guided_table_bound — never a silent downgrade."""
    config = _config(2, k=2, guided_table_max_states=2)
    box = {}

    def hooks(s):
        box["sched"] = s
        _precompile_guided_tables(s)

    out = _run(config, [_guided_request([1, 2], 16, CHOICES)],
               hooks=hooks)
    sched = box["sched"]
    assert out[0][1] == "stop"
    assert sched.runner.chained_calls == 0
    assert "guided_table_bound" in _fallback_reasons(sched)


def _stop_seq_request(prompt, max_tokens, seqs, stop=None):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, ignore_eos=True,
            stop=stop or ["x"] * len(seqs),
            stop_token_seqs=[list(s) for s in seqs],
        ),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[],
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


def test_stop_string_token_seq_chains_byte_identical():
    """Stop-string rows with canonical token seqs chain via the
    suffix-hash approximation: the device freezes the row at the
    matching token, the host's exact check names the STOP, and the
    stream matches the sync path (which runs the same exact check)."""
    plain = _streams(1, max_tokens=24)
    seq = [plain[0][0][3], plain[0][0][4]]  # tokens 4-5 of the stream

    def reqs():
        return [_stop_seq_request(PROMPTS[0], 24, [seq])]

    rs = reqs()
    assert all(er.device_checkable for er in rs)
    want = _run(_config(1), rs)
    assert want[0][1] == "stop" and len(want[0][0]) == 5
    box = {}

    def grab(s):
        box["sched"] = s

    got = _run(_config(2), reqs(), hooks=grab)
    assert got == want
    sched = box["sched"]
    assert sched.runner.chained_calls > 0, "stop-seq row never chained"
    assert sum(sched._device_finished_ctr.values.values()) == 1
    assert sched.allocator.used == 0


def test_stop_string_false_positive_resumes_byte_identical():
    """A suffix-hash collision (injected via the fake's candidate hook)
    freezes a row the host cannot confirm: the scheduler must flag the
    false positive, close the chain, and resume the row so the stream
    is STILL byte-identical to the sync path — with zero leaked blocks
    and the fallback counter naming stop_false_positive."""
    never = [499, 498]  # a seq the stream never produces

    def reqs():
        return [_stop_seq_request(p, 21, [never]) for p in PROMPTS]

    want = _run(_config(1), reqs())
    assert all(f == "length" for _, f in want)
    box = {}
    fired = []

    def hooks(s):
        box["sched"] = s

        def force(slot, gen):
            if slot == 0 and gen == 6 and not fired:
                fired.append((slot, gen))
                return True
            return False

        s.runner.force_stop_candidate = force

    got = _run(_config(2), reqs(), hooks=hooks)
    assert fired, "test is vacuous: the candidate hook never fired"
    assert got == want
    sched = box["sched"]
    assert sched.runner.chained_calls > 1
    assert "stop_false_positive" in _fallback_reasons(sched)
    assert sched.allocator.used == 0, "false-positive path leaked blocks"


def test_stop_ids_width_16_chains_and_overflow_is_named():
    """9-16 stop/eos ids chain now (the old width-8 cliff); >16 fall
    back with reason stop_ids_overflow instead of silently."""
    plain = _streams(1, max_tokens=24)
    eos16 = [plain[0][0][5]] + list(range(400, 415))  # 16 ids, one hits
    assert len(eos16) == 16
    want = _streams(1, max_tokens=24, eos=eos16)
    assert want[0][1] == "eos"
    box = {}
    got = _streams(2, max_tokens=24, eos=eos16, sched_out=box)
    assert got == want
    assert box["sched"].runner.chained_calls > 0, "16-id row never chained"

    eos17 = list(range(400, 417))
    rs = [_request(PROMPTS[0], 8, eos=eos17)]
    assert not rs[0].device_checkable
    assert rs[0].chain_fallback == "stop_ids_overflow"
    box2 = {}

    def grab(s):
        box2["sched"] = s

    _run(_config(2), rs, hooks=grab)
    assert "stop_ids_overflow" in _fallback_reasons(box2["sched"])


def test_n_gt_1_fans_out_into_chain_members():
    """serving-level n>1 fan-out: each choice is an independent n=1
    chain member; deltas fold at drain tagged with their choice index,
    per-choice streams match n separate single-choice runs, and the
    chain engages (depth 2)."""
    from dynamo_tpu.engine.serving import JaxServingEngine
    from dynamo_tpu.runtime.engine import Context

    def fan_run(depth):
        config = _config(depth)

        async def go():
            runner = FakeRunner(config)
            sched = Scheduler(runner, config)
            engine = JaxServingEngine(runner, sched, config)
            sched.start()
            req = PreprocessedRequest(
                token_ids=[1, 17, 43],
                stop_conditions=StopConditions(max_tokens=9,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0, n=3),
                eos_token_ids=[],
            )
            per_choice = {i: [] for i in range(3)}
            finishes = {}
            async for out in engine.generate(Context(req)):
                c = out.get("choice")
                per_choice[c].extend(out.get("token_ids", []))
                if out.get("finish_reason"):
                    finishes[c] = out["finish_reason"]
            chained = sched.runner.chained_calls
            await engine.close()
            return per_choice, finishes, chained

        loop = asyncio.new_event_loop()
        try:
            return loop.run_until_complete(go())
        finally:
            loop.close()

    single = _run(_config(1), [_request([1, 17, 43], 9)])
    sync_c, sync_f, _ = fan_run(1)
    chain_c, chain_f, chained = fan_run(2)
    assert chain_c == sync_c
    assert chain_f == sync_f == {0: "length", 1: "length", 2: "length"}
    # greedy choices are identical streams, each equal to a lone run
    for i in range(3):
        assert chain_c[i] == single[0][0]
    assert chained > 1, "n>1 children never chained"


def test_mixed_workload_chains_with_attributed_fallbacks():
    """The acceptance shape: a mixed batch (plain + guided + stop-seq)
    runs with chain length p50 > 1 and every sync pass attributed to a
    named reason in dynamo_engine_sync_fallback_total."""
    plain = _streams(1, max_tokens=20)
    seq = [plain[1][0][4], plain[1][0][5]]

    def reqs():
        return [
            _request(PROMPTS[0], 20),
            _stop_seq_request(PROMPTS[1], 20, [seq]),
            _guided_request(PROMPTS[2], 20, CHOICES),
        ]

    want = _run(_config(1, k=2), reqs())
    box = {}

    def hooks(s):
        box["sched"] = s
        _precompile_guided_tables(s)

    got = _run(_config(2, k=2), reqs(), hooks=hooks)
    assert got == want
    sched = box["sched"]
    assert sched._last_chain_len > 1 or sched._chain_dispatched > 1
    assert sched.runner.chained_calls > 1
    # every counted fallback reason is named (no empty labels)
    assert all(r for r in _fallback_reasons(sched))
    assert sched.allocator.used == 0


# --------------------------------------------------------------------------
# the decode step one step ahead of the host (ISSUE 57): step k goes to the
# device before step k-1 is read, fed k-1's tokens on the device; the host
# still decides every finish, one step later
# --------------------------------------------------------------------------


class TapeRunner(FedRunner):
    """A fed runner that writes each ``step`` it is given on ``tape``:
    ("decode", tokens out, live slots, fed slots, {slot: position}, the
    step it was fed) or ("prefill", tokens out, the rows' slots); a test
    adds ("fetch", kind, tokens read) through ``_tape_fetches``. The
    arrays themselves are kept (compared with ``is``), so no id is used
    twice."""

    def __init__(self, config):
        super().__init__(config)
        self.tape = []

    def step(self, tokens, positions, btab, slot_map, ctx_lens, last_idx,
             *args, commit=None, sample_slots=None, prev_tokens=None, **kw):
        out = super().step(
            tokens, positions, btab, slot_map, ctx_lens, last_idx, *args,
            commit=commit, sample_slots=sample_slots,
            prev_tokens=prev_tokens, **kw)
        tokens = np.asarray(tokens)
        if tokens.shape[1] == 1:
            live = [int(i) for i in np.flatnonzero(np.asarray(commit))]
            self.tape.append((
                "decode", out[0], live,
                [i for i in live if tokens[i, 0] == FED],
                {i: int(np.asarray(positions)[i, 0]) for i in live},
                prev_tokens))
        else:
            self.tape.append(
                ("prefill", out[0], [int(i) for i in sample_slots]))
        return out


def _tape_fetches(sched):
    fetch = sched._fetch

    async def _fetch(loop, kind, arrays, *a, tokens_at=0, **kw):
        sched.runner.tape.append(("fetch", kind, arrays[tokens_at]))
        return await fetch(loop, kind, arrays, *a, tokens_at=tokens_at, **kw)

    sched._fetch = _fetch


def _at(tape, what, array):
    """Where on the tape ``array`` was made (``what`` a dispatch) or read
    (``what`` "fetch")."""
    return next(i for i, e in enumerate(tape)
                if (e[0] == "fetch") == (what == "fetch")
                and e[1 if what != "fetch" else 2] is array)


def _ahead_run(reqs, hooks=None, **cfg_kw):
    """The requests over a ``TapeRunner`` with the step ahead; returns
    (streams, scheduler)."""
    box = {}

    def both(sched):
        box["sched"] = sched
        _tape_fetches(sched)
        if hooks:
            hooks(sched)

    out = _run(_config(1, k=1, **cfg_kw), reqs, hooks=both,
               runner_cls=TapeRunner)
    return out, box["sched"]


def _count(counter):
    return sum(counter.values.values())


def test_ahead_step_is_dispatched_before_the_step_before_is_read():
    """Every decode step but the first goes out before the step before
    it is fetched, its rows fed on the device; the streams are the
    synchronous scheduler's."""
    want = _streams(1, k=1)
    got, sched = _ahead_run([_request(p, 21) for p in PROMPTS])
    assert got == want
    tape = sched.runner.tape
    steps = [e for e in tape if e[0] == "decode"]
    assert len(steps) == 20       # 21 tokens a row, the first a prefill's
    first, rest = steps[0], steps[1:]
    assert first[3] == [] and first[5] is None
    for before, step in zip(steps, rest):
        assert step[5] is before[1]                   # fed by the step before
        assert step[3] == step[2] == before[2]        # every row, on the device
        assert _at(tape, "decode", step[1]) < _at(tape, "fetch", before[1])
        assert all(step[4][i] == before[4][i] + 1 for i in step[2])
    assert _count(sched._ahead_ctr) == 19
    assert _count(sched._ahead_discarded_ctr) == 0
    assert not _fallback_reasons(sched)
    # one fetch a step, each after its own dispatch
    assert _count(sched._fetches_ctr) == 20 + 1       # and the prefill's
    assert sched._ahead is None and sched.allocator.used == 0


def test_ahead_row_that_stops_has_its_next_row_dropped():
    """A row that ends at step k-1 by a stop token was already in step
    k: that row of k is read and dropped, never emitted or counted, and
    the request's usage is what the synchronous pass gives."""
    plain = _streams(1, k=1)
    eos = plain[0][0][6]           # the first row stops at its 7th token
    assert eos not in plain[0][0][:6]
    want = _streams(1, k=1, eos=[eos])
    assert want[0] == (plain[0][0][:7], "eos")
    reqs = [_request(p, 21, eos=[eos]) for p in PROMPTS]
    got, sched = _ahead_run(reqs)
    assert got == want
    stopped = [er for er, (toks, fin) in zip(reqs, got) if fin == "eos"]
    assert reqs[0] in stopped
    assert _count(sched._ahead_discarded_ctr) == len(stopped)
    for er, (toks, _) in zip(reqs, got):
        # usage: what was emitted, and nothing of the dropped row
        assert er.generated == er.decode_tokens + 1 == len(toks)
        assert er.ctx.counts["decode_tokens"] == len(toks) - 1
    # the first row's slot: in the step after its last (fed, dropped),
    # in none after that
    steps = [e for e in sched.runner.tape if e[0] == "decode"]
    with_row = [i for i, e in enumerate(steps) if 0 in e[2]]
    assert with_row == list(range(7))    # 6 of its own after the prefill's + 1
    assert 0 in steps[6][3]
    assert sched.allocator.used == 0


def test_ahead_row_that_ends_by_length_is_not_in_the_next_step():
    """The host knows a row's last step by its count (and by the model's
    length): the row is left out of the step after, so nothing is
    dropped."""
    reqs = [_request(PROMPTS[0], 5), _request(PROMPTS[1], 9)]
    want = _run(_config(1, k=1), [_request(PROMPTS[0], 5),
                                  _request(PROMPTS[1], 9)])
    got, sched = _ahead_run(reqs)
    assert got == want and [len(t) for t, _ in got] == [5, 9]
    steps = [e for e in sched.runner.tape if e[0] == "decode"]
    assert [e[2] for e in steps] == [[0, 1]] * 4 + [[1]] * 4
    assert _count(sched._ahead_discarded_ctr) == 0
    assert _count(sched._ahead_ctr) == 7
    # and by the horizon: a prompt that ends two short of max_model_len
    horizon = _config(1, k=1, max_model_len=16)
    long = list(range(1, 12))
    want = _run(horizon, [_request(long, 40)])
    got, sched = _ahead_run([_request(long, 40)], max_model_len=16)
    assert got == want and got[0][1] == "length"
    assert _count(sched._ahead_discarded_ctr) == 0
    assert _count(sched._ahead_ctr) == len(got[0][0]) - 2


def test_ahead_freed_slots_prefill_follows_the_step_that_wrote_to_it():
    """Five requests on four slots: the fifth takes the slot of the row
    that stopped, whose dropped row of the step ahead still wrote there.
    Its prefill chunk is dispatched after that step (the device runs
    programs in dispatch order), and every stream is the synchronous
    scheduler's."""
    plain = _streams(1, k=1)
    eos = plain[0][0][6]

    def reqs():
        return [_request(p, 21, eos=[eos])
                for p in (*PROMPTS, [4, 4], [8, 1, 8])]

    want = _run(_config(1, k=1), reqs())
    got, sched = _ahead_run(reqs())
    assert got == want
    tape = sched.runner.tape
    # the last step that ran slot 0's first occupant: its dropped row
    dropped = next(i for i, e in enumerate(tape) if e[0] == "decode"
                   and 0 in e[3] and sum(
                       1 for f in tape[:i + 1]
                       if f[0] == "decode" and 0 in f[2]) == 7)
    late = [i for i, e in enumerate(tape) if e[0] == "prefill"
            and 0 in e[2] and i > 4]
    assert late and late[0] > dropped
    assert _count(sched._ahead_discarded_ctr) >= 1
    assert sched.allocator.used == 0


def test_ahead_kv_oom_drains_the_step_then_preempts():
    """A block that cannot be had for a continuing row: the step in
    flight is read and applied first (``kv_oom``), and preemption then
    runs from committed state, as it always did."""
    want = _streams(1, max_tokens=24, k=1, num_kv_blocks=64)
    preempts = []

    def hooks(sched):
        orig = sched._preempt

        def spy(er):
            tape = sched.runner.tape
            made = [e[1] for e in tape if e[0] == "decode"]
            read = [e[2] for e in tape if e[0] == "fetch"]
            assert sched._ahead is None and all(
                any(m is r for r in read) for m in made), (
                "preempted with a step still in flight")
            assert er.context_len == len(er.seq.token_ids)
            preempts.append(er.request_id)
            return orig(er)

        sched._preempt = spy

    got, sched = _ahead_run([_request(p, 24) for p in PROMPTS], hooks=hooks,
                            num_kv_blocks=10)
    assert preempts, "test is vacuous: no preemption happened"
    assert [(len(t), f) for t, f in got] == [(24, "length")] * 3
    assert got == want
    assert "kv_oom" in _fallback_reasons(sched)
    assert _count(sched._ahead_ctr) > 0
    assert sched.allocator.used == 0


def test_ahead_guided_row_holds_the_pass_to_the_host():
    """The host rewrites a guided row's mask from each token: while one
    is active no step is dispatched ahead, under the reason ``guided``;
    the others go ahead again once it has ended."""
    def reqs():
        return [_request(PROMPTS[0], 30),
                _guided_request(PROMPTS[2], 20, CHOICES)]

    want = _run(_config(1, k=1), reqs())
    got, sched = _ahead_run(reqs())
    assert got == want
    assert _fallback_reasons(sched) == {"guided"}
    steps = [e for e in sched.runner.tape if e[0] == "decode"]
    assert all(e[5] is None and not e[3] for e in steps if 1 in e[2])
    assert _count(sched._ahead_ctr) > 10
    assert all(e[5] is not None for e in steps[-10:])


def _ahead_interrupted(after, interrupt):
    """Two requests over a ``TapeRunner``; once the first has
    ``after`` tokens, ``interrupt(sched, reqs)`` (a coroutine) runs with
    a decode step in flight. Returns (scheduler, requests, what each
    request's queue held up to then or to its end)."""

    async def go():
        config = _config(1, k=1)
        sched = Scheduler(TapeRunner(config), config)
        _tape_fetches(sched)
        reqs = [_request(PROMPTS[0], 40), _request(PROMPTS[1], 40)]
        sched.start()
        for er in reqs:
            sched.add_request(er)
        got = [[], []]
        try:
            while len(got[0]) < after:
                out = await reqs[0].out_queue.get()
                got[0].extend(out.token_ids)
            assert sched._ahead is not None, "no step in flight"
            await interrupt(sched, reqs)
            for er, toks in zip(reqs, got):
                while not er.out_queue.empty():
                    out = er.out_queue.get_nowait()
                    if out is not None:
                        toks.extend(out.token_ids)
        finally:
            await sched.stop()
        return sched, reqs, got

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


def _every_step_was_read(sched):
    tape = sched.runner.tape
    read = [e[2] for e in tape if e[0] == "fetch"]
    return all(any(e[1] is r for r in read)
               for e in tape if e[0] == "decode")


def test_ahead_seize_and_extract_find_committed_state():
    """A graceful ``seize`` lets the loop end on its barrier: the step in
    flight is read and applied (``stop``), so what ``extract_requests``
    hands to a migration is committed: every token that was emitted, and
    no other, lies in the host's mirror or is the pending one."""
    want = _run(_config(1, k=1), [_request(PROMPTS[0], 40),
                                  _request(PROMPTS[1], 40)])

    async def interrupt(sched, reqs):
        await sched.seize()
        assert sched._ahead is None

    sched, reqs, got = _ahead_interrupted(6, interrupt)
    assert _every_step_was_read(sched)
    assert "stop" in _fallback_reasons(sched)
    out = sched.extract_requests()
    assert {id(er) for er in out} == {id(er) for er in reqs}
    for er, toks, (full, _) in zip(reqs, got, want):
        assert toks == full[:len(toks)] and len(toks) == er.generated >= 6
        assert er.context_len == len(er.seq.token_ids)
        assert er.seq.token_ids[len(er.prompt):] + [er.pending_token] == toks
    # a hard seize abandons the step: nothing of it was emitted
    async def hard(sched, reqs):
        await sched.seize(hard=True)
        assert sched._ahead is None

    sched, reqs, got = _ahead_interrupted(6, hard)
    for er, toks, (full, _) in zip(reqs, got, want):
        assert toks == full[:len(toks)] and len(toks) == er.generated
        assert er.seq.token_ids[len(er.prompt):] + [er.pending_token] == toks


def test_ahead_stop_reads_the_step_in_flight():
    async def interrupt(sched, reqs):
        await sched.stop()

    sched, reqs, got = _ahead_interrupted(6, interrupt)
    assert sched._ahead is None and _every_step_was_read(sched)
    assert "stop" in _fallback_reasons(sched)
    for er, toks in zip(reqs, got):
        assert len(toks) == er.generated


def test_ahead_cancel_drops_the_row_and_leaves_the_others():
    """A client that leaves with a step in flight: its rows of the steps
    already dispatched are read and dropped, its slot and blocks go back
    at the next admit, and the other stream is untouched."""
    want = _run(_config(1, k=1), [_request(PROMPTS[0], 40),
                                  _request(PROMPTS[1], 40)])

    async def interrupt(sched, reqs):
        reqs[0].ctx.stop_generating()
        while True:
            out = await reqs[1].out_queue.get()
            if out is None:
                break
            got1.extend(out.token_ids)

    got1 = []
    sched, reqs, got = _ahead_interrupted(6, interrupt)
    assert reqs[0].finish == "cancelled"
    assert got[1] + got1 == want[1][0]
    assert got[0] == want[0][0][:len(got[0])]
    assert _count(sched._ahead_discarded_ctr) >= 1
    assert _every_step_was_read(sched) and sched.allocator.used == 0


def test_ahead_every_fallback_names_its_reason():
    """Over a mixed run (a guided row, a stop token, a short pool, more
    requests than slots): a pass that had a step in flight and did not
    dispatch ahead of it either dispatched nothing (every row known to
    end) or counted a reason; and the streams are the synchronous
    scheduler's."""
    plain = _streams(1, k=1)
    eos = plain[1][0][9]

    def reqs():
        return [_request(PROMPTS[0], 24, eos=[eos]),
                _request(PROMPTS[1], 24, eos=[eos]),
                _guided_request(PROMPTS[2], 20, CHOICES),
                _request([4, 4], 24, eos=[eos]),
                _request([8, 1, 8], 7, eos=[eos])]

    seen = []

    def hooks(sched):
        decode = sched._decode

        async def spy(loop, active, k_steps=1):
            had = sched._ahead is not None
            before = (_count(sched._ahead_ctr),
                      _count(sched._sync_fallback_ctr),
                      sum(e[0] == "decode" for e in sched.runner.tape))
            await decode(loop, active, k_steps)
            after = (_count(sched._ahead_ctr),
                     _count(sched._sync_fallback_ctr),
                     sum(e[0] == "decode" for e in sched.runner.tape))
            seen.append((had, *(b - a for a, b in zip(before, after))))

        sched._decode = spy

    want = _run(_config(1, k=1, num_kv_blocks=12), reqs())
    got, sched = _ahead_run(reqs(), hooks=hooks, num_kv_blocks=12)
    assert got == want
    for had, ahead, fell, dispatched in seen:
        assert ahead + fell <= 1 and dispatched <= 1
        if had and not ahead:
            assert fell == 1 or dispatched == 0, (had, ahead, fell, dispatched)
        if not had:
            assert not ahead
    reasons = _fallback_reasons(sched)
    assert reasons and reasons <= {"guided", "kv_oom"} and all(reasons)
    assert sum(a for _, a, _, _ in seen) == _count(sched._ahead_ctr) > 0
    assert sched.allocator.used == 0


def test_ahead_step_behind_a_chunk_is_read_before_the_prompts_last_chunk():
    """A prompt of three chunks beside a row that decodes: the decode
    step of a middle chunk's pass stands behind that chunk in the
    device's queue, and the next pass, whose chunk ends the prompt, waits
    for its chunk. The step in flight is read before that wait
    (``behind_chunk``): its tokens were ready a chunk earlier. A prompt
    of one chunk leaves the step in flight, as ever."""
    cfg = dict(max_prefill_tokens_per_step=8, prefill_buckets=[8, 16, 32])

    def reqs(long):
        return [_request(PROMPTS[0], 30), _request(long, 12)]

    long = list(range(3, 23))             # 20 tokens: chunks of 8, 8, 4
    want = _run(_config(1, k=1, **cfg), reqs(long))
    got, sched = _ahead_run(reqs(long), **cfg)
    assert got == want
    assert "behind_chunk" in _fallback_reasons(sched)
    tape = sched.runner.tape
    chunks = [i for i, e in enumerate(tape) if e[0] == "prefill"]
    # (the short prompt's one chunk, then the long one's three)
    assert len(chunks) == 4
    last = tape[chunks[-1]]
    behind = next(e for e in reversed(tape[:chunks[-1]])
                  if e[0] == "decode")          # dispatched behind chunk two
    assert _at(tape, "decode", behind[1]) > chunks[-2]
    assert _at(tape, "fetch", behind[1]) < _at(tape, "fetch", last[1])
    # one chunk a prompt: nothing to read early, no such reason
    got, sched = _ahead_run(reqs([5, 6, 7, 8]), **cfg)
    assert got == _run(_config(1, k=1, **cfg), reqs([5, 6, 7, 8]))
    assert "behind_chunk" not in _fallback_reasons(sched)
