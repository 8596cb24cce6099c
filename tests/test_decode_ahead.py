"""The decode step one step ahead of the host, on real tiny engines
(ISSUE 57).

``Scheduler._decode`` dispatches step k before it has read step k-1,
feeding k-1's tokens to k on the device (``ModelRunner.step(prev_tokens=)``,
``step_inputs.FED``). The host still decides every finish, block and
window page itself, one step later, so the stream a client sees is token
for token and log-probability for log-probability what the synchronous
pass gives. Held here on one engine a kind of sequence state: a Llama
trunk (pages only), ``falcon_h1`` (a record by slot beside the pages)
and ``afmoe`` (the window pool, pages taken and given back one position
on). The synchronous pass is the same scheduler with every pass made to
fall back: the test patches the reason, the program has no switch.

And the program is one program: warm-up leaves no compile for the first
step that is fed from the device, on one device and on the four-device
mesh. The scheduler's logic alone, over a fake runner, is in
tests/test_decode_pipeline.py.
"""

import asyncio
import dataclasses
import uuid

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext


def _llama(**over):
    kw = dict(
        model=ModelConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=4,
                          num_kv_heads=2),
        max_batch_size=4, max_model_len=128, kv_block_size=8,
        num_kv_blocks=64, dtype="float32", prefill_buckets=[16, 64],
        allow_random_weights=True, seed=11, max_prefill_batch=2)
    kw.update(over)
    return EngineConfig(**kw)


def _falcon_h1():
    import test_falcon_h1_reference as t

    return t._engine_config()


def _afmoe():
    import test_afmoe_reference as t

    return t._engine_config()


KINDS = {"llama": _llama, "falcon_h1": _falcon_h1, "afmoe": _afmoe}


@pytest.fixture(scope="module", params=sorted(KINDS))
def runner(request):
    return ModelRunner(KINDS[request.param]())


def _request(prompt, max_tokens, sampling=None, stop=None):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, ignore_eos=True,
            stop_token_ids_hidden=stop),
        sampling_options=sampling or SamplingOptions(temperature=0.0),
        output_options=OutputOptions(logprobs=2),
        eos_token_ids=[],
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


def _prompts(vocab, lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


def _serve(runner, requests, fall_back, config=None):
    """The requests through a scheduler over ``runner``; with
    ``fall_back`` every decode pass is held to the host's pace under a
    reason of the test's. Returns (streams, the scheduler): a stream is
    (tokens, log-probabilities, top alternatives, finish)."""

    async def go():
        sched = Scheduler(runner, config or runner.config)
        if fall_back:
            sched._ahead_block_reason = lambda active, k_steps: "test"
        sched.start()

        async def collect(er):
            toks, lps, tops, finish = [], [], [], None
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks, lps, tops, getattr(finish, "value", finish)
                toks.extend(out.token_ids)
                for lp in out.logprobs or []:
                    lps.append(lp.logprob)
                    tops.append(lp.top)
                finish = out.finish_reason or finish
        try:
            for er in requests:
                sched.add_request(er)
            got = await asyncio.gather(*(collect(er) for er in requests))
        finally:
            await sched.stop()
        return got, sched

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


def _total(counter):
    return sum(counter.values.values())


def _traffic(vocab, stop_on=None):
    """Six requests on four slots: greedy and seeded, lengths that end at
    different steps, so that slots are taken again while steps are in
    flight. ``stop_on``: (request, token) pairs that stop a stream."""
    prompts = _prompts(vocab, [20, 9, 33, 14, 5, 26])
    tokens = [24, 17, 30, 11, 21, 8]
    sampling = [None, SamplingOptions(temperature=0.8, seed=7), None,
                SamplingOptions(temperature=0.7, seed=3, top_k=40,
                                frequency_penalty=0.5), None, None]
    stops = dict(stop_on or ())
    return [_request(p, n, s, stop=[stops[i]] if i in stops else None)
            for i, (p, n, s) in enumerate(zip(prompts, tokens, sampling))]


def test_streams_with_the_step_ahead_are_the_synchronous_ones(runner):
    """By length, then with three of the streams cut by a stop token
    (each at a token the first run gave it, mid-stream): tokens,
    log-probabilities and alternatives equal bit for bit, finishes equal;
    with the step ahead nearly every step goes out before the one before
    it is read, a stopped row costs one dropped row, and no pass falls
    back; held back, none goes ahead and every pass says ``test``."""
    vocab = runner.config.model.vocab_size
    want, held = _serve(runner, _traffic(vocab), fall_back=True)
    got, sched = _serve(runner, _traffic(vocab), fall_back=False)
    assert got == want
    assert [f for *_, f in got] == ["length"] * 6
    assert [len(t) for t, *_ in got] == [24, 17, 30, 11, 21, 8]
    assert _total(held._ahead_ctr) == 0
    assert {dict(k)["reason"] for k in held._sync_fallback_ctr.values} \
        == {"test"}
    steps = _total(sched._fetches_ctr) - sum(
        v for k, v in sched._fetches_ctr.values.items()
        if dict(k)["kind"] == "prefill")
    assert _total(sched._ahead_ctr) >= 0.8 * steps > 20
    assert _total(sched._ahead_discarded_ctr) == 0
    assert not sched._sync_fallback_ctr.values
    assert sched.allocator.used == 0 and held.allocator.used == 0

    # a stop token a stream: one it did not give earlier, past its start
    stop_on = []
    for i in (0, 2, 4):
        toks = want[i][0]
        j = next(j for j in range(5, len(toks)) if toks[j] not in toks[:j])
        stop_on.append((i, toks[j]))
    want_s, _ = _serve(runner, _traffic(vocab, stop_on), fall_back=True)
    got_s, sched = _serve(runner, _traffic(vocab, stop_on), fall_back=False)
    assert got_s == want_s
    for i, token in stop_on:
        assert got_s[i][3] == "stop" and got_s[i][0][-1] == token
        assert len(got_s[i][0]) < len(want[i][0])
    assert [got_s[i][3] for i in (1, 3, 5)] == ["length"] * 3
    # a stopped row had one more row dispatched, which was dropped (unless
    # that step was its last by the count as well, or a prompt's last
    # chunk was read in between)
    assert 1 <= _total(sched._ahead_discarded_ctr) <= 3
    assert sched.allocator.used == 0
    if sched.window is not None:
        assert sched.window.used == 0


def test_preemption_with_the_step_ahead_streams_the_same(runner):
    """A pool too small for the rows: the pass that cannot have a block
    for a continuing row reads the step in flight first (``kv_oom``) and
    then preempts from committed state; the resumed streams are the
    synchronous ones."""
    vocab = runner.config.model.vocab_size
    pages = {8: 13, 16: 50}[runner.config.kv_block_size]
    config = dataclasses.replace(runner.config, num_kv_blocks=pages)
    lengths = [20, 18, 21] if runner.config.kv_block_size == 8 \
        else [256, 150, 330]

    def reqs():
        return [_request(p, 40) for p in _prompts(vocab, lengths, seed=12)]

    want, held = _serve(runner, reqs(), fall_back=True, config=config)
    got, sched = _serve(runner, reqs(), fall_back=False, config=config)
    assert _total(held._preemptions) > 0, "vacuous: nothing was preempted"
    assert got == want
    assert "kv_oom" in {dict(k)["reason"]
                        for k in sched._sync_fallback_ctr.values}
    assert _total(sched._ahead_ctr) > 0
    assert sched.allocator.used == 0


@pytest.mark.parametrize("tp", [1, 4])
def test_warm_up_leaves_no_compile_for_the_step_fed_from_the_device(tp):
    """The decode program takes the step before's tokens as one more
    argument, its own output's sharding: fed a step's output or the
    zeros that stand in where no step ran before, it is one executable.
    After warm-up a served run, whose steps are nearly all fed from the
    device, compiles nothing: no late first dispatch, no program in the
    jitted step's cache that warm-up did not leave there, and no compile
    that jax reports outside a first dispatch."""
    config = _llama(
        model=ModelConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_layers=2, num_heads=8,
                          num_kv_heads=4),
        tp_size=tp, prefill_buckets=[16, 32])
    runner = ModelRunner(config)
    runner.warmup()
    programs = runner._decode_step._cache_size()
    dispatched = len(runner.compiles.records)

    def untracked():
        return sum(v for k, v in runner.compiles._parts.values.items()
                   if dict(k)["phase"] == "late"
                   and dict(k)["part"] in ("trace", "lower", "compile"))

    prompts = _prompts(256, [12, 7, 20])
    got, sched = _serve(runner, [_request(p, 16) for p in prompts],
                        fall_back=False)
    assert [len(t) for t, *_ in got] == [16] * 3
    assert _total(sched._ahead_ctr) >= 12
    assert runner.compiles.late_compiles == 0
    assert len(runner.compiles.records) == dispatched
    assert runner._decode_step._cache_size() == programs
    assert untracked() == 0
    text = sched.registry.render()
    assert 'phase="late"' not in "".join(
        ln for ln in text.splitlines()
        if ln.startswith("dynamo_engine_xla_compiles_total"))


def test_warm_up_leaves_no_compile_for_the_block_pass_fed_from_the_device():
    """``jit_decode_block`` takes the pass before's ``new_ids`` as one
    more argument, its own output's sharding (ISSUE 60): fed a pass's
    output or the zeros that stand in where no pass ran before, it is
    one executable a table width. After warm-up a served run of a block
    family, whose passes are nearly all fed from the device, compiles
    no block pass: no program in the jitted pass's cache that warm-up
    did not leave there, no first dispatch of ``decode_block``, no
    compile that jax reports outside a first dispatch."""
    import test_block_decode as t

    runner = ModelRunner(t._engine_config(prefill_buckets=[32]))
    runner.warmup()
    programs = runner._decode_block._cache_size()
    dispatched = len(runner.compiles.records)
    assert programs == len(runner.warmed_widths["decode_block"]["widths"])

    def untracked():
        return sum(v for k, v in runner.compiles._parts.values.items()
                   if dict(k)["phase"] == "late"
                   and dict(k)["part"] in ("trace", "lower", "compile"))

    prompts = _prompts(255, [12, 7, 21])
    got, sched = _serve(runner, [_request(p, 16) for p in prompts],
                        fall_back=False)
    assert [len(t) for t, *_ in got] == [16] * 3
    assert _total(sched._ahead_ctr) >= 6
    assert runner.compiles.late_compiles == 0
    assert len(runner.compiles.records) == dispatched
    assert runner._decode_block._cache_size() == programs
    assert untracked() == 0
