"""Fused multi-step decode (EngineConfig.multi_step_decode).

The K-step burst must be invisible in outputs: the same prompts, seeds,
and sampling knobs produce bit-identical token streams whether the
engine dispatches per token (K=1) or per burst (K>1) — the burst fuses
dispatch, not semantics. Reference analog: the multi-step scheduling of
the engines behind examples/llm/components/worker.py, which likewise
trades ITL granularity for dispatch amortization.
"""

import asyncio
import json
import os

import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.serving import JaxServingEngine
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context

from fixtures import make_model_dir

TINY = dict(
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=256,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
)


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    d = make_model_dir(tmp_path_factory.mktemp("msmodel"), name="tiny-ms")
    cfg = LlamaConfig(**TINY, tie_word_embeddings=False)
    torch.manual_seed(0)
    LlamaForCausalLM(cfg).save_pretrained(d, safe_serialization=True)
    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c["eos_token_id"] = 2
    c["bos_token_id"] = 1
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    return d


def _config(model_dir, multi_step, pipeline=1, **kw):
    cfg = ModelConfig.from_model_dir(model_dir)
    return EngineConfig(
        model=cfg, max_batch_size=4, max_model_len=128, kv_block_size=8,
        num_kv_blocks=96, dtype="float32", multi_step_decode=multi_step,
        decode_pipeline_depth=pipeline, **kw,
    )


async def _collect(engine, token_ids, sampling, max_tokens=24,
                   ignore_eos=True, stop_hidden=None):
    req = PreprocessedRequest(
        token_ids=list(token_ids),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, ignore_eos=ignore_eos,
            stop_token_ids_hidden=stop_hidden,
        ),
        sampling_options=sampling,
    )
    toks, finish = [], None
    async for out in engine.generate(Context(req)):
        toks.extend(out["token_ids"])
        if out.get("finish_reason"):
            finish = out["finish_reason"]
    return toks, finish


def _runs(model_dir, multi_step, pipeline=1):
    async def go():
        mdc = ModelDeploymentCard.from_local_path(model_dir)
        engine = await JaxServingEngine.create(
            mdc, engine_config=_config(model_dir, multi_step, pipeline),
            warmup=False,
        )
        results = []
        # greedy; seeded sampling; penalties + repetition; concurrent pair
        results.append(await _collect(
            engine, [1, 17, 43, 99, 7], SamplingOptions(temperature=0.0)))
        results.append(await _collect(
            engine, [1, 5, 9, 13], SamplingOptions(temperature=0.8, seed=7)))
        results.append(await _collect(
            engine, [1, 100, 200, 300],
            SamplingOptions(temperature=0.7, seed=3, top_k=40,
                            frequency_penalty=0.5, repetition_penalty=1.2)))
        pair = await asyncio.gather(
            _collect(engine, [1, 42, 42], SamplingOptions(temperature=0.0)),
            _collect(engine, [1, 7, 7, 7, 7],
                     SamplingOptions(temperature=0.9, seed=11)),
        )
        results.extend(pair)
        await engine.close()
        return results

    return asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())


def test_burst_streams_bit_equal_to_single_step(model_dir):
    assert _runs(model_dir, 1) == _runs(model_dir, 4)


@pytest.mark.asyncio
async def test_burst_actually_engages(model_dir):
    # guard against the equivalence tests passing vacuously: K=4 must
    # produce ~4x fewer device dispatches for the same token count
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    engine = await JaxServingEngine.create(
        mdc, engine_config=_config(model_dir, 4), warmup=False)
    toks, _ = await _collect(engine, [1, 5, 9],
                             SamplingOptions(temperature=0.0), max_tokens=16)
    steps = engine.scheduler.steps
    await engine.close()
    assert len(toks) == 16
    # 1 prefill dispatch + ceil(16/4) bursts, plus slack for scheduling
    assert steps <= 8, f"burst never engaged ({steps} dispatches)"


@pytest.mark.asyncio
async def test_burst_stop_mid_burst_trims_and_finishes(model_dir):
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    single = await JaxServingEngine.create(
        mdc, engine_config=_config(model_dir, 1), warmup=False)
    # find greedy continuation, then declare its 2nd token a hidden stop:
    # under K=4 the stop lands mid-burst and the tail must be trimmed
    toks, _ = await _collect(single, [1, 5, 9],
                             SamplingOptions(temperature=0.0), max_tokens=6)
    stop_tok = toks[1]
    want, want_finish = await _collect(
        single, [1, 5, 9], SamplingOptions(temperature=0.0), max_tokens=6,
        stop_hidden=[stop_tok])
    await single.close()
    assert want_finish == "stop" and len(want) < len(toks)

    burst = await JaxServingEngine.create(
        mdc, engine_config=_config(model_dir, 4), warmup=False)
    got, finish = await _collect(
        burst, [1, 5, 9], SamplingOptions(temperature=0.0), max_tokens=6,
        stop_hidden=[stop_tok])
    await burst.close()
    assert (got, finish) == (want, want_finish)


@pytest.mark.asyncio
async def test_burst_near_model_len_falls_back_and_finishes(model_dir):
    # a context within K of max_model_len forces per-token stepping; the
    # request still ends with reason length at the same point
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    cfg = _config(model_dir, 8)
    cfg.max_model_len = 32
    engine = await JaxServingEngine.create(
        mdc, engine_config=cfg, warmup=False)
    toks, finish = await _collect(
        engine, list(range(1, 21)), SamplingOptions(temperature=0.0),
        max_tokens=64)
    await engine.close()
    assert finish == "length"
    assert len(toks) == 32 - 20  # runs right up to max_model_len


def test_pipelined_streams_bit_equal_to_sync(model_dir):
    """The chain (decode_pipeline_depth=2) must be invisible in
    outputs: greedy, seeded sampling, penalties, and concurrent pairs
    all produce byte-identical streams vs the synchronous path."""
    assert _runs(model_dir, 4, pipeline=1) == _runs(model_dir, 4, pipeline=2)


def test_pipelined_single_step_bursts_bit_equal(model_dir):
    # depth 2 with multi_step_decode=1 runs a K=1 burst program —
    # still identical to the plain per-token path
    assert _runs(model_dir, 1, pipeline=1) == _runs(model_dir, 1, pipeline=2)


@pytest.mark.asyncio
async def test_pipelined_eos_mid_burst_freezes_and_finishes(model_dir):
    """A stop token landing mid-burst under depth 2 freezes the row on
    the device while later bursts are already queued: nothing emits past
    it, the blocks reserved ahead roll back, the slot frees, and the
    emitted stream must equal the synchronous path's, byte for byte."""
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    single = await JaxServingEngine.create(
        mdc, engine_config=_config(model_dir, 4), warmup=False)
    toks, _ = await _collect(single, [1, 5, 9],
                             SamplingOptions(temperature=0.0), max_tokens=12)
    stop_tok = toks[5]  # lands mid-burst under K=4
    want, want_finish = await _collect(
        single, [1, 5, 9], SamplingOptions(temperature=0.0), max_tokens=12,
        stop_hidden=[stop_tok])
    await single.close()
    assert want_finish == "stop" and len(want) < len(toks)

    piped = await JaxServingEngine.create(
        mdc, engine_config=_config(model_dir, 4, pipeline=2), warmup=False)
    got, finish = await _collect(
        piped, [1, 5, 9], SamplingOptions(temperature=0.0), max_tokens=12,
        stop_hidden=[stop_tok])
    sched = piped.scheduler
    assert sched.pipeline_bursts > 0, "chain never engaged"
    # the roll-back returned every block (no leak from headroom)
    assert sched.allocator.used == 0
    await piped.close()
    assert (got, finish) == (want, want_finish)


@pytest.mark.asyncio
async def test_pipelined_bubble_metric_and_depth_gauge(model_dir):
    """The depth-2 run must dispatch ahead (depth gauge reads 2 while a
    chain is open) and record bubble observations; the sync run
    records strictly positive gaps."""
    mdc = ModelDeploymentCard.from_local_path(model_dir)

    async def run(depth):
        engine = await JaxServingEngine.create(
            mdc, engine_config=_config(model_dir, 4, pipeline=depth),
            warmup=False)
        await _collect(engine, [1, 5, 9], SamplingOptions(temperature=0.0),
                       max_tokens=16)
        hist = engine.scheduler._bubble_hist
        key = ()
        totals = hist.totals.get(key, 0)
        sums = hist.sums.get(key, 0.0)
        bursts = engine.scheduler.pipeline_bursts
        exposition = engine.scheduler.registry.render()
        await engine.close()
        return totals, sums, bursts, exposition

    n_sync, sum_sync, bursts_sync, _ = await run(1)
    n_pipe, sum_pipe, bursts_pipe, expo = await run(2)
    assert bursts_sync == 0 and bursts_pipe > 0
    assert n_sync > 0 and sum_sync > 0.0  # sync path: real host bubbles
    assert n_pipe > 0  # the chain still observes (mostly zeros)
    assert "dynamo_engine_decode_pipeline_bubble_seconds_bucket" in expo
    assert "dynamo_engine_decode_pipeline_depth" in expo


@pytest.mark.asyncio
async def test_burst_with_prefix_cache_reuse(model_dir):
    # burst-written blocks enter the prefix cache; a rerun must hit the
    # cache and still produce the identical stream
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    engine = await JaxServingEngine.create(
        mdc, engine_config=_config(model_dir, 4, enable_prefix_caching=True),
        warmup=False)
    prompt = [1] + list(range(50, 50 + 23))
    first, _ = await _collect(engine, prompt, SamplingOptions(temperature=0.0))
    second, _ = await _collect(engine, prompt, SamplingOptions(temperature=0.0))
    m = engine.metrics()
    await engine.close()
    assert first == second
    assert m["gpu_prefix_cache_hit_rate"] > 0.0
