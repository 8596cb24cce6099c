"""Draft-model speculative decoding.

A small same-tokenizer model proposes K tokens per round with its fused
burst; the target verifies K+1 positions in one forward. The stream is
provably identical to plain greedy decoding for ANY draft — the draft
only changes how much work each round amortizes — and the draft's paged
cache mirrors the target's block ids, so prefix-cache hits and resume
carry valid draft context. Reference analog: the draft/verify
speculation of the engines the reference delegates to (SURVEY §2.4).
"""

import asyncio
import json
import os

import numpy as np
import pytest

import jax

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.serving import JaxServingEngine, build_draft_config
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.protocols.common import (
    OutputOptions, PreprocessedRequest, SamplingOptions, StopConditions,
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


def _save_llama(d, seed, layers=2):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(**{**TINY, "num_hidden_layers": layers},
                      tie_word_embeddings=False)
    torch.manual_seed(seed)
    LlamaForCausalLM(cfg).save_pretrained(d, safe_serialization=True)
    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c["eos_token_id"] = 2
    c["bos_token_id"] = 1
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    return d


@pytest.fixture(scope="module")
def target_dir(tmp_path_factory):
    return _save_llama(
        make_model_dir(tmp_path_factory.mktemp("target"), name="tiny-hf"), 0
    )


@pytest.fixture(scope="module")
def draft_dir(tmp_path_factory):
    # different weights, 1 layer: a genuinely different (worse) model
    return _save_llama(
        make_model_dir(tmp_path_factory.mktemp("draft"), name="tiny-draft"),
        7, layers=1,
    )


async def _serve(model_dir, prompts, draft=None, k=4, max_tokens=12,
                 chain_len_out=None, **econfig_kw):
    econfig = EngineConfig(
        model=ModelConfig.from_model_dir(model_dir),
        max_batch_size=2, max_model_len=128, kv_block_size=8,
        num_kv_blocks=64, dtype="float32", prefill_buckets=[32],
        spec_draft_model=draft, spec_draft_tokens=k if draft else 0,
        **econfig_kw,
    )
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    engine = await JaxServingEngine.create(
        mdc, engine_config=econfig, warmup=False)
    outs = []
    for prompt in prompts:
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(
                max_tokens=max_tokens, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for out in engine.generate(Context(req)):
            toks.extend(out["token_ids"])
        outs.append(toks)
    stats = engine.scheduler.metrics() if hasattr(engine, "scheduler") else {}
    proposed = engine.scheduler.spec_proposed
    accepted = engine.scheduler.spec_accepted
    if chain_len_out is not None:
        chain_len_out["chain_len"] = engine.scheduler._last_chain_len
        chain_len_out["spec_rounds"] = sum(
            engine.scheduler._spec_accept_hist.totals.values()
        )
    await engine.close()
    del stats
    return outs, proposed, accepted


PROMPTS = [[1, 17, 43, 99, 7, 3], [1, 250, 12, 5, 77, 140, 9, 33]]


def test_draft_stream_identical_to_plain_greedy(target_dir, draft_dir):
    """THE speculation invariant: any draft, same stream."""
    ref, _, _ = asyncio.run(_serve(target_dir, PROMPTS))
    got, proposed, accepted = asyncio.run(
        _serve(target_dir, PROMPTS, draft=draft_dir)
    )
    assert got == ref
    assert proposed > 0  # speculation actually engaged
    assert 0 <= accepted <= proposed


def test_draft_chained_rounds_stream_identical(target_dir, draft_dir):
    """ISSUE 13: at decode_pipeline_depth=2, draft/target
    rounds interleave off the SAME device carry (no host barrier
    between rounds) — the stream must still equal plain greedy, the
    chain must actually run (>1 round between host barriers), and
    proposals must flow through the chained verify program."""
    ref, _, _ = asyncio.run(_serve(target_dir, PROMPTS, max_tokens=16))
    box = {}
    got, proposed, accepted = asyncio.run(_serve(
        target_dir, PROMPTS, draft=draft_dir, max_tokens=16,
        decode_pipeline_depth=2, chain_len_out=box,
    ))
    assert got == ref
    assert proposed > 0
    assert 0 <= accepted <= proposed
    assert box["spec_rounds"] > 0, "chained verify never ran"
    assert box["chain_len"] > 1, "host barrier still per round"


def test_self_draft_chained_accepts_everything(target_dir):
    """Draft == target under the chained rounds: every proposal
    verifies, so acceptance stays 100% through the carry-folded
    accept path too."""
    ref, _, _ = asyncio.run(_serve(target_dir, PROMPTS[:1]))
    got, proposed, accepted = asyncio.run(_serve(
        target_dir, PROMPTS[:1], draft=target_dir,
        decode_pipeline_depth=2,
    ))
    assert got == ref
    assert proposed > 0 and accepted == proposed


def test_self_draft_accepts_everything(target_dir):
    """Draft == target: every proposal verifies, so each round emits
    K+1 tokens and acceptance is 100%."""
    ref, _, _ = asyncio.run(_serve(target_dir, PROMPTS[:1]))
    got, proposed, accepted = asyncio.run(
        _serve(target_dir, PROMPTS[:1], draft=target_dir)
    )
    assert got == ref
    assert proposed > 0 and accepted == proposed


def test_draft_with_prefix_cache_hit(target_dir, draft_dir):
    """A second identical prompt prefix-hits the target's cache; the
    draft mirror shares block ids, so its context is valid too and the
    stream stays exact."""
    prompts = [PROMPTS[0], PROMPTS[0]]
    ref, _, _ = asyncio.run(_serve(target_dir, prompts))
    got, _, _ = asyncio.run(_serve(target_dir, prompts, draft=draft_dir))
    assert got == ref
    assert got[0] == got[1]


def test_draft_config_validation(target_dir, draft_dir):
    mcfg = ModelConfig.from_model_dir(target_dir)
    with pytest.raises(ValueError, match="2..16"):
        EngineConfig(model=mcfg, spec_draft_model=draft_dir,
                     spec_draft_tokens=1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        EngineConfig(model=mcfg, spec_draft_model=draft_dir,
                     spec_draft_tokens=4, spec_ngram_tokens=4)
    with pytest.raises(ValueError, match="host KV tier"):
        EngineConfig(model=mcfg, spec_draft_model=draft_dir,
                     spec_draft_tokens=4, host_kv_blocks=8)

    with pytest.raises(ValueError, match="without spec_draft_model"):
        EngineConfig(model=mcfg, spec_draft_tokens=4)

    # the draft must cover the target's serving horizon
    too_long = EngineConfig(model=mcfg, max_model_len=4096,
                            spec_draft_model=draft_dir, spec_draft_tokens=4)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        build_draft_config(too_long)

    cfg = EngineConfig(model=mcfg, max_model_len=128,
                       spec_draft_model=draft_dir, spec_draft_tokens=4)
    dcfg = build_draft_config(cfg)
    assert dcfg.model.vocab_size >= mcfg.vocab_size
    assert dcfg.multi_step_decode == 5  # K+1 burst
    assert dcfg.spec_draft_model is None


def test_draft_composes_with_fp8_cache_and_tp(target_dir, draft_dir):
    """Draft speculation atop an fp8 KV cache and a tp-sharded target
    (the draft inherits the cache dtype; it always runs unsharded):
    stream equals the plain engine with the SAME cache dtype."""

    async def serve(draft, kv_dtype, tp):
        econfig = EngineConfig(
            model=ModelConfig.from_model_dir(target_dir),
            max_batch_size=2, max_model_len=128, kv_block_size=8,
            num_kv_blocks=64, dtype="float32", prefill_buckets=[32],
            kv_cache_dtype=kv_dtype, tp_size=tp,
            spec_draft_model=draft, spec_draft_tokens=4 if draft else 0,
        )
        mdc = ModelDeploymentCard.from_local_path(target_dir)
        engine = await JaxServingEngine.create(
            mdc, engine_config=econfig, warmup=False)
        req = PreprocessedRequest(
            token_ids=PROMPTS[0],
            stop_conditions=StopConditions(max_tokens=10, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for out in engine.generate(Context(req)):
            toks.extend(out["token_ids"])
        await engine.close()
        return toks

    ref = asyncio.run(serve(None, "fp8", 1))
    got = asyncio.run(serve(draft_dir, "fp8", 1))
    assert got == ref

    ref_tp = asyncio.run(serve(None, "auto", 2))
    got_tp = asyncio.run(serve(draft_dir, "auto", 2))
    assert got_tp == ref_tp


def test_draft_engine_mixed_traffic_soak(target_dir, draft_dir):
    """Concurrent greedy (spec-eligible), sampled, guided, and logprobs
    requests on a draft-enabled engine: the batch oscillates between the
    speculative and plain paths (which mirror on the draft), and every
    stream must finish with the greedy ones matching a plain engine."""

    async def run(draft):
        econfig = EngineConfig(
            model=ModelConfig.from_model_dir(target_dir),
            max_batch_size=4, max_model_len=128, kv_block_size=8,
            num_kv_blocks=96, dtype="float32", prefill_buckets=[32],
            spec_draft_model=draft, spec_draft_tokens=4 if draft else 0,
        )
        mdc = ModelDeploymentCard.from_local_path(target_dir)
        engine = await JaxServingEngine.create(
            mdc, engine_config=econfig, warmup=False)

        def req(prompt, guided=None, logprobs=None, **kw):
            return PreprocessedRequest(
                token_ids=prompt,
                stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
                sampling_options=SamplingOptions(
                    guided_choice_token_ids=guided, **kw),
                output_options=OutputOptions(logprobs=logprobs),
            )

        async def collect(r):
            toks = []
            async for out in engine.generate(Context(r)):
                toks.extend(out["token_ids"])
            return toks

        reqs = [
            req(PROMPTS[0], temperature=0.0),                      # greedy
            req(PROMPTS[1], temperature=1.0, seed=3),              # sampled
            req([1, 9, 9, 2], temperature=0.0,
                guided=[[5, 9, 7], [40, 41]]),                     # guided
            req([1, 40, 41, 7], temperature=0.0, logprobs=2),      # greedy+lps
        ]
        outs = await asyncio.gather(*(collect(r) for r in reqs))
        await engine.close()
        return outs

    plain = asyncio.run(run(None))
    drafted = asyncio.run(run(draft_dir))
    # every row is deterministic given its per-request PRNG key and
    # counters (sampling state is per-slot, independent of engine path),
    # so ALL four streams must match the draft-less engine exactly
    assert drafted == plain
    assert drafted[2] in ([5, 9, 7], [40, 41])
    assert all(len(t) > 0 for t in drafted)
