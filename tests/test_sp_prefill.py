"""Sequence-parallel long-context prefill (EngineConfig.sp_size,
docs/long_context.md).

The acceptance contract: a prompt routed through the mesh-sharded SP
chunk ladder produces a decode stream byte-identical to the dense
single-device ladder (same checkpoint, same seeds), the first decode
burst dispatches BEFORE the final chunk's outputs are host-synced (the
early decode handoff), and a request cancelled mid-SP-prefill leaks
zero blocks.
"""

import asyncio
import json
import os

import numpy as np
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
from dynamo_tpu.telemetry.flight import FlightRecorder

from fixtures import make_model_dir

TINY = dict(
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=512,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
)


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    d = make_model_dir(tmp_path_factory.mktemp("spmodel"), name="tiny-sp")
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


def _config(model_dir, sp=1, **kw):
    cfg = ModelConfig.from_model_dir(model_dir)
    kw.setdefault("max_prefill_tokens_per_step", 32)
    if sp > 1:
        kw.setdefault("long_prefill_threshold_tokens", 48)
        # sp bucket = largest bucket <= sp * budget = 256 → one chunk
        # would swallow the whole prompt; cap the budget so the ladder
        # genuinely chunks (bucket 128, prompt ~200 → 2+ chunks)
        kw["max_prefill_tokens_per_step"] = 16
    kw.setdefault("max_model_len", 384)
    kw.setdefault("num_kv_blocks", 160)
    return EngineConfig(
        model=cfg, max_batch_size=4, kv_block_size=8,
        dtype="float32", sp_size=sp, **kw,
    )


async def _collect(engine, token_ids, sampling, max_tokens=16,
                   ignore_eos=True):
    req = PreprocessedRequest(
        token_ids=list(token_ids),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, ignore_eos=ignore_eos,
        ),
        sampling_options=sampling,
    )
    toks, finish = [], None
    async for out in engine.generate(Context(req)):
        toks.extend(out["token_ids"])
        if out.get("finish_reason"):
            finish = out["finish_reason"]
    return toks, finish


def _prompt(n, seed=3):
    return [1] + [
        int(t) for t in
        np.random.default_rng(seed).integers(3, 500, n - 1)
    ]


async def _make_engine(model_dir, sp, flight=None, **kw):
    mdc = ModelDeploymentCard.from_local_path(model_dir)
    cfg = _config(model_dir, sp=sp, **kw)
    engine = await JaxServingEngine.create(
        mdc, engine_config=cfg, warmup=False,
    )
    if flight is not None:
        engine.scheduler.flight = flight
    return engine


def test_sp_stream_matches_dense_ladder(model_dir):
    """The CPU-mesh differential: SP chunked prefill ≡ dense chunked
    prefill (greedy AND seeded sampling), with the decode stream
    byte-identical and zero leaked blocks on both engines."""

    async def go(sp):
        engine = await _make_engine(model_dir, sp)
        long_p = _prompt(200)
        results = [
            await _collect(engine, long_p, SamplingOptions(temperature=0.0)),
            await _collect(engine, long_p,
                           SamplingOptions(temperature=0.8, seed=11)),
            # short prompt: stays on the dense ladder on BOTH engines
            await _collect(engine, _prompt(20),
                           SamplingOptions(temperature=0.0)),
        ]
        chunks = sum(engine.scheduler._sp_chunks_c.values.values())
        used = engine.scheduler.allocator.used
        await engine.close()
        return results, chunks, used

    dense, d_chunks, d_used = asyncio.run(go(1))
    spres, s_chunks, s_used = asyncio.run(go(8))
    assert dense == spres
    assert d_chunks == 0          # no SP program on the dense engine
    assert s_chunks >= 2          # the long prompt genuinely chunked
    assert d_used == 0 and s_used == 0
    # the streams are real generations, not empty
    assert len(spres[0][0]) == 16


def test_sp_pallas_kernel_route_stream_matches_xla(model_dir, monkeypatch):
    """The whole-engine kernel-campaign differential: an SP engine
    serving on the Pallas route (interpret mode on CPU — the paged
    prefix-walk kernel inside sp_chunk_attention) must emit the same
    decode stream as the XLA-route engine, greedy and seeded."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    from dynamo_tpu.llm.model_card import ModelDeploymentCard as MDC
    from dynamo_tpu.ops import attention as attn

    def routed(route):
        return sum(
            v for k, v in attn.ATTENTION_ROUTE_COUNTER.values.items()
            if dict(k).get("route") == route
        )

    async def go(impl):
        mdc = MDC.from_local_path(model_dir)
        cfg = _config(model_dir, sp=8)
        cfg.model.attention_impl = impl
        engine = await JaxServingEngine.create(
            mdc, engine_config=cfg, warmup=False,
        )
        long_p = _prompt(200)
        res = [
            await _collect(engine, long_p,
                           SamplingOptions(temperature=0.0), max_tokens=8),
            await _collect(engine, long_p,
                           SamplingOptions(temperature=0.8, seed=11),
                           max_tokens=8),
        ]
        chunks = sum(engine.scheduler._sp_chunks_c.values.values())
        used = engine.scheduler.allocator.used
        await engine.close()
        return res, chunks, used

    base_kernel = routed("sp_ring_kernel")
    xla_res, x_chunks, x_used = asyncio.run(go("xla"))
    assert routed("sp_ring_kernel") == base_kernel
    pal_res, p_chunks, p_used = asyncio.run(go("pallas"))
    assert routed("sp_ring_kernel") > base_kernel
    assert xla_res == pal_res
    assert x_chunks >= 2 and p_chunks >= 2
    assert x_used == 0 and p_used == 0
    assert len(pal_res[0][0]) == 8


def test_sp_early_handoff_overlaps_final_drain(model_dir):
    """The early decode handoff: the first decode burst dispatches off
    the DEVICE-resident first token, before the final SP chunk's
    outputs are host-synced — pinned two ways: the runner receives a
    non-numpy (device) tokens0, and the flight ring shows sp_handoff
    recorded before sp_drain."""
    flight = FlightRecorder(capacity=256)

    async def go():
        engine = await _make_engine(model_dir, 8, flight=flight)
        runner = engine.runner
        seen = {}
        orig = runner.decode_burst

        def spy(tokens0, *a, **kw):
            seen.setdefault("tokens0_type", type(tokens0))
            return orig(tokens0, *a, **kw)

        runner.decode_burst = spy
        toks, _ = await _collect(
            engine, _prompt(200), SamplingOptions(temperature=0.0))
        await engine.close()
        return toks, seen

    toks, seen = asyncio.run(go())
    assert len(toks) == 16
    # tokens0 arrived as a device array — the first token was never
    # synced to the host before the burst dispatched
    assert seen["tokens0_type"] is not np.ndarray
    kinds = [e["kind"] for e in flight.snapshot()]
    assert "scheduler.sp_handoff" in kinds
    assert "scheduler.sp_drain" in kinds
    assert kinds.index("scheduler.sp_handoff") < kinds.index(
        "scheduler.sp_drain")
    # the ladder really ran multiple chunks before the handoff
    assert kinds.count("scheduler.sp_chunk") >= 2


def test_sp_cancel_mid_prefill_leaks_nothing(model_dir):
    """Conn-drop / cancellation mid-SP-prefill: the ladder drops the
    request, every block frees, and the engine keeps serving."""

    async def go():
        engine = await _make_engine(model_dir, 8)
        req = PreprocessedRequest(
            token_ids=_prompt(200),
            stop_conditions=StopConditions(max_tokens=16, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        ctx = Context(req)
        agen = engine.generate(ctx)
        task = asyncio.ensure_future(agen.__anext__())
        # let admission + the first chunk happen, then drop the client
        for _ in range(50):
            await asyncio.sleep(0.01)
            if engine.scheduler.sp_active is not None:
                break
        ctx.context.stop_generating()
        try:
            await asyncio.wait_for(task, timeout=30)
        except (StopAsyncIteration, asyncio.TimeoutError):
            pass
        await agen.aclose()
        # the scheduler reaps the cancel on its next passes
        for _ in range(100):
            await asyncio.sleep(0.01)
            if (engine.scheduler.allocator.used == 0
                    and engine.scheduler.sp_active is None):
                break
        used = engine.scheduler.allocator.used
        sp_active = engine.scheduler.sp_active
        # the engine still serves new work afterwards
        toks, _ = await _collect(
            engine, _prompt(60, seed=9), SamplingOptions(temperature=0.0))
        await engine.close()
        return used, sp_active, toks

    used, sp_active, toks = asyncio.run(go())
    assert used == 0
    assert sp_active is None
    assert len(toks) == 16


def test_sp_metrics_and_warmup(model_dir):
    """The prefill_sp program warms up front (no late compile on the
    first long prompt) and the catalog instruments move."""

    async def go():
        mdc = ModelDeploymentCard.from_local_path(model_dir)
        engine = await JaxServingEngine.create(
            mdc, engine_config=_config(model_dir, sp=8), warmup=True,
        )
        tracker = engine.runner.compiles
        assert any(p == "prefill_sp" for (p, _k) in tracker._seen)
        await _collect(engine, _prompt(200), SamplingOptions(temperature=0.0))
        text = engine.scheduler.registry.render()
        await engine.close()
        return text

    text = asyncio.run(go())
    assert "dynamo_engine_prefill_sp_chunks_total" in text
    assert "dynamo_engine_prefill_sp_axis_depth 8.0" in text
    assert "dynamo_engine_prefill_sp_exposed_seconds" in text
    # tokens counter moved by at least the long prompt's suffix
    for line in text.splitlines():
        if line.startswith("dynamo_engine_prefill_sp_tokens_total"):
            assert float(line.split()[-1]) >= 199
            break
    else:
        raise AssertionError("sp tokens counter missing")


@pytest.mark.slow
def test_sp_long_context_e2e(model_dir):
    """Genuinely long prompt (multiple hundreds of tokens, many chunks)
    — the slow-marked long-context e2e."""

    async def go(sp):
        engine = await _make_engine(
            model_dir, sp, max_model_len=448, num_kv_blocks=256)
        toks, fin = await _collect(
            engine, _prompt(400), SamplingOptions(temperature=0.0),
            max_tokens=24)
        await engine.close()
        return toks, fin

    assert asyncio.run(go(8)) == asyncio.run(go(1))


def test_embeddings_ride_the_prefill_path(model_dir):
    """/v1/embeddings engine half: the batched cacheless prefill trunk
    produces deterministic, batch-invariant, L2-normalized vectors with
    correct usage counts — and touches no KV blocks."""
    from dynamo_tpu.llm.embeddings import Embedder, EmbeddingError
    from dynamo_tpu.llm.tokenizer import HFTokenizer

    async def go():
        engine = await _make_engine(model_dir, 1)
        tok = HFTokenizer.from_model_path(model_dir)
        emb = Embedder(tok, engine,
                       max_model_len=engine.config.max_model_len,
                       vocab_size=engine.config.model.vocab_size)
        v1, n1 = await emb.embed("hello world")
        v2, n2 = await emb.embed(["hello world", "something else entirely"])
        used = engine.scheduler.allocator.used
        # invalid token ids reject at the door
        try:
            await emb.embed([[10_000_000]])
            bad = False
        except EmbeddingError:
            bad = True
        await engine.close()
        return v1, n1, v2, n2, used, bad

    v1, n1, v2, n2, used, bad = asyncio.run(go())
    assert used == 0            # no KV blocks were ever allocated
    assert bad
    assert n1 >= 1 and n2 > n1
    # batch row 0 == the single-input vector (same program family)
    np.testing.assert_allclose(v2[0], v1[0], rtol=1e-5, atol=1e-5)
    # unit norm, and distinct inputs embed distinctly
    assert abs(np.linalg.norm(v1[0]) - 1.0) < 1e-5
    assert not np.allclose(v2[0], v2[1])


def test_sp_backlog_honors_the_prefill_batch_cap(model_dir):
    """SP-routed admissions pre-allocate their whole prompt's blocks, so
    the sp backlog is bounded by max_prefill_batch — oversize backlogs
    wait block-free in `waiting`, exactly like the dense path."""

    async def go():
        engine = await _make_engine(model_dir, 8, max_prefill_batch=2)
        sched = engine.scheduler
        tasks = []
        for i in range(4):
            req = PreprocessedRequest(
                token_ids=_prompt(180, seed=20 + i),
                stop_conditions=StopConditions(max_tokens=4,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0),
            )

            async def consume(r=req):
                toks = []
                async for out in engine.generate(Context(r)):
                    toks.extend(out["token_ids"])
                return toks

            tasks.append(asyncio.ensure_future(consume()))
        max_backlog = 0
        while not all(t.done() for t in tasks):
            backlog = len(sched.sp_queue) + (
                1 if sched.sp_active is not None else 0)
            max_backlog = max(max_backlog, backlog)
            await asyncio.sleep(0.005)
        results = [await t for t in tasks]
        used = sched.allocator.used
        await engine.close()
        return max_backlog, results, used

    max_backlog, results, used = asyncio.run(go())
    assert max_backlog <= 2          # the cap held under a 4-prompt burst
    assert all(len(r) == 4 for r in results)  # everyone still completed
    assert used == 0
