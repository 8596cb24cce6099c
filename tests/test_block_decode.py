"""A family whose decode unit is a block (models/sdar.py; models.BlockUnit):
the block mask on every attention route, the choice of what a pass
unmasks, and the scheduler's block pass (a block's tokens leave when it is
whole and it is kept by the pass that first denoises the next, finishes
inside a block, preemption between blocks, prefix sharing, the refusals),
through ``Scheduler``, ``JaxServingEngine`` and the HTTP service that
``cli/run`` builds. The trunk's logits against the plain reference are
``tests/test_sdar_reference.py``'s.
"""

import asyncio
import dataclasses
import hashlib
import json
import os
import sys
import threading
import urllib.error
import urllib.request
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.sampling import block_select
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.engine.serving import _refuse_for_block_unit
from dynamo_tpu.models import sdar
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.ops.pallas_attention import paged_flash_attention
from dynamo_tpu.ops.pallas_decode import paged_verify_attention
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext, EngineError
from dynamo_tpu.telemetry.flight import FlightRecorder

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from references import sdar as reference  # noqa: E402

MASK = 255
HF = {
    "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "max_position_embeddings": 512,
    "tie_word_embeddings": False, "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "sliding_window": None,
    "use_sliding_window": False, "max_window_layers": 3,
    "block_length": 4, "mask_token_id": MASK, "denoising_steps": 2,
    "remasking_strategy": "sequential", "confidence_threshold": 0.9,
}
PAGE = 16
F32_ATOL = 1e-4


def _hf(**over):
    return {**HF, **over}


def _engine_config(hf=HF, **over):
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf),
                              attention_impl="xla")
    return EngineConfig(**{**dict(
        model=cfg, max_batch_size=4, max_model_len=256, kv_block_size=PAGE,
        num_kv_blocks=64, prefill_buckets=[32, 64], dtype="float32", seed=3),
        **over})


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, n).tolist() for n in lengths]


# ---------- (b) the block mask on every attention route ----------

def _dense(q, k, v, q_pos, ctx, block_len):
    """[S, H, D] queries at ``q_pos`` over ``ctx`` keys [T, KVH, D]: the
    mask written out, j < (p // B + 1) * B."""
    s, h, d = q.shape
    g = h // k.shape[1]
    j = np.arange(k.shape[0])
    mask = (j[None, :] < (q_pos[:, None] // block_len + 1) * block_len) & (
        j[None, :] < ctx)
    kk, vv = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    logits = np.einsum("shd,thd->hst", q, kk) * d ** -0.5
    logits = np.where(mask[None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hst,thd->shd", p, vv)


def _paged(rng, ctx_lens, kvh=2, d=128, pages=32):
    """A cache of ``pages`` pages whose rows hold ``ctx_lens`` keys in
    pages handed out in a scrambled order."""
    b = len(ctx_lens)
    w = max(-(-c // PAGE) for c in ctx_lens)
    k_cache = rng.standard_normal((pages, PAGE, kvh, d)).astype(np.float32)
    v_cache = rng.standard_normal((pages, PAGE, kvh, d)).astype(np.float32)
    order = rng.permutation(np.arange(1, pages))
    bt = np.zeros((b, w), np.int32)
    for i in range(b):
        bt[i] = order[i * w:(i + 1) * w]
    dense = [(k_cache[bt[i]].reshape(w * PAGE, kvh, d),
              v_cache[bt[i]].reshape(w * PAGE, kvh, d)) for i in range(b)]
    return k_cache, v_cache, bt, dense


@pytest.mark.parametrize("block_len", [2, 4, 8])
@pytest.mark.parametrize("route", ["xla", "verify", "flash", "prefill"])
def test_block_mask_on_every_route_equals_the_dense_mask(route, block_len):
    """Contexts that end mid-page; a block pass's queries are the last
    ``block_len`` positions of the context, a prefill chunk's 32 start at
    a block's edge."""
    rng = np.random.default_rng(block_len)
    h, kvh, d = 4, 2, 128
    s = 32 if route in ("flash", "prefill") else block_len
    ctx_lens = np.asarray([40 + s, 16 + s, 88 + s], np.int32)
    ctx_lens -= ctx_lens % block_len          # whole blocks, mid-page
    base = ctx_lens - s
    q = rng.standard_normal((3, s, h, d)).astype(np.float32)
    q_pos = base[:, None] + np.arange(s)[None, :]
    if route == "prefill":
        k = rng.standard_normal((3, s, kvh, d)).astype(np.float32)
        v = rng.standard_normal((3, s, kvh, d)).astype(np.float32)
        valid = np.asarray([s, s - block_len, s], np.int32)
        got = attn_ops.prefill_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(valid), block_len=block_len)
        for i in range(3):
            want = _dense(q[i], k[i], v[i], np.arange(s), valid[i], block_len)
            np.testing.assert_allclose(np.asarray(got[i])[:valid[i]],
                                       want[:valid[i]], atol=2e-5)
        return
    k_cache, v_cache, bt, dense = _paged(rng, ctx_lens, kvh, d)
    args = [jnp.asarray(x) for x in (q, k_cache, v_cache, bt)]
    if route == "xla":
        got = attn_ops.paged_attention(
            *args, jnp.asarray(q_pos), jnp.asarray(ctx_lens),
            block_len=block_len)
    elif route == "verify":
        got = paged_verify_attention(
            *args, jnp.asarray(base), jnp.asarray(ctx_lens),
            block_len=block_len, interpret=True)
    else:
        got = paged_flash_attention(
            *args, jnp.asarray(base), jnp.asarray(ctx_lens),
            block_len=block_len, q_chunk=16, interpret=True)
    for i in range(3):
        want = _dense(q[i], *dense[i], q_pos[i], ctx_lens[i], block_len)
        np.testing.assert_allclose(np.asarray(got[i]), want, atol=2e-5)
        causal = _dense(q[i], *dense[i], q_pos[i], ctx_lens[i], 1)
        assert np.abs(causal - want).max() > 1e-3     # the mask bites


@pytest.mark.parametrize("block_len", [2, 4, 8])
@pytest.mark.parametrize("route", ["xla", "verify", "flash", "attention"])
def test_two_blocks_a_row_straddle_a_page_under_the_dense_mask(route, block_len):
    """A block pass is ``S = 2 · block_len`` consecutive queries a row: a
    whole block that ends at a page's edge (48) and the block behind it
    on the next page, a pair inside one page, and a row whose second half
    is dead (``context_lens`` ends with its first: the live half's queries
    are held to the dense mask, the dead half's are nobody's). ``attention``
    is the dispatch a trunk calls (its verify route, interpreted)."""
    rng = np.random.default_rng(100 + block_len)
    h, kvh, d = 4, 2, 128
    s = 2 * block_len
    base = np.asarray([48 - block_len, 16, 80 - block_len], np.int32)
    ctx_lens = base + np.asarray([s, s, block_len], np.int32)
    q = rng.standard_normal((3, s, h, d)).astype(np.float32)
    q_pos = base[:, None] + np.arange(s)[None, :]
    k_cache, v_cache, bt, dense = _paged(
        rng, np.maximum(ctx_lens, base + s), kvh, d)
    args = [jnp.asarray(x) for x in (q, k_cache, v_cache, bt)]
    if route == "xla":
        got = attn_ops.paged_attention(
            *args, jnp.asarray(q_pos), jnp.asarray(ctx_lens),
            block_len=block_len)
    elif route == "verify":
        got = paged_verify_attention(
            *args, jnp.asarray(base), jnp.asarray(ctx_lens),
            block_len=block_len, interpret=True)
    elif route == "flash":
        got = paged_flash_attention(
            *args, jnp.asarray(base), jnp.asarray(ctx_lens),
            block_len=block_len, q_chunk=s, interpret=True)
    else:
        got = attn_ops.attention(
            *args, jnp.asarray(q_pos), jnp.asarray(ctx_lens), impl="pallas",
            interpret=True, block_len=block_len)
    for i, live in enumerate((s, s, block_len)):
        want = _dense(q[i], *dense[i], q_pos[i], ctx_lens[i], block_len)
        np.testing.assert_allclose(np.asarray(got[i])[:live], want[:live],
                                   atol=2e-5)
        causal = _dense(q[i], *dense[i], q_pos[i], ctx_lens[i], 1)
        assert np.abs(causal - want)[:live].max() > 1e-3   # the mask bites
    # the second block of a pair sees the first's keys, all of them
    first_only = _dense(q[0], *dense[0], q_pos[0], ctx_lens[0] - block_len,
                        block_len)
    assert np.abs(first_only - np.asarray(got[0]))[block_len:].max() > 1e-3


def _route_programs(block_len=None):
    """The lowered text of the four routes at one small shape; ``None``
    leaves the argument out (the call every other family makes)."""
    kw = {} if block_len is None else {"block_len": block_len}
    f32, i32 = jnp.float32, jnp.int32
    cache = jax.ShapeDtypeStruct((8, PAGE, 2, 128), f32)
    bt = jax.ShapeDtypeStruct((2, 4), i32)
    vec = jax.ShapeDtypeStruct((2,), i32)

    def q(s):
        return jax.ShapeDtypeStruct((2, s, 4, 128), f32)

    return {
        "xla": jax.jit(lambda *a: attn_ops.paged_attention(*a, **kw)).lower(
            q(4), cache, cache, bt, jax.ShapeDtypeStruct((2, 4), i32), vec),
        "verify": jax.jit(lambda *a: paged_verify_attention(
            *a, interpret=True, **kw)).lower(q(4), cache, cache, bt, vec, vec),
        "flash": jax.jit(lambda *a: paged_flash_attention(
            *a, interpret=True, **kw)).lower(q(64), cache, cache, bt, vec, vec),
        "prefill": jax.jit(lambda *a: attn_ops.prefill_attention(
            *a, **kw)).lower(q(16), jax.ShapeDtypeStruct((2, 16, 2, 128), f32),
                             jax.ShapeDtypeStruct((2, 16, 2, 128), f32), vec),
    }


# sha256 of the programs above as commit 855e2e2 (the parent of the PR
# that brought block_len) lowers them, this file's ``_route_programs()``
# run there: a change to a kernel changes them, and is then to say so.
# The verify kernel's is its own since PR 49 took its walk through
# ``_walk`` (e45060255abd846b before): the text at block length 1 as that
# commit lowers it, held so that a block length of 1 stays that program
PARENT_PROGRAMS = {
    "xla": "cf711ae27d413515",
    "verify": "ee1e85da2d77dab1",
    "flash": "e922bcb742055b1e",
    "prefill": "979fae04dd6263e9",
}


def _sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("route", ["xla", "verify", "flash", "prefill"])
def test_block_length_one_is_the_program_it_always_was(route):
    """With block length 1 (every other family) a route's lowered program
    is the text it lowers to with the argument left out, which is the
    text the parent commit lowers; with a block it is another."""
    plain, one, four = (_route_programs(b)[route] for b in (None, 1, 4))
    assert one.as_text() == plain.as_text()
    assert _sha(plain) == PARENT_PROGRAMS[route]
    assert four.as_text() != plain.as_text()


def test_one_query_a_row_never_carries_a_block():
    cache = jnp.zeros((8, PAGE, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="block_len=4 with one query"):
        attn_ops.attention(
            jnp.zeros((2, 1, 4, 128)), cache, cache, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), jnp.int32),
            impl="xla", block_len=4)


# ---------- (c) the choice of what a pass unmasks ----------

def _numpy_select(ids, sampled, conf, quota, strategy, threshold):
    """The three published rules, one row at a time."""
    new = ids.copy()
    for r in range(ids.shape[0]):
        masked = [o for o in range(ids.shape[1]) if ids[r, o] == MASK]
        n = int(quota[r])
        by_conf = sorted(masked, key=lambda o: (-conf[r, o], o))
        if strategy == "sequential":
            take = masked[:n]
        elif strategy == "low_confidence_static":
            take = by_conf[:n]
        else:
            over = [o for o in masked if conf[r, o] > threshold]
            take = over if len(over) >= n else by_conf[:n]
        for o in take:
            new[r, o] = sampled[r, o]
    return new


@pytest.mark.parametrize("length", [4, 8])
@pytest.mark.parametrize("strategy", models.REMASKING)
def test_the_choice_equals_a_numpy_port_of_the_published_rules(strategy, length):
    rng = np.random.default_rng(length)
    rows = 64
    ids = np.where(rng.random((rows, length)) < 0.6, MASK,
                   rng.integers(0, 200, (rows, length))).astype(np.int32)
    ids[0], ids[1] = MASK, 7                   # all masked; no mask, quota 0
    sampled = rng.integers(0, 200, (rows, length)).astype(np.int32)
    # confidences on both sides of the threshold, with exact ties
    conf = np.round(rng.random((rows, length)), 1).astype(np.float32)
    conf[2] = 0.95                             # every one over: all are taken
    conf[3] = 0.5                              # none over: the fallback
    quota = np.minimum((ids == MASK).sum(-1), rng.integers(1, 4, rows))
    quota[1] = 0
    unit = models.BlockUnit(length=length, mask_id=MASK, steps=2,
                            strategy=strategy, threshold=0.9)
    new, taken, left = jax.jit(
        lambda *a: block_select(*a, unit))(
        jnp.asarray(ids), jnp.asarray(sampled), jnp.log(jnp.asarray(conf)),
        jnp.asarray(quota.astype(np.int32)))
    want = _numpy_select(ids, sampled, conf, quota, strategy, 0.9)
    np.testing.assert_array_equal(np.asarray(new), want)
    np.testing.assert_array_equal(np.asarray(taken), want != ids)
    np.testing.assert_array_equal(np.asarray(left), (want == MASK).sum(-1))
    if strategy == "low_confidence_dynamic":
        assert (np.asarray(new)[2] != MASK).all()      # over the threshold
        assert np.asarray(taken)[3].sum() == quota[3]  # the fallback
    else:
        np.testing.assert_array_equal(np.asarray(taken).sum(-1), quota)


@pytest.mark.parametrize("length,steps,want", [
    (4, 1, (4,)), (4, 2, (2, 2)), (4, 3, (2, 1, 1)), (4, 4, (1, 1, 1, 1)),
    (8, 3, (3, 3, 2))])
def test_quotas_give_the_remainder_to_the_first_passes(length, steps, want):
    unit = models.BlockUnit(length, MASK, steps, "sequential", 0.9)
    assert unit.quotas() == want == tuple(reference.quotas(length, steps))


# ---------- the scheduler's block pass ----------

def _request(prompt, max_tokens, logprobs=0, sampling=None, **stops):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(
            max_tokens=max_tokens, **{"ignore_eos": True, **stops}),
        sampling_options=SamplingOptions(**(sampling or {"temperature": 0.0})),
        output_options=OutputOptions(logprobs=logprobs),
        eos_token_ids=stops.pop("eos", []),
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


def _drive(runner, config, requests, hook=None, staggered=False,
           leaves=None):
    """(scheduler, [(tokens, log-probabilities, tokens a chunk, finish
    reason)] a request, {request id: the pass that unmasked each emitted
    token}). ``leaves``: {request's index: its client leaves once it has
    that many tokens}."""
    async def go():
        flight = FlightRecorder(capacity=4096)
        sched = Scheduler(runner, config, flight=flight)
        if hook is not None:
            hook(sched)
        sched.start()

        limit = {id(requests[i]): n for i, n in (leaves or {}).items()}

        async def collect(er):
            toks, lps, chunks, finish = [], [], [], None
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks, lps, chunks, finish
                toks.extend(out.token_ids)
                if out.token_ids:
                    chunks.append(len(out.token_ids))
                lps.extend(lp.logprob for lp in out.logprobs or [])
                finish = out.finish_reason or finish
                if len(toks) >= limit.get(id(er), 1e9):
                    er.ctx.stop_generating()
        try:
            got = []
            if staggered:
                for er in requests:
                    sched.add_request(er)
                    got.append(await collect(er))
            else:
                for er in requests:
                    sched.add_request(er)
                got = await asyncio.gather(*(collect(er) for er in requests))
            return sched, got, flight
        finally:
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        sched, got, flight = loop.run_until_complete(go())
    finally:
        loop.close()
    passes = {}
    for ev in flight.snapshot():
        if ev["kind"] == "scheduler.block_commit":
            passes.setdefault(ev["request_id"], []).extend(ev["data"]["passes"])
    return sched, got, passes


def _rows(sched):
    return {ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in sched.registry.render().splitlines()
            if ln.startswith("dynamo_") and " " in ln}


_RUNNERS = {}


def _runner(steps=2, strategy="sequential", **over):
    key = (steps, strategy, tuple(sorted(over.items())))
    if key not in _RUNNERS:
        _RUNNERS[key] = ModelRunner(_engine_config(
            _hf(denoising_steps=steps, remasking_strategy=strategy), **over))
    return _RUNNERS[key]


KINDS = 'dynamo_scheduler_block_row_passes_total{kind="%s"}'
FOLDED = "dynamo_scheduler_block_keeps_folded_total"   # no line until it counts


@pytest.mark.parametrize("steps,strategy", [
    (1, "sequential"), (2, "sequential"), (4, "sequential"),
    (1, "low_confidence_static"), (2, "low_confidence_static"),
    (4, "low_confidence_static"), (1, "low_confidence_dynamic"),
    (2, "low_confidence_dynamic"), (3, "low_confidence_dynamic"),
    (4, "low_confidence_dynamic")])
def test_engine_passes_and_log_probabilities_equal_the_procedure(steps, strategy):
    """(a) Through the scheduler and ``jit_decode_block``, four prompts
    with tails of 1, 2, 3 and 0 tokens, greedy: the tokens, the pass that
    unmasked each and the log-probability it was taken under are the
    plain loop's (``references/sdar.generate``: one forward a state, no
    state shared between two blocks). The engine's passes a block are
    ``steps`` under both static rules, not ``steps`` + 1, and within
    [1, B] under the dynamic one: no pass only keeps a block, every block
    but a request's last is kept by the first pass of the next; a block
    leaves whole."""
    runner = _runner(steps, strategy)
    hf = _hf(denoising_steps=steps, remasking_strategy=strategy)
    prompts = _prompts((37, 50, 3, 64))
    requests = [_request(p, 13) for p in prompts]
    sched, got, passes = _drive(runner, runner.config, requests)
    for er, prompt, (toks, lps, chunks, _) in zip(requests, prompts, got):
        want_toks, want_lps, want_passes = reference.generate(
            hf, runner.params, prompt, 13)
        assert toks == want_toks
        np.testing.assert_allclose(lps, want_lps, atol=F32_ATOL)
        assert passes[er.request_id] == want_passes
        tail = len(prompt) % 4
        assert chunks == [4 - tail, 4, 4, 13 - 12 + tail][:len(chunks)]
        assert sum(chunks) == 13
    rows = _rows(sched)
    blocks = rows["dynamo_scheduler_blocks_completed_total"]
    denoise = rows[KINDS % "denoise"]
    assert blocks == 16 and KINDS % "commit" not in rows
    assert rows.get(FOLDED, 0) == blocks - 4
    assert rows["dynamo_scheduler_block_tokens_emitted_total"] == 4 * 13
    assert rows["dynamo_engine_block_denoise_length_count"] == blocks
    assert rows["dynamo_engine_block_denoise_length_sum"] == denoise
    if strategy == "low_confidence_dynamic":
        assert blocks <= denoise <= 4 * blocks
    else:
        # a prompt's tail opens the first block: fewer masks there, and
        # a pass takes its quota or what is left (tails 1, 2, 3 and 0)
        quotas = models.BlockUnit(4, MASK, steps, strategy, 0.9).quotas()
        first = [sum(1 for t in range(steps) if sum(quotas[:t]) < 4 - tail)
                 for tail in (1, 2, 3, 0)]
        assert denoise == steps * (blocks - 4) + sum(first)
    # a chunk of k tokens is k gaps of a k-th each: never one gap of 0
    assert rows["dynamo_scheduler_inter_token_latency_seconds_count"] == (
        4 * 13 - sum(g[2][0] for g in got))


@pytest.mark.parametrize("length", [1, 2, 3, 4, 5, 17])
def test_a_short_prompt_and_a_tail_open_the_first_block(length):
    """(e) A prompt shorter than one block has no kept position: its
    first block sits at position 0 with the prompt as its opening ids,
    nothing is prefilled, and the row's positions start at 0. One of
    exactly a block prefills it and opens a block of masks; 5 and 17
    prefill whole blocks and open the first with a tail of one."""
    runner = _runner()
    prompt = _prompts((length,), seed=11)[0]
    er = _request(prompt, 10)
    sched, [(toks, lps, chunks, finish)], passes = _drive(
        runner, runner.config, [er])
    want_toks, want_lps, want_passes = reference.generate(
        HF, runner.params, prompt, 10)
    assert toks == want_toks and passes[er.request_id] == want_passes
    np.testing.assert_allclose(lps, want_lps, atol=F32_ATOL)
    tail = length % 4
    assert chunks[0] == 4 - tail and str(finish.value) == "length"
    rows = _rows(sched)
    blocks = -(-(tail + 10) // 4)
    assert rows["dynamo_scheduler_blocks_completed_total"] == blocks
    assert rows.get(FOLDED, 0) == blocks - 1
    # two passes a block, one where the tail leaves at most two masks
    assert rows[KINDS % "denoise"] == 2 * blocks - (tail >= 2)
    assert sched.allocator.used == 0


def test_the_last_block_under_max_model_len_leaves_whole():
    """A row whose next block would pass ``max_model_len`` finishes with
    ``length`` when the block that still fits is whole, all of it sent:
    positions [252, 256) of 256."""
    runner = _runner()
    prompt = _prompts((238,), seed=12)[0]
    sched, [(toks, lps, chunks, finish)], _ = _drive(
        runner, runner.config, [_request(prompt, 40)])
    want_toks, want_lps, _ = reference.generate(HF, runner.params, prompt, 18)
    assert toks == want_toks and chunks == [2, 4, 4, 4, 4]
    np.testing.assert_allclose(lps, want_lps, atol=F32_ATOL)
    assert str(finish.value) == "length" and sched.allocator.used == 0


@pytest.mark.parametrize("max_tokens", [1, 6, 9])
def test_max_tokens_inside_a_block_drops_the_rest_of_it(max_tokens):
    """(b) ``max_tokens`` that is no multiple of the block: the tokens
    leave with the pass that made the block whole and no pass follows for
    the row (two passes a block, the last block never kept)."""
    runner = _runner()
    prompts = _prompts((37, 64))
    sched, got, _ = _drive(runner, runner.config,
                           [_request(p, max_tokens) for p in prompts])
    for prompt, (toks, lps, chunks, finish) in zip(prompts, got):
        want = reference.generate(HF, runner.params, prompt, max_tokens)[0]
        assert toks == want and len(lps) == max_tokens
        assert str(finish.value) == "length"
    assert sched.allocator.used == 0
    rows = _rows(sched)
    blocks = -(-(1 + max_tokens) // 4) + -(-max_tokens // 4)
    assert rows["dynamo_scheduler_blocks_completed_total"] == blocks
    assert rows[KINDS % "denoise"] == 2 * blocks and KINDS % "commit" not in rows
    assert rows.get(FOLDED, 0) == blocks - 2


@pytest.mark.parametrize("max_tokens,shared", [(16, 64), (17, 80), (20, 80)])
def test_a_page_of_unkept_keys_is_never_registered(max_tokens, shared):
    """(b) A prompt of four pages whose answer's fourth block ends the
    fifth page at position 80. Where the request ends with that block the
    block is never kept and the page not registered: a later request over
    the same 80 tokens shares four pages and computes the fifth. Where the
    request goes on, the pass that first denoises the block at 80 keeps
    the one before it and the page is shared. Either way the later
    request's stream is the plain loop's."""
    runner = _runner()
    prompt = _prompts((64,), seed=8)[0]
    first = _request(prompt, max_tokens)
    stream = reference.generate(HF, runner.params, prompt, 20)[0]
    later = _request(prompt + stream[:16] + _prompts((5,), seed=9)[0], 8)
    sched, got, _ = _drive(runner, runner.config, [first, later],
                           staggered=True)
    assert got[0][0] == stream[:max_tokens]
    assert first.cached_tokens == 0 and later.cached_tokens == shared
    want_toks, want_lps, _ = reference.generate(
        HF, runner.params, later.prompt, 8)
    assert got[1][0] == want_toks
    np.testing.assert_allclose(got[1][1], want_lps, atol=F32_ATOL)


@pytest.mark.parametrize("how", ["eos", "stop_id", "stop_seq"])
@pytest.mark.parametrize("at", [1, 4, 8])
def test_a_finish_inside_a_block_drops_the_rest_of_it(how, at):
    """(b) EOS, a hidden stop id and a stop string's canonical tokens
    that end at offset ``at`` of the greedy stream (inside a block: the
    prompt's tail is 1, so blocks end at offsets 2, 6, 10). The tokens
    leave with the pass that made the block whole; no pass follows."""
    runner = _runner()
    prompt = _prompts((41,), seed=3)[0]
    stream = reference.generate(HF, runner.params, prompt, 13)[0]
    # the finishing token (pair) must not occur earlier in the stream
    assert stream[at] not in stream[:at]
    if how == "eos":
        er = _request(prompt, 13, ignore_eos=False)
        er.req.eos_token_ids = [stream[at]]
    elif how == "stop_id":
        er = _request(prompt, 13, stop_token_ids_hidden=[stream[at]])
    else:
        er = _request(prompt, 13, stop=["x"],
                      stop_token_seqs=[[stream[at - 1], stream[at]]])
    er.classify_finish()
    sched, [(toks, lps, chunks, finish)], _ = _drive(runner, runner.config, [er])
    assert toks == stream[:at + 1]
    assert str(finish.value) == ("eos" if how == "eos" else "stop")
    assert er.generated == at + 1 and sched.allocator.used == 0
    rows = _rows(sched)
    blocks = (at + 1) // 4 + 1
    assert rows["dynamo_scheduler_blocks_completed_total"] == blocks
    assert rows[KINDS % "denoise"] == 2 * blocks
    assert rows.get(FOLDED, 0) == blocks - 1


def test_a_preempted_row_resumes_between_blocks_with_the_same_stream():
    """(c) Fourteen pages for four rows that come to need twenty: rows go
    back to waiting between blocks, re-prefill prompt plus what they had
    emitted under the block mask, and every greedy stream is the
    unpreempted one's (the plain loop's). A row runs out of pages at the
    first pass of a block, when it holds the block before it whole, sent
    and not kept: those tokens go into ``resume_tokens`` with the kept
    ones."""
    runner = _runner()
    config = dataclasses.replace(runner.config, num_kv_blocks=14)
    prompts = _prompts((37, 50, 3, 64))
    preempted = []

    def hook(sched):
        preempt = sched._preempt

        def counted(er):
            assert er.pending_token == -1
            preempted.append((er.request_id, er.generated, len(er.unkept)))
            preempt(er)
            # between blocks: everything emitted so far is whole blocks,
            # the unkept one among them, and nothing sent is lost
            assert (len(er.prompt) + len(er.resume_tokens)) % 4 == 0
            assert len(er.resume_tokens) == er.generated
            assert er.unkept == [] and er.block == []
        sched._preempt = counted

    sched, got, _ = _drive(runner, config, [_request(p, 60) for p in prompts],
                           hook=hook)
    assert preempted and any(unkept == 4 for _, _, unkept in preempted)
    for prompt, (toks, lps, chunks, _) in zip(prompts, got):
        want_toks, want_lps, _ = reference.generate(HF, runner.params, prompt, 60)
        assert toks == want_toks
        np.testing.assert_allclose(lps, want_lps, atol=F32_ATOL)
    assert sched.allocator.used == 0
    assert _rows(sched)["dynamo_scheduler_preemptions_total"] == len(preempted)


def test_a_prefix_hit_and_a_miss_give_the_same_log_probabilities():
    """Prefix sharing stays on: a page of 16 is whole blocks, so its keys
    depend on nothing past its end. The same prompt twice, the second
    after the first has finished: its whole pages are found, and its
    stream and log-probabilities are the first's and the plain loop's."""
    runner = _runner()
    prompt = _prompts((70,), seed=5)[0]
    first, second = _request(prompt, 12), _request(prompt, 12)
    other = _request(prompt[:48] + _prompts((9,), seed=6)[0], 12)
    sched, got, _ = _drive(runner, runner.config, [first, second, other],
                           staggered=True)
    assert first.cached_tokens == 0
    assert second.cached_tokens == 64 and other.cached_tokens == 48
    assert got[0][0] == got[1][0]
    np.testing.assert_allclose(got[0][1], got[1][1], atol=1e-6)
    for er, (toks, lps, _, _) in zip((first, second, other), got):
        want_toks, want_lps, _ = reference.generate(
            HF, runner.params, er.prompt, 12)
        assert toks == want_toks
        np.testing.assert_allclose(lps, want_lps, atol=F32_ATOL)


def test_sampled_rows_draw_a_key_a_position_and_a_pass():
    """Temperature 1: two requests with one seed give one stream, another
    seed another; every token is inside the vocabulary and never the
    mask id."""
    runner = _runner()
    prompt = _prompts((21,))[0]

    def sampled(seed):
        return _request(prompt, 24, sampling=dict(
            temperature=1.0, top_p=0.9, seed=seed))

    _, got, _ = _drive(runner, runner.config,
                       [sampled(1), sampled(1), sampled(2)])
    assert got[0][0] == got[1][0] != got[2][0]
    assert all(0 <= t < 256 and t != MASK for g in got for t in g[0])


# ---------- the block pass one pass ahead of the host (ISSUE 60) ----------

def _held(sched):
    """Every pass is landed before the next is built, under a reason of
    the test's: the program has no switch."""
    sched._ahead_block_reason = lambda active, k_steps: "test"


def _total(counter, **labels):
    return sum(v for k, v in counter.values.items()
               if labels.items() <= dict(k).items())


def _reasons(sched):
    return {dict(k)["reason"] for k in sched._sync_fallback_ctr.values}


def _ahead_traffic(stop_on=None):
    """Six requests on four slots, so that a slot is taken again with a
    pass in flight: prompts' tails of 1, 2, 3, 0, 2 and 2 tokens, answers
    that end inside a block and on its edge, greedy and seeded rows, two
    top alternatives a token. ``stop_on``: {request: a hidden stop id}."""
    prompts = _prompts((37, 50, 3, 64, 18, 26), seed=4)
    tokens = (13, 24, 17, 30, 11, 21)
    sampling = (None, dict(temperature=0.8, seed=7), None,
                dict(temperature=0.7, seed=3, top_k=40), None, None)
    requests = []
    for i, (p, n, sm) in enumerate(zip(prompts, tokens, sampling)):
        stops = ({"stop_token_ids_hidden": [stop_on[i]]}
                 if i in (stop_on or {}) else {})
        requests.append(_request(p, n, logprobs=2, sampling=sm, **stops))
    return requests


def _usage(requests, got):
    """What a response's ``usage`` is counted from, a request."""
    return [(len(er.prompt), er.generated, er.decode_tokens, len(toks),
             er.cached_tokens) for er, (toks, *_) in zip(requests, got)]


def _both(runner, config, make, **kw):
    """``make()``'s requests with every pass landed before the next is
    built, then with the pass ahead: the two runs' (scheduler, streams,
    usage), the streams' finish reasons as their values."""
    out = []
    for hook in (_held, None):
        requests = make()
        sched, got, _ = _drive(runner, config, requests, hook=hook, **kw)
        got = [(t, l, c, getattr(f, "value", f)) for t, l, c, f in got]
        out.append((sched, got, _usage(requests, got)))
        assert sched.allocator.used == 0 and sched._ahead is None
    return out


@pytest.mark.parametrize("case", ["max_tokens", "stop", "tail", "cancel",
                                  "kv_oom"])
@pytest.mark.parametrize("strategy", ["sequential", "low_confidence_static"])
def test_the_pass_ahead_streams_what_the_landed_passes_stream(strategy, case):
    """Pass k+1 goes out before pass k is read, a row's block fed k's
    ``new_ids`` on the device: tokens, log-probabilities, the tokens a
    chunk, finish reasons and what ``usage`` is counted from are those
    of the same run with every pass landed first, bit for bit, under
    both rules whose pass unmasks the quota the host gave. The cases:
    answers that end by ``max_tokens`` inside a block (a row the host
    knows to end is left out of the next pass, nothing is dropped); a
    stop id inside a block (the row's next pass was dispatched and is
    dropped, never counted); first blocks that open with a prompt's tail
    while a pass is in flight; a client that leaves mid-stream; and a
    pool too small, where the pass that cannot have a page reads the
    pass in flight first (``kv_oom``) and preempts from committed
    state."""
    runner = _runner(2, strategy)
    config, make, kw = runner.config, _ahead_traffic, {}
    if case == "stop":
        # a stop id a stream: one it gives once, inside a block
        (_, free, _), _ = _both(runner, config, make)
        stop_on = {}
        for i, tail in ((0, 1), (2, 3), (4, 2)):
            toks = free[i][0]
            stop_on[i] = next(
                toks[j] for j in range(2, len(toks))
                if toks[j] not in toks[:j] and (tail + j) % 4 != 3)
        make = lambda: _ahead_traffic(stop_on)        # noqa: E731
    elif case == "tail":
        make = lambda: [_request(p, 19, logprobs=2) for p in _prompts(  # noqa: E731
            (5, 33, 18, 7, 35, 50, 3), seed=6)]
    elif case == "cancel":
        kw = {"leaves": {1: 8, 3: 4}}
    elif case == "kv_oom":
        config = dataclasses.replace(config, num_kv_blocks=14)
        make = lambda: [_request(p, 60, logprobs=2) for p in _prompts(  # noqa: E731
            (37, 50, 3, 64))]

    (held, want, want_usage), (sched, got, usage) = _both(
        runner, config, make, **kw)
    gone = sorted(kw.get("leaves", ()))
    for i, (w, g) in enumerate(zip(want, got)):
        if i in gone:
            # where a client's leaving is seen depends on the host's pace
            assert g[3] == w[3] == "cancelled"
            short, long = sorted((w[0], g[0]), key=len)
            assert long[:len(short)] == short and len(short) >= kw["leaves"][i]
        else:
            assert g == w, i
            assert usage[i] == want_usage[i], i
    passes = _total(sched._fetches_ctr, kind="decode")
    assert _total(held._ahead_ctr) == 0 and _reasons(held) == {"test"}
    assert _total(sched._ahead_ctr) >= 0.8 * passes > 10
    # the counters of a block family: a dropped row counts for nothing
    for name in ("_blocks_completed", "_block_tokens", "_block_keeps_folded",
                 "_block_row_passes"):
        if not gone:
            assert _total(getattr(sched, name)) == _total(getattr(held, name))
    dropped = _total(sched._ahead_discarded_ctr)
    if case in ("max_tokens", "tail"):
        assert [f for *_, f in got] == ["length"] * len(got)
        assert dropped == 0 and not _reasons(sched)
    elif case == "stop":
        for i, token in stop_on.items():
            assert got[i][3] == "stop" and got[i][0][-1] == token
            assert len(got[i][0]) < len(free[i][0])
        assert 1 <= dropped <= 3 and not _reasons(sched)
    elif case == "cancel":
        assert dropped >= 1
    else:
        assert _total(held._preemptions) > 0, "vacuous: nothing was preempted"
        assert "kv_oom" in _reasons(sched)
        assert _total(sched._preemptions) > 0


def test_the_dynamic_rule_reads_every_pass_before_the_next():
    """Under ``low_confidence_dynamic`` the count a pass unmasks depends
    on the confidences, which the host has not read: no pass goes ahead,
    every pass says ``dynamic_unmask``, and the streams are the plain
    loop's as ever."""
    runner = _runner(2, "low_confidence_dynamic")
    requests = _ahead_traffic()
    sched, got, _ = _drive(runner, runner.config, requests)
    assert [len(t) for t, *_ in got] == [13, 24, 17, 30, 11, 21]
    assert _total(sched._ahead_ctr) == 0 and sched._ahead is None
    assert _reasons(sched) == {"dynamic_unmask"}
    assert (_total(sched._sync_fallback_ctr, reason="dynamic_unmask")
            == _total(sched._fetches_ctr, kind="decode") > 10)


# ---------- (g) what assumes one token a row a pass is refused ----------

@pytest.mark.parametrize("setting,path", [
    (dict(spec_ngram_tokens=3), "spec_ngram_tokens"),
    (dict(spec_draft_model="/nowhere", spec_draft_tokens=4), "spec_draft_model"),
    (dict(sp_size=2), "sp_size"),
    (dict(pp_size=3), "pp_size"),
    (dict(tp_size=2), "tp_size"),
    (dict(ep_size=2), "ep_size"),
    (dict(host_kv_blocks=8), "host_kv_blocks"),
    (dict(prefix_pull=True), "prefix_pull"),
    (dict(multi_step_decode=4), "multi_step_decode"),
    (dict(decode_pipeline_depth=2), "decode_pipeline_depth"),
])
def test_engine_settings_refused_at_start_up(setting, path):
    with pytest.raises(ValueError, match=rf"{path} is refused for the sdar "
                                         "family.*block of 4 positions"):
        ModelRunner(_engine_config(**setting))


@pytest.mark.parametrize("setting,match", [
    (dict(prefill_buckets=[30, 64]), "a prefill bucket of 30"),
    (dict(kv_block_size=6), "kv_block_size of 6"),
])
def test_shapes_that_split_a_block_are_refused_at_start_up(setting, match):
    with pytest.raises(ValueError, match=match + " is not whole blocks of 4"):
        ModelRunner(_engine_config(**setting))


@pytest.mark.parametrize("path", ["remote_prefill", "migration"])
def test_paths_refused_where_they_start(path):
    runner = _runner()
    with pytest.raises(ValueError, match=f"{path} is refused for the sdar"):
        if path == "remote_prefill":
            Scheduler(runner, runner.config, disagg=object())
        else:
            runner.gather_blocks_device([1])
    sched = Scheduler(runner, runner.config)
    assert sched._chain_block_reason([], True) == "block_unit"


@pytest.mark.parametrize("name,sampling,output", [
    ("presence_penalty", dict(presence_penalty=0.5), {}),
    ("frequency_penalty", dict(frequency_penalty=0.5), {}),
    ("repetition_penalty", dict(repetition_penalty=1.2), {}),
    ("guided_decoding", dict(guided_choice_token_ids=[[5, 6]]), {}),
    ("guided_decoding", dict(guided_json={"type": "object"}), {}),
    ("logit_bias", dict(logit_bias={"7": 2.0}), {}),
    ("prompt_logprobs", {}, dict(prompt_logprobs=1)),
])
def test_request_options_refused_at_admission(name, sampling, output):
    unit = sdar.decode_unit(_engine_config().model)
    req = PreprocessedRequest(
        token_ids=[5, 6, 7], stop_conditions=StopConditions(max_tokens=4),
        sampling_options=SamplingOptions(temperature=0.0, **sampling),
        output_options=OutputOptions(**output), eos_token_ids=[])
    with pytest.raises(EngineError, match=f"{name} is refused.*block of 4"):
        _refuse_for_block_unit(req, unit)
    plain = PreprocessedRequest(
        token_ids=[5, 6, 7], stop_conditions=StopConditions(max_tokens=4),
        sampling_options=SamplingOptions(temperature=0.7, top_p=0.9, min_p=0.05,
                                         top_k=40, repetition_penalty=1.0),
        output_options=OutputOptions(logprobs=1), eos_token_ids=[])
    _refuse_for_block_unit(plain, unit)
    _refuse_for_block_unit(req, None)       # every other family: untouched


@pytest.mark.parametrize("key", sdar.CLAIMED_KEYS)
def test_generation_keys_on_another_model_type_are_refused_by_name(key):
    with pytest.raises(NotImplementedError, match=f"qwen3_moe.*{key}"):
        ModelConfig.from_hf_config({**HF, "model_type": "qwen3_moe"}
                                   | {k: None for k in sdar.CLAIMED_KEYS
                                      if k != key})


@pytest.mark.parametrize("over,match", [
    (dict(block_length=None), "needs block_length"),
    (dict(mask_token_id=None), "needs mask_token_id"),
    (dict(block_length=3), "block_length=3"),
    (dict(block_length=64), "block_length=64"),
    (dict(denoising_steps=5), "denoising_steps=5"),
    (dict(remasking_strategy="random"), "remasking_strategy='random'"),
    (dict(mask_token_id=256), "mask_token_id=256"),
])
def test_the_family_refuses_what_it_does_not_compute(over, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        ModelConfig.from_hf_config(_hf(**over))


def test_the_family_is_resolved_by_model_type_and_declares_its_unit():
    cfg = ModelConfig.from_hf_config(HF)
    assert cfg.model_family == "sdar" and models.resolve(cfg) is sdar
    assert (cfg.block_length, cfg.mask_token_id, cfg.denoising_steps,
            cfg.remasking_strategy, cfg.confidence_threshold) == (
        4, MASK, 2, "sequential", 0.9)
    unit = sdar.decode_unit(cfg)
    assert (unit.length, unit.mask_id, unit.steps, unit.strategy) == (
        4, MASK, 2, "sequential")
    # the published defaults where config.json leaves them out
    bare = ModelConfig.from_hf_config(
        {k: v for k, v in HF.items() if k not in (
            "denoising_steps", "remasking_strategy", "confidence_threshold")})
    assert sdar.decode_unit(bare).steps == 4
    assert sdar.decode_unit(bare).strategy == "low_confidence_dynamic"
    assert sdar.decode_unit(bare).threshold == 0.9
    # a Qwen3-MoE config without the keys is mixtral's, one token a pass
    qwen = ModelConfig.from_hf_config(
        {k: v for k, v in HF.items() if k not in sdar.CLAIMED_KEYS}
        | {"model_type": "qwen3_moe"})
    assert models.family(qwen).name == "mixtral" and qwen.block_length == 0
    assert not hasattr(models.resolve(qwen), "decode_unit")
    # prefix sharing stays on; q/k norms exist whatever a checkpoint brings
    assert getattr(sdar, "SEQUENCE_STATE", models.PAGES_ONLY) is models.PAGES_ONLY
    shapes = jax.eval_shape(lambda: sdar.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["layers"]["q_norm"].shape == (3, 16)


def test_scopes_in_the_lowered_block_pass():
    runner = _runner()
    b, w = 4, 8
    zb = np.zeros((b, 8), np.int32)       # two blocks a row
    from dynamo_tpu.engine import step_inputs
    buf = step_inputs.pack(zb, zb, np.zeros((b, w), np.int32), zb - 1,
                           keys=np.zeros(2, np.uint32), want_top=False,
                           context_lens=np.ones(b), last_idx=np.zeros(b),
                           top_k=np.zeros(b), temperature=np.zeros(b),
                           top_p=np.ones(b))
    text = runner._decode_block.lower(
        runner.params, *runner.kv_cache, buf, zb[:, :4],
        runner.moe_counts).as_text(
        debug_info=True)
    assert "jit_decode_block" in text or "decode_block" in text
    for scope in ("attn/block_attn", "mlp", "moe_route", "moe_experts",
                  "lm_head", "sampling", "block_select"):
        assert scope in text, scope


def test_warm_up_logs_the_verify_kernels_chunks(monkeypatch, caplog):
    """On the kernel route the block pass walks its pages through the
    verify kernel, whose chunks ``ModelRunner.warmup`` logs beside the
    decode kernels': at this shape (float32 pages of 16 tokens x 2 heads
    x 128 lanes, a table of 16) sixteen pages a chunk, wide or the
    tail's."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    hf = _hf(num_hidden_layers=1)
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf),
                              attention_impl="pallas")
    runner = ModelRunner(dataclasses.replace(
        _engine_config(hf), model=cfg, max_batch_size=2, prefill_buckets=[32]))
    with caplog.at_level("INFO", logger="dynamo_tpu.engine.model_runner"):
        runner.warmup()
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("decode kernels' chunks: ")]
    page = 2 * PAGE * 2 * 128 * 4
    assert {"kernel": "paged_verify_attention", "page_bytes": page,
            "wide_pages": 16, "tail_pages": 16,
            "wide_bytes": 16 * page} in json.loads(line.split(": ", 1)[1])


# ---------- served: cli/run's engine and HTTP service ----------

class _Served:
    """``cli/run``'s ``in=http out=jax`` at the tiny shape, in a thread of
    its own: the benchmark harness's model directory (a word-level
    vocabulary, id ``i`` renders as ``t<i>``), ``build_engine`` and
    ``run_http``."""

    def __init__(self, tmp):
        from harness import server
        from harness.modeldir import write_model_dir

        self.port = server.free_port()
        model_dir = write_model_dir(os.path.join(tmp, "model"),
                                    {**HF, "eos_token_id": 2, "bos_token_id": 1})
        extra = os.path.join(tmp, "engine_args.json")
        with open(extra, "w") as f:
            json.dump({"seed": 3, "attention_impl": "xla", "dtype": "float32",
                       "prefill_buckets": [32, 64]}, f)
        from dynamo_tpu.cli.run import build_parser
        self.flags = build_parser().parse_args([
            "--model-path", model_dir, "--model-name", "tiny-sdar",
            "--allow-random-weights", "--http-host", "127.0.0.1",
            "--http-port", str(self.port), "--extra-engine-args", extra,
            "--max-model-len", "256", "--max-batch-size", "4",
            "--num-kv-blocks", "64"])
        self.loop = asyncio.new_event_loop()
        self.ready = threading.Event()
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()
        assert self.ready.wait(300), "the service did not come up"
        if self.error is not None:
            raise self.error

    def _run(self):
        from harness import server

        asyncio.set_event_loop(self.loop)

        async def main():
            try:
                self.engine, self.task = await server.start(self.flags)
            except Exception as e:  # noqa: BLE001
                self.error = e
                self.ready.set()
                return
            self.ready.set()
            self.stopped = asyncio.Event()
            await self.stopped.wait()
            await server.stop(self.task)
            await self.engine.core_engine.close()

        self.loop.run_until_complete(main())

    def close(self):
        self.loop.call_soon_threadsafe(self.stopped.set)
        self.thread.join(60)

    def post(self, body, path="/v1/completions"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
            data=json.dumps({"model": "tiny-sdar", **body}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = _Served(str(tmp_path_factory.mktemp("sdar")))
    yield s
    s.close()


def _tokens(text):
    return [int(t[1:]) for t in text.split()]


def test_served_stream_delivers_a_block_a_chunk(served):
    """(h) SSE: a whole block is one chunk of up to four tokens, and the
    stream is the plain loop's."""
    prompt = _prompts((37,))[0]
    status, raw = served.post({"prompt": prompt, "max_tokens": 13,
                               "temperature": 0, "ignore_eos": True,
                               "stream": True,
                               "stream_options": {"include_usage": True}})
    assert status == 200
    chunks, usage = [], None
    for line in raw.splitlines():
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        d = json.loads(line[6:])
        usage = d.get("usage") or usage
        text = "".join(c.get("text") or "" for c in d.get("choices", ()))
        if text:
            chunks.append(_tokens(text))
    runner = served.engine.core_engine.runner
    want = reference.generate(HF, runner.params, prompt, 13)[0]
    assert [t for c in chunks for t in c] == want
    assert [len(c) for c in chunks] == [3, 4, 4, 2]
    assert usage == {"prompt_tokens": 37, "completion_tokens": 13,
                     "total_tokens": 50}


@pytest.mark.parametrize("max_tokens", [5, 8])
def test_served_usage_and_log_probabilities(served, max_tokens):
    """(e) What the benchmark's probes ask: greedy, ``logprobs: 1``, not
    streamed; usage counts every token and no more."""
    prompt = _prompts((50,))[0]
    status, raw = served.post({"prompt": prompt, "max_tokens": max_tokens,
                               "temperature": 0, "ignore_eos": True,
                               "logprobs": 1})
    assert status == 200
    d = json.loads(raw)
    lp = d["choices"][0]["logprobs"]
    runner = served.engine.core_engine.runner
    toks, lps, _ = reference.generate(HF, runner.params, prompt, max_tokens)
    assert _tokens(" ".join(lp["tokens"])) == toks
    np.testing.assert_allclose(lp["token_logprobs"], lps, atol=F32_ATOL)
    assert all(len(top) == 1 for top in lp["top_logprobs"])
    assert d["usage"]["completion_tokens"] == max_tokens
    assert d["choices"][0]["finish_reason"] == "length"


def test_served_stop_string_inside_a_block(served):
    prompt = _prompts((41,), seed=3)[0]
    runner = served.engine.core_engine.runner
    stream = reference.generate(HF, runner.params, prompt, 13)[0]
    assert stream[4] not in stream[:4]          # offset 4: inside a block
    status, raw = served.post({"prompt": prompt, "max_tokens": 13,
                               "temperature": 0, "stop": [f"t{stream[4]}"]})
    assert status == 200
    d = json.loads(raw)
    assert d["choices"][0]["finish_reason"] == "stop"
    assert _tokens(d["choices"][0]["text"]) == stream[:4]


@pytest.mark.parametrize("body,name", [
    ({"presence_penalty": 0.5}, "presence_penalty"),
    ({"frequency_penalty": 0.5}, "frequency_penalty"),
    ({"logit_bias": {"7": 5}}, "logit_bias"),
    ({"echo": True, "logprobs": 1}, "prompt_logprobs"),
])
def test_served_refusals_are_http_400(served, body, name):
    status, raw = served.post({"prompt": [5, 6, 7, 8, 9], "max_tokens": 4,
                               **body})
    assert status == 400 and name in raw and "block of 4" in raw
    # and the server still serves
    status, _ = served.post({"prompt": [5, 6, 7, 8, 9], "max_tokens": 4,
                             "temperature": 0})
    assert status == 200
