"""The served AFMoE path (window and full attention layers by
``layer_types``, each kind's pages in a pool and behind a table of its
own, the window kind's given back as they fall behind the window; dense
then expert feed-forwards; prefill in chunks, decode one token a step)
against the benchmark's plain reference, ``benchmark/references/
afmoe.py`` — the same file the benchmark's ``correct`` is decided by;
there is no second copy.

Tiny ``afmoe`` shape that keeps the ratios: 4 query heads over 2 kv
heads, the layers ``s s s f | s s s f`` with the first two dense, 8
experts of which 2 a token and one shared, a window of 32 tokens (two
pages), so that a context of a few hundred tokens is several windows
long and every chunk and every page of decode releases.
"""

import asyncio
import dataclasses
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.block_allocator import window_keep_from
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.models import afmoe, mixtral
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext
from dynamo_tpu.telemetry.registry import MetricsRegistry

import served  # noqa: E402  (puts benchmark/ on the path)
from references import afmoe as reference  # noqa: E402

WINDOW = 32
HF = {
    "architectures": ["AfmoeForCausalLM"], "model_type": "afmoe",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
                   + ["sliding_attention"] * 3 + ["full_attention"],
    "global_attn_every_n_layers": 4, "num_dense_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "mup_enabled": True, "sliding_window": WINDOW, "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "max_position_embeddings": 1024, "tie_word_embeddings": False,
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "load_balance_coeff": 0.001,
    "use_grouped_mm": True,
}
PAGE = 16
SLOTS = 4
WIDTH = 32        # pages a sequence: 512 tokens
# float32 on both sides: the two differ in the order of the sums (a walk
# of pages against a masked product, sorted rows of experts against
# every expert on every token) and in nothing else; differences seen are
# 1e-5 to 4e-5 in log-probability, the smallest deliberate fault below
# reads over 1e-2
F32_ATOL = 1e-3


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, seed=7, **over):
    cfg = _cfg(**over)
    return cfg, afmoe.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _reference_logprobs(params, seq, hf=HF):
    return served.reference_logprobs(reference, hf, params, seq, pad=128)


def Served(cfg, params, dtype, **kw):
    """32 pages of 16 a slot behind page 0, which is nobody's; the
    window kind's pages from a real ``WindowPool`` (``served.Served``)."""
    return served.Served(afmoe, cfg, params, dtype, block=PAGE, width=WIDTH,
                         slots=SLOTS, **kw)


_seqs, _serve_case = served.seqs, served.serve_case


CASES = {
    # (a) a prompt shorter than the window in one chunk, decode across
    # the window's edge (32) and two pages past it
    "crosses_the_window_in_decode": dict(lengths=[20 + 50], n_decode=50,
                                         cuts=[], width=64),
    # (b) ten windows of prompt in five chunks, boundaries off the page
    # of 16, every chunk after the first releases; then 40 decode steps
    # (two and a half pages: three releases)
    "five_chunks": dict(lengths=[330 + 40], n_decode=40,
                        cuts=[64, 100, 228, 292], width=128),
    # (c) one chunk of twelve windows, a decode step that completes a
    # page (400 = 25 pages) and ones that start the next
    "one_chunk_page_edge": dict(lengths=[390 + 24], n_decode=24, cuts=[],
                                width=512),
    # (d) rows of different lengths, a pad row between them, slots that
    # are not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[40 + 6, 300 + 6, 150 + 6], n_decode=6,
                          cuts=[128, 256], width=128, slots=[2, 0, 3],
                          pad_row=True),
}


def _check_case(case, served, params):
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    slots = c.get("slots", list(range(len(seqs))))
    got = _serve_case(served, seqs, slots, c["n_decode"], c["cuts"],
                      c["width"], c.get("pad_row", False))
    for seq, lp in zip(seqs, got):
        np.testing.assert_allclose(lp, _reference_logprobs(params, seq),
                                   rtol=0, atol=F32_ATOL)
    return c


@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case):
    """Chunked prefill, then decode through both pools, give the
    reference's full-forward log-softmax at every position; a window
    layer's row never holds more than its reckoned pages, and every
    chunk past the window's first gives pages back."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    c = _check_case(case, served, params)
    ec = EngineConfig(model=cfg, kv_block_size=PAGE,
                      prefill_buckets=[c["width"]],
                      max_prefill_tokens_per_step=c["width"])
    assert served.peak["decode"] <= ec.window_pages_a_row() == 3
    assert served.peak["prefill"] <= ec.window_pages_a_row(c["width"])
    chunks = len(c["cuts"]) + 1
    if case == "five_chunks":
        assert all(n > 0 for n in served.released[1:chunks])
        # the first decode step gives back the last chunk's allowance
        assert sum(n > 0 for n in served.released[chunks:]) == 4
    assert sum(served.released) > 0


# K and V of every page no sequence holds, after every pass: a large
# finite value in both; NaN in K where the route masks its scores by a
# select (all three do: ops/attention.paged_attention, the decode and
# the flash kernel), a finite value in V, which every route multiplies
# by a weight of exactly 0
POISONS = {"finite": (1e3, 1e3), "nan_keys": (float("nan"), 1e3)}


@pytest.mark.parametrize("poison", list(POISONS))
@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_a_released_page_is_never_read_unmasked(route, poison, monkeypatch):
    """With every free page of both kinds (and the pages 0, which
    released table entries name) overwritten after each pass, the logits
    are still the reference's: on the XLA route, and on the decode and
    flash kernels in the interpreter."""
    if route == "kernels":
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg, params = _params(
        jnp.float32, attention_impl="xla" if route == "xla" else "pallas")
    # a pool of exactly what the rows need: released pages are handed
    # out again at once, to the same row and to others
    served = Served(cfg, params, jnp.float32, poison=POISONS[poison],
                    pool_pages=1 + 3 * (2 + 64 // PAGE + 1))
    case = "five_chunks" if route == "xla" else "crosses_the_window_in_decode"
    _check_case(case, served, params)
    if route == "xla":
        _check_case("batch_unequal", served, params)


def test_bfloat16_served_path_stays_near_the_reference():
    """bfloat16 weights, activations and pages of both kinds (the router
    float32) against the float32 reference on the same weights: a
    rounding-sized difference, far under what a wrong program reads."""
    cfg, params = _params(jnp.bfloat16)
    c = CASES["five_chunks"]
    seq = _seqs(c["lengths"], seed=3)[0]
    got = _serve_case(Served(cfg, params, jnp.bfloat16), [seq], [0],
                      c["n_decode"], c["cuts"], c["width"])[0]
    want = _reference_logprobs(params, seq)
    at = np.abs(got - want)[np.arange(len(seq)), want.argmax(axis=-1)]
    # the log-probability of the likeliest token, as ``correct`` compares
    # it: mean 0.10-0.11, largest 0.46-0.60 on this shape (hidden 64, two
    # of eight experts: far coarser than the chip's 2048 and 8 of 128)
    assert at.mean() < 0.2 and at.max() < 1.2


def test_resume_after_preemption_and_slot_reuse():
    """A sequence dropped after 10 decoded tokens and prefilled again
    from position 0 (prompt + the 10), into the slot another sequence
    has used meanwhile, continues as the reference says."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    a, b = _seqs([200 + 30, 90 + 8], seed=4)
    want_a, want_b = _reference_logprobs(params, a), _reference_logprobs(params, b)
    got = _serve_case(served, [a[:210]], [1], 10, [], 256)[0]
    np.testing.assert_allclose(got, want_a[:210], atol=F32_ATOL)
    got = _serve_case(served, [b], [1], 8, [], 128)[0]
    np.testing.assert_allclose(got, want_b, atol=F32_ATOL)
    got = _serve_case(served, [a], [1], 20, [128], 128)[0]
    np.testing.assert_allclose(got, want_a, atol=F32_ATOL)
    assert served.pool.used == len(served.rows[1].window_ids)


def _biased_combine(x, router_w, top_k, scoring="sigmoid", norm_topk=True,
                    routed_scaling=1.0, router_bias=None, **_):
    """The router with ``expert_bias`` left in the combine weights."""
    probs = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    select = probs + router_bias.astype(jnp.float32)[None, :]
    vals, idx = jax.lax.top_k(select, top_k)
    return vals / vals.sum(-1, keepdims=True) * routed_scaling, idx


def _rounded_router(route):
    """The router with its scores from a bfloat16 product of bfloat16
    operands (scripts/long_probes.py --fault bf16_router)."""
    def route_top_k(x, router_w, *args, **kwargs):
        logits = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16))
        eye = jnp.eye(router_w.shape[1], dtype=jnp.float32)
        return route(logits.astype(jnp.float32), eye, *args, **kwargs)
    return route_top_k


def _skip_norm(which):
    """``afmoe.rms_norm`` with one of a layer's four norms (in, post
    attention, pre mlp, post mlp: the order a layer's body calls them
    in) left out."""
    real, calls = afmoe.rms_norm, iter(range(1 << 30))

    def norm(x, w, eps):
        return x if next(calls) % 4 == which else real(x, w, eps)

    return norm


def _wrong(fault, monkeypatch):
    """A served program with one line of the equations left out or
    changed; the reference keeps the right one."""
    cfg, params = _params(jnp.float32)
    served_params = params

    def without(*keys):
        return {**params, "runs": [{k: v for k, v in run.items()
                                    if k not in keys} for run in params["runs"]]}

    def prologue(rope_of):
        real = afmoe.qkv_prologue
        monkeypatch.setattr(
            afmoe, "qkv_prologue",
            lambda *a, rope=True: real(*a, rope=rope_of(rope)))

    if fault == "rope_on_a_global_layer":
        prologue(lambda rope: True)
    elif fault == "no_rope_on_a_window_layer":
        prologue(lambda rope: False)
    elif fault == "no_gate":
        monkeypatch.setattr(afmoe, "_gated", lambda o, x, lp: o)
    elif fault == "no_qk_norm":
        served_params = without("q_norm", "k_norm")
    elif fault in ("no_post_attention_norm", "no_post_mlp_norm"):
        monkeypatch.setattr(afmoe, "rms_norm", _skip_norm(
            1 if fault == "no_post_attention_norm" else 3))
    elif fault == "no_embedding_scale":
        cfg = dataclasses.replace(cfg, embedding_multiplier=1.0)
    elif fault == "expert_bias_in_the_combine_weights":
        monkeypatch.setattr(mixtral, "route_top_k", _biased_combine)
    elif fault == "bfloat16_router":
        monkeypatch.setattr(mixtral, "route_top_k",
                            _rounded_router(mixtral.route_top_k))
    elif fault == "no_expert_bias_in_the_choice":
        served_params = without("router_bias")
    elif fault == "no_route_scale":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif fault == "no_shared_expert":
        served_params = without("w_sh_gate", "w_sh_up", "w_sh_down")
    elif fault == "window_on_a_global_layer":
        real = afmoe.attention
        monkeypatch.setattr(
            afmoe, "attention",
            lambda *a, sliding_window=None, **k: real(
                *a, sliding_window=cfg.sliding_window, **k))
    elif fault == "no_window_on_a_window_layer":
        real = afmoe.attention
        monkeypatch.setattr(
            afmoe, "attention",
            lambda *a, sliding_window=None, **k: real(
                *a, sliding_window=None, **k))
    elif fault == "window_one_key_short":
        cfg = dataclasses.replace(cfg, sliding_window=WINDOW - 1)
    return Served(cfg, served_params, jnp.float32, fresh=True), params


@pytest.mark.parametrize("fault", [
    "rope_on_a_global_layer", "no_rope_on_a_window_layer", "no_gate",
    "no_qk_norm", "no_post_attention_norm", "no_post_mlp_norm",
    "no_embedding_scale", "expert_bias_in_the_combine_weights",
    "bfloat16_router", "no_expert_bias_in_the_choice", "no_route_scale", "no_shared_expert",
    "window_on_a_global_layer", "no_window_on_a_window_layer",
    "window_one_key_short"])
def test_reference_tells_wrong_programs_apart(fault, monkeypatch):
    served, params = _wrong(fault, monkeypatch)
    c = CASES["five_chunks"]
    seq = _seqs(c["lengths"], seed=5)[0]
    got = _serve_case(served, [seq], [0], c["n_decode"], c["cuts"], c["width"])[0]
    assert np.abs(got - _reference_logprobs(params, seq)).max() > 3 * F32_ATOL


def test_a_dense_layer_is_not_routed_and_runs_follow_the_kinds():
    """``kind_runs``: the first ``num_dense_layers`` layers dense, the
    kinds by ``layer_types`` with period four, each run's first index
    counted among the layers of its attention kind."""
    cfg = _cfg()
    assert afmoe.kind_runs(cfg) == [
        ((True, True), 0, 2), ((True, False), 2, 1), ((False, False), 0, 1),
        ((True, False), 3, 3), ((False, False), 1, 1)]
    params = afmoe.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert ["router" in run for run in params["runs"]] == [False, True, True,
                                                          True, True]
    assert params["runs"][0]["w_gate"].shape == (2, 64, 96)
    assert params["runs"][3]["w_gate"].shape == (3, 8, 64, 32)
    assert reference.runs_of(HF["layer_types"], 2) == [
        (*kind, n) for kind, _, n in afmoe.kind_runs(cfg)]
    k_side, _ = afmoe.init_kv_cache(cfg, 10, PAGE, jnp.bfloat16, window_blocks=7)
    assert k_side.full.shape == (2, 10, PAGE, 2, 128)
    assert k_side.window.shape == (6, 7, PAGE, 2, 128)
    assert k_side.dtype == jnp.bfloat16


def _engine_config(**over):
    kw = dict(model=_cfg(), max_batch_size=SLOTS, max_model_len=512,
              kv_block_size=PAGE, num_kv_blocks=96, dtype="float32",
              prefill_buckets=[64, 128], max_prefill_tokens_per_step=64,
              seed=11, max_prefill_batch=2)
    kw.update(over)
    return EngineConfig(**kw)


@pytest.mark.parametrize("setting,path", [
    (dict(spec_ngram_tokens=2), "spec_ngram_tokens"),
    (dict(spec_draft_model="/no/such/draft", spec_draft_tokens=4),
     "spec_draft_model"),
    (dict(sp_size=2, prefill_buckets=[64, 128]), "sp_size"),
    (dict(pp_size=2), "pp_size"),
    (dict(tp_size=2), "tp_size"),
    (dict(ep_size=2), "ep_size"),
    (dict(host_kv_blocks=8), "host_kv_blocks"),
    (dict(prefix_pull=True), "prefix_pull"),
    (dict(multi_step_decode=4), "multi_step_decode"),
    (dict(decode_pipeline_depth=2), "decode_pipeline_depth"),
])
def test_paths_that_do_not_know_the_kind_are_refused_at_start_up(setting, path):
    with pytest.raises(ValueError, match=rf"{path} is refused for the afmoe "
                                         "family.*pool and a table"):
        ModelRunner(_engine_config(**setting))


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(_engine_config())


@pytest.mark.parametrize("path", ["remote_prefill", "migration"])
def test_paths_refused_where_they_start(runner, path):
    with pytest.raises(ValueError, match=f"{path} is refused for the afmoe"):
        if path == "remote_prefill":
            Scheduler(runner, runner.config, disagg=object())
        else:
            runner.gather_blocks_device([1])


@pytest.mark.parametrize("key,value", [
    ("layer_types", HF["layer_types"]), ("num_dense_layers", 2),
    ("num_shared_experts", 1), ("mup_enabled", True)])
def test_afmoe_keys_on_another_model_type_are_refused_by_name(key, value):
    """The parent served this checkpoint through mixtral.py, without a
    word: one whole-model window, no dense layers, no shared expert."""
    plain = {k: v for k, v in HF.items()
             if k not in ("layer_types", "num_dense_layers",
                          "num_shared_experts", "mup_enabled")}
    hf = {**plain, "model_type": "qwen3_moe",
          "architectures": ["Qwen3MoeForCausalLM"], key: value}
    with pytest.raises(NotImplementedError, match=f"qwen3_moe.*{key}"):
        ModelConfig.from_hf_config(hf)
    ModelConfig.from_hf_config({**plain, "model_type": "qwen3_moe"})


def test_the_family_is_resolved_by_model_type_and_refuses_what_it_lacks():
    assert models.resolve(_cfg()) is afmoe
    with pytest.raises(NotImplementedError, match="layer_types"):
        models.resolve(dataclasses.replace(_cfg(), model_family=""))
    uniform = {**HF, "model_type": "qwen3", "layer_types": ["full_attention"] * 8}
    for key in ("num_dense_layers", "num_shared_experts", "mup_enabled"):
        uniform.pop(key)
    assert ModelConfig.from_hf_config(uniform).layer_types == ()
    with pytest.raises(NotImplementedError, match="score_func"):
        ModelConfig.from_hf_config({**HF, "score_func": "softmax"})
    with pytest.raises(NotImplementedError, match="n_group"):
        ModelConfig.from_hf_config({**HF, "n_group": 2})
    with pytest.raises(ValueError, match="layer_types has 7 entries"):
        ModelConfig.from_hf_config({**HF, "layer_types": HF["layer_types"][:7]})
    with pytest.raises(ValueError, match="unknown kinds"):
        ModelConfig.from_hf_config(
            {**HF, "layer_types": ["chunked_attention"] * 8})
    cfg = _cfg()
    assert cfg.embedding_multiplier == 8.0 and cfg.first_k_dense_replace == 2
    assert cfg.n_shared_experts == 1 and cfg.routed_scaling_factor == 2.826


def test_the_window_pool_is_derived_and_other_families_have_none():
    ec = _engine_config()
    # page 0, three pages a decoding slot, and what two rows of one
    # prefill step hold more: a 64-token chunk's pages beside the window's
    assert ec.window_pages_a_row() == 3 and ec.window_pages_a_row(64) == 7
    assert ec.window_pool_pages() == 1 + SLOTS * 3 + 2 * (7 - 3)
    assert window_keep_from(31, WINDOW, PAGE) == 0
    assert window_keep_from(47, WINDOW, PAGE) == 1      # keys 16..47
    assert window_keep_from(48, WINDOW, PAGE) == 1      # keys 17..48
    plain = dataclasses.replace(_cfg(), layer_types=(), model_family="")
    assert dataclasses.replace(ec, model=plain).window_pool_pages() == 0


def _request(prompt, max_tokens):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        output_options=OutputOptions(logprobs=0),
        eos_token_ids=[],
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


def _drive(sched, requests):
    async def go():
        sched.start()

        async def collect(er):
            toks, lps = [], []
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks, lps
                toks.extend(out.token_ids)
                lps.extend(lp.logprob for lp in out.logprobs or [])
        try:
            for er in requests:
                sched.add_request(er)
            return await asyncio.gather(*(collect(er) for er in requests))
        finally:
            await sched.stop()
    return go()


def _metric_rows(sched):
    return {ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in sched.registry.render().splitlines()
            if ln.startswith("dynamo_") and " " in ln}


@pytest.mark.parametrize("full_pages", [96, 50])
def test_engine_streams_equal_reference_through_both_pools(runner, full_pages):
    """Through the scheduler, both pools and ``ModelRunner.step``: a
    prompt of eight windows and two others, prefilled in 64-token chunks
    and decoded 40 tokens; every emitted token is the reference's argmax
    at its log-probability, no row ever holds more window pages than
    reckoned, most of what was taken is given back while the sequences
    run, and both pools are empty at the end. With 50 pages of the full
    kind one sequence is preempted and resumes from position 0."""
    config = dataclasses.replace(runner.config, num_kv_blocks=full_pages)
    prompts = _seqs([8 * WINDOW, 150, 330], seed=12)
    preempted, held = [], {"prefill": 0, "decode": 0}

    async def go():
        sched = Scheduler(runner, config)
        preempt, take = sched._preempt, sched._take_window

        def counted_take(er, needed):
            ok = take(er, needed)
            phase = "prefill" if er in sched.prefilling else "decode"
            held[phase] = max(held[phase], len(er.window_ids))
            return ok

        sched._preempt = lambda er: (preempted.append(er.request_id), preempt(er))
        sched._take_window = counted_take
        usage = []
        orig_chunk = sched._prefill_chunk

        async def chunk(loop, ers):
            await orig_chunk(loop, ers)
            usage.append(_metric_rows(sched))

        sched._prefill_chunk = chunk
        got = await _drive(sched, [_request(p, 40) for p in prompts])
        return sched, got, usage

    loop = asyncio.new_event_loop()
    try:
        sched, got, usage = loop.run_until_complete(go())
    finally:
        loop.close()
    assert bool(preempted) == (full_pages == 50)
    for prompt, (toks, lps) in zip(prompts, got):
        assert len(toks) == 40
        want = _reference_logprobs(runner.params, prompt + toks)
        at = np.arange(len(prompt) - 1, len(prompt) + 39)
        np.testing.assert_array_equal(np.argmax(want[at], axis=-1), toks)
        np.testing.assert_allclose(lps, want[at, toks], atol=F32_ATOL)
    assert 0 < held["decode"] <= config.window_pages_a_row()
    assert held["decode"] < held["prefill"] <= config.window_pages_a_row(64)
    rows = _metric_rows(sched)
    taken = rows["dynamo_kv_window_pages_allocated_total"]
    released = rows["dynamo_kv_window_pages_released_total"]
    assert 0.5 * taken < released < taken
    assert sched.window.used == 0 and sched.allocator.used == 0
    assert rows['dynamo_kv_pool_usage_ratio{kind="window"}'] == 0.0
    mid = usage[len(usage) // 2]
    assert mid["dynamo_kv_block_usage_ratio"] == max(
        mid['dynamo_kv_pool_usage_ratio{kind="full"}'],
        mid['dynamo_kv_pool_usage_ratio{kind="window"}']) > 0
    # nothing is registered or matched for a family with two kinds of page
    assert not sched.allocator.by_hash


def test_other_families_get_the_allocator_they_had(runner):
    """No window pool, no second table, no new instrument: the plain
    allocator's /metrics has no ``dynamo_kv_pool_usage_ratio``."""
    from dynamo_tpu.engine.block_allocator import BlockAllocator

    reg = MetricsRegistry()
    plain = BlockAllocator(16, PAGE, registry=reg)
    assert plain.window is None and "dynamo_kv_pool" not in reg.render()
    assert "dynamo_kv_window" not in reg.render()
    plain.allocate_n(4)
    assert plain.usage() == 0.25


def test_scopes_in_the_lowered_programs():
    cfg, params = _params(jnp.float32)
    cache = afmoe.init_kv_cache(cfg, 32, PAGE, jnp.float32, window_blocks=16)

    def text(s, w):
        args = (jnp.zeros((2, s), jnp.int32), jnp.zeros((2, s), jnp.int32), cache,
                jnp.zeros((2, 2 * w), jnp.int32), jnp.zeros((2, s), jnp.int32),
                jnp.ones((2,), jnp.int32))
        return jax.jit(lambda *a: afmoe.forward(params, cfg, *a)).lower(
            *args).as_text(debug_info=True)

    for program in (text(1, 16), text(64, 16)):
        for scope in ("attn/attn_window", "attn/attn_full", "kv_window",
                      "kv_full", "mlp", "moe_route", "moe_experts", "moe_shared"):
            assert scope in program, scope


def test_random_weights_serve_logits_of_a_few_units():
    cfg, params = _params(jnp.float32)
    seq = _seqs([64], seed=1)[0]
    want = _reference_logprobs(params, seq)
    logits_std = np.std(want - want.mean(axis=-1, keepdims=True), axis=-1)
    np.testing.assert_allclose(logits_std.mean(), afmoe.LOGIT_STD, rtol=0.25)
    assert params["runs"][1]["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["runs"][1]["router_bias"]).max()) > 0
