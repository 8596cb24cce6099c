"""The MiMo-V2 family's surface: ``config_fields`` on the catalog's keys,
the refusals by name, the period layout of ``F | S S S S F S`` and of the
published 48 entries, the row of ``models.FAMILIES`` and what the engine
derives from it (``tests/test_mimo_v2_reference.py`` holds the served
path against the reference; two files so that two workers share them)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.models import afmoe, llama, mimo_v2, trunk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2.5-ep16.json")) as f:
    SERVED = json.load(f)
# the published 48 entries (the catalog's row; the configuration keeps
# their first seven)
PATTERN_48 = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
FREQ_48 = [0] + [1] * 47
PUBLISHED = {**{k: v for k, v in SERVED.items() if k != "expert_share"},
             "num_hidden_layers": 48, "hybrid_layer_pattern": PATTERN_48,
             "moe_layer_freq": FREQ_48, "n_routed_experts": 256,
             "vocab_size": 152576, "max_position_embeddings": 1048576}
F, S = mimo_v2.FULL, mimo_v2.WINDOW


def test_config_fields_on_the_catalogs_keys():
    cfg = ModelConfig.from_hf_config(SERVED)
    assert cfg.model_family == "mimo_v2"
    assert models.family(cfg).name == "mimo_v2" and models.resolve(cfg) is mimo_v2
    assert cfg.layer_types == (F, S, S, S, S, F, S)
    assert cfg.first_k_dense_replace == 1
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.swa_num_kv_heads) == (64, 4, 8)
    assert (cfg.head_dim, cfg.v_head_dim, mimo_v2.rotary_dim(cfg)) == (192, 128, 64)
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (1e7, 1e4)
    assert cfg.rope_scaling is None and cfg.rms_norm_eps == 1e-5
    assert cfg.attention_value_scale == 0.707 and cfg.sliding_window == 128
    assert cfg.swa_sink_bias and not cfg.full_sink_bias
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (16, 256, 0)
    assert (cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.n_shared_experts, cfg.intermediate_size) == (8, 2048, 0, 16384)
    assert (cfg.moe_scoring_func, cfg.norm_topk_prob, cfg.topk_method,
            cfg.routed_scaling_factor, cfg.n_group, cfg.topk_group) == (
        "sigmoid", True, "noaux_tc", 1.0, 1, 1)
    whole = ModelConfig.from_hf_config(PUBLISHED)
    assert (whole.num_layers, whole.num_experts, whole.experts_of) == (48, 256, 0)
    assert whole.layer_types.count(F) == 9 and whole.layer_types.count(S) == 39
    # before the rows told by shape: mixtral's (num_experts > 0) would take it
    rows = [r.name for r in models.FAMILIES]
    assert rows.index("mimo_v2") < rows.index("mixtral")


def test_the_family_keeps_two_pools_and_refuses_what_afmoe_refuses():
    state = mimo_v2.SEQUENCE_STATE
    assert state.window_pool and state.private and not state.slots
    assert set(state.refused) == set(afmoe.SEQUENCE_STATE.refused)
    assert len(state.refused) == 12
    assert "expert_share" in state.refused["ep_size"]
    assert "kv heads" in state.refused["tp_size"]
    ec = EngineConfig(model=ModelConfig.from_hf_config(SERVED),
                      **SERVED["serve"])
    # page 0, 9 a decoding row, 128 more for the prefilling row's chunk
    assert ec.window_pages_a_row() == 9
    assert ec.window_pool_pages() == 1 + 32 * 9 + 128 == 417


REFUSED = [
    ({"moe_layer_freq": [0, 1, 0, 1, 1, 1, 1]}, NotImplementedError,
     "moe_layer_freq"),
    ({"moe_layer_freq": [0, 1, 1]}, NotImplementedError, "moe_layer_freq"),
    ({"n_group": 8, "topk_group": 4}, (NotImplementedError, ValueError),
     "n_group|topk_group"),
    ({"n_shared_experts": 1}, NotImplementedError, "n_shared_experts"),
    ({"routed_scaling_factor": 2.5}, NotImplementedError,
     "routed_scaling_factor"),
    ({"scoring_func": "softmax"}, NotImplementedError, "scoring_func"),
    ({"swa_head_dim": 128}, NotImplementedError, "swa_head_dim"),
    ({"swa_num_attention_heads": 32}, NotImplementedError,
     "swa_num_attention_heads"),
    ({"swa_v_head_dim": 64}, NotImplementedError, "swa_v_head_dim"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}},
     NotImplementedError, "rope_scaling"),
    ({"hybrid_layer_pattern": [0, 1, 1]}, ValueError, "hybrid_layer_pattern"),
    ({"hybrid_layer_pattern": [0, 2, 1, 1, 1, 0, 1]}, ValueError,
     "hybrid_layer_pattern"),
    ({"hybrid_layer_pattern": [0] * 7}, NotImplementedError, "both kinds"),
    ({"sliding_window_size": 256}, NotImplementedError, "sliding_window_size"),
    ({"attention_chunk_size": 64}, NotImplementedError, "attention_chunk_size"),
    ({"partial_rotary_factor": 0.33}, ValueError, "partial_rotary_factor"),
    ({"n_routed_experts": 0}, NotImplementedError, "routed experts"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"tie_word_embeddings": True}, NotImplementedError, "tie_word_embeddings"),
    ({"expert_share": {"of_experts": 250, "rank": 0}}, ValueError, "share"),
]


@pytest.mark.parametrize("keys,error,named", REFUSED,
                         ids=[named.split("|")[0] + str(i)
                              for i, (_, _, named) in enumerate(REFUSED)])
def test_what_the_module_does_not_compute_is_refused_by_name(keys, error, named):
    with pytest.raises(error, match=named):
        ModelConfig.from_hf_config({**SERVED, **keys})


def test_the_keys_are_refused_under_another_model_type_by_name():
    plain = {"model_type": "some_other_trunk", "vocab_size": 64,
             "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 2}
    with pytest.raises(NotImplementedError,
                       match="some_other_trunk.*hybrid_layer_pattern") as e:
        ModelConfig.from_hf_config({**plain, "hybrid_layer_pattern": [0, 1],
                                    "attention_value_scale": 0.707})
    assert "mimo_v2" in str(e.value)
    # the field under a family that keeps one page shape
    cfg = ModelConfig.from_hf_config(SERVED)
    with pytest.raises(NotImplementedError, match="swa_num_kv_heads"):
        models.resolve(dataclasses.replace(cfg, model_family="afmoe"))
    # without its own pattern the keys it shares with dots3 are not its claim
    assert mimo_v2.claimed_keys({"swa_rope_theta": 1e4, "expert_share": {}}) == []


@pytest.mark.parametrize("pattern,freq,prefix,periods", [
    # F | S S S S F S: layer 0 dense in front, then a period that is the
    # window run alone and one of a full layer and a window layer
    ([0, 1, 1, 1, 1, 0, 1], [0] + [1] * 6, [(F, 0, 0)],
     [(0, 0, 0, 4), (1, 1, 4, 1)]),
    # the published 48: S S S S, seven times F S S S S S, and the last F
    (PATTERN_48, FREQ_48, [(F, 0, 0)],
     [(0, 0, 0, 4)] + [(1 + i, 1, 4 + 5 * i, 5) for i in range(7)]
     + [(8, 1, 0, 0)]),
])
def test_the_period_layout(pattern, freq, prefix, periods):
    cfg = ModelConfig.from_hf_config({
        **PUBLISHED, "num_hidden_layers": len(pattern),
        "hybrid_layer_pattern": pattern, "moe_layer_freq": freq})
    got_prefix, got = trunk.period_layout(cfg, (F, S))
    assert got_prefix == prefix
    assert list(zip(*(np.asarray(c).tolist() for c in got))) == periods
    # every layer behind the prefix is in one period, once
    assert sum(p[1] + p[3] for p in periods) == len(pattern) - 1


def test_init_params_stacks_by_kind_and_holds_the_share():
    cfg = ModelConfig.from_hf_config(SERVED)
    shapes = jax.eval_shape(
        lambda: mimo_v2.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    full, window = shapes[F], shapes[S]
    assert full["wq"].shape == (2, 4096, 64 * 192)
    assert full["wk"].shape == (2, 4096, 4 * 192)
    assert full["wv"].shape == (2, 4096, 4 * 128)
    assert window["wk"].shape == (5, 4096, 8 * 192)
    assert window["wv"].shape == (5, 4096, 8 * 128)
    assert window["wo"].shape == (5, 64 * 128, 4096)
    assert window["sinks"].shape == (5, 64) and window["sinks"].dtype == jnp.float32
    assert "sinks" not in full
    assert shapes["dense"]["w_gate"].shape == (1, 4096, 16384)
    moe = shapes["moe"]
    assert moe["router"].shape == (6, 4096, 256)        # as wide as published
    assert moe["router_bias"].shape == (6, 256)
    assert moe["w_gate"].shape == (6, 16, 4096, 2048)   # the sixteen held
    assert moe["w_down"].shape == (6, 16, 2048, 4096)
    assert shapes["lm_head"].shape == (4096, 19072)
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 3.42e9 < count < 3.44e9                      # ISSUE 59: 3430 M
    specs = mimo_v2.param_specs(shapes)
    assert jax.tree.structure(specs) == jax.tree.structure(shapes)


def test_partial_rope_rotates_the_front_lanes_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 24), jnp.float32)
    pos = jnp.arange(5)[None] + 7
    got = llama.apply_rope(x, pos, 1e4, rotary_dim=8)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    np.testing.assert_allclose(
        np.asarray(got[..., :8]),
        np.asarray(llama.apply_rope(x[..., :8], pos, 1e4)), atol=0)
    # the whole head, and a width that is the head's, are the old program
    whole = llama.apply_rope(x, pos, 1e4)
    np.testing.assert_array_equal(
        np.asarray(llama.apply_rope(x, pos, 1e4, rotary_dim=24)),
        np.asarray(whole))
    assert not np.allclose(np.asarray(whole[..., 8:]), np.asarray(x[..., 8:]))


# ---------- the engine: start-up refusals, both pools, the counters ----------

import asyncio  # noqa: E402
import uuid  # noqa: E402

from dynamo_tpu.engine.model_runner import ModelRunner  # noqa: E402
from dynamo_tpu.engine.scheduler import (EngineRequest, Scheduler,  # noqa: E402
                                         prefill_pairs)
from dynamo_tpu.protocols.common import (OutputOptions,  # noqa: E402
                                         PreprocessedRequest, SamplingOptions,
                                         StopConditions)
from dynamo_tpu.runtime.engine import AsyncEngineContext  # noqa: E402

from test_mimo_v2_reference import (F32_ATOL, HF, PAGE, SLOTS, WINDOW,  # noqa: E402
                                    _cfg, _reference_logprobs, _seqs)


def _engine_config(**over):
    kw = dict(model=_cfg(), max_batch_size=SLOTS, max_model_len=512,
              kv_block_size=PAGE, num_kv_blocks=96, dtype="float32",
              prefill_buckets=[64, 128], max_prefill_tokens_per_step=64,
              seed=11, max_prefill_batch=2)
    kw.update(over)
    return EngineConfig(**kw)


@pytest.mark.parametrize("setting,path,reason", [
    (dict(tp_size=2), "tp_size", "kv heads"),
    (dict(ep_size=2), "ep_size", "expert_share"),
    (dict(spec_ngram_tokens=2), "spec_ngram_tokens", "rolls back"),
    (dict(multi_step_decode=4), "multi_step_decode", "window page"),
])
def test_paths_that_do_not_know_the_kind_are_refused_at_start_up(
        setting, path, reason):
    with pytest.raises(ValueError, match=rf"{path} is refused for the mimo_v2 "
                                         rf"family.*{reason}"):
        ModelRunner(_engine_config(**setting))


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(_engine_config())


def _request(prompt, max_tokens):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        output_options=OutputOptions(logprobs=0), eos_token_ids=[])
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue())


def test_engine_streams_equal_reference_and_counts_the_prefill_pairs(runner):
    """Through the scheduler, both pools and ``ModelRunner.step``: three
    prompts prefilled in 64-token chunks and decoded 24 tokens; every
    emitted token is the reference's argmax at its log-probability (the
    engine's own weights, the sinks as ``init_params`` draws them), both
    pools are empty at the end, and the scheduler has counted, for every
    chunk it dispatched, the pairs a full layer's triangle and a window
    layer's band allow."""
    prompts = _seqs([8 * WINDOW, 150, 70], seed=12)

    async def go():
        sched = Scheduler(runner, runner.config)
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
            ers = [_request(p, 24) for p in prompts]
            for er in ers:
                sched.add_request(er)
            return sched, await asyncio.gather(*(collect(er) for er in ers))
        finally:
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        sched, got = loop.run_until_complete(go())
    finally:
        loop.close()
    for prompt, (toks, lps) in zip(prompts, got):
        assert len(toks) == 24
        want = _reference_logprobs(runner.params, prompt + toks)
        at = np.arange(len(prompt) - 1, len(prompt) + 23)
        np.testing.assert_array_equal(np.argmax(want[at], axis=-1), toks)
        np.testing.assert_allclose(lps, want[at, toks], atol=F32_ATOL)
    assert sched.window.used == 0 and sched.allocator.used == 0
    rows = {ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in sched.registry.render().splitlines()
            if ln.startswith("dynamo_") and " " in ln}
    # whatever the chunking was, a prompt's chunks tile its positions
    full = sum(prefill_pairs(0, len(p), WINDOW)[0] for p in prompts)
    band = sum(prefill_pairs(0, len(p), WINDOW)[1] for p in prompts)
    assert rows['dynamo_attention_prefill_pairs_total{kind="full"}'] == full
    assert rows['dynamo_attention_prefill_pairs_total{kind="window"}'] == band
    assert band < full
    chunks = rows["dynamo_attention_prefill_chunks_total"]
    assert -(-8 * WINDOW // 64) <= chunks <= sum(-(-len(p) // 64) for p in prompts)
    assert rows["dynamo_kv_window_pages_released_total"] > 0


def test_prefill_pairs_are_a_triangle_and_a_band():
    assert prefill_pairs(0, 4) == (1 + 2 + 3 + 4, 0)
    assert prefill_pairs(0, 4, 128) == (10, 10)
    assert prefill_pairs(126, 130, 128) == (127 + 128 + 129 + 130,
                                            127 + 128 + 128 + 128)
    assert prefill_pairs(2048, 4096, 128) == (
        sum(range(2049, 4097)), 2048 * 128)
    # chunks tile: the pairs of a prompt are the pairs of its chunks
    whole = prefill_pairs(0, 1000, 128)
    parts = [prefill_pairs(a, b, 128) for a, b in ((0, 300), (300, 301),
                                                   (301, 1000))]
    assert whole == tuple(map(sum, zip(*parts)))


def test_scopes_in_the_lowered_programs():
    cfg = _cfg()
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = mimo_v2.init_kv_cache(cfg, 32, PAGE, jnp.float32, window_blocks=16)

    def text(s, w):
        args = (jnp.zeros((2, s), jnp.int32), jnp.zeros((2, s), jnp.int32), cache,
                jnp.zeros((2, 2 * w), jnp.int32), jnp.zeros((2, s), jnp.int32),
                jnp.ones((2,), jnp.int32))
        return jax.jit(lambda *a: mimo_v2.forward(params, cfg, *a)).lower(
            *args).as_text(debug_info=True)

    for program in (text(1, 16), text(64, 16)):
        for scope in ("attn/attn_window", "attn/attn_full", "kv_window",
                      "kv_full", "mlp", "moe_route", "moe_experts", "lm_head"):
            assert scope in program, scope
        assert "moe_shared" not in program      # the family has none


def test_random_weights_serve_logits_of_a_few_units():
    cfg = _cfg()
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    seq = _seqs([64], seed=1)[0]
    want = _reference_logprobs(params, seq)
    logits_std = np.std(want - want.mean(axis=-1, keepdims=True), axis=-1)
    np.testing.assert_allclose(logits_std.mean(), mimo_v2.LOGIT_STD, rtol=0.3)
    sinks = np.asarray(params[S]["sinks"])
    assert sinks.dtype == np.float32 and sinks.shape == (5, HF["num_attention_heads"])
    assert abs(sinks.mean() - mimo_v2.SINK_MEAN) < 0.5
    assert params["moe"]["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["moe"]["router_bias"]).max()) > 0
