"""The Nemotron-H family's surface: ``config_fields`` on the catalog's
keys, the refusals by name, the pattern's periods (the stage's eleven
letters and the published 88), the walk by kind, the row of
``models.FAMILIES`` and the engine through ``ModelRunner`` and the
scheduler (``tests/test_nemotron_h_reference.py`` holds the served path
against the reference; two files so that two workers share them)."""

import asyncio
import dataclasses
import json
import os
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.models import falcon_h1, nemotron_h, trunk
from dynamo_tpu.protocols.common import (OutputOptions, PreprocessedRequest,
                                         SamplingOptions, StopConditions)
from dynamo_tpu.runtime.engine import AsyncEngineContext

from test_nemotron_h_reference import (BLOCK, F32_ATOL, HF, SHARES, SLOTS, _cfg,
                                       _reference_logprobs, _seqs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron-3-super-ep4.json")) as f:
    SERVED = json.load(f)
# the published 88 letters (the catalog's row; the configuration keeps
# their first eleven)
PATTERN_88 = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*E"
              "MEMEMEMEM*EMEMEMEM*EMEMEMEME")
PUBLISHED = {**{k: v for k, v in SERVED.items() if k != "expert_share"},
             "num_hidden_layers": 88, "hybrid_override_pattern": PATTERN_88,
             "n_routed_experts": 512, "vocab_size": 131072,
             "max_position_embeddings": 262144}
M, A, E = nemotron_h.MAMBA, nemotron_h.ATTENTION, nemotron_h.EXPERTS


def test_config_fields_on_the_catalogs_keys():
    cfg = ModelConfig.from_hf_config(SERVED)
    assert cfg.model_family == "nemotron_h"
    assert models.family(cfg).name == "nemotron_h"
    assert models.resolve(cfg) is nemotron_h
    assert cfg.layer_types == (M, E, M, E, M, E, M, A, E, M, E)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_head,
            cfg.mamba_d_state, cfg.mamba_n_groups, cfg.mamba_d_conv,
            cfg.mamba_chunk_size) == (8192, 128, 64, 128, 8, 4, 128)
    assert (cfg.moe_latent_size, cfg.moe_intermediate_size,
            cfg.shared_intermediate_size, cfg.mlp_hidden_act) == (
        1024, 2688, 5376, "relu2")
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank,
            cfg.num_experts_per_tok) == (128, 512, 0, 22)
    assert (cfg.moe_scoring_func, cfg.norm_topk_prob, cfg.topk_method,
            cfg.routed_scaling_factor, cfg.n_group, cfg.topk_group) == (
        "sigmoid", True, "noaux_tc", 5, 1, 1)
    assert cfg.rms_norm_eps == 1e-5 and not cfg.tie_word_embeddings
    assert (cfg.vocab_size, cfg.max_position_embeddings) == (32768, 4096)
    # no multiplier anywhere, and no field of another family
    assert (cfg.residual_multiplier, cfg.embedding_multiplier,
            cfg.lm_head_multiplier, cfg.attention_multiplier) == (1, 1, 1, 0)
    whole = ModelConfig.from_hf_config(PUBLISHED)
    assert (whole.num_layers, whole.num_experts, whole.experts_of) == (88, 512, 0)
    assert [whole.layer_types.count(k) for k in (M, A, E)] == [40, 8, 40]
    # before the rows told by shape: mixtral's (num_experts > 0) would take it
    rows = [r.name for r in models.FAMILIES]
    assert rows.index("nemotron_h") < rows.index("mixtral")
    assert models.family(cfg).reads == ("mamba_d_ssm", "layer_types")


def test_the_family_keeps_state_by_slot_and_refuses_what_falcon_h1_refuses():
    state = nemotron_h.SEQUENCE_STATE
    assert state.slots and state.private and not state.window_pool
    assert set(state.refused) == set(falcon_h1.SEQUENCE_STATE.refused) | {"ep_size"}
    for path, reason in falcon_h1.SEQUENCE_STATE.refused.items():
        if path != "tp_size":       # by the same sentences
            assert state.refused[path] == reason
    assert "expert_share" in state.refused["ep_size"]
    assert "not sharded" in state.refused["tp_size"]


REFUSED = [
    # a letter the family does not know (a dense feed-forward layer)
    ({"hybrid_override_pattern": "MEMEMEM*EM-"}, ValueError,
     r"unknown letters \['-'\]"),
    ({"hybrid_override_pattern": "MEME"}, ValueError, "4 letters for 11 layers"),
    # a share that does not divide 512, and a rank past the last share
    ({"n_routed_experts": 120}, ValueError, "share"),
    ({"expert_share": {"of_experts": 512, "rank": 4}}, ValueError, "share"),
    ({"n_groups": 3}, ValueError, "n_groups"),
    ({"mamba_head_dim": 48}, ValueError, "mamba_head_dim"),
    ({"mlp_hidden_act": "silu"}, NotImplementedError, "mlp_hidden_act"),
    ({"mamba_proj_bias": True}, NotImplementedError, "mamba_proj_bias"),
    ({"use_conv_bias": False}, NotImplementedError, "use_conv_bias"),
    ({"attention_bias": True}, NotImplementedError, "attention_bias"),
    ({"residual_in_fp32": True}, NotImplementedError, "residual_in_fp32"),
    ({"n_group": 8, "topk_group": 4}, (NotImplementedError, ValueError),
     "n_group"),
    ({"n_shared_experts": 2}, NotImplementedError, "n_shared_experts"),
    ({"tie_word_embeddings": True}, NotImplementedError, "tie_word_embeddings"),
    ({"moe_latent_size": 0}, NotImplementedError, "moe_latent_size"),
    ({"moe_shared_expert_intermediate_size": 0}, NotImplementedError,
     "shared expert"),
    ({"n_routed_experts": 0}, NotImplementedError, "routed experts"),
]


@pytest.mark.parametrize("keys,error,named", REFUSED,
                         ids=[f"{list(k)[0]}{i}"
                              for i, (k, _, _) in enumerate(REFUSED)])
def test_what_the_module_does_not_compute_is_refused_by_name(keys, error, named):
    with pytest.raises(error, match=named):
        ModelConfig.from_hf_config({**SERVED, **keys})


def test_the_pattern_is_still_refused_under_another_model_type():
    """``hybrid_override_pattern`` is this family's under its own
    ``model_type`` and Falcon-H1's claim under any other, refused there
    with Falcon-H1's sentence, which names this family too."""
    plain = {"model_type": "some_other_trunk", "vocab_size": 64,
             "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 2}
    with pytest.raises(NotImplementedError,
                       match="some_other_trunk.*hybrid_override_pattern") as e:
        ModelConfig.from_hf_config({**plain, "hybrid_override_pattern": "M*"})
    assert str(e.value).endswith(falcon_h1.CLAIM.format(
        keys="hybrid_override_pattern"))
    assert "nemotron_h" in falcon_h1.CLAIM
    assert "hybrid_override_pattern" in falcon_h1.CLAIMED_KEYS
    # what only this family claims is refused with its own sentence
    with pytest.raises(NotImplementedError,
                       match="some_other_trunk.*moe_latent_size") as e:
        ModelConfig.from_hf_config({**plain, "moe_latent_size": 1024})
    assert "models/nemotron_h.py" in str(e.value)
    # and under a family that does not compute it
    with pytest.raises(NotImplementedError,
                       match="granitemoehybrid.*hybrid_override_pattern"):
        ModelConfig.from_hf_config({
            **plain, "model_type": "granitemoehybrid",
            "hybrid_override_pattern": "M*"})
    # the field under a family that dispatches the hidden stream
    cfg = ModelConfig.from_hf_config(SERVED)
    with pytest.raises(NotImplementedError, match="moe_latent_size"):
        models.resolve(dataclasses.replace(cfg, model_family="granite_hybrid"))


@pytest.mark.parametrize("pattern,periods", [
    # the stage: M E three times, M * E, M E
    ("MEMEMEM*EME", [(0, 1, 0, 0, 0, 1), (1, 1, 0, 0, 1, 1), (2, 1, 0, 0, 2, 1),
                     (3, 1, 0, 1, 3, 1), (4, 1, 1, 0, 4, 1)]),
    # any sequence parses: runs, a kind left out, a period of one letter
    ("MM*EE", [(0, 2, 0, 1, 0, 2)]),
    ("E*M", [(0, 0, 0, 0, 0, 1), (0, 0, 0, 1, 1, 0), (0, 1, 1, 0, 1, 0)]),
])
def test_the_patterns_periods(pattern, periods):
    kinds = [nemotron_h.LETTERS[c] for c in pattern]
    assert [tuple(p) for p in trunk.kind_periods(kinds, nemotron_h.PERIOD)] \
        == periods


def test_the_published_88_letters_are_forty_periods_of_one_body_a_kind():
    kinds = [nemotron_h.LETTERS[c] for c in PATTERN_88]
    periods = trunk.kind_periods(kinds, nemotron_h.PERIOD)
    assert len(periods) == 40
    assert {tuple(p[1::2]) for p in periods} == {(1, 0, 1), (1, 1, 1)}
    assert sum(p[3] for p in periods) == 8
    # every layer is walked once, in the pattern's order
    walked = []
    for p in periods:
        for at, kind in enumerate(nemotron_h.PERIOD):
            walked += [(kind, p[2 * at] + j) for j in range(p[2 * at + 1])]
    seen = {k: 0 for k in nemotron_h.PERIOD}
    for kind, (got, i) in zip(kinds, walked):
        assert got == kind and i == seen[kind]
        seen[kind] += 1
    with pytest.raises(ValueError, match="lightning"):
        trunk.kind_periods(["mamba", "lightning"], nemotron_h.PERIOD)


def test_walk_kinds_follows_the_pattern_over_the_stacks():
    """The walk hands ``body`` each layer once, in the pattern's order,
    with its own index among its kind and its own slice of the stack;
    the kinds with one layer a period are unrolled, the attention's none
    or one is a loop of traced length."""
    kinds = [nemotron_h.LETTERS[c] for c in "MEMEMEM*EME"]
    stacks = {M: {"w": jnp.arange(5.0) + 10}, A: {"w": jnp.arange(1.0) + 20},
              E: {"w": jnp.arange(5.0) + 30}}
    code = {M: 1.0, A: 2.0, E: 3.0}

    def body(kind, lp, carry, i):
        log, n = carry
        row = jnp.stack([code[kind], i.astype(jnp.float32), lp["w"]])
        return jax.lax.dynamic_update_slice(log, row[None], (n, 0)), n + 1

    run = jax.jit(lambda: trunk.walk_kinds(
        kinds, nemotron_h.PERIOD, stacks, body,
        (jnp.zeros((11, 3)), jnp.int32(0))))
    log, n = run()
    assert int(n) == 11
    seen = {k: 0 for k in nemotron_h.PERIOD}
    for kind, row in zip(kinds, np.asarray(log)):
        base = {M: 10, A: 20, E: 30}[kind]
        assert tuple(row) == (code[kind], seen[kind], base + seen[kind])
        seen[kind] += 1
    text = run.lower().as_text()
    # one scan over the periods and one loop of traced length in it
    assert text.count("stablehlo.while") == 2
    assert trunk.walk_kinds([], nemotron_h.PERIOD, stacks, body, "as is") == "as is"


def test_init_params_stacks_by_kind_and_holds_the_share():
    cfg = ModelConfig.from_hf_config(SERVED)
    shapes = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    assert set(shapes) == {"embed", M, A, E, "final_norm", "lm_head"}
    assert shapes[M]["ssm_in"].shape == (5, 4096, 8192 + 10240 + 128)
    assert shapes[M]["conv_w"].shape == (5, 4, 10240)
    assert shapes[A]["wq"].shape == (1, 4096, 4096)
    assert shapes[A]["wk"].shape == (1, 4096, 256)
    moe = shapes[E]
    assert moe["router"].shape == (5, 4096, 512)        # the published width
    assert moe["router_bias"].shape == (5, 512)
    assert moe["router_bias"].dtype == jnp.float32
    assert moe["w_latent_in"].shape == (5, 4096, 1024)
    assert moe["w_up"].shape == (5, 128, 1024, 2688)    # the experts held
    assert moe["w_down"].shape == (5, 128, 2688, 1024)
    assert moe["w_latent_out"].shape == (5, 1024, 4096)
    assert moe["w_sh_up"].shape == (5, 4096, 5376)
    assert "w_gate" not in moe and "w_sh_gate" not in moe   # no gate matrix
    assert "ln2" not in moe and "ln2" not in shapes[M]      # one norm a layer
    assert shapes["lm_head"].shape == (4096, 32768)         # not tied
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == 4648163712                 # ISSUE 62's 4648 M, 9.30 GB
    k, v = jax.eval_shape(lambda: nemotron_h.init_kv_cache(
        cfg, 24576, 16, jnp.bfloat16, num_slots=128))
    assert k.kv.shape == (1, 24576, 16, 2, 128)
    assert k.state.shape == (5, 128, 64, 128, 128) and k.state.dtype == jnp.float32
    assert v.state.shape == (5, 128, 3, 10240) and v.state.dtype == jnp.bfloat16


# ---------- the engine: start-up refusals, the scheduler, the counters ----------

def _engine_config(hf=HF, **over):
    kw = dict(model=_cfg(hf), max_batch_size=SLOTS, max_model_len=512,
              kv_block_size=BLOCK, num_kv_blocks=96, dtype="float32",
              prefill_buckets=[64, 128], max_prefill_tokens_per_step=64,
              seed=11, max_prefill_batch=2)
    kw.update(over)
    return EngineConfig(**kw)


@pytest.mark.parametrize("setting,path,reason", [
    (dict(tp_size=2), "tp_size", "not sharded"),
    (dict(ep_size=2), "ep_size", "expert_share"),
    (dict(spec_ngram_tokens=2), "spec_ngram_tokens", "rolls back"),
    (dict(multi_step_decode=4), "multi_step_decode", "recurrent state"),
])
def test_paths_refused_for_the_family_by_name(setting, path, reason):
    with pytest.raises(ValueError, match=rf"{path} is refused for the "
                                         rf"nemotron_h family.*{reason}"):
        ModelRunner(_engine_config(**setting))


@pytest.fixture(scope="module")
def runner():
    # rank 1 of four: two of the eight experts held
    return ModelRunner(_engine_config(SHARES[1]))


def _request(prompt, max_tokens):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        output_options=OutputOptions(logprobs=0), eos_token_ids=[])
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue())


def test_engine_streams_equal_the_reference_and_count_the_held_picks(runner):
    """Through the scheduler, the records by slot and ``ModelRunner.step``
    as every family: three prompts prefilled in 64-token chunks and
    decoded 24 tokens by a rank that holds two of eight experts; every
    emitted token is the reference's argmax at its log-probability (the
    engine's own weights, the reference given the same share), and the
    step has counted the picks of the five expert layers and those that
    fell on a held expert."""
    prompts = _seqs([150, 70, 9], seed=12)

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
        want = _reference_logprobs(runner.params, prompt + toks, SHARES[1])
        at = np.arange(len(prompt) - 1, len(prompt) + 23)
        np.testing.assert_array_equal(np.argmax(want[at], axis=-1), toks)
        np.testing.assert_allclose(lps, want[at, toks], atol=F32_ATOL)
    assert sched.allocator.used == 0
    rows = {ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in sched.registry.render().splitlines()
            if ln.startswith("dynamo_moe_") and " " in ln}

    def total(name):
        return sum(v for k, v in rows.items() if k.startswith(name + "{"))

    # every token of every prompt and every decoded token but the last
    # went through five expert layers of three picks
    tokens = sum(len(p) + 23 for p in prompts)
    assert total("dynamo_moe_routed_rows_total") == tokens * 5 * 3
    held = total("dynamo_moe_held_picks_total")
    assert 0 < held < tokens * 5 * 3
    # two experts held a layer: a step offers ten slots
    assert total("dynamo_moe_expert_slots_total") % 10 == 0
    assert 0 < total("dynamo_moe_active_experts_total") \
        <= total("dynamo_moe_expert_slots_total")


def test_scopes_in_the_lowered_programs():
    cfg = _cfg()
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = nemotron_h.init_kv_cache(cfg, 32, BLOCK, jnp.float32, num_slots=2)

    def text(s):
        args = (jnp.zeros((2, s), jnp.int32), jnp.zeros((2, s), jnp.int32), cache,
                jnp.zeros((2, 16), jnp.int32), jnp.zeros((2, s), jnp.int32),
                jnp.ones((2,), jnp.int32))
        return jax.jit(lambda *a: nemotron_h.forward(params, cfg, *a)).lower(
            *args).as_text(debug_info=True)

    decode, prefill = text(1), text(64)
    for program in (decode, prefill):
        for scope in ("embed", "ssm/ssm_conv", "attn", "mlp/moe_route",
                      "mlp/moe_latent", "mlp/moe_experts", "mlp/moe_shared",
                      "lm_head"):
            assert scope in program, scope
        # the latent projections stand outside the router's scope
        assert "moe_route/moe_latent" not in program
    assert "ssm/ssm_state" in decode and "ssm/ssm_scan" in prefill


def test_random_weights_serve_logits_of_a_few_units():
    cfg = _cfg()
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    seq = _seqs([64], seed=1)[0]
    want = _reference_logprobs(params, seq)
    logits_std = np.std(want - want.mean(axis=-1, keepdims=True), axis=-1)
    np.testing.assert_allclose(logits_std.mean(), nemotron_h.LOGIT_STD, rtol=0.3)
    assert float(jnp.abs(params[E]["router_bias"]).max()) > 0
    # the heads remember: 1 / (step x A) within the horizons drawn
    step = jax.nn.softplus(params[M]["dt_bias"])
    horizon = 1.0 / (step * jnp.exp(params[M]["A_log"]))
    lo, hi = nemotron_h.STATE_HORIZON
    assert float(horizon.min()) >= lo * 0.99 and float(horizon.max()) <= hi * 1.01
    bc = params[M]["conv_b"][:, cfg.mamba_d_ssm:]
    assert float(bc.min()) >= 0.5 and float(bc.max()) <= 1.5
