"""Phi-3 (fused qkv/gate_up checkpoints) and Qwen3 (per-head q/k norms)
— both served by the llama trunk, validated logit-exact vs HF."""

import inspect
import json
import os
import re

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu import models
from dynamo_tpu.models import llama, resolve
from dynamo_tpu.models.loader import load_checkpoint_params

from fixtures import make_model_dir

PROMPT = [1, 17, 43, 99, 7, 3, 250, 12, 5, 77]


def _save(tmp, name, hf_cls, hf_cfg):
    import torch

    d = make_model_dir(tmp, name=name)
    torch.manual_seed(0)
    hf_cls(hf_cfg).save_pretrained(d, safe_serialization=True)
    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c["eos_token_id"] = 2
    c["bos_token_id"] = 1
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    return d


def _hf_reference(model_dir, hf_cls):
    import torch

    model = hf_cls.from_pretrained(
        model_dir, torch_dtype=torch.float32, attn_implementation="eager"
    )
    model.eval()
    with torch.no_grad():
        logits = model(torch.tensor([PROMPT])).logits[0].numpy()
        gen = model.generate(
            torch.tensor([PROMPT]), max_new_tokens=8, do_sample=False,
        )[0][len(PROMPT):].tolist()
    return logits, gen


def _our_logits(model_dir):
    cfg = ModelConfig.from_model_dir(model_dir)
    cfg.attention_impl = "xla"
    arch = resolve(cfg)
    assert arch is llama
    params = load_checkpoint_params(model_dir, cfg, arch, jnp.float32)
    s = len(PROMPT)
    k, v = llama.init_kv_cache(cfg, 16, 8, jnp.float32)
    logits, _ = llama.forward(
        params, cfg, jnp.asarray([PROMPT], jnp.int32),
        jnp.arange(s, dtype=jnp.int32)[None], (k, v),
        jnp.arange(4, dtype=jnp.int32)[None],
        jnp.arange(s, dtype=jnp.int32)[None],
        jnp.asarray([s], jnp.int32),
    )
    return np.asarray(logits[0])


async def _engine_greedy(model_dir, n):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.serving import JaxServingEngine
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    mdc = ModelDeploymentCard.from_local_path(model_dir)
    mcfg = ModelConfig.from_model_dir(model_dir)
    mcfg.attention_impl = "xla"
    engine = await JaxServingEngine.create(
        mdc, engine_config=EngineConfig(
            model=mcfg, max_batch_size=2, max_model_len=64, kv_block_size=8,
            num_kv_blocks=32, dtype="float32",
        ), warmup=False)
    req = PreprocessedRequest(
        token_ids=PROMPT,
        stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
    )
    toks = []
    async for out in engine.generate(Context(req)):
        toks.extend(out["token_ids"])
    await engine.close()
    return toks


@pytest.fixture(scope="module")
def phi3_dir(tmp_path_factory):
    from transformers import Phi3Config, Phi3ForCausalLM

    cfg = Phi3Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, pad_token_id=0,
    )
    return _save(tmp_path_factory.mktemp("phi3"), "tiny-phi3",
                 Phi3ForCausalLM, cfg)


@pytest.fixture(scope="module")
def qwen3_dir(tmp_path_factory):
    from transformers import Qwen3Config, Qwen3ForCausalLM

    cfg = Qwen3Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-5,
        rope_theta=10000.0, tie_word_embeddings=False,
    )
    return _save(tmp_path_factory.mktemp("qwen3"), "tiny-qwen3",
                 Qwen3ForCausalLM, cfg)


def test_phi3_sliding_window_logits_match_hf(tmp_path):
    # whole-model sliding window (mistral/phi3 semantics): window smaller
    # than the prompt so the mask bites, compared against HF eager
    from transformers import Phi3Config, Phi3ForCausalLM

    cfg = Phi3Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, pad_token_id=0, sliding_window=4,
    )
    d = _save(tmp_path, "tiny-phi3-sw", Phi3ForCausalLM, cfg)
    mc = ModelConfig.from_model_dir(d)
    assert mc.sliding_window == 4
    hf_logits, _ = _hf_reference(d, Phi3ForCausalLM)
    np.testing.assert_allclose(
        _our_logits(d), hf_logits, rtol=2e-4, atol=2e-4
    )


def test_phi3_longrope_both_profiles_match_hf(tmp_path):
    """Phi-3 128k-style longrope: the short profile (prompt inside the
    pretraining window) and the long profile (prompt beyond it) must
    both match HF, including the always-on attention factor."""
    import torch
    from transformers import Phi3Config, Phi3ForCausalLM

    cfg = Phi3Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, original_max_position_embeddings=16,
        rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False,
        pad_token_id=0,
        rope_scaling={
            "type": "longrope",
            "short_factor": [1.0 + 0.1 * i for i in range(8)],
            "long_factor": [2.0 + 0.5 * i for i in range(8)],
        },
    )
    d = _save(tmp_path, "tiny-phi3-lr", Phi3ForCausalLM, cfg)

    model = Phi3ForCausalLM.from_pretrained(
        d, torch_dtype=torch.float32, attn_implementation="eager")
    model.eval()
    mc = ModelConfig.from_model_dir(d)
    mc.attention_impl = "xla"
    params = load_checkpoint_params(d, mc, llama, jnp.float32)

    def ours(prompt):
        s = len(prompt)
        k, v = llama.init_kv_cache(mc, 16, 8, jnp.float32)
        logits, _ = llama.forward(
            params, mc, jnp.asarray([prompt], jnp.int32),
            jnp.arange(s, dtype=jnp.int32)[None], (k, v),
            jnp.arange(8, dtype=jnp.int32)[None],
            jnp.arange(s, dtype=jnp.int32)[None],
            jnp.asarray([s], jnp.int32),
        )
        return np.asarray(logits[0])

    short_prompt = PROMPT               # 10 tokens <= 16: short profile
    long_prompt = (PROMPT * 3)[:24]     # 24 tokens  > 16: long profile
    for prompt in (short_prompt, long_prompt):
        with torch.no_grad():
            hf = model(torch.tensor([prompt])).logits[0].numpy()
        np.testing.assert_allclose(ours(prompt), hf, rtol=2e-4, atol=2e-4)


def test_longrope_profile_is_per_row():
    # a long-context request co-batched with a short one must not flip
    # the short row onto the long profile
    from dynamo_tpu.models.llama import apply_rope

    scaling = {
        "type": "longrope",
        "short_factor": [1.0 + 0.1 * i for i in range(8)],
        "long_factor": [2.0 + 0.5 * i for i in range(8)],
        "original_max_position_embeddings": 16,
        "max_position_embeddings": 64,
    }
    x = jnp.ones((2, 4, 2, 16), jnp.float32)
    positions = jnp.tile(jnp.arange(4, dtype=jnp.int32)[None], (2, 1))
    mixed = apply_rope(x, positions, 10000.0, scaling,
                       seq_basis=jnp.asarray([10, 40], jnp.int32))
    alone = apply_rope(x[:1], positions[:1], 10000.0, scaling,
                       seq_basis=jnp.asarray([10], jnp.int32))
    np.testing.assert_allclose(np.asarray(mixed[0]), np.asarray(alone[0]),
                               rtol=1e-6)
    # and the long row really uses a different profile
    assert not np.allclose(np.asarray(mixed[1]), np.asarray(mixed[0]))


def test_phi3_logits_match_hf(phi3_dir):
    from transformers import Phi3ForCausalLM

    hf_logits, _ = _hf_reference(phi3_dir, Phi3ForCausalLM)
    np.testing.assert_allclose(
        _our_logits(phi3_dir), hf_logits, rtol=2e-4, atol=2e-4
    )


def test_qwen3_logits_match_hf(qwen3_dir):
    from transformers import Qwen3ForCausalLM

    hf_logits, _ = _hf_reference(qwen3_dir, Qwen3ForCausalLM)
    np.testing.assert_allclose(
        _our_logits(qwen3_dir), hf_logits, rtol=2e-4, atol=2e-4
    )


@pytest.mark.asyncio
async def test_phi3_engine_greedy_matches_hf(phi3_dir):
    from transformers import Phi3ForCausalLM

    _, hf_gen = _hf_reference(phi3_dir, Phi3ForCausalLM)
    assert await _engine_greedy(phi3_dir, 8) == hf_gen


@pytest.mark.asyncio
async def test_qwen3_engine_greedy_matches_hf(qwen3_dir):
    from transformers import Qwen3ForCausalLM

    _, hf_gen = _hf_reference(qwen3_dir, Qwen3ForCausalLM)
    assert await _engine_greedy(qwen3_dir, 8) == hf_gen


# ---------- the table of families (models/__init__.py) ----------

# a published config no row names, and nothing a family claims in it
PLAIN_HF = {"model_type": "some_other_trunk",
            "architectures": ["SomeOtherForCausalLM"], "vocab_size": 64,
            "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 2}
# a ModelConfig with the row's own field set
# the surface as models/__init__.py states it: the names of its required
# paragraph, and those each optional bullet gives before its colon
_REQUIRED_DOC, _OPTIONAL_DOC = models.__doc__.split("**Required**")[1].split(
    "**Optional**")
_NAME = re.compile(r"``(\w+)[(`]")
REQUIRED = set(_NAME.findall(_REQUIRED_DOC)) - {"ModelRunner"}
OPTIONAL = {name for bullet in _OPTIONAL_DOC.split("\n- ")[1:]
            for name in _NAME.findall(bullet.split(":")[0])}
FIELD_VALUES = {"hc_mult": 4, "mamba_d_ssm": 64, "block_length": 4,
                "residual_multiplier": 0.22, "kda_num_heads": 4,
                "index_topk": 32, "swa_num_kv_heads": 8,
                "moe_latent_size": 32,
                "mixer_types": ("minicpm4", "lightning-attn"),
                "layer_types": ("sliding_attention", "full_attention")}


@pytest.mark.parametrize("row", models.FAMILIES, ids=lambda row: row.name)
def test_a_family_is_one_module_and_one_row(row):
    """A case a row: the module has the required surface, in the one
    signature the engine calls (what else the engine asks a family for
    is held to the docstring's list below); every
    published key the row claims is refused by name under another
    ``model_type``; ``resolve`` refuses the row's field when the row is
    not the one selected."""
    module = row.module
    assert module.__name__ == f"dynamo_tpu.models.{row.name}"
    assert REQUIRED == {"init_params", "param_specs", "init_kv_cache",
                        "forward", "logits_from_hidden"}
    for name in REQUIRED:
        assert callable(getattr(module, name)), name
    for name in ("num_slots", "window_blocks"):
        assert name in inspect.signature(module.init_kv_cache).parameters
    assert "state_slots" in inspect.signature(module.forward).parameters
    if row.model_types or row.architecture:
        assert callable(module.config_fields)
    if row.staged:
        assert getattr(module, "SEQUENCE_STATE", models.PAGES_ONLY) \
            is models.PAGES_ONLY
    if not row.field:
        assert not hasattr(module, "claimed_keys")
        return
    claimed = (tuple(getattr(module, "CLAIMED_KEYS", ()))
               + tuple(p + "x" for p in getattr(module, "CLAIMED_PREFIXES", ())))
    assert claimed
    for key in claimed:
        with pytest.raises(NotImplementedError,
                           match=f"some_other_trunk.*{key}"):
            ModelConfig.from_hf_config({**PLAIN_HF, key: 2})
    assert ModelConfig.from_hf_config(PLAIN_HF).model_family == ""
    stray = ModelConfig(**{row.field: FIELD_VALUES[row.field]})
    assert stray.model_family == ""
    with pytest.raises(NotImplementedError, match=row.field):
        models.resolve(stray)


def test_the_engine_asks_a_family_for_listed_names_only():
    """``engine/`` and ``parallel/`` read a family's module under the
    names ``models/__init__.py`` lists, and name no family's module."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    listed = REQUIRED | OPTIONAL | {"__name__"}
    names = "|".join(row.name for row in models.FAMILIES if row.name != "llama")
    for rel in ("engine/model_runner.py", "engine/scheduler.py",
                "engine/config.py", "parallel/pipeline.py"):
        with open(os.path.join(root, "dynamo_tpu", rel)) as f:
            src = f.read()
        asked = set(re.findall(r'getattr\((?:self\.)?arch,\s*"(\w+)"', src))
        asked |= set(re.findall(r"\b(?:self\.)?arch\.(\w+)", src))
        assert asked <= listed, (rel, asked - listed)
        code = "\n".join(line.split("#")[0] for line in src.splitlines())
        code = re.sub(r'"""(?:.|\n)*?"""', "", code)
        assert not re.search(rf"models(?: import|\.)\s*(?:{names})\b", code), rel


# ---------- claims that two families share (PR 48) ----------

_MIXER = {"mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
          "mamba_n_groups": 1, "mamba_d_conv": 4}
_GRANITE = {"model_type": "granitemoehybrid", "vocab_size": 64,
            "hidden_size": 32, "intermediate_size": 16,
            "shared_intermediate_size": 24, "num_hidden_layers": 2,
            "layer_types": ["mamba", "attention"], "num_attention_heads": 2,
            "num_local_experts": 4, "num_experts_per_tok": 2,
            "mamba_expand": 2, "residual_multiplier": 0.22,
            "attention_multiplier": 0.0625, "logits_scaling": 16,
            "embedding_multiplier": 12, "tie_word_embeddings": True, **_MIXER}
_FALCON = {"model_type": "falcon_h1", "vocab_size": 64, "hidden_size": 32,
           "intermediate_size": 48, "num_hidden_layers": 2,
           "num_attention_heads": 2, "mamba_d_ssm": 64, **_MIXER}


@pytest.mark.parametrize("hf,family,mixer_only", [
    (_GRANITE, "granite_hybrid", True), (_FALCON, "falcon_h1", False)])
def test_two_families_compute_the_state_space_keys(hf, family, mixer_only):
    """``mamba_*`` is Falcon-H1's claim and Granite 4.0-H's, a mixed
    ``layer_types`` afmoe's and Granite 4.0-H's: a config reaches the row
    its ``model_type`` names, with the other's fields unset."""
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_family == family and models.family(cfg).name == family
    assert cfg.mamba_d_ssm == 64 and cfg.mamba_n_heads == 4
    assert bool(cfg.layer_types) is mixer_only
    assert (cfg.residual_multiplier != 1.0) is mixer_only


_KIMI = {"model_type": "kimi_linear", "vocab_size": 64, "hidden_size": 32,
         "intermediate_size": 48, "moe_intermediate_size": 16,
         "num_hidden_layers": 3, "num_attention_heads": 2,
         "linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [3],
                                "num_heads": 2, "head_dim": 16,
                                "short_conv_kernel_size": 4},
         "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "mla_use_nope": True, "first_k_dense_replace": 1,
         "num_experts": 2, "expert_share": {"of_experts": 8, "rank": 3},
         "num_experts_per_token": 2, "num_shared_experts": 1,
         "routed_scaling_factor": 2.446}


def test_a_latent_config_with_kda_layers_reaches_its_own_row():
    """``kimi_linear`` has ``kv_lora_rank > 0``, deepseek's shape rule:
    its row stands first and takes it by name, with ``expert_share``
    (Granite's claim too) and ``num_shared_experts`` (afmoe's) its own
    under this ``model_type``; a latent config with a stray
    ``kda_num_heads`` is refused for deepseek."""
    cfg = ModelConfig.from_hf_config(_KIMI)
    assert cfg.model_family == "kimi_linear" and cfg.kv_lora_rank == 16
    assert models.family(cfg).name == "kimi_linear"
    assert models.FAMILIES.index(models.family(cfg)) < next(
        i for i, r in enumerate(models.FAMILIES) if r.name == "deepseek")
    assert cfg.layer_types == ("kda", "kda", "mla")
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (2, 8, 3)
    assert cfg.mamba_d_ssm == 0 and cfg.residual_multiplier == 1.0
    import dataclasses
    stray = dataclasses.replace(cfg, model_family="")
    with pytest.raises(NotImplementedError, match="kda_num_heads"):
        models.resolve(stray)
    with pytest.raises(NotImplementedError,
                       match="some_other_trunk.*linear_attn_config") as e:
        ModelConfig.from_hf_config(
            {**PLAIN_HF, "linear_attn_config": _KIMI["linear_attn_config"]})
    assert "kimi_linear" in str(e.value)


_DOTS3 = {"model_type": "dots3_note", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 48, "moe_intermediate_size": 16,
          "num_hidden_layers": 3, "first_k_dense_replace": 1,
          "layer_types": ["full_attention", "full_attention",
                          "sliding_attention"],
          "num_attention_heads": 2, "q_lora_rank": 16, "kv_lora_rank": 16,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "swa_num_attention_heads": 1, "swa_q_lora_rank": 16,
          "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
          "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16,
          "swa_rope_theta": 50000, "sliding_window_size": 9,
          "index_topk": 8, "index_n_heads": 2, "index_head_dim": 16,
          "attention_gate_type": "headwise",
          "swa_attention_gate_type": "headwise",
          "apply_mla_qkv_lora_rescale": True, "n_routed_experts": 2,
          "expert_share": {"of_experts": 8, "rank": 1}, "n_shared_experts": 1,
          "num_experts_per_tok": 2, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc"}


def test_a_latent_config_with_an_indexer_reaches_its_own_row():
    """``dots3_note`` has ``kv_lora_rank > 0``, deepseek's shape rule,
    and a mixed ``layer_types``, afmoe's claim: its row stands before
    both and takes it by name, with ``expert_share`` its own under this
    ``model_type``; a latent config with a stray ``index_topk`` is
    refused for deepseek, and the ``swa_*`` / ``index_*`` keys under
    another ``model_type`` by name."""
    cfg = ModelConfig.from_hf_config(_DOTS3)
    assert cfg.model_family == "dots3" and models.family(cfg).name == "dots3"
    assert models.FAMILIES.index(models.family(cfg)) < next(
        i for i, r in enumerate(models.FAMILIES) if r.name == "deepseek")
    assert cfg.layer_types == tuple(_DOTS3["layer_types"])
    assert (cfg.sliding_window, cfg.index_topk, cfg.swa_kv_lora_rank) == (9, 8, 32)
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (2, 8, 1)
    assert cfg.kda_num_heads == 0 and cfg.hc_mult == 1
    assert models.family(cfg).module.SEQUENCE_STATE.window_pool
    import dataclasses
    with pytest.raises(NotImplementedError, match="index_topk"):
        models.resolve(dataclasses.replace(cfg, model_family=""))
    with pytest.raises(NotImplementedError,
                       match="some_other_trunk.*index_topk") as e:
        ModelConfig.from_hf_config({**PLAIN_HF, "index_topk": 8,
                                    "swa_kv_lora_rank": 32})
    assert "dots3" in str(e.value)
    # the key under the model_type that computes a window without it
    with pytest.raises(NotImplementedError, match="sliding_window_size"):
        ModelConfig.from_hf_config({
            "model_type": "afmoe", "vocab_size": 64, "hidden_size": 32,
            "num_hidden_layers": 2, "num_attention_heads": 2,
            "layer_types": ["sliding_attention", "full_attention"],
            "sliding_window": 8, "sliding_window_size": 9})


def test_a_window_and_full_config_with_its_own_kv_heads_reaches_its_own_row():
    """``mimo_v2`` has routed experts, mixtral's shape rule, and the
    ``swa_*`` keys, ``sliding_window_size`` and ``expert_share`` that
    dots3 (and Granite, Kimi) claim: its row stands before the rows told
    by shape and takes it by name, those keys its own under this
    ``model_type``; its ``hybrid_layer_pattern`` becomes the
    ``layer_types`` the engine's window pool reads, and a config of
    another family with a stray ``swa_num_kv_heads`` is refused."""
    hf = {"model_type": "mimo_v2", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 48, "moe_intermediate_size": 16,
          "num_hidden_layers": 3, "hybrid_layer_pattern": [0, 1, 1],
          "moe_layer_freq": [0, 1, 1], "num_attention_heads": 4,
          "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
          "swa_num_attention_heads": 4, "head_dim": 24, "swa_head_dim": 24,
          "v_head_dim": 16, "swa_v_head_dim": 16, "sliding_window": 8,
          "sliding_window_size": 8, "partial_rotary_factor": 0.334,
          "attention_value_scale": 0.707, "swa_rope_theta": 10000,
          "add_swa_attention_sink_bias": True, "n_routed_experts": 2,
          "expert_share": {"of_experts": 8, "rank": 3},
          "num_experts_per_tok": 2, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "layernorm_epsilon": 1e-6}
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_family == "mimo_v2" and models.family(cfg).name == "mimo_v2"
    assert models.FAMILIES.index(models.family(cfg)) < next(
        i for i, r in enumerate(models.FAMILIES) if r.name == "mixtral")
    assert cfg.layer_types == ("full_attention", "sliding_attention",
                               "sliding_attention")
    assert (cfg.num_kv_heads, cfg.swa_num_kv_heads, cfg.first_k_dense_replace,
            cfg.rms_norm_eps) == (1, 2, 1, 1e-6)
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (2, 8, 3)
    assert cfg.index_topk == 0 and cfg.kv_lora_rank == 0
    assert models.family(cfg).module.SEQUENCE_STATE.window_pool
    import dataclasses
    with pytest.raises(NotImplementedError, match="layer_types"):
        models.resolve(dataclasses.replace(cfg, model_family=""))
    with pytest.raises(NotImplementedError, match="swa_num_kv_heads"):
        models.resolve(dataclasses.replace(cfg, model_family="",
                                           layer_types=()))
    # dots3's own config keeps its swa_* keys: this family claims them
    # under its own model_type only
    assert ModelConfig.from_hf_config(_DOTS3).model_family == "dots3"


def test_a_pattern_of_single_sublayers_reaches_its_own_row():
    """``nemotron_h`` has routed experts, mixtral's shape rule, and the
    keys Falcon-H1 claims for every published trunk with recurrent layers
    (``hybrid_override_pattern``, ``conv_kernel``, ``mamba_*``, ``ssm_*``)
    and ``expert_share`` (Granite's, Kimi's): its row stands before the
    rows told by shape and takes it by name, those keys its own under
    this ``model_type``; the letters become the ``layer_types`` of one
    sublayer a layer, and a config of another family with a stray
    ``moe_latent_size`` is refused."""
    hf = {"model_type": "nemotron_h", "vocab_size": 64, "hidden_size": 32,
          "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
          "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
          "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 8,
          "n_groups": 2, "conv_kernel": 4, "chunk_size": 16, "expand": 2,
          "mlp_hidden_act": "relu2", "moe_latent_size": 16,
          "moe_intermediate_size": 24, "intermediate_size": 24,
          "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
          "n_routed_experts": 2, "expert_share": {"of_experts": 8, "rank": 3},
          "num_experts_per_tok": 3, "routed_scaling_factor": 5,
          "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E"}
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_family == "nemotron_h"
    assert models.family(cfg).name == "nemotron_h"
    assert models.FAMILIES.index(models.family(cfg)) < next(
        i for i, r in enumerate(models.FAMILIES) if r.name == "mixtral")
    assert cfg.layer_types == ("mamba", "moe", "mamba", "attention", "moe")
    assert (cfg.mamba_d_ssm, cfg.mamba_n_groups, cfg.mamba_d_state) == (64, 2, 8)
    assert (cfg.moe_latent_size, cfg.mlp_hidden_act,
            cfg.shared_intermediate_size) == (16, "relu2", 48)
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (2, 8, 3)
    assert cfg.residual_multiplier == 1.0 and cfg.kv_lora_rank == 0
    assert models.family(cfg).module.SEQUENCE_STATE.slots
    import dataclasses
    # mixtral's row would take it by shape, and is refused its fields
    with pytest.raises(NotImplementedError, match="mamba_d_ssm"):
        models.resolve(dataclasses.replace(cfg, model_family=""))
    with pytest.raises(NotImplementedError, match="moe_latent_size"):
        models.resolve(dataclasses.replace(cfg, model_family="",
                                           mamba_d_ssm=0, layer_types=()))
    # Granite's own config keeps its mamba_* keys and its expert_share
    assert ModelConfig.from_hf_config(_GRANITE).model_family == "granite_hybrid"
    assert ModelConfig.from_hf_config(_KIMI).model_family == "kimi_linear"


@pytest.mark.parametrize("keys,named", [
    (_MIXER, "mamba_"),
    ({"hybrid_override_pattern": "MEM*E"}, "hybrid_override_pattern"),
    ({"layer_types": ["mamba", "attention"]}, "layer_types"),
    ({"layer_types": ["sliding_attention", "full_attention"]}, "layer_types"),
    ({"residual_multiplier": 0.22}, "residual_multiplier"),
    ({"expert_share": {"of_experts": 8, "rank": 0}}, "expert_share"),
])
def test_a_third_model_type_with_shared_keys_is_refused_by_name(keys, named):
    """Under a ``model_type`` no row names, what two families claim is
    still refused, and the sentence names both families that compute
    it."""
    with pytest.raises(NotImplementedError,
                       match=f"some_other_trunk.*{named}") as e:
        ModelConfig.from_hf_config({**PLAIN_HF, **keys})
    assert "granite_hybrid" in str(e.value)
    if named in ("mamba_", "hybrid_override_pattern"):
        assert "falcon_h1" in str(e.value) and "minicpm_sala" in str(e.value)
        assert "nemotron_h" in str(e.value)
    # and under a family that does not compute them
    with pytest.raises(NotImplementedError, match=named):
        ModelConfig.from_hf_config(
            {**PLAIN_HF, "model_type": "sdar_moe", "block_length": 4, **keys})


def test_a_stray_shared_field_is_refused_for_every_other_row():
    """``family()`` lets the row that reads another's field have it
    (``Family.reads``) and no other."""
    granite = ModelConfig.from_hf_config(_GRANITE)
    assert models.family(granite).reads == ("mamba_d_ssm", "layer_types")
    stray = ModelConfig(mamba_d_ssm=64, layer_types=("mamba", "attention"))
    with pytest.raises(NotImplementedError, match="mamba_d_ssm"):
        models.resolve(stray)
    falcon = ModelConfig.from_hf_config(_FALCON)
    import dataclasses
    with pytest.raises(NotImplementedError, match="layer_types"):
        models.resolve(dataclasses.replace(
            falcon, layer_types=("mamba", "attention")))
