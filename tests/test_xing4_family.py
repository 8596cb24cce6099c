"""The ``xing4_0`` family through the engine and the loader: the served
path against the reference itself is tests/test_xing4_reference.py,
whose tiny shape, parameters and reference these tests share (a file of
its own so that the two run on two workers).

``ModelRunner.step`` against the reference, the named scopes, a program
with one stream lowering as before, a checkpoint in published names
with the multi-token-prediction module left out, and every refusal.
"""

import dataclasses
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import deepseek, loader, mhc
from xing4_tiny import (BLOCK, F32_ATOL, HF, _cfg, _params,
                        _reference_logprobs, _seqs, _serve)


def test_model_runner_step_logprobs_equal_reference():
    """Through ModelRunner.step (trunk, head on the sampled position,
    sampling pass): the greedy token's log-probability after prefill and
    after two decode steps, and the prompt's, equal the reference's; the
    carries between programs keep [.., D]."""
    ecfg = EngineConfig(model=_cfg(), max_batch_size=2, max_model_len=64,
                        kv_block_size=BLOCK, num_kv_blocks=32, dtype="float32",
                        prefill_buckets=[32], seed=11)
    runner = ModelRunner(ecfg)
    assert runner.params["layers"]["hc_mlp_phi"].shape == (2, 256, 24)
    assert 'dynamo_engine_model_info{family="deepseek",hc_mult="4"} 1' in \
        runner.compiles.registry.render()
    b, w, s = 2, ecfg.blocks_per_seq, 32
    seqs = _seqs([11, 21], seed=5)
    lens = [len(q) for q in seqs]
    btab = np.arange(b * w, dtype=np.int32).reshape(b, w)
    tok = np.zeros((b, s), np.int32)
    pos = np.zeros((b, s), np.int32)
    slot = np.full((b, s), -1, np.int32)
    targets = np.zeros((b, s), np.int32)
    for i, q in enumerate(seqs):
        n = lens[i]
        tok[i, :n], pos[i, :n], pos[i, n:] = q, np.arange(n), n - 1
        slot[i, :n] = btab[i, pos[i, :n] // BLOCK] * BLOCK + pos[i, :n] % BLOCK
        targets[i, : n - 1] = q[1:]
    zeros, ones = np.zeros(b, np.float32), np.ones(b, np.float32)

    def step(tok, pos, slot, ctx, last, **kw):
        return runner.step(tok, pos, btab, slot, ctx, last, zeros,
                           np.zeros(b, np.int32), ones, jax.random.PRNGKey(0), **kw)

    nt, lps, _, _, prompt_lps, _ = step(
        tok, pos, slot, np.asarray(lens, np.int32),
        np.asarray([n - 1 for n in lens], np.int32),
        targets=targets, want_prompt=True)
    nt, lps, prompt_lps = np.asarray(nt), np.asarray(lps), np.asarray(prompt_lps)
    for _ in range(2):
        for i in range(b):
            seqs[i].append(int(nt[i]))
        p = np.asarray([[len(q) - 1] for q in seqs], np.int32)
        sl = np.stack([btab[i, p[i] // BLOCK] * BLOCK + p[i] % BLOCK for i in range(b)])
        prev_lps = lps
        nt, lps, *_ = step(np.asarray([[q[-1]] for q in seqs], np.int32), p, sl,
                           p[:, 0] + 1, np.zeros(b, np.int32))
        nt, lps = np.asarray(nt), np.asarray(lps)
        for i, q in enumerate(seqs):
            want = _reference_logprobs(runner.params, q)
            assert int(np.argmax(want[len(q) - 2])) == q[-1]
            np.testing.assert_allclose(prev_lps[i], want[len(q) - 2, q[-1]],
                                       atol=F32_ATOL)
    for i, n in enumerate(lens):
        want = _reference_logprobs(runner.params, seqs[i][:n])
        np.testing.assert_allclose(
            prompt_lps[i, : n - 1],
            want[np.arange(n - 1), np.asarray(seqs[i][1:n])], atol=F32_ATOL)


def _decode_args(cfg, dtype=jnp.float32, b=2):
    cache = deepseek.init_kv_cache(cfg, 8, BLOCK, dtype)
    return (jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32), cache,
            jnp.zeros((b, 4), jnp.int32), jnp.zeros((b, 1), jnp.int32),
            jnp.ones((b,), jnp.int32))


def test_scopes_in_the_lowered_decode_program():
    cfg, params = _params(jnp.float32)
    text = jax.jit(lambda *a: deepseek.forward_counted(
        params, cfg, *a)).lower(*_decode_args(cfg)).as_text(debug_info=True)
    for scope in ("attn/mhc_coeff", "attn/mhc_sinkhorn", "attn/mhc_mix",
                  "mlp/mhc_coeff", "mlp/mhc_sinkhorn", "mlp/mhc_mix", "mhc_fan",
                  "mlp/moe_experts", "attn/mla_cache"):
        assert scope in text, scope


def test_one_stream_lowers_without_the_mixing(monkeypatch):
    """``hc_mult`` 1 (every other configuration): the trunk's program is
    what it is without models/mhc.py: none of its functions is called,
    none of its scopes is in the text, and the text is the same with the
    module's functions taken away."""
    hf = {k: v for k, v in HF.items()
          if not k.startswith(("hc_", "mhc_")) and k != "model_type"}
    cfg = dataclasses.replace(ModelConfig.from_hf_config(
        {**hf, "model_type": "deepseek_v3"}), attention_impl="xla")
    assert cfg.hc_mult == 1
    params = deepseek.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    assert not any(k.startswith("hc_") for k in params["layers"])

    def lowered(**kw):
        return jax.jit(lambda *a: deepseek.forward_counted(
            params, cfg, *a)).lower(*_decode_args(cfg)).as_text(**kw)

    # no scope of the module's ("mhc_", not "mhc": a jitted helper jax
    # traced first under models/mhc.py keeps that file in its locations)
    assert "mhc_" not in lowered(debug_info=True)
    with_module = lowered()

    def refuse(*a, **k):
        raise AssertionError("models/mhc.py was called for one stream")

    for name in ("read", "write", "fan_out", "read_out", "coefficients"):
        monkeypatch.setattr(mhc, name, refuse)
    assert lowered() == with_module


def _published_checkpoint(path, rs):
    """A checkpoint of the tiny shape in the names a published one is
    assumed to have, with the multi-token-prediction module's tensors
    under ``model.layers.3``."""
    from safetensors.numpy import save_file

    d, n, h = 64, 4, 4
    t = {"model.embed_tokens.weight": (256, d), "model.norm.weight": (d,),
         "lm_head.weight": (256, d)}

    def layer(i, moe):
        p = f"model.layers.{i}."
        out = {
            "input_layernorm.weight": (d,), "post_attention_layernorm.weight": (d,),
            "self_attn.q_a_proj.weight": (24, d), "self_attn.q_a_layernorm.weight": (24,),
            "self_attn.q_b_proj.weight": (h * 32, 24),
            "self_attn.kv_a_proj_with_mqa.weight": (32 + 16, d),
            "self_attn.kv_a_layernorm.weight": (32,),
            "self_attn.kv_b_proj.weight": (h * 32, 32),
            "self_attn.o_proj.weight": (d, h * 16),
        }
        for mod in loader.XING4_MHC_MODULES.values():
            out.update({
                f"{mod}.phi_pre.weight": (n, n * d), f"{mod}.phi_post.weight": (n, n * d),
                f"{mod}.phi_res.weight": (n * n, n * d), f"{mod}.b_pre": (n,),
                f"{mod}.b_post": (n,), f"{mod}.b_res": (n, n),
                f"{mod}.alpha_pre": (1,), f"{mod}.alpha_post": (1,),
                f"{mod}.alpha_res": (1,)})
        if moe:
            out.update({"mlp.gate.weight": (8, d),
                        "mlp.gate.e_score_correction_bias": (8,)})
            for e in range(8):
                out.update({f"mlp.experts.{e}.gate_proj.weight": (32, d),
                            f"mlp.experts.{e}.up_proj.weight": (32, d),
                            f"mlp.experts.{e}.down_proj.weight": (d, 32)})
            out.update({"mlp.shared_experts.gate_proj.weight": (32, d),
                        "mlp.shared_experts.up_proj.weight": (32, d),
                        "mlp.shared_experts.down_proj.weight": (d, 32)})
        else:
            out.update({"mlp.gate_proj.weight": (128, d), "mlp.up_proj.weight": (128, d),
                        "mlp.down_proj.weight": (d, 128)})
        return {p + k: v for k, v in out.items()}

    for i in range(3):
        t.update(layer(i, moe=i >= 1))
    mtp = layer(3, moe=True)
    mtp.update({"model.layers.3.eh_proj.weight": (d, 2 * d),
                "model.layers.3.enorm.weight": (d,), "model.layers.3.hnorm.weight": (d,),
                "model.layers.3.embed_tokens.weight": (256, d),
                "model.layers.3.shared_head.head.weight": (256, d),
                "model.layers.3.shared_head.norm.weight": (d,)})
    t.update(mtp)
    tensors = {k: rs.standard_normal(s).astype(np.float32) for k, s in t.items()}
    save_file(tensors, os.path.join(path, "model.safetensors"))
    return tensors, len(mtp)


def test_loader_reads_published_names_and_leaves_the_mtp_module_out(tmp_path, caplog):
    tensors, n_mtp = _published_checkpoint(str(tmp_path), np.random.RandomState(0))
    cfg = _cfg()
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.models.loader"):
        params = loader.load_checkpoint_params(str(tmp_path), cfg, deepseek,
                                               jnp.bfloat16)
    said = [r.getMessage() for r in caplog.records if "multi-token" in r.getMessage()]
    assert len(said) == 1 and f"left out {n_mtp} tensors of layers >= 3" in said[0]
    assert params["dense_layers"]["ln1"].shape[0] == 1
    assert params["layers"]["w_gate"].shape == (2, 8, 64, 32)
    assert params["layers"]["w_gate"].dtype == jnp.bfloat16
    # the tree init_params makes, tensor for tensor
    want = jax.eval_shape(lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, params) == \
        jax.tree.map(lambda a: a.shape, want)
    for group, li, idx in (("dense_layers", 0, 0), ("layers", 1, 2)):
        for sub, mod in loader.XING4_MHC_MODULES.items():
            p = f"model.layers.{idx}.{mod}."
            phi = np.asarray(params[group][f"hc_{sub}_phi"][li])
            assert phi.dtype == np.float32       # exact: never through bfloat16
            np.testing.assert_array_equal(phi[:, :4], tensors[p + "phi_pre.weight"].T)
            np.testing.assert_array_equal(phi[:, 4:8], tensors[p + "phi_post.weight"].T)
            np.testing.assert_array_equal(phi[:, 8:], tensors[p + "phi_res.weight"].T)
            b = np.asarray(params[group][f"hc_{sub}_b"][li])
            np.testing.assert_array_equal(b[8:].reshape(4, 4), tensors[p + "b_res"])
            np.testing.assert_array_equal(b[:4], tensors[p + "b_pre"])
            alpha = np.asarray(params[group][f"hc_{sub}_alpha"][li])
            np.testing.assert_array_equal(
                alpha, [tensors[p + f"alpha_{k}"][0] for k in ("pre", "post", "res")])
    # and the loaded tree serves: the reference agrees on it
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seq = _seqs([20], seed=4)[0]
    got = _serve(cfg, p32, [seq], 2, 32, jnp.float32)[0]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _reference_logprobs(p32, seq), atol=1e-3)


def test_a_missing_mixing_tensor_is_an_incomplete_checkpoint(tmp_path):
    from safetensors.numpy import save_file

    tensors, _ = _published_checkpoint(str(tmp_path), np.random.RandomState(0))
    del tensors["model.layers.1.mlp_hc.phi_post.weight"]
    save_file(tensors, os.path.join(str(tmp_path), "model.safetensors"))
    with pytest.raises(ValueError, match="hc_mlp_phi has parts"):
        loader.load_checkpoint_params(str(tmp_path), _cfg(), deepseek, jnp.bfloat16)


@pytest.mark.parametrize("key", ["hc_mult", "mhc_h_res_clamp_max",
                                 "hyper_connection_rate"])
def test_from_hf_refuses_hyper_connection_keys_of_another_model_type(key):
    """A checkpoint with a changed residual path this program has no
    family for is refused at its config, before a weight streams: served
    as deepseek_v3 it would give wrong tokens without a word."""
    hf = {k: v for k, v in HF.items() if not k.startswith(("hc_", "mhc_"))}
    hf["model_type"] = "deepseek_v3"
    assert ModelConfig.from_hf_config(hf).hc_mult == 1
    with pytest.raises(NotImplementedError, match="hyper-connection keys"):
        ModelConfig.from_hf_config({**hf, key: HF.get(key, 2)})


def test_refusals_of_the_mixed_streams():
    # the published keys select the path
    cfg = ModelConfig.from_hf_config(HF)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == \
        (4, 20, 1e-6, (-1.5, 1.5))
    assert models.resolve(cfg) is deepseek
    # no latent attention: no trunk carries the streams
    with pytest.raises(NotImplementedError, match="kv_lora_rank"):
        ModelConfig.from_hf_config({**HF, "kv_lora_rank": None})
    with pytest.raises(NotImplementedError, match="hc_mult=4"):
        models.resolve(ModelConfig(hc_mult=4))
    # a pipeline stage hands [B, S, D] on
    ecfg = EngineConfig(model=_cfg(), max_batch_size=2, max_model_len=64,
                        kv_block_size=BLOCK, num_kv_blocks=32, dtype="float32",
                        prefill_buckets=[16], pp_size=2)
    with pytest.raises(NotImplementedError, match="pp_size 2 is refused with hc_mult 4"):
        ModelRunner(ecfg)
