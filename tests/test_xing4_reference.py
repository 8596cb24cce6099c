"""The served ``xing4_0`` path (four residual streams mixed around every
sublayer, models/mhc.py, over latent attention with a query bottleneck
and YaRN and routed experts) against the benchmark's plain reference,
``benchmark/references/xing4.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy.

Tiny ``xing4_0`` shape: 1 dense + 2 MoE layers, 8 experts top-3 + 1
shared, a query bottleneck of 24, YaRN over an original length of 16 (so
the cases' positions lie on both sides of it), ``hc_mult`` 4, 20
Sinkhorn iterations and a clamp of +-1.5, narrow enough that it holds a
third of the logits (the published +-30 never engages on random
weights).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import deepseek, llama, mhc

import served  # noqa: E402
from xing4_tiny import (BF16_ATOL, BF16_MEDIAN, F32_ATOL,  # noqa: E402
                        YARN, _cfg, _params, _reference_logprobs, _seqs,
                        _serve, reference)

# the cases of tests/test_deepseek_v3_reference.py; every one has
# positions below and above YaRN's original length of 16 but batch_8
CASES = {
    "block_boundary": dict(lengths=[13 + 4], n_decode=4, chunk=16),
    "chunked": dict(lengths=[21 + 3], n_decode=3, chunk=8),
    "batch_unequal": dict(lengths=[5 + 3, 13 + 3, 17 + 3], n_decode=3, chunk=32),
    "batch_8": dict(lengths=[6 + 2] * 8, n_decode=2, chunk=8),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case, dtype):
    dt = jnp.dtype(dtype)
    cfg, params = _params(dt)
    # the mixing tensors are float32 whatever the trunk's dtype
    assert params["layers"]["hc_attn_phi"].dtype == jnp.float32
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    got = _serve(cfg, params, seqs, c["n_decode"], c["chunk"], dt)
    served.assert_close(got, [_reference_logprobs(params, q) for q in seqs],
                        dtype, F32_ATOL, BF16_MEDIAN, BF16_ATOL, bf16_share=0.9)


def test_norm_weights_of_the_query_bottleneck_and_the_latent():
    """Every norm weight random instead of 1 (``ln_q`` of the query
    bottleneck and ``ln_kv`` among them): both sides apply each where
    the other does."""
    cfg, params = _params(jnp.float32)
    rs = np.random.RandomState(1)
    for group in ("dense_layers", "layers"):
        params[group] = dict(params[group])
        for k in ("ln1", "ln2", "ln_q", "ln_kv"):
            shape = params[group][k].shape
            params[group][k] = jnp.asarray(rs.uniform(0.5, 1.5, shape), jnp.float32)
    seq = _seqs([24], seed=2)[0]
    got = _serve(cfg, params, [seq], 4, 8, jnp.float32)[0]
    np.testing.assert_allclose(got, _reference_logprobs(params, seq), atol=F32_ATOL)


@functools.lru_cache(maxsize=None)
def _sound_run():
    """One sequence of 40 through the sound program and the reference."""
    cfg, params = _params(jnp.float32)
    seq = _seqs([40], seed=3)[0]
    want = _reference_logprobs(params, seq)
    idx = (np.arange(len(seq) - 1), np.asarray(seq[1:]))
    base = _serve(cfg, params, [seq], 8, 16, jnp.float32)[0]
    return cfg, params, seq, want, idx, np.abs(base[:-1][idx] - want[:-1][idx]).mean()


@pytest.mark.parametrize("wrong", [
    "mixing_tensors_bf16", "five_iterations", "no_clamp", "static_only",
    "no_mscale_on_the_softmax"])
def test_reference_tells_wrong_programs_apart(wrong):
    """What a limit has to catch, at the tiny shape in float32: the
    mixing tensors rounded to bfloat16 (a loader that cast them with the
    trunk), 5 Sinkhorn iterations in place of 20, the clamp left out,
    the static mapping ``b`` alone (``phi`` zero: no dependence on the
    token), YaRN without its ``mscale^2`` on the softmax scale: each
    moves the mean |d log p| by orders of magnitude over the served
    path's own, and past F32_ATOL."""
    cfg, params, seq, want, idx, base_err = _sound_run()
    p2 = {k: dict(v) if isinstance(v, dict) else v for k, v in params.items()}
    if wrong == "mixing_tensors_bf16":
        for group in ("dense_layers", "layers"):
            for k in mhc.PARAM_KEYS:
                p2[group][k] = params[group][k].astype(jnp.bfloat16).astype(jnp.float32)
    elif wrong == "five_iterations":
        cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=5)
    elif wrong == "no_clamp":
        cfg = dataclasses.replace(cfg, hc_res_clamp=(-1e9, 1e9))
    elif wrong == "static_only":
        for group in ("dense_layers", "layers"):
            for sub in mhc.SUBLAYERS:
                p2[group][f"hc_{sub}_phi"] = jnp.zeros_like(params[group][f"hc_{sub}_phi"])
    else:
        cfg = dataclasses.replace(cfg, rope_scaling={**YARN, "mscale_all_dim": 0})
    got = _serve(cfg, p2, [seq], 8, 16, jnp.float32, fresh=True)[0]
    err = np.abs(got[:-1][idx] - want[:-1][idx]).mean()
    assert base_err < 1e-5
    assert err > 100 * base_err and err > F32_ATOL


def test_mixing_matrices_are_doubly_stochastic_and_gates_bounded():
    cfg, params = _params(jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    streams = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 4 * 64), jnp.float32)
    for sub in mhc.SUBLAYERS:
        h_pre, h_post, h_res = map(np.asarray, mhc.coefficients(streams, lp, sub, cfg))
        assert h_pre.shape == (4, 15) and h_res.shape == (4, 4, 15)
        assert (h_pre >= 0).all() and (h_pre <= 1).all()
        assert (h_post >= 0).all() and (h_post <= 2).all()
        np.testing.assert_allclose(h_res.sum(axis=0), 1.0, atol=1e-4)   # columns
        np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=1e-4)   # rows
        # token-dependent, and neither the identity nor the uniform matrix
        assert np.abs(h_res[..., 0] - h_res[..., 1]).max() > 0.05
        assert np.abs(h_res - 0.25).max() > 0.1
        assert np.abs(h_res - np.eye(4)[..., None]).max() > 0.1
    # the clamp of this shape engages (the published +-30 would not)
    z = np.asarray(jnp.einsum("tk,kc->tc", streams.reshape(15, -1),
                              lp["hc_attn_phi"]))[:, 8:]
    assert (np.abs(z) > 1.5).mean() > 0.05
    # the fan-out copies and the read-out sums
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 64), jnp.float32)
    np.testing.assert_allclose(mhc.read_out(mhc.fan_out(h, cfg), cfg), 4 * h,
                               rtol=1e-6)


@pytest.mark.parametrize("original", [16, 64])
def test_yarn_alone_against_the_published_formula(original):
    """The engine's rotation (``llama.apply_rope``) and softmax scale
    (``mla_softmax_scale``) against the reference's own YaRN, at
    positions on both sides of the original length."""
    sc = {**YARN, "original_max_position_embeddings": original}
    d, theta = 16, 10000.0
    inv = np.asarray(reference.yarn_inv_freq(d, theta, sc))
    plain = theta ** (-np.arange(0, d, 2) / d)
    assert np.isclose(inv[0], plain[0])              # the fastest keeps its frequency
    assert np.isclose(inv[-1], plain[-1] / 64)       # the slowest is interpolated
    pos = np.asarray([[0, 1, original - 1, original, original + 1, 5 * original, 4000]])
    x = np.random.RandomState(0).standard_normal((1, pos.shape[1], 2, d)).astype(np.float32)
    got = np.asarray(llama.apply_rope(jnp.asarray(x), jnp.asarray(pos), theta, sc))
    ang = pos[0][:, None] * inv[None, :]
    cos, sin = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want, atol=2e-4)   # float32 angles up to 4000 rad
    cfg = dataclasses.replace(_cfg(), rope_scaling=sc)
    m = reference.yarn_mscale(64, 1)
    assert m == pytest.approx(0.1 * np.log(64) + 1)
    assert deepseek.mla_softmax_scale(cfg) == pytest.approx(32 ** -0.5 * m * m)
