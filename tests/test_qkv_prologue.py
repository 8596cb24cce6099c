"""``llama.qkv_prologue`` against the plain formula, bit for bit.

The prologue keeps the head reshape out of the three projections with an
``optimization_barrier`` (so that on the chip each product reads its
layer's slice of the stacked weight in place: tests/test_chip_compile.py
holds the compiled program to that). A barrier changes no value; these
cases hold it to that for every family's variant of the prologue, and
run its batching and partitioning rules once each.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import llama, quant

B, S = 4, 3


def plain_prologue(cfg, x, lp, b, s, positions, seq_basis):
    """``x @ w`` (+ bias) -> reshape -> norm / multiplier -> rope, as
    the families' reference implementations write it: no barrier."""
    q, k, v = (quant.dense(x, lp[w]) for w in ("wq", "wk", "wv"))
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if "q_norm" in lp:
        q = llama.rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = llama.rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if cfg.key_multiplier != 1.0:
        k = (k.astype(jnp.float32) * cfg.key_multiplier).astype(k.dtype)
    rope = functools.partial(llama.apply_rope, positions=positions,
                             theta=cfg.rope_theta, scaling=cfg.rope_scaling,
                             seq_basis=seq_basis)
    return rope(q), rope(k), v


def _case(heads, kv_heads, head_dim, *, bias=False, qk_norm=False,
          key_multiplier=1.0, int8=False, dtype=jnp.bfloat16):
    d = heads * head_dim
    cfg = ModelConfig(hidden_size=d, num_heads=heads, num_kv_heads=kv_heads,
                      head_dim=head_dim, num_layers=1, attention_bias=bias)
    cfg = dataclasses.replace(cfg, key_multiplier=key_multiplier)
    keys = iter(jax.random.split(jax.random.PRNGKey(heads * head_dim), 16))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * shape[0] ** -0.5).astype(dtype)

    lp = {"wq": w(d, heads * head_dim), "wk": w(d, kv_heads * head_dim),
          "wv": w(d, kv_heads * head_dim)}
    if bias:
        lp.update(bq=w(heads * head_dim), bk=w(kv_heads * head_dim),
                  bv=w(kv_heads * head_dim))
    if qk_norm:
        lp.update(q_norm=1 + w(head_dim), k_norm=1 + w(head_dim))
    if int8:
        lp = quant.quantize_params(lp)
        assert isinstance(lp["wq"], quant.QuantizedWeight)
    x = jax.random.normal(next(keys), (B, S, d), jnp.float32).astype(dtype)
    positions = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) * 7 % 50
    ctx = positions.max(axis=1) + 1
    return cfg, lp, x, positions, ctx


def _jit(fn, cfg, lp, x, positions, ctx):
    return jax.jit(lambda lp, x, positions, ctx: fn(
        cfg, x, lp, B, S, positions, ctx))(lp, x, positions, ctx)


def _vmapped(fn, cfg, lp, x, positions, ctx):
    # three prompts' worth of rows through one layer's weights
    xs = jnp.stack([x, x * 0.5, -x])
    return jax.jit(jax.vmap(lambda x: fn(cfg, x, lp, B, S, positions, ctx)))(xs)


def _shard_mapped(fn, cfg, lp, x, positions, ctx):
    # rows over two devices, weights whole on each: the manual region a
    # pipelined pp x dp program runs the prologue in
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    rows = P("dp")
    body = jax.shard_map(
        lambda lp, x, positions, ctx: fn(cfg, x, lp, B // 2, S, positions, ctx),
        mesh=mesh, in_specs=(P(), rows, rows, rows), out_specs=rows)
    return jax.jit(body)(lp, x, positions, ctx)


CASES = {
    "mha_head96": (dict(heads=4, kv_heads=4, head_dim=96), _jit),
    "gqa_head128": (dict(heads=4, kv_heads=2, head_dim=128), _jit),
    "qwen2_biases": (dict(heads=4, kv_heads=2, head_dim=64, bias=True), _jit),
    "qwen3_qk_norms": (dict(heads=4, kv_heads=2, head_dim=64, qk_norm=True), _jit),
    "falcon_h1_key_multiplier": (
        dict(heads=5, kv_heads=1, head_dim=128, key_multiplier=0.39), _jit),
    "int8_weights": (dict(heads=4, kv_heads=2, head_dim=64, int8=True), _jit),
    "float32_biases_norms": (
        dict(heads=2, kv_heads=2, head_dim=64, bias=True, qk_norm=True,
             dtype=jnp.float32), _jit),
    "vmap": (dict(heads=4, kv_heads=2, head_dim=64, bias=True), _vmapped),
    "shard_map": (dict(heads=4, kv_heads=2, head_dim=64, qk_norm=True),
                  _shard_mapped),
}


@pytest.mark.parametrize("name", list(CASES))
def test_qkv_prologue_equals_the_plain_formula_bit_for_bit(name):
    kwargs, run = CASES[name]
    case = _case(**kwargs)
    got = run(llama.qkv_prologue, *case)
    want = run(plain_prologue, *case)
    cfg = case[0]
    lead = got[0].shape[:-4]    # vmap's axis, else nothing
    assert got[0].shape == lead + (B, S, cfg.num_heads, cfg.head_dim)
    assert got[2].shape == lead + (B, S, cfg.num_kv_heads, cfg.head_dim)
    for g, w_, which in zip(got, want, "qkv"):
        assert g.dtype == w_.dtype, which
        assert np.isfinite(np.asarray(g, np.float32)).all(), which
        assert np.asarray(g, np.float32).std() > 0.05, which
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_), err_msg=which)


def test_qkv_prologue_holds_the_three_products_behind_one_barrier():
    """What the chip's compile rests on (tests/test_chip_compile.py):
    the three products, biases included, reach one barrier as
    ``[b, s, out]`` and every head reshape comes after it."""
    cfg, lp, x, positions, ctx = _case(heads=4, kv_heads=2, head_dim=64, bias=True)
    jaxpr = jax.make_jaxpr(lambda lp, x: llama.qkv_prologue(
        cfg, x, lp, B, S, positions, ctx))(lp, x).jaxpr
    names = [e.primitive.name for e in jaxpr.eqns]
    assert names.count("optimization_barrier") == 1
    at = names.index("optimization_barrier")
    assert names[:at].count("dot_general") == 3 and "dot_general" not in names[at:]
    assert "reshape" not in names[:at] and names[at:].count("reshape") >= 3
    barrier = jaxpr.eqns[at]
    assert [v.aval.shape for v in barrier.invars] == [
        (B, S, 256), (B, S, 128), (B, S, 128)]
