"""Interpret-mode differentials for the kernel campaign.

Two kernels, each checked against the engine's pre-existing XLA
formulation (the same strategy as tests/test_pallas_decode.py):

- the sequence-parallel ring-prefill's paged prefix walk
  (ops/pallas_sp.py via parallel/sequence.sp_chunk_attention) vs the
  XLA gather route, plus a jaxpr audit that the kernel route never
  materializes the gathered [1, W·bs, KVH, D] prefix;
- the verify kernel's softcap / sinks / fp8-KV specializations
  (ops/pallas_decode.paged_verify_attention) vs the gather/softmax
  reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import sampling as S
from dynamo_tpu.ops.attention import paged_attention
from dynamo_tpu.ops.pallas_decode import paged_verify_attention
from dynamo_tpu.parallel.mesh import make_mesh
from dynamo_tpu.parallel.sequence import sp_chunk_attention


# --------------------------------------------------------------------------
# SP ring-prefill: paged prefix-walk kernel vs the XLA gather route
# --------------------------------------------------------------------------

_SP_DIMS = dict(b=1, s=16, h=4, kvh=2, d=16, L=2, N=8, bs=8, W=8)


def _sp_case(seed=0):
    rng = np.random.default_rng(seed)
    c = _SP_DIMS
    q = jnp.asarray(rng.normal(size=(c["b"], c["s"], c["h"], c["d"])),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(c["b"], c["s"], c["kvh"], c["d"])),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(c["b"], c["s"], c["kvh"], c["d"])),
                    jnp.float32)
    kc = jnp.asarray(
        rng.normal(size=(c["L"], c["N"], c["bs"], c["kvh"], c["d"])),
        jnp.float32)
    vc = jnp.asarray(
        rng.normal(size=(c["L"], c["N"], c["bs"], c["kvh"], c["d"])),
        jnp.float32)
    btab = jnp.asarray(rng.permutation(c["N"])[: c["W"]], jnp.int32)[None, :]
    return q, k, v, kc, vc, btab


@pytest.mark.parametrize(
    "chunk_start,context_len",
    [
        (24, 37),   # multi-page committed prefix ending mid-page
        (0, 13),    # first chunk: empty prefix, ring pass only
        (19, 35),   # prefix boundary mid-page (partial last page DMA)
    ],
)
def test_sp_kernel_matches_gather_route(chunk_start, context_len):
    """The kernel route (ring partials over fresh K/V + the paged
    prefix walk, exp-weighted merge) must match the gather route's one
    joint softmax row-for-row."""
    q, k, v, kc, vc, btab = _sp_case()
    mesh = make_mesh({"sp": 4})
    ref = sp_chunk_attention(
        q, k, v, kc, vc, btab, chunk_start, context_len, 1, mesh,
        impl="xla",
    )
    out = sp_chunk_attention(
        q, k, v, kc, vc, btab, chunk_start, context_len, 1, mesh,
        impl="pallas", interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            items = val if isinstance(val, (list, tuple)) else [val]
            for item in items:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)


def _materializes_prefix(fn, *args):
    """Does any intermediate in fn's jaxpr carry the full gathered
    prefix — a [*, W·bs, ...] array (every cache slot widthwise)?"""
    full = _SP_DIMS["W"] * _SP_DIMS["bs"]
    jaxpr = jax.make_jaxpr(fn)(*args)
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = getattr(getattr(var, "aval", None), "shape", ())
            if len(shape) >= 4 and full in shape:
                return True
    return False


def test_sp_kernel_route_never_materializes_the_prefix():
    """The point of the page-walk kernel: the committed prefix streams
    page-by-page through the DMA scratch and NEVER exists as a
    [1, W·bs, KVH, D] array. The gather route is the positive control —
    its jaxpr must show the materialized prefix this audit looks for."""
    q, k, v, kc, vc, btab = _sp_case()
    mesh = make_mesh({"sp": 4})

    def route(impl):
        return lambda *a: sp_chunk_attention(
            *a, 24, 37, 1, mesh, impl=impl, interpret=(impl == "pallas"),
        )

    assert _materializes_prefix(route("xla"), q, k, v, kc, vc, btab)
    assert not _materializes_prefix(route("pallas"), q, k, v, kc, vc, btab)


# --------------------------------------------------------------------------
# verify kernel specializations: softcap / sinks / fp8 KV
# --------------------------------------------------------------------------


def _verify_case(seed, layers=2, b=2, h=4, kvh=2, d=32, bs=8, w=8, s=4):
    rng = np.random.default_rng(seed)
    n_blocks = b * w + 3
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32)
    v_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(n_blocks)[: b * w].reshape(b, w), jnp.int32)
    ctx = jnp.asarray([29, 53], jnp.int32)
    positions = (ctx - s)[:, None] + jnp.arange(s)[None, :]
    return q, k_cache, v_cache, bt, ctx, positions, s


def test_verify_softcap_matches_xla_reference():
    """Gemma-2-class verify: logit soft-capping is a static Mosaic
    specialization of the verify kernel, checked against the gather
    reference's cap·tanh(logits/cap)."""
    q, kc, vc, bt, ctx, positions, s = _verify_case(21)
    ref = paged_attention(q, kc[1], vc[1], bt, positions, ctx, softcap=30.0)
    out = paged_verify_attention(
        q, kc, vc, bt, ctx - s, ctx,
        layer_idx=jnp.int32(1), interpret=True, softcap=30.0,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )


def test_verify_sinks_matches_xla_reference():
    """GPT-OSS-class verify: per-head sink logits join each query's
    softmax denominator (no value contribution), alongside the runtime
    sliding window the family alternates."""
    rng = np.random.default_rng(22)
    q, kc, vc, bt, ctx, positions, s = _verify_case(22)
    sinks = jnp.asarray(rng.standard_normal(q.shape[2]), jnp.float32)
    ref = paged_attention(
        q, kc[0], vc[0], bt, positions, ctx,
        sliding_window=16, sinks=sinks,
    )
    out = paged_verify_attention(
        q, kc, vc, bt, ctx - s, ctx,
        layer_idx=jnp.int32(0), interpret=True,
        window=jnp.asarray(16, jnp.int32), sinks=sinks,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("variant", ["plain", "softcap", "sinks"])
def test_verify_fp8_kv_matches_xla_reference(variant):
    """fp8 KV serving x verify: the cache stores e4m3 and the kernel
    upcasts after the DMA — compared against the gather reference over
    the SAME stored values (upcast at the gather), so the check is
    exact, not a quantization-error bound."""
    rng = np.random.default_rng(23)
    q, kc, vc, bt, ctx, positions, s = _verify_case(23)
    kf8 = kc.astype(jnp.float8_e4m3fn)
    vf8 = vc.astype(jnp.float8_e4m3fn)
    k32 = kf8.astype(jnp.float32)
    v32 = vf8.astype(jnp.float32)
    ref_kw, kern_kw = {}, {}
    if variant == "softcap":
        ref_kw["softcap"] = kern_kw["softcap"] = 30.0
    elif variant == "sinks":
        sinks = jnp.asarray(rng.standard_normal(q.shape[2]), jnp.float32)
        ref_kw = dict(sliding_window=16, sinks=sinks)
        kern_kw = dict(window=jnp.asarray(16, jnp.int32), sinks=sinks)
    ref = paged_attention(q, k32[1], v32[1], bt, positions, ctx, **ref_kw)
    out = paged_verify_attention(
        q, kf8, vf8, bt, ctx - s, ctx,
        layer_idx=jnp.int32(1), interpret=True, **kern_kw,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )
