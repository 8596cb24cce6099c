"""Decode-specialized Pallas paged attention vs. the XLA reference path.

Runs in interpret mode on CPU (manual-DMA semantics are emulated by the
Pallas interpreter). Reference analog: correctness strategy mirrors
tests/test_pallas_attention.py — check against ops/attention.py's
gather/softmax path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import (
    attention,
    paged_attention,
    scatter_kv_stacked,
)
from dynamo_tpu.ops.live_rows import live_row_list
from dynamo_tpu.ops.pallas_decode import paged_decode_attention


def make_stacked_case(rng, layers, b, h, kvh, d, bs, w, dtype=jnp.float32):
    n_blocks = b * w + 3
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), dtype)
    k_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), dtype
    )
    v_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), dtype
    )
    perm = rng.permutation(n_blocks)[: b * w]
    block_tables = jnp.asarray(perm.reshape(b, w), jnp.int32)
    return q, k_cache, v_cache, block_tables


@pytest.mark.parametrize("ppc", [8, 2, 1])  # 2/1 force the multi-chunk
@pytest.mark.parametrize("ctx", [[1, 17, 64, 128], [38, 6, 1, 90]])
def test_decode_matches_xla_reference(ctx, ppc):
    """ppc < live pages exercises the double-buffered prefetch loop
    (slot alternation + wait ordering), not just the single-chunk case."""
    rng = np.random.default_rng(0)
    layers, b, h, kvh, d, bs, w = 3, 4, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray(ctx, jnp.int32)
    positions = (ctx - 1)[:, None]

    for li in range(layers):
        ref = paged_attention(
            q, k_cache[li], v_cache[li], bt, positions, ctx
        )
        out = paged_decode_attention(
            q, k_cache, v_cache, bt, ctx,
            layer_idx=jnp.int32(li), pages_per_chunk=ppc, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"layer {li}",
        )


def test_decode_gqa_bf16_small_chunk():
    """Odd GQA group + bf16 + pages_per_chunk > live pages."""
    rng = np.random.default_rng(1)
    layers, b, h, kvh, d, bs, w = 2, 2, 8, 2, 32, 8, 4
    q, k_cache, v_cache, bt = make_stacked_case(
        rng, layers, b, h, kvh, d, bs, w, jnp.bfloat16
    )
    ctx = jnp.asarray([9, 23], jnp.int32)
    positions = (ctx - 1)[:, None]
    ref = paged_attention(q, k_cache[1], v_cache[1], bt, positions, ctx)
    out = paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        layer_idx=jnp.int32(1), pages_per_chunk=8, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_attention_dispatch_decode_stacked():
    """attention() routes S=1 + stacked cache through the decode kernel."""
    rng = np.random.default_rng(2)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([40, 3, 77, 128], jnp.int32)
    positions = (ctx - 1)[:, None]
    ref = paged_attention(q, k_cache[0], v_cache[0], bt, positions, ctx)
    out = attention(
        q, k_cache, v_cache, bt, positions, ctx,
        impl="pallas", interpret=True, layer_idx=jnp.int32(0),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_attention_dispatch_decode_on_mesh():
    """Decode kernel under a dp x tp shard_map mesh."""
    from dynamo_tpu.engine.model_runner import build_mesh

    rng = np.random.default_rng(3)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 4
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([12, 30, 64, 5], jnp.int32)
    positions = (ctx - 1)[:, None]

    mesh = build_mesh(2, 4)
    ref = paged_attention(q, k_cache[1], v_cache[1], bt, positions, ctx)
    out = attention(
        q, k_cache, v_cache, bt, positions, ctx,
        impl="pallas", mesh=mesh, interpret=True, layer_idx=jnp.int32(1),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_scatter_kv_stacked_matches_per_layer():
    """Stacked scatter == slice + scatter_kv + splice, incl. -1 drops."""
    from dynamo_tpu.ops.attention import scatter_kv

    rng = np.random.default_rng(4)
    layers, n, bs, kvh, dk = 3, 6, 8, 2, 16
    b, s = 2, 4
    k_all = jnp.asarray(rng.standard_normal((layers, n, bs, kvh, dk)), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((layers, n, bs, kvh, dk)), jnp.float32)
    new_k = jnp.asarray(rng.standard_normal((b, s, kvh, dk)), jnp.float32)
    new_v = jnp.asarray(rng.standard_normal((b, s, kvh, dk)), jnp.float32)
    slots = jnp.asarray([[0, 5, 17, -1], [30, 31, -1, 2]], jnp.int32)

    for li in range(layers):
        k2, v2 = scatter_kv_stacked(k_all, v_all, new_k, new_v, slots, jnp.int32(li))
        ref_k, ref_v = scatter_kv(k_all[li], v_all[li], new_k, new_v, slots)
        np.testing.assert_array_equal(np.asarray(k2[li]), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(v2[li]), np.asarray(ref_v))
        # other layers untouched
        for lj in range(layers):
            if lj != li:
                np.testing.assert_array_equal(
                    np.asarray(k2[lj]), np.asarray(k_all[lj])
                )


def test_prefill_kernel_stacked_layer_idx():
    """paged_flash_attention with a stacked cache + runtime layer index."""
    from dynamo_tpu.ops.pallas_attention import paged_flash_attention

    rng = np.random.default_rng(5)
    layers, b, s, h, kvh, d, bs = 2, 2, 32, 8, 4, 64, 16
    w = 4
    n_blocks = b * w + 1
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k_cache = jnp.asarray(rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w), jnp.int32)
    base = np.zeros(b, np.int32)
    ctx = jnp.full((b,), s, jnp.int32)
    positions = jnp.asarray(base)[:, None] + jnp.arange(s)[None, :]

    for li in range(layers):
        ref = paged_attention(q, k_cache[li], v_cache[li], bt, positions, ctx)
        out = paged_flash_attention(
            q, k_cache, v_cache, bt, jnp.asarray(base), ctx,
            layer_idx=jnp.int32(li), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_mla_decode_matches_xla_reference():
    """MLA decode kernel vs models/deepseek.mla_paged_attention (interpret)."""
    from dynamo_tpu.models.deepseek import mla_paged_attention
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    rng = np.random.default_rng(7)
    layers, b, h, r, rd, bs, w = 2, 4, 8, 32, 16, 8, 8
    n_blocks = b * w + 2
    q_lat = jnp.asarray(rng.standard_normal((b, 1, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, 1, h, rd)), jnp.float32)
    c_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, 1, bs, r)), jnp.float32
    )
    kr_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, 1, bs, rd)), jnp.float32
    )
    bt = jnp.asarray(
        rng.permutation(n_blocks)[: b * w].reshape(b, w), jnp.int32
    )
    ctx = jnp.asarray([1, 13, 40, 64], jnp.int32)
    positions = (ctx - 1)[:, None]
    scale = 0.25

    for li in range(layers):
        ref = mla_paged_attention(
            q_lat, q_rope, c_cache[li], kr_cache[li], bt, positions, ctx, scale
        )
        out = mla_paged_decode_attention(
            q_lat, q_rope, c_cache, kr_cache, bt, ctx,
            layer_idx=jnp.int32(li), scale=scale, pages_per_chunk=2,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"layer {li}",
        )


def test_mla_attention_dispatch_and_mesh():
    """deepseek.mla_attention routes decode to the kernel, incl. tp mesh."""
    from dynamo_tpu.engine.model_runner import build_mesh
    from dynamo_tpu.models.deepseek import mla_attention, mla_paged_attention

    rng = np.random.default_rng(8)
    layers, b, h, r, rd, bs, w = 2, 4, 8, 32, 16, 8, 4
    n_blocks = b * w + 1
    q_lat = jnp.asarray(rng.standard_normal((b, 1, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, 1, h, rd)), jnp.float32)
    c_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, 1, bs, r)), jnp.float32
    )
    kr_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, 1, bs, rd)), jnp.float32
    )
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w), jnp.int32)
    ctx = jnp.asarray([5, 17, 30, 9], jnp.int32)
    positions = (ctx - 1)[:, None]

    ref = mla_paged_attention(
        q_lat, q_rope, c_cache[1], kr_cache[1], bt, positions, ctx, 0.5
    )
    out = mla_attention(
        q_lat, q_rope, c_cache, kr_cache, jnp.int32(1), bt, positions, ctx,
        0.5, impl="pallas", interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    mesh = build_mesh(2, 4)
    out = mla_attention(
        q_lat, q_rope, c_cache, kr_cache, jnp.int32(1), bt, positions, ctx,
        0.5, impl="pallas", mesh=mesh, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

@pytest.mark.parametrize("window", [1, 7, 24, 64, 1000])
def test_decode_windowed_matches_xla_reference(window):
    """Sliding-window decode (Mistral/Gemma-2 even layers): the kernel
    starts its page walk at the window's first live chunk, so parity with
    the XLA mask is the proof the skipped chunks were truly dead."""
    rng = np.random.default_rng(9)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([1, 17, 64, 128], jnp.int32)
    positions = (ctx - 1)[:, None]

    ref = paged_attention(
        q, k_cache[1], v_cache[1], bt, positions, ctx, sliding_window=window
    )
    out = paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        layer_idx=jnp.int32(1), pages_per_chunk=2, interpret=True,
        window=jnp.asarray(window, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_decode_softcap_matches_xla_reference():
    """Gemma-2 logit softcapping, with and without a window on top."""
    rng = np.random.default_rng(10)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([5, 33, 90, 128], jnp.int32)
    positions = (ctx - 1)[:, None]

    ref = paged_attention(
        q, k_cache[0], v_cache[0], bt, positions, ctx, softcap=30.0
    )
    out = paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        layer_idx=jnp.int32(0), interpret=True, softcap=30.0,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )

    ref = paged_attention(
        q, k_cache[1], v_cache[1], bt, positions, ctx, softcap=30.0,
        sliding_window=20,
    )
    out = paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        layer_idx=jnp.int32(1), interpret=True, softcap=30.0,
        window=jnp.asarray(20, jnp.int32), pages_per_chunk=1,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_decode_traced_window_per_layer():
    """Gemma-2 alternates windowed/full layers inside one jitted scan: the
    window must work as a TRACED per-layer scalar without retracing."""
    rng = np.random.default_rng(11)
    layers, b, h, kvh, d, bs, w = 2, 2, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([47, 111], jnp.int32)
    positions = (ctx - 1)[:, None]

    @jax.jit
    def both_layers(q, k_cache, v_cache, bt, ctx):
        def one(li):
            win = jnp.where(li % 2 == 0, jnp.int32(24), jnp.int32(2**30))
            return paged_decode_attention(
                q, k_cache, v_cache, bt, ctx, layer_idx=li,
                interpret=True, window=win,
            )
        return one(jnp.int32(0)), one(jnp.int32(1))

    out0, out1 = both_layers(q, k_cache, v_cache, bt, ctx)
    ref0 = paged_attention(
        q, k_cache[0], v_cache[0], bt, positions, ctx, sliding_window=24
    )
    ref1 = paged_attention(q, k_cache[1], v_cache[1], bt, positions, ctx)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(ref0), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref1), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [1, 30, 100, 4096])
def test_prefill_windowed_softcap_matches_xla_reference(window):
    """Flash-prefill kernel with window + softcap (Gemma-2 prefill): the
    kv_map's lower page clamp must not skip any live page."""
    from dynamo_tpu.ops.pallas_attention import paged_flash_attention

    rng = np.random.default_rng(12)
    layers, b, s, h, kvh, d, bs = 2, 2, 64, 8, 4, 64, 16
    w = 8
    n_blocks = b * w + 1
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32
    )
    v_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32
    )
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w), jnp.int32)
    # chunked-prefill shape: rows continue at different bases past cached ctx
    base = jnp.asarray([0, 48], jnp.int32)
    ctx = jnp.asarray([s, 48 + s], jnp.int32)
    positions = base[:, None] + jnp.arange(s)[None, :]

    ref = paged_attention(
        q, k_cache[1], v_cache[1], bt, positions, ctx,
        softcap=30.0, sliding_window=window,
    )
    out = paged_flash_attention(
        q, k_cache, v_cache, bt, base, ctx,
        layer_idx=jnp.int32(1), interpret=True, softcap=30.0,
        window=jnp.asarray(window, jnp.int32), q_chunk=32,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_attention_dispatch_windowed_softcap_rides_pallas():
    """attention() no longer forces XLA for softcap/sliding_window — the
    kernels implement both; parity at the dispatch level, decode+prefill."""
    rng = np.random.default_rng(13)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([9, 33, 77, 128], jnp.int32)
    positions = (ctx - 1)[:, None]

    ref = attention(
        q, k_cache, v_cache, bt, positions, ctx, impl="xla",
        layer_idx=jnp.int32(0), softcap=25.0, sliding_window=18,
    )
    out = attention(
        q, k_cache, v_cache, bt, positions, ctx, impl="pallas",
        interpret=True, layer_idx=jnp.int32(0), softcap=25.0,
        sliding_window=18,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    # prefill dispatch (S > 1, affine positions)
    s = 32
    qp = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    basep = jnp.zeros((b,), jnp.int32)
    posp = basep[:, None] + jnp.arange(s)[None, :]
    ctxp = jnp.full((b,), s, jnp.int32)
    ref = attention(
        qp, k_cache, v_cache, bt, posp, ctxp, impl="xla",
        layer_idx=jnp.int32(1), softcap=25.0, sliding_window=12,
    )
    out = attention(
        qp, k_cache, v_cache, bt, posp, ctxp, impl="pallas",
        interpret=True, layer_idx=jnp.int32(1), softcap=25.0,
        sliding_window=12,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_decode_sinks_matches_xla_reference():
    """GPT-OSS attention sinks: both kernels fold exp(sink - m) into the
    finalize denominator; parity vs the XLA sink column, with and
    without a window on top."""
    rng = np.random.default_rng(14)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([1, 17, 64, 128], jnp.int32)
    positions = (ctx - 1)[:, None]
    sinks = jnp.asarray(rng.standard_normal(h), jnp.float32)

    ref = paged_attention(
        q, k_cache[1], v_cache[1], bt, positions, ctx, sinks=sinks
    )
    out = paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        layer_idx=jnp.int32(1), pages_per_chunk=2, interpret=True,
        sinks=sinks,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    ref = paged_attention(
        q, k_cache[0], v_cache[0], bt, positions, ctx, sinks=sinks,
        sliding_window=20,
    )
    out = paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        layer_idx=jnp.int32(0), interpret=True, sinks=sinks,
        window=jnp.asarray(20, jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_prefill_sinks_matches_xla_reference():
    from dynamo_tpu.ops.pallas_attention import paged_flash_attention

    rng = np.random.default_rng(15)
    layers, b, s, h, kvh, d, bs = 2, 2, 64, 8, 4, 64, 16
    w = 8
    n_blocks = b * w + 1
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32
    )
    v_cache = jnp.asarray(
        rng.standard_normal((layers, n_blocks, bs, kvh, d)), jnp.float32
    )
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w), jnp.int32)
    base = jnp.asarray([0, 48], jnp.int32)
    ctx = jnp.asarray([s, 48 + s], jnp.int32)
    positions = base[:, None] + jnp.arange(s)[None, :]
    sinks = jnp.asarray(rng.standard_normal(h), jnp.float32)

    ref = paged_attention(
        q, k_cache[0], v_cache[0], bt, positions, ctx, sinks=sinks,
        sliding_window=30,
    )
    out = paged_flash_attention(
        q, k_cache, v_cache, bt, base, ctx,
        layer_idx=jnp.int32(0), interpret=True, q_chunk=32,
        window=jnp.asarray(30, jnp.int32), sinks=sinks,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_attention_dispatch_sinks_rides_pallas_incl_mesh():
    """attention() routes sinks to the kernels (no more XLA forcing),
    incl. under the dp x tp shard_map where sinks shard with the heads."""
    from dynamo_tpu.engine.model_runner import build_mesh

    rng = np.random.default_rng(16)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 4
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([12, 30, 64, 5], jnp.int32)
    positions = (ctx - 1)[:, None]
    sinks = jnp.asarray(rng.standard_normal(h), jnp.float32)

    ref = attention(
        q, k_cache, v_cache, bt, positions, ctx, impl="xla",
        layer_idx=jnp.int32(1), sinks=sinks,
    )
    out = attention(
        q, k_cache, v_cache, bt, positions, ctx, impl="pallas",
        interpret=True, layer_idx=jnp.int32(1), sinks=sinks,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    mesh = build_mesh(2, 4)
    out = attention(
        q, k_cache, v_cache, bt, positions, ctx, impl="pallas",
        mesh=mesh, interpret=True, layer_idx=jnp.int32(1), sinks=sinks,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# the fused S-token verify kernel (speculative propose-verify rounds)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("ppc", [8, 2, 1])
def test_verify_matches_xla_reference(ppc):
    """paged_verify_attention vs the gather/softmax reference: S query
    tokens at affine positions (ctx - S + s), one page walk per row —
    chunked prefetch exercised at ppc < live pages."""
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention

    rng = np.random.default_rng(7)
    layers, b, h, kvh, d, bs, w, s = 2, 3, 8, 4, 64, 16, 8, 5
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    _, k_cache, v_cache, bt = make_stacked_case(
        rng, layers, b, h, kvh, d, bs, w
    )
    ctx = jnp.asarray([s + 1, 37, 101], jnp.int32)  # incl. the S tail
    positions = (ctx - s)[:, None] + jnp.arange(s)[None, :]

    for li in range(layers):
        ref = paged_attention(
            q, k_cache[li], v_cache[li], bt, positions, ctx
        )
        out = paged_verify_attention(
            q, k_cache, v_cache, bt, ctx - s, ctx,
            layer_idx=jnp.int32(li), pages_per_chunk=ppc, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"layer {li}",
        )


def test_verify_windowed_matches_xla_reference():
    """Sliding window on the verify tail: each query's own lower bound
    applies (key > q_pos - window)."""
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention

    rng = np.random.default_rng(8)
    layers, b, h, kvh, d, bs, w, s = 2, 2, 4, 2, 32, 8, 8, 4
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    _, k_cache, v_cache, bt = make_stacked_case(
        rng, layers, b, h, kvh, d, bs, w
    )
    ctx = jnp.asarray([29, 53], jnp.int32)
    positions = (ctx - s)[:, None] + jnp.arange(s)[None, :]
    ref = paged_attention(
        q, k_cache[0], v_cache[0], bt, positions, ctx,
        sliding_window=16,
    )
    out = paged_verify_attention(
        q, k_cache, v_cache, bt, ctx - s, ctx,
        layer_idx=jnp.int32(0), interpret=True,
        window=jnp.asarray(16, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )


def test_attention_dispatch_small_s_rides_verify_kernel():
    """attention() routes 1 < S <= VERIFY_MAX_S through the verify
    kernel (affine verify layout) and matches the XLA reference."""
    rng = np.random.default_rng(9)
    layers, b, h, kvh, d, bs, w, s = 2, 2, 8, 4, 64, 16, 8, 3
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    _, k_cache, v_cache, bt = make_stacked_case(
        rng, layers, b, h, kvh, d, bs, w
    )
    ctx = jnp.asarray([s, 64], jnp.int32)
    positions = (ctx - s)[:, None] + jnp.arange(s)[None, :]
    ref = attention(
        q, k_cache, v_cache, bt, positions, ctx,
        impl="xla", layer_idx=jnp.int32(1),
    )
    out = attention(
        q, k_cache, v_cache, bt, positions, ctx,
        impl="pallas", interpret=True, layer_idx=jnp.int32(1),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )


def test_verify_padded_chunk_valid_rows_match_flash_contract():
    """A right-padded small chunk (ctx < base + S — the shape a custom
    sub-32 prefill bucket would produce): valid rows must match the XLA
    reference exactly; pad rows are garbage the caller discards (the
    flash kernel's contract)."""
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention

    rng = np.random.default_rng(11)
    layers, b, h, kvh, d, bs, w, s = 2, 2, 4, 2, 32, 8, 8, 6
    valid = 4  # last 2 query rows are padding
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    _, k_cache, v_cache, bt = make_stacked_case(
        rng, layers, b, h, kvh, d, bs, w
    )
    base = jnp.asarray([10, 3], jnp.int32)
    ctx = base + valid
    positions = base[:, None] + jnp.arange(s)[None, :]
    ref = paged_attention(
        q, k_cache[0], v_cache[0], bt, positions, ctx
    )
    out = paged_verify_attention(
        q, k_cache, v_cache, bt, base, ctx,
        layer_idx=jnp.int32(0), interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out)[:, :valid], np.asarray(ref)[:, :valid],
        rtol=2e-5, atol=2e-5,
    )


# ---- the verify kernel walks a row as the decode kernel does (_walk) ----
#
# bf16 pages of 16 tokens as served, float32 queries (the kernel upcasts
# a page to the query's dtype, so the XLA route over the same values in
# float32 is held to float32's tolerance, which is also what the kernel
# gave before it took the walk); the interpreter's fresh scratch is NaN as
# VMEM may be, so a page neither copied nor cleared shows in the output


def _verify_case(rng, s, h, kvh, d, ctx, dtype=jnp.bfloat16, bs=16, spare=2):
    b, layers = len(ctx), 2
    w = -(-max(ctx) // bs) + spare
    n_blocks = b * w + 2
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((layers, n_blocks, bs, kvh, d)), dtype)
    v = jnp.asarray(rng.standard_normal((layers, n_blocks, bs, kvh, d)), dtype)
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w),
                     jnp.int32)
    return q, k, v, bt, jnp.asarray(ctx, jnp.int32)


def _verify_contexts(wide, bs=16):
    """Rows of one page (part full), wide - 1 pages, wide pages to the
    last key, wide + 1, and two wide chunks and a tail that does not
    fill its sixteen pages, ending inside a page."""
    return [9, (wide - 1) * bs - 3, wide * bs, (wide + 1) * bs - 7,
            (2 * wide + 3) * bs - 11]


# SDAR-30B-A3B's heads (32 over 4 of 128), S positions a row: the pages
# a wide chunk holds follow the page's bytes and the scores of a kv
# head's S * G rows (512 KB of them at 17 positions and 32 pages)
VERIFY_WIDE = {1: 64, 4: 64, 8: 64, 17: 32}


@pytest.mark.parametrize("block_len", [1, 4], ids=["causal", "block_4"])
@pytest.mark.parametrize("s", list(VERIFY_WIDE))
def test_verify_at_the_derived_chunk_matches_xla_reference(s, block_len):
    """Speculation's S queries at their own lengths (query i sees the keys
    up to base + i) and a block pass's mask, over rows that end on every
    edge of the walk."""
    from dynamo_tpu.ops.pallas_decode import (
        chunks_traced, paged_verify_attention)

    h, kvh, d, wide = 32, 4, 128, VERIFY_WIDE[s]
    ctx = [c + (-c) % block_len for c in _verify_contexts(wide)]
    q, k, v, bt, ctx = _verify_case(
        np.random.default_rng(50 + s), s, h, kvh, d, ctx)
    base = jnp.maximum(ctx - s, 0)
    positions = base[:, None] + jnp.arange(s)[None, :]
    ref = paged_attention(
        q, k[1].astype(jnp.float32), v[1].astype(jnp.float32), bt,
        positions, ctx, block_len=block_len)
    out = paged_verify_attention(
        q, k, v, bt, base, ctx, layer_idx=jnp.int32(1), interpret=True,
        block_len=block_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    page = 2 * 16 * kvh * d * 2
    assert {"kernel": "paged_verify_attention", "page_bytes": page,
            "wide_pages": wide, "tail_pages": 16,
            "wide_bytes": wide * page} in chunks_traced()


# (cache dtype, kv heads, kv heads a product): a head is a product of
# its own where its rows can be read apart from the others' (32-bit
# rows, and 16-bit rows two to a word, which comes apart); the four fp8
# heads of a word are folded together, and every head where the words do
# not divide them
VERIFY_PRODUCTS = {
    "f32_a_head": (jnp.float32, 4, 1),
    "bf16_a_head_of_four": (jnp.bfloat16, 4, 1),
    "bf16_a_head_of_eight": (jnp.bfloat16, 8, 1),
    "bf16_a_head_of_two": (jnp.bfloat16, 2, 1),
    "f16_a_head_of_four": (jnp.float16, 4, 1),
    "bf16_three_heads_together": (jnp.bfloat16, 3, 3),
    "bf16_one_head": (jnp.bfloat16, 1, 1),
    "fp8_a_word_of_four": (jnp.float8_e4m3fn, 8, 4),
    "fp8_two_heads_together": (jnp.float8_e4m3fn, 2, 2),
}


@pytest.mark.parametrize("name", list(VERIFY_PRODUCTS))
def test_verify_takes_a_words_heads_apart_where_it_can(name):
    """Sinks and a window ride along: a head's sink reaches its own rows
    in every grouping, and the walk starts mid-chunk."""
    from dynamo_tpu.ops.pallas_decode import (
        chunk_pages, chunks_traced, paged_verify_attention)

    dtype, kvh, per = VERIFY_PRODUCTS[name]
    s, g, d = 3, 2, 64
    rng = np.random.default_rng(60 + kvh)
    q, k, v, bt, ctx = _verify_case(rng, s, kvh * g, kvh, d,
                                    [200, 37, 16 * 16, 5], dtype)
    sinks = jnp.asarray(rng.standard_normal(kvh * g), jnp.float32)
    base = ctx - s
    positions = base[:, None] + jnp.arange(s)[None, :]
    ref = paged_attention(
        q, k[0].astype(jnp.float32), v[0].astype(jnp.float32), bt,
        positions, ctx, sliding_window=150, sinks=sinks)
    out = paged_verify_attention(
        q, k, v, bt, base, ctx, layer_idx=jnp.int32(0), interpret=True,
        window=jnp.int32(150), sinks=sinks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the scores a page adds are a product's rows against its columns
    page = 2 * 16 * kvh * d * jnp.dtype(dtype).itemsize
    wide = chunk_pages(page, per * s * g * 16 * per * 4, 16, bt.shape[1])
    assert {"kernel": "paged_verify_attention", "page_bytes": page,
            "wide_pages": wide, "tail_pages": 16,
            "wide_bytes": wide * page} in chunks_traced()


@pytest.mark.parametrize("ppc", [1, 2, 4])
def test_verify_honours_a_pinned_chunk(ppc):
    """``pages_per_chunk`` makes every chunk that many pages (a test's,
    the sweep's), as the decode kernels take it."""
    from dynamo_tpu.ops.pallas_decode import (
        chunks_traced, paged_verify_attention)

    s, h, kvh, d = 4, 8, 4, 64
    q, k, v, bt, ctx = _verify_case(np.random.default_rng(70), s, h, kvh, d,
                                    [9, 5 * 16, 7 * 16 - 3])
    positions = (ctx - s)[:, None] + jnp.arange(s)[None, :]
    ref = paged_attention(q, k[1].astype(jnp.float32),
                          v[1].astype(jnp.float32), bt, positions, ctx)
    out = paged_verify_attention(
        q, k, v, bt, ctx - s, ctx, layer_idx=jnp.int32(1), interpret=True,
        pages_per_chunk=ppc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    page = 2 * 16 * kvh * d * 2
    assert {"kernel": "paged_verify_attention", "page_bytes": page,
            "wide_pages": ppc, "tail_pages": ppc,
            "wide_bytes": ppc * page} in chunks_traced()


@pytest.mark.parametrize("value", [float("nan"), float("inf")],
                         ids=["nan", "inf"])
def test_verify_never_reads_a_page_the_row_does_not_own(value):
    """As the decode kernel's: the unused pages poisoned and the table
    past a row's last page pointed at one; the output is finite and the
    clean cache's, bit for bit."""
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention

    s, h, kvh, d = 8, 32, 4, 128
    q, k, v, bt, ctx = _verify_case(np.random.default_rng(71), s, h, kvh, d,
                                    _verify_contexts(VERIFY_WIDE[s]))
    call = dict(layer_idx=jnp.int32(0), interpret=True, block_len=4)
    base = jnp.maximum(ctx - s, 0)
    clean = np.asarray(paged_verify_attention(q, k, v, bt, base, ctx, **call))
    k_bad, _ = _poison_unused(k, bt, ctx, float("nan"))
    v_bad, bt_bad = _poison_unused(v, bt, ctx, value)
    out = np.asarray(paged_verify_attention(q, k_bad, v_bad, bt_bad, base,
                                            ctx, **call))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)


@pytest.mark.parametrize("live", [[0, 0, 0, 0], [1, 1, 1, 1], [0, 1, 0, 1]],
                         ids=["no_live_row", "every_row", "two_of_four"])
def test_verify_walks_the_live_rows_and_zeros_the_others(live):
    """The row list of the decode kernels, where a caller has one: the
    grid's bound is the traced count, the rows it names are the XLA
    route's and every other row is zeros."""
    from dynamo_tpu.ops.live_rows import live_row_list
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention

    s, h, kvh, d = 4, 8, 4, 64
    q, k, v, bt, ctx = _verify_case(np.random.default_rng(72), s, h, kvh, d,
                                    [40, 9, 5 * 16, 130])
    positions = (ctx - s)[:, None] + jnp.arange(s)[None, :]
    ref = np.asarray(paged_attention(
        q, k[1].astype(jnp.float32), v[1].astype(jnp.float32), bt,
        positions, ctx))
    rows = live_row_list(jnp.asarray(live, bool))

    def call(rows):
        return paged_verify_attention(
            q, k, v, bt, ctx - s, ctx, layer_idx=jnp.int32(1),
            interpret=True, live_rows=rows)

    out = np.asarray(call(rows))
    mask = np.asarray(live, bool)
    np.testing.assert_allclose(out[mask], ref[mask], rtol=2e-5, atol=2e-5)
    assert not out[~mask].any()
    (grid,) = _pallas_grids(jax.make_jaxpr(call)(rows).jaxpr)
    assert not isinstance(grid[0], int)


# ---- the grid is the live rows, not the batch ----
#
# a pad row of the decode batch as Scheduler._decode fills it: context 1
# and no slot. The kernels walk the compacted list of the other rows and
# return zeros in the rows they never visited.

# name: (live mask, heads, kv heads, head dim, lanes, layer, kernel kwargs)
LIVE_ROW_CASES = {
    "no_live_row": ([0, 0, 0, 0, 0, 0], 8, 4, 64, 64, 1, {}),
    "every_row_live": ([1, 1, 1, 1, 1, 1], 8, 4, 64, 64, 1, {}),
    "a_live_row_after_idle_ones": ([0, 0, 0, 1, 0, 1], 8, 4, 64, 64, 1, {}),
    "mha_head_96_in_128_lanes": ([1, 0, 0, 1, 1, 0], 4, 4, 96, 128, 1, {}),
    "gqa_32_over_8": ([0, 1, 1, 0, 0, 1], 32, 8, 64, 64, 1, {}),
    "a_window": ([1, 0, 1, 1, 0, 0], 8, 4, 64, 64, 1, {"window": 20}),
    "sinks": ([0, 1, 0, 1, 1, 0], 8, 4, 64, 64, 1, {"sinks": True}),
    "one_head": ([1, 0, 0, 0, 1, 1], 4, 1, 64, 64, 1, {"one_head": True}),
    "layer_0_of_3": ([0, 1, 1, 0, 1, 0], 8, 4, 64, 64, 0, {}),
    "layer_2_of_3": ([1, 1, 0, 0, 0, 1], 8, 4, 64, 64, 2, {}),
}


@pytest.mark.parametrize("name", list(LIVE_ROW_CASES))
def test_decode_walks_the_live_rows_and_zeros_the_others(name):
    live, h, kvh, d, lanes, li, kw = LIVE_ROW_CASES[name]
    kw = dict(kw)
    rng = np.random.default_rng(21)
    layers, b, bs, w = 3, len(live), 16, 8
    q, k_cache, v_cache, bt = make_stacked_case(
        rng, layers, b, h, kvh, lanes, bs, w)
    if lanes != d:      # a head of 96 in 128 lanes: the pad lanes are zero
        keep = jnp.arange(lanes) < d
        q, k_cache, v_cache = q * keep, k_cache * keep, v_cache * keep
    live = np.asarray(live, bool)
    ctx = jnp.asarray(np.where(live, [1, 17, 64, 128, 38, 90], 1), jnp.int32)
    sinks = (jnp.asarray(rng.standard_normal(h), jnp.float32)
             if kw.pop("sinks", False) else None)
    window = kw.pop("window", None)
    ref = paged_attention(
        q, k_cache[li], v_cache[li], bt, (ctx - 1)[:, None], ctx,
        scale=d ** -0.5, sliding_window=window, sinks=sinks)
    if kw.get("one_head"):
        k_cache, v_cache = k_cache[:, :, :, 0], v_cache[:, :, :, 0]
    call = dict(layer_idx=jnp.int32(li), scale=d ** -0.5, pages_per_chunk=2,
                interpret=True, sinks=sinks,
                window=None if window is None else jnp.int32(window), **kw)
    out = np.asarray(paged_decode_attention(
        q, k_cache, v_cache, bt, ctx,
        live_rows=live_row_list(jnp.asarray(live)), **call))
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    assert not out[~live].any()
    if live.all():      # the same walk as a call without a mask
        plain = paged_decode_attention(q, k_cache, v_cache, bt, ctx, **call)
        np.testing.assert_array_equal(out, np.asarray(plain))


@pytest.mark.parametrize("live,li", [
    ([0, 0, 0, 0], 1), ([1, 1, 1, 1], 1), ([0, 0, 1, 0], 0), ([1, 0, 0, 1], 1)],
    ids=["no_live_row", "every_row_live", "a_live_row_after_idle_ones",
         "layer_1"])
def test_mla_decode_walks_the_live_rows_and_zeros_the_others(live, li):
    from dynamo_tpu.models.deepseek import mla_paged_attention
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    rng = np.random.default_rng(22)
    layers, b, h, r, rd, bs, w = 2, 4, 8, 32, 16, 8, 8
    n_blocks = b * w + 2
    q_lat = jnp.asarray(rng.standard_normal((b, 1, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, 1, h, rd)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((layers, n_blocks, 1, bs, r)),
                    jnp.float32)
    kr = jnp.asarray(rng.standard_normal((layers, n_blocks, 1, bs, rd)),
                     jnp.float32)
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w),
                     jnp.int32)
    live = np.asarray(live, bool)
    ctx = jnp.asarray(np.where(live, [1, 13, 40, 64], 1), jnp.int32)
    ref = mla_paged_attention(q_lat, q_rope, c[li], kr[li], bt,
                              (ctx - 1)[:, None], ctx, 0.25)
    call = dict(layer_idx=jnp.int32(li), scale=0.25, pages_per_chunk=2,
                interpret=True)
    out = np.asarray(mla_paged_decode_attention(
        q_lat, q_rope, c, kr, bt, ctx,
        live_rows=live_row_list(jnp.asarray(live)), **call))
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    assert not out[~live].any()
    if live.all():
        plain = mla_paged_decode_attention(q_lat, q_rope, c, kr, bt, ctx,
                                           **call)
        np.testing.assert_array_equal(out, np.asarray(plain))


@pytest.mark.parametrize("dp,tp,walks", [(1, 4, True), (2, 4, False)],
                         ids=["tp4", "dp2_tp4_walks_every_row"])
def test_attention_dispatch_live_rows_on_mesh(dp, tp, walks):
    """The list and its count enter the decode route's shard_map as
    replicated operands; where "dp" splits the batch every row is walked
    (the list would have to be a shard)."""
    from dynamo_tpu.engine.model_runner import build_mesh
    from dynamo_tpu.ops import attention as attn_ops
    from dynamo_tpu.ops.live_rows import decode_live_rows

    rng = np.random.default_rng(23)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 4
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    slots = jnp.asarray([[-1], [7], [-1], [30]], jnp.int32)
    live_rows = decode_live_rows(slots)
    ctx = jnp.asarray([1, 30, 1, 5], jnp.int32)
    positions = (ctx - 1)[:, None]
    ref = np.asarray(paged_attention(q, k_cache[1], v_cache[1], bt, positions,
                                     ctx))
    with attn_ops.route_program("test"):
        out = np.asarray(attention(
            q, k_cache, v_cache, bt, positions, ctx, impl="pallas",
            mesh=build_mesh(dp, tp), interpret=True, layer_idx=jnp.int32(1),
            live_rows=live_rows))
        assert attn_ops.row_list_traced() is walks
    rows = np.asarray(live_rows.live)
    np.testing.assert_allclose(out[rows], ref[rows], rtol=2e-5, atol=2e-5)
    if walks:
        assert not out[~rows].any()
    else:
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def _pallas_grids(jaxpr):
    """The grid of every pallas_call in a jaxpr, nested ones included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids.extend(_pallas_grids(sub))
    return grids


def test_the_grids_first_bound_is_the_traced_count_of_live_rows():
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    rng = np.random.default_rng(24)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 4
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.ones((b,), jnp.int32)

    def gqa(live_rows):
        return paged_decode_attention(q, k_cache, v_cache, bt, ctx,
                                      interpret=True, live_rows=live_rows)

    def mla(live_rows):
        n_blocks = k_cache.shape[1]
        return mla_paged_decode_attention(
            q, q[..., :16], jnp.zeros((layers, n_blocks, 1, bs, d)),
            jnp.zeros((layers, n_blocks, 1, bs, 16)), bt, ctx,
            interpret=True, live_rows=live_rows)

    for fn in (gqa, mla):
        (bound,), = _pallas_grids(jax.make_jaxpr(
            lambda live: fn(live_row_list(live)))(jnp.ones((b,), bool)).jaxpr)
        assert not isinstance(bound, int), bound     # a value of the step's
        (bound,), = _pallas_grids(jax.make_jaxpr(lambda: fn(None))().jaxpr)
        assert bound == b                            # no mask: every row


# ---- a chunk sized by its bytes, only a row's own pages copied ----
#
# the walk at the sizes ``chunk_pages`` derives at the shapes the
# benchmark's cells serve (bf16 pages of 16 tokens), in the interpreter,
# whose fresh scratch is NaN as VMEM may be: a page of a slot that was
# neither copied nor cleared shows as NaN in the output

# name: (q heads, kv heads, lanes, bytes an element, pages a wide chunk)
CHUNK_SHAPES = {
    "phi3": (32, 32, 128, 2, 8),
    "trinity": (32, 4, 128, 2, 64),
    "mistral_tp4_shard": (8, 2, 128, 2, 64),
    "falcon_h1": (20, 4, 128, 2, 64),
    "sala_pair": (16, 1, 128, 2, 64),
    "phi3_fp8": (32, 32, 128, 1, 8),
    "gqa_64_over_8": (64, 8, 128, 2, 16),
}


@pytest.mark.parametrize("name", list(CHUNK_SHAPES))
def test_chunk_pages_follows_the_pages_bytes(name):
    from dynamo_tpu.ops.pallas_decode import chunk_pages

    h, kvh, d, itemsize, want = CHUNK_SHAPES[name]
    page, scores = 2 * 16 * kvh * d * itemsize, h * 16 * kvh * 4
    assert chunk_pages(page, scores, 8, 256) == want
    # never wider than the table, never under the tail's chunk
    assert chunk_pages(page, scores, 8, 24) == min(want, 16)
    assert chunk_pages(page, scores, 4, 4) == 4


def test_chunk_pages_of_the_latent_cache():
    from dynamo_tpu.ops.pallas_decode import chunk_pages

    # moonlight / xing4: 16 tokens of latent 512 + rope key 128, 16 heads
    assert chunk_pages(16 * 640 * 2, 16 * 16 * 4, 16, 256) == 64
    assert chunk_pages(16 * 640 * 2, 32 * 16 * 4, 16, 8) == 16


def _chunk_case(rng, h, kvh, d, ctx, layers=2):
    """bf16 caches as served, float32 queries (the kernel upcasts a page
    to the query's dtype, so the reference over the same values in
    float32 is held to float32's tolerance)."""
    bs, b = 16, len(ctx)
    w = -(-max(ctx) // bs) + 2
    n_blocks = b * w + 2
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((layers, n_blocks, bs, kvh, d)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((layers, n_blocks, bs, kvh, d)),
                    jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w),
                     jnp.int32)
    return q, k, v, bt, jnp.asarray(ctx, jnp.int32)


# a context that ends on a chunk's first page, on its last page and one
# page past it (the wide chunk's 64 pages or the tail's 8), and a short one
WIDE_CTX = [64 * 16 + 5, 2 * 64 * 16 - 3, 2 * 64 * 16 + 1, 300]
TAIL_CTX = [8 * 16 + 5, 2 * 8 * 16 - 3, 2 * 8 * 16 + 1, 40]
# name: (shape, contexts, reference kwargs)
WALK_CASES = {
    "phi3": ("phi3", TAIL_CTX, {}),
    "phi3_window_from_mid_chunk": ("phi3", TAIL_CTX, {"sliding_window": 100}),
    "trinity": ("trinity", WIDE_CTX, {}),
    # 2048 keys from page 34 of the third row: a wide chunk, then the tail
    "trinity_window_from_mid_chunk": (
        "trinity", [2600, 2049, 2048 + 64 * 16 + 7, 300],
        {"sliding_window": 2048}),
    "trinity_sinks": ("trinity", WIDE_CTX, {"sinks": True}),
    "trinity_softcap": ("trinity", WIDE_CTX, {"softcap": 30.0}),
    "mistral_tp4_shard": ("mistral_tp4_shard", WIDE_CTX, {}),
    "falcon_h1": ("falcon_h1", WIDE_CTX, {}),
}


@pytest.mark.parametrize("name", list(WALK_CASES))
def test_decode_at_the_derived_chunk_matches_xla_reference(name):
    from dynamo_tpu.ops.pallas_decode import chunks_traced

    shape, ctx, kw = WALK_CASES[name]
    h, kvh, d, _, wide = CHUNK_SHAPES[shape]
    rng = np.random.default_rng(42)
    q, k, v, bt, ctx = _chunk_case(rng, h, kvh, d, ctx)
    kw = dict(kw)
    sinks = (jnp.asarray(rng.standard_normal(h), jnp.float32)
             if kw.pop("sinks", False) else None)
    ref = paged_attention(
        q, k[1].astype(jnp.float32), v[1].astype(jnp.float32), bt,
        (ctx - 1)[:, None], ctx, sinks=sinks, **kw)
    window = kw.get("sliding_window")
    out = paged_decode_attention(
        q, k, v, bt, ctx, layer_idx=jnp.int32(1), interpret=True,
        sinks=sinks, softcap=kw.get("softcap", 0.0),
        window=None if window is None else jnp.int32(window))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert {"kernel": "paged_decode_attention",
            "page_bytes": 2 * 16 * kvh * d * 2, "wide_pages": wide,
            "tail_pages": 8,
            "wide_bytes": wide * 2 * 16 * kvh * d * 2} in chunks_traced()


def _mla_chunk_case(rng, ctx, h=16, r=512, rd=128, layers=2):
    bs, b = 16, len(ctx)
    w = -(-max(ctx) // bs) + 2
    n_blocks = b * w + 2
    ql = jnp.asarray(rng.standard_normal((b, 1, h, r)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((b, 1, h, rd)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((layers, n_blocks, 1, bs, r)),
                    jnp.bfloat16)
    kr = jnp.asarray(rng.standard_normal((layers, n_blocks, 1, bs, rd)),
                     jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(n_blocks)[: b * w].reshape(b, w),
                     jnp.int32)
    return ql, qr, c, kr, bt, jnp.asarray(ctx, jnp.int32)


@pytest.mark.parametrize("ctx", [WIDE_CTX, [16 * 16 + 5, 2 * 16 * 16 - 3,
                                            2 * 16 * 16 + 1, 40]],
                         ids=["wide_chunks", "the_tail_alone"])
def test_mla_decode_at_moonlights_chunk_matches_xla_reference(ctx):
    from dynamo_tpu.models.deepseek import mla_paged_attention
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    ql, qr, c, kr, bt, ctx = _mla_chunk_case(np.random.default_rng(43), ctx)
    scale = 192 ** -0.5
    ref = mla_paged_attention(
        ql, qr, c[1].astype(jnp.float32), kr[1].astype(jnp.float32), bt,
        (ctx - 1)[:, None], ctx, scale)
    out = mla_paged_decode_attention(
        ql, qr, c, kr, bt, ctx, layer_idx=jnp.int32(1), scale=scale,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _poison_unused(cache, bt, ctx, value, bs=16):
    """``value`` in every page of every layer that no row's live range
    names, and every table entry past a row's last live page pointed at
    such a page: a walk that copied it would read it."""
    bt, ctx = np.asarray(bt), np.asarray(ctx)
    live_pages = -(-ctx // bs)
    used = np.zeros(cache.shape[1], bool)
    for row, n in zip(bt, live_pages):
        used[row[:n]] = True
    unused = np.flatnonzero(~used)
    poisoned = jnp.where(
        jnp.asarray(used).reshape((1, -1) + (1,) * (cache.ndim - 2)),
        cache, jnp.asarray(value, cache.dtype))
    past = np.arange(bt.shape[1])[None, :] >= live_pages[:, None]
    return poisoned, jnp.asarray(np.where(past, unused[0], bt), jnp.int32)


@pytest.mark.parametrize("value", [float("nan"), float("inf")],
                         ids=["nan", "inf"])
@pytest.mark.parametrize("shape", ["phi3", "trinity"])
def test_decode_never_reads_a_page_the_row_does_not_own(shape, value):
    """The value cache's unused pages hold NaN or Inf, the key cache's
    NaN, and the table past a row's last page names one of them: the
    output is finite and the one of the clean cache, bit for bit."""
    h, kvh, d, _, _ = CHUNK_SHAPES[shape]
    ctx = TAIL_CTX if shape == "phi3" else WIDE_CTX
    q, k, v, bt, ctx = _chunk_case(np.random.default_rng(44), h, kvh, d, ctx)
    call = dict(layer_idx=jnp.int32(0), interpret=True)
    clean = np.asarray(paged_decode_attention(q, k, v, bt, ctx, **call))
    k_bad, _ = _poison_unused(k, bt, ctx, float("nan"))
    v_bad, bt_bad = _poison_unused(v, bt, ctx, value)
    out = np.asarray(paged_decode_attention(q, k_bad, v_bad, bt_bad, ctx,
                                            **call))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)


@pytest.mark.parametrize("value", [float("nan"), float("inf")],
                         ids=["nan", "inf"])
def test_mla_decode_never_reads_a_page_the_row_does_not_own(value):
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    ql, qr, c, kr, bt, ctx = _mla_chunk_case(np.random.default_rng(45),
                                             WIDE_CTX)
    call = dict(layer_idx=jnp.int32(0), scale=192 ** -0.5, interpret=True)
    clean = np.asarray(mla_paged_decode_attention(ql, qr, c, kr, bt, ctx,
                                                  **call))
    c_bad, bt_bad = _poison_unused(c, bt, ctx, value)
    kr_bad, _ = _poison_unused(kr, bt, ctx, float("nan"))
    out = np.asarray(mla_paged_decode_attention(ql, qr, c_bad, kr_bad,
                                                bt_bad, ctx, **call))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)


def test_decode_table_width_that_is_no_power_of_two():
    """A table of 6 pages: the tail's chunk is the power of two under it,
    and a row of 5 or 6 pages is a whole chunk and a part of one."""
    rng = np.random.default_rng(46)
    layers, b, h, kvh, d, bs, w = 2, 4, 8, 4, 64, 16, 6
    q, k_cache, v_cache, bt = make_stacked_case(rng, layers, b, h, kvh, d, bs, w)
    ctx = jnp.asarray([96, 65, 64, 3], jnp.int32)
    ref = paged_attention(q, k_cache[1], v_cache[1], bt, (ctx - 1)[:, None],
                          ctx)
    out = paged_decode_attention(q, k_cache, v_cache, bt, ctx,
                                 layer_idx=jnp.int32(1), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------- keys wider than values, keys in parts of lanes ----------

def _wide_key_case(rng, kvh, s, d=192, dv=128, h=16, bs=16, w=8, b=3,
                   layers=2):
    """Keys of ``d`` lanes over values of ``dv`` (models/mimo_v2.py): the
    cache as ``scatter_stacked`` and ``split_lanes`` write it, one stack
    a lane tile of the keys, and the same keys as one padded stack."""
    from dynamo_tpu.ops.attention import (lane_pad, pad_minor,
                                          scatter_stacked, split_lanes)
    n_blocks = b * w + 2
    ctx = np.asarray([s + 5, s + 40, bs * w - 1][:b], np.int32)
    bt = jnp.asarray(
        1 + rng.permutation(n_blocks - 1)[: b * w].reshape(b, w), jnp.int32)
    stacks = tuple(
        jnp.zeros((layers, n_blocks, bs, kvh, lanes), jnp.float32)
        for lanes in (128,) * (lane_pad(d) // 128) + (lane_pad(dv),))
    # every context's keys and values, written through the table
    t = int(ctx.max())
    k = jnp.asarray(rng.standard_normal((b, t, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kvh, dv)), jnp.float32)
    pos = np.arange(t)[None, :].repeat(b, 0)
    slots = np.asarray(bt)[np.arange(b)[:, None], pos // bs] * bs + pos % bs
    slots = jnp.asarray(np.where(pos < ctx[:, None], slots, -1), jnp.int32)
    *k_parts, v_all = scatter_stacked(stacks, (*split_lanes(k), v), slots,
                                      jnp.int32(1))
    k_one = jnp.concatenate(k_parts, -1)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    base = jnp.asarray(ctx - s, jnp.int32)
    return (q, tuple(k_parts), k_one, v_all, bt, base, jnp.asarray(ctx),
            pad_minor)


@pytest.mark.parametrize("sinks", [False, True])
@pytest.mark.parametrize("kvh,window", [(4, None), (8, 24)])
@pytest.mark.parametrize("s", [1, 64])
def test_keys_in_parts_and_narrower_values_on_every_route(s, kvh, window,
                                                          sinks):
    """The decode kernel (S = 1) and the flash kernel (S = 64, a sink as
    its running softmax's first term) over keys of 192 lanes kept as two
    stacks of 128 and values of 128, at 4 and 8 kv heads: the XLA route
    over the same tuple, and the XLA route over the keys as one stack of
    256 lanes, give the same ``[B, S, H, 128]``."""
    rng = np.random.default_rng(7 + s + kvh)
    q, k_parts, k_one, v_all, bt, base, ctx, pad_minor = _wide_key_case(
        rng, kvh, s)
    sk = (jnp.asarray(rng.standard_normal(q.shape[2]) + 2.0, jnp.float32)
          if sinks else None)
    positions = base[:, None] + jnp.arange(s)[None, :]
    want = paged_attention(
        pad_minor(q, 256), k_one[1], v_all[1], bt, positions, ctx,
        scale=192 ** -0.5, sliding_window=window, sinks=sk)
    assert want.shape == q.shape[:3] + (128,)
    for impl in ("xla", "pallas"):
        got = attention(q, k_parts, v_all, bt, positions, ctx, impl=impl,
                        interpret=True, layer_idx=jnp.int32(1),
                        sliding_window=window, sinks=sk, v_dim=128)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)


def test_narrower_values_on_the_verify_kernel_and_parts_refused_there():
    """One stack of keys of 256 lanes over values of 128 on the verify
    kernel (S = 8); keys in parts are the decode and flash kernels' and
    the XLA route's, refused on the verify kernel and sent to the XLA
    route by ``auto``."""
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention
    rng = np.random.default_rng(3)
    q, k_parts, k_one, v_all, bt, base, ctx, pad_minor = _wide_key_case(
        rng, 8, 8)
    positions = base[:, None] + jnp.arange(8)[None, :]
    want = paged_attention(pad_minor(q, 256), k_one[1], v_all[1], bt,
                           positions, ctx, scale=192 ** -0.5)
    got = paged_verify_attention(
        pad_minor(q, 256), k_one, v_all, bt, base, ctx,
        layer_idx=jnp.int32(1), scale=192 ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(NotImplementedError, match="parts"):
        attention(q, k_parts, v_all, bt, positions, ctx, impl="pallas",
                  interpret=True, layer_idx=jnp.int32(1), v_dim=128)


def test_the_flash_kernels_query_block_follows_its_bytes():
    from dynamo_tpu.ops.pallas_attention import q_block_rows
    # every accepted cell's heads keep 128 rows: Phi-3's 32 of 128 lanes
    # (96 padded), a tp=4 shard's 8, Falcon-H1's 20, Trinity's and SDAR's 32
    for heads in (8, 20, 32):
        assert q_block_rows(heads, 128, 128, 2) == 128
    # 64 heads of 256 / 128 lanes (models/mimo_v2.py) fit at 64 rows, of
    # 64 lanes padded to 128 (GPT-OSS) too, and never more than asked
    assert q_block_rows(64, 256, 128, 2) == 64
    assert q_block_rows(64, 128, 128, 2) == 64
    assert q_block_rows(8, 128, 128, 2, most=32) == 32


@pytest.mark.parametrize("kvh,window,sinks", [(4, None, False), (8, 24, True),
                                              (8, 70, True)])
def test_the_flash_kernel_over_keys_in_parts_in_several_query_blocks(
        kvh, window, sinks):
    """Keys in parts under two blocks of 16 queries that start past
    position 0 (``base_pos`` > 0), over a table of seven or of eleven
    pages: a window's first page and a block's last lie inside the
    table, and a sink starts each block's statistics anew."""
    rng = np.random.default_rng(11 + kvh)
    q, k_parts, k_one, v_all, bt, base, ctx, pad_minor = _wide_key_case(
        rng, kvh, 32, w=7 if kvh == 4 else 11)
    sk = (jnp.asarray(rng.standard_normal(q.shape[2]) + 2.0, jnp.float32)
          if sinks else None)
    positions = base[:, None] + jnp.arange(32)[None, :]
    want = paged_attention(
        pad_minor(q, 256), k_one[1], v_all[1], bt, positions, ctx,
        scale=192 ** -0.5, sliding_window=window, sinks=sk)
    from dynamo_tpu.ops.pallas_attention import paged_flash_attention
    got = paged_flash_attention(
        pad_minor(q, 256), k_parts, v_all, bt, base, ctx,
        layer_idx=jnp.int32(1), scale=192 ** -0.5, interpret=True,
        q_chunk=16, window=window, sinks=sk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
