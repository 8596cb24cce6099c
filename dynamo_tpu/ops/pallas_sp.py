"""Pallas TPU paged-prefix partials for sequence-parallel prefill.

The SP chunk ladder (parallel/sequence.sp_chunk_attention) folds two key
sources into one softmax: the chunk's fresh K/V (rotated around the sp
ring) and the committed prefix already living in the paged KV cache. The
XLA formulation GATHERS the whole prefix — ``kc[block_tables]`` builds a
``[1, W·bs, KVH, D]`` array per layer before the sharding constraint can
split it, so per-device prefill memory scales with the full context and
the 128k ladder is gather-bound, not attention-bound.

This kernel is the other half of the kernelized path: each sp device
computes online-softmax PARTIALS (unnormalized accumulator ``acc``,
running max ``m``, running sum ``l``) of its local query shard against
the paged prefix, reading pages straight from HBM with the same
double-buffered ``make_async_copy`` walk as pallas_decode.py — the cache
is replicated over sp (only tp shards KV heads), so every device walks
its local copy and per-device memory is O(pages in flight), not
O(gathered prefix). The caller merges these partials with the ring
pass's (parallel/ring_attention._ring_partials) and normalizes once.

No softcap/sinks variants: the engine's SP gate only admits llama-family
dense GQA trunks (engine/model_runner._build_sp_prefill), which use
neither. fp8 caches upcast after the DMA exactly like the decode kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_decode import MASK_VALUE, _out_struct


def _prefix_kernel(
    bt_ref,    # scalar prefetch: block tables [B, W] (SMEM)
    pfx_ref,   # scalar prefetch: prefix length [1] (keys at pos < pfx live)
    li_ref,    # scalar prefetch: layer index [1]
    q_ref,     # [1, S, KVH, G, D] VMEM block (the device's query shard)
    k_hbm,     # [L, N, page, KVH, D] in HBM (ANY)
    v_hbm,
    acc_ref,   # [1, S, KVH, G, D] f32 — UNNORMALIZED accumulator
    m_ref,     # [1, rows, 128] f32 lane-broadcast running max
    l_ref,     # [1, rows, 128] f32 lane-broadcast running sum
    k_buf,
    v_buf,
    sem,
    *,
    scale: float,
    block_size: int,
    pages_per_chunk: int,
):
    """One grid step = one batch row; the fori_loop walks ONLY the pages
    holding committed-prefix keys (pos < prefix_len).

    Same GQA head-flattening trick as ``_decode_kernel``: the chunk KV
    flattens to [chunk_t·KVH, D], one MXU dot pair scores every query
    row against every (token, head) column, and iota masks kill
    cross-head and out-of-prefix columns. No causal term: every prefix
    key precedes every chunk query by construction (pos < prefix_len <=
    chunk positions) — pad query rows are zeroed by the CALLER at merge
    (their ring partials are already empty, so zeroed prefix partials
    make the whole row 0).

    A zero-length prefix (the prompt's first chunk) issues no DMA at
    all and returns empty partials (m = MASK_VALUE, l = 0, acc = 0).
    """
    b = pl.program_id(0)
    pfx = pfx_ref[0]
    li = li_ref[0]
    npages = pl.cdiv(pfx, block_size)          # 0 when the prefix is empty
    nchunks = pl.cdiv(npages, pages_per_chunk)

    _, s, kvh, g, d = q_ref.shape
    rows = s * kvh * g
    chunk_t = pages_per_chunk * block_size
    cols = chunk_t * kvh

    def page_copy(chunk, slot, i, hbm, buf):
        # pages past the live range duplicate the last live page — their
        # key positions land >= pfx and the mask kills them. max() guards
        # the npages == 0 case (nothing starts then, but the index must
        # still be in range at trace time).
        p = jnp.maximum(
            jnp.minimum(chunk * pages_per_chunk + i, npages - 1), 0
        )
        return pltpu.make_async_copy(
            hbm.at[li, bt_ref[b, p]], buf.at[slot, i], sem.at[slot]
        )

    def start(chunk, slot):
        for i in range(pages_per_chunk):
            page_copy(chunk, slot, i, k_hbm, k_buf).start()
            page_copy(chunk, slot, i, v_hbm, v_buf).start()

    def wait(chunk, slot):
        for i in range(pages_per_chunk):
            page_copy(chunk, slot, i, k_hbm, k_buf).wait()
            page_copy(chunk, slot, i, v_hbm, v_buf).wait()

    @pl.when(nchunks > 0)
    def _warmup():
        start(0, 0)

    q = q_ref[0].reshape(rows, d)  # rows ordered (s, head, group)

    col_head = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % kvh
    row_head = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) % (kvh * g)
    ) // g
    head_match = col_head == row_head                    # loop-invariant
    col_tok = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) // kvh

    def body(c, carry):
        m, l, acc = carry                                 # [rows,128]x2, [rows,D]
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nchunks)
        def _prefetch():
            start(c + 1, jax.lax.rem(c + 1, 2))

        wait(c, slot)
        # upcast from the cache storage dtype (fp8 serving stores e4m3)
        k = k_buf[slot].reshape(cols, d).astype(q.dtype)
        v = v_buf[slot].reshape(cols, d).astype(q.dtype)

        key_pos = c * chunk_t + col_tok
        mask = head_match & (key_pos < pfx)

        s_log = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                         # [rows, cols]
        s_log = jnp.where(mask, s_log, MASK_VALUE)

        m_cur = jnp.max(s_log, -1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p_unn = jnp.exp(s_log - m_new[:, 0:1])
        l_new = alpha * l + jnp.sum(p_unn, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p_unn.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * alpha[:, 0:1] + pv

    m0 = jnp.full((rows, 128), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((rows, 128), jnp.float32)
    acc0 = jnp.zeros((rows, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nchunks, body, (m0, l0, acc0))
    # NO normalization — the caller merges with the ring partials first
    acc_ref[0] = acc.reshape(s, kvh, g, d)
    m_ref[0] = m
    l_ref[0] = l


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_chunk", "interpret")
)
def paged_prefix_attention_partials(
    q: jax.Array,            # [B, S, H, D] local query shard (post-RoPE)
    k_cache: jax.Array,      # [L, N, page, KVH, Dpad] stacked (or 4-D)
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, W] int32
    prefix_len: jax.Array,   # scalar int32 — keys at pos < prefix_len live
    layer_idx: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    pages_per_chunk: int = 8,
    interpret: bool = False,
):
    """Online-softmax partials of ``q`` against the committed paged
    prefix (cache positions ``< prefix_len``), read page-by-page from
    HBM. Returns ``(acc, m, l)`` with ``acc`` [B, S, KVH, G, D] f32
    unnormalized, ``m``/``l`` [B, S, KVH, G] f32 — merge with another
    key source's partials, then divide by the combined ``l``.

    Pad query rows (the chunk tail) produce partials against the whole
    prefix; the caller masks their ``l``/``acc`` to zero at merge.
    """
    b, s, h, d = q.shape
    if k_cache.ndim == 4:
        k_cache, v_cache = k_cache[None], v_cache[None]
    _, _, block_size, kvh, dk = k_cache.shape
    g = h // kvh
    if scale is None:
        scale = d ** -0.5
    if d != dk:
        # zero pad lanes score 0 against the cache's zeroed pad lanes
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, dk - d)])
    li = (
        jnp.zeros((1,), jnp.int32)
        if layer_idx is None
        else jnp.asarray(layer_idx, jnp.int32).reshape(1)
    )
    pfx = jnp.asarray(prefix_len, jnp.int32).reshape(1)
    pages_per_chunk = min(pages_per_chunk, block_tables.shape[1])
    qs = q.reshape(b, s, kvh, g, dk)
    rows = s * kvh * g

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, s, kvh, g, dk), lambda i, *_: (i, 0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, s, kvh, g, dk), lambda i, *_: (i, 0, 0, 0, 0)
            ),
            pl.BlockSpec((1, rows, 128), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, rows, 128), lambda i, *_: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM(
                (2, pages_per_chunk, block_size, kvh, dk), k_cache.dtype
            ),
            pltpu.VMEM(
                (2, pages_per_chunk, block_size, kvh, dk), v_cache.dtype
            ),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    acc, m, l = pl.pallas_call(
        functools.partial(
            _prefix_kernel,
            scale=scale,
            block_size=block_size,
            pages_per_chunk=pages_per_chunk,
        ),
        grid_spec=grid_spec,
        out_shape=[
            _out_struct((b, s, kvh, g, dk), jnp.float32, q, k_cache),
            _out_struct((b, rows, 128), jnp.float32, q, k_cache),
            _out_struct((b, rows, 128), jnp.float32, q, k_cache),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        pfx,
        li,
        qs,
        k_cache,
        v_cache,
    )
    ml = m[:, :, 0].reshape(b, s, kvh, g)
    ll = l[:, :, 0].reshape(b, s, kvh, g)
    return acc[..., :d], ml, ll
