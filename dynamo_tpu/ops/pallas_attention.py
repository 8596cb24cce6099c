"""Pallas TPU paged flash attention over the block-paged KV cache.

Replaces ops/attention.py's XLA gather path on TPU: instead of
materializing the gathered [B, W*bs, KVH, D] keys in HBM, the kernel
streams cache pages HBM→VMEM through the Pallas pipeline (the page
index_map reads the scalar-prefetched block table, so the gather IS the
pipeline's double-buffered DMA) and runs an online-softmax (flash)
accumulation in VMEM scratch. One grid step = one cache page for one
(batch row, query chunk): all KV heads of that page are processed so the
page DMA is one contiguous [bs, KVH, D] burst.

Reference analog: the vLLM/SGLang GPU paged-attention kernels the
reference delegated to (SURVEY.md §2.4, §7 hard-part #1).

API contract (matches the engine's scheduler): query positions of a step
are affine — token s of the q block sits at absolute position
``base_pos + s``. Pad rows past the true suffix produce garbage rows the
caller discards (their causal mask is wider but bounded by context_lens).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_decode import MASK_VALUE, _out_struct


def _kernel(
    bt_ref,     # scalar prefetch: block tables [B, W]
    ctx_ref,    # scalar prefetch: context lens [B]
    base_ref,   # scalar prefetch: base query position [B]
    li_ref,     # scalar prefetch: layer index [1] (consumed by index_maps)
    win_ref,    # scalar prefetch: sliding window [1] (>= ctx disables)
    q_ref,      # [1, Sc, KVH, G, D] (VMEM block)
    k_ref,      # [1, 1, bs, KVH, D] — one cache page of one layer
    v_ref,
    *rest,      # ([sinks_ref [1, KVH, G] when has_sinks], o_ref, m/l/acc scratch)
    scale: float,
    block_size: int,
    softcap: float,
    has_sinks: bool = False,
    block_len: int = 1,
):
    if has_sinks:
        sinks_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    c = pl.program_id(1)
    w = pl.program_id(2)
    num_w = pl.num_programs(2)

    _, sc, kvh, g, d = q_ref.shape
    rows = sc * g

    @pl.when(w == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx = ctx_ref[b]
    base = base_ref[b]
    window = win_ref[0]
    page_start = w * block_size
    chunk_base = base + c * sc  # absolute position of this chunk's row 0

    # page live iff it holds context AND is causally visible to the chunk
    # AND (with a window) its last key is within window of some chunk query
    if block_len == 1:
        live = jnp.logical_and(page_start < ctx, page_start <= chunk_base + sc - 1)
    else:
        # the chunk's last query sees to the end of its block
        live = jnp.logical_and(
            page_start < ctx,
            page_start < ((chunk_base + sc - 1) // block_len + 1) * block_len)
    live = jnp.logical_and(
        live, page_start + block_size + window > chunk_base + 1
    )

    @pl.when(live)
    def _compute():
        # lanes = key slot in page; sublanes = (s_local, group) query row
        key_pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1
        )
        qpos = chunk_base + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0
        ) // g
        causal = (key_pos <= qpos if block_len == 1
                  else key_pos < (qpos // block_len + 1) * block_len)
        mask = jnp.logical_and(causal, key_pos < ctx)
        mask = jnp.logical_and(mask, key_pos > qpos - window)

        for h in range(kvh):
            lo = h * rows
            q = q_ref[0, :, h, :, :].reshape(rows, d)          # [rows, D]
            # upcast from the cache storage dtype (fp8 serving)
            k = k_ref[0, 0, :, h, :].astype(q.dtype)            # [bs, D]
            v = v_ref[0, 0, :, h, :].astype(q.dtype)

            s_log = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                           # [rows, bs]
            if softcap:
                s_log = softcap * jnp.tanh(s_log / softcap)
            s_log = jnp.where(mask, s_log, MASK_VALUE)

            m_prev = m_scr[lo : lo + rows, 0:1]                 # [rows, 1]
            l_prev = l_scr[lo : lo + rows, 0:1]
            m_cur = jnp.max(s_log, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_log - m_new)                          # [rows, bs]
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                   # [rows, D]
            acc_scr[lo : lo + rows, :] = acc_scr[lo : lo + rows, :] * alpha + pv
            m_scr[lo : lo + rows, :] = jnp.broadcast_to(m_new, (rows, 128))
            l_scr[lo : lo + rows, :] = jnp.broadcast_to(l_new, (rows, 128))

    @pl.when(w == num_w - 1)
    def _finalize():
        for h in range(kvh):
            lo = h * rows
            l = l_scr[lo : lo + rows, 0:1]
            if has_sinks:
                # virtual sink key: denominator-only (any shared exp
                # shift cancels, so the keys-only running max serves)
                sk = jnp.broadcast_to(
                    sinks_ref[0, h][None, :], (sc, g)
                ).reshape(rows, 1)
                l = l + jnp.exp(sk - m_scr[lo : lo + rows, 0:1])
            l = jnp.where(l == 0.0, 1.0, l)
            out = (acc_scr[lo : lo + rows, :] / l).astype(o_ref.dtype)
            o_ref[0, :, h, :, :] = out.reshape(sc, g, d)


@functools.partial(
    jax.jit, static_argnames=("scale", "q_chunk", "interpret", "softcap",
                              "block_len")
)
def paged_flash_attention(
    q: jax.Array,            # [B, S, H, D] (post-RoPE)
    k_cache: jax.Array,      # [N_blocks, bs, KVH, D] or stacked [L, N, bs, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, W] int32
    base_pos: jax.Array,     # [B] int32 — absolute position of q[:, 0]
    context_lens: jax.Array, # [B] int32
    layer_idx=None,          # scalar int32 into L (default 0)
    scale: Optional[float] = None,
    q_chunk: int = 128,
    interpret: bool = False,
    softcap: float = 0.0,    # Gemma-2: logits ← cap·tanh(logits/cap)
    window=None,             # sliding window (int or traced scalar); None = off
    sinks=None,              # [H] per-head sink logits (GPT-OSS); None = off
    block_len: int = 1,      # static; > 1: causal over blocks of this many
                             # positions, full inside one (models/sdar.py)
) -> jax.Array:
    b, s, h, d = q.shape
    if k_cache.ndim == 4:
        k_cache, v_cache = k_cache[None], v_cache[None]
    _, n_blocks, block_size, kvh, _ = k_cache.shape
    li = (
        jnp.zeros((1,), jnp.int32)
        if layer_idx is None
        else jnp.asarray(layer_idx, jnp.int32).reshape(1)
    )
    win = (
        jnp.full((1,), jnp.int32(2**30))
        if window is None
        else jnp.asarray(window, jnp.int32).reshape(1)
    )
    w = block_tables.shape[1]
    g = h // kvh
    if scale is None:
        scale = d ** -0.5

    # largest divisor of S that fits the chunk budget (buckets are usually
    # powers of two, giving sc == q_chunk; odd max_model_len still works)
    sc = next(c for c in range(min(s, q_chunk), 0, -1) if s % c == 0)
    num_chunks = s // sc

    qg = q.reshape(b, num_chunks, sc, kvh, g, d)  # chunk dim explicit
    # re-flatten chunks into the grid: block index_map picks (b, c)
    qg = qg.reshape(b * num_chunks, sc, kvh, g, d)

    def last_needed_page(b_idx, c, ctx_ref, base_ref):
        # furthest page this (b, chunk) can touch — clamping the page grid
        # index to it makes trailing steps re-request the same page, which
        # the pipeline skips (no DMA) and the kernel skips (not live).
        by_ctx = jnp.maximum(ctx_ref[b_idx] - 1, 0) // block_size
        last_seen = base_ref[b_idx] + (c + 1) * sc - 1
        if block_len > 1:
            last_seen = (last_seen // block_len + 1) * block_len - 1
        by_causal = jnp.maximum(last_seen, 0) // block_size
        return jnp.minimum(by_ctx, by_causal)

    def first_needed_page(b_idx, c, base_ref, win_ref):
        # nearest page a windowed chunk can see: the chunk's first query
        # (at base + c*sc) sees nothing before base + c*sc - window + 1.
        # Window off (2**30) clamps to page 0. Leading grid steps re-fetch
        # this page; the pipeline skips the repeat DMAs and the kernel's
        # live predicate skips their compute.
        lo = base_ref[b_idx] + c * sc - win_ref[0] + 1
        return jnp.maximum(lo, 0) // block_size

    def q_map(i, c, wi, bt, ctx, base, li, win):
        return (i * num_chunks + c, 0, 0, 0, 0)

    def kv_map(i, c, wi, bt, ctx, base, li, win):
        wi = jnp.minimum(wi, last_needed_page(i, c, ctx, base))
        wi = jnp.maximum(wi, first_needed_page(i, c, base, win))
        return (li[0], bt[i, wi], 0, 0, 0)

    has_sinks = sinks is not None
    in_specs = [
        pl.BlockSpec((1, sc, kvh, g, d), q_map),
        pl.BlockSpec((1, 1, block_size, kvh, d), kv_map),
        pl.BlockSpec((1, 1, block_size, kvh, d), kv_map),
    ]
    if has_sinks:
        in_specs.append(
            pl.BlockSpec((1, kvh, g), lambda *_: (0, 0, 0))
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, num_chunks, w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, sc, kvh, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((kvh * sc * g, 128), jnp.float32),
            pltpu.VMEM((kvh * sc * g, 128), jnp.float32),
            pltpu.VMEM((kvh * sc * g, d), jnp.float32),
        ],
    )

    operands = [
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        base_pos.astype(jnp.int32),
        li,
        win,
        qg,
        k_cache,
        v_cache,
    ]
    if has_sinks:
        operands.append(jnp.asarray(sinks, jnp.float32).reshape(1, kvh, g))

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_size=block_size, softcap=softcap,
            has_sinks=has_sinks,
            **({} if block_len == 1 else {"block_len": block_len}),
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct(
            (b * num_chunks, sc, kvh, g, d), q.dtype, q, k_cache,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, s, h, d)
