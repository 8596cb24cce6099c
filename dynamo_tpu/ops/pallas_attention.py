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

A block of queries is as many positions as its bytes leave room for in
VMEM (``q_block_rows``: 128 at 32 heads of 128 lanes, 64 at 64 heads of
256 / 128). The values' lanes and the output's are ``v_cache``'s own; the
keys may be several stacks, each a part of their lanes
(ops/attention.split_lanes), and a score is then the sum of the parts'
products. A sink (a learned logit a head, a key with no value) is the
running softmax's first term: the statistics start at ``m = sink, l =
1`` with an empty accumulator, and the finalize has no branch for it
(Mosaic refuses the broadcast a finalize-time sink needs).

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

from .pallas_decode import MASK_VALUE, _out_struct, _pow2_floor

# what a block of queries keeps in VMEM while its pages stream by: the
# query block and the output block (two buffers each: the pipeline's), the
# float32 accumulator and the two lane-broadcast running statistics. The
# block's rows are sized so that these stay within this much (128 rows
# at 32 heads of 128 lanes, 10.5 MB; 64 heads of 256 / 128 lanes take
# 24 MB at 128 rows and Mosaic refuses the kernel for its scoped VMEM)
Q_BLOCK_BYTES = 12 << 20


def q_block_rows(heads: int, d: int, dv: int, itemsize: int,
                 most: int = 128) -> int:
    """Query positions a block of the flash kernel holds, from the bytes
    a position keeps resident (``Q_BLOCK_BYTES``): ``most`` wherever that
    fits, else the largest power of two that does."""
    row = heads * (2 * d * itemsize + 2 * dv * itemsize + 4 * dv
                   + 2 * 128 * 4)
    return min(most, _pow2_floor(Q_BLOCK_BYTES // row))


def _kernel(
    bt_ref,     # scalar prefetch: block tables [B, W]
    ctx_ref,    # scalar prefetch: context lens [B]
    base_ref,   # scalar prefetch: base query position [B]
    li_ref,     # scalar prefetch: layer index [1] (consumed by index_maps)
    win_ref,    # scalar prefetch: sliding window [1] (>= ctx disables)
    q_ref,      # [1, Sc, KVH, G, D] (VMEM block)
    *rest,      # k_refs (a part of the keys' lanes each), v_ref:
                # [1, 1, bs, KVH, lanes], one cache page of one layer;
                # ([sinks_ref [KVH * Sc * G, 128] when has_sinks],
                # o_ref, m/l/acc scratch)
    scale: float,
    block_size: int,
    softcap: float,
    has_sinks: bool = False,
    block_len: int = 1,
    k_parts: int = 1,
):
    k_refs, v_ref, rest = rest[:k_parts], rest[k_parts], rest[k_parts + 1:]
    if has_sinks:
        sinks_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    c = pl.program_id(1)
    w = pl.program_id(2)
    num_w = pl.num_programs(2)

    _, sc, kvh, g, d = q_ref.shape
    dv = v_ref.shape[-1]    # the values' lanes: a side of the cache has its own
    rows = sc * g

    @pl.when(w == 0)
    def _init():
        if has_sinks:
            # the sink is the running softmax's first term: a key of
            # logit sink and no value (weight 1 at the maximum it sets,
            # nothing in the accumulator), so every later page folds in
            # as after any other and the finalize knows nothing of it
            m_scr[:] = sinks_ref[...]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
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
            ks = [ref[0, 0, :, h, :].astype(q.dtype)            # [bs, lanes]
                  for ref in k_refs]
            v = v_ref[0, 0, :, h, :].astype(q.dtype)

            # (keys kept as several stacks of lanes: a product a part,
            # each over its own lanes of the query)
            lanes = [0]
            for k in ks:
                lanes.append(lanes[-1] + k.shape[-1])
            s_log = functools.reduce(jnp.add, [
                jax.lax.dot_general(
                    q if k_parts == 1 else q[:, a:b], k,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) for k, a, b in zip(ks, lanes, lanes[1:])
            ]) * scale                                          # [rows, bs]
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
            l = jnp.where(l == 0.0, 1.0, l)
            out = (acc_scr[lo : lo + rows, :] / l).astype(o_ref.dtype)
            o_ref[0, :, h, :, :] = out.reshape(sc, g, dv)


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
    # the keys may be kept as several stacks, each a part of their lanes
    # (ops/attention.split_lanes); one stack is every family's but one
    k_caches = list(k_cache) if isinstance(k_cache, (tuple, list)) else None
    if k_caches is None:
        if k_cache.ndim == 4:
            k_cache, v_cache = k_cache[None], v_cache[None]
        k_caches = [k_cache]
    else:
        assert sum(k.shape[-1] for k in k_caches) == d, "q spans the parts"
    k_cache = k_caches[0]
    _, n_blocks, block_size, kvh, _ = k_cache.shape
    dv = v_cache.shape[-1]   # the output's width: the v side's own lanes
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
    # powers of two, giving sc == q_chunk; odd max_model_len still works),
    # the budget what the block's bytes leave of it (q_block_rows)
    q_chunk = q_block_rows(h, d, dv, q.dtype.itemsize, q_chunk)
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
        *(pl.BlockSpec((1, 1, block_size, kvh, k.shape[-1]), kv_map)
          for k in k_caches),
        pl.BlockSpec((1, 1, block_size, kvh, dv), kv_map),
    ]
    if has_sinks:
        in_specs.append(
            pl.BlockSpec((kvh * sc * g, 128), lambda *_: (0, 0))
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, num_chunks, w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, sc, kvh, g, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((kvh * sc * g, 128), jnp.float32),
            pltpu.VMEM((kvh * sc * g, 128), jnp.float32),
            pltpu.VMEM((kvh * sc * g, dv), jnp.float32),
        ],
    )

    operands = [
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        base_pos.astype(jnp.int32),
        li,
        win,
        qg,
        *k_caches,
        v_cache,
    ]
    if has_sinks:
        # a head's logit at each of its (s, g) rows of the statistics,
        # lane-broadcast as they are: rows ordered (kv head, s, g)
        operands.append(jnp.broadcast_to(
            jnp.asarray(sinks, jnp.float32).reshape(kvh, 1, g, 1),
            (kvh, sc, g, 128)).reshape(kvh * sc * g, 128))

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_size=block_size, softcap=softcap,
            has_sinks=has_sinks,
            **({} if block_len == 1 else {"block_len": block_len}),
            **({} if len(k_caches) == 1 else {"k_parts": len(k_caches)}),
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct(
            (b * num_chunks, sc, kvh, g, dv), q.dtype, q, k_cache,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, s, h, dv)
