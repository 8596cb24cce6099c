"""Engine-facing sequence-parallel prefill attention.

``sp_prefill_attention`` is the drop-in long-context replacement for
ops/attention.py::prefill_attention: same [B, S, ...] interface, but the
sequence dim is sharded over the mesh's ``sp`` axis so a prompt far larger
than one chip's attention memory prefills across the slice. Handles
padding to the axis size and strategy selection (ring for very long S,
all-to-all when heads divide nicely).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import _NEG, _ring_partials, ring_attention, ulysses_attention


def choose_strategy(seq_len: int, num_kv_heads: int, sp: int) -> str:
    """ring: communication scales with S and works for any head count;
    ulysses: lower latency at moderate S but needs KVH % sp == 0."""
    if num_kv_heads % sp == 0 and seq_len <= 32768:
        return "ulysses"
    return "ring"


def sp_prefill_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, KVH, D]
    v: jax.Array,
    valid_lens: jax.Array,  # [B]
    mesh: Mesh,
    axis: str = "sp",
    strategy: str = "auto",
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal self-attention over the full prompt, sequence-sharded.

    Pads S up to a multiple of the sp axis size (padded positions are
    masked via position id -1) and strips the padding from the output.
    """
    sp = mesh.shape[axis]
    b, s, _h, _d = q.shape
    pad = (-s) % sp
    if pad:
        zeros_q = jnp.zeros((b, pad) + q.shape[2:], q.dtype)
        zeros_kv = jnp.zeros((b, pad) + k.shape[2:], k.dtype)
        q = jnp.concatenate([q, zeros_q], axis=1)
        k = jnp.concatenate([k, zeros_kv], axis=1)
        v = jnp.concatenate([v, zeros_kv], axis=1)
    s_padded = s + pad
    # global positions; everything at/after a row's valid_len is padding
    pos = jnp.arange(s_padded, dtype=jnp.int32)[None, :].repeat(b, axis=0)
    pos = jnp.where(pos < valid_lens[:, None], pos, -1)

    if strategy == "auto":
        strategy = choose_strategy(s_padded, k.shape[2], sp)
    if strategy == "ring":
        out = ring_attention(q, k, v, pos, pos, mesh, axis=axis, scale=scale)
    elif strategy == "ulysses":
        out = ulysses_attention(q, k, v, pos, pos, mesh, axis=axis, scale=scale)
    else:
        raise ValueError(f"unknown sp strategy {strategy!r}; use auto|ring|ulysses")
    return out[:, :s]


def sp_chunk_attention(
    q: jax.Array,            # [1, S, H, D] post-RoPE chunk queries
    k: jax.Array,            # [1, S, KVH, D] the chunk's fresh keys
    v: jax.Array,            # [1, S, KVH, D]
    k_cache: jax.Array,      # [L, N, bs, KVH, Dpad] stacked paged cache
    v_cache: jax.Array,
    block_tables: jax.Array,  # [1, W] this sequence's block ids
    chunk_start,             # traced scalar: first absolute position
    context_len,             # traced scalar: chunk end (valid tokens incl.)
    layer_idx,               # traced scalar: layer into the stacked cache
    mesh: Mesh,
    axis: str = "sp",
    head_axis: Optional[str] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Attention for ONE sequence-sharded prefill chunk of a long prompt.

    The serving half of sequence parallelism (engine/model_runner.py
    ``prefill_sp``): the chunk's queries and fresh K/V are sharded over
    the mesh's ``axis``; earlier chunks' KV already live in the paged
    cache. Both sources fold into ONE online softmax. Two routes:

    - **Pallas kernel route** (``impl`` resolves to pallas): one ring
      pass over the chunk's fresh K/V only
      (ring_attention._ring_partials), while each device reads the
      committed prefix straight out of its local paged cache with the
      double-buffered page-DMA kernel
      (ops/pallas_sp.paged_prefix_attention_partials) — the cache is
      replicated over ``axis`` (only ``head_axis`` shards it), so no
      gather, no concat, and per-device prefix memory is O(pages in
      flight). The two partial sets merge exp-weighted and normalize
      once, bit-compatible row-for-row with one joint softmax.

    - **XLA gather route** (fallback): the committed prefix is gathered
      from the cache for this layer, sharded over the same axis,
      concatenated behind the chunk's K/V, and rotated around the ring
      with global position ids doing all masking — per-device key
      memory O((S + W·bs)/sp), but the gather itself materializes the
      full [1, W·bs, KVH, D] prefix before the sharding constraint can
      split it.

    Both routes: chunk keys carry their global positions (causal
    intra-chunk); prefix keys are exactly the cache slots
    ``< chunk_start`` (committed KV only — the chunk's own
    just-scattered slots are never double-counted, and a prefix-cache
    hit's reused blocks are covered for free).

    Ring (not Ulysses) deliberately: arbitrary head counts, and the
    rotation overlaps the interconnect with compute at exactly the long
    sequence lengths this path exists for.
    """
    from ..ops.attention import (
        pallas_interpret,
        record_route,
        resolve_attention_impl,
    )

    b, s, _h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    sp = mesh.shape[axis]
    if resolve_attention_impl(impl) == "pallas":
        interpret = interpret or pallas_interpret()
        if s % sp:
            raise ValueError(
                f"sp chunk S must divide the {axis!r} axis: S={s}, sp={sp}"
            )
        record_route("sp_ring_kernel")
        return _sp_chunk_kernel_route(
            q, k, v, k_cache, v_cache,
            block_tables.astype(jnp.int32),
            jnp.asarray(chunk_start, jnp.int32).reshape(1),
            jnp.asarray(context_len, jnp.int32).reshape(1),
            jnp.asarray(layer_idx, jnp.int32).reshape(1),
            mesh=mesh, axis=axis, head_axis=head_axis, scale=scale,
            interpret=interpret,
        )
    record_route("sp_ring_gather")
    l, n_blocks = k_cache.shape[:2]
    # layer indexing through the gather (ops/attention.py idiom): block
    # n of layer li is flat row li*N + n — no full-layer copy
    kc = k_cache.reshape((l * n_blocks,) + k_cache.shape[2:])
    vc = v_cache.reshape((l * n_blocks,) + v_cache.shape[2:])
    rows = block_tables + layer_idx * n_blocks               # [1, W]
    w = block_tables.shape[1]
    bs_sz = k_cache.shape[2]
    pk = kc[rows].reshape(b, w * bs_sz, kc.shape[-2], kc.shape[-1])
    pv = vc[rows].reshape(b, w * bs_sz, vc.shape[-2], vc.shape[-1])
    # slice lane padding away and upcast fp8 storage to the compute dtype
    pk = pk[..., :d].astype(q.dtype)
    pv = pv[..., :d].astype(q.dtype)
    # distribute the gathered prefix over the sequence axis BEFORE the
    # ring, so no device ever holds the whole context
    kv_spec = NamedSharding(mesh, P(None, axis, head_axis, None))
    pk = jax.lax.with_sharding_constraint(pk, kv_spec)
    pv = jax.lax.with_sharding_constraint(pv, kv_spec)

    idx = jnp.arange(s, dtype=jnp.int32)[None, :]
    take = context_len - chunk_start
    qpos = jnp.where(idx < take, chunk_start + idx, -1)      # [1, S]
    cpos = jnp.arange(w * bs_sz, dtype=jnp.int32)[None, :]
    # prefix keys: strictly before the chunk (committed KV only); the
    # chunk's own slots and any pad/garbage blocks mask to -1
    ppos = jnp.where(cpos < chunk_start, cpos, -1)

    kk = jnp.concatenate([k, pk], axis=1)
    vv = jnp.concatenate([v, pv], axis=1)
    kpos = jnp.concatenate([qpos, ppos], axis=1)
    if (s % sp) or (kk.shape[1] % sp):
        raise ValueError(
            f"sp chunk shapes must divide the {axis!r} axis: "
            f"S={s}, S+W*bs={kk.shape[1]}, sp={sp}"
        )
    return ring_attention(
        q, kk, vv, qpos, kpos, mesh, axis=axis, scale=scale,
        head_axis=head_axis,
    )


def _sp_chunk_kernel_route(
    q, k, v, k_cache, v_cache, block_tables, chunk_start, context_len,
    layer_idx, *, mesh, axis, head_axis, scale, interpret,
):
    """Kernelized chunk attention: ring partials over the fresh chunk K/V
    merged with paged-prefix partials read in place from the cache.

    One shard_map: queries/chunk-KV sharded [None, axis, head_axis,
    None]; the cache enters sharded ONLY over ``head_axis`` (replicated
    across ``axis`` — exactly the engine's CACHE_SPEC), so each sp
    device walks its local pages for its own query shard and the full
    [W·bs] prefix is never materialized anywhere.

    The merge is the standard two-source online-softmax combine: with
    per-row (m_r, l_r, o_r) from the ring and (m_p, l_p, acc_p) from
    the prefix kernel, ``m = max(m_r, m_p)``, each side scales by
    ``exp(m_x − m)``, sums add, and one divide normalizes. Pad query
    rows (position -1) have empty ring partials already; their prefix
    partials are masked to empty here so the row stays exactly 0.
    """
    b, s, h, d = q.shape
    kernel = functools.partial(
        _sp_chunk_body, axis=axis, scale=scale, interpret=interpret,
    )
    seq = P(None, axis, head_axis, None)
    pos = P(None, axis)
    cache = P(None, None, None, head_axis, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(seq, seq, seq, pos, P(None, None), cache, cache,
                  P(None), P(None)),
        out_specs=seq,
        check_vma=False,
    )(
        q, k, v,
        _chunk_qpos(s, chunk_start, context_len),
        block_tables, k_cache, v_cache, chunk_start, layer_idx,
    )


def _chunk_qpos(s, chunk_start, context_len):
    """Global query positions for one chunk; rows past the valid tail
    (the last chunk's padding) get -1 and mask out everywhere."""
    idx = jnp.arange(s, dtype=jnp.int32)[None, :]
    return jnp.where(idx < context_len - chunk_start,
                     chunk_start + idx, -1)


def _sp_chunk_body(q, k, v, qpos, btab, kc, vc, pfx, li, *,
                   axis, scale, interpret):
    from ..ops.pallas_sp import paged_prefix_attention_partials

    b, sq, h, d = q.shape
    # ring over the chunk's fresh K/V only: kpos == qpos (the chunk IS
    # the newest keys; causality intra-chunk via global positions)
    o_r, m_r, l_r = _ring_partials(
        q, k, v, qpos, qpos, axis=axis, scale=scale
    )                                            # [B,KVH,G,Sq(,D)] f32
    acc_p, m_p, l_p = paged_prefix_attention_partials(
        q, kc, vc, btab, pfx[0], li[0],
        scale=scale, interpret=interpret,
    )                                            # [B,Sq,KVH,G(,D)] f32
    acc_p = acc_p.transpose(0, 2, 3, 1, 4)
    m_p = m_p.transpose(0, 2, 3, 1)
    l_p = l_p.transpose(0, 2, 3, 1)
    # pad query rows attended the whole prefix inside the kernel (it has
    # no notion of query validity); empty their partials so the merged
    # row is exactly 0 like the gather route's
    padded = (qpos < 0)[:, None, None, :]
    m_p = jnp.where(padded, _NEG, m_p)
    l_p = jnp.where(padded, 0.0, l_p)
    acc_p = jnp.where(padded[..., None], 0.0, acc_p)

    m = jnp.maximum(m_r, m_p)
    a_r = jnp.exp(m_r - m)
    a_p = jnp.exp(m_p - m)
    l_tot = a_r * l_r + a_p * l_p
    o = (o_r * a_r[..., None] + acc_p * a_p[..., None]) / jnp.where(
        l_tot == 0.0, 1.0, l_tot
    )[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)
