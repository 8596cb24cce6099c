"""Sequence-parallel attention for long-context prefill.

Two interchangeable strategies over an ``sp`` mesh axis (neither exists in
the reference, which caps context by config and offloads long prefills —
SURVEY.md §2.12; this is the TPU-native long-context answer):

- **Ring attention** (`ring_attention`): Q stays put; K/V (and their
  position ids) rotate around the ring via ``ppermute`` while each device
  accumulates flash-style online-softmax partials (running max ``m``, sum
  ``l``, weighted accumulator ``o``). sp devices hold S/sp of the sequence
  each, so per-device attention memory is O((S/sp)^2) and the K/V rotation
  overlaps with compute on the ICI ring. Communication-optimal for
  S >> heads.

- **Ulysses / all-to-all** (`ulysses_attention`): two ``all_to_all``s
  reshard [seq/sp, H] -> [seq, H/sp], run plain local attention over the
  full sequence with H/sp heads per device, then reshard back. Cheaper at
  moderate S when H is divisible by sp; requires KVH % sp == 0.

Both handle GQA (H query heads grouped over KVH KV heads) and causal
masking by *global* position ids, so ragged/padded batches work: pad
positions with -1 and they are masked out everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -0.5 * jnp.finfo(jnp.float32).max


def _gqa_scores(q5, k, scale):
    """q5: [B,Sq,KVH,G,D] f32; k: [B,Sk,KVH,D] -> [B,KVH,G,Sq,Sk]."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q5, k.astype(jnp.float32)) * scale


def _causal_mask(q_pos, k_pos):
    """[B,Sq],[B,Sk] global positions -> bool [B,1,1,Sq,Sk]; -1 pads drop."""
    valid = (k_pos >= 0)[:, None, None, None, :] & (q_pos >= 0)[:, None, None, :, None]
    causal = k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    return valid & causal


def _ring_partials(q, k, v, q_pos, k_pos, *, axis: str, scale: float):
    """Per-device online-softmax partials under shard_map: the full ring
    rotation WITHOUT the final normalization. Returns the unnormalized
    accumulator ``o`` [B,KVH,G,Sq,D] f32 plus the running max ``m`` and
    sum ``l`` [B,KVH,G,Sq] f32 — so callers can merge further key
    sources (the paged-prefix kernel in parallel/sequence.py) before
    dividing. Fully-masked rows keep m == _NEG and l == 0."""
    n = lax.psum(1, axis)
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q5 = q.reshape(b, sq, kvh, g, d).astype(jnp.float32)

    o = jnp.zeros((b, kvh, g, sq, d), jnp.float32)
    m = jnp.full((b, kvh, g, sq), _NEG, jnp.float32)
    l = jnp.zeros((b, kvh, g, sq), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(_, carry):
        o, m, l, k, v, k_pos = carry
        s = _gqa_scores(q5, k, scale)                        # [B,KVH,G,Sq,Sk]
        s = jnp.where(_causal_mask(q_pos, k_pos), s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rows with no valid key anywhere keep m_new == _NEG; zero their
        # probabilities so the final output is 0, not mean(V)
        p = jnp.where(
            (m_new > _NEG / 2)[..., None], jnp.exp(s - m_new[..., None]), 0.0
        )
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32)
        )
        k, v, k_pos = (lax.ppermute(x, axis, perm) for x in (k, v, k_pos))
        return o, m_new, l, k, v, k_pos

    o, m, l, _, _, _ = lax.fori_loop(0, n, body, (o, m, l, k, v, k_pos))
    return o, m, l


def _ring_kernel(q, k, v, q_pos, k_pos, *, axis: str, scale: float):
    """Per-device body under shard_map: seq dim sharded over ``axis``."""
    b, sq, h, d = q.shape
    o, _m, l = _ring_partials(q, k, v, q_pos, k_pos, axis=axis, scale=scale)
    out = o / jnp.maximum(l, 1e-30)[..., None]               # fully-masked rows -> 0
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def ring_attention(
    q: jax.Array,       # [B, Sq, H, D]
    k: jax.Array,       # [B, Sk, KVH, D]
    v: jax.Array,       # [B, Sk, KVH, D]
    q_positions: jax.Array,   # [B, Sq] global positions (-1 = pad)
    kv_positions: jax.Array,  # [B, Sk]
    mesh: Mesh,
    axis: str = "sp",
    scale: Optional[float] = None,
    head_axis: Optional[str] = None,
) -> jax.Array:
    """Causal GQA attention with the sequence dim sharded over ``axis``.

    Sq and Sk must each be divisible by the axis size (they need not be
    equal: the serving chunk path concatenates the chunk's fresh K/V
    with the gathered committed prefix, so Sk > Sq). ``head_axis``
    optionally shards the head dim too (tensor parallelism composes:
    heads over tp, sequence over sp — the ring rotates within each tp
    shard's heads). Returns [B, Sq, H, D] sharded the same way as q.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    seq = P(None, axis, head_axis, None)
    pos = P(None, axis)
    kernel = functools.partial(_ring_kernel, axis=axis, scale=scale)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(seq, seq, seq, pos, pos),
        out_specs=seq,
        check_vma=False,
    )(q, k, v, q_positions, kv_positions)


def _ulysses_kernel(q, k, v, q_pos, k_pos, *, axis: str, scale: float):
    b, _s_loc, _h, d = q.shape  # [B, S/n, H, D] per device

    def to_seq_major(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]: split heads, gather sequence
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def to_head_major(x):
        # inverse: [B, S, H/n, D] -> [B, S/n, H, D]
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    q_f = to_seq_major(q)
    k_f = to_seq_major(k)
    v_f = to_seq_major(v)
    qp = lax.all_gather(q_pos, axis, axis=1, tiled=True)   # [B, S]
    kp = lax.all_gather(k_pos, axis, axis=1, tiled=True)

    kvh_loc = k_f.shape[2]
    g = q_f.shape[2] // kvh_loc
    q5 = q_f.reshape(b, q_f.shape[1], kvh_loc, g, d).astype(jnp.float32)
    s = _gqa_scores(q5, k_f, scale)
    s = jnp.where(_causal_mask(qp, kp), s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(m > _NEG / 2, jnp.exp(s - m), 0.0)  # fully-masked rows -> 0
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_f.astype(jnp.float32))
    o = o / jnp.maximum(p.sum(-1), 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, q_f.shape[1], q_f.shape[2], d)
    return to_head_major(o).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all sequence parallelism: reshard seq->heads, attend, reshard
    back. Requires KVH % axis_size == 0 (heads divide over the axis)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[axis]
    if k.shape[2] % n != 0:
        raise ValueError(f"ulysses needs num_kv_heads % sp == 0, got {k.shape[2]} % {n}")
    seq = P(None, axis, None, None)
    pos = P(None, axis)
    kernel = functools.partial(_ulysses_kernel, axis=axis, scale=scale)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(seq, seq, seq, pos, pos),
        out_specs=seq,
        check_vma=False,
    )(q, k, v, q_positions, kv_positions)


def dense_reference(q, k, v, q_positions, kv_positions, scale=None):
    """Unsharded causal GQA attention — the correctness oracle for tests."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    q5 = q.reshape(b, sq, kvh, h // kvh, d).astype(jnp.float32)
    s = _gqa_scores(q5, k, scale)
    s = jnp.where(_causal_mask(q_positions, kv_positions), s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(m > _NEG / 2, jnp.exp(s - m), 0.0)  # fully-masked rows -> 0
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(p.sum(-1), 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)
