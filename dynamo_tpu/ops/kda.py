"""The Kimi-Delta-Attention recurrence (arXiv:2510.26692 section 3): one
equation, its plain form, the chunked form prefill runs and the kernel
decode runs, as ops/ssm.py has its own three.

Per head, with state ``S ∈ R^{K×V}`` (key × value), log-decay ``g_t ∈
(−∞, 0]^K`` a *channel* of the key (``α_t = exp(g_t)``) and write
strength ``β_t ∈ [0, 1]``::

    S̄   = Diag(α_t) · S_{t−1}
    S_t = S̄ + β_t · k_t ⊗ (v_t − S̄ᵀ k_t)
    o_t = S_tᵀ q_t

What is written is the *error* of the decayed state's own reading at
``k_t`` (the delta rule), so the update is not a rank-one add of given
vectors as ops/ssm.py's: ``S̄ᵀ k_t`` has to be known first. A token with
``g_t = 0`` and ``β_t = 0`` leaves the state as it was: that is how the
caller marks pad positions and idle rows.

- ``kda_decode_update``: one token, the equation as written, in plain
  ``jnp``. No served program calls it; it is the oracle the other two
  are held to.
- ``kda_decode_step``: one token for the rows of a decode step, on the
  stacked records where they lie: a Pallas kernel that reads a live
  row's heads into VMEM once, decays them, reads them against ``k``,
  adds the rank-one term, reads them against ``q`` and writes them once,
  and moves nothing for a row without a token (``ssm_decode_step``'s
  contract).
- ``kda_chunked_scan``: a run of ``S`` tokens from a given state, in the
  chunked (WY / UT-transform) form. With ``Γ_t = Σ_{i≤t} g_i`` inside a
  chunk of ``Q`` tokens that starts from ``S_0``, the rank-one terms
  ``u_t = β_t (v_t − S̄_tᵀ k_t)`` solve one unit-lower-triangular system,

      (I + Diag(β) A) U = Diag(β) (V − (K ⊙ e^Γ) S_0),
      A[t, s] = Σ_c k_t[c] k_s[c] e^{Γ_t[c] − Γ_s[c]},  s < t,

  and then ``O = (Q ⊙ e^Γ) S_0 + B U`` with ``B[t, s] = Σ_c q_t[c]
  k_s[c] e^{Γ_t[c] − Γ_s[c]}``, ``s ≤ t``, and ``S_Q = Diag(e^{Γ_Q}) S_0
  + (K ⊙ e^{Γ_Q − Γ})ᵀ U``. It computes the recurrence above, not an
  approximation of it. The pairwise decay is a vector, so ``A`` and
  ``B`` do not factor through one scalar a pair as in
  ``ssm.ssd_chunked_scan``; and ``e^{−Γ_s}`` alone overflows under a
  strong gate. So a chunk is cut into sub-chunks of ``SUB`` tokens:
  between two sub-chunks the exponent is split at the later one's first
  boundary, ``(Γ_t − R) + (R − Γ_s)`` with both parts ≤ 0, and the two
  factors multiply as matrices; inside a sub-chunk the exponent is
  taken pair by pair, a channel at a time. No exponent is ever
  positive.

The chunked scan is XLA on every platform. The decode kernel is one
route too: compiled on the chip, in the Pallas interpreter elsewhere, so
the CPU tests walk what the chip runs. The state and the decays stay
float32 (the kernel's arithmetic is float32 on the vector unit); the
chunked products take their operands in the activations' dtype and
accumulate in float32, the triangular solve is float32.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm import _STATE_BLOCK_BYTES

# tokens a chunk of the scan's matrix form, and a sub-chunk inside it
CHUNK = 64
SUB = 16


def kda_decode_update(
    q: jax.Array,     # [B, H, K] (already normalised and scaled)
    k: jax.Array,     # [B, H, K]
    v: jax.Array,     # [B, H, V]
    g: jax.Array,     # [B, H, K] float32 ≤ 0, 0 where the row has no token
    beta: jax.Array,  # [B, H] float32, 0 where the row has no token
    s: jax.Array,     # [B, H, K, V] float32
) -> Tuple[jax.Array, jax.Array]:
    """(o [B, H, V] float32, new state [B, H, K, V] float32)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s_bar = s * jnp.exp(g)[..., None]
    read = jnp.sum(s_bar * k[..., None], axis=-2)                  # S̄ᵀ k
    s = s_bar + k[..., None] * (beta[..., None] * (v - read))[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def _decode_kernel(layer_ref, rows_ref, cols_ref, bv_ref, s_ref, y_ref, o_ref):
    """One block of heads of one live row: cols [4, K, hb] (α, k, β·k and
    q, heads on lanes so that a head's column spreads over the state's
    lanes), bv [hb, V] (β·v), s / o [hb, K, V], y [hb, V]."""
    del layer_ref, rows_ref
    for j in range(s_ref.shape[0]):
        alpha, k, bk, q = (cols_ref[i, :, j:j + 1] for i in range(4))
        s_bar = s_ref[j].astype(jnp.float32) * alpha
        # β (v − S̄ᵀ k): one row over the value channels
        u = bv_ref[j:j + 1, :] - jnp.sum(s_bar * bk, axis=0, keepdims=True)
        s = s_bar + k * u
        o_ref[j] = s.astype(o_ref.dtype)
        y_ref[j:j + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def kda_decode_step(
    q: jax.Array,        # [B, H, K] (already normalised and scaled)
    k: jax.Array,        # [B, H, K]
    v: jax.Array,        # [B, H, V]
    g: jax.Array,        # [B, H, K] float32 ≤ 0
    beta: jax.Array,     # [B, H] float32
    records: jax.Array,  # [L, slots, H, K, V]; row i of the step is slot i
    layer: jax.Array,    # int32 scalar, traced
    live_rows,           # ops/live_rows.LiveRows: the rows that hold a token
) -> Tuple[jax.Array, jax.Array]:
    """(o [B, H, V] float32, zero in a row without a token; the records
    with layer ``layer`` of the live rows advanced by one token).

    ``kda_decode_update`` on ``records[layer, :B]``, where the records
    lie, as ``ssm.ssm_decode_step``: the buffer is the kernel's input and
    its output, the layer is picked by the index map from a prefetched
    scalar, and the grid is (live row, block of heads) over the compacted
    list of live rows, so a row without a token, a slot past ``B`` and
    every other layer come out bit for bit as they went in. A block is
    read into VMEM once and written from it once; between the two it is
    decayed, read against ``β k``, given its rank-one term and read
    against ``q``. The arithmetic is float32 whatever the records'
    dtype; the state is rounded to it once, on the way out."""
    b, heads, kd = q.shape
    vd = v.shape[-1]
    f32 = jnp.float32
    fit = max(1, _STATE_BLOCK_BYTES // (kd * vd * records.dtype.itemsize))
    hb = max(n for n in range(1, min(heads, fit) + 1) if heads % n == 0)
    nb = heads // hb
    live, rows, n = live_rows
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1)
    cols = cols.reshape(b, 4, nb, hb, kd).transpose(0, 2, 1, 4, 3)
    bv = (beta[..., None] * v).reshape(b, nb, hb, vd)

    def by_row5(i, j, layer_ref, rows_ref):
        return rows_ref[i], j, 0, 0, 0

    def by_row(i, j, layer_ref, rows_ref):
        return rows_ref[i], j, 0, 0

    def state(i, j, layer_ref, rows_ref):
        return layer_ref[0], rows_ref[i], j, 0, 0

    y, records = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, nb),
            in_specs=[
                pl.BlockSpec((None, None, 4, kd, hb), by_row5),
                pl.BlockSpec((None, None, hb, vd), by_row),
                pl.BlockSpec((None, None, hb, kd, vd), state),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hb, vd), by_row),
                pl.BlockSpec((None, None, hb, kd, vd), state),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, nb, hb, vd), f32),
                   jax.ShapeDtypeStruct(records.shape, records.dtype)],
        # operands count the two prefetched scalars: the records in, the
        # records out
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="kda_decode_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, cols, bv, records)
    # a row the grid never visited is memory nobody wrote
    return jnp.where(live[:, None, None], y.reshape(b, heads, vd), 0.0), records


def kda_chunked_scan(
    q: jax.Array,     # [B, S, H, K] (already normalised and scaled)
    k: jax.Array,     # [B, S, H, K]
    v: jax.Array,     # [B, S, H, V]
    g: jax.Array,     # [B, S, H, K] float32 ≤ 0, 0 at pad positions
    beta: jax.Array,  # [B, S, H] float32, 0 at pad positions
    s0: jax.Array,    # [B, H, K, V] float32: the state before the run
    chunk: int = CHUNK,
    sub: int = SUB,
) -> Tuple[jax.Array, jax.Array]:
    """(o [B, S, H, V] float32, state after the run [B, H, K, V] float32)."""
    b, s, h, kd = q.shape
    vd = v.shape[-1]
    f32, act = jnp.float32, v.dtype
    c = min(sub, s)
    qn = -(-min(chunk, s) // c) * c      # a chunk is whole sub-chunks
    pad = -s % qn
    if pad:     # g = 0, β = 0 there: no effect on the state, outputs dropped
        q, k, v, g, beta = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    n, m = (s + pad) // qn, qn // c

    def chunks(t):   # [B, n·Q, H, ...] -> [n, B, H, Q, ...]
        t = t.reshape((b, n, qn) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)

    tril = jnp.tril(jnp.ones((c, c), bool))

    def one_chunk(state, inp):
        qc, kc, vc, gc, bc = inp         # [B, H, Q, K|V], β [B, H, Q]
        cum = jnp.cumsum(gc, axis=2)                             # Γ, ≤ 0
        kf, qf = kc.astype(f32), qc.astype(f32)
        # Γ at the last token before each sub-chunk (0 before the first)
        bound = jnp.concatenate(
            [jnp.zeros_like(cum[:, :, :1]), cum[:, :, c - 1:-1:c]], axis=2)
        own = jnp.repeat(bound, c, axis=2)                       # [B, H, Q, K]
        # a token's row from its sub-chunk's boundary on: e^{Γ_t − R_i}
        to_t = jnp.exp(cum - own)
        k_t = (kf * to_t).astype(act).reshape(b, h, m, c, kd)
        q_t = (qf * to_t).astype(act).reshape(b, h, m, c, kd)
        # every earlier token's column up to sub-chunk i's boundary,
        # e^{R_i − Γ_s}; a token at or past the boundary gives none
        before = (jnp.arange(qn)[None, :] < (jnp.arange(m) * c)[:, None])
        from_s = jnp.exp(jnp.where(
            before[None, None, :, :, None],
            bound[:, :, :, None, :] - cum[:, :, None, :, :], -jnp.inf))
        k_s = (kf[:, :, None] * from_s).astype(act)              # [B,H,m,Q,K]
        a = jnp.einsum("bhick,bhisk->bhics", k_t, k_s,
                       preferred_element_type=f32)               # [B,H,m,c,Q]
        bm = jnp.einsum("bhick,bhisk->bhics", q_t, k_s,
                        preferred_element_type=f32)
        # inside a sub-chunk: pair by pair, a channel at a time
        cs = cum.reshape(b, h, m, c, kd)
        pair = jnp.exp(jnp.where(
            tril[None, None, None, :, :, None],
            cs[:, :, :, :, None, :] - cs[:, :, :, None, :, :], -jnp.inf))
        ks = kf.reshape(b, h, m, c, kd)
        kk = ks[:, :, :, None, :, :] * pair                      # [B,H,m,t,s,K]
        a_in = jnp.sum(ks[:, :, :, :, None, :] * kk, axis=-1)
        b_in = jnp.sum(qf.reshape(b, h, m, c, kd)[:, :, :, :, None, :] * kk,
                       axis=-1)
        eye = jnp.eye(m, dtype=f32)[None, None, :, None, :, None]
        a = a.reshape(b, h, m, c, m, c) + eye * jnp.where(
            tril & ~jnp.eye(c, dtype=bool), a_in, 0.0)[:, :, :, :, None, :]
        bm = bm.reshape(b, h, m, c, m, c) + eye * b_in[:, :, :, :, None, :]
        a, bm = a.reshape(b, h, qn, qn), bm.reshape(b, h, qn, qn)

        # u_t = β_t (v_t − S̄_tᵀ k_t): (I + Diag(β) A) U = Diag(β) (V − K̃ S_0)
        s_act = state.astype(act)
        decay = jnp.exp(cum)
        rhs = vc.astype(f32) - jnp.einsum(
            "bhtk,bhkv->bhtv", (kf * decay).astype(act), s_act,
            preferred_element_type=f32)
        u = jax.scipy.linalg.solve_triangular(
            jnp.eye(qn, dtype=f32) + bc[..., None] * a, bc[..., None] * rhs,
            lower=True, unit_diagonal=True)
        u_act = u.astype(act)
        o = jnp.einsum("bhtk,bhkv->bhtv", (qf * decay).astype(act), s_act,
                       preferred_element_type=f32)
        o = o + jnp.einsum("bhts,bhsv->bhtv", bm.astype(act), u_act,
                           preferred_element_type=f32)
        total = cum[:, :, -1]                                    # [B, H, K]
        k_end = (kf * jnp.exp(total[:, :, None] - cum)).astype(act)
        state = state * jnp.exp(total)[..., None] + jnp.einsum(
            "bhsk,bhsv->bhkv", k_end, u_act, preferred_element_type=f32)
        return state, o

    s_end, o = jax.lax.scan(
        one_chunk, s0.astype(f32),
        (chunks(q), chunks(k), chunks(v), chunks(g.astype(f32)),
         jnp.moveaxis(beta.astype(f32).reshape(b, n, qn, h), (1, 3), (0, 2))))
    # [n, B, H, Q, V] -> [B, S, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(b, s + pad, h, vd)
    return o[:, :s], s_end
