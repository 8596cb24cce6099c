"""The Mamba-2 state-space recurrence: one equation, its plain form, the
chunked form prefill runs and the kernel decode runs.

Per head, with state ``h ∈ R^{P×N}``, scalar decay rate ``A < 0`` and
step ``Δ_t ≥ 0``::

    h_t = exp(Δ_t A) · h_{t−1} + Δ_t · x_t ⊗ B_t
    y_t = h_t C_t + D · x_t

``B`` and ``C`` are shared by the heads of a group (``G`` groups, head
``h`` uses group ``h // (H / G)``). A token with ``Δ_t = 0`` leaves the
state as it was and adds nothing: that is how the caller marks pad
positions and idle rows.

Lightning linear attention (models/minicpm_sala.py) is the same
recurrence with ``Δ = 1`` at a token, a decay that is a constant of the
head (``A = ln λ_h``), a group a head (``G = H``: the key plays ``B``,
the scaled query ``C``, the value ``x``) and ``D = 0``:
``S_t = λ_h S_{t−1} + v_t ⊗ k_t``, ``o_t = S_t q_t``. Both families run
the three functions below; nothing here knows which one calls.

- ``ssm_decode_update``: one token, the equation as written, in plain
  ``jnp``. No served program calls it (on the chip XLA makes two fusions
  a layer of it, three passes over every slot's state: PERF.md section
  6); it is the oracle the other two are held to.
- ``ssm_decode_step``: one token for the rows of a decode step, on the
  stacked records where they lie: a Pallas kernel that reads a live
  row's state once, updates it, reads it out against ``C`` and writes it
  once, and moves nothing for a row without a token.
- ``ssd_chunked_scan``: a run of ``S`` tokens from a given state, in the
  chunked (state-space duality) form: inside a chunk of ``Q`` tokens the
  outputs are matrix products against a ``Q × Q`` decay-masked score
  matrix, across chunks one state is carried. It computes the recurrence
  above, not an approximation of it; ``tests/test_falcon_h1_reference.py``
  holds it to the token-by-token form.

The chunked scan is XLA on every platform. The decode kernel is one
route too, as ops/grouped_matmul.py: compiled on the chip, in the Pallas
interpreter elsewhere, so the CPU tests walk what the chip runs. The
state and the decays stay float32 (the kernel's arithmetic is float32 on
the vector unit, no matrix-unit product that would round the state);
the chunked products take their operands in the activations' dtype and
accumulate in float32.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _heads_of_groups(m: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: each group's row for its heads."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def ssm_decode_update(
    x: jax.Array,    # [B, H, P]
    dt: jax.Array,   # [B, H] float32, 0 where the row has no token
    a: jax.Array,    # [H] float32, negative
    bm: jax.Array,   # [B, G, N]
    cm: jax.Array,   # [B, G, N]
    d: jax.Array,    # [H]
    h: jax.Array,    # [B, H, P, N] float32
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, H, P] float32, new state [B, H, P, N] float32)."""
    heads = x.shape[1]
    f32 = jnp.float32
    x = x.astype(f32)
    bh = _heads_of_groups(bm.astype(f32), heads)              # [B, H, N]
    ch = _heads_of_groups(cm.astype(f32), heads)
    decay = jnp.exp(dt * a)                                   # [B, H]
    h = (h * decay[:, :, None, None]
         + (dt[:, :, None] * x)[..., None] * bh[:, :, None, :])
    y = jnp.sum(h * ch[:, :, None, :], axis=-1) + d.astype(f32)[None, :, None] * x
    return y, h


# state a block of the decode kernel: [heads, P, N] of one row, read
# into VMEM once and written from it once. 2 MiB is 16 of Falcon-H1's 32
# heads (128 x 256 float32), a group's worth, 8 MiB with both directions
# double-buffered, inside the v5e's default scoped VMEM of 16 MiB. On the
# chip, six layers of [64, 32, 128, 256] with 34 rows live: 2.75 ms at
# 2 MiB, 2.86 at 1 MiB, 3.13 at 512 KiB (my chip run, PR 34): a step of
# the grid costs 0.35 us beside the 2.6 us a MiB takes each way
_STATE_BLOCK_BYTES = 2 << 20


def _head_block(heads: int, heads_per_group: int, head_bytes: int) -> int:
    """Heads a block: the most whose state fits ``_STATE_BLOCK_BYTES``
    among the divisors of a group's heads (a block then reads one row of
    B and one of C) and, where a whole group fits with room to spare (a
    group a head: 64 KiB of lightning attention's state), the whole
    groups that divide the heads (a block then reads a row a group)."""
    fit = max(1, _STATE_BLOCK_BYTES // head_bytes)
    return max(k for k in range(1, min(heads, fit) + 1)
               if heads_per_group % k == 0
               or (k % heads_per_group == 0 and heads % k == 0))


def _decode_kernel(layer_ref, rows_ref, xdt_ref, decay_ref, bc_ref, h_ref,
                   y_ref, o_ref, *, heads_per_group: int):
    """One block of heads of one live row: xdt [P, hb] (Δ·x, heads on
    lanes so that a head's column spreads over the state's lanes), decay
    [1, hb], bc [gb, 2, N] (B and C of the block's groups: one, or
    ``hb / heads_per_group`` whole ones), h / o [hb, P, N], y [P, hb]."""
    del layer_ref, rows_ref
    for j in range(h_ref.shape[0]):
        jg = j // heads_per_group
        b_row, c_row = bc_ref[jg, 0:1, :], bc_ref[jg, 1:2, :]
        h = (h_ref[j].astype(jnp.float32) * decay_ref[:, j:j + 1]
             + xdt_ref[:, j:j + 1] * b_row)
        o_ref[j] = h.astype(o_ref.dtype)
        y_ref[:, j:j + 1] = jnp.sum(h * c_row, axis=-1, keepdims=True)


def ssm_decode_step(
    x: jax.Array,        # [B, H, P]
    dt: jax.Array,       # [B, H] float32, 0 where the row has no token
    a: jax.Array,        # [H] float32, negative
    bm: jax.Array,       # [B, G, N]
    cm: jax.Array,       # [B, G, N]
    d: jax.Array,        # [H]
    records: jax.Array,  # [L, slots, H, P, N]; row i of the step is slot i
    layer: jax.Array,    # int32 scalar, traced
    live_rows,           # ops/live_rows.LiveRows: the rows that hold a token
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, H, P] float32, zero in a row without a token; the records
    with layer ``layer`` of the live rows advanced by one token).

    ``ssm_decode_update`` on ``records[layer, :B]``, where the records
    lie: the buffer is the kernel's input and its output
    (``input_output_aliases``), and the layer is picked by the index map
    from a prefetched scalar, so nothing slices a layer out or puts one
    back. The grid is (live row, block of heads) over the compacted list
    of live rows, its first bound the number of them, known on the device
    only: a row without a token is no step of the grid, so nothing is
    fetched, computed or written back for it, and with no live row the
    kernel does nothing. A row without a token, a slot past ``B`` and
    every other layer come out bit for bit as they went in. The
    arithmetic is float32 whatever the records' dtype; the state is
    rounded to it once, on the way out."""
    b, heads, p = x.shape
    g, n_state = bm.shape[-2:]
    per_group = heads // g
    f32 = jnp.float32
    hb = _head_block(heads, per_group, p * n_state * records.dtype.itemsize)
    nb, gb = heads // hb, max(1, hb // per_group)   # blocks; groups a block
    live, rows, n = live_rows
    x = x.astype(f32)
    xdt = (dt[:, :, None] * x).reshape(b, nb, hb, p).transpose(0, 1, 3, 2)
    decay = jnp.exp(dt * a).reshape(b, nb, 1, hb)
    bc = jnp.stack([bm.astype(f32), cm.astype(f32)], axis=2)   # [B, G, 2, N]

    def by_row(i, j, layer_ref, rows_ref):
        return rows_ref[i], j, 0, 0

    def by_group(i, j, layer_ref, rows_ref):
        return rows_ref[i], j * hb // per_group // gb, 0, 0

    def state(i, j, layer_ref, rows_ref):
        return layer_ref[0], rows_ref[i], j, 0, 0

    y, records = pl.pallas_call(
        functools.partial(_decode_kernel, heads_per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, nb),
            in_specs=[
                pl.BlockSpec((None, None, p, hb), by_row),
                pl.BlockSpec((None, None, 1, hb), by_row),
                pl.BlockSpec((None, gb, 2, n_state), by_group),
                pl.BlockSpec((None, None, hb, p, n_state), state),
            ],
            out_specs=[
                pl.BlockSpec((None, None, p, hb), by_row),
                pl.BlockSpec((None, None, hb, p, n_state), state),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, nb, p, hb), f32),
                   jax.ShapeDtypeStruct(records.shape, records.dtype)],
        # operands count the two prefetched scalars: the records in, the
        # records out
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="ssm_decode_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, xdt, decay, bc, records)
    y = y.transpose(0, 1, 3, 2).reshape(b, heads, p)
    y = y + d.astype(f32)[None, :, None] * x
    # a row the grid never visited is memory nobody wrote
    return jnp.where(live[:, None, None], y, 0.0), records


def ssd_chunked_scan(
    x: jax.Array,    # [B, S, H, P]
    dt: jax.Array,   # [B, S, H] float32, 0 at pad positions
    a: jax.Array,    # [H] float32, negative
    bm: jax.Array,   # [B, S, G, N]
    cm: jax.Array,   # [B, S, G, N]
    d: jax.Array,    # [H]
    h0: jax.Array,   # [B, H, P, N] float32: the state before the run
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, S, H, P] float32, state after the run [B, H, P, N] float32)."""
    b, s, heads, p = x.shape
    g, n = bm.shape[-2:]
    k = heads // g
    f32 = jnp.float32
    q = min(chunk, s)
    pad = -s % q
    if pad:     # Δ = 0 there: no effect on the state, outputs dropped
        x, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    c = (s + pad) // q
    act = x.dtype
    xc = x.reshape(b, c, q, g, k, p)
    dtc = dt.reshape(b, c, q, g, k)
    bc = bm.reshape(b, c, q, g, n)
    cc = cm.reshape(b, c, q, g, n)
    cum = jnp.cumsum(dtc * a.reshape(g, k), axis=2)           # [B, C, Q, G, K] ≤ 0
    total = cum[:, :, -1]                                     # [B, C, G, K]
    cum_h = cum.transpose(0, 1, 3, 4, 2)                      # [B, C, G, K, Q]
    dt_h = dtc.transpose(0, 1, 3, 4, 2)

    # inside a chunk: y_t += Σ_{s≤t} (C_t·B_s) exp(cum_t − cum_s) Δ_s x_s
    # (the Q x Q matrices keep t, s as their minor dimensions: whole tiles)
    scores = jnp.einsum("bctgn,bcsgn->bcgts", cc, bc,
                        preferred_element_type=f32)           # [B, C, G, t, s]
    diff = cum_h[..., :, None] - cum_h[..., None, :]          # [B, C, G, K, t, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), diff, -jnp.inf))
    m = scores[:, :, :, None] * decay * dt_h[..., None, :]
    y = jnp.einsum("bcgkts,bcsgkp->bctgkp", m.astype(act), xc,
                   preferred_element_type=f32)

    # what each chunk adds to the state by its end
    xw = (xc.astype(f32)
          * (jnp.exp(total[:, :, None] - cum) * dtc)[..., None]).astype(act)
    adds = jnp.einsum("bcsgn,bcsgkp->bcgkpn", bc, xw,
                      preferred_element_type=f32)             # [B, C, G, K, P, N]

    # across chunks: the state each chunk starts from
    def carry(h, inp):
        add, tot = inp
        return h * jnp.exp(tot)[..., None, None] + add, h

    h_end, h_start = jax.lax.scan(
        carry, h0.reshape(b, g, k, p, n).astype(f32),
        (adds.transpose(1, 0, 2, 3, 4, 5), total.transpose(1, 0, 2, 3)))
    h_start = h_start.transpose(1, 0, 2, 3, 4, 5)             # [B, C, G, K, P, N]
    y = y + jnp.einsum("bctgn,bcgkpn->bctgkp", cc, h_start.astype(act),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y + d.astype(f32).reshape(g, k)[:, :, None] * xc.astype(f32)
    y = y.reshape(b, s + pad, heads, p)[:, :s]
    return y, h_end.reshape(b, heads, p, n)
