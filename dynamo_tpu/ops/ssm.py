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
  once, and moves nothing for a row without a token. The records lie in
  the kernel's order, ``[H / k, N, k P]`` a slot a layer
  (``state_to_record``): ``P`` on the lanes, ``k`` heads of a group side
  by side where ``P`` is under a vreg's 128 lanes, ``N`` on the
  sublanes. Δ·x and the decay are then rows over a tile's lanes, ``B``
  and ``C`` are made columns once a group, and the read-out is a sum
  over sublanes: the body's work follows the vregs of state, not the
  number of heads (PERF.md section 6, PR 53: with ``N`` on the lanes a
  head of 64 x 128 paid a lane broadcast, a lane reduce and a one-lane
  store a vreg, twice Falcon-H1's, and read 67 % of the HBM peak where
  Falcon-H1's read 83 %). The oracle keeps the equation's order
  ``[H, P, N]``; the chunked scan takes, carries and returns the state
  in the records' order, so no served program transposes a state.
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


# state a block of the decode kernel: [tiles, N, lanes] of one row, read
# into VMEM once and written from it once. 2 MiB is 16 of Falcon-H1's 32
# heads (a tile a head of 256 x 128 float32), a group's worth, and 64 of
# Granite's 128 (32 tiles of two heads of 128 x 64 side by side); 8 MiB
# with both directions double-buffered, inside the v5e's default scoped
# VMEM of 16 MiB. On the chip, six layers of Falcon-H1's state with 34
# rows live: 2.75 ms at 2 MiB, 2.86 at 1 MiB, 3.13 at 512 KiB (my chip
# run, PR 34, the body of that PR): a step of the grid costs 0.35 us
# beside the 2.6 us a MiB takes each way. ops/kda.py sizes its own
# kernel's blocks by the same constant
_STATE_BLOCK_BYTES = 2 << 20
_LANES = 128

# (heads, P, N, heads a group, bytes an element) -> what the kernel was
# traced with there: what ModelRunner.warmup logs
_blocks_traced: dict = {}


def lane_heads(p: int, heads_per_group: int) -> int:
    """Heads that lie side by side on the lanes of a record's tile: the
    most of a group's heads (they share ``B`` and ``C``) whose ``P`` fill
    a vreg's 128 lanes, two at Granite's ``P`` = 64, one from 128 on."""
    return max(k for k in range(1, max(1, _LANES // p) + 1)
               if heads_per_group % k == 0)


def state_to_record(h: jax.Array, heads_per_group: int) -> jax.Array:
    """[..., H, P, N] (the equation's order) -> [..., H / k, N, k P] (a
    record's): ``P`` on the lanes, ``k = lane_heads`` heads side by side,
    the state's ``N`` on the sublanes. For tests and tools: no served
    program transposes a state (``ssd_chunked_scan`` takes and returns
    records)."""
    *lead, heads, p, n = h.shape
    k = lane_heads(p, heads_per_group)
    h = h.reshape(*lead, heads // k, k, p, n)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, heads // k, n, k * p)


def record_to_state(r: jax.Array, p: int) -> jax.Array:
    """[..., H / k, N, k P] -> [..., H, P, N]: ``state_to_record`` undone."""
    *lead, tiles, n, w = r.shape
    r = r.reshape(*lead, tiles, n, w // p, p)
    return jnp.moveaxis(r, -3, -1).reshape(*lead, tiles * (w // p), p, n)


def record_shape(heads: int, p: int, n: int, heads_per_group: int):
    """(H / k, N, k P): a slot's record of one layer."""
    k = lane_heads(p, heads_per_group)
    return heads // k, n, k * p


def _tile_block(tiles: int, tiles_per_group: int, tile_bytes: int) -> int:
    """Tiles a block: the most whose state fits ``_STATE_BLOCK_BYTES``
    among the divisors of a group's tiles (a block then reads one row of
    B and one of C) and, where a whole group fits with room to spare (a
    group a head: 64 KiB of lightning attention's state), the whole
    groups that divide the tiles (a block then reads a row a group)."""
    fit = max(1, _STATE_BLOCK_BYTES // tile_bytes)
    return max(k for k in range(1, min(tiles, fit) + 1)
               if tiles_per_group % k == 0
               or (k % tiles_per_group == 0 and tiles % k == 0))


def blocks_traced() -> list:
    """[{heads, p, n, heads_per_group, itemsize, lane_heads,
    tiles_per_block, groups_per_block, groups_per_turn, block_bytes}] of
    the state kernels traced in this process."""
    return [dict(zip(("heads", "p", "n", "heads_per_group", "itemsize"), key),
                 **value) for key, value in sorted(_blocks_traced.items())]


# groups of B and C made columns in one turn of the body's loop over a
# block's groups, each in a scratch slot of its own: the transposes of
# one group then run under the vector work of another. Lightning
# attention's 32 groups of one tile at 24 live rows, nine layers (my chip
# run, PR 53): 170.7 us a layer with one group a turn, 160.1 with two,
# 157.0 with four, 155.9 with eight, where the copies alone take 154.9
_GROUPS_A_TURN = 8


def _decode_kernel(layer_ref, rows_ref, xdt_ref, decay_ref, bc_ref, h_ref,
                   y_ref, o_ref, cols_ref):
    """One block of tiles of one live row. A tile is ``[N, W]``: the
    state's ``N`` on the sublanes and, on the lanes, the ``P`` of the
    ``k`` heads that lie side by side (``W = k P``). xdt, decay, y
    [tiles, W] (a tile's Δ·x and decay are rows over its lanes: a
    sublane broadcast, which costs nothing); bc [groups, 2, N] (B and C
    of the block's groups: one, or several whole ones); h / o [tiles, N,
    W]; cols [turn, 2, N, 128] scratch: B and C of the groups in hand as
    columns spread over the lanes, made once a group by one transpose
    each and read by every tile of it. The read-out sums over sublanes:
    vreg adds, and one sublane reduce a tile. Nothing in a tile's work
    crosses lanes, so it follows the vregs of state whatever the size of
    a head."""
    del layer_ref, rows_ref
    tiles, n, w = h_ref.shape
    groups, turn = bc_ref.shape[0], cols_ref.shape[0]
    per_group = tiles // groups
    f32 = jnp.float32
    # sublanes a step: a vreg's rows (a packed one's for 2-byte records)
    step = 8 * max(1, 4 // h_ref.dtype.itemsize)
    step = step if n % step == 0 else n

    def tile(j, slot):
        for c0 in range(0, w, _LANES):
            wc = min(_LANES, w - c0)
            decay = decay_ref[pl.ds(j, 1), c0:c0 + wc]
            xdt = xdt_ref[pl.ds(j, 1), c0:c0 + wc]
            acc = jnp.zeros((step, wc), f32)
            for s0 in range(0, n, step):
                h = (h_ref[j, s0:s0 + step, c0:c0 + wc].astype(f32) * decay
                     + cols_ref[slot, 0, s0:s0 + step, :wc] * xdt)
                o_ref[j, s0:s0 + step, c0:c0 + wc] = h.astype(o_ref.dtype)
                acc = acc + h * cols_ref[slot, 1, s0:s0 + step, :wc]
            y_ref[pl.ds(j, 1), c0:c0 + wc] = jnp.sum(acc, axis=0,
                                                     keepdims=True)

    def group(gi, slot):
        for i in range(2):      # B[n], C[n] -> [N, 128], each over the lanes
            cols_ref[slot, i] = jnp.broadcast_to(
                bc_ref[gi, i:i + 1, :], (_LANES, n)).T
        if per_group == 1:
            tile(gi, slot)
            return

        def its_tile(t, carry):
            tile(gi * per_group + t, slot)
            return carry

        jax.lax.fori_loop(0, per_group, its_tile, 0)

    def a_turn(g0, carry):
        for u in range(turn):
            group(g0 * turn + u, u)
        return carry

    if groups == 1:
        group(0, 0)
    else:
        jax.lax.fori_loop(0, groups // turn, a_turn, 0)


def ssm_decode_step(
    x: jax.Array,        # [B, H, P]
    dt: jax.Array,       # [B, H] float32, 0 where the row has no token
    a: jax.Array,        # [H] float32, negative
    bm: jax.Array,       # [B, G, N]
    cm: jax.Array,       # [B, G, N]
    d: jax.Array,        # [H]
    records: jax.Array,  # [L, slots, H / k, N, k P]; row i is slot i
    layer: jax.Array,    # int32 scalar, traced
    live_rows,           # ops/live_rows.LiveRows: the rows that hold a token
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, H, P] float32, zero in a row without a token; the records
    with layer ``layer`` of the live rows advanced by one token).

    ``ssm_decode_update`` on ``records[layer, :B]`` (in a record's order:
    ``state_to_record``), where the records lie: the buffer is the
    kernel's input and its output (``input_output_aliases``), and the
    layer is picked by the index map from a prefetched scalar, so
    nothing slices a layer out or puts one back. The grid is (live row,
    block of tiles) over the compacted list of live rows, its first
    bound the number of them, known on the device only: a row without a
    token is no step of the grid, so nothing is fetched, computed or
    written back for it, and with no live row the kernel does nothing. A
    row without a token, a slot past ``B`` and every other layer come
    out bit for bit as they went in. The arithmetic is float32 on the
    vector unit whatever the records' dtype; the state is rounded to it
    once, on the way out.

    What the body does is decided by what is seen here: ``P`` and a
    group's heads give the heads a tile (``lane_heads``), the tile's
    bytes the tiles a block (``_tile_block``), the groups a block
    whether the body walks groups and how many a turn
    (``_GROUPS_A_TURN``). One body serves every shape: at many heads a
    group it transposes ``B`` and ``C`` once a block, at lightning
    attention's (a group a head) once a tile, as many cross-lane
    operations a vreg as the body before PR 53 had, hidden under other
    groups' tiles (PERF.md section 6, PR 53). A tile narrower than 128
    lanes (``P`` < 128 with a head a group) leaves lanes idle; no served
    shape has one."""
    b, heads, p = x.shape
    g, n_state = bm.shape[-2:]
    per_group = heads // g
    f32 = jnp.float32
    k = lane_heads(p, per_group)
    tiles, w = heads // k, k * p
    assert records.shape[2:] == (tiles, n_state, w), (records.shape, x.shape)
    tpg = per_group // k                                   # tiles a group
    tb = _tile_block(tiles, tpg, n_state * w * records.dtype.itemsize)
    nb, gb = tiles // tb, max(1, tb // tpg)     # blocks; groups a block
    turn = max(u for u in range(1, _GROUPS_A_TURN + 1) if gb % u == 0)
    _blocks_traced[heads, p, n_state, per_group, records.dtype.itemsize] = {
        "lane_heads": k, "tiles_per_block": tb, "groups_per_block": gb,
        "groups_per_turn": turn,
        "block_bytes": tb * n_state * w * records.dtype.itemsize}
    live, rows, n = live_rows
    x = x.astype(f32)
    xdt = (dt[:, :, None] * x).reshape(b, nb, tb, w)
    decay = jnp.repeat(jnp.exp(dt * a), p, axis=-1).reshape(b, nb, tb, w)
    bc = jnp.stack([bm.astype(f32), cm.astype(f32)], axis=2)   # [B, G, 2, N]

    def by_row(i, j, layer_ref, rows_ref):
        return rows_ref[i], j, 0, 0

    def by_group(i, j, layer_ref, rows_ref):
        return rows_ref[i], j * tb // tpg // gb, 0, 0

    def state(i, j, layer_ref, rows_ref):
        return layer_ref[0], rows_ref[i], j, 0, 0

    y, records = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, nb),
            in_specs=[
                pl.BlockSpec((None, None, tb, w), by_row),
                pl.BlockSpec((None, None, tb, w), by_row),
                pl.BlockSpec((None, gb, 2, n_state), by_group),
                pl.BlockSpec((None, None, tb, n_state, w), state),
            ],
            out_specs=[
                pl.BlockSpec((None, None, tb, w), by_row),
                pl.BlockSpec((None, None, tb, n_state, w), state),
            ],
            scratch_shapes=[pltpu.VMEM((turn, 2, n_state, _LANES), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, nb, tb, w), f32),
                   jax.ShapeDtypeStruct(records.shape, records.dtype)],
        # operands count the two prefetched scalars: the records in, the
        # records out
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="ssm_decode_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, xdt, decay, bc, records)
    y = y.reshape(b, heads, p) + d.astype(f32)[None, :, None] * x
    # a row the grid never visited is memory nobody wrote
    return jnp.where(live[:, None, None], y, 0.0), records


def ssd_chunked_scan(
    x: jax.Array,    # [B, S, H, P]
    dt: jax.Array,   # [B, S, H] float32, 0 at pad positions
    a: jax.Array,    # [H] float32, negative
    bm: jax.Array,   # [B, S, G, N]
    cm: jax.Array,   # [B, S, G, N]
    d: jax.Array,    # [H]
    h0: jax.Array,   # [B, H / j, N, j P] float32: the state before the run
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, S, H, P] float32, state after the run [B, H / j, N, j P]
    float32).

    ``h0`` and the state returned are records as the decode kernel keeps
    them (``state_to_record``), not the equation's ``[B, H, P, N]``. The
    state is carried in that order, ``[B, G, i, N, j, P]`` (a group's
    ``i`` tiles of ``j`` heads side by side), and every product that
    makes or reads it names that order: a trunk's prefill step then has
    no transposition between the rows it takes from the records and the
    rows it puts back. (One beside them, made by XLA or by a kernel,
    whole or a row at a time, and the program of two rows of 1024 tokens
    at Granite's state never came back on the chip: PERF.md section 6,
    PR 53.)"""
    b, s, heads, p = x.shape
    g, n = bm.shape[-2:]
    k = heads // g
    kj = lane_heads(p, k)           # heads a tile; tiles a group
    ki = k // kj
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
    adds = jnp.einsum("bcsgn,bcsgijp->bcginjp", bc,
                      xw.reshape(b, c, q, g, ki, kj, p),
                      preferred_element_type=f32)         # [B, C, G, i, N, j, P]

    # across chunks: the state each chunk starts from
    def carry(h, inp):
        add, tot = inp
        return h * jnp.exp(tot)[:, :, :, None, :, None] + add, h

    h_end, h_start = jax.lax.scan(
        carry, h0.reshape(b, g, ki, n, kj, p).astype(f32),
        (jnp.moveaxis(adds, 1, 0),
         jnp.moveaxis(total.reshape(b, c, g, ki, kj), 1, 0)))
    h_start = jnp.moveaxis(h_start, 0, 1)                 # [B, C, G, i, N, j, P]
    y = y + jnp.einsum("bctgn,bcginjp->bctgijp", cc, h_start.astype(act),
                       preferred_element_type=f32
                       ).reshape(b, c, q, g, k, p) * jnp.exp(cum)[..., None]
    y = y + d.astype(f32).reshape(g, k)[:, :, None] * xc.astype(f32)
    y = y.reshape(b, s + pad, heads, p)[:, :s]
    return y, h_end.reshape(b, heads // kj, n, kj * p)
