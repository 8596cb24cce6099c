"""``ops/ssm.ssm_decode_step``, the decode kernel of the Mamba-2 state
update, against the equation's plain form ``ssm_decode_update``: the
live rows' records advanced by one token where they lie, everything else
bit for bit as it was. The records lie in the kernel's order (``P`` on
the lanes, ``ssm.state_to_record``); the oracle takes the equation's.
Here the kernel runs in the Pallas interpreter (the route every backend
but the TPU takes); ``tests/test_chip_compile.py`` compiles it for the
v5e at the benchmark's shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.live_rows import live_row_list

LAYERS = 3


def _inputs(h, p, n, g, slots, live, seed=0, dtype=jnp.float32):
    """A decode step's operands for ``len(live)`` rows (Δ = 0 in the rows
    that hold no token, as the trunk makes it) and random records."""
    rs = np.random.RandomState(seed)
    live = np.asarray(live, bool)
    b = len(live)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.5), (b, h))) * live[:, None]
    args = (rs.randn(b, h, p), dt, -rs.uniform(1, 16, h), rs.randn(b, g, n),
            rs.randn(b, g, n), rs.randn(h))
    records = ssm.state_to_record(
        jnp.asarray(rs.randn(LAYERS, slots, h, p, n), jnp.float32), h // g)
    return ([jnp.asarray(t, jnp.float32) for t in args],
            records.astype(dtype), live)


@jax.jit
def _step(args, records, layer, live):
    return ssm.ssm_decode_step(*args, records, layer, live_row_list(live))


def _oracle(args, records, layer, b):
    """(y, the new state as a record) of the plain update."""
    x, g = args[0], args[3].shape[1]
    y, h = ssm.ssm_decode_update(*args, ssm.record_to_state(
        records[layer, :b].astype(jnp.float32), x.shape[2]))
    return y, ssm.state_to_record(h, x.shape[1] // g)


# (H, P, N, G, slots, live mask, layer)
CASES = {
    # the tiny trunk's mixer (tests/test_falcon_h1_reference.py): 6 heads
    # in 2 groups, so a block is a group's 3 heads
    "no_live_row": (6, 8, 16, 2, 4, [0, 0, 0, 0], 1),
    "every_row_live": (6, 8, 16, 2, 4, [1, 1, 1, 1], 0),
    "live_row_after_a_run_of_idle_ones": (6, 8, 16, 2, 6, [0, 0, 0, 0, 1, 0], 1),
    "first_row_only_last_layer": (6, 8, 16, 2, 4, [1, 0, 0, 0], 2),
    "idle_rows_between_live_ones": (6, 8, 16, 2, 5, [1, 0, 1, 0, 1], 2),
    "fewer_rows_than_slots": (6, 8, 16, 2, 7, [0, 1, 1], 1),
    # a state of whole (8, 128) tiles, one group, one head a group
    "one_group_of_eight_heads": (8, 8, 128, 1, 3, [0, 1, 0], 2),
    "a_head_a_group": (4, 16, 128, 4, 2, [1, 1], 0),
    # lightning attention's shape (models/minicpm_sala.py): as many
    # groups as heads, so a block holds several whole groups and reads a
    # row of B and of C for each
    "eight_groups_of_one_head": (8, 16, 16, 8, 3, [1, 0, 1], 1),
    "four_groups_of_two_heads": (8, 8, 16, 4, 4, [0, 1, 1, 0], 2),
    # Granite's proportions (models/granite_hybrid.py): one group for
    # many heads of few rows (P < N), two of them side by side on a
    # tile's 128 lanes, an odd count of live rows; under a block of
    # 128 KiB the row's eight tiles are four blocks of two
    "one_group_of_sixteen_heads_two_a_tile": (
        16, 64, 128, 1, 6, [1, 0, 1, 1, 0], 1),
    "blocks_of_two_tiles_of_one_group": (
        16, 64, 128, 1, 4, [0, 1, 1, 1], 2, {"block_bytes": 128 << 10}),
    "blocks_of_two_tiles_bfloat16": (
        16, 64, 128, 1, 4, [1, 1, 0, 1], 0,
        {"block_bytes": 64 << 10, "dtype": jnp.bfloat16}),
    # groups of four heads of 64: two tiles a group, a block of two
    # whole groups (the body walks groups, then a group's tiles)
    "two_groups_a_block_two_tiles_a_group": (
        16, 64, 128, 4, 3, [1, 0, 1], 1, {"block_bytes": 256 << 10}),
    # three heads a group at P = 32: the three lie side by side and a
    # tile's 96 lanes are not a vreg's width
    "three_heads_a_tile_of_96_lanes": (6, 32, 16, 2, 3, [0, 1, 1], 0),
    # P over a vreg's 128 lanes: a tile is walked 128 lanes at a time
    "a_head_of_two_vregs_of_lanes": (2, 256, 16, 1, 3, [1, 1, 0], 2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_the_plain_update_on_the_live_rows_and_touches_nothing_else(
        case, monkeypatch):
    h, p, n, g, slots, live, layer, *opts = CASES[case]
    opts = opts[0] if opts else {}
    dtype = opts.get("dtype", jnp.float32)
    args, records, live = _inputs(h, p, n, g, slots, live, seed=len(case),
                                  dtype=dtype)
    b = len(live)
    step = _step
    if "block_bytes" in opts:       # a trace of its own under the limit
        monkeypatch.setattr(ssm, "_STATE_BLOCK_BYTES", opts["block_bytes"])
        step = jax.jit(_step.__wrapped__)
    y, new = step(args, records, jnp.int32(layer), jnp.asarray(live))
    if "block_bytes" in opts:
        key = {"heads": h, "p": p, "n": n, "heads_per_group": h // g,
               "itemsize": jnp.dtype(dtype).itemsize}
        traced, = [t for t in ssm.blocks_traced() if key.items() <= t.items()]
        assert traced["block_bytes"] == opts["block_bytes"], traced
    want_y, want_h = _oracle(args, records, layer, b)
    f32 = jnp.float32
    y, new, before = (np.asarray(y), np.asarray(new.astype(f32)),
                      np.asarray(records.astype(f32)))
    want_h = want_h.astype(dtype).astype(f32)     # rounded once, on the way out
    np.testing.assert_allclose(y[live], np.asarray(want_y)[live],
                               rtol=1e-5, atol=1e-5)
    # a bfloat16 record within one rounding of the oracle's rounded state
    # (a product fused into a sum on one side moves the float32 a bit)
    np.testing.assert_allclose(new[layer, :b][live], np.asarray(want_h)[live],
                               rtol=1e-5 if dtype == f32 else 2 ** -7,
                               atol=1e-5)
    # a row without a token: no output, and its record as it went in;
    # so the slots past the step's rows, and every other layer
    assert not y[~live].any()
    np.testing.assert_array_equal(new[layer, :b][~live], before[layer, :b][~live])
    np.testing.assert_array_equal(new[layer, b:], before[layer, b:])
    others = [i for i in range(LAYERS) if i != layer]
    np.testing.assert_array_equal(new[others], before[others])


def test_forty_steps_in_a_row_stay_on_the_plain_update():
    """The kernel's state fed back to it 40 times against the oracle's fed
    back to the oracle: the difference does not grow with the steps."""
    h, p, n, g, slots = 6, 8, 16, 2, 4
    live = np.array([1, 0, 1, 1], bool)
    _, records, _ = _inputs(h, p, n, g, slots, live, seed=1)
    state = ssm.record_to_state(records[1, :4], p)
    errs = []
    for t in range(40):
        args, _, _ = _inputs(h, p, n, g, slots, live, seed=100 + t)
        y, records = _step(args, records, jnp.int32(1), jnp.asarray(live))
        want_y, state = ssm.ssm_decode_update(*args, state)
        errs.append(max(float(jnp.abs(y - want_y)[live].max()),
                        float(jnp.abs(ssm.record_to_state(records[1, :4], p)
                                      - state).max())))
    assert max(errs) < 1e-5
    assert max(errs[30:]) <= 2 * max(errs[:10]) + 1e-6


def test_bfloat16_records_are_taken_as_found_and_computed_in_float32():
    """A records buffer in bfloat16 (the wrong program of
    test_falcon_h1_reference's ``bf16_state``) is read, updated in
    float32 and rounded once on the way out."""
    args, records, live = _inputs(6, 8, 16, 2, 4, [0, 1, 1, 0], seed=5,
                                  dtype=jnp.bfloat16)
    y, new = _step(args, records, jnp.int32(2), jnp.asarray(live))
    assert new.dtype == jnp.bfloat16 and y.dtype == jnp.float32
    want_y, want_h = _oracle(args, records, 2, 4)
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(new[2].astype(jnp.float32))[live],
        np.asarray(want_h.astype(jnp.bfloat16).astype(jnp.float32))[live])
    np.testing.assert_array_equal(
        np.asarray(new.astype(jnp.float32))[:, ~live],
        np.asarray(records.astype(jnp.float32))[:, ~live])


@pytest.mark.parametrize("live,rows,n", [
    ([0, 1, 0, 1], [1, 3], 2),
    ([1, 1, 1], [0, 1, 2], 3),
    ([0, 0, 0], [], 0),
    ([0, 0, 1, 0, 0], [2], 1),
    ([1], [0], 1),
])
def test_live_row_list_is_the_live_rows_in_order(live, rows, n):
    _, got_rows, got_n = live_row_list(jnp.asarray(live, bool))
    assert got_rows.dtype == jnp.int32 and got_n.dtype == jnp.int32
    assert int(got_n) == n and got_rows[:n].tolist() == rows
    # what follows them is a row's number too: an index map may read it
    assert got_rows.shape == (len(live),) and int(got_rows.max()) < len(live)


@pytest.mark.parametrize("tiles,per_group,tile_bytes,want", [
    (32, 16, 256 * 128 * 4, 16),    # Falcon-H1-34B: a group's heads, 2 MiB
    (32, 16, 512 * 128 * 4, 8),     # a state twice as wide: half of them
    (2, 1, 16 * 24 * 4, 2),         # the tiny trunk: both groups' one tile
    (10, 5, 2 << 20, 1),            # a tile as large as a block
    (24, 12, (2 << 20) // 5, 4),    # room for five: the divisor below it
    (32, 1, 128 * 128 * 4, 32),     # MiniCPM-SALA: a group a head, a whole row
    (32, 1, 1 << 20, 2),            # larger heads: two whole groups
    (12, 2, (2 << 20) // 5, 4),     # room for five tiles: two groups of two
    (16, 4, (2 << 20) // 9, 8),     # room for nine: two groups of four
    (64, 64, 128 * 128 * 4, 32),    # Granite: 64 of the group's 128 heads
])
def test_tile_block_divides_a_group_or_holds_whole_ones(tiles, per_group,
                                                       tile_bytes, want):
    assert ssm._tile_block(tiles, per_group, tile_bytes) == want


@pytest.mark.parametrize("heads,p,n,per_group,k,shape", [
    (128, 64, 128, 128, 2, (64, 128, 128)),   # Granite: two heads a tile
    (32, 128, 256, 16, 1, (32, 256, 128)),    # Falcon-H1-34B: a head a tile
    (32, 128, 128, 1, 1, (32, 128, 128)),     # lightning attention
    (6, 8, 16, 3, 3, (2, 16, 24)),            # the tiny trunk: a group a tile
    (8, 8, 128, 8, 8, (1, 128, 64)),          # eight of 8 fill half the lanes
    (8, 16, 16, 1, 1, (8, 16, 16)),           # a group a head: no neighbour
    (12, 32, 16, 6, 3, (4, 16, 96)),          # four would fit, three divide
    (4, 256, 64, 4, 1, (4, 64, 256)),         # P over a vreg's lanes
    (4, 16, 16, 1, 1, (4, 16, 16)),           # the tiny lightning trunk
])
def test_a_record_lays_a_groups_heads_side_by_side_on_the_lanes(
        heads, p, n, per_group, k, shape):
    assert ssm.lane_heads(p, per_group) == k
    assert ssm.record_shape(heads, p, n, per_group) == shape
    h = jnp.arange(3 * heads * p * n, dtype=jnp.float32).reshape(3, heads, p, n)
    r = ssm.state_to_record(h, per_group)
    assert r.shape == (3,) + shape
    # tile t, sublane i, lane (j, q): head t k + j, row q, column i
    t, i, j, q = shape[0] - 1, n - 1, k - 1, p // 2
    assert r[1, t, i, j * p + q] == h[1, t * k + j, q, i]
    np.testing.assert_array_equal(ssm.record_to_state(r, p), h)


def test_kernel_computes_lightning_attentions_update():
    """Δ = 1 at a live row, a decay that is a constant of the head, a
    group a head, no skip: ``S = λ S + v ⊗ k``, ``o = S q``."""
    h, d, slots = 4, 16, 3
    rs = np.random.RandomState(5)
    live = np.array([True, False, True])
    v, k, q = (rs.randn(slots, h, d).astype(np.float32) for _ in range(3))
    log_decay = -(2.0 ** (-8.0 * (np.arange(h) + 1) / h)).astype(np.float32)
    records = rs.randn(LAYERS, slots, h, d, d).astype(np.float32)
    args = [jnp.asarray(t) for t in (
        v, live[:, None] * np.ones((slots, h), np.float32), log_decay, k, q,
        np.zeros(h, np.float32))]
    o, new = _step(args, ssm.state_to_record(jnp.asarray(records), 1),
                   jnp.int32(1), jnp.asarray(live))
    o, new = np.asarray(o), np.asarray(ssm.record_to_state(new, d))
    lam = np.exp(log_decay)[None, :, None, None]
    want = lam * records[1] + v[..., :, None] * k[..., None, :]
    np.testing.assert_allclose(new[1][live], want[live], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new[1][~live], records[1][~live])
    np.testing.assert_array_equal(new[[0, 2]], records[[0, 2]])
    np.testing.assert_allclose(
        o[live], np.einsum("bhvk,bhk->bhv", want, q)[live], rtol=1e-5, atol=1e-5)
    assert not o[~live].any()
