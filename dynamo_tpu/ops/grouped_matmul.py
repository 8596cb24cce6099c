"""Grouped matmul for routed experts: rows sorted by expert, one weight
matrix a group.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

``group_sizes[g]`` rows belong to group g, in order; the rows after the
last group (pad rows, rows of another expert-parallel member) belong to
none and come back as zeros. Only the weights of groups that have rows
are read, so a decode step streams the experts its batch chose and no
others, and a token is never dropped: there is no capacity.

One route: the Pallas grouped-matmul kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox.gmm``): a grid over row
tiles whose index maps pick each tile's expert from scalar-prefetched
group metadata, empty groups squeezed out of the grid. Off the TPU the
same kernel runs in the Pallas interpreter, so the CPU tests walk the
index maps and the group metadata the chip does. (On this toolchain,
jax 0.9.0 on the v5e, XLA compiles ``lax.ragged_dot`` to a grouped
kernel of its own, tiles 16 x 512 x 128, that also reads only groups
with rows, but at Moonlight's shapes it takes 2.4 x as long at a decode
batch of 8, 0.745 against 0.316 ms a product, and 3.6 x as long on a
2048-token chunk, 4.05 against 1.12 ms; PERF.md, PR 26.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

# row tile: a bf16 vreg holds 16 sublanes; 128 rows a visit measured
# best or equal from 48 to 12288 rows on the v5e (256 pads a decode
# batch's rows, 512 doubles the work of a tile two experts share)
_ROW_ALIGN = 16
_MAX_ROW_TILE = 128
# weight tile [tk, tn]: the contraction whole at each of the three
# widths the benchmark's configurations have (Moonlight 2048 and 1408,
# Xing4.0 3584 and 1024), 512 output columns: at most 3.5 MiB a buffer
# in bfloat16, 9.3 MiB with the row and output tiles double-buffered,
# inside the default scoped VMEM of 16 MiB. A contraction wider than
# _K_TILE is visited in tiles of it, the last one masked. Chosen on the
# v5e at [rows, 3584] x [64, 3584, 1024], a product at 256 / 8192 rows
# (my chip run, PR 33): the contraction whole 0.671 / 1.090 ms; 2048
# (what this file had: two visits, the second masked to 1536) 0.832 /
# 1.635; 1792 0.833 / 1.625; 3584 whole with 256 columns 0.751 / 1.209,
# with 128 0.751 / 1.403; 1792 x 1024 0.739 / 1.468. 2048 and 1408 get
# the tiles they had: (2048, 512) 0.542 / 0.972, (1408, 512) 0.578 /
# 1.035. The down-projection [rows, 1024] x [64, 1024, 3584] keeps 512
# columns, 0.698 / 1.396; 896 read 0.666 / 1.222 and 1792 0.683 / 1.156,
# left to a perf_opt that can claim it (PERF.md section 7). A
# contraction of 4096 (granite-4.0-h-small's experts, [rows, 4096] x
# [36, 4096, 768] at 640 / 20 480 rows; my chip run, PR 48) is whole
# too, 4 MiB a buffer: visited as 3584 + 512 masked it read 0.643 /
# 1.927 ms a product, whole 0.362 / 0.862 (2048 twice 0.461 / 1.370;
# whole with 384 columns 0.350 / 0.754, with 256 0.351 / 0.799: left as
# above). No benchmark configuration's contraction lies between the two
# values, so their tiles are what they were. A contraction wider than
# _K_TILE is visited in the largest tile up to it that divides it, no
# visit masked, where one of at least half of it does (Mixtral's down
# product of 14336 keeps its four visits of 3584, as before PR 48; 8192
# is two of 4096), and otherwise in tiles of _K_TILE with the last one
# masked. Nothing wider than 4096 has been timed on the chip (no cell
# has one); a gain there is a perf_opt's to claim.
_K_TILE = 4096
_N_TILE = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _k_tile(k: int) -> int:
    if k <= _K_TILE:
        return k
    whole = (t for t in range(_K_TILE, _K_TILE // 2 - 1, -128) if k % t == 0)
    return next(whole, _K_TILE)


def _tiling(rows: int, k: int, n: int):
    tm = min(_MAX_ROW_TILE, _round_up(rows, _ROW_ALIGN))
    return tm, _k_tile(k), min(_round_up(n, 128), _N_TILE)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   layer=None) -> jax.Array:
    """lhs [R, K] (rows sorted by group), rhs [G, K, N], group_sizes [G]
    int32 with sum <= R  ->  [R, N] in lhs's dtype; rows past the last
    group are zero.

    ``layer`` (int32 scalar, traced): ``rhs`` is the stack of every
    layer's weights, [L, G, K, N], and the product uses layer ``layer``
    of it. The kernel finds the layer through its index map, in HBM: a
    layer scan that sliced the stack would have XLA copy the layer's
    weights (1.1 GB an expert layer of Moonlight) before every kernel
    call, at three times the cost of the products themselves (PERF.md,
    PR 26)."""
    rows, k = lhs.shape
    n = rhs.shape[-1]
    groups = group_sizes.shape[0]
    sizes = group_sizes
    if layer is not None:
        # the other layers' groups are empty: the grid squeezes them out
        rhs = rhs.reshape((-1,) + rhs.shape[2:])
        sizes = lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), jnp.int32), group_sizes,
            (layer * groups,))
    tm, tk, tn = _tiling(rows, k, n)
    padded = _round_up(rows, tm)
    x = lhs if padded == rows else jnp.pad(lhs, ((0, padded - rows), (0, 0)))
    out = gmm(x, rhs, sizes, preferred_element_type=lhs.dtype,
              tiling=(tm, tk, tn),
              interpret=jax.default_backend() != "tpu")[:rows]
    # the kernel never visits rows outside every group: what it leaves
    # there is uninitialised memory
    in_group = jnp.arange(rows)[:, None] < group_sizes.sum()
    return jnp.where(in_group, out, jnp.zeros((), out.dtype))
