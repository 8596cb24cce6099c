"""Sinkhorn normalisation of many small matrices at once: the mixing
matrices of models/mhc.py, one ``n x n`` matrix a token.

    M = exp(clip(logits, lo, hi));  iters times:  M /= (column sums + eps);  M /= (row sums + eps)

Left to XLA, the unrolled iterations come out as four small fusions an
iteration (a sum and a division for the columns, the same for the rows:
79 to 81 operations a sublayer in the compiled decode and prefill
programs of Xing4.0, v5e compiler, PR 33), each a launch of its own
over a few vregs. Here they are one kernel: the ``n^2`` entries are
``n^2`` slabs ``[T / 128, 128]`` with tokens on sublanes and lanes, a
row or column sum is ``n - 1`` adds of slabs, a division one reciprocal
of the sum and ``n`` products, and nothing but elementwise operations
between whole slabs: no reduction inside a vreg, no partial tile.

One route, as ops/grouped_matmul.py: off the TPU the same kernel runs
in the Pallas interpreter, so the CPU tests walk what the chip runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
# sublane rows of 128 tokens a block: [n^2, 64, 128] float32 is 512 KiB
# in and as much out at n = 4, and 64 covers the largest prefill step
# (8192 tokens) in one block
_MAX_BLOCK_ROWS = 64


def _kernel(z_ref, o_ref, *, n, iters, eps, lo, hi):
    def iteration(_, m):
        m = [list(row) for row in m]
        for j in range(n):                       # columns
            inv = 1.0 / (sum(m[i][j] for i in range(1, n)) + m[0][j] + eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv
        for i in range(n):                       # rows
            inv = 1.0 / (sum(m[i][j] for j in range(1, n)) + m[i][0] + eps)
            m[i] = [x * inv for x in m[i]]
        return m

    # a loop and not twenty copies of the body: the same arithmetic in
    # the same order, a twentieth of the program. Unrolled, the body was
    # a third of a tiny trunk's lowering in the interpreter, and on the
    # v5e the cell's warm set-up was 105 s against 86 (14 launches a
    # decode step: 0.0124 ms unrolled, 0.0133 as a loop; PERF.md, PR 33)
    m = jax.lax.fori_loop(0, iters, iteration, [
        [jnp.exp(jnp.clip(z_ref[i * n + j], lo, hi)) for j in range(n)]
        for i in range(n)])
    for i in range(n):
        for j in range(n):
            o_ref[i * n + j] = m[i][j]


def sinkhorn(logits: jax.Array, n: int, iters: int, eps: float,
             clamp) -> jax.Array:
    """logits [n*n, T] float32 (row-major matrices, tokens minor) ->
    the normalised matrices [n*n, T]: after the last iteration every
    row sums to 1 and every column nearly so."""
    nn, t = logits.shape
    rows = -(-t // _LANES)
    block = min(rows, _MAX_BLOCK_ROWS)
    rows = -(-rows // block) * block
    z = jnp.pad(logits, ((0, 0), (0, rows * _LANES - t)))
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, iters=iters, eps=eps,
                          lo=clamp[0], hi=clamp[1]),
        out_shape=jax.ShapeDtypeStruct((nn, rows, _LANES), jnp.float32),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((nn, block, _LANES), lambda r: (0, r, 0))],
        out_specs=pl.BlockSpec((nn, block, _LANES), lambda r: (0, r, 0)),
        interpret=jax.default_backend() != "tpu",
        name="mhc_sinkhorn",
    )(z.reshape(nn, rows, _LANES))
    return out.reshape(nn, rows * _LANES)[:, :t]
