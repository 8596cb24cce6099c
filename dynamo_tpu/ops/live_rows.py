"""The rows of a decode step that hold a token, as a list a kernel's grid
can walk.

A decode program runs ``max_batch_size`` rows whatever the traffic; the
scheduler marks a row without a sequence with the drop slot
(``slot_mapping == -1``). The decode kernels (ops/pallas_decode.py,
ops/ssm.py) take the compacted list of the other rows as a prefetched
scalar operand and its length as their grid's first bound, a value known
on the device only: a row without a token is then no step of the grid.
The list is the same for every layer and every kernel of a step, so a
trunk makes it once (``decode_live_rows``), outside its layer scan, and
hands the one value to each of them.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class LiveRows(NamedTuple):
    """A decode step's rows that hold a token, as a kernel takes them."""
    live: jax.Array   # [B] bool: the mask
    rows: jax.Array   # [B] int32: the live rows' numbers in order, then B - 1
    n: jax.Array      # int32: how many of them: the grid's first bound


def live_row_list(live: jax.Array) -> LiveRows:
    """live [B] bool -> the mask, the numbers of the rows that hold a
    token, in order, in ``rows[:n]`` (``B - 1`` after them: a row's number
    too, an index map may read it), and ``n``: what a decode kernel's
    grid walks."""
    b = live.shape[0]
    seen = jnp.cumsum(live.astype(jnp.int32))                 # [B]
    # the (i+1)-th live row is the first with seen > i
    rows = (seen[None, :] <= jnp.arange(b)[:, None]).sum(axis=1)
    return LiveRows(live, jnp.minimum(rows, b - 1).astype(jnp.int32),
                    seen[-1])


def decode_live_rows(
    slot_mapping: jax.Array,  # [B, S] flat cache slot per token; -1: none
) -> Optional[LiveRows]:
    """``live_row_list`` of a decode step (``S == 1``: a row's one token
    is real where it has a slot), and None for any other step: what a
    trunk hands to its attention and state kernels."""
    if slot_mapping.shape[1] != 1:
        return None
    return live_row_list(slot_mapping[:, 0] >= 0)
