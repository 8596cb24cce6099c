"""Learned sparse selection inside paged latent attention, and latent
attention in blocks (models/dots3.py).

A full layer of ``dots3_note`` keeps one ``index_head_dim``-wide key a
token beside the token's row (the latent and behind it the rope key, one
row of one page stack: models/dots3.py says why). The rows lie in pages;
**the indexer's keys lie by slot, in sequence order**: records ``[full
layers, slots, T, di']``, a token's key at its row's slot and its own
position (``record_len``: ``T``). The family's pages are private to a
sequence (models/afmoe.py), so nothing needs these keys in pages, and
every decode step reads all of a row's: where they lie one after the
other they are the product's operand as they are; in pages of 4 KB the
step gathered 36.8 k pages a layer at 19-23 ns a page into a copy the
product then read back (PERF.md section 6, PR 63). A query scores every
earlier key with ``J`` small heads,

    I(t, s) = Σ_j w_t,j · ReLU(q^I_t,j · k^I_s)          float32

and attends to the ``index_topk`` keys of largest ``I(t, ·)`` alone (to
all of them while there are no more than that). The pick is exact and
sort-free: the ``k``-th largest score of a row by the cutoff search of
engine/sampling.py (``kth_largest``), everything over it, and of the
entries equal to it the earliest, so that a row keeps ``min(k, keys)``
keys whatever ties there are.

- **decode** (one query a row, ``picked_decode_attention``): the scores
  of the keys of each row's record under the table's width (row *i* is
  slot *i*; what an earlier sequence left past ``context_len`` is hidden
  by the pick's mask, so a slot is never cleared), the pick, the picked
  tokens' rows gathered out of the pages (``[B, k, r' + rd']``, one
  lookup a key), one dense absorbed product over them. The work follows
  the block table's width (the scores and the pick), so the program is
  one of the width ladder's (``record_table_width``).
- **prefill** (``blocked_latent_attention``): a block of ``QUERY_BLOCK``
  queries at a time against blocks of ``KEY_BLOCK`` keys with a running
  softmax, from the first block a query of the block can see (the
  window's, for a window layer) to the last (the causal edge): no score
  tensor is wider than a block, and the work follows the keys that are
  there, not the table's width. A key block is the pages of whichever
  stacks the kind keeps, side by side (a full layer's one, a window
  layer's latents and rope keys). A full layer's query block first
  scores the same blocks of positions with the indexer, each a slice of
  the row's record, and makes its pick a mask.

Scopes: ``dsa_index`` (scores), ``dsa_select`` (cutoff, mask, the picked
tokens' list), ``dsa_attend`` (the gather of the picked rows and the
product over them; in prefill the masked blocks).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..engine.sampling import kth_largest
from .attention import record_table_width

QUERY_BLOCK = 256
KEY_BLOCK = 1024
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


class Indexer(NamedTuple):
    """A full layer's indexer for the step's queries."""
    q: jax.Array        # [B, S, J, di] the indexer's queries
    w: jax.Array        # [B, S, J] float32 head weights
    keys: jax.Array     # [L, slots, T, di] the indexer's keys by slot
    slots: jax.Array    # [B] int32 each row's slot
    topk: int


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I`` of queries [B, S, J, di] (weights [B, S, J]) against keys
    [B, T, di] -> [B, S, T] float32: operands as stored, sums float32."""
    dots = jnp.einsum("bsjd,btd->bsjt", q, keys.astype(q.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bsjt,bsj->bst", jax.nn.relu(dots),
                      w.astype(jnp.float32))


LANES = 128


def _lane_blocks(mask: jax.Array):
    """mask [..., T] -> (its blocks of 128 [..., T / 128, 128], the
    running count inside each block, inclusive, float32). A running
    count along 18 k entries is a product with a triangle of ones here
    (exact: 0 / 1 operands, float32 sums), because the compiler's
    ``cumsum`` of so long an axis is a window reduction that took 10 ms
    a pick on the v5e (PERF.md section 6, PR 54)."""
    t = mask.shape[-1]
    pad = -t % LANES
    if pad:
        mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    blocks = mask.reshape(mask.shape[:-1] + (-1, LANES))
    triangle = (jnp.arange(LANES)[:, None]
                <= jnp.arange(LANES)[None, :]).astype(jnp.bfloat16)
    within = jnp.einsum("...l,lm->...m", blocks.astype(jnp.bfloat16),
                        triangle, preferred_element_type=jnp.float32)
    return blocks, within


def running_count(mask: jax.Array) -> jax.Array:
    """int32 [..., T]: how many of ``mask[..., :i + 1]`` are true."""
    _, within = _lane_blocks(mask)
    totals = within[..., -1].astype(jnp.int32)
    before = jnp.cumsum(totals, -1) - totals        # a few hundred blocks
    seen = within.astype(jnp.int32) + before[..., None]
    return seen.reshape(seen.shape[:-2] + (-1,))[..., :mask.shape[-1]]


def pick_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """bool [..., T]: the ``min(k, valid entries)`` entries of largest
    score among the ``valid`` ones of each row; of equal scores the
    earliest."""
    scores = jnp.where(valid, scores, -jnp.inf)
    if scores.shape[-1] <= k:
        return valid
    cut = kth_largest(scores, k)[..., None]
    over = scores > cut
    ties = valid & (scores == cut)
    room = k - over.sum(-1, keepdims=True, dtype=jnp.int32)
    return over | (ties & (running_count(ties) <= room))


def picked_list(mask: jax.Array, k: int, values=None, bound: int = 0):
    """mask [B, T] with at most ``k`` true a row -> (their columns in
    order [B, k] int32, 0 past the row's count; the counts [B]).
    ``values`` [B, T] int32 in ``[0, bound)``: the list is of the true
    columns' values instead (a picked key's row in the cache), so that
    nothing is looked up by the list afterwards.

    Without a gather, a scatter or a long running count (a gather costs
    the v5e 10-20 ns an index whatever it fetches: 65 k picked columns
    0.7 ms a layer): inside each block of 128 columns the true ones are
    moved to the front by a one-hot reduction (the ``r``-th true column
    of a block), and place ``j`` of the list then reads its block (the
    first whose running total passes ``j``) and its rank inside it
    through two more one-hot products. A product carries digits under
    128, exact in bfloat16."""
    b, t = mask.shape
    blocks, within = _lane_blocks(mask)                       # [B, nb, 128]
    nb = blocks.shape[1]
    lane = jnp.arange(LANES)
    if values is None:
        values = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        bound = t
    values = jnp.pad(values, ((0, 0), (0, nb * LANES - t))).reshape(
        b, nb, LANES)
    # front[b, n, r]: the value at block n's (r + 1)-th true column
    hit = blocks[:, :, None, :] & (
        within[:, :, None, :] == (lane[:, None] + 1).astype(jnp.float32))
    front = jnp.sum(jnp.where(hit, values[:, :, None, :], 0), -1)
    digits = max(1, -(-(max(bound, 2) - 1).bit_length() // 7))
    front = jnp.stack([(front >> (7 * d)) & 127 for d in range(digits)], -2)
    totals = within[..., -1].astype(jnp.int32)                # [B, nb]
    ends = jnp.cumsum(totals, -1)
    count = ends[:, -1]
    place = jnp.arange(k, dtype=jnp.int32)
    passed = ends[:, None, :] <= place[None, :, None]         # [B, k, nb]
    block = jnp.minimum(passed.sum(-1, dtype=jnp.int32), nb - 1)
    rank = place[None] - jnp.sum(
        jnp.where(passed, totals[:, None, :], 0), -1)         # inside its block
    of_block = jnp.einsum(
        "bkn,bndr->bkdr",
        (block[..., None] == jnp.arange(nb)).astype(jnp.bfloat16),
        front.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    digit = jnp.sum(jnp.where(rank[..., None, None] == lane, of_block, 0.0),
                    -1).astype(jnp.int32)                     # [B, k, digits]
    out = sum(digit[..., d] << (7 * d) for d in range(digits))
    return jnp.where(place[None] < count[:, None], out, 0), count


def _layer_pages(cache: jax.Array, li):
    """Layer ``li``'s pages [N, page, d] of a stacked cache
    [L, N, 1, page, d], and a table's offset into the flat [L N, ...]."""
    l, n = cache.shape[:2]
    flat = cache.reshape((l * n,) + cache.shape[3:])
    return flat, jnp.asarray(li, jnp.int32) * n


def _gather_pages(cache, li, table):
    """The pages ``table`` [B, W] names in layer ``li`` -> [B, W page, d]
    (the gather indexes the layer: no copy of it)."""
    flat, base = _layer_pages(cache, li)
    got = flat[table + base]                      # [B, W, page, d]
    return got.reshape(table.shape[0], -1, got.shape[-1])


def record_len(max_len: int, page: int) -> int:
    """Positions of a slot's record for sequences of up to ``max_len``
    tokens: whole key blocks of the blocked prefill, so that the slice
    that is a key block never overhangs the record (a
    ``dynamic_slice`` that does moves its start, and would score other
    positions' keys)."""
    kb = KEY_BLOCK // page * page
    return -(-max_len // kb) * kb


def _flat_records(records, li, slots):
    """Records [L, slots, T, d] as ``[L slots, T, d]`` and where layer
    ``li``'s record of each of ``slots`` [B] is in it (``_layer_pages``:
    an index into the flat view takes no slice of the stack)."""
    l, n = records.shape[:2]
    flat = records.reshape((l * n,) + records.shape[2:])
    return flat, jnp.asarray(li, jnp.int32) * n + slots


def _under_table(records, t: int):
    if t > records.shape[2]:
        raise ValueError(
            f"a block table of {t} positions over records of "
            f"{records.shape[2]} a slot (record_len)")


def picked_decode_attention(q_lat, q_rope, rows_all, li, table,
                            context_lens, scale: float, index: Indexer):
    """One query a row (q_lat [B, 1, H, r'], q_rope [B, 1, H, rd'], padded
    to their lanes of a row of ``rows_all`` [L, N, 1, page, r' + rd'])
    over the ``index.topk`` keys its indexer picks -> [B, 1, H, r' + rd']:
    the latent output in the first ``r'`` lanes (the value product runs
    over a gathered row whole, so that no copy of the rows' latent part
    is made; what lies behind is not an output)."""
    record_table_width()
    b = q_lat.shape[0]
    page = rows_all.shape[3]
    t = table.shape[1] * page
    k = min(index.topk, t)
    _under_table(index.keys, t)
    with jax.named_scope("dsa_index"):
        # row i is slot i: the b records' first t positions where they lie
        # (the offset held as a value: where the compiler knows the layer,
        # the dense prefix's, it would make the slice a static one and
        # copy it, 151 MB a step at 32 x 18 k keys, where the loop's
        # dynamic slice is the product's operand in place)
        flat, first = _flat_records(index.keys, li, 0)
        keys = jax.lax.dynamic_slice(
            flat, (jax.lax.optimization_barrier(first), 0, 0),
            (b, t, flat.shape[-1]))
        scores = index_scores(index.q, index.w, keys)[:, 0]
    with jax.named_scope("dsa_select"):
        valid = jnp.arange(t)[None] < context_lens[:, None]
        # a key's row of the flat [L N page, d] view of the cache
        _, base = _layer_pages(rows_all, li)
        at = ((table + base)[:, :, None] * page
              + jnp.arange(page)).reshape(b, t)
        rows, count = picked_list(
            pick_mask(scores, valid, index.topk), k, values=at,
            bound=rows_all.shape[0] * rows_all.shape[1] * page)
    with jax.named_scope("dsa_attend"):
        q = jnp.concatenate([q_lat, q_rope], -1)[:, 0]
        picked = rows_all.reshape(-1, rows_all.shape[-1])[rows].astype(q.dtype)
        s_log = jnp.einsum("bhd,bkd->bhk", q, picked,
                           preferred_element_type=jnp.float32) * scale
        live = jnp.arange(k)[None] < count[:, None]
        s_log = jnp.where(live[:, None], s_log, MASK_VALUE)
        probs = jax.nn.softmax(s_log, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhk,bkd->bhd", probs, picked)
    return out[:, None]


def blocked_latent_attention(q_lat, q_rope, stacks, li, table,
                             positions, valid, context_lens, scale: float,
                             sliding_window: Optional[int] = None,
                             index: Optional[Indexer] = None):
    """Latent attention of queries [B, S, H, r'] / [B, S, H, rd'] over the
    pages ``table`` [B, W] names in layer ``li`` of ``stacks``, in blocks
    (module docstring) -> latent output [B, S, H, r']. ``stacks``: the
    page stacks [L, N, 1, page, ·] whose lanes side by side are a key's
    ``r' + rd'`` (one stack of whole rows, or the latents and the rope
    keys), the latent first. ``valid`` [B, S]: the
    queries that are tokens (a pad query's output is not read). A query
    at ``p`` sees keys ``<= p`` under ``context_lens``, the last
    ``sliding_window`` of them where given, those ``index`` picks where
    given."""
    b, s, h, r = q_lat.shape
    page = stacks[0].shape[3]
    w = table.shape[1]
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    kp = min(KEY_BLOCK // page, w)                  # pages a key block
    kb = kp * page
    n_kb = -(-w // kp)
    # whole key blocks: a table padded with page 0, which no row holds
    table = jnp.pad(table, ((0, 0), (0, n_kb * kp - w)))
    t = n_kb * kb
    f32 = jnp.float32

    def keys_of(cache, j):
        pages = jax.lax.dynamic_slice_in_dim(table, j * kp, kp, axis=1)
        return _gather_pages(cache, li, pages)

    if index is not None:
        _under_table(index.keys, t)
        records, record = _flat_records(index.keys, li, index.slots)

    def index_keys_of(j):
        """Positions [j kb, (j + 1) kb) of each row's record: a prefill
        step has few rows, a slice each (falcon_h1.slot_records)."""
        return jnp.concatenate([jax.lax.dynamic_slice(
            records, (record[i], j * kb, 0), (1, kb, records.shape[-1]))
            for i in range(b)])

    def visible(pos, key_pos):
        """[B, qb, kb']: key positions a query may see, the pick apart."""
        see = ((key_pos[None, None] <= pos[:, :, None])
               & (key_pos[None, None] < context_lens[:, None, None]))
        if sliding_window is not None:
            see = see & (key_pos[None, None] > pos[:, :, None] - sliding_window)
        return see

    def block(args):
        ql, qr, pos, ok, *iq_iw = args        # a block of qb queries
        real = jnp.where(ok, pos, -1)
        hi = jnp.max(real) // kb + 1          # key blocks [lo, hi)
        lo = jnp.int32(0)
        if sliding_window is not None:
            first = jnp.min(jnp.where(ok, pos, t)) - sliding_window + 1
            lo = jnp.clip(first, 0, t) // kb
        keep = None
        if index is not None:
            with jax.named_scope("dsa_index"):
                def score(j, acc):
                    part = index_scores(*iq_iw, index_keys_of(j))
                    return jax.lax.dynamic_update_slice_in_dim(
                        acc, part, j * kb, axis=2)
                scores = jax.lax.fori_loop(
                    lo, hi, score, jnp.full((b, qb, t), -jnp.inf, f32))
            with jax.named_scope("dsa_select"):
                keep = pick_mask(scores, visible(pos, jnp.arange(t)),
                                 index.topk)

        # one product a pair of blocks scores both parts: two would each
        # write the block's [qb, H, kb] float32 scores (134 MB at 128
        # heads) for a third operation to add
        q = jnp.concatenate([ql, qr], -1)

        def fold(j, carry):
            m, l, acc = carry
            got = [keys_of(stack, j).astype(ql.dtype) for stack in stacks]
            key = jnp.concatenate(got, -1)    # (of one stack: itself)
            c = got[0][..., :r]                               # [B, kb, r]
            s_log = jnp.einsum("bqhd,bkd->bqhk", q, key,
                               preferred_element_type=f32) * scale
            see = (visible(pos, j * kb + jnp.arange(kb)) if keep is None
                   else jax.lax.dynamic_slice_in_dim(keep, j * kb, kb, axis=2))
            s_log = jnp.where(see[:, :, None], s_log, MASK_VALUE)
            m1 = jnp.maximum(m, s_log.max(-1))
            p = jnp.where(see[:, :, None], jnp.exp(s_log - m1[..., None]), 0.0)
            fade = jnp.exp(m - m1)
            acc = acc * fade[..., None] + jnp.einsum(
                "bqhk,bkr->bqhr", p.astype(ql.dtype), c,
                preferred_element_type=f32)
            return m1, l * fade + p.sum(-1), acc

        with jax.named_scope("dsa_attend" if index is not None
                             else "latent_blocks"):
            m, l, acc = jax.lax.fori_loop(lo, hi, fold, (
                jnp.full((b, qb, h), MASK_VALUE, f32),
                jnp.zeros((b, qb, h), f32), jnp.zeros((b, qb, h, r), f32)))
        return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(ql.dtype)

    def split(x):       # [B, S, ...] -> [S / qb, B, qb, ...]
        return jnp.moveaxis(
            x.reshape((b, s // qb, qb) + x.shape[2:]), 1, 0)

    of_index = () if index is None else (index.q, index.w)
    out = jax.lax.map(block, tuple(split(x) for x in (
        q_lat, q_rope, positions, valid, *of_index)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, r)
