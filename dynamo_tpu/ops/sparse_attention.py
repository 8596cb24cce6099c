"""Block-sparse attention over the paged cache (InfLLM-V2, as the
MiniCPM4 family publishes it): a query past ``dense_len`` tokens of
context scores *compressed keys*, keeps a few blocks of its context by
that score, and attends to the tokens of the kept blocks only.

For the query at position ``t`` (``n = t + 1`` tokens visible) and each
kv head:

- compressed key ``c_j = mean(k[stride·j : stride·j + kernel_size])``,
  for the windows that lie whole inside the visible tokens
  (``stride·j + kernel_size ≤ n``);
- ``p = softmax_j(q · c_j / √d)`` per query head, summed over the query
  heads of the kv head's group;
- a block of ``block_size`` tokens scores the largest ``p_j`` among the
  compressed keys whose window overlaps it;
- kept: the first ``init_blocks`` blocks, every block that overlaps the
  last ``window_size`` tokens, and the ``topk`` best-scoring of the
  others (ties: the lower block first);
- causal softmax attention over the tokens of the kept blocks.

With ``n ≤ dense_len`` the query attends to every visible key.

**What is kept on the device.** ``kernel_stride`` is the page size and
``kernel_size`` twice it, so a compressed key is the mean of two
neighbouring *page means*: the cache of compressed keys is one float32
mean a page a kv head, indexed by physical page like the pages
themselves, written when a page fills (a prefill chunk's whole pages, the
decode step that writes a page's last token) from the keys as the page
holds them.

**Pages a kv head.** The selection differs between kv heads, so the
family lays its pages out a head at a time: physical page ``n·KVH + g``
holds kv head ``g`` of block ``n`` (``[L, N·KVH, page, D]``). A (row, kv
head) pair is then a row of its own with one kv head to a kernel that
walks a block table: decode runs the paged decode kernel
(``ops/pallas_decode.paged_decode_attention``; its gather in XLA off the
chip) over a *compacted* table of the kept pages, in order, the row's
current page last, and reads nothing else.

**Prefill** computes a chunk's queries against the row's whole context,
gathered once, as a dense product under the kept-block mask, a tile of
queries at a time: at three attention layers of twelve the products are
a few per cent of a chunk's time, and no page walk is as wide as the
table. Nothing is approximated on either path: the same blocks as the
equations pick.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from .attention import (pad_minor, pallas_interpret, record_route,
                        record_row_list, record_table_width,
                        resolve_attention_impl)
from .live_rows import LiveRows
from .pallas_decode import paged_decode_attention

# queries a tile of prefill's masked product: [KVH·G·tile, T] float32
# scores are 302 MB at 32 heads and 18 432 keys
PREFILL_QUERY_TILE = 128


@dataclasses.dataclass(frozen=True)
class SparseShape:
    """The published ``sparse_config`` in pages of ``page`` tokens."""
    page: int
    pages_per_block: int
    topk: int
    init_blocks: int
    window_size: int
    dense_len: int

    @property
    def block_size(self) -> int:
        return self.page * self.pages_per_block

    def blocks(self, width: int) -> int:
        """Blocks a block table of ``width`` pages covers."""
        return -(-width // self.pages_per_block)

    def selects(self, width: int) -> bool:
        """Whether a row of ``width`` pages can be past ``dense_len``."""
        return width * self.page > self.dense_len

    def kept_pages(self, width: int) -> int:
        """The most pages a row of ``width`` pages keeps."""
        if not self.selects(width):
            return width
        window_blocks = -(-self.window_size // self.block_size) + 1
        sparse = (self.init_blocks + self.topk + window_blocks) * self.pages_per_block
        return min(width, max(-(-self.dense_len // self.page), sparse))


def sparse_shape(cfg, page: int) -> SparseShape:
    """From a ModelConfig's ``sparse_*`` fields; refuses what the page
    means cannot express."""
    if (cfg.sparse_kernel_stride != page
            or cfg.sparse_kernel_size != 2 * page
            or cfg.sparse_block_size % page):
        raise NotImplementedError(
            f"sparse_config kernel_stride {cfg.sparse_kernel_stride}, "
            f"kernel_size {cfg.sparse_kernel_size}, block_size "
            f"{cfg.sparse_block_size} with pages of {page} tokens: the "
            "compressed keys are kept as page means (stride = page, "
            "kernel = two pages, a block whole pages)")
    return SparseShape(page, cfg.sparse_block_size // page, cfg.sparse_topk,
                       cfg.sparse_init_blocks, cfg.sparse_window_size,
                       cfg.sparse_dense_len)


def compressed_probs(q, means, n, scale: float, page: int):
    """q [..., G, D], means [..., W, D] float32 page means in sequence
    order, n [...] visible tokens -> [..., W − 1] float32: the softmax of
    each query head over the valid compressed keys, summed over the G
    heads (zero at a key whose window is not whole inside ``n``)."""
    f32 = jnp.float32
    c = 0.5 * (means[..., :-1, :] + means[..., 1:, :])
    j = jnp.arange(c.shape[-2])
    valid = (j + 2) * page <= n[..., None]                       # [..., J]
    logits = jnp.einsum("...gd,...jd->...gj", q.astype(f32) * scale, c,
                        precision=jax.lax.Precision.HIGHEST)
    logits = jnp.where(valid[..., None, :], logits, jnp.finfo(f32).min)
    p = jax.nn.softmax(logits, axis=-1) * valid[..., None, :]
    return p.sum(axis=-2)


def block_scores(p, shape: SparseShape, n_blocks: int):
    """p [..., J] over compressed keys -> [..., n_blocks]: the largest
    among the keys whose window (pages j, j + 1) overlaps the block
    (pages r·m .. r·m + r − 1): j from r·m − 1 to r·m + r − 1."""
    r = shape.pages_per_block
    width = r * n_blocks + 1
    # shifted[i] = p[i − 1]; nothing before the first key or past the last
    shifted = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(1, 0)])[..., :width]
    shifted = jnp.pad(shifted, [(0, 0)] * (p.ndim - 1)
                      + [(0, width - shifted.shape[-1])])
    inner = shifted[..., :-1].reshape(p.shape[:-1] + (n_blocks, r)).max(-1)
    return jnp.maximum(inner, shifted[..., r::r])


def kept_blocks(scores, n, shape: SparseShape):
    """scores [..., NB], n [...] visible tokens -> bool [..., NB]: the
    blocks the query attends to (every visible block with ``n ≤
    dense_len``)."""
    nb = scores.shape[-1]
    m = jnp.arange(nb)
    n = n[..., None]
    visible = m * shape.block_size < n
    first_window = jnp.maximum(n - shape.window_size, 0) // shape.block_size
    forced = (m < shape.init_blocks) | (m >= first_window)
    vals, idx = jax.lax.top_k(jnp.where(forced, -1.0, scores),
                              min(shape.topk, nb))
    picked = ((idx[..., :, None] == m) & (vals[..., :, None] >= 0)).any(-2)
    return visible & ((n <= shape.dense_len) | forced | picked)


def _head_pages(block_tables, kvh: int):
    """[B, W] block ids -> [B, KVH, W] pages of the head-a-page layout."""
    return block_tables[:, None, :] * kvh + jnp.arange(kvh)[None, :, None]


def _flat_pages(pages_all):
    """[L, N·KVH, page, D] -> [L·N·KVH, page, D] (a view): a layer's
    page ``i`` is row ``layer · N·KVH + i``, so that no layer is sliced
    out of the stack."""
    l, per_layer, page, d = pages_all.shape
    return pages_all.reshape(l * per_layer, page, d)


def scatter_head_pages(k_all, v_all, k, v, slot_mapping, li):
    """Write a step's keys and values into layer ``li`` of the pages
    where they lie. k_all / v_all [L, N·KVH, page, D]; k / v [B, S, KVH,
    d]; slot_mapping [B, S] the engine's flat slots (block · page +
    offset; −1: no token): head ``g`` of that slot is row ``(block · KVH
    + g) · page + offset`` of its layer."""
    l, per_layer, page, d = k_all.shape
    kvh = k.shape[2]
    rows = per_layer * page
    idx = (((slot_mapping // page)[..., None] * kvh + jnp.arange(kvh)) * page
           + (slot_mapping % page)[..., None])                        # [B, S, KVH]
    idx = jnp.where((slot_mapping >= 0)[..., None], li * rows + idx, l * rows)
    idx = idx.reshape(-1)

    def put(pages_all, new):
        flat = pages_all.reshape(l * rows, d)
        new = pad_minor(new, d).astype(pages_all.dtype).reshape(-1, d)
        return flat.at[idx].set(new, mode="drop").reshape(pages_all.shape)

    return put(k_all, k), put(v_all, v)


def head_live_rows(live_rows, kvh: int):
    """A step's live rows (ops/live_rows.LiveRows or None) as the (row,
    kv head) pairs ``decode_attention`` walks: pair ``row · kvh + g`` is
    live where its row is."""
    if live_rows is None:
        return None
    live, rows, n = live_rows
    pairs = rows[:, None] * kvh + jnp.arange(kvh, dtype=rows.dtype)
    return LiveRows(jnp.repeat(live, kvh), pairs.reshape(-1), n * kvh)


def _walk_tables(q, k_all, v_all, li, tables, n_kept, scale: float, impl: str,
                 live_rows=None):
    """q [R, G, D], one kv head a row; tables [R, W] pages of layer
    ``li`` in the order they are walked; n_kept [R] tokens they hold ->
    [R, G, D]. The paged decode kernel on the chip (or in the
    interpreter: it walks ``live_rows`` alone, zeros in the others),
    a gather and a masked product in XLA elsewhere."""
    r, g, d = q.shape
    if resolve_attention_impl(impl) == "pallas":
        record_route("decode")
        if live_rows is not None:
            record_row_list()
        return paged_decode_attention(
            q[:, None], k_all, v_all, tables, n_kept, layer_idx=li,
            scale=scale, interpret=pallas_interpret(), one_head=True,
            live_rows=live_rows)[:, 0]
    record_route("xla")
    idx = li * k_all.shape[1] + tables
    k = _flat_pages(k_all)[idx].reshape(r, -1, d).astype(q.dtype)
    v = _flat_pages(v_all)[idx].reshape(r, -1, d).astype(q.dtype)
    logits = jnp.einsum("rgd,rtd->rgt", q * scale, k,
                        preferred_element_type=jnp.float32)
    held = jnp.arange(k.shape[1])[None, None, :] < n_kept[:, None, None]
    logits = jnp.where(held, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("rgt,rtd->rgd", probs, v)


def write_page_means(means_all, k_all, li, block_tables, first, last,
                     n_pages: int, kvh: int):
    """The means of the pages that tokens ``[first, last)`` of each row
    complete, from the keys as the cache holds them (after the step's
    keys were written). means_all [L, N·KVH, D] float32; k_all [L, N·KVH,
    page, D]; first / last [B]; ``n_pages`` static: the most pages a
    row's tokens can end in. A row without tokens (``last ≤ first``)
    writes nothing."""
    l, per_layer, d = means_all.shape
    page = k_all.shape[2]
    w = block_tables.shape[1]
    lp = (first // page)[:, None] + jnp.arange(n_pages)[None, :]     # [B, P]
    end = (lp + 1) * page
    complete = (end > first[:, None]) & (end <= last[:, None]) & (lp < w)
    blocks = jnp.take_along_axis(block_tables, jnp.minimum(lp, w - 1), axis=1)
    idx = li * per_layer + _head_pages(blocks, kvh)                  # [B, KVH, P]
    means = _flat_pages(k_all)[idx].astype(jnp.float32).mean(axis=-2)
    idx = jnp.where(complete[:, None, :], idx, l * per_layer)
    flat = means_all.reshape(l * per_layer, d)
    flat = flat.at[idx.reshape(-1)].set(means.reshape(-1, d), mode="drop")
    return flat.reshape(means_all.shape)


def _row_means(means_all, li, head_pages):
    l, per_layer, d = means_all.shape
    return means_all.reshape(l * per_layer, d)[li * per_layer + head_pages]


def decode_attention(q, k_all, v_all, means_all, li, block_tables,
                     context_lens, shape: SparseShape, kvh: int, scale: float,
                     impl: str = "auto",
                     live_rows=None) -> Tuple[jax.Array, jax.Array]:
    """One query a row. q [B, 1, H, D]; block_tables [B, W]; context_lens
    [B] (the query included) -> (out [B, 1, H, D] as wide as the cache's
    lanes, kept [B]: the tokens the row's first kv head attended to).

    Each (row, kv head) gets a table of its kept pages in sequence
    order, the row's current page last (it overlaps the window, so it is
    always kept, and it is the only page that can be part full), and
    the decode kernel walks that table as it walks any row's. A table no
    wider than ``dense_len`` holds no row that selects: its program has
    no selection in it. ``live_rows``: the (row, kv head) pairs whose
    row holds a token (``head_live_rows``), the pairs the kernel walks."""
    q = pad_minor(q, k_all.shape[-1])   # the cache's lanes; pad lanes are zero
    b, _, h, d = q.shape
    w = block_tables.shape[1]
    page = shape.page
    n = context_lens.astype(jnp.int32)
    pages = _head_pages(block_tables, kvh)                           # [B, KVH, W]
    if shape.selects(w):
        # W page means gathered a (row, kv head), scored, and W entries
        # sorted: the width is part of what this program does
        record_table_width()
        with jax.named_scope("sparse_select"):
            n2 = jnp.broadcast_to(n[:, None], (b, kvh))
            p = compressed_probs(q.reshape(b, kvh, h // kvh, d),
                                 _row_means(means_all, li, pages), n2,
                                 scale, page)
            kept = kept_blocks(block_scores(p, shape, shape.blocks(w)), n2,
                               shape)                                # [B, KVH, NB]
            live_page = jnp.arange(w) < (-(-n // page))[:, None, None]
            kept_page = (jnp.repeat(kept, shape.pages_per_block, axis=-1)[..., :w]
                         & live_page)
            # the kept pages first, in sequence order (one sort and one
            # gather: a list built from the picks by index arithmetic
            # took two gathers and 0.25 ms a layer more on the chip)
            iota = jnp.arange(w)
            order = jnp.argsort(jnp.where(kept_page, iota, w + iota),
                                axis=-1)[..., :shape.kept_pages(w)]
            pages = jnp.take_along_axis(pages, order, axis=-1)
            count = kept_page.sum(axis=-1).astype(jnp.int32)         # [B, KVH]
            n_kept = (jnp.maximum(count - 1, 0) * page
                      + ((n - 1) % page + 1)[:, None])
    else:
        n_kept = jnp.broadcast_to(n[:, None], (b, kvh))
    with jax.named_scope("sparse_attn"):
        out = _walk_tables(
            q.reshape(b * kvh, h // kvh, d), k_all, v_all, li,
            pages.reshape(b * kvh, -1), n_kept.reshape(b * kvh), scale, impl,
            live_rows)
    return out.reshape(b, 1, h, d), n_kept[:, 0]


def prefill_attention(q, k_all, v_all, means_all, li, block_tables,
                      positions, shape: SparseShape, kvh: int,
                      scale: float) -> jax.Array:
    """A chunk of queries a row, after the chunk's keys were written.
    q [B, S, H, D]; positions [B, S] -> [B, S, H, D] as wide as the
    cache's lanes.

    A row's whole context is gathered once ([KVH, W·page, D] each of K
    and V) and a tile of ``PREFILL_QUERY_TILE`` queries at a time scores
    the compressed keys, keeps its blocks query by query, and takes the
    dense product under that mask and the causal one."""
    q = pad_minor(q, k_all.shape[-1])   # the cache's lanes; pad lanes are zero
    b, s, h, d = q.shape
    g = h // kvh
    w = block_tables.shape[1]
    page, t = shape.page, w * shape.page
    tq = min(s, PREFILL_QUERY_TILE)
    if s % tq:
        raise ValueError(f"a prefill chunk of {s} queries is no whole "
                         f"number of tiles of {tq}")
    per_layer = k_all.shape[1]
    key_pos = jnp.arange(t)
    selects = shape.selects(w)
    outs = []
    for i in range(b):
        pages = _head_pages(block_tables[i:i + 1], kvh)[0]           # [KVH, W]
        idx = li * per_layer + pages
        k_ctx = _flat_pages(k_all)[idx].reshape(kvh, t, d).astype(q.dtype)
        v_ctx = _flat_pages(v_all)[idx].reshape(kvh, t, d).astype(q.dtype)
        means = (_row_means(means_all, li, pages)[:, None]
                 if selects else None)                               # [KVH, 1, W, D]

        def tile(args):
            q_t, pos_t = args                        # [tq, KVH, G, D], [tq]
            q_t = q_t.transpose(1, 0, 2, 3)          # [KVH, tq, G, D]
            mask = key_pos[None, None, :] <= pos_t[None, :, None]
            if selects:
                with jax.named_scope("sparse_select"):
                    n = jnp.broadcast_to(pos_t[None, :] + 1, (kvh, tq))
                    p = compressed_probs(q_t, means, n, scale, page)
                    kept = kept_blocks(
                        block_scores(p, shape, shape.blocks(w)), n, shape)
                    mask &= jnp.repeat(kept, shape.block_size, axis=-1)[..., :t]
            with jax.named_scope("sparse_attn"):
                logits = jnp.einsum("kqgd,ktd->kqgt", q_t * scale, k_ctx,
                                    preferred_element_type=jnp.float32)
                logits = jnp.where(mask[:, :, None, :], logits,
                                   jnp.finfo(jnp.float32).min)
                probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
                return jnp.einsum("kqgt,ktd->qkgd", probs, v_ctx)

        out = jax.lax.map(tile, (q[i].reshape(s // tq, tq, kvh, g, d),
                                 positions[i].reshape(s // tq, tq)))
        outs.append(out.reshape(s, h, d))
    return jnp.stack(outs)
