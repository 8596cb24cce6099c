"""Pallas TPU decode-specialized paged attention (S == 1).

Why a second kernel: decode dominates serving time and has a degenerate
shape — one query token per sequence attending to the whole paged
context. The general kernel (ops/pallas_attention.py) drives its page
walk with a grid dimension sized to the block-table *capacity* W, so a
sequence with 32 live pages still pays W=128 grid steps of machinery per
layer (profiled at ~0.9 ms/layer on v5e for the 1B flagship — 40x the
bandwidth bound). Here the page walk is a data-dependent ``fori_loop``
bounded by ``ceil(context_len / page)`` inside a grid over the rows that
hold a token: work is proportional to *live* context, not capacity, and
to the rows that decode, not to ``max_batch_size``. The decode and MLA
kernels take the compacted list of live rows (ops/live_rows.py) as a
prefetched operand and its length, a traced value, as the grid's bound:
grid step ``i`` serves row ``rows[i]``, and a pad row of the batch (a
whole chunk's copies, products and ``exp`` before) is no step at all.
Its output is memory nobody wrote, which the wrappers return as zeros.
A caller without a mask gets every row walked in order.

Mechanics: the paged KV cache stays in HBM (``memory_space=ANY``); the
kernel pulls pages VMEM-ward itself with double-buffered async copies
(``pltpu.make_async_copy``) — page indices come from the scalar-prefetched
block table, so the indirection rides the DMA engine, and compute on
chunk c overlaps the fetch of chunk c+1. The cache may be the engine's
full stacked-by-layer array ([L, N, page, KVH, D]); the layer to read is
a runtime index (``layer_idx``) so the per-layer ``lax.scan`` over the
transformer trunk needs no per-layer cache slicing (which XLA
materializes as a copy of the whole layer).

How a row is walked (``_walk``: the decode, MLA and verify kernels): a
chunk is sized by its bytes, not by a count of pages. ``chunk_pages``
derives the pages of a wide chunk at trace time from the page's bytes,
the scores its columns add and the table's width (about 2 MB of K and V:
8 pages of Phi-3's 32 kv heads, 64 of Trinity-Mini's 4 or of the latent
cache), and a row walks wide chunks while it has that many pages left,
then chunks of the tail's 8 (16 for MLA and for verify), so a chat-length
row never pays for a product wider than its keys. Only a row's own pages
are copied: a chunk copies the pages of it the row owns (a loop of
four-page turns, the same count on the start and on the wait), and what
the last chunk leaves uncopied is cleared where it is a value. A page
reaches VMEM as its [page * KVH, D] rows, so a chunk is the dots' operand
as it lies.

Reference analog: the decode-path paged-attention kernels of the GPU
engines the reference delegates to (SURVEY.md §2.4); same role as
vLLM's paged_attention_v2 CUDA kernel, reimagined for the TPU memory
system (explicit HBM→VMEM pipeline instead of SM shared memory).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .live_rows import LiveRows

MASK_VALUE = -1e30
# a token's place no query's bounds reach (_verify_kernel)
OTHER_HEAD = 2 ** 30


def _out_struct(shape, dtype, *arrays) -> jax.ShapeDtypeStruct:
    """out_shape whose varying-manual-axes set is the union of the
    inputs': the output varies over every manual mesh axis any input
    varies over, so the kernels compose with ``check_vma=True``
    shard_maps (the partial-manual pipeline in parallel/pipeline.py).
    Empty outside shard_map tracing."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _walked_rows(b: int, live_rows: Optional[LiveRows]):
    """(rows [B] int32, n) a kernel's grid walks: the step's live rows,
    or every row in order with ``n = b``, a Python int, for a caller
    without a mask."""
    if live_rows is None:
        return jnp.arange(b, dtype=jnp.int32), b
    return live_rows.rows, live_rows.n


def _zero_unwalked(out: jax.Array, live_rows: Optional[LiveRows]) -> jax.Array:
    """A row the grid never visited is memory nobody wrote: zeros there,
    so that nothing unwritten reaches an MLP, a router or the sampler."""
    if live_rows is None:
        return out
    live = live_rows.live
    return jnp.where(live.reshape((-1,) + (1,) * (out.ndim - 1)), out, 0)


# A wide chunk holds about this much of the cache (K and V, or the latent
# and its rope key): scripts/chunk_sweep.py on the v5e, PERF.md §5 "Since
# PR 42". Under it a chunk's fixed costs weigh too much (a 262 KB chunk
# of eight 16 KB pages reads at 42 % of 819 GB/s, 2 MB at 69 %); over
# it nothing more is won and the slots crowd VMEM.
CHUNK_BYTES = 2 << 20
# ... and no more than this of float32 scores ([query rows, columns] of a
# chunk: Phi-3's at eight pages), nor more pages than this (128 read
# within 0.3 points of 64 at every small page and double the slots)
SCORE_BYTES = 512 << 10
MAX_CHUNK_PAGES = 64

# (kernel, bytes a page, bytes of scores a page) -> (pages a wide chunk,
# pages a tail chunk) of every decode kernel traced so far: what
# ModelRunner.warmup logs
_chunks_traced: dict = {}


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def chunk_pages(page_bytes: int, score_bytes: int, tail_pages: int,
                width: int) -> int:
    """Pages a wide chunk of a row's walk holds, from what a wrapper sees
    in its inputs: the bytes a page moves (``page_bytes``: every stream),
    the float32 scores a page adds (``score_bytes``), the tail's chunk
    and the block table's width. The largest power of two within
    ``CHUNK_BYTES``, ``SCORE_BYTES``, ``MAX_CHUNK_PAGES`` and the table,
    and never under the tail's: 8 at Phi-3's 262 KB a page (no wide
    chunk: the walk is the tail's), 64 at Trinity-Mini's 32 KB, a tp=4
    shard's 16 KB and the latent cache's 20 KB."""
    pages = min(CHUNK_BYTES // page_bytes, SCORE_BYTES // score_bytes,
                MAX_CHUNK_PAGES, width)
    return max(_pow2_floor(pages), tail_pages)


def _chunks(kernel: str, pinned: Optional[int], tail_pages: int,
            page_bytes: int, score_bytes: int, width: int):
    """(pages a wide chunk, pages a tail chunk) of one traced call.
    ``pinned`` (a kernel's test, the sweep) makes every chunk that many
    pages; nobody who serves sets it."""
    tail = _pow2_floor(min(pinned or tail_pages, width))
    wide = tail if pinned else chunk_pages(page_bytes, score_bytes, tail, width)
    _chunks_traced[kernel, page_bytes, score_bytes] = (wide, tail)
    return wide, tail


def chunks_traced() -> list:
    """[{kernel, page_bytes, wide_pages, tail_pages, wide_bytes}] of the
    decode kernels traced in this process."""
    return [
        {"kernel": kernel, "page_bytes": page_bytes, "wide_pages": wide,
         "tail_pages": tail, "wide_bytes": wide * page_bytes}
        for (kernel, page_bytes, _), (wide, tail)
        in sorted(_chunks_traced.items())
    ]


def _fold(carry, s_log, v):
    """One chunk into the running softmax: ``carry`` (m, l [rows, 128]
    lane-broadcast, acc [rows, D]), the chunk's masked scores [rows,
    cols] in float32 and its values [cols, D]."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s_log, -1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p_unn = jnp.exp(s_log - m_new[:, 0:1])                # [rows, cols]
    l_new = alpha * l + jnp.sum(p_unn, -1, keepdims=True)
    pv = jax.lax.dot_general(
        p_unn.astype(v.dtype), v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                     # [rows, D]
    return m_new, l_new, acc * alpha[:, 0:1] + pv


# pages a turn of a chunk's copy loop, straight-line inside the turn: a
# chunk's copies all unrolled (128 a site at 64 pages) took a second a
# kernel to trace and lower, on every warm start, for every program
_RUN = 4


def _walk(b, bt_ref, sem, streams, *, first_page, npages, wide: int,
          tail: int, attend, carry):
    """A row's walk over its pages ``[first_page, npages)``: whole chunks
    of ``wide`` pages while that many are left, then chunks of ``tail``
    pages, two VMEM slots, chunk ``c + 1`` copied while ``c`` is computed.
    ``streams``: (block -> its page in HBM, VMEM buffer [2, wide, ...],
    whether the buffer is a value) a cache; ``attend(carry, slot,
    first page, pages)`` folds one chunk into the running softmax.

    Only the row's own pages are copied: chunk ``c`` holds ``pages(c)``
    of them, ``wide``, ``tail`` or what is left of the row, the same
    count on the start and on the wait so that a slot's semaphore counts
    match. What the row's last chunk does not fill of its ``tail`` pages
    it clears where the buffer is a value: those scores are masked, but
    ``0 * NaN`` is NaN and VMEM holds whatever it held."""
    n_wide = (npages - first_page) // wide if wide > tail else 0
    tail0 = first_page + n_wide * wide
    n_chunks = n_wide + pl.cdiv(npages - tail0, tail)

    def first(c):       # chunk c's first page
        return jnp.where(c < n_wide, first_page + c * wide,
                         tail0 + (c - n_wide) * tail)

    def pages(c):       # how many of the row's pages chunk c holds
        return jnp.where(c < n_wide, wide,
                         jnp.minimum(npages - first(c), tail))

    def copies(c, do):
        """``do`` (start or wait) every copy of the pages chunk c holds."""
        slot, base, n = jax.lax.rem(c, 2), first(c), pages(c)

        def one(i):
            for src, buf, _ in streams:
                do(pltpu.make_async_copy(
                    src(bt_ref[b, base + i]), buf.at[slot, i], sem.at[slot]
                ))

        def run(r, _):
            for j in range(_RUN):
                one(r * _RUN + j)

        jax.lax.fori_loop(0, n // _RUN, run, None)
        jax.lax.fori_loop(n - n % _RUN, n, lambda i, _: one(i), None)
        return slot, n

    def start(c):
        slot, n = copies(c, lambda cp: cp.start())

        def clear(i, _):
            for _, buf, is_value in streams:
                if is_value:
                    buf[slot, i] = jnp.zeros(buf.shape[2:], buf.dtype)

        # (a wide chunk is whole: nothing to clear)
        jax.lax.fori_loop(n, jnp.where(c < n_wide, n, tail), clear, None)

    def wait(c, width: int):
        if width == tail:
            copies(c, lambda cp: cp.wait())
            return
        # a wide chunk: the slot's bytes are the sum of its pages', so one
        # wait a stream awaits them all
        slot = jax.lax.rem(c, 2)
        for _, buf, _ in streams:
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    def chunks(lo, hi, width: int, carry):
        def body(c, carry):
            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                start(c + 1)

            wait(c, width)
            return attend(carry, jax.lax.rem(c, 2), first(c), width)

        return jax.lax.fori_loop(lo, hi, body, carry)

    start(0)
    if wide > tail:
        carry = chunks(0, n_wide, wide, carry)
    return chunks(n_wide, n_chunks, tail, carry)


def _decode_kernel(
    rows_ref,  # scalar prefetch: the rows the grid walks [B]
    bt_ref,    # scalar prefetch: block tables [B, W] (SMEM)
    ctx_ref,   # scalar prefetch: context lens [B]
    li_ref,    # scalar prefetch: layer index [1]
    win_ref,   # scalar prefetch: sliding window [1] (>= ctx disables)
    q_ref,     # [1, KVH, G, D] VMEM block
    *rest,     # k_hbm a part of the keys' lanes, v_hbm: [L, N, page * KVH,
               # lanes] in HBM (ANY), a page's (token, head) rows;
               # ([sinks_ref [1, rows] when has_sinks], o_ref, scratch...)
    scale: float,
    block_size: int,
    wide_pages: int,
    tail_pages: int,
    softcap: float,
    has_sinks: bool = False,
    k_parts: int = 1,
):
    """One grid step = one live batch row (``rows_ref`` names it);
    ``_walk`` copies and folds only its LIVE pages: wide chunks sized by
    their bytes (``chunk_pages``) while the row has that many pages
    left, chunks of ``tail_pages`` after, so a short row pays for no
    wider product than it has keys.

    Compute is ONE pair of MXU dots per chunk for ALL kv heads: a page
    arrives as its [page * KVH, D] rows (the wrapper's view of the cache:
    the same bytes, and a chunk is [chunk_t * KVH, D] with no relayout of
    four-row or two-row tiles), every q row scores against every (token,
    head) column; a head-match mask (+ the validity mask) drives
    cross-head scores to MASK_VALUE, so their softmax weight is exactly 0
    and the single probs @ V dot sums only same-head contributions. This
    trades KVH× redundant MXU flops (trivial at decode shapes) for not
    issuing KVH tiny [G, chunk] dots per chunk — decode attention is DMA
    bound; op-issue overhead was the previous kernel's limiter.

    With a sliding window the walk starts at the first page holding a
    visible key (the decode query sits at ctx-1, so only positions in
    [ctx - window, ctx) matter): windowed decode costs O(window) DMA,
    not O(context) — the gathered XLA path always pays full width.

    ``has_sinks`` (GPT-OSS): a learned per-row logit joins the softmax
    as a virtual key with no value — one exp(sink - m) term added to
    the denominator at finalize.
    """
    k_hbms, (v_hbm, *rest) = rest[:k_parts], rest[k_parts:]
    if has_sinks:
        sinks_ref, o_ref, *k_bufs, v_buf, sem = rest
    else:
        o_ref, *k_bufs, v_buf, sem = rest
    b = rows_ref[pl.program_id(0)]
    ctx = ctx_ref[b]
    li = li_ref[0]
    npages = pl.cdiv(ctx, block_size)          # live pages (ctx >= 1 in decode)
    # first key position the decode query (at ctx-1) can see
    win_start = jnp.maximum(ctx - win_ref[0], 0)

    _, kvh, g, d = q_ref.shape
    dv = v_buf.shape[-1]    # the values' lanes: a side of the cache has its own
    rows = kvh * g
    q = q_ref[0].reshape(rows, d)  # [KVH*G, D], rows ordered (head, group)

    def columns(pages):
        # column j of a flattened chunk is (token j // KVH, head j % KVH);
        # row r serves head r // G — both masks are plain iota arithmetic
        shape = (rows, pages * block_size * kvh)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row_head = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // g
        return col % kvh == row_head, col // kvh

    # loop-invariant, one pair a chunk width
    masks = {pages: columns(pages) for pages in {wide_pages, tail_pages}}

    def attend(carry, slot, first_page, pages):
        head_match, col_tok = masks[pages]
        cols = pages * block_size * kvh
        # upcast from the cache storage dtype (fp8 serving stores e4m3;
        # the dots and the p·V product must run at the compute dtype)
        ks = [buf[slot, :pages].reshape(cols, buf.shape[-1]).astype(q.dtype)
              for buf in k_bufs]
        v = v_buf[slot, :pages].reshape(cols, dv).astype(q.dtype)

        # decode causality: the query is the newest token, so every key
        # with position < ctx is visible — a pure validity mask (plus the
        # window's lower bound; win_start == 0 when the window is off).
        key_pos = first_page * block_size + col_tok
        mask = head_match & (key_pos < ctx) & (key_pos >= win_start)

        # (keys kept as several stacks of lanes: a product a part, each
        # over its own lanes of the query)
        lanes = [0]
        for k in ks:
            lanes.append(lanes[-1] + k.shape[-1])
        s_log = functools.reduce(jnp.add, [
            jax.lax.dot_general(
                q if k_parts == 1 else q[:, lo:hi], k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) for k, lo, hi in zip(ks, lanes, lanes[1:])
        ]) * scale                                        # [rows, cols]
        if softcap:
            s_log = softcap * jnp.tanh(s_log / softcap)
        return _fold(carry, jnp.where(mask, s_log, MASK_VALUE), v)

    # m/l ride as [rows, 128] lane-broadcast carries (the layout Mosaic
    # handles without sub-lane-width relayouts; same trick as the scratch
    # accumulators in pallas_attention.py)
    m0 = jnp.full((rows, 128), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((rows, 128), jnp.float32)
    acc0 = jnp.zeros((rows, dv), jnp.float32)
    m, l, acc = _walk(
        b, bt_ref, sem,
        [(lambda n, k_hbm=k_hbm: k_hbm.at[li, n], k_buf, False)
         for k_hbm, k_buf in zip(k_hbms, k_bufs)]
        + [(lambda n: v_hbm.at[li, n], v_buf, True)],
        first_page=win_start // block_size, npages=npages,
        wide=wide_pages, tail=tail_pages, attend=attend,
        carry=(m0, l0, acc0),
    )
    l1 = l[:, 0:1]
    if has_sinks:
        # the sink is a virtual key with no value: denominator only.
        # Any shared shift works for the exp terms (it cancels), so the
        # keys-only running max m serves without a combined-max pass.
        l1 = l1 + jnp.exp(
            sinks_ref[0][:, None].astype(jnp.float32) - m[:, 0:1]
        )
    l1 = jnp.where(l1 == 0.0, 1.0, l1)
    o_ref[0] = (acc / l1).astype(o_ref.dtype).reshape(kvh, g, dv)


def _mla_decode_kernel(
    rows_ref,  # scalar prefetch: the rows the grid walks [B]
    bt_ref,    # scalar prefetch: block tables [B, W]
    ctx_ref,   # scalar prefetch: context lens [B]
    li_ref,    # scalar prefetch: layer index [1]
    ql_ref,    # [1, H, R]   latent-absorbed queries
    qr_ref,    # [1, H, RD]  decoupled rope queries
    c_hbm,     # [L, N, 1, page, R]  compressed latent cache (ANY)
    kr_hbm,    # [L, N, 1, page, RD] shared rope-key cache (ANY)
    o_ref,     # [1, H, R]
    c_buf,     # VMEM [2, P, page, R]
    kr_buf,    # VMEM [2, P, page, RD]
    sem,       # DMA semaphores [2]
    *,
    scale: float,
    block_size: int,
    wide_pages: int,
    tail_pages: int,
    sliding_window: Optional[int] = None,
):
    """MLA decode: score = q_lat·c + q_rope·k_rope, output = softmax·c.

    The same walk as _decode_kernel (``_walk``: wide chunks, then the
    tail's, only live pages copied), but the two key components stream
    together and the value IS the latent (attention weights re-read c) —
    so each page moves R+RD bytes once, not twice. A page is [page, R]:
    whole (16, 128) tiles of the HBM layout, which is why the latent
    cache keeps its one head in front of the page
    (models/deepseek.init_kv_cache) — Mosaic cannot slice a page out of
    [page, 1, R], whose single head XLA pads to a sublane pair.

    ``sliding_window`` (a latent window layer, models/dots3.py): the
    query at ctx - 1 sees positions [ctx - window, ctx) alone, and the
    walk starts at the first page that holds one, as ``_decode_kernel``'s.
    """
    b = rows_ref[pl.program_id(0)]
    ctx = ctx_ref[b]
    li = li_ref[0]
    # first key position the decode query (at ctx-1) can see
    win_start = (0 if sliding_window is None
                 else jnp.maximum(ctx - sliding_window, 0))

    _, h, r = ql_ref.shape
    rd = qr_ref.shape[-1]
    ql = ql_ref[0]  # [H, R]
    qr = qr_ref[0]  # [H, RD]

    def attend(carry, slot, first_page, pages):
        chunk_t = pages * block_size
        # upcast from the cache storage dtype (fp8 serving stores e4m3;
        # no-op for bf16) — the score dots need a uniform compute dtype
        c = c_buf[slot, :pages].reshape(chunk_t, r).astype(ql.dtype)
        kr = kr_buf[slot, :pages].reshape(chunk_t, rd).astype(ql.dtype)

        key_pos = first_page * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_t), 1
        )
        valid = key_pos < ctx
        if sliding_window is not None:
            valid = valid & (key_pos >= win_start)

        s_log = (
            jax.lax.dot_general(
                ql, c, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + jax.lax.dot_general(
                qr, kr, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * scale                                        # [H, chunk_t]
        # the value IS the latent: [H, R] out
        return _fold(carry, jnp.where(valid, s_log, MASK_VALUE), c)

    # [H, 128] lane-broadcast running stats (see _decode_kernel)
    m0 = jnp.full((h, 128), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((h, 128), jnp.float32)
    acc0 = jnp.zeros((h, r), jnp.float32)
    m, l, acc = _walk(
        b, bt_ref, sem,
        [(lambda n: c_hbm.at[li, n, 0], c_buf, True),
         (lambda n: kr_hbm.at[li, n, 0], kr_buf, False)],
        first_page=win_start // block_size, npages=pl.cdiv(ctx, block_size),
        wide=wide_pages, tail=tail_pages, attend=attend,
        carry=(m0, l0, acc0),
    )
    l1 = l[:, 0:1]
    l1 = jnp.where(l1 == 0.0, 1.0, l1)
    o_ref[0] = (acc / l1).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_chunk", "interpret",
                              "sliding_window")
)
def mla_paged_decode_attention(
    q_lat: jax.Array,        # [B, 1, H, R] latent-absorbed queries
    q_rope: jax.Array,       # [B, 1, H, RD] post-RoPE decoupled queries
    c_cache: jax.Array,      # [L, N, 1, page, R] (or 4-D single layer)
    kr_cache: jax.Array,     # [L, N, 1, page, RD]
    block_tables: jax.Array, # [B, W] int32
    context_lens: jax.Array, # [B] int32
    layer_idx: Optional[jax.Array] = None,
    scale: float = 1.0,
    pages_per_chunk: Optional[int] = None,  # tests pin it; None: chunk_pages
    interpret: bool = False,
    live_rows: Optional[LiveRows] = None,  # the rows that hold a token
    sliding_window: Optional[int] = None,  # the last so many keys alone
) -> jax.Array:
    """DeepSeek MLA single-token attention over the compressed cache.

    Returns the latent output [B, 1, H, R] (caller applies W_uv). Same
    role as models/deepseek.mla_paged_attention's decode case without the
    per-layer gather: the layer is indexed inside HBM. The tail's 16
    pages a chunk measured best of 4 / 8 / 16 / 32 at Moonlight's widths
    on the v5e when every chunk was that size (PERF.md, PR 26; the gather
    route 3.0 / 9.0 / 17.7 ms at a table of 64 / 128 / 256 blocks); a
    row with 64 pages left walks them as one chunk (``chunk_pages``;
    PERF.md §5, PR 42). ``live_rows``: as ``paged_decode_attention``.
    """
    b, s, h, r = q_lat.shape
    assert s == 1, "decode kernel is specialized to one query token"
    rd = q_rope.shape[-1]
    if c_cache.ndim == 4:
        c_cache, kr_cache = c_cache[None], kr_cache[None]
    block_size = c_cache.shape[3]
    li = (
        jnp.zeros((1,), jnp.int32)
        if layer_idx is None
        else jnp.asarray(layer_idx, jnp.int32).reshape(1)
    )
    wide, tail = _chunks(
        "mla_paged_decode_attention", pages_per_chunk, 16,
        block_size * (r + rd) * c_cache.dtype.itemsize, h * block_size * 4,
        block_tables.shape[1])
    rows, n = _walked_rows(b, live_rows)

    def by_row(i, rows_ref, *_):
        return rows_ref[i], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, r), by_row),
            pl.BlockSpec((1, h, rd), by_row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, r), by_row),
        scratch_shapes=[
            pltpu.VMEM((2, wide, block_size, r), c_cache.dtype),
            pltpu.VMEM((2, wide, block_size, rd), kr_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _mla_decode_kernel,
            scale=scale,
            block_size=block_size,
            wide_pages=wide,
            tail_pages=tail,
            sliding_window=sliding_window,
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct((b, h, r), q_lat.dtype, q_lat, c_cache),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        rows,
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        li,
        q_lat.reshape(b, h, r),
        q_rope.reshape(b, h, rd),
        c_cache,
        kr_cache,
    )
    return _zero_unwalked(out.reshape(b, 1, h, r), live_rows)


def _verify_kernel(
    rows_ref,  # scalar prefetch: the rows the grid walks [B]
    bt_ref,    # scalar prefetch: block tables [B, W] (SMEM)
    ctx_ref,   # scalar prefetch: context lens [B] (incl. all S new slots)
    base_ref,  # scalar prefetch: base query position [B] (q[:, 0]'s pos)
    li_ref,    # scalar prefetch: layer index [1]
    win_ref,   # scalar prefetch: sliding window [1] (>= ctx disables)
    q_ref,     # [1, KVH / per, per * S * G, D] VMEM block: the (head,
               # s, g) rows of the per kv heads of a product
    k_hbm,     # [L, N, page * KVH, D] in HBM (ANY): a page's (token, head) rows
    v_hbm,
    *rest,     # ([sinks_ref [KVH / per, per * S * G] when has_sinks],
               # o_ref, scratch...)
    scale: float,
    block_size: int,
    wide_pages: int,
    tail_pages: int,
    softcap: float,
    s_q: int,
    has_sinks: bool = False,
    block_len: int = 1,
):
    """Multi-token verify attention: S query tokens per row over the
    SAME single page walk — the speculative propose-verify step's
    attention reads each KV page once instead of the flash-prefill
    kernel's per-query-block passes over the table capacity.

    The walk is ``_decode_kernel``'s (``_walk``: wide chunks sized by
    their bytes, then the tail's, only the row's own pages copied). A
    chunk is folded a product at a time, and a product is a kv head's
    own wherever its keys can be read apart from the other heads'
    (``keys``): its S * G query rows against its pages * page keys, so
    no score is computed against another head's keys and none needs a
    head-match mask (at S = 8 the scores of every head against every
    head are KVH times the products, the bytes and the ``exp`` of a
    chunk, and the MXU takes a chunk's keys in about the time HBM gives
    them). Where it cannot (fp8's four heads a word, a head count the
    words do not divide) the heads it holds together are one product
    under that mask.

    Query s sits at absolute position base + s (the flash kernel's
    affine contract — base rides as its own prefetch operand, so a
    right-padded chunk behaves exactly like flash: pad rows score
    against the bounded valid range and the caller discards them), and
    key j is visible iff j <= base + s AND j < ctx (and inside the
    sliding window). With a static ``block_len`` B > 1 the first term is
    j < ((base + s) // B + 1) · B: causal over blocks of B, full inside
    one (a block pass of models/sdar.py: every query of the block sees
    all B of its keys).

    ``has_sinks`` (GPT-OSS): the per-head sink logit joins EVERY query
    position's softmax as a denominator-only virtual key.
    """
    if has_sinks:
        sinks_ref, o_ref, k_buf, v_buf, sem = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
    b = rows_ref[pl.program_id(0)]
    ctx = ctx_ref[b]
    base = base_ref[b]
    li = li_ref[0]
    # the earliest key ANY query can see (query 0's window lower bound)
    win_start = jnp.maximum(base + 1 - win_ref[0], 0)

    _, products, rows, _ = q_ref.shape
    kvh = k_buf.shape[2] // block_size
    per = kvh // products          # kv heads a product
    group = rows // (per * s_q)    # query heads a kv head

    # each row's query position (rows ordered (head, s, g), affine from
    # the base operand) and the keys it sees: [q_lo, q_hi)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    q_pos = base + row // group % s_q
    q_hi = jnp.minimum(
        q_pos + 1 if block_len == 1
        else (q_pos // block_len + 1) * block_len, ctx)
    q_lo = q_pos - win_ref[0] + 1

    # a 32-bit word of the packed cache holds a token's rows of
    # word_heads neighbouring heads; the word rows of a page that hold
    # the same heads are every words-th, and a word of two 16-bit heads
    # is taken apart (``keys``); none of it where every head is in the
    # one product
    word_heads = 4 // k_buf.dtype.itemsize
    words = max(kvh // word_heads, 1)
    halves = word_heads == 2 and per < kvh

    def tokens(pages):
        """[rows, cols] the token a column holds in a chunk of ``pages``,
        or none a row will ever see (``OTHER_HEAD``) where the column is
        another head's than the row's: column j of a product's rows is
        token j // per of its head j % per, and of a head taken out of
        its words, token j // 2 of the chunk's first half or, at odd j,
        of its second."""
        shape = (rows, pages * block_size * per)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if halves:
            return col // 2 + col % 2 * (pages * block_size // 2)
        if per == 1:
            return col
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // (group * s_q)
        return jnp.where(col % per == head, col // per, OTHER_HEAD)

    # loop-invariant, one a chunk width
    col_tok = {pages: tokens(pages) for pages in {wide_pages, tail_pages}}

    def keys(buf, slot, pages, w, dtype):
        """A chunk's rows of the heads that share word row w of a token,
        one [pages * page * per, D] array a product. Row t * KVH + h of
        a page is token t of head h and a 32-bit word packs ``word_heads``
        neighbouring rows, so those heads' rows are every ``words``-th
        word row: a strided load, which Mosaic has for 32 bits alone.
        A word of two 16-bit heads comes apart in three bit operations a
        head: the low halves of the chunk's first half beside those of
        its second are the packed rows of the even head (tokens in the
        order ``tokens`` names), the high halves the odd head's. Any
        other word's heads stay together as the rows they are."""
        d = buf.shape[-1]       # a side's own lanes
        if per == kvh:
            return [buf[slot, :pages].reshape(
                pages * block_size * kvh, d).astype(dtype)]
        packed = buf.bitcast(jnp.uint32)[
            slot, :pages, pl.ds(w, block_size, stride=words)
        ].reshape(pages * block_size, d)
        if not halves:
            return [pltpu.bitcast(packed, buf.dtype).astype(dtype)]
        a, b = jnp.split(packed, 2)
        high = jnp.uint32(0xFFFF0000)
        return [pltpu.bitcast(head, buf.dtype).astype(dtype)
                for head in ((a & ~high) | (b << 16), (a >> 16) | (b & high))]

    def attend(carry, slot, first_page, pages):
        # two compares a score, the same for every product: the chunk's
        # first key comes off the rows' bounds, not onto the columns
        first_key = first_page * block_size
        mask = ((col_tok[pages] < q_hi - first_key)
                & (col_tok[pages] >= q_lo - first_key))

        def fold(carry, q, k, v):
            s_log = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [rows, cols]
            if softcap:
                s_log = softcap * jnp.tanh(s_log / softcap)
            return _fold(carry, jnp.where(mask, s_log, MASK_VALUE), v)

        # upcast from the cache storage dtype as _decode_kernel does
        dtype = q_ref.dtype
        chunk = [(k, v) for w in range(words)
                 for k, v in zip(keys(k_buf, slot, pages, w, dtype),
                                 keys(v_buf, slot, pages, w, dtype))]
        return tuple(fold(carry[i], q_ref[0, i], k, v)
                     for i, (k, v) in enumerate(chunk))

    m0 = jnp.full((rows, 128), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((rows, 128), jnp.float32)
    acc0 = jnp.zeros((rows, v_buf.shape[-1]), jnp.float32)
    heads = _walk(
        b, bt_ref, sem,
        [(lambda n: k_hbm.at[li, n], k_buf, False),
         (lambda n: v_hbm.at[li, n], v_buf, True)],
        first_page=win_start // block_size,
        npages=pl.cdiv(ctx, block_size),
        wide=wide_pages, tail=tail_pages, attend=attend,
        carry=((m0, l0, acc0),) * products,
    )
    for j, (m, l, acc) in enumerate(heads):
        l1 = l[:, 0:1]
        if has_sinks:
            # denominator-only virtual key (see _decode_kernel — any
            # shared shift cancels, so the keys-only running max m serves
            # without a combined-max pass)
            l1 = l1 + jnp.exp(
                sinks_ref[j][:, None].astype(jnp.float32) - m[:, 0:1]
            )
        l1 = jnp.where(l1 == 0.0, 1.0, l1)
        o_ref[0, j] = (acc / l1).astype(o_ref.dtype)


# largest tail the verify kernel serves: beyond it the flash-prefill
# kernel's blocked pipeline wins anyway (spec rounds are K+1 <= 17)
VERIFY_MAX_S = 32


@functools.partial(
    jax.jit,
    static_argnames=("scale", "pages_per_chunk", "interpret", "softcap",
                     "block_len"),
)
def paged_verify_attention(
    q: jax.Array,            # [B, S, H, D] (post-RoPE), S small
    k_cache: jax.Array,      # [L, N, page, KVH, D] stacked (or 4-D)
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, W] int32
    base_pos: jax.Array,     # [B] int32 — absolute position of q[:, 0]
    context_lens: jax.Array, # [B] int32 (valid keys; may be < base + S)
    layer_idx: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    pages_per_chunk: Optional[int] = None,  # tests pin it; None: chunk_pages
    interpret: bool = False,
    softcap: float = 0.0,
    window=None,
    sinks=None,              # [H] per-head sink logits (GPT-OSS); None = off
    block_len: int = 1,      # static; > 1: causal over blocks, full inside
    live_rows: Optional[LiveRows] = None,  # the rows that hold a token
) -> jax.Array:
    """S-token verify attention over the paged cache; returns
    [B, S, H, Dv] (``v_cache``'s lanes). The flash kernel's affine contract: query s of row b
    sits at ``base_pos[b] + s``; rows past ``context_lens`` (a padded
    chunk) produce garbage the caller discards. A row is walked as
    ``paged_decode_attention`` walks it (``chunk_pages`` over the page's
    bytes and the scores of a product's rows), and ``live_rows`` means
    what it means there. The tail's chunk is sixteen pages: with S
    positions a row a chunk's fixed costs weigh more than in decode, and
    at SDAR's shape (S = 8, 32 KB a page) rows of 600 to 2800 keys read
    12 % faster with 16 than with 8, and no faster with 32 (PERF.md §5,
    PR 49)."""
    b, s, h, d = q.shape
    assert s <= VERIFY_MAX_S, "verify kernel serves small S tails"
    if k_cache.ndim == 4:
        k_cache, v_cache = k_cache[None], v_cache[None]
    _, _, block_size, kvh, _ = k_cache.shape
    # a page as its (token, head) rows, the values' lanes the v side's
    # own: see paged_decode_attention
    dv = v_cache.shape[-1]
    k_cache = k_cache.reshape(k_cache.shape[:2] + (block_size * kvh, d))
    v_cache = v_cache.reshape(v_cache.shape[:2] + (block_size * kvh, dv))
    g = h // kvh
    # a chunk is folded a product at a time: a kv head's own rows where
    # the kernel can read them apart from the others' (32-bit rows; 16-bit
    # rows two to a 32-bit word of the packed cache, which comes apart),
    # else the heads of a word together (four of fp8), or every head where
    # the words do not divide them (_verify_kernel.keys)
    word_heads = 4 // k_cache.dtype.itemsize
    per = (kvh if kvh % word_heads
           else 1 if word_heads == 2 else word_heads)  # kv heads a product
    products = kvh // per
    if scale is None:
        scale = d ** -0.5
    li = (
        jnp.zeros((1,), jnp.int32)
        if layer_idx is None
        else jnp.asarray(layer_idx, jnp.int32).reshape(1)
    )
    win = (
        jnp.full((1,), jnp.int32(2**30))
        if window is None
        else jnp.asarray(window, jnp.int32).reshape(1)
    )
    wide, tail = _chunks(
        "paged_verify_attention", pages_per_chunk, 16,
        block_size * kvh * (d + dv) * k_cache.dtype.itemsize,
        per * s * g * block_size * per * 4, block_tables.shape[1])
    # a product's query rows together, ordered (head, s, g)
    by_product = (b, products, per * s * g, d)
    qs = q.reshape(b, s, products, per, g, d).transpose(
        0, 2, 3, 1, 4, 5).reshape(by_product)
    has_sinks = sinks is not None

    rows, n = _walked_rows(b, live_rows)

    def by_row(i, rows_ref, *_):
        return rows_ref[i], 0, 0, 0

    in_specs = [
        pl.BlockSpec((1,) + by_product[1:], by_row),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if has_sinks:
        # replicated to every grid step: a head's logit at each of its
        # (s, g) rows
        in_specs.append(pl.BlockSpec(by_product[1:3], lambda i, *_: (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1,) + by_product[1:3] + (dv,), by_row),
        scratch_shapes=[
            pltpu.VMEM((2, wide) + k_cache.shape[2:], k_cache.dtype),
            pltpu.VMEM((2, wide) + v_cache.shape[2:], v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    operands = [
        rows,
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        base_pos.astype(jnp.int32),
        li,
        win,
        qs,
        k_cache,
        v_cache,
    ]
    if has_sinks:
        operands.append(jnp.broadcast_to(
            jnp.asarray(sinks, jnp.float32).reshape(kvh, 1, g), (kvh, s, g)
        ).reshape(by_product[1:3]))

    out = pl.pallas_call(
        functools.partial(
            _verify_kernel,
            scale=scale,
            block_size=block_size,
            wide_pages=wide,
            tail_pages=tail,
            softcap=softcap,
            s_q=s,
            has_sinks=has_sinks,
            **({} if block_len == 1 else {"block_len": block_len}),
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct(by_product[:3] + (dv,), q.dtype, q, k_cache),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*operands)
    out = out.reshape(b, products, per, s, g, dv).transpose(
        0, 3, 1, 2, 4, 5).reshape(b, s, h, dv)
    return _zero_unwalked(out, live_rows)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "pages_per_chunk", "interpret", "softcap",
                     "one_head"),
)
def paged_decode_attention(
    q: jax.Array,            # [B, 1, H, D] (post-RoPE)
    k_cache: jax.Array,      # [L, N, page, KVH, D] stacked (or [N, page, KVH, D])
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, W] int32
    context_lens: jax.Array, # [B] int32
    layer_idx: Optional[jax.Array] = None,  # scalar int32 into L (default 0)
    scale: Optional[float] = None,
    pages_per_chunk: Optional[int] = None,  # tests pin it; None: chunk_pages
    interpret: bool = False,
    softcap: float = 0.0,    # Gemma-2: logits ← cap·tanh(logits/cap)
    window=None,             # sliding window (int or traced scalar); None = off
    sinks=None,              # [H] per-head sink logits (GPT-OSS); None = off
    one_head: bool = False,  # the caches are [L, N, page, D]: a kv head a page
    live_rows: Optional[LiveRows] = None,  # the rows that hold a token
) -> jax.Array:
    """Single-token paged attention; returns [B, 1, H, Dv], ``Dv`` the
    lanes of ``v_cache`` (the keys' ``D`` wherever the two sides are one
    width).

    ``window`` may be traced (Gemma-2 alternates windowed/full layers
    inside its layer scan), so it rides as a scalar-prefetch operand; the
    kernel starts its page walk at the window's first live chunk.

    ``live_rows`` (ops/live_rows.py: a trunk makes it once a step and
    hands it to every layer): the grid walks those rows alone and every
    other row comes back zero. Without it every row is walked.

    How a row is walked is derived here and nowhere else: a wide chunk's
    pages from the bytes of a page (``chunk_pages``), eight pages a chunk
    of the tail; a caller has nothing to set."""
    b, s, h, d = q.shape
    assert s == 1, "decode kernel is specialized to one query token"
    # the keys may be kept as several stacks, each a part of their lanes
    # (ops/attention.split_lanes says why); one stack is every family's
    # but that one
    k_parts = tuple(k_cache) if isinstance(k_cache, (tuple, list)) else None
    if k_parts is not None:
        k_cache = k_parts[0]
    if one_head:
        # [L, N, page, D]: a page holds one kv head and has no head axis
        # (a unit axis there is a slice Mosaic's tiling refuses); every
        # query head of a row attends to it
        _, _, block_size, _ = k_cache.shape
        kvh = 1
    else:
        if k_cache.ndim == 4:
            assert k_parts is None, "keys in parts are stacked by layer"
            k_cache, v_cache = k_cache[None], v_cache[None]
        _, _, block_size, kvh, _ = k_cache.shape
    # a page as its (token, head) rows: the same bytes (XLA's tiles of
    # [KVH, D] for KVH of 2 or 4 laid end to end are its tiles of eight
    # rows, so the reshape is a bitcast), and a chunk in VMEM is
    # [chunk_t * KVH, D] as the dots want it, where a buffer of
    # [.., KVH, D] tiles had to be repacked row group by row group.
    # The values' width is the v side's own (a family whose values are
    # narrower than its keys: models/mimo_v2.py), and so is the output's
    dv = v_cache.shape[-1]
    if k_parts is None:
        k_caches = [k_cache.reshape(k_cache.shape[:2] + (block_size * kvh, d))]
    else:
        k_caches = [k.reshape(k.shape[:2] + (block_size * kvh, k.shape[-1]))
                    for k in k_parts]
        assert sum(k.shape[-1] for k in k_caches) == d, "q spans the parts"
    k_cache = k_caches[0]
    v_cache = v_cache.reshape(v_cache.shape[:2] + (block_size * kvh, dv))
    g = h // kvh
    if scale is None:
        scale = d ** -0.5
    li = (
        jnp.zeros((1,), jnp.int32)
        if layer_idx is None
        else jnp.asarray(layer_idx, jnp.int32).reshape(1)
    )
    win = (
        jnp.full((1,), jnp.int32(2**30))
        if window is None
        else jnp.asarray(window, jnp.int32).reshape(1)
    )
    wide, tail = _chunks(
        "paged_decode_attention", pages_per_chunk, 8,
        block_size * kvh * (d + dv) * k_cache.dtype.itemsize,
        h * block_size * kvh * 4, block_tables.shape[1])

    qs = q.reshape(b, kvh, g, d)
    has_sinks = sinks is not None

    rows, n = _walked_rows(b, live_rows)

    def by_row(i, rows_ref, *_):
        return rows_ref[i], 0, 0, 0

    in_specs = [
        pl.BlockSpec((1, kvh, g, d), by_row),
        *(pl.BlockSpec(memory_space=pl.ANY) for _ in k_caches),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if has_sinks:
        # [1, rows] replicated to every grid step; row order (kv, g)
        # matches the kernel's q flattening
        in_specs.append(pl.BlockSpec((1, kvh * g), lambda i, *_: (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, dv), by_row),
        scratch_shapes=[
            *(pltpu.VMEM((2, wide) + k.shape[2:], k.dtype) for k in k_caches),
            pltpu.VMEM((2, wide) + v_cache.shape[2:], v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    operands = [
        rows,
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        li,
        win,
        qs,
        *k_caches,
        v_cache,
    ]
    if has_sinks:
        operands.append(
            jnp.asarray(sinks, jnp.float32).reshape(1, kvh * g)
        )

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            scale=scale,
            block_size=block_size,
            wide_pages=wide,
            tail_pages=tail,
            softcap=softcap,
            has_sinks=has_sinks,
            **({} if k_parts is None else {"k_parts": len(k_caches)}),
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct((b, kvh, g, dv), q.dtype, q, k_cache),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*operands)
    return _zero_unwalked(out.reshape(b, 1, h, dv), live_rows)
