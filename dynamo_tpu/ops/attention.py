"""Attention over the paged KV cache.

Unified design: new K/V are always scattered into the cache first, then
queries attend over gathered cache blocks — the same code path serves
bucketed prefill (S>1, narrow KV width) and single-token decode (S=1, full
width). The XLA path below is the reference implementation; the Pallas
flash/paged kernel (ops/pallas_attention.py) replaces it on TPU where the
gather would otherwise materialize B×W×bs keys in HBM.

Replaces the role of the reference's GPU engines' paged attention (the
reference delegated to vLLM; SURVEY.md §7 "hard parts" #1).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..telemetry.registry import Counter
from .pallas_attention import paged_flash_attention
from .pallas_decode import (
    VERIFY_MAX_S,
    paged_decode_attention,
    paged_verify_attention,
)

LANE = 128  # TPU vector lane width — HBM layouts tile the minor dim to this

# ---------- route observability ----------
#
# Which kernel served each program: the dispatch decision below is made
# at TRACE time (it is static per compiled specialization), so the
# counter increments once per (program, shape-bucket) compile — the
# fleet-level signal is which route each program's traces took, not a
# per-step rate. The engine registers this singleton into the runner's
# compile registry (rendered in the scheduler's scrape) and installs
# ``route_program`` as the CompileTracker's dispatch hook so records
# carry the program label.
ATTENTION_ROUTE_COUNTER = Counter(
    "dynamo_engine_attention_route_total",
    "Attention kernel route chosen at trace time per compiled program "
    "specialization, labelled program= (the engine program tracing) and "
    "route=xla|decode|verify|flash|sp_ring_kernel|sp_ring_gather",
)

_route_program = "unknown"
_row_list_traced = False
_table_width_traced = False


@contextlib.contextmanager
def route_program(name: str):
    """Label route records with the engine program being dispatched
    (installed as CompileTracker.dispatch_cm — active only while a
    tracked dispatch, and therefore its trace, is on the stack)."""
    global _route_program, _row_list_traced, _table_width_traced
    prev = _route_program
    _route_program = name
    _row_list_traced = False
    _table_width_traced = False
    try:
        yield
    finally:
        _route_program = prev


def record_route(route: str) -> None:
    """Stamp one route decision (called from the dispatch seams here
    and in parallel/sequence.py — trace-time Python, never traced). The
    ``xla`` route gathers every page the table has room for, so it also
    stamps ``record_table_width``."""
    ATTENTION_ROUTE_COUNTER.inc(program=_route_program, route=route)
    if route == "xla":
        record_table_width()


def record_row_list() -> None:
    """Stamp that the program being traced took a decode kernel with a
    list of live rows: its pad rows are no grid steps. Trace-time, like
    ``record_route``; the runner reads it off the dispatch that traced
    (``row_list_traced``) and the scheduler counts skipped rows only for
    such a program."""
    global _row_list_traced
    _row_list_traced = True


def row_list_traced() -> bool:
    """Whether a trace since the innermost ``route_program`` was entered
    called ``record_row_list``."""
    return _row_list_traced


def record_table_width() -> None:
    """Stamp that an operation of the program being traced does work in
    proportion to the block table's width ``W``, whatever the rows hold:
    a gather of ``[B, W]`` pages, or block selection's scores and sort
    over ``W`` entries (ops/sparse_attention.decode_attention). The
    kernels do not: they walk ``ceil(context_len / page)`` pages a live
    row and never read a pad entry. Trace-time, like ``record_route``;
    the runner reads it off the dispatch that traced
    (``table_width_traced``) and compiles a decode program at the
    narrower widths of ``EngineConfig.kv_width_buckets`` only where it
    was stamped."""
    global _table_width_traced
    _table_width_traced = True


def table_width_traced() -> bool:
    """Whether a trace since the innermost ``route_program`` was entered
    called ``record_table_width``."""
    return _table_width_traced


def lane_pad(d: int) -> int:
    """Smallest multiple of LANE >= d.

    KV caches are allocated with their minor (head/latent) dim padded to
    this: Mosaic requires DMA slices of HBM refs to be lane-aligned, and
    XLA pads the tiled HBM layout to 128 lanes anyway — so a head_dim-64
    cache already occupies 128 lanes physically; making the padding
    explicit costs no memory and unlocks the manual-DMA decode kernels
    (ops/pallas_decode.py). Pad lanes are kept zero (zero-padded writes)
    so padded q · padded k contributes nothing to attention scores.
    """
    return -(-d // LANE) * LANE


def pad_minor(x: jax.Array, d: int) -> jax.Array:
    """Zero-pad the trailing dim of x up to d (no-op if already d)."""
    if x.shape[-1] == d:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, d - x.shape[-1])]
    return jnp.pad(x, pad)


def split_lanes(x: jax.Array):
    """``x [..., D]`` as ``ceil(D / LANE)`` parts of ``LANE`` lanes each,
    the last zero-padded: how keys wider than a lane tile are kept where
    a page holds fewer kv heads than a tile has rows (4 of 256 lanes,
    models/mimo_v2.py). The decode kernels read a page as its (token,
    head) rows, ``[page * KVH, lanes]``, which is the same bytes as
    ``[page, KVH, lanes]`` only while a row is one lane tile wide: XLA
    tiles ``[4, 256]`` as two ``(4, 128)`` tiles a token, the rows' view
    as ``(8, 128)`` tiles of two tokens, and the reshape between them
    copied the whole stack every layer of every step (2.4 GB). A stack a
    lane tile keeps every reshape a bitcast; a score is the sum of the
    parts' products."""
    d = x.shape[-1]
    x = pad_minor(x, lane_pad(d))
    return tuple(x[..., lo:lo + LANE] for lo in range(0, lane_pad(d), LANE))


def scatter_stacked(stacks, news, slot_mapping: jax.Array,
                    layer_idx: jax.Array):
    """``scatter_kv_stacked`` over any number of stacks ``[L, N, block,
    heads, lanes]`` (each with its own heads and lanes) written at the
    same slots: ``news[i] [B, S, heads, <= lanes]`` into ``stacks[i]``."""
    l, n_blocks, block_size = stacks[0].shape[:3]
    idx = slot_mapping.reshape(-1)
    per_layer = n_blocks * block_size
    flat_idx = jnp.where((idx < 0) | (idx >= per_layer), l * per_layer,
                         layer_idx * per_layer + idx)
    out = []
    for stack, new in zip(stacks, news):
        heads, lanes = stack.shape[-2:]
        new = pad_minor(new, lanes).astype(stack.dtype)
        flat = stack.reshape(l * per_layer, heads, lanes)
        flat = flat.at[flat_idx].set(new.reshape(-1, heads, lanes),
                                     mode="drop")
        out.append(flat.reshape(stack.shape))
    return tuple(out)


def scatter_kv(
    k_cache: jax.Array,  # [N_blocks, block_size, KVH, D] (one layer)
    v_cache: jax.Array,
    new_k: jax.Array,    # [B, S, KVH, D]
    new_v: jax.Array,
    slot_mapping: jax.Array,  # [B, S] flat slot index (block*bs + off); -1 → drop
) -> Tuple[jax.Array, jax.Array]:
    """Write new K/V into cache slots. Out-of-range (-1) slots are dropped.

    The two caches may have different trailing (heads, dim) — MLA stores a
    latent in "k" and the shared rope key in "v" (models/deepseek.py)."""
    n_blocks, block_size, kvh, dk = k_cache.shape
    vh, dv = v_cache.shape[-2:]
    # cast at the write (fp8 KV cache stores e4m3; no-op otherwise)
    new_k = pad_minor(new_k, dk).astype(k_cache.dtype)
    new_v = pad_minor(new_v, dv).astype(v_cache.dtype)
    flat_k = k_cache.reshape(n_blocks * block_size, kvh, dk)
    flat_v = v_cache.reshape(n_blocks * block_size, vh, dv)
    idx = slot_mapping.reshape(-1)
    # jax wraps negative scatter indices (-1 == last slot), so map the drop
    # sentinel to a genuinely out-of-range index for mode="drop" to act on
    idx = jnp.where(idx < 0, n_blocks * block_size, idx)
    flat_k = flat_k.at[idx].set(new_k.reshape(-1, kvh, dk), mode="drop")
    flat_v = flat_v.at[idx].set(new_v.reshape(-1, vh, dv), mode="drop")
    return (
        flat_k.reshape(n_blocks, block_size, kvh, dk),
        flat_v.reshape(n_blocks, block_size, vh, dv),
    )


def scatter_kv_stacked(
    k_all: jax.Array,  # [L, N_blocks, block_size, KVH, Dk] (stacked layers)
    v_all: jax.Array,  # [L, N_blocks, block_size, VH, Dv]
    new_k: jax.Array,  # [B, S, KVH, Dk]
    new_v: jax.Array,  # [B, S, VH, Dv]
    slot_mapping: jax.Array,  # [B, S] flat slot index (block*bs + off); -1 → drop
    layer_idx: jax.Array,     # scalar int32
) -> Tuple[jax.Array, jax.Array]:
    """Write new K/V into one layer of the *stacked* cache, in place.

    The per-layer scan used to slice the layer out (a whole-layer copy),
    scatter, and splice it back (another copy) — ~0.5 ms/layer of pure
    HBM traffic on the 1B flagship. Scattering at ``layer*N*bs + slot``
    into a flat view keeps XLA's in-place scatter on the donated carry.
    """
    l, n_blocks, block_size, kvh, dk = k_all.shape
    vh, dv = v_all.shape[-2:]
    new_k = pad_minor(new_k, dk).astype(k_all.dtype)
    new_v = pad_minor(new_v, dv).astype(v_all.dtype)
    idx = slot_mapping.reshape(-1)
    # drop sentinel AND per-layer overflow → past-the-end: a negative index
    # would wrap (see scatter_kv), and a positive out-of-range one would land
    # in the next layer's slab after the layer offset — both must drop
    per_layer = n_blocks * block_size
    total = l * per_layer
    flat_idx = jnp.where(
        (idx < 0) | (idx >= per_layer), total, layer_idx * per_layer + idx
    )
    flat_k = k_all.reshape(l * n_blocks * block_size, kvh, dk)
    flat_v = v_all.reshape(l * n_blocks * block_size, vh, dv)
    flat_k = flat_k.at[flat_idx].set(new_k.reshape(-1, kvh, dk), mode="drop")
    flat_v = flat_v.at[flat_idx].set(new_v.reshape(-1, vh, dv), mode="drop")
    return flat_k.reshape(k_all.shape), flat_v.reshape(v_all.shape)


def paged_attention(
    q: jax.Array,            # [B, S, H, D] (post-RoPE)
    k_cache: jax.Array,      # [N_blocks, block_size, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, W] block ids for each sequence
    q_positions: jax.Array,  # [B, S] absolute position of each query token
    context_lens: jax.Array, # [B] total valid tokens (incl. current) per seq
    scale: Optional[float] = None,
    softcap: float = 0.0,    # Gemma-2: logits ← cap·tanh(logits/cap)
    sliding_window=None,     # scalar (may be traced): keys within the window
    sinks=None,              # [H] per-head attention-sink logits (GPT-OSS)
    block_len: int = 1,      # static: causal over blocks of this many, full inside
) -> jax.Array:
    """Reference paged attention: gather → masked softmax → weighted sum.

    Causal semantics: query at absolute position p attends cache positions
    j where j <= p and j < context_len — and, with ``sliding_window`` w,
    j > p - w. Cache position of slot s in the gathered layout is exactly
    its sequence position (block_tables are in sequence order).

    ``block_len`` B > 1 (a family that generates by diffusion over
    blocks, models/sdar.py): causal over blocks of B positions and full
    inside one, j < (p // B + 1) · B (``block_causal``); 1 is the line
    above, and the program is then the one it always was.

    ``sinks``: a learned per-head logit that joins the softmax as a
    virtual key contributing NO value — its only effect is the extra
    exp(sink) term in the denominator (GPT-OSS attention sinks).

    The values may be narrower than the keys (``v_cache``'s own lanes):
    the output is ``[B, S, H, Dv]``. ``k_cache`` may be a tuple of
    stacks, each a part of the keys' lanes (``split_lanes``).
    """
    b, s, h, d = q.shape
    parts = k_cache if isinstance(k_cache, (tuple, list)) else None
    _, block_size, kvh, _ = (k_cache if parts is None else parts[0]).shape
    dv = v_cache.shape[-1]
    w = block_tables.shape[1]
    groups = h // kvh
    if scale is None:
        scale = d ** -0.5

    # gather: [B, W, bs, KVH, D] → [B, W*bs, KVH, D]; upcast from the
    # cache storage dtype (fp8 serving) to the compute dtype
    k = (k_cache[block_tables] if parts is None else jnp.concatenate(
        [part[block_tables] for part in parts], axis=-1))
    k = k.reshape(b, w * block_size, kvh, d).astype(q.dtype)
    v = v_cache[block_tables].reshape(b, w * block_size, kvh, dv).astype(q.dtype)

    # [B, S, H, D] x [B, T, KVH, D] with GQA: fold H → (KVH, G)
    qg = q.reshape(b, s, kvh, groups, d)
    logits = jnp.einsum("bskgd,btkd->bskgt", qg * scale, k)

    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)

    key_pos = jnp.arange(w * block_size)[None, None, :]          # [1, 1, T]
    causal = block_causal(key_pos, q_positions[:, :, None], block_len)  # [B, S, T]
    valid = key_pos < context_lens[:, None, None]                 # [B, 1→S, T]
    mask = causal & valid                                         # [B, S, T]
    if sliding_window is not None:
        mask &= key_pos > (q_positions[:, :, None] - sliding_window)
    mask = mask[:, :, None, None, :]                              # [B, S, 1, 1, T]
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)

    if sinks is not None:
        # append the sink as one extra softmax column per (kv head,
        # group), then drop its probability — the value sum is over real
        # keys only, but the denominator includes exp(sink)
        sink_col = jnp.broadcast_to(
            jnp.asarray(sinks, logits.dtype).reshape(1, 1, kvh, groups, 1),
            (b, s, kvh, groups, 1),
        )
        logits = jnp.concatenate([logits, sink_col], axis=-1)
        probs = jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(q.dtype)[..., :-1]
    else:
        probs = jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
    out = jnp.einsum("bskgt,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, dv)


def block_causal(key_pos, q_pos, block_len: int):
    """The causal mask of every attention route: key j is visible to the
    query at p iff j <= p, or, with a static ``block_len`` B > 1, iff
    j < (p // B + 1) · B (causal over blocks of B, full inside one). A
    Python branch on the static B, so that at 1 the traced operations are
    ``j <= p`` and nothing else."""
    if block_len == 1:
        return key_pos <= q_pos
    return key_pos < (q_pos // block_len + 1) * block_len


def resolve_attention_impl(impl: str) -> str:
    """'auto' → pallas on TPU, xla elsewhere (pallas still testable on CPU
    via interpret=True)."""
    if impl in ("xla", "pallas"):
        return impl
    if impl != "auto":
        raise ValueError(f"unknown attention impl {impl!r}; use auto|xla|pallas")
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def pallas_interpret() -> bool:
    """``DYN_PALLAS_INTERPRET=1`` runs every kernel in the Pallas
    interpreter, so CPU tests can drive the kernel routes through jitted
    model forwards (models don't plumb ``interpret``). Refused on a TPU
    backend: an interpreted kernel there would serve under the
    decode/flash route labels without ever going through Mosaic."""
    if not os.environ.get("DYN_PALLAS_INTERPRET"):
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "DYN_PALLAS_INTERPRET is set on a TPU backend: interpret mode "
            "is for CPU tests only — unset it to compile the kernels"
        )
    return True


def mosaic_rejects(route: str, has_sinks: bool, kv_dtype,
                   kv_heads: int) -> bool:
    """Kernel specializations Mosaic rejects on v5e (jax 0.9.0 / libtpu
    0.0.34 — the compiler's errors are in PERF.md's kernel table and
    under ROADMAP Design 3): the sink-bias finalize of the verify
    kernel (the flash kernel takes the sink as its running softmax's
    first term and compiles), and the fp8-cache page copies of the
    decode and verify kernels when one device's kv heads are not a
    multiple of fp8's sublane tiling of 4 (kvh 8 compiles and agrees
    with XLA, kvh 2 does not). ``auto`` never selects these — the XLA
    route serves and the route counter says so; an explicit ``pallas``
    compiles them and raises the compiler's error."""
    fp8 = jnp.dtype(kv_dtype) == jnp.float8_e4m3fn
    return ((has_sinks and route == "verify")
            or (fp8 and kv_heads % 4 != 0 and route in ("decode", "verify")))


def batch_axis(mesh, b: int) -> Optional[str]:
    """The mesh axis a kernel route's shard_map splits its ``b`` rows
    over: "dp" where ``b`` divides (the scheduler prefills with B=1,
    which each dp group then computes redundantly; decode, where B =
    max_batch_size, shards), else None."""
    if mesh is None or b % mesh.shape.get("dp", 1) != 0:
        return None
    return "dp"


def kernel_live_rows(live_rows, mesh, dp: Optional[str]):
    """The step's live rows (ops/live_rows.LiveRows or None) as a decode
    kernel route hands them to its kernel, a replicated operand of its
    shard_map: as given, or None where the shard_map splits the rows
    over more than one "dp" group (the list would have to be a shard:
    every row is walked there). Records the fact for the program being
    traced (``record_row_list``)."""
    if live_rows is None or (dp is not None and mesh.shape[dp] > 1):
        return None
    record_row_list()
    return live_rows


def attention(
    q: jax.Array,            # [B, S, H, D]
    k_cache: jax.Array,      # [N_blocks, bs, KVH, D] or stacked [L, N, bs, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, W]
    positions: jax.Array,    # [B, S] absolute query positions
    context_lens: jax.Array, # [B]
    impl: str = "auto",
    mesh=None,
    interpret: bool = False,
    layer_idx=None,          # required when the cache is stacked (5-D)
    scale: Optional[float] = None,  # override the head-dim default
    softcap: float = 0.0,           # Gemma-2 attention logit softcapping
    sliding_window=None,            # scalar window (int or traced); None = off
    sinks=None,                     # [H] attention-sink logits (GPT-OSS)
    live_rows=None,                 # decode_live_rows of the step, or None
    block_len: int = 1,             # static; > 1: block-causal (block_causal)
    v_dim: Optional[int] = None,    # the values' true width; None: the query's
) -> jax.Array:
    """Paged-attention dispatch: XLA gather path or the Pallas kernels.

    Returns ``[B, S, H, v_dim]``: the values' width, which is the
    query's unless the family says otherwise (``v_dim``: keys of 192 and
    values of 128, models/mimo_v2.py; every route reads the values'
    lanes off ``v_cache``, so the two sides of the cache may differ).

    ``block_len`` B > 1: the mask is causal over blocks of B positions and
    full inside one, on the XLA route, the verify kernel and the flash
    kernel (a block pass is B queries a row, so S == 1 never carries it).

    ``live_rows`` (ops/live_rows.decode_live_rows, made by the trunk
    outside its layer scan): the decode kernel walks those rows alone
    and returns zeros in the others; every other route ignores it and
    computes every row.

    ``sinks`` (GPT-OSS): a per-head logit joining every softmax as a
    virtual key with no value — the decode and verify kernels fold it
    into their finalize denominator, the flash kernel starts its running
    softmax from it; the XLA path appends a softmax column.

    Accepts the engine's full stacked-by-layer cache plus a runtime
    ``layer_idx`` — the Pallas kernels index the layer inside HBM, so the
    per-layer scan never materializes a layer copy. Decode (S == 1) takes
    the latency-tuned kernel (pallas_decode.py); prefill takes the
    flash-pipeline kernel (pallas_attention.py), which assumes affine
    query positions (positions[:, s] == positions[:, 0] + s) — the
    scheduler's layout. With a multi-device mesh it runs under shard_map:
    batch over "dp", KV heads over "tp" (no collectives — attention is
    head/batch parallel).
    """
    # keys kept as several stacks of lanes (split_lanes): every route
    # takes the tuple; ``k_cache`` below is the first part, for what is
    # read of its shape and dtype
    k_parts = tuple(k_cache) if isinstance(k_cache, (tuple, list)) else None
    if k_parts is not None:
        k_cache = k_parts[0]
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                "keys in parts of lanes are not sharded over a mesh")
    stacked = k_cache.ndim == 5
    li = jnp.asarray(0 if layer_idx is None else layer_idx, jnp.int32)
    # scale from the TRUE head dim; the cache may carry lane padding
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    out_d = d if v_dim is None else v_dim
    dk = (k_cache.shape[-1] if k_parts is None
          else sum(part.shape[-1] for part in k_parts))
    q = pad_minor(q, dk)  # zero pad lanes score 0 against zero cache pad
    # small-S tails (the speculative verify's K+1 positions; follows the
    # flash kernel's affine base_pos contract, so small custom prefill
    # buckets mask correctly too) take the fused verify kernel: ONE page
    # walk for all S queries instead of the flash kernel's per-query-
    # block passes over the table capacity
    s_q = q.shape[1]
    route = ("decode" if s_q == 1
             else "verify" if s_q <= VERIFY_MAX_S else "flash")
    has_sinks = sinks is not None
    resolved = resolve_attention_impl(impl)
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if impl == "auto" and (mosaic_rejects(
            route, has_sinks, k_cache.dtype, k_cache.shape[-2] // tp)
            # the verify kernel reads one stack of keys
            or (k_parts is not None and route == "verify")):
        resolved = "xla"
    if block_len > 1 and s_q == 1:
        raise ValueError(
            f"block_len={block_len} with one query a row: a block pass "
            "carries the whole block")
    if resolved == "xla":
        if stacked:
            # index the layer through the gather itself: block id n of
            # layer li lives at flat row li*N + n. dynamic_index_in_dim
            # would materialize a full-layer copy every scan step (~2x the
            # whole cache in HBM traffic per forward); offsetting the
            # (tiny) block table is free
            l, n_blocks = k_cache.shape[:2]
            k_cache = k_cache.reshape((l * n_blocks,) + k_cache.shape[2:])
            v_cache = v_cache.reshape((l * n_blocks,) + v_cache.shape[2:])
            block_tables = block_tables + li * n_blocks
            if k_parts is not None:
                k_cache = tuple(part.reshape((l * n_blocks,) + part.shape[2:])
                                for part in k_parts)
        elif k_parts is not None:
            k_cache = k_parts
        record_route("xla")
        return paged_attention(q, k_cache, v_cache, block_tables, positions,
                               context_lens, scale=scale, softcap=softcap,
                               sliding_window=sliding_window,
                               sinks=sinks, block_len=block_len)[..., :out_d]

    interpret = interpret or pallas_interpret()
    if k_parts is not None:
        if not stacked or route == "verify":
            raise NotImplementedError(
                "keys in parts of lanes: stacked by layer, and on the decode "
                "and flash kernels and the XLA route only")
        k_cache = k_parts
    elif not stacked:
        k_cache, v_cache = k_cache[None], v_cache[None]
    # the window may be a traced scalar (Gemma-2 alternates windowed/full
    # layers inside its layer scan) — it rides as a [1] operand so the
    # kernels stay compiled once across layers; None = disabled sentinel
    win = (
        jnp.full((1,), jnp.int32(2**30))
        if sliding_window is None
        else jnp.asarray(sliding_window, jnp.int32).reshape(1)
    )
    sink_args = (sinks,) if has_sinks else ()
    dp = batch_axis(mesh, q.shape[0])
    record_route(route)
    if route == "verify":
        fn = functools.partial(
            paged_verify_attention, scale=scale, interpret=interpret,
            softcap=softcap, block_len=block_len,
        )
        vbase = positions[:, 0].astype(jnp.int32)
        args = (q, k_cache, v_cache, block_tables, vbase, context_lens,
                li, win) + sink_args

        def call(q, k_cache, v_cache, block_tables, vbase, context_lens,
                 li, win, *sk):
            return fn(q, k_cache, v_cache, block_tables, vbase,
                      context_lens, li, window=win,
                      sinks=sk[0] if sk else None)
    elif route == "decode":
        fn = functools.partial(
            paged_decode_attention, scale=scale, interpret=interpret,
            softcap=softcap,
        )
        live_rows = kernel_live_rows(live_rows, mesh, dp)
        args = (q, k_cache, v_cache, block_tables, context_lens, li,
                win, live_rows) + sink_args

        def call(q, k_cache, v_cache, block_tables, context_lens, li, win,
                 live_rows, *sk):
            return fn(q, k_cache, v_cache, block_tables, context_lens, li,
                      window=win, sinks=sk[0] if sk else None,
                      live_rows=live_rows)
    else:
        fn = functools.partial(
            paged_flash_attention, scale=scale, interpret=interpret,
            softcap=softcap, block_len=block_len,
        )
        base_pos = positions[:, 0].astype(jnp.int32)
        args = (q, k_cache, v_cache, block_tables, base_pos, context_lens,
                li, win) + sink_args

        def call(q, k_cache, v_cache, block_tables, base_pos, context_lens,
                 li, win, *sk):
            return fn(q, k_cache, v_cache, block_tables, base_pos,
                      context_lens, li, window=win,
                      sinks=sk[0] if sk else None)
    if mesh is not None and mesh.size > 1:
        in_specs = [
            P(dp, None, "tp", None),           # q [B, S, H, D]
            P(None, None, None, "tp", None),   # k_cache [L, N, bs, KVH, D]
            P(None, None, None, "tp", None),   # v_cache
            P(dp, None),                       # block_tables
        ]
        if route != "decode":
            in_specs.append(P(dp))             # base_pos (flash + verify)
        in_specs.extend([P(dp), P(), P()])     # context_lens, layer_idx, win
        if route == "decode":
            in_specs.append(P())               # live_rows (or None)
        if has_sinks:
            in_specs.append(P("tp"))           # sinks follow the head shard
        call = jax.shard_map(
            call,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(dp, None, "tp", None),
            check_vma=False,  # pallas out_shape carries no vma annotation
        )
    return call(*args)[..., :out_d]


def prefill_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, KVH, D]
    v: jax.Array,
    valid_lens: jax.Array,  # [B] number of real (non-pad) tokens
    scale: Optional[float] = None,
    block_len: int = 1,     # static; > 1: block-causal (block_causal)
) -> jax.Array:
    """Dense causal self-attention for prefill without cache reads (used when
    the whole context is the in-flight prompt — no prefix-cache hit)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    if scale is None:
        scale = d ** -0.5
    qg = q.reshape(b, s, kvh, groups, d)
    logits = jnp.einsum("bskgd,btkd->bskgt", qg * scale, k)
    q_pos = jnp.arange(s)[None, :, None]
    k_pos = jnp.arange(s)[None, None, :]
    mask = block_causal(k_pos, q_pos, block_len) & (
        k_pos < valid_lens[:, None, None])
    logits = jnp.where(mask[:, :, None, None, :], logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bskgt,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)
