"""Batched in-jit sampling: greedy / temperature / top-k / top-p / min-p plus
presence, frequency and repetition penalties — all per slot.

All parameters are per-request arrays so one compiled program serves every
sampling configuration in the batch (no recompiles when requests differ).
temperature == 0 means greedy. Every request samples from its own PRNG key
(seeded requests are bit-reproducible and isolated from their batchmates —
reference surface: lib/llm/src/protocols/common.rs:248-316 SamplingOptions;
where a decode program walks its rows a tile at a time, ``_walk_tiles``,
every walk sums a row along the vocabulary within the same ``[ROW_TILE, V]``
operations, so that holds however full the batch is: 0 units in the last
place across loads on a v5e, scripts/pad_row_cost.py --tail).

Penalty state lives on device as two [num_slots, vocab] buffers owned by the
ModelRunner: ``counts`` (how often each token was *generated*) and ``seen``
(tokens present in the prompt). Penalty semantics follow the de-facto
standard the reference's engines implement (vLLM):

- repetition_penalty r: for tokens in prompt or output, positive logits are
  divided by r, negative multiplied (r == 1 disables).
- presence_penalty: subtracted once from every token that has been generated.
- frequency_penalty: subtracted per occurrence of a generated token.
- min_p: after temperature scaling, tokens with prob < min_p * max_prob drop.

The filters run in the order penalties → temperature → top-k → min-p →
top-p, in float32. ``filter_logits`` does the last three with one cutoff
value a row and no sort (nor argsort, [B, V] gather or scatter): the
cutoff is searched for, a few bits of its float32 image a pass, each pass
a count or a masked sum along the unsorted row, so the cost is a function
of B · V alone (1.07 ms at [64, 163840] on a v5e where the sort it
replaced was 6.6 ms and the filter around it 7.4; PERF.md §6, PR 29) and
of the bits two entries of a row can differ in: sixteen where the rows
are the head's bfloat16 logits with nothing added, and then half the
passes (``_largest_threshold``; PERF.md §6, PR 61).
Entries exactly equal to the cutoff are all kept, by top-p as by top-k.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..ops.live_rows import live_row_list
from ..protocols.common import SamplingOptions


@dataclasses.dataclass
class SamplingParams:
    """Per-slot device arrays; batch dimension leads."""

    temperature: jax.Array          # [B] f32; 0 → greedy
    top_k: jax.Array                # [B] i32; 0 → disabled
    top_p: jax.Array                # [B] f32; 1.0 → disabled
    min_p: jax.Array                # [B] f32; 0.0 → disabled
    presence_penalty: jax.Array     # [B] f32; 0.0 → disabled
    frequency_penalty: jax.Array    # [B] f32; 0.0 → disabled
    repetition_penalty: jax.Array   # [B] f32; 1.0 → disabled
    keys: jax.Array                 # [B, 2] u32 per-request base PRNG keys
    counters: jax.Array             # [B] i32 fold-in step counters

    @classmethod
    def zeros(cls, batch: int) -> "SamplingParams":
        return cls(
            temperature=jnp.zeros(batch, jnp.float32),
            top_k=jnp.zeros(batch, jnp.int32),
            top_p=jnp.ones(batch, jnp.float32),
            min_p=jnp.zeros(batch, jnp.float32),
            presence_penalty=jnp.zeros(batch, jnp.float32),
            frequency_penalty=jnp.zeros(batch, jnp.float32),
            repetition_penalty=jnp.ones(batch, jnp.float32),
            keys=jnp.zeros((batch, 2), jnp.uint32),
            counters=jnp.arange(batch, dtype=jnp.int32),
        )


jax.tree_util.register_dataclass(
    SamplingParams,
    data_fields=[f.name for f in dataclasses.fields(SamplingParams)],
    meta_fields=[],
)


def host_row(opts: SamplingOptions):
    """One request's SamplingOptions → the per-slot host scalars
    (temperature, top_k, top_p, min_p, presence, frequency, repetition)."""
    temp = opts.temperature if opts.temperature is not None else 1.0
    return (
        float(temp),
        int(opts.top_k) if opts.top_k and opts.top_k > 0 else 0,
        float(opts.top_p) if opts.top_p is not None else 1.0,
        float(opts.min_p) if opts.min_p else 0.0,
        float(opts.presence_penalty) if opts.presence_penalty else 0.0,
        float(opts.frequency_penalty) if opts.frequency_penalty else 0.0,
        float(opts.repetition_penalty) if opts.repetition_penalty else 1.0,
    )


def seed_to_key(seed: int) -> np.ndarray:
    """A per-request base key from an explicit user seed (uint32[2])."""
    seed = int(seed)
    return np.asarray(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32
    )


def _row_keys(params: SamplingParams) -> jax.Array:
    """Fold each row's step counter into its base key (typed key array)."""
    def fold(kdata, c):
        return jax.random.fold_in(
            jax.random.wrap_key_data(kdata, impl="threefry2x32"), c
        )
    return jax.vmap(fold)(params.keys, params.counters)


# the order-preserving integer image of a float32: flip the sign bit of a
# non-negative value and every bit of a negative one, and unsigned order
# is float order. -inf is the smallest image of a number; the images
# under it, and those over +inf's, are NaN bit patterns.
_SIGN = np.uint32(0x80000000)
_KEY_NEG_INF = np.uint32(0x007FFFFF)

# bits of the cutoff's image settled by one pass over the row: a pass
# tests 2**bits - 1 thresholds, so a search takes 32 / bits passes, 16 /
# bits where sixteen bits tell the row's entries apart. Timed alone on a
# v5e at [64, 163840], top-p rows (PERF.md §6, PR 29): 1 bit 2.09 ms (a
# pass streams the row at 590 GB/s), 2 bits 1.19, 4 bits 1.07 (fifteen
# thresholds a pass are bound by the vector unit, no longer by memory);
# with top-k rows too 3.91 / 2.12 / 1.77. Timed again for the short search
# (scripts/pad_row_cost.py --tail --search-bits 4,2; PERF.md §5 "Since
# PR 61"): the served tail's tile of 16 untouched rows takes 0.88 / 0.50 /
# 0.43 ms at V = 261 120 / 163 840 / 131 072 with 4 bits a pass and 0.91 /
# 0.53 / 0.46 with 2, a tile that holds a touched row 1.05 / 0.61 / 0.52
# and 1.12 / 0.66 / 0.57, where the parent's tile takes 1.00 / 0.60 / 0.50
# (at 2 bits a request with a penalty would pay for the others' gain; that
# call looked at the rows' bits, 0.05 ms a tile at 261 120: with the look
# at the bias rows instead, ``_adds_nothing``, 4 bits read 0.83 / 0.50 /
# 0.43 and 1.00 / 0.60 / 0.51); the block pass over [128, 151 936] alone
# goes the other way, 1.80 with 4 bits and 1.21 with 2 (a pass of fifteen
# thresholds costs an entry 16 ps there and 10 in a tile of [16, 261 120];
# PERF.md §7 "Left by PR 61" (1)). One constant: 4 stays
_SEARCH_BITS = 4


def _key_value(key: jax.Array, low=None) -> jax.Array:
    """The float32 whose image is ``key``. Keys under -inf's read -inf, so
    a predicate "x >= value" stays monotone over all 2**32 keys; keys over
    +inf's read NaN, which no entry reaches. ``low`` (a uint32 mask of low
    bits) is set in a negative value's key first: its image is the
    complement of its bits, so that is the key of the value whose bits
    under the mask are zero."""
    if low is not None:
        key = jnp.where(key >= _SIGN, key, key | low)
    bits = jnp.where(key >= _SIGN, key ^ _SIGN, ~key)
    value = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where(key <= _KEY_NEG_INF, -jnp.inf, value)


# a bfloat16 widened to float32 has these bits zero
_BELOW_BFLOAT16 = np.uint32(0xFFFF)


def _where(known, a, b):
    """``a`` where ``known`` else ``b``, for a bool that the trace knows or
    a scalar that only the device does."""
    if isinstance(known, (bool, np.bool_)):
        return a if known else b
    return jnp.where(known, a, b)


def short_search(head_dtype, mesh: Optional[Mesh] = None) -> bool:
    """Whether ``sample`` may find the cutoffs of logits that the head made
    in ``head_dtype`` by the short search (``_largest_threshold``): the
    head's values are bfloat16. Where a bias or penalty rows go with them
    the rows at hand are looked at on the device, which takes one device:
    several would have to agree on the loop's trip count, an all-reduce a
    step for four passes of their sixteen rows."""
    return (jnp.dtype(head_dtype) == jnp.bfloat16
            and (mesh is None or mesh.size == 1))


def _pin(x: jax.Array, mesh: Mesh, *spec) -> jax.Array:
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


def _whole_rows(logits: jax.Array, mesh: Optional[Mesh]) -> bool:
    """Whether ``sample`` lays [B, V] logits out with whole rows on a
    device: on a mesh that shards them (the head makes them with the batch
    over "dp" and the vocabulary over "tp") and whose devices divide the
    rows."""
    if mesh is None:
        return False
    devices = mesh.shape["dp"] * mesh.shape["tp"]
    return devices > 1 and logits.shape[0] % devices == 0


def _largest_threshold(holds, rows: tuple, short=False,
                       divisor: Optional[jax.Array] = None) -> jax.Array:
    """The largest float32 ``t`` a row for which ``holds(t)`` is true, an
    array of shape ``rows``.

    ``holds`` takes thresholds ``[*rows, J]``, J candidates a row, and
    returns bools of that shape; it has to be monotone: true
    up to some value, false above it. The image of ``t`` is built from its
    top bit down, ``_SEARCH_BITS`` at a time: the next digit is the number
    of candidates that hold.

    ``short`` (a bool, the trace's or a scalar of the device's) says that
    ``holds`` changes only at values ``b / divisor`` [*rows], ``b`` a
    bfloat16: then the candidates are those quotients, ``b`` built from the
    sixteen bits of its image that can differ, in half the passes, and
    the answer is such a quotient. The loop is one either way, its trip
    count read on the device where ``short`` is."""
    digits = jnp.arange(1, 1 << _SEARCH_BITS, dtype=jnp.uint32)
    passes = 32 // _SEARCH_BITS
    low = (None if short is False
           else _where(short, _BELOW_BFLOAT16, np.uint32(0)))

    def value(key):
        t = _key_value(key, low)
        if short is False:
            return t
        by = divisor.reshape(rows + (1,) * (t.ndim - len(rows)))
        return _where(short, t / by, t)

    def settle(i, key):
        shift = jnp.asarray(32 - _SEARCH_BITS * (i + 1)).astype(jnp.uint32)
        ok = holds(value(key[..., None] | (digits << shift)))
        return key | (ok.sum(-1).astype(jnp.uint32) << shift)

    # a loop and not its 32 / bits copies: the loop's operand is the row as
    # it lies in memory, so no pass can take the row's producers (bias,
    # penalties, temperature) into its fusion and read their inputs too;
    # unrolled it is 0.05 ms faster at [64, 163840], alone
    key = jax.lax.fori_loop(
        0, _where(short, passes // 2, passes), settle,
        jnp.zeros(rows, jnp.uint32))
    return value(key)


def _each(reduce, thresholds: jax.Array) -> jax.Array:
    """``reduce(t)`` for each of the J thresholds a row, ``[..., J]`` →
    ``[..., J]``. J reductions of one ``[B, V]`` row, which the compiler
    makes one fusion that reads the row once; a single reduction of a
    ``[B, J, V]`` mask it splits, and stores what the J share."""
    return jnp.stack(
        [reduce(t) for t in jnp.moveaxis(thresholds, -1, 0)], -1)


def kth_largest(values: jax.Array, k) -> jax.Array:
    """The ``k``-th largest entry of each row of ``values`` [..., N]
    float32 (``k`` an int or [...] int32, at least 1), by the cutoff
    search and no sort; -inf where a row has fewer than ``k`` entries
    over -inf (ops/latent_select.py picks an indexer's keys with it)."""
    k = jnp.asarray(k, jnp.int32)

    def count(t):
        return (values >= t[..., None]).sum(-1, dtype=jnp.int32)

    return _largest_threshold(
        lambda ts: _each(count, ts) >= k[..., None], values.shape[:-1])


def filter_logits(
    scaled: jax.Array,  # [..., V] f32 temperature-scaled logits
    top_k: jax.Array,   # [...] i32; 0 → disabled
    top_p: jax.Array,   # [...] f32; 1.0 → disabled
    min_p: jax.Array,   # [...] f32; 0.0 → disabled
    bfloat16_over=None,  # [...] f32: see "the short search"
    short=False,
) -> jax.Array:
    """top-k → min-p → top-p: ``scaled`` with every dropped entry at -inf.

    Each filter keeps the entries at or above one value, so the three
    together come to one number a row, the smallest kept logit, and the
    mask in vocabulary order is ``scaled >= cutoff``. Nothing is sorted:
    each value is the largest threshold that still satisfies a monotone
    predicate, found by ``_largest_threshold`` in a fixed number of
    reductions over the unsorted row whatever the data.

    - top-k: the k-th largest value is the largest ``t`` with
      ``count(x >= t) >= k`` (integer counts: exact). Searched only when
      some row asks.
    - min-p: ``prob < min_p * max_prob`` is ``x < max + log(min_p)``.
    - top-p: an entry stays iff the probability strictly above it is under
      ``top_p`` (so the top token always stays), which makes the cutoff
      the largest ``t`` with ``mass(x >= t) >= top_p * mass(alive)``, the
      mass ``exp(x - max)`` recomputed in the pass over the entries top-k
      and min-p left alive. ``top_p >= 1`` keeps all of them.

    Entries exactly equal to the cutoff are ALL kept, by top-p as by
    top-k. -inf entries lie below every threshold and carry no mass. A
    pass is one fusion that reads the ``[B, V]`` row once. Alone on a
    v5e, top-p rows: 1.07 ms at ``[64, 163840]`` where the one
    values-only sort this replaced, with its softmaxes and cumulative
    sum, took 7.41 (1.77 with top-k rows too), under 0.2 against 0.74 at
    ``[32, 32064]`` and under 0.2 against 0.30 at ``[16, 32768]``
    (PERF.md §6, PR 29).

    The short search. A pass at fifteen thresholds is bound by the vector
    unit, so a search costs its passes, and those follow the bits in which
    two entries of a row can differ. ``short`` (a bool, the trace's or the
    device's) says that every entry of ``scaled`` is a bfloat16 value over
    its row's ``bfloat16_over`` (``sample``: the head's logits untouched,
    over the temperature): both searches then try only such quotients,
    which sixteen bits tell apart, in four passes where a float32 row
    takes eight. The kept set is the same. Both predicates change only at
    an entry's value, so the full search's answer is the smallest kept
    entry ``s``, itself such a quotient, which the short one tries; and
    dividing by a positive number is monotone, so the largest candidate
    that holds divides to ``s`` again. Two distinct logits whose
    quotients round to one float32 are one candidate here as they are
    one value there: both stay or both go, as the order of the filters
    and the ties are decided on ``scaled`` in either search.
    """
    v = scaled.shape[-1]
    row_max = scaled.max(axis=-1)

    def largest_threshold(holds):
        return _largest_threshold(holds, row_max.shape, short, bfloat16_over)

    def kth_largest():
        def count(t):
            return (scaled >= t[..., None]).sum(-1, dtype=jnp.int32)

        k = jnp.clip(top_k, 1, v)[..., None]
        t = largest_threshold(lambda ts: _each(count, ts) >= k)
        return jnp.where(top_k > 0, t, -jnp.inf)

    floor = jax.lax.cond(
        jnp.any(top_k > 0), kth_largest,
        lambda: jnp.full(row_max.shape, -jnp.inf, jnp.float32),
    )
    # min_p 0 → log 0 = -inf: nothing dropped
    floor = jnp.maximum(floor, row_max + jnp.log(min_p))

    def alive_mass(ts):                               # [..., J] → [..., J]
        e = jnp.exp(scaled - row_max[..., None])
        return _each(
            lambda t: jnp.where(scaled >= t[..., None], e, 0.0).sum(-1),
            jnp.maximum(ts, floor[..., None]))

    need = top_p[..., None] * alive_mass(floor[..., None])
    nucleus = largest_threshold(lambda ts: alive_mass(ts) >= need)
    cutoff = jnp.where(top_p >= 1.0, floor, jnp.maximum(nucleus, floor))
    # the top token stays whatever top_p says
    cutoff = jnp.where(cutoff <= row_max, cutoff, row_max)
    return jnp.where(scaled >= cutoff[..., None], scaled, -jnp.inf)


def _adds_nothing(params: SamplingParams, bias: Optional[jax.Array],
                  penalised: bool) -> jax.Array:
    """Whether ``sample`` leaves every row at hand as the head made it: a
    bias row (``bias`` [B, V] or None) of zeros, and -inf, which only
    replaces a value by another bfloat16; and, where count rows are
    handed in (``penalised``), every row's penalties neutral. The bias
    rows are looked at one maximum a row, a reduction of the row
    maximum's form that reads ``bias`` alone: a test of the sum's own
    bits makes the compiler write the summed rows out for it (0.05 ms a
    tile of [16, 261 120] on a v5e; PERF.md §6, PR 61)."""
    nothing = jnp.asarray(True)
    if bias is not None:
        nothing &= jnp.all(jnp.where(
            jnp.isneginf(bias), 0.0, jnp.abs(bias)).max(axis=-1) == 0.0)
    if penalised:
        nothing &= jnp.all((params.repetition_penalty == 1.0)
                           & (params.frequency_penalty == 0.0)
                           & (params.presence_penalty == 0.0))
    return nothing


def sample(
    logits: jax.Array,  # [B, V] as the head made them, or f32
    params: SamplingParams,
    counts: Optional[jax.Array] = None,   # [B, V] i32 generated-token counts
    seen: Optional[jax.Array] = None,     # [B, V] bool prompt-token presence
    bias: Optional[jax.Array] = None,     # [B, V] f32 OpenAI logit_bias rows
    mesh: Optional[Mesh] = None,          # the step's mesh, where it has one
    head_dtype=None,   # the head's, where ``logits`` were widened since
) -> jax.Array:
    """Returns sampled token ids [B].

    The filter's search is the short one (``filter_logits``) where the
    rows are bfloat16 values still: known to the trace where the head
    made bfloat16 and neither a bias nor penalty rows are handed in (the
    block family), looked up on the device where they are (the served
    tail: a tile whose rows all have a bias row of zeros, or of zeros and
    -inf, and neutral penalties; ``_adds_nothing``), never where the head
    made float32 or on a mesh of several devices (``short_search``)."""
    narrow = short_search(
        logits.dtype if head_dtype is None else head_dtype, mesh)
    logits = logits.astype(jnp.float32)
    # The filter's search reduces along the vocabulary in every pass, and
    # along a vocabulary that stays sharded each pass would all-reduce. So
    # the whole tail runs on whole rows: one all-to-all of the logits (the
    # penalty state is replicated over "tp", so its rows cost nothing).
    # Both ends are pinned, because the partitioner carries a layout it is
    # given as far as it can: whole rows alone reach back into the head's
    # product, which then gathers its weights on every device (2.5 ms of a
    # tp=4 Mistral step, PERF.md §6, PR 29), and row-sharded tokens reach
    # forward into the counts' update, which then all-reduces [B, V].
    whole_rows = _whole_rows(logits, mesh)
    if whole_rows:
        params, counts, seen, bias = jax.tree_util.tree_map(
            lambda x: _pin(x, mesh, "dp", *[None] * (x.ndim - 1)),
            (params, counts, seen, bias))        # as they come; None stays
        logits = _pin(_pin(logits, mesh, "dp", "tp"), mesh, ("dp", "tp"), None)
    if narrow and (bias is not None or counts is not None):
        narrow = _adds_nothing(params, bias, counts is not None)
    if bias is not None:
        logits = logits + bias

    # ---- penalties (on raw logits, before temperature) ----
    if counts is not None:
        generated = counts > 0
        ever = generated if seen is None else (generated | seen)
        rp = params.repetition_penalty[:, None]
        logits = jnp.where(
            ever, jnp.where(logits > 0, logits / rp, logits * rp), logits
        )
        logits = logits - params.frequency_penalty[:, None] * counts.astype(jnp.float32)
        logits = logits - params.presence_penalty[:, None] * generated.astype(jnp.float32)

    greedy = jnp.argmax(logits, axis=-1)

    # temperature scaling (guard against 0 for the sampled branch)
    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = logits / temp

    scaled = filter_logits(scaled, params.top_k, params.top_p, params.min_p,
                           bfloat16_over=temp[:, 0], short=narrow)

    row_keys = _row_keys(params)
    sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(row_keys, scaled)
    tokens = jnp.where(
        params.temperature <= 0.0, greedy, sampled).astype(jnp.int32)
    return _pin(tokens, mesh, "dp") if whole_rows else tokens


# ---- the tail over the rows that hold a token ----

# Rows a tile, fixed from a timing of the tail alone on a v5e
# (scripts/pad_row_cost.py --tail; PERF.md §5 "Since PR 47", §6). A tile's
# gathers copy every row of their source whatever the tile holds, so a
# tile costs its rows' share of the pass over all rows plus 0.1-0.25 ms:
# at [64, 261120], 16 rows live, tiles of 8 / 16 / 32 take 1.36 / 1.00 /
# 1.71 ms where all 64 rows at once take 2.94, at [64, 163840] 0.81 /
# 0.60 / 0.98 of 1.68, at [64, 131072] 0.70 / 0.50 / 0.85 of 1.53. Eight
# rows a tile pay the gathers twice for the same rows; 32 are one coarse
# step for a batch a quarter full. (All with the full search; a tile of 16
# untouched rows takes 0.83 / 0.50 / 0.43 since PR 61, the gathers what
# they were.)
ROW_TILE = 16


def tile_rows(logits: jax.Array, mesh: Optional[Mesh], live) -> int:
    """Rows a tile where the tail of ``logits`` [R, V] runs a tile of rows
    at a time (``over_live_rows``, ``over_all_rows``), 0 where it runs
    over ``[R, V]`` at once: it walks tiles where it is told which rows
    are read, the logits lie on one device and half the rows are at least
    a tile. On a mesh of several the tail keeps its full-row form
    (``sample``'s whole rows are 16 a device at tp=4, and a list a device
    would have to be made inside a ``shard_map``)."""
    tiles = (live is not None and 2 * ROW_TILE <= logits.shape[0]
             and (mesh is None or mesh.size == 1))
    return ROW_TILE if tiles else 0


def walks_live_rows(live, rows: int, tile: int):
    """Whether a batch of ``rows`` with ``live`` of them live (traced or
    not) is walked by its list of live rows: while the list is at least a
    tile shorter than the batch. Every tile of the list gathers its rows
    anew, the walk over all rows gathers once: of [64, 261120], 48 / 64
    live, the list takes 2.66 / 3.53 ms and all rows 3.12, of [64, 163840]
    1.69 / 2.25 and 1.86, of [64, 131072] 1.49 / 1.97 and 1.49 (my chip
    run, PR 47)."""
    return live <= rows - tile


def tiled_rows(live: int, rows: int, tile: int) -> int:
    """Rows the tail of a ``rows``-row program that walks tiles of
    ``tile`` runs with ``live`` rows live: whole tiles of them by the
    list, every row past it (``walks_live_rows``). The scheduler's count
    (``dynamo_scheduler_sampling_rows_run_total``)."""
    if walks_live_rows(live, rows, tile):
        return -(-live // tile) * tile
    return rows


def short_search_rows(live, plain, tile: int) -> int:
    """Of the rows ``tiled_rows`` counts, those whose tile (or whole
    batch, ``tile`` 0) holds no live row but a ``plain`` one (both [R]
    bool, host arrays): a request with no bias row and neutral penalties,
    whose logits are the head's. Where the head makes bfloat16
    (``short_search``) those are the rows whose search ran the short form
    (``dynamo_scheduler_sampling_short_search_rows_total``), as far as the
    host knows: the device looks at the bias rows themselves
    (``_adds_nothing``), so a guided mask of 0 and -inf runs short
    uncounted, and a row without a sequence is taken as plain where its
    slot may hold the bias row of the request before."""
    live, plain = np.asarray(live, bool), np.asarray(plain, bool)
    r, n = live.size, int(live.sum())
    if not tile:
        return r if plain[live].all() else 0
    if walks_live_rows(n, r, tile):
        listed = np.concatenate([plain[live], np.ones(-n % tile, bool)])
        return int(listed.reshape(-1, tile).all(axis=1).sum()) * tile
    ok = plain | ~live
    return sum(min(tile, r - at) for at in range(0, r, tile)
               if ok[min(at, r - tile):][:tile].all())


def _walk_tiles(r: int, trips, of_tile, tail) -> tuple:
    """``tail`` a tile at a time, ``trips`` tiles: ``of_tile(i)`` gives
    tile ``i``'s inputs and the rows [T] its outputs belong to (``r``:
    to none). A tuple of ``[r, ...]`` arrays, zero where nothing was
    written.

    Whatever hands it the tiles, the tail is traced on ``[T, V]`` arrays
    that are made before it starts (the barrier), so a row's sums along
    the vocabulary are made the same way in every walk, and its token and
    log-probability do not depend on how full the batch is. Without the
    barrier a tile's making is fused into the tail's reductions, a
    gather's pieces otherwise than a slice, and the log-probabilities of
    the two walks differ by 1-2 units in the last place on the chip (as
    a pass over ``[R, V]`` at once differs from either by one); the
    pieces are also read again by every fusion that takes them, which
    costs more than writing the tile once (16 of ``[64, 131072]``: 0.67
    ms against 0.50; scripts/pad_row_cost.py --tail, my chip run,
    PR 47)."""
    def body(i, outs):
        mine, to = of_tile(i)
        drawn = tail(jax.lax.optimization_barrier(mine))
        return tuple(o.at[to].set(x, mode="drop")
                     for o, x in zip(outs, drawn))

    outs = tuple(jnp.zeros((r,) + o.shape[1:], o.dtype)
                 for o in jax.eval_shape(lambda: tail(of_tile(0)[0])))
    return jax.lax.fori_loop(0, trips, body, outs)


def over_live_rows(live: jax.Array, t: int, of_rows, tail) -> tuple:
    """``tail`` on the rows where ``live`` [R] is true and zeros on the
    others. ``of_rows(rows)`` takes ``rows`` [T] int32, numbers of rows,
    and returns those rows of the tail's inputs; ``tail`` takes that and
    returns a tuple of arrays ``[T, ...]``, one entry a row, none
    depending on another row's.

    The live rows are walked a tile of ``t`` at a time in a loop whose
    trip count, ``ceil(n / t)``, is known on the device only: a row
    without a token costs nothing, and a step's cost follows the batch
    that is there and not ``max_batch_size``. Each tile gathers its own
    rows: one gather of all the listed rows before the loop, its result
    sliced a tile, was timed and lost (the ``[R, V]`` arrays are written
    and read again: 1.55 ms against 0.91 at 16 of ``[64, 261120]``; my
    chip run, PR 47). The last tile's rows past the list's end are its
    padding (row ``R - 1`` again): computed, and written nowhere."""
    r = live.shape[0]
    listed = live_row_list(live)
    rows = jnp.concatenate(
        [listed.rows, jnp.full((-r % t,), r - 1, jnp.int32)])

    def of_tile(i):
        at = jax.lax.dynamic_slice_in_dim(rows, i * t, t)
        return of_rows(at), jnp.where(i * t + jnp.arange(t) < listed.n, at, r)

    return _walk_tiles(r, (listed.n + t - 1) // t, of_tile, tail)


def over_all_rows(live: jax.Array, t: int, whole, tail) -> tuple:
    """``over_live_rows`` for a batch too full for its list to pay:
    ``whole`` is the tail's inputs for every row (gathered once, as a pass
    over all rows gathers them), and a tile is ``t`` rows as they lie
    (the last one moved back to end with the batch: its first rows are
    the tile's before, written twice with the same values)."""
    r = live.shape[0]

    def of_tile(i):
        at = jnp.minimum(i * t, r - t) + jnp.arange(t)
        mine = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, at[0], t), whole)
        return mine, jnp.where(live[at], at, r)

    return _walk_tiles(r, -(-r // t), of_tile, tail)


# ---- device-resident finish detection (the persistent decode loop) ----
#
# The fused decode burst can evaluate EOS / hidden-stop / max-token /
# model-len checks inside its scan and freeze finished rows instead of
# ending the burst (model_runner._build_burst's device-finish variant).
# The per-row stop-token set rides as a fixed-width id matrix; requests
# whose set overflows the width stay on the host sync path (the
# scheduler's admission-time "device-checkable" classification) and are
# COUNTED there (dynamo_engine_sync_fallback_total{reason}) instead of
# silently downgrading.

# ids per row: eos ids + hidden stop ids, -1 padded. Widened 8 → 16
# (two rows' worth of the original matrix packed into one): requests
# with 9-16 stop/eos ids used to fall out of the chain silently.
STOP_ID_WIDTH = 16


def stop_id_row(eos_ids, hidden_ids, ignore_eos: bool) -> Optional[np.ndarray]:
    """One request's device stop-token row: the merged eos (unless
    suppressed) + hidden-stop id set, -1 padded to ``STOP_ID_WIDTH``.
    Returns None when the set overflows the width — the request is not
    device-checkable and must keep host-side finish checks."""
    ids = set() if ignore_eos else {int(t) for t in (eos_ids or [])}
    ids |= {int(t) for t in (hidden_ids or [])}
    if len(ids) > STOP_ID_WIDTH:
        return None
    row = np.full(STOP_ID_WIDTH, -1, np.int32)
    row[: len(ids)] = sorted(ids)
    return row


def device_finish_mask(
    tokens: jax.Array,     # [B] i32 the step's sampled tokens
    gen: jax.Array,        # [B] i32 generated count INCLUDING this token
    pos: jax.Array,        # [B] i32 position the step's forward ran at
    stop_ids: jax.Array,   # [B, STOP_ID_WIDTH] i32, -1 padded
    min_new: jax.Array,    # [B] i32 min_tokens (suppresses eos/stop below)
    max_new: jax.Array,    # [B] i32 effective max_tokens
    max_model_len: int,
) -> jax.Array:
    """Per-row finish verdict for one scan step — the exact device
    mirror of ``Scheduler._check_finish``: at host-check time the
    committed context is ``pos + 1`` (the pending token's KV was just
    written), so the model-len bound reads ``pos + 2 >= max_model_len``.
    Token ids are non-negative, so the -1 padding never matches."""
    hit = (tokens[:, None] == stop_ids).any(axis=1)
    stop = (gen >= min_new) & hit
    length = (gen >= max_new) | (pos + 2 >= max_model_len)
    return stop | length


# ---- device-approximate stop strings (suffix ring + rolling hash) ----
#
# Stop STRINGS are a text-level condition the engine cannot evaluate
# exactly (it holds no tokenizer), so chained rows use an APPROXIMATION:
# the preprocessor ships each stop string's canonical tokenization
# (StopConditions.stop_token_seqs), the burst program carries a ring of
# the last SUFFIX_RING_W emitted tokens per row, and each step compares
# rolling polynomial hashes of the ring's suffixes against the
# precomputed per-sequence target hashes. A match FREEZES the row as a
# stop *candidate*; the host confirms on drain with an exact token-
# suffix compare (Scheduler._check_finish runs the same check on every
# emitted token, so a true candidate already carries its STOP verdict)
# and a hash collision resumes the row byte-identically. Non-canonical
# tokenizations of a stop string are still caught by the backend
# detokenizer jail, exactly as on the sync path.

SUFFIX_RING_W = 32   # trailing tokens carried per row (also feeds ngram)
STOP_SEQ_WIDTH = 4   # stop sequences per row the device can watch
STOP_SEQ_MAX_LEN = 8 # tokens per watched sequence

_HASH_P = np.uint32(1000003)


def stop_seq_hash(seq) -> int:
    """Polynomial hash of one token sequence (uint32, wrapping) — the
    host mirror of the in-program rolling suffix hash."""
    h = np.uint32(0)
    with np.errstate(over="ignore"):
        for t in seq:
            h = np.uint32(h * _HASH_P + np.uint32(int(t) + 1))
    return int(h)


def stop_seq_rows(seqs):
    """Pack one request's stop token sequences into the device rows:
    ``(hashes [STOP_SEQ_WIDTH] uint32, lens [STOP_SEQ_WIDTH] int32)``.
    Returns None when the set overflows the width/length bounds — the
    request is not device-checkable (counted, never silent)."""
    seqs = [tuple(int(t) for t in s) for s in (seqs or []) if s]
    if not seqs or len(seqs) > STOP_SEQ_WIDTH:
        return None
    if any(len(s) > STOP_SEQ_MAX_LEN for s in seqs):
        return None
    hashes = np.zeros(STOP_SEQ_WIDTH, np.uint32)
    lens = np.zeros(STOP_SEQ_WIDTH, np.int32)
    for i, s in enumerate(seqs):
        hashes[i] = stop_seq_hash(s)
        lens[i] = len(s)
    return hashes, lens


def ring_init(tokens, width: int = SUFFIX_RING_W) -> np.ndarray:
    """Host-side ring fill: the last ``width`` tokens of the emitted
    history (prompt + generated, ending with the pending token), -1
    padded on the left. The chain-fill input for the burst carry."""
    row = np.full(width, -1, np.int32)
    tail = list(tokens)[-width:]
    if tail:
        row[-len(tail):] = tail
    return row


def ring_push(ring: jax.Array, tokens: jax.Array,
              live: jax.Array) -> jax.Array:
    """Shift each LIVE row's ring left and append its new token."""
    shifted = jnp.concatenate(
        [ring[:, 1:], tokens[:, None].astype(ring.dtype)], axis=1
    )
    return jnp.where(live[:, None], shifted, ring)


def suffix_hashes(ring: jax.Array) -> jax.Array:
    """[B, STOP_SEQ_MAX_LEN + 1] rolling hashes of the ring's trailing
    suffixes: column L is the hash of the last L tokens (column 0 = 0).
    Unrolled over the (small, static) max length — pure vector ops."""
    b, w = ring.shape
    toks = (ring.astype(jnp.uint32) + jnp.uint32(1))
    cols = [jnp.zeros((b,), jnp.uint32)]
    p_pow = jnp.uint32(1)
    for ell in range(1, STOP_SEQ_MAX_LEN + 1):
        cols.append(cols[-1] + toks[:, w - ell] * p_pow)
        p_pow = p_pow * _HASH_P
    return jnp.stack(cols, axis=1)


def stop_candidate_mask(
    ring: jax.Array,       # [B, W] trailing tokens INCLUDING this step's
    gen: jax.Array,        # [B] generated count including this token
    min_new: jax.Array,    # [B] min_tokens (suppresses stops below)
    stop_hash: jax.Array,  # [B, STOP_SEQ_WIDTH] uint32 target hashes
    stop_len: jax.Array,   # [B, STOP_SEQ_WIDTH] i32 lengths (0 = unused)
) -> jax.Array:
    """Per-row stop-STRING candidate verdict for one step: any watched
    sequence whose length-L suffix hash matches, gated so the whole
    suffix is generated output (gen >= L) and min_tokens is satisfied."""
    hs = suffix_hashes(ring)                              # [B, L+1]
    sel = jnp.take_along_axis(
        hs, jnp.clip(stop_len, 0, STOP_SEQ_MAX_LEN), axis=1
    )                                                     # [B, NS]
    cand = (
        (stop_len > 0)
        & (gen[:, None] >= stop_len)
        & (gen[:, None] >= min_new[:, None])
        & (sel == stop_hash)
    )
    return cand.any(axis=1)


# alternatives returned with every step — covers OpenAI's top_logprobs
# (≤ 20); a fixed width keeps the step program's shapes static
TOP_LOGPROBS_K = 20


def top_k_width(vocab_size: int) -> int:
    """The step program's top-logprobs width: lax.top_k(k) requires
    k <= vocab (tiny test vocabs would otherwise fail outright)."""
    return min(TOP_LOGPROBS_K, vocab_size)


def top_logprobs_for(logits: jax.Array, logp: Optional[jax.Array] = None) -> tuple:
    """(values [B, K], ids [B, K]) of the K most likely tokens per row.

    Pass ``logp`` to reuse an already-computed log_softmax (the step
    program shares it with the chosen-token logprob)."""
    if logp is None:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    vals, ids = jax.lax.top_k(logp, top_k_width(logits.shape[-1]))
    return vals, ids.astype(jnp.int32)


# ---- a block pass (a family whose decode unit is a block) ----
#
# models.BlockUnit: a row's pending unit is L ids, each a token or the
# mask id. A pass samples every position of the block and then chooses
# which masked positions take their sample.

# a position's fold-in counter is its absolute position times this, plus
# the pass's number within its block (at most L + 1 <= 33 passes a block)
_PASS_STRIDE = 64


@jax.named_scope("sampling")
def sample_block_positions(cfg, logits: jax.Array, params: SamplingParams,
                           positions: jax.Array, want_top: jax.Array,
                           mask_id: int):
    """Sampling at every position of every row's block: ``logits``
    [R·L, V] in row-major order of ``positions`` [R, L], the row's
    sampling parameters at each of its positions (temperature, top-k,
    top-p and min-p; the penalties and the bias rows are refused for such
    a family). The mask id is never a prediction: its logit is -inf
    before sampling and before the log-softmax, so that a sampled token
    cannot read as still masked. Returns (tokens [R·L], their
    log-probabilities [R·L], top alternatives [R·L, K] twice, zeros
    unless ``want_top``)."""
    length = positions.shape[1]
    v = logits.shape[-1]
    # -inf is a bfloat16 too: the rows stay what ``head_dtype`` says
    head_dtype = logits.dtype
    logits = jnp.where(jnp.arange(v) == mask_id, -jnp.inf,
                       logits.astype(jnp.float32))
    at_position = jax.tree_util.tree_map(
        lambda x: jnp.repeat(x, length, axis=0), params)
    at_position = dataclasses.replace(
        at_position, counters=positions.reshape(-1) * _PASS_STRIDE
        + at_position.counters)
    tokens = sample(logits, at_position, head_dtype=head_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lps = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    kw = top_k_width(cfg.vocab_size)
    top_vals, top_ids = jax.lax.cond(
        want_top,
        lambda lp_: top_logprobs_for(logits, lp_),
        lambda lp_: (jnp.zeros((lp_.shape[0], kw), jnp.float32),
                     jnp.zeros((lp_.shape[0], kw), jnp.int32)),
        logp,
    )
    return tokens, lps, top_vals, top_ids


@jax.named_scope("block_select")
def block_select(ids: jax.Array, sampled: jax.Array, lps: jax.Array,
                 quota: jax.Array, unit):
    """Which masked positions of each row's block take their sample in
    this pass, by ``unit.strategy`` (models.REMASKING, the published
    rules), ``quota`` [R] of them a row (0: a row that holds nothing):

    - ``sequential``: the first ``quota`` masked positions, left to right;
    - ``low_confidence_static``: the ``quota`` masked positions of highest
      confidence (ties to the left);
    - ``low_confidence_dynamic``: every masked position whose confidence
      is over ``unit.threshold`` if there are at least ``quota`` of them,
      else as static.

    The confidence of a position is the probability of its sampled token,
    ``exp(lps)``, computed whatever the rule. ``ids``, ``sampled``,
    ``lps``: [R, L]. Returns (the block's new ids, the positions taken
    [R, L] bool, the count still masked [R])."""
    length = ids.shape[1]
    masked = ids == unit.mask_id
    confidence = jnp.where(masked, jnp.exp(lps), -1.0)
    offs = jnp.arange(length)
    order = (jnp.broadcast_to(-offs.astype(jnp.float32), confidence.shape)
             if unit.strategy == "sequential" else confidence)
    # a position's rank among its row's masked positions: how many of them
    # come before it in the order (a higher key, ties to the left)
    k_i, k_j = order[:, :, None], order[:, None, :]
    before = masked[:, None, :] & (
        (k_j > k_i) | ((k_j == k_i) & (offs[None, None, :] < offs[None, :, None])))
    taken = masked & (before.sum(-1) < quota[:, None])
    if unit.strategy == "low_confidence_dynamic":
        over = masked & (confidence > unit.threshold)
        taken = jnp.where((over.sum(-1) >= quota)[:, None], over, taken)
    new_ids = jnp.where(taken, sampled, ids)
    return new_ids, taken, (new_ids == unit.mask_id).sum(-1).astype(jnp.int32)
