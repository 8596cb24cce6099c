"""Batched in-jit sampling: greedy / temperature / top-k / top-p / min-p plus
presence, frequency and repetition penalties — all per slot.

All parameters are per-request arrays so one compiled program serves every
sampling configuration in the batch (no recompiles when requests differ).
temperature == 0 means greedy. Every request samples from its own PRNG key
(seeded requests are bit-reproducible and isolated from their batchmates —
reference surface: lib/llm/src/protocols/common.rs:248-316 SamplingOptions).

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
top-p, in float32. ``filter_logits`` does the last three with one
values-only sort and one cutoff value a row (no argsort, no [B, V] gather
or scatter); entries exactly equal to the cutoff are all kept, by top-p as
by top-k.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

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


def filter_logits(
    scaled: jax.Array,  # [..., V] f32 temperature-scaled logits
    top_k: jax.Array,   # [...] i32; 0 → disabled
    top_p: jax.Array,   # [...] f32; 1.0 → disabled
    min_p: jax.Array,   # [...] f32; 0.0 → disabled
) -> jax.Array:
    """top-k → min-p → top-p: ``scaled`` with every dropped entry at -inf.

    Each filter keeps a prefix of the row sorted by descending value, so
    the three together come to one number a row: the smallest kept
    logit. ONE values-only sort gives the sorted row; the masks, both
    softmaxes and the cumulative sum run on it in place; the cutoff is a
    masked min; and the mask in vocabulary order is ``scaled >= cutoff``.
    No argsort, no [B, V] gather and no [B, V] scatter, which a TPU does
    one element at a time (PERF.md §6, PR 24). Entries exactly equal to
    the cutoff are ALL kept, by top-p as by top-k.
    """
    v = scaled.shape[-1]
    # values alone, so stability means nothing, and asking for it makes
    # the compiler carry an iota through the sort: 1.05 against 0.61 ms
    # at [32, 32064] on a v5e
    sorted_desc = jnp.flip(jnp.sort(scaled, axis=-1, stable=False), axis=-1)

    # top-k: mask everything below the k-th largest (k=0 → no-op)
    k_idx = jnp.clip(top_k - 1, 0, v - 1)[..., None]
    kth = jnp.take_along_axis(sorted_desc, k_idx, axis=-1)
    kept = jnp.where(
        (top_k[..., None] > 0) & (sorted_desc < kth), -jnp.inf, sorted_desc
    )

    # min-p: drop tokens whose prob is below min_p * max_prob. Computed on
    # the already-top-k-masked logits, like the engines the reference wraps.
    probs = jax.nn.softmax(kept, axis=-1)
    kept = jnp.where(
        probs < min_p[..., None] * probs.max(axis=-1, keepdims=True),
        -jnp.inf, kept,
    )

    # top-p (nucleus): keep the prefix whose exclusive cumulative prob is
    # under p (so the top token always stays); entries top-k or min-p
    # dropped carry no probability and never set the cutoff
    probs = jax.nn.softmax(kept, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs < top_p[..., None]) & (kept > -jnp.inf)
    cutoff = jnp.min(jnp.where(keep, kept, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(scaled >= cutoff, scaled, -jnp.inf)


def sample(
    logits: jax.Array,  # [B, V] f32
    params: SamplingParams,
    counts: Optional[jax.Array] = None,   # [B, V] i32 generated-token counts
    seen: Optional[jax.Array] = None,     # [B, V] bool prompt-token presence
    bias: Optional[jax.Array] = None,     # [B, V] f32 OpenAI logit_bias rows
) -> jax.Array:
    """Returns sampled token ids [B]."""
    logits = logits.astype(jnp.float32)
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

    scaled = filter_logits(scaled, params.top_k, params.top_p, params.min_p)

    row_keys = _row_keys(params)
    sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(row_keys, scaled)
    return jnp.where(params.temperature <= 0.0, greedy, sampled).astype(jnp.int32)


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
