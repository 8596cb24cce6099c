"""Fused Pallas sampling epilogue: the per-step decode tail in ONE kernel.

Every decode step ends with the same ladder (engine/model_runner.py
``_sample_and_logprobs`` + the chained burst's finish checks): penalty
application against the slot's generated-count/prompt-presence rows,
temperature + top-k / top-p / min-p filtering, the categorical draw, the
sampled token's logprob, the penalty-count commit, and — in the chained
burst — the device-finish verdict (eos/stop-id/max-token/model-len) and
the stop-string suffix-ring rolling hash. As XLA ops that tail is a
string of small [B, V] kernels dispatched between the forward and the
next step's launch; at chained-burst cadence the launch overhead of the
tail is a visible slice of inter-token latency. This kernel runs the
whole tail as one ``pallas_call`` over a batch-row grid.

Bit-identity is by CONSTRUCTION, not by tolerance: on each row the
kernel body runs the penalty ops of ``engine/sampling.sample`` in the
same order and f32 math, and then CALLS the filter that ``sample``
calls (``engine/sampling.filter_logits``: one values-only sort, the
top-k / min-p / top-p masks on the sorted row, a cutoff value, entries
tied with the cutoff all kept), and the categorical draw uses the identity
``jax.random.categorical(key, logits) == argmax(gumbel(key, shape) +
logits)`` (that IS jax's implementation) with the per-row gumbel noise
precomputed OUTSIDE the kernel from the same ``_row_keys`` fold-in. In
interpret mode the body lowers to the same XLA ops the dense ladder
runs, so the token/logprob stream is bit-equal — the differential test
asserts exact equality. The kernel does NOT lower for TPU yet (its
``(1, V)`` blocks break the (8, 128) block rule and the 1-D sort /
cumsum body has no Mosaic lowering — PERF.md kernel table), so
``fused_epilogue: auto`` never selects it; ``on`` is for CPU interpret
runs and raises the compiler's error on a chip.

The penalty-count commit writes through an aliased counts buffer whose
block index is the row's sample slot (scalar-prefetched). That in-place
form requires each grid step to own its output row, so it only engages
when the caller guarantees unique slots (``alias_counts=True`` — the
decode/burst paths, whose slots are ``arange``); the batched-prefill
step, whose pad rows share slot 0 with a potentially live row, keeps the
commit as a scatter-add outside the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..engine.sampling import _HASH_P, STOP_SEQ_MAX_LEN, filter_logits

LANE = 128


def _epilogue_kernel(
    slots_ref,     # scalar prefetch: sample slot per batch row [B] (SMEM)
    logits_ref,    # [1, V] the row's raw head logits
    bias_ref,      # [1, V] f32 — the slot's persistent logit_bias row
    *rest,
    v: int,
    max_model_len: int,
    has_extra: bool,
    with_finish: bool,
    alias_counts: bool,
    hash_p: int,
    max_suffix_len: int,
):
    if has_extra:
        extra_ref, *rest = rest
    gum_ref, fpar_ref, ipar_ref, cin_ref, seen_ref, *rest = rest
    if with_finish:
        sid_ref, ring_ref, shash_ref, slen_ref, *rest = rest
    if alias_counts:
        cout_ref, *rest = rest
    tok_ref, lp_ref, *rest = rest
    if with_finish:
        hard_ref, cand_ref, rout_ref = rest

    # ---- engine/sampling.sample on one row: its penalty ops mirrored,
    # its filter called ----
    raw = logits_ref[0].astype(jnp.float32)
    rb = bias_ref[0]
    if has_extra:
        rb = rb + extra_ref[0]
    logits = raw + rb

    cnt = cin_ref[0]
    generated = cnt > 0
    ever = generated | seen_ref[0]
    rp = fpar_ref[0, 5]
    logits = jnp.where(
        ever, jnp.where(logits > 0, logits / rp, logits * rp), logits
    )
    logits = logits - fpar_ref[0, 4] * cnt.astype(jnp.float32)
    logits = logits - fpar_ref[0, 3] * generated.astype(jnp.float32)

    greedy = jnp.argmax(logits)

    temp = jnp.maximum(fpar_ref[0, 0], 1e-6)
    scaled = logits / temp

    scaled = filter_logits(
        scaled, ipar_ref[0, 0], fpar_ref[0, 1], fpar_ref[0, 2]
    )

    # categorical(key, l) IS argmax(gumbel(key) + l); the gumbel row was
    # drawn outside from the identical _row_keys fold-in
    sampled = jnp.argmax(gum_ref[0] + scaled)
    nt = jnp.where(fpar_ref[0, 0] <= 0.0, greedy, sampled).astype(jnp.int32)

    # chosen-token logprob from the UNPENALIZED biased logits — the same
    # log_softmax the dense tail shares with its top-K branch
    lp = jax.nn.log_softmax(raw + rb)[nt]

    live = ipar_ref[0, 1] > 0
    if alias_counts:
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)[0] == nt
        ).astype(jnp.int32)
        cout_ref[0] = cnt + jnp.where(live, onehot, 0)

    tok_ref[0] = jnp.broadcast_to(nt, (LANE,))
    lp_ref[0] = jnp.broadcast_to(lp, (LANE,))

    if not with_finish:
        return

    # ---- device_finish_mask + ring_push + stop_candidate_mask ----
    gen_n = ipar_ref[0, 2] + ipar_ref[0, 1]
    pos = ipar_ref[0, 3]
    min_new = ipar_ref[0, 4]
    max_new = ipar_ref[0, 5]
    hit = (nt == sid_ref[0]).any()
    hard = ((gen_n >= min_new) & hit) | (gen_n >= max_new) | (
        pos + 2 >= max_model_len
    )
    hard_ref[0] = jnp.broadcast_to(hard.astype(jnp.int32), (LANE,))

    ring_row = ring_ref[0]
    shifted = jnp.concatenate([ring_row[1:], nt[None].astype(ring_row.dtype)])
    ring_n = jnp.where(live, shifted, ring_row)
    rout_ref[0] = ring_n

    # rolling polynomial suffix hashes, uint32 wraparound — the exact
    # arithmetic of sampling.suffix_hashes unrolled on one row
    w = ring_n.shape[0]
    toks_u = ring_n.astype(jnp.uint32) + jnp.uint32(1)
    hs = [jnp.uint32(0)]
    p_pow = jnp.uint32(1)
    for ell in range(1, max_suffix_len + 1):
        hs.append(hs[-1] + toks_u[w - ell] * p_pow)
        p_pow = p_pow * jnp.uint32(hash_p)
    hlen = slen_ref[0]                              # [NS] i32
    sel = jnp.zeros(hlen.shape, jnp.uint32)
    for ell in range(0, max_suffix_len + 1):
        sel = jnp.where(hlen == ell, hs[ell], sel)
    cand = (
        (hlen > 0)
        & (gen_n >= hlen)
        & (gen_n >= min_new)
        & (sel == shash_ref[0])
    ).any()
    cand_ref[0] = jnp.broadcast_to(cand.astype(jnp.int32), (LANE,))


def fused_sampling_epilogue(
    last_logits: jax.Array,   # [B, V] head output for the step
    gumbel: jax.Array,        # [B, V] f32 per-row gumbel noise (see above)
    samp_scalars: Tuple,      # (temperature, top_k, top_p, min_p,
                              #  presence, frequency, repetition) — [B] each
    counts: jax.Array,        # [num_slots, V] i32 generated-token counts
    seen: jax.Array,          # [num_slots, V] bool prompt presence
    bias: jax.Array,          # [num_slots, V] f32 logit_bias rows
    sample_slots: jax.Array,  # [B] i32 — each row's slot
    commit: jax.Array,        # [B] bool — live rows (gates the count
                              # commit, the ring push, and gen_n)
    extra_bias: Optional[jax.Array] = None,  # [B, V] in-program bias (guided)
    finish: Optional[Tuple] = None,
    # finish = (gen, pos, min_new, max_new, stop_ids, ring,
    #           stop_hash, stop_hlen) — the chained burst's carry rows
    max_model_len: int = 0,
    alias_counts: bool = True,
    interpret: bool = False,
):
    """One-dispatch decode tail. Returns ``(next_tokens [B] i32,
    lps [B] f32, counts)`` — plus ``(hard [B] bool, cand [B] bool,
    ring_new [B, W])`` when ``finish`` is given. Token/logprob values are
    bit-identical to the unfused ``sample`` + ``log_softmax`` ladder."""
    b, v = last_logits.shape
    ns = counts.shape[0]
    has_extra = extra_bias is not None
    with_finish = finish is not None
    temperature, top_k, top_p, min_p, presence, frequency, repetition = (
        samp_scalars
    )
    fpar = jnp.stack(
        [temperature, top_p, min_p, presence, frequency, repetition], axis=1
    ).astype(jnp.float32)
    icols = [top_k.astype(jnp.int32), commit.astype(jnp.int32)]
    if with_finish:
        gen, pos, min_new, max_new, stop_ids, ring, stop_hash, stop_hlen = (
            finish
        )
        icols += [gen.astype(jnp.int32), pos.astype(jnp.int32),
                  min_new.astype(jnp.int32), max_new.astype(jnp.int32)]
    ipar = jnp.stack(icols, axis=1)

    def row(i, s):
        return (i, 0)

    def slot_row(i, s):
        return (s[i], 0)

    in_specs = [
        pl.BlockSpec((1, v), row),                       # logits
        pl.BlockSpec((1, v), slot_row),                  # bias
    ]
    operands = [last_logits, bias]
    if has_extra:
        in_specs.append(pl.BlockSpec((1, v), row))
        operands.append(extra_bias)
    in_specs += [
        pl.BlockSpec((1, v), row),                       # gumbel
        pl.BlockSpec((1, fpar.shape[1]), row),           # fpar
        pl.BlockSpec((1, ipar.shape[1]), row),           # ipar
        pl.BlockSpec((1, v), slot_row),                  # counts
        pl.BlockSpec((1, v), slot_row),                  # seen
    ]
    operands += [gumbel.astype(jnp.float32), fpar, ipar, counts, seen]
    if with_finish:
        in_specs += [
            pl.BlockSpec((1, stop_ids.shape[1]), row),
            pl.BlockSpec((1, ring.shape[1]), row),
            pl.BlockSpec((1, stop_hash.shape[1]), row),
            pl.BlockSpec((1, stop_hlen.shape[1]), row),
        ]
        operands += [stop_ids, ring, stop_hash.astype(jnp.uint32),
                     stop_hlen.astype(jnp.int32)]

    out_shape, out_specs, aliases = [], [], {}
    if alias_counts:
        # flattened-operand index of counts: slots + logits + bias
        # [+ extra] + gumbel + fpar + ipar
        aliases[6 + int(has_extra)] = 0
        out_shape.append(jax.ShapeDtypeStruct((ns, v), counts.dtype))
        out_specs.append(pl.BlockSpec((1, v), slot_row))
    out_shape += [
        jax.ShapeDtypeStruct((b, LANE), jnp.int32),
        jax.ShapeDtypeStruct((b, LANE), jnp.float32),
    ]
    out_specs += [pl.BlockSpec((1, LANE), row), pl.BlockSpec((1, LANE), row)]
    if with_finish:
        out_shape += [
            jax.ShapeDtypeStruct((b, LANE), jnp.int32),
            jax.ShapeDtypeStruct((b, LANE), jnp.int32),
            jax.ShapeDtypeStruct((b, ring.shape[1]), ring.dtype),
        ]
        out_specs += [
            pl.BlockSpec((1, LANE), row),
            pl.BlockSpec((1, LANE), row),
            pl.BlockSpec((1, ring.shape[1]), row),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        functools.partial(
            _epilogue_kernel,
            v=v,
            max_model_len=max_model_len,
            has_extra=has_extra,
            with_finish=with_finish,
            alias_counts=alias_counts,
            hash_p=int(_HASH_P),
            max_suffix_len=STOP_SEQ_MAX_LEN,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # sequential grid: the aliased counts row of a pad row may
        # duplicate another row's slot; arbitrary (not parallel) order
        # keeps the read-modify-write of each block well-defined
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        input_output_aliases=aliases,
        interpret=interpret,
    )(sample_slots.astype(jnp.int32), *operands)

    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    if alias_counts:
        counts = outs.pop(0)
    nt = outs.pop(0)[:, 0]
    lps = outs.pop(0)[:, 0]
    if not alias_counts:
        counts = counts.at[sample_slots, nt].add(commit.astype(jnp.int32))
    if not with_finish:
        return nt, lps, counts
    hard = outs.pop(0)[:, 0] > 0
    cand = outs.pop(0)[:, 0] > 0
    ring_new = outs.pop(0)
    return nt, lps, counts, hard, cand, ring_new
