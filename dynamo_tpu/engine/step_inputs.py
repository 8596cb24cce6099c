"""The step's per-pass inputs as ONE host array.

``ModelRunner.step`` hands ``jit_decode_step`` / ``jit_prefill_step``
everything that changes from pass to pass in one ``[B, F + W + 4·S]``
``int32`` array: one host→device put a token in place of twenty-one
(each of which the jitted call placed again on every chip of a tp mesh).
The compiled program's first lines take it apart again (``unpack``).

A row is one batch row, so the array shards on ``dp`` like the arrays it
replaces. The layout is a function of the shapes (``B``, ``S``, ``W``)
only:

    [0, F)            one word a row: the ``_INT`` columns, the two
                      ``uint32`` key words, the three step-wide flags
                      (the same word in every row, so that any shard
                      holds them), the six ``float32`` sampling
                      parameters as their bit patterns
    [F, F + W)        the block table
    [F + W, … + 4·S)  tokens, positions, slot_mapping, targets

A bit pattern crosses unchanged: ``ndarray.view`` on the host,
``lax.bitcast_convert_type`` on the device; no cast runs on the chip.

A decode step's token may be ``FED``: the host had not read the token
the step before sampled for that row when it packed this one, and the
program takes it from that step's ``next_tokens``, still on the device
(``ModelRunner._build_step``). No vocabulary has a negative id, so the
mark needs no column and the layout is what it was.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .sampling import SamplingParams

# in place of a decode row's token: "the one the step before sampled"
FED = -1

_INT = ("context_lens", "last_idx", "sample_slots", "counters", "commit",
        "top_k")
_KEYS = len(_INT)                      # two columns
_FLAGS = _KEYS + 2                     # want_top, want_prompt, want_greedy
_FLOAT = ("temperature", "top_p", "min_p", "presence_penalty",
          "frequency_penalty", "repetition_penalty")
_FLOATS = _FLAGS + 3
F = _FLOATS + len(_FLOAT)


class StepInputs(NamedTuple):
    """What ``unpack`` gives the traced step, dtypes as the step had them
    when each was an argument of its own."""

    tokens: jax.Array         # [B, S] i32
    positions: jax.Array      # [B, S] i32
    block_tables: jax.Array   # [B, W] i32
    slot_mapping: jax.Array   # [B, S] i32
    context_lens: jax.Array   # [B] i32
    last_idx: jax.Array       # [B] i32
    samp: SamplingParams
    sample_slots: jax.Array   # [B] i32
    commit: jax.Array         # [B] bool
    want_top: jax.Array       # [] bool
    targets: jax.Array        # [B, S] i32
    want_prompt: jax.Array    # [] bool
    want_greedy: jax.Array    # [] bool


# what ``ModelRunner.step`` fills in for a field its caller left ``None``
# (``sample_slots`` and ``counters`` default to the row's index); a field
# that is not here has no default
_DEFAULTS = {"min_p": 0.0, "presence_penalty": 0.0, "frequency_penalty": 0.0,
             "repetition_penalty": 1.0, "commit": False}


def pack(tokens, positions, block_tables, slot_mapping, targets=None, *,
         keys, want_top, want_prompt=False, want_greedy=False,
         **columns) -> np.ndarray:
    """Host side: a FRESH buffer a pass (a transfer still in flight never
    sees the scheduler's persistent arrays change). ``columns`` holds the
    ``_INT`` and ``_FLOAT`` fields by name, an array of ``[B]``, a scalar
    or ``None`` (its default) each; numpy converts to int32 / float32 as
    ``jnp.asarray(x, dtype)`` did."""
    b, s = tokens.shape
    w = block_tables.shape[1]
    buf = np.empty((b, F + w + 4 * s), np.int32)

    def column(name):
        value = columns.get(name)
        if value is not None:
            return value
        if name in ("sample_slots", "counters"):
            return np.arange(b)
        return _DEFAULTS[name]

    for c, name in enumerate(_INT):
        buf[:, c] = column(name)
    buf.view(np.uint32)[:, _KEYS:_FLAGS] = keys
    buf[:, _FLAGS:_FLOATS] = (want_top, want_prompt, want_greedy)
    as_f32 = buf.view(np.float32)
    for c, name in enumerate(_FLOAT, _FLOATS):
        as_f32[:, c] = column(name)
    buf[:, F:F + w] = block_tables
    for i, seq in enumerate((tokens, positions, slot_mapping,
                             0 if targets is None else targets)):
        buf[:, F + w + i * s:F + w + (i + 1) * s] = seq
    return buf


def unpack(buf: jax.Array, s: int) -> StepInputs:
    """Device side (traced): slices and bit-casts, nothing else."""
    w = buf.shape[1] - F - 4 * s
    col = {name: buf[:, c] for c, name in enumerate(_INT)}
    f32 = jax.lax.bitcast_convert_type(buf[:, _FLOATS:F], jnp.float32)
    col.update((name, f32[:, c]) for c, name in enumerate(_FLOAT))
    tokens, positions, slot_mapping, targets = (
        buf[:, F + w + i * s:F + w + (i + 1) * s] for i in range(4))
    # row 0 of the global array: every row carries the flags, so under
    # dp > 1 the partitioner has them on whichever shard it reads
    want_top, want_prompt, want_greedy = (
        buf[0, c] != 0 for c in range(_FLAGS, _FLOATS))
    return StepInputs(
        tokens=tokens, positions=positions, block_tables=buf[:, F:F + w],
        slot_mapping=slot_mapping, context_lens=col["context_lens"],
        last_idx=col["last_idx"],
        samp=SamplingParams(
            keys=jax.lax.bitcast_convert_type(
                buf[:, _KEYS:_FLAGS], jnp.uint32),
            counters=col["counters"], top_k=col["top_k"],
            **{name: col[name] for name in _FLOAT}),
        sample_slots=col["sample_slots"], commit=col["commit"] != 0,
        want_top=want_top, targets=targets, want_prompt=want_prompt,
        want_greedy=want_greedy,
    )
