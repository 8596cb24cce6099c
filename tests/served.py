"""One driver of a family's served path, for the reference tests
(``tests/test_*_reference.py``, ``test_dots3_family.py``,
``test_xing4_family.py``); not collected.

A reference test is a tiny published config, the benchmark's reference
module, the file's own tolerances and its deliberate faults; what every
such file otherwise repeated is here: the driver that runs a family's
``forward`` over a paged cache as the engine does (``Served``:
rows that name their slots, pad rows, idle rows; the slot table for a
family with records by slot, the second table and a real ``WindowPool``
for a family with a window pool), the case runner (``serve_case``,
``serve_chunks``), the sequences, the reference's log-probabilities and
the float32 / bfloat16 comparison.

**A family's programs are built once a process, not once a case.** The
jitted forward takes the weights as an argument (as ``ModelRunner``'s
programs do) and is kept by (family module, ``ModelConfig``, what else
selects a program: ``DYN_PALLAS_INTERPRET``); ``jax.jit`` keeps a
compiled program a shape and dtype under it, so the cases of a file
share the decode program and every prefill shape they have in common.
The reference's built function is kept by (reference module, config,
padded length, length, ``build``'s keywords). ``--dist loadfile`` keeps
a file on one worker, so a module-level cache is enough.

**A case that makes a wrong program passes ``fresh=True``** and neither
reads nor fills that cache: a fault that patches a module changes
nothing a key could see, and a fault case that silently ran the sound
program would pass nothing (``test_falcon_h1_reference.py::
test_a_fresh_program_is_traced_again_after_a_cached_run``).
"""

import dataclasses
import json
import os
import sys
import types
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu import models
from dynamo_tpu.engine.block_allocator import WindowPool
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.telemetry.registry import MetricsRegistry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:       # ``from references import <family>``
    sys.path.insert(0, BENCH)


def cfg_of(hf, **over):
    """The tiny config as the engine reads it, on the XLA route unless
    ``over`` says otherwise."""
    return dataclasses.replace(ModelConfig.from_hf_config(hf),
                               **{"attention_impl": "xla", **over})


def seqs(lengths, seed, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, vocab, n).tolist() for n in lengths]


_REFERENCES = {}


def reference_logprobs(reference, hf, params, seq, pad=8, **build):
    """The reference's log-probabilities at every position of ``seq``
    (padded to a multiple of ``pad``); ``build``: ``lower=`` where the
    reference has controls."""
    t_pad = -(-len(seq) // pad) * pad
    key = (reference.__name__, json.dumps(hf, sort_keys=True), t_pad, len(seq),
           tuple(sorted(build.items())))
    if key not in _REFERENCES:
        _REFERENCES[key] = reference.build(hf, t_pad, len(seq), **build)
    tokens = np.zeros(t_pad, np.int32)
    tokens[: len(seq)] = seq
    return np.asarray(_REFERENCES[key](params, jnp.asarray(tokens),
                                       jnp.arange(len(seq), dtype=jnp.int32)))


_PROGRAMS = {}


def program(family, cfg, fresh=False):
    """``fn(params, cache, tokens, positions, tables, slots, context_lens,
    state_slots) -> (logits, cache)``, jitted. ``fresh``: a program of
    its own, traced now from the modules as they are now."""
    def build():
        return jax.jit(
            lambda params, cache, tok, pos, bt, slot, ctx, ss: family.forward(
                params, cfg, tok, pos, cache, bt, slot, ctx, state_slots=ss))

    if fresh:
        return build()
    key = (family.__name__, repr(cfg), os.environ.get("DYN_PALLAS_INTERPRET"))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = build()
    return _PROGRAMS[key]


class Served:
    """A family's forward over a paged cache of ``slots`` slots, driven
    as the engine drives it: a prefill step's rows name their slots and
    may be fewer, padded or idle; a decode step has one row a slot.

    ``width`` pages of ``block`` tokens a slot, each slot's its own;
    ``spare``: page 0 is nobody's and an idle row's table points there
    (without one an idle row keeps its own pages). A family with a
    window pool (``SEQUENCE_STATE.window_pool``) gets the second table:
    the window kind's pages come from a real ``WindowPool`` through the
    scheduler's own ``_release_window`` and ``_take_window`` (called
    unbound on a stand-in that has what they read), released before
    every pass by that pass's first query. ``poison``: after every pass,
    every page that no sequence holds (both kinds' free pages and the
    two pages 0) and every position of a record by slot past its slot's
    tokens is filled with ``poison[0]`` in K and ``poison[1]`` in V.
    ``state_dtype``: the k side's records in another dtype, a
    deliberately wrong program. ``fresh``: ``program``'s."""

    def __init__(self, family, cfg, params, dtype, *, block, width, slots=4,
                 spare=True, poison=None, pool_pages=None, state_dtype=None,
                 fresh=False):
        self.family, self.cfg, self.params = family, cfg, params
        self.vocab, self.poison = cfg.vocab_size, poison
        self.block, self.width, self.slots = block, width, slots
        self.spare = int(spare)
        self.pages = slots * width + self.spare
        pool_pages = pool_pages or self.pages
        cache = family.init_kv_cache(cfg, self.pages, block, dtype,
                                     num_slots=slots, window_blocks=pool_pages)
        if state_dtype is not None:
            cache = (dataclasses.replace(
                cache[0], state=cache[0].state.astype(state_dtype)), cache[1])
        self.cache = cache
        self.btab = self.spare + np.arange(
            slots * width, dtype=np.int32).reshape(slots, width)
        self.fwd = program(family, cfg, fresh)
        self.window = getattr(family, "SEQUENCE_STATE",
                              models.PAGES_ONLY).window_pool
        if self.window:
            self.pool = WindowPool(pool_pages, MetricsRegistry())
            self.sched = types.SimpleNamespace(
                config=types.SimpleNamespace(model=cfg, kv_block_size=block),
                window=self.pool, passes=0,
                _host=types.SimpleNamespace(
                    wtab=np.zeros((slots, width), np.int32)))
            self.rows = [types.SimpleNamespace(slot=s, window_ids=deque(),
                                               window_first=0)
                         for s in range(slots)]
            self.peak = {"prefill": 0, "decode": 0}
            self.released = []                 # pages given back, a pass
        self.tokens = [0] * slots          # tokens of context written a slot

    def start(self, slot):
        """A new sequence in ``slot``: the old one's window pages go back."""
        if self.window:
            Scheduler._drop_window(self.sched, self.rows[slot])
            self.sched._host.wtab[slot] = 0
        self.tokens[slot] = 0

    def _window_pages(self, slot, first, last, phase):
        row, before = self.rows[slot], self.pool.available
        Scheduler._release_window(self.sched, row, first)
        freed = self.pool.available - before
        assert Scheduler._take_window(self.sched, row, last // self.block + 1)
        self.peak[phase] = max(self.peak[phase], len(row.window_ids))
        return freed

    def _page_slots(self, slot, positions):
        return (self.btab[slot, positions // self.block] * self.block
                + positions % self.block)

    def logprobs(self, logits):
        return np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32), -1))

    def _run(self, tok, pos, bt, slot, ctx, ss):
        logits, self.cache = self.fwd(
            self.params, self.cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(bt), jnp.asarray(slot), jnp.asarray(ctx),
            jnp.asarray(ss, jnp.int32))
        if self.poison is not None:
            self._poison()
        return self.logprobs(logits)

    def _poison(self):
        held = np.concatenate([self.btab[s, :-(-n // self.block)]
                               for s, n in enumerate(self.tokens)])
        free = {"full": np.setdiff1d(np.arange(self.pages), held),
                "window": np.asarray([0] + self.pool.free)}
        # records along a slot's positions (models/dots3.py's ``index``
        # [L, slots, T, d]): every position no token of the slot has
        past = (np.arange(self.cache[1].index.shape[2])[None]
                >= np.asarray(self.tokens)[:, None]
                if hasattr(self.cache[1], "index") else None)

        def by_slot(side, value):
            return {"index": jax.tree.map(
                lambda x: jnp.where(past[None, :, :, None], value, x),
                side.index)} if past is not None else {}

        # (a kind's pages may be several stacks: models/dots3.py)
        self.cache = tuple(
            dataclasses.replace(side, **by_slot(side, value), **{
                kind: jax.tree.map(lambda x: x.at[:, ids].set(value),
                                   getattr(side, kind))
                for kind, ids in free.items()})
            for side, value in zip(self.cache, self.poison))

    def tables(self, slot):
        if not self.window:
            return self.btab[slot]
        return np.concatenate([self.btab[slot], self.sched._host.wtab[slot]])

    def _idle_tables(self, b):
        return np.zeros((b, self.width * (2 if self.window else 1)), np.int32)

    def prefill(self, rows, width):
        """``rows``: (slot, tokens, start) or None for a pad row; each
        row's tokens sit at positions start.. and are padded to
        ``width``. Returns the log-softmax at every valid position."""
        b = len(rows)
        tok = np.zeros((b, width), np.int32)
        pos = np.zeros((b, width), np.int32)
        slot = np.full((b, width), -1, np.int32)
        bt = self._idle_tables(b)
        ctx, ss, freed = np.ones(b, np.int32), np.zeros(b, np.int32), 0
        for i, row in enumerate(rows):
            if row is None:
                continue
            s, toks, start = row
            n = len(toks)
            if self.window:
                freed += self._window_pages(s, start, start + n - 1, "prefill")
            self.tokens[s] = start + n
            tok[i, :n] = toks
            pos[i, :n], pos[i, n:] = np.arange(start, start + n), start + n - 1
            slot[i, :n] = self._page_slots(s, pos[i, :n])
            bt[i], ctx[i], ss[i] = self.tables(s), start + n, s
        if self.window:
            self.released.append(freed)
        lp = self._run(tok, pos, bt, slot, ctx, ss)
        return [None if r is None else lp[i, :len(r[1])]
                for i, r in enumerate(rows)]

    def decode(self, rows):
        """``rows``: {slot: (token, position)}; the other slots idle."""
        n = self.slots
        tok = np.zeros((n, 1), np.int32)
        pos = np.zeros((n, 1), np.int32)
        slot = np.full((n, 1), -1, np.int32)
        bt = self._idle_tables(n) if self.spare else self.btab.copy()
        freed = 0
        for s, (t, p) in rows.items():
            if self.window:
                freed += self._window_pages(s, p, p, "decode")
            self.tokens[s] = p + 1
            tok[s, 0], pos[s, 0], bt[s] = t, p, self.tables(s)
            slot[s, 0] = self._page_slots(s, np.asarray(p))
        if self.window:
            self.released.append(freed)
        lp = self._run(tok, pos, bt, slot, pos[:, 0] + 1, np.arange(n))
        return {s: lp[s, 0] for s in rows}

    def state(self):
        """The records by slot of the two sides, float32."""
        return (np.asarray(self.cache[0].state, np.float32),
                np.asarray(self.cache[1].state, np.float32))

    def counts(self):
        return np.asarray(self.family.step_counts(self.cache))


def serve_case(served, seqs, slots, n_decode, cuts, width, pad_row=False):
    """Prefill each sequence's prompt in chunks cut at ``cuts`` (shared
    boundaries, clipped to each prompt), all sequences as rows of the
    same steps, then decode ``n_decode`` teacher-forced tokens. Returns
    the log-softmax at every position of every sequence."""
    lens = [len(q) - n_decode for q in seqs]
    out = [np.zeros((len(q), served.vocab), np.float32) for q in seqs]
    for s in slots:
        served.start(s)
    edges = [0] + list(cuts) + [max(lens)]
    for lo, hi in zip(edges[:-1], edges[1:]):
        rows, who = [], []
        for i, q in enumerate(seqs):
            a, b = min(lo, lens[i]), min(hi, lens[i])
            if b > a:
                rows.append((slots[i], q[a:b], a))
                who.append((i, a, b))
        if pad_row:
            rows.insert(1, None)
            who.insert(1, None)
        for got, w in zip(served.prefill(rows, width), who):
            if w is not None:
                out[w[0]][w[1]:w[2]] = got
    for step in range(n_decode):
        got = served.decode({slots[i]: (q[lens[i] + step], lens[i] + step)
                             for i, q in enumerate(seqs)})
        for i in range(len(seqs)):
            out[i][lens[i] + step] = got[slots[i]]
    return out


def serve_chunks(family, cfg, params, prompts, n_decode, chunk, dtype,
                 block=8, fresh=False):
    """A family with one kind of page and nothing by slot: the prompts
    (each with its continuation) as the rows of one batch, a slot a
    prompt, prefilled in chunks of ``chunk`` tokens padded to it, then
    ``n_decode`` teacher-forced tokens a row."""
    served = Served(family, cfg, params, dtype, block=block, width=8,
                    slots=len(prompts), spare=False, fresh=fresh)
    longest = max(len(p) for p in prompts) - n_decode
    return serve_case(served, prompts, list(range(len(prompts))), n_decode,
                      list(range(chunk, longest, chunk)), chunk)


def assert_close(got, want, dtype, f32_atol, bf16_median=None, bf16_atol=None,
                 bf16_share=1.0):
    """``got`` and ``want``: a sequence's log-probabilities each, a list
    of them. float32: every entry within ``f32_atol``. bfloat16: of the
    positions' largest differences over the vocabulary, the median
    under ``bf16_median`` and ``bf16_share`` of them under
    ``bf16_atol``."""
    worst = []
    for lp, ref in zip(got, want):
        if jnp.dtype(dtype) == jnp.float32:
            np.testing.assert_allclose(lp, ref, rtol=0, atol=f32_atol)
        worst.extend(np.abs(lp - ref).max(axis=1))
    if jnp.dtype(dtype) == jnp.bfloat16:
        worst = np.asarray(worst)
        assert np.median(worst) < bf16_median
        assert np.mean(worst < bf16_atol) >= bf16_share
