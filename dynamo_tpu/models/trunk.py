"""What the composed families share, written once: the two sides a cache
that is not a bare page stack is made of, the two walks over a trunk's
layers, and the ``forward`` over a ``forward_counted``.

A family whose layers are of more than one kind stacks its weights one
of two ways, and takes the walk that goes with it (``walk_kinds`` is
``walk_periods``' sibling for a trunk whose layers are one sublayer
each, no feed-forward behind a mixer: models/nemotron_h.py):

- **by kind** (``params[kind]``, ``params["dense"]``, ``params["moe"]``:
  models/kimi_linear.py, models/dots3.py): ``walk_periods``. The dense
  prefix is a body a layer; behind it the trunk is one ``lax.scan`` over
  *periods* (a run of the first kind, then a run of the second), each
  run a ``fori_loop`` of traced length over its kind's stack, so a
  program holds one body a kind whatever the pattern says.
- **by run** (``params["runs"]``, ``llama.layer_runs``: models/afmoe.py,
  models/granite_hybrid.py, models/minicpm_sala.py): ``walk_runs``, one
  ``lax.scan`` a homogeneous run over that run's stacked weights, the
  run's kind choosing the layer's body and the part of the cache that
  rides in the carry.

Either walk names no family and no kind: the family hands it its bodies
(which own the norms, the residual's dtype and the named scopes) and its
cache **in the order it is carried**. That order is part of the
program: a ``while``'s operands are flattened from the carry, and a
program's text is what ``scripts/layer_loop.py --hash`` compares.

The families with one kind of layer keep their own single scan
(``llama.run_layers`` and the staged families over it,
``falcon_h1.forward``): ROADMAP Design 4 (a).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..engine.config import ModelConfig
from .llama import layer_runs, lm_logits, rms_norm, swiglu_mlp

Params = Dict[str, Any]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SlotCache:
    """One side of the cache: the pages and, beside them, the records
    kept by slot."""
    kv: Any      # [L, N, block, KVH, D] pages, as llama's
    state: Any   # [L, slots, ...] one record a layer a slot

    @property
    def dtype(self):
        """The pages' element type: what a caller that asks a side of
        the cache for its dtype means (the records keep their own).
        benchmark/run.py reads ``runner.kv_cache[0].dtype``."""
        return self.kv.dtype

    @property
    def pages(self):
        return self.kv

    @property
    def rest(self):
        return self.state


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KindCache:
    """A side of the cache: the full layers' pages and the window
    layers', ``[layers of the kind, pages of its pool, block, KVH, D]``
    each, the page's shape the kind's own (models/mimo_v2.py: kv heads a
    kind, lanes a side); a kind's pages may be several such stacks
    (models/dots3.py, mimo_v2's keys)."""
    full: Any
    window: Any

    @property
    def dtype(self):
        """benchmark/run.py reads ``runner.kv_cache[0].dtype``."""
        return jax.tree.leaves(self.full)[0].dtype

    @property
    def pages(self):
        return self.full

    @property
    def rest(self):
        return self.window


def window_slots(window_table, positions, slot_mapping, block_size: int):
    """Where a window layer writes each token: the slot of its position
    in the page its own table names; -1 where the step writes nothing."""
    page = jnp.take_along_axis(window_table, positions // block_size, axis=1)
    return jnp.where(slot_mapping >= 0,
                     page * block_size + positions % block_size, -1)


def scaled(x: jax.Array, m) -> jax.Array:
    """``x · m`` with the product taken in float32 and rounded once: a
    multiplier rounded to bfloat16 first would be off by up to 0.4 %
    everywhere (the published code multiplies the same way)."""
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def layer_at(stack: Params, i) -> Params:
    """Layer ``i`` of a kind's stacked weights."""
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False), stack)


def period_layout(cfg: ModelConfig, pair):
    """(the dense prefix's layers [(kind, index among its kind, index
    among the dense)], the periods after it as four int32 vectors: the
    first layer of ``pair[0]``'s index among its kind and how many
    follow, the same of ``pair[1]``'s layers behind them). ``pair``: the
    two kinds a period is made of, in its order."""
    kinds = cfg.layer_types
    n_dense = min(cfg.first_k_dense_replace, len(kinds))
    base = {kind: 0 for kind in pair}
    prefix = []
    for i, kind in enumerate(kinds[:n_dense]):
        prefix.append((kind, base[kind], i))
        base[kind] += 1
    periods = []
    for kind, start, n in layer_runs(kinds[n_dense:]):
        if kind == pair[0] or not periods:   # the two kinds' runs alternate
            periods.append([0, 0, 0, 0])
        at = 0 if kind == pair[0] else 2
        periods[-1][at:at + 2] = [base[kind] + start, n]
    return prefix, [jnp.asarray(c, jnp.int32) for c in zip(*periods)]


def walk_periods(params: Params, cfg: ModelConfig, pair, mixer, experts,
                 hidden, cache):
    """A trunk stacked by kind, walked: -> (hidden, cache, int32 [3]:
    ``mixtral.routing_stats`` summed over the expert layers).

    ``mixer(kind, layer_params, hidden, cache, i) -> (hidden, cache)``:
    layer ``i`` of ``kind``'s mixer with its norm and its residual add.
    ``cache``: any pytree; it is carried as it is handed in.
    ``experts() -> (the expert layers' scanned group, moe_fn)``
    (``mixtral.split_expert_stacks``, ``make_moe_mlp_fn``), asked for
    behind the dense prefix and only where expert layers follow. Every
    feed-forward reads the residual through ``ln2`` in the weights'
    dtype, whatever the residual's own."""
    act = params["embed"].dtype

    def normed(hidden, weight):
        return rms_norm(hidden, weight, cfg.rms_norm_eps).astype(act)

    def mixed(kind, carry, i):
        hidden, cache, stats, fi = carry
        hidden, cache = mixer(kind, layer_at(params[kind], i), hidden, cache, i)
        return hidden, cache, stats, fi

    # (the expert stack's running index rides last)
    carry = (hidden, cache, jnp.zeros((3,), jnp.int32), jnp.int32(0))
    prefix, periods = period_layout(cfg, pair)
    for kind, i, di in prefix:      # the dense prefix: a body a layer
        hidden, *rest = mixed(kind, carry, i)
        lp = layer_at(params["dense"], di)
        with jax.named_scope("mlp"):
            hidden = hidden + swiglu_mlp(normed(hidden, lp["ln2"]), lp)
        carry = (hidden, *rest)

    if periods:
        moe, moe_fn = experts()

        def routed(kind, first):
            def layer(j, carry):
                hidden, cache, stats, fi = mixed(kind, carry, first + j)
                lp = layer_at(moe, fi)
                with jax.named_scope("mlp"):
                    y, aux = moe_fn(normed(hidden, lp["ln2"]), lp)
                return hidden + y, cache, stats + aux, fi + 1
            return layer

        def period(carry, p):
            a0, an, b0, bn = p
            for kind, first, n in ((pair[0], a0, an), (pair[1], b0, bn)):
                if kind in params:
                    carry = jax.lax.fori_loop(0, n, routed(kind, first), carry)
            return carry, None

        carry, _ = jax.lax.scan(period, carry, periods)
    hidden, cache, stats, _ = carry
    return hidden, cache, stats


def kind_periods(kinds, template):
    """``kinds`` (one a layer) as periods of ``template`` (the kinds in
    the order a period holds them): each period takes, kind by kind, as
    many of the next layers as are of that kind, none included. ->
    a row a period, ``(first, count)`` a kind of the template flattened:
    the kind's first layer of the period, counted among its kind, and
    how many follow. Any sequence of the template's kinds parses (at
    worst a layer a period)."""
    unknown = sorted(set(kinds) - set(template))
    if unknown:
        raise ValueError(f"layers of kinds {unknown}: not of {template}")
    seen = {kind: 0 for kind in template}
    periods, i = [], 0
    while i < len(kinds):
        row = []
        for kind in template:
            n = 0
            while i + n < len(kinds) and kinds[i + n] == kind:
                n += 1
            row += [seen[kind], n]
            seen[kind] += n
            i += n
        periods.append(row)
    return periods


def walk_kinds(kinds, template, stacks: Params, body, carry):
    """A trunk whose layers are one sublayer each, stacked by kind
    (``stacks[kind]``: models/nemotron_h.py), walked: -> carry.

    One ``lax.scan`` over the periods of ``template``
    (``kind_periods``); inside a period each kind's layers in turn, so a
    program holds one body a kind whatever the pattern says. A kind that
    has the same count in every period is unrolled in the period's body
    (``M E`` of a pattern ``M E M * E``: no loop around them); one whose
    count differs is a ``fori_loop`` of traced length over its stack
    (the ``*``: none or one). ``body(kind, layer_params, carry, i) ->
    carry``: layer ``i`` of ``kind``, with its norm, its residual add
    and its named scope; the carry is any pytree and is carried as it
    is handed in."""
    periods = kind_periods(kinds, template)
    if not periods:
        return carry
    columns = list(zip(*periods))

    def period(carry, p):
        for at, kind in enumerate(template):
            first, n = p[2 * at], p[2 * at + 1]

            def layer(j, carry, kind=kind, first=first):
                return body(kind, layer_at(stacks[kind], first + j), carry,
                            first + j)

            counts = set(columns[2 * at + 1])
            if len(counts) == 1:
                for j in range(counts.pop()):
                    carry = layer(j, carry)
            else:
                carry = jax.lax.fori_loop(0, n, layer, carry)
        return carry, None

    carry, _ = jax.lax.scan(
        period, carry, tuple(jnp.asarray(c, jnp.int32) for c in columns))
    return carry


def walk_runs(runs, stacked, layer_of, hidden, cache: Dict, stats=None):
    """A trunk stacked by run, walked: -> (hidden, cache, stats).

    ``runs``: ``llama.layer_runs``' (kind, the run's first index among
    the layers stacked in its kind's cache, its length) a run, beside
    ``stacked``, each run's stacked weights (``params["runs"]``).
    ``cache[kind]``: what a layer of the kind carries and hands on to
    the next run of its kind. ``layer_of(kind, run) -> (the group to
    scan, layer)``, asked for a run just before its scan, with ``layer``
    the scan's body: ``layer((hidden, own, li), layer_params) ->
    ((hidden, own, li + 1), aux)``, ``li`` the layer's index in its
    kind's cache. (A family that hands back one body for every run of a
    kind has it traced once, and its constants are in the program
    once.) ``stats``: the routing counters so far, to which a run adds
    its layers' ``aux`` where they return one (None for a trunk whose
    layers count nothing)."""
    for (kind, start, _), run in zip(runs, stacked):
        scanned, layer = layer_of(kind, run)
        (hidden, own, _), aux = jax.lax.scan(
            layer, (hidden, cache[kind], jnp.int32(start)), scanned)
        cache = {**cache, kind: own}
        if aux is not None:
            stats = stats + aux.sum(axis=0)
    return hidden, cache, stats


def forward_over(forward_counted, logits_from_hidden=lm_logits):
    """The ``forward`` ``ModelRunner`` asks of a family (models/__init__.py)
    over its ``forward_counted``: the hidden states, or the head on all
    of them."""

    def forward(
        params: Params,
        cfg: ModelConfig,
        tokens: jax.Array,        # [B, S]
        positions: jax.Array,     # [B, S]
        kv_cache,                 # init_kv_cache's pair
        block_tables: jax.Array,  # [B, W]; [B, 2 W] with a window pool
        slot_mapping: jax.Array,  # [B, S]; −1: no token here
        context_lens: jax.Array,  # [B]
        mesh=None,
        return_hidden: bool = False,
        state_slots=None,         # [B] each prefill row's slot; decode: row i
    ):
        hidden, cache, _ = forward_counted(
            params, cfg, tokens, positions, kv_cache, block_tables,
            slot_mapping, context_lens, mesh=mesh, state_slots=state_slots)
        if return_hidden:
            return hidden, cache
        with jax.named_scope("lm_head"):
            return logits_from_hidden(hidden, params, cfg), cache

    return forward
