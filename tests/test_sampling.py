"""engine/sampling.sample against a plain NumPy reference of its documented
semantics, plus a structural guard on what the filter may cost.

The reference is written independently of ``sample``: float64, one row at
a time, sort INDICES, walk the cumulative sum, mask BY INDEX. ``sample``
does none of that (no sort at all: a cutoff value found by searching for
it, see ``filter_logits``), so agreement here is agreement of semantics. Float32
against float64 can only disagree where a decision sits on its threshold,
so the reference also returns how far every decision was from its
threshold and the cases assert that their data keeps clear of them.
"""

import collections
import dataclasses
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import sampling as S

_B = 6
_SAMPLE = jax.jit(S.sample)
_FILTER = jax.jit(S.filter_logits)

# one column a row of a batch: (temperature, top_k, top_p, min_p, presence,
# frequency, repetition)
_REGIMES = {
    "greedy": [(0.0, 0, 1.0, 0.0, 0.0, 0.0, 1.0)] * _B,
    "top_k": [(0.7, 1, 1.0, 0.0, 0.0, 0.0, 1.0),
              (1.0, 3, 1.0, 0.0, 0.0, 0.0, 1.0),
              (1.3, 5, 1.0, 0.0, 0.0, 0.0, 1.0),
              (0.9, 12, 1.0, 0.0, 0.0, 0.0, 1.0),
              (1.0, 16, 1.0, 0.0, 0.0, 0.0, 1.0),
              (1.0, 100000, 1.0, 0.0, 0.0, 0.0, 1.0)],   # k > V: no-op
    "top_p": [(0.7, 0, 0.9, 0.0, 0.0, 0.0, 1.0),
              (1.0, 0, 0.5, 0.0, 0.0, 0.0, 1.0),
              (1.3, 0, 0.95, 0.0, 0.0, 0.0, 1.0),
              (0.9, 0, 0.1, 0.0, 0.0, 0.0, 1.0),
              (1.0, 0, 0.99, 0.0, 0.0, 0.0, 1.0),
              (2.0, 0, 0.7, 0.0, 0.0, 0.0, 1.0)],
    "min_p": [(0.7, 0, 1.0, 0.05, 0.0, 0.0, 1.0),
              (1.0, 0, 1.0, 0.2, 0.0, 0.0, 1.0),
              (1.3, 0, 1.0, 0.01, 0.0, 0.0, 1.0),
              (0.9, 0, 1.0, 0.5, 0.0, 0.0, 1.0),
              (1.0, 0, 1.0, 0.9, 0.0, 0.0, 1.0),
              (2.0, 0, 1.0, 0.1, 0.0, 0.0, 1.0)],
    "all_three": [(0.7, 12, 0.9, 0.05, 0.0, 0.0, 1.0),
                  (1.0, 5, 0.8, 0.02, 0.0, 0.0, 1.0),
                  (1.3, 16, 0.95, 0.01, 0.0, 0.0, 1.0),
                  (0.9, 3, 0.5, 0.2, 0.0, 0.0, 1.0),
                  (1.0, 10, 0.6, 0.1, 0.0, 0.0, 1.0),
                  (2.0, 8, 0.7, 0.03, 0.0, 0.0, 1.0)],
    "penalties": [(0.7, 12, 0.9, 0.05, 0.5, 0.0, 1.2),
                  (1.0, 0, 0.8, 0.0, 0.0, 0.3, 1.0),
                  (1.3, 5, 1.0, 0.0, 1.1, 0.2, 1.05),
                  (0.0, 0, 1.0, 0.0, 0.7, 0.4, 1.3),
                  (1.0, 0, 1.0, 0.1, 0.0, 0.0, 1.5),
                  (0.9, 8, 0.7, 0.02, 0.4, 0.1, 0.8)],
    "mixed_rows": [(0.0, 0, 1.0, 0.0, 0.0, 0.0, 1.0),
                   (0.7, 5, 1.0, 0.0, 0.0, 0.0, 1.0),
                   (1.0, 0, 0.9, 0.0, 0.0, 0.0, 1.0),
                   (1.3, 0, 1.0, 0.2, 0.0, 0.0, 1.0),
                   (0.9, 10, 0.8, 0.05, 0.5, 0.3, 1.2),
                   (0.0, 7, 0.3, 0.4, 0.0, 0.0, 1.0)],
}


def _params(rows, keys, counters):
    cols = list(zip(*rows))
    f32 = lambda c: jnp.asarray(c, jnp.float32)
    return S.SamplingParams(
        temperature=f32(cols[0]), top_k=jnp.asarray(cols[1], jnp.int32),
        top_p=f32(cols[2]), min_p=f32(cols[3]), presence_penalty=f32(cols[4]),
        frequency_penalty=f32(cols[5]), repetition_penalty=f32(cols[6]),
        keys=jnp.asarray(keys, jnp.uint32),
        counters=jnp.asarray(counters, jnp.int32),
    )


def _logits(rng, v):
    """Rows shaped like a language model's: a bulk, and a head of tokens
    that carries the probability, its gaps wide beside float32's error."""
    x = rng.normal(size=(_B, v)) * 1.5
    for r in range(_B):
        h = int(min(v - 1, rng.integers(4, 24)))
        head = rng.choice(v, size=h, replace=False)
        x[r, head] += 6.0 + np.cumsum(rng.uniform(0.25, 0.9, size=h))
    return x.astype(np.float32)


def _gumbel(params, v):
    # jax.random.categorical(key, l) IS argmax(gumbel(key, l.shape) + l)
    return np.asarray(jax.vmap(
        lambda k: jax.random.gumbel(k, (v,), jnp.float32)
    )(S._row_keys(params)))


def _softmax64(x, alive):
    e = np.where(alive, np.exp(x - x[alive].max()), 0.0)
    return e / e.sum()


# how far a decision must sit from its threshold for float32 and float64
# to have to agree: a cumulative probability, a ratio to min_p, and the
# gap between the two best candidates of the draw
_CLEAR_CUM, _CLEAR_RATIO, _CLEAR_DRAW = 1e-5, 1e-4, 1e-3


def _reference_row(x, row, cnt, seen, gumbel):
    """One row of the documented semantics: penalties → temperature →
    top-k → min-p → top-p, ties with a cutoff all kept. Returns (scaled,
    kept by index, unsure by index, token, clear). ``unsure`` marks the
    entries whose cumulative probability is within ``_CLEAR_CUM`` of
    top_p: the last filter's decision on them is float32's to make (with
    top_p 1.0, "disabled", that is the far tail, whose exclusive sum
    rounds to 1). ``clear`` says that every other decision (min-p, whose
    outcome the nucleus is renormalised by, and the draw) sat clear of its
    threshold. A greedy row has no kept set."""
    temp, top_k, top_p, min_p, presence, frequency, repetition = row
    v = x.shape[0]
    generated = cnt > 0
    ever = generated | seen
    x = np.where(ever, np.where(x > 0, x / repetition, x * repetition), x)
    x = x - frequency * cnt - presence * generated
    if temp <= 0.0:
        top2 = np.sort(x)[-2:]
        return x, None, None, int(np.argmax(x)), top2[1] - top2[0] > _CLEAR_DRAW
    s = x / max(temp, 1e-6)
    order = np.argsort(-s, kind="stable")         # indices, descending
    ranked = s[order]
    alive = np.ones(v, bool)                      # by rank
    if top_k > 0:
        alive &= ranked >= ranked[min(top_k, v) - 1]
    clear = True
    if min_p > 0.0:
        ratio = _softmax64(ranked, alive)
        ratio = ratio / ratio.max()
        clear = np.abs(ratio[alive] / min_p - 1.0).min() > _CLEAR_RATIO
        alive &= ~(ratio < min_p)
    probs = _softmax64(ranked, alive)
    cum, last = 0.0, -1
    unsure_rank = np.zeros(v, bool)
    for i in range(v):                            # walk the cumulative sum
        if not alive[i]:
            break
        unsure_rank[i] = i > 0 and abs(cum - top_p) < _CLEAR_CUM
        if cum < top_p and last == i - 1:
            last = i
        cum += probs[i]
    while last + 1 < v and alive[last + 1] and ranked[last + 1] == ranked[last]:
        last += 1                                 # ties with the cutoff stay
    kept = np.zeros(v, bool)
    kept[order[: last + 1]] = True                # mask by index
    unsure = np.zeros(v, bool)
    unsure[order[unsure_rank]] = True
    drawn = np.where(kept | unsure, s + gumbel, -np.inf)
    token = int(np.argmax(drawn))
    top2 = np.sort(drawn)[-2:]
    clear = clear and not unsure[token] and top2[1] - top2[0] > _CLEAR_DRAW
    return s, kept, unsure, token, clear


def _reference(logits, bias, counts, seen, rows, gumbel):
    return [
        _reference_row(
            logits[r].astype(np.float64) + bias[r], rows[r], counts[r],
            seen[r], gumbel[r].astype(np.float64),
        )
        for r in range(len(rows))
    ]


def _case(regime, v, seed):
    rng = np.random.default_rng(seed)
    rows = _REGIMES[regime]
    logits = _logits(rng, v)
    bias = (rng.normal(size=(_B, v)) * 0.25).astype(np.float32)
    counts = rng.integers(0, 3, size=(_B, v)).astype(np.int32)
    seen = rng.integers(0, 2, size=(_B, v)).astype(bool)
    params = _params(rows, rng.integers(0, 2**32, size=(_B, 2)),
                     rng.integers(0, 1000, size=_B))
    return rows, logits, bias, counts, seen, params


@pytest.mark.parametrize("regime,v", [
    (regime, v) for regime in sorted(_REGIMES) for v in (17, 64, 32064)
] + [("top_p", 163840), ("all_three", 163840)])
def test_sample_matches_numpy_reference(regime, v):
    """The kept set and, with the same keys, the drawn token of every row
    agree with the reference, whatever its batchmates ask for."""
    rows, logits, bias, counts, seen, params = _case(regime, v, 24 + v)
    ref = _reference(logits, bias, counts, seen, rows, _gumbel(params, v))
    tokens = np.asarray(_SAMPLE(logits, params, counts, seen, bias))
    for r, (scaled, kept, unsure, token, clear) in enumerate(ref):
        # the case's data must sit clear of the thresholds, or float32
        # and float64 may fairly disagree: the cure is another seed, not
        # a tolerance
        assert clear, (regime, v, r)
        assert tokens[r] == token, (regime, v, r)
        if kept is None:
            continue
        got = np.asarray(_FILTER(
            jnp.asarray(scaled, jnp.float32)[None],
            params.top_k[r:r + 1], params.top_p[r:r + 1],
            params.min_p[r:r + 1],
        ))[0]
        sure = ~unsure
        assert sure[kept].sum() > 0 and unsure.sum() <= v // 2, (regime, v, r)
        np.testing.assert_array_equal(
            np.isfinite(got)[sure], kept[sure], err_msg=f"row {r}")
        np.testing.assert_array_equal(
            got[kept & sure], scaled.astype(np.float32)[kept & sure])


@pytest.mark.parametrize("top_k,top_p", [(2, 1.0), (0, 0.6)],
                         ids=["top_k", "top_p"])
def test_ties_at_the_cutoff_are_all_kept(top_k, top_p):
    """Rank 2 is the cutoff of both filters and three entries share its
    value: top-k 2 keeps all four, and so does a nucleus that ends inside
    the tie (0.5 + 0.1 ≥ 0.6 after the first of them)."""
    row = np.full(17, -30.0, np.float32)
    row[3] = np.log(0.5)
    row[[1, 8, 15]] = np.log(0.1)
    row[[2, 5]] = np.log(0.05)
    kept = np.isfinite(np.asarray(_FILTER(
        jnp.asarray(row)[None], jnp.asarray([top_k], jnp.int32),
        jnp.asarray([top_p], jnp.float32), jnp.zeros(1, jnp.float32),
    ))[0])
    assert sorted(np.flatnonzero(kept)) == [1, 3, 8, 15]
    # and the draw reaches every one of them, and nothing else: one key,
    # 64 counters, a row each
    n = 64
    drawn = _SAMPLE(
        jnp.tile(jnp.asarray(row), (n, 1)),
        _params([(1.0, top_k, top_p, 0.0, 0.0, 0.0, 1.0)] * n,
                [[7, 11]] * n, range(n)),
    )
    assert set(np.asarray(drawn).tolist()) == {1, 3, 8, 15}


def test_seeded_stream_repeats_and_ignores_batchmates():
    """The same key and counters give the same tokens twice, in another
    row of another batch, beside other requests with other filters."""
    v, steps = 64, 12
    rng = np.random.default_rng(7)
    mine = _logits(rng, v)[:1]
    row = (0.8, 10, 0.9, 0.02, 0.0, 0.0, 1.0)
    key = [123456789, 987654321]

    def stream(position, others_seed):
        others = np.random.default_rng(others_seed)
        logits = _logits(others, v)
        logits[position] = mine[0]
        rows = list(_REGIMES["mixed_rows"])
        rows[position] = row
        keys = others.integers(0, 2**32, size=(_B, 2))
        keys[position] = key
        out = []
        for step in range(steps):
            counters = others.integers(0, 1000, size=_B)
            counters[position] = step
            out.append(int(_SAMPLE(
                logits, _params(rows, keys, counters))[position]))
        return out

    first = stream(0, 1)
    assert first == stream(0, 1)
    assert first == stream(4, 2)
    assert len(set(first)) > 1          # a stream, not a constant


def _kept(row, top_k, top_p, min_p):
    got = np.asarray(_FILTER(
        jnp.asarray(row, jnp.float32)[None], jnp.asarray([top_k], jnp.int32),
        jnp.asarray([top_p], jnp.float32), jnp.asarray([min_p], jnp.float32),
    ))[0]
    kept = np.isfinite(got)
    np.testing.assert_array_equal(got[kept], np.asarray(row, np.float32)[kept])
    return sorted(np.flatnonzero(kept).tolist())


def _row_of(probs, at, v=17, fill=-np.inf, shift=0.0):
    row = np.full(v, fill, np.float64)
    row[list(at)] = np.log(probs) + shift
    return row


_EVERY = list(range(17))
# what a search for the cutoff can get wrong and a sort cannot:
# (row, top_k, top_p, min_p, the indices that stay)
_EDGES = {
    # one value, so every entry ties with the cutoff
    "equal_logits_top_p": (np.full(17, 0.3), 0, 0.5, 0.0, _EVERY),
    "equal_logits_top_k": (np.full(17, 0.3), 3, 1.0, 0.0, _EVERY),
    "equal_logits_min_p": (np.full(17, -4.0), 0, 1.0, 0.9, _EVERY),
    # -inf entries (a guided mask, logit_bias) lie below every threshold
    # and carry no mass: 0 < 0.7, 0.5 < 0.7, 0.8 >= 0.7
    "neg_inf_under_top_p": (
        _row_of([0.5, 0.3, 0.15, 0.05], [11, 2, 7, 14]), 0, 0.7, 0.0, [2, 11]),
    "neg_inf_under_top_k": (
        _row_of([0.5, 0.3, 0.15, 0.05], [11, 2, 7, 14]), 3, 1.0, 0.0,
        [2, 7, 11]),
    "neg_inf_top_k_past_the_finite": (
        _row_of([0.5, 0.3, 0.15, 0.05], [11, 2, 7, 14]), 9, 1.0, 0.0,
        [2, 7, 11, 14]),
    # the others' exp(x - max) is 0 in float32
    "one_token_holds_all_the_mass": (
        _row_of([1.0], [4], fill=-200.0, shift=50.0), 0, 0.9, 0.0, [4]),
    "one_token_holds_all_the_mass_no_filter": (
        _row_of([1.0], [4], fill=-200.0, shift=50.0), 0, 1.0, 0.0, _EVERY),
    # the top token stays though its probability alone is over top_p
    "top_p_under_the_top_probability": (
        _row_of([0.6, 0.2, 0.1, 0.1], [9, 0, 16, 3], fill=-30.0), 0, 0.3,
        0.0, [9]),
    # the image of a float32 changes its form at the sign: cutoffs on
    # either side of zero, and on it (0.0 and -0.0 are one value)
    "straddling_zero_top_k_cutoff_zero": (
        np.array([-1.5, 0.0, 1.5, -0.5, -0.0, 0.5, -2.5] + [-9.0] * 10),
        3, 1.0, 0.0, [1, 2, 4, 5]),
    "straddling_zero_top_k_cutoff_negative": (
        np.array([-1.5, 0.0, 1.5, -0.5, -0.0, 0.5, -2.5] + [-9.0] * 10),
        5, 1.0, 0.0, [1, 2, 3, 4, 5]),
    "straddling_zero_top_k_cutoff_positive": (
        np.array([-1.5, 0.0, 1.5, -0.5, -0.0, 0.5, -2.5] + [-9.0] * 10),
        2, 1.0, 0.0, [2, 5]),
    # log p + 1: 0.08, -0.20, -0.61, -1.30; 0 < 0.6, 0.4 < 0.6, 0.7 >= 0.6
    "straddling_zero_top_p": (
        _row_of([0.4, 0.3, 0.2, 0.1], [5, 12, 1, 8], shift=1.0), 0, 0.6, 0.0,
        [5, 12]),
    "straddling_zero_min_p": (
        _row_of([0.4, 0.3, 0.2, 0.1], [5, 12, 1, 8], shift=1.0), 0, 1.0, 0.6,
        [5, 12]),
    "top_k_equal_to_v": (np.linspace(-3.0, 3.0, 17), 17, 1.0, 0.0, _EVERY),
    "top_k_over_v": (np.linspace(-3.0, 3.0, 17), 1000, 1.0, 0.0, _EVERY),
    # no filter: every finite entry stays, those without mass in float32
    # and the most negative float32 too
    "no_filter_wide_range": (
        np.linspace(-300.0, 40.0, 17), 0, 1.0, 0.0, _EVERY),
    "no_filter_lowest_float": (
        np.array([np.finfo(np.float32).min, 3.0e38, -1e-45, 1e-45]
                 + [0.5] * 13), 0, 1.0, 0.0, _EVERY),
    "no_filter_with_neg_inf": (
        _row_of([0.5, 0.3, 0.15, 0.05], [11, 2, 7, 14]), 0, 1.0, 0.0,
        [2, 7, 11, 14]),
}


@pytest.mark.parametrize("case", sorted(_EDGES))
def test_filter_edges(case):
    row, top_k, top_p, min_p, stays = _EDGES[case]
    assert _kept(row, top_k, top_p, min_p) == stays


def test_filter_rows_do_not_see_each_other():
    """Every edge row at once, one batch, each with its own filters."""
    cases = [_EDGES[c] for c in sorted(_EDGES)]
    got = np.asarray(_FILTER(
        jnp.asarray(np.stack([c[0] for c in cases]), jnp.float32),
        jnp.asarray([c[1] for c in cases], jnp.int32),
        jnp.asarray([c[2] for c in cases], jnp.float32),
        jnp.asarray([c[3] for c in cases], jnp.float32),
    ))
    for r, c in enumerate(cases):
        assert sorted(np.flatnonzero(np.isfinite(got[r])).tolist()) == c[4], r


_REDUCTIONS = ("reduce_", "arg", "cum")


def _walk(jaxpr, times=1):
    """Every equation with the number of times it runs: a ``scan``'s body
    its length, a ``while``'s an unknown number (None)."""
    for eqn in jaxpr.eqns:
        yield eqn, times
        inner = times
        if eqn.primitive.name == "scan":
            inner = None if times is None else times * eqn.params["length"]
        elif eqn.primitive.name == "while":
            inner = None
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, inner)


# ---- the short search: bfloat16 rows with nothing added (PR 61) ----

_SHORT_V = 1031
_FILTER_SHORT = jax.jit(lambda *a: S.filter_logits(*a, short=True))
_FILTER_EITHER = jax.jit(lambda short, *a: S.filter_logits(*a, short=short))
# rows of bfloat16 values: what a search over sixteen bits has to get
# right that one over thirty-two gets right by brute force
_SHORT_ROWS = {
    "positive": lambda rng, x: np.abs(x) + 1.0,
    "negative": lambda rng, x: -np.abs(x) - 1.0,
    "mixed_signs": lambda rng, x: x,
    # a few dozen distinct values a row: the cutoff always sits in a tie
    "ties_at_the_cutoff": lambda rng, x: np.round(x * 2.0) / 2.0,
    "neg_inf_entries": lambda rng, x: np.where(
        rng.random(x.shape) < 0.3, -np.inf, x),
    # a block family's rows: the mask id is never a prediction
    "mask_id_at_neg_inf": lambda rng, x: np.where(
        np.arange(x.shape[1]) == x.shape[1] - 1, -np.inf, x),
    # subnormal and huge magnitudes on both sides of zero
    "wide_exponents": lambda rng, x: x * np.exp2(
        rng.integers(-140, 100, size=x.shape)),
}
_SHORT_REGIMES = dict(
    _REGIMES, top_p_one_and_over=[
        (0.7, 0, 1.0, 0.0, 0.0, 0.0, 1.0), (1.0, 5, 1.0, 0.0, 0.0, 0.0, 1.0),
        (1.3, 0, 1.5, 0.1, 0.0, 0.0, 1.0), (0.9, 0, 1.0, 0.2, 0.0, 0.0, 1.0),
        (1.0, 0, 0.999, 0.0, 0.0, 0.0, 1.0), (2.0, 3, 2.0, 0.0, 0.0, 0.0, 1.0)])
del _SHORT_REGIMES["penalties"]           # what takes a row out of the case


def _short_case(kind, regime):
    rng = np.random.default_rng(zlib.crc32(f"{kind}/{regime}".encode()))
    x = _SHORT_ROWS[kind](rng, rng.normal(size=(_B, _SHORT_V)) * 3.0)
    rows = [tuple(r[:4]) + (0.0, 0.0, 1.0) for r in _SHORT_REGIMES[regime]]
    params = _params(rows, rng.integers(0, 2**32, size=(_B, 2)),
                     rng.integers(0, 1000, size=_B))
    with np.errstate(over="ignore"):
        return jnp.asarray(x.astype(np.float32), jnp.bfloat16), params


@pytest.mark.parametrize("regime", sorted(_SHORT_REGIMES))
@pytest.mark.parametrize("kind", sorted(_SHORT_ROWS))
def test_short_search_keeps_the_full_searchs_set_and_draws_its_token(
        kind, regime):
    """On rows of bfloat16 values the search over the sixteen bits that
    can differ gives ``filter_logits`` the very array the search over
    all thirty-two gives it, known to the trace or told on the device,
    and ``sample`` the same tokens whether it is handed the head's
    bfloat16 (the short search by the trace), the same with a bias of
    zeros and no count (short, by the look of them), or the values as
    float32 (the full search, the program from before there were two)."""
    logits, params = _short_case(kind, regime)
    wide = logits.astype(jnp.float32)
    temp = jnp.maximum(params.temperature, 1e-6)
    filters = (wide / temp[:, None], params.top_k, params.top_p, params.min_p)
    full = np.asarray(_FILTER(*filters))
    for name, got in (
            ("trace", _FILTER_SHORT(*filters, temp)),
            ("device", _FILTER_EITHER(jnp.asarray(True), *filters, temp)),
            ("device, full", _FILTER_EITHER(jnp.asarray(False), *filters,
                                            temp))):
        np.testing.assert_array_equal(np.asarray(got), full, err_msg=name)
    assert np.isfinite(full).any(axis=1).all()      # the top token stays

    tokens = np.asarray(_SAMPLE(wide, params))
    np.testing.assert_array_equal(np.asarray(_SAMPLE(logits, params)), tokens)
    nothing = (jnp.zeros(wide.shape, jnp.int32),
               jnp.zeros(wide.shape, jnp.bool_), jnp.zeros_like(wide))
    np.testing.assert_array_equal(
        np.asarray(_SAMPLE(logits, params, *nothing)), tokens)
    np.testing.assert_array_equal(
        np.asarray(_SAMPLE(wide, params, *nothing)), tokens)


def _passes(monkeypatch, logits, params, *state, **kw):
    """The passes of the top-p search of one eager ``sample``: calls of
    ``_each`` but the one that sums the alive mass."""
    calls, each = [], S._each
    monkeypatch.setattr(
        S, "_each", lambda *a: calls.append(1) or each(*a))
    with jax.disable_jit():
        S.sample(logits, params, *state, **kw)
    return len(calls) - 1


_TOUCHES = {
    # (what goes with the bfloat16 logits, the search's passes)
    "nothing_handed_in": (None, 4),
    "zero_bias_neutral_penalties": ({}, 4),
    "guided_mask_of_neg_inf": ({"bias": -np.inf}, 4),
    "a_bias_entry": ({"bias": 0.3}, 8),
    "a_repetition_penalty": ({"repetition": 1.3}, 8),
    "a_frequency_penalty": ({"frequency": 0.2}, 8),
    "a_presence_penalty": ({"presence": 0.7}, 8),
}


@pytest.mark.parametrize("touch", sorted(_TOUCHES))
def test_search_length_follows_the_bits_that_differ(touch, monkeypatch):
    """Four passes where every row at hand is still the head's bfloat16
    (nothing handed in: known to the trace; a zero bias and neutral
    penalties, or a mask of -inf: looked up on the device), eight as soon as
    ONE entry of ONE row is a general float32, and the drawn tokens are
    those of the program handed the same values as float32, bit for bit.
    A float32 head searches eight passes whatever goes with it."""
    with_, passes = _TOUCHES[touch]
    logits, params = _short_case("mixed_signs", "top_p")
    wide, state = logits.astype(jnp.float32), ()
    if with_ is not None:
        counts = np.zeros(wide.shape, np.int32)
        counts[2, 5] = 2
        bias = np.zeros(wide.shape, np.float32)
        bias[4, 9] = with_.get("bias", 0.0)
        at_row_2 = lambda x, neutral: jnp.full(_B, neutral).at[2].set(x)
        params = dataclasses.replace(
            params,
            repetition_penalty=at_row_2(with_.get("repetition", 1.0), 1.0),
            frequency_penalty=at_row_2(with_.get("frequency", 0.0), 0.0),
            presence_penalty=at_row_2(with_.get("presence", 0.0), 0.0))
        state = (jnp.asarray(counts), jnp.zeros(wide.shape, jnp.bool_),
                 jnp.asarray(bias))
    assert _passes(monkeypatch, logits, params, *state) == passes
    assert _passes(monkeypatch, wide, params, *state) == 8
    assert _passes(monkeypatch, wide, params, *state,
                   head_dtype=jnp.bfloat16) == passes
    np.testing.assert_array_equal(
        np.asarray(_SAMPLE(logits, params, *state)),
        np.asarray(_SAMPLE(wide, params, *state)))


def test_short_search_is_for_bfloat16_on_one_device():
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:4])
    assert S.short_search(jnp.bfloat16)
    assert S.short_search(jnp.bfloat16, Mesh(devices[:1].reshape(1, 1),
                                             ("dp", "tp")))
    assert not S.short_search(jnp.bfloat16, Mesh(devices.reshape(1, 4),
                                                 ("dp", "tp")))
    assert not S.short_search(jnp.float32)
    assert not S.short_search(jnp.float16)      # eleven bits of fraction


# (rows, the live ones, those of them that are not plain, rows a tile,
#  rows counted): ``short_search_rows`` beside ``tiled_rows``
_SHORT_COUNTS = {
    "no_tiles_all_plain": (24, range(5), [], 0, 24),
    "no_tiles_one_touched": (24, range(5), [3], 0, 0),
    "no_tiles_nothing_live": (24, [], [], 0, 24),
    "listed_all_plain": (64, range(0, 60, 3), [], 16, 32),
    # 20 live rows by their list: 16 and 4 (and the pad); the 17th of them
    "listed_touched_in_the_last_tile": (64, range(0, 60, 3), [48], 16, 16),
    "listed_touched_in_the_first_tile": (64, range(0, 60, 3), [0], 16, 16),
    "listed_touched_in_both": (64, range(0, 60, 3), [0, 57], 16, 0),
    # past 48 of 64 live every row as it lies, four tiles
    "as_they_lie_all_plain": (64, range(60), [], 16, 64),
    "as_they_lie_one_tile_touched": (64, range(60), [17], 16, 48),
    # 72 rows: the fifth tile is moved back to rows 56-71 and counts 8
    "as_they_lie_moved_back_tile": (72, range(72), [60], 16, 48),
    "as_they_lie_touched_under_the_overlap": (72, range(72), [70], 16, 64),
}


@pytest.mark.parametrize("case", sorted(_SHORT_COUNTS))
def test_short_search_rows_counts_the_tiles_without_a_touched_row(case):
    r, live, touched, tile, counted = _SHORT_COUNTS[case]
    held, plain = np.zeros(r, bool), np.ones(r, bool)
    held[list(live)] = True
    plain[touched] = False
    assert S.short_search_rows(held, plain, tile) == counted
    run = S.tiled_rows(int(held.sum()), r, tile) if tile else r
    assert counted <= run
    if not touched:
        assert counted == run


@pytest.mark.parametrize("touched", ["none", "biased", "penalised"])
def test_tile_with_a_touched_row_agrees_with_the_full_search_bit_for_bit(
        touched):
    """The served tail over 72 rows in tiles of 16, bfloat16 logits, all
    rows plain but one (none; one with a bias row; one with penalties):
    every row's token and log-probability are those of the same tail
    handed the values as float32, which searches all 32 bits in every
    tile as the program did before: the plain tiles' rows draw the same
    tokens by the short search, and the tile that holds the touched row
    (which takes the full one) agrees bit for bit."""
    import types

    from dynamo_tpu.engine import model_runner as mr

    case = _tail_case()
    b, v, n = _TAIL_B, _TAIL_V, _TAIL_SLOTS
    rows = [(0.7, 0, 0.9, 0.0, 0.0, 0.0, 1.0)] * b
    bias = np.zeros((n, v), np.float32)
    at = 37
    if touched == "biased":
        bias[int(case["slots"][at])] = np.asarray(case["bias"][0])
    if touched == "penalised":
        rows[at] = (0.7, 0, 0.9, 0.0, 0.5, 0.3, 1.2)
    samp = dataclasses.replace(
        _params(rows, np.zeros((b, 2)), np.zeros(b)),
        keys=case["samp"].keys, counters=case["samp"].counters)
    live = jnp.ones(b, bool).at[::5].set(False)    # 57 live: by the list

    def run(logits):
        return jax.jit(lambda x: mr._sample_and_logprobs(
            types.SimpleNamespace(vocab_size=v), None, x, samp,
            case["counts"], case["seen"], jnp.asarray(bias), case["slots"],
            live, jnp.asarray(False), live=live)[:2])(logits)

    got, ref = run(case["logits"]), run(case["logits"].astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    assert len(set(np.asarray(got[0])[np.asarray(live)].tolist())) > 8


def test_sample_holds_one_body_a_search_whatever_its_length():
    """With bfloat16 logits and a bias the search's length is known on
    the device only, and it is the trip count of the ONE loop each search
    is: no ``cond`` between a short copy and a full one (a second body in
    every program's warm-up). Traced: one loop for top-k's search behind
    its gate and one for top-p's, the only loops that reduce over
    ``[b, v]``, fifteen reductions a pass each, one ``exp`` of the row in
    all of them; compiled for the CPU: as many ``while`` instructions
    as the program of a float32 head has, which knows one length."""
    b, v = 8, 512
    sd = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_map(
        lambda a: sd((b,) + a.shape[1:], a.dtype), S.SamplingParams.zeros(1))
    operands = (sd((b, v), jnp.bfloat16), params, sd((b, v), jnp.int32),
                sd((b, v), jnp.bool_), sd((b, v), jnp.float32))
    jaxpr = jax.make_jaxpr(S.sample)(*operands).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count("cond") == 1
    loops = [e for e, _ in _walk(jaxpr)
             if e.primitive.name in ("while", "scan")
             and any(x.primitive.name.startswith(_REDUCTIONS)
                     and x.invars[0].aval.size == b * v
                     for x, _ in _walk(e.params["body_jaxpr"].jaxpr
                                       if e.primitive.name == "while"
                                       else e.params["jaxpr"].jaxpr))]
    assert [e.primitive.name for e in loops] == ["while", "while"]
    for loop in loops:
        inside = list(_walk(loop.params["body_jaxpr"].jaxpr))
        assert not [e for e, _ in inside if e.primitive.name == "cond"]
        assert sum(1 for e, _ in inside
                   if e.primitive.name.startswith(_REDUCTIONS)
                   and e.invars[0].aval.size == b * v) == 15
    assert sum(1 for loop in loops
               for e, _ in _walk(loop.params["body_jaxpr"].jaxpr)
               if e.primitive.name == "exp"
               and e.outvars[0].aval.size == b * v) == 1
    whiles = [len(re.findall(r" while\(", jax.jit(S.sample).lower(
        sd((b, v), dtype), *operands[1:]).compile().as_text()))
        for dtype in (jnp.bfloat16, jnp.float32)]
    assert whiles[0] == whiles[1] >= 2, whiles



# the search settles 4 bits of the cutoff's image a pass with fifteen
# thresholds (one fusion on the chip): 8 passes x 15 reductions for top-p,
# as many for top-k behind its cond, and the row maximum, the alive mass,
# the greedy argmax and the draw's argmax beside them; 4 passes where the
# trace knows the rows for bfloat16 values
_MAX_FULL_REDUCTIONS = {"float32": 2 * 8 * 15 + 8, "bfloat16": 2 * 4 * 15 + 8}


@pytest.mark.parametrize("head", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,v", [(32, 32064), (64, 163840)])
def test_sample_sorts_nothing_and_reads_the_logits_a_bounded_number_of_times(
        b, v, head):
    """What made the pass 19 ms of every decode step on a v5e, and then
    6.6 ms at a 164 k vocabulary, cannot come back unseen by the CPU-only
    tests: at the served shapes ``sample`` traces to NO sort, to no gather
    that fetches and no scatter that writes as many elements as the
    logits have (a TPU does those one element at a time), and the
    reductions over the whole ``[b, v]`` array, each times the trips of
    the loops around it, stay under a stated number: of a float32 head
    with its penalty and bias rows, and of a bfloat16 head's logits alone
    (a block family's pass), half the passes. Trace only."""
    sd = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_map(
        lambda a: sd((b,) + a.shape[1:], a.dtype), S.SamplingParams.zeros(1))
    state = (sd((b, v), jnp.int32), sd((b, v), jnp.bool_),
             sd((b, v), jnp.float32)) if head == "float32" else ()
    jaxpr = jax.make_jaxpr(S.sample)(sd((b, v), jnp.dtype(head)), params,
                                     *state)
    full_reductions = 0
    for e, times in _walk(jaxpr.jaxpr):
        name = e.primitive.name
        assert name != "sort", e
        if name == "gather":
            _, indices = e.invars
            assert e.outvars[0].aval.size < b * v, e
            assert indices.aval.size < b * v, e
        if name.startswith("scatter"):
            assert e.invars[2].aval.size < b * v, e
        if name.startswith(_REDUCTIONS) and e.invars[0].aval.size >= b * v:
            assert times is not None, f"in a loop of unknown length: {e}"
            full_reductions += times
    assert (_MAX_FULL_REDUCTIONS[head] - 4 * 15 < full_reductions
            <= _MAX_FULL_REDUCTIONS[head]), full_reductions


# ---- the tail over the rows that hold a token (model_runner) ----

_TAIL_B, _TAIL_V, _TAIL_SLOTS = 72, 257, 80


def _tail_case():
    """A batch of 72 rows on 80 slots, every regime's rows in turn, each
    row with its own key, counter, penalty rows and bias row."""
    rng = np.random.default_rng(47)
    kinds = [r for name in ("mixed_rows", "penalties", "all_three", "greedy")
             for r in _REGIMES[name]]
    rows = [kinds[i % len(kinds)] for i in range(_TAIL_B)]
    b, v, n = _TAIL_B, _TAIL_V, _TAIL_SLOTS
    return dict(
        logits=jnp.asarray(rng.normal(size=(b, v)) * 3.0, jnp.bfloat16),
        samp=_params(rows, rng.integers(0, 2**32, size=(b, 2)),
                     rng.integers(0, 1000, size=b)),
        counts=jnp.asarray(rng.integers(0, 3, size=(n, v)), jnp.int32),
        seen=jnp.asarray(rng.integers(0, 2, size=(n, v)).astype(bool)),
        bias=jnp.asarray(rng.normal(size=(n, v)) * 0.3, jnp.float32),
        slots=jnp.asarray(rng.permutation(n)[:b], jnp.int32),
        extra=jnp.asarray(
            np.where(rng.random((b, v)) < 0.1, -1e9, 0.0), jnp.float32),
    )


def _tail(masked, guided):
    import types

    from dynamo_tpu.engine import model_runner as mr

    cfg = types.SimpleNamespace(vocab_size=_TAIL_V)

    def run(case, live, commit, want_top):
        return mr._sample_and_logprobs(
            cfg, None, case["logits"], case["samp"], case["counts"],
            case["seen"], case["bias"], case["slots"], commit, want_top,
            extra_bias=case["extra"] if guided else None,
            live=live if masked else None)

    return jax.jit(run)


@pytest.fixture(scope="module")
def tails():
    return {(masked, guided): _tail(masked, guided)
            for masked in (False, True) for guided in (False, True)}


@pytest.mark.parametrize("lie", ["scattered", "contiguous"])
@pytest.mark.parametrize("n", [0, 1, S.ROW_TILE - 1, S.ROW_TILE,
                               S.ROW_TILE + 1, 2 * S.ROW_TILE + 1,
                               _TAIL_B - S.ROW_TILE,
                               _TAIL_B - S.ROW_TILE + 1, _TAIL_B])
def test_tail_on_live_rows_is_the_tail_on_all_rows_bit_for_bit(tails, n, lie):
    """With the mask of rows whose token is read, the tail gives those
    rows the very tokens, log-probabilities and top alternatives it gives
    them without one, whichever tile a row lands in (none, one, the last
    of two or four, and in a fuller batch a tile of rows as they lie),
    zeros to the others, and counts the token of a committed row and of
    no other."""
    case = _tail_case()
    rng = np.random.default_rng(n)
    live = np.zeros(_TAIL_B, bool)
    start = int(rng.integers(0, _TAIL_B - n + 1))
    live[rng.permutation(_TAIL_B)[:n] if lie == "scattered"
         else np.arange(start, start + n)] = True
    commit = live & (rng.random(_TAIL_B) < 0.7)      # a burst's frozen rows
    for guided in (False, True):
        got = tails[True, guided](case, live, commit, True)
        ref = tails[False, guided](case, live, commit, True)
        for name, g, r in zip(("tokens", "lps", "top_vals", "top_ids"),
                              got, ref):
            np.testing.assert_array_equal(
                np.asarray(g)[live], np.asarray(r)[live], err_msg=name)
        assert not np.asarray(got[0])[~live].any()
        assert not np.asarray(got[1])[~live].any()
        added = np.asarray(got[4]) - np.asarray(case["counts"])
        want = np.zeros_like(added)
        np.add.at(want, (np.asarray(case["slots"])[commit],
                         np.asarray(ref[0])[commit]), 1)
        np.testing.assert_array_equal(added, want)


def test_decode_tail_runs_in_a_loop_over_tiles_with_a_traced_bound():
    """At a served shape the tail with a mask traces to one ``cond`` on
    how full the batch is, each branch one loop of tiles in which every
    reduction along the vocabulary sees a tile's rows: the live rows'
    tiles by their list, of unknown trip count, nothing gathered or
    scattered at the batch's size; or every row's, the rows gathered
    once as a pass over all rows gathers them. Both walks run the same
    operations on ``[T, V]``, so a row's sums do not depend on how full
    the batch is. Outside the two, and outside the gated
    top-alternatives branch, nothing reduces over ``[B, V]``. Without a
    mask the tail is no call and no loop: the pass over ``[B, V]`` as it
    lay in the program before. Trace only."""
    import types

    from dynamo_tpu.engine import model_runner as mr

    b, v, t = 64, 163840, S.ROW_TILE
    sd = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_map(
        lambda a: sd((b,) + a.shape[1:], a.dtype), S.SamplingParams.zeros(1))

    def traced(masked):
        return jax.make_jaxpr(
            lambda *a: mr._sample_and_logprobs(
                types.SimpleNamespace(vocab_size=v), None, *a[:-1],
                live=a[-1] if masked else None)
        )(sd((b, v), jnp.bfloat16), params, sd((b, v), jnp.int32),
          sd((b, v), jnp.bool_), sd((b, v), jnp.float32), sd((b,), jnp.int32),
          sd((b,), jnp.bool_), sd((), jnp.bool_), sd((b,), jnp.bool_)).jaxpr

    def reductions(jp):
        """(elements reduced, times or None) of every reduction in it."""
        return [(e.invars[0].aval.size, times) for e, times in _walk(jp)
                if e.primitive.name.startswith(_REDUCTIONS)]

    plain = traced(False)
    assert not [e for e in plain.eqns if e.primitive.name in ("jit", "pjit")
                and e.params["name"] == "_tail_ops"]
    # the top-k search's gate and want_top: none on how full the batch is
    assert [e.primitive.name for e in plain.eqns].count("cond") == 2
    assert max(size for size, _ in reductions(plain)) == b * v

    # the tiled tail is traced once for all of a process's decode
    # programs: a call of its own inside them
    (call,) = [e for e in traced(True).eqns
               if e.primitive.name in ("jit", "pjit")
               and e.params["name"] == "_tail_ops"]
    jaxpr = call.params["jaxpr"].jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2                      # the fill, and want_top
    outside = [e for e in jaxpr.eqns if e.primitive.name != "cond"]
    assert not [e for e in outside if e.primitive.name == "while"]
    assert all(e.invars[0].aval.size < b * v for e in outside
               if e.primitive.name.startswith(_REDUCTIONS))
    every, listed = (br.jaxpr for br in conds[0].params["branches"])
    bodies = []
    for walk in (every, listed):
        # one loop (a scan where the trip count is the batch's), and in
        # it the search on [T, V] alone
        (loop,) = [e for e in walk.eqns
                   if e.primitive.name in ("while", "scan")]
        assert (loop.primitive.name == "while") == (walk is listed)
        bodies.append(loop.params[
            "body_jaxpr" if walk is listed else "jaxpr"].jaxpr)
        inside = reductions(bodies[-1])
        assert inside and max(size for size, _ in inside) == t * v
        assert sum(1 for size, _ in inside if size == t * v) > 8
        assert not [1 for size, _ in reductions(walk) if size > t * v]
        for e, _ in _walk(walk):
            if e.primitive.name.startswith("scatter"):
                assert e.invars[2].aval.size <= t, e
        # and in a tile one loop a search, its length the tile's own (a
        # trip count read on the device): no branch between two copies
        searches = [e for e, _ in _walk(bodies[-1])
                    if e.primitive.name in ("while", "scan")]
        assert [e.primitive.name for e in searches] == ["while", "while"]
        assert [e.primitive.name for e, _ in _walk(bodies[-1])].count(
            "cond") == 1                        # the top-k search's gate
    # by the list: the tile's own rows are all that is ever gathered
    for e, _ in _walk(listed):
        if e.primitive.name == "gather":
            assert e.outvars[0].aval.size <= t * v, e
    # every row: the penalty and bias rows once, outside the loop
    assert sorted(e.outvars[0].aval.size for e in every.eqns
                  if e.primitive.name == "gather")[-3:] == [b * v] * 3
    assert not [e for e, _ in _walk(bodies[0])
                if e.primitive.name == "gather"
                and e.outvars[0].aval.size >= t * v]
    # and the two walks reduce the same things in the same order
    assert ([size for size, _ in reductions(bodies[0])]
            == [size for size, _ in reductions(bodies[1])])


@pytest.mark.parametrize("rows,devices", [(16, 1), (24, 1), (64, 4)])
def test_tail_that_walks_no_tiles_lowers_to_the_tail_without_a_mask(
        rows, devices):
    """Fewer rows than two tiles, or a mesh of several devices: the mask
    changes nothing, and the program holds the tail's operations as it
    held them before there was one (no call, no loop; the text is
    compared). Lowering only."""
    import types

    from jax.sharding import Mesh

    from dynamo_tpu.engine import model_runner as mr

    v = 4096
    mesh = None if devices == 1 else Mesh(
        np.array(jax.devices()[:devices]).reshape(1, devices), ("dp", "tp"))
    sd = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_map(
        lambda a: sd((rows,) + a.shape[1:], a.dtype),
        S.SamplingParams.zeros(1))
    operands = (sd((rows, v), jnp.bfloat16), params, sd((rows, v), jnp.int32),
                sd((rows, v), jnp.bool_), sd((rows, v), jnp.float32),
                sd((rows,), jnp.int32), sd((rows,), jnp.bool_),
                sd((), jnp.bool_), sd((rows,), jnp.bool_))

    def text(masked):
        return jax.jit(lambda *a: mr._sample_and_logprobs(
            types.SimpleNamespace(vocab_size=v), mesh, *a[:-1],
            live=a[-1] if masked else None)).lower(*operands).as_text()

    assert S.tile_rows(operands[0], mesh, operands[-1]) == 0
    assert text(True) == text(False)


_COLLECTIVE = re.compile(
    r" = (.*?) (all-reduce|all-gather|all-to-all|collective-permute"
    r"|reduce-scatter)(?:-start)?\(")
_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations"
    r"|true_computation|false_computation)=\{?([^,}\s][^}\s]*(?:, [^}\s]+)*)")
_Collective = collections.namedtuple(
    "_Collective", "kind elements op_name looped")


def _collectives(hlo):
    """Every collective of a compiled module: its kind, the elements of
    its result, the traced operation it came from, and whether it sits in
    a while loop's body or in a computation a body calls."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)

    def reach(k, seen):
        if k in seen or k not in comps:
            return seen
        seen.add(k)
        for line in comps[k]:
            for group in _CALLS.findall(line):
                for callee in group.split(", "):
                    reach(callee.lstrip("%"), seen)
        return seen

    looped = set()
    for body in re.findall(r"body=%?([\w.\-]+)", hlo):
        reach(body, looped)
    found = []
    for k, lines in comps.items():
        for line in lines:
            m = _COLLECTIVE.search(line)
            if m:
                shapes = re.findall(r"\w+\[([\d,]*)\]", m.group(1))
                op = re.search(r'op_name="([^"]*)"', line)
                found.append(_Collective(
                    m.group(2),
                    sum(int(np.prod([int(d) for d in dims.split(",") if d]))
                        for dims in shapes),
                    op.group(1) if op else "", k in looped))
    return found


def _tp4_step_collectives(dtype):
    """The collectives of the whole decode step of a small model at tp=4
    (four of the virtual devices), as the runner compiles it."""
    from dynamo_tpu.engine import model_runner as mr
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig

    b, v = 64, 4096
    runner = mr.ModelRunner(EngineConfig(
        model=ModelConfig(vocab_size=v, hidden_size=256,
                          intermediate_size=512, num_layers=2,
                          num_heads=8, num_kv_heads=4),
        max_batch_size=b, max_model_len=128, kv_block_size=8,
        num_kv_blocks=256, dtype=dtype, allow_random_weights=True,
        prefill_buckets=[8, 16], tp_size=4))
    step, called = runner._decode_step, {}
    runner._decode_step = lambda *args: called.setdefault(
        "out", step(*called.setdefault("args", args)))
    w = runner.config.kv_width_buckets()[0]
    zeros = lambda *shape: np.zeros(shape, np.int32)
    runner.step(
        zeros(b, 1), zeros(b, 1), zeros(b, w), zeros(b, 1),
        np.ones(b, np.int32), zeros(b), np.full(b, 0.7, np.float32),
        zeros(b), np.full(b, 0.9, np.float32),
        seed_keys=np.zeros((b, 2), np.uint32), counters=zeros(b),
        sample_slots=np.arange(b, dtype=np.int32), commit=np.ones(b, bool))
    hlo = step.lower(*jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        called["args"])).compile().as_text()
    return _collectives(hlo)


def test_decode_step_at_tp4_moves_the_logits_once():
    """The whole decode step of a small model at tp=4 (four of the virtual
    devices), as the runner compiles it: the head makes the logits with
    the vocabulary over "tp", the penalty state is replicated over it.
    The search reduces along the vocabulary in every pass, so ``sample``
    lays whole rows on a device; left to the partitioner the vocabulary
    stays sharded and each pass all-reduces. What the first form of that
    did on the chip is held off here too: whole rows, pinned at one end
    only, reached back into the head's product, which gathered its
    [hidden, V] weights on every device (2.5 ms of a Mistral step), and
    forward into the counts' update, which all-reduced [B, V].

    So: no collective that sampling makes sits inside a loop, and all the
    step's collectives together move under 1.5 B·V elements (one
    all-to-all of a device's logits, B·V / 4, and the top-logprobs
    branch's gather of B·V; the one-sort step this replaced moved 2.9
    B·V here, counted on its lowering of this same call)."""
    b, v = 64, 4096
    found = _tp4_step_collectives("float32")
    sampling = [c for c in found if "/sampling/" in c.op_name]
    assert sampling, "the all-to-all of the logits carries the scope"
    assert not [c for c in sampling if c.looped]
    assert any(c.looped for c in found)     # the layers' all-reduces are
    assert sum(c.elements for c in found) < 1.5 * b * v, found


def test_decode_step_at_tp4_of_a_bfloat16_head_has_no_new_collective(
        monkeypatch):
    """The same step of a bfloat16 model, whose head hands the tail
    bfloat16 logits: the search's length would be a trip count that four
    devices have to agree on, so on a mesh of several the rows keep the
    full search (``short_search``) and the compiled step holds the very
    collectives of the program that knows no short search at all, none of
    sampling's in a loop."""
    found = _tp4_step_collectives("bfloat16")
    assert not [c for c in found if "/sampling/" in c.op_name and c.looped]
    monkeypatch.setattr(S, "short_search", lambda *a: False)
    assert found == _tp4_step_collectives("bfloat16")
