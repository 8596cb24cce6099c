"""The served dots3-note path (latent layers of two kinds by
``layer_types``: full layers behind a learned indexer that picks
``index_topk`` keys, window layers with ranks, heads and a page shape of
their own whose pages are given back behind the window; a dense
feed-forward behind the first layer and routed experts held as one
rank's share behind the others) against the benchmark's plain reference,
``benchmark/references/dots3.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy.

Tiny ``dots3_note`` shape that keeps the ratios: the published order
``F F S S S F S S S``, 4 full heads of 16 + 8 over a latent of 16, 2
window heads of 24 + 8 over a latent of 32, an indexer of 4 heads of 16
that picks 32 keys, a window of 17 and pages of 16, so that a pick, a
released page and a page boundary all occur inside a hundred tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import deepseek, dots3
from dynamo_tpu.ops import latent_select

from dots3_tiny import (BF16_ATOL, BF16_MEDIAN, F32_ATOL, FULL,  # noqa: E402
                        HF, PAGE, SHARES, TOPK, WRONG, Served, _cfg, _params,
                        _reference_logprobs, _seqs, _serve_case, _share_of)

CASES = {
    # a prompt under the window and the pick in one chunk; decode across
    # the window's edge (17), a page's (16, 32, 48) and the pick's (32)
    "crosses_both_in_decode": dict(lengths=[12 + 50], n_decode=50, cuts=[],
                                   width=64),
    # prefill in three chunks cut off the page: the first under the pick,
    # the second across it, every chunk past the first releases; decode
    # picks 32 of 100-130 keys
    "three_chunks": dict(lengths=[100 + 30], n_decode=30, cuts=[40, 77],
                         width=64),
    # one chunk of two query blocks (width 512, QUERY_BLOCK 256): the
    # pick is a mask a query; a decode step that completes a page (400)
    "one_chunk_page_edge": dict(lengths=[390 + 12], n_decode=12, cuts=[],
                                width=512),
    # rows of different lengths, a pad row between them, slots that are
    # not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[20 + 6, 300 + 6, 150 + 6], n_decode=6,
                          cuts=[128, 256], width=128, slots=[2, 0, 3],
                          pad_row=True),
}


def _check_case(case, served, params, hf=HF):
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    slots = c.get("slots", list(range(len(seqs))))
    got = _serve_case(served, seqs, slots, c["n_decode"], c["cuts"],
                      c["width"], c.get("pad_row", False))
    for seq, lp in zip(seqs, got):
        np.testing.assert_allclose(lp, _reference_logprobs(params, seq, hf),
                                   rtol=0, atol=F32_ATOL)
    return c


@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case):
    """Chunked prefill in blocks, then decode through both pools and the
    indexer's cache, give the reference's full-forward log-softmax at
    every position, on both sides of ``index_topk`` and of the window; a
    window layer's row never holds more than its reckoned pages, and
    chunks past the window's first give pages back."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    c = _check_case(case, served, params)
    ec = EngineConfig(model=cfg, kv_block_size=PAGE,
                      prefill_buckets=[c["width"]],
                      max_prefill_tokens_per_step=c["width"])
    assert served.peak["decode"] <= ec.window_pages_a_row() == 3
    assert served.peak["prefill"] <= ec.window_pages_a_row(c["width"])
    if case == "three_chunks":
        assert all(n > 0 for n in served.released[1:3])
    assert sum(served.released) > 0


@pytest.mark.parametrize("picks", [False, True])
def test_bfloat16_served_path_stays_near_the_reference(picks):
    """bfloat16 weights, activations and pages of both kinds against the
    float32 reference on the same weights. With ``index_topk`` past the
    context (no pick) the difference is rounding's (medians of 0.2 are
    seen). With the pick, rounding moves a key or two a query across the
    cutoff, and of 32 keys under scores of deviation 3 one key can be
    most of what a head attends to: a median near 1.0 is seen at this
    size and single positions up to 5.7, so only the median is held (the
    chip's 2048 keys of 2200 and more read 0.08 on average: PERF.md
    section 6, PR 54)."""
    hf = HF if picks else {**HF, "index_topk": 4096}
    cfg, params = _params(jnp.bfloat16, hf)
    c = CASES["three_chunks"]
    seq = _seqs(c["lengths"], seed=len("three_chunks"))[0]
    got = _serve_case(Served(cfg, params, jnp.bfloat16), [seq], [0],
                      c["n_decode"], c["cuts"], c["width"])[0]
    worst = np.abs(got - _reference_logprobs(params, seq, hf)).max(axis=1)
    assert np.median(worst) < (1.6 if picks else BF16_MEDIAN)
    if not picks:
        assert np.max(worst) < BF16_ATOL


@pytest.mark.parametrize("rank", [0, 2])
def test_one_ranks_share_equals_the_reference_given_the_same_share(rank):
    """Four of the sixteen experts held: program and reference both route
    over sixteen and add the held experts' terms alone; the partial
    result goes on through the layers and the two still agree."""
    cfg = _cfg(SHARES[rank])
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (4, 16, rank)
    _, whole = _params(jnp.float32)
    params = _share_of(whole, rank)
    _check_case("three_chunks", Served(cfg, params, jnp.float32), params,
                hf=SHARES[rank])


def test_small_blocks_of_queries_and_keys(monkeypatch):
    """Four query blocks a chunk against key blocks of two pages: the
    running softmax across key blocks, the window's first block, the
    causal edge inside a block, and a table that is not whole blocks."""
    monkeypatch.setattr(latent_select, "QUERY_BLOCK", 32)
    monkeypatch.setattr(latent_select, "KEY_BLOCK", 48)
    cfg, params = _params(jnp.float32)
    _check_case("batch_unequal", Served(cfg, params, jnp.float32, fresh=True),
                params)


# every page no sequence holds, after every pass: a large finite value in
# the k side (the window kind's latents, the full kind's rows: a latent
# is the value too, and every route multiplies it by a weight of exactly
# 0) and NaN in the v side (the window kind's rope keys and, at every
# position of a slot past its tokens, the indexer's keys by slot, whose
# scores every route masks by a select)
POISON = (1e3, float("nan"))


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_a_released_page_is_never_read_unmasked(route, monkeypatch):
    """With every free page of both kinds (and the pages 0, which
    released table entries name) overwritten after each pass, the logits
    are still the reference's: the blocked prefill and the picked decode
    on both routes, the window layers' decode by the dense gather and by
    the latent kernel in the interpreter."""
    if route == "kernels":
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg, params = _params(
        jnp.float32, attention_impl="xla" if route == "xla" else "pallas")
    # a pool of exactly what the rows need: released pages are handed
    # out again at once, to the same row and to others
    served = Served(cfg, params, jnp.float32, poison=POISON,
                    pool_pages=1 + 3 * (2 + 128 // PAGE + 1))
    _check_case("three_chunks" if route == "kernels" else "batch_unequal",
                served, params)
    assert sum(served.released) > 0


def test_the_window_pool_gives_back_what_falls_behind():
    """``WindowPool`` counts: of the pages a sequence of 402 tokens took,
    all but the window's are back while it runs, and all when it ends."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    seq = _seqs([390 + 12], seed=3)[0]
    total = served.pool.available
    _serve_case(served, [seq], [1], 12, [128, 256, 384], 128)
    held = total - served.pool.available
    assert 2 <= held <= 3                       # a window of 17: two pages
    assert sum(served.released) >= 402 // PAGE - 2
    served.start(1)
    assert served.pool.available == total


# ---------- the indexer ----------

def _layer0(t=96, seed=11):
    """Layer 0's normed input and its arrays, one row of ``t`` tokens."""
    cfg, params = _params(jnp.float32)
    lp = {k: v[0] for k, v in params[FULL].items()}
    seq = _seqs([t], seed)[0]
    x = deepseek.rms_norm(params["embed"][jnp.asarray(seq)][None], lp["ln1"],
                          cfg.rms_norm_eps)
    return cfg, params, lp, seq, x


def test_the_picked_sets_equal_the_references():
    """The served indexer (its projections, the rotated front, the
    LayerNorm, the scaled weights, ReLU and the sort-free pick with the
    earliest of equal scores) picks, for every query of layer 0, exactly
    the keys the reference's ``lax.top_k`` picks: all of them up to
    ``index_topk`` keys, 32 after."""
    cfg, params, lp, seq, x = _layer0()
    t = len(seq)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    cq = mla_cq(cfg, lp, x, pos)
    qi, ki, wi = dots3.index_projections(cfg, lp, x, cq, pos)
    scores = latent_select.index_scores(qi, wi, ki)[0]           # [T, T]
    causal = np.tril(np.ones((t, t), bool))
    got = np.asarray(latent_select.pick_mask(scores, jnp.asarray(causal), TOPK))
    picks = []
    _reference_logprobs(params, seq, picked_out=picks)
    want = np.asarray(picks[0])[:t, :t]
    assert (got == want).all()
    assert (got.sum(1) == np.minimum(np.arange(t) + 1, TOPK)).all()
    assert not got[TOPK + 8:].all(axis=1).any()     # a pick, not a window
    assert len(picks) == HF["layer_types"].count(FULL)


def _projected(cfg, lp, x, pos):
    """``mla_project`` of one row as a full layer calls it: (the query's
    latent, q_nope, q_rope, the latent, the rotated key)."""
    sq, skv = dots3.lora_rescale(cfg)
    return deepseek.mla_project(cfg, x, lp, 1, x.shape[1], pos, q_scale=sq,
                                kv_scale=skv)


def mla_cq(cfg, lp, x, pos):
    return _projected(cfg, lp, x, pos)[0]


def test_pick_mask_keeps_k_whatever_ties_there_are():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.5, 3.0, 2.0, -1.0]])
    valid = jnp.asarray([[True] * 7 + [False]])
    got = np.asarray(latent_select.pick_mask(scores, valid, 3))[0]
    assert got.tolist() == [False, True, True, True] + [False] * 4
    got = np.asarray(latent_select.pick_mask(scores, valid, 5))[0]
    assert got.tolist() == [False, True, True, True, False, True, True, False]
    few = jnp.asarray([[True, True] + [False] * 6])
    assert np.asarray(latent_select.pick_mask(scores, few, 3))[0].tolist() == \
        [True, True] + [False] * 6
    cols, count = latent_select.picked_list(jnp.asarray(got)[None], 6)
    assert np.asarray(cols)[0].tolist() == [1, 2, 3, 5, 6, 0] and int(count[0]) == 5


def test_the_indexers_key_is_written_once_a_token_and_read_by_decode():
    """After a prefill of 70 tokens and 10 decode steps the indexer's
    records hold, in the sequence's slot, a key at each of its 80
    positions in every full layer and nothing behind them or in another
    slot, layer 0's the key ``index_projections`` gives; with the keys
    zeroed the next decode step picks other keys and says something
    else."""
    cfg, params, lp, seq, x = _layer0(t=81)
    served = Served(cfg, params, jnp.float32)
    _serve_case(served, [seq[:80]], [1], 10, [], 128)
    ki_all = np.asarray(served.cache[1].index)          # [3, slots, T, 128]
    held = ki_all[:, 1, :80]
    assert (np.abs(held).max(-1) > 0).all()
    assert not ki_all[:, 1, 80:].any() and not ki_all[:, [0, 2, 3]].any()
    pos = jnp.arange(81, dtype=jnp.int32)[None]
    _, ki, _ = dots3.index_projections(cfg, lp, x, mla_cq(cfg, lp, x, pos), pos)
    np.testing.assert_allclose(held[0, :, :16], np.asarray(ki)[0, :80],
                               atol=1e-5)
    before = served.cache
    sound = served.decode({1: (seq[80], 80)})[1]
    served.cache = (before[0], dataclasses.replace(
        before[1], index=jnp.zeros_like(before[1].index)))
    assert np.abs(served.decode({1: (seq[80], 80)})[1] - sound).max() > WRONG


def test_a_full_layers_row_holds_the_latent_then_the_rotated_key():
    """After a prefill of 70 tokens in two chunks and 10 decode steps a
    full layer's page holds, at each of the sequence's 80 positions, the
    token's row whole: the latent in the first ``kv_lora_rank`` lanes,
    the rotated key from ``lane_pad(kv_lora_rank)`` on (not from the
    rank: 16 is padded to 128 here), and zeros in every other lane, since
    decode's one score product sums over them; layer 0's the values
    ``mla_project`` gives."""
    cfg, params, lp, seq, x = _layer0(t=80)
    r, rd, lat = cfg.kv_lora_rank, cfg.qk_rope_head_dim, 128
    served = Served(cfg, params, jnp.float32)
    _serve_case(served, [seq], [1], 10, [40], 128)
    rows_all = np.asarray(served.cache[0].full)         # [3, N, 1, page, 256]
    assert rows_all.shape[-1] == lat + 128
    held = rows_all[:, served.btab[1, :80 // PAGE], 0].reshape(3, 80, -1)
    assert (np.abs(held[..., :r]).max(-1) > 0).all()
    assert (np.abs(held[..., lat:lat + rd]).max(-1) > 0).all()
    assert not held[..., r:lat].any() and not held[..., lat + rd:].any()
    assert not rows_all[:, served.btab[1, 80 // PAGE:]].any()
    c_kv, kr = _projected(cfg, lp, x,
                          jnp.arange(80, dtype=jnp.int32)[None])[3:]
    np.testing.assert_allclose(held[0, :, :r], np.asarray(c_kv)[0], atol=1e-5)
    np.testing.assert_allclose(held[0, :, lat:lat + rd], np.asarray(kr)[0],
                               atol=1e-5)


def test_the_steps_counters():
    """A decode step counts, for its live rows, the keys live and the
    ``min(keys, index_topk)`` a full layer attended to."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    a, b = _seqs([20 + 2, 60 + 2], seed=2)
    _serve_case(served, [a, b], [0, 2], 2, [], 64)
    kept, live, picked, steps = np.asarray(dots3.step_counts(served.cache))
    assert steps == 2 and live == 21 + 22 + 61 + 62
    assert kept == 21 + 22 + 2 * TOPK and picked == 2
    assert [n for n, _ in dots3.STEP_COUNTERS][:2] == [
        "dynamo_sparse_attention_kept_tokens_total",
        "dynamo_sparse_attention_context_tokens_total"]
