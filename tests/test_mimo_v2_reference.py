"""The served MiMo-V2 path (window and full attention layers by
``hybrid_layer_pattern`` whose kv-head counts differ, keys wider than
values, rotary on part of a head with a base a kind, a scale on the
values, a learned sink in the window layers in prefill as in decode;
each kind's pages in a pool and behind a table of its own; a dense
layer, then routed experts with no shared one; prefill in chunks, decode
one token a step) against the benchmark's plain reference,
``benchmark/references/mimo_v2.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy.

Tiny ``mimo_v2`` shape that keeps the ratios: 8 query heads over 2 kv
heads in a full layer and 4 in a window layer, keys of 24 (8 rotated)
and values of 16, the layers ``F | S S S S F S`` with layer 0 dense, 8
experts of which 2 a token, a window of 32 tokens (two pages), so that a
context of a few hundred tokens is several windows long and every chunk
and every page of decode releases.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import mimo_v2, mixtral, trunk

import served  # noqa: E402  (puts benchmark/ on the path)
from references import mimo_v2 as reference  # noqa: E402

WINDOW = 32
HF = {
    "architectures": ["MiMoV2ForCausalLM"], "model_type": "mimo_v2",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 7,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 24,
    "v_head_dim": 16, "swa_num_attention_heads": 8,
    "swa_num_key_value_heads": 4, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "sliding_window": WINDOW, "sliding_window_size": WINDOW,
    "attention_chunk_size": WINDOW, "rope_theta": 10000000,
    "swa_rope_theta": 10000,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "n_routed_experts": 8, "n_shared_experts": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": None,
    "layernorm_epsilon": 1e-5, "hidden_act": "silu",
    "attention_bias": False, "max_position_embeddings": 1024,
    "tie_word_embeddings": False,
}
PAGE = 16
SLOTS = 4
WIDTH = 32        # pages a sequence: 512 tokens
# float32 on both sides: the two differ in the order of the sums (a walk
# of pages against a masked product, sorted rows of experts against
# every expert on every token) and in nothing else; the smallest
# deliberate fault below reads over 1e-2
F32_ATOL = 1e-3


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, seed=7, hf=HF, **over):
    cfg = _cfg(hf, **over)
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(seed), dtype)
    # a window of 32 keys, not 128: the sink's logit where it takes the
    # same share of a full window's mass (SINK_MEAN is reckoned for 128)
    params[mimo_v2.WINDOW]["sinks"] = (
        params[mimo_v2.WINDOW]["sinks"] + np.log(WINDOW / 128.0))
    return cfg, params


def _reference_logprobs(params, seq, hf=HF, lower=()):
    return served.reference_logprobs(reference, hf, params, seq, pad=128,
                                     lower=lower)


def Served(cfg, params, dtype, **kw):
    """32 pages of 16 a slot behind page 0, which is nobody's; the
    window kind's pages from a real ``WindowPool`` (``served.Served``)."""
    return served.Served(mimo_v2, cfg, params, dtype, block=PAGE, width=WIDTH,
                         slots=SLOTS, **kw)


_seqs, _serve_case = served.seqs, served.serve_case


CASES = {
    # a prompt shorter than the window in one chunk, decode across the
    # window's edge (32) and two pages past it
    "crosses_the_window_in_decode": dict(lengths=[20 + 50], n_decode=50,
                                         cuts=[], width=64),
    # ten windows of prompt in five chunks, boundaries off the page of
    # 16, every chunk after the first releases; then 40 decode steps
    "five_chunks": dict(lengths=[330 + 40], n_decode=40,
                        cuts=[64, 100, 228, 292], width=128),
    # rows of different lengths, a pad row between them, slots that are
    # not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[40 + 6, 300 + 6, 150 + 6], n_decode=6,
                          cuts=[128, 256], width=128, slots=[2, 0, 3],
                          pad_row=True),
}


def _check_case(case, served, params, seed=None):
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case) if seed is None else seed)
    slots = c.get("slots", list(range(len(seqs))))
    got = _serve_case(served, seqs, slots, c["n_decode"], c["cuts"],
                      c["width"], c.get("pad_row", False))
    for seq, lp in zip(seqs, got):
        np.testing.assert_allclose(lp, _reference_logprobs(params, seq),
                                   rtol=0, atol=F32_ATOL)
    return c


@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case):
    """Chunked prefill, then decode through both pools, give the
    reference's full-forward log-softmax at every position; a window
    layer's row never holds more than its reckoned pages."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    c = _check_case(case, served, params)
    ec = EngineConfig(model=cfg, kv_block_size=PAGE,
                      prefill_buckets=[c["width"]],
                      max_prefill_tokens_per_step=c["width"])
    assert served.peak["decode"] <= ec.window_pages_a_row() == 3
    assert served.peak["prefill"] <= ec.window_pages_a_row(c["width"])
    assert sum(served.released) > 0


def test_a_page_has_its_kinds_heads_and_its_sides_lanes():
    """A stack's kv heads are its kind's, a side's lanes its own, and
    the keys are a stack a lane tile."""
    cfg = _cfg()
    k, v = mimo_v2.init_kv_cache(cfg, 10, PAGE, jnp.float32, window_blocks=5)
    assert [p.shape for p in k.full] == [(2, 10, PAGE, 2, 128)]
    assert [p.shape for p in k.window] == [(5, 5, PAGE, 4, 128)]
    assert v.full.shape == (2, 10, PAGE, 2, 128)
    assert v.window.shape == (5, 5, PAGE, 4, 128)
    wide = dataclasses.replace(cfg, head_dim=192, v_head_dim=128)
    k, v = jax.eval_shape(lambda: mimo_v2.init_kv_cache(
        wide, 10, PAGE, jnp.bfloat16, window_blocks=5))
    assert [p.shape[-2:] for p in k.full] == [(2, 128)] * 2
    assert [p.shape[-2:] for p in k.window] == [(4, 128)] * 2
    assert v.full.shape[-2:] == (2, 128) and v.window.shape[-2:] == (4, 128)
    assert k.dtype == v.dtype == jnp.bfloat16


# K and V of every page no sequence holds, after every pass: a large
# finite value in both; NaN in K where the route masks its scores by a
# select, a finite value in V, which every route multiplies by a weight
# of exactly 0
POISONS = {"finite": (1e3, 1e3), "nan_keys": (float("nan"), 1e3)}


@pytest.mark.parametrize("poison", list(POISONS))
@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_a_released_page_is_never_read_unmasked(route, poison, monkeypatch):
    """With every free page of both kinds (and both pages 0, which
    released table entries name) overwritten after each pass, the logits
    are still the reference's: on the XLA route, and on the decode and
    flash kernels in the interpreter (the flash kernel with the sink as
    its running softmax's first term, chunks of 64 and 128 queries)."""
    if route == "kernels":
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg, params = _params(
        jnp.float32, attention_impl="xla" if route == "xla" else "pallas")
    served = Served(cfg, params, jnp.float32, poison=POISONS[poison],
                    pool_pages=1 + 3 * (2 + 128 // PAGE + 1))
    case = "five_chunks" if route == "xla" else "crosses_the_window_in_decode"
    _check_case(case, served, params)
    if route == "xla":
        _check_case("batch_unequal", served, params)


def test_the_kernels_serve_chunked_prefill(monkeypatch):
    """Five chunks through the flash kernel in the interpreter (keys of
    128 lanes, values of 128, both kinds' kv heads, the sink), then
    decode through the decode kernel."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg, params = _params(jnp.float32, attention_impl="pallas")
    _check_case("five_chunks", Served(cfg, params, jnp.float32), params)


def test_bfloat16_served_path_stays_near_the_reference():
    """bfloat16 weights, activations and pages of both kinds (the router
    and every softmax float32) against the float32 reference on the same
    weights: a rounding-sized difference, far under what a wrong program
    reads."""
    cfg, params = _params(jnp.bfloat16)
    c = CASES["five_chunks"]
    seq = _seqs(c["lengths"], seed=3)[0]
    got = _serve_case(Served(cfg, params, jnp.bfloat16), [seq], [0],
                      c["n_decode"], c["cuts"], c["width"])[0]
    want = _reference_logprobs(params, seq)
    at = np.abs(got - want)[np.arange(len(seq)), want.argmax(axis=-1)]
    assert at.mean() < 0.2 and at.max() < 1.2


def test_the_sink_takes_a_tenth_to_a_half_of_a_full_window():
    """``init_params`` draws the sink's logit so that dropping it shows:
    over a full window of keys whose scores have deviation
    ``ATTN_SCORE_STD``, a sink at its mean takes a tenth to a half of
    the softmax's mass."""
    rs = np.random.RandomState(0)
    scores = mimo_v2.ATTN_SCORE_STD * rs.standard_normal((2000, 128))
    keys = np.exp(scores).sum(-1)
    share = np.exp(mimo_v2.SINK_MEAN) / (keys + np.exp(mimo_v2.SINK_MEAN))
    assert 0.1 < np.median(share) < 0.5


def _rounded_router(route):
    """The router with its scores from a bfloat16 product of bfloat16
    operands."""
    def route_top_k(x, router_w, *args, **kwargs):
        logits = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16))
        eye = jnp.eye(router_w.shape[1], dtype=jnp.float32)
        return route(logits.astype(jnp.float32), eye, *args, **kwargs)
    return route_top_k


def _wrong(fault, monkeypatch):
    """A served program with one line of the equations left out or
    changed; the reference keeps the right one."""
    cfg, params = _params(jnp.float32)
    served_params = params
    real_attention, real_rope = mimo_v2.attention, mimo_v2.apply_rope

    if fault == "no_sink":
        served_params = {**params, mimo_v2.WINDOW: {
            k: v for k, v in params[mimo_v2.WINDOW].items() if k != "sinks"}}
    elif fault == "no_sink_in_prefill":
        def attention(q, *a, sinks=None, **k):
            return real_attention(
                q, *a, sinks=sinks if q.shape[1] == 1 else None, **k)
        monkeypatch.setattr(mimo_v2, "attention", attention)
    elif fault == "rope_over_the_whole_head":
        monkeypatch.setattr(
            mimo_v2, "apply_rope",
            lambda x, pos, theta, rotary_dim=None: real_rope(x, pos, theta))
    elif fault == "one_theta_for_both_kinds":
        cfg = dataclasses.replace(cfg, swa_rope_theta=cfg.rope_theta)
    elif fault == "no_value_scale":
        cfg = dataclasses.replace(cfg, attention_value_scale=1.0)
    elif fault == "window_one_key_short":
        cfg = dataclasses.replace(cfg, sliding_window=WINDOW - 1)
    elif fault == "window_one_key_long":
        # (the pages are given back by the true window: the key one past
        # it is still on its page unless the page's edge is the window's)
        monkeypatch.setattr(
            mimo_v2, "attention",
            lambda *a, sliding_window=None, **k: real_attention(
                *a, sliding_window=(None if sliding_window is None
                                    else sliding_window + 1), **k))
    elif fault == "values_read_at_the_keys_width":
        # the output cut to the query's width, not the values': lanes 16
        # to 23 of a head are pad, and the heads then lie 24 apart
        def attention(q, *a, v_dim=None, **k):
            o = real_attention(q, *a, v_dim=None, **k)
            return o.reshape(o.shape[:2] + (-1,))[..., :q.shape[2] * v_dim
                                                  ].reshape(o.shape[:3] + (v_dim,))
        monkeypatch.setattr(mimo_v2, "attention", attention)
    elif fault == "kv_heads_swapped":
        # each kind's keys and values grouped as the other kind's heads
        # are: a full layer's 2 kv heads read as 4 of half the queries
        monkeypatch.setattr(
            mimo_v2, "kv_heads",
            lambda c, kind: (c.num_kv_heads if kind == mimo_v2.WINDOW
                             else c.swa_num_kv_heads))
        swap = {mimo_v2.FULL: 4, mimo_v2.WINDOW: 2}
        served_params = {**params, **{
            kind: {**params[kind],
                   "wk": _regroup(params[kind]["wk"], kvh, 24),
                   "wv": _regroup(params[kind]["wv"], kvh, 16)}
            for kind, kvh in swap.items()}}
    elif fault == "bfloat16_router":
        monkeypatch.setattr(mixtral, "route_top_k",
                            _rounded_router(mixtral.route_top_k))
    elif fault == "no_correction_bias_in_the_choice":
        served_params = {**params, "moe": {
            k: v for k, v in params["moe"].items() if k != "router_bias"}}
    return Served(cfg, served_params, jnp.float32, fresh=True), params


def _regroup(w, kvh, width):
    """A projection of another kv-head count from the same columns: the
    first ``kvh`` heads' worth, repeated or cut to ``kvh x width``."""
    n, d, cols = w.shape
    want = kvh * width
    return jnp.tile(w, (1, 1, -(-want // cols)))[:, :, :want]


FAULTS = ["no_sink", "no_sink_in_prefill", "rope_over_the_whole_head",
          "one_theta_for_both_kinds", "no_value_scale",
          "window_one_key_short", "window_one_key_long",
          "values_read_at_the_keys_width", "kv_heads_swapped",
          "bfloat16_router", "no_correction_bias_in_the_choice"]


@pytest.mark.parametrize("fault", FAULTS)
def test_reference_tells_wrong_programs_apart(fault, monkeypatch):
    served, params = _wrong(fault, monkeypatch)
    c = CASES["five_chunks"]
    seq = _seqs(c["lengths"], seed=5)[0]
    got = _serve_case(served, [seq], [0], c["n_decode"], c["cuts"], c["width"])[0]
    assert np.abs(got - _reference_logprobs(params, seq)).max() > 3 * F32_ATOL


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_a_control_moves_the_reference(control):
    """``build(lower=...)``: each named departure reads over the limit
    against the sound reference, on the same weights."""
    _, params = _params(jnp.float32)
    seq = _seqs([200], seed=9)[0]
    sound = _reference_logprobs(params, seq)
    assert np.abs(_reference_logprobs(params, seq, lower=(control,))
                  - sound).max() > 3 * F32_ATOL
    with pytest.raises(ValueError, match="lower"):
        reference.build(HF, 8, 8, lower=("weights",))


# ---------- one rank's share, and the shares add up ----------

SHARED = {**HF, "n_routed_experts": 2,
          "expert_share": {"of_experts": 8, "rank": 1}}


def _share_of(params, rank, held):
    keep = slice(held * rank, held * rank + held)
    return {**params, "moe": {
        k: (v[:, keep] if k in mixtral.EXPERT_STACKS else v)
        for k, v in params["moe"].items()}}


def test_one_ranks_share_equals_the_reference_given_the_same_share():
    """Rank 1 of 4 holds experts 2 and 3 of 8: the router stays 8 wide,
    the layer adds the held experts' terms alone, in the program and in
    the reference alike."""
    _, whole = _params(jnp.float32)
    cfg = _cfg(SHARED)
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (2, 8, 1)
    params = _share_of(whole, 1, 2)
    c = CASES["crosses_the_window_in_decode"]
    seq = _seqs(c["lengths"], seed=11)[0]
    got = _serve_case(Served(cfg, params, jnp.float32), [seq], [0],
                      c["n_decode"], c["cuts"], c["width"])[0]
    np.testing.assert_allclose(
        got, _reference_logprobs(params, seq, hf=SHARED), atol=F32_ATOL)
    # and the share is not the whole: the uncut reference reads elsewhere
    assert np.abs(got - _reference_logprobs(whole, seq)).max() > 3 * F32_ATOL


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """For a layer of 16 experts: the routed parts of the sixteen ranks
    that hold one expert each are the uncut reference's whole layer; in
    the reference given the shares, and in the program
    (``routed_experts(held=...)``) against the same uncut reference."""
    hf = {**HF, "n_routed_experts": 16, "num_experts_per_tok": 4}
    cfg = _cfg(hf)
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    lp = {k: v[1] for k, v in params["moe"].items()}          # one layer
    x = jax.random.normal(jax.random.PRNGKey(5), (48, cfg.hidden_size),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.expert_layer(hf)(x, lp))
        parts, got = [], []
        for rank in range(16):
            one = {**hf, "n_routed_experts": 1,
                   "expert_share": {"of_experts": 16, "rank": rank}}
            mine = {k: (v[rank:rank + 1] if k in mixtral.EXPERT_STACKS else v)
                    for k, v in lp.items()}
            parts.append(np.asarray(reference.expert_layer(one)(x, mine)))
            y, _ = mixtral.moe_mlp(
                x, lp["router"], *(lp[k][rank:rank + 1]
                                   for k in mixtral.EXPERT_STACKS),
                cfg.num_experts_per_tok, scoring=cfg.moe_scoring_func,
                norm_topk=cfg.norm_topk_prob,
                routed_scaling=cfg.routed_scaling_factor,
                router_bias=lp["router_bias"], held=(rank, 1))
            np.testing.assert_allclose(y, parts[-1], atol=1e-4)
            got.append(np.asarray(y))
    assert sum(np.abs(p).max() > 1e-3 for p in parts) >= 12    # most are picked
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)
    np.testing.assert_allclose(sum(got), want, atol=2e-4)


def test_the_value_scale_is_multiplied_in_float32_and_rounded_once():
    v = jax.random.normal(jax.random.PRNGKey(1), (4096,)).astype(jnp.bfloat16)
    want = (v.astype(jnp.float32) * 0.707).astype(jnp.bfloat16)
    got = trunk.scaled(v, 0.707)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
