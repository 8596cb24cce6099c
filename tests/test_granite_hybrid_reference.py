"""The served Granite 4.0-H path (layers that are a Mamba-2 mixer or
NoPE attention by ``layer_types``, routed experts and a shared expert
behind each; state by slot for the mixer layers only, pages for the
attention layers only; one expert-parallel rank's share of the experts)
against the benchmark's plain reference,
``benchmark/references/granite_hybrid.py`` — the same file the
benchmark's ``correct`` is decided by; there is no second copy.

Tiny ``granitemoehybrid`` shape that keeps the ratios: two runs of
mamba layers around an attention layer, 8 mixer heads in one group, 8
query heads over 2 kv heads, 8 experts top-3 and a shared expert, every
published multiplier (the softmax scale 1 / head_dim as published, not
its square root).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu import models
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import granite_hybrid, llama, mixtral
from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.live_rows import decode_live_rows

import served  # noqa: E402  (puts benchmark/ on the path)
from references import granite_hybrid as reference  # noqa: E402

BLOCK, SLOTS = 8, 4

HF = {
    "architectures": ["GraniteMoeHybridForCausalLM"],
    "model_type": "granitemoehybrid",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 24,
    "shared_intermediate_size": 48, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 32, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 8, "num_experts_per_tok": 3,
    "position_embedding_type": "nope", "normalization_function": "rmsnorm",
    "attention_bias": False, "hidden_act": "silu", "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "tie_word_embeddings": True,
    # the published multipliers of granite-4.0-h-small, the softmax
    # scale 1 / head_dim as there (1 / 128 at a head of 128)
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 16,
}
# rank ``r`` of two: four of the eight experts held
SHARES = {r: {**HF, "num_local_experts": 4,
              "expert_share": {"of_experts": 8, "rank": r}} for r in (0, 1)}
# float32 on both sides: the two differ in the order of the sums (the
# chunked form against the recurrence, paged against dense attention,
# sorted grouped products against every expert in turn) and in nothing
# else; differences seen are 1e-5 in log-probability at any position,
# and the smallest deliberate fault below (router logits from a
# bfloat16 product: the experts of a layer are one prototype and a
# spread, so a flipped near-tie moves little) reads 1.5e-3
F32_ATOL = 2e-4
WRONG = 1e-3
# bfloat16 weights, activations, pages and conv window (the SSM state and
# the router float32) against the float32 reference on the same bfloat16
# weights, the largest difference over the vocabulary at one position;
# at a hidden size of 64 rounding is coarser than on the chip
BF16_MEDIAN = 0.2
BF16_ATOL = 0.8


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, hf=HF, seed=7):
    cfg = _cfg(hf)
    return cfg, granite_hybrid.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _share_of(params, rank):
    """Rank ``rank``'s four experts of the uncut model's eight: the same
    weights, so that the shares can be added up."""
    keep = slice(4 * rank, 4 * rank + 4)
    runs = [{k: (v[:, keep] if k in mixtral.EXPERT_STACKS else v)
             for k, v in run.items()} for run in params["runs"]]
    return {**params, "runs": runs}


def _reference_logprobs(params, seq, hf=HF):
    """The reference's log-probabilities at every position of ``seq``."""
    return served.reference_logprobs(reference, hf, params, seq)


def Served(cfg, params, dtype, state_dtype=None, fresh=False):
    """48 pages of 8 a slot, every page a slot's own (as
    tests/test_falcon_h1_reference.py drives Falcon-H1)."""
    return served.Served(granite_hybrid, cfg, params, dtype, block=BLOCK, width=48,
                         slots=SLOTS, spare=False, state_dtype=state_dtype,
                         fresh=fresh)


_seqs, _serve_case = served.seqs, served.serve_case


CASES = {
    # one prefill, the whole prompt in one padded chunk
    "one_prefill": dict(lengths=[29 + 2], n_decode=2, cuts=[], width=32),
    # prefill in three chunks, boundaries off the scan's chunk of 32 and
    # off the page of 8
    "three_chunks": dict(lengths=[150 + 2], n_decode=2, cuts=[45, 101],
                         width=64),
    # prefill, then 40 decode steps through the state and the pages
    "decode_40": dict(lengths=[21 + 40], n_decode=40, cuts=[], width=32),
    # rows of different lengths, a pad row between them, slots that are
    # not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[5 + 6, 45 + 6, 19 + 6], n_decode=6,
                          cuts=[16, 32], width=16, slots=[2, 0, 3],
                          pad_row=True),
}


def _compare(case, dtype, hf, params_of=lambda p: p):
    dt = jnp.dtype(dtype)
    cfg = _cfg(hf)
    _, whole = _params(dt)
    params = params_of(whole)
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    slots = c.get("slots", list(range(len(seqs))))
    got = _serve_case(Served(cfg, params, dt), seqs, slots, c["n_decode"],
                      c["cuts"], c["width"], c.get("pad_row", False))
    served.assert_close(got, [_reference_logprobs(params, q, hf) for q in seqs],
                        dtype, F32_ATOL, BF16_MEDIAN, BF16_ATOL)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case, dtype):
    """Prefill, chunked prefill and decode through the state kept by
    slot and the attention layer's pages give the reference's
    full-forward log-softmax at every position, every expert held."""
    _compare(case, dtype, HF)


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("case", ["three_chunks", "batch_unequal"])
def test_one_ranks_share_equals_the_reference_given_the_same_share(case, rank):
    """Four of the eight experts held: the program and the reference
    both route over eight, weigh with the softmax over the three chosen
    and add the held experts' terms alone; the partial result goes on
    through the layers and the two still agree."""
    _compare(case, "float32", SHARES[rank],
             lambda whole: _share_of(whole, rank))


def test_resume_after_preemption_and_slot_reuse():
    """A sequence dropped after 10 decoded tokens and prefilled again
    from position 0 (prompt + the 10), into the slot another sequence
    has used meanwhile, continues as the reference says; the second
    user of a slot starts from zeros, not from what the first left."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    a, b = _seqs([17 + 30, 23 + 8], seed=4)
    want_a, want_b = _reference_logprobs(params, a), _reference_logprobs(params, b)
    got = _serve_case(served, [a[:27]], [1], 10, [], 32)[0]      # 17 + 10 tokens
    np.testing.assert_allclose(got, want_a[:27], atol=F32_ATOL)
    got = _serve_case(served, [b], [1], 8, [], 32)[0]   # b takes a's slot
    np.testing.assert_allclose(got, want_b, atol=F32_ATOL)
    got = _serve_case(served, [a], [1], 20, [], 32)[0]  # a again, from 0
    np.testing.assert_allclose(got, want_a, atol=F32_ATOL)


def _bf16_router(monkeypatch):
    route = mixtral.route_top_k

    def route_top_k(x, router_w, *args, **kwargs):
        logits = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16))
        eye = jnp.eye(router_w.shape[1], dtype=jnp.float32)
        return route(logits.astype(jnp.float32), eye, *args, **kwargs)

    monkeypatch.setattr(mixtral, "route_top_k", route_top_k)


def _rotary(monkeypatch):
    monkeypatch.setattr(
        granite_hybrid, "make_gqa_attn_fn",
        lambda *a, **kw: llama.make_gqa_attn_fn(*a, **{**kw, "rope": True}))


WRONG_PROGRAMS = {
    # the state held in bfloat16: the recurrence feeds its rounding back
    "bf16_state": dict(state_dtype=jnp.bfloat16),
    # the gate a softmax over all eight experts, not over the chosen three
    "gate_over_all": dict(cfg=dict(norm_topk_prob=False)),
    # the scores scaled by head_dim ** -0.5, not by the published 1 / head_dim
    "sqrt_scale": dict(cfg=dict(attention_multiplier=0.0)),
    # a rotary embedding on the attention layer's queries and keys
    "rotary": dict(patch=_rotary),
    # router logits from a bfloat16 product
    "bf16_router": dict(patch=_bf16_router),
    # the residual multiplier left out
    "no_residual_multiplier": dict(cfg=dict(residual_multiplier=1.0)),
}


@pytest.mark.parametrize("fault", list(WRONG_PROGRAMS))
def test_a_wrong_program_is_told_apart(fault, monkeypatch):
    """Each of these is a program that computes something else than the
    published equations; in float32 every one stands well clear of the
    sound program's agreement with the reference."""
    spec = WRONG_PROGRAMS[fault]
    cfg, params = _params(jnp.float32)
    cfg = dataclasses.replace(cfg, **spec.get("cfg", {}))
    if "patch" in spec:
        spec["patch"](monkeypatch)
    c = CASES["decode_40"]
    seq = _seqs(c["lengths"], seed=3)[0]
    served = Served(cfg, params, jnp.float32, spec.get("state_dtype"),
                    fresh=True)
    got = _serve_case(served, [seq], [0], c["n_decode"], c["cuts"], c["width"])[0]
    off = np.abs(got - _reference_logprobs(params, seq)).max()
    assert off > WRONG, off


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_the_references_controls_compute_below_the_stated_precision(control):
    """``build(lower=(name,))`` is what the chip's limits were set
    against (``scripts/long_probes.py --controls``): the same reference
    with one part in the precision below. Each differs from the
    reference and stays finite; built with none it is the reference."""
    _, params = _params(jnp.float32)
    seq = _seqs([150], seed=11)[0]
    tokens = jnp.asarray(np.asarray(seq + [0] * 2, np.int32))
    at = jnp.arange(len(seq), dtype=jnp.int32)
    want = np.asarray(reference.build(HF, 152, 150)(params, tokens, at))
    same = np.asarray(reference.build(HF, 152, 150, lower=())(params, tokens, at))
    np.testing.assert_array_equal(same, want)
    got = np.asarray(reference.build(HF, 152, 150, lower=(control,))(
        params, tokens, at))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() > 10 * F32_ATOL


def test_the_reference_refuses_a_control_it_does_not_have():
    with pytest.raises(ValueError, match="lower="):
        reference.build(HF, 8, 8, lower=("weights",))


# ---------- the shares add up ----------

def _layer_inputs(seed=5, t=24):
    cfg, params = _params(jnp.float32)
    run = {k: v[1] for k, v in params["runs"][0].items()}     # one layer
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, cfg.hidden_size),
                          jnp.float32)
    return cfg, run, x


def _program_share(cfg, lp, x, held):
    """``mixtral.moe_mlp`` told which experts it holds: that share's part
    of the routed sum."""
    first, count = held
    y, stats = mixtral.moe_mlp(
        x, lp["router"], *(lp[k][first:first + count]
                           for k in mixtral.EXPERT_STACKS),
        cfg.num_experts_per_tok, scoring="softmax", norm_topk=True, held=held)
    return np.asarray(y), np.asarray(stats)


def test_the_shares_add_up_to_the_uncut_layer():
    """For a layer of 8 experts: the routed parts of share 0 and share 1
    plus the shared expert, counted once, are the uncut reference's
    whole layer; in the reference given the shares, and in the program
    (``routed_experts(held=...)``) against the same uncut reference."""
    cfg, lp, x = _layer_inputs()
    whole, shared = reference.expert_layer(HF)(x, lp)
    want = np.asarray(whole + shared)
    parts = []
    for rank in (0, 1):
        mine = {k: (v[4 * rank:4 * rank + 4] if k in mixtral.EXPERT_STACKS else v)
                for k, v in lp.items()}
        routed, again = reference.expert_layer(SHARES[rank])(x, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)   # every rank alike
        parts.append(np.asarray(routed))
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01
    np.testing.assert_allclose(parts[0] + parts[1] + np.asarray(shared), want,
                               atol=1e-5)
    # the program's shares against the same uncut layer
    got, stats = zip(*(_program_share(cfg, lp, x, (4 * r, 4)) for r in (0, 1)))
    for r in (0, 1):
        np.testing.assert_allclose(got[r], parts[r], atol=1e-4)
    np.testing.assert_allclose(got[0] + got[1] + np.asarray(shared), want,
                               atol=1e-4)
    # the counters: every pick is somebody's, and an expert is held once
    picks = x.shape[0] * cfg.num_experts_per_tok
    assert stats[0][1] == stats[1][1] == picks
    assert stats[0][2] + stats[1][2] == picks
    assert 0 < stats[0][2] < picks
    whole_y, whole_stats = _program_share(cfg, lp, x, (0, 8))
    np.testing.assert_allclose(whole_y + np.asarray(shared), want, atol=1e-4)
    assert whole_stats[0] == stats[0][0] + stats[1][0]
    assert whole_stats[2] == picks


def test_the_shares_add_up_through_ep_axis_on_virtual_devices():
    """The same partial sums from ``axis_index`` inside a shard_map over
    two virtual devices, and one ``psum``: the whole routed sum."""
    cfg, lp, x = _layer_inputs()
    whole, _ = reference.expert_layer(HF)(x, lp)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    stacks = tuple(lp[k] for k in mixtral.EXPERT_STACKS)

    def local(x, router, w_gate, w_up, w_down):
        y, _ = mixtral.moe_mlp(x, router, w_gate, w_up, w_down,
                               cfg.num_experts_per_tok, scoring="softmax",
                               norm_topk=True, ep_axis="ep")
        # each member's partial sum, and their sum
        return y[None], jax.lax.psum(y, "ep")

    parts, total = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P("ep"), P("ep"), P("ep")),
        out_specs=(P("ep"), P()), check_vma=False)(x, lp["router"], *stacks)
    np.testing.assert_allclose(total, whole, atol=1e-4)
    for r in (0, 1):
        stated, _ = _program_share(cfg, lp, x, (4 * r, 4))
        np.testing.assert_allclose(parts[r], stated, atol=1e-6)


# ---------- the mixer at many heads a group; NoPE; the scale ----------

def test_mixer_kernel_at_many_heads_a_group():
    """``ssm_decode_step`` with one group of B and C for all the heads
    (H / G = 8 here, 128 at the published size) against the plain
    ``ssm_decode_update``, idle rows untouched. The records in the
    kernel's order: the eight heads of 16 side by side on a tile's 128
    lanes."""
    rs = np.random.RandomState(0)
    b, heads, p, n, layers = 4, 8, 16, 16, 3
    x = jnp.asarray(rs.randn(b, heads, p), jnp.float32)
    dt = jnp.asarray(np.abs(rs.randn(b, heads)) * 0.1, jnp.float32)
    dt = dt.at[2].set(0.0)                       # row 2 holds no token
    a = -jnp.asarray(rs.uniform(1, 16, heads), jnp.float32)
    bm = jnp.asarray(rs.randn(b, 1, n), jnp.float32)
    cm = jnp.asarray(rs.randn(b, 1, n), jnp.float32)
    d = jnp.ones((heads,), jnp.float32)
    records = jnp.asarray(rs.randn(layers, b, heads, p, n), jnp.float32)
    slot = jnp.asarray([[0], [1], [-1], [3]], jnp.int32)
    as_laid = ssm.state_to_record(records, heads)
    assert as_laid.shape == (layers, b, 1, n, heads * p)
    y, out = ssm.ssm_decode_step(x, dt, a, bm, cm, d, as_laid, jnp.int32(1),
                                 decode_live_rows(slot))
    out = ssm.record_to_state(out, p)
    want_y, want_h = ssm.ssm_decode_update(x, dt, a, bm, cm, d, records[1])
    live = np.asarray([0, 1, 3])
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1])[live],
                               np.asarray(want_h)[live], atol=1e-5)
    assert np.array_equal(np.asarray(out[1, 2]), np.asarray(records[1, 2]))
    assert np.array_equal(np.asarray(out[0]), np.asarray(records[0]))
    assert np.array_equal(np.asarray(out[2]), np.asarray(records[2]))


def test_chunked_scan_at_one_group_equals_the_recurrence():
    """``ssd_chunked_scan`` with G = 1 and a chunk that does not divide
    the run against ``ssm_decode_update`` token by token."""
    rs = np.random.RandomState(1)
    b, s, heads, p, n = 2, 45, 8, 16, 16
    x = jnp.asarray(rs.randn(b, s, heads, p), jnp.float32)
    dt = jnp.asarray(np.abs(rs.randn(b, s, heads)) * 0.1, jnp.float32)
    a = -jnp.asarray(rs.uniform(1, 16, heads), jnp.float32)
    bm = jnp.asarray(rs.randn(b, s, 1, n), jnp.float32)
    cm = jnp.asarray(rs.randn(b, s, 1, n), jnp.float32)
    d = jnp.ones((heads,), jnp.float32)
    h0 = jnp.asarray(rs.randn(b, heads, p, n), jnp.float32)
    # the scan takes and returns records (``state_to_record``)
    y, h1 = ssm.ssd_chunked_scan(x, dt, a, bm, cm, d,
                                 ssm.state_to_record(h0, heads), 32)
    h1 = ssm.record_to_state(h1, p)
    h, ys = h0, []
    for t in range(s):
        yt, h = ssm.ssm_decode_update(x[:, t], dt[:, t], a, bm[:, t], cm[:, t],
                                      d, h)
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, axis=1), atol=2e-4)
    np.testing.assert_allclose(h1, h, atol=2e-4)


def test_the_attention_layer_has_no_position_and_the_published_scale():
    """Swapping two earlier tokens changes nothing an attention layer
    with no positional term computes for a later query (the mixer layers
    do see the order, so this is the attention function alone), and the
    published scale is what reaches the kernel."""
    cfg = _cfg()
    assert granite_hybrid.softmax_scale(cfg) == 0.125 != cfg.head_dim ** -0.5
    _, params = _params(jnp.float32)
    lp = {k: v[0] for k, v in params["runs"][1].items()}
    t = 12
    x = jax.random.normal(jax.random.PRNGKey(2), (1, t, cfg.hidden_size))
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    pages = jnp.zeros((1, 4, BLOCK, cfg.num_kv_heads, 128), jnp.float32)
    table = jnp.arange(4, dtype=jnp.int32)[None]

    def last(x):
        fn = llama.make_gqa_attn_fn(
            cfg, 1, t, pos, pos, table, jnp.asarray([t], jnp.int32), None,
            rope=False, scale=granite_hybrid.softmax_scale(cfg))
        return np.asarray(fn(x, lp, pages, pages, jnp.int32(0))[0][0, -1])

    swapped = x.at[0, 2].set(x[0, 7]).at[0, 7].set(x[0, 2])
    np.testing.assert_allclose(last(x), last(swapped), atol=1e-5)


# ---------- the family's surface and what it refuses ----------

def test_the_published_config_reaches_the_family():
    cfg = ModelConfig.from_hf_config(HF)
    assert cfg.model_family == "granite_hybrid"
    assert models.resolve(cfg) is granite_hybrid
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (8, 0, 0)
    assert cfg.mamba_d_ssm == 128 and cfg.lm_head_multiplier == 1 / 16
    share = ModelConfig.from_hf_config(SHARES[1])
    assert (share.num_experts, share.experts_of, share.expert_rank) == (4, 8, 1)
    shapes = jax.eval_shape(
        lambda: granite_hybrid.init_params(share, jax.random.PRNGKey(0)))
    run = shapes["runs"][0]
    assert run["router"].shape == (2, 64, 8)         # the published width
    assert run["w_gate"].shape == (2, 4, 64, 24)     # the experts held
    assert "lm_head" not in shapes                   # tied
    k, v = jax.eval_shape(lambda: granite_hybrid.init_kv_cache(
        share, 16, BLOCK, jnp.bfloat16, num_slots=SLOTS))
    assert k.kv.shape[0] == 1 and k.state.shape[:2] == (3, SLOTS)
    assert v.state.shape == (3, SLOTS, 3, 128 + 2 * 16)
    assert k.state.dtype == jnp.float32 and k.dtype == jnp.bfloat16


@pytest.mark.parametrize("key,value,error", [
    ("position_embedding_type", "rope", NotImplementedError),
    ("mamba_proj_bias", True, NotImplementedError),
    ("mamba_conv_bias", False, NotImplementedError),
    ("mamba_n_groups", 3, ValueError),
    ("layer_types", ["mamba", "mamba", "lightning", "mamba"], ValueError),
    ("layer_types", ["mamba", "attention"], ValueError),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, NotImplementedError),
    ("shared_intermediate_size", 0, NotImplementedError),
    ("expert_share", {"of_experts": 12, "rank": 0}, ValueError),
    ("expert_share", {"of_experts": 16, "rank": 2}, ValueError),
])
def test_what_the_module_does_not_compute_is_refused(key, value, error):
    with pytest.raises(error, match=key.split("_")[0]):
        ModelConfig.from_hf_config({**HF, key: value})


@pytest.mark.parametrize("path,setting", [
    ("ep_size", dict(ep_size=2)), ("tp_size", dict(tp_size=2)),
    ("spec_ngram_tokens", dict(spec_ngram_tokens=2)),
    ("multi_step_decode", dict(multi_step_decode=4)),
])
def test_paths_refused_for_the_family_by_name(path, setting):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.model_runner import ModelRunner

    with pytest.raises(ValueError, match=f"{path} is refused for the "
                                         "granite_hybrid family"):
        ModelRunner(EngineConfig(model=_cfg(), max_batch_size=2,
                                 max_model_len=64, kv_block_size=BLOCK,
                                 num_kv_blocks=16, dtype="float32", **setting))


# ---------- the draw: what a long-lived state is read out by ----------

def test_the_draw_keeps_a_long_states_read_out_away_from_zero(monkeypatch):
    """A head that remembers a thousand tokens holds, nearly, a multiple
    of one fixed matrix (the running mean of ``x (x) B``), so a token
    reads out of it that matrix times one number, ``m_B . C_t``, the
    same for every head of the one group, and the gated norm divides
    ``y`` by it. Where that number comes near zero every rounding of the
    step is multiplied (one token in a few thousand read 0.8 off on the
    chip where the mean was 0.06, ``granite_hybrid.BC_CONV_BIAS``). Under
    the draw it stays near its median at every position; under Mamba-2's
    symmetric conv bias it does not, which is what this test would show
    of a draw that went back."""
    cfg = _cfg()
    d_ssm, n = cfg.mamba_d_ssm, cfg.mamba_d_state
    tokens = np.random.RandomState(5).randint(3, HF["vocab_size"], 2048)

    def read_out(bias):
        monkeypatch.setattr(granite_hybrid, "BC_CONV_BIAS", bias)
        params = granite_hybrid.init_params(cfg, jax.random.PRNGKey(3),
                                            jnp.float32)
        lp = {k: v[0] for k, v in params["runs"][0].items()}
        x = llama.rms_norm(params["embed"][tokens] * cfg.embedding_multiplier,
                           lp["ln1"], cfg.rms_norm_eps)
        bc = x @ lp["ssm_in"][:, 2 * d_ssm:2 * d_ssm + 2 * n]
        kc = cfg.mamba_d_conv
        past = jnp.concatenate([jnp.zeros((kc - 1, 2 * n)), bc])
        bc = jax.nn.silu(sum(past[k:k + len(tokens)] * lp["conv_w"][k, d_ssm:]
                             for k in range(kc)) + lp["conv_b"][d_ssm:])
        b, c = np.asarray(bc[:, :n]), np.asarray(bc[:, n:])
        return c @ b.mean(axis=0)

    drawn = read_out(granite_hybrid.BC_CONV_BIAS)
    assert drawn.min() > 0.5 * np.median(drawn)
    symmetric = read_out((-cfg.mamba_d_conv ** -0.5, cfg.mamba_d_conv ** -0.5))
    assert symmetric.min() < 0.2 * np.median(symmetric)


# ---------- the loader: a checkpoint under the published tensor names ----------

def _write_checkpoint(path, cfg, params):
    """The uncut model's weights under the tensor names of a
    ``granitemoehybrid`` checkpoint (transformers' layout: ``[out, in]``
    matrices, the experts' ``[E, 2 I, D]`` with gate then up, the conv
    ``[C, 1, K]``)."""
    import torch
    from safetensors.torch import save_file

    def t(x):
        return torch.from_numpy(np.array(x, np.float32, order="C"))

    out = {"model.embed_tokens.weight": t(params["embed"]),
           "model.norm.weight": t(params["final_norm"])}
    at = 0
    for (kind, _, n), run in zip(llama.layer_runs(cfg.layer_types),
                                 params["runs"]):
        for j in range(n):
            lp = {k: np.asarray(v[j], np.float32) for k, v in run.items()}
            pre = f"model.layers.{at + j}."
            out[pre + "input_layernorm.weight"] = t(lp["ln1"])
            out[pre + "post_attention_layernorm.weight"] = t(lp["ln2"])
            out[pre + "block_sparse_moe.router.layer.weight"] = t(lp["router"].T)
            out[pre + "block_sparse_moe.input_linear.weight"] = t(np.concatenate(
                [lp["w_gate"], lp["w_up"]], axis=-1).transpose(0, 2, 1))
            out[pre + "block_sparse_moe.output_linear.weight"] = t(
                lp["w_down"].transpose(0, 2, 1))
            out[pre + "shared_mlp.input_linear.weight"] = t(np.concatenate(
                [lp["w_sh_gate"], lp["w_sh_up"]], axis=-1).T)
            out[pre + "shared_mlp.output_linear.weight"] = t(lp["w_sh_down"].T)
            if kind == "mamba":
                out[pre + "mamba.in_proj.weight"] = t(lp["ssm_in"].T)
                out[pre + "mamba.conv1d.weight"] = t(lp["conv_w"].T[:, None, :])
                out[pre + "mamba.conv1d.bias"] = t(lp["conv_b"])
                for name, key in (("dt_bias", "dt_bias"), ("A_log", "A_log"),
                                  ("D", "D"), ("norm.weight", "ssm_norm")):
                    out[pre + "mamba." + name] = t(lp[key])
                out[pre + "mamba.out_proj.weight"] = t(lp["ssm_out"].T)
            else:
                for name, key in (("q_proj", "wq"), ("k_proj", "wk"),
                                  ("v_proj", "wv"), ("o_proj", "wo")):
                    out[pre + f"self_attn.{name}.weight"] = t(lp[key].T)
        at += n
    save_file(out, os.path.join(path, "model.safetensors"))


@pytest.mark.parametrize("rank", [None, 0, 1])
def test_loader_reads_the_published_names_and_the_held_slices_only(tmp_path, rank):
    """A checkpoint the test writes with the published tensor names
    loads into the family's pytree; a configuration that holds one
    rank's share gets that rank's experts and the whole router."""
    from dynamo_tpu.models.loader import load_checkpoint_params

    whole_cfg, whole = _params(jnp.float32)
    _write_checkpoint(str(tmp_path), whole_cfg, whole)
    cfg = whole_cfg if rank is None else _cfg(SHARES[rank])
    want = whole if rank is None else _share_of(whole, rank)
    got = load_checkpoint_params(str(tmp_path), cfg, granite_hybrid, jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    run = got["runs"][0]
    assert run["router"].shape[-1] == 8
    assert run["w_gate"].shape[1] == (8 if rank is None else 4)
    # and the loaded share serves what the reference given it computes
    if rank == 1:
        seq = _seqs([20], seed=9)[0]
        lp = _serve_case(Served(cfg, got, jnp.float32), [seq], [0], 4, [], 16)[0]
        np.testing.assert_allclose(
            lp, _reference_logprobs(got, seq, SHARES[1]), atol=F32_ATOL)
