"""The served Kimi Linear path (layers that are Kimi Delta Attention or
latent attention without a positional term by the published 1-based
lists, a dense feed-forward behind the first layer and routed experts
and a shared expert behind the others; state by slot for the KDA layers
only, latent pages for the others only; one expert-parallel rank's share
of the experts) against the benchmark's plain reference,
``benchmark/references/kimi_linear.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy. And the recurrence's
three forms (``ops/kda.py``) against each other.

Tiny ``kimi_linear`` shape that keeps the ratios: two periods of KDA
layers before a latent one (2 + 1, the first KDA layer followed by the
dense feed-forward, and 2 + 1), 4 KDA heads of 16, 4 latent heads of 16
+ 8 over a latent of 32, 16 experts top-3 and a shared expert.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import deepseek, kimi_linear, llama, mixtral, trunk
from dynamo_tpu.ops import kda
from dynamo_tpu.ops.live_rows import decode_live_rows

import served  # noqa: E402  (puts benchmark/ on the path)
from references import kimi_linear as reference  # noqa: E402

BLOCK, SLOTS = 8, 4

HF = {
    "architectures": ["KimiLinearForCausalLM"], "model_type": "kimi_linear",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 24, "num_hidden_layers": 6,
    "linear_attn_config": {"kda_layers": [1, 2, 4, 5], "full_attn_layers": [3, 6],
                           "head_dim": 16, "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "first_k_dense_replace": 1, "num_experts": 16, "num_experts_per_token": 3,
    "num_shared_experts": 1, "moe_router_activation_func": "sigmoid",
    "moe_renormalize": True, "moe_layer_freq": 1, "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "routed_scaling_factor": 2.446,
    "num_nextn_predict_layers": 0, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "tie_word_embeddings": False,
    "model_max_length": 512,
}
# rank ``r`` of four: four of the sixteen experts held
RANKS = 4
SHARES = {r: {**HF, "num_experts": 4,
              "expert_share": {"of_experts": 16, "rank": r}} for r in range(RANKS)}
# float32 on both sides: the two differ in the order of the sums (the
# chunked WY form against the recurrence, absorbed paged attention
# against un-absorbed dense, sorted grouped products against every
# expert in turn) and in nothing else; differences seen are 4e-5 in
# log-probability at any position, and the smallest deliberate fault
# below reads over 1e-2
F32_ATOL = 3e-4
WRONG = 3e-3
# bfloat16 weights, activations, pages and conv window (the KDA state and
# the router float32) against the float32 reference on the same bfloat16
# weights, the largest difference over the vocabulary at one position;
# at a hidden size of 64 and heads of 16 rounding is coarser than on the
# chip, and the delta rule reads the state back against a rounded key
# (medians of 0.13-0.45 and 1.1 at most are seen; with the mixer's
# projections in float32 0.22 / 0.53)
BF16_MEDIAN = 0.8
BF16_ATOL = 2.5


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, hf=HF, seed=7):
    cfg = _cfg(hf)
    return cfg, kimi_linear.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _share_of(params, rank, held=4):
    """Rank ``rank``'s experts of the uncut model's sixteen: the same
    weights, so that the shares can be added up."""
    keep = slice(held * rank, held * rank + held)
    moe = {k: (v[:, keep] if k in mixtral.EXPERT_STACKS else v)
           for k, v in params["moe"].items()}
    return {**params, "moe": moe}


def _reference_logprobs(params, seq, hf=HF, lower=()):
    """The reference's log-probabilities at every position of ``seq``."""
    return served.reference_logprobs(reference, hf, params, seq, lower=lower)


def Served(cfg, params, dtype, state_dtype=None, fresh=False):
    """48 pages of 8 a slot, every page a slot's own (as
    tests/test_falcon_h1_reference.py drives Falcon-H1)."""
    return served.Served(kimi_linear, cfg, params, dtype, block=BLOCK, width=48,
                         slots=SLOTS, spare=False, state_dtype=state_dtype,
                         fresh=fresh)


_seqs, _serve_case = served.seqs, served.serve_case


CASES = {
    # one prefill, the whole prompt in one padded chunk
    "one_prefill": dict(lengths=[29 + 2], n_decode=2, cuts=[], width=32),
    # prefill in three chunks, boundaries off the scan's chunk of 64 and
    # sub-chunk of 16 and off the page of 8
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
    slot (the conv windows carried across the chunk and the prefill /
    decode boundaries) and the latent layers' pages give the reference's
    full-forward log-softmax at every position, every expert held."""
    _compare(case, dtype, HF)


@pytest.mark.parametrize("rank", [0, 2])
@pytest.mark.parametrize("case", ["three_chunks", "batch_unequal"])
def test_one_ranks_share_equals_the_reference_given_the_same_share(case, rank):
    """Four of the sixteen experts held: the program and the reference
    both route over sixteen, weigh with the renormalised scores of the
    three picked and add the held experts' terms alone; the partial
    result goes on through the layers and the two still agree."""
    _compare(case, "float32", SHARES[rank],
             lambda whole: _share_of(whole, rank))


def test_resume_after_preemption_and_slot_reuse():
    """A sequence dropped after 10 decoded tokens and prefilled again
    from position 0 (prompt + the 10), into the slot another sequence
    has used meanwhile, continues as the uninterrupted one and as the
    reference says; the second user of a slot starts from zeros, not
    from what the first left."""
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
    uninterrupted = _serve_case(Served(cfg, params, jnp.float32), [a], [3], 30,
                                [], 32)[0]
    np.testing.assert_allclose(got, uninterrupted, atol=F32_ATOL)


def test_the_conv_windows_are_the_slots_last_inputs():
    """After a prefill of n tokens a slot's window in the first KDA layer
    is the last three rows of ``RMSNorm(embed) W_qkv``; a decode step
    shifts it by the token's row; a slot that idles keeps its own."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    seq = _seqs([13], seed=8)[0]
    lp = {k: v[0] for k, v in params["kda"].items()}
    rows = np.asarray(llama.rms_norm(params["embed"][np.asarray(seq)], lp["ln1"],
                                     cfg.rms_norm_eps) @ lp["w_qkv"])
    served.prefill([(2, seq[:12], 0)], 16)
    np.testing.assert_allclose(served.state()[1][0, 2], rows[9:12], atol=1e-5)
    assert not served.state()[1][0, 1].any()
    served.decode({2: (seq[12], 12)})
    np.testing.assert_allclose(served.state()[1][0, 2], rows[10:13], atol=1e-5)
    before = served.state()
    served.decode({0: (seq[0], 0)})          # slot 2 idles
    after = served.state()
    np.testing.assert_array_equal(after[0][:, 2], before[0][:, 2])
    np.testing.assert_array_equal(after[1][:, 2], before[1][:, 2])


# ---------- the recurrence's three forms ----------

def _draw(seed, b, s, h, kd, vd, strongest, weakest=1e-3):
    """q, k as the trunk hands them (normalised, q scaled), log-decays
    log-uniform in ``[-strongest, -weakest]``, β in (0, 1), a state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, s, h, kd))
    k = jax.random.normal(ks[1], (b, s, h, kd))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * kd ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, vd))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, kd), minval=np.log(weakest),
                                    maxval=np.log(strongest)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, kd, vd))


def _token_by_token(q, k, v, g, beta, s0):
    def token(s, x):
        o, s = kda.kda_decode_update(*x, s)
        return s, o

    s1, o = jax.lax.scan(token, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s1


@pytest.mark.parametrize("length,strongest", [
    (64, 0.5),      # one whole chunk
    (100, 0.5),     # not a multiple of the chunk nor of the sub-chunk
    (37, 20.0),     # decays down to e^-20 a token: e^-Γ would overflow
    (200, 20.0),    # the same across three chunk boundaries
    (7, 3.0),       # shorter than a sub-chunk
])
def test_chunked_scan_equals_the_recurrence(length, strongest):
    """``kda_chunked_scan`` from a given state against
    ``kda_decode_update`` token by token, in float32: outputs and the
    state after the run."""
    args = _draw(length, 2, length, 3, 16, 24, strongest)
    want_o, want_s = _token_by_token(*args)
    got_o, got_s = jax.jit(kda.kda_chunked_scan)(*args)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_chunked_scan_passes_the_state_at_pad_positions():
    """``g = 0, β = 0`` at a row's pad positions leaves the state as of
    its last real token, and a row of pads alone as it was."""
    q, k, v, g, beta, s0 = _draw(3, 2, 50, 2, 16, 16, 2.0)
    real = jnp.asarray([31, 0])
    valid = jnp.arange(50)[None, :] < real[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    _, got = kda.kda_chunked_scan(q, k, v, g, beta, s0)
    _, want = _token_by_token(q[:1, :31], k[:1, :31], v[:1, :31], g[:1, :31],
                              beta[:1, :31], s0[:1])
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1], s0[1], atol=1e-6)


@pytest.mark.parametrize("records_dtype", ["float32", "bfloat16"])
def test_decode_kernel_equals_the_recurrence_and_leaves_idle_rows(records_dtype):
    """``kda_decode_step`` (in the interpreter here, compiled on the
    chip) on layer 1 of three against ``kda_decode_update``: live rows
    advanced, rows without a token, slots past the step's rows and the
    other layers bit for bit as they were."""
    b, h, kd, layers, slots = 5, 4, 128, 3, 7
    dt = jnp.dtype(records_dtype)
    q, k, v, g, beta, _ = _draw(11, b, 1, h, kd, kd, 20.0)
    q, k, v, g, beta = (t[:, 0] for t in (q, k, v, g, beta))
    records = jax.random.normal(jax.random.PRNGKey(9),
                                (layers, slots, h, kd, kd)).astype(dt)
    slot = jnp.asarray([[0], [-1], [2], [3], [-1]], jnp.int32)
    live = np.asarray([0, 2, 3])
    g = jnp.where(slot >= 0, g.reshape(b, -1), 0.0).reshape(g.shape)
    beta = jnp.where(slot >= 0, beta, 0.0)
    o, out = jax.jit(kda.kda_decode_step)(
        q, k, v, g, beta, records, jnp.int32(1), decode_live_rows(slot))
    want_o, want_s = kda.kda_decode_update(
        q, k, v, g, beta, records[1, :b].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               atol=1e-5)
    assert not np.asarray(o)[[1, 4]].any()
    # rounded to the records' dtype once, on the way out: an ulp of it
    np.testing.assert_allclose(
        np.asarray(out[1].astype(jnp.float32))[live],
        np.asarray(want_s)[live], atol=1e-5,
        rtol=0 if records_dtype == "float32" else 2 ** -7)
    for untouched in (out[0] == records[0], out[2] == records[2],
                      out[1, [1, 4]] == records[1, [1, 4]],
                      out[1, b:] == records[1, b:]):
        assert bool(untouched.all())


# ---------- a wrong program is told apart ----------

def _scalar_decay(monkeypatch):
    def mean_of_channels(fn):
        def wrong(q, k, v, g, *rest):
            return fn(q, k, v, jnp.broadcast_to(
                jnp.mean(g, -1, keepdims=True), g.shape), *rest)
        return wrong
    for name in ("kda_decode_step", "kda_chunked_scan"):
        monkeypatch.setattr(kimi_linear, name,
                            mean_of_channels(getattr(kimi_linear, name)))


def _beta_one(monkeypatch):
    def writes_all(fn):
        def wrong(q, k, v, g, beta, *rest):
            return fn(q, k, v, g, jnp.where(beta > 0, 1.0, 0.0), *rest)
        return wrong
    for name in ("kda_decode_step", "kda_chunked_scan"):
        monkeypatch.setattr(kimi_linear, name,
                            writes_all(getattr(kimi_linear, name)))


def _rope_on_mla(monkeypatch):
    monkeypatch.setattr(
        kimi_linear, "make_mla_attn_fn",
        lambda *a, **kw: deepseek.make_mla_attn_fn(*a, **{**kw, "rope": True}))


def _bf16_router(monkeypatch):
    route = mixtral.route_top_k

    def route_top_k(x, router_w, *args, **kwargs):
        logits = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16))
        eye = jnp.eye(router_w.shape[1], dtype=jnp.float32)
        return route(logits.astype(jnp.float32), eye, *args, **kwargs)

    monkeypatch.setattr(mixtral, "route_top_k", route_top_k)


WRONG_PROGRAMS = {
    # the state held in bfloat16: the recurrence feeds its rounding back
    "bf16_state": dict(state_dtype=jnp.bfloat16),
    # the decay one scalar a head: the channel mean of g
    "scalar_decay": dict(patch=_scalar_decay),
    # a rotary term on the latent layers' 64-wide parts
    "rope_on_mla": dict(patch=_rope_on_mla),
    # β ≡ 1: every token overwrites what its key reads
    "beta_one": dict(patch=_beta_one),
    # the gates not renormalised over the picked
    "gates_not_renormalised": dict(cfg=dict(norm_topk_prob=False)),
    # the routed sum not scaled
    "no_routed_scaling": dict(cfg=dict(routed_scaling_factor=1.0)),
    # router scores from a bfloat16 product
    "bf16_router": dict(patch=_bf16_router),
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
def test_the_references_controls_compute_something_else(control):
    """``build(lower=(name,))`` is what the chip's limits were set
    against (``scripts/long_probes.py --controls``): the same reference
    with one part below the stated precision, or wrong. Each differs
    from the reference and stays finite; built with none it is the
    reference."""
    _, params = _params(jnp.float32)
    seq = _seqs([150], seed=11)[0]
    want = _reference_logprobs(params, seq)
    np.testing.assert_array_equal(_reference_logprobs(params, seq, lower=()), want)
    got = _reference_logprobs(params, seq, lower=(control,))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() > 10 * F32_ATOL


def test_the_reference_refuses_a_control_it_does_not_have():
    with pytest.raises(ValueError, match="lower="):
        reference.build(HF, 8, 8, lower=("weights",))


# ---------- the shares add up ----------

def _layer_inputs(seed=5, t=48):
    cfg, params = _params(jnp.float32)
    lp = {k: v[1] for k, v in params["moe"].items()}          # one layer
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, cfg.hidden_size),
                          jnp.float32)
    return cfg, lp, x


def _program_share(cfg, lp, x, held):
    """``mixtral.moe_mlp`` told which experts it holds: that share's part
    of the routed sum, as the family's ``make_moe_mlp_fn`` calls it."""
    first, count = held
    y, stats = mixtral.moe_mlp(
        x, lp["router"], *(lp[k][first:first + count]
                           for k in mixtral.EXPERT_STACKS),
        cfg.num_experts_per_tok, scoring=cfg.moe_scoring_func,
        norm_topk=cfg.norm_topk_prob, routed_scaling=cfg.routed_scaling_factor,
        router_bias=lp["router_bias"], held=held)
    return np.asarray(y), np.asarray(stats)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """For a layer of 16 experts: the routed parts of the sixteen ranks
    that hold one expert each, plus the shared expert counted once, are
    the uncut reference's whole layer; in the reference given the
    shares, and in the program (``routed_experts(held=...)``) against
    the same uncut reference."""
    cfg, lp, x = _layer_inputs()
    whole, shared = reference.expert_layer(HF)(x, lp)
    want = np.asarray(whole + shared)
    parts, got, stats = [], [], []
    for rank in range(16):
        hf = {**HF, "num_experts": 1,
              "expert_share": {"of_experts": 16, "rank": rank}}
        mine = {k: (v[rank:rank + 1] if k in mixtral.EXPERT_STACKS else v)
                for k, v in lp.items()}
        routed, again = reference.expert_layer(hf)(x, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)   # every rank alike
        parts.append(np.asarray(routed))
        y, s = _program_share(cfg, lp, x, (rank, 1))
        np.testing.assert_allclose(y, parts[-1], atol=1e-4)
        got.append(y)
        stats.append(s)
    assert sum(np.abs(p).max() > 1e-3 for p in parts) >= 12    # most are picked
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), want, atol=1e-5)
    np.testing.assert_allclose(sum(got) + np.asarray(shared), want, atol=2e-4)
    # the counters: every pick is somebody's, and an expert is held once
    picks = x.shape[0] * cfg.num_experts_per_tok
    assert all(s[1] == picks for s in stats)
    assert sum(s[2] for s in stats) == picks
    whole_y, whole_stats = _program_share(cfg, lp, x, (0, 16))
    np.testing.assert_allclose(whole_y + np.asarray(shared), want, atol=2e-4)
    assert whole_stats[0] == sum(s[0] for s in stats)
    assert whole_stats[2] == picks


# ---------- the latent layers have no position; Moonlight's keep theirs ----------

def _mla_last(cfg, lp, x, rope):
    t = x.shape[1]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    c = jnp.zeros((1, 4, 1, BLOCK, 128), jnp.float32)
    table = jnp.arange(4, dtype=jnp.int32)[None]
    kwargs = {} if rope is None else {"rope": rope}
    fn = deepseek.make_mla_attn_fn(
        cfg, 1, t, pos, pos, table, jnp.asarray([t], jnp.int32), **kwargs)
    return np.asarray(fn(x, lp, c, c, jnp.int32(0))[0][0, -1])


def test_the_latent_layer_has_no_position():
    """Swapping two earlier tokens changes nothing a latent layer
    without the rotation computes for a later query (the KDA layers do
    see the order, so this is the attention function alone); with the
    rotation it does."""
    cfg, params = _params(jnp.float32)
    lp = {k: v[0] for k, v in params["mla"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.hidden_size))
    swapped = x.at[0, 2].set(x[0, 7]).at[0, 7].set(x[0, 2])
    np.testing.assert_allclose(_mla_last(cfg, lp, x, False),
                               _mla_last(cfg, lp, swapped, False), atol=1e-5)
    assert np.abs(_mla_last(cfg, lp, x, True)
                  - _mla_last(cfg, lp, swapped, True)).max() > 1e-3


@pytest.mark.parametrize("hc_mult", [1, 4], ids=["moonlight", "xing4"])
def test_the_nope_parameter_leaves_the_other_latent_families_as_they_were(hc_mult):
    """Moonlight's and Xing4's trunks call ``make_mla_attn_fn`` without
    the parameter: their lowered program is the one an explicit
    ``rope=True`` gives, operation for operation, and their logits are
    unchanged; ``rope=False`` is another program."""
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=24, n_shared_experts=1, first_k_dense_replace=1,
        attention_impl="xla", hc_mult=hc_mult,
        model_family="deepseek" if hc_mult > 1 else "")
    assert models.resolve(cfg) is deepseek
    params = deepseek.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    t = 10
    tokens = jnp.arange(3, 3 + t, dtype=jnp.int32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    table = jnp.arange(4, dtype=jnp.int32)[None]

    def lowered_and_logits():
        # a new function a reading: jit keeps a trace by the function
        def forward(cache):
            return deepseek.forward(params, cfg, tokens, pos, cache, table,
                                    pos, jnp.asarray([t], jnp.int32))[0]

        cache = deepseek.init_kv_cache(cfg, 4, BLOCK, jnp.float32)
        return (jax.jit(forward).lower(cache).as_text(),
                np.asarray(forward(cache)))

    as_now, logits = lowered_and_logits()
    make = deepseek.make_mla_attn_fn
    try:
        deepseek.make_mla_attn_fn = lambda *a, **kw: make(*a, **{**kw, "rope": True})
        text, same = lowered_and_logits()
        assert text == as_now
        np.testing.assert_array_equal(same, logits)
        deepseek.make_mla_attn_fn = lambda *a, **kw: make(*a, **{**kw, "rope": False})
        text, other = lowered_and_logits()
        assert text != as_now
        assert np.abs(other - logits).max() > 1e-3
    finally:
        deepseek.make_mla_attn_fn = make


# ---------- the family's surface and what it refuses ----------

def test_the_published_config_reaches_the_family():
    cfg = ModelConfig.from_hf_config(HF)
    assert cfg.model_family == "kimi_linear"
    assert models.resolve(cfg) is kimi_linear      # not deepseek's shape rule
    assert cfg.layer_types == ("kda", "kda", "mla", "kda", "kda", "mla")
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel) == (4, 16, 4)
    assert (cfg.num_experts, cfg.experts_of, cfg.expert_rank) == (16, 0, 0)
    assert (cfg.num_experts_per_tok, cfg.n_shared_experts, cfg.moe_scoring_func,
            cfg.norm_topk_prob, cfg.routed_scaling_factor) == \
        (3, 1, "sigmoid", True, 2.446)
    assert cfg.max_position_embeddings == 512 and cfg.q_lora_rank == 0
    prefix, periods = trunk.period_layout(cfg, ("kda", "mla"))
    assert prefix == [("kda", 0, 0)]
    assert [p.tolist() for p in periods] == [[1, 2], [1, 2], [0, 1], [1, 1]]
    share = ModelConfig.from_hf_config(SHARES[1])
    assert (share.num_experts, share.experts_of, share.expert_rank) == (4, 16, 1)
    shapes = jax.eval_shape(
        lambda: kimi_linear.init_params(share, jax.random.PRNGKey(0)))
    assert shapes["moe"]["router"].shape == (5, 64, 16)      # the published width
    assert shapes["moe"]["w_gate"].shape == (5, 4, 64, 24)   # the experts held
    assert shapes["dense"]["w_gate"].shape == (1, 64, 96)
    assert shapes["kda"]["w_qkv"].shape == (4, 64, 3 * 64)
    assert shapes["kda"]["dt_bias"].dtype == jnp.float32
    assert shapes["lm_head"].shape == (64, 256)              # untied
    k, v = jax.eval_shape(lambda: kimi_linear.init_kv_cache(
        share, 16, BLOCK, jnp.bfloat16, num_slots=SLOTS))
    assert k.kv.shape == (2, 16, 1, BLOCK, 128) and v.kv.shape[-1] == 128
    assert k.state.shape == (4, SLOTS, 4, 16, 16)
    assert v.state.shape == (4, SLOTS, 3, 3 * 64)
    assert k.state.dtype == jnp.float32 and k.dtype == jnp.bfloat16


def test_the_draw_spreads_the_horizons_and_saturates_nothing():
    """A channel's forgetting horizon 1 / (exp(A_log) softplus(dt_bias))
    is inside ``STATE_HORIZON`` and spread over it; exp(A_log) is the
    published initialisation's [1, 16]."""
    cfg, params = _params(jnp.float32)
    p = params["kda"]
    decay = np.exp(np.asarray(p["A_log"]))                    # [L, H]
    step = np.asarray(jax.nn.softplus(p["dt_bias"])).reshape(4, 4, 16)
    horizon = 1.0 / (decay[..., None] * step)
    lo, hi = kimi_linear.STATE_HORIZON
    assert decay.min() >= 1.0 and decay.max() <= 16.0
    assert horizon.min() >= lo * 0.99 and horizon.max() <= hi * 1.01
    assert np.median(horizon) < 0.25 * hi and np.median(horizon) > 4 * lo


@pytest.mark.parametrize("key,value,error", [
    ("mla_use_nope", False, NotImplementedError),
    ("q_lora_rank", 48, NotImplementedError),
    ("num_expert_group", 2, NotImplementedError),
    ("num_nextn_predict_layers", 1, NotImplementedError),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, NotImplementedError),
    ("moe_layer_freq", 2, NotImplementedError),
    ("num_shared_experts", 0, NotImplementedError),
    ("linear_attn_config", {**HF["linear_attn_config"], "kda_layers": [1, 2, 4]},
     ValueError),
    ("linear_attn_config", {**HF["linear_attn_config"],
                            "full_attn_layers": [0, 3]}, ValueError),
    ("expert_share", {"of_experts": 24, "rank": 0}, ValueError),
    ("expert_share", {"of_experts": 32, "rank": 2}, ValueError),
])
def test_what_the_module_does_not_compute_is_refused(key, value, error):
    named = {"linear_attn_config": "kda_layers", "expert_share": "share",
             "num_shared_experts": "shared expert"}.get(key, key)
    with pytest.raises(error, match=named):
        ModelConfig.from_hf_config({**HF, key: value})


def test_a_config_without_mla_use_nope_is_refused_too():
    hf = {k: v for k, v in HF.items() if k != "mla_use_nope"}
    with pytest.raises(NotImplementedError, match="mla_use_nope"):
        ModelConfig.from_hf_config(hf)


@pytest.mark.parametrize("path,setting", [
    ("ep_size", dict(ep_size=2)), ("tp_size", dict(tp_size=2)),
    ("spec_ngram_tokens", dict(spec_ngram_tokens=2)),
    ("multi_step_decode", dict(multi_step_decode=4)),
])
def test_paths_refused_for_the_family_by_name(path, setting):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.model_runner import ModelRunner

    with pytest.raises(ValueError, match=f"{path} is refused for the "
                                         "kimi_linear family"):
        ModelRunner(EngineConfig(model=_cfg(), max_batch_size=2,
                                 max_model_len=64, kv_block_size=BLOCK,
                                 num_kv_blocks=16, dtype="float32", **setting))
