"""The served Nemotron-H path (layers that are a Mamba-2 mixer, NoPE
attention or an expert block **alone** by ``hybrid_override_pattern``;
experts of two matrices and a squared ReLU in a latent the token is
projected into once; state by slot for the mixer layers, pages for the
attention layer; one expert-parallel rank's share of the experts)
against the benchmark's plain reference,
``benchmark/references/nemotron_h.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy.

Tiny ``nemotron_h`` shape that keeps the ratios: the published stage's
eleven letters ``MEMEMEM*EME``, 8 mixer heads in two groups (eight at
the published size), 8 query heads over 2 kv heads, 8 experts top-3 in a
latent half the hidden size, a shared expert, the published scaling 5.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import falcon_h1, mixtral, nemotron_h

import served  # noqa: E402  (puts benchmark/ on the path)
from references import nemotron_h as reference  # noqa: E402

BLOCK, SLOTS = 8, 4

HF = {
    "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h",
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 11,
    "hybrid_override_pattern": "MEMEMEM*EME",
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 32, "expand": 2,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False,
    "use_conv_bias": True, "use_bias": False, "attention_bias": False,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "intermediate_size": 24, "moe_intermediate_size": 24,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 48,
    "moe_shared_expert_overlap": False, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5,
    "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5, "residual_in_fp32": False,
    "rope_theta": 10000, "partial_rotary_factor": 1,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    # read, and its layers not built
    "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
}
RANKS = 4
# rank ``r`` of four: two of the eight experts held
SHARES = {r: {**HF, "n_routed_experts": 2,
              "expert_share": {"of_experts": 8, "rank": r}}
          for r in range(RANKS)}
# float32 on both sides: the two differ in the order of the sums (the
# chunked form against the recurrence, paged against dense attention,
# sorted grouped products against every expert in turn) and in nothing
# else; differences seen are 1e-5 in log-probability at any position,
# and the smallest deliberate fault below reads over 1e-3
F32_ATOL = 2e-4
WRONG = 1e-3
# bfloat16 weights, activations, residual stream, pages and conv window
# (the SSM state and the router float32) against the float32 reference
# on the same bfloat16 weights, the largest difference over the
# vocabulary at one position; at a hidden size of 64 rounding is coarser
# than on the chip, and a flipped pick among eight random experts moves
# more than one among 512 near-alike ones
BF16_MEDIAN = 0.25
BF16_ATOL = 1.0


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, hf=HF, seed=7):
    cfg = _cfg(hf)
    return cfg, nemotron_h.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _share_of(params, rank):
    """Rank ``rank``'s two experts of the uncut model's eight: the same
    weights, so that the shares can be added up."""
    keep = slice(2 * rank, 2 * rank + 2)
    moe = {k: (v[:, keep] if k in nemotron_h.EXPERT_STACKS else v)
           for k, v in params["moe"].items()}
    return {**params, "moe": moe}


def _reference_logprobs(params, seq, hf=HF):
    return served.reference_logprobs(reference, hf, params, seq)


def Served(cfg, params, dtype, state_dtype=None, fresh=False):
    """48 pages of 8 a slot, every page a slot's own."""
    return served.Served(nemotron_h, cfg, params, dtype, block=BLOCK, width=48,
                         slots=SLOTS, spare=False, state_dtype=state_dtype,
                         fresh=fresh)


_seqs, _serve_case = served.seqs, served.serve_case

# two prefill shapes ([1, 32] and [3, 32]) and the decode program: the
# cases share them
CASES = {
    # prefill in three chunks, boundaries off the scan's chunk of 32 and
    # off the page of 8, then two decode steps
    "three_chunks": dict(lengths=[70 + 2], n_decode=2, cuts=[29, 45],
                         width=32),
    # prefill, then 40 decode steps through the state and the pages
    "decode_40": dict(lengths=[21 + 40], n_decode=40, cuts=[], width=32),
    # rows of different lengths, a pad row between them, slots that are
    # not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[5 + 6, 45 + 6, 19 + 6], n_decode=6,
                          cuts=[16, 32], width=32, slots=[2, 0, 3],
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


# (bfloat16 at the case that runs every program: tier-1's time is short)
@pytest.mark.parametrize("case,dtype", [
    *((case, "float32") for case in CASES), ("batch_unequal", "bfloat16")])
def test_served_path_equals_reference(case, dtype):
    """Prefill, chunked prefill and decode through the state kept by
    slot and the attention layer's pages give the reference's
    full-forward log-softmax at every position, every expert held."""
    _compare(case, dtype, HF)


@pytest.mark.parametrize("rank", [3])
def test_one_ranks_share_equals_the_reference_given_the_same_share(rank):
    """Two of the eight experts held: the program and the reference both
    route over eight, weigh with the gates over the three chosen and add
    the held experts' terms alone; the partial result goes on through
    the layers and the two still agree."""
    _compare("batch_unequal", "float32", SHARES[rank],
             lambda whole: _share_of(whole, rank))


def _norm_over_all(monkeypatch):
    gated = falcon_h1._gated_norm
    monkeypatch.setattr(
        falcon_h1, "_gated_norm",
        lambda y, z, weight, groups, eps: gated(y, z, weight, 1, eps))


def _no_square(monkeypatch):
    def experts(xs, eid, group_sizes, layer, w_up, w_down):
        h = jax.nn.relu(mixtral.expert_matmul(xs, w_up, group_sizes, eid, layer))
        return mixtral.expert_matmul(h, w_down, group_sizes, eid, layer)

    monkeypatch.setitem(mixtral._EXPERT_BODIES, "relu2",
                        (experts, mixtral._EXPERT_BODIES["relu2"][1]))


def _silu_gate(monkeypatch):
    def experts(xs, eid, group_sizes, layer, w_up, w_down):
        h = mixtral.expert_matmul(xs, w_up, group_sizes, eid, layer)
        return mixtral.expert_matmul(jax.nn.silu(h) * h, w_down, group_sizes,
                                     eid, layer)

    monkeypatch.setitem(mixtral._EXPERT_BODIES, "relu2",
                        (experts, mixtral._EXPERT_BODIES["relu2"][1]))


def _router_reads_latent(monkeypatch):
    moe_mlp = mixtral.moe_mlp

    def wrong(x, router_w, *args, rows=None, **kwargs):
        # the latent where the stream belongs (the router's first rows)
        pad = x.shape[1] - rows.shape[1]
        return moe_mlp(jnp.pad(rows, ((0, 0), (0, pad))), router_w, *args,
                       rows=rows, **kwargs)

    monkeypatch.setattr(mixtral, "moe_mlp", wrong)


def _second_residual(monkeypatch):
    make = nemotron_h.make_moe_mlp_fn

    def make_moe_mlp_fn(*args, **kwargs):
        moe_fn = make(*args, **kwargs)

        def with_its_input(x, lp):      # the block's input added once more
            y, aux = moe_fn(x, lp)
            return y + x, aux
        return with_its_input

    monkeypatch.setattr(nemotron_h, "make_moe_mlp_fn", make_moe_mlp_fn)


WRONG_PROGRAMS = {
    # the state held in bfloat16: the recurrence feeds its rounding back
    "bf16_state": dict(state_dtype=jnp.bfloat16),
    # the gated norm over the whole d_inner, not over each group's part
    "norm_over_all": dict(patch=_norm_over_all),
    # relu without the square
    "no_square": dict(patch=_no_square),
    # a SiLU gate on the one matrix where the squared ReLU belongs
    "silu_gate": dict(patch=_silu_gate),
    # the routed scaling of 5 left out
    "no_scaling": dict(cfg=dict(routed_scaling_factor=1.0)),
    # the router reading the latent, not the hidden-wide stream
    "router_reads_latent": dict(patch=_router_reads_latent),
    # a layer given a second residual path
    "second_residual": dict(patch=_second_residual),
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
    served_ = Served(cfg, params, jnp.float32, spec.get("state_dtype"),
                     fresh=True)
    got = _serve_case(served_, [seq], [0], c["n_decode"], c["cuts"],
                      c["width"])[0]
    off = np.abs(got - _reference_logprobs(params, seq)).max()
    assert off > WRONG, off


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_the_references_controls_compute_below_the_stated_precision(control):
    """``build(lower=(name,))`` is what the chip's limits were set
    against: the same reference with one part in the precision below.
    Each differs from the reference and stays finite."""
    _, params = _params(jnp.float32)
    seq = _seqs([70], seed=11)[0]
    want = _reference_logprobs(params, seq)
    got = served.reference_logprobs(reference, HF, params, seq,
                                    lower=(control,))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() > 10 * F32_ATOL


def test_the_reference_refuses_a_control_it_does_not_have():
    with pytest.raises(ValueError, match="lower="):
        reference.build(HF, 8, 8, lower=("weights",))


# ---------- the shares add up ----------

def _program_share(cfg, lp, x, held):
    """``mixtral.moe_mlp`` told which experts it holds: that share's part
    of the routed sum, in the latent."""
    first, count = held
    rows = x @ lp["w_latent_in"]
    y, stats = mixtral.moe_mlp(
        x, lp["router"], None, lp["w_up"][first:first + count],
        lp["w_down"][first:first + count], cfg.num_experts_per_tok,
        scoring="sigmoid", norm_topk=True,
        routed_scaling=cfg.routed_scaling_factor,
        router_bias=lp["router_bias"], held=held, rows=rows,
        activation="relu2")
    return np.asarray(y), np.asarray(stats)


def test_the_four_shares_add_up_to_the_uncut_expert_block():
    """For a layer of 8 experts: the routed parts of the four ranks,
    summed in the latent and projected once, plus the shared expert,
    counted once, are the uncut reference's whole expert block; in the
    reference given the shares, and in the program
    (``routed_experts(held=...)`` over the latent rows) against the same
    uncut reference."""
    cfg, params = _params(jnp.float32)
    lp = {k: v[1] for k, v in params["moe"].items()}       # one layer
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size),
                          jnp.float32)
    whole, shared = reference.expert_layer(HF)(x, lp)
    out = lp["w_latent_out"]
    want = np.asarray(whole @ out + shared)
    parts = []
    for rank in range(RANKS):
        mine = {k: (v[2 * rank:2 * rank + 2]
                    if k in nemotron_h.EXPERT_STACKS else v)
                for k, v in lp.items()}
        routed, again = reference.expert_layer(SHARES[rank])(x, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)  # every rank alike
        parts.append(np.asarray(routed))
    assert all(np.abs(p).max() > 0.01 for p in parts)
    np.testing.assert_allclose(sum(parts) @ np.asarray(out) + np.asarray(shared),
                               want, atol=1e-5)
    # the program's shares against the same uncut block
    got, stats = zip(*(_program_share(cfg, lp, x, (2 * r, 2))
                       for r in range(RANKS)))
    for r in range(RANKS):
        np.testing.assert_allclose(got[r], parts[r], atol=1e-4)
    np.testing.assert_allclose(sum(got) @ np.asarray(out) + np.asarray(shared),
                               want, atol=1e-4)
    # the counters: every pick is somebody's, and an expert is held once
    picks = x.shape[0] * cfg.num_experts_per_tok
    assert all(s[1] == picks for s in stats)
    assert sum(s[2] for s in stats) == picks
    assert all(0 < s[2] < picks for s in stats)
