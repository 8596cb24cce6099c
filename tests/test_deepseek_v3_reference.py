"""The served DeepSeek-V3-family path (MLA in the absorbed form over the
paged latent cache, drop-free routed experts) against the benchmark's
plain reference, ``benchmark/references/deepseek_v3.py`` — the same file
the benchmark's ``correct`` is decided by; there is no second copy.

Tiny ``deepseek_v3`` shape: no q_lora, 1 dense + 2 MoE layers, 8 experts
top-3 + 2 shared, sigmoid scores, a non-zero selection bias
(``noaux_tc``), routed scaling 2.446.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import deepseek

import served  # noqa: E402  (puts benchmark/ on the path)
from references import deepseek_v3 as reference  # noqa: E402

HF = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "deepseek_v3",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_rope_head_dim": 16, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 3,
    "n_shared_experts": 2, "first_k_dense_replace": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 2.446, "n_group": 1,
    "topk_group": 1, "rope_theta": 50000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
}
BLOCK = 8
# float32 on both sides: the two differ in the order of products
# (absorbed against un-absorbed attention, sorted grouped products
# against every expert on every token) and in nothing else, so logits
# agree to float32 rounding carried through 3 layers; 1e-4 is ~10x the
# differences seen (1e-5) and 100x under what one flipped expert choice
# or one dropped row does (1e-2 and up)
F32_ATOL = 1e-4
# bfloat16 weights, activations and cache against the float32 reference
# on the same bfloat16 weights. Measured on this shape, the largest
# difference over the vocabulary at one position: median 0.038-0.046 and
# at most 0.077 in three cases; in batch_8, 4 of 64 positions read
# 0.3-1.6, where rounding flipped one of a token's top-3 of 8 experts
# (a near-tie of two selection scores). So the limit is on the bulk:
# the median position within BF16_MEDIAN, nine in ten within BF16_ATOL.
BF16_MEDIAN = 0.1
BF16_ATOL = 0.25


def _cfg(attention_impl="xla"):
    return served.cfg_of(HF, attention_impl=attention_impl)


def _params(dtype):
    cfg = _cfg()
    params = deepseek.init_params(cfg, jax.random.PRNGKey(7), dtype)
    assert "router_bias" in params["layers"]          # noaux_tc: made from the seed
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) > 0
    return cfg, params


def _reference_logprobs(params, seq):
    """The reference's log-probabilities at every position of ``seq``."""
    return served.reference_logprobs(reference, HF, params, seq)


def _serve(cfg, params, prompts, n_decode, chunk, dtype, fresh=False):
    """Prefill the prompts (in chunks of ``chunk`` tokens, padded to it)
    and decode ``n_decode`` teacher-forced tokens through the paged
    latent cache; returns per sequence the log-softmax of the logits at
    every position. ``prompts`` carry their continuation: prompt_len
    tokens are prefilled, the next n_decode are fed one a step."""
    return served.serve_chunks(deepseek, cfg, params, prompts, n_decode, chunk,
                               dtype, block=BLOCK, fresh=fresh)


_seqs = served.seqs


CASES = {
    # prompt of 13 with blocks of 8: the cache read crosses a block
    "block_boundary": dict(lengths=[13 + 4], n_decode=4, chunk=16),
    # prompt of 21 through a prefill bucket of 8: three chunks, the
    # last one padded
    "chunked": dict(lengths=[21 + 3], n_decode=3, chunk=8),
    # unequal lengths in one batch: pad rows in prefill, three rows a
    # decode step (the capacity formulation dropped rows here)
    "batch_unequal": dict(lengths=[5 + 3, 13 + 3, 9 + 3], n_decode=3, chunk=16),
    # eight rows a decode step, 8 experts top-3
    "batch_8": dict(lengths=[6 + 2] * 8, n_decode=2, chunk=8),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case, dtype):
    """Prefill, chunked prefill and decode through the paged cache give
    the reference's full-forward log-softmax of the logits at every
    position."""
    dt = jnp.dtype(dtype)
    cfg, params = _params(dt)
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    got = _serve(cfg, params, seqs, c["n_decode"], c["chunk"], dt)
    served.assert_close(got, [_reference_logprobs(params, q) for q in seqs],
                        dtype, F32_ATOL, BF16_MEDIAN, BF16_ATOL, bf16_share=0.9)


@pytest.mark.parametrize("rounded", ["router_logits_bf16", "cache_fp8"])
def test_reference_tells_precisions_apart(rounded):
    """What the benchmark's mean limit has to discriminate, at the tiny
    shape in float32: rounding the router's logits to bfloat16 (the
    parent's ``(x @ W).astype(float32)`` on bfloat16 operands), or an
    8-bit latent cache, moves the mean |d log p| by orders of magnitude
    over the served path's own."""
    cfg, params = _params(jnp.float32)
    seq = _seqs([40], seed=3)[0]
    want = _reference_logprobs(params, seq)
    idx = (np.arange(len(seq) - 1), np.asarray(seq[1:]))
    base = _serve(cfg, params, [seq], 8, 16, jnp.float32)[0]
    base_err = np.abs(base[:-1][idx] - want[:-1][idx]).mean()
    if rounded == "router_logits_bf16":
        p2 = jax.tree.map(lambda a: a, params)
        # a router whose product is rounded to bfloat16: same as rounding
        # activations and weights of that product first
        p2["layers"] = dict(params["layers"])
        p2["layers"]["router"] = params["layers"]["router"].astype(
            jnp.bfloat16).astype(jnp.float32)
        got = _serve(cfg, p2, [seq], 8, 16, jnp.float32, fresh=True)[0]
    else:
        got = _serve(cfg, params, [seq], 8, 16, jnp.float8_e4m3fn,
                     fresh=True)[0]
    err = np.abs(got[:-1][idx] - want[:-1][idx]).mean()
    assert base_err < 1e-5
    assert err > 100 * base_err


def test_random_experts_are_a_prototype_and_a_spread():
    """``init_params``: a layer's routed experts are one prototype plus
    ``EXPERT_SPREAD`` of their own (a flipped near-tie of the router then
    moves the output by that share, as a trained model's similar experts
    do), with the variance of every other random weight; layers do not
    share a prototype and every expert is its own matrix."""
    _, params = _params(jnp.float32)
    s = deepseek.EXPERT_SPREAD
    for name, fan_in in (("w_gate", HF["hidden_size"]), ("w_up", HF["hidden_size"]),
                         ("w_down", HF["moe_intermediate_size"])):
        w = np.asarray(params["layers"][name])               # [L, E, in, out]
        flat = w.reshape(w.shape[0], w.shape[1], -1)
        np.testing.assert_allclose(flat.var() * fan_in, 1.0, rtol=0.1)
        corr = np.corrcoef(flat[0])                           # experts of layer 0
        off = corr[~np.eye(len(corr), dtype=bool)]
        np.testing.assert_allclose(off, 1.0 - s * s, atol=0.02)
        assert np.abs(flat[0, 0] - flat[0, 1]).max() > 0
        assert abs(np.corrcoef(flat[0, 0], flat[1, 0])[0, 1]) < 0.2


def test_model_runner_step_logprobs_equal_reference():
    """Through ModelRunner.step (the engine's program: trunk, head on the
    sampled position, sampling pass): the log-probability of the greedy
    token after prefill and after each decode step, and the prompt's
    log-probabilities, equal the reference's."""
    cfg = _cfg()
    ecfg = EngineConfig(model=cfg, max_batch_size=2, max_model_len=64,
                        kv_block_size=BLOCK, num_kv_blocks=32, dtype="float32",
                        prefill_buckets=[16], seed=11)
    runner = ModelRunner(ecfg)
    b, w, s = 2, ecfg.blocks_per_seq, 16
    seqs = _seqs([11, 16], seed=5)
    lens = [len(q) for q in seqs]
    btab = np.arange(b * w, dtype=np.int32).reshape(b, w)
    tok = np.zeros((b, s), np.int32)
    pos = np.zeros((b, s), np.int32)
    slot = np.full((b, s), -1, np.int32)
    targets = np.zeros((b, s), np.int32)
    for i, q in enumerate(seqs):
        n = lens[i]
        tok[i, :n], pos[i, :n], pos[i, n:] = q, np.arange(n), n - 1
        slot[i, :n] = btab[i, pos[i, :n] // BLOCK] * BLOCK + pos[i, :n] % BLOCK
        targets[i, : n - 1] = q[1:]
    zeros, ones = np.zeros(b, np.float32), np.ones(b, np.float32)

    def step(tok, pos, slot, ctx, last, **kw):
        return runner.step(tok, pos, btab, slot, ctx, last, zeros,
                           np.zeros(b, np.int32), ones, jax.random.PRNGKey(0), **kw)

    nt, lps, _, _, prompt_lps, _ = step(
        tok, pos, slot, np.asarray(lens, np.int32),
        np.asarray([n - 1 for n in lens], np.int32),
        targets=targets, want_prompt=True)
    nt, lps, prompt_lps = np.asarray(nt), np.asarray(lps), np.asarray(prompt_lps)
    for _ in range(2):        # two decode steps on the greedy continuation
        for i in range(b):
            seqs[i].append(int(nt[i]))
        p = np.asarray([[len(q) - 1] for q in seqs], np.int32)
        sl = np.stack([btab[i, p[i] // BLOCK] * BLOCK + p[i] % BLOCK for i in range(b)])
        prev_lps = lps
        nt, lps, *_ = step(np.asarray([[q[-1]] for q in seqs], np.int32), p, sl,
                           p[:, 0] + 1, np.zeros(b, np.int32))
        nt, lps = np.asarray(nt), np.asarray(lps)
        for i, q in enumerate(seqs):
            want = _reference_logprobs(runner.params, q)
            # greedy: the served token is the reference's argmax, at its log-prob
            assert int(np.argmax(want[len(q) - 2])) == q[-1]
            np.testing.assert_allclose(prev_lps[i], want[len(q) - 2, q[-1]],
                                       atol=F32_ATOL)
    for i, n in enumerate(lens):
        want = _reference_logprobs(runner.params, seqs[i][:n])
        np.testing.assert_allclose(
            prompt_lps[i, : n - 1],
            want[np.arange(n - 1), np.asarray(seqs[i][1:n])], atol=F32_ATOL)

    # the three counters moved, by phase, on the registry /metrics renders
    text = runner.compiles.registry.render()
    rows = {ln.split(" ")[0]: float(ln.split(" ")[1])
            for ln in text.splitlines() if ln.startswith("dynamo_moe_")}
    e, moe_layers, k = 8, 2, 3
    assert rows['dynamo_moe_expert_slots_total{phase="prefill"}'] == e * moe_layers
    assert rows['dynamo_moe_expert_slots_total{phase="decode"}'] == 2 * e * moe_layers
    assert rows['dynamo_moe_routed_rows_total{phase="prefill"}'] == sum(lens) * k * moe_layers
    assert rows['dynamo_moe_routed_rows_total{phase="decode"}'] == 2 * b * k * moe_layers
    active = rows['dynamo_moe_active_experts_total{phase="decode"}']
    assert 2 * k * moe_layers <= active <= 2 * min(e, b * k) * moe_layers
    # rendering again without a step adds nothing
    assert runner.compiles.registry.render() == text


def test_scopes_in_the_lowered_decode_program():
    """moe_route / moe_experts / moe_shared nest inside mlp, mla_cache
    inside attn, in the decode program's lowered text."""
    cfg, params = _params(jnp.float32)
    cache = deepseek.init_kv_cache(cfg, 8, BLOCK, jnp.float32)
    b = 2
    args = (jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32), cache,
            jnp.zeros((b, 4), jnp.int32), jnp.zeros((b, 1), jnp.int32),
            jnp.ones((b,), jnp.int32))
    text = jax.jit(lambda *a: deepseek.forward_counted(
        params, cfg, *a)).lower(*args).as_text(debug_info=True)
    for scope in ("mlp/moe_route", "mlp/moe_experts", "mlp/moe_shared",
                  "attn/mla_cache"):
        assert scope in text, scope


def test_explicit_pallas_mla_decode_runs_in_the_interpreter(monkeypatch):
    """attention_impl="pallas" + DYN_PALLAS_INTERPRET=1: the MLA decode
    kernel (the chip's decode route) runs in the Pallas interpreter
    through the model and agrees with the gather formulation."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    _, params = _params(jnp.float32)
    seq = _seqs([12], seed=9)[0]
    xla = _serve(_cfg("xla"), params, [seq], 3, 16, jnp.float32)[0]
    pallas = _serve(_cfg("pallas"), params, [seq], 3, 16, jnp.float32)[0]
    np.testing.assert_allclose(pallas, xla, atol=F32_ATOL)


@pytest.mark.parametrize("case", ["block_boundary", "batch_unequal", "batch_8"])
def test_kernel_route_equals_reference(case, monkeypatch):
    """The route the chip decodes on (the MLA decode kernel, here in the
    interpreter) against the reference: every row walks its own live
    pages of a table 8 blocks wide, whatever the other rows hold."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    spec = CASES[case]
    _, params = _params(jnp.float32)
    seqs = _seqs(spec["lengths"], seed=23)
    got = _serve(_cfg("pallas"), params, seqs, spec["n_decode"], spec["chunk"],
                 jnp.float32)
    for seq, lp in zip(seqs, got):
        np.testing.assert_allclose(lp, _reference_logprobs(params, seq),
                                   atol=F32_ATOL)


def test_scatter_latent_stacked_writes_one_layer_and_drops():
    """New rows land at layer li, slot block*bs + off of the
    [L, N, 1, bs, r] caches; -1 and out-of-layer slots drop."""
    l, n, bs, r, rd = 3, 4, BLOCK, 128, 128
    c0 = jnp.zeros((l, n, 1, bs, r), jnp.float32)
    kr0 = jnp.zeros((l, n, 1, bs, rd), jnp.float32)
    new_c = jnp.arange(1, 5, dtype=jnp.float32)[None, :, None] * jnp.ones((1, 4, 32))
    new_kr = -jnp.arange(1, 5, dtype=jnp.float32)[None, :, None] * jnp.ones((1, 4, 16))
    slots = jnp.asarray([[2 * bs + 3, -1, n * bs, 0]], jnp.int32)
    c, kr = jax.jit(deepseek.scatter_latent_stacked)(
        c0, kr0, new_c, new_kr, slots, jnp.int32(1))
    c, kr = np.asarray(c), np.asarray(kr)
    assert (c[1, 2, 0, 3, :32] == 1).all() and (c[1, 2, 0, 3, 32:] == 0).all()
    assert (kr[1, 2, 0, 3, :16] == -1).all()
    assert (c[1, 0, 0, 0, :32] == 4).all() and (kr[1, 0, 0, 0, :16] == -4).all()
    assert np.count_nonzero(c) == 2 * 32 and np.count_nonzero(kr) == 2 * 16
    assert not c[0].any() and not c[2].any()


def test_blocks_leave_the_device_in_the_wire_layout():
    """The latent cache keeps its one head in front of the page on the
    device ([L, N, 1, bs, r]); gathered blocks are [L, n, bs, 1, r] like
    every family's, lane padding off, and scatter takes them back."""
    cfg = _cfg()
    ecfg = EngineConfig(model=cfg, max_batch_size=2, max_model_len=64,
                        kv_block_size=BLOCK, num_kv_blocks=16, dtype="float32",
                        prefill_buckets=[16], seed=11)
    runner = ModelRunner(ecfg)
    layers, r, rd = HF["num_hidden_layers"], HF["kv_lora_rank"], HF["qk_rope_head_dim"]
    assert runner.kv_cache[0].shape == (layers, 16, 1, BLOCK, 128)
    assert runner.kv_cache[1].shape == (layers, 16, 1, BLOCK, 128)
    rs = np.random.RandomState(3)
    k = rs.standard_normal((layers, 3, BLOCK, 1, r)).astype(np.float32)
    v = rs.standard_normal((layers, 3, BLOCK, 1, rd)).astype(np.float32)
    runner.scatter_blocks([5, 2, 9], k, v)
    k2, v2 = runner.gather_blocks([5, 2, 9])
    assert k2.shape == k.shape and v2.shape == v.shape
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    # token t of block 2 is row t of its page
    np.testing.assert_array_equal(
        np.asarray(runner.kv_cache[0])[:, 2, 0, :, :r], k[:, 1, :, 0, :])
    kz, _ = runner.gather_blocks([0])
    assert not kz.any()
