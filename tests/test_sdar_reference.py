"""The served SDAR path (``model_type: sdar_moe``: prefill of whole blocks
under the block mask, then denoise passes and a commit pass a block
through the paged cache) against the benchmark's plain reference,
``benchmark/references/sdar.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy. Logits, not tokens.

Tiny ``sdar_moe`` shape that keeps the ratios: 4 query heads over 2 kv
heads with q/k norms, 8 experts of which 2 a token, no shared expert,
blocks of 4.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import mixtral, sdar

import served  # noqa: E402  (puts benchmark/ on the path)
from references import sdar as reference  # noqa: E402

MASK = 255
HF = {
    "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "max_position_embeddings": 512,
    "tie_word_embeddings": False, "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "sliding_window": None,
    "use_sliding_window": False, "max_window_layers": 3,
    "block_length": 4, "mask_token_id": MASK, "denoising_steps": 2,
    "remasking_strategy": "sequential", "confidence_threshold": 0.9,
}
PAGE, SLOTS, WIDTH = 16, 4, 8      # 33 pages, page 0 nobody's
# float32 on both sides: the two differ in the order of the sums (a walk
# of pages against a masked product, sorted rows of experts against every
# expert on every token) and in nothing else; differences seen are 1e-6
# to 4e-6 in log-probability, the smallest deliberate fault below reads
# over 1e-2
F32_ATOL = 1e-4


def _cfg(impl="xla", **over):
    return served.cfg_of({**HF, **over}, attention_impl=impl)


_PARAMS = {}


def _params(dtype=jnp.float32, seed=7):
    if (dtype, seed) not in _PARAMS:
        _PARAMS[dtype, seed] = sdar.init_params(
            _cfg(), jax.random.PRNGKey(seed), dtype)
    return _PARAMS[dtype, seed]


class OneSequence(served.Served):
    """One sequence's pages, scattered over the cache, driven as the
    engine drives it: a prefill chunk, or a block pass over the block's
    own slots. The mask token is no token's continuation: its logit is
    left out of the softmax."""

    def __init__(self, cfg, params, dtype=jnp.float32, fresh=False):
        super().__init__(sdar, cfg, params, dtype, block=PAGE, width=WIDTH,
                         slots=SLOTS, fresh=fresh)
        rng = np.random.default_rng(1)
        self.btab[0] = rng.permutation(
            np.arange(1, self.pages))[:WIDTH].astype(np.int32)

    def logprobs(self, logits):
        logits = np.array(logits, np.float32)
        logits[..., MASK] = -np.inf
        return np.asarray(jax.nn.log_softmax(logits, axis=-1))

    def run(self, ids, start):
        """Log-probabilities [len(ids), V] of ``ids`` at positions
        ``start..``, their keys and values written to their slots."""
        return self.prefill([(0, ids, start)], len(ids))[0]



def _state(seq, n, shown):
    """The sequence up to the block at ``n`` with the block's first
    ``shown`` positions final and the rest masked."""
    return list(seq[:n + shown]) + [MASK] * (4 - shown)


_STATE_PROGRAMS = {}


def _reference_state(params, ids):
    t = len(ids)
    if t not in _STATE_PROGRAMS:
        _STATE_PROGRAMS[t] = reference.state_logprobs(HF, t)
    return np.asarray(_STATE_PROGRAMS[t](params, jnp.asarray(ids, jnp.int32)))


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_prefill_and_block_passes_equal_the_references_states(tail, steps):
    """(a) A prompt of 36 + ``tail`` tokens and two blocks behind it,
    teacher-forced: the prefill of the prompt's whole blocks, then every
    state the schedule of ``steps`` denoise passes visits in each block
    (the prompt's tail unmasked in the first), then its commit pass; the
    log-probabilities at all four positions of every pass against one
    plain forward of that state."""
    params = _params()
    rng = np.random.default_rng(10 * tail + steps)
    prompt_len = 36 + tail
    seq = rng.integers(3, 250, prompt_len + 8).tolist()
    whole = prompt_len - tail
    served = OneSequence(_cfg(), params)
    got = served.run(seq[:whole], 0)
    want = _reference_state(params, seq[:whole])
    np.testing.assert_allclose(got, want, atol=F32_ATOL)
    quotas = reference.quotas(4, steps)
    for n, first in ((whole, tail), (whole + 4, 0)):
        shown = first
        for quota in quotas:
            if shown >= 4:
                break
            ids = _state(seq, n, shown)
            got = served.run(ids[n:], n)
            want = _reference_state(params, ids)[n:]
            np.testing.assert_allclose(got, want, atol=F32_ATOL)
            shown = min(4, shown + quota)
        # the commit pass: the block's final tokens, keys and values kept
        got = served.run(seq[n:n + 4], n)
        want = _reference_state(params, seq[:n + 4])[n:]
        np.testing.assert_allclose(got, want, atol=F32_ATOL)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_and_kernels_equal_the_reference(impl, monkeypatch):
    """A prompt prefilled in chunks whose edges are blocks' (16, 32, 20),
    then a block's passes, on the XLA route and on the kernels in the
    interpreter (the flash kernel in prefill, the verify kernel in the
    block pass)."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    params = _params()
    seq = np.random.default_rng(3).integers(3, 250, 76).tolist()
    served = OneSequence(_cfg(impl), params)
    want = _reference_state(params, seq[:68])
    at = 0
    for take in (16, 32, 20):
        got = served.run(seq[at:at + take], at)
        np.testing.assert_allclose(got, want[at:at + take], atol=F32_ATOL)
        at += take
    for shown in (0, 2):
        ids = _state(seq, 68, shown)
        np.testing.assert_allclose(served.run(ids[68:], 68),
                                   _reference_state(params, ids)[68:],
                                   atol=F32_ATOL)


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
@pytest.mark.parametrize("prompt_len", [3, 36, 37, 38, 39])
def test_one_forward_of_every_state_equals_a_forward_a_state(prompt_len, steps):
    """``build``, which the benchmark's ``correct`` runs, puts the clean
    sequence and a copy of the returned tokens' blocks a pass into one
    forward; ``state_logprobs`` is the definition, one forward a state.
    Row ``i`` of ``build`` is the definition's row of the state the
    returned token ``i`` was unmasked under (sequential)."""
    hf = {**HF, "denoising_steps": steps}
    params = _params()
    rng = np.random.default_rng(prompt_len)
    n_out = 10
    seq = rng.integers(3, 250, prompt_len + n_out).tolist()
    tokens = np.zeros(128, np.int32)
    tokens[:len(seq)] = seq
    got = np.asarray(reference.build(hf, 128, n_out)(
        params, jnp.asarray(tokens),
        jnp.arange(prompt_len - 1, prompt_len - 1 + n_out, dtype=jnp.int32)))
    quotas = reference.quotas(4, steps)
    for i in range(n_out):
        p = prompt_len + i
        n, o = p - p % 4, p % 4
        first = prompt_len % 4 if n == prompt_len - prompt_len % 4 else 0
        shown = first
        for quota in quotas:
            if o < shown + quota:
                break
            shown += quota
        want = _reference_state(params, _state(seq, n, shown))[p]
        np.testing.assert_allclose(got[i], want, atol=2e-5)


def _faulty(fault):
    """A served program with one deliberate fault."""
    cfg, params = _cfg(), _params()
    if fault == "causal_mask":          # one token a pass's mask
        cfg = dataclasses.replace(cfg, block_length=0)
    elif fault == "block_of_8":
        cfg = dataclasses.replace(cfg, block_length=8)
    elif fault == "no_qk_norm":
        layers = {k: v for k, v in params["layers"].items()
                  if k not in ("q_norm", "k_norm")}
        params = {**params, "layers": layers}
    elif fault == "unnormed_top_k":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif fault == "theta":
        cfg = dataclasses.replace(cfg, rope_theta=10000.0)
    return cfg, params


@pytest.mark.parametrize("fault", ["causal_mask", "block_of_8", "no_qk_norm",
                                   "unnormed_top_k", "theta", "shifted_head"])
def test_reference_tells_wrong_programs_apart(fault):
    cfg, params = _faulty(fault)
    seq = np.random.default_rng(4).integers(3, 250, 44).tolist()
    ids = _state(seq, 40, 2)
    served = OneSequence(cfg, params, fresh=True)
    served.run(ids[:40], 0)
    got = served.run(ids[40:], 40)
    want = _reference_state(_params(), ids)[40:]
    if fault == "shifted_head":         # read as the next token's
        got, want = got[:-1], want[1:]
    got, want = np.delete(got, MASK, -1), np.delete(want, MASK, -1)
    assert np.abs(got - want).max() > 1e-2


def test_the_trunk_is_mixtrals_and_random_weights_follow_the_recipe():
    assert sdar.forward is mixtral.forward
    assert sdar.forward_counted is mixtral.forward_counted
    assert sdar.param_specs is mixtral.param_specs
    params = _params()
    layers = params["layers"]
    assert float(layers["q_norm"][0, 0]) == sdar.ATTN_SCORE_STD
    assert float(layers["k_norm"][0, 0]) == 1.0
    np.testing.assert_allclose(float(jnp.std(params["embed"])), 1.0, rtol=0.05)
    # a layer's experts are one prototype plus a spread of a tenth
    w = np.asarray(layers["w_gate"][0])
    assert np.corrcoef(w[0].ravel(), w[1].ravel())[0, 1] > 0.95
    seq = np.random.default_rng(2).integers(3, 250, 64).tolist()
    logp = _reference_state(params, seq)
    finite = np.delete(logp, MASK, axis=-1)
    std = np.std(finite - finite.mean(-1, keepdims=True), axis=-1)
    np.testing.assert_allclose(std.mean(), sdar.LOGIT_STD, rtol=0.25)


def test_bfloat16_served_path_stays_near_the_reference():
    params = _params(jnp.bfloat16)
    seq = np.random.default_rng(5).integers(3, 250, 44).tolist()
    served = OneSequence(_cfg(), params, jnp.bfloat16)
    served.run(seq[:40], 0)
    ids = _state(seq, 40, 2)
    got = served.run(ids[40:], 40)
    want = _reference_state(params, ids)[40:]
    at = np.arange(4), np.argmax(want, -1)
    assert np.abs(got[at] - want[at]).max() < 0.25
