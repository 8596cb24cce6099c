"""The tiny ``dots3_note`` shape, its parameters, its reference and its
driver, for tests/test_dots3_reference.py and tests/test_dots3_family.py
(two files so that two workers share them; not collected)."""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import dots3, mixtral

import served  # noqa: E402  (puts benchmark/ on the path)
from references import dots3 as reference  # noqa: E402

PAGE = 16
TOPK, WINDOW = 32, 17
FULL, SWA = "full_attention", "sliding_attention"
HF = {
    "architectures": ["Dots3NoteForCausalLM"], "model_type": "dots3_note",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 24, "num_hidden_layers": 9,
    "layer_types": [FULL, FULL, SWA, SWA, SWA, FULL, SWA, SWA, SWA],
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    "swa_attention_gate_type": "headwise", "attention_gate_type": "headwise",
    "apply_mla_qkv_lora_rescale": True, "index_head_dim": 16,
    "index_n_heads": 4, "index_topk": TOPK, "sliding_window_size": WINDOW,
    "first_k_dense_replace": 1, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "moe_layer_freq": 1, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "rope_theta": 80000000, "rope_scaling": None,
    "attention_bias": False, "tie_word_embeddings": False,
    "max_position_embeddings": 512,
}
# rank ``r`` of four: four of the sixteen experts held
SHARES = {r: {**HF, "n_routed_experts": 4,
              "expert_share": {"of_experts": 16, "rank": r}} for r in range(4)}
# float32 on both sides: the two differ in the order of the sums (absorbed
# paged attention in blocks against un-absorbed dense, the cutoff search
# against a sort, sorted grouped products against every expert in turn)
# and in nothing else; differences seen are 4e-5 in log-probability, and
# the smallest deliberate fault below reads over 1e-2
F32_ATOL = 1e-3
WRONG = 5e-3
# bfloat16 weights, activations and pages (indexer and router scores
# float32) against the float32 reference on the same weights, the
# largest difference over the vocabulary at one position; at a hidden
# size of 64 rounding is coarser than on the chip
BF16_MEDIAN = 0.4
BF16_ATOL = 2.0


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, hf=HF, seed=7, **over):
    cfg = _cfg(hf, **over)
    return cfg, dots3.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _share_of(params, rank, held=4):
    """Rank ``rank``'s experts of the uncut model's sixteen."""
    keep = slice(held * rank, held * rank + held)
    moe = {k: (v[:, keep] if k in mixtral.EXPERT_STACKS else v)
           for k, v in params["moe"].items()}
    return {**params, "moe": moe}


def _reference_logprobs(params, seq, hf=HF, lower=(), picked_out=None):
    """``picked_out``: a list the reference appends every full layer's
    picks to while it runs; such a run is built for itself."""
    if picked_out is None:
        return served.reference_logprobs(reference, hf, params, seq, pad=128,
                                         lower=lower)
    t_pad = -(-len(seq) // 128) * 128
    tokens = np.zeros(t_pad, np.int32)
    tokens[: len(seq)] = seq
    fn = reference.build(hf, t_pad, len(seq), lower=lower,
                         picked_out=picked_out)
    return np.asarray(fn(params, jnp.asarray(tokens),
                         jnp.arange(len(seq), dtype=jnp.int32)))


def Served(cfg, params, dtype, **kw):
    """32 pages of 16 a slot behind page 0, which is nobody's; the
    window kind's pages from a real ``WindowPool`` through the
    scheduler's own release and take (``served.Served``, as
    tests/test_afmoe_reference.py drives afmoe)."""
    return served.Served(dots3, cfg, params, dtype, block=PAGE, width=32,
                         slots=4, **kw)


_seqs, _serve_case = served.seqs, served.serve_case
