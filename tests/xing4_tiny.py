"""The tiny ``xing4_0`` shape, its parameters, its reference and its
driver, for tests/test_xing4_reference.py and tests/test_xing4_family.py
(two files so that two workers share them; not collected)."""

import jax

from dynamo_tpu.models import deepseek

import served  # noqa: E402  (puts benchmark/ on the path)
from references import xing4 as reference  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
HF = {
    "architectures": ["Xing4ForCausalLM"], "model_type": "xing4_0",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": 24, "qk_rope_head_dim": 16, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 3,
    "n_shared_experts": 1, "first_k_dense_replace": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 2, "n_group": 1,
    "topk_group": 1, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "rope_scaling": YARN, "max_position_embeddings": 256,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -1.5, "mhc_h_res_clamp_max": 1.5,
}
BLOCK = 8
# float32 on both sides: the two differ in the order of products
# (absorbed against un-absorbed attention, sorted grouped products
# against every expert on every token, the streams' norm behind the
# projection against before it, tokens minor against tokens major) and
# in nothing else; 1e-4 is ~10x the differences seen (8e-6) and far
# under what a wrong coefficient does (1e-2 and up, below)
F32_ATOL = 1e-4
# bfloat16 weights, streams and cache (float32 coefficients) against the
# float32 reference on the same weights: the largest difference over the
# vocabulary at one position. Measured on this shape: median 0.05-0.08,
# nine in ten positions under 0.2; the rest are flipped near-ties of the
# router (as tests/test_deepseek_v3_reference.py), so the limit is on
# the bulk
BF16_MEDIAN = 0.15
BF16_ATOL = 0.4


def _cfg(attention_impl="xla", **replace):
    return served.cfg_of(HF, attention_impl=attention_impl, **replace)


def _params(dtype):
    cfg = _cfg()
    return cfg, deepseek.init_params(cfg, jax.random.PRNGKey(7), dtype)


def _reference_logprobs(params, seq):
    return served.reference_logprobs(reference, HF, params, seq)


def _serve(cfg, params, prompts, n_decode, chunk, dtype, fresh=False):
    """Prefill in chunks, then teacher-forced decode through the paged
    latent cache: the same walk as the other latent-attention family's
    tests (the tiny shapes share vocabulary and block size)."""
    return served.serve_chunks(deepseek, cfg, params, prompts, n_decode, chunk,
                               dtype, block=BLOCK, fresh=fresh)


_seqs = served.seqs
