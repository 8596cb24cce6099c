"""SDAR (``model_type: sdar_moe``; JetLM's SDAR-30B-A3B-Chat): generation
by diffusion over blocks on a Qwen3-MoE trunk.

The trunk is models/mixtral.py's (GQA, rotary embedding, per-head q/k
norms, softmax-routed experts with the chosen gates renormalised, no
shared expert) and nothing of it is copied here. Two things are the
family's own:

**The mask is causal over blocks and full inside one.** With block length
``B``, the query at position ``p`` sees key ``j`` iff ``j < (p // B + 1)
· B``: in prefill, in a block pass, on every attention route
(``ops/attention.block_causal``; ``make_gqa_attn_fn`` hands
``cfg.block_length`` down). The logits at position ``i`` are the
distribution of the token at position ``i``: a masked position predicts
itself, there is no shift by one.

**The decode unit is a block** (``decode_unit``). A row's pending unit is
``B`` ids at ``[n, n + B)``, each a token or ``mask_token_id``. A denoise
pass runs the trunk over the block's positions (which see every key below
``n`` and all ``B`` keys of the block), samples every masked position and
unmasks ``B // denoising_steps`` of them (the remainder to the first
passes) by ``remasking_strategy``; an unmasked position never changes
again, so a block is whole, and its tokens leave, when its last denoise
pass returns. What is kept of a block is the keys and values of its ``B``
final tokens. **A block is kept by the pass that first denoises the block
behind it; there is no commit pass.** That pass runs the ``2B`` positions
``[n, n + 2B)`` under the mask above: the whole block's positions see what
a pass of their own would show them, and the next block's see the whole
block's keys of the same layer, which the trunk scatters into the cache
before its kernel reads it. A block of ``B`` costs ``denoising_steps``
passes. The last block of a request is never kept: nothing reads its
keys. A denoise pass writes the keys and values of the block it denoises
into the block's own slots too, where the next pass overwrites them: no
other sequence can read a block that is not kept (a page is registered
only once the kept context has passed its end), so that is the same
mathematics as not writing them. A prompt of ``P`` tokens prefills its
first ``(P // B) · B`` under the block mask; the other ``P % B`` open the
first block, already unmasked.

Prefix sharing stays on: a page of 16 tokens is whole blocks, so its keys
depend on nothing past its end (``tests/test_block_decode.py`` holds a hit
and a miss to the same logits).

Scopes: ``attn`` (with ``block_attn`` around a block pass's kernel call
alone) and ``mlp`` with mixtral's ``moe_*``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..engine.config import ModelConfig
from ..ops.pallas_decode import VERIFY_MAX_S
from . import REMASKING, BlockUnit
from .llama import init_kv_cache  # noqa: F401  (one kind of page)
from .mixtral import (forward, forward_counted,  # noqa: F401
                      logits_from_hidden, param_specs, random_expert_stacks)

Params = Dict[str, Any]

# standard deviation of the served logits under random weights, and of
# q·k / sqrt(head_dim) (the query norm's weight: the per-head norms make
# the scores' size a matter of that weight alone; at 1.0 attention is
# spread thinly over every key and the pages' precision does not show:
# models/afmoe.py ATTN_SCORE_STD)
LOGIT_STD = 2.0
ATTN_SCORE_STD = 3.0

# what assumes one token a row a pass, path -> reason: engine settings are
# refused at start-up (ModelRunner), request options at admission
# (engine/serving.py, HTTP 400)
_IN_ORDER = ("penalties count tokens in the order they were generated; a "
             "block's are unmasked in any order")
_REFUSED = {
    "spec_ngram_tokens": "a proposal is verified one token after another; "
                         "a block's positions are unmasked in no such order",
    "spec_draft_model": "the draft's mirror cache is advanced one token a "
                        "step",
    "sp_size": "sequence-parallel prefill masks causally by position, not "
               "over blocks",
    "pp_size": "the pipeline stages a one-token decode step",
    "tp_size": "the block pass's head, sampling and choice at every "
               "position of a block are laid out for one device",
    "ep_size": "the block pass is laid out for one device",
    "host_kv_blocks": "an offloaded block is restored under a one-token "
                      "decode step's bookkeeping",
    "prefix_pull": "a pulled prefix ends where a one-token prefill samples",
    "multi_step_decode": "the fused burst feeds one sampled token back a "
                         "step",
    "decode_pipeline_depth": "the chained burst carries one pending token "
                             "a row on the device",
    "remote_prefill": "a prefill worker ends a prompt by sampling one "
                      "token; a block family's prefill samples none",
    "migration": "a migrated sequence brings one pending token, not a "
                 "block in flight",
    "presence_penalty": _IN_ORDER,
    "frequency_penalty": _IN_ORDER,
    "repetition_penalty": _IN_ORDER,
    "guided_decoding": "a grammar's mask follows the tokens left to right, "
                       "one at a time",
    "logit_bias": "the bias row is applied at one sampled position a step",
    "prompt_logprobs": "a position's logits are its own token's "
                       "distribution under the block mask, not the next "
                       "token's given its prefix",
}

# published keys only this family computes (models.published): under
# another model_type the trunk would be served one token a pass under a
# causal mask, and wrong tokens
CLAIMED_KEYS = ("block_length", "mask_token_id", "denoising_steps",
                "remasking_strategy", "confidence_threshold")
CLAIM = ("{keys} and no family here generates by diffusion over blocks "
         "under that model_type (sdar is the family whose decode unit is a "
         "block: models/sdar.py, model_type sdar_moe)")


def claimed_keys(config: dict) -> List[str]:
    return [k for k in CLAIMED_KEYS if config.get(k) is not None]


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    sdar_moe``. The five generation keys are no part of the published
    ``config.json`` (the published ``generate.py`` takes them as
    arguments); a served model's ``config.json`` carries them as its
    generation defaults, and one without ``block_length`` and
    ``mask_token_id`` is refused, not guessed at."""
    missing = [k for k in ("block_length", "mask_token_id")
               if config.get(k) is None]
    if missing:
        raise NotImplementedError(
            f"model_type sdar_moe needs {', '.join(missing)} in its "
            "config.json (the published generate.py takes them as "
            "arguments: block_length 4, mask_token_id 151669)")
    block = int(config["block_length"])
    steps = int(config.get("denoising_steps") or block)
    strategy = str(config.get("remasking_strategy")
                   or "low_confidence_dynamic")
    if not 1 < block <= VERIFY_MAX_S // 2 or 16 % block:
        raise NotImplementedError(
            f"block_length={block}: a block pass is one call of the verify "
            f"kernel over two blocks (S = 2 x block_length <= "
            f"{VERIFY_MAX_S}) and a block never straddles a page of 16")
    if not 1 <= steps <= block:
        raise ValueError(f"denoising_steps={steps} for a block of {block}")
    if strategy not in REMASKING:
        raise NotImplementedError(
            f"remasking_strategy={strategy!r} (one of {', '.join(REMASKING)})")
    if not 0 <= int(config["mask_token_id"]) < int(config["vocab_size"]):
        raise ValueError(f"mask_token_id={config['mask_token_id']} outside "
                         f"the vocabulary of {config['vocab_size']}")
    if config.get("shared_expert_intermediate_size") or config.get(
            "n_shared_experts"):
        raise NotImplementedError("sdar_moe with a shared expert")
    return dict(
        block_length=block, mask_token_id=int(config["mask_token_id"]),
        denoising_steps=steps, remasking_strategy=strategy,
        confidence_threshold=float(config.get("confidence_threshold", 0.9)),
    )


def decode_unit(cfg: ModelConfig) -> Optional[BlockUnit]:
    """The family's decode unit: a block of ``cfg.block_length``
    positions (models.BlockUnit)."""
    return BlockUnit(
        length=cfg.block_length, mask_id=cfg.mask_token_id,
        steps=cfg.denoising_steps or cfg.block_length,
        strategy=cfg.remasking_strategy or "low_confidence_dynamic",
        threshold=cfg.confidence_threshold, refused=_REFUSED)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, the recipe of the other expert
    families (models/afmoe.py, models/deepseek.py): an embedding of unit
    size; every projection a fan-in-scaled normal; every norm weighs 1.0
    but the query's, which weighs ``ATTN_SCORE_STD``; a layer's experts
    are one prototype plus a spread (``mixtral.random_expert_stacks``);
    the head is drawn for logits of standard deviation ``LOGIT_STD``.
    The per-head q/k norms exist here whatever a checkpoint brings."""
    l, d = cfg.num_layers, cfg.hidden_size
    h, kvh, hd, e = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts
    inter = cfg.moe_intermediate_size or cfg.intermediate_size
    keys = jax.random.split(key, 10)

    def w(key, shape, fan_in, gain=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    params: Params = {
        "embed": jax.random.normal(
            keys[0], (cfg.vocab_size, d), jnp.float32).astype(dtype),
        "layers": {
            "ln1": jnp.ones((l, d), dtype),
            "wq": w(keys[1], (l, d, h * hd), d),
            "wk": w(keys[2], (l, d, kvh * hd), d),
            "wv": w(keys[3], (l, d, kvh * hd), d),
            "wo": w(keys[4], (l, h * hd, d), h * hd),
            "q_norm": jnp.full((l, hd), ATTN_SCORE_STD, dtype),
            "k_norm": jnp.ones((l, hd), dtype),
            "ln2": jnp.ones((l, d), dtype),
            "router": w(keys[5], (l, d, e), d),
            "w_gate": random_expert_stacks(keys[6], (l, e, d, inter), d, dtype),
            "w_up": random_expert_stacks(keys[7], (l, e, d, inter), d, dtype),
            "w_down": random_expert_stacks(keys[8], (l, e, inter, d), inter,
                                           dtype),
        },
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[9], (d, cfg.vocab_size), d, LOGIT_STD)
    return params
