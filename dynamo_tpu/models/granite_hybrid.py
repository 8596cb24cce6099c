"""Granite 4.0-H (``model_type: granitemoehybrid``): one trunk whose
layers are a Mamba-2 mixer **or** grouped-query attention, by
``layer_types``, each followed by routed experts and a shared expert.

With ``e = embedding_multiplier``, ``r = residual_multiplier``, ``a =
attention_multiplier``, ``s = logits_scaling``:

- ``h = embed(ids) · e``;
- ``h ← h + r · M(RMSNorm(h))``, ``M`` by the layer's entry:
  - ``mamba``: Falcon-H1's mixer (``falcon_h1.make_ssm_fn``, whose
    docstring has the equations) with no µP vector, one group of ``B``
    and ``C`` for all the heads, the gated norm over the whole
    ``d_ssm = mamba_expand · hidden_size``;
  - ``attention``: llama's prologue and kernels with **no rotary
    embedding** (``position_embedding_type: nope``) and the scores
    scaled by ``a``, not by ``head_dim ** -0.5``;
- ``n = RMSNorm(h)``; ``h ← h + r · (Σ_{e∈S} g_e FFN_e(n) + FFN_sh(n))``:
  mixtral's router and sorted grouped products (``S`` the
  ``num_experts_per_tok`` largest of the float32 logits ``n W_r``, ``g``
  the softmax over those: ``route_top_k(scoring="softmax",
  norm_topk=True)``), every expert and the shared one a SwiGLU
  (``[g | u] = n W_in``, ``W_out (SiLU(g) ⊙ u)``);
- logits ``= RMSNorm(h) Eᵀ / s``, the head tied to the embedding.

**One expert-parallel rank's share.** ``cfg.num_experts`` counts the
experts whose weights are here; where ``cfg.experts_of`` is set the
router keeps that published width and the expert stacks hold rank
``cfg.expert_rank``'s ``num_experts`` of them. A layer then adds that
share's part of the routed sum (``mixtral.routed_experts(held=...)``)
and the whole shared expert, and that partial result goes on to the next
layer: what the absent rank would have added is computed nowhere.

**Two caches, as models/minicpm_sala.py.** The attention layers hold
pages and no state, the mixer layers state and no pages, so a side of
the cache is a ``trunk.SlotCache`` stacked over each kind's own
layers: the k side ``(key pages [A, N, block, KVH, D], SSM state [M,
slots, H / 2, N, 2 P] float32)`` (two heads of 64 side by side on the
lanes: ``ops/ssm.state_to_record``), the v side ``(value pages, conv
window [M, slots, d_conv − 1, C])``. The trunk scans each homogeneous run of
``layer_types`` over that run's stacked weights (``params["runs"]``;
``trunk.walk_runs``),
the expert stacks kept whole and indexed by layer inside the kernel.
The family keeps recurrent state, so it inherits Falcon-H1's
``SEQUENCE_STATE`` and the engine's handling of it.

Scopes: ``ssm`` with ``ssm_conv`` and ``ssm_state`` (decode) or
``ssm_scan`` (prefill) inside; ``attn``; ``mlp`` with ``moe_route``,
``moe_experts`` and ``moe_shared`` inside.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import lane_pad
from ..ops.live_rows import decode_live_rows
from . import falcon_h1
from .falcon_h1 import conv_dim, make_ssm_fn, ssm_record_shape
from .llama import (layer_runs, lm_logits, make_gqa_attn_fn, rms_norm,
                    run_specs)
from .mixtral import (expert_share_fields, make_moe_mlp_fn,
                      random_expert_stacks, split_expert_stacks)
from .trunk import SlotCache, forward_over, scaled, walk_runs

Params = Dict[str, Any]

MAMBA, ATTENTION = "mamba", "attention"

# Falcon-H1's: state by slot beside the pages, and every path it refuses;
# the expert stacks are kept a run of layers and the share is stated, so
# the mesh's ep axis is refused too
SEQUENCE_STATE = dataclasses.replace(
    falcon_h1.SEQUENCE_STATE, refused={
        **falcon_h1.SEQUENCE_STATE.refused,
        "tp_size": "the mixer's heads and state, the two stacks of the "
                   "cache and the expert stacks are not sharded",
        "ep_size": "the expert stacks are kept a run of layers and not "
                   "sharded; one rank's share is stated in the config "
                   "(expert_share) and served on a device of its own",
    })

# published keys only this family computes (models.published): a
# trunk with the Granite multipliers, a shared expert by width or a
# stated share of its experts would be served by llama.py or mixtral.py
# without them. The mixer's keys and a layer_types that mixes kinds are
# Falcon-H1's and afmoe's claims too: under this model_type they are
# this family's, under a third they are refused with those sentences
CLAIMED_KEYS = ("residual_multiplier", "attention_multiplier",
                "logits_scaling", "shared_intermediate_size", "expert_share")
CLAIMED_PREFIXES = ("mamba_",)
CLAIM = ("{keys} and no family here implements them under that model_type "
         "(granite_hybrid is the family with the Granite multipliers, a "
         "shared expert by shared_intermediate_size, mamba or attention "
         "layers by layer_types and a stated expert_share: "
         "models/granite_hybrid.py, model_type granitemoehybrid)")


def claimed_keys(config: dict) -> List[str]:
    """``CLAIMED_KEYS`` and the mixer's keys where set, and a
    ``layer_types`` that names a ``mamba`` layer (Gemma-2, GPT-OSS and
    afmoe publish lists of attention kinds: theirs)."""
    keys = sorted(k for k in config
                  if k in CLAIMED_KEYS or k.startswith(CLAIMED_PREFIXES))
    if MAMBA in (config.get("layer_types") or ()):
        keys.append("layer_types")
    return keys


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    granitemoehybrid``; what this module does not compute is refused
    here, before any weight is made. ``expert_share`` (``{"of_experts",
    "rank"}``) is the one key the published config lacks: a
    configuration that holds one expert-parallel rank's share gives the
    experts held under ``num_local_experts`` and the published count
    and the rank there."""
    only = {
        "position_embedding_type": "nope", "mamba_proj_bias": False,
        "mamba_conv_bias": True, "attention_bias": False,
        "hidden_act": "silu", "normalization_function": "rmsnorm",
        "rope_scaling": None,
    }
    for key, value in only.items():
        if config.get(key) is not None and config[key] != value:
            raise NotImplementedError(
                f"granitemoehybrid with {key}={config[key]!r} "
                f"(models/granite_hybrid.py computes {key}={value!r} only)")
    kinds = tuple(config.get("layer_types") or ())
    layers = int(config["num_hidden_layers"])
    unknown = sorted(set(kinds) - {MAMBA, ATTENTION})
    if len(kinds) != layers or unknown:
        raise ValueError(
            f"granitemoehybrid: layer_types has {len(kinds)} entries for "
            f"{layers} layers, unknown kinds {unknown} (mamba | attention)")
    hidden = int(config["hidden_size"])
    heads, d_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    groups = int(config.get("mamba_n_groups", 1))
    d_ssm = int(config.get("mamba_expand", 2)) * hidden
    if heads * d_head != d_ssm or heads % groups:
        raise ValueError(
            f"granitemoehybrid: mamba_expand x hidden_size {d_ssm} != "
            f"mamba_n_heads {heads} x mamba_d_head {d_head}, or "
            f"mamba_n_groups {groups} does not divide the heads")
    held = int(config.get("num_local_experts", 0) or 0)
    if held <= 0 or not config.get("shared_intermediate_size"):
        raise NotImplementedError(
            "granitemoehybrid without routed experts or without a shared "
            "expert (models/granite_hybrid.py computes both in every layer)")
    return dict(
        layer_types=kinds,
        mamba_d_ssm=d_ssm, mamba_n_heads=heads, mamba_d_head=d_head,
        mamba_d_state=int(config["mamba_d_state"]), mamba_n_groups=groups,
        mamba_d_conv=int(config.get("mamba_d_conv", 4)),
        mamba_chunk_size=int(config.get("mamba_chunk_size", 256)),
        embedding_multiplier=float(config.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(config.get("residual_multiplier", 1.0)),
        attention_multiplier=float(config.get("attention_multiplier", 0.0)
                                   or 0.0),
        lm_head_multiplier=1.0 / float(config.get("logits_scaling", 1.0)),
        # the published config has no key for an expert's width:
        # intermediate_size is it
        moe_intermediate_size=int(config["intermediate_size"]),
        shared_intermediate_size=int(config["shared_intermediate_size"]),
        moe_scoring_func="softmax", norm_topk_prob=True,
        **expert_share_fields(config, held),
    )


# standard deviation of the served logits and of the attention layers'
# scores under random weights (models/falcon_h1.py says why 3.0), and
# the embedding's share of the residual stream's size at the last layer.
# The head is the embedding: a stream that still carried its token's
# embedding at unit size would meet it again in the head, 64 logit
# standard deviations above every other token, and every request would
# repeat its last prompt token with log-probability 0, which compares
# nothing. So the embedding is drawn small and every sublayer adds a
# vector of unit size (its output projection divided by r): after twenty
# sublayers the token's own row of the head reads about one standard
# deviation of the others. (The logits at 3.0 and not the other
# families' 2.0: a log-probability's difference scales with it, and at
# 3.0 the sound program and the bfloat16 state lie on their own sides of
# the reference's two limits with room, below.)
LOGIT_STD = 3.0
ATTN_SCORE_STD = 3.0
EMBED_SHARE = 1.0 / 128.0
# (one layer in ten attends, so what the pages are rounded to is a
# twentieth of the stream's sublayers: an fp8 page cache reads as the
# bfloat16 one does on the chip, and drawing the attention layer's
# output three or six times a mixer's, or its scores at 6.0, raised the
# sound program's differences with the fp8 one's: PERF.md section 6, PR
# 48. The attention layer's weights stay plain.)
#
# Tokens after which a mixer head has forgotten, 1 / (Δ · A), drawn
# log-uniform between these two. Mamba-2's own initialisation (A uniform
# in [1, 16] beside Δ log-uniform in [1e-3, 1e-1]) forgets in a dozen
# tokens at the median and reads its state out at 7 % of the skip term
# D · x: rounding such a state to bfloat16 every token cannot add up,
# and a comparison on the chip reads a bfloat16 state as it reads the
# float32 one. Nine layers in ten carry everything between tokens
# through that state, so the heads are drawn to remember across the
# contexts the cells serve (a prefill bucket to the whole of
# max_model_len): A = 1 / (Δ · horizon), Δ as published. The state then
# gives most of y (several times the skip term once a horizon has
# passed), what it is rounded to adds up over a horizon
# (references/granite_hybrid.py: the control the limits stand against),
# and a state forgotten between two tokens reads many times what it
# read.
STATE_HORIZON = (1024.0, 4096.0)
# The conv's bias under the B and C channels, uniform between these two
# (Mamba-2's own: ±d_conv^-½ under every channel; x keeps that). A state
# that remembers a thousand tokens is, to a few per cent, a multiple of
# the running mean of x ⊗ B: one fixed matrix a head, m_x ⊗ m_B. What a
# token reads out of it is that matrix times one number, m_B · C_t, the
# same for every head because the heads share one group, and the gated
# norm then divides y by it. Under the symmetric bias B and C are SiLUs
# of zero-mean inputs: m_B · C_t has a mean of three of its standard
# deviations and comes within a fifth of its median once in a thousand
# positions, and there every rounding of the step (the bfloat16 of B, C
# and x that the configuration states, not the state's) is multiplied
# by five or more: one token in a few thousand read 0.5 to 1.6 off where
# the mean was 0.04 (the CPU at a hidden size of 512; the chip's 0.78 at
# seed 1140390009, PERF.md section 6). With the bias positive, m_B · C_t
# stays within a fifth of its median at every position (a mean of
# eighteen standard deviations), the largest difference of 27 648
# positions is 0.37 at a mean of 0.038, and the state gives nearly all of
# y (the skip term D · x is then held by tier-1's float32 comparison and
# not by the chip's).
BC_CONV_BIAS = (0.5, 1.5)


def softmax_scale(cfg: ModelConfig) -> float:
    return cfg.attention_multiplier or cfg.head_dim ** -0.5


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families, each sublayer's output projection divided by
    ``residual_multiplier`` so that it adds a vector of unit size
    (Falcon-H1 divides by its multipliers the same way). The query
    projection is drawn for scores of standard deviation
    ``ATTN_SCORE_STD`` under the published scale; the embedding for a
    stream of ``EMBED_SHARE`` of its final size after the multiplier,
    and the final norm's weight for logits of standard deviation
    ``LOGIT_STD`` through the tied head and ``logits_scaling`` (see
    ``LOGIT_STD``). The mixer's small parameters as
    ``falcon_h1.init_mixer`` but ``A_log``, drawn for a head's horizon
    (``STATE_HORIZON``), and the conv's bias under B and C
    (``BC_CONV_BIAS``); a layer's experts one prototype plus a
    spread (``mixtral.random_expert_stacks``), the experts held drawn as
    the stacks they are (a share is not a slice of a larger draw)."""
    d, inter = cfg.hidden_size, cfg.moe_intermediate_size
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shared, held = cfg.shared_intermediate_size, cfg.num_experts
    res = cfg.residual_multiplier

    def w(key, shape, fan_in, gain=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    runs = []
    for r, (kind, _, n) in enumerate(layer_runs(cfg.layer_types)):
        keys = jax.random.split(jax.random.fold_in(key, r + 1), 17)
        if kind == MAMBA:
            run = falcon_h1.init_mixer(cfg, keys[:6], n, dtype,
                                       out_gain=1.0 / res)
            run = falcon_h1.remember_long(cfg, run, keys[13], keys[14],
                                          STATE_HORIZON, BC_CONV_BIAS)
        else:
            run = {
                "wq": w(keys[0], (n, d, h * hd), d,
                        ATTN_SCORE_STD * hd ** -0.5 / softmax_scale(cfg)),
                "wk": w(keys[1], (n, d, kvh * hd), d),
                "wv": w(keys[2], (n, d, kvh * hd), d),
                "wo": w(keys[3], (n, h * hd, d), h * hd, 1.0 / res),
            }
        run.update({
            "ln1": jnp.ones((n, d), dtype),
            "ln2": jnp.ones((n, d), dtype),
            # as wide as the published experts, whatever is held
            "router": w(keys[6], (n, d, cfg.experts_of or held), d),
            "w_gate": random_expert_stacks(keys[7], (n, held, d, inter), d,
                                           dtype),
            "w_up": random_expert_stacks(keys[8], (n, held, d, inter), d,
                                         dtype),
            # the routed sum and the shared expert add about one vector
            # of unit size between them: each down projection's gain is
            # 1 / (sqrt(2) r), written for the experts as a fan-in
            "w_down": random_expert_stacks(
                keys[9], (n, held, inter, d), inter * 2.0 * res * res, dtype),
            "w_sh_gate": w(keys[10], (n, d, shared), d),
            "w_sh_up": w(keys[11], (n, d, shared), d),
            "w_sh_down": w(keys[12], (n, shared, d), shared,
                           0.5 ** 0.5 / res),
        })
        runs.append(run)
    stream = (2 * cfg.num_layers) ** 0.5      # its size at the last layer
    embed_std = EMBED_SHARE * stream / cfg.embedding_multiplier
    params: Params = {
        "embed": (jax.random.normal(jax.random.fold_in(key, 0),
                                    (cfg.vocab_size, d), jnp.float32)
                  * embed_std).astype(dtype),
        "runs": runs,
        "final_norm": jnp.ones((d,), dtype),
    }
    if cfg.tie_word_embeddings:
        params["final_norm"] = jnp.full(
            (d,), LOGIT_STD / (cfg.lm_head_multiplier * embed_std * d ** 0.5),
            dtype)
    else:
        params["lm_head"] = w(jax.random.fold_in(key, 99), (d, cfg.vocab_size),
                              d, LOGIT_STD / cfg.lm_head_multiplier)
    return params


param_specs = run_specs    # tp > 1 and ep > 1 are refused for the family
CACHE_SPEC = SlotCache(kv=P(), state=P())


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(SlotCache(k pages [A, ...], SSM state [M, slots, H / 2, N,
    2 P] float32: Falcon-H1's ``ssm_record_shape``, two heads of 64 side
    by side), SlotCache(v pages, conv window [M, slots, d_conv − 1,
    C]))``: ``A`` attention layers, ``M`` mixer layers. The conv window
    keeps the trunk's dtype whatever the pages' (Falcon-H1's)."""
    n_attn = cfg.layer_types.count(ATTENTION)
    n_mamba = cfg.num_layers - n_attn
    pages = (n_attn, num_blocks, block_size, cfg.num_kv_heads,
             lane_pad(cfg.head_dim))
    act = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
    ssm = jnp.zeros((n_mamba, num_slots) + ssm_record_shape(cfg), jnp.float32)
    conv = jnp.zeros((n_mamba, num_slots, cfg.mamba_d_conv - 1,
                      conv_dim(cfg)), act)
    return (SlotCache(jnp.zeros(pages, dtype), ssm),
            SlotCache(jnp.zeros(pages, dtype), conv))


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None, state_slots=None):
    """(hidden [B, S, D], cache, int32 [3]: ``mixtral.routing_stats``
    summed over the layers, the experts counted those held)."""
    del mesh    # one device: tp, ep, pp and sp are refused for the family
    b, s = tokens.shape
    if state_slots is None:
        state_slots = jnp.arange(b, dtype=jnp.int32)
    with jax.named_scope("embed"):
        hidden = scaled(params["embed"][tokens], cfg.embedding_multiplier)
    # a decode step's rows that hold a token: one list for the mixer's
    # and the attention's kernels and every run of layers
    live_rows = decode_live_rows(slot_mapping)
    ssm_fn = make_ssm_fn(cfg, b, s, positions, slot_mapping, state_slots,
                         live_rows)
    attn_fn = make_gqa_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens, None,
        live_rows=live_rows, rope=False, scale=softmax_scale(cfg))
    res, eps = cfg.residual_multiplier, cfg.rms_norm_eps
    k_side, v_side = kv_cache

    def layer_of(kind, run):
        scanned, stacks = split_expert_stacks(run)
        mlp_fn = make_moe_mlp_fn(cfg, b, s, slot_mapping, stacks=stacks)
        scope, mixer = (("ssm", ssm_fn) if kind == MAMBA
                        else ("attn", attn_fn))

        def layer(carry, lp):
            hidden, (k_all, v_all), li = carry
            with jax.named_scope(scope):
                delta, k_all, v_all = mixer(
                    rms_norm(hidden, lp["ln1"], eps), lp, k_all, v_all, li)
            hidden = hidden + scaled(delta, res)
            with jax.named_scope("mlp"):
                y, aux = mlp_fn(rms_norm(hidden, lp["ln2"], eps), lp)
                hidden = hidden + scaled(y, res)
            return (hidden, (k_all, v_all), li + 1), aux

        return scanned, layer

    hidden, cache, stats = walk_runs(
        layer_runs(cfg.layer_types), params["runs"], layer_of, hidden,
        {MAMBA: (k_side.state, v_side.state),
         ATTENTION: (k_side.kv, v_side.kv)}, jnp.zeros((3,), jnp.int32))
    cache = (SlotCache(cache[ATTENTION][0], cache[MAMBA][0]),
             SlotCache(cache[ATTENTION][1], cache[MAMBA][1]))
    return hidden, cache, stats


def logits_from_hidden(hidden: jax.Array, params: Params,
                       cfg: ModelConfig) -> jax.Array:
    return scaled(lm_logits(hidden, params, cfg), cfg.lm_head_multiplier)


forward = forward_over(forward_counted, logits_from_hidden)
