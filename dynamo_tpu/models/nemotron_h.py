"""Nemotron-H (``model_type: nemotron_h``; Nemotron 3 Super is one): one
trunk whose layers are **one sublayer each**, a Mamba-2 mixer, grouped-
query attention or an expert block, by the letters of
``hybrid_override_pattern`` (``M``, ``*``, ``E``). No layer has a second
norm or a second residual add. With ``n = RMSNorm(h)``:

- ``h = embed(ids)``; for each layer ``h ← h + Mix(n)``; logits
  ``= RMSNorm(h) W_head``, the head not tied, no multiplier anywhere;
- ``M``: Falcon-H1's mixer (``falcon_h1.make_ssm_fn``, whose docstring
  has the equations) with no µP vector; ``n_groups`` groups of ``B`` and
  ``C``, a head reads its group's, and the gated norm is over each
  group's ``d_inner / n_groups`` channels, the gate before the norm;
- ``*``: llama's prologue and kernels with **no positional term**
  (``make_gqa_attn_fn(rope=False)``; ``rope_theta`` and
  ``partial_rotary_factor`` are published and unused by the family's
  attention), scores scaled by ``head_dim ** -0.5``, no bias;
- ``E`` (LatentMoE): ``s = sigmoid(n W_r)`` in float32 over every
  published expert; ``S`` the ``num_experts_per_tok`` largest of ``s +
  b_corr``; ``g_e = routed_scaling_factor · s_e / Σ_S s``; ``z = n
  W_fc1`` (hidden → ``moe_latent_size``), once, before the dispatch;
  ``r = Σ_{e∈S} g_e W2_e relu(W1_e z)²`` (an expert is two matrices and
  has no gate matrix: ``mlp_hidden_act: relu2``); out ``= r W_fc2 +
  W_sh2 relu(W_sh1 n)²``. The router and the shared expert read the
  hidden-wide ``n``, not the latent (``mixtral.make_moe_mlp_fn`` with
  ``cfg.moe_latent_size``: scope ``moe_latent`` around the two latent
  projections).

**One expert-parallel rank's share**, as models/granite_hybrid.py:
``cfg.num_experts`` counts the experts whose weights are here; where
``cfg.experts_of`` is set the router keeps that published width and the
stacks hold rank ``cfg.expert_rank``'s. An expert layer adds that
share's part of the routed sum (summed in the latent, projected once)
and the whole shared expert, and that partial result goes on.

**Not run:** the multi-token-prediction module
(``num_nextn_predict_layers``, ``mtp_hybrid_override_pattern``): its
keys are read and its layers are not built.

**Stacked by kind, walked by the pattern.** ``params["mamba"]``,
``["attention"]``, ``["moe"]`` hold each kind's layers; the trunk is one
scan over the pattern's periods ``M [*] E`` (``trunk.walk_kinds``), so a
program holds one mixer body, one attention body and one expert body
whatever the pattern says (88 letters in 88 runs of one would be 88
bodies to compile). The expert stacks are kept whole and indexed by
layer inside the kernel. Two caches, each stacked over its own layers
(models/granite_hybrid.py): the k side ``(key pages [A, N, block, KVH,
D], SSM state [M, slots, H / 2, N, 2 P] float32)``, the v side ``(value
pages, conv window [M, slots, d_conv − 1, C])``. The family keeps
recurrent state, so it inherits Falcon-H1's ``SEQUENCE_STATE``.

Scopes: ``ssm`` with ``ssm_conv`` and ``ssm_state`` (decode) or
``ssm_scan`` (prefill) inside; ``attn``; ``mlp`` with ``moe_route``,
``moe_latent``, ``moe_experts`` and ``moe_shared`` inside.
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
from .llama import lm_logits, make_gqa_attn_fn, rms_norm
from .mixtral import (expert_share_fields, make_moe_mlp_fn,
                      random_expert_stacks, split_expert_stacks)
from .trunk import SlotCache, forward_over, walk_kinds

Params = Dict[str, Any]

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "moe"
# the published letters, in the order a period of the pattern holds them
LETTERS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}
PERIOD = (MAMBA, ATTENTION, EXPERTS)
# an expert's two matrices (it has no gate matrix)
EXPERT_STACKS = ("w_up", "w_down")

# Falcon-H1's: state by slot beside the pages, and every path it refuses;
# the expert stacks are kept whole and the share is stated, so the mesh's
# ep axis is refused too (models/granite_hybrid.py's two sentences)
SEQUENCE_STATE = dataclasses.replace(
    falcon_h1.SEQUENCE_STATE, refused={
        **falcon_h1.SEQUENCE_STATE.refused,
        "tp_size": "the mixer's heads and state, the two stacks of the "
                   "cache and the expert stacks are not sharded",
        "ep_size": "the expert stacks are kept whole and not sharded; one "
                   "rank's share is stated in the config (expert_share) "
                   "and served on a device of its own",
    })

# published keys only this family computes (models.published). The
# pattern, conv_kernel and the two prefixes are Falcon-H1's claim too and
# expert_share Granite's: under this model_type they are this family's,
# under a third they are refused with those families' sentences
CLAIMED_KEYS = ("hybrid_override_pattern", "mtp_hybrid_override_pattern",
                "moe_latent_size", "moe_shared_expert_intermediate_size",
                "conv_kernel", "expert_share")
CLAIMED_PREFIXES = ("mamba_", "ssm_")
CLAIM = ("{keys} and no family here implements them under that model_type "
         "(nemotron_h is the family whose layers are a Mamba-2 mixer, "
         "attention or an expert block alone by hybrid_override_pattern, "
         "with experts in a latent of moe_latent_size and a stated "
         "expert_share: models/nemotron_h.py, model_type nemotron_h)")


def claimed_keys(config: dict) -> List[str]:
    return sorted(k for k in config
                  if k in CLAIMED_KEYS or k.startswith(CLAIMED_PREFIXES))


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    nemotron_h``; what this module does not compute is refused here,
    before any weight is made. ``expert_share`` (``{"of_experts",
    "rank"}``) is the one key the published config lacks (as
    models/granite_hybrid.py): the experts held are under
    ``n_routed_experts``."""
    only = {
        "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
        "use_bias": False, "use_conv_bias": True, "mamba_hidden_act": "silu",
        "mlp_hidden_act": "relu2", "residual_in_fp32": False,
        "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
        "moe_shared_expert_overlap": False, "tie_word_embeddings": False,
        "sliding_window": None, "rope_scaling": None,
    }
    for key, value in only.items():
        if config.get(key, value) != value:
            raise NotImplementedError(
                f"nemotron_h with {key}={config[key]!r} (models/nemotron_h.py "
                f"computes {key}={value!r} only)")
    pattern = str(config.get("hybrid_override_pattern") or "")
    layers = int(config["num_hidden_layers"])
    unknown = sorted(set(pattern) - set(LETTERS))
    if len(pattern) != layers or unknown:
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern has {len(pattern)} letters "
            f"for {layers} layers, unknown letters {unknown} (M: mixer | *: "
            "attention | E: experts; models/nemotron_h.py computes no dense "
            "feed-forward layer)")
    hidden = int(config["hidden_size"])
    heads, d_head = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    groups = int(config.get("n_groups", 1))
    d_ssm = int(config.get("expand", 2)) * hidden
    if heads * d_head != d_ssm or heads % groups:
        raise ValueError(
            f"nemotron_h: expand x hidden_size {d_ssm} != mamba_num_heads "
            f"{heads} x mamba_head_dim {d_head}, or n_groups {groups} does "
            "not divide the heads")
    held = int(config.get("n_routed_experts", 0) or 0)
    latent = int(config.get("moe_latent_size", 0) or 0)
    shared = int(config.get("moe_shared_expert_intermediate_size", 0) or 0)
    if held <= 0 or latent <= 0 or shared <= 0:
        raise NotImplementedError(
            "nemotron_h without routed experts, without moe_latent_size or "
            "without a shared expert (models/nemotron_h.py computes an "
            "expert layer with all three)")
    return dict(
        layer_types=tuple(LETTERS[c] for c in pattern),
        rms_norm_eps=float(config.get("layer_norm_epsilon",
                                      config.get("norm_eps", 1e-5))),
        mamba_d_ssm=d_ssm, mamba_n_heads=heads, mamba_d_head=d_head,
        mamba_d_state=int(config["ssm_state_size"]), mamba_n_groups=groups,
        mamba_d_conv=int(config.get("conv_kernel", 4)),
        mamba_chunk_size=int(config.get("chunk_size", 128)),
        moe_latent_size=latent, mlp_hidden_act="relu2",
        shared_intermediate_size=shared,
        # sigmoid scores, the correction bias in the pick alone, one
        # group: DeepSeek-V3's router (mixtral.route_top_k)
        moe_scoring_func="sigmoid", topk_method="noaux_tc",
        n_group=1, topk_group=1,
        **expert_share_fields(config, held),
    )


# standard deviation of the served logits and of the attention layers'
# scores under random weights, the horizons a mixer head forgets over and
# the conv's bias under B and C: models/granite_hybrid.py says why each
# (there one group reads a long-lived state out by one number; here a
# group of sixteen heads does, eight numbers a token, and the same bias
# keeps each away from zero)
LOGIT_STD = 3.0
ATTN_SCORE_STD = 3.0
STATE_HORIZON = (1024.0, 4096.0)
BC_CONV_BIAS = (0.5, 1.5)
# mean of relu(x)^4 under a standard normal: the second moment of what
# the squared ReLU hands the second matrix
RELU2_MOMENT = 1.5


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families, every sublayer adding a vector of about unit size
    (models/granite_hybrid.py's reasons): the mixer as
    ``falcon_h1.init_mixer`` with the heads drawn to remember
    (``falcon_h1.remember_long``: ``STATE_HORIZON``, ``BC_CONV_BIAS``);
    the query for scores of standard deviation ``ATTN_SCORE_STD``; an
    expert's second matrix divided by the squared ReLU's size and by
    ``routed_scaling_factor``, so that the routed sum over every
    published expert and the shared expert add about one between them (a
    share adds its part of that); a layer's experts one prototype plus a
    spread (``mixtral.random_expert_stacks``), the experts held drawn as
    the stacks they are; the router's correction bias small and not
    zero, as models/deepseek.py; the untied head for logits of standard
    deviation ``LOGIT_STD``."""
    d, h, kvh, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    latent, inter = cfg.moe_latent_size, cfg.moe_intermediate_size
    shared, held = cfg.shared_intermediate_size, cfg.num_experts
    of = cfg.experts_of or held
    count = {kind: cfg.layer_types.count(kind) for kind in PERIOD}
    keys = iter(jax.random.split(key, 32))

    def w(shape, fan_in, gain=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def experts(shape, fan_in):
        return random_expert_stacks(next(keys), shape, fan_in, dtype)

    n = count[MAMBA]
    mixer = falcon_h1.init_mixer(cfg, [next(keys) for _ in range(6)], n, dtype)
    mixer = falcon_h1.remember_long(cfg, mixer, next(keys), next(keys),
                                    STATE_HORIZON, BC_CONV_BIAS)
    mixer["ln1"] = jnp.ones((n, d), dtype)
    n = count[ATTENTION]
    attention = {
        "ln1": jnp.ones((n, d), dtype),
        "wq": w((n, d, h * hd), d, ATTN_SCORE_STD),
        "wk": w((n, d, kvh * hd), d),
        "wv": w((n, d, kvh * hd), d),
        "wo": w((n, h * hd, d), h * hd),
    }
    n = count[EXPERTS]
    moe = {
        "ln1": jnp.ones((n, d), dtype),
        # as wide as the published experts, whatever is held
        "router": w((n, d, of), d),
        "router_bias": 0.05 * jax.random.normal(next(keys), (n, of),
                                                jnp.float32),
        "w_latent_in": w((n, d, latent), d),
        "w_up": experts((n, held, latent, inter), latent),
        # gains of 1 / (√2 s) on the routed sum and 1 / √2 on the shared
        # expert, written for the experts as a fan-in
        "w_down": experts(
            (n, held, inter, latent),
            inter * RELU2_MOMENT * 2.0 * cfg.routed_scaling_factor ** 2),
        "w_latent_out": w((n, latent, d), latent),
        "w_sh_up": w((n, d, shared), d),
        "w_sh_down": w((n, shared, d), shared * RELU2_MOMENT, 0.5 ** 0.5),
    }
    return {
        "embed": w((cfg.vocab_size, d), d),
        MAMBA: mixer, ATTENTION: attention, EXPERTS: moe,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, cfg.vocab_size), d, LOGIT_STD),
    }


def param_specs(params: Params) -> Dict:
    """Every weight replicated: tp > 1 and ep > 1 are refused."""
    return jax.tree.map(lambda _: P(), params)


CACHE_SPEC = SlotCache(kv=P(), state=P())


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(SlotCache(k pages [A, ...], SSM state [M, slots, H / 2, N,
    2 P] float32: Falcon-H1's ``ssm_record_shape``), SlotCache(v pages,
    conv window [M, slots, d_conv − 1, C]))``: ``A`` attention layers,
    ``M`` mixer layers; an expert layer keeps nothing. The conv window
    keeps the trunk's dtype whatever the pages' (Falcon-H1's)."""
    n_attn = cfg.layer_types.count(ATTENTION)
    n_mamba = cfg.layer_types.count(MAMBA)
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
    summed over the expert layers, the experts counted those held)."""
    del mesh    # one device: tp, ep, pp and sp are refused for the family
    b, s = tokens.shape
    if state_slots is None:
        state_slots = jnp.arange(b, dtype=jnp.int32)
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
    # a decode step's rows that hold a token: one list for the mixer's
    # and the attention's kernels and every layer
    live_rows = decode_live_rows(slot_mapping)
    ssm_fn = make_ssm_fn(cfg, b, s, positions, slot_mapping, state_slots,
                         live_rows)
    attn_fn = make_gqa_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens, None,
        live_rows=live_rows, rope=False)
    moe, stacks = split_expert_stacks(params[EXPERTS], EXPERT_STACKS)
    moe_fn = make_moe_mlp_fn(cfg, b, s, slot_mapping, stacks=stacks)
    k_side, v_side = kv_cache

    def layer(kind, lp, carry, i):
        """``h ← h + Mix(RMSNorm(h))``: one norm, one sublayer, one add."""
        hidden, (k, v, ssm, conv), stats = carry
        n = rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps)
        if kind == MAMBA:
            with jax.named_scope("ssm"):
                delta, ssm, conv = ssm_fn(n, lp, ssm, conv, i)
        elif kind == ATTENTION:
            with jax.named_scope("attn"):
                delta, k, v = attn_fn(n, lp, k, v, i)
        else:
            with jax.named_scope("mlp"):
                delta, aux = moe_fn(n, lp)
            stats = stats + aux
        return hidden + delta, (k, v, ssm, conv), stats

    hidden, (k, v, ssm, conv), stats = walk_kinds(
        cfg.layer_types, PERIOD, {**params, EXPERTS: moe}, layer,
        (hidden, (k_side.kv, v_side.kv, k_side.state, v_side.state),
         jnp.zeros((3,), jnp.int32)))
    return hidden, (SlotCache(k, ssm), SlotCache(v, conv)), stats


forward = forward_over(forward_counted)
logits_from_hidden = lm_logits
