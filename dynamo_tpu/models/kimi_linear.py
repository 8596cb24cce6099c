"""Kimi Linear (``model_type: kimi_linear``): one trunk whose layers are
Kimi Delta Attention **or** latent attention with no positional term, by
the published 1-based lists ``linear_attn_config.kda_layers`` and
``full_attn_layers``; the first ``first_k_dense_replace`` layers are
followed by a dense SwiGLU, every other by routed experts and a shared
expert. Pre-norm residual everywhere, final RMSNorm, untied head.

With ``n = RMSNorm(x)``:

- ``kda`` (arXiv:2510.26692 section 3; ``H`` heads of ``K``, a state
  ``S ∈ R^{K×K}`` a head, key × value):
  ``[q̂ | k̂ | v] = SiLU(conv(n W_qkv))`` (causal depthwise convolution of
  ``short_conv_kernel_size`` taps a channel, no bias);
  ``q = q̂ / ‖q̂‖₂ · K^{-1/2}``, ``k = k̂ / ‖k̂‖₂`` a head;
  ``g = −exp(A_log_h) · softplus(n W_fa W_fb + dt_bias)``, a log-decay a
  *channel*; ``β = sigmoid(n W_b)`` a head;
  ``S̄ = Diag(e^g) S``, ``S ← S̄ + β k ⊗ (v − S̄ᵀ k)``, ``o = Sᵀ q``
  (``ops/kda.py``: prefill runs the chunked scan, decode the kernel on
  the records where they lie);
  output ``(RMSNorm_head(o) ⊙ sigmoid(n W_ga W_gb)) W_o``.
- ``mla``: ``deepseek.make_mla_attn_fn`` with the rotation left out
  (``mla_use_nope``): the query's and the key's ``qk_rope_head_dim``-wide
  parts are carried as projected, the cache line is Moonlight's.
- experts: ``mixtral.make_moe_mlp_fn`` (sigmoid scores in float32 over
  every published expert, the ``num_experts_per_token`` largest of score
  + correction bias, gates the scores renormalised over the picked and
  times ``routed_scaling_factor``), plus the shared expert.

**One expert-parallel rank's share**, as models/granite_hybrid.py:
``cfg.num_experts`` counts the experts whose weights are here; where
``cfg.experts_of`` is set the router keeps that published width and the
stacks hold rank ``cfg.expert_rank``'s. A layer adds that share's part
of the routed sum and the whole shared expert.

**Two caches, each stacked over its own layers** (models/minicpm_sala.py):
a side of the cache is a ``trunk.SlotCache``: the k side ``(latent
pages [A, N, 1, block, r], KDA state [M, slots, H, K, K] float32)``, the
v side ``(rope-key pages [A, N, 1, block, rd], conv window [M, slots,
taps − 1, 3 H K])``: ``A`` latent layers, ``M`` KDA layers. The state is
float32 whatever the trunk's dtype (the recurrence feeds its own
rounding back every token; not an option). The family keeps recurrent
state, so it inherits Falcon-H1's ``SEQUENCE_STATE``.

**What else is float32 whatever the trunk's dtype**, fixed choices too:
the residual stream (``forward_counted``) and, inside a KDA mixer,
everything between a product's accumulator and the next product's
operand (``make_kda_fn``: the projections hand their sums on in float32).
At whole depth the trunk's own bfloat16 rounding is what the benchmark's
comparison mostly reads (``benchmark/references/kimi_linear.py``); these
two take a quarter of it away at no cost the chip shows.

**One body a kind.** The weights are stacked by kind (``params["kda"]``,
``["mla"]``, ``["dense"]``, ``["moe"]``), not by run: after the dense
prefix the trunk is one scan over *periods* (``trunk.walk_periods``: a run
of KDA layers, then a run of latent layers), each run a loop of traced length over its kind's
stack, so a program holds one KDA body and one latent body whatever the
lists say (27 layers in 14 runs would otherwise be 14 bodies to
compile).

Scopes: ``kda`` (the whole mixer) with ``kda_conv``, ``kda_gate`` and
``kda_state`` (decode) or ``kda_scan`` (prefill) inside; ``attn`` with
``mla_cache`` inside; ``mlp`` with ``moe_route``, ``moe_experts`` and
``moe_shared`` inside.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import lane_pad
from ..ops.kda import kda_chunked_scan, kda_decode_step
from ..ops.live_rows import decode_live_rows
from . import falcon_h1
from .deepseek import make_mla_attn_fn, mla_softmax_scale
from .falcon_h1 import slot_records
from .llama import lm_logits, rms_norm
from .mixtral import (expert_share_fields, make_moe_mlp_fn,
                      random_expert_stacks, split_expert_stacks)
from .quant import QuantizedWeight, dense
from .trunk import SlotCache, forward_over, walk_periods

Params = Dict[str, Any]

KDA, MLA = "kda", "mla"

# Falcon-H1's: state by slot beside the pages, and every path it refuses;
# the expert stacks are kept whole and the share is stated, so the mesh's
# ep axis is refused too
SEQUENCE_STATE = dataclasses.replace(
    falcon_h1.SEQUENCE_STATE, refused={
        **falcon_h1.SEQUENCE_STATE.refused,
        "tp_size": "the KDA heads and state, the two stacks of the cache "
                   "and the expert stacks are not sharded",
        "ep_size": "the expert stacks are kept whole and not sharded; one "
                   "rank's share is stated in the config (expert_share) "
                   "and served on a device of its own",
    })

# published keys only this family computes (models.published).
# expert_share is Granite's claim too and num_shared_experts afmoe's:
# under this model_type they are this family's
CLAIMED_KEYS = ("linear_attn_config", "mla_use_nope", "expert_share",
                "num_shared_experts")
CLAIM = ("{keys} and no family here implements them under that model_type "
         "(kimi_linear is the family with Kimi Delta Attention or latent "
         "attention without a positional term by linear_attn_config, and "
         "a stated expert_share: models/kimi_linear.py, model_type "
         "kimi_linear; granite_hybrid the other with a stated expert_share: "
         "models/granite_hybrid.py; afmoe the other with "
         "num_shared_experts: models/afmoe.py)")
# the L2 norm of q̂ and k̂: x · rsqrt(Σ x² + eps), as the published kernel
L2_EPS = 1e-6


def claimed_keys(config: dict) -> List[str]:
    return sorted(k for k in config if k in CLAIMED_KEYS)


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    kimi_linear``; what this module does not compute is refused here,
    before any weight is made. ``expert_share`` (``{"of_experts",
    "rank"}``) is the one key the published config lacks: a configuration
    that holds one expert-parallel rank's share gives the experts held
    under ``num_experts`` and the published count and the rank there."""
    only = {
        "mla_use_nope": True, "q_lora_rank": None, "rope_scaling": None,
        "num_expert_group": 1, "num_nextn_predict_layers": 0,
        "moe_layer_freq": 1, "hidden_act": "silu",
    }
    for key, value in only.items():
        # a key left out reads as what is computed, but mla_use_nope,
        # which the published class leaves false
        got = config.get(key, False if key == "mla_use_nope" else value)
        if (got or None) != (value or None):
            raise NotImplementedError(
                f"kimi_linear with {key}={config.get(key)!r} "
                f"(models/kimi_linear.py computes {key}={value!r} only)")
    lin = config.get("linear_attn_config") or {}
    layers = int(config["num_hidden_layers"])
    kda = [int(i) for i in lin.get("kda_layers") or ()]
    full = [int(i) for i in lin.get("full_attn_layers") or ()]
    if sorted(kda + full) != list(range(1, layers + 1)):
        raise ValueError(
            f"kimi_linear: linear_attn_config.kda_layers {kda} and "
            f"full_attn_layers {full} do not name each of the layers 1 to "
            f"{layers} once (the lists count from 1)")
    held = int(config.get("num_experts", 0) or 0)
    if held <= 0 or not config.get("num_shared_experts"):
        raise NotImplementedError(
            "kimi_linear without routed experts or without a shared expert "
            "(models/kimi_linear.py computes both behind every layer past "
            "first_k_dense_replace)")
    return dict(
        layer_types=tuple(KDA if i in kda else MLA
                          for i in range(1, layers + 1)),
        kda_num_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
        kda_conv_kernel=int(lin.get("short_conv_kernel_size", 4)),
        max_position_embeddings=int(config.get(
            "model_max_length", config.get("max_position_embeddings", 4096))),
        num_experts_per_tok=int(config["num_experts_per_token"]),
        n_shared_experts=int(config["num_shared_experts"]),
        moe_scoring_func=config.get("moe_router_activation_func", "sigmoid"),
        norm_topk_prob=bool(config.get("moe_renormalize", True)),
        # one group: use_grouped_topk is then a plain top-k; the
        # correction bias steers the pick only (DeepSeek-V3's router)
        n_group=1, topk_group=1, topk_method="noaux_tc",
        **expert_share_fields(config, held),
    )


# standard deviation of the served logits and of the latent layers'
# scores under random weights (models/falcon_h1.py says why 3.0;
# models/granite_hybrid.py why the logits too)
LOGIT_STD = 3.0
ATTN_SCORE_STD = 3.0
# Tokens after which a channel of a KDA head has forgotten, 1 /
# (exp(A_log_h) · softplus(dt_bias_c)), drawn log-uniform between these
# two: a trained layer's channels spread from the local (a phrase) to
# the whole context the cells serve. A head's exp(A_log) is uniform in
# [1, 16] as the published initialisation; dt_bias is what gives the
# channel its horizon under it. The low-rank gate's own term moves a
# token's decay around that by e^±GATE_STD.
STATE_HORIZON = (32.0, 4096.0)
# standard deviation of the low-rank decay gate's term n W_fa W_fb (the
# output gate and β are plain fan-in draws: deviation 1, a sigmoid
# between 0.27 and 0.73 for two tokens in three, not saturated)
GATE_STD = 0.5
# root mean square of a sigmoid of a standard normal: what the output
# gate leaves of a normalised head
GATE_RMS = 0.54


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families, each sublayer adding a vector of about unit size: a KDA
    layer's output projection is divided by ``GATE_RMS``; the query of a
    latent layer is drawn for scores of standard deviation
    ``ATTN_SCORE_STD`` under the published scale; the routed sum and the
    shared expert add about one between them (the experts' down
    projections also divided by ``routed_scaling_factor``); the head for
    logits of standard deviation ``LOGIT_STD``. ``A_log`` and ``dt_bias``
    for a channel's horizon (``STATE_HORIZON``), the conv uniform in
    ±taps^-½ without bias; a layer's experts one prototype plus a spread
    (``mixtral.random_expert_stacks``), the experts held drawn as the
    stacks they are (a share is not a slice of a larger draw); the
    router's correction bias small and not zero, as models/deepseek.py."""
    d, h, kd = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
    hk, taps = h * kd, cfg.kda_conv_kernel
    n_kda = cfg.layer_types.count(KDA)
    n_mla = cfg.num_layers - n_kda
    n_dense = min(cfg.first_k_dense_replace, cfg.num_layers)
    n_moe = cfg.num_layers - n_dense
    ah, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    inter, moe_inter = cfg.intermediate_size, cfg.moe_intermediate_size
    held, of = cfg.num_experts, cfg.experts_of or cfg.num_experts
    keys = iter(jax.random.split(key, 40))

    def w(shape, fan_in, gain=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    params: Params = {
        "embed": w((cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, cfg.vocab_size), d, LOGIT_STD),
    }
    if n_kda:
        decay = uniform((n_kda, h), 1.0, 16.0)           # exp(A_log)
        horizon = jnp.exp(uniform((n_kda, h, kd), *map(jnp.log, STATE_HORIZON)))
        step = 1.0 / (decay[..., None] * horizon)        # softplus(dt_bias)
        params[KDA] = {
            "ln1": jnp.ones((n_kda, d), dtype),
            "w_qkv": w((n_kda, d, 3 * hk), d),
            "conv_w": uniform((n_kda, taps, 3 * hk), -taps ** -0.5,
                              taps ** -0.5).astype(dtype),
            "w_fa": w((n_kda, d, kd), d),
            "w_fb": w((n_kda, kd, hk), kd, GATE_STD),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).reshape(n_kda, hk),
            "A_log": jnp.log(decay),
            "w_b": w((n_kda, d, h), d),
            "w_ga": w((n_kda, d, kd), d),
            "w_gb": w((n_kda, kd, hk), kd),
            "o_norm": jnp.ones((n_kda, kd), dtype),
            "wo": w((n_kda, hk, d), hk, 1.0 / GATE_RMS),
        }
    if n_mla:
        scale = mla_softmax_scale(cfg)
        params[MLA] = {
            "ln1": jnp.ones((n_mla, d), dtype),
            "wq": w((n_mla, d, ah * (nope + rope)), d,
                    ATTN_SCORE_STD * (nope + rope) ** -0.5 / scale),
            "w_dkv": w((n_mla, d, r), d),
            "ln_kv": jnp.ones((n_mla, r), dtype),
            "w_kr": w((n_mla, d, rope), d),
            "w_uk": w((n_mla, r, ah, nope), r),
            "w_uv": w((n_mla, r, ah, vd), r),
            "wo": w((n_mla, ah * vd, d), ah * vd),
        }
    if n_dense:
        params["dense"] = {
            "ln2": jnp.ones((n_dense, d), dtype),
            "w_gate": w((n_dense, d, inter), d),
            "w_up": w((n_dense, d, inter), d),
            "w_down": w((n_dense, inter, d), inter),
        }
    if n_moe:
        sh = cfg.n_shared_experts * moe_inter

        def experts(shape, fan_in):
            return random_expert_stacks(next(keys), shape, fan_in, dtype)

        params["moe"] = {
            "ln2": jnp.ones((n_moe, d), dtype),
            # as wide as the published experts, whatever is held
            "router": w((n_moe, d, of), d),
            "router_bias": 0.05 * jax.random.normal(
                next(keys), (n_moe, of), jnp.float32),
            "w_gate": experts((n_moe, held, d, moe_inter), d),
            "w_up": experts((n_moe, held, d, moe_inter), d),
            # the routed sum and the shared expert add about one vector
            # of unit size between them: gains of 1 / (√2 s) and 1 / √2,
            # written for the experts as a fan-in
            "w_down": experts((n_moe, held, moe_inter, d),
                              moe_inter * 2.0 * cfg.routed_scaling_factor ** 2),
            "w_sh_gate": w((n_moe, d, sh), d),
            "w_sh_up": w((n_moe, d, sh), d),
            "w_sh_down": w((n_moe, sh, d), sh, 0.5 ** 0.5),
        }
    return params


def param_specs(params: Params) -> Dict:
    """Every weight replicated: tp > 1 and ep > 1 are refused."""
    return jax.tree.map(lambda _: P(), params)


CACHE_SPEC = SlotCache(kv=P(), state=P())


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(SlotCache(latent pages [A, N, 1, block, r], KDA state [M,
    slots, H, K, K] float32), SlotCache(rope-key pages [A, N, 1, block,
    rd], conv window [M, slots, taps − 1, 3 H K]))``: ``A`` latent
    layers in models/deepseek.py's layout, ``M`` KDA layers. The conv
    window keeps the trunk's dtype whatever the pages' (Falcon-H1's)."""
    n_kda = cfg.layer_types.count(KDA)
    n_mla = cfg.num_layers - n_kda
    h, kd = cfg.kda_num_heads, cfg.kda_head_dim
    act = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
    c = jnp.zeros((n_mla, num_blocks, 1, block_size,
                   lane_pad(cfg.kv_lora_rank)), dtype)
    kr = jnp.zeros((n_mla, num_blocks, 1, block_size,
                    lane_pad(cfg.qk_rope_head_dim)), dtype)
    state = jnp.zeros((n_kda, num_slots, h, kd, kd), jnp.float32)
    conv = jnp.zeros((n_kda, num_slots, cfg.kda_conv_kernel - 1, 3 * h * kd),
                     act)
    return SlotCache(c, state), SlotCache(kr, conv)


def _l2_norm(x: jax.Array, scale: float = 1.0) -> jax.Array:
    """float32, whatever comes in."""
    f = x.astype(jnp.float32)
    return f * (jax.lax.rsqrt(jnp.sum(f * f, axis=-1, keepdims=True) + L2_EPS)
                * scale)


def _dense_f32(x: jax.Array, w) -> jax.Array:
    """``quant.dense`` that hands the product on as accumulated, in
    float32: the operands are the trunk's dtype, the sum is not rounded
    to it (the matrix unit accumulates in float32 either way)."""
    if isinstance(w, QuantizedWeight):
        return dense(x, w).astype(jnp.float32)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def make_kda_fn(cfg: ModelConfig, b: int, s: int, positions, slot_mapping,
                state_slots, live_rows):
    """The mixer of one KDA layer: ``kda_fn(n1, layer_params, state_all,
    conv_all, li) -> (delta, state_all, conv_all)`` over the records
    stacked over the KDA layers, updated where they lie. ``live_rows``:
    the step's ``decode_live_rows(slot_mapping)``, the rows the decode
    kernel walks."""
    h, kd, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
    hk = h * kd
    valid = slot_mapping >= 0                       # [B, S] real tokens
    n_valid = valid.sum(axis=1).astype(jnp.int32)   # [B]
    decode = s == 1
    read, write = slot_records(b, decode, valid[:, 0], state_slots,
                               None if decode else positions[:, 0] == 0)
    f32 = jnp.float32

    def kda_fn(x, lp, state_all, conv_all, li):
        # float32 from the projection's sum to the normalised heads (the
        # window alone is kept in the trunk's dtype): a sum, four
        # products and a SiLU rounded at each step add up before the
        # delta rule reads the state back against k
        qkv = _dense_f32(x, lp["w_qkv"])                         # [B, S, 3HK]
        with jax.named_scope("kda_conv"):
            # the window: the slot's last taps − 1 inputs, then the chunk
            xp = jnp.concatenate([read(conv_all, li).astype(f32), qkv], axis=1)
            qkv = jax.nn.silu(sum(
                xp[:, t:t + s] * lp["conv_w"][t].astype(f32)
                for t in range(taps)))
            # the inputs that end at the row's last valid token (the old
            # window itself where the row has none)
            keep = n_valid[:, None] + jnp.arange(taps - 1)[None, :]
            conv_all = write(conv_all, li, jnp.take_along_axis(
                xp, keep[:, :, None], axis=1))
        q, k, v = (qkv[..., i * hk:(i + 1) * hk].reshape(b, s, h, kd)
                   for i in range(3))
        # q, k and v go on in float32: the kernel computes in it; the scan
        # rounds q and k once, with their decay, into its products and
        # takes the products' dtype from v, the activations'
        q, k = _l2_norm(q, kd ** -0.5), _l2_norm(k)
        with jax.named_scope("kda_gate"):
            f = _dense_f32(dense(x, lp["w_fa"]), lp["w_fb"])
            g = -(jnp.exp(lp["A_log"].astype(f32))[:, None]
                  * jax.nn.softplus(f + lp["dt_bias"]).reshape(b, s, h, kd))
            beta = jax.nn.sigmoid(_dense_f32(x, lp["w_b"]))
            # no token: the state passes
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        if decode:
            # the kernel updates the live rows' records where they lie
            with jax.named_scope("kda_state"):
                o, state_all = kda_decode_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    state_all, li, live_rows)
                o = o[:, None]
        else:
            with jax.named_scope("kda_scan"):
                o, s1 = kda_chunked_scan(q, k, v.astype(x.dtype), g, beta,
                                         read(state_all, li))
                state_all = write(state_all, li, s1)
        # the read-out stays float32 through its norm and gate
        o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(_dense_f32(dense(x, lp["w_ga"]), lp["w_gb"]))
        o = (o.reshape(b, s, hk) * gate).astype(x.dtype)
        # float32 into the float32 residual stream
        return _dense_f32(o, lp["wo"]), state_all, conv_all

    return kda_fn


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None, state_slots=None):
    """(hidden [B, S, D], cache, int32 [3]: ``mixtral.routing_stats``
    summed over the expert layers, the experts counted those held)."""
    del mesh    # one device: tp, ep, pp and sp are refused for the family
    b, s = tokens.shape
    if state_slots is None:
        state_slots = jnp.arange(b, dtype=jnp.int32)
    # the residual stream is float32 whatever the trunk's dtype (a fixed
    # choice of the program, as the state): after 54 sublayers of unit
    # size it is seven times one of them, and rounding it to bfloat16 at
    # every add would cost each sublayer a hundredth of what it adds.
    # Every sublayer reads it through its norm, in the trunk's dtype
    act = params["embed"].dtype
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens].astype(jnp.float32)
    # a decode step's rows that hold a token: one list for the state's
    # and the latent kernels
    live_rows = decode_live_rows(slot_mapping)
    kda_fn = make_kda_fn(cfg, b, s, positions, slot_mapping, state_slots,
                         live_rows)
    mla_fn = make_mla_attn_fn(cfg, b, s, positions, slot_mapping,
                              block_tables, context_lens, rope=False)
    # (made here, ahead of the dense prefix: where a program's operations
    # stand is part of its text, scripts/layer_loop.py --hash)
    moe, stacks = split_expert_stacks(params["moe"])
    moe_fn = make_moe_mlp_fn(cfg, b, s, slot_mapping, stacks=stacks)
    k_side, v_side = kv_cache

    def mixer(kind, lp, hidden, cache, i):
        c, kr, state, conv = cache
        n1 = rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps).astype(act)
        if kind == KDA:
            with jax.named_scope("kda"):
                delta, state, conv = kda_fn(n1, lp, state, conv, i)
        else:
            with jax.named_scope("attn"):
                delta, c, kr = mla_fn(n1, lp, c, kr, i)
        return hidden + delta, (c, kr, state, conv)

    hidden, (c, kr, state, conv), stats = walk_periods(
        params, cfg, (KDA, MLA), mixer, lambda: (moe, moe_fn), hidden,
        (k_side.kv, v_side.kv, k_side.state, v_side.state))
    return (hidden.astype(act), (SlotCache(c, state), SlotCache(kr, conv)),
            stats)


forward = forward_over(forward_counted)
logits_from_hidden = lm_logits
