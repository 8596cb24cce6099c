"""MiMo-V2 (``model_type: mimo_v2``; Xiaomi's MiMo-V2-Flash and V2.5):
grouped-query attention of two kinds by ``hybrid_layer_pattern`` (0 a
full layer, 1 a window layer), the kinds differing in their kv-head
count, their rope base and a learned sink, keys wider than values, over
a dense SwiGLU behind the first layers and routed experts with no shared
expert behind the others (``moe_layer_freq``, a list). Pre-norm residual
everywhere, final RMSNorm, untied head.

With ``h`` the residual stream, ``N_*`` RMS norms with a learned weight
and ``layernorm_epsilon``, layer ``l``, ``window = hybrid_layer_pattern[l]
== 1``, ``KVH = swa_num_key_value_heads if window else
num_key_value_heads``, ``r = int(head_dim · partial_rotary_factor)``:

    a      = N_in(h)
    q,k,v  = a Wq [H × head_dim], a Wk [KVH × head_dim], a Wv [KVH × v_head_dim]
    q,k    = rope on lanes [0, r) of every head (half rotation, pairs
             (i, i + r/2)), θ = swa_rope_theta if window else rope_theta;
             lanes [r, head_dim) as projected
    v      = attention_value_scale · v        (float32 product, rounded once)
    s_ij   = q_i·k_j / √head_dim,  j ≤ i,  and i − j < sliding_window if window
    window:  p_ij = exp(s_ij − m_i) / (Σ_j exp(s_ij − m_i) + exp(b_head − m_i)),
             b a learned float32 logit a query head
             (add_swa_attention_sink_bias): a key with no value
    full:    p = softmax_j(s)
    h      = h + (p v) Wo                     [H × v_head_dim → hidden]
    m      = N_post(h)
    y      = SwiGLU(m)                        moe_layer_freq[l] == 0
    y      = Σ_{e∈S} w_e FFN_e(m)             otherwise: models/mixtral.py's
             router (float32 sigmoid scores, the num_experts_per_tok
             largest of score + e_score_correction_bias, gates the
             unbiased scores renormalised), no shared expert; one
             expert-parallel rank's share as models/kimi_linear.py
             states it (``expert_share``)
    h      = h + y
    logits = N_final(h) W_head

**A page's shape by kind and by side.** A side of the cache is a
``trunk.KindCache`` and the engine serves its two pools as it serves
models/afmoe.py's (this family inherits that ``SEQUENCE_STATE``); here
the two stacks of a side differ in their kv heads and the two sides in
their lanes: the v side ``(full [Lf, N, page, KVH_f, lane_pad(v_head_dim)],
window [Lw, Nw, page, KVH_w, lane_pad(v_head_dim)])``; the k side the
same over ``lane_pad(head_dim)`` lanes, each kind's kept as one stack a
lane tile (a tuple of ``lane_pad(head_dim) / 128`` stacks of 128 lanes:
``ops/attention.split_lanes`` says why a page of 4 kv heads of 256 lanes
cannot be one array). ``ops/attention.attention`` and the kernels read
the values' lanes off the v side and return the values' width
(``v_dim``), and a score is the sum of the parts' products.

**One body a kind**: the weights are stacked by kind
(``params["full_attention"]``, ``["sliding_attention"]``, ``["dense"]``,
``["moe"]``), the dense prefix is a body a layer and the rest one scan
over periods (``trunk.walk_periods``: a run of full layers, then the run
of window layers behind it).

Scopes: ``attn`` with ``attn_full`` or ``attn_window`` inside (norm,
projections, rope, scatter, kernel, output), ``kv_full`` or ``kv_window``
around the kernel alone; ``mlp`` with mixtral's ``moe_*``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import (LANE, attention, lane_pad, scatter_stacked,
                             split_lanes)
from ..ops.live_rows import decode_live_rows
from . import afmoe
from .llama import apply_rope, lm_logits, rms_norm
from .mixtral import (expert_share_fields, make_moe_mlp_fn,
                      random_expert_stacks, split_expert_stacks)
from .quant import dense
from .trunk import KindCache, forward_over, scaled, walk_periods, window_slots

Params = Dict[str, Any]

FULL, WINDOW = afmoe.GLOBAL, afmoe.LOCAL

# afmoe's: the window kind's pages in a pool and behind a table of their
# own, and every path refused for that; the share is stated, so the
# mesh's ep axis stays refused, and the two stacks of a side differ in
# their kv heads, so tp does too
SEQUENCE_STATE = dataclasses.replace(
    afmoe.SEQUENCE_STATE,
    keeps="its window layers' pages (a kv-head count of their own) in a "
          "pool and a table of their own",
    refused={
        **afmoe.SEQUENCE_STATE.refused,
        "ep_size": "the expert stacks are kept whole and not sharded; one "
                   "rank's share is stated in the config (expert_share) "
                   "and served on a device of its own",
        "tp_size": "the two page stacks differ in their kv heads and "
                   "neither they nor the window table are sharded",
    })

# published keys only this family computes (models.published); the
# second group is dots3's claim too (and expert_share Granite's and
# Kimi's): this family's under its own model_type, theirs to refuse under
# a third
CLAIMED_KEYS = ("hybrid_layer_pattern", "attention_value_scale",
                "add_swa_attention_sink_bias", "add_full_attention_sink_bias")
SHARED_KEYS = ("sliding_window_size", "expert_share")
SHARED_PREFIXES = ("swa_",)
CLAIM = ("{keys} and no family here implements them under that model_type "
         "(mimo_v2 is the family whose window and full layers "
         "(hybrid_layer_pattern) differ in their kv heads (swa_*), with "
         "keys wider than values, rotary on part of a head, a scale on the "
         "values and a learned sink in the window layers: "
         "models/mimo_v2.py, model_type mimo_v2)")

LOGIT_STD = 2.0        # models/granite_hybrid.py says why the logits too
ATTN_SCORE_STD = 3.0   # models/falcon_h1.py says why 3.0
EXPERT_BIAS_STD = 0.05
# a window layer's sink logits under random weights: scores of deviation
# ATTN_SCORE_STD over a full window of 128 keys sum to about 128 · e^4.5
# ≈ e^9.35, so a logit near 8 takes a fifth of a full window's mass and
# one deviation either way a tenth to two fifths. A sink near 0 would
# take a ten-thousandth: rounding would hide it, and a program that
# dropped it would pass
SINK_MEAN, SINK_STD = 8.0, 1.0


def claimed_keys(config: dict) -> List[str]:
    mine = config.get("model_type") == "mimo_v2"
    return sorted(k for k in config if k in CLAIMED_KEYS or (mine and (
        k in SHARED_KEYS or k.startswith(SHARED_PREFIXES))))


def dense_prefix(freq, layers: int) -> int:
    """How many leading layers ``moe_layer_freq`` (a list, 0 a dense
    layer and 1 an expert layer) keeps dense; a list that is not zeros,
    then ones is refused, not approximated."""
    freq = [int(f) for f in freq]
    n_dense = freq.index(1) if 1 in freq else len(freq)
    if len(freq) != layers or freq != [0] * n_dense + [1] * (layers - n_dense):
        raise NotImplementedError(
            f"mimo_v2 with moe_layer_freq={freq} for {layers} layers "
            "(models/mimo_v2.py computes a dense prefix, then expert "
            "layers: zeros, then ones, one entry a layer)")
    return n_dense


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    mimo_v2``; what this module does not compute is refused here, before
    any weight is made. ``expert_share`` (``{"of_experts", "rank"}``) is
    the one key the published config lacks (as models/kimi_linear.py)."""
    heads, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    vd = int(config.get("v_head_dim") or hd)
    only = {
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "n_shared_experts": None,
        "hybrid_block_size": None, "num_nextn_predict_layers": 0,
        # a window layer's heads are a full layer's but for their kv heads
        "swa_num_attention_heads": heads, "swa_head_dim": hd,
        "swa_v_head_dim": vd,
    }
    if config.get("routed_scaling_factor") not in (None, 1, 1.0):
        raise NotImplementedError(
            f"mimo_v2 with routed_scaling_factor="
            f"{config['routed_scaling_factor']!r} (models/mimo_v2.py computes "
            "the published null only: the routed sum is not scaled)")
    for key, value in only.items():
        got = config.get(key, value)
        if (got or None) != (value or None):
            raise NotImplementedError(
                f"mimo_v2 with {key}={got!r} "
                f"(models/mimo_v2.py computes {key}={value!r} only)")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise NotImplementedError(
            f"mimo_v2 with rope_scaling={scaling!r} (models/mimo_v2.py "
            "computes the default frequencies only)")
    layers = int(config["num_hidden_layers"])
    pattern = [int(p) for p in config.get("hybrid_layer_pattern") or ()]
    if len(pattern) != layers or set(pattern) - {0, 1}:
        raise ValueError(
            f"mimo_v2: hybrid_layer_pattern has {len(pattern)} entries for "
            f"{layers} layers, values {sorted(set(pattern))} (0 a full "
            "layer | 1 a window layer)")
    window = int(config.get("sliding_window") or 0)
    if (set(pattern) != {0, 1} or window <= 0
            or int(config.get("sliding_window_size", window)) != window):
        raise NotImplementedError(
            "mimo_v2 without layers of both kinds, without sliding_window "
            "or with a sliding_window_size that differs from it "
            "(models/mimo_v2.py keeps a stack of pages a kind)")
    chunk = config.get("attention_chunk_size")
    if chunk not in (None, window):
        raise NotImplementedError(
            f"mimo_v2 with attention_chunk_size={chunk} beside a window of "
            f"{window} (models/mimo_v2.py computes the window alone)")
    rotary = float(config.get("partial_rotary_factor", 1.0))
    if int(hd * rotary) % 2 or not 0 < int(hd * rotary) <= hd:
        raise ValueError(
            f"mimo_v2: partial_rotary_factor {rotary} of a head of {hd} "
            "does not rotate a whole number of pairs")
    held = int(config.get("n_routed_experts", 0) or 0)
    if held <= 0:
        raise NotImplementedError(
            "mimo_v2 without routed experts (models/mimo_v2.py computes "
            "them behind every layer past the dense prefix)")
    return dict(
        layer_types=tuple(WINDOW if p else FULL for p in pattern),
        sliding_window=window,
        first_k_dense_replace=dense_prefix(
            config.get("moe_layer_freq") or [1] * layers, layers),
        rms_norm_eps=float(config.get("layernorm_epsilon", 1e-5)),
        rope_scaling=None,
        v_head_dim=vd,
        swa_num_kv_heads=int(config["swa_num_key_value_heads"]),
        swa_rope_theta=float(config["swa_rope_theta"]),
        partial_rotary_factor=rotary,
        attention_value_scale=float(config.get("attention_value_scale", 1.0)),
        swa_sink_bias=bool(config.get("add_swa_attention_sink_bias")),
        full_sink_bias=bool(config.get("add_full_attention_sink_bias")),
        n_shared_experts=0, moe_scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=1.0, n_group=1, topk_group=1,
        topk_method="noaux_tc",
        **expert_share_fields(config, held),
    )


def kv_heads(cfg: ModelConfig, kind: str) -> int:
    return cfg.swa_num_kv_heads if kind == WINDOW else cfg.num_kv_heads


def has_sink(cfg: ModelConfig, kind: str) -> bool:
    return cfg.swa_sink_bias if kind == WINDOW else cfg.full_sink_bias


def rotary_dim(cfg: ModelConfig) -> int:
    return int(cfg.head_dim * cfg.partial_rotary_factor)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families, each sublayer adding a vector of about unit size: ``Wq``
    times ``ATTN_SCORE_STD`` (scores of that deviation under the
    published scale), ``Wo`` divided by the value scale, the routed sum
    of every published expert about one, so the share held adds its
    share; the head for logits of deviation ``LOGIT_STD``; a layer's
    experts one prototype plus a spread (``mixtral.random_expert_stacks``),
    drawn as the stacks they are; the router's correction bias small and
    not zero; a sink's logit normal around ``SINK_MEAN``, float32."""
    d, h, hd, vd = cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    inter, moe_inter = cfg.intermediate_size, cfg.moe_intermediate_size
    held, of = cfg.num_experts, cfg.experts_of or cfg.num_experts
    n_dense = min(cfg.first_k_dense_replace, cfg.num_layers)
    n_moe = cfg.num_layers - n_dense
    keys = iter(jax.random.split(key, 32))

    def w(shape, fan_in, gain=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def mixers(kind):
        n, kvh = cfg.layer_types.count(kind), kv_heads(cfg, kind)
        out = {
            "ln1": jnp.ones((n, d), dtype),
            "wq": w((n, d, h * hd), d, ATTN_SCORE_STD),
            "wk": w((n, d, kvh * hd), d),
            "wv": w((n, d, kvh * vd), d),
            "wo": w((n, h * vd, d), h * vd, 1.0 / cfg.attention_value_scale),
        }
        if has_sink(cfg, kind):
            out["sinks"] = SINK_MEAN + SINK_STD * jax.random.normal(
                next(keys), (n, h), jnp.float32)
        return out

    params: Params = {
        "embed": w((cfg.vocab_size, d), 1),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, cfg.vocab_size), d, LOGIT_STD),
        FULL: mixers(FULL),
        WINDOW: mixers(WINDOW),
    }
    if n_dense:
        params["dense"] = {
            "ln2": jnp.ones((n_dense, d), dtype),
            "w_gate": w((n_dense, d, inter), d),
            "w_up": w((n_dense, d, inter), d),
            "w_down": w((n_dense, inter, d), inter),
        }
    if n_moe:
        def experts(shape, fan_in):
            return random_expert_stacks(next(keys), shape, fan_in, dtype)

        params["moe"] = {
            "ln2": jnp.ones((n_moe, d), dtype),
            # as wide as the published experts, whatever is held
            "router": w((n_moe, d, of), d),
            "router_bias": EXPERT_BIAS_STD * jax.random.normal(
                next(keys), (n_moe, of), jnp.float32),
            "w_gate": experts((n_moe, held, d, moe_inter), d),
            "w_up": experts((n_moe, held, d, moe_inter), d),
            "w_down": experts((n_moe, held, moe_inter, d), moe_inter),
        }
    return params


def param_specs(params: Params) -> Dict:
    """Every weight replicated: tp > 1 and ep > 1 are refused."""
    return jax.tree.map(lambda _: P(), params)


CACHE_SPEC = KindCache(full=P(), window=P())


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(KindCache(k full, k window), KindCache(v full, v window))``:
    ``num_blocks`` pages a full layer, ``window_blocks`` a window layer
    (page 0 of those is the one no sequence holds); a stack's kv heads
    its kind's, a side's lanes its own, the keys' a tuple of stacks of
    one lane tile each."""
    def stack(kind, blocks, lanes):
        return jnp.zeros((cfg.layer_types.count(kind), blocks, block_size,
                          kv_heads(cfg, kind), lanes), dtype)

    kinds = ((FULL, num_blocks), (WINDOW, window_blocks))
    return (
        KindCache(*(tuple(stack(kind, blocks, LANE)
                          for _ in range(lane_pad(cfg.head_dim) // LANE))
                    for kind, blocks in kinds)),
        KindCache(*(stack(kind, blocks, lane_pad(cfg.v_head_dim))
                    for kind, blocks in kinds)))


def make_attn_fn(cfg: ModelConfig, kind: str, b: int, s: int, positions,
                 slots, table, context_lens, live_rows):
    """``fn(a, layer_params, k_all, v_all, li) -> (o Wo, k_all, v_all)``
    over the page stacks of ``kind`` (``k_all`` the keys' parts),
    ``slots`` and ``table`` that kind's."""
    h, hd, vd = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    kvh = kv_heads(cfg, kind)
    theta = cfg.swa_rope_theta if kind == WINDOW else cfg.rope_theta
    kernel = "kv_window" if kind == WINDOW else "kv_full"

    def fn(x, lp, k_all, v_all, li):
        # (held before the head axis, as llama.qkv_prologue holds them)
        q, k, v = jax.lax.optimization_barrier(
            (dense(x, lp["wq"]), dense(x, lp["wk"]), dense(x, lp["wv"])))
        q = apply_rope(q.reshape(b, s, h, hd), positions, theta,
                       rotary_dim=rotary_dim(cfg))
        k = apply_rope(k.reshape(b, s, kvh, hd), positions, theta,
                       rotary_dim=rotary_dim(cfg))
        v = scaled(v.reshape(b, s, kvh, vd), cfg.attention_value_scale)
        *k_all, v_all = scatter_stacked(
            (*k_all, v_all), (*split_lanes(k), v), slots, li)
        k_all = tuple(k_all)
        with jax.named_scope(kernel):
            o = attention(
                q, k_all, v_all, table, positions, context_lens,
                impl=cfg.attention_impl, layer_idx=li,
                sliding_window=cfg.sliding_window if kind == WINDOW else None,
                sinks=lp.get("sinks"), live_rows=live_rows, v_dim=vd)
        return dense(o.reshape(b, s, h * vd), lp["wo"]), k_all, v_all

    return fn


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None, state_slots=None):
    """(hidden [B, S, D], cache, int32 [3]: ``mixtral.routing_stats``
    summed over the expert layers, the experts counted those held).
    ``block_tables`` is ``[B, 2 W]``: the full kind's table, then the
    window kind's (models/afmoe.py)."""
    # one device: tp, ep, pp and sp are refused for the family; no
    # records by slot
    del mesh, state_slots
    b, s = tokens.shape
    w = block_tables.shape[1] // 2
    tables = {FULL: block_tables[:, :w], WINDOW: block_tables[:, w:]}
    k_side, v_side = kv_cache
    page = v_side.full.shape[2]
    slots = {FULL: slot_mapping,
             WINDOW: window_slots(tables[WINDOW], positions, slot_mapping,
                                  page)}
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
    # the rows of a decode step that hold a token: one list for every
    # layer's kernel
    live_rows = decode_live_rows(slot_mapping)
    attn = {kind: make_attn_fn(cfg, kind, b, s, positions, slots[kind],
                               tables[kind], context_lens, live_rows)
            for kind in (FULL, WINDOW)}

    def mixer(kind, lp, hidden, pages, i):
        scope = "attn_full" if kind == FULL else "attn_window"
        with jax.named_scope("attn"), jax.named_scope(scope):
            delta, *own = attn[kind](
                rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps), lp,
                *pages[kind], i)
        return hidden + delta, {**pages, kind: tuple(own)}

    def experts():
        moe, stacks = split_expert_stacks(params["moe"])
        return moe, make_moe_mlp_fn(cfg, b, s, slot_mapping, stacks=stacks)

    hidden, pages, stats = walk_periods(
        params, cfg, (FULL, WINDOW), mixer, experts, hidden,
        {FULL: (k_side.full, v_side.full),
         WINDOW: (k_side.window, v_side.window)})
    cache = (KindCache(pages[FULL][0], pages[WINDOW][0]),
             KindCache(pages[FULL][1], pages[WINDOW][1]))
    return hidden, cache, stats


forward = forward_over(forward_counted)
logits_from_hidden = lm_logits
