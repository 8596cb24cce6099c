"""Mixtral-family sparse-MoE decoder with expert parallelism.

Same attention trunk as models/llama.py (GQA + RoPE + paged KV, one
lax.scan over stacked layer weights); the dense SwiGLU MLP is replaced by
a top-k routed mixture of experts.

TPU-first dispatch (GShard/Switch dense formulation, not the reference's
approach — the reference only passes moe_expert_parallel_size through to
TRT-LLM, SURVEY.md §2.12): routing produces a 0/1 dispatch tensor
[T, E, C] (token → expert slot with capacity C), expert compute is three
batched einsums over [E, C, D] — static shapes, MXU-shaped matmuls, no
scatter/gather — and the expert (E) dimension shards over the mesh's
``ep`` axis while expert intermediates shard over ``tp``. XLA inserts the
token all-to-alls implied by resharding [T, E, C] against [E, ...].

Tokens beyond an expert's capacity are dropped for that expert (their
residual stream still flows); capacity_factor sizes C.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from typing import Optional

from ..engine.config import ModelConfig
from .llama import (  # shared trunk + specs
    ATTN_LAYER_SPECS,
    base_specs,
    decoder_forward,
    init_kv_cache,
    logits_from_hidden,  # noqa: F401  (engine samples from hidden slices)
)
from .quant import dense, expert_einsum

Params = Dict[str, Any]
KVCache = Tuple[jax.Array, jax.Array]

__all__ = [
    "init_params", "init_kv_cache", "forward", "param_specs", "moe_mlp",
    "make_moe_mlp_fn", "expert_capacity",
]


def expert_capacity(
    num_tokens: int, num_experts: int, top_k: int, capacity_factor: float = 2.0
) -> int:
    """Per-expert slot count C. At factor 1.0 a perfectly balanced router
    drops nothing; headroom absorbs imbalance."""
    return max(1, int(num_tokens * top_k * capacity_factor / num_experts))


def _dispatch_combine(gate_vals, gate_idx, e: int, capacity: int,
                      valid: Optional[jax.Array],
                      ep_axis: Optional[str] = None):
    """Token-major slot assignment shared by every routed-MoE variant:
    one-hot the expert choices, queue tokens per expert with a cumsum,
    drop past ``capacity``, and return the [T, E, C] dispatch (0/1) and
    combine (gate-weighted) tensors. Pad tokens (``valid == 0``) claim
    no slots and contribute nothing.

    ``ep_axis`` (manual shard_map callers): the queueing runs over the
    GLOBAL expert set — capacity order identical to unsharded math —
    and the tensors are then sliced to this member's experts, making
    the caller's output a partial sum to psum over the axis."""
    t, top_k = gate_idx.shape
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)      # [T, K, E]
    if valid is not None:
        onehot = onehot * valid[:, None, None]
        gate_vals = gate_vals * valid[:, None]
    flat = onehot.reshape(t * top_k, e)
    pos = jnp.cumsum(flat, axis=0) - flat                        # queue pos
    keep = (pos < capacity).astype(jnp.float32) * flat
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    slot = (pos_oh * keep[..., None]).reshape(t, top_k, e, capacity)
    dispatch = slot.sum(axis=1)                                  # [T, E, C]
    combine = (slot * gate_vals[:, :, None, None]).sum(axis=1)
    if ep_axis is not None:
        e_local = e // jax.lax.axis_size(ep_axis)
        e0 = lax.axis_index(ep_axis) * e_local
        dispatch = lax.dynamic_slice_in_dim(dispatch, e0, e_local, axis=1)
        combine = lax.dynamic_slice_in_dim(combine, e0, e_local, axis=1)
    return dispatch, combine


def moe_mlp(
    x: jax.Array,         # [T, D] flattened tokens
    router_w: jax.Array,  # [D, E]
    w_gate: jax.Array,    # [E, D, I]
    w_up: jax.Array,      # [E, D, I]
    w_down: jax.Array,    # [E, I, D]
    top_k: int,
    capacity: int,
    valid: Optional[jax.Array] = None,  # [T] 1.0 = real token, 0.0 = pad
    scoring: str = "softmax",           # "softmax" (Mixtral/V2) | "sigmoid" (V3)
    norm_topk: bool = True,             # renormalize top-k gate weights
    routed_scaling: float = 1.0,        # DeepSeek routed_scaling_factor
    router_bias: Optional[jax.Array] = None,  # [E] V3 e_score_correction_bias
    n_group: int = 1,                   # DeepSeek group-limited routing
    topk_group: int = 1,                # groups the top-k may draw from
    ep_axis: Optional[str] = None,      # manual-shard_map expert axis
) -> jax.Array:
    """Top-k routed SwiGLU experts via dense one-hot dispatch.

    Pad tokens (``valid == 0``) claim no expert slots and contribute
    nothing — otherwise bucket padding would displace real tokens from
    capacity-bounded experts. Routing semantics are configurable to match
    the checkpoint family: Mixtral = softmax scores + renormalized top-k;
    DeepSeek-V2 = softmax, norm_topk_prob=False, scaled routed output;
    DeepSeek-V3 = sigmoid scores.

    ``ep_axis``: inside a manual shard_map where the expert stacks are
    sharded over that mesh axis (the pipelined pp x ep program), the
    routing (cheap, replicated) runs over the GLOBAL expert set and the
    dispatch/combine tensors are sliced to this member's experts; the
    returned value is then a PARTIAL sum the caller must psum over the
    axis (together with its tp reduction).
    """
    e = router_w.shape[1]

    logits = (x @ router_w).astype(jnp.float32)                          # [T, E]
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown moe scoring {scoring!r}")
    # selection scores vs combine weights: V3's bias steers expert
    # *selection* only; the combine weights are always the unbiased probs
    select = probs if router_bias is None else probs + router_bias[None, :]
    if n_group > 1:
        # DeepSeek group-limited routing (reference serves these configs
        # via vLLM passthrough, lib/engines/vllm0_8/src/lib.rs:374-380):
        # score each group of E/G experts — V3 "noaux_tc" by its top-2
        # sum of biased scores, V2 "group_limited_greedy" by its max —
        # keep the topk_group best groups, and zero every other expert's
        # selection score (HF masked_fill(~mask, 0.0); scores are
        # sigmoid/softmax outputs ≥ 0, so zeroed experts lose top_k to
        # any live one)
        t = select.shape[0]
        gsize = e // n_group
        grouped = select.reshape(t, n_group, gsize)
        if router_bias is not None:
            top2, _ = lax.top_k(grouped, min(2, gsize))
            group_scores = top2.sum(axis=-1)                       # [T, G]
        else:
            group_scores = grouped.max(axis=-1)                    # [T, G]
        _, gsel = lax.top_k(group_scores, topk_group)              # [T, KG]
        gmask = jax.nn.one_hot(gsel, n_group, dtype=select.dtype).sum(1)
        select = jnp.where(
            jnp.repeat(gmask, gsize, axis=1) > 0, select, 0.0
        )
    _, gate_idx = lax.top_k(select, top_k)                         # [T, K]
    gate_vals = jnp.take_along_axis(probs, gate_idx, axis=1)
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9
        )
    gate_vals = gate_vals * routed_scaling

    # e from router_w, not the expert stacks' .shape — they may be
    # QuantizedWeight (int8 serving), which carries no .shape
    dispatch, combine = _dispatch_combine(gate_vals, gate_idx, e, capacity,
                                          valid, ep_axis=ep_axis)

    x_e = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)   # [E, C, D]
    # expert_einsum: dispatches to int8 weights (scale on the out axis)
    # when the checkpoint is served quantized
    h = jax.nn.silu(expert_einsum("ecd,edi->eci", x_e, w_gate))
    h = h * expert_einsum("ecd,edi->eci", x_e, w_up)
    y_e = expert_einsum("eci,eid->ecd", h, w_down)                 # [E, C, D]
    return jnp.einsum("tec,ecd->td", combine.astype(x.dtype), y_e)


def gptoss_moe(
    x: jax.Array,          # [T, D] flattened tokens
    router_w: jax.Array,   # [D, E]
    router_b: jax.Array,   # [E]
    w_gate_up: jax.Array,  # [E, D, 2I] (gate/up INTERLEAVED on the last dim)
    b_gate_up: jax.Array,  # [E, 2I]
    w_down: jax.Array,     # [E, I, D]
    b_down: jax.Array,     # [E, D]
    top_k: int,
    capacity: int,
    valid: Optional[jax.Array] = None,
    alpha: float = 1.702,
    limit: float = 7.0,
    ep_axis: Optional[str] = None,
    tp_axis: Optional[str] = None,
) -> jax.Array:
    """GPT-OSS routed experts (semantics match HF modeling_gpt_oss):

    - router logits include the bias in BOTH selection and combine
      weights, softmaxed over the selected top-k only;
    - experts compute a clamped sigmoid-GLU: gate capped at +limit, up
      clamped to ±limit, out = (up+1) · gate·sigmoid(alpha·gate);
    - gate/up arrive interleaved in one fused projection, and every
      projection carries a bias.
    Same dense one-hot dispatch/capacity machinery as moe_mlp, incl.
    the manual-shard_map ``ep_axis`` contract (partial sums the caller
    psums over the axis).

    ``tp_axis`` (manual shard_map): the expert stacks arrive tp-SHARDED
    — w_gate_up/b_gate_up a contiguous even-aligned chunk of the
    interleaved 2I columns (whole gate/up pairs, matching the w_down row
    chunk of the same intermediate channels), so the local clamped-GLU
    is exact on its channels and the down contraction is a genuine
    tp-partial; b_down (an output-dim bias every member would add)
    scales by 1/tp so the caller's psum restores it once.
    """
    e = router_w.shape[1]

    logits = (x @ router_w).astype(jnp.float32) + router_b.astype(jnp.float32)
    gate_vals, gate_idx = lax.top_k(logits, top_k)                   # [T, K]
    gate_vals = jax.nn.softmax(gate_vals, axis=-1)

    dispatch, combine = _dispatch_combine(gate_vals, gate_idx, e, capacity,
                                          valid, ep_axis=ep_axis)

    x_e = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)     # [E, C, D]
    gu = expert_einsum("ecd,edi->eci", x_e, w_gate_up) + b_gate_up[:, None, :]
    gate = jnp.minimum(gu[..., 0::2], limit)
    up = jnp.clip(gu[..., 1::2], -limit, limit)
    h = (up + 1.0) * (gate * jax.nn.sigmoid(gate * alpha))
    y_e = expert_einsum("eci,eid->ecd", h, w_down)
    b = b_down[:, None, :]
    if tp_axis is not None:
        b = b / jax.lax.axis_size(tp_axis)
    y_e = y_e + b
    return jnp.einsum("tec,ecd->td", combine.astype(x.dtype), y_e)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    l, d_model = cfg.num_layers, cfg.hidden_size
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    inter = cfg.moe_intermediate_size or cfg.intermediate_size
    e = cfg.num_experts
    keys = jax.random.split(key, 12)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    params: Params = {
        "embed": w(keys[0], (cfg.vocab_size, d_model), d_model),
        "layers": {
            "ln1": jnp.ones((l, d_model), dtype),
            "wq": w(keys[1], (l, d_model, h * hd), d_model),
            "wk": w(keys[2], (l, d_model, kvh * hd), d_model),
            "wv": w(keys[3], (l, d_model, kvh * hd), d_model),
            "wo": w(keys[4], (l, h * hd, d_model), h * hd),
            "ln2": jnp.ones((l, d_model), dtype),
            "router": w(keys[5], (l, d_model, e), d_model),
            "w_gate": w(keys[6], (l, e, d_model, inter), d_model),
            "w_up": w(keys[7], (l, e, d_model, inter), d_model),
            "w_down": w(keys[8], (l, e, inter, d_model), inter),
        },
        "final_norm": jnp.ones((d_model,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[9], (d_model, cfg.vocab_size), d_model)
    return params


def param_specs(params: Params) -> Dict:
    """Megatron TP on attention; experts over ep, expert intermediates over
    tp (so one expert's matmuls still tensor-parallelize within its group)."""
    layer_specs = {
        **ATTN_LAYER_SPECS,
        "router": P(),
        "w_gate": P(None, "ep", None, "tp"),
        "w_up": P(None, "ep", None, "tp"),
        "w_down": P(None, "ep", "tp", None),
    }
    specs = base_specs(params)
    specs["layers"] = {k: layer_specs[k] for k in params["layers"]}
    return specs


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, S]
    positions: jax.Array,     # [B, S]
    kv_cache: KVCache,
    block_tables: jax.Array,  # [B, W]
    slot_mapping: jax.Array,  # [B, S]
    context_lens: jax.Array,  # [B]
    mesh=None,
    return_hidden: bool = False,
) -> Tuple[jax.Array, KVCache]:
    """Returns (logits [B, S, V], updated kv_cache): the shared decoder
    trunk (models/llama.py decoder_forward) with the routed-experts MLP.
    Bucket-padding tokens (slot_mapping < 0) are masked out of routing."""
    b, s = tokens.shape
    return decoder_forward(
        params, cfg, tokens, positions, kv_cache, block_tables,
        slot_mapping, context_lens, mesh=mesh,
        mlp_fn=make_moe_mlp_fn(cfg, b, s, slot_mapping),
        return_hidden=return_hidden,
    )


def make_moe_mlp_fn(cfg: ModelConfig, b: int, s: int, slot_mapping: jax.Array,
                    ep_axis: Optional[str] = None,
                    tp_axis: Optional[str] = None):
    """Routed-experts mlp_fn for run_layers/decoder_forward; shared with
    models/deepseek.py (DeepSeek MoE layers, incl. its shared expert).
    ``ep_axis`` (manual shard_map callers): see moe_mlp — the routed part
    becomes a partial sum the caller reduces over the axis. ``tp_axis``
    is accepted for factory-contract uniformity and ignored: the
    bias-free expert stacks tp-shard their inner dims, so the output is
    already a genuine tp-partial."""
    del tp_axis
    capacity = expert_capacity(
        b * s, cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_capacity_factor
    )
    valid = (slot_mapping.reshape(b * s) >= 0).astype(jnp.float32)

    def mlp(x, layer_params):
        y = moe_mlp(
            x.reshape(b * s, -1),
            layer_params["router"],
            layer_params["w_gate"], layer_params["w_up"], layer_params["w_down"],
            cfg.num_experts_per_tok, capacity, valid=valid,
            scoring=cfg.moe_scoring_func, norm_topk=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor,
            router_bias=layer_params.get("router_bias"),
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            ep_axis=ep_axis,
        )
        y = y.reshape(b, s, -1)
        if "w_sh_gate" in layer_params:
            # always-on shared expert(s) alongside the routed ones
            gate = jax.nn.silu(dense(x, layer_params["w_sh_gate"]))
            sh = dense(
                gate * dense(x, layer_params["w_sh_up"]),
                layer_params["w_sh_down"],
            )
            if ep_axis is not None:
                # the caller psums the routed PARTIAL over ep (and tp);
                # the shared expert's weights replicate across ep, so
                # every member computes the same contribution — scale by
                # 1/ep so the joint psum restores it exactly once (the
                # same trick gptoss uses for its replicated biases under
                # manual tp). Under tp the w_sh_* columns/rows shard
                # Megatron-style, so sh is already a genuine tp-partial.
                sh = sh / jax.lax.axis_size(ep_axis)
            y = y + sh
        return y

    return mlp
