"""Mixtral-family sparse-MoE decoder with expert parallelism.

Same attention trunk as models/llama.py (GQA + RoPE + paged KV, one
lax.scan over stacked layer weights); the dense SwiGLU MLP is replaced by
a top-k routed mixture of experts.

Drop-free sorted dispatch (not the reference's approach — the reference
only passes moe_expert_parallel_size through to TRT-LLM, SURVEY.md
§2.12): router -> top-k -> the T·k (token, choice) rows sorted by expert
-> grouped products that read only the experts that have rows
(ops/grouped_matmul.py) -> un-sort and combine with the gate weights.
Shapes are static (T·k rows whatever was routed), nothing of size
[T, E, ·] is ever made, and every token's every chosen expert is
computed at any batch size. The expert (E) dimension shards over the
mesh's ``ep`` axis and expert intermediates over ``tp``: each member
computes the rows of its own experts and one psum combines them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.grouped_matmul import grouped_matmul
from .llama import (  # shared trunk + specs
    ATTN_LAYER_SPECS,
    base_specs,
    init_kv_cache,
    lm_logits,
    logits_from_hidden,  # noqa: F401  (engine samples from hidden slices)
    make_gqa_attn_fn,
    run_layers,
)
from .quant import QuantizedWeight, dense, mirror_specs

Params = Dict[str, Any]
KVCache = Tuple[jax.Array, jax.Array]

__all__ = [
    "init_params", "init_kv_cache", "forward", "param_specs", "moe_mlp",
    "make_moe_mlp_fn", "routed_experts", "route_top_k", "expert_share_fields",
]


def route_top_k(
    x: jax.Array,         # [T, D]
    router_w: jax.Array,  # [D, E]
    top_k: int,
    scoring: str = "softmax",
    norm_topk: bool = True,
    routed_scaling: float = 1.0,
    router_bias: Optional[jax.Array] = None,
    n_group: int = 1,
    topk_group: int = 1,
):
    """Router of the Mixtral / DeepSeek families -> (gate weights [T, K]
    float32, expert ids [T, K]).

    The logits are a float32 product of float32 operands, as published
    (``F.linear(x.float(), W.float())``): rounding them to bfloat16 first
    flips near-ties of a top-6 of 64. Mixtral = softmax scores +
    renormalized top-k; DeepSeek-V2 = softmax, norm_topk_prob=False,
    scaled routed output; DeepSeek-V3 = sigmoid scores, and its
    ``router_bias`` steers *selection* only: the combine weights are
    always the unbiased scores."""
    e = router_w.shape[1]
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)                # [T, E]
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown moe scoring {scoring!r}")
    select = probs if router_bias is None else (
        probs + router_bias.astype(jnp.float32)[None, :])
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
    return gate_vals * routed_scaling, gate_idx


def expert_matmul(xs: jax.Array, w, group_sizes: jax.Array,
                  eid: jax.Array, layer=None) -> jax.Array:
    """Grouped product of sorted rows with a stacked expert weight
    [E, in, out], plain or int8 (models/quant.QuantizedWeight). With
    ``layer`` the weight is every layer's stack [L, E, in, out] and the
    kernel indexes the layer (ops/grouped_matmul.py)."""
    if isinstance(w, QuantizedWeight):
        q, scale = w.q, w.scale.astype(xs.dtype)
        if layer is not None:
            q, scale = q[layer], scale[layer]
        y = grouped_matmul(xs, q.astype(xs.dtype), group_sizes)
        # per-output-channel scale by each row's expert (rows of no
        # expert carry a clipped id and a zero product)
        return y * scale[jnp.minimum(eid, scale.shape[0] - 1)]
    return grouped_matmul(xs, w, group_sizes, layer=layer)


def held_experts(n_experts: int, held=None, ep_axis: Optional[str] = None):
    """``(first, count)`` of the experts whose weights are here, of the
    ``n_experts`` the router scored: all of them; ``held``'s (a stated
    share: one expert-parallel rank's on a device of its own, the other
    ranks' experts nowhere); or, inside a shard_map over ``ep_axis``,
    this member's part of either."""
    first, count = (0, n_experts) if held is None else held
    if ep_axis is not None:
        count = count // jax.lax.axis_size(ep_axis)
        first = first + lax.axis_index(ep_axis) * count
    return first, count


def expert_share_fields(config: dict, held: int) -> dict:
    """ModelConfig's ``experts_of`` and ``expert_rank`` from a
    configuration's ``expert_share`` (``{"of_experts", "rank"}``: the one
    key a published config lacks, for a configuration that holds one
    expert-parallel rank's ``held`` experts of the published count).
    Without it every expert is held. ModelConfig refuses a share that
    does not divide the published count, or a rank past the last
    share."""
    share = config.get("expert_share") or {}
    return dict(
        experts_of=int(share.get("of_experts", held)) if share else 0,
        expert_rank=int(share.get("rank", 0)))


def routed_experts(x, gate_vals, gate_idx, valid, n_experts: int, experts,
                   weights, ep_axis: Optional[str] = None, layer=None,
                   held=None):
    """Drop-free dispatch shared by every routed-MoE variant.

    The T·k (token, choice) rows are sorted by expert; ``experts(xs, eid,
    group_sizes, layer, *weights)`` computes the sorted rows [R, D] -> [R, D]
    with grouped products that read only the experts that have rows; the
    result is un-sorted and combined with the gate weights. No capacity:
    every row of a real token is computed whatever the others chose. Pad
    tokens (``valid == 0``) get zero weight and belong to no group.

    ``ep_axis`` (inside a manual shard_map whose expert stacks are
    sharded over that axis): routing ran over the GLOBAL expert set;
    rows of another member's experts belong to no local group, so the
    return value is a PARTIAL sum the caller psums over the axis.
    ``held`` (``(first, count)``: the expert stacks are one rank's share
    of the ``n_experts`` routed over, on a device of its own): the same
    partial sum and nobody to add it to; a pick that falls on an absent
    expert is a row of no group, and nothing stands in for it.
    ``layer``: the weights are every layer's stacks (expert_matmul)."""
    t, top_k = gate_idx.shape
    with jax.named_scope("moe_route"):
        e0, e_local = held_experts(n_experts, held, ep_axis)
        eid = gate_idx.reshape(-1).astype(jnp.int32) - e0            # [R]
        mine = (eid >= 0) & (eid < e_local) & jnp.repeat(valid > 0, top_k)
        # rows of no local group sort behind the last expert
        eid = jnp.where(mine, eid, e_local)
        order = jnp.argsort(eid, stable=True)
        eid_sorted = eid[order]
        group_sizes = jnp.zeros((e_local + 1,), jnp.int32).at[eid].add(1)[:e_local]
        xs = x[order // top_k]                                       # [R, D]
    with jax.named_scope("moe_experts"):
        ys = experts(xs, eid_sorted, group_sizes, layer, *weights)
    with jax.named_scope("moe_route"):
        # the combine in float32: k products a token, summed once
        w_rows = jnp.where(mine, gate_vals.reshape(-1), 0.0)[order]
        ys = ys.astype(jnp.float32) * w_rows[:, None]
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        return ys[inverse].reshape(t, top_k, -1).sum(axis=1)


def _sharded_experts(mesh, weights, weight_specs, fn,
                     x, gate_vals, gate_idx, valid, layer):
    """``fn(x, gate_vals, gate_idx, valid, layer, weights, ep_axis,
    tp_axis)`` under GSPMD on a multi-device mesh: a Pallas call cannot
    be partitioned by the compiler, so the expert computation runs in a
    shard_map of its own — expert stacks over ``ep``, their
    intermediates over ``tp``, tokens over ``dp`` where they split —
    each member computes the rows of its own experts and one psum over
    (ep, tp) finishes the combine and the row-parallel contraction."""
    names = mesh.axis_names
    ep = "ep" if mesh.shape.get("ep", 1) > 1 else None
    tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
    reduce_axes = tuple(a for a in (ep, tp) if a)
    stacked = layer is not None

    def lead(w, spec):      # the layer axis of a stack kept whole
        whole = (stacked and not isinstance(w, QuantizedWeight)
                 and w.ndim == len(spec) + 1)
        return (None,) if whole else ()

    dp = "dp" if ("dp" in names and x.shape[0] % mesh.shape["dp"] == 0) else None
    # keyed, so that quant.mirror_specs walks it (int8 stacks carry a
    # scale whose spec drops the in axis)
    keyed = {str(i): w for i, w in enumerate(weights)}
    specs = mirror_specs(keyed, {
        str(i): P(*(a if a in names else None for a in lead(w, s) + s))
        for i, (w, s) in enumerate(zip(weights, weight_specs))
    })

    def local(x, gate_vals, gate_idx, valid, layer, keyed):
        y = fn(x, gate_vals, gate_idx, valid, layer if stacked else None,
               [keyed[str(i)] for i in range(len(keyed))], ep, tp)
        return lax.psum(y, reduce_axes) if reduce_axes else y

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None), P(dp), P(), specs),
        out_specs=P(dp, None), check_vma=False,
    )(x, gate_vals, gate_idx, valid,
      jnp.asarray(layer if stacked else 0, jnp.int32), keyed)


def _dispatch(mesh, ep_axis, tp_axis, weights, weight_specs, fn,
              x, gate_vals, gate_idx, valid, layer=None):
    """Run ``fn`` directly (one device, or inside a caller's manual
    shard_map that names ``ep_axis``/``tp_axis``) or in a shard_map of
    its own (GSPMD caller on a multi-device mesh)."""
    if valid is None:
        valid = jnp.ones((x.shape[0],), jnp.float32)
    if ep_axis is None and tp_axis is None and mesh is not None and mesh.size > 1:
        return _sharded_experts(mesh, weights, weight_specs, fn,
                                x, gate_vals, gate_idx, valid, layer)
    return fn(x, gate_vals, gate_idx, valid, layer, list(weights),
              ep_axis, tp_axis)


def _swiglu_experts(xs, eid, group_sizes, layer, w_gate, w_up, w_down):
    h = jax.nn.silu(expert_matmul(xs, w_gate, group_sizes, eid, layer))
    h = h * expert_matmul(xs, w_up, group_sizes, eid, layer)
    return expert_matmul(h, w_down, group_sizes, eid, layer)


_SWIGLU_SPECS = (("ep", None, "tp"), ("ep", None, "tp"), ("ep", "tp", None))


def _relu2_experts(xs, eid, group_sizes, layer, w_up, w_down):
    """Two matrices around a squared ReLU, no gate matrix
    (``mlp_hidden_act: relu2``, models/nemotron_h.py)."""
    h = jnp.square(jax.nn.relu(expert_matmul(xs, w_up, group_sizes, eid, layer)))
    return expert_matmul(h, w_down, group_sizes, eid, layer)


# activation -> (the expert body, its weights' specs): ``moe_mlp``'s
# ``w_gate`` is None where the body has no gate matrix
_EXPERT_BODIES = {"silu": (_swiglu_experts, _SWIGLU_SPECS),
                  "relu2": (_relu2_experts, _SWIGLU_SPECS[1:])}


def moe_mlp(
    x: jax.Array,         # [T, D] flattened tokens
    router_w: jax.Array,  # [D, E]
    w_gate: jax.Array,    # [E, D, I]
    w_up: jax.Array,      # [E, D, I]
    w_down: jax.Array,    # [E, I, D]
    top_k: int,
    valid: Optional[jax.Array] = None,  # [T] 1.0 = real token, 0.0 = pad
    scoring: str = "softmax",           # "softmax" (Mixtral/V2) | "sigmoid" (V3)
    norm_topk: bool = True,             # renormalize top-k gate weights
    routed_scaling: float = 1.0,        # DeepSeek routed_scaling_factor
    router_bias: Optional[jax.Array] = None,  # [E] V3 e_score_correction_bias
    n_group: int = 1,                   # DeepSeek group-limited routing
    topk_group: int = 1,                # groups the top-k may draw from
    ep_axis: Optional[str] = None,      # manual-shard_map expert axis
    mesh=None,                          # GSPMD caller's mesh
    layer=None,                         # w_* are every layer's stacks [L, E, ..]
    held=None,                          # (first, count): w_* are a share of E
    rows: Optional[jax.Array] = None,   # [T, K]: what is dispatched, if not x
    activation: str = "silu",           # "silu" (SwiGLU) | "relu2" (w_gate None)
):
    """Top-k routed experts, drop-free (routed_experts) -> (y [T, D],
    routing_stats): SwiGLU experts, or with ``activation="relu2"`` two
    matrices around a squared ReLU (``w_gate`` is then None). ``rows``:
    the router reads ``x`` and the experts ``rows``, a token's row in
    the width the experts work in (a latent: models/nemotron_h.py), and
    ``y`` is that wide.

    ``ep_axis``: inside a manual shard_map where the expert stacks are
    sharded over that mesh axis (the pipelined pp x ep program), the
    routing (cheap, replicated) runs over the GLOBAL expert set and each
    member computes the rows of its own experts; the returned value is
    then a PARTIAL sum the caller must psum over the axis (together with
    its tp reduction). ``mesh`` (GSPMD callers): on a multi-device mesh
    the expert computation runs in its own shard_map and the value is
    whole. ``layer``: the three expert weights are every layer's
    stacks, indexed inside the kernel (expert_matmul). ``held``: the
    three hold ``count`` of the router's experts, from ``first``; the
    value is then that share's part of the routed sum (routed_experts)."""
    e = router_w.shape[1]
    with jax.named_scope("moe_route"):
        gate_vals, gate_idx = route_top_k(
            x, router_w, top_k, scoring=scoring, norm_topk=norm_topk,
            routed_scaling=routed_scaling, router_bias=router_bias,
            n_group=n_group, topk_group=topk_group)

    experts, specs = _EXPERT_BODIES[activation]
    weights = tuple(w for w in (w_gate, w_up, w_down) if w is not None)

    def fn(x, gate_vals, gate_idx, valid, layer, weights, ep_axis, tp_axis):
        del tp_axis  # bias-free stacks: the output is a genuine tp-partial
        return routed_experts(x, gate_vals, gate_idx, valid, e,
                              experts, weights, ep_axis=ep_axis,
                              layer=layer, held=held)

    y = _dispatch(mesh, ep_axis, None, weights, specs, fn,
                  x if rows is None else rows, gate_vals, gate_idx, valid,
                  layer).astype(x.dtype)
    return y, routing_stats(gate_idx, valid, e, held)


def routing_stats(gate_idx: jax.Array, valid: Optional[jax.Array],
                  n_experts: int, held=None) -> jax.Array:
    """int32 [3]: the experts held that at least one real token chose,
    the (token, choice) rows of real tokens, and those of them that fell
    on an expert held (all of them where every expert is): the engine's
    counters; a caller that does not count drops it and the compiler
    drops the work."""
    with jax.named_scope("moe_route"):
        t, top_k = gate_idx.shape
        live = (jnp.ones((t,), jnp.int32) if valid is None
                else (valid > 0).astype(jnp.int32))
        hits = jnp.zeros((n_experts,), jnp.int32).at[gate_idx.reshape(-1)].add(
            jnp.repeat(live, top_k))
        first, count = held_experts(n_experts, held)
        here = hits[first:first + count]
        return jnp.stack([(here > 0).sum(), hits.sum(), here.sum()]
                         ).astype(jnp.int32)


def _gptoss_experts(alpha: float, limit: float, tp_axis: Optional[str]):
    def experts(xs, eid, group_sizes, layer, w_gate_up, b_gate_up, w_down,
                b_down):
        # the two weights may be every layer's stacks (``layer``); the
        # biases are small and always the layer's own
        row_e = jnp.minimum(eid, b_down.shape[0] - 1)   # rows of no group: masked
        gu = expert_matmul(xs, w_gate_up, group_sizes, eid, layer) + b_gate_up[row_e]
        gate = jnp.minimum(gu[..., 0::2], limit)
        up = jnp.clip(gu[..., 1::2], -limit, limit)
        h = (up + 1.0) * (gate * jax.nn.sigmoid(gate * alpha))
        b = b_down[row_e]
        if tp_axis is not None:
            b = b / jax.lax.axis_size(tp_axis)
        return expert_matmul(h.astype(xs.dtype), w_down, group_sizes, eid,
                             layer) + b

    return experts


_GPTOSS_SPECS = (("ep", None, "tp"), ("ep", "tp"), ("ep", "tp", None),
                 ("ep", None))


def gptoss_moe(
    x: jax.Array,          # [T, D] flattened tokens
    router_w: jax.Array,   # [D, E]
    router_b: jax.Array,   # [E]
    w_gate_up: jax.Array,  # [E, D, 2I] (gate/up INTERLEAVED on the last dim)
    b_gate_up: jax.Array,  # [E, 2I]
    w_down: jax.Array,     # [E, I, D]
    b_down: jax.Array,     # [E, D]
    top_k: int,
    valid: Optional[jax.Array] = None,
    alpha: float = 1.702,
    limit: float = 7.0,
    ep_axis: Optional[str] = None,
    tp_axis: Optional[str] = None,
    mesh=None,
    layer=None,            # w_gate_up / w_down are every layer's stacks
):
    """GPT-OSS routed experts (semantics match HF modeling_gpt_oss) ->
    (y [T, D], routing_stats):

    - router logits include the bias in BOTH selection and combine
      weights, softmaxed over the selected top-k only;
    - experts compute a clamped sigmoid-GLU: gate capped at +limit, up
      clamped to ±limit, out = (up+1) · gate·sigmoid(alpha·gate);
    - gate/up arrive interleaved in one fused projection, and every
      projection carries a bias (a gather by the row's expert id).
    Same drop-free dispatch as moe_mlp (routed_experts), incl. the
    manual-shard_map ``ep_axis`` contract (partial sums the caller
    psums over the axis) and the GSPMD ``mesh`` route.

    ``tp_axis`` (manual shard_map): the expert stacks arrive tp-SHARDED
    — w_gate_up/b_gate_up a contiguous even-aligned chunk of the
    interleaved 2I columns (whole gate/up pairs, matching the w_down row
    chunk of the same intermediate channels), so the local clamped-GLU
    is exact on its channels and the down contraction is a genuine
    tp-partial; b_down (an output-dim bias every member would add)
    scales by 1/tp so the caller's psum restores it once.
    """
    e = router_w.shape[1]
    with jax.named_scope("moe_route"):
        logits = jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST) + router_b.astype(jnp.float32)
        gate_vals, gate_idx = lax.top_k(logits, top_k)               # [T, K]
        gate_vals = jax.nn.softmax(gate_vals, axis=-1)

    def fn(x, gate_vals, gate_idx, valid, layer, weights, ep_axis, tp_axis):
        return routed_experts(x, gate_vals, gate_idx, valid, e,
                              _gptoss_experts(alpha, limit, tp_axis), weights,
                              ep_axis=ep_axis, layer=layer)

    y = _dispatch(mesh, ep_axis, tp_axis,
                  (w_gate_up, b_gate_up, w_down, b_down), _GPTOSS_SPECS,
                  fn, x, gate_vals, gate_idx, valid, layer).astype(x.dtype)
    return y, routing_stats(gate_idx, valid, e)


# Random routed experts are unrelated functions, so where rounding flips
# a near-tie of the router (the 6th and 7th of 64 scores) a token's
# output jumps as a trained model's does not: a trained router's
# near-ties are experts that resemble each other. Random experts are
# therefore drawn as one prototype a layer plus this share of their own
# (in standard deviations; 1.0 = unrelated): a flipped choice then moves
# the output by this share of what a wrong expert would otherwise, and
# every expert is still its own matrix in memory.
EXPERT_SPREAD = 0.1


def random_expert_stacks(key, shape, fan_in, dtype):
    """[L, E, in, out]: a layer's experts are one prototype plus a spread
    of their own (EXPERT_SPREAD), at a fan-in-scaled normal's variance
    (every family whose random experts a router picks among)."""
    kp, ko = jax.random.split(key)
    proto = jax.random.normal(kp, shape[:1] + (1,) + shape[2:], jnp.float32)
    own = jax.random.normal(ko, shape, jnp.float32)
    s = EXPERT_SPREAD
    return (((1.0 - s * s) ** 0.5 * proto + s * own)
            * (fan_in ** -0.5)).astype(dtype)


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
    state_slots=None,         # a family with records by slot reads it
) -> Tuple[jax.Array, KVCache]:
    """Returns (logits [B, S, V], updated kv_cache): the shared decoder
    trunk (models/llama.py) with the routed-experts MLP. Bucket-padding
    tokens (slot_mapping < 0) are masked out of routing."""
    hidden, kv_cache, _ = forward_counted(
        params, cfg, tokens, positions, kv_cache, block_tables,
        slot_mapping, context_lens, mesh=mesh)
    if return_hidden:
        return hidden, kv_cache
    with jax.named_scope("lm_head"):
        return lm_logits(hidden, params, cfg), kv_cache


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None):
    """``forward(return_hidden=True)`` and a third value, int32 [3]:
    (experts with a row, routed rows, rows of an expert held:
    routing_stats) summed over the layers — what the
    engine's step adds to its counters (ModelRunner._init_moe_counters)."""
    b, s = tokens.shape
    scanned, stacks = split_expert_stacks(params["layers"])
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
    attn_fn = make_gqa_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens, mesh)
    hidden, kv_cache, _, stats = run_layers(
        hidden, kv_cache, scanned, cfg, attn_fn,
        make_moe_mlp_fn(cfg, b, s, slot_mapping, mesh=mesh, stacks=stacks))
    return hidden, kv_cache, stats.sum(axis=0)


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def split_expert_stacks(layers: Dict, keys=EXPERT_STACKS
                        ) -> Tuple[Dict, Optional[Dict]]:
    """(the layer group for run_layers' scan, the expert stacks kept
    whole). A scan that slices the stacked expert weights hands the
    kernel a copy of the layer's experts every step; kept out of the
    scan, the kernel indexes the layer in HBM (ops/grouped_matmul.py)
    and the scanned group carries the layer's number, ``moe_layer``, in
    their place. int8 stacks stay in the scan (their dequantised copy
    is a layer's either way)."""
    if any(isinstance(layers[k], QuantizedWeight) for k in keys):
        return layers, None
    scanned = {k: v for k, v in layers.items() if k not in keys}
    scanned["moe_layer"] = jnp.arange(layers["router"].shape[0], dtype=jnp.int32)
    return scanned, {k: layers[k] for k in keys}


def make_moe_mlp_fn(cfg: ModelConfig, b: int, s: int, slot_mapping: jax.Array,
                    ep_axis: Optional[str] = None,
                    tp_axis: Optional[str] = None, mesh=None, stacks=None):
    """Routed-experts mlp_fn for run_layers, -> (y, moe_mlp's routing
    stats); shared with models/deepseek.py (DeepSeek MoE layers, incl.
    its shared expert).
    ``ep_axis`` (manual shard_map callers): see moe_mlp — the routed part
    becomes a partial sum the caller reduces over the axis. ``tp_axis``
    is accepted for factory-contract uniformity and ignored: the
    bias-free expert stacks tp-shard their inner dims, so the output is
    already a genuine tp-partial. ``mesh``: the GSPMD caller's mesh.
    ``stacks`` (split_expert_stacks): every
    layer's expert weights, whole; the layer's parameters then carry
    ``moe_layer`` in their place and the kernel indexes the layer.
    ``cfg.moe_latent_size`` (models/nemotron_h.py): the experts work in
    a latent, ``w_latent_in`` before the dispatch and ``w_latent_out``
    behind the combine (scope ``moe_latent``); ``cfg.mlp_hidden_act``
    ``relu2``: experts, routed and shared, of two matrices."""
    del tp_axis
    valid = (slot_mapping.reshape(b * s) >= 0).astype(jnp.float32)
    # one rank's share of the published experts (ModelConfig.experts_of)
    held = ((cfg.expert_rank * cfg.num_experts, cfg.num_experts)
            if cfg.experts_of else None)

    def mlp(x, layer_params):
        w = layer_params if stacks is None else stacks
        flat, rows = x.reshape(b * s, -1), None
        if cfg.moe_latent_size:
            # the experts work in a latent: a token goes into it once,
            # before the dispatch; the router reads the stream itself
            with jax.named_scope("moe_latent"):
                rows = dense(flat, layer_params["w_latent_in"])
        y, stats = moe_mlp(
            flat,
            layer_params["router"],
            w.get("w_gate"), w["w_up"], w["w_down"],
            cfg.num_experts_per_tok, valid=valid,
            scoring=cfg.moe_scoring_func, norm_topk=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor,
            router_bias=layer_params.get("router_bias"),
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            ep_axis=ep_axis, mesh=mesh,
            layer=None if stacks is None else layer_params["moe_layer"],
            held=held, rows=rows, activation=cfg.mlp_hidden_act,
        )
        if rows is not None:
            # the gated sum leaves the latent once
            with jax.named_scope("moe_latent"):
                y = dense(y, layer_params["w_latent_out"])
        y = y.reshape(b, s, -1)
        if "w_sh_up" in layer_params:
            # always-on shared expert(s) alongside the routed ones: a
            # SwiGLU, or without a gate matrix two matrices around a
            # squared ReLU (mlp_hidden_act relu2), on the stream itself
            with jax.named_scope("moe_shared"):
                if "w_sh_gate" in layer_params:
                    gate = jax.nn.silu(dense(x, layer_params["w_sh_gate"]))
                    hidden = gate * dense(x, layer_params["w_sh_up"])
                else:
                    hidden = jnp.square(jax.nn.relu(
                        dense(x, layer_params["w_sh_up"])))
                sh = dense(hidden, layer_params["w_sh_down"])
                if ep_axis is not None:
                    # the caller psums the routed PARTIAL over ep (and
                    # tp); the shared expert's weights replicate across
                    # ep, so every member computes the same contribution
                    # — scale by 1/ep so the joint psum restores it
                    # exactly once (the same trick gptoss uses for its
                    # replicated biases under manual tp). Under tp the
                    # w_sh_* columns/rows shard Megatron-style, so sh is
                    # already a genuine tp-partial.
                    sh = sh / jax.lax.axis_size(ep_axis)
                y = y + sh
        return y, stats

    return mlp
