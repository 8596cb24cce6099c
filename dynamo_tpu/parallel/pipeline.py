"""Pipeline parallelism: layers sharded by stage, microbatches in flight.

The last parallelism axis from SURVEY.md §2.12 (reference analog: the
vllm0_7 engine's Ray-based pipeline_parallel_size pass-through,
lib/engines/vllm0_7/src/{ray.rs,vllm_inc.py:38} — the reference never
implements PP itself, it forwards a flag to vLLM).

TPU-first formulation — a *collective* GPipe schedule inside one SPMD
program (no per-stage processes, no RPC):

- the mesh's ``pp`` axis holds P stages; the stacked layer params
  [L, ...] reshape to [P, L/P, ...] and shard on the leading axis, so
  under ``shard_map`` each device owns its stage's layer block and the
  per-layer ``lax.scan`` runs over just L/P layers;
- the paged KV cache [L, N, bs, KVH, D] shards the same way — each
  stage reads/writes only its own layer slab, in place;
- the batch splits into M microbatches; for T = M + P - 1 ticks every
  device runs the same step: compute its layer block on the microbatch
  it currently holds, then ``lax.ppermute`` the activations one stage
  down the ring. Stage 0 injects (embedding) and the last stage
  collects; warm-up/drain ticks carry garbage that is masked out — KV
  writes use the scatter drop sentinel so invalid ticks touch nothing.

Embedding/logits stay replicated (cheap relative to the trunk); combine
``pp`` with ``tp``/``dp`` axes by nesting specs — this module only owns
the pp dimension.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..models import llama

KVCache = Tuple[jax.Array, jax.Array]


def stage_params(params, num_stages: int):
    """Reshape stacked layer params [L, ...] → [P, L/P, ...] for pp sharding.

    The pipeline stages exactly ONE homogeneous layer group. A non-MoE
    MLA model (models/deepseek.py, num_experts=0) stacks its trunk under
    "dense_layers" instead of "layers"; it is renamed here — the staged
    tree is consumed only by pipeline_forward, which addresses the trunk
    as "layers". Mixed dense+MoE trunks (first_k_dense_replace > 0) keep
    their dense prefix UNstaged under "dense_layers": XLA's homogeneous
    stage scan cannot hold two differently-shaped layer pytrees, so the
    (short) prefix replicates to every stage and runs at injection while
    only the MoE trunk shards over pp.
    """
    key = "layers" if "layers" in params else "dense_layers"
    l = jax.tree.leaves(params[key])[0].shape[0]
    if l % num_stages:
        raise ValueError(f"{l} layers not divisible by {num_stages} pp stages")
    staged = dict(params)
    if key == "layers" and "dense_layers" in params:
        # mixed dense+MoE trunk (DeepSeek first_k_dense_replace > 0):
        # the stage scan cannot stack two differently-shaped layer
        # pytrees, so the (short) dense prefix stays UNstaged — it is
        # kept under "dense_layers", replicated to every stage, and
        # computed redundantly at injection (pipeline_forward); only
        # the homogeneous MoE trunk shards over pp.
        pass
    else:
        staged.pop("dense_layers", None)
    staged["layers"] = jax.tree.map(
        lambda x: x.reshape(num_stages, l // num_stages, *x.shape[1:]),
        params[key],
    )
    return staged


def stage_cache(kv_cache: KVCache, num_stages: int,
                prefix_layers: int = 0) -> KVCache:
    """[L, N, bs, KVH, D] → [P, L/P, N, bs, KVH, D] (stage-local slabs).

    ``prefix_layers`` > 0 (mixed dense+MoE MLA trunks): the first k
    layers belong to the replicated dense prefix — each side becomes
    ``{"pre": [k, ...] replicated, "stg": [P, (L-k)/P, ...] staged}``.
    """
    def split(c):
        l = c.shape[0]
        if l % num_stages:
            raise ValueError(
                f"{l} cache layers not divisible by {num_stages} pp stages"
            )
        return c.reshape(num_stages, l // num_stages, *c.shape[1:])

    if prefix_layers:
        return tuple(
            {"pre": c[:prefix_layers], "stg": split(c[prefix_layers:])}
            for c in kv_cache
        )
    return tuple(split(c) for c in kv_cache)


def unstage_cache(kv_cache: KVCache) -> KVCache:
    """Inverse of stage_cache: back to the wire layout [L, ...] with
    prefix layers (if any) leading."""
    def flat(c):
        if isinstance(c, dict):
            stg = c["stg"].reshape(-1, *c["stg"].shape[2:])
            return jnp.concatenate([c["pre"], stg], axis=0)
        return c.reshape(-1, *c.shape[2:])

    return tuple(flat(c) for c in kv_cache)


def param_specs(params, tp: bool = False, arch=None) -> dict:
    """Placement specs for staged params: layer stacks shard over pp on
    the stage axis. With ``tp`` the inner dims also shard Megatron-style —
    each spec is the family's per-layer tp spec with "pp" prepended for
    the stage axis (wq/wk/wv/w_gate/w_up column-parallel, wo/w_down
    row-parallel; MoE experts additionally over "ep"); lm_head stays
    vocab-sharded over tp at the outer (GSPMD) level."""
    arch = arch or llama
    specs = {"embed": P(), "final_norm": P()}
    if "lm_head" in params:
        specs["lm_head"] = P(None, "tp") if tp else P()
    # always start from the family's specs so non-tp axes (MoE "ep" on
    # the expert stacks) survive even when tp is off — only the "tp"
    # names are stripped at tp=1. Families whose staged trunk may be a
    # renamed group (deepseek's dense_layers) provide pp_trunk_specs.
    trunk_specs = getattr(arch, "pp_trunk_specs", None)
    if trunk_specs is not None:
        layer_specs = trunk_specs(params["layers"])
    else:
        layer_specs = arch.param_specs({"layers": params["layers"]})["layers"]

    def axis(a):
        return None if (a == "tp" and not tp) else a

    specs["layers"] = {
        k: P("pp", *(axis(a) for a in s)) for k, s in layer_specs.items()
    }
    if "dense_layers" in params:
        # replicated dense prefix (mixed MLA trunk): every stage holds
        # and computes it; its tp axes strip (MLA pp requires tp=1)
        prefix_specs = (trunk_specs(params["dense_layers"])
                        if trunk_specs is not None
                        else arch.param_specs(
                            {"dense_layers": params["dense_layers"]}
                        )["dense_layers"])
        specs["dense_layers"] = {
            k: P(*(axis(a) for a in s)) for k, s in prefix_specs.items()
        }
    # int8 serving: QuantizedWeight leaves need mirrored spec NODES (the
    # scale is one rank lower than q) — both for device_put and for the
    # shard_map in_specs below
    from ..models import quant

    return quant.mirror_specs(params, specs)


CACHE_SPEC = P("pp")  # [P, L/P, N, bs, KVH, D]
# with tp: KV heads shard over tp inside each stage's slab
CACHE_SPEC_TP = P("pp", None, None, None, "tp", None)


def pipeline_forward(
    params,                   # staged params (stage_params output)
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, S]
    positions: jax.Array,     # [B, S]
    kv_cache: KVCache,        # staged cache (stage_cache output)
    block_tables: jax.Array,  # [B, W]
    slot_mapping: jax.Array,  # [B, S]
    context_lens: jax.Array,  # [B]
    mesh,
    num_microbatches: Optional[int] = None,
    return_hidden: bool = False,
    arch=None,                # family module (llama default; mixtral = MoE)
) -> Tuple[jax.Array, KVCache]:
    """GQA-family forward with the trunk pipelined over the pp axis.

    Returns (logits [B, S, V], updated staged cache) — same contract as
    the family's forward modulo the staged cache layout. M defaults to P
    (the minimum that fills the pipeline; raise it to shrink the bubble).

    The shard_map is fully manual (dp/ep included) with explicit
    collectives — a partial-manual formulation (dp/ep left to GSPMD)
    crashes XLA's bf16 AllReducePromotion pass on this toolchain, because
    shardy inserts a sharding_constraint inside the psum reducer region:

    - dp: microbatch rows shard over "dp" when divisible; the KV cache is
      replicated across dp, so each member all-gathers every member's new
      K/V + slots before the cache scatter (make_gqa_attn_fn's
      kv_gather_axis) and attends its local rows only. A batch too small
      to split (B=1 prefill) is computed replicated — the non-pp path's
      behavior.
    - ep (MoE): expert stacks shard over "ep"; routing runs replicated
      over the global expert set, each member computes its local experts,
      and ONE psum over (tp, ep) finishes both the Megatron row-parallel
      contraction and the expert combine (moe_mlp's ep_axis). The
      dispatch is drop-free (models/mixtral.routed_experts), so a
      microbatch's routing gives what the unstaged engine's gives.

    Families plug in through module hooks with llama defaults:
    ``embed_tokens`` / ``make_attn_fn`` / ``run_layers`` / ``mlp_fn``
    (Gemma-2 overrides all four for its scaled embeddings, softcap +
    alternating-window attention, and sandwich-norm layer step; the
    window alternation follows the GLOBAL layer index via
    make_attn_fn's layer_offset).
    """
    import dataclasses as _dc
    import math as _math

    arch = arch or llama
    embed_fn = getattr(arch, "embed_tokens", llama.embed_tokens)
    make_attn = getattr(arch, "make_attn_fn", llama.make_gqa_attn_fn)
    run_layers_fn = getattr(arch, "run_layers", llama.run_layers)
    family_mlp = getattr(arch, "mlp_fn", llama.swiglu_mlp)
    # routed-MoE families expose a per-tick mlp factory taking the
    # manual ep axis (mixtral.make_moe_mlp_fn; gptoss.make_mlp_fn)
    moe_maker = None
    if getattr(cfg, "num_experts", 0):
        moe_maker = (
            getattr(arch, "make_moe_mlp_fn", None)
            or getattr(arch, "make_mlp_fn", None)
        )
    moe = moe_maker is not None
    num_stages = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    ep = mesh.shape.get("ep", 1) if moe else 1
    b, s = tokens.shape
    # auto microbatching: M = P fills the pipeline, but the batch must
    # split evenly — prefill runs at B=1, so fall back to the largest
    # divisor (m=1 degrades to stage-serial execution, still correct)
    m = num_microbatches or (
        num_stages if b % num_stages == 0 else _math.gcd(b, num_stages)
    )
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    mb = b // m
    # shard microbatch rows over dp when they split evenly; otherwise
    # every dp member computes the full rows redundantly (exactly the
    # non-pp engine's prefill-at-B=1 behavior)
    shard_dp = dp > 1 and mb % dp == 0
    mb_local = mb // dp if shard_dp else mb
    batch_spec = P(None, "dp") if shard_dp else P()

    def split_mb(x):
        return x.reshape(m, mb, *x.shape[1:])

    tokens_mb = split_mb(tokens)
    positions_mb = split_mb(positions)
    tables_mb = split_mb(block_tables)
    slots_mb = split_mb(slot_mapping)
    ctx_mb = split_mb(context_lens)

    cache_spec = CACHE_SPEC_TP if tp > 1 else CACHE_SPEC
    # each stage computes attention/MLP on its tp-local head/column shard
    # (activations replicated over tp, Megatron-style: one psum after the
    # attention output projection, one after w_down)
    local_cfg = (
        _dc.replace(
            cfg,
            num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp,
        )
        if tp > 1 else cfg
    )
    # reduce only over axes the mesh actually has (library callers may
    # build pp-only or pp x ep meshes; ep > 1 implies an ep axis exists)
    attn_axes = ("tp",) if "tp" in mesh.axis_names else ()
    mlp_axes = attn_axes + (("ep",) if ep > 1 else ())

    # mixed dense+MoE MLA trunk: a replicated dense prefix rides beside
    # the staged trunk — its params/cache replicate to every stage and
    # the prefix compute runs redundantly at injection (the prefix is a
    # few layers of sixty-plus; redundancy beats heterogeneous staging)
    has_prefix = "dense_layers" in params
    side_spec = (
        {"pre": P(), "stg": cache_spec} if has_prefix else cache_spec
    )

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            param_specs(params, tp=tp > 1, arch=arch),
            (side_spec, side_spec),
            batch_spec, batch_spec, batch_spec, batch_spec, batch_spec,
        ),
        out_specs=(batch_spec, (side_spec, side_spec)),
        check_vma=False,
    )
    def run(params, kv_cache, tokens_mb, positions_mb, tables_mb, slots_mb, ctx_mb):
        stage = lax.axis_index("pp")
        is_first = stage == 0
        is_last = stage == num_stages - 1
        # shard_map gives the local block with a leading singleton stage dim
        local_layers = jax.tree.map(lambda x: x[0], params["layers"])
        layers_per_stage = jax.tree.leaves(local_layers)[0].shape[0]
        if has_prefix:
            k_pre, v_pre = kv_cache[0]["pre"], kv_cache[1]["pre"]
            k_local, v_local = kv_cache[0]["stg"][0], kv_cache[1]["stg"][0]
        else:
            k_pre = v_pre = None
            k_local, v_local = kv_cache[0][0], kv_cache[1][0]

        d_model = cfg.hidden_size
        ticks = m + num_stages - 1

        def tick(t, carry):
            x_state, k_local, v_local, k_pre, v_pre, outputs = carry
            # which microbatch does THIS stage hold at tick t?
            mb_idx = jnp.clip(t - stage, 0, m - 1)
            valid = jnp.logical_and(t - stage >= 0, t - stage < m)

            tok = lax.dynamic_index_in_dim(tokens_mb, mb_idx, 0, keepdims=False)
            pos = lax.dynamic_index_in_dim(positions_mb, mb_idx, 0, keepdims=False)
            tab = lax.dynamic_index_in_dim(tables_mb, mb_idx, 0, keepdims=False)
            slots = lax.dynamic_index_in_dim(slots_mb, mb_idx, 0, keepdims=False)
            ctx = lax.dynamic_index_in_dim(ctx_mb, mb_idx, 0, keepdims=False)

            # invalid (warm-up/drain) ticks must not write KV: the drop
            # sentinel routes their scatter out of range
            slots = jnp.where(valid, slots, -1)

            # stage 0 injects the embedded microbatch; others use the
            # activations ppermuted in at the end of the previous tick.
            # With a dense prefix, injection = embed + the replicated
            # prefix layers: every stage computes its current
            # microbatch's prefix identically (writes land on disjoint
            # slots, so the replicated caches converge regardless of
            # tick order) and discards the result unless it is stage 0.
            injected = embed_fn(params, tok)
            if has_prefix:
                pre_attn = make_attn(
                    local_cfg, mb_local, s, pos, slots, tab, ctx,
                    mesh=None,
                    kv_gather_axis="dp" if shard_dp else None,
                    layer_offset=0, tp_axis=None,
                )
                injected, (k_pre, v_pre), _, _ = run_layers_fn(
                    injected, (k_pre, v_pre), params["dense_layers"],
                    cfg, pre_attn, llama.swiglu_mlp,
                )
            x_in = jnp.where(is_first, injected, x_state)

            # layer_offset and tp_axis are part of the factory contract:
            # the stage's first GLOBAL layer index (gemma2/gptoss window
            # alternation) and the manual tp axis (families with
            # replicated additive terms — gptoss's bo/b_down — scale
            # them so the Megatron psum restores each exactly once)
            # a size-1 tp axis still rides the psum (identity) but is
            # NOT a manual tp shard — factories that reject or rescale
            # under manual tp (MLA; gptoss's replicated biases) must
            # only see a real one
            tp_ax = "tp" if (attn_axes and tp > 1) else None
            base_attn = make_attn(
                local_cfg, mb_local, s, pos, slots, tab, ctx, mesh=None,
                kv_gather_axis="dp" if shard_dp else None,
                layer_offset=stage * layers_per_stage,
                tp_axis=tp_ax,
            )
            base_mlp = family_mlp
            if moe:
                routed = moe_maker(
                    cfg, mb_local, s, slots,
                    ep_axis="ep" if ep > 1 else None,
                    tp_axis=tp_ax,
                )

                def base_mlp(x, lp):
                    return routed(x, lp)[0]   # the counters go uncounted here
            if mlp_axes:
                def attn_fn(x, lp, k, v, li):
                    delta, k, v = base_attn(x, lp, k, v, li)
                    return (
                        lax.psum(delta, attn_axes) if attn_axes else delta
                    ), k, v

                def mlp_fn(x, lp):
                    # ONE reduction finishes both the Megatron
                    # row-parallel contraction (tp) and, for MoE, the
                    # local-expert combine (ep)
                    return lax.psum(base_mlp(x, lp), mlp_axes)
            else:
                attn_fn, mlp_fn = base_attn, base_mlp
            hidden, (k_local, v_local), _, _ = run_layers_fn(
                x_in, (k_local, v_local), local_layers, cfg, attn_fn,
                mlp_fn,
            )

            # last stage collects its finished microbatch
            out_idx = jnp.clip(t - (num_stages - 1), 0, m - 1)
            take = jnp.logical_and(is_last, valid)
            current = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(take, hidden, current), out_idx, 0
            )

            # rotate activations one stage down the ring
            x_state = lax.ppermute(
                hidden, "pp",
                [(i, (i + 1) % num_stages) for i in range(num_stages)],
            )
            return x_state, k_local, v_local, k_pre, v_pre, outputs

        x0 = jnp.zeros((mb_local, s, d_model), params["embed"].dtype)
        out0 = jnp.zeros((m, mb_local, s, d_model), params["embed"].dtype)
        x_state, k_local, v_local, k_pre, v_pre, outputs = lax.fori_loop(
            0, ticks, tick, (x0, k_local, v_local, k_pre, v_pre, out0)
        )

        # only the last stage holds real outputs; psum broadcasts them
        outputs = lax.psum(
            jnp.where(is_last, outputs, jnp.zeros_like(outputs)), "pp"
        )
        if has_prefix:
            return outputs, ({"pre": k_pre, "stg": k_local[None]},
                             {"pre": v_pre, "stg": v_local[None]})
        return outputs, (k_local[None], v_local[None])

    outputs, kv_cache = run(
        params, kv_cache, tokens_mb, positions_mb, tables_mb, slots_mb, ctx_mb
    )
    hidden = outputs.reshape(b, s, -1)
    if return_hidden:
        return hidden, kv_cache
    return arch.logits_from_hidden(hidden, params, cfg), kv_cache
