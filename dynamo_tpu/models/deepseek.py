"""DeepSeek-class decoder with Multi-head Latent Attention (MLA).

MLA compresses each token's KV state to a low-rank latent ``c_kv``
(kv_lora_rank wide) plus one shared RoPE key (qk_rope_head_dim wide) —
the paged cache stores ONLY those two vectors per token, cutting KV
memory by ~an order of magnitude vs per-head K/V and letting far more
sequences fit in HBM (the reference serves DeepSeek-R1 only by delegating
to engines that implement MLA; SURVEY.md §7 step 8 names MLA a scale-out
milestone for this framework).

TPU-first formulation — the *absorbed* form runs everywhere (prefill and
decode) so attention reads the compressed cache directly:

    score(q, t) = (q_nope W_uk) · c_kv[t] + q_rope · k_rope[t]
    out_latent  = softmax(score) @ c_kv        ->  o = out_latent W_uv W_o

i.e. W_uk is folded into the query and W_uv applied after attention, so
the per-token cache line stays [kv_lora_rank + qk_rope_head_dim] and the
big einsums stay MXU-shaped. TP shards query/output heads; the latent
cache is replicated over tp (it is tiny and per-token, not per-head).

Full DeepSeek-V2/V3 MLP topology: the first ``first_k_dense_replace``
layers use a dense SwiGLU at ``intermediate_size``; the remaining layers
are MoE with experts at ``moe_intermediate_size`` plus ``n_shared_experts``
always-on shared experts. All of it reuses the shared trunk pieces:
llama.run_layers scans each layer group, mixtral.make_moe_mlp_fn routes.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import (
    batch_axis,
    kernel_live_rows,
    lane_pad,
    pad_minor,
    pallas_interpret,
    record_route,
    resolve_attention_impl,
)
from ..ops.live_rows import decode_live_rows
from .llama import (
    apply_rope,
    base_specs,
    gather_kv_writes,
    lm_logits,
    rms_norm,
    run_layers,
    swiglu_mlp,
)
from . import mhc
# the one model_type this module is selected by name for is the mixed
# residual streams': its published keys are that path's
from .mhc import (CLAIM, CLAIMED_PREFIXES, claimed_keys,  # noqa: F401
                  config_fields)
# (benchmark/references/deepseek_v3.py names EXPERT_SPREAD here)
from .mixtral import (EXPERT_SPREAD, make_moe_mlp_fn,  # noqa: F401
                      random_expert_stacks, split_expert_stacks)
from .quant import dense

Params = Dict[str, Any]
KVCache = Tuple[jax.Array, jax.Array]  # (latent c_kv, shared k_rope) caches

# the latent cache is replicated across tp (no head dim to shard)
CACHE_SPEC = P()



def refuse_staged(config) -> None:
    """What the family refuses under ``pp_size > 1``."""
    if config.model.hc_mult > 1:
        raise NotImplementedError(
            f"pp_size {config.pp_size} is refused with hc_mult "
            f"{config.model.hc_mult}: a pipeline stage hands [B, S, D] to "
            "the next (parallel/pipeline.py), and the residual streams of "
            "models/mhc.py are [B, S, n D]"
        )
    if config.tp_size > 1:
        raise NotImplementedError(
            "MLA over pp composes with dp/ep, not tp: the "
            "compressed latent cache has a single head, so "
            "there is no head axis for the manual-tp stage "
            "to shard (MLA tp runs on the GSPMD non-pp path)"
        )


def init_kv_cache(
    cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
    num_slots: int = 1, window_blocks: int = 1, max_len: int = 0,
) -> KVCache:
    """Compressed cache: c_kv [L,N,1,bs,r] + k_rope [L,N,1,bs,rd].

    The one "head" sits in FRONT of the page, unlike the per-head caches
    ([L,N,bs,KVH,D]): the device tiles an array's two minor dims, and in
    [bs, 1, r] it pads the single head to a sublane pair (the compiler's
    memref is 16x2x512 a page), a page slice Mosaic refuses. [1, bs, r]
    is whole tiles: a page is one contiguous [bs, r] slab, which the
    decode kernel copies as it is, and [L*N*bs, r] is a view of the
    cache. Off the device (block transfer, host offload) blocks keep the
    one wire layout [L,n,bs,1,r] (engine/model_runner._build_block_ops).
    Minor dims are lane-padded (ops/attention.lane_pad)."""
    c = jnp.zeros(
        (cfg.num_layers, num_blocks, 1, block_size, lane_pad(cfg.kv_lora_rank)),
        dtype,
    )
    kr = jnp.zeros(
        (cfg.num_layers, num_blocks, 1, block_size,
         lane_pad(cfg.qk_rope_head_dim)),
        dtype,
    )
    return c, kr


def scatter_rows_stacked(caches, news, slot_mapping, li):
    """Write the new tokens' rows (``news``: [B,S,d] each) into layer
    ``li`` of the stacked caches ([L,N,1,bs,d'] each, one geometry), in
    place: slot ``block*bs + off`` of layer li is row ``li*N*bs + slot``
    of the flat [L*N*bs, d'] view (ops/attention.scatter_kv_stacked's
    contract for this layout; -1 and out-of-layer slots drop)."""
    l, n_blocks, _, block_size, _ = caches[0].shape
    per_layer = n_blocks * block_size
    idx = slot_mapping.reshape(-1)
    flat_idx = jnp.where(
        (idx < 0) | (idx >= per_layer), l * per_layer, li * per_layer + idx
    )

    def put(cache, new):
        d = cache.shape[-1]
        new = pad_minor(new, d).astype(cache.dtype).reshape(-1, d)
        flat = cache.reshape(l * per_layer, d)
        return flat.at[flat_idx].set(new, mode="drop").reshape(cache.shape)

    return tuple(put(cache, new) for cache, new in zip(caches, news))


def scatter_latent_stacked(c_all, kr_all, new_c, new_kr, slot_mapping, li):
    """The new tokens' latent [B,S,r] and rope key [B,S,rd] into layer
    ``li`` of the two stacked caches (``scatter_rows_stacked``)."""
    return scatter_rows_stacked((c_all, kr_all), (new_c, new_kr),
                                slot_mapping, li)


def _split_layer_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(dense-prefix layers, MoE layers)."""
    if cfg.num_experts <= 0:
        return cfg.num_layers, 0
    k = min(cfg.first_k_dense_replace, cfg.num_layers)
    return k, cfg.num_layers - k


def _attn_params(cfg: ModelConfig, n_layers: int, key, w, dtype) -> Dict:
    d_model, h = cfg.hidden_size, cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd = cfg.v_head_dim
    l = n_layers
    keys = jax.random.split(key, 8)
    out: Dict[str, jax.Array] = {
        "ln1": jnp.ones((l, d_model), dtype),
        "w_dkv": w(keys[0], (l, d_model, r), d_model),
        "ln_kv": jnp.ones((l, r), dtype),
        "w_kr": w(keys[1], (l, d_model, rope), d_model),
        "w_uk": w(keys[2], (l, r, h, nope), r),
        "w_uv": w(keys[3], (l, r, h, vd), r),
        "wo": w(keys[4], (l, h * vd, d_model), h * vd),
        "ln2": jnp.ones((l, d_model), dtype),
    }
    if qr > 0:
        out["w_dq"] = w(keys[5], (l, d_model, qr), d_model)
        out["ln_q"] = jnp.ones((l, qr), dtype)
        out["w_uq"] = w(keys[6], (l, qr, h * (nope + rope)), qr)
    else:
        out["wq"] = w(keys[5], (l, d_model, h * (nope + rope)), d_model)
    return out


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    d_model = cfg.hidden_size
    inter = cfg.intermediate_size
    moe_inter = cfg.moe_intermediate_size or inter
    e = cfg.num_experts
    n_dense, n_moe = _split_layer_counts(cfg)
    keys = jax.random.split(key, 12)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    def experts(key, shape, fan_in):
        return random_expert_stacks(key, shape, fan_in, dtype)

    params: Params = {
        "embed": w(keys[0], (cfg.vocab_size, d_model), d_model),
        "final_norm": jnp.ones((d_model,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[1], (d_model, cfg.vocab_size), d_model)

    if n_dense > 0:
        group = _attn_params(cfg, n_dense, keys[2], w, dtype)
        group["w_gate"] = w(keys[3], (n_dense, d_model, inter), d_model)
        group["w_up"] = w(keys[4], (n_dense, d_model, inter), d_model)
        group["w_down"] = w(keys[5], (n_dense, inter, d_model), inter)
        if cfg.hc_mult > 1:
            group.update(mhc.init_params(
                cfg, n_dense, jax.random.fold_in(key, 12)))
        params["dense_layers"] = group

    if n_moe > 0:
        moe = _attn_params(cfg, n_moe, keys[6], w, dtype)
        moe["router"] = w(keys[7], (n_moe, d_model, e), d_model)
        if cfg.topk_method == "noaux_tc":
            # V3's e_score_correction_bias: small and non-zero, so that
            # random weights exercise "the bias steers selection only"
            # (a trained checkpoint's is a few hundredths too); float32
            # as published
            moe["router_bias"] = 0.05 * jax.random.normal(
                jax.random.fold_in(keys[7], 1), (n_moe, e), jnp.float32)
        moe["w_gate"] = experts(keys[8], (n_moe, e, d_model, moe_inter), d_model)
        moe["w_up"] = experts(keys[9], (n_moe, e, d_model, moe_inter), d_model)
        moe["w_down"] = experts(keys[10], (n_moe, e, moe_inter, d_model), moe_inter)
        if cfg.n_shared_experts > 0:
            sh = cfg.n_shared_experts * moe_inter
            sk = jax.random.split(keys[11], 3)
            moe["w_sh_gate"] = w(sk[0], (n_moe, d_model, sh), d_model)
            moe["w_sh_up"] = w(sk[1], (n_moe, d_model, sh), d_model)
            moe["w_sh_down"] = w(sk[2], (n_moe, sh, d_model), sh)
        if cfg.hc_mult > 1:
            moe.update(mhc.init_params(cfg, n_moe, jax.random.fold_in(key, 13)))
        params["layers"] = moe
    return params


_MLA_ATTN_SPECS = {
    "ln1": P(), "ln2": P(), "ln_kv": P(),
    "w_dkv": P(), "w_kr": P(),
    "w_uk": P(None, None, "tp", None),
    "w_uv": P(None, None, "tp", None),
    "wo": P(None, "tp", None),
    "wq": P(None, None, "tp"),
    "w_dq": P(), "ln_q": P(), "w_uq": P(None, None, "tp"),
    # the mixing tensors of models/mhc.py: replicated, as the streams are
    **{k: P() for k in mhc.PARAM_KEYS},
}


_DENSE_LAYER_SPECS = {
    **_MLA_ATTN_SPECS,
    "w_gate": P(None, None, "tp"),
    "w_up": P(None, None, "tp"),
    "w_down": P(None, "tp", None),
}
_MOE_LAYER_SPECS = {
    **_MLA_ATTN_SPECS,
    "router": P(),
    "router_bias": P(),
    "w_gate": P(None, "ep", None, "tp"),
    "w_up": P(None, "ep", None, "tp"),
    "w_down": P(None, "ep", "tp", None),
    "w_sh_gate": P(None, None, "tp"),
    "w_sh_up": P(None, None, "tp"),
    "w_sh_down": P(None, "tp", None),
}


def param_specs(params: Params) -> Dict:
    """Heads shard over tp; latent down-projections + cache replicate;
    experts (if MoE) over ep like models/mixtral.py."""
    specs = base_specs(params)
    if "dense_layers" in params:
        specs["dense_layers"] = {
            k: _DENSE_LAYER_SPECS[k] for k in params["dense_layers"]
        }
    if "layers" in params:  # present iff the config is MoE
        specs["layers"] = {k: _MOE_LAYER_SPECS[k] for k in params["layers"]}
    return specs


def mla_paged_attention(
    q_lat: jax.Array,      # [B, S, H, r] — queries absorbed into latent space
    q_rope: jax.Array,     # [B, S, H, rd] — post-RoPE decoupled queries
    c_cache: jax.Array,    # [N, 1, bs, r]
    kr_cache: jax.Array,   # [N, 1, bs, rd]
    block_tables: jax.Array,  # [B, W]
    q_positions: jax.Array,   # [B, S]
    context_lens: jax.Array,  # [B]
    scale: float,
    sliding_window=None,      # a query sees the last so many keys alone
) -> jax.Array:
    """Attention over the compressed cache; returns latent output [B,S,H,r]."""
    b, s, h, r = q_lat.shape
    _, _, block_size, rd = kr_cache.shape
    w = block_tables.shape[1]
    t = w * block_size

    # upcast from the cache storage dtype (fp8 serving stores e4m3)
    c = c_cache[block_tables].reshape(b, t, r).astype(q_lat.dtype)
    kr = kr_cache[block_tables].reshape(b, t, rd).astype(q_lat.dtype)

    scores = (
        jnp.einsum("bshr,btr->bsht", q_lat, c)
        + jnp.einsum("bshd,btd->bsht", q_rope, kr)
    ) * scale
    key_pos = jnp.arange(t)[None, None, :]
    mask = (key_pos <= q_positions[:, :, None]) & (
        key_pos < context_lens[:, None, None]
    )
    if sliding_window is not None:
        mask = mask & (key_pos > q_positions[:, :, None] - sliding_window)
    scores = jnp.where(mask[:, :, None, :], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bsht,btr->bshr", probs, c)


@jax.named_scope("mla_cache")
def mla_attention(
    q_lat, q_rope, c_all, kr_all, li, block_tables, positions, context_lens,
    scale, impl="auto", mesh=None, interpret=False, live_rows=None,
    sliding_window=None,
):
    """MLA attention dispatch over the stacked compressed caches (the
    scope ``mla_cache``: the cache read, scores, softmax and PV, apart
    from the projections around it).

    Decode (S == 1) on the kernel route (``auto`` on a TPU, or an
    explicit ``pallas``; ``DYN_PALLAS_INTERPRET=1`` runs it in the
    interpreter like every other kernel) uses the MLA decode kernel
    (ops/pallas_decode.py): it indexes the layer inside HBM and walks
    each row's LIVE pages only, so a step costs what its sequences hold
    and not rows x the block table's width. Every other case (prefill,
    the CPU) gathers the layer's blocks through the table and runs the
    dense formulation. Query heads shard over "tp" under a multi-device
    mesh; the latent caches are replicated (no head dim). ``live_rows``
    (ops/live_rows.decode_live_rows, made once a step): the kernel walks
    those rows alone and returns zeros in the others; the dense
    formulation ignores it. ``sliding_window`` (models/dots3.py's window
    layers): a query sees its last so many keys alone; the kernel starts
    its walk at the window's first page.
    """
    kernel = (q_lat.shape[1] == 1
              and resolve_attention_impl(impl) == "pallas")
    record_route("decode" if kernel else "xla")

    # caches carry lane padding; zero-padded queries score 0 against the
    # zero pad lanes, and the padded latent output is sliced back below
    r = q_lat.shape[-1]
    q_lat = pad_minor(q_lat, c_all.shape[-1])
    q_rope = pad_minor(q_rope, kr_all.shape[-1])

    if kernel:
        from ..ops.pallas_decode import mla_paged_decode_attention

        interpret = interpret or pallas_interpret()
        dp = batch_axis(mesh, q_lat.shape[0])
        live_rows = kernel_live_rows(live_rows, mesh, dp)

        def fn(ql, qr, c, kr, bt, ctx, li, live_rows):
            return mla_paged_decode_attention(
                ql, qr, c, kr, bt, ctx, layer_idx=li, scale=scale,
                interpret=interpret, live_rows=live_rows,
                sliding_window=sliding_window,
            )

        li_arr = jnp.asarray(li, jnp.int32)
        if mesh is not None and mesh.size > 1:
            fn = jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(
                    P(dp, None, "tp", None),   # q_lat [B, 1, H, R]
                    P(dp, None, "tp", None),   # q_rope
                    CACHE_SPEC,                # c cache (replicated)
                    CACHE_SPEC,                # kr cache
                    P(dp, None),               # block_tables
                    P(dp),                     # context_lens
                    P(),                       # layer idx
                    P(),                       # live_rows (or None)
                ),
                out_specs=P(dp, None, "tp", None),
                check_vma=False,
            )
        return fn(q_lat, q_rope, c_all, kr_all, block_tables,
                  context_lens, li_arr, live_rows)[..., :r]

    # layer indexing through the gather (see ops/attention.attention):
    # block n of layer li is flat row li*N + n — no full-layer copy
    l, n_blocks = c_all.shape[:2]
    c_flat = c_all.reshape((l * n_blocks,) + c_all.shape[2:])
    kr_flat = kr_all.reshape((l * n_blocks,) + kr_all.shape[2:])
    li_arr = jnp.asarray(li, jnp.int32)
    return mla_paged_attention(
        q_lat, q_rope, c_flat, kr_flat, block_tables + li_arr * n_blocks,
        positions, context_lens, scale, sliding_window,
    )[..., :r]


def mla_softmax_scale(cfg) -> float:
    """MLA attention softmax scale, incl. DeepSeek's yarn mscale.

    With yarn + mscale_all_dim, the softmax scale carries mscale_all_dim²
    over the WHOLE score (nope + rope); the rope part's cos/sin carry the
    mscale/mscale_all ratio (llama.apply_rope) — together the rope score
    scales by mscale², per DeepSeek's own modeling code (the checkpoints
    were trained with it). transformers' NATIVE DeepseekV2 class omits
    the softmax adjustment (its V3 class applies it); this framework
    follows the canonical training-time semantics for both —
    tests/test_loaders.py pins this computed scale.
    """
    from .llama import yarn_mscale

    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = cfg.rope_scaling or {}
    if (sc.get("rope_type") or sc.get("type")) == "yarn":
        mscale_all = float(sc.get("mscale_all_dim") or 0.0)
        if mscale_all:
            m = yarn_mscale(float(sc.get("factor", 1.0)), mscale_all)
            scale = scale * m * m
    return scale


def mla_project(cfg, x, lp, b, s, positions, rope: bool = True,
                q_scale: float = 1.0, kv_scale: float = 1.0,
                hold_heads: bool = False):
    """The latent projections of one layer's new tokens -> (the query's
    latent [B,S,qr] after its norm, None without ``w_uq``; q_nope
    [B,S,H,nope]; q_rope [B,S,H,rd]; the key-value latent [B,S,r] after
    its norm; the shared rope key [B,S,rd]), the two rope parts rotated
    unless ``rope`` is false. ``q_scale`` / ``kv_scale``: constants
    after the two latent norms (models/dots3.py,
    ``apply_mla_qkv_lora_rescale``); 1.0 multiplies nothing.
    ``hold_heads``: the query's product ends as [B, S, H (nope + rope)]
    behind an ``optimization_barrier`` before the compiler sees a head
    axis, as ``llama.qkv_prologue`` holds its three: folded into the
    dot, the reshape makes the layer loop copy its slice of ``w_uq`` out
    of the stack and transpose it (50 MB a full layer of
    models/dots3.py, 201 MB for its six window layers at once).

    quant.dense serves these int8 under --quantization (w_kr and the
    absorbed w_uk/w_uv stay full precision, see quant.py keys)."""
    h = cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = None
    # queries (optionally through the q low-rank bottleneck)
    if "w_uq" in lp:
        cq = rms_norm(dense(x, lp["w_dq"]), lp["ln_q"], cfg.rms_norm_eps)
        if q_scale != 1.0:
            cq = (cq.astype(jnp.float32) * q_scale).astype(cq.dtype)
        qfull = dense(cq, lp["w_uq"])
    else:
        qfull = dense(x, lp["wq"])
    if hold_heads:
        qfull = jax.lax.optimization_barrier(qfull)
    qfull = qfull.reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = qfull[..., :nope], qfull[..., nope:]
    if rope:
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                            cfg.rope_scaling)

    # compressed KV state for the new tokens
    c_kv = rms_norm(dense(x, lp["w_dkv"]), lp["ln_kv"], cfg.rms_norm_eps)
    if kv_scale != 1.0:
        c_kv = (c_kv.astype(jnp.float32) * kv_scale).astype(c_kv.dtype)
    kr = x @ lp["w_kr"]  # [B, S, rd]
    if rope:
        kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0]
    return cq, q_nope, q_rope, c_kv, kr


def make_mla_attn_fn(cfg, b, s, positions, slot_mapping, block_tables,
                     context_lens, mesh=None, kv_gather_axis=None,
                     rope: bool = True):
    """MLA attention block for llama.run_layers.

    ``rope=False``: layers with no positional term (models/kimi_linear.py,
    ``mla_use_nope``): the ``qk_rope_head_dim``-wide parts of the query
    and of the key are carried as projected, the cache line is the same.

    ``kv_gather_axis``: inside a manual shard_map whose batch rows shard
    over that axis while the latent cache stays replicated across it
    (the pipelined pp x dp program), every member must apply every
    member's cache writes — the new latent/rope-key rows and their slots
    are all-gathered over the axis before the scatter (exactly
    llama.make_gqa_attn_fn's contract)."""
    scale = mla_softmax_scale(cfg)
    # a decode step's rows that hold a token: the same for every layer,
    # made once, outside the scan
    live_rows = decode_live_rows(slot_mapping)

    def attn_fn(x, lp, c_all, kr_all, li):
        _, q_nope, q_rope, c_kv, kr = mla_project(
            cfg, x, lp, b, s, positions, rope=rope)

        # in-place scatter into the stacked caches
        c_w, kr_w, slots_w = c_kv, kr, slot_mapping
        if kv_gather_axis is not None:
            c_w, kr_w, slots_w = gather_kv_writes(
                c_w, kr_w, slot_mapping, kv_gather_axis
            )
        c_all, kr_all = scatter_latent_stacked(
            c_all, kr_all, c_w, kr_w, slots_w, li
        )

        # absorb W_uk into the query, attend over the latent cache
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, lp["w_uk"])
        o_lat = mla_attention(
            q_lat, q_rope, c_all, kr_all, li, block_tables, positions,
            context_lens, scale, impl=cfg.attention_impl, mesh=mesh,
            live_rows=live_rows,
        )
        o = jnp.einsum("bshr,rhv->bshv", o_lat, lp["w_uv"])
        delta = dense(o.reshape(b, s, -1), lp["wo"])
        return delta, c_all, kr_all

    return attn_fn


def make_attn_fn(cfg, b, s, positions, slot_mapping, block_tables,
                 context_lens, mesh=None, kv_gather_axis=None,
                 layer_offset=0, tp_axis=None):
    """Pipeline attention factory (parallel/pipeline.py family-hook
    contract, the pattern Gemma-2/GPT-OSS stage through). MLA has no
    per-layer alternation, so ``layer_offset`` is accepted and ignored;
    ``tp_axis`` must be None — the latent cache has a single head, so
    there is no head axis to shard inside a manual-tp stage (MLA tp runs
    on the GSPMD non-pp path; model_runner guards this)."""
    del layer_offset
    if tp_axis is not None:
        raise NotImplementedError(
            "MLA under pp composes with dp/ep, not manual tp (the "
            "compressed latent cache has no head axis to shard)"
        )
    return make_mla_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens,
        mesh=mesh, kv_gather_axis=kv_gather_axis,
    )


def pp_trunk_specs(group: Dict) -> Dict:
    """Per-leaf tp/ep specs for the ONE homogeneous layer group the
    pipeline stages (parallel/pipeline.py consults this instead of
    param_specs because the staged group may be the renamed
    dense_layers of a non-MoE config)."""
    table = _MOE_LAYER_SPECS if "router" in group else _DENSE_LAYER_SPECS
    return {k: table[k] for k in group}


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
    """Returns (logits [B, S, V], updated (c_kv, k_rope) caches). Dense
    prefix layers then MoE layers, chained through one contiguous cache.

    Decode steps on the kernel route (``auto`` on a TPU) run the MLA
    decode kernel (ops/pallas_decode.py mla_paged_decode_attention);
    prefill and the CPU run the dense gather formulation
    (mla_paged_attention)."""
    hidden, kv_cache, _ = forward_counted(
        params, cfg, tokens, positions, kv_cache, block_tables,
        slot_mapping, context_lens, mesh=mesh)
    if return_hidden:
        return hidden, kv_cache
    return lm_logits(hidden, params, cfg), kv_cache


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None):
    """``forward(return_hidden=True)`` and a third value, int32 [3]:
    mixtral.routing_stats summed over the MoE layers, zeros
    for a dense configuration (as mixtral.forward_counted)."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
    if cfg.hc_mult > 1:
        # the trunk carries hc_mult residual streams [B, S, n D]
        # (models/mhc.py); every caller keeps [B, S, D]
        hidden = mhc.fan_out(hidden, cfg)
    attn_fn = make_mla_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens,
        mesh=mesh,
    )

    li = 0
    stats = jnp.zeros((3,), jnp.int32)
    if "dense_layers" in params:
        hidden, kv_cache, li, _ = run_layers(
            hidden, kv_cache, params["dense_layers"], cfg, attn_fn,
            swiglu_mlp, li0=li,
        )
    if "layers" in params:  # present iff the config is MoE
        scanned, stacks = split_expert_stacks(params["layers"])
        hidden, kv_cache, li, aux = run_layers(
            hidden, kv_cache, scanned, cfg, attn_fn,
            make_moe_mlp_fn(cfg, b, s, slot_mapping, mesh=mesh, stacks=stacks),
            li0=li,
        )
        stats = aux.sum(axis=0)
    if cfg.hc_mult > 1:
        hidden = mhc.read_out(hidden, cfg)
    return hidden, kv_cache, stats


# final norm + lm head over any [..., D] slice (engine/model_runner.py)
logits_from_hidden = lm_logits
