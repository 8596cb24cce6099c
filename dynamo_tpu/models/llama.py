"""Llama-family decoder, functional JAX, paged-KV, scan-over-layers.

Design notes (TPU-first):
- Pure functions over a params pytree; layer weights are stacked on a
  leading L axis and the transformer body is one ``lax.scan`` whose carry
  holds (hidden, kv_cache) — compile time is O(1) in depth and the donated
  cache updates in place.
- The same ``forward`` serves bucketed prefill (S>1) and decode (S=1):
  new K/V are scattered into the paged cache, then attention runs over
  gathered cache blocks (ops/attention.py). ``kv_width`` bounds how many
  blocks are gathered so prefill doesn't pay full-context gathers.
- GQA, RoPE, RMSNorm, SwiGLU per the Llama architecture. Weights load from
  HF safetensors via models/loader.py.

This module is the engine the reference never had natively (it delegated
GPU work to vLLM/SGLang — SURVEY.md §2.4); here the model IS part of the
framework.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import attention, lane_pad, scatter_kv_stacked
from ..ops.live_rows import decode_live_rows
from . import mhc
from .quant import dense

Params = Dict[str, Any]
KVCache = Tuple[jax.Array, jax.Array]  # k, v: [L, N_blocks, bs, KVH, D]


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    norm = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (norm * weight.astype(jnp.float32)).astype(dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(
    head_dim: int, theta: float, scaling: Optional[dict] = None
) -> Tuple[jax.Array, float]:
    """(inverse rope frequencies, attention factor) with HF
    ``rope_scaling`` applied.

    "linear" divides all frequencies by the factor; "llama3" (Llama-3.1+)
    scales low-frequency bands with a smooth ramp between the high/low
    wavelength thresholds; "yarn" (DeepSeek-V2/V3 and NTK-extended
    models) blends interpolated and extrapolated frequencies over the
    beta_fast/beta_slow correction range and returns the mscale
    attention factor the rotation must be multiplied by (cos/sin
    scaling; q and k each carry it, so scores scale by its square —
    matching transformers' ROPE_INIT_FUNCTIONS and DeepSeek's
    mscale/mscale_all_dim variant exactly). Unknown types warn and load
    unscaled (degrades only beyond the original context window).
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if not scaling:
        return inv_freq, 1.0
    kind = scaling.get("rope_type") or scaling.get("type")
    factor = float(scaling.get("factor", 1.0))
    if kind == "linear":
        return inv_freq / factor, 1.0
    if kind == "llama3":
        low = float(scaling.get("low_freq_factor", 1.0))
        high = float(scaling.get("high_freq_factor", 4.0))
        orig = float(scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = (orig / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        return jnp.where(
            wavelen < orig / high, inv_freq,            # high freq: keep
            jnp.where(wavelen > orig / low, inv_freq / factor, scaled),
        ), 1.0
    if kind == "yarn":
        orig = float(scaling.get("original_max_position_embeddings", 4096))
        beta_fast = float(scaling.get("beta_fast", 32.0))
        beta_slow = float(scaling.get("beta_slow", 1.0))

        def correction_dim(num_rotations: float) -> float:
            return (head_dim / 2.0) * math.log(
                orig / (num_rotations * 2.0 * math.pi)
            ) / math.log(theta)

        low = max(math.floor(correction_dim(beta_fast)), 0)
        # transformers clamps to head_dim - 1 (not the D/2 frequency
        # count) — the ramp denominator must match HF exactly or every
        # mid-band blend shifts
        high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001  # avoid a zero-width ramp
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
            / (high - low), 0.0, 1.0,
        )
        extrapolation_w = 1.0 - ramp   # high-frequency dims: keep as-is
        inv = (inv_freq / factor) * (1.0 - extrapolation_w) \
            + inv_freq * extrapolation_w
        attention_factor = scaling.get("attention_factor")
        if attention_factor is None:
            mscale = float(scaling.get("mscale") or 0.0)
            mscale_all = float(scaling.get("mscale_all_dim") or 0.0)
            if mscale and mscale_all:
                # DeepSeek variant: ratio of the two mscale curves —
                # taken only when BOTH keys are present, exactly as
                # transformers' _compute_yarn_parameters does
                attention_factor = yarn_mscale(factor, mscale) / yarn_mscale(
                    factor, mscale_all
                )
            else:
                attention_factor = yarn_mscale(factor)
        return inv, float(attention_factor)
    if kind in ("longrope", "su"):
        # handled in apply_rope: the short/long factor choice depends on
        # the call's sequence length (a traced value), not just config
        raise ValueError(
            "longrope is resolved inside apply_rope, not rope_frequencies"
        )
    if kind not in (None, "default"):
        import logging

        logging.getLogger(__name__).warning(
            "rope_scaling type %r not implemented; serving with unscaled "
            "frequencies (contexts beyond the original window degrade)",
            kind,
        )
    return inv_freq, 1.0


def _longrope_frequencies(d: int, theta: float, scaling: dict, positions,
                          seq_basis=None):
    """Phi-3 longrope (transformers _compute_longrope_parameters +
    dynamic_rope_update): per-dim short/long frequency rescaling, the
    profile chosen PER ROW by whether that sequence's covered context
    exceeds the pretraining window — a traced comparison, since one
    compiled program serves all lengths, and per-row so one long request
    cannot flip co-batched short requests onto the long profile. Keys
    roped while a sequence was still short keep their short-profile
    rotation as it grows — exactly what HF's cached generation does
    (dynamic_rope_update re-ropes only new positions). The attention
    factor sqrt(1 + ln(len_ratio)/ln(original)) rides cos/sin regardless
    of profile, as HF applies it.

    ``seq_basis`` [B] is each row's covered context length (the engine
    passes context_lens); without it, each row's max position stands in.
    """
    missing = [k for k in ("short_factor", "long_factor") if k not in scaling]
    if missing or "original_max_position_embeddings" not in scaling:
        raise ValueError(
            f"longrope rope_scaling needs short_factor/long_factor and "
            f"original_max_position_embeddings (missing: "
            f"{missing + [k for k in ['original_max_position_embeddings'] if k not in scaling]}); "
            "ModelConfig.from_hf_config injects the window fields from "
            "the checkpoint config"
        )
    original = scaling["original_max_position_embeddings"]
    maxpos = scaling.get("max_position_embeddings", original)
    factor = maxpos / original
    attn_factor = scaling.get("attention_factor") or (
        1.0 if factor <= 1.0
        else math.sqrt(1.0 + math.log(factor) / math.log(original))
    )
    base_pow = theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    short = jnp.asarray(scaling["short_factor"], jnp.float32)
    long = jnp.asarray(scaling["long_factor"], jnp.float32)
    if seq_basis is None:
        seq_basis = jnp.max(positions, axis=-1) + 1  # [B]
    is_long = (seq_basis > original)[:, None, None]   # [B, 1, 1]
    ext = jnp.where(is_long, long[None, None, :], short[None, None, :])
    return 1.0 / (ext * base_pow), float(attn_factor)  # [B, 1, D/2]


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float,
    scaling: Optional[dict] = None,
    seq_basis=None,  # [B] covered context per row (longrope profile choice)
    rotary_dim: Optional[int] = None,  # lanes rotated; None: the whole head
) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S]. HF-style half-rotation RoPE.

    The yarn attention factor rides on cos/sin (as in transformers), so
    q·k scores carry its square without touching the softmax scale.

    ``rotary_dim`` r < D (``partial_rotary_factor``, models/mimo_v2.py):
    lanes [0, r) of every head are rotated (pairs (i, i + r / 2), the
    frequencies those of a head of r), lanes [r, D) carried as projected.
    """
    d = x.shape[-1]
    if rotary_dim is not None and rotary_dim < d:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, theta, scaling,
                        seq_basis), x[..., rotary_dim:]], axis=-1)
    kind = (scaling or {}).get("rope_type", (scaling or {}).get("type"))
    if kind in ("longrope", "su"):
        # [B, 1, D/2] — per-row profile; broadcasts with positions below
        inv_freq, attn_factor = _longrope_frequencies(
            d, theta, scaling, positions, seq_basis
        )
    else:
        inv_freq, attn_factor = rope_frequencies(d, theta, scaling)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :] * attn_factor            # [B, S, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :] * attn_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params with the right shapes/layout (tests, benchmarks)."""
    l, d_model = cfg.num_layers, cfg.hidden_size
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    inter = cfg.intermediate_size
    keys = jax.random.split(key, 10)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    layers = {
        "ln1": jnp.ones((l, d_model), dtype),
        "wq": w(keys[1], (l, d_model, h * hd), d_model),
        "wk": w(keys[2], (l, d_model, kvh * hd), d_model),
        "wv": w(keys[3], (l, d_model, kvh * hd), d_model),
        "wo": w(keys[4], (l, h * hd, d_model), h * hd),
        "ln2": jnp.ones((l, d_model), dtype),
        "w_gate": w(keys[5], (l, d_model, inter), d_model),
        "w_up": w(keys[6], (l, d_model, inter), d_model),
        "w_down": w(keys[7], (l, inter, d_model), inter),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((l, h * hd), dtype)
        layers["bk"] = jnp.zeros((l, kvh * hd), dtype)
        layers["bv"] = jnp.zeros((l, kvh * hd), dtype)
    params: Params = {
        "embed": w(keys[0], (cfg.vocab_size, d_model), d_model),
        "layers": layers,
        "final_norm": jnp.ones((d_model,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[8], (d_model, cfg.vocab_size), d_model)
    return params


# attention-trunk specs shared by every family using decoder_forward
ATTN_LAYER_SPECS = {
    "ln1": P(),
    "wq": P(None, None, "tp"),
    "wk": P(None, None, "tp"),
    "wv": P(None, None, "tp"),
    "wo": P(None, "tp", None),
    "ln2": P(),
    # qkv biases follow their projection's output sharding
    "bq": P(None, "tp"),
    "bk": P(None, "tp"),
    "bv": P(None, "tp"),
    # per-head-dim q/k norms (Qwen3): shared across heads → replicated
    "q_norm": P(),
    "k_norm": P(),
}


def base_specs(params: Params) -> Dict:
    """Specs for the non-layer params (embed / final_norm / lm_head)."""
    specs: Dict = {"embed": P(), "final_norm": P()}
    if "lm_head" in params:
        specs["lm_head"] = P(None, "tp")
    return specs


def param_specs(params: Params) -> Dict:
    """PartitionSpecs mirroring the param pytree (Megatron TP layout)."""
    layer_specs = {
        **ATTN_LAYER_SPECS,
        "w_gate": P(None, None, "tp"),
        "w_up": P(None, None, "tp"),
        "w_down": P(None, "tp", None),
    }
    specs = base_specs(params)
    specs["layers"] = {k: layer_specs[k] for k in params["layers"]}
    return specs


def init_kv_cache(
    cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
    num_slots: int = 1, window_blocks: int = 1, max_len: int = 0,
) -> KVCache:
    # (num_slots, window_blocks, max_len: what the engine offers every
    # family; one kind of page takes none of them)
    # minor dim lane-padded: physically free (XLA tiles HBM to 128 lanes)
    # and required by the manual-DMA decode kernel (ops/attention.lane_pad)
    shape = (
        cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads,
        lane_pad(cfg.head_dim),
    )
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def run_specs(params: Params) -> Dict:
    """Every weight replicated, for a trunk kept a run of layers
    (``params["runs"]``: ``layer_runs``) whose family refuses tp and ep."""
    specs = {k: P() for k in base_specs(params) if k in params}
    specs["runs"] = [{k: P() for k in run} for run in params["runs"]]
    return specs


def layer_runs(kinds, index_key=lambda kind: kind) -> List[Tuple[Any, int, int]]:
    """``kinds`` (one a layer) as runs of one kind, for a trunk that
    scans each run over its own stacked weights: (kind, the run's first
    index among the layers that share its ``index_key``: the layers
    stacked in one cache, its length)."""
    runs, seen = [], {}
    for kind in kinds:
        key = index_key(kind)
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(key, 0), 1])
        seen[key] = seen.get(key, 0) + 1
    return [tuple(r) for r in runs]


def embed_tokens(params: Params, tokens: jax.Array) -> jax.Array:
    """Plain embedding lookup (Gemma overrides with its sqrt(d) scale)."""
    return params["embed"][tokens]


def swiglu_mlp(x: jax.Array, layer_params) -> jax.Array:
    gate = jax.nn.silu(dense(x, layer_params["w_gate"]))
    return dense(gate * dense(x, layer_params["w_up"]), layer_params["w_down"])


def alternating_window(cfg, li, layer_offset=0):
    """Per-layer sliding window for families whose layer_types alternate
    sliding/full starting sliding at GLOBAL layer 0 (Gemma-2, GPT-OSS;
    the pattern is validated at config parse for gpt-oss). ``li`` may be
    traced (inside the layer scan); ``layer_offset`` is the stage's first
    global layer index under pipeline staging. None when the family has
    no window at all."""
    if not cfg.sliding_window:
        return None
    return jnp.where(
        (li + layer_offset) % 2 == 0, cfg.sliding_window, jnp.int32(1 << 30)
    )


def gather_kv_writes(k, v, slot_mapping, axis):
    """All-gather new K/V and their slots over a manual mesh axis whose
    members shard the batch rows while replicating the KV cache (the
    pipelined pp x dp program): every member must apply EVERY member's
    cache writes or the replicas diverge. Shared by the GQA and Gemma-2
    attention factories."""
    return (
        jax.lax.all_gather(k, axis, axis=0, tiled=True),
        jax.lax.all_gather(v, axis, axis=0, tiled=True),
        jax.lax.all_gather(slot_mapping, axis, axis=0, tiled=True),
    )


def qkv_prologue(cfg, x, layer_params, b, s, positions, seq_basis,
                 rope: bool = True):
    """The per-layer QKV head: projections (+ Qwen2 biases), head
    reshape, Qwen3 per-head norms, RoPE. ONE implementation shared by
    the dense paged path, the sequence-parallel chunk path, and the
    cacheless embeddings trunk — the SP path's bit-identical-KV
    contract depends on these never drifting. ``rope=False``: a layer
    with no positional term (models/afmoe.py's full-attention layers)."""
    h_heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(x, layer_params["wq"])
    k = dense(x, layer_params["wk"])
    v = dense(x, layer_params["wv"])
    if "bq" in layer_params:  # Qwen2-family qkv biases, pre-rope
        q = q + layer_params["bq"]
        k = k + layer_params["bk"]
        v = v + layer_params["bv"]
    # the products end as [b, s, out] before the compiler sees a head
    # axis: folded into the dot, the reshape makes the weight
    # [heads, head_dim, D], a bitcast only of the weight transposed, and
    # the layer loop then copies its slice out of the stack and
    # transposes it (57 MB a Phi-3 layer) where wo and the MLP's three
    # are read in place (scripts/layer_loop.py shows either)
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    q = q.reshape(b, s, h_heads, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if "q_norm" in layer_params:  # Qwen3-family per-head norms, pre-rope
        q = rms_norm(q, layer_params["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer_params["k_norm"], cfg.rms_norm_eps)
    if cfg.key_multiplier != 1.0:  # Falcon-H1's fixed µP scalar, pre-rope
        k = (k.astype(jnp.float32) * cfg.key_multiplier).astype(k.dtype)
    if not rope:
        return q, k, v
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling,
                   seq_basis=seq_basis)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling,
                   seq_basis=seq_basis)
    return q, k, v


def make_gqa_attn_fn(cfg, b, s, positions, slot_mapping, block_tables,
                     context_lens, mesh, kv_gather_axis=None,
                     layer_offset=0, tp_axis=None, live_rows=None,
                     rope: bool = True, scale=None):
    """The standard attention block: QKV + RoPE, paged-KV scatter, GQA
    attention, output projection. Families with different attention (MLA,
    models/deepseek.py) plug their own via run_layers' attn_fn.

    ``kv_gather_axis``: inside a manual shard_map whose batch rows shard
    over that mesh axis while the KV cache stays replicated across it
    (the pipelined pp x dp program, parallel/pipeline.py), every member
    must apply EVERY member's cache writes or the replicas diverge — the
    new K/V and their slots are all-gathered over the axis before the
    scatter; attention still runs on the local rows only.

    ``layer_offset`` is part of the family attn-factory contract (the
    pipeline passes the stage's first GLOBAL layer index): this family
    has no per-layer-index semantics, so it is accepted and ignored —
    Gemma-2's window alternation is the consumer.

    ``live_rows``: the step's ``decode_live_rows(slot_mapping)`` where
    the trunk has made it for another kernel of the layer (Falcon-H1's
    mixer); made here otherwise.

    ``rope=False``, ``scale``: layers with no positional term and a
    published softmax scale that is not ``head_dim ** -0.5``
    (models/granite_hybrid.py)."""
    del layer_offset  # no global-layer-index semantics in this family
    del tp_axis  # qkv biases are tp-sharded; no replicated additive terms
    h_heads, hd = cfg.num_heads, cfg.head_dim
    # a family that generates by diffusion over blocks (models/sdar.py):
    # the mask is causal over blocks and full inside one, in prefill and
    # in a block pass (two blocks a row: engine/model_runner.py
    # _build_block_step); the pass's kernel call alone is scope block_attn
    block_len = max(1, cfg.block_length)
    def kernel_scope():
        return (jax.named_scope("block_attn") if s == 2 * cfg.block_length
                else contextlib.nullcontext())
    # a decode step's rows that hold a token: the same for every layer,
    # made once, outside the scan
    if live_rows is None:
        live_rows = decode_live_rows(slot_mapping)

    def attn_fn(x, layer_params, k_all, v_all, li):
        q, k, v = qkv_prologue(cfg, x, layer_params, b, s, positions,
                               context_lens, rope=rope)

        # in-place scatter into the stacked cache + layer-indexed kernels:
        # no per-layer cache slice is ever materialized inside the scan
        if kv_gather_axis is not None:
            k_w, v_w, slots_w = gather_kv_writes(k, v, slot_mapping,
                                                 kv_gather_axis)
        else:
            k_w, v_w, slots_w = k, v, slot_mapping
        k_all, v_all = scatter_kv_stacked(k_all, v_all, k_w, v_w, slots_w, li)
        with kernel_scope():
            attn = attention(
                q, k_all, v_all, block_tables, positions, context_lens,
                impl=cfg.attention_impl, mesh=mesh, layer_idx=li,
                # mistral/phi3-style whole-model window (0 = full attention;
                # rides the XLA path — see ops/attention.py)
                sliding_window=cfg.sliding_window or None,
                live_rows=live_rows,
                **({} if scale is None else {"scale": scale}),
                **({} if block_len == 1 else {"block_len": block_len}),
            )
        delta = dense(attn.reshape(b, s, h_heads * hd), layer_params["wo"])
        return delta, k_all, v_all

    return attn_fn


def make_sp_gqa_attn_fn(cfg, b, s, positions, slot_mapping, block_tables,
                        context_lens, chunk_start, mesh, sp_axis="sp",
                        head_axis=None):
    """Sequence-parallel sibling of make_gqa_attn_fn for long-context
    prefill (parallel/sequence.py): the chunk's tokens are sharded over
    the mesh's ``sp_axis``; QKV projections / RoPE / MLP are position-
    local and partition for free, attention runs as one ring pass over
    the chunk's fresh K/V merged with the committed paged prefix (read
    in place by the Pallas page-walk kernel, or gathered on the XLA
    fallback — parallel/sequence.sp_chunk_attention), and the
    fresh K/V scatter into the paged cache exactly as the dense path
    does (GSPMD collects the sequence shards at the scatter). B is 1 by
    construction — one oversized prompt owns the whole mesh."""
    from ..parallel.sequence import sp_chunk_attention

    h_heads, hd = cfg.num_heads, cfg.head_dim

    def attn_fn(x, layer_params, k_all, v_all, li):
        q, k, v = qkv_prologue(cfg, x, layer_params, b, s, positions,
                               context_lens)
        # the prefix gather reads the INCOMING cache (pre-scatter): the
        # chunk's own positions are masked there anyway, and gathering
        # before the scatter lets XLA overlap the two instead of
        # serializing on the donated buffer
        attn = sp_chunk_attention(
            q, k, v, k_all, v_all, block_tables, chunk_start,
            context_lens[0], li, mesh, axis=sp_axis, head_axis=head_axis,
            impl=cfg.attention_impl,
        )
        k_all, v_all = scatter_kv_stacked(k_all, v_all, k, v, slot_mapping, li)
        delta = dense(attn.reshape(b, s, h_heads * hd), layer_params["wo"])
        return delta, k_all, v_all

    return attn_fn


def sp_decoder_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [1, S] one chunk, S sharded over sp
    positions: jax.Array,     # [1, S] absolute positions (pad → repeat last)
    kv_cache: KVCache,
    block_tables: jax.Array,  # [1, W]
    slot_mapping: jax.Array,  # [1, S] flat cache slot per token; -1 drops
    context_lens: jax.Array,  # [1] valid tokens incl. this chunk
    chunk_start,              # traced scalar: chunk's first absolute position
    mesh,
    sp_axis: str = "sp",
    head_axis=None,
    mlp_fn=swiglu_mlp,
) -> Tuple[jax.Array, KVCache]:
    """One sequence-parallel prefill chunk through the GQA trunk.

    Returns (pre-final-norm hidden [1, S, D], updated kv_cache) — the
    engine samples from the last valid position via logits_from_hidden,
    exactly like the dense step program's return_hidden path."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
    attn_fn = make_sp_gqa_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens,
        chunk_start, mesh, sp_axis=sp_axis, head_axis=head_axis,
    )
    hidden, kv_cache, _, _ = run_layers(
        hidden, kv_cache, params["layers"], cfg, attn_fn, mlp_fn
    )
    return hidden, kv_cache


def run_layers(
    hidden: jax.Array,
    kv_cache: KVCache,
    layers,                   # stacked layer pytree (leading L axis)
    cfg: ModelConfig,
    attn_fn,                  # (x, lp, k_all, v_all, li) -> (delta, k_all, v_all)
    mlp_fn,                   # (x, lp) -> [B, S, D]
    li0: int = 0,             # first layer's index into the KV cache
):
    """One lax.scan over a stacked group of decoder layers.

    Families mix groups with different weights (DeepSeek: k dense layers
    then MoE layers) by chaining calls — ``li0`` keeps cache layer indices
    contiguous across groups. Returns (hidden, kv_cache, next_li, aux):
    an mlp_fn may return ``(y, aux)`` (the routed experts' counters) and
    ``aux`` is then every layer's, stacked; None for a plain mlp_fn.

    With ``cfg.hc_mult > 1`` ``hidden`` is the family's residual streams
    [B, S, n D] and each sublayer reads and writes them through
    models/mhc.py; otherwise the two ``hidden + delta`` below are all
    there is.
    """
    k_all, v_all = kv_cache
    mixed = cfg.hc_mult > 1

    def layer_step(carry, layer_params):
        hidden, k_all, v_all, li = carry
        # named scopes are metadata on the lowered operations (the
        # profiler's capture and the HLO dump show them); the compiled
        # code is the same with and without them
        with jax.named_scope("attn"):
            x = hidden
            if mixed:
                x, coeffs = mhc.read(hidden, layer_params, "attn", cfg)
            x = rms_norm(x, layer_params["ln1"], cfg.rms_norm_eps)
            delta, k_all, v_all = attn_fn(x, layer_params, k_all, v_all, li)
            hidden = (mhc.write(hidden, delta, coeffs, cfg) if mixed
                      else hidden + delta)
        with jax.named_scope("mlp"):
            x = hidden
            if mixed:
                x, coeffs = mhc.read(hidden, layer_params, "mlp", cfg)
            x = rms_norm(x, layer_params["ln2"], cfg.rms_norm_eps)
            out = mlp_fn(x, layer_params)
            delta, aux = out if isinstance(out, tuple) else (out, None)
            hidden = (mhc.write(hidden, delta, coeffs, cfg) if mixed
                      else hidden + delta)
        return (hidden, k_all, v_all, li + 1), aux

    (hidden, k_all, v_all, li), aux = jax.lax.scan(
        layer_step, (hidden, k_all, v_all, jnp.int32(li0)), layers
    )
    return hidden, (k_all, v_all), li, aux


def lm_logits(hidden: jax.Array, params: Params, cfg: ModelConfig) -> jax.Array:
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        return hidden @ params["embed"].T  # tied: embed stays unquantized
    return dense(hidden, lm_head)


# the engine's name for "final norm + lm head over any [..., D] slice":
# it samples from last-position hidden states without paying the full
# [B, S, V] head (engine/model_runner.py)
logits_from_hidden = lm_logits


def decoder_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, S]
    positions: jax.Array,     # [B, S] absolute positions (pad → repeat last)
    kv_cache: KVCache,
    block_tables: jax.Array,  # [B, W] (W = kv_width blocks)
    slot_mapping: jax.Array,  # [B, S] flat cache slot per token; -1 drops
    context_lens: jax.Array,  # [B] valid tokens incl. the ones being written
    mesh=None,                # multi-device mesh for the pallas shard_map path
    mlp_fn=swiglu_mlp,       # (normed_x [B,S,D], layer_params) -> [B,S,D]
    return_hidden: bool = False,
) -> Tuple[jax.Array, KVCache]:
    """Shared decoder trunk: embed → scan(attention + mlp_fn) → logits.

    The attention block (RoPE, paged-KV scatter, GQA attention) is common
    to GQA families; ``mlp_fn`` is the per-family feed-forward — dense
    SwiGLU here, routed experts in models/mixtral.py.
    Returns (logits [B, S, V], updated kv_cache) — or the pre-final-norm
    hidden states [B, S, D] with ``return_hidden``, so the engine can
    run ``logits_from_hidden`` on just the positions it samples (the
    full-S lm head is the dominant prefill matmul otherwise).
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]  # [B, S, D]
    attn_fn = make_gqa_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens, mesh
    )
    hidden, kv_cache, _, _ = run_layers(
        hidden, kv_cache, params["layers"], cfg, attn_fn, mlp_fn
    )
    if return_hidden:
        return hidden, kv_cache
    with jax.named_scope("lm_head"):
        return lm_logits(hidden, params, cfg), kv_cache


def embed_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,      # [R, S] right-padded prompt rows
    positions: jax.Array,   # [R, S] (pad → repeat last)
    valid_lens: jax.Array,  # [R] real tokens per row
) -> jax.Array:
    """Prefill-only trunk for the embeddings workload: dense causal
    self-attention with NO cache reads or writes (the whole context is
    the prompt; nothing decodes afterwards, so paged-KV state would be
    pure waste), final norm, and the LAST valid position's hidden state
    as the sequence embedding — the standard decoder-LM pooling. The
    engine L2-normalizes at the edge. Returns [R, D] float32."""
    from ..ops.attention import prefill_attention

    b, s = tokens.shape
    h_heads, hd = cfg.num_heads, cfg.head_dim
    hidden = params["embed"][tokens]

    def attn_fn(x, layer_params, k_all, v_all, li):
        q, k, v = qkv_prologue(cfg, x, layer_params, b, s, positions,
                               valid_lens)
        attn = prefill_attention(q, k, v, valid_lens)
        delta = dense(attn.reshape(b, s, h_heads * hd), layer_params["wo"])
        return delta, k_all, v_all

    dummy = jnp.zeros((), jnp.float32)
    hidden, _, _, _ = run_layers(
        hidden, (dummy, dummy), params["layers"], cfg, attn_fn, swiglu_mlp
    )
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    rows = jnp.arange(b)
    return hidden[rows, valid_lens - 1].astype(jnp.float32)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    positions: jax.Array,
    kv_cache: KVCache,
    block_tables: jax.Array,
    slot_mapping: jax.Array,
    context_lens: jax.Array,
    mesh=None,
    return_hidden: bool = False,
    state_slots=None,         # a family with records by slot reads it
) -> Tuple[jax.Array, KVCache]:
    """Llama forward = shared trunk with the dense SwiGLU MLP."""
    return decoder_forward(
        params, cfg, tokens, positions, kv_cache, block_tables,
        slot_mapping, context_lens, mesh=mesh, mlp_fn=swiglu_mlp,
        return_hidden=return_hidden,
    )
