"""Gemma-2 family: sandwich norms, GeGLU, softcapped + alternating
sliding-window attention, tied embeddings.

Architecture deltas vs the llama trunk (matching HF
transformers/models/gemma2/modeling_gemma2.py, validated logit-exact in
tests/test_gemma2.py):

- embeddings scaled by sqrt(hidden_size) (cast to the activation dtype
  first, like HF's ``normalizer`` tensor);
- RMSNorm multiplies by ``1 + weight`` and runs in float32;
- four norms per layer: pre/post attention and pre/post MLP — the post
  norms apply to the block OUTPUT before the residual add;
- GeGLU MLP (tanh-approximated gelu on the gate);
- attention scaled by ``query_pre_attn_scalar**-0.5`` with logit
  softcapping, and EVEN layers see only a sliding window of the cache
  (``config.layer_types``: sliding/full alternating from layer 0);
- logits through the tied embedding with final softcapping.

Softcap/window serve on the Pallas kernels natively (the window rides as
a runtime scalar operand; ops/attention.py). Reference analog: the Gemma
models of the engines the reference delegates to (vLLM model zoo,
SURVEY §2.4).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import attention, scatter_kv_stacked
from ..ops.live_rows import decode_live_rows
from .llama import (  # noqa: F401  (shared cache layout)
    alternating_window,
    apply_rope,
    gather_kv_writes,
    init_kv_cache,
)
from .quant import dense

Params = Dict
KVCache = Tuple[jax.Array, jax.Array]

CACHE_SPEC = P(None, None, None, "tp", None)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Gemma RMSNorm: float32 compute, multiply by (1 + weight)."""
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (n * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def config_fields(config: dict) -> dict:
    """The window alternates by layer whatever ``use_sliding_window``
    says (the common translation honours that key)."""
    return {"sliding_window": config.get("sliding_window", 0) or 0}


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    l, d_model = cfg.num_layers, cfg.hidden_size
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    inter = cfg.intermediate_size
    keys = jax.random.split(key, 9)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    layers = {
        "ln1": jnp.zeros((l, d_model), dtype),           # (1 + w) centered
        "wq": w(keys[1], (l, d_model, h * hd), d_model),
        "wk": w(keys[2], (l, d_model, kvh * hd), d_model),
        "wv": w(keys[3], (l, d_model, kvh * hd), d_model),
        "wo": w(keys[4], (l, h * hd, d_model), h * hd),
        "ln_post_attn": jnp.zeros((l, d_model), dtype),
        "ln_pre_mlp": jnp.zeros((l, d_model), dtype),
        "w_gate": w(keys[5], (l, d_model, inter), d_model),
        "w_up": w(keys[6], (l, d_model, inter), d_model),
        "w_down": w(keys[7], (l, inter, d_model), inter),
        "ln_post_mlp": jnp.zeros((l, d_model), dtype),
    }
    return {
        "embed": w(keys[0], (cfg.vocab_size, d_model), d_model),
        "layers": layers,
        "final_norm": jnp.zeros((d_model,), dtype),
    }


def param_specs(params: Params) -> Dict:
    layer_specs = {
        "ln1": P(), "ln_post_attn": P(), "ln_pre_mlp": P(),
        "ln_post_mlp": P(),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "w_gate": P(None, None, "tp"),
        "w_up": P(None, None, "tp"),
        "w_down": P(None, "tp", None),
    }
    specs = {
        "embed": P(),
        "final_norm": P(),
        "layers": {k: layer_specs[k] for k in params["layers"]},
    }
    if "lm_head" in params:
        specs["lm_head"] = P(None, "tp")
    return specs


def embed_tokens(params: Params, tokens: jax.Array) -> jax.Array:
    """Gemma scales embeddings by sqrt(hidden_size) (HF ``normalizer``)."""
    hidden = params["embed"][tokens]
    d_model = params["embed"].shape[-1]
    return hidden * jnp.asarray(math.sqrt(d_model), hidden.dtype)


def make_attn_fn(cfg, b, s, positions, slot_mapping, block_tables,
                 context_lens, mesh, kv_gather_axis=None, layer_offset=0,
                 tp_axis=None):
    """Gemma-2 attention block for run_layers: plain-rope QKV,
    query_pre_attn_scalar scaling, logit softcap, and the alternating
    per-layer sliding window (EVEN layers windowed). Same contract as
    llama.make_gqa_attn_fn incl. ``kv_gather_axis`` (the pipelined
    pp x dp program's replicated-cache sync; see llama.py).

    ``layer_offset``: under pipeline staging ``li`` is the STAGE-LOCAL
    layer index (it addresses the stage's cache slab), but the
    sliding/full alternation follows the GLOBAL layer number — the
    stage's first global layer index comes in here (may be traced)."""
    del tp_axis  # bias-free projections; the wo matmul is the partial
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = (cfg.query_pre_attn_scalar or hd) ** -0.5
    live_rows = decode_live_rows(slot_mapping)

    def attn_fn(x, lp, k_all, v_all, li):
        q = dense(x, lp["wq"]).reshape(b, s, h, hd)
        k = dense(x, lp["wk"]).reshape(b, s, kvh, hd)
        v = dense(x, lp["wv"]).reshape(b, s, kvh, hd)
        q = apply_rope(q, positions, cfg.rope_theta, None)
        k = apply_rope(k, positions, cfg.rope_theta, None)
        if kv_gather_axis is not None:
            k_w, v_w, slots_w = gather_kv_writes(k, v, slot_mapping,
                                                 kv_gather_axis)
        else:
            k_w, v_w, slots_w = k, v, slot_mapping
        k_all, v_all = scatter_kv_stacked(k_all, v_all, k_w, v_w, slots_w, li)
        # layer_types alternates sliding/full starting sliding at layer 0
        window = alternating_window(cfg, li, layer_offset)
        attn = attention(
            q, k_all, v_all, block_tables, positions, context_lens,
            impl=cfg.attention_impl, mesh=mesh, layer_idx=li,
            scale=scale, softcap=cfg.attn_logit_softcap,
            sliding_window=window, live_rows=live_rows,
        )
        delta = dense(attn.reshape(b, s, h * hd), lp["wo"])
        return delta, k_all, v_all

    return attn_fn


def mlp_fn(x: jax.Array, lp) -> jax.Array:
    """GeGLU (tanh-approximated gelu on the gate)."""
    gate = jax.nn.gelu(dense(x, lp["w_gate"]), approximate=True)
    return dense(gate * dense(x, lp["w_up"]), lp["w_down"])


def run_layers(hidden, kv_cache, layers, cfg, attn_fn, mlp, li0: int = 0):
    """Sandwich-norm layer scan: pre/post norms around BOTH the attention
    and MLP blocks, post norms applied to the block output before the
    residual add. Same contract as llama.run_layers (pipeline staging
    calls this with psum-wrapped attn/mlp); the dense MLP has no aux."""
    eps = cfg.rms_norm_eps
    k_all, v_all = kv_cache

    def layer_step(carry, lp):
        hidden, k_all, v_all, li = carry
        with jax.named_scope("attn"):
            x = rms_norm(hidden, lp["ln1"], eps)
            delta, k_all, v_all = attn_fn(x, lp, k_all, v_all, li)
            hidden = hidden + rms_norm(delta, lp["ln_post_attn"], eps)
        with jax.named_scope("mlp"):
            x = rms_norm(hidden, lp["ln_pre_mlp"], eps)
            hidden = hidden + rms_norm(mlp(x, lp), lp["ln_post_mlp"], eps)
        return (hidden, k_all, v_all, li + 1), None

    (hidden, k_all, v_all, li), _ = jax.lax.scan(
        layer_step, (hidden, k_all, v_all, jnp.int32(li0)), layers
    )
    return hidden, (k_all, v_all), li, None


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, S]
    positions: jax.Array,     # [B, S]
    kv_cache: KVCache,        # stacked [L, N, bs, KVH, Dpad]
    block_tables: jax.Array,  # [B, W]
    slot_mapping: jax.Array,  # [B, S]
    context_lens: jax.Array,  # [B]
    mesh=None,
    return_hidden: bool = False,
    state_slots=None,         # a family with records by slot reads it
) -> Tuple[jax.Array, KVCache]:
    b, s = tokens.shape
    with jax.named_scope("embed"):
        hidden = embed_tokens(params, tokens)
    attn_fn = make_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens, mesh
    )
    hidden, kv_cache, _, _ = run_layers(
        hidden, kv_cache, params["layers"], cfg, attn_fn, mlp_fn
    )
    if return_hidden:
        return hidden, kv_cache
    return logits_from_hidden(hidden, params, cfg), kv_cache


def logits_from_hidden(hidden: jax.Array, params: Params,
                       cfg: ModelConfig) -> jax.Array:
    """Final (1+w) norm + tied-or-untied head + final softcapping over
    any [..., D] slice (the engine samples from last-position hidden)."""
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    lm_head = params.get("lm_head")  # untied finetunes; normally tied
    logits = (
        hidden @ params["embed"].T if lm_head is None
        else dense(hidden, lm_head)
    )
    cap = cfg.final_logit_softcap
    if cap:
        logits = cap * jnp.tanh(logits / cap)
    return logits
