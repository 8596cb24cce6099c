"""MiniCPM-SALA (``model_type: minicpm_sala``): one trunk whose layers
are of two kinds, each with its own kind of cache. ``mixer_types`` names
every layer's token mixer; the feed-forward (SwiGLU) follows each.

Common: ``h = embed(ids) · scale_emb``; every sublayer is pre-norm
RMSNorm and adds ``scale_depth / √depth`` times its output to the
residual, ``depth`` the *published* number of layers (``depth_of``: a
trunk served cut in depth keeps the published scale, which is a width
of the residual and not a count of the layers held); logits =
``head(RMSNorm(h) / (hidden_size / dim_model_base))``.

``lightning-attn`` (gated linear attention), ``n = RMSNorm(x)``, ``H``
heads of ``d``:

- ``q, k, v = n W_q, n W_k, n W_v``; ``q, k ←`` per-head RMSNorm, then
  the rotary embedding over the whole head;
- ``S_t = λ_h S_{t−1} + v_t ⊗ k_t``, ``o_t = S_t q_t / √d``: Mamba-2's
  recurrence with ``Δ = 1``, a group a head and no skip, so prefill runs
  ``ops/ssm.ssd_chunked_scan`` and decode ``ops/ssm.ssm_decode_step`` as
  Falcon-H1 does, on a float32 state ``[H, d, d]`` kept by slot;
- ``λ_h = exp(−s_h (1 − l / (depth − 1) + 1e-5))``, ``s_h = 2^(−8 (h + 1)
  / H)``, ``l`` the layer's published index (``log_decay``, a constant
  of the layer made with the weights);
- ``o ← RMSNorm(o)`` per head ``⊙ sigmoid(n W_g)``; output ``o W_o``.

``minicpm4`` (InfLLM-V2 block-sparse attention, ``ops/
sparse_attention.py``): grouped-query attention without rotary
embedding, per-head RMSNorm on q and k, dense up to ``dense_len`` tokens
of context and over the kept blocks past it; ``o ⊙ sigmoid(n W_g)``,
then ``W_o``.

**Two caches.** The attention layers hold pages and no state, the
lightning layers state and no pages, so each is stacked over its own
layers only: a side of the cache is a ``trunk.SlotCache(kv, state)``
with the step's counters beside it, the k side with the key pages ``[A,
N·KVH, page, D]`` (a kv head a page: ops/sparse_attention.py) and the
lightning state ``[L, slots, H, d, d]`` float32 (a head's ``[key,
value]``: the value's dimension on the lanes, the order
``ops/ssm.ssm_decode_step`` walks), the v side with the
value pages and, as its ``state``, the compressed keys: one float32 mean
a page ``[A, N·KVH, D]``. The trunk scans each homogeneous run of
``mixer_types`` over that run's stacked weights (``params["runs"]``).

The family keeps recurrent state, so it inherits ``SEQUENCE_STATE``
and the engine's handling (state by slot, prefix hits blanked, resume
from position 0) from Falcon-H1.

Scopes: ``lightning`` (the whole mixer) with ``lightning_state``
(decode) or ``lightning_scan`` (prefill) inside, ``attn`` with
``sparse_select`` and ``sparse_attn`` inside, ``mlp``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops import sparse_attention as sparse
from ..ops.attention import lane_pad
from ..ops.live_rows import decode_live_rows
from ..ops.ssm import record_shape, ssd_chunked_scan, ssm_decode_step
from .falcon_h1 import CLAIM, SEQUENCE_STATE, slot_records  # noqa: F401
from .llama import (apply_rope, layer_runs, lm_logits, rms_norm, run_specs,
                    swiglu_mlp)
from .quant import dense
from .trunk import SlotCache, scaled, walk_runs

Params = Dict[str, Any]

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# published keys only this family computes, refused under any other
# model_type with Falcon-H1's sentence (models.published)
CLAIMED_KEYS = ("mixer_types",)
CLAIMED_PREFIXES = ("lightning_",)


def claimed_keys(config: dict) -> List[str]:
    return sorted(k for k in config
                  if k in CLAIMED_KEYS or k.startswith(CLAIMED_PREFIXES))


def config_fields(config: dict) -> dict:
    """ModelConfig's MiniCPM-SALA fields from the published keys; what
    the family module does not compute is refused here, before any weight
    is made. ``sparse_config`` (the MiniCPM4 family's published group)
    and ``depth_cut`` (``{"of_layers", "first_layer"}``: which layers of
    the published trunk a cut configuration holds) are optional groups."""
    only = {
        "attn_use_rope": False, "lightning_use_rope": True, "qk_norm": True,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True, "attention_bias": False,
        "rope_scaling": None, "hidden_act": "silu",
        "lightning_scale": "1/sqrt(d)",
    }
    for key, value in only.items():
        if config.get(key, value) != value:
            raise NotImplementedError(
                f"minicpm_sala with {key}={config[key]!r} "
                f"(models/minicpm_sala.py computes {key}={value!r} only)")
    mixers = tuple(config.get("mixer_types") or ())
    layers = int(config["num_hidden_layers"])
    unknown = sorted(set(mixers) - {"lightning-attn", "minicpm4"})
    if len(mixers) != layers or unknown:
        raise ValueError(
            f"minicpm_sala: mixer_types has {len(mixers)} entries for "
            f"{layers} layers, unknown kinds {unknown} (lightning-attn | "
            "minicpm4)")
    heads = int(config.get("lightning_nh", config["num_attention_heads"]))
    if int(config.get("lightning_nkv", heads)) != heads:
        raise NotImplementedError(
            "minicpm_sala with lightning_nkv != lightning_nh: the state is "
            "kept a head (models/minicpm_sala.py)")
    cut = config.get("depth_cut") or {}
    sparse = config.get("sparse_config") or {}
    fields = dict(
        mixer_types=mixers, lightning_heads=heads,
        lightning_head_dim=int(config.get("lightning_head_dim",
                                          config.get("head_dim", 128))),
        scale_emb=float(config.get("scale_emb", 1.0)),
        scale_depth=float(config.get("scale_depth", 1.0)),
        dim_model_base=int(config.get("dim_model_base",
                                      config["hidden_size"])),
        depth_of=int(cut.get("of_layers", layers)),
        first_layer=int(cut.get("first_layer", 0)),
    )
    for key in ("kernel_size", "kernel_stride", "block_size", "topk",
                "init_blocks", "window_size", "dense_len"):
        if key in sparse:
            fields[f"sparse_{key}"] = int(sparse[key])
    return fields


# standard deviation of the served logits, and of q·k / sqrt(d) in the
# attention layers, under random weights (the query norm's weight: the
# per-head norms make the scores' size a matter of that weight alone).
# As models/falcon_h1.py: at 1.0 attention is spread thinly over every
# key and neither the pages' precision nor a dropped block shows. At 3.0
# with a plain fan-in output projection an fp8 page cache still read as
# the bfloat16 one does on the chip (PERF.md section 6, PR 37): three
# layers of twelve attend, and each reaches the residual through
# scale_depth / sqrt(32) = 0.25 and a gate of a half. So the attention
# layers' output projection is divided by those two fixed scalars (as
# Falcon-H1's matrices by its multipliers): an attention layer then adds
# what it adds in a family without them, and the pages' precision shows.
# The other sublayers keep plain fan-in weights: with every sublayer at
# unit size twelve layers amplify bfloat16's own rounding six-fold
LOGIT_STD = 2.0
ATTN_SCORE_STD = 3.0
GATE_MEAN = 0.5
# tokens a chunk of the lightning scan's matrix form
SCAN_CHUNK = 256
PAGE = 16   # the engine's kv_block_size: the published kernel_stride

# the step's counters, in the order of the v side's ``counts``
# (the engine renders them on /metrics: ModelRunner._init_family_counters)
STEP_COUNTERS = (
    ("dynamo_sparse_attention_kept_tokens_total",
     "Tokens a sparse attention layer attended to, summed over the live "
     "rows of every decode step (one layer's: the layers keep alike)"),
    ("dynamo_sparse_attention_context_tokens_total",
     "Tokens of context, summed over the live rows of every decode step: "
     "what a dense layer would have attended to"),
    ("dynamo_sparse_attention_rows_total",
     "Live rows past dense_len (rows that selected their blocks), summed "
     "over decode steps"),
    ("dynamo_sparse_attention_decode_steps_total", "Decode steps counted"),
    ("dynamo_lightning_scan_tokens_total",
     "Tokens a lightning layer's chunked scan advanced its states by, "
     "summed over the rows of every prefill step (one layer's)"),
    ("dynamo_lightning_scan_steps_total", "Prefill steps counted"),
)


def step_counts(kv_cache):
    return kv_cache[1].counts


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SalaCache(SlotCache):
    """A side of the cache: pages, the records beside them, and the
    step's counters (int32, wrapping: a reader takes differences). Both
    sides have one structure, as the engine shards and donates them
    alike; the counters of the k side are not used."""
    counts: Any = None


CACHE_SPEC = SalaCache(kv=P(), state=P(), counts=P())


def log_decays(cfg: ModelConfig) -> jax.Array:
    """[layers, H] float32: ``ln λ_h`` of every layer (used by the
    lightning ones), from the published index and depth."""
    h = cfg.lightning_heads
    slopes = 2.0 ** (-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1.0) / h)
    index = cfg.first_layer + jnp.arange(cfg.num_layers, dtype=jnp.float32)
    depth = cfg.depth_of or cfg.num_layers
    return -slopes[None, :] * (1.0 - index / max(depth - 1, 1) + 1e-5)[:, None]


def residual_scale(cfg: ModelConfig) -> float:
    return cfg.scale_depth / math.sqrt(cfg.depth_of or cfg.num_layers)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families; the embedding divided by ``scale_emb`` (hidden states of
    unit size), the head drawn for logits of standard deviation
    ``LOGIT_STD`` after the ``hidden_size / dim_model_base`` division,
    the attention layers' query norm weighing ``ATTN_SCORE_STD`` and
    their output projection divided by the residual's ``scale_depth /
    √depth`` and the gate's mean (see ``ATTN_SCORE_STD``)."""
    d, inter = cfg.hidden_size, cfg.intermediate_size
    lh, ld = cfg.lightning_heads, cfg.lightning_head_dim
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    decays = log_decays(cfg)
    res = residual_scale(cfg)

    def w(key, shape, fan_in, gain=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    runs, at = [], 0
    for r, (kind, _, n) in enumerate(layer_runs(cfg.mixer_types)):
        keys = jax.random.split(jax.random.fold_in(key, r + 1), 8)
        qw, kw = (lh * ld, lh * ld) if kind == LIGHTNING else (h * hd, kvh * hd)
        hdim = ld if kind == LIGHTNING else hd
        run = {
            "ln1": jnp.ones((n, d), dtype),
            "wq": w(keys[0], (n, d, qw), d),
            "wk": w(keys[1], (n, d, kw), d),
            "wv": w(keys[2], (n, d, kw), d),
            "wg": w(keys[3], (n, d, qw), d),
            "wo": w(keys[4], (n, qw, d), qw,
                    1.0 / (res * GATE_MEAN) if kind == SPARSE else 1.0),
            "q_norm": jnp.full((n, hdim), 1.0 if kind == LIGHTNING
                               else ATTN_SCORE_STD, dtype),
            "k_norm": jnp.ones((n, hdim), dtype),
            "ln2": jnp.ones((n, d), dtype),
            "w_gate": w(keys[5], (n, d, inter), d),
            "w_up": w(keys[6], (n, d, inter), d),
            "w_down": w(keys[7], (n, inter, d), inter),
        }
        if kind == LIGHTNING:
            run["o_norm"] = jnp.ones((n, qw), dtype)
            run["log_decay"] = decays[at:at + n]
        runs.append(run)
        at += n
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    params: Params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32)
                  / cfg.scale_emb).astype(dtype),
        "runs": runs,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(k_head, (d, cfg.vocab_size), d,
                              LOGIT_STD * d / (cfg.dim_model_base or d))
    return params


param_specs = run_specs    # tp > 1 is refused for state by slot


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(SalaCache(k pages, lightning state, -), SalaCache(v pages,
    page means, counters))``; see the module docstring."""
    kinds = cfg.mixer_types
    n_attn, n_light = kinds.count(SPARSE), kinds.count(LIGHTNING)
    kvh = cfg.num_kv_heads
    pages = (n_attn, num_blocks * kvh, block_size, lane_pad(cfg.head_dim))
    # a record as the state kernel walks it: [H, d (key), d (value)], the
    # value's dimension on the lanes (ops/ssm.record_shape)
    state = jnp.zeros((n_light, num_slots) + record_shape(
        cfg.lightning_heads, cfg.lightning_head_dim, cfg.lightning_head_dim,
        1), jnp.float32)
    means = jnp.zeros((n_attn, num_blocks * kvh, pages[-1]), jnp.float32)
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    return (SalaCache(jnp.zeros(pages, dtype), state, counts),
            SalaCache(jnp.zeros(pages, dtype), means, counts))


def _gate(o, x, lp):
    return o * jax.nn.sigmoid(dense(x, lp["wg"]).astype(jnp.float32)).astype(o.dtype)


def make_lightning_fn(cfg: ModelConfig, b: int, s: int, positions,
                      slot_mapping, state_slots, live_rows):
    """``fn(n1, layer_params, state_all, li) -> (delta, state_all)`` over
    the state records stacked over the lightning layers. ``live_rows``:
    the step's ``decode_live_rows(slot_mapping)``."""
    h, hd = cfg.lightning_heads, cfg.lightning_head_dim
    valid = slot_mapping >= 0
    decode = s == 1
    live = valid[:, 0]
    read, write = slot_records(b, decode, live, state_slots,
                               None if decode else positions[:, 0] == 0)
    dt = jnp.broadcast_to(valid[..., None].astype(jnp.float32), (b, s, h))
    no_skip = jnp.zeros((h,), jnp.float32)

    def fn(x, lp, state_all, li):
        q = dense(x, lp["wq"]).reshape(b, s, h, hd)
        k = dense(x, lp["wk"]).reshape(b, s, h, hd)
        v = dense(x, lp["wv"]).reshape(b, s, h, hd)
        q = apply_rope(rms_norm(q, lp["q_norm"], cfg.rms_norm_eps),
                       positions, cfg.rope_theta)
        k = apply_rope(rms_norm(k, lp["k_norm"], cfg.rms_norm_eps),
                       positions, cfg.rope_theta)
        q = scaled(q, hd ** -0.5)
        a = lp["log_decay"].astype(jnp.float32)
        if decode:
            with jax.named_scope("lightning_state"):
                o, state_all = ssm_decode_step(
                    v[:, 0], dt[:, 0], a, k[:, 0], q[:, 0], no_skip,
                    state_all, li, live_rows)
                o = o[:, None]
        else:
            with jax.named_scope("lightning_scan"):
                o, s1 = ssd_chunked_scan(v, dt, a, k, q, no_skip,
                                         read(state_all, li), SCAN_CHUNK)
                state_all = write(state_all, li, s1)
        o = rms_norm(o.astype(x.dtype), lp["o_norm"].reshape(h, hd),
                     cfg.rms_norm_eps).reshape(b, s, h * hd)
        return dense(_gate(o, x, lp), lp["wo"]), state_all

    return fn


def make_sparse_fn(cfg: ModelConfig, b: int, s: int, positions, slot_mapping,
                   block_tables, context_lens, live_rows):
    """``fn(n1, layer_params, k_all, v_all, means_all, li) -> (delta,
    k_all, v_all, means_all, kept [B])`` over the pages and page means
    stacked over the attention layers. ``live_rows``: the step's
    ``decode_live_rows(slot_mapping)``."""
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shape = sparse.sparse_shape(cfg, PAGE)
    scale = hd ** -0.5
    valid = slot_mapping >= 0
    first = positions[:, 0].astype(jnp.int32)
    last = first + valid.sum(axis=1).astype(jnp.int32)
    # the (row, kv head) pairs of a decode step whose row holds a token:
    # the same for every layer, made once, outside the scan
    head_rows = sparse.head_live_rows(live_rows, kvh)

    def fn(x, lp, k_all, v_all, means_all, li):
        q = rms_norm(dense(x, lp["wq"]).reshape(b, s, h, hd), lp["q_norm"],
                     cfg.rms_norm_eps)
        k = rms_norm(dense(x, lp["wk"]).reshape(b, s, kvh, hd), lp["k_norm"],
                     cfg.rms_norm_eps)
        v = dense(x, lp["wv"]).reshape(b, s, kvh, hd)
        k_all, v_all = sparse.scatter_head_pages(k_all, v_all, k, v,
                                                 slot_mapping, li)
        with jax.named_scope("sparse_select"):
            means_all = sparse.write_page_means(
                means_all, k_all, li, block_tables, first, last,
                s // PAGE + 1, kvh)
        if s == 1:
            o, kept = sparse.decode_attention(
                q, k_all, v_all, means_all, li, block_tables, context_lens,
                shape, kvh, scale, impl=cfg.attention_impl,
                live_rows=head_rows)
        else:
            o = sparse.prefill_attention(
                q, k_all, v_all, means_all, li, block_tables, positions,
                shape, kvh, scale)
            kept = context_lens.astype(jnp.int32)
        o = o[..., :hd].reshape(b, s, h * hd)
        return dense(_gate(o, x, lp), lp["wo"]), k_all, v_all, means_all, kept

    return fn


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, S]
    positions: jax.Array,     # [B, S]
    kv_cache,                 # init_kv_cache's pair
    block_tables: jax.Array,  # [B, W]
    slot_mapping: jax.Array,  # [B, S]; −1: no token here
    context_lens: jax.Array,  # [B]
    mesh=None,
    return_hidden: bool = False,
    state_slots=None,         # [B] each prefill row's slot; decode: row i
):
    del mesh    # one device: tp, pp and sp are refused for the family
    b, s = tokens.shape
    if state_slots is None:
        state_slots = jnp.arange(b, dtype=jnp.int32)
    with jax.named_scope("embed"):
        hidden = scaled(params["embed"][tokens], cfg.scale_emb)
    # a decode step's rows that hold a token: one list for both kinds
    # of layer, made outside their scans
    live_rows = decode_live_rows(slot_mapping)
    lightning_fn = make_lightning_fn(cfg, b, s, positions, slot_mapping,
                                     state_slots, live_rows)
    sparse_fn = make_sparse_fn(cfg, b, s, positions, slot_mapping,
                               block_tables, context_lens, live_rows)
    res = residual_scale(cfg)
    k_side, v_side = kv_cache

    def feed_forward(hidden, lp):
        with jax.named_scope("mlp"):
            n2 = rms_norm(hidden, lp["ln2"], cfg.rms_norm_eps)
            return hidden + scaled(swiglu_mlp(n2, lp), res)

    def lightning_layer(carry, lp):
        hidden, state, li = carry
        n1 = rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps)
        with jax.named_scope("lightning"):
            delta, state = lightning_fn(n1, lp, state, li)
        hidden = feed_forward(hidden + scaled(delta, res), lp)
        return (hidden, state, li + 1), None

    def sparse_layer(carry, lp):
        hidden, (k_pages, v_pages, means, _), li = carry
        n1 = rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            delta, k_pages, v_pages, means, kept = sparse_fn(
                n1, lp, k_pages, v_pages, means, li)
        hidden = feed_forward(hidden + scaled(delta, res), lp)
        return (hidden, (k_pages, v_pages, means, kept), li + 1), None

    # a sparse layer hands on, behind its pages and means, the keys its
    # rows kept: the last one's are the step's count
    hidden, cache, _ = walk_runs(
        layer_runs(cfg.mixer_types), params["runs"], lambda kind, run: (
            run, lightning_layer if kind == LIGHTNING else sparse_layer),
        hidden,
        {LIGHTNING: k_side.state,
         SPARSE: (k_side.kv, v_side.kv, v_side.state,
                  context_lens.astype(jnp.int32))})
    state, (k_pages, v_pages, means, kept) = cache[LIGHTNING], cache[SPARSE]

    live = slot_mapping >= 0
    if s == 1:
        live, n = live[:, 0], context_lens.astype(jnp.int32)
        step = [jnp.sum(jnp.where(live, kept, 0)), jnp.sum(jnp.where(live, n, 0)),
                jnp.sum(live & (n > cfg.sparse_dense_len)), 1, 0, 0]
    else:
        step = [0, 0, 0, 0, jnp.sum(live), 1]
    counts = v_side.counts + jnp.stack(
        [jnp.asarray(c, jnp.int32) for c in step])
    cache = (SalaCache(k_pages, state, k_side.counts),
             SalaCache(v_pages, means, counts))
    if return_hidden:
        return hidden, cache
    with jax.named_scope("lm_head"):
        return logits_from_hidden(hidden, params, cfg), cache


def logits_from_hidden(hidden: jax.Array, params: Params,
                       cfg: ModelConfig) -> jax.Array:
    width = cfg.hidden_size / (cfg.dim_model_base or cfg.hidden_size)
    # the head is linear: dividing its output is dividing its input
    return scaled(lm_logits(hidden, params, cfg), 1.0 / width)
