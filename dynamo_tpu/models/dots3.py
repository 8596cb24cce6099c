"""dots3-note (``model_type: dots3_note``): a trunk whose layers all keep
a latent cache, of two kinds by ``layer_types``, over a dense SwiGLU
behind the first ``first_k_dense_replace`` layers and routed experts with
a shared expert behind the others. Pre-norm residual everywhere, final
RMSNorm, untied head.

With ``n = RMSNorm(h)``, ``s_q = (hidden / q_lora_rank)^½`` and ``s_kv =
(hidden / kv_lora_rank)^½`` (``apply_mla_qkv_lora_rescale``: constants
after the two latent norms, kept as scalars and not folded into the
norms' weights, so a checkpoint's norm weights load as published; 1.0
where the key is false):

- ``full_attention`` (``num_attention_heads`` heads, ``rope_theta``):
  ``c_q = s_q · RMSNorm(n W_dq)``; ``[q_nope | q_r]_h = c_q W_uq,h``;
  ``c = s_kv · RMSNorm(n W_dkv)``, ``k_r = n W_kr``; ``q_r`` and ``k_r``
  rotated; ``k_nope,h = c W_uk,h``, ``v_h = c W_uv,h``. The indexer:
  ``q^I_j = c_q W^I_q,j`` (``index_n_heads`` heads of ``index_head_dim``),
  ``k^I = LayerNorm(n W^I_k)`` (one a token, cached), the first
  ``qk_rope_head_dim`` of each rotated as ``q_r`` / ``k_r`` are, ``w = n
  W^I_w · index_n_heads^-½ · index_head_dim^-½``; ``I(t, s) = Σ_j w_t,j ·
  ReLU(q^I_t,j · k^I_s)`` in float32, and ``S_t`` the ``index_topk`` keys
  ``s ≤ t`` of largest ``I(t, ·)`` (all of them while there are no more).
  ``o_h = softmax_{s ∈ S_t}((q_nope,h · k_nope,h,s + q_r,h · k_r,s) ·
  (nope + rope)^-½) v_h,s``.
- ``sliding_attention``: the same latent attention with the ``swa_*``
  head count, ranks, head sizes and rope base, no indexer, and a key
  ``s`` seen by ``t`` iff ``0 ≤ t − s < sliding_window``.
- both: ``o_h ← o_h · σ((n W_g)_h)`` (``attention_gate_type:
  headwise``), then ``W_o``.
- experts: ``mixtral.make_moe_mlp_fn`` as Moonlight runs it (sigmoid
  scores in float32, the ``num_experts_per_tok`` largest of score +
  correction bias, gates the unbiased scores renormalised and times
  ``routed_scaling_factor``), plus the shared expert; one
  expert-parallel rank's share as models/kimi_linear.py states it
  (``expert_share``: ``cfg.num_experts`` held of ``cfg.experts_of``).

The served program absorbs ``W_uk`` into the query and applies ``W_uv``
after attention (models/deepseek.py), so a token's cache line is the
latent and the rope key (and the indexer's key in a full layer).

**Two kinds of page, a page shape a kind, and the indexer's keys by
slot.** A side of the cache is a ``LatentKinds`` (``trunk.KindCache``
with the step's counters and the records by slot): the k side ``(full
[Lf, N, 1, page, r' + rd'], window [Lw, Nw, 1, page, r_w'])``, the v
side ``(full nothing, window rope keys [Lw, Nw, 1, page, rd_w'], index
[Lf, slots, T, di'])``, a primed width its ``lane_pad``. **A full
layer's page holds a token's row whole**: the latent in lanes ``[:r]``,
the rotated key in ``[r':r' + rd]``, zeros between and behind (640 lanes
at rank 512 and a rope key of 64: 1280 B a token in bfloat16).
Nothing walks a full layer's pages: decode looks the picked tokens' rows
up one by one and a gather costs the chip by the index far more than by
the byte (scripts/gather_sweep.py), so a row is one lookup, not a latent
and a rope key apart. The window kind keeps the two stacks
``mla_paged_decode_attention`` walks (deepseek.init_kv_cache's layout).
The window kind's pages come from the allocator's second pool behind a
table of their own and go back as they fall behind the window
(models/afmoe.py says how the engine serves that; this family inherits
its ``SEQUENCE_STATE``).

**The indexer's keys are records by slot, in sequence order**
(``ops/latent_select.py`` says why): ``index[li, slot, p]`` is the key
of the token at position ``p`` of the sequence in ``slot``, ``T``
positions a slot (``record_len`` of the longest sequence the engine
admits). The family's pages are private to a sequence already, so the
keys need no page: a decode step's row *i* is slot *i* and reads ``[li,
:b]`` as the product's operand; a prefill row names its slot
(``state_slots``, as a family with recurrent state is told;
``SEQUENCE_STATE.slots``). A step writes a token's key at its position;
a slot is never cleared, because a key at or past ``context_len`` is
never read. The records do not grow with the context: they are counted
with the window kind's pages (``LatentKinds.rest``), 256 B a position a
full layer in bfloat16, for every slot at its longest whatever the pool
holds.

**Routes.** Decode, full layer: the indexer's scores of the row's
record under the table's width, the pick, one gather of the picked tokens' rows and one dense
absorbed product over them (``ops/latent_select.picked_decode_attention``;
XLA on every backend, a program of the width ladder). Decode, window layer: the
latent decode kernel from the window's first page
(``deepseek.mla_attention(sliding_window=)``; the dense gather off the
TPU). Prefill, both kinds: ``ops/latent_select.blocked_latent_attention``,
blocks of queries against blocks of keys, the pick a mask a query.

**One body a kind**, as models/kimi_linear.py: the weights are stacked
by kind (``params["full_attention"]``, ``["sliding_attention"]``,
``["dense"]``, ``["moe"]``), the dense prefix is a body a layer and the
rest one scan over periods (``trunk.walk_periods``: a run of full
layers, then the run of window layers behind it).

Scopes: ``attn`` with ``attn_full`` or ``attn_window`` inside;
``dsa_index``, ``dsa_select`` and ``dsa_attend`` inside ``mla_cache`` of
a full layer, ``swa_latent`` around a window layer's kernel; ``mlp`` with
mixtral's ``moe_*``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import lane_pad, pad_minor
from ..ops.latent_select import (Indexer, blocked_latent_attention,
                                 picked_decode_attention, record_len)
from ..ops.live_rows import decode_live_rows
from . import afmoe
from .deepseek import (mla_attention, mla_project, mla_softmax_scale,
                       scatter_rows_stacked)
from .llama import apply_rope, lm_logits, rms_norm
from .mixtral import (expert_share_fields, make_moe_mlp_fn,
                      random_expert_stacks, split_expert_stacks)
from .quant import dense
from .trunk import KindCache, forward_over, walk_periods, window_slots

Params = Dict[str, Any]

FULL, WINDOW = afmoe.GLOBAL, afmoe.LOCAL

# afmoe's: the window kind's pages in a pool and behind a table of their
# own, and every path refused for that (the paths a family with records
# by slot refuses: falcon_h1's); records by slot beside them, so the
# trunk is told each row's slot; the share is stated, so the mesh's ep
# axis stays refused
SEQUENCE_STATE = dataclasses.replace(
    afmoe.SEQUENCE_STATE, slots=True,
    keeps="its window layers' latent pages in a pool and a table of their "
          "own, and an indexer's key a token by slot beside its full "
          "layers' pages",
    refused={
        **afmoe.SEQUENCE_STATE.refused,
        "ep_size": "the expert stacks are kept whole and not sharded; one "
                   "rank's share is stated in the config (expert_share) "
                   "and served on a device of its own",
        "prefix_pull": "a pulled prefix brings the full kind's latent and "
                       "rope-key pages only, not the indexer's keys nor "
                       "the window kind's pages",
    })

# published keys only this family computes (models.published);
# expert_share is Granite's and Kimi's claim too: under this model_type
# it is this family's
CLAIMED_KEYS = ("attention_gate_type", "apply_mla_qkv_lora_rescale",
                "sliding_window_size", "expert_share")
CLAIMED_PREFIXES = ("swa_", "index_")
CLAIM = ("{keys} and no family here implements them under that model_type "
         "(dots3 is the family with a learned indexer over latent pages "
         "(index_*), latent window layers of their own shape (swa_*, "
         "sliding_window_size), a gate a head and the latent norms' "
         "rescale: models/dots3.py, model_type dots3_note)")

LOGIT_STD = 2.0        # models/granite_hybrid.py says why the logits too
ATTN_SCORE_STD = 3.0   # models/falcon_h1.py says why 3.0
# root mean square of a sigmoid of a standard normal: what the gate
# leaves of a head (models/kimi_linear.py)
GATE_RMS = 0.54
LN_EPS = 1e-5          # the indexer's LayerNorm (the published default)


def claimed_keys(config: dict) -> List[str]:
    keys = sorted(k for k in config
                  if k in CLAIMED_KEYS or k.startswith(CLAIMED_PREFIXES))
    # a mixed layer_types is afmoe's claim (and Granite's): this
    # family's under its own model_type, theirs to refuse under a third
    if (config.get("model_type") == "dots3_note"
            and "layer_types" in afmoe.claimed_keys(config)):
        keys.insert(0, "layer_types")
    return keys


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    dots3_note``; what this module does not compute is refused here,
    before any weight is made. ``expert_share`` (``{"of_experts",
    "rank"}``) is the one key the published config lacks (as
    models/kimi_linear.py)."""
    only = {
        "attention_gate_type": "headwise", "swa_attention_gate_type":
        "headwise", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "hidden_act": "silu", "rope_scaling": None, "attention_bias": False,
        "tie_word_embeddings": False, "moe_layer_freq": 1, "n_group": 1,
        "topk_group": 1, "num_nextn_predict_layers": 0,
    }
    for key, value in only.items():
        if (config.get(key, value) or None) != (value or None):
            raise NotImplementedError(
                f"dots3_note with {key}={config.get(key)!r} "
                f"(models/dots3.py computes {key}={value!r} only)")
    kinds = tuple(config.get("layer_types") or ())
    layers = int(config["num_hidden_layers"])
    unknown = sorted(set(kinds) - {FULL, WINDOW})
    if len(kinds) != layers or unknown:
        raise ValueError(
            f"dots3_note: layer_types has {len(kinds)} entries for {layers} "
            f"layers, unknown kinds {unknown} ({WINDOW} | {FULL})")
    window = int(config.get("sliding_window_size") or 0)
    if set(kinds) != {FULL, WINDOW} or window <= 0:
        raise NotImplementedError(
            "dots3_note without layers of both kinds or without "
            "sliding_window_size (models/dots3.py keeps a stack of pages a "
            "kind)")
    if not config.get("q_lora_rank") or not config.get("n_shared_experts"):
        raise NotImplementedError(
            "dots3_note without q_lora_rank (the indexer's queries are made "
            "from the query's latent) or without a shared expert "
            "(models/dots3.py computes both)")
    held = int(config.get("n_routed_experts", 0) or 0)
    swa = {name: int(config[f"swa_{key}"]) for name, key in (
        ("swa_num_heads", "num_attention_heads"),
        ("swa_q_lora_rank", "q_lora_rank"),
        ("swa_kv_lora_rank", "kv_lora_rank"),
        ("swa_qk_nope_head_dim", "qk_nope_head_dim"),
        ("swa_qk_rope_head_dim", "qk_rope_head_dim"),
        ("swa_v_head_dim", "v_head_dim"))}
    return dict(
        layer_types=kinds, sliding_window=window, **swa,
        swa_rope_theta=float(config["swa_rope_theta"]),
        index_topk=int(config["index_topk"]),
        index_n_heads=int(config["index_n_heads"]),
        index_head_dim=int(config["index_head_dim"]),
        mla_lora_rescale=bool(config.get("apply_mla_qkv_lora_rescale")),
        attention_gate="headwise",
        n_group=1, topk_group=1,
        **expert_share_fields(config, held),
    )


def kind_cfg(cfg: ModelConfig, kind: str) -> ModelConfig:
    """``cfg`` as the latent layers of ``kind`` read it: a window layer's
    head count, ranks, head sizes and rope base in the fields a full
    layer's are in (what models/deepseek.py reads)."""
    if kind == FULL:
        return cfg
    return dataclasses.replace(
        cfg, num_heads=cfg.swa_num_heads, q_lora_rank=cfg.swa_q_lora_rank,
        kv_lora_rank=cfg.swa_kv_lora_rank,
        qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
        qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
        v_head_dim=cfg.swa_v_head_dim, rope_theta=cfg.swa_rope_theta)


def lora_rescale(cfg: ModelConfig):
    """(s_q, s_kv) of a kind's config."""
    if not cfg.mla_lora_rescale:
        return 1.0, 1.0
    return ((cfg.hidden_size / cfg.q_lora_rank) ** 0.5,
            (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5)


def _mixer_params(cfg: ModelConfig, n: int, w, dtype, indexer: bool) -> Params:
    """``n`` stacked latent mixers of one kind (``cfg``: ``kind_cfg``)."""
    d, h = cfg.hidden_size, cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    sq, skv = lora_rescale(cfg)
    # scores of deviation ATTN_SCORE_STD under the published scale: the
    # query's latent has entries of size s_q, the key's of s_kv, the rope
    # key of 1
    unit = (nope * (sq * skv) ** 2 + rope * sq ** 2) ** 0.5
    out = {
        "ln1": jnp.ones((n, d), dtype),
        "w_dq": w((n, d, qr), d),
        "ln_q": jnp.ones((n, qr), dtype),
        "w_uq": w((n, qr, h * (nope + rope)), qr,
                  ATTN_SCORE_STD / (unit * mla_softmax_scale(cfg))),
        "w_dkv": w((n, d, r), d),
        "ln_kv": jnp.ones((n, r), dtype),
        "w_kr": w((n, d, rope), d),
        # a head's own [nope, r] and [r, v] matrices where they lie
        # (deepseek.py keeps [r, H, nope] and [r, H, v]; at 64 heads of a
        # rank of 1024 the decode step copied the six window layers'
        # 101 MB of w_uv to this order every step, and a full layer's
        # 17 MB of w_uk)
        "w_uk": w((n, h, nope, r), r),
        "w_uv": w((n, h, r, vd), r),
        "w_g": w((n, d, h), d),
        # a head's output is an average of values of size s_kv, under a
        # gate of root mean square GATE_RMS
        "wo": w((n, h * vd, d), h * vd, 1.0 / (skv * GATE_RMS)),
    }
    if indexer:
        j, di = cfg.index_n_heads, cfg.index_head_dim
        out.update({
            "wi_q": w((n, qr, j * di), qr),
            "wi_k": w((n, d, di), d),
            "ln_ik": jnp.ones((n, di), dtype),
            "ln_ik_b": jnp.zeros((n, di), dtype),
            "wi_w": w((n, d, j), d),
        })
    return out


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families, each sublayer adding a vector of about unit size
    (``_mixer_params``; the routed sum and the shared expert add about
    one between them, as models/kimi_linear.py), attention scores of
    deviation ``ATTN_SCORE_STD`` and logits of ``LOGIT_STD``.

    The indexer is drawn on its own (plain fan-in draws of ``W^I_q``,
    ``W^I_k`` and ``W^I_w``, the LayerNorm at weight 1 and bias 0), tied
    to nothing of the attention it picks for: a key's score for a query
    is then a sum of ``index_n_heads`` terms ``w_j ReLU(x_j)`` with
    ``x_j`` of deviation ``s_q · index_head_dim^½`` and ``w_j`` of
    ``(index_n_heads · index_head_dim)^-½``, so the scores of a row's
    keys are spread with a deviation near 1.3 (s_q = 5^½): far over what
    bfloat16 operands round (a few thousandths), so that a pick is a
    pick and not a tie broken by rounding. A layer's experts are one
    prototype plus a spread (``mixtral.random_expert_stacks``), the
    experts held drawn as the stacks they are; the router's correction
    bias small and not zero, as models/deepseek.py."""
    d = cfg.hidden_size
    inter, moe_inter = cfg.intermediate_size, cfg.moe_intermediate_size
    held, of = cfg.num_experts, cfg.experts_of or cfg.num_experts
    n_full = cfg.layer_types.count(FULL)
    n_dense = min(cfg.first_k_dense_replace, cfg.num_layers)
    n_moe = cfg.num_layers - n_dense
    keys = iter(jax.random.split(key, 64))

    def w(shape, fan_in, gain=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    params: Params = {
        "embed": w((cfg.vocab_size, d), 1),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w((d, cfg.vocab_size), d, LOGIT_STD),
    }
    params[FULL] = _mixer_params(cfg, n_full, w, dtype, indexer=True)
    params[WINDOW] = _mixer_params(kind_cfg(cfg, WINDOW), cfg.num_layers - n_full,
                                   w, dtype, indexer=False)
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


# the step's counters, in the order of the v side's ``counts`` (the
# engine renders them on /metrics: ModelRunner._init_family_counters);
# MiniCPM-SALA's names: the same quantities of another sparse layer
STEP_COUNTERS = (
    ("dynamo_sparse_attention_kept_tokens_total",
     "Keys a full layer attended to (the indexer's pick), summed over the "
     "live rows of every decode step (one layer's: the layers keep alike)"),
    ("dynamo_sparse_attention_context_tokens_total",
     "Keys live, summed over the live rows of every decode step: what the "
     "indexer scored and a dense layer would have attended to"),
    ("dynamo_sparse_attention_rows_total",
     "Live rows past index_topk keys (rows that picked), summed over "
     "decode steps"),
    ("dynamo_sparse_attention_decode_steps_total", "Decode steps counted"),
)


def step_counts(kv_cache):
    return kv_cache[1].counts


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentKinds(KindCache):
    """A side of the cache: the full layers' pages and the window
    layers', each kind with a page shape of its own, the step's
    counters (int32, wrapping: a reader takes differences) and the full
    layers' indexer keys by slot (``index``). The v side has the
    counters that count and the records, the k side neither's use (the
    two sides have one structure as the engine shards and donates them
    alike)."""
    counts: Any = None
    index: Any = ()

    @property
    def rest(self):
        """What does not grow with the context: the window kind's
        pages and the records by slot."""
        return self.window, self.index


CACHE_SPEC = LatentKinds(full=P(), window=P(), counts=P(), index=P())


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(LatentKinds(rows of the full kind (latent ‖ rope key), latents
    of the window kind, -), LatentKinds(nothing of the full kind, rope
    keys of the window kind, counters, the indexer's keys by slot))``:
    ``num_blocks`` pages a full layer, ``window_blocks`` a window layer
    (page 0 of those is the one no sequence holds), the one "head" in
    front of the page (deepseek.init_kv_cache); a page's lanes are its
    parts' widths, each in whole lanes. The records: ``num_slots`` slots
    of ``record_len`` positions for sequences of up to ``max_len``
    tokens (the engine's ``max_model_len``; left out, the model's
    own)."""
    n_full = cfg.layer_types.count(FULL)
    wcfg = kind_cfg(cfg, WINDOW)

    def pages(layers, blocks, *widths):
        return jnp.zeros((layers, blocks, 1, block_size,
                          sum(map(lane_pad, widths))), dtype)

    full = (n_full, num_blocks)
    window = (cfg.num_layers - n_full, window_blocks)
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    return (
        LatentKinds(pages(*full, cfg.kv_lora_rank, cfg.qk_rope_head_dim),
                    pages(*window, wcfg.kv_lora_rank), counts),
        LatentKinds((), pages(*window, wcfg.qk_rope_head_dim), counts,
                    jnp.zeros((n_full, num_slots, record_len(
                        max_len or cfg.max_position_embeddings, block_size),
                        lane_pad(cfg.index_head_dim)), dtype)))


def _layer_norm(x, weight, bias, eps=LN_EPS):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def _rope_front(x, positions, cfg: ModelConfig):
    """The first ``qk_rope_head_dim`` of the last axis rotated ([B, S, H,
    d]), the rest as projected."""
    return apply_rope(x, positions, cfg.rope_theta,
                      rotary_dim=cfg.qk_rope_head_dim)


def index_projections(cfg: ModelConfig, lp, x, cq, positions):
    """The indexer's side of a full layer's new tokens, from the layer's
    normed input ``x`` [B, S, D] and the query's latent ``cq``: (queries
    [B, S, J, di], the token's key [B, S, di], head weights [B, S, J]
    float32)."""
    b, s = x.shape[:2]
    j, di = cfg.index_n_heads, cfg.index_head_dim
    # (held before the head axis, as deepseek.mla_project holds w_uq's)
    qi = jax.lax.optimization_barrier(dense(cq, lp["wi_q"]))
    qi = _rope_front(qi.reshape(b, s, j, di), positions, cfg)
    ki = _layer_norm(dense(x, lp["wi_k"]), lp["ln_ik"], lp["ln_ik_b"])
    ki = _rope_front(ki[:, :, None], positions, cfg)[:, :, 0]
    wi = dense(x, lp["wi_w"]).astype(jnp.float32) * (j * di) ** -0.5
    return qi, ki, wi


def write_index_keys(records, new, li, state_slots, positions, valid):
    """The new tokens' indexer keys ``new`` [B, S, di] into layer ``li``
    of ``records`` [L, slots, T, di'], in place: row ``r``'s at ``[li,
    state_slots[r], positions[r]]``, by the scatter that writes the
    pages (``scatter_rows_stacked``: a slot's record is one page of
    ``T`` positions to it). A position that is no token (``valid`` [B,
    S] false: a chunk's pad repeats its last position) or lies past
    ``T`` (there is none: the engine admits no such token) writes
    nothing."""
    t = records.shape[2]
    at = jnp.where(valid & (positions < t),
                   state_slots[:, None] * t + positions, -1)
    return scatter_rows_stacked(
        (records[:, :, None],), (new,), at, li)[0][:, :, 0]


def make_mixer_fn(cfg: ModelConfig, kind: str, b: int, s: int, positions,
                  slots, table, valid, context_lens, live_rows,
                  state_slots):
    """The latent mixer of one layer of ``kind``: ``fn(n1, layer_params,
    caches, li) -> (delta, caches)`` over what that kind keeps (a full
    layer's rows in pages and its indexer's keys by slot; a window
    layer's latents and rope keys), ``slots`` and ``table`` that kind's,
    ``state_slots`` [B] each row's slot."""
    kcfg = kind_cfg(cfg, kind)
    sq, skv = lora_rescale(kcfg)
    scale = mla_softmax_scale(kcfg)
    decode = s == 1
    r = kcfg.kv_lora_rank
    lat = lane_pad(r)       # the latent's lanes of a page, either kind

    def fn(x, lp, caches, li):
        cq, q_nope, q_rope, c_kv, kr = mla_project(
            kcfg, x, lp, b, s, positions, q_scale=sq, kv_scale=skv,
            hold_heads=True)
        # what a key is read from: the kind's page stacks, and a full
        # layer's indexer keys by slot behind them
        new, stacks, records, index = (c_kv, kr), caches, (), None
        if kind == FULL:
            with jax.named_scope("dsa_index"):
                qi, ki, wi = index_projections(cfg, lp, x, cq, positions)
            # a token's row: the latent in whole lanes, then the rotated key
            new = (jnp.concatenate([pad_minor(c_kv, lat), kr], -1),)
            stacks, records = caches[:1], (write_index_keys(
                caches[1], ki, li, state_slots, positions, valid),)
            # (zero lanes of a padded query score 0 against the pad)
            index = Indexer(pad_minor(qi, records[0].shape[-1]), wi,
                            records[0], state_slots, cfg.index_topk)
        stacks = scatter_rows_stacked(stacks, new, slots, li)
        caches = (*stacks, *records)

        # absorb W_uk into the query, attend over the latent cache
        q_lat = pad_minor(jnp.einsum("bshn,hnr->bshr", q_nope, lp["w_uk"]),
                           lat)
        q_rope = pad_minor(q_rope,
                            sum(st.shape[-1] for st in stacks) - lat)
        if kind == FULL and decode:
            with jax.named_scope("mla_cache"):
                o_lat = picked_decode_attention(
                    q_lat, q_rope, *stacks, li, table, context_lens, scale,
                    index)
        elif decode:
            with jax.named_scope("swa_latent"):
                o_lat = mla_attention(
                    q_lat, q_rope, *stacks, li, table, positions,
                    context_lens, scale, impl=cfg.attention_impl,
                    live_rows=live_rows, sliding_window=cfg.sliding_window)
        else:
            with jax.named_scope("mla_cache" if kind == FULL
                                 else "swa_latent"):
                o_lat = blocked_latent_attention(
                    q_lat, q_rope, stacks, li, table, positions, valid,
                    context_lens, scale,
                    sliding_window=(cfg.sliding_window if kind == WINDOW
                                    else None),
                    index=index)
        o = jnp.einsum("bshr,hrv->bshv", o_lat[..., :r], lp["w_uv"])
        gate = jax.nn.sigmoid(dense(x, lp["w_g"]).astype(jnp.float32))
        o = o * gate[..., None].astype(o.dtype)
        return dense(o.reshape(b, s, -1), lp["wo"]), caches

    return fn


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None, state_slots=None):
    """(hidden [B, S, D], cache, int32 [3]: ``mixtral.routing_stats``
    summed over the expert layers, the experts counted those held).
    ``block_tables`` is ``[B, 2 W]``: the full kind's table, then the
    window kind's (models/afmoe.py)."""
    # one device: tp, ep, pp and sp are refused for the family
    del mesh
    b, s = tokens.shape
    if state_slots is None or s == 1:   # a decode step's row i is slot i
        state_slots = jnp.arange(b, dtype=jnp.int32)
    w = block_tables.shape[1] // 2
    tables = {FULL: block_tables[:, :w], WINDOW: block_tables[:, w:]}
    k_side, v_side = kv_cache
    page = k_side.full.shape[3]
    slots = {FULL: slot_mapping,
             WINDOW: window_slots(tables[WINDOW], positions, slot_mapping,
                                  page)}
    valid = slot_mapping >= 0
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
    # the rows of a decode step that hold a token: one list for every
    # layer's kernel
    live_rows = decode_live_rows(slot_mapping)
    mixers = {kind: make_mixer_fn(cfg, kind, b, s, positions, slots[kind],
                                  tables[kind], valid, context_lens,
                                  live_rows, state_slots)
              for kind in (FULL, WINDOW)}

    def mixer(kind, lp, hidden, pages, i):
        scope = "attn_full" if kind == FULL else "attn_window"
        with jax.named_scope("attn"), jax.named_scope(scope):
            delta, own = mixers[kind](
                rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps), lp,
                pages[kind], i)
        return hidden + delta, {**pages, kind: own}

    def experts():
        moe, stacks = split_expert_stacks(params["moe"])
        return moe, make_moe_mlp_fn(cfg, b, s, slot_mapping, stacks=stacks)

    hidden, pages, stats = walk_periods(
        params, cfg, (FULL, WINDOW), mixer, experts, hidden,
        {FULL: (k_side.full, v_side.index),
         WINDOW: (k_side.window, v_side.window)})

    counts = v_side.counts
    if s == 1:
        # a live row's full layers attend to min(keys, index_topk)
        live = valid[:, 0]
        keys = jnp.where(live, context_lens, 0).astype(jnp.int32)
        counts = counts + jnp.stack([
            jnp.minimum(keys, cfg.index_topk).sum(), keys.sum(),
            (keys > cfg.index_topk).sum(), jnp.int32(1)]).astype(jnp.int32)
    rows_f, ki_f = pages[FULL]
    c_w, kr_w = pages[WINDOW]
    cache = (LatentKinds(rows_f, c_w, k_side.counts),
             LatentKinds((), kr_w, counts, ki_f))
    return hidden, cache, stats


forward = forward_over(forward_counted)
logits_from_hidden = lm_logits
