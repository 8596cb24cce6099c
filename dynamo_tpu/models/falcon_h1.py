"""Falcon-H1 (``model_type: falcon_h1``): every layer runs a Mamba-2
state-space mixer and grouped-query attention in parallel on the same
normed input and adds both to the residual; then a SwiGLU feed-forward.

With ``n1 = RMSNorm(x)``:

- mixer: ``u = in_proj(n1 · ssm_in_multiplier)`` times the µP vector over
  its parts ``[z | x | B | C | dt]``; ``x‖B‖C`` through a causal depthwise
  conv (width ``mamba_d_conv``, bias) and SiLU; ``Δ = softplus(dt +
  dt_bias)``, ``A = −exp(A_log)``; per head ``h_t = exp(Δ_t A) h_{t−1} +
  Δ_t x_t ⊗ B_t``, ``y_t = h_t C_t + D x_t``; ``y ← GroupedRMSNorm(y ·
  SiLU(z))`` (gate before the norm); ``m = out_proj(y) · ssm_out_multiplier``;
- attention: llama's prologue and kernels (``qkv_prologue`` scales the key
  by ``key_multiplier`` before the rotary embedding), in and out
  multipliers around it;
- ``x ← x + m + a``; ``x ← x + down(up(n2) · SiLU(gate(n2) · m0)) · m1``.

**State beside the pages.** The attention branch's keys and values are
paged as in every GQA family. The mixer's state is a second kind of
per-sequence device state that is not paged: one fixed-size record a
layer a *slot* (the engine's decode row), the heads' ``[P, N]`` in
float32 (the recurrence feeds its own rounding back every token; not an
option) laid as the decode kernel walks them, ``[H / k, N, k P]``
(``ops/ssm.state_to_record``: ``P`` on the lanes), plus
the conv's last ``d_conv − 1`` inputs. Both ride in the cache pytree the
programs already carry and donate: each side is a ``trunk.SlotCache(kv=pages,
state=records)`` (the k side holds the SSM state, the v side the conv
window).

Which row is which slot, and what is valid, is read from what the step
already gets: a token whose cache slot is −1 (a pad position, a pad row,
an idle decode row) is no token, so its Δ is 0 and the state passes it
unchanged. A decode step's row *i* is slot *i*, and the rows that hold a
token have their SSM records advanced where they lie, by one kernel a
layer (``ops/ssm.ssm_decode_step``: one read and one write of a live
row's state, nothing for an idle one). A prefill row names its
slot (``state_slots``, the row's sampling slot), starts from zeros when
its first position is 0 and from its slot's state otherwise (the next
chunk of one prompt), and writes the state back as of its last valid
token. So a preempted sequence resumes by re-prefilling from position 0
(engine/scheduler.py), and nothing else may move a sequence's pages
without its state: ``SEQUENCE_STATE`` names what the engine refuses
for this family.

Scopes: ``attn`` (attention branch), ``ssm`` (whole mixer) with
``ssm_conv`` and ``ssm_state`` (decode's one-token update) or
``ssm_scan`` (a prefill chunk's chunked scan) inside it, ``mlp``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.live_rows import decode_live_rows
from ..ops.ssm import record_shape, ssd_chunked_scan, ssm_decode_step
from . import SequenceState, llama
from .llama import (ATTN_LAYER_SPECS, base_specs, lm_logits,
                    make_gqa_attn_fn, rms_norm)
from .quant import dense
from .trunk import SlotCache, scaled

Params = Dict[str, Any]

# what a sequence keeps besides its pages: records the engine sizes by
# slot (init_kv_cache's num_slots; forward's state_slots names each
# row's). Refused at start-up, by name: paths that move, share or roll
# back a sequence's pages without its state, path -> reason
_REFUSED = {
    "spec_ngram_tokens": "a rejected proposal rolls back pages; the "
                         "recurrent state has already absorbed it",
    "spec_draft_model": "a rejected draft token rolls back pages; the "
                        "recurrent state has already absorbed it",
    "sp_size": "sequence-parallel prefill shards one prompt's tokens; the "
               "recurrence is sequential over them",
    "pp_size": "the pipeline stages the paged cache only; no stage would "
               "hold the recurrent state",
    "tp_size": "the mixer's heads and state are not sharded",
    "host_kv_blocks": "an offloaded block restores pages without the state "
                      "that followed them",
    "prefix_pull": "a pulled prefix brings pages without the state at its "
                   "end",
    "multi_step_decode": "the fused burst has no test with the recurrent "
                         "state in its carry",
    "decode_pipeline_depth": "the chained burst freezes finished rows by "
                             "their page slot only; untested with the "
                             "recurrent state",
    "remote_prefill": "a prefill worker ships pages without the state",
    "migration": "a migrated sequence brings pages without the state",
}
SEQUENCE_STATE = SequenceState(
    slots=True, keeps="recurrent state by slot beside the paged cache",
    refused=_REFUSED)

# published keys only this family computes (models.published):
# its own, and those of other published trunks with recurrent layers
# (hybrid_override_pattern, conv_kernel and the two prefixes are
# nemotron_h's under its own model_type, as the mixer's keys are
# Granite's under granitemoehybrid; under a third they are refused here)
CLAIMED_KEYS = (
    "layers_block_type", "hybrid_override_pattern", "linear_num_value_heads",
    "linear_conv_kernel_dim", "state_size", "time_step_rank", "rwkv_version",
    "conv_kernel", "d_state",
)
CLAIMED_PREFIXES = ("mamba_", "ssm_")
# (a trunk with recurrent layers this program has no family for would
# fall through to llama and serve nonsense)
CLAIM = ("recurrent-layer keys ({keys}, ...) and no family here implements "
         "it (falcon_h1 is the state-space family with attention beside the "
         "mixer in every layer, models/falcon_h1.py; granite_hybrid the one "
         "whose layers are a mixer or attention by layer_types, "
         "models/granite_hybrid.py; nemotron_h the one whose layers are a "
         "mixer, attention or experts alone by hybrid_override_pattern, "
         "models/nemotron_h.py; minicpm_sala the linear-attention one, "
         "models/minicpm_sala.py)")


def claimed_keys(config: dict) -> List[str]:
    return sorted(k for k in config
                  if k in CLAIMED_KEYS or k.startswith(CLAIMED_PREFIXES))


def config_fields(config: dict) -> dict:
    """ModelConfig's Falcon-H1 fields from the published keys; what the
    family module does not compute is refused here, before any weight
    is made."""
    only = {
        "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "mamba_proj_bias": False, "mamba_conv_bias": True,
        "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
        "rope_scaling": None, "attn_layer_indices": None,
    }
    for key, value in only.items():
        if config.get(key, value) != value:
            raise NotImplementedError(
                f"falcon_h1 with {key}={config[key]!r} (models/falcon_h1.py "
                f"computes {key}={value!r} only)")
    d_ssm = int(config["mamba_d_ssm"])
    heads, d_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    groups = int(config.get("mamba_n_groups", 1))
    if heads * d_head != d_ssm or heads % groups:
        raise ValueError(
            f"falcon_h1: mamba_d_ssm {d_ssm} != mamba_n_heads {heads} x "
            f"mamba_d_head {d_head}, or mamba_n_groups {groups} does not "
            "divide the heads")
    ssm_m = tuple(float(m) for m in config.get("ssm_multipliers", (1.0,) * 5))
    mlp_m = tuple(float(m) for m in config.get("mlp_multipliers", (1.0, 1.0)))
    if len(ssm_m) != 5 or len(mlp_m) != 2:
        raise ValueError("falcon_h1: ssm_multipliers has 5 entries (z, x, B, "
                         "C, dt) and mlp_multipliers 2 (gate, down)")
    scalars = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")
    return dict(
        mamba_d_ssm=d_ssm, mamba_n_heads=heads, mamba_d_head=d_head,
        mamba_d_state=int(config["mamba_d_state"]), mamba_n_groups=groups,
        mamba_d_conv=int(config.get("mamba_d_conv", 4)),
        mamba_chunk_size=int(config.get("mamba_chunk_size", 128)),
        ssm_multipliers=ssm_m, mlp_multipliers=mlp_m,
        **{k: float(config.get(k, 1.0)) for k in scalars},
    )


# standard deviation of the served logits under random weights
LOGIT_STD = 2.0
# standard deviation of q·k / sqrt(head_dim) under random weights. At 1.0
# (plain fan-in weights) attention is spread thinly over every key, as a
# trained model's is not, and what the keys and values are rounded to
# averages out of the result: an fp8 page cache then reads as the
# bfloat16 one does (mean |Δ log p| 0.022 against 0.019 on the chip,
# PERF.md §6, PR 31). At 3.0 a few keys carry a row's attention and the
# cache's precision shows.
ATTN_SCORE_STD = 3.0


CACHE_SPEC = SlotCache(kv=P(None, None, None, "tp", None), state=P())


def conv_dim(cfg: ModelConfig) -> int:
    return cfg.mamba_d_ssm + 2 * cfg.mamba_n_groups * cfg.mamba_d_state


def in_proj_parts(cfg: ModelConfig) -> Tuple[int, ...]:
    """Widths of in_proj's five parts ``[z | x | B | C | dt]``."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    return (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)


def mup_vector(cfg: ModelConfig) -> jax.Array:
    """``ssm_multipliers`` spread over in_proj's five parts."""
    return jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip(in_proj_parts(cfg), cfg.ssm_multipliers)])


def init_mixer(cfg: ModelConfig, keys, l: int, dtype, in_gain=1.0,
               out_gain: float = 1.0) -> Params:
    """``l`` layers of the mixer's parameters from six keys (in_proj,
    conv weight, conv bias, Δ, A, out_proj); ``in_gain`` (a scalar or a
    vector over in_proj's columns) and ``out_gain`` times the fan-in
    scale of the two projections. The small ones as ``init_params``
    says (shared with models/granite_hybrid.py)."""
    d, d_ssm, nh = cfg.hidden_size, cfg.mamba_d_ssm, cfg.mamba_n_heads
    kc, cd = cfg.mamba_d_conv, conv_dim(cfg)
    k_in, k_cw, k_cb, k_dt, k_a, k_out = keys

    def uniform(key, shape, lo, hi):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)

    dt = jnp.exp(uniform(k_dt, (l, nh), jnp.log(1e-3), jnp.log(1e-1)))
    bound = kc ** -0.5
    return {
        "ssm_in": (jax.random.normal(k_in, (l, d, sum(in_proj_parts(cfg))),
                                     jnp.float32)
                   * (d ** -0.5) * in_gain).astype(dtype),
        "conv_w": uniform(k_cw, (l, kc, cd), -bound, bound).astype(dtype),
        "conv_b": uniform(k_cb, (l, cd), -bound, bound).astype(dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(uniform(k_a, (l, nh), 1.0, 16.0)),
        "D": jnp.ones((l, nh), jnp.float32),
        "ssm_norm": jnp.ones((l, d_ssm), dtype),
        "ssm_out": (jax.random.normal(k_out, (l, d_ssm, d), jnp.float32)
                    * (out_gain * d_ssm ** -0.5)).astype(dtype),
    }


def remember_long(cfg: ModelConfig, run: Params, k_horizon, k_bias,
                  horizon: Tuple[float, float],
                  bc_bias: Tuple[float, float]) -> Params:
    """``init_mixer``'s layers redrawn so that the state counts in a
    comparison (models/granite_hybrid.py ``STATE_HORIZON`` and
    ``BC_CONV_BIAS`` say why): a head forgets after ``1 / (Δ · A)``
    tokens, drawn log-uniform in ``horizon``, ``A = 1 / (Δ · horizon)``
    with ``Δ = softplus(dt_bias)``; the conv's bias under the B and C
    channels (those past x) uniform in ``bc_bias``."""
    lo, hi = horizon
    tokens = jnp.exp(jax.random.uniform(
        k_horizon, run["A_log"].shape, jnp.float32, jnp.log(lo), jnp.log(hi)))
    run["A_log"] = -jnp.log(tokens * jax.nn.softplus(run["dt_bias"]))
    bc = run["conv_b"][:, cfg.mamba_d_ssm:]
    run["conv_b"] = run["conv_b"].at[:, cfg.mamba_d_ssm:].set(
        jax.random.uniform(k_bias, bc.shape, jnp.float32,
                           *bc_bias).astype(run["conv_b"].dtype))
    return run


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed. Every matrix is fan-in-scaled normal
    as in the other families, **divided by the fixed µP multipliers that
    sit beside it**, so that each branch's activations have the size they
    have in a family without multipliers (the multipliers shrink them by
    up to 128 x; fan-in weights alone would serve log-probabilities of
    −ln V everywhere and a comparison would see nothing). The query
    projection is drawn for attention scores of standard deviation
    ``ATTN_SCORE_STD`` and the head for logits of standard deviation
    ``LOGIT_STD``. ``A_log``, ``dt_bias``, ``D`` and the conv as the
    Mamba-2 reference initialises them: A uniform in [1, 16], Δ log-uniform in [1e-3, 1e-1] (``dt_bias``
    its inverse softplus), D = 1, conv weight and bias uniform in
    ±d_conv^-½."""
    l, d = cfg.num_layers, cfg.hidden_size
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    inter = cfg.intermediate_size
    keys = jax.random.split(key, 16)

    def w(key, shape, fan_in, gain=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    a_in, a_out = cfg.attention_in_multiplier, cfg.attention_out_multiplier
    in_gain = 1.0 / (cfg.ssm_in_multiplier * mup_vector(cfg))       # [9248]
    layers = {
        "ln1": jnp.ones((l, d), dtype),
        "wq": w(keys[1], (l, d, h * hd), d, ATTN_SCORE_STD / a_in),
        "wk": w(keys[2], (l, d, kvh * hd), d, 1.0 / (a_in * cfg.key_multiplier)),
        "wv": w(keys[3], (l, d, kvh * hd), d, 1.0 / a_in),
        "wo": w(keys[4], (l, h * hd, d), h * hd, 1.0 / a_out),
        **init_mixer(cfg, keys[9:15], l, dtype, in_gain,
                     1.0 / cfg.ssm_out_multiplier),
        "ln2": jnp.ones((l, d), dtype),
        "w_gate": w(keys[5], (l, d, inter), d, 1.0 / cfg.mlp_multipliers[0]),
        "w_up": w(keys[6], (l, d, inter), d),
        "w_down": w(keys[7], (l, inter, d), inter, 1.0 / cfg.mlp_multipliers[1]),
    }
    params: Params = {
        # hidden states of unit size after the embedding multiplier
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, d), jnp.float32)
                  / cfg.embedding_multiplier).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(keys[8], (d, cfg.vocab_size), d,
                              LOGIT_STD / cfg.lm_head_multiplier)
    return params


SSM_LAYER_SPECS = {k: P() for k in (
    "ssm_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm",
    "ssm_out")}


def param_specs(params: Params) -> Dict:
    """The attention and feed-forward matrices carry llama's tp layout;
    the mixer is replicated (tp > 1 is refused for this family)."""
    layer_specs = {
        **ATTN_LAYER_SPECS, **SSM_LAYER_SPECS,
        "w_gate": P(None, None, "tp"),
        "w_up": P(None, None, "tp"),
        "w_down": P(None, "tp", None),
    }
    specs = base_specs(params)
    specs["layers"] = {k: layer_specs[k] for k in params["layers"]}
    return specs


def ssm_record_shape(cfg: ModelConfig):
    """A slot's record of one mixer layer, as the decode kernel walks it."""
    return record_shape(cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                        cfg.mamba_n_heads // cfg.mamba_n_groups)


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(SlotCache(k pages, SSM state [L, slots, H / k, N, k P] float32:
    ``ops/ssm.record_shape``), SlotCache(v pages, conv window [L, slots,
    d_conv − 1, conv_dim]))``. The conv window keeps the trunk's dtype
    whatever the pages' (an fp8 page cache does not round the window)."""
    k, v = llama.init_kv_cache(cfg, num_blocks, block_size, dtype)
    act = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
    ssm = jnp.zeros((cfg.num_layers, num_slots) + ssm_record_shape(cfg),
                    jnp.float32)
    conv = jnp.zeros((cfg.num_layers, num_slots, cfg.mamba_d_conv - 1,
                      conv_dim(cfg)), act)
    return SlotCache(k, ssm), SlotCache(v, conv)


def _grouped_rms_norm(y, weight, groups: int, eps: float):
    """RMS norm over each of ``groups`` equal parts of the last axis."""
    shape = y.shape
    return rms_norm(y.reshape(shape[:-1] + (groups, -1)),
                    weight.reshape(groups, -1), eps).reshape(shape)


def _gated_norm(y, z, weight, groups: int, eps: float):
    """``mamba_norm_before_gate: false``: the gate, then the norm."""
    return _grouped_rms_norm(y * jax.nn.silu(z), weight, groups, eps)


def _row_major(records: jax.Array) -> jax.Array:
    """The mixer's records held in their dimensions' own order through a
    prefill step. The chunked scan makes a row's new state by products
    whose results it transposes; left free, XLA's layout assignment
    makes that free at Granite's state (two heads side by side) by
    keeping *all the records* the other way round through the layer
    loop, and copies them on the way in and out: 2.4 GB a step."""
    return with_layout_constraint(
        records, Layout(major_to_minor=tuple(range(records.ndim))))


def slot_records(b: int, decode: bool, live, state_slots, fresh):
    """``(read, write)`` over records stacked ``[L, slots, ...]``:
    ``read(records, li)`` is layer ``li``'s records of the step's rows
    (zeros for a prefill row that is ``fresh``: its first position is 0),
    ``write(records, li, rows)`` puts the rows' new records back. A
    decode step's row *i* is slot *i*; a prefill row names its slot
    (``state_slots``), and one that is no sequence (not ``live``) puts
    back what is there (its slot number may be a live row's)."""

    # A prefill step has few rows (the row ladder stops at 8), so each
    # row's record is sliced out and put back on its own: a gather or a
    # scatter over the records makes XLA copy a layer's worth, or all.
    def record(records, li, slot):
        start = (li, slot) + (0,) * (records.ndim - 2)
        return jax.lax.dynamic_slice(
            records, start, (1, 1) + records.shape[2:])

    def read(records, li):
        if decode:      # row i is slot i
            return jax.lax.dynamic_index_in_dim(
                records, li, 0, keepdims=False)[:b]
        rows = jnp.concatenate(
            [record(records, li, state_slots[i])[0] for i in range(b)])
        zero = fresh.reshape((b,) + (1,) * (rows.ndim - 1))
        return jnp.where(zero, jnp.zeros_like(rows), rows)

    def write(records, li, rows):
        rows = rows.astype(records.dtype)
        if decode:
            return records.at[li, :b].set(rows)
        for i in range(b):
            new = jnp.where(live[i], rows[i][None, None],
                            record(records, li, state_slots[i]))
            records = jax.lax.dynamic_update_slice(
                records, new, (li, state_slots[i]) + (0,) * (rows.ndim - 1))
        return records

    return read, write


def make_ssm_fn(cfg: ModelConfig, b: int, s: int, positions, slot_mapping,
                state_slots, live_rows):
    """The mixer of one layer: ``ssm_fn(n1, layer_params, ssm_all,
    conv_all, li) -> (m, ssm_all, conv_all)`` over the stacked state
    records, updated where they lie. ``live_rows``: the step's
    ``decode_live_rows(slot_mapping)``, the rows the decode kernel
    walks."""
    nh, hp, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    g, kc, d_ssm = cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_d_ssm
    parts = in_proj_parts(cfg)
    splits = [sum(parts[:i + 1]) for i in range(4)]
    mup = mup_vector(cfg)
    valid = slot_mapping >= 0                       # [B, S] real tokens
    n_valid = valid.sum(axis=1).astype(jnp.int32)   # [B]
    decode = s == 1
    live = valid[:, 0]
    read, write = slot_records(b, decode, live, state_slots,
                               None if decode else positions[:, 0] == 0)

    def ssm_fn(x, lp, ssm_all, conv_all, li):
        u = scaled(dense(scaled(x, cfg.ssm_in_multiplier), lp["ssm_in"]), mup)
        z, xbc, dt_raw = (u[..., :splits[0]], u[..., splits[0]:splits[3]],
                          u[..., splits[3]:])
        with jax.named_scope("ssm_conv"):
            # the window: the slot's last d_conv − 1 inputs, then the chunk
            xp = jnp.concatenate([read(conv_all, li).astype(xbc.dtype), xbc],
                                 axis=1)                      # [B, S + K−1, C]
            conv = sum(xp[:, k:k + s] * lp["conv_w"][k] for k in range(kc))
            xbc = jax.nn.silu(conv + lp["conv_b"])
            # the inputs that end at the row's last valid token (the old
            # window itself where the row has none)
            keep = n_valid[:, None] + jnp.arange(kc - 1)[None, :]
            conv_all = write(conv_all, li, jnp.take_along_axis(
                xp, keep[:, :, None], axis=1))
        xs = xbc[..., :d_ssm].reshape(b, s, nh, hp)
        bm = xbc[..., d_ssm:d_ssm + g * n].reshape(b, s, g, n)
        cm = xbc[..., d_ssm + g * n:].reshape(b, s, g, n)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0)   # no token: state passes
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
        if decode:
            # the kernel updates the live rows' records where they lie
            with jax.named_scope("ssm_state"):
                y, ssm_all = ssm_decode_step(
                    xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], lp["D"],
                    ssm_all, li, live_rows)
                y = y[:, None]
        else:
            with jax.named_scope("ssm_scan"):
                # the scan carries the state in the records' order
                y, h1 = ssd_chunked_scan(
                    xs, dt, a, bm, cm, lp["D"],
                    read(_row_major(ssm_all), li), cfg.mamba_chunk_size)
                ssm_all = _row_major(write(ssm_all, li, h1))
        y = y.reshape(b, s, d_ssm).astype(x.dtype)
        y = _gated_norm(y, z, lp["ssm_norm"], g, cfg.rms_norm_eps)
        return (scaled(dense(y, lp["ssm_out"]), cfg.ssm_out_multiplier),
                ssm_all, conv_all)

    return ssm_fn


def _mlp(cfg: ModelConfig, x, lp):
    gate = jax.nn.silu(scaled(dense(x, lp["w_gate"]), cfg.mlp_multipliers[0]))
    return scaled(dense(gate * dense(x, lp["w_up"]), lp["w_down"]),
                   cfg.mlp_multipliers[1])


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
    b, s = tokens.shape
    if state_slots is None:
        state_slots = jnp.arange(b, dtype=jnp.int32)
    with jax.named_scope("embed"):
        hidden = scaled(params["embed"][tokens], cfg.embedding_multiplier)
    # a decode step's rows that hold a token: one list for the mixer's
    # and the attention's kernels and every layer, made outside the scan
    live_rows = decode_live_rows(slot_mapping)
    attn_fn = make_gqa_attn_fn(
        cfg, b, s, positions, slot_mapping, block_tables, context_lens, mesh,
        live_rows=live_rows)
    ssm_fn = make_ssm_fn(cfg, b, s, positions, slot_mapping, state_slots,
                         live_rows)

    def layer_step(carry, lp):
        hidden, k_all, v_all, li = carry
        n1 = rms_norm(hidden, lp["ln1"], cfg.rms_norm_eps)
        with jax.named_scope("ssm"):
            m, ssm, conv = ssm_fn(n1, lp, k_all.state, v_all.state, li)
        with jax.named_scope("attn"):
            a, k, v = attn_fn(scaled(n1, cfg.attention_in_multiplier), lp,
                              k_all.kv, v_all.kv, li)
            a = scaled(a, cfg.attention_out_multiplier)
        hidden = hidden + m + a
        with jax.named_scope("mlp"):
            n2 = rms_norm(hidden, lp["ln2"], cfg.rms_norm_eps)
            hidden = hidden + _mlp(cfg, n2, lp)
        return (hidden, SlotCache(k, ssm), SlotCache(v, conv), li + 1), None

    (hidden, k_all, v_all, _), _ = jax.lax.scan(
        layer_step, (hidden, kv_cache[0], kv_cache[1], jnp.int32(0)),
        params["layers"])
    if return_hidden:
        return hidden, (k_all, v_all)
    with jax.named_scope("lm_head"):
        return logits_from_hidden(hidden, params, cfg), (k_all, v_all)


def logits_from_hidden(hidden: jax.Array, params: Params,
                       cfg: ModelConfig) -> jax.Array:
    return scaled(lm_logits(hidden, params, cfg), cfg.lm_head_multiplier)
