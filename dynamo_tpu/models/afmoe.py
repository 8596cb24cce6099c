"""AFMoE (``model_type: afmoe``; Arcee's Trinity family): grouped-query
attention whose layers differ in how much of the context they keep,
over dense-then-expert feed-forwards. ``layer_types`` names every
layer's attention, ``sliding_attention`` (the last ``sliding_window``
keys, rotary embedding) or ``full_attention`` (every key, no positional
term at all).

With ``h`` the residual stream, ``N_*`` RMS norms with a learned weight,
layer ``l``, ``local = layer_types[l] == "sliding_attention"``:

    h0    = embed[tokens] · √hidden_size                 (mup_enabled)
    a     = N_in(h)
    q,k,v = a Wq, a Wk, a Wv;   g = a Wg
    q,k   = N_q(q), N_k(k)      per head, one weight [head_dim] each
    q,k   = rope(q, k)          if local
    s_ij  = q_i·k_j / √head_dim,  j ≤ i,  and i − j < sliding_window if local
    o     = softmax(s) v ⊙ sigmoid(g)                    before Wo
    h     = h + N_post_attn(o Wo)
    m     = N_pre_mlp(h)
    y     = SwiGLU(m)                                    l <  num_dense_layers
    y     = Σ_{e∈S} w_e FFN_e(m) + FFN_shared(m)         otherwise
    h     = h + N_post_mlp(y)
    logits = N_final(h) W_head

The router is models/mixtral.py's (``route_top_k``: float32 sigmoid
scores, ``expert_bias`` steers the choice only, the chosen scores
renormalised and times ``route_scale``), the experts its sorted grouped
products with the shared expert beside them (``make_moe_mlp_fn``).

**Two kinds of page.** A full layer keeps every page of the context; a
window layer needs only the pages that reach back ``sliding_window``
tokens from the newest query. So a side of the cache is a
``trunk.KindCache``: one stack of pages over the full layers and one over the
window layers, each with its own pool in the allocator
(engine/block_allocator.py: ``num_kv_blocks`` is the full kind's pool,
the window kind's is derived, ``EngineConfig.window_pool_pages``) and
its own block table a row. The step's table is ``[B, 2 W]``: the full
kind's ``W`` entries, then the window kind's, both indexed by the
context's page (position // block size). A window layer's page behind
the window goes back to its pool while the sequence runs
(engine/scheduler.py ``_release_window``) and its table entry then
names page 0 of the window stack, which is never handed out and never
written: the kernels may walk to it (the decode kernel starts at a
whole chunk of pages), and read zeros under their masks. Where a window
layer writes is read from its own table (``position // block``), not
from the step's slot mapping, which is the full kind's.

Nothing else knows the kind, so every path that moves or shares a
sequence's pages by one block id is refused for this family, by name
(``SEQUENCE_STATE``); prefix hits are blanked, no block is registered,
and a preempted sequence resumes by prefill from position 0.

The trunk scans each run of layers of one kind (attention and
feed-forward alike) over that run's stacked weights (``params["runs"]``;
``kind_runs``; ``trunk.walk_runs``).

Scopes: ``attn`` with ``attn_window`` or ``attn_full`` inside (norm,
projections, rope, scatter, kernel, gate, output), and ``kv_window`` or
``kv_full`` around the kernel alone; ``mlp`` with mixtral's ``moe_*``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from ..ops.attention import attention, lane_pad, scatter_kv_stacked
from ..ops.live_rows import decode_live_rows
from . import SequenceState
from .llama import (layer_runs, lm_logits, qkv_prologue, rms_norm,
                    run_specs, swiglu_mlp)
from .mixtral import (make_moe_mlp_fn, random_expert_stacks,
                      split_expert_stacks)
from .quant import dense
from .trunk import KindCache, forward_over, walk_runs, window_slots

Params = Dict[str, Any]

LOCAL, GLOBAL = "sliding_attention", "full_attention"
# the window layers give pages back while a sequence runs: the engine
# keeps a second pool (init_kv_cache's window_blocks) and a second table
# a row for them. Refused at start-up, by name: paths that move, share
# or roll back a sequence's pages by one block id, path -> reason
_REFUSED = {
    "spec_ngram_tokens": "a rejected proposal rolls back pages by one "
                         "block id; the window kind's are not rolled back",
    "spec_draft_model": "the draft's mirror cache shares the full kind's "
                        "block ids and has no window pool",
    "sp_size": "sequence-parallel prefill writes one prompt's pages from "
               "every chip by the full kind's table alone",
    "pp_size": "the pipeline stages one stack of pages; the two kinds' "
               "stacks differ in depth",
    "tp_size": "the two page stacks and the window table are not sharded",
    "ep_size": "the expert stacks are kept a run of layers and not sharded",
    "host_kv_blocks": "an offloaded block restores the full kind's page "
                      "only",
    "prefix_pull": "a pulled prefix brings the full kind's pages only",
    "multi_step_decode": "the fused burst grows its tables on the device "
                         "by one block id; no window page is released or "
                         "taken inside it",
    "decode_pipeline_depth": "the chained burst runs ahead of the host, "
                             "which releases and takes the window pages",
    "remote_prefill": "a prefill worker ships the full kind's pages only",
    "migration": "a migrated sequence brings the full kind's pages only",
}
SEQUENCE_STATE = SequenceState(
    window_pool=True, refused=_REFUSED,
    keeps="its window layers' pages in a pool and a table of their own")

# published keys only this family computes (models.published): a
# trunk whose layers differ in kind, with dense layers before its
# experts, a shared expert or a scaled embedding, would be served by
# mixtral.py or llama.py without them
CLAIMED_KEYS = ("num_dense_layers", "num_shared_experts", "mup_enabled")
CLAIM = ("{keys} and no family here implements them under that model_type "
         "(afmoe is the family with window and full layers by layer_types, "
         "num_dense_layers, num_shared_experts and mup_enabled: "
         "models/afmoe.py; granite_hybrid the one whose layer_types name a "
         "state-space mixer or attention: models/granite_hybrid.py)")


def claimed_keys(config: dict) -> List[str]:
    """``CLAIMED_KEYS`` where set, and ``layer_types`` where it mixes
    kinds (Gemma-2 and GPT-OSS publish the alternation their modules
    compute from the layer's index)."""
    keys = [k for k in CLAIMED_KEYS if config.get(k)]
    arch = str(config.get("architectures", "")).lower()
    mixed = len(set(config.get("layer_types") or ())) > 1
    if mixed and not ("gptoss" in arch or "gemma" in arch):
        keys.insert(0, "layer_types")
    return keys


def config_fields(config: dict) -> dict:
    """ModelConfig's fields from the published keys of ``model_type:
    afmoe``; what models/afmoe.py does not compute is refused here,
    before any weight is made."""
    only = {"score_func": "sigmoid", "route_norm": True, "n_group": 1,
            "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
            "rope_scaling": None, "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False}
    for key, value in only.items():
        if (config.get(key, value) or value) != value:
            raise NotImplementedError(
                f"afmoe with {key}={config[key]!r} "
                f"(models/afmoe.py computes {key}={value!r} only)")
    kinds = tuple(config.get("layer_types") or ())
    layers = int(config["num_hidden_layers"])
    unknown = sorted(set(kinds) - {"sliding_attention", "full_attention"})
    if len(kinds) != layers or unknown:
        raise ValueError(
            f"afmoe: layer_types has {len(kinds)} entries for {layers} "
            f"layers, unknown kinds {unknown} (sliding_attention | "
            "full_attention)")
    window = int(config.get("sliding_window") or 0)
    if "sliding_attention" in kinds and window <= 0:
        raise ValueError("afmoe: sliding_attention layers need sliding_window")
    return dict(
        layer_types=kinds,
        sliding_window=window,
        first_k_dense_replace=int(config.get("num_dense_layers", 0) or 0),
        n_shared_experts=int(config.get("num_shared_experts", 0) or 0),
        moe_scoring_func="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=float(config.get("route_scale", 1.0) or 1.0),
        embedding_multiplier=(math.sqrt(int(config["hidden_size"]))
                              if config.get("mup_enabled") else 1.0),
    )


# standard deviation of the served logits under random weights, and of
# q·k / sqrt(head_dim) (the query norm's weight: the per-head norms make
# the scores' size a matter of that weight alone; at 1.0 attention is
# spread thinly over every key and the pages' precision does not show:
# models/falcon_h1.py ATTN_SCORE_STD)
LOGIT_STD = 2.0
ATTN_SCORE_STD = 3.0
# expert_bias of random weights: small and non-zero, so that "the bias
# steers the choice only" is exercised (as deepseek.init_params)
EXPERT_BIAS_STD = 0.05


CACHE_SPEC = KindCache(full=P(), window=P())


def kind_runs(cfg: ModelConfig) -> List[Tuple[Tuple[bool, bool], int, int]]:
    """The layers as runs of one kind (``llama.layer_runs``): ((window
    layer?, dense feed-forward?), the run's first index among the layers
    of its attention kind, its length)."""
    dense_layers = min(cfg.first_k_dense_replace, cfg.num_layers)
    kinds = [(kind == LOCAL, i < dense_layers)
             for i, kind in enumerate(cfg.layer_types)]
    return layer_runs(kinds, index_key=lambda kind: kind[0])


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights from the seed, fan-in-scaled normal as in the other
    families. The embedding is divided by its multiplier (hidden states
    of unit size); every norm weighs 1.0 but the query's, which weighs
    ``ATTN_SCORE_STD``; the four norms a layer make every sublayer add a
    vector of unit size whatever the gate's mean and ``route_scale`` do
    to its inside; the head is drawn for logits of standard deviation
    ``LOGIT_STD``. A layer's experts are one prototype plus a spread
    (``mixtral.random_expert_stacks``), ``expert_bias`` small normal, float32."""
    d, inter = cfg.hidden_size, cfg.intermediate_size
    moe_inter = cfg.moe_intermediate_size or inter
    h, kvh, hd, e = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts

    def w(key, shape, fan_in, gain=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * (gain * fan_in ** -0.5)).astype(dtype)

    def experts(key, shape, fan_in):
        return random_expert_stacks(key, shape, fan_in, dtype)

    runs = []
    for r, ((_, is_dense), _, n) in enumerate(kind_runs(cfg)):
        keys = jax.random.split(jax.random.fold_in(key, r + 1), 13)
        run = {
            "ln1": jnp.ones((n, d), dtype),
            "wq": w(keys[0], (n, d, h * hd), d),
            "wk": w(keys[1], (n, d, kvh * hd), d),
            "wv": w(keys[2], (n, d, kvh * hd), d),
            "wg": w(keys[3], (n, d, h * hd), d),
            "wo": w(keys[4], (n, h * hd, d), h * hd),
            "q_norm": jnp.full((n, hd), ATTN_SCORE_STD, dtype),
            "k_norm": jnp.ones((n, hd), dtype),
            "ln1_post": jnp.ones((n, d), dtype),
            "ln2": jnp.ones((n, d), dtype),
            "ln2_post": jnp.ones((n, d), dtype),
        }
        if is_dense:
            run["w_gate"] = w(keys[5], (n, d, inter), d)
            run["w_up"] = w(keys[6], (n, d, inter), d)
            run["w_down"] = w(keys[7], (n, inter, d), inter)
        else:
            run["router"] = w(keys[5], (n, d, e), d)
            run["router_bias"] = EXPERT_BIAS_STD * jax.random.normal(
                keys[6], (n, e), jnp.float32)
            run["w_gate"] = experts(keys[7], (n, e, d, moe_inter), d)
            run["w_up"] = experts(keys[8], (n, e, d, moe_inter), d)
            run["w_down"] = experts(keys[9], (n, e, moe_inter, d), moe_inter)
            if cfg.n_shared_experts > 0:
                sh = cfg.n_shared_experts * moe_inter
                run["w_sh_gate"] = w(keys[10], (n, d, sh), d)
                run["w_sh_up"] = w(keys[11], (n, d, sh), d)
                run["w_sh_down"] = w(keys[12], (n, sh, d), sh)
        runs.append(run)
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    params: Params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32)
                  / cfg.embedding_multiplier).astype(dtype),
        "runs": runs,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(k_head, (d, cfg.vocab_size), d, LOGIT_STD)
    return params


param_specs = run_specs    # tp > 1 and ep > 1 are refused for the family


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 1,
                  window_blocks: int = 1, max_len: int = 0):
    """``(KindCache(k full, k window), KindCache(v full, v window))``:
    ``num_blocks`` pages a full layer, ``window_blocks`` a window layer
    (page 0 of those is the one no sequence holds)."""
    n_local = sum(kind == LOCAL for kind in cfg.layer_types)
    page = (block_size, cfg.num_kv_heads, lane_pad(cfg.head_dim))
    full = (cfg.num_layers - n_local, num_blocks) + page
    window = (n_local, window_blocks) + page
    return tuple(KindCache(jnp.zeros(full, dtype), jnp.zeros(window, dtype))
                 for _ in range(2))


def _gated(o, x, lp):
    """``o ⊙ sigmoid(a Wg)``: elementwise, before the output projection."""
    gate = jax.nn.sigmoid(dense(x, lp["wg"]).astype(jnp.float32))
    return o * gate.astype(o.dtype)


def make_attn_fn(cfg: ModelConfig, b: int, s: int, positions, slots, table,
                 context_lens, local: bool, live_rows):
    """``fn(a, layer_params, k_all, v_all, li) -> (o Wo, k_all, v_all)``
    over the page stack of the layer's kind, ``slots`` and ``table``
    that kind's; ``live_rows``: ``decode_live_rows`` of the step."""
    h, hd = cfg.num_heads, cfg.head_dim
    kernel = "kv_window" if local else "kv_full"

    def fn(x, lp, k_all, v_all, li):
        q, k, v = qkv_prologue(cfg, x, lp, b, s, positions, context_lens,
                               rope=local)
        k_all, v_all = scatter_kv_stacked(k_all, v_all, k, v, slots, li)
        with jax.named_scope(kernel):
            o = attention(
                q, k_all, v_all, table, positions, context_lens,
                impl=cfg.attention_impl, layer_idx=li,
                sliding_window=cfg.sliding_window if local else None,
                live_rows=live_rows)
        o = _gated(o.reshape(b, s, h * hd), x, lp)
        return dense(o, lp["wo"]), k_all, v_all

    return fn


def forward_counted(params, cfg, tokens, positions, kv_cache, block_tables,
                    slot_mapping, context_lens, mesh=None, state_slots=None):
    """(hidden [B, S, D], cache, int32 [3]: mixtral.routing_stats summed
    over the expert layers), as mixtral.forward_counted.
    ``block_tables`` is ``[B, 2 W]``: the full kind's table, then the
    window kind's."""
    # one device: tp, ep, pp and sp are refused for the family; no
    # records by slot
    del mesh, state_slots
    b, s = tokens.shape
    w = block_tables.shape[1] // 2
    tables = {False: block_tables[:, :w], True: block_tables[:, w:]}
    block_size = kv_cache[0].full.shape[2]
    slots = {False: slot_mapping,
             True: window_slots(tables[True], positions, slot_mapping,
                                block_size)}
    with jax.named_scope("embed"):
        hidden = params["embed"][tokens]
        hidden = (hidden.astype(jnp.float32)
                  * cfg.embedding_multiplier).astype(hidden.dtype)
    k_side, v_side = kv_cache
    stats = jnp.zeros((3,), jnp.int32)
    eps = cfg.rms_norm_eps
    # the rows of a decode step that hold a token: one list for every
    # run of layers and both kinds of page
    live_rows = decode_live_rows(slot_mapping)

    def layer_of(local, run):
        attn_fn = make_attn_fn(cfg, b, s, positions, slots[local],
                               tables[local], context_lens, local, live_rows)
        if "router" in run:
            scanned, stacks = split_expert_stacks(run)
            mlp_fn = make_moe_mlp_fn(cfg, b, s, slot_mapping, stacks=stacks)
        else:       # a run of the dense prefix
            scanned, mlp_fn = run, swiglu_mlp
        scope = "attn_window" if local else "attn_full"

        def layer(carry, lp):
            hidden, (k_all, v_all), li = carry
            with jax.named_scope("attn"), jax.named_scope(scope):
                delta, k_all, v_all = attn_fn(
                    rms_norm(hidden, lp["ln1"], eps), lp, k_all, v_all, li)
                hidden = hidden + rms_norm(delta, lp["ln1_post"], eps)
            with jax.named_scope("mlp"):
                out = mlp_fn(rms_norm(hidden, lp["ln2"], eps), lp)
                y, aux = out if isinstance(out, tuple) else (out, None)
                hidden = hidden + rms_norm(y, lp["ln2_post"], eps)
            return (hidden, (k_all, v_all), li + 1), aux

        return scanned, layer

    # (a run is of one attention kind and one kind of feed-forward; the
    # pages are the attention kind's)
    runs = [(local, start, n) for (local, _), start, n in kind_runs(cfg)]
    hidden, pages, stats = walk_runs(
        runs, params["runs"], layer_of, hidden,
        {False: (k_side.full, v_side.full),
         True: (k_side.window, v_side.window)}, stats)
    cache = (KindCache(pages[False][0], pages[True][0]),
             KindCache(pages[False][1], pages[True][1]))
    return hidden, cache, stats


forward = forward_over(forward_counted)
logits_from_hidden = lm_logits
