"""The plain reference of MiMo-V2 (``model_type: mimo_v2``; MiMo-V2.5 and
MiMo-V2-Flash): a float32 forward of the layer equations ISSUE 59 wrote
down from the catalog's ``config`` and ``described_as``. What the config
is silent on is marked ``[assumed]`` here and listed under ``assumed`` in
the configuration's file.

With ``h`` the residual stream, ``N_*`` RMS norms with a learned weight
and ``layernorm_epsilon``, layer ``l``, ``window =
hybrid_layer_pattern[l] == 1``, ``KVH = swa_num_key_value_heads if window
else num_key_value_heads``, ``r = int(head_dim x partial_rotary_factor)``:

    a      = N_in(h)
    q,k,v  = a Wq [H x head_dim], a Wk [KVH x head_dim], a Wv [KVH x v_head_dim]
    q,k    = rope on lanes [0, r) of every head (half rotation, pairs (i, i + r/2)),
             theta = swa_rope_theta if window else rope_theta; lanes [r, head_dim) as projected
    v      = attention_value_scale x v       [assumed: on the values, before the product]
    s_ij   = q_i . k_j / sqrt(head_dim),  j <= i,  and i - j < sliding_window if window
                                             [assumed: the window counts the query's own position]
    window:  p_ij = exp(s_ij - m_i) / (sum_j exp(s_ij - m_i) + exp(b_head - m_i)),
             m_i = max(max_j s_ij, b_head); b a learned float32 logit a query head
             (add_swa_attention_sink_bias): a key with no value
    full:    p = softmax_j(s)                (add_full_attention_sink_bias false)
    h      = h + (p v) Wo
    m      = N_post(h)
    moe_layer_freq[l] == 0:  y = (silu(m W1) * (m W3)) W2
    moe_layer_freq[l] == 1:  r = sigmoid(m Wr)               float32
             S = the num_experts_per_tok largest of r + e_score_correction_bias
             w = r[S] / sum r[S]             norm_topk_prob; x 1 (routed_scaling_factor null)
             y = sum_{e in S, e held} w_e (silu(m W1_e) * (m W3_e)) W2_e     no shared expert
    h      = h + y
    logits = N_final(h) W_head

``attention_chunk_size`` says nothing beyond ``sliding_window`` [assumed].

Each line **by its definition**: attention is a full masked product over
every key of the sequence (a window layer's too: the mask and the sink's
column are all that tell the kinds apart), a block of ``QUERY_BLOCK``
queries at a time so that a long probe fits beside the served model; the
experts are every held expert on every token, one at a time, weighted by
the gate, zero where the expert was not chosen. ``expert_share``: the
weights hold ``n_routed_experts`` of the ``expert_share.of_experts`` the
router scores, those of rank ``expert_share.rank``; the reference routes
over every published expert and adds the terms of the experts held and
no others (without the key every expert is held and the sum is whole).
The vocabulary is the slice the configuration holds. Plain ``jax.numpy``
in float32 under ``default_matmul_precision("highest")``: no cache, no
page of either kind, no kernel, no batching, nothing imported from
``dynamo_tpu.models`` or ``dynamo_tpu.ops`` (the field names of
``ModelConfig`` are read once, at import, to refuse a program without the
family). It reads the engine's parameter arrays, because the weights are
data (random, from the seed): ``{"embed", "full_attention",
"sliding_attention": ln1, wq, wk, wv, wo (and sinks [n, H] float32 where
the kind has one) stacked over the layers of the kind; "dense": ln2,
w_gate, w_up, w_down over the dense prefix; "moe": ln2, router [D, E],
router_bias [E], w_gate, w_up [E_held, D, I], w_down [E_held, I, D] over
the expert layers; "final_norm", "lm_head"}``, ``x @ w``.

Departures from the published code, and readings of it:

- the published checkpoint fuses q, k and v into one tensor
  (``attention_projection_layout: fused_qkv``): a layout of the file, not
  of the computation; three matrices here;
- the rotary embedding is the engine's half rotation over the first ``r``
  lanes, the published ``rotate_half`` on ``[..., :rotary_dim]``;
- ``e_score_correction_bias`` steers the choice only (``noaux_tc``);
  ``n_group`` = ``topk_group`` = 1 is a plain top-k, another value is
  refused, not approximated;
- the three multi-token-prediction layers, the vision tower and the audio
  encoder have no key in the catalog's row: not run, nothing stands in.

``build(lower=...)``: the reference computed wrongly in one named way
(``CONTROLS``), everything else as it is, for what a serving's probes
read against a program that differs so (``scripts/long_probes.py
--controls``).

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced: 64 tokens a run (the harness's four probes of 16
greedy tokens). The served path computes in bfloat16 (weights,
activations, pages of both kinds) with float32 scores, softmaxes, sink
logits and router; the reference takes the same bfloat16 weights to
float32. Seven layers without post-norms: the exact embedding damps the
rounding as in the families without them. Readings on the v5e (my chip
run, PR 59; PERF.md section 6; the 7-layer configuration through the
server):

- the sound program, the cell's own probes, 29 servings on 27 seeds
  (the last seven, 2259000601-606 and 611, on the tree as handed in):
  mean of a run 0.034-0.060, largest single difference 0.11-0.51
  (three of the 29 over 0.23: 0.41, 0.43, 0.51);
  past them (``scripts/long_probes.py``, each probe's 16 tokens on their
  own; three seeds, the last two on the tree as handed in): at 9400
  tokens 0.045-0.065 / 0.11-0.23, at 16 000 tokens 0.040-0.054 /
  0.09-0.20;
- an fp8 page cache (``kv_cache_dtype: fp8``, the nearest precision
  below the configuration's), the same probes, three seeds: mean over
  the harness's four probes 0.165, 0.218 and 0.187 (a probe 0.120-0.292),
  largest 0.449, 0.689 and 0.492; at 9400 tokens 0.131-0.157 /
  0.37-0.45: **not correct by the mean**, correct by the largest;
- the six wrong programs made here on a serving's probes
  (``build(lower=...)``, 96 tokens: the harness's four, 9400 and 16 000):
  the sink dropped mean 1.29 / largest 3.72; rope over the whole head
  3.14 / 7.61; one theta for both kinds 1.71 / 4.71; the value scale
  dropped 1.21 / 4.86; the window one key short 0.10 / 0.83 and one key
  long 0.13 / 0.68 (a probe shorter than the window reads 0.000: it
  never meets the edge);

- ``LOGPROB_MEAN_ATOL`` 0.1: 1.66 x the largest sound mean of a run
  (0.060) and 1.65 x under the lowest of the fp8 cache's three means
  over a run (0.165); under every wrong program's, the window's
  off-by-one (0.105, 0.129: just) included. (It stood at 0.09, from the
  first five servings' 0.051, until the sixteenth read 0.059; seeds
  2259000501-506 and 511 ran under 0.09 and would have passed either,
  the 29th serving's 0.0604 is the first that 0.09 left under 1.5 x
  of room; 0.1 is the limit every serving of the tree as handed in
  ran under);
- ``LOGPROB_ATOL`` 1.0 on a single token: 2.0 x the largest sound
  difference of 29 runs (0.51; the first five read 0.21 at most: a
  fresh seed reads higher, and the limit PR 54 set at twice its first
  three runs was broken by the fifth) and under a third of what the
  four gross faults read (3.7-7.6); it is what catches a non-finite
  value, a page read after its release, a mask or a position. The fp8
  cache (0.45-0.69) and the window's off-by-one (0.68-0.83) pass it and
  fail the mean.

In float32 on the CPU the served path agrees with this file to 1e-5 in
log-probability at a tiny shape across several windows and releases
(``tests/test_mimo_v2_reference.py``, limit 1e-3; the wrong programs
there read over 3e-3), so what the chip shows is rounding.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens (PERF.md section 6, PR 59)
LOGPROB_ATOL = 1.0
LOGPROB_MEAN_ATOL = 0.1

MLP_SLICES = 4      # a dense feed-forward goes to float32 a quarter at a time
HEAD_SLICES = 16    # the head a sixteenth of the vocabulary at a time
QUERY_BLOCK = 128   # queries of an attention layer computed together

# what ``build(lower=...)`` can compute wrongly, everything else as it is
CONTROLS = ("no_sink", "rope_whole_head", "one_theta", "no_value_scale",
            "window_short", "window_long")


def _refuse_a_program_without_the_family() -> None:
    """A program whose ``ModelConfig`` has no ``swa_num_kv_heads`` takes
    the published keys for a Mixtral trunk's (``n_routed_experts`` > 0),
    builds gigabytes of weights and serves one kind of layer with whole
    rope and no sink: wrong tokens after minutes of set-up. This module
    is imported before anything is built (``run.py``), so such a program
    is refused here, in seconds (as ``references/afmoe.py``). The
    configuration's fields are all that is read of the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"swa_num_kv_heads", "partial_rotary_factor", "experts_of"} <= fields:
        raise ImportError(
            "this program has no trunk whose window and full layers differ "
            "in their kv heads, with rotary on part of a head and a sink "
            "(ModelConfig has no swa_num_kv_heads / partial_rotary_factor / "
            "experts_of): it cannot serve model_type mimo_v2, and "
            "references/mimo_v2.py has nothing to compare it with")


_refuse_a_program_without_the_family()

FULL, WINDOW = "full_attention", "sliding_attention"


def expert_layer(hf: dict):
    """``fn(m [T, D] float32, layer_params) -> y [T, D]``: the routed sum
    that the experts held give (all of it where the configuration holds
    every expert). The router scores every published expert; a pick of
    an expert that is not held adds nothing. Called under the caller's
    matmul precision (``build``'s is highest)."""
    import jax
    import jax.numpy as jnp

    top_k = int(hf["num_experts_per_tok"])
    held = int(hf["n_routed_experts"])
    share = hf.get("expert_share") or {}
    first = int(share.get("rank", 0)) * held     # the first expert held
    f32 = jnp.float32

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def expert_mlp(m, lp, layer=None):
        """``lp``: one layer's arrays, or with ``layer`` the stacks of
        every expert layer, of which that one is read."""
        if layer is None:
            lp, layer = {k: v[None] for k, v in lp.items()}, 0
        router, bias = lp["router"][layer], lp["router_bias"][layer]
        r = jax.nn.sigmoid(m @ router.astype(f32))               # [T, E published]
        select = r + bias.astype(f32)[None, :]                   # the choice only
        _, chosen = jax.lax.top_k(select, top_k)                 # [T, k]
        gate = jnp.zeros_like(r).at[
            jnp.arange(m.shape[0])[:, None], chosen].set(
                jnp.take_along_axis(r, chosen, axis=1))
        gate = gate / gate.sum(-1, keepdims=True)
        # the experts held, one at a time; a pick of an absent one adds nothing
        mine = jax.lax.dynamic_slice_in_dim(gate, first, held, axis=1)

        def one_expert(y, ew):   # one expert's weights to float32 at a time
            w_e, e = ew
            wg, wu, wd = (jax.lax.dynamic_slice(
                lp[k], (layer, e, 0, 0), (1, 1) + lp[k].shape[2:])[0, 0]
                .astype(f32) for k in ("w_gate", "w_up", "w_down"))
            return y + w_e[:, None] * swiglu(m, wg, wu, wd), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                            (mine.T, jnp.arange(held)))
        return y

    return expert_mlp


def build(hf: dict, t_pad: int, n_out: int, lower=()):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V]."""
    import jax
    import jax.numpy as jnp

    if hf.get("model_type") != "mimo_v2":
        raise NotImplementedError("the reference of model_type mimo_v2")
    if set(lower) - set(CONTROLS):
        raise ValueError(f"lower={lower!r}: of {CONTROLS}")
    for key, only in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("n_group", 1),
                      ("topk_group", 1), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if (hf.get(key, only) or only) != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    if hf.get("n_shared_experts") or hf.get("routed_scaling_factor") not in (
            None, 1, 1.0):
        raise NotImplementedError(
            "the reference has no shared expert and no routed scaling")
    layers = int(hf["num_hidden_layers"])
    pattern = [int(p) for p in hf["hybrid_layer_pattern"]]
    freq = [int(f) for f in hf["moe_layer_freq"]]
    if len(pattern) != layers or len(freq) != layers:
        raise ValueError(f"hybrid_layer_pattern {pattern} / moe_layer_freq "
                         f"{freq} for {layers} layers")
    n_heads, hd = int(hf["num_attention_heads"]), int(hf["head_dim"])
    vd = int(hf.get("v_head_dim") or hd)
    n_kv = {FULL: int(hf["num_key_value_heads"]),
            WINDOW: int(hf["swa_num_key_value_heads"])}
    theta = {FULL: float(hf["rope_theta"]), WINDOW: float(hf["swa_rope_theta"])}
    if "one_theta" in lower:
        theta[WINDOW] = theta[FULL]
    sink = {FULL: bool(hf.get("add_full_attention_sink_bias")),
            WINDOW: (bool(hf.get("add_swa_attention_sink_bias"))
                     and "no_sink" not in lower)}
    rot = hd if "rope_whole_head" in lower else int(
        hd * float(hf.get("partial_rotary_factor", 1.0)))
    v_scale = (1.0 if "no_value_scale" in lower
               else float(hf.get("attention_value_scale", 1.0)))
    window = (int(hf["sliding_window"]) - ("window_short" in lower)
              + ("window_long" in lower))
    eps = float(hf.get("layernorm_epsilon", 1e-5))
    f32 = jnp.float32
    qb = QUERY_BLOCK if t_pad % QUERY_BLOCK == 0 else t_pad
    pos = jnp.arange(t_pad)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, base):   # x [T, H, d]: half rotation over lanes [0, rot)
        inv = 1.0 / base ** (jnp.arange(0, rot, 2, dtype=f32) / rot)
        ang = pos[:, None].astype(f32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)

    def attention(a, w, kind):
        kvh = n_kv[kind]
        g = n_heads // kvh
        q = rope((a @ w["wq"]).reshape(t_pad, n_heads, hd), theta[kind])
        k = rope((a @ w["wk"]).reshape(t_pad, kvh, hd), theta[kind])
        v = (a @ w["wv"]).reshape(t_pad, kvh, vd) * v_scale
        q = q.reshape(t_pad, kvh, g, hd) * hd ** -0.5

        def block(args):   # a block of queries: q_b [qb, KVH, G, hd], i_b [qb]
            q_b, i_b = args
            mask = pos[None, :] <= i_b[:, None]                      # j <= i
            if kind == WINDOW:
                mask &= i_b[:, None] - pos[None, :] < window
            s = jnp.einsum("qkgd,tkd->kgqt", q_b, k)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            if sink[kind]:
                # the sink's column: a key with no value
                col = jnp.broadcast_to(
                    w["sinks"].reshape(kvh, g, 1, 1), (kvh, g, qb, 1))
                p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
            else:
                p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", p, v)

        o = jax.lax.map(block, (q.reshape(t_pad // qb, qb, kvh, g, hd),
                                pos.reshape(t_pad // qb, qb)))
        return o.reshape(t_pad, n_heads * vd) @ w["wo"]

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def dense_mlp(m, lp):   # a slice of the intermediate width at a time
        inter = lp["w_gate"].shape[1]
        parts = MLP_SLICES if inter % MLP_SLICES == 0 else 1
        width = inter // parts

        def one(y, i):
            wg, wu = (jax.lax.dynamic_slice_in_dim(lp[k], i * width, width, 1)
                      .astype(f32) for k in ("w_gate", "w_up"))
            wd = jax.lax.dynamic_slice_in_dim(lp["w_down"], i * width, width, 0)
            return y + swiglu(m, wg, wu, wd.astype(f32)), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(parts))
        return y

    expert_mlp = expert_layer(hf)

    def at(stack, i):
        return {k: w[i] for k, w in stack.items()}

    def head_logits(x, head):   # [n, D] x [D, V] in slices of the vocabulary
        vocab = head.shape[1]
        parts = HEAD_SLICES if vocab % HEAD_SLICES == 0 else 1
        width = vocab // parts

        def one(i):
            cols = jax.lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
            return x @ cols.astype(f32)

        return jax.lax.map(one, jnp.arange(parts)).transpose(1, 0, 2).reshape(
            x.shape[0], vocab)

    def forward(params, tokens, out_positions):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(f32)
            seen = {FULL: 0, WINDOW: 0, "dense": 0, "moe": 0}
            for l in range(layers):       # a layer at a time, as published
                kind = WINDOW if pattern[l] else FULL
                ffn = "moe" if freq[l] else "dense"
                w = {k: v.astype(f32)
                     for k, v in at(params[kind], seen[kind]).items()}
                seen[kind] += 1
                x = x + attention(rms(x, w["ln1"]), w, kind)
                i = seen[ffn]
                seen[ffn] += 1
                m = rms(x, params[ffn]["ln2"][i].astype(f32))
                # (an expert layer reads its experts out of the whole
                # stack, one at a time: a layer's sixteen sliced out
                # first are 0.8 GB a layer, all six at once in the
                # compiled program, and a probe of 9400 tokens then did
                # not fit beside the served model)
                x = x + (expert_mlp(m, params[ffn], layer=i) if freq[l]
                         else dense_mlp(m, at(params[ffn], i)))
            x = rms(x[out_positions], params["final_norm"].astype(f32))
            return jax.nn.log_softmax(head_logits(x, params["lm_head"]), axis=-1)

    return jax.jit(forward)
