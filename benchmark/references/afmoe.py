"""The plain reference of AFMoE (``model_type: afmoe``; Arcee's Trinity
family): a float32 forward of the layer equations as the published
implementation computes them (``transformers`` ``models/afmoe/
modeling_afmoe.py``) and ISSUE 40 wrote them down. The catalog's config
fixes every size and is silent on the gate, the q/k norms, the four
norms and the rope rule: those come from that source and are listed
under ``assumed`` in the configuration's file.

With ``h`` the residual stream, ``N_*`` RMS norms with a learned weight
and ``rms_norm_eps``, layer ``l``, ``local = layer_types[l] ==
"sliding_attention"``:

    h0     = Emb[tokens] * sqrt(hidden_size)              (mup_enabled)
    a      = N_in(h)
    q,k,v  = a Wq, a Wk, a Wv;   g = a Wg
    q,k    = N_q(q), N_k(k)      per head, one weight [head_dim] each
    q,k    = rope(q, k; theta, the whole head)   if local; nothing if not
    s_ij   = q_i . k_j / sqrt(head_dim),  j <= i,  and i - j < sliding_window if local
    o      = (softmax_j(s) v) * sigmoid(g)                before Wo
    h      = h + N_post_attn(o Wo)
    m      = N_pre_mlp(h)
    l <  num_dense_layers:  y = (silu(m W1) * (m W3)) W2
    l >= num_dense_layers:  p = sigmoid(m Wr)             float32
                            S = top-k of (p + expert_bias)
                            w = p[S] / (sum p[S] + 1e-20) * route_scale
                            y = sum_{e in S} w_e FFN_e(m) + FFN_shared(m)
    h      = h + N_post_mlp(y)
    logits = N_final(h) W_head

Each line **by its definition**: attention is a full masked product over
every key of the sequence (a window layer's too: the mask is all that
tells them apart), a block of ``QUERY_BLOCK`` queries at a time so that
16 k tokens fit beside the served model; the experts are every expert on
every token, one expert at a time, weighted by the gate, zero where the
expert was not chosen (no sort, no groups, no capacity). Plain
``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no pages of either kind, no kernel, no batching, nothing
imported from ``dynamo_tpu.models`` or ``dynamo_tpu.ops`` (the field
names of ``ModelConfig`` are read once, at import, to refuse a program
without the family). It reads the engine's parameter arrays, because the
weights are data (random, from the seed): ``{"embed", "runs": [a dict of
arrays stacked over each run of layers of one kind (window or full
attention, dense or expert feed-forward): ln1, wq, wk, wv, wg, wo,
q_norm, k_norm, ln1_post, ln2, ln2_post, and w_gate, w_up, w_down
([D, I] dense, [E, D, I] experts), router [D, E], router_bias [E],
w_sh_gate, w_sh_up, w_sh_down], "final_norm", "lm_head"}``, ``x @ w``.

Departures from the published code, and readings of it:

- the rotary embedding is the engine's half rotation (pairs ``(i, i +
  d/2)``), the published code's ``rotate_half``;
- ``expert_bias`` is the published ``expert_bias`` buffer (float32),
  added to the scores for the choice only;
- the shared expert is one SwiGLU of width ``num_shared_experts x
  moe_intermediate_size``;
- ``n_group``, ``topk_group``, ``num_expert_groups`` and
  ``num_limited_groups`` are 1 as published (no group-limited choice);
  another value is refused, not approximated. ``load_balance_coeff`` and
  ``use_grouped_mm`` say nothing about the forward pass.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced, 64 tokens a run (four probes of 16 greedy
tokens). The served path computes in bfloat16 (weights, activations,
pages of both kinds) with a float32 router; the reference takes the same
bfloat16 weights to float32. Every sublayer here adds a vector of unit
size to the residual (the post-norms, weight 1.0), so after sixteen
sublayers nearly all of the hidden state is computed and bfloat16's
rounding is not damped by an exact embedding as in the families without
post-norms: the served path reads a mean of 0.12-0.17 where they read
0.03. Readings on the v5e (my chip run, PR 40; PERF.md section 6; the
8-layer configuration, through the server):

- the cell's own probes, ten runs on ten seeds: mean of a run
  0.116-0.167, largest single difference 0.33-0.56; past them
  (``scripts/long_probes.py``, five seeds, each probe's 16 tokens held
  to these limits on their own): at 9400 tokens mean 0.154-0.245,
  largest 0.39-0.67; at 16 000 mean 0.147-0.256, largest 0.46-0.65
  (the fp8 cache there 1.46 / 3.78 and 1.94 / 4.34);
- an fp8 page cache (``kv_cache_dtype: fp8``, the nearest precision
  below the configuration's): mean of a probe 0.55-1.43 (0.93 over the
  four), largest 1.42-3.25: not correct by both limits;
- a router whose scores come from a bfloat16 product reads as the
  float32 one does (mean of a probe 0.093-0.128 against 0.093-0.129 on
  the same seed): the bfloat16 hidden state, not the router's own
  product, decides what near-ties there are, as PR 26 found for
  Moonlight. These limits cannot tell it; tier-1's float32 comparison on
  the CPU does (a router rounded to bfloat16 moves the choice of 8 of
  128 at once).
- with the post-norms' weights drawn at 0.5 and 0.25 instead (a
  calibration run, not served) the same three read 0.055-0.10 | 0.25-0.40
  | as plain, and 0.028-0.038 | 0.075-0.12 | as plain: the fp8 cache
  stands 8 x over the stated precision at 1.0, 4 x at 0.5 and 3 x at
  0.25, so the weights stay at 1.0, where the comparison tells most.

- ``LOGPROB_MEAN_ATOL`` 0.4: 2.4 x the largest sound mean of a run
  (0.167) and 2.3 x under the fp8 cache's mean over a run (0.93); 1.6 x
  the largest sound mean of one long probe's 16 tokens (0.256) and
  1.4 x under the smallest fp8 mean of any single short probe (0.55;
  the long ones read 1.46-1.94);
- ``LOGPROB_ATOL`` 1.0 on a single token: 1.8 x the largest sound
  difference of the cell's probes (0.56), 1.5 x that of the long ones
  (0.67), and under the fp8 cache's largest in every probe (1.42-4.34);
  it is also what catches a non-finite value or a gross fault (a page
  read after its release, a mask, a position).

In float32 on the CPU the served path agrees with this file to 1e-5 in
log-probability at a tiny shape across several windows and releases
(``tests/test_afmoe_reference.py``, limit 1e-3; the fifteen wrong
programs there read over 3e-3), so what the chip shows is rounding.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens (PERF.md section 6, PR 40)
LOGPROB_ATOL = 1.0
LOGPROB_MEAN_ATOL = 0.4

MLP_SLICES = 4      # a dense feed-forward goes to float32 a quarter at a time
HEAD_SLICES = 16    # the head a sixteenth of the vocabulary at a time
QUERY_BLOCK = 128   # queries of an attention layer computed together

LOCAL, GLOBAL = "sliding_attention", "full_attention"


def _refuse_a_program_without_the_family() -> None:
    """A program whose ``ModelConfig`` has no ``layer_types`` takes the
    published keys for a Mixtral trunk's (``num_experts`` > 0), builds
    12 GB of weights and serves one whole-model window, no dense layers,
    no shared expert and no gate: wrong tokens after minutes of set-up.
    This module is imported before anything is built (``run.py``), so
    such a program is refused here, in seconds, as ``references/
    minicpm_sala.py`` refuses one without ``mixer_types``. The
    configuration's fields are all that is read of the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    if "layer_types" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise ImportError(
            "this program has no trunk of window and full attention layers "
            "by layer_types (ModelConfig has no layer_types): it cannot "
            "serve model_type afmoe, and references/afmoe.py has nothing "
            "to compare it with")


_refuse_a_program_without_the_family()


def runs_of(layer_types, dense_layers: int):
    """[(window layer?, dense feed-forward?, length)] of each run of
    layers of one kind, in the order of the engine's ``params["runs"]``."""
    runs = []
    for i, kind in enumerate(layer_types):
        key = [kind == LOCAL, i < dense_layers]
        if runs and runs[-1][:2] == key:
            runs[-1][2] += 1
        else:
            runs.append(key + [1])
    return [tuple(r) for r in runs]


def build(hf: dict, t_pad: int, n_out: int):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V]."""
    import jax
    import jax.numpy as jnp

    if hf.get("model_type") != "afmoe":
        raise NotImplementedError("the reference of model_type afmoe")
    for key, only in (("score_func", "sigmoid"), ("route_norm", True),
                      ("n_group", 1), ("topk_group", 1),
                      ("num_expert_groups", 1), ("num_limited_groups", 1),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if (hf.get(key, only) or only) != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    kinds = list(hf["layer_types"])
    if len(kinds) != int(hf["num_hidden_layers"]) or set(kinds) - {LOCAL, GLOBAL}:
        raise ValueError(f"layer_types {kinds} for {hf['num_hidden_layers']} layers")
    hidden = int(hf["hidden_size"])
    n_heads, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or hidden // n_heads)
    g = n_heads // n_kv
    window = int(hf.get("sliding_window") or 0)
    theta = float(hf.get("rope_theta", 10000.0))
    eps = float(hf.get("rms_norm_eps", 1e-5))
    dense_layers = int(hf.get("num_dense_layers", 0) or 0)
    top_k = int(hf["num_experts_per_tok"])
    route_scale = float(hf.get("route_scale", 1.0) or 1.0)
    emb_scale = hidden ** 0.5 if hf.get("mup_enabled") else 1.0
    f32 = jnp.float32
    qb = QUERY_BLOCK if t_pad % QUERY_BLOCK == 0 else t_pad
    pos = jnp.arange(t_pad)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x):   # x [T, H, d], half rotation over the whole head
        d = x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = pos[:, None].astype(f32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(a, w, local):
        q = rms((a @ w["wq"]).reshape(t_pad, n_heads, hd), w["q_norm"])
        k = rms((a @ w["wk"]).reshape(t_pad, n_kv, hd), w["k_norm"])
        v = (a @ w["wv"]).reshape(t_pad, n_kv, hd)
        if local:
            q, k = rope(q), rope(k)
        q = q.reshape(t_pad, n_kv, g, hd) * hd ** -0.5

        def block(args):   # a block of queries: q_b [qb, KVH, G, hd], i_b [qb]
            q_b, i_b = args
            mask = pos[None, :] <= i_b[:, None]                      # j <= i
            if local:
                mask &= i_b[:, None] - pos[None, :] < window
            s = jnp.einsum("qkgd,tkd->kgqt", q_b, k)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, (q.reshape(t_pad // qb, qb, n_kv, g, hd),
                                pos.reshape(t_pad // qb, qb)))
        o = o.reshape(t_pad, n_heads * hd) * jax.nn.sigmoid(a @ w["wg"])
        return o @ w["wo"]

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

    def expert_mlp(m, lp):
        p = jax.nn.sigmoid(m @ lp["router"].astype(f32))             # [T, E]
        select = p + lp["router_bias"].astype(f32)[None, :]          # the choice only
        _, chosen = jax.lax.top_k(select, top_k)                     # [T, k]
        gate = jnp.zeros_like(p).at[jnp.arange(t_pad)[:, None], chosen].set(
            jnp.take_along_axis(p, chosen, axis=1))
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) * route_scale

        def one_expert(y, ew):   # one expert's weights to float32 at a time
            w_e, wg, wu, wd = ew
            return y + w_e[:, None] * swiglu(m, wg.astype(f32), wu.astype(f32),
                                             wd.astype(f32)), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                            (gate.T, lp["w_gate"], lp["w_up"], lp["w_down"]))
        if "w_sh_gate" in lp:
            y = y + swiglu(m, lp["w_sh_gate"].astype(f32),
                           lp["w_sh_up"].astype(f32), lp["w_sh_down"].astype(f32))
        return y

    small = ("ln1", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm",
             "ln1_post", "ln2", "ln2_post")

    def layer_of(local, is_dense):
        def layer(x, lp):
            w = {k: lp[k].astype(f32) for k in small}
            x = x + rms(attention(rms(x, w["ln1"]), w, local), w["ln1_post"])
            m = rms(x, w["ln2"])
            y = dense_mlp(m, lp) if is_dense else expert_mlp(m, lp)
            return x + rms(y, w["ln2_post"]), None
        return layer

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
            x = params["embed"][tokens].astype(f32) * emb_scale
            for (local, is_dense, _), run in zip(runs_of(kinds, dense_layers),
                                                 params["runs"]):
                x, _ = jax.lax.scan(layer_of(local, is_dense), x, run)
            x = rms(x[out_positions], params["final_norm"].astype(f32))
            return jax.nn.log_softmax(head_logits(x, params["lm_head"]), axis=-1)

    return jax.jit(forward)
