"""The plain reference of Nemotron-H (``model_type: nemotron_h``;
NVIDIA-Nemotron-3-Super-120B-A12B is one): a float32 forward of the
layer equations as the catalog's ``config`` and ``described_as`` give
them and ISSUE 62 wrote them down.

With ``N`` an RMS norm with a learned weight (``eps =
layer_norm_epsilon``) and layer ``l`` of the kind its letter of
``hybrid_override_pattern`` names, **one sublayer a layer**:

    h      = Emb[tokens]
    n      = N_l(h)
    M:  [z | xBC | dt] = n W_in                      no bias
        xBC  = silu(conv(xBC) + b_conv)              causal depthwise, width conv_kernel
        D_t  = softplus(dt_t + dt_bias),  A = -exp(A_log)          per head
        S_t  = exp(D_t A) S_{t-1} + D_t x_t (x) B_t   state [P, N] a head, from zeros
        y_t  = S_t C_t + D x_t                       B, C of the head's group (n_groups)
        m    = N_g(y * silu(z)) W_out                the gate, then the norm over each
                                                     group's d_inner / n_groups channels
    *:  q, k, v = n Wq, n Wk, n Wv                   no bias, no positional term
        s_ij = q_i . k_j / sqrt(head_dim),  j <= i;  m = (softmax_j(s) v) Wo
    E:  s    = sigmoid(n W_r)                        float32, every published expert
        S    = the num_experts_per_tok largest of s + b_corr
        g_e  = routed_scaling_factor * s_e / sum_S s          (norm_topk_prob)
        z    = n W_fc1                               hidden -> moe_latent_size, once
        r    = sum_{e in S, e held} g_e W2_e relu(W1_e z)^2    no gate matrix (relu2)
        m    = r W_fc2 + W_sh2 relu(W_sh1 n)^2       the router and the shared expert
                                                     read n, not the latent
    h      = h + m                                   no second norm, no second add
    logits = N_final(h) W_head                       the head is not tied

**The share.** The configuration this reference is built from holds one
expert-parallel rank's experts: ``n_routed_experts`` of the
``expert_share.of_experts`` the router scores, those of rank
``expert_share.rank``. The reference is given the same share: it routes
over every published expert, weighs with the gates over all the chosen
ones, and adds in the latent the terms of the experts held and no
others; the two latent projections and the shared expert are whole. What
the absent ranks' experts would have added is left out here as in the
program, and that partial result goes on to the next layer. Without
``expert_share`` every expert is held and the sum is whole
(``tests/test_nemotron_h_reference.py`` adds four shares up against it).

Each line **by its definition**: the recurrence is the recurrence, one
token at a time through ``lax.scan`` from a zero state (the served
program runs a prefill chunk in the chunked matrix form and decodes
through a state it keeps by slot); attention is a full masked product
over every key, a block of ``QUERY_BLOCK`` queries at a time so that it
fits beside the served model; the experts are every held expert on every
token, one at a time, weighted by the gate, zero where the expert was
not chosen (no sort, no groups, no capacity); the layers are walked
letter by letter (the served program scans the pattern's periods over
weights stacked by kind). Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no pages, no kernel,
no batching (a wide weight goes to float32 a block of columns at a time
and a layer is a program of its own, so that the reference fits beside
the served model), nothing imported from ``dynamo_tpu.models`` or
``dynamo_tpu.ops`` (the field names of ``ModelConfig`` are read once, at
import, to refuse a program without the family). It reads the engine's
parameter arrays, because the weights are data (random, from the seed):
``{"embed", "mamba": {ln1, ssm_in [D, 2 d_ssm + 2 G N + H], conv_w [K,
C], conv_b, dt_bias, A_log, D, ssm_norm, ssm_out}, "attention": {ln1,
wq, wk, wv, wo}, "moe": {ln1, router [D, E], router_bias [E],
w_latent_in [D, Z], w_up [E_held, Z, I], w_down [E_held, I, Z],
w_latent_out [Z, D], w_sh_up, w_sh_down}, "final_norm", "lm_head"}``,
each kind's arrays stacked over its own layers, ``x @ w``.

Departures from the published description, and readings of it (each
also under ``assumed`` in the configuration's file):

- the **multi-token-prediction module** (``num_nextn_predict_layers``
  1, ``mtp_hybrid_override_pattern`` ``*E``) is not run, here or in the
  program: it proposes tokens and changes no logit of the trunk;
- the gated norm is the family's ``RMSNormGated`` with ``group_size =
  d_inner / n_groups``, the gate before the norm ``[assumed]``;
- the attention layers apply no positional term ``[assumed:
  nemotron_h's attention applies none; rope_theta and
  partial_rotary_factor are in the config and unused by it]``;
- the published mixer clamps ``D_t`` to ``(0, inf)``: softplus is
  positive, the clamp does nothing and is left out; ``time_step_min /
  max / floor`` are initialisation keys;
- the correction bias steers the pick alone and the gates are the
  unbiased scores renormalised, then times ``routed_scaling_factor``
  (DeepSeek-V3's router, which ``n_group`` / ``topk_group`` /
  ``norm_topk_prob`` / ``routed_scaling_factor`` are the keys of);
- biases on the projections, a SiLU expert, ``n_group`` > 1, a tied
  head and a letter that is none of ``M * E`` are refused, not
  approximated.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced, 64 tokens a run (four probes of 16 greedy tokens:
three prompts of 64-512 tokens and one of 2200). The served path
computes in bfloat16 (weights, activations, the residual stream, pages,
conv window) with a float32 SSM state and a float32 router; the
reference takes the same bfloat16 weights to float32. The weights are
drawn so that the state counts (``models/nemotron_h.py``:
``STATE_HORIZON``, ``BC_CONV_BIAS``, logits of deviation 3.0;
``models/granite_hybrid.py`` says why each). Readings on the v5e at the
published widths (the configuration that names this module: eleven
layers, 128 of 512 experts; my chip run, PR 62; PERF.md section 6), a
run's four probes together as the harness compares them:

- **the served program**, eight runs of the cell and two servings at the
  harness's lengths, a seed each: largest single difference 0.096-0.345
  (0.345, 0.210, 0.192, 0.174, then 0.146 and under), mean 0.025-0.037;
  the probe of 2200 tokens alone 0.058-0.096 / 0.027-0.029 in the two
  servings, the short probes alone 0.042-0.192 / 0.020-0.035;
- **the state in bfloat16** (``build(lower=("state",))``: this reference
  with the recurrent state rounded to bfloat16 from each token to the
  next, the precision below the float32 the configuration states for
  it, in the served program's place on a serving's probes, two seeds,
  ``scripts/long_probes.py --controls state``): largest 1.249, 1.244
  (each at the probe of 2200 tokens), mean 0.140, 0.162; the probe of
  2200 tokens alone 1.24-1.25 / 0.35-0.53, the short probes alone
  0.06-0.32 / 0.015-0.100, which is why the long probe decides;
- ``LOGPROB_ATOL`` 0.7 on a single token: 2.0 x the largest sound
  difference of ten readings (0.345) and 1.8 x under the smaller of
  the bfloat16 state's (1.244, which is 3.6 x the sound largest): a run
  with the state in bfloat16 is not correct by this limit, at both
  seeds read;
- ``LOGPROB_MEAN_ATOL`` 0.1: 2.7 x the largest sound mean of a run
  (0.037) and 1.4 x under the smaller mean of the bfloat16 state
  (0.140): the state fails this one too. Both limits are Granite
  4.0-H's (``references/granite_hybrid.py``), whose two readings lie
  where these do: the same mixer widths, the same draw of the horizons.

**What these limits do not see, and cannot**, each read as the state
was (two seeds): router logits from a bfloat16 product
(``lower=("router",)``) read mean 0.005-0.007, largest 0.045-0.066, and
the attention layer's keys and values held in fp8 (``lower=("pages",)``)
mean 0.020-0.021, largest 0.062-0.101: under what the sound program
itself reads against this file (one layer in eleven attends; the
experts of a layer are a prototype and a spread, so a flipped pick moves
little). The float32 comparison of tier-1 on the CPU is what holds
those two.

In float32 on the CPU the served path agrees with this file to 1e-5 in
log-probability at a tiny shape through chunked prefill and decode
(``tests/test_nemotron_h_reference.py``, limit 2e-4; the wrong programs
there read over 1e-3), so what the chip shows is rounding.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens (PERF.md section 6, PR 62)
LOGPROB_ATOL = 0.7
LOGPROB_MEAN_ATOL = 0.1

QUERY_BLOCK = 128   # queries of an attention layer computed together

LETTERS = {"M": "mamba", "*": "attention", "E": "moe"}
# what ``build(lower=...)`` can compute in the precision below the stated
CONTROLS = ("state", "router", "pages")


def _refuse_a_program_without_the_family() -> None:
    """A program without the family refuses the published keys itself
    (``hybrid_override_pattern`` under a ``model_type`` it does not
    know), but only after the harness has written a model directory and
    started the engine. This module is imported before anything is built
    (``run.py``), so such a program is refused here, at once, as
    ``references/granite_hybrid.py`` does. The configuration's fields
    are all that is read of the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"moe_latent_size", "mlp_hidden_act"} <= fields:
        raise ImportError(
            "this program has no trunk of layers that are a mixer, an "
            "attention or an expert block alone with experts in a latent "
            "(ModelConfig has no moe_latent_size / mlp_hidden_act): it "
            "cannot serve model_type nemotron_h, and "
            "references/nemotron_h.py has nothing to compare it with")


_refuse_a_program_without_the_family()


def _dot_cols(x, w, parts: int):
    """``x @ w`` in float32, ``w`` taken to float32 a block of columns
    at a time (``parts`` blocks where they divide the columns): the
    whole float32 copy of a wide weight does not fit beside the served
    model."""
    import jax
    import jax.numpy as jnp

    cols = w.shape[1]
    if cols % parts:
        parts = 1
    width = cols // parts

    def one(i):
        block = jax.lax.dynamic_slice_in_dim(w, i * width, width, axis=1)
        return x @ block.astype(jnp.float32)

    out = jax.lax.map(one, jnp.arange(parts))           # [parts, T, width]
    return out.transpose(1, 0, 2).reshape(x.shape[0], cols)


def expert_layer(hf: dict, lower=()):
    """``fn(n [T, D], layer's arrays) -> (routed [T, Z], shared [T,
    D])``: the part of the routed sum, **in the latent**, that the
    experts held give (all of it where the configuration states no
    share), and the shared expert, float32. A layer adds ``routed W_fc2
    + shared``. ``lower`` as ``build``'s."""
    import jax
    import jax.numpy as jnp

    top_k = int(hf["num_experts_per_tok"])
    held = int(hf["n_routed_experts"])
    share = hf.get("expert_share") or {}
    first = int(share.get("rank", 0)) * held     # the first expert held
    scaling = float(hf.get("routed_scaling_factor", 1.0))
    f32 = jnp.float32

    def relu2(x, w1, w2):
        return jnp.square(jax.nn.relu(x @ w1)) @ w2

    def experts(n, lp):
        if "router" in lower:   # a bfloat16 product of bfloat16 operands
            bf16 = jnp.bfloat16
            logits = jnp.dot(n.astype(bf16), lp["router"].astype(bf16),
                             preferred_element_type=bf16).astype(f32)
        else:
            logits = n @ lp["router"].astype(f32)                    # [T, E]
        score = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(score + lp["router_bias"].astype(f32), top_k)
        picked = jnp.take_along_axis(score, chosen, axis=1)          # [T, k]
        if hf.get("norm_topk_prob", True):
            picked = picked / picked.sum(axis=-1, keepdims=True)
        gate = jnp.zeros_like(score).at[
            jnp.arange(n.shape[0])[:, None], chosen].set(picked * scaling)
        # the experts held, one at a time; a pick of an absent one adds nothing
        mine = jax.lax.dynamic_slice_in_dim(gate, first, held, axis=1)
        z = n @ lp["w_latent_in"].astype(f32)                        # [T, Z]

        def one_expert(r, ew):   # one expert's weights to float32 at a time
            g_e, w1, w2 = ew
            return r + g_e[:, None] * relu2(z, w1.astype(f32), w2.astype(f32)), None

        r, _ = jax.lax.scan(one_expert, jnp.zeros_like(z),
                            (mine.T, lp["w_up"], lp["w_down"]))
        hidden = jnp.square(jax.nn.relu(_dot_cols(n, lp["w_sh_up"], 6)))
        return r, _dot_cols(hidden, lp["w_sh_down"], 4)

    return experts


def build(hf: dict, t_pad: int, n_out: int, lower=()):
    """fn(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V].

    One jitted program a kind of layer, called letter by letter with the
    kind's stacked arrays and the layer's index, and one for the head,
    each taking its wide weights to float32 a block at a time
    (``_dot_cols``, an expert at a time): a float32 copy of the whole
    stage is 18.6 GB and the reference runs beside the served model.

    ``lower`` names what is computed in the precision below the one the
    configuration states, everything else as it is: ``"state"`` (the
    recurrent state held in bfloat16 from one token to the next),
    ``"router"`` (the router's logits a bfloat16 product) and ``"pages"``
    (an attention layer's keys and values held in fp8, e4m3). The
    comparison that decides ``correct`` builds with none.
    (``lax.reduce_precision`` and not a cast there and back: the chip's
    compiler is allowed excess precision and removes the pair.)"""
    import jax
    import jax.numpy as jnp

    if set(lower) - set(CONTROLS):
        raise ValueError(f"lower={lower!r}: of {CONTROLS}")
    if hf.get("model_type") != "nemotron_h":
        raise NotImplementedError("the reference of model_type nemotron_h")
    for key, only in (("attention_bias", False), ("mamba_proj_bias", False),
                      ("mlp_bias", False), ("use_bias", False),
                      ("use_conv_bias", True), ("mamba_hidden_act", "silu"),
                      ("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("n_shared_experts", 1), ("tie_word_embeddings", False)):
        if hf.get(key, only) != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    pattern = str(hf["hybrid_override_pattern"])
    if len(pattern) != int(hf["num_hidden_layers"]) or set(pattern) - set(LETTERS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} for "
                         f"{hf['num_hidden_layers']} layers (M * E)")
    hidden = int(hf["hidden_size"])
    n_heads, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or hidden // n_heads)
    rep = n_heads // n_kv
    eps = float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))
    mh, mp = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    n, g, kc = (int(hf["ssm_state_size"]), int(hf.get("n_groups", 1)),
                int(hf.get("conv_kernel", 4)))
    d_ssm, hg = mh * mp, mh // g            # hg heads read a group's B and C
    f32 = jnp.float32
    qb = QUERY_BLOCK if t_pad % QUERY_BLOCK == 0 else t_pad
    pos = jnp.arange(t_pad)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def attention(a, w):
        q = _dot_cols(a, w["wq"], 4).reshape(t_pad, n_kv, rep, hd) * hd ** -0.5
        k = (a @ w["wk"].astype(f32)).reshape(t_pad, n_kv, hd)
        v = (a @ w["wv"].astype(f32)).reshape(t_pad, n_kv, hd)
        if "pages" in lower:   # keys and values kept in fp8 (e4m3), as a page would
            k, v = (jax.lax.reduce_precision(x, 4, 3) for x in (k, v))

        def block(args):   # a block of queries: q_b [qb, KVH, G, hd], i_b [qb]
            q_b, i_b = args
            mask = pos[None, :] <= i_b[:, None]                      # j <= i
            s = jnp.einsum("qkgd,tkd->kgqt", q_b, k)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, (q.reshape(t_pad // qb, qb, n_kv, rep, hd),
                                pos.reshape(t_pad // qb, qb)))
        return _dot_cols(o.reshape(t_pad, n_heads * hd), w["wo"], 4)

    def mixer(a, w):
        u = _dot_cols(a, w["ssm_in"], 5)
        z, xbc, dt = (u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * g * n],
                      u[:, 2 * d_ssm + 2 * g * n:])
        # causal depthwise conv: tap k meets the input K - 1 - k tokens back
        conv_w, conv_b = w["conv_w"].astype(f32), w["conv_b"].astype(f32)
        xp = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), f32), xbc], 0)
        xbc = jax.nn.silu(sum(xp[k:k + t_pad] * conv_w[k] for k in range(kc))
                          + conv_b)
        # heads by group: a head reads its group's B and C
        x = xbc[:, :d_ssm].reshape(t_pad, g, hg, mp)
        bm = xbc[:, d_ssm:d_ssm + g * n].reshape(t_pad, g, n)
        cm = xbc[:, d_ssm + g * n:].reshape(t_pad, g, n)
        delta = jax.nn.softplus(dt + w["dt_bias"]).reshape(t_pad, g, hg)
        decay_rate = -jnp.exp(w["A_log"].astype(f32)).reshape(g, hg)
        skip = w["D"].astype(f32).reshape(g, hg)

        def token(s, inp):   # the recurrence, one token; s [G, Hg, P, N]
            x_t, b_t, c_t, d_t = inp
            s = (jnp.exp(d_t * decay_rate)[:, :, None, None] * s
                 + (d_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
            if "state" in lower:   # kept in bfloat16 between two tokens
                s = jax.lax.reduce_precision(s, 8, 7)
            return s, (jnp.einsum("ghpn,gn->ghp", s, c_t)
                       + skip[:, :, None] * x_t)

        _, y = jax.lax.scan(token, jnp.zeros((g, hg, mp, n), f32),
                            (x, bm, cm, delta))
        y = y.reshape(t_pad, d_ssm) * jax.nn.silu(z)      # the gate, then the norm
        y = y.reshape(t_pad, g, d_ssm // g)               # over each group's channels
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return _dot_cols(y.reshape(t_pad, d_ssm) * w["ssm_norm"].astype(f32),
                         w["ssm_out"], 4)

    experts = expert_layer(hf, lower)

    def moe(a, lp):
        routed, shared = experts(a, lp)
        return routed @ lp["w_latent_out"].astype(f32) + shared

    def layer_of(sublayer):
        def layer(x, stack, i):   # one norm, one sublayer, one add
            with jax.default_matmul_precision("highest"):
                lp = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                      for k, v in stack.items()}
                return x + sublayer(rms(x, lp["ln1"].astype(f32)), lp)
        return jax.jit(layer)

    layers = {"mamba": layer_of(mixer), "attention": layer_of(attention),
              "moe": layer_of(moe)}

    @jax.jit
    def head(x, final_norm, lm_head, out_positions):
        with jax.default_matmul_precision("highest"):
            x = rms(x[out_positions], final_norm.astype(f32))
            return jax.nn.log_softmax(_dot_cols(x, lm_head, 16), axis=-1)

    def forward(params, tokens, out_positions):
        x = params["embed"][tokens].astype(f32)
        seen = {kind: 0 for kind in layers}
        for letter in pattern:
            kind = LETTERS[letter]
            x = layers[kind](x, params[kind], jnp.int32(seen[kind]))
            seen[kind] += 1
        return head(x, params["final_norm"], params["lm_head"], out_positions)

    return forward
