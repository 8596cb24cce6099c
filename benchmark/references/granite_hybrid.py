"""The plain reference of Granite 4.0-H (``model_type:
granitemoehybrid``; granite-4.0-h-small is one): a float32 forward of
the layer equations as the published implementation computes them
(``transformers`` ``models/granitemoehybrid/modeling_granitemoehybrid.py``)
and ISSUE 48 wrote them down.

With ``e = embedding_multiplier``, ``r = residual_multiplier``, ``a =
attention_multiplier``, ``s = logits_scaling``, ``N`` an RMS norm with a
learned weight, layer ``l`` of kind ``layer_types[l]``:

    h      = Emb[tokens] * e
    n      = N_in(h)
    mamba:      [z | xBC | dt] = n W_in                      no bias, no muP vector
                xBC  = silu(conv(xBC) + b_conv)              causal depthwise, width mamba_d_conv
                D_t  = softplus(dt_t + dt_bias),  A = -exp(A_log)          per head
                S_t  = exp(D_t A) S_{t-1} + D_t x_t (x) B_t   state [P, N] a head, from zeros
                y_t  = S_t C_t + D x_t                       B, C shared by the heads of a group
                m    = N_gate(y * silu(z)) W_out             the gate, then the norm over each group
    attention:  q, k, v = n Wq, n Wk, n Wv                   no bias, no positional term (nope)
                s_ij = a q_i . k_j,  j <= i;  m = (softmax_j(s) v) Wo       a, not 1/sqrt(head_dim)
    h      = h + r m
    n      = N_post(h)
    t      = n W_r                                           float32, every published expert
    S      = the num_experts_per_tok largest of t;  g = softmax(t[S])      over the chosen only
    y      = sum_{e in S, e held} g_e FFN_e(n)  +  FFN_shared(n)
             FFN(n) = W_out (silu(u1) * u2),  [u1 | u2] = n W_in
    h      = h + r y
    logits = N_final(h) Emb^T / s                            the head is the embedding

**The share.** The configuration this reference is built from holds one
expert-parallel rank's experts: ``num_local_experts`` of the
``expert_share.of_experts`` the router scores, those of rank
``expert_share.rank``. The reference is given the same share: it routes
over every published expert, weighs with the softmax over all the chosen
ones, and adds the terms of the experts held and no others; what the
absent rank's experts would have added is left out here as in the
program, and that partial result goes on to the next layer. Without
``expert_share`` every expert is held and the sum is whole
(``tests/test_granite_hybrid_reference.py`` adds the shares up against
it).

Each line **by its definition**: the recurrence is the recurrence, one
token at a time through ``lax.scan`` from a zero state (the served
program runs a prefill chunk in the chunked matrix form and decodes
through a state it keeps by slot); attention is a full masked product
over every key, a block of ``QUERY_BLOCK`` queries at a time so that it
fits beside the served model; the experts are every held expert on every
token, one at a time, weighted by the gate, zero where the expert was
not chosen (no sort, no groups, no capacity). Plain ``jax.numpy`` in
float32 under ``default_matmul_precision("highest")``: no cache, no
pages, no kernel, no batching, nothing imported from
``dynamo_tpu.models`` or ``dynamo_tpu.ops`` (the field names of
``ModelConfig`` are read once, at import, to refuse a program without
the family). It reads the engine's parameter arrays, because the weights
are data (random, from the seed): ``{"embed", "runs": [a dict of arrays
stacked over each run of layers of one kind: ln1, ln2, router [D, E],
w_gate, w_up [E_held, D, I], w_down [E_held, I, D], w_sh_gate, w_sh_up,
w_sh_down, and ssm_in [D, 2 d_ssm + 2 G N + H], conv_w [K, C], conv_b,
dt_bias, A_log, D, ssm_norm, ssm_out for a mamba run or wq, wk, wv, wo
for an attention run], "final_norm"}``, ``x @ w``.

Departures from the published code, and readings of it (each also under
``assumed`` in the configuration's file):

- ``intermediate_size`` is one expert's width (the config has no key of
  its own for it; the catalog's note says so); ``input_linear`` is read
  as ``[gate | up]`` in that order, as the published ``chunk(2)`` does;
- the published mixer clamps ``D_t`` to ``time_step_limit = (0, inf)``:
  softplus is positive, the clamp does nothing and is left out;
- the published router takes the top-k of the logits and then the
  softmax over them, in float32: the same here;
- the published code adds the routed sum and the shared expert before
  the residual multiplier: the same here;
- ``mamba_proj_bias: true``, a rotary ``position_embedding_type``, a
  scaled rotary embedding, ``attention_bias: true`` and a
  ``layer_types`` entry that is neither kind are refused, not
  approximated.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced, 64 tokens a run (four probes of 16 greedy tokens:
three prompts of 64-512 tokens and one of 2200). The served path
computes in bfloat16 (weights, activations, pages, conv window) with a
float32 SSM state and a float32 router; the reference takes the same
bfloat16 weights to float32. The weights are drawn so that the state
counts and so that no position multiplies what is rounded
(``models/granite_hybrid.py``: ``STATE_HORIZON``, a head forgets after
1024 to 4096 tokens, where under Mamba-2's own initialisation no control
below could be told from the sound program; ``BC_CONV_BIAS``, the conv's
bias under B and C positive, because under the symmetric one the number
a token reads out of a long-lived state came near zero once in a
thousand positions and the gated norm divided by it: 0.78 in one token
of the sound program at seed 1140390009, the driver's check; logits of
deviation 3.0). Readings on the v5e at the published widths (the
configuration that names this module, 10 layers, 36 of 72 experts; my
chip run, PR 48, third round; PERF.md section 6), a run's four probes
together as the harness compares them, and a single probe past them
(``scripts/long_probes.py`` at 3900 tokens):

- **the served program**, six runs of the cell and two servings at the
  harness's lengths, seed 1140390009 among both: largest single
  difference 0.104-0.141, mean 0.029-0.044 (0.1405 / 0.0439 at that
  seed; PERF.md section 6 has each); a probe of 3900 tokens alone 0.124
  / 0.043;
- **the state in bfloat16** (``build(lower=("state",))``: this reference
  with the recurrent state rounded to bfloat16 from each token to the
  next, the precision below the float32 the configuration states for
  it, in the served program's place on the same probes, two seeds):
  largest 1.38, 1.57 (each at the probe of 2200 tokens), mean 0.157,
  0.142; the probe of 2200 tokens alone 1.38-1.57 / 0.45-0.46, one of
  3900 alone 1.56 / 0.71; the short probes alone 0.02-0.31 / 0.008-0.12,
  which is why the long probe decides;
- a wrong program (``scripts/long_probes.py --fault sqrt_scale``, one
  serving at the harness's lengths): the attention layer's scores
  scaled by 1/sqrt(128) and not by the published 1/128, largest 1.43,
  mean 0.345 over the run (a probe alone 0.90-1.43 / 0.30-0.40): not
  correct by both limits (0.87 / 0.286 under the second round's draw,
  and the gates a softmax over all 72 experts 5.36 / 0.81 there, not
  read again);
- ``LOGPROB_ATOL`` 0.7 on a single token: 5.0 x the largest sound
  difference (0.141) and 2.0 x under the smallest of the bfloat16 state
  (1.38, which is 9.8 x the sound largest): a run with the state in
  bfloat16 is not correct by this limit, at both seeds read;
- ``LOGPROB_MEAN_ATOL`` 0.1: 2.3 x the largest sound mean of a run
  (0.044) and 1.4 x under the smallest mean of the bfloat16 state
  (0.142), 3.4 x under the wrong program's: the state fails this one
  too.

Neither limit moved in the third round; the numbers drawn did. (The
second round's draw, horizons of 256 to 4096 under the symmetric bias
and logits of deviation 2.0, read 0.106-0.320 / 0.031-0.058 over
fourteen sound runs and servings and then 0.78 / 0.060 at the driver's
seed, against 1.31-4.70 / 0.126-0.376 for the bfloat16 state: the sound
program's largest had a tail that fourteen runs did not show. On the CPU
at a hidden size of 512 that draw read up to 1.58 in 2304 positions of
the sound program at a mean of 0.066, this one 0.37 at most in 27 648
at a mean of 0.038, the survival falling fourfold every 0.05 past 0.2.)

**What these limits do not see, and cannot**, each read as the state
was (the control in the served program's place, three seeds): router
logits from a bfloat16 product (``lower=("router",)``) read mean
0.0044-0.0089, largest 0.046-0.067, and keys and values held in fp8
(``lower=("pages",)``, e4m3) mean 0.014-0.027, largest 0.047-0.119: a
sixth and a half of what the sound program itself reads against this
file (0.029-0.044; the third round's one seed 0.055 / 0.0044 and 0.107 /
0.027). They are not below the stated precision in what
they return: the bfloat16 rounding that the configuration states for
every activation moves a log-probability more than either does, so no
limit on this comparison, or on any other quantity computed through
bfloat16 activations, stands between them and the sound program (PR 26
and PR 40 read the same of their routers; one layer in ten attends, and
the served program with ``kv_cache_dtype: fp8`` read 0.045-0.055 /
0.10-0.147 before the redraw, as the sound one). The float32 comparison
of tier-1 on the CPU is what holds those two.

In float32 on the CPU the served path agrees with this file to 1e-5 in
log-probability at a tiny shape through chunked prefill, decode, an idle
row and a resumed one (``tests/test_granite_hybrid_reference.py``, limit
2e-4; the wrong programs there, a bfloat16 state, a gate over all the
experts, a rotary embedding, a scale of ``head_dim ** -0.5`` and a
bfloat16 router, read over 1e-3), so what the chip shows is rounding.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens (PERF.md section 6, PR 48)
LOGPROB_ATOL = 0.7
LOGPROB_MEAN_ATOL = 0.1

HEAD_SLICES = 16    # the head a sixteenth of the vocabulary at a time
QUERY_BLOCK = 128   # queries of an attention layer computed together

MAMBA, ATTENTION = "mamba", "attention"
# what ``build(lower=...)`` can compute in the precision below the stated
CONTROLS = ("state", "router", "pages")


def _refuse_a_program_without_the_family() -> None:
    """A program without the family refuses the published keys itself
    (``mamba_*`` under a ``model_type`` it does not know), but only after
    the harness has written a model directory and started the engine.
    This module is imported before anything is built (``run.py``), so
    such a program is refused here, at once, as ``references/afmoe.py``
    does. The configuration's fields are all that is read of the
    program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"residual_multiplier", "experts_of"} <= fields:
        raise ImportError(
            "this program has no trunk of mamba or attention layers under "
            "routed experts held as one rank's share (ModelConfig has no "
            "residual_multiplier / experts_of): it cannot serve model_type "
            "granitemoehybrid, and references/granite_hybrid.py has nothing "
            "to compare it with")


_refuse_a_program_without_the_family()


def runs_of(layer_types):
    """[(kind, length)] of each run of layers of one kind, in the order
    of the engine's ``params["runs"]``."""
    runs = []
    for kind in layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [tuple(r) for r in runs]


def expert_layer(hf: dict, lower=()):
    """``fn(m [T, D], layer's arrays) -> (routed, shared)``: the part of
    the routed sum that the experts held give (all of it where the
    configuration states no share) and the shared expert, each ``[T, D]``
    float32. A layer adds ``r · (routed + shared)``. ``lower`` as
    ``build``'s."""
    import jax
    import jax.numpy as jnp

    top_k = int(hf["num_experts_per_tok"])
    held = int(hf["num_local_experts"])
    share = hf.get("expert_share") or {}
    first = int(share.get("rank", 0)) * held     # the first expert held
    f32 = jnp.float32

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def experts(m, lp):
        if "router" in lower:   # a bfloat16 product of bfloat16 operands
            bf16 = jnp.bfloat16
            logits = jnp.dot(m.astype(bf16), lp["router"].astype(bf16),
                             preferred_element_type=bf16).astype(f32)
        else:
            logits = m @ lp["router"].astype(f32)                    # [T, E]
        top, chosen = jax.lax.top_k(logits, top_k)                   # [T, k]
        gate = jnp.zeros_like(logits).at[
            jnp.arange(m.shape[0])[:, None], chosen].set(
            jax.nn.softmax(top, axis=-1))
        # the experts held, one at a time; a pick of an absent one adds nothing
        mine = jax.lax.dynamic_slice_in_dim(gate, first, held, axis=1)

        def one_expert(y, ew):   # one expert's weights to float32 at a time
            w_e, wg, wu, wd = ew
            return y + w_e[:, None] * swiglu(m, wg.astype(f32), wu.astype(f32),
                                             wd.astype(f32)), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                            (mine.T, lp["w_gate"], lp["w_up"], lp["w_down"]))
        return y, swiglu(m, lp["w_sh_gate"].astype(f32),
                         lp["w_sh_up"].astype(f32), lp["w_sh_down"].astype(f32))

    return experts


def build(hf: dict, t_pad: int, n_out: int, lower=()):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V].

    ``lower`` names what is computed in the precision below the one the
    configuration states, everything else as it is: ``"state"`` (the
    recurrent state held in bfloat16 from one token to the next),
    ``"router"`` (the router's logits a bfloat16 product) and ``"pages"``
    (an attention layer's keys and values held in fp8, e4m3). These are the
    controls the limits were set against (``CONTROLS``); the comparison
    that decides ``correct`` builds with none. (``lax.reduce_precision``
    and not a cast there and back: the chip's compiler is allowed excess
    precision and removes the pair, and the control then reads 0.)"""
    import jax
    import jax.numpy as jnp

    if set(lower) - set(CONTROLS):
        raise ValueError(f"lower={lower!r}: of {CONTROLS}")
    if hf.get("model_type") != "granitemoehybrid":
        raise NotImplementedError("the reference of model_type granitemoehybrid")
    for key, only in (("position_embedding_type", "nope"),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("attention_bias", False), ("rope_scaling", None),
                      ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm")):
        if hf.get(key) is not None and hf[key] != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    kinds = list(hf["layer_types"])
    if len(kinds) != int(hf["num_hidden_layers"]) or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types {kinds} for {hf['num_hidden_layers']} layers")
    hidden = int(hf["hidden_size"])
    n_heads, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or hidden // n_heads)
    rep = n_heads // n_kv
    eps = float(hf.get("rms_norm_eps", 1e-5))
    e_mult = float(hf.get("embedding_multiplier", 1.0))
    r_mult = float(hf.get("residual_multiplier", 1.0))
    a_mult = float(hf.get("attention_multiplier") or hd ** -0.5)
    s_div = float(hf.get("logits_scaling", 1.0))
    mh, mp = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    n, g, kc = (int(hf["mamba_d_state"]), int(hf.get("mamba_n_groups", 1)),
                int(hf.get("mamba_d_conv", 4)))
    d_ssm = mh * mp
    f32 = jnp.float32
    qb = QUERY_BLOCK if t_pad % QUERY_BLOCK == 0 else t_pad
    pos = jnp.arange(t_pad)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def attention(a, w):
        q = (a @ w["wq"]).reshape(t_pad, n_kv, rep, hd) * a_mult
        k = (a @ w["wk"]).reshape(t_pad, n_kv, hd)
        v = (a @ w["wv"]).reshape(t_pad, n_kv, hd)
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
        return o.reshape(t_pad, n_heads * hd) @ w["wo"]

    def mixer(a, w):
        u = a @ w["ssm_in"]
        z, xbc, dt = (u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * g * n],
                      u[:, 2 * d_ssm + 2 * g * n:])
        # causal depthwise conv: tap k meets the input K - 1 - k tokens back
        xp = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), f32), xbc], 0)
        xbc = jax.nn.silu(sum(xp[k:k + t_pad] * w["conv_w"][k] for k in range(kc))
                          + w["conv_b"])
        x = xbc[:, :d_ssm].reshape(t_pad, mh, mp)
        bm = jnp.repeat(xbc[:, d_ssm:d_ssm + g * n].reshape(t_pad, g, n),
                        mh // g, axis=1)                              # [T, H, N]
        cm = jnp.repeat(xbc[:, d_ssm + g * n:].reshape(t_pad, g, n),
                        mh // g, axis=1)
        delta = jax.nn.softplus(dt + w["dt_bias"])                    # [T, H]
        decay_rate = -jnp.exp(w["A_log"])                             # [H]

        def token(s, inp):   # the recurrence, one token
            x_t, b_t, c_t, d_t = inp
            s = (jnp.exp(d_t * decay_rate)[:, None, None] * s
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            if "state" in lower:   # kept in bfloat16 between two tokens
                s = jax.lax.reduce_precision(s, 8, 7)
            return s, jnp.einsum("hpn,hn->hp", s, c_t) + w["D"][:, None] * x_t

        _, y = jax.lax.scan(token, jnp.zeros((mh, mp, n), f32),
                            (x, bm, cm, delta))
        y = y.reshape(t_pad, d_ssm) * jax.nn.silu(z)      # the gate, then the norm
        y = y.reshape(t_pad, g, d_ssm // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return (y.reshape(t_pad, d_ssm) * w["ssm_norm"]) @ w["ssm_out"]

    experts = expert_layer(hf, lower)

    small = {MAMBA: ("ssm_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                     "ssm_norm", "ssm_out"),
             ATTENTION: ("wq", "wk", "wv", "wo")}

    def layer_of(kind):
        token_mixer = mixer if kind == MAMBA else attention

        def layer(x, lp):
            w = {k: lp[k].astype(f32) for k in small[kind] + ("ln1", "ln2")}
            x = x + r_mult * token_mixer(rms(x, w["ln1"]), w)
            routed, shared = experts(rms(x, w["ln2"]), lp)
            return x + r_mult * (routed + shared), None
        return layer

    def head_logits(x, embed):   # [n, D] x [V, D]^T in slices of the vocabulary
        vocab = embed.shape[0]
        parts = HEAD_SLICES if vocab % HEAD_SLICES == 0 else 1
        width = vocab // parts

        def one(i):
            rows = jax.lax.dynamic_slice_in_dim(embed, i * width, width, axis=0)
            return x @ rows.astype(f32).T

        return jax.lax.map(one, jnp.arange(parts)).transpose(1, 0, 2).reshape(
            x.shape[0], vocab)

    def forward(params, tokens, out_positions):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(f32) * e_mult
            for (kind, _), run in zip(runs_of(kinds), params["runs"]):
                x, _ = jax.lax.scan(layer_of(kind), x, run)
            x = rms(x[out_positions], params["final_norm"].astype(f32))
            head = params.get("lm_head")
            logits = (head_logits(x, params["embed"]) if head is None
                      else x @ head.astype(f32))
            return jax.nn.log_softmax(logits / s_div, axis=-1)

    return jax.jit(forward)
