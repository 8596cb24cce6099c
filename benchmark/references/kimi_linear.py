"""The plain reference of Kimi Linear (``model_type: kimi_linear``;
Kimi-Linear-48B-A3B-Instruct is one): a float32 forward of the layer
equations as the paper (arXiv:2510.26692 section 3) and the published
modelling code compute them and ISSUE 52 wrote them down.

``N`` an RMS norm with a learned weight; layers are numbered from 1 in
``linear_attn_config``; layer ``l`` is of the kind whose list names it:

    h      = Emb[tokens]
    n      = N_in(h)
    kda:   [q^ | k^ | v] = silu(conv(n W_qkv))        causal depthwise, short_conv_kernel_size taps, no bias
           q = q^ / |q^|_2 / sqrt(K),  k = k^ / |k^|_2              a head of K channels (eps 1e-6 under the root)
           g = -exp(A_log_h) softplus(n W_fa W_fb + dt_bias)        a log-decay a channel, <= 0
           b = sigmoid(n W_b)                                       a head
           S' = diag(exp(g_t)) S_{t-1}                              S in R^{K x K}, key x value, from zeros
           S_t = S' + b_t k_t (v_t - S'^T k_t)^T
           o_t = S_t^T q_t
           m  = (N_head(o) * sigmoid(n W_ga W_gb)) W_o
    mla:   q = n W_q                                  H x (nope + rope), no low-rank query
           c = N_kv(n W_dkv),  k_r = n W_kr           the latent (kv_lora_rank) and the shared 64-wide key
           k = [c W_uk | k_r],  v = c W_uv            a head; NO rotary term on either 64-wide part (mla_use_nope)
           s_ij = q_i . k_j / sqrt(nope + rope), j <= i;  m = (softmax_j(s) v) W_o
    h      = h + m
    n      = N_post(h)
    layer <= first_k_dense_replace:  y = W_down (silu(n W_gate) * n W_up)
    else:  s = sigmoid(n W_r)                         float32, every published expert
           P = the num_experts_per_token largest of s + bias        one group: a plain top-k
           w_e = s_e / sum_{P} s * routed_scaling_factor            moe_renormalize
           y = sum_{e in P, e held} w_e FFN_e(n) + FFN_shared(n)
    h      = h + y
    logits = N_final(h) W_head                        untied

**The share.** The configuration this reference is built from holds one
expert-parallel rank's experts: ``num_experts`` of the
``expert_share.of_experts`` the router scores, those of rank
``expert_share.rank``. The reference is given the same share: it routes
over every published expert, weighs with the gates of all the picked
ones, and adds the terms of the experts held and no others; what the
absent ranks' experts would have added is left out here as in the
program. Without ``expert_share`` every expert is held and the sum is
whole (``tests/test_kimi_linear_reference.py`` adds the sixteenths up
against it).

Each line **by its definition**: the recurrence one token at a time
through ``lax.scan`` from a zero state (the served program runs a
prefill chunk in the WY chunked form and decodes through a state it
keeps by slot); latent attention un-absorbed, every key expanded to
every head, a full masked product over the whole causal sequence, a
block of ``QUERY_BLOCK`` queries at a time (the served program keeps the
latent and absorbs ``W_uk`` into the query); the experts every held
expert on every token, one at a time, weighted by the gate, zero where
it was not picked. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no pages, no kernel,
no batching, nothing imported from ``dynamo_tpu.models`` or
``dynamo_tpu.ops`` (the field names of ``ModelConfig`` are read once, at
import, to refuse a program without the family). **Layer by layer**: one
jitted function a kind of layer, called with that layer's arrays, so
that the float32 copies of one layer's weights are all that lies beside
the served model's 14 GB. It reads the engine's parameter arrays,
because the weights are data (random, from the seed): ``{"embed",
"final_norm", "lm_head" [D, V], "kda": {ln1, w_qkv [D, 3HK], conv_w
[taps, 3HK], w_fa [D, K], w_fb [K, HK], dt_bias [HK], A_log [H], w_b
[D, H], w_ga, w_gb, o_norm [K], wo [HK, D]}, "mla": {ln1, wq, w_dkv,
ln_kv, w_kr, w_uk [r, H, nope], w_uv [r, H, v], wo}, "dense": {ln2,
w_gate, w_up, w_down}, "moe": {ln2, router [D, E], router_bias [E],
w_gate, w_up [E_held, D, I], w_down [E_held, I, D], w_sh_gate, w_sh_up,
w_sh_down}}``, each stacked over the layers of its kind in their order,
``x @ w``.

What the catalog's ``config`` does not give and the published modelling
code does (each also under ``assumed`` in the configuration's file):
the low-rank gates' inner width (``head_dim`` = 128), ``A_log`` a head
and ``dt_bias`` a channel, convolutions without bias and with SiLU, the
L2 norm's epsilon (1e-6, under the root), ``β`` a plain sigmoid, the
output gate a sigmoid before ``W_o``, ``k_r`` carried unrotated, the
router's correction bias, the softmax scale ``(nope + rope)^{-1/2}``.
``mla_use_nope: false``, a ``q_lora_rank``, ``num_expert_group > 1``,
``num_nextn_predict_layers > 0`` and a ``rope_scaling`` are refused, not
approximated.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced, 64 tokens a run (four probes of 16 greedy tokens:
three prompts of 64-512 tokens and one of 2200). The served path
computes in bfloat16 (weights, the operands of every product, pages,
conv window) with a float32 KDA state, a float32 router and a float32
residual stream; the reference takes the same bfloat16 weights to
float32. **This is the first configuration served at whole depth**: 27
layers, 54 sublayers of about unit size under logits of deviation 3.0,
and what bfloat16 rounds in one sublayer reaches the head through all
the sublayers behind it, so the sound program stands a quarter of a nat
a token from this file where the configurations cut to 6-12 layers stand
0.02-0.05 (a perturbation of 1e-3 of the embedding moves the greedy
token's log-probability by 0.010, 0.023 and 0.045 at 4, 8 and 16 layers
of this trunk in float32 on the CPU: linear in the depth, and what is
injected at every sublayer adds up as its square). Readings on the v5e at
the published widths (the configuration that names this module; my chip
runs, PR 52; PERF.md section 6):

- **the served program** (as committed: the KDA mixer's projections hand
  their sums on in float32 and the residual stream is float32; with both
  in bfloat16 the same two seeds read 0.342-0.345 / 1.26-1.42): a run's
  four probes together 0.219-0.289 mean and 0.746-0.941 largest over four
  seeds of the cell and of ``scripts/long_probes.py --lengths harness``;
  40 probes of 300 tokens in one serving (640 tokens) mean 0.247, a
  probe's mean 0.19-0.31 (deviation 0.047), largest single 0.920; 8
  probes of 2200 (128 tokens) mean 0.275, largest 1.077; a probe of 3000
  0.231 / 0.572: 1.077 is the largest of 1100 tokens, and the upper tail
  falls tenfold in about 0.26;
- **the KDA state in bfloat16** (``build(lower=("state",))``: this
  reference with the state rounded to bfloat16 from each token to the
  next, the precision below the float32 the configuration states for it,
  in the served program's place on a serving's probes, three seeds): the
  harness's four probes together mean 0.387, 0.500, 0.547 and largest
  1.75, 2.48, 2.31 (each at the probe of 2200 tokens, which alone reads
  0.78-1.13 / 1.75-2.48; a probe of 3000 alone 0.92-0.99 / 1.99-2.53; the
  short probes alone 0.14-0.58 / 0.31-1.41, where rounding has had a few
  hundred tokens to add up and reads as the trunk's own): not correct by
  both limits at each of the three seeds, by 1.08 x and 1.09 x at the
  nearest;
- **the three wrong programs**, the same way (``lower=("scalar_decay",)``,
  ``("rope_on_mla",)``, ``("beta_one",)``, two seeds): the decay one
  scalar a head mean 8.6-9.3 and largest 15.0-17.7; a rotary term on the
  latent layers' 64-wide parts 3.95-4.10 / 9.7-11.0; ``β ≡ 1`` 11.2-11.7
  / 17.7-18.8: another model, by two orders of magnitude;
- ``LOGPROB_MEAN_ATOL`` 0.36: 1.25 x the largest sound mean of a run
  (0.289) and 3.7 deviations of a run's mean (0.03 between seeds) over
  their middle (0.25); 1.08 x under the smallest mean of the bfloat16
  state (0.387) and 1.4-1.5 x under the other two;
- ``LOGPROB_ATOL`` 1.6 on a single token: 1.49 x the largest of 1100
  sound tokens (1.077: by the tail's fall a token passes 1.6 once in 10^5)
  and 1.09 x under the smallest largest of the bfloat16 state (1.75),
  1.4-1.55 x under the other two.

**What these limits can and cannot tell apart.** A wrong recurrence, a
positional term or a write strength of one are told apart by a factor of
ten and more. **The state's precision is told apart with little room,
and only as the control makes it**: the control rounds the state 2216
times in the long probe, once a token through the prompt. A *served*
program with its records in bfloat16 (``scripts/long_probes.py --fault
bf16_state``) rounds them once a prefill step and once a decoded token,
19 times in the same probe (the chunked scan carries the state in
float32 across a step's 1024 tokens), and the harness's probes decode 16
tokens: such a program read 0.218 mean and 0.663 largest over the
harness's four probes and 0.168 / 0.539 at 3000 tokens, every probe
inside the limits, as the sound one does (the three wrong programs
served the same way: 10.1 / 18.1, 3.78 / 11.9 and 11.6 / 18.8; PERF.md
section 6), and would part from it only over thousands of
decoded tokens, which no probe of the harness has (PERF.md section 7,
"Left by PR 37" and "Left by PR 52": a `benchmark` PR's). Router scores
from a bfloat16 product and latent pages in fp8 move less than the
trunk's own rounding, as every expert configuration before this one read
(PR 26, PR 40, PR 48); the float32 comparison of tier-1 on the CPU is
what holds those.

In float32 on the CPU the served path agrees with this file to 4e-5 in
log-probability at a tiny shape through chunked prefill, decode, an idle
row and a resumed one (``tests/test_kimi_linear_reference.py``, limit
3e-4; the wrong programs there, a bfloat16 state, a scalar decay, a
rotary term, ``β ≡ 1``, gates not renormalised, no routed scaling and a
bfloat16 router, read over 3e-3), so what the chip shows is rounding.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens (PERF.md section 6, PR 52)
LOGPROB_ATOL = 1.6
LOGPROB_MEAN_ATOL = 0.36

HEAD_SLICES = 16    # the head a sixteenth of the vocabulary at a time
QUERY_BLOCK = 128   # queries of a latent layer computed together
L2_EPS = 1e-6

KDA, MLA = "kda", "mla"
# what ``build(lower=...)`` can compute below what the configuration
# states, or wrongly: the KDA state held in bfloat16 from token to token;
# the decay one scalar a head (the channel mean of g); a rotary term on
# the latent layers' 64-wide parts; β ≡ 1
CONTROLS = ("state", "scalar_decay", "rope_on_mla", "beta_one")


def _refuse_a_program_without_the_family() -> None:
    """A program without the family refuses the published keys itself,
    but only after the harness has written a model directory and started
    the engine. This module is imported before anything is built
    (``run.py``), so such a program is refused here, at once, as
    ``references/granite_hybrid.py`` does. The configuration's fields
    are all that is read of the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if not {"kda_num_heads", "experts_of"} <= fields:
        raise ImportError(
            "this program has no trunk of Kimi Delta Attention and latent "
            "layers under routed experts held as one rank's share "
            "(ModelConfig has no kda_num_heads / experts_of): it cannot "
            "serve model_type kimi_linear, and references/kimi_linear.py "
            "has nothing to compare it with")


_refuse_a_program_without_the_family()


def layer_kinds(hf: dict):
    """[kind] of layers 1..L from the two published 1-based lists."""
    lin = hf["linear_attn_config"]
    layers = int(hf["num_hidden_layers"])
    kda, full = list(lin["kda_layers"]), list(lin["full_attn_layers"])
    if sorted(kda + full) != list(range(1, layers + 1)):
        raise ValueError(f"kda_layers {kda} and full_attn_layers {full} do "
                         f"not name each of the layers 1 to {layers} once")
    return [KDA if i in kda else MLA for i in range(1, layers + 1)]


def _swiglu(x, wg, wu, wd):
    import jax

    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def expert_layer(hf: dict):
    """``fn(m [T, D], layer's arrays) -> (routed, shared)``: the part of
    the routed sum that the experts held give (all of it where the
    configuration states no share) and the shared expert, each ``[T, D]``
    float32. A layer adds ``routed + shared``."""
    import jax
    import jax.numpy as jnp

    top_k = int(hf["num_experts_per_token"])
    held = int(hf["num_experts"])
    share = hf.get("expert_share") or {}
    first = int(share.get("rank", 0)) * held     # the first expert held
    scaling = float(hf.get("routed_scaling_factor", 1.0))
    renorm = bool(hf.get("moe_renormalize", True))
    f32 = jnp.float32

    def experts(m, lp):
        s = jax.nn.sigmoid(m @ lp["router"].astype(f32))             # [T, E]
        _, picked = jax.lax.top_k(s + lp["router_bias"].astype(f32), top_k)
        w = jnp.take_along_axis(s, picked, axis=1)    # the unbiased scores
        if renorm:
            w = w / jnp.sum(w, axis=1, keepdims=True)
        gate = jnp.zeros_like(s).at[
            jnp.arange(m.shape[0])[:, None], picked].set(w * scaling)
        # the experts held, one at a time; a pick of an absent one adds nothing
        mine = jax.lax.dynamic_slice_in_dim(gate, first, held, axis=1)

        def one_expert(y, ew):   # one expert's weights to float32 at a time
            w_e, wg, wu, wd = ew
            return y + w_e[:, None] * _swiglu(
                m, wg.astype(f32), wu.astype(f32), wd.astype(f32)), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                            (mine.T, lp["w_gate"], lp["w_up"], lp["w_down"]))
        return y, _swiglu(m, lp["w_sh_gate"].astype(f32),
                          lp["w_sh_up"].astype(f32), lp["w_sh_down"].astype(f32))

    return experts


def build(hf: dict, t_pad: int, n_out: int, lower=()):
    """``fn(params, tokens[t_pad], out_positions[n_out]) -> log-probs
    [n_out, V]``, a jitted function a kind of layer called layer by
    layer.

    ``lower`` names what is computed below what the configuration
    states, or wrongly, everything else as it is (``CONTROLS``): the
    controls the limits were set against; the comparison that decides
    ``correct`` builds with none. (``lax.reduce_precision`` and not a
    cast there and back: the chip's compiler is allowed excess precision
    and removes the pair.)"""
    import jax
    import jax.numpy as jnp

    if set(lower) - set(CONTROLS):
        raise ValueError(f"lower={lower!r}: of {CONTROLS}")
    if hf.get("model_type") != "kimi_linear":
        raise NotImplementedError("the reference of model_type kimi_linear")
    if hf.get("mla_use_nope") is not True:
        raise NotImplementedError("the reference has no mla_use_nope false")
    for key, only in (("q_lora_rank", None), ("rope_scaling", None),
                      ("num_expert_group", 1), ("num_nextn_predict_layers", 0),
                      ("moe_layer_freq", 1), ("hidden_act", "silu"),
                      ("moe_router_activation_func", "sigmoid")):
        if (hf.get(key, only) or None) != (only or None):
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    kinds = layer_kinds(hf)
    lin = hf["linear_attn_config"]
    kh, kd = int(lin["num_heads"]), int(lin["head_dim"])
    taps = int(lin.get("short_conv_kernel_size", 4))
    hk = kh * kd
    heads = int(hf["num_attention_heads"])
    r, nope, rope, vd = (int(hf[k]) for k in (
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    theta = float(hf.get("rope_theta", 10000.0))
    eps = float(hf.get("rms_norm_eps", 1e-5))
    n_dense = int(hf.get("first_k_dense_replace", 0))
    f32 = jnp.float32
    qb = QUERY_BLOCK if t_pad % QUERY_BLOCK == 0 else t_pad
    pos = jnp.arange(t_pad)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    def kda(n, w):
        qkv = n @ w["w_qkv"]
        # causal depthwise conv: tap t meets the input taps - 1 - t back
        xp = jnp.concatenate([jnp.zeros((taps - 1, 3 * hk), f32), qkv], 0)
        qkv = jax.nn.silu(sum(xp[t:t + t_pad] * w["conv_w"][t]
                              for t in range(taps)))
        q, k, v = (qkv[:, i * hk:(i + 1) * hk].reshape(t_pad, kh, kd)
                   for i in range(3))
        q, k = l2(q) * kd ** -0.5, l2(k)
        g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
            (n @ w["w_fa"]) @ w["w_fb"] + w["dt_bias"]).reshape(t_pad, kh, kd)
        beta = jax.nn.sigmoid(n @ w["w_b"])                       # [T, H]
        if "scalar_decay" in lower:   # one decay a head: the channels' mean
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        if "beta_one" in lower:
            beta = jnp.ones_like(beta)

        def token(s, inp):   # the recurrence, one token; s [H, K, V]
            q_t, k_t, v_t, g_t, b_t = inp
            s_bar = jnp.exp(g_t)[:, :, None] * s
            err = v_t - jnp.einsum("hkv,hk->hv", s_bar, k_t)
            s = s_bar + b_t[:, None, None] * k_t[:, :, None] * err[:, None, :]
            if "state" in lower:   # kept in bfloat16 between two tokens
                s = jax.lax.reduce_precision(s, 8, 7)
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((kh, kd, kd), f32),
                            (q, k, v, g, beta))
        gate = jax.nn.sigmoid((n @ w["w_ga"]) @ w["w_gb"])
        return (rms(o, w["o_norm"]).reshape(t_pad, hk) * gate) @ w["wo"]

    def rotated(x, at):   # the rotary embedding a wrong program would add
        half = x.shape[-1] // 2
        freq = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = at.astype(f32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def mla(n, w):
        q = (n @ w["wq"]).reshape(t_pad, heads, nope + rope)
        c = rms(n @ w["w_dkv"], w["ln_kv"])                       # [T, r]
        k_r = n @ w["w_kr"]                                       # [T, rope]
        if "rope_on_mla" in lower:
            q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:], pos)], -1)
            k_r = rotated(k_r, pos)
        k = jnp.concatenate([
            jnp.einsum("tr,rhn->thn", c, w["w_uk"]),
            jnp.broadcast_to(k_r[:, None, :], (t_pad, heads, rope))], -1)
        v = jnp.einsum("tr,rhv->thv", c, w["w_uv"])
        scale = (nope + rope) ** -0.5

        def block(args):   # a block of queries: q_b [qb, H, d], i_b [qb]
            q_b, i_b = args
            mask = pos[None, :] <= i_b[:, None]                   # j <= i
            s = jnp.einsum("qhd,thd->hqt", q_b, k) * scale
            s = jnp.where(mask[None], s, -jnp.inf)
            return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, (q.reshape(t_pad // qb, qb, heads, nope + rope),
                                pos.reshape(t_pad // qb, qb)))
        return o.reshape(t_pad, heads * vd) @ w["wo"]

    experts = expert_layer(hf)
    mixers = {KDA: kda, MLA: mla}

    def jitted(fn):
        def under_highest(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(under_highest)

    def mixer_layer(kind):
        def layer(x, lp):
            w = {k: v.astype(f32) for k, v in lp.items()}
            return x + mixers[kind](rms(x, w["ln1"]), w)
        return jitted(layer)

    @jitted
    def dense_layer(x, lp):
        w = {k: v.astype(f32) for k, v in lp.items()}
        return x + _swiglu(rms(x, w["ln2"]), w["w_gate"], w["w_up"], w["w_down"])

    @jitted
    def experts_behind(x, lp):
        routed, shared = experts(rms(x, lp["ln2"].astype(f32)), lp)
        return x + routed + shared

    @jitted
    def head(x, out_positions, final_norm, lm_head):
        x = rms(x[out_positions], final_norm.astype(f32))
        vocab = lm_head.shape[1]
        parts = HEAD_SLICES if vocab % HEAD_SLICES == 0 else 1
        width = vocab // parts

        def one(i):
            cols = jax.lax.dynamic_slice_in_dim(lm_head, i * width, width, 1)
            return x @ cols.astype(f32)

        logits = jax.lax.map(one, jnp.arange(parts)).transpose(1, 0, 2)
        return jax.nn.log_softmax(logits.reshape(x.shape[0], vocab), axis=-1)

    mixer_of = {kind: mixer_layer(kind) for kind in set(kinds)}

    def at(stack, i):
        return {k: v[i] for k, v in stack.items()}

    def forward(params, tokens, out_positions):
        x = params["embed"][tokens].astype(f32)
        seen = {KDA: 0, MLA: 0}
        for l, kind in enumerate(kinds):
            x = mixer_of[kind](x, at(params[kind], seen[kind]))
            seen[kind] += 1
            x = (dense_layer(x, at(params["dense"], l)) if l < n_dense
                 else experts_behind(x, at(params["moe"], l - n_dense)))
        return head(x, out_positions, params["final_norm"], params["lm_head"])

    return forward
