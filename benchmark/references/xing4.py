"""The plain reference of ``model_type: xing4_0`` (Xing4.0-29B-A4B): a
float32 forward written from the layer's equations.

A DeepSeek-V3 layer (latent attention, then a dense SwiGLU in the first
``first_k_dense_replace`` layers and routed experts after) whose
residual path is **manifold-constrained hyper-connections** (mHC;
DeepSeek 2025, on the hyper-connections of Zhu et al. 2024): a token
carries ``n = hc_mult`` residual streams ``X [n, D]``, and each of a
layer's two sublayers ``F`` (``MLA(RMSNorm_ln1(.))``,
``MLP_or_MoE(RMSNorm_ln2(.))``) is wrapped, with parameters of its own:

    x^     = RMSNorm(vec(X); eps = hc_eps)          over all n D values
    H_pre  = sigmoid(a_pre  x^ phi_pre  + b_pre)    [n]
    H_post = 2 sigmoid(a_post x^ phi_post + b_post) [n]
    M      = exp(clip(a_res mat(x^ phi_res) + b_res, clamp_min, clamp_max))
    M      = rows(cols(M))  hc_sinkhorn_iters times [n, n]
    u = sum_i H_pre[i] X[i];  y = F(u);  X'[i] = sum_j M[i, j] X[j] + H_post[i] y

Here it is computed token by token in the layout of the equations
(``X [T, n, D]``, ``M [T, n, n]``, a Python loop of the iterations, the
mixes as einsums); the served program keeps tokens minor and never
forms ``x^`` (``dynamo_tpu/models/mhc.py``).

**Assumed**, where the published ``config.json`` fixes only ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps`` and the clamp (each also under
``assumed`` in the configuration's file):

- ``x^`` has no learned weight (the mHC paper's RMSNorm of the
  flattened streams feeds three linear maps, which would absorb one);
- one Sinkhorn iteration normalises the columns, then the rows, so the
  last step leaves every row summing to 1 (the paper's ``T_r(T_c(.))``);
- ``hc_eps`` is also added to each of those sums before the division;
- the streams start as ``n`` copies of the embedding and are summed
  after the last layer (hyper-connections paper);
- ``mat(.)`` is row-major: column ``i n + j`` of ``phi_res`` gives
  ``M[i, j]``, the weight of stream ``j`` in new stream ``i``;
- ``phi``, ``b`` and the three scalars are float32, and the engine holds
  a sublayer's ``phi_pre | phi_post | phi_res`` as one ``[n D, 2n + n^2]``
  matrix (``hc_<sub>_phi``), ``b`` likewise, ``alpha`` as ``[3]``.

**Attention.** The query goes through its bottleneck (``q = RMSNorm(x
W_dq) W_uq``), the rest is latent attention in its un-absorbed form as
in ``references/deepseek_v3.py``. **YaRN from the published formula**
(Peng et al. 2023, as DeepSeek-V3's modelling code applies it): with
``d`` the rotary width, ``dim(r) = d ln(L / (2 pi r)) / (2 ln theta)``
the dimension that turns ``r`` times over the original length ``L``,
``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` clamped
to ``[0, d - 1]``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``,

    inv_freq_i = (1 - ramp_i) theta^(-2i/d) + ramp_i theta^(-2i/d) / factor

so fast dimensions keep their frequency and slow ones are interpolated;
``m(s) = 0.1 s ln(factor) + 1``; cos and sin carry ``m(mscale) /
m(mscale_all_dim)`` and the softmax scale is ``(nope + rope)^-0.5
m(mscale_all_dim)^2``.

**Experts** as in ``references/deepseek_v3.py``: router logits in
float32, sigmoid scores, selection on ``scores + bias``, weights the
unbiased scores renormalised and times ``routed_scaling_factor``; every
expert on every token, one at a time; the shared expert always on; the
head in slices of the vocabulary.

Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernels, nothing
imported from ``dynamo_tpu.models`` or ``dynamo_tpu.ops``. It reads the
engine's parameter arrays (the weights are data, from the seed).

Departures from the published description, each of layout:

- the rotary embedding is the engine's half rotation; the published
  code rotates interleaved pairs after a permutation of the projections'
  columns, which the loader applies once (as ``deepseek_v3.py``);
- the multi-token-prediction module (``num_nextn_predict_layers`` 1) is
  not computed: the main model's logits do not depend on it;
- ``n_group > 1`` is refused, not approximated.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced, 64 tokens a run (four probes of 16 greedy
tokens). The served path computes in bfloat16 (weights, streams, latent
cache) with float32 mixing coefficients, router logits and attention
scores; this file takes the same bfloat16 weights to float32. Measured
on the v5e at the published widths (the configuration that names this
module, 7 layers) through the benchmark's own cell, 128 callers on 64
slots (PR 33; PERF.md section 6):

- the served program, twelve runs on twelve seeds (three of them
  traced): a run's mean difference 0.0452-0.0592, its largest
  0.150-0.297;
- the same program with its latent cache in fp8 (``kv_cache_dtype fp8``,
  the precision below the bfloat16 the configuration states for it), two
  seeds: mean 0.2150 and 0.2180, largest 0.888 and 0.682; both runs end
  ``correct: false``, by each limit.

``LOGPROB_MEAN_ATOL`` 0.11 lies 1.9 x over the largest sound mean and
2.0 x under the smaller fp8 mean; ``LOGPROB_ATOL`` 0.5 lies 1.7 x over
the largest sound difference and 1.4 x under the smaller fp8 one, with
the more room on the sound side, since fresh seeds read higher and one
token's difference is a flipped near-tie of the router where it is
large. The sound mean is higher than Moonlight's (0.023-0.035 under
``deepseek_v3.py``'s limits) because four bfloat16 streams are mixed
and rounded twice a layer. In float32 on the CPU the served path agrees
with this file to 8e-6 in log-probabilities at a tiny shape
(``tests/test_xing4_reference.py``, limit 1e-4), so all of the
difference on the chip is rounding and none of it arithmetic; what
moves 64 tokens' mean by less than 0.05 (the mixing tensors in
bfloat16, five iterations for twenty, no clamp, the static mapping
alone, no ``mscale`` on the softmax) is pinned by those tests in
float32.
"""

from __future__ import annotations

import math

# Limits of the comparison that decides ``correct`` (harness/reference.py):
# one token's log-probability, and the mean over a run's probe tokens.
# Each lies between two chip readings of the benchmark's own cell: see
# the module docstring ("Tolerance").
LOGPROB_ATOL = 0.5
LOGPROB_MEAN_ATOL = 0.11

HEAD_SLICES = 16   # the head goes to float32 a sixteenth of the vocabulary at a time
SUBLAYERS = ("attn", "mlp")


def _refuse_a_program_with_one_stream() -> None:
    """A program whose ``ModelConfig`` has no ``hc_mult`` takes the
    published keys for DeepSeek-V3's, builds 11 GB of weights and serves
    the checkpoint through a plain residual path: wrong tokens after
    minutes of set-up. This module is imported before anything is built
    (``run.py``), so such a program is refused here, in seconds. The
    configuration's fields are all that is read of the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    if "hc_mult" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise ImportError(
            "this program has no mixed residual streams (ModelConfig has no "
            "hc_mult): it cannot serve model_type xing4_0, and "
            "references/xing4.py has nothing to compare it with")


_refuse_a_program_with_one_stream()


def yarn_inv_freq(d: int, theta: float, sc: dict):
    """The rotary inverse frequencies [d / 2] (a list of floats) under a
    ``rope_scaling`` of type yarn, from the published formula."""
    factor = float(sc["factor"])
    length = float(sc["original_max_position_embeddings"])

    def dim_of(rotations: float) -> float:
        return d * math.log(length / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(sc.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(sc.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(d // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freq = theta ** (-2.0 * i / d)
        out.append((1.0 - ramp) * freq + ramp * freq / factor)
    return out


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def build(hf: dict, t_pad: int, n_out: int):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V]."""
    import jax
    import jax.numpy as jnp

    n_heads = int(hf["num_attention_heads"])
    nope, rope_d = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    theta = float(hf.get("rope_theta", 10000.0))
    eps = float(hf.get("rms_norm_eps", 1e-6))
    top_k = int(hf["num_experts_per_tok"])
    scaling = float(hf.get("routed_scaling_factor", 1.0))
    norm_topk = bool(hf.get("norm_topk_prob", True))
    scoring = hf.get("scoring_func", "softmax")
    n = int(hf["hc_mult"])
    iters = int(hf["hc_sinkhorn_iters"])
    hc_eps = float(hf["hc_eps"])
    clamp = (float(hf["mhc_h_res_clamp_min"]), float(hf["mhc_h_res_clamp_max"]))
    if not hf.get("q_lora_rank"):
        raise NotImplementedError("xing4_0 routes its query through a bottleneck")
    if int(hf.get("n_group") or 1) > 1:
        raise NotImplementedError("the reference has no group-limited routing")
    if scoring not in ("sigmoid", "softmax"):
        raise NotImplementedError(f"scoring_func {scoring!r}")
    f32 = jnp.float32

    sc = hf.get("rope_scaling") or None
    scale = (nope + rope_d) ** -0.5
    rot_scale = 1.0
    if sc is None:
        inv_freq = [theta ** (-2.0 * i / rope_d) for i in range(rope_d // 2)]
    elif sc.get("type", sc.get("rope_type")) == "yarn":
        inv_freq = yarn_inv_freq(rope_d, theta, sc)
        factor = float(sc["factor"])
        m_all = yarn_mscale(factor, float(sc.get("mscale_all_dim") or 0.0))
        rot_scale = yarn_mscale(factor, float(sc.get("mscale") or 1.0)) / m_all
        scale = scale * m_all * m_all
    else:
        raise NotImplementedError(f"rope_scaling {sc!r}")

    def rms(x, w, e=eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + e) * w

    pos = jnp.arange(t_pad)
    ang = pos[:, None].astype(f32) * jnp.asarray(inv_freq, f32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * rot_scale, jnp.sin(ang)[:, None, :] * rot_scale

    def rope(x):   # x [T, H, rope_d], half rotation
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    mask = pos[None, :] <= pos[:, None]                   # causal [q, k]

    def one_head(qkv):   # q, k [T, nope + rope_d], v [T, v]: one head at a time
        q, k, v = qkv
        p = jax.nn.softmax(jnp.where(mask, (q @ k.T) * scale, -jnp.inf), axis=-1)
        return p @ v

    def attention(u, w):   # u [T, D] -> the attention sublayer's output
        h = rms(u, w["ln1"])
        q = (rms(h @ w["w_dq"], w["ln_q"]) @ w["w_uq"]).reshape(
            t_pad, n_heads, nope + rope_d)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], -1)
        c_kv = rms(h @ w["w_dkv"], w["ln_kv"])                       # [T, r]
        k_rope = rope((h @ w["w_kr"])[:, None, :])                   # [T, 1, rope_d]
        k_nope = jnp.einsum("tr,rhn->thn", c_kv, w["w_uk"])
        v = jnp.einsum("tr,rhv->thv", c_kv, w["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (t_pad, n_heads, rope_d))], -1)
        o = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                   v.transpose(1, 0, 2)))            # [H, T, v]
        return o.transpose(1, 0, 2).reshape(t_pad, -1) @ w["wo"]

    def swiglu(h, w_gate, w_up, w_down):
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    def wrapped(x, lp, sub, fn):
        """One sublayer ``fn`` around the streams x [T, n, D]."""
        phi = lp[f"hc_{sub}_phi"].astype(f32)            # [n D, 2n + n^2]
        bias = lp[f"hc_{sub}_b"].astype(f32)
        a_pre, a_post, a_res = (lp[f"hc_{sub}_alpha"].astype(f32)[i] for i in range(3))
        xh = rms(x.reshape(t_pad, -1), 1.0, hc_eps)      # x^ [T, n D]
        z = xh @ phi
        h_pre = jax.nn.sigmoid(a_pre * z[:, :n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(a_post * z[:, n:2 * n] + bias[n:2 * n])
        logits = a_res * z[:, 2 * n:] + bias[2 * n:]
        m = jnp.exp(jnp.clip(logits, *clamp)).reshape(t_pad, n, n)
        for _ in range(iters):
            m = m / (m.sum(axis=1, keepdims=True) + hc_eps)   # each column by its sum
            m = m / (m.sum(axis=2, keepdims=True) + hc_eps)   # each row by its sum
        u = jnp.einsum("ti,tid->td", h_pre, x)
        y = fn(u)
        return jnp.einsum("tij,tjd->tid", m, x) + h_post[:, :, None] * y[:, None, :]

    attn_keys = ("ln1", "w_dq", "ln_q", "w_uq", "w_dkv", "ln_kv", "w_kr", "w_uk",
                 "w_uv", "wo", "ln2")

    def to_f32(lp, keys):
        return {k: lp[k].astype(f32) for k in keys}

    def routed(h, w, lp, shared):
        logits = h @ w["router"]                                     # [T, E]
        scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        select = scores
        if "router_bias" in lp:
            select = scores + lp["router_bias"].astype(f32)[None, :]
        _, chosen = jax.lax.top_k(select, top_k)                     # [T, k]
        gate = jnp.zeros_like(scores).at[jnp.arange(t_pad)[:, None], chosen].set(
            jnp.take_along_axis(scores, chosen, axis=1))             # [T, E]
        if norm_topk:
            gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
        gate = gate * scaling

        def one_expert(y, ew):   # one expert's weights to float32 at a time
            g, wg, wu, wd = ew
            return y + g[:, None] * swiglu(h, wg.astype(f32), wu.astype(f32),
                                           wd.astype(f32)), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                            (gate.T, lp["w_gate"], lp["w_up"], lp["w_down"]))
        if shared:
            y = y + swiglu(h, w["w_sh_gate"], w["w_sh_up"], w["w_sh_down"])
        return y

    def dense_layer(x, lp):
        w = to_f32(lp, attn_keys + ("w_gate", "w_up", "w_down"))
        x = wrapped(x, lp, "attn", lambda u: attention(u, w))
        x = wrapped(x, lp, "mlp", lambda u: swiglu(
            rms(u, w["ln2"]), w["w_gate"], w["w_up"], w["w_down"]))
        return x, None

    def moe_layer(x, lp):
        shared = tuple(k for k in ("w_sh_gate", "w_sh_up", "w_sh_down") if k in lp)
        w = to_f32(lp, attn_keys + ("router",) + shared)
        x = wrapped(x, lp, "attn", lambda u: attention(u, w))
        x = wrapped(x, lp, "mlp", lambda u: routed(rms(u, w["ln2"]), w, lp, shared))
        return x, None

    def head_logits(x, head):   # [n, D] x [D, V] in slices of the vocabulary
        vocab = head.shape[1]
        k = HEAD_SLICES if vocab % HEAD_SLICES == 0 else 1
        width = vocab // k

        def one(i):
            cols = jax.lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
            return x @ cols.astype(f32)

        return jax.lax.map(one, jnp.arange(k)).transpose(1, 0, 2).reshape(
            x.shape[0], vocab)

    def forward(params, tokens, out_positions):
        with jax.default_matmul_precision("highest"):
            emb = params["embed"][tokens].astype(f32)                # [T, D]
            x = jnp.broadcast_to(emb[:, None, :], (t_pad, n, emb.shape[-1]))
            if "dense_layers" in params:
                x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
            if "layers" in params:
                x, _ = jax.lax.scan(moe_layer, x, params["layers"])
            h = x.sum(axis=1)
            h = rms(h[out_positions], params["final_norm"].astype(f32))
            head = params.get("lm_head")
            head = params["embed"].T if head is None else head
            return jax.nn.log_softmax(head_logits(h, head), axis=-1)

    return jax.jit(forward)
