"""The plain reference of the Falcon-H1 family (``model_type:
falcon_h1``; Falcon-H1-34B-Instruct is one): a float32 forward as
published.

Every layer runs two token mixers **in parallel on the same normed
input** and adds both to the residual. With ``n1 = RMSNorm(x)``
(``input_layernorm``):

- **Mamba-2 mixer.** ``u = in_proj(n1 · ssm_in_multiplier)``, times the
  µP vector that holds ``ssm_multipliers[0..4]`` over its parts ``[z |
  x | B | C | dt]``. ``x‖B‖C`` goes through a causal depthwise conv of
  width ``mamba_d_conv`` with bias, then SiLU. ``Δ = softplus(dt +
  dt_bias)``, ``A = −exp(A_log)`` a head. Per head, state ``h ∈
  R^{P×N}``: ``h_t = exp(Δ_t A) h_{t−1} + Δ_t x_t ⊗ B_t``, ``y_t = h_t
  C_t + D x_t`` (head ``h`` uses group ``h // (H / G)`` of ``B``, ``C``).
  Then ``y ← GroupedRMSNorm(y · SiLU(z))`` (``mamba_norm_before_gate``
  false: the gate first; the norm over each of the ``G`` groups of
  ``d_ssm / G``, one learned weight of ``d_ssm``); ``m = out_proj(y) ·
  ssm_out_multiplier``.
- **Attention.** ``q, k, v`` from ``n1 · attention_in_multiplier``, no
  bias; ``k ← k · key_multiplier`` before the rotary embedding; causal
  grouped-query attention, scale ``head_dim^-½``; ``a = o_proj(·) ·
  attention_out_multiplier``.
- ``x ← x + m + a``; ``n2 = RMSNorm(x)`` (``pre_ff_layernorm``); ``x ← x +
  down(up(n2) · SiLU(gate(n2) · mlp_multipliers[0])) · mlp_multipliers[1]``.
- Embeddings × ``embedding_multiplier``; final RMSNorm; logits =
  ``lm_head(·) · lm_head_multiplier``.

The recurrence **is the recurrence**: one token at a time through
``lax.scan``, from a zero state. (The served program computes a prefill
chunk in the chunked state-space-duality form, matrix products over
chunks of ``mamba_chunk_size``, and decodes through a state it keeps by
slot: algebraically the same, computed another way, so the two are
independent.)

Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernels, no
batching, nothing imported from ``dynamo_tpu.models`` or
``dynamo_tpu.ops``. It reads the engine's parameter arrays, because the
weights are data (random, from the seed): ``{"embed", "layers": {ln1, wq,
wk, wv, wo, ssm_in [D, 2·d_ssm + 2·G·N + H], conv_w [K, C], conv_b [C],
dt_bias [H], A_log [H], D [H], ssm_norm [d_ssm], ssm_out [d_ssm, D], ln2,
w_gate, w_up, w_down}, "final_norm", "lm_head"}``, stacked over the
layers, ``x @ w``. So that it fits beside the served model on the chip
(13 GB of 16), the feed-forward and the head go to float32 a slice at a
time.

Departures from the published code, each one a matter of layout and not
of arithmetic:

- the rotary embedding is the engine's half rotation (pairs ``(i, i +
  d/2)``), which is also what the published Falcon-H1 code does;
- ``mamba_use_mlp: true`` is taken to name the feed-forward the block
  has anyway;
- the published code clamps ``Δ`` to ``time_step_limit = (0, ∞)``:
  softplus is positive, so the clamp does nothing and is left out;
- a scaled rotary embedding, ``mamba_norm_before_gate: true``,
  ``mamba_rms_norm: false`` and any bias but the conv's are refused, not
  approximated.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced, 64 tokens a run (four probes of 16 greedy
tokens). The served path computes in bfloat16 (weights, activations,
pages, conv window) with a float32 SSM state; the reference takes the
same bfloat16 weights to float32. Measured on the v5e at the published
widths (the configuration that names this module, 6 layers) through the
benchmark's own cell at its own rate (PR 31; PERF.md section 6), the
random weights drawn for attention scores of standard deviation 3.0:

- the served program, seven runs, seven seeds: a run's mean difference
  0.0231-0.0331, its largest 0.081-0.128;
- the same program with its pages in fp8 (``kv_cache_dtype fp8``, the
  precision below the bfloat16 the configuration states for them), two
  seeds: mean 0.0943 and 0.1165, largest 0.383 and 0.387.

``LOGPROB_MEAN_ATOL`` 0.06 lies 1.8 x over the largest sound mean and
1.6 x under the smallest fp8 mean; ``LOGPROB_ATOL`` 0.25 lies 1.95 x
over the largest sound difference and 1.5 x under the smallest fp8 one:
an fp8 cache fails both, every sound run passes both with the more room
on the sound side, since fresh seeds read higher. In float32 on the CPU
the served path agrees with this file to 3e-5 at a tiny shape
(``tests/test_falcon_h1_reference.py``, limit 1e-3), so all of the
difference on the chip is rounding and none of it arithmetic.

What these limits do not see: the SSM state kept in bfloat16 (the
precision below the float32 the program keeps it in) reads as the sound
program does (mean 0.0258, largest 0.074, one run), because a probe
decodes 16 tokens and the state's rounding needs hundreds of steps to
add up past the bfloat16 activations' own; the float32 comparison of the
tests, over 40 decode steps, is what holds the state's precision. With
plain fan-in query weights (attention scores of standard deviation 1.0,
attention spread thinly over every key) the limits could not see the
fp8 pages either: sound 0.0182-0.0211 / 0.057-0.094 over nine runs, fp8
0.0218 / 0.059; that is why the scores are drawn wider
(``models/falcon_h1.py`` ``ATTN_SCORE_STD``).
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens: see the module docstring
LOGPROB_ATOL = 0.25
LOGPROB_MEAN_ATOL = 0.06

MLP_SLICES = 4     # the feed-forward goes to float32 a quarter at a time
HEAD_SLICES = 32   # the head a thirty-second of the vocabulary at a time


def build(hf: dict, t_pad: int, n_out: int):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V]."""
    import jax
    import jax.numpy as jnp

    for key, only in (("mamba_rms_norm", True), ("mamba_norm_before_gate", False),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("projectors_bias", False), ("rope_scaling", None)):
        if hf.get(key, only) != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    n_heads, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or int(hf["hidden_size"]) // n_heads)
    theta = float(hf.get("rope_theta", 10000.0))
    eps = float(hf.get("rms_norm_eps", 1e-5))
    d_ssm, mh, mp = (int(hf["mamba_d_ssm"]), int(hf["mamba_n_heads"]),
                     int(hf["mamba_d_head"]))
    n, g, kc = (int(hf["mamba_d_state"]), int(hf["mamba_n_groups"]),
                int(hf["mamba_d_conv"]))
    m = {k: float(hf.get(k, 1.0)) for k in (
        "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier")}
    ssm_m = [float(v) for v in hf.get("ssm_multipliers", [1.0] * 5)]
    gate_m, down_m = (float(v) for v in hf.get("mlp_multipliers", [1.0, 1.0]))
    f32 = jnp.float32
    widths = (d_ssm, d_ssm, g * n, g * n, mh)
    mup = jnp.concatenate([jnp.full((w,), v, f32) for w, v in zip(widths, ssm_m)])

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    pos = jnp.arange(t_pad)

    def rope(x):   # x [T, H, hd], half rotation
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd)
        ang = pos[:, None].astype(f32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    mask = pos[None, :] <= pos[:, None]                   # causal [q, k]

    def one_head(qkv):   # q, k, v [T, hd]: one query head at a time
        q, k, v = qkv
        p = jax.nn.softmax(jnp.where(mask, (q @ k.T) * hd ** -0.5, -jnp.inf), -1)
        return p @ v

    def attention(n1, w):
        x = n1 * m["attention_in_multiplier"]
        q = rope((x @ w["wq"]).reshape(t_pad, n_heads, hd))
        k = rope((x @ w["wk"]).reshape(t_pad, n_kv, hd) * m["key_multiplier"])
        v = (x @ w["wv"]).reshape(t_pad, n_kv, hd)
        rep = n_heads // n_kv          # query head h reads kv head h // rep
        o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                   jnp.repeat(k, rep, axis=1).transpose(1, 0, 2),
                                   jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)))
        return (o.transpose(1, 0, 2).reshape(t_pad, -1) @ w["wo"]
                ) * m["attention_out_multiplier"]

    def mixer(n1, w):
        u = ((n1 * m["ssm_in_multiplier"]) @ w["ssm_in"]) * mup
        z, xbc, dt = (u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * g * n],
                      u[:, 2 * d_ssm + 2 * g * n:])
        # causal depthwise conv: tap k meets the input K − 1 − k tokens back
        xp = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), f32), xbc], 0)
        xbc = jax.nn.silu(sum(xp[k:k + t_pad] * w["conv_w"][k] for k in range(kc))
                          + w["conv_b"])
        x = xbc[:, :d_ssm].reshape(t_pad, mh, mp)
        bm = jnp.repeat(xbc[:, d_ssm:d_ssm + g * n].reshape(t_pad, g, n),
                        mh // g, axis=1)                              # [T, H, N]
        cm = jnp.repeat(xbc[:, d_ssm + g * n:].reshape(t_pad, g, n),
                        mh // g, axis=1)
        delta = jax.nn.softplus(dt + w["dt_bias"])                    # [T, H]
        a = -jnp.exp(w["A_log"])                                      # [H]

        def token(h, inp):   # the recurrence, one token
            x_t, b_t, c_t, d_t = inp
            h = (jnp.exp(d_t * a)[:, None, None] * h
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return h, jnp.einsum("hpn,hn->hp", h, c_t) + w["D"][:, None] * x_t

        _, y = jax.lax.scan(token, jnp.zeros((mh, mp, n), f32),
                            (x, bm, cm, delta))
        y = y.reshape(t_pad, d_ssm) * jax.nn.silu(z)      # the gate, then the norm
        y = y.reshape(t_pad, g, d_ssm // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return ((y.reshape(t_pad, d_ssm) * w["ssm_norm"]) @ w["ssm_out"]
                ) * m["ssm_out_multiplier"]

    def mlp(n2, lp):   # a slice of the intermediate width at a time
        inter = lp["w_gate"].shape[1]
        parts = MLP_SLICES if inter % MLP_SLICES == 0 else 1
        width = inter // parts

        def one(y, i):
            wg, wu = (jax.lax.dynamic_slice_in_dim(lp[k], i * width, width, 1)
                      .astype(f32) for k in ("w_gate", "w_up"))
            wd = jax.lax.dynamic_slice_in_dim(lp["w_down"], i * width, width, 0)
            return y + (jax.nn.silu((n2 @ wg) * gate_m) * (n2 @ wu)) @ wd.astype(f32), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(n2), jnp.arange(parts))
        return y * down_m

    small = ("ln1", "wq", "wk", "wv", "wo", "ssm_in", "conv_w", "conv_b",
             "dt_bias", "A_log", "D", "ssm_norm", "ssm_out", "ln2")

    def layer(x, lp):
        w = {k: lp[k].astype(f32) for k in small}
        n1 = rms(x, w["ln1"])
        x = x + mixer(n1, w) + attention(n1, w)
        return x + mlp(rms(x, w["ln2"]), lp), None

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
            x = params["embed"][tokens].astype(f32) * m["embedding_multiplier"]
            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = rms(x[out_positions], params["final_norm"].astype(f32))
            head = params.get("lm_head")
            head = params["embed"].T if head is None else head
            return jax.nn.log_softmax(
                head_logits(x, head) * m["lm_head_multiplier"], axis=-1)

    return jax.jit(forward)
