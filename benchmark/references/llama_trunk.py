"""The plain reference of the Llama-style trunk: a float32 forward as
published.

RMSNorm, rotary embedding (half rotation), multi-head or grouped-query
attention with an optional whole-model sliding window, SwiGLU, untied
head. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernels, no
batching, and nothing imported from ``dynamo_tpu.models`` or
``dynamo_tpu.ops``. It reads the engine's parameter arrays, because the
weights are data: random, from the seed, in the layout
``{"embed", "layers": {ln1, wq, wk, wv, wo, ln2, w_gate, w_up, w_down}``
(stacked over layers, ``x @ w``)``, "final_norm", "lm_head"}``.

One file serves every configuration of this trunk: the sizes come from
the configuration's published keys.

**Tolerance.** The served path computes in bfloat16 (weights, activations
and cache); the reference takes the same bfloat16 weights up to float32
and keeps float32 throughout. What is compared is the log-probability of
each returned token, teacher-forced, 64 tokens a run. Measured on the
v5e over about 35 runs of both configurations (PR 22): the mean
difference of a run 0.0106-0.0162, the largest difference 0.031-0.062.

- ``LOGPROB_MEAN_ATOL`` 0.03, about twice the largest mean measured, is
  the limit that tells precisions apart: rounding noise averages out
  over a run's tokens, a coarser format is a bias that does not.
- ``LOGPROB_ATOL`` 0.15 on a single token, about two and a half times
  the largest difference measured, is there for gross faults (a wrong
  mask, position or block): one token in a few thousand lies in the
  tail, and a run must not fail on it.

What the mean limit discriminates was measured once, on the CPU (the
program's XLA route, bfloat16, this trunk at Phi-3-mini's widths cut to
4 and to 8 layers, the same probes, two seeds each; PR 22, not a chip
number). With the bfloat16 cache the mean was 0.0114-0.0128 and the
largest difference 0.035-0.053: the chip's scale, and no larger at 8
layers than at 4. With ``--kv-cache-dtype fp8`` the mean was
0.049-0.056 and the largest 0.154-0.176: four times as much, over the
mean's limit by 1.6 to 1.9 times in every run and over the single
token's by a hair. An 8-bit cache on the chip has not been
through the probes: PERF.md lists it. 8-bit weights were
not tried: the reference reads the engine's arrays, and quantised ones
are not the seed's.
"""

from __future__ import annotations

import math

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens: see the module docstring
LOGPROB_ATOL = 0.15
LOGPROB_MEAN_ATOL = 0.03


def build(hf: dict, t_pad: int, n_out: int):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V]."""
    import jax
    import jax.numpy as jnp

    n_heads = int(hf["num_attention_heads"])
    n_kv = int(hf.get("num_key_value_heads", n_heads))
    d_head = int(hf.get("head_dim") or hf["hidden_size"] // n_heads)
    theta = float(hf.get("rope_theta", 10000.0))
    eps = float(hf.get("rms_norm_eps", 1e-5))
    window = int(hf.get("sliding_window") or 0)
    if hf.get("rope_scaling"):
        raise NotImplementedError("the reference has no scaled rotary embedding")
    f32 = jnp.float32

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):   # x [T, H, D]
        inv = 1.0 / theta ** (jnp.arange(0, d_head, 2, dtype=f32) / d_head)
        ang = pos[:, None].astype(f32) * inv              # [T, D/2]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : d_head // 2], x[..., d_head // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    pos = jnp.arange(t_pad)
    mask = pos[None, :] <= pos[:, None]                   # causal [q, k]
    if window:
        mask &= pos[None, :] > pos[:, None] - window      # the last `window` keys

    def one_head(qkv):   # [T, D] each; one head at a time bounds the scores
        q, k, v = qkv
        s = (q @ k.T) / math.sqrt(d_head)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return p @ v

    def layer(x, lp):
        w = jax.tree.map(lambda a: a.astype(f32), lp)
        h = rms(x, w["ln1"])
        q = rope((h @ w["wq"]).reshape(t_pad, n_heads, d_head), pos)
        k = rope((h @ w["wk"]).reshape(t_pad, n_kv, d_head), pos)
        v = (h @ w["wv"]).reshape(t_pad, n_kv, d_head)
        rep = n_heads // n_kv
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        o = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                   v.transpose(1, 0, 2)))  # [H, T, D]
        x = x + o.transpose(1, 0, 2).reshape(t_pad, n_heads * d_head) @ w["wo"]
        h = rms(x, w["ln2"])
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return x, None

    def forward(params, tokens, out_positions):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(f32)
            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = rms(x[out_positions], params["final_norm"].astype(f32))
            head = params.get("lm_head")
            head = params["embed"].T if head is None else head
            return jax.nn.log_softmax(x @ head.astype(f32), axis=-1)

    return jax.jit(forward)
