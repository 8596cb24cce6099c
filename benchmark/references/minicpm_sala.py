"""The plain reference of MiniCPM-SALA (``model_type: minicpm_sala``): a
float32 forward of the layer equations, as ISSUE 37 wrote them down from
the published config and the MiniCPM4 / Lightning-Attention-2 papers.

Common: ``h = embed(ids) · scale_emb``; every sublayer is pre-norm
RMSNorm and adds ``scale_depth / √depth`` times its output to the
residual, ``depth`` the published number of layers (``depth_cut.
of_layers`` in a configuration cut in depth); SwiGLU feed-forward;
logits ``= head(RMSNorm(h) / (hidden_size / dim_model_base))``.

``lightning-attn`` layer, ``n = RMSNorm(x)``, ``H`` heads of ``d``:

- ``q, k, v = n W_q, n W_k, n W_v``; ``q, k ←`` per-head RMSNorm, then
  the rotary embedding (θ = ``rope_theta``) over the whole head;
- ``S_t = λ_h S_{t−1} + k_tᵀ v_t``, ``o_t = (q_t / √d) S_t``, **one
  token at a time** through ``lax.scan`` from a zero state (the served
  program computes a prefill chunk in the chunked matrix form and
  decodes through a state it keeps by slot);
- ``λ_h = exp(−s_h (1 − l / (depth − 1) + 1e-5))``, ``s_h = 2^(−8 (h + 1)
  / H)``, ``l`` the layer's published index: computed here from the
  formula, not read from the served program's array;
- ``o ← RMSNorm(o)`` per head ``⊙ sigmoid(n W_g)``; output ``o W_o``.

``minicpm4`` layer (InfLLM-V2 block-sparse attention), no rotary
embedding, per-head RMSNorm on q and k, scale ``1/√d``. For the query at
position ``t``, ``n = t + 1`` tokens visible:

- ``n ≤ dense_len``: causal softmax attention over all keys;
- past it, per kv head: compressed keys ``c_j = mean(k[stride·j :
  stride·j + kernel_size])`` over the windows whole inside the ``n``
  tokens; ``p = softmax_j(q · c_j / √d)`` per query head, summed over the
  group's query heads; a block of ``block_size`` tokens scores the
  largest ``p_j`` among the compressed keys whose window overlaps it;
  kept are the first ``init_blocks`` blocks, the blocks that overlap the
  last ``window_size`` tokens and the ``topk`` best of the others (ties:
  the lower block); causal softmax attention over the kept blocks'
  tokens;
- ``o ⊙ sigmoid(n W_g)``, then ``W_o``.

Each of these is computed **by its definition**: the windows are
gathered and averaged (the served program keeps one mean a page and
averages two), a block's score is a maximum under an explicit overlap
matrix, the ``topk`` are the first of a stable descending sort, and the
attention is a full masked product over every key, a block of
``QUERY_BLOCK`` queries at a time so that 16 k tokens fit beside the
served model. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernels, no
batching, nothing imported from ``dynamo_tpu.models`` or
``dynamo_tpu.ops`` (the field names of ``ModelConfig`` are read once, at
import, to refuse a program without the family). It reads the engine's
parameter arrays, because the weights are data (random, from the seed):
``{"embed", "runs": [a dict of
arrays stacked over each homogeneous run of mixer_types: ln1, wq, wk, wv,
wg, wo, q_norm, k_norm, ln2, w_gate, w_up, w_down, and o_norm in a
lightning run], "final_norm", "lm_head"}``, ``x @ w``.

Departures from the published description, and readings of it:

- the rotary embedding is the engine's half rotation (pairs ``(i, i +
  d/2)``), as in the published MiniCPM code;
- "``t ≤ dense_len``" is read as ``n = t + 1 ≤ dense_len`` (a context of
  exactly ``dense_len`` tokens is still dense);
- the block score as the maximum over the overlapping compressed keys
  is the simplest reading of InfLLM-V2's pooling (ISSUE 37); the
  published kernel's pooling may weigh the overlaps differently;
- the decay slopes are Lightning-Attention-2's as MiniMax-01 builds
  them; the catalog's config has no decay key (``assumed`` in the
  configuration's file), and neither it nor any equation here uses
  ``mup_denominator``;
- the output norm's weight is one ``[H · d]`` vector, a slice a head.

**Tolerance.** What is compared is the log-probability of each returned
token, teacher-forced. The served path computes in bfloat16 (weights,
activations, pages) with a float32 lightning state and float32 page
means; the reference takes the same bfloat16 weights to float32. Two
pairs of limits, and the readings that set them, are in PERF.md section
6 (PR 37):

- ``LOGPROB_ATOL`` / ``LOGPROB_MEAN_ATOL`` hold every context up to
  ``dense_len`` and are the cell's (``harness/drive.py`` sends no longer
  probe). On the chip the served path reads at most 0.11 / 0.032 there
  and an fp8 page cache 0.26-0.37 / 0.10-0.11;
- ``SELECTING_LOGPROB_ATOL`` / ``SELECTING_LOGPROB_MEAN_ATOL`` hold a
  context past ``dense_len`` (``scripts/long_probes.py``; ``limits_for``
  picks the pair). There the pick is discrete: of 114-217 blocks the 64
  best are kept, the scores of the blocks around the 64th place lie
  0.3 % apart under weights from a seed, and bfloat16's rounding of the
  residual stream moves them by more, so a few blocks of 64 tokens
  differ from the reference's in most queries ("ties apart", ISSUE 37).
  Each is 1 % of what the query attends to, and now and then holds one
  of the few keys that carry the softmax. The served path reads up to
  0.49 / 0.11 there (five seeds). These limits fail half the picks
  dropped (1.05-1.54 / 0.45-0.82); they cannot tell an fp8 page cache
  from the stated precision at every seed (0.20-0.76 / 0.10-0.26): the
  probes under ``dense_len`` do that. Neither pair tells a bfloat16
  state on the chip (it reads as the float32 one does, 0.05-0.09 /
  0.025-0.029 under ``dense_len`` and 0.19-0.36 / 0.073-0.074 past it:
  a lightning layer reaches the residual through 0.247 and a gate of a
  half); tier-1's float32 comparison on the CPU does, as for Falcon-H1.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens (PERF.md section 6, PR 37)
LOGPROB_ATOL = 0.25
LOGPROB_MEAN_ATOL = 0.06
# the same for a context in which the sparse layers select their blocks
SELECTING_LOGPROB_ATOL = 0.75
SELECTING_LOGPROB_MEAN_ATOL = 0.2

MLP_SLICES = 4      # the feed-forward goes to float32 a quarter at a time
HEAD_SLICES = 8     # the head an eighth of the vocabulary at a time
QUERY_BLOCK = 128   # queries of a sparse layer computed together

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                   "topk": 64, "init_blocks": 1, "window_size": 2048,
                   "dense_len": 8192}


def _refuse_a_program_without_the_family() -> None:
    """A program whose ``ModelConfig`` has no ``mixer_types`` takes the
    published keys for a llama trunk's, builds 8 GB of weights and serves
    dense rotary attention in every layer: wrong tokens after minutes of
    set-up (the parent of PR 37 ran this cell for 232 s before this
    module failed on its parameters). This module is imported before
    anything is built (``run.py``), so such a program is refused here,
    in seconds, as ``references/xing4.py`` refuses one without mixed
    residual streams. The configuration's fields are all that is read of
    the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    if "mixer_types" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise ImportError(
            "this program has no trunk of linear-attention and block-sparse "
            "layers (ModelConfig has no mixer_types): it cannot serve "
            "model_type minicpm_sala, and references/minicpm_sala.py has "
            "nothing to compare it with")


_refuse_a_program_without_the_family()


def limits_for(hf: dict, context_tokens: int):
    """(one token's limit, the mean's) for a probe whose context (prompt
    and returned tokens) reaches ``context_tokens``: the cell's pair up to
    ``dense_len``, the selecting pair past it."""
    sp = {**SPARSE_DEFAULTS, **(hf.get("sparse_config") or {})}
    if context_tokens <= sp["dense_len"]:
        return LOGPROB_ATOL, LOGPROB_MEAN_ATOL
    return SELECTING_LOGPROB_ATOL, SELECTING_LOGPROB_MEAN_ATOL


def runs_of(mixers):
    """[(kind, first global layer index, length)] of each homogeneous run."""
    runs = []
    for i, kind in enumerate(mixers):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return [tuple(r) for r in runs]


def build(hf: dict, t_pad: int, n_out: int):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs [n_out, V]."""
    import jax
    import jax.numpy as jnp

    for key, only in (("attn_use_rope", False), ("lightning_use_rope", True),
                      ("qk_norm", True), ("use_output_gate", True),
                      ("use_output_norm", True), ("attn_use_output_gate", True),
                      ("attention_bias", False), ("rope_scaling", None)):
        if hf.get(key, only) != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    if hf.get("model_type") != "minicpm_sala":
        raise NotImplementedError("the reference of model_type minicpm_sala")
    mixers = list(hf["mixer_types"])
    hidden = int(hf["hidden_size"])
    n_heads, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or hidden // n_heads)
    lh = int(hf.get("lightning_nh", n_heads))
    ld = int(hf.get("lightning_head_dim", hd))
    theta = float(hf.get("rope_theta", 10000.0))
    eps = float(hf.get("rms_norm_eps", 1e-6))
    cut = hf.get("depth_cut") or {}
    depth = int(cut.get("of_layers", len(mixers)))
    first_layer = int(cut.get("first_layer", 0))
    res = float(hf.get("scale_depth", 1.0)) / depth ** 0.5
    scale_emb = float(hf.get("scale_emb", 1.0))
    head_div = hidden / float(hf.get("dim_model_base", hidden))
    sp = {**SPARSE_DEFAULTS, **(hf.get("sparse_config") or {})}
    ks, stride, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    topk, init_blocks = sp["topk"], sp["init_blocks"]
    window, dense_len = sp["window_size"], sp["dense_len"]
    f32 = jnp.float32
    qb = QUERY_BLOCK if t_pad % QUERY_BLOCK == 0 else t_pad

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    pos = jnp.arange(t_pad)

    def rope(x):   # x [T, H, d], half rotation over the whole head
        d = x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = pos[:, None].astype(f32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def lightning(n1, w, layer_index):
        q = rope(rms((n1 @ w["wq"]).reshape(t_pad, lh, ld), w["q_norm"]))
        k = rope(rms((n1 @ w["wk"]).reshape(t_pad, lh, ld), w["k_norm"]))
        v = (n1 @ w["wv"]).reshape(t_pad, lh, ld)
        slopes = 2.0 ** (-8.0 * (jnp.arange(lh, dtype=f32) + 1.0) / lh)
        lam = jnp.exp(-slopes * (1.0 - layer_index / max(depth - 1, 1) + 1e-5))

        def token(s, inp):   # the recurrence, one token: S [H, d_k, d_v]
            q_t, k_t, v_t = inp
            s = lam[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
            return s, jnp.einsum("hk,hkv->hv", q_t * ld ** -0.5, s)

        _, o = jax.lax.scan(token, jnp.zeros((lh, ld, ld), f32), (q, k, v))
        o = rms(o, w["o_norm"].reshape(lh, ld)).reshape(t_pad, lh * ld)
        return (o * jax.nn.sigmoid(n1 @ w["wg"])) @ w["wo"]

    # compressed keys: window j covers tokens [stride·j, stride·j + ks)
    n_comp = max((t_pad - ks) // stride + 1, 0)
    n_blocks = -(-t_pad // bs)
    win_tokens = (jnp.arange(n_comp)[:, None] * stride + jnp.arange(ks)[None, :])
    comp_start = jnp.arange(n_comp) * stride
    blk_start = jnp.arange(n_blocks) * bs
    # window j overlaps block m
    overlap = ((comp_start[None, :] < blk_start[:, None] + bs)
               & (comp_start[None, :] + ks > blk_start[:, None]))    # [NB, J]
    blk_of_token = pos // bs

    def sparse(n1, w):
        g = n_heads // n_kv
        q = rms((n1 @ w["wq"]).reshape(t_pad, n_kv, g, hd), w["q_norm"])
        k = rms((n1 @ w["wk"]).reshape(t_pad, n_kv, hd), w["k_norm"])
        v = (n1 @ w["wv"]).reshape(t_pad, n_kv, hd)
        comp = k[win_tokens].mean(axis=1) if n_comp else k[:0]      # [J, KVH, hd]
        scale = hd ** -0.5

        def block(args):   # a block of queries: q_b [qb, KVH, G, hd], t_b [qb]
            q_b, t_b = args
            n = t_b + 1
            causal = pos[None, :] <= t_b[:, None]                    # [qb, T]
            if n_comp:
                valid = (comp_start[None, :] + ks) <= n[:, None]     # [qb, J]
                lg = jnp.einsum("qkgd,jkd->kgqj", q_b * scale, comp)
                lg = jnp.where(valid[None, None], lg, -jnp.inf)
                p = jnp.exp(lg - jnp.max(lg, -1, keepdims=True, initial=-1e30))
                p = jnp.where(valid[None, None], p, 0.0)
                p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
                p = p.sum(axis=1)                                    # [KVH, qb, J]
                score = jnp.max(jnp.where(overlap[None, None], p[:, :, None, :],
                                          -jnp.inf), axis=-1)        # [KVH, qb, NB]
            else:
                score = jnp.zeros((n_kv, q_b.shape[0], n_blocks), f32)
            m = jnp.arange(n_blocks)[None, :]
            first_window = jnp.maximum(n - window, 0)[:, None] // bs
            forced = (m < init_blocks) | (m >= first_window)         # [qb, NB]
            cand = jnp.where(forced[None], -jnp.inf, score)
            order = jnp.argsort(-cand, axis=-1, stable=True)         # best first
            rank = jnp.argsort(order, axis=-1, stable=True)
            picked = (rank < topk) & ~forced[None]
            kept = forced[None] | picked | (n <= dense_len)[None, :, None]
            mask = kept[:, :, blk_of_token] & causal[None]           # [KVH, qb, T]
            lg = jnp.einsum("qkgd,tkd->kgqt", q_b * scale, k)
            lg = jnp.where(mask[:, None], lg, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(lg, axis=-1), v)

        o = jax.lax.map(block, (q.reshape(t_pad // qb, qb, n_kv, g, hd),
                                pos.reshape(t_pad // qb, qb)))
        o = o.reshape(t_pad, n_heads * hd)
        return (o * jax.nn.sigmoid(n1 @ w["wg"])) @ w["wo"]

    def mlp(n2, lp):   # a slice of the intermediate width at a time
        inter = lp["w_gate"].shape[1]
        parts = MLP_SLICES if inter % MLP_SLICES == 0 else 1
        width = inter // parts

        def one(y, i):
            wg, wu = (jax.lax.dynamic_slice_in_dim(lp[k], i * width, width, 1)
                      .astype(f32) for k in ("w_gate", "w_up"))
            wd = jax.lax.dynamic_slice_in_dim(lp["w_down"], i * width, width, 0)
            return y + (jax.nn.silu(n2 @ wg) * (n2 @ wu)) @ wd.astype(f32), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(n2), jnp.arange(parts))
        return y

    small = ("ln1", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm", "ln2",
             "o_norm")

    def layer_of(kind):
        def layer(carry, lp):
            x, index = carry
            w = {k: lp[k].astype(f32) for k in small if k in lp}
            n1 = rms(x, w["ln1"])
            delta = lightning(n1, w, index) if kind == LIGHTNING else sparse(n1, w)
            x = x + res * delta
            x = x + res * mlp(rms(x, w["ln2"]), lp)
            return (x, index + 1.0), None
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
            x = params["embed"][tokens].astype(f32) * scale_emb
            for (kind, start, _), run in zip(runs_of(mixers), params["runs"]):
                (x, _), _ = jax.lax.scan(
                    layer_of(kind), (x, jnp.float32(first_layer + start)), run)
            x = rms(x[out_positions], params["final_norm"].astype(f32)) / head_div
            head = params.get("lm_head")
            head = params["embed"].T if head is None else head
            return jax.nn.log_softmax(head_logits(x, head), axis=-1)

    return jax.jit(forward)
