"""The plain reference of SDAR (``model_type: sdar_moe``; JetLM's
SDAR-30B-A3B-Chat): a float32 forward of the layer equations and the
generation procedure as ISSUE 45 wrote them down, recalling the published
``modeling_sdar_moe.py`` and ``generate.py`` (github.com/JetAstra/SDAR).
The catalog's config fixes every size and is silent on the block length,
the mask id and the procedure: those are listed under ``assumed`` in the
configuration's file.

A layer (all alike), ``x`` of ``[T, D]``, block length ``B``:

    h    = rms_norm(x, w_in)
    q    = (h Wq) [T, H, d];  k = (h Wk) [T, KVH, d];  v = (h Wv)        no bias
    q, k = rms_norm(q, w_qn[d]), rms_norm(k, w_kn[d])     per head, before the rope
    q, k = rope(q, k; theta, half rotation over the whole head, no scaling)
    s_ij = q_i . k_j / sqrt(d)   where  j // B <= i // B,  else -inf      GQA
    x    = x + (softmax(s) v) Wo
    m    = rms_norm(x, w_post)
    p    = softmax(float32(m) float32(Wr))                 over all experts
    S, g = top_k(p);  g = g / sum(g)                       norm_topk_prob
    x    = x + sum_{e in S} g_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e

then the final ``rms_norm`` and the untied head. **The logits at position
``i`` are the distribution of the token at position ``i``**: a masked
position predicts itself, there is no shift by one.

Generation (``generate``, a plain loop): the first ``(P // B) · B`` prompt
tokens are context; the other ``P % B`` open the first block, unmasked. A
block is ``B`` ids at ``[n, n + B)``, each a token or ``mask_token_id``.
A denoise pass is one forward over the sequence up to ``n + B`` (every
position below ``n`` final, the block as it stands) under the mask above;
at each masked position a token is taken (greedy here) with its
probability as confidence; ``B // denoising_steps`` positions (the
remainder to the first passes) take their token, by
``remasking_strategy``: ``sequential`` the first masked ones left to
right, ``low_confidence_static`` those of highest confidence,
``low_confidence_dynamic`` every one over ``confidence_threshold`` if
that is at least as many, else as static. Once no mask is left the block
is final (the served program's commit pass, which keeps its keys and
values, computes nothing this loop needs: the next forward recomputes
them) and the next block opens.

Each line **by its definition**: attention is a full masked product over
every key, a block of ``QUERY_BLOCK`` queries at a time; the experts are
every expert on every token, one expert at a time, weighted by the gate,
zero where not chosen. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no pages, no kernel,
no batching, nothing imported from ``dynamo_tpu.models`` or
``dynamo_tpu.ops`` (the field names of ``ModelConfig`` are read once, at
import, to refuse a program without the family). It reads the engine's
parameter arrays, because the weights are data (random, from the seed):
``{"embed", "layers": {ln1, wq, wk, wv, wo, q_norm, k_norm, ln2, router,
w_gate, w_up, w_down} stacked over the layers, "final_norm", "lm_head"}``,
``x @ w``.

**What ``build`` compares.** ``harness/reference.check_probes`` hands over
the prompt and the returned tokens only (``tokens[t_pad]``, ``out_pos[i]
= P - 1 + i``) and compares row ``i`` at the returned token ``i``. The
state a token was sampled under therefore has to follow from the tokens
alone, and under ``sequential`` it does: the token at offset ``o`` of its
block (first masked offset ``m0``: ``P % B`` in the first block, 0 after)
was unmasked in the first pass ``g`` whose cumulative quota passes ``o -
m0``, when the offsets below ``m0 + quota_0 + .. + quota_{g-1}`` held
their final tokens, the rest the mask id, and every earlier block was
whole. ``state_logprobs`` is that definition, one forward a state.
``build`` evaluates the same states in one forward: the clean sequence,
and behind it, for every pass ``g``, a copy of the blocks that hold
returned tokens as they stood at pass ``g``; a copy's queries see the
clean keys of earlier blocks and the keys of their own block in their own
copy (``tests/test_sdar_reference.py`` ties the two forms to 1e-5). Under
either confidence rule the order is not recoverable from the tokens and
``build`` refuses; those rules are held on the CPU against ``generate``.

Departures from the published code, and readings of it:

- the rotary embedding is the engine's half rotation (pairs ``(i, i +
  d/2)``), the published ``rotate_half``;
- the mask id is never a prediction: its logit is -inf before the
  softmax (a sampled mask id would read as still masked; the published
  code leaves that to the trained weights, random weights do not);
- the confidence of a position is the probability of its token under the
  plain softmax (temperature 1, nothing filtered), which is what the API
  reports as its log-probability; the published ``generate.py`` reads it
  off the filtered, temperature-scaled distribution. The cell's rule,
  ``sequential``, reads no confidence.

**Tolerance.** See ``LOGPROB_ATOL`` below.
"""

from __future__ import annotations

# absolute tolerance on one token's log-probability, and on the mean
# absolute difference over a run's probe tokens. The served path computes
# in bfloat16 (weights, activations, pages) with a float32 router; the
# reference takes the same bfloat16 weights to float32. Readings on the
# v5e (my chip run, PR 45; PERF.md section 6; the 7-layer configuration,
# through the server):
# - the cell's own 64 probe tokens, ten runs on ten seeds (4000000201,
#   4000000401-406, 4000000411, 4000000601-602, the last two from the
#   committed files): mean of a run 0.0416-0.0534, largest single
#   difference 0.122-0.188; past them (scripts/long_probes.py, five
#   seeds 4000000301-305, each probe's 16 tokens on their own): at 150 and
#   420 tokens mean 0.034-0.061, largest 0.108-0.156; at 1000 mean
#   0.036-0.047, largest 0.071-0.146; at 3000 mean 0.030-0.045, largest
#   0.086-0.143. (A masked position's input is the mask id's embedding
#   whatever the token will be, so its logits rest on attention and the
#   experts alone and bfloat16's rounding is damped by no exact embedding:
#   0.045 where the one-token families read 0.03.)
# - an fp8 page cache (kv_cache_dtype fp8, the nearest precision below the
#   configuration's; seeds 4000000301-302, probes of 150, 420, 2200, 1000
#   and 3000 tokens): mean of a probe 0.163-0.345 (0.215-0.27 over the
#   harness's kind of four), largest 0.397-0.822 (0.69 and 0.82 over a
#   run): not correct by both limits;
# - a served block_length of 8 against the reference's 4: mean of a probe
#   0.23-0.94, largest 0.52-2.04: not correct by both, at every length;
# - a served denoising_steps of 4 against the reference's 2 (three seeds):
#   not correct in each run, by the single-token limit and by one probe,
#   the shortest (100-150 tokens: largest 0.41-0.76, mean 0.139-0.193);
#   its probes of 300 tokens and more read inside (mean 0.035-0.088,
#   largest 0.095-0.315): with random weights, what a block's neighbours
#   hold (a token or the mask) moves a position's logits by less the more
#   keys its attention is spread over, so these limits tell a wrong
#   schedule at short context only. tier-1's float32 comparison on the CPU
#   (tests/test_block_decode.py) holds every pass of every rule to 1e-4.
# LOGPROB_MEAN_ATOL 0.1: 1.9 x the largest sound mean of a run (0.0534)
# and 1.6 x that of one long probe (0.061); 2.2 x under the fp8 cache's
# smallest mean over a run (0.215) and 1.6 x under its smallest of one
# probe (0.163). LOGPROB_ATOL 0.35 on a single token: 1.9 x the largest
# sound difference (0.188 in 640 + 320 tokens) and 2 x under the fp8
# cache's largest over a run (0.69); it is also what catches a non-finite
# value or a gross fault (a mask, a position, a pass's state).
LOGPROB_ATOL = 0.35
LOGPROB_MEAN_ATOL = 0.1

HEAD_SLICES = 16    # the head a sixteenth of the vocabulary at a time
QUERY_BLOCK = 128   # queries of an attention layer computed together


def _refuse_a_program_without_the_family() -> None:
    """A program whose ``ModelConfig`` has no ``block_length`` takes the
    published keys for a Mixtral trunk's (``num_experts`` > 0), builds
    10 GB of weights and serves them one token a pass under a causal
    mask: wrong tokens after minutes of set-up. This module is imported
    before anything is built (``run.py``), so such a program is refused
    here, in seconds, as ``references/afmoe.py`` refuses one without
    ``layer_types``. The configuration's fields are all that is read of
    the program."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelConfig

    if "block_length" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise ImportError(
            "this program has no family whose decode unit is a block "
            "(ModelConfig has no block_length): it cannot serve model_type "
            "sdar_moe, and references/sdar.py has nothing to compare it with")


_refuse_a_program_without_the_family()


def quotas(block: int, steps: int):
    """Positions pass ``t`` of a block unmasks at least: ``block //
    steps``, the remainder to the first passes."""
    base, rem = divmod(block, steps)
    return [base + (t < rem) for t in range(steps)]


def _sizes(hf: dict):
    if hf.get("model_type") != "sdar_moe":
        raise NotImplementedError("the reference of model_type sdar_moe")
    for key, only in (("norm_topk_prob", True), ("rope_scaling", None),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("attention_bias", False), ("decoder_sparse_step", 1),
                      ("use_sliding_window", False)):
        if hf.get(key, only) != only:
            raise NotImplementedError(f"the reference has no {key}={hf[key]!r}")
    if hf.get("mlp_only_layers"):
        raise NotImplementedError("the reference has no mlp_only_layers")
    hidden = int(hf["hidden_size"])
    n_heads, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    return dict(
        hidden=hidden, n_heads=n_heads, n_kv=n_kv,
        hd=int(hf.get("head_dim") or hidden // n_heads),
        theta=float(hf.get("rope_theta", 10000.0)),
        eps=float(hf.get("rms_norm_eps", 1e-6)),
        top_k=int(hf["num_experts_per_tok"]),
        block=int(hf["block_length"]), mask_id=int(hf["mask_token_id"]),
        steps=int(hf.get("denoising_steps") or hf["block_length"]),
        strategy=str(hf.get("remasking_strategy") or "low_confidence_dynamic"),
        threshold=float(hf.get("confidence_threshold", 0.9)),
    )


def _forward(z: dict, t: int):
    """``(params, tokens [t], pos [t], visible(i_pos.., j..) mask fn) ->
    hidden [t, D]`` of the trunk above, and the head over chosen rows."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n_heads, n_kv, hd = z["n_heads"], z["n_kv"], z["hd"]
    g = n_heads // n_kv
    eps, theta, top_k = z["eps"], z["theta"], z["top_k"]
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):   # x [T, H, d], half rotation over the whole head
        d = x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = pos[:, None].astype(f32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(a, w, pos, visible):
        q = rope(rms((a @ w["wq"]).reshape(t, n_heads, hd), w["q_norm"]), pos)
        k = rope(rms((a @ w["wk"]).reshape(t, n_kv, hd), w["k_norm"]), pos)
        v = (a @ w["wv"]).reshape(t, n_kv, hd)
        q = q.reshape(t, n_kv, g, hd) * hd ** -0.5
        idx = jnp.arange(t)

        def block(args):   # a block of queries: q_b [qb, KVH, G, hd], i_b [qb]
            q_b, i_b = args
            s = jnp.einsum("qkgd,tkd->kgqt", q_b, k)
            s = jnp.where(visible(i_b[:, None], idx[None, :])[None, None],
                          s, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, (q.reshape(t // qb, qb, n_kv, g, hd),
                                idx.reshape(t // qb, qb)))
        return o.reshape(t, n_heads * hd) @ w["wo"]

    def expert_mlp(m, lp):
        p = jax.nn.softmax(m @ lp["router"].astype(f32), axis=-1)    # [T, E]
        _, chosen = jax.lax.top_k(p, top_k)                          # [T, k]
        gate = jnp.zeros_like(p).at[jnp.arange(t)[:, None], chosen].set(
            jnp.take_along_axis(p, chosen, axis=1))
        gate = gate / gate.sum(-1, keepdims=True)

        def one_expert(y, ew):   # one expert's weights to float32 at a time
            w_e, wg, wu, wd = ew
            h = jax.nn.silu(m @ wg.astype(f32)) * (m @ wu.astype(f32))
            return y + w_e[:, None] * (h @ wd.astype(f32)), None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                            (gate.T, lp["w_gate"], lp["w_up"], lp["w_down"]))
        return y

    small = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2")

    def trunk(params, tokens, pos, visible):
        def layer(x, lp):
            w = {k: lp[k].astype(f32) for k in small}
            x = x + attention(rms(x, w["ln1"]), w, pos, visible)
            return x + expert_mlp(rms(x, w["ln2"]), lp), None

        x = params["embed"][tokens].astype(f32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return x

    def head_logprobs(params, x):   # [n, D] -> log-probs [n, V], a slice at a time
        head = params["lm_head"]
        x = rms(x, params["final_norm"].astype(f32))
        vocab = head.shape[1]
        parts = HEAD_SLICES if vocab % HEAD_SLICES == 0 else 1
        width = vocab // parts

        def one(i):
            cols = jax.lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
            return x @ cols.astype(f32)

        logits = jax.lax.map(one, jnp.arange(parts)).transpose(1, 0, 2).reshape(
            x.shape[0], vocab)
        # the mask id is never a prediction
        logits = jnp.where(jnp.arange(vocab) == z["mask_id"], -jnp.inf, logits)
        return jax.nn.log_softmax(logits, axis=-1)

    return trunk, head_logprobs


def state_logprobs(hf: dict, t: int):
    """The definition, one forward a state: jit(params, ids [t]) ->
    log-probs [t, V] of a sequence of ``t`` ids (tokens, and the mask id
    where a position is masked) under the block mask."""
    import jax

    z = _sizes(hf)
    trunk, head_logprobs = _forward(z, t)
    blen = z["block"]

    def forward(params, ids):
        import jax.numpy as jnp

        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(t)
            x = trunk(params, ids, pos, lambda i, j: j // blen <= i // blen)
            return head_logprobs(params, x)

    return jax.jit(forward)


def generate(hf: dict, params, prompt, max_tokens: int):
    """The procedure as a plain loop, greedy: -> (tokens, the
    log-probability each was taken under, the pass of its block that
    unmasked it). One ``state_logprobs`` forward a pass, compiled a
    length."""
    import numpy as np

    z = _sizes(hf)
    blen, mask_id = z["block"], z["mask_id"]
    programs = {}
    seq = list(prompt[: len(prompt) - len(prompt) % blen])
    block = list(prompt[len(seq):]) + [mask_id] * (blen - len(prompt) % blen)
    out, lps, passes = [], [], []
    while len(out) < max_tokens:
        first = sum(t != mask_id for t in block)   # the prompt's tail
        lp_of, pass_of = [None] * blen, [-1] * blen
        for t, quota in enumerate(
                quotas(blen, z["steps"]) + [blen] * blen):
            masked = [o for o in range(blen) if block[o] == mask_id]
            if not masked:
                break
            n = len(seq) + blen
            if n not in programs:
                programs[n] = state_logprobs(hf, n)
            logp = np.asarray(programs[n](params, np.asarray(seq + block)))[-blen:]
            x0 = logp.argmax(-1)
            conf = {o: float(np.exp(logp[o, x0[o]])) for o in masked}
            by_conf = sorted(masked, key=lambda o: (-conf[o], o))
            quota = min(quota, len(masked))
            if z["strategy"] == "sequential":
                take = masked[:quota]
            elif z["strategy"] == "low_confidence_static":
                take = by_conf[:quota]
            else:
                over = [o for o in masked if conf[o] > z["threshold"]]
                take = over if len(over) >= quota else by_conf[:quota]
            for o in take:
                block[o] = int(x0[o])
                lp_of[o], pass_of[o] = float(logp[o, x0[o]]), t
        out += block[first:]
        lps += lp_of[first:]
        passes += pass_of[first:]
        seq += block
        block = [mask_id] * blen
    return out[:max_tokens], lps[:max_tokens], passes[:max_tokens]


def build(hf: dict, t_pad: int, n_out: int):
    """jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs
    [n_out, V]: row ``i`` is the distribution the returned token ``i``
    (at position ``out_positions[i] + 1``) was taken under."""
    import jax
    import jax.numpy as jnp

    z = _sizes(hf)
    if z["strategy"] != "sequential":
        raise NotImplementedError(
            f"remasking_strategy {z['strategy']!r}: the pass a token was "
            "unmasked in follows from the tokens under 'sequential' alone")
    blen, mask_id = z["block"], z["mask_id"]
    cum = [0]
    for q in quotas(blen, z["steps"]):
        cum.append(cum[-1] + q)        # offsets unmasked before pass g: cum[g]
    n_pass = len(cum) - 1
    n_blocks = -(-n_out // blen) + 1   # the blocks that can hold a returned token
    extra = n_pass * n_blocks * blen
    t_ext = t_pad + -(-extra // QUERY_BLOCK) * QUERY_BLOCK
    trunk, head_logprobs = _forward(z, t_ext)

    def forward(params, tokens, out_positions):
        with jax.default_matmul_precision("highest"):
            p_len = out_positions[0] + 1                 # the prompt's length
            first = p_len // blen * blen                 # the first block's start
            tail = p_len - first
            # the copies: pass g's view of block k at offset o
            g, k, o = jnp.meshgrid(jnp.arange(n_pass), jnp.arange(n_blocks),
                                   jnp.arange(blen), indexing="ij")
            g, k, o = g.reshape(-1), k.reshape(-1), o.reshape(-1)
            c_pos = first + k * blen + o
            m0 = jnp.where(k == 0, tail, 0)
            seen = o < m0 + jnp.asarray(cum)[g]
            c_ids = jnp.where(
                seen, tokens[jnp.minimum(c_pos, t_pad - 1)], mask_id)
            pad = t_ext - t_pad - extra
            ids = jnp.concatenate(
                [tokens, c_ids, jnp.zeros((pad,), tokens.dtype)])
            pos = jnp.concatenate(
                [jnp.arange(t_pad), c_pos, jnp.zeros((pad,), c_pos.dtype)])
            # 0 clean, g + 1 a copy, -1 the padding (sees and is seen by itself)
            copy = jnp.concatenate(
                [jnp.zeros((t_pad,), jnp.int32), (g + 1).astype(jnp.int32),
                 jnp.full((pad,), -1, jnp.int32)])
            blk = pos // blen

            def visible(i, j):
                clean_key = jnp.where(copy[i] == 0, blk[j] <= blk[i],
                                      (copy[i] > 0) & (blk[j] < blk[i]))
                own = (copy[j] == copy[i]) & (blk[j] == blk[i])
                return jnp.where(copy[j] == 0, clean_key, own)

            x = trunk(params, ids, pos, visible)
            # returned token i: its block, offset, and the pass that took it
            at = out_positions + 1
            k_i, o_i = (at - first) // blen, at % blen
            rel = o_i - jnp.where(k_i == 0, tail, 0)
            g_i = (rel[:, None] >= jnp.asarray(cum)[None, 1:]).sum(-1)
            rows = t_pad + (g_i * n_blocks + k_i) * blen + o_i
            return head_logprobs(params, x[rows])

    return jax.jit(forward)
