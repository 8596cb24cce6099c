"""Is an fp8 (e4m3) MLA latent cache accurate enough to serve?

The engine guards fp8 KV to GQA families (engine/model_runner.py): the
MLA compressed latent doubles as BOTH the key source (through the
absorbed W_uk) and the value (through W_uv), so e4m3 noise passes
through two learned projections instead of landing directly in a
softmax-bounded score. This script puts a number on that intuition:
same-seed tiny models, caches round-tripped
through e4m3 after prefill, logit deltas + greedy divergence vs the
full-precision cache — GQA (llama) side by side with MLA (deepseek),
plus the rope-half-only variant (quantize k_rope, keep the latent c in
bf16) as the candidate middle ground.

Run (CPU, ~1 min): JAX_PLATFORMS=cpu \
    python examples/llm/benchmarks/fp8_mla_accuracy.py
Results land next to this file as fp8_mla_accuracy.json.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.engine.config import ModelConfig  # noqa: E402
from dynamo_tpu.models import deepseek, llama  # noqa: E402

STEPS = 24
B, CTX0 = 2, 33


def _roundtrip(x, which):
    if which == "none":
        return x
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _run(cfg, arch, quant_fn, forced=None, steps=STEPS):
    """Decode ``steps`` tokens; cache round-trips through e4m3 per
    ``quant_fn`` after every write. ``forced`` [B, steps+1] teacher-
    forces the input tokens so every variant sees IDENTICAL inputs —
    the per-step logit delta then measures cache-quantization noise
    alone, not trajectory divergence. Returns (greedy_tokens [B, T],
    per_step_logits [T, B, V])."""
    params = arch.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n_blocks, bs = 64, 8
    cache = arch.init_kv_cache(cfg, n_blocks, bs, jnp.float32)
    w = 16
    bt = jnp.asarray(
        np.arange(B * w, dtype=np.int32).reshape(B, w) % n_blocks)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg.vocab_size, (B, CTX0)).astype(np.int32)

    pos = jnp.tile(jnp.arange(CTX0, dtype=jnp.int32), (B, 1))
    slots = (bt.repeat(bs, axis=1)[:, :CTX0] * bs
             + (jnp.arange(CTX0) % bs)[None, :])
    ctx = jnp.full((B,), CTX0, jnp.int32)
    logits, cache = arch.forward(
        params, cfg, jnp.asarray(prompt), pos, cache, bt, slots, ctx)
    cache = tuple(quant_fn(c, i) for i, c in enumerate(cache))

    toks = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    greedy = [np.asarray(toks)]
    step_logits = [np.asarray(logits[:, -1])]
    for t in range(steps):
        p = CTX0 + t
        inp = (jnp.asarray(forced[:, t]) if forced is not None else toks)
        step_slots = (bt[:, p // bs] * bs + p % bs)[:, None]
        logits, cache = arch.forward(
            params, cfg, inp[:, None],
            jnp.full((B, 1), p, jnp.int32), cache, bt, step_slots,
            jnp.full((B,), p + 1, jnp.int32))
        cache = tuple(quant_fn(c, i) for i, c in enumerate(cache))
        toks = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        greedy.append(np.asarray(toks))
        step_logits.append(np.asarray(logits[:, -1]))
    return np.stack(greedy, 1), np.stack(step_logits)


def _compare(cfg, arch, variants):
    base_toks, base_logits = _run(cfg, arch, lambda c, i: c)
    rows = {}
    for name, fn in variants.items():
        # teacher-force the BASELINE's greedy tokens: identical inputs,
        # so logit deltas isolate the cache noise
        toks, logits = _run(cfg, arch, fn, forced=base_toks)
        flips = (toks != base_toks).mean()
        rel = float(np.abs(logits - base_logits).mean()
                    / (np.abs(base_logits).mean() + 1e-9))
        # noise relative to the logit MARGIN that decides the argmax
        top2 = np.sort(base_logits, -1)[..., -2:]
        margin = float((top2[..., 1] - top2[..., 0]).mean())
        noise = float(np.abs(logits - base_logits).max(-1).mean())
        rows[name] = {
            "teacher_forced_argmax_flip_rate": round(float(flips), 4),
            "mean_rel_logit_err": round(rel, 5),
            "mean_max_logit_noise": round(noise, 4),
            "mean_top2_margin": round(margin, 4),
        }
    return rows


def main() -> None:
    gqa = ModelConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
        attention_impl="xla",
    )
    mla = ModelConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=4, num_heads=8, num_kv_heads=8, head_dim=16,
        kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=16,
        v_head_dim=16, attention_impl="xla",
    )
    results = {
        "note": (
            "e4m3 cache round-trip after every write vs full-precision "
            "cache; same seed/weights/prompts. GQA quantizes k+v (the "
            "shipped --kv-cache-dtype fp8 path); MLA variants: full "
            "(latent c + k_rope), rope_only (k_rope quantized, latent "
            "kept), latent_only (latent quantized, k_rope kept)."
        ),
        "steps": STEPS,
        "gqa_llama": _compare(gqa, llama, {
            "fp8_kv": lambda c, i: _roundtrip(c, "q"),
        }),
        "mla_deepseek": _compare(mla, deepseek, {
            "fp8_full": lambda c, i: _roundtrip(c, "q"),
            "fp8_rope_only": lambda c, i: (
                _roundtrip(c, "q") if i == 1 else c),
            "fp8_latent_only": lambda c, i: (
                _roundtrip(c, "q") if i == 0 else c),
        }),
    }
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "results", "fp8_mla_accuracy.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
