"""The comparison that decides ``correct``: the served log-probability
of every returned token against a plain reference, teacher-forced.

The reference itself (the architecture's float32 forward and the two
limits measured for it) is a module of ``benchmark/references``, named
by the configuration's file and handed in here: one comparison for every
architecture, so that no reference brings a laxer one of its own.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

PAD_MULTIPLE = 128


def padded_length(n: int) -> int:
    return -(-n // PAD_MULTIPLE) * PAD_MULTIPLE


def reference_logprobs(module, params, hf: dict, prompt: List[int],
                       returned: List[int], programs: Dict) -> np.ndarray:
    """The reference's log-probability of each returned token, given the
    prompt and the returned tokens before it."""
    import jax.numpy as jnp

    seq = list(prompt) + list(returned)
    t_pad, n_out = padded_length(len(seq)), len(returned)
    key = (t_pad, n_out)
    if key not in programs:
        programs[key] = module.build(hf, t_pad, n_out)
    tokens = np.zeros(t_pad, np.int32)
    tokens[: len(seq)] = seq    # causal: the pad cannot reach a real position
    out_pos = np.arange(len(prompt) - 1, len(prompt) - 1 + n_out, dtype=np.int32)
    logp = np.asarray(programs[key](params, jnp.asarray(tokens), jnp.asarray(out_pos)))
    return logp[np.arange(n_out), np.asarray(returned)]


def check_probes(module, params, hf: dict, probes: List[dict], token_id) -> dict:
    """Compare every probe with the reference ``module`` builds, under
    that module's limits. Returns a record with ``ok``, the errors
    measured and the reason where it failed."""
    atol, mean_atol = module.LOGPROB_ATOL, module.LOGPROB_MEAN_ATOL
    programs: Dict = {}
    worst, diffs, reasons = 0.0, [], []
    for i, p in enumerate(probes):
        if p.get("status") != 200:
            reasons.append(f"probe {i}: HTTP {p.get('status')} {p.get('error')}")
            continue
        served = p["token_logprobs"]
        usage = p.get("usage") or {}
        if (usage.get("prompt_tokens") != len(p["prompt"])
                or usage.get("completion_tokens") != len(served)
                or not served):
            reasons.append(f"probe {i}: usage {usage} for a prompt of "
                           f"{len(p['prompt'])} and {len(served)} tokens")
            continue
        returned = [token_id(t) for t in p["tokens"]]
        ref = reference_logprobs(module, params, hf, p["prompt"], returned, programs)
        d = np.abs(ref - np.asarray(served, np.float64))
        diffs.extend(d.tolist())
        worst = max(worst, float(d.max()))
        if not np.all(np.isfinite(d)) or d.max() > atol:
            reasons.append(
                f"probe {i} ({len(p['prompt'])} prompt tokens): log-probability "
                f"off by {d.max():.4f} > {atol}")
    mean_err = float(np.mean(diffs)) if diffs else float("nan")
    if diffs and mean_err > mean_atol:
        reasons.append(f"mean |log-probability difference| {mean_err:.4f} "
                       f"> {mean_atol}")
    return {"ok": not reasons and bool(diffs), "max_abs_err": worst,
            "mean_abs_err": mean_err, "tokens_compared": len(diffs),
            "reasons": reasons}
