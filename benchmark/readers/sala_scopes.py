"""Metrics of a trunk of linear-attention and block-sparse attention
layers (MiniCPM-SALA, docs/models.md) from the
profiler's capture and the program's ``dynamo_sparse_attention_*``
counters.

The served program carries ``jax.named_scope``s beside ``attn`` and
``mlp``: ``lightning`` around a whole linear-attention mixer, with
``lightning_state`` (a decode step's one-token state update and
read-out) or ``lightning_scan`` (a prefill chunk's chunked scan) inside;
inside ``attn``, ``sparse_select`` (the compressed keys' upkeep and
scores, the pick) and ``sparse_attn`` (attention over the kept pages).
A program without those scopes or counters (a parent commit from before
them, another family) gives every stat here nothing to read.

What the state must move and multiply is in ``readers/
lightning_costs.py``; what the sparse layers must read is the
configuration's module of ``benchmark/attention_costs``. Which sequences
were running is taken from the client's records as the attention
rooflines take it; how many tokens a prefill execution computed, from
the program's ``dynamo_lightning_scan_*`` counters.
"""

from __future__ import annotations

import re

from harness import prom
from harness.manifest import architecture_module
from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import lightning_costs
from readers.device_trace import _mean_decode_step_bytes
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

TOP_SCOPES = SCOPES + ("lightning",)
FINE_SCOPES = ("lightning_state", "lightning_scan", "sparse_select",
               "sparse_attn")
SCAN_TOKENS = "dynamo_lightning_scan_tokens_total"
SCAN_STEPS = "dynamo_lightning_scan_steps_total"


def _seconds(device: dict, scopes, program: str):
    """(seconds in any of ``scopes``, executions of ``program``)."""
    total, n = 0.0, 0
    for scope in scopes:
        among = TOP_SCOPES if scope in TOP_SCOPES else FINE_SCOPES
        seconds, n = scope_seconds(device, scope, program, among)
        total += seconds
    return total, n


def _tokens_an_execution(run: RunData):
    """Tokens the scan advanced its states by in one prefill execution,
    from the program's two counters between the /metrics samples that
    bracket the captured slice (a closed loop of long prompts may bring
    no request's first token inside a 4 s slice, so the client's records
    cannot say which prompts it computed); None without the counters."""
    if not run.prom_samples or run.trace_slice is None:
        return None
    s0, s1 = run.trace_slice
    before = [s for t, s in run.prom_samples if t <= s0]
    after = [s for t, s in run.prom_samples if t >= s1]
    lo = before[-1] if before else run.prom_samples[0][1]
    hi = after[0] if after else run.prom_samples[-1][1]
    steps = prom.delta(lo, hi, SCAN_STEPS)
    return prom.delta(lo, hi, SCAN_TOKENS) / steps if steps > 0 else None


def read(run: RunData, args: dict, path: str = None):
    stat = args["stat"]
    if stat == "counter_ratio_pct":
        if not run.prom_start or not run.prom_end:
            return None
        den = prom.delta(run.prom_start, run.prom_end, args["denominator"])
        if not den > 0:
            return None
        return 100.0 * prom.delta(run.prom_start, run.prom_end,
                                  args["numerator"]) / den

    device = _device(run, path)
    if device is None:
        return None
    program = args["program"]
    seconds, n = _seconds(device, args["scopes"], program)
    if not n or not seconds:
        return None
    if stat == "scope_ms_per_execution":
        return 1e3 * seconds / n, n
    if stat == "scope_share_of_program_pct":
        mods = [m for m in device["modules"] if re.search(program, m.name)]
        return 100.0 * seconds / sum(m.dur for m in mods), n
    peaks = peaks_for(run.device_kind)
    if stat == "state_decode_roofline_pct":      # HBM-bound
        least_s = (n * _mean_decode_step_bytes(run, lightning_costs)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "sparse_decode_roofline_pct":     # HBM-bound
        cost = architecture_module(run.cell.config, run.cell.config_name,
                                   "attention_cost")
        least_s = (n * _mean_decode_step_bytes(run, cost)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "scan_prefill_roofline_pct":      # FLOP-bound
        tokens = _tokens_an_execution(run)
        if not tokens:
            return None
        least_s = (lightning_costs.scan_flops(run.hf, n * tokens)
                   / peaks["flops_bf16"])
        return 100.0 * least_s / seconds, n
    raise ValueError(f"sala_scopes reader: unknown stat {stat!r}")
