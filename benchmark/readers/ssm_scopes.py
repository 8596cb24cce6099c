"""Metrics of a state-space mixer from the profiler's capture.

The served program of a family with a Mamba-2 mixer (docs/models.md)
carries ``jax.named_scope``s beside
``attn`` and ``mlp``: ``ssm`` around the whole mixer (projections, conv,
recurrence, gated norm) and inside it ``ssm_conv`` (the causal conv and
its window), ``ssm_state`` (a decode step's one-token state update and
read-out) and ``ssm_scan`` (a prefill chunk's chunked scan). A program
without those scopes (a parent commit from before them, a model without
a mixer) gives every stat here nothing to read.

What the state must move and multiply is in ``readers/ssm_costs.py``;
which sequences were running, and which prompts were computed, is taken
from the client's records as the attention rooflines take them
(``readers/device_trace.py``), so a row the program touches without a
sequence in it lowers the share.
"""

from __future__ import annotations

import re

from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import ssm_costs
from readers.device_trace import _computed_chunks, _mean_decode_step_bytes
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

# the mixer beside the step's other scopes, and the scopes inside it
TOP_SCOPES = SCOPES + ("ssm",)
FINE_SCOPES = ("ssm_conv", "ssm_state", "ssm_scan")


def _seconds(device: dict, scope: str, program: str):
    scopes = TOP_SCOPES if scope in TOP_SCOPES else FINE_SCOPES
    return scope_seconds(device, scope, program, scopes)


def read(run: RunData, args: dict, path: str = None):
    device = _device(run, path)
    if device is None:
        return None
    stat, program = args["stat"], args["program"]
    seconds, n = _seconds(device, args["scope"], program)
    if not n or not seconds:
        return None
    if stat == "scope_ms_per_execution":
        return 1e3 * seconds / n, n
    if stat == "scope_share_of_program_pct":
        mods = [m for m in device["modules"] if re.search(program, m.name)]
        return 100.0 * seconds / sum(m.dur for m in mods), n
    peaks = peaks_for(run.device_kind)
    if stat == "state_decode_roofline_pct":      # HBM-bound
        # the window is read and written in ssm_conv, the state in
        # ssm_state: the bytes of both over the time of both
        seconds += _seconds(device, "ssm_conv", program)[0]
        least_s = (n * _mean_decode_step_bytes(run, ssm_costs)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "scan_prefill_roofline_pct":      # FLOP-bound
        tokens = sum(length for _, length in _computed_chunks(run))
        if not tokens:
            return None
        least_s = ssm_costs.scan_flops(run.hf, tokens) / peaks["flops_bf16"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"ssm_scopes reader: unknown stat {stat!r}")
