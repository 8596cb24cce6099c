"""Metrics of the mixed residual streams (mHC) from the profiler's
capture.

The served program of a configuration with ``hc_mult > 1``
(docs/models.md) carries ``jax.named_scope``s inside ``attn`` and
``mlp``: ``mhc_coeff`` (the norm of the flattened streams, the
projection, the sigmoids), ``mhc_sinkhorn`` (the clamp, the exponential
and the iterations) and ``mhc_mix`` (a sublayer's read ``u`` and the
update of the streams), and ``mhc_fan`` around the fan-out after the
embedding and the sum before the head. A program without those scopes
(a parent commit from before them, a model with one residual stream)
gives every stat here nothing to read.

A metric's file gives ``scopes`` (a list of them, or ``"all"``),
``program`` (a regular expression on the program's name) and ``stat``.
What the streams must move is in ``readers/mhc_costs.py``; which
sequences were running, and which prompts were computed, is taken from
the client's records as the attention rooflines take them
(``readers/device_trace.py``), so a row or a pad position the program
touches for nobody lowers the share.
"""

from __future__ import annotations

import re

from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import mhc_costs
from readers.device_trace import _computed_chunks, _mean_decode_step_bytes
from readers.moe_scopes import _device
from readers.scope_ops import scope_seconds

SCOPES = ("mhc_coeff", "mhc_sinkhorn", "mhc_mix", "mhc_fan")


def _seconds(device: dict, scopes, program: str):
    """(own seconds of the operations in any of ``scopes`` inside the
    executions of ``program``, number of those executions)."""
    total, n = 0.0, 0
    for scope in (SCOPES if scopes == "all" else scopes):
        seconds, n = scope_seconds(device, scope, program, SCOPES)
        total += seconds
    return total, n


def read(run: RunData, args: dict, path: str = None):
    if "hc_mult" not in run.hf:
        return None
    device = _device(run, path)
    if device is None:
        return None
    stat, program = args["stat"], args["program"]
    seconds, n = _seconds(device, args["scopes"], program)
    if not n or not seconds:
        return None
    if stat == "scope_ms_per_execution":
        return 1e3 * seconds / n, n
    if stat == "scope_share_of_program_pct":
        mods = [m for m in device["modules"] if re.search(program, m.name)]
        return 100.0 * seconds / sum(m.dur for m in mods), n
    if stat == "decode_roofline_pct":            # HBM-bound
        least_s = (n * _mean_decode_step_bytes(run, mhc_costs)
                   / peaks_for(run.device_kind)["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat not in ("scope_ms_per_1000_prompt_tokens", "prefill_roofline_pct"):
        raise ValueError(f"mhc_scopes reader: unknown stat {stat!r}")
    tokens = sum(length for _, length in _computed_chunks(run))
    if not tokens:
        return None
    if stat == "scope_ms_per_1000_prompt_tokens":
        return 1e6 * seconds / tokens, n
    least_s = (mhc_costs.step_bytes(run.hf, tokens, executions=n)   # HBM-bound
               / peaks_for(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds, n
