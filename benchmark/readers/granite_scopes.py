"""Metrics of a Granite 4.0-H trunk (layers that are a Mamba-2 mixer or
attention, routed experts held as one rank's share behind each;
docs/models.md) from the profiler's capture and the program's
``dynamo_moe_*`` counters.

The served program nests ``jax.named_scope``s: ``ssm`` around a mixer
layer's whole mixer with ``ssm_conv``, ``ssm_state`` (decode) and
``ssm_scan`` (prefill) inside; ``attn`` around an attention layer's;
inside every layer's ``mlp``, ``moe_route`` (router, top-k, sort, gather
and combine), ``moe_experts`` (the grouped products of the experts held)
and ``moe_shared``. It counts on the device, by ``phase``: the held
experts that had rows (``dynamo_moe_active_experts_total`` of
``dynamo_moe_expert_slots_total``), the picks of real tokens
(``dynamo_moe_routed_rows_total``) and those of them that fell on a held
expert (``dynamo_moe_held_picks_total``). A program without those scopes
or counters (a parent commit from before them, another family) gives
every stat here nothing to read and never raises.

Here is only what differs for this trunk: the three shares of a
roofline, whose costs count mixer layers by ``layer_types`` and experts
by ``num_local_experts`` (``readers/granite_costs.py``), and the joint
share of two scopes in the decode program. A scope's milliseconds a
step and the counters' ratios are the same quantities as in any other
cell and are read by ``readers/ssm_scopes.py`` and
``readers/moe_scopes.py`` under the one entry every cell reads them by
(PR 58; this trunk's cell is on those entries' lists).

What the state and the held experts must move and multiply is in
``readers/granite_costs.py``, counted from the configuration's keys;
which sequences were running and which prompts were computed is taken
from the client's records as the attention rooflines take it
(``readers/device_trace.py``).
"""

from __future__ import annotations

import re

from harness import prom
from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import granite_costs
from readers.device_trace import _computed_chunks, _mean_decode_step_bytes
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

# the mixer beside the step's other scopes, and the scopes inside the
# mixer and the feed-forward; an operation of ``ssm`` or ``mlp`` outside
# the fine ones belongs to none of them
TOP_SCOPES = SCOPES + ("ssm",)
FINE_SCOPES = ("ssm_conv", "ssm_state", "ssm_scan",
               "moe_route", "moe_experts", "moe_shared")
COUNTERS = ("dynamo_moe_active_experts_total", "dynamo_moe_expert_slots_total",
            "dynamo_moe_held_picks_total")


def _seconds(device: dict, scopes, program: str):
    """(own seconds of the operations in any of ``scopes``, executions)."""
    total, n = 0.0, 0
    for scope in scopes:
        among = TOP_SCOPES if scope in TOP_SCOPES else FINE_SCOPES
        s, n = scope_seconds(device, scope, program, among)
        total += s
    return total, n


def _slice_counts(run: RunData, phase: str):
    """Deltas of the counters for ``phase`` between the /metrics samples
    that bracket the captured slice: (held experts with a row, held
    expert slots, picks on a held expert), or None where the program has
    no such counter or nothing was counted."""
    if not run.prom_samples or run.trace_slice is None:
        return None
    s0, s1 = run.trace_slice
    before = [s for t, s in run.prom_samples if t <= s0]
    after = [s for t, s in run.prom_samples if t >= s1]
    lo = before[-1] if before else run.prom_samples[0][1]
    hi = after[0] if after else run.prom_samples[-1][1]
    if prom.value(hi, COUNTERS[2]) is None:      # a program without the share
        return None
    got = tuple(prom.delta(lo, hi, m, {"phase": phase}) for m in COUNTERS)
    return got if got[1] > 0 else None


def read(run: RunData, args: dict, path: str = None):
    device = _device(run, path)
    if device is None:
        return None
    stat, program = args["stat"], args["program"]
    seconds, n = _seconds(device, args["scopes"], program)
    if not n or not seconds:
        return None
    if stat == "scope_share_of_program_pct":
        mods = [m for m in device["modules"] if re.search(program, m.name)]
        return 100.0 * seconds / sum(m.dur for m in mods), n
    peaks = peaks_for(run.device_kind)
    if stat == "state_decode_roofline_pct":      # HBM-bound
        # the scopes are ssm_state and ssm_conv: the state is read and
        # written in the one, the window in the other; the bytes of both
        # over the time of both
        least_s = (n * _mean_decode_step_bytes(run, granite_costs)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "scan_prefill_roofline_pct":      # FLOP-bound
        tokens = sum(length for _, length in _computed_chunks(run))
        if not tokens:
            return None
        least_s = granite_costs.scan_flops(run.hf, tokens) / peaks["flops_bf16"]
        return 100.0 * least_s / seconds, n
    if stat == "experts_decode_roofline_pct":    # HBM-bound
        counts = _slice_counts(run, args["phase"])
        if counts is None:
            return None
        active, slots, held_rows = counts
        steps = granite_costs.steps_of_slots(run.hf, slots)
        per_step = granite_costs.experts_decode_bytes(
            run.hf, active, held_rows) / steps
        least_s = n * per_step / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"granite_scopes reader: unknown stat {stat!r}")
