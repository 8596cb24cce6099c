"""Metrics of a dots3_note trunk (latent layers of two kinds, the full
kind behind a learned indexer, routed experts held as one rank's share;
docs/models.md) from the profiler's capture and the program's counters.

The served program nests ``jax.named_scope``s inside ``attn``:
``attn_full`` or ``attn_window`` around a whole attention sublayer;
inside a full layer ``dsa_index`` (the indexer's projections and its
scores of the live keys), ``dsa_select`` (the cutoff, the mask, the
picked tokens' list) and ``dsa_attend`` (the gather of the picked rows
and the product over them), the last two and the scores inside
``mla_cache``; inside a window layer ``swa_latent`` around the latent
decode kernel; inside every ``mlp``, ``moe_route``, ``moe_experts`` and
``moe_shared``. A program without those scopes or counters (a parent
commit from before them, another family) gives every stat here nothing
to read and never raises.

The shares of a roofline are of the work itself: the bytes a route must
read are the configuration's attention-cost module's part for it
(``index_step_bytes``, ``picked_step_bytes``, ``window_step_bytes``), at
the contexts of the sequences running in the slice, as the attention
rooflines take them (``readers/device_trace.py``); the experts' as
``readers/kimi_scopes.py`` counts them, through ``readers/
dots3_costs.py``.
"""

from __future__ import annotations

import re
import types

from harness.manifest import architecture_module
from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import dots3_costs
from readers.device_trace import _mean_decode_step_bytes
from readers.granite_scopes import _slice_counts
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

# ``attn`` gives way to the kind's own scope (readers/window_scopes.py)
SUBLAYER_SCOPES = tuple(s for s in SCOPES if s != "attn") + (
    "attn_full", "attn_window")
FINE_SCOPES = ("dsa_index", "dsa_select", "dsa_attend", "swa_latent",
               "moe_route", "moe_experts", "moe_shared")


def _seconds(device: dict, scopes, program: str):
    """(own seconds of the operations in any of ``scopes``, executions)."""
    total, n = 0.0, 0
    for scope in scopes:
        among = SUBLAYER_SCOPES if scope in SUBLAYER_SCOPES else FINE_SCOPES
        s, n = scope_seconds(device, scope, program, among)
        total += s
    return total, n


def read(run: RunData, args: dict, path: str = None):
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
    peaks = peaks_for(run.device_kind)
    if stat == "route_decode_roofline_pct":      # HBM-bound
        cost = architecture_module(run.cell.config, run.cell.config_name,
                                   "attention_cost")
        part = getattr(cost, args["bytes"], None)
        if part is None:        # another configuration's cost module
            return None
        least_s = (n * _mean_decode_step_bytes(
            run, types.SimpleNamespace(decode_step_bytes=part))
            / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "experts_decode_roofline_pct":    # HBM-bound
        counts = _slice_counts(run, args["phase"])
        if counts is None:
            return None
        active, slots, held_rows = counts
        steps = dots3_costs.steps_of_slots(run.hf, slots)
        per_step = dots3_costs.experts_decode_bytes(
            run.hf, active, held_rows) / steps
        least_s = n * per_step / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"dots3_scopes reader: unknown stat {stat!r}")
