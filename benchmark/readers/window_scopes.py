"""Metrics of a trunk whose attention layers are of two kinds, window
and full, each over its own kind of page (the AFMoE family,
docs/models.md), from the profiler's capture and the program's
``dynamo_kv_window_pages_*`` counters.

The served program nests ``jax.named_scope``s inside ``attn``:
``attn_window`` or ``attn_full`` around a whole attention sublayer
(norm, projections, rope, scatter, kernel, gate, output), and inside
those ``kv_window`` or ``kv_full`` around the kernel alone. A program
without those scopes or counters (a parent commit from before them,
another family) gives every stat here nothing to read.

What the two kinds of layer must read is the configuration's module of
``benchmark/attention_costs``; which sequences were running is taken
from the client's records as the attention rooflines take it.
"""

from __future__ import annotations

import re

from harness.manifest import architecture_module
from harness.peaks import peaks_for
from harness.rundata import RunData
from readers.device_trace import _mean_decode_step_bytes
from readers import moe_scopes
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

# ``attn`` gives way to the kind's own scope, so that an operation the
# compiler left without a name between a window layer's and a
# feed-forward's belongs to neither
SUBLAYER_SCOPES = tuple(s for s in SCOPES if s != "attn") + (
    "attn_window", "attn_full")
KERNEL_SCOPES = ("kv_window", "kv_full")


def _seconds(device: dict, scopes, program: str):
    """(seconds in any of ``scopes``, executions of ``program``)."""
    total, n = 0.0, 0
    for scope in scopes:
        among = KERNEL_SCOPES if scope in KERNEL_SCOPES else SUBLAYER_SCOPES
        seconds, n = scope_seconds(device, scope, program, among)
        total += seconds
    return total, n


def read(run: RunData, args: dict, path: str = None):
    stat = args["stat"]
    if stat == "counter_ratio_pct":      # the counters' ratio over the window
        return moe_scopes.read(run, args)

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
    if stat == "window_full_decode_roofline_pct":     # HBM-bound
        cost = architecture_module(run.cell.config, run.cell.config_name,
                                   "attention_cost")
        least_s = (n * _mean_decode_step_bytes(run, cost)
                   / peaks_for(run.device_kind)["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    raise ValueError(f"window_scopes reader: unknown stat {stat!r}")
