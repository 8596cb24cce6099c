"""Metrics of a Kimi Linear trunk (layers that are Kimi Delta Attention
or latent attention, routed experts held as one rank's share behind all
but the first; docs/models.md) from the profiler's capture and the
program's ``dynamo_moe_*`` counters.

The served program nests ``jax.named_scope``s: ``kda`` around a KDA
layer's whole mixer with ``kda_conv``, ``kda_gate``, ``kda_state``
(decode: the kernel that reads a live row's state once and writes it
once, and the small operations that lay its operands out) and
``kda_scan`` (prefill: the chunked scan) inside; ``attn`` around a
latent layer's with ``mla_cache`` inside; inside every layer's ``mlp``,
``moe_route``, ``moe_experts`` (the grouped products of the experts
held) and ``moe_shared``. It counts on the device, by ``phase``: the
held experts that had rows (``dynamo_moe_active_experts_total`` of
``dynamo_moe_expert_slots_total``), the picks of real tokens
(``dynamo_moe_routed_rows_total``) and those of them that fell on a held
expert (``dynamo_moe_held_picks_total``). A program without those scopes
or counters (a parent commit from before them, another family) gives
every stat here nothing to read and never raises.

Here is what differs for this trunk: a scope's milliseconds a step where
the scope is this family's (``readers/scope_ops.py`` and
``readers/moe_scopes.py`` do not know ``kda``), the shares of a roofline
whose costs count KDA layers and expert layers from the configuration's
lists (``readers/kimi_costs.py``), and the joint share of two scopes in
the decode program. The other cells' quantities (a fine scope of the
experts, the counters' ratios, the latent kernel's share through the
configuration's attention-cost module, the latent sublayer's time) are
read by ``readers/moe_scopes.py`` and ``readers/scope_ops.py`` under
the one entry every cell reads them by (PR 58).

Which sequences were running is taken from the client's records as the
attention rooflines take it (``readers/device_trace.py``).

No stat here reads a prefill program: in the one cell of this trunk a
slot is refilled once in about 1500 steps, so the capture's four seconds
hold between no prefill and four, and a metric of the ``kda_scan`` scope
would be missing from some traced runs (PERF.md section 7, left by PR
52). The scope is in every capture that holds a prefill all the same
(``python -m harness.trace`` lists it), and ``kimi_costs.scan_flops``
counts what it must multiply.
"""

from __future__ import annotations

import re

from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import kimi_costs
from readers.device_trace import _mean_decode_step_bytes
from readers.granite_scopes import _slice_counts
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

# the KDA mixer beside the step's other scopes, and the scopes inside
# the mixers and the feed-forward; an operation of ``kda``, ``attn`` or
# ``mlp`` outside the fine ones belongs to none of them
TOP_SCOPES = SCOPES + ("kda",)
FINE_SCOPES = ("kda_conv", "kda_gate", "kda_state", "kda_scan", "mla_cache",
               "moe_route", "moe_experts", "moe_shared")


def _seconds(device: dict, scopes, program: str):
    """(own seconds of the operations in any of ``scopes``, executions)."""
    total, n = 0.0, 0
    for scope in scopes:
        among = TOP_SCOPES if scope in TOP_SCOPES else FINE_SCOPES
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
    if stat == "state_decode_roofline_pct":      # HBM-bound
        least_s = (n * _mean_decode_step_bytes(run, kimi_costs)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "experts_decode_roofline_pct":    # HBM-bound
        counts = _slice_counts(run, args["phase"])
        if counts is None:
            return None
        active, slots, held_rows = counts
        steps = kimi_costs.steps_of_slots(run.hf, slots)
        per_step = kimi_costs.experts_decode_bytes(
            run.hf, active, held_rows) / steps
        least_s = n * per_step / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"kimi_scopes reader: unknown stat {stat!r}")
