"""Metrics of a MiMo-V2 trunk (window and full attention layers whose
pages differ in shape, a learned sink in the window layers, routed
experts held as one rank's share; docs/models.md) from the profiler's
capture and the program's counters.

The served program nests ``jax.named_scope``s inside ``attn``:
``attn_window`` or ``attn_full`` around a whole attention sublayer, and
inside those ``kv_window`` or ``kv_full`` around the kernel alone;
inside every ``mlp``, ``moe_route`` and ``moe_experts``. The scheduler
counts the query-key pairs a layer of each kind is allowed for every
prefill chunk it dispatches (``dynamo_attention_prefill_pairs_total
{kind}``, beside ``dynamo_attention_prefill_chunks_total``), and the
trace records which attention route each compiled program took
(``dynamo_engine_attention_route_total{route}``). A program without
those scopes or counters (a parent commit from before them, another
family) gives every stat here nothing to read and never raises.

The shares of a roofline are of the work itself: a kind's decode bytes
are the configuration's attention-cost module's part for it
(``window_step_bytes``, ``full_step_bytes``) at the contexts of the
sequences running in the slice; a kind's prefill FLOPs the pairs the
scheduler counted a chunk, over the /metrics samples that bracket the
slice, times the chunks the slice ran; the experts' as
``readers/dots3_scopes.py`` counts them, through ``readers/
mimo_costs.py``.
"""

from __future__ import annotations

import types

from harness import prom
from harness.manifest import architecture_module
from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import mimo_costs
from readers.device_trace import _mean_decode_step_bytes
from readers.granite_scopes import _slice_counts
from readers.moe_scopes import _device
from readers.scope_ops import scope_seconds

KERNEL_SCOPES = ("kv_window", "kv_full")
FINE_SCOPES = KERNEL_SCOPES + ("moe_route", "moe_experts")
PAIRS = "dynamo_attention_prefill_pairs_total"
CHUNKS = "dynamo_attention_prefill_chunks_total"
ROUTES = "dynamo_engine_attention_route_total"


def _seconds(device: dict, scopes, program: str):
    """(own seconds of the operations in any of ``scopes``, executions)."""
    total, n = 0.0, 0
    for scope in scopes:
        s, n = scope_seconds(device, scope, program, FINE_SCOPES)
        total += s
    return total, n


def _bracket(run: RunData):
    """The /metrics samples that bracket the captured slice, or None."""
    if not run.prom_samples or run.trace_slice is None:
        return None
    s0, s1 = run.trace_slice
    before = [s for t, s in run.prom_samples if t <= s0]
    after = [s for t, s in run.prom_samples if t >= s1]
    return (before[-1] if before else run.prom_samples[0][1],
            after[0] if after else run.prom_samples[-1][1])


def _pairs_a_chunk(run: RunData, kind: str):
    """Pairs one layer of ``kind`` is allowed, a prefill chunk, over the
    samples that bracket the slice; None without the counters."""
    ends = _bracket(run)
    if ends is None or prom.value(ends[1], CHUNKS) is None:
        return None
    chunks = prom.delta(*ends, CHUNKS)
    if chunks <= 0:
        return None
    return prom.delta(*ends, PAIRS, {"kind": kind}) / chunks


def _xla_routes(run: RunData):
    """Compiled programs whose attention took the XLA gather route, at
    the window's end; None for a program without the counter."""
    if prom.value(run.prom_end, ROUTES) is None:
        return None
    return prom.value(run.prom_end, ROUTES, {"route": "xla"}) or 0.0


def read(run: RunData, args: dict, path: str = None):
    stat = args["stat"]
    if stat == "xla_routes":
        return _xla_routes(run)
    device = _device(run, path)
    if device is None:
        return None
    program = args["program"]
    seconds, n = _seconds(device, args["scopes"], program)
    if not n or not seconds:
        return None
    if stat == "scope_ms_per_execution":
        return 1e3 * seconds / n, n
    peaks = peaks_for(run.device_kind)
    cost = architecture_module(run.cell.config, run.cell.config_name,
                               "attention_cost")
    if stat == "kind_decode_roofline_pct":       # HBM-bound
        part = getattr(cost, args["bytes"], None)
        if part is None:        # another configuration's cost module
            return None
        least_s = (n * _mean_decode_step_bytes(
            run, types.SimpleNamespace(decode_step_bytes=part))
            / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "kind_prefill_roofline_pct":      # FLOP-bound
        flops = getattr(cost, args["flops"], None)
        pairs = _pairs_a_chunk(run, args["kind"])
        if flops is None or pairs is None:
            return None
        least_s = n * flops(run.hf, pairs) / peaks["flops_bf16"]
        return 100.0 * least_s / seconds, n
    if stat == "experts_decode_roofline_pct":    # HBM-bound
        counts = _slice_counts(run, args["phase"])
        if counts is None:
            return None
        active, slots, held_rows = counts
        steps = mimo_costs.steps_of_slots(run.hf, slots)
        per_step = mimo_costs.experts_decode_bytes(
            run.hf, active, held_rows) / steps
        least_s = n * per_step / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"mimo_scopes reader: unknown stat {stat!r}")
