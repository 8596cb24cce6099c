"""Metrics of a family whose decode unit is a block (generation by
diffusion over blocks, docs/models.md), from the profiler's capture and
the program's ``dynamo_scheduler_block_*`` and ``dynamo_moe_*`` counters.

The served program's one decode program, ``jit_decode_block``, nests
``jax.named_scope``s: ``block_attn`` inside ``attn`` around the verify
kernel's call alone, ``moe_route`` and ``moe_experts`` inside ``mlp``,
``sampling`` (the head's logits to a token and its log-probability at
every position of every block) and ``block_select`` (the confidence and
the choice of what the pass unmasks) after the trunk. The scheduler
counts the rows of its block passes by kind (``denoise`` | ``commit``),
the blocks committed and the tokens they emitted. A program without
those scopes or counters (a parent commit from before them, another
family) gives every stat here nothing to read.

What the block's attention must read is the configuration's module of
``benchmark/attention_costs`` (K and V of every key of the context, once
a pass whatever the number of queries a row: the kernel walks a row's
pages once for all of its block's queries); which sequences were running
is taken from the client's records as the attention rooflines take it.
What the expert products must move is ``readers/expert_costs.py``.
"""

from __future__ import annotations

from harness import prom
from harness.manifest import architecture_module
from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import expert_costs
from readers.device_trace import _mean_decode_step_bytes
from readers.moe_scopes import _device, _slice_counts
from readers.scope_ops import scope_seconds

# the innermost scopes this reader tells apart; an operation of ``attn``
# or ``mlp`` outside them belongs to none of these
FINE_SCOPES = ("block_attn", "moe_route", "moe_experts", "sampling",
               "block_select", "lm_head", "embed")


def _counter_ratio(run: RunData, args: dict):
    if not run.prom_start or not run.prom_end:
        return None
    den = prom.delta(run.prom_start, run.prom_end, args["denominator"],
                     args.get("denominator_labels"))
    if not den > 0:
        return None
    num = prom.delta(run.prom_start, run.prom_end, args["numerator"],
                     args.get("numerator_labels"))
    return float(args.get("scale", 1)) * num / den


def read(run: RunData, args: dict, path: str = None):
    stat = args["stat"]
    if stat == "counter_ratio":      # the counters' ratio over the window
        return _counter_ratio(run, args)
    device = _device(run, path)
    if device is None:
        return None
    program = args["program"]

    seconds, n = 0.0, 0
    for scope in args["scopes"]:
        s, n = scope_seconds(device, scope, program, FINE_SCOPES)
        seconds += s
    if not n or not seconds:
        return None
    if stat == "scope_ms_per_execution":
        return 1e3 * seconds / n, n
    peaks = peaks_for(run.device_kind)
    if stat == "block_attn_roofline_pct":        # HBM-bound
        cost = architecture_module(run.cell.config, run.cell.config_name,
                                   "attention_cost")
        least_s = n * _mean_decode_step_bytes(run, cost) / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    if stat == "experts_decode_roofline_pct":    # HBM-bound
        counts = _slice_counts(run, args["phase"])
        if counts is None:
            return None
        active, slots, rows = counts
        # expert slots a pass: every expert of every layer
        passes = slots / (int(run.hf["num_experts"])
                          * int(run.hf["num_hidden_layers"]))
        per_pass = expert_costs.decode_bytes(run.hf, active, rows) / passes
        least_s = n * per_pass / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"block_scopes reader: unknown stat {stat!r}")
