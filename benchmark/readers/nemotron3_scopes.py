"""Metrics of a Nemotron-H trunk (layers that are a Mamba-2 mixer, an
attention or an expert block alone; latent experts held as one rank's
share; docs/models.md) from the profiler's capture and the program's
``dynamo_moe_*`` counters.

The served program nests ``jax.named_scope``s: ``ssm`` around a mixer
layer's whole mixer with ``ssm_conv``, ``ssm_state`` (decode) and
``ssm_scan`` (prefill) inside; ``attn`` around the attention layer's;
inside an expert layer's ``mlp``, ``moe_route`` (router, top-k, sort,
gather and combine), ``moe_latent`` (the projection into the latent
before the dispatch and the one out of it behind the combine),
``moe_experts`` (the two grouped products of the experts held) and
``moe_shared``. It counts on the device, by ``phase``, as every expert
family (``readers/granite_scopes.py`` names the counters). A program
without those scopes or counters (a parent commit from before the
family) gives every stat here nothing to read and never raises.

Here is what differs for this trunk: a scope's milliseconds a step where
the scope is this family's (``readers/moe_scopes.py`` does not know
``moe_latent``), the shares of a roofline whose costs count the layers
by the pattern's letters and an expert as two matrices in the latent
(``readers/nemotron3_costs.py``), and the joint share of scopes in the
decode program. The other cells' quantities (a scope's milliseconds a
step, the counters' ratios) are the general entries' (PR 58), read by
``readers/ssm_scopes.py`` and ``readers/moe_scopes.py``.

Which sequences were running is taken from the client's records as the
attention rooflines take it (``readers/device_trace.py``); which prompts
were computed is counted here (``_slice_prompt_tokens``).
"""

from __future__ import annotations

import re

from harness.peaks import peaks_for
from harness.rundata import RunData
from readers import nemotron3_costs
from readers.device_trace import _mean_decode_step_bytes
from readers.granite_scopes import _slice_counts
from readers.moe_scopes import _device
from readers.scope_ops import SCOPES, scope_seconds

# the mixer beside the step's other scopes, and the scopes inside the
# mixer and the expert block; an operation of ``ssm`` or ``mlp`` outside
# the fine ones belongs to none of them
TOP_SCOPES = SCOPES + ("ssm",)
FINE_SCOPES = ("ssm_conv", "ssm_state", "ssm_scan",
               "moe_route", "moe_latent", "moe_experts", "moe_shared")


def _seconds(device: dict, scopes, program: str):
    """(own seconds of the operations in any of ``scopes``, executions)."""
    total, n = 0.0, 0
    for scope in scopes:
        among = TOP_SCOPES if scope in TOP_SCOPES else FINE_SCOPES
        s, n = scope_seconds(device, scope, program, among)
        total += s
    return total, n


def _slice_prompt_tokens(run: RunData) -> int:
    """Prompt tokens of the requests whose first token arrived inside
    the captured slice: each was prefilled there, whole (the mix shares
    nothing and a family with records by slot blanks prefix hits). A
    request the window's end cut later counts too: in a closed loop of
    answers of 1024-2048 tokens none that starts in the slice ends inside
    the window, and ``readers/device_trace._computed_chunks``, which
    drops every request that did not end, finds no prompt at all."""
    s0, s1 = run.trace_slice
    return sum(r["prompt_tokens"] for r in run.records
               if r["status"] == 200 and r["token_times"]
               and s0 <= r["token_times"][0] <= s1)


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
        # the scopes are ssm_state and ssm_conv: the state is read and
        # written in the one, the window in the other; the bytes of both
        # over the time of both
        least_s = (n * _mean_decode_step_bytes(run, nemotron3_costs)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / seconds, n
    if stat == "scan_prefill_roofline_pct":      # FLOP-bound
        tokens = _slice_prompt_tokens(run)
        if not tokens:
            return None
        least_s = nemotron3_costs.scan_flops(run.hf, tokens) / peaks["flops_bf16"]
        return 100.0 * least_s / seconds, n
    if stat == "experts_decode_roofline_pct":    # HBM-bound
        counts = _slice_counts(run, args["phase"])
        if counts is None:
            return None
        active, slots, held_rows = counts
        steps = nemotron3_costs.steps_of_slots(run.hf, slots)
        per_step = nemotron3_costs.experts_decode_bytes(
            run.hf, active, held_rows) / steps
        least_s = n * per_step / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / seconds, n
    raise ValueError(f"nemotron3_scopes reader: unknown stat {stat!r}")
