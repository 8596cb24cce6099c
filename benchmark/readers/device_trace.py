"""Metrics from the profiler's capture of a slice of the window.

Programs are told apart by what runs inside them, not by their names:
the engine's decode and prefill steps are one jitted function at
different shapes, so both are ``jit_step(<fingerprint>)`` in the trace.
A metric's file gives ``with_op``, a regular expression on operation
names; a program execution belongs to the metric when an operation that
matches ran inside it.

What the attention of a configuration must read and multiply is not
known here: the configuration's file names a module of
``benchmark/attention_costs`` for it. This reader keeps the trace's
side: which executions, the kernel's time, the peaks, the division.
"""

from __future__ import annotations

import re

from harness.manifest import architecture_module
from harness.peaks import peaks_for
from harness.rundata import RunData, failed

BLOCK = 16   # the engine's kv_block_size default: a cached prefix is whole blocks


def _matches(pattern: str, ev) -> bool:
    # the name only: an event's full HLO line also names its operands
    return bool(re.search(pattern, ev.name))


def _modules_with(trace, device: int, pattern: str):
    """Program executions on ``device`` inside which an op matching
    ``pattern`` ran, and those ops."""
    ops = [o for o in trace.ops[device] if _matches(pattern, o)]
    mods, hit_ops, i = [], [], 0
    for m in trace.modules[device]:
        end = m.start + m.dur
        while i < len(ops) and ops[i].start < m.start:
            i += 1
        j = i
        while j < len(ops) and ops[j].start < end:
            j += 1
        if j > i:
            mods.append(m)
            hit_ops.extend(ops[i:j])
        i = j
    return mods, hit_ops


def _tp(run: RunData) -> int:
    return int(run.serve.get("tensor_parallel_size", 1))


def _slice_requests(run: RunData):
    """Requests whose first token arrived inside the captured slice."""
    s0, s1 = run.trace_slice
    return [r for r in run.records
            if not failed(r) and s0 <= r["token_times"][0] <= s1]


def _mean_decode_step_bytes(run: RunData, cost) -> float:
    """Time-average over the slice of the bytes one decode step must
    read on one device: at each of 64 instants, ``cost``'s answer for
    the contexts of the sequences running then."""
    s0, s1 = run.trace_slice
    n, total = 64, 0
    for k in range(n):
        t = s0 + (k + 0.5) * (s1 - s0) / n
        contexts = []
        for r in run.records:
            times = r["token_times"]
            if not times or not times[0] <= t <= times[-1]:
                continue
            emitted = sum(c for tt, c in zip(times, r["chunk_tokens"]) if tt <= t)
            contexts.append(r["prompt_tokens"] + emitted)
        total += cost.decode_step_bytes(run.hf, _tp(run), run.cache_itemsize,
                                        contexts)
    return total / n


def _computed_chunks(run: RunData):
    """(start, length) of the prompt tokens each slice request had to
    compute: all of them, or what follows the shared prefix where an
    earlier request of its group had its first token before this one
    was sent (the prefix cache then holds the prefix's whole blocks)."""
    first_token = {}
    for r in run.records:
        if r["group"] and not failed(r):
            g = first_token.setdefault(r["group"], [])
            g.append(r["token_times"][0])
    out = []
    for r in _slice_requests(run):
        cached = 0
        if r["group"] and any(t < r["send"] for t in first_token[r["group"]]):
            cached = (r.get("prefix_tokens", 0) // BLOCK) * BLOCK
        out.append((cached, r["prompt_tokens"] - cached))
    return out


def read(run: RunData, args: dict):
    trace = run.device_trace
    if trace is None:
        return None
    stat = args["stat"]
    d0 = trace.devices[0]
    if stat == "idle_pct":
        # the chip that waits longest
        return 100.0 * max(trace.idle_share(d) for d in trace.devices)
    if stat == "op_share_of_busy_pct":
        own = sum(o.own for o in trace.ops[d0] if _matches(args["op"], o))
        return 100.0 * own / trace.busy_s[d0] if trace.busy_s[d0] else None

    if stat != "decode_kernel_roofline_pct":     # HBM-bound
        raise ValueError(f"device_trace reader: unknown stat {stat!r}")
    mods, kernel_ops = _modules_with(trace, d0, args["with_op"])
    kernel_s = sum(o.own for o in kernel_ops)
    if not kernel_s:
        return None
    cost = architecture_module(run.cell.config, run.cell.config_name,
                               "attention_cost")
    per_step = _mean_decode_step_bytes(run, cost)
    least_s = len(mods) * per_step / peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s, len(mods)
