"""Device time by the program's named scopes.

The step's traced function carries ``jax.named_scope``s (``embed``,
``attn``, ``mlp``, ``lm_head``, ``sampling``); the compiler keeps the
scope in each lowered operation's name stack, and the capture has it as
the ``tf_op`` stat of the operation's metadata
(``harness/xplane_meta.py``). A metric's file gives ``scope`` (the
name-stack component) and ``program`` (a regular expression on the
``XLA Modules`` names, e.g. the decode programs): the value is the own
time of the operations in that scope inside those programs' executions,
per execution. A program without such names or scopes (a parent commit
from before them) gives nothing to read.
"""

from __future__ import annotations

import os
import re

from harness import manifest
from harness.rundata import RunData
from harness.trace import find_xplane
from harness.xplane_meta import load_op_events

# the scopes of the step's traced function (models/llama.py,
# engine/model_runner.py)
SCOPES = ("embed", "attn", "mlp", "lm_head", "sampling")


def profile_dir(run: RunData) -> str:
    """Where run.py had the profiler write this run's capture."""
    return os.path.join(manifest.ROOT, ".bench_work", run.cell.name, "profile")


def _scope_of(detail: str, scopes) -> str:
    """The first of ``scopes`` that is a component of the name stack."""
    parts = detail.split("/")
    return next((p for p in parts if p in scopes), "")


def scope_seconds(device: dict, scope: str, program: str, scopes=SCOPES):
    """(own seconds of ops in ``scope`` inside executions of programs
    matching ``program``, number of those executions).

    The compiler makes some operations of its own (a fusion of a
    reshaped gather, a sort it split off) and gives them no name stack.
    Such an operation takes the scope of its neighbours in time when the
    nearest named operation before it and the nearest after it, inside
    the same execution, agree; otherwise it belongs to no scope."""
    mods = [m for m in device["modules"] if re.search(program, m.name)]
    ops = device["ops"]
    total, i = 0.0, 0
    for m in mods:                       # both lists are sorted by start
        end = m.start + m.dur
        while i < len(ops) and ops[i].start < m.start:
            i += 1
        j = i
        while j < len(ops) and ops[j].start < end:
            j += 1
        inside = ops[i:j]
        named = [_scope_of(o.detail, scopes) if o.detail else None
                 for o in inside]
        after, nxt = [None] * len(inside), None
        for k in range(len(inside) - 1, -1, -1):
            after[k] = nxt
            if named[k] is not None:
                nxt = named[k]
        before = None
        for k, o in enumerate(inside):
            here = named[k]
            if here is None:
                here = before if before == after[k] else ""
            else:
                before = here
            if here == scope:
                total += o.own
        i = j
    return total, len(mods)


def read(run: RunData, args: dict, path: str = None):
    if run.device_trace is None:
        return None
    path = path or find_xplane(profile_dir(run))
    if path is None:
        return None
    devices = load_op_events(path)
    if not devices:
        return None
    stat = args["stat"]
    if stat == "scope_ms_per_execution":
        seconds, n = scope_seconds(devices[min(devices)], args["scope"],
                                   args["program"])
        if not n or not seconds:
            return None
        return 1e3 * seconds / n, n
    raise ValueError(f"scope_ops reader: unknown stat {stat!r}")
