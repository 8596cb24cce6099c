"""Device time by named scope (``readers/scope_ops.py``) and the
metadata stats it rests on (``harness/xplane_meta.py``), against the cut
of a traced v5e run of PR 23 (``data/v5e-spans.*``) and hand-made
operation lists."""

import json
import os

import pytest

from harness import trace, xplane_meta
from harness.trace import Event
from readers import scope_ops

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")


@pytest.fixture(scope="module")
def recorded_ops():
    with open(os.path.join(DATA, "v5e-spans.expected.json")) as f:
        return xplane_meta.load_op_events(CUT), json.load(f)


def test_metadata_reader_agrees_with_profile_data(recorded_ops):
    devices, want = recorded_ops
    t = trace.load(CUT)
    assert sorted(devices) == t.devices == [0]
    ops, mods = devices[0]["ops"], devices[0]["modules"]
    assert len(ops) == len(t.ops[0]) == want["n_ops"]
    assert len(mods) == len(t.modules[0]) == want["n_modules"]
    assert [o.name for o in ops[:50]] == [o.name for o in t.ops[0][:50]]
    assert sum(o.own for o in ops) == pytest.approx(t.busy_s[0], rel=1e-4)
    assert ops[0].start == pytest.approx(t.ops[0][0].start, abs=1e-9)
    named = [o for o in ops if o.detail]
    assert sum(o.detail.startswith("jit(") for o in named) > 0.99 * len(named)
    assert {"jit_decode_step"} <= {m.name.split("(")[0] for m in mods}


def test_scopes_partition_a_decode_step(recorded_ops):
    devices, want = recorded_ops
    per_scope = {}
    for scope in scope_ops.SCOPES:
        s, n = scope_ops.scope_seconds(devices[0], scope, "^jit_decode_")
        assert n == want["decode_programs"]
        per_scope[scope] = s
    assert sum(per_scope.values()) <= want["decode_program_s"]
    # the five scopes hold most of a step; the rest is the layer scan's
    # own slicing of the stacked weights, which no scope covers
    assert sum(per_scope.values()) > 0.85 * want["decode_program_s"]
    assert per_scope["attn"] > per_scope["mlp"] > per_scope["lm_head"] > 0


def test_sampling_takes_its_unnamed_neighbours(recorded_ops):
    devices, want = recorded_ops
    s, n = scope_ops.scope_seconds(devices[0], "sampling", "^jit_decode_")
    assert want["sampling_named_s"] < s
    assert s <= want["sampling_named_s"] + want["unnamed_in_decode_s"]
    # nothing of it inside another program's executions
    assert scope_ops.scope_seconds(devices[0], "sampling", "^jit_nothing")[1] == 0


def _run(t):
    from harness.manifest import Cell
    from harness.rundata import RunData

    cell = Cell("c", 1, {}, "k", {}, "m", {"drain_s": 1}, [], [])
    return RunData(cell=cell, hf={}, serve={}, seconds=1.0, window=(0.0, 1.0),
                   setup_seconds=0.0, records=[], prom_start={}, prom_end={},
                   device_trace=t)


ARGS = {"stat": "scope_ms_per_execution", "scope": "sampling",
        "program": "^jit_decode_"}


def test_reader_reads_the_cut(recorded_ops):
    devices, want = recorded_ops
    ms, n = scope_ops.read(_run(trace.load(CUT)), ARGS, path=CUT)
    s, _ = scope_ops.scope_seconds(devices[0], "sampling", "^jit_decode_")
    assert n == want["decode_programs"]
    assert ms == pytest.approx(1e3 * s / n)
    assert 15.0 < ms < 25.0      # PERF.md, section 5: about 19 ms a step


def test_reader_finds_nothing_where_there_is_nothing():
    """An untraced run, a run that left no capture, and a program whose
    operations carry no such scope or whose programs have other names
    (PR 22's ``jit_step``: the cut of its capture holds no stats at all)."""
    old = os.path.join(DATA, "v5e-decode-prefill.xplane.pb")
    run = _run(None)
    assert scope_ops.read(run, ARGS, path=CUT) is None
    run = _run(trace.load(old))
    assert scope_ops.read(run, ARGS, path=old) is None
    assert scope_ops.read(run, dict(ARGS, program="^jit_step"), path=old) is None
    assert scope_ops.read(run, ARGS) is None          # no profile directory
    with pytest.raises(ValueError, match="unknown stat"):
        scope_ops.read(_run(trace.load(CUT)), {"stat": "nope"}, path=CUT)


def _ops(spec):
    return [Event(n, s, d, own=d, detail=tf) for n, s, d, tf in spec]


def test_unnamed_operations_follow_neighbours_that_agree():
    mods = [Event("jit_decode_step(1)", 0.0, 10.0), Event("jit_prefill_step(2)", 20.0, 5.0)]
    ops = _ops([
        ("fusion.1", 0.0, 1.0, "jit(decode_step)/while/body/closed_call/attn/dot_general:"),
        ("fusion.2", 1.0, 1.0, ""),                      # attn before, lm_head after: nobody's
        ("fusion.3", 2.0, 1.0, "jit(decode_step)/lm_head/dot_general:"),
        ("fusion.4", 3.0, 1.0, "jit(decode_step)/sampling/jit(sort)/sort:"),
        ("fusion.5", 4.0, 2.0, ""),                      # between two sampling ops
        ("sort.9", 6.0, 1.0, ""),
        ("fusion.6", 7.0, 1.0, "jit(decode_step)/sampling/gather:"),
        ("fusion.7", 8.0, 1.0, ""),                      # nothing named after it
        ("fusion.8", 21.0, 3.0, "jit(prefill_step)/sampling/gather:"),
    ])
    device = {"modules": mods, "ops": ops}
    assert scope_ops.scope_seconds(device, "sampling", "^jit_decode_") == (5.0, 1)
    assert scope_ops.scope_seconds(device, "attn", "^jit_decode_") == (1.0, 1)
    assert scope_ops.scope_seconds(device, "sampling", "^jit_prefill_") == (3.0, 1)
    assert scope_ops.scope_seconds(device, "sampling", "^jit_") == (8.0, 2)
    # a scope's name inside another word is not the scope
    ops[0].detail = "jit(decode_step)/resampling/x:"
    assert scope_ops.scope_seconds(device, "sampling", "^jit_decode_")[0] == 5.0
