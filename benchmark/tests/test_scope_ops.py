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


# ---- PR 58: one name a reading. Where the surviving entry's reader is
# not the merged one's, both read the same number from the same capture.

def _both(run, path, old, new):
    """(what the merged entry's reader read, what the surviving one's
    reads): (reader module name, args) each."""
    import importlib

    def read(which):
        name, args = which
        module = importlib.import_module("readers." + name)
        return module.read(run, args, path=path)
    return read(old), read(new)


def _by_kernel(t):
    """The decode program's time an execution as three cells read it
    until PR 58 (``device_trace``'s stat of that name, gone with its
    last entry): over the executions that hold the decode kernel."""
    from readers import device_trace

    mods, _ = device_trace._modules_with(t, 0, "paged_decode_attention")
    return (1e3 * sum(m.dur for m in mods) / len(mods), len(mods)) if mods else None


PROGRAM_BY_NAME = ("moe_scopes", {"stat": "program_ms_per_execution",
                                  "program": "^jit_decode_"})
ATTN_BY_TRUNK = ("kimi_scopes", {"stat": "scope_ms_per_execution",
                                 "scopes": ["attn"], "program": "^jit_decode_"})
ATTN_BY_NAME = ("scope_ops", {"stat": "scope_ms_per_execution", "scope": "attn",
                              "program": "^jit_decode_"})


@pytest.mark.parametrize("cut, steps", [("v5e-spans", 10), ("v5e-sync-parts", 5),
                                        ("v5e-sync-parts-early", 4),
                                        ("v5e-step-in-flight", 7),
                                        ("v5e-planes-first-capture", 6),
                                        ("v5e-planes-second-capture", 6)])
def test_the_decode_programs_time_by_its_kernel_and_by_its_name(cut, steps):
    """Three cells read the decode program's time through the kernel
    inside it and nine through its name, under six names; the name
    reads the executions the kernel read. (The two loaders round a
    picosecond differently: ``ProfileData`` gives whole nanoseconds.)"""
    from readers import moe_scopes

    path = os.path.join(DATA, cut + ".xplane.pb")
    t = trace.load(path)
    old, new = _by_kernel(t), moe_scopes.read(_run(t), PROGRAM_BY_NAME[1], path=path)
    assert old[1] == new[1] == steps
    assert new[0] == pytest.approx(old[0], rel=1e-6)


def test_a_program_from_before_the_names_has_no_decode_program_to_read():
    """PR 22's capture: decode and prefill are both ``jit_step``."""
    from readers import moe_scopes

    path = os.path.join(DATA, "v5e-decode-prefill.xplane.pb")
    t = trace.load(path)
    assert _by_kernel(t)[1] == 9
    assert moe_scopes.read(_run(t), PROGRAM_BY_NAME[1], path=path) is None


def test_the_attention_sublayers_time_by_either_reader_on_the_cut():
    """The latent sublayer's time a step was one trunk's own reader's
    under the trunk's own name; the general reader reads the same
    operations (a scope beside ``attn`` takes none of them)."""
    old, new = _both(_run(trace.load(CUT)), CUT, ATTN_BY_TRUNK, ATTN_BY_NAME)
    assert old == new and old[1] == 10 and 11.0 < old[0] < 13.0


def _step_of_every_family():
    """Hand-made: two executions of a decode program whose operations
    carry the scopes of every family the merged entries are read in, an
    operation the compiler left without a name between two of a scope's
    and one between two scopes."""
    ops, mods = [], []
    for i in range(2):
        t0 = i * 0.03
        mods.append(Event("jit_decode_block(1)", t0, 0.020))
        stack = "jit(decode_block)/while/body/"
        for k, (dur, scope) in enumerate((
                (0.0004, "attn/dot_general"),
                (0.0002, "attn/block_attn/pallas_call"),
                (0.0003, "attn/mla_cache/dot_general"),
                (0.0005, "attn_full/dsa_attend/gather"),
                (0.0001, None),                    # attn_full before, attn_window after
                (0.0006, "attn_window/swa_latent/pallas_call"),
                (0.0007, "kda/kda_state/pallas_call"),
                (0.0008, "ssm/ssm_state/pallas_call"),
                (0.0010, "mlp/moe_route/top_k"),
                (0.0040, "mlp/moe_experts/pallas_call"),
                (0.0002, None),                    # between two of the experts'
                (0.0050, "mlp/moe_experts/pallas_call"),
                (0.0003, None),                    # the experts before, shared after
                (0.0009, "mlp/moe_shared/dot_general"),
                (0.0011, "sampling/reduce"))):
            ops.append(Event("fusion.%d" % k, t0 + 0.001 * (k + 1), dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


@pytest.mark.parametrize("old, new, want_ms", [
    # the experts' products: the block family's reader and the general one
    (("block_scopes", {"stat": "scope_ms_per_execution",
                       "scopes": ["moe_experts"], "program": "^jit_decode_"}),
     ("moe_scopes", {"stat": "scope_ms_per_execution", "scope": "moe_experts",
                     "program": "^jit_decode_"}), 4.0 + 0.2 + 5.0),
    # the latent sublayer: the delta-rule trunk's reader and the general one
    (ATTN_BY_TRUNK, ATTN_BY_NAME, 0.4 + 0.2 + 0.3),
    # both kinds' sublayers of the step: the indexed trunk's reader and
    # the two-kinds-of-page one
    (("dots3_scopes", {"stat": "scope_share_of_program_pct",
                       "scopes": ["attn_full", "attn_window"],
                       "program": "^jit_decode_"}),
     ("window_scopes", {"stat": "scope_share_of_program_pct",
                        "scopes": ["attn_window", "attn_full"],
                        "program": "^jit_decode_"}), 100 * (0.5 + 0.6) / 20.0),
])
def test_two_readers_of_one_reading_agree_on_a_step_of_every_family(
        monkeypatch, old, new, want_ms):
    from readers import moe_scopes

    device = _step_of_every_family()
    for module in (moe_scopes, scope_ops):
        monkeypatch.setattr(module, "load_op_events", lambda path: {0: device})
    was, now = _both(_run(trace.load(CUT)), CUT, old, new)
    assert was == now == (pytest.approx(want_ms), 2)
