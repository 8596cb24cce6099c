"""What the ``falcon-h1-chat`` cell brings as code: the recurrent
state's byte and FLOP model (``readers/ssm_costs.py``) and the reader of
the mixer's scopes (``readers/ssm_scopes.py``), against hand-made inputs
and the cut of a traced v5e run of PR 23 (``data/v5e-spans.*``: a
program from before the scopes, which has to give nothing to read and
never raise)."""

import os

import pytest

from harness import trace
from harness.manifest import Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import moe_scopes, ssm_costs, ssm_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
FALCON = load_cell("falcon-h1-chat").config
METRICS = ("ssm_mixer_ms_per_step", "ssm_state_ms_per_step",
           "ssm_decode_roofline", "ssm_prefill_scan_roofline",
           "ssm_share_of_decode_step")
# the mixer's and the state's time a step and the mixer's share of it
# are read in every cell with a Mamba-2 mixer; the two shares of a
# roofline count this family's layers
SHARED = {"ssm_mixer_ms_per_step", "ssm_state_ms_per_step",
          "ssm_share_of_decode_step"}


def test_state_record_of_falcon_h1_34b():
    assert ssm_costs.state_elements(FALCON) == 32 * 128 * 256
    assert ssm_costs.record_bytes(FALCON) == 32 * 128 * 256 * 4 + 3 * 5120 * 2
    # 4.22 MB a layer a sequence; six layers, read and written
    one = ssm_costs.decode_step_bytes(FALCON, 1, 2, [600])
    assert one == 6 * 2 * 4225024
    # whatever the contexts, the page cache's element size or tp
    assert ssm_costs.decode_step_bytes(FALCON, 4, 1, [16, 4000, 7]) == 3 * one
    assert ssm_costs.decode_step_bytes(FALCON, 1, 2, []) == 0
    # a float32 trunk keeps a float32 window
    assert ssm_costs.record_bytes({**FALCON, "torch_dtype": "float32"}) == \
        32 * 128 * 256 * 4 + 3 * 5120 * 4


def test_scan_flops_are_four_an_element_a_token_a_layer():
    assert ssm_costs.scan_flops(FALCON, 1) == 4 * 32 * 128 * 256 * 6
    assert ssm_costs.scan_flops(FALCON, 2048) == 2048 * ssm_costs.scan_flops(FALCON, 1)


def test_the_cell_lists_the_five_metrics_and_only_there():
    cell = load_cell("falcon-h1-chat")
    assert cell.chips == 1 and cell.traffic_name == "chat"
    assert cell.config["reference"] == "falcon_h1"
    got = {m.name: m for m in cell.per_layer}
    for name in METRICS:
        assert got[name].reader == "ssm_scopes" and got[name].moves == "itl_p50_ms"
    for m in load_manifest()["per_layer"]:
        if m["name"] in METRICS:
            # its own readings here alone; a reading another family's
            # cell makes too lists that cell as well (PR 58)
            assert m["workloads"] == ["falcon-h1-chat"] or (
                m["name"] in SHARED and "falcon-h1-chat" in m["workloads"])
            assert m["layer"] == "state-space mixer"
    # the configuration as the catalog has it, but for the two cuts
    assert FALCON["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert (FALCON["num_hidden_layers"], FALCON["max_position_embeddings"]) == (6, 4096)
    assert (FALCON["mamba_d_ssm"], FALCON["mamba_d_state"], FALCON["vocab_size"],
            FALCON["intermediate_size"]) == (4096, 256, 261120, 21504)


def _run(t=None, **kw):
    cell = Cell("falcon-h1-chat", 1, {}, "falcon-h1-34b", FALCON, "chat",
                {"drain_s": 1}, [], [])
    fields = dict(cell=cell, hf=FALCON, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scope, program="^jit_decode_"):
    return {"stat": stat, "scope": scope, "program": program}


def test_a_program_without_the_mixers_scopes_gives_nothing_and_does_not_raise():
    run = _run(trace.load(CUT))
    for stat, scope, program in (
            ("scope_ms_per_execution", "ssm", "^jit_decode_"),
            ("scope_ms_per_execution", "ssm_state", "^jit_decode_"),
            ("scope_share_of_program_pct", "ssm", "^jit_decode_"),
            ("state_decode_roofline_pct", "ssm_state", "^jit_decode_"),
            ("scan_prefill_roofline_pct", "ssm_scan", "^jit_prefill_"),
            ("scope_ms_per_execution", "ssm", "^jit_nothing")):
        assert ssm_scopes.read(run, _args(stat, scope, program), path=CUT) is None
    # no capture at all
    assert ssm_scopes.read(_run(), _args("scope_ms_per_execution", "ssm")) is None


def _device(state_s, steps, program="jit_decode_step(1)"):
    """Hand-made capture (handed over in place of what
    ``moe_scopes._device``, which the reader loads its capture with,
    would read from the file): ``steps`` executions of 20 ms; in each the
    mixer's projection, conv and state update (or scan), an attention
    op and a feed-forward op, and an operation the compiler left
    without a name stack between two of the mixer's."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.03
        mods.append(Event(program, t0, 0.020))
        stack = "jit(step)/while/body/"
        inner = "ssm_scan" if "prefill" in program else "ssm_state"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0010, "ssm/dot_general"),
                ("fusion.2", 0.0020, 0.0002, "ssm/ssm_conv/add"),
                ("copy.3", 0.0022, 0.0001, None),
                ("fusion.4", 0.0030, state_s, f"ssm/{inner}/mul"),
                ("decode.5", 0.0120, 0.0005, "attn/pallas_call"),
                ("fusion.6", 0.0130, 0.0050, "mlp/dot_general")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5, prompt=100):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def test_decode_metrics_from_scope_time_and_live_sequences(monkeypatch):
    steps, live = 5, 40
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5),
               trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.006, steps)})
    ms, n = ssm_scopes.read(run, _args("scope_ms_per_execution", "ssm"), path=CUT)
    # projection 1.0 + conv 0.2 + the unnamed copy between two of the
    # mixer's operations 0.1 + state 6.0
    assert n == steps and ms == pytest.approx(7.3)
    ms, _ = ssm_scopes.read(run, _args("scope_ms_per_execution", "ssm_state"),
                            path=CUT)
    assert ms == pytest.approx(6.0)
    pct, _ = ssm_scopes.read(run, _args("scope_share_of_program_pct", "ssm"),
                             path=CUT)
    assert pct == pytest.approx(100 * 7.3 / 20)
    pct, n = ssm_scopes.read(run, _args("state_decode_roofline_pct", "ssm_state"),
                             path=CUT)
    least = live * 6 * 2 * 4225024 / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0062)
    assert 0 < pct < 100
    # half the sequences, the same time (rows touched for nobody): half
    run.records = _records(live // 2, first_token=0.5)
    half, _ = ssm_scopes.read(run, _args("state_decode_roofline_pct", "ssm_state"),
                              path=CUT)
    assert half == pytest.approx(pct / 2)
    # the scan's scope is not in a decode program
    assert ssm_scopes.read(run, _args("scope_ms_per_execution", "ssm_scan"),
                           path=CUT) is None
    with pytest.raises(ValueError, match="unknown stat"):
        ssm_scopes.read(run, _args("nothing", "ssm"), path=CUT)


def test_prefill_scan_roofline_from_the_slices_prompts(monkeypatch):
    # three prompts of 500 whose first token came inside the slice
    run = _run(trace.load(CUT), records=_records(3, first_token=1.5, prompt=500)
               + _records(2, first_token=0.2, prompt=900), trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.004, 3, "jit_prefill_step(3)")})
    args = _args("scan_prefill_roofline_pct", "ssm_scan", "^jit_prefill_")
    pct, n = ssm_scopes.read(run, args, path=CUT)
    flops = 4 * 1500 * 6 * 32 * 128 * 256
    assert n == 3 and pct == pytest.approx(100 * (flops / 197e12) / 0.012)
    assert 0 < pct < 100
    # no prompt computed in the slice: nothing to read
    run.records = _records(2, first_token=0.2, prompt=900)
    assert ssm_scopes.read(run, args, path=CUT) is None
