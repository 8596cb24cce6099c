"""What the ``granite-batch`` cell brings as code: the byte and FLOP
model of the mixer layers' state and of the experts held
(``readers/granite_costs.py``), the attention layers' pages
(``attention_costs/attention_layers_kv.py``) and the reader of the
trunk's scopes and counters (``readers/granite_scopes.py``), against
hand-made inputs and the cut of a traced v5e run of PR 23
(``data/v5e-spans.*``: a program from before the scopes, which has to
give nothing to read and never raise)."""

import json
import os

import pytest

from attention_costs import attention_layers_kv, per_head_kv
from harness import prom, trace
from harness.manifest import ROOT, Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import granite_costs, granite_scopes, moe_scopes, ssm_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
GRANITE = load_cell("granite-batch").config
# metric -> (its layer, its reader): the three shares of a roofline and
# the joint share are this trunk's own (``OWN``); the rest is read as in
# any cell, under the one name every cell reads it by (PR 58 merged the
# ``granite_`` twins into them)
METRICS = {
    "ssm_mixer_ms_per_step": ("state-space mixer", "ssm_scopes"),
    "ssm_state_ms_per_step": ("state-space mixer", "ssm_scopes"),
    "granite_ssm_decode_roofline": ("state-space mixer", "granite_scopes"),
    "granite_ssm_prefill_scan_roofline": ("state-space mixer", "granite_scopes"),
    "moe_experts_ms_per_step": ("routed experts", "moe_scopes"),
    "granite_experts_roofline": ("routed experts", "granite_scopes"),
    "moe_route_ms_per_step": ("routed experts", "moe_scopes"),
    "moe_held_pick_share": ("routed experts", "moe_scopes"),
    "moe_active_expert_share": ("routed experts", "moe_scopes"),
    "granite_state_experts_share_of_decode_step": ("compiled programs",
                                                   "granite_scopes"),
    "decode_program_ms_per_step": ("compiled programs", "moe_scopes"),
    "output_tokens_per_s": ("client (whole served path)", "client"),
    "ttft_from_send_p50_ms": ("client (whole served path)", "client"),
}
RECORD = 128 * 64 * 128 * 4 + 3 * 8448 * 2      # a mixer layer a sequence
EXPERT = 3 * 4096 * 768 * 2                     # one expert's three matrices


def test_layers_and_experts_are_counted_from_the_configurations_keys():
    assert granite_costs.mixer_layers(GRANITE) == 9
    assert granite_costs.attention_layers(GRANITE) == 1
    assert granite_costs.held_experts(GRANITE) == 36
    assert granite_costs.state_elements(GRANITE) == 128 * 64 * 128
    assert granite_costs.record_bytes(GRANITE) == RECORD == 4244992
    # nine mixer layers, read and written, whatever the contexts, the
    # page cache's element size or tp
    one = granite_costs.decode_step_bytes(GRANITE, 1, 2, [600])
    assert one == 9 * 2 * RECORD
    assert granite_costs.decode_step_bytes(GRANITE, 4, 1, [16, 4000, 7]) == 3 * one
    assert granite_costs.decode_step_bytes(GRANITE, 1, 2, []) == 0
    assert granite_costs.scan_flops(GRANITE, 2048) == 2048 * 4 * 9 * 128 * 64 * 128
    assert granite_costs.expert_weight_bytes(GRANITE) == EXPERT
    assert granite_costs.experts_decode_bytes(GRANITE, 360, 3200) == \
        360 * EXPERT + 3200 * 2 * 4096 * 2
    assert granite_costs.steps_of_slots(GRANITE, 36 * 10 * 7) == 7
    # another cut of the same model: its own counts
    other = {**GRANITE, "layer_types": ["attention", "mamba"],
             "num_hidden_layers": 2, "num_local_experts": 72}
    assert (granite_costs.mixer_layers(other), granite_costs.held_experts(other)) \
        == (1, 72)


def test_pages_are_counted_in_the_attention_layers_only():
    # one layer of ten: 8 kv heads x 128 x K and V x 2 B a key
    assert attention_layers_kv.decode_step_bytes(GRANITE, 1, 2, [100, 900]) == \
        1000 * 2 * 8 * 128 * 2
    assert attention_layers_kv.decode_step_bytes(GRANITE, 1, 2, [100, 900]) * 10 == \
        per_head_kv.decode_step_bytes(GRANITE, 1, 2, [100, 900])
    assert attention_layers_kv.prefill_flops(GRANITE, 1, [(0, 64)]) * 10 == \
        per_head_kv.prefill_flops(GRANITE, 1, [(0, 64)])


def test_the_cell_the_configuration_and_the_metrics_as_the_manifest_has_them():
    cell = load_cell("granite-batch")
    assert cell.chips == 1 and cell.traffic_name == "batch"
    assert cell.cell["clients"] == 128 and cell.config["serve"]["max_batch_size"] == 64
    assert cell.config["reference"] == "granite_hybrid"
    assert cell.config["attention_cost"] == "attention_layers_kv"
    got = {m.name: m for m in cell.per_layer}
    man = load_manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    for name, (layer, reader) in METRICS.items():
        assert got[name].reader == reader
        assert got[name].moves == "itl_p50_ms"
        cells = listed[name].get("workloads")
        if name.startswith("granite_"):
            assert cells == ["granite-batch"]
        else:
            assert cells is None or "granite-batch" in cells
        assert listed[name]["layer"] == layer
    assert sum(n.startswith("granite_") for n in got) == 4
    # the configuration as the catalog has it, but for the four cuts
    assert GRANITE["reduced"] == ["num_hidden_layers", "layer_types",
                                  "num_local_experts", "max_position_embeddings"]
    assert GRANITE["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (GRANITE["num_hidden_layers"], GRANITE["num_local_experts"],
            GRANITE["expert_share"]) == (10, 36, {"of_experts": 72, "rank": 0})
    assert (GRANITE["hidden_size"], GRANITE["mamba_n_heads"], GRANITE["mamba_d_head"],
            GRANITE["mamba_d_state"], GRANITE["mamba_chunk_size"],
            GRANITE["intermediate_size"], GRANITE["shared_intermediate_size"],
            GRANITE["num_experts_per_tok"], GRANITE["vocab_size"]) == \
        (4096, 128, 64, 128, 256, 768, 1536, 10, 100352)
    assert (GRANITE["embedding_multiplier"], GRANITE["residual_multiplier"],
            GRANITE["attention_multiplier"], GRANITE["logits_scaling"]) == \
        (12, 0.22, 0.0078125, 16)
    entry = next(c for c in man["configs"] if c["name"] == "granite-4.0-h-small-ep2")
    assert entry["reduced"] == GRANITE["reduced"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f) == GRANITE


def _run(t=None, **kw):
    cell = Cell("granite-batch", 1, {}, "granite-4.0-h-small-ep2", GRANITE,
                "batch", {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=GRANITE, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes, program="^jit_decode_", **more):
    return {"stat": stat, "scopes": scopes, "program": program, **more}


RATIO = {"stat": "counter_ratio_pct",
         "numerator": "dynamo_moe_held_picks_total",
         "denominator": "dynamo_moe_routed_rows_total"}


def test_granite_reader_gives_nothing_without_the_scopes_or_the_counters():
    run = _run(trace.load(CUT))
    for stat, scopes, program in (
            ("scope_share_of_program_pct", ["ssm_state", "moe_experts"], "^jit_decode_"),
            ("state_decode_roofline_pct", ["ssm_state", "ssm_conv"], "^jit_decode_"),
            ("experts_decode_roofline_pct", ["moe_experts"], "^jit_decode_"),
            ("scan_prefill_roofline_pct", ["ssm_scan"], "^jit_prefill_")):
        assert granite_scopes.read(
            run, _args(stat, scopes, program, phase="decode"), path=CUT) is None
    # no capture at all, and no counters at all
    assert granite_scopes.read(_run(), _args(
        "scope_share_of_program_pct", ["ssm_state", "moe_experts"])) is None
    assert moe_scopes.read(_run(), RATIO) is None


def _device(state_s, experts_s, steps, program="jit_decode_step(1)"):
    """Hand-made capture: ``steps`` executions of 30 ms; in each a mixer
    layer's projection, conv and state update (or scan), an operation
    the compiler left without a name stack between two of the mixer's,
    the attention layer's kernel, routing, the grouped products and the
    shared expert."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.04
        mods.append(Event(program, t0, 0.030))
        stack = "jit(step)/while/body/"
        inner = "ssm_scan" if "prefill" in program else "ssm_state"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0010, "ssm/dot_general"),
                ("fusion.2", 0.0020, 0.0002, "ssm/ssm_conv/add"),
                ("copy.3", 0.0022, 0.0001, None),
                ("fusion.4", 0.0030, state_s, f"ssm/{inner}/mul"),
                ("decode.5", 0.0120, 0.0005, "attn/pallas_call"),
                ("fusion.6", 0.0130, 0.0007, "mlp/moe_route/sort"),
                ("gmm.7", 0.0140, experts_s, "mlp/moe_experts/pallas_call"),
                ("fusion.8", 0.0280, 0.0004, "mlp/moe_shared/dot_general")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5, prompt=100):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def _counters(active, slots, rows, held, phase="decode"):
    return prom.parse("".join(
        f'dynamo_moe_{name}_total{{phase="{phase}"}} {value}\n'
        for name, value in (("active_experts", active), ("expert_slots", slots),
                            ("routed_rows", rows), ("held_picks", held))))


def test_granite_decode_metrics_from_scope_time_live_sequences_and_counters(monkeypatch):
    steps, live = 5, 60
    zero = _counters(0, 0, 0, 0)
    # seven steps between the samples that bracket the slice: every held
    # expert of every layer had rows, 600 picks a step of which 300 held
    end = _counters(7 * 360, 7 * 360, 7 * 6000, 7 * 3000)
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5),
               trace_slice=(1.0, 2.0), prom_start=zero, prom_end=end,
               prom_samples=[(0.9, zero), (2.1, end)])
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.008, 0.010, steps)})
    # the scopes' times, through the readers the cell's metric files name
    by_file = {m.name: m for m in load_cell("granite-batch").per_layer}
    readers = {"ssm_scopes": ssm_scopes, "moe_scopes": moe_scopes}

    def ms_of(metric):
        m = by_file[metric]
        return readers[m.reader].read(run, m.args, path=CUT)

    # projection 1.0 + conv 0.2 + the unnamed copy 0.1 + state 8.0
    assert ms_of("ssm_mixer_ms_per_step") == (pytest.approx(9.3), steps)
    assert ms_of("ssm_state_ms_per_step")[0] == pytest.approx(8.0)
    assert ms_of("moe_experts_ms_per_step")[0] == pytest.approx(10.0)
    assert ms_of("moe_route_ms_per_step")[0] == pytest.approx(0.7)
    assert ms_of("decode_program_ms_per_step") == (pytest.approx(30.0), steps)
    pct, _ = granite_scopes.read(run, _args(
        "scope_share_of_program_pct", ["ssm_state", "moe_experts"]), path=CUT)
    assert pct == pytest.approx(100 * 18.0 / 30)
    pct, n = granite_scopes.read(run, _args(
        "state_decode_roofline_pct", ["ssm_state", "ssm_conv"]), path=CUT)
    least = live * 9 * 2 * RECORD / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0082)
    assert 0 < pct < 100
    # the experts held that had rows, and the rows that fell on them
    pct, n = granite_scopes.read(run, _args(
        "experts_decode_roofline_pct", ["moe_experts"], phase="decode"), path=CUT)
    least = (360 * EXPERT + 3000 * 2 * 4096 * 2) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.010)
    assert 0 < pct < 100
    # the counters' ratios over the window
    assert by_file["moe_held_pick_share"].args == RATIO
    assert ms_of("moe_held_pick_share") == pytest.approx(50.0)
    assert ms_of("moe_active_expert_share") == pytest.approx(100.0)
    with pytest.raises(ValueError, match="unknown stat"):
        granite_scopes.read(run, _args("nothing", ["ssm"]), path=CUT)


def test_granite_prefill_scan_roofline_from_the_slices_prompts(monkeypatch):
    run = _run(trace.load(CUT), records=_records(3, first_token=1.5, prompt=500)
               + _records(2, first_token=0.2, prompt=900), trace_slice=(1.0, 2.0))
    monkeypatch.setattr(
        moe_scopes, "load_op_events",
        lambda path: {0: _device(0.004, 0.010, 3, "jit_prefill_step(3)")})
    args = _args("scan_prefill_roofline_pct", ["ssm_scan"], "^jit_prefill_")
    pct, n = granite_scopes.read(run, args, path=CUT)
    flops = 4 * 1500 * 9 * 128 * 64 * 128
    assert n == 3 and pct == pytest.approx(100 * (flops / 197e12) / 0.012)
    assert 0 < pct < 100
    run.records = _records(2, first_token=0.2, prompt=900)
    assert granite_scopes.read(run, args, path=CUT) is None
