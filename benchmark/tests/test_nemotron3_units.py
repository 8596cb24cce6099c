"""What the ``nemotron3-reasoning`` cell brings as code: the byte and
FLOP model of the mixer layers' state and of the latent experts held
(``readers/nemotron3_costs.py``) and the reader of the trunk's scopes
and counters (``readers/nemotron3_scopes.py``), against hand arithmetic,
hand-made inputs and the cut of a traced v5e run of PR 23
(``data/v5e-spans.*``: a program from before the scopes, which has to
give nothing to read and never raise)."""

import json
import os

import pytest

from harness import prom, trace
from harness.manifest import ROOT, Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import moe_scopes, nemotron3_costs, nemotron3_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
CELL = "nemotron3-reasoning"
NEMOTRON = load_cell(CELL).config
# metric -> (its layer, the stat its file asks of nemotron3_scopes)
OWN = {
    "nemotron3_experts_roofline": ("routed experts",
                                   "experts_decode_roofline_pct"),
    "nemotron3_latent_ms_per_step": ("routed experts",
                                     "scope_ms_per_execution"),
    "nemotron3_latent_experts_share_of_decode_step": (
        "compiled programs", "scope_share_of_program_pct"),
    "nemotron3_ssm_decode_roofline": ("state-space mixer",
                                      "state_decode_roofline_pct"),
    "nemotron3_ssm_prefill_scan_roofline": ("state-space mixer",
                                            "scan_prefill_roofline_pct"),
}
STATE = 128 * 64 * 128 * 4                      # 4.194 MB a mixer layer a slot
RECORD = STATE + 3 * 10240 * 2                  # with the conv window
EXPERT = 2 * 1024 * 2688 * 2                    # 11.01 MB: two matrices
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_nemotron3_layers_and_experts_are_counted_from_the_patterns_letters():
    assert (nemotron3_costs.mixer_layers(NEMOTRON),
            nemotron3_costs.attention_layers(NEMOTRON),
            nemotron3_costs.expert_layers(NEMOTRON)) == (5, 1, 5)
    assert nemotron3_costs.held_experts(NEMOTRON) == 128
    # eight groups of B and C change what a head reads, not the state
    assert 4 * nemotron3_costs.state_elements(NEMOTRON) == STATE == 4194304
    assert nemotron3_costs.state_elements({**NEMOTRON, "n_groups": 1}) * 4 == STATE
    # ... but the conv window carries every group's B and C
    assert nemotron3_costs.record_bytes(NEMOTRON) == RECORD == 4255744
    assert nemotron3_costs.record_bytes({**NEMOTRON, "n_groups": 1}) == \
        STATE + 3 * (8192 + 256) * 2
    # five mixer layers, read and written, whatever the contexts, the
    # page cache's element size or tp
    one = nemotron3_costs.decode_step_bytes(NEMOTRON, 1, 2, [600])
    assert one == 5 * 2 * RECORD
    assert nemotron3_costs.decode_step_bytes(NEMOTRON, 4, 1, [16, 4000, 7]) == 3 * one
    assert nemotron3_costs.decode_step_bytes(NEMOTRON, 1, 2, []) == 0
    assert nemotron3_costs.scan_flops(NEMOTRON, 2048) == 2048 * 4 * 5 * 128 * 64 * 128
    # an expert is two matrices in the latent, a row is 1024 wide both ways
    assert nemotron3_costs.expert_weight_bytes(NEMOTRON) == EXPERT == 11010048
    assert nemotron3_costs.row_bytes(NEMOTRON) == 2 * 1024 * 2
    assert nemotron3_costs.experts_decode_bytes(NEMOTRON, 640, 700) == \
        640 * EXPERT + 700 * 2 * 1024 * 2
    assert nemotron3_costs.steps_of_slots(NEMOTRON, 128 * 5 * 7) == 7
    # the published depth: its own counts
    whole = {**NEMOTRON, "hybrid_override_pattern": "MEMEMEM*E" * 8 + "ME" * 8,
             "n_routed_experts": 512}
    assert (nemotron3_costs.mixer_layers(whole), nemotron3_costs.attention_layers(whole),
            nemotron3_costs.expert_layers(whole), nemotron3_costs.held_experts(whole)) \
        == (40, 8, 40, 512)


def test_nemotron3_cell_configuration_and_metrics_as_the_manifest_has_them():
    cell = load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "reasoning-gen"
    assert cell.cell["loop"] == "closed"
    assert cell.cell["clients"] == cell.config["serve"]["max_batch_size"] == 128
    assert cell.cell["limits"] == {"ttft_ms": 1000, "request_mean_gap_ms": 100}
    assert cell.config["reference"] == "nemotron_h"
    assert cell.config["attention_cost"] == "attention_layers_kv"
    assert {"itl_p50_ms", "setup_s"} == {m.name for m in cell.end_to_end}
    got = {m.name: m for m in cell.per_layer}
    man = load_manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    for name, (layer, stat) in OWN.items():
        assert got[name].reader == "nemotron3_scopes"
        assert got[name].args["stat"] == stat
        assert got[name].moves == "itl_p50_ms"
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] == layer
        assert listed[name]["source"] == "device_trace"
    assert sorted(n for n in listed if n.startswith("nemotron3_")) == sorted(OWN)
    # new entries stand at the end of their lists, in the issue's order
    assert [m["name"] for m in man["per_layer"][-5:]] == list(OWN)
    assert man["workloads"][-1]["name"] == CELL
    assert len(man["per_layer"]) <= 128
    entry = man["configs"][-1]
    assert entry["name"] == "nemotron-3-super-ep4"
    assert entry["reduced"] == NEMOTRON["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "max_position_embeddings"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f) == NEMOTRON
    assert (NEMOTRON["num_hidden_layers"], NEMOTRON["hybrid_override_pattern"],
            NEMOTRON["n_routed_experts"], NEMOTRON["expert_share"],
            NEMOTRON["vocab_size"], NEMOTRON["max_position_embeddings"]) == (
        11, "MEMEMEM*EME", 128, {"of_experts": 512, "rank": 0}, 32768, 4096)
    # every width as published
    assert (NEMOTRON["hidden_size"], NEMOTRON["mamba_num_heads"],
            NEMOTRON["mamba_head_dim"], NEMOTRON["ssm_state_size"],
            NEMOTRON["n_groups"], NEMOTRON["conv_kernel"], NEMOTRON["chunk_size"],
            NEMOTRON["num_attention_heads"], NEMOTRON["num_key_value_heads"],
            NEMOTRON["head_dim"], NEMOTRON["moe_latent_size"],
            NEMOTRON["moe_intermediate_size"],
            NEMOTRON["moe_shared_expert_intermediate_size"],
            NEMOTRON["num_experts_per_tok"], NEMOTRON["routed_scaling_factor"]) == (
        4096, 128, 64, 128, 8, 4, 128, 32, 2, 128, 1024, 2688, 5376, 22, 5)
    for text in (NEMOTRON["source"], NEMOTRON["stands_for"]):
        assert text
    assert any("multi-token-prediction" in a and "not run" in a
               for a in NEMOTRON["assumed"])
    assert any("jax.eval_shape" in a and "9.2963 GB" in a
               for a in NEMOTRON["assumed"])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_nemotron3_every_key_of_the_catalogs_config_is_the_files_but_the_five_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert NEMOTRON["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if NEMOTRON.get(k) != v)
    assert differs == sorted(NEMOTRON["reduced"])
    assert row["config"]["hybrid_override_pattern"].startswith(
        NEMOTRON["hybrid_override_pattern"])


def _run(t=None, **kw):
    cell = Cell(CELL, 1, {}, "nemotron-3-super-ep4", NEMOTRON, "reasoning-gen",
                {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=NEMOTRON, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes, program="^jit_decode_", **more):
    return {"stat": stat, "scopes": scopes, "program": program, **more}


def test_nemotron3_reader_gives_nothing_without_the_scopes_or_the_counters():
    run = _run(trace.load(CUT))
    for name, m in {m.name: m for m in load_cell(CELL).per_layer}.items():
        if name in OWN:
            assert nemotron3_scopes.read(run, m.args, path=CUT) is None, name
    # no capture at all
    assert nemotron3_scopes.read(_run(), _args(
        "scope_ms_per_execution", ["moe_latent"])) is None


def _device(state_s, experts_s, steps, program="jit_decode_step(1)"):
    """Hand-made capture: ``steps`` executions of 30 ms; in each a mixer
    layer's projection, conv and state update (or scan), an operation
    the compiler left without a name stack between two of the mixer's,
    the attention layer's kernel, and an expert layer: routing, the
    projection into the latent, the grouped products, the projection out
    of it, the combine and the shared expert."""
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
                ("fusion.7", 0.0138, 0.0001, "mlp/moe_latent/dot_general"),
                ("gmm.8", 0.0140, experts_s, "mlp/moe_experts/pallas_call"),
                ("fusion.9", 0.0270, 0.0003, "mlp/moe_route/gather"),
                ("fusion.10", 0.0274, 0.0002, "mlp/moe_latent/dot_general"),
                ("fusion.11", 0.0280, 0.0004, "mlp/moe_shared/dot_general")):
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


def test_nemotron3_decode_metrics_from_scope_time_live_sequences_and_counters(monkeypatch):
    steps, live = 5, 120
    zero = _counters(0, 0, 0, 0)
    # seven steps between the samples that bracket the slice: 600 of the
    # 640 held experts of the five layers had rows, 2640 picks a layer a
    # step of which 660 held
    end = _counters(7 * 600, 7 * 640, 7 * 5 * 2640, 7 * 5 * 660)
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5),
               trace_slice=(1.0, 2.0), prom_start=zero, prom_end=end,
               prom_samples=[(0.9, zero), (2.1, end)])
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.008, 0.012, steps)})
    by_file = {m.name: m for m in load_cell(CELL).per_layer}

    def read(metric):
        return nemotron3_scopes.read(run, by_file[metric].args, path=CUT)

    # the two latent projections, a side of the dispatch each
    assert read("nemotron3_latent_ms_per_step") == (pytest.approx(0.3), steps)
    # route 0.7 + 0.3, latent 0.3, experts 12.0 of 30
    pct, _ = read("nemotron3_latent_experts_share_of_decode_step")
    assert pct == pytest.approx(100 * 13.3 / 30)
    pct, n = read("nemotron3_ssm_decode_roofline")
    least = live * 5 * 2 * RECORD / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0082)
    assert 0 < pct < 100
    # the experts held that had rows, and the rows that fell on them
    pct, n = read("nemotron3_experts_roofline")
    least = (600 * EXPERT + 5 * 660 * 2 * 1024 * 2) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.012)
    assert 0 < pct < 100
    # every expert with a row, at the HBM peak itself: 100 and not over
    exact = (640 * EXPERT + 5 * 660 * 2 * 1024 * 2) / 819e9
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.008, exact, steps)})
    run.prom_end = end = _counters(7 * 640, 7 * 640, 7 * 5 * 2640, 7 * 5 * 660)
    run.prom_samples = [(0.9, zero), (2.1, end)]
    assert read("nemotron3_experts_roofline")[0] == pytest.approx(100.0)
    with pytest.raises(ValueError, match="unknown stat"):
        nemotron3_scopes.read(run, _args("nothing", ["ssm"]), path=CUT)


def test_nemotron3_prefill_scan_roofline_from_the_slices_prompts(monkeypatch):
    run = _run(trace.load(CUT), records=_records(3, first_token=1.5, prompt=500)
               + _records(2, first_token=0.2, prompt=900), trace_slice=(1.0, 2.0))
    monkeypatch.setattr(
        moe_scopes, "load_op_events",
        lambda path: {0: _device(0.004, 0.010, 3, "jit_prefill_step(3)")})
    args = load_cell(CELL)
    args = next(m.args for m in args.per_layer
                if m.name == "nemotron3_ssm_prefill_scan_roofline")
    pct, n = nemotron3_scopes.read(run, args, path=CUT)
    flops = 4 * 1500 * 5 * 128 * 64 * 128
    assert n == 3 and pct == pytest.approx(100 * (flops / 197e12) / 0.012)
    assert 0 < pct < 100
    # a request that started in the slice and was cut at the window's
    # end was prefilled in the slice all the same
    cut = dict(_records(1, first_token=1.2, prompt=700)[0], done=False,
               error="open at the end of the drain")
    run.records = run.records + [cut]
    pct, _ = nemotron3_scopes.read(run, args, path=CUT)
    assert pct == pytest.approx(100 * (flops * 2200 / 1500 / 197e12) / 0.012)
    run.records = _records(2, first_token=0.2, prompt=900)
    assert nemotron3_scopes.read(run, args, path=CUT) is None
