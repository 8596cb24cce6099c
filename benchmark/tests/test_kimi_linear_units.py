"""What the ``kimi-linear-reasoning`` cell brings as code: the byte and
FLOP model of the KDA layers' state and of the experts held
(``readers/kimi_costs.py``), the latent layers' pages
(``attention_costs/latent_kv_full_attn_layers.py``) and the reader of
the trunk's scopes and counters (``readers/kimi_scopes.py``), against
hand counts, hand-made captures and the cut of a traced v5e run of PR 23
(``data/v5e-spans.*``: a program from before the scopes, which has to
give nothing to read and never raise)."""

import json
import os

import pytest

from attention_costs import latent_kv, latent_kv_full_attn_layers
from harness import prom, trace
from harness.manifest import ROOT, Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import kimi_costs, kimi_scopes, moe_scopes, scope_ops

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
KIMI = load_cell("kimi-linear-reasoning").config
KDA = "linear attention (delta rule, KDA)"
# metric -> (its layer, its reader): the trunk's own under its prefix
# (``OWN``), the rest under the one name every cell reads it by (PR 58
# merged the ``kimi_`` twins into them; the latent sublayer's time is
# ``readers/scope_ops.py``'s there, and reads what ``kimi_scopes`` read)
METRICS = {
    "kimi_kda_ms_per_step": (KDA, "kimi_scopes"),
    "kimi_kda_state_ms_per_step": (KDA, "kimi_scopes"),
    "kimi_kda_decode_roofline": (KDA, "kimi_scopes"),
    "mla_attn_ms_per_step": ("latent attention", "scope_ops"),
    "mla_decode_roofline": ("latent attention", "moe_scopes"),
    "moe_experts_ms_per_step": ("routed experts", "moe_scopes"),
    "kimi_experts_roofline": ("routed experts", "kimi_scopes"),
    "moe_route_ms_per_step": ("routed experts", "moe_scopes"),
    "moe_held_pick_share": ("routed experts", "moe_scopes"),
    "moe_active_expert_share": ("routed experts", "moe_scopes"),
    "kimi_kda_experts_share_of_decode_step": ("compiled programs", "kimi_scopes"),
    "decode_program_ms_per_step": ("compiled programs", "moe_scopes"),
    "output_tokens_per_s": ("client (whole served path)", "client"),
}
OWN = {n for n in METRICS if n.startswith("kimi_")}
STATE = 32 * 128 * 128 * 4                      # a KDA layer a sequence
VECTORS = 3 * 4096 * 2 + (2 * 4096 + 32) * 4    # q, k, v; g, the read-out, beta
EXPERT = 3 * 2304 * 1024 * 2                    # one expert's three matrices
KEY = (512 + 128) * 2                           # a key a latent layer


def test_kimi_layers_and_experts_are_counted_from_the_configurations_keys():
    assert kimi_costs.kda_layers(KIMI) == 20
    assert kimi_costs.expert_layers(KIMI) == 26
    assert kimi_costs.held_experts(KIMI) == 16
    assert kimi_costs.state_elements(KIMI) == 32 * 128 * 128
    assert kimi_costs.step_vector_bytes(KIMI) == VECTORS == 57472
    # twenty KDA layers, read and written, whatever the contexts, the
    # page cache's element size or tp
    one = kimi_costs.decode_step_bytes(KIMI, 1, 2, [600])
    assert one == 20 * (2 * STATE + VECTORS) == 85_035_520
    assert kimi_costs.decode_step_bytes(KIMI, 4, 1, [16, 4000, 7]) == 3 * one
    assert kimi_costs.decode_step_bytes(KIMI, 1, 2, []) == 0
    assert kimi_costs.scan_flops(KIMI, 1024) == 1024 * 6 * 20 * 32 * 128 * 128
    assert kimi_costs.expert_weight_bytes(KIMI) == EXPERT == 14_155_776
    assert kimi_costs.experts_decode_bytes(KIMI, 360, 224) == \
        360 * EXPERT + 224 * 2 * 2304 * 2
    assert kimi_costs.steps_of_slots(KIMI, 16 * 26 * 7) == 7
    # another cut of the same model: its own counts
    other = {**KIMI, "linear_attn_config": {
        **KIMI["linear_attn_config"], "kda_layers": [1, 2, 3],
        "full_attn_layers": [4]}, "num_hidden_layers": 4, "num_experts": 128}
    assert (kimi_costs.kda_layers(other), kimi_costs.expert_layers(other),
            kimi_costs.held_experts(other)) == (3, 3, 128)


def test_kimi_latent_keys_are_counted_in_the_latent_layers_only():
    # seven layers of 27: (512 + 64 -> 128) x 2 B a key
    assert latent_kv_full_attn_layers.attention_layers(KIMI) == 7
    assert latent_kv_full_attn_layers.decode_step_bytes(KIMI, 1, 2, [100, 900]) == \
        1000 * 7 * KEY == 8_960_000
    assert latent_kv_full_attn_layers.decode_step_bytes(KIMI, 1, 2, [100, 900]) * 27 == \
        latent_kv.decode_step_bytes(KIMI, 1, 2, [100, 900]) * 7
    # absorbed form: (2 x 512 + 64) multiply-adds a head a pair
    assert latent_kv_full_attn_layers.prefill_flops(KIMI, 1, [(0, 64)]) == \
        2 * (64 * 65 // 2) * 32 * 1088 * 7


def test_kimi_cell_configuration_and_metrics_as_the_manifest_has_them():
    cell = load_cell("kimi-linear-reasoning")
    assert cell.chips == 1 and cell.traffic_name == "reasoning-gen"
    assert cell.cell["clients"] == 64 and cell.config["serve"]["max_batch_size"] == 64
    assert cell.config["reference"] == "kimi_linear"
    assert cell.config["attention_cost"] == "latent_kv_full_attn_layers"
    got = {m.name: m for m in cell.per_layer}
    man = load_manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    assert {n for n in listed if n.startswith("kimi_")} == OWN and len(OWN) == 5
    for name, (layer, reader) in METRICS.items():
        assert got[name].reader == reader
        assert got[name].moves == "itl_p50_ms"
        cells = listed[name].get("workloads")
        if name in OWN:
            assert cells == ["kimi-linear-reasoning"]
        else:
            assert cells is None or "kimi-linear-reasoning" in cells
        assert listed[name]["layer"] == layer
    # the configuration as the catalog has it, but for the two cuts
    assert KIMI["reduced"] == ["num_experts", "model_max_length"]
    lin = KIMI["linear_attn_config"]
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17,
                                 18, 19, 21, 22, 23, 25, 26]
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == \
        (32, 128, 4)
    assert (KIMI["num_hidden_layers"], KIMI["num_experts"], KIMI["expert_share"],
            KIMI["model_max_length"]) == (27, 16, {"of_experts": 256, "rank": 0}, 4096)
    assert (KIMI["hidden_size"], KIMI["intermediate_size"],
            KIMI["moe_intermediate_size"], KIMI["num_experts_per_token"],
            KIMI["num_shared_experts"], KIMI["first_k_dense_replace"],
            KIMI["vocab_size"]) == (2304, 9216, 1024, 8, 1, 1, 163840)
    assert (KIMI["kv_lora_rank"], KIMI["qk_nope_head_dim"], KIMI["qk_rope_head_dim"],
            KIMI["v_head_dim"], KIMI["num_attention_heads"], KIMI["mla_use_nope"],
            KIMI["q_lora_rank"], KIMI["routed_scaling_factor"]) == \
        (512, 128, 64, 128, 32, True, None, 2.446)
    entry = next(c for c in man["configs"] if c["name"] == "kimi-linear-48b-a3b-ep16")
    assert entry["reduced"] == KIMI["reduced"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f) == KIMI


def _run(t=None, **kw):
    cell = Cell("kimi-linear-reasoning", 1, {}, "kimi-linear-48b-a3b-ep16", KIMI,
                "reasoning-gen", {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=KIMI, serve={}, seconds=1.0,
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


def test_kimi_reader_gives_nothing_without_the_scopes_or_the_counters():
    run = _run(trace.load(CUT))
    for stat, scopes, program in (
            ("scope_ms_per_execution", ["kda"], "^jit_decode_"),
            ("scope_share_of_program_pct", ["kda_state", "moe_experts"], "^jit_decode_"),
            ("state_decode_roofline_pct", ["kda_state"], "^jit_decode_"),
            ("experts_decode_roofline_pct", ["moe_experts"], "^jit_decode_")):
        assert kimi_scopes.read(
            run, _args(stat, scopes, program, phase="decode"), path=CUT) is None
    # no capture at all, and no counters at all
    assert kimi_scopes.read(_run(), _args(
        "scope_share_of_program_pct", ["kda_state", "moe_experts"])) is None
    assert moe_scopes.read(_run(), RATIO) is None


def _device(state_s, experts_s, steps):
    """Hand-made capture: ``steps`` executions of 30 ms; in each a KDA
    layer's projection, conv, gate and state update, an
    operation the compiler left without a name stack between two of the
    mixer's, a latent layer's projection and kernel, routing, the
    grouped products and the shared expert."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.04
        mods.append(Event("jit_decode_step(1)", t0, 0.030))
        stack = "jit(step)/while/body/while/body/"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0010, "kda/dot_general"),
                ("fusion.2", 0.0020, 0.0002, "kda/kda_conv/add"),
                ("copy.3", 0.0022, 0.0001, None),
                ("fusion.3", 0.0024, 0.0003, "kda/kda_gate/softplus"),
                ("kda.4", 0.0030, state_s, "kda/kda_state/pallas_call"),
                ("fusion.5", 0.0120, 0.0003, "attn/dot_general"),
                ("decode.5", 0.0125, 0.0005, "attn/mla_cache/pallas_call"),
                ("fusion.6", 0.0130, 0.0007, "mlp/moe_route/sort"),
                ("gmm.7", 0.0140, experts_s, "mlp/moe_experts/pallas_call"),
                ("fusion.8", 0.0280, 0.0004, "mlp/moe_shared/dot_general")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": 100, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def _counters(active, slots, rows, held, phase="decode"):
    return prom.parse("".join(
        f'dynamo_moe_{name}_total{{phase="{phase}"}} {value}\n'
        for name, value in (("active_experts", active), ("expert_slots", slots),
                            ("routed_rows", rows), ("held_picks", held))))


def test_kimi_decode_metrics_from_scope_time_live_sequences_and_counters(monkeypatch):
    steps, live = 5, 60
    zero = _counters(0, 0, 0, 0)
    # seven steps between the samples that bracket the slice: 360 of the
    # 416 held experts of the 26 layers had rows, 480 picks a step of
    # which 32 fell on a held expert
    end = _counters(7 * 360, 7 * 416, 7 * 480 * 26, 7 * 32 * 26)
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5),
               trace_slice=(1.0, 2.0), prom_start=zero, prom_end=end,
               prom_samples=[(0.9, zero), (2.1, end)], cache_itemsize=2)
    for module in (moe_scopes, scope_ops):
        monkeypatch.setattr(module, "load_op_events",
                            lambda path: {0: _device(0.008, 0.010, steps)})
    # the scopes' times, through the readers the cell's metric files name
    by_file = {m.name: m for m in load_cell("kimi-linear-reasoning").per_layer}
    readers = {"kimi_scopes": kimi_scopes, "moe_scopes": moe_scopes,
               "scope_ops": scope_ops}

    def read(metric):
        m = by_file[metric]
        return readers[m.reader].read(run, m.args, path=CUT)

    # projection 1.0 + conv 0.2 + the unnamed copy 0.1 + gate 0.3 + state 8.0
    assert read("kimi_kda_ms_per_step") == (pytest.approx(9.6), steps)
    assert read("kimi_kda_state_ms_per_step")[0] == pytest.approx(8.0)
    assert read("mla_attn_ms_per_step")[0] == pytest.approx(0.8)
    # as the trunk's own reader read it under its own name until PR 58
    assert kimi_scopes.read(run, _args("scope_ms_per_execution", ["attn"]),
                            path=CUT) == read("mla_attn_ms_per_step")
    assert read("moe_experts_ms_per_step")[0] == pytest.approx(10.0)
    assert read("moe_route_ms_per_step")[0] == pytest.approx(0.7)
    assert read("decode_program_ms_per_step") == (pytest.approx(30.0), steps)
    assert read("kimi_kda_experts_share_of_decode_step")[0] == \
        pytest.approx(100 * 18.0 / 30)
    pct, n = read("kimi_kda_decode_roofline")
    least = live * 20 * (2 * STATE + VECTORS) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.008)
    assert 0 < pct < 100
    # the latent kernel: 60 sequences of 101 keys in seven layers
    pct, n = read("mla_decode_roofline")
    assert n == steps and pct == pytest.approx(
        100 * (live * 101 * 7 * KEY / 819e9) / 0.0005)
    # the experts held that had rows, and the rows that fell on them
    pct, n = read("kimi_experts_roofline")
    least = (360 * EXPERT + 32 * 26 * 2 * 2304 * 2) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.010)
    assert 0 < pct < 100
    # the counters' ratios over the window
    assert by_file["moe_held_pick_share"].args == RATIO
    assert read("moe_held_pick_share") == pytest.approx(100 * 32 / 480)
    assert read("moe_active_expert_share") == pytest.approx(100 * 360 / 416)
    with pytest.raises(ValueError, match="unknown stat"):
        kimi_scopes.read(run, _args("nothing", ["kda"]), path=CUT)


def test_kimi_no_metric_of_the_cell_reads_a_prefill_program():
    """A slot is refilled once in about 1500 steps, so the capture's four
    seconds hold between no prefill and four: a metric that reads one
    would be missing from some traced runs' lines, which refuses a
    check. Every device metric of the cell reads the decode program."""
    names = set()
    for m in load_cell("kimi-linear-reasoning").per_layer:
        if m.name.startswith("kimi_"):
            names.add(m.name)
            assert m.args.get("program", "^jit_decode_") == "^jit_decode_", m.name
            assert "kda_scan" not in m.args.get("scopes", ()), m.name
    assert names == OWN
