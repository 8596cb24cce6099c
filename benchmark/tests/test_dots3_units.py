"""What the ``dots3-longdoc`` cell brings as code: the byte and FLOP
model of the three attention routes
(``attention_costs/dots3_indexed_latent.py``: the indexer over every
live key, attention over the picked keys, the window layers) and of the
experts held (``readers/dots3_costs.py``), and the reader of the trunk's
scopes and counters (``readers/dots3_scopes.py``), against hand counts,
hand-made captures and the cut of a traced v5e run of PR 23
(``data/v5e-spans.*``: a program from before the scopes, which has to
give nothing to read and never raise)."""

import json
import os

import pytest

from attention_costs import dots3_indexed_latent as cost
from harness import prom, trace
from harness.manifest import ROOT, Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import (dots3_costs, dots3_scopes, moe_scopes, prom_sample,
                     sala_scopes, window_scopes)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
DOTS3 = load_cell("dots3-longdoc").config
INDEXER = "learned sparse latent attention (indexer)"
# metric -> (its layer, its reader): the trunk's own five under its
# prefix (``OWN``), the rest under the one name every cell reads it by.
# PR 54 could list eight (BENCHMARK.json holds at most 128 per-layer
# metrics and had 120) and read the others by hand; PR 58 merged the
# twins of every cell and appended this cell to the general entries.
METRICS = {
    "dots3_select_ms_per_step": (INDEXER, "dots3_scopes"),
    "window_full_share_of_decode_step": ("compiled programs", "window_scopes"),
    "sparse_kept_share": ("block-sparse attention (InfLLM-V2)", "sala_scopes"),
    "decode_program_ms_per_step": ("compiled programs", "moe_scopes"),
    "dots3_index_roofline": (INDEXER, "dots3_scopes"),
    "dots3_picked_attn_roofline": (INDEXER, "dots3_scopes"),
    "dots3_window_decode_roofline": ("latent attention", "dots3_scopes"),
    "dots3_experts_roofline": ("routed experts", "dots3_scopes"),
    "moe_experts_ms_per_step": ("routed experts", "moe_scopes"),
    "moe_route_ms_per_step": ("routed experts", "moe_scopes"),
    "moe_active_expert_share": ("routed experts", "moe_scopes"),
    "moe_held_pick_share": ("routed experts", "moe_scopes"),
    "window_pages_released_share": ("block allocator", "window_scopes"),
    "kv_window_usage_max": ("block allocator", "prom_sample"),
    "kv_full_usage_max": ("block allocator", "prom_sample"),
}
OWN = {n for n in METRICS if n.startswith("dots3_")}


def _ratio(numerator, denominator, **labels):
    args = {"stat": "counter_ratio_pct", "numerator": numerator,
            "denominator": denominator}
    return {**args, "labels": labels} if labels else args


INDEX_KEY = 128 * 2                 # an indexer's key a full layer
FULL_KEY = (512 + 128) * 2          # latent and rope key a full layer
WINDOW_KEY = (1024 + 128) * 2       # a window layer
EXPERT = 3 * 5120 * 1536 * 2        # one expert's three matrices


def test_dots3_routes_are_counted_from_the_keys_live():
    assert cost.layers(DOTS3) == (3, 6)
    # a row of 14 000 keys: the indexer reads them all, attention 2048
    # of them, a window layer 513; a row of 300: all, all, all
    assert cost.index_step_bytes(DOTS3, 1, 2, [14000, 300]) == \
        3 * 14300 * INDEX_KEY == 10_982_400
    assert cost.picked_step_bytes(DOTS3, 1, 2, [14000, 300]) == \
        3 * (2048 + 300) * FULL_KEY == 9_016_320
    assert cost.window_step_bytes(DOTS3, 1, 2, [14000, 300]) == \
        6 * (513 + 300) * WINDOW_KEY == 11_238_912
    assert cost.decode_step_bytes(DOTS3, 4, 2, [14000, 300]) == \
        10_982_400 + 9_016_320 + 11_238_912
    assert cost.decode_step_bytes(DOTS3, 1, 1, [14000, 300]) * 2 == \
        cost.decode_step_bytes(DOTS3, 1, 2, [14000, 300])
    assert cost.decode_step_bytes(DOTS3, 1, 2, []) == 0
    # a chunk of 4 queries from position 2046: 2047..2050 keys visible
    pairs = 2047 + 2048 + 2049 + 2050
    picked = 2047 + 3 * 2048
    band = 4 * 513
    assert cost.prefill_flops(DOTS3, 1, [(2046, 4)]) == 2 * (
        3 * (pairs * 64 * 128 + picked * 128 * (2 * 512 + 64))
        + 6 * band * 64 * (2 * 1024 + 64))
    # under the pick and the window both are the triangle
    tri = 64 * 65 // 2
    assert cost.prefill_flops(DOTS3, 1, [(0, 64)]) == 2 * tri * (
        3 * (64 * 128 + 128 * 1088) + 6 * 64 * 2112)


def test_dots3_experts_are_counted_from_the_configurations_keys():
    assert dots3_costs.expert_layers(DOTS3) == 8
    assert dots3_costs.held_experts(DOTS3) == 16
    assert dots3_costs.experts_decode_bytes(DOTS3, 70, 16) == \
        70 * EXPERT + 16 * 2 * 5120 * 2
    assert EXPERT == 47_185_920
    assert dots3_costs.steps_of_slots(DOTS3, 16 * 8 * 7) == 7


def test_dots3_cell_configuration_and_metrics_as_the_manifest_has_them():
    cell = load_cell("dots3-longdoc")
    assert cell.chips == 1 and cell.traffic_name == "longdoc-gen"
    assert cell.cell["clients"] == cell.config["serve"]["max_batch_size"]
    assert cell.config["reference"] == "dots3"
    assert cell.config["attention_cost"] == "dots3_indexed_latent"
    got = {m.name: m for m in cell.per_layer}
    man = load_manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    assert {n for n in listed if n.startswith("dots3_")} == OWN and len(OWN) == 5
    for name, (layer, reader) in METRICS.items():
        assert got[name].reader == reader
        assert got[name].moves == "itl_p50_ms"
        cells = listed[name].get("workloads")
        if name in OWN:
            assert cells == ["dots3-longdoc"]
        else:
            assert cells is None or "dots3-longdoc" in cells
        assert listed[name]["layer"] == layer
    assert [w["name"] for w in man["workloads"]][-1] == "dots3-longdoc"
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    # the configuration as the catalog has it, but for the five cuts
    assert DOTS3["reduced"] == ["num_hidden_layers", "layer_types",
                                "n_routed_experts", "vocab_size",
                                "max_position_embeddings"]
    full, window = "full_attention", "sliding_attention"
    assert DOTS3["layer_types"] == [full, full, window, window, window,
                                    full, window, window, window]
    assert (DOTS3["num_hidden_layers"], DOTS3["n_routed_experts"],
            DOTS3["expert_share"], DOTS3["vocab_size"],
            DOTS3["max_position_embeddings"]) == \
        (9, 16, {"of_experts": 256, "rank": 0}, 19008, 18432)
    assert (DOTS3["hidden_size"], DOTS3["intermediate_size"],
            DOTS3["moe_intermediate_size"], DOTS3["num_experts_per_tok"],
            DOTS3["n_shared_experts"], DOTS3["first_k_dense_replace"]) == \
        (5120, 13824, 1536, 8, 1, 1)
    assert (DOTS3["num_attention_heads"], DOTS3["q_lora_rank"],
            DOTS3["kv_lora_rank"], DOTS3["qk_nope_head_dim"],
            DOTS3["qk_rope_head_dim"], DOTS3["v_head_dim"],
            DOTS3["rope_theta"]) == (128, 1024, 512, 128, 64, 128, 80000000)
    assert (DOTS3["swa_num_attention_heads"], DOTS3["swa_q_lora_rank"],
            DOTS3["swa_kv_lora_rank"], DOTS3["swa_qk_nope_head_dim"],
            DOTS3["swa_qk_rope_head_dim"], DOTS3["swa_v_head_dim"],
            DOTS3["swa_rope_theta"], DOTS3["sliding_window_size"]) == \
        (64, 1024, 1024, 192, 64, 128, 50000, 513)
    assert (DOTS3["index_n_heads"], DOTS3["index_head_dim"],
            DOTS3["index_topk"]) == (64, 128, 2048)
    entry = next(c for c in man["configs"] if c["name"] == "dots3-note-prev-ep16")
    assert entry["reduced"] == DOTS3["reduced"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f) == DOTS3


def _run(t=None, **kw):
    cell = Cell("dots3-longdoc", 1, {}, "dots3-note-prev-ep16", DOTS3,
                "longdoc-gen", {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=DOTS3, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes, program="^jit_decode_", **more):
    return {"stat": stat, "scopes": scopes, "program": program, **more}


def test_dots3_reader_gives_nothing_without_the_scopes_or_the_counters():
    run = _run(trace.load(CUT))
    for stat, scopes, more in (
            ("scope_ms_per_execution", ["dsa_index"], {}),
            ("scope_share_of_program_pct", ["attn_full", "attn_window"], {}),
            ("route_decode_roofline_pct", ["dsa_attend"],
             {"bytes": "picked_step_bytes"}),
            ("experts_decode_roofline_pct", ["moe_experts"],
             {"phase": "decode"})):
        assert dots3_scopes.read(run, _args(stat, scopes, **more),
                                 path=CUT) is None
    assert dots3_scopes.read(_run(), _args(
        "scope_ms_per_execution", ["swa_latent"])) is None
    kept = next(m for m in load_cell("dots3-longdoc").per_layer
                if m.name == "sparse_kept_share")
    assert sala_scopes.read(_run(), kept.args) is None


def _device(steps):
    """Hand-made capture: ``steps`` executions of 20 ms; in each a full
    layer (projections, the indexer's scores, an operation the compiler
    left without a name stack between two of them, the pick, the picked
    rows' gather and product), a window layer (projections, the latent
    kernel), routing, the grouped products and the shared expert."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.03
        mods.append(Event("jit_decode_step(1)", t0, 0.020))
        stack = "jit(step)/while/body/"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0010, "attn/attn_full/dot_general"),
                ("fusion.2", 0.0020, 0.0008, "attn/attn_full/dsa_index/dot_general"),
                ("copy.3", 0.0028, 0.0001, None),
                ("fusion.3", 0.0029, 0.0002,
                 "attn/attn_full/mla_cache/dsa_index/reduce"),
                ("fusion.4", 0.0031, 0.0005,
                 "attn/attn_full/mla_cache/dsa_select/while"),
                ("fusion.5", 0.0036, 0.0020,
                 "attn/attn_full/mla_cache/dsa_attend/gather"),
                ("fusion.6", 0.0060, 0.0012, "attn/attn_window/dot_general"),
                ("decode.7", 0.0072, 0.0004,
                 "attn/attn_window/swa_latent/mla_cache/pallas_call"),
                ("fusion.8", 0.0080, 0.0003, "mlp/moe_route/sort"),
                ("gmm.9", 0.0090, 0.0050, "mlp/moe_experts/pallas_call"),
                ("fusion.10", 0.0150, 0.0004, "mlp/moe_shared/dot_general")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, prompt, first_token=0.5):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def _counters(active, slots, rows, held, kept, live, released, taken):
    text = "".join(
        f'dynamo_moe_{name}_total{{phase="decode"}} {value}\n'
        for name, value in (("active_experts", active), ("expert_slots", slots),
                            ("routed_rows", rows), ("held_picks", held)))
    text += (f"dynamo_sparse_attention_kept_tokens_total {kept}\n"
             f"dynamo_sparse_attention_context_tokens_total {live}\n"
             f"dynamo_kv_window_pages_released_total {released}\n"
             f"dynamo_kv_window_pages_allocated_total {taken}\n"
             'dynamo_kv_pool_usage_ratio{kind="full"} 0.5\n'
             'dynamo_kv_pool_usage_ratio{kind="window"} 0.25\n')
    return prom.parse(text)


def test_dots3_decode_metrics_from_scope_time_live_keys_and_counters(monkeypatch):
    steps, live, prompt = 5, 30, 13999
    zero = _counters(0, 0, 0, 0, 0, 0, 0, 0)
    # seven steps between the samples that bracket the slice: 70 of the
    # 128 held experts of the 8 layers had rows, 240 picks a step a layer
    # of which 15 fell on a held expert
    end = _counters(7 * 70, 7 * 128, 7 * 240 * 8, 7 * 15 * 8,
                    7 * 30 * 2048, 7 * 30 * 14000, 900, 1000)
    run = _run(trace.load(CUT), records=_records(live, prompt),
               trace_slice=(1.0, 2.0), prom_start=zero, prom_end=end,
               prom_samples=[(0.9, zero), (2.1, end)], cache_itemsize=2)
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(steps)})
    by_file = {m.name: m for m in load_cell("dots3-longdoc").per_layer}
    readers = {"dots3_scopes": dots3_scopes, "moe_scopes": moe_scopes,
               "window_scopes": window_scopes, "sala_scopes": sala_scopes,
               "prom_sample": prom_sample}

    def read(metric):
        m = by_file[metric]
        if m.reader == "prom_sample":
            return prom_sample.read(run, m.args)
        return readers[m.reader].read(run, m.args, path=CUT)

    def scope_ms(scope):
        return dots3_scopes.read(
            run, _args("scope_ms_per_execution", [scope]), path=CUT)

    # the indexer's projections 0.8 + the unnamed copy between two of its
    # operations 0.1 + the scores' reduction 0.2
    assert scope_ms("dsa_index") == (pytest.approx(1.1), steps)
    assert read("dots3_select_ms_per_step")[0] == pytest.approx(0.5)
    assert scope_ms("dsa_attend")[0] == pytest.approx(2.0)
    assert scope_ms("swa_latent")[0] == pytest.approx(0.4)
    assert read("moe_experts_ms_per_step")[0] == pytest.approx(5.0)
    assert read("moe_route_ms_per_step")[0] == pytest.approx(0.3)
    assert read("decode_program_ms_per_step") == (pytest.approx(20.0), steps)
    # both kinds' sublayers: 1.0 + 0.8 + 0.1 + 0.2 + 0.5 + 2.0 and 1.2 + 0.4
    assert read("window_full_share_of_decode_step")[0] == \
        pytest.approx(100 * 6.2 / 20)
    # thirty sequences of 14 000 keys (the prompt and the first token)
    pct, n = read("dots3_index_roofline")
    assert n == steps and pct == pytest.approx(
        100 * (live * 14000 * 3 * INDEX_KEY / 819e9) / 0.0011)
    pct, _ = read("dots3_picked_attn_roofline")
    assert pct == pytest.approx(100 * (live * 2048 * 3 * FULL_KEY / 819e9) / 0.002)
    pct, _ = read("dots3_window_decode_roofline")
    assert pct == pytest.approx(100 * (live * 513 * 6 * WINDOW_KEY / 819e9) / 0.0004)
    pct, n = read("dots3_experts_roofline")
    least = (70 * EXPERT + 15 * 8 * 2 * 5120 * 2) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.005)
    for name in ("dots3_index_roofline", "dots3_picked_attn_roofline",
                 "dots3_window_decode_roofline", "dots3_experts_roofline"):
        assert 0 < read(name)[0] < 100
    # the counters' ratios over the window, and the pools' fullest sample
    assert read("sparse_kept_share") == pytest.approx(100 * 2048 / 14000)
    assert read("window_pages_released_share") == pytest.approx(90.0)
    assert read("moe_active_expert_share") == pytest.approx(100 * 70 / 128)
    assert read("moe_held_pick_share") == pytest.approx(100 * 15 / 240)
    assert read("kv_full_usage_max")[0] == pytest.approx(50.0)
    assert read("kv_window_usage_max")[0] == pytest.approx(25.0)
    with pytest.raises(ValueError, match="unknown stat"):
        dots3_scopes.read(run, _args("nothing", ["dsa_index"]), path=CUT)
    # a configuration whose cost module has no such part: nothing to read
    assert dots3_scopes.read(run, _args(
        "route_decode_roofline_pct", ["dsa_attend"], bytes="no_such_part"),
        path=CUT) is None


def test_dots3_no_metric_of_the_cell_reads_a_prefill_program():
    """A traced run's four seconds may hold no prefill chunk's end or
    several: every device metric of the cell reads the decode program,
    which every slice of this closed loop holds."""
    names = set()
    for m in load_cell("dots3-longdoc").per_layer:
        if m.name.startswith("dots3_"):
            names.add(m.name)
            assert m.args.get("program", "^jit_decode_") == "^jit_decode_", m.name
    assert names == OWN
