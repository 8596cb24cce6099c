"""What the ``mimo-longdoc`` cell brings as code: the byte and FLOP model
of its two kinds of attention (``attention_costs/mimo_window_full_kv.py``:
window layers of 8 kv heads over the last 128 keys, full layers of 4 over
every key, keys of 192 over values of 128, the lanes a page pads them
with not counted) and of the
experts held (``readers/mimo_costs.py``: the expert layers counted from
the ``moe_layer_freq`` list), and the reader of the trunk's scopes and
counters (``readers/mimo_scopes.py``), against hand counts, a hand-made
capture and the cut of a traced v5e run of PR 23 (``data/v5e-spans.*``: a
program from before the scopes and the counters, which has to give
nothing to read and never raise)."""

import json
import os

import pytest

from attention_costs import mimo_window_full_kv as cost
from harness import prom, trace
from harness.manifest import ROOT, Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import dots3_costs, mimo_costs, mimo_scopes, moe_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
MIMO = load_cell("mimo-longdoc").config
ATTENTION = "window and full attention (two kinds of page)"
METRICS = {
    "mimo_window_kernel_ms_per_step": ATTENTION,
    "mimo_full_kernel_ms_per_step": ATTENTION,
    "mimo_window_decode_roofline": ATTENTION,
    "mimo_full_decode_roofline": ATTENTION,
    "mimo_window_prefill_roofline": ATTENTION,
    "mimo_full_prefill_roofline": ATTENTION,
    "mimo_experts_roofline": "routed experts",
    "mimo_xla_attention_routes": "Pallas kernels",
}
WINDOW_TOKEN = 8 * (192 + 128) * 2      # a token in one window layer
FULL_TOKEN = 4 * (192 + 128) * 2        # in one full layer
EXPERT = 3 * 4096 * 2048 * 2            # one expert's three matrices
PAIR = 2 * 64 * (192 + 128)             # FLOPs a query-key pair, all heads


def test_mimo_kinds_are_counted_from_the_keys_live():
    assert cost.layers(MIMO) == (5, 2)
    assert cost.token_bytes(MIMO, 2) == (WINDOW_TOKEN, FULL_TOKEN) == (5120, 2560)
    # a row of 14 000 keys: a window layer reads 128, a full layer all;
    # a row of 100: all, all
    assert cost.window_step_bytes(MIMO, 1, 2, [14000, 100]) == \
        5 * (128 + 100) * WINDOW_TOKEN == 5_836_800
    assert cost.full_step_bytes(MIMO, 1, 2, [14000, 100]) == \
        2 * 14100 * FULL_TOKEN == 72_192_000
    assert cost.decode_step_bytes(MIMO, 4, 2, [14000, 100]) == \
        5_836_800 + 72_192_000
    assert cost.decode_step_bytes(MIMO, 1, 1, [14000, 100]) * 2 == \
        cost.decode_step_bytes(MIMO, 1, 2, [14000, 100])
    assert cost.decode_step_bytes(MIMO, 1, 2, []) == 0
    # a chunk of 4 queries from position 2046: 2047..2050 keys visible in
    # a full layer, 128 each in a window layer
    assert cost.prefill_pairs([(2046, 4)], 128) == (
        4 * 128, 2047 + 2048 + 2049 + 2050)
    # under the window both are the triangle; across it a band
    assert cost.prefill_pairs([(0, 64)], 128) == (64 * 65 // 2,) * 2
    assert cost.prefill_pairs([(126, 4)], 128)[0] == 127 + 128 + 128 + 128
    assert cost.pair_flops(MIMO) == PAIR == 40_960
    assert cost.prefill_flops(MIMO, 1, [(2046, 4)]) == PAIR * (
        5 * 512 + 2 * 8194)
    assert cost.window_prefill_flops(MIMO, 10) == 5 * 10 * PAIR
    assert cost.full_prefill_flops(MIMO, 10) == 2 * 10 * PAIR


def test_the_schedulers_pairs_are_the_cost_modules():
    """``dynamo_attention_prefill_pairs_total`` counts what the cost
    module counts from the same chunks: the program's counter and the
    benchmark's arithmetic are written apart and must agree."""
    from dynamo_tpu.engine.scheduler import prefill_pairs
    for start, length in ((0, 64), (0, 2048), (126, 4), (2046, 4),
                          (10240, 2048), (17000, 920)):
        full, band = prefill_pairs(start, start + length, 128)
        assert (band, full) == cost.prefill_pairs([(start, length)], 128)
    assert prefill_pairs(0, 10) == (55, 0)


def test_mimo_experts_are_counted_from_the_list():
    assert mimo_costs.expert_layers(MIMO) == 6
    # first_k_dense_replace is not this configuration's key: the general
    # count would take every layer for an expert layer
    assert dots3_costs.expert_layers(MIMO) == 7
    assert mimo_costs.held_experts(MIMO) == 16
    assert mimo_costs.experts_decode_bytes(MIMO, 60, 30) == \
        60 * EXPERT + 30 * 2 * 4096 * 2
    assert EXPERT == 50_331_648
    assert mimo_costs.steps_of_slots(MIMO, 16 * 6 * 7) == 7


def test_mimo_cell_configuration_and_metrics_as_the_manifest_has_them():
    cell = load_cell("mimo-longdoc")
    assert cell.chips == 1 and cell.traffic_name == "longdoc-gen"
    assert cell.cell["clients"] == cell.config["serve"]["max_batch_size"] == 32
    assert cell.cell["loop"] == "closed"
    assert cell.cell["limits"] == {"ttft_ms": 1000, "request_mean_gap_ms": 100}
    assert cell.config["reference"] == "mimo_v2"
    assert cell.config["attention_cost"] == "mimo_window_full_kv"
    got = {m.name: m for m in cell.per_layer}
    man = load_manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    assert {n for n in listed if n.startswith("mimo_")} == set(METRICS)
    for name, layer in METRICS.items():
        assert got[name].reader == "mimo_scopes"
        assert got[name].moves == "itl_p50_ms"
        assert listed[name]["workloads"] == ["mimo-longdoc"]
        assert listed[name]["layer"] == layer
    assert "mimo-longdoc" in [w["name"] for w in man["workloads"]]
    assert {m.name for m in cell.end_to_end} == {"itl_p50_ms", "setup_s"}
    # the configuration as the catalog has it, but for the cuts
    assert MIMO["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert (MIMO["num_hidden_layers"], MIMO["hybrid_layer_pattern"],
            MIMO["moe_layer_freq"], MIMO["n_routed_experts"],
            MIMO["expert_share"], MIMO["vocab_size"],
            MIMO["max_position_embeddings"]) == (
        7, [0, 1, 1, 1, 1, 0, 1], [0, 1, 1, 1, 1, 1, 1], 16,
        {"of_experts": 256, "rank": 0}, 19072, 18432)
    assert (MIMO["hidden_size"], MIMO["intermediate_size"],
            MIMO["moe_intermediate_size"], MIMO["num_experts_per_tok"],
            MIMO["n_shared_experts"]) == (4096, 16384, 2048, 8, None)
    assert (MIMO["num_attention_heads"], MIMO["num_key_value_heads"],
            MIMO["swa_num_key_value_heads"], MIMO["head_dim"],
            MIMO["v_head_dim"], MIMO["sliding_window"],
            MIMO["partial_rotary_factor"], MIMO["attention_value_scale"],
            MIMO["rope_theta"], MIMO["swa_rope_theta"]) == (
        64, 4, 8, 192, 128, 128, 0.334, 0.707, 10000000, 10000)
    entry = next(c for c in man["configs"] if c["name"] == "mimo-v2.5-ep16")
    assert entry["reduced"] == MIMO["reduced"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f) == MIMO


def _run(t=None, **kw):
    cell = Cell("mimo-longdoc", 1, {}, "mimo-v2.5-ep16", MIMO,
                "longdoc-gen", {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=MIMO, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes, program="^jit_decode_", **more):
    return {"stat": stat, "scopes": scopes, "program": program, **more}


def test_mimo_reader_gives_nothing_without_the_scopes_or_the_counters():
    run = _run(trace.load(CUT))
    for m in load_cell("mimo-longdoc").per_layer:
        if m.name.startswith("mimo_"):
            assert mimo_scopes.read(run, m.args, path=CUT) is None, m.name
            assert mimo_scopes.read(_run(), m.args) is None, m.name


def _device(steps, chunks):
    """Hand-made capture: ``steps`` decode executions of 15 ms, in each a
    window layer (projections, the kernel) and a full layer (projections,
    an operation the compiler left without a name stack, the kernel),
    routing and the grouped products; ``chunks`` prefill executions of
    400 ms with both kernels."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.02
        mods.append(Event("jit_decode_step(1)", t0, 0.015))
        stack = "jit(step)/while/body/"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0010, "attn/attn_window/dot_general"),
                ("decode.2", 0.0020, 0.0008,
                 "attn/attn_window/kv_window/pallas_call"),
                ("fusion.3", 0.0030, 0.0010, "attn/attn_full/dot_general"),
                ("decode.4", 0.0040, 0.0050,
                 "attn/attn_full/kv_full/pallas_call"),
                ("copy.5", 0.0090, 0.0001, None),
                ("fusion.6", 0.0092, 0.0003, "mlp/moe_route/sort"),
                ("gmm.7", 0.0100, 0.0040, "mlp/moe_experts/pallas_call")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    for i in range(chunks):
        t0 = 1.0 + i * 0.5
        mods.append(Event("jit_prefill_step(2)", t0, 0.4))
        stack = "jit(step)/while/body/"
        for name, start, dur, scope in (
                ("flash.1", 0.01, 0.05, "attn/attn_window/kv_window/pallas_call"),
                ("flash.2", 0.10, 0.25, "attn/attn_full/kv_full/pallas_call"),
                ("gmm.3", 0.36, 0.01, "mlp/moe_experts/pallas_call")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope))
    ops.sort(key=lambda e: e.start)
    mods.sort(key=lambda e: e.start)
    return {"ops": ops, "modules": mods}


def _records(n, prompt, first_token=0.5):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def _counters(active, slots, rows, held, window_pairs, full_pairs, chunks,
              routes=(("decode", "decode", 1), ("prefill", "flash", 2))):
    text = "".join(
        f'dynamo_moe_{name}_total{{phase="decode"}} {value}\n'
        for name, value in (("active_experts", active), ("expert_slots", slots),
                            ("routed_rows", rows), ("held_picks", held)))
    text += (f'dynamo_attention_prefill_pairs_total{{kind="window"}} {window_pairs}\n'
             f'dynamo_attention_prefill_pairs_total{{kind="full"}} {full_pairs}\n'
             f"dynamo_attention_prefill_chunks_total {chunks}\n")
    text += "".join(
        f'dynamo_engine_attention_route_total{{program="{p}",route="{r}"}} {n}\n'
        for p, r, n in routes)
    return prom.parse(text)


def test_mimo_metrics_from_scope_time_live_keys_and_counters(monkeypatch):
    steps, chunks, live, prompt = 5, 2, 30, 13999
    zero = _counters(0, 0, 0, 0, 0, 0, 0)
    # seven steps and three chunks between the samples that bracket the
    # slice: 60 of the 96 held experts of the 6 layers had rows, 256
    # picks a step a layer of which 16 fell on a held expert; a chunk of
    # 2048 queries from position 8192
    band, full = cost.prefill_pairs([(8192, 2048)], 128)
    end = _counters(7 * 60, 7 * 96, 7 * 256 * 6, 7 * 16 * 6,
                    3 * band, 3 * full, 3)
    run = _run(trace.load(CUT), records=_records(live, prompt),
               trace_slice=(1.0, 2.0), prom_start=zero, prom_end=end,
               prom_samples=[(0.9, zero), (2.1, end)], cache_itemsize=2)
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(steps, chunks)})
    by_file = {m.name: m for m in load_cell("mimo-longdoc").per_layer}

    def read(metric):
        return mimo_scopes.read(run, by_file[metric].args, path=CUT)

    assert read("mimo_window_kernel_ms_per_step") == (pytest.approx(0.8), steps)
    # the kernel 5.0 and the unnamed copy behind it, which lies between
    # the kernel and routing and so belongs to neither
    assert read("mimo_full_kernel_ms_per_step") == (pytest.approx(5.0), steps)
    # thirty sequences of 14 000 keys (the prompt and the first token)
    pct, n = read("mimo_window_decode_roofline")
    assert n == steps and pct == pytest.approx(
        100 * (live * 128 * 5 * WINDOW_TOKEN / 819e9) / 0.0008)
    pct, _ = read("mimo_full_decode_roofline")
    assert pct == pytest.approx(
        100 * (live * 14000 * 2 * FULL_TOKEN / 819e9) / 0.005)
    # pairs a chunk over the bracket, times the chunks the slice ran
    pct, n = read("mimo_window_prefill_roofline")
    assert n == chunks and pct == pytest.approx(
        100 * (5 * band * PAIR / 197e12) / 0.05)
    pct, _ = read("mimo_full_prefill_roofline")
    assert pct == pytest.approx(100 * (2 * full * PAIR / 197e12) / 0.25)
    pct, n = read("mimo_experts_roofline")
    least = (60 * EXPERT + 16 * 6 * 2 * 4096 * 2) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.004)
    for name in METRICS:
        if name.endswith("_roofline"):
            assert 0 < read(name)[0] < 100, name
    assert read("mimo_xla_attention_routes") == 0.0
    fell = _run(prom_end=_counters(0, 0, 0, 0, 0, 0, 0, routes=(
        ("decode", "decode", 1), ("prefill", "xla", 2), ("prefill", "flash", 1))))
    assert mimo_scopes.read(
        fell, by_file["mimo_xla_attention_routes"].args) == 2.0
    with pytest.raises(ValueError, match="unknown stat"):
        mimo_scopes.read(run, _args("nothing", ["kv_full"]), path=CUT)
    # a configuration whose cost module has no such part: nothing to read
    assert mimo_scopes.read(run, _args(
        "kind_decode_roofline_pct", ["kv_full"], bytes="no_such_part"),
        path=CUT) is None
    assert mimo_scopes.read(run, _args(
        "kind_prefill_roofline_pct", ["kv_full"], "^jit_prefill_",
        kind="full", flops="no_such_part"), path=CUT) is None
