"""What the ``trinity-longdoc`` cell brings as code: what window and
full attention layers must read and multiply (``attention_costs/
window_full_kv.py``), the reader of the family's scopes and counters
(``readers/window_scopes.py``) and the reference's own bookkeeping
(``references/afmoe.py``), against hand-made inputs and the cut of a
traced v5e run of PR 23 (``data/v5e-spans.*``: a program from before the
scopes, which has to give nothing to read and never raise)."""

import json
import os

import pytest

from attention_costs import per_head_kv, window_full_kv
from harness import prom, trace
from harness.manifest import ROOT, Cell, load_cell, load_manifest
from harness.rundata import RunData, read_metric
from harness.trace import Event
from readers import moe_scopes, window_scopes
from references import afmoe as reference

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
TRINITY = load_cell("trinity-longdoc").config
LAYER = "window and full attention (two kinds of page)"
METRICS = {
    "window_attn_ms_per_step": ("window_scopes", LAYER),
    "full_attn_ms_per_step": ("window_scopes", LAYER),
    "window_full_share_of_decode_step": ("window_scopes", "compiled programs"),
    "window_full_decode_roofline": ("window_scopes", LAYER),
    "window_pages_released_share": ("window_scopes", "block allocator"),
    "kv_window_usage_max": ("prom_sample", "block allocator"),
    "kv_full_usage_max": ("prom_sample", "block allocator"),
}
# the two pools, the release, the two kinds' sublayers and their joint
# share are read in every cell with pages of two kinds; the share of a
# roofline is this family's cost module's
SHARED = set(METRICS) - {"window_full_decode_roofline"}
PAGE_ROW = 4 * 128 * 2      # a key or a value of every kv head, bfloat16


@pytest.mark.parametrize("n,want", [
    (1, 8), (2048, 8 * 2048), (2049, 6 * 2048 + 2 * 2049),
    (16384, 6 * 2048 + 2 * 16384)])
def test_attended_keys_six_window_layers_and_two_full(n, want):
    assert window_full_kv.attended(n, 2048, 6, 2) == want
    assert window_full_kv.decode_step_bytes(TRINITY, 1, 2, [n]) == 2 * want * PAGE_ROW


def test_decode_bytes_add_over_sequences_and_know_the_cache_element():
    one = window_full_kv.decode_step_bytes(TRINITY, 1, 2, [12000])
    assert one == 2 * (6 * 2048 + 2 * 12000) * PAGE_ROW
    assert window_full_kv.decode_step_bytes(TRINITY, 1, 2, [12000] * 24) == 24 * one
    assert window_full_kv.decode_step_bytes(TRINITY, 1, 1, [12000]) == one // 2
    # all-layer full attention would read 8 x 12000 keys: the window's worth
    assert one < 0.4 * 2 * 8 * 12000 * PAGE_ROW


@pytest.mark.parametrize("chunk", [(0, 5), (0, 2048), (100, 3000), (2047, 2),
                                   (2048, 2048), (14336, 2048)])
def test_prefill_flops_cut_the_windows_triangle_to_a_band(chunk):
    start, length = chunk
    pairs = sum(6 * min(p + 1, 2048) + 2 * (p + 1)
                for p in range(start, start + length))
    assert window_full_kv.prefill_flops(TRINITY, 1, [chunk]) == 4 * pairs * 32 * 128
    assert window_full_kv.prefill_flops(TRINITY, 1, [chunk, (0, 7)]) == (
        4 * (pairs + 8 * 28) * 32 * 128)
    # every layer full, as per_head_kv counts a model with no window
    no_window = {**TRINITY, "sliding_window": 0}
    assert window_full_kv.prefill_flops(TRINITY, 1, [chunk]) <= (
        per_head_kv.prefill_flops(no_window, 1, [chunk]))


def test_the_cell_lists_the_seven_metrics_and_only_there():
    cell = load_cell("trinity-longdoc")
    assert cell.chips == 1 and cell.traffic_name == "longdoc-gen"
    assert cell.config["reference"] == "afmoe"
    assert cell.config["attention_cost"] == "window_full_kv"
    assert cell.cell["loop"] == "closed" and cell.cell["clients"] == 16
    assert cell.cell["limits"] == load_cell("sala-longdoc").cell["limits"]
    got = {m.name: m for m in cell.per_layer}
    for name, (reader, _) in METRICS.items():
        assert got[name].reader == reader and got[name].moves == "itl_p50_ms"
    for m in load_manifest()["per_layer"]:
        if m["name"] in METRICS:
            # its own readings here alone; a reading another family's
            # cell makes too lists that cell as well (PR 58)
            assert m["workloads"] == ["trinity-longdoc"] or (
                m["name"] in SHARED and "trinity-longdoc" in m["workloads"])
            assert m["layer"] == METRICS[m["name"]][1]
    # the configuration as the catalog has it, but for the three cuts
    assert TRINITY["reduced"] == ["num_hidden_layers", "layer_types",
                                  "max_position_embeddings"]
    assert (TRINITY["num_hidden_layers"], TRINITY["max_position_embeddings"]) == (
        8, 18432)
    assert TRINITY["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert (TRINITY["hidden_size"], TRINITY["intermediate_size"],
            TRINITY["moe_intermediate_size"], TRINITY["vocab_size"],
            TRINITY["num_experts"], TRINITY["num_experts_per_tok"],
            TRINITY["num_shared_experts"], TRINITY["num_dense_layers"],
            TRINITY["sliding_window"], TRINITY["num_key_value_heads"]) == (
                2048, 6144, 1024, 200192, 128, 8, 1, 2, 2048, 4)
    # every prompt of the mix is several windows long, and the longest
    # request fits a slot's pages of the full kind
    mix = cell.traffic
    assert mix["prompt_tokens"]["min"] >= 4 * TRINITY["sliding_window"]
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= TRINITY["serve"]["max_model_len"]
    slots = TRINITY["serve"]["max_batch_size"]
    assert slots == cell.cell["clients"]
    assert TRINITY["serve"]["num_kv_blocks"] * 16 >= slots * longest
    assert set(TRINITY["serve"]) == set(load_cell("sala-longdoc").config["serve"])
    with open(os.path.join(ROOT, "benchmark", "cells", "trinity-longdoc.json")) as f:
        assert {"predicted", "found", "rehearsal"} <= set(json.load(f))


def test_the_references_runs_and_limits():
    assert reference.runs_of(TRINITY["layer_types"], 2) == [
        (True, True, 2), (True, False, 1), (False, False, 1),
        (True, False, 3), (False, False, 1)]
    assert reference.runs_of(["full_attention"] * 2, 0) == [(False, False, 2)]
    assert 0 < reference.LOGPROB_MEAN_ATOL < reference.LOGPROB_ATOL
    with pytest.raises(NotImplementedError, match="score_func"):
        reference.build({**TRINITY, "score_func": "softmax"}, 128, 1)
    with pytest.raises(NotImplementedError, match="afmoe"):
        reference.build({**TRINITY, "model_type": "mixtral"}, 128, 1)


def _run(t=None, **kw):
    cell = Cell("trinity-longdoc", 1, {}, "trinity-mini-26b-a3b", TRINITY,
                "longdoc-gen", {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=TRINITY, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes, program="^jit_decode_"):
    return {"stat": stat, "scopes": list(scopes), "program": program}


RATIO = {"stat": "counter_ratio_pct",
         "numerator": "dynamo_kv_window_pages_released_total",
         "denominator": "dynamo_kv_window_pages_allocated_total"}


def test_a_program_without_window_scopes_gives_nothing_and_does_not_raise():
    run = _run(trace.load(CUT))
    for stat, scopes, program in (
            ("scope_ms_per_execution", ["attn_window"], "^jit_decode_"),
            ("scope_ms_per_execution", ["attn_full"], "^jit_decode_"),
            ("scope_share_of_program_pct", ["attn_window", "attn_full"],
             "^jit_decode_"),
            ("window_full_decode_roofline_pct", ["kv_window", "kv_full"],
             "^jit_decode_"),
            ("scope_ms_per_execution", ["attn_window"], "^jit_nothing")):
        assert window_scopes.read(run, _args(stat, scopes, program), path=CUT) is None
    # no capture at all, no counters, and a parent's /metrics without the
    # labelled gauge
    assert window_scopes.read(_run(), _args("scope_ms_per_execution",
                                            ["attn_window"])) is None
    assert window_scopes.read(_run(), RATIO) is None
    other = prom.parse("dynamo_kv_block_usage_ratio 0.5\n")
    assert window_scopes.read(_run(prom_start=other, prom_end=other), RATIO) is None
    cell = load_cell("trinity-longdoc")
    sampled = _run(prom_samples=[(0.5, other), (1.5, other)])
    for name in ("kv_window_usage_max", "kv_full_usage_max"):
        metric = next(m for m in cell.per_layer if m.name == name)
        assert read_metric(metric, sampled) == (None, 0)


def _device(steps, program="jit_decode_step(1)"):
    """Hand-made capture: ``steps`` executions of 20 ms; in each a window
    layer (projection, scatter, kernel, gate), a full layer (projection,
    kernel) and a feed-forward op, with an operation the compiler left
    without a name stack between two of the window layer's."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.03
        mods.append(Event(program, t0, 0.020))
        stack = "jit(step)/while/body/"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0004, "attn/attn_window/dot_general"),
                ("copy.2", 0.0014, 0.0001, None),
                ("fusion.3", 0.0015, 0.0002, "attn/attn_window/scatter"),
                ("decode.4", 0.0020, 0.0012, "attn/attn_window/kv_window/pallas_call"),
                ("fusion.5", 0.0035, 0.0003, "attn/attn_window/logistic"),
                ("fusion.6", 0.0040, 0.0005, "attn/attn_full/dot_general"),
                ("decode.7", 0.0050, 0.0030, "attn/attn_full/kv_full/pallas_call"),
                ("fusion.8", 0.0100, 0.0050, "mlp/moe_experts/dot_general")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5, prompt=100):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def test_window_and_full_metrics_from_scope_time_and_live_sequences(monkeypatch):
    steps, live = 5, 22
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5,
                                                 prompt=12000),
               trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(steps)})
    ms, n = window_scopes.read(run, _args("scope_ms_per_execution",
                                          ["attn_window"]), path=CUT)
    # the unnamed copy between two of the window layer's operations is its
    assert n == steps and ms == pytest.approx(0.4 + 0.1 + 0.2 + 1.2 + 0.3)
    ms, _ = window_scopes.read(run, _args("scope_ms_per_execution",
                                          ["attn_full"]), path=CUT)
    assert ms == pytest.approx(3.5)
    pct, _ = window_scopes.read(run, _args(
        "scope_share_of_program_pct", ["attn_window", "attn_full"]), path=CUT)
    assert pct == pytest.approx(100 * (2.2 + 3.5) / 20)
    # 12 001 tokens of context (the prompt and one emitted) a sequence,
    # over the two kernels' time alone
    pct, n = window_scopes.read(run, _args(
        "window_full_decode_roofline_pct", ["kv_window", "kv_full"]), path=CUT)
    least = live * window_full_kv.decode_step_bytes(TRINITY, 1, 2, [12001]) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0042)
    assert 0 < pct < 100
    assert window_scopes.read(run, _args("scope_ms_per_execution", ["kv_full"],
                                         "^jit_prefill_"), path=CUT) is None
    with pytest.raises(ValueError, match="unknown stat"):
        window_scopes.read(run, _args("nothing", ["attn_full"]), path=CUT)


def test_released_share_and_pool_usage_from_the_programs_metrics():
    start = prom.parse("dynamo_kv_window_pages_allocated_total 1000\n"
                       "dynamo_kv_window_pages_released_total 400\n")
    end = prom.parse("dynamo_kv_window_pages_allocated_total 21000\n"
                     "dynamo_kv_window_pages_released_total 18400\n")
    got = window_scopes.read(_run(prom_start=start, prom_end=end), RATIO)
    assert got == pytest.approx(90.0)

    def sample(full, window):
        return prom.parse(
            "dynamo_kv_block_usage_ratio %g\n"
            'dynamo_kv_pool_usage_ratio{kind="full"} %g\n'
            'dynamo_kv_pool_usage_ratio{kind="window"} %g\n'
            % (max(full, window), full, window))

    cell = load_cell("trinity-longdoc")
    run = _run(prom_samples=[(0.5, sample(0.5, 0.9)), (1.5, sample(0.8, 0.97)),
                             (2.5, sample(0.7, 0.95))])
    by_name = {m.name: m for m in cell.per_layer}
    assert read_metric(by_name["kv_window_usage_max"], run) == (
        pytest.approx(97.0), 3)
    assert read_metric(by_name["kv_full_usage_max"], run) == (
        pytest.approx(80.0), 3)
    assert read_metric(by_name["kv_block_usage_max"], run) == (
        pytest.approx(97.0), 3)
