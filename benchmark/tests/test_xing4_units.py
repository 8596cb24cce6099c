"""What the ``xing4-batch`` cell brings as code: the byte model of the
mixed residual streams (``readers/mhc_costs.py``), the reader of their
scopes (``readers/mhc_scopes.py``) and the configuration's arithmetic
against its file, on hand-made inputs and the cut of a traced v5e run of
PR 23 (``data/v5e-spans.*``: a program from before the scopes, which has
to give nothing to read and never raise)."""

import json
import os

import pytest

from harness import trace
from harness.manifest import Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import mhc_costs, mhc_scopes, moe_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
XING4 = load_cell("xing4-batch").config
METRICS = ("mhc_ms_per_step", "mhc_sinkhorn_ms_per_step",
           "mhc_share_of_decode_step", "mhc_prefill_ms_per_ktok",
           "mhc_decode_roofline", "mhc_prefill_roofline")


def test_least_bytes_of_the_mixed_streams():
    # streams in and out, y in, u out: (2 x 4 + 2) x 3584 x 2 B a token a sublayer
    assert mhc_costs.token_bytes(XING4) == 10 * 3584 * 2 == 71680
    assert mhc_costs.coefficient_columns(XING4) == 24
    assert mhc_costs.param_bytes(XING4) == 4 * (14336 * 24 + 24 + 3)
    assert mhc_costs.sublayers(XING4) == 14
    one = mhc_costs.decode_step_bytes(XING4, 1, 2, [500])
    assert one == 14 * (71680 + mhc_costs.param_bytes(XING4))
    # rows count, contexts, tp and the cache's element size do not
    assert mhc_costs.decode_step_bytes(XING4, 4, 1, [16, 4000, 7]) == \
        14 * (3 * 71680 + mhc_costs.param_bytes(XING4))
    # 1000 prompt tokens: 1.0 GB, 1.2 ms at the chip's 819 GB/s
    assert mhc_costs.step_bytes(XING4, 1000) == pytest.approx(1.0e9, rel=0.03)
    # a float32 trunk moves twice the stream
    assert mhc_costs.token_bytes({**XING4, "torch_dtype": "float32"}) == 2 * 71680


def test_the_configuration_is_the_catalogs_but_for_three_keys():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
    assert XING4["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if XING4.get(k) != v)
    assert differ == sorted(XING4["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "max_position_embeddings"])
    assert (XING4["num_hidden_layers"], XING4["first_k_dense_replace"],
            XING4["max_position_embeddings"]) == (7, 1, 4096)
    assert (XING4["hc_mult"], XING4["hc_sinkhorn_iters"], XING4["n_routed_experts"],
            XING4["num_experts_per_tok"], XING4["vocab_size"], XING4["q_lora_rank"],
            XING4["num_nextn_predict_layers"]) == (4, 20, 64, 4, 131072, 768, 1)
    assert XING4["rope_scaling"]["factor"] == 64
    entry = next(c for c in load_manifest()["configs"] if c["name"] == "xing4-29b-a4b")
    assert entry["reduced"] == XING4["reduced"] and entry["source"] == XING4["source"]


def test_the_files_byte_arithmetic():
    d, v, h = XING4["hidden_size"], XING4["vocab_size"], XING4["num_attention_heads"]
    r, qr = XING4["kv_lora_rank"], XING4["q_lora_rank"]
    nope, rope, vd = (XING4["qk_nope_head_dim"], XING4["qk_rope_head_dim"],
                      XING4["v_head_dim"])
    attn = (d * qr + qr * h * (nope + rope) + d * r + d * rope
            + r * h * nope + r * h * vd + h * vd * d)
    assert attn == 28_409_856
    dense = 2 * (attn + 3 * d * XING4["intermediate_size"])
    mi, e = XING4["moe_intermediate_size"], XING4["n_routed_experts"]
    mhc = 2 * mhc_costs.param_bytes(XING4)
    expert = 2 * (attn + e * 3 * d * mi + 3 * d * mi + d * e) + mhc
    total = 2 * 2 * v * d + dense + mhc + 6 * expert
    assert 2 * v * d == pytest.approx(0.940e9, rel=1e-3)
    assert dense == pytest.approx(0.255e9, rel=5e-3)
    assert expert == pytest.approx(1.491e9, rel=1e-3)
    assert total == pytest.approx(11.08e9, rel=2e-3)
    cache = XING4["serve"]["num_kv_blocks"] * 16 * 640 * 2 * XING4["num_hidden_layers"]
    assert cache == pytest.approx(0.44e9, rel=2e-3)
    said = " ".join(XING4["assumed"])
    for number in ("0.940 GB", "1.491 GB", "11.08 GB", "0.44 GB"):
        assert number in said


def test_xing4_batch_lists_the_six_metrics_and_only_there():
    cell = load_cell("xing4-batch")
    assert cell.chips == 1 and cell.traffic_name == "batch"
    assert cell.cell["loop"] == "closed" and cell.cell["clients"] == 128
    assert cell.config["serve"]["max_batch_size"] == 64
    assert cell.config["reference"] == "xing4"
    got = {m.name: m for m in cell.per_layer}
    for name in METRICS:
        assert got[name].reader == "mhc_scopes" and got[name].moves == "itl_p50_ms"
    for m in load_manifest()["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == ["xing4-batch"]
            assert m["layer"] == "residual streams (mHC)"
    assert {m.name for m in cell.end_to_end} == {"itl_p50_ms", "setup_s"}


def _run(t=None, hf=XING4, **kw):
    cell = Cell("xing4-batch", 1, {}, "xing4-29b-a4b", hf, "batch",
                {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=hf, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes="all", program="^jit_decode_"):
    return {"stat": stat, "scopes": scopes, "program": program}


def test_a_program_without_the_mixing_scopes_gives_nothing_and_does_not_raise():
    run = _run(trace.load(CUT))
    for stat, program in (("scope_ms_per_execution", "^jit_decode_"),
                          ("scope_share_of_program_pct", "^jit_decode_"),
                          ("decode_roofline_pct", "^jit_decode_"),
                          ("scope_ms_per_1000_prompt_tokens", "^jit_prefill_"),
                          ("prefill_roofline_pct", "^jit_prefill_"),
                          ("scope_ms_per_execution", "^jit_nothing")):
        assert mhc_scopes.read(run, _args(stat, program=program), path=CUT) is None
    assert mhc_scopes.read(run, _args("scope_ms_per_execution", ["mhc_sinkhorn"]),
                           path=CUT) is None
    # no capture at all, and a configuration with one residual stream
    assert mhc_scopes.read(_run(), _args("scope_ms_per_execution")) is None
    one_stream = {k: v for k, v in XING4.items() if k != "hc_mult"}
    assert mhc_scopes.read(_run(trace.load(CUT), hf=one_stream),
                           _args("scope_ms_per_execution"), path=CUT) is None


def _device(mix_s, steps, program="jit_decode_step(1)"):
    """Hand-made capture: ``steps`` executions of 20 ms; in each the
    fan-out, then around attention and the experts the coefficients, the
    Sinkhorn kernel, the read and the update, and an operation the
    compiler left without a name stack between two of the update's."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.03
        mods.append(Event(program, t0, 0.020))
        stack = "jit(step)/while/body/"
        for name, start, dur, scope in (
                ("broadcast.1", 0.0001, 0.0001, "mhc_fan/tile"),
                ("fusion.2", 0.0010, 0.0002, "attn/mhc_coeff/dot_general"),
                ("mhc_sinkhorn.3", 0.0012, 0.0001, "attn/mhc_sinkhorn/pallas_call"),
                ("fusion.4", 0.0014, mix_s, "attn/mhc_mix/mul"),
                ("decode.5", 0.0050, 0.0005, "attn/mla_cache/pallas_call"),
                ("fusion.6", 0.0060, mix_s, "attn/mhc_mix/mul"),
                ("copy.7", 0.0095, 0.0001, None),
                ("fusion.8", 0.0096, 0.0001, "attn/mhc_mix/concatenate"),
                ("fusion.9", 0.0100, 0.0050, "mlp/moe_experts/gmm")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5, prompt=100):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def test_mixing_decode_metrics_from_scope_time_and_live_sequences(monkeypatch):
    steps, live = 5, 50
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5),
               trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.0003, steps)})
    ms, n = mhc_scopes.read(run, _args("scope_ms_per_execution"), path=CUT)
    # fan 0.1 + coeff 0.2 + sinkhorn 0.1 + read 0.3 + update 0.3 + 0.1
    # and the unnamed copy between two of the update's operations 0.1
    assert n == steps and ms == pytest.approx(1.2)
    ms, _ = mhc_scopes.read(run, _args("scope_ms_per_execution", ["mhc_sinkhorn"]),
                            path=CUT)
    assert ms == pytest.approx(0.1)
    pct, _ = mhc_scopes.read(run, _args("scope_share_of_program_pct"), path=CUT)
    assert pct == pytest.approx(100 * 1.2 / 20)
    pct, n = mhc_scopes.read(run, _args("decode_roofline_pct"), path=CUT)
    least = mhc_costs.decode_step_bytes(XING4, 1, 2, [0] * live) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0012)
    assert 0 < pct < 100
    with pytest.raises(ValueError, match="unknown stat"):
        mhc_scopes.read(run, _args("nothing"), path=CUT)


def test_mixing_prefill_metrics_from_the_slices_prompts(monkeypatch):
    run = _run(trace.load(CUT), records=_records(3, first_token=1.5, prompt=500)
               + _records(2, first_token=0.2, prompt=900), trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(0.003, 3, "jit_prefill_step(3)")})
    ms, n = mhc_scopes.read(
        run, _args("scope_ms_per_1000_prompt_tokens", program="^jit_prefill_"), path=CUT)
    seconds = 3 * (0.0006 + 2 * 0.003)
    assert n == 3 and ms == pytest.approx(1e6 * seconds / 1500)
    pct, _ = mhc_scopes.read(
        run, _args("prefill_roofline_pct", program="^jit_prefill_"), path=CUT)
    least = mhc_costs.step_bytes(XING4, 1500, executions=3) / 819e9
    assert pct == pytest.approx(100 * least / seconds) and 0 < pct < 100
    # no prompt computed in the slice: nothing to read
    run.records = _records(2, first_token=0.2, prompt=900)
    assert mhc_scopes.read(
        run, _args("prefill_roofline_pct", program="^jit_prefill_"), path=CUT) is None
