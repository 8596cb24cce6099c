"""What the ``sala-longdoc`` cell brings as code: what block-sparse
attention must read and multiply (``attention_costs/
block_sparse_kv.py``), the lightning state's byte and FLOP model
(``readers/lightning_costs.py``) and the reader of the family's scopes
and counters (``readers/sala_scopes.py``), against hand-made inputs and
the cut of a traced v5e run of PR 23 (``data/v5e-spans.*``: a program
from before the scopes, which has to give nothing to read and never
raise)."""

import os

import pytest

from attention_costs import block_sparse_kv
from harness import prom, trace
from harness.manifest import Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import lightning_costs, moe_scopes, sala_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
SALA = load_cell("sala-longdoc").config
SP = block_sparse_kv.sparse_config(SALA)
METRICS = {
    "lightning_ms_per_step": "linear attention (lightning)",
    "lightning_decode_roofline": "linear attention (lightning)",
    "lightning_prefill_roofline": "linear attention (lightning)",
    "sparse_select_ms_per_step": "block-sparse attention (InfLLM-V2)",
    "sparse_attn_ms_per_step": "block-sparse attention (InfLLM-V2)",
    "sparse_decode_roofline": "block-sparse attention (InfLLM-V2)",
    "sparse_kept_share": "block-sparse attention (InfLLM-V2)",
    "linear_sparse_share_of_decode_step": "compiled programs",
}
# the kept share is read in every cell that counts kept and context keys
SHARED = {"sparse_kept_share"}


@pytest.mark.parametrize("n,want", [
    (1, 1), (8192, 8192),            # dense up to dense_len
    # 8193: blocks 0..128 visible; the window starts in block 96, so 33
    # window blocks, the first, and 64 of the 95 others; the last block
    # holds one token
    (8193, (33 + 1 + 64) * 64 - 63),
    (16384, (32 + 1 + 64) * 64),     # a block's edge: 32 window blocks
    (17920, (32 + 1 + 64) * 64),
    (17921, (33 + 1 + 64) * 64 - 63),
])
def test_kept_tokens_of_the_published_sparse_config(n, want):
    assert block_sparse_kv.kept_tokens(n, SP) == want


def test_kept_tokens_with_few_blocks_to_choose_from():
    sp = {**SP, "dense_len": 64, "window_size": 64, "topk": 2}
    # 300 tokens: five blocks, the window's two, the first, both others
    assert block_sparse_kv.kept_tokens(300, sp) == 300
    # 400 tokens: seven blocks, window 5..6, first, two of four others
    assert block_sparse_kv.kept_tokens(400, sp) == 5 * 64 - 48
    assert block_sparse_kv.compressed_keys(400, sp) == (400 - 32) // 16 + 1
    assert block_sparse_kv.compressed_keys(64, sp) == 0       # dense: none scored
    assert block_sparse_kv.compressed_keys(8192, SP) == 0


def test_decode_bytes_and_prefill_flops_count_three_layers_of_twelve():
    assert block_sparse_kv.sparse_layers(SALA) == 3
    assert lightning_costs.lightning_layers(SALA) == 9
    one = block_sparse_kv.decode_step_bytes(SALA, 1, 2, [16384])
    # K and V of 6208 kept tokens and 1023 compressed keys, 2 kv heads x
    # 128 x 2 B, three layers
    assert one == (2 * 6208 + 1023) * 2 * 128 * 2 * 3
    assert block_sparse_kv.decode_step_bytes(SALA, 1, 2, [16384, 16384]) == 2 * one
    assert block_sparse_kv.decode_step_bytes(SALA, 1, 2, [100]) == 2 * 100 * 512 * 3
    # a dense decode step would read 16384 keys: the share the step keeps
    assert one < 0.45 * 2 * 16384 * 512 * 3
    flops = block_sparse_kv.prefill_flops(SALA, 1, [(9000, 2)])
    want = sum(4 * block_sparse_kv.kept_tokens(n, SP)
               + 2 * block_sparse_kv.compressed_keys(n, SP) for n in (9001, 9002))
    assert flops == want * 32 * 128 * 3


@pytest.mark.parametrize("context,pair", [
    (2216, "cell"), (8192, "cell"), (8193, "selecting"), (16016, "selecting")])
def test_the_references_limits_by_context(context, pair):
    """Up to dense_len the cell's pair; past it, where the pick of blocks
    is discrete, the wider pair (scripts/long_probes.py)."""
    from references import minicpm_sala as ref

    want = {"cell": (ref.LOGPROB_ATOL, ref.LOGPROB_MEAN_ATOL),
            "selecting": (ref.SELECTING_LOGPROB_ATOL,
                          ref.SELECTING_LOGPROB_MEAN_ATOL)}[pair]
    assert ref.limits_for({"sparse_config": SP}, context) == want
    assert ref.limits_for({}, context) == want      # the published group
    assert ref.limits_for({"sparse_config": {"dense_len": 64}}, context) == (
        ref.SELECTING_LOGPROB_ATOL, ref.SELECTING_LOGPROB_MEAN_ATOL)
    assert want[0] > want[1] > 0


def test_state_record_of_minicpm_sala():
    assert lightning_costs.state_elements(SALA) == 32 * 128 * 128
    assert lightning_costs.record_bytes(SALA) == 32 * 128 * 128 * 4
    one = lightning_costs.decode_step_bytes(SALA, 1, 2, [12000])
    assert one == 9 * 2 * 2097152
    assert lightning_costs.decode_step_bytes(SALA, 4, 1, [16, 4000, 7]) == 3 * one
    assert lightning_costs.scan_flops(SALA, 2048) == 4 * 2048 * 9 * 32 * 128 * 128


def test_the_cell_lists_the_eight_metrics_and_only_there():
    cell = load_cell("sala-longdoc")
    assert cell.chips == 1 and cell.traffic_name == "longdoc-gen"
    assert cell.config["reference"] == "minicpm_sala"
    assert cell.config["attention_cost"] == "block_sparse_kv"
    assert cell.cell["loop"] == "closed" and cell.cell["clients"] == 24
    got = {m.name: m for m in cell.per_layer}
    for name in METRICS:
        assert got[name].reader == "sala_scopes" and got[name].moves == "itl_p50_ms"
    for m in load_manifest()["per_layer"]:
        if m["name"] in METRICS:
            # its own readings here alone; a reading another family's
            # cell makes too lists that cell as well (PR 58)
            assert m["workloads"] == ["sala-longdoc"] or (
                m["name"] in SHARED and "sala-longdoc" in m["workloads"])
            assert m["layer"] == METRICS[m["name"]]
    # the configuration as the catalog has it, but for the three cuts
    assert SALA["reduced"] == ["num_hidden_layers", "mixer_types",
                               "max_position_embeddings"]
    assert (SALA["num_hidden_layers"], SALA["max_position_embeddings"]) == (12, 32768)
    assert SALA["mixer_types"] == (["minicpm4"] + ["lightning-attn"] * 6
                                   + ["minicpm4"] * 2 + ["lightning-attn"] * 3)
    assert SALA["depth_cut"] == {"of_layers": 32, "first_layer": 9}
    assert (SALA["hidden_size"], SALA["intermediate_size"], SALA["vocab_size"],
            SALA["lightning_nh"], SALA["num_key_value_heads"]) == (
                4096, 16384, 73448, 32, 2)
    # every prompt of the mix is past dense_len, and the longest request fits
    mix = cell.traffic
    assert mix["prompt_tokens"]["min"] > SP["dense_len"]
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= SALA["serve"]["max_model_len"]
    assert SALA["serve"]["num_kv_blocks"] * 16 >= 24 * longest


def _run(t=None, **kw):
    cell = Cell("sala-longdoc", 1, {}, "minicpm-sala-9b", SALA, "longdoc-gen",
                {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=SALA, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes, program="^jit_decode_"):
    return {"stat": stat, "scopes": list(scopes), "program": program}


def test_a_program_without_salas_scopes_gives_nothing_and_does_not_raise():
    run = _run(trace.load(CUT))
    for stat, scopes, program in (
            ("scope_ms_per_execution", ["lightning"], "^jit_decode_"),
            ("scope_ms_per_execution", ["sparse_attn"], "^jit_decode_"),
            ("scope_share_of_program_pct",
             ["lightning", "sparse_select", "sparse_attn"], "^jit_decode_"),
            ("state_decode_roofline_pct", ["lightning_state"], "^jit_decode_"),
            ("sparse_decode_roofline_pct", ["sparse_select", "sparse_attn"],
             "^jit_decode_"),
            ("scan_prefill_roofline_pct", ["lightning_scan"], "^jit_prefill_"),
            ("scope_ms_per_execution", ["lightning"], "^jit_nothing")):
        assert sala_scopes.read(run, _args(stat, scopes, program), path=CUT) is None
    # no capture at all, and no counters
    assert sala_scopes.read(_run(), _args("scope_ms_per_execution",
                                          ["lightning"])) is None
    ratio = {"stat": "counter_ratio_pct",
             "numerator": "dynamo_sparse_attention_kept_tokens_total",
             "denominator": "dynamo_sparse_attention_context_tokens_total"}
    assert sala_scopes.read(_run(), ratio) is None
    empty = prom.parse("dynamo_other_total 3\n")
    assert sala_scopes.read(_run(prom_start=empty, prom_end=empty), ratio) is None


def _device(steps, program="jit_decode_step(1)"):
    """Hand-made capture: ``steps`` executions of 20 ms; in each a
    lightning mixer (projection, state update or scan), an attention
    layer (projection, selection, kept-page attention) and a
    feed-forward op, with an operation the compiler left without a name
    stack between two of the selection's."""
    ops, mods = [], []
    inner = "lightning_scan" if "prefill" in program else "lightning_state"
    for i in range(steps):
        t0 = i * 0.03
        mods.append(Event(program, t0, 0.020))
        stack = "jit(step)/while/body/"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0010, "lightning/dot_general"),
                ("kernel.2", 0.0020, 0.0015, f"lightning/{inner}/pallas_call"),
                ("fusion.3", 0.0040, 0.0004, "attn/dot_general"),
                ("fusion.4", 0.0050, 0.0003, "attn/sparse_select/top_k"),
                ("copy.5", 0.0053, 0.0001, None),
                ("sort.6", 0.0054, 0.0002, "attn/sparse_select/sort"),
                ("decode.7", 0.0060, 0.0030, "attn/sparse_attn/pallas_call"),
                ("fusion.8", 0.0100, 0.0050, "mlp/dot_general")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5, prompt=100):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [1, 1],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def test_sala_decode_metrics_from_scope_time_and_live_sequences(monkeypatch):
    steps, live = 5, 22
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5,
                                                 prompt=12000),
               trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(steps)})
    ms, n = sala_scopes.read(run, _args("scope_ms_per_execution", ["lightning"]),
                             path=CUT)
    assert n == steps and ms == pytest.approx(2.5)
    ms, _ = sala_scopes.read(run, _args("scope_ms_per_execution",
                                        ["sparse_select"]), path=CUT)
    # the unnamed copy between two of the selection's operations is its
    assert ms == pytest.approx(0.6)
    ms, _ = sala_scopes.read(run, _args("scope_ms_per_execution",
                                        ["sparse_attn"]), path=CUT)
    assert ms == pytest.approx(3.0)
    pct, _ = sala_scopes.read(run, _args(
        "scope_share_of_program_pct",
        ["lightning", "sparse_select", "sparse_attn"]), path=CUT)
    assert pct == pytest.approx(100 * (2.5 + 0.6 + 3.0) / 20)
    pct, n = sala_scopes.read(run, _args("state_decode_roofline_pct",
                                         ["lightning_state"]), path=CUT)
    least = live * 9 * 2 * 2097152 / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0015)
    assert 0 < pct < 100
    # 12 001 tokens of context (the prompt and one emitted) a sequence
    pct, _ = sala_scopes.read(run, _args("sparse_decode_roofline_pct",
                                         ["sparse_select", "sparse_attn"]),
                              path=CUT)
    least = live * block_sparse_kv.decode_step_bytes(SALA, 1, 2, [12001]) / 819e9
    assert pct == pytest.approx(100 * least / 0.0036)
    assert 0 < pct < 100
    assert sala_scopes.read(run, _args("scope_ms_per_execution",
                                       ["lightning_scan"]), path=CUT) is None
    with pytest.raises(ValueError, match="unknown stat"):
        sala_scopes.read(run, _args("nothing", ["lightning"]), path=CUT)


def test_lightning_scan_roofline_from_the_programs_counters(monkeypatch):
    """Tokens a prefill execution from the two counters between the
    samples that bracket the slice, times the executions captured."""
    def sample(tokens, steps):
        return prom.parse(f"dynamo_lightning_scan_tokens_total {tokens}\n"
                          f"dynamo_lightning_scan_steps_total {steps}\n")

    samples = [(0.5, sample(10000, 5)), (0.9, sample(12048, 6)),
               (2.1, sample(30480, 15)), (2.9, sample(99999, 40))]
    run = _run(trace.load(CUT), prom_samples=samples, trace_slice=(1.0, 2.0))
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(3, "jit_prefill_step(3)")})
    args = _args("scan_prefill_roofline_pct", ["lightning_scan"], "^jit_prefill_")
    pct, n = sala_scopes.read(run, args, path=CUT)
    # (30480 - 12048) / (15 - 6) = 2048 tokens an execution, three captured
    flops = 4 * 3 * 2048 * 9 * 32 * 128 * 128
    assert n == 3 and pct == pytest.approx(100 * (flops / 197e12) / 0.0045)
    assert 0 < pct < 100
    # a program without the counters, or no prefill step between the samples
    run.prom_samples = [(0.9, prom.parse("dynamo_other_total 1\n")),
                        (2.1, prom.parse("dynamo_other_total 2\n"))]
    assert sala_scopes.read(run, args, path=CUT) is None
    run.prom_samples = []
    assert sala_scopes.read(run, args, path=CUT) is None


def test_kept_share_is_the_counters_ratio_over_the_window():
    start = prom.parse("dynamo_sparse_attention_kept_tokens_total 1000\n"
                       "dynamo_sparse_attention_context_tokens_total 2000\n")
    end = prom.parse("dynamo_sparse_attention_kept_tokens_total 63080\n"
                     "dynamo_sparse_attention_context_tokens_total 142000\n")
    got = sala_scopes.read(_run(prom_start=start, prom_end=end), {
        "stat": "counter_ratio_pct",
        "numerator": "dynamo_sparse_attention_kept_tokens_total",
        "denominator": "dynamo_sparse_attention_context_tokens_total"})
    assert got == pytest.approx(100 * 62080 / 140000)
