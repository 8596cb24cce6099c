"""What the ``sdar-reasoning`` cell brings as code: the reader of the
block family's scopes and counters (``readers/block_scopes.py``) and the
reference's own bookkeeping (``references/sdar.py``), against hand-made
inputs and the cut of a traced v5e run of PR 23 (``data/v5e-spans.*``: a
program from before the scopes, which has to give nothing to read and
never raise)."""

import os

import pytest

from attention_costs import per_head_kv
from harness import prom, trace
from harness.manifest import Cell, load_cell, load_manifest
from harness.rundata import RunData
from harness.trace import Event
from readers import block_scopes, expert_costs, moe_scopes
from references import sdar as reference

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "v5e-spans.xplane.pb")
SDAR = load_cell("sdar-reasoning").config
# the block family's own seven (``block_scopes``), and the program's time
# and the experts' under the names every cell reads them by (PR 58:
# ``readers/moe_scopes.py`` reads what ``block_scopes`` read under the
# cell's own two names)
GENERAL = {"decode_program_ms_per_step", "moe_experts_ms_per_step"}
METRICS = {
    "block_passes_per_block": "scheduler",
    "block_tokens_per_row_pass": "scheduler",
    "block_commit_pass_share": "scheduler",
    "decode_program_ms_per_step": "compiled programs",
    "block_attn_ms_per_step": "Pallas kernels",
    "block_attn_roofline": "Pallas kernels",
    "block_select_ms_per_step": "compiled programs",
    "moe_experts_ms_per_step": "routed experts",
    "sdar_experts_roofline": "routed experts",
}
PASSES = "dynamo_scheduler_block_row_passes_total"


def test_the_cell_lists_the_nine_metrics_and_only_there():
    cell = load_cell("sdar-reasoning")
    assert cell.chips == 1 and cell.traffic_name == "reasoning-gen"
    assert cell.config["reference"] == "sdar"
    assert cell.config["attention_cost"] == "per_head_kv"
    assert cell.cell["loop"] == "closed" and cell.cell["clients"] == 32
    assert cell.cell["limits"] == load_cell("trinity-longdoc").cell["limits"]
    got = {m.name: m for m in cell.per_layer}
    for name in METRICS:
        assert got[name].reader == ("moe_scopes" if name in GENERAL
                                    else "block_scopes")
        assert got[name].moves == "itl_p50_ms"
    for m in load_manifest()["per_layer"]:
        if m["name"] in METRICS:
            cells = m.get("workloads")
            if m["name"] in GENERAL:
                assert cells is None or "sdar-reasoning" in cells
            else:
                assert cells == ["sdar-reasoning"]
            assert m["layer"] == METRICS[m["name"]]
    # every metric that lists no cells is read here too
    everywhere = {m["name"] for m in load_manifest()["per_layer"]
                  if "workloads" not in m}
    assert everywhere <= set(got)


def test_the_configuration_is_the_catalogs_but_for_depth_and_context():
    assert SDAR["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: SDAR[k] for k in published} == published
    assert SDAR["num_hidden_layers"] == 7
    assert SDAR["max_position_embeddings"] == SDAR["serve"]["max_model_len"] == 3200
    assert (SDAR["block_length"], SDAR["denoising_steps"],
            SDAR["remasking_strategy"]) == (4, 2, "sequential")
    assert 0 <= SDAR["mask_token_id"] < SDAR["vocab_size"]
    # every slot holds the longest request of the mix
    mix = load_cell("sdar-reasoning").traffic
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest + 128 == SDAR["serve"]["max_model_len"]
    assert SDAR["serve"]["num_kv_blocks"] == 32 * SDAR["serve"]["max_model_len"] // 16


@pytest.mark.parametrize("block,steps,want", [
    (4, 1, [4]), (4, 2, [2, 2]), (4, 4, [1, 1, 1, 1]), (4, 3, [2, 1, 1]),
    (8, 3, [3, 3, 2])])
def test_the_references_quotas_and_limits(block, steps, want):
    assert reference.quotas(block, steps) == want
    assert 0 < reference.LOGPROB_MEAN_ATOL < reference.LOGPROB_ATOL
    with pytest.raises(NotImplementedError, match="sdar_moe"):
        reference.build({**SDAR, "model_type": "mixtral"}, 128, 1)
    with pytest.raises(NotImplementedError, match="low_confidence_dynamic"):
        reference.build({**SDAR, "remasking_strategy":
                         "low_confidence_dynamic"}, 128, 1)
    with pytest.raises(NotImplementedError, match="norm_topk_prob"):
        reference.build({**SDAR, "norm_topk_prob": False}, 128, 1)


def _run(t=None, **kw):
    cell = Cell("sdar-reasoning", 1, {}, "sdar-30b-a3b-chat", SDAR,
                "reasoning-gen", {"drain_s": 0}, [], [])
    fields = dict(cell=cell, hf=SDAR, serve={}, seconds=1.0,
                  window=(0.0, 1.0), setup_seconds=0.0, records=[],
                  prom_start={}, prom_end={}, device_trace=t,
                  device_kind="TPU v5 lite")
    fields.update(kw)
    return RunData(**fields)


def _args(stat, scopes=(), program="^jit_decode_", **more):
    return {"stat": stat, "scopes": list(scopes), "program": program, **more}


RATIO = {"stat": "counter_ratio", "numerator": PASSES,
         "denominator": "dynamo_scheduler_blocks_completed_total"}


def test_a_program_without_block_scopes_gives_nothing_and_does_not_raise():
    run = _run(trace.load(CUT))
    for stat, scopes, program in (
            ("scope_ms_per_execution", ["block_attn"], "^jit_decode_"),
            ("scope_ms_per_execution", ["block_select"], "^jit_decode_"),
            ("block_attn_roofline_pct", ["block_attn"], "^jit_decode_"),
            ("experts_decode_roofline_pct", ["moe_experts"], "^jit_decode_"),
            ("scope_ms_per_execution", ["block_attn"], "^jit_nothing")):
        assert block_scopes.read(
            run, _args(stat, scopes, program, phase="decode"), path=CUT) is None
    # no capture at all, no counters, and a parent's /metrics without them
    assert block_scopes.read(_run(), _args("scope_ms_per_execution",
                                           ["block_attn"])) is None
    assert block_scopes.read(_run(), RATIO) is None
    other = prom.parse("dynamo_kv_block_usage_ratio 0.5\n")
    assert block_scopes.read(_run(prom_start=other, prom_end=other), RATIO) is None


def _device(steps, program="jit_decode_block"):
    """Hand-made capture: ``steps`` executions of 20 ms; in each a
    projection, the verify kernel, the experts' products, the head,
    sampling (with an operation the compiler left without a name stack
    inside it) and the choice."""
    ops, mods = [], []
    for i in range(steps):
        t0 = i * 0.03
        mods.append(Event(program, t0, 0.020))
        stack = "jit(decode_block)/"
        for name, start, dur, scope in (
                ("fusion.1", 0.0010, 0.0004, "while/body/attn/dot_general"),
                ("verify.2", 0.0015, 0.0002, "while/body/attn/block_attn/pallas_call"),
                ("fusion.3", 0.0020, 0.0110, "while/body/mlp/moe_experts/pallas_call"),
                ("fusion.4", 0.0140, 0.0010, "lm_head/dot_general"),
                ("fusion.5", 0.0150, 0.0008, "sampling/reduce"),
                ("sort.6", 0.0158, 0.0002, None),
                ("fusion.7", 0.0160, 0.0004, "sampling/log_softmax"),
                ("fusion.8", 0.0165, 0.0001, "block_select/select_n")):
            ops.append(Event(name, t0 + start, dur, own=dur,
                             detail=stack + scope if scope else ""))
    return {"ops": ops, "modules": mods}


def _records(n, first_token=1.5, prompt=100):
    return [{"token_times": [first_token, 10.0], "chunk_tokens": [4, 4],
             "prompt_tokens": prompt, "status": 200, "error": None,
             "done": True, "group": None, "send": 0.0}
            for _ in range(n)]


def _moe(active, slots, rows):
    return prom.parse(
        'dynamo_moe_active_experts_total{phase="decode"} %d\n'
        'dynamo_moe_expert_slots_total{phase="decode"} %d\n'
        'dynamo_moe_routed_rows_total{phase="decode"} %d\n'
        % (active, slots, rows))


def test_block_metrics_from_scope_time_live_sequences_and_counters(monkeypatch):
    steps, live = 5, 32
    run = _run(trace.load(CUT), records=_records(live, first_token=0.5,
                                                 prompt=1200),
               trace_slice=(1.0, 2.0),
               prom_samples=[(0.9, _moe(0, 0, 0)),
                             (2.1, _moe(50 * 7 * 128, 50 * 7 * 128,
                                        50 * 7 * 1024))])
    monkeypatch.setattr(moe_scopes, "load_op_events",
                        lambda path: {0: _device(steps)})
    by_file = {m.name: m for m in load_cell("sdar-reasoning").per_layer}
    program = by_file["decode_program_ms_per_step"]
    ms, n = moe_scopes.read(run, program.args, path=CUT)
    assert n == steps and ms == pytest.approx(20.0)
    ms, n = block_scopes.read(run, _args("scope_ms_per_execution",
                                         ["block_attn"]), path=CUT)
    assert n == steps and ms == pytest.approx(0.2)
    # the unnamed sort between two of sampling's operations is sampling's
    ms, _ = block_scopes.read(run, _args("scope_ms_per_execution",
                                         ["sampling", "block_select"]), path=CUT)
    assert ms == pytest.approx(0.8 + 0.2 + 0.4 + 0.1)
    ms, _ = block_scopes.read(run, _args("scope_ms_per_execution",
                                         ["moe_experts"]), path=CUT)
    assert ms == pytest.approx(11.0)
    # and under the name every cell reads it by, through the general reader
    experts = by_file["moe_experts_ms_per_step"]
    assert moe_scopes.read(run, experts.args, path=CUT) == (
        pytest.approx(11.0), steps)
    # 1204 tokens of context (the prompt and one block) a sequence: K and V
    # of every key once a pass, whatever the block's four queries
    pct, n = block_scopes.read(run, _args("block_attn_roofline_pct",
                                          ["block_attn"]), path=CUT)
    least = live * per_head_kv.decode_step_bytes(SDAR, 1, 2, [1204]) / 819e9
    assert n == steps and pct == pytest.approx(100 * least / 0.0002)
    assert per_head_kv.decode_step_bytes(SDAR, 1, 2, [1204]) == (
        2 * 1204 * 4 * 128 * 2 * 7)
    # every expert of seven layers a pass, 1024 rows a layer
    pct, _ = block_scopes.read(run, _args(
        "experts_decode_roofline_pct", ["moe_experts"], phase="decode"), path=CUT)
    per_pass = expert_costs.decode_bytes(SDAR, 7 * 128, 7 * 1024)
    assert pct == pytest.approx(100 * per_pass / 819e9 / 0.011)
    assert 0 < pct < 100
    with pytest.raises(ValueError, match="unknown stat"):
        block_scopes.read(run, _args("nothing", ["block_attn"]), path=CUT)


def test_passes_tokens_and_the_commit_share_from_the_programs_counters():
    def sample(denoise, commit, blocks, tokens):
        return prom.parse(
            '%s{kind="denoise"} %d\n%s{kind="commit"} %d\n'
            "dynamo_scheduler_blocks_completed_total %d\n"
            "dynamo_scheduler_block_tokens_emitted_total %d\n"
            % (PASSES, denoise, PASSES, commit, blocks, tokens))

    run = _run(prom_start=sample(100, 50, 50, 190),
               prom_end=sample(2080, 1050, 1050, 4180))
    by_name = {m.name: m for m in load_cell("sdar-reasoning").per_layer}
    read = lambda name: block_scopes.read(run, by_name[name].args)  # noqa: E731
    assert read("block_passes_per_block") == pytest.approx(2980 / 1000)
    assert read("block_tokens_per_row_pass") == pytest.approx(3990 / 2980)
    assert read("block_commit_pass_share") == pytest.approx(100 * 1000 / 2980)
