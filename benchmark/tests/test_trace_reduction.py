"""The reduction from a profiler capture to numbers, checked against a
cut of a capture recorded on the v5e (phi3-chat, chip call 1 of PR 22:
one second around a prefill step, ``Async XLA Ops`` and the events' stats
dropped to keep it small). The expected values were worked out straight
from the protobuf by a throw-away script, not by this code."""

import json
import os
import shutil

import pytest

import attention_costs
from harness import manifest, trace
from harness.manifest import Cell
from harness.rundata import RunData
from readers import device_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "v5e-decode-prefill"
# the configuration's file names the module that knows its attention's shape
K_AND_V = {"attention_cost": "per_head_kv"}


@pytest.fixture(scope="module")
def recorded():
    t = trace.load(os.path.join(DATA, NAME + ".xplane.pb"))
    with open(os.path.join(DATA, NAME + ".expected.json")) as f:
        return t, json.load(f)


def test_busy_and_idle_are_the_union_of_op_intervals(recorded):
    t, want = recorded
    assert t.devices == [0]
    assert len(t.ops[0]) == want["n_ops"] and len(t.modules[0]) == want["n_modules"]
    assert t.busy_s[0] == pytest.approx(want["busy_s"], rel=1e-4)
    lo, hi = t.span[0]
    assert hi - lo == pytest.approx(want["device_span_s"], rel=1e-4)
    assert 0.0 < t.idle_share(0) < 1.0
    # own times partition the busy time: a loop is not counted over its body
    assert sum(o.own for o in t.ops[0]) == pytest.approx(want["busy_s"], rel=1e-3)


def test_programs_are_told_apart_by_the_kernel_inside(recorded):
    t, want = recorded
    mods, kern = device_trace._modules_with(t, 0, "paged_decode_attention")
    assert len(mods) == want["decode_programs"]
    assert sum(m.dur for m in mods) == pytest.approx(want["decode_program_s"], rel=1e-4)
    inside = [k for k in kern]
    assert sum(k.own for k in inside) <= want["decode_kernel_s"] * (1 + 1e-4)
    mods, kern = device_trace._modules_with(t, 0, "paged_flash_attention")
    assert len(mods) == want["prefill_programs"] >= 1
    assert sum(m.dur for m in mods) == pytest.approx(want["prefill_program_s"], rel=1e-4)
    assert sum(k.own for k in kern) == pytest.approx(want["flash_kernel_s"], rel=1e-4)
    assert len(kern) == want["flash_kernel_calls"]


def test_kernel_time_is_the_kernels_own_events(recorded):
    t, want = recorded
    dec = [o for o in t.ops[0] if o.name.startswith("paged_decode_attention")]
    assert len(dec) == want["decode_kernel_calls"]
    assert sum(o.own for o in dec) == pytest.approx(want["decode_kernel_s"], rel=1e-4)


def test_breakdown_names_and_gaps(recorded):
    t, _ = recorded
    top = trace.top_ops(t, 0, 10)
    assert len(top) == 10 and top[0][0].startswith("paged_flash_attention")
    assert all(" = " not in name for name, _ in top)
    gaps = trace.idle_gaps(t, 0, 5)
    assert len(gaps) == 5 and gaps[0][1] >= gaps[-1][1] > 0
    assert all("jit_step(" in label or "start" in label for label, _ in gaps)


def test_metrics_from_the_recorded_trace(recorded):
    t, want = recorded
    hf = {"num_attention_heads": 32, "num_key_value_heads": 32, "hidden_size": 3072,
          "num_hidden_layers": 32, "sliding_window": 2047}
    cell = Cell("c", 1, {}, "k", K_AND_V, "m", {"drain_s": 1}, [], [])
    # one sequence of 1000 tokens decoding all through the capture
    rec = {"rid": "a", "group": "", "phase": "window", "due": 0.0, "send": 0.0,
           "prompt_tokens": 1000, "max_tokens": 8, "prefix_tokens": 0,
           "token_times": [0.0, 10.0], "chunk_tokens": [1, 1],
           "usage": None, "done": True, "status": 200, "error": None}
    run = RunData(cell=cell, hf=hf, serve={"tensor_parallel_size": 1}, seconds=1.0,
                  window=(0.0, 10.0), setup_seconds=0.0, records=[rec],
                  prom_start={}, prom_end={}, device_trace=t,
                  trace_slice=(1.0, 2.0), device_kind="TPU v5 lite")
    share, n = device_trace.read(run, {"stat": "decode_kernel_roofline_pct",
                                       "with_op": "paged_decode_attention"})
    assert n == want["decode_programs"]
    # 1001 tokens x 0.5 MiB a token a step, at 819 GB/s, against kernel time
    least = n * 1001 * 2 * 32 * 128 * 2 * 32 / 819e9
    kernel = sum(k.own for k in device_trace._modules_with(
        t, 0, "paged_decode_attention")[1])
    assert share == pytest.approx(100 * least / kernel, rel=1e-4)
    assert device_trace.read(run, {"stat": "idle_pct"}) == pytest.approx(
        100 * (1 - want["busy_s"] / t.window_s), rel=1e-4)
    run.device_kind = "some other chip"
    with pytest.raises(KeyError, match="no published peaks"):
        device_trace.read(run, {"stat": "decode_kernel_roofline_pct",
                                "with_op": "paged_decode_attention"})


def test_the_prompts_computed_in_the_slice_are_the_clients_records(recorded):
    """What the prefill rooflines of the scope readers charge: the
    prompts of the requests whose first token fell in the slice, less a
    shared prefix that an earlier request of the group had computed."""
    t, want = recorded
    cell = Cell("c", 4, {}, "k", K_AND_V, "m", {"drain_s": 1}, [], [])
    rec = {"rid": "a", "group": "", "phase": "window", "due": 0.0, "send": 0.0,
           "prompt_tokens": 300, "max_tokens": 8, "prefix_tokens": 0,
           "token_times": [1.5, 3.0], "chunk_tokens": [1, 1],
           "usage": None, "done": True, "status": 200, "error": None}
    late = dict(rec, rid="b", token_times=[2.5, 3.0])      # first token after the slice
    first = dict(rec, rid="c", group="g", prefix_tokens=100, token_times=[1.2, 3.0])
    second = dict(first, rid="d", send=1.3, token_times=[1.6, 3.0])
    run = RunData(cell=cell, hf={}, serve={"tensor_parallel_size": 4}, seconds=1.0,
                  window=(0.0, 10.0), setup_seconds=0.0,
                  records=[rec, late, first, second],
                  prom_start={}, prom_end={}, device_trace=t,
                  trace_slice=(1.0, 2.0), device_kind="TPU v5 lite")
    # the second of the group finds 96 of the prefix's 100 tokens cached: whole blocks
    assert device_trace._computed_chunks(run) == [(0, 300), (0, 300), (96, 204)]
    assert device_trace.read(run, {"stat": "op_share_of_busy_pct", "op": "all-reduce"}) == 0.0
    with pytest.raises(ValueError, match="unknown stat"):
        device_trace.read(run, {"stat": "program_ms_per_execution",
                                "with_op": "paged_decode_attention"})


# ---- the reader against its parent, and against another cost module ----

with open(os.path.join(DATA, NAME + ".readers.expected.json")) as _f:
    PARENT = json.load(_f)


def _case_run(t, case, config=K_AND_V):
    tp = case["tensor_parallel_size"]
    cell = Cell("c", tp, {}, "k", config, "m", {"drain_s": 1}, [], [])
    return RunData(cell=cell, hf=case["hf"], serve={"tensor_parallel_size": tp},
                   seconds=1.0, window=(0.0, 10.0), setup_seconds=0.0,
                   records=case["records"], prom_start={}, prom_end={},
                   device_trace=t, trace_slice=tuple(PARENT["trace_slice"]),
                   device_kind="TPU v5 lite")


@pytest.mark.parametrize("stat", sorted(PARENT["stats"]))
@pytest.mark.parametrize("case", sorted(PARENT["cases"]))
def test_the_recorded_trace_reduces_as_it_did_before_the_cost_moved(recorded, case, stat):
    """Every statistic of the reader, to the last digit, as the parent
    of PR 25 computed it with the shape knowledge inside the reader."""
    t, _ = recorded
    case = PARENT["cases"][case]
    got = device_trace.read(_case_run(t, case), PARENT["stats"][stat])
    want = case["parent"][stat]
    if stat == "decode_kernel_roofline_pct":
        # the one difference: the parent cut the averaged keys to a whole
        # number; the bytes are now averaged as they are (0 to 1 key more)
        keys = case["parent"]["mean_attended_keys"]
        want = [want[0] * keys / int(keys), want[1]]
        if keys == int(keys):
            assert list(got) == want
        assert list(got) == pytest.approx(want, rel=1e-12)
    else:
        assert (list(got) if isinstance(got, tuple) else got) == want


THROWAWAY_COST = '''
from attention_costs import per_head_kv

def decode_step_bytes(hf, tensor_parallel_size, cache_itemsize, context_lens):
    return per_head_kv.decode_step_bytes(
        hf, tensor_parallel_size, cache_itemsize, context_lens) // 2

def prefill_flops(hf, tensor_parallel_size, chunks):
    return 3 * per_head_kv.prefill_flops(hf, tensor_parallel_size, chunks)
'''


def test_the_roofline_shares_follow_the_cost_module_the_configuration_names(
        recorded, tmp_path, monkeypatch):
    """A module that charges half the bytes halves the decode kernel's
    share; the trace's side (executions, kernel time, peak) is the same."""
    t, _ = recorded
    # the benchmark's directory of cost modules, with one more file in it
    there = tmp_path / "attention_costs"
    shutil.copytree(attention_costs.__path__[0], there,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (there / "throwaway_half.py").write_text(THROWAWAY_COST)
    monkeypatch.setattr(manifest, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(attention_costs, "__path__",
                        [*attention_costs.__path__, str(there)])
    case = PARENT["cases"]["many_sequences_window_one_chip"]
    half = {"attention_cost": "throwaway_half"}
    args = PARENT["stats"]["decode_kernel_roofline_pct"]
    full, n = device_trace.read(_case_run(t, case), args)
    got, n_half = device_trace.read(_case_run(t, case, half), args)
    assert n_half == n and got == pytest.approx(0.5 * full, rel=1e-6)
    # the statistics that need no shape never ask for the module
    assert device_trace.read(_case_run(t, case, {}), PARENT["stats"]["idle_pct"]) > 0
    # and a configuration that names none is refused where one is needed
    with pytest.raises(manifest.ManifestError, match="attention_cost"):
        device_trace.read(_case_run(t, case, {}), PARENT["stats"]["decode_kernel_roofline_pct"])
