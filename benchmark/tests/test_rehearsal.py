"""The whole command at tiny widths on the CPU (``--cpu-rehearsal``:
interpret-mode kernels, four virtual devices for the tp cell). It shows
that the paths, arguments and control flow are right and that the
harness is driven by data. It says nothing about the chip: the command
prints DRY RUN and never the result line.

    python -m pytest benchmark/tests -q        # about three minutes
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import manifest

ROOT = manifest.ROOT


def _run(root, *args, env=None, timeout=600):
    e = dict(os.environ)
    e.pop("DYN_TRACE_JSONL", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=e, capture_output=True, text=True, timeout=timeout)


def _dry_result(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("DRY RUN")
    assert lines[-1].startswith("DRY RUN {"), lines[-1][:200]
    # never the result line: no line of the output is a bare JSON object
    assert not any(ln.startswith("{") for ln in lines)
    return json.loads(lines[-1][len("DRY RUN "):])


def _copy_of_the_benchmark(tmp_path, with_program=True):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    if with_program:
        os.symlink(os.path.join(ROOT, "dynamo_tpu"), os.path.join(root, "dynamo_tpu"))
    return root


@pytest.mark.parametrize("cell,trace", [
    ("phi3-chat", 1), ("phi3-batch", 0), ("mistral-tp4-chat", 1)])
def test_rehearsal(cell, trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", cell, "--seed", "5",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    want = manifest.load_cell(cell)
    if trace:
        # what the CPU can read it reads; no device metric has a value
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
        assert "breakdown" not in res and "busy_s" not in res["device"]
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
        assert all(v["value"] > 0 for v in res["metrics"].values())


THROWAWAY_KIND = '''
import random
from harness.traffic import Plan, Request, tokens

def build(mix, cell, vocab, seed, seconds):
    rng, n = random.Random(seed), int(mix["requests"])
    return Plan("open", [
        Request(f"t{i}", (i + 0.5) * seconds / n, tokens(mix["prompt"], vocab, rng),
                mix["output"], seed=i) for i in range(n)])
'''


def test_a_cell_a_mix_a_kind_a_configuration_and_a_metric_are_added_as_files_only(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    b = os.path.join(root, "benchmark")

    def put(rel, obj):
        with open(os.path.join(b, rel), "w") as f:
            f.write(obj) if isinstance(obj, str) else json.dump(obj, f)

    cfg = json.load(open(os.path.join(b, "configs", "phi3-mini-4k.json")))
    cfg["rehearsal"]["model"]["num_hidden_layers"] = 1
    put("configs/throwaway-config.json", cfg)
    put("traffic/throwaway-mix.json", {
        "kind": "independent", "sampling": {"temperature": 0.0},
        "arrivals": {"process": "gamma", "cv": 2.0},
        "prompt_tokens": {"dist": "fixed", "value": 12, "min": 12, "max": 12},
        "output_tokens": {"dist": "mixture", "parts": [
            {"weight": 3, "dist": "uniform", "min": 3, "max": 5},
            {"weight": 1, "dist": "fixed", "value": 6, "min": 6, "max": 6}]},
        "ramp_s": 1, "drain_s": 20})
    limits = {"ttft_ms": 9e9, "request_mean_gap_ms": 9e9}
    put("cells/throwaway-cell.json", {"loop": "open", "rate": 2.0, "limits": limits})
    # a traffic kind is a module found by its name
    put("generators/throwaway_kind.py", THROWAWAY_KIND)
    put("traffic/throwaway-kind-mix.json", {
        "kind": "throwaway_kind", "requests": 5, "prompt": 9, "output": 3,
        "sampling": {"temperature": 0.0}, "ramp_s": 1, "drain_s": 20})
    put("cells/throwaway-kind-cell.json", {"loop": "open", "limits": limits})
    # and a cell over a mix that is there (sessions over a shared prefix)
    put("cells/throwaway-sessions.json", {"loop": "open", "rate": 3.0, "limits": limits,
                                          "rehearsal": {"rate": 1.5}})
    put("layer_metrics/throwaway_steps.json", {
        "reader": "prom_delta",
        "args": {"metric": "dynamo_scheduler_step_duration_seconds_count"}})
    put("end_to_end/throwaway_ttft_max_ms.json", {
        "reader": "client", "args": {"stat": "ttft_ms", "from": "due", "q": 100}})
    man = json.load(open(os.path.join(root, "BENCHMARK.json")))
    in_every_cell = {m["name"] for m in man["end_to_end"] if "workloads" not in m}
    cells = {"throwaway-cell": "throwaway-mix", "throwaway-kind-cell": "throwaway-kind-mix",
             "throwaway-sessions": "docqa"}
    man["configs"].append({"name": "throwaway-config", "source": "none",
                           "file": "benchmark/configs/throwaway-config.json",
                           "reduced": [], "why": "test"})
    man["workloads"] += [{"name": c, "config": "throwaway-config", "traffic": t,
                          "chips": 1, "why": "test"} for c, t in cells.items()]
    man["end_to_end"].append({"name": "throwaway_ttft_max_ms", "unit": "ms",
                              "better": "lower", "bound": 0.1, "source": "host_clock",
                              "workloads": list(cells)})
    man["per_layer"].append({"name": "throwaway_steps", "unit": "count",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler", "moves": "throwaway_ttft_max_ms",
                             "workloads": list(cells)})
    # the metrics on the shelf (a file each, no manifest entry: nothing
    # judged today is moved by them) come back by entries alone
    shelf = ({f[:-5] for f in os.listdir(os.path.join(b, "layer_metrics"))}
             - {m["name"] for m in man["per_layer"]})
    assert shelf
    man["per_layer"] += [{"name": n, "unit": "ms", "better": "lower",
                          "source": "host_clock", "layer": "shelf",
                          "moves": "throwaway_ttft_max_ms",
                          "workloads": ["throwaway-cell"]} for n in sorted(shelf)]
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))

    def run(cell, trace):
        return _dry_result(_run(root, "--workload", cell, "--seed", "1", "--seconds", "4",
                                "--trace", str(trace), "--cpu-rehearsal"))

    res = run("throwaway-cell", 0)
    assert set(res["metrics"]) == {"throwaway_ttft_max_ms"} | in_every_cell
    assert res["attempted"] == 8 and res["correct"] is True
    res = run("throwaway-cell", 1)
    # with whatever the manifest reports in every cell
    assert res["metrics"]["throwaway_steps"]["value"] > 0
    # ... and the shelf's, but for those only a device trace can give
    from_trace = {n for n in shelf if json.load(open(os.path.join(
        b, "layer_metrics", n + ".json")))["reader"] == "device_trace"}
    assert shelf - from_trace <= set(res["metrics"]) and not from_trace & set(res["metrics"])
    res = run("throwaway-kind-cell", 0)
    assert res["attempted"] == 5 and res["failed"] == 0 and res["correct"] is True
    # 1.5 req/s in threes: 2 sessions in 4 s; a late session's last turns fall due after it
    res = run("throwaway-sessions", 0)
    assert 4 <= res["attempted"] <= 6 and res["failed"] == 0 and res["correct"] is True


def test_without_a_tpu_there_is_no_result():
    proc = _run(ROOT, "--workload", "phi3-chat", "--seed", "1", "--seconds", "5",
                "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_without_the_program_there_is_no_result(tmp_path):
    root = _copy_of_the_benchmark(tmp_path, with_program=False)
    proc = _run(root, "--workload", "phi3-chat", "--seed", "1", "--seconds", "5",
                "--trace", "0", env={"JAX_PLATFORMS": ""})
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_a_performance_switch_in_a_configuration_is_refused(tmp_path):
    from harness import server

    cfg = json.load(open(os.path.join(manifest.BENCH_DIR, "configs",
                                      "phi3-mini-4k.json")))
    cfg["serve"]["multi_step_decode"] = 4
    with pytest.raises(ValueError, match="performance switches"):
        server.build_flags(cfg, "x", str(tmp_path), 0, 1, False)
