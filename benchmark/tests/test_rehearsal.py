"""The whole command at tiny widths on the CPU (``--cpu-rehearsal``:
interpret-mode kernels, four virtual devices for the tp cell). It shows
that the paths, arguments and control flow are right and that the
harness is driven by data. It says nothing about the chip: the command
prints DRY RUN and never the result line.

    python -m pytest benchmark/tests -q        # about four minutes
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


# a throw-away architecture: the trunk's reference under another name
# with one visible difference (a limit), and a cost module of its own
THROWAWAY_REFERENCE = '''
from references.llama_trunk import build  # noqa: F401

LOGPROB_ATOL = 0.149
LOGPROB_MEAN_ATOL = 0.03
'''

THROWAWAY_COST = '''
"""One latent a key, shared by K and V and by every head, on every device."""
LANES = 128


def _padded(n):
    return -(-int(n) // LANES) * LANES


def decode_step_bytes(hf, tensor_parallel_size, cache_itemsize, context_lens):
    line = _padded(hf["kv_lora_rank"]) + _padded(hf["qk_rope_head_dim"])
    return sum(context_lens) * line * cache_itemsize * int(hf["num_hidden_layers"])


def prefill_flops(hf, tensor_parallel_size, chunks):
    heads = max(1, int(hf["num_attention_heads"]) // tensor_parallel_size)
    pairs = sum(start + i + 1 for start, length in chunks for i in range(length))
    width = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]
    return 2 * pairs * heads * width * int(hf["num_hidden_layers"])
'''

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
    # an architecture is modules found by the names the configuration gives
    put("references/throwaway_reference.py", THROWAWAY_REFERENCE)
    put("attention_costs/throwaway_cost.py", THROWAWAY_COST)
    cfg.update(reference="throwaway_reference", attention_cost="throwaway_cost",
               reduced=["num_hidden_layers"])
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
    # every file of layer_metrics/ has its entry since PR 58: the shelf
    # of files without one went, with the code they alone used
    assert ({f[:-5] for f in os.listdir(os.path.join(b, "layer_metrics"))}
            - {"throwaway_steps"}
            == {m["name"] for m in man["per_layer"]} - {"throwaway_steps"})
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))
    assert _files_only(root) == []

    said = []

    def run(cell, trace):
        said.append(_run(root, "--workload", cell, "--seed", "1", "--seconds", "4",
                         "--trace", str(trace), "--cpu-rehearsal"))
        return _dry_result(said[-1])

    res = run("throwaway-cell", 0)
    assert set(res["metrics"]) == {"throwaway_ttft_max_ms"} | in_every_cell
    assert res["attempted"] == 8 and res["correct"] is True
    # the run says which reference judged it, and under which limits
    assert res["reference"]["name"] == "throwaway_reference"
    assert res["reference"]["tokens_compared"] == 64
    line = next(ln for ln in said[-1].stdout.splitlines() if ln.startswith("reference:"))
    assert line.startswith("reference: throwaway_reference,") and "(limit 0.149)" in line
    res = run("throwaway-cell", 1)
    # with whatever the manifest reports in every cell
    assert res["metrics"]["throwaway_steps"]["value"] > 0
    res = run("throwaway-kind-cell", 0)
    assert res["attempted"] == 5 and res["failed"] == 0 and res["correct"] is True
    # 1.5 req/s in threes: 2 sessions in 4 s; a late session's last turns fall due after it
    res = run("throwaway-sessions", 0)
    assert 4 <= res["attempted"] <= 6 and res["failed"] == 0 and res["correct"] is True


def _files_only(root):
    """Nothing that was there has changed: what `git status` would show
    as modified under benchmark/ is empty."""
    changed = []
    for d, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            src = os.path.join(d, f)
            with open(src, "rb") as a, open(os.path.join(
                    root, os.path.relpath(src, ROOT)), "rb") as b:
                if a.read() != b.read():
                    changed.append(os.path.relpath(src, ROOT))
    return changed


# the tiny MLA + MoE keys of tests/test_mla.py and tests/test_moe.py, as a
# published config.json spells them
MLA_MOE_KEYS = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "deepseek_v3",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": None, "qk_rope_head_dim": 8,
    "qk_nope_head_dim": 12, "v_head_dim": 12, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 2.446, "n_group": 1,
    "topk_group": 1, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}

# called with the engine's parameter tree of that family (a dense group
# and an expert group); not a forward: that is a model_config PR's
THROWAWAY_MLA_REFERENCE = '''
import math

LOGPROB_ATOL = 0.15
LOGPROB_MEAN_ATOL = 0.03
WANTED = {"dense_layers": ("w_dkv", "w_kr", "w_uk", "w_uv", "w_gate"),
          "layers": ("w_dkv", "router", "w_gate", "w_sh_gate")}


def build(hf, t_pad, n_out):
    import jax.numpy as jnp

    def forward(params, tokens, out_positions):
        for group, keys in WANTED.items():
            missing = [k for k in keys if k not in params[group]]
            assert not missing, (group, missing, sorted(params[group]))
        dense, experts = (params[g]["w_dkv"].shape[0] for g in WANTED)
        assert dense == hf["first_k_dense_replace"], dense
        assert dense + experts == hf["num_hidden_layers"], (dense, experts)
        assert params["layers"]["router"].shape[-1] == hf["n_routed_experts"]
        vocab = params["embed"].shape[0]
        return jnp.full((n_out, vocab), -math.log(vocab), jnp.float32)

    return forward
'''

READ_THE_STORED_CAPTURE = '''
import json, os, sys
sys.path[:0] = [os.path.join(os.getcwd(), "benchmark"), os.getcwd()]
from harness import manifest, trace
from harness.rundata import RunData, read_metric

cell = manifest.load_cell(sys.argv[1])
rec = {"rid": "a", "group": "", "phase": "window", "due": 0.0, "send": 0.0,
       "prompt_tokens": 1000, "max_tokens": 8, "prefix_tokens": 0,
       "token_times": [0.0, 10.0], "chunk_tokens": [1, 1], "usage": None,
       "done": True, "status": 200, "error": None}
hf = {k: v for k, v in cell.config.items() if k != "rehearsal"}
run = RunData(cell=cell, hf=hf, serve={"tensor_parallel_size": 1}, seconds=1.0,
              window=(0.0, 10.0), setup_seconds=0.0, records=[rec], prom_start={},
              prom_end={}, trace_slice=(1.0, 2.0), device_kind="TPU v5 lite",
              device_trace=trace.load("benchmark/tests/data/v5e-decode-prefill.xplane.pb"))
print(json.dumps({m.name: read_metric(m, run)[0] for m in cell.per_layer
                  if m.reader == "device_trace"}))
'''


def test_an_architecture_is_added_as_files_only(tmp_path):
    """A reference module, a cost module and a configuration that names
    them, beside what is there: a reference that computes something else
    fails the run and says why, and another family of models (a latent
    cache, routed experts, a dense group and an expert group of layers)
    goes through the same harness."""
    root = _copy_of_the_benchmark(tmp_path)
    b = os.path.join(root, "benchmark")

    def put(rel, obj):
        with open(os.path.join(b, rel), "w") as f:
            f.write(obj) if isinstance(obj, str) else json.dump(obj, f)

    trunk = open(os.path.join(b, "references", "llama_trunk.py")).read()
    no_rotary = trunk.replace("    def rope(x, pos):   # x [T, H, D]\n",
                              "    def rope(x, pos):\n        return x\n\n"
                              "    def rotated(x, pos):\n")
    assert no_rotary != trunk
    put("references/throwaway_no_rotary.py", no_rotary)
    put("references/throwaway_mla_moe.py", THROWAWAY_MLA_REFERENCE)
    put("attention_costs/throwaway_latent.py", THROWAWAY_COST)
    cfg = json.load(open(os.path.join(b, "configs", "phi3-mini-4k.json")))
    cfg["reference"] = "throwaway_no_rotary"
    put("configs/throwaway-no-rotary.json", cfg)
    serve = dict(cfg["rehearsal"]["serve"])
    put("configs/throwaway-mla-moe.json", {
        **MLA_MOE_KEYS, "reference": "throwaway_mla_moe",
        "attention_cost": "throwaway_latent", "reduced": ["num_hidden_layers"],
        "serve": serve,
        "rehearsal": {"serve": serve, "probe_scale": 0.03, "attention_impl": "auto"}})
    limits = {"ttft_ms": 9e9, "request_mean_gap_ms": 9e9}
    cells = {"throwaway-no-rotary-cell": "throwaway-no-rotary",
             "throwaway-mla-moe-cell": "throwaway-mla-moe"}
    man = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for cell, config in cells.items():
        put(f"cells/{cell}.json", {"loop": "open", "rate": 1.0, "limits": limits})
        man["configs"].append({"name": config, "source": "none", "reduced": [],
                               "file": f"benchmark/configs/{config}.json", "why": "test"})
        man["workloads"].append({"name": cell, "config": config, "traffic": "chat",
                                 "chips": 1, "why": "test"})
    # a metric classified by a kernel lists the cells that run the kernel
    # (the trunk's list the trunk's cells), so the new family brings its own
    name = "throwaway_latent_decode_roofline"
    put(f"layer_metrics/{name}.json", {
        "reader": "device_trace",
        "args": {"stat": "decode_kernel_roofline_pct",
                 "with_op": "paged_decode_attention"}})
    man["per_layer"].append({
        "name": name, "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "Pallas kernels", "moves": "itl_p50_ms",
        "workloads": ["throwaway-mla-moe-cell"]})
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))
    assert _files_only(root) == []

    def run(cell, trace):
        proc = _run(root, "--workload", cell, "--seed", "3", "--seconds", "4",
                    "--trace", str(trace), "--cpu-rehearsal")
        return _dry_result(proc), proc.stdout

    # the forward without the rotary embedding is another model
    res, out = run("throwaway-no-rotary-cell", 0)
    assert res["correct"] is False and res["failed"] == 0
    assert res["reference"]["name"] == "throwaway_no_rotary"
    line = next(ln for ln in out.splitlines() if ln.startswith("reference:"))
    assert "FAILED" in line and "log-probability" in line

    # another model_type through the same harness: served, probed, the
    # reference called on its parameter tree, every host-side metric read
    res, out = run("throwaway-mla-moe-cell", 1)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["reference"] == {
        "name": "throwaway_mla_moe", "tokens_compared": 64,
        "max_abs_err": res["reference"]["max_abs_err"],
        "mean_abs_err": res["reference"]["mean_abs_err"]}
    assert res["correct"] is False            # a uniform answer is not the model's
    assert "decode_batch_mean" in res["metrics"]
    # its attention metrics go through the cost module it names: on the
    # stored v5e capture, a 1001-token sequence decoding all through it
    proc = subprocess.run([sys.executable, "-c", READ_THE_STORED_CAPTURE,
                           "throwaway-mla-moe-cell"], cwd=root, capture_output=True,
                          text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    executions, kernel = _decode_kernel_in_the_stored_capture()
    # latent 16 and rope key 8, a row of 128 lanes each, 2 bytes, 3 layers
    least_s = len(executions) * 1001 * (128 + 128) * 2 * 3 / 819e9
    assert got == {
        "throwaway_latent_decode_roofline": pytest.approx(
            100 * least_s / sum(o.own for o in kernel), rel=1e-9),
        "device_idle_share": pytest.approx(got["device_idle_share"])}


def _decode_kernel_in_the_stored_capture():
    from harness import trace
    from readers import device_trace

    t = trace.load(os.path.join(manifest.BENCH_DIR, "tests", "data",
                                "v5e-decode-prefill.xplane.pb"))
    return device_trace._modules_with(t, 0, "paged_decode_attention")


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
