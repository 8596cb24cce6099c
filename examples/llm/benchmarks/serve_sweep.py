"""Real-chip serving sweep: flagship-shape engine behind the OpenAI frontend.

Stands up ``in=http out=jax`` with the flagship Llama-3.2-1B-class config
(random-init weights — this measures serving performance, not model
quality) and drives ``loadgen.py`` concurrency levels against it,
mirroring the reference's perf.sh methodology (reference:
examples/llm/benchmarks/perf.sh:18-54 — genai-perf concurrency sweep at
fixed ISL/OSL). Writes one results JSON.

    python examples/llm/benchmarks/serve_sweep.py \
        --out examples/llm/benchmarks/results/serving_tpu_r04.json

The model dir is synthesized on the fly: flagship config.json + the test
tokenizer (512-entry BPE). Sampled ids outside the tokenizer's range
decode to empty strings, which is fine for timing: every generated token
still crosses the full scheduler/detokenizer/SSE path.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def make_flagship_dir(tmp: str, smoke: bool = False) -> str:
    from fixtures import make_model_dir
    from __graft_entry__ import FLAGSHIP

    dims = dict(FLAGSHIP)
    if smoke:  # tiny dims: harness logic check on CPU, not a measurement
        dims.update(hidden_size=64, intermediate_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16)
        dims.pop("vocab_size")  # tokenizer-sized vocab is fine for smoke
    overrides = {
        "hidden_size": dims["hidden_size"],
        "intermediate_size": dims["intermediate_size"],
        "num_hidden_layers": dims["num_layers"],
        "num_attention_heads": dims["num_heads"],
        "num_key_value_heads": dims["num_kv_heads"],
        "head_dim": dims["head_dim"],
        "rope_theta": dims["rope_theta"],
    }
    if "vocab_size" in dims:
        overrides["vocab_size"] = dims["vocab_size"]
    return make_model_dir(tmp, name="flagship-1b", context_length=2048,
                          config_overrides=overrides)


def make_draft_dir(tmp: str, target_dir: str, layers: int,
                   smoke: bool = False) -> str:
    """Same-tokenizer quarter-width draft next to the target: the engine
    requires exact vocab match (engine/serving.build_draft_config)."""
    import json as _json
    import shutil

    from fixtures import make_model_dir
    from __graft_entry__ import FLAGSHIP

    dims = dict(FLAGSHIP)
    if smoke:
        dims.update(hidden_size=64, intermediate_size=128,
                    num_heads=4, num_kv_heads=2, head_dim=16)
    overrides = {
        "hidden_size": max(dims["hidden_size"] // 4, 64),
        "intermediate_size": max(dims["intermediate_size"] // 4, 128),
        "num_hidden_layers": layers,
        "num_attention_heads": max(dims["num_heads"] // 4, 2),
        "num_key_value_heads": max(dims["num_kv_heads"] // 4, 1),
        "head_dim": dims["head_dim"],
        "rope_theta": dims["rope_theta"],
    }
    with open(os.path.join(target_dir, "config.json")) as f:
        overrides["vocab_size"] = _json.load(f)["vocab_size"]
    d = make_model_dir(tmp, name="flagship-draft", context_length=2048,
                       config_overrides=overrides)
    # identical tokenizer files (the two must share a tokenizer)
    for fn in ("tokenizer.json", "tokenizer_config.json"):
        src = os.path.join(target_dir, fn)
        if os.path.exists(src):
            shutil.copy(src, os.path.join(d, fn))
    return d


async def scrape_spec_metrics(url: str) -> dict:
    """Pull speculation counters off the frontend's /metrics gauges."""
    import re

    import aiohttp

    out = {}
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{url}/metrics") as r:
                text = await r.text()
        for key in ("spec_proposed_tokens", "spec_accepted_tokens"):
            # in-process engines expose dynamo_scheduler_*_total counters
            # (telemetry registry); subprocess/BYO engines still surface
            # dict snapshots as dynamo_engine_* callback gauges
            m = re.search(
                rf"^dynamo_scheduler_{key}_total ([0-9.eE+-]+)$", text,
                re.MULTILINE,
            ) or re.search(rf"^dynamo_engine_{key} ([0-9.eE+-]+)$", text,
                           re.MULTILINE)
            if m:
                out[key] = float(m.group(1))
    except Exception:
        pass
    if out.get("spec_proposed_tokens"):
        out["acceptance_rate"] = round(
            out.get("spec_accepted_tokens", 0.0)
            / out["spec_proposed_tokens"], 4)
    return out


async def wait_ready(url: str, timeout_s: float, server) -> None:
    import aiohttp

    deadline = time.monotonic() + timeout_s
    async with aiohttp.ClientSession() as s:
        while time.monotonic() < deadline:
            if server.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={server.returncode} during warmup "
                    "(see its log tail below)")
            try:
                async with s.get(f"{url}/health") as r:
                    if r.status == 200:
                        return
            except Exception:
                pass
            await asyncio.sleep(2.0)
    raise TimeoutError(f"server at {url} not ready in {timeout_s:.0f}s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--port", type=int, default=8123)
    ap.add_argument("--concurrency", default="1,4,8,16,32")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--isl", type=int, default=1000)
    ap.add_argument("--osl", type=int, default=150)
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--multi-step-decode", type=int, default=8)
    ap.add_argument("--quantization", default=None)
    ap.add_argument("--warmup-timeout", type=float, default=1500.0)
    ap.add_argument("--note", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model on CPU (JAX_PLATFORMS=cpu): harness "
                         "logic check, not a measurement")
    ap.add_argument("--server-arg", action="append", default=[],
                    help="extra flag passed through to cli.run (repeat; "
                         "e.g. --server-arg=--kv-cache-dtype "
                         "--server-arg=fp8) — lets a chip sweep exercise "
                         "any serving lever without editing the harness")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="synthesize a same-tokenizer draft model with "
                         "this many layers (quarter width) and serve "
                         "with --spec-draft-model: measures draft-model "
                         "speculation end to end, acceptance scraped "
                         "from /metrics (0 = off)")
    ap.add_argument("--spec-draft-tokens", type=int, default=4)
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="serve_sweep_")
    model_dir = make_flagship_dir(tmp, smoke=args.smoke)
    draft_dir = None
    if args.spec_draft_layers:
        draft_dir = make_draft_dir(
            tmp, model_dir, layers=args.spec_draft_layers, smoke=args.smoke)
    url = f"http://127.0.0.1:{args.port}"

    cmd = [
        sys.executable, "-m", "dynamo_tpu.cli.run",
        "in=http", "out=jax",
        "--model-path", model_dir, "--model-name", "flagship-1b",
        "--allow-random-weights",
        "--http-port", str(args.port),
        "--max-batch-size", str(args.max_batch_size),
        "--max-model-len", "2048",
        "--num-kv-blocks", "2048",
        "--multi-step-decode", str(args.multi_step_decode),
    ]
    if args.quantization:
        cmd += ["--quantization", args.quantization]
    if draft_dir is not None:
        cmd += ["--spec-draft-model", draft_dir,
                "--spec-draft-tokens", str(args.spec_draft_tokens)]
    cmd += args.server_arg
    env = dict(os.environ)
    if args.smoke:
        env["JAX_PLATFORMS"] = "cpu"
    server_log = os.path.join(tmp, "server.log")
    with open(server_log, "w") as lf:
        server = subprocess.Popen(
            cmd, cwd=REPO, stdout=lf, stderr=subprocess.STDOUT, env=env,
            start_new_session=True,
        )
    levels = []

    def write_out(t_ready: float) -> None:
        # re-written after every level: an aborted sweep (loadgen
        # timeout, Ctrl-C) keeps the levels already measured — real-chip
        # time is too scarce to lose an hour of completed levels
        out = {
            "note": args.note or (
                "Serving sweep on ONE real TPU v5e chip: "
                "flagship 1B-class llama (random weights), in=http "
                "out=jax, streaming chat completions. Measures the full "
                "stack: HTTP+SSE, preprocessor, continuous batching, "
                "chunked prefill, fused multi-step decode."),
            "config": {
                "model": "llama-1b-class (FLAGSHIP dims)",
                "max_batch_size": args.max_batch_size,
                "multi_step_decode": args.multi_step_decode,
                "quantization": args.quantization,
                "server_args": args.server_arg,  # the lever under test
                "spec_draft_layers": args.spec_draft_layers or None,
                "spec_draft_tokens": (args.spec_draft_tokens
                                      if args.spec_draft_layers else None),
                "isl": args.isl, "osl": args.osl,
            },
            **({"spec": spec_box} if spec_box else {}),
            "sweep_wall_s": round(time.monotonic() - t_ready, 1),
            "levels": levels,
        }
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    spec_box: dict = {}
    try:
        asyncio.run(wait_ready(url, args.warmup_timeout, server))
        t_ready = time.monotonic()
        for c in [int(x) for x in args.concurrency.split(",")]:
            try:
                lg = subprocess.run(
                    [sys.executable, "examples/llm/benchmarks/loadgen.py",
                     "--url", url, "--model", "flagship-1b",
                     "--concurrency", str(c),
                     "--requests", str(max(args.requests, 2 * c)),
                     "--isl", str(args.isl), "--osl", str(args.osl)],
                    cwd=REPO, capture_output=True, text=True, timeout=1800,
                )
            except subprocess.TimeoutExpired:
                print(f"loadgen c={c} timed out; keeping completed "
                      "levels", flush=True)
                break
            for line in lg.stdout.splitlines():
                line = line.strip()
                if line.startswith("{"):
                    try:
                        lvl = json.loads(line)
                    except ValueError:
                        continue  # log line that happens to start with '{'
                    levels.append(lvl)
                    print(json.dumps(lvl), flush=True)
            if lg.returncode != 0:
                print(f"loadgen c={c} rc={lg.returncode}: "
                      f"{lg.stderr[-500:]}", flush=True)
            if draft_dir is not None:
                spec_box.update(asyncio.run(scrape_spec_metrics(url)))
            write_out(t_ready)
        write_out(t_ready)
        print(f"wrote {args.out}", flush=True)
    finally:
        try:
            os.killpg(server.pid, signal.SIGTERM)
        except Exception:
            server.terminate()
        try:
            server.wait(timeout=20)
        except Exception:
            try:
                os.killpg(server.pid, signal.SIGKILL)
            except Exception:
                pass
        sys.stdout.write(open(server_log).read()[-2000:])


if __name__ == "__main__":
    main()
