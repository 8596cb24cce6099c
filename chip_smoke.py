#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once — ``python -m dynamo_tpu.cli.run in=http
out=jax`` on its default route: HTTP -> preprocessor -> JaxServingEngine
-> scheduler -> ModelRunner programs -> Pallas kernels — at the full
published width of one model, with random weights from a seed, and
checks what comes back.

- Leg A, one chip: Llama-3.2-1B.
- Leg B, four chips in ONE process (``--tensor-parallel-size 4``): the
  Llama-3.1-8B shape. Runs when leg A's child reported >= 4 devices, or
  when asked for with ``--legs b|ab`` (then fewer than four devices is a
  failure).

One process per chip: this parent never imports jax; each leg is one
child that owns the chip(s) for its lifetime, bounded by a wall-clock
limit and killed on expiry. Nothing is read from the network. Without
an accelerator the script exits non-zero and prints no result.
``--cpu-dry-run`` debugs the harness itself at tiny widths on the CPU
(interpret-mode kernels); its output is headed DRY RUN and is never a
pass for the chip.

The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
DEVICE_LINE = "engine device: "

_LLAMA3_ROPE = {
    "rope_type": "llama3", "low_freq_factor": 1.0, "high_freq_factor": 4.0,
    "original_max_position_embeddings": 8192,
}
# published config.json values (meta-llama/Llama-3.2-1B, Llama-3.1-8B —
# the shape of BASELINE's DeepSeek-R1-Distill-Llama-8B)
LLAMA_3_2_1B = {
    "hidden_size": 2048, "num_hidden_layers": 16, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 64, "intermediate_size": 8192,
    "vocab_size": 128256, "rope_theta": 500000.0, "rms_norm_eps": 1e-5,
    "rope_scaling": {**_LLAMA3_ROPE, "factor": 32.0},
    "tie_word_embeddings": True, "max_position_embeddings": 131072,
}
LLAMA_3_1_8B = {
    "hidden_size": 4096, "num_hidden_layers": 32, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
    "vocab_size": 128256, "rope_theta": 500000.0, "rms_norm_eps": 1e-5,
    "rope_scaling": {**_LLAMA3_ROPE, "factor": 8.0},
    "tie_word_embeddings": False, "max_position_embeddings": 131072,
}
# --cpu-dry-run: the same code path at widths the CPU interpreter can
# finish; the vocabulary is the tokenizer's own
TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 8, "intermediate_size": 128,
    "rope_theta": 500000.0, "rope_scaling": {**_LLAMA3_ROPE, "factor": 32.0},
    "tie_word_embeddings": True, "max_position_embeddings": 131072,
}

STARTUP_LIMIT_S = 1000.0  # init + warmup compiles, cold cache (leg A: 447 s)
TRAFFIC_LIMIT_S = 120.0   # the whole request mix took ~15 s on the chip
EXIT_LIMIT_S = 30.0


class Failed(Exception):
    pass


def _check(ok: bool, what: str, failures: list) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        failures.append(what)


def _cache_dir() -> str:
    """Where the children keep compiled programs: placed from outside,
    or the checkout's own (dynamo_tpu/engine/device.py — the child
    reports its directory and the leg checks they agree)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")


def _cache_entries() -> int:
    try:
        return sum(n.endswith("-cache") for n in os.listdir(_cache_dir()))
    except FileNotFoundError:
        return 0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _request(port: int, path: str, body=None) -> urllib.request.Request:
    return urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )


def _http(port: int, path: str, body=None):
    """(status, bytes) — never raises on an HTTP error status."""
    try:
        with urllib.request.urlopen(_request(port, path, body),
                                    timeout=TRAFFIC_LIMIT_S) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _make_model_dir(name: str, overrides: dict) -> str:
    sys.path.insert(0, os.path.join(HERE, "tests"))
    from fixtures import make_model_dir  # tokenizers only; no jax

    return make_model_dir(OUT_DIR, name=name, context_length=2048,
                          config_overrides=overrides)


class Server:
    """One ``cli.run in=http out=jax`` child and its log."""

    def __init__(self, name: str, args: list, env: dict, startup_limit_s):
        self.startup_limit_s = startup_limit_s
        self.port = _free_port()
        self.log_path = os.path.join(OUT_DIR, f"{name}.log")
        self.log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cli.run", "in=http", "out=jax",
             "--http-host", "127.0.0.1", "--http-port", str(self.port), *args],
            cwd=HERE, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def log_text(self) -> str:
        self.log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_ready(self) -> float:
        """Seconds from spawn to the listening line; raises on death or
        on the wall-clock limit."""
        while True:
            if self.proc.poll() is not None:
                raise Failed(
                    f"server exited rc={self.proc.returncode} before it "
                    f"listened:\n{self.log_text()[-3000:]}")
            if "listening on http://" in self.log_text():
                return time.monotonic() - self.t0
            if time.monotonic() - self.t0 > self.startup_limit_s:
                raise Failed(
                    f"server not listening after {self.startup_limit_s:.0f}s:"
                    f"\n{self.log_text()[-3000:]}")
            time.sleep(0.5)

    def device(self) -> dict:
        for line in self.log_text().splitlines():
            if DEVICE_LINE in line:
                return json.loads(line.split(DEVICE_LINE, 1)[1])
        raise Failed("no start-up device line in the server log")

    def stop(self) -> int:
        """SIGTERM, bounded wait, then the exit code (None = killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(EXIT_LIMIT_S)
            except subprocess.TimeoutExpired:
                pass
        rc = self.proc.poll()
        self.kill()
        return rc

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self.log.close()


def _tokens_of(choice: dict) -> list:
    """The generated token stream as the API renders it: token text, or
    the decimal id for ids past the tiny tokenizer."""
    lp = choice.get("logprobs") or {}
    if "content" in lp:   # chat
        return [(e["token"], e["logprob"]) for e in lp["content"]]
    return list(zip(lp.get("tokens", []), lp.get("token_logprobs", [])))


def _traffic(port: int, model: str, scale: float, failures: list) -> None:
    """The request mix of ISSUE 21; ``scale`` shrinks prompt and output
    lengths for the dry run."""
    max_tokens = max(4, int(64 * scale))
    status, body = _http(port, "/v1/models")
    ids = [m["id"] for m in json.loads(body).get("data", [])] if status == 200 else []
    _check(status == 200 and model in ids, f"/v1/models lists {model}", failures)

    # shorter than one KV block, so the repeat below cannot hit the
    # prefix cache and must retrace exactly the same programs
    chat = {
        "model": model, "max_tokens": max_tokens, "temperature": 0,
        "ignore_eos": True, "logprobs": True,
        "messages": [{"role": "user", "content": "hello world"}],
    }

    def run_chat():
        status, body = _http(port, "/v1/chat/completions", chat)
        d = json.loads(body) if status == 200 else {}
        ok = (status == 200 and d["usage"]["completion_tokens"] == max_tokens)
        return ok, (_tokens_of(d["choices"][0]) if ok else [])

    ok, first = run_chat()
    _check(ok, f"chat completion: 200, {max_tokens} completion tokens", failures)

    # streamed completion read to [DONE]
    req = _request(port, "/v1/completions", {
        "model": model, "prompt": "the quick brown fox", "stream": True,
        "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
        "stream_options": {"include_usage": True},
    })
    done, usage, status = False, None, None
    try:
        with urllib.request.urlopen(req, timeout=TRAFFIC_LIMIT_S) as r:
            status = r.status
            for raw in r:
                line = raw.decode().strip()
                if line == "data: [DONE]":
                    done = True
                elif line.startswith("data: "):
                    usage = json.loads(line[6:]).get("usage") or usage
    except urllib.error.HTTPError as e:
        status = e.code
    _check(status == 200 and done and usage is not None
           and usage["completion_tokens"] == max_tokens,
           f"streamed completion: 200, [DONE], {max_tokens} completion tokens",
           failures)

    # eight concurrent completions, token-id prompts spread over several
    # prefill buckets and (at the top) more than one chunk per step
    rng = random.Random(21)
    lengths = [max(8, int(n * scale))
               for n in (300, 450, 600, 750, 900, 1100, 1300, 1500)]
    prompts = [[rng.randrange(6, 256) for _ in range(n)] for n in lengths]
    results = [None] * len(lengths)

    def one(i: int) -> None:
        body = {
            "model": model, "prompt": prompts[i], "max_tokens": max_tokens,
            "temperature": 0, "ignore_eos": True,
        }
        if i == 3:
            body["logprobs"] = 1
        results[i] = _http(port, "/v1/completions", body)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(lengths))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(TRAFFIC_LIMIT_S)
    good, bad = 0, ""
    for i, res in enumerate(results):
        if res is None or res[0] != 200:
            bad = bad or f"; first failure: {res and (res[0], res[1][:300])}"
            continue
        d = json.loads(res[1])
        if (d["usage"]["completion_tokens"] == max_tokens
                and d["usage"]["prompt_tokens"] == lengths[i]):
            good += 1
        if i == 3:
            lps = [lp for _, lp in _tokens_of(d["choices"][0])]
            _check(len(lps) == max_tokens and all(
                lp is not None and math.isfinite(lp) and lp <= 0 for lp in lps),
                f"logprobs: {max_tokens} values, finite and <= 0", failures)
    _check(good == len(lengths),
           f"8 concurrent completions (prompts {lengths[0]}..{lengths[-1]} "
           f"tokens): {good}/8 returned 200 with {max_tokens} tokens{bad}",
           failures)

    ok, again = run_chat()
    _check(ok and len(first) == max_tokens and again == first,
           "the first request again: identical token stream and logprobs",
           failures)


def _metric_rows(text: str, name: str) -> list:
    return [ln for ln in text.splitlines() if ln.startswith(name + "{")]


def _check_metrics(port: int, failures: list) -> None:
    status, body = _http(port, "/metrics")
    text = body.decode() if status == 200 else ""
    routes = _metric_rows(text, "dynamo_engine_attention_route_total")
    _check(any('program="decode"' in r and 'route="decode"' in r
               for r in routes), 'route="decode" on the decode program',
           failures)
    _check(any('program="prefill"' in r and 'route="flash"' in r
               for r in routes), 'route="flash" on the prefill program',
           failures)
    _check(bool(routes) and not any('route="xla"' in r for r in routes),
           'no route="xla" row', failures)
    compiles = _metric_rows(text, "dynamo_engine_xla_compiles_total")
    late = [r for r in compiles if 'phase="startup"' not in r]
    _check(bool(compiles) and not late,
           f"every xla compile is phase=\"startup\" ({len(compiles)} rows"
           f"{', late: ' + '; '.join(late) if late else ''})", failures)


def run_leg(name: str, config: dict, extra_args: list, env: dict,
            want_devices: int, dry: bool) -> dict:
    """Start one server, drive the traffic, check, stop. Returns the
    leg's record; ``failures`` lists every check that did not hold."""
    print(f"\n== {name} ==", flush=True)
    failures: list = []
    model_dir = _make_model_dir(name, config)
    before = _cache_entries()
    # leg B compiles SPMD programs over twice the layers; it only runs
    # where the 1200 s of the one-chip contract do not apply
    server = Server(name, ["--model-path", model_dir, "--model-name", name,
                           "--allow-random-weights", *extra_args], env,
                    STARTUP_LIMIT_S * (2 if want_devices > 1 else 1))
    rec = {"leg": name, "failures": failures}
    try:
        startup_s = server.wait_ready()
        dev = rec["device"] = server.device()
        after = _cache_entries()
        print(f"  platform={dev['platform']} device_kind={dev['device_kind']!r} "
              f"devices={dev['device_count']} mesh={dev['mesh'] or '{}'}\n"
              f"  start-up {startup_s:.1f} s (set-up, not a metric); "
              f"compile-cache entries {before} -> {after} in {_cache_dir()}",
              flush=True)
        rec.update(startup_s=round(startup_s, 1), cache_before=before,
                   cache_after=after)
        _check(dev["platform"] == ("cpu" if dry else "tpu")
               and bool(dev["device_kind"]),
               f"start-up line: platform {dev['platform']}, kind "
               f"{dev['device_kind']!r}, {dev['device_count']} device(s)",
               failures)
        _check(dev["device_count"] >= want_devices
               and math.prod(dev["mesh"].values() or [1]) == want_devices,
               f"mesh spans {want_devices} device(s)", failures)
        _check(os.path.realpath(dev["compile_cache_dir"])
               == os.path.realpath(_cache_dir()),
               "the child's compile cache is where this script counts", failures)
        used = dev["bytes_in_use"]
        if want_devices > 1 and not dry:
            _check(None not in used and max(used) <= 1.25 * min(used),
                   f"per-device bytes_in_use within 25%: {used}", failures)
        elif None not in used:
            print(f"  bytes_in_use after warmup: {used}", flush=True)
        _traffic(server.port, name, 0.125 if dry else 1.0, failures)
        _check_metrics(server.port, failures)
        _check(server.proc.poll() is None, "server still alive", failures)
        rc = server.stop()
        _check(rc == 0, f"clean exit on SIGTERM (rc={rc})", failures)
    except Failed as e:
        print(f"  [FAIL] {e}", flush=True)
        failures.append(str(e).splitlines()[0])
    finally:
        server.kill()
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="debug this harness on the CPU at tiny widths; "
                         "never a pass for the chip")
    ap.add_argument("--legs", choices=["auto", "a", "b", "ab"], default="auto",
                    help="auto: leg A, then leg B when A's child saw >= 4 "
                         "devices; with b or ab leg B is required (fewer "
                         "than four devices fails)")
    args = ap.parse_args()
    dry = args.cpu_dry_run
    os.makedirs(OUT_DIR, exist_ok=True)
    env = dict(os.environ)
    serve = ["--max-model-len", "2048", "--max-batch-size", "8",
             "--num-kv-blocks", "2048"]
    if dry:
        print("DRY RUN — CPU, tiny widths, interpret-mode kernels; this "
              "says nothing about the chip", flush=True)
        extra = os.path.join(OUT_DIR, "dry_run_engine_args.json")
        with open(extra, "w") as f:
            # auto resolves to xla on the CPU; the dry run wants the
            # kernel routes (interpreted) so the route checks mean something
            json.dump({"attention_impl": "pallas", "dtype": "float32"}, f)
        env.update(JAX_PLATFORMS="cpu", DYN_PALLAS_INTERPRET="1",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        serve = ["--max-model-len", "256", "--max-batch-size", "8",
                 "--num-kv-blocks", "256", "--extra-engine-args", extra]
        leg_a, leg_b = ("dry-a", TINY), ("dry-b", TINY)
    else:
        # the first platform named is jax's default backend
        if env.get("JAX_PLATFORMS", "").lower().split(",")[0].strip() == "cpu":
            print("chip_smoke: JAX_PLATFORMS asks for the cpu — no chip "
                  "here. --cpu-dry-run debugs the harness; it is not a pass.",
                  file=sys.stderr)
            return 2
        if "DYN_PALLAS_INTERPRET" in env:
            print("chip_smoke: DYN_PALLAS_INTERPRET is set; the chip run "
                  "must compile its kernels", file=sys.stderr)
            return 2
        leg_a = ("llama-3.2-1b", LLAMA_3_2_1B)
        leg_b = ("llama-3.1-8b-tp4", LLAMA_3_1_8B)

    legs = []
    if args.legs != "b":
        legs.append(run_leg(*leg_a, serve, env, 1, dry))
    n_dev = legs[0].get("device", {}).get("device_count", 0) if legs else 0
    if "b" in args.legs or dry or n_dev >= 4:
        legs.append(run_leg(
            *leg_b, serve + ["--tensor-parallel-size", "4"], env, 4, dry))
    else:
        print(f"\n== leg B not run: {n_dev} device(s) visible, it needs "
              "four (--legs ab makes that a failure) ==", flush=True)

    with open(os.path.join(OUT_DIR, "result.json"), "w") as f:
        json.dump({"dry_run": dry, "legs": legs}, f, indent=1)
    failed = [f"{leg['leg']}: {w}" for leg in legs for w in leg["failures"]]
    print(flush=True)
    if failed:
        print("FAILED:\n  " + "\n  ".join(failed), flush=True)
        return 1
    if dry:
        print(json.dumps({"dry_run": True, "harness_checks_passed": True}))
        return 0
    dev = legs[0]["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
