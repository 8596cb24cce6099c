#!/usr/bin/env python3
"""Correctness probes longer than the benchmark's own, on the chip.

    python3 scripts/long_probes.py --workload <cell> --lengths 9400,16000 --seed <n>

``benchmark/harness/drive.py`` sends three short probes and one of 2200
tokens. A configuration whose mechanism starts past that (MiniCPM-SALA's
block selection, past ``dense_len`` 8192) is held to its reference there
by this script: the cell's configuration served as ``benchmark/run.py``
serves it (the same flags, weights from ``--seed``), prompts of the
given lengths sent one at a time through ``/v1/completions`` (greedy, 16
tokens, with log-probabilities), and ``harness/reference.check_probes``
with the limits of the configuration's reference module. One JSON line;
exit 0 when every probe is inside the limits.

``--engine-args '{"kv_cache_dtype": "fp8"}'`` lays engine settings over
the configuration's (the reading of a precision below the stated one,
which has to come out as not correct: PERF.md section 6). ``--fault``
serves a deliberately wrong program against the unchanged reference,
for the readings that say what the limits can tell apart: ``bf16_state``
(the served family's records by slot held in bfloat16: MiniCPM-SALA's
lightning state, Falcon-H1's SSM state), ``half_topk`` (half the picked
blocks dropped: ``sparse_config.topk`` halved in the served model's
``config.json`` only), ``bf16_router`` (the routed experts' router
scores from a bfloat16 product of bfloat16 operands, as the activations
arrive, where the stated program takes both to float32: Trinity-Mini,
``trinity-longdoc`` at 9400 and 16 000 tokens, PR 40), ``steps_4`` and
``block_8`` (SDAR, ``sdar-reasoning``, PR 45: four denoising steps a
block, or blocks of eight, in the served model's ``config.json`` only,
against the reference's two and four), ``gate_over_all`` and
``sqrt_scale`` (Granite 4.0-H, ``granite-batch``, PR 48: the gates a
softmax over every published expert and not over the chosen ones, and
the attention layers' scores scaled by ``head_dim ** -0.5`` in the
served model's ``config.json`` only, against the published 1 /
head_dim), ``scalar_decay``, ``rope_on_mla`` and ``beta_one`` (Kimi
Linear, ``kimi-linear-reasoning``, PR 52: a KDA layer's decay taken as
one scalar a head, the channel mean of ``g``; a rotary term on the latent
layers' 64-wide parts; every token written at full strength; and
``bf16_state`` holds its KDA state in bfloat16 as the other families'
records), and for dots3-note (``dots3-longdoc``, PR 54) ``half_topk``
again (``index_topk`` halved in the served model's ``config.json``
only: 1024 picked keys against the reference's 2048), ``window_short``
and ``window_long`` (``sliding_window_size`` 512 or 514 there, against
the reference's 513). Where the reference module has ``limits_for``, a
probe is held to the pair it gives for the probe's context; otherwise to
the module's one pair.

``--controls state,router,pages`` reads, on the same probes, what the
reference gives when part of it is computed in the precision below the
stated one, or wrongly (``build(..., lower=(name,))``, where the
reference module has ``CONTROLS``: Granite 4.0-H's state in bfloat16 from
token to token, its router's logits a bfloat16 product, its attention
layers' keys and values in fp8; Kimi Linear's ``state``,
``scalar_decay``, ``rope_on_mla`` and ``beta_one``, the four faults above
made in the reference; dots3-note's ``half_topk``, ``no_relu`` (the
indexer's scores without their ReLU), ``window_short``, ``window_long``
and ``no_gate`` (the gate a head on the attention output left out);
Nemotron-H's ``state``, ``router`` and ``pages`` as Granite's, PR 62):
the control's log-probabilities
stand in the served program's place in ``check_probes``, each probe on
its own and all together as the harness compares them. Nothing is
decoded for it, so a control costs a reference pass a probe. The exit
code says nothing of the controls: which of them has to come out as not
correct, and which cannot, is the reference module's to say.
``--lengths harness`` sends the lengths ``benchmark/run.py`` sends at
that seed (``harness/drive.probe_lengths``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [BENCH, ROOT]

PROBE_TOKENS = 16
FAULTS = ("bf16_state", "half_topk", "bf16_router", "steps_4", "block_8",
          "gate_over_all", "sqrt_scale", "scalar_decay", "rope_on_mla",
          "beta_one", "window_short", "window_long")


def serve_wrongly(fault: str, model_dir: str) -> None:
    """Make the program about to be served wrong in one named way; the
    reference keeps the configuration as it is."""
    if fault in ("half_topk", "steps_4", "block_8", "sqrt_scale",
                 "window_short", "window_long"):
        path = os.path.join(model_dir, "config.json")
        with open(path) as f:
            config = json.load(f)
        if fault == "half_topk" and "index_topk" in config:
            config["index_topk"] //= 2
        elif fault == "half_topk":
            config["sparse_config"]["topk"] //= 2
        elif fault in ("window_short", "window_long"):
            config["sliding_window_size"] += 1 if fault == "window_long" else -1
        elif fault == "steps_4":
            config["denoising_steps"] = 4
        elif fault == "sqrt_scale":
            heads = config["num_attention_heads"]
            config["attention_multiplier"] = (
                config.get("head_dim") or config["hidden_size"] // heads) ** -0.5
        else:
            config["block_length"] = 8
        with open(path, "w") as f:
            json.dump(config, f)
    elif fault == "bf16_state":
        import dataclasses

        import jax.numpy as jnp

        from dynamo_tpu import models
        from dynamo_tpu.engine.config import ModelConfig

        family = models.resolve(ModelConfig.from_model_dir(model_dir))
        init = family.init_kv_cache

        def init_kv_cache(*args, **kwargs):
            k, v = init(*args, **kwargs)
            return dataclasses.replace(k, state=k.state.astype(jnp.bfloat16)), v

        family.init_kv_cache = init_kv_cache
    elif fault == "bf16_router":
        import jax.numpy as jnp

        from dynamo_tpu.models import mixtral

        route = mixtral.route_top_k

        def route_top_k(x, router_w, *args, **kwargs):
            # rounded operands and a rounded product; the float32 the
            # stated router then computes in changes neither
            logits = jnp.dot(x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16))
            eye = jnp.eye(router_w.shape[1], dtype=jnp.float32)
            return route(logits.astype(jnp.float32), eye, *args, **kwargs)

        mixtral.route_top_k = route_top_k
    elif fault in ("scalar_decay", "beta_one"):
        import jax.numpy as jnp

        from dynamo_tpu.models import kimi_linear

        def wrongly(fn):
            def wrong(q, k, v, g, beta, *rest):
                if fault == "scalar_decay":   # the channels' mean a head
                    g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
                else:                         # 0 stays 0: no token there
                    beta = jnp.where(beta > 0, 1.0, 0.0)
                return fn(q, k, v, g, beta, *rest)
            return wrong

        for name in ("kda_decode_step", "kda_chunked_scan"):
            setattr(kimi_linear, name, wrongly(getattr(kimi_linear, name)))
    elif fault == "rope_on_mla":
        from dynamo_tpu.models import deepseek, kimi_linear

        kimi_linear.make_mla_attn_fn = lambda *args, **kwargs: (
            deepseek.make_mla_attn_fn(*args, **{**kwargs, "rope": True}))
    elif fault == "gate_over_all":
        from dynamo_tpu.models import mixtral

        route = mixtral.route_top_k
        mixtral.route_top_k = lambda *args, **kwargs: route(
            *args, **{**kwargs, "norm_topk": False})


def read_control(reference, name: str, params, hf: dict, probes: list,
                 token_id) -> dict:
    """The reference with ``name`` computed in the precision below the
    stated one, in the served program's place: its log-probability of
    every returned token against the unchanged reference's, under the
    module's limits, each probe on its own and all of them together."""
    import functools

    from harness.reference import check_probes, reference_logprobs

    lowered = types.SimpleNamespace(
        build=functools.partial(reference.build, lower=(name,)))
    programs: dict = {}
    stood_in = [{**p, "token_logprobs": reference_logprobs(
        lowered, params, hf, p["prompt"],
        [token_id(t) for t in p["tokens"]], programs).tolist()}
        for p in probes if p.get("status") == 200]
    kept = ("ok", "max_abs_err", "mean_abs_err", "tokens_compared")
    out = {"together": None, "probes": []}
    for group in (stood_in, *([p] for p in stood_in)):
        ref = check_probes(reference, params, hf, group, token_id)
        row = {k: ref[k] for k in kept}
        if group is stood_in:
            out["together"] = row
        else:
            out["probes"].append({"prompt_tokens": len(group[0]["prompt"]), **row})
    return out


async def amain(args) -> int:
    import aiohttp

    from harness import manifest, server
    from harness.drive import probe_lengths
    from harness.loadgen import _probes as send_probes
    from harness.modeldir import token_id
    from harness.reference import check_probes
    from harness.traffic import probe_prompts

    cell = manifest.load_cell(args.workload)
    reference = manifest.architecture_module(cell.config, cell.config_name,
                                             "reference")
    work = os.path.join(ROOT, ".bench_work", cell.name + "-long-probes")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    port = server.free_port()
    flags, hf = server.build_flags(cell.config, cell.config_name, work,
                                   args.seed, port, rehearsal=False)
    if args.engine_args:
        with open(flags.extra_engine_args) as f:
            extra = json.load(f)
        extra.update(json.loads(args.engine_args))
        with open(flags.extra_engine_args, "w") as f:
            json.dump(extra, f)
    if args.fault:
        serve_wrongly(args.fault, flags.model_path)
    if server.tpu_devices(cell.chips) is None:
        print("long_probes: no TPU here", file=sys.stderr)
        return 3
    t0 = time.monotonic()
    engine, serving = await server.start(flags)
    runner = engine.core_engine.runner
    print(f"serving after {time.monotonic() - t0:.1f} s", flush=True)
    lengths = []
    for n in args.lengths.split(","):
        lengths += (probe_lengths(hf, args.seed, 1.0) if n == "harness"
                    else [int(n)])
    probes = []
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=600)) as session:
        for prompt in probe_prompts(lengths, int(hf["vocab_size"]), args.seed):
            t1 = time.monotonic()      # one at a time, as the harness sends them
            probes += await send_probes(session, {
                "base_url": f"http://127.0.0.1:{port}", "model": cell.config_name,
                "probes": [prompt], "probe_tokens": PROBE_TOKENS})
            print(f"probe of {len(prompt)} tokens: HTTP {probes[-1]['status']}, "
                  f"{time.monotonic() - t1:.2f} s", flush=True)
    out = {"workload": cell.name, "seed": args.seed, "lengths": lengths,
           "engine_args": json.loads(args.engine_args or "{}"),
           "fault": args.fault, "probes": []}
    ok = True
    loop = asyncio.get_running_loop()
    limits_for = getattr(reference, "limits_for", None)
    for probe in probes:      # one at a time: each has its own limits' verdict
        t1 = time.monotonic()
        atol, mean_atol = (
            limits_for(hf, len(probe["prompt"]) + PROBE_TOKENS) if limits_for
            else (reference.LOGPROB_ATOL, reference.LOGPROB_MEAN_ATOL))
        held_to = types.SimpleNamespace(
            build=reference.build, LOGPROB_ATOL=atol, LOGPROB_MEAN_ATOL=mean_atol)
        ref = await loop.run_in_executor(
            None, check_probes, held_to, runner.params, hf, [probe], token_id)
        ok = ok and ref["ok"]
        out["probes"].append({
            "prompt_tokens": len(probe["prompt"]), "ok": ref["ok"],
            "max_abs_err": ref["max_abs_err"], "mean_abs_err": ref["mean_abs_err"],
            "limits": {"max": atol, "mean": mean_atol},
            "tokens_compared": ref["tokens_compared"], "reasons": ref["reasons"],
            "reference_s": round(time.monotonic() - t1, 1)})
    for name in filter(None, args.controls.split(",")):
        out.setdefault("controls", {})[name] = await loop.run_in_executor(
            None, read_control, reference, name, runner.params, hf, probes,
            token_id)
    stats = [d.memory_stats() or {} for d in runner.mesh.devices.flat]
    out["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    await server.stop(serving)
    await engine.core_engine.close()
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lengths", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--engine-args", default="")
    ap.add_argument("--fault", choices=FAULTS, default=None)
    ap.add_argument("--controls", default="")
    return asyncio.run(amain(ap.parse_args()))


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
