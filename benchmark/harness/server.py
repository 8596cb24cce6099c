"""Start the program's own server in this process, which then holds the
chip(s) for the whole run.

The engine and the HTTP service are built by the functions
``dynamo_tpu.cli.run`` uses for ``in=http out=jax`` (its parser,
``build_engine``, ``run_http``), on the default route and the default
scheduler settings. A configuration sets deployment settings only; a
performance switch in a configuration's ``serve`` group is refused, so
that a PR which changes a default shows in every cell.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
from typing import Any, Dict, Tuple

from .manifest import ARCHITECTURE_MODULES
from .modeldir import write_model_dir

# what a configuration's "serve" group may set, and where each goes
CLI_SETTINGS = {
    "max_model_len": "--max-model-len",
    "max_batch_size": "--max-batch-size",
    "num_kv_blocks": "--num-kv-blocks",
    "tensor_parallel_size": "--tensor-parallel-size",
    "expert_parallel_size": "--expert-parallel-size",
}
ENGINE_SETTINGS = ("prefill_buckets", "max_prefill_tokens_per_step",
                   "max_prefill_batch")
# never from a configuration (ISSUE 22 §1): they stay at the program's defaults
PERFORMANCE_SWITCHES = (
    "multi_step_decode", "decode_pipeline_depth", "attention_impl",
    "device_finish", "kv_cache_dtype", "quantization", "fused_epilogue",
    "dtype",
)
# keys of a configuration's file that are not the model's published config
NOT_MODEL_KEYS = ("serve", "assumed", "reduced", "source", "stands_for",
                  "notes", "rehearsal", *ARCHITECTURE_MODULES)


def hf_config_of(config: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in config.items() if k not in NOT_MODEL_KEYS}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_flags(config: Dict[str, Any], name: str, work_dir: str, seed: int,
                port: int, rehearsal: bool):
    """The configuration -> the CLI's own flag namespace."""
    from dynamo_tpu.cli.run import build_parser

    serve = dict(config["serve"])
    hf = hf_config_of(config)
    extra: Dict[str, Any] = {"seed": int(seed)}
    if rehearsal:
        # the CPU rehearsal overlays tiny widths and interpret-mode
        # kernels; it prints DRY RUN and never a result (run.py). A
        # family whose kernels take no notice of DYN_PALLAS_INTERPRET
        # (the latent cache's decode kernel) rehearses on the route its
        # group names: "attention_impl": "auto", the CPU's XLA route
        over = config.get("rehearsal", {})
        hf.update(over.get("model", {}))
        serve.update(over.get("serve", {}))
        extra.update({"attention_impl": over.get("attention_impl", "pallas"),
                      "dtype": "float32"})
    bad = [k for k in serve if k in PERFORMANCE_SWITCHES]
    unknown = [k for k in serve
               if k not in CLI_SETTINGS and k not in ENGINE_SETTINGS]
    if bad or unknown:
        raise ValueError(
            f"configuration {name!r}: 'serve' may hold deployment settings "
            f"only; performance switches {bad}, unknown keys {unknown}")
    model_dir = write_model_dir(os.path.join(work_dir, "model"), hf)
    extra.update({k: serve[k] for k in ENGINE_SETTINGS if k in serve})
    extra_path = os.path.join(work_dir, "engine_args.json")
    with open(extra_path, "w") as f:
        json.dump(extra, f)
    argv = ["--model-path", model_dir, "--model-name", name,
            "--allow-random-weights", "--http-host", "127.0.0.1",
            "--http-port", str(port), "--extra-engine-args", extra_path]
    for key, flag in CLI_SETTINGS.items():
        if key in serve:
            argv += [flag, str(serve[key])]
    return build_parser().parse_args(argv), hf


def tpu_devices(chips: int):
    """Place the compile cache (before the first touch of the backend),
    then the TPU devices jax sees, or None where it finds another
    platform or fewer than ``chips``."""
    from dynamo_tpu.engine.device import configure_compile_cache

    configure_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices


async def start(flags) -> Tuple[Any, asyncio.Task]:
    """Build the engine (weights from the seed, warm-up of every program)
    and serve it. Returns the pipeline engine and the serving task."""
    from dynamo_tpu.cli.run import build_engine, run_http

    engine, mdc = await build_engine("jax", flags)
    task = asyncio.ensure_future(run_http(flags, engine, mdc))
    loop = asyncio.get_running_loop()
    deadline = loop.time() + 60.0
    while True:
        if task.done():
            task.result()   # raises what stopped it
            raise RuntimeError("the HTTP service ended before it listened")
        try:
            _, w = await asyncio.open_connection("127.0.0.1", flags.http_port)
            w.close()
            return engine, task
        except OSError:
            if loop.time() > deadline:
                raise RuntimeError("the HTTP service did not listen in 60 s")
            await asyncio.sleep(0.05)


async def stop(task: asyncio.Task) -> None:
    """What SIGTERM does in run_http, without the signal: cancel the
    serving task and let its ``finally`` close the service."""
    task.cancel()
    try:
        await task
    except asyncio.CancelledError:
        pass
