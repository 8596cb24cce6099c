#!/usr/bin/env python3
"""What a compiled trunk does to its layers' weights, without a chip.

Compiles a configuration's decode (or prefill) trunk for a described
TPU topology, as tests/test_chip_compile.py does, and prints the body
of every layer loop: one operation a line with its result's shape,
layout and memory space. A weight that is *streamed* enters its product
fusion as the whole stacked ``[L, ...]`` parameter beside the layer
index; a weight that is *staged* shows first as a stand-alone
``dynamic-slice`` fusion (often into ``S(1)``) and perhaps a ``copy``
to another layout, each yielding an array of the weight's size
(docs/perf_tuning.md). The names it prints are the names the profiler's
capture gives (``breakdown.device_ops`` of a traced benchmark run).

    JAX_PLATFORMS=cpu python scripts/layer_loop.py \
        --config benchmark/configs/phi3-mini-4k.json [--tokens 2048]

``--tokens 1`` (the default) is a decode step of ``max_batch_size``
rows; more is a prefill step of one row. ``tensor_parallel_size`` in the
file's ``serve`` builds the real mesh over the described devices.
Nothing runs: no time, no result. It imports the model code and no cell
runs it. ``--hash`` prints only the sha256 of the lowered (not yet
compiled) text, its kernels without their source locations, to tell
whether a change reaches a configuration.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

BIG = 1 << 20      # elements: a projection weight has at least this many
_SHAPE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


def lower_trunk(config_path, devices, tokens=1, width=256):
    """The trunk of one step of ``config_path`` (a benchmark
    configuration: HF keys and a ``serve`` group), lowered for
    ``devices`` (a described topology's). The caller steers
    ``jax.default_backend()`` to "tpu" for the routes a chip takes."""
    from dynamo_tpu import models
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.model_runner import CACHE_SPEC, build_mesh

    with open(config_path) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]
    arch = models.resolve(cfg)
    tp = serve.get("tensor_parallel_size", 1)
    mesh = build_mesh(1, tp, devices=list(devices))

    def on(spec):
        return NamedSharding(mesh, spec)

    def placed(shapes, specs):
        # (a spec may stand for a tuple of stacks: models/dots3.py)
        return jax.tree.map(
            lambda sp, part: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=on(sp)), part),
            specs, shapes, is_leaf=lambda sp: isinstance(sp, P))

    shapes = jax.eval_shape(
        lambda: arch.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    params = placed(shapes, arch.param_specs(shapes))
    rows = serve["max_batch_size"] if tokens == 1 else 1
    # what the engine offers every family (ModelRunner._init_device_state):
    # its decode slots, the window pool's pages as it derives them and
    # the longest sequence it admits
    from dynamo_tpu.engine.config import EngineConfig

    pool = EngineConfig(model=cfg, **{
        k: v for k, v in serve.items()
        if k in {f.name for f in dataclasses.fields(EngineConfig)}
    }).window_pool_pages()
    if getattr(arch, "SEQUENCE_STATE", models.PAGES_ONLY).window_pool:
        width *= 2      # two kinds of page: a table a kind, side by side
    cache = jax.eval_shape(lambda: arch.init_kv_cache(
        cfg, serve["num_kv_blocks"], 16, jnp.bfloat16,
        num_slots=serve["max_batch_size"], window_blocks=pool,
        max_len=serve["max_model_len"]))
    spec = getattr(arch, "CACHE_SPEC", CACHE_SPEC)
    cache = tuple(placed(side, spec) for side in cache)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on(P()))

    def step(params, k_side, v_side, toks, positions, bt, slot_map, ctx):
        return arch.forward(params, cfg, toks, positions, (k_side, v_side), bt,
                            slot_map, ctx, mesh=mesh if tp > 1 else None,
                            return_hidden=True)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, *cache, i32(rows, tokens), i32(rows, tokens), i32(rows, width),
        i32(rows, tokens), i32(rows))


def without_locations(lowered_text):
    """``lowered_text`` with every Mosaic kernel's serialized body
    replaced by its MLIR printed without source locations. The bytecode
    carries the line of every frame that led to the kernel, so an edit
    above a caller in its file (``llama.run_layers`` is on most kernels'
    way) would otherwise read as a change to the program."""
    import base64

    from jax._src.lib.mlir import ir

    def plain(match):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, lowered_text)


def computations(text):
    """``{name: [operation lines]}`` for every computation of a
    compiled module's text."""
    found, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            lines = found[head.group(1)] = []
        elif line.startswith("}"):
            lines = None
        elif lines is not None and " = " in line:
            lines.append(line)
    return found


def loop_bodies(text):
    """``computations`` narrowed to the body of every ``while``."""
    bodies = set(re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", text))
    return {name: lines for name, lines in computations(text).items()
            if name in bodies}


def _result_type(rest):
    """The result type that ``rest`` (an operation's line after " = ")
    starts with: up to the first space, or for a tuple up to the
    parenthesis that closes it (a layout's tiling has its own)."""
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i + 1]
    return rest


def operations(lines):
    """``(name, result type with layout, opcode, elements, called
    computation)`` of each operation line; a tuple's elements are its
    largest member's."""
    out = []
    for line in lines:
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        result = _result_type(rest)
        opcode = re.match(r" ([a-z][\w\-]*)\(", rest[len(result):])
        if not opcode:
            continue
        sizes = [math.prod(int(n) for n in d.split(",") if n)
                 for d in _SHAPE.findall(result)]
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        out.append((name.lstrip("%"), result, opcode.group(1),
                    max(sizes, default=0), calls.group(1) if calls else None))
    return out


def staged_weights(text, least=BIG):
    """Names of the operations inside the layer loops' bodies that
    yield an array of a projection weight's size without computing
    anything: a ``copy``, or a fusion that is a ``dynamic-slice`` and
    nothing else. Empty where every weight is read where it lies."""
    every = computations(text)

    def only_slices(comp):
        kinds = {op for _, _, op, _, _ in operations(every.get(comp, []))}
        return "dynamic-slice" in kinds and kinds <= {
            "parameter", "constant", "dynamic-slice", "bitcast", "tuple",
            "get-tuple-element", "compare", "select", "add", "clamp"}

    hits = []
    for lines in loop_bodies(text).values():
        for name, _, opcode, elements, calls in operations(lines):
            if elements >= least and (
                    opcode in ("copy", "dynamic-slice")
                    or opcode == "fusion" and only_slices(calls)):
                hits.append(name)
    return hits


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--tokens", type=int, default=1,
                    help="1: a decode step; more: a prefill step of one row")
    ap.add_argument("--width", type=int, default=256, help="block-table width")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--hash", action="store_true",
                    help="print the lowered text's sha256 and stop")
    args = ap.parse_args()

    from jax.experimental import topologies

    jax.default_backend = lambda: "tpu"     # the kernels' routes, not the CPU's
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    lowered = lower_trunk(args.config, topo.devices, args.tokens, args.width)
    if args.hash:
        text = without_locations(lowered.as_text())
        print(hashlib.sha256(text.encode()).hexdigest(), args.config,
              f"tokens={args.tokens}")
        return
    compiled = lowered.compile()
    text = compiled.as_text()
    staged = staged_weights(text)
    for body, lines in loop_bodies(text).items():
        ops = operations(lines)
        print(f"== {body}: {len(ops)} operations")
        for name, result, opcode, _, _ in ops:
            mark = " <-- weight-sized" if name in staged else ""
            if len(result) > 120:       # the loop's own tuple of everything
                result = result[:117] + "..."
            print(f"  {name:44s} {opcode:22s} {result}{mark}")
    mem = compiled.memory_analysis()
    print(f"staged or copied weights: {staged or 'none'}")
    print(f"temp_size_in_bytes {mem.temp_size_in_bytes} "
          f"argument_size_in_bytes {mem.argument_size_in_bytes}")


if __name__ == "__main__":
    main()
