#!/usr/bin/env python3
"""What a pad row of the decode batch costs the decode kernels, on the
chip: a step's worth of calls (one a layer) of ``paged_decode_attention``
and ``mla_paged_decode_attention`` alone, at the shapes the benchmark's
cells serve, with a few rows live and the rest as the scheduler fills
them (``ctx_lens = 1``, no slot).

    python scripts/pad_row_cost.py [--repo DIR] [--out chiprun_out/pad_row_cost.json]

``--repo`` is the checkout whose ``dynamo_tpu`` is timed (a parent commit
unpacked beside this one); a checkout whose kernels take no ``live_rows``
walks every row, which is the comparison. Each case prints the median
wall time of a step's calls over ``--reps`` dispatches and per call; the
last line is the whole table as JSON. It measures the chip and nothing
else: on any other backend it says so and exits 1 (the interpreter's
answers are tests/test_pallas_decode.py's business).
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import statistics
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--out", default=None)
ap.add_argument("--reps", type=int, default=60)
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.repo))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.ops import pallas_decode  # noqa: E402

PAGE = 16
# name: (layers, rows, q heads, kv heads, lanes, table width, live rows)
GQA_CASES = {
    # benchmark/configs/phi3-mini-4k.json: 32 x 32 heads of 96 in 128 lanes
    "phi3-chat": (32, 32, 32, 32, 128, 32, 5),
    "phi3-full": (32, 32, 32, 32, 128, 32, 32),
    "phi3-idle": (32, 32, 32, 32, 128, 32, 0),
    # mistral-7b-v0.3-tp4.json, one shard of four: 8 heads over 2 kv heads
    "mistral-tp4-shard": (32, 64, 8, 2, 128, 32, 9),
    "mistral-tp4-shard-full": (32, 64, 8, 2, 128, 32, 64),
    # falcon-h1-34b.json: six layers, 20 heads over 4 kv heads
    "falcon-h1-chat": (6, 64, 20, 4, 128, 64, 26),
}
# moonlight-16b-a3b.json: nine layers, 16 heads, latent 512, rope key 128
MLA_CASES = {
    "moonlight-chat": (9, 64, 16, 512, 128, 256, 17),
    "moonlight-full": (9, 64, 16, 512, 128, 256, 64),
}
CONTEXT = (250, 350)     # a live row's context: the chat mix's ~300


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.bfloat16)


def _rows(rng, b, width, n_live, n_blocks):
    """(block tables, context lens, live mask): live rows spread over the
    batch as slots are, contexts of ~300; a pad row as Scheduler._decode
    fills it (context 1, table of zeros)."""
    live = np.zeros(b, bool)
    live[rng.permutation(b)[:n_live]] = True
    ctx = np.where(live, rng.integers(*CONTEXT, size=b), 1).astype(np.int32)
    bt = np.where(live[:, None], rng.integers(1, n_blocks, (b, width)), 0)
    return (jnp.asarray(bt, jnp.int32), jnp.asarray(ctx), jnp.asarray(live))


def _time(step, operands, reps):
    out = step(*operands)
    jax.block_until_ready(out)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = step(*operands)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    return statistics.median(times)


def _takes_live_rows(fn):
    return "live_rows" in inspect.signature(fn).parameters


def gqa_case(name, rng, reps):
    layers, b, h, kvh, d, width, n_live = GQA_CASES[name]
    n_blocks = 640
    shape = (layers, n_blocks, PAGE, kvh, d)
    k, v, q = _normal(0, shape), _normal(1, shape), _normal(2, (b, 1, h, d))
    bt, ctx, live = _rows(rng, b, width, n_live, n_blocks)
    fn = pallas_decode.paged_decode_attention

    @jax.jit
    def step(q, k, v, bt, ctx):
        kw = {}
        if _takes_live_rows(fn):    # once a step, as a trunk makes it
            from dynamo_tpu.ops.live_rows import live_row_list
            kw["live_rows"] = live_row_list(live)

        def layer(q, li):
            return fn(q, k, v, bt, ctx, layer_idx=li, **kw), None

        return jax.lax.scan(layer, q, jnp.arange(layers, dtype=jnp.int32))[0]

    return layers, b, n_live, _time(step, (q, k, v, bt, ctx), reps)


def mla_case(name, rng, reps):
    layers, b, h, r, rd, width, n_live = MLA_CASES[name]
    n_blocks = 3072
    c = _normal(0, (layers, n_blocks, 1, PAGE, r))
    kr = _normal(1, (layers, n_blocks, 1, PAGE, rd))
    ql, qr = _normal(2, (b, 1, h, r)), _normal(3, (b, 1, h, rd))
    bt, ctx, live = _rows(rng, b, width, n_live, n_blocks)
    fn = pallas_decode.mla_paged_decode_attention

    @jax.jit
    def step(ql, qr, c, kr, bt, ctx):
        kw = {}
        if _takes_live_rows(fn):
            from dynamo_tpu.ops.live_rows import live_row_list
            kw["live_rows"] = live_row_list(live)

        def layer(ql, li):
            return fn(ql, qr, c, kr, bt, ctx, layer_idx=li, scale=192 ** -0.5,
                      **kw), None

        return jax.lax.scan(layer, ql, jnp.arange(layers, dtype=jnp.int32))[0]

    return layers, b, n_live, _time(step, (ql, qr, c, kr, bt, ctx), reps)


def main():
    if jax.default_backend() != "tpu":
        sys.exit(f"pad_row_cost.py times the kernels on a TPU; the backend "
                 f"here is {jax.default_backend()!r}: nothing measured")
    device = jax.devices()[0]
    table = {"repo": os.path.abspath(args.repo), "device": device.device_kind,
             "live_rows": _takes_live_rows(pallas_decode.paged_decode_attention),
             "cases": {}}
    for cases, run in ((GQA_CASES, gqa_case), (MLA_CASES, mla_case)):
        for name in cases:
            layers, b, n_live, seconds = run(name, np.random.default_rng(7),
                                             args.reps)
            row = {"layers": layers, "rows": b, "live": n_live,
                   "step_ms": 1e3 * seconds,
                   "call_us": 1e6 * seconds / layers}
            table["cases"][name] = row
            print(f"{name:24s} rows {b:3d} live {n_live:3d} "
                  f"step {row['step_ms']:.3f} ms  call {row['call_us']:.1f} us",
                  flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))


if __name__ == "__main__":
    main()
