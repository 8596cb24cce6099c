#!/usr/bin/env python3
"""How many pages a chunk the decode kernels' walk wants, on the chip:
``paged_decode_attention``, ``mla_paged_decode_attention`` and (shape
``sdar``: a block pass's eight positions a row under the block mask)
``paged_verify_attention`` alone, every row live, over pages a chunk x
context x the page sizes the benchmark's cells serve.

    python scripts/chunk_sweep.py [--repo DIR] [--shapes phi3,trinity,...]
        [--contexts 256,2048,16384] [--pages 8,16,32,64,128]
        [--out chiprun_out/chunk_sweep.json]

Each line is one (shape, context, pages a wide chunk): the median wall
time of a call, the time a page of one row takes, and the bytes the
rows' live pages hold over that time as a share of 819 GB/s. The wide
chunk is forced by setting ``pallas_decode``'s three limits
(``CHUNK_BYTES``, ``SCORE_BYTES``, ``MAX_CHUNK_PAGES``) before each
trace, so what is timed is the kernel as served with another byte
target; ``rule`` marks the line the limits as committed derive. This
table is what fixed them (PERF.md §5 "Since PR 42"). ``--repo`` a
checkout of a parent commit, whose kernels have no such limits, is
timed with ``pages_per_chunk`` instead (every chunk that size), as is
a verify kernel that does not take its chunks from the rule. It
measures the chip and nothing else: on any other backend it says so
and exits 1.
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
ap.add_argument("--shapes", default="phi3,trinity,tp4,moonlight")
ap.add_argument("--contexts", default="256,2048,16384")
ap.add_argument("--pages", default="8,16,32,64,128")
ap.add_argument("--out", default=None)
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.repo))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.ops import pallas_decode  # noqa: E402

HAS_RULE = hasattr(pallas_decode, "chunk_pages")
# the verify kernel walks by the rule where nothing pins its chunks
VERIFY_RULE = inspect.signature(pallas_decode.paged_verify_attention) \
    .parameters["pages_per_chunk"].default is None
VERIFY_S, VERIFY_BLOCK = 8, 4    # sdar-reasoning: two blocks of four a row
PAGE, ROWS, CALLS, WIDTH = 16, 16, 8, 1152
HBM_BYTES_PER_S = 819e9          # TPU v5e (benchmark/harness/peaks.py)
# name: (kind, heads, a, c): q heads over ``a`` kv heads of ``c`` lanes
# ("pair": the cache holds a kv head a page, [L, N, page, lanes]), or, for
# the latent cache, heads over a latent of ``a`` and a rope key of ``c``;
# a page of K (or of the latent) in bf16 beside it
SHAPES = {
    "phi3": ("gqa", 32, 32, 128),        # 131 KB: phi3-mini-4k
    "trinity": ("gqa", 32, 4, 128),      # 16 KB: trinity-mini-26b-a3b
    "tp4": ("gqa", 8, 2, 128),           # 8 KB: a shard of mistral-7b tp=4
    "falcon": ("gqa", 20, 4, 128),       # 16 KB: falcon-h1-34b
    "sala": ("pair", 16, 1, 128),        # 4 KB: minicpm-sala's (row, kv head)
    "moonlight": ("mla", 16, 512, 128),  # 20 KB: moonlight-16b-a3b, xing4
    "sdar": ("verify", 32, 4, 128),      # 16 KB: sdar-30b-a3b, S = 8
}


def by_rule(name):
    """Whether this checkout's kernel at ``name`` sizes its chunks itself."""
    return HAS_RULE and (SHAPES[name][0] != "verify" or VERIFY_RULE)


def page_of(name):
    """(bytes a page moves, bytes of float32 scores it adds, pages a tail
    chunk): what the wrappers hand ``chunk_pages`` at this shape."""
    kind, h, a, c = SHAPES[name]
    if kind == "mla":
        return PAGE * (a + c) * 2, h * PAGE * 4, 16
    if kind == "verify":
        # a kv head a product: its (s, g) rows against its tokens
        return 2 * PAGE * a * c * 2, VERIFY_S * (h // a) * PAGE * 4, 16
    return 2 * PAGE * a * c * 2, h * PAGE * a * 4, 8


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.bfloat16)


def _time(step, operands):
    jax.block_until_ready(step(*operands))
    t0 = time.perf_counter()
    jax.block_until_ready(step(*operands))
    once = time.perf_counter() - t0
    reps = max(3, min(200, int(0.05 / max(once, 1e-6))))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = step(*operands)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    return statistics.median(times) / CALLS


def case(name, context, pages):
    """(seconds a call, bytes the live pages hold, pages a row, pages a
    wide chunk as traced) at one point."""
    kind, h, a, c = SHAPES[name]
    rng = np.random.default_rng(7)
    ctx = (context - rng.integers(0, 3 * PAGE, ROWS)).astype(np.int32)
    ctx = np.maximum(ctx, 1)
    layers = 2
    n_blocks = max(1024, 2 * -(-context // PAGE))
    bt = jnp.asarray(rng.integers(1, n_blocks, (ROWS, WIDTH)), jnp.int32)
    lis = jnp.arange(CALLS, dtype=jnp.int32) % layers
    kw = {}
    if by_rule(name):
        # a wide chunk of exactly ``pages``, whatever the page's bytes
        pallas_decode.CHUNK_BYTES = pallas_decode.SCORE_BYTES = 1 << 40
        pallas_decode.MAX_CHUNK_PAGES = pages
        jax.clear_caches()
    else:
        kw["pages_per_chunk"] = pages
    page_bytes, _, tail = page_of(name)
    if kind == "verify":
        kvh, d = a, c
        shape = (layers, n_blocks, PAGE, kvh, d)
        ops = (_normal(2, (ROWS, VERIFY_S, h, d)), _normal(0, shape),
               _normal(1, shape), bt, jnp.asarray(ctx))

        @jax.jit
        def step(q, k, v, bt, ctx):
            def call(q, li):
                return pallas_decode.paged_verify_attention(
                    q, k, v, bt, ctx - VERIFY_S, ctx, layer_idx=li,
                    block_len=VERIFY_BLOCK, **kw), None
            return jax.lax.scan(call, q, lis)[0]
    elif kind != "mla":
        kvh, d = a, c
        shape = (layers, n_blocks, PAGE, kvh, d)
        if kind == "pair":
            shape, kw["one_head"] = (layers, n_blocks, PAGE, d), True
        ops = (_normal(2, (ROWS, 1, h, d)), _normal(0, shape),
               _normal(1, shape), bt, jnp.asarray(ctx))

        @jax.jit
        def step(q, k, v, bt, ctx):
            def call(q, li):
                return pallas_decode.paged_decode_attention(
                    q, k, v, bt, ctx, layer_idx=li, **kw), None
            return jax.lax.scan(call, q, lis)[0]
    else:
        r, rd = a, c
        ops = (_normal(2, (ROWS, 1, h, r)), _normal(3, (ROWS, 1, h, rd)),
               _normal(0, (layers, n_blocks, 1, PAGE, r)),
               _normal(1, (layers, n_blocks, 1, PAGE, rd)),
               bt, jnp.asarray(ctx))

        @jax.jit
        def step(ql, qr, c, kr, bt, ctx):
            def call(ql, li):
                return pallas_decode.mla_paged_decode_attention(
                    ql, qr, c, kr, bt, ctx, layer_idx=li, scale=192 ** -0.5,
                    **kw), None
            return jax.lax.scan(call, ql, lis)[0]

    live_pages = -(-ctx // PAGE)
    seconds = _time(step, ops)
    # a wide chunk is never under the tail's
    traced = pages if "pages_per_chunk" in kw else max(pages, tail)
    return seconds, int(live_pages.sum()) * page_bytes, live_pages, traced


def rule_pages(name):
    """Pages a wide chunk the limits as they stand derive at ``name``."""
    return pallas_decode.chunk_pages(*page_of(name), WIDTH)


def main():
    if jax.default_backend() != "tpu":
        sys.exit(f"chunk_sweep.py times the kernels on a TPU; the backend "
                 f"here is {jax.default_backend()!r}: nothing measured")
    table = {"repo": os.path.abspath(args.repo),
             "device": jax.devices()[0].device_kind, "rows": ROWS, "lines": []}
    shapes = args.shapes.split(",")
    # before a case moves the limits
    rules = {name: rule_pages(name) if by_rule(name) else None
             for name in shapes}
    for name in shapes:
        for context in map(int, args.contexts.split(",")):
            for pages in map(int, args.pages.split(",")):
                if 2 * pages * page_of(name)[0] > 12 << 20:
                    continue        # more VMEM than a kernel may hold
                try:
                    seconds, nbytes, live, traced = case(name, context, pages)
                except Exception as e:  # a chunk Mosaic refuses: say so
                    print(f"{name:10s} ctx {context:6d} pages {pages:4d} "
                          f"refused: {str(e)[:200]!r}", flush=True)
                    continue
                line = {"shape": name, "context": context, "pages": traced,
                        "rule": traced == rules[name],
                        "call_us": 1e6 * seconds,
                        "page_us": 1e6 * seconds / int(live.sum()),
                        "hbm_share_pct": 100 * nbytes / seconds
                        / HBM_BYTES_PER_S}
                table["lines"].append(line)
                print(f"{name:10s} ctx {context:6d} pages {traced:4d} call "
                      f"{line['call_us']:9.1f} us  page {line['page_us']:7.4f}"
                      f" us  {line['hbm_share_pct']:5.1f} % of 819 GB/s"
                      f"{'  rule' if line['rule'] else ''}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))


if __name__ == "__main__":
    main()
