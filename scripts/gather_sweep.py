#!/usr/bin/env python3
"""What an XLA gather costs the chip, at ``dots3-longdoc``'s shapes: rows
of a full layer's page stack looked up by the indexer's pick, as
``ops/latent_select.picked_decode_attention`` looks them up in a decode
step (32 live rows, 2048 picked keys a row, sorted inside a row and
spread over the row's pages, which lie anywhere in the layer's pool).

    python scripts/gather_sweep.py [--forms rows512,rows128,...]
        [--out chiprun_out/gather_sweep.json]

Forms, each one full layer's work (a decode step runs three):

- ``rows<W>``: ``stack.reshape(-1, W)[ids]`` -> ``[32, 2048, W]``, one
  gather of 65 536 rows of ``W`` bfloat16 lanes (512: the latent's stack
  before PR 55; 128: the rope key's; 640: the two in one row, the full
  kind's page since PR 55; 768: a row padded to six lane groups).
- ``pair``: the 512- and the 128-lane gather in one program (what a full
  layer paid before PR 55).
- ``rows5x128``: the 640 lanes kept as ``[..., 5, 128]``.
- ``pages``: the indexer's key pages, ``[3 N, 16, 128][table]`` with a
  table of ``[32, 1152]`` (36 864 lookups of 4 KB): the other gather of
  a decode step, and the fit's point at 4 KB.
- ``attend_pair`` / ``attend_row`` / ``attend_row_sliced``: the whole of
  ``dsa_attend`` a layer: the gather(s), the scores of 128 heads in
  float32, the softmax and the value product. ``pair`` sums two score
  products over the two gathers (before PR 55); ``row`` is one product
  over the 640-lane rows and the value product over all 640 lanes (what
  ``picked_decode_attention`` does now: the caller drops the lanes past
  the rank); ``row_sliced`` multiplies the values by ``rows[..., :512]``.

Each line: microseconds a layer, nanoseconds an index, GB/s of rows
fetched. Last, the least-squares line through the single gathers: **ns
an index + ns a KB**, which is what the next change on this route (the
indexer's pages) starts from. It measures the chip and nothing else: on
any other backend it says so and exits 1.

Chip readings (TPU v5 lite, the host's clock around whole programs; my
chip run, PR 55, seed 55, contexts of 12.2 k on average), a layer:

    rows512             929.2 us   14.18 ns an index   1024 B    72.2 GB/s
    rows128             707.6 us   10.80 ns an index    256 B    23.7 GB/s
    pair               1637.5 us   12.49 ns an index  (2 x 65 536 lookups)
    rows640            1004.0 us   15.32 ns an index   1280 B    83.6 GB/s
    rows768            1109.9 us   16.94 ns an index   1536 B    90.7 GB/s
    rows5x128         10277.5 us  156.82 ns an index   1280 B     8.2 GB/s
    pages               855.6 us   23.21 ns an index   4096 B   176.5 GB/s
    attend_pair        1622.3 us
    attend_row         1120.1 us
    attend_row_sliced  1111.2 us

``rows640 / pair`` = 0.613 (ISSUE 55 changes the layout under 0.65). The
four row gathers lie on **9.5 ns an index + 4.8 ns a KB** within 0.25
ns; with the 4 KB pages (5.5 ns under that line: a larger piece is
cheaper a byte) the line is 11.0 + 3.2 within 1.2 ns. A row kept as
``[5, 128]`` is ten times slower (a gather of five sublane rows a
lookup): a row's lanes stay one minor dimension. The products hide
behind the gathers: ``attend_pair`` takes what ``pair`` takes, and the
value product over all 640 lanes costs what the one over a slice does.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

LAYERS, PAGES, PAGE = 3, 36864, 16      # the cell's full kind (num_kv_blocks)
ROWS, PICKED, TABLE = 32, 2048, 1152    # live rows, index_topk, table width
HEADS, RANK = 128, 512
CONTEXT = (9216, 16384)                 # longdoc-gen's prompts, log-uniform
BF16 = jnp.bfloat16
F32 = jnp.float32


def picked_ids(rng, layer=1):
    """(row ids [ROWS, PICKED] into the flat ``[L N page, W]`` view, the
    page table [ROWS, TABLE] into ``[L N, ...]``): each row's pages drawn
    from the pool without replacement, its picked tokens ``PICKED`` of its
    context's without replacement, in the order of the context (as
    ``latent_select.picked_list`` lists them)."""
    lens = np.exp(rng.uniform(*np.log(CONTEXT), ROWS)).astype(np.int64)
    owned = rng.permutation(PAGES)[:ROWS * TABLE].reshape(
        ROWS, TABLE)
    ids = np.empty((ROWS, PICKED), np.int64)
    for b in range(ROWS):
        tokens = np.sort(rng.choice(lens[b], PICKED, replace=False))
        ids[b] = (layer * PAGES + owned[b, tokens // PAGE]) * PAGE + tokens % PAGE
    live = np.arange(TABLE)[None] < -(-lens // PAGE)[:, None]
    table = np.where(live, owned, 0) + layer * PAGES
    return jnp.asarray(ids, jnp.int32), jnp.asarray(table, jnp.int32), lens


def stack(key, *minor):
    """A full kind's page stack as the flat view the gathers index."""
    return jax.random.normal(key, (LAYERS * PAGES * PAGE,) + minor, BF16)


def _softmax_values(s_log, values):
    probs = jax.nn.softmax(s_log, axis=-1).astype(BF16)
    return jnp.einsum("bhk,bkr->bhr", probs, values)


def attend_pair(c_all, kr_all, q_lat, q_rope, ids):
    c, kr = c_all[ids], kr_all[ids]
    s_log = (jnp.einsum("bhr,bkr->bhk", q_lat, c, preferred_element_type=F32)
             + jnp.einsum("bhd,bkd->bhk", q_rope, kr,
                          preferred_element_type=F32)) * 0.07
    return _softmax_values(s_log, c)


def attend_row(rows_all, q, ids, sliced=False):
    rows = rows_all[ids]
    s_log = jnp.einsum("bhd,bkd->bhk", q, rows,
                       preferred_element_type=F32) * 0.07
    return _softmax_values(s_log, rows[..., :RANK] if sliced else rows)


def _time(fn, *operands):
    """Median seconds a call."""
    jax.block_until_ready(fn(*operands))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*operands))
    once = time.perf_counter() - t0
    reps = max(3, min(200, int(0.3 / max(once, 1e-6))))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*operands)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    return statistics.median(times)


def cases(ids, table):
    """name -> (seconds a layer, lookups, bytes a lookup)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(55), 16))
    take = jax.jit(lambda x, i: x[i])
    n = ids.size

    def q(width):
        return jax.random.normal(next(keys), (ROWS, HEADS, width), BF16)

    def rows(*minor):
        x = stack(next(keys), *minor)
        seconds = _time(take, x, ids)
        return seconds, n, 2 * int(np.prod(minor))

    def pair(fn, *queries):
        c_all, kr_all = stack(next(keys), 512), stack(next(keys), 128)
        return _time(jax.jit(fn), c_all, kr_all, *queries, ids), 2 * n, 640

    def row(sliced):
        fn = jax.jit(lambda x, qq, i: attend_row(x, qq, i, sliced))
        return _time(fn, stack(next(keys), 640), q(640), ids), n, 1280

    def pages():
        x = stack(next(keys), 128).reshape(LAYERS * PAGES, PAGE, 128)
        return _time(take, x, table), table.size, 2 * PAGE * 128

    return {
        "rows512": lambda: rows(512), "rows128": lambda: rows(128),
        "rows640": lambda: rows(640), "rows768": lambda: rows(768),
        "rows5x128": lambda: rows(5, 128),
        "pair": lambda: pair(lambda c, kr, i: (c[i], kr[i])),
        "pages": pages,
        "attend_pair": lambda: pair(attend_pair, q(512), q(128)),
        "attend_row": lambda: row(False),
        "attend_row_sliced": lambda: row(True),
    }


def fit(lines, forms=r"rows\d+|pages"):
    """Least squares ``ns an index = a + b · KB`` through the single
    gathers whose name matches ``forms``."""
    pts = [(l["bytes_a_lookup"] / 1024, l["ns_an_index"]) for l in lines
           if re.fullmatch(forms, l["form"])]
    if len(pts) < 2:
        return None
    kb, ns = map(np.asarray, zip(*pts))
    b, a = np.polyfit(kb, ns, 1)
    return {"ns_an_index": float(a), "ns_a_kb": float(b),
            "largest_residual_ns": float(np.abs(a + b * kb - ns).max())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--forms", default="rows512,rows128,pair,rows640,"
                    "rows768,rows5x128,pages,attend_pair,attend_row,"
                    "attend_row_sliced")
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"gather_sweep.py times gathers on a TPU; the backend here "
                 f"is {jax.default_backend()!r}: nothing measured")
    ids, table, lens = picked_ids(np.random.default_rng(args.seed))
    found = {"device": jax.devices()[0].device_kind, "seed": args.seed,
             "context_mean": float(lens.mean()), "lines": []}
    every = cases(ids, table)
    for form in args.forms.split(","):
        seconds, lookups, nbytes = every[form]()
        line = {"form": form, "layer_us": 1e6 * seconds,
                "ns_an_index": 1e9 * seconds / lookups,
                "bytes_a_lookup": nbytes,
                "gb_per_s": lookups * nbytes / seconds / 1e9}
        found["lines"].append(line)
        print(f"{form:18s} layer {line['layer_us']:8.1f} us  "
              f"{line['ns_an_index']:6.2f} ns an index  "
              f"{nbytes:5d} B a lookup  {line['gb_per_s']:6.1f} GB/s",
              flush=True)
    by = {l["form"]: l["layer_us"] for l in found["lines"]}
    if {"rows640", "pair"} <= set(by):
        found["row_over_pair"] = by["rows640"] / by["pair"]
        print(f"rows640 / pair = {found['row_over_pair']:.3f} "
              "(ISSUE 55's stop rule: under 0.65)")
    found["fit"] = fit(found["lines"])
    found["fit_rows_alone"] = fit(found["lines"], r"rows\d+")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    print(json.dumps(found))


if __name__ == "__main__":
    main()
