#!/usr/bin/env python3
"""What the block table's width costs a decode step, on the chip.

    python3 scripts/width_rungs.py --workload phi3-chat --rows 4 --tokens 400 \
        --seed <n> --out chiprun_out/<dir>/rungs.json

The cell's configuration is built as ``benchmark/run.py`` builds it (the
same flags, weights from ``--seed``, warm-up included), and then one and
the same batch (``--rows`` rows of ``--tokens`` tokens, the other slots
idle) goes through ``ModelRunner.step`` at every rung of
``EngineConfig.kv_width_buckets`` that covers it, whatever warm-up
compiled: the rungs nobody warmed compile here. A rung is timed twice,
in rising and in falling order:

- ``step_ms``: ``--steps`` dispatches back to back and one wait at the
  end, a step. The device's time of the program where the host is ahead
  of it (``host_ms`` well under ``step_ms``).
- ``host_ms``: the median time one ``ModelRunner.step`` call takes to
  return (packing the ``[B, W]`` table into the step's input, the
  transfer's enqueue, the dispatch), each step waited for before the
  next starts.
- ``table_copy_us``: the scheduler's part, ``btab[:, :w].copy()``.

PR 51 read it before taking the narrower rungs away from the programs
whose kernels walk live pages (PERF.md section 6): a rung more than 1 %
of a step faster than the full width names an operation that reads the
width. Off a TPU it measures nothing and exits 3 (``--cpu-rehearsal``
runs the tiny shapes of the cell's rehearsal, to debug the script).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [BENCH, ROOT]


def batch(cfg, rows: int, tokens: int, width: int):
    """One decode step's arguments as the scheduler lays them out:
    ``rows`` live slots whose last token is at position ``tokens - 1``,
    each with blocks of its own; an idle slot has context 1, slot -1
    and commits nothing."""
    import numpy as np

    b, page = cfg.max_batch_size, cfg.kv_block_size
    nblocks = -(-tokens // page)
    live = np.arange(b) < rows
    btab = np.zeros((b, width), np.int32)
    btab[:rows, :nblocks] = 1 + np.arange(rows * nblocks).reshape(rows, nblocks)
    pos = np.where(live, tokens - 1, 0).astype(np.int32)[:, None]
    slot = np.where(live[:, None],
                    np.take_along_axis(btab, pos // page, 1) * page + pos % page,
                    -1).astype(np.int32)
    f32 = lambda x: np.full(b, x, np.float32)
    args = (np.zeros((b, 1), np.int32), pos, btab, slot,
            np.where(live, tokens, 1).astype(np.int32), np.zeros(b, np.int32),
            f32(0), np.zeros(b, np.int32), f32(1))
    kwargs = dict(min_p=f32(0), presence_penalty=f32(0), frequency_penalty=f32(0),
                  repetition_penalty=f32(1),
                  seed_keys=np.zeros((b, 2), np.uint32),
                  counters=np.zeros(b, np.int32),
                  sample_slots=np.arange(b, dtype=np.int32), commit=live)
    return args, kwargs


def time_rung(runner, args, kwargs, steps: int) -> dict:
    import jax
    import numpy as np

    jax.block_until_ready(runner.step(*args, **kwargs)[0])   # compiled, placed
    t0 = time.perf_counter()
    for _ in range(steps):
        out = runner.step(*args, **kwargs)
    jax.block_until_ready(out[0])
    step_ms = (time.perf_counter() - t0) / steps * 1e3
    host = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = runner.step(*args, **kwargs)
        host.append((time.perf_counter() - t0) * 1e3)
        jax.block_until_ready(out[0])
    btab, copies = args[2], []
    wide = np.zeros((btab.shape[0], runner.config.blocks_per_seq), np.int32)
    for _ in range(200):
        t0 = time.perf_counter()
        wide[:, :btab.shape[1]].copy()
        copies.append((time.perf_counter() - t0) * 1e6)
    return {"step_ms": step_ms, "host_ms": statistics.median(host),
            "table_copy_us": statistics.median(copies)}


async def amain(args) -> int:
    from harness import manifest, server

    cell = manifest.load_cell(args.workload)
    work = os.path.join(ROOT, ".bench_work", cell.name + "-width-rungs")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    flags, _ = server.build_flags(cell.config, cell.config_name, work, args.seed,
                                  server.free_port(), rehearsal=args.cpu_rehearsal)
    if not args.cpu_rehearsal and server.tpu_devices(cell.chips) is None:
        print("width_rungs: no TPU here", file=sys.stderr)
        return 3
    from dynamo_tpu.cli.run import build_engine

    t0 = time.monotonic()
    engine, _ = await build_engine("jax", flags)
    runner = engine.core_engine.runner
    cfg = runner.config
    print(f"built and warmed in {time.monotonic() - t0:.1f} s: "
          f"{json.dumps(runner.warmed_widths)}", flush=True)
    need = -(-args.tokens // cfg.kv_block_size)
    rungs = [w for w in cfg.kv_width_buckets() if w >= need]
    out = {"workload": cell.name, "seed": args.seed, "rows": args.rows,
           "tokens": args.tokens, "batch": cfg.max_batch_size,
           "blocks_per_seq": cfg.blocks_per_seq, "steps": args.steps,
           "warmed": runner.warmed_widths, "rungs": []}
    rows = {w: {"width": w} for w in rungs}
    for order, pass_ in (("rising", rungs), ("falling", rungs[::-1])):
        for w in pass_:
            got = time_rung(runner, *batch(cfg, args.rows, args.tokens, w),
                            args.steps)
            rows[w].update({f"{k}.{order}": v for k, v in got.items()})
            print(json.dumps({"width": w, "order": order, **got}), flush=True)
    full = rows[cfg.blocks_per_seq]
    for w in rungs:
        r = rows[w]
        r["step_ms"] = (r["step_ms.rising"] + r["step_ms.falling"]) / 2
        r["host_ms"] = (r["host_ms.rising"] + r["host_ms.falling"]) / 2
        out["rungs"].append(r)
    for r in out["rungs"]:
        # how much of a full-width step the rung saves (+: the rung is faster)
        r["step_saved_share"] = 1 - r["step_ms"] / full["step_ms"]
        r["host_saved_ms"] = full["host_ms"] - r["host_ms"]
    await engine.core_engine.close()
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(out), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--tokens", type=int, required=True)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    return asyncio.run(amain(ap.parse_args()))


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
