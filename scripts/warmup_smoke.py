#!/usr/bin/env python3
"""A cell's start on the chip, one warm-up shape at a time: does every
program ``ModelRunner.warmup`` dispatches come back?

    python scripts/warmup_smoke.py --workload <cell> [--limit 150]
        [--seed 1] [--cpu-rehearsal]

A program can compile for the chip here, pass every CPU test and never
return on the real one (PERF.md section 6, PR 53: Granite's prefill
program of two rows of 1024 tokens with a transposition of the rows'
state before the chunk loop). A cell's run then hangs until its time
limit and says nothing. This builds the cell's engine as
``benchmark/run.py`` does (it runs that file, so the configuration, the
weights and the flags are the cell's), waits for each ``step`` of
warm-up before the next is dispatched, and holds each to ``--limit``
seconds: a shape over it is named (``HANGS``) and the process exits 3.
When warm-up returns it prints ``ALL SHAPES RAN`` and exits 0 without
offering any load: 1.4 to 2.7 chip-minutes a mixer cell (my chip run,
PR 53). It is a builder's tool: no cell and no test runs it.
"""

from __future__ import annotations

import argparse
import os
import runpy
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ap = argparse.ArgumentParser()
ap.add_argument("--workload", required=True)
ap.add_argument("--limit", type=float, default=150.0,
                help="seconds a shape's compile and first run may take")
ap.add_argument("--seed", type=int, default=1)
ap.add_argument("--cpu-rehearsal", action="store_true")
args = ap.parse_args()
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from dynamo_tpu.engine.model_runner import ModelRunner  # noqa: E402

_step, _warmup = ModelRunner.step, ModelRunner.warmup


def say(*words):
    print("warmup_smoke:", *words, flush=True)


def step(self, tokens, *rest, **kw):
    shape, t0 = tuple(tokens.shape), time.time()

    def hangs():
        say("step", shape, f"HANGS ({args.limit:g} s)")
        os._exit(3)

    timer = threading.Timer(args.limit, hangs)
    timer.start()
    out = _step(self, tokens, *rest, **kw)
    jax.block_until_ready((out[0], self.kv_cache))
    timer.cancel()
    say("step", shape, f"ran in {time.time() - t0:.1f} s")
    return out


def warmup(self, *a, **kw):
    _warmup(self, *a, **kw)
    say("ALL SHAPES RAN")
    sys.stdout.flush()
    os._exit(0)


ModelRunner.step, ModelRunner.warmup = step, warmup
sys.argv = ["benchmark/run.py", "--workload", args.workload, "--seed",
            str(args.seed), "--seconds", "1", "--trace", "0"] + (
                ["--cpu-rehearsal"] if args.cpu_rehearsal else [])
os.chdir(ROOT)
runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"), run_name="__main__")
