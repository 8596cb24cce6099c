"""Run the load generator as a child and follow its events."""

from __future__ import annotations

import asyncio
import json
import os
import random
import sys
from typing import Awaitable, Callable, Optional

from .manifest import BENCH_DIR, Cell
from .traffic import probe_prompts

EVENTS = ("PROBES_DONE", "WINDOW_START", "WINDOW_END", "DONE")
PROBE_TOKENS = 16


def probe_lengths(hf: dict, seed: int, scale: float) -> list:
    """Three short prompts and a long one: past the window where the
    model has one (so that the window masks something), else long enough
    to take more than one prefill step's budget. The long one's length
    is fixed, so that the reference compiles one program for it."""
    rng = random.Random(f"probe-lengths:{seed}")
    window = int(hf.get("sliding_window") or 0)
    long = window + 200 if window else 2200
    return [max(4, int(n * scale))
            for n in (*(rng.randrange(64, 513) for _ in range(3)), long)]


def make_plan(cell: Cell, hf: dict, port: int, seed: int, seconds: float,
              trace: bool, work: str, rehearsal: bool = False) -> dict:
    """Everything the load generator needs, as one JSON object."""
    scale = (cell.config.get("rehearsal", {}).get("probe_scale", 1.0)
             if rehearsal else 1.0)
    vocab = int(hf["vocab_size"])
    return {
        "base_url": f"http://127.0.0.1:{port}", "model": cell.config_name,
        "seed": seed, "seconds": seconds, "vocab_size": vocab,
        "traffic": cell.traffic, "cell": cell.cell,
        "sampling": cell.traffic["sampling"], "trace": trace,
        "probes": probe_prompts(probe_lengths(hf, seed, scale), vocab, seed),
        "probe_tokens": PROBE_TOKENS, "out": os.path.join(work, "client.json"),
    }


async def _pump(stream, events: dict, log: list) -> None:
    """The child's stdout: event lines set futures, the rest is kept."""
    while True:
        raw = await stream.readline()
        if not raw:
            return
        line = raw.decode(errors="replace").rstrip()
        word, _, rest = line.partition(" ")
        if word in events and not events[word].done():
            events[word].set_result(rest)
        else:
            log.append(line)


async def drive(plan: dict, work: str,
                on_window: Optional[Callable[[float], Awaitable]] = None):
    """Write the plan, run ``harness.loadgen`` on it, and return
    (what it recorded, the window's start, what ``on_window`` returned).
    ``on_window(t0)`` is awaited as soon as the window opens (the traced
    run's capture)."""
    plan_path = os.path.join(work, "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    loop = asyncio.get_running_loop()
    events = {k: loop.create_future() for k in EVENTS}
    child = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "harness.loadgen", plan_path, cwd=BENCH_DIR,
        stdout=asyncio.subprocess.PIPE, stderr=sys.stderr)
    log: list = []
    pump = asyncio.ensure_future(_pump(child.stdout, events, log))
    side = None
    try:
        ended = asyncio.ensure_future(child.wait())
        done, _ = await asyncio.wait(
            [events["WINDOW_START"], ended], return_when=asyncio.FIRST_COMPLETED)
        if events["WINDOW_START"] not in done:
            raise RuntimeError(f"the load generator ended (rc {child.returncode}) "
                               f"before the window: {log[-5:]}")
        t0 = float(events["WINDOW_START"].result())
        if on_window is not None:
            side = await on_window(t0)
        await ended
        await pump
        if child.returncode != 0 or not events["DONE"].done():
            raise RuntimeError(f"the load generator failed (rc {child.returncode}): "
                               f"{log[-5:]}")
    finally:
        if child.returncode is None:
            child.kill()
            await child.wait()
    with open(plan["out"]) as f:
        return json.load(f), t0, side
