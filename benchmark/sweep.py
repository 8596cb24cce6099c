#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip. Not part of a run.

    python3 benchmark/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30

Starts the cell's server once, then offers the cell's traffic at each
rate in turn (ramp, window, drain, as a run does) and prints one row per
rate: the share of the window's requests that met both of the cell's
limits, the tails, and the backlog at the window's end. The knee is the
highest rate at which at least 90 % meet both limits with no growing
backlog; the cell's file then fixes 0.8 x knee as its rate
(``--write-rate`` puts it there, to two significant figures). The table
goes into PERF.md by hand.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from harness import manifest, prom, server  # noqa: E402
from harness.drive import drive, make_plan  # noqa: E402
from harness.rundata import RunData, failed  # noqa: E402
from harness.stats import percentile  # noqa: E402
from readers import client  # noqa: E402


async def amain(args) -> int:
    cell = manifest.load_cell(args.workload)
    work = os.path.join(ROOT, ".bench_work", "sweep-" + cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    port = server.free_port()
    flags, hf = server.build_flags(cell.config, cell.config_name, work,
                                   args.seed, port, False)
    if server.tpu_devices(cell.chips) is None:
        print("sweep: no TPU, or fewer chips than the cell asks for", file=sys.stderr)
        return 3
    engine, serving = await server.start(flags)
    rows = []
    for i, rate in enumerate(args.rates):
        cell.cell = {**cell.cell, "rate": rate}
        plan = make_plan(cell, hf, port, args.seed + i, args.seconds, False, work)
        plan["probes"] = plan["probes"][:1]
        got, _, _ = await drive(plan, work)
        run = RunData.from_client(got, cell=cell, hf=hf, serve=vars(flags),
                                  seconds=args.seconds, setup_seconds=0.0)
        rs = run.in_window
        half = (run.window[0] + run.window[1]) / 2
        ttft = client.ttfts(run, "due")
        # a growing backlog shows as TTFT rising through the window
        first = [t for r, t in zip(rs, ttft) if r["due"] < half]
        second = [t for r, t in zip(rs, ttft) if r["due"] >= half]
        row = {
            "rate": rate, "attempted": len(rs),
            "failed": sum(failed(r) for r in rs),
            "limits_met_pct": client.read(run, {"stat": "limits_met_pct"})[0],
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p90_ms": percentile(ttft, 90),
            "ttft_p50_first_half_ms": percentile(first, 50),
            "ttft_p50_second_half_ms": percentile(second, 50),
            "req_gap_p90_ms": percentile(client.request_mean_gaps(run), 90),
            "itl_p50_ms": percentile(client.token_gaps(run), 50),
            "itl_p99_ms": percentile(client.token_gaps(run), 99),
            "tokens_per_s": client.window_output_tokens(run) / args.seconds,
            "waiting_at_end": prom.value(run.prom_end, "dynamo_scheduler_waiting_requests"),
            "active_at_end": prom.value(run.prom_end, "dynamo_scheduler_active_slots"),
            "kv_usage_at_end": prom.value(run.prom_end, "dynamo_kv_block_usage_ratio"),
            "preemptions": prom.delta(run.prom_start, run.prom_end,
                                      "dynamo_scheduler_preemptions_total"),
        }
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    await server.stop(serving)
    await engine.core_engine.close()
    out = os.path.join(ROOT, "chiprun_out", f"sweep-{cell.name}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    knee = knee_of(rows, cell.cell["limits"]["ttft_ms"])
    rate = two_significant(0.8 * knee)
    print(f"KNEE {knee:g} req/s -> rate {rate:g} req/s", flush=True)
    if args.write_rate and knee > 0:
        path = os.path.join(BENCH_DIR, "cells", cell.name + ".json")
        with open(path) as f:
            data = json.load(f)
        data["rate"] = rate
        with open(path, "w") as f:
            json.dump(data, f, indent=1)
            f.write("\n")
    return 0


def knee_of(rows: list, ttft_limit_ms: float) -> float:
    """The highest swept rate at which >= 90 % of requests met both
    limits, none failed, and nothing says the backlog grows: nobody is
    waiting at the end, and the second half's median TTFT is neither
    much above the first's nor above half the limit (with a dozen
    requests to a half, medians of a few hundred ms swing two-fold on
    their own). 0.0 where no rate qualifies."""
    ok = [r["rate"] for r in rows
          if r["limits_met_pct"] >= 90.0 and not r["failed"]
          and (r["waiting_at_end"] or 0) <= 2
          and r["ttft_p50_second_half_ms"] <= max(
              1.5 * r["ttft_p50_first_half_ms"] + 100.0, 0.5 * ttft_limit_ms)]
    return max(ok, default=0.0)


def two_significant(x: float) -> float:
    return float(f"{x:.2g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--write-rate", action="store_true",
                    help="put 0.8 x knee into the cell's file")
    return asyncio.run(amain(ap.parse_args()))


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
