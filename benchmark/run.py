#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = set-up (model directory from the configuration, weights on the
device from the seed, warm-up of every program from the compile cache,
correctness probes, ramp) -> window of ``--seconds`` -> drain -> the
reference check -> one JSON line -> exit 0. This process holds the
chip(s) for the whole run: it serves, takes the profiler's trace and
runs the reference. The load generator is a child that never imports
jax (``harness/loadgen.py``).

Everything about a cell, a configuration, a traffic mix or a metric is
read from the files ``BENCHMARK.json`` names (``harness/manifest.py``).

``--trace 0`` turns nothing extra on and reports the end-to-end metrics.
``--trace 1`` is a run of its own with the same traffic: ``X-Request-Id``
on every request, ``/metrics`` sampled each second and one profiler
capture from the middle of the window; it reports the per-layer metrics
and ``breakdown``.

Without a TPU the command exits non-zero and prints no result line.
``--cpu-rehearsal`` (for ``benchmark/tests`` only) runs the whole path at
tiny widths on the CPU, prints ``DRY RUN`` and never the result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from harness import manifest, prom  # noqa: E402
from harness.drive import drive, make_plan  # noqa: E402
from harness.modeldir import token_id  # noqa: E402
from harness.rundata import RunData, failed, read_metric  # noqa: E402
from readers.client import summary as client_summary  # noqa: E402

WORK = os.path.join(ROOT, ".bench_work")
TRACE_SLICE_S = 4.0       # the profiler's capture, from the window's middle
EXIT_NO_DEVICE = 3


def say(msg: str) -> None:
    print(msg, flush=True)


def _first_platform() -> str:
    return os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0].strip()


def _capture(trace_dir: str, at: float, seconds: float) -> tuple:
    """Blocking: one profiler capture of ``seconds`` starting at ``at``
    (monotonic). The python tracer is off: it hooks every call of the
    scheduler's loop, and that loop is what is being measured."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    time.sleep(max(0.0, at - time.monotonic()))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.monotonic()
    time.sleep(seconds)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    return t0, t1


def _usage_ok(r: dict) -> bool:
    u = r.get("usage") or {}
    return (u.get("prompt_tokens") == r["prompt_tokens"]
            and u.get("completion_tokens") == r["max_tokens"]
            and sum(r["chunk_tokens"]) == r["max_tokens"])


def _route_rows(samples: dict) -> dict:
    rows = prom.rows(samples, "dynamo_engine_attention_route_total")
    return {":".join(v for k, v in lab if k in ("program", "route")): n
            for lab, n in rows.items()}


async def amain(args) -> int:
    rehearsal = args.cpu_rehearsal
    cell = manifest.load_cell(args.workload, rehearsal=rehearsal)
    ref_name = cell.config["reference"]
    reference = manifest.architecture_module(cell.config, cell.config_name,
                                             "reference")
    work = os.path.join(WORK, cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    from harness import server

    port = server.free_port()
    flags, hf = server.build_flags(cell.config, cell.config_name, work,
                                   args.seed, port, rehearsal)
    devices = server.tpu_devices(cell.chips)
    if devices is None:
        if not rehearsal:
            print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s) and "
                  "jax does not see them", file=sys.stderr)
            return EXIT_NO_DEVICE
        import jax

        devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    say(f"cell {cell.name}: configuration {cell.config_name}, traffic "
        f"{cell.traffic_name}, {cell.chips} chip(s), seed {args.seed}, "
        f"window {args.seconds:g} s, trace {args.trace}")

    engine, serving = await server.start(flags)
    runner = engine.core_engine.runner
    say(f"serving after {time.monotonic() - T_START:.1f} s (weights, warm-up)")

    plan = make_plan(cell, hf, port, args.seed, args.seconds, bool(args.trace),
                     work, rehearsal)
    loop = asyncio.get_running_loop()
    trace_dir = os.path.join(work, "profile")

    async def on_window(t0: float):
        say(f"window open: set-up took {t0 - T_START:.1f} s")
        if not args.trace:
            return None
        slice_s = min(TRACE_SLICE_S, args.seconds)
        at = t0 + (args.seconds - slice_s) / 2
        return await loop.run_in_executor(None, _capture, trace_dir, at, slice_s)

    got, t0, trace_slice = await drive(plan, work, on_window)

    # the reference the configuration names, on the chip, after the drain
    # and outside the window
    from harness.reference import check_probes

    t_ref = time.monotonic()
    ref = await loop.run_in_executor(
        None, check_probes, reference, runner.params, hf, got["probes"], token_id)
    say(f"reference: {ref_name}, {ref['tokens_compared']} tokens, max |dlogp| "
        f"{ref['max_abs_err']:.5f} (limit {reference.LOGPROB_ATOL:g}), mean "
        f"{ref['mean_abs_err']:.5f} (limit {reference.LOGPROB_MEAN_ATOL:g}) "
        f"({time.monotonic() - t_ref:.1f} s){'' if ref['ok'] else ' FAILED: ' + '; '.join(ref['reasons'])}")

    stats = [d.memory_stats() or {} for d in runner.mesh.devices.flat]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    in_use = [s.get("bytes_in_use") for s in stats]
    cache_itemsize = runner.kv_cache[0].dtype.itemsize
    await server.stop(serving)
    await engine.core_engine.close()

    run = RunData.from_client(
        got, cell=cell, hf=hf, serve=vars(flags), seconds=args.seconds,
        setup_seconds=t0 - T_START, trace_slice=trace_slice, device_kind=kind,
        cache_itemsize=cache_itemsize)
    device = {"platform": platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        from harness import trace as tr

        path = tr.find_xplane(trace_dir)
        if path is None:
            raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
        try:
            run.device_trace = t = tr.load(path)
        except ValueError as e:
            if not rehearsal:
                raise
            say(f"(rehearsal) {e}")
        else:
            d0 = t.devices[0]
            device["busy_s"], device["window_s"] = t.mean_busy_s, t.window_s
            breakdown = {"device_ops": tr.top_ops(t, d0, 10),
                         "idle_gaps": tr.idle_gaps(t, d0, 5)}

    in_window = run.in_window
    n_failed = sum(failed(r) for r in in_window)
    bad_usage = [r["rid"] for r in in_window if not failed(r) and not _usage_ok(r)]
    late = prom.delta(run.prom_start, run.prom_end,
                      "dynamo_engine_xla_compiles_total", {"phase": "late"})
    lateness = max(((r["send"] - r["due"]) * 1e3 for r in in_window), default=0.0)
    say(f"requests in the window: {len(in_window)} attempted, {n_failed} failed, "
        f"{len(bad_usage)} with wrong usage counts {bad_usage[:3]}; "
        f"generator lateness max {lateness:.2f} ms")
    say(f"device: {kind} x{cell.chips}, bytes_in_use {in_use}, peak {peak}; "
        f"attention routes {_route_rows(run.prom_end)}")
    if late:
        say(f"WARNING: {late:g} late compile(s) inside the window: "
            "this run's timings are void")
    for r in in_window:
        if failed(r):
            say(f"  failed {r['rid']}: status {r['status']} done {r['done']} "
                f"tokens {sum(r['chunk_tokens'])}/{r['max_tokens']} {r['error']}")

    say("  client, for the record: " + "; ".join(
        f"{k} {v[0]:.5g} (n={v[1]})" for k, v in client_summary(run).items() if v))
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value, n = read_metric(m, run)
        if value is None:
            say(f"  {m.name}: nothing to read")
            continue
        metrics[m.name] = {"value": value, "unit": m.unit}
        say(f"  {m.name} = {value:.6g} {m.unit}" + (f"  (n={n})" if n else ""))

    result = {"correct": bool(ref["ok"] and not bad_usage),
              "attempted": len(in_window), "failed": n_failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["reference"] = {"name": ref_name, **{k: ref[k] for k in (
        "max_abs_err", "mean_abs_err", "tokens_compared")}}
    result["compiles_in_window"] = late
    if rehearsal:
        say("DRY RUN " + json.dumps(result))
        return 0
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="benchmark/tests only: tiny widths on the CPU, "
                         "interpret-mode kernels; prints DRY RUN, never a result")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        say("DRY RUN: CPU, tiny widths, interpret-mode kernels; this says "
            "nothing about the chip")
        os.environ.update(
            JAX_PLATFORMS="cpu", DYN_PALLAS_INTERPRET="1",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")
    else:
        if _first_platform() == "cpu":
            print("benchmark: JAX_PLATFORMS asks for the cpu: no chip here",
                  file=sys.stderr)
            return EXIT_NO_DEVICE
        if "DYN_PALLAS_INTERPRET" in os.environ:
            print("benchmark: DYN_PALLAS_INTERPRET is set; a measured run "
                  "compiles its kernels", file=sys.stderr)
            return EXIT_NO_DEVICE
    try:
        return asyncio.run(amain(args))
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the engine's worker threads are not all daemons; everything that
    # matters has been closed and printed
    os._exit(rc)
