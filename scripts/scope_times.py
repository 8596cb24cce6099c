#!/usr/bin/env python3
"""Own device time by scope and by operation, a program, of a traced
benchmark run's capture.

    python3 scripts/scope_times.py .bench_work/<cell>/profile

Reads the capture ``benchmark/run.py --trace 1`` leaves (run it where
the capture is: on the chip, in the same call) through
``benchmark/harness/xplane_meta.load_op_events`` and prints, for the
decode and the prefill programs, the mean execution and the own time an
execution of every nest of the trunks' scopes and of the fourteen
largest operations: the by-hand reading PERF.md section 5 gives a cell
whose general readings no manifest entry lists yet."""
import collections, os, re, sys
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness.trace import find_xplane
from harness.xplane_meta import load_op_events
path = find_xplane(sys.argv[1])
dev = load_op_events(path)
dev = dev[min(dev)]
ops, mods = dev["ops"], dev["modules"]
SCOPES = ("embed", "attn_window", "attn_full", "kv_window", "kv_full", "mlp", "moe_route", "moe_experts", "lm_head", "sampling")
for prog in ("jit_decode_", "jit_prefill_"):
    ms = [m for m in mods if m.name.startswith(prog)]
    if not ms:
        continue
    by_scope, by_op, total = collections.Counter(), collections.Counter(), 0.0
    for m in ms:
        end = m.start + m.dur
        for o in ops:
            if m.start <= o.start < end:
                parts = o.detail.split("/") if o.detail else []
                inner = [p for p in parts if p in SCOPES]
                by_scope["/".join(inner) or "(none)"] += o.own
                by_op[o.name + " | " + (inner[-1] if inner else "")] += o.own
                total += o.own
    n = len(ms)
    print(f"== {prog}: {n} executions, mean {1e3*sum(m.dur for m in ms)/n:.3f} ms, own {1e3*total/n:.3f} ms")
    for k, v in by_scope.most_common(14):
        print(f"  scope {k:40s} {1e3*v/n:9.3f} ms")
    for k, v in by_op.most_common(14):
        print(f"  op    {k:40s} {1e3*v/n:9.3f} ms")
