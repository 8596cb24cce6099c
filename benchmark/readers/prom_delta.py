"""Deltas of the program's /metrics counters over the window."""

from harness import prom
from harness.rundata import RunData
from readers.client import window_decode_tokens


def read(run: RunData, args: dict):
    if not run.prom_start or not run.prom_end:
        return None
    d = prom.delta(run.prom_start, run.prom_end, args["metric"], args.get("labels"))
    op = args.get("op", "delta")
    if op == "delta":
        return d
    if op == "pct_of_window":        # a *_seconds_sum as a share of the window
        return 100.0 * d / run.seconds
    if op == "decode_tokens_per_delta":   # tokens decode steps made, per step
        return window_decode_tokens(run) / d if d > 0 else None
    raise ValueError(f"prom_delta reader: unknown op {op!r}")
