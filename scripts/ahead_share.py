#!/usr/bin/env python3
"""How often the decode step ran ahead of the host in a benchmark run.

Reads the two ``/metrics`` scrapes a run of ``benchmark/run.py`` keeps
(``.bench_work/<cell>/client.json``: the measured window's two ends) and
prints one JSON object, over the window:

- ``decode_fetches``: synchronous waits for a decode result
  (``dynamo_scheduler_fetches_total{kind="decode"}``), one a step;
- ``ahead``: steps dispatched before the step before them was read
  (``dynamo_scheduler_decode_ahead_total``), and ``ahead_share`` of the
  fetches;
- ``discarded``: rows of such steps whose token was dropped
  (``dynamo_scheduler_decode_ahead_discarded_total``), and
  ``discarded_share`` of the rows dispatched that held a sequence (the
  tokens emitted past a request's first, by the inter-token histogram's
  count, plus the dropped ones; for a family whose decode unit is a
  block, whose pass is ``jit_decode_block`` and runs ahead as the step
  does, the rows its passes landed,
  ``dynamo_scheduler_block_row_passes_total``, plus the dropped ones);
- ``fallbacks``: ``dynamo_engine_sync_fallback_total`` by reason, and
  ``unnamed``: fetches that neither went ahead nor fell back under a
  reason (the first step after the device ran out of rows).

    python scripts/ahead_share.py .bench_work/<cell>/client.json \
        [--out chiprun_out/<dir>/ahead.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))

from harness import prom  # noqa: E402


def shares(start_text: str, end_text: str) -> dict:
    start, end = prom.parse(start_text), prom.parse(end_text)

    def moved(metric, labels=None):
        return prom.delta(start, end, metric, labels)

    fetches = moved("dynamo_scheduler_fetches_total", {"kind": "decode"})
    ahead = moved("dynamo_scheduler_decode_ahead_total")
    discarded = moved("dynamo_scheduler_decode_ahead_discarded_total")
    # rows that held a sequence and were applied: one a token, or one a
    # row pass of a block family (a chunk of k tokens is k gaps there)
    rows = (moved("dynamo_scheduler_block_row_passes_total")
            or moved("dynamo_scheduler_inter_token_latency_seconds_count"))
    name = "dynamo_engine_sync_fallback_total"
    reasons = {dict(lab).get("reason", ""): v - start.get((name, lab), 0.0)
               for lab, v in prom.rows(end, name).items()}
    reasons = {r: v for r, v in reasons.items() if v}
    return {
        "decode_fetches": fetches, "ahead": ahead,
        "ahead_share": ahead / fetches if fetches else None,
        "discarded": discarded,
        "discarded_share": (discarded / (rows + discarded)
                            if rows + discarded else None),
        "fallbacks": reasons,
        "unnamed": fetches - ahead - sum(reasons.values()),
        "preemptions": moved("dynamo_scheduler_preemptions_total"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("client_json")
    ap.add_argument("--out")
    args = ap.parse_args()
    with open(args.client_json) as f:
        run = json.load(f)
    got = shares(run["prom_start"], run["prom_end"])
    text = json.dumps(got)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
