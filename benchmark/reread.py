#!/usr/bin/env python3
"""Read a kept traced run again, on the CPU, with the manifest and the
readers as they stand.

A ``benchmark`` PR that changes a reader, or appends a cell to an
entry's ``workloads`` list, has to show that every entry a cell lists
still prints a value there. A traced run costs five to eight minutes
of a chip; what it measured can be kept and read as often as the
readers change. A kept run is a directory named ``<cell>.<tag>`` with

- ``rundata.json``: ``{"got": what harness/loadgen.py wrote,
  "fields": RunData's other fields but the cell}`` (PR 58's builder
  kept them with a wrapper of ``run.py`` that is not in the tree);
- ``capture.xplane.pb.gz``: the profiler's capture of that run.

    python3 benchmark/reread.py [--out readings.json] <dir> [<dir> ...]

prints each listed entry with its value and exits 1 where an entry a
cell lists has nothing to read or a time reads under zero. ``--out``
writes ``{cell: {"origin": ..., "metrics": {name: value}}}``, the form
of ``tests/data/per-layer-readings.json``, which holds for every entry
and every cell that lists it one value a run on the v5e gave.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from harness import manifest, trace  # noqa: E402
from harness.rundata import RunData, read_metric  # noqa: E402
from readers.scope_ops import profile_dir  # noqa: E402

TIMES = ("ms", "s")     # units of a duration
REMAINDERS = {"setup_unnamed_s"}   # set-up less its named parts: +-1 us


def load(kept: str) -> RunData:
    cell = manifest.load_cell(os.path.basename(kept.rstrip("/")).rsplit(".", 1)[0])
    with open(os.path.join(kept, "rundata.json")) as f:
        data = json.load(f)
    fields = dict(data["fields"], trace_slice=tuple(data["fields"]["trace_slice"]))
    run = RunData.from_client(data["got"], cell=cell, **fields)
    # where run.py has the profiler write a run's capture, and where the
    # scope readers look for it
    prof = profile_dir(run)
    shutil.rmtree(prof, ignore_errors=True)
    os.makedirs(prof)
    path = os.path.join(prof, "kept.xplane.pb")
    with gzip.open(os.path.join(kept, "capture.xplane.pb.gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    run.device_trace = trace.load(path)
    return run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kept", nargs="+")
    ap.add_argument("--out")
    args = ap.parse_args()
    out, bad = {}, 0
    for kept in args.kept:
        run = load(kept)
        print(f"{run.cell.name} ({kept}): {len(run.cell.per_layer)} entries listed")
        values = {}
        for m in run.cell.per_layer:
            value, n = read_metric(m, run)
            wrong = value is None or (m.unit in TIMES and value < 0
                                      and m.name not in REMAINDERS)
            bad += wrong
            said = "nothing to read" if value is None else f"{value:.6g} {m.unit}"
            print(f"  {'!!' if wrong else '  '} {m.name} = {said}"
                  + (f"  (n={n})" if n else ""))
            if value is not None:
                values[m.name] = value
        out[run.cell.name] = {"origin": f"benchmark/reread.py of {kept}",
                              "metrics": values}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
