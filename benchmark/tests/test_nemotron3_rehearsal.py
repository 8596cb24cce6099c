"""``nemotron3-reasoning`` at tiny widths on the CPU
(``--cpu-rehearsal``): the ``nemotron_h`` shape of the configuration's
``rehearsal`` group (the stage's eleven letters ``MEMEMEM*EME`` at a
hidden size of 64, 8 mixer heads in two groups, 8 query heads over 2 kv
heads, experts of two matrices in a latent of 32, 4 of 16 held as rank
0's share, top-6, scaling 5) served through the harness on the route
``auto`` takes, the state kept by slot for the mixer layers and pages
for the attention layer, against ``references/nemotron_h.py`` given the
same share. It says nothing about the chip. One traced run, so that
``tests/test_benchmark_units.py`` can adopt it: the untraced run reports
the two end-to-end metrics and nothing this one does not."""

import os
import sys

from harness import manifest

# (beside this file, also where tests/test_benchmark_units.py adopts it)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_rehearsal import ROOT, _dry_result, _run  # noqa: E402

CELL = "nemotron3-reasoning"


def test_nemotron3_rehearsal():
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2262000007",
                           "--seconds", "5", "--trace", "1", "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "nemotron_h"
    # float32 at tiny widths: the served path (chunked scan, state by
    # slot, sorted latent rows of the held experts) and the reference
    # (the recurrence, every held expert in turn) agree far inside the
    # limits measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    got = set(res["metrics"])
    device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
    assert not got & device
    assert got == {m.name for m in manifest.load_cell(CELL).per_layer} - device
    assert 0 < res["metrics"]["kv_block_usage_max"]["value"] <= 100
    assert res["metrics"]["preemptions"]["value"] == 0
