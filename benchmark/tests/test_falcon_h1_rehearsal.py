"""``falcon-h1-chat`` at tiny widths on the CPU (``--cpu-rehearsal``):
the ``falcon_h1`` shape of the configuration's ``rehearsal`` group (10
query heads over 2 kv heads, a mixer of 6 heads in 2 groups with a scan
chunk of 16, every published multiplier) served through the harness on
the route ``auto`` takes, the state kept by slot beside the pages,
against ``references/falcon_h1.py``. Like ``test_rehearsal.py`` it says
nothing about the chip and stays out of tier-1 (about two minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "falcon-h1-chat"


@pytest.mark.parametrize("trace", [0, 1])
def test_falcon_h1_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2147483659",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "falcon_h1"
    # float32 at tiny widths: the served path (chunked scan, state by
    # slot) and the reference (the recurrence) agree far inside the
    # limits measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    want = manifest.load_cell(CELL)
    if trace:
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
