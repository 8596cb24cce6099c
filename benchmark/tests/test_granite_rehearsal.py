"""``granite-batch`` at tiny widths on the CPU (``--cpu-rehearsal``):
the ``granitemoehybrid`` shape of the configuration's ``rehearsal``
group (two runs of mamba layers around an attention layer, 8 mixer heads
in one group, 8 query heads over 2 kv heads, 4 of 8 experts held as rank
0's share, top-3, every published multiplier) served through the harness
on the route ``auto`` takes, the state kept by slot for the mixer layers
and pages for the attention layer, against
``references/granite_hybrid.py`` given the same share. Like
``test_rehearsal.py`` it says nothing about the chip and stays out of
tier-1 (about two minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "granite-batch"


@pytest.mark.parametrize("trace", [0, 1])
def test_granite_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2147483659",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "granite_hybrid"
    # float32 at tiny widths: the served path (chunked scan, state by
    # slot, sorted rows of the held experts) and the reference (the
    # recurrence, every held expert in turn) agree far inside the limits
    # measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    want = manifest.load_cell(CELL)
    if trace:
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
        # rank 0 holds half of the experts: about half of the picks
        assert 30 < res["metrics"]["moe_held_pick_share"]["value"] < 70
        assert 0 < res["metrics"]["moe_active_expert_share"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
