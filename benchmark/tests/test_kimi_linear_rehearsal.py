"""``kimi-linear-reasoning`` at tiny widths on the CPU
(``--cpu-rehearsal``): the ``kimi_linear`` shape of the configuration's
``rehearsal`` group (six layers, four of them Kimi Delta Attention of 4
heads of 16 and two latent attention without a positional term, a dense
feed-forward behind the first and 4 of 16 experts held as rank 0's share
behind the others, top-3) served through the harness on the route
``auto`` takes, the state kept by slot for the KDA layers and latent
pages for the other two, against ``references/kimi_linear.py`` given the
same share. Like ``test_rehearsal.py`` it says nothing about the chip
and stays out of tier-1 (about two minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "kimi-linear-reasoning"


@pytest.mark.parametrize("trace", [0, 1])
def test_kimi_linear_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2147483659",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "kimi_linear"
    # float32 at tiny widths: the served path (chunked scan, the state
    # kernel in the interpreter, absorbed latent attention over pages,
    # sorted rows of the held experts) and the reference (the recurrence,
    # un-absorbed attention, every held expert in turn) agree far inside
    # the limits measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    want = manifest.load_cell(CELL)
    if trace:
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
        # rank 0 holds a quarter of the experts: about a quarter of the picks
        assert 10 < res["metrics"]["moe_held_pick_share"]["value"] < 45
        assert 0 < res["metrics"]["moe_active_expert_share"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
