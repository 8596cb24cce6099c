"""``trinity-longdoc`` at tiny widths on the CPU (``--cpu-rehearsal``):
the ``afmoe`` shape of the configuration's ``rehearsal`` group (eight
layers, window and full 3 : 1, two dense and six of 8 experts, a window
of 64 tokens = four pages, so that the long probe and every chunk of the
traffic's prompts release pages) served through the harness with the
kernels in the interpreter, against ``references/afmoe.py``. Like
``test_rehearsal.py`` it says nothing about the chip and stays out of
tier-1 (about two minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "trinity-longdoc"


@pytest.mark.parametrize("trace", [0, 1])
def test_afmoe_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2147483659",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "afmoe"
    # float32 at tiny widths: the served path (pages of two kinds, the
    # window kind's released, sorted rows of experts) and the reference
    # (a masked product, every expert on every token) agree far inside
    # the limits measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    want = manifest.load_cell(CELL)
    if trace:
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
        # the release ran: prompts of 280-400 tokens over a window of 64
        assert 50 < res["metrics"]["window_pages_released_share"]["value"] < 100
        assert res["metrics"]["kv_block_usage_max"]["value"] == max(
            res["metrics"]["kv_window_usage_max"]["value"],
            res["metrics"]["kv_full_usage_max"]["value"])
        assert res["metrics"]["preemptions"]["value"] == 0
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
