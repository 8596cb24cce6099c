"""``sala-longdoc`` at tiny widths on the CPU (``--cpu-rehearsal``): the
``minicpm_sala`` shape of the configuration's ``rehearsal`` group (two
block-sparse attention layers around two lightning ones, ``dense_len``
64 so that the long probe and the traffic's prompts cross it) served
through the harness with the kernels in the interpreter (the state
kernel, the paged decode kernel over the kept pages), against
``references/minicpm_sala.py``. Like ``test_rehearsal.py`` it says
nothing about the chip and stays out of tier-1 (about two minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "sala-longdoc"


@pytest.mark.parametrize("trace", [0, 1])
def test_minicpm_sala_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2147483659",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "minicpm_sala"
    # float32 at tiny widths: the served path (chunked scan, state by
    # slot, page means, a walk of the kept pages) and the reference (the
    # recurrence, window means, a masked product) agree far inside the
    # limits measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    want = manifest.load_cell(CELL)
    if trace:
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
        # the selection ran: the traffic's prompts are past dense_len
        assert 0 < res["metrics"]["sparse_kept_share"]["value"] < 100
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
