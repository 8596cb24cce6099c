"""``xing4-batch`` at tiny widths on the CPU (``--cpu-rehearsal``): the
``xing4_0`` shape of the configuration's ``rehearsal`` group (four
residual streams, 20 Sinkhorn iterations with a clamp of +-2, a query
bottleneck of 24, YaRN over an original length of 64, shorter than the
rehearsal's contexts, 1 dense + 2 expert layers, 8 experts top-3 + 1
shared) served through the harness on the route ``auto`` takes, the
grouped-matmul and Sinkhorn kernels in the Pallas interpreter, against
``references/xing4.py``. Like ``test_rehearsal.py`` it says nothing
about the chip and stays out of tier-1 (about two minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "xing4-batch"


@pytest.mark.parametrize("trace", [0, 1])
def test_xing4_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2147483693",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "xing4"
    # float32 at tiny widths: the served path (streams side by side,
    # tokens minor, absorbed attention) and the reference (the
    # equations' layout) agree far inside the limits measured for
    # bfloat16 on the chip
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
