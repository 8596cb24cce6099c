"""``dots3-longdoc`` at tiny widths on the CPU (``--cpu-rehearsal``): the
``dots3_note`` shape of the configuration's ``rehearsal`` group (the
published nine layers ``F F S S S F S S S`` at a hidden size of 64, an
indexer of 4 heads of 16 that picks 64 keys, a window of 33, 4 of 16
experts held as rank 0's share, top-3) served through the harness with
the window layers' latent kernel in the interpreter, both pools and the
indexer's cache, prompts of 280-400 tokens in chunks of 256, against
``references/dots3.py`` given the same share. Like ``test_rehearsal.py``
it says nothing about the chip and stays out of tier-1 (about three
minutes).
"""

import pytest

from harness import manifest
from test_rehearsal import ROOT, _dry_result, _run

CELL = "dots3-longdoc"


@pytest.mark.parametrize("trace", [0, 1])
def test_dots3_rehearsal(trace):
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2254000007",
                           "--seconds", "5", "--trace", str(trace),
                           "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "dots3"
    # float32 at tiny widths: the served path (blocks of absorbed
    # attention over pages, the cutoff search, the picked rows' gather,
    # the latent kernel in the interpreter) and the reference
    # (un-absorbed attention over the whole sequence, lax.top_k) agree
    # far inside the limits measured for bfloat16 on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    want = manifest.load_cell(CELL)
    if trace:
        got = set(res["metrics"])
        device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
        assert not got & device
        assert got == {m.name for m in want.per_layer} - device
        # 64 keys of 280-420: a fifth
        assert 12 < res["metrics"]["sparse_kept_share"]["value"] < 30
        assert 0 < res["metrics"]["kv_block_usage_max"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {m.name for m in want.end_to_end}
