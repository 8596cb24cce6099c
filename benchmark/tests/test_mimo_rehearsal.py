"""``mimo-longdoc`` at tiny widths on the CPU (``--cpu-rehearsal``): the
``mimo_v2`` shape of the configuration's ``rehearsal`` group (the seven
layers ``F | S S S S F S`` at a hidden size of 64, 8 query heads over 2
and 4 kv heads, keys of 24 over values of 16, a window of 32 under a
sink, 4 of 16 experts held as rank 0's share, top-3) served through the
harness with both kernels in the interpreter and both pools, prompts of
280-400 tokens in chunks of 256, against ``references/mimo_v2.py`` given
the same share. It says nothing about the chip. One traced run (under a
minute), so that ``tests/test_benchmark_units.py`` can adopt it: the
untraced run reports the two end-to-end metrics and nothing this one
does not."""

import os
import sys

from harness import manifest

# (beside this file, also where tests/test_benchmark_units.py adopts it)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_rehearsal import ROOT, _dry_result, _run  # noqa: E402

CELL = "mimo-longdoc"


def test_mimo_rehearsal():
    man = manifest.load_manifest()
    res = _dry_result(_run(ROOT, "--workload", CELL, "--seed", "2259000007",
                           "--seconds", "5", "--trace", "1", "--cpu-rehearsal"))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["reference"]["name"] == "mimo_v2"
    # float32 at tiny widths: the served path (the flash kernel with the
    # sink as its first term and the decode kernel in the interpreter,
    # pages of two kinds) and the reference (a masked product over the
    # whole sequence) agree far inside the limits measured on the chip
    assert res["reference"]["max_abs_err"] < 1e-3
    assert res["compiles_in_window"] == 0
    got = set(res["metrics"])
    device = {m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
    assert not got & device
    assert got == {m.name for m in manifest.load_cell(CELL).per_layer} - device
    assert res["metrics"]["mimo_xla_attention_routes"]["value"] == 0
    assert 0 < res["metrics"]["kv_block_usage_max"]["value"] <= 100
