"""The benchmark's own unit tests, in tier-1.

``benchmark/tests`` holds the yardstick's arithmetic (statistics,
traffic, operations and bytes, the manifest's shape) and the reduction
from a profiler capture to numbers, checked against cuts of v5e
captures. They need no server and no device, so they run here too, each
case under its own name: this module loads those files and takes their
tests and fixtures as its own. The three-minute CPU rehearsal of the
whole command (``benchmark/tests/test_rehearsal.py``) stays out.
"""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
FILES = ("test_units.py", "test_trace_reduction.py", "test_host_spans.py",
         "test_scope_ops.py", "test_moonlight_units.py",
         "test_falcon_h1_units.py", "test_xing4_units.py",
         "test_minicpm_sala_units.py", "test_afmoe_units.py",
         "test_sync_parts.py", "test_granite_units.py",
         "test_kimi_linear_units.py", "test_dots3_units.py",
         # (its rehearsal too: one run of under a minute; the other
         # cells' take two to four and stay out)
         "test_mimo_units.py", "test_mimo_rehearsal.py",
         # (the newest cell's rehearsal takes 60 to 100 s and stays out,
         # as the older cells' do: cd benchmark && python -m pytest
         # tests/test_nemotron3_rehearsal.py)
         "test_nemotron3_units.py")


def _adopt(filename: str) -> None:
    """Execute one file of benchmark/tests as a module (with the import
    path benchmark/tests/conftest.py gives it) and bind its tests and
    fixtures here, so that pytest collects each under this file."""
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    name = "benchmark_tests_" + filename[:-3]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "tests", filename))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    for key, value in vars(module).items():
        is_fixture = (hasattr(value, "_pytestfixturefunction")
                      or type(value).__name__ == "FixtureFunctionDefinition")
        if key.startswith("test_") or is_fixture:
            assert key not in globals(), f"{filename}: {key} defined twice"
            globals()[key] = value


for _file in FILES:
    _adopt(_file)


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


# Two cases of benchmark/tests were written against the manifest as it
# stood and fail on one with a thirteenth cell (PR 59; a file the
# benchmark has is a `benchmark` PR's to edit: PERF.md section 7, "Left
# by PR 59" (5)): PR 54's asserts that its cell is the manifest's last,
# and PR 58's is a case a cell of today's manifest over data that holds
# PR 57's twelve. Both run here as they are, on the manifest as it is,
# and are expected to fail where they do until that PR mends them; what
# the first held of the whole manifest is held below.
_STALE = "asserts the manifest as it stood before PR 59: a `benchmark` PR's to mend"


def _expected_to_fail_on_a_later_cell(test):
    module = sys.modules[test.__module__]
    with open(module.PARENT) as f:
        kept = json.load(f)["workloads"]
    test.pytestmark = [pytest.mark.parametrize("cell_name", [
        c if c in kept else pytest.param(c, marks=pytest.mark.xfail(
            reason=_STALE, raises=AssertionError))
        for c in _cells()]).mark]


pytest.mark.xfail(reason=_STALE, raises=AssertionError)(
    test_dots3_cell_configuration_and_metrics_as_the_manifest_has_them)  # noqa: F821
_expected_to_fail_on_a_later_cell(
    test_what_a_cell_read_at_pr57_it_reads_under_a_surviving_name)  # noqa: F821


def test_one_cell_on_four_chips_and_each_cells_own_entries_list_it_alone():
    """Over the whole manifest, as it is: one cell takes four chips, and
    an entry under a cell's own prefix lists that cell alone."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert {"dots3-longdoc", "mimo-longdoc", "nemotron3-reasoning"} <= set(_cells())
    for prefix, cell, own in (("dots3_", "dots3-longdoc", 5),
                              ("mimo_", "mimo-longdoc", 8),
                              ("nemotron3_", "nemotron3-reasoning", 5)):
        lists = [m.get("workloads") for m in man["per_layer"]
                 if m["name"].startswith(prefix)]
        assert lists == [[cell]] * own


# ---------------------------------------------------------------------
# a metric that arrived as files only (ISSUE 32): its case is here, its
# two /metrics samples are data under benchmark/tests/data
# ---------------------------------------------------------------------

@pytest.mark.parametrize("cell_name", _cells())
def test_yield_inflight_share_from_two_metrics_samples(cell_name):
    from harness import manifest, prom
    from harness.rundata import RunData, read_metric

    with open(os.path.join(BENCH, "tests", "data",
                           "yield-inflight.metrics.json")) as f:
        data = json.load(f)
    cell = manifest.load_cell(cell_name)
    metric = next(m for m in cell.per_layer if m.name == "yield_inflight_share")
    assert (metric.unit, metric.better, metric.moves) == (
        "%", "higher", "itl_p50_ms")

    def run(start, end):
        return RunData(cell=cell, hf={}, serve={}, seconds=51.0,
                       window=(0.0, 51.0), setup_seconds=0.0, records=[],
                       prom_start=prom.parse(start), prom_end=prom.parse(end))

    value, _ = read_metric(metric, run(data["start"], data["end"]))
    assert value == pytest.approx(data["expected_pct"])
    # a program from before the counters, or a window without a turn:
    # nothing to read, and nothing raised
    assert read_metric(metric, run(data["parent"], data["parent"])) == (None, 0)
    assert read_metric(metric, run(data["end"], data["end"])) == (None, 0)
