"""Set-up in its parts (ISSUE 50): ``readers/startup_parts.py`` and the
``prom_sample`` files over a canned ``/metrics`` text and a hand-made
``RunData``, then one CPU rehearsal that has to report all ten with the
partition closed. PR 50 brought seventeen; PR 58 retired the seven that
read under 1 % of set-up in every cell on the ledger's PR 57 lines (the
card, the mesh, the pool, the runner's Python, the harness's imports,
warm-up's compile part warm and its wait): what they held is still in
the scrape, inside ``dynamo_engine_startup_seconds`` and the compile
parts, and the unnamed remainder still closes over all of it."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest, prom
from harness.manifest import Cell
from harness.rundata import RunData, read_metric

ROOT = manifest.ROOT

# a start of 60 s: the harness began at 1000.0, the package was imported
# at 1002.5, the service listened at 1040.0 and the window opened at
# 1060.0. The phases after ``import`` sum to 37.5 s less 0.25 s that no
# phase holds (a mark left out), which is what unnamed_s must read.
PHASES = {"backend": 6.0, "model_card": 1.5, "device_init": 0.25,
          "weights": 4.0, "kv_cache": 0.5, "runner": 0.75, "engine": 0.05,
          "warmup": 23.0, "scheduler": 0.2, "listening": 1.0}
UNNAMED = 37.5 - sum(PHASES.values())
# warm-up's 23 s: two decode programs and one prefill program in their
# parts, and the wait
PROGRAMS = {
    "decode": {"trace": 4.0, "lower": 3.0, "load": 2.5, "compile": 0.0,
               "rest": 0.5},
    "prefill": {"trace": 5.0, "lower": 4.0, "load": 1.5, "compile": 0.25,
                "rest": 0.25},
}
# weight init's helper jits: outside any first dispatch, and apart
UNTRACKED = {"trace": 0.5, "lower": 0.25, "load": 0.0, "compile": 2.0}
WAIT = 2.0
WANT = {
    "setup_backend_s": 6.0, "setup_serve_s": 1.2,
    # over the programs
    "warmup_trace_s": 9.0, "warmup_lower_s": 7.0,
    "warmup_cache_load_s": 4.0,
    "warmup_rest_s": 0.75, "warmup_cache_misses": 1.0,
    "warmup_programs": 3.0,
    "setup_probes_ramp_s": 20.0,
    "setup_unnamed_s": UNNAMED,
}
RETIRED = ("setup_model_card_s", "setup_device_init_s", "setup_kv_cache_s",
           "setup_runner_s", "warmup_wait_s", "warmup_compile_s",
           "setup_before_program_s")
BEFORE_PROGRAM = 2.5        # the harness began at 1000.0, the import at 1002.5
COMPILE = 0.25              # the prefill program's compile part


def _text(marks=True, phases=PHASES):
    lines = []
    if marks:
        lines += [
            'dynamo_engine_startup_mark_monotonic_seconds{mark="import"} 1002.5',
            'dynamo_engine_startup_mark_monotonic_seconds{mark="listening"} 1040.0',
            'dynamo_engine_startup_mark_monotonic_seconds{mark="warmup"} 1038.8',
        ]
    gauge = dict(phases, warmup_wait=WAIT, serve=1.2)
    lines += ['dynamo_engine_startup_seconds{phase="%s"} %r' % kv
              for kv in gauge.items()]
    for program, parts in PROGRAMS.items():
        lines += ['dynamo_engine_xla_compile_part_seconds_total{part="%s",'
                  'phase="startup",program="%s"} %r' % (part, program, s)
                  for part, s in parts.items()]
    lines += ['dynamo_engine_xla_compile_part_seconds_total{part="%s",'
              'phase="startup_untracked",program="untracked"} %r' % kv
              for kv in UNTRACKED.items()]
    lines += [
        'dynamo_engine_compile_cache_total{phase="startup_untracked",'
        'program="untracked",result="miss"} 3.0',
        # a late compile is no part of the start
        'dynamo_engine_xla_compile_part_seconds_total{part="compile",'
        'phase="late",program="decode"} 30.0',
        'dynamo_engine_compile_cache_total{phase="startup",program="decode",result="hit"} 2.0',
        'dynamo_engine_compile_cache_total{phase="startup",program="decode",result="miss"} 0.0',
        'dynamo_engine_compile_cache_total{phase="startup",program="prefill",result="hit"} 0.0',
        'dynamo_engine_compile_cache_total{phase="startup",program="prefill",result="miss"} 1.0',
        'dynamo_engine_compile_cache_total{phase="late",program="decode",result="miss"} 1.0',
        'dynamo_engine_xla_compiles_total{phase="startup",program="decode"} 2.0',
        'dynamo_engine_xla_compiles_total{phase="startup",program="prefill"} 1.0',
        'dynamo_engine_xla_compiles_total{phase="late",program="decode"} 1.0',
    ]
    return "\n".join(lines) + "\n"


def _run(text):
    cell = Cell("c", 1, {}, "k", {}, "m", {"drain_s": 1}, [], [])
    return RunData(cell=cell, hf={}, serve={}, seconds=51.0,
                   window=(1060.0, 1111.0), setup_seconds=60.0, records=[],
                   prom_start={}, prom_end=prom.parse(text))


def _new_metrics():
    """The ten as the manifest and their files give them."""
    per_layer = {m.name: m for m in manifest.load_cell("phi3-chat").per_layer}
    return [per_layer[name] for name in WANT]


def test_the_manifest_lists_the_ten_for_every_cell():
    man = manifest.load_manifest()
    entries = {m["name"]: m for m in man["per_layer"]}
    for name in WANT:
        e = entries[name]
        assert e["moves"] == "setup_s" and e["better"] == "lower"
        assert e["layer"] == "compiled programs" and "workloads" not in e
        assert e["unit"] == ("count" if name in (
            "warmup_cache_misses", "warmup_programs") else "s")
    # in the order they came in, and none of the retired seven
    assert [m["name"] for m in man["per_layer"] if m["name"] in WANT] == list(WANT)
    assert not set(RETIRED) & set(entries)
    readers = {m.name: m.reader for m in _new_metrics()}
    assert sorted(n for n, r in readers.items() if r == "startup_parts") == [
        "setup_probes_ramp_s", "setup_unnamed_s"]
    assert all(r in ("prom_sample", "startup_parts") for r in readers.values())


@pytest.mark.parametrize("name", list(WANT))
def test_each_metric_reads_the_canned_scrape(name):
    metric = next(m for m in _new_metrics() if m.name == name)
    value, _ = read_metric(metric, _run(_text()))
    assert value == pytest.approx(WANT[name], abs=1e-9)


def test_the_parts_close_over_setup_and_over_warmup():
    got = {m.name: read_metric(m, _run(_text()))[0] for m in _new_metrics()}
    # set-up: the harness's two ends, the program's phases, the rest
    assert (BEFORE_PROGRAM + sum(PHASES.values())
            + got["setup_probes_ramp_s"] + got["setup_unnamed_s"]
            ) == pytest.approx(60.0)
    # warm-up: its first dispatches' parts and the wait (the compile part
    # and the wait have no metric of their own since PR 58)
    assert sum(got[n] for n in (
        "warmup_trace_s", "warmup_lower_s", "warmup_cache_load_s",
        "warmup_rest_s")) + COMPILE + WAIT == pytest.approx(PHASES["warmup"])


def test_a_missing_phase_shows_as_unnamed_seconds():
    text = _text(phases={k: v for k, v in PHASES.items() if k != "weights"})
    metric = next(m for m in _new_metrics() if m.name == "setup_unnamed_s")
    value, _ = read_metric(metric, _run(text))
    assert value == pytest.approx(UNNAMED + PHASES["weights"])


def test_a_program_without_the_series_leaves_all_ten_out():
    """A parent commit: the four old phases on the gauge and the compile
    counter, no marks, no parts, no cache counter."""
    text = "\n".join([
        'dynamo_engine_startup_seconds{phase="device_init"} 0.25',
        'dynamo_engine_startup_seconds{phase="weights"} 4.0',
        'dynamo_engine_startup_seconds{phase="kv_cache"} 0.5',
        'dynamo_engine_startup_seconds{phase="warmup"} 23.0',
    ]) + "\n"
    got = {m.name: read_metric(m, _run(text))[0] for m in _new_metrics()}
    assert all(v is None for v in got.values()), got


def test_a_rehearsal_reports_all_ten_with_the_partition_closed():
    e = dict(os.environ)
    e.pop("DYN_TRACE_JSONL", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "phi3-chat", "--seed", "50", "--seconds", "5",
         "--trace", "1", "--cpu-rehearsal"],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("DRY RUN {"), last[:200]
    got = {k: v["value"] for k, v in
           json.loads(last[len("DRY RUN "):])["metrics"].items()}
    assert all(got.get(name) is not None for name in WANT), got
    assert abs(got["setup_unnamed_s"]) < 1.0
    assert got["setup_probes_ramp_s"] > 0 and not set(RETIRED) & set(got)
    assert got["warmup_programs"] >= 3
    parts = sum(got[n] for n in ("warmup_trace_s", "warmup_lower_s",
                                 "warmup_cache_load_s", "warmup_rest_s"))
    assert 0 < parts <= got["setup_warmup_s"] + 0.01
