"""The yardstick's arithmetic: statistics, traffic, ops/bytes, peaks,
the manifest's shape. No jax, no server."""

import functools
import json
import os
import re

import pytest

from attention_costs import per_head_kv
from harness import manifest, peaks, prom, stats, traffic
from harness.modeldir import token_id, token_text

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 90) == pytest.approx(9.0)
    assert stats.percentile([], 50) is None


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.gaps_of(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


def test_traffic_is_the_seeds_and_the_work_is_fixed():
    mix = json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", "chat.json")))
    cell = {"loop": "open", "rate": 2.5}
    a = traffic.build_plan(mix, cell, 32064, 7, 40.0)
    b = traffic.build_plan(mix, cell, 32064, 7, 40.0)
    c = traffic.build_plan(mix, cell, 32064, 8, 40.0)
    assert [r.prompt for r in a.requests] == [r.prompt for r in b.requests]
    assert [r.due_s for r in a.requests] != [r.due_s for r in c.requests]
    in_window = lambda p: [r for r in p.requests if r.due_s >= 0]  # noqa: E731
    assert len(in_window(a)) == len(in_window(c)) == 100
    tok = lambda p: sum(len(r.prompt) for r in in_window(p))  # noqa: E731
    # stratified draws: two seeds carry nearly the same token totals
    assert abs(tok(a) - tok(c)) / tok(a) < 0.25
    assert all(16 <= len(r.prompt) <= 2048 and 16 <= r.max_tokens <= 512
               for r in a.requests)


def test_sessions_share_their_prefix():
    mix = json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", "docqa.json")))
    plan = traffic.build_plan(mix, {"loop": "open", "rate": 3.0}, 32064, 1, 30.0)
    groups = {}
    for r in plan.requests:
        groups.setdefault(r.group, []).append(r)
    assert all(len(g) == 3 for g in groups.values())
    for g in groups.values():
        n = g[0].prefix_tokens
        assert 1024 <= n <= 3072
        assert g[0].prompt[:n] == g[1].prompt[:n] == g[2].prompt[:n]
        assert g[0].prompt[n:] != g[1].prompt[n:]
        assert g[1].due_s - g[0].due_s == pytest.approx(1.5)


def test_unknown_traffic_kind_is_an_error():
    for kind in ("nope", "../harness/traffic", None):
        with pytest.raises(ValueError, match="not registered"):
            traffic.build_plan({"kind": kind}, {}, 10, 0, 1.0)


def test_bursty_arrivals_keep_the_count_and_the_interval():
    import random
    import statistics

    def cv(spec):
        at = traffic.fixed_count_arrivals(4.0, -5.0, 95.0, random.Random(3), spec)
        assert len(at) == 400 and at == sorted(at) and -5.0 <= at[0] and at[-1] < 95.0
        gaps = [b - a for a, b in zip(at, at[1:])]
        return statistics.pstdev(gaps) / statistics.mean(gaps)

    assert cv(None) == cv({"process": "poisson"}) == pytest.approx(1.0, abs=0.15)
    assert cv({"process": "gamma", "cv": 2.5}) > 1.8
    with pytest.raises(ValueError, match="arrival process"):
        cv({"process": "nope"})


def test_a_distribution_can_be_a_mixture():
    import random

    chat = {"dist": "lognormal", "median": 220, "sigma": 0.9, "min": 16, "max": 2048}
    mixed = {"dist": "mixture", "parts": [
        {"weight": 9, **chat}, {"weight": 1, "dist": "loguniform", "min": 3000, "max": 4000}]}
    xs = traffic.stratified_lengths(mixed, 200, random.Random(1))
    assert sum(x >= 3000 for x in xs) == 20 and all(16 <= x <= 4000 for x in xs)


def test_ops_and_bytes_know_window_and_lane_padding():
    # head 96 is stored in rows of 128 lanes, and a row is read whole
    assert per_head_kv.lane_padded(96) == 128 and per_head_kv.lane_padded(128) == 128
    one = per_head_kv.decode_attention_bytes([1000], 32, 96, 32)
    assert one == 2 * 1000 * 32 * 128 * 2 * 32          # 0.5 MiB a token
    assert per_head_kv.decode_attention_bytes([5000], 32, 96, 32, window=2047) \
        == 2 * 2047 * 32 * 128 * 2 * 32
    full = per_head_kv.prefill_attention_flops([(0, 4)], 2, 8, 1)
    assert full == 4 * (1 + 2 + 3 + 4) * 2 * 8
    assert per_head_kv.prefill_attention_flops([(0, 4)], 2, 8, 1, window=2) \
        == 4 * (1 + 2 + 2 + 2) * 2 * 8
    # a cached prefix is attended to, not recomputed
    assert per_head_kv.prefill_attention_flops([(2, 2)], 2, 8, 1) \
        == 4 * (3 + 4) * 2 * 8


MHA_96_WINDOW = {"num_attention_heads": 32, "num_key_value_heads": 32,
                 "hidden_size": 3072, "num_hidden_layers": 32,
                 "sliding_window": 2047}
GQA_128 = {"num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
           "hidden_size": 4096, "num_hidden_layers": 32, "sliding_window": None}


@pytest.mark.parametrize("hf, tp, itemsize, contexts, want", [
    # the whole shape comes from the published keys: head 3072 / 32 = 96 -> 128
    (MHA_96_WINDOW, 1, 2, [1000], 2 * 1000 * 32 * 128 * 2 * 32),
    # each sequence is cut to the window on its own; no sequence, no bytes
    (MHA_96_WINDOW, 1, 2, [5000, 100], 2 * (2047 + 100) * 32 * 128 * 2 * 32),
    (MHA_96_WINDOW, 1, 2, [], 0),
    # a device holds its share of the kv heads, and never less than one
    (GQA_128, 4, 2, [1000, 24], 2 * 1024 * 2 * 128 * 2 * 32),
    (GQA_128, 16, 2, [1000], 2 * 1000 * 1 * 128 * 2 * 32),
    # an 8-bit cache is half the bytes
    (GQA_128, 4, 1, [1000], 2 * 1000 * 2 * 128 * 1 * 32),
])
def test_the_per_head_cost_module_reads_k_and_v_of_a_devices_heads(
        hf, tp, itemsize, contexts, want):
    assert per_head_kv.decode_step_bytes(hf, tp, itemsize, contexts) == want


@pytest.mark.parametrize("hf, tp, chunks, want", [
    (MHA_96_WINDOW, 1, [(0, 4)], 4 * (1 + 2 + 3 + 4) * 32 * 96 * 32),
    # the window bounds the keys a query multiplies
    (MHA_96_WINDOW, 1, [(3000, 2)], 4 * (2047 + 2047) * 32 * 96 * 32),
    # a device's share of the query heads; a cached prefix is attended, not recomputed
    (GQA_128, 4, [(2, 2), (0, 1)], 4 * (3 + 4 + 1) * 8 * 128 * 32),
    (GQA_128, 4, [], 0),
])
def test_the_per_head_cost_module_multiplies_a_devices_heads(hf, tp, chunks, want):
    assert per_head_kv.prefill_flops(hf, tp, chunks) == want


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_prometheus_text():
    text = ('# HELP x\nfoo_total{phase="decode",program="a"} 3\n'
            'foo_total{phase="prefill",program="a"} 4\nbar 1.5\n')
    s = prom.parse(text)
    assert prom.value(s, "foo_total", {"phase": "decode"}) == 3
    assert prom.value(s, "foo_total") == 7
    assert prom.value(s, "absent") is None
    assert prom.delta({}, s, "bar") == 1.5


def test_token_text_round_trip():
    assert token_id(" " + token_text(31999)) == 31999
    with pytest.raises(ValueError):
        token_id("hello")


def test_manifest_names_files_and_keeps_code_free_of_names():
    man = manifest.load_manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    names = set(cells) | {c["name"] for c in man["configs"]} | e2e
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])       # every file is there
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in man["per_layer"]:
        names.add(m["name"])
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            moved = next(x for x in man["end_to_end"] if x["name"] == m["moves"])
            assert c in moved.get("workloads", cells), (m["name"], c)
        # a metric that tells programs apart by a kernel inside them has
        # something to read only where that kernel runs: it lists its
        # cells, so that a configuration with other kernels can come
        spec = json.load(open(os.path.join(
            manifest.BENCH_DIR, "layer_metrics", m["name"] + ".json")))
        assert "with_op" not in spec.get("args", {}) or "workloads" in m, m["name"]
    for n in names | {w["traffic"] for w in man["workloads"]}:
        assert NAME.match(n), n
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    # one file per metric, cell and configuration. Every per-layer file
    # has its entry (PR 58 emptied the shelf of files without one); an
    # end-to-end file without an entry is a time to first token, judged
    # by nothing until a cell's runs repeat it closely enough
    b = manifest.BENCH_DIR
    listed = lambda d: {f[:-5] for f in os.listdir(os.path.join(b, d))}  # noqa: E731
    assert listed("layer_metrics") == {m["name"] for m in man["per_layer"]}
    assert listed("end_to_end") >= e2e and listed("cells") == cells
    names |= listed("layer_metrics") | listed("end_to_end")
    assert listed("configs") == {c["name"] for c in man["configs"]}
    assert listed("traffic") >= {w["traffic"] for w in man["workloads"]}
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, len(cells) // 4)
    # every configuration names a module that is there for each
    # architecture-specific part
    modules = set()
    for key, d in manifest.ARCHITECTURE_MODULES.items():
        there = manifest.module_names(d)
        assert there and all(NAME.match(n) for n in there)
        modules |= set(there)
        for c in man["configs"]:
            assert json.load(open(os.path.join(manifest.ROOT, c["file"])))[key] in there
    # the harness is driven by data: no cell, configuration or metric is
    # named in the code, and no architecture's module in the code that
    # finds it by name
    def python_files(*dirs):
        return [os.path.join(b, d, f) for d in dirs
                for f in os.listdir(os.path.join(b, d)) if f.endswith(".py")]

    finders = [os.path.join(b, "run.py"),
               *python_files("harness", "readers", "generators")]
    for path in finders + python_files(*manifest.ARCHITECTURE_MODULES.values()):
        text = open(path).read()
        for n in names | (modules if path in finders else set()):
            assert not re.search(r"(?<![A-Za-z0-9_])" + re.escape(n) + r"(?![A-Za-z0-9_])",
                                 text), (n, path)


@pytest.mark.parametrize("key", sorted(manifest.ARCHITECTURE_MODULES))
@pytest.mark.parametrize("given", ["missing", "unknown", "a_path"])
def test_a_configuration_has_to_name_its_architectures_modules(tmp_path, key, given):
    """No default: a configuration whose file does not name a module
    that is there, for its reference or for its attention's cost, is
    refused with the key and the names that exist."""
    man = manifest.load_manifest()
    w = man["workloads"][0]
    c = next(c for c in man["configs"] if c["name"] == w["config"])
    config = json.load(open(os.path.join(manifest.ROOT, c["file"])))
    if given == "missing":
        del config[key]
    else:
        config[key] = {"unknown": "no_such_module", "a_path": "../harness/stats"}[given]
    c["file"] = "config.json"
    json.dump(config, open(tmp_path / "config.json", "w"))
    json.dump(man, open(tmp_path / "BENCHMARK.json", "w"))
    there = manifest.module_names(manifest.ARCHITECTURE_MODULES[key])
    with pytest.raises(manifest.ManifestError) as e:
        manifest.load_cell(w["name"], root=str(tmp_path))
    assert repr(key) in str(e.value) and all(n in str(e.value) for n in there)
    assert w["config"] in str(e.value)
    # and with the name in place the same files load
    config[key] = there[0]
    json.dump(config, open(tmp_path / "config.json", "w"))
    assert manifest.load_cell(w["name"], root=str(tmp_path)).config[key] == there[0]


def test_a_per_layer_metric_listed_where_its_target_is_not_reported_is_refused(tmp_path):
    man = manifest.load_manifest()
    first, other = (w["name"] for w in man["workloads"][:2])
    moved = next(m for m in man["end_to_end"] if m["name"] != "setup_s")
    moved["workloads"] = [other]          # the first cell no longer reports it
    json.dump(man, open(tmp_path / "BENCHMARK.json", "w"))
    with pytest.raises(manifest.ManifestError, match="does not report"):
        manifest.load_cell(first, root=str(tmp_path))


# ---- PR 58: one entry a reading, and room for the next architecture ----

PER_LAYER_LIMIT = 128       # the most entries a PR may hand in
OWN_ENTRIES_A_PR = 12       # what a model_config or tracing PR may add
PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "per-layer-pr57.json")
# retired by PR 58: under 1 % of set-up (0.0 of the hops) in every cell
# on the ledger's PR 57 lines
RETIRED = {"setup_device_init_s", "setup_runner_s", "setup_kv_cache_s",
           "setup_model_card_s", "warmup_compile_s", "setup_before_program_s",
           "warmup_wait_s", "sync_hop_busy_share"}
# a merged entry whose reader or args are not the surviving one's: what
# it read then -> what reads it now. Both read the same number from the
# same capture (test_scope_ops.py, test_sync_parts.py, test_host_spans.py).
DECODE = {"program": "^jit_decode_"}
KEPT = {"stat": "counter_ratio_pct",
        "numerator": "dynamo_sparse_attention_kept_tokens_total",
        "denominator": "dynamo_sparse_attention_context_tokens_total"}
BY_NAME = ("moe_scopes", dict(DECODE, stat="program_ms_per_execution"))
MERGED_READERS = [
    (("device_trace", {"stat": "program_ms_per_execution",
                       "with_op": "paged_decode_attention"}), BY_NAME),
    (("block_scopes", dict(DECODE, stat="program_ms_per_execution")), BY_NAME),
    (("block_scopes", dict(DECODE, stat="scope_ms_per_execution",
                           scopes=["moe_experts"])),
     ("moe_scopes", dict(DECODE, stat="scope_ms_per_execution",
                         scope="moe_experts"))),
    (("kimi_scopes", dict(DECODE, stat="scope_ms_per_execution",
                          scopes=["attn"])),
     ("scope_ops", dict(DECODE, stat="scope_ms_per_execution", scope="attn"))),
    (("dots3_scopes", dict(DECODE, stat="scope_share_of_program_pct",
                           scopes=["attn_full", "attn_window"])),
     ("window_scopes", dict(DECODE, stat="scope_share_of_program_pct",
                            scopes=["attn_window", "attn_full"]))),
    (("moe_scopes", KEPT), ("sala_scopes", KEPT)),
]


def _reading(reader, args):
    return reader, json.dumps(args, sort_keys=True)


SAME_READING = {_reading(*old): _reading(*new) for old, new in MERGED_READERS}


def _entries():
    """The manifest's per-layer entries, each with its file's reading."""
    out = []
    for m in manifest.load_manifest()["per_layer"]:
        spec = json.load(open(os.path.join(
            manifest.BENCH_DIR, "layer_metrics", m["name"] + ".json")))
        out.append((m, _reading(spec["reader"], spec.get("args", {}))))
    return out


def _cells_of(entry, cells):
    return entry.get("workloads", cells)


def test_the_manifest_has_room_and_reads_nothing_under_two_names(capsys):
    """128 entries is the most a PR may hand in (PR 54's first hand-in
    listed 138 and was refused before any run). The manifest filled up
    because a reading scoped to one cell got a twin for the next cell; so
    no two entries may make the same reading (reader and args) in a cell
    both list, and the room left is printed where the next builder sees
    it: a model_config or tracing PR adds entries only for the scopes and
    counters that are new with it, under its own prefix, twelve at most
    (PERF.md section 3), and the next benchmark PR appends its cell to
    the general entries' lists."""
    man = manifest.load_manifest()
    cells = [w["name"] for w in man["workloads"]]
    entries = _entries()
    assert len(entries) <= PER_LAYER_LIMIT
    by_reading = {}
    for m, reading in entries:
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        # a list is the manifest's cells in the manifest's order, each once
        assert _cells_of(m, cells) == [c for c in cells if c in _cells_of(m, cells)]
        for other in by_reading.setdefault(reading, []):
            both = set(_cells_of(m, cells)) & set(_cells_of(other, cells))
            assert not both, (m["name"], other["name"], sorted(both))
        by_reading[reading].append(m)
    room = PER_LAYER_LIMIT - len(entries)
    with capsys.disabled():
        print(f"\nBENCHMARK.json: {len(entries)} per-layer entries, room for "
              f"{room} more ({room // OWN_ENTRIES_A_PR} PRs of "
              f"{OWN_ENTRIES_A_PR})")


@pytest.mark.parametrize("cell_name", [w["name"] for w in
                                       manifest.load_manifest()["workloads"]])
def test_what_a_cell_read_at_pr57_it_reads_under_a_surviving_name(cell_name):
    """Over the parent's manifest, kept as data with each entry's reader
    and args: every reading the cell made then it makes now, under one
    name, but for the retired entries and for the sync tail, which names
    the program it waits for since PR 58 (its ``program`` argument; the
    reading with a step in flight is test_sync_parts.py's)."""
    parent = json.load(open(PARENT))
    assert cell_name in parent["workloads"] and len(parent["per_layer"]) == 128
    now = {}
    for m in manifest.load_cell(cell_name).per_layer:
        reading = _reading(m.reader, m.args)
        assert reading not in now, (m.name, now[reading])
        now[reading] = m.name
    seen = set()
    for e in parent["per_layer"]:
        if cell_name not in _cells_of(e, parent["workloads"]):
            continue
        if e["name"] in RETIRED:
            assert e["name"] not in now.values()
            continue
        reading = _reading(e["reader"], e["args"])
        if reading not in now and "span" in e["args"]:
            reading = _reading(e["reader"], dict(e["args"], **DECODE))
        reading = SAME_READING.get(reading, reading)
        assert reading in now, (e["name"], reading)
        seen.add(now[reading])
    # and under as many names as it made readings
    assert len(seen) == len({e["name"] for e in parent["per_layer"]
                             if cell_name in _cells_of(e, parent["workloads"])
                             and e["name"] not in RETIRED})


def _listed():
    man = manifest.load_manifest()
    cells = [w["name"] for w in man["workloads"]]
    return [pytest.param(m, c, id=f"{m['name']}-{c}")
            for m in man["per_layer"] if "workloads" in m
            for c in _cells_of(m, cells)]


@pytest.mark.parametrize("entry, cell_name", _listed())
def test_a_listed_entry_is_loaded_in_each_cell_of_its_list(entry, cell_name):
    """One case an (entry, cell of its list): the cell loads and holds the
    entry with its unit, direction and the end-to-end metric it moves.
    (The cases of the entries PR 58 merged away are here under the
    surviving names.)"""
    found = [m for m in _load_cell(cell_name).per_layer if m.name == entry["name"]]
    assert len(found) == 1
    m = found[0]
    assert (m.unit, m.better, m.moves) == (
        entry["unit"], entry["better"], entry["moves"])


READINGS = os.path.join(os.path.dirname(PARENT), "per-layer-readings.json")


@pytest.mark.parametrize("cell_name", sorted(json.load(open(READINGS))))
def test_every_entry_a_cell_lists_has_a_reading_from_the_chip(cell_name):
    """A listed entry that a cell cannot read is ``null`` on the ledger
    and blocks the next benchmark PR, so a cell goes onto a list only
    once a traced run of it on the v5e printed a value. The file holds
    one such value for every entry and every cell that lists it, with
    where it came from (the plain command's result line, or
    ``benchmark/reread.py`` over a kept run); no time reads under zero.
    An entry a later PR brings (in no cell's readings) is that PR's to
    show."""
    readings = json.load(open(READINGS))
    known = {name for cell in readings.values() for name in cell["metrics"]}
    got = readings[cell_name]["metrics"]
    assert readings[cell_name]["origin"]
    listed = [m for m in _load_cell(cell_name).per_layer if m.name in known]
    assert len(listed) >= 40
    for m in listed:
        assert m.name in got, m.name
        if m.unit in ("ms", "s") and m.name != "setup_unnamed_s":
            assert got[m.name] >= 0, (m.name, got[m.name])
    assert abs(got["setup_unnamed_s"]) < 1e-4      # set-up's parts add up


_load_cell = functools.lru_cache(maxsize=None)(manifest.load_cell)
