"""Prometheus metric-name convention checks (shared core).

Every instrument registered anywhere in ``dynamo_tpu/`` must be named
``dynamo_<component>_<name>_<unit>`` (telemetry/registry.py module
docstring): lowercase snake_case, a component segment after the prefix,
and a recognized unit suffix. Counters additionally end in ``_total``;
histograms measure something, so they end in a base unit (seconds,
bytes, tokens), never ``_total``/``_ratio``.

The check is static (AST walk over instrument-registration call sites)
so drift is caught without importing — or starting — any component.
Dynamic-name escape hatches (``register_callback_gauges`` dict
prefixes) are exempt by design.

This module is both the engine behind the dynlint ``metric-name`` rule
(rules/metric_name.py) and the implementation ``scripts/
check_metric_names.py`` shims over; the directory-walk helpers keep
that script's historical CLI/exit-code contract.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Iterator, List, NamedTuple, Optional

PREFIX = "dynamo_"

# the unit vocabulary: extend deliberately, not ad hoc
# ("depth" added for structural stage-count gauges — the decode
# pipeline's dispatch depth; same count family as slots/blocks.
# "replicas" added with the SLA planner's replica-target gauge — worker
# pool size is a first-class count unit in the deployment plane.
# "length" added with the persistent decode loop's burst-chain gauge —
# dispatches between host barriers; a structural count like depth, and
# the Grafana panel derives p50/p99 via quantile_over_time.
# "fraction" added with the live roofline gauge: unlike "ratio" (a
# part-of-whole share of counted things), a fraction names achieved-
# over-bound against a PHYSICAL limit — dynamo_engine_roofline_fraction
# is achieved HBM bytes/s over the chip's peak)
UNIT_SUFFIXES = (
    "total", "seconds", "bytes", "tokens", "blocks",
    "requests", "slots", "ratio", "info", "depth", "replicas", "length",
    "fraction",
    # "channels" admitted deliberately with the unified transfer plane's
    # live-channel gauge (dynamo_transfer_channels): a count of open
    # plane connections per {plane,backend} pair — "requests" would
    # misread channels as workload volume
    "channels",
)
# what a histogram may measure. "length" admitted deliberately with the
# speculative acceptance-length histogram (dynamo_engine_spec_accept_
# length): a per-round accepted-token count is a measured quantity like
# tokens, but "tokens" would misread as throughput volume — the length
# distribution (p50/p99 via quantile_over_time) is the signal.
BASE_UNITS = ("seconds", "bytes", "tokens", "length")

# registration call sites: registry/metrics-module methods and the raw
# instrument constructors
METHOD_KINDS = {
    "counter": "counter",
    "gauge": "gauge",
    "histogram": "histogram",
    "callback_gauge": "gauge",
}
CONSTRUCTOR_KINDS = {
    "Counter": "counter",
    "Gauge": "gauge",
    "Histogram": "histogram",
    "CallbackGauge": "gauge",
}


class RegisteredMetric(NamedTuple):
    name: str
    kind: str  # counter | gauge | histogram
    file: str
    line: int


def _literal_name(node: ast.AST) -> Optional[str]:
    """First-argument expression → metric name, or None if unknowable.

    Plain string literals pass through; f-strings substitute ``dynamo``
    for interpolated prefixes (the ``f"{prefix}_..."`` idiom) so the
    constant tail is still checked.
    """
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for piece in node.values:
            if isinstance(piece, ast.Constant) and isinstance(piece.value, str):
                parts.append(piece.value)
            elif isinstance(piece, ast.FormattedValue) and not parts:
                parts.append("dynamo")  # leading {prefix}
            else:
                return None  # interpolation mid-name: not statically checkable
        return "".join(parts)
    return None


def iter_tree_metrics(tree: ast.AST, rel: str) -> Iterator[RegisteredMetric]:
    """Registration call sites in one parsed module."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        func = node.func
        kind = None
        if isinstance(func, ast.Attribute):
            kind = METHOD_KINDS.get(func.attr)
        elif isinstance(func, ast.Name):
            kind = CONSTRUCTOR_KINDS.get(func.id)
        if kind is None:
            continue
        name = _literal_name(node.args[0])
        if name is None or not name.startswith(PREFIX):
            # dynamic names and non-metric first args (e.g. an
            # unrelated .histogram() API) are out of scope
            continue
        yield RegisteredMetric(name, kind, rel, node.lineno)


def iter_registered_metrics(root: str) -> Iterator[RegisteredMetric]:
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:
                    continue  # other lint's problem
            rel = os.path.relpath(path, os.path.dirname(root))
            yield from iter_tree_metrics(tree, rel)


def check_name(metric: RegisteredMetric) -> List[str]:
    """One metric → list of human-readable violations (empty = clean)."""
    problems = []
    name, kind = metric.name, metric.kind
    if name != name.lower() or not all(
        c.isascii() and (c.isalnum() or c == "_") for c in name
    ):
        problems.append("must be lowercase snake_case ([a-z0-9_])")
    parts = name.split("_")
    if len(parts) < 3:
        problems.append(
            "needs at least dynamo_<component>_<name>_<unit> segments")
    # the unit is the LAST underscore-delimited segment — a plain
    # endswith would wave through "subtotal"/"kilobytes" tails
    unit = parts[-1]
    if unit not in UNIT_SUFFIXES:
        problems.append(
            f"must end in a unit suffix {UNIT_SUFFIXES}")
    if kind == "counter" and not name.endswith("_total"):
        problems.append("counters must end in _total")
    if kind != "counter" and name.endswith("_total"):
        problems.append("_total names a counter; this is a " + kind)
    if kind == "histogram" and unit not in BASE_UNITS:
        problems.append(
            f"histograms must measure a base unit {BASE_UNITS}")
    return problems


def run_check(root: str) -> List[str]:
    """Lint every registration under ``root`` → list of violation lines."""
    violations = []
    for metric in iter_registered_metrics(root):
        for problem in check_name(metric):
            violations.append(
                f"{metric.file}:{metric.line}: {metric.name}: {problem}")
    return violations


def main(argv: List[str]) -> int:
    root = argv[1] if len(argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "dynamo_tpu",
    )
    violations = run_check(root)
    for line in violations:
        print(line)
    if violations:
        print(f"{len(violations)} metric-name violation(s)")
        return 1
    count = sum(1 for _ in iter_registered_metrics(root))
    print(f"{count} registered metric names conform to "
          f"{PREFIX}<component>_<name>_<unit>")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
