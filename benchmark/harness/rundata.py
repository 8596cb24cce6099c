"""What one run measured, as every reader sees it, and the registry
that finds a reader by the name in a metric's file."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, List, Optional, Tuple

from .manifest import Cell, Metric


@dataclasses.dataclass
class RunData:
    cell: Cell
    hf: Dict[str, Any]                 # the model's published keys, as run
    serve: Dict[str, Any]              # the deployment settings, as run
    seconds: float
    window: Tuple[float, float]        # time.monotonic() of the window
    setup_seconds: float
    records: List[dict]                # the load generator's, every request
    prom_start: Dict                   # /metrics at the window's two ends
    prom_end: Dict
    prom_samples: List[Tuple[float, Dict]] = dataclasses.field(default_factory=list)
    device_trace: Any = None           # harness.trace.DeviceTrace
    trace_slice: Optional[Tuple[float, float]] = None   # monotonic, of the capture
    device_kind: str = ""
    cache_itemsize: int = 2            # bytes of one element of the served cache

    @classmethod
    def from_client(cls, got: dict, **fields) -> "RunData":
        """From what the load generator wrote (harness/loadgen.py)."""
        from . import prom

        return cls(
            window=tuple(got["window"]), records=got["records"],
            prom_start=prom.parse(got["prom_start"]),
            prom_end=prom.parse(got["prom_end"]),
            prom_samples=[(s["t"], prom.parse(s["text"]))
                          for s in got["prom_samples"]],
            **fields)

    @property
    def in_window(self) -> List[dict]:
        return [r for r in self.records if r["phase"] == "window"]

    @property
    def fail_value_ms(self) -> float:
        """What a failed request's latency counts as: it ranks last."""
        return (self.seconds + self.cell.traffic["drain_s"]) * 1e3


def failed(r: dict) -> bool:
    """HTTP error, truncated stream, or open after the drain."""
    return (r["status"] != 200 or r["error"] is not None or not r["done"]
            or not r["token_times"])


def read_metric(metric: Metric, run: RunData):
    """(value, samples) from the reader the metric's file names;
    (None, 0) where the reader found nothing to read."""
    module = importlib.import_module(f"readers.{metric.reader}")
    got = module.read(run, metric.args)
    if got is None:
        return None, 0
    if isinstance(got, tuple):
        return got
    return got, 0
