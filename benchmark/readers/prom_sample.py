"""A gauge of the program's /metrics: at the window's end, or over the
once-a-second samples a traced run takes."""

from harness import prom
from harness.rundata import RunData


def read(run: RunData, args: dict):
    at = args.get("at", "end")
    scale = args.get("scale", 1.0)
    if at == "end":
        v = prom.value(run.prom_end, args["metric"], args.get("labels"))
        return None if v is None else v * scale
    vals = [prom.value(s, args["metric"], args.get("labels"))
            for _, s in run.prom_samples]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    if at == "mean":
        return scale * sum(vals) / len(vals), len(vals)
    if at == "max":
        return scale * max(vals), len(vals)
    raise ValueError(f"prom_sample reader: unknown at {at!r}")
