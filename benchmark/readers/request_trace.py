"""The program's per-request stage stamps (``DYN_TRACE_JSONL``), joined
to the client's records on ``X-Request-Id``. A span is named by the
stamp that closes it: ``admission`` runs from ``queued`` to admission."""

from harness.rundata import RunData, failed
from harness.stats import percentile


def _span(trace: dict, name: str):
    for s in trace.get("spans", ()):
        if s["name"] == name:
            return s
    return None


def read(run: RunData, args: dict):
    stat, xs = args["stat"], []
    for r in run.in_window:
        tr = run.request_traces.get(r["rid"])
        if tr is None or failed(r):
            continue
        if stat == "span_ms":
            s = _span(tr, args["span"])
            if s is not None:
                xs.append(s["duration_s"] * 1e3)
        elif stat == "client_ttft_minus_stamp_ms":
            # the client's TTFT from its send time, minus the time from
            # the request's first stamp (HTTP ingress) to the stamp named
            s = _span(tr, args["span"])
            if s is not None:
                stamped = s["offset_s"] + s["duration_s"]
                xs.append((r["token_times"][0] - r["send"] - stamped) * 1e3)
        else:
            raise ValueError(f"request_trace reader: unknown stat {stat!r}")
    if not xs:
        return None
    return percentile(xs, args["q"]), len(xs)
