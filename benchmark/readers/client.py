"""Metrics from the load generator's own records (host clock)."""

from __future__ import annotations

from harness.rundata import RunData, failed
from harness.stats import percentile


def token_gaps(run: RunData):
    """Every gap between consecutive output tokens that lies inside the
    window, of any request, in ms. A chunk that carries k tokens counts
    as k gaps of a k-th of the time since the chunk before."""
    t0, t1 = run.window
    out = []
    for r in run.records:
        times, counts = r["token_times"], r["chunk_tokens"]
        for i in range(1, len(times)):
            if times[i - 1] >= t0 and times[i] <= t1:
                out.extend([(times[i] - times[i - 1]) * 1e3 / counts[i]] * counts[i])
    return out


def _ttft(r: dict, origin: str) -> float:
    return (r["token_times"][0] - r[origin]) * 1e3


def _mean_gap(r: dict):
    """Mean gap between one request's tokens; None with fewer than two."""
    n = sum(r["chunk_tokens"])
    if n < 2:
        return None
    return (r["token_times"][-1] - r["token_times"][0]) * 1e3 / (n - 1)


def ttfts(run: RunData, origin: str):
    return [run.fail_value_ms if failed(r) else _ttft(r, origin)
            for r in run.in_window]


def request_mean_gaps(run: RunData):
    """Per completed request of the window: mean gap between its tokens."""
    gaps = [_mean_gap(r) for r in run.in_window if not failed(r)]
    return [g for g in gaps if g is not None]


def window_output_tokens(run: RunData) -> int:
    t0, t1 = run.window
    return sum(n for r in run.records
               for t, n in zip(r["token_times"], r["chunk_tokens"]) if t0 <= t <= t1)


def window_decode_tokens(run: RunData) -> int:
    """Output tokens of the window that a decode step made: a request's
    first token comes out of its prefill step."""
    t0, t1 = run.window
    firsts = sum(1 for r in run.records
                 if r["token_times"] and t0 <= r["token_times"][0] <= t1)
    return window_output_tokens(run) - firsts


def read(run: RunData, args: dict):
    stat = args["stat"]
    if stat == "ttft_ms":
        xs = ttfts(run, args.get("from", "due"))
    elif stat == "gap_ms":
        xs = token_gaps(run)
    elif stat == "request_mean_gap_ms":
        xs = request_mean_gaps(run)
    elif stat == "late_ms":
        xs = [(r["send"] - r["due"]) * 1e3 for r in run.in_window]
    elif stat == "window_tokens_per_second":
        return window_output_tokens(run) / run.seconds, len(run.in_window)
    elif stat == "limits_met_pct":
        lim = run.cell.cell["limits"]
        rs = run.in_window
        if not rs:
            return None
        ok = sum(1 for r in rs if not failed(r)
                 and _ttft(r, "due") <= lim["ttft_ms"]
                 and (_mean_gap(r) or 0.0) <= lim["request_mean_gap_ms"])
        return 100.0 * ok / len(rs), len(rs)
    else:
        raise ValueError(f"client reader: unknown stat {stat!r}")
    if not xs:
        return None
    return percentile(xs, args["q"]), len(xs)


def summary(run: RunData) -> dict:
    """The client's statistics whatever the cell reports, for the line
    that states sample counts before the result."""
    stats = {
        "ttft p50": {"stat": "ttft_ms", "from": "due", "q": 50},
        "ttft p90": {"stat": "ttft_ms", "from": "due", "q": 90},
        "gap p50": {"stat": "gap_ms", "q": 50},
        "gap p99": {"stat": "gap_ms", "q": 99},
        "request mean gap p90": {"stat": "request_mean_gap_ms", "q": 90},
        "tokens/s": {"stat": "window_tokens_per_second"},
        "inside both limits %": {"stat": "limits_met_pct"},
    }
    return {k: read(run, a) for k, a in stats.items()}
