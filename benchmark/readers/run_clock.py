"""What the harness itself timed (host clock)."""

from harness.rundata import RunData


def read(run: RunData, args: dict):
    if args["stat"] == "setup_seconds":
        return run.setup_seconds
    raise ValueError(f"run_clock reader: unknown stat {args['stat']!r}")
