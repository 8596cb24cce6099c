"""Set-up as the harness and the program see it together (ISSUE 50).

The judged set-up time is the harness's: ``run.py``'s first line
(``T_START``) to the window's start. The program's own timeline
(``dynamo_tpu/telemetry/flight.StartupTimeline``) starts later, at the
package's import, and ends earlier, when the HTTP service listens; both
are on ``time.monotonic()``, which one machine's processes share, and
``dynamo_engine_startup_mark_monotonic_seconds{mark}`` gives the
program's two ends. So set-up is

- *before the program*: ``T_START`` to the mark ``import``: the
  harness's own imports and its manifest;
- the program's phases (``dynamo_engine_startup_seconds{phase}``), each
  the time from the mark before it;
- *probes and ramp*: the mark ``listening`` to the window's start: the
  harness's correctness probes and the mix's ramp, which is traffic and
  which no change to start-up shortens;

and ``unnamed_s`` is what is left of set-up after all three: the check
on the partition. ``T_START`` on the monotonic clock is
``run.window[0] - run.setup_seconds``. A program without the marks (a
parent commit) gives every stat here nothing to read.
"""

from __future__ import annotations

from harness import prom
from harness.rundata import RunData

MARK = "dynamo_engine_startup_mark_monotonic_seconds"
PHASE = "dynamo_engine_startup_seconds"


def read(run: RunData, args: dict):
    imported = prom.value(run.prom_end, MARK, {"mark": "import"})
    listening = prom.value(run.prom_end, MARK, {"mark": "listening"})
    if imported is None or listening is None:
        return None
    before = imported - (run.window[0] - run.setup_seconds)
    after = run.window[0] - listening
    stat = args["stat"]
    if stat == "probes_ramp_s":
        return after
    if stat == "unnamed_s":
        # a phase the scrape lacks counts as 0 and so shows here
        named = sum(prom.value(run.prom_end, PHASE, {"phase": p}) or 0.0
                    for p in args["phases"])
        return run.setup_seconds - before - named - after
    raise ValueError(f"startup_parts reader: unknown stat {stat!r}")
