"""What every traffic generator is made of. No jax: the load generator imports it.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``). Its
``kind`` names a module of ``benchmark/generators`` (found by that name,
as a reader is); everything else in the file is that generator's
parameters. A later PR adds a mix as a new data file for a kind that
exists, or a kind as a new module; it edits nothing here. So that a mix
is data wherever it can be, the pieces here are general: a length
distribution can be a mixture (of log-uniform pieces between a public
trace's percentiles, say), and arrivals are Poisson or gamma (bursty)
by a parameter.

Everything is drawn from the seed, and the *amount* of work is fixed:

- arrivals are conditioned on their count: exactly
  ``round(rate * duration)`` of them. ``poisson`` (the default) places
  them uniformly at random, so gaps between neighbours are exponential
  in the limit: bursts and lulls are there, but two seeds offer the same
  load. ``gamma`` draws gaps with the coefficient of variation ``cv``
  and scales them to the duration (``cv`` 1 is ``poisson`` in law);
- lengths are stratified draws: request i of n takes the quantile
  ``(i + u_i) / n`` of its distribution and the draws are then shuffled,
  so every seed sees the whole distribution, tail included, and two
  seeds carry nearly the same token totals. The strata are laid over
  what a window really sends: the ramp's and the window's arrivals are
  drawn apart, and a closed loop's sequence is drawn in blocks of one
  request per caller, so that any stretch of it is a fair sample.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import random
import re
from statistics import NormalDist
from typing import List, Optional

# prompts keep clear of the ids a tokenizer reserves (unk, bos, eos)
FIRST_PLAIN_ID = 8
KIND = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


@dataclasses.dataclass
class Request:
    rid: str
    due_s: Optional[float]     # open loop: seconds from window start (<0: ramp)
    prompt: List[int]
    max_tokens: int
    seed: int
    group: str = ""            # e.g. the document a question belongs to
    prefix_tokens: int = 0     # leading tokens shared with the group's other requests


@dataclasses.dataclass
class Plan:
    loop: str                  # "open" | "closed"
    requests: List[Request]    # open: by due time; closed: the order they are sent in
    clients: int = 0           # closed loop only


def _quantile(spec: dict, q: float) -> float:
    """The length at quantile ``q`` of the distribution ``spec``."""
    kind = spec["dist"]
    if kind == "mixture":
        # {"parts": [{"weight": w, ...a distribution}, ...]}: the strata
        # are split between the parts by weight, and kept within a part
        parts = spec["parts"]
        total = sum(p["weight"] for p in parts)
        acc = 0.0
        for p in parts:
            w = p["weight"] / total
            if q < acc + w or p is parts[-1]:
                return _quantile(p, min(max((q - acc) / w, 0.0), 1.0))
            acc += w
    lo, hi = spec["min"], spec["max"]
    if kind == "lognormal":
        x = math.exp(math.log(spec["median"])
                     + spec["sigma"] * NormalDist().inv_cdf(min(max(q, 1e-9), 1 - 1e-9)))
    elif kind == "loguniform":
        x = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "fixed":
        x = spec["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(x, lo), hi)


def stratified_lengths(spec: dict, n: int, rng: random.Random) -> List[int]:
    out = [int(round(_quantile(spec, (i + rng.random()) / n))) for i in range(n)]
    rng.shuffle(out)
    return out


def stratified_in_blocks(spec: dict, blocks: List[int],
                         rng: random.Random) -> List[int]:
    """Stratified draws block by block: ``blocks`` are the block sizes."""
    return [x for n in blocks if n for x in stratified_lengths(spec, n, rng)]


def fixed_count_arrivals(rate: float, t0: float, t1: float, rng: random.Random,
                         arrivals: Optional[dict] = None) -> List[float]:
    """``round(rate * (t1 - t0))`` arrival times in [t0, t1); ``arrivals``
    is the mix's group of that name (module docstring)."""
    n = int(round(rate * (t1 - t0)))
    process = (arrivals or {}).get("process", "poisson")
    if process == "poisson":
        return sorted(t0 + rng.random() * (t1 - t0) for _ in range(n))
    if process == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        gaps = [rng.gammavariate(shape, 1.0) for _ in range(n + 1)]
        total, at, out = sum(gaps), 0.0, []
        for g in gaps[:n]:
            at += g
            out.append(t0 + (t1 - t0) * at / total)
        return out
    raise ValueError(f"unknown arrival process {process!r}")


def tokens(n: int, vocab: int, rng: random.Random) -> List[int]:
    return rng.choices(range(FIRST_PLAIN_ID, vocab), k=n)


def ramp_and_window(mix: dict, rate: float, seconds: float, rng: random.Random):
    """Ramp and window are filled separately so the window's count is
    fixed whatever the ramp's length. Returns the due times and the
    two counts (the blocks lengths are stratified over)."""
    ramp = fixed_count_arrivals(rate, -mix["ramp_s"], 0.0, rng, mix.get("arrivals"))
    window = fixed_count_arrivals(rate, 0.0, seconds, rng, mix.get("arrivals"))
    return ramp + window, [len(ramp), len(window)]


def build_plan(mix: dict, cell: dict, vocab: int, seed: int,
               seconds: float) -> Plan:
    """The plan the mix's ``kind`` builds: ``generators/<kind>.py`` has
    ``build(mix, cell, vocab, seed, seconds) -> Plan``."""
    kind = str(mix.get("kind"))
    try:
        if not KIND.match(kind):
            raise ModuleNotFoundError(kind)
        module = importlib.import_module(f"generators.{kind}")
    except ModuleNotFoundError:
        raise ValueError(f"traffic kind {kind!r} is not registered: there is "
                         f"no benchmark/generators/{kind}.py")
    return module.build(mix, cell, vocab, seed, seconds)


def probe_prompts(lengths: List[int], vocab: int, seed: int) -> List[List[int]]:
    rng = random.Random(f"probes:{seed}")
    return [tokens(n, vocab, rng) for n in lengths]
