"""Sessions over a shared prefix: each session's prefix (a document) is
followed by ``turns`` requests, prefix + a fresh suffix, due at a fixed
spacing whether or not the earlier answer is back. The cell's rate
counts requests, so sessions arrive at rate / turns.

Parameters: ``turns``, ``turn_spacing_s``, ``prefix_tokens``,
``suffix_tokens``, ``output_tokens`` (length distributions), ``ramp_s``,
optionally ``arrivals``."""

import random

from harness.traffic import (Plan, Request, ramp_and_window,
                             stratified_in_blocks, tokens)


def build(mix: dict, cell: dict, vocab: int, seed: int, seconds: float) -> Plan:
    if cell["loop"] != "open":
        raise ValueError("shared_prefix_sessions is an open-loop generator")
    rng = random.Random(f"shared_prefix_sessions:{seed}")
    turns, spacing = int(mix["turns"]), float(mix["turn_spacing_s"])
    starts, blocks = ramp_and_window(mix, cell["rate"] / turns, seconds, rng)
    prefixes = stratified_in_blocks(mix["prefix_tokens"], blocks, rng)
    per_turn = [b * turns for b in blocks]
    suffixes = stratified_in_blocks(mix["suffix_tokens"], per_turn, rng)
    outputs = stratified_in_blocks(mix["output_tokens"], per_turn, rng)
    reqs = []
    for d, start in enumerate(starts):
        prefix = tokens(prefixes[d], vocab, rng)
        for t in range(turns):
            k = d * turns + t
            reqs.append(Request(
                rid=f"s{seed}-{d}-{t}", due_s=start + t * spacing,
                prompt=prefix + tokens(suffixes[k], vocab, rng),
                max_tokens=outputs[k], seed=rng.randrange(1 << 31),
                group=f"s{d}", prefix_tokens=prefixes[d]))
    reqs.sort(key=lambda r: r.due_s)
    return Plan(loop="open", requests=reqs)
