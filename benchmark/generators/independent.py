"""Requests that share nothing: every prompt is fresh random ids.

Parameters: ``prompt_tokens`` and ``output_tokens`` (length
distributions), ``ramp_s``, optionally ``arrivals`` (open loop) and
``closed_requests_per_client`` (closed loop)."""

import random

from harness.traffic import (Plan, Request, ramp_and_window,
                             stratified_in_blocks, tokens)


def build(mix: dict, cell: dict, vocab: int, seed: int, seconds: float) -> Plan:
    rng = random.Random(f"independent:{seed}")
    if cell["loop"] == "open":
        dues, blocks = ramp_and_window(mix, cell["rate"], seconds, rng)
    else:
        dues = None
        # more than any run can finish; what is not sent costs nothing
        blocks = [int(cell["clients"])] * int(mix.get("closed_requests_per_client", 24))
    n = sum(blocks)
    prompts = stratified_in_blocks(mix["prompt_tokens"], blocks, rng)
    outputs = stratified_in_blocks(mix["output_tokens"], blocks, rng)
    reqs = [
        Request(rid=f"r{seed}-{i}", due_s=dues[i] if dues else None,
                prompt=tokens(prompts[i], vocab, rng),
                max_tokens=outputs[i], seed=rng.randrange(1 << 31))
        for i in range(n)
    ]
    return Plan(loop=cell["loop"], requests=reqs, clients=int(cell.get("clients", 0)))
