"""What a dots3_note trunk's held experts must move, counted from the
configuration's own keys: the expert layers those past
``first_k_dense_replace``, the experts held ``n_routed_experts`` (one
expert-parallel rank's share where ``expert_share`` states one). What
its three attention routes must read is the configuration's module of
``benchmark/attention_costs`` (the indexer over every live key,
attention over the picked keys, the window layers). The algorithm's needs, not what a form of it happens to do. No
jax.
"""

from __future__ import annotations

from readers import expert_costs


def expert_layers(hf: dict) -> int:
    return int(hf["num_hidden_layers"]) - int(hf.get("first_k_dense_replace", 0))


def held_experts(hf: dict) -> int:
    """Experts whose weights the chip holds, of the published
    ``expert_share.of_experts`` (all of them without a share)."""
    return int(hf["n_routed_experts"])


def experts_decode_bytes(hf: dict, active_held: float, held_rows: float) -> float:
    """Bytes the expert products of steps that touched ``active_held``
    held experts (summed over layers and steps) with ``held_rows`` rows
    on held experts must move (``readers/expert_costs.py``)."""
    return expert_costs.decode_bytes(hf, active_held, held_rows)


def steps_of_slots(hf: dict, slots: float) -> float:
    """Steps behind a delta of ``dynamo_moe_expert_slots_total``: the
    experts held x the expert layers a step."""
    return slots / (held_experts(hf) * expert_layers(hf))
