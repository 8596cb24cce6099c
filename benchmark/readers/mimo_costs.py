"""What a MiMo-V2 trunk's held experts must move, counted from the
configuration's own keys: the expert layers the ones of the
``moe_layer_freq`` **list** (``readers/dots3_costs.py`` counts them from
``first_k_dense_replace``, which this configuration has not), the
experts held ``n_routed_experts`` (one expert-parallel rank's share
where ``expert_share`` states one; both as ``readers/dots3_costs.py``
counts them). What its two kinds of attention must
read and multiply is the configuration's module of
``benchmark/attention_costs``. The algorithm's needs, not what a form of
it happens to do. No jax.
"""

from __future__ import annotations

from readers.dots3_costs import experts_decode_bytes, held_experts  # noqa: F401


def expert_layers(hf: dict) -> int:
    return sum(int(f) for f in hf["moe_layer_freq"])


def steps_of_slots(hf: dict, slots: float) -> float:
    """Steps behind a delta of ``dynamo_moe_expert_slots_total``: the
    experts held x the expert layers a step."""
    return slots / (held_experts(hf) * expert_layers(hf))
