"""What a Kimi Linear trunk's delta-rule state and held experts must
move and multiply, counted from the configuration's own keys: the KDA
layers by ``linear_attn_config.kda_layers``, the expert layers those
past ``first_k_dense_replace``, the experts held by ``num_experts`` (one
expert-parallel rank's share where ``expert_share`` states one). These
are the algorithm's needs, not what a form of it happens to do, so a
roofline share made from them cannot pass 100 %. No jax.

Per **KDA** layer and sequence the state is ``[num_heads, head_dim,
head_dim]`` in float32 (key x value a head). A decode step must read a
running sequence's state and write it back, in every KDA layer and in no
latent layer, and beside it the step's vectors: ``q``, ``k`` and ``v``
(``num_heads x head_dim`` each, in the trunk's dtype), the log-decay a
channel and the write strength a head (float32) in, the read-out
(float32) out. (The conv window is the ``kda_conv`` scope's, not the
kernel's, and is not counted here.) A token of prefill must, by the
recurrence itself, the least any form does, multiply-add every element
of the state three times: read against ``k``, the rank-one add, read
against ``q``: 6 FLOPs an element (the decay's multiplication is
counted nowhere, as ``readers/ssm_costs.py`` counts none).

A routed **expert** is a SwiGLU of ``hidden_size x
moe_intermediate_size``: a step reads the three matrices of every *held*
expert that has at least one row, once, and each row that fell on a held
expert once in and once out (``readers/expert_costs.py``). A pick of an
absent expert is computed nowhere and moves nothing.
"""

from __future__ import annotations

from readers import expert_costs


def kda_layers(hf: dict) -> int:
    return len(hf["linear_attn_config"]["kda_layers"])


def expert_layers(hf: dict) -> int:
    return int(hf["num_hidden_layers"]) - int(hf.get("first_k_dense_replace", 0))


def held_experts(hf: dict) -> int:
    """Experts whose weights the chip holds, of the published
    ``expert_share.of_experts`` (all of them without a share)."""
    return int(hf["num_experts"])


def state_elements(hf: dict) -> int:
    """Elements of one sequence's state in one KDA layer."""
    lin = hf["linear_attn_config"]
    return int(lin["num_heads"]) * int(lin["head_dim"]) ** 2


def step_vector_bytes(hf: dict) -> int:
    """One sequence's vectors of one decode step in one KDA layer: q, k
    and v in the trunk's dtype, g, beta and the read-out in float32."""
    lin = hf["linear_attn_config"]
    heads, width = int(lin["num_heads"]), int(lin["num_heads"]) * int(lin["head_dim"])
    return 3 * width * expert_costs._itemsize(hf) + (2 * width + heads) * 4


def decode_step_bytes(hf: dict, tp: int, itemsize: int, contexts) -> int:
    """Bytes one decode step must move for the states of the sequences
    running then: each read once and written once in every KDA layer,
    whatever its context, and the step's vectors. (The signature of a
    module of ``benchmark/attention_costs``: ``tp`` and the page cache's
    ``itemsize`` say nothing here; the state is float32 and not
    sharded.)"""
    return len(contexts) * kda_layers(hf) * (
        2 * 4 * state_elements(hf) + step_vector_bytes(hf))


def scan_flops(hf: dict, tokens: float) -> float:
    """FLOPs the recurrence needs for ``tokens`` tokens, all KDA layers:
    what the chunked scan of prefill (scope ``kda_scan``) is held to. No
    metric reads it yet: the one cell of this trunk has too few prefills
    in a capture (``readers/kimi_scopes.py``)."""
    return 6.0 * tokens * kda_layers(hf) * state_elements(hf)


def expert_weight_bytes(hf: dict) -> int:
    """One expert's three matrices."""
    return expert_costs.expert_weight_bytes(hf)


def experts_decode_bytes(hf: dict, active_held: float, held_rows: float) -> float:
    """Bytes the expert products of steps that touched ``active_held``
    held experts (summed over layers and steps) with ``held_rows`` rows
    on held experts must move."""
    return expert_costs.decode_bytes(hf, active_held, held_rows)


def steps_of_slots(hf: dict, slots: float) -> float:
    """Steps behind a delta of ``dynamo_moe_expert_slots_total``: the
    experts held x the expert layers a step."""
    return slots / (held_experts(hf) * expert_layers(hf))
