"""What a Granite 4.0-H trunk's mixer state and held experts must move
and multiply, counted from the configuration's own keys: the mixer
layers and the attention layers by ``layer_types``, the experts held by
``num_local_experts`` (one expert-parallel rank's share where
``expert_share`` states one). These are the algorithm's needs, not what
a form of it happens to do, so a roofline share made from them cannot
pass 100 %. No jax.

Per **mixer** layer and sequence the state is ``[mamba_n_heads,
mamba_d_head, mamba_d_state]`` in float32 and the causal conv's last
``mamba_d_conv - 1`` inputs (``d_ssm + 2 x mamba_n_groups x
mamba_d_state`` wide, ``d_ssm = mamba_n_heads x mamba_d_head``) in the
trunk's dtype. A decode step must read a running sequence's record and
write it back, in every mixer layer and in no attention layer; a token
of prefill must multiply-add every element of the state twice (into it
and out of it: 4 FLOPs an element; ``readers/ssm_costs.py`` says why).

A routed **expert** is a SwiGLU of ``hidden_size x intermediate_size``
(the published config has no key of its own for an expert's width): a
step reads the three matrices of every *held* expert that has at least
one row, once, and each row that fell on a held expert once in and once
out. A pick of an absent expert is computed nowhere and moves nothing.
"""

from __future__ import annotations

from readers import expert_costs, ssm_costs

MAMBA, ATTENTION = "mamba", "attention"


def _as_mixer(hf: dict) -> dict:
    """The keys ``readers/ssm_costs.py`` reads, for a config that gives
    ``d_ssm`` as heads x head and may leave the defaults out."""
    return {**hf, "mamba_d_ssm": int(hf["mamba_n_heads"]) * int(hf["mamba_d_head"]),
            "mamba_n_groups": hf.get("mamba_n_groups", 1),
            "mamba_d_conv": hf.get("mamba_d_conv", 4)}


def _as_experts(hf: dict) -> dict:
    """The key ``readers/expert_costs.py`` reads an expert's width from."""
    return {**hf, "moe_intermediate_size": hf["intermediate_size"]}


def mixer_layers(hf: dict) -> int:
    return list(hf["layer_types"]).count(MAMBA)


def attention_layers(hf: dict) -> int:
    return list(hf["layer_types"]).count(ATTENTION)


def held_experts(hf: dict) -> int:
    """Experts whose weights the chip holds, of the published
    ``expert_share.of_experts`` (all of them without a share)."""
    return int(hf["num_local_experts"])


def state_elements(hf: dict) -> int:
    """Elements of one sequence's SSM state in one mixer layer."""
    return ssm_costs.state_elements(hf)


def record_bytes(hf: dict) -> int:
    """One sequence's record in one mixer layer: the float32 state and
    the conv window."""
    return ssm_costs.record_bytes(_as_mixer(hf))


def decode_step_bytes(hf: dict, tp: int, itemsize: int, contexts) -> int:
    """Bytes one decode step must move for the records of the sequences
    running then: each read once and written once in every mixer layer,
    whatever its context. (The signature of a module of
    ``benchmark/attention_costs``: ``tp`` and the page cache's
    ``itemsize`` say nothing here; the state is not sharded.)"""
    return len(contexts) * mixer_layers(hf) * 2 * record_bytes(hf)


def scan_flops(hf: dict, tokens: float) -> float:
    """FLOPs the recurrence needs for ``tokens`` tokens, all mixer layers."""
    return 4.0 * tokens * mixer_layers(hf) * state_elements(hf)


def expert_weight_bytes(hf: dict) -> int:
    """One expert's three matrices."""
    return expert_costs.expert_weight_bytes(_as_experts(hf))


def experts_decode_bytes(hf: dict, active_held: float, held_rows: float) -> float:
    """Bytes the expert products of steps that touched ``active_held``
    held experts (summed over layers and steps) with ``held_rows`` rows
    on held experts must move."""
    return expert_costs.decode_bytes(_as_experts(hf), active_held, held_rows)


def steps_of_slots(hf: dict, slots: float) -> float:
    """Steps behind a delta of ``dynamo_moe_expert_slots_total``: the
    experts held x the layers (every layer has experts) a step."""
    return slots / (held_experts(hf) * int(hf["num_hidden_layers"]))
