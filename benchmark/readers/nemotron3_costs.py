"""What a Nemotron-H trunk's mixer state and held latent experts must
move and multiply, counted from the configuration's own keys: the mixer,
attention and expert layers by the letters of
``hybrid_override_pattern`` (``M``, ``*``, ``E``: a layer is one of the
three alone), the experts held by ``n_routed_experts`` (one
expert-parallel rank's share where ``expert_share`` states one). These
are the algorithm's needs, not what a form of it happens to do, so a
roofline share made from them cannot pass 100 %. No jax.

Per **mixer** layer and sequence the state is ``[mamba_num_heads,
mamba_head_dim, ssm_state_size]`` in float32 (the ``n_groups`` groups of
``B`` and ``C`` change what a head reads, not the state's elements) and
the causal conv's last ``conv_kernel - 1`` inputs (``d_inner + 2 x
n_groups x ssm_state_size`` wide, ``d_inner = mamba_num_heads x
mamba_head_dim``) in the trunk's dtype. A decode step must read a
running sequence's record and write it back, in every mixer layer and in
no other; a token of prefill must multiply-add every element of the
state twice (into it and out of it: 4 FLOPs an element;
``readers/ssm_costs.py`` says why).

A routed **expert** works in the latent: **two** matrices of
``moe_latent_size x moe_intermediate_size`` around a squared ReLU, no
gate matrix. A step reads both matrices of every *held* expert that has
at least one row, once, and each row that fell on a held expert once in
and once out, ``moe_latent_size`` wide both ways. A pick of an absent
expert is computed nowhere and moves nothing. (The two latent
projections and the shared expert are dense products on the hidden-wide
stream and are no part of these counts.)
"""

from __future__ import annotations

from readers import ssm_costs

MIXER, ATTENTION, EXPERTS = "M", "*", "E"


def _itemsize(hf: dict) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}.get(
        str(hf.get("torch_dtype", "bfloat16")), 2)


def layers_of(hf: dict, letter: str) -> int:
    return str(hf["hybrid_override_pattern"]).count(letter)


def mixer_layers(hf: dict) -> int:
    return layers_of(hf, MIXER)


def attention_layers(hf: dict) -> int:
    return layers_of(hf, ATTENTION)


def expert_layers(hf: dict) -> int:
    return layers_of(hf, EXPERTS)


def held_experts(hf: dict) -> int:
    """Experts whose weights the chip holds, of the published
    ``expert_share.of_experts`` (all of them without a share)."""
    return int(hf["n_routed_experts"])


def _as_mixer(hf: dict) -> dict:
    """The keys ``readers/ssm_costs.py`` reads, from this family's
    published names for them."""
    heads, head = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    return {**hf, "mamba_n_heads": heads, "mamba_d_head": head,
            "mamba_d_ssm": heads * head, "mamba_d_state": hf["ssm_state_size"],
            "mamba_n_groups": hf.get("n_groups", 1),
            "mamba_d_conv": hf.get("conv_kernel", 4)}


def state_elements(hf: dict) -> int:
    """Elements of one sequence's SSM state in one mixer layer."""
    return ssm_costs.state_elements(_as_mixer(hf))


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
    """One expert's two matrices."""
    return (2 * int(hf["moe_latent_size"]) * int(hf["moe_intermediate_size"])
            * _itemsize(hf))


def row_bytes(hf: dict) -> int:
    """One routed row in and out, in the latent."""
    return 2 * int(hf["moe_latent_size"]) * _itemsize(hf)


def experts_decode_bytes(hf: dict, active_held: float, held_rows: float) -> float:
    """Bytes the expert products of steps that touched ``active_held``
    held experts (summed over layers and steps) with ``held_rows`` rows
    on held experts must move."""
    return active_held * expert_weight_bytes(hf) + held_rows * row_bytes(hf)


def steps_of_slots(hf: dict, slots: float) -> float:
    """Steps behind a delta of ``dynamo_moe_expert_slots_total``: the
    experts held x the expert layers a step."""
    return slots / (held_experts(hf) * expert_layers(hf))
