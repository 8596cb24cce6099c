"""What a lightning linear-attention layer's recurrent state must move
and multiply, from its shapes: the recurrence's own needs, not what a
form of it happens to do, so a roofline share made from them cannot pass
100 %. No jax.

Per ``lightning-attn`` layer and sequence the state is ``[lightning_nh,
lightning_head_dim, lightning_head_dim]`` in float32 (``S_t = λ S_{t−1} +
k_tᵀ v_t``, ``o_t = q_t S_t``). One token must read the sequence's state
and write it back, and multiply-add every element twice (into it and out
of it): 4 FLOPs an element, as ``readers/ssm_costs.py`` counts a Mamba-2
state. The layers of the other kind hold no state.
"""

from __future__ import annotations

LIGHTNING_KIND = "lightning-attn"


def lightning_layers(hf: dict) -> int:
    return sum(1 for kind in hf["mixer_types"] if kind == LIGHTNING_KIND)


def state_elements(hf: dict) -> int:
    """Elements of one sequence's state in one layer."""
    heads = int(hf.get("lightning_nh", hf["num_attention_heads"]))
    d = int(hf.get("lightning_head_dim", hf.get("head_dim", 128)))
    return heads * d * d


def record_bytes(hf: dict) -> int:
    return 4 * state_elements(hf)


def decode_step_bytes(hf: dict, tp: int, itemsize: int, contexts) -> int:
    """Bytes one decode step must move for the states of the sequences
    running then: each read once and written once in every lightning
    layer, whatever its context. (The signature of a module of
    ``benchmark/attention_costs``: ``tp`` and the page cache's
    ``itemsize`` say nothing here.)"""
    return len(contexts) * lightning_layers(hf) * 2 * record_bytes(hf)


def scan_flops(hf: dict, tokens: float) -> float:
    """FLOPs the recurrence needs for ``tokens`` tokens, all layers."""
    return 4.0 * tokens * lightning_layers(hf) * state_elements(hf)
