"""What a Mamba-2 mixer's recurrent state must move and multiply, from
its shapes: the recurrence's own needs, not what a form of it happens to
do, so a roofline share made from them cannot pass 100 %. No jax.

Per layer and sequence the mixer keeps a state ``[mamba_n_heads,
mamba_d_head, mamba_d_state]`` in float32 and the causal conv's last
``mamba_d_conv - 1`` inputs (``mamba_d_ssm + 2 x mamba_n_groups x
mamba_d_state`` wide) in the trunk's dtype. One token

- must read the sequence's record and write it back (decode: one token a
  step, so the whole record, both ways, every step; the token's own
  activations are a thousandth of that and are not counted);
- must multiply-add every element of the state twice: once into it
  (``h = decay x h + dt x x (x) B``: a product and a sum an element, the
  decay and the outer product's scalars being a row's and a column's and
  not an element's) and once out of it (``y = h C``): 4 FLOPs an element.
  A chunked form does more (its score and decay matrices, a state read
  a chunk); that is the form's cost and lowers its share.
"""

from __future__ import annotations


def _itemsize(hf: dict) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}.get(
        str(hf.get("torch_dtype", "bfloat16")), 2)


def state_elements(hf: dict) -> int:
    """Elements of one sequence's SSM state in one layer."""
    return (int(hf["mamba_n_heads"]) * int(hf["mamba_d_head"])
            * int(hf["mamba_d_state"]))


def record_bytes(hf: dict) -> int:
    """One sequence's record in one layer: the float32 state and the
    conv window."""
    conv_dim = (int(hf["mamba_d_ssm"])
                + 2 * int(hf["mamba_n_groups"]) * int(hf["mamba_d_state"]))
    return (4 * state_elements(hf)
            + (int(hf["mamba_d_conv"]) - 1) * conv_dim * _itemsize(hf))


def decode_step_bytes(hf: dict, tp: int, itemsize: int, contexts) -> int:
    """Bytes one decode step must move for the records of the sequences
    running then: each read once and written once in every layer,
    whatever its context. (The signature of a module of
    ``benchmark/attention_costs``: ``tp`` and the page cache's
    ``itemsize`` say nothing here; the state is not sharded.)"""
    return len(contexts) * int(hf["num_hidden_layers"]) * 2 * record_bytes(hf)


def scan_flops(hf: dict, tokens: float) -> float:
    """FLOPs the recurrence needs for ``tokens`` tokens, all layers."""
    return 4.0 * tokens * int(hf["num_hidden_layers"]) * state_elements(hf)
