"""What manifold-constrained hyper-connections (``hc_mult`` residual
streams mixed around every sublayer; ``dynamo_tpu/models/mhc.py``) must
move, from the shapes: the least any implementation moves, not what a
form of it happens to do, so a roofline share made from it cannot pass
100 %. No jax.

A layer has two sublayers. Around each, a token's streams (``hc_mult x
hidden_size`` values in the trunk's dtype) are read and written, the
sublayer's input ``u`` goes out and its output ``y`` comes in (``hidden_
size`` each): an implementation that fuses the update of one sublayer
with the coefficients and the read of the next still reads the streams
once and writes them once a sublayer. ``phi`` (``[hc_mult hidden_size,
2 hc_mult + hc_mult^2]``), ``b`` and the three ``alpha`` are float32 and
read once a sublayer an execution of the program. The coefficients
themselves (24 values a token) stay on the chip. The arithmetic is 24
multiply-adds a stream value for the projection and ``hc_mult + 1`` a
value for the mixes: far under the bandwidth's time on this chip, so the
bound is HBM.
"""

from __future__ import annotations

SUBLAYERS_A_LAYER = 2


def _itemsize(hf: dict) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}.get(
        str(hf.get("torch_dtype", "bfloat16")), 2)


def coefficient_columns(hf: dict) -> int:
    n = int(hf["hc_mult"])
    return 2 * n + n * n


def token_bytes(hf: dict) -> int:
    """Bytes one token must move around one sublayer: the streams in and
    out, ``y`` in, ``u`` out."""
    n = int(hf["hc_mult"])
    return (2 * n + 2) * int(hf["hidden_size"]) * _itemsize(hf)


def param_bytes(hf: dict) -> int:
    """One sublayer's ``phi``, ``b`` and ``alpha``, float32."""
    n, c = int(hf["hc_mult"]), coefficient_columns(hf)
    return 4 * (n * int(hf["hidden_size"]) * c + c + 3)


def sublayers(hf: dict) -> int:
    return SUBLAYERS_A_LAYER * int(hf["num_hidden_layers"])


def step_bytes(hf: dict, tokens: float, executions: float = 1.0) -> float:
    """Bytes for ``tokens`` tokens through every sublayer, the parameters
    read once an execution."""
    return sublayers(hf) * (tokens * token_bytes(hf)
                            + executions * param_bytes(hf))


def decode_step_bytes(hf: dict, tp: int, itemsize: int, contexts) -> int:
    """Bytes one decode step must move for the sequences running then:
    one token each, whatever its context. (The signature of a module of
    ``benchmark/attention_costs``: ``tp`` and the page cache's
    ``itemsize`` say nothing here; the streams are replicated over tp.)"""
    return int(step_bytes(hf, len(contexts)))
