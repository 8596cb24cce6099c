"""Grouped-query attention over a paged cache in a trunk where only the
layers ``layer_types`` names ``attention`` have pages (the others are
state-space mixers with state by slot and no pages: Granite 4.0-H).

``attention_costs/per_head_kv.py``'s needs (K and V of every key of
every kv head, a head in rows of 128 lanes; QK^T and PV over the true
head size), counted over the attention layers alone: a decode step reads
a sequence's keys in one layer of ten and in no mixer layer. The scale
of the scores (the published ``attention_multiplier``) and the absence
of a positional term move neither bytes nor multiply-adds.
"""

from __future__ import annotations

from typing import Iterable

from attention_costs.per_head_kv import (decode_attention_bytes,
                                         prefill_attention_flops)

ATTENTION = "attention"


def _shape(hf: dict) -> tuple:
    """(heads, kv heads, head size, attention layers) of the published keys."""
    heads = int(hf["num_attention_heads"])
    kv_heads = int(hf.get("num_key_value_heads", heads))
    head_dim = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    return heads, kv_heads, head_dim, list(hf["layer_types"]).count(ATTENTION)


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    _, kv_heads, head_dim, layers = _shape(hf)
    return decode_attention_bytes(
        context_lens, max(1, kv_heads // tensor_parallel_size), head_dim,
        layers, None, cache_itemsize)


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    heads, _, head_dim, layers = _shape(hf)
    return prefill_attention_flops(
        chunks, max(1, heads // tensor_parallel_size), head_dim, layers, None)
