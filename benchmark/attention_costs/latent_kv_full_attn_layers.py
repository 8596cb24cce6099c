"""Latent attention over a paged cache in a trunk where only the layers
``linear_attn_config.full_attn_layers`` names have pages (the others are
Kimi Delta Attention layers with state by slot and no pages: Kimi
Linear).

``attention_costs/latent_kv.py``'s needs (the latent and the shared
64-wide key of every key, each in rows of 128 lanes, read once and
serving as key and as value; the absorbed form's multiply-adds a head a
pair), counted over the latent layers alone: a decode step reads a
sequence's keys in 7 layers of 27 and in no KDA layer. That the 64-wide
parts carry no rotation (``mla_use_nope``) moves neither bytes nor
multiply-adds.
"""

from __future__ import annotations

from typing import Iterable

from attention_costs import latent_kv


def attention_layers(hf: dict) -> int:
    return len(hf["linear_attn_config"]["full_attn_layers"])


def _as_latent_layers(hf: dict) -> dict:
    """The configuration as ``latent_kv`` reads it: its layers the
    latent ones."""
    return {**hf, "num_hidden_layers": attention_layers(hf)}


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    return latent_kv.decode_step_bytes(
        _as_latent_layers(hf), tensor_parallel_size, cache_itemsize,
        context_lens)


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    return latent_kv.prefill_flops(
        _as_latent_layers(hf), tensor_parallel_size, chunks)
