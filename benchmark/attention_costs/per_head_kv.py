"""Attention over a paged cache that holds K and V of every kv head:
multi-head or grouped-query attention, with an optional whole-model
sliding window.

What an attention call must move and compute, from its shapes. These are
the algorithm's needs, not what a kernel happens to do: bytes a decode
step has to read from the cache, and the multiply-adds a prefill chunk
has to make. Both know the sliding window (keys outside it need neither
reading nor multiplying) and the lane padding (the cache stores a head
in rows of 128 lanes, and a row is read whole, so a head of 96 costs
128). Under tensor parallelism a device holds its share of the heads:
``num_key_value_heads // tp`` of the cache, ``num_attention_heads //
tp`` of the queries.
"""

from __future__ import annotations

from typing import Iterable, Optional

LANES = 128


def lane_padded(head_dim: int) -> int:
    return -(-head_dim // LANES) * LANES


def attended(context_len: int, window: Optional[int]) -> int:
    """Keys one query at the end of ``context_len`` tokens attends to."""
    return min(context_len, window) if window else context_len


def decode_attention_bytes(context_lens: Iterable[int], num_kv_heads: int,
                           head_dim: int, num_layers: int,
                           window: Optional[int] = None,
                           cache_itemsize: int = 2) -> int:
    """HBM bytes one decode step must read from the paged cache over all
    layers: K and V of every attended key of every sequence (on one
    device: pass that device's share of the kv heads)."""
    keys = sum(attended(int(c), window) for c in context_lens)
    return (2 * keys * num_kv_heads * lane_padded(head_dim)
            * cache_itemsize * num_layers)


def prefill_attention_flops(chunks: Iterable[tuple], num_heads: int,
                            head_dim: int, num_layers: int,
                            window: Optional[int] = None) -> int:
    """FLOPs the attention of prefill chunks needs over all layers.
    ``chunks`` is [(start, length), ...]: ``length`` new tokens after
    ``start`` tokens of context. Query at position p attends
    min(p + 1, window) keys; QK^T and PV are 2 FLOPs per multiply-add
    each, over the true head size (padding lanes carry zeros the
    algorithm does not need)."""
    pairs = 0
    for start, length in chunks:
        for p in range(int(start), int(start) + int(length)):
            pairs += attended(p + 1, window)
    return 4 * pairs * num_heads * head_dim * num_layers


def _shape(hf: dict) -> tuple:
    """(heads, kv heads, head size, layers, window) of the published keys."""
    heads = int(hf["num_attention_heads"])
    kv_heads = int(hf.get("num_key_value_heads", heads))
    head_dim = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    window = int(hf.get("sliding_window") or 0) or None
    return heads, kv_heads, head_dim, int(hf["num_hidden_layers"]), window


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    """K and V of a device's share of the kv heads, each sequence's keys
    cut to the window."""
    _, kv_heads, head_dim, layers, window = _shape(hf)
    return decode_attention_bytes(
        context_lens, max(1, kv_heads // tensor_parallel_size), head_dim,
        layers, window, cache_itemsize)


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    """QK^T and PV of a device's share of the query heads."""
    heads, _, head_dim, layers, window = _shape(hf)
    return prefill_attention_flops(
        chunks, max(1, heads // tensor_parallel_size), head_dim, layers, window)
