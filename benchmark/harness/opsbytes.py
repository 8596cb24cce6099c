"""What an attention call must move and compute, from its shapes.

These are the algorithm's needs, not what a kernel happens to do: bytes
a decode step has to read from the cache, and the multiply-adds a
prefill chunk has to make. Both know the sliding window (keys outside
it need neither reading nor multiplying) and the lane padding (the cache
stores a head in rows of 128 lanes, and a row is read whole, so a head
of 96 costs 128).
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
