"""Attention over two kinds of paged cache in one model: layers that
attend to the last ``sliding_window`` keys and layers that attend to
every key (``layer_types``: ``sliding_attention`` | ``full_attention``;
the AFMoE family, docs/models.md). Both kinds hold K and V of every kv
head.

What the equations must read and multiply, not what a kernel happens to
do: a window layer's query with ``n`` tokens visible needs the last
``min(n, sliding_window)`` keys and values, a full layer's all ``n``;
a page walked for nobody (a whole chunk of pages before the window's
first key, an entry that names the page no sequence holds) is the
kernel's, and lowers a roofline share made from these. The cache stores
a head in rows of 128 lanes and a row is read whole.
"""

from __future__ import annotations

from typing import Iterable

LANES = 128
LOCAL, GLOBAL = "sliding_attention", "full_attention"


def lane_padded(head_dim: int) -> int:
    return -(-head_dim // LANES) * LANES


def _shape(hf: dict) -> tuple:
    """(heads, kv heads, head size, window layers, full layers, window)."""
    heads = int(hf["num_attention_heads"])
    kv_heads = int(hf.get("num_key_value_heads", heads))
    head_dim = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    kinds = list(hf["layer_types"])
    return (heads, kv_heads, head_dim, kinds.count(LOCAL), kinds.count(GLOBAL),
            int(hf["sliding_window"]))


def attended(n: int, window: int, local_layers: int, full_layers: int) -> int:
    """Keys the query with ``n`` tokens visible attends to, summed over
    the layers."""
    return local_layers * min(n, window) + full_layers * n


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    """K and V of every attended key of every sequence, of every kv head
    (the family is not sharded: ``tensor_parallel_size`` says nothing
    here), over both kinds of layer."""
    _, kv_heads, head_dim, n_local, n_full, window = _shape(hf)
    keys = sum(attended(int(n), window, n_local, n_full) for n in context_lens)
    return 2 * keys * kv_heads * lane_padded(head_dim) * cache_itemsize


def _triangle(lo: int, hi: int) -> int:
    """lo + (lo + 1) + ... + (hi - 1)."""
    return (hi - lo) * (lo + hi - 1) // 2


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    """QK^T and PV (4 FLOPs a key a head element) of every query of the
    chunks ``[(start, length), ...]``: the query at position ``p``
    attends to ``p + 1`` keys in a full layer and to ``min(p + 1,
    window)`` in a window layer, whose triangle is so cut to a band."""
    heads, _, head_dim, n_local, n_full, window = _shape(hf)
    pairs = 0
    for start, length in chunks:
        lo, hi = int(start) + 1, int(start) + int(length) + 1   # keys visible
        full = _triangle(lo, hi)
        under = _triangle(lo, min(hi, window)) if lo < window else 0
        band = under + window * max(0, hi - max(lo, window))
        pairs += n_full * full + n_local * band
    return 4 * pairs * heads * head_dim
