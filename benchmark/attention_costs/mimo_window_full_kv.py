"""Attention over two kinds of paged cache whose pages differ in shape
(``model_type: mimo_v2``, docs/models.md): window layers
(``hybrid_layer_pattern`` 1: ``swa_num_key_value_heads`` kv heads, the
last ``sliding_window`` keys) and full layers (0:
``num_key_value_heads`` kv heads, every key), keys of ``head_dim`` and
values of ``v_head_dim``.

What the equations must read and multiply, not what a kernel happens to
do: a window layer's query with ``n`` tokens visible needs the last
``min(n, sliding_window)`` keys and values of its kv heads, a full
layer's all ``n`` of its own; a page walked for nobody is the kernel's,
and lowers a roofline share made from these. So do lanes a layout pads
with: the program stores keys of 192 in 256 lanes and reads the row
whole, and a key counts its 192 here (ISSUE 59 wrote 256 + 128 lanes a
head, "lane-padded rows read whole"; counted so, a later layout that
stored 192 would read a share a third higher for the same work, and
over 100 at the peak: REVIEW of PR 59). The sink is a logit a head: no
bytes, no FLOPs worth counting.
"""

from __future__ import annotations

from typing import Iterable

def layers(hf: dict) -> tuple:
    """(window layers, full layers)."""
    pattern = [int(p) for p in hf["hybrid_layer_pattern"]]
    return pattern.count(1), pattern.count(0)


def token_bytes(hf: dict, cache_itemsize: int) -> tuple:
    """(bytes a token keeps in one window layer, in one full layer): K
    and V of the kind's kv heads, at the widths the equations give
    them."""
    hd, vd = int(hf["head_dim"]), int(hf.get("v_head_dim") or hf["head_dim"])
    row = (hd + vd) * cache_itemsize
    return (int(hf["swa_num_key_value_heads"]) * row,
            int(hf["num_key_value_heads"]) * row)


def window_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    """K and V of the last ``sliding_window`` keys of every sequence, in
    every window layer (the family is not sharded:
    ``tensor_parallel_size`` says nothing here)."""
    window = int(hf["sliding_window"])
    keys = sum(min(int(n), window) for n in context_lens)
    return layers(hf)[0] * keys * token_bytes(hf, cache_itemsize)[0]


def full_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                    context_lens: Iterable[int]) -> int:
    """K and V of every key of every sequence, in every full layer."""
    keys = sum(int(n) for n in context_lens)
    return layers(hf)[1] * keys * token_bytes(hf, cache_itemsize)[1]


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    context_lens = list(context_lens)
    return (window_step_bytes(hf, tensor_parallel_size, cache_itemsize,
                              context_lens)
            + full_step_bytes(hf, tensor_parallel_size, cache_itemsize,
                              context_lens))


def pair_flops(hf: dict) -> int:
    """FLOPs of one query-key pair over every query head: QK^T over
    ``head_dim`` and PV over ``v_head_dim``, 2 a multiply-add."""
    hd, vd = int(hf["head_dim"]), int(hf.get("v_head_dim") or hf["head_dim"])
    return 2 * int(hf["num_attention_heads"]) * (hd + vd)


def window_prefill_flops(hf: dict, pairs: float) -> float:
    """``pairs``: the query-key pairs one window layer's mask allows
    (``dynamo_attention_prefill_pairs_total{kind="window"}``)."""
    return layers(hf)[0] * pairs * pair_flops(hf)


def full_prefill_flops(hf: dict, pairs: float) -> float:
    return layers(hf)[1] * pairs * pair_flops(hf)


def _triangle(lo: int, hi: int) -> int:
    """lo + (lo + 1) + ... + (hi - 1)."""
    return (hi - lo) * (lo + hi - 1) // 2


def prefill_pairs(chunks: Iterable[tuple], window: int) -> tuple:
    """(pairs of a window layer, of a full layer) of the chunks
    ``[(start, length), ...]``: the query at position ``p`` attends to
    ``p + 1`` keys in a full layer and to ``min(p + 1, window)`` in a
    window layer, whose triangle is so cut to a band."""
    band = full = 0
    for start, length in chunks:
        lo, hi = int(start) + 1, int(start) + int(length) + 1   # keys visible
        full += _triangle(lo, hi)
        under = _triangle(lo, min(hi, window)) if lo < window else 0
        band += under + window * max(0, hi - max(lo, window))
    return band, full


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    """QK^T and PV of every query of the chunks, over both kinds."""
    band, full = prefill_pairs(chunks, int(hf["sliding_window"]))
    return int(window_prefill_flops(hf, band) + full_prefill_flops(hf, full))
