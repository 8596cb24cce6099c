"""Latent attention over two kinds of paged cache in one model, the
full kind behind a learned indexer (``dots3_note``: ``layer_types``
names each layer ``full_attention`` or ``sliding_attention``, both
latent, each kind with a head count, ranks and a page shape of its own).

What the equations must read and multiply, from the configuration's own
keys and the keys live: the work itself, not what implements it, so a
roofline share made from these cannot pass 100 %.

- **The indexer** of a full layer scores every live key of a sequence:
  a decode step reads each key's ``index_head_dim``-wide indexer key
  once (rows of 128 lanes), in every full layer.
- **Attention of a full layer** is over the ``min(n, index_topk)`` keys
  the indexer picks of a sequence's ``n``: their latent
  (``kv_lora_rank``) and shared rope key (``qk_rope_head_dim``, a row of
  128 lanes), read once, the latent serving as key and as value.
- **A window layer** attends to the last ``min(n, sliding_window_size)``
  keys: their latent (``swa_kv_lora_rank``) and rope key.

The latent has no head axis and is replicated under tensor parallelism:
the bytes do not depend on ``tensor_parallel_size``. Prefill is counted
in the absorbed form, the one the program runs (``attention_costs/
latent_kv.py``): ``2 r + rope`` multiply-adds a head a (query, attended
key) pair, and the indexer's ``index_n_heads x index_head_dim`` a
(query, earlier key) pair in a full layer.
"""

from __future__ import annotations

from typing import Iterable

LANES = 128
FULL, WINDOW = "full_attention", "sliding_attention"


def lane_padded(width: int) -> int:
    return -(-width // LANES) * LANES


def layers(hf: dict) -> tuple:
    """(full layers, window layers)."""
    kinds = list(hf["layer_types"])
    return kinds.count(FULL), kinds.count(WINDOW)


def index_key_bytes(hf: dict, cache_itemsize: int) -> int:
    return lane_padded(int(hf["index_head_dim"])) * cache_itemsize


def full_key_bytes(hf: dict, cache_itemsize: int) -> int:
    return (lane_padded(int(hf["kv_lora_rank"]))
            + lane_padded(int(hf["qk_rope_head_dim"]))) * cache_itemsize


def window_key_bytes(hf: dict, cache_itemsize: int) -> int:
    return (lane_padded(int(hf["swa_kv_lora_rank"]))
            + lane_padded(int(hf["swa_qk_rope_head_dim"]))) * cache_itemsize


def index_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                     context_lens: Iterable[int]) -> int:
    """The indexer's keys of every live key, every full layer."""
    del tensor_parallel_size
    return (layers(hf)[0] * sum(int(n) for n in context_lens)
            * index_key_bytes(hf, cache_itemsize))


def picked_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    """Latent and rope key of every picked key, every full layer."""
    del tensor_parallel_size
    topk = int(hf["index_topk"])
    return (layers(hf)[0] * sum(min(int(n), topk) for n in context_lens)
            * full_key_bytes(hf, cache_itemsize))


def window_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    """Latent and rope key of every key in the window, every window layer."""
    del tensor_parallel_size
    window = int(hf["sliding_window_size"])
    return (layers(hf)[1] * sum(min(int(n), window) for n in context_lens)
            * window_key_bytes(hf, cache_itemsize))


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    contexts = [int(n) for n in context_lens]
    return sum(part(hf, tensor_parallel_size, cache_itemsize, contexts)
               for part in (index_step_bytes, picked_step_bytes,
                            window_step_bytes))


def _triangle(lo: int, hi: int) -> int:
    """lo + (lo + 1) + ... + (hi - 1)."""
    return (hi - lo) * (lo + hi - 1) // 2


def _band(lo: int, hi: int, width: int) -> int:
    """min(lo, width) + ... + min(hi - 1, width)."""
    under = _triangle(lo, min(hi, width)) if lo < width else 0
    return under + width * max(0, hi - max(lo, width))


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    """Of the chunks ``[(start, length), ...]``: the query at position
    ``p`` has ``p + 1`` keys visible; the indexer scores them all, a full
    layer attends to ``min(p + 1, index_topk)`` of them, a window layer
    to ``min(p + 1, sliding_window_size)``. 2 FLOPs a multiply-add; a
    device computes its share of the heads."""
    n_full, n_window = layers(hf)
    tp = max(1, tensor_parallel_size)
    heads = max(1, int(hf["num_attention_heads"]) // tp)
    w_heads = max(1, int(hf["swa_num_attention_heads"]) // tp)
    per_pair = 2 * int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
    w_per_pair = (2 * int(hf["swa_kv_lora_rank"])
                  + int(hf["swa_qk_rope_head_dim"]))
    index_pair = int(hf["index_n_heads"]) * int(hf["index_head_dim"])
    total = 0
    for start, length in chunks:
        lo, hi = int(start) + 1, int(start) + int(length) + 1   # keys visible
        total += n_full * (
            _triangle(lo, hi) * index_pair
            + _band(lo, hi, int(hf["index_topk"])) * heads * per_pair)
        total += n_window * (_band(lo, hi, int(hf["sliding_window_size"]))
                             * w_heads * w_per_pair)
    return 2 * total
