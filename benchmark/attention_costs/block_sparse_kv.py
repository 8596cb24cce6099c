"""Block-sparse attention (InfLLM-V2, as the MiniCPM4 family publishes
it) over a paged cache that holds K and V of every kv head and one
compressed key a ``kernel_stride`` tokens, in the layers whose
``mixer_types`` entry is ``minicpm4``. The other layers (linear
attention) hold no pages; what their state must move is in
``readers/lightning_costs.py``.

What the equations must read and multiply, not what a kernel happens to
do: a query with ``n`` tokens visible attends to all of them up to
``dense_len``, and past it to the tokens of the kept blocks (the first
``init_blocks``, the blocks that overlap the last ``window_size`` tokens
and ``topk`` others) after scoring every compressed key whose window
lies inside ``n``. The compressed keys count at the cache's element
size, whatever a program keeps them in. ``sparse_config`` is read from
the configuration's keys, with the MiniCPM4 family's published values
where a key is absent.
"""

from __future__ import annotations

from typing import Iterable

LANES = 128
SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                   "topk": 64, "init_blocks": 1, "window_size": 2048,
                   "dense_len": 8192}
SPARSE_KIND = "minicpm4"


def lane_padded(head_dim: int) -> int:
    return -(-head_dim // LANES) * LANES


def sparse_config(hf: dict) -> dict:
    return {**SPARSE_DEFAULTS, **(hf.get("sparse_config") or {})}


def sparse_layers(hf: dict) -> int:
    return sum(1 for kind in hf["mixer_types"] if kind == SPARSE_KIND)


def kept_tokens(n: int, sp: dict) -> int:
    """Keys the query with ``n`` tokens visible attends to."""
    if n <= sp["dense_len"]:
        return n
    bs = sp["block_size"]
    visible = -(-n // bs)
    first_window = max(n - sp["window_size"], 0) // bs
    forced = min(sp["init_blocks"], first_window) + (visible - first_window)
    picked = min(sp["topk"], max(first_window - sp["init_blocks"], 0))
    # the last visible block is part full
    return (forced + picked) * bs - (visible * bs - n)


def compressed_keys(n: int, sp: dict) -> int:
    """Compressed keys whose window lies inside ``n`` tokens; none is
    scored by a query that attends densely."""
    if n <= sp["dense_len"] or n < sp["kernel_size"]:
        return 0
    return (n - sp["kernel_size"]) // sp["kernel_stride"] + 1


def _shape(hf: dict) -> tuple:
    heads = int(hf["num_attention_heads"])
    kv_heads = int(hf.get("num_key_value_heads", heads))
    head_dim = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    return heads, kv_heads, head_dim


def decode_step_bytes(hf: dict, tensor_parallel_size: int, cache_itemsize: int,
                      context_lens: Iterable[int]) -> int:
    """K and V of the kept tokens and the compressed keys scored, of
    every kv head, over the sparse layers (the family is not sharded:
    ``tensor_parallel_size`` says nothing here)."""
    _, kv_heads, head_dim = _shape(hf)
    sp = sparse_config(hf)
    rows = sum(2 * kept_tokens(int(n), sp) + compressed_keys(int(n), sp)
               for n in context_lens)
    return (rows * kv_heads * lane_padded(head_dim) * cache_itemsize
            * sparse_layers(hf))


def prefill_flops(hf: dict, tensor_parallel_size: int,
                  chunks: Iterable[tuple]) -> int:
    """QK^T and PV over the kept tokens (4 FLOPs a key a head element)
    and the compressed keys' scores (2), of every query of the chunks
    ``[(start, length), ...]``, over the sparse layers."""
    heads, _, head_dim = _shape(hf)
    sp = sparse_config(hf)
    pairs = 0
    for start, length in chunks:
        for p in range(int(start), int(start) + int(length)):
            pairs += 4 * kept_tokens(p + 1, sp) + 2 * compressed_keys(p + 1, sp)
    return pairs * heads * head_dim * sparse_layers(hf)
