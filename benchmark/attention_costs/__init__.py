"""One module per kind of attention cache: what its attention must read
and multiply, found by the name under ``attention_cost`` in a
configuration's file. The whole knowledge of the shape sits in the
module (kv heads a device, lane padding, window, K and V or one shared
latent, which layers attend how); the readers keep the trace's side
(which executions, the kernel's time, the peaks, the division).

A module has

- ``decode_step_bytes(hf, tensor_parallel_size, cache_itemsize,
  context_lens)``: the bytes one decode step must read from the cache on
  one device, over all layers, for running sequences of those context
  lengths;
- ``prefill_flops(hf, tensor_parallel_size, chunks)``: the FLOPs the
  attention of prefill chunks ``[(start, length), ...]`` needs on one
  device, over all layers (``length`` new tokens after ``start`` tokens
  of context; 2 FLOPs a multiply-add).

``hf`` is the configuration's published keys. These are the algorithm's
needs, not what a kernel happens to do, so a roofline share made from
them cannot pass 100 %. No jax. A new kind of cache is a new file here.
"""
