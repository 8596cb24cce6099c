"""One module per architecture: its plain reference, found by the name
under ``reference`` in a configuration's file (as a reader is found by
the name in a metric's file).

A module has

- ``build(hf, t_pad, n_out)``: from the configuration's published keys,
  ``jit(params, tokens[t_pad], out_positions[n_out]) -> log-probs
  [n_out, vocab]``: the architecture's forward as published, in plain
  ``jax.numpy`` and float32 at ``highest`` matmul precision, with no
  cache, kernel or batching. ``params`` is the engine's parameter tree
  (the weights are data: random, from the seed); nothing else of the
  program is used, and nothing is imported from ``dynamo_tpu.models`` or
  ``dynamo_tpu.ops``;
- ``LOGPROB_ATOL`` and ``LOGPROB_MEAN_ATOL``: the limits on one token's
  log-probability and on a run's mean difference, each with what it was
  measured on in the module's docstring. They belong to the
  architecture: what bfloat16 rounding does to a routed expert's choice
  is not what it does to a dense trunk.

The comparison that decides ``correct`` is one piece of code for all of
them (``harness/reference.py``) and takes the module. A new architecture
is a new file here; a configuration whose file names no module, or one
that is not here, is refused (``harness/manifest.py``).
"""
