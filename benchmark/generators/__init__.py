"""One module per traffic kind, found by the ``kind`` in a mix's file.
``build(mix, cell, vocab, seed, seconds)`` returns a
``harness.traffic.Plan``. No jax: the load generator imports these."""
