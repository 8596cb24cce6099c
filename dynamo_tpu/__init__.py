"""dynamo-tpu: a TPU-native distributed LLM inference-serving framework.

Capabilities modeled on NVIDIA Dynamo (see SURVEY.md), rebuilt TPU-first:
an OpenAI-compatible frontend, a distributed runtime (lease-based discovery +
pub/sub messaging + TCP dial-back streaming), KV-cache-aware routing over a
global radix index, disaggregated prefill/decode with HBM-to-HBM KV transfer,
and a native JAX/XLA serving engine (paged attention, continuous batching,
pjit/shard_map parallelism) in place of GPU engines.
"""

import time as _time

# the start-up timeline's first mark (telemetry/flight.py): the earliest
# moment the program has
T_IMPORT = _time.monotonic()

__version__ = "0.1.0"
