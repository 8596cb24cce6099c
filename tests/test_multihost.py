"""Two-process multi-host bring-up over localhost (CPU backend).

Each process plays one "node": rank 0 hosts the JAX coordinator
(the leader, reference MultiNodeConfig leader_addr semantics,
lib/llm/src/engines.rs:39-57), both join via
parallel.mesh.initialize_multihost, and together they run ONE jitted
sharded step over a global 4-device dp x tp mesh — the GPU-free
equivalent of the reference's Ray leader/follower vLLM bring-up
(lib/engines/vllm0_7/src/ray.rs:66-230).
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.environ["REPO_ROOT"])
from dynamo_tpu.parallel.mesh import MultiHostConfig, initialize_multihost, make_mesh

rank = int(sys.argv[1])
leader = sys.argv[2]
initialize_multihost(MultiHostConfig(
    leader_addr=leader, num_nodes=2, node_rank=rank,
))

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

devices = jax.devices()
assert len(devices) == 4, f"global device count {len(devices)}"
assert jax.process_count() == 2

mesh = make_mesh({"dp": 2, "tp": 2}, devices)
x_spec = NamedSharding(mesh, P("dp", None))
w_spec = NamedSharding(mesh, P(None, "tp"))

# one sharded "layer step": batch over dp, features over tp
xg = np.arange(4 * 8, dtype=np.float32).reshape(4, 8) / 100.0
wg = np.linspace(-1, 1, 64, dtype=np.float32).reshape(8, 8)
x = jax.make_array_from_process_local_data(x_spec, xg[rank * 2 : rank * 2 + 2])
w = jax.device_put(wg, w_spec)

y = jax.jit(lambda x, w: jnp.tanh(x @ w), out_shardings=x_spec)(x, w)
# this process's devices all sit in one dp row -> every addressable shard
# holds the same 2 global rows (replicated over local tp)
want = np.tanh(xg[rank * 2 : rank * 2 + 2] @ wg)
for s in y.addressable_shards:
    np.testing.assert_allclose(np.asarray(s.data), want, rtol=1e-4, atol=1e-6)
print(f"RANK{rank}_OK", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_sharded_step(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    leader = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPO_ROOT"] = repo
    # each process contributes 2 virtual CPU devices -> 4 global
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(rank), leader],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for rank in (0, 1)
    ]
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
    assert "RANK0_OK" in outs[0]
    assert "RANK1_OK" in outs[1]


_MODEL_WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.environ["REPO_ROOT"])
from dynamo_tpu.parallel.mesh import MultiHostConfig, initialize_multihost

rank = int(sys.argv[1])
leader = sys.argv[2]
initialize_multihost(MultiHostConfig(
    leader_addr=leader, num_nodes=2, node_rank=rank,
))

import numpy as np
from jax.experimental import multihost_utils

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner

assert jax.process_count() == 2 and len(jax.devices()) == 4

mcfg = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
    attention_impl="xla",
)
cfg = EngineConfig(
    model=mcfg, max_batch_size=4, max_model_len=64, kv_block_size=8,
    num_kv_blocks=64, dtype="float32", dp_size=2, tp_size=2,
    prefill_buckets=[16], allow_random_weights=True,
)
# params derive deterministically from the config seed on every process;
# the runner shards them over the GLOBAL 2-process x 2-device mesh
runner = ModelRunner(cfg)
assert runner.mesh.devices.size == 4

b, s, bs, w = 4, 16, cfg.kv_block_size, cfg.blocks_per_seq
rng = np.random.default_rng(0)
tokens = rng.integers(0, 512, (b, s)).astype(np.int32)
positions = np.tile(np.arange(s, dtype=np.int32), (b, 1))
btab = np.zeros((b, w), np.int32)
for i in range(b):
    btab[i, : s // bs] = np.arange(i * (s // bs), (i + 1) * (s // bs))
slots = np.take_along_axis(btab, positions // bs, axis=1) * bs + positions % bs
ctx = np.full(b, s, np.int32)

out1, *_ = runner.step(
    tokens, positions, btab, slots, ctx, np.full(b, s - 1, np.int32),
    np.zeros(b, np.float32), np.zeros(b, np.int32), np.ones(b, np.float32),
    jax.random.PRNGKey(0),
)
t1 = multihost_utils.process_allgather(out1, tiled=True)
t1 = np.asarray(t1).reshape(-1)[:b]

dec = t1.reshape(b, 1).astype(np.int32)
dslots = np.zeros((b, 1), np.int32)
for i in range(b):
    btab[i, s // bs] = b * (s // bs) + i
    dslots[i, 0] = btab[i, s // bs] * bs
out2, *_ = runner.step(
    dec, np.full((b, 1), s, np.int32), btab, dslots,
    np.full(b, s + 1, np.int32), np.zeros(b, np.int32),
    np.zeros(b, np.float32), np.zeros(b, np.int32), np.ones(b, np.float32),
    jax.random.PRNGKey(1),
)
t2 = multihost_utils.process_allgather(out2, tiled=True)
t2 = np.asarray(t2).reshape(-1)[:b]
print(f"RANK{rank}_TOKENS {' '.join(map(str, t1))} | {' '.join(map(str, t2))}",
      flush=True)
print(f"RANK{rank}_OK", flush=True)
"""


def _expected_tokens():
    """The same prefill+decode on a single-process runner — the multihost
    step must be numerically the same model."""
    import numpy as np

    import jax
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.model_runner import ModelRunner

    mcfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
        attention_impl="xla",
    )
    cfg = EngineConfig(
        model=mcfg, max_batch_size=4, max_model_len=64, kv_block_size=8,
        num_kv_blocks=64, dtype="float32",
        prefill_buckets=[16], allow_random_weights=True,
    )
    runner = ModelRunner(cfg)
    b, s, bs, w = 4, 16, cfg.kv_block_size, cfg.blocks_per_seq
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, (b, s)).astype(np.int32)
    positions = np.tile(np.arange(s, dtype=np.int32), (b, 1))
    btab = np.zeros((b, w), np.int32)
    for i in range(b):
        btab[i, : s // bs] = np.arange(i * (s // bs), (i + 1) * (s // bs))
    slots = np.take_along_axis(btab, positions // bs, axis=1) * bs + positions % bs
    ctx = np.full(b, s, np.int32)
    out1, *_ = runner.step(
        tokens, positions, btab, slots, ctx, np.full(b, s - 1, np.int32),
        np.zeros(b, np.float32), np.zeros(b, np.int32), np.ones(b, np.float32),
        jax.random.PRNGKey(0),
    )
    t1 = np.asarray(out1)
    dec = t1.reshape(b, 1).astype(np.int32)
    dslots = np.zeros((b, 1), np.int32)
    for i in range(b):
        btab[i, s // bs] = b * (s // bs) + i
        dslots[i, 0] = btab[i, s // bs] * bs
    out2, *_ = runner.step(
        dec, np.full((b, 1), s, np.int32), btab, dslots,
        np.full(b, s + 1, np.int32), np.zeros(b, np.int32),
        np.zeros(b, np.float32), np.zeros(b, np.int32), np.ones(b, np.float32),
        jax.random.PRNGKey(1),
    )
    t2 = np.asarray(out2)
    return list(map(int, t1)), list(map(int, t2))


@pytest.mark.slow
def test_two_process_model_runner_step():
    """A real ModelRunner serving step (bucketed prefill + batched decode)
    over a 2-process x 2-device-each dp x tp mesh — the serving math, not
    a toy matmul. Greedy tokens must match the single-process runner
    bit-for-bit (same params, same inputs)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want1, want2 = _expected_tokens()
    leader = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPO_ROOT"] = repo
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MODEL_WORKER, str(rank), leader],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for rank in (0, 1)
    ]
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
    expected = (f"TOKENS {' '.join(map(str, want1))} | "
                f"{' '.join(map(str, want2))}")
    for rank, out in enumerate(outs):
        assert f"RANK{rank}_OK" in out
        assert expected in out, f"rank {rank} tokens diverged:\n{out}"
