"""Collective (ICI/DCN-analog) KV transfer plane.

Two real processes join a jax.distributed world over localhost CPU and
move KV block payloads HBM-analog → HBM-analog through the shared
ppermute program (disagg/ici_transfer.py) — the GPU-free equivalent of
the reference's NIXL RDMA path (examples/llm/utils/nixl.py:59-109).
The in-process tests cover the TCP control frames: ids ride the socket,
and a cancelled request must still enter the collective (deadlock
avoidance) while its payload is dropped.
"""

import asyncio
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer

_WORKER = r"""
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
import jax.numpy as jnp
from dynamo_tpu.disagg.ici_transfer import IciKvTransfer

K_SHAPE = (2, 1, 4, 2, 8)   # [L, n, bs, KVH, D]
V_SHAPE = (2, 1, 4, 2, 8)
xfer = IciKvTransfer(
    (K_SHAPE, V_SHAPE), jnp.float32, sender_rank=1, receiver_rank=0,
)
assert xfer.pairs == 2, xfer.pairs  # striping across both device pairs

rng = np.random.default_rng(3)
n = 3  # not a bucket size: exercises pad-to-bucket (4) + slice-back
k_blocks = rng.normal(size=(2, n, 4, 2, 8)).astype(np.float32)
v_blocks = rng.normal(size=(2, n, 4, 2, 8)).astype(np.float32)

if rank == 1:
    xfer.send(k_blocks, v_blocks, seq=41)
    # second transfer re-uses the compiled program
    xfer.send(k_blocks[:, :1] * 2.0, v_blocks[:, :1] * 2.0, seq=42)
    # a balancing entry pairs an orphaned receiver entry with seq -1
    xfer.send_balancing_entry(1)
    print("RANK1_OK", flush=True)
else:
    k, v, seq = xfer.recv(n)
    assert seq == 41, seq
    np.testing.assert_allclose(np.asarray(k), k_blocks, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v), v_blocks, rtol=1e-6)
    k2, v2, seq2 = xfer.recv(1)
    assert seq2 == 42, seq2
    np.testing.assert_allclose(np.asarray(k2), k_blocks[:, :1] * 2.0, rtol=1e-6)
    k3, v3, seq3 = xfer.recv(1)
    assert seq3 == -1, seq3            # poison payload → caller drops
    assert not np.any(np.asarray(k3))
    print("RANK0_OK", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_collective_transfer():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    leader = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPO_ROOT"] = repo
    # two virtual devices per process: the transfer stripes the payload
    # across both device pairs (the single-pair path is the degenerate
    # case of the same program)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(rank), leader],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            text=True,
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


class _StubIci:
    """Stands in for IciKvTransfer.recv on the server side."""

    def __init__(self, seq=0):
        self.calls = []
        self.seq = seq
        self.k = np.ones((2, 2, 4, 2, 8), np.float32)
        self.v = np.full((2, 2, 4, 2, 8), 2.0, np.float32)

    def recv(self, nblocks):
        self.calls.append(nblocks)
        return self.k[:, :nblocks], self.v[:, :nblocks], self.seq


async def test_ici_header_scatters_via_collective():
    ici = _StubIci(seq=9)
    scattered = []
    server = KvTransferServer(
        scatter=lambda rid, ids, k, v: scattered.append((rid, ids, k, v)),
        on_commit=lambda *a: None,
        ici_recv=ici.recv,
    )
    await server.start()
    try:
        client = await KvTransferClient("127.0.0.1", server.port).connect()
        await client.send_ici_blocks("r1", [5, 9], seq=9)
        await client.send_commit("r1", 7)
        await client.close()
    finally:
        await server.close()
    assert ici.calls == [2]
    (rid, ids, k, v), = scattered
    assert rid == "r1" and ids == [5, 9]
    np.testing.assert_array_equal(k, ici.k)
    np.testing.assert_array_equal(v, ici.v)
    assert "ici" in server.descriptor["modes"]


async def test_seq_mismatch_drops_mispaired_payload():
    """A payload whose embedded seq differs from the header's (orphaned
    collective entry pairing with a later send) must never be scattered."""
    ici = _StubIci(seq=3)
    scattered = []
    server = KvTransferServer(
        scatter=lambda rid, ids, k, v: scattered.append(rid),
        on_commit=lambda *a: None,
        ici_recv=ici.recv,
    )
    await server.start()
    try:
        client = await KvTransferClient("127.0.0.1", server.port).connect()
        await client.send_ici_blocks("r1", [5], seq=7)  # header says 7
        await client.send_commit("r1", 0)
        await client.close()
    finally:
        await server.close()
    assert ici.calls == [1]
    assert scattered == []


async def test_cancelled_request_still_enters_collective():
    """Un-authorized ici frames must still call recv (sender is already in
    the collective — skipping would deadlock both workers) but drop data."""
    ici = _StubIci()
    scattered = []
    server = KvTransferServer(
        scatter=lambda rid, ids, k, v: scattered.append(rid),
        on_commit=lambda *a: None,
        authorize=lambda rid, ids: False,
        ici_recv=ici.recv,
    )
    await server.start()
    try:
        client = await KvTransferClient("127.0.0.1", server.port).connect()
        await client.send_ici_blocks("gone", [1])
        await client.send_commit("gone", 0)
        await client.close()
    finally:
        await server.close()
    assert ici.calls == [1]   # entered the collective
    assert scattered == []    # but nothing written


async def test_commit_after_dropped_payload_is_nacked():
    """ADVICE r2 medium-1: a dropped payload (seq mismatch here) must
    poison the request's commit — the decode side would otherwise resume
    over blocks that were never scattered. The sender sees the nack; the
    decode future stays unresolved and local-prefill fallback kicks in."""
    ici = _StubIci(seq=3)
    commits = []
    server = KvTransferServer(
        scatter=lambda rid, ids, k, v: None,
        on_commit=lambda rid, *a: commits.append(rid),
        ici_recv=ici.recv,
    )
    await server.start()
    try:
        client = await KvTransferClient("127.0.0.1", server.port).connect()
        await client.send_ici_blocks("r1", [5], seq=7)  # payload mis-paired
        assert await client.send_commit("r1", 0) is False  # nacked
        # a healthy request on the same connection still commits
        ici.seq = 8
        await client.send_ici_blocks("r2", [6], seq=8)
        assert await client.send_commit("r2", 1) is True
        await client.close()
    finally:
        await server.close()
    assert commits == ["r2"]


async def test_unauthorized_tcp_frame_nacks_commit():
    """The authorize=False drop path marks the request too (TCP frames)."""
    server = KvTransferServer(
        scatter=lambda rid, ids, k, v: None,
        on_commit=lambda *a: pytest.fail("must not commit"),
        authorize=lambda rid, ids: False,
    )
    await server.start()
    try:
        client = await KvTransferClient("127.0.0.1", server.port).connect()
        k = np.zeros((1, 1, 4, 2, 8), np.float32)
        await client.send_blocks("gone", [3], k, k)
        assert await client.send_commit("gone", 0) is False
        await client.close()
    finally:
        await server.close()


async def test_ici_recv_timeout_abandons_plane():
    """ADVICE r2 medium-2: a sender lost after the header must not strand
    the handler forever — the bounded recv times out, the plane is
    abandoned receiver-side, and the request's commit is nacked."""

    class _HangIci:
        def recv(self, nblocks):
            # long enough to trip the 0.3 s bound, short enough that the
            # stranded non-daemon executor thread doesn't hold pytest's
            # interpreter exit hostage
            import time

            time.sleep(5)
            return None, None, 0

    server = KvTransferServer(
        scatter=lambda rid, ids, k, v: pytest.fail("must not scatter"),
        on_commit=lambda *a: pytest.fail("must not commit"),
        ici_recv=_HangIci().recv,
        ici_recv_timeout_s=0.3,
    )
    await server.start()
    try:
        client = await KvTransferClient("127.0.0.1", server.port).connect()
        await client.send_ici_blocks("r1", [5], seq=1)
        assert await client.send_commit("r1", 0) is False  # nacked
        assert server.ici_recv is None  # plane abandoned
        assert "ici" not in server.descriptor["modes"]
        await client.close()
    finally:
        await server.close()


_DEATH_WORKER = r"""
import os, sys, threading
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
import jax.numpy as jnp
from dynamo_tpu.disagg.ici_transfer import IciKvTransfer

K_SHAPE = (2, 1, 4, 2, 8)
xfer = IciKvTransfer(
    (K_SHAPE, K_SHAPE), jnp.float32, sender_rank=1, receiver_rank=0,
)
k = np.ones((2, 1, 4, 2, 8), np.float32)

if rank == 1:
    xfer.send(k, k, seq=7)       # one good pairing proves the plane works
    print("RANK1_DYING", flush=True)
    os._exit(1)                  # peer death BEFORE the second entry
else:
    k1, v1, seq = xfer.recv(1)
    assert seq == 7, seq
    # the sender is now dead; the unpaired recv must not hang this
    # process forever — bound it the way the serving layer does
    # (KvTransferServer.ici_recv_timeout_s) and classify the plane dead
    result = {}
    def attempt():
        try:
            result["r"] = xfer.recv(1)
        except BaseException as e:
            result["e"] = type(e).__name__
    t = threading.Thread(target=attempt, daemon=True)
    t.start()
    t.join(timeout=25.0)
    if t.is_alive():
        print("RANK0_OK survivor-bounded-timeout", flush=True)
        os._exit(0)              # daemon thread still parked in the collective
    if "e" in result:
        print("RANK0_OK survivor-error", result["e"], flush=True)
        os._exit(0)
    print("RANK0_BAD got data from a dead peer", flush=True)
    os._exit(1)
"""


def test_peer_death_mid_collective_bounds_the_survivor():
    """VERDICT r4 item 8: kill one side between paired entries. The
    survivor must classify the plane dead (error or bounded timeout) —
    never hang forever, never fabricate data. Recovery above this layer:
    the server's ici_recv_timeout_s abandons the plane and the request
    falls back to TCP/local (tests in test_disagg.py)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    leader = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPO_ROOT"] = repo
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _DEATH_WORKER, str(rank), leader],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            text=True,
        )
        for rank in (0, 1)
    ]
    try:
        out1, _ = procs[1].communicate(timeout=240)
        out0, _ = procs[0].communicate(timeout=240)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    assert "RANK1_DYING" in out1
    assert procs[1].returncode == 1  # died on purpose
    assert "RANK0_OK" in out0, out0
    assert procs[0].returncode == 0, out0
