"""HBM-to-HBM KV block transfer over ICI/DCN via XLA collectives.

The TCP plane (disagg/transfer.py) moves KV blocks device → host → socket
→ host → device; correct everywhere, but bounded by PCIe + host copies.
When the prefill and decode workers share one ``jax.distributed`` process
group (same pod slice, or cross-slice over DCN), the bytes can instead
ride the interconnect directly: both sides enter one jitted ``ppermute``
over a two-device "peer" mesh — the sender's HBM shard lands in the
receiver's HBM with XLA routing it over ICI (or DCN between slices),
no host involvement. This is the TPU-native analog of the reference's
NIXL RDMA writes (docs/disagg_serving.md:60-100,
examples/llm/utils/nixl.py:59-109): the "registered memory descriptor"
becomes a mesh + sharding, and the "RDMA put" an XLA collective.

Control flow stays on the existing TCP channel (ordering + commit): the
sender first streams an ``ici_blocks`` header (ids, bucket — no payload),
then both sides enter the collective for the bucketed block arrays. A
lost peer surfaces as the collective's timeout rather than a hung socket.

The streamed prefill pipeline (disagg/prefill_worker.py) drives this
plane PIPELINED: while one ``send`` runs in an executor thread, the next
frame's device gather (and the next prefill chunk's compute) dispatch on
the event loop — safe because ``send`` only touches its own gathered
arrays, never the runner's donated cache buffers. The 1:1 pairing
discipline is preserved by construction: at most one collective is in
flight, and frame i+1's header is written only after frame i's ``send``
resolved, so an ``IciSendError`` always classifies against the last
header sent and the balancing rules below apply unchanged.

The payload STRIPES across device pairs: the mesh is [2, P] ("peer" ×
"pair") over min(sender-local, receiver-local) devices (rounded down to
a power of two), the bucketed block axis splits into P stripes, and the
single ppermute moves every stripe concurrently over its own link — so
transfer bandwidth scales with the local device count instead of being
bounded by one ICI link (each stripe is an independent peer hop in the
same collective program).
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


logger = logging.getLogger(__name__)


class IciSendError(RuntimeError):
    """A send failed. ``entered`` tells the caller whether the collective
    was dispatched: False → the receiver's entry is still unpaired (send a
    balancing entry); True → the collective itself failed, which unwinds
    BOTH processes' entries (do not balance — there is nothing to pair)."""

    def __init__(self, cause: BaseException, entered: bool):
        super().__init__(f"ici send failed ({cause}); entered={entered}")
        self.cause = cause
        self.entered = entered


class IciKvTransfer:
    """One sender↔receiver pair of the collective transfer plane.

    Both processes construct this with the same ``(sender_rank,
    receiver_rank)`` and the same block shapes, then the sender calls
    :meth:`send` while the receiver calls :meth:`recv` — each call is one
    entry into the shared collective program, so the two sides MUST pair
    calls 1:1 (the ``ici_blocks`` header on the TCP channel provides that
    ordering). A sequence number rides INSIDE the collective payload: if
    a sender dies between header and collective, the orphaned receiver
    entry eventually pairs with a later send — the embedded seq then
    mismatches the header's and the payload is dropped instead of being
    scattered under the wrong request (see KvTransferServer).

    ``buckets`` defaults to the runner's block-op ladder so gathered
    shapes hit the compiled programs exactly; payloads larger than the
    top bucket must be chunked by the caller (PrefillWorker does).
    """

    def __init__(
        self,
        kv_block_shape: Tuple[Tuple[int, ...], Tuple[int, ...]],
        dtype,
        sender_rank: int = 0,
        receiver_rank: int = 1,
        buckets: Optional[Sequence[int]] = None,
    ):
        if buckets is None:
            from ..engine.model_runner import ModelRunner

            buckets = ModelRunner.BLOCK_OP_BUCKETS
        if jax.process_count() < 2:
            raise RuntimeError(
                "ICI kv transfer needs a multi-process jax.distributed "
                "world (use parallel.mesh.initialize_multihost)"
            )
        self.k_shape, self.v_shape = kv_block_shape  # [L, bs, KVH, D]-like
        self.dtype = dtype
        self.buckets = tuple(sorted(buckets))
        self.sender_rank = sender_rank
        self.receiver_rank = receiver_rank
        me = jax.process_index()
        if me not in (sender_rank, receiver_rank):
            raise RuntimeError(
                f"process {me} is neither sender {sender_rank} nor "
                f"receiver {receiver_rank}"
            )
        self.is_sender = me == sender_rank

        def local_devices_of(rank: int):
            devs = [d for d in jax.devices() if d.process_index == rank]
            if not devs:
                raise RuntimeError(f"no devices for process {rank}")
            return devs

        devs_s = local_devices_of(sender_rank)
        devs_r = local_devices_of(receiver_rank)
        # stripe across as many device PAIRS as both sides have; a power
        # of two keeps stripes even over the power-of-two buckets
        pairs = min(len(devs_s), len(devs_r))
        while pairs & (pairs - 1):
            pairs -= 1
        self.pairs = pairs
        # peer axis: [sender, receiver]; pair axis: the parallel links
        self.mesh = Mesh(
            np.array([devs_s[:pairs], devs_r[:pairs]]),
            ("peer", "pair"),
        )
        self.sharding = NamedSharding(self.mesh, P("peer", "pair"))
        self._programs: Dict[int, object] = {}

    # ---------- the collective ----------

    def _program(self, bucket: int):
        # key by EFFECTIVE bucket: every bucket below the pair count pads
        # to the same shapes, and duplicate XLA compiles of an identical
        # program are pure waste on compile-bound TPU hosts
        eff_key = self._eff_bucket(bucket)
        prog = self._programs.get(eff_key)
        if prog is not None:
            return prog

        def step(k_buf, v_buf, seq_buf):
            # peer 0 → peer 1 on every pair link at once; peer 1's (zero)
            # shard rotates back to 0 and is discarded — a pure shift
            # would need a conditional, and the dead shard costs the same
            # hop either way
            perm = [(0, 1), (1, 0)]
            return (
                jax.lax.ppermute(k_buf, "peer", perm),
                jax.lax.ppermute(v_buf, "peer", perm),
                jax.lax.ppermute(seq_buf, "peer", perm),
            )

        eff = self._eff_bucket(bucket)
        kb = self._local_shape(self.k_shape, eff)
        vb = self._local_shape(self.v_shape, eff)
        prog = jax.jit(
            jax.shard_map(
                step, mesh=self.mesh,
                in_specs=(P("peer", "pair"), P("peer", "pair"),
                          P("peer", "pair")),
                out_specs=(P("peer", "pair"), P("peer", "pair"),
                           P("peer", "pair")),
            ),
        )
        self._programs[eff_key] = (prog, kb, vb)
        return self._programs[eff_key]

    def _eff_bucket(self, bucket: int) -> int:
        """Bucket padded so the block axis splits evenly across pairs
        (rounded UP to a multiple — a truncating split would silently
        drop the tail stripes of non-power-of-two custom buckets)."""
        return -(-bucket // self.pairs) * self.pairs

    def _local_shape(self, shape: Tuple[int, ...], eff: int) -> Tuple[int, ...]:
        # block arrays are [L, n, bs, heads, d]; the n axis carries the
        # (padded) bucket and stripes across pairs inside _global
        return (shape[0], eff) + tuple(shape[2:])

    def bucket_for(self, nblocks: int) -> int:
        for b in self.buckets:
            if nblocks <= b:
                return b
        return self.buckets[-1]

    def _global(self, local: jnp.ndarray) -> jax.Array:
        """Local payload [L, eff_bucket, ...] → [2, P, L, stripe, ...]
        peer×pair-sharded global (this side's row populated, the peer's
        addressed by its own process)."""
        st = local.shape[1] // self.pairs
        row = 0 if self.is_sender else 1
        shards = [
            jax.device_put(
                local[:, i * st : (i + 1) * st][None, None],
                self.mesh.devices[row, i],
            )
            for i in range(self.pairs)
        ]
        return jax.make_array_from_single_device_arrays(
            (2, self.pairs, local.shape[0], st) + tuple(local.shape[2:]),
            self.sharding,
            shards,
        )

    def _stage(self, bucket: int, k_local, v_local, seq: int):
        """Device-put the peer-sharded operands. Errors here are
        PRE-entry: the collective has not been dispatched yet."""
        prog, _, _ = self._program(bucket)
        return prog, (
            self._global(k_local),
            self._global(v_local),
            self._global(jnp.full((1, 8 * self.pairs), seq, jnp.int32)),
        )

    def _enter(self, bucket: int, k_local, v_local, seq: int):
        prog, args = self._stage(bucket, k_local, v_local, seq)
        ko, vo, so = prog(*args)
        # each process addresses its own row of pair stripes; reassemble
        # them in pair order. Pulling seq to host synchronizes, so
        # collective failures surface here.
        def assemble(out):
            stripes = sorted(out.addressable_shards, key=lambda s: s.index[1])
            parts = [s.data[0, 0] for s in stripes]
            if len(parts) == 1:
                return parts[0]
            # stripes are committed to their own devices; gather them onto
            # the first local device (device-to-device hop) to hand one
            # array downstream
            dev0 = parts[0].devices().pop()
            return jnp.concatenate(
                [jax.device_put(p, dev0) for p in parts], axis=1
            )

        k_shard = assemble(ko)
        v_shard = assemble(vo)
        seq_shard = int(np.asarray(so.addressable_shards[0].data).ravel()[0])
        return k_shard, v_shard, seq_shard

    # ---------- roles ----------

    def send(self, k_blocks, v_blocks, seq: int = 0) -> None:
        """Sender side: k/v [L, n<=top bucket, bs, heads, d] device or host.

        Raises IciSendError carrying whether the collective was entered —
        the caller needs that to keep the plane's 1:1 pairing discipline.
        """
        assert self.is_sender
        n = k_blocks.shape[1]
        if n > self.buckets[-1]:
            raise ValueError(
                f"{n} blocks exceed the top transfer bucket "
                f"{self.buckets[-1]}; chunk the payload"
            )
        bucket = self.bucket_for(n)
        eff = self._eff_bucket(bucket)
        entered = False
        t0 = time.monotonic()
        try:
            k = jnp.asarray(k_blocks, self.dtype)
            v = jnp.asarray(v_blocks, self.dtype)
            if n < eff:
                pad = [(0, 0)] * k.ndim
                pad[1] = (0, eff - n)
                k = jnp.pad(k, pad)
                v = jnp.pad(v, pad)
            prog, args = self._stage(bucket, k, v, seq)
            entered = True
            # synchronize: jax dispatch is async, and a collective failure
            # must surface HERE (inside the entered=True window) for the
            # caller's pairing-discipline classification — not at some
            # unrelated later device sync
            jax.block_until_ready(prog(*args))
        except BaseException as e:
            raise IciSendError(e, entered) from e
        # collective-plane observability: each frame's seq/size/duration
        # lands in the flight ring, so a stitched-trace gap over the ici
        # hop is attributable frame by frame (thread-safe append — this
        # runs on the prefill worker's executor thread)
        from ..telemetry.flight import flight_recorder

        flight_recorder().record(
            "disagg.ici_send", seq=int(seq), blocks=int(n),
            duration_s=round(time.monotonic() - t0, 4),
        )

    def send_balancing_entry(self, nblocks: int) -> None:
        """Pair an orphaned receiver entry (header out, collective never
        entered) with a poison payload: seq -1 matches no header, so the
        receiver drops it and the plane returns to 1:1. Synchronous: a
        failure must surface to the caller, which then abandons the
        plane rather than logging it healthy."""
        assert self.is_sender
        bucket = self.bucket_for(nblocks)
        _, kb, vb = self._program(bucket)
        prog, args = self._stage(
            bucket, jnp.zeros(kb, self.dtype),
            jnp.zeros(vb, self.dtype), -1,
        )
        jax.block_until_ready(prog(*args))

    def recv(self, nblocks: int):
        """Receiver side: returns (k, v, seq) — device arrays
        [L, n, bs, heads, d] plus the seq embedded by the sender."""
        assert not self.is_sender
        bucket = self.bucket_for(nblocks)
        (prog, kb, vb) = self._program(bucket)
        k0 = jnp.zeros(kb, self.dtype)
        v0 = jnp.zeros(vb, self.dtype)
        t0 = time.monotonic()
        k, v, seq = self._enter(bucket, k0, v0, 0)
        from ..telemetry.flight import flight_recorder

        flight_recorder().record(
            "disagg.ici_recv", seq=int(seq), blocks=int(nblocks),
            duration_s=round(time.monotonic() - t0, 4),
        )
        return k[:, :nblocks], v[:, :nblocks], seq


def kv_block_shapes(config) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Transfer-plane block shapes for an EngineConfig — must agree on
    both workers (same reason block geometry is pinned in the MDC).

    Trailing dims are the LOGICAL kv dims: the runner's jitted gather
    strips the cache's lane padding and its scatter re-pads, so the
    interconnect moves only real bytes (matches the TCP wire format).
    """
    from ..models import resolve

    m = config.model
    arch = resolve(m)
    name = arch.__name__.rsplit(".", 1)[-1]
    l, bs = m.num_layers, config.kv_block_size
    if name == "deepseek":
        return (
            (l, 1, bs, 1, m.kv_lora_rank),
            (l, 1, bs, 1, m.qk_rope_head_dim),
        )
    d = m.head_dim
    return ((l, 1, bs, m.num_kv_heads, d), (l, 1, bs, m.num_kv_heads, d))
