"""IciBackend: block payloads device-to-device over the interconnect.

Generalizes disagg/ici_transfer.py's pipelined collective path into a
backend every plane can negotiate: headers (ids, seq, offsets) still
ride the TCP control connection — they carry ordering and
authorization — while the k/v bytes enter the jitted collective and
move HBM→HBM, the host touching nothing but headers. The discipline
that makes this safe is concentrated here:

- **one collective in flight** — entries are strictly ordered and
  payloads pair with headers 1:1, so a sender writes header i+1 only
  after collective i resolved; receivers serialize entries behind a
  lock.
- **seq cross-check** — the sequence number rides IN the collective
  payload and is compared against the header's: a sender that died
  between header and collective leaves an entry that pairs with a
  LATER send, and the mismatch drops the mis-paired payload instead of
  scattering bytes under the wrong block ids.
- **bounded receive** — a stranded collective recv owns its thread
  forever; it runs on a daemon thread behind ``asyncio.wait_for``, and
  a timeout abandons the plane receiver-side (stop advertising "ici";
  in-flight requests poison, future transfers ride tcp).
- **poison/balancing on send failure** — a failure BEFORE entering the
  collective leaves the receiver with an unpaired entry: pair it with
  a poison payload (seq -1 never matches) and keep the plane. A
  failure AFTER entering (or unknowable) abandons the plane — the
  distributed runtime is suspect, tcp from now on.

:class:`LoopbackIciTransfer` is the in-process stand-in with the same
interface — the loopback differentials (tests/test_transfer_plane.py)
run the full negotiation, framing, and poison discipline on CPU without
a second host.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import queue as _queue
import threading
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

DEFAULT_RECV_TIMEOUT_S = 120.0


def call_in_daemon_thread(fn, *args) -> "concurrent.futures.Future":
    """Run fn on a fresh DAEMON thread. A stranded collective recv
    blocks its thread forever; ThreadPoolExecutor workers are
    non-daemon and joined by an atexit hook, so a wedged one would
    hang interpreter shutdown — daemon threads don't."""
    fut: concurrent.futures.Future = concurrent.futures.Future()

    def work():
        try:
            result = fn(*args)
        except BaseException as e:
            if not fut.cancelled():
                fut.set_exception(e)
        else:
            if not fut.cancelled():
                fut.set_result(result)

    threading.Thread(target=work, daemon=True, name="ici-recv").start()
    return fut


async def bounded_collective_recv(recv: Callable[[int], tuple],
                                  nblocks: int,
                                  timeout_s: float) -> tuple:
    """One collective receive, bounded: ``recv(nblocks)`` runs on a
    daemon thread (it may never return — see above) behind
    ``asyncio.wait_for``. Raises ``asyncio.TimeoutError`` when the
    sender was lost after its header; the caller abandons the plane."""
    return await asyncio.wait_for(
        asyncio.wrap_future(call_in_daemon_thread(recv, nblocks)),
        timeout=timeout_s,
    )


async def settle_collective_send(loop, plane, fut, ndst: int,
                                 on_abandon: Callable[[], None]) -> None:
    """Await a collective send entered via an executor and, on failure,
    run the pairing discipline: pre-entry failures get a balancing
    poison entry (plane stays usable); entered/unknowable failures
    abandon the plane via ``on_abandon``. Always re-raises the failure
    — the caller's transfer is lost either way and must fall back."""
    from ..disagg.ici_transfer import IciSendError

    try:
        await fut
    except IciSendError as e:
        if not e.entered:
            # receiver holds an unpaired entry for this header — pair
            # it with a poison payload (seq -1 never matches) so the
            # plane stays 1:1 and REMAINS usable for the retry
            try:
                await loop.run_in_executor(
                    None, lambda n=ndst: plane.send_balancing_entry(n)
                )
                logger.warning(
                    "collective send failed before entering; balanced "
                    "the plane and keeping it"
                )
            except BaseException:
                logger.exception(
                    "balancing entry failed; abandoning the collective "
                    "plane (tcp fallback)"
                )
                on_abandon()
        else:
            # the collective itself failed — both sides' entries
            # unwound, but the distributed runtime is now suspect
            logger.exception(
                "ici collective failed; abandoning the plane "
                "(tcp fallback)"
            )
            on_abandon()
        raise
    except BaseException:
        # not even classifiable as an IciSendError (loopback doubles,
        # interpreter teardown): pairing state unknowable → abandon
        logger.exception(
            "collective send failed unclassifiably; abandoning the plane"
        )
        on_abandon()
        raise


class IciBackend:
    """One plane's handle on a collective transfer endpoint.

    Wraps an ``IciKvTransfer``-shaped object (``send``/``recv``/
    ``send_balancing_entry``/``buckets``/ranks) with the bounded-recv,
    seq-allocation, and abandonment discipline. ``alive`` flips False
    on abandonment — negotiation then routes new transfers over tcp.
    """

    name = "ici"

    def __init__(self, plane, recv_timeout_s: float = DEFAULT_RECV_TIMEOUT_S):
        self.plane = plane
        self.alive = True
        self.recv_timeout_s = recv_timeout_s
        self._seq = 0
        # collective entries are strictly ordered — serialize receives
        # across connections (the payloads pair with headers 1:1)
        self.recv_lock = asyncio.Lock()

    @property
    def sender_rank(self):
        return getattr(self.plane, "sender_rank", None)

    @property
    def receiver_rank(self):
        return getattr(self.plane, "receiver_rank", None)

    @property
    def buckets(self) -> Sequence[int]:
        return self.plane.buckets

    def abandon(self) -> None:
        self.alive = False

    def next_seq(self) -> int:
        self._seq += 1
        return self._seq

    async def send(self, k_dev, v_dev, seq: int, ndst: int) -> int:
        """Enter the collective with one frame's device arrays; returns
        payload bytes moved. Raises on failure AFTER running the
        pairing discipline (balance or abandon)."""
        loop = asyncio.get_running_loop()
        fut = loop.run_in_executor(
            None, lambda a=k_dev, b=v_dev, s=seq: self.plane.send(a, b, s)
        )
        await settle_collective_send(loop, self.plane, fut, ndst,
                                     self.abandon)
        return int(k_dev.nbytes) + int(v_dev.nbytes)

    async def recv(self, nblocks: int) -> Tuple:
        """One bounded, serialized collective receive → (k, v, seq).
        A timeout abandons the plane and re-raises — the stranded recv
        owns the plane's ordering, so it is unusable from here on."""
        try:
            async with self.recv_lock:
                return await bounded_collective_recv(
                    self.plane.recv, nblocks, self.recv_timeout_s
                )
        except asyncio.TimeoutError:
            logger.error(
                "collective recv timed out after %.0fs (sender lost "
                "after header?) — abandoning the ici plane on the "
                "receiver side", self.recv_timeout_s,
            )
            self.abandon()
            raise


class LoopbackIciTransfer:
    """In-process collective-plane double with IciKvTransfer's surface.

    One object is BOTH endpoints: ``send`` (executor thread on the
    sending side) hands device arrays to ``recv`` (daemon thread on the
    receiving side) through a depth-1 queue — the real plane's
    one-collective-in-flight pairing, minus the mesh. Arrays are passed
    by reference: nothing is host-synced or copied, so a loopback
    transfer is as zero-copy as the CPU backend allows, and tests can
    assert no whole-sequence host buffer ever materializes.

    ``fail_next_send`` arms a one-shot failure for chaos tests:
    ``"pre"`` raises before pairing (balancing discipline), ``"post"``
    after (abandonment discipline).
    """

    def __init__(self, sender_rank: int = 0, receiver_rank: int = 1,
                 buckets: Sequence[int] = (16,)):
        self.sender_rank = sender_rank
        self.receiver_rank = receiver_rank
        self.buckets = list(buckets)
        self._q: _queue.Queue = _queue.Queue(maxsize=1)
        self.fail_next_send: Optional[str] = None
        self.sent = 0
        self.balanced = 0

    def _eff(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def send(self, k, v, seq: int = 0) -> None:
        from ..disagg.ici_transfer import IciSendError

        if self.fail_next_send == "pre":
            self.fail_next_send = None
            raise IciSendError(RuntimeError("loopback chaos: pre-entry"),
                               entered=False)
        self._q.put((k, v, int(seq)))
        self.sent += 1
        if self.fail_next_send == "post":
            self.fail_next_send = None
            raise IciSendError(RuntimeError("loopback chaos: post-entry"),
                               entered=True)

    def send_balancing_entry(self, nblocks: int) -> None:
        n = self._eff(nblocks)
        self._q.put((np.zeros((1, n, 1, 1, 1), np.float32),
                     np.zeros((1, n, 1, 1, 1), np.float32), -1))
        self.balanced += 1

    def recv(self, nblocks: int) -> Tuple:
        k, v, seq = self._q.get()
        return k[:, :nblocks], v[:, :nblocks], seq
