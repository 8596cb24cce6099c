"""Paged KV block allocator with prefix-cache reuse and KV event hooks.

Semantics follow the reference's block-manager design (SURVEY.md §2.2,
reference: lib/llm/src/kv/{manager,reuse,reserved}.rs) re-designed
around the engine's flat block-id space:

- ``allocate_prompt`` stages exactly like the reference's
  ``KvStorageManager::prepare_prefill_sequence`` (kv/manager.rs:22-121):
  match INFLIGHT blocks first (refcount > 0 — another sequence is
  actively computing/holding the same prefix, reference kv/reserved.rs),
  then REUSABLE pooled blocks (refcount 0, state preserved), then take
  fresh/evicted blocks and restore any host-tier extension.
- The reuse pool is priority-ordered FIFO, not flat LRU (reference
  kv/reuse.rs AvailableBlocks): eviction pops the lowest priority class
  first and oldest-returned within a class, so important prefixes (e.g.
  system prompts) are retained longest. Priorities attach per sequence
  hash via ``set_priority`` — the reference's UpdateBlock control path.
- ``pin_blocks``/``unpin_blocks`` fence a block against reclaim while an
  out-of-band consumer (host-tier restore in flight, a KV transfer
  reading the slot) depends on its contents — the reference's fence/
  reset machinery (kv/reuse.rs fence, docstring "Synchronization").
  Freeing a pinned block defers the release until unpin.
- Completed blocks (prompt or generated) are registered by sequence hash
  and announced via the ``events`` callback — the same stream the KV-aware
  router indexes (kv_router/publisher.py).
- A second kind of page (``window_pages`` > 0; models/afmoe.py): the
  pages of layers that attend to a window are a pool of their own
  (``WindowPool``), private to a sequence, taken as it grows and given
  back as they fall behind the window. Everything above is the full
  kind's; ``usage()`` is the fuller of the two.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Callable, Dict, List, Optional, Set, Tuple

from ..tokens import compute_block_hashes


@dataclasses.dataclass
class KvEventSink:
    """Engine-side KV event hooks (no-op by default).

    The ``_cold`` pair announces cold-tier residency (kv/cold_tier.py
    spills/evictions) so the router can score a rehydratable prefix —
    discounted vs a warm hit (kv_router/scheduler.py cold_discount)."""

    on_stored: Callable[[List[int], Optional[int]], None] = lambda hashes, parent: None
    on_removed: Callable[[List[int]], None] = lambda hashes: None
    on_stored_cold: Callable[[List[int], Optional[int]], None] = (
        lambda hashes, parent: None
    )
    on_removed_cold: Callable[[List[int]], None] = lambda hashes: None


class _ReusePool:
    """Priority-ordered FIFO of refcount-0 cached blocks.

    Eviction order is (priority asc, return-tick asc): the lowest
    priority class is drained first, oldest first within a class —
    the reference's PriorityKey ordering (kv/reuse.rs:246-270).
    Implemented as a lazy-deletion heap; membership is the dict.
    """

    def __init__(self) -> None:
        self._entry: Dict[int, Tuple[int, int]] = {}  # bid → (prio, tick)
        self._heap: List[Tuple[int, int, int]] = []   # (prio, tick, bid)
        self._tick = itertools.count()

    def add(self, bid: int, priority: int = 0) -> None:
        tick = next(self._tick)
        self._entry[bid] = (priority, tick)
        heapq.heappush(self._heap, (priority, tick, bid))

    def discard(self, bid: int) -> None:
        self._entry.pop(bid, None)  # heap entry invalidated lazily

    def reprioritize(self, bid: int, priority: int) -> None:
        if bid in self._entry:
            # keeps its FIFO position within the NEW class via a new tick
            self.add(bid, priority)

    def pop(self, skip: Optional[Set[int]] = None) -> Optional[int]:
        """Evict the (priority, FIFO)-first block, skipping ``skip``."""
        deferred: List[Tuple[int, int, int]] = []
        out: Optional[int] = None
        while self._heap:
            prio, tick, bid = heapq.heappop(self._heap)
            if self._entry.get(bid) != (prio, tick):
                continue  # stale entry (discarded or reprioritized)
            if skip and bid in skip:
                deferred.append((prio, tick, bid))
                continue
            del self._entry[bid]
            out = bid
            break
        for item in deferred:  # pinned blocks keep their order
            heapq.heappush(self._heap, item)
        return out

    def __contains__(self, bid: int) -> bool:
        return bid in self._entry

    def __len__(self) -> int:
        return len(self._entry)


def window_keep_from(next_pos: int, window: int, block_size: int) -> int:
    """The first page of a context that a window layer still needs when
    its next query is at ``next_pos``: that query attends to the keys
    above ``next_pos`` − ``window``, later ones to later keys, so every
    page wholly at or below ``next_pos`` − ``window`` can go."""
    return max(0, (next_pos - window + 1) // block_size)


class WindowPool:
    """Pages of the window kind: a free list, nothing more. A page
    belongs to one sequence from ``take`` to ``give`` (no hash, no
    sharing, no tier), so what the full kind's pool does for prefixes
    has nothing to act on here. Page 0 is never handed out: a table
    entry that names no page of the sequence points at it, and nothing
    writes it."""

    def __init__(self, num_pages: int, registry) -> None:
        if num_pages < 2:
            raise ValueError(f"a window pool of {num_pages} pages holds none")
        self.num_pages = num_pages
        self.free: List[int] = list(range(num_pages - 1, 0, -1))  # pop() → page 1 first
        self._allocated = registry.counter(
            "dynamo_kv_window_pages_allocated_total",
            "Pages of the window kind handed to sequences",
        )
        self._released = registry.counter(
            "dynamo_kv_window_pages_released_total",
            "Pages of the window kind given back while their sequence ran "
            "on, because they fell behind its window",
        )

    @property
    def available(self) -> int:
        return len(self.free)

    @property
    def used(self) -> int:
        return self.num_pages - 1 - len(self.free)

    def usage(self) -> float:
        return self.used / (self.num_pages - 1)

    def take(self) -> int:
        if not self.free:
            raise MemoryError("window pages exhausted")
        self._allocated.inc()
        return self.free.pop()

    def give(self, pages, behind_window: bool = False) -> None:
        """Back to the pool; ``behind_window`` counts them as released
        (a finished or preempted sequence's are not)."""
        self.free.extend(pages)
        if behind_window:
            self._released.inc(len(pages))


class BlockAllocator:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        events: Optional[KvEventSink] = None,
        tier2=None,  # Optional[KvHostTier] — host-RAM offload tier
        registry=None,  # Optional[telemetry.MetricsRegistry]
        flight=None,  # Optional[telemetry.FlightRecorder]
        window_pages: int = 0,  # the window kind's pool (WindowPool); 0: none
    ):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self.events = events or KvEventSink()
        if flight is None:
            from ..telemetry.flight import flight_recorder

            flight = flight_recorder()
        self.flight = flight
        self.tier2 = tier2
        # evictions collected during one allocation; offloaded in a single
        # batched gather (one device round-trip) by flush_offload
        self._pending_offload: List[Tuple[int, int]] = []
        self.free: List[int] = list(range(num_blocks - 1, -1, -1))  # pop() → block 0 first
        # sequence_hash → block id (cached, complete blocks)
        self.by_hash: Dict[int, int] = {}
        self.block_hash: Dict[int, int] = {}   # block id → sequence hash
        self.refcount: Dict[int, int] = {}
        # refcount-0 cached blocks, priority-FIFO order — evictable
        self.reusable = _ReusePool()
        # sequence_hash → retention priority (default 0; higher = kept longer)
        self.hash_priority: Dict[int, int] = {}
        # fenced blocks: excluded from eviction/free until unpinned.
        # COUNTED — two consumers can fence the same block (e.g. two
        # concurrent transfers reading it); the fence holds until the
        # last unpin
        self.pinned: Dict[int, int] = {}
        self._deferred_free: List[int] = []
        # match staging telemetry (reference manager.rs staging order)
        self.matched_inflight_total = 0
        self.matched_reusable_total = 0
        if registry is None:
            from ..telemetry.registry import MetricsRegistry

            registry = MetricsRegistry()  # private; owner renders nothing
        self._evictions = registry.counter(
            "dynamo_kv_evictions_total",
            "Cached blocks evicted from the reuse pool to satisfy demand",
        )
        registry.callback_gauge(
            "dynamo_kv_active_blocks", "KV blocks in use",
            # dynrace: domain(executor)
            lambda: self.used,
        )
        registry.callback_gauge(
            "dynamo_kv_total_blocks", "KV cache capacity in blocks",
            # dynrace: domain(executor)
            lambda: self.num_blocks,
        )
        registry.callback_gauge(
            "dynamo_kv_block_usage_ratio", "used / total KV blocks",
            # dynrace: domain(executor)
            lambda: self.usage(),
        )
        self.window: Optional[WindowPool] = None
        if window_pages:
            self.window = WindowPool(window_pages, registry)
            registry.callback_gauge(
                "dynamo_kv_pool_usage_ratio",
                "used / total pages of each kind's pool, for a model with "
                "two kinds of page (kind=full|window); "
                "dynamo_kv_block_usage_ratio is the fuller of the two",
                # dynrace: domain(executor)
                lambda: [({"kind": "full"}, self.used / self.num_blocks),
                         ({"kind": "window"}, self.window.usage())],
            )

    # ---------- accounting ----------

    @property
    def available(self) -> int:
        pinned_reusable = sum(1 for b in self.pinned if b in self.reusable)
        return len(self.free) + len(self.reusable) - pinned_reusable

    @property
    def used(self) -> int:
        return self.num_blocks - self.available

    # ---------- priorities / fences ----------

    def set_priority(self, sequence_hashes: List[int], priority: int) -> None:
        """Retention priority for blocks by content hash (reference:
        kv/reuse.rs UpdateBlock). Applies to blocks already pooled and to
        any future pooling of these hashes; priority 0 blocks evict first."""
        for h in sequence_hashes:
            if priority == 0:
                self.hash_priority.pop(h, None)
            else:
                self.hash_priority[h] = priority
            bid = self.by_hash.get(h)
            if bid is not None and bid in self.reusable:
                self.reusable.reprioritize(bid, priority)

    def pin_blocks(self, block_ids: List[int]) -> None:
        """Fence blocks against reclaim: a pinned block is never evicted
        from the reuse pool, and a concurrent free defers until the LAST
        unpin — the guard for restores/transfers reading the slot
        out-of-band."""
        for bid in block_ids:
            self.pinned[bid] = self.pinned.get(bid, 0) + 1

    def unpin_blocks(self, block_ids: List[int]) -> None:
        for bid in block_ids:
            n = self.pinned.get(bid, 0) - 1
            if n > 0:
                self.pinned[bid] = n
            else:
                self.pinned.pop(bid, None)
        if self._deferred_free:
            # a block re-acquired while pinned (probe_prefix matched it and
            # _ref'd) cancels its pending free: releasing it now would make
            # a LIVE block evictable (silent KV corruption on reuse)
            self._deferred_free = [
                b for b in self._deferred_free if self.refcount.get(b, 0) == 0
            ]
            ready = [b for b in self._deferred_free if b not in self.pinned]
            self._deferred_free = [
                b for b in self._deferred_free if b in self.pinned
            ]
            if ready:
                self._release(ready)

    def fence(self) -> None:
        """Synchronization point (reference kv/reuse.rs fence): all
        offloads queued/staged so far are committed to the host tier."""
        self.flush_offload()
        if self.tier2 is not None:
            self.tier2.drain()

    # ---------- core ops ----------

    def _take_block(self) -> int:
        if self.free:
            return self.free.pop()
        bid = self.reusable.pop(skip=self.pinned)
        if bid is not None:
            self._evictions.inc()
            self.flight.record(
                "kv.eviction", block=bid,
                offloaded=self.tier2 is not None,
            )
            h = self.block_hash.pop(bid, None)
            if h is not None:
                self.by_hash.pop(h, None)
                if self.tier2 is not None:
                    # KV is still intact in the slot — queue it for host
                    # offload; flushed (batched) before the slot is written
                    self._pending_offload.append((h, bid))
                self.events.on_removed([h])
            return bid
        self.flight.record(
            "kv.oom", used=self.used, total=self.num_blocks,
            pinned=len(self.pinned),
        )
        raise MemoryError("KV cache exhausted")

    def flush_offload(self) -> None:
        """Offload all queued evictions in one batched device gather.

        Must run before the evicted slots are overwritten; callers that
        allocate with ``flush=False`` own that ordering.
        """
        if self._pending_offload:
            pending, self._pending_offload = self._pending_offload, []
            self.tier2.offload_batch(pending)

    def match_prefix(self, token_ids: List[int]) -> Tuple[List[int], List[int]]:
        """Longest HBM-cached prefix of complete blocks.
        Returns (block_ids, their sequence hashes)."""
        hashes, blocks, _host = self.probe_prefix(token_ids)
        return blocks, hashes[: len(blocks)]

    def probe_prefix(self, token_ids: List[int]):
        """One hashing pass over both tiers.

        Returns (hashes, hbm_blocks, host_hashes): the HBM-resident prefix
        blocks, then the host-tier run extending it. Feed the result into
        ``allocate_prompt(probe=...)`` so hot callers hash the prompt once.
        ``cached_tokens(probe)`` gives the restorable-token count for
        scheduling decisions (e.g. the disagg local-vs-remote verdict).
        """
        if not self.enable_prefix_caching:
            return [], [], []
        hashes = compute_block_hashes(token_ids, self.block_size)
        blocks: List[int] = []
        for h in hashes:
            bid = self.by_hash.get(h)
            if bid is None:
                break
            blocks.append(bid)
        host_hashes: List[int] = []
        if self.tier2 is not None:
            host_hashes = self.tier2.match_extension(hashes, len(blocks))
        return hashes, blocks, host_hashes

    def cached_tokens(self, probe) -> int:
        _hashes, blocks, host_hashes = probe
        return (len(blocks) + len(host_hashes)) * self.block_size

    def allocate_prompt(
        self, token_ids: List[int], probe=None
    ) -> Tuple[List[int], int]:
        """Allocate blocks for a prompt; reuse cached prefix blocks from HBM
        and restore host-tier blocks into fresh slots.

        ``probe`` may carry a just-computed ``probe_prefix`` result (valid
        only if no allocator mutation happened in between).
        Returns (block_ids covering ceil(len/bs) blocks, num_cached_tokens).
        Raises MemoryError if the demand cannot be met (caller queues).
        """
        n_needed = max(1, -(-len(token_ids) // self.block_size))
        hashes, cached_blocks, host_hashes = (
            probe if probe is not None else self.probe_prefix(token_ids)
        )
        cached_blocks = list(cached_blocks)
        host_hashes = list(host_hashes)
        # a full-prompt hit still needs the last block re-filled only if the
        # prompt ends mid-block; always recompute at least one token so the
        # engine has logits to sample from
        if (len(cached_blocks) + len(host_hashes)) * self.block_size >= len(token_ids):
            if host_hashes:
                host_hashes.pop()
            else:
                cached_blocks = cached_blocks[:-1]
        n_new = n_needed - len(cached_blocks)
        # pinning the matched prefix removes its refcount-0 blocks from the
        # evictable pool, so subtract them — otherwise _take_block could
        # exhaust mid-allocation after state was already mutated
        pinned = sum(
            1 for bid in cached_blocks
            if bid in self.reusable and bid not in self.pinned
        )
        if n_new > self.available - pinned:
            self.flight.record(
                "kv.oom", needed=n_new,
                available=self.available - pinned, total=self.num_blocks,
            )
            raise MemoryError(
                f"need {n_new} blocks, {self.available - pinned} available"
            )
        # staging telemetry: inflight (shared with a live sequence) vs
        # reusable-pool matches — the reference's two match stages
        self.matched_inflight_total += sum(
            1 for bid in cached_blocks if self.refcount.get(bid, 0) > 0
        )
        self.matched_reusable_total += sum(
            1 for bid in cached_blocks if self.refcount.get(bid, 0) == 0
        )
        for bid in cached_blocks:
            self._ref(bid)
        new_blocks = [self._take_block() for _ in range(n_new)]
        for bid in new_blocks:
            self.refcount[bid] = self.refcount.get(bid, 0) + 1
        # offload evicted blocks (one batched gather) BEFORE restore may
        # write new data into any of those same slots
        self.flush_offload()

        if host_hashes:
            # commit staged offloads first: drain applies capacity
            # eviction, and the keep-check below must see the post-drain
            # store (a staged hash can be the one capacity evicts)
            self.tier2.drain()
            # taking blocks above may itself have evicted host-tier entries
            # (capacity pressure) — keep only the still-resident prefix run
            keep = 0
            while keep < len(host_hashes) and self.tier2.has(host_hashes[keep]):
                keep += 1
            host_hashes = host_hashes[:keep]
        if host_hashes:
            restore_bids = new_blocks[: len(host_hashes)]
            # fence the restore targets for the duration of the restore
            # dispatch: nothing may reclaim a slot with a copy in flight
            self.pin_blocks(restore_bids)
            try:
                self.tier2.restore(host_hashes, restore_bids)
            finally:
                self.unpin_blocks(restore_bids)
            for i, h in enumerate(host_hashes):
                idx = len(cached_blocks) + i
                parent = hashes[idx - 1] if idx > 0 else None
                self.register_complete(restore_bids[i], h, parent)

        num_cached = (len(cached_blocks) + len(host_hashes)) * self.block_size
        return cached_blocks + new_blocks, num_cached

    def allocate_n(self, n: int) -> List[int]:
        """``n`` anonymous blocks, all-or-nothing (migration admits: a
        partial reservation would strand a half-scattered transfer).
        On MemoryError everything taken so far is released first."""
        got: List[int] = []
        try:
            for _ in range(n):
                got.append(self.allocate_block(flush=False))
        except MemoryError:
            self.free_blocks(got)
            raise
        self.flush_offload()
        return got

    def allocate_block(self, flush: bool = True) -> int:
        """One more block for a growing (decoding) sequence.

        ``flush=False`` defers the host-offload gather so a caller growing
        many sequences in one step pays one batched device round-trip; it
        must call ``flush_offload()`` before the evicted slots are written.
        """
        bid = self._take_block()
        if flush:
            self.flush_offload()
        self.refcount[bid] = self.refcount.get(bid, 0) + 1
        return bid

    def _ref(self, bid: int) -> None:
        self.refcount[bid] = self.refcount.get(bid, 0) + 1
        self.reusable.discard(bid)  # no longer evictable

    def register_complete(
        self, bid: int, sequence_hash: int, parent_hash: Optional[int]
    ) -> None:
        """A block is now full with known content — make it matchable."""
        if not self.enable_prefix_caching:
            return
        existing = self.by_hash.get(sequence_hash)
        if existing is not None and existing != bid:
            return  # identical content already cached under another block
        self.by_hash[sequence_hash] = bid
        self.block_hash[bid] = sequence_hash
        self.events.on_stored([sequence_hash], parent_hash)

    def rollback_tail(self, block_ids: List[int], keep: int) -> List[int]:
        """Release the over-allocated tail of a sequence's block list.

        The decode chain reserves block headroom against its own
        dispatch count before every dispatch; a row that finishes
        (eos/stop/max-token/cancel) deep into a chain holds blocks for
        positions it froze before reaching, which the host never
        committed. Those tail blocks are by construction anonymous
        (registration only ever covers positions below the host
        ``context_len``), so releasing them returns them straight to the
        free list. Returns the retained prefix.
        """
        keep = max(0, keep)
        tail = block_ids[keep:]
        if tail:
            self.free_blocks(tail)
        return block_ids[:keep]

    def free_blocks(self, block_ids: List[int]) -> None:
        """Release a sequence's references. Hashed blocks become reusable
        (still matchable until evicted); anonymous blocks go to the free
        list. Pinned blocks defer until ``unpin_blocks``."""
        ready: List[int] = []
        for bid in block_ids:
            rc = self.refcount.get(bid, 0) - 1
            if rc > 0:
                self.refcount[bid] = rc
                continue
            self.refcount.pop(bid, None)
            if bid in self.pinned:
                if bid not in self._deferred_free:  # re-freed after re-ref
                    self._deferred_free.append(bid)
                continue
            ready.append(bid)
        self._release(ready)

    def _release(self, block_ids: List[int]) -> None:
        removed_hashes: List[int] = []
        for bid in block_ids:
            h = self.block_hash.get(bid)
            if h is not None and self.enable_prefix_caching:
                self.reusable.add(bid, self.hash_priority.get(h, 0))
            else:
                h = self.block_hash.pop(bid, None)
                if h is not None:
                    self.by_hash.pop(h, None)
                    removed_hashes.append(h)
                self.free.append(bid)
        if removed_hashes:
            self.events.on_removed(removed_hashes)

    def usage(self) -> float:
        full = self.used / self.num_blocks if self.num_blocks else 0.0
        return full if self.window is None else max(full, self.window.usage())
