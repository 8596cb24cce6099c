"""Continuous-batching scheduler: the engine's beating heart.

An asyncio loop interleaving bucketed prefills with batched decode steps
over a fixed set of slots (static shapes → no recompiles as membership
changes). Per-request state tracks paged blocks, chained block hashes (for
prefix cache + KV events), and cooperative cancellation.

The reference outsourced all of this to vLLM/SGLang (SURVEY.md §7
"the JAX serving engine itself" is hard-part #1) — this is the native
replacement: admission → prefill (prefix-cache aware) → decode loop →
finish/free, with ForwardPassMetrics-style telemetry for the KV router.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models import PAGES_ONLY
from ..protocols.common import (
    EngineOutput,
    FinishReason,
    PreprocessedRequest,
    TokenLogprob,
)
from ..runtime.engine import AsyncEngineContext
from ..telemetry.flight import FlightRecorder, flight_recorder
from ..telemetry.registry import STEP_BUCKETS, MetricsRegistry
from ..telemetry.tracing import span
from ..tokens import TokenSequence
from ..utils import faults
from .block_allocator import BlockAllocator, KvEventSink, window_keep_from
from .config import EngineConfig
from .model_runner import ModelRunner
from .sampling import (
    STOP_ID_WIDTH,
    STOP_SEQ_WIDTH,
    SUFFIX_RING_W,
    host_row,
    ring_init,
    seed_to_key,
    short_search_rows,
    stop_id_row,
    stop_seq_rows,
    tiled_rows,
)
from .step_inputs import FED

logger = logging.getLogger(__name__)


# constrained decoding lives in engine/guided.py; re-exported here for
# callers/tests that import the trie primitives from the scheduler
from .guided import (  # noqa: F401,E402
    GUIDED_END,
    TrieConstraint,
    build_choice_trie,
    compile_device_table,
)


def ngram_propose(history: List[int], match: int, k: int) -> List[int]:
    """Prompt-lookup proposal: find the most recent earlier occurrence of
    the trailing ``match``-gram in the sequence's own history and return
    up to ``k`` tokens that followed it. Reference analog: the ngram
    speculative decoding of the engines the reference delegates to."""
    n = len(history)
    if n < match + 1 or k <= 0:
        return []
    tail = np.asarray(history[-match:], np.int64)
    h = np.asarray(history, np.int64)
    # windows over h[:-1]: every start i has at least one continuation
    # token, and the trailing gram itself (start n-match) is excluded
    win = np.lib.stride_tricks.sliding_window_view(h[:-1], match)
    hits = np.nonzero((win == tail).all(axis=1))[0]
    if hits.size == 0:
        return []
    # latest match whose continuation is full-length; else the earliest
    # (longest) one — a repetitive tail would otherwise propose almost
    # nothing because the most recent occurrence abuts the history end
    full = hits[hits + match + k <= n]
    i = int(full[-1]) if full.size else int(hits[0])
    return [int(t) for t in history[i + match: i + match + k]]


def prefill_pairs(start: int, end: int, window: int = 0):
    """(pairs under a causal mask, pairs under a window of ``window``
    keys) of the queries at positions ``[start, end)``: the query at
    ``p`` sees ``p + 1`` keys, or the last ``min(p + 1, window)``."""
    def triangle(lo, hi):       # lo + (lo + 1) + ... + (hi - 1)
        return (hi - lo) * (lo + hi - 1) // 2
    lo, hi = start + 1, end + 1                 # keys visible
    full = triangle(lo, hi)
    if not window:
        return full, 0
    under = triangle(lo, min(hi, window)) if lo < window else 0
    return full, under + window * max(0, hi - max(lo, window))


def prefill_bucket_cap(cfg: EngineConfig, rows: int = 1) -> Optional[int]:
    """Largest prefill bucket such that ``rows * bucket`` fits the
    per-step token budget (the ITL bound counts padded positions, so the
    cap is on the padded product). None when even the smallest bucket
    overruns — the caller sheds rows (the scheduler) or floors at the
    smallest bucket (the prefill worker: one chunk must still advance or
    prefill livelocks). No budget = no cap.

    Shared by the scheduler's local chunked prefill and the disagg
    prefill worker's streamed chunking — both sides MUST derive the same
    ladder or remote chunk shapes drift from local ones.
    """
    budget = cfg.max_prefill_tokens_per_step
    if not budget:
        return cfg.prefill_buckets[-1]
    allowed = [b for b in cfg.prefill_buckets if rows * b <= budget]
    return allowed[-1] if allowed else None


def build_prefill_arrays(cfg: EngineConfig, prompt: List[int], num_cached: int,
                         block_ids: List[int], bucket: Optional[int] = None):
    """Batch-of-1 arrays for one bucketed prefill step.

    Shared by the scheduler's local prefill and the disagg prefill worker.
    Returns (tokens, positions, block_tables, slot_mapping, context_lens,
    last_idx) — the leading arguments of ``ModelRunner.step``. Pass
    ``bucket`` to pad to a caller-chosen bucket (the batched prefill path
    pads every row to the batch's common bucket).
    """
    suffix = prompt[num_cached:]
    bucket = bucket or cfg.bucket_for(len(suffix))
    w = cfg.blocks_per_seq
    bs = cfg.kv_block_size

    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, : len(suffix)] = suffix
    positions = np.full((1, bucket), num_cached + len(suffix) - 1, np.int32)
    positions[0, : len(suffix)] = np.arange(num_cached, len(prompt))
    slot_map = np.full((1, bucket), -1, np.int32)
    for i, pos in enumerate(range(num_cached, len(prompt))):
        slot_map[0, i] = block_ids[pos // bs] * bs + pos % bs
    btab = np.zeros((1, w), np.int32)
    btab[0, : len(block_ids)] = block_ids
    ctx_lens = np.asarray([len(prompt)], np.int32)
    last_idx = np.asarray([len(suffix) - 1], np.int32)
    return tokens, positions, btab, slot_map, ctx_lens, last_idx


@dataclasses.dataclass
class EngineRequest:
    request_id: str
    prompt: List[int]
    req: PreprocessedRequest
    ctx: AsyncEngineContext
    out_queue: asyncio.Queue
    # sampling scalars (one slot row each; see engine/sampling.py)
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    base_key: Optional[np.ndarray] = None  # uint32[2] per-request PRNG key
    want_logprobs: bool = False
    logprobs_n: int = 0  # alternatives per token (OpenAI top_logprobs)
    # OutputOptions.prompt_logprobs: logprob of every prompt token given
    # its prefix, computed during prefill (device rows collected per
    # chunk, converted once on the final chunk)
    want_prompt_lps: bool = False
    prompt_lp_parts: List = dataclasses.field(default_factory=list)
    # sent once with the first output — a preempted request's re-prefill
    # must not recompute or re-emit them mid-stream
    prompt_lps_emitted: bool = False
    # runtime state
    slot: int = -1
    block_ids: List[int] = dataclasses.field(default_factory=list)
    # a family with two kinds of page (models/afmoe.py): the window
    # kind's pages this row holds, for the context's pages window_first,
    # window_first + 1, ... (what lies before has been given back)
    window_ids: deque = dataclasses.field(default_factory=deque)
    window_first: int = 0
    num_cached: int = 0
    context_len: int = 0          # tokens whose KV is (being) written
    pending_token: int = -1       # sampled but KV not yet written
    generated: int = 0
    # per-request accounting for the trace record (published on the
    # context at finish): prompt tokens found in the prefix cache and
    # prompt tokens the prefill computed, summed over every admission
    # (a preempted request's recompute counts), tokens decode steps
    # made, and how often the request was preempted
    cached_tokens: int = 0
    computed_tokens: int = 0
    decode_tokens: int = 0
    preemptions: int = 0
    seq: Optional[TokenSequence] = None
    registered_blocks: int = 0
    finish: Optional[FinishReason] = None
    # chunked-prefill progress (tokens of prefill_tokens with KV written)
    prefill_tokens: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0
    # preemption-resume: generated tokens already emitted before preemption;
    # re-prefilled (prompt + resume_tokens) so the stream CONTINUES
    resume_tokens: List[int] = dataclasses.field(default_factory=list)
    # guided decoding: the constraint cursor (TrieConstraint for
    # guided_choice — built at admission; JsonConstraint for guided_json
    # — attached by serving.generate, which owns the grammar cache) and
    # the token ids its mask currently allows (for sparse bias edits)
    guided: Optional[object] = None
    guided_allowed: List[int] = dataclasses.field(default_factory=list)
    # disaggregated prefill state
    remote_future: Optional[asyncio.Future] = None
    remote_deadline: float = 0.0
    remote_attempted: bool = False
    # cluster-KV-fabric prefix pull (kv/fabric.py): the in-flight pull
    # (a _PendingPull while queued in scheduler.pending_pull), whether a
    # pull was already tried (one attempt per request — the fallback
    # must not loop), and whether a committed pull pre-allocated this
    # request's blocks (``_start_prefill`` then skips allocation)
    pull: Optional[object] = None
    pull_attempted: bool = False
    pull_ready: bool = False
    # monotonic deadline before which the pull plan is not re-run for
    # this request (a no-plan outcome is sticky on the ~1 ms loop
    # cadence — the ownership view only changes on peer-event cadence)
    pull_backoff_until: float = 0.0
    # held out of LOCAL admission while another request's in-flight
    # pull fetches (part of) this prompt's prefix — cleared early by
    # that pull's commit/fallback, bounded by its deadline
    pull_hold_until: float = 0.0
    # monotonic deadline before which the remote-eligibility probe is not
    # re-run (set when a prefix-hit rejection made it pointless for a while;
    # time-based — the scheduler loop can spin every ~1 ms)
    remote_backoff_until: float = 0.0
    # telemetry: monotonic time of the last token emission (0 = none yet);
    # drives the inter-token-latency histogram and the first_token span
    last_emit_t: float = 0.0
    # the chain emitted tokens for this request since the last trace
    # mark — a ``decode_pipeline`` stage is stamped when the chained
    # segment ends (finish or barrier), so span attribution separates
    # overlapped decode from the synchronous tail
    pipeline_span_open: bool = False
    # device-resident finish detection: the admission-time classification
    # (hoisted out of the per-token hot path — _check_finish consults
    # these precomputed sets instead of re-deriving eos/stop lists every
    # token) plus the packed device stop-id row for the chained burst.
    # ``device_checkable`` means every finish condition is expressible
    # on device: eos/hidden-stop/max-tokens within STOP_ID_WIDTH, and
    # stop STRINGS only via their canonical token sequences within the
    # suffix-ring bounds (the device-approximate path). ``chain_fallback``
    # names WHY a request is not checkable so the scheduler's
    # sync-fallback counter attributes every sync pass. Guided decoding
    # is checked live at dispatch (the constraint attaches after
    # admission and its device table compiles in an executor).
    device_checkable: bool = False
    chain_fallback: Optional[str] = None
    device_frozen: bool = False  # finish came from the device mask
    fin_eos: frozenset = dataclasses.field(default_factory=frozenset)
    fin_stop: frozenset = dataclasses.field(default_factory=frozenset)
    fin_min_new: int = 0
    fin_max_new: int = 16384
    fin_stop_row: Optional[np.ndarray] = None
    # canonical stop-string token sequences (host-exact check in
    # _check_finish on EVERY path) + their packed device hash rows
    fin_stop_seqs: tuple = ()
    fin_stop_hash: Optional[np.ndarray] = None
    fin_stop_hlen: Optional[np.ndarray] = None
    # trailing emitted tokens (prompt + generated, ending with the
    # pending token): the host mirror of the burst carry's suffix ring —
    # feeds the exact stop-seq check and the chain-fill ring
    ring_tail: deque = dataclasses.field(
        default_factory=lambda: deque(maxlen=SUFFIX_RING_W)
    )
    # chain-transient flags: the guided bias row was reset to
    # logit_bias-only for a device-table chain (reinstalled at barrier),
    # and the row froze on a suffix-hash FALSE positive (resumes at the
    # barrier; gates the drain's pad handling meanwhile)
    chain_bias_reset: bool = False
    chain_fp: bool = False
    # memoized guided-table cache key (the trie key is a tuple over
    # every choice's token ids — too heavy to rebuild twice per pass)
    guided_key: Optional[tuple] = None
    # a family whose decode unit is a block (models.BlockUnit):
    # ``unkept``, a whole block at [context_len, context_len + L) whose
    # tokens were sent and whose final keys and values the row's next
    # pass writes (empty: none); behind it the block in flight, each id
    # a token or the mask id; its first ``block_first`` ids are the
    # prompt's tail (not generated, never emitted); the passes made over
    # it so far; and for each unmasked position its log-probability, top
    # alternatives and the pass that unmasked it (-1: the prompt's)
    unkept: List[int] = dataclasses.field(default_factory=list)
    block: List[int] = dataclasses.field(default_factory=list)
    block_first: int = 0
    block_pass: int = 0
    block_lps: List = dataclasses.field(default_factory=list)
    block_tops: List = dataclasses.field(default_factory=list)
    block_passes: List[int] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        self.classify_finish()

    def classify_finish(self) -> None:
        """Precompute the finish-check state once per request."""
        sc = self.req.stop_conditions
        so = self.req.sampling_options
        self.fin_min_new = self.min_new
        self.fin_max_new = self.max_new
        self.fin_eos = (
            frozenset() if sc.ignore_eos
            else frozenset(int(t) for t in (self.req.eos_token_ids or []))
        )
        self.fin_stop = frozenset(
            int(t) for t in (sc.stop_token_ids_hidden or [])
        )
        row = stop_id_row(
            self.req.eos_token_ids, sc.stop_token_ids_hidden, sc.ignore_eos
        )
        n = so.n
        self.fin_stop_row = row
        self.fin_stop_seqs = ()
        self.fin_stop_hash = None
        self.fin_stop_hlen = None
        reason = None
        if row is None:
            reason = "stop_ids_overflow"
        elif n is not None and n > 1:
            # serving fans n>1 into independent n=1 children; a direct
            # multi-choice request stays on the host path defensively
            reason = "n_gt_1"
        if sc.stop:
            seqs = [
                tuple(int(t) for t in s)
                for s in (getattr(sc, "stop_token_seqs", None) or [])
                if s
            ]
            if seqs and len(seqs) == len(sc.stop):
                # host-exact stop-seq finish applies on EVERY path (sync
                # and chained stay byte-identical); the packed hash rows
                # are the device approximation's inputs
                self.fin_stop_seqs = tuple(seqs)
                packed = stop_seq_rows(seqs)
                if packed is not None:
                    self.fin_stop_hash, self.fin_stop_hlen = packed
                elif reason is None:
                    reason = "stop_seqs_overflow"
            elif reason is None:
                # no canonical tokenizations shipped (direct engine API
                # callers): text-level stops stay a host/backend concern
                reason = "stop_seqs_unavailable"
        self.chain_fallback = reason
        self.device_checkable = reason is None

    @property
    def max_new(self) -> int:
        # `is None`, not falsy: an explicit 0 means an empty completion —
        # the serving layer fast-paths it, but the invariant lives HERE
        mt = self.req.stop_conditions.max_tokens
        return 16384 if mt is None else mt

    @property
    def min_new(self) -> int:
        return self.req.stop_conditions.min_tokens or 0


class _HostBatchState:
    """Persistent ``(B, ·)`` host-side decode arrays.

    The decode hot loop used to rebuild every sampling array (temp/
    top_k/top_p/min_p/pres/freq/rep/keys) and the block table from
    per-request Python loops on EVERY pass, even when batch membership
    was unchanged — O(B·blocks_per_seq) of pure host overhead per
    dispatch. These arrays now persist across passes and mutate only
    when a slot's occupant changes (``install``) or a live row grows
    blocks (``sync_blocks``). Rows of departed requests keep stale
    values: they ride with ``commit=False``, so nothing reads their
    outputs and the device never counts their samples.
    """

    def __init__(self, cfg: EngineConfig, window_pages: bool = False):
        b = cfg.max_batch_size
        # the window kind's table a slot (two kinds of page only): kept
        # entry by entry as pages are taken and given back; 0 names the
        # page no sequence holds
        self.wtab = (np.zeros((b, cfg.blocks_per_seq), np.int32)
                     if window_pages else None)
        self.temp = np.zeros(b, np.float32)
        self.top_k = np.zeros(b, np.int32)
        self.top_p = np.ones(b, np.float32)
        self.min_p = np.zeros(b, np.float32)
        self.pres = np.zeros(b, np.float32)
        self.freq = np.zeros(b, np.float32)
        self.rep = np.ones(b, np.float32)
        self.keys = np.zeros((b, 2), np.uint32)
        self.btab = np.zeros((b, cfg.blocks_per_seq), np.int32)
        # blocks of each row already mirrored into ``btab``
        self.synced_blocks = np.zeros(b, np.int32)
        # device-finish state (membership-static, consumed by the chained
        # burst): packed stop-token ids, the min/max token bounds, and
        # the stop-string suffix-hash targets
        self.stop_ids = np.full((b, STOP_ID_WIDTH), -1, np.int32)
        self.min_new = np.zeros(b, np.int32)
        self.max_new = np.full(b, np.iinfo(np.int32).max, np.int32)
        self.stop_hash = np.zeros((b, STOP_SEQ_WIDTH), np.uint32)
        self.stop_hlen = np.zeros((b, STOP_SEQ_WIDTH), np.int32)

    def install(self, er: "EngineRequest") -> None:
        """(Re)write one slot's rows at admission / membership change."""
        i = er.slot
        (self.temp[i], self.top_k[i], self.top_p[i], self.min_p[i],
         self.pres[i], self.freq[i], self.rep[i]) = (
            er.temperature, er.top_k, er.top_p, er.min_p,
            er.presence_penalty, er.frequency_penalty,
            er.repetition_penalty,
        )
        self.keys[i] = er.base_key
        self.min_new[i] = er.fin_min_new
        self.max_new[i] = min(er.fin_max_new, np.iinfo(np.int32).max)
        self.stop_ids[i] = (
            er.fin_stop_row if er.fin_stop_row is not None else -1
        )
        self.stop_hash[i] = (
            er.fin_stop_hash if er.fin_stop_hash is not None else 0
        )
        self.stop_hlen[i] = (
            er.fin_stop_hlen if er.fin_stop_hlen is not None else 0
        )
        n = len(er.block_ids)
        self.btab[i, :n] = er.block_ids
        self.btab[i, n:] = 0
        self.synced_blocks[i] = n
        if self.wtab is not None:
            self.wtab[i] = 0

    def sync_blocks(self, er: "EngineRequest") -> None:
        """Mirror a live row's grown (or rolled-back) block list."""
        i = er.slot
        n = len(er.block_ids)
        s = int(self.synced_blocks[i])
        if n == s:
            return
        if n < s:
            self.btab[i, n:s] = 0
        else:
            self.btab[i, s:n] = er.block_ids[s:]
        self.synced_blocks[i] = n


@dataclasses.dataclass
class _SpPrefill:
    """The in-flight sequence-parallel prefill: one oversized prompt
    advancing a mesh-wide chunk per scheduler pass. Chunks are
    dispatch-only (no host sync); the final chunk's outputs — and the
    early decode burst chained off its device-resident sampled token —
    reconcile together in ``_sp_finish``."""

    er: EngineRequest
    t0: float                       # ladder start (monotonic)
    chunks: int = 0
    final_dispatch_t: float = 0.0


@dataclasses.dataclass
class _PendingPull:
    """One in-flight prefix pull (scheduler.pending_pull entry).

    The request already holds its full prompt allocation; ``targets``
    (the pull destination blocks) are PINNED for the duration so
    nothing reclaims a slot with a scatter in flight. The scheduler
    owns both ends: pin at submit, unpin at reap — commit, fallback,
    cancel, and drain all funnel through the reap path."""

    plan: object                    # kv.fabric.PullPlan
    task: asyncio.Task              # the fabric.pull coroutine
    targets: List[int]              # destination block ids (pinned)
    hashes: List[int]               # the prompt's full hash chain
    deadline: float                 # monotonic fallback deadline


def _request_transfer(arrays) -> int:
    """Ask the runtime for each array's copy to the host now
    (``copy_to_host_async``), so that the transfer queues behind the
    program that makes the array instead of starting when ``np.asarray``
    first asks, after the program has ended. Returns how many arrays had
    the method (a host stand-in has none and is read as it is)."""
    n = 0
    for x in arrays:
        start = getattr(x, "copy_to_host_async", None)
        if start is not None:
            start()
            n += 1
    return n


@dataclasses.dataclass
class _InflightBurst:
    """One dispatched-but-unreconciled chained burst (pipeline depth 2).

    Everything the host needs to reconcile the burst AFTER later ones
    are already on device: the device-resident output arrays (synced in
    one executor hop — the loop's only host sync). Rows the device froze
    read -1 pads, which ``_apply_burst`` skips.
    """

    active: List["EngineRequest"]  # rows committed at dispatch
    toks: object                   # device [K, B] sampled tokens
    lps: object                    # device [K, B] their logprobs
    tv: object                     # device [K, B, KW] top alternatives
    ti: object
    k_steps: int
    # dispatch timestamp for the drain-lag histogram
    dispatch_t: float = 0.0
    # device-time accounting (telemetry/device_time.py): HBM bytes this
    # burst must stream and the tokens it samples, fixed at dispatch
    read_bytes: float = 0.0
    tokens: int = 0
    # chained propose-verify round (scheduler._decode_chained_spec):
    # [S, B] outputs with -1 pads past acceptance, plus the per-row
    # proposed/accepted counts for the acceptance-length histogram
    spec: bool = False
    nprop: object = None           # device [B] proposal counts
    nacc: object = None            # device [B] accepted-token counts
    # output arrays whose copy to the host was requested at dispatch
    prefetched: int = 0


@dataclasses.dataclass
class _StepInFlight:
    """One decode dispatch (``Scheduler._decode``) whose tokens the host
    has not read: what ``_decode_land`` (a block pass: ``_block_land``)
    needs to apply it, fixed at the dispatch. While it is in flight the
    next step can be built and dispatched on top of it (``ahead``), its
    continuing rows fed from ``arrays[0]`` on the device."""

    # the rows, each with the slot it had at dispatch (a row that has
    # finished since may have lost it to another)
    rows: List[Tuple["EngineRequest", int]]
    # device next_tokens, lps, top_vals, top_ids (a block pass: new_ids,
    # lps, top_vals, top_ids, the count still masked)
    arrays: list
    k_steps: int
    t_dispatch: float
    prefetched: int                # arrays whose copy to the host was requested
    read_bytes: float = 0.0        # device-time accounting, as _InflightBurst's
    # dispatched before the step before it was read
    ahead: bool = False
    # dispatched behind a prefill chunk of the same pass that nobody
    # waited for (a prompt's middle chunk): in the device's queue the
    # chunk stands before it
    behind_chunk: bool = False


class Scheduler:
    def __init__(
        self,
        runner: ModelRunner,
        config: EngineConfig,
        events: Optional[KvEventSink] = None,
        disagg=None,  # Optional[RemotePrefillCoordinator]
        draft_runner: Optional[ModelRunner] = None,
        registry: Optional[MetricsRegistry] = None,
        flight: Optional[FlightRecorder] = None,
    ):
        self.runner = runner
        self.config = config
        # what the family keeps for a sequence besides one kind of page
        # (models.SequenceState; FakeRunner test doubles keep nothing).
        # Either kind is private to its sequence: a prefix hit would hand
        # another sequence pages without the state that followed them, or
        # without the window kind's pages, so hits are blanked and no
        # block is registered; a sequence starts, and resumes after
        # preemption, by prefilling from position 0 (where a trunk with
        # records by slot zeroes its slot's)
        keeps = getattr(runner, "keeps", PAGES_ONLY)
        # the family's decode unit where it is a block of positions
        # (models.BlockUnit): the block pass is then the decode pass
        self.unit = getattr(runner, "unit", None)
        self._quotas = self.unit.quotas() if self.unit is not None else ()
        self.private_pages = keeps.private
        # (the two counters of a family with records by slot)
        self.recurrent = keeps.slots
        if (self.private_pages or self.unit is not None) and disagg is not None:
            runner.refuse_without_state("remote_prefill")
        self.disagg = disagg
        # flight recorder: the process-wide engine-event ring every layer
        # records into (telemetry/flight.py); injectable for tests
        self.flight = flight if flight is not None else flight_recorder()
        # draft-model speculation: the draft's paged cache mirrors the
        # target's block ids — every prefill chunk replays on the draft,
        # and the decode loop proposes with the draft's K-step burst
        self.draft = draft_runner
        # shared metrics registry: the scheduler's, the allocator's, and
        # (attached below) the disagg coordinator's instruments all render
        # in the frontend's single /metrics exposition
        self.registry = registry or MetricsRegistry()
        sink = events or KvEventSink()
        tier2 = None
        if config.host_kv_blocks > 0:
            from ..kv import KvHostTier

            # device-array gather: offload staging keeps the D2H copy
            # asynchronous (host_tier.drain materializes later)
            tier2 = KvHostTier(
                runner.gather_blocks_device, runner.scatter_blocks,
                config.host_kv_blocks,
            )
        cold = None
        if config.cold_tier_blocks > 0:
            from ..kv import KvColdTier

            # content-addressed spill tier: host-tier-evicted blocks
            # survive to disk; residency is advertised through the cold
            # event hooks so routers can score rehydratable prefixes
            cold = KvColdTier(
                config.cold_tier_dir, config.cold_tier_blocks,
                registry=self.registry,
                on_stored=lambda hashes, parent: sink.on_stored_cold(
                    hashes, parent),
                on_removed=lambda hashes: sink.on_removed_cold(hashes),
            )
            tier2.on_evict = cold.offer
        self.allocator = BlockAllocator(
            config.num_kv_blocks, config.kv_block_size,
            config.enable_prefix_caching, sink, tier2=tier2,
            registry=self.registry, flight=self.flight,
            # (a second pool: pages taken as a row grows and given back
            # behind the window, _take_window / _release_window)
            window_pages=(config.window_pool_pages()
                          if keeps.window_pool else 0),
        )
        self.window = self.allocator.window
        # cluster KV fabric (kv/fabric.py): cross-worker prefix pull +
        # cold-tier rehydration. Built whenever either capability is
        # configured; the CLI/discovery layer attaches the peer view
        # (event feed + pull-server descriptors) onto scheduler.fabric.
        self.fabric = None
        if (config.prefix_pull or cold is not None) \
                and config.enable_prefix_caching:
            from ..kv import KvFabric

            self.fabric = KvFabric(
                runner, self.allocator,
                engine_id=f"eng-{id(self):x}",
                block_size=config.kv_block_size,
                cold=cold,
                peer_pull=config.prefix_pull,
                min_pull_blocks=config.prefix_pull_min_blocks,
                pull_timeout_s=config.prefix_pull_timeout_s,
                registry=self.registry,
                flight=self.flight,
            )
        self.pending_pull: List[EngineRequest] = []
        # sequence-parallel long-context prefill (config.sp_size > 1,
        # docs/long_context.md): oversized prompts admitted past the
        # long_prefill_threshold_tokens class queue here and advance one
        # SP chunk per loop pass — one prompt owns the mesh at a time
        # (the program is batch-of-1 by construction)
        self.sp_queue: List[EngineRequest] = []
        self.sp_active: Optional[_SpPrefill] = None
        self.waiting: deque = deque()
        # persistent decode-step host arrays (see _HostBatchState)
        self._host = _HostBatchState(config, keeps.window_pool)
        self.pending_remote: List[EngineRequest] = []
        self.slots: List[Optional[EngineRequest]] = [None] * config.max_batch_size
        # the prefill BATCH: up to max_prefill_batch requests whose
        # chunked prefills run as rows of one step
        self.prefilling: List[EngineRequest] = []
        self.wake = asyncio.Event()
        self._rng = np.random.default_rng(config.seed)
        self._task: Optional[asyncio.Task] = None
        self._stopping = False
        # drain gate (recovery/): True stops ALL admission — local slot
        # claims, remote-prefill submits — while committed work proceeds;
        # exported in metrics() so the KV router skips this worker
        self.draining = False
        # telemetry (ForwardPassMetrics analog, SURVEY.md §2.2 KV metrics)
        self.prefix_hit_tokens = 0
        self.prefix_total_tokens = 0
        self.steps = 0
        self.passes = 0  # loop passes: the ``step`` stat of sched.* spans
        # ngram speculative decoding acceptance telemetry
        self.spec_proposed = 0
        self.spec_accepted = 0
        # the device-idle bookkeeping behind the bubble histogram, and a
        # chained-dispatch counter for tests/metrics
        self._last_burst_done_t: Optional[float] = None
        self.pipeline_bursts = 0
        # persistent decode loop (config.decode_pipeline_depth=2): bursts
        # dispatched off the device-resident carry, reconciled by the
        # async row drain. Membership is FIXED for a chain's lifetime
        # (finished rows freeze on device); it compacts only at the
        # chain barrier (admission, preemption, KV-OOM, drain, stop).
        self._chain: deque = deque()   # _InflightBurst FIFO awaiting drain
        self._chain_members: List[EngineRequest] = []
        # device (tokens, pos, gen, done, ring, gstate)
        self._chain_carry = None
        self._chain_dispatched = 0     # bursts since the chain started
        self._chain_pos0: Dict[int, int] = {}  # slot → context at start
        self._last_chain_len = 0
        # which program family the open chain runs: None (closed),
        # "plain" (decode_burst_chained) or "spec" (propose-verify
        # rounds) — switching kinds forces the barrier first
        self._chain_kind: Optional[str] = None
        # a suffix-hash stop candidate the host could not confirm (hash
        # collision): the chain closes at the next pass and the row
        # resumes byte-identically
        self._chain_fp = False
        # compiled guided device tables, shared across requests with the
        # same grammar: key → DeviceGuidedTable (None = exceeded the
        # state bound; sync path keeps the request, counted). In-flight
        # executor compiles in _guided_table_futs.
        self._guided_tables: Dict[tuple, object] = {}
        self._guided_table_futs: Dict[tuple, object] = {}
        # watchdog heartbeat: stamped at the top of EVERY loop pass, so a
        # loop wedged INSIDE a pass (hung compile, dead device sync) goes
        # stale while a healthy-but-waiting loop stays fresh
        self.last_loop_t = time.monotonic()
        # this pass has dispatched a program whose result it has not
        # fetched / has taken its turn for the frontend (sched.yield)
        self._inflight = False
        self._turn_taken = False
        # the decode step (a block family's block pass) that runs one
        # step ahead of the host (``_decode``): dispatched, its tokens
        # not read. Only a runner whose decode program takes the step
        # before's tokens on the device can be run so
        # (``ModelRunner.step(prev_tokens=)``, ``decode_block(prev_ids=)``;
        # a test's stand-in says so itself)
        self._ahead: Optional[_StepInFlight] = None
        self._feeds_tokens = getattr(runner, "feeds_tokens", False)
        # the step's two halves: the block pass's where the family's
        # decode unit is a block, one token a row otherwise
        self._halves = ((self._decode_dispatch, self._decode_land)
                        if self.unit is None
                        else (self._block_dispatch, self._block_land))
        # this pass dispatched a prefill chunk and did not wait for it
        self._chunk_unread = False
        self._build_instruments()
        if disagg is not None and getattr(disagg, "registry", None) is not None:
            self.registry.attach(disagg.registry)
        # the runner's XLA compile instruments render in this scrape too
        # (FakeRunner test doubles carry no tracker — guard)
        compiles = getattr(runner, "compiles", None)
        if compiles is not None:
            self.registry.attach(compiles.registry)
        # live device-time + roofline accounting: observations feed at
        # the loop's EXISTING reconciliation seams (executor host syncs,
        # is_ready row drains) — never an added hot-path sync
        self.device_time = getattr(runner, "device_time", None)
        if self.device_time is not None:
            self.registry.attach(self.device_time.registry)

    def _build_instruments(self) -> None:
        """Register the scheduler's Prometheus instruments (the full
        catalog is documented in docs/observability.md)."""
        reg = self.registry
        self._step_hist = reg.histogram(
            "dynamo_scheduler_step_duration_seconds",
            "One scheduler loop pass that made progress",
            buckets=STEP_BUCKETS,
        )
        self._phase_hist = reg.histogram(
            "dynamo_scheduler_phase_duration_seconds",
            "Loop-phase latency, labelled phase="
            "admission|prefill|decode|host_sync; phases are disjoint "
            "(host_sync time is carved out of its enclosing phase)",
            buckets=STEP_BUCKETS,
        )
        # device→host sync time (and the frontend's turn, which a pass
        # takes inside the window since it comes before the fetch)
        # accumulated inside the current prefill/decode phase window —
        # subtracted from that window's observation so summing phase
        # series never double-counts
        self._host_sync_s = 0.0
        self._itl_hist = reg.histogram(
            "dynamo_scheduler_inter_token_latency_seconds",
            "Gap between consecutive token emissions of one request",
            buckets=STEP_BUCKETS,
        )
        self._bubble_hist = reg.histogram(
            "dynamo_engine_decode_pipeline_bubble_seconds",
            "The gap the device sees between consecutive decode bursts, "
            "by the host's clock: the previous burst's tokens on the host "
            "(t_ready) to the next dispatch (0 when the next burst was "
            "dispatched while the previous one was still executing)",
            buckets=STEP_BUCKETS,
        )
        reg.callback_gauge(
            "dynamo_engine_decode_pipeline_depth",
            "Decode dispatch depth in effect: 2 while a chain is open "
            "(bursts in flight ahead of host reconciliation), else 1",
            # dynrace: domain(executor)
            lambda: 2 if self._chain else 1,
        )
        self._device_finished_ctr = reg.counter(
            "dynamo_engine_device_finished_rows_total",
            "Rows whose finish (eos/hidden-stop/max-tokens/model-len) "
            "was detected inside the decode burst program and frozen on "
            "device instead of ending the burst",
        )
        self._drain_lag_hist = reg.histogram(
            "dynamo_engine_decode_drain_lag_seconds",
            "Chained decode: one burst's dispatch-to-host-reconciliation "
            "lag — how far the asynchronous row drain runs behind the "
            "device",
            buckets=STEP_BUCKETS,
        )
        reg.callback_gauge(
            "dynamo_engine_decode_burst_chain_length",
            "Decode bursts dispatched since the last host barrier: the "
            "open chain's running count, else the last completed "
            "chain's length (>1 means the host barrier is no longer "
            "per burst)",
            # dynrace: domain(executor)
            lambda: self._chain_dispatched or self._last_chain_len,
        )
        self._sync_fallback_ctr = reg.counter(
            "dynamo_engine_sync_fallback_total",
            "Decode passes that fell back to the per-burst host-sync "
            "path while the persistent chain was enabled, or that read "
            "their step before the next was dispatched where the step "
            "could have run ahead (_decode), labelled "
            "reason= with the constraint that forced it (the shrunken "
            "fallback ladder: every remaining sync pass is attributed)",
        )
        self._ahead_ctr = reg.counter(
            "dynamo_scheduler_decode_ahead_total",
            "Decode steps (block passes of a family whose decode unit "
            "is a block) dispatched before the step before them was "
            "read, their continuing rows fed that step's tokens (that "
            "pass's block) on the "
            "device: over dynamo_scheduler_fetches_total{kind=\"decode\"} "
            "it is the share of steps the device did not wait for",
        )
        self._ahead_discarded_ctr = reg.counter(
            "dynamo_scheduler_decode_ahead_discarded_total",
            "Rows of such steps whose token (a block pass's row: its "
            "ids) was dropped: the row had "
            "ended (a stop token or string, a cancel) at the step before, "
            "which the host read only after this one was dispatched",
        )
        self._spec_accept_hist = reg.histogram(
            "dynamo_engine_spec_accept_length",
            "Accepted speculative tokens per propose-verify round "
            "(chained in-carry rounds; proposals that verify on-chip)",
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0),
        )
        # a family whose decode unit is a block (all stay 0 otherwise)
        self._block_row_passes = reg.counter(
            "dynamo_scheduler_block_row_passes_total",
            "Rows of block passes that held a sequence, by kind=denoise "
            "(the pass unmasked some of the row's block; it may also have "
            "kept the whole block before it) | commit (the pass only kept "
            "a block: none does, a block is kept by the pass that first "
            "denoises the next)",
        )
        self._blocks_completed = reg.counter(
            "dynamo_scheduler_blocks_completed_total",
            "Blocks made whole (by their last denoise pass: their tokens "
            "left then)",
        )
        self._block_keeps_folded = reg.counter(
            "dynamo_scheduler_block_keeps_folded_total",
            "Whole blocks whose final keys and values were kept by the "
            "first denoise pass of the block behind them (every block but "
            "a request's last, which is never kept)",
        )
        self._block_tokens = reg.counter(
            "dynamo_scheduler_block_tokens_emitted_total",
            "Tokens emitted as blocks became whole (a block's generated "
            "positions up to a finish inside it)",
        )
        self._block_passes_hist = reg.histogram(
            "dynamo_engine_block_denoise_length",
            "Denoise passes a whole block took (a length in passes)",
            buckets=tuple(float(i) for i in range(1, 34)),
        )
        self._preemptions = reg.counter(
            "dynamo_scheduler_preemptions_total",
            "Requests evicted back to the waiting queue on KV OOM",
        )
        # a family with recurrent state by slot (both stay 0 otherwise)
        self._state_resets = reg.counter(
            "dynamo_engine_recurrent_state_resets_total",
            "Sequences that started, or resumed after preemption, at "
            "position 0: the slot's recurrent state was zeroed",
        )
        self._prefix_blanked = reg.counter(
            "dynamo_engine_prefix_hits_blanked_total",
            "Admissions whose prefix-cache hit was blanked because the "
            "family keeps recurrent state the cached pages do not carry",
        )
        # sequence-parallel long-context prefill (docs/long_context.md)
        self._sp_chunks_c = reg.counter(
            "dynamo_engine_prefill_sp_chunks_total",
            "Mesh-wide sequence-parallel prefill chunks dispatched "
            "(each advances sp_prefill_bucket() tokens of one oversized "
            "prompt across the sp axis)",
        )
        self._sp_tokens_c = reg.counter(
            "dynamo_engine_prefill_sp_tokens_total",
            "Prompt tokens prefilled through the sequence-parallel "
            "program (suffix tokens only; prefix-cache hits excluded)",
        )
        reg.callback_gauge(
            "dynamo_engine_prefill_sp_axis_depth",
            "Size of the mesh's sequence-parallel axis (1 = the SP "
            "program is not built; long prompts take the dense ladder)",
            # dynrace: domain(executor)
            lambda: self.config.sp_size,
        )
        self._sp_exposed_h = reg.histogram(
            "dynamo_engine_prefill_sp_exposed_seconds",
            "Handoff exposure of one SP prefill: time after the final "
            "chunk's dispatch during which NO decode work for the "
            "request was in flight — ~0 when the early decode burst "
            "chained off the device-resident first token, else the "
            "whole final-chunk drain",
            buckets=STEP_BUCKETS,
        )
        self._spec_proposed_ctr = reg.counter(
            "dynamo_scheduler_spec_proposed_tokens_total",
            "Speculative tokens proposed (ngram or draft model)",
        )
        self._spec_accepted_ctr = reg.counter(
            "dynamo_scheduler_spec_accepted_tokens_total",
            "Speculative tokens accepted by the verify step",
        )
        reg.callback_gauge(
            "dynamo_scheduler_active_slots",
            "Batch slots currently decoding or prefilling",
            # off-loop render vs loop-side slot assignment: count over a
            # list() snapshot, never the live slot table
            # dynrace: domain(executor)
            lambda: sum(1 for s in list(self.slots) if s is not None),
        )
        reg.callback_gauge(
            "dynamo_scheduler_total_slots",
            "Configured max_batch_size",
            # dynrace: domain(executor)
            lambda: self.config.max_batch_size,
        )
        reg.callback_gauge(
            "dynamo_scheduler_slot_occupancy_ratio",
            "active_slots / total_slots",
            # dynrace: domain(executor)
            lambda: (
                sum(1 for s in list(self.slots) if s is not None)
                / self.config.max_batch_size
            ),
        )
        reg.callback_gauge(
            "dynamo_scheduler_waiting_requests",
            "Admission queue depth (local waiting + pending remote "
            "prefill + pending prefix pulls)",
            # dynrace: domain(executor)
            lambda: (len(self.waiting) + len(self.pending_remote)
                     + len(self.pending_pull)),
        )
        reg.callback_gauge(
            "dynamo_scheduler_draining_info",
            "1 while this engine is gated for drain/recovery (admission "
            "refused, routers skip it) — the fleet hub's per-worker "
            "drain-state column reads this",
            # dynrace: domain(executor)
            lambda: 1.0 if self.draining else 0.0,
        )
        reg.callback_gauge(
            "dynamo_kv_prefix_hit_ratio",
            "Prompt tokens served from the prefix cache / all prompt tokens",
            # dynrace: domain(executor)
            lambda: (
                self.prefix_hit_tokens / self.prefix_total_tokens
                if self.prefix_total_tokens else 0.0
            ),
        )

        self._prefix_hit_ctr = reg.counter(
            "dynamo_kv_prefix_hit_tokens_total",
            "Prompt tokens served from the prefix cache at admission "
            "(the ratio gauge above is cumulative; a window's own hit "
            "share is the delta of this over the lookup counter's)",
        )
        self._prefix_lookup_ctr = reg.counter(
            "dynamo_kv_prefix_lookup_tokens_total",
            "Prompt tokens looked up in the prefix cache at admission",
        )
        self._queue_wait_hist = reg.histogram(
            "dynamo_scheduler_queue_wait_seconds",
            "Time a request waited in the admission queue: its queued "
            "(or preempted) mark to its admission mark",
        )

        self._yield_ctr = reg.counter(
            "dynamo_scheduler_yield_seconds_total",
            "Time the loop spent inside sched.yield, its one turn a "
            "progressed pass for everything else on the event loop "
            "(HTTP, tokenizer, detokenizer, SSE, /metrics)",
        )
        self._yield_inflight_ctr = reg.counter(
            "dynamo_scheduler_yield_inflight_seconds_total",
            "The part of dynamo_scheduler_yield_seconds_total spent "
            "while a dispatched program's result was not yet fetched: "
            "frontend work the device's own step hides",
        )

        self._prefill_pairs_ctr = reg.counter(
            "dynamo_attention_prefill_pairs_total",
            "Query-key pairs the attention mask allows one layer, summed "
            "over the rows of every prefill chunk dispatched: kind=\"full\" "
            "a causal layer's (the query at position p sees p + 1 keys: a "
            "triangle), kind=\"window\" a layer's that sees the last "
            "sliding_window keys (min(p + 1, sliding_window): a band; "
            "counted where the model has such a window). What a prefill "
            "attention roofline divides by, times the layers of the kind",
        )
        self._prefill_chunks_ctr = reg.counter(
            "dynamo_attention_prefill_chunks_total",
            "Prefill programs dispatched, counted with "
            "dynamo_attention_prefill_pairs_total: pairs a program",
        )

        self._decode_rows_ctr = reg.counter(
            "dynamo_scheduler_decode_rows_total",
            "Rows of the decode programs dispatched: max_batch_size a "
            "step of a pass, whether or not the row held a sequence "
            "(decode, decode_burst, decode_burst_df: the programs that "
            "reach the decode kernels; a speculative burst verifies "
            "several tokens a row on another route and is not counted)",
        )
        self._decode_rows_skipped_ctr = reg.counter(
            "dynamo_scheduler_decode_rows_skipped_total",
            "The part of dynamo_scheduler_decode_rows_total that held no "
            "sequence and that the attention kernels did not walk: "
            "counted only for a program whose trace took a decode kernel "
            "with a list of live rows (0 on the XLA route, or for a "
            "trunk that hands its kernels no mask)",
        )
        self._sampling_rows_ctr = reg.counter(
            "dynamo_scheduler_sampling_rows_total",
            "Rows of the decode programs' sampling tail (penalties, "
            "filters, the draw, the log-probability), whether or not "
            "read: max_batch_size a step, counted with "
            "dynamo_scheduler_decode_rows_total",
        )
        self._sampling_rows_run_ctr = reg.counter(
            "dynamo_scheduler_sampling_rows_run_total",
            "The part of dynamo_scheduler_sampling_rows_total the tail "
            "ran on: the live rows in whole tiles while a program whose "
            "tail walks tiles has a tile of rows free, all of them "
            "otherwise (a fuller batch, a mesh of several devices, a "
            "block pass, fewer rows than two tiles)",
        )
        self._sampling_short_rows_ctr = reg.counter(
            "dynamo_scheduler_sampling_short_search_rows_total",
            "The part of dynamo_scheduler_sampling_rows_run_total whose "
            "cutoff search ran its short form, half the passes: rows of a "
            "tile (a whole batch where the tail walks none) in which "
            "every request is without logit_bias, guided mask and "
            "penalties, in a program whose head makes bfloat16 logits on "
            "one device; as the host knows it, the device looks at the "
            "tile's bias rows and penalties (sampling.short_search_rows)",
        )

        self._fetch_ctr = reg.counter(
            "dynamo_scheduler_fetch_seconds_total",
            "A synchronous wait for a device result (_fetch), by part: "
            "ready_wait = blocked after the frontend's turn until the "
            "tokens are on the host, copy = the arrays after them, hop = "
            "from the executor thread back to the scheduler's loop; "
            "kind=decode|prefill. The three sum to the host_sync phase",
        )
        self._fetches_ctr = reg.counter(
            "dynamo_scheduler_fetches_total",
            "Synchronous waits for a device result, kind=decode|prefill",
        )
        self._prefetched_ctr = reg.counter(
            "dynamo_scheduler_fetch_prefetched_total",
            "Arrays of those waits whose copy to the host was requested "
            "before the wait began (at the dispatch), kind=decode|prefill: "
            "over arrays a fetch x dynamo_scheduler_fetches_total it is "
            "1.0 where every result is a device array",
        )

    def _observe_host_sync(self, dt: float) -> None:
        self._phase_hist.observe(dt, phase="host_sync")
        self._host_sync_s += dt

    async def _frontend_turn(self) -> None:
        """``sched.yield``: the pass's one turn for everything else on
        the event loop. ``_emit`` only queues a token; the request's own
        task (detokenizer, SSE write) and the HTTP ingress of new
        requests run here. Taken between the pass's last dispatch and
        the wait for its result (``_fetch``), the turn costs the device
        nothing; a pass that fetched nothing takes it at its end."""
        if self._turn_taken:
            return
        self._turn_taken = True
        inflight = (self._inflight or bool(self._chain)
                    or self._ahead is not None)
        t0 = time.monotonic()
        with span("sched.yield", step=self.passes, inflight=int(inflight)):
            await asyncio.sleep(0)
        dt = time.monotonic() - t0
        self._host_sync_s += dt   # no phase's own time: carved out of it
        self._yield_ctr.inc(dt)
        if inflight:
            self._yield_inflight_ctr.inc(dt)

    def _decode_follows(self, finishing: List[EngineRequest]) -> bool:
        """Will this pass dispatch a decode step after the prefill it
        is in? Yes if a row decodes already, or a row of ``finishing``
        (its prompt ends here) has more than its first token to give.
        The frontend's turn then waits for that dispatch."""
        return any(er.fin_max_new - er.generated > 1 for er in finishing) \
            or any(s is not None and s not in self.prefilling
                   and not self._is_sp(s) for s in self.slots)

    async def _fetch(self, loop, kind: str, arrays, turn: bool = True,
                     tokens_at: int = 0, chaos: Optional[str] = None,
                     prefetched: Optional[int] = None):
        """Wait for the result of the pass's latest dispatch and bring it
        to the host. The copy of every array is requested first
        (``_request_transfer``, under ``sched.<kind>.request``: the calls
        into the runtime are the pass's work on the loop's thread, and
        stood in no span before): a synchronous caller comes here straight
        from its dispatch, so the transfers queue behind the step on the
        device's side, with the frontend's turn and the rest of the step
        between the request and the wait; a chained burst and a decode
        step (``_decode_dispatch``: its wait may be a pass later) made
        the request at their own dispatch and pass the count
        (``prefetched``). Then the frontend's turn (``turn``: unless a
        later dispatch of this pass will take it), then one
        ``np.asarray`` after another over the device ``arrays`` on an
        executor thread, under ``sched.<kind>.sync``. Every synchronous
        result passes here, so the wait is written once, in its parts:

        - ``sync.ready``: until the tokens (``arrays[tokens_at]``; a
          prompt-scoring prefill copies its accumulated rows first, as
          it always did) are on the host: the landing of a transfer that
          was already queued, not its start. Its end is ``t_ready``, the
          program's "result ready" stamp;
        - ``sync.copy``: the arrays after them, which the early request
          leaves on the host or close to it; what is left of it is what
          one packed result would save;
        - the hop back from the executor thread to the loop, where this
          coroutine queues behind whatever frontend task is running. It
          crosses threads, so the capture has it as ``sched.*.sync`` end
          minus ``sync.fetch`` end and the program as a counter.

        ``dynamo_scheduler_fetch_seconds_total`` has the same three by
        the host's clock (``ready_wait`` from the moment the wait began,
        so it holds what was left of the device's step); they sum to the
        host_sync phase, stamped at the same two moments: time blocked
        on the device, with the frontend's work already done.
        ``dynamo_scheduler_fetch_prefetched_total`` and the stat
        ``prefetched`` of ``sync.fetch`` count the arrays whose copy was
        requested before the wait began. ``chaos`` names a fault site
        (utils/faults.py) that wedges the executor thread first.
        Returns (host arrays, ``t_ready``)."""
        if prefetched is None:
            with span(f"sched.{kind}.request", step=self.passes):
                prefetched = _request_transfer(arrays)
        if turn:
            await self._frontend_turn()
        head, rest = arrays[:tokens_at + 1], arrays[tokens_at + 1:]

        def _nbytes(xs):
            return sum(getattr(x, "nbytes", 0) for x in xs)

        def _to_host():
            if chaos is not None:
                faults.maybe_hang(chaos)
            with span("sync.fetch", prefetched=prefetched):
                with span("sync.ready", bytes=_nbytes(head)):
                    out = [np.asarray(x) for x in head]
                t_ready = time.monotonic()
                if rest:
                    with span("sync.copy", arrays=len(rest),
                              bytes=_nbytes(rest)):
                        out.extend(np.asarray(x) for x in rest)
                return out, t_ready, time.monotonic()

        t_sync = time.monotonic()
        with span(f"sched.{kind}.sync", step=self.passes):
            out, t_ready, t_copied = await loop.run_in_executor(
                None, _to_host)
            t_resumed = time.monotonic()
        # programs run in dispatch order: nothing older is pending either
        self._inflight = False
        self._observe_host_sync(t_resumed - t_sync)
        self._fetches_ctr.inc(kind=kind)
        self._prefetched_ctr.inc(prefetched, kind=kind)
        self._fetch_ctr.inc(t_ready - t_sync, part="ready_wait", kind=kind)
        self._fetch_ctr.inc(t_copied - t_ready, part="copy", kind=kind)
        self._fetch_ctr.inc(t_resumed - t_copied, part="hop", kind=kind)
        return out, t_ready

    def _mark_admission(self, er: EngineRequest) -> None:
        """The admission mark, and the wait since the request last
        entered the queue (its ``queued`` or ``preempted`` mark)."""
        waited_from = next(
            (t for name, t in reversed(er.ctx.stages)
             if name in ("queued", "preempted")), None)
        er.ctx.add_stage("admission")
        if waited_from is not None:
            self._queue_wait_hist.observe(er.ctx.stages[-1][1] - waited_from)

    def _count_prefix_lookup(self, er: EngineRequest,
                             lookup_tokens: int) -> None:
        """One admission's prefix-cache lookup: hit and looked-up
        tokens, for the engine and for the request's trace record."""
        self.prefix_hit_tokens += er.num_cached
        self.prefix_total_tokens += lookup_tokens
        self._prefix_hit_ctr.inc(er.num_cached)
        self._prefix_lookup_ctr.inc(lookup_tokens)
        er.cached_tokens += er.num_cached
        er.computed_tokens += lookup_tokens - er.num_cached

    # ---------- public API ----------

    def start(self) -> None:
        # any compile past this point interrupts live serving — the
        # tracker tags it "late" (the recompile-storm signal)
        for r in (self.runner, self.draft):
            compiles = getattr(r, "compiles", None)
            if compiles is not None:
                compiles.mark_serving_started()
        startup = getattr(self.runner, "startup", None)
        if startup is not None:
            startup.mark("scheduler")
        if self.fabric is not None and self.fabric.cold is not None:
            # restart-warm on EVERY embedding (single-process serve,
            # tests, distributed workers): prime the cold index off-loop
            # so spilled prefixes survive a process restart. refresh()
            # is idempotent — the CLI's distributed wiring also primes.
            self.fabric.hold_task(
                asyncio.get_running_loop().run_in_executor(
                    None, self.fabric.cold.refresh
                )
            )
        self._task = asyncio.create_task(self._run())

    async def stop(self) -> None:
        self._stopping = True
        self.wake.set()
        if self._task:
            await self._task
        for er in self.pending_remote:
            if self.disagg is not None:
                self.disagg.cancel(er.request_id)
            self._finish(er, FinishReason.CANCELLED)
        self.pending_remote.clear()
        for er in self.pending_pull:
            self._release_pull(er)
            self._finish(er, FinishReason.CANCELLED)
        self.pending_pull.clear()
        if self.fabric is not None:
            await self.fabric.close()
        if self.disagg is not None:
            await self.disagg.close()

    def _prepare_request(self, er: EngineRequest) -> None:
        """Per-request host fields shared by local admission and
        migration admit (everything except the PRNG key, which a
        migrated request brings along)."""
        so = er.req.sampling_options
        (er.temperature, er.top_k, er.top_p, er.min_p, er.presence_penalty,
         er.frequency_penalty, er.repetition_penalty) = host_row(so)
        # logprobs is a COUNT: 0 = chosen token's logprob with no
        # alternatives (None = off) — bool() would drop the 0 case
        er.want_logprobs = er.req.output_options.logprobs is not None
        er.logprobs_n = int(er.req.output_options.logprobs or 0)
        er.want_prompt_lps = er.req.output_options.prompt_logprobs is not None

    def add_request(self, er: EngineRequest) -> None:
        self._prepare_request(er)
        so = er.req.sampling_options
        if so.seed is not None:
            # per-request key: seeded sampling is reproducible AND isolated
            # from batchmates (each slot samples from its own PRNG stream)
            er.base_key = seed_to_key(int(so.seed))
        else:
            er.base_key = self._rng.integers(
                0, 2**32, size=2, dtype=np.uint32
            )
        er.ctx.add_stage("queued")
        self.waiting.append(er)
        self.wake.set()

    # ---------- drain / migration surface (recovery/) ----------

    def set_draining(self, draining: bool = True) -> None:
        """Gate admission: committed work proceeds, nothing new starts.
        The flag rides the metrics() snapshot so KV routers skip this
        worker, and the watchdog treats a draining engine as stopping
        (gated queued work must not read as starvation)."""
        self.draining = draining
        self.wake.set()

    async def seize(self, hard: bool = False, timeout_s: float = 5.0) -> None:
        """Stop the loop for drain/migration.

        Graceful (``hard=False``) lets the loop run its normal exit
        barriers — every dispatched burst reconciles and streams its
        tokens — and escalates to a cancel after ``timeout_s`` (a
        half-wedged loop must not hang the drain). Hard cancels
        immediately: a loop wedged inside a pass (the watchdog-trip
        case) would never finish a barrier. Un-reconciled device work is
        abandoned — its tokens were never emitted, so the committed host
        state the migration packages stays exact.
        """
        self._stopping = True
        self.draining = True
        self.wake.set()
        task, self._task = self._task, None
        if task is not None:
            if not hard:
                try:
                    await asyncio.wait_for(asyncio.shield(task), timeout_s)
                except asyncio.TimeoutError:
                    logger.warning(
                        "graceful seize timed out after %.1fs; cancelling "
                        "the scheduler loop", timeout_s,
                    )
                    hard = True
            if hard and not task.done():
                task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            except Exception:
                logger.exception("scheduler loop raised during seize")
        if self._chain or self._ahead is not None:
            self.flight.record(
                "scheduler.burst_abandon", chained=len(self._chain),
                ahead=int(self._ahead is not None),
            )
        self._ahead = None
        self._chain.clear()
        self._chain_members = []
        self._chain_carry = None
        self._chain_dispatched = 0
        self._chain_pos0 = {}
        self._chain_kind = None
        self._chain_fp = False

    def extract_requests(self) -> List[EngineRequest]:
        """Detach every live request (slots, prefill batch, waiting
        queue, pending remote prefills) WITHOUT finishing their streams
        — the recovery controller migrates or fails each one. Requests
        keep their block lists; the caller owns releasing them (after a
        hot migration gathers the KV). Call only after ``seize``."""
        out: List[EngineRequest] = []
        for i, er in enumerate(self.slots):
            if er is None:
                continue
            self.slots[i] = None
            er.slot = -1
            out.append(er)
        self.prefilling.clear()
        # SP-mid-prefill requests migrate cold (partial KV is never
        # packaged); any dispatched chunk work is abandoned with them
        self.sp_queue.clear()
        self.sp_active = None
        while self.waiting:
            out.append(self.waiting.popleft())
        for er in self.pending_remote:
            if self.disagg is not None:
                self.disagg.cancel(er.request_id, reason="drain")
            er.remote_future = None
            out.append(er)
        self.pending_remote.clear()
        for er in self.pending_pull:
            # in-flight pulls abort; the request migrates cold (its
            # blocks hold no registered KV — packaging frees them)
            self._release_pull(er)
            out.append(er)
        self.pending_pull.clear()
        for er in out:
            self.flight.record(
                "scheduler.extract", request_id=er.request_id,
                trace_id=er.ctx.trace_id, generated=er.generated,
                blocks=len(er.block_ids),
            )
        return out

    def admit_migrated(self, er: EngineRequest, committed_tokens: List[int],
                       block_ids: List[int]) -> bool:
        """Admit a request migrated from a draining peer.

        Hot (``block_ids`` non-empty, their KV already scattered): enter
        the decode loop directly, exactly like a committed remote prefill
        — except nothing is emitted here; every token up to and
        including the pending one already streamed from the source.
        Cold: join the waiting queue; the preemption-resume machinery
        re-prefills ``prompt + resume_tokens`` and continues the stream.
        Returns False (caller frees the blocks and nacks) when no slot
        is free at install time."""
        if self.private_pages:
            self.runner.refuse_without_state("migration")
        self._prepare_request(er)
        if er.base_key is None:
            # source predates per-request keys (or state was trimmed):
            # fresh key — sampled continuations diverge from the
            # counterfactual un-migrated stream, greedy ones do not
            er.base_key = self._rng.integers(0, 2**32, size=2,
                                             dtype=np.uint32)
        er.ctx.add_stage("migration.resume")
        self.flight.record(
            "scheduler.migrate_in", request_id=er.request_id,
            trace_id=er.ctx.trace_id, hot=bool(block_ids),
            generated=er.generated,
        )
        if not block_ids:
            # cold: never try remote prefill for a resumed stream (the
            # remote path would restart from the prompt alone)
            er.remote_attempted = bool(er.resume_tokens)
            self.waiting.append(er)
            self.wake.set()
            return True
        slot = self._free_slot()
        if slot is None:
            return False
        bs = self.config.kv_block_size
        er.slot = slot
        er.block_ids = list(block_ids)
        er.context_len = len(committed_tokens)
        er.num_cached = 0
        er.resume_tokens = []
        er.seq = TokenSequence(committed_tokens, block_size=bs)
        er.registered_blocks = 0
        # every fallible step runs BEFORE the slot publishes: a failed
        # install (e.g. a geometry surprise the receiver's reserve gate
        # missed) must leave this scheduler exactly as it was — the
        # written host-state row is harmless while the slot stays empty
        self._host.install(er)
        # penalty/PRNG state: presence of the prompt plus counts of every
        # generated token (including the pending one — it was sampled and
        # emitted; only its KV write is still owed)
        gen = list(committed_tokens[len(er.prompt):])
        if er.pending_token >= 0:
            gen = gen + [er.pending_token]
        er.ring_tail.clear()
        er.ring_tail.extend(
            (list(committed_tokens)
             + ([er.pending_token] if er.pending_token >= 0 else [])
             )[-SUFFIX_RING_W:]
        )
        self.runner.set_sample_row(
            slot, er.prompt, gen,
            logit_bias=er.req.sampling_options.logit_bias,
        )
        # completed prefix blocks become matchable here too — a migrated
        # prefix seeds this worker's prefix cache
        self._register_completed_blocks(er)
        self.slots[slot] = er
        self.wake.set()
        return True

    def metrics(self) -> dict:
        active = sum(1 for s in self.slots if s is not None)
        out = {
            "request_active_slots": active,
            "request_total_slots": self.config.max_batch_size,
            "kv_active_blocks": self.allocator.used,
            "kv_total_blocks": self.allocator.num_blocks,
            "num_requests_waiting": (
                len(self.waiting) + len(self.pending_remote)
                + len(self.pending_pull)
            ),
            "gpu_cache_usage_perc": self.allocator.usage(),
            "gpu_prefix_cache_hit_rate": (
                self.prefix_hit_tokens / self.prefix_total_tokens
                if self.prefix_total_tokens else 0.0
            ),
            # KV routers exclude draining workers from every decision
            # (kv_router/scheduler.py) — the snapshot is the fastest
            # deregistration channel there is
            "draining": self.draining,
        }
        if self.config.spec_ngram_tokens or self.draft is not None:
            out["spec_proposed_tokens"] = self.spec_proposed
            out["spec_accepted_tokens"] = self.spec_accepted
        if self.config.chain_enabled:
            out["decode_pipeline_bursts"] = self.pipeline_bursts
            out["decode_burst_chain_length"] = (
                self._chain_dispatched or self._last_chain_len
            )
        if self.allocator.tier2 is not None:
            out.update(self.allocator.tier2.metrics())
        if self.fabric is not None and self.fabric.cold is not None:
            out.update(self.fabric.cold.metrics())
        if self.disagg is not None:
            out.update(self.disagg.metrics())
        return out

    # ---------- watchdog surface (telemetry/watchdog.py) ----------

    def watchdog_probe(self) -> dict:
        """Liveness snapshot the stall watchdog samples: heartbeat stamp
        of the last loop pass, the dispatch counter, and the pending-work
        breakdown (local waiting vs remote-prefill waits vs active
        slots)."""
        return {
            "heartbeat_t": self.last_loop_t,
            "steps": self.steps,
            "queue_depth": len(self.waiting),
            "pending_remote": len(self.pending_remote),
            # pull waits own their deadline (fallback → local), so the
            # watchdog must not read them as starvation — same contract
            # as remote waits
            "pending_pull": len(self.pending_pull),
            "active": sum(1 for s in self.slots if s is not None),
            # a draining engine's gated queue must not read as
            # starvation — recovery owns it now, not the watchdog
            "stopping": self._stopping or self.draining,
        }

    def request_table(self) -> List[dict]:
        """Active request snapshot for the flight artifact: every slot's
        occupant plus the waiting/pending-remote queues."""
        out = []
        for i, er in enumerate(self.slots):
            if er is None:
                continue
            out.append({
                "state": (
                    "prefilling" if er in self.prefilling
                    else "sp_prefilling" if self._is_sp(er)
                    else "decoding"
                ),
                "slot": i,
                "request_id": er.request_id,
                "trace_id": er.ctx.trace_id,
                "prompt_tokens": len(er.prompt),
                "generated": er.generated,
                "context_len": er.context_len,
                "blocks": len(er.block_ids),
                "guided": er.guided is not None,
            })
        for state, ers in (("waiting", list(self.waiting)),
                           ("pending_remote", self.pending_remote),
                           ("pending_pull", self.pending_pull)):
            out.extend({
                "state": state,
                "request_id": er.request_id,
                "trace_id": er.ctx.trace_id,
                "prompt_tokens": len(er.prompt),
                "generated": er.generated,
            } for er in ers)
        return out

    # ---------- helpers ----------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _emit(self, er: EngineRequest, token: int, logprob: Optional[float],
              top: Optional[dict] = None,
              prompt_lps: Optional[list] = None) -> None:
        self._emit_tokens(er, [token],
                          None if logprob is None else [logprob], [top],
                          prompt_lps)

    def _emit_tokens(self, er: EngineRequest, tokens: List[int],
                     logprobs: Optional[List[float]], tops: List,
                     prompt_lps: Optional[list] = None) -> None:
        """One ``EngineOutput`` of ``tokens`` (one, or a committed
        block's). A chunk of k tokens is k gaps of a k-th each in the
        inter-token histogram, as the benchmark's client counts it."""
        now = time.monotonic()
        if er.last_emit_t:
            for _ in tokens:
                self._itl_hist.observe((now - er.last_emit_t) / len(tokens))
        else:
            er.ctx.add_stage("first_token")
        er.last_emit_t = now
        out = EngineOutput(
            token_ids=list(tokens),
            finish_reason=er.finish,
            logprobs=(
                [TokenLogprob(t, lp, top)
                 for t, lp, top in zip(tokens, logprobs, tops)]
                if logprobs is not None else None
            ),
            prompt_logprobs=prompt_lps,
        )
        er.out_queue.put_nowait(out)

    def _top_row(self, er: EngineRequest, top_vals, top_ids, row: int):
        """The request's top-N alternatives dict from a step's [B, K]
        top-logprob arrays (None unless the request asked for them)."""
        if not er.want_logprobs or er.logprobs_n <= 0:
            return None
        n = min(er.logprobs_n, top_vals.shape[1])
        return {
            int(t): float(v)
            for t, v in zip(top_ids[row, :n], top_vals[row, :n])
        }

    def _finish(self, er: EngineRequest, reason: FinishReason, emit: bool = True) -> None:
        er.finish = reason
        self.flight.record(
            "scheduler.finish", request_id=er.request_id,
            trace_id=er.ctx.trace_id, reason=str(reason),
            generated=er.generated, device_finished=er.device_frozen,
        )
        er.ctx.add_stage("completion")
        er.ctx.counts.update(
            cached_tokens=er.cached_tokens,
            computed_tokens=er.computed_tokens,
            decode_tokens=er.decode_tokens, preemptions=er.preemptions,
        )
        if emit:
            er.out_queue.put_nowait(EngineOutput(token_ids=[], finish_reason=reason))
        er.out_queue.put_nowait(None)  # stream end sentinel
        if er.slot >= 0:
            self.slots[er.slot] = None
        self.allocator.free_blocks(er.block_ids)
        er.block_ids = []
        self._drop_window(er)

    def _advance_row(self, er: EngineRequest, token: int) -> None:
        """Commit ONE sampled token to host state: the previous pending
        token's KV is now written (push + register), the new token
        becomes pending, and finish checks run. The single shared
        implementation behind the synchronous decode loop, the
        speculative accept loop, and the pipeline's reconciliation —
        one copy, so the paths' streams cannot drift."""
        self._commit_kv(er, er.pending_token)
        er.pending_token = token
        self._note_token(er, token)

    def _commit_kv(self, er: EngineRequest, token: int) -> None:
        """``token``'s keys and values are written for good: the host's
        mirror of the cache takes it and a page it completes is
        registered."""
        er.seq.push(token)
        er.context_len += 1
        self._register_completed_blocks(er)

    def _note_token(self, er: EngineRequest, token: int) -> None:
        """``token`` is generated output: counts, the stop-string ring and
        the finish checks see it (every decode path's tokens pass here,
        in order)."""
        er.generated += 1
        er.decode_tokens += 1
        # the ring tail mirrors the burst carry's suffix ring (ends with
        # the pending token) — _check_finish's stop-seq compare and the
        # next chain fill both read it
        er.ring_tail.append(token)
        er.finish = self._check_finish(er, token)

    def _ensure_block_for(self, er: EngineRequest, position: int) -> bool:
        """Make sure a block exists covering ``position``."""
        bs = self.config.kv_block_size
        needed = position // bs + 1
        while len(er.block_ids) < needed:
            try:
                # flush deferred: the decode loop grows many sequences per
                # step and batches the eviction-offload gather afterwards
                er.block_ids.append(self.allocator.allocate_block(flush=False))
            except MemoryError:
                return False
        return self.window is None or self._take_window(er, needed)

    # ---------- the window kind's pages (two kinds of page) ----------

    def _release_window(self, er: EngineRequest, next_pos: int) -> None:
        """Give back the window pages no query at ``next_pos`` or later
        can see: a window layer's query at p attends to keys above p −
        sliding_window, so every page wholly at or below ``next_pos`` −
        sliding_window goes. Its table entry then names page 0, which no
        sequence holds, so a kernel that walks to it (the decode kernel
        starts at a whole chunk of pages) reads zeros under its mask.
        Called before a pass takes pages, so a row never holds more than
        ``EngineConfig.window_pages_a_row`` of them."""
        keep_from = window_keep_from(
            next_pos, self.config.model.sliding_window,
            self.config.kv_block_size)
        n = min(keep_from - er.window_first, len(er.window_ids))
        if n <= 0:
            return
        with span("sched.window.release", step=self.passes, pages=n):
            self.window.give([er.window_ids.popleft() for _ in range(n)],
                             behind_window=True)
            self._host.wtab[er.slot, er.window_first:er.window_first + n] = 0
            er.window_first += n

    def _take_window(self, er: EngineRequest, needed: int) -> bool:
        """Window pages up to the context's page ``needed`` − 1; False
        (nothing taken back) where the pool runs out."""
        held = er.window_first + len(er.window_ids)
        if needed - held > self.window.available:
            return False
        for page in range(held, needed):
            bid = self.window.take()
            er.window_ids.append(bid)
            self._host.wtab[er.slot, page] = bid
        return True

    def _drop_window(self, er: EngineRequest) -> None:
        """A finished or preempted row's window pages, all of them."""
        if er.window_ids:
            self.window.give(er.window_ids)
        er.window_ids = deque()
        er.window_first = 0

    def _register_completed_blocks(self, er: EngineRequest) -> None:
        """Hash-register blocks whose KV is complete (matchable + KV events).

        ``er.seq`` mirrors exactly the tokens whose KV sits in cache, so its
        frozen blocks line up 1:1 with ``er.block_ids``. Nothing is
        registered for a family with recurrent state or two kinds of
        page: a later sequence could take the pages but not the state
        that followed them, nor the window kind's pages."""
        if self.private_pages:
            return
        n_complete = min(er.context_len // self.config.kv_block_size, len(er.seq.blocks))
        for i in range(er.registered_blocks, n_complete):
            blk = er.seq.blocks[i]
            self.allocator.register_complete(
                er.block_ids[i], blk.sequence_hash, blk.parent_sequence_hash
            )
        er.registered_blocks = max(er.registered_blocks, n_complete)

    # ---------- the loop ----------

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while not self._stopping:
            progressed = False
            pass_t0 = time.monotonic()
            # watchdog heartbeat (telemetry/watchdog.py): a wedge INSIDE
            # this pass — hung compile, dead host sync — leaves it stale
            self.last_loop_t = pass_t0
            self.passes += 1
            self._inflight = self._turn_taken = False
            self._chunk_unread = False

            # the sched.* spans (telemetry/tracing.span) put this pass's
            # seams into the profiler's trace. They follow one another
            # and never overlap; no span wraps the whole pass, which
            # would cover every device-idle gap and name none. Leaf
            # spans hold no await (sched.admit's only one is the remote
            # prefill submit of a disaggregated engine); only
            # sched.*.sync, sched.yield and sched.wait cross one.
            # A pass is admit, build, dispatch, request (of the result's
            # copy to the host), yield, sync, emit: the
            # frontend's turn (sched.yield) comes while the device
            # computes what the pass dispatched, and at the pass's end
            # only where it fetched nothing (_frontend_turn). Where the
            # decode step runs ahead (_decode), sync and emit are of the
            # step the pass before dispatched, and this pass's own is
            # left in flight.
            with span("sched.admit", step=self.passes):
                # drop cancelled requests (client disconnects / kills)
                for er in list(self.waiting):
                    if er.ctx.is_stopped:
                        self.waiting.remove(er)
                        self._finish(er, FinishReason.CANCELLED)
                for er in [s for s in self.slots if s is not None]:
                    if er.ctx.is_stopped:
                        if er in self.prefilling:
                            self.prefilling.remove(er)
                        self._sp_drop(er)
                        self._finish(er, FinishReason.CANCELLED)

                # remote prefill completions / cancellations / timeouts
                if self.pending_remote:
                    progressed |= self._reap_remote()

                # prefix-pull completions / fallbacks / timeouts
                if self.pending_pull:
                    progressed |= self._reap_pulls()

                # admission, pulls first: a prefix pull is only a block
                # reservation + a transfer (no local compute), and a pulled
                # prefix shrinks the suffix every later decision (remote
                # prefill, local chunking) sees
                t_adm = time.monotonic()
                admitted = False
                if (self.fabric is not None and not self.draining
                        and self.fabric.may_hold_any()):
                    for er in list(self.waiting):
                        if len(self.pending_pull) >= self.config.max_batch_size:
                            break
                        if self._try_submit_pull(er):
                            self.waiting.remove(er)
                            progressed = admitted = True
                if self.disagg is not None and not self.draining:
                    for er in list(self.waiting):
                        if (len(self.pending_remote)
                                >= self.config.max_batch_size):
                            break
                        if await self._try_submit_remote(er):
                            self.waiting.remove(er)
                            progressed = admitted = True

                # local admission: claim a slot + blocks, join the prefill
                # batch (up to max_prefill_batch prompts prefill together).
                # Requests held for an overlapping in-flight prefix pull
                # (pull_hold_until) are skipped, not admitted to recompute
                # what the pull is about to install; everyone else keeps
                # FIFO order.
                # both ladders honor the prefill-batch cap: SP-routed
                # admissions pre-allocate their WHOLE prompt's blocks while
                # the single-owner ladder serves one prompt at a time, so an
                # unbounded sp_queue would pin the block pool idle and
                # preempt-thrash live decode streams — oversize backlogs
                # wait block-free in `waiting`, exactly like the dense path
                while (self.waiting
                       and not self.draining
                       and len(self.prefilling) < self.config.max_prefill_batch
                       and (len(self.sp_queue)
                            + (1 if self.sp_active is not None else 0)
                            < self.config.max_prefill_batch)
                       and self._free_slot() is not None):
                    now_h = time.monotonic()
                    er = next((e for e in self.waiting
                               if e.pull_hold_until <= now_h), None)
                    if er is None:
                        break  # everyone waiting is held on a pull
                    try:
                        self._start_prefill(er)
                    except MemoryError:
                        break  # no memory — wait for a sequence to finish
                    self.waiting.remove(er)
                    progressed = admitted = True
                if admitted:
                    self._phase_hist.observe(
                        time.monotonic() - t_adm, phase="admission"
                    )

            # one prefill step (≤ max_prefill_tokens_per_step tokens,
            # split across the batch) per loop pass, interleaved with the
            # decode step below so active streams keep a bounded ITL
            # while prompts prefill (reference analog: chunked-prefill +
            # batching of the engines behind
            # examples/llm/components/worker.py:72-74)
            if self.prefilling:
                t_pf = time.monotonic()
                self._host_sync_s = 0.0
                # prefill work interleaves into the device stream: the
                # burst-to-burst idle clock no longer means anything
                self._last_burst_done_t = None
                await self._prefill_chunk(loop, list(self.prefilling))
                self._phase_hist.observe(
                    max(0.0, time.monotonic() - t_pf - self._host_sync_s),
                    phase="prefill",
                )
                progressed = True

            # sequence-parallel long-context ladder: one mesh-wide chunk
            # per pass (dispatch-only until the final chunk), so decode
            # ITL stays bounded while a 128k prompt prefills across the
            # slice
            if self.sp_active is not None or self.sp_queue:
                t_sp = time.monotonic()
                self._host_sync_s = 0.0
                self._last_burst_done_t = None
                if await self._sp_advance(loop):
                    self._phase_hist.observe(
                        max(0.0,
                            time.monotonic() - t_sp - self._host_sync_s),
                        phase="prefill",
                    )
                    progressed = True

            # decode every active slot: one token, or a fused K-step
            # burst (multi_step_decode) when nothing is waiting on the
            # runner — prefill work pins K to 1 so chunked-prefill
            # interleaving (bounded TTFT) is never traded for throughput
            active = [
                s for s in self.slots
                if s is not None and s not in self.prefilling
                and not self._is_sp(s)
            ]
            if active:
                t_dec = time.monotonic()
                self._host_sync_s = 0.0
                runner_idle = not (self.prefilling or self.waiting
                                   or self.pending_remote
                                   or self.sp_active is not None
                                   or self.sp_queue)
                speculating = (
                    self.config.spec_ngram_tokens > 0
                    or self.draft is not None
                )
                spec_now = (speculating and runner_idle
                            and all(self._spec_eligible(er) for er in active))
                # depth 2: why can this pass NOT chain off the device
                # carry? (None = it can)
                chain_on = self.config.chain_enabled
                refused = None
                if chain_on:
                    refused = (
                        self._spec_chain_reason(active) if spec_now
                        else self._chain_block_reason(active, runner_idle)
                    )
                if (chain_on and refused is None) or spec_now:
                    # these paths build from committed state
                    await self._land_ahead(
                        loop, "spec" if spec_now else "chain")
                    active = [er for er in active if er.finish is None]
                if not active:
                    pass
                elif chain_on and refused is None:
                    if spec_now:
                        # persistent loop, speculative: chain
                        # propose-verify rounds off the device-resident
                        # carry — no host barrier between draft/target
                        # rounds
                        await self._decode_chained_spec(loop, active)
                    else:
                        # persistent loop: chain the next burst off the
                        # device-resident carry; finished rows freeze on
                        # device and drain asynchronously
                        await self._decode_chained(loop, active)
                else:
                    if refused:
                        # the chain did not engage this pass: attribute
                        # the sync fallback to its reason (every
                        # remaining sync pass at depth 2 is named)
                        self._note_sync_fallback(refused)
                    await self._chain_barrier(loop)
                    active = [er for er in active if er.finish is None]
                    if not active:
                        pass
                    elif spec_now:
                        # speculative verify (ngram or draft-model
                        # proposals) on the host sync path
                        await self._decode_spec(loop, active)
                    else:
                        k_steps = self.config.multi_step_decode
                        if k_steps > 1 and not runner_idle:
                            k_steps = 1
                        await self._decode(loop, active, k_steps)
                self._phase_hist.observe(
                    max(0.0, time.monotonic() - t_dec - self._host_sync_s),
                    phase="decode",
                )
                progressed = True
            elif self._ahead is not None:
                # every row of the step in flight was cancelled: its
                # tokens are read and dropped
                await self._land_ahead(loop, "no_rows")
                progressed = True
            elif self._chain or self._chain_members:
                # every chained row finished or was cancelled while the
                # chain was still dispatching: reconcile the queue and
                # close the chain (frozen rows' pads apply as no-ops)
                await self._chain_barrier(loop)
                progressed = True

            # materialize staged host-tier offloads now that this pass's
            # device work is already dispatched: the D2H copies overlapped
            # the step; drain only waits out any straggler
            if self.allocator.tier2 is not None:
                self.allocator.tier2.drain()

            if not progressed:
                self.wake.clear()
                # about to sleep: the device-idle clock must not count
                # request-starved idle as a pipeline bubble
                self._last_burst_done_t = None
                if self.device_time is not None:
                    self.device_time.idle()
                # no runnable work: idle no scheduler change recovers
                with span("sched.wait", step=self.passes):
                    if not self.waiting and not any(self.slots):
                        if self.pending_remote or self.pending_pull:
                            # sleep but wake on remote/pull completion —
                            # the bounded wait keeps deadline checks live
                            # even if a stalled pull never completes its
                            # future
                            try:
                                await asyncio.wait_for(
                                    self.wake.wait(), timeout=0.5)
                            except asyncio.TimeoutError:
                                pass
                        else:
                            await self.wake.wait()
                    else:
                        await asyncio.sleep(0.001)
            else:
                # a pass that fetched nothing (only reaped or admitted,
                # a prompt's middle chunk, the chain's bursts already in
                # flight) has not given the frontend its turn yet: the
                # loop never spins without one
                await self._frontend_turn()
                self._step_hist.observe(time.monotonic() - pass_t0)

        # stopping: reconcile the step in flight and any chained burst so
        # no sampled tokens are silently dropped and no device work is
        # abandoned
        await self._land_ahead(loop, "stop")
        await self._chain_barrier(loop)

    # ---------- persistent decode loop (decode_pipeline_depth=2) ----------

    async def _apply_burst(self, loop, infl: _InflightBurst,
                           ready_hint: Optional[float] = None) -> None:
        """Host half of the chain: sync one burst's sampled tokens (the
        decode loop's ONLY host sync), emit/stream them, and run the
        finish checks that mirror the device's freeze verdicts.

        ``ready_hint`` is the moment an ``is_ready`` probe saw the
        outputs materialized (the async row drain) — the device-time
        observation below prefers it over the fetch's own ``t_ready`` so
        drain lag is not charged as device compute."""
        # chaos site: DYN_FAULT=decode_burst_hang wedges the executor
        # thread — the exact executor-side shape of a hung Mosaic compile
        # or a dead device mid-sync (utils/faults.py). The chain's
        # bursts are in flight already, and so are their transfers
        # (requested at the burst's dispatch): neither the turn nor the
        # request is this fetch's.
        # Spec rounds carry no logprob outputs (spec-eligible rows want
        # none) but do carry acceptance accounting.
        arrays = ([infl.toks, infl.nprop, infl.nacc] if infl.spec
                  else [infl.toks, infl.lps, infl.tv, infl.ti])
        got, t_ready = await self._fetch(
            loop, "decode", arrays, turn=False, chaos="decode_burst_hang",
            prefetched=infl.prefetched)
        lpn = tv = ti = nprop = nacc = None
        if infl.spec:
            toks, nprop, nacc = got
        else:
            toks, lpn, tv, ti = got
        with span("sched.decode.emit", step=self.passes,
                  rows=len(infl.active)):
            self._last_burst_done_t = t_ready
            if self.device_time is not None and infl.dispatch_t:
                self.device_time.observe(
                    "decode_burst_df", "decode", infl.dispatch_t,
                    ready_hint if ready_hint is not None else t_ready,
                    read_bytes=infl.read_bytes, tokens=infl.tokens,
                )
            for j in range(infl.k_steps):
                for er in infl.active:
                    if er.finish is not None:
                        continue  # finished/cancelled: frozen pads discarded
                    token = int(toks[j, er.slot])
                    if token < 0:
                        if er.chain_fp:
                            continue  # already flagged: resumes at barrier
                        if infl.spec and j > 0:
                            # spec rounds pad past the acceptance length —
                            # every LIVE row still emits its correction at
                            # j=0, so only a j=0 pad means a frozen row
                            continue
                        if (er.fin_stop_hash is not None
                                and er.finish is None):
                            # the device's suffix-hash stop candidate froze
                            # this row, but the host's EXACT token-suffix
                            # check (_check_finish, ran on every emitted
                            # token above) never fired: a hash collision.
                            # Flag it — the chain closes at the next pass
                            # and the row resumes byte-identically from its
                            # committed state (no tokens were lost: frozen
                            # rows never over-decode).
                            er.chain_fp = True
                            self._chain_fp = True
                            self._note_sync_fallback("stop_false_positive")
                            self.flight.record(
                                "scheduler.stop_false_positive",
                                request_id=er.request_id,
                                trace_id=er.ctx.trace_id,
                                generated=er.generated,
                            )
                            continue
                        # -1 pad: the device froze this row at an earlier
                        # step, whose application above set er.finish. A pad
                        # with NO host verdict means the device mask and the
                        # host mirror diverged — finishing the row loudly
                        # beats decoding a frozen zombie forever.
                        logger.error(
                            "device froze %s without a host finish verdict "
                            "(device_finish_mask / _check_finish mirror "
                            "divergence?); forcing STOP", er.request_id,
                        )
                        er.finish = FinishReason.STOP
                        # emit=True: unlike the normal path, no preceding
                        # _emit carried the finish_reason — the client must
                        # still see one before the stream sentinel
                        self._finish_chained(er, emit=True)
                        continue
                    self._advance_row(er, token)
                    if er.guided is not None:
                        # chained guided rows: advance the host cursor
                        # (verdicts only — the device computed the mask; the
                        # barrier reinstalls the host mask if needed)
                        self._guided_after_token(er, edit=False)
                    er.pipeline_span_open = True
                    self._emit(
                        er, token,
                        (float(lpn[j, er.slot])
                         if (lpn is not None and er.want_logprobs) else None),
                        (self._top_row(er, tv[j], ti[j], er.slot)
                         if tv is not None else None),
                    )
                    if er.finish is not None:
                        # the device's mask froze this row at exactly
                        # this step — the host check is the mirror that
                        # names the reason and finalizes bookkeeping
                        er.device_frozen = True
                        self._device_finished_ctr.inc()
                        self._finish_chained(er)
            if infl.spec and nprop is not None:
                for er in infl.active:
                    p = int(nprop[er.slot])
                    if p <= 0:
                        continue  # frozen rows propose nothing this round
                    a = int(nacc[er.slot])
                    self.spec_proposed += p
                    self.spec_accepted += min(a, p)
                    self._spec_proposed_ctr.inc(p)
                    self._spec_accepted_ctr.inc(min(a, p))
                    self._spec_accept_hist.observe(float(a))

    def _finish_chained(self, er: EngineRequest, emit: bool = False) -> None:
        """A chained row finished: roll the headroom blocks the chain
        reserved ahead of it (``_chain_reserve``; the frozen row never
        wrote them) back into the allocator, stamp the
        ``decode_pipeline`` span, and free the slot.

        Rolling back under queued bursts is harmless: the blocks are
        anonymous (never registered), frozen rows write no KV, and
        device dispatch ordering lands earlier writes before any later
        program's writes to a reallocated slot.
        """
        bs = self.config.kv_block_size
        keep = -(-er.context_len // bs)  # blocks covering committed KV
        rolled = max(0, len(er.block_ids) - keep)
        er.block_ids = self.allocator.rollback_tail(er.block_ids, keep)
        self.flight.record(
            "scheduler.rollback", request_id=er.request_id,
            trace_id=er.ctx.trace_id, blocks=rolled,
            reason=str(er.finish),
        )
        self._host.sync_blocks(er)
        if er.pipeline_span_open:
            er.ctx.add_stage("decode_pipeline")
            er.pipeline_span_open = False
        # emit=False on the normal path: the finishing token's _emit
        # already carried the finish_reason. The mirror-divergence
        # fallback passes emit=True — nothing was emitted there.
        self._finish(er, er.finish, emit=emit)

    # bursts allowed in flight ahead of the async drain: beyond this the
    # dispatcher waits out the oldest sync (the device has CHAIN_MAX
    # bursts queued — it cannot run dry while the host catches up), so
    # per-burst device output buffers stay bounded
    CHAIN_MAX_INFLIGHT = 4

    def _note_sync_fallback(self, reason: str) -> None:
        self._sync_fallback_ctr.inc(reason=reason)

    def _chain_block_reason(self, active: List[EngineRequest],
                            runner_idle: bool) -> Optional[str]:
        """Why can this pass NOT chain a plain burst off the device
        carry? None = it can. The shrunken fallback ladder: stop-string
        rows chain via the suffix-hash approximation, guided rows via a
        compiled device table, n>1 arrives as independent n=1 children —
        what remains is named here and counted per sync pass
        (dynamo_engine_sync_fallback_total{reason})."""
        cfg = self.config
        if self.unit is not None:
            # the chain carries one pending token a row on the device;
            # this family's rows hold a block in flight
            return "block_unit"
        if not runner_idle:
            return "not_idle"
        if not active:
            return "no_rows"
        if self._chain_fp:
            # a suffix-hash false positive froze a row the host must
            # resume: close the chain first (the barrier clears this)
            return "stop_false_positive"
        if self.draft is not None:
            # plain (non-spec) chaining would starve the draft's mirror
            # cache for these rows; draft engines chain through the
            # propose-verify rounds instead
            return "draft_mirror"
        if self._chain_members and self._chain_kind not in (None, "plain"):
            return "chain_kind"
        tables = set()
        for er in active:
            if not er.device_checkable:
                return er.chain_fallback or "not_checkable"
            if er.guided is not None:
                r = self._guided_chain_reason(er)
                if r:
                    return r
                tables.add(id(self._guided_tables[
                    self._guided_table_key(er)]))
        if len(tables) > 1:
            # the burst program takes ONE transition table; requests
            # sharing a grammar share a table (the common case), mixed
            # grammars wait for membership to separate them
            return "guided_multi_grammar"
        if self._chain_members:
            member_ids = {id(m) for m in self._chain_members}
            if any(id(er) not in member_ids for er in active):
                return "membership"
        return None

    def _spec_chain_reason(
            self, active: List[EngineRequest]) -> Optional[str]:
        """Why can this pass NOT chain propose-verify rounds? (Callers
        established spec_now: speculation configured, runner idle, every
        row spec-eligible — greedy, penalty-free, unguided.)"""
        cfg = self.config
        if self._chain_fp:
            return "stop_false_positive"
        if not getattr(self.runner, "spec_burst_ready",
                       hasattr(self.runner, "decode_burst_spec")):
            return "spec_program"
        P = (cfg.spec_draft_tokens if self.draft is not None
             else cfg.spec_ngram_tokens)
        n = self._chain_dispatched
        for er in active:
            if not er.device_checkable:
                return er.chain_fallback or "not_checkable"
            # conservative horizon guard: the host's committed context
            # lags the drain queue, so bound by the chain's own dispatch
            # count — the round's S-position forward must stay inside
            # the model-len horizon (the sync verify makes the same
            # per-pass check)
            pos0 = self._chain_pos0.get(er.slot, er.context_len)
            if pos0 + (n + 1) * (P + 1) + 1 > cfg.max_model_len:
                return "spec_near_horizon"
        if self._chain_members:
            if self._chain_kind not in (None, "spec"):
                return "chain_kind"
            member_ids = {id(m) for m in self._chain_members}
            if any(id(er) not in member_ids for er in active):
                return "membership"
        return None

    # ---------- guided device tables (engine/guided.py) ----------

    # compiled tables kept at most this many distinct grammars: each is
    # a dense [states, vocab] int32 (tens of MB at real vocab sizes), so
    # adversarial per-request unique choice lists must not grow memory
    # without bound. LRU; eviction is safe mid-chain because every
    # chained pass re-checks presence (_guided_chain_reason) BEFORE the
    # dispatch reads the cache — a missing table just recompiles.
    GUIDED_TABLE_CACHE = 16

    def _guided_table_key(self, er: EngineRequest) -> tuple:
        if er.guided_key is not None:
            return er.guided_key
        eos = tuple(sorted(int(t) for t in (er.req.eos_token_ids or [])))
        g = er.guided
        if isinstance(g, TrieConstraint):
            key = ("trie",
                   tuple(tuple(int(t) for t in c) for c in g._choice_ids),
                   eos)
        else:
            # JsonConstraint: the grammar object is shared across
            # requests with the same spec (serving's cache), so its
            # identity keys
            key = ("json", id(g.grammar), eos)
        er.guided_key = key
        return key

    def _compile_guided_table(self, er: EngineRequest):
        """Executor-side table compile (also called directly by tests).
        Returns the DeviceGuidedTable or None (bound exceeded)."""
        return compile_device_table(
            er.guided, self.config.model.vocab_size,
            er.req.eos_token_ids or [],
            max_states=self.config.guided_table_max_states,
        )

    def _guided_chain_reason(self, er: EngineRequest) -> Optional[str]:
        """Is this guided row chainable right now? Kicks the (executor)
        table compile on first sight; the row serves on the sync path
        until the table lands."""
        if not self.config.guided_device_table:
            return "guided_disabled"
        key = self._guided_table_key(er)
        if key in self._guided_tables:
            table = self._guided_tables[key]
            # LRU touch + cap: evict the coldest grammar's table when a
            # new one would exceed the bound (re-checked every pass, so
            # an evicted-then-needed table simply recompiles)
            self._guided_tables.pop(key)
            self._guided_tables[key] = table
            while len(self._guided_tables) > self.GUIDED_TABLE_CACHE:
                self._guided_tables.pop(
                    next(iter(self._guided_tables)))
            if table is None:
                return "guided_table_bound"
            if table.state_id(er.guided) is None:
                # the cursor is in a state the BFS never reached — only
                # a bug can produce this; stay on the sync path loudly
                logger.warning(
                    "guided cursor state unmapped in the device table "
                    "for %s; keeping the sync path", er.request_id,
                )
                return "guided_state_unmapped"
            return None
        if key not in self._guided_table_futs:
            # the per-state vocab sweep must never run on the event
            # loop — compile in an executor, chain once it lands
            loop = asyncio.get_running_loop()
            fut = loop.run_in_executor(
                None, self._compile_guided_table, er
            )

            def _done(f, key=key):
                try:
                    self._guided_tables[key] = f.result()
                except Exception:
                    logger.exception("guided device-table compile failed")
                    self._guided_tables[key] = None
                self._guided_table_futs.pop(key, None)
                self.wake.set()

            fut.add_done_callback(_done)
            self._guided_table_futs[key] = fut
        return "guided_table_pending"

    def _chain_ready(self, infl: _InflightBurst) -> bool:
        """Non-blocking: are this burst's outputs already materialized?
        (Host test doubles return numpy — always ready.)"""
        return getattr(infl.toks, "is_ready", lambda: True)()

    async def _apply_chain_head(self, loop) -> None:
        """Reconcile the oldest queued chained burst (FIFO — token order
        per row) and record its drain lag."""
        infl = self._chain.popleft()
        # outputs already materialized? then NOW is the ready stamp the
        # device-time estimator should use — the sync below only copies
        ready_hint = time.monotonic() if self._chain_ready(infl) else None
        await self._apply_burst(loop, infl, ready_hint=ready_hint)
        self._drain_lag_hist.observe(time.monotonic() - infl.dispatch_t)

    async def _chain_prologue(self, loop, active, kind):
        """The shared open/validate ladder of a chained pass: barrier
        on a chain-KIND switch (plain ↔ spec program families), open the
        chain if none is, and resolve the live member list. Returns
        ``(active, live, members)`` or None — None means every fallback
        already ran and the caller just returns."""
        if self._chain_members and self._chain_kind not in (None, kind):
            await self._chain_barrier(loop)
            active = [er for er in active if er.finish is None]
            if not active:
                return None
        if not self._chain_members:
            self._chain_members = list(active)
            self._chain_kind = kind
            self._chain_carry = None
            self._chain_dispatched = 0
            self._chain_pos0 = {er.slot: er.context_len for er in active}
        members = self._chain_members
        live = [er for er in members if er.finish is None]
        if not live:
            await self._chain_barrier(loop)
            return None
        return active, live, members

    def _chain_reserve(self, live, advance) -> bool:
        """Block headroom for the chain's next dispatch: positions a
        never-frozen row runs through ``chain_pos0 + (n+1)*advance - 1``
        — reserve one past that (the carry slot), capped at the
        model-len horizon (the device freezes rows there; blocks past it
        are never touched). False ⇒ KV OOM: the caller falls back
        through ``_chain_oom_fallback``."""
        cfg = self.config
        n = self._chain_dispatched
        ok = True
        for er in live:
            limit = min(self._chain_pos0[er.slot] + (n + 1) * advance,
                        cfg.max_model_len - 1)
            if not self._ensure_block_for(er, limit):
                ok = False
                break
            self._host.sync_blocks(er)
        self.allocator.flush_offload()
        return ok

    async def _chain_oom_fallback(self, loop, active, sync_steps) -> None:
        """KV OOM under a chain: preemption needs fully-committed host
        state, so the chain closes at a barrier and the pass falls back
        to one sync decode (which owns preemption)."""
        self._note_sync_fallback("kv_oom")
        await self._chain_barrier(loop)
        rest = [er for er in active if er.finish is None]
        if rest:
            await self._decode(loop, rest, sync_steps)

    def _chain_masks(self, members, live, *asked):
        """(commit mask, block-table slice) for one chained dispatch of
        the programs ``asked`` (``_table_width``)."""
        cfg = self.config
        commit = np.zeros(cfg.max_batch_size, bool)
        for er in members:
            commit[er.slot] = er.finish is None
        w = self._table_width(live, *asked)
        return commit, self._host.btab[:, :w].copy()

    def _chain_fill(self, live, with_guided):
        """The chain-fill carry from committed host state (first
        dispatch of a chain). Spec chains carry no guided cursors
        (spec-eligible rows are unguided by admission)."""
        b = self.config.max_batch_size
        tokens0 = np.zeros(b, np.int32)
        positions0 = np.zeros(b, np.int32)
        gen0 = np.zeros(b, np.int32)
        done0 = np.zeros(b, bool)
        ring0 = np.full((b, SUFFIX_RING_W), -1, np.int32)
        gstate0 = np.full(b, -1, np.int32)
        for er in live:
            tokens0[er.slot] = er.pending_token
            positions0[er.slot] = er.context_len
            gen0[er.slot] = er.generated
            ring0[er.slot] = ring_init(er.ring_tail)
            if with_guided and er.guided is not None:
                gstate0[er.slot] = self._guided_tables[
                    self._guided_table_key(er)].state_id(er.guided)
        return tokens0, positions0, gen0, done0, ring0, gstate0

    def _chain_observe_bubble(self, tokens0) -> None:
        """Device-idle bookkeeping (a host-observed approximation): a
        carry already materialized at dispatch time means the device ran
        dry since the last reconciliation. Must run BEFORE the dispatch
        consumes ``self._chain_carry``."""
        now = time.monotonic()
        if self._last_burst_done_t is not None:
            if self._chain_carry is None:
                self._bubble_hist.observe(now - self._last_burst_done_t)
            else:
                ready = getattr(tokens0, "is_ready", lambda: True)()
                self._bubble_hist.observe(
                    now - self._last_burst_done_t if ready else 0.0
                )
        self._last_burst_done_t = None

    async def _chain_drain(self, loop, members) -> None:
        """Asynchronous row drain after a chained dispatch: reconcile
        every burst whose outputs already materialized (never gating the
        dispatch), enforce the in-flight bound, and close the chain when
        every member finished (anything still queued is frozen
        over-decode)."""
        while self._chain and self._chain_ready(self._chain[0]):
            await self._apply_chain_head(loop)
        while len(self._chain) >= self.CHAIN_MAX_INFLIGHT:
            await self._apply_chain_head(loop)
        if all(er.finish is not None for er in members):
            await self._chain_barrier(loop)

    async def _decode_chained(self, loop,
                              active: List[EngineRequest]) -> None:
        """One persistent-loop pass: dispatch the next burst straight off
        the device-resident carry — WITHOUT waiting for any previous
        burst's host reconciliation — then drain whatever bursts have
        already materialized.

        Finished rows freeze inside the burst program (no sampling, no
        KV writes, -1 pads out), so membership never changes mid-chain:
        the commit mask marks members, the device ``done`` mask marks
        frozen rows, and rows cancelled on the host simply drop out of
        the commit mask at the next dispatch. Block headroom is tracked
        against the chain's own dispatch count (the host's committed
        ``context_len`` lags by the whole drain queue), capped at the
        model-len horizon — the device's LENGTH check freezes rows there,
        so near-horizon rows stay chained instead of forcing sync.
        """
        cfg = self.config
        k_steps = max(1, cfg.multi_step_decode)
        opened = await self._chain_prologue(loop, active, "plain")
        if opened is None:
            return
        active, live, members = opened
        n = self._chain_dispatched
        with span("sched.decode.build", step=self.passes, rows=len(live)):
            reserved = self._chain_reserve(live, k_steps)
            if reserved:
                hs = self._host
                commit, btab = self._chain_masks(
                    members, live, (self.runner, "decode_burst_df"))
                want_top = any(er.logprobs_n > 0 for er in members)
                # guided members ride the device transition table: ONE table per
                # chain (_chain_block_reason enforced it), their bias rows reset
                # to logit_bias-only so the in-program mask is not double-applied
                # (the barrier reinstalls the host mask)
                gtable_dev = None
                guided_live = [er for er in live if er.guided is not None]
                if guided_live:
                    table = self._guided_tables[
                        self._guided_table_key(guided_live[0])]
                    bucket = self.runner.guided_state_bucket(table.n_states)
                    gtable_dev = table.device(bucket)
                    for er in guided_live:
                        if not er.chain_bias_reset:
                            self._set_plain_bias(er)
                            er.chain_bias_reset = True
                if self._chain_carry is None:
                    (tokens0, positions0, gen0, done0, ring0,
                     gstate0) = self._chain_fill(live, with_guided=True)
                else:
                    (tokens0, positions0, gen0, done0, ring0,
                     gstate0) = self._chain_carry

                self._chain_observe_bubble(tokens0)

        if not reserved:
            return await self._chain_oom_fallback(loop, active, k_steps)
        with span("sched.decode.dispatch", step=self.passes,
                  rows=len(live)):
            toks, lps, tv, ti, carry = self.runner.decode_burst_chained(
                tokens0, positions0, gen0, done0, btab,
                hs.temp, hs.top_k, hs.top_p,
                min_p=hs.min_p, presence_penalty=hs.pres,
                frequency_penalty=hs.freq, repetition_penalty=hs.rep,
                seed_keys=hs.keys, commit=commit, stop_ids=hs.stop_ids,
                min_new=hs.min_new, max_new=hs.max_new,
                ring0=ring0, gstate0=gstate0,
                stop_hash=hs.stop_hash, stop_hlen=hs.stop_hlen,
                gtable=gtable_dev, want_top=want_top,
            )
            self._count_decode_rows("decode_burst_df", live, k_steps)
            self._chain_carry = carry
            self._chain_dispatched += 1
            self.steps += 1
            self.pipeline_bursts += 1
            self.flight.record(
                "scheduler.burst_dispatch", k_steps=k_steps, rows=len(live),
                chained=True,
                chain_len=self._chain_dispatched,
                requests=[er.request_id for er in live[:8]],
            )
            dt = self.device_time
            self._chain.append(_InflightBurst(
                active=list(live), toks=toks, lps=lps, tv=tv, ti=ti,
                k_steps=k_steps, dispatch_t=time.monotonic(),
                read_bytes=dt.decode_read_bytes(
                    k_steps,
                    sum(min(self._chain_pos0[er.slot] + n * k_steps,
                            cfg.max_model_len) for er in live),
                ) if dt is not None else 0.0,
                tokens=k_steps * len(live),
                prefetched=_request_transfer((toks, lps, tv, ti)),
            ))
        await self._chain_drain(loop, members)

    async def _decode_chained_spec(self, loop,
                                   active: List[EngineRequest]) -> None:
        """One chained propose-verify pass: ONE spec round dispatched
        straight off the device-resident carry — proposals from the
        carry's trailing-token ring (ngram) or from the draft model's
        chained burst on the SAME carry (draft), verified by one
        S = K+1-position forward whose accepted prefix + correction
        commit with the plain chain's freeze semantics. No host barrier
        between rounds: the draft consumes the target's device carry
        directly, acceptance folds into the carry on device, and the
        async row drain reconciles rounds as their outputs materialize
        (per-row acceptance lengths ride back for the
        dynamo_engine_spec_accept_length histogram).
        """
        cfg = self.config
        b = cfg.max_batch_size
        P = (cfg.spec_draft_tokens if self.draft is not None
             else cfg.spec_ngram_tokens)
        S = P + 1
        opened = await self._chain_prologue(loop, active, "spec")
        if opened is None:
            return
        active, live, members = opened
        # headroom: a round advances a never-frozen row by at most S
        # positions (accepted prefix + correction); near-horizon rounds
        # never dispatch (_spec_chain_reason barriers them first)
        n = self._chain_dispatched
        with span("sched.decode.build", step=self.passes, rows=len(live)):
            reserved = self._chain_reserve(live, S)
            if reserved:
                hs = self._host
                commit, btab = self._chain_masks(
                    members, live, (self.runner, "decode_burst_spec"),
                    (self.draft, "decode_burst"))
                if self._chain_carry is None:
                    (tokens0, positions0, gen0, done0, ring0,
                     gstate0) = self._chain_fill(live, with_guided=False)
                else:
                    (tokens0, positions0, gen0, done0, ring0,
                     gstate0) = self._chain_carry

        if not reserved:
            return await self._chain_oom_fallback(loop, active, 1)
        with span("sched.decode.dispatch", step=self.passes,
                  rows=len(live)):
            props = None
            if self.draft is not None:
                # draft round chained off the SAME carry: its burst consumes
                # the target's device-resident tokens/positions and its
                # commit mask is gated by the device done carry — no host
                # barrier anywhere in the draft → verify round trip
                import jax.numpy as jnp

                commit_dev = jnp.logical_and(
                    jnp.asarray(commit),
                    jnp.logical_not(jnp.asarray(done0, jnp.bool_)),
                )
                dtemp, dtop_k, dtop_p, dkw = self._inert_sampling(b)
                dtoks, *_ = self.draft.decode_burst(
                    tokens0, positions0, btab, dtemp, dtop_k, dtop_p,
                    commit=commit_dev, want_top=False, **dkw,
                )
                props = jnp.transpose(dtoks[:P])  # [B, P] device proposals
                self.steps += 1

            self._chain_observe_bubble(tokens0)

            toks, nprop, nacc, carry = self.runner.decode_burst_spec(
                tokens0, positions0, gen0, done0, ring0, gstate0, btab,
                commit=commit, stop_ids=hs.stop_ids, min_new=hs.min_new,
                max_new=hs.max_new, stop_hash=hs.stop_hash,
                stop_hlen=hs.stop_hlen, proposals=props,
            )
            self._chain_carry = carry
            self._chain_dispatched += 1
            self.steps += 1
            self.pipeline_bursts += 1
            self.flight.record(
                "scheduler.burst_dispatch", k_steps=S, rows=len(live),
                chained=True, spec=True,
                chain_len=self._chain_dispatched,
                requests=[er.request_id for er in live[:8]],
            )
            dt = self.device_time
            self._chain.append(_InflightBurst(
                active=list(live), toks=toks, lps=None, tv=None, ti=None,
                k_steps=S, dispatch_t=time.monotonic(),
                spec=True, nprop=nprop, nacc=nacc,
                read_bytes=dt.decode_read_bytes(
                    1,
                    sum(min(self._chain_pos0[er.slot] + n * S + S,
                            cfg.max_model_len) for er in live),
                ) if dt is not None else 0.0,
                tokens=len(live),
                prefetched=_request_transfer((toks, nprop, nacc)),
            ))
        await self._chain_drain(loop, members)

    def _set_plain_bias(self, er: EngineRequest) -> None:
        """Reset one slot's bias row to the request's logit_bias alone —
        a device-table chain computes the guided mask in-program, so the
        host-installed mask must not double-apply."""
        v = self.config.model.vocab_size
        row = np.zeros(v, np.float32)
        for tid, bv in (er.req.sampling_options.logit_bias or {}).items():
            tid = int(tid)
            if 0 <= tid < v:
                row[tid] += float(bv)
        self.runner.set_bias_row(er.slot, row)

    def _reinstall_guided_mask(self, er: EngineRequest) -> None:
        """Back to host-masked guided decoding (chain closed): rebuild
        the dense mask from the CURRENT cursor state — the drain
        advanced the host cursor token-by-token, so it is exact."""
        mask = self._guided_mask(er)
        for tid, bv in (er.req.sampling_options.logit_bias or {}).items():
            tid = int(tid)
            if 0 <= tid < len(mask):
                mask[tid] += float(bv)
        self.runner.set_bias_row(er.slot, mask)

    async def _chain_barrier(self, loop) -> None:
        """Host barrier: reconcile every queued chained burst and close
        the chain — the ONLY place chain membership compacts. Runs before
        admission-driven sync passes, preemption, program-family
        switches, and shutdown."""
        if not self._chain and not self._chain_members:
            return
        bursts = self._chain_dispatched
        while self._chain:
            await self._apply_chain_head(loop)
        if self._chain_members:
            self.flight.record(
                "scheduler.burst_drain", bursts=bursts,
                rows=len(self._chain_members),
            )
            for er in self._chain_members:
                er.chain_fp = False
                if er.chain_bias_reset:
                    er.chain_bias_reset = False
                    if er.finish is None and er.guided is not None:
                        self._reinstall_guided_mask(er)
                if er.finish is None and er.pipeline_span_open:
                    er.ctx.add_stage("decode_pipeline")
                    er.pipeline_span_open = False
        if bursts:
            self._last_chain_len = bursts
        self._chain_members = []
        self._chain_carry = None
        self._chain_dispatched = 0
        self._chain_pos0 = {}
        self._chain_kind = None
        self._chain_fp = False

    # ---------- cluster KV fabric: prefix pull (kv/fabric.py) ----------

    def _try_submit_pull(self, er: EngineRequest) -> bool:
        """Start a prefix pull for this waiting request?

        Engages when the fabric's ownership view (peer KV events, cold
        tier index) holds a longer prefix run than every local tier.
        The request reserves its FULL prompt allocation now (exactly
        like a remote-prefill submit), pins the pull targets, and waits
        in ``pending_pull`` while the transfer streams — the scheduler
        keeps serving everyone else. One attempt per request: any
        failure falls back to plain local prefill, byte-identically.
        """
        if (er.pull_attempted or er.resume_tokens
                or (er.want_prompt_lps and not er.prompt_lps_emitted)):
            # resumed streams re-prefill prompt+resume (no pullable
            # chain for the generated tail); prompt-logprob requests
            # must run every position through the model anyway
            return False
        if time.monotonic() < er.pull_backoff_until:
            return False
        probe = self.allocator.probe_prefix(er.prompt)
        hashes, local_blocks, host_hashes = probe
        n_local = len(local_blocks) + len(host_hashes)
        plan = self.fabric.plan(hashes, n_local, len(er.prompt))
        if plan is None:
            # nothing worth pulling right now: don't re-hash the whole
            # prompt on every loop pass while the request queues
            er.pull_backoff_until = time.monotonic() + 0.25
            return False
        planned = set(plan.hashes)
        for other in self.pending_pull:
            if (other.pull is not None
                    and not planned.isdisjoint(other.pull.plan.hashes)):
                # a pull already in flight fetches (part of) this run —
                # its commit registers the prefix for everyone, so HOLD
                # this request out of local admission until the pull
                # resolves instead of transferring (or recomputing) the
                # same blocks N× (the shared-system-prompt burst on a
                # cold worker). Commit/fallback clear the hold early;
                # the pull's own deadline bounds it.
                er.pull_backoff_until = time.monotonic() + 0.05
                er.pull_hold_until = other.pull.deadline
                return False
        try:
            er.block_ids, er.num_cached = self.allocator.allocate_prompt(
                er.prompt, probe=probe
            )
        except MemoryError:
            # transient — the pull stays worth trying once memory frees
            # (only an actual transfer attempt burns the one shot)
            er.pull_backoff_until = time.monotonic() + 0.25
            return False
        bs = self.config.kv_block_size
        if er.num_cached // bs != plan.start_block:
            # the local hit shrank inside allocate_prompt (host-tier
            # capacity eviction raced the probe): the planned run no
            # longer abuts the cached prefix — abandon the pull (a
            # re-plan against the new local state may still pull)
            self.allocator.free_blocks(er.block_ids)
            er.block_ids = []
            er.num_cached = 0
            er.pull_backoff_until = time.monotonic() + 0.25
            return False
        er.pull_attempted = True
        targets = er.block_ids[
            plan.start_block:plan.start_block + plan.blocks
        ]
        self.allocator.pin_blocks(targets)
        task = asyncio.get_running_loop().create_task(
            self.fabric.pull(
                plan, targets, request_id=er.request_id,
                trace_id=er.ctx.trace_id,
            ),
            name=f"kv-pull-{er.request_id[:8]}",
        )
        task.add_done_callback(lambda _f: self.wake.set())
        er.pull = _PendingPull(
            plan=plan, task=task, targets=targets, hashes=hashes,
            deadline=time.monotonic() + self.fabric.pull_timeout_s,
        )
        self.flight.record(
            "scheduler.pull_submit", request_id=er.request_id,
            trace_id=er.ctx.trace_id, source=plan.source,
            worker=plan.worker_id, blocks=plan.blocks,
        )
        self.pending_pull.append(er)
        return True

    def _reap_pulls(self) -> bool:
        """Commit finished pulls, fall back on failures and deadlines."""
        progressed = False
        now = time.monotonic()
        for er in list(self.pending_pull):
            pp: _PendingPull = er.pull
            if er.ctx.is_stopped:
                self.pending_pull.remove(er)
                self._release_pull(er)
                self._finish(er, FinishReason.CANCELLED)
                # requests held on THIS pull must not wait out its
                # stale deadline after a client disconnect
                self._clear_pull_holds()
                progressed = True
            elif pp.task.done():
                self.pending_pull.remove(er)
                served, reason = 0, "empty"
                if not pp.task.cancelled():
                    try:
                        served = pp.task.result()
                    except Exception as e:
                        reason = "error"
                        logger.warning(
                            "prefix pull failed for %s (%s); local "
                            "recompute fallback", er.request_id, e,
                        )
                if served > 0:
                    self._commit_pull(er, served)
                else:
                    self._fallback_pull(er, reason)
                progressed = True
            elif now > pp.deadline:
                # a dead/stalled source must never hold the request:
                # cancel the transfer and recompute locally
                pp.task.cancel()
                self.pending_pull.remove(er)
                self._fallback_pull(er, "timeout")
                progressed = True
        return progressed

    def _release_pull(self, er: EngineRequest) -> None:
        """Unwind a pull's reservation state (task + pins). Blocks stay
        with the request — commit registers them, fallback/finish frees
        them."""
        pp: _PendingPull = er.pull
        er.pull = None
        if not pp.task.done():
            pp.task.cancel()
        self.allocator.unpin_blocks(pp.targets)

    def _commit_pull(self, er: EngineRequest, served: int) -> None:
        """A pull landed ``served`` blocks: register the content-
        addressed prefix (matchable + KV events, exactly as if this
        engine had computed it) and re-queue for the tail prefill."""
        pp: _PendingPull = er.pull
        self._release_pull(er)
        bs = self.config.kv_block_size
        for i in range(served):
            idx = pp.plan.start_block + i
            parent = pp.hashes[idx - 1] if idx > 0 else None
            self.allocator.register_complete(
                pp.targets[i], pp.hashes[idx], parent
            )
        er.num_cached += served * bs
        er.pull_ready = True
        # closing-mark semantics: the wait-and-transfer span since the
        # queued mark is the fabric's — the tail prefill's own span
        # follows under "prefill"
        er.ctx.add_stage("kv_fabric")
        self.flight.record(
            "scheduler.pull_commit", request_id=er.request_id,
            trace_id=er.ctx.trace_id, source=pp.plan.source,
            blocks=served, cached_tokens=er.num_cached,
        )
        self.waiting.appendleft(er)
        self._clear_pull_holds()
        self.wake.set()

    def _clear_pull_holds(self) -> None:
        """A pull resolved (commit or fallback): release every waiting
        request held for it — their next pass re-probes against the
        new local state (commit → the prefix is now a local hit)."""
        for w in self.waiting:
            w.pull_hold_until = 0.0
            w.pull_backoff_until = 0.0

    def _fallback_pull(self, er: EngineRequest, reason: str) -> None:
        """Pull failed/expired/served nothing: release everything and
        recompute locally. The stream is byte-identical to the
        no-fabric run — nothing was registered, so the allocator state
        matches a fresh admission exactly."""
        self._release_pull(er)
        self.allocator.free_blocks(er.block_ids)
        er.block_ids = []
        er.num_cached = 0
        # marker span (the "preempted"/"remote_fallback" idiom): the
        # pull wait is attributable, and the second "queued" epoch in
        # the trace is a fallback re-admission, not a bug
        er.ctx.add_stage("pull_fallback")
        self.flight.record(
            "kv_fabric.local_fallback", request_id=er.request_id,
            trace_id=er.ctx.trace_id, reason=reason,
        )
        self.waiting.appendleft(er)
        self._clear_pull_holds()
        self.wake.set()

    # ---------- disaggregated prefill (decode side) ----------

    async def _try_submit_remote(self, er: EngineRequest) -> bool:
        """Conditional disagg: enqueue this prompt for remote prefill?

        Mirrors the decode worker's decision point (reference:
        examples/llm/components/worker.py:180-195 — disagg router verdict
        from prompt length, prefix-hit length, and prefill queue depth).
        """
        if er.remote_attempted:
            return False  # already tried remote once — prefill locally
        if er.pull_ready:
            # a committed prefix pull pre-allocated this request's
            # blocks; the (now small) tail prefills locally
            return False
        if time.monotonic() < er.remote_backoff_until:
            return False
        if er.resume_tokens:
            # preempted stream: only the local path knows to re-prefill
            # prompt + resume_tokens; the remote path would restart the
            # stream from the prompt alone
            return False
        if er.want_prompt_lps:
            # prompt logprobs need every position's logits on THIS engine
            # (the remote protocol ships KV + one sampled token, not a
            # [S, V] logits sweep) — prefill locally
            return False
        if (er.req.sampling_options.guided_choice_token_ids
                or er.req.sampling_options.guided_json
                or er.guided is not None):
            # the remote prefill samples the FIRST token without this
            # engine's guided mask — constrained requests (choice trie
            # OR json grammar) prefill locally
            return False
        # the long-prefill admission class (docs/long_context.md): in
        # disagg mode, prompts past the threshold PREFER the prefill
        # pool regardless of the router's length/queue heuristics — the
        # pool's workers run the SP chunk ladder, and a 128k prompt on
        # this engine's dense ladder would head-of-line-block decode far
        # longer than any queue wait (the in-flight cap in _run still
        # bounds the submit count). Engines with their own SP mesh keep
        # the router's verdict: the local ladder is just as parallel.
        force_long = (
            self.config.long_prefill_threshold_tokens > 0
            and not getattr(self.runner, "sp_ready", False)
            and len(er.prompt) >= self.config.long_prefill_threshold_tokens
        )
        # cheap pre-check before the (hash-the-whole-prompt) prefix probe:
        # a larger prefix hit can only make the uncached suffix smaller,
        # so a prompt that doesn't qualify with hit=0 never qualifies —
        # and this loop runs for EVERY waiting request EVERY pass
        if not force_long and not self.disagg.decide(len(er.prompt), 0):
            return False
        probe = self.allocator.probe_prefix(er.prompt)
        # host-tier blocks count as hit: restoring them locally is far
        # cheaper than a remote prefill round-trip
        prefix_hit = self.allocator.cached_tokens(probe)
        # a big local prefix hit can shrink the suffix back under the
        # threshold — then the class no longer applies
        if force_long and len(er.prompt) - prefix_hit < \
                self.config.long_prefill_threshold_tokens:
            force_long = False
        if not force_long and not self.disagg.decide(len(er.prompt),
                                                     prefix_hit):
            # rejected on the hit term. NOT permanent: cached prefixes can
            # be evicted and the router threshold is live-tunable — back
            # off instead, so the (whole-prompt) probe doesn't re-run on
            # every scheduler pass while conditions are unchanged
            er.remote_backoff_until = time.monotonic() + 0.25
            return False
        er.remote_attempted = True
        try:
            er.block_ids, er.num_cached = self.allocator.allocate_prompt(
                er.prompt, probe=probe
            )
        except MemoryError:
            return False
        try:
            er.remote_future = await self.disagg.submit(
                er.request_id, er.prompt, er.block_ids, er.num_cached,
                temperature=er.temperature, top_k=er.top_k, top_p=er.top_p,
                min_p=er.min_p, presence_penalty=er.presence_penalty,
                frequency_penalty=er.frequency_penalty,
                repetition_penalty=er.repetition_penalty,
                seed=er.req.sampling_options.seed,
                want_logprobs=er.want_logprobs,
                logprobs_n=er.logprobs_n,
                logit_bias=er.req.sampling_options.logit_bias,
                trace_id=er.ctx.trace_id,
                ctx=er.ctx,  # kv_transfer stage mark stamped at commit
            )
        except Exception:
            # queue unreachable — release and let the local path take it
            logger.exception("remote prefill submit failed for %s; going local",
                             er.request_id)
            self.allocator.free_blocks(er.block_ids)
            er.block_ids = []
            er.num_cached = 0
            return False
        self._count_prefix_lookup(er, len(er.prompt))
        self._mark_admission(er)
        self.flight.record(
            "scheduler.remote_submit", request_id=er.request_id,
            trace_id=er.ctx.trace_id, prompt_tokens=len(er.prompt),
            cached=er.num_cached,
        )
        er.remote_deadline = time.monotonic() + self.disagg.prefill_timeout_s
        er.remote_future.add_done_callback(lambda _f: self.wake.set())
        self.pending_remote.append(er)
        return True

    def _reap_remote(self) -> bool:
        """Install completed remote prefills; handle cancels and timeouts."""
        progressed = False
        now = time.monotonic()
        for er in list(self.pending_remote):
            if er.ctx.is_stopped:
                self.pending_remote.remove(er)
                self.disagg.cancel(er.request_id)
                self._finish(er, FinishReason.CANCELLED)
                progressed = True
                continue
            fut = er.remote_future
            if fut.done() and not fut.cancelled():
                slot = self._free_slot()
                if slot is None:
                    break  # keep completion ordering; wait for a slot
                self.pending_remote.remove(er)
                self._install_remote(er, slot)
                progressed = True
            elif now > er.remote_deadline:
                # prefill worker lost / queue starved — fall back to local
                logger.warning("remote prefill timeout for %s; local fallback",
                               er.request_id)
                self.pending_remote.remove(er)
                self.disagg.cancel(er.request_id, reason="timeout")
                self.flight.record(
                    "disagg.local_fallback", request_id=er.request_id,
                    trace_id=er.ctx.trace_id, reason="timeout",
                )
                self.allocator.free_blocks(er.block_ids)
                er.block_ids = []
                er.num_cached = 0
                er.remote_future = None
                # marker span (same idiom as "preempted"): the second
                # "admission" in the trace is a fallback re-admission,
                # not a bug — and the remote wait is attributable to it
                er.ctx.add_stage("remote_fallback")
                self.waiting.appendleft(er)
                progressed = True
        return progressed

    def _install_remote(self, er: EngineRequest, slot: int) -> None:
        """A remote prefill committed — enter the decode loop.

        The prefill worker already wrote the KV blocks into our cache and
        sampled the first token (max_tokens=1 semantics, reference:
        examples/llm/components/prefill_worker.py:148-178)."""
        token, lp, top = er.remote_future.result()
        er.ctx.add_stage("remote_prefill")
        er.remote_future = None
        er.slot = slot
        self.slots[slot] = er
        self._host.install(er)
        er.context_len = len(er.prompt)
        er.pending_token = token
        er.generated = 1
        # penalty/PRNG state for the decode steps this slot is entering
        self.runner.set_sample_row(
            slot, er.prompt, [token],
            logit_bias=er.req.sampling_options.logit_bias,
        )
        er.seq = TokenSequence(er.prompt, block_size=self.config.kv_block_size)
        self._register_completed_blocks(er)
        er.ring_tail.clear()
        er.ring_tail.extend(er.prompt[-SUFFIX_RING_W:])
        er.ring_tail.append(token)
        er.finish = self._check_finish(er, token)
        if top and er.logprobs_n > 0:
            top = dict(list(top.items())[: er.logprobs_n])
        else:
            top = None
        self._emit(er, token, lp if er.want_logprobs else None, top)
        if er.finish is not None:
            self._finish(er, er.finish, emit=False)

    def _start_prefill(self, er: EngineRequest) -> None:
        """Claim a slot + KV blocks and enter the chunked-prefill state.

        A preempted request resumes here: ``prompt + resume_tokens`` is
        re-prefilled so the emitted stream *continues* from where it left
        off instead of restarting (vLLM recompute-preemption semantics)."""
        slot = self._free_slot()
        assert slot is not None
        self._mark_admission(er)
        self.flight.record(
            "scheduler.admission", request_id=er.request_id,
            trace_id=er.ctx.trace_id, slot=slot,
            prompt_tokens=len(er.prompt), resumed=bool(er.resume_tokens),
        )
        tokens_all = er.prompt + er.resume_tokens
        if self.window is not None and self.window.available < min(
                -(-len(tokens_all) // self.config.kv_block_size),
                self.config.window_pages_a_row(
                    self.config.prefill_chunk_tokens())):
            # admission asks both pools: the window kind's must hold
            # what this row's prefill can come to hold
            raise MemoryError("window pages: no room for one more prefill")
        # ring tail mirrors the emitted history (a resumed request's
        # replayed tail included) so stop-seq checks and chain fills
        # continue exactly where the stream left off
        er.ring_tail.clear()
        er.ring_tail.extend(tokens_all[-SUFFIX_RING_W:])
        if er.pull_ready and er.block_ids:
            # a committed prefix pull already allocated the blocks,
            # scattered the pulled run, and registered it (num_cached
            # covers local + pulled) — only the tail prefills below
            er.pull_ready = False
        elif self.private_pages or (
                er.want_prompt_lps and not er.prompt_lps_emitted):
            # every prompt position must run through the model — a prefix
            # cache hit would skip its logits (prompt logprobs), its
            # part of the recurrent state or its window pages (a family
            # with two kinds of page registers none, so its probe finds
            # none). Blank the probe's hits so
            # allocation proceeds with zero cached tokens. (A resumed
            # request that already emitted them uses the cache normally.)
            probe = self.allocator.probe_prefix(tokens_all)
            er.block_ids, er.num_cached = self.allocator.allocate_prompt(
                tokens_all, probe=(probe[0], [], [])
            )
            if self.recurrent:      # (counted once the blocks are had)
                self._state_resets.inc()
                if probe[1] or probe[2]:
                    self._prefix_blanked.inc()
        else:
            er.block_ids, er.num_cached = self.allocator.allocate_prompt(tokens_all)
        if not er.remote_attempted:  # remote fallback already counted itself
            self._count_prefix_lookup(er, len(tokens_all))
        if self.unit is not None:
            # a block family prefills whole blocks only; the rest of the
            # prompt opens the first block, already unmasked
            whole = len(tokens_all) - len(tokens_all) % self.unit.length
            self._open_block(er, tokens_all[whole:])
            tokens_all = tokens_all[:whole]
        er.prefill_tokens = tokens_all
        er.prefill_pos = er.num_cached
        er.context_len = er.num_cached
        er.slot = slot
        self.slots[slot] = er
        self._host.install(er)
        er.seq = TokenSequence(tokens_all, block_size=self.config.kv_block_size)
        er.registered_blocks = 0
        # guided decoding: (re)build the constraint and walk it past any
        # already-emitted tokens (a resumed request continues mid-stream)
        gids = er.req.sampling_options.guided_choice_token_ids
        if gids:
            er.guided = TrieConstraint(gids)
        elif er.guided is not None:
            er.guided.reset()  # json constraint attached by serving
        if er.guided is not None:
            for t in er.resume_tokens:
                if er.guided.advance(int(t)) != "ok":
                    # derailed resume (tokens that never followed the
                    # mask — unreachable in normal operation): an
                    # all-banned mask would still emit one unconstrained
                    # token (an additive constant constrains nothing),
                    # so finish the stream here instead
                    self._finish(er, FinishReason.STOP)
                    return
            if not self._guided_allowed_ids(er):
                # dead state: the vocab cannot express any legal
                # continuation (serving validates expressibility at
                # grammar build, so this is a defensive backstop)
                self._finish(er, FinishReason.STOP)
                return
        # penalty state for the slot: prompt presence + (on resume) counts
        # of the already-generated tokens (+ the guided mask for the
        # FIRST sampled token — the prefill's final chunk samples it)
        self.runner.set_sample_row(
            slot, er.prompt, er.resume_tokens,
            logit_bias=er.req.sampling_options.logit_bias,
            guided_mask=(
                self._guided_mask(er) if er.guided is not None else None
            ),
        )
        if self._sp_eligible(er):
            # long-context admission class: the whole mesh prefills this
            # one prompt, a sequence-sharded chunk per pass
            self.sp_queue.append(er)
        elif self.unit is not None and er.prefill_pos >= len(er.prefill_tokens):
            # nothing to prefill (a prompt shorter than a block, or whole
            # blocks all found in the prefix cache): straight to decode
            self._register_completed_blocks(er)
            self._end_block_prefill(er)
        else:
            self.prefilling.append(er)

    # ---------- sequence-parallel long-context prefill ----------

    def _sp_eligible(self, er: EngineRequest) -> bool:
        """Route this admission to the sequence-parallel ladder?

        The SP program exists (sp_size > 1, supported trunk), the
        uncached suffix crosses the admission threshold, and nothing in
        the request needs the dense ladder's full-S head (prompt
        logprobs) or a mirrored draft cache (the draft has no SP
        program — its chunk replay would go stale)."""
        cfg = self.config
        if not (getattr(self.runner, "sp_ready", False)
                and cfg.long_prefill_threshold_tokens > 0):
            return False
        suffix = len(er.prefill_tokens) - er.num_cached
        if suffix < cfg.long_prefill_threshold_tokens:
            return False
        if er.want_prompt_lps and not er.prompt_lps_emitted:
            return False
        return self.draft is None

    def _is_sp(self, er: EngineRequest) -> bool:
        return (self.sp_active is not None and self.sp_active.er is er) \
            or er in self.sp_queue

    def _sp_kernel_route(self) -> bool:
        """Did the SP ladder's chunk attention take the paged-DMA
        kernel route (parallel/sequence.sp_chunk_attention)? Drives the
        device-time byte model: the kernel streams the committed prefix
        once; the XLA gather pays a materialize write + re-read."""
        from ..ops.attention import resolve_attention_impl

        return resolve_attention_impl(
            self.config.model.attention_impl) == "pallas"

    def _sp_drop(self, er: EngineRequest) -> None:
        """Remove a cancelled/finished request from the SP ladder. Any
        already-dispatched chunk work is pure over-compute into the
        request's own blocks — freed with the request, nothing leaks."""
        if self.sp_active is not None and self.sp_active.er is er:
            self.sp_active = None
        if er in self.sp_queue:
            self.sp_queue.remove(er)

    async def _sp_advance(self, loop) -> bool:
        """One pass of the SP ladder: dispatch the active request's next
        mesh-wide chunk (dispatch-only — the device runs ahead while the
        loop serves decode), register the previously completed chunk's
        blocks into the prefix cache, and on the final chunk run the
        early decode handoff + drain."""
        with span("sched.prefill.build", step=self.passes, rows=1):
            st = self.sp_active
            while st is None and self.sp_queue:
                er = self.sp_queue.pop(0)
                if er.finish is not None or er.ctx.is_stopped:
                    continue
                st = self.sp_active = _SpPrefill(er=er, t0=time.monotonic())
            if st is None:
                return False
            er = st.er
            if er.finish is not None or er.ctx.is_stopped:
                self.sp_active = None
                if er.finish is None:
                    self._finish(er, FinishReason.CANCELLED)
                return True
            total = len(er.prefill_tokens)
            start = er.prefill_pos
            end = min(start + self.runner.sp_chunk_tokens, total)
            final = end >= total
        with span("sched.prefill.dispatch", step=self.passes, rows=1,
                  tokens=end - start):
            t_disp = time.monotonic()
            outs = self.runner.sp_prefill_chunk(
                er.prefill_tokens[:end], start, er.block_ids,
                temperature=er.temperature, top_k=er.top_k, top_p=er.top_p,
                min_p=er.min_p, presence_penalty=er.presence_penalty,
                frequency_penalty=er.frequency_penalty,
                repetition_penalty=er.repetition_penalty,
                seed_keys=er.base_key, counters=er.generated,
                sample_slot=er.slot, commit=final,
                want_top=final and er.logprobs_n > 0,
            )
            self._inflight = True
        with span("sched.prefill.emit", step=self.passes, rows=1):
            self.steps += 1
            st.chunks += 1
            self._sp_chunks_c.inc()
            self._sp_tokens_c.inc(end - start)
            er.prefill_pos = end
            er.context_len = end
            # chunk-commit seam: the chunk's blocks become matchable (and KV
            # events publish, feeding fabric ownership) as soon as the write
            # is SCHEDULED — device dispatch order guarantees it lands
            # before any later program reads it, the same contract the dense
            # ladder and the disagg streamed transfer rely on
            self._register_completed_blocks(er)
            self.flight.record(
                "scheduler.sp_chunk", request_id=er.request_id,
                trace_id=er.ctx.trace_id, start=start, end=end, final=final,
                chunk=st.chunks,
            )
        if not final:
            return True
        st.final_dispatch_t = t_disp
        try:
            await self._sp_finish(loop, st, outs)
        finally:
            self.sp_active = None
        return True

    async def _sp_finish(self, loop, st: _SpPrefill, outs) -> None:
        """Early decode handoff + drain for a finished SP ladder.

        The final chunk's sampled token is still device-resident; when
        the request can take a plain decode burst, dispatch one
        IMMEDIATELY with that token composed into the batch row on
        device — the first decode burst is then executing before any
        host sync of the prefill outputs happens (the overlap the tests
        pin). One executor sync drains both; emission runs the exact
        dense-path discipline (tokens past a finish are discarded with
        the request's own blocks)."""
        er = st.er
        cfg = self.config
        next_tokens, lps, top_vals, top_ids = outs
        hs = self._host
        b = cfg.max_batch_size
        bs = cfg.kv_block_size
        ctx0 = er.context_len  # the first sampled token's position
        k_steps = cfg.multi_step_decode
        with span("sched.decode.build", step=self.passes, rows=1):
            burst = None
            can_burst = (
                self.runner._burst is not None
                and er.guided is None
                and er.max_new > 1
                and ctx0 + k_steps + 1 <= cfg.max_model_len
                and all(self._ensure_block_for(er, ctx0 + j)
                        for j in range(k_steps))
            )
            # allocator contract (same as every dense dispatch site): any
            # host-offload gathers the block growth above deferred must
            # materialize BEFORE the burst overwrites the evicted slots
            self.allocator.flush_offload()
            if can_burst:
                hs.sync_blocks(er)
                w = self._table_width([er], (self.runner, "decode_burst"))
                btab = hs.btab[:, :w].copy()
                import jax.numpy as jnp
                tok0 = jnp.zeros(b, jnp.int32).at[er.slot].set(next_tokens[0])
                pos0 = np.zeros(b, np.int32)
                pos0[er.slot] = ctx0
                ctrs = np.zeros(b, np.int32)
                ctrs[er.slot] = er.generated + 1  # after the prefill token
                commit = np.zeros(b, bool)
                commit[er.slot] = True
        if can_burst:
            with span("sched.decode.dispatch", step=self.passes, rows=1):
                t_burst = time.monotonic()
                burst = self.runner.decode_burst(
                    tok0, pos0, btab, hs.temp, hs.top_k, hs.top_p,
                    min_p=hs.min_p, presence_penalty=hs.pres,
                    frequency_penalty=hs.freq, repetition_penalty=hs.rep,
                    seed_keys=hs.keys, counters=ctrs, commit=commit,
                    want_top=er.logprobs_n > 0,
                )
                self._count_decode_rows("decode_burst", [er], k_steps)
                self.steps += 1
                self._sp_exposed_h.observe(t_burst - st.final_dispatch_t)
                self.flight.record(
                    "scheduler.sp_handoff", request_id=er.request_id,
                    trace_id=er.ctx.trace_id, k_steps=k_steps,
                )

        synced, t_done = await self._fetch(
            loop, "prefill",
            [next_tokens, lps, top_vals, top_ids, *(burst or ())],
            turn=not self._decode_follows([er]))
        with span("sched.prefill.emit", step=self.passes, rows=1):
            if burst is None:
                self._sp_exposed_h.observe(t_done - st.final_dispatch_t)
            if self.device_time is not None:
                self.device_time.observe(
                    "prefill_sp", "prefill", st.final_dispatch_t, t_done,
                    read_bytes=self.device_time.sp_prefill_read_bytes(
                        st.chunks, er.context_len,
                        kernel=self._sp_kernel_route(),
                    ),
                )
                if burst is not None:
                    # the burst's own arrays came with the copies, after
                    # the first token: the loop's stamp bounds its end
                    self.device_time.observe(
                        "decode_burst", "decode", t_burst, time.monotonic(),
                        read_bytes=self.device_time.decode_read_bytes(
                            k_steps, er.context_len,
                        ),
                        tokens=k_steps,
                    )
            self.flight.record(
                "scheduler.sp_drain", request_id=er.request_id,
                trace_id=er.ctx.trace_id, chunks=st.chunks,
                handoff=burst is not None,
            )
            toks_pf, lps_pf, tv_pf, ti_pf = synced[:4]
            er.ctx.add_stage("prefill")
            token = int(toks_pf[0])
            er.pending_token = token
            er.generated += 1
            er.ring_tail.append(token)
            er.finish = self._check_finish(er, token)
            self._guided_after_token(er)
            self._emit(
                er, token,
                float(lps_pf[0]) if er.want_logprobs else None,
                self._top_row(er, tv_pf, ti_pf, 0),
            )
            if er.finish is not None:
                # trailing burst tokens (if any) are pure over-decode into
                # the request's own blocks — freed with the request
                self._finish(er, er.finish, emit=False)
                return
            if burst is None:
                return
            toks_b, lps_b, tv_b, ti_b = synced[4:]
            for j in range(k_steps):
                if er.finish is not None or er.ctx.is_stopped:
                    break
                tok_j = int(toks_b[j, er.slot])
                self._advance_row(er, tok_j)
                self._guided_after_token(er)
                self._emit(
                    er, tok_j,
                    float(lps_b[j, er.slot]) if er.want_logprobs else None,
                    self._top_row(er, tv_b[j], ti_b[j], er.slot),
                )
                if er.finish is not None:
                    self._finish(er, er.finish, emit=False)

    async def _prefill_chunk(self, loop, ers: List[EngineRequest]) -> None:
        """ONE batched prefill step: every prefilling request advances a
        chunk as a row of the same program (rows padded to the power-of-
        two ladder, lengths to the common bucket); rows that finish their
        prompt sample/emit. The token budget splits across rows."""
        cfg = self.config
        with span("sched.prefill.build", step=self.passes, rows=len(ers)):
            rows = cfg.prefill_row_bucket(len(ers))
            # the ITL bound is on COMPUTED positions = padded rows x padded
            # bucket, so cap the bucket at the largest that keeps
            # rows * bucket within budget (padding included), not just the
            # per-row take (prefill_bucket_cap — shared with the disagg
            # prefill worker's streamed chunking)
            cap = prefill_bucket_cap(cfg, rows)
            # a full batch can exceed the budget even at the smallest
            # bucket — admit fewer rows this step instead of overrunning
            # (the tail of `ers` stays in self.prefilling for next pass)
            while cap is None and rows > cfg.PREFILL_ROW_BUCKETS[0]:
                rows = max(r for r in cfg.PREFILL_ROW_BUCKETS if r < rows)
                ers = ers[:rows]
                cap = prefill_bucket_cap(cfg, rows)
            # budget < one row at the smallest bucket: best-effort floor
            # (a single row must still advance or prefill livelocks)
            bucket_cap = cap if cap is not None else cfg.prefill_buckets[0]
            plan = []  # (er, start, end, take, final)
            for er in ers:
                total = len(er.prefill_tokens)
                take = min(total - er.prefill_pos, bucket_cap)
                end = er.prefill_pos + take
                if self.window is not None:
                    # two kinds of page: what fell behind this chunk's
                    # first query goes back, then the chunk's own pages
                    # are taken (the full kind's came with admission)
                    self._release_window(er, er.prefill_pos)
                    if not self._take_window(
                            er, -(-end // cfg.kv_block_size)):
                        logger.warning("window pages exhausted: preempting "
                                       "%s in prefill", er.request_id)
                        self.prefilling.remove(er)
                        self._preempt(er)
                        continue
                plan.append((er, er.prefill_pos, end, take, end >= total))
            if not plan:
                return
            bucket = cfg.bucket_for(max(p[3] for p in plan))  # <= bucket_cap

            tokens = np.zeros((rows, bucket), np.int32)
            positions = np.zeros((rows, bucket), np.int32)
            btab = np.zeros((rows, cfg.blocks_per_seq), np.int32)
            slot_map = np.full((rows, bucket), -1, np.int32)
            ctx_lens = np.ones(rows, np.int32)
            last_idx = np.zeros(rows, np.int32)
            temp = np.zeros(rows, np.float32)
            top_k = np.zeros(rows, np.int32)
            top_p = np.ones(rows, np.float32)
            min_p = np.zeros(rows, np.float32)
            pres = np.zeros(rows, np.float32)
            freq = np.zeros(rows, np.float32)
            rep = np.ones(rows, np.float32)
            keys = np.zeros((rows, 2), np.uint32)
            ctrs = np.zeros(rows, np.int32)
            sample_slots = np.zeros(rows, np.int32)
            commit = np.zeros(rows, bool)
            targets = np.zeros((rows, bucket), np.int32)
            n_tgts = [0] * len(plan)
            want_prompt = False
            wtab = None if self.window is None else np.zeros_like(btab)

            for i, (er, start, end, take, final) in enumerate(plan):
                t, p, bt, sm, cl, li = build_prefill_arrays(
                    cfg, er.prefill_tokens[:end], start, er.block_ids,
                    bucket=bucket,
                )
                tokens[i], positions[i] = t[0], p[0]
                btab[i], slot_map[i] = bt[0], sm[0]
                if wtab is not None:
                    wtab[i] = self._host.wtab[er.slot]
                ctx_lens[i], last_idx[i] = cl[0], li[0]
                (temp[i], top_k[i], top_p[i], min_p[i], pres[i], freq[i],
                 rep[i]) = (er.temperature, er.top_k, er.top_p, er.min_p,
                            er.presence_penalty, er.frequency_penalty,
                            er.repetition_penalty)
                keys[i] = er.base_key
                ctrs[i] = er.generated
                sample_slots[i] = er.slot
                commit[i] = final
                if er.want_prompt_lps and not er.prompt_lps_emitted:
                    # target at bucket index j (absolute position start+j) is
                    # the NEXT prompt token; only prompt positions count (a
                    # resumed request's generation tokens are not prompt)
                    want_prompt = True
                    nxt = er.prefill_tokens[start + 1 : end + 1]
                    targets[i, : len(nxt)] = nxt
                    n_tgts[i] = max(0, min(take, len(er.prompt) - 1 - start))

        window = cfg.model.sliding_window
        for _, start, end, *_ in plan:
            full, band = prefill_pairs(start, end, window)
            self._prefill_pairs_ctr.inc(full, kind="full")
            if window:
                self._prefill_pairs_ctr.inc(band, kind="window")
        self._prefill_chunks_ctr.inc()
        with span("sched.prefill.dispatch", step=self.passes,
                  rows=rows, tokens=rows * bucket):
            t0 = time.monotonic()
            next_tokens, lps, top_vals, top_ids, plps, _ = self.runner.step(
                tokens, positions, btab, slot_map, ctx_lens, last_idx,
                temp, top_k, top_p,
                min_p=min_p, presence_penalty=pres, frequency_penalty=freq,
                repetition_penalty=rep, seed_keys=keys, counters=ctrs,
                sample_slots=sample_slots, commit=commit,
                want_top=any(er.logprobs_n > 0 for er, *_ in plan),
                targets=targets, want_prompt=want_prompt,
                **({} if wtab is None else {"window_tables": wtab}),
            )
            self.steps += 1
            if self.draft is not None:
                # mirror the chunk on the draft model: same tokens, same
                # slots, same (shared) block ids — so the draft cache holds
                # the full context every speculative round assumes. Sampling
                # is inert (commit all-False; nothing reads the outputs).
                dtemp, dtop_k, dtop_p, dkw = self._inert_sampling(rows)
                self.draft.step(
                    tokens, positions, btab, slot_map, ctx_lens, last_idx,
                    dtemp, dtop_k, dtop_p,
                    sample_slots=sample_slots,
                    commit=np.zeros(rows, bool), want_top=False, **dkw,
                )
            self._inflight = True

        # what follows a dispatch on the host: the chunk's blocks become
        # matchable; the first-token emit comes after the sync below
        with span("sched.prefill.emit", step=self.passes, rows=len(plan)):
            finals = []
            for i, (er, start, end, take, final) in enumerate(plan):
                if n_tgts[i] > 0:
                    # keep the DEVICE row; one host conversion at the end
                    er.prompt_lp_parts.append((plps[i : i + 1], n_tgts[i]))
                er.prefill_pos = end
                er.context_len = end
                # prefix blocks become matchable (and KV events publish) as
                # soon as each chunk's KV is scheduled — device ordering
                # guarantees the write lands before any later step reads it
                self._register_completed_blocks(er)
                logger.debug("prefill chunk %s [%d:%d)/%d %.1fms",
                             er.request_id, start, end,
                             len(er.prefill_tokens),
                             1e3 * (time.monotonic() - t0))
                if final:
                    finals.append(i)
        if not finals:
            self._chunk_unread = True
            return
        if self.unit is not None:
            # a block family's prefill samples nothing: its first tokens
            # come from the first block's passes, so there is nothing to
            # fetch and the rows go on to decode
            for i in finals:
                self.prefilling.remove(plan[i][0])
                self._end_block_prefill(plan[i][0])
            return

        if self._ahead is not None and self._ahead.behind_chunk:
            # the decode step in flight stands between the chunk of the
            # pass before and this one in the device's queue. Waiting for
            # this chunk first would hold its tokens, ready a whole chunk
            # earlier, until this chunk has run (the longest gap a client
            # sees would be two chunks, where it was one): read it first
            await self._land_ahead(loop, "behind_chunk")
        # every device→host transfer off the event loop: any accumulated
        # prompt-logprob rows (an echo+logprobs prompt may hold many chunk
        # rows) first, as they always were, then the final rows' outputs
        lp_rows = [row for i in finals
                   for row, _ in plan[i][0].prompt_lp_parts]
        synced, t_ready = await self._fetch(
            loop, "prefill",
            [*lp_rows, next_tokens, lps, top_vals, top_ids],
            turn=not self._decode_follows([plan[i][0] for i in finals]),
            tokens_at=len(lp_rows))
        toks, lpn, tv, ti = synced[len(lp_rows):]
        with span("sched.prefill.emit", step=self.passes, rows=len(finals)):
            host_rows = iter(synced[:len(lp_rows)])
            plists = {
                i: [float(x) for _, cnt in plan[i][0].prompt_lp_parts
                    for x in next(host_rows)[0, :cnt]]
                for i in finals
                if plan[i][0].prompt_lp_parts
            }
            if self.device_time is not None:
                # non-final chunks never sync; their device time folds into
                # this observation via the serialized-interval estimator
                self.device_time.observe("prefill", "prefill", t0, t_ready)
            for i in finals:
                er = plan[i][0]
                self.prefilling.remove(er)
                er.ctx.add_stage("prefill")
                prompt_lps = None
                if er.want_prompt_lps and not er.prompt_lps_emitted:
                    # OpenAI/vLLM convention: the first prompt token has no
                    # conditioning prefix — its entry is None
                    prompt_lps = [None] + plists.get(i, [])
                    er.prompt_lps_emitted = True
                er.prompt_lp_parts = []
                if er.max_new == 0:
                    # prompt-scoring request (echo + logprobs + max_tokens=0):
                    # the prefill ran for its logits; no token is emitted
                    er.finish = FinishReason.LENGTH
                    er.out_queue.put_nowait(EngineOutput(
                        token_ids=[], finish_reason=er.finish,
                        prompt_logprobs=prompt_lps,
                    ))
                    self._finish(er, er.finish, emit=False)
                    continue
                token = int(toks[i])
                er.pending_token = token
                er.generated += 1  # += not =: resumed requests keep their count
                er.ring_tail.append(token)
                er.finish = self._check_finish(er, token)
                self._guided_after_token(er)
                self._emit(er, token, float(lpn[i]) if er.want_logprobs else None,
                           self._top_row(er, tv, ti, i), prompt_lps=prompt_lps)
                if er.finish is not None:
                    self._finish(er, er.finish, emit=False)

    def _spec_eligible(self, er: EngineRequest) -> bool:
        """Speculative verify preserves the exact stream only for greedy,
        penalty-free, bias-free requests that want no logprobs: the
        verify step's raw argmax must equal what sequential sampling
        would pick, and per-position logprobs are not computed. Guided
        rows are excluded too — their mask changes every step."""
        return (er.temperature == 0.0
                and er.presence_penalty == 0.0
                and er.frequency_penalty == 0.0
                and er.repetition_penalty == 1.0
                and not er.want_logprobs and er.logprobs_n == 0
                and not er.req.sampling_options.logit_bias
                and er.guided is None)

    def _guided_allowed_ids(self, er: EngineRequest) -> List[int]:
        """Token ids the constraint permits next, plus the eos ids
        wherever the constrained output may legally end (a terminal trie
        node; a complete top-level JSON value)."""
        v = self.config.model.vocab_size
        ids, at_end = er.guided.allowed()
        allowed = [t for t in ids if 0 <= t < v]
        if at_end:
            allowed.extend(
                int(e) for e in er.req.eos_token_ids or []
                if 0 <= int(e) < v
            )
        return allowed

    def _guided_mask(self, er: EngineRequest) -> np.ndarray:
        """Dense [V] additive mask for the NEXT sampled token: 0 for the
        allowed ids, a large negative everywhere else. Used at admission
        (set_sample_row); per-step updates edit sparsely instead."""
        v = self.config.model.vocab_size
        mask = np.full(v, -1e9, np.float32)
        er.guided_allowed = self._guided_allowed_ids(er)
        mask[er.guided_allowed] = 0.0
        return mask

    def _guided_after_token(self, er: EngineRequest,
                            edit: bool = True) -> None:
        """Advance the constraint past the just-sampled token; install
        the next mask, or finish when the constraint completes. Runs
        between _check_finish and _emit so the completing token still
        streams.

        ``edit=False`` (the chained drain): advance the cursor and judge
        verdicts only — the device computed this token's mask from the
        transition table, and the barrier reinstalls the host mask if
        the row ever returns to the sync path."""
        if er.guided is None or er.finish is not None:
            return
        key_before = er.guided.state_key()
        verdict = er.guided.advance(er.pending_token)
        if verdict != "ok":
            # "done": constraint complete (closing brace / final choice
            # token). "derail": eos at a legal end point (eos is never
            # in the constraint's own alphabet) or a defensive fallback.
            er.finish = FinishReason.STOP
            return
        if not edit:
            return
        if er.guided.state_key() == key_before:
            # same machine state → identical allowed set (e.g. JSON
            # string-body tokens): the installed mask is already right
            return
        # sparse edit: only the old node's and new node's neighborhoods
        # change — O(branching), not O(vocab), per token
        user_bias = er.req.sampling_options.logit_bias or {}
        new_allowed = self._guided_allowed_ids(er)
        if not new_allowed:
            # dead state mid-stream (vocab cannot continue the grammar
            # and no legal end here): stop at the valid prefix instead
            # of emitting an unconstrained token through an all-banned
            # mask
            er.finish = FinishReason.STOP
            return
        new_set = set(new_allowed)
        changed = list(new_set | set(er.guided_allowed))
        vals = [
            (0.0 if t in new_set else -1e9) + float(user_bias.get(t, 0.0))
            for t in changed
        ]
        if not self.runner.edit_bias_entries(er.slot, changed, vals):
            # neighborhood wider than the largest edit bucket: rebuild
            mask = self._guided_mask(er)
            for tid, b in user_bias.items():
                tid = int(tid)
                if 0 <= tid < len(mask):
                    mask[tid] += float(b)
            self.runner.set_bias_row(er.slot, mask)
        er.guided_allowed = new_allowed

    @staticmethod
    def _inert_sampling(n: int):
        """Greedy, penalty-free sampling arrays for draft-mirror runs
        (nothing reads the sampled outputs): positional (temperature,
        top_k, top_p) plus the keyword tail as one dict."""
        zf = np.zeros(n, np.float32)
        zi = np.zeros(n, np.int32)
        return zf, zi, np.ones(n, np.float32), dict(
            min_p=zf, presence_penalty=zf, frequency_penalty=zf,
            repetition_penalty=np.ones(n, np.float32),
            seed_keys=np.zeros((n, 2), np.uint32), counters=zi,
        )

    async def _draft_propose(self, loop, active: List[EngineRequest],
                             K: int) -> dict:
        """K greedy proposals per row from the draft model's fused burst.

        ONE extra dispatch per round: the draft's ``multi_step_decode``
        is K+1, so the burst also writes the K-th proposal's KV into the
        mirror cache (the (K+1)th sampled token is discarded — it exists
        only to drive that final KV write). Inactive rows run inert.
        """
        cfg = self.config
        b = cfg.max_batch_size
        with span("sched.decode.build", step=self.passes, rows=len(active)):
            w = self._table_width(active, (self.draft, "decode_burst"))
            tokens0 = np.zeros(b, np.int32)
            positions0 = np.zeros(b, np.int32)
            btab = np.zeros((b, w), np.int32)
            commit = np.zeros(b, bool)
            for er in active:
                i = er.slot
                tokens0[i] = er.pending_token
                positions0[i] = er.context_len
                btab[i, : len(er.block_ids)] = er.block_ids
                commit[i] = True
            temp, top_k, top_p, kw = self._inert_sampling(b)
        with span("sched.decode.dispatch", step=self.passes,
                  rows=len(active)):
            toksK, *_ = self.draft.decode_burst(
                tokens0, positions0, btab, temp, top_k, top_p,
                commit=commit, want_top=False, **kw,
            )
            self._inflight = True

        # the verify dispatch below is the pass's last: the turn is its
        (tk,), _ = await self._fetch(loop, "decode", [toksK], turn=False)
        self.steps += 1
        return {
            er.slot: [int(t) for t in tk[:K, er.slot]] for er in active
        }

    async def _decode_spec(self, loop, active: List[EngineRequest]) -> None:
        """One speculative decode pass: propose up to K tokens per row —
        from the row's own history (ngram) or from the draft model's
        fused K-step burst — verify all K+1 positions in ONE target
        forward (decode is bandwidth-bound — the weights stream once
        either way), and emit the accepted prefix plus the correction
        token.

        KV discipline matches the burst path: every proposed position's
        KV is written during the verify (and, for draft proposals, into
        the draft's mirror cache during the burst); rejected positions'
        slots are simply rewritten when decoding reaches them again, and
        block registration only ever covers positions below the host
        context_len, which advances by accepted tokens only.
        """
        cfg = self.config
        b = cfg.max_batch_size
        bs = cfg.kv_block_size
        # verify-step dispatches are not decode bursts; stop the clock
        self._last_burst_done_t = None
        K = cfg.spec_draft_tokens if self.draft is not None \
            else cfg.spec_ngram_tokens
        S = K + 1
        if any(er.context_len + S + 1 > cfg.max_model_len for er in active):
            # a row is within K of the horizon; it finishes momentarily
            return await self._decode(loop, active, 1)

        props: dict = {}
        with span("sched.decode.build", step=self.passes, rows=len(active)):
            if self.draft is None:
                # ngram proposals first: when nothing matches anywhere
                # (non-repetitive output), the K+1-wide verify would be
                # pure per-step overhead — run the normal decode (incl.
                # its fused burst) instead
                for er in active:
                    history = list(er.seq.token_ids) + [er.pending_token]
                    props[er.slot] = ngram_propose(
                        history, cfg.spec_ngram_match, K
                    )
            verify = self.draft is not None or any(props.values())
            if verify:
                for er in list(active):
                    ok = all(
                        self._ensure_block_for(er, er.context_len + j)
                        for j in range(S)
                    )
                    if not ok:
                        logger.warning("KV OOM: preempting %s",
                                       er.request_id)
                        self._preempt(er)
                        active.remove(er)
                self.allocator.flush_offload()
        if not verify:
            return await self._decode(loop, active, cfg.multi_step_decode)
        if not active:
            return

        if self.draft is not None:
            # draft proposals: ONE K-step greedy burst of the small model
            # (blocks are allocated above, so the burst's KV writes into
            # the mirror cache land in valid slots)
            props = await self._draft_propose(loop, active, K)

        with span("sched.decode.build", step=self.passes, rows=len(active)):
            w = self._table_width(active, (self.runner, "verify"))
            tokens = np.zeros((b, S), np.int32)
            positions = np.zeros((b, S), np.int32)
            slot_map = np.full((b, S), -1, np.int32)
            btab = np.zeros((b, w), np.int32)
            ctx_lens = np.ones(b, np.int32)
            last_idx = np.zeros(b, np.int32)

            for er in active:
                i = er.slot
                pos0 = er.context_len
                prop = props[i]
                row = [er.pending_token] + prop
                tokens[i, : len(row)] = row
                positions[i] = pos0 + np.arange(S)
                for j in range(S):
                    pj = pos0 + j
                    slot_map[i, j] = er.block_ids[pj // bs] * bs + pj % bs
                btab[i, : len(er.block_ids)] = er.block_ids
                # causal masking is by absolute position, so padding rows'
                # junk keys (past their proposal) are invisible to every
                # valid query at an earlier position
                ctx_lens[i] = pos0 + S
                last_idx[i] = len(row) - 1

            zf, zi = np.zeros(b, np.float32), np.zeros(b, np.int32)
        with span("sched.decode.dispatch", step=self.passes,
                  rows=len(active)):
            t_dispatch = time.monotonic()
            *_, greedy_all = self.runner.step(
                tokens, positions, btab, slot_map, ctx_lens, last_idx,
                zf, zi, np.ones(b, np.float32),
                min_p=zf, presence_penalty=zf, frequency_penalty=zf,
                repetition_penalty=np.ones(b, np.float32),
                seed_keys=np.zeros((b, 2), np.uint32), counters=zi,
                sample_slots=np.arange(b, dtype=np.int32),
                commit=np.zeros(b, bool),  # greedy chain: counts never consulted
                want_top=False, want_greedy=True,
            )
            self._inflight = True

        (ga,), t_ready = await self._fetch(loop, "decode", [greedy_all])
        with span("sched.decode.emit", step=self.passes, rows=len(active)):
            if self.device_time is not None:
                # the verify forward is one decode-shaped step over S
                # positions: weights once + each row's (ctx + S) KV
                self.device_time.observe(
                    "spec_verify", "decode", t_dispatch, t_ready,
                    read_bytes=self.device_time.decode_read_bytes(
                        1, sum(er.context_len + S for er in active),
                    ),
                    tokens=len(active),
                )
            self.steps += 1

            for er in active:
                if er.finish is not None:
                    continue
                i = er.slot
                prop = props[i]
                a = 0
                while a < len(prop) and int(ga[i, a]) == prop[a]:
                    a += 1
                self.spec_proposed += len(prop)
                self.spec_accepted += a
                self._spec_proposed_ctr.inc(len(prop))
                self._spec_accepted_ctr.inc(a)
                # emit accepted prefix + the correction token, with the same
                # pending-token discipline as every other decode path
                for j in range(a + 1):
                    if er.finish is not None:
                        break
                    token = int(ga[i, j])
                    self._advance_row(er, token)
                    self._emit(er, token, None, None)
                    if er.finish is not None:
                        self._finish(er, er.finish, emit=False)

    def _table_width(self, rows: List[EngineRequest], *asked) -> int:
        """The block-table width of one dispatch over ``rows``. ``asked``:
        a (runner, decode-shaped program) pair for each program handed
        the table (the target's, and the draft's where it mirrors the
        dispatch; a runner that is None is skipped). Each runner says the
        narrowest width it has its program at that covers the longest
        row (``ModelRunner.table_width``: the full width unless the
        program's trace read the table at its width), and the widest of
        those exists for all of them. A runner that says nothing of
        widths (a test's stand-in) gets the configuration's ladder."""
        nblocks = max(len(er.block_ids) for er in rows)
        return max(
            r.table_width(program, nblocks) if hasattr(r, "table_width")
            else self.config.kv_width_bucket(nblocks)
            for r, program in asked if r is not None)

    def _count_decode_rows(self, program: str, live: List[EngineRequest],
                           steps: int = 1) -> None:
        """Count one dispatch of the decode program ``program`` (the
        runner's name for it) over the rows ``live`` that hold a
        sequence, each in the row of its slot:
        the batch's rows a step, and the pad rows among them where the
        program's attention kernels walk a list of live rows and so
        take no grid step for them (``ModelRunner.row_list_programs``,
        recorded when the program was traced: call this after the
        dispatch). Likewise the rows its sampling tail ran on
        (``sampling_tile_programs``, ``sampling.tiled_rows``; a row that
        freezes inside a burst still counts for all its steps), and those
        of them whose search was the short one
        (``sampling_short_programs``, ``sampling.short_search_rows``)."""
        b = self.config.max_batch_size
        self._decode_rows_ctr.inc(b * steps)
        if program in getattr(self.runner, "row_list_programs", ()):
            self._decode_rows_skipped_ctr.inc((b - len(live)) * steps)
        self._sampling_rows_ctr.inc(b * steps)
        tile = getattr(self.runner, "sampling_tile_programs", {}).get(program)
        run = tiled_rows(len(live), b, tile) if tile else b
        self._sampling_rows_run_ctr.inc(run * steps)
        if program in getattr(self.runner, "sampling_short_programs", ()):
            touched = [
                er.slot for er in live
                if er.guided is not None
                or er.req.sampling_options.logit_bias
                or (er.presence_penalty, er.frequency_penalty,
                    er.repetition_penalty) != (0.0, 0.0, 1.0)]
            if touched:          # by tile; else every row run, as is usual
                held, plain = np.zeros(b, bool), np.ones(b, bool)
                held[[er.slot for er in live]] = True
                plain[touched] = False
                run = short_search_rows(held, plain, tile or 0)
            self._sampling_short_rows_ctr.inc(run * steps)

    async def _decode(self, loop, active: List[EngineRequest],
                      k_steps: int = 1) -> None:
        """One decode step over ``active`` (a fused burst of ``k_steps``
        where the caller asks for one), **one step ahead of the host**
        where it can be: the step this pass dispatches (k) goes to the
        device before the step the pass before dispatched (k−1) has been
        read, and the host's work for k−1 (the wait for its tokens, the
        emit loop, the frontend's turn) runs under k. A pass is then
        build(k), dispatch(k), request(k), yield, sync(k−1), emit(k−1).

        Building k with k−1 in flight, a row of k−1 is taken as
        continuing: one position and one counter on, its token
        ``step_inputs.FED`` (the program reads it off k−1's
        ``next_tokens``, which never left the device). A row the host
        knows k−1 ends (``_ends_at_next``) is left out; one that turns
        out to have ended there, by a stop token or string, has its row
        of k dropped when k is read (``_decode_land``). A row that joins
        from a prefill chunk brings its token from the host, as ever.
        The host still decides every finish, block, window page and
        admission itself, from tokens it has read: one step later.

        Where the pass cannot go ahead it says why
        (``_ahead_block_reason``; no block or window page for a
        continuing row is ``kv_oom``) on
        ``dynamo_engine_sync_fallback_total``: k−1 is read and applied
        first and k built from committed state, which is what preemption
        needs. A step that the next may run ahead of is left in flight;
        any other is read before the pass ends, as every step was. A
        pass whose prefill chunk ends a prompt reads that chunk's first
        token before its decode dispatch, as ever (``_prefill_chunk``);
        where the step in flight stands behind the chunk of the pass
        before, it is read before that wait (``behind_chunk``), so that
        its tokens are not held for a chunk they did not wait for.

        A family whose decode unit is a block runs the same way through
        the same slot (``_halves``: ``_block_dispatch`` / ``_block_land``
        in place of ``_decode_dispatch`` / ``_decode_land``): pass k+1
        goes out before pass k's ids are read, a row's block fed k's
        ``new_ids`` on the device (``_block_next``), unless the rule
        lets the confidences decide how many positions a pass unmasks
        (``dynamic_unmask``)."""
        cfg = self.config
        # a K-step burst writes K tokens of KV per row before the host
        # sees any of them, so every row needs blocks for all K positions
        # up front, and no row may run past the block-table/model-len
        # horizon mid-burst (such rows finish within one burst anyway —
        # fall back to per-token stepping for everyone this pass)
        if k_steps > 1 and any(
            er.context_len + k_steps + 1 > cfg.max_model_len for er in active
        ):
            k_steps = 1
        if self.draft is not None:
            # plain decode must keep the draft's mirror cache current
            # (the next speculative round assumes draft KV for every
            # position < context); the mirror runs per-token, so pin the
            # target to per-token too — with a draft configured, the
            # fused burst's role is played by speculation itself
            k_steps = 1
        if any(er.guided is not None for er in active):
            # guided rows rewrite their mask between tokens on the host;
            # a fused burst would sample K tokens against one stale mask.
            # NOTE this pins the WHOLE batch (all rows share one
            # dispatch), so concurrent unguided requests also lose the
            # burst while any guided request is active — documented in
            # docs/models.md. Splitting guided rows into their own
            # dispatch would pay two program launches per step, worse
            # than the amortization it saves at serving batch sizes.
            k_steps = 1

        dispatch, land = self._halves
        hold = (self._ahead_block_reason(active, k_steps)
                if self._feeds_tokens else None)
        prev, step = self._ahead, None
        if prev is not None and hold is None:
            step = dispatch(active, 1, prev)
        fell = "kv_oom" if step is False else hold
        if fell is not None:
            self._note_sync_fallback(fell)
        if prev is not None and fell is not None:
            # k−1 is read and applied before k is built
            self._ahead = None
            await land(loop, prev)
            prev = None
            active = [er for er in active if er.finish is None]
        if prev is None:
            step = dispatch(active, k_steps, None)
        self._ahead = step
        if prev is not None:
            await land(loop, prev)   # sync(k−1), emit(k−1): under k
        if step is not None and (hold is not None or not self._feeds_tokens):
            self._ahead = None
            await land(loop, step)

    def _ahead_block_reason(self, active: List[EngineRequest],
                            k_steps: int) -> Optional[str]:
        """Why must the host read this pass's decode step before it
        builds the next? None: it need not, and the step may stay in
        flight. What the pass can see in its input, nothing else."""
        if any(er.guided is not None for er in active):
            # the host rewrites a guided row's mask from the token
            return "guided"
        if self.draft is not None:
            # the draft's mirror step takes the tokens from the host
            return "draft_mirror"
        if k_steps > 1:
            # a fused burst feeds itself; the host reads K tokens a row
            return "multi_step"
        if (self.unit is not None
                and self.unit.strategy == "low_confidence_dynamic"):
            # how many positions a pass unmasks depends on the
            # confidences: the host cannot build the next pass unread
            return "dynamic_unmask"
        return None

    def _ends_at_next(self, er: EngineRequest) -> bool:
        """Does the host know, before it has read the step in flight,
        that the token of that step is ``er``'s last? By the count and by
        the model-length horizon, ``_check_finish``'s last two lines one
        token on (a stop token or string it cannot know)."""
        return (er.generated + 1 >= er.fin_max_new
                or er.context_len + 2 >= self.config.max_model_len)

    async def _land_ahead(self, loop, reason: str) -> None:
        """Read and apply the decode step in flight, if there is one,
        because of ``reason``: what follows builds from committed state
        (another decode path, the loop's end; where the chain has
        ``_chain_barrier``)."""
        step, self._ahead = self._ahead, None
        if step is not None:
            self._note_sync_fallback(reason)
            await self._halves[1](loop, step)

    def _decode_dispatch(self, active: List[EngineRequest], k_steps: int,
                         prev: Optional[_StepInFlight]):
        """Build and dispatch one decode step (``sched.decode.build``,
        ``.dispatch``, ``.request``) and return its ``_StepInFlight``;
        None where no row is left to step. ``prev``: the step in flight
        this one is built ahead of (``_decode``); a block or window page
        that cannot be had then returns False with nothing preempted
        (what was reserved is what the rows need once ``prev`` is
        applied), where from committed state (``prev`` None) the row is
        preempted."""
        cfg = self.config
        b = cfg.max_batch_size
        bs = cfg.kv_block_size
        with span("sched.decode.build", step=self.passes,
                  rows=len(active)):
            inflight = ({id(er) for er, _ in prev.rows}
                        if prev is not None else ())
            # (row, its position, its counter, is its token on the
            # device): a row of the step in flight stands one token on,
            # and is left out where that token is known to be its last
            rows = []
            for er in active:
                on = int(id(er) in inflight)
                if not (on and self._ends_at_next(er)):
                    rows.append((er, er.context_len + on,
                                 er.generated + on, bool(on)))
            # make sure each active sequence has blocks for its next position
            # (all k_steps of them under a burst)
            if self.window is not None:
                # two kinds of page: every row first gives back what fell
                # behind its next query, so that what one row frees
                # another can take in this same pass
                for er, pos, *_ in rows:
                    self._release_window(er, pos)
            for row in list(rows):
                er, pos, *_ = row
                ok = all(
                    self._ensure_block_for(er, pos + j)
                    for j in range(k_steps)
                )
                if not ok and prev is not None:
                    self.allocator.flush_offload()
                    return False
                if not ok:
                    # out of memory: evict the youngest request back to waiting
                    # (simple preemption — recompute later)
                    logger.warning("KV OOM: preempting %s", er.request_id)
                    self._preempt(er)
                    rows.remove(row)
            # one batched host-offload gather for every eviction this step,
            # before the step below overwrites the evicted slots
            self.allocator.flush_offload()
            if not rows:
                return None
            live = [er for er, *_ in rows]

            # the table's width: full where the program's kernels walk
            # live pages; where its trace gathered [B, W] pages the LIVE
            # context rounded up a power-of-two ladder, so that a short
            # context doesn't pay max_model_len's gather (one compiled
            # program per rung)
            w = self._table_width(
                live,
                (self.runner, "decode_burst" if k_steps > 1 else "decode"),
                (self.draft, "decode"))   # its mirror (k_steps is 1 then)

            # sampling params and the block table come from the persistent
            # host state (mutated only on membership / block growth); only
            # the genuinely per-pass scalars are rebuilt here
            hs = self._host
            tokens = np.zeros((b, 1), np.int32)
            positions = np.zeros((b, 1), np.int32)
            slot_map = np.full((b, 1), -1, np.int32)
            ctx_lens = np.ones(b, np.int32)
            last_idx = np.zeros(b, np.int32)
            ctrs = np.zeros(b, np.int32)
            commit = np.zeros(b, bool)

            for er, pos, gen, fed in rows:
                i = er.slot
                hs.sync_blocks(er)
                tokens[i, 0] = FED if fed else er.pending_token
                positions[i, 0] = pos
                slot_map[i, 0] = er.block_ids[pos // bs] * bs + pos % bs
                ctx_lens[i] = pos + 1
                ctrs[i] = gen
                commit[i] = True
            # .copy(), not a view: the persistent table mutates across passes
            # while a dispatched program's host→device transfer may still be
            # in flight — the step must capture a stable snapshot
            btab = hs.btab[:, :w].copy()

            # the [B, V] top-k sort only runs when some active request
            # asked for alternatives (ADVICE r2: fixed decode-path cost)
            want_top = any(er.logprobs_n > 0 for er in live)

            self._note_dispatch(live, k_steps, prev)
            read_bytes = (self.device_time.decode_read_bytes(
                k_steps, sum(pos for _, pos, *_ in rows))
                if self.device_time is not None else 0.0)
        with span("sched.decode.dispatch", step=self.passes,
                  rows=len(rows), ahead=int(prev is not None)):
            t_dispatch = time.monotonic()
            if k_steps > 1:
                next_tokens, lps, top_vals, top_ids = self.runner.decode_burst(
                    tokens[:, 0], positions[:, 0], btab,
                    hs.temp, hs.top_k, hs.top_p,
                    min_p=hs.min_p, presence_penalty=hs.pres,
                    frequency_penalty=hs.freq,
                    repetition_penalty=hs.rep, seed_keys=hs.keys, counters=ctrs,
                    commit=commit, want_top=want_top,
                )
            else:
                next_tokens, lps, top_vals, top_ids, *_ = self.runner.step(
                    tokens, positions, btab, slot_map, ctx_lens, last_idx,
                    hs.temp, hs.top_k, hs.top_p,
                    min_p=hs.min_p, presence_penalty=hs.pres,
                    frequency_penalty=hs.freq,
                    repetition_penalty=hs.rep, seed_keys=hs.keys, counters=ctrs,
                    sample_slots=np.arange(b, dtype=np.int32), commit=commit,
                    want_top=want_top,
                    **({} if hs.wtab is None
                       else {"window_tables": hs.wtab[:, :w].copy()}),
                    **({} if prev is None
                       else {"prev_tokens": prev.arrays[0]}),
                )
                if self.draft is not None:
                    # mirror the step on the draft (inert sampling): the
                    # speculative rounds assume the draft cache covers every
                    # position the target has decoded
                    dtemp, dtop_k, dtop_p, dkw = self._inert_sampling(b)
                    self.draft.step(
                        tokens, positions, btab, slot_map, ctx_lens, last_idx,
                        dtemp, dtop_k, dtop_p,
                        sample_slots=np.arange(b, dtype=np.int32),
                        commit=np.zeros(b, bool), want_top=False, **dkw,
                    )
            self._count_decode_rows(
                "decode_burst" if k_steps > 1 else "decode", live, k_steps)
        return self._in_flight(live, [next_tokens, lps, top_vals, top_ids],
                               k_steps, t_dispatch, read_bytes, prev)

    def _note_dispatch(self, live: List[EngineRequest], k_steps: int,
                       prev: Optional[_StepInFlight]) -> None:
        """A decode step's dispatch on the bubble histogram and the
        flight record."""
        # synchronous path: the device has been idle since the previous
        # burst's tokens reached the host (t_ready) — that gap IS the
        # bubble running ahead (or the chain) exists to close; a step
        # dispatched behind one in flight left the device none
        if prev is not None:
            self._bubble_hist.observe(0.0)
        elif self._last_burst_done_t is not None:
            self._bubble_hist.observe(
                time.monotonic() - self._last_burst_done_t
            )
        self._last_burst_done_t = None
        self.flight.record(
            "scheduler.burst_dispatch", k_steps=k_steps, rows=len(live),
            requests=[er.request_id for er in live[:8]],
        )

    def _in_flight(self, live: List[EngineRequest], arrays: list,
                   k_steps: int, t_dispatch: float, read_bytes: float,
                   prev: Optional[_StepInFlight]) -> _StepInFlight:
        """The step just dispatched as ``_decode`` keeps it. The copy to
        the host is asked for here, at the dispatch (``_fetch`` says
        why), whichever pass waits for it."""
        self._inflight = True
        if prev is not None:
            self._ahead_ctr.inc()
        with span("sched.decode.request", step=self.passes):
            return _StepInFlight(
                rows=[(er, er.slot) for er in live], arrays=arrays,
                k_steps=k_steps, t_dispatch=t_dispatch,
                prefetched=_request_transfer(arrays), read_bytes=read_bytes,
                ahead=prev is not None, behind_chunk=self._chunk_unread,
            )

    async def _decode_land(self, loop, step: _StepInFlight) -> None:
        """The host's half of a decode step: wait for its tokens
        (``_fetch``: the frontend's turn first, unless the pass has
        taken it) and walk the rows. A row that has finished since the
        dispatch (it ended at the step before, which was read after
        this one went out; or its client left) has its token dropped:
        never emitted, never counted. Its KV went to a position past
        everything committed, in a block the row still owned at the
        dispatch; its slot's record is overwritten at the next
        admission; and the device runs programs in dispatch order, so a
        later prefill into the freed slot or page lands after it."""
        k_steps = step.k_steps
        (toks, lpn, tv, ti), t_ready = await self._fetch(
            loop, "decode", step.arrays,
            chaos="decode_burst_hang",  # chaos site (see _apply_burst)
            prefetched=step.prefetched)
        with span("sched.decode.emit", step=self.passes,
                  rows=len(step.rows)):
            self._last_burst_done_t = t_ready
            if self.device_time is not None:
                self.device_time.observe(
                    "decode_burst" if k_steps > 1 else "decode", "decode",
                    step.t_dispatch, t_ready, read_bytes=step.read_bytes,
                    tokens=k_steps * len(step.rows),
                )
            self.steps += 1
            if k_steps == 1:
                # [B] → [1, B] so the emit loop below is one shape
                toks, lpn = toks[None], lpn[None]
                tv, ti = tv[None], ti[None]
            dropped = sum(er.finish is not None for er, _ in step.rows)
            if step.ahead and dropped:
                self._ahead_discarded_ctr.inc(dropped)

            # emit in step order; a request that finishes at step j has its
            # trailing burst tokens (sampled ahead on device) discarded —
            # their KV went into this request's own still-unregistered or
            # over-allocated blocks, which are freed with the request, so
            # nothing another sequence can observe was touched
            for j in range(k_steps):
                for er, slot in step.rows:
                    if er.finish is not None:
                        continue
                    token = int(toks[j, slot])
                    self._advance_row(er, token)
                    self._guided_after_token(er)
                    self._emit(
                        er, token,
                        float(lpn[j, slot]) if er.want_logprobs else None,
                        self._top_row(er, tv[j], ti[j], slot),
                    )
                    if er.finish is not None:
                        self._finish(er, er.finish, emit=False)

    # ---------- the block pass (a family whose decode unit is a block) ----------

    def _open_block(self, er: EngineRequest, opening: List[int]) -> None:
        """The row's next block: ``opening`` (the prompt's tail, already
        unmasked; nothing after the first block) and masks."""
        unit = self.unit
        er.block = list(opening) + [unit.mask_id] * (unit.length - len(opening))
        er.block_first = len(opening)
        er.block_pass = 0
        er.block_lps = [None] * unit.length
        er.block_tops = [None] * unit.length
        er.block_passes = [-1] * unit.length

    def _end_block_prefill(self, er: EngineRequest) -> None:
        """A block family's prompt is in the cache up to its last whole
        block: the row decodes from the next pass on (no token was
        sampled; the first ones come when the first block is whole)."""
        er.ctx.add_stage("prefill")
        if er.max_new == 0:
            er.finish = FinishReason.LENGTH
            self._finish(er, er.finish)

    def _block_whole(self, er: EngineRequest) -> None:
        """A denoise pass left no mask in ``er.block``: its generated
        tokens pass the shared commit in order (counts, the stop-string
        ring, the finish checks) and leave in one ``EngineOutput``; a
        finish inside the block drops the rest of it. A row that goes on
        holds the block as ``unkept`` and opens the next behind it: the
        pass that first denoises that one writes this one's final keys
        and values (``_decode_block``). A row that finished has no
        further pass, and its last block is never kept."""
        unit = self.unit
        self._blocks_completed.inc()
        self._block_passes_hist.observe(float(er.block_pass))
        sent = []
        for o in range(er.block_first, unit.length):
            self._note_token(er, er.block[o])
            sent.append(o)
            if er.finish is not None:
                break
        if (er.finish is None and er.context_len + 2 * unit.length
                > self.config.max_model_len):
            er.finish = FinishReason.LENGTH   # no room for one more block
        self._block_tokens.inc(len(sent))
        self.flight.record(
            "scheduler.block_commit", request_id=er.request_id,
            start=er.context_len,
            passes=[er.block_passes[o] for o in sent],
        )
        if sent:
            self._emit_tokens(
                er, [er.block[o] for o in sent],
                [er.block_lps[o] for o in sent] if er.want_logprobs else None,
                [er.block_tops[o] for o in sent])
        if er.finish is not None:
            self._finish(er, er.finish, emit=not sent)
        else:
            er.unkept = er.block
            self._open_block(er, [])

    def _block_next(self, er: EngineRequest, on: bool):
        """What ``er``'s next block pass carries: (the kept context's
        end, the ids it writes (``L`` or ``2L``), the masks in the block
        it denoises, that block's pass number). ``on``: a pass of the row
        is in flight and the row is taken as that pass leaves it, which
        the host knows without reading it under the rules that unmask
        the quota it gave and no more (``_ahead_block_reason``): the
        block it denoised is ``FED`` (the program reads it off that
        pass's ``new_ids``), to be denoised again where masks are left,
        else whole, carried as ``unkept`` is with the next block's masks
        behind it. None where the host knows that pass to be the row's
        last: its block comes out whole and ``_block_whole`` ends the row
        there by the count or by the model's length (a stop token or
        string it cannot know)."""
        unit, length = self.unit, self.unit.length
        masked = er.block.count(unit.mask_id)
        if not on:
            return (er.context_len, er.unkept + er.block, masked,
                    er.block_pass)
        n = er.context_len + len(er.unkept)
        left = masked - self._block_quota(masked, er.block_pass)
        if left:
            return n, [FED] * length, left, er.block_pass + 1
        if (er.generated + length - er.block_first >= er.fin_max_new
                or n + 2 * length > self.config.max_model_len):
            return None
        return n, [FED] * length + [unit.mask_id] * length, length, 0

    def _block_quota(self, masked: int, block_pass: int) -> int:
        """Positions pass ``block_pass`` of a block with ``masked`` masks
        unmasks at least; a pass past the schedule's end (the dynamic
        rule never needs one) takes what is left."""
        quotas = self._quotas
        return min(masked, quotas[block_pass]
                   if block_pass < len(quotas) else masked)

    def _block_dispatch(self, active: List[EngineRequest], k_steps: int,
                        prev: Optional[_StepInFlight]):
        """Build and dispatch one block pass over every decoding row
        (``jit_decode_block``, ``2L`` consecutive positions a row from
        its kept context's end), the block family's ``_decode_dispatch``
        and with its returns. Every row's pass is a denoise pass of its
        block in flight: it unmasks the pass's quota of positions, and
        where that leaves no mask the block is whole and its tokens
        leave (``_block_whole``).
        A row that holds a whole block not yet kept carries it in the
        first half of its positions and the block in flight in the
        second, all with slots: the pass writes the whole block's final
        keys and values, and only when it is read does the block pass
        ``_commit_kv`` (kept; a page it completes is registered). Any
        other row (a request's first block, a later pass of a block) has
        its block in the first half and a dead second half: no slot, so
        no write and no expert row, and nothing read of it. Rows of one
        pass are at different phases. Pages are taken for what the pass
        writes; a block never straddles a page, two blocks may lie on
        two. ``prev``: the pass in flight this one is built ahead of; a
        row of it is built as ``_block_next`` says."""
        cfg, unit = self.config, self.unit
        b, bs, length = cfg.max_batch_size, cfg.kv_block_size, unit.length

        inflight = {id(er) for er, _ in prev.rows} if prev is not None else ()
        nexts = []         # (row, what _block_next says of it)
        for er in active:
            nxt = self._block_next(er, id(er) in inflight)
            if nxt is not None:        # else the pass in flight ends it
                nexts.append((er, *nxt))
        with span("sched.decode.build", step=self.passes, rows=len(active),
                  denoise_rows=len(nexts), commit_rows=0,
                  fold_rows=sum(len(ids) > length for _, _, ids, *_ in nexts)):
            rows = []
            for row in nexts:
                er, n, ids, *_ = row
                if self._ensure_block_for(er, n + len(ids) - 1):
                    rows.append(row)
                elif prev is not None:
                    self.allocator.flush_offload()
                    return False
                else:
                    # out of memory: back to waiting, between blocks (the
                    # block in flight is dropped, none of it was emitted;
                    # a whole block not yet kept goes with what was sent)
                    logger.warning("KV OOM: preempting %s", er.request_id)
                    self._preempt(er)
            self.allocator.flush_offload()
            if not rows:
                return None
            live = [er for er, *_ in rows]
            w = self._table_width(live, (self.runner, "decode_block"))

            hs = self._host
            tokens = np.zeros((b, 2 * length), np.int32)
            positions = np.zeros((b, 2 * length), np.int32)
            slot_map = np.full((b, 2 * length), -1, np.int32)
            ctx_lens = np.ones(b, np.int32)
            quota = np.zeros(b, np.int32)
            passes = np.zeros(b, np.int32)
            offs = np.arange(2 * length)
            for er, n, ids, masked, block_pass in rows:
                i = er.slot
                hs.sync_blocks(er)
                tokens[i, :len(ids)] = ids     # what the pass writes
                positions[i] = n + offs
                for at in range(0, len(ids), length):
                    p = n + at
                    slot_map[i, at:at + length] = (
                        er.block_ids[p // bs] * bs + p % bs + offs[:length])
                ctx_lens[i] = n + len(ids)
                quota[i] = self._block_quota(masked, block_pass)
                passes[i] = block_pass
            btab = hs.btab[:, :w].copy()
            want_top = any(er.logprobs_n > 0 for er in live)
            self._note_dispatch(live, 1, prev)
            read_bytes = (self.device_time.decode_read_bytes(
                1, sum(n for _, n, *_ in rows))
                if self.device_time is not None else 0.0)
        with span("sched.decode.dispatch", step=self.passes,
                  rows=len(rows), ahead=int(prev is not None)):
            t_dispatch = time.monotonic()
            outs = self.runner.decode_block(
                tokens, positions, btab, slot_map, ctx_lens, quota,
                hs.temp, hs.top_k, hs.top_p, min_p=hs.min_p,
                seed_keys=hs.keys, counters=passes, want_top=want_top,
                **({} if prev is None else {"prev_ids": prev.arrays[0]}),
            )
            self._count_decode_rows("decode_block", live)
        # new_ids, lps, top_vals, top_ids, left
        return self._in_flight(live, list(outs), 1, t_dispatch, read_bytes,
                               prev)

    async def _block_land(self, loop, step: _StepInFlight) -> None:
        """The host's half of a block pass, the block family's
        ``_decode_land``: wait for the pass's ids and walk the rows. A
        row's state is the one its pass was built on (the pass before it
        was applied first), so what it held as ``unkept`` is what the
        pass wrote for good. A row that has finished since the dispatch
        has its row of the pass dropped, as ``_decode_land`` says:
        nothing of it is kept, emitted or counted, and what it wrote
        lies past everything committed, in pages the row owned at the
        dispatch."""
        unit, length = self.unit, self.unit.length
        (new_ids, lpn, tv, ti, left), t_ready = await self._fetch(
            loop, "decode", step.arrays, chaos="decode_burst_hang",
            prefetched=step.prefetched)
        with span("sched.decode.emit", step=self.passes,
                  rows=len(step.rows)):
            self._last_burst_done_t = t_ready
            rows = [(er, i) for er, i in step.rows if er.finish is None]
            if step.ahead and len(rows) < len(step.rows):
                self._ahead_discarded_ctr.inc(len(step.rows) - len(rows))
            if self.device_time is not None:
                self.device_time.observe(
                    "decode_block", "decode", step.t_dispatch, t_ready,
                    read_bytes=step.read_bytes,
                    tokens=length * sum(not left[i] for _, i in rows),
                )
            self.steps += 1
            self._block_row_passes.inc(len(rows), kind="denoise")
            for er, i in rows:
                if er.unkept:
                    # the pass wrote the whole block's final keys and
                    # values: kept, here and only here
                    for token in er.unkept:
                        self._commit_kv(er, token)
                    er.unkept = []
                    self._block_keeps_folded.inc()
                for o in range(length):
                    token = int(new_ids[i, o])
                    if er.block[o] != unit.mask_id or token == unit.mask_id:
                        continue
                    er.block[o] = token
                    er.block_lps[o] = float(lpn[i, o])
                    er.block_tops[o] = self._top_row(er, tv[i], ti[i], o)
                    er.block_passes[o] = er.block_pass
                er.block_pass += 1
                if unit.mask_id not in er.block:
                    self._block_whole(er)

    def _preempt(self, er: EngineRequest) -> None:
        """Return a request to the waiting queue, releasing its blocks.

        Tokens already emitted to the client are PRESERVED: on re-admission
        the request re-prefills ``prompt + resume_tokens`` and the stream
        continues where it stopped (never restarts or diverges)."""
        self._preemptions.inc()
        er.preemptions += 1
        self.flight.record(
            "scheduler.preemption", request_id=er.request_id,
            trace_id=er.ctx.trace_id, generated=er.generated,
            blocks_freed=len(er.block_ids),
        )
        er.ctx.add_stage("preempted")
        if er.slot >= 0:
            self.slots[er.slot] = None
            er.slot = -1
        self.allocator.free_blocks(er.block_ids)
        er.block_ids = []
        self._drop_window(er)
        # seq mirrors tokens whose KV was written; everything past the
        # original prompt is generated output, plus the not-yet-written
        # pending token — all already emitted to the client
        gen = er.seq.token_ids[len(er.prompt):] if er.seq is not None else []
        if er.pending_token >= 0:
            gen = gen + [er.pending_token]
        if self.unit is not None and er.seq is not None:
            # a block family is preempted between blocks: the block in
            # flight is dropped (none of it was emitted) but for the
            # tokens that opened it, which were the prompt's or an earlier
            # admission's; a whole block not yet kept was sent, and goes
            # with the kept ones
            gen = (er.seq.token_ids + er.unkept
                   + er.block[:er.block_first])[len(er.prompt):]
            er.unkept, er.block = [], []
        er.resume_tokens = list(gen)
        er.context_len = 0
        er.num_cached = 0
        er.pending_token = -1
        er.seq = None
        er.registered_blocks = 0
        er.prefill_tokens = []
        er.prefill_pos = 0
        # re-prefill recomputes prompt logprobs from scratch
        er.prompt_lp_parts = []
        # er.generated keeps its value: max_tokens accounting + PRNG
        # fold-in counters continue, not restart
        self.waiting.appendleft(er)

    def _check_finish(self, er: EngineRequest, token: int) -> Optional[FinishReason]:
        """Per-token finish verdict off the admission-time classification
        (EngineRequest.classify_finish): set membership against the
        precomputed frozensets instead of re-deriving eos/stop lists
        from the request every token — this runs for EVERY emitted token
        of every request (incl. the async drain's hot path). Must stay
        the exact host mirror of sampling.device_finish_mask (+ the
        suffix-hash stop approximation: the exact token-suffix compare
        below is what the device's hash candidate approximates, and it
        runs on BOTH paths so chain and sync streams stay identical)."""
        if er.generated >= er.fin_min_new:
            # eos/stops suppressed below min_tokens; ignore_eos already
            # emptied fin_eos at classification
            if token in er.fin_eos:
                return FinishReason.EOS
            if token in er.fin_stop:
                return FinishReason.STOP
            if er.fin_stop_seqs:
                # canonical-tokenization stop strings: the ring tail
                # ends with this token (callers note it first); only
                # generated output may match (gen >= L). Non-canonical
                # tokenizations remain the backend jail's concern.
                tail = tuple(er.ring_tail)
                for seq in er.fin_stop_seqs:
                    length = len(seq)
                    if (er.generated >= length
                            and len(tail) >= length
                            and tail[-length:] == seq):
                        return FinishReason.STOP
        if er.generated >= er.fin_max_new:
            return FinishReason.LENGTH
        if er.context_len + 1 >= self.config.max_model_len:
            return FinishReason.LENGTH
        return None
