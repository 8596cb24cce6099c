"""Jitted prefill/decode step programs + mesh sharding.

Two compiled programs per prefill bucket plus one decode program, all with
static shapes (SURVEY.md §7 hard-part #1: dynamic batch membership without
recompiles). The KV cache is donated through every call so XLA updates it
in place in HBM.

Sharding (TPU-first): mesh axes ("dp", "tp"). Attention heads, KV heads,
MLP intermediate, and the vocab dim of lm_head shard over "tp" (Megatron
layout — XLA inserts the all-reduces after wo / w_down); the batch dim of
activations shards over "dp". Single-device collapses to a trivial mesh.
"""

from __future__ import annotations

import contextlib
import functools
import json
import logging
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import models
from ..models import llama, quant
from ..ops.attention import (pad_minor, row_list_traced,
                             table_width_traced)
from ..telemetry.flight import CompileTracker, StartupTimeline
from ..telemetry.registry import Counter
from .config import EngineConfig
from .device import check_serving_device
from . import step_inputs
from .sampling import (SamplingParams, block_select, over_all_rows,
                       over_live_rows, sample, sample_block_positions,
                       short_search, tile_rows, top_k_width,
                       top_logprobs_for, walks_live_rows)

logger = logging.getLogger(__name__)


def build_mesh(dp: int, tp: int, devices=None, ep: int = 1, pp: int = 1,
               sp: int = 1) -> Mesh:
    """(pp, dp, sp, ep, tp) mesh; tp innermost so its collectives ride
    the fastest ICI, pp outermost so stage hops cross the slowest links
    (stages communicate once per microbatch tick, tp all-reduces twice
    per layer), sp between dp and ep — the ring rotation's per-hop
    payload is one K/V shard, heavier than an ep dispatch but far
    lighter than tp's twice-per-layer all-reduces. ep=1/pp=1/sp=1 keep
    those axes present (specs may name them) but trivial.

    Device pick: LOCAL devices when they suffice — in a multi-process
    world (disagg workers sharing a jax.distributed group for the ICI
    transfer plane) each engine runs its own independent program and must
    not claim the peer's devices. A mesh larger than the local count is
    the single-engine multi-host case and takes the global list.
    """
    n = pp * dp * sp * ep * tp
    if devices is None:
        local = jax.local_devices()
        devices = local if n <= len(local) else jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh {pp}x{dp}x{sp}x{ep}x{tp} needs {n} devices, "
            f"have {len(devices)}"
        )
    arr = np.asarray(devices[:n]).reshape(pp, dp, sp, ep, tp)
    return Mesh(arr, ("pp", "dp", "sp", "ep", "tp"))


def param_specs(params) -> Dict:
    """Llama param specs (kept for back-compat; models now own their specs)."""
    return llama.param_specs(params)


CACHE_SPEC = P(None, None, None, "tp", None)  # [L, N, bs, KVH, D] — KV heads over tp


class _DeviceFedCounter(Counter):
    """A counter brought up to date from a device accumulator when it is
    rendered (``refresh`` may feed sibling counters too)."""

    def __init__(self, name: str, help_: str, refresh):
        super().__init__(name, help_)
        self._refresh = refresh

    def render(self):
        self._refresh()
        return super().render()


# rows a tile where the program being traced walks its sampling tail in
# tiles of rows, else 0 (``_sample_and_logprobs`` sets it,
# ``ModelRunner._track`` clears it before a dispatch and reads it after)
_tiles_traced = 0
# whether the program being traced may find its cutoffs by the short search
# (``sampling.short_search`` of its head's logits; set and read likewise)
_short_traced = False


def _record_search(logits, mesh=None) -> None:
    """Stamp how the program being traced, whose head made ``logits``,
    may search for its cutoffs. Trace-time, like
    ops/attention.record_row_list."""
    global _short_traced
    _short_traced = short_search(logits.dtype, mesh)


def _sample_and_logprobs(cfg, mesh, last_logits, samp, counts, seen, bias,
                         sample_slots, commit, want_top, extra_bias=None,
                         live=None):
    """The per-token tail (``_tail_ops``) as a decode or prefill program
    traces it, under the scope ``sampling``. Where it walks tiles of rows
    (``sampling.tile_rows``) it is traced once for all the programs of a
    process that hand it the same shapes (``_tail``; a decode program a
    block-table width: the tail is the same in each, and its two walks
    are a quarter of a second of every one's warm start); elsewhere its
    operations lie in the program as they always did."""
    global _tiles_traced
    _record_search(last_logits, mesh)
    tile = tile_rows(last_logits, mesh, live)
    args = (mesh, top_k_width(cfg.vocab_size), tile, last_logits, samp,
            counts, seen, bias, sample_slots, commit, want_top, extra_bias,
            live if tile else None)
    with jax.named_scope("sampling"):
        if not tile:
            return _tail_ops(*args)
        _tiles_traced = tile
        return _tail(*args)


def _tail_ops(mesh, kw, tile, last_logits, samp, counts, seen, bias,
              sample_slots, commit, want_top, extra_bias, live):
    """The per-token tail shared by the single step and every scan
    iteration of the fused burst: penalty-aware sampling, the sampled
    token's logprob, gated top-K alternatives, and the committed-count
    update. One implementation, so the burst's bit-identity guarantee
    can't drift from the single-step path.

    ``extra_bias`` is an additive [B, V] term computed in-program (the
    chained burst's device-guided mask); the sync path expresses the
    same mask through the persistent ``bias`` buffer instead, so adding
    it here keeps the two paths' logits — and logprobs — bit-equal.

    ``live`` ([B] bool, given with ``tile`` > 0) says which rows' token
    is read. The ``[rows, V]`` work then runs ``tile`` rows at a time:
    over the list of live rows while that is a tile shorter than the
    batch (``sampling.over_live_rows``), over all rows as they lie past
    that (``over_all_rows``); a row that is not live comes back as token
    0 with log-probability 0 either way. Every row keeps its own key and
    counter, no reduction crosses rows and both walks run the same
    ``[tile, V]`` operations, so a live row's token and log-probability
    depend neither on its batchmates nor on how many they are."""
    b = last_logits.shape[0]

    def log_probs(logits, row_bias):
        return jax.nn.log_softmax(
            (logits + row_bias).astype(jnp.float32), axis=-1
        )

    def tail(logits, samp, row_counts, row_seen, row_bias, extra):
        if extra is not None:
            row_bias = row_bias + extra
        tokens = sample(logits, samp, row_counts, row_seen, bias=row_bias,
                        mesh=mesh)
        logp = log_probs(logits, row_bias)
        return tokens, jnp.take_along_axis(
            logp, tokens[:, None], axis=-1)[:, 0], logp

    def of_rows(rows=None):
        """The tail's inputs, of every row or of ``rows``; the penalty
        and bias rows from the slot buffers directly."""
        mine = (lambda x: x) if rows is None else (lambda x: x[rows])
        slots = mine(sample_slots)
        return (mine(last_logits), jax.tree_util.tree_map(mine, samp),
                counts[slots], seen[slots], bias[slots],
                None if extra_bias is None else mine(extra_bias))

    if tile:
        drawn = lambda mine: tail(*mine)[:2]
        next_tokens, lps = jax.lax.cond(
            walks_live_rows(live.sum(), b, tile),
            lambda: over_live_rows(live, tile, of_rows, drawn),
            lambda: over_all_rows(live, tile, of_rows(), drawn),
        )

        def all_logp():
            # behind its gate, over every row at once: no walk's to share
            row_bias = bias[sample_slots]
            if extra_bias is not None:
                row_bias = row_bias + extra_bias
            return log_probs(last_logits, row_bias)
    else:
        next_tokens, lps, logp = tail(*of_rows())
        all_logp = lambda: logp
    # top-K alternatives only when some active request asked (OpenAI
    # top_logprobs): the [B, V] top_k sort is fixed hot-path cost
    # otherwise. lax.cond keeps one compiled program either way.
    top_vals, top_ids = jax.lax.cond(
        want_top,
        lambda: top_logprobs_for(last_logits, all_logp()),
        lambda: (jnp.zeros((b, kw), jnp.float32),
                 jnp.zeros((b, kw), jnp.int32)),
    )
    # count the sampled token as generated for its slot — but only for
    # rows whose sample the scheduler will keep (``commit``)
    counts = counts.at[sample_slots, next_tokens].add(
        commit.astype(jnp.int32)
    )
    return next_tokens, lps, top_vals, top_ids, counts


_tail = jax.jit(_tail_ops, static_argnums=(0, 1, 2))


def _ngram_props(ring: jax.Array, match: int, k: int) -> jax.Array:
    """In-program prompt-lookup proposal from the carry's trailing-token
    ring: find the latest earlier occurrence of the trailing ``match``-
    gram whose ``k``-token continuation is fully inside the ring and
    return it ([B, k], -1 where nothing matches). The device analog of
    scheduler.ngram_propose bounded to the ring window — proposals only
    affect acceptance length, never stream content (the verify emits the
    target's own greedy tokens), so the narrower window is free."""
    b, w = ring.shape
    tail = ring[:, w - match:]                       # [B, m]
    n_starts = w - match                             # excludes the tail itself
    s_idx = jnp.arange(n_starts)
    win_idx = s_idx[:, None] + jnp.arange(match)[None, :]   # [S0, m]
    wins = ring[:, win_idx]                          # [B, S0, m]
    hit = (wins == tail[:, None, :]).all(-1) & (wins >= 0).all(-1)
    full = (s_idx + match + k) <= w                  # continuation in-ring
    cand = hit & full[None, :]
    s_best = jnp.max(jnp.where(cand, s_idx[None, :], -1), axis=1)  # latest
    has = s_best >= 0
    cont_idx = jnp.clip(s_best, 0)[:, None] + match + jnp.arange(k)[None, :]
    props = jnp.take_along_axis(ring, cont_idx, axis=1)
    return jnp.where(has[:, None] & (props >= 0), props, -1)


class ModelRunner:
    """Owns params + cache on device and the compiled step programs."""

    # ``step(prev_tokens=)``: the decode program reads a row's token off
    # the step before's output on the device, which is what lets the
    # scheduler dispatch a step before it has read that one
    feeds_tokens = True

    def __init__(
        self,
        config: EngineConfig,
        params=None,
        mesh: Optional[Mesh] = None,
        model_dir: Optional[str] = None,
    ):
        check_serving_device()
        # XLA compile observability: every compiled-program dispatch site
        # below runs through compiles.track(program, shape-bucket key) —
        # the first dispatch of a new key is the compile, and a compile
        # after mark_serving_started() is a "late" compile (the
        # recompile-storm signal; see telemetry/flight.py). The scheduler
        # / prefill worker attach compiles.registry into the engine's
        # scrape and flip the serving flag when they start. Made first:
        # what weight init's helper jits compile is counted there too
        # (program="untracked").
        self.compiles = CompileTracker()
        # the split of set-up, one mark where each phase's work ends
        # (telemetry/flight.StartupTimeline; dynamo_engine_startup_seconds):
        # the card and the tokenizer are done when this is entered, then
        # reaching the device(s), weights, cache pool, the programs'
        # Python and warm-up, each timed to the moment its arrays are on
        # the device. ``startup_s[phase]`` is the timeline's ``seconds``.
        self.startup = StartupTimeline(self.compiles.registry,
                                       self.compiles.records)
        self.startup_s = self.startup.seconds
        self.startup.mark("model_card")
        self.config = config
        cfg = config.model
        self.family = models.family(cfg)
        self.arch = self.family.module
        # what the family keeps for a sequence besides one kind of page
        # (records by slot, a second pool of pages: it rides in the cache
        # pytree) and the paths refused for it, its module's declaration
        self.keeps: models.SequenceState = getattr(
            self.arch, "SEQUENCE_STATE", models.PAGES_ONLY)
        # the family's decode unit where it is a block of positions and
        # not one token (models.BlockUnit): the block pass is then the one
        # decode program, and what assumes a token a row a pass is refused
        self.unit: Optional[models.BlockUnit] = getattr(
            self.arch, "decode_unit", lambda cfg: None)(cfg)
        if self.unit is not None:
            from ..ops.pallas_decode import VERIFY_MAX_S

            # a block pass is one call of the verify kernel over two
            # blocks; a block never straddles a page; a prefill chunk ends
            # at a block's edge
            if not 1 < self.unit.length <= VERIFY_MAX_S // 2:
                raise ValueError(
                    f"block length {self.unit.length}: a block pass is one "
                    f"verify-kernel call over two blocks "
                    f"(S = 2 x block length <= {VERIFY_MAX_S})")
            for what, n in (("kv_block_size", config.kv_block_size),
                            *(("a prefill bucket", b)
                              for b in config.prefill_buckets)):
                if n % self.unit.length:
                    raise ValueError(
                        f"{what} of {n} is not whole blocks of "
                        f"{self.unit.length}")
        for path, on in (
            ("spec_ngram_tokens", config.spec_ngram_tokens > 0),
            ("spec_draft_model", bool(config.spec_draft_model)),
            ("sp_size", config.sp_size > 1),
            ("pp_size", config.pp_size > 1),
            ("tp_size", config.tp_size > 1),
            ("ep_size", config.ep_size > 1),
            ("host_kv_blocks", config.host_kv_blocks > 0),
            ("prefix_pull", config.prefix_pull),
            ("multi_step_decode", config.multi_step_decode > 1),
            ("decode_pipeline_depth", config.chain_enabled),
        ):
            if on:
                self.refuse_without_state(path)
        self.dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        if config.kv_cache_dtype not in ("auto", "fp8"):
            raise ValueError(
                f"unknown kv_cache_dtype {config.kv_cache_dtype!r} "
                "(auto | fp8)"
            )
        # fp8 KV covers MLA too: the "latent too sensitive" intuition
        # did not survive measurement — teacher-forced e4m3 round-trip
        # noise on the full latent+rope cache matches the GQA fp8 path
        # (rel logit err 0.043 vs 0.042, argmax flip 0.10 vs 0.10;
        # examples/llm/benchmarks/results/fp8_mla_accuracy.json), and
        # quantizing only the rope half halves the noise again if a
        # future accuracy budget wants it.
        self.kv_dtype = (
            jnp.float8_e4m3fn if config.kv_cache_dtype == "fp8"
            else self.dtype
        )
        self.mesh = mesh or build_mesh(
            config.dp_size, config.tp_size, ep=config.ep_size,
            pp=config.pp_size, sp=config.sp_size,
        )
        # mixed dense+MoE MLA trunk under pp: the dense prefix stays
        # replicated (params, cache, and compute) while the MoE trunk
        # stages — parallel/pipeline.py's has_prefix path
        self._pp_prefix_layers = (
            cfg.first_k_dense_replace
            if (config.pp_size > 1 and cfg.kv_lora_rank > 0
                and cfg.num_experts > 0)
            else 0
        )
        if config.pp_size > 1:
            if not self.family.staged:
                raise NotImplementedError(
                    "pipeline parallelism does not stage the "
                    f"{self.family.name} family (models.FAMILIES)")
            getattr(self.arch, "refuse_staged", lambda config: None)(config)
            # only the STAGED trunk must tile into stages — a mixed MLA
            # trunk's dense prefix is replicated, not staged (real V3:
            # 61 layers = 3 dense + 58 staged, pp2-able)
            staged_layers = cfg.num_layers - self._pp_prefix_layers
            if staged_layers % config.pp_size:
                raise ValueError(
                    f"{staged_layers} staged layers not divisible by "
                    f"pp {config.pp_size}"
                )

        if cfg.kv_lora_rank == 0 and cfg.num_kv_heads % config.tp_size != 0:
            # (MLA caches a per-token latent, no KV head dim to shard)
            raise ValueError(
                f"num_kv_heads {cfg.num_kv_heads} not divisible by tp {config.tp_size}"
            )
        if cfg.num_heads % config.tp_size != 0:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by tp {config.tp_size}"
            )
        if cfg.num_experts and cfg.num_experts % config.ep_size != 0:
            raise ValueError(
                f"num_experts {cfg.num_experts} not divisible by ep {config.ep_size}"
            )
        # config-only quantization checks, BEFORE any checkpoint I/O: a
        # 70B load must not stream for minutes just to hit a config error
        if cfg.quantization and cfg.quantization != "int8":
            raise ValueError(
                f"unknown quantization {cfg.quantization!r} (only int8)"
            )

        # the mesh and the checks of the configuration: the weights'
        # phase starts here
        self.startup.mark("device_init")
        if params is None:
            if model_dir is not None:
                from ..models.loader import has_checkpoint, load_checkpoint_params

                if has_checkpoint(model_dir):
                    # raises for architectures without a loader — never
                    # silently serve random weights against a checkpoint
                    params = load_checkpoint_params(
                        model_dir, cfg, self.arch, self.dtype
                    )
                elif config.allow_random_weights:
                    logger.warning("no checkpoint in %s — random init", model_dir)
                else:
                    raise FileNotFoundError(
                        f"no *.safetensors under {model_dir}; the engine will "
                        "not silently serve random weights — provide a "
                        "safetensors checkpoint or set allow_random_weights"
                    )
            if params is None:
                # jitted onto the family's own shardings so each device
                # draws only its shard: an eager init builds every
                # full-size array on the default device first (the 8B
                # shape's w_gate alone is 7.5 GB in f32 — chip 0 OOMs
                # before tp=4 ever spreads it)
                init = functools.partial(
                    self.arch.init_params, cfg, dtype=self.dtype)
                key = jax.random.PRNGKey(config.seed)
                params = jax.jit(init, out_shardings=jax.tree.map(
                    lambda sp: NamedSharding(self.mesh, sp),
                    self.arch.param_specs(jax.eval_shape(init, key)),
                    is_leaf=lambda x: isinstance(x, P),
                ))(key)

        if cfg.quantization:
            params = quant.quantize_params(params)

        if config.pp_size > 1:
            # stage the stacked layers/cache for the collective GPipe
            # schedule: [L, ...] → [P, L/P, ...] sharded on the stage axis
            from ..parallel import pipeline as pp_mod

            params = pp_mod.stage_params(params, config.pp_size)
            # pp_mod.param_specs mirrors QuantizedWeight leaves itself (the
            # same tree feeds pipeline_forward's shard_map in_specs); the
            # family's own specs carry ep for MoE expert stacks
            pspecs = pp_mod.param_specs(
                params, tp=config.tp_size > 1, arch=self.arch
            )
            cache_spec = (
                pp_mod.CACHE_SPEC_TP if config.tp_size > 1
                else pp_mod.CACHE_SPEC
            )
            if self._pp_prefix_layers:
                # replicated prefix slab + staged trunk slab per side
                cache_spec = {"pre": P(), "stg": cache_spec}
        else:
            pspecs = self.arch.param_specs(params)
            if cfg.quantization:
                pspecs = quant.mirror_specs(params, pspecs)
            cache_spec = getattr(self.arch, "CACHE_SPEC", CACHE_SPEC)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)), params, pspecs
        )
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )

        self.cache_sharding = jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), cache_spec,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.state_sharding = NamedSharding(self.mesh, P("dp", None))
        jax.block_until_ready(self.params)
        self.startup.mark("weights")
        self._init_device_state()
        jax.block_until_ready((self.kv_cache, self.sample_state))
        self.startup.mark("kv_cache")

        # attention-route observability: the dispatch seams in
        # ops/attention.py / parallel/sequence.py record which kernel
        # served each trace; the tracked dispatch supplies the program
        # label, and the singleton counter renders in this runner's
        # compile registry (attached to the engine scrape)
        from ..ops import attention as _attn_ops

        self.compiles.dispatch_cm = _attn_ops.route_program
        # the decode programs whose trace took a kernel with a list of
        # live rows (``_track``): only for these does the scheduler
        # count a pad row as skipped
        self.row_list_programs: set = set()
        # and those whose trace walks the sampling tail in tiles of rows,
        # with the rows a tile: the scheduler counts whole tiles for them
        self.sampling_tile_programs: dict = {}
        # and those whose head hands the tail bfloat16 logits on one
        # device: a tile of untouched rows finds its cutoffs in half the
        # passes (``sampling.short_search``), which the scheduler counts
        self.sampling_short_programs: set = set()
        # the decode-shaped programs whose trace did work in proportion
        # to the block table's width (ops/attention.record_table_width):
        # these alone exist at the narrower rungs of
        # ``EngineConfig.kv_width_buckets`` (``table_width``)
        self.width_programs: set = set()
        # what warm-up dispatched each of them at, and why (its last log)
        self.warmed_widths: dict = {}
        if (_attn_ops.ATTENTION_ROUTE_COUNTER.name
                not in self.compiles.registry.names()):
            self.compiles.registry.register(
                _attn_ops.ATTENTION_ROUTE_COUNTER)

        # live device-time + roofline accounting (telemetry/device_time.py):
        # the byte model: per decode step the device streams every param
        # leaf once plus each live row's KV context.
        # kv_bytes_per_token is EXACT for any cache layout (GQA, MLA
        # latent, fp8, pp-staged): total cache bytes over total token
        # capacity. The scheduler feeds observations at its existing
        # reconciliation seams and attaches device_time.registry.
        from ..telemetry.device_time import DeviceTimeTracker

        def _leaf_bytes(tree) -> float:
            return float(sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
                if hasattr(x, "size") and hasattr(x, "dtype")
            ))

        self.param_bytes = _leaf_bytes(self.params)
        # a side of the cache that is more than a page stack answers for
        # its parts (models/__init__.py, CACHE_SPEC): the pages that grow
        # with the context, and what else it holds (records by slot, or
        # the window kind's pages, bounded by the window)
        pages = tuple(getattr(side, "pages", side) for side in self.kv_cache)
        rest = _leaf_bytes(
            tuple(getattr(side, "rest", ()) for side in self.kv_cache))
        self.kv_bytes_per_token = _leaf_bytes(pages) / max(
            1, config.num_kv_blocks * config.kv_block_size
        )
        if self.keeps.window_pool:
            window = _leaf_bytes(tuple(s.window for s in self.kv_cache))
            logger.info(
                "two kinds of page: full %d pages (%.3f GB), window %d "
                "pages (%.3f GB; %d a decoding row, %d a row in prefill)",
                config.num_kv_blocks, _leaf_bytes(pages) / 1e9,
                config.window_pool_pages(), window / 1e9,
                config.window_pages_a_row(),
                config.window_pages_a_row(config.prefill_chunk_tokens()))
            rest -= window      # (what is left is by slot: models/dots3.py)
        if self.keeps.slots:
            self.compiles.registry.gauge(
                "dynamo_engine_recurrent_state_bytes",
                "Device bytes of the recurrent state records held by slot "
                "beside the paged cache (all layers, all slots)",
            ).set(rest)
        self.compiles.registry.gauge(
            "dynamo_engine_model_info",
            "1 for the architecture this engine serves: family= the "
            "implementing module of models/, hc_mult= residual streams a "
            "token (1: the plain residual path)",
        ).set(1.0, family=self.family.name, hc_mult=str(cfg.hc_mult))
        self.device_time = DeviceTimeTracker(
            param_bytes=self.param_bytes,
            kv_bytes_per_token=self.kv_bytes_per_token,
            device_kind=self.mesh.devices.flat[0].device_kind,
        )

        self._init_moe_counters()
        self._init_family_counters()
        self._build_step()
        self._build_block_step()
        self._build_burst()
        self._build_spec_burst()
        self._build_sp_prefill()
        self._build_block_ops()
        self._build_sample_row()
        # batched cacheless embedding programs, compiled per (rows,
        # bucket) on first use (the /v1/embeddings workload)
        self._embed_progs: Dict[Tuple[int, int], Any] = {}
        # the programs are built and nothing is compiled
        self.startup.mark("runner")

    def refuse_without_state(self, path: str) -> None:
        """Raise, by name, for a path that would move, share or roll back
        a sequence's pages without what the family keeps beside them
        (``SequenceState.refused``); nothing for a path it does not
        refuse, and so nothing for a family that keeps one kind of page."""
        why = self.keeps.refused.get(path)
        if why is not None:
            raise ValueError(
                f"{path} is refused for the {self.family.name} family, "
                f"which keeps {self.keeps.keeps}: {why}"
            )
        why = None if self.unit is None else self.unit.refused.get(path)
        if why is not None:
            raise ValueError(
                f"{path} is refused for the {self.family.name} family, "
                f"whose decode unit is a block of {self.unit.length} "
                f"positions: {why}"
            )

    # ---------- routed experts' counters ----------

    def _init_moe_counters(self) -> None:
        """``dynamo_moe_{active_experts,expert_slots,routed_rows,
        held_picks}_total`` by phase=decode|prefill, counted on the
        device: the step carries a [2, 4] int32 accumulator (phase x
        (experts held with at least one row, routed rows, those of them
        that fell on an expert held, steps), summed over the MoE layers:
        ``models/mixtral.routing_stats``) the way it
        carries the sampling ``counts``, and /metrics reads it when it
        is rendered — no fetch and no program of its own a step. int32
        wraps; the reader takes differences modulo 2**32. A
        configuration without experts carries nothing, and so does the
        pipelined program and the burst programs (whose steps go
        uncounted)."""
        cfg = self.config.model
        self.moe_counts = None
        # every family models.resolve gives a config with experts has a
        # ``forward_counted`` (mixtral, gptoss, deepseek)
        if cfg.num_experts <= 0 or self.config.pp_size > 1:
            return
        self.moe_counts = jax.device_put(
            np.zeros((2, 4), np.int32), NamedSharding(self.mesh, P()))
        slots_a_step = cfg.num_experts * cfg.num_moe_layers()   # those held
        last = np.zeros((2, 4), np.int64)
        lock = threading.Lock()

        def refresh():
            # the read waits for the step in flight (up to one decode
            # step), which is why /metrics renders off the event loop
            # (http/service.handle_metrics); the registry is rendered
            # from several threads, so one delta is taken at a time
            with lock:
                now = np.asarray(self.moe_counts).astype(np.int64)
                delta = (now - last) & 0xFFFFFFFF
                last[...] = now
                for i, phase in enumerate(("decode", "prefill")):
                    active.inc(float(delta[i, 0]), phase=phase)
                    rows.inc(float(delta[i, 1]), phase=phase)
                    held.inc(float(delta[i, 2]), phase=phase)
                    slots.inc(float(delta[i, 3] * slots_a_step), phase=phase)

        # registered (and so rendered) first: its one read of the device
        # brings all four up to date
        reg = self.compiles.registry
        active = _DeviceFedCounter(
            "dynamo_moe_active_experts_total",
            "Experts held here that had at least one row, summed over MoE "
            "layers and steps, by phase=decode|prefill (counted on the device)",
            refresh)
        reg.register(active)
        slots = reg.counter(
            "dynamo_moe_expert_slots_total",
            "Experts held x MoE layers x steps: what active_experts would "
            "be if every step touched every expert held, by phase")
        rows = reg.counter(
            "dynamo_moe_routed_rows_total",
            "(token, chosen expert) rows of real tokens, summed over MoE "
            "layers and steps, by phase")
        held = reg.counter(
            "dynamo_moe_held_picks_total",
            "Those routed rows whose expert is held here: all of them "
            "unless the engine holds one expert-parallel rank's share "
            "(ModelConfig.experts_of), then that share's, by phase")

    def _init_family_counters(self) -> None:
        """Counters a family keeps in its cache pytree (``STEP_COUNTERS``:
        (name, help) of each, ``step_counts(kv_cache)`` the int32
        accumulators; models/minicpm_sala.py counts what its sparse
        layers kept). The trunk adds to them inside the step, so nothing
        is fetched or dispatched for them; /metrics reads them when it
        is rendered, as the routed experts' counters are read. int32
        wraps; the reader takes differences modulo 2**32."""
        named = getattr(self.arch, "STEP_COUNTERS", None)
        if not named:
            return
        last = np.zeros(len(named), np.int64)
        lock = threading.Lock()

        def refresh():
            with lock:
                try:
                    now = np.asarray(
                        self.arch.step_counts(self.kv_cache)).astype(np.int64)
                except RuntimeError:
                    # the cache is donated to the step being dispatched:
                    # the next rendering reads what that step leaves
                    return
                delta = (now - last) & 0xFFFFFFFF
                last[...] = now
                for counter, d in zip(counters, delta):
                    counter.inc(float(d))

        reg = self.compiles.registry
        (name, help_), rest = named[0], named[1:]
        first = _DeviceFedCounter(name, help_, refresh)
        reg.register(first)
        counters = [first] + [reg.counter(n, h) for n, h in rest]

    # ---------- the unified step program ----------

    @contextlib.contextmanager
    def _track(self, program: str, key: str, shape: Optional[str] = None,
               **stats):
        """``compiles.track`` around one dispatch of a decode program,
        keeping what its trace recorded beside the route: whether the
        attention kernels were handed a list of live rows
        (ops/attention.record_row_list), whether anything read the block
        table at its width (ops/attention.record_table_width; filed
        under ``shape`` where one tracked program has several shapes of
        table), the tile its sampling tail walks and whether its search
        may be the short one (``_sample_and_logprobs``)."""
        global _tiles_traced, _short_traced
        with self.compiles.track(program, key, **stats) as first:
            _tiles_traced, _short_traced = 0, False
            yield first
            if first and row_list_traced():
                self.row_list_programs.add(program)
            if first and table_width_traced():
                self.width_programs.add(shape or program)
            if first and _tiles_traced:
                self.sampling_tile_programs[program] = _tiles_traced
            if first and _short_traced:
                self.sampling_short_programs.add(program)

    def _make_forward(self, counted: bool = False):
        """(trunk, head) closures both compiled programs trace: the trunk
        returns pre-final-norm hidden states, the head applies final norm
        + lm head (+ per-family logit tail) to any [..., D] slice. The
        split lets the step run the head on ONLY the sampled positions —
        the full-S [B, S, V] head is the dominant prefill matmul and pure
        waste for every position nobody reads. ``counted`` (routed
        experts, unstaged): the trunk is the family's
        ``forward_counted`` and returns a third value, the step's routing
        counters (see ``_init_moe_counters``)."""
        cfg = self.config.model
        mesh = self.mesh
        arch = self.arch
        keeps_slots = self.keeps.slots     # (pp_size > 1 is refused for it)
        if self.config.pp_size > 1:
            from ..parallel.pipeline import pipeline_forward

            trunk = functools.partial(
                pipeline_forward, return_hidden=True, arch=arch)
        elif counted:
            trunk = arch.forward_counted
        else:
            trunk = None

        # state_slots: each row's slot, for a trunk that keeps records by
        # slot (the step's; a burst's row i is slot i)
        def forward(params, cache, tokens, positions, bt, slots, ctx,
                    state_slots=None):
            args = (params, cfg, tokens, positions, cache, bt, slots, ctx)
            if trunk is not None:
                # a counted trunk with records by slot is told the slots
                by_slot = {"state_slots": state_slots} if keeps_slots else {}
                return trunk(*args, mesh=mesh, **by_slot)
            return arch.forward(*args, mesh=mesh, return_hidden=True,
                                state_slots=state_slots)

        def head(hidden, params):
            with jax.named_scope("lm_head"):
                return arch.logits_from_hidden(hidden, params, cfg)

        return forward, head

    def _build_step(self):
        cfg = self.config.model
        mesh = self.mesh
        batch_spec = NamedSharding(mesh, P("dp"))
        batch2_spec = NamedSharding(mesh, P("dp", None))
        repl = NamedSharding(mesh, P())
        moe = self.moe_counts is not None
        forward, head = self._make_forward(counted=moe)

        def step(s, params, k_cache, v_cache, counts, seen, bias, packed,
                 *moe_counts, prev_tokens=None):
            # every per-pass input arrives in one array (step_inputs.py)
            (tokens, positions, block_tables, slot_mapping, context_lens,
             last_idx, samp, sample_slots, commit, want_top, targets,
             want_prompt, want_greedy) = step_inputs.unpack(packed, s)
            if prev_tokens is not None:
                # a row whose token the host had not read when it packed
                # this step (step_inputs.FED) takes the one the step
                # before it sampled, which never left the device
                tokens = jnp.where(tokens == step_inputs.FED,
                                   prev_tokens[:, None], tokens)
            hidden, (k_cache, v_cache), *moe_step = forward(
                params, (k_cache, v_cache), tokens, positions,
                block_tables, slot_mapping, context_lens, sample_slots,
            )
            b = tokens.shape[0]
            # the full-S [B, S, V] head exists ONLY inside this gated
            # branch — it serves two consumers that need every position:
            # prompt logprobs (OutputOptions.prompt_logprobs, reference:
            # lib/llm/src/protocols/common.rs:320-341) and the ngram
            # speculative verify's per-position argmax. Everything else
            # samples from the last_idx slice below, so ordinary prefill
            # never pays vocab-width compute for positions nobody reads.
            want_full = jnp.logical_or(want_prompt, want_greedy)

            def full_head(h):
                lg = head(h, params)                      # [B, S, V]
                # the f32 log_softmax + gather serves prompt_logprobs
                # only — a speculative verify (want_greedy) needs just
                # the argmax, so keep the two consumers' costs separate
                plp = jax.lax.cond(
                    want_prompt,
                    lambda l: jnp.take_along_axis(
                        jax.nn.log_softmax(l.astype(jnp.float32), axis=-1),
                        targets[..., None], axis=-1,
                    )[..., 0],
                    lambda l: jnp.zeros(l.shape[:2], jnp.float32),
                    lg,
                )
                ga = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return plp, ga

            prompt_lps, greedy_all = jax.lax.cond(
                want_full,
                full_head,
                lambda h: (jnp.zeros(h.shape[:2], jnp.float32),
                           jnp.zeros(h.shape[:2], jnp.int32)),
                hidden,
            )
            last_logits = head(
                hidden[jnp.arange(b), last_idx], params
            )  # [B, V]
            # a decode step reads the token of a row that has a slot (the
            # mask the trunk's list of live rows is made from, and the
            # scheduler's ``commit`` there); a prefill step's few rows all
            # take the tail
            next_tokens, lps, top_vals, top_ids, counts = _sample_and_logprobs(
                cfg, mesh, last_logits, samp, counts, seen, bias,
                sample_slots, commit, want_top,
                live=slot_mapping[:, 0] >= 0 if s == 1 else None,
            )
            out = (next_tokens, lps, top_vals, top_ids, prompt_lps,
                   greedy_all, k_cache, v_cache, counts, seen, bias)
            if moe_counts:
                # row 0 decode, row 1 prefill: (experts with a row,
                # routed rows, rows held, steps); carried like ``counts``, read
                # when /metrics is rendered
                row = jnp.concatenate(
                    [moe_step[0], jnp.ones((1,), jnp.int32)])
                out += (moe_counts[0].at[int(s > 1)].add(row),)
            return out

        # one body, two names: the profiler's trace and the HLO dump
        # then read jit_decode_step(...) and jit_prefill_step(...)
        # (step() picks by S, as its CompileTracker label does). The
        # packed array's width is F + W + 4·S, so prefill takes S as a
        # static argument; decode's is 1. The decode step alone takes the
        # step before it's ``next_tokens`` (``[B]``, not donated: the
        # scheduler has yet to fetch it), after the packed array
        def decode_step(params, k_cache, v_cache, counts, seen, bias, packed,
                        prev_tokens, *moe_counts):
            return step(1, params, k_cache, v_cache, counts, seen, bias,
                        packed, *moe_counts, prev_tokens=prev_tokens)

        def prefill_step(s, *args):
            return step(s, *args)

        state = (
            self.param_shardings,        # params
            self.cache_sharding,         # k
            self.cache_sharding,         # v
            self.state_sharding,         # counts
            self.state_sharding,         # seen
            self.state_sharding,         # bias
            batch2_spec,                 # packed [B, F + W + 4·S]
        )
        counters = (repl,) if moe else ()    # routed experts' counters
        out_shardings = (batch_spec, batch_spec, batch2_spec, batch2_spec,
                         batch2_spec, batch2_spec,
                         self.cache_sharding, self.cache_sharding,
                         self.state_sharding, self.state_sharding,
                         self.state_sharding) + counters
        self._packed_sharding = batch2_spec
        self._tokens_sharding = batch_spec
        # what a decode step (a block pass) is fed where none ran before
        # it, by its shape: [B] ([B, L])
        self._no_prev: Dict[Tuple[int, ...], jax.Array] = {}
        self._decode_step = jax.jit(
            decode_step, donate_argnums=(1, 2, 3, 4, 5),
            in_shardings=state + (batch_spec,) + counters,
            out_shardings=out_shardings)
        self._prefill_step = jax.jit(
            prefill_step, static_argnums=(0,),
            donate_argnums=(2, 3, 4, 5, 6),
            in_shardings=state + counters, out_shardings=out_shardings)

    # ---------- the block pass (a family whose decode unit is a block) ----------

    def _build_block_step(self):
        """``jit_decode_block``: one pass over ``[rows, 2B]`` consecutive
        positions of a family whose decode unit is a block
        (``self.unit``). A row's ``2B`` positions start at its kept
        context's end and hold one of two things, told apart by what the
        scheduler gave slots to (a slot of -1 writes nothing, routes to no
        expert, and nothing of its position's output is read):

        - a whole block and the block behind it, both with slots: the
          pass writes the whole block's final keys and values, which is
          what keeps it, and is the first denoise pass of the one behind
          (whose positions see those keys in the same layer: the trunk
          scatters before its kernel reads). There is no commit pass;
        - a block being denoised and ``B`` dead positions behind it: a
          request's first block, and any later pass of a block.

        The trunk runs all ``2B`` positions under the block mask; the
        head, sampling and the chosen token's log-probability run at the
        ``B`` positions of the block being denoised alone (scope
        ``sampling``), then the confidence and the choice of what this
        pass unmasks (scope ``block_select``). A denoise pass's own keys
        and values land in its block's slots, where its next pass
        overwrites them. The packed input is the step's (step_inputs.py)
        at ``S = 2B``, its ``last_idx`` column carrying the row's quota
        (0: a row that holds nothing) and ``counters`` the pass's number
        within its block. After it the program takes the pass before's
        ``new_ids`` (``[rows, B]``, not donated: the scheduler has yet to
        fetch it), from which a row's first block is read where the host
        packed ``step_inputs.FED``, as ``jit_decode_step`` takes its
        ``prev_tokens``."""
        unit = self.unit
        self._decode_block = None
        if unit is None:
            return
        cfg = self.config.model
        mesh = self.mesh
        batch_spec = NamedSharding(mesh, P("dp"))
        batch2_spec = NamedSharding(mesh, P("dp", None))
        batch3_spec = NamedSharding(mesh, P("dp", None, None))
        repl = NamedSharding(mesh, P())
        moe = self.moe_counts is not None
        forward, head = self._make_forward(counted=moe)
        blen = unit.length

        def decode_block(params, k_cache, v_cache, packed, prev_ids,
                         *moe_counts):
            inp = step_inputs.unpack(packed, 2 * blen)
            # a row's first block where the host had not read the pass
            # before when it packed this one (step_inputs.FED) is that
            # pass's ``new_ids``, which never left the device: the block
            # it denoised, to be denoised again or, whole, to be kept
            inp = inp._replace(tokens=inp.tokens.at[:, :blen].set(jnp.where(
                inp.tokens[:, :blen] == step_inputs.FED, prev_ids,
                inp.tokens[:, :blen])))
            hidden, (k_cache, v_cache), *moe_step = forward(
                params, (k_cache, v_cache), inp.tokens, inp.positions,
                inp.block_tables, inp.slot_mapping, inp.context_lens,
                inp.sample_slots,
            )
            rows = inp.tokens.shape[0]
            # the block being denoised is the row's second half where that
            # half is written (the first is then the whole block this pass
            # keeps), its first half otherwise
            behind = inp.slot_mapping[:, blen] >= 0

            def denoised(x):       # [rows, 2B, ...] -> [rows, B, ...]
                return jnp.where(
                    behind.reshape((rows,) + (1,) * (x.ndim - 1)),
                    x[:, blen:], x[:, :blen])

            ids, positions = denoised(inp.tokens), denoised(inp.positions)
            logits = head(denoised(hidden).reshape(rows * blen, -1), params)
            _record_search(logits)
            x0, lps, top_vals, top_ids = sample_block_positions(
                cfg, logits, inp.samp, positions, inp.want_top,
                unit.mask_id)
            new_ids, taken, left = block_select(
                ids, x0.reshape(rows, blen), lps.reshape(rows, blen),
                inp.last_idx, unit)
            out = (new_ids, jnp.where(taken, lps.reshape(rows, blen), 0.0),
                   top_vals.reshape(rows, blen, -1),
                   top_ids.reshape(rows, blen, -1), left, k_cache, v_cache)
            if moe_counts:
                row = jnp.concatenate(
                    [moe_step[0], jnp.ones((1,), jnp.int32)])
                out += (moe_counts[0].at[0].add(row),)
            return out

        self._decode_block = jax.jit(
            decode_block, donate_argnums=(1, 2),
            in_shardings=(self.param_shardings, self.cache_sharding,
                          self.cache_sharding, batch2_spec, batch2_spec)
            + ((repl,) if moe else ()),
            out_shardings=(batch2_spec, batch2_spec, batch3_spec,
                           batch3_spec, batch_spec, self.cache_sharding,
                           self.cache_sharding) + ((repl,) if moe else ()),
        )

    def _unfed(self, shape: Tuple[int, ...], sharding) -> jax.Array:
        """Zeros on the device in place of the output of a step (a
        block pass) before, where none is fed; kept a shape."""
        if shape not in self._no_prev:
            self._no_prev[shape] = jax.device_put(
                np.zeros(shape, np.int32), sharding)
        return self._no_prev[shape]

    def decode_block(
        self,
        tokens: np.ndarray,        # [B, 2L] ids, mask id where masked
        positions: np.ndarray,     # [B, 2L] consecutive from the kept end
        block_tables: np.ndarray,  # [B, W]
        slot_mapping: np.ndarray,  # [B, 2L]; -1: a dead position
        context_lens: np.ndarray,  # [B] the end of what the row writes
        quota: np.ndarray,         # [B] positions this pass unmasks; 0: no row
        temperature: np.ndarray,
        top_k: np.ndarray,
        top_p: np.ndarray,
        *,
        min_p: Optional[np.ndarray] = None,
        seed_keys: Optional[np.ndarray] = None,
        counters: Optional[np.ndarray] = None,    # [B] the pass within its block
        want_top: bool = False,
        prev_ids: Optional[jax.Array] = None,  # [B, L] the pass before's
    ) -> Tuple[jax.Array, ...]:
        """Run one block pass (``_build_block_step`` says what a row's
        ``2L`` positions hold); returns device arrays, all of the block
        being denoised (its new ids [B, L], the log-probabilities of the
        positions unmasked in this pass [B, L] and 0 elsewhere, top
        alternatives [B, L, K] twice, the count still masked [B]).
        ``prev_ids``: the ``new_ids`` the pass before returned, still on
        the device; a row whose first ``L`` entries of ``tokens`` are
        ``step_inputs.FED`` reads them from its row of it."""
        b, s = tokens.shape
        width = block_tables.shape[1]
        if seed_keys is None:
            seed_keys = np.zeros(2, np.uint32)
        buf = step_inputs.pack(
            tokens, positions, block_tables, slot_mapping,
            keys=seed_keys, want_top=want_top, context_lens=context_lens,
            last_idx=quota, counters=counters, top_k=top_k,
            temperature=temperature, top_p=top_p, min_p=min_p,
        )
        with self._track("decode_block", f"b{b}_s{s}_w{width}", arrays=1):
            moe = () if self.moe_counts is None else (self.moe_counts,)
            if prev_ids is None:
                prev_ids = self._unfed((b, s // 2), self._packed_sharding)
            new_ids, lps, top_vals, top_ids, left, k, v, *moe = (
                self._decode_block(
                    self.params, *self.kv_cache,
                    jax.device_put(buf, self._packed_sharding), prev_ids,
                    *moe))
        self.kv_cache = (k, v)
        if moe:
            self.moe_counts = moe[0]
        return new_ids, lps, top_vals, top_ids, left

    def _build_burst(self):
        """K fused decode steps per dispatch (config.multi_step_decode).

        A ``lax.scan`` chains K single-token decode steps inside ONE
        compiled program: each iteration feeds the sampled token back as
        the next input and derives its KV slot from the block table on
        device, so the host pays scheduler bookkeeping + launch latency
        once per K tokens instead of per token. Sampling math and PRNG
        fold-in (base key + ``counters + step``) are identical to the
        single-step program — the token stream is bit-equal for any K.
        The reference reaches the same amortization through its engines'
        multi-step scheduling; this is the one-SPMD-program version.
        """
        K = self.config.multi_step_decode
        self._burst = None
        self._burst_df = None
        if (K <= 1 and self.config.decode_pipeline_depth < 2
                and self.config.sp_size <= 1):
            # the chain always runs through the burst program (its
            # carry keeps sampled tokens device-resident), so depth 2
            # with multi_step_decode=1 compiles a K=1 scan
            # — and so does the SP engine's early decode handoff, which
            # chains the first burst off the final chunk's device token
            return
        cfg = self.config.model
        mesh = self.mesh
        bs = self.config.kv_block_size
        batch_spec = NamedSharding(mesh, P("dp"))
        batch2_spec = NamedSharding(mesh, P("dp", None))
        repl = NamedSharding(mesh, P())
        steps_spec = NamedSharding(mesh, P(None, "dp"))
        steps3_spec = NamedSharding(mesh, P(None, "dp", None))
        forward, head = self._make_forward()

        import dataclasses as _dc

        def decode_burst(params, k_cache, v_cache, counts, seen, bias,
                         tokens0, positions0, block_tables, samp,
                         sample_slots, commit, want_top):
            b = tokens0.shape[0]
            rows = jnp.arange(b)

            def one(carry, step_i):
                k_cache, v_cache, counts, toks, pos = carry
                # the slot for each row's pending token, straight from the
                # block table (the host precomputes this in the single-step
                # path); inactive rows write nowhere
                slot = block_tables[rows, pos // bs] * bs + pos % bs
                slot = jnp.where(commit, slot, -1)
                hidden, (k_cache, v_cache) = forward(
                    params, (k_cache, v_cache), toks[:, None], pos[:, None],
                    block_tables, slot[:, None], pos + 1,
                )
                samp_i = _dc.replace(samp, counters=samp.counters + step_i)
                nt, lp, tv, ti, counts = _sample_and_logprobs(
                    cfg, mesh, head(hidden[:, 0], params), samp_i, counts,
                    seen, bias, sample_slots, commit, want_top, live=commit,
                )
                return (k_cache, v_cache, counts, nt, pos + 1), (nt, lp, tv, ti)

            init = (k_cache, v_cache, counts, tokens0, positions0)
            (k_cache, v_cache, counts, _, _), (toks, lps, tvs, tis) = (
                jax.lax.scan(one, init, jnp.arange(K))
            )
            return (toks, lps, tvs, tis, k_cache, v_cache, counts, seen,
                    bias)

        samp_spec = SamplingParams(
            temperature=batch_spec, top_k=batch_spec, top_p=batch_spec,
            min_p=batch_spec, presence_penalty=batch_spec,
            frequency_penalty=batch_spec, repetition_penalty=batch_spec,
            keys=batch2_spec, counters=batch_spec,
        )
        self._burst = jax.jit(
            decode_burst,
            donate_argnums=(1, 2, 3, 4, 5),
            in_shardings=(
                self.param_shardings,
                self.cache_sharding, self.cache_sharding,
                self.state_sharding, self.state_sharding, self.state_sharding,
                batch_spec,                  # tokens0 [B]
                batch_spec,                  # positions0 [B]
                batch2_spec,                 # block_tables [B, W]
                samp_spec,
                batch_spec,                  # sample_slots
                batch_spec,                  # commit
                repl,                        # want_top
            ),
            out_shardings=(steps_spec, steps_spec, steps3_spec, steps3_spec,
                           self.cache_sharding, self.cache_sharding,
                           self.state_sharding, self.state_sharding,
                           self.state_sharding),
        )

        if not self.config.chain_enabled:
            return

        # ---- the device-finish (persistent-loop) variant ----
        #
        # Same K-step scan, plus a per-row ``done`` carry and on-device
        # finish state: EOS / hidden-stop membership ([B, STOP_ID_WIDTH]
        # id matrix), per-row generated-token counters against min/max
        # bounds, and the model-len horizon — evaluated each step by
        # sampling.device_finish_mask, the exact mirror of
        # Scheduler._check_finish. A row that finishes FREEZES: its KV
        # slot goes to -1 (no writes), its sampling-penalty counts stop
        # updating (``live`` gates _sample_and_logprobs' commit), its
        # position/token/counter carries stop advancing, and its output
        # lane emits -1 pads. The burst itself never ends early, so the
        # scheduler can chain dispatches off the returned device carry
        # without any host round-trip.
        #
        # The carry additionally holds the UNRESTRICTED-traffic state:
        # ``ring`` — the row's trailing SUFFIX_RING_W emitted tokens,
        # hashed each step against the stop strings' canonical-
        # tokenization hashes (sampling.stop_candidate_mask; a match
        # freezes the row as a *candidate* the host confirms exactly on
        # drain) — and ``gstate``, the guided-grammar cursor advanced
        # through a bounded device transition table (``gtable``:
        # state × token → next state, -1 reject, state 0 = DONE;
        # engine/guided.compile_device_table). Rows with gstate < 0 are
        # unguided and never consult the table.
        from .sampling import (
    block_select,
    sample_block_positions,
            device_finish_mask,
            ring_push,
            stop_candidate_mask,
        )

        max_len = self.config.max_model_len

        def decode_burst_df(params, k_cache, v_cache, counts, seen, bias,
                            tokens0, positions0, gen0, done0, ring0,
                            gstate0, block_tables, samp, sample_slots,
                            commit, want_top, stop_ids, min_new, max_new,
                            stop_hash, stop_hlen, gtable):
            b = tokens0.shape[0]
            rows = jnp.arange(b)

            def one(carry, _step_i):
                (k_cache, v_cache, counts, toks, pos, gen, done, ring,
                 gstate) = carry
                live = jnp.logical_and(commit, jnp.logical_not(done))
                slot = block_tables[rows, pos // bs] * bs + pos % bs
                slot = jnp.where(live, slot, -1)
                hidden, (k_cache, v_cache) = forward(
                    params, (k_cache, v_cache), toks[:, None], pos[:, None],
                    block_tables, slot[:, None], pos + 1,
                )
                # PRNG fold-in counter IS the carried generated count, so
                # a frozen row's counter stops with it and a live row's
                # matches the single-step path exactly
                samp_i = _dc.replace(samp, counters=gen)
                # guided mask from the device table: the sync path bakes
                # the same mask into the persistent bias buffer, so
                # adding it here keeps logits (and logprobs) bit-equal
                guided = gstate >= 0
                sel = jnp.where(guided, gstate, 0)
                grow = gtable[sel]                       # [B, V]
                gmask = jnp.where(
                    guided[:, None] & (grow < 0), -1e9, 0.0
                ).astype(jnp.float32)
                nt, lp, tv, ti, counts = _sample_and_logprobs(
                    cfg, mesh, head(hidden[:, 0], params), samp_i, counts,
                    seen, bias, sample_slots, live, want_top,
                    extra_bias=gmask, live=live,
                )
                gen_n = gen + live.astype(jnp.int32)
                ring_n = ring_push(ring, nt, live)
                hard = device_finish_mask(
                    nt, gen_n, pos, stop_ids, min_new, max_new, max_len
                )
                cand = stop_candidate_mask(
                    ring_n, gen_n, min_new, stop_hash, stop_hlen
                )
                # grammar advance on the sampled token: DONE (state 0)
                # completes the constraint; a reject (< 0) is
                # unreachable through the mask but freezes defensively —
                # the host names either verdict on drain. A hard finish
                # (eos at a legal end) wins, mirroring the host's
                # _check_finish-before-guided-advance order.
                gnext = gtable[sel, nt]
                gdone = guided & jnp.logical_not(hard) & (gnext <= 0)
                newly = live & (hard | cand | gdone)
                done_n = done | newly
                # the finishing token still emits (the host streams it);
                # later steps of a frozen row emit -1 pads
                out_tok = jnp.where(live, nt, -1)
                out_lp = jnp.where(live, lp, 0.0)
                adv = live & jnp.logical_not(newly)
                toks_n = jnp.where(adv, nt, toks)
                pos_n = jnp.where(adv, pos + 1, pos)
                gstate_n = jnp.where(adv & guided, gnext, gstate)
                return ((k_cache, v_cache, counts, toks_n, pos_n, gen_n,
                         done_n, ring_n, gstate_n),
                        (out_tok, out_lp, tv, ti))

            init = (k_cache, v_cache, counts, tokens0, positions0, gen0,
                    done0, ring0, gstate0)
            ((k_cache, v_cache, counts, tok_c, pos_c, gen_c, done_c,
              ring_c, gstate_c),
             (toks, lps, tvs, tis)) = jax.lax.scan(
                one, init, jnp.arange(K)
            )
            return (toks, lps, tvs, tis, tok_c, pos_c, gen_c, done_c,
                    ring_c, gstate_c,
                    k_cache, v_cache, counts, seen, bias)

        self._burst_df = jax.jit(
            decode_burst_df,
            donate_argnums=(1, 2, 3, 4, 5),
            in_shardings=(
                self.param_shardings,
                self.cache_sharding, self.cache_sharding,
                self.state_sharding, self.state_sharding, self.state_sharding,
                batch_spec,                  # tokens0 [B]
                batch_spec,                  # positions0 [B]
                batch_spec,                  # gen0 [B]
                batch_spec,                  # done0 [B]
                batch2_spec,                 # ring0 [B, RING_W]
                batch_spec,                  # gstate0 [B]
                batch2_spec,                 # block_tables [B, W]
                samp_spec,
                batch_spec,                  # sample_slots
                batch_spec,                  # commit
                repl,                        # want_top
                batch2_spec,                 # stop_ids [B, E]
                batch_spec,                  # min_new [B]
                batch_spec,                  # max_new [B]
                batch2_spec,                 # stop_hash [B, NS]
                batch2_spec,                 # stop_hlen [B, NS]
                repl,                        # gtable [S, V]
            ),
            out_shardings=(steps_spec, steps_spec, steps3_spec, steps3_spec,
                           batch_spec, batch_spec, batch_spec, batch_spec,
                           batch2_spec, batch_spec,
                           self.cache_sharding, self.cache_sharding,
                           self.state_sharding, self.state_sharding,
                           self.state_sharding),
        )

    def _build_spec_burst(self):
        """Propose-verify rounds chained off the SAME device carry as
        the device-finish burst — the in-carry half of speculative
        decoding (ISSUE 13 / ROADMAP item 2).

        One dispatch = one round: S = K+1 positions run through one
        forward (the pending token + up to K proposals), the full head's
        per-position argmax is the verify, the accepted prefix + the
        correction token commit with the SAME freeze semantics as the
        plain chained burst (finish mask + suffix-hash stop candidates
        per emitted token), and the carry feeds the next round without a
        host barrier. Two jit variants share the traced round body:
        ``_spec_ngram`` derives proposals from the carry's trailing-token
        ring in-program; ``_spec_verify`` takes them as a device array —
        the draft model's chained burst output — so draft/target rounds
        interleave with no host sync between them. Spec-eligible rows
        are greedy and penalty-free (scheduler._spec_eligible), so the
        round needs no sampling params and never touches the
        counts/seen/bias buffers — exactly like the sync verify's
        commit=False dispatch.
        """
        self._spec_ngram = None
        self._spec_verify = None
        cfg_e = self.config
        K = (cfg_e.spec_draft_tokens if cfg_e.spec_draft_model
             else cfg_e.spec_ngram_tokens)
        if K <= 0 or not cfg_e.chain_enabled:
            return
        cfg = self.config.model
        mesh = self.mesh
        bs = self.config.kv_block_size
        batch_spec = NamedSharding(mesh, P("dp"))
        batch2_spec = NamedSharding(mesh, P(None, "dp"))
        batchrow_spec = NamedSharding(mesh, P("dp", None))
        repl = NamedSharding(mesh, P())
        forward, head = self._make_forward()
        from .sampling import (
            device_finish_mask,
            ring_push,
            stop_candidate_mask,
        )

        S = K + 1
        max_len = self.config.max_model_len
        match = self.config.spec_ngram_match

        def spec_verify(params, k_cache, v_cache, tokens0, positions0,
                        gen0, done0, ring0, gstate0, block_tables, commit,
                        stop_ids, min_new, max_new, stop_hash, stop_hlen,
                        props):
            b = tokens0.shape[0]
            rows = jnp.arange(b)
            live0 = jnp.logical_and(commit, jnp.logical_not(done0))
            valid = props >= 0                               # [B, K]
            row_toks = jnp.concatenate(
                [tokens0[:, None], jnp.where(valid, props, 0)], axis=1
            )                                                # [B, S]
            poss = positions0[:, None] + jnp.arange(S)[None, :]
            slots = block_tables[rows[:, None], poss // bs] * bs + poss % bs
            slots = jnp.where(live0[:, None], slots, -1)
            hidden, (k_cache, v_cache) = forward(
                params, (k_cache, v_cache), row_toks, poss, block_tables,
                slots, positions0 + S,
            )
            greedy = jnp.argmax(
                head(hidden, params), axis=-1
            ).astype(jnp.int32)                              # [B, S]
            m = valid & (greedy[:, :K] == props)
            acc = jnp.cumprod(m.astype(jnp.int32), axis=1).sum(axis=1)
            nprop = jnp.where(live0, valid.astype(jnp.int32).sum(axis=1), 0)

            # acceptance accounting matches the sync verify: proposals
            # that VERIFIED, even if a finish truncates the emit below
            # (the freeze-fold decides what streams, not what counted)
            nacc = jnp.where(live0, acc, 0)

            # fold the emitted positions in order, re-running the exact
            # per-token finish/freeze logic of the plain chained burst
            outs = []
            toks_c, pos_c, gen_c = tokens0, positions0, gen0
            done_c, ring_c = done0, ring0
            for j in range(S):
                t_j = greedy[:, j]
                emit = live0 & jnp.logical_not(done_c) & (j <= acc)
                gen_c = gen_c + emit.astype(jnp.int32)
                ring_c = ring_push(ring_c, t_j, emit)
                hard = device_finish_mask(
                    t_j, gen_c, pos_c, stop_ids, min_new, max_new, max_len
                )
                cand = stop_candidate_mask(
                    ring_c, gen_c, min_new, stop_hash, stop_hlen
                )
                newly = emit & (hard | cand)
                outs.append(jnp.where(emit, t_j, -1))
                adv = emit & jnp.logical_not(newly)
                toks_c = jnp.where(adv, t_j, toks_c)
                pos_c = jnp.where(adv, pos_c + 1, pos_c)
                done_c = done_c | newly
            return (jnp.stack(outs, axis=0), nprop, nacc, toks_c, pos_c,
                    gen_c, done_c, ring_c, gstate0, k_cache, v_cache)

        common_in = (
            self.param_shardings,
            self.cache_sharding, self.cache_sharding,
            batch_spec,      # tokens0
            batch_spec,      # positions0
            batch_spec,      # gen0
            batch_spec,      # done0
            batchrow_spec,   # ring0
            batch_spec,      # gstate0
            batchrow_spec,   # block_tables
            batch_spec,      # commit
            batchrow_spec,   # stop_ids
            batch_spec,      # min_new
            batch_spec,      # max_new
            batchrow_spec,   # stop_hash
            batchrow_spec,   # stop_hlen
        )
        common_out = (
            batch2_spec,     # toks [S, B]
            batch_spec,      # nprop
            batch_spec,      # nacc
            batch_spec, batch_spec, batch_spec, batch_spec,  # tok/pos/gen/done
            batchrow_spec,   # ring
            batch_spec,      # gstate
            self.cache_sharding, self.cache_sharding,
        )

        def spec_ngram(params, k_cache, v_cache, tokens0, positions0,
                       gen0, done0, ring0, gstate0, block_tables, commit,
                       stop_ids, min_new, max_new, stop_hash, stop_hlen):
            props = _ngram_props(ring0, match, K)
            return spec_verify(
                params, k_cache, v_cache, tokens0, positions0, gen0,
                done0, ring0, gstate0, block_tables, commit, stop_ids,
                min_new, max_new, stop_hash, stop_hlen, props,
            )

        if cfg_e.spec_draft_model:
            self._spec_verify = jax.jit(
                spec_verify,
                donate_argnums=(1, 2),
                in_shardings=common_in + (batchrow_spec,),  # props [B, K]
                out_shardings=common_out,
            )
        else:
            self._spec_ngram = jax.jit(
                spec_ngram,
                donate_argnums=(1, 2),
                in_shardings=common_in,
                out_shardings=common_out,
            )
        self._spec_k = K

    def _build_sp_prefill(self):
        """The sequence-parallel long-context prefill program.

        One compiled shape: [1, S] chunk tokens sharded over the mesh's
        ``sp`` axis (S = config.sp_prefill_bucket(); short/final chunks
        pad into it), fresh K/V scattered into the paged cache exactly
        like the dense ladder, attention = one ring pass over the chunk
        plus the gathered committed prefix (parallel/sequence.py
        sp_chunk_attention), and the dense step's sampling tail on the
        last valid position so the final chunk's sampled token — and its
        logprobs — are bit-identical to what the dense ladder would have
        produced. Non-final chunks dispatch with commit=False and
        nothing reads their outputs.
        """
        self._sp_prefill = None
        cfg_e = self.config
        if cfg_e.sp_size <= 1:
            return
        if "sp" not in self.mesh.axis_names or self.mesh.shape["sp"] <= 1:
            raise ValueError(
                f"sp_size {cfg_e.sp_size} needs an 'sp' mesh axis of that "
                f"size (got mesh {dict(self.mesh.shape)})"
            )
        cfg = self.config.model
        if (self.arch is not llama or cfg.sliding_window
                or cfg.attn_logit_softcap or cfg.num_experts
                or cfg.kv_lora_rank):
            raise ValueError(
                "sequence-parallel prefill currently serves llama-family "
                "GQA dense trunks without sliding windows (the ring "
                "kernel has no MLA/MoE/windowed variant yet)"
            )
        mesh = self.mesh
        sp = cfg_e.sp_size
        head_axis = "tp" if cfg_e.tp_size > 1 else None
        S = cfg_e.sp_prefill_bucket()
        bs = cfg_e.kv_block_size
        # block-table width padded so the gathered prefix (W*bs keys)
        # shards evenly over the axis alongside the chunk's S
        w = cfg_e.blocks_per_seq
        while (w * bs) % sp:
            w += 1
        self._sp_bucket = S
        self._sp_width = w
        repl = NamedSharding(mesh, P())
        seq_spec = NamedSharding(mesh, P(None, "sp"))
        forward, head = self._make_forward()
        del forward  # the SP trunk has its own

        def prefill_sp(params, k_cache, v_cache, counts, seen, bias,
                       tokens, positions, block_tables, slot_mapping,
                       context_lens, chunk_start, last_idx, samp,
                       sample_slots, commit, want_top):
            hidden, (k_cache, v_cache) = llama.sp_decoder_forward(
                params, cfg, tokens, positions, (k_cache, v_cache),
                block_tables, slot_mapping, context_lens, chunk_start,
                mesh, sp_axis="sp", head_axis=head_axis,
            )
            b = tokens.shape[0]
            last_logits = head(hidden[jnp.arange(b), last_idx], params)
            next_tokens, lps, top_vals, top_ids, counts = (
                _sample_and_logprobs(
                    cfg, mesh, last_logits, samp, counts, seen, bias,
                    sample_slots, commit, want_top,
                )
            )
            return (next_tokens, lps, top_vals, top_ids, k_cache, v_cache,
                    counts, seen, bias)

        samp_spec = SamplingParams(
            temperature=repl, top_k=repl, top_p=repl, min_p=repl,
            presence_penalty=repl, frequency_penalty=repl,
            repetition_penalty=repl, keys=repl, counters=repl,
        )
        self._sp_prefill = jax.jit(
            prefill_sp,
            donate_argnums=(1, 2, 3, 4, 5),
            in_shardings=(
                self.param_shardings,
                self.cache_sharding, self.cache_sharding,
                self.state_sharding, self.state_sharding,
                self.state_sharding,
                seq_spec,                    # tokens [1, S]
                seq_spec,                    # positions [1, S]
                repl,                        # block_tables [1, W]
                seq_spec,                    # slot_mapping [1, S]
                repl,                        # context_lens [1]
                repl,                        # chunk_start scalar
                repl,                        # last_idx [1]
                samp_spec,
                repl,                        # sample_slots [1]
                repl,                        # commit [1]
                repl,                        # want_top
            ),
            out_shardings=(repl, repl, repl, repl,
                           self.cache_sharding, self.cache_sharding,
                           self.state_sharding, self.state_sharding,
                           self.state_sharding),
        )

    @property
    def sp_ready(self) -> bool:
        """Is the sequence-parallel prefill program built? (The scheduler
        and the disagg prefill worker gate the SP ladder on this.)"""
        return getattr(self, "_sp_prefill", None) is not None

    @property
    def sp_chunk_tokens(self) -> int:
        """Tokens one SP chunk advances (the fixed compiled bucket)."""
        return self._sp_bucket

    def sp_prefill_chunk(
        self,
        prompt,                    # full token list UP TO the chunk end
        start: int,                # chunk's first position (KV before it
        block_ids,                 #   is already committed)
        *,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        min_p: float = 0.0,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        repetition_penalty: float = 1.0,
        seed_keys=None,            # [2] u32 per-request key
        counters: int = 0,
        sample_slot: int = 0,
        commit: bool = False,      # final chunk samples/commits
        want_top: bool = False,
    ):
        """Dispatch ONE sequence-parallel prefill chunk ([start,
        len(prompt)) of the prompt, ≤ sp_chunk_tokens tokens). Returns
        the step-tail device arrays ``(next_tokens, lps, top_vals,
        top_ids)`` — meaningful only on the committing (final) chunk.
        Dispatch-only: no host sync happens here."""
        S = self._sp_bucket
        w = self._sp_width
        bs = self.config.kv_block_size
        suffix = prompt[start:]
        take = len(suffix)
        assert 0 < take <= S, (take, S)
        tokens = np.zeros((1, S), np.int32)
        tokens[0, :take] = suffix
        positions = np.full((1, S), len(prompt) - 1, np.int32)
        positions[0, :take] = np.arange(start, len(prompt))
        slot_map = np.full((1, S), -1, np.int32)
        for i, pos in enumerate(range(start, len(prompt))):
            slot_map[0, i] = block_ids[pos // bs] * bs + pos % bs
        btab = np.zeros((1, w), np.int32)
        btab[0, : len(block_ids)] = block_ids
        if seed_keys is None:
            seed_keys = np.zeros(2, np.uint32)
        samp = SamplingParams(
            temperature=jnp.asarray([temperature], jnp.float32),
            top_k=jnp.asarray([top_k], jnp.int32),
            top_p=jnp.asarray([top_p], jnp.float32),
            min_p=jnp.asarray([min_p], jnp.float32),
            presence_penalty=jnp.asarray([presence_penalty], jnp.float32),
            frequency_penalty=jnp.asarray([frequency_penalty], jnp.float32),
            repetition_penalty=jnp.asarray([repetition_penalty],
                                           jnp.float32),
            keys=jnp.asarray(np.asarray(seed_keys, np.uint32)[None, :]),
            counters=jnp.asarray([counters], jnp.int32),
        )
        with self.compiles.track("prefill_sp", f"s{S}_w{w}"):
            (next_tokens, lps, top_vals, top_ids, k, v, counts, seen,
             bias) = self._sp_prefill(
                self.params, self.kv_cache[0], self.kv_cache[1],
                self.sample_state[0], self.sample_state[1],
                self.sample_state[2],
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(btab), jnp.asarray(slot_map),
                jnp.asarray([len(prompt)], jnp.int32),
                jnp.asarray(start, jnp.int32),
                jnp.asarray([take - 1], jnp.int32),
                samp,
                jnp.asarray([sample_slot], jnp.int32),
                jnp.asarray([commit], jnp.bool_),
                jnp.asarray(bool(want_top), jnp.bool_),
            )
        self.kv_cache = (k, v)
        self.sample_state = (counts, seen, bias)
        return next_tokens, lps, top_vals, top_ids

    @property
    def spec_burst_ready(self) -> bool:
        """Are the chained propose-verify programs built? (The scheduler
        gates the spec chain on this; test doubles may just define
        decode_burst_spec.)"""
        return (getattr(self, "_spec_ngram", None) is not None
                or getattr(self, "_spec_verify", None) is not None)

    def decode_burst_spec(
        self,
        tokens0,                   # [B] np (chain start) or device carry
        positions0,
        gen0,
        done0,
        ring0,                     # [B, SUFFIX_RING_W]
        gstate0,                   # [B] (passthrough; spec rows unguided)
        block_tables: np.ndarray,  # [B, W]
        *,
        commit,                    # [B] bool (host np or device)
        stop_ids: np.ndarray,
        min_new: np.ndarray,
        max_new: np.ndarray,
        stop_hash: np.ndarray,
        stop_hlen: np.ndarray,
        proposals=None,            # [B, K] device array (draft) or None (ngram)
    ):
        """One chained propose-verify round; returns ``(toks [S, B],
        nprop [B], nacc [B], carry)`` with -1 pads past each row's
        acceptance/freeze and the same carry tuple as
        ``decode_burst_chained``."""
        b = block_tables.shape[0]
        args = (
            self.params, self.kv_cache[0], self.kv_cache[1],
            jnp.asarray(tokens0, jnp.int32),
            jnp.asarray(positions0, jnp.int32),
            jnp.asarray(gen0, jnp.int32),
            jnp.asarray(done0, jnp.bool_),
            jnp.asarray(ring0, jnp.int32),
            jnp.asarray(gstate0, jnp.int32),
            jnp.asarray(block_tables, jnp.int32),
            jnp.asarray(commit, jnp.bool_),
            jnp.asarray(stop_ids, jnp.int32),
            jnp.asarray(min_new, jnp.int32),
            jnp.asarray(max_new, jnp.int32),
            jnp.asarray(stop_hash, jnp.uint32),
            jnp.asarray(stop_hlen, jnp.int32),
        )
        with self._track(
            "decode_burst_spec", f"b{b}_w{block_tables.shape[1]}"
        ):
            if proposals is None:
                out = self._spec_ngram(*args)
            else:
                out = self._spec_verify(
                    *args, jnp.asarray(proposals, jnp.int32)
                )
        (toks, nprop, nacc, tok_c, pos_c, gen_c, done_c, ring_c,
         gstate_c, k, v) = out
        self.kv_cache = (k, v)
        return toks, nprop, nacc, (tok_c, pos_c, gen_c, done_c, ring_c,
                                   gstate_c)

    def decode_burst(
        self,
        tokens0: np.ndarray,       # [B] pending token per row
        positions0: np.ndarray,    # [B] its position
        block_tables: np.ndarray,  # [B, W] covering positions0 + K
        temperature: np.ndarray,
        top_k: np.ndarray,
        top_p: np.ndarray,
        *,
        min_p: np.ndarray,
        presence_penalty: np.ndarray,
        frequency_penalty: np.ndarray,
        repetition_penalty: np.ndarray,
        seed_keys: np.ndarray,
        counters: np.ndarray,
        commit: np.ndarray,        # [B] row is live (inactive rows inert)
        want_top: bool = False,
    ):
        """Run the K-step fused decode; returns [K, B]-leading arrays."""
        samp = SamplingParams(
            temperature=jnp.asarray(temperature, jnp.float32),
            top_k=jnp.asarray(top_k, jnp.int32),
            top_p=jnp.asarray(top_p, jnp.float32),
            min_p=jnp.asarray(min_p, jnp.float32),
            presence_penalty=jnp.asarray(presence_penalty, jnp.float32),
            frequency_penalty=jnp.asarray(frequency_penalty, jnp.float32),
            repetition_penalty=jnp.asarray(repetition_penalty, jnp.float32),
            keys=jnp.asarray(seed_keys, jnp.uint32),
            counters=jnp.asarray(counters, jnp.int32),
        )
        b = tokens0.shape[0]
        with self._track(
            "decode_burst", f"b{b}_w{block_tables.shape[1]}"
        ):
            (toks, lps, tvs, tis, k, v, counts, seen, bias) = self._burst(
                self.params, self.kv_cache[0], self.kv_cache[1],
                self.sample_state[0], self.sample_state[1],
                self.sample_state[2],
                jnp.asarray(tokens0, jnp.int32),
                jnp.asarray(positions0, jnp.int32),
                jnp.asarray(block_tables, jnp.int32),
                samp,
                jnp.arange(b, dtype=jnp.int32),
                jnp.asarray(commit, jnp.bool_),
                jnp.asarray(bool(want_top), jnp.bool_),
            )
        self.kv_cache = (k, v)
        self.sample_state = (counts, seen, bias)
        return toks, lps, tvs, tis

    # guided device tables pad their state dim to this ladder so each
    # bucket is one compiled burst program, not one per grammar
    GUIDED_STATE_BUCKETS = (1, 64, 256, 1024)

    def guided_state_bucket(self, n_states: int) -> int:
        for s in self.GUIDED_STATE_BUCKETS:
            if n_states <= s:
                return s
        return self.GUIDED_STATE_BUCKETS[-1]

    def _dummy_guided_table(self):
        """The shared [1, V] all-reject table for unguided dispatches —
        rows with gstate < 0 never consult it."""
        if getattr(self, "_dummy_gtable", None) is None:
            self._dummy_gtable = jnp.full(
                (1, self.config.model.vocab_size), -1, jnp.int32
            )
        return self._dummy_gtable

    def decode_burst_chained(
        self,
        tokens0,                   # [B] np (chain start) or device carry
        positions0,                # [B] likewise
        gen0,                      # [B] generated-token counts, likewise
        done0,                     # [B] bool done mask, likewise
        block_tables: np.ndarray,  # [B, W]
        temperature: np.ndarray,
        top_k: np.ndarray,
        top_p: np.ndarray,
        *,
        min_p: np.ndarray,
        presence_penalty: np.ndarray,
        frequency_penalty: np.ndarray,
        repetition_penalty: np.ndarray,
        seed_keys: np.ndarray,
        commit: np.ndarray,        # [B] row is a (live) chain member
        stop_ids: np.ndarray,      # [B, STOP_ID_WIDTH] -1-padded stop set
        min_new: np.ndarray,       # [B] i32
        max_new: np.ndarray,       # [B] i32
        ring0=None,                # [B, SUFFIX_RING_W] trailing tokens
        gstate0=None,              # [B] guided table state (-1 unguided)
        stop_hash=None,            # [B, STOP_SEQ_WIDTH] uint32 targets
        stop_hlen=None,            # [B, STOP_SEQ_WIDTH] i32 lengths
        gtable=None,               # [S, V] device table (None = dummy)
        want_top: bool = False,
    ):
        """Run one K-step burst with device-resident finish detection.

        Returns ``(toks, lps, tvs, tis, carry)`` with [K, B]-leading
        output arrays (-1 pads past each row's finish) and ``carry`` the
        next dispatch's device-resident ``(tokens, positions, gen, done,
        ring, gstate)`` — feed it straight back as the leading carry
        arguments to chain bursts without a host round-trip.
        """
        from .sampling import STOP_SEQ_WIDTH, SUFFIX_RING_W

        b = block_tables.shape[0]
        samp = SamplingParams(
            temperature=jnp.asarray(temperature, jnp.float32),
            top_k=jnp.asarray(top_k, jnp.int32),
            top_p=jnp.asarray(top_p, jnp.float32),
            min_p=jnp.asarray(min_p, jnp.float32),
            presence_penalty=jnp.asarray(presence_penalty, jnp.float32),
            frequency_penalty=jnp.asarray(frequency_penalty, jnp.float32),
            repetition_penalty=jnp.asarray(repetition_penalty, jnp.float32),
            keys=jnp.asarray(seed_keys, jnp.uint32),
            counters=jnp.asarray(gen0, jnp.int32),  # carried in-scan
        )
        if ring0 is None:
            ring0 = np.full((b, SUFFIX_RING_W), -1, np.int32)
        if gstate0 is None:
            gstate0 = np.full(b, -1, np.int32)
        if stop_hash is None:
            stop_hash = np.zeros((b, STOP_SEQ_WIDTH), np.uint32)
        if stop_hlen is None:
            stop_hlen = np.zeros((b, STOP_SEQ_WIDTH), np.int32)
        if gtable is None:
            gtable = self._dummy_guided_table()
        with self._track(
            "decode_burst_df",
            f"b{b}_w{block_tables.shape[1]}_g{gtable.shape[0]}",
        ):
            (toks, lps, tvs, tis, tok_c, pos_c, gen_c, done_c, ring_c,
             gstate_c, k, v, counts, seen, bias) = self._burst_df(
                self.params, self.kv_cache[0], self.kv_cache[1],
                self.sample_state[0], self.sample_state[1],
                self.sample_state[2],
                jnp.asarray(tokens0, jnp.int32),
                jnp.asarray(positions0, jnp.int32),
                jnp.asarray(gen0, jnp.int32),
                jnp.asarray(done0, jnp.bool_),
                jnp.asarray(ring0, jnp.int32),
                jnp.asarray(gstate0, jnp.int32),
                jnp.asarray(block_tables, jnp.int32),
                samp,
                jnp.arange(b, dtype=jnp.int32),
                jnp.asarray(commit, jnp.bool_),
                jnp.asarray(bool(want_top), jnp.bool_),
                jnp.asarray(stop_ids, jnp.int32),
                jnp.asarray(min_new, jnp.int32),
                jnp.asarray(max_new, jnp.int32),
                jnp.asarray(stop_hash, jnp.uint32),
                jnp.asarray(stop_hlen, jnp.int32),
                jnp.asarray(gtable, jnp.int32),
            )
        self.kv_cache = (k, v)
        self.sample_state = (counts, seen, bias)
        return toks, lps, tvs, tis, (tok_c, pos_c, gen_c, done_c, ring_c,
                                     gstate_c)

    def step(
        self,
        tokens: np.ndarray,        # [B, S]
        positions: np.ndarray,     # [B, S]
        block_tables: np.ndarray,  # [B, W]
        slot_mapping: np.ndarray,  # [B, S]
        context_lens: np.ndarray,  # [B]
        last_idx: np.ndarray,      # [B] index of the position to sample from
        temperature: np.ndarray,
        top_k: np.ndarray,
        top_p: np.ndarray,
        key: Optional[jax.Array] = None,
        *,
        min_p: Optional[np.ndarray] = None,
        presence_penalty: Optional[np.ndarray] = None,
        frequency_penalty: Optional[np.ndarray] = None,
        repetition_penalty: Optional[np.ndarray] = None,
        seed_keys: Optional[np.ndarray] = None,   # [B, 2] u32 per-row keys
        counters: Optional[np.ndarray] = None,    # [B] i32 fold-in counters
        sample_slots: Optional[np.ndarray] = None,  # [B] i32 state-row per batch row
        commit: Optional[np.ndarray] = None,      # [B] bool count sampled token
        want_top: bool = True,  # compute top-K alternatives this step?
        targets: Optional[np.ndarray] = None,  # [B, S] next-prompt-token ids
        want_prompt: bool = False,  # compute prompt logprobs at `targets`?
        want_greedy: bool = False,  # per-position argmax (spec verify)?
        window_tables: Optional[np.ndarray] = None,  # [B, W] the window kind's
        prev_tokens: Optional[jax.Array] = None,  # [B] the step before's
    ) -> Tuple[jax.Array, ...]:
        """Run one compiled step; returns (next_tokens, logprobs) device arrays.

        ``prev_tokens`` (a decode step only): the ``next_tokens`` the
        step dispatched before this one returned, still on the device. A
        row whose entry of ``tokens`` is ``step_inputs.FED`` reads its
        token there, so the host can dispatch this step before it has
        read that one (``Scheduler._decode``). It is the same program
        with or without: left out, the argument is a ``[B]`` of zeros
        that no row reads.

        ``window_tables`` (a family with two kinds of page, models/
        afmoe.py): the window kind's table, laid beside ``block_tables``
        in the packed input; left out, every entry names page 0, which
        no sequence holds (warm-up, a step that writes nothing).

        Legacy callers pass a single ``key`` (tests, warmup, dry runs): it is
        broadcast into per-row keys with the row index as fold-in counter.
        The scheduler passes per-request ``seed_keys``/``counters`` instead.
        """
        b, s = tokens.shape
        width = block_tables.shape[1]
        if self.keeps.window_pool:
            block_tables = np.concatenate(
                [block_tables, np.zeros_like(block_tables)
                 if window_tables is None else window_tables], axis=1)
        if seed_keys is None:
            # PRNGKey(0)'s two words are zero
            seed_keys = (np.zeros(2, np.uint32) if key is None else
                         np.asarray(jax.random.key_data(key), np.uint32))
        # one fresh host array a pass, put once with the sharding the
        # program expects: the jitted call then places nothing again
        buf = step_inputs.pack(
            tokens, positions, block_tables, slot_mapping, targets,
            keys=seed_keys, want_top=want_top, want_prompt=want_prompt,
            want_greedy=want_greedy, context_lens=context_lens,
            last_idx=last_idx, sample_slots=sample_slots, counters=counters,
            commit=commit, top_k=top_k, temperature=temperature, top_p=top_p,
            min_p=min_p, presence_penalty=presence_penalty,
            frequency_penalty=frequency_penalty,
            repetition_penalty=repetition_penalty,
        )
        # a decode-shaped step is sized by ``table_width``: one query a
        # row, or the speculative verify's K + 1 (the one caller that
        # wants every position's argmax)
        shape = "decode" if s == 1 else "verify" if want_greedy else "prefill"
        with self._track(
            "prefill" if s > 1 else "decode",
            f"b{b}_s{s}_w{width}", shape=shape, arrays=1,
        ):
            moe = () if self.moe_counts is None else (self.moe_counts,)
            fed = ()
            if s == 1:
                if prev_tokens is None:
                    prev_tokens = self._unfed((b,), self._tokens_sharding)
                fed = (prev_tokens,)
            args = (self.params, *self.kv_cache, *self.sample_state,
                    jax.device_put(buf, self._packed_sharding), *fed, *moe)
            (next_tokens, lps, top_vals, top_ids, prompt_lps, greedy_all,
             k, v, counts, seen, bias, *moe) = (
                self._prefill_step(s, *args) if s > 1
                else self._decode_step(*args))
        self.kv_cache = (k, v)
        self.sample_state = (counts, seen, bias)
        if moe:
            self.moe_counts = moe[0]
        return next_tokens, lps, top_vals, top_ids, prompt_lps, greedy_all

    @property
    def embed_ready(self) -> bool:
        """Can this runner serve the embeddings workload? Llama-family
        GQA dense trunks without sliding windows (embed_forward runs the
        cacheless dense-attention trunk)."""
        cfg = self.config.model
        return (self.arch is llama and not cfg.sliding_window
                and not cfg.num_experts and not cfg.kv_lora_rank
                and self.config.pp_size == 1)

    def embed_prompts(self, prompts) -> np.ndarray:
        """Batched prefill-only embeddings: prompts → [n, D] float32.

        Rides the batched-prefill shape discipline — rows pad to the
        PREFILL_ROW_BUCKETS ladder, lengths to the prefill bucket ladder
        (one compiled program per (rows, bucket), built on first use) —
        but through the CACHELESS trunk (models/llama.embed_forward): no
        block allocation, no KV writes, no decode slot. Blocking (host
        sync inside); callers on an event loop run it in an executor.
        """
        if not self.embed_ready:
            raise ValueError(
                "embeddings are served by llama-family GQA dense trunks "
                "only (no MoE/MLA/sliding-window embed path yet)"
            )
        cfg = self.config
        out = np.zeros((len(prompts), cfg.model.hidden_size), np.float32)
        i = 0
        while i < len(prompts):
            batch = prompts[i : i + cfg.PREFILL_ROW_BUCKETS[-1]]
            rows = cfg.prefill_row_bucket(len(batch))
            bucket = cfg.bucket_for(max(len(p) for p in batch))
            tokens = np.zeros((rows, bucket), np.int32)
            positions = np.zeros((rows, bucket), np.int32)
            valid = np.ones(rows, np.int32)
            for j, p in enumerate(batch):
                tokens[j, : len(p)] = p
                positions[j, : len(p)] = np.arange(len(p))
                positions[j, len(p):] = len(p) - 1
                valid[j] = len(p)
            prog = self._embed_progs.get((rows, bucket))
            if prog is None:
                mesh = self.mesh
                arch = self.arch

                def embed_fn(params, t, pos, vl):
                    return arch.embed_forward(
                        params, self.config.model, t, pos, vl
                    )

                repl = NamedSharding(mesh, P())
                prog = jax.jit(
                    embed_fn,
                    in_shardings=(self.param_shardings, repl, repl, repl),
                    out_shardings=repl,
                )
                self._embed_progs[(rows, bucket)] = prog
            with self.compiles.track("embed", f"r{rows}_s{bucket}"):
                vecs = prog(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(valid),
                )
            out[i : i + len(batch)] = np.asarray(vecs)[: len(batch)]
            i += len(batch)
        return out

    def set_sample_row(
        self, slot: int, prompt_ids, generated_ids=(), logit_bias=None,
        guided_mask=None,
    ) -> None:
        """Install sampling state for a slot at admission: prompt presence,
        generated-token counts (non-empty when resuming a preempted
        stream), and the request's OpenAI logit_bias row — plus, for
        guided decoding, the initial token mask (``guided_mask``: dense
        [V] float32 the logit_bias entries add onto)."""
        v = self.config.model.vocab_size
        # defense in depth: the engine rejects out-of-vocab prompts at
        # admission (serving.py), but this state write must never fault
        # the scheduler loop — numpy fancy indexing neither clamps nor
        # drops, so filter
        seen_row = np.zeros(v, bool)
        if len(prompt_ids):
            ids = np.asarray(prompt_ids, np.int64)
            seen_row[ids[(ids >= 0) & (ids < v)]] = True
        counts_row = np.zeros(v, np.int32)
        if len(generated_ids):
            gids = np.asarray(generated_ids, np.int64)
            np.add.at(counts_row, gids[(gids >= 0) & (gids < v)], 1)
        bias_row = (
            np.asarray(guided_mask, np.float32).copy()
            if guided_mask is not None else np.zeros(v, np.float32)
        )
        for tid, b in (logit_bias or {}).items():
            tid = int(tid)
            if 0 <= tid < v:
                bias_row[tid] += float(b)
        with self.compiles.track("sample_row", f"v{v}"):
            self.sample_state = self._set_row_jit(
                self.sample_state[0], self.sample_state[1],
                self.sample_state[2],
                jnp.asarray(slot, jnp.int32), jnp.asarray(counts_row),
                jnp.asarray(seen_row), jnp.asarray(bias_row),
            )

    # ---------- paged-block gather / scatter ----------
    #
    # The KV data-movement primitive behind disaggregated prefill→decode
    # transfer and host-memory offload — the TPU-native role of the
    # reference's CUDA block-copy kernel + NIXL RDMA path (reference:
    # lib/llm/src/kernels/block_copy.cu:40-758, lib/llm/src/kv/layer.rs
    # CopyStream). XLA compiles the gather/scatter over the [L, N, bs, H, D]
    # cache; block counts are bucketed so each bucket compiles once.

    def _build_sample_row(self):
        repl = NamedSharding(self.mesh, P())

        def set_row(counts, seen, bias, slot, counts_row, seen_row, bias_row):
            return (
                counts.at[slot].set(counts_row),
                seen.at[slot].set(seen_row),
                bias.at[slot].set(bias_row),
            )

        self._set_row_jit = jax.jit(
            set_row,
            donate_argnums=(0, 1, 2),
            in_shardings=(self.state_sharding, self.state_sharding,
                          self.state_sharding, repl, repl, repl, repl),
            out_shardings=(self.state_sharding, self.state_sharding,
                           self.state_sharding),
        )

        def set_bias(bias, slot, bias_row):
            return bias.at[slot].set(bias_row)

        # bias-only row update (guided decoding rewrites its mask every
        # step; counts/seen must not be touched mid-stream)
        self._set_bias_jit = jax.jit(
            set_bias,
            donate_argnums=(0,),
            in_shardings=(self.state_sharding, repl, repl),
            out_shardings=self.state_sharding,
        )

        def edit_bias(bias, slot, ids, vals):
            row = bias[slot]
            # pad ids are vocab_size (out of range) → dropped
            row = row.at[ids].set(vals, mode="drop")
            return bias.at[slot].set(row)

        # sparse per-step edits: guided masks change only at a trie
        # node's neighborhood (a handful of ids), not across the vocab —
        # one compiled program per id-count bucket, no [V] H2D per token
        self._edit_bias_jit = jax.jit(
            edit_bias,
            donate_argnums=(0,),
            in_shardings=(self.state_sharding, repl, repl, repl),
            out_shardings=self.state_sharding,
        )

    BIAS_EDIT_BUCKETS = (8, 32, 128)

    def set_bias_row(self, slot: int, bias_row: np.ndarray) -> None:
        """Replace ONE slot's sampler bias row (guided decoding's
        per-step token mask; also carries the request's logit_bias)."""
        counts, seen, bias = self.sample_state
        with self.compiles.track(
            "guided_mask", f"v{self.config.model.vocab_size}"
        ):
            self.sample_state = (
                counts, seen,
                self._set_bias_jit(
                    bias, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(bias_row, jnp.float32),
                ),
            )

    def edit_bias_entries(self, slot: int, ids, vals) -> bool:
        """Sparse update of ONE slot's bias row: ``row[ids] = vals``.

        ids/vals pad to a small static bucket (pad id = vocab_size,
        dropped by the scatter). Returns False when the edit exceeds the
        largest bucket — the caller falls back to set_bias_row."""
        n = len(ids)
        bucket = next(
            (b for b in self.BIAS_EDIT_BUCKETS if n <= b), None
        )
        if bucket is None:
            return False
        v = self.config.model.vocab_size
        ids_p = np.full(bucket, v, np.int32)
        vals_p = np.zeros(bucket, np.float32)
        ids_p[:n] = np.asarray(ids, np.int32)
        vals_p[:n] = np.asarray(vals, np.float32)
        counts, seen, bias = self.sample_state
        with self.compiles.track("guided_mask_edit", f"n{bucket}"):
            self.sample_state = (
                counts, seen,
                self._edit_bias_jit(
                    bias, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(ids_p), jnp.asarray(vals_p),
                ),
            )
        return True

    BLOCK_OP_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

    def _build_block_ops(self):
        repl = NamedSharding(self.mesh, P())
        # transferred blocks use the LOGICAL trailing dims — the cache's
        # lane padding (ops/attention.lane_pad) stays on-device and off
        # the wire; gather slices it away, scatter re-pads with zeros
        cfg = self.config.model
        if getattr(cfg, "kv_lora_rank", 0):
            true_dims = (cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        else:
            true_dims = (cfg.head_dim, cfg.head_dim)
        # the wire layout is always [L, n, bs, H, D]; a pp-staged cache
        # ([P, L/P, N, ...]) flattens its stage axis at the gather and
        # re-splits at the scatter, so disagg transfer / host offload see
        # one format regardless of pipeline layout. Mixed MLA trunks
        # ({"pre", "stg"} sides) flatten with prefix layers leading —
        # the same order deepseek.forward runs them.
        staged = self.config.pp_size > 1
        n_pre = self._pp_prefix_layers
        bs = self.config.kv_block_size

        def wire(blocks):
            # [L, n, <cache's own page dims>, D] -> [L, n, bs, H, D]: a
            # no-op for the per-head caches; the latent cache keeps its
            # one head in front of the page (deepseek.init_kv_cache)
            return blocks.reshape(
                blocks.shape[:2] + (bs, -1, blocks.shape[-1]))

        def gather(k_cache, v_cache, ids):
            # per-slab indexing: only the GATHERED blocks concatenate,
            # never the full cache (a {"pre","stg"} concat would move
            # the whole cache per 64-block bucket)
            def g(c, dim):
                if isinstance(c, dict):
                    stg = c["stg"].reshape(-1, *c["stg"].shape[2:])
                    return wire(jnp.concatenate(
                        [c["pre"][:, ids, ..., :dim],
                         stg[:, ids, ..., :dim]], axis=0,
                    ))
                if staged:
                    c = c.reshape(-1, *c.shape[2:])
                return wire(c[:, ids, ..., :dim])

            return g(k_cache, true_dims[0]), g(v_cache, true_dims[1])

        self._gather_jit = jax.jit(
            gather,
            in_shardings=(self.cache_sharding, self.cache_sharding, repl),
            out_shardings=(repl, repl),
        )

        def scatter(k_cache, v_cache, ids, k_blocks, v_blocks):
            def sc(c, blocks):
                page = (c["pre"] if isinstance(c, dict) else c).shape[-3:-1]
                blocks = blocks.reshape(
                    blocks.shape[:2] + page + blocks.shape[-1:])
                if isinstance(c, dict):
                    blocks = pad_minor(blocks, c["pre"].shape[-1])
                    blocks = blocks.astype(c["pre"].dtype)
                    stg_shape = c["stg"].shape
                    stg = c["stg"].reshape(-1, *stg_shape[2:])
                    return {
                        # per-slab scatter: blocks split on the layer
                        # axis (prefix layers lead the wire layout)
                        "pre": c["pre"].at[:, ids].set(blocks[:n_pre]),
                        "stg": stg.at[:, ids].set(blocks[n_pre:])
                        .reshape(stg_shape),
                    }
                blocks = pad_minor(blocks, c.shape[-1]).astype(c.dtype)
                if staged:
                    shape = c.shape
                    c = c.reshape(-1, *shape[2:])
                    return c.at[:, ids].set(blocks).reshape(shape)
                return c.at[:, ids].set(blocks)

            return sc(k_cache, k_blocks), sc(v_cache, v_blocks)

        self._scatter_jit = jax.jit(
            scatter,
            donate_argnums=(0, 1),
            in_shardings=(self.cache_sharding, self.cache_sharding, repl, repl, repl),
            out_shardings=(self.cache_sharding, self.cache_sharding),
        )

    def _bucket_ids(self, n: int) -> int:
        for b in self.BLOCK_OP_BUCKETS:
            if n <= b:
                return b
        return self.BLOCK_OP_BUCKETS[-1]

    def gather_blocks(self, block_ids) -> Tuple[np.ndarray, np.ndarray]:
        """Read KV blocks out of HBM → host arrays [L, n, bs, KVH, D] ×2."""
        return self.blocks_to_host(*self.gather_blocks_device(block_ids))

    @staticmethod
    def blocks_to_host(k_dev, v_dev) -> Tuple[np.ndarray, np.ndarray]:
        """Host-sync one gathered (k, v) block frame.

        The blocking half of the streamed-transfer split: callers on an
        event loop dispatch ``gather_blocks_device`` inline (cheap, and it
        must serialize with ``step``'s donated cache buffers) and run this
        device→host copy in an executor, so the wire pump never stalls the
        loop (disagg/prefill_worker.py's bounded per-chunk frames).
        """
        return np.asarray(jax.device_get(k_dev)), np.asarray(jax.device_get(v_dev))

    def gather_blocks_device(self, block_ids):
        """Read KV blocks as DEVICE arrays [L, n, bs, KVH, D] ×2.

        Same bucketed gather as gather_blocks without the host round-trip.
        Dispatch-only (no host sync): feeds the collective transfer plane
        (disagg/ici_transfer.py, HBM→HBM — must never bounce through
        numpy) and the streamed prefill pipeline's chunk-sized frames,
        which pair it with ``blocks_to_host`` off-loop.
        """
        self.refuse_without_state("migration")
        ids = list(block_ids)
        ks, vs = [], []
        i = 0
        while i < len(ids):
            chunk = ids[i : i + self.BLOCK_OP_BUCKETS[-1]]
            bucket = self._bucket_ids(len(chunk))
            padded = chunk + [chunk[-1]] * (bucket - len(chunk))
            with self.compiles.track("kv_gather", f"n{bucket}"):
                k, v = self._gather_jit(
                    self.kv_cache[0], self.kv_cache[1],
                    jnp.asarray(padded, jnp.int32)
                )
            ks.append(k[:, : len(chunk)])
            vs.append(v[:, : len(chunk)])
            i += len(chunk)
        if len(ks) == 1:
            return ks[0], vs[0]
        return jnp.concatenate(ks, axis=1), jnp.concatenate(vs, axis=1)

    def scatter_blocks(self, block_ids, k_blocks, v_blocks) -> None:
        """Write KV block data [L, n, bs, KVH, D] into HBM cache slots.

        Accepts numpy OR already-device-resident jax arrays (callers that
        must not block the event loop stage with ``jax.device_put`` first).
        """
        self.refuse_without_state("migration")
        ids = list(block_ids)
        assert k_blocks.shape[1] == len(ids), (k_blocks.shape, len(ids))
        kb_all = jnp.asarray(k_blocks)
        vb_all = jnp.asarray(v_blocks)
        i = 0
        while i < len(ids):
            chunk = ids[i : i + self.BLOCK_OP_BUCKETS[-1]]
            bucket = self._bucket_ids(len(chunk))
            pad = bucket - len(chunk)
            padded_ids = chunk + [chunk[-1]] * pad
            kb = kb_all[:, i : i + len(chunk)]
            vb = vb_all[:, i : i + len(chunk)]
            if pad:
                # duplicate the last block's data for the repeated pad ids —
                # identical values land on the same slot, so order is benign
                kb = jnp.concatenate([kb, jnp.repeat(kb[:, -1:], pad, axis=1)], axis=1)
                vb = jnp.concatenate([vb, jnp.repeat(vb[:, -1:], pad, axis=1)], axis=1)
            with self.compiles.track("kv_scatter", f"n{bucket}"):
                k, v = self._scatter_jit(
                    self.kv_cache[0], self.kv_cache[1],
                    jnp.asarray(padded_ids, jnp.int32), kb, vb,
                )
            self.kv_cache = (k, v)
            i += len(chunk)

    def _init_device_state(self) -> None:
        """Build the donated device state: the paged KV cache and the
        per-slot sampling state (generated-token counts, prompt presence,
        OpenAI logit_bias rows — [num_slots, vocab]; see engine/sampling.py).
        Jitted onto its shardings, so no device ever holds more than its
        own shard (an eager ``jnp.zeros`` lands whole on device 0)."""
        cfg = self.config

        def make():
            cache = tuple(self.arch.init_kv_cache(
                cfg.model, cfg.num_kv_blocks, cfg.kv_block_size,
                self.kv_dtype, num_slots=cfg.max_batch_size,
                window_blocks=cfg.window_pool_pages(),
                max_len=cfg.max_model_len,
            ))
            if cfg.pp_size > 1:
                from ..parallel.pipeline import stage_cache

                cache = stage_cache(cache, cfg.pp_size,
                                    prefix_layers=self._pp_prefix_layers)
            b, v = cfg.max_batch_size, cfg.model.vocab_size
            return cache, (jnp.zeros((b, v), jnp.int32),
                           jnp.zeros((b, v), jnp.bool_),
                           jnp.zeros((b, v), jnp.float32))

        self.kv_cache, self.sample_state = jax.jit(make, out_shardings=(
            (self.cache_sharding,) * 2, (self.state_sharding,) * 3,
        ))()

    def table_width(self, program: str, nblocks: int) -> int:
        """The block-table width of a dispatch of the decode-shaped
        ``program`` (``decode``, ``verify``, ``decode_block``,
        ``decode_burst``, ``decode_burst_df``, ``decode_burst_spec``)
        whose longest row holds ``nblocks`` blocks.

        A program whose trace read the table at its width (an XLA
        gather, block selection: ``width_programs``) pays for every
        entry, so it exists at each rung of
        ``EngineConfig.kv_width_buckets`` and gets the smallest that
        covers the batch. A program whose kernels walk live pages does
        the same work at any width and exists at the full one only; so
        does a program not yet traced, whose first dispatch, at the full
        width, is what decides."""
        if program in self.width_programs:
            return self.config.kv_width_bucket(nblocks)
        return self.config.blocks_per_seq

    def _warm_widths(self, program: str):
        """The widths warm-up dispatches ``program`` at, the caller
        dispatching between two: the full width first, then the narrower
        rungs if that trace read the table at its width. Notes in
        ``warmed_widths`` what it yielded and why."""
        full = self.config.blocks_per_seq
        yield full
        reads = program in self.width_programs
        rest = ([w for w in self.config.kv_width_buckets() if w != full]
                if reads else [])
        yield from rest
        self.warmed_widths[program] = {
            "widths": rest + [full],
            "why": "gather traced" if reads else "kernel walk"}

    def warmup(self, decode_batch: Optional[int] = None) -> None:
        """Compile every serving program up front: each decode-shaped
        program at every block-table width the scheduler can ask for
        (``_warm_widths``: the full width, and the narrower rungs of
        ``EngineConfig.kv_width_buckets`` only where the program's trace
        read the table at its width), and the prefill program per (row
        bucket, length bucket) the scheduler can pick.

        The scheduler sizes decode block tables with ``table_width`` and
        prefill steps with prefill_row_bucket x bucket_for, so serving
        touches ladders of shapes; compiling them here keeps
        multi-ten-second TPU compiles out of the first requests' latency
        (the analog of GPU engines' startup capture sweeps).

        A program that fails to compile — a Pallas kernel Mosaic
        rejects at this model's shapes — raises here with the
        compiler's message and the engine does not start. There is no
        path from a compile error to another attention route:
        ``attention_impl="xla"`` is the operator's explicit choice.
        """
        # warm-up's phase starts here: what lies between the runner's
        # return and this (the scheduler and the engine built around it)
        # is the phase ``engine``
        self.startup.mark("engine")
        first_dispatches = len(self.compiles.records)
        b = decode_batch or self.config.max_batch_size
        # the sample-row install program is shape-invariant and otherwise
        # compiles at the FIRST admission — a needless late compile on
        # the first real request (flagged by the CompileTracker; writing
        # zero rows to slot 0 is inert, admission overwrites them)
        self.set_sample_row(0, [])
        zeros2 = np.zeros((b, 1), np.int32)
        if self.unit is not None:
            # the block pass is the family's one decode program (inert:
            # every slot is the drop sentinel, every quota 0)
            zb = np.zeros((b, 2 * self.unit.length), np.int32)
            inert = (np.full_like(zb, -1), np.ones(b, np.int32),
                     np.zeros(b, np.int32), np.zeros(b, np.float32),
                     np.zeros(b, np.int32), np.ones(b, np.float32))
            for w in self._warm_widths("decode_block"):
                ids, *_ = self.decode_block(
                    zb, zb, np.zeros((b, w), np.int32), *inert)
            # once more fed with its own output, as the scheduler feeds it
            # a pass ahead (the same program, as the decode step's below)
            self.decode_block(
                zb, zb, np.zeros((b, self.config.blocks_per_seq), np.int32),
                *inert, prev_ids=ids)
        fed = None
        for w in self._warm_widths("decode") if self.unit is None else ():
            fed, *_ = self.step(
                zeros2, zeros2, np.zeros((b, w), np.int32),
                np.full((b, 1), -1, np.int32),
                np.ones(b, np.int32), np.zeros(b, np.int32),
                np.zeros(b, np.float32), np.zeros(b, np.int32),
                np.ones(b, np.float32),
                jax.random.PRNGKey(0),
            )
        if fed is not None:
            # once more fed with its own output, as the scheduler feeds it
            # a step ahead (the same program: no first dispatch, and the
            # call's fast path has seen both kinds of argument)
            self.step(
                zeros2, zeros2,
                np.zeros((b, self.config.blocks_per_seq), np.int32),
                np.full((b, 1), -1, np.int32),
                np.ones(b, np.int32), np.zeros(b, np.int32),
                np.zeros(b, np.float32), np.zeros(b, np.int32),
                np.ones(b, np.float32),
                jax.random.PRNGKey(0), prev_tokens=fed,
            )
        # the fused multi-step decode program at its widths (inert rows:
        # commit all-False writes nothing and samples noise)
        if self._burst is not None:
            z1 = np.zeros(b, np.int32)
            for w in self._warm_widths("decode_burst"):
                self.decode_burst(
                    z1, z1, np.zeros((b, w), np.int32),
                    np.zeros(b, np.float32), z1, np.ones(b, np.float32),
                    min_p=np.zeros(b, np.float32),
                    presence_penalty=np.zeros(b, np.float32),
                    frequency_penalty=np.zeros(b, np.float32),
                    repetition_penalty=np.ones(b, np.float32),
                    seed_keys=np.zeros((b, 2), np.uint32), counters=z1,
                    commit=np.zeros(b, bool), want_top=False,
                )
        # the device-finish burst variant at its widths (inert:
        # commit all-False, so no row writes KV or counts); compiling it
        # here keeps the persistent loop's first chain off the late-
        # compile path exactly like the plain burst above
        if getattr(self, "_burst_df", None) is not None:
            from .sampling import STOP_ID_WIDTH

            z1 = np.zeros(b, np.int32)
            for w in self._warm_widths("decode_burst_df"):
                self.decode_burst_chained(
                    z1, z1, z1, np.zeros(b, bool),
                    np.zeros((b, w), np.int32),
                    np.zeros(b, np.float32), z1, np.ones(b, np.float32),
                    min_p=np.zeros(b, np.float32),
                    presence_penalty=np.zeros(b, np.float32),
                    frequency_penalty=np.zeros(b, np.float32),
                    repetition_penalty=np.ones(b, np.float32),
                    seed_keys=np.zeros((b, 2), np.uint32),
                    commit=np.zeros(b, bool),
                    stop_ids=np.full((b, STOP_ID_WIDTH), -1, np.int32),
                    min_new=z1, max_new=np.full(b, 1, np.int32),
                    want_top=False,
                )
        # the chained propose-verify round (spec state in the burst
        # carry) at its widths; inert like the burst warmups
        if self._spec_ngram is not None or self._spec_verify is not None:
            from .sampling import (
                STOP_ID_WIDTH,
                STOP_SEQ_WIDTH,
                SUFFIX_RING_W,
            )

            z1 = np.zeros(b, np.int32)
            K = self._spec_k
            for w in self._warm_widths("decode_burst_spec"):
                self.decode_burst_spec(
                    z1, z1, z1, np.zeros(b, bool),
                    np.full((b, SUFFIX_RING_W), -1, np.int32),
                    np.full(b, -1, np.int32),
                    np.zeros((b, w), np.int32),
                    commit=np.zeros(b, bool),
                    stop_ids=np.full((b, STOP_ID_WIDTH), -1, np.int32),
                    min_new=z1, max_new=np.full(b, 1, np.int32),
                    stop_hash=np.zeros((b, STOP_SEQ_WIDTH), np.uint32),
                    stop_hlen=np.zeros((b, STOP_SEQ_WIDTH), np.int32),
                    proposals=(
                        None if self._spec_verify is None
                        else np.full((b, K), -1, np.int32)
                    ),
                )
        # the ngram-speculative verify shape (S = K+1 on decode-width
        # tables) at its widths
        if self.config.spec_ngram_tokens:
            sK = self.config.spec_ngram_tokens + 1
            zs = np.zeros((b, sK), np.int32)
            for w in self._warm_widths("verify"):
                self.step(
                    zs, zs, np.zeros((b, w), np.int32),
                    np.full((b, sK), -1, np.int32),
                    np.ones(b, np.int32), np.zeros(b, np.int32),
                    np.zeros(b, np.float32), np.zeros(b, np.int32),
                    np.ones(b, np.float32),
                    jax.random.PRNGKey(0), want_greedy=True,
                )
        # the sequence-parallel prefill program (ONE compiled shape):
        # inert dispatch — every slot is the drop sentinel, commit is
        # False — so the long-context admission class never pays its
        # multi-second compile on the first real 128k prompt
        if getattr(self, "_sp_prefill", None) is not None:
            S_sp, w_sp = self._sp_bucket, self._sp_width
            repl_tok = np.zeros((1, S_sp), np.int32)
            with self.compiles.track("prefill_sp", f"s{S_sp}_w{w_sp}"):
                outs_sp = self._sp_prefill(
                    self.params, self.kv_cache[0], self.kv_cache[1],
                    self.sample_state[0], self.sample_state[1],
                    self.sample_state[2],
                    jnp.asarray(repl_tok), jnp.asarray(repl_tok),
                    jnp.asarray(np.zeros((1, w_sp), np.int32)),
                    jnp.asarray(np.full((1, S_sp), -1, np.int32)),
                    jnp.asarray([1], jnp.int32), jnp.asarray(0, jnp.int32),
                    jnp.asarray([0], jnp.int32),
                    SamplingParams(
                        temperature=jnp.zeros(1, jnp.float32),
                        top_k=jnp.zeros(1, jnp.int32),
                        top_p=jnp.ones(1, jnp.float32),
                        min_p=jnp.zeros(1, jnp.float32),
                        presence_penalty=jnp.zeros(1, jnp.float32),
                        frequency_penalty=jnp.zeros(1, jnp.float32),
                        repetition_penalty=jnp.ones(1, jnp.float32),
                        keys=jnp.zeros((1, 2), jnp.uint32),
                        counters=jnp.zeros(1, jnp.int32),
                    ),
                    jnp.asarray([0], jnp.int32),
                    jnp.asarray([False], jnp.bool_),
                    jnp.asarray(False, jnp.bool_),
                )
            # the inert dispatch consumed the donated cache/state buffers
            # — adopt the returned ones (values unchanged: drop-sentinel
            # slots wrote nothing, commit=False counted nothing)
            self.kv_cache = (outs_sp[4], outs_sp[5])
            self.sample_state = (outs_sp[6], outs_sp[7], outs_sp[8])
        # every prefill-shaped program the scheduler can dispatch: the
        # batched-prefill row ladder x the length buckets within the
        # per-step token budget (scheduler.prefill_bucket_cap), at full
        # table width — so the flash-prefill kernel's compiles happen,
        # and fail, here rather than on the first real prompt burst
        from .scheduler import prefill_bucket_cap

        w = self.config.blocks_per_seq
        buckets = self.config.prefill_buckets
        rows = self.config.prefill_row_buckets()
        for r in rows:
            cap = prefill_bucket_cap(self.config, r)
            if cap is None:
                # over budget even at the smallest bucket: the scheduler
                # sheds rows down to one, which still advances there
                cap = buckets[0] if r == rows[0] else 0
            for s in (b for b in buckets if b <= cap):
                self.step(
                    np.zeros((r, s), np.int32), np.zeros((r, s), np.int32),
                    np.zeros((r, w), np.int32), np.full((r, s), -1, np.int32),
                    np.ones(r, np.int32), np.zeros(r, np.int32),
                    np.zeros(r, np.float32), np.zeros(r, np.int32),
                    np.ones(r, np.float32),
                    jax.random.PRNGKey(0),
                )
        # every program has executed once when warm-up is over: the
        # gauge then holds compile (or cache load) plus first execution
        jax.block_until_ready((self.kv_cache, self.sample_state))
        self.startup.mark("warmup")
        # what no first dispatch holds: the wait above for the executions
        # queued behind the dispatches, and the host loops between them
        dispatched = sum(r["duration_s"]
                         for r in self.compiles.records[first_dispatches:])
        self.startup.within(
            "warmup_wait", max(0.0, self.startup_s["warmup"] - dispatched))
        # what the decode kernels' walk was sized to at this model's
        # pages (ops/pallas_decode.chunk_pages: static a configuration)
        from ..ops.pallas_decode import chunks_traced

        chunks = chunks_traced()
        if chunks:
            logger.info("decode kernels' chunks: %s", json.dumps(chunks))
        # and what the state kernel's body was given at this model's
        # heads (ops/ssm.ssm_decode_step: heads a tile, tiles a block)
        from ..ops.ssm import blocks_traced

        blocks = blocks_traced()
        if blocks:
            logger.info("state kernel's blocks: %s", json.dumps(blocks))
        logger.info("decode programs' table widths: %s",
                    json.dumps(self.warmed_widths))
