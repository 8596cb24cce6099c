"""JaxServingEngine: the AsyncEngine facade over runner + scheduler.

The token-level engine that slots into the pipeline where the reference
plugged vLLM/SGLang (reference: lib/llm/src/engines.rs ExecutionContext —
PreprocessedRequest in, streamed EngineOutput deltas out).
"""

from __future__ import annotations

import asyncio
import json
import logging
import uuid
from typing import Any, AsyncIterator, Optional

from ..protocols.common import EngineOutput, PreprocessedRequest
from ..runtime.engine import AsyncEngine, Context, EngineError
from .block_allocator import KvEventSink
from .config import EngineConfig, ModelConfig
from .device import device_report
from .model_runner import ModelRunner
from .scheduler import EngineRequest, Scheduler

logger = logging.getLogger(__name__)


def engine_config_from_mdc(mdc, flags=None, extra=None) -> EngineConfig:
    """The one place MDC + CLI flags become an EngineConfig.

    Shared by decode engines and prefill workers — block geometry MUST match
    across disaggregated workers or transferred KV lands in the wrong slots.

    ``extra`` is the ``--extra-engine-args`` JSON passthrough (reference:
    dynamo-run flags.rs:175): keys naming ModelConfig fields override the
    model config (e.g. ``attention_impl``), keys naming EngineConfig
    fields override the engine config; unknown keys are rejected loudly.
    """
    import dataclasses

    model_cfg = ModelConfig.from_hf_config(mdc.config) if mdc.config else ModelConfig()
    if getattr(flags, "quantization", None):
        model_cfg.quantization = flags.quantization
    if extra is None:
        extra = load_extra_engine_args(flags)
    extra = dict(extra or {})
    model_extra = {}
    engine_extra = {}
    model_fields = {f.name for f in dataclasses.fields(ModelConfig)}
    engine_fields = {f.name for f in dataclasses.fields(EngineConfig)}
    for key, value in extra.items():
        if key in model_fields:
            model_extra[key] = value
        elif key in engine_fields and key != "model":
            engine_extra[key] = value
        else:
            raise ValueError(
                f"--extra-engine-args key {key!r} matches no ModelConfig or "
                f"EngineConfig field"
            )
    if model_extra:
        # replace (not setattr) so __post_init__ re-validates/derives —
        # e.g. kv_lora_rank without the MLA head dims must fail loudly
        model_cfg = dataclasses.replace(model_cfg, **model_extra)
    return _apply_engine_extra(engine_extra, EngineConfig(
        model=model_cfg,
        max_batch_size=getattr(flags, "max_batch_size", 8),
        max_model_len=getattr(flags, "max_model_len", None)
        or min(mdc.context_length, model_cfg.max_position_embeddings),
        kv_block_size=mdc.kv_block_size,
        tp_size=getattr(flags, "tensor_parallel_size", 1),
        ep_size=getattr(flags, "expert_parallel_size", 1),
        dp_size=getattr(flags, "data_parallel_size", 1),
        pp_size=getattr(flags, "pipeline_parallel_size", 1),
        # sequence-parallel long-context prefill (docs/long_context.md)
        sp_size=getattr(flags, "sequence_parallel_size", 1) or 1,
        long_prefill_threshold_tokens=getattr(
            flags, "long_prefill_threshold_tokens", 0) or 0,
        host_kv_blocks=getattr(flags, "host_kv_blocks", 0) or 0,
        num_kv_blocks=getattr(flags, "num_kv_blocks", None) or 2048,
        multi_step_decode=getattr(flags, "multi_step_decode", 1) or 1,
        decode_pipeline_depth=getattr(flags, "decode_pipeline_depth", 1) or 1,
        # no `or 2` fallback: an explicit 0 must clamp to 1 (serial), not
        # silently flip back to double-buffered
        disagg_stream_depth=(
            2 if getattr(flags, "disagg_stream_depth", None) is None
            else flags.disagg_stream_depth
        ),
        spec_ngram_tokens=getattr(flags, "spec_ngram_tokens", 0) or 0,
        spec_ngram_match=getattr(flags, "spec_ngram_match", 3) or 3,
        # unrestricted chain (docs/performance.md): guided device tables
        guided_device_table=not getattr(
            flags, "no_guided_device_table", False),
        guided_table_max_states=getattr(
            flags, "guided_table_max_states", 256) or 256,
        # no `or` fallback: an explicit 0 must DISABLE the watchdog, not
        # silently restore the default deadline
        watchdog_stall_s=(
            30.0 if getattr(flags, "watchdog_stall_s", None) is None
            else flags.watchdog_stall_s
        ),
        spec_draft_model=getattr(flags, "spec_draft_model", None),
        spec_draft_tokens=getattr(flags, "spec_draft_tokens", 0) or 0,
        allow_random_weights=getattr(flags, "allow_random_weights", False),
        kv_cache_dtype=getattr(flags, "kv_cache_dtype", "auto") or "auto",
        # cluster KV fabric (kv/fabric.py): cross-worker prefix pull +
        # the content-addressed cold tier
        prefix_pull=getattr(flags, "prefix_pull", False),
        prefix_pull_min_blocks=getattr(
            flags, "prefix_pull_min_blocks", 2) or 2,
        prefix_pull_timeout_s=getattr(
            flags, "prefix_pull_timeout_s", 30.0) or 30.0,
        cold_tier_dir=getattr(flags, "cold_tier_dir", "") or "",
        cold_tier_blocks=getattr(flags, "cold_tier_blocks", 0) or 0,
    ))


def load_extra_engine_args(flags) -> dict:
    """--extra-engine-args <file.json> → dict (reference: dynamo-run's
    JSON passthrough, flags.rs:175). The ONE parse site — the CLI's
    python-file engine path reuses it."""
    path = getattr(flags, "extra_engine_args", None)
    if not path:
        return {}
    with open(path) as f:
        return json.load(f)


def _apply_engine_extra(extra: dict, cfg: EngineConfig) -> EngineConfig:
    """Apply --extra-engine-args EngineConfig overrides after construction.

    dataclasses.replace re-runs __post_init__, but the bucket derivation
    only fires when prefill_buckets is None — so a max_model_len override
    without an explicit bucket list must drop the already-derived buckets
    or the new length would keep the old (possibly too-short) ladder."""
    if not extra:
        return cfg
    import dataclasses

    if "max_model_len" in extra and "prefill_buckets" not in extra:
        extra = dict(extra, prefill_buckets=None)
    return dataclasses.replace(cfg, **extra)


def build_draft_config(target: EngineConfig) -> EngineConfig:
    """EngineConfig for the draft model of draft-speculative decoding.

    The draft's paged cache MIRRORS the target's block ids (same
    allocator decisions drive both), so block geometry must match
    exactly; the draft always runs unsharded (it is small by
    construction) with its K-step fused burst as the proposal program.
    """
    import dataclasses

    draft_model = ModelConfig.from_model_dir(target.spec_draft_model)
    if draft_model.vocab_size != target.model.vocab_size:
        # smaller: target ids are out of range for the draft. LARGER is
        # just as bad in the other direction — the draft can propose ids
        # the target's embedding gather clamps and the verify step never
        # accepts, silently wasting every speculation round.
        raise ValueError(
            f"draft vocab {draft_model.vocab_size} != target "
            f"{target.model.vocab_size}: the two must share a tokenizer "
            "(out-of-range ids are either invalid for the draft or "
            "never-accepted noise for the target)"
        )
    if draft_model.max_position_embeddings < target.max_model_len:
        raise ValueError(
            f"draft max_position_embeddings "
            f"{draft_model.max_position_embeddings} < target max_model_len "
            f"{target.max_model_len}: past its rope range the draft's "
            "proposals degrade to noise and every round pays for nothing"
        )
    return dataclasses.replace(
        target,
        model=draft_model,
        spec_draft_model=None, spec_draft_tokens=0,  # no recursion
        tp_size=1, dp_size=1, ep_size=1, pp_size=1,
        # K+1 burst steps for K proposals: the extra step writes the
        # K-th proposal's KV into the mirror cache, so a fully-accepted
        # round leaves no draft-KV hole behind the new context
        multi_step_decode=target.spec_draft_tokens + 1,
    )


def _refuse_for_block_unit(req: PreprocessedRequest, unit) -> None:
    """A family whose decode unit is a block (models.BlockUnit) refuses,
    by name, the request options that assume one token a row a pass
    (``unit.refused``); the HTTP edge answers 400."""
    if unit is None:
        return
    so = req.sampling_options
    asked = (
        ("presence_penalty", bool(so.presence_penalty)),
        ("frequency_penalty", bool(so.frequency_penalty)),
        ("repetition_penalty",
         so.repetition_penalty not in (None, 0, 1, 1.0)),
        ("guided_decoding", bool(so.guided_json or so.guided_choice_token_ids
                                 or so.guided_choice)),
        ("logit_bias", bool(so.logit_bias)),
        ("prompt_logprobs", req.output_options.prompt_logprobs is not None),
    )
    for name, on in asked:
        if on and name in unit.refused:
            raise EngineError(
                f"{name} is refused for a model whose decode unit is a "
                f"block of {unit.length} positions: {unit.refused[name]}")


class JaxServingEngine(AsyncEngine):
    def __init__(self, runner: ModelRunner, scheduler: Scheduler, config: EngineConfig):
        self.runner = runner
        self.scheduler = scheduler
        self.config = config
        # stall watchdog (telemetry/watchdog.py), attached by create();
        # held here so close() can cancel its task
        self.watchdog = None
        # guided JSON: grammars (and the vocab piece table they share)
        # are compiled once per distinct spec and reused across requests
        self._model_path: Optional[str] = None
        self._pieces = None
        self._json_grammars: dict = {}

    @classmethod
    async def create(
        cls,
        mdc,
        flags=None,
        engine_config: Optional[EngineConfig] = None,
        params=None,
        events: Optional[KvEventSink] = None,
        mesh=None,
        warmup: bool = True,
        disagg_factory=None,
    ) -> "JaxServingEngine":
        """Build from a ModelDeploymentCard (+CLI flags or explicit config).

        ``disagg_factory(runner) -> RemotePrefillCoordinator`` enables
        conditional remote prefill (disaggregated serving) on this engine.
        """
        if engine_config is None:
            engine_config = engine_config_from_mdc(mdc, flags)
        loop = asyncio.get_running_loop()
        runner_fut = loop.run_in_executor(
            None,
            lambda: ModelRunner(engine_config, params=params, mesh=mesh,
                                model_dir=mdc.model_path),
        )
        draft_runner = None
        if engine_config.spec_draft_model:
            # target and draft builds share nothing — load concurrently
            draft_config = build_draft_config(engine_config)
            draft_fut = loop.run_in_executor(
                None,
                lambda: ModelRunner(
                    draft_config, model_dir=engine_config.spec_draft_model
                ),
            )
            runner, draft_runner = await asyncio.gather(runner_fut, draft_fut)
        else:
            runner = await runner_fut
        disagg = None
        if disagg_factory is not None:
            if draft_runner is not None:
                raise ValueError(
                    "spec_draft_model is incompatible with disaggregated "
                    "remote prefill: remotely-computed KV never passes "
                    "through the draft model, so its mirror cache would "
                    "be stale for every remote-prefilled request"
                )
            disagg = await disagg_factory(runner)
        scheduler = Scheduler(runner, engine_config, events, disagg=disagg,
                              draft_runner=draft_runner)
        engine = cls(runner, scheduler, engine_config)
        engine._model_path = mdc.model_path  # guided-JSON piece table
        if warmup:
            futs = [loop.run_in_executor(None, runner.warmup)]
            if draft_runner is not None:
                futs.append(loop.run_in_executor(None, draft_runner.warmup))
            await asyncio.gather(*futs)
        # the one line that says what this engine came up on — parsed by
        # chip_smoke.py; bytes_in_use is after warmup, so it is what
        # params + cache + compiled programs really hold on each device
        logger.info("engine device: %s", json.dumps(device_report(runner.mesh)))
        scheduler.start()
        # and the one that says where its start went: the timeline from
        # the package's import to here, every first dispatch in its parts
        # (an HTTP frontend adds ``listening`` and writes the same record
        # to the DYN_TRACE_JSONL sink: cli/run.run_http)
        logger.info("engine start-up: %s", json.dumps(runner.startup.record()))
        if engine_config.watchdog_stall_s > 0:
            from ..telemetry.watchdog import StallWatchdog

            # registered into the scheduler's registry so the trip
            # counter and loop-lag gauge render in the engine scrape;
            # registered as a dump source so GET /debug/flight and
            # SIGUSR2 include this engine's probe + request table
            engine.watchdog = StallWatchdog(
                probe=scheduler.watchdog_probe,
                requests=scheduler.request_table,
                registry=scheduler.registry,
                flight=scheduler.flight,
                interval_s=engine_config.watchdog_interval_s,
                stall_s=engine_config.watchdog_stall_s,
            ).start()
        return engine

    async def generate(self, request: Context[Any]) -> AsyncIterator[dict]:
        if self.scheduler.draining or self.scheduler._stopping:
            # a draining engine's admission is gated, and its extraction
            # pass has (or will have) already run — a request queued now
            # would sit in a seized scheduler forever. Fail fast with the
            # retryable subclass (HTTP edge → 503 + Retry-After).
            from ..runtime.engine import EngineDrainingError

            raise EngineDrainingError(
                "engine is draining (recovery or rolling update); "
                "retry against the worker pool"
            )
        payload = request.payload
        req = (
            payload
            if isinstance(payload, PreprocessedRequest)
            else PreprocessedRequest.from_wire(payload)
        )
        if not req.token_ids:
            raise EngineError("empty prompt")
        if len(req.token_ids) >= self.config.max_model_len:
            raise EngineError(
                f"prompt length {len(req.token_ids)} exceeds engine max_model_len "
                f"{self.config.max_model_len}"
            )
        # token-id prompts arrive unvalidated from /v1/completions; an
        # out-of-range id would fault deep inside the scheduler's penalty
        # state (numpy fancy indexing) and kill the engine loop for
        # everyone — reject HERE, per request
        vocab = self.config.model.vocab_size
        bad = next(
            (t for t in req.token_ids if not 0 <= int(t) < vocab), None
        )
        if bad is not None:
            raise EngineError(
                f"prompt token id {bad} outside vocab [0, {vocab})"
            )
        _refuse_for_block_unit(req, self.scheduler.unit)
        n = req.sampling_options.n
        if n is not None and n > 1:
            # engine-level n>1 fan-out: each choice becomes an
            # INDEPENDENT scheduler request (n=1, seed offset by choice
            # index — the preprocessor's _child_request convention), so
            # every choice is an ordinary device-checkable row the
            # persistent chain serves like any other; the choice-fold
            # happens here at drain, each delta tagged with its
            # EngineOutput.choice index.
            if n > 20:  # OpenAI's cap; also bounds the fan-out
                raise EngineError("n must be <= 20")
            async for out in self._generate_fanout(request, req, n):
                yield out
            return
        if (req.stop_conditions.max_tokens == 0
                and req.output_options.prompt_logprobs is None):
            # an empty completion: nothing to schedule, finish immediately
            # (AFTER the validation above — unsupported shapes must reject
            # consistently regardless of max_tokens). Prompt-SCORING
            # requests (prompt_logprobs + max_tokens=0, the OpenAI
            # echo+logprobs idiom) do schedule: the prefill must run for
            # its logits even though no token is generated.
            from ..protocols.common import EngineOutput, FinishReason

            yield EngineOutput(
                token_ids=[], finish_reason=FinishReason.LENGTH
            ).to_wire()
            return
        guided = None
        if req.sampling_options.guided_json:
            guided = await self._json_constraint(
                req.sampling_options.guided_json
            )
        er = EngineRequest(
            request_id=request.id or uuid.uuid4().hex,
            prompt=list(req.token_ids),
            req=req,
            ctx=request.context,
            out_queue=asyncio.Queue(),
            guided=guided,
        )
        self.scheduler.add_request(er)
        try:
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return
                yield out.to_wire()
        finally:
            # consumer went away (stop/kill/break) — scheduler will reap it
            request.context.stop_generating()

    async def _generate_fanout(self, request: Context[Any],
                               req: PreprocessedRequest, n: int):
        """n>1 as n independent n=1 scheduler requests sharing the
        caller's cancellation context; deltas interleave in completion
        order, each stamped with its choice index, and the stream ends
        when every choice's sentinel arrived."""
        import dataclasses as _dc

        from ..runtime.engine import AsyncEngineContext

        base_seed = req.sampling_options.seed
        # per-choice child contexts (the preprocessor fan-out's
        # convention): cancellation isolation per choice, spans folded
        # back into the parent trace with #<choice> suffixes
        child_ctxs = [
            AsyncEngineContext(trace_id=request.context.trace_id)
            for _ in range(n)
        ]

        async def relay_stop() -> None:
            await request.context.wait_stopped()
            for c in child_ctxs:
                c.stop_generating()

        relay = asyncio.ensure_future(relay_stop())
        children = []
        base_id = request.id or uuid.uuid4().hex
        for i in range(n):
            child_req = _dc.replace(
                req,
                sampling_options=_dc.replace(
                    req.sampling_options, n=1,
                    seed=(base_seed + i) if base_seed is not None else None,
                ),
            )
            er = EngineRequest(
                request_id=f"{base_id}#{i}",
                prompt=list(req.token_ids),
                req=child_req,
                ctx=child_ctxs[i],
                out_queue=asyncio.Queue(),
                guided=(
                    await self._json_constraint(
                        req.sampling_options.guided_json)
                    if req.sampling_options.guided_json else None
                ),
            )
            children.append(er)
        merged: asyncio.Queue = asyncio.Queue()

        async def pump(i: int, er: EngineRequest):
            while True:
                out = await er.out_queue.get()
                await merged.put((i, out))
                if out is None:
                    return

        tasks = [asyncio.ensure_future(pump(i, er))
                 for i, er in enumerate(children)]
        for er in children:
            self.scheduler.add_request(er)
        open_choices = n
        try:
            while open_choices:
                i, out = await merged.get()
                if out is None:
                    open_choices -= 1
                    continue
                out.choice = i
                yield out.to_wire()
        finally:
            for t in tasks:
                t.cancel()
            relay.cancel()
            for c in child_ctxs:
                c.stop_generating()
            request.context.merge_stages_from(child_ctxs)

    async def _json_constraint(self, spec: dict):
        """Per-request cursor over the (cached) compiled grammar. The
        first request with a new spec pays the compile + the O(vocab)
        piece-table build in an executor thread; the scheduler loop
        never blocks on it."""
        import json as _json

        from ..runtime.engine import EngineError
        from .guided import JsonConstraint, JsonGrammar, build_piece_table

        key = _json.dumps(spec, sort_keys=True)
        entry = self._json_grammars.get(key)
        if isinstance(entry, asyncio.Future):
            # a concurrent first request is already building this spec:
            # await it instead of paying the O(vocab) sweep N times
            grammar = await asyncio.shield(entry)
        else:
            grammar = entry
        if grammar is None:
            if self._model_path is None:
                raise EngineError(
                    "guided json requires a tokenizer; this engine was "
                    "built without a model path"
                )
            loop = asyncio.get_running_loop()

            def build():
                if self._pieces is None:
                    from ..llm.tokenizer import HFTokenizer

                    tok = HFTokenizer.from_model_path(self._model_path)
                    self._pieces = build_piece_table(
                        tok, self.config.model.vocab_size
                    )
                schema = (spec.get("schema")
                          if spec.get("type") == "json_schema" else None)
                g = JsonGrammar(self._pieces, schema)
                # the first O(vocab) mask sweep belongs HERE (executor
                # thread), not on the event loop — and it doubles as
                # the expressibility check
                ids, _at_end = JsonConstraint(g).allowed()
                if not ids:
                    # e.g. a tokenizer whose vocab has no brace/quote
                    # pieces: the grammar is unsatisfiable — reject the
                    # request instead of streaming junk-then-stop
                    raise EngineError(
                        "guided json: this model's tokenizer cannot "
                        "express the requested grammar (no legal first "
                        "token)"
                    )
                return g

            fut = loop.create_future()
            self._json_grammars[key] = fut  # followers await this build
            try:
                grammar = await loop.run_in_executor(None, build)
            except ValueError as e:
                err = EngineError(f"guided json: {e}")
                fut.set_exception(err)
                fut.exception()  # consumed (no un-retrieved warning)
                self._json_grammars.pop(key, None)
                raise err
            except BaseException as e:
                fut.set_exception(e)
                fut.exception()
                self._json_grammars.pop(key, None)
                raise
            fut.set_result(grammar)
            # bounded LRU over distinct specs: each grammar's per-state
            # mask cache can reach vocab-sized lists — adversarial
            # unique-schema traffic must not grow memory without limit
            evictable = [k for k, v in self._json_grammars.items()
                         if not isinstance(v, asyncio.Future)]
            while len(self._json_grammars) > 32 and evictable:
                self._json_grammars.pop(evictable.pop(0), None)
            self._json_grammars[key] = grammar  # resolve future → value
        else:
            self._json_grammars.pop(key, None)
            self._json_grammars[key] = grammar  # LRU touch
        return JsonConstraint(grammar)

    @property
    def embed_ready(self) -> bool:
        return getattr(self.runner, "embed_ready", False)

    async def embed(self, prompts):
        """Batched prefill-only embeddings (the /v1/embeddings engine
        half): [n] token-id lists → [n, D] float32. The cacheless embed
        program reads params only — no donated buffers — so the device
        round trip can ride an executor thread beside the scheduler
        loop's own dispatches."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, self.runner.embed_prompts, prompts
        )

    def metrics(self) -> dict:
        return self.scheduler.metrics()

    @property
    def registry(self):
        """The engine's MetricsRegistry (scheduler + KV allocator +
        disagg instruments) — attach it to the frontend's ServiceMetrics
        so one /metrics scrape covers every layer."""
        return self.scheduler.registry

    async def close(self) -> None:
        # watchdog first: a slow drain during scheduler.stop() must not
        # read as a stall and dump a spurious artifact mid-shutdown
        if self.watchdog is not None:
            await self.watchdog.stop()
        await self.scheduler.stop()
