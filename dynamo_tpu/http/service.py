"""OpenAI-compatible HTTP frontend (aiohttp).

Routes: POST /v1/chat/completions, POST /v1/completions, GET /v1/models,
GET /metrics, GET /health. SSE streaming with a client-disconnect monitor
that stops generation; non-streaming requests aggregate the chunk stream.

Reference analog: lib/llm/src/http/service/openai.rs:132-539 (axum routes +
disconnect monitor), service.rs ModelManager, service_v2 builder, and the
model discovery watcher (http/service/discovery.rs:37-171) that hot-adds
remote models registered in the discovery plane.
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Any, AsyncIterator, Dict, Optional

import msgpack
from aiohttp import web

from ..protocols import sse
from ..protocols.annotated import Annotated
from ..utils.logging import stage_summary
from ..protocols.openai import (
    ChatCompletionChunk,
    ChatCompletionRequest,
    CompletionRequest,
    CompletionResponse,
    ModelInfo,
    ModelList,
    aggregate_chat_stream,
    aggregate_completion_stream,
)
from ..runtime.client import Client, NoInstancesError, RouterMode
from ..runtime.component import DistributedRuntime
from ..runtime.discovery import WatchEventType
from ..runtime.engine import (
    AsyncEngine,
    AsyncEngineContext,
    Context,
    EngineDrainingError,
    EngineError,
)
from ..runtime.network import ResponseStreamError
from ..telemetry.tracing import TraceRecorder, span
from .metrics import ServiceMetrics

logger = logging.getLogger(__name__)

MODEL_REGISTRY_PREFIX = "models/"  # under the http namespace


class ModelManager:
    """name → engine maps for chat and completion models, as a live view
    over the model registry (registry/registry.py): served aliases and
    tenant visibility resolve through the registered cards; engines
    without cards (local single-model serving, BYO) stay public under
    their exact name."""

    def __init__(self, registry=None) -> None:
        from ..registry.registry import ModelRegistry

        self.chat_engines: Dict[str, AsyncEngine] = {}
        self.completion_engines: Dict[str, AsyncEngine] = {}
        self.metadata: Dict[str, dict] = {}  # name → /v1/models extras
        self.registry = registry or ModelRegistry()

    def set_metadata(self, name: str, **meta) -> None:
        self.metadata.setdefault(name, {}).update(
            {k: v for k, v in meta.items() if v is not None}
        )

    def set_card(self, card) -> None:
        self.registry.put(card)

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self.chat_engines[name] = engine

    def add_completion_model(self, name: str, engine: AsyncEngine) -> None:
        self.completion_engines[name] = engine

    def remove_model(self, name: str) -> None:
        self.chat_engines.pop(name, None)
        self.completion_engines.pop(name, None)
        self.metadata.pop(name, None)  # a re-registration starts clean
        self.registry.remove(name)

    def resolve(self, model: str, tenant: Optional[str] = None
                ) -> Optional[str]:
        """Requested name/alias → canonical pool name, or None (unknown
        OR invisible to the tenant — the same answer, so tenants cannot
        probe each other's catalogs). Card-less engine names resolve to
        themselves and are public."""
        if self.registry.lookup(model) is not None:
            return self.registry.resolve(model, tenant)
        if model in self.chat_engines or model in self.completion_engines:
            return model
        return None

    def served_names(self) -> list:
        """Every model with an engine, visibility-blind — the operator
        surface (/health), never a tenant-facing catalog."""
        return sorted(set(self.chat_engines) | set(self.completion_engines))

    def model_names(self, tenant: Optional[str] = None) -> list:
        names = set(self.chat_engines) | set(self.completion_engines)
        if not self.registry.cards:
            return sorted(names)
        visible = []
        for name in names:
            card = self.registry.card(name)
            if card is None or card.visible_to(tenant):
                visible.append(name)
        return sorted(visible)


class HttpService:
    def __init__(
        self,
        manager: Optional[ModelManager] = None,
        host: str = "0.0.0.0",
        port: int = 8080,
        metrics_prefix: str = "dynamo",
        profile_dir: Optional[str] = None,
        admission=None,  # planner.admission.AdmissionController
        slo=None,        # telemetry.slo.SloTracker
        trace_ttl_s: Optional[float] = None,
        trace_capacity: Optional[int] = None,
        hub=None,        # telemetry.hub.FleetHub
        incidents=None,  # telemetry.incidents.IncidentRecorder
        quotas=None,     # registry.tenants.TenantQuotas
        pools=None,      # registry.pools.PoolManager
    ):
        self.manager = manager or ModelManager()
        self.host = host
        self.port = port
        self.metrics = ServiceMetrics(metrics_prefix)
        # optional HTTP-edge admission control (priority classes, bounded
        # queues, load shedding) — the actuated end of the SLA planner
        self.admission = admission
        if admission is not None:
            self.metrics.attach_registry(admission.registry)
        # multi-tenant quota layer (registry/tenants.py): X-Tenant →
        # per-tenant token buckets, checked BEFORE the priority queues
        # so one tenant's spike sheds that tenant at the door
        self.quotas = quotas
        if quotas is not None:
            self.metrics.attach_registry(quotas.registry)
        # per-model pool manager (registry/pools.py): cold-start gate +
        # scale-to-zero loop; None = models must be warm to serve
        self.pools = None
        if pools is not None:
            self.attach_pools(pools)
        self.metrics.attach_registry(self.manager.registry.registry)
        # optional SLO attainment + goodput accounting: per-request
        # TTFT / worst-ITL verdicts at the edge (telemetry/slo.py)
        if slo is not None:
            self.metrics.slo = slo
            self.metrics.attach_registry(slo.registry)
        # completed request traces: ingress-assigned trace ids (honoring
        # X-Request-Id) → span breakdowns at GET /debug/requests/{id},
        # cluster-stitched timelines at GET /debug/trace/{id}. Bounded
        # by max-entries LRU AND TTL (evictions counted on
        # dynamo_trace_evicted_total) so traffic can't grow trace memory
        self.traces = TraceRecorder(
            capacity=trace_capacity, ttl_s=trace_ttl_s,
            registry=self.metrics.registry,
        )
        self.profile_dir = profile_dir
        self.app = web.Application()
        self.app.router.add_post("/v1/chat/completions", self.handle_chat)
        self.app.router.add_post("/v1/completions", self.handle_completions)
        self.app.router.add_post("/v1/embeddings", self.handle_embeddings)
        self.app.router.add_get("/v1/models", self.handle_models)
        self.app.router.add_get("/metrics", self.handle_metrics)
        self.app.router.add_get("/health", self.handle_health)
        self.app.router.add_get("/debug/requests", self.handle_debug_requests)
        self.app.router.add_get("/debug/requests/{rid}", self.handle_debug_request)
        self.app.router.add_get("/debug/trace/{rid}", self.handle_debug_trace)
        self.app.router.add_get("/debug/flight", self.handle_flight)
        # zero-downtime rolling updates: drain + live-migrate in-flight
        # requests to peers (recovery/controller.py). Wired by the CLI
        # when --self-heal builds a RecoveryController; 501 otherwise.
        self.drainer = None  # async (mode, respawn) -> summary dict
        self.app.router.add_post("/admin/drain", self.handle_admin_drain)
        # dynamic model management (registry/registry.py RegistryAdmin,
        # wired by the CLI when a discovery plane exists; 501 otherwise)
        # — the llmctl/dynamoctl surface over HTTP
        self.registry_admin = None
        self.app.router.add_get("/admin/models", self.handle_admin_models)
        self.app.router.add_post("/admin/models",
                                 self.handle_admin_model_add)
        self.app.router.add_delete("/admin/models/{name}",
                                   self.handle_admin_model_remove)
        self.app.router.add_get("/admin/pools", self.handle_admin_pools)
        # fleet telemetry hub + incident recorder (telemetry/hub.py,
        # telemetry/incidents.py): wired by the CLI (--hub /
        # DYN_INCIDENT_DIR); the routes answer 501 when the subsystem is
        # off so an operator learns the flag instead of guessing at 404s
        self.hub = hub
        self.incidents = incidents
        if hub is not None:
            self.metrics.attach_registry(hub.registry)
        if incidents is not None:
            self.metrics.attach_registry(incidents.registry)
        self.app.router.add_get("/fleet/metrics", self.handle_fleet_metrics)
        self.app.router.add_get("/fleet/workers", self.handle_fleet_workers)
        self.app.router.add_get("/debug/incidents", self.handle_incidents)
        if profile_dir:
            # opt-in only: trace capture costs device time and writes disk
            self.app.router.add_get("/debug/profile", self.handle_profile)
            self._profile_lock = asyncio.Lock()
        self._runner: Optional[web.AppRunner] = None
        self._site: Optional[web.TCPSite] = None

    def attach_pools(self, pools) -> None:
        """Attach a PoolManager after construction (the CLI builds it
        once the model watcher exists) — gates requests AND merges its
        instruments into this service's exposition."""
        self.pools = pools
        self.metrics.attach_registry(pools.registry)

    # ---------- lifecycle ----------

    async def start(self) -> None:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        self._site = web.TCPSite(self._runner, self.host, self.port)
        await self._site.start()
        if self.port == 0:
            self.port = self._runner.addresses[0][1]
        logger.info("http service on %s:%d", self.host, self.port)

    async def stop_accepting(self) -> None:
        """Close the listening socket but keep in-flight connections alive
        (the first phase of graceful shutdown: drain without accepting)."""
        if self._site is not None:
            await self._site.stop()
            self._site = None

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
        # close() joins the trace writer thread — off-loop, so a hung
        # JSONL filesystem can't stall the rest of shutdown
        await asyncio.get_running_loop().run_in_executor(
            None, self.traces.close)

    # ---------- helpers ----------

    @staticmethod
    def _error(status: int, message: str, err_type: str = "invalid_request_error"):
        return web.json_response(
            {"error": {"message": message, "type": err_type, "code": status}},
            status=status,
        )

    @staticmethod
    def _model_not_found(model: str):
        """The OpenAI 404 body — also the answer for a model another
        tenant CAN see (existence must not leak across tenants)."""
        return web.json_response(
            {"error": {
                "message": f"The model '{model}' does not exist or you "
                           "do not have access to it.",
                "type": "invalid_request_error",
                "param": "model",
                "code": "model_not_found",
            }},
            status=404,
        )

    def _resolve_tenant(self, request: web.Request) -> str:
        """X-Tenant → tenant id (absent/garbage degrades to default —
        the X-Priority parsing contract). Tenant IDENTITY always parses
        — card visibility must work on a quota-less frontend too; the
        quota gate additionally counts garbage headers."""
        from ..registry.tenants import TENANT_HEADER, parse_tenant

        header = request.headers.get(TENANT_HEADER)
        if self.quotas is not None:
            return self.quotas.resolve(header)
        return parse_tenant(header)

    async def _handle_inference(
        self, request: web.Request, request_cls, engines: Dict[str, AsyncEngine],
        chunk_cls, aggregate, kind: str = "chat",
    ) -> web.StreamResponse:
        try:
            body = await request.json()
            with span("http.ingress"):
                api_req = request_cls.model_validate(body)
        except (json.JSONDecodeError, ValueError) as e:
            return self._error(400, f"invalid request: {e}")

        tenant = self._resolve_tenant(request)
        # registry resolution: alias → canonical pool name, tenant
        # visibility enforced (unknown and invisible answer identically)
        name = self.manager.resolve(api_req.model, tenant)
        if name is None:
            return self._model_not_found(api_req.model)
        card = self.manager.registry.card(name)
        if card is not None and card.model_type not in (kind, "both"):
            # registered for the OTHER endpoint kind: for this API the
            # model does not exist — a 404, never a forever-retry 503
            return self._model_not_found(api_req.model)
        if name != api_req.model:
            # canonicalize the OUTBOUND model: downstream hops (the
            # processor's pool partition, worker metadata, per-model
            # metrics) key on the canonical pool name — an alias must
            # not leak past the edge (responses echo the resolved
            # model, the OpenAI alias convention)
            api_req.model = name
        rid = (request.headers.get("X-Request-Id") or "").strip()[:128]
        if self.quotas is not None:
            # tenant token buckets BEFORE the priority queues: a tenant
            # over its requests/s or tokens/s budget is shed at the door
            # (429 + Retry-After), other tenants untouched
            from ..planner.admission import AdmissionRejected

            try:
                self.quotas.admit(tenant, request_id=rid)
            except AdmissionRejected as e:
                return web.json_response(
                    {"error": {"message": str(e), "type": "overloaded",
                               "code": 429}},
                    status=429,
                    headers={"Retry-After": e.retry_after_header},
                )
        if self.pools is not None:
            self.pools.note_request(name)
            if card is not None:
                # cold-start gate: a warm pool passes through in one
                # dict lookup; a registered-but-cold model (scale-to-
                # zero drained its pool, or the record exists with no
                # client yet) kicks a spawn with the model's card and
                # holds the request, bounded — past the deadline it
                # sheds with 503 + Retry-After
                from ..registry.pools import ColdStartTimeout

                try:
                    await self.pools.await_capacity(name)
                except ColdStartTimeout as e:
                    return web.json_response(
                        {"error": {"message": str(e),
                                   "type": "service_unavailable",
                                   "code": 503}},
                        status=503,
                        headers={"Retry-After":
                                 str(max(1, int(e.retry_after_s)))},
                    )
        engine = engines.get(name)
        if engine is None:
            if card is not None:
                # the card exists but no worker serves the pool and no
                # cold-start path is configured: transient, retryable
                return web.json_response(
                    {"error": {"message": f"model '{api_req.model}' has "
                               "no live workers",
                               "type": "service_unavailable", "code": 503}},
                    status=503, headers={"Retry-After": "5"},
                )
            return self._model_not_found(api_req.model)

        admitted = False
        if self.admission is not None:
            # priority-class admission control (planner/admission.py):
            # shed/deadline rejections answer 429 + Retry-After BEFORE the
            # request counts as inflight — shed traffic is accounted on
            # the dynamo_planner_* instruments, not the service timers
            from ..planner.admission import AdmissionRejected, parse_priority

            priority = parse_priority(request.headers.get("X-Priority"))
            try:
                await self.admission.acquire(priority, request_id=rid)
                admitted = True
            except AdmissionRejected as e:
                return web.json_response(
                    {"error": {"message": str(e), "type": "overloaded",
                               "code": 429}},
                    status=429,
                    headers={"Retry-After": e.retry_after_header},
                )

        # per-model accounting keys on the CANONICAL pool name, so an
        # alias's traffic lands on its model's series
        timer = self.metrics.track(name)
        status = "error"
        # token-bucket accounting by ACTUAL streamed tokens — the charge
        # rides the same sites the SLO goodput counter does
        if self.quotas is not None:
            quotas, q_tenant = self.quotas, tenant

            def charge(n: int) -> None:
                quotas.charge_tokens(q_tenant, n)
        else:
            charge = None
        # ingress-assigned trace id: honor the client's X-Request-Id so
        # callers can correlate their logs with /debug/requests/{id} and
        # every downstream hop (scheduler spans, remote prefill) by id.
        # It is correlation-only: the engine-side request id stays a fresh
        # UUID (AsyncEngineContext.id), so a reused/duplicate client id
        # cannot collide in scheduler or disagg-coordinator state.
        ctx = Context(api_req, AsyncEngineContext(trace_id=rid or None))
        ctx.add_stage("http")
        try:
            stream = engine.generate(ctx).__aiter__()
            # prime the first chunk BEFORE committing a status line so
            # request-validation errors (raised on first iteration of the
            # pipeline generator) still map to proper HTTP codes
            try:
                first = await stream.__anext__()
            except StopAsyncIteration:
                first = None
            if api_req.stream:
                resp, status = await self._stream_sse(
                    request, ctx, first, stream, timer, charge=charge)
                return resp
            def _check_annotated(chunk):
                """None for data chunks; the envelope for annotations.
                Error envelopes raise — a swallowed error must not look ok."""
                ann = Annotated.maybe_from_wire(chunk)
                if ann is not None and ann.is_error:
                    raise EngineError(
                        ann.comment[0] if ann.comment else "engine error"
                    )
                return ann

            chunks = []
            if first is not None and _check_annotated(first) is None:
                chunks.append(chunk_cls.model_validate(_as_dict(first)))
            async for chunk in stream:
                if _check_annotated(chunk) is not None:
                    continue  # annotations are stream-only side channel
                d = _as_dict(chunk)
                if _has_payload(d):
                    n = _payload_tokens(d)
                    timer.token(n)
                    if charge is not None:
                        charge(n)
                chunks.append(chunk_cls.model_validate(d))
            status = "success"
            return web.json_response(
                aggregate(chunks).model_dump(exclude_none=True),
                headers={"X-Request-Id": ctx.trace_id},
            )
        except EngineDrainingError as e:
            # transient: the worker behind this engine is draining for a
            # recovery or rolling update — clients/LBs should retry
            return web.json_response(
                {"error": {"message": str(e), "type": "service_unavailable",
                           "code": 503}},
                status=503, headers={"Retry-After": "1"},
            )
        except (EngineError, ValueError) as e:
            return self._error(400, str(e))
        except NoInstancesError as e:
            # an empty pool is transient by design (workers churn,
            # scale-to-zero drains) — tell the client when to come back
            return web.json_response(
                {"error": {"message": str(e), "type": "service_unavailable",
                           "code": 503}},
                status=503, headers={"Retry-After": "5"},
            )
        except (ResponseStreamError, asyncio.TimeoutError) as e:
            return self._error(502, str(e), "engine_error")
        except _StreamDisconnect:
            status = "disconnect"
            raise ConnectionResetError("client disconnected")
        except asyncio.CancelledError:
            ctx.context.stop_generating()
            status = "disconnect"
            raise
        finally:
            if admitted:
                self.admission.release()
            ctx.context.stop_generating()
            timer.finish(status)
            self.traces.record(ctx.trace_id, name, status,
                               ctx.stages, ctx=ctx.context)
            if ctx.stages and logger.isEnabledFor(logging.DEBUG):
                logger.debug(
                    "request %s %s: %s",
                    ctx.trace_id, status, stage_summary(ctx.stages),
                    extra={"request_id": ctx.trace_id,
                           "stages": [s for s, _ in ctx.stages]},
                )

    async def _stream_sse(
        self,
        request: web.Request,
        ctx: Context,
        first: Any,
        chunks: AsyncIterator[Any],
        timer,
        charge=None,  # tenant token-bucket accounting (registry/tenants.py)
    ):
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                "X-Request-Id": ctx.trace_id,
            }
        )
        await resp.prepare(request)

        async def _write(chunk) -> bool:
            """Write one stream element; True = stream must terminate."""
            ann = Annotated.maybe_from_wire(chunk)
            if ann is not None:
                if ann.is_error:
                    # match the mid-stream exception convention below:
                    # error payload on a data line, then end the stream
                    await resp.write(sse.encode_event(
                        {"error": {"message": ann.comment[0] if ann.comment
                                   else "engine error"}}
                    ))
                    return True
                # annotation events ride SSE event/comment lines with no
                # data payload (reference annotated.rs wire mapping)
                await resp.write(sse.encode_event(
                    None, event=ann.event,
                    comment=ann.comment[0] if ann.comment else None,
                ))
                return False
            # the write yields only under backpressure (the socket's
            # buffer above its high-water mark): the span is then as long
            # as the wait, and other tasks' spans nest inside it
            with span("http.sse_write"):
                d = _as_dict(chunk)
                if _has_payload(d):
                    n = _payload_tokens(d)
                    timer.token(n)
                    if charge is not None:
                        charge(n)
                await resp.write(sse.encode_event(d))
            return False

        try:
            failed = first is not None and await _write(first)
            if not failed:
                async for chunk in chunks:
                    if await _write(chunk):
                        failed = True
                        break
            await resp.write(sse.encode_done())
            await resp.write_eof()
            if failed:
                ctx.context.stop_generating()
                return resp, "error"
            return resp, "success"
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away — stop generation upstream
            ctx.context.stop_generating()
            raise _StreamDisconnect()
        except (EngineError, ResponseStreamError, NoInstancesError) as e:
            # mid-stream failure: emit an error event, then end the stream
            await resp.write(sse.encode_event({"error": {"message": str(e)}}))
            await resp.write(sse.encode_done())
            await resp.write_eof()
            return resp, "error"

    # ---------- routes ----------

    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_inference(
            request, ChatCompletionRequest, self.manager.chat_engines,
            ChatCompletionChunk, aggregate_chat_stream, kind="chat",
        )

    async def handle_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_inference(
            request, CompletionRequest, self.manager.completion_engines,
            CompletionResponse, aggregate_completion_stream,
            kind="completions",
        )

    async def handle_embeddings(self, request: web.Request) -> web.Response:
        """POST /v1/embeddings — the prefill-only workload riding the
        batched-prefill path (llm/embeddings.py): OpenAI-shaped request
        (input: str | [str] | [ids] | [[ids]]) and response (data rows
        + usage counts). Served when the resolved engine carries an
        ``embedder``; engines without one (echo chat, remote pools whose
        frontend sits on the decode tier) answer 501 with a routing
        hint."""
        import base64 as _b64

        from ..llm.embeddings import EmbeddingError

        try:
            body = await request.json()
        except json.JSONDecodeError as e:
            return self._error(400, f"invalid request: {e}")
        if not isinstance(body, dict):
            return self._error(400, "request body must be a JSON object")
        model = body.get("model")
        if not isinstance(model, str) or not model:
            return self._error(400, "missing model")
        if "input" not in body:
            return self._error(400, "missing input")
        fmt = body.get("encoding_format", "float")
        if fmt not in ("float", "base64"):
            return self._error(
                400, "encoding_format must be 'float' or 'base64'")
        tenant = self._resolve_tenant(request)
        name = self.manager.resolve(model, tenant)
        if name is None:
            return self._model_not_found(model)
        engine = (self.manager.chat_engines.get(name)
                  or self.manager.completion_engines.get(name))
        embedder = getattr(engine, "embedder", None)
        if embedder is None:
            return self._error(
                501,
                f"model '{model}' does not serve embeddings on this "
                "frontend (embeddings ride the prefill path — route to "
                "a prefill-pool frontend; docs/long_context.md)",
                err_type="not_implemented",
            )
        try:
            vectors, ntok = await embedder.embed(body["input"])
        except EmbeddingError as e:
            return self._error(400, str(e))
        data = []
        for i, vec in enumerate(vectors):
            if fmt == "base64":
                import numpy as _np

                emb = _b64.b64encode(
                    _np.asarray(vec, _np.float32).tobytes()
                ).decode("ascii")
            else:
                emb = vec
            data.append(
                {"object": "embedding", "index": i, "embedding": emb}
            )
        return web.json_response({
            "object": "list",
            "data": data,
            "model": model,
            "usage": {"prompt_tokens": ntok, "total_tokens": ntok},
        })

    async def handle_models(self, request: web.Request) -> web.Response:
        """GET /v1/models — card-enriched (family, context length,
        aliases, owned_by) and filtered by the caller's tenant
        visibility; card-less engines keep their metadata-only rows."""
        tenant = self._resolve_tenant(request)
        data = []
        for name in self.manager.model_names(tenant):
            meta = dict(self.manager.metadata.get(name, {}))
            card = self.manager.registry.card(name)
            if card is not None:
                meta.setdefault("model_type", card.model_type)
                if card.context_length:
                    meta.setdefault("max_model_len", card.context_length)
                data.append(ModelInfo(
                    id=name, owned_by=card.owned_by, family=card.family,
                    aliases=card.aliases or None, **meta,
                ))
            else:
                data.append(ModelInfo(id=name, **meta))
        return web.json_response(
            ModelList(data=data).model_dump(exclude_none=True)
        )

    async def handle_metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=self.metrics.render(), content_type="text/plain")

    async def handle_health(self, request: web.Request) -> web.Response:
        # operator surface: every served model, visibility-blind — a
        # readiness probe must see tenant-scoped models too
        return web.json_response(
            {"status": "ok", "models": self.manager.served_names()})

    async def handle_debug_requests(self, request: web.Request) -> web.Response:
        """GET /debug/requests?limit=N — the most recent completed traces
        (newest last), for finding an id when the client didn't pick one."""
        try:
            limit = int(request.query.get("limit", "20"))
        except ValueError:
            return web.json_response({"error": "bad limit"}, status=400)
        return web.json_response(
            {"traces": self.traces.recent(max(1, min(limit, 200)))}
        )

    async def handle_debug_request(self, request: web.Request) -> web.Response:
        """GET /debug/requests/{id} — per-request span breakdown (stage
        names, offsets, durations) for a completed request. Issue the
        request with an X-Request-Id header to pick the id yourself."""
        rid = request.match_info["rid"]
        trace = self.traces.get(rid)
        if trace is None:
            return web.json_response(
                {"error": f"no completed trace for request id {rid!r} "
                          "(unknown, evicted, or still in flight)"},
                status=404,
            )
        return web.json_response(trace)

    async def handle_debug_trace(self, request: web.Request) -> web.Response:
        """GET /debug/trace/{id} — the request X-ray: every process's
        spans (frontend, router hop, decode engine, prefill worker,
        migration peer) stitched onto ONE clock-adjusted axis, plus the
        per-hop offset/rtt estimates and the unattributed gaps. The
        cluster answer to "where did this request's 900 ms TTFT go"."""
        from ..telemetry.stitch import stitched_timeline, timeline_gaps

        rid = request.match_info["rid"]
        trace = self.traces.get(rid)
        if trace is None:
            return web.json_response(
                {"error": f"no completed trace for request id {rid!r} "
                          "(unknown, evicted, or still in flight)"},
                status=404,
            )
        stitched = stitched_timeline(trace)
        return web.json_response({
            "request_id": trace["request_id"],
            "model": trace.get("model"),
            "status": trace.get("status"),
            "total_s": trace.get("total_s"),
            "sources": stitched["sources"],
            "timeline": stitched["timeline"],
            "gaps": timeline_gaps(stitched["timeline"],
                                  min_gap_s=0.0005),
        })

    async def handle_flight(self, request: web.Request) -> web.Response:
        """GET /debug/flight[?save=1][&request=<id>] — the flight-recorder
        dump on demand: ring events (optionally filtered to one request
        id), all-thread stacks, every registered engine's liveness probe,
        request table, and metrics snapshot (telemetry/watchdog.py). The
        same artifact the stall watchdog writes on a trip; ``save=1``
        additionally persists it to DYN_FLIGHT_DIR."""
        from ..telemetry.watchdog import build_flight_artifact, write_flight_artifact

        loop = asyncio.get_running_loop()
        # stack walking + metrics rendering off-loop: /debug/flight is
        # exactly the endpoint an operator hits when the loop is ailing
        artifact = await loop.run_in_executor(
            None, lambda: build_flight_artifact(reason="debug_endpoint")
        )
        if request.query.get("save"):
            # persist the COMPLETE dump before any response filtering: an
            # on-disk artifact must never silently be a one-request slice
            artifact["artifact_path"] = await loop.run_in_executor(
                None, lambda: write_flight_artifact(artifact)
            )
        rid = request.query.get("request")
        if rid:
            artifact["events"] = [
                e for e in artifact["events"]
                if e.get("request_id") == rid or e.get("trace_id") == rid
            ]
            artifact["filtered_to_request"] = rid
        return web.json_response(artifact, dumps=lambda o: json.dumps(
            o, default=str))

    async def handle_admin_drain(self, request: web.Request) -> web.Response:
        """POST /admin/drain[?mode=migrate|fail][&respawn=1] — stop
        admission, let committed bursts finish, live-migrate the rest to
        healthy peers, and (optionally) respawn — the rolling-model-
        update runbook in docs/self_healing.md. Returns the drain
        summary (requests finished / migrated / failed, duration)."""
        if self.drainer is None:
            return web.json_response(
                {"error": "no recovery controller attached "
                          "(serve with --self-heal)"},
                status=501,
            )
        mode = request.query.get("mode", "migrate")
        if mode not in ("migrate", "fail"):
            return web.json_response({"error": f"bad mode {mode!r}"},
                                     status=400)
        respawn = request.query.get("respawn") in ("1", "true", "yes")
        summary = await self.drainer(mode=mode, respawn=respawn)
        return web.json_response(summary)

    async def handle_admin_models(self, request: web.Request) -> web.Response:
        """GET /admin/models — every registered card, unfiltered (this
        is the operator surface, not the tenant-scoped /v1/models)."""
        return web.json_response({
            "models": [card.to_wire() for _, card in
                       sorted(self.manager.registry.cards.items())],
        })

    async def handle_admin_model_add(self, request: web.Request
                                     ) -> web.Response:
        """POST /admin/models — register a model card dynamically (the
        ``llmctl http add`` / ``dynamoctl models add`` analogue). The
        frontend's watcher picks the record up and binds the route; no
        restart. Body: a ModelCard wire dict (name + endpoint required)."""
        if self.registry_admin is None:
            return web.json_response(
                {"error": "no registry admin attached (serve with a "
                          "discovery plane: --store-port)"},
                status=501,
            )
        from ..registry.cards import ModelCard

        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object (a card)")
            card = ModelCard.from_wire(body)
            if not card.name or not card.endpoint:
                raise ValueError("name and endpoint are required")
            await self.registry_admin.add(card)
        except (json.JSONDecodeError, ValueError, TypeError) as e:
            return self._error(400, f"invalid model card: {e}")
        return web.json_response({"registered": card.name})

    async def handle_admin_model_remove(self, request: web.Request
                                        ) -> web.Response:
        """DELETE /admin/models/{name} — unregister; routes unbind as
        the watcher sees the delete."""
        if self.registry_admin is None:
            return web.json_response(
                {"error": "no registry admin attached (serve with a "
                          "discovery plane: --store-port)"},
                status=501,
            )
        name = request.match_info["name"]
        card = self.manager.registry.card(name)
        await self.registry_admin.remove(
            name, card.model_type if card is not None else None)
        return web.json_response({"removed": name})

    async def handle_admin_pools(self, request: web.Request) -> web.Response:
        """GET /admin/pools — per-model pool rows: live workers, idle
        age, cold-start state (what the scale-to-zero policy sees)."""
        if self.pools is None:
            return web.json_response(
                {"error": "no pool manager attached (serve with "
                          "--pool-scale-to-zero-idle-s or a cold-start "
                          "backend)"},
                status=501,
            )
        return web.json_response({"pools": self.pools.snapshot()})

    async def handle_fleet_metrics(self, request: web.Request) -> web.Response:
        """GET /fleet/metrics — cluster rollups (sum/max/avg by role,
        counter rates) from the fleet hub's scraped histories."""
        if self.hub is None:
            return web.json_response(
                {"error": "no fleet hub attached (serve with --hub)"},
                status=501,
            )
        return await self.hub.handle_fleet_metrics(request)

    async def handle_fleet_workers(self, request: web.Request) -> web.Response:
        """GET /fleet/workers — per-worker KV/busy/roofline/SLO/drain
        rows; what scripts/dynamotop.py renders live."""
        if self.hub is None:
            return web.json_response(
                {"error": "no fleet hub attached (serve with --hub)"},
                status=501,
            )
        return await self.hub.handle_fleet_workers(request)

    async def handle_incidents(self, request: web.Request) -> web.Response:
        """GET /debug/incidents[?id=] — list / fetch incident bundles."""
        if self.incidents is None:
            return web.json_response(
                {"error": "no incident recorder attached (set "
                          "DYN_INCIDENT_DIR or --incident-dir)"},
                status=501,
            )
        return await self.incidents.handle_debug_incidents(request)

    async def handle_profile(self, request: web.Request) -> web.Response:
        """GET /debug/profile?seconds=N — capture an XLA profiler trace of
        live traffic (enabled only with a configured profile dir)."""
        from ..utils.profiling import CaptureBusyError, capture_trace_async

        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            return web.json_response({"error": "bad seconds"}, status=400)
        if seconds != seconds:  # NaN survives min/max clamping
            return web.json_response({"error": "bad seconds"}, status=400)
        seconds = min(max(seconds, 0.1), 60.0)
        # jax allows ONE active trace per process — serialize via a
        # non-blocking lock so a concurrent capture gets a clean 409
        if self._profile_lock.locked():
            return web.json_response(
                {"error": "a capture is already in flight"}, status=409
            )
        async with self._profile_lock:
            try:
                trace_dir = await capture_trace_async(
                    self.profile_dir, seconds)
            except CaptureBusyError as e:
                # the PROCESS-wide profiler lock is held by a capture that
                # didn't come through this endpoint (an incident bundle's
                # profile window) — same clean 409, never a crash
                return web.json_response({"error": str(e)}, status=409)
        return web.json_response({"trace_dir": trace_dir, "seconds": seconds})


class _StreamDisconnect(Exception):
    """Internal: SSE client went away mid-stream."""


def _as_dict(chunk: Any) -> Any:
    if hasattr(chunk, "model_dump"):
        return chunk.model_dump(exclude_none=True)
    return chunk


def _payload_tokens(chunk: Any) -> int:
    """Token count of one payload chunk, for SLO goodput accounting.
    OpenAI chat/completions chunks carry one token per chunk on every
    current engine path (the scheduler emits per token even under
    speculative decode); token-level shapes expose token_ids, so a
    future multi-token chunk still counts fully."""
    if isinstance(chunk, dict) and isinstance(chunk.get("token_ids"), list):
        return len(chunk["token_ids"])
    return 1


def _has_payload(chunk: Any) -> bool:
    """True if the chunk carries generated content (TTFT should fire)."""
    if not isinstance(chunk, dict):
        return True
    for choice in chunk.get("choices", []):
        if (choice.get("delta") or {}).get("content") or choice.get("text"):
            return True
    return False


# ---------- model registry + discovery watcher ----------


def model_registry_key(namespace: str, model_type: str, name: str) -> str:
    return f"{namespace}/{MODEL_REGISTRY_PREFIX}{model_type}/{name}"


async def register_model(
    drt: DistributedRuntime,
    namespace: str,
    name: str,
    endpoint_path: str,
    model_type: str = "chat",
    mdc: Optional[dict] = None,
    lease_scoped: bool = True,
    card=None,  # registry.cards.ModelCard: the fleet card riding along
) -> None:
    """Register a served model in the discovery plane (llmctl analog).

    ``endpoint_path`` is a dyn://ns.comp.ep address whose workers accept
    OpenAI-level requests (preprocessing is worker-side, as in the
    reference's v0.1.1 layout). With ``card`` the record carries the
    full fleet card (family, aliases, tenant visibility, cold-start
    material) the registry-aware frontend serves and pools by.
    """
    entry = {"name": name, "endpoint": endpoint_path, "model_type": model_type}
    if mdc:
        entry["mdc"] = mdc
    if card is not None:
        entry["card"] = card.to_wire()
    lease = await drt.discovery.primary_lease() if lease_scoped else None
    await drt.discovery.kv_put(
        model_registry_key(namespace, model_type, name),
        msgpack.packb(entry, use_bin_type=True),
        lease_id=lease.id if lease else None,
    )


async def unregister_model(
    drt: DistributedRuntime, namespace: str, name: str, model_type: str = "chat"
) -> None:
    await drt.discovery.kv_delete(model_registry_key(namespace, model_type, name))


async def list_models(drt: DistributedRuntime, namespace: str) -> list:
    kvs = await drt.discovery.kv_get_prefix(f"{namespace}/{MODEL_REGISTRY_PREFIX}")
    return [msgpack.unpackb(v, raw=False) for v in kvs.values()]


def parse_endpoint_path(path: str):
    """'dyn://ns.comp.ep' → (ns, comp, ep)."""
    body = path[len("dyn://"):] if path.startswith("dyn://") else path
    parts = body.split(".")
    if len(parts) != 3:
        raise ValueError(f"bad endpoint path {path!r}; want dyn://ns.comp.ep")
    return parts[0], parts[1], parts[2]


class ModelWatcher:
    """Hot-add/remove models from discovery-plane registrations."""

    def __init__(
        self,
        drt: DistributedRuntime,
        manager: ModelManager,
        namespace: str = "public",
        router_mode: RouterMode = RouterMode.ROUND_ROBIN,
    ):
        self.drt = drt
        self.manager = manager
        self.namespace = namespace
        self.router_mode = router_mode
        self._clients: Dict[str, Client] = {}
        self._task: Optional[asyncio.Task] = None
        self._watcher = None
        # strong refs to in-flight client.close() tasks spawned from the
        # sync delete path: a bare ensure_future can be GC'd mid-close
        # and would drop any close() exception on the floor
        self._closing: set = set()

    async def start(self) -> None:
        prefix = f"{self.namespace}/{MODEL_REGISTRY_PREFIX}"
        snapshot, watcher = await self.drt.discovery.watch_prefix(prefix)
        self._watcher = watcher
        for key, value in snapshot.items():
            await self._handle_put(key, value)
        self._task = self.drt.runtime.spawn(self._loop(watcher))

    async def _loop(self, watcher) -> None:
        async for ev in watcher:
            try:
                if ev.type == WatchEventType.PUT:
                    await self._handle_put(ev.key, ev.value)
                else:
                    self._handle_delete(ev.key)
            except Exception:
                logger.exception("model watcher failed on %s", ev.key)

    async def _handle_put(self, key: str, value: bytes) -> None:
        entry = msgpack.unpackb(value, raw=False)
        name = entry["name"]
        ns, comp, ep = parse_endpoint_path(entry["endpoint"])
        endpoint = self.drt.namespace(ns).component(comp).endpoint(ep)
        card = None
        if entry.get("card"):
            from ..registry.cards import ModelCard

            try:
                card = ModelCard.from_wire(entry["card"])
            except (TypeError, ValueError):
                logger.warning("malformed model card for %s ignored "
                               "(serving by entry fields only)", name,
                               exc_info=True)
        # per-model pool: when a card names the pool, the client only
        # routes to endpoint instances whose registration metadata says
        # they serve THIS model (several pools can share one component);
        # card-less registrations keep the whole-endpoint behavior
        client = await Client(
            endpoint, self.router_mode,
            model=card.name if card is not None else None,
        ).start()
        previous = self._clients.pop(name, None)
        if previous is not None:
            # re-registration PUT: release the old client's watch task
            # instead of leaking one per worker churn event
            await previous.close()
        # start clean: a narrowed model_type must not leave the closed
        # client behind in the other engine map, nor stale metadata
        self.manager.remove_model(name)
        self._clients[name] = client
        model_type = entry.get("model_type", "chat")
        self.manager.set_metadata(
            name,
            model_type=model_type,
            max_model_len=(entry.get("mdc") or {}).get("context_length"),
        )
        if card is not None:
            self.manager.set_card(card)
        if model_type in ("chat", "both"):
            self.manager.add_chat_model(name, client)
        if model_type in ("completions", "both"):
            self.manager.add_completion_model(name, client)
        logger.info("model %s → %s registered (%s)", name, entry["endpoint"], model_type)

    def pool_size(self, name: str) -> int:
        """Live workers in one model's pool — what the pool manager's
        cold-start gate and scale-to-zero policy consult."""
        client = self._clients.get(name)
        if client is None:
            return 0
        return len(client.eligible_ids())

    def _handle_delete(self, key: str) -> None:
        name = key.rsplit("/", 1)[-1]
        self.manager.remove_model(name)
        client = self._clients.pop(name, None)
        if client is not None:
            task = asyncio.ensure_future(client.close())
            self._closing.add(task)

            def _done(t: asyncio.Task, model: str = name) -> None:
                self._closing.discard(t)
                if not t.cancelled() and t.exception() is not None:
                    logger.warning("closing client for removed model %s "
                                   "failed: %s", model, t.exception())

            task.add_done_callback(_done)
        logger.info("model %s removed", name)

    async def stop(self) -> None:
        if self._watcher is not None:
            self._watcher.cancel()
        if self._task is not None:
            self._task.cancel()
        # drain close() tasks spawned by deletes racing shutdown, so their
        # exceptions are observed before the loop is torn down under them
        if self._closing:
            await asyncio.gather(*list(self._closing), return_exceptions=True)
        for client in self._clients.values():
            await client.close()
