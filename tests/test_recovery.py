"""Self-healing serving (recovery/): policy ladder units + chaos e2e.

The acceptance bar (ISSUE 8): an injected mid-burst wedge yields a
watchdog trip followed by automated drain, live migration of in-flight
requests to a healthy peer with a byte-identical continued stream, and
a respawned engine re-registered in discovery — no leaked blocks or
slots on either side, and the KV router never routes to the draining
worker. Faults come from utils/faults.py (DYN_FAULT sites), engines are
the deterministic FakeRunner (token = f(prev, pos), so any scheduling —
including a cross-engine resume — must reproduce the same stream).
"""

import asyncio
import os
import uuid

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
from dynamo_tpu.kv_router.scheduler import AllWorkersBusy, KvScheduler
from dynamo_tpu.planner.admission import (
    AdmissionConfig,
    AdmissionController,
    AdmissionRejected,
)
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.recovery import (
    MigrationServer,
    MigrationSink,
    MigrationState,
    RecoveryConfig,
    RecoveryController,
    migration_class,
)
from dynamo_tpu.transfer.framing import pack_frame, read_header
from dynamo_tpu.runtime.engine import AsyncEngineContext
from dynamo_tpu.telemetry.flight import FlightRecorder
from dynamo_tpu.telemetry.watchdog import StallWatchdog
from dynamo_tpu.tokens import TokenSequence
from dynamo_tpu.utils import faults

from test_decode_pipeline import FakeRunner


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


class MigRunner(FakeRunner):
    """FakeRunner + the block-op surface the migration plane uses.

    KV payloads are zeros (the fake's token rule depends only on the
    carry, never on cache contents) — block *accounting* stays real, so
    the leak assertions are meaningful; ``sync_delay`` slows decode
    syncs so a test can reliably drain mid-stream."""

    def __init__(self, config, sync_delay=0.0):
        super().__init__(config)
        self.sync_delay = sync_delay
        self.scattered = []

    def gather_blocks(self, block_ids):
        bs = self.config.kv_block_size
        shape = (1, len(block_ids), bs, 1, 4)
        return (np.zeros(shape, np.float16), np.zeros(shape, np.float16))

    def scatter_blocks(self, block_ids, k, v):
        self.scattered.append(list(block_ids))

    def decode_burst(self, *args, **kw):
        out = super().decode_burst(*args, **kw)
        if not self.sync_delay:
            return out

        delay = self.sync_delay

        class _Slow:
            def __init__(self, arr):
                self._arr = np.asarray(arr)

            def __array__(self, dtype=None):
                import time

                time.sleep(delay)
                a = self._arr
                return a.astype(dtype) if dtype is not None else a

            def __getitem__(self, item):
                return _Slow(self._arr[item])

        return tuple(_Slow(a) for a in out)


def _config(**kw):
    kw.setdefault("num_kv_blocks", 64)
    kw.setdefault("max_model_len", 256)
    kw.setdefault("multi_step_decode", 4)
    return EngineConfig(
        model=ModelConfig(vocab_size=512, hidden_size=32,
                          intermediate_size=64, num_layers=1, num_heads=2,
                          num_kv_heads=1),
        max_batch_size=4, kv_block_size=8, dtype="float32",
        enable_prefix_caching=False, **kw,
    )


def _request(prompt, max_tokens, sampling=None):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=sampling or SamplingOptions(temperature=0.0),
        eos_token_ids=[],
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


async def _collect(er, limit=None):
    toks, finish = [], None
    while True:
        out = await asyncio.wait_for(er.out_queue.get(), timeout=60)
        if out is None:
            return toks, finish
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            finish = out.finish_reason
        if limit is not None and len(toks) >= limit:
            return toks, finish


def _baseline(prompt, max_tokens):
    """The unperturbed stream: one healthy scheduler, start to finish."""
    config = _config()

    async def go():
        sched = Scheduler(MigRunner(config), config,
                          flight=FlightRecorder())
        sched.start()
        er = _request(prompt, max_tokens)
        sched.add_request(er)
        try:
            return await _collect(er)
        finally:
            await sched.stop()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


# --------------------------------------------------------------------------
# unit: migrate-vs-fail decision per request class
# --------------------------------------------------------------------------


def _decode_state(er, n_tokens=6):
    """Put a request into plain decode state (committed KV, pending)."""
    toks = list(er.prompt) + list(range(100, 100 + n_tokens))
    er.seq = TokenSequence(toks, block_size=8)
    er.context_len = len(toks)
    er.pending_token = 7
    er.generated = n_tokens + 1
    return er


def test_migration_class_policy():
    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    try:
        # plain decode-state → hot
        assert migration_class(_decode_state(_request([1, 2, 3], 20))) == "hot"
        # still waiting (no KV yet) → cold
        assert migration_class(_request([1, 2, 3], 20)) == "cold"
        # mid-prefill (KV covers a prefix only) → cold
        er = _request(list(range(1, 30)), 20)
        er.seq = TokenSequence(er.prompt, block_size=8)
        er.context_len = 8
        assert migration_class(er) == "cold"
        # guided_choice rebuilds its trie on the peer → cold
        er = _decode_state(_request([1, 2, 3], 20, SamplingOptions(
            temperature=0.0, guided_choice_token_ids=[[5, 6]])))
        assert migration_class(er) == "cold"
        # guided_json's grammar cursor cannot serialize → fail
        er = _decode_state(_request([1, 2, 3], 20, SamplingOptions(
            temperature=0.0, guided_json={"type": "json_object"})))
        assert migration_class(er) == "fail"
        # prompt logprobs not yet emitted → cold (peer recomputes)
        er = _decode_state(_request([1, 2, 3], 20))
        er.want_prompt_lps = True
        assert migration_class(er) == "cold"
    finally:
        asyncio.set_event_loop(None)
        loop.close()


# --------------------------------------------------------------------------
# unit: respawn ladder (backoff + consecutive-failure budget)
# --------------------------------------------------------------------------


async def test_respawn_backoff_doubles_and_budget_gives_up(monkeypatch):
    delays = []
    real_sleep = asyncio.sleep

    async def fake_sleep(d):
        delays.append(d)
        await real_sleep(0)

    monkeypatch.setattr(asyncio, "sleep", fake_sleep)
    calls = []

    async def bad_respawner():
        calls.append(1)
        raise RuntimeError("spawn failed")

    c = RecoveryController(
        respawner=bad_respawner,
        config=RecoveryConfig(respawn_backoff_s=0.01, max_respawns=3),
    )
    assert await c._respawn("test") is False
    assert len(calls) == 3
    assert delays == [0.01, 0.02, 0.04]
    assert c.consecutive_respawn_failures == 3


async def test_respawn_success_resets_budget():
    registered = []

    async def good_respawner():
        return None

    async def register():
        registered.append(1)

    c = RecoveryController(
        respawner=good_respawner, register=register,
        config=RecoveryConfig(respawn_backoff_s=0.01, max_respawns=3),
    )
    c.consecutive_respawn_failures = 2  # prior failures, budget not blown
    assert await c._respawn("test") is True
    assert c.consecutive_respawn_failures == 0
    assert registered == [1]


# --------------------------------------------------------------------------
# unit: drain gates, router exclusion, admission drain
# --------------------------------------------------------------------------


async def test_set_draining_gates_admission_until_cleared():
    config = _config()
    sched = Scheduler(MigRunner(config), config, flight=FlightRecorder())
    sched.set_draining(True)
    sched.start()
    er = _request([1, 2, 3], 4)
    sched.add_request(er)
    await asyncio.sleep(0.1)
    assert er in sched.waiting and er.slot < 0, \
        "draining scheduler admitted a request"
    assert sched.metrics()["draining"] is True
    assert sched.watchdog_probe()["stopping"] is True
    sched.set_draining(False)
    toks, finish = await _collect(er)
    assert len(toks) == 4
    await sched.stop()


def test_router_never_picks_draining_worker():
    ks = KvScheduler(block_size=8)
    ks.update_metrics("sick", ForwardPassMetrics(
        request_total_slots=4, kv_total_blocks=64, draining=True))
    ks.update_metrics("ok", ForwardPassMetrics(
        request_total_slots=4, kv_total_blocks=64))
    for _ in range(20):
        assert ks.schedule(32, OverlapScores()).worker_id == "ok"
    assert ks.draining_skips == 20
    ks.update_metrics("ok", ForwardPassMetrics(
        request_total_slots=4, kv_total_blocks=64, draining=True))
    with pytest.raises(AllWorkersBusy):
        ks.schedule(32, OverlapScores())


async def test_admission_draining_rejects_and_flushes_queued():
    ac = AdmissionController(AdmissionConfig(
        limit=1, queue_depth=4, queue_timeout_s=30.0))
    await ac.acquire(1)
    queued = asyncio.ensure_future(ac.acquire(2))
    await asyncio.sleep(0.01)
    ac.set_draining(True)
    with pytest.raises(AdmissionRejected) as ei:
        await queued
    assert ei.value.outcome == "draining"
    with pytest.raises(AdmissionRejected) as ei:
        await ac.acquire(2)
    assert ei.value.outcome == "draining"
    ac.set_draining(False)
    ac.release()
    await ac.acquire(2)  # admits again after the drain clears


# --------------------------------------------------------------------------
# POST /admin/drain
# --------------------------------------------------------------------------


async def test_admin_drain_endpoint():
    import aiohttp

    from dynamo_tpu.http.service import HttpService, ModelManager

    service = HttpService(ModelManager(), host="127.0.0.1", port=0)
    await service.start()
    calls = {}

    async def drainer(mode, respawn):
        calls.update(mode=mode, respawn=respawn)
        return {"migrated": 2, "failed": 0}

    try:
        async with aiohttp.ClientSession() as s:
            url = f"http://127.0.0.1:{service.port}/admin/drain"
            async with s.post(url) as r:
                assert r.status == 501  # no controller attached
            service.drainer = drainer
            async with s.post(url + "?mode=migrate&respawn=1") as r:
                assert r.status == 200
                assert (await r.json())["migrated"] == 2
            async with s.post(url + "?mode=bogus") as r:
                assert r.status == 400
    finally:
        await service.stop()
    assert calls == {"mode": "migrate", "respawn": True}


# --------------------------------------------------------------------------
# migration plane: partial-stream poison on the receiver
# --------------------------------------------------------------------------


async def test_receiver_poisons_partial_migration():
    config = _config()
    dst = Scheduler(MigRunner(config), config, flight=FlightRecorder())
    dst.start()
    server = await MigrationServer(
        MigrationSink(dst, dst.runner)).start()
    try:
        state = MigrationState(
            request_id="m1", trace_id="t1",
            req=_request([1, 2, 3], 8).req.to_wire(),
            committed_tokens=[1, 2, 3, 9], resume_tokens=[],
            pending_token=7, generated=2, base_key=[1, 2],
            prompt_lps_emitted=False, kv_block_size=config.kv_block_size,
        )
        reader, writer = await asyncio.open_connection(
            server.host, server.port)
        pack_frame(writer, {"type": "mig_begin", "state": state.to_wire(),
                       "nblocks": 2})
        await writer.drain()
        ack = await read_header(reader, "migration")
        assert ack["ok"]
        assert dst.allocator.used == 2  # reservation held
        writer.close()  # sender dies before commit
        for _ in range(50):
            if dst.allocator.used == 0:
                break
            await asyncio.sleep(0.02)
        assert dst.allocator.used == 0, "poisoned reservation leaked blocks"
        assert all(s is None for s in dst.slots), "nothing may be installed"
    finally:
        await server.close()
        await dst.stop()


# --------------------------------------------------------------------------
# live migration e2e: healthy drain (rolling update), hot KV transfer
# --------------------------------------------------------------------------


def _drive_migration(wedge: bool, max_tokens=48, conn_drop=False):
    """Run a request on a source engine, disturb it mid-stream (admin
    drain, or a DYN_FAULT wedge + watchdog trip), and return everything
    the assertions need."""
    config = _config()
    prompt = [1, 17, 43]
    out = {}

    async def go():
        src_runner = MigRunner(config, sync_delay=0.02)
        dst_runner = MigRunner(config)
        src = Scheduler(src_runner, config, flight=FlightRecorder())
        dst = Scheduler(dst_runner, config, flight=FlightRecorder())
        src.start()
        dst.start()
        server = await MigrationServer(
            MigrationSink(dst, dst_runner)).start()
        peers = [{"host": server.host, "port": server.port,
                  "engine_id": "dst"}]
        if conn_drop:
            # first attempt's connection is dropped by the fault — the
            # controller must fail over to the next peer (same receiver)
            peers = peers + peers
        wd = None
        if wedge:
            wd = StallWatchdog(
                probe=src.watchdog_probe, requests=src.request_table,
                flight=src.flight, interval_s=0.02, stall_s=0.15,
            ).start()
        respawned = []
        hooks = []

        async def respawner():
            respawned.append(1)
            return None

        async def register():
            hooks.append("register")

        async def deregister():
            hooks.append("deregister")

        controller = RecoveryController(
            engine_id="src", scheduler=src, runner=src_runner,
            watchdog=wd, peers=lambda: peers, respawner=respawner,
            register=register, deregister=deregister,
            config=RecoveryConfig(drain_grace_s=0.05,
                                  respawn_backoff_s=0.01),
            flight=src.flight,
        ).attach()

        er = _request(prompt, max_tokens)
        src.add_request(er)
        toks, finish = await _collect(er, limit=6)  # stream is live
        assert finish is None, "request finished before the disturbance"
        if wedge:
            # next decode sync wedges in its executor thread; detection
            # and recovery must be fully automatic from here
            faults.arm("decode_burst_hang", "once")
        else:
            if conn_drop:
                faults.arm("transfer_conn_drop", "once")
            summary = await controller.drain(hard=False, reason="admin")
            out["summary"] = summary
        rest, finish = await _collect(er)
        out["toks"], out["finish"] = toks + rest, finish
        if wedge:
            out["trips"] = [t["reason"] for t in wd.trips]
            # the automatic ladder records its summary when it completes
            for _ in range(100):
                if controller.recoveries:
                    break
                await asyncio.sleep(0.02)
            out["summary"] = controller.recoveries[0]
            out["respawned"] = bool(respawned)
        out["hooks"] = hooks
        out["stages"] = [s for s, _ in er.ctx.stages]
        # cluster-stitched trace material: the peer's span export rode
        # the mig_end frame back into the source context
        out["remote"] = list(er.ctx.remote_spans)
        out["src_used"] = src.allocator.used
        out["src_metrics"] = src.metrics()
        out["dst_steps"] = dst.steps
        out["dst_scattered"] = list(dst_runner.scattered)
        out["migrations"] = controller.registry.render()
        faults.release()
        if wd is not None:
            await wd.stop()
        await controller.close()
        await server.close()
        await dst.stop()
        await src.stop()
        out["dst_used"] = dst.allocator.used

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
    out["want"] = _baseline(prompt, max_tokens)
    return out


def test_admin_drain_migrates_hot_stream_byte_identical():
    out = _drive_migration(wedge=False)
    assert out["summary"]["migrated"] == 1
    assert out["summary"]["failed"] == 0
    # byte-identical continuation across the engine hop
    assert (out["toks"], out["finish"]) == out["want"]
    # hot: the KV actually crossed the wire into the peer's cache
    assert out["dst_scattered"], "no KV was scattered on the peer"
    assert out["dst_steps"] > 0, "the peer never decoded"
    assert 'mode="hot",outcome="committed"' in out["migrations"] \
        or 'outcome="committed",mode="hot"' in out["migrations"]
    # zero leaks on either side, and the hop is traceable from BOTH
    # ends: the source stamps migration.relay at commit, the peer's
    # migration.resume (and its decode tail) ships back on mig_end
    assert out["src_used"] == 0
    assert out["dst_used"] == 0
    assert "migration.relay" in out["stages"]
    peer_sets = [rs for rs in out["remote"]
                 if rs["source"] == "migration_peer"]
    assert peer_sets, "peer span export never arrived on mig_end"
    peer_names = [n for n, _ in peer_sets[0]["spans"]]
    assert "migration.resume" in peer_names
    assert "completion" in peer_names
    assert "deregister" in out["hooks"]


def test_migration_conn_drop_fails_over_to_next_peer():
    out = _drive_migration(wedge=False, conn_drop=True)
    assert out["summary"]["migrated"] == 1
    assert (out["toks"], out["finish"]) == out["want"]
    assert out["src_used"] == 0 and out["dst_used"] == 0


# --------------------------------------------------------------------------
# the chaos e2e: wedge → trip → drain → migrate → respawn
# --------------------------------------------------------------------------


def test_wedge_trips_drain_migrate_respawn():
    out = _drive_migration(wedge=True)
    # detection: exactly one decode_stall for one wedge
    assert out["trips"] == ["decode_stall"]
    # recovery: automated drain migrated the in-flight request (cold —
    # a wedged device cannot be gathered from) and respawned
    assert out["summary"]["reason"] == "decode_stall"
    assert out["summary"]["migrated"] == 1
    assert out["summary"]["failed"] == 0
    assert out["summary"]["respawned"] is True
    assert out["respawned"]
    assert out["hooks"] == ["deregister", "register"]
    # the continued stream is byte-identical to an unwedged run
    assert (out["toks"], out["finish"]) == out["want"]
    # zero leaked blocks on the source, none on the target either
    assert out["src_used"] == 0
    assert out["dst_used"] == 0
    # the draining snapshot excludes the sick worker from routing
    sick = ForwardPassMetrics.from_wire(out["src_metrics"])
    assert sick.draining is True
    ks = KvScheduler(block_size=8)
    ks.update_metrics("src", sick)
    ks.update_metrics("dst", ForwardPassMetrics(
        request_total_slots=4, kv_total_blocks=64))
    for _ in range(10):
        assert ks.schedule(16, OverlapScores()).worker_id == "dst"
    # the hop shows up in the request's stitched trace from both ends:
    # relay on the source, resume (cold re-prefill + decode) on the peer
    assert "migration.relay" in out["stages"]
    peer_sets = [rs for rs in out["remote"]
                 if rs["source"] == "migration_peer"]
    assert peer_sets, "peer span export never arrived on mig_end"
    assert "migration.resume" in [n for n, _ in peer_sets[0]["spans"]]


# --------------------------------------------------------------------------
# supervised-child satellite: restart telemetry + down listeners
# --------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_child_exit_fault_respawns_with_restart_metric(tmp_path):
    from test_subprocess_engine import ECHO_ENGINE, child_env, write_engine

    from dynamo_tpu.llm.engines.subprocess_host import SubprocessEngine
    from dynamo_tpu.runtime.engine import Context, EngineError

    env = child_env()
    env["DYN_FAULT"] = "child_exit:once"
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, ECHO_ENGINE), child_env=env,
        restart_backoff_s=0.05,
    )
    downs = []
    eng.add_down_listener(downs.append)
    try:
        # first request: the child exits hard before serving it
        with pytest.raises(EngineError):
            async for _ in eng.generate(Context({"token_ids": [1]})):
                pass
        # disarm: DYN_FAULT is re-parsed by every fresh child, so the
        # "once" would otherwise fire again in the respawned process
        eng.child_env.pop("DYN_FAULT", None)
        # next request respawns and serves
        toks = [
            t
            for c in [c async for c in eng.generate(
                Context({"token_ids": [3, 1]}))]
            for t in c.get("token_ids", [])
        ]
        assert toks == [3, 1]
        assert eng.spawn_count == 2
        assert downs, "down listener never fired"
        text = eng.host_registry.render()
        assert "dynamo_engine_restarts_total" in text
        assert 'dynamo_engine_restarts_total{reason="exit"} 1.0' in text \
            or 'dynamo_engine_restarts_total{reason="disconnect"} 1.0' in text
    finally:
        await eng.close()


# --------------------------------------------------------------------------
# draining rejections are retryable (engine facade + HTTP mapping)
# --------------------------------------------------------------------------


async def test_draining_engine_rejects_with_retryable_error():
    from dynamo_tpu.engine.serving import JaxServingEngine
    from dynamo_tpu.runtime.engine import Context, EngineDrainingError

    config = _config()
    sched = Scheduler(MigRunner(config), config, flight=FlightRecorder())
    engine = JaxServingEngine(sched.runner, sched, config)
    sched.set_draining(True)
    with pytest.raises(EngineDrainingError):
        async for _ in engine.generate(Context(_request([1, 2, 3], 4).req)):
            pass


async def test_http_maps_draining_to_503_with_retry_after():
    import aiohttp

    from dynamo_tpu.http.service import HttpService, ModelManager
    from dynamo_tpu.runtime.engine import EngineDrainingError

    class DrainingEngine:
        def generate(self, ctx):
            async def gen():
                raise EngineDrainingError("engine is draining")
                yield  # pragma: no cover

            return gen()

    manager = ModelManager()
    manager.add_chat_model("m", DrainingEngine())
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={"model": "m",
                      "messages": [{"role": "user", "content": "hi"}]},
            ) as r:
                assert r.status == 503
                assert r.headers.get("Retry-After") == "1"
                body = await r.json()
                assert body["error"]["type"] == "service_unavailable"
    finally:
        await service.stop()


async def test_receiver_nacks_oversized_migration():
    """A sequence the target cannot hold (beyond its max_model_len /
    block-table width) must nack at reserve — before any state mutates
    on the healthy peer — not blow up inside install."""
    from dynamo_tpu.recovery import MigrationRejected

    config = _config(max_model_len=64)
    dst = Scheduler(MigRunner(config), config, flight=FlightRecorder())
    sink = MigrationSink(dst, dst.runner)
    # hot: 100 committed tokens >= the target's 64-token horizon
    state = MigrationState(
        request_id="big", trace_id="t",
        req=_request(list(range(1, 10)), 8).req.to_wire(),
        committed_tokens=list(range(1, 101)), resume_tokens=[],
        pending_token=7, generated=91, base_key=[1, 2],
        prompt_lps_emitted=False, kv_block_size=config.kv_block_size,
    )
    with pytest.raises(MigrationRejected):
        sink.reserve(state, 13)
    # cold: prompt + resume past the horizon nacks too
    state2 = MigrationState(
        request_id="big2", trace_id="t",
        req=_request(list(range(1, 60)), 8).req.to_wire(),
        committed_tokens=[], resume_tokens=list(range(1, 10)),
        pending_token=-1, generated=9, base_key=[1, 2],
        prompt_lps_emitted=False, kv_block_size=config.kv_block_size,
    )
    with pytest.raises(MigrationRejected):
        sink.reserve(state2, 0)
    # geometry mismatch on the block table width
    state3 = MigrationState(
        request_id="wide", trace_id="t",
        req=_request([1, 2, 3], 8).req.to_wire(),
        committed_tokens=[1, 2, 3, 4], resume_tokens=[],
        pending_token=7, generated=2, base_key=[1, 2],
        prompt_lps_emitted=False, kv_block_size=config.kv_block_size,
    )
    with pytest.raises(MigrationRejected):
        sink.reserve(state3, config.blocks_per_seq + 1)
    assert dst.allocator.used == 0
    assert all(s is None for s in dst.slots)


# --------------------------------------------------------------------------
# stream re-bind: the source relay exits at the handoff (ISSUE 12
# satellite; the PR 8 carry-over)
# --------------------------------------------------------------------------


async def test_stream_rebind_lets_source_relay_exit():
    """A follow_migrated_stream consumer sees the `migrated` control
    frame, attaches directly to the peer, the peer's pump hands off
    (mig_handoff → the source's relay ends while the peer is STILL
    generating), and the continued stream is byte-identical."""
    from dynamo_tpu.recovery.migration import follow_migrated_stream
    from dynamo_tpu.telemetry.flight import flight_recorder

    config = _config()
    prompt = [1, 17, 43]
    max_tokens = 48
    src_runner = MigRunner(config, sync_delay=0.02)
    # the peer decodes slowly too, so the attach handshake (and the
    # handoff) reliably lands mid-stream, not after it ended
    dst_runner = MigRunner(config, sync_delay=0.02)
    src = Scheduler(src_runner, config, flight=FlightRecorder())
    dst = Scheduler(dst_runner, config, flight=FlightRecorder())
    src.start()
    dst.start()
    server = await MigrationServer(MigrationSink(dst, dst_runner)).start()
    controller = RecoveryController(
        engine_id="src", scheduler=src, runner=src_runner,
        peers=lambda: [{"host": server.host, "port": server.port,
                        "engine_id": "dst"}],
        config=RecoveryConfig(drain_grace_s=0.05),
        flight=src.flight,
    )
    er = _request(prompt, max_tokens)
    src.add_request(er)

    async def queue_stream():
        while True:
            out = await er.out_queue.get()
            if out is None:
                return
            yield out

    toks = []
    finish = None

    async def consume():
        nonlocal finish
        stream = follow_migrated_stream(queue_stream(), ctx=er.ctx)
        async for out in stream:
            assert out.migrated is None, "control frame leaked"
            toks.extend(out.token_ids)
            if out.finish_reason is not None:
                finish = out.finish_reason

    # how many tokens the CLIENT had when the source's relay duty
    # ended — the handoff must land mid-stream, not at its end. Taken at
    # the relay task's own completion: the relay lives a millisecond or
    # two, and a poll of controller._relays can miss it altogether.
    relay_done = asyncio.get_running_loop().create_future()
    hold = controller._hold

    def hold_and_watch(relay):
        relay.add_done_callback(
            lambda _t: relay_done.done() or relay_done.set_result(len(toks)))
        hold(relay)

    controller._hold = hold_and_watch

    loop = asyncio.get_running_loop()
    task = loop.create_task(consume())
    while len(toks) < 6:  # the stream is live on the source
        await asyncio.sleep(0.01)
    summary = await controller.drain(hard=False, reason="admin")
    assert summary["migrated"] == 1 and summary["failed"] == 0
    relay_done_at_token = await asyncio.wait_for(relay_done, timeout=60)
    await asyncio.wait_for(task, timeout=60)

    # _baseline drives its own event loop — run it in a thread
    want = await asyncio.to_thread(_baseline, prompt, max_tokens)
    assert (toks, finish) == want
    # the handoff actually happened: the source's relay duty ended
    # while the peer was still generating (the source could exit here)
    kinds = [e["kind"] for e in flight_recorder().snapshot()]
    assert "recovery.migrate_handoff" in kinds
    assert relay_done_at_token is not None
    assert relay_done_at_token < len(want[0]), (
        "relay only ended at stream end — no handoff happened")
    # the peer's span export arrived over the ATTACHED connection
    peer_sets = [rs for rs in er.ctx.remote_spans
                 if rs["source"] == "migration_peer"]
    assert peer_sets and "migration.resume" in [
        n for n, _ in peer_sets[0]["spans"]]
    # zero leaks on either side
    assert src.allocator.used == 0
    await controller.close()
    await server.close()
    await dst.stop()
    await src.stop()
    assert dst.allocator.used == 0


async def test_rebind_attach_failure_falls_back_to_relay():
    """If the consumer cannot reach the peer (e.g. a NATed client), the
    relay keeps carrying the stream to its end — byte-identical, no
    error surfaced."""
    from dynamo_tpu.protocols.common import EngineOutput, FinishReason
    from dynamo_tpu.recovery.migration import follow_migrated_stream

    async def fake_stream():
        # a source stream whose migrated frame points at a dead port,
        # then relays the full stream itself (what the source does
        # when nobody attaches)
        yield EngineOutput(token_ids=[1])
        yield EngineOutput(migrated={"host": "127.0.0.1", "port": 9,
                                     "resume_id": "x"})
        yield EngineOutput(token_ids=[2])
        yield EngineOutput(token_ids=[3],
                           finish_reason=FinishReason.LENGTH)

    toks, finish = [], None
    async for out in follow_migrated_stream(fake_stream()):
        toks.extend(out.token_ids)
        finish = out.finish_reason or finish
    assert toks == [1, 2, 3]
    assert finish == FinishReason.LENGTH
