"""Out-of-process engine hosting: crash containment, heartbeat, respawn.

VERDICT r3 item 3 — the analog of the reference's supervised engine
subprocesses (reference: lib/engines/sglang/src/worker.rs:307-445). The
acceptance bar: kill -9 the engine mid-stream → the request fails
cleanly (error prologue when nothing streamed yet), the worker stays up,
and the next request serves off a respawned child.
"""

import asyncio
import os
import signal

import pytest

from dynamo_tpu.llm.engines.subprocess_host import (
    EngineStreamDied,
    SubprocessEngine,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext, Context, EngineError
from dynamo_tpu.runtime.network import _pump

def child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


ECHO_ENGINE = """
import asyncio

async def generate(request):
    for t in request.get("token_ids", []):
        yield {"token_ids": [t]}
    yield {"token_ids": [], "finish_reason": "stop"}
"""

SLOW_ENGINE = """
import asyncio

async def generate(request):
    yield {"token_ids": [1]}
    await asyncio.sleep(600)
    yield {"token_ids": [2]}
"""

STALL_BEFORE_FIRST = """
import asyncio

async def generate(request):
    await asyncio.sleep(600)
    yield {"token_ids": [1]}
"""

WEDGED_ENGINE = """
import time

async def generate(request):
    yield {"token_ids": [1]}
    time.sleep(600)   # blocks the child's event loop: pings go unanswered
    yield {"token_ids": [2]}
"""

RAISING_INIT = """
async def initialize(engine_args):
    raise RuntimeError("bad credentials")

async def generate(request):
    yield {}
"""

USER_ERROR_ENGINE = """
async def generate(request):
    yield {"token_ids": [7]}
    raise ValueError("model exploded")
"""


def write_engine(tmp_path, src, name="eng.py"):
    p = tmp_path / name
    p.write_text(src)
    return str(p)


@pytest.mark.asyncio
async def test_subprocess_engine_streams_and_closes(tmp_path):
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, ECHO_ENGINE), child_env=child_env()
    )
    try:
        chunks = [c async for c in eng.generate(Context(
            {"token_ids": [3, 1, 4]}
        ))]
        toks = [t for c in chunks for t in c.get("token_ids", [])]
        assert toks == [3, 1, 4]
        assert chunks[-1]["finish_reason"] == "stop"
        # concurrent streams multiplex over the one socket
        outs = await asyncio.gather(*[
            _collect(eng, {"token_ids": [i, i + 1]}) for i in range(4)
        ])
        assert outs == [[i, i + 1] for i in range(4)]
        assert eng.spawn_count == 1
    finally:
        await eng.close()


async def _collect(eng, payload):
    return [
        t
        for c in [c async for c in eng.generate(Context(payload))]
        for t in c.get("token_ids", [])
    ]


@pytest.mark.asyncio
async def test_kill9_midstream_fails_cleanly_and_respawns(tmp_path):
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, SLOW_ENGINE), child_env=child_env(),
        restart_backoff_s=0.05,
    )
    try:
        stream = eng.generate(Context({"token_ids": []})).__aiter__()
        first = await asyncio.wait_for(stream.__anext__(), timeout=30)
        assert first == {"token_ids": [1]}

        os.kill(eng._proc.pid, signal.SIGKILL)
        with pytest.raises(EngineStreamDied):
            await asyncio.wait_for(stream.__anext__(), timeout=30)

        # the worker survives: the next request respawns the child and
        # serves (swap the file to the echo engine so the respawned child
        # — which re-reads it — finishes its stream)
        write_engine(tmp_path, ECHO_ENGINE)
        chunks = [c async for c in eng.generate(Context({"token_ids": [9]}))]
        assert chunks[0] == {"token_ids": [9]}
        assert chunks[-1]["finish_reason"] == "stop"
        assert eng.spawn_count == 2
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_kill9_before_first_output_maps_to_error_prologue(tmp_path):
    """Through the real network plane: a request whose engine dies before
    any output must produce {t: prologue, ok: False}, not a hang or an
    empty stream."""
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, STALL_BEFORE_FIRST), child_env=child_env(),
        restart_backoff_s=0.05,
    )
    sent = []

    async def send(frame):
        sent.append(frame)

    async def stream_fn(ctx):
        async for c in eng.generate(Context({"token_ids": []}, ctx)):
            yield c

    try:
        ctx = AsyncEngineContext("req-1")
        pump = asyncio.create_task(_pump(stream_fn, ctx, send))
        await asyncio.sleep(1.0)  # request is in flight, nothing streamed
        os.kill(eng._proc.pid, signal.SIGKILL)
        await asyncio.wait_for(pump, timeout=30)
        assert sent, "no frames reached the requester"
        assert sent[0]["t"] == "prologue"
        assert sent[0]["ok"] is False
        assert "engine" in sent[0]["error"]
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_wedged_child_detected_by_heartbeat_and_killed(tmp_path):
    """A child whose event loop is blocked (the compile-hang failure mode)
    never exits on its own — only the missed-pong path can catch it."""
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, WEDGED_ENGINE), child_env=child_env(),
        heartbeat_interval_s=0.2, heartbeat_misses=2, restart_backoff_s=0.05,
    )
    try:
        stream = eng.generate(Context({"token_ids": []})).__aiter__()
        first = await asyncio.wait_for(stream.__anext__(), timeout=30)
        assert first == {"token_ids": [1]}
        pid = eng._proc.pid
        with pytest.raises(EngineStreamDied) as ei:
            await asyncio.wait_for(stream.__anext__(), timeout=30)
        assert "heartbeat" in str(ei.value)
        # the wedged process was actually killed, not leaked
        for _ in range(50):
            try:
                os.kill(pid, 0)
                await asyncio.sleep(0.1)
            except ProcessLookupError:
                break
        else:
            pytest.fail(f"wedged child {pid} still alive")
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_user_error_is_engine_error_not_restart(tmp_path):
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, USER_ERROR_ENGINE), child_env=child_env(),
    )
    try:
        chunks = []
        with pytest.raises(EngineError, match="model exploded"):
            async for c in eng.generate(Context({"token_ids": []})):
                chunks.append(c)
        assert chunks == [{"token_ids": [7]}]
        # a user exception is NOT a process failure: the same child serves
        # the next request (which, for this engine file, errors the same way)
        assert eng.spawn_count == 1
        chunks2 = []
        with pytest.raises(EngineError, match="model exploded"):
            async for c in eng.generate(Context({"token_ids": []})):
                chunks2.append(c)
        assert chunks2 == [{"token_ids": [7]}]
        assert eng.spawn_count == 1
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_init_error_reported_not_retried(tmp_path):
    with pytest.raises(EngineError, match="bad credentials"):
        await SubprocessEngine.load(
            write_engine(tmp_path, RAISING_INIT), child_env=child_env(),
        )


@pytest.mark.asyncio
async def test_cli_isolate_engine_flag_wires_subprocess_host(tmp_path):
    import argparse

    from dynamo_tpu.cli.run import _load_python_engine
    from dynamo_tpu.llm.engines.python_file import PythonFileEngine

    path = write_engine(tmp_path, ECHO_ENGINE)
    flags = argparse.Namespace(isolate_engine=False, extra_engine_args=None)
    eng = await _load_python_engine(path, flags)
    assert isinstance(eng, PythonFileEngine)

    flags.isolate_engine = True
    # the CLI path inherits os.environ in the child; scrub for CI the same
    # way production scrubs nothing (the hook is healthy there)
    import unittest.mock

    with unittest.mock.patch.dict(os.environ, child_env(), clear=True):
        eng = await _load_python_engine(path, flags)
    try:
        assert isinstance(eng, SubprocessEngine)
        assert await _collect(eng, {"token_ids": [5]}) == [5]
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_http_service_survives_engine_kill(tmp_path):
    """The full worker surface: an OpenAI-level subprocess engine behind
    the HTTP frontend; kill -9 the engine child between requests → the
    frontend process stays up and the next request serves."""
    import aiohttp

    from dynamo_tpu.http.service import HttpService, ModelManager

    OPENAI_ECHO = """
import time, uuid

async def generate(request):
    text = request["messages"][-1]["content"]
    yield {
        "id": "chatcmpl-" + uuid.uuid4().hex,
        "object": "chat.completion.chunk",
        "created": int(time.time()),
        "model": request.get("model", "sub"),
        "choices": [{"index": 0, "delta": {"role": "assistant",
                                           "content": text},
                     "finish_reason": "stop"}],
    }
"""
    path = write_engine(tmp_path, OPENAI_ECHO, "openai_echo.py")
    eng = await SubprocessEngine.load(
        path, child_env=child_env(), restart_backoff_s=0.05,
    )
    manager = ModelManager()
    manager.add_chat_model("sub", eng)
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async def ask(text):
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{service.port}/v1/chat/completions",
                    json={"model": "sub",
                          "messages": [{"role": "user", "content": text}]},
                ) as r:
                    return r.status, await r.json()

        status, body = await ask("hello")
        assert status == 200
        assert body["choices"][0]["message"]["content"] == "hello"

        os.kill(eng._proc.pid, signal.SIGKILL)
        # wait for the supervisor to notice (read-loop EOF) so the next
        # request deterministically takes the respawn path
        for _ in range(100):
            if eng._proc is None:
                break
            await asyncio.sleep(0.05)
        # the frontend survives; the next request respawns the engine
        status, body = await ask("again")
        assert status == 200
        assert body["choices"][0]["message"]["content"] == "again"
        assert eng.spawn_count == 2
    finally:
        await service.stop()
        await eng.close()


@pytest.mark.asyncio
async def test_stop_cancels_child_stream(tmp_path):
    eng = await SubprocessEngine.load(
        write_engine(tmp_path, SLOW_ENGINE), child_env=child_env(),
    )
    try:
        ctx = AsyncEngineContext("req-s")
        stream = eng.generate(Context({"token_ids": []}, ctx)).__aiter__()
        first = await asyncio.wait_for(stream.__anext__(), timeout=30)
        assert first == {"token_ids": [1]}
        ctx.stop_generating()
        # the child cancels the generator task and ends the stream
        with pytest.raises(StopAsyncIteration):
            while True:
                await asyncio.wait_for(stream.__anext__(), timeout=30)
        # engine still healthy for the next request (first chunk only —
        # this engine file then sleeps by design)
        ctx2 = AsyncEngineContext("req-s2")
        stream2 = eng.generate(Context({"token_ids": []}, ctx2)).__aiter__()
        assert await asyncio.wait_for(stream2.__anext__(), timeout=30) == \
            {"token_ids": [1]}
        ctx2.stop_generating()
        assert eng.spawn_count == 1
    finally:
        await eng.close()


# ---------------------------------------------------------------------------
# @jax: the native engine hosted out-of-process (VERDICT r4 item 5 — the
# actual compile-hang hazard runs as a supervised child; reference analog
# lib/engines/sglang/src/worker.rs:307-445)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def jax_model_dir(tmp_path_factory):
    import json

    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from fixtures import make_model_dir

    d = make_model_dir(tmp_path_factory.mktemp("subproc_jax"), name="tiny-hf")
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    LlamaForCausalLM(cfg).save_pretrained(d, safe_serialization=True)
    with open(os.path.join(d, "config.json")) as f:
        c = json.load(f)
    c["eos_token_id"] = 2
    c["bos_token_id"] = 1
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(c, f)
    return str(d)


def _jax_flags(model_dir):
    return {
        "model_path": model_dir, "model_name": "tiny-hf",
        "kv_block_size": 8, "max_batch_size": 2, "max_model_len": 64,
        "extra_engine_args": None, "isolate_engine": False,
    }


def _greedy_req(n=4):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    return PreprocessedRequest(
        token_ids=[3, 7, 11],
        stop_conditions=StopConditions(max_tokens=n),
        sampling_options=SamplingOptions(temperature=0.0),
    ).to_wire()


@pytest.mark.asyncio
async def test_jax_engine_hosted_in_subprocess(jax_model_dir):
    """@jax child: serve → SIGSTOP (a wedged Mosaic compile freezes the
    child's loop exactly like this) → heartbeat kill → respawn → serve."""
    from dynamo_tpu.engine.block_allocator import KvEventSink

    kv_events = []
    sink = KvEventSink(
        on_stored=lambda h, p: kv_events.append(("stored", list(h), p)),
        on_removed=lambda h: kv_events.append(("removed", list(h))),
    )
    eng = await SubprocessEngine.load(
        "@jax", {"flags": _jax_flags(jax_model_dir)},
        child_env=child_env(), init_timeout_s=300.0,
        heartbeat_interval_s=0.3, heartbeat_misses=3,
        restart_backoff_s=0.05, events=sink,
    )
    try:
        toks = await asyncio.wait_for(_collect(eng, _greedy_req()), 60)
        assert len(toks) == 4
        assert eng.spawn_count == 1

        # inject the wedge: freeze the child process (its event loop —
        # and with it every pong — stops, like a hung in-process compile)
        pid = eng._proc.pid
        os.kill(pid, signal.SIGSTOP)
        with pytest.raises((EngineError, EngineStreamDied)) as ei:
            await asyncio.wait_for(_collect(eng, _greedy_req()), 60)
        assert "heartbeat" in str(ei.value)
        # SIGKILL still lands on a SIGSTOPped pid; reaped by the host
        for _ in range(100):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                break
            await asyncio.sleep(0.1)
        else:
            os.kill(pid, signal.SIGCONT)
            pytest.fail(f"wedged jax child {pid} still alive")

        # serving resumes on a respawned child, greedy stream identical
        toks2 = await asyncio.wait_for(_collect(eng, _greedy_req()), 120)
        assert toks2 == toks
        assert eng.spawn_count == 2
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_jax_subprocess_forwards_kv_events_and_metrics(jax_model_dir):
    """The child's allocator events replay into the worker-side sink
    (KV-aware routing keeps working out-of-process) and engine metrics
    ride the heartbeat pongs."""
    from dynamo_tpu.engine.block_allocator import KvEventSink

    kv_events = []
    sink = KvEventSink(
        on_stored=lambda h, p: kv_events.append(("stored", list(h), p)),
        on_removed=lambda h: kv_events.append(("removed", list(h))),
    )
    eng = await SubprocessEngine.load(
        "@jax", {"flags": _jax_flags(jax_model_dir)},
        child_env=child_env(), init_timeout_s=300.0,
        heartbeat_interval_s=0.2, events=sink,
    )
    try:
        # a full-block prompt (block size 8) gets its prefix registered
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )

        req = PreprocessedRequest(
            token_ids=list(range(3, 3 + 16)),
            stop_conditions=StopConditions(max_tokens=2),
            sampling_options=SamplingOptions(temperature=0.0),
        ).to_wire()
        toks = await asyncio.wait_for(_collect(eng, req), 60)
        assert len(toks) == 2
        for _ in range(100):  # events ride the async pump; wait briefly
            if any(e[0] == "stored" for e in kv_events):
                break
            await asyncio.sleep(0.05)
        assert any(e[0] == "stored" for e in kv_events)
        # metrics piggyback on pongs
        for _ in range(100):
            if eng.metrics():
                break
            await asyncio.sleep(0.05)
        assert isinstance(eng.metrics(), dict) and eng.metrics()
    finally:
        await eng.close()


@pytest.mark.asyncio
async def test_child_death_purges_advertised_kv_hashes():
    """A dead child takes its allocator with it: every block hash it
    advertised as stored must replay as removed into the worker-side
    sink, or KV-aware routing would chase prefix hits that cannot
    occur (code-review r5 finding)."""
    from dynamo_tpu.engine.block_allocator import KvEventSink

    events = []
    sink = KvEventSink(
        on_stored=lambda h, p: events.append(("stored", list(h))),
        on_removed=lambda h: events.append(("removed", list(h))),
    )
    eng = SubprocessEngine("@unused", events=sink)
    eng._on_kv_frame({"t": "kv", "ev": "stored", "hashes": [11, 12],
                      "parent": None})
    eng._on_kv_frame({"t": "kv", "ev": "stored", "hashes": [13],
                      "parent": 12})
    eng._on_kv_frame({"t": "kv", "ev": "removed", "hashes": [12]})
    assert eng._kv_live_hashes == {11, 13}
    await eng._on_child_down("test kill")
    assert ("removed", [11, 13]) in events
    assert eng._kv_live_hashes == set()
