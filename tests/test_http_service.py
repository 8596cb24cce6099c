"""HTTP frontend tests: real aiohttp server + aiohttp client, SSE + metrics."""

import asyncio
import json

import aiohttp
import pytest

from dynamo_tpu.http.service import (
    HttpService,
    ModelManager,
    ModelWatcher,
    register_model,
    unregister_model,
)
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.engines.echo import EchoEngineCore, EchoEngineFull
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.llm.tokenizer import HFTokenizer
from dynamo_tpu.protocols import sse
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.pipeline import build_pipeline
from dynamo_tpu.runtime.transports.memory import MemoryHub

from fixtures import make_model_dir


async def start_echo_service():
    manager = ModelManager()
    manager.add_chat_model("echo", EchoEngineFull())
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    return service


@pytest.mark.asyncio
async def test_models_and_health():
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{service.port}/v1/models") as r:
                body = await r.json()
                assert r.status == 200
                assert body["data"][0]["id"] == "echo"
            async with s.get(f"http://127.0.0.1:{service.port}/health") as r:
                assert (await r.json())["status"] == "ok"
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_chat_streaming_sse():
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "echo",
                    "messages": [{"role": "user", "content": "one two three"}],
                    "stream": True,
                },
            ) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/event-stream")
                raw = await r.read()
        payloads = list(sse.parse_stream(raw))
        text = "".join(
            c["choices"][0].get("delta", {}).get("content") or ""
            for c in payloads if c.get("choices")
        )
        assert text.strip() == "one two three"
        assert raw.decode().strip().endswith("data: [DONE]")
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_chat_non_streaming_aggregates():
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "echo",
                    "messages": [{"role": "user", "content": "hello there"}],
                },
            ) as r:
                assert r.status == 200
                body = await r.json()
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["content"].strip() == "hello there"
        assert body["choices"][0]["finish_reason"] == "stop"
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_unknown_model_404_and_bad_body_400():
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={"model": "nope", "messages": [{"role": "user", "content": "x"}]},
            ) as r:
                assert r.status == 404
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                data=b"not json",
            ) as r:
                assert r.status == 400
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={"model": "echo"},  # missing messages
            ) as r:
                assert r.status == 400
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_beam_search_fields_rejected_400():
    """use_beam_search/length_penalty are engine pass-throughs in the
    reference (lib/llm/src/protocols/common.rs:248-316) that no engine here
    honors — they must be rejected loudly, not silently ignored."""
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            for field, value in (("use_beam_search", True), ("length_penalty", 0.8)):
                async with s.post(
                    f"http://127.0.0.1:{service.port}/v1/chat/completions",
                    json={
                        "model": "echo",
                        "messages": [{"role": "user", "content": "x"}],
                        field: value,
                    },
                ) as r:
                    assert r.status == 400
                    body = await r.json()
                    assert field in body["error"]["message"]
                async with s.post(
                    f"http://127.0.0.1:{service.port}/v1/completions",
                    json={"model": "echo", "prompt": "x", field: value},
                ) as r:
                    assert r.status == 400
            # no-op values (vLLM-client serialized defaults) are allowed:
            # null, use_beam_search=false, length_penalty=1.0
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "echo",
                    "messages": [{"role": "user", "content": "x"}],
                    "use_beam_search": False,
                    "length_penalty": 1.0,
                },
            ) as r:
                assert r.status == 200
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "echo",
                    "messages": [{"role": "user", "content": "x"}],
                    "use_beam_search": None,
                },
            ) as r:
                assert r.status == 200
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_streaming_validation_error_is_http_400(tmp_path):
    """Oversized prompt with stream=true must get a 400, not a 200-SSE-error."""
    model_dir = make_model_dir(tmp_path)
    mdc = ModelDeploymentCard.from_local_path(model_dir, "tiny")
    tok = HFTokenizer.from_pretrained_dir(model_dir)
    engine = build_pipeline([OpenAIPreprocessor(mdc, tok), Backend(tok)], EchoEngineCore())
    manager = ModelManager()
    manager.add_chat_model("tiny", engine)
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "tiny",
                    "messages": [{"role": "user", "content": "word " * 600}],
                    "stream": True,
                },
            ) as r:
                assert r.status == 400
                body = await r.json()
                assert "exceeds context" in body["error"]["message"]
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_metrics_exposed():
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            await s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={"model": "echo", "messages": [{"role": "user", "content": "x"}]},
            )
            async with s.get(f"http://127.0.0.1:{service.port}/metrics") as r:
                text = await r.text()
        assert 'dynamo_http_service_requests_total{model="echo",status="success"} 1' in text
        assert "dynamo_http_service_request_duration_seconds_bucket" in text
        assert "dynamo_http_service_time_to_first_token_seconds" in text
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_full_pipeline_over_http(tmp_path):
    """Tokenizing pipeline (preprocessor→backend→echo_core) behind HTTP."""
    model_dir = make_model_dir(tmp_path)
    mdc = ModelDeploymentCard.from_local_path(model_dir, "tiny")
    tok = HFTokenizer.from_pretrained_dir(model_dir)
    engine = build_pipeline([OpenAIPreprocessor(mdc, tok), Backend(tok)], EchoEngineCore())
    manager = ModelManager()
    manager.add_chat_model("tiny", engine)
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "tiny",
                    "messages": [{"role": "user", "content": "the quick brown fox"}],
                    "max_tokens": 64,
                },
            ) as r:
                body = await r.json()
        assert "the quick brown fox" in body["choices"][0]["message"]["content"]
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_model_watcher_hot_add_remove():
    """Worker registers a model in discovery → frontend hot-adds it."""
    hub = MemoryHub()
    worker_drt = DistributedRuntime.in_process(hub)
    front_drt = DistributedRuntime.in_process(hub)

    # worker serving OpenAI-level requests
    ep = worker_drt.namespace("prod").component("worker").endpoint("generate")

    async def handler(payload, ctx):
        from dynamo_tpu.runtime.engine import Context

        async for chunk in EchoEngineFull().generate(Context(payload, ctx)):
            yield chunk

    serving = await ep.serve(handler)

    manager = ModelManager()
    watcher = ModelWatcher(front_drt, manager, namespace="public")
    await watcher.start()
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        await register_model(
            worker_drt, "public", "remote-echo", "dyn://prod.worker.generate"
        )
        await asyncio.sleep(0.05)
        assert "remote-echo" in manager.model_names()

        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "remote-echo",
                    "messages": [{"role": "user", "content": "routed hello"}],
                },
            ) as r:
                assert r.status == 200
                body = await r.json()
        assert body["choices"][0]["message"]["content"].strip() == "routed hello"

        await unregister_model(worker_drt, "public", "remote-echo")
        await asyncio.sleep(0.05)
        assert "remote-echo" not in manager.model_names()
    finally:
        await service.stop()
        await watcher.stop()
        await serving.stop()


@pytest.mark.asyncio
async def test_profile_endpoint_captures_trace(tmp_path):
    """--profile-dir exposes /debug/profile; a capture writes a trace dir
    (jax profiler works on CPU, so this runs the real capture path)."""
    import os

    manager = ModelManager()
    manager.add_chat_model("echo", EchoEngineFull())
    service = HttpService(
        manager, host="127.0.0.1", port=0, profile_dir=str(tmp_path)
    )
    await service.start()
    try:
        async with aiohttp.ClientSession() as s:
            url = f"http://127.0.0.1:{service.port}/debug/profile?seconds=0.2"
            async with s.get(url) as r:
                body = await r.json()
                assert r.status == 200
                assert body["trace_dir"].startswith(str(tmp_path))
            # the capture produced profiler artifacts on disk
            files = [
                os.path.join(dp, f)
                for dp, _dn, fn in os.walk(body["trace_dir"]) for f in fn
            ]
            assert files, "no trace files written"
            async with s.get(
                f"http://127.0.0.1:{service.port}/debug/profile?seconds=abc"
            ) as r:
                assert r.status == 400
            async with s.get(
                f"http://127.0.0.1:{service.port}/debug/profile?seconds=nan"
            ) as r:
                assert r.status == 400  # NaN survives min/max clamps
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_profile_endpoint_absent_without_dir():
    service = await start_echo_service()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{service.port}/debug/profile"
            ) as r:
                assert r.status == 404
    finally:
        await service.stop()


def test_metrics_callback_gauges_render():
    """Engine metrics registered as callback gauges appear on /metrics
    renders, pulled fresh each time; a failing callback renders nothing
    rather than taking the endpoint down."""
    from dynamo_tpu.http.metrics import ServiceMetrics

    m = ServiceMetrics("dynamo")
    state = {"kv_active_blocks": 3, "gpu_prefix_cache_hit_rate": 0.5,
             "spec_accepted_tokens": 7, "label": "not-a-number",
             "flag": True}
    m.register_callback_gauges("dynamo_engine", lambda: state)
    text = m.render()
    assert "dynamo_engine_kv_active_blocks 3.0" in text
    assert "dynamo_engine_spec_accepted_tokens 7.0" in text
    assert "label" not in text and "flag" not in text  # numbers only
    state["kv_active_blocks"] = 9  # pulled fresh at every render
    assert "dynamo_engine_kv_active_blocks 9.0" in m.render()

    m2 = ServiceMetrics("dynamo")
    m2.register_callback_gauges("dynamo_engine", lambda: 1 / 0)
    assert m2.render()  # endpoint survives a broken engine callback


# --------------------------------------------------------------------------
# /v1/embeddings — the prefill-only workload (llm/embeddings.py)
# --------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_embeddings_endpoint_openai_shape():
    from dynamo_tpu.llm.embeddings import EchoEmbedder

    manager = ModelManager()
    engine = EchoEngineFull()
    engine.embedder = EchoEmbedder(dim=8)
    manager.add_chat_model("echo", engine)
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async with aiohttp.ClientSession() as s:
            url = f"http://127.0.0.1:{service.port}/v1/embeddings"
            # single string
            async with s.post(url, json={"model": "echo",
                                         "input": "hello world"}) as r:
                body = await r.json()
                assert r.status == 200
                assert body["object"] == "list"
                assert body["model"] == "echo"
                row = body["data"][0]
                assert row["object"] == "embedding" and row["index"] == 0
                assert len(row["embedding"]) == 8
                assert body["usage"]["prompt_tokens"] == 2
                assert body["usage"]["total_tokens"] == 2
                first = row["embedding"]
            # batch of strings: per-row indexes, deterministic vectors
            async with s.post(url, json={
                "model": "echo", "input": ["hello world", "other"],
            }) as r:
                body = await r.json()
                assert [d["index"] for d in body["data"]] == [0, 1]
                assert body["data"][0]["embedding"] == first
                assert body["data"][1]["embedding"] != first
            # token-id input shapes
            async with s.post(url, json={"model": "echo",
                                         "input": [1, 2, 3]}) as r:
                body = await r.json()
                assert r.status == 200
                assert body["usage"]["prompt_tokens"] == 3
            async with s.post(url, json={
                "model": "echo", "input": [[1, 2], [3, 4, 5]],
            }) as r:
                body = await r.json()
                assert len(body["data"]) == 2
                assert body["usage"]["prompt_tokens"] == 5
            # base64 encoding round-trips to the float rows
            async with s.post(url, json={
                "model": "echo", "input": "hello world",
                "encoding_format": "base64",
            }) as r:
                import base64

                import numpy as np

                body = await r.json()
                dec = np.frombuffer(
                    base64.b64decode(body["data"][0]["embedding"]),
                    np.float32,
                )
                assert np.allclose(dec, np.asarray(first, np.float32))
            # error shapes
            async with s.post(url, json={"model": "echo"}) as r:
                assert r.status == 400
            async with s.post(url, json={"model": "echo",
                                         "input": {"bad": 1}}) as r:
                assert r.status == 400
            async with s.post(url, json={"model": "nope",
                                         "input": "x"}) as r:
                assert r.status == 404
                assert (await r.json())["error"]["code"] == "model_not_found"
    finally:
        await service.stop()


@pytest.mark.asyncio
async def test_embeddings_501_without_embedder():
    service = await start_echo_service()  # plain engine, no embedder
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{service.port}/v1/embeddings",
                json={"model": "echo", "input": "x"},
            ) as r:
                assert r.status == 501
                assert "prefill" in (await r.json())["error"]["message"]
    finally:
        await service.stop()
