"""The load generator: a process of its own that never imports jax.

``python -m harness.loadgen <plan.json>`` (cwd ``benchmark/``). One
thread, one asyncio loop, HTTP/SSE to ``/v1/completions`` with token-id
prompts. It sends the correctness probes, then the cell's traffic: a
ramp that is not measured, the window, and a drain in which requests due
inside the window are followed to completion. All times are
``time.monotonic()``, which on Linux is one clock for every process of
the host, so the parent can put them beside its own.

Lines it prints for the parent (one per event, flushed):
``PROBES_DONE``, ``WINDOW_START <t>``, ``WINDOW_END <t>``, ``DONE``.
The records go to the plan's ``out`` file as one JSON object.

SSE timing arithmetic after ``examples/llm/benchmarks/loadgen.py``; the
open-loop schedule is new (that file only has a closed loop).
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import List, Optional

import aiohttp

from .traffic import Plan, Request, build_plan

# one token of harness/modeldir.py's vocabulary renders as " t<id>", so
# the tokens in a chunk are the t's in its text: a program that streams
# several tokens per chunk is still counted token by token
TOKEN_MARK = "t"


async def _one(session: aiohttp.ClientSession, plan: dict, req: Request,
               due: Optional[float], phase: str, records: List[dict]) -> dict:
    body = {
        "model": plan["model"], "prompt": req.prompt,
        "max_tokens": req.max_tokens, "stream": True, "ignore_eos": True,
        "stream_options": {"include_usage": True}, "seed": req.seed,
        **plan["sampling"],
    }
    headers = {"X-Request-Id": req.rid} if plan["trace"] else {}
    r = {
        "rid": req.rid, "group": req.group, "phase": phase, "due": due,
        "prompt_tokens": len(req.prompt), "max_tokens": req.max_tokens,
        "prefix_tokens": req.prefix_tokens,
        "send": None, "token_times": [], "chunk_tokens": [],
        "usage": None, "done": False, "status": None, "error": None,
    }
    records.append(r)
    r["send"] = time.monotonic()
    try:
        async with session.post(plan["base_url"] + "/v1/completions",
                                json=body, headers=headers) as resp:
            r["status"] = resp.status
            if resp.status != 200:
                r["error"] = (await resp.text())[:300]
                return r
            async for raw in resp.content:
                now = time.monotonic()
                if not raw.startswith(b"data: "):
                    continue
                data = raw[6:].strip()
                if data == b"[DONE]":
                    r["done"] = True
                    continue
                chunk = json.loads(data)
                if chunk.get("usage"):
                    r["usage"] = chunk["usage"]
                n = sum((c.get("text") or "").count(TOKEN_MARK)
                        for c in chunk.get("choices", ()))
                if n:
                    r["token_times"].append(now)
                    r["chunk_tokens"].append(n)
    except asyncio.CancelledError:
        r["error"] = "open at the end of the drain"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        r["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        r["end"] = time.monotonic()
    return r


async def _sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        await asyncio.sleep(d)


async def _get_text(session, plan, path: str) -> str:
    async with session.get(plan["base_url"] + path) as resp:
        return await resp.text()


async def _probes(session, plan: dict) -> List[dict]:
    """Greedy, with logprobs, one at a time on an idle server."""
    out = []
    for prompt in plan["probes"]:
        body = {"model": plan["model"], "prompt": prompt,
                "max_tokens": plan["probe_tokens"], "temperature": 0,
                "ignore_eos": True, "logprobs": 1}
        async with session.post(plan["base_url"] + "/v1/completions",
                                json=body) as resp:
            text = await resp.text()
            if resp.status != 200:
                out.append({"prompt": prompt, "status": resp.status,
                            "error": text[:300]})
                continue
            d = json.loads(text)
            lp = d["choices"][0].get("logprobs") or {}
            out.append({"prompt": prompt, "status": 200,
                        "tokens": lp.get("tokens", []),
                        "token_logprobs": lp.get("token_logprobs", []),
                        "usage": d.get("usage")})
    return out


async def _sampler(session, plan, samples: list, t0: float, t1: float) -> None:
    """Traced runs only: /metrics once a second inside the window."""
    t = t0 + 1.0
    while t < t1:
        await _sleep_until(t)
        samples.append({"t": time.monotonic(),
                        "text": await _get_text(session, plan, "/metrics")})
        t += 1.0


async def _open_loop(session, plan, traffic: Plan, t0: float, t1: float,
                     records: List[dict]) -> List[tuple]:
    tasks = []
    for req in traffic.requests:
        if req.due_s >= t1 - t0:
            break   # due after the window: never sent
        due = t0 + req.due_s
        await _sleep_until(due)
        phase = "window" if req.due_s >= 0 else "ramp"
        tasks.append((phase, asyncio.ensure_future(
            _one(session, plan, req, due, phase, records))))
    return tasks


async def _closed_loop(session, plan, traffic: Plan, t0: float, t1: float,
                       records: List[dict]) -> List[tuple]:
    """``clients`` callers, each sending the next request of the seeded
    sequence when its last one completes. A closed loop is measured by
    what completes: a request belongs to the window when it *ends*
    inside it, whenever it was sent, and what is still running at the
    window's end is cut without a drain (with more callers than slots a
    request outlives any drain a run could afford)."""
    it = iter(traffic.requests)
    tasks: List[tuple] = []

    async def client() -> None:
        while time.monotonic() < t1:
            req = next(it, None)
            if req is None:
                raise RuntimeError("closed loop ran out of requests: raise "
                                   "closed_requests_per_client in the mix")
            task = asyncio.ensure_future(
                _one(session, plan, req, time.monotonic(), "running", records))
            tasks.append(("running", task))
            try:
                r = await task
            except asyncio.CancelledError:
                return
            r["phase"] = "window" if t0 <= r["end"] <= t1 else "ramp"

    drivers = [asyncio.ensure_future(client()) for _ in range(traffic.clients)]
    await _sleep_until(t1)
    return tasks + [("driver", d) for d in drivers]


async def amain(plan: dict) -> int:
    traffic = build_plan(plan["traffic"], plan["cell"], plan["vocab_size"],
                         plan["seed"], plan["seconds"])
    records: List[dict] = []
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        probes = await _probes(session, plan)
        print("PROBES_DONE", flush=True)

        ramp_s, seconds = plan["traffic"]["ramp_s"], plan["seconds"]
        t0 = time.monotonic() + ramp_s + 0.05
        t1 = t0 + seconds
        samples: list = []
        prom = {}

        async def edges() -> None:
            await _sleep_until(t0)
            prom["start"] = await _get_text(session, plan, "/metrics")
            print(f"WINDOW_START {t0!r}", flush=True)
            await _sleep_until(t1)
            prom["end"] = await _get_text(session, plan, "/metrics")
            print(f"WINDOW_END {t1!r}", flush=True)

        side = [asyncio.ensure_future(edges())]
        if plan["trace"]:
            side.append(asyncio.ensure_future(
                _sampler(session, plan, samples, t0, t1)))
        loop_fn = _open_loop if traffic.loop == "open" else _closed_loop
        tasks = await loop_fn(session, plan, traffic, t0, t1, records)
        await _sleep_until(t1)
        await asyncio.gather(*side)

        # drain: requests due in the window are followed to completion
        deadline = t1 + plan["traffic"]["drain_s"]
        pending = [t for phase, t in tasks
                   if phase == "window" and not t.done()]
        if pending:
            await asyncio.wait(
                pending, timeout=max(0.0, deadline - time.monotonic()))
        for _, t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*(t for _, t in tasks), return_exceptions=True)
        t_drained = time.monotonic()

    with open(plan["out"], "w") as f:
        json.dump({
            "window": [t0, t1], "drained": t_drained, "probes": probes,
            "prom_start": prom.get("start", ""), "prom_end": prom.get("end", ""),
            "prom_samples": samples, "records": records,
        }, f)
    print("DONE", flush=True)
    return 0


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    return asyncio.run(amain(plan))


if __name__ == "__main__":
    sys.exit(main())
