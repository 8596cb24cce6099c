"""Out-of-process engine hosting: supervised subprocess + framed IPC.

Reference analog: the reference runs GPU engines as supervised child
processes with an IPC plane and liveness checks (reference:
lib/engines/sglang/src/worker.rs:307-445 spawn/monitor/respawn,
lib/engines/vllm0_7/src/worker.rs:96-115, ZMQ plane
lib/runtime/src/transports/zmq.rs:98-418). Here the same isolation is
built TPU-first: the hazard this quarantines is not a CUDA OOM but a
pathological Mosaic/XLA compile that can hang an entire host process
(and, through it, the worker's lease bookkeeping). The engine child can
hang or die arbitrarily; the hosting worker stays alive, fails the
in-flight requests cleanly through the error prologue, and respawns.

Plane layout (one unix socket per engine, frames are the runtime's
4-byte length-prefixed msgpack maps — same codec as runtime/network.py):

    parent → child:  {t: "init", engine_args}          once, first
                     {t: "req",  id, payload}          start a stream
                     {t: "stop", id} | {t: "kill", id} cancel a stream
                     {t: "ping", n}                    heartbeat
                     {t: "shutdown"}                   graceful exit
    child → parent:  {t: "ready"} | {t: "init_error", error}
                     {t: "data", id, payload}
                     {t: "end",  id} | {t: "error", id, error}
                     {t: "pong", n}

Streams multiplex over the one socket by request id. Heartbeats ride the
same socket on purpose: a child whose event loop is wedged (compile hang
in the import path, user code blocking the loop) stops ponging even
though the process is alive — exactly the failure kill -9 can't detect
from the outside.

Supervision policy: a child that exits, breaks the socket, or misses
``heartbeat_misses`` consecutive pongs is SIGKILLed; every in-flight
request fails with ``EngineError`` (before first output → the network
layer's error prologue) or ``EngineStreamDied`` (mid-stream). The next
``generate`` respawns lazily, up to ``max_restarts`` consecutive
failed spawns with exponential backoff; a successful init resets the
budget.

Engine-author contract: the heartbeat measures the child's EVENT LOOP,
so a ``generate`` that runs long synchronous work inline (a blocking
jit compile, CPU tokenization loops) will stop ponging and be killed as
wedged. Run sync work through ``run_in_executor`` (as
examples/external_engine/engine.py does) — or raise the budget: the
defaults (5s × 6 misses ≈ 30s) and ``init_timeout_s`` are tunable per
engine via the CLI's ``--engine-heartbeat-s/--engine-heartbeat-misses/
--engine-init-timeout-s``.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import sys
import tempfile
import uuid
from typing import Any, AsyncIterator, Dict, Optional

from ...runtime.engine import AsyncEngine, Context, EngineError

logger = logging.getLogger(__name__)


class EngineStreamDied(Exception):
    """The engine process died after the stream had started."""


def _to_wire(payload: Any) -> Any:
    if hasattr(payload, "model_dump"):
        return payload.model_dump(exclude_none=True)
    if hasattr(payload, "to_wire"):
        return payload.to_wire()
    return payload


class SubprocessEngine(AsyncEngine):
    """Hosts a BYO python-file engine (python_file.py contract) in a
    supervised child process behind the AsyncEngine trait."""

    def __init__(
        self,
        path: str,
        engine_args: Optional[dict] = None,
        *,
        init_timeout_s: float = 120.0,
        heartbeat_interval_s: float = 5.0,
        heartbeat_misses: int = 6,
        max_restarts: int = 3,
        restart_backoff_s: float = 0.5,
        child_env: Optional[Dict[str, str]] = None,
        events=None,  # KvEventSink: child "kv" frames replay into it
    ):
        self.path = path
        self.engine_args = engine_args or {}
        self.events = events
        # refreshed by each pong (the child piggybacks engine.metrics()
        # on the heartbeat); read synchronously by stats handlers
        self._last_metrics: dict = {}
        # block hashes the live child has advertised as stored: a child
        # that dies takes its allocator (and every cached block) with
        # it, so the worker-side sink must see them removed or the KV
        # router would route to prefix hits that can never occur
        self._kv_live_hashes: set = set()
        self.init_timeout_s = init_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.heartbeat_misses = heartbeat_misses
        self.max_restarts = max_restarts
        self.restart_backoff_s = restart_backoff_s
        self.child_env = child_env

        self._proc: Optional[asyncio.subprocess.Process] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._reader_task: Optional[asyncio.Task] = None
        self._hb_task: Optional[asyncio.Task] = None
        self._streams: Dict[str, asyncio.Queue] = {}
        self._pong = 0
        self._spawn_lock: Optional[asyncio.Lock] = None
        self._consecutive_failures = 0
        self._closed = False
        # observability for tests/metrics: how many times the child was
        # (re)spawned successfully
        self.spawn_count = 0
        # respawn observability: child deaths were invisible to telemetry
        # — the restart counter (scraped via host_registry) and the
        # engine.respawn flight event make every supervision cycle an
        # auditable fact instead of a log line
        from ...telemetry.registry import MetricsRegistry

        self.host_registry = MetricsRegistry()
        self._restarts = self.host_registry.counter(
            "dynamo_engine_restarts_total",
            "Supervised engine-child respawns, labelled reason="
            "exit|heartbeat|disconnect|manual (what took the previous "
            "child down)",
        )
        self._last_down_kind: Optional[str] = None
        # child-death subscribers (recovery/controller.py): called with
        # the down reason AFTER streams are failed; never during close()
        self._down_listeners: list = []

    @classmethod
    async def load(
        cls, path: str, engine_args: Optional[dict] = None, **kw
    ) -> "SubprocessEngine":
        # "@"-prefixed specs are built-in engines ("@jax"), not files
        if not path.startswith("@") and not os.path.exists(path):
            raise FileNotFoundError(f"python engine file not found: {path}")
        eng = cls(path, engine_args, **kw)
        await eng._ensure_running()
        return eng

    def metrics(self) -> dict:
        """Engine metrics as of the last heartbeat pong (the hosted
        engine's metrics() output; {} until the first pong arrives)."""
        return self._last_metrics

    # ---------- lifecycle ----------

    async def _ensure_running(self) -> None:
        if self._closed:
            raise EngineError("engine host is closed")
        if self._spawn_lock is None:
            self._spawn_lock = asyncio.Lock()
        async with self._spawn_lock:
            if self._proc is not None and self._proc.returncode is None \
                    and self._writer is not None:
                return
            delay = self.restart_backoff_s
            while True:
                if self._consecutive_failures > self.max_restarts:
                    raise EngineError(
                        f"engine {self.path} failed to start "
                        f"{self._consecutive_failures} consecutive times; "
                        "giving up"
                    )
                try:
                    await self._spawn_once()
                    self._consecutive_failures = 0
                    return
                except EngineError:
                    raise
                except Exception as e:
                    self._consecutive_failures += 1
                    logger.warning(
                        "engine spawn attempt failed (%d/%d): %s",
                        self._consecutive_failures, self.max_restarts, e,
                    )
                    if self._consecutive_failures > self.max_restarts:
                        raise EngineError(
                            f"engine {self.path} failed to start: {e}"
                        ) from e
                    await asyncio.sleep(delay)
                    delay *= 2

    async def _spawn_once(self) -> None:
        sock_dir = tempfile.mkdtemp(prefix="dyn-engine-")
        sock_path = os.path.join(sock_dir, "ipc.sock")
        connected: asyncio.Future = asyncio.get_running_loop().create_future()

        async def on_connect(reader, writer):
            if not connected.done():
                connected.set_result((reader, writer))
            else:  # only the hosted child may dial in
                writer.close()

        server = await asyncio.start_unix_server(on_connect, sock_path)
        env = dict(os.environ if self.child_env is None else self.child_env)
        env["DYN_ENGINE_SOCKET"] = sock_path
        # the child runs `-m dynamo_tpu...`: make the package importable
        # regardless of the parent's cwd
        pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        pp = env.get("PYTHONPATH")
        env["PYTHONPATH"] = pkg_parent + (os.pathsep + pp if pp else "")
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "dynamo_tpu.llm.engines.subprocess_host",
            self.path, env=env,
        )
        try:
            reader, writer = await asyncio.wait_for(
                connected, timeout=self.init_timeout_s
            )
            from ...runtime.transports.dynstore import read_frame, write_frame

            write_frame(writer, {"t": "init", "engine_args": self.engine_args})
            await writer.drain()
            frame = await asyncio.wait_for(
                read_frame(reader), timeout=self.init_timeout_s
            )
            if frame is None:
                raise RuntimeError("engine exited during init")
            if frame.get("t") == "init_error":
                # a deterministic user-code failure: do not burn restarts
                raise EngineError(
                    f"engine init failed: {frame.get('error')}"
                )
            if frame.get("t") != "ready":
                raise RuntimeError(f"unexpected init reply {frame.get('t')!r}")
        except (asyncio.TimeoutError, RuntimeError):
            with contextlib.suppress(ProcessLookupError):
                proc.kill()
            raise
        finally:
            server.close()
            # the socket only exists for the initial dial-in; a
            # crash-looping engine must not accumulate tmp dirs
            with contextlib.suppress(OSError):
                os.unlink(sock_path)
            with contextlib.suppress(OSError):
                os.rmdir(sock_dir)
        self._proc = proc
        self._writer = writer
        self._pong = 0
        self.spawn_count += 1
        if self.spawn_count > 1:
            # a RE-spawn: the previous child died for _last_down_kind
            reason = self._last_down_kind or "unknown"
            self._restarts.inc(reason=reason)
            from ...telemetry.flight import flight_recorder

            flight_recorder().record(
                "engine.respawn", path=self.path, pid=proc.pid,
                spawn=self.spawn_count, reason=reason,
            )
        self._reader_task = asyncio.create_task(self._read_loop(reader))
        self._hb_task = asyncio.create_task(self._heartbeat_loop(writer))
        logger.info(
            "engine subprocess for %s up (pid %d)", self.path, proc.pid
        )

    async def _read_loop(self, reader: asyncio.StreamReader) -> None:
        from ...runtime.transports.dynstore import read_frame

        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break
                t = frame.get("t")
                if t == "pong":
                    self._pong = frame.get("n", 0)
                    if "m" in frame:
                        self._last_metrics = frame["m"]
                    continue
                if t == "kv":
                    self._on_kv_frame(frame)
                    continue
                q = self._streams.get(frame.get("id"))
                if q is not None:
                    q.put_nowait(frame)
        except (ConnectionResetError, BrokenPipeError, OSError):
            pass
        finally:
            await self._on_child_down("engine process disconnected")

    def _on_kv_frame(self, frame: dict) -> None:
        """Replay a child KV event into the worker-side sink — the KV
        router's radix index stays current even though the allocator
        lives in the engine child."""
        if self.events is None:
            return
        try:
            hashes = frame.get("hashes") or []
            if frame.get("ev") == "stored":
                self._kv_live_hashes.update(hashes)
                self.events.on_stored(hashes, frame.get("parent"))
            elif frame.get("ev") == "removed":
                self._kv_live_hashes.difference_update(hashes)
                self.events.on_removed(hashes)
        except Exception:
            logger.exception("KV event replay failed")

    async def _heartbeat_loop(self, writer: asyncio.StreamWriter) -> None:
        from ...runtime.transports.dynstore import write_frame

        n = 0
        try:
            while True:
                await asyncio.sleep(self.heartbeat_interval_s)
                n += 1
                write_frame(writer, {"t": "ping", "n": n})
                await writer.drain()
                if n - self._pong > self.heartbeat_misses:
                    logger.error(
                        "engine %s missed %d heartbeats; killing (a wedged "
                        "child — e.g. a hung compile — never exits on its own)",
                        self.path, n - self._pong,
                    )
                    await self._on_child_down(
                        f"engine unresponsive: missed "
                        f"{n - self._pong} heartbeats",
                        kind="heartbeat",
                    )
                    return
        except (ConnectionResetError, BrokenPipeError, OSError):
            await self._on_child_down("engine process disconnected")
        except asyncio.CancelledError:
            raise

    async def _on_child_down(self, reason: str,
                             kind: str = "disconnect") -> None:
        """Fail all in-flight streams and reap the child. Idempotent —
        and the hand-off is claimed SYNCHRONOUSLY before the first await:
        the heartbeat path and the read-loop EOF path race to call this,
        and the loser must find nothing left to fail (else the requester
        sees the generic 'disconnected' instead of the real reason)."""
        proc, self._proc = self._proc, None
        writer, self._writer = self._writer, None
        streams, self._streams = self._streams, {}
        hb, self._hb_task = self._hb_task, None
        winner = proc is not None or writer is not None or bool(streams)
        # the dead child's cached blocks died with its allocator: purge
        # them from the worker-side radix index before anything else
        # (synchronous, like the stream failures below)
        dead_hashes, self._kv_live_hashes = self._kv_live_hashes, set()
        if dead_hashes and self.events is not None:
            try:
                self.events.on_removed(sorted(dead_hashes))
            except Exception:
                logger.exception("KV purge after child death failed")
        if proc is not None and proc.returncode is not None:
            reason = f"{reason} (exit code {proc.returncode})"
            kind = "exit"
        if winner and not self._closed:
            self._last_down_kind = kind
            for fn in list(self._down_listeners):
                try:
                    fn(kind)
                except Exception:
                    logger.exception("engine down listener failed")
        # fail the streams before any await: past the first suspension
        # point this task can itself be cancelled by the competing path
        # (the read loop cancels the heartbeat task, and vice versa), and
        # a cancelled loser must not take the error frames with it
        for q in streams.values():
            q.put_nowait({"t": "error", "error": reason, "died": True})
        if hb is not None and hb is not asyncio.current_task():
            hb.cancel()
        if writer is not None:
            with contextlib.suppress(Exception):
                writer.close()
        if proc is not None:
            with contextlib.suppress(ProcessLookupError):
                proc.kill()
            with contextlib.suppress(Exception):
                await proc.wait()

    def add_down_listener(self, fn) -> None:
        """Subscribe to child deaths (sync callback with the down kind;
        not invoked for close()). The recovery controller uses this to
        run its respawn ladder proactively instead of waiting for the
        next request to pay the spawn."""
        self._down_listeners.append(fn)

    async def respawn(self, reason: str = "manual", card=None) -> None:
        """Kill the current child (failing its streams) and bring a
        fresh one up NOW — the supervised-child half of a recovery
        respawn or a rolling engine restart.

        ``card`` (a registry ModelCard or its wire dict) swaps the
        model the child serves: the flag-driven "@jax" child re-reads
        model_path/model_name on spawn, so a respawn with a different
        card IS the multi-model cold start (registry/pools.py) —
        hundreds of logical models per chip, one at a time."""
        if card is not None:
            flags = self.engine_args.get("flags")
            if not isinstance(flags, dict):
                from ...runtime.engine import EngineError

                raise EngineError(
                    "this engine host cannot swap model cards (no "
                    "flag-driven child; serve out=jax --isolate-engine)"
                )
            wire = card.to_wire() if hasattr(card, "to_wire") else dict(card)
            if not wire.get("model_path"):
                from ...runtime.engine import EngineError

                raise EngineError(
                    f"model card {wire.get('name')!r} carries no "
                    "model_path — cannot cold-start from it"
                )
            flags["model_path"] = wire["model_path"]
            flags["model_name"] = wire.get("name") or flags.get("model_name")
            if wire.get("kv_block_size"):
                flags["kv_block_size"] = int(wire["kv_block_size"])
            reason = f"{reason} (card={wire.get('name')})"
        await self._on_child_down(f"manual respawn: {reason}",
                                  kind="manual")
        await self._ensure_running()

    async def close(self) -> None:
        self._closed = True
        writer = self._writer
        if writer is not None:
            from ...runtime.transports.dynstore import write_frame

            with contextlib.suppress(Exception):
                write_frame(writer, {"t": "shutdown"})
                await writer.drain()
            proc = self._proc
            if proc is not None:
                with contextlib.suppress(asyncio.TimeoutError):
                    await asyncio.wait_for(proc.wait(), timeout=2.0)
        await self._on_child_down("engine host closed")
        if self._reader_task is not None:
            self._reader_task.cancel()

    # ---------- serving ----------

    async def generate(self, request: Context[Any]) -> AsyncIterator[Any]:
        await self._ensure_running()
        from ...runtime.transports.dynstore import write_frame

        rid = f"{request.id}-{uuid.uuid4().hex[:8]}"
        q: asyncio.Queue = asyncio.Queue()
        self._streams[rid] = q
        writer = self._writer
        started = False
        ctx = request.context

        async def watch_cancel():
            await ctx.wait_stopped()
            t = "kill" if ctx.is_killed else "stop"
            w = self._writer
            if w is not None:
                with contextlib.suppress(Exception):
                    write_frame(w, {"t": t, "id": rid})
                    await w.drain()

        cancel_task = asyncio.create_task(watch_cancel())
        try:
            write_frame(writer, {"t": "req", "id": rid,
                                 "payload": _to_wire(request.payload)})
            await writer.drain()
            while True:
                frame = await q.get()
                t = frame.get("t")
                if t == "data":
                    started = True
                    yield frame.get("payload")
                elif t == "end":
                    return
                elif t == "error":
                    msg = frame.get("error", "engine error")
                    if frame.get("died") and started:
                        # the stream was already flowing: the network
                        # layer turns this into a mid-stream err frame
                        raise EngineStreamDied(msg)
                    raise EngineError(msg)
                else:
                    logger.warning("unexpected engine frame %r", t)
        finally:
            cancel_task.cancel()
            self._streams.pop(rid, None)


# ---------------------------------------------------------------------------
# child entrypoint
# ---------------------------------------------------------------------------


async def _build_child_engine(engine_path: str, engine_args: dict,
                              event_post) -> AsyncEngine:
    """Instantiate the hosted engine inside the child.

    ``engine_path`` is a python-file path (pystr:/pytok: contract) or
    the ``@jax`` sentinel — the native JAX serving engine, THE engine
    whose Mosaic/XLA compiles are the wedge hazard this host exists to
    quarantine. For ``@jax``, ``engine_args['flags']`` carries the
    parent CLI's flag namespace as a plain dict; KV events flow back to
    the parent as ``{"t": "kv"}`` frames via ``event_post``."""
    if engine_path == "@jax":
        from types import SimpleNamespace

        from ...cli.run import load_mdc
        from ...engine.block_allocator import KvEventSink
        from ...engine.device import configure_compile_cache
        from ...engine.serving import JaxServingEngine

        configure_compile_cache()
        flags = SimpleNamespace(**(engine_args.get("flags") or {}))
        mdc = load_mdc(flags)
        sink = KvEventSink(
            on_stored=lambda hashes, parent: event_post(
                {"t": "kv", "ev": "stored",
                 "hashes": [int(h) for h in hashes],
                 "parent": None if parent is None else int(parent)}),
            on_removed=lambda hashes: event_post(
                {"t": "kv", "ev": "removed",
                 "hashes": [int(h) for h in hashes]}),
        )
        return await JaxServingEngine.create(mdc, flags, events=sink)
    from .python_file import PythonFileEngine

    return await PythonFileEngine.load(engine_path, engine_args)


async def _child_main(engine_path: str) -> int:
    sock = os.environ["DYN_ENGINE_SOCKET"]
    reader, writer = await asyncio.open_unix_connection(sock)
    from ...runtime.transports.dynstore import read_frame, write_frame

    init = await read_frame(reader)
    if init is None or init.get("t") != "init":
        return 2

    tasks: Dict[str, asyncio.Task] = {}
    send_lock = asyncio.Lock()

    async def send(frame: dict) -> None:
        async with send_lock:  # frames from concurrent streams interleave
            write_frame(writer, frame)
            await writer.drain()

    # KV events are posted synchronously from scheduler hooks; a FIFO
    # queue + one pump preserves stored/removed ordering (reordering a
    # block's stored after its removed would corrupt the radix index)
    event_q: asyncio.Queue = asyncio.Queue()

    async def _event_pump() -> None:
        while True:
            await send(await event_q.get())

    try:
        engine = await _build_child_engine(
            engine_path, init.get("engine_args") or {}, event_q.put_nowait
        )
    # dynlint: allow(silent-except) - error IS surfaced: the init_error frame below
    except BaseException as e:  # report, don't just die: init errors are
        write_frame(writer, {          # deterministic, not restartable
            "t": "init_error", "error": f"{type(e).__name__}: {e}",
        })
        await writer.drain()
        return 3
    pump_task = asyncio.create_task(_event_pump())  # noqa: F841
    write_frame(writer, {"t": "ready"})
    await writer.drain()

    async def run_stream(rid: str, payload: Any) -> None:
        try:
            async for chunk in engine.generate(Context(payload)):
                await send({"t": "data", "id": rid, "payload": chunk})
            await send({"t": "end", "id": rid})
        except asyncio.CancelledError:
            await send({"t": "end", "id": rid})
            raise
        # dynlint: allow(silent-except) - error IS surfaced: relayed as a wire frame
        except BaseException as e:
            await send({
                "t": "error", "id": rid,
                "error": f"{type(e).__name__}: {e}",
            })
        finally:
            tasks.pop(rid, None)

    while True:
        frame = await read_frame(reader)
        if frame is None:
            break
        t = frame.get("t")
        if t == "ping":
            # pongs double as the metrics channel: the parent's
            # stats_handler is synchronous, so it reads the cache the
            # latest pong refreshed (≤ one heartbeat interval stale)
            pong = {"t": "pong", "n": frame.get("n", 0)}
            if hasattr(engine, "metrics"):
                try:
                    pong["m"] = engine.metrics()
                # dynlint: allow(silent-except) - best-effort metrics must never kill the pong
                except Exception:
                    pass
            await send(pong)
        elif t == "req":
            from ...utils import faults

            if faults.fire("child_exit"):
                # chaos site: the child dies hard mid-serve — the parent
                # must fail the stream and respawn (utils/faults.py)
                os._exit(17)
            rid = frame["id"]
            tasks[rid] = asyncio.create_task(
                run_stream(rid, frame.get("payload"))
            )
        elif t in ("stop", "kill"):
            task = tasks.get(frame.get("id"))
            if task is not None:
                task.cancel()
        elif t == "shutdown":
            break
    for task in list(tasks.values()):
        task.cancel()
    return 0


def main() -> None:
    if len(sys.argv) != 2:
        print("usage: python -m dynamo_tpu.llm.engines.subprocess_host "
              "<engine_file.py>", file=sys.stderr)
        raise SystemExit(2)
    raise SystemExit(asyncio.run(_child_main(sys.argv[1])))


if __name__ == "__main__":
    main()
