"""serve CLI: ``python -m dynamo_tpu.cli.run in=<src> out=<engine> [flags]``.

The dynamo-run analog (reference: launch/dynamo-run/src/{main,lib}.rs —
in={http,text,stdin,batch:,dyn://} × out={echo_full,echo_core,engines...}).
Wires the local pipeline frontend → preprocessor → backend → engine and
serves it over the chosen input.

Examples:
  python -m dynamo_tpu.cli.run in=http out=echo_full --http-port 8080
  python -m dynamo_tpu.cli.run in=http out=echo_core --model-path /path/to/model
  python -m dynamo_tpu.cli.run in=http out=jax --model-path /path/to/model
  python -m dynamo_tpu.cli.run in=dyn://ns.comp.ep out=jax --model-path ... \
      --store-port 4871 --model-name my-model
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import sys
from typing import List, Optional

logger = logging.getLogger(__name__)


def parse_io(args: List[str]):
    src, engine = "http", "echo_full"
    rest = []
    for a in args:
        if a.startswith("in="):
            src = a[3:]
        elif a.startswith("out="):
            engine = a[4:]
        else:
            rest.append(a)
    return src, engine, rest


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dynamo-tpu run", add_help=True)
    p.add_argument("--model-path", default=None, help="HF snapshot dir")
    p.add_argument("--model-name", default=None, help="served model name")
    p.add_argument("--http-host", default="0.0.0.0")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--store-host", default="127.0.0.1", help="dynstore host")
    p.add_argument("--store-port", type=int, default=None, help="dynstore port (distributed mode)")
    p.add_argument("--namespace", default="public")
    p.add_argument("--router-mode", default="round_robin",
                   choices=["random", "round_robin", "kv"])
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--expert-parallel-size", type=int, default=1,
                   help="experts shard over the ep mesh axis (MoE)")
    p.add_argument("--data-parallel-size", type=int, default=1,
                   help="batch shards over the dp mesh axis")
    p.add_argument("--sequence-parallel-size", type=int, default=1,
                   help="sequence-parallel axis for long-context prefill: "
                        "one oversized prompt's tokens shard across this "
                        "many devices (ring attention + chunk-streamed KV "
                        "commit; docs/long_context.md). Decode is "
                        "unaffected. Llama-family GQA dense models only.")
    p.add_argument("--long-prefill-threshold-tokens", type=int, default=0,
                   help="admission class: prompts whose uncached suffix is "
                        "at least this long take the sequence-parallel "
                        "prefill program (or, in disagg mode, prefer the "
                        "prefill-worker pool). 0 = default to the per-step "
                        "prefill budget when --sequence-parallel-size > 1, "
                        "else disabled.")
    p.add_argument("--pipeline-parallel-size", type=int, default=1,
                   help="dense trunk stages over the pp mesh axis "
                        "(collective GPipe; reference analog: "
                        "pipeline_parallel_size=num_nodes)")
    p.add_argument("--token-level", action="store_true",
                   help="serve PreprocessedRequests (engine worker behind a processor)")
    p.add_argument("--worker-endpoint", default=None,
                   help="dyn://ns.comp.ep of token-level workers (processor role)")
    p.add_argument("--kv-block-size", type=int, default=16)
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=None)
    p.add_argument("--extra-engine-args", default=None, help="JSON file of engine kwargs")
    p.add_argument("--isolate-engine", action="store_true",
                   help="host the engine (out=jax, pystr:, pytok:) in a "
                        "supervised subprocess (heartbeat + respawn; an "
                        "engine crash or hung Mosaic/XLA compile cannot "
                        "take the worker down)")
    p.add_argument("--engine-heartbeat-s", type=float, default=5.0,
                   help="isolated-engine heartbeat interval; the child's "
                        "event loop must pong within interval x misses "
                        "(sync work belongs in run_in_executor)")
    p.add_argument("--engine-heartbeat-misses", type=int, default=6,
                   help="consecutive missed pongs before the isolated "
                        "engine is declared wedged and killed")
    p.add_argument("--engine-init-timeout-s", type=float, default=120.0,
                   help="isolated-engine spawn+initialize() deadline")
    p.add_argument("--host-kv-blocks", type=int, default=0,
                   help="host-RAM KV offload tier capacity in blocks (0 = off)")
    p.add_argument("--multi-step-decode", type=int, default=1,
                   help="decode steps fused per device dispatch (tokens "
                        "stream in bursts of K; 1 = per-token)")
    p.add_argument("--decode-pipeline-depth", type=int, default=1,
                   help="2 = the persistent decode loop: the burst "
                        "carries a per-row done mask (eos/stop/max-token "
                        "checks inside the scan; finished rows freeze), "
                        "so bursts chain back-to-back off the device "
                        "carry while the host streams earlier bursts' "
                        "tokens; 0/1 = strictly synchronous")
    p.add_argument("--guided-table-max-states", type=int, default=256,
                   help="unrestricted chain: state bound for compiling "
                        "guided grammars to device transition tables "
                        "(in-bound grammars chain; larger ones keep the "
                        "host sync path, counted in "
                        "dynamo_engine_sync_fallback_total)")
    p.add_argument("--no-guided-device-table", action="store_true",
                   help="disable guided device tables: guided rows keep "
                        "the per-token host mask path")
    p.add_argument("--disagg-stream-depth", type=int, default=2,
                   help="streamed remote prefill: KV transfer frames in "
                        "flight on the prefill worker (2 double-buffers "
                        "— next frame gathers while the previous one is "
                        "on the wire; 1 = strictly serial frames)")
    p.add_argument("--quantization", choices=["int8"], default=None,
                   help="serving-time weight-only quantization (halves "
                        "the decode weight stream; llama-family)")
    p.add_argument("--kv-cache-dtype", choices=["auto", "fp8"],
                   default="auto",
                   help="paged KV cache storage dtype: fp8 halves the "
                        "decode KV stream and doubles cache capacity "
                        "(~6%% elementwise KV error; GQA families)")
    p.add_argument("--spec-ngram-tokens", type=int, default=0,
                   help="ngram speculative decoding: propose up to K "
                        "tokens per step from the context's own history "
                        "(greedy requests; 0 = off)")
    p.add_argument("--spec-draft-model", default=None,
                   help="draft-model speculative decoding: HF dir of a "
                        "small same-tokenizer model that proposes "
                        "--spec-draft-tokens per round (one fused burst) "
                        "for the target to verify in one forward")
    p.add_argument("--spec-draft-tokens", type=int, default=0,
                   help="proposals per draft round (2..16)")
    p.add_argument("--spec-ngram-match", type=int, default=3,
                   help="trailing n-gram length the proposer looks up")
    p.add_argument("--num-kv-blocks", type=int, default=2048,
                   help="HBM paged-cache capacity in blocks")
    p.add_argument("--allow-random-weights", action="store_true",
                   help="serve random-init weights when the model dir has no "
                        "checkpoint (topology dry runs only)")
    # disaggregated prefill/decode (xPyD)
    p.add_argument("--remote-prefill", action="store_true",
                   help="decode worker: offload long prefills to the prefill queue")
    p.add_argument("--max-local-prefill-length", type=int, default=1000,
                   help="un-cached prompt tokens above this go remote")
    p.add_argument("--max-prefill-queue-size", type=int, default=2,
                   help="skip remote prefill when the queue is this deep")
    p.add_argument("--advertise-host", default="127.0.0.1",
                   help="host other workers use to reach this worker's KV transfer server")
    p.add_argument("--kv-transfer", choices=("tcp", "ici"), default="tcp",
                   help="KV block payload path: tcp (host bounce, works "
                        "anywhere) or ici (HBM-to-HBM XLA collective; "
                        "requires prefill+decode in one jax.distributed "
                        "world via --num-nodes/--leader-addr)")
    p.add_argument("--ici-sender-rank", type=int, default=1,
                   help="jax process index of the prefill (sender) worker")
    p.add_argument("--ici-receiver-rank", type=int, default=0,
                   help="jax process index of the decode (receiver) worker")
    # multi-host bring-up (reference MultiNodeConfig {num_nodes, node_rank,
    # leader_addr}, lib/llm/src/engines.rs:39-57; Ray leader/follower,
    # lib/engines/vllm0_7/src/ray.rs:66-230 — here JAX's coordinator is the
    # leader and the mesh spans slices, ICI within / DCN across)
    p.add_argument("--num-nodes", type=int, default=1,
                   help="hosts in this worker's mesh (multi-host serving)")
    p.add_argument("--node-rank", type=int, default=0,
                   help="this host's rank (0 = leader/coordinator)")
    p.add_argument("--leader-addr", default="",
                   help="host:port of node 0's JAX coordinator")
    # profiling (utils/profiling.py — XLA profiler, the TPU-first answer
    # to the reference's external genai-perf measurement)
    p.add_argument("--profile-dir", default="",
                   help="enable GET /debug/profile trace capture into this "
                        "directory (in=http only)")
    p.add_argument("--profiler-port", type=int, default=0,
                   help="start the jax profiler gRPC server on this port "
                        "(TensorBoard remote capture; any role)")
    # flight recorder + stall watchdog (telemetry/flight.py, watchdog.py)
    p.add_argument("--flight-dir", default="",
                   help="directory for flight artifacts (watchdog trips, "
                        "SIGUSR2, /debug/flight?save=1); also settable "
                        "via DYN_FLIGHT_DIR")
    p.add_argument("--watchdog-stall-s", type=float, default=None,
                   help="stall-watchdog deadline: trip (and dump a "
                        "flight artifact) when the engine has pending "
                        "work but its loop heartbeat or dispatch counter "
                        "has been stale this long (default 30; 0 = off)")
    # self-healing serving (recovery/): trip → drain → migrate → respawn
    p.add_argument("--self-heal", action="store_true",
                   help="automated recovery: watchdog trips (and "
                        "supervised-child deaths) drive drain → live "
                        "request migration to a healthy peer → respawn; "
                        "also enables POST /admin/drain for zero-"
                        "downtime rolling updates")
    p.add_argument("--drain-grace-s", type=float, default=5.0,
                   help="soft-drain grace: how long committed work may "
                        "finish on its own before migration starts")
    p.add_argument("--respawn-max", type=int, default=3,
                   help="consecutive failed respawns before the "
                        "recovery controller gives up")
    p.add_argument("--respawn-backoff-s", type=float, default=1.0,
                   help="respawn backoff base (doubles per consecutive "
                        "failure)")
    p.add_argument("--migrate-peers", default="",
                   help="comma-separated host:port list of peer "
                        "migration receivers (in=dyn:// workers discover "
                        "peers through the discovery plane instead)")
    p.add_argument("--migrate-port", type=int, default=0,
                   help="port for this worker's inbound-migration "
                        "receiver (0 = ephemeral; started only with "
                        "--self-heal on a native engine)")
    # cluster KV fabric (kv/fabric.py, docs/kv_fabric.md): cross-worker
    # prefix pull + content-addressed cold tier
    p.add_argument("--prefix-pull", action="store_true",
                   help="cluster KV fabric: on a router-detected remote "
                        "prefix hit, PULL the owning worker's committed "
                        "KV blocks over the transfer plane instead of "
                        "recomputing them (peers + ownership discovered "
                        "through the component's KV event stream; pull "
                        "failure falls back to local recompute "
                        "byte-identically)")
    p.add_argument("--prefix-pull-min-blocks", type=int, default=2,
                   help="minimum remote/cold extension (blocks past the "
                        "local hit) worth a pull")
    p.add_argument("--prefix-pull-timeout-s", type=float, default=30.0,
                   help="per-pull deadline before the local-recompute "
                        "fallback takes over")
    p.add_argument("--cold-tier-dir", default="",
                   help="content-addressed cold KV tier: spill host-"
                        "tier-evicted blocks to checksummed files in "
                        "this directory (shared mount → any worker, "
                        "including a respawned one, rehydrates them); "
                        "requires --host-kv-blocks > 0")
    p.add_argument("--cold-tier-blocks", type=int, default=0,
                   help="cold-tier capacity in blocks (0 = off; set "
                        "together with --cold-tier-dir)")
    # closed-loop SLA planner + HTTP-edge admission control (planner/)
    p.add_argument("--admission-limit", type=int, default=0,
                   help="HTTP-edge admission control: max concurrently "
                        "admitted requests; overflow queues per priority "
                        "class (X-Priority: high|normal|low), dequeued "
                        "highest-first, shed with 429 + Retry-After on "
                        "saturation or deadline (0 = admission off)")
    p.add_argument("--admission-queue-depth", type=int, default=64,
                   help="per-priority-class admission queue bound")
    p.add_argument("--admission-queue-timeout-s", type=float, default=10.0,
                   help="queue-wait deadline before a queued request is "
                        "shed with 429")
    p.add_argument("--planner", action="store_true",
                   help="in=http: run an in-process planner loop that "
                        "tightens/relaxes admission (and the disagg "
                        "split) from the engine's own load signals")
    p.add_argument("--planner-interval-s", type=float, default=2.0,
                   help="planner observe→decide→actuate cadence")
    p.add_argument("--planner-min-replicas", type=int, default=1)
    p.add_argument("--planner-max-replicas", type=int, default=8)
    p.add_argument("--planner-cooldown-s", type=float, default=30.0,
                   help="scale-up cooldown per role (scale-down waits "
                        "4x this)")
    p.add_argument("--planner-deployment", default=None,
                   help="in=planner: api-store deployment record whose "
                        "per-role replica counts the planner patches "
                        "(the operator applies them via --api-store-url)")
    p.add_argument("--api-store-url", default=None,
                   help="in=planner: api-store base URL for replica "
                        "actuation")
    # SLO targets + goodput accounting at the HTTP edge (telemetry/slo.py)
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="time-to-first-token SLO in ms: per-request "
                        "attainment + goodput (SLO-met tokens/s) export "
                        "on /metrics and feed the planner's slo.* "
                        "signals (0 = unjudged)")
    p.add_argument("--slo-itl-ms", type=float, default=0.0,
                   help="inter-token-latency SLO in ms, judged on each "
                        "request's WORST token gap at the edge (0 = "
                        "unjudged)")
    # fleet telemetry hub (telemetry/hub.py): cluster-wide /metrics
    # scrape → history rings → /fleet/metrics + /fleet/workers rollups
    p.add_argument("--hub", action="store_true",
                   help="run a fleet telemetry hub in this process "
                        "(in=http or in=planner): scrape every "
                        "--hub-target and discovery-registered metrics "
                        "sidecar into history rings and serve "
                        "/fleet/metrics + /fleet/workers (dynamotop's "
                        "data source); hub rollups also feed the "
                        "planner's fleet-level saturation signals")
    p.add_argument("--hub-interval-s", type=float, default=2.0,
                   help="hub scrape cadence")
    p.add_argument("--hub-target", action="append", default=None,
                   metavar="ROLE=URL",
                   help="static scrape target (repeatable): "
                        "decode=http://host:9090 — /metrics is appended "
                        "when missing; discovery-registered sidecars "
                        "are scraped in addition")
    # incident recorder (telemetry/incidents.py): trigger-driven capture
    # bundles (flight artifact + metric history + affected traces +
    # optional profiler window) at trip time
    p.add_argument("--incident-dir", default="",
                   help="capture incident bundles into this directory "
                        "on watchdog trips, recovery-ladder engagement, "
                        "SLO-floor breaches, and late-compile bursts; "
                        "also settable via DYN_INCIDENT_DIR; bundles "
                        "are listed at GET /debug/incidents and "
                        "rendered by scripts/flightdump.py --incident")
    p.add_argument("--incident-cooldown-s", type=float, default=60.0,
                   help="per-reason incident capture cooldown (one "
                        "wedge produces one bundle, not one per trip "
                        "edge)")
    p.add_argument("--incident-profile-s", type=float, default=0.0,
                   help="opt-in: include a jax.profiler capture window "
                        "of this many seconds in each incident bundle "
                        "(0 = off; skipped cleanly when a manual "
                        "/debug/profile capture is in flight)")
    # per-request trace store bounds (telemetry/tracing.py)
    p.add_argument("--trace-ttl-s", type=float, default=None,
                   help="evict completed /debug/requests traces older "
                        "than this (default 600; 0 keeps until the "
                        "capacity bound evicts them)")
    p.add_argument("--trace-capacity", type=int, default=None,
                   help="max completed traces held for /debug/requests "
                        "and /debug/trace (LRU beyond it; default 512)")
    # multi-model multi-tenant fleet (registry/, docs/multi_model.md)
    p.add_argument("--served-alias", action="append", default=None,
                   metavar="ALIAS",
                   help="extra name this model answers to (repeatable); "
                        "rides the model card workers publish at "
                        "startup, resolved by registry-aware frontends")
    p.add_argument("--model-tenants", default=None,
                   help="comma-separated tenant allow list for this "
                        "model's card (unset = public; tenant-scoped "
                        "models are invisible — 404 — to other tenants)")
    p.add_argument("--tenant-rps", type=float, default=0.0,
                   help="per-tenant requests/s token bucket (X-Tenant "
                        "header; unknown/garbage degrades to the "
                        "'default' tenant; 0 = unlimited). Exceeding "
                        "tenants are shed with 429 + Retry-After while "
                        "other tenants are untouched")
    p.add_argument("--tenant-tps", type=float, default=0.0,
                   help="per-tenant streamed-tokens/s token bucket "
                        "(charged by actual streamed tokens; overdraft "
                        "delays the tenant's next admission; 0 = "
                        "unlimited)")
    p.add_argument("--tenant-burst-s", type=float, default=2.0,
                   help="token-bucket capacity in seconds of rate")
    p.add_argument("--tenant-quotas", default=None, metavar="FILE.json",
                   help="per-tenant overrides: {tenant: {requests_per_s,"
                        " tokens_per_s, burst_s}}")
    p.add_argument("--pool-scale-to-zero-idle-s", type=float, default=0.0,
                   help="drain a model's pool to zero replicas after "
                        "this long without a request (0 = off); the "
                        "next request for the cold model triggers a "
                        "cold-start respawn with that model's card")
    p.add_argument("--pool-cold-start-deadline-s", type=float,
                   default=30.0,
                   help="how long a request for a cold model waits for "
                        "a worker to join the pool before shedding "
                        "with 503 + Retry-After")
    p.add_argument("--pool-cooldown-s", type=float, default=30.0,
                   help="per-model pool action pacing (scale-to-zero / "
                        "cold-start decisions)")
    p.add_argument("--router-staleness-bound-s", type=float, default=0.0,
                   help="KV router: skip workers whose scraped load "
                        "snapshot is older than this many seconds "
                        "(0 = trust snapshots forever)")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="dyn:// roles: serve this process's Prometheus "
                        "registry on a sidecar GET /metrics port (the "
                        "router's per-worker load view, a token-level "
                        "worker's scheduler/KV instruments; 0 = off — "
                        "in=http exposes /metrics on the service port)")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def _make_ici(flags, runner):
    """--kv-transfer ici → the collective HBM-to-HBM plane, else None."""
    if getattr(flags, "kv_transfer", "tcp") != "ici":
        return None
    from ..disagg.ici_transfer import IciKvTransfer, kv_block_shapes

    import jax as _jax

    # the cache side may be a {"pre","stg"} pytree (mixed MLA under pp);
    # every leaf shares one storage dtype
    kv_dtype = _jax.tree.leaves(runner.kv_cache[0])[0].dtype
    return IciKvTransfer(
        kv_block_shapes(runner.config),
        kv_dtype,
        sender_rank=flags.ici_sender_rank,
        receiver_rank=flags.ici_receiver_rank,
    )


def load_mdc(flags):
    from ..llm.model_card import ModelDeploymentCard
    from ..models.hub import resolve_model_path

    if not flags.model_path:
        raise SystemExit("this mode requires --model-path")
    # accept a HF repo id anywhere a path is accepted (reference:
    # launch/dynamo-run/src/hub.rs) — local dirs pass through untouched
    flags.model_path = resolve_model_path(flags.model_path)
    if flags.model_path.endswith(".gguf"):
        from ..llm.gguf import mdc_from_gguf

        return mdc_from_gguf(
            flags.model_path, flags.model_name,
            kv_block_size=flags.kv_block_size,
        )
    return ModelDeploymentCard.from_local_path(
        flags.model_path, flags.model_name, kv_block_size=flags.kv_block_size
    )


def _engine_args(flags) -> dict:
    """--extra-engine-args <file.json> → kwargs for the engine."""
    from ..engine.serving import load_extra_engine_args

    return load_extra_engine_args(flags)


async def _load_python_engine(path: str, flags):
    """BYO python-file engine, in-process or (``--isolate-engine``)
    hosted in a supervised subprocess with heartbeat + respawn."""
    if getattr(flags, "isolate_engine", False):
        from ..llm.engines.subprocess_host import SubprocessEngine

        return await SubprocessEngine.load(
            path, _engine_args(flags),
            heartbeat_interval_s=getattr(flags, "engine_heartbeat_s", 5.0),
            heartbeat_misses=getattr(flags, "engine_heartbeat_misses", 6),
            init_timeout_s=getattr(flags, "engine_init_timeout_s", 120.0),
        )
    from ..llm.engines.python_file import PythonFileEngine

    return await PythonFileEngine.load(path, _engine_args(flags))


async def build_core_engine(engine_spec: str, flags, mdc, events=None, drt=None):
    """Token-level engine (PreprocessedRequest → EngineOutput stream)."""
    from ..llm.engines.echo import EchoEngineCore

    if engine_spec == "echo_core":
        return EchoEngineCore()
    if engine_spec.startswith("pytok:"):
        return await _load_python_engine(
            engine_spec[len("pytok:"):], flags
        )
    if engine_spec == "jax":
        if getattr(flags, "isolate_engine", False):
            # the native JAX engine is the actual compile-hang hazard
            # (a wedged Mosaic compile freezes the whole host process);
            # host it as a supervised child: heartbeats catch the wedge,
            # the worker keeps its lease, in-flight requests fail through
            # the error prologue, and the next request respawns the
            # child (warm-started via the persistent compilation cache).
            # One process per chip: this parent never touches jax, so
            # the child owns the device for its lifetime.
            if getattr(flags, "remote_prefill", False):
                raise SystemExit(
                    "--isolate-engine is incompatible with "
                    "--remote-prefill: the disagg coordinator needs "
                    "in-process access to the runner's KV cache"
                )
            from ..llm.engines.subprocess_host import SubprocessEngine

            wire_flags = {
                k: v for k, v in vars(flags).items()
                if isinstance(v, (str, int, float, bool, list, dict))
                or v is None
            }
            wire_flags["isolate_engine"] = False  # no recursion
            return await SubprocessEngine.load(
                "@jax", {"flags": wire_flags},
                heartbeat_interval_s=getattr(flags, "engine_heartbeat_s", 5.0),
                heartbeat_misses=getattr(flags, "engine_heartbeat_misses", 6),
                init_timeout_s=getattr(flags, "engine_init_timeout_s", 120.0),
                events=events,
            )
        from ..engine.serving import JaxServingEngine

        disagg_factory = None
        if getattr(flags, "remote_prefill", False):
            if drt is None:
                raise SystemExit("--remote-prefill requires distributed mode (in=dyn://)")

            async def disagg_factory(runner):
                from ..disagg import DisaggRouter, RemotePrefillCoordinator

                router = DisaggRouter(
                    max_local_prefill_length=flags.max_local_prefill_length,
                    max_prefill_queue_size=flags.max_prefill_queue_size,
                    model_name=flags.model_name,
                    namespace=flags.namespace,
                )
                return await RemotePrefillCoordinator(
                    drt, runner, namespace=flags.namespace,
                    router=router, advertise_host=flags.advertise_host,
                    ici=_make_ici(flags, runner),
                ).start()

        return await JaxServingEngine.create(
            mdc, flags, events=events, disagg_factory=disagg_factory
        )
    raise SystemExit(f"unknown core engine {engine_spec!r}")


async def build_engine(engine_spec: str, flags, drt=None, events=None):
    """Returns (openai_engine, mdc_or_None). The engine accepts
    ChatCompletionRequest/CompletionRequest contexts and yields chunks."""
    from ..llm.engines.echo import EchoEngineFull

    if engine_spec == "none":
        # pure frontend: models come exclusively from the discovery watcher
        return None, None
    if engine_spec == "echo_full":
        from ..llm.embeddings import EchoEmbedder

        engine = EchoEngineFull()
        # the echo stack serves /v1/embeddings too (deterministic
        # hash-seeded vectors) so the endpoint is drivable creds-free
        engine.embedder = EchoEmbedder()
        return engine, None
    if engine_spec.startswith("pystr:"):
        # bring-your-own OpenAI-level engine (reference: out=pystr:<file>)
        engine = await _load_python_engine(
            engine_spec[len("pystr:"):], flags
        )
        return engine, None

    if engine_spec in ("echo_core", "jax") or engine_spec.startswith("pytok:"):
        from ..llm.backend import Backend
        from ..llm.preprocessor import OpenAIPreprocessor
        from ..llm.tokenizer import HFTokenizer
        from ..runtime.pipeline import build_pipeline

        if engine_spec == "jax" and not getattr(flags, "isolate_engine",
                                                False):
            # this process holds the device: bring its runtime up before
            # the card and the tokenizer, so that the start-up timeline's
            # ``backend`` and ``model_card`` phases are each one thing
            # (an --isolate-engine parent stays off jax)
            from ..engine.device import check_serving_device

            check_serving_device()
        mdc = load_mdc(flags)
        tokenizer = HFTokenizer.from_model_path(flags.model_path)
        core = await build_core_engine(engine_spec, flags, mdc, events, drt=drt)
        pipe = build_pipeline(
            [OpenAIPreprocessor(mdc, tokenizer), Backend(tokenizer)], core
        )
        # the recovery wiring (and /admin/drain) needs the token-level
        # engine behind the preprocessing stages
        pipe.core_engine = core
        if getattr(core, "host_registry", None) is not None:
            # subprocess-hosted engines: the supervision registry
            # (restart counter) rides separately from the dict-gauge
            # metrics the child pongs back
            pipe.host_registry = core.host_registry
        if hasattr(core, "metrics"):
            # surfaced on the frontend's /metrics as engine gauges
            # (run_http) — slot/KV occupancy, prefix hits, speculation
            # acceptance; the reference publishes the same counters via
            # its ForwardPassMetrics plane
            pipe.engine_metrics = core.metrics
        if getattr(core, "registry", None) is not None:
            # in-process jax engine: its full instrument set (scheduler
            # step/phase histograms, KV counters, disagg RTT) merges into
            # the frontend's exposition instead of the dict-gauge fallback
            pipe.telemetry_registry = core.registry
        if getattr(core, "embed_ready", False) and hasattr(core, "embed"):
            # /v1/embeddings rides the batched-prefill path of THIS
            # engine (llm/embeddings.py; prefill-only, no decode slot)
            from ..llm.embeddings import Embedder

            vocab = None
            cfg_e = getattr(core, "config", None)
            if cfg_e is not None:
                vocab = cfg_e.model.vocab_size
            pipe.embedder = Embedder(
                tokenizer, core,
                max_model_len=(
                    cfg_e.max_model_len if cfg_e is not None
                    else mdc.context_length
                ),
                vocab_size=vocab,
            )
        return pipe, mdc

    raise SystemExit(f"unknown engine {engine_spec!r}")


async def _setup_self_healing(flags, core, admission=None, drt=None,
                              component: str = "backend",
                              peer_ranker=None, instance_id: str = "",
                              ici=None):
    """--self-heal wiring: a RecoveryController per engine plus (native
    engines) a migration receiver for peers draining TOWARD this worker.

    Returns (controller, migration_server) — either may be None. Native
    in-process engines get the full ladder (trip → drain → migrate);
    subprocess-hosted engines get the respawn ladder driven by child
    deaths (their drain/migrate happens inside the child's own stack).
    """
    import uuid as _uuid

    import msgpack as _msgpack

    from ..recovery import (
        MigrationServer,
        MigrationSink,
        RecoveryConfig,
        RecoveryController,
        migration_key,
    )

    config = RecoveryConfig(
        drain_grace_s=flags.drain_grace_s,
        respawn_backoff_s=flags.respawn_backoff_s,
        max_respawns=flags.respawn_max,
    )
    # supervised-child engines: respawn ladder only — the wedge/death
    # detection and stream failure live in the subprocess host itself.
    # respawn() (not _ensure_running) so POST /admin/drain?respawn=1
    # actually restarts a LIVE child (rolling engine restart), while a
    # dead child just respawns; the controller suppresses the down
    # listener during its own drain so the kill doesn't re-trigger it.
    if hasattr(core, "add_down_listener"):
        controller = RecoveryController(
            engine_id=f"eng-{_uuid.uuid4().hex[:12]}",
            respawner=core.respawn,
            admission=admission,
            config=config,
        )
        core.add_down_listener(controller.on_child_down)
        return controller, None

    scheduler = getattr(core, "scheduler", None)
    if scheduler is None:
        return None, None  # echo/BYO engines have nothing to recover
    engine_id = f"eng-{_uuid.uuid4().hex[:12]}"
    sink = MigrationSink(scheduler, core.runner)
    server = await MigrationServer(
        sink, host=flags.advertise_host, port=flags.migrate_port,
        ici=ici,
        ici_rank=None if ici is None else getattr(ici, "receiver_rank",
                                                  None),
    ).start()

    static_peers = [
        {"host": hp.rsplit(":", 1)[0], "port": int(hp.rsplit(":", 1)[1]),
         "engine_id": f"static-{hp}"}
        for hp in flags.migrate_peers.split(",") if hp.strip()
    ]
    peers = (lambda: static_peers)
    deregister = register = None
    if drt is not None:
        key = migration_key(flags.namespace, component, engine_id)
        # worker_id: the KV-event id this worker publishes under — the
        # join key peer fabrics use to rank migration targets by prefix
        # overlap (their ownership view is keyed by KV-event ids, not
        # migration engine ids)
        desc = _msgpack.packb(
            dict(server.descriptor, engine_id=engine_id,
                 **({"worker_id": instance_id} if instance_id else {})),
            use_bin_type=True,
        )
        lease = await drt.discovery.primary_lease()
        await drt.discovery.kv_put(key, desc, lease_id=lease.id)
        # snapshot of live peer receivers, primed now and refreshed per
        # drain; excludes self by engine_id inside the controller
        peer_cache: list = list(static_peers)

        async def refresh_peers():
            prefix = migration_key(flags.namespace, component, "")
            kvs = await drt.discovery.kv_get_prefix(prefix)
            peer_cache[:] = static_peers + [
                _msgpack.unpackb(v, raw=False) for v in kvs.values()
            ]

        async def deregister():
            # routers already skip us via the draining snapshot; this
            # removes the migration descriptor so no peer drains INTO a
            # draining worker. Delete FIRST and unconditionally — a
            # flaky peer refresh must neither leave the dead worker's
            # descriptor registered nor abort the drain (the cache keeps
            # its last known pool on refresh failure).
            await drt.discovery.kv_delete(key)
            try:
                await refresh_peers()  # post-delete: self is gone too
            except Exception:
                logger.warning("peer refresh failed during drain; using "
                               "last known peers", exc_info=True)

        async def register():
            await drt.discovery.kv_put(key, desc, lease_id=lease.id)

        try:
            await refresh_peers()
        except Exception:
            logger.warning("initial migration-peer discovery failed; "
                           "starting with static peers only", exc_info=True)
        peers = (lambda: peer_cache)

    controller = RecoveryController(
        engine_id=engine_id,
        scheduler=scheduler,
        runner=core.runner,
        watchdog=getattr(core, "watchdog", None),
        peers=peers,
        deregister=deregister,
        register=register,
        admission=admission,
        config=config,
        peer_ranker=peer_ranker,
        ici=ici,
    )
    return controller, server


def _pool_scope_peers(peers: dict, endpoint_records: dict,
                      model: str = "") -> tuple:
    """Filter a fabric peer-descriptor map to this worker's model pool.

    Several model pools can share one component (per-model clients and
    the KV router partition a shared component's instances by the
    ``model`` metadata on their lease-scoped endpoint records), but the
    fabric descriptor prefix is component-wide — so without this filter
    a pull could splice another model's KV blocks into this pool's
    cache. Peers with no endpoint record yet (descriptor published
    before the registration landed) or no model metadata (single-pool
    deployments) are kept: missing metadata is a wildcard, same as the
    client-side partition rule. Returns ``(scoped, live)`` where
    ``live`` is every instance id holding an endpoint record — the
    indexer-prune set, which stays pool-agnostic because liveness is a
    property of the lease, not the pool.
    """
    import msgpack as _msgpack

    pool_of: dict = {}
    for key, raw in endpoint_records.items():
        wid = key.rsplit(":", 1)[-1]
        try:
            pool_of[wid] = _msgpack.unpackb(raw, raw=False).get("model")
        except Exception:
            logger.debug("unreadable endpoint record for %s; treating "
                         "its pool as wildcard", wid, exc_info=True)
            pool_of[wid] = None
    scoped = {
        wid: desc for wid, desc in peers.items()
        if not model or pool_of.get(wid) in (None, model)
    }
    return scoped, set(pool_of)


async def _setup_kv_fabric(flags, core, drt=None, component: str = "backend",
                           endpoint=None, instance_id: str = "",
                           model: str = "", ici=None):
    """Cluster-KV-fabric wiring for a token-level worker.

    The engine already built its fabric half (Scheduler.fabric — cold
    tier + pull machinery) from the EngineConfig knobs; this attaches
    the cluster half: the pull SERVER (advertised in discovery under
    ``fabric_key`` so peers can pull from this worker), the peer
    descriptor cache (refreshed on a cadence), and the ownership view
    (the component's KV event stream — the same events the router
    indexes). Returns the fabric or None.
    """
    import msgpack as _msgpack

    from ..kv.fabric import fabric_key
    from ..kv_router.protocols import KV_EVENT_SUBJECT, RouterEvent

    scheduler = getattr(core, "scheduler", None)
    fabric = getattr(scheduler, "fabric", None) if scheduler else None
    if fabric is None:
        return None
    if instance_id:
        # the ownership view keys workers by the SAME id the KV event
        # publisher stamps, so self-events are skippable and peer scores
        # map onto descriptors
        fabric.engine_id = instance_id
    if fabric.cold is not None:
        # respawn-warm: prime the cold index off-loop so the first
        # request after a recovery respawn sees the spilled prefixes
        n = await asyncio.get_running_loop().run_in_executor(
            None, fabric.cold.refresh
        )
        if n:
            logger.info("cold tier primed: %d resident blocks", n)
    if not fabric.peer_pull:
        # cold-tier-only configuration: local disk spill was the opt-in,
        # not cross-worker networking — no pull server, no peer view
        return fabric
    if ici is not None:
        # intra-pod peers negotiate device-to-device pulls off this
        # plane; the descriptor below advertises it
        fabric.set_ici(ici)
    server = await fabric.serve(host=flags.advertise_host)
    if drt is None or endpoint is None:
        return fabric
    key = fabric_key(flags.namespace, component, fabric.engine_id)
    # the pull server's descriptor carries modes (+ ici_rank) so peers
    # can negotiate the transfer backend per pair — TCP stays the
    # universal fallback
    desc = _msgpack.packb(
        dict(getattr(server, "descriptor", None)
             or {"host": flags.advertise_host, "port": server.port},
             engine_id=fabric.engine_id),
        use_bin_type=True,
    )
    lease = await drt.discovery.primary_lease()
    await drt.discovery.kv_put(key, desc, lease_id=lease.id)

    peer_cache: dict = {}

    async def refresh_peers():
        prefix = fabric_key(flags.namespace, component, "")
        kvs = await drt.discovery.kv_get_prefix(prefix)
        peers = {}
        for v in kvs.values():
            d = _msgpack.unpackb(v, raw=False)
            wid = d.get("engine_id")
            if wid and wid != fabric.engine_id:
                peers[wid] = d
        # prune dead workers from the ownership view: respawn churn
        # mints a fresh id per incarnation, so without this the indexer
        # accumulates dead workers' hash runs forever (and keeps the
        # admission gate open with nothing pullable). Liveness comes
        # from the lease-scoped ENDPOINT registry (keyed by the same
        # instance id KV events carry), not the pull-server descriptors
        # — workers without a pull server (cold-tier-only, plain
        # KV-routed) still publish events and still die. The same
        # records carry pool membership, scoping pulls to this model.
        eps = await drt.discovery.kv_get_prefix(
            endpoint.component.etcd_prefix())
        peers, live = _pool_scope_peers(peers, eps, model)
        peer_cache.clear()
        peer_cache.update(peers)
        for wid in list(fabric.indexer.worker_ids):
            if wid != fabric.engine_id and wid not in live:
                fabric.remove_worker(wid)

    async def refresh_loop():
        while True:
            try:
                await refresh_peers()
            except Exception:
                # discovery hiccup: keep the last known pool — a pull
                # to a dead descriptor just falls back to recompute
                logger.debug("fabric peer refresh failed", exc_info=True)
            await asyncio.sleep(5.0)

    try:
        await refresh_peers()
    except Exception:
        logger.warning("initial fabric peer discovery failed; starting "
                       "with no peers", exc_info=True)
    fabric.peers = (lambda: peer_cache)
    fabric.hold_task(drt.runtime.spawn(refresh_loop()))

    # the ownership view rides the SAME event subject the KV router
    # consumes; apply_event skips this engine's own events
    sub = await endpoint.component.subscribe_event(KV_EVENT_SUBJECT)

    async def consume_events():
        async for msg in sub:
            try:
                fabric.apply_event(RouterEvent.from_wire(
                    _msgpack.unpackb(msg.payload, raw=False)
                ))
            except Exception:
                logger.exception("bad kv event on the fabric feed")

    fabric.hold_task(drt.runtime.spawn(consume_events()))
    return fabric


def _model_card(flags, mdc, endpoint_path: str, model_type: str = "both"):
    """The fleet card a worker publishes at startup (registry/cards.py):
    name + pool endpoint + family/context from the deployment card,
    aliases and tenant visibility from the flags."""
    from ..registry.cards import card_from_mdc

    tenants = None
    if flags.model_tenants is not None:
        tenants = [t.strip() for t in flags.model_tenants.split(",")
                   if t.strip()]
    return card_from_mdc(
        mdc, endpoint_path,
        name=flags.model_name or mdc.display_name,
        model_type=model_type,
        aliases=flags.served_alias or [],
        tenants=tenants,
    )


def _advertise_model(registry, name: Optional[str]) -> None:
    """Stamp the model this process serves on its metrics registry —
    the fleet hub reads the label into /fleet/workers' MODEL column."""
    if registry is None or not name:
        return
    registry.gauge(
        "dynamo_registry_model_info",
        "1 for the model= this worker currently serves",
    ).set(1.0, model=name)


def _build_quotas(flags, admissions_registry=None):
    """--tenant-* → a TenantQuotas gate for the HTTP edge, or None.
    ``admissions_registry`` shares the admission controller's counter
    family so outcome="quota" rides the same instrument."""
    if (flags.tenant_rps <= 0 and flags.tenant_tps <= 0
            and not flags.tenant_quotas):
        return None
    from ..registry.tenants import TenantQuotas

    quotas = TenantQuotas.from_flags(
        flags.tenant_rps, flags.tenant_tps,
        overrides_path=flags.tenant_quotas,
        burst_s=flags.tenant_burst_s,
    )
    if admissions_registry is not None:
        quotas.bind_admissions(admissions_registry)
    return quotas


def _build_pools(flags, manager, watcher):
    """Pool manager for the multi-model frontend: scale-to-zero for
    idle model pools and cold-start gating for requests that find
    their pool empty. Replica actuation rides the api-store record
    when --api-store-url/--planner-deployment are set (the operator
    reconciles the patch, like the standalone planner); without a
    backend, cold requests just wait out the deadline for an
    externally-started worker."""
    from ..registry import (
        PoolConfig,
        PoolManager,
        PoolPolicy,
        PoolPolicyConfig,
        StorePoolBackend,
    )

    backend = None
    if flags.api_store_url and flags.planner_deployment:
        from ..deploy.store_source import ApiStoreClient

        backend = StorePoolBackend(
            ApiStoreClient(flags.api_store_url), flags.planner_deployment)
    if backend is None and flags.pool_scale_to_zero_idle_s <= 0:
        return None
    return PoolManager(
        manager.registry, watcher.pool_size,
        spawner=backend.spawn if backend is not None else None,
        drainer=backend.drain if backend is not None else None,
        config=PoolConfig(
            cold_start_deadline_s=flags.pool_cold_start_deadline_s),
        policy=PoolPolicy(PoolPolicyConfig(
            idle_to_zero_s=flags.pool_scale_to_zero_idle_s,
            cooldown_s=flags.pool_cooldown_s,
        )),
    )


def _build_hub(flags):
    """--hub → a FleetHub over the static --hub-target list (discovery
    targets attach later, once a DistributedRuntime exists)."""
    if not getattr(flags, "hub", False):
        return None
    from ..telemetry.hub import FleetHub, parse_target_flag

    return FleetHub(
        targets=[parse_target_flag(s) for s in (flags.hub_target or [])],
        interval_s=flags.hub_interval_s,
    )


async def _setup_incidents(flags, registry=None, watchdog=None,
                           recovery=None, slo=None, compiles=None):
    """DYN_INCIDENT_DIR / --incident-dir → an IncidentRecorder wired to
    every degradation edge this process emits, plus a local history
    sampler so bundles carry the metric curve INTO the incident.

    Returns (recorder, sampler) — both None when no dir is configured.
    """
    from ..telemetry.incidents import (
        IncidentConfig,
        IncidentRecorder,
        incident_dir,
        late_compile_probe,
        slo_probe,
    )

    if not incident_dir():
        return None, None
    from ..telemetry.history import LocalHistorySampler, MetricHistory

    recorder = IncidentRecorder(
        IncidentConfig(
            cooldown_s=flags.incident_cooldown_s,
            profile_s=flags.incident_profile_s,
        ),
        history=MetricHistory(window_s=600.0),
    )
    if watchdog is not None:
        recorder.watch_watchdog(watchdog)
    if recovery is not None:
        recorder.watch_recovery(recovery)
    if slo is not None:
        recorder.add_probe(slo_probe(slo))
    if compiles is not None:
        recorder.add_probe(late_compile_probe(compiles))
    sampler = None
    if registry is not None:
        sampler = LocalHistorySampler(
            registry, history=recorder.history, interval_s=5.0
        ).start()
    recorder.start()
    return recorder, sampler


async def run_http(flags, engine, mdc) -> None:
    from ..http.service import HttpService, ModelManager, ModelWatcher

    manager = ModelManager()
    if engine is not None:
        name = flags.model_name or (mdc.display_name if mdc else "echo")
        manager.add_chat_model(name, engine)
        if mdc is not None:  # pipeline engines dispatch chat AND completions
            manager.add_completion_model(name, engine)
        manager.set_metadata(
            name,
            model_type="both" if mdc is not None else "chat",
            max_model_len=mdc.context_length if mdc is not None else None,
        )
    admission = None
    if flags.admission_limit > 0:
        from ..planner import AdmissionConfig, AdmissionController

        admission = AdmissionController(AdmissionConfig(
            limit=flags.admission_limit,
            queue_depth=flags.admission_queue_depth,
            queue_timeout_s=flags.admission_queue_timeout_s,
        ))
    slo = None
    if flags.slo_ttft_ms > 0 or flags.slo_itl_ms > 0:
        from ..telemetry.slo import SloTracker

        slo = SloTracker(
            ttft_s=flags.slo_ttft_ms / 1e3 if flags.slo_ttft_ms > 0 else None,
            itl_s=flags.slo_itl_ms / 1e3 if flags.slo_itl_ms > 0 else None,
        )
    hub = _build_hub(flags)
    quotas = _build_quotas(
        flags, admission.registry if admission is not None else None)
    service = HttpService(
        manager, flags.http_host, flags.http_port,
        profile_dir=flags.profile_dir or None,
        admission=admission,
        slo=slo,
        trace_ttl_s=flags.trace_ttl_s,
        trace_capacity=flags.trace_capacity,
        hub=hub,
        quotas=quotas,
    )
    if engine is not None:
        # the model this frontend serves locally, for the fleet hub's
        # MODEL column (the distributed shape advertises per worker)
        _advertise_model(
            service.metrics.registry,
            flags.model_name or (mdc.display_name if mdc else "echo"))
    if hub is not None:
        # the frontend scrapes ITSELF (engine registries attach into the
        # service registry below, so one local scrape covers every layer
        # of this process) alongside the remote targets
        hub.add_local("frontend", "frontend", service.metrics.registry)
    if getattr(engine, "telemetry_registry", None) is not None:
        # in-process engine: one registry, one exposition — HTTP,
        # scheduler, KV allocator, and disagg instruments in one scrape
        service.metrics.attach_registry(engine.telemetry_registry)
    elif engine is not None and hasattr(engine, "engine_metrics"):
        # subprocess-hosted / BYO engine: metrics cross the process
        # boundary as a dict — expose them as callback gauges
        service.metrics.register_callback_gauges(
            "dynamo_engine", engine.engine_metrics
        )
    if getattr(engine, "host_registry", None) is not None:
        # supervision instruments (engine-child restart counter)
        service.metrics.attach_registry(engine.host_registry)

    recovery = migserver = None
    if flags.self_heal and engine is not None:
        core = getattr(engine, "core_engine", engine)
        recovery, migserver = await _setup_self_healing(
            flags, core, admission=admission
        )
        if recovery is not None:
            recovery.attach()
            service.drainer = recovery.admin_drain
            service.metrics.attach_registry(recovery.registry)

    planner = None
    if flags.planner:
        # in-process planner: the frontend's own saturation signals drive
        # admission tightening (and, with an engine attached, the
        # engine's slot/KV/queue state feeds the policy too)
        from ..planner import (
            LocalActuator,
            Planner,
            PlannerConfig,
            PolicyConfig,
            SlaPolicy,
            engine_metrics_source,
        )

        policy = SlaPolicy(PolicyConfig(
            min_replicas=flags.planner_min_replicas,
            max_replicas=flags.planner_max_replicas,
            scale_up_cooldown_s=flags.planner_cooldown_s,
            scale_down_cooldown_s=flags.planner_cooldown_s * 4,
        ))
        planner = Planner(
            policy, config=PlannerConfig(interval_s=flags.planner_interval_s)
        )
        if admission is not None:
            planner.add_source(admission.snapshot)
            planner.add_actuator(LocalActuator(admission=admission))
        if slo is not None:
            # user-visible latency as a first-class planner signal: the
            # policy sheds on SLO attainment, not just queue proxies
            from ..planner import slo_source

            planner.add_source(slo_source(slo))
        if engine is not None and hasattr(engine, "engine_metrics"):
            planner.add_source(engine_metrics_source(engine.engine_metrics))
        if hub is not None:
            # fleet-level saturation: the policy consults the scraped
            # POOL's busy/KV/SLO rollups, not just this process's view
            planner.add_source(hub.signal_source())
        service.metrics.attach_registry(planner.registry)
        planner.start()

    # incident recorder: wired to every degradation edge this process
    # emits (engine watchdog, recovery ladder, SLO floor, late compiles)
    core = getattr(engine, "core_engine", engine) if engine is not None else None
    incidents, inc_sampler = await _setup_incidents(
        flags, registry=service.metrics.registry,
        watchdog=getattr(core, "watchdog", None),
        recovery=recovery, slo=slo,
        compiles=getattr(getattr(core, "runner", None), "compiles", None),
    )
    if incidents is not None:
        service.incidents = incidents
        service.metrics.attach_registry(incidents.registry)

    watcher = None
    pools = None
    if flags.store_port is not None:
        from ..registry.registry import RegistryAdmin
        from ..runtime.component import DistributedRuntime
        from ..runtime.client import RouterMode

        drt = await DistributedRuntime.connect(flags.store_host, flags.store_port)
        if hub is not None:
            # distributed frontend: scrape every sidecar workers
            # registered in the discovery plane, on top of the statics
            from ..telemetry.hub import discovery_targets

            hub.discover = discovery_targets(drt, flags.namespace)
        watcher = ModelWatcher(
            drt, manager, flags.namespace, RouterMode(flags.router_mode)
        )
        await watcher.start()
        # dynamic model management (POST/DELETE /admin/models,
        # dynamoctl): writes the same discovery records workers publish
        service.registry_admin = RegistryAdmin(drt, flags.namespace)
        # per-model pool elasticity: scale-to-zero + cold-start gating
        pools = _build_pools(flags, manager, watcher)
        if pools is not None:
            service.attach_pools(pools)
            pools.start(spawn=drt.runtime.spawn)
    if hub is not None:
        hub.start()

    await service.start()
    runner = getattr(getattr(engine, "core_engine", None), "runner", None)
    if runner is not None:
        # an in-process jax engine: the last mark of its start-up
        # timeline. Where DYN_TRACE_JSONL is set the whole of it goes to
        # the sink as the record "startup" (not into the request ring,
        # whose TTL would drop it)
        startup = runner.startup
        startup.mark("listening")
        startup.within("serve", startup.seconds["scheduler"]
                       + startup.seconds["listening"])
        service.traces.write(startup.record())
    print(f"listening on http://{flags.http_host}:{service.port}", flush=True)
    # SIGTERM drains in-flight requests for up to the configured grace
    # period (reference WorkerConfig.graceful_shutdown_timeout, DYN_WORKER_
    # env) instead of dropping streams mid-token
    import signal

    from ..utils.config import RuntimeSettings

    settings = RuntimeSettings.from_settings()
    stop_event = asyncio.Event()
    force_event = asyncio.Event()
    loop = asyncio.get_running_loop()

    def _on_signal():
        # first signal: drain; second: skip the drain and exit now
        if stop_event.is_set():
            force_event.set()
        stop_event.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, _on_signal)
        except (NotImplementedError, RuntimeError):
            pass
    try:
        await stop_event.wait()
        # stop accepting first — otherwise new requests keep arriving and
        # the drain below can never converge under steady traffic
        await service.stop_accepting()
        deadline = loop.time() + settings.graceful_shutdown_timeout
        while (service.metrics.inflight_total() > 0
               and loop.time() < deadline and not force_event.is_set()):
            await asyncio.sleep(0.1)
    finally:
        if planner is not None:
            planner.stop()
        if pools is not None:
            await pools.stop()
        if hub is not None:
            await hub.stop()
        if inc_sampler is not None:
            await inc_sampler.stop()
        if incidents is not None:
            await incidents.stop()
        if recovery is not None:
            await recovery.close()
        if migserver is not None:
            await migserver.close()
        if watcher:
            await watcher.stop()
        await service.stop()


async def run_text(flags, engine, mdc, interactive: bool = True) -> None:
    from ..protocols.annotated import Annotated
    from ..protocols.openai import ChatCompletionRequest
    from ..runtime.engine import Context

    name = flags.model_name or (mdc.display_name if mdc else "echo")
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("> "))
        except (EOFError, KeyboardInterrupt):
            return
        if not line.strip():
            continue
        req = ChatCompletionRequest(
            model=name, messages=[{"role": "user", "content": line}], stream=True
        )
        async for chunk in engine.generate(Context(req)):
            if Annotated.maybe_from_wire(chunk) is not None:
                continue  # annotation envelopes carry no printable text
            d = chunk if isinstance(chunk, dict) else chunk.model_dump(exclude_none=True)
            for choice in d.get("choices", []):
                content = (choice.get("delta") or {}).get("content")
                if content:
                    print(content, end="", flush=True)
        print()


async def advertise_sidecar(drt, flags, mserver, role: str,
                            instance: str) -> None:
    """Register a process's /metrics sidecar in discovery so a fleet hub
    (in=hub / --hub) finds it without static config; the lease-scoped
    key vanishes with the worker. Shared by every sidecar-running role
    (run_worker's three shapes, run_prefill)."""
    if mserver is None:
        return
    from ..telemetry.hub import register_metrics_endpoint

    try:
        await register_metrics_endpoint(
            drt, flags.namespace, role, instance,
            f"http://{flags.advertise_host}:{mserver.port}/metrics",
        )
    except Exception:
        logger.warning("metrics-sidecar discovery registration "
                       "failed; hub scrapes need --hub-target",
                       exc_info=True)


async def run_worker(flags, engine_spec: str, path: str) -> None:
    """Distributed worker roles (in=dyn://ns.comp.ep):

    - default: full OpenAI-level worker (preprocess+engine+detokenize here)
    - --token-level: engine worker serving PreprocessedRequests, publishing
      KV events + ForwardPassMetrics for KV-aware routers
    - out=processor: preprocess + KV-route to --worker-endpoint workers
    """
    import uuid

    from ..http.service import parse_endpoint_path, register_model
    from ..runtime.component import DistributedRuntime
    from ..runtime.engine import Context
    from ..telemetry.server import maybe_start_metrics_server

    if flags.store_port is None:
        raise SystemExit("in=dyn:// requires --store-port")
    if engine_spec == "none":
        raise SystemExit("out=none is only valid with in=http (pure frontend)")
    ns_name, comp, ep_name = parse_endpoint_path(path)
    drt = await DistributedRuntime.connect(flags.store_host, flags.store_port)
    endpoint = drt.namespace(ns_name).component(comp).endpoint(ep_name)
    mserver = None  # sidecar /metrics exposition (--metrics-port)
    incidents = inc_sampler = None

    def make_openai_handler(engine):
        async def handler(payload, ctx):
            from ..protocols.annotated import Annotated
            from ..protocols.openai import ChatCompletionRequest, CompletionRequest

            cls = ChatCompletionRequest if "messages" in payload else CompletionRequest
            async for chunk in engine.generate(Context(cls.model_validate(payload), ctx)):
                if isinstance(chunk, Annotated):
                    yield chunk.to_wire()
                else:
                    yield chunk if isinstance(chunk, dict) else chunk.model_dump(exclude_none=True)

        return handler

    if engine_spec == "processor":
        from ..kv_router.router import KvRouter
        from ..llm.processor import build_processor_pipeline
        from ..runtime.client import Client, RouterMode

        if not flags.worker_endpoint:
            raise SystemExit("out=processor requires --worker-endpoint")
        mdc = load_mdc(flags)
        wns, wcomp, wep = parse_endpoint_path(flags.worker_endpoint)
        w_endpoint = drt.namespace(wns).component(wcomp).endpoint(wep)
        client = Client(
            w_endpoint,
            RouterMode.ROUND_ROBIN if flags.router_mode == "kv"
            else RouterMode(flags.router_mode),
        )
        router = None
        if flags.router_mode == "kv":
            router = await KvRouter(
                w_endpoint.component, client, block_size=flags.kv_block_size,
                staleness_bound_s=flags.router_staleness_bound_s,
            ).start()
        else:
            await client.start()
        engine = build_processor_pipeline(mdc, client, router)
        name = flags.model_name or mdc.display_name
        serving = await endpoint.serve(make_openai_handler(engine),
                                       span_source="processor",
                                       metadata={"model": name})
        await register_model(drt, flags.namespace, name, path, model_type="both",
                             mdc={"context_length": mdc.context_length},
                             card=_model_card(flags, mdc, path))
        if router is not None:
            # the router's own observability surface: per-worker scraped
            # load + routing decisions, previously internal-only
            _advertise_model(router.registry, name)
            mserver = await maybe_start_metrics_server(
                router.registry, flags.metrics_port
            )
            await advertise_sidecar(
                drt, flags, mserver, "processor",
                f"processor-{uuid.uuid4().hex[:12]}")
        print(f"processor serving {path} (model={name} → {flags.worker_endpoint})", flush=True)

    elif flags.token_level:
        from ..kv_router.publisher import KvEventPublisher, KvMetricsPublisher

        mdc = load_mdc(flags)
        instance_id = f"w-{uuid.uuid4().hex[:12]}"
        publisher = KvEventPublisher(endpoint.component, instance_id)
        publisher.start()
        core = await build_core_engine(
            engine_spec, flags, mdc, events=publisher.as_sink(), drt=drt
        )

        async def handler(payload, ctx):
            async for out in core.generate(Context(payload, ctx)):
                yield out

        metrics_fn = core.metrics if hasattr(core, "metrics") else dict
        model_name = flags.model_name or mdc.display_name
        serving = await endpoint.serve(
            handler,
            instance_id=instance_id,
            stats_handler=KvMetricsPublisher(metrics_fn).stats_handler,
            span_source="decode_engine",
            # pool membership rides the lease-scoped endpoint record:
            # per-model clients and the KV router partition instances
            # of a shared component by this metadata
            metadata={"model": model_name},
        )
        _advertise_model(getattr(core, "registry", None), model_name)
        # one ICI plane per worker, shared by the fabric pull path and
        # hot migration — a single collective-ordering lock means the
        # two planes can never interleave (mis-pair) their collectives
        ici = None
        if getattr(core, "runner", None) is not None:
            raw_ici = _make_ici(flags, core.runner)
            if raw_ici is not None:
                from ..transfer.ici import IciBackend

                ici = IciBackend(raw_ici)
        # cluster KV fabric: pull server + peer/ownership view, keyed by
        # the same instance id the KV event publisher stamps
        fabric = await _setup_kv_fabric(
            flags, core, drt=drt, component=comp, endpoint=endpoint,
            instance_id=instance_id, model=model_name, ici=ici,
        )
        recovery = None
        if flags.self_heal:
            # watchdog trips drain this worker, migrate its in-flight
            # requests to peer workers discovered under the component's
            # migration prefix, and respawn (docs/self_healing.md);
            # migration targets rank by the fabric's ownership view
            # (prefix overlap) when one exists
            recovery, _migserver = await _setup_self_healing(
                flags, core, drt=drt, component=comp,
                peer_ranker=fabric.rank_peers if fabric is not None
                else None,
                instance_id=instance_id, ici=ici,
            )
            if recovery is not None:
                recovery.attach()
                reg = getattr(core, "registry", None)
                if reg is not None:
                    reg.attach(recovery.registry)
        # incident bundles at trip time: the engine worker is where the
        # wedges actually happen — a decode_stall here must leave its
        # evidence on disk even after recovery respawns the engine
        incidents, inc_sampler = await _setup_incidents(
            flags, registry=getattr(core, "registry", None),
            watchdog=getattr(core, "watchdog", None),
            recovery=recovery,
            compiles=getattr(getattr(core, "runner", None), "compiles", None),
        )
        if incidents is not None:
            reg = getattr(core, "registry", None)
            if reg is not None:
                reg.attach(incidents.registry)
        # in-process jax engines carry the full scheduler/KV registry;
        # workers with no registry (echo, BYO) just skip the sidecar
        mserver = await maybe_start_metrics_server(
            getattr(core, "registry", None), flags.metrics_port
        )
        await advertise_sidecar(drt, flags, mserver, "decode_engine",
                                instance_id)
        print(f"token-level worker {instance_id} serving {path}", flush=True)

    else:
        engine, mdc = await build_engine(engine_spec, flags, drt=drt)
        name = flags.model_name or (mdc.display_name if mdc else "echo")
        serving = await endpoint.serve(make_openai_handler(engine),
                                       metadata={"model": name})
        model_type = "both" if mdc is not None else "chat"
        await register_model(
            drt, flags.namespace, name, path, model_type=model_type,
            mdc={"context_length": mdc.context_length} if mdc else None,
            card=_model_card(flags, mdc, path, model_type)
            if mdc is not None else None,
        )
        _advertise_model(
            getattr(engine, "telemetry_registry", None), name)
        mserver = await maybe_start_metrics_server(
            getattr(engine, "telemetry_registry", None), flags.metrics_port
        )
        await advertise_sidecar(
            drt, flags, mserver, "worker", f"worker-{uuid.uuid4().hex[:12]}")
        print(f"worker serving {path} (model={name})", flush=True)

    try:
        await asyncio.Event().wait()
    finally:
        if inc_sampler is not None:
            await inc_sampler.stop()
        if incidents is not None:
            await incidents.stop()
        if mserver is not None:
            await mserver.stop()
        await serving.stop()


async def run_prefill(flags) -> None:
    """Dedicated prefill worker: consumes the namespace prefill queue.

    The prefill_worker role of the disagg graph (reference:
    examples/llm/components/prefill_worker.py poll loop)."""
    from ..disagg import PrefillWorker
    from ..engine.model_runner import ModelRunner
    from ..engine.serving import engine_config_from_mdc
    from ..runtime.component import DistributedRuntime
    from ..telemetry.server import maybe_start_metrics_server

    if flags.store_port is None:
        raise SystemExit("in=prefill requires --store-port")
    mdc = load_mdc(flags)
    engine_config = engine_config_from_mdc(mdc, flags)
    drt = await DistributedRuntime.connect(flags.store_host, flags.store_port)
    loop = asyncio.get_running_loop()
    runner = await loop.run_in_executor(
        None, lambda: ModelRunner(engine_config, model_dir=mdc.model_path)
    )
    worker = PrefillWorker(
        drt, runner, engine_config, namespace=flags.namespace,
        ici=_make_ici(flags, runner),
    )
    # same sidecar the decode workers run: prefill throughput, transfer
    # bytes, queue wait, and the transfer-overlap histograms land in a
    # scrapeable /metrics instead of only the ad-hoc metrics() dict
    _advertise_model(worker.registry,
                     flags.model_name or mdc.display_name)
    mserver = await maybe_start_metrics_server(
        worker.registry, flags.metrics_port
    )
    import uuid

    await advertise_sidecar(
        drt, flags, mserver, "prefill_worker",
        f"prefill-{uuid.uuid4().hex[:12]}")
    print(f"prefill worker consuming {worker.queue.name}", flush=True)
    try:
        await worker.run()
    finally:
        if mserver is not None:
            await mserver.stop()
        await worker.close()
        await drt.close()


async def run_hub(flags) -> None:
    """Standalone fleet-telemetry-hub role (in=hub): scrape every
    --hub-target and discovery-registered metrics sidecar into history
    rings and serve /metrics (the hub's own instruments + rollup
    gauges), /fleet/metrics, /fleet/workers, and /debug/incidents on
    ``--http-port`` — the process ``scripts/dynamotop.py`` points at."""
    from ..runtime.component import DistributedRuntime
    from ..telemetry.hub import FleetHub, discovery_targets, parse_target_flag
    from ..telemetry.incidents import IncidentRecorder, incident_dir
    from ..telemetry.server import MetricsServer

    targets = [parse_target_flag(s) for s in (flags.hub_target or [])]
    discover = None
    drt = None
    if flags.store_port is not None:
        drt = await DistributedRuntime.connect(
            flags.store_host, flags.store_port)
        discover = discovery_targets(drt, flags.namespace)
    if not targets and discover is None:
        raise SystemExit(
            "in=hub needs scrape targets: --hub-target role=url and/or "
            "--store-port for discovery-registered sidecars"
        )
    hub = FleetHub(targets=targets, discover=discover,
                   interval_s=flags.hub_interval_s)
    routes = [
        ("GET", "/fleet/metrics", hub.handle_fleet_metrics),
        ("GET", "/fleet/workers", hub.handle_fleet_workers),
    ]
    incidents = None
    if incident_dir():
        # listing/fetch surface only — triggers live in the engine
        # processes that own the evidence
        incidents = IncidentRecorder()
        routes.append(("GET", "/debug/incidents",
                       incidents.handle_debug_incidents))
    else:
        # same 501-with-hint contract as the frontend: an operator must
        # learn the flag, not guess at a bare 404
        async def _incidents_off(request):
            from aiohttp import web

            return web.json_response(
                {"error": "no incident recorder attached (set "
                          "DYN_INCIDENT_DIR or --incident-dir)"},
                status=501,
            )

        routes.append(("GET", "/debug/incidents", _incidents_off))
    server = await MetricsServer(
        hub.registry, flags.http_host, flags.http_port, routes=routes
    ).start()
    hub.start()
    print(f"fleet hub on http://{flags.http_host}:{server.port} "
          f"({len(targets)} static target(s)"
          f"{', discovery-driven' if discover else ''})", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await hub.stop()
        await server.stop()
        if drt is not None:
            await drt.close()


async def run_planner(flags) -> None:
    """Standalone SLA-planner role (in=planner): scrape the worker pool's
    load snapshots + the prefill work-queue depth, run the policy, and
    actuate — disagg-router thresholds through the discovery plane, and
    per-role replica counts through the api-store record the operator
    reconciles (``--api-store-url`` + ``--planner-deployment``)."""
    from ..disagg.protocols import PrefillQueue
    from ..http.service import parse_endpoint_path
    from ..kv_router.metrics_aggregator import KvMetricsAggregator
    from ..planner import (
        LocalActuator,
        Planner,
        PlannerConfig,
        PolicyConfig,
        SlaPolicy,
        StoreScaleActuator,
        aggregator_source,
    )
    from ..runtime.client import Client, RouterMode
    from ..runtime.component import DistributedRuntime
    from ..telemetry.server import maybe_start_metrics_server

    if flags.store_port is None:
        raise SystemExit("in=planner requires --store-port")
    if not flags.worker_endpoint:
        raise SystemExit(
            "in=planner requires --worker-endpoint "
            "(the decode workers to observe)"
        )
    drt = await DistributedRuntime.connect(flags.store_host, flags.store_port)
    wns, wcomp, wep = parse_endpoint_path(flags.worker_endpoint)
    client = Client(
        drt.namespace(wns).component(wcomp).endpoint(wep),
        RouterMode.ROUND_ROBIN,
    )
    await client.start()
    aggregator = KvMetricsAggregator(client)
    aggregator.start()

    policy = SlaPolicy(
        PolicyConfig(
            min_replicas=flags.planner_min_replicas,
            max_replicas=flags.planner_max_replicas,
            scale_up_cooldown_s=flags.planner_cooldown_s,
            scale_down_cooldown_s=flags.planner_cooldown_s * 4,
        ),
        initial_local_prefill_length=flags.max_local_prefill_length,
        initial_prefill_queue_size=flags.max_prefill_queue_size,
    )
    planner = Planner(
        policy, config=PlannerConfig(interval_s=flags.planner_interval_s)
    )
    planner.add_source(aggregator_source(aggregator))

    # prefill work-queue depth: same cached-poll pattern the decode-side
    # coordinator uses (disagg/coordinator.py _depth_loop). The dict
    # starts EMPTY and empties again on failure — fabricating a 0 here
    # would read as "queue drained" and steer the rebalance policy the
    # wrong way exactly when the messaging plane is down.
    queue = PrefillQueue(drt.messaging, flags.namespace)
    depth: dict = {}

    async def _depth_loop() -> None:
        while True:
            try:
                depth["prefill.queue_depth"] = float(await queue.depth())
            except Exception:
                depth.clear()
                logger.debug("prefill queue depth refresh failed",
                             exc_info=True)
            await asyncio.sleep(1.0)

    depth_task = drt.runtime.spawn(_depth_loop())
    planner.add_source(lambda: depth)

    planner.add_actuator(LocalActuator(
        discovery=drt.discovery, namespace=flags.namespace,
        model_name=flags.model_name,
    ))
    if flags.api_store_url and flags.planner_deployment:
        from ..deploy.store_source import ApiStoreClient

        planner.add_actuator(StoreScaleActuator(
            ApiStoreClient(flags.api_store_url), flags.planner_deployment,
        ))
    else:
        logger.warning(
            "in=planner without --api-store-url/--planner-deployment: "
            "scale actions will be decided and logged but not actuated"
        )

    hub = _build_hub(flags)
    routes = None
    if hub is not None:
        # fleet hub riding the planner: scrape the discovery-registered
        # sidecars, feed fleet-level saturation into the policy, and
        # serve /fleet/* next to the planner's own exposition
        from ..telemetry.hub import discovery_targets

        hub.discover = discovery_targets(drt, flags.namespace)
        planner.add_source(hub.signal_source())
        planner.registry.attach(hub.registry)
        routes = [
            ("GET", "/fleet/metrics", hub.handle_fleet_metrics),
            ("GET", "/fleet/workers", hub.handle_fleet_workers),
        ]
        hub.start(spawn=drt.runtime.spawn)

    mserver = await maybe_start_metrics_server(
        planner.registry, flags.metrics_port, routes=routes
    )
    planner.start(spawn=drt.runtime.spawn)
    print(f"planner observing {flags.worker_endpoint} "
          f"every {flags.planner_interval_s:.1f}s"
          f"{' + fleet hub' if hub else ''}", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        planner.stop()
        depth_task.cancel()
        if hub is not None:
            await hub.stop()
        if mserver is not None:
            await mserver.stop()
        aggregator.stop()
        await client.close()
        await drt.close()


async def amain(argv: List[str]) -> None:
    src, engine_spec, rest = parse_io(argv)
    flags = build_parser().parse_args(rest)
    from ..utils.logging import setup_logging
    setup_logging(logging.DEBUG if flags.verbose else logging.INFO)

    if flags.flight_dir:
        # one env var is the single source of truth for every dump site
        # (watchdog trips, SIGUSR2, /debug/flight?save=1)
        import os

        os.environ["DYN_FLIGHT_DIR"] = flags.flight_dir
    if flags.incident_dir:
        # same single-source-of-truth pattern for incident bundles
        import os

        os.environ["DYN_INCIDENT_DIR"] = flags.incident_dir
    # SIGUSR2 → flight artifact, on EVERY role (frontend, worker,
    # prefill): the zero-downtime way to ask "what is this process
    # doing" — works even when the event loop is wedged
    from ..telemetry.watchdog import install_signal_dump

    install_signal_dump()

    if src == "prefill" or (engine_spec == "jax"
                            and not flags.isolate_engine):
        # this process compiles: place the cache before any backend
        # touch (an --isolate-engine parent stays off jax; its child
        # places its own in llm/engines/subprocess_host.py)
        from ..engine.device import configure_compile_cache

        configure_compile_cache()

    if flags.num_nodes > 1:
        # must run before the first jax backend touch in this process so
        # jax.devices() is already global when the engine builds its mesh
        from ..parallel.mesh import MultiHostConfig, initialize_multihost

        initialize_multihost(MultiHostConfig(
            leader_addr=flags.leader_addr,
            num_nodes=flags.num_nodes,
            node_rank=flags.node_rank,
        ))

    if flags.profiler_port:
        # AFTER multihost init: start_server touches the backend, which
        # would pin a local-only world before jax.distributed runs
        from ..utils.profiling import enable_profiler_server

        enable_profiler_server(flags.profiler_port)

    if src == "prefill":
        await run_prefill(flags)
        return
    if src == "planner":
        await run_planner(flags)
        return
    if src == "hub":
        await run_hub(flags)
        return
    if src.startswith("dyn://"):
        await run_worker(flags, engine_spec, src)
        return

    engine, mdc = await build_engine(engine_spec, flags)
    if src == "http":
        await run_http(flags, engine, mdc)
    elif src in ("text", "stdin"):
        await run_text(flags, engine, mdc)
    elif src.startswith("batch:"):
        from .batch import run_batch

        await run_batch(flags, engine, mdc, src[len("batch:"):])
    else:
        raise SystemExit(f"unknown input {src!r}")


def main() -> None:
    try:
        asyncio.run(amain(sys.argv[1:]))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
