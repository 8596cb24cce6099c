"""Benchmark: decode throughput of the native JAX engine on one TPU chip.

Runs the flagship Llama-3.2-1B-class config (bf16, paged KV cache) and
measures steady-state batched decode throughput. Prints ONE JSON line.

``vs_baseline`` is measured tokens/sec divided by the single-chip
HBM-roofline estimate for the same model/batch (decode is bandwidth-bound:
every step must stream all weights + the batch's KV context from HBM).
v5e: ~819 GB/s HBM. A value near 1.0 means the engine is at roofline;
the reference's engines (vLLM-class) typically sit at 0.5-0.7 of roofline
on their hardware (no absolute numbers are published in the reference —
BASELINE.md).

Attempt order: the per-token XLA path first, then the engine's fused
multi-step decode on the same XLA path (multi_step_decode: 8 steps per
dispatch via lax.scan — amortizes the fixed dispatch overhead that
dominates small-model decode), then the levers, then the Pallas burst
attempt with the remaining budget. The best valid number wins.

One process per chip: this parent never touches a jax backend; every
attempt is a child that owns the chip for its lifetime, under a hard
timeout (a Mosaic compile can hang rather than fail). Without a TPU
backend the bench exits non-zero unless BENCH_SMOKE=1 (tiny shapes, a
logic check, never a measurement), and with no result it exits non-zero:
it never prints a number it did not just measure. Budget knobs:
BENCH_TOTAL_BUDGET_S (default 1380), BENCH_TIMEOUT_S (per-XLA-attempt,
default 600), BENCH_XLA_ONLY=1, BENCH_SINGLE_STEP_ONLY=1.
"""

from __future__ import annotations

import json
import os as _os
import subprocess
import sys as _sys
import time

import numpy as np

_HERE = _os.path.dirname(_os.path.abspath(__file__))
_sys.path.insert(0, _HERE)

METRIC = "decode_tokens_per_sec_per_chip_1b_bf16_b8_ctx512"
# a child that finds no TPU backend (and no BENCH_SMOKE) exits with this,
# and so does the bench
NO_CHIP_RC = 3


def _bench_device():
    """First call of every attempt child: place the compile cache, then
    name the device being measured. Without BENCH_SMOKE a non-TPU
    backend ends the child — a CPU timing is never printed under a chip
    metric's name."""
    from dynamo_tpu.engine.device import configure_compile_cache

    configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not _os.environ.get("BENCH_SMOKE"):
        _sys.stderr.write(
            f"bench: backend is {dev.platform!r}, not a TPU; set "
            "BENCH_SMOKE=1 for a tiny-shape logic check\n")
        raise SystemExit(NO_CHIP_RC)
    return dev


def run_once(attention_impl: str, burst: int = 1,
             pipeline: bool = False, persistent: bool = False,
             spec: bool = False, guided: bool = False) -> dict:
    import os

    dev = _bench_device()
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import FLAGSHIP
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.telemetry.device_time import HBM_PEAK_GBPS

    smoke = bool(os.environ.get("BENCH_SMOKE"))  # tiny shapes: logic check only
    mcfg = ModelConfig(**(dict(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2,
    ) if smoke else FLAGSHIP), attention_impl=attention_impl)
    cfg = EngineConfig(
        model=mcfg, max_batch_size=8, max_model_len=2048, kv_block_size=16,
        num_kv_blocks=1024, dtype="float32" if smoke else "bfloat16",
    )
    b, bs = cfg.max_batch_size, cfg.kv_block_size
    ctx = 512  # steady-state context per sequence
    # the engine sizes decode block tables to the live context
    # (EngineConfig.kv_width_bucket); the bench mirrors that
    w = cfg.kv_width_bucket(ctx // bs + 1)

    dtype = jnp.float32 if smoke else jnp.bfloat16
    params = llama.init_params(mcfg, jax.random.PRNGKey(0), dtype)
    if os.environ.get("BENCH_QUANT") == "int8":
        # weight-only int8 serving (models/quant.py): halves the weight
        # stream; the roofline below re-computes from the actual leaf
        # bytes, so vs_baseline stays honest for the quantized program
        from dynamo_tpu.models.quant import quantize_params

        params = quantize_params(params)
    kv_dtype = (
        jnp.float8_e4m3fn if os.environ.get("BENCH_KV") == "fp8" else dtype
    )
    k_cache, v_cache = llama.init_kv_cache(
        mcfg, cfg.num_kv_blocks, cfg.kv_block_size, kv_dtype
    )

    block_tables = jnp.asarray(
        np.arange(b * w, dtype=np.int32).reshape(b, w) % cfg.num_kv_blocks
    )

    def decode_step(params, k_cache, v_cache, tokens, positions,
                    slot_mapping, context_lens):
        logits, (k_cache, v_cache) = llama.forward(
            params, mcfg, tokens, positions, (k_cache, v_cache),
            block_tables, slot_mapping, context_lens,
        )
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), k_cache, v_cache

    step = jax.jit(decode_step, donate_argnums=(1, 2))

    tokens = jnp.zeros((b, 1), jnp.int32)
    positions = jnp.full((b, 1), ctx, jnp.int32)
    slot_mapping = (block_tables[:, ctx // bs] * bs + ctx % bs)[:, None]
    context_lens = jnp.full((b,), ctx + 1, jnp.int32)

    if burst > 1 and persistent and spec:
        # the engine's chained propose-verify round (decode_burst_spec):
        # each dispatch runs ONE S = burst-position forward (pending
        # token + proposals), takes the per-position argmax as the
        # verify, and folds acceptance + the done-mask freeze into the
        # device carry — the serving scheduler's shape for speculative
        # traffic under --device-finish. The measured number is verified
        # positions/s (the full-acceptance ceiling; real acceptance
        # scales it by (a+1)/S — the live
        # dynamo_engine_spec_accept_length histogram is the serving-time
        # scaler).
        stop_ids = jnp.full((b, 8), mcfg.vocab_size + 1, jnp.int32)
        S = burst
        spec_positions = positions + jnp.arange(S)[None, :]
        spec_slots = jnp.tile(slot_mapping, (1, S))

        def spec_round(params, k_cache, v_cache, tok0, done0):
            row_toks = jnp.tile(tok0[:, None], (1, S))
            logits, (k_cache, v_cache) = llama.forward(
                params, mcfg, row_toks, spec_positions, (k_cache, v_cache),
                block_tables, spec_slots, context_lens + S,
            )
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = greedy[:, :-1] == row_toks[:, 1:]
            acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
            nt = jnp.take_along_axis(greedy, acc[:, None], axis=1)[:, 0]
            nt = jnp.where(done0, tok0, nt)
            done = done0 | (nt[:, None] == stop_ids).any(axis=1)
            return nt, done, k_cache, v_cache

        step = jax.jit(spec_round, donate_argnums=(1, 2))
        done0 = jnp.zeros((b,), jnp.bool_)

        def dispatch(out, k, v):
            nt, _done, k, v = step(params, k, v, out, done0)
            return nt, k, v
    elif burst > 1 and persistent:
        # the engine's persistent decode loop (device_finish): the fused
        # K-step burst additionally carries a per-row done mask and runs
        # the stop-token membership check each step — the on-device
        # finish detection the serving scheduler uses to chain bursts
        # without a per-burst host barrier. The stop set here is chosen
        # never to hit (token ids are < vocab), so the chain runs full
        # length while paying the real per-step check cost. With
        # ``guided`` the carry additionally holds a per-row grammar
        # state advanced through a device transition table whose row
        # masks the logits each step (the serving scheduler's shape for
        # in-bound guided traffic under --device-finish) — transitions
        # never reject, so the chain runs full length while paying the
        # real mask-compute + table-lookup cost.
        stop_ids = jnp.full((b, 8), mcfg.vocab_size + 1, jnp.int32)
        n_states = 64
        gtable = (
            jnp.asarray(
                np.random.default_rng(0).integers(
                    1, n_states, size=(n_states, mcfg.vocab_size)
                ), jnp.int32,
            ) if guided else None
        )

        def decode_burst_df(params, k_cache, v_cache, tok0, done0, gst0):
            def one(carry, _):
                k_cache, v_cache, toks, done, gst = carry
                logits, (k_cache, v_cache) = llama.forward(
                    params, mcfg, toks[:, None], positions,
                    (k_cache, v_cache), block_tables, slot_mapping,
                    context_lens,
                )
                last = logits[:, -1]
                if gtable is not None:
                    last = last + jnp.where(gtable[gst] < 0, -1e9, 0.0)
                nt = jnp.argmax(last, axis=-1).astype(jnp.int32)
                nt = jnp.where(done, toks, nt)  # frozen rows hold
                done = done | (nt[:, None] == stop_ids).any(axis=1)
                if gtable is not None:
                    gst = gtable[gst, nt]
                return (k_cache, v_cache, nt, done, gst), None
            (k_cache, v_cache, nt, done, gst), _ = jax.lax.scan(
                one, (k_cache, v_cache, tok0, done0, gst0), None,
                length=burst
            )
            return nt, done, gst, k_cache, v_cache

        step = jax.jit(decode_burst_df, donate_argnums=(1, 2))
        done0 = jnp.zeros((b,), jnp.bool_)
        gst0 = jnp.zeros((b,), jnp.int32)

        def dispatch(out, k, v):
            nt, _done, _gst, k, v = step(params, k, v, out, done0, gst0)
            return nt, k, v
    elif burst > 1:
        # the engine's multi_step_decode path: K steps fused into one
        # dispatch via lax.scan (steady-state position, same per-token
        # work) — measures how much of the per-dispatch overhead the
        # fused program removes
        def decode_burst(params, k_cache, v_cache, tok0):
            def one(carry, _):
                k_cache, v_cache, toks = carry
                nt, k_cache, v_cache = decode_step(
                    params, k_cache, v_cache, toks[:, None], positions,
                    slot_mapping, context_lens,
                )
                return (k_cache, v_cache, nt), None
            (k_cache, v_cache, nt), _ = jax.lax.scan(
                one, (k_cache, v_cache, tok0), None, length=burst
            )
            return nt, k_cache, v_cache
        step = jax.jit(decode_burst, donate_argnums=(1, 2))
        dispatch = lambda out, k, v: step(params, k, v, out)  # noqa: E731
    else:
        dispatch = lambda out, k, v: step(  # noqa: E731
            params, k, v, out[:, None], positions, slot_mapping, context_lens
        )

    # warmup / compile
    out = jnp.zeros((b,), jnp.int32) if burst > 1 else tokens[:, 0]
    out, k_cache, v_cache = dispatch(out, k_cache, v_cache)
    out.block_until_ready()

    n_steps = (4 * burst) if smoke else 64
    t0 = time.perf_counter()
    if persistent:
        # the engine's persistent decode loop: bursts dispatch
        # back-to-back off the device-resident carry (finish detection
        # rides inside the program — no per-burst verdict needed on the
        # host), while a drain thread syncs every burst's tokens to the
        # host — as serving must stream them — WITHOUT ever gating the
        # next dispatch. Compare against xla:k8:pipelined (per-burst
        # sync overlapped but still completing before dispatch k+2) and
        # xla:k8 (never syncs, the unreachable upper bound).
        import concurrent.futures as _cf

        with _cf.ThreadPoolExecutor(max_workers=1) as drain:
            drains = []
            for _ in range(n_steps // burst):
                out, k_cache, v_cache = dispatch(out, k_cache, v_cache)
                drains.append(drain.submit(np.asarray, out))
            for f in drains:
                f.result()
    elif pipeline:
        # the engine's dispatch-ahead decode loop
        # (EngineConfig.decode_pipeline_depth=2): every burst's sampled
        # tokens ARE synced to the host (the serving engine must stream
        # them), but the sync happens AFTER the next burst is dispatched,
        # so the host conversion overlaps device compute instead of
        # serializing with it. Compare against the plain burst attempt
        # (no per-burst sync at all — an upper bound the engine can't
        # reach) to see what the overlap recovers.
        prev = None
        for _ in range(n_steps // burst):
            out, k_cache, v_cache = dispatch(out, k_cache, v_cache)
            if prev is not None:
                np.asarray(prev)  # reconcile burst k while k+1 executes
            prev = out
        np.asarray(prev)
    else:
        for _ in range(n_steps // burst):
            out, k_cache, v_cache = dispatch(out, k_cache, v_cache)
        out.block_until_ready()
    dt = time.perf_counter() - t0

    toks_per_sec = b * (n_steps // burst) * burst / dt

    # HBM roofline: per decode step, stream weights once + per-seq KV(ctx)
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    kv_bytes_per_seq = (
        2 * mcfg.num_layers * ctx * mcfg.num_kv_heads * mcfg.head_dim
        * jnp.dtype(kv_dtype).itemsize
    )
    step_bytes = param_bytes + b * kv_bytes_per_seq
    # no peak for this device kind (a smoke run on the CPU) → no fraction
    peak_gbps = HBM_PEAK_GBPS.get(dev.device_kind)
    if peak_gbps is None and not smoke:
        raise SystemExit(f"no HBM peak for device kind {dev.device_kind!r}")
    roofline_toks = (peak_gbps or 0.0) * 1e9 / step_bytes * b

    metric = METRIC
    if os.environ.get("BENCH_QUANT") == "int8":
        # a different workload must not masquerade as the bf16 series
        metric = metric.replace("_bf16_", "_int8_")
    if os.environ.get("BENCH_KV") == "fp8":
        metric += "_kvfp8"
    return {
        "metric": metric,
        "value": round(toks_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": (round(toks_per_sec / roofline_toks, 3)
                        if roofline_toks else None),
        "device_kind": dev.device_kind,
        "smoke": smoke,
    }


def run_sp_prefill(ctx: int) -> dict:
    """The long-context prefill lever (xla:k8:sp-prefill): prefill
    tokens/s of the sequence-parallel chunk ladder across the mesh vs
    the single-chip dense chunk ladder, at one context length.

    Runs the REAL serving programs (ModelRunner.sp_prefill_chunk and
    ModelRunner.step over the scheduler's shared bucket ladder), so the
    number includes every cost the engine pays: chunk padding, paged
    prefix gathers, the ring rotation, and the final sampling tail.
    CPU smoke (BENCH_SMOKE=1) forces an 8-device virtual host platform
    so the mesh logic is exercised creds-free.
    """
    import os

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if smoke and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    _bench_device()
    import jax
    import numpy as _np

    from __graft_entry__ import FLAGSHIP
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.engine.scheduler import (
        build_prefill_arrays,
        prefill_bucket_cap,
    )

    n_dev = len(jax.devices())
    sp = 8 if n_dev >= 8 else max(1, n_dev)
    mdims = dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
    ) if smoke else dict(FLAGSHIP)
    mdims["max_position_embeddings"] = max(
        mdims.get("max_position_embeddings", 4096), ctx + 64)
    mcfg = ModelConfig(**mdims, attention_impl="xla")
    bs = 16
    blocks = ctx // bs + 8

    def build(sp_size):
        cfg = EngineConfig(
            model=mcfg, max_batch_size=1, max_model_len=ctx + 64,
            kv_block_size=bs, num_kv_blocks=blocks,
            dtype="float32" if smoke else "bfloat16",
            sp_size=sp_size,
            max_prefill_tokens_per_step=64 if smoke else 8192,
        )
        return cfg, ModelRunner(cfg, model_dir=None)

    prompt = [int(t) for t in _np.random.default_rng(0).integers(
        1, mcfg.vocab_size, ctx)]
    block_ids = list(range(ctx // bs + 1))
    zeros1 = _np.zeros(1, _np.float32)

    def dense_ladder(cfg, runner):
        cap = prefill_bucket_cap(cfg) or cfg.prefill_buckets[0]
        pos, outs, chunks = 0, None, 0
        t0 = time.perf_counter()
        while pos < ctx:
            end = min(pos + cap, ctx)
            arrays = build_prefill_arrays(cfg, prompt[:end], pos, block_ids)
            outs = runner.step(
                *arrays, zeros1, _np.zeros(1, _np.int32),
                _np.ones(1, _np.float32),
                seed_keys=_np.zeros((1, 2), _np.uint32),
                counters=_np.zeros(1, _np.int32),
                sample_slots=_np.zeros(1, _np.int32),
                commit=_np.asarray([end >= ctx]), want_top=False,
            )
            pos, chunks = end, chunks + 1
        _np.asarray(outs[0])  # drain
        return time.perf_counter() - t0, chunks

    def sp_ladder(cfg, runner):
        cap = runner.sp_chunk_tokens
        pos, outs, chunks = 0, None, 0
        t0 = time.perf_counter()
        while pos < ctx:
            end = min(pos + cap, ctx)
            outs = runner.sp_prefill_chunk(
                prompt[:end], pos, block_ids, commit=end >= ctx,
            )
            pos, chunks = end, chunks + 1
        _np.asarray(outs[0])  # drain
        return time.perf_counter() - t0, chunks

    # dense single-chip ladder first (compile + measure), then free it
    # before the SP runner claims HBM
    cfg_d, runner_d = build(1)
    dense_ladder(cfg_d, runner_d)  # compile pass
    dense_s, dense_chunks = dense_ladder(cfg_d, runner_d)
    del runner_d

    cfg_sp, runner_sp = build(sp)
    sp_ladder(cfg_sp, runner_sp)  # compile pass
    sp_s, sp_chunks = sp_ladder(cfg_sp, runner_sp)

    return {
        "metric": f"prefill_tokens_per_sec_1b_ctx{ctx}",
        "value": round(ctx / sp_s, 1),
        "unit": "tokens/s",
        "dense_tokens_per_s": round(ctx / dense_s, 1),
        "speedup_vs_single_chip": round(dense_s / sp_s, 3),
        "sp_axis": sp,
        "sp_chunks": sp_chunks,
        "dense_chunks": dense_chunks,
        "ctx": ctx,
        "smoke": smoke,
    }


def run_sp_kernel(ctx: int) -> dict:
    """The paged SP ring-prefill kernel lever (xla:k8:sp-kernel):
    prefill tokens/s of the sequence-parallel ladder with the Pallas
    page-walk prefix kernel (ops/pallas_sp.py — the committed prefix is
    read page-by-page from the cache via double-buffered DMA) vs the
    XLA gather path (which materializes the whole [1, W*bs] prefix per
    layer). Both runs go through the REAL SP serving program; only the
    attention route differs. CPU smoke (BENCH_SMOKE=1) runs the kernel
    in interpret mode over an 8-device virtual host platform, proving
    the route end-to-end creds-free (the number is then a smoke
    artifact, not a perf claim).
    """
    import os

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if smoke:
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.environ["DYN_PALLAS_INTERPRET"] = "1"
    _bench_device()
    import jax
    import numpy as _np

    from __graft_entry__ import FLAGSHIP
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.model_runner import ModelRunner

    n_dev = len(jax.devices())
    sp = 8 if n_dev >= 8 else max(1, n_dev)
    mdims = dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
    ) if smoke else dict(FLAGSHIP)
    mdims["max_position_embeddings"] = max(
        mdims.get("max_position_embeddings", 4096), ctx + 64)
    bs = 16
    blocks = ctx // bs + 8

    def build(impl):
        mcfg = ModelConfig(**mdims, attention_impl=impl)
        cfg = EngineConfig(
            model=mcfg, max_batch_size=1, max_model_len=ctx + 64,
            kv_block_size=bs, num_kv_blocks=blocks,
            dtype="float32" if smoke else "bfloat16",
            sp_size=sp,
            max_prefill_tokens_per_step=64 if smoke else 8192,
        )
        return cfg, ModelRunner(cfg, model_dir=None)

    prompt = [int(t) for t in _np.random.default_rng(0).integers(
        1, mdims["vocab_size"], ctx)]
    block_ids = list(range(ctx // bs + 1))

    def sp_ladder(runner):
        cap = runner.sp_chunk_tokens
        pos, outs, chunks = 0, None, 0
        t0 = time.perf_counter()
        while pos < ctx:
            end = min(pos + cap, ctx)
            outs = runner.sp_prefill_chunk(
                prompt[:end], pos, block_ids, commit=end >= ctx,
            )
            pos, chunks = end, chunks + 1
        _np.asarray(outs[0])  # drain
        return time.perf_counter() - t0, chunks

    cfg_x, runner_x = build("xla")
    sp_ladder(runner_x)  # compile pass
    gather_s, chunks = sp_ladder(runner_x)
    del runner_x

    cfg_k, runner_k = build("pallas")
    sp_ladder(runner_k)  # compile pass
    kernel_s, _ = sp_ladder(runner_k)

    return {
        "metric": f"sp_kernel_prefill_tokens_per_sec_ctx{ctx}",
        "value": round(ctx / kernel_s, 1),
        "unit": "tokens/s",
        "gather_tokens_per_s": round(ctx / gather_s, 1),
        "speedup_vs_gather": round(gather_s / kernel_s, 3),
        "sp_axis": sp,
        "chunks": chunks,
        "ctx": ctx,
        "smoke": smoke,
    }


def run_fused_epilogue(iters: int = 200) -> dict:
    """The fused sampling-epilogue lever (xla:k8:fused-epilogue):
    per-step latency of the decode tail — penalties, top-k/top-p/min-p
    sampling, count commit, finish verdict + stop-suffix hash — as the
    ONE-dispatch Pallas kernel (ops/pallas_epilogue.py) vs the unfused
    [B, V] XLA op ladder. Drives the REAL shared tail
    (model_runner._sample_and_logprobs with fused on/off), so the two
    timings cover exactly what the chained burst pays per token. CPU
    smoke runs the kernel in interpret mode (route proof, not perf).
    """
    import functools
    import os
    import types

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if smoke:
        os.environ["DYN_PALLAS_INTERPRET"] = "1"
    _bench_device()
    import jax
    import jax.numpy as jnp
    import numpy as _np

    from dynamo_tpu.engine.model_runner import _sample_and_logprobs
    from dynamo_tpu.engine.sampling import SamplingParams

    b, v, ns = (8, 2048, 8) if smoke else (64, 32768, 64)
    iters = 20 if smoke else iters
    rng = _np.random.default_rng(0)
    cfg = types.SimpleNamespace(vocab_size=v)
    logits = jnp.asarray(rng.normal(size=(b, v)), jnp.float32)
    counts = jnp.zeros((ns, v), jnp.int32)
    seen = jnp.zeros((ns, v), jnp.bool_)
    bias = jnp.zeros((ns, v), jnp.float32)
    slots = jnp.arange(b, dtype=jnp.int32)
    commit = jnp.ones((b,), jnp.bool_)
    samp = SamplingParams.zeros(b)
    samp = _dataclasses_replace_samp(samp, b)
    want_top = jnp.asarray(False)

    def tail(fused):
        @jax.jit
        def run(logits, samp, counts, seen, bias):
            return _sample_and_logprobs(
                cfg, logits, samp, counts, seen, bias, slots, commit,
                want_top, fused=fused,
            )[:3]
        return run

    results = {}
    for name, fused in (("xla", False), ("fused", True)):
        fn = tail(fused)
        jax.block_until_ready(fn(logits, samp, counts, seen, bias))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(logits, samp, counts, seen, bias)
        jax.block_until_ready(out)
        results[name] = (time.perf_counter() - t0) / iters

    return {
        "metric": "fused_epilogue_tail_us_per_step",
        "value": round(results["fused"] * 1e6, 2),
        "unit": "us",
        "xla_tail_us": round(results["xla"] * 1e6, 2),
        "speedup_vs_xla": round(results["xla"] / results["fused"], 3),
        "batch": b,
        "vocab": v,
        "iters": iters,
        "smoke": smoke,
    }


def _dataclasses_replace_samp(samp, b):
    """Non-trivial sampling params so the lever times the whole ladder
    (temperature + top-k + top-p + penalties), not constant-folded
    no-ops."""
    import dataclasses

    import jax.numpy as jnp

    return dataclasses.replace(
        samp,
        temperature=jnp.full((b,), 0.8, jnp.float32),
        top_k=jnp.full((b,), 40, jnp.int32),
        top_p=jnp.full((b,), 0.95, jnp.float32),
        repetition_penalty=jnp.full((b,), 1.1, jnp.float32),
    )


def run_ici_pull(nblocks: int = 0, chunk: int = 16) -> dict:
    """The unified-transfer-plane payload lever (xla:k8:ici-pull): KV
    block throughput of the ici (device-to-device collective) payload
    path vs the tcp fallback, through the REAL plane seams — the tcp
    side pays the full framing bill (executor byte-pack, socket frames,
    decode, host→device install), the ici side enters the collective
    plane with device arrays and the host touches only headers.

    On hardware the collective rides the actual interconnect; CPU smoke
    (BENCH_SMOKE=1) runs the loopback plane (transfer/ici.py), so the
    framing, one-in-flight pairing, and seq cross-check are exercised
    creds-free — there the RATIO is the logic check, not a perf claim.
    """
    import asyncio
    import os

    _bench_device()
    import jax.numpy as jnp
    import numpy as _np

    from dynamo_tpu.transfer import (
        IciBackend,
        LoopbackIciTransfer,
        TcpBackend,
        pack_frame,
        read_header,
    )

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if not nblocks:
        nblocks = 128 if smoke else 2048
    bs, heads, hd = (16, 2, 32) if smoke else (16, 8, 128)
    frames = [
        (jnp.asarray(_np.random.default_rng(i).standard_normal(
            (1, chunk, bs, heads, hd), dtype=_np.float32)),) * 2
        for i in range(nblocks // chunk)
    ]
    frame_bytes = 2 * int(frames[0][0].nbytes)

    async def tcp_pass() -> float:
        done = asyncio.Event()

        async def handle(reader, writer):
            while True:
                header = await read_header(reader, "bench")
                if header is None or header.get("type") == "end":
                    break
                k, v = await TcpBackend.recv_blocks(reader, header)
                # the install cost a real pull pays before scatter
                jnp.asarray(k).block_until_ready()
                jnp.asarray(v).block_until_ready()
            done.set()
            writer.close()

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        t0 = time.perf_counter()
        for i, (k, v) in enumerate(frames):
            await TcpBackend.send_blocks(
                writer, {"type": "blocks", "offset": i * chunk}, k, v)
        pack_frame(writer, {"type": "end"})
        await writer.drain()
        await done.wait()
        wall = time.perf_counter() - t0
        writer.close()
        server.close()
        await server.wait_closed()
        return wall

    async def ici_pass() -> float:
        lb = LoopbackIciTransfer(buckets=(chunk,))
        tx, rx = IciBackend(lb), IciBackend(lb)

        async def pull():
            for _ in frames:
                k, v, _seq = await rx.recv(chunk)
                k.block_until_ready()
                v.block_until_ready()

        t0 = time.perf_counter()
        task = asyncio.ensure_future(pull())
        for k, v in frames:
            await tx.send(k, v, tx.next_seq(), chunk)
        await task
        return time.perf_counter() - t0

    loop = asyncio.new_event_loop()
    try:
        tcp_s = loop.run_until_complete(tcp_pass())  # warm executor/socket
        tcp_s = min(tcp_s, loop.run_until_complete(tcp_pass()))
        ici_s = loop.run_until_complete(ici_pass())
        ici_s = min(ici_s, loop.run_until_complete(ici_pass()))
    finally:
        loop.close()
    return {
        "metric": "kv_pull_blocks_per_sec_ici",
        "value": round(nblocks / ici_s, 1),
        "unit": "blocks/s",
        "tcp_blocks_per_s": round(nblocks / tcp_s, 1),
        "speedup_vs_tcp": round(tcp_s / ici_s, 3),
        "nblocks": nblocks,
        "chunk_blocks": chunk,
        "frame_bytes": frame_bytes,
        "smoke": smoke,
    }


def _run_child(label: str, call: str, timeout_s: float):
    """One attempt — ``bench.<call>`` — in a child with a hard timeout.

    A Mosaic compile can (rarely) hang rather than fail; an in-process
    attempt would then wedge the whole bench, and a parent that had
    touched jax would hold the chip its children need. The child prints
    its result JSON on the last line; timeout/crash → None and the
    caller moves on. A child that found no TPU backend ends the bench.
    """
    code = ("import json, bench; "
            f"print('BENCH_RESULT ' + json.dumps(bench.{call}))")
    try:
        proc = subprocess.run(
            [_sys.executable, "-c", code], capture_output=True, text=True,
            timeout=timeout_s, cwd=_HERE,
        )
    except subprocess.TimeoutExpired:
        print(f"bench[{label}] timed out after {timeout_s:.0f}s", flush=True)
        return None
    if proc.returncode == NO_CHIP_RC:
        _sys.stderr.write(proc.stderr[-2000:])
        raise SystemExit(NO_CHIP_RC)
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("BENCH_RESULT "):
            result = json.loads(line[len("BENCH_RESULT "):])
            # one line per attempt: the log keeps the whole lever table
            # even though only the best goes on the final line
            print(f"attempt[{label}]: {json.dumps(result)}", flush=True)
            return result
    _sys.stderr.write(proc.stderr[-4000:])
    print(f"bench[{label}] failed (rc={proc.returncode})", flush=True)
    return None


def _run_impl(label: str, impl: str, timeout_s: float, burst: int = 1,
              **levers):
    kw = "".join(f", {k}={v!r}" for k, v in levers.items())
    return _run_child(label, f"run_once({impl!r}, {burst}{kw})", timeout_s)


def main() -> None:
    total_budget = float(_os.environ.get("BENCH_TOTAL_BUDGET_S", "1380"))
    xla_timeout = min(float(_os.environ.get("BENCH_TIMEOUT_S", "600")),
                      total_budget)
    single_step_only = bool(_os.environ.get("BENCH_SINGLE_STEP_ONLY"))
    smoke = bool(_os.environ.get("BENCH_SMOKE"))
    t0 = time.monotonic()

    def remaining() -> float:
        return total_budget - (time.monotonic() - t0)

    def better(cand, best):
        return cand if cand is not None and (
            best is None or cand["value"] > best["value"]) else best

    best = _run_impl("xla:k1", "xla", xla_timeout)

    # the engine's fused multi-step decode (multi_step_decode=K): same
    # program shape, K dispatches' overhead amortized into one; K=16
    # checks for a remaining tail
    if remaining() > 360 and not single_step_only:
        burst = _run_impl("xla:k8", "xla", min(300.0, remaining() - 240),
                          burst=8)
        best = better(burst, best)
        if burst is not None and remaining() > 460:
            best = better(_run_impl(
                "xla:k16", "xla", min(300.0, remaining() - 300), burst=16,
            ), best)

    # the engine's dispatch-ahead decode pipeline
    # (decode_pipeline_depth=2): the same fused K=8 burst, but every
    # burst's tokens are synced to the host — as serving must — with the
    # sync overlapped behind the next burst's device time. This is the
    # engine-shaped number (plain k8 never syncs, an upper bound the
    # scheduler cannot reach).
    if remaining() > 360 and not single_step_only:
        best = better(_run_impl(
            "xla:k8:pipelined", "xla", min(300.0, remaining() - 240),
            burst=8, pipeline=True,
        ), best)

    # the persistent decode loop (device-resident finish + chained
    # dispatch + async row drain): the serving scheduler's shape under
    # --device-finish. Strictly more overlap than :pipelined — dispatch
    # never waits for ANY burst's host sync to complete.
    if remaining() > 360 and not single_step_only:
        best = better(_run_impl(
            "xla:k8:persistent", "xla", min(300.0, remaining() - 240),
            burst=8, persistent=True,
        ), best)

    # the unrestricted-chain levers (ISSUE 13): the chained propose-
    # verify round (spec) and the device-guided-table chain (guided).
    # Spec measures verified positions/s — a full-acceptance ceiling —
    # so it is logged only; guided IS a decode tokens/s measurement and
    # may win the headline.
    if remaining() > 360 and not single_step_only:
        _run_impl("xla:k8:persistent-spec", "xla",
                  min(300.0, remaining() - 240), burst=8, persistent=True,
                  spec=True)
    if remaining() > 360 and not single_step_only:
        best = better(_run_impl(
            "xla:k8:persistent-guided", "xla",
            min(300.0, remaining() - 240), burst=8, persistent=True,
            guided=True,
        ), best)

    # different metric families below: logged per attempt, never the
    # decode headline.
    # long-context sequence-parallel prefill (docs/long_context.md), one
    # child per context so a hang at 128k cannot eat the 32k number
    for sp_ctx in ((512, 1024) if smoke else (32768, 131072)):
        if remaining() <= 300 or single_step_only:
            break
        _run_child(f"xla:k8:sp-prefill:ctx{sp_ctx}",
                   f"run_sp_prefill({sp_ctx})",
                   min(420.0, remaining() - 180))
    # the paged SP ring-prefill KERNEL vs the XLA gather route
    if remaining() > 300 and not single_step_only:
        _run_child("xla:k8:sp-kernel",
                   f"run_sp_kernel({512 if smoke else 32768})",
                   min(420.0, remaining() - 180))
    # the decode tail as one Pallas dispatch vs the unfused XLA ladder
    if remaining() > 150 and not single_step_only:
        _run_child("xla:k8:fused-epilogue", "run_fused_epilogue()",
                   min(240.0, remaining() - 90))
    # KV block throughput, ici device-to-device vs tcp framing
    # (docs/transfer_plane.md)
    if remaining() > 150 and not single_step_only:
        _run_child("xla:k8:ici-pull", "run_ici_pull()",
                   min(240.0, remaining() - 90))

    if remaining() > 240 and not _os.environ.get("BENCH_XLA_ONLY"):
        pallas = _run_impl("pallas:k8", "pallas",
                           max(min(remaining() - 120, 480), 60), burst=8)
        if pallas is None:
            # if the scanned burst wrapper is what failed, the
            # single-step Pallas attempt is still worth having
            pallas = _run_impl("pallas:k1", "pallas", max(remaining(), 60))
        best = better(pallas, best)

    if best is None:
        raise SystemExit("bench: every attempt failed or timed out; no result")
    print(json.dumps(best))


if __name__ == "__main__":
    main()
