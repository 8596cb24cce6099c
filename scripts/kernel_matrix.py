#!/usr/bin/env python3
"""Every Pallas kernel specialization x {lowers for TPU, compiles under
Mosaic, agrees with the XLA reference} — the table in PERF.md.

On any backend each case is lowered with ``lowering_platforms=("tpu",)``
(tests/test_bringup.py runs the default-route cases this way on
the CPU). On a TPU backend each case is also compiled and run, and the
default-route cases — decode and flash-prefill at the two chip_smoke.py
shapes, through the same ``ops.attention.attention`` dispatch the models
call — are compared with ``attention_impl="xla"`` on random inputs.
Lowering is not Mosaic compilation: VMEM limits and tiling are only
checked by the compile.

    python scripts/kernel_matrix.py [--out chiprun_out/kernel_matrix.json]

Exit code 1 when a default-route case fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.ops.attention import attention, lane_pad  # noqa: E402

PAGE, N_BLOCKS, LAYERS = 16, 256, 2

# (kv heads, q heads, true head dim) as one device sees them
SHAPES = {
    "llama-3.2-1b": (8, 32, 64),        # chip_smoke leg A, one chip
    "llama-3.1-8b/tp4": (2, 8, 128),    # leg B, one tp shard of four
    "toy": (2, 4, 128),
    "sdar-30b-a3b": (4, 32, 128),       # sdar-reasoning's block pass
}


def _paged_inputs(shape, b, s, w, kv_dtype=jnp.bfloat16, seed=0):
    """Random q + cache + block tables; row i's context ends at a
    different page so live/partial/dead pages are all exercised."""
    kvh, h, d = SHAPES[shape]
    rng = np.random.default_rng(seed)
    dk = lane_pad(d)
    cache = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((LAYERS, N_BLOCKS, PAGE, kvh, dk), np.float32)
        * (np.arange(dk) < d), kv_dtype)
    q = jnp.asarray(rng.standard_normal((b, s, h, d), np.float32),
                    jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(N_BLOCKS)[: b * w].reshape(b, w)
                     if b * w <= N_BLOCKS
                     else rng.integers(0, N_BLOCKS, (b, w)), jnp.int32)
    ctx = np.minimum(w * PAGE, s + 5 + (np.arange(b) * 37) % (w * PAGE))
    pos = jnp.asarray((ctx - s)[:, None] + np.arange(s)[None, :], jnp.int32)
    return (q, cache(), cache(), bt, pos, jnp.asarray(ctx, jnp.int32))


def _attention_case(shape, b, s, w, kv_dtype=jnp.bfloat16, **kw):
    args = _paged_inputs(shape, b, s, w, kv_dtype)

    def run(impl):
        return jax.jit(lambda *a: attention(
            *a, impl=impl, layer_idx=jnp.int32(1), **kw))

    return run("pallas"), args, run("xla")


def _mla_case(kv_dtype=jnp.bfloat16):
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    b, w, h, r, rd = 2, 4, 4, 128, 128
    c = jnp.zeros((LAYERS, N_BLOCKS, 1, PAGE, r), kv_dtype)
    kr = jnp.zeros((LAYERS, N_BLOCKS, 1, PAGE, rd), kv_dtype)
    args = (jnp.ones((b, 1, h, r), jnp.bfloat16),
            jnp.ones((b, 1, h, rd), jnp.bfloat16), c, kr,
            jnp.asarray(np.arange(b * w).reshape(b, w), jnp.int32),
            jnp.asarray([17, 33], jnp.int32), jnp.int32(1))
    return jax.jit(mla_paged_decode_attention), args, None


def _sp_prefix_case():
    from dynamo_tpu.ops.pallas_sp import paged_prefix_attention_partials

    q, k, v, bt, _, _ = _paged_inputs("toy", 1, 128, 4)
    args = (q, k, v, bt, jnp.int32(40), jnp.int32(1))
    return jax.jit(paged_prefix_attention_partials), args, None


FP8 = jnp.float8_e4m3fn
SINKS = dict(sinks=jnp.ones((4,), jnp.float32), sliding_window=jnp.int32(16))
SOFTCAP = dict(softcap=50.0, sliding_window=jnp.int32(16))

# name -> (on the default route of the smoke shapes?, builder)
CASES = {}
for _shape in ("llama-3.2-1b", "llama-3.1-8b/tp4"):
    for _w in (8, 128):
        CASES[f"decode {_shape} b8 w{_w}"] = (
            True, lambda s=_shape, w=_w: _attention_case(s, 8, 1, w))
    for _b, _s in ((4, 64), (2, 512), (1, 2048)):
        CASES[f"flash {_shape} b{_b} s{_s}"] = (
            True, lambda s=_shape, b=_b, n=_s: _attention_case(s, b, n, 128))
CASES.update({
    "decode softcap+window": (
        False, lambda: _attention_case("toy", 2, 1, 4, **SOFTCAP)),
    "decode sinks": (False, lambda: _attention_case("toy", 2, 1, 4, **SINKS)),
    "decode fp8-kv": (False, lambda: _attention_case("toy", 2, 1, 4, FP8)),
    "decode fp8-kv llama-3.2-1b": (
        False, lambda: _attention_case("llama-3.2-1b", 8, 1, 128, FP8)),
    "flash softcap+window": (
        False, lambda: _attention_case("toy", 1, 128, 8, **SOFTCAP)),
    "flash sinks": (False, lambda: _attention_case("toy", 1, 128, 8, **SINKS)),
    "flash fp8-kv": (False, lambda: _attention_case("toy", 1, 128, 8, FP8)),
    "flash fp8-kv llama-3.2-1b": (
        False, lambda: _attention_case("llama-3.2-1b", 1, 2048, 128, FP8)),
    "verify s4": (False, lambda: _attention_case("toy", 2, 4, 4)),
    "verify s4 llama-3.2-1b": (
        False, lambda: _attention_case("llama-3.2-1b", 8, 4, 128)),
    "verify s8 block 4 sdar-30b-a3b": (
        False, lambda: _attention_case("sdar-30b-a3b", 8, 8, 128, block_len=4)),
    "verify softcap": (
        False, lambda: _attention_case("toy", 2, 4, 4, softcap=50.0)),
    "verify sinks": (False, lambda: _attention_case("toy", 2, 4, 4, **SINKS)),
    "verify fp8-kv": (False, lambda: _attention_case("toy", 2, 4, 4, FP8)),
    "verify fp8-kv llama-3.2-1b": (
        False, lambda: _attention_case("llama-3.2-1b", 8, 4, 128, FP8)),
    "mla decode": (False, _mla_case),
    "mla decode fp8-kv": (False, lambda: _mla_case(FP8)),
    "sp paged-prefix partials": (False, _sp_prefix_case),
})


def _short(e: BaseException) -> str:
    lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
    return f"{type(e).__name__}: {' | '.join(lines[:4])}"[:600]


def run_case(name: str, on_tpu: bool) -> dict:
    default_route, build = CASES[name]
    row = {"kernel": name, "default_route": default_route}
    fn, args, ref = build()
    try:
        traced = fn.trace(*args)
        traced.lower(lowering_platforms=("tpu",))
        row["lowers"] = True
    except Exception as e:  # noqa: BLE001 — the error IS the table entry
        row["lowers"] = _short(e)
    if not on_tpu:
        return row
    try:
        out = jax.block_until_ready(fn(*args))
        row["compiles"] = True
    except Exception as e:  # noqa: BLE001
        row["compiles"] = _short(e)
        return row
    if ref is not None:
        want = np.asarray(ref(*args), np.float32)
        got = np.asarray(out, np.float32)
        row["max_abs_err_vs_xla"] = float(np.max(np.abs(got - want)))
        row["finite"] = bool(np.isfinite(got).all())
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--only", default="", help="substring filter")
    a = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    rows = []
    for name in CASES:
        if a.only and a.only not in name:
            continue
        row = run_case(name, on_tpu)
        rows.append(row)
        print(json.dumps(row), flush=True)
    report = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__, "rows": rows,
    }
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(report, f, indent=1)
    # bf16 attention over <= 2048 keys: outputs are O(1) averages of
    # O(1) values; 0.05 is ~6 bf16 ulps at 1.0
    bad = [
        r["kernel"] for r in rows if r["default_route"] and (
            r["lowers"] is not True
            or (on_tpu and (r.get("compiles") is not True
                            or not r.get("finite")
                            or r["max_abs_err_vs_xla"] > 0.05)))
    ]
    print(json.dumps({"device": report["device"], "default_route_failed": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
