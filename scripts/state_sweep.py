#!/usr/bin/env python3
"""The mixer's state kernel alone, on the chip: ``ops/ssm.ssm_decode_step``
over a step's mixer layers at the three served shapes (Granite 4.0-H's 128
heads of 64 x 128 in one group, Falcon-H1's 32 of 128 x 256 in two,
lightning attention's 32 of 128 x 128, a group a head) with 64, 34 and 24
of the slots live, as GB/s of state moved (a live row's state read once
and written once) and as a share of 819 GB/s.

    python scripts/state_sweep.py [--repo DIR] [--shapes granite,falcon,sala]
        [--live 64,34,24] [--forms served,rows,rows_mxu,copy]
        [--out chiprun_out/state_sweep.json]

Forms (PERF.md section 6, PR 53; section 7 "Left by PR 48" (1) before it):

- ``served``: the kernel of ``--repo`` as its trunks call it. Since PR 53
  that is form 2, ``P`` on the lanes: records ``[L, slots, H / k, N,
  k P]``, the broadcasts of ``B`` and ``C`` once a group, the read-out a
  sum over sublanes. A checkout from before it (no
  ``ops/ssm.state_to_record``) is timed on its own records ``[L, slots,
  H, P, N]``, which is the comparison with a parent commit.
- ``rows``: form 1, the records as they lay before PR 53 (``N`` on the
  lanes) and the body PR 34 wrote: a loop over a block's heads, a head a
  lane broadcast of the decay and of Δ·x, a lane reduce of the read-out
  and a one-lane store of ``y`` a row of eight sublanes.
- ``rows_mxu``: form 1 with the read-out moved to the matrix unit
  (``C · h'ᵀ`` over a group's rows at ``precision=HIGHEST``); the
  update's broadcasts as in ``rows``.
- ``copy``: a block read into VMEM and written back, nothing else: what
  the copies allow at that block.

``rows``, ``rows_mxu`` and ``copy`` live here and nowhere else: nothing
serves them. Every form but ``copy`` is held to ``ssm_decode_update`` on
the live rows before it is timed (``err``: the largest difference of
``y`` and of the new state). It measures the chip and nothing else: on
any other backend it says so and exits 1.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--shapes", default="granite,falcon,sala")
ap.add_argument("--live", default="64,34,24")
ap.add_argument("--forms", default="served,rows,rows_mxu,copy")
ap.add_argument("--out", default=None)
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.repo))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from dynamo_tpu.ops import ssm  # noqa: E402
from dynamo_tpu.ops.live_rows import live_row_list  # noqa: E402

LANES_SERVED = hasattr(ssm, "state_to_record")    # form 2 (since PR 53)
HBM_BYTES_PER_S = 819e9          # TPU v5e (benchmark/harness/peaks.py)
BLOCK_BYTES = 2 << 20            # ops/ssm._STATE_BLOCK_BYTES
F32 = jnp.float32
# name: (mixer layers, slots, H, P, N, G) as the cell serves it
SHAPES = {
    "granite": (9, 64, 128, 64, 128, 1),     # granite-batch
    "falcon": (6, 64, 32, 128, 256, 2),      # falcon-h1-chat
    "sala": (9, 24, 32, 128, 128, 32),       # sala-longdoc (lightning)
}


# ---------------- form 1 and the copies: kept here for the comparison

def _head_block(heads, per_group, head_bytes):
    """Heads a block of form 1 (``ops/ssm._head_block`` before PR 53)."""
    fit = max(1, BLOCK_BYTES // head_bytes)
    return max(k for k in range(1, min(heads, fit) + 1)
               if per_group % k == 0
               or (k % per_group == 0 and heads % k == 0))


def _rows_kernel(layer_ref, rows_ref, xdt_ref, decay_ref, bc_ref, h_ref,
                 y_ref, o_ref, *scratch, heads_per_group, mxu):
    """xdt [P, hb], decay [1, hb], bc [gb, 2, N], h / o [hb, P, N]; y
    [P, hb], or [1, hb P] off the matrix unit."""
    del layer_ref, rows_ref
    hb, p, n = h_ref.shape
    kept = scratch[0] if scratch else o_ref      # the block's h' in float32
    for j in range(hb):
        jg = j // heads_per_group
        h = (h_ref[j].astype(F32) * decay_ref[:, j:j + 1]
             + xdt_ref[:, j:j + 1] * bc_ref[jg, 0:1, :])
        o_ref[j] = h.astype(o_ref.dtype)
        if not mxu:
            y_ref[:, j:j + 1] = jnp.sum(h * bc_ref[jg, 1:2, :], axis=-1,
                                        keepdims=True)
        elif scratch:
            kept[j] = h
    if mxu:
        per = hb // bc_ref.shape[0]
        for jg in range(bc_ref.shape[0]):
            rows = kept[jg * per:(jg + 1) * per].reshape(per * p, n)
            y = jax.lax.dot_general(
                jnp.broadcast_to(bc_ref[jg, 1:2, :], (8, n)), rows,
                (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=F32)
            y_ref[:, jg * per * p:(jg + 1) * per * p] = y[0:1]


def _rows_step(x, dt, a, bm, cm, d, records, layer, live_rows, *, mxu=False,
               copy=False):
    """Form 1 on records [L, slots, H, P, N]."""
    b, heads, p = x.shape
    g, n_state = bm.shape[-2:]
    per_group = heads // g
    hb = _head_block(heads, per_group, p * n_state * records.dtype.itemsize)
    nb, gb = heads // hb, max(1, hb // per_group)
    live, rows, n = live_rows
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def by_row(i, j, layer_ref, rows_ref):
        return rows_ref[i], j, 0, 0

    def by_group(i, j, layer_ref, rows_ref):
        return rows_ref[i], j * hb // per_group // gb, 0, 0

    def state(i, j, layer_ref, rows_ref):
        return layer_ref[0], rows_ref[i], j, 0, 0

    spec = pl.BlockSpec((None, None, hb, p, n_state), state)
    call = functools.partial(
        pl.pallas_call,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=jax.default_backend() != "tpu", name="ssm_decode_step")
    if copy:
        def kernel(layer_ref, rows_ref, h_ref, o_ref):
            o_ref[...] = h_ref[...]

        records = call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n, nb), in_specs=[spec],
                out_specs=spec),
            out_shape=jax.ShapeDtypeStruct(records.shape, records.dtype),
            input_output_aliases={2: 0})(layer, rows, records)
        return jnp.zeros((b, heads, p), F32), records
    x = x.astype(F32)
    xdt = (dt[:, :, None] * x).reshape(b, nb, hb, p).transpose(0, 1, 3, 2)
    decay = jnp.exp(dt * a).reshape(b, nb, 1, hb)
    bc = jnp.stack([bm.astype(F32), cm.astype(F32)], axis=2)
    y_block = (1, hb * p) if mxu else (p, hb)
    y, records = call(
        functools.partial(_rows_kernel, heads_per_group=per_group, mxu=mxu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, nb),
            in_specs=[pl.BlockSpec((None, None, p, hb), by_row),
                      pl.BlockSpec((None, None, 1, hb), by_row),
                      pl.BlockSpec((None, gb, 2, n_state), by_group), spec],
            out_specs=[pl.BlockSpec((None, None) + y_block, by_row), spec],
            scratch_shapes=[pltpu.VMEM((hb, p, n_state), F32)]
            if mxu and records.dtype != F32 else []),
        out_shape=[jax.ShapeDtypeStruct((b, nb) + y_block, F32),
                   jax.ShapeDtypeStruct(records.shape, records.dtype)],
        input_output_aliases={5: 1})(layer, rows, xdt, decay, bc, records)
    if not mxu:
        y = y.transpose(0, 1, 3, 2)
    y = y.reshape(b, heads, p) + d.astype(F32)[None, :, None] * x
    return jnp.where(live[:, None, None], y, 0.0), records


FORMS = {
    # name: (step, whether its records lie with P on the lanes)
    "served": (ssm.ssm_decode_step, LANES_SERVED),
    "rows": (_rows_step, False),
    "rows_mxu": (functools.partial(_rows_step, mxu=True), False),
    "copy": (functools.partial(_rows_step, copy=True), False),
}


# ---------------- the measurement

def _operands(name, n_live):
    """A step's operands and float32 records [L, slots, H, P, N], made on
    the device; ``n_live`` of the slots hold a token."""
    layers, slots, h, p, n, g = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    live = np.zeros(slots, bool)
    live[np.random.default_rng(7).choice(slots, n_live, replace=False)] = True
    live = jnp.asarray(live)
    dt = jnp.exp(jax.random.uniform(keys[1], (slots, h), F32, np.log(1e-3),
                                    np.log(0.5))) * live[:, None]
    ops = (jax.random.normal(keys[0], (slots, h, p), F32), dt,
           -jax.random.uniform(keys[2], (h,), F32, 1.0, 16.0),
           jax.random.normal(keys[3], (slots, g, n), F32),
           jax.random.normal(keys[4], (slots, g, n), F32),
           jax.random.normal(keys[5], (h,), F32))
    return ops, jax.random.normal(keys[6], (layers, slots, h, p, n), F32), live


def _time(step, ops, records):
    """Median seconds a call of ``step`` (every mixer layer once), the
    records handed on from call to call as a decode program hands them."""
    _, records = step(ops, records)
    jax.block_until_ready(records)
    t0 = time.perf_counter()
    _, records = step(ops, records)
    jax.block_until_ready(records)
    once = time.perf_counter() - t0
    reps = max(3, min(100, int(0.3 / max(once, 1e-6))))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            _, records = step(ops, records)
        jax.block_until_ready(records)
        times.append((time.perf_counter() - t0) / reps)
    return statistics.median(times)


def case(name, form, n_live):
    layers, slots, h, p, n, g = SHAPES[name]
    fn, lanes = FORMS[form]
    ops, records, live = _operands(name, n_live)
    lr = live_row_list(live)
    mask = np.asarray(live)
    want_y, want_h = jax.jit(ssm.ssm_decode_update)(*ops, records[1])
    if lanes:       # as the trunks keep them
        records = jax.jit(lambda r: ssm.state_to_record(r, h // g),
                          donate_argnums=0)(records)
    y, new = jax.jit(lambda o, r: fn(*o, r, jnp.int32(1), lr))(ops, records)
    got_h = ssm.record_to_state(new[1], p) if lanes else new[1]
    err = 0.0 if form == "copy" else max(
        float(jnp.abs(y - want_y)[mask].max()),
        float(jnp.abs(got_h - want_h)[mask].max()))
    del new, got_h, want_h

    def step(o, r):
        total = 0.0
        for li in range(layers):
            y, r = fn(*o, r, jnp.int32(li), lr)
            total = total + y.sum()
        return total, r

    seconds = _time(jax.jit(step, donate_argnums=(1,)), ops, records)
    nbytes = 2 * layers * int(mask.sum()) * h * p * n * 4
    return seconds, nbytes, err


def main():
    if jax.default_backend() != "tpu":
        sys.exit(f"state_sweep.py times the kernel on a TPU; the backend "
                 f"here is {jax.default_backend()!r}: nothing measured")
    table = {"repo": os.path.abspath(args.repo),
             "device": jax.devices()[0].device_kind,
             "served_form": "lanes" if LANES_SERVED else "rows", "lines": []}
    for name in args.shapes.split(","):
        layers, slots = SHAPES[name][:2]
        for form in args.forms.split(","):
            for n_live in map(int, args.live.split(",")):
                if n_live > slots:
                    continue
                try:
                    seconds, nbytes, err = case(name, form, n_live)
                except Exception as e:  # a body Mosaic refuses: say so
                    print(f"{name:8s} {form:9s} live {n_live:3d} refused: "
                          f"{str(e)[:300]!r}", flush=True)
                    continue
                line = {"shape": name, "form": form, "live": n_live,
                        "layer_us": 1e6 * seconds / layers,
                        "row_us": 1e6 * seconds / layers / n_live,
                        "gb_per_s": nbytes / seconds / 1e9,
                        "hbm_share_pct": 100 * nbytes / seconds
                        / HBM_BYTES_PER_S, "err": err}
                table["lines"].append(line)
                print(f"{name:8s} {form:9s} live {n_live:3d}  layer "
                      f"{line['layer_us']:8.1f} us  row {line['row_us']:6.2f}"
                      f" us  {line['gb_per_s']:6.1f} GB/s  "
                      f"{line['hbm_share_pct']:5.1f} % of 819 GB/s  "
                      f"err {err:.1e}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))


if __name__ == "__main__":
    main()
