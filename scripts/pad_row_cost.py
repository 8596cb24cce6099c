#!/usr/bin/env python3
"""What a pad row of the decode batch costs the decode kernels, on the
chip: a step's worth of calls (one a layer) of ``paged_decode_attention``
and ``mla_paged_decode_attention`` alone, at the shapes the benchmark's
cells serve, with a few rows live and the rest as the scheduler fills
them (``ctx_lens = 1``, no slot).

    python scripts/pad_row_cost.py [--repo DIR] [--out chiprun_out/pad_row_cost.json]
    python scripts/pad_row_cost.py --tail [--tiles 8,16,32] [--repo DIR] [--out ...]
    JAX_PLATFORMS=cpu python scripts/pad_row_cost.py --tail --hash [--repo DIR]

``--repo`` is the checkout whose ``dynamo_tpu`` is timed (a parent commit
unpacked beside this one); a checkout whose kernels take no ``live_rows``
walks every row, which is the comparison. Each case prints the median
wall time of a step's calls over ``--reps`` dispatches and per call; the
last line is the whole table as JSON. It measures the chip and nothing
else: on any other backend it says so and exits 1 (the interpreter's
answers are tests/test_pallas_decode.py's business).

``--tail`` times the other thing a pad row costs instead: the sampling
tail of a decode step alone (``model_runner._sample_and_logprobs``:
penalty rows, the filter's search, the draw, the log-probability, the
counts' update) at the cells' ``[rows, vocabulary]`` with a quarter, half,
three quarters and all of the rows live, as the chat mix asks
(temperature 0.7, top-p 0.9; a pad row as the host leaves it), once over
every row (``live`` left out: what a checkout from before the mask does)
and once a tile size of ``--tiles``, which stands in for
``sampling.ROW_TILE`` in that trace (``--always``: by the list of live
rows however full the batch is, where the program walks all rows as they
lie in the batch's last tile: what that threshold was fixed from). It also says what
the live rows' tokens and log-probabilities were compared with the same
rows' in a batch with every row live (``across_loads``: a row's result
must not depend on how full the batch is) and with the pass over every
row at once (``against_one_pass``: another program, whose sums along the
vocabulary are tiled otherwise): whether every token is the same, and
the largest difference of a log-probability in units in the last place.
This is the timing ``ROW_TILE`` was fixed from (PERF.md §5 "Since
PR 47"). Since PR 61 a tile of bfloat16 rows with nothing added finds its
cutoffs by the short search (``sampling.filter_logits``): every time is
taken twice, as the mix sends the rows (``ms``, ``all rows``) and with
one entry of bias a row, which makes every row a general float32 and
the same program run the full search (``ms_full``, ``all rows full``);
``short_equals_full`` says whether ``filter_logits`` kept the same set
both ways on those rows; the block family's pass over ``[128, 151936]``
(``sample_block_positions``, the short search known to its trace) is
timed beside the same values handed over as float32, which its trace
takes as a general row; and ``--search-bits 2,4`` repeats all of it with
``sampling._SEARCH_BITS`` set to each. ``--tail --hash`` needs no chip
and times nothing: it prints the sha256 of the tail's lowered text at
every cell's ``[rows, vocabulary]``, the mask handed where the checkout
takes one, to tell under two checkouts which configurations' tails a
change reaches.
"""

from __future__ import annotations

import argparse
import dataclasses
import inspect
import json
import os
import statistics
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--out", default=None)
ap.add_argument("--reps", type=int, default=60)
ap.add_argument("--tail", action="store_true",
                help="time the sampling tail, not the decode kernels")
ap.add_argument("--tiles", default="8,16,32",
                help="--tail: the tile sizes to stand in for ROW_TILE")
ap.add_argument("--always", action="store_true",
                help="--tail: walk the list of live rows however full the "
                "batch is, not only while the program would "
                "(sampling.walks_live_rows)")
ap.add_argument("--search-bits", default="",
                help="--tail: values to stand in for sampling._SEARCH_BITS, "
                "each timed in turn (default: the one the checkout has)")
ap.add_argument("--hash", action="store_true",
                help="--tail: print the lowered tail's sha256 a shape and "
                "stop (no chip)")
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.repo))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.ops import pallas_decode  # noqa: E402

PAGE = 16
# name: (layers, rows, q heads, kv heads, lanes, table width, live rows)
GQA_CASES = {
    # benchmark/configs/phi3-mini-4k.json: 32 x 32 heads of 96 in 128 lanes
    "phi3-chat": (32, 32, 32, 32, 128, 32, 5),
    "phi3-full": (32, 32, 32, 32, 128, 32, 32),
    "phi3-idle": (32, 32, 32, 32, 128, 32, 0),
    # mistral-7b-v0.3-tp4.json, one shard of four: 8 heads over 2 kv heads
    "mistral-tp4-shard": (32, 64, 8, 2, 128, 32, 9),
    "mistral-tp4-shard-full": (32, 64, 8, 2, 128, 32, 64),
    # falcon-h1-34b.json: six layers, 20 heads over 4 kv heads
    "falcon-h1-chat": (6, 64, 20, 4, 128, 64, 26),
}
# moonlight-16b-a3b.json: nine layers, 16 heads, latent 512, rope key 128
MLA_CASES = {
    "moonlight-chat": (9, 64, 16, 512, 128, 256, 17),
    "moonlight-full": (9, 64, 16, 512, 128, 256, 64),
}
CONTEXT = (250, 350)     # a live row's context: the chat mix's ~300


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.bfloat16)


def _rows(rng, b, width, n_live, n_blocks):
    """(block tables, context lens, live mask): live rows spread over the
    batch as slots are, contexts of ~300; a pad row as Scheduler._decode
    fills it (context 1, table of zeros)."""
    live = np.zeros(b, bool)
    live[rng.permutation(b)[:n_live]] = True
    ctx = np.where(live, rng.integers(*CONTEXT, size=b), 1).astype(np.int32)
    bt = np.where(live[:, None], rng.integers(1, n_blocks, (b, width)), 0)
    return (jnp.asarray(bt, jnp.int32), jnp.asarray(ctx), jnp.asarray(live))


def _time(step, operands, reps):
    out = step(*operands)
    jax.block_until_ready(out)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = step(*operands)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    return statistics.median(times)


def _takes_live_rows(fn):
    return "live_rows" in inspect.signature(fn).parameters


def gqa_case(name, rng, reps):
    layers, b, h, kvh, d, width, n_live = GQA_CASES[name]
    n_blocks = 640
    shape = (layers, n_blocks, PAGE, kvh, d)
    k, v, q = _normal(0, shape), _normal(1, shape), _normal(2, (b, 1, h, d))
    bt, ctx, live = _rows(rng, b, width, n_live, n_blocks)
    fn = pallas_decode.paged_decode_attention

    @jax.jit
    def step(q, k, v, bt, ctx):
        kw = {}
        if _takes_live_rows(fn):    # once a step, as a trunk makes it
            from dynamo_tpu.ops.live_rows import live_row_list
            kw["live_rows"] = live_row_list(live)

        def layer(q, li):
            return fn(q, k, v, bt, ctx, layer_idx=li, **kw), None

        return jax.lax.scan(layer, q, jnp.arange(layers, dtype=jnp.int32))[0]

    return layers, b, n_live, _time(step, (q, k, v, bt, ctx), reps)


def mla_case(name, rng, reps):
    layers, b, h, r, rd, width, n_live = MLA_CASES[name]
    n_blocks = 3072
    c = _normal(0, (layers, n_blocks, 1, PAGE, r))
    kr = _normal(1, (layers, n_blocks, 1, PAGE, rd))
    ql, qr = _normal(2, (b, 1, h, r)), _normal(3, (b, 1, h, rd))
    bt, ctx, live = _rows(rng, b, width, n_live, n_blocks)
    fn = pallas_decode.mla_paged_decode_attention

    @jax.jit
    def step(ql, qr, c, kr, bt, ctx):
        kw = {}
        if _takes_live_rows(fn):
            from dynamo_tpu.ops.live_rows import live_row_list
            kw["live_rows"] = live_row_list(live)

        def layer(ql, li):
            return fn(ql, qr, c, kr, bt, ctx, layer_idx=li, scale=192 ** -0.5,
                      **kw), None

        return jax.lax.scan(layer, ql, jnp.arange(layers, dtype=jnp.int32))[0]

    return layers, b, n_live, _time(step, (ql, qr, c, kr, bt, ctx), reps)


# name: (rows, vocabulary) of benchmark/configs/: falcon-h1-34b,
# moonlight-16b-a3b, xing4-29b-a4b, phi3-mini-4k
TAIL_CASES = {
    "falcon-h1": (64, 261120),
    "moonlight": (64, 163840),
    "xing4": (64, 131072),
    "phi3": (32, 32064),
}
# sdar-30b-a3b: 32 rows of a block of 4, every position sampled
BLOCK_CASES = {"sdar-block": (32, 4, 151936)}
# the shapes no walk is traced at, for --hash: minicpm-sala-9b,
# trinity-mini-26b-a3b
UNTILED_CASES = {"sala": (24, 73448), "trinity": (16, 200192)}


def tail_hashes():
    """{name: sha256 of the lowered tail at that shape}, abstract
    operands: nothing is compiled or run."""
    import hashlib
    import types

    from dynamo_tpu.engine import model_runner, sampling

    tail = model_runner._sample_and_logprobs
    masked = "live" in inspect.signature(tail).parameters
    sd = jax.ShapeDtypeStruct
    found = {}
    for name, (b, v) in {**TAIL_CASES, **UNTILED_CASES}.items():
        samp = jax.tree_util.tree_map(
            lambda a: sd((b,) + a.shape[1:], a.dtype),
            sampling.SamplingParams.zeros(1))
        text = jax.jit(lambda *a: tail(
            types.SimpleNamespace(vocab_size=v), None, *a[:-1],
            **({"live": a[-1]} if masked else {}))).lower(
            sd((b, v), jnp.bfloat16), samp, sd((b, v), jnp.int32),
            sd((b, v), jnp.bool_), sd((b, v), jnp.float32),
            sd((b,), jnp.int32), sd((b,), jnp.bool_), sd((), jnp.bool_),
            sd((b,), jnp.bool_)).as_text()
        found[name] = hashlib.sha256(text.encode()).hexdigest()
        print(found[name], f"{name:10s} [{b}, {v}]", flush=True)
    return found


def _time_tail(step, operands, reps):
    """``_time`` for a program that is given the counts and gives them
    back, as a decode step is (donated: the update is in place)."""
    logits, samp, counts, *rest = operands
    counts = jnp.copy(counts)
    times = []
    for _ in range(6):
        t0 = time.perf_counter()
        for _ in range(reps):
            toks, lps, counts = step(logits, samp, counts, *rest)
        jax.block_until_ready(counts)
        times.append((time.perf_counter() - t0) / reps)
    return statistics.median(times[1:]), (toks, lps)


def _ulps(a, b):
    """Largest distance of two float32 arrays in units in the last place."""
    def image(x):
        bits = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(bits < 0, -(bits & 0x7FFFFFFF), bits)
    return int(np.abs(image(a) - image(b)).max(initial=0))


def _compared(into, got, ref, live):
    """Fold the comparison of ``got`` with ``ref`` (tokens, logprobs) on
    the rows ``live`` into ``into``."""
    (toks, lps), (r_toks, r_lps) = [
        [np.asarray(x)[live] for x in pair] for pair in (got, ref)]
    into["tokens_equal"] &= bool(np.array_equal(toks, r_toks))
    into["logprob_ulps"] = max(into["logprob_ulps"], _ulps(lps, r_lps))


def tail_case(name, rng, reps, tiles):
    """One shape: {"all rows": ms, "<T>": {"<live>": ms, ...}, ...} and
    what every tiled pass gave the live rows beside what they get in a
    full batch and in the pass over all rows at once."""
    import types

    from dynamo_tpu.engine import model_runner, sampling

    b, v = TAIL_CASES[name]
    tail = model_runner._sample_and_logprobs
    cfg = types.SimpleNamespace(vocab_size=v)
    logits = _normal(0, (b, v)) * 3.0
    state = (jnp.zeros((b, v), jnp.int32), jnp.zeros((b, v), jnp.bool_),
             jnp.zeros((b, v), jnp.float32))
    slots = jnp.arange(b, dtype=jnp.int32)
    keys = jnp.asarray(rng.integers(0, 2 ** 32, (b, 2)), jnp.uint32)

    def operands(n_live):
        live = np.zeros(b, bool)
        live[rng.permutation(b)[:n_live]] = True
        samp = sampling.SamplingParams.zeros(b)
        samp = dataclasses.replace(
            samp, keys=keys,
            temperature=jnp.where(live, 0.7, 0.0).astype(jnp.float32),
            top_p=jnp.where(live, 0.9, 1.0).astype(jnp.float32))
        return logits, samp, *state, slots, jnp.asarray(live)

    def program(masked):
        # the counts come back as a step returns them, donated
        def run(logits, samp, counts, seen, bias, slots, live):
            kw = {"live": live} if masked else {}
            toks, lps, _, _, counts = tail(
                cfg, None, logits, samp, counts, seen, bias, slots, live,
                jnp.asarray(False), **kw)
            return toks, lps, counts
        return jax.jit(run, donate_argnums=2)

    lives = sorted({b // 4, b // 2, 3 * b // 4, b})
    row = {"rows": b, "vocabulary": v}
    plain = program(False)
    wanted = {n: operands(n) for n in lives}
    short = hasattr(sampling, "short_search")

    def general(ops):
        """``ops`` with an entry of bias a row: no row is bfloat16 any
        more, and the program searches all 32 bits."""
        logits, samp, counts, seen, bias, *rest = ops
        return (logits, samp, counts, seen, bias.at[:, 0].set(1e-3), *rest)

    seconds, one_pass = _time_tail(plain, wanted[b], reps)
    row["all rows"] = 1e3 * seconds
    print(f"{name:12s} [{b}, {v}] every row    {row['all rows']:.3f} ms",
          flush=True)
    if short:
        row["all rows full"] = 1e3 * _time_tail(
            plain, general(wanted[b]), reps)[0]
        row["short_equals_full"] = _short_equals_full(sampling, logits)
        print(f"{name:12s} [{b}, {v}] every row, 32 bits "
              f"{row['all rows full']:.3f} ms  short equals full "
              f"{row['short_equals_full']}", flush=True)
    if "live" not in inspect.signature(tail).parameters:
        return row
    if args.always:
        model_runner.walks_live_rows = lambda live, rows, tile: True
    for t in tiles:
        sampling.ROW_TILE = t
        tiled = program(True)
        row[str(t)] = cell = {"ms": {}}
        got = {}
        for n in lives:
            seconds, got[n] = _time_tail(tiled, wanted[n], reps)
            cell["ms"][str(n)] = 1e3 * seconds
        if short:
            cell["ms_full"] = {
                str(n): 1e3 * _time_tail(tiled, general(wanted[n]), reps)[0]
                for n in lives}
        for against in ("across_loads", "against_one_pass"):
            cell[against] = {"tokens_equal": True, "logprob_ulps": 0}
        for n in lives:
            live = np.asarray(wanted[n][-1])
            _compared(cell["across_loads"], got[n], got[b], live)
            _compared(cell["against_one_pass"], got[n], one_pass, live)
        print(f"{name:12s} [{b}, {v}] tiles of {t:2d}  " + "  ".join(
            f"{n} live {ms:.3f} ms" for n, ms in cell["ms"].items())
            + ("  32 bits " + "  ".join(
                f"{ms:.3f}" for ms in cell["ms_full"].values())
               if short else "")
            + f"  across loads {cell['across_loads']}"
            + f"  against one pass {cell['against_one_pass']}", flush=True)
    return row


def _short_equals_full(sampling, logits):
    """Whether ``filter_logits`` keeps the same entries of the bfloat16
    ``logits`` over a few temperatures by the short search and by the
    full one: top-p rows, top-k and min-p rows among them."""
    b = logits.shape[0]
    rows = np.arange(b)
    top_p = jnp.asarray(np.where(rows % 4 == 3, 1.0, 0.9), jnp.float32)
    top_k = jnp.asarray(np.where(rows % 3 == 0, 40, 0), jnp.int32)
    min_p = jnp.asarray(np.where(rows % 5 == 0, 0.02, 0.0), jnp.float32)
    keep = jax.jit(lambda short, *a: jnp.isfinite(
        sampling.filter_logits(*a, short=short)))
    same = True
    for temperature in (0.7, 1.0, 0.3, 1.9):
        temp = jnp.full((b,), temperature, jnp.float32)
        operands = (logits.astype(jnp.float32) / temp[:, None], top_k, top_p,
                    min_p, temp)
        same &= bool(jnp.array_equal(keep(jnp.asarray(True), *operands),
                                     keep(jnp.asarray(False), *operands)))
    return same


def block_case(name, rng, reps):
    """The block family's sampling at every position of a pass,
    ``[rows * length, V]``: the head's bfloat16 logits (since PR 61 the
    short search, known to the trace) and the same values as float32 (a
    general row: the full search, and two bytes an entry more to read)."""
    import types

    from dynamo_tpu.engine import sampling

    r, length, v = BLOCK_CASES[name]
    cfg = types.SimpleNamespace(vocab_size=v)
    logits = _normal(0, (r * length, v)) * 3.0
    samp = dataclasses.replace(
        sampling.SamplingParams.zeros(r),
        keys=jnp.asarray(rng.integers(0, 2 ** 32, (r, 2)), jnp.uint32),
        temperature=jnp.full((r,), 0.7, jnp.float32),
        top_p=jnp.full((r,), 0.9, jnp.float32))
    positions = jnp.asarray(
        rng.integers(0, 2048, (r, 1)) + np.arange(length), jnp.int32)
    step = jax.jit(lambda x: sampling.sample_block_positions(
        cfg, x, samp, positions, jnp.asarray(False), v - 1)[:2])
    row = {"rows": r * length, "vocabulary": v}
    toks = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        x = logits.astype(dtype)
        row[jnp.dtype(dtype).name] = 1e3 * _time(step, (x,), reps)
        toks[dtype] = np.asarray(step(x)[0])
    row["tokens_equal"] = bool(np.array_equal(*toks.values()))
    print(f"{name:12s} [{r * length}, {v}] bfloat16 {row['bfloat16']:.3f} ms"
          f"  float32 {row['float32']:.3f} ms  tokens equal "
          f"{row['tokens_equal']}", flush=True)
    return row


def main():
    if args.tail and args.hash:
        return _finish({"repo": os.path.abspath(args.repo),
                        "tail_hashes": tail_hashes()})
    if jax.default_backend() != "tpu":
        sys.exit(f"pad_row_cost.py times the kernels on a TPU; the backend "
                 f"here is {jax.default_backend()!r}: nothing measured")
    device = jax.devices()[0]
    if args.tail:
        from dynamo_tpu.engine import sampling

        tiles = [int(t) for t in args.tiles.split(",")]
        table = {"repo": os.path.abspath(args.repo),
                 "device": device.device_kind, "always": args.always,
                 "tail": {}}
        for bits in [int(n) for n in args.search_bits.split(",") if n] or [
                sampling._SEARCH_BITS]:
            if bits != sampling._SEARCH_BITS:
                sampling._SEARCH_BITS = bits
                jax.clear_caches()
            print(f"{bits} bits a pass", flush=True)
            table["tail"][f"{bits} bits a pass"] = {
                **{name: tail_case(name, np.random.default_rng(7),
                                   args.reps, tiles) for name in TAIL_CASES},
                **{name: block_case(name, np.random.default_rng(7), args.reps)
                   for name in BLOCK_CASES}}
        return _finish(table)
    table = {"repo": os.path.abspath(args.repo), "device": device.device_kind,
             "live_rows": _takes_live_rows(pallas_decode.paged_decode_attention),
             "cases": {}}
    for cases, run in ((GQA_CASES, gqa_case), (MLA_CASES, mla_case)):
        for name in cases:
            layers, b, n_live, seconds = run(name, np.random.default_rng(7),
                                             args.reps)
            row = {"layers": layers, "rows": b, "live": n_live,
                   "step_ms": 1e3 * seconds,
                   "call_us": 1e6 * seconds / layers}
            table["cases"][name] = row
            print(f"{name:24s} rows {b:3d} live {n_live:3d} "
                  f"step {row['step_ms']:.3f} ms  call {row['call_us']:.1f} us",
                  flush=True)
    _finish(table)


def _finish(table):
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))


if __name__ == "__main__":
    main()
