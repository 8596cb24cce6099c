"""A pad row of the decode batch is no grid step, and a row that decodes
is never one.

Whole engines on the kernel route (the Pallas interpreter here) against
the XLA route, with fewer sequences than slots so that every decode
program carries pad rows: the streams are the same token for token, the
scheduler counts the rows it skipped only for a program whose trace
handed its kernels a list of live rows, and on the XLA route it counts
none. Every program that reaches the decode route is driven: the step,
the fused burst and the chained burst. The kernels themselves are held
to the XLA reference in tests/test_pallas_decode.py.

The sampling tail takes the same mask: with a tile small enough for four
slots the same engines stream what they stream without tiles, the burst
and the chain what the single step streams (rows finish inside a burst of
four), and ``dynamo_scheduler_sampling_rows_run_total`` stays under
``..._rows_total`` while the batch has pad rows.
"""

import asyncio

import types

import pytest

from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.serving import JaxServingEngine
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.protocols.common import SamplingOptions

from test_multi_step import _collect, _config, model_dir  # noqa: F401

ROWS = "dynamo_scheduler_decode_rows_total"
SKIPPED = "dynamo_scheduler_decode_rows_skipped_total"

# name: (multi_step_decode, decode_pipeline_depth, the program's name)
PROGRAMS = {
    "step": (1, 1, "decode"),
    "burst": (4, 1, "decode_burst"),
    "chain": (4, 2, "decode_burst_df"),
}


PROGRAMS_BY_STEPS = {(m, p): program for m, p, program in PROGRAMS.values()}


def _held(n, biased=None):
    """``n`` requests as ``Scheduler._count_decode_rows`` reads them, in
    slots 0 to n - 1, the one in slot ``biased`` with a logit_bias."""
    return [types.SimpleNamespace(
        slot=i, guided=None, presence_penalty=0.0, frequency_penalty=0.0,
        repetition_penalty=1.0, req=types.SimpleNamespace(
            sampling_options=SamplingOptions(
                logit_bias={7: 1.5} if i == biased else None)))
        for i in range(n)]


def _serve(model_dir, impl, multi_step, pipeline, dtype="float32"):
    async def go():
        cfg = _config(model_dir, multi_step, pipeline)
        cfg.dtype = dtype
        cfg.model.attention_impl = impl
        engine = await JaxServingEngine.create(
            ModelDeploymentCard.from_local_path(model_dir),
            engine_config=cfg, warmup=False)
        sched = engine.scheduler
        # one sequence on four slots, then two at once, the second
        # admitted into a slot after an idle one's
        streams = [await _collect(
            engine, [1, 17, 43, 99, 7], SamplingOptions(temperature=0.0),
            max_tokens=12)]
        streams += await asyncio.gather(
            _collect(engine, [1, 42, 42], SamplingOptions(temperature=0.0),
                     max_tokens=9),
            _collect(engine, [1, 7, 7, 7, 7],
                     SamplingOptions(temperature=0.9, seed=11), max_tokens=14))
        counted = (sum(sched._decode_rows_ctr.values.values()),
                   sum(sched._decode_rows_skipped_ctr.values.values()))
        programs = set(engine.runner.row_list_programs)
        text = sched.registry.render()
        total = lambda c: sum(c.values.values())
        tail = types.SimpleNamespace(
            programs=dict(engine.runner.sampling_tile_programs),
            rows=total(sched._sampling_rows_ctr),
            run=total(sched._sampling_rows_run_ctr))
        # a float32 head: no row's search was the short one
        tail.short = total(sched._sampling_short_rows_ctr)
        tail.short_programs = set(engine.runner.sampling_short_programs)
        # and one step each of a full batch and of one row, as counted
        program = PROGRAMS_BY_STEPS[multi_step, pipeline]
        counters = (sched._sampling_rows_ctr, sched._sampling_rows_run_ctr,
                    sched._sampling_short_rows_ctr)
        for live in (4, 1):
            before = [total(c) for c in counters]
            sched._count_decode_rows(program, _held(live))
            setattr(tail, f"step_of_{live}", tuple(
                total(c) - was for c, was in zip(counters[:2], before)))
        # the same of a program whose head makes bfloat16: every row run
        # where no request adds to its logits, none of a tile that holds
        # one with a bias
        engine.runner.sampling_short_programs.add(program)
        for name, held in (("plain", _held(4)), ("one_plain", _held(1)),
                           ("biased", _held(4, biased=2))):
            before = [total(c) for c in counters]
            sched._count_decode_rows(program, held)
            setattr(tail, f"short_of_{name}", tuple(
                total(c) - was for c, was in zip(counters[1:], before[1:])))
        await engine.close()
        return streams, counted, programs, text, tail

    return asyncio.new_event_loop().run_until_complete(go())


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_pad_rows_are_skipped_and_live_rows_never(model_dir, monkeypatch,  # noqa: F811
                                                  name):
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    multi_step, pipeline, program = PROGRAMS[name]
    xla, (x_rows, x_skipped), x_programs, x_text, _ = _serve(
        model_dir, "xla", multi_step, pipeline)
    pal, (p_rows, p_skipped), p_programs, p_text, _ = _serve(
        model_dir, "pallas", multi_step, pipeline)
    assert [len(t) for t, _ in pal] == [12, 9, 14]
    assert pal == xla
    # the XLA route walks every row: nothing is skipped, and said so
    assert x_rows > 0 and x_skipped == 0 and not x_programs
    # the kernel route: the program's trace took the list, and at most
    # two of four rows ever held a sequence
    assert program in p_programs
    assert p_rows % 4 == 0 and 0.5 * p_rows <= p_skipped < p_rows
    for text in (x_text, p_text):
        assert ROWS in text and SKIPPED in text


def test_a_bfloat16_heads_rows_are_counted_as_searched_short(model_dir):  # noqa: F811
    """The same three requests on a bfloat16 model: the traces of the decode and
    prefill programs saw bfloat16 logits on one device, and every row the tail ran
    on is counted as searched in half the passes (no request has a bias,
    a mask or a penalty)."""
    streams, _, _, text, tail = _serve(model_dir, "xla", 1, 1, "bfloat16")
    assert [len(t) for t, _ in streams] == [12, 9, 14]
    assert tail.short_programs == {"decode", "prefill"}
    assert tail.short == tail.run == tail.rows > 0
    assert "dynamo_scheduler_sampling_short_search_rows_total" in text


_STEP_STREAMS = []


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_sampling_runs_on_tiles_of_live_rows_and_streams_the_same(
        model_dir, monkeypatch, name):  # noqa: F811
    multi_step, pipeline, program = PROGRAMS[name]
    # four slots are fewer than two of the real tiles: every row, as before
    plain, _, _, text, p_tail = _serve(model_dir, "xla", multi_step, pipeline)
    assert not p_tail.programs and p_tail.rows == p_tail.run > 0
    assert p_tail.step_of_4 == p_tail.step_of_1 == (4, 4)
    assert p_tail.short == 0 and not p_tail.short_programs
    assert p_tail.short_of_plain == p_tail.short_of_one_plain == (4, 4)
    assert p_tail.short_of_biased == (4, 0)
    # tiles of two: walked while at most two of the four rows are live,
    # which they always are here; a stream does not change for it
    monkeypatch.setattr(sampling, "ROW_TILE", 2)
    tiled, _, _, _, t_tail = _serve(model_dir, "xla", multi_step, pipeline)
    assert [len(t) for t, _ in tiled] == [12, 9, 14]
    assert tiled == plain
    assert t_tail.programs[program] == 2
    assert t_tail.rows % 4 == 0 and t_tail.run == t_tail.rows // 2
    assert t_tail.step_of_4 == (4, 4) and t_tail.step_of_1 == (4, 2)
    assert t_tail.short == 0
    assert t_tail.short_of_plain == (4, 4)
    assert t_tail.short_of_one_plain == (2, 2)
    assert t_tail.short_of_biased == (4, 2)     # rows 2 and 3's tile: long
    assert "dynamo_scheduler_sampling_rows_total" in text
    assert "dynamo_scheduler_sampling_rows_run_total" in text
    assert "dynamo_scheduler_sampling_short_search_rows_total" in text
    # a burst of four steps with rows that finish inside it (12, 9 and 14
    # tokens) streams what the single step streams
    if name == "step":
        _STEP_STREAMS[:] = [tiled]
    elif _STEP_STREAMS:
        assert [t for t, _ in tiled] == [t for t, _ in _STEP_STREAMS[0]]
