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
"""

import asyncio

import pytest

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


def _serve(model_dir, impl, multi_step, pipeline):
    async def go():
        cfg = _config(model_dir, multi_step, pipeline)
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
        await engine.close()
        return streams, counted, programs, text

    return asyncio.new_event_loop().run_until_complete(go())


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_pad_rows_are_skipped_and_live_rows_never(model_dir, monkeypatch,  # noqa: F811
                                                  name):
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    multi_step, pipeline, program = PROGRAMS[name]
    xla, (x_rows, x_skipped), x_programs, x_text = _serve(
        model_dir, "xla", multi_step, pipeline)
    pal, (p_rows, p_skipped), p_programs, p_text = _serve(
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
