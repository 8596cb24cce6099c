"""The block-table widths a decode-shaped program is compiled at follow
from what its trace did, not from a fixed ladder.

A program whose kernels walk a row's live pages does the same work at
any width, so warm-up compiles it at ``blocks_per_seq`` alone and the
scheduler sizes every table to that; a program whose trace read the
table at its width (the XLA gather, MiniCPM-SALA's block selection)
keeps every rung of ``EngineConfig.kv_width_buckets`` and gets the
smallest that covers the batch. Tiny models on the CPU: the kernels run
in the Pallas interpreter, the default route is the gather.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import llama, minicpm_sala
from dynamo_tpu.ops import attention as attn_ops

from test_block_decode import HF as SDAR_HF, _drive, _request
from test_minicpm_sala_reference import HF as SALA_HF, SPARSE

LLAMA_HF = {
    "architectures": ["LlamaForCausalLM"], "model_type": "llama",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "max_position_embeddings": 2048, "tie_word_embeddings": False,
}
PAGE = 16
WIDTH = 16                      # blocks_per_seq: the ladder is 8, 16
# name: (the family's configuration, the runner's method, its program)
PROGRAMS = {
    "step": (LLAMA_HF, "step", "decode"),
    "decode_block": ({**SDAR_HF, "num_hidden_layers": 1}, "decode_block",
                     "decode_block"),
}


def _runner(hf, impl, **over):
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf),
                              attention_impl=impl)
    return ModelRunner(EngineConfig(**{**dict(
        model=cfg, max_batch_size=2, max_model_len=WIDTH * PAGE,
        kv_block_size=PAGE, num_kv_blocks=48, prefill_buckets=[64],
        max_prefill_batch=1, dtype="float32", seed=3), **over}))


def _decode_keys(runner, program):
    """The block-table widths ``program`` was first dispatched at, in
    order (a step's prefill shapes are another program)."""
    return [int(r["key"].rsplit("_w", 1)[1]) for r in runner.compiles.records
            if r["program"] == program]


def _serve(runner, method, prompts, max_tokens=6):
    """Streams of ``prompts`` through a scheduler, one after another so
    that every context length decodes alone, and the table widths
    ``method`` was dispatched at (its decode-shaped calls)."""
    widths = []
    inner = getattr(runner, method)

    def recording(tokens, positions, block_tables, *args, **kwargs):
        if method != "step" or np.shape(tokens)[1] == 1:
            widths.append(np.shape(block_tables)[1])
        return inner(tokens, positions, block_tables, *args, **kwargs)

    setattr(runner, method, recording)
    try:
        _, got, _ = _drive(runner, runner.config,
                           [_request(p, max_tokens) for p in prompts],
                           staggered=True)
    finally:
        delattr(runner, method)
    return [toks for toks, *_ in got], widths


def _prompts(blocks, seed=0):
    """A prompt that decodes inside its ``n``-th block, for each n."""
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, (n - 1) * PAGE + 3).tolist() for n in blocks]


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_a_kernel_walk_is_warmed_and_served_at_one_width(monkeypatch, caplog,
                                                         name):
    """(a) Under the kernel route warm-up dispatches one decode-shaped
    key, at ``blocks_per_seq``, says so, and contexts of 1, 9 and
    ``blocks_per_seq`` - 1 blocks are served by it with no late compile."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    hf, method, program = PROGRAMS[name]
    runner = _runner(hf, "pallas")
    with caplog.at_level("INFO", logger="dynamo_tpu.engine.model_runner"):
        runner.warmup()
    assert _decode_keys(runner, program) == [WIDTH]
    assert program not in runner.width_programs
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("decode programs' table widths: ")]
    assert json.loads(line.split(": ", 1)[1]) == {
        program: {"widths": [WIDTH], "why": "kernel walk"}}
    assert [runner.table_width(program, n) for n in (1, 9, WIDTH)] == [WIDTH] * 3
    streams, widths = _serve(runner, method, _prompts([1, 9, WIDTH - 1]))
    assert [len(s) for s in streams] == [6, 6, 6]
    assert widths and set(widths) == {WIDTH}
    assert _decode_keys(runner, program) == [WIDTH]
    assert runner.compiles.late_compiles == 0


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_a_gather_keeps_every_rung_and_gets_the_smallest_that_covers(caplog,
                                                                     name):
    """(b) On the CPU's default route (the XLA gather) warm-up sweeps the
    ladder as it always did, the full width first, and the scheduler
    picks the smallest rung that covers the batch."""
    hf, method, program = PROGRAMS[name]
    runner = _runner(hf, "auto")
    assert attn_ops.resolve_attention_impl("auto") == "xla"
    with caplog.at_level("INFO", logger="dynamo_tpu.engine.model_runner"):
        runner.warmup()
    assert runner.config.kv_width_buckets() == [8, WIDTH]
    assert _decode_keys(runner, program) == [WIDTH, 8]
    assert program in runner.width_programs
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("decode programs' table widths: ")]
    assert json.loads(line.split(": ", 1)[1]) == {
        program: {"widths": [8, WIDTH], "why": "gather traced"}}
    streams, widths = _serve(runner, method, _prompts([1, 9, WIDTH - 1]))
    assert [len(s) for s in streams] == [6, 6, 6]
    # one block: the first rung; nine and fifteen: the full width
    assert widths[0] == 8 and widths[-1] == WIDTH
    assert sorted(set(widths)) == [8, WIDTH]
    assert runner.compiles.late_compiles == 0


@pytest.mark.parametrize("impl,first_only", [("pallas", True), ("xla", False)])
def test_without_warm_up_the_first_dispatch_is_at_full_width_and_decides(
        monkeypatch, impl, first_only):
    """(d) ``warmup=False``: nothing is known of the decode program until
    it has traced, so its first dispatch is at ``blocks_per_seq``; after
    it a kernel walk never asks for another width, and a gather asks for
    the rungs of the ladder."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    runner = _runner(LLAMA_HF, impl)
    assert runner.table_width("decode", 1) == WIDTH      # not yet traced
    streams, widths = _serve(runner, "step", _prompts([1, 9]))
    assert [len(s) for s in streams] == [6, 6]
    assert widths[0] == WIDTH
    if first_only:
        assert set(widths) == {WIDTH}
        assert _decode_keys(runner, "decode") == [WIDTH]
    else:
        assert widths[1] == 8 and _decode_keys(runner, "decode") == [WIDTH, 8]
        assert set(widths) <= set(runner.config.kv_width_buckets())
    # what each route streams does not depend on the table's width
    _STREAMS.setdefault("streams", streams)
    assert streams == _STREAMS["streams"]


_STREAMS: dict = {}


def test_a_rows_logits_do_not_depend_on_the_tables_width(monkeypatch):
    """(c) The same work: one batch through the kernel route at a rung and
    at the full width. Where the walk's chunks are the same (rungs of 64
    pages and over at this page) the logits are equal bit for bit; a rung
    that caps ``chunk_pages`` folds the same keys in other chunks, and is
    held to the kernel tests' float32 tolerance."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(
        ModelConfig.from_hf_config({**LLAMA_HF, "num_hidden_layers": 1}),
        attention_impl="pallas")
    params = llama.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    full, rows = 128, 3
    ctx = np.array([5, 100, 128], np.int32)       # 1, 7 and 8 pages
    k, v = llama.init_kv_cache(cfg, rows * 8 + 1, PAGE, jnp.float32)
    k, v = (jax.random.normal(jax.random.PRNGKey(i), c.shape, c.dtype)
            for i, c in enumerate((k, v)))
    table = np.zeros((rows, full), np.int32)
    table[:, :8] = 1 + np.arange(rows * 8).reshape(rows, 8)
    tokens = np.array([[7], [99], [200]], np.int32)
    positions = (ctx - 1)[:, None]
    slots = np.take_along_axis(table, positions // PAGE, 1) * PAGE + positions % PAGE

    def logits(width):
        out, _ = jax.jit(
            lambda bt: llama.forward(params, cfg, tokens, positions, (k, v), bt,
                                     slots, ctx))(table[:, :width])
        return np.asarray(out)

    at_full = logits(full)
    assert np.isfinite(at_full).all() and np.ptp(at_full) > 0.1
    np.testing.assert_array_equal(logits(64), at_full)
    for rung in (8, 32):
        np.testing.assert_allclose(logits(rung), at_full, atol=2e-5, rtol=0)


def test_block_selection_reads_the_width_and_keeps_its_ladder(monkeypatch,
                                                              caplog):
    """(e) MiniCPM-SALA under the kernel route: the pages are walked by
    the decode kernel, but where a table can hold a row past
    ``dense_len`` the program scores and sorts the table's width of
    page means, and says so; every rung is warmed, and a rung no wider
    than ``dense_len`` still has no selection in it. Warm-up also says
    which parameters served the lightning layers' state kernel, as it
    says the page walks' chunks."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    hf = {**SALA_HF, "sparse_config": {**SPARSE, "dense_len": 8 * PAGE}}
    runner = _runner(hf, "pallas", max_model_len=32 * PAGE, num_kv_blocks=80,
                     max_batch_size=2)
    with caplog.at_level("INFO", logger="dynamo_tpu.engine.model_runner"):
        runner.warmup()
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("state kernel's blocks: ")]
    # four heads of 16 x 16, a group a head: a head a tile, the row's four
    # tiles one block, its four groups' B and C made columns in one turn
    nh, hd = hf["lightning_nh"], hf["lightning_head_dim"]
    assert {"heads": nh, "p": hd, "n": hd, "heads_per_group": 1,
            "itemsize": 4, "lane_heads": 1, "tiles_per_block": 4,
            "groups_per_block": 4, "groups_per_turn": 4,
            "block_bytes": nh * hd * hd * 4} \
        in json.loads(line.split(": ", 1)[1])
    assert runner.config.kv_width_buckets() == [8, 16, 32]
    assert _decode_keys(runner, "decode") == [32, 8, 16]
    assert runner.warmed_widths == {
        "decode": {"widths": [8, 16, 32], "why": "gather traced"}}
    assert [runner.table_width("decode", n) for n in (1, 9, 17)] == [8, 16, 32]
    # with no rung past dense_len nothing selects, and one width is enough
    dense = {**hf, "sparse_config": {**SPARSE, "dense_len": 32 * PAGE}}
    cfg = dataclasses.replace(ModelConfig.from_hf_config(hf),
                              attention_impl="pallas")
    params = minicpm_sala.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    cache = minicpm_sala.init_kv_cache(cfg, 40, PAGE, jnp.float32, num_slots=2)

    def traced(hf_, w):
        cfg_ = dataclasses.replace(ModelConfig.from_hf_config(hf_),
                                   attention_impl="pallas")
        args = (jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32),
                cache, jnp.zeros((2, w), jnp.int32),
                jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), jnp.int32))
        with attn_ops.route_program("probe"):
            text = jax.jit(lambda *a: minicpm_sala.forward(
                params, cfg_, *a)).lower(*args).as_text()
            return attn_ops.table_width_traced(), "top_k" in text

    assert traced(hf, 8) == (False, False)
    assert traced(hf, 16) == (True, True)
    assert traced(dense, 32) == (False, False)


def test_a_trace_says_whether_it_read_the_tables_width():
    """The flag is per tracked dispatch: entering ``route_program``
    clears it, the ``xla`` route and ``record_table_width`` set it, a
    kernel route does not."""
    with attn_ops.route_program("a"):
        assert not attn_ops.table_width_traced()
        attn_ops.record_route("decode")
        attn_ops.record_route("verify")
        attn_ops.record_route("flash")
        assert not attn_ops.table_width_traced()
        attn_ops.record_route("xla")
        assert attn_ops.table_width_traced()
    with attn_ops.route_program("b"):
        assert not attn_ops.table_width_traced()
        attn_ops.record_table_width()
        assert attn_ops.table_width_traced()


def test_a_variant_mosaic_rejects_falls_back_to_the_gather_and_says_so(
        monkeypatch):
    """``attention_impl="auto"`` where it resolves to the kernels (a TPU;
    patched here): sinks on the verify shape are what ``mosaic_rejects``
    sends to the XLA gather, so that program reports the width and keeps
    its ladder, while the same model's one-query step walks on the decode
    kernel and does not."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    resolve = attn_ops.resolve_attention_impl
    monkeypatch.setattr(attn_ops, "resolve_attention_impl",
                        lambda impl: "pallas" if impl == "auto" else resolve(impl))
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.normal(size=(4, PAGE, 2, 128)), jnp.float32)
            for _ in range(2))
    table = jnp.asarray([[1, 2]], jnp.int32)
    sinks = jnp.zeros((2,), jnp.float32)

    def traced(s):
        q = jnp.asarray(rng.normal(size=(1, s, 2, 128)), jnp.float32)
        pos = jnp.arange(20 - s, 20, dtype=jnp.int32)[None]
        with attn_ops.route_program("probe"):
            out = attn_ops.attention(q, k, v, table, pos,
                                     jnp.asarray([20], jnp.int32), sinks=sinks)
            assert np.isfinite(np.asarray(out)).all()
            return attn_ops.table_width_traced()

    assert attn_ops.mosaic_rejects("verify", True, jnp.float32, 2)
    assert traced(2) is True          # the verify shape: the gather
    assert traced(1) is False         # one query a row: the decode kernel
