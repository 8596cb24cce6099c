"""The served MiniCPM-SALA path (lightning linear-attention layers with
their state by slot, block-sparse attention layers with pages and
compressed keys, in one trunk; prefill in chunks, decode one token a
step) against the benchmark's plain reference,
``benchmark/references/minicpm_sala.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy.

Tiny ``minicpm_sala`` shape that keeps the ratios: 4 query heads over 2
kv heads, 4 lightning heads, the layers ``minicpm4, lightning-attn x 2,
minicpm4`` (two runs of the sparse kind around one of the other), the
published page, kernel and block sizes with ``dense_len`` 64, a window of
64 and ``topk`` 2, so that a context of a few hundred tokens selects.
"""

import asyncio
import dataclasses
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler
from dynamo_tpu.models import minicpm_sala
from dynamo_tpu.ops import sparse_attention as sparse
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext

import served  # noqa: E402  (puts benchmark/ on the path)
from references import minicpm_sala as reference  # noqa: E402

SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 2,
          "init_blocks": 1, "window_size": 64, "dense_len": 64}
HF = {
    "architectures": ["MiniCPMSALAForCausalLM"], "model_type": "minicpm_sala",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_use_rope": True, "lightning_scale": "1/sqrt(d)",
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    "rope_theta": 10000, "rms_norm_eps": 1e-6, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "mup_denominator": 32,
    "max_position_embeddings": 1024, "tie_word_embeddings": False,
    "depth_cut": {"of_layers": 8, "first_layer": 2},
    "sparse_config": SPARSE,
}
PAGE = 16
SLOTS = 4
WIDTH = 32        # pages a sequence: 512 tokens
# float32 on both sides: the two differ in the order of the sums (the
# chunked form against the recurrence, page means against window means,
# a walk of kept pages against a masked product) and in nothing else;
# differences seen are 4e-6 to 1e-5 in log-probability at any position,
# and the smallest deliberate fault below reads over 1e-2
F32_ATOL = 1e-3


def _cfg(hf=HF, **over):
    return served.cfg_of(hf, **over)


def _params(dtype, seed=7, hf=HF):
    cfg = _cfg(hf)
    return cfg, minicpm_sala.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _reference_logprobs(params, seq, hf=HF):
    return served.reference_logprobs(reference, hf, params, seq, pad=128)


def Served(cfg, params, dtype, state_dtype=None, fresh=False):
    """32 pages of 16 a slot behind block 0, which is nobody's: an idle
    row's table points there."""
    return served.Served(minicpm_sala, cfg, params, dtype, block=PAGE,
                         width=WIDTH, slots=SLOTS, state_dtype=state_dtype,
                         fresh=fresh)


_seqs, _serve_case = served.seqs, served.serve_case


CASES = {
    # (a) a prompt under dense_len in one chunk, and decode across
    # dense_len (64): the first steps dense, the last ones select
    "crosses_dense_len_in_decode": dict(lengths=[50 + 40], n_decode=40,
                                        cuts=[], width=64),
    # (b) prefill in three chunks, boundaries off the page of 16 and off
    # the scan's chunk; the first chunk crosses dense_len inside itself;
    # then 20 decode steps that select
    "three_chunks": dict(lengths=[330 + 20], n_decode=20, cuts=[100, 228],
                         width=128),
    # (c) one long chunk, a decode step that completes a page (400 = 25
    # pages) and ones that start the next
    "one_chunk_page_edge": dict(lengths=[390 + 24], n_decode=24, cuts=[],
                                width=512),
    # (d) rows of different lengths, a pad row between them, slots that
    # are not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[40 + 6, 300 + 6, 150 + 6], n_decode=6,
                          cuts=[128, 256], width=128, slots=[2, 0, 3],
                          pad_row=True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case):
    """Chunked prefill, the compressed keys written by prefill and by
    decode, the selection and decode through state and pages give the
    reference's full-forward log-softmax at every position."""
    cfg, params = _params(jnp.float32)
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    slots = c.get("slots", list(range(len(seqs))))
    served = Served(cfg, params, jnp.float32)
    got = _serve_case(served, seqs, slots, c["n_decode"], c["cuts"],
                      c["width"], c.get("pad_row", False))
    for seq, lp in zip(seqs, got):
        np.testing.assert_allclose(lp, _reference_logprobs(params, seq),
                                   rtol=0, atol=F32_ATOL)
    kept, context, rows, steps, scanned, chunks = served.counts()
    assert steps == c["n_decode"] and 0 < kept <= context
    assert rows > 0                              # rows past dense_len
    # every prompt token went through the scan once, a chunk a prefill step
    assert scanned == sum(n - c["n_decode"] for n in c["lengths"])
    assert chunks == len(c["cuts"]) + 1
    if max(c["lengths"]) > 5 * SPARSE["block_size"] + PAGE:
        # more blocks than the first, the two best and the window's two
        assert kept < context


def test_bfloat16_served_path_stays_near_the_reference():
    """bfloat16 weights, activations and pages (the state and the page
    means float32) against the float32 reference on the same weights: a
    rounding-sized difference, far under what a wrong program reads."""
    cfg, params = _params(jnp.bfloat16)
    c = CASES["three_chunks"]
    seq = _seqs(c["lengths"], seed=3)[0]
    got = _serve_case(Served(cfg, params, jnp.bfloat16), [seq], [0],
                      c["n_decode"], c["cuts"], c["width"])[0]
    worst = np.abs(got - _reference_logprobs(params, seq)).max(axis=1)
    # the largest difference over the vocabulary a position: median 0.12
    # on this shape (hidden 64: coarser than the chip's); a position or
    # two read 0.5-0.8 where rounding moved one of the two picked blocks
    # (top 2 of 3 here, top 64 of 100-250 on the chip)
    assert np.median(worst) < 0.2 and np.quantile(worst, 0.95) < 0.6


def test_resume_after_preemption_and_slot_reuse():
    """A sequence dropped after 10 decoded tokens and prefilled again
    from position 0 (prompt + the 10), into the slot another sequence has
    used meanwhile, continues as the reference says; the second user of
    a slot starts from zeros, not from what the first left."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    a, b = _seqs([200 + 30, 90 + 8], seed=4)
    want_a, want_b = _reference_logprobs(params, a), _reference_logprobs(params, b)
    got = _serve_case(served, [a[:210]], [1], 10, [], 256)[0]
    np.testing.assert_allclose(got, want_a[:210], atol=F32_ATOL)
    got = _serve_case(served, [b], [1], 8, [], 128)[0]
    np.testing.assert_allclose(got, want_b, atol=F32_ATOL)
    got = _serve_case(served, [a], [1], 20, [128], 128)[0]
    np.testing.assert_allclose(got, want_a, atol=F32_ATOL)


def _plain_picks(scores, n, sp=SPARSE):
    """The kept blocks of one query by the definition, in numpy."""
    bs = sp["block_size"]
    visible = (n - 1) // bs + 1
    if n <= sp["dense_len"]:
        return set(range(visible))
    first_window = max(n - sp["window_size"], 0) // bs
    forced = {m for m in range(visible)
              if m < sp["init_blocks"] or m >= first_window}
    others = [m for m in range(visible) if m not in forced]
    best = sorted(others, key=lambda m: (-scores[m], m))[:sp["topk"]]
    return forced | set(best)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_picked_blocks_are_the_definitions(seed):
    """``kept_blocks`` on seeded scores without ties, at contexts under,
    at and past dense_len, at a block's edge and inside one; and
    ``block_scores`` as the maximum over the overlapping windows."""
    rs = np.random.RandomState(seed)
    shape = sparse.SparseShape(PAGE, 4, SPARSE["topk"], SPARSE["init_blocks"],
                               SPARSE["window_size"], SPARSE["dense_len"])
    nb = 12
    ns = np.array([1, 63, 64, 65, 128, 129, 300, 511, 512, 640, 767, 768])
    p = rs.rand(len(ns), 4 * nb - 1).astype(np.float32)     # one a window
    got_scores = np.asarray(sparse.block_scores(jnp.asarray(p), shape, nb))
    for m in range(nb):
        js = [j for j in range(4 * m - 1, 4 * m + 4) if 0 <= j < p.shape[1]]
        np.testing.assert_array_equal(got_scores[:, m], p[:, js].max(axis=1))
    kept = np.asarray(sparse.kept_blocks(jnp.asarray(got_scores),
                                         jnp.asarray(ns), shape))
    for row, n in enumerate(ns):
        assert set(np.flatnonzero(kept[row])) == _plain_picks(got_scores[row], n), n


def _wrong(fault, monkeypatch):
    """A served program with one deliberate fault."""
    cfg, params = _params(jnp.float32)
    state_dtype, served_params = None, params
    if fault == "bf16_state":
        state_dtype = jnp.bfloat16
    elif fault == "one_block_fewer":
        cfg = dataclasses.replace(cfg, sparse_topk=SPARSE["topk"] - 1)
    elif fault == "no_first_block":
        cfg = dataclasses.replace(cfg, sparse_init_blocks=0)
    elif fault == "dense_everywhere":
        cfg = dataclasses.replace(cfg, sparse_dense_len=1 << 20)
    elif fault == "unshifted_decay":      # the cut's indices, not the published
        runs = list(params["runs"])
        runs[1] = {**runs[1], "log_decay": minicpm_sala.log_decays(
            dataclasses.replace(cfg, first_layer=0))[1:3]}
        served_params = {**params, "runs": runs}
    elif fault == "cut_depth_scale":
        cfg = dataclasses.replace(cfg, depth_of=cfg.num_layers)
    elif fault == "stale_page_means":
        monkeypatch.setattr(sparse, "write_page_means",
                            lambda means, *a, **k: means)
    return Served(cfg, served_params, jnp.float32, state_dtype,
                  fresh=True), params


@pytest.mark.parametrize("fault", [
    "bf16_state", "one_block_fewer", "no_first_block", "dense_everywhere",
    "unshifted_decay", "cut_depth_scale", "stale_page_means"])
def test_reference_tells_wrong_programs_apart(fault, monkeypatch):
    served, params = _wrong(fault, monkeypatch)
    c = CASES["three_chunks"]
    seq = _seqs(c["lengths"], seed=5)[0]
    got = _serve_case(served, [seq], [0], c["n_decode"], c["cuts"], c["width"])[0]
    assert np.abs(got - _reference_logprobs(params, seq)).max() > 3 * F32_ATOL


def _engine_config(**over):
    kw = dict(model=_cfg(), max_batch_size=SLOTS, max_model_len=512,
              kv_block_size=PAGE, num_kv_blocks=96, dtype="float32",
              prefill_buckets=[64, 128], max_prefill_tokens_per_step=128,
              seed=11, max_prefill_batch=2)
    kw.update(over)
    return EngineConfig(**kw)


@pytest.mark.parametrize("setting,path", [
    (dict(spec_ngram_tokens=2), "spec_ngram_tokens"),
    (dict(tp_size=2), "tp_size"),
    (dict(host_kv_blocks=8), "host_kv_blocks"),
    (dict(multi_step_decode=4), "multi_step_decode"),
    (dict(decode_pipeline_depth=2), "decode_pipeline_depth"),
])
def test_paths_that_cannot_carry_the_state_are_refused_at_start_up(setting, path):
    with pytest.raises(ValueError, match=rf"{path} is refused for the "
                                         "minicpm_sala family.*recurrent state"):
        ModelRunner(_engine_config(**setting))


def test_mixer_types_on_another_family_is_refused_by_name():
    hf = {**HF, "model_type": "some_other_hybrid"}
    with pytest.raises(NotImplementedError, match="some_other_hybrid.*lightning_"):
        ModelConfig.from_hf_config(hf)
    with pytest.raises(NotImplementedError, match="mixer_types"):
        models.resolve(dataclasses.replace(_cfg(), model_family=""))
    assert models.resolve(_cfg()) is minicpm_sala
    with pytest.raises(NotImplementedError, match="attn_use_rope"):
        ModelConfig.from_hf_config({**HF, "attn_use_rope": True})
    with pytest.raises(ValueError, match="mixer_types has 3 entries"):
        ModelConfig.from_hf_config({**HF, "mixer_types": HF["mixer_types"][:3]})
    with pytest.raises(NotImplementedError, match="kernel_stride 8"):
        sparse.sparse_shape(dataclasses.replace(_cfg(), sparse_kernel_stride=8), PAGE)


def _request(prompt, max_tokens):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        output_options=OutputOptions(logprobs=0),
        eos_token_ids=[],
    )
    return EngineRequest(
        request_id=uuid.uuid4().hex, prompt=list(prompt), req=req,
        ctx=AsyncEngineContext(), out_queue=asyncio.Queue(),
    )


def _drive(sched, requests):
    async def go():
        sched.start()

        async def collect(er):
            toks, lps = [], []
            while True:
                out = await er.out_queue.get()
                if out is None:
                    return toks, lps
                toks.extend(out.token_ids)
                lps.extend(lp.logprob for lp in out.logprobs or [])
        try:
            for er in requests:
                sched.add_request(er)
            return await asyncio.gather(*(collect(er) for er in requests))
        finally:
            await sched.stop()
    return go()


def test_engine_streams_equal_reference_through_preemption():
    """Through the scheduler, the allocator and ``ModelRunner.step``: a
    cache too small for three long sequences preempts one, which resumes
    by re-prefilling from position 0 (state, pages and page means made
    again); every emitted token is the reference's argmax at its
    log-probability, and the step's counters reach /metrics."""
    runner = ModelRunner(_engine_config())
    config = dataclasses.replace(runner.config, num_kv_blocks=52)
    prompts = _seqs([300, 150, 330], seed=12)
    preempted = []

    async def go():
        sched = Scheduler(runner, config)
        orig = sched._preempt
        sched._preempt = lambda er: (preempted.append(er.request_id), orig(er))
        got = await _drive(sched, [_request(p, 40) for p in prompts])
        return sched, got

    loop = asyncio.new_event_loop()
    try:
        sched, got = loop.run_until_complete(go())
    finally:
        loop.close()
    assert preempted, "test is vacuous: nothing was preempted"
    for prompt, (toks, lps) in zip(prompts, got):
        assert len(toks) == 40
        want = _reference_logprobs(runner.params, prompt + toks)
        at = np.arange(len(prompt) - 1, len(prompt) + 39)
        np.testing.assert_array_equal(np.argmax(want[at], axis=-1), toks)
        np.testing.assert_allclose(lps, want[at, toks], atol=F32_ATOL)
    text = sched.registry.render()
    rows = {ln.split(" ")[0]: float(ln.split(" ")[1]) for ln in text.splitlines()
            if ln.startswith("dynamo_") and " " in ln and "{" not in ln}
    assert rows["dynamo_engine_recurrent_state_resets_total"] == 3 + len(preempted)
    kept = rows["dynamo_sparse_attention_kept_tokens_total"]
    assert 0 < kept < rows["dynamo_sparse_attention_context_tokens_total"]
    assert rows["dynamo_sparse_attention_rows_total"] > 0


def test_scopes_in_the_lowered_programs():
    cfg, params = _params(jnp.float32)
    cache = minicpm_sala.init_kv_cache(cfg, 32, PAGE, jnp.float32, num_slots=2)

    def text(s, w):
        args = (jnp.zeros((2, s), jnp.int32), jnp.zeros((2, s), jnp.int32), cache,
                jnp.zeros((2, w), jnp.int32), jnp.zeros((2, s), jnp.int32),
                jnp.ones((2,), jnp.int32))
        return jax.jit(lambda *a: minicpm_sala.forward(params, cfg, *a)).lower(
            *args).as_text(debug_info=True)

    decode, narrow, prefill = text(1, 16), text(1, 4), text(64, 16)
    for scope in ("lightning/lightning_state", "attn/sparse_select",
                  "attn/sparse_attn", "mlp"):
        assert scope in decode, scope
    assert "lightning_scan" not in decode
    # a table no wider than dense_len: nothing selects, only the means' upkeep
    assert "top_k" not in narrow and "top_k" in decode
    # (a tile of queries at a time: the two scopes are inside the loop's)
    for scope in ("lightning/lightning_scan", "attn/", "sparse_select",
                  "sparse_attn"):
        assert scope in prefill, scope
    assert "lightning_state" not in prefill


def test_random_weights_serve_logits_of_a_few_units():
    cfg, params = _params(jnp.float32)
    seq = _seqs([64], seed=1)[0]
    want = _reference_logprobs(params, seq)
    logits_std = np.std(want - want.mean(axis=-1, keepdims=True), axis=-1)
    np.testing.assert_allclose(logits_std.mean(), minicpm_sala.LOGIT_STD, rtol=0.25)
    # the decays are the published layers' (indices 3 and 4 of 8), not the
    # cut's: lambda_h = exp(-s_h (1 - l / 7 + 1e-5))
    decay = np.asarray(params["runs"][1]["log_decay"])
    s_h = 2.0 ** (-8.0 * (np.arange(4) + 1) / 4)
    np.testing.assert_allclose(decay[0], -s_h * (1 - 3 / 7 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(decay[1], -s_h * (1 - 4 / 7 + 1e-5), rtol=1e-6)
    assert minicpm_sala.residual_scale(cfg) == pytest.approx(1.4 / 8 ** 0.5)
