"""The served Falcon-H1 path (a Mamba-2 mixer beside grouped-query
attention in every layer; the mixer's state kept by slot beside the
paged cache, prefill in the chunked form, decode one token a step)
against the benchmark's plain reference,
``benchmark/references/falcon_h1.py`` — the same file the benchmark's
``correct`` is decided by; there is no second copy.

Tiny ``falcon_h1`` shape that keeps the ratios: 10 query heads over 2
kv heads (a query group of 5, heads not a power of two), 6 mixer heads
in 2 groups, every published multiplier.
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
from dynamo_tpu.models import falcon_h1
from dynamo_tpu.ops import ssm
from dynamo_tpu.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import AsyncEngineContext

import served  # noqa: E402  (puts benchmark/ on the path)
from references import falcon_h1 as reference  # noqa: E402

HF = {
    "architectures": ["FalconH1ForCausalLM"], "model_type": "falcon_h1",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 10,
    "num_key_value_heads": 2, "head_dim": 16,
    "mamba_d_ssm": 48, "mamba_n_heads": 6, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_use_mlp": True, "rope_theta": 100000000000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    # the published multipliers of Falcon-H1-34B-Instruct
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "embedding_multiplier": 5.656854249492381,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
}
BLOCK = 8
SLOTS = 4
# float32 on both sides: the two differ in the order of the sums (the
# chunked form against the recurrence, paged against dense attention) and
# in nothing else; differences seen are 4e-6 to 3e-5 in log-probability
# at any position, and the smallest deliberate fault below reads 4e-3
F32_ATOL = 1e-3
# bfloat16 weights, activations, pages and conv window (the SSM state
# float32) against the float32 reference on the same bfloat16 weights:
# the largest difference over the vocabulary at one position. Measured
# on this shape (hidden 64: coarser than the chip's) over the cases
# below: median 0.067-0.074, largest 0.13-0.22; the limits are twice
# that. What tells programs apart is the float32 comparison.
BF16_MEDIAN = 0.15
BF16_ATOL = 0.5


def _cfg(**over):
    return served.cfg_of(HF, **over)


def _params(dtype, seed=7):
    cfg = _cfg()
    return cfg, falcon_h1.init_params(cfg, jax.random.PRNGKey(seed), dtype)


def _reference_logprobs(params, seq):
    """The reference's log-probabilities at every position of ``seq``."""
    return served.reference_logprobs(reference, HF, params, seq)


def Served(cfg, params, dtype, state_dtype=None, fresh=False):
    """48 pages of 8 a slot, every page a slot's own."""
    return served.Served(falcon_h1, cfg, params, dtype, block=BLOCK, width=48,
                         slots=SLOTS, spare=False, state_dtype=state_dtype,
                         fresh=fresh)


_seqs, _serve_case = served.seqs, served.serve_case


CASES = {
    # (a) one prefill, the whole prompt in one padded chunk
    "one_prefill": dict(lengths=[29 + 2], n_decode=2, cuts=[], width=32),
    # (b) prefill in two and in three chunks, boundaries off the scan's
    # chunk of 128 and off the page of 8; the step's width of 192 is no
    # multiple of 128 either
    "two_chunks": dict(lengths=[300 + 2], n_decode=2, cuts=[150], width=192),
    "three_chunks": dict(lengths=[300 + 2], n_decode=2, cuts=[110, 221],
                         width=192),
    # (c) prefill, then 40 decode steps through the state
    "decode_40": dict(lengths=[21 + 40], n_decode=40, cuts=[], width=32),
    # (d) rows of different lengths, a pad row between them, slots that
    # are not the rows' order; the short rows idle while the long prefill
    "batch_unequal": dict(lengths=[5 + 6, 45 + 6, 19 + 6], n_decode=6,
                          cuts=[16, 32], width=16, slots=[2, 0, 3],
                          pad_row=True),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_served_path_equals_reference(case, dtype):
    """Prefill, chunked prefill and decode through the state kept by
    slot give the reference's full-forward log-softmax at every
    position."""
    dt = jnp.dtype(dtype)
    cfg, params = _params(dt)
    c = CASES[case]
    seqs = _seqs(c["lengths"], seed=len(case))
    slots = c.get("slots", list(range(len(seqs))))
    got = _serve_case(Served(cfg, params, dt), seqs, slots, c["n_decode"],
                      c["cuts"], c["width"], c.get("pad_row", False))
    served.assert_close(got, [_reference_logprobs(params, q) for q in seqs],
                        dtype, F32_ATOL, BF16_MEDIAN, BF16_ATOL)


def test_resume_after_preemption_and_slot_reuse():
    """(e) a sequence dropped after 10 decoded tokens and prefilled again
    from position 0 (prompt + the 10), into the slot another sequence
    has used meanwhile, continues as the reference says; (f) the second
    user of a slot starts from zeros, not from what the first left."""
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    a, b = _seqs([17 + 30, 23 + 8], seed=4)
    want_a, want_b = _reference_logprobs(params, a), _reference_logprobs(params, b)
    got = _serve_case(served, [a[:27]], [1], 10, [], 32)[0]      # 17 + 10 tokens
    np.testing.assert_allclose(got, want_a[:27], atol=F32_ATOL)
    # (f) b takes slot 1 while a's state is still in it
    got = _serve_case(served, [b], [1], 8, [], 32)[0]
    np.testing.assert_allclose(got, want_b, atol=F32_ATOL)
    # (e) a resumes in the same slot: prefill of 27 from position 0, 20 more
    got = _serve_case(served, [a], [1], 20, [], 32)[0]
    np.testing.assert_allclose(got, want_a, atol=F32_ATOL)


def test_idle_rows_and_pad_rows_leave_every_other_state_untouched():
    cfg, params = _params(jnp.float32)
    served = Served(cfg, params, jnp.float32)
    a, b = _seqs([20, 20], seed=9)
    served.prefill([(0, a, 0), (3, b, 0)], 32)
    ssm0, conv0 = served.state()
    assert np.abs(ssm0[:, [0, 3]]).min(axis=(0, 2, 3, 4)).max() >= 0
    assert np.abs(ssm0[:, [1, 2]]).max() == 0 and np.abs(ssm0[:, 0]).max() > 0
    # a decode step for slot 0 alone, then a prefill of slot 2 beside a pad row
    served.decode({0: (5, 20)})
    served.prefill([None, (2, b[:7], 0)], 16)
    ssm1, conv1 = served.state()
    np.testing.assert_array_equal(ssm1[:, [1, 3]], ssm0[:, [1, 3]])
    np.testing.assert_array_equal(conv1[:, [1, 3]], conv0[:, [1, 3]])
    assert np.abs(ssm1[:, 0] - ssm0[:, 0]).max() > 0
    assert np.abs(ssm1[:, 2]).max() > 0


@pytest.mark.parametrize("seed,s,chunk,g", [
    (0, 64, 16, 2), (1, 40, 16, 2), (2, 7, 128, 2),
    # lightning attention's form (models/minicpm_sala.py): a group a
    # head, Δ = 1 at a token, a constant decay a head
    (3, 64, 16, 6), (4, 50, 32, 6)])
def test_chunked_scan_is_the_recurrence(seed, s, chunk, g):
    """``ops/ssm.ssd_chunked_scan`` from a given state, with pad positions
    (Δ = 0) inside and at the end of the run, against
    ``ssm_decode_update`` applied token by token."""
    rs = np.random.RandomState(seed)
    b, h, p, n = 2, 6, 8, 16
    x = rs.randn(b, s, h, p).astype(np.float32)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.5), (b, s, h))).astype(np.float32)
    a = -rs.uniform(1, 16, h).astype(np.float32)
    if g == h:
        dt[:] = 1.0
        a = -(2.0 ** (-8.0 * (np.arange(h) + 1) / h)).astype(np.float32)
    dt[0, s // 2:] = 0.0          # row 0: its second half is padding
    dt[1, 3] = 0.0
    bm = rs.randn(b, s, g, n).astype(np.float32)
    cm = rs.randn(b, s, g, n).astype(np.float32)
    d = rs.randn(h).astype(np.float32)
    h0 = rs.randn(b, h, p, n).astype(np.float32)
    # the scan takes and returns records (``state_to_record``)
    y, h1 = ssm.ssd_chunked_scan(
        *map(jnp.asarray, (x, dt, a, bm, cm, d)),
        ssm.state_to_record(jnp.asarray(h0), h // g), chunk)
    h1 = ssm.record_to_state(h1, p)
    state, ys = jnp.asarray(h0), []
    for t in range(s):
        y_t, state = ssm.ssm_decode_update(
            *map(jnp.asarray, (x[:, t], dt[:, t], a, bm[:, t], cm[:, t], d)), state)
        ys.append(y_t)
    np.testing.assert_allclose(y, np.stack(ys, 1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h1, state, rtol=1e-4, atol=1e-4)
    # row 0's state stopped at its last valid token
    half = jnp.asarray(h0[:1])
    for t in range(s // 2):
        _, half = ssm.ssm_decode_update(
            *map(jnp.asarray, (x[:1, t], dt[:1, t], a, bm[:1, t], cm[:1, t], d)), half)
    np.testing.assert_allclose(h1[:1], half, rtol=1e-4, atol=1e-4)


def _wrong(fault, monkeypatch):
    """A served program with one deliberate fault: (cfg, params, kwargs
    of Served)."""
    cfg, params = _params(jnp.float32)
    layers = dict(params["layers"])
    kw = {}
    if fault == "bf16_state":
        kw["state_dtype"] = jnp.bfloat16
    elif fault == "no_conv_bias":
        layers["conv_b"] = jnp.zeros_like(layers["conv_b"])
    elif fault == "no_d_skip":
        layers["D"] = jnp.zeros_like(layers["D"])
    elif fault == "gate_after_norm":
        monkeypatch.setattr(
            falcon_h1, "_gated_norm",
            lambda y, z, w, g, eps: falcon_h1._grouped_rms_norm(y, w, g, eps)
            * jax.nn.silu(z))
    elif fault == "no_key_multiplier":
        cfg = dataclasses.replace(cfg, key_multiplier=1.0)
    return cfg, {**params, "layers": layers}, kw


@pytest.mark.parametrize("fault", ["bf16_state", "no_conv_bias", "no_d_skip",
                                   "gate_after_norm", "no_key_multiplier"])
def test_reference_tells_wrong_programs_apart(fault, monkeypatch):
    """Each of five wrong programs fails the float32 limit, against the
    reference run on the true weights and configuration."""
    _, params = _params(jnp.float32)
    seq = _seqs([24 + 40], seed=6)[0]
    want = _reference_logprobs(params, seq)
    cfg, wrong_params, kw = _wrong(fault, monkeypatch)
    got = _serve_case(Served(cfg, wrong_params, jnp.float32, fresh=True, **kw),
                      [seq], [0], 40, [], 32)[0]
    print(fault, np.abs(got - want).max())
    assert np.abs(got - want).max() > 3 * F32_ATOL


def test_a_fresh_program_is_traced_again_after_a_cached_run(monkeypatch):
    """What the fault cases of every reference file stand on: after the
    sound program has run and is kept (``served.program``), a module
    patched into a wrong one is what a ``fresh`` driver runs; the same
    driver without ``fresh`` would have run the kept program and told
    nothing apart."""
    cfg, params = _params(jnp.float32)
    seq = _seqs([24 + 8], seed=6)[0]

    def run(**kw):
        return _serve_case(Served(cfg, params, jnp.float32, **kw), [seq], [0],
                           8, [], 32)[0]

    sound = run()
    assert served.program(falcon_h1, cfg) is served.program(falcon_h1, cfg)
    kept = len(served._PROGRAMS)
    monkeypatch.setattr(falcon_h1, "_gated_norm",
                        lambda y, z, w, g, eps: jnp.zeros_like(y))
    np.testing.assert_array_equal(run(), sound)       # the kept program
    patched = run(fresh=True)
    assert np.abs(patched - sound).max() > 3 * F32_ATOL
    assert len(served._PROGRAMS) == kept               # and it was not kept


def _engine_config(**over):
    kw = dict(model=_cfg(), max_batch_size=SLOTS, max_model_len=128,
              kv_block_size=BLOCK, num_kv_blocks=64, dtype="float32",
              prefill_buckets=[16, 64], seed=11, max_prefill_batch=2)
    kw.update(over)
    return EngineConfig(**kw)


@pytest.mark.parametrize("setting,path", [
    (dict(spec_ngram_tokens=2), "spec_ngram_tokens"),
    (dict(sp_size=2, prefill_buckets=[16, 64]), "sp_size"),
    (dict(pp_size=2), "pp_size"),
    (dict(tp_size=2), "tp_size"),
    (dict(host_kv_blocks=8), "host_kv_blocks"),
    (dict(prefix_pull=True), "prefix_pull"),
    (dict(multi_step_decode=4), "multi_step_decode"),
    (dict(decode_pipeline_depth=2), "decode_pipeline_depth"),
])
def test_paths_that_cannot_carry_the_state_are_refused_at_start_up(setting, path):
    with pytest.raises(ValueError, match=rf"{path} is refused for the falcon_h1 "
                                         "family.*recurrent state"):
        ModelRunner(_engine_config(**setting))


def test_draft_speculation_is_refused_at_start_up(tmp_path):
    with pytest.raises(ValueError, match="spec_draft_model is refused for the "
                                         "falcon_h1 family"):
        ModelRunner(_engine_config(spec_draft_model=str(tmp_path),
                                   spec_draft_tokens=2))


@pytest.fixture(scope="module")
def runner():
    return ModelRunner(_engine_config())


def test_remote_prefill_migration_and_block_transfer_are_refused(runner):
    config = runner.config

    async def go():
        with pytest.raises(ValueError, match="remote_prefill is refused"):
            Scheduler(runner, config, disagg=object())
        sched = Scheduler(runner, config)
        with pytest.raises(ValueError, match="migration is refused"):
            sched.admit_migrated(_request([1, 2, 3], 4), [1, 2, 3], [])
    asyncio.new_event_loop().run_until_complete(go())
    with pytest.raises(ValueError, match="refused for the falcon_h1 family"):
        runner.gather_blocks([0])
    with pytest.raises(ValueError, match="refused for the falcon_h1 family"):
        runner.scatter_blocks([0], np.zeros((2, 1, 8, 2, 16)), np.zeros((2, 1, 8, 2, 16)))


def test_unknown_recurrent_trunk_is_refused_by_name():
    """A ``model_type`` with recurrent-layer keys and no family here must
    not fall through to llama and serve the attention half alone."""
    hf = {**HF, "model_type": "some_other_hybrid"}
    with pytest.raises(NotImplementedError, match="some_other_hybrid.*mamba_"):
        ModelConfig.from_hf_config(hf)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        models.resolve(dataclasses.replace(_cfg(), model_family=""))
    assert models.resolve(_cfg()) is falcon_h1
    with pytest.raises(NotImplementedError, match="mamba_norm_before_gate"):
        ModelConfig.from_hf_config({**HF, "mamba_norm_before_gate": True})


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


def test_engine_streams_equal_reference_through_preemption(runner):
    """Through the scheduler, the allocator and ``ModelRunner.step``: a
    cache too small for three sequences preempts one, which resumes by
    re-prefilling from position 0; every emitted token is the reference's
    argmax at its log-probability. A prefix hit is blanked and counted,
    and no block is registered for reuse."""
    config = dataclasses.replace(runner.config, num_kv_blocks=14)
    prompts = _seqs([20, 18, 21], seed=12)
    preempted = []

    async def go():
        sched = Scheduler(runner, config)
        orig = sched._preempt
        sched._preempt = lambda er: (preempted.append(er.request_id), orig(er))
        # a block another sequence could match: registered by hand, since
        # the family registers none itself
        from dynamo_tpu.tokens import compute_block_hashes
        h = compute_block_hashes(prompts[0], BLOCK)
        bid = sched.allocator.allocate_block()
        sched.allocator.register_complete(bid, h[0], None)
        sched.allocator.free_blocks([bid])
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
            if ln.startswith("dynamo_engine_") and " " in ln}
    assert rows["dynamo_engine_prefix_hits_blanked_total"] >= 1
    assert rows["dynamo_engine_recurrent_state_resets_total"] == 3 + len(preempted)
    # float32 engine: the SSM state and the conv window, 4 bytes each
    m = config.model
    assert rows["dynamo_engine_recurrent_state_bytes"] == m.num_layers * SLOTS * 4 * (
        m.mamba_n_heads * m.mamba_d_head * m.mamba_d_state
        + (m.mamba_d_conv - 1) * falcon_h1.conv_dim(m))
    # only the hand-made block was ever registered
    assert len(sched.allocator.by_hash) <= 1


def test_scopes_in_the_lowered_programs():
    """``ssm`` holds ``ssm_conv`` and ``ssm_state`` in the decode program,
    ``ssm_scan`` in the prefill program; ``attn`` and ``mlp`` beside it."""
    cfg, params = _params(jnp.float32)
    cache = falcon_h1.init_kv_cache(cfg, 8, BLOCK, jnp.float32, num_slots=2)

    def text(s):
        args = (jnp.zeros((2, s), jnp.int32), jnp.zeros((2, s), jnp.int32), cache,
                jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, s), jnp.int32),
                jnp.ones((2,), jnp.int32))
        return jax.jit(lambda *a: falcon_h1.forward(params, cfg, *a)).lower(
            *args).as_text(debug_info=True)

    decode, prefill = text(1), text(16)
    for scope in ("ssm/ssm_conv", "ssm/ssm_state", "attn", "mlp"):
        assert scope in decode, scope
    assert "ssm_scan" not in decode
    assert "ssm/ssm_scan" in prefill and "ssm_state" not in prefill


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (the layer
    scan's body, a nested jit), not a kernel's own body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


def test_decode_program_donates_the_records_and_updates_them_in_place():
    """The decode program hands the SSM records to the kernel as its input
    and its output, and nothing else makes an array of their shape: no
    slice of a layer, no ``.at[li, :b].set``. Compiled with the cache
    donated, the records' argument is aliased to the records' result.
    (What the compiler then copies is a backend's business: off the TPU
    the interpreter's loop over the grid carries the buffer and copies
    it; for the v5e, ``tests/test_chip_compile.py`` asserts that the
    compiled program at the benchmark's size has no copy of the state.)"""
    cfg, params = _params(jnp.float32)
    cache = falcon_h1.init_kv_cache(cfg, 8, BLOCK, jnp.float32, num_slots=SLOTS)
    shape = cache[0].state.shape
    args = (jnp.zeros((SLOTS, 1), jnp.int32), jnp.zeros((SLOTS, 1), jnp.int32),
            cache, jnp.zeros((SLOTS, 8), jnp.int32),
            jnp.zeros((SLOTS, 1), jnp.int32), jnp.ones((SLOTS,), jnp.int32))

    def step(*a):
        return falcon_h1.forward(params, cfg, *a)

    eqns = list(_equations(jax.make_jaxpr(step)(*args).jaxpr))
    makers = {e.primitive.name for e in eqns
              if any(getattr(v.aval, "shape", None) == shape for v in e.outvars)}
    assert makers == {"pallas_call", "scan"}, makers      # scan: the carry
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"
               and e.params["name"] == "ssm_decode_step"]
    assert len(kernels) == 1                              # one, in the scan's body
    # operand 5 (after the two prefetched scalars) is result 1; the
    # equation's inputs start with the grid's bound, the number of live rows
    assert tuple(kernels[0].params["input_output_aliases"]) == ((5, 1),)
    assert kernels[0].invars[1 + 5].aval.shape == shape
    assert kernels[0].outvars[1].aval.shape == shape

    text = jax.jit(step, donate_argnums=(2,)).lower(*args).compile().as_text()
    header = text.split("\n", 1)[0]
    layout = header.split("entry_computation_layout={(", 1)[1].split(", ")
    param = next(i for i, t in enumerate(layout)
                 if t.startswith("f32[%s]" % ",".join(map(str, shape))))
    assert f"({param}, {{}}, may-alias)" in header, header[:400]


def test_random_weights_serve_logits_of_a_few_units():
    """``init_params`` divides each matrix by the multipliers beside it:
    the served logits spread by ``LOGIT_STD``, where plain fan-in weights
    would leave every log-probability at −ln V."""
    cfg, params = _params(jnp.float32)
    seq = _seqs([32], seed=1)[0]
    want = _reference_logprobs(params, seq)
    logits_std = np.std(want - want.mean(axis=-1, keepdims=True), axis=-1)
    np.testing.assert_allclose(logits_std.mean(), falcon_h1.LOGIT_STD, rtol=0.25)
    lay = params["layers"]
    a = -np.exp(np.asarray(lay["A_log"]))
    assert a.min() >= -16.0 - 1e-3 and a.max() <= -1.0 + 1e-3
    dt = np.log1p(np.exp(np.asarray(lay["dt_bias"])))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    np.testing.assert_array_equal(np.asarray(lay["D"]), 1.0)
    assert np.abs(np.asarray(lay["conv_b"])).max() > 0
