"""The step's per-pass inputs cross to the device as one packed int32
array (engine/step_inputs.py): the layout bit for bit, the count of
host→device puts a ``step()`` makes, and ``step()`` against the same
forward and sampling tail called with an array a field."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model_runner as mr
from dynamo_tpu.engine import step_inputs
from dynamo_tpu.engine.config import EngineConfig, ModelConfig

FLOATS = step_inputs._FLOAT


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _awkward_fields(b, s, w, rng):
    """Values a careless cast would lose."""
    f = np.array([-0.0, np.inf, 1e-45, 1.0, -np.inf, 0.7, 3.4e38, 1e-40],
                 np.float32)
    fields = {name: np.roll(f, i)[np.arange(b) % f.size]
              for i, name in enumerate(FLOATS)}
    fields["top_p"][0] = np.float32(1e-45)          # sub-normal
    fields["temperature"][1] = np.float32(-0.0)
    fields.update(
        context_lens=rng.integers(1, 2 ** 31 - 1, b).astype(np.int32),
        last_idx=rng.integers(0, s, b).astype(np.int32),
        sample_slots=rng.permutation(b).astype(np.int32),
        counters=rng.integers(0, 2 ** 31 - 1, b).astype(np.int32),
        commit=(np.arange(b) % 3 == 0),              # mixed
        top_k=rng.integers(0, 50, b).astype(np.int32),
    )
    keys = rng.integers(2 ** 31, 2 ** 32, (b, 2), dtype=np.uint64).astype(
        np.uint32)
    keys[0] = (0xFFFFFFFF, 0x80000000)
    seqs = dict(
        tokens=rng.integers(0, 2 ** 31 - 1, (b, s)).astype(np.int32),
        positions=rng.integers(0, 4096, (b, s)).astype(np.int32),
        block_tables=rng.integers(0, 2 ** 20, (b, w)).astype(np.int32),
        slot_mapping=np.where(rng.random((b, s)) < 0.4, -1,
                              rng.integers(0, 2 ** 20, (b, s))).astype(np.int32),
        targets=rng.integers(0, 2 ** 17, (b, s)).astype(np.int32),
    )
    return fields, keys, seqs


@pytest.mark.parametrize("flags", [(True, False, True), (False, True, False)])
@pytest.mark.parametrize("w", [4, 24])
@pytest.mark.parametrize("s", [1, 5])
def test_layout_round_trips_bit_for_bit(s, w, flags):
    b = 6
    fields, keys, seqs = _awkward_fields(b, s, w, np.random.default_rng(s * w))
    buf = step_inputs.pack(
        seqs["tokens"], seqs["positions"], seqs["block_tables"],
        seqs["slot_mapping"], seqs["targets"], keys=keys,
        want_top=flags[0], want_prompt=flags[1], want_greedy=flags[2],
        **fields)
    assert buf.dtype == np.int32
    assert buf.shape == (b, step_inputs.F + w + 4 * s)
    got = jax.jit(step_inputs.unpack, static_argnums=1)(buf, s)

    for name, want in seqs.items():
        assert np.asarray(getattr(got, name)).dtype == np.int32, name
        np.testing.assert_array_equal(getattr(got, name), want, err_msg=name)
    for name in ("context_lens", "last_idx", "sample_slots"):
        np.testing.assert_array_equal(getattr(got, name), fields[name], name)
    for name in FLOATS + ("top_k", "counters"):
        have = np.asarray(getattr(got.samp, name))
        assert have.dtype == fields[name].dtype, name
        np.testing.assert_array_equal(_bits(have), _bits(fields[name]), name)
    assert np.asarray(got.samp.keys).dtype == np.uint32
    np.testing.assert_array_equal(got.samp.keys, keys)
    assert np.asarray(got.commit).dtype == np.bool_
    np.testing.assert_array_equal(got.commit, fields["commit"])
    for name, want in zip(("want_top", "want_prompt", "want_greedy"), flags):
        have = np.asarray(getattr(got, name))
        assert have.dtype == np.bool_ and have.shape == (), name
        assert bool(have) is want, name


def test_pack_fills_the_defaults_and_makes_a_fresh_buffer():
    b, s, w = 3, 1, 4
    z = np.zeros((b, s), np.int32)
    btab = np.arange(b * w, dtype=np.int32).reshape(b, w)
    kw = dict(keys=np.zeros(2, np.uint32), want_top=True, context_lens=1,
              last_idx=0, top_k=0, temperature=[0.0, 0.5, 1.0], top_p=1.0,
              min_p=None, commit=None)
    one = step_inputs.pack(z, z, btab, z - 1, **kw)
    two = step_inputs.pack(z, z, btab, z - 1, **kw)
    assert one is not two and not np.shares_memory(one, btab)
    np.testing.assert_array_equal(one, two)
    got = step_inputs.unpack(jnp.asarray(one), s)
    np.testing.assert_array_equal(got.samp.repetition_penalty, np.ones(b))
    np.testing.assert_array_equal(got.samp.min_p, np.zeros(b))
    np.testing.assert_array_equal(got.samp.counters, np.arange(b))
    np.testing.assert_array_equal(got.sample_slots, np.arange(b))
    assert not np.asarray(got.commit).any()
    assert bool(got.want_top) and not bool(got.want_prompt)
    with pytest.raises(KeyError, match="top_p"):
        step_inputs.pack(z, z, btab, z - 1, **{**kw, "top_p": None})
    np.testing.assert_array_equal(got.samp.temperature, [0.0, 0.5, 1.0])
    np.testing.assert_array_equal(got.targets, z)
    np.testing.assert_array_equal(got.slot_mapping, z - 1)


# ---------------------------------------------------------------------
# a tiny runner: the count of puts, and step() against the per-array call
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def runner():
    cfg = EngineConfig(
        model=ModelConfig(vocab_size=256, hidden_size=32,
                          intermediate_size=64, num_layers=2,
                          num_heads=2, num_kv_heads=1),
        max_batch_size=4, max_model_len=64, kv_block_size=8,
        num_kv_blocks=32, dtype="float32", allow_random_weights=True,
        prefill_buckets=[8, 16])
    return mr.ModelRunner(cfg)


def _step_args(b, s, w, *, sampled=False):
    rng = np.random.default_rng(b * 131 + s)
    tokens = rng.integers(1, 256, (b, s)).astype(np.int32)
    positions = np.tile(np.arange(s, dtype=np.int32), (b, 1))
    btab = np.zeros((b, w), np.int32)
    btab[:, :2] = 1 + 2 * np.arange(b)[:, None] + np.arange(2)
    slots = btab[:, :1] * 8 + positions      # s <= 8: one block
    args = (tokens, positions, btab, slots.astype(np.int32),
            np.full(b, s, np.int32), np.full(b, s - 1, np.int32))
    if not sampled:
        return args + (np.zeros(b, np.float32), np.zeros(b, np.int32),
                       np.ones(b, np.float32)), dict(
            seed_keys=np.zeros((b, 2), np.uint32),
            counters=np.zeros(b, np.int32),
            sample_slots=np.arange(b, dtype=np.int32),
            commit=np.ones(b, bool))
    # greedy and sampled rows, every filter and penalty in play
    return args + (np.array([0.0, 0.9, 1.3, 0.7], np.float32)[:b],
                   np.array([0, 40, 0, 5], np.int32)[:b],
                   np.array([1.0, 0.9, 1.0, 0.5], np.float32)[:b]), dict(
        min_p=np.array([0.0, 0.0, 0.05, 0.0], np.float32)[:b],
        presence_penalty=np.array([0.0, 0.5, 0.0, -0.3], np.float32)[:b],
        frequency_penalty=np.array([0.3, 0.0, 0.2, 0.0], np.float32)[:b],
        repetition_penalty=np.array([1.0, 1.2, 1.0, 0.8], np.float32)[:b],
        seed_keys=rng.integers(0, 2 ** 32, (b, 2), dtype=np.uint64).astype(
            np.uint32),
        counters=np.array([3, 0, 7, 2 ** 31 - 1], np.int32)[:b],
        sample_slots=np.arange(b, dtype=np.int32)[::-1].copy(),
        commit=np.array([True, False, True, True])[:b],
        want_top=True)


@pytest.mark.parametrize("s", [1, 8], ids=["decode", "prefill"])
def test_a_step_makes_one_put_and_no_array_of_its_own(runner, monkeypatch, s):
    b = runner.config.max_batch_size if s == 1 else 2
    w = (runner.config.kv_width_buckets()[0] if s == 1
         else runner.config.blocks_per_seq)
    args, kw = _step_args(b, s, w)
    runner.step(*args, **kw)            # compiled; not what is counted

    calls = {"device_put": 0, "asarray": 0, "array": 0}
    spans = []

    def counting(name, real):
        def wrapper(*a, **k):
            calls[name] += 1
            return real(*a, **k)
        return wrapper

    monkeypatch.setattr(mr.jax, "device_put",
                        counting("device_put", jax.device_put))
    monkeypatch.setattr(mr.jnp, "asarray", counting("asarray", jnp.asarray))
    monkeypatch.setattr(mr.jnp, "array", counting("array", jnp.array))
    from dynamo_tpu.telemetry import flight
    real_span = flight.span
    monkeypatch.setattr(
        flight, "span",
        lambda name, **stats: (spans.append((name, stats)),
                               real_span(name, **stats))[1])
    # an implicit transfer (a numpy array handed to the jitted program)
    # raises; the one explicit put does not
    with jax.transfer_guard_host_to_device("disallow"):
        out = runner.step(*args, **kw)
    jax.block_until_ready(out)
    assert calls == {"device_put": 1, "asarray": 0, "array": 0}
    name = "dispatch.decode" if s == 1 else "dispatch.prefill"
    assert [st for n, st in spans if n == name] == [
        {"key": f"b{b}_s{s}_w{w}", "arrays": 1}]


def test_the_guard_catches_a_host_array_handed_to_the_program(runner):
    """What the test above leans on: under the guard the jitted step
    refuses a numpy array, so a field that slipped past the packer
    would raise there."""
    b, w = runner.config.max_batch_size, runner.config.kv_width_buckets()[0]
    args, kw = _step_args(b, 1, w)
    buf = step_inputs.pack(
        *args[:4], keys=kw["seed_keys"], want_top=False,
        context_lens=args[4], last_idx=args[5], temperature=args[6],
        top_k=args[7], top_p=args[8])
    prev = jnp.zeros(b, jnp.int32)   # (the step before's tokens)
    lowered = runner._decode_step.lower(
        runner.params, *runner.kv_cache, *runner.sample_state, buf, prev)
    compiled = lowered.compile()
    state = jax.tree.map(jnp.copy, (runner.params, *runner.kv_cache,
                                    *runner.sample_state))
    with jax.transfer_guard_host_to_device("disallow"):
        with pytest.raises(Exception, match="[Dd]isallowed host-to-device"):
            compiled(*state, buf, prev)


def _per_array_reference(runner, args, kw):
    """The same forward, head and sampling tail with an array a field,
    as the step took them before they were packed; on copies of the
    runner's state."""
    cfg = runner.config.model
    forward, head = runner._make_forward(counted=False)
    (tokens, positions, btab, slots, ctx, last_idx, temp, top_k, top_p) = args
    b = tokens.shape[0]
    samp = mr.SamplingParams(
        temperature=jnp.asarray(temp, jnp.float32),
        top_k=jnp.asarray(top_k, jnp.int32),
        top_p=jnp.asarray(top_p, jnp.float32),
        min_p=jnp.asarray(kw.get("min_p", np.zeros(b)), jnp.float32),
        presence_penalty=jnp.asarray(
            kw.get("presence_penalty", np.zeros(b)), jnp.float32),
        frequency_penalty=jnp.asarray(
            kw.get("frequency_penalty", np.zeros(b)), jnp.float32),
        repetition_penalty=jnp.asarray(
            kw.get("repetition_penalty", np.ones(b)), jnp.float32),
        keys=jnp.asarray(kw["seed_keys"], jnp.uint32),
        counters=jnp.asarray(kw["counters"], jnp.int32))

    @jax.jit
    def run(params, k, v, counts, seen, bias):
        hidden, _ = forward(params, (k, v), jnp.asarray(tokens),
                            jnp.asarray(positions), jnp.asarray(btab),
                            jnp.asarray(slots), jnp.asarray(ctx))
        logits = head(hidden[jnp.arange(b), jnp.asarray(last_idx)], params)
        return mr._sample_and_logprobs(
            cfg, runner.mesh, logits, samp, counts, seen, bias,
            jnp.asarray(kw["sample_slots"]), jnp.asarray(kw["commit"]),
            jnp.asarray(bool(kw.get("want_top", True))))

    return run(runner.params, *runner.kv_cache, *runner.sample_state)


@pytest.mark.parametrize("s", [1, 8], ids=["decode", "prefill"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_step_matches_the_per_array_call(runner, s, sampled):
    b = 4
    w = (runner.config.kv_width_buckets()[0] if s == 1
         else runner.config.blocks_per_seq)
    args, kw = _step_args(b, s, w, sampled=sampled)
    # a logit_bias row and a history for the penalties to act on
    runner.set_sample_row(0, [5, 6, 7], [9, 9, 11], logit_bias={3: 4.0, 9: -50})
    runner.set_sample_row(1, [1, 2], [200, 200, 200, 17])
    want = _per_array_reference(runner, args, kw)
    counts_before = np.asarray(runner.sample_state[0]).copy()
    toks, lps, top_vals, top_ids, *_ = runner.step(*args, **kw)

    np.testing.assert_array_equal(toks, want[0])
    np.testing.assert_array_equal(_bits(lps), _bits(want[1]))
    np.testing.assert_array_equal(_bits(top_vals), _bits(want[2]))
    np.testing.assert_array_equal(top_ids, want[3])
    np.testing.assert_array_equal(runner.sample_state[0], want[4])
    committed = np.asarray(runner.sample_state[0]).sum() - counts_before.sum()
    assert committed == int(np.sum(kw["commit"]))
