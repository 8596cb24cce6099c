"""The dots3-note family's wrong programs, its reference's controls, the
shares of an expert layer, what the shared latent code still lowers to
for the other latent families, and the family's surface and refusals
(``tests/test_dots3_reference.py`` holds the served path against the
reference; two files so that two workers share them)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import models
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.models import afmoe, deepseek, dots3, mixtral, trunk
from dynamo_tpu.ops import latent_select

import served
from dots3_tiny import (FULL, HF, PAGE, SWA, TOPK, WINDOW, WRONG, Served,
                        _cfg, _params, _reference_logprobs, _seqs,
                        _serve_case, reference)


# ---------- wrong programs ----------

def _wrong_topk(cfg):
    return dataclasses.replace(cfg, index_topk=TOPK // 2)


def _wrong_window(delta):
    return lambda cfg: dataclasses.replace(
        cfg, sliding_window=cfg.sliding_window + delta)


WRONG_PROGRAMS = {
    "half_topk": _wrong_topk,
    "window_short": _wrong_window(-1),
    "window_long": _wrong_window(+1),
    "no_rescale": lambda cfg: dataclasses.replace(cfg, mla_lora_rescale=False),
    "one_theta": lambda cfg: dataclasses.replace(
        cfg, swa_rope_theta=cfg.rope_theta),
}


@pytest.mark.parametrize("fault", list(WRONG_PROGRAMS))
def test_a_wrong_program_is_told_apart(fault):
    """Served with half the pick, a window one key short or long, the
    latent norms' constants left out or the full layers' rope base in the
    window layers, the same weights read far outside the float32 limit."""
    cfg, params = _params(jnp.float32)
    served = Served(WRONG_PROGRAMS[fault](cfg), params, jnp.float32,
                    fresh=True)
    seq = _seqs([100 + 30], seed=len("three_chunks"))[0]
    got = _serve_case(served, [seq], [0], 30, [40, 77], 64)[0]
    assert np.abs(got - _reference_logprobs(params, seq)).max() > WRONG


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_the_references_controls_compute_something_else(control):
    """Each control the limits were set against (``build(lower=...)``)
    moves the reference's own log-probabilities far outside the float32
    limit, and only past the threshold it concerns."""
    _, params = _params(jnp.float32)
    seq = _seqs([120], seed=9)[0]
    sound = _reference_logprobs(params, seq)
    other = _reference_logprobs(params, seq, lower=(control,))
    d = np.abs(other - sound).max(axis=1)
    assert d.max() > WRONG
    first = {"half_topk": TOPK // 2, "no_relu": TOPK, "window_short": WINDOW - 1,
             "window_long": WINDOW, "no_gate": 0}[control]
    assert d[:first].max(initial=0.0) < 1e-5 and d[first:first + 8].max() > 1e-4


def test_the_second_pair_of_limits_starts_past_twice_the_pick():
    """The harness's probes (2216 tokens at most) are held to the
    module's one pair; ``scripts/long_probes.py`` holds a probe past
    twice ``index_topk`` to the wider pair."""
    hf = {"index_topk": 2048}
    short = (reference.LOGPROB_ATOL, reference.LOGPROB_MEAN_ATOL)
    assert reference.limits_for(hf, 2216) == reference.limits_for(hf, 4096) == short
    long = reference.limits_for(hf, 9416)
    assert long == reference.limits_for(hf, 4097)
    assert long[0] >= short[0] and long[1] > short[1]


def test_the_reference_refuses_a_control_it_does_not_have():
    with pytest.raises(ValueError, match="lower="):
        reference.build(HF, 8, 8, lower=("weights",))


# ---------- the shares add up ----------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """For a layer of 16 experts: the routed parts of the sixteen ranks
    that hold one expert each, plus the shared expert counted once, are
    the uncut reference's whole layer; in the reference given the
    shares, and in the program (``moe_mlp(held=...)``) against the same
    uncut reference."""
    cfg, params = _params(jnp.float32)
    lp = {k: v[1] for k, v in params["moe"].items()}          # one layer
    x = jax.random.normal(jax.random.PRNGKey(5), (48, cfg.hidden_size),
                          jnp.float32)
    whole, shared = reference.expert_layer(HF)(x, lp)
    want = np.asarray(whole + shared)
    parts, got, stats = [], [], []
    for rank in range(16):
        hf = {**HF, "n_routed_experts": 1,
              "expert_share": {"of_experts": 16, "rank": rank}}
        mine = {k: (v[rank:rank + 1] if k in mixtral.EXPERT_STACKS else v)
                for k, v in lp.items()}
        routed, again = reference.expert_layer(hf)(x, mine)
        np.testing.assert_allclose(again, shared, atol=1e-6)   # every rank alike
        parts.append(np.asarray(routed))
        y, s = mixtral.moe_mlp(
            x, lp["router"], *(lp[k][rank:rank + 1]
                               for k in mixtral.EXPERT_STACKS),
            cfg.num_experts_per_tok, scoring=cfg.moe_scoring_func,
            norm_topk=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor,
            router_bias=lp["router_bias"], held=(rank, 1))
        np.testing.assert_allclose(y, parts[-1], atol=1e-4)
        got.append(np.asarray(y))
        stats.append(np.asarray(s))
    assert sum(np.abs(p).max() > 1e-3 for p in parts) >= 12    # most are picked
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), want, atol=1e-5)
    np.testing.assert_allclose(sum(got) + np.asarray(shared), want, atol=2e-4)
    picks = x.shape[0] * cfg.num_experts_per_tok
    assert all(s[1] == picks for s in stats)
    assert sum(s[2] for s in stats) == picks


# ---------- the other latent families are as they were ----------

@pytest.mark.parametrize("hc_mult", [1], ids=["moonlight"])
def test_the_latent_projections_lower_as_before_for_the_other_families(hc_mult):
    """``deepseek.mla_project`` with the constants at 1.0 and
    ``mla_attention`` without a window are what Moonlight's trunk (and
    Xing4's, the same two calls under ``hc_mult``) ran before this family: scaling by an explicit 1.0 and a
    window no context reaches change the lowered text (so the defaults
    multiply and mask nothing), and the logits not at all."""
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, kv_lora_rank=32, q_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=24, n_shared_experts=1,
        first_k_dense_replace=1, attention_impl="xla", hc_mult=hc_mult,
        model_family="deepseek" if hc_mult > 1 else "")
    assert models.resolve(cfg) is deepseek
    params = deepseek.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    t = 10
    tokens = jnp.arange(3, 3 + t, dtype=jnp.int32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    table = jnp.arange(4, dtype=jnp.int32)[None]

    def lowered_and_logits():
        def forward(cache):
            return deepseek.forward(params, cfg, tokens, pos, cache, table,
                                    pos, jnp.asarray([t], jnp.int32))[0]

        cache = deepseek.init_kv_cache(cfg, 4, PAGE, jnp.float32)
        return (jax.jit(forward).lower(cache).as_text(),
                np.asarray(forward(cache)))

    as_now, logits = lowered_and_logits()
    assert "multiply" in as_now
    project, attend = deepseek.mla_project, deepseek.mla_attention
    try:
        deepseek.mla_project = lambda *a, **kw: project(
            *a, **{**kw, "q_scale": 2.0, "kv_scale": 1.0})
        text, other = lowered_and_logits()
        assert text != as_now and np.abs(other - logits).max() > 1e-3
        deepseek.mla_project = project
        deepseek.mla_attention = lambda *a, **kw: attend(
            *a, **{**kw, "sliding_window": 4})
        text, other = lowered_and_logits()
        assert text != as_now and np.abs(other - logits).max() > 1e-3
        deepseek.mla_attention = lambda *a, **kw: attend(
            *a, **{**kw, "sliding_window": None})
        text, same = lowered_and_logits()
        assert text == as_now
        np.testing.assert_array_equal(same, logits)
    finally:
        deepseek.mla_project, deepseek.mla_attention = project, attend


# ---------- the family's surface and what it refuses ----------

def test_the_published_config_reaches_the_family():
    cfg = ModelConfig.from_hf_config(HF)
    assert cfg.model_family == "dots3"
    assert models.resolve(cfg) is dots3        # not deepseek's shape rule
    assert cfg.layer_types == tuple(HF["layer_types"])
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (32, 4, 16)
    assert (cfg.sliding_window, cfg.swa_num_heads, cfg.swa_kv_lora_rank,
            cfg.swa_q_lora_rank, cfg.swa_qk_nope_head_dim,
            cfg.swa_qk_rope_head_dim, cfg.swa_v_head_dim,
            cfg.swa_rope_theta) == (17, 2, 32, 32, 24, 8, 16, 50000.0)
    assert cfg.mla_lora_rescale and cfg.attention_gate == "headwise"
    assert (cfg.num_experts, cfg.experts_of, cfg.topk_method) == (16, 0, "noaux_tc")
    window = dots3.kind_cfg(cfg, SWA)
    assert (window.num_heads, window.kv_lora_rank, window.rope_theta) == \
        (2, 32, 50000.0)
    assert dots3.lora_rescale(cfg) == (2 ** 0.5, 2.0)
    assert dots3.lora_rescale(window) == (2 ** 0.5, 2 ** 0.5)
    prefix, periods = trunk.period_layout(cfg, (FULL, SWA))
    assert prefix == [(FULL, 0, 0)]
    assert [p.tolist() for p in periods] == [[1, 2], [1, 1], [0, 3], [3, 3]]
    assert dots3.SEQUENCE_STATE.window_pool and dots3.SEQUENCE_STATE.private
    assert dots3.SEQUENCE_STATE.slots
    # a page shape a kind: a full layer's page a token's row whole (the
    # latent of 16 and the rope key of 8, each in whole lanes), a window
    # layer's latents of 32 and rope keys apart; the indexer's keys by
    # slot, whole key blocks of positions a slot (2 slots of sequences
    # of up to 100 tokens: one block of 1024), counted with what does
    # not grow with the context
    k_side, v_side = jax.eval_shape(
        lambda: dots3.init_kv_cache(cfg, 9, PAGE, jnp.bfloat16, num_slots=2,
                                    window_blocks=5, max_len=100))
    assert k_side.full.shape == (3, 9, 1, PAGE, 256)
    assert k_side.window.shape == (6, 5, 1, PAGE, 128)
    assert (k_side.index, v_side.full) == ((), ())
    assert v_side.index.shape == (3, 2, 1024, 128)
    assert v_side.window.shape == (6, 5, 1, PAGE, 128)
    assert v_side.rest == (v_side.window, v_side.index)
    assert k_side.dtype == v_side.index.dtype == jnp.bfloat16
    # left out, the positions the model itself can have (512)
    assert jax.eval_shape(lambda: dots3.init_kv_cache(
        cfg, 9, PAGE, jnp.bfloat16))[1].index.shape == (3, 1, 1024, 128)
    assert [latent_select.record_len(n, PAGE)
            for n in (1, 1024, 1025, 18432)] == [1024, 1024, 2048, 18432]


def test_a_decode_step_looks_a_picked_key_up_once():
    """The lowered decode step holds, under ``dsa_attend``, exactly one
    ``gather`` a full layer's body (the dense prefix's layer 0 and the
    period's loop): a picked key's row is one lookup in the one stack
    that holds the latent and the rope key side by side. Two stacks were
    two lookups, and a lookup costs the chip by the index
    (scripts/gather_sweep.py). Under ``dsa_index`` there is none: the
    indexer's keys are scored where they lie, by slot (until PR 63 a
    gather of their pages a body)."""
    cfg, params = _params(jnp.float32)
    rows, width = 2, 4
    cache = jax.eval_shape(lambda: dots3.init_kv_cache(
        cfg, 9, PAGE, jnp.float32, window_blocks=5))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(cache, tokens, positions, tables, slots, context):
        return dots3.forward(params, cfg, tokens, positions, cache, tables,
                             slots, context)

    text = jax.jit(step).lower(
        cache, i32(rows, 1), i32(rows, 1), i32(rows, 2 * width), i32(rows, 1),
        i32(rows)).as_text(debug_info=True)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    gathers = [named.get(m, "") for m in re.findall(
        r'stablehlo\.gather.*loc\((#loc\d+)\)', text)]
    under = {scope: sum(f"/{scope}/" in name for name in gathers)
             for scope in ("dsa_index", "dsa_attend", "dsa_select")}
    assert under == {"dsa_index": 0, "dsa_attend": 2, "dsa_select": 0}


@pytest.mark.parametrize("key,value,error", [
    ("attention_gate_type", "elementwise", NotImplementedError),
    ("swa_attention_gate_type", None, NotImplementedError),
    ("scoring_func", "softmax", NotImplementedError),
    ("topk_method", "greedy", NotImplementedError),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, NotImplementedError),
    ("moe_layer_freq", 2, NotImplementedError),
    ("n_group", 4, NotImplementedError),
    ("q_lora_rank", None, NotImplementedError),
    ("n_shared_experts", 0, NotImplementedError),
    ("sliding_window_size", 0, NotImplementedError),
    ("layer_types", [FULL] * 9, NotImplementedError),
    ("layer_types", [FULL, SWA], ValueError),
    ("expert_share", {"of_experts": 24, "rank": 0}, ValueError),
])
def test_what_the_module_does_not_compute_is_refused(key, value, error):
    named = {"expert_share": "share", "n_shared_experts": "shared expert",
             "layer_types": "layer", "sliding_window_size": "sliding_window_size"
             }.get(key, key)
    with pytest.raises(error, match=named):
        ModelConfig.from_hf_config({**HF, key: value})


def test_the_families_keys_are_refused_under_another_model_type():
    plain = {"model_type": "some_other_trunk", "vocab_size": 128,
             "hidden_size": 64, "num_hidden_layers": 2,
             "num_attention_heads": 4}
    for key in ("index_topk", "swa_kv_lora_rank", "sliding_window_size",
                "apply_mla_qkv_lora_rescale", "attention_gate_type"):
        with pytest.raises(NotImplementedError, match=f"some_other_trunk.*{key}") as e:
            ModelConfig.from_hf_config({**plain, key: HF[key]})
        assert "dots3" in str(e.value)
    stray = dataclasses.replace(_cfg(), model_family="")
    with pytest.raises(NotImplementedError, match="index_topk"):
        models.resolve(stray)
    # afmoe's configs are afmoe's still; a mixed layer_types is this
    # family's under its own model_type only
    assert dots3.claimed_keys({"model_type": "afmoe", "layer_types": [FULL, SWA],
                               "sliding_window": 8}) == []
    assert afmoe.claimed_keys(HF) == ["layer_types"]
    assert dots3.claimed_keys(HF)[0] == "layer_types"


@pytest.mark.parametrize("path,setting", [
    ("ep_size", dict(ep_size=2)), ("tp_size", dict(tp_size=2)),
    ("spec_ngram_tokens", dict(spec_ngram_tokens=2)),
    ("multi_step_decode", dict(multi_step_decode=4)),
    ("host_kv_blocks", dict(host_kv_blocks=8)),
])
def test_paths_refused_for_the_family_by_name(path, setting):
    from dynamo_tpu.engine.model_runner import ModelRunner

    with pytest.raises(ValueError, match=f"{path} is refused for the "
                                         "dots3 family"):
        ModelRunner(EngineConfig(model=_cfg(), max_batch_size=2,
                                 max_model_len=64, kv_block_size=PAGE,
                                 num_kv_blocks=16, dtype="float32", **setting))


# ---------- the indexer's keys by slot (PR 63) ----------

_LI = 1      # the layer the op-level cases read, of two


def _paged_full_layer(seed, lens, slots, n_slots=4, topk=16):
    """One full layer's cache both ways, float32: token rows in pages
    behind a table a row (pages in shuffled order), and the indexer's
    keys (a) in pages of the same geometry, the form before PR 63, and
    (b) by slot, row ``r``'s at ``[LI, slots[r], position]`` with large
    stale keys at every position past its length and in every other
    slot. -> (rows_all, key_pages, records, table, context_lens)."""
    rs = np.random.RandomState(seed)
    layers, page, width, d_row, di = 2, PAGE, 8, 256, 128
    n = 1 + n_slots * width
    t_rec = latent_select.record_len(width * page, page)
    rows_all = rs.randn(layers, n, 1, page, d_row).astype(np.float32)
    key_pages = np.zeros((layers, n, 1, page, di), np.float32)
    records = 50.0 * rs.randn(layers, n_slots, t_rec, di).astype(np.float32)
    table = 1 + rs.permutation(n_slots * width).reshape(n_slots, width)
    table = table[:len(lens)].astype(np.int32)
    for r, (length, slot) in enumerate(zip(lens, slots)):
        keys = rs.randn(length, di).astype(np.float32)
        at = np.arange(length)
        key_pages[_LI, table[r, at // page], 0, at % page] = keys
        records[_LI, slot, :length] = keys
    return (jnp.asarray(rows_all), jnp.asarray(key_pages),
            jnp.asarray(records), jnp.asarray(table),
            jnp.asarray(lens, jnp.int32), topk)


def _plain_picked_attention(q, rows, scores, see, topk, scale):
    """Queries [B, S, H, d] over rows [B, T, d] of which each query
    keeps ``pick_mask`` of its ``scores`` [B, S, T] among ``see``."""
    keep = latent_select.pick_mask(scores, see, topk)
    s_log = jnp.einsum("bshd,btd->bsht", q, rows) * scale
    probs = jax.nn.softmax(jnp.where(keep[:, :, None], s_log, -jnp.inf), -1)
    return jnp.einsum("bsht,btd->bshd", probs, rows), np.asarray(keep)


def test_decode_with_keys_by_slot_equals_the_paged_form():
    """A decode step's picks and outputs from the records where they lie
    (row i is slot i, an idle row among them, tables in shuffled page
    order) are those of the paged form: the same keys gathered out of
    pages by the table, scored, picked and attended to plainly. What
    lies past a row's length (large stale keys) is never picked."""
    lens = [100, 37, 1, 128]
    rows_all, key_pages, records, table, ctx, topk = _paged_full_layer(
        5, lens, slots=range(4))
    rs = np.random.RandomState(6)
    b, h, j = len(lens), 2, 4
    q_lat, q_rope = (jnp.asarray(rs.randn(b, 1, h, 128), jnp.float32)
                     for _ in range(2))
    iq = jnp.asarray(rs.randn(b, 1, j, 128), jnp.float32)
    iw = jnp.asarray(rs.randn(b, 1, j), jnp.float32)
    got = latent_select.picked_decode_attention(
        q_lat, q_rope, rows_all, _LI, table, ctx, 0.1,
        latent_select.Indexer(iq, iw, records, jnp.arange(b), topk))
    gathered = latent_select._gather_pages(key_pages, _LI, table)
    t = gathered.shape[1]
    see = (jnp.arange(t)[None] < ctx[:, None])[:, None]
    want, keep = _plain_picked_attention(
        jnp.concatenate([q_lat, q_rope], -1),
        latent_select._gather_pages(rows_all, _LI, table),
        latent_select.index_scores(iq, iw, gathered), see, topk, 0.1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    by_slot = latent_select.pick_mask(
        latent_select.index_scores(iq, iw, records[_LI, :b, :t]), see, topk)
    assert (np.asarray(by_slot) == keep).all()
    assert keep.sum(-1)[:, 0].tolist() == [16, 16, 1, 16]


def test_prefill_with_keys_by_slot_equals_the_paged_form():
    """A prefill chunk's rows name their slots, not in the rows' order:
    every query's pick and output from slices of its row's record are
    those of the paged form, a pad row and a row's pad queries apart."""
    lens, slots = [100, 37, 128], [3, 0, 2]
    rows_all, key_pages, records, table, ctx, topk = _paged_full_layer(
        7, lens, slots)
    rs = np.random.RandomState(8)
    b, s, h, j = len(lens), 32, 2, 4
    q_lat, q_rope = (jnp.asarray(rs.randn(b, s, h, 128), jnp.float32)
                     for _ in range(2))
    iq = jnp.asarray(rs.randn(b, s, j, 128), jnp.float32)
    iw = jnp.asarray(rs.randn(b, s, j), jnp.float32)
    # each row's chunk ends at its length; the second has 5 tokens only
    n_valid = np.asarray([32, 5, 32])
    start = np.asarray(lens) - n_valid
    pos = np.minimum(start[:, None] + np.arange(s), np.asarray(lens)[:, None] - 1)
    valid = np.arange(s)[None] < n_valid[:, None]
    got = latent_select.blocked_latent_attention(
        q_lat, q_rope, (rows_all,), _LI, table, jnp.asarray(pos, jnp.int32),
        jnp.asarray(valid), ctx, 0.1, index=latent_select.Indexer(
            iq, iw, records, jnp.asarray(slots, jnp.int32), topk))
    gathered = latent_select._gather_pages(key_pages, _LI, table)
    key_pos = np.arange(gathered.shape[1])
    see = jnp.asarray(key_pos[None, None] <= pos[:, :, None])
    want, keep = _plain_picked_attention(
        jnp.concatenate([q_lat, q_rope], -1),
        latent_select._gather_pages(rows_all, _LI, table),
        latent_select.index_scores(iq, iw, gathered), see, topk, 0.1)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[..., :128][valid], atol=2e-5)
    by_slot = latent_select.pick_mask(latent_select.index_scores(
        iq, iw, records[_LI, jnp.asarray(slots), :len(key_pos)]), see, topk)
    assert (np.asarray(by_slot) == keep)[valid].all()


def test_a_slots_next_sequence_never_reads_what_the_last_one_left():
    """A slot is never cleared: a shorter sequence that takes a slot
    after a longer one (whose keys, made a thousand times larger, would
    win every pick they entered) writes its own positions and reads no
    other, through prefill in chunks and decode."""
    cfg, params = _params(jnp.float32)
    drive = Served(cfg, params, jnp.float32)
    a, b = _seqs([300 + 4, 90 + 20], seed=12)
    _serve_case(drive, [a], [2], 4, [128, 256], 128)
    k_side, v_side = drive.cache
    left = np.asarray(v_side.index)
    assert (np.abs(left[:, 2, :304]).max(-1) > 0).all()
    drive.cache = (k_side, dataclasses.replace(
        v_side, index=v_side.index.at[:, 2].multiply(1e3)))
    got = _serve_case(drive, [b], [2], 20, [40, 77], 64)[0]
    np.testing.assert_allclose(got, _reference_logprobs(params, b), rtol=0,
                               atol=1e-3)
    now = np.asarray(drive.cache[1].index)
    np.testing.assert_array_equal(now[:, 2, 110:], 1e3 * left[:, 2, 110:])
    np.testing.assert_array_equal(now[:, [0, 1, 3]], left[:, [0, 1, 3]])


def test_a_last_chunk_whose_bucket_overhangs_the_record(monkeypatch):
    """Records of 48 positions a slot (key blocks of 48, a model of 48
    positions) and a last chunk of 16 tokens from position 30 in a
    bucket of 32: positions 30-61 overhang the record. The chunk writes
    its 16 keys, leaves every earlier key as it was, and the sequence
    reads as the reference says to its last position. A table wider
    than the records is refused when the step is traced."""
    monkeypatch.setattr(latent_select, "KEY_BLOCK", 48)
    cfg, params = _params(jnp.float32, max_position_embeddings=48)
    drive = served.Served(dots3, cfg, params, jnp.float32, block=PAGE,
                          width=3, slots=4, fresh=True)
    assert drive.cache[1].index.shape == (3, 4, 48, 128)
    seq = _seqs([48], seed=13)[0]
    want = _reference_logprobs(params, seq)
    got = drive.prefill([(1, seq[:30], 0)], 32)[0]
    np.testing.assert_allclose(got, want[:30], rtol=0, atol=1e-3)
    first = np.asarray(drive.cache[1].index)
    got = drive.prefill([(1, seq[30:46], 30)], 32)[0]
    np.testing.assert_allclose(got, want[30:46], rtol=0, atol=1e-3)
    then = np.asarray(drive.cache[1].index)
    np.testing.assert_array_equal(then[:, 1, :30], first[:, 1, :30])
    assert (np.abs(then[:, 1, 30:46]).max(-1) > 0).all()
    assert not then[:, 1, 46:].any() and not then[:, [0, 2, 3]].any()
    for p in (46, 47):
        got = drive.decode({1: (seq[p], p)})[1]
        np.testing.assert_allclose(got, want[p], rtol=0, atol=1e-3)
    # (positions that run on past the record, as a chunk's pads might)
    keys = jnp.ones((1, 8, 16))
    put = dots3.write_index_keys(
        jnp.zeros((2, 3, 48, 128)), keys, 1, jnp.asarray([2]),
        jnp.arange(44, 52)[None], jnp.ones((1, 8), bool))
    assert np.asarray(put).sum() == 4 * 16
    assert np.asarray(put)[1, 2, 44:, :16].all()
    wide = served.Served(dots3, cfg, params, jnp.float32, block=PAGE,
                         width=4, slots=4, fresh=True)
    with pytest.raises(ValueError, match="96 positions over records of 48"):
        wide.prefill([(1, seq[:30], 0)], 32)


def test_the_served_path_through_a_preemption_and_a_resume():
    """A sequence dropped after 10 decoded tokens, its slot taken by
    another meanwhile, and prefilled again from position 0 (prompt and
    the 10) into another slot and then into its old one, continues as
    the reference says: the keys by slot are written anew by the resume
    and nothing of the slot's last user is read."""
    cfg, params = _params(jnp.float32)
    drive = Served(cfg, params, jnp.float32)
    a, b = _seqs([70 + 30, 50 + 8], seed=14)
    want_a, want_b = (_reference_logprobs(params, q) for q in (a, b))
    got = _serve_case(drive, [a[:80]], [1], 10, [40], 64)[0]
    np.testing.assert_allclose(got, want_a[:80], rtol=0, atol=1e-3)
    got = _serve_case(drive, [b], [1], 8, [], 64)[0]       # preempted: b in 1
    np.testing.assert_allclose(got, want_b, rtol=0, atol=1e-3)
    for slot in (3, 1):     # a resumes: prefill of 80 from 0, 20 more
        got = _serve_case(drive, [a], [slot], 20, [64], 64)[0]
        np.testing.assert_allclose(got, want_a, rtol=0, atol=1e-3)


def test_the_refusals_are_the_window_pools_with_records_by_slot_too():
    """``slots=True`` beside ``window_pool=True``: the paths refused are
    afmoe's (and this family's two), every path a family with records
    by slot refuses among them, and ``ModelRunner`` is told the rows'
    slots for such a family."""
    from dynamo_tpu.models import falcon_h1

    state = dots3.SEQUENCE_STATE
    assert state.slots and state.window_pool and state.private
    assert set(state.refused) == set(afmoe.SEQUENCE_STATE.refused)
    assert set(falcon_h1.SEQUENCE_STATE.refused) <= set(state.refused)
    same = {p: why for p, why in state.refused.items()
            if p not in ("ep_size", "prefix_pull")}
    assert same == {p: afmoe.SEQUENCE_STATE.refused[p] for p in same}
    assert "by slot" in state.keeps


@pytest.mark.parametrize("path,setting", [
    ("sp_size", dict(sp_size=2)), ("pp_size", dict(pp_size=2)),
    ("prefix_pull", dict(prefix_pull=True)),
    ("decode_pipeline_depth", dict(decode_pipeline_depth=2)),
    ("spec_draft_model", dict(spec_draft_model="draft", spec_draft_tokens=2)),
])
def test_paths_refused_for_records_by_slot_are_refused_by_name(path, setting):
    from dynamo_tpu.engine.model_runner import ModelRunner

    with pytest.raises(ValueError, match=f"{path} is refused for the "
                                         "dots3 family.*by slot"):
        ModelRunner(EngineConfig(model=_cfg(), max_batch_size=2,
                                 max_model_len=64, kv_block_size=PAGE,
                                 num_kv_blocks=16, dtype="float32",
                                 prefill_buckets=[32], **setting))
