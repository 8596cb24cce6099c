"""Guided decoding (vLLM-style ``guided_choice``).

The preprocessor tokenizes each choice; the engine walks a token trie
and rewrites the sampler's bias row per step, so the completion is
exactly one of the choices under greedy OR sampled decoding. Reference
analog: the guided decoding of the engines the reference delegates to
(vLLM guided_choice; the reference proxies OpenAI-level JSON through)."""

import asyncio
import os

import numpy as np
import pytest

import jax

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.serving import JaxServingEngine
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.models import llama
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, attention_impl="xla",
)

CHOICES = [[5, 9, 7], [5, 2], [40, 41, 42, 43]]


async def _generate(engine, *, temperature=0.0, seed=None, choices=CHOICES,
                    max_tokens=8, logit_bias=None):
    req = PreprocessedRequest(
        token_ids=[1, 17, 43, 99],
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=temperature, seed=seed, logit_bias=logit_bias,
            guided_choice_token_ids=choices,
        ),
    )
    toks, finish = [], None
    async for out in engine.generate(Context(req)):
        toks.extend(out["token_ids"])
        if out.get("finish_reason"):
            finish = out["finish_reason"]
    return toks, finish


async def _engine(**cfg_kw):
    econfig = EngineConfig(
        model=CFG, max_batch_size=2, max_model_len=64, kv_block_size=8,
        num_kv_blocks=32, dtype="float32", prefill_buckets=[16],
        allow_random_weights=True, **cfg_kw,
    )
    mdc = ModelDeploymentCard(display_name="t", slug="t")
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jax.numpy.float32)
    return await JaxServingEngine.create(
        mdc, engine_config=econfig, params=params, warmup=False)


def test_guided_choice_greedy_and_sampled():
    async def run():
        engine = await _engine()
        greedy, finish = await _generate(engine)
        assert greedy in CHOICES and finish == "stop"
        # sampled runs stay inside the choice set too (mask, not luck:
        # 124 of 128 vocab ids are banned at the root)
        seen = set()
        for seed in range(4):
            toks, fin = await _generate(engine, temperature=1.5, seed=seed)
            assert toks in CHOICES and fin == "stop"
            seen.add(tuple(toks))
        await engine.close()
        return greedy, seen

    greedy, seen = asyncio.run(run())
    assert greedy  # non-empty


def test_guided_prefix_choice_resolves_to_longer_or_stops():
    """[5] is a strict prefix of [5, 9, 7]: after emitting 5 the engine
    allows {9} ∪ eos; with ignore_eos + no eos in vocab path the longer
    choice wins deterministically under greedy."""
    async def run():
        engine = await _engine()
        toks, fin = await _generate(
            engine, choices=[[5], [5, 9, 7]], max_tokens=8)
        await engine.close()
        return toks, fin

    toks, fin = asyncio.run(run())
    assert toks in ([5], [5, 9, 7]) and fin == "stop"


def test_guided_respects_max_tokens():
    async def run():
        engine = await _engine()
        toks, fin = await _generate(
            engine, choices=[[40, 41, 42, 43]], max_tokens=2)
        await engine.close()
        return toks, fin

    toks, fin = asyncio.run(run())
    assert toks == [40, 41] and fin == "length"


def test_guided_excluded_from_speculation_paths():
    """A guided row must not ride ngram speculation or the fused burst
    (its mask changes per step) — and the output stays constrained."""
    async def run():
        engine = await _engine(spec_ngram_tokens=4, multi_step_decode=4)
        toks, fin = await _generate(engine)
        await engine.close()
        return toks, fin

    toks, fin = asyncio.run(run())
    assert toks in CHOICES and fin == "stop"


# ---------------------------------------------------------------------------
# guided JSON (response_format / vLLM guided_json — VERDICT r4 item 6)
# ---------------------------------------------------------------------------

import json as _json
import random as _random

from dynamo_tpu.engine.guided import (
    JsonConstraint,
    JsonGrammar,
    build_piece_table,
    compile_schema,
)

# a deliberately adversarial piece table: structural chars, multi-char
# fusions, numbers, escapes, literals, and junk that must get masked out
PIECES = [None] * 128
for i, s in enumerate([
    '{', '}', '[', ']', '"', ':', ',', ' ', '\n', '-',
    '0', '1', '7', '25', '3.5', '0.25', 'e5', 'E-2', '.5',
    'a', 'b', 'ab', 'name', 'x', 'y z', 'true', 'false', 'null',
    '{"', '"}', '":', '": ', '", "', '"a"', '\\', '\\n', '\\u00e9',
    'tr', 'ue', 'nu', 'll', '[]', '{}', '[1', ',2]', 'word up',
    '!', '@#', '<tag>', "'", '\t', '\x01',
]):
    PIECES[i + 2] = s  # 0/1 reserved (None → banned like specials)


def _decode(toks):
    return "".join(PIECES[t] for t in toks)


def _random_walk(grammar, seed, max_steps=300):
    """Random token walk over the masked vocab; returns (text, done)."""
    rng = _random.Random(seed)
    c = JsonConstraint(grammar)
    toks = []
    for _ in range(max_steps):
        ids, at_end = c.allowed()
        assert ids or at_end, "dead state with no way out"
        if not ids:
            return _decode(toks), True  # only eos remains
        t = rng.choice(ids)
        toks.append(t)
        v = c.advance(t)
        assert v != "derail", (PIECES[t], _decode(toks))
        if v == "done":
            return _decode(toks), True
        if at_end and rng.random() < 0.25:
            return _decode(toks), True  # simulate eos at a legal end
    return _decode(toks), False


def test_json_object_random_walks_always_parse():
    g = JsonGrammar(PIECES)
    finished = 0
    for seed in range(40):
        text, done = _random_walk(g, seed)
        if done:
            finished += 1
            obj = _json.loads(text)  # every finished walk parses
            assert isinstance(obj, dict)  # json_object ⇒ top-level object
    assert finished >= 20  # the machine actually terminates walks


def test_json_schema_random_walks_validate():
    schema = {
        "type": "object",
        "properties": {
            "name": {"type": "string"},
            "a": {"type": "integer"},
            "ab": {"enum": ["x", "ab", 7]},
            "b": {"type": "array", "items": {"type": "number"}},
        },
        "required": ["name"],
    }
    g = JsonGrammar(PIECES, schema)
    finished = 0
    for seed in range(40):
        text, done = _random_walk(g, seed)
        if not done:
            continue
        finished += 1
        obj = _json.loads(text)
        assert set(obj) <= {"name", "a", "ab", "b"}
        assert "name" in obj and isinstance(obj["name"], str)
        if "a" in obj:
            assert isinstance(obj["a"], int) and not isinstance(obj["a"], bool)
        if "ab" in obj:
            assert obj["ab"] in ("x", "ab", 7)
        if "b" in obj:
            assert isinstance(obj["b"], list)
            assert all(isinstance(v, (int, float)) for v in obj["b"])
    assert finished >= 15


def test_json_schema_unsupported_keywords_rejected():
    for bad in (
        {"type": "string", "pattern": "a+"},
        {"type": "number", "minimum": 3},
        {"type": "array", "items": {}, "minItems": 1},
        {"oneOf": [{"type": "string"}]},
        {"type": ["string", "number"]},
        # 'required' without 'properties' cannot be enforced
        {"type": "object", "required": ["id"]},
        # property names needing JSON escaping are not walkable
        {"type": "object", "properties": {'a"b': {"type": "string"}}},
        {"type": "object", "properties": {"a\nb": {"type": "string"}}},
    ):
        with pytest.raises(ValueError):
            compile_schema(bad)
    # annotations pass
    compile_schema({"type": "object", "title": "T", "description": "d",
                    "properties": {"a": {"type": "string", "default": "q"}}})


def test_json_engine_end_to_end_parses():
    """Through the real engine: random weights + the piece-table mask ⇒
    whatever greedy emits, the finished completion parses as JSON."""
    async def run():
        engine = await _engine()
        # inject the synthetic piece table (no tokenizer in this fixture)
        engine._pieces = PIECES + [None] * (CFG.vocab_size - len(PIECES))
        engine._model_path = "<injected>"
        req = PreprocessedRequest(
            token_ids=[1, 17, 43, 99],
            stop_conditions=StopConditions(max_tokens=48, ignore_eos=True),
            sampling_options=SamplingOptions(
                temperature=0.0,
                guided_json={"type": "json_object"},
            ),
        )
        toks, finish = [], None
        async for out in engine.generate(Context(req)):
            toks.extend(out["token_ids"])
            if out.get("finish_reason"):
                finish = out["finish_reason"]
        await engine.close()
        return toks, finish

    toks, finish = asyncio.run(run())
    text = _decode(toks)
    if finish == "stop":
        assert isinstance(_json.loads(text), dict)
    else:  # budget hit mid-object: still a valid JSON *prefix*
        g = JsonGrammar(PIECES)
        assert g.run_piece(g.initial(), text) is not None


def test_json_grammar_released_twice_is_no_key_error():
    """A follower that awaited a concurrent build touches the key's LRU
    slot when it wakes — after other specs' builds may have evicted it
    (the 32-entry bound). The second release of the key must not raise
    on the serving path."""
    from types import SimpleNamespace

    from dynamo_tpu.engine.guided import JsonConstraint

    async def run():
        spec = {"type": "json_object"}
        key = _json.dumps(spec, sort_keys=True)
        build = asyncio.get_running_loop().create_future()
        eng = SimpleNamespace(_json_grammars={key: build})
        follower = asyncio.ensure_future(
            JaxServingEngine._json_constraint(eng, spec))
        await asyncio.sleep(0)          # the follower awaits the build
        build.set_result(JsonGrammar(PIECES))
        eng._json_grammars.pop(key)     # evicted before the follower wakes
        return await follower, key, eng

    cursor, key, eng = asyncio.run(run())
    assert isinstance(cursor, JsonConstraint)
    assert key in eng._json_grammars


def test_json_engine_sampled_conformance():
    """Sampled decoding (several seeds) stays inside the grammar."""
    async def run():
        engine = await _engine()
        engine._pieces = PIECES + [None] * (CFG.vocab_size - len(PIECES))
        engine._model_path = "<injected>"
        outs = []
        for seed in range(3):
            req = PreprocessedRequest(
                token_ids=[1, 17, 43, 99],
                stop_conditions=StopConditions(max_tokens=40, ignore_eos=True),
                sampling_options=SamplingOptions(
                    temperature=1.2, seed=seed,
                    guided_json={"type": "json_object"},
                ),
            )
            toks, finish = [], None
            async for out in engine.generate(Context(req)):
                toks.extend(out["token_ids"])
                if out.get("finish_reason"):
                    finish = out["finish_reason"]
            outs.append((toks, finish))
        await engine.close()
        return outs

    outs = asyncio.run(run())
    g = JsonGrammar(PIECES)
    for toks, finish in outs:
        text = _decode(toks)
        if finish == "stop":
            assert isinstance(_json.loads(text), dict), text
        else:
            assert g.run_piece(g.initial(), text) is not None, text


def test_piece_table_from_real_tokenizer(tmp_path):
    """build_piece_table models mid-sequence rendering: decoding token
    by token through the table must equal decoding the whole sequence."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from fixtures import build_tiny_tokenizer

    from dynamo_tpu.llm.tokenizer import HFTokenizer

    tok = HFTokenizer(build_tiny_tokenizer())
    pieces = build_piece_table(tok, tok.vocab_size)
    ids = tok.encode("hello world this is a test", add_special_tokens=False)
    assert "".join(pieces[i] for i in ids) == tok.decode(ids)
