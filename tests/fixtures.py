"""Shared test fixtures: a tiny trained tokenizer + fake HF model dir."""

import json
import os

from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "hello world this is a test of the tokenizer",
    "paged attention on tpu with jax and pallas kernels",
    "distributed serving with disaggregated prefill and decode",
    "USER: what is the capital of france? ASSISTANT: paris STOP",
    "a b c d e f g h i j k l m n o p q r s t u v w x y z",
    "0 1 2 3 4 5 6 7 8 9 émojis ünïcode ✓ 中文 tokens",
    # JSON structural characters: guided-JSON decoding needs the
    # tokenizer to be able to EXPRESS the grammar (braces, quotes,
    # colons, commas, brackets, minus, dot, backslash)
    '{"name": "value", "n": [1, 2.5, -3], "ok": true, "x": null}',
]

CHAT_TEMPLATE = (
    "{{ bos_token }}"
    "{% for message in messages %}"
    "<|{{ message.role }}|>{{ message.content }}</s>"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)


def build_tiny_tokenizer() -> Tokenizer:
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=512,
        show_progress=False,
        special_tokens=["<unk>", "<s>", "</s>", "<|user|>", "<|assistant|>", "<|system|>"],
    )
    tok.train_from_iterator(CORPUS, trainer)
    return tok


def make_model_dir(tmp_path, name="tiny-llama", context_length=256,
                   config_overrides=None) -> str:
    """Write a fake HF snapshot dir: tokenizer.json + config.json + tokenizer_config.json.

    ``config_overrides`` merges extra/replacement keys into config.json
    (e.g. real model dims for a flagship-shape serving benchmark).
    """
    model_dir = os.path.join(str(tmp_path), name)
    os.makedirs(model_dir, exist_ok=True)
    tok = build_tiny_tokenizer()
    tok.save(os.path.join(model_dir, "tokenizer.json"))
    eos_id = tok.token_to_id("</s>")
    bos_id = tok.token_to_id("<s>")
    config = {
        "model_type": "llama",
        "eos_token_id": eos_id,
        "bos_token_id": bos_id,
        "max_position_embeddings": context_length,
        "vocab_size": tok.get_vocab_size(),
    }
    config.update(config_overrides or {})
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(model_dir, "tokenizer_config.json"), "w") as f:
        json.dump(
            {
                "chat_template": CHAT_TEMPLATE,
                "bos_token": "<s>",
                "eos_token": "</s>",
            },
            f,
        )
    return model_dir
