"""Write the model directory a configuration is served from.

``config.json`` is the configuration's published keys as the file gives
them. The tokenizer is the benchmark's own, because there is no network
and prompts are token ids anyway: a word-level vocabulary as large as the
model's, in which id ``i`` renders as ``t<i>``. Every generated token
therefore yields text, so the server streams one SSE chunk per token and
the client can time each token; the probe's token strings map back to
ids by dropping the ``t``. (The repo's 286-entry test tokenizer renders
almost every id of a 32k vocabulary as nothing, and the server sends no
chunk for a token without text.)
"""

from __future__ import annotations

import json
import os

UNK_ID, BOS_ID, EOS_ID = 0, 1, 2   # plain entries: every id renders as t<id>
CHAT_TEMPLATE = (
    "{{ bos_token }}{% for message in messages %}"
    "<|{{ message.role }}|>{{ message.content }}</s>{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)


def token_text(i: int) -> str:
    return f"t{i}"


def token_id(text: str) -> int:
    """Inverse of :func:`token_text`, for the probe's returned tokens."""
    text = text.strip()
    if not (text.startswith("t") and text[1:].isdigit()):
        raise ValueError(f"not a token of the benchmark's vocabulary: {text!r}")
    return int(text[1:])


def write_model_dir(path: str, hf_config: dict) -> str:
    from tokenizers import Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    vocab = {token_text(i): i for i in range(int(hf_config["vocab_size"]))}
    tok = Tokenizer(models.WordLevel(vocab, unk_token=token_text(UNK_ID)))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(os.path.join(path, "tokenizer.json"))
    config = dict(hf_config)
    config.setdefault("bos_token_id", BOS_ID)
    config.setdefault("eos_token_id", EOS_ID)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"chat_template": CHAT_TEMPLATE,
                   "bos_token": token_text(BOS_ID),
                   "eos_token": token_text(EOS_ID)}, f)
    return path
