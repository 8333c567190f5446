"""A tokenizer the client can read. The presets serve with the byte
tokenizer, which renders no text for ids above 255, and the frontend
sends no SSE chunk for no text. A WordLevel tokenizer with one word
`w<id>` per id of the model's vocabulary renders every id, so time to
first token and the gaps are read where users read them, and a session's
next turn carries the served ids back exactly. It is an ordinary
`--tokenizer <dir>`; the directory is generated at set-up, never
committed, and rebuilt only when the vocabulary size changes."""

from __future__ import annotations

import json
from pathlib import Path


def ensure(vocab_size: int, base: Path) -> Path:
    """The tokenizer directory for `vocab_size` under `base`, built if
    it is not there yet."""
    out = base / f"wordlevel-v2-{vocab_size}"
    done = out / "tokenizer_config.json"
    if done.exists():
        return out
    from tokenizers import Tokenizer, models, pre_tokenizers

    out.mkdir(parents=True, exist_ok=True)
    vocab = {f"w{i}": i for i in range(vocab_size)}
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="w0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(str(out / "tokenizer.json"))
    # written last: its presence marks the directory complete
    with open(done, "w") as f:
        # no special tokens: decoding must render every id, `w0` included
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    return out


def ids_of(text: str) -> list[int]:
    """The ids a served text stands for ("w17 w4" -> [17, 4])."""
    return [int(w[1:]) for w in text.split()]
