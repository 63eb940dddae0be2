"""The configurations' tokenizer: a copy of the program's deterministic hash
tokenizer (``repro.data.tokenizer.HashTokenizer``). The benchmark counts
history tokens and builds the reference's inputs with it, so neither
depends on the program's own copy."""
from __future__ import annotations

import re
import zlib
from typing import List

_WORD_RE = re.compile(r"[a-zA-Z0-9]+|[^\sa-zA-Z0-9]")


class HashTokenizer:
    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> List[int]:
        return [3 + (zlib.crc32(t.encode()) % (self.vocab_size - 3))
                for t in _WORD_RE.findall(text.lower())]
