"""The benchmark's tokenizer: every id is one character, and nothing ends
a sequence.

The program's default ByteTokenizer drops ids >= 256 when decoding, so a
streamed delta is mostly empty and nothing is sent, and its id 257 ends a
random-weight sequence at a random length. With this one a client counts
tokens by counting characters, every token makes a non-empty SSE delta,
and a request ends after exactly ``max_tokens``.
"""
from __future__ import annotations

_BASE = 0x4E00                  # id 0 is U+4E00; JSON carries every one as \uXXXX
_LIMIT = 0xD800 - _BASE         # ids must stay below the surrogates


class OneCharTokenizer:
    eos_id = None
    eos_token_id = None
    bos_id = None

    def __init__(self, vocab_size: int):
        if vocab_size > _LIMIT:
            raise ValueError(f"vocabulary {vocab_size} exceeds {_LIMIT}")
        self.vocab_size = vocab_size

    def encode(self, text: str, add_bos: bool = False) -> list:
        return [ord(c) - _BASE for c in text]

    def decode(self, ids) -> str:
        return "".join(chr(_BASE + int(i)) for i in ids)
