"""``OneCharTokenizer`` for vocabularies wider than the 35,328 characters
between U+4E00 and the surrogates (OLMoE: 50,304 ids): the same mapping for
the ids it covers, and one character of the supplementary planes (from
U+10000) for each id above them. Every id is still one Python character,
so a client counts tokens by counting characters, every token makes a
non-empty SSE delta, nothing ends a sequence, and JSON carries such a
character as a pair of ``\\uXXXX`` escapes that ``json.loads`` joins again.
"""
from __future__ import annotations

from .tokenizer import _BASE, _LIMIT

_HIGH = 0x10000


class WideTokenizer:
    eos_id = None
    eos_token_id = None
    bos_id = None

    def __init__(self, vocab_size: int):
        if vocab_size - _LIMIT > 0x110000 - _HIGH:
            raise ValueError(f"vocabulary {vocab_size} exceeds Unicode")
        self.vocab_size = vocab_size

    def encode(self, text: str, add_bos: bool = False) -> list:
        return [ord(c) - _BASE if ord(c) < _HIGH else ord(c) - _HIGH + _LIMIT
                for c in text]

    def decode(self, ids) -> str:
        return "".join(chr(_BASE + i if i < _LIMIT else _HIGH + i - _LIMIT)
                       for i in map(int, ids))
