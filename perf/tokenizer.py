"""The benchmark's tokenizer: token id t <-> the character chr(0x100 + t).

There is no network, so no published tokenizer file can be fetched; the
program's own `ByteTokenizer` decodes only ids below 256, and with a
32768-entry vocabulary nearly every generated token would decode to nothing,
so the server would stream no event until a request ended. Here a prompt of N
characters is N tokens, every token is one printable character, and every
streamed event carries text. What it cannot show: the cost of a real
tokenizer's encode and incremental decode.
"""

from __future__ import annotations

BASE = 0x100


class BenchTokenizer:
    eos_token_ids: tuple[int, ...] = ()

    def __init__(self, vocab_size: int):
        if BASE + vocab_size > 0xD800:
            raise ValueError("vocabulary reaches the surrogate range")
        self.vocab_size = vocab_size

    def encode(self, text: str) -> list[int]:
        return [(ord(c) - BASE) % self.vocab_size for c in text]

    def decode(self, ids) -> str:
        return text_of(ids)

    def apply_chat_template(self, messages: list[dict]) -> list[int]:
        return self.encode("".join(str(m.get("content", "")) for m in messages))


def text_of(ids) -> str:
    return "".join(chr(BASE + int(i)) for i in ids)
