"""The benchmark's tokenizer: one token <-> one printable character.

There is no network, so no published tokenizer file can be fetched; the
program's own `ByteTokenizer` decodes only ids below 256, and with a
32768-entry vocabulary nearly every generated token would decode to nothing,
so the server would stream no event until a request ended. Here a prompt of N
characters is N tokens, every token is one printable character, and every
streamed event carries text. What it cannot show: the cost of a real
tokenizer's encode and incremental decode.

Ids below `LOW` (55,040) are `chr(0x100 + t)`, which ends just under the
surrogates at U+D800. Ids from `LOW` up go into the supplementary planes,
`chr(0x10000 + t - LOW)`: no surrogate, and no U+FFFD, which the server holds
back at a text's tail (a token mapped there would be streamed an event late).
So a vocabulary of any published size fits, and a vocabulary under 55,040
ids reads and writes the characters it always did.
"""

from __future__ import annotations

BASE = 0x100
LOW = 0xD800 - BASE  # ids below this are characters of the basic plane
HIGH_BASE = 0x10000
HIGH_OFFSET = HIGH_BASE - LOW
MAX_VOCAB = 0x110000 - HIGH_OFFSET


class BenchTokenizer:
    eos_token_ids: tuple[int, ...] = ()

    def __init__(self, vocab_size: int):
        if not 0 < vocab_size <= MAX_VOCAB:
            raise ValueError(
                f"a vocabulary of {vocab_size} ids does not fit the planes "
                f"(1 to {MAX_VOCAB})")
        self.vocab_size = vocab_size

    def encode(self, text: str) -> list[int]:
        v = self.vocab_size
        return [(o - BASE if (o := ord(c)) < HIGH_BASE else o - HIGH_OFFSET) % v
                for c in text]

    def decode(self, ids) -> str:
        return text_of(ids)

    def apply_chat_template(self, messages: list[dict]) -> list[int]:
        return self.encode("".join(str(m.get("content", "")) for m in messages))


def text_of(ids) -> str:
    # The server decodes a stream's whole text at every event, on the timed
    # path: a list comprehension, and nothing converted that `chr` takes.
    return "".join([chr(t + BASE if t < LOW else t + HIGH_OFFSET) for t in ids])
