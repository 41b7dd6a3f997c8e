"""A stream's handler decodes the end of its text at an event, not all of it.

`EngineServer._collect` needs the stream's whole text at every token (stop
strings, the held-back tail of a character cut in two), and used to decode
every token served so far to get it: the square of an answer's length. It now
keeps the text as a head that is settled and the tokens after it, and moves
the split up only where the text splits too. Held here against the whole
decode, event by event, under tokenizers whose text does not split anywhere:
bytes that cut characters in two, a leading space dropped at the start of a
sequence, spaces cleaned up before punctuation.
"""

import random

import jax
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine import server as server_mod
from kubeai_tpu.engine.engine import StepEvent
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer, _EventQueue
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama


class Counting:
    """Counts the tokens handed to `decode`."""

    eos_token_ids = ()

    def __init__(self):
        self.decoded = 0

    def decode(self, ids):
        self.decoded += len(ids)
        return self._decode(list(ids))


class Bytes(Counting):
    def _decode(self, ids):
        return ByteTokenizer().decode(ids)


class Pieces(Counting):
    """Word pieces as a sentencepiece model decodes them: a piece that opens
    a word carries its space, the space at the start of a sequence is
    dropped, and a space before punctuation is cleaned away."""

    WORDS = ["a", "bc", "def", ".", ",", "!", "gh", "i"]

    def _decode(self, ids):
        text = "".join(
            (" " if t % 2 else "") + self.WORDS[t // 2 % len(self.WORDS)]
            for t in ids)
        for mark in ".,!":
            text = text.replace(" " + mark, mark)
        return text[1:] if text.startswith(" ") else text


def _tokens(kind: str, n: int, seed: int) -> list[int]:
    r = random.Random(seed)
    if kind == "pieces":
        return [r.randrange(64) for _ in range(n)]
    # Text of one to four bytes a character, some bytes no UTF-8 at all.
    out: list[int] = []
    while len(out) < n:
        out.extend(
            [r.randrange(256)] if r.random() < 0.15
            else chr(r.choice([0x61, 0xE9, 0x20AC, 0x1F600])).encode())
    return out[:n]


@pytest.fixture(scope="module")
def server():
    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    engine = Engine(
        "llama", cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
        cfg=EngineConfig(num_slots=2, max_seq_len=64),
        eos_token_ids=tok.eos_token_ids,
    )
    return EngineServer(engine, tok, "tiny", host="127.0.0.1", port=0)


def _collect(server, tokenizer, tokens, tail, monkeypatch, **params):
    monkeypatch.setattr(server_mod, "DECODE_TAIL_TOKENS", tail)
    monkeypatch.setattr(server, "tokenizer", tokenizer)
    q = _EventQueue()
    for i, t in enumerate(tokens):
        last = i == len(tokens) - 1
        q.put(StepEvent(7, t, last, "length" if last else ""))
    deltas = []
    out = server._collect(
        7, q, SamplingParams(temperature=0.0, max_tokens=len(tokens), **params),
        on_delta=lambda text, new=(): deltas.append((text, list(new))),
    )
    return out, deltas


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("kind", ["bytes", "pieces"])
def test_every_delta_is_the_whole_decodes(server, monkeypatch, kind, seed):
    tokens = _tokens(kind, 700, seed)
    make = Bytes if kind == "bytes" else Pieces
    whole, split = make(), make()
    # A tail no stream reaches: the whole text decoded at every token.
    want = _collect(server, whole, tokens, 10**9, monkeypatch)
    got = _collect(server, split, tokens, 8, monkeypatch)
    assert got == want
    text, finish, n = got[0]
    assert (text, finish, n) == (make()._decode(tokens), "length", len(tokens))
    assert "".join(d[0] for d in got[1]) == text
    assert [t for _, new in got[1] for t in new] == tokens
    # The square of the length against a few tails a token.
    assert whole.decoded > 700 * 700 // 2
    assert split.decoded < 700 * 8 * 4


@pytest.mark.parametrize("kind", ["bytes", "pieces"])
def test_a_stop_string_ends_the_stream_where_the_whole_decode_ends_it(
        server, monkeypatch, kind):
    tokens = _tokens(kind, 400, 5)
    make = Bytes if kind == "bytes" else Pieces
    full = make()._decode(tokens)
    stop = full[300:303] if kind == "pieces" else "€a€"
    assert stop in full
    want = _collect(server, make(), tokens, 10**9, monkeypatch, stop=(stop,))
    got = _collect(server, make(), tokens, 8, monkeypatch, stop=(stop,))
    assert got == want
    assert got[0][1] == "stop" and got[0][0] == full[:full.index(stop)]


def test_the_split_waits_where_the_text_does_not_split(server, monkeypatch):
    """A tail that would start inside a character decodes to a replacement
    mark the whole text does not have there: the split stays where it was
    until a later token's tail starts on a character."""
    tokens = list("€".encode() * 40)  # 120 bytes, a character every 3
    tok = Bytes()
    (text, _, _), deltas = _collect(server, tok, tokens, 4, monkeypatch)
    assert text == "€" * 40 == "".join(d[0] for d in deltas)
    assert "�" not in "".join(d[0] for d in deltas)
