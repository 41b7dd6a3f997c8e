"""Past both lines: a vocabulary over 55,040 ids (where `chr(0x100 + t)`
reaches the surrogates) and prompts that keep off the ids a configuration
reserves. The tokenizer alone, the prompt rule alone, and the CPU rehearsal
`tiny-mistral-bigvocab.closed` end to end (151,936 ids, prompts below
151,643: the drawn row's numbers)."""

import json

import pytest

import test_rehearsal as rehearsal  # its `run`, `load_config`, `KEYS`
from perf import loadgen, traffic
from perf.tokenizer import LOW, MAX_VOCAB, BenchTokenizer, text_of

BIG = 154_880  # the largest vocabulary of the drawn rows
TOKENIZER = BenchTokenizer(BIG)


def _round_trip(t):
    return lambda: TOKENIZER.encode(text_of([t])) == [t] and len(text_of([t])) == 1


def _no_surrogate_and_no_replacement_character():
    text = text_of(range(BIG))
    return len(text) == BIG and not any(
        0xD800 <= ord(c) <= 0xDFFF or ord(c) == 0xFFFD for c in text)


def _the_whole_table_round_trips():
    return TOKENIZER.encode(text_of(range(BIG))) == list(range(BIG))


def _low_ids_keep_their_characters():
    # What every accepted cell's prompts are made of: the same bytes as ever.
    return text_of(range(LOW)) == "".join(chr(0x100 + t) for t in range(LOW)) and (
        BenchTokenizer(32768).encode(text_of(range(32768))) == list(range(32768)))


def _a_small_vocabulary_still_wraps_what_it_cannot_hold():
    # `encode` of a character outside the table was `(ord(c) - 0x100) % vocab`.
    return BenchTokenizer(512).encode("a" + chr(0x100 + 600)) == [
        (ord("a") - 0x100) % 512, 600 % 512]


CASES = {
    "id 0": _round_trip(0),
    "id 55039, the last below the surrogates": _round_trip(LOW - 1),
    "id 55040, the first in the supplementary planes": _round_trip(LOW),
    "id 65277, U+FFFD if the old rule simply ran on": _round_trip(0xFFFD - 0x100),
    "id 151935": _round_trip(151_935),
    "no surrogate and no U+FFFD in the whole table":
        _no_surrogate_and_no_replacement_character,
    "the whole table round trips": _the_whole_table_round_trips,
    "ids below 55040 are the characters they were": _low_ids_keep_their_characters,
    "a small vocabulary wraps as it did": _a_small_vocabulary_still_wraps_what_it_cannot_hold,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_tokenizer(case):
    assert LOW == 55_040
    assert CASES[case]()


@pytest.mark.parametrize("size,fits", [
    (1, True), (55_040, True), (55_041, True), (65_536, True), (151_936, True),
    (BIG, True), (MAX_VOCAB, True), (0, False), (-3, False), (MAX_VOCAB + 1, False)])
def test_the_constructor_refuses_only_what_the_planes_cannot_hold(size, fits):
    if fits:
        assert BenchTokenizer(size).vocab_size == size
        assert ord(text_of([size - 1])) <= 0x10FFFF
    else:
        with pytest.raises(ValueError):
            BenchTokenizer(size)


@pytest.mark.parametrize("cfg,want", [
    ({"vocab_size": 512}, 512),  # absent: the whole vocabulary, as ever
    ({"vocab_size": 151_936, "prompt_vocab_size": 151_643}, 151_643),
    ({"vocab_size": 512, "prompt_vocab_size": 512}, 512),
    ({"vocab_size": 512, "prompt_vocab_size": 513}, None),
    ({"vocab_size": 512, "prompt_vocab_size": 0}, None),
])
def test_prompt_vocab_size_is_one_number_at_most_the_vocabulary(cfg, want):
    if want is None:
        with pytest.raises(ValueError):
            traffic.prompt_vocab(cfg)
    else:
        assert traffic.prompt_vocab(cfg) == want


def test_a_prompt_drawn_below_the_reserved_ids_holds_none_of_them():
    # 3 tokens of a vocabulary of 8, a thousand requests: every id is drawn,
    # and none at or over the number the prompts were given.
    ids = {t for i in range(1000) for t in traffic.prompt_tokens(7, i, 3, 5)}
    assert ids == set(range(5))
    body = json.loads(loadgen.request_body(
        "m", {"index": 2, "prompt_len": 400, "max_tokens": 1}, 151_643, 9))
    sent = BenchTokenizer(151_936).encode(body["prompt"])
    assert len(sent) == 400 and max(sent) < 151_643 and max(sent) >= LOW
    assert sent == traffic.prompt_tokens(9, 2, 400, 151_643)


def test_the_rehearsal_past_both_lines(tmp_path, monkeypatch):
    """`tiny-mistral-bigvocab.closed`: the real engine and server on the CPU
    with 151,936 ids. Served ids over 55,040 are streamed, each in an event
    of its own (a token whose text the server held back would ride on the
    next event), the prompts were drawn below 151,643 on both sides of the
    comparison, and `correct` is true."""
    records = tmp_path / "records.json"
    monkeypatch.setenv("PERF_KEEP_RECORDS", str(records))
    rc, lines, err = rehearsal.run(
        tmp_path, "--workload", "tiny-mistral-bigvocab.closed",
        "--seed", str(2**31 + 34), "--seconds", "2", "--trace", "0")
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert list(line) == rehearsal.KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    cfg = rehearsal.load_config("tiny-mistral-bigvocab.closed")
    assert set(line["compared"]) == set(cfg["correct"]) | {"failed"}
    assert {"out_tok_s", "setup_s"} == set(line["metrics"])  # as mistral-7b.decode-sat
    assert "perf: prompts drawn from the first 151643 of 151936 ids" in lines
    done = [r for r in json.loads(records.read_text())["records"] if r["ok"]]
    served = [t for r in done for t in r["token_ids"]]
    assert len(served) > 100 and max(served) < 151_936
    assert sum(t >= LOW for t in served) > len(served) // 4
    # Every event carries text: one token an event, none held back.
    assert all(n == 1 for r in done for _, n in r["events"])
    assert all(len(r["events"]) == len(r["token_ids"]) == r["max_tokens"] for r in done)
    assert "handover" not in done[0] and "routes" not in done[0]
