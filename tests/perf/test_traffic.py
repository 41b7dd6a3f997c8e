"""The generator is a pure function of the seed, every seed offers the same
work, and the load generator's clock arithmetic is right on a fake clock."""

import pytest

from perf import e2e, loadgen, traffic

CHAT = traffic.load_mix("chat")
SAT = traffic.load_mix("decode-sat")


def test_schedule_is_a_pure_function_of_the_seed():
    a = traffic.open_schedule(CHAT, 2**31 + 7, 40.0)
    assert a == traffic.open_schedule(CHAT, 2**31 + 7, 40.0)
    assert a != traffic.open_schedule(CHAT, 2**31 + 8, 40.0)
    assert traffic.prompt_tokens(5, 3, 64, 32768) == traffic.prompt_tokens(5, 3, 64, 32768)
    assert traffic.closed_sequences(SAT, 9, 24) == traffic.closed_sequences(SAT, 9, 24)


def test_every_seed_offers_the_same_multiset_of_work():
    a = traffic.open_schedule(CHAT, 1, 40.0)
    b = traffic.open_schedule(CHAT, 99, 40.0)
    win = lambda s: [r for r in s if r["due"] >= 0]  # noqa: E731
    assert len(win(a)) == len(win(b)) == round(CHAT["rate_rps"] * 40)
    for key in ("prompt_len", "max_tokens"):
        assert sorted(r[key] for r in win(a)) == sorted(r[key] for r in win(b))
    gaps = lambda s: sorted(round(y["due"] - x["due"], 9)  # noqa: E731
                            for x, y in zip(win(s), win(s)[1:]))
    assert sum(gaps(a)) == pytest.approx(sum(gaps(b)), rel=0.05)
    pool = lambda seed: sorted(  # noqa: E731
        (r["prompt_len"], r["max_tokens"])
        for c in traffic.closed_sequences(SAT, seed, 24) for r in c)
    assert [p for p, _ in pool(1)] == [p for p, _ in pool(2)]


def test_lengths_stay_inside_the_mix_and_the_context():
    for r in traffic.open_schedule(CHAT, 3, 40.0):
        assert 32 <= r["prompt_len"] <= 1536 and 8 <= r["max_tokens"] <= 448
        assert r["prompt_len"] + r["max_tokens"] < 2048
        assert -CHAT["preroll_s"] <= r["due"] < 40.0
    for cycle in traffic.closed_sequences(SAT, 3, 24):
        assert len(cycle) == traffic.CLOSED_POOL_PER_CLIENT
        for r in cycle:
            assert 65 <= r["prompt_len"] <= 256 and 128 <= r["max_tokens"] <= 384


def test_stratified_lengths_follow_the_distribution():
    xs = traffic.stratified(CHAT["prompt_tokens"], 1001)
    assert xs[500] == 512  # the median
    assert xs == sorted(xs) and xs[0] >= 32 and xs[-1] == 1536
    gaps = traffic.exponential_gaps(240, 40.0)
    assert sum(gaps) == pytest.approx(40.0)
    assert max(gaps) > 5 * (40.0 / 240)  # a Poisson process has long gaps


def test_clients_come_from_the_configuration():
    assert traffic.num_clients(SAT, {"num_slots": 24}) == 24
    assert traffic.num_clients({"clients": 3}, {"num_slots": 24}) == 3


def test_clock_counts_from_the_windows_start_on_a_fake_clock():
    wall, mono = [1000.0], [50.0]
    clock = loadgen.Clock(1010.0, wall=lambda: wall[0], mono=lambda: mono[0])
    assert clock.now() == pytest.approx(-10.0)
    mono[0] += 12.5  # the wall clock may jump; the monotonic one decides
    wall[0] += 99.0
    assert clock.now() == pytest.approx(2.5)
    slept = []

    def sleep(s):
        slept.append(s)
        mono[0] += s

    clock.sleep_until(4.0, sleep=sleep)
    assert clock.now() == pytest.approx(4.0) and sum(slept) == pytest.approx(1.5)


def test_lateness_and_ttft_count_from_the_due_time():
    rec = [{"due": 1.0, "sent": 1.004, "ok": True, "events": [[1.5, 1], [1.6, 1]]},
           {"due": 2.0, "sent": 2.5, "ok": True, "events": [[3.0, 1], [3.2, 1]]},
           {"due": -1.0, "sent": -1.0, "ok": True, "events": [[0.1, 1]]}]
    assert e2e.late_p95_ms(rec, 10.0) == pytest.approx(4 + 0.95 * 496)
    # 0.5 s and 1.0 s from DUE (the second was sent half a second late, and
    # the wait counts); the pre-roll request is not in the window.
    assert e2e.ttft_p95_ms("open", rec, 10.0) == pytest.approx(500 + 0.95 * 500)
