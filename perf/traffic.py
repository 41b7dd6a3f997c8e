"""The one traffic generator: a mix file's parameters + a seed -> requests.

Pure functions of their arguments and free of JAX, so the load generator's
child process can import them. Every seed gets the SAME multiset of lengths
and inter-arrival gaps (the distribution's quantiles at evenly spaced
probabilities); the seed decides only their order and the prompts' contents.
So two seeds offer the same work, and runs differ by order alone.
"""

from __future__ import annotations

import json
import math
import os
import random
from statistics import NormalDist

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    """`traffic/<name>.json`; a file may `extend` another and override keys
    (a later cell's rate is then a two-line file)."""
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if "extends" in mix:
        mix = {**load_mix(mix.pop("extends"), root), **mix}
    return mix


def _quantile(spec: dict, p: float) -> float:
    dist = spec["dist"]
    if dist == "uniform":
        return spec["low"] + p * (spec["high"] - spec["low"])
    if dist == "lognormal":
        z = NormalDist().inv_cdf(p)
        return spec["median"] * math.exp(spec["sigma"] * z)
    if dist == "fixed":
        return spec["value"]
    raise ValueError(f"unknown length distribution {dist!r}")


def stratified(spec: dict, n: int) -> list[int]:
    """n whole numbers: the quantiles at (i + 1/2) / n, clipped to the range."""
    lo, hi = spec.get("low", 1), spec.get("high", 1 << 30)
    return [
        int(min(hi, max(lo, round(_quantile(spec, (i + 0.5) / n)))))
        for i in range(n)
    ]


def exponential_gaps(n: int, total: float) -> list[float]:
    """n gaps, the exponential quantiles, scaled to sum to `total`."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def _rng(seed: int, stream: int) -> random.Random:
    return random.Random(seed * 1_000_003 + stream)


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list[int]:
    r = _rng(seed, 7919 + index)
    return [r.randrange(vocab) for _ in range(length)]


def prompt_vocab(cfg: dict) -> int:
    """The ids a prompt is drawn from, `[0, this)`. A family's reserved ids
    (a mask, end-of-turn marks) lie at the top of its table, and in a prompt
    one of them is an instruction and not a token; a configuration whose
    family has such ids states `prompt_vocab_size`, the first of them. The
    tokenizer and the logits keep the whole `vocab_size`."""
    vocab = int(cfg["vocab_size"])
    n = int(cfg.get("prompt_vocab_size", vocab))
    if not 0 < n <= vocab:
        raise ValueError(
            f"prompt_vocab_size {n} is not in 1..vocab_size ({vocab})")
    return n


def _sized(mix: dict, n: int, seed: int, stream: int) -> list[tuple[int, int]]:
    prompts = stratified(mix["prompt_tokens"], n)
    outputs = stratified(mix["output_tokens"], n)
    _rng(seed, stream).shuffle(prompts)
    _rng(seed, stream + 1).shuffle(outputs)
    return list(zip(prompts, outputs))


def open_schedule(mix: dict, seed: int, seconds: float) -> list[dict]:
    """Open loop: requests with due times in [-preroll, seconds), relative
    to the start of the measured window. Pre-roll and window are two sets
    of their own, so the window's work does not depend on the seed."""
    rate = float(mix["rate_rps"])
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    out: list[dict] = []
    for stream, (start, span) in enumerate(
        ((-float(mix.get("preroll_s", 0.0)), float(mix.get("preroll_s", 0.0))),
         (0.0, float(seconds)))
    ):
        n = int(round(rate * span))
        if n <= 0:
            continue
        gaps = exponential_gaps(n, span)
        _rng(seed, 10 + stream).shuffle(gaps)
        sizes = _sized(mix, n, seed, 20 + 2 * stream)
        # The first gap is halved so arrivals straddle the span evenly.
        t = start - gaps[0] / 2
        for gap, (plen, olen) in zip(gaps, sizes):
            t += gap
            out.append({"due": t, "prompt_len": plen, "max_tokens": olen})
    out.sort(key=lambda r: r["due"])
    for i, r in enumerate(out):
        r["index"] = i
    return out


CLOSED_POOL_PER_CLIENT = 16


def closed_sequences(mix: dict, seed: int, clients: int) -> list[list[dict]]:
    """Closed loop: for each client the cycle of requests it sends one
    after another. One stratified pool, dealt round robin."""
    pool = _sized(mix, clients * CLOSED_POOL_PER_CLIENT, seed, 30)
    seqs: list[list[dict]] = [[] for _ in range(clients)]
    for i, (plen, olen) in enumerate(pool):
        seqs[i % clients].append(
            {"index": i, "prompt_len": plen, "max_tokens": olen}
        )
    return seqs


def num_clients(mix: dict, engine_cfg: dict) -> int:
    c = mix.get("clients", 1)
    return int(engine_cfg[c]) if isinstance(c, str) else int(c)


def prompt_length_range(mix: dict) -> tuple[int, int]:
    spec = mix["prompt_tokens"]
    if spec["dist"] == "fixed":
        return spec["value"], spec["value"]
    return spec["low"], spec["high"]
