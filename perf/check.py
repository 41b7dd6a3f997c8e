"""How `correct` is decided: what the window served, against the reference.

Once the window has closed and the program's state is freed, a sample of the
requests the window finished (drawn from the seed, the longest always in it)
is run through the plain float32 reference, once per request, over the prompt
followed by the tokens that were served. At every served position the
reference gives a best logit and the served token's logit; the difference is
the gap. Greedy serving in a sound program puts a token first that the
reference puts first or nearly first, so its gaps are rounding: small and
rare. Compared, each beside its limit from the configuration's file:

- `max_gap`: the widest gap over the sample;
- `mean_gap`: the mean gap over the sample's served tokens (0 wherever the
  served token is the reference's first);
- `flip_share`: the share of served tokens that are not the reference's first
  (printed, not compared: it saturates, so a lower precision moves it by
  less than three times);
- `short`: requests of the sample whose served tokens are not exactly the
  `max_tokens` they asked for (limit 0: no stop token exists).

The control (a study, not part of a run) is the same reference computed in a
lower precision: at each position of the same prompts and tokens, the gap of
the token that precision puts first.

`compared` in what is returned holds each number that decided `correct`
beside its limit (a number that could not be read is `None`, and over).
"""

from __future__ import annotations

import gc
import random

from perf import traffic

SAMPLE_REQUESTS = 6
SAMPLE_MIN_TOKENS = 600


def device_arrays(*owners) -> list:
    """Every device array that the attributes of `owners` hold, found by
    walking them and not by name: a cache with one latent pool, or with state
    beside its pages, is covered like the two page pools."""
    import jax

    return [leaf for o in owners if o is not None
            for leaf in jax.tree.leaves(dict(vars(o)))
            if isinstance(leaf, jax.Array) and not leaf.is_deleted()]


def free_engine(engine) -> None:
    """Give the device back before the reference runs, so the peak that is
    reported stays the program's and the reference fits."""
    for leaf in device_arrays(engine, engine.cache):
        leaf.delete()
    gc.collect()


def break_tokens(engine, every: int = 5) -> None:
    """The fault the second test plants: every `every`-th token is altered
    where the engine hands it out."""
    step = engine.step
    seen = [0]

    def broken():
        out = []
        for ev in step():
            seen[0] += 1
            if seen[0] % every == 0:
                ev = ev._replace(token=(ev.token + 1) % 251)
            out.append(ev)
        return out

    engine.step = broken


def sample(records: list[dict], seed: int) -> list[dict]:
    done = [r for r in records if r.get("ok") and r["token_ids"]]
    if not done:
        return []
    done.sort(key=lambda r: (r["index"], r.get("lap", 0)))
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["token_ids"]))
    rest = [r for r in done if r is not longest]
    random.Random(seed * 31 + 5).shuffle(rest)
    picked, tokens = [longest], len(longest["token_ids"])
    for r in rest:
        if len(picked) >= SAMPLE_REQUESTS and tokens >= SAMPLE_MIN_TOKENS:
            break
        if len(picked) >= 2 * SAMPLE_REQUESTS:
            break
        picked.append(r)
        tokens += len(r["token_ids"])
    return picked


def _gaps(np, logits, tokens):
    best = logits.max(axis=-1)
    got = logits[np.arange(len(tokens)), tokens]
    return best - got, logits.argmax(axis=-1)


def served_against_reference(reference, cfg, key, mix, records, seed, vocab,
                             controls=(), log=print) -> dict:
    import numpy as np

    limits = cfg.get("correct", {})
    picked = sample(records, seed)
    gaps, flips, short = [], 0, 0
    control = {q: {"gaps": [], "flips": 0} for q in controls}
    seqs, served_of = [], []
    for r in picked:
        served = [int(t) for t in r["token_ids"]]
        short += int(len(served) != r["max_tokens"])
        plen = r["prompt_len"]
        seq = traffic.prompt_tokens(seed, r["index"], plen, vocab) + served[:-1]
        seqs.append((seq, list(range(plen - 1, plen - 1 + len(served)))))
        served_of.append(np.asarray(served))
    # One shape for the whole cell: the mix's longest request, rounded up.
    _, longest_prompt = traffic.prompt_length_range(mix)
    longest_output = mix["output_tokens"].get(
        "high", mix["output_tokens"].get("value"))
    padding = {"pad_to": -(-(longest_prompt + longest_output) // 256) * 256,
               "rows_pad": -(-longest_output // 128) * 128}
    logits = [np.asarray(x)
              for x in reference.forward(cfg, key, seqs, **padding)]
    firsts = []
    for lg, served in zip(logits, served_of):
        g, first = _gaps(np, lg, served)
        gaps.extend(g.tolist())
        flips += int((first != served).sum())
        firsts.append(first)
    for q in controls:
        lower = [np.asarray(x) for x in reference.forward(cfg, key, seqs, quant=q, **padding)]
        for lg, lq, first in zip(logits, lower, firsts):
            gq, _ = _gaps(np, lg, lq.argmax(axis=-1))
            control[q]["gaps"].extend(gq.tolist())
            control[q]["flips"] += int((lq.argmax(axis=-1) != first).sum())
    n = len(gaps)
    readings = {
        "max_gap": max(gaps) if gaps else None,
        "mean_gap": sum(gaps) / n if n else None,
        "flip_share": flips / n if n else None,
        "short": short,
    }
    ok = bool(picked)
    compared = {}
    for name, value in readings.items():
        if name not in limits:
            log(f"correct: {name} = {_fmt(value)}  (read, not compared)")
            continue
        limit = limits[name]
        within = value is not None and value <= limit
        ok = ok and within
        compared[name] = [value, limit]
        log(f"correct: {name} = {_fmt(value)}  limit {limit}  "
            f"{'ok' if within else 'OVER'}")
    log(f"correct: sample of {len(picked)} requests, {n} served tokens")
    control = {q: {"max_gap": max(c["gaps"]), "mean_gap": sum(c["gaps"]) / n,
                   "flip_share": c["flips"] / n}
               for q, c in control.items()} if n else {}
    for q, c in control.items():
        log(f"control {q}: " + "  ".join(f"{k} = {_fmt(v)}" for k, v in c.items()))
    return {"correct": ok, **readings, "tokens": n, "compared": compared,
            "control": control}


def _fmt(value) -> str:
    return "unread" if value is None else f"{value:.6g}"
