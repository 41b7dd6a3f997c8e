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

**A configuration that routes tokens to experts.** With seeded weights a
router's k-th and (k+1)-th scores lie within the served precision's rounding
for some tokens in every layer; the program takes the other expert there, and
a reference that took its own would compare two different computations. So
the timed run asks the program for the expert sets it took (`kubeai_routes`,
docs/concepts/expert-routes.md), and where the reference's `forward` has a
`routes` parameter it is made to FOLLOW them: each routed layer computes with
the given sets, and the reference says what it would have taken itself. The
three numbers above are then read on the followed computation, and three
more hold the router:

- `route_rows_bad`: sampled requests whose blocks are not exactly rows
  `0 .. P+N-2` of shape `[routed layers, k]` as `/v1/state` says (limit 0;
  `assemble_routes`). Such a request is not followed.
- `followed_share`: the share of (position, routed layer) decisions whose
  given set is not the reference's own, as sets. Rounding flips a few per
  cent; a router that takes other experts, or a lower precision, many more.
- `route_trail`: the `ROUTE_TRAIL_QUANTILE` quantile, over all decisions, of
  how far the given set trails the reference's own: the reference's k-th
  selection score minus the lowest selection score in the given set (0 where
  the sets are equal). A sound flip trails by rounding; a wrong expert by the
  spread of the router's scores.

The control of a routed reference takes its own sets in the lower precision;
the float32 reference follows those, and the same six numbers are read.

**A generator of its own.** The comparison above assumes one token a
forward, left to right: served token i is held against row `P-1+i` of one
forward over `prompt + served[:-1]`. A family that generates otherwise (a
block of positions denoised over several forwards and committed by
confidence; tokens drafted and verified) chooses token i at a forward and a
row that depend on decisions taken inside the served precision's rounding,
as a router's k-th score is. So its reference FOLLOWS what the program
handed over, and says itself what to ask for and how to replay it. Such a
module has (perf/README.md, "A generator of its own"):

- `HANDOVER`: a tuple of request flags. The timed run sets each `true` in
  every request and keeps each chunk's blocks of that name, in arrival order,
  undecoded, under `handover[<flag>]` on the record;
- `DECISIONS`: the kinds of decision it follows (`routes`, `order`, ...);
- `replay(cfg, key, requests, *, state, quant=None, follow=None, pad_to,
  rows_pad)`: a request is `{"prompt", "served", "handover"}`, `state` is the
  server's `/v1/state`. A request comes back as None where its hand-over
  breaks the family's own rule (missing, surplus, mis-shaped, out of order:
  never cut or padded), else as `{"logits", "own", <kind>: {"differs",
  "trail"}, ...}`: `logits` float32 `[N, vocab]`, row i the reference's
  logits at the forward and row that, by the hand-over, chose served token i,
  on the state the hand-over describes, every handed-over decision followed;
  `own` what it would have decided itself at each decision, in the form
  `follow` takes; `differs` a boolean a decision (the given one is not its
  own) and `trail` how far the given one trails its own on the score the
  family selects by (0 where equal). `follow=None` follows the request's
  hand-over, `follow="own"` decides for itself on the served state, a list
  (one entry a request) follows those.

What stays here: the sample, `short`, the three gap readings from the
returned rows against the served tokens, `decisions_bad` (sampled requests
that came back None; limit 0), and a kind: `<kind>_followed_share` (the mean
of `differs`), `<kind>_trail` (its `ROUTE_TRAIL_QUANTILE` quantile) and
`<kind>_trail_max` (printed). The control is `replay(quant=q, follow="own")`
in the program's place and then the float32 `replay(follow=<its own>)`.
`compared_names` says which limits a configuration of each kind of family has
to state.

`compared` in what is returned holds each number that decided `correct`
beside its limit (a number that could not be read is `None`, and over).
"""

from __future__ import annotations

import base64
import gc
import inspect
import random

from perf import traffic

SAMPLE_REQUESTS = 6
SAMPLE_MIN_TOKENS = 600
ROUTE_TRAIL_QUANTILE = 0.99
ROUTE_DTYPES = {"uint8": "<u1", "uint16": "<u2", "uint32": "<u4"}


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


def break_router(params, reference):
    """The fault `--break-path route` plants, before the engine is built:
    the expert columns of every router leaf (the reference module's
    `ROUTER_LEAVES`, last axis = experts) are rolled by one, so the program
    takes, and hands over, other experts than the reference's router would."""
    import jax
    import jax.numpy as jnp

    names = getattr(reference, "ROUTER_LEAVES", ())
    hit = []

    def roll(path, leaf):
        if getattr(path[-1], "key", None) not in names:
            return leaf
        hit.append(path)
        return jax.device_put(jnp.roll(leaf, 1, axis=-1), leaf.sharding)

    params = jax.tree_util.tree_map_with_path(roll, params)
    if not hit:
        raise SystemExit("perf: --break-path route found no router leaf "
                         f"{names} in the served weights")
    return params


def takes_routes(reference) -> bool:
    """A reference whose `forward` has a `routes` parameter is a routed
    family's; one without is a dense family's and is called as ever."""
    return "routes" in inspect.signature(reference.forward).parameters


def replays(reference) -> bool:
    """A reference with `replay` is a family's that generates in its own
    way: it is asked to replay what the program handed over."""
    return hasattr(reference, "replay")


DENSE = ("max_gap", "mean_gap", "short")


def compared_names(reference) -> set[str]:
    """The limits a configuration has to state under `correct`, learned
    from what its reference module has."""
    if replays(reference):
        return {*DENSE, "decisions_bad", *(
            f"{kind}_{what}" for kind in reference.DECISIONS
            for what in ("followed_share", "trail"))}
    if takes_routes(reference):
        return {*DENSE, "route_rows_bad", "followed_share", "route_trail"}
    return set(DENSE)


def assemble_routes(blocks, positions: int, moe: dict):
    """The rows `[positions, routed layers, k]` of one request from its
    `kubeai_routes` blocks in arrival order, or None where they break the
    row rule: every block of the shape `/v1/state` says, expert ids below
    `experts` and distinct in a set, the first block at position 0, each
    later one starting where the rows held so far end (a gap is missing
    rows), and exactly `positions` rows at the end (neither cut nor
    padded). A block may step back only as a re-admission after a
    preemption does: from position 0, over everything held so far; the
    later rows are the ones the cache holds."""
    import numpy as np

    shape = [int(moe["routed_layers"]), int(moe["k"])]
    rows = np.zeros((0, *shape), np.int64)
    for block in blocks or ():
        try:
            dtype = np.dtype(ROUTE_DTYPES[block["dtype"]])
            start, n = int(block["start"]), int(block["rows"])
            raw = base64.b64decode(block["data"])
            if list(block["shape"]) != shape or n < 1 or (
                    len(raw) != n * shape[0] * shape[1] * dtype.itemsize):
                return None
            new = np.frombuffer(raw, dtype).reshape(n, *shape).astype(np.int64)
        except (KeyError, TypeError, ValueError):
            return None
        if start == len(rows):
            rows = np.concatenate([rows, new])
        elif start == 0 and n >= len(rows):
            rows = new
        else:
            return None
    ordered = np.sort(rows, axis=-1)
    if len(rows) != positions or (rows >= int(moe["experts"])).any() or (
            ordered[..., 1:] == ordered[..., :-1]).any():
        return None
    return rows


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


def _followed(np, differs, trails, names) -> tuple[dict, int]:
    """`names` = (share, trail, largest trail): the share of decisions whose
    given one is not the reference's own, the trail's quantile and its
    largest; and the number of decisions they were read over."""
    differs = np.concatenate([np.ravel(d) for d in differs] or [np.zeros(0)])
    trails = np.concatenate([np.ravel(t) for t in trails] or [np.zeros(0)])
    if not differs.size or differs.size != trails.size:
        return dict.fromkeys(names), 0
    return dict(zip(names, (
        float(differs.mean()),
        float(np.quantile(trails, ROUTE_TRAIL_QUANTILE)),
        float(trails.max())))), int(differs.size)


def _route_readings(np, given, own, trail) -> tuple[dict, int]:
    """Over the followed sequences of a routed reference."""
    pairs = [(g, o, t) for g, o, t in zip(given, own, trail) if g is not None]
    return _followed(
        np, [(np.sort(g, -1) != np.sort(o, -1)).any(-1) for g, o, _ in pairs],
        [t for _, _, t in pairs],
        ("followed_share", "route_trail", "route_trail_max"))


def _decision_readings(np, kinds, results) -> tuple[dict, int]:
    """Over the replayed requests of a generator's reference, a kind."""
    out, decisions = {}, 0
    for kind in kinds:
        got = [r[kind] for r in results if r is not None]
        of_kind, n = _followed(
            np, [g["differs"] for g in got], [g["trail"] for g in got],
            (f"{kind}_followed_share", f"{kind}_trail", f"{kind}_trail_max"))
        out.update(of_kind)
        decisions += n
    return out, decisions


def _follower(np, reference, cfg, key, seqs, given, requests, state, padding):
    """How a family's reference is run. `run(quant=None, follow=None)` gives
    the logits a request (None for one that could not be replayed), what the
    reference decided itself (for a later `follow`; None for a family that
    follows nothing), the readings of the decisions it followed, and their
    number. `follow=None` follows what the program handed over, `"own"`
    nothing, a list (one entry a request) those."""
    if replays(reference):
        def run(quant=None, follow=None):
            results = reference.replay(cfg, key, requests, state=state,
                                       quant=quant, follow=follow, **padding)
            return ([None if r is None else np.asarray(r["logits"]) for r in results],
                    [None if r is None else r["own"] for r in results],
                    *_decision_readings(np, reference.DECISIONS, results))
    elif given is not None:
        def run(quant=None, follow=None):
            sets = (given if follow is None else
                    [None] * len(seqs) if follow == "own" else follow)
            logits, own, trail = reference.forward(
                cfg, key, seqs, quant=quant, routes=sets, **padding)
            return ([np.asarray(x) for x in logits], own,
                    *_route_readings(np, sets, own, trail))
    else:
        def run(quant=None, follow=None):
            logits = reference.forward(cfg, key, seqs, quant=quant, **padding)
            return [np.asarray(x) for x in logits], None, {}, 0
    return run


def served_against_reference(reference, cfg, key, mix, records, seed, vocab,
                             controls=(), log=print, moe=None, state=None) -> dict:
    """`vocab` is what the prompts were drawn from. `moe` is `/v1/state`'s
    block of an engine that hands its routes over (the timed run then asked
    for them), None for any other engine; `state` is `/v1/state` whole, for a
    reference that replays."""
    import numpy as np

    limits = cfg.get("correct", {})
    replayed = replays(reference)
    routed = not replayed and bool(moe) and takes_routes(reference)
    picked = sample(records, seed)
    gaps, flips, short = [], 0, 0
    seqs, served_of, requests = [], [], []
    given = [] if routed else None
    for r in picked:
        served = [int(t) for t in r["token_ids"]]
        short += int(len(served) != r["max_tokens"])
        plen = r["prompt_len"]
        prompt = traffic.prompt_tokens(seed, r["index"], plen, vocab)
        seqs.append((prompt + served[:-1],
                     list(range(plen - 1, plen - 1 + len(served)))))
        served_of.append(np.asarray(served))
        requests.append({"prompt": prompt, "served": served,
                         "handover": r.get("handover") or {}})
        if routed:
            given.append(assemble_routes(r.get("routes"), len(seqs[-1][0]), moe))
    # One shape for the whole cell: the mix's longest request, rounded up.
    _, longest_prompt = traffic.prompt_length_range(mix)
    longest_output = mix["output_tokens"].get(
        "high", mix["output_tokens"].get("value"))
    padding = {"pad_to": -(-(longest_prompt + longest_output) // 256) * 256,
               "rows_pad": -(-longest_output // 128) * 128}
    run = _follower(np, reference, cfg, key, seqs, given, requests, state, padding)

    logits, _, of_decisions, decisions = run()
    firsts = []
    for lg, served in zip(logits, served_of):
        if lg is None:
            firsts.append(None)
            continue
        g, first = _gaps(np, lg, served)
        gaps.extend(g.tolist())
        flips += int((first != served).sum())
        firsts.append(first)
    n = len(gaps)
    readings = {
        "max_gap": max(gaps) if gaps else None,
        "mean_gap": sum(gaps) / n if n else None,
        "flip_share": flips / n if n else None,
        "short": short,
    }
    if routed or replayed:
        # Requests whose hand-over broke the rule: a routed one is not
        # followed, a replayed one is left out of every other reading.
        bad = sum(g is None for g in (given if routed else logits))
        log(f"correct: {'routes' if routed else ', '.join(reference.DECISIONS)} "
            f"followed over {decisions} decisions of {len(picked) - bad} requests")
        readings.update({"route_rows_bad" if routed else "decisions_bad": bad},
                        **of_decisions)
    control = {}
    for q in controls if n else ():
        # The lower precision in the program's place. One that follows
        # decisions takes its own, which the float32 reference then follows.
        lower, took, _, _ = run(quant=q, follow="own")
        full, _, of_q, _ = (logits, None, {}, 0) if took is None else run(follow=took)
        gq, fq = [], 0
        for lg, lq, first in zip(full, lower, firsts):
            if first is None:
                continue
            gq.extend(_gaps(np, lg, lq.argmax(axis=-1))[0].tolist())
            fq += int((lq.argmax(axis=-1) != first).sum())
        control[q] = {"max_gap": max(gq), "mean_gap": sum(gq) / n,
                      "flip_share": fq / n, **of_q}
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
    for name in limits:
        if name not in readings:  # a limit nothing was read for is over
            ok = False
            compared[name] = [None, limits[name]]
            log(f"correct: {name} = unread  limit {limits[name]}  OVER")
    log(f"correct: sample of {len(picked)} requests, {n} served tokens")
    for q, c in control.items():
        log(f"control {q}: " + "  ".join(f"{k} = {_fmt(v)}" for k, v in c.items()))
        over = [k for k, v in c.items()
                if k in limits and v is not None and v > limits[k]]
        log(f"control {q} lands over: {', '.join(over) or 'NO LIMIT'}")
    # What was read of the decisions followed, in per cent, for a per-layer
    # metric of kind `observed`: a router's is `route_followed_share`, a
    # generator's kinds keep their names.
    observed = {("route_" + name if routed else name): 100.0 * value
                for name, value in of_decisions.items()
                if name.endswith("followed_share") and value is not None}
    return {"correct": ok, **readings, "tokens": n, "compared": compared,
            "control": control, "observed": observed}


def _fmt(value) -> str:
    return "unread" if value is None else f"{value:.6g}"
