"""A stub family that generates by diffusion over blocks, numpy only: what a
`model_config` PR for such a family puts at `perf/reference/<family>.py`
(tests/perf/test_generator.py copies this file there, in a temporary tree).

**The model.** Token and position embeddings, then layers of single-head
attention and a mixture of `X` = 8 experts of which a row takes `K` = 2 (the
selection score is the router logit; weights are the softmax over the taken
set), a norm and a head. A forward runs some rows against the cache (K and V
of every earlier position, a layer) with attention that is full inside the
rows.

**Generation.** The prompt is one forward, which writes its K and V. Then
blocks of `B` = 4 positions: a block starts as `mask_token_id` in every row;
each denoising forward runs the B rows against the cache and COMMITS, of the
rows still masked, the one whose top-token probability is highest and every
one over `confidence_threshold` (so at least B / T a forward, T = 4 forwards
at the most), each to its top token; once no mask is left one more forward
over the finished block writes its K and V. Every forward routes every row
anew: up to (T + 1) x B route rows a block, not B.

**The hand-over** (`HANDOVER`: the request flag `stub_forwards`). A chunk
carries a list of blocks, one a forward, in the order they ran:
`{"start", "rows", "commit", "routes"}`: the first row's position, the number
of rows, the offsets of the rows this forward committed (`[]` for the prompt's
forward and a block's last), and the expert sets taken, `[rows, layers, K]`.
**The rule** (`_rule`): the prompt's forward first (start 0, P rows);
then, a block, 1 to T denoising forwards whose commits are non-empty and
together name each of the B rows once, and one forward with no commit;
N / B blocks, nothing missing, nothing twice, nothing left over; every set K
distinct ids below X. A hand-over that breaks it is not replayed (None).

**Decisions** (`DECISIONS`). `routes`: one a (forward, row, layer); the trail
is the own K-th router logit minus the lowest logit of the given set.
`order`: one a denoising forward; the given commit differs where it is not
the set the rule above would commit on that state, and trails by the larger
of: how far a committed row that the rule would leave masked lies under the
nearer of the rule's two bars (the best masked row's confidence, the
threshold); how far a row that was left masked lies over the threshold.

`generate` is the stand-in program: it serves and writes the hand-over.
"""

import numpy as np

HANDOVER = ("stub_forwards",)
DECISIONS = ("routes", "order")
ROUTER_LEAVES = ("router",)
X, K = 8, 2
B, T = 4, 4
MAX_POSITIONS = 256


def _weights(hf, key):
    rng = np.random.default_rng(int(key))
    E, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    n = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    sq = np.sqrt(E)
    return {"embed": n(V, E), "pos": n(MAX_POSITIONS, E) * 0.5,
            "head": n(E, V) * 4.0 / sq,
            "wq": n(L, E, E) / sq, "wk": n(L, E, E) / sq, "wv": n(L, E, E) / sq,
            "wo": n(L, E, E) / sq, "router": n(L, E, X) * 2.0 / sq,
            "experts": n(L, X, E, E) / sq}


def _low(x, quant):
    """A lower precision: fewer mantissa bits in every activation."""
    if quant is None:
        return x
    bits = {"bf16": 8, "fp8": 3, "int8": 5}[quant]
    m, e = np.frexp(x)
    return np.ldexp(np.round(m * 2**bits) / 2**bits, e).astype(np.float32)


def _norm(x):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)


def _softmax(x):
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _forward(w, hf, cache, ids, start, quant, given):
    """The rows `ids` at positions `start..` against `cache` (a layer: K and
    V of every earlier position). `given` is None or the `[rows, layers, K]`
    sets to take. Returns the logits `[rows, V]`, the rows' own K and V a
    layer, the own sets `[rows, layers, K]` and the trail `[rows, layers]`."""
    E = hf["hidden_size"]
    x = _low(w["embed"][np.asarray(ids)] + w["pos"][start:start + len(ids)], quant)
    kv, own, trail = [], [], []
    for j in range(hf["num_hidden_layers"]):
        h = _low(_norm(x), quant)
        k, v = _low(h @ w["wk"][j], quant), _low(h @ w["wv"][j], quant)
        kv.append((k, v))
        keys = np.concatenate([cache[j][0], k])
        vals = np.concatenate([cache[j][1], v])
        p = _softmax(_low(h @ w["wq"][j], quant) @ keys.T / np.sqrt(E))
        x = x + _low((p @ vals) @ w["wo"][j], quant)
        h = _low(_norm(x), quant)
        score = _low(h @ w["router"][j], quant)
        mine = np.argsort(-score, axis=-1, kind="stable")[:, :K]
        sets = mine if given is None else np.asarray(given)[:, j]
        taken = np.take_along_axis(score, sets, -1)
        trail.append(np.take_along_axis(score, mine, -1)[:, -1] - taken.min(-1))
        own.append(mine)
        out = np.einsum("te,tkef->tkf", h, w["experts"][j][sets])
        x = x + _low(np.einsum("tk,tkf->tf", _softmax(taken), np.tanh(out)), quant)
    logits = _low(_norm(x), quant) @ w["head"]
    return logits, kv, np.stack(own, 1), np.stack(trail, 1)


def _extend(cache, kv):
    return [(np.concatenate([ck, k]), np.concatenate([cv, v]))
            for (ck, cv), (k, v) in zip(cache, kv)]


def _empty_cache(hf):
    z = np.zeros((0, hf["hidden_size"]), np.float32)
    return [(z, z)] * hf["num_hidden_layers"]


def _commit(hf, conf, masked):
    """The rule: of the masked rows, the most confident and every one over
    the threshold."""
    rows = np.flatnonzero(masked)
    best = rows[np.argmax(conf[rows])]
    return sorted({int(best), *(int(r) for r in rows
                                if conf[r] > hf["confidence_threshold"])})


def _order_trail(hf, conf, masked, given, mine):
    """How far the given commit trails the rule's own: a committed row the
    rule would leave masked trails whichever of the rule's two bars is
    nearer (the best masked row's confidence, the threshold); a row over the
    threshold that was left masked trails by what it is over."""
    bar = min(conf[np.flatnonzero(masked)].max(), hf["confidence_threshold"])
    behind = [bar - conf[r] for r in given if r not in mine]
    left = [conf[r] - hf["confidence_threshold"] for r in mine if r not in given]
    return float(max([0.0, *behind, *left]))


def generate(hf, key, prompt, n_out, quant=None):
    """Serve `n_out` tokens (a multiple of B) after `prompt`: the tokens and
    the hand-over's blocks, one a forward."""
    w, mask = _weights(hf, key), hf["mask_token_id"]
    wire = lambda start, commit, own: {  # noqa: E731
        "start": start, "rows": len(own), "commit": commit, "routes": own.tolist()}
    _, kv, own, _ = _forward(w, hf, _empty_cache(hf), prompt, 0, quant, None)
    cache, blocks, served = _extend(_empty_cache(hf), kv), [wire(0, [], own)], []
    for start in range(len(prompt), len(prompt) + n_out, B):
        ids, masked = [mask] * B, np.ones(B, bool)
        while masked.any():
            logits, _, own, _ = _forward(w, hf, cache, ids, start, quant, None)
            commit = _commit(hf, _softmax(logits).max(-1), masked)
            for r in commit:
                ids[r], masked[r] = int(logits[r].argmax()), False
            blocks.append(wire(start, commit, own))
        _, kv, own, _ = _forward(w, hf, cache, ids, start, quant, None)
        cache = _extend(cache, kv)
        blocks.append(wire(start, [], own))
        served += ids
    return served, blocks


def _parse(blocks):
    """The hand-over's blocks as the forwards `follow` takes: `{"start",
    "commit", "routes"}` each; None where a block is mis-shaped."""
    forwards = []
    for b in blocks or ():
        try:
            routes = np.asarray(b["routes"])
            forwards.append({"start": int(b["start"]), "routes": routes,
                             "commit": [int(r) for r in b["commit"]]})
            if len(routes) != int(b["rows"]) or routes.dtype.kind not in "iu":
                return None
        except (KeyError, TypeError, ValueError):
            return None
    return forwards


def _rule(hf, n_prompt, n_served, forwards, experts):
    """Whether `forwards` are a whole generation of `n_served` tokens after
    `n_prompt`, forward for forward (the module's docstring has the rule)."""
    if n_served % B or not forwards:
        return False
    for f in forwards:
        routes = f["routes"]
        ordered = np.sort(routes, -1)
        if routes.shape[1:] != (hf["num_hidden_layers"], K) or (
                (routes < 0).any() or (routes >= experts).any()
                or (ordered[..., 1:] == ordered[..., :-1]).any()):
            return False
    shape = lambda f: (f["start"], len(f["routes"]))  # noqa: E731
    if shape(forwards[0]) != (0, n_prompt) or forwards[0]["commit"]:
        return False
    at = 1
    for start in range(n_prompt, n_prompt + n_served, B):
        masked, denoising = set(range(B)), 0
        while masked:
            if at >= len(forwards) or shape(forwards[at]) != (start, B):
                return False
            commit = forwards[at]["commit"]
            if not commit or len(set(commit)) != len(commit) or not set(commit) <= masked:
                return False
            masked -= set(commit)
            denoising, at = denoising + 1, at + 1
        if denoising > T or at >= len(forwards) or (
                shape(forwards[at]) != (start, B) or forwards[at]["commit"]):
            return False
        at += 1
    return at == len(forwards)


def _replay_one(w, hf, prompt, served, forwards, quant):
    """Teacher-forced on the served tokens. `forwards` are followed, or None:
    every decision is its own."""
    mask = hf["mask_token_id"]
    given = None if forwards is None else iter(forwards)
    own = []
    differs, trail = {"routes": [], "order": []}, {"routes": [], "order": []}

    def run(cache, ids, start):
        f = None if given is None else next(given)
        logits, kv, mine, behind = _forward(
            w, hf, cache, ids, start, quant, None if f is None else f["routes"])
        sets = mine if f is None else f["routes"]
        differs["routes"].append((np.sort(sets, -1) != np.sort(mine, -1)).any(-1))
        trail["routes"].append(behind)
        own.append({"start": start, "commit": [], "routes": mine})
        return f, logits, kv

    _, _, kv = run(_empty_cache(hf), prompt, 0)
    cache, rows = _extend(_empty_cache(hf), kv), []
    for start in range(len(prompt), len(prompt) + len(served), B):
        tokens = served[start - len(prompt):][:B]
        ids, masked, chosen = [mask] * B, np.ones(B, bool), [None] * B
        while masked.any():
            f, logits, _ = run(cache, ids, start)
            conf = _softmax(logits).max(-1)
            mine = own[-1]["commit"] = _commit(hf, conf, masked)
            commit = mine if f is None else f["commit"]
            differs["order"].append(set(commit) != set(mine))
            trail["order"].append(_order_trail(hf, conf, masked, commit, mine))
            for r in commit:
                chosen[r], ids[r], masked[r] = logits[r], tokens[r], False
        _, _, kv = run(cache, ids, start)
        cache = _extend(cache, kv)
        rows += chosen
    return {"logits": np.stack(rows).astype(np.float32), "own": own,
            **{kind: {"differs": np.concatenate([np.ravel(d) for d in differs[kind]]),
                      "trail": np.concatenate([np.ravel(t) for t in trail[kind]])}
               for kind in DECISIONS}}


def replay(hf, key, requests, *, state, quant=None, follow=None, pad_to=0, rows_pad=0):
    w = _weights(hf, key)
    experts = int(((state or {}).get("moe") or {}).get("experts", X))
    out = []
    for i, request in enumerate(requests):
        prompt, served = request["prompt"], request["served"]
        forwards = None
        if follow != "own":
            forwards = (_parse(request["handover"].get(HANDOVER[0]))
                        if follow is None else follow[i])
            if forwards is None or not _rule(
                    hf, len(prompt), len(served), forwards, experts):
                out.append(None)
                continue
        out.append(_replay_one(w, hf, prompt, served, forwards, quant))
    return out
