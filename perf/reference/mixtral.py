"""Plain reference for the Mixtral architecture (MixtralForCausalLM).

The Mistral block (perf/reference/mistral.py, the benchmark's own file) with
the MLP replaced by the published sparse mixture: a linear router over
`num_local_experts`, the `num_experts_per_tok` largest logits selected, a
softmax over the SELECTED logits only (renormalised, as published), and the
sum of the selected experts' SwiGLU outputs weighted by it. Float32,
"highest", one expert's weights at a time, each expert run on the tokens
routed to it and on no other (gathered, computed, added back: the published
`index_add` form).

**Routes.** Every layer has a router, so "routed layers" are all layers, in
order. The **selection score** is the router logit: the k largest are
selected, and the weights are the softmax of the selected logits. With
`routes` the forward takes, for each sequence, the expert sets it is GIVEN
(`[rows, routed layers, k]` global expert ids, one row a position, or None to
let that sequence take its own) and weights them by the same rule over the
given set. It then also returns what it would have taken itself and how far
the given set trails it (`forward` below). A lower precision's own sets come
from `forward(..., quant=q, routes=[None, ...])`.

Imports nothing of the program. Weights come from the seed, per layer and
per expert, and `served_params` stacks them as the program's loader does:
`w_gate`/`w_up` `[layers, experts, E, M]`, `w_down` `[layers, experts, M, E]`,
`router` `[layers, E, experts]`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import mistral as base
from perf.reference.mistral import _Frozen, _f32, matmul, rms_norm


def expert_weights(hf: dict, lkey, expert) -> dict:
    s = base.sizes(hf)
    k = jax.random.split(jax.random.fold_in(lkey, 100 + expert), 3)
    return {
        "w_gate": base._normal(k[0], (s["E"], s["M"])),
        "w_up": base._normal(k[1], (s["E"], s["M"])),
        "w_down": base._normal(k[2], (s["M"], s["E"])),
    }


def shared_weights(hf: dict, seed_key, layer) -> dict:
    """Attention, norms and router of one block."""
    lkey = base.layer_key(seed_key, layer)
    w = base.attn_weights(hf, lkey)
    w["router"] = base._normal(
        jax.random.fold_in(lkey, 9),
        (hf["hidden_size"], hf["num_local_experts"]),
    )
    return w


def layer_weights(hf: dict, seed_key, layer) -> dict:
    lkey = base.layer_key(seed_key, layer)
    experts = jax.vmap(lambda x: expert_weights(hf, lkey, x))(
        jnp.arange(hf["num_local_experts"], dtype=jnp.int32)
    )
    return {**shared_weights(hf, seed_key, layer), **experts}


def served_params(hf: dict, seed_key) -> dict:
    return base.served_params(hf, seed_key, layer_fn=layer_weights)


# Leaves of `served_params` whose last axis is the experts: what the planted
# fault `--break-path route` permutes (perf/check.py:break_router).
ROUTER_LEAVES = ("router",)
DISPATCH_BLOCK = 128  # an expert's tokens are padded to a multiple of this


def route(hf: dict, h, router, given, rows, quant=None):
    """One sequence's routing in one layer. `given` [T, k] holds the sets to
    take in its first `rows` rows; every later row takes its own. Returns
    the sets taken [T, k], the dense weights [T, experts] (softmax over the
    taken logits, 0 elsewhere), the reference's own sets [T, k] (best
    first) and the trail [T]: the own k-th logit minus the lowest logit of
    the taken set, which is 0 wherever the two are equal as sets."""
    logits = matmul(h, router, quant)
    ownv, own = jax.lax.top_k(logits, hf["num_experts_per_tok"])
    follow = (jnp.arange(h.shape[0]) < rows)[:, None]
    sets = jnp.where(follow, given, own)
    taken = jnp.take_along_axis(logits, sets, axis=-1)
    probs = jax.nn.softmax(taken, axis=-1)
    onehot = jax.nn.one_hot(sets, hf["num_local_experts"], dtype=jnp.float32)
    weights = jnp.einsum("tk,tkx->tx", probs, onehot)
    return sets, weights, own, ownv[:, -1] - taken.min(axis=-1)


def expert_apply(hf: dict, h, w, quant=None):
    gate = jax.nn.silu(matmul(h, w["w_gate"], quant))
    return matmul(gate * matmul(h, w["w_up"], quant), w["w_down"], quant)


def dispatch(hf: dict, x, h, w, weights, expert, idx, live, quant=None):
    """x + this expert's weighted output on the tokens `idx` (padded: a
    padded entry is not `live` and adds nothing)."""
    out = expert_apply(hf, h[idx], w, quant) * (weights[idx, expert] * live)[:, None]
    return x.at[idx].add(out)


_make_shared = jax.jit(shared_weights, static_argnums=0)
_make_expert = jax.jit(
    lambda hf, seed_key, layer, x: expert_weights(
        hf, base.layer_key(seed_key, layer), x
    ),
    static_argnums=0,
)
_route = jax.jit(route, static_argnums=(0, 5))
_dispatch = jax.jit(dispatch, static_argnums=(0, 8))


def layer_apply(hf, seed_key, layer, xs, quant, given, lengths):
    """One block on every sequence of `xs` (each [T, E], the first
    `lengths[i]` positions real). `given[i]` is None or the [rows, k] sets
    sequence i has to take in this layer. Returns the new `xs` and, per
    sequence, the own sets [length, k] and the trail [length]."""
    f, k = _Frozen(hf), hf["num_experts_per_tok"]
    w = _f32(_make_shared(f, seed_key, layer))
    xs = [base.attention_jit(f, x, w, quant) for x in xs]
    hs = [rms_norm(x, w["post_attn_norm"], hf["rms_norm_eps"]) for x in xs]
    taken, weights, own, trail = [], [], [], []
    for h, g, n in zip(hs, given, lengths):
        rows = 0 if g is None else len(g)
        if rows > n:
            raise ValueError(f"{rows} rows of routes for {n} positions")
        padded = np.zeros((h.shape[0], k), np.int32)
        if rows:
            padded[:rows] = g
        s, wt, o, t = _route(f, h, w["router"], padded, rows, quant)
        taken.append(np.asarray(s)[:n])
        weights.append(wt)
        own.append(np.asarray(o)[:n])
        trail.append(np.asarray(t)[:n])
    for expert in range(hf["num_local_experts"]):
        we = _f32(_make_expert(f, seed_key, layer, expert))
        for i, (h, s) in enumerate(zip(hs, taken)):
            (idx,) = np.nonzero((s == expert).any(axis=-1))
            if not len(idx):
                continue
            size = min(h.shape[0], -(-len(idx) // DISPATCH_BLOCK) * DISPATCH_BLOCK)
            padded = np.zeros(size, np.int32)
            padded[:len(idx)] = idx
            live = (np.arange(size) < len(idx)).astype(np.float32)
            xs[i] = _dispatch(f, xs[i], h, we, weights[i], expert, padded,
                              live, quant)
    return xs, own, trail


def forward(hf: dict, seed_key, seqs, quant=None, routes=None, **padding):
    """Logits as `mistral.forward` gives them. With `routes` (one entry a
    sequence: `[rows, routed layers, k]` expert ids to take, rows = the
    sequence's tokens, or None for its own) it returns
    `(logits, own, trail)`: per sequence the reference's own sets
    `[rows, routed layers, k]` and the trail `[rows, routed layers]`."""
    lengths = [len(tokens) for tokens, _ in seqs]
    own, trail = [], []

    def apply(hf, seed_key, layer, xs, quant=None):
        given = [None if r is None else np.asarray(r)[:, layer]
                 for r in routes or [None] * len(seqs)]
        xs, o, t = layer_apply(hf, seed_key, layer, xs, quant, given, lengths)
        own.append(o)
        trail.append(t)
        return xs

    logits = base.forward(hf, seed_key, seqs, quant, apply, **padding)
    if routes is None:
        return logits
    per_seq = lambda layers: [  # noqa: E731
        np.stack([one[i] for one in layers], axis=1) for i in range(len(seqs))]
    return logits, per_seq(own), per_seq(trail)


sizes = base.sizes
