"""Plain reference for the EXAONE-MoE architecture (ExaoneMoeForCausalLM,
`model_type` `exaone_moe`, e.g. K-EXAONE-236B-A23B): window and global attention
layers mixed, a leading dense layer, and routed layers whose router scores by a
sigmoid and selects on the score plus a bias, with one shared expert.

`E` = hidden size, eps = `rms_norm_eps`, `rms(x; w) = x * rsqrt(mean(x^2) +
eps) * w`. Block `l`: `h = x + Attn_l(rms(x))`, `y = h + FFN_l(rms(h))`. Final
`rms`, untied head, no bias anywhere.

**Attention** (`H` query heads, `KVH` key / value heads of `D`): `q = W_q x`,
`k = W_k x`, `v = W_v x`; `rms` over each head's `D` on q and k; rotary
(rotate-half, `rope_parameters.rope_theta`, all `D` dimensions) on q and k in
WINDOW layers and none in global layers; causal softmax attention at `D^-1/2`,
grouped-query, a key at position `j` visible to a query at `i` iff `i - W < j
<= i` in a window layer (`W` = `sliding_window`) and iff `j <= i` in a global
one; `W_o`. Layer `l` is global where `layer_types[l]` is `full_attention`
(the file keeps the published list whole; its first `num_hidden_layers`
entries are run).

**FFN.** Layers below `first_k_dense_replace`: `W_down(silu(W_gate x) * W_up
x)` at `intermediate_size`. Every other layer is routed: `s = sigmoid(W_r x)`
over ALL `router_num_experts` (float32); the `num_experts_per_tok` taken `T`
are the largest of `s + b` (`b` the selection bias, float32, one a router
column; with `n_group` = `topk_group` = 1 the group step is the identity);
weights `w_i = routed_scaling_factor * s_i / sum_{j in T} s_j`
(`norm_topk_prob`); `FFN(x) = sum_{i in T} w_i E_i(x) + E_shared(x)`, every `E`
a SwiGLU of `moe_intermediate_size` (the shared one of `num_shared_experts`
times that), the shared one ungated. **The expert share**, as in the program:
the file says how many experts are held here (`num_experts`), how wide the
router is (`router_num_experts`) and which share this is
(`expert_share_index`: global ids `index * num_experts ..`). The sum runs over
the taken experts that are HELD, under the weights of the whole taken set;
what the absent ones would add is left out, and that partial result goes on
to the next layer. Every share computes the shared expert and the dense
layer alike. An expert's weights are seeded by its GLOBAL id, so the shares
of one seed are the parts of one model (tests/unit/test_exaone_moe.py adds
them up). `vocab_size` is the slice of the vocabulary held here: a smaller
vocabulary.

**Routes.** Routed layer `j` is layer `j + first_k_dense_replace`; a leading
dense layer has no row. The **selection score** is `s + b`. With `routes` the
forward takes, for each sequence, the expert sets it is GIVEN (`[rows, routed
layers, k]` global ids, held or not) and weights them by the same rule over
the given set; it returns what it would have taken itself and the trail: its
own k-th `s + b` minus the lowest `s + b` of the given set.

**Departures from the published description**, each an `assumed` item of the
configuration's file: the pre-norm residual, the QK-norm and the rotary in
window layers only are the EXAONE-4 family's conventions, which `config.json`
does not state; the selection bias is assumed present, as in the family whose
router keys the config carries, and seeded uniform in +-0.05 (a trained one is
a load-balancing offset of that order) so that a program that drops it shows
in `followed_share` and `route_trail`; the checkpoint's one
multi-token-prediction layer moves no logit of the main model and is not
computed.

**What a window family brings beside this module**: which layers keep a ring
(`ModelFamily.kv_layers`), counts of its own (below: global layers by
resident tokens, window layers by at most `sliding_window` tokens a slot, held
experts by the count a step's rows touch), a reader kind for its grouped
products (`perf/reader_kinds/routed_experts.py`) and an AOT guard of its own
(`tests/perf/test_aot_kexaone.py`).

Float32, matmul precision "highest", no kernel, no cache, one sequence at a
time through attention (`lax.map`: a loop) in blocks of `QUERY_BLOCK` query
rows so that 4k positions fit, one expert at a time through the mixture, one
layer of weights at a time. Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import mistral as base
from perf.reference.mistral import _Frozen, _f32, _normal, matmul, rms_norm

# Leaves of `served_params` whose last axis is the experts the router scores:
# what the planted fault `--break-path route` rolls (the bias with its column).
ROUTER_LEAVES = ("router", "router_bias")
DISPATCH_BLOCK = 1024  # rows of one expert a call: one shape, whatever the load
QUERY_BLOCK = 256  # query rows of one block of scores: [H, 256, T] float32
BIAS_RANGE = 0.05  # the selection bias is drawn uniformly in +- this
BF16, F32 = 2, 4


def sizes(hf: dict) -> dict:
    layers, held = hf["num_hidden_layers"], hf["num_experts"]
    kinds = hf["layer_types"][:layers]
    if len(kinds) != layers:
        raise ValueError(f"layer_types names {len(kinds)} of {layers} layers")
    glob = sum(kind == "full_attention" for kind in kinds)
    dense = hf.get("first_k_dense_replace", 0)
    return {
        "E": hf["hidden_size"], "V": hf["vocab_size"], "NL": layers,
        "H": hf["num_attention_heads"], "KVH": hf["num_key_value_heads"],
        "D": hf["head_dim"], "W": hf["sliding_window"],
        "global": glob, "window": layers - glob,
        "dense": dense, "routed": layers - dense,
        "Md": hf["intermediate_size"], "M": hf["moe_intermediate_size"],
        "Ms": hf["moe_intermediate_size"] * hf.get("num_shared_experts", 1),
        "X": held, "XR": hf.get("router_num_experts", held),
        "first": hf.get("expert_share_index", 0) * held,
        "k": hf["num_experts_per_tok"],
        "theta": hf["rope_parameters"]["rope_theta"],
        "scale": hf.get("routed_scaling_factor", 1.0),
    }


def is_global(hf: dict, layer: int) -> bool:
    return hf["layer_types"][layer] == "full_attention"


def _flat(hf: dict) -> _Frozen:
    """The configuration as a static argument: nested groups left out, the
    one nested number the layers need brought up."""
    flat = {k: v for k, v in hf.items() if not isinstance(v, (dict, list))}
    return _Frozen({**flat, "rope_theta": hf["rope_parameters"]["rope_theta"]})


# ---- the seeded weights -----------------------------------------------------


def attn_weights(hf: dict, seed_key, layer) -> dict:
    E, H, KVH, D = (hf[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 4), 4)
    return {
        "input_norm": jnp.ones((E,), jnp.bfloat16),
        "wq": _normal(k[0], (E, H * D)),
        "wk": _normal(k[1], (E, KVH * D)),
        "wv": _normal(k[2], (E, KVH * D)),
        "wo": _normal(k[3], (H * D, E)),
        "q_norm": jnp.ones((D,), jnp.bfloat16),
        "k_norm": jnp.ones((D,), jnp.bfloat16),
    }


def dense_weights(hf: dict, seed_key, layer) -> dict:
    E, M = hf["hidden_size"], hf["intermediate_size"]
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 7), 3)
    return {
        "post_norm": jnp.ones((E,), jnp.bfloat16),
        "w_gate": _normal(k[0], (E, M)),
        "w_up": _normal(k[1], (E, M)),
        "w_down": _normal(k[2], (M, E)),
    }


def moe_weights(hf: dict, seed_key, layer) -> dict:
    """Norm, router, selection bias and shared expert of one routed layer."""
    E = hf["hidden_size"]
    XR = hf.get("router_num_experts", hf["num_experts"])
    Ms = hf["moe_intermediate_size"] * hf.get("num_shared_experts", 1)
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 5), 5)
    return {
        "post_norm": jnp.ones((E,), jnp.bfloat16),
        "router": _normal(k[0], (E, XR)),
        "router_bias": jax.random.uniform(
            k[1], (XR,), jnp.float32, -BIAS_RANGE, BIAS_RANGE),
        "shared_gate": _normal(k[2], (E, Ms)),
        "shared_up": _normal(k[3], (E, Ms)),
        "shared_down": _normal(k[4], (Ms, E)),
    }


def expert_weights(hf: dict, seed_key, layer, expert) -> dict:
    """One expert by its GLOBAL id."""
    E, M = hf["hidden_size"], hf["moe_intermediate_size"]
    k = jax.random.split(
        jax.random.fold_in(base.layer_key(seed_key, layer), 100 + expert), 3)
    return {
        "w_gate": _normal(k[0], (E, M)),
        "w_up": _normal(k[1], (E, M)),
        "w_down": _normal(k[2], (M, E)),
    }


def top_weights(hf: dict, seed_key) -> dict:
    E, V = hf["hidden_size"], hf["vocab_size"]
    k = jax.random.split(jax.random.fold_in(seed_key, 1), 2)
    return {
        "embed": _normal(k[0], (V, E)),
        "final_norm": jnp.ones((E,), jnp.bfloat16),
        "lm_head": _normal(k[1], (V, E)),
    }


def served_params(hf: dict, seed_key) -> dict:
    """The whole model in the program's layout: attention `[layers, ...]`,
    the leading dense layers' FFN `[dense layers, ...]`, norm, router, bias
    and shared expert `[routed layers, ...]`, the held experts `[routed
    layers, held, ...]`."""
    s = sizes(hf)
    every = jnp.arange(s["NL"], dtype=jnp.int32)
    held = s["first"] + jnp.arange(s["X"], dtype=jnp.int32)

    def over(fn, layers):
        return jax.lax.map(lambda l: fn(hf, seed_key, l), layers)

    return {
        **top_weights(hf, seed_key),
        "layers": {
            "attn": over(attn_weights, every),
            "dense": over(dense_weights, every[: s["dense"]]),
            "moe": over(moe_weights, every[s["dense"]:]),
            "experts": jax.lax.map(
                lambda l: jax.lax.map(
                    lambda x: expert_weights(hf, seed_key, l, x), held),
                every[s["dense"]:]),
        },
    }


# ---- the layers -------------------------------------------------------------


def attention_block(hf, x, w, window, quant=None):
    """x [T, E] -> x + attention(rms(x)). `window` 0: a global layer (no
    rotary); else a window layer of that many positions (rotary). Scores a
    block of `QUERY_BLOCK` query rows at a time."""
    H, KVH, D = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    T, eps = x.shape[0], hf["rms_norm_eps"]
    h = rms_norm(x, w["input_norm"], eps)
    q = rms_norm(matmul(h, w["wq"], quant).reshape(T, H, D), w["q_norm"], eps)
    k = rms_norm(matmul(h, w["wk"], quant).reshape(T, KVH, D), w["k_norm"], eps)
    v = matmul(h, w["wv"], quant).reshape(T, KVH, D)
    if window:
        theta = hf.get("rope_theta") or hf["rope_parameters"]["rope_theta"]
        q, k = base.rope(q, theta), base.rope(k, theta)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} positions are no whole blocks of {block}")
    cols = jnp.arange(T)[None, :]

    def rows(at):
        qb = jax.lax.dynamic_slice_in_dim(q, at, block, axis=0)
        i = (at + jnp.arange(block))[:, None]
        seen = cols <= i
        if window:
            seen = seen & (cols > i - window)
        scores = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(jnp.float32(D))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(block, H * D)

    attn = jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, H * D)
    return x + matmul(attn, w["wo"], quant)


def dense_block(hf, x, w, quant=None):
    """x [R, E] -> x + the dense SwiGLU on rms(x)."""
    h = rms_norm(x, w["post_norm"], hf["rms_norm_eps"])
    mid = jax.nn.silu(matmul(h, w["w_gate"], quant)) * matmul(h, w["w_up"], quant)
    return x + matmul(mid, w["w_down"], quant)


def route(hf, x, w, given, follow, quant=None):
    """Rows x [R, E] after attention. `given` [R, k] are the sets to take
    where `follow` [R]; every other row takes its own. Returns the normed
    rows, the sets taken, their weights (the sigmoid scores of the taken,
    renormalised and scaled), the reference's own sets (best first) and the
    trail [R]: the own k-th selection score minus the lowest selection score
    of the taken set."""
    h = rms_norm(x, w["post_norm"], hf["rms_norm_eps"])
    s = jax.nn.sigmoid(matmul(h, w["router"], quant))
    select = s + w["router_bias"]
    ownv, own = jax.lax.top_k(select, hf["num_experts_per_tok"])
    sets = jnp.where(follow[:, None], given, own)
    taken = jnp.take_along_axis(s, sets, axis=-1)
    weights = hf.get("routed_scaling_factor", 1.0) * taken / jnp.sum(
        taken, axis=-1, keepdims=True)
    return (h, sets, weights, own,
            ownv[:, -1] - jnp.take_along_axis(select, sets, axis=-1).min(axis=-1))


def shared_expert(hf, x, h, w, quant=None):
    """x + the shared expert's output on the normed rows h, ungated."""
    mid = jax.nn.silu(matmul(h, w["shared_gate"], quant)) * matmul(
        h, w["shared_up"], quant)
    return x + matmul(mid, w["shared_down"], quant)


def dispatch(hf, seed_key, layer, x, h, sets, probs, expert, idx, live, quant=None):
    """x + one expert's weighted output on the rows `idx` (padded: a padded
    entry is not `live` and adds nothing). The expert's weights are made
    from the seed here, upcast, used on these rows and dropped."""
    w = _f32(expert_weights(hf, seed_key, layer, expert))
    weight = jnp.sum(jnp.where(sets[idx] == expert, probs[idx], 0.0), -1) * live
    hi = h[idx]
    out = matmul(jax.nn.silu(matmul(hi, w["w_gate"], quant))
                 * matmul(hi, w["w_up"], quant), w["w_down"], quant)
    return x.at[idx].add(out * weight[:, None])


def head(hf, x, rows, final_norm, lm_head, quant=None):
    """Logits [len(rows), V] at positions `rows` of x [T, E]."""
    return matmul(rms_norm(x[rows], final_norm, hf["rms_norm_eps"]), lm_head.T, quant)


_make_top = jax.jit(lambda hf, key: _f32(top_weights(hf, key)), static_argnums=0)
_make_attn = jax.jit(lambda hf, key, l: _f32(attn_weights(hf, key, l)), static_argnums=0)
_make_dense = jax.jit(lambda hf, key, l: _f32(dense_weights(hf, key, l)), static_argnums=0)
_make_moe = jax.jit(lambda hf, key, l: _f32(moe_weights(hf, key, l)), static_argnums=0)
_attention = jax.jit(
    lambda hf, xs, w, window, quant: jax.lax.map(
        lambda x: attention_block(hf, x, w, window, quant), xs),
    static_argnums=(0, 3, 4))
_dense = jax.jit(dense_block, static_argnums=(0, 3))
_route = jax.jit(route, static_argnums=(0, 5))
_shared = jax.jit(shared_expert, static_argnums=(0, 4))
_dispatch = jax.jit(dispatch, static_argnums=(0, 10))
_head = jax.jit(head, static_argnums=(0, 5))


def experts_apply(hf, seed_key, layer, x, given, follow, live, quant=None):
    """The mixture of one routed layer on rows x [R, E] (`live` [R]: padding
    rows take no routed expert): the shared expert on every row, then the
    held experts one at a time, each on the rows whose taken set names it.
    Returns the new rows, the own sets [R, k], the trail [R]."""
    f, s = _flat(hf), sizes(hf)
    w = _make_moe(f, seed_key, layer)
    h, sets, probs, own, trail = _route(f, x, w, given, follow, quant)
    x = _shared(f, x, h, w, quant)
    taken = np.asarray(sets)
    rows = np.flatnonzero(live)
    for expert in range(s["first"], s["first"] + s["X"]):
        idx = rows[(taken[rows] == expert).any(axis=-1)]
        for at in range(0, len(idx), DISPATCH_BLOCK):
            part = idx[at:at + DISPATCH_BLOCK]
            padded = np.zeros(DISPATCH_BLOCK, np.int32)
            padded[:len(part)] = part
            x = _dispatch(
                f, seed_key, layer, x, h, sets, probs, expert, padded,
                (np.arange(DISPATCH_BLOCK) < len(part)).astype(np.float32), quant)
    return x, np.asarray(own), np.asarray(trail)


def forward(hf: dict, seed_key, seqs, quant=None, routes=None, pad_to=base.ROW_BLOCK,
            rows_pad=128):
    """For each (tokens, rows) of `seqs`, the logits [len(rows), V] (float32)
    at positions `rows`; every sequence right-padded to `pad_to` (causal
    layers: padding changes no earlier position). With `routes` (one entry a
    sequence: `[rows, routed layers, k]` global expert ids to take, rows =
    the sequence's tokens, or None for its own) it returns `(logits, own,
    trail)`: per sequence the reference's own sets `[rows, routed layers,
    k]` and the trail `[rows, routed layers]`."""
    f, s = _flat(hf), sizes(hf)
    n, k = len(seqs), s["k"]
    lengths = [len(tokens) for tokens, _ in seqs]
    given = np.zeros((n, pad_to, s["routed"], k), np.int32)
    follow = np.zeros((n, pad_to), bool)
    for i, r in enumerate(routes or ()):
        if r is None:
            continue
        r = np.asarray(r)
        if len(r) > lengths[i]:
            raise ValueError(f"{len(r)} rows of routes for {lengths[i]} positions")
        given[i, :len(r)], follow[i, :len(r)] = r, True
    live = (np.arange(pad_to)[None, :] < np.asarray(lengths)[:, None]).reshape(-1)
    own, trail = [], []
    with jax.default_matmul_precision("highest"):
        top = _make_top(f, seed_key)
        xs = jnp.stack([top["embed"][base._padded(tokens, pad_to)]
                        for tokens, _ in seqs])
        for layer in range(s["NL"]):
            xs = _attention(f, xs, _make_attn(f, seed_key, layer),
                            0 if is_global(hf, layer) else s["W"], quant)
            flat = xs.reshape(n * pad_to, -1)
            if layer < s["dense"]:
                flat = _dense(f, flat, _make_dense(f, seed_key, layer), quant)
            else:
                flat, o, t = experts_apply(
                    hf, seed_key, layer, flat,
                    given[:, :, layer - s["dense"]].reshape(n * pad_to, k),
                    follow.reshape(-1), live, quant)
                own.append(o.reshape(n, pad_to, k))
                trail.append(t.reshape(n, pad_to))
            xs = flat.reshape(n, pad_to, -1)
        logits = [
            _head(f, xs[i], base._padded(rows, rows_pad), top["final_norm"],
                  top["lm_head"], quant)[: len(rows)]
            for i, (_, rows) in enumerate(seqs)]
    if routes is None:
        return logits
    return (logits,
            [np.stack([o[i, :lengths[i]] for o in own], axis=1) for i in range(n)],
            [np.stack([t[i, :lengths[i]] for t in trail], axis=1) for i in range(n)])


# ---- counts ------------------------------------------------------------------


def _attn_params(hf: dict) -> int:
    """An attention layer's parameters, norms included."""
    s = sizes(hf)
    return (s["E"] * s["H"] * s["D"] + 2 * s["E"] * s["KVH"] * s["D"]
            + s["H"] * s["D"] * s["E"] + s["E"] + 2 * s["D"])


def _dense_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["E"] * s["Md"] + s["E"]


def _moe_params(hf: dict) -> int:
    """A routed layer's bf16 parameters outside its routed experts: norm,
    router, shared expert (the bias is float32 and counted beside)."""
    s = sizes(hf)
    return s["E"] * s["XR"] + 3 * s["E"] * s["Ms"] + s["E"]


def _outside_experts_bytes(hf: dict) -> int:
    """Every byte of weights a decode step reads whatever it routes:
    attention in every layer, the dense layers' FFN, norm, router, bias and
    shared expert of every routed layer, the final norm and the head (one
    embedding row a token is left out)."""
    s = sizes(hf)
    return (BF16 * (s["NL"] * _attn_params(hf) + s["dense"] * _dense_params(hf)
                    + s["routed"] * _moe_params(hf) + s["E"] + s["V"] * s["E"])
            + s["routed"] * s["XR"] * F32)


def expert_bytes(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["E"] * s["M"] * BF16


def weight_bytes(hf: dict) -> int:
    """Every parameter held on the chip: the held experts of every routed
    layer, everything outside them, and the embedding."""
    s = sizes(hf)
    return (_outside_experts_bytes(hf) + s["routed"] * s["X"] * expert_bytes(hf)
            + s["V"] * s["E"] * BF16)


def routed_layers(hf: dict) -> int:
    """Layers that have a router: what a forward runs the grouped products
    of."""
    return sizes(hf)["routed"]


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values a token leaves for as long as its sequence lives: in
    the global layers only."""
    s = sizes(hf)
    return 2 * s["global"] * s["KVH"] * s["D"] * BF16


def window_bytes_per_slot(hf: dict, length: float | None = None) -> float:
    """Keys and values a slot's window layers attend at `length` resident
    tokens: at most `sliding_window` of them (None: a full window)."""
    s = sizes(hf)
    tokens = s["W"] if length is None else min(float(length), s["W"])
    return 2.0 * s["window"] * s["KVH"] * s["D"] * BF16 * tokens


def experts_touched(hf: dict, rows: float) -> float:
    """Held experts that `rows` rows are expected to touch in a layer at even
    routing: a row misses a given expert with probability 1 - k / experts."""
    s = sizes(hf)
    return s["X"] * (1.0 - (1.0 - s["k"] / s["XR"]) ** rows)


def moe_experts_bytes(hf: dict, touched: float) -> float:
    """Weight bytes of one layer's grouped products: the held experts that
    hold a row."""
    return touched * expert_bytes(hf)


def moe_experts_flops(hf: dict, rows: float) -> float:
    """FLOPs of one layer's grouped products on `rows` rows: of the
    `num_experts_per_tok` assignments a row, the held share at even routing."""
    s = sizes(hf)
    return 2.0 * 3 * s["E"] * s["M"] * rows * s["k"] * s["X"] / s["XR"]


def decode_step_bytes_per_chip(hf: dict, resident_tokens: float, chips: int) -> float:
    """Least HBM traffic of one decode step with the configuration's slots
    live: the weights outside the experts once, the held experts its rows are
    expected to touch in each routed layer, the global layers' keys and
    values by the resident tokens, the window layers' by at most a window a
    slot."""
    s, slots = sizes(hf), hf["engine"]["num_slots"]
    return (_outside_experts_bytes(hf)
            + s["routed"] * moe_experts_bytes(hf, experts_touched(hf, slots))
            + resident_tokens * kv_bytes_per_token(hf)
            + slots * window_bytes_per_slot(hf, resident_tokens / slots)) / chips


def prefill_flops_per_token(hf: dict, context: float = 0.0) -> float:
    """Useful FLOPs to prefill one prompt token on this chip: 2 per matrix
    parameter it meets (attention, the dense FFN, router, shared expert, the
    held share of its `num_experts_per_tok` experts), and attention against
    `context` earlier tokens in the global layers, against at most a window
    of them in the others (4 H D each)."""
    s = sizes(hf)
    moe = _moe_params(hf) + 3 * s["E"] * s["M"] * s["k"] * s["X"] / s["XR"]
    return (2.0 * (s["NL"] * _attn_params(hf) + s["dense"] * _dense_params(hf)
                   + s["routed"] * moe)
            + 4 * s["H"] * s["D"] * (
                s["global"] * context + s["window"] * min(context, s["W"])))
