"""Plain reference for the Qwen3-Next architecture (Qwen3NextForCausalLM, e.g.
Qwen3-Next-80B-A3B-Instruct): Gated DeltaNet and gated attention layers, three
to one, each followed by a mixture of experts with a shared expert.

`E` = hidden size, eps = `rms_norm_eps`. `norm0(x; w) = x * rsqrt(mean(x^2) +
eps) * (1 + w)` (the weight is zero-centred) for the two layer norms, the
final norm and the q / k norms over a head. Layer `i` (0-based) is full
attention where `(i + 1) % full_attention_interval == 0`, else Gated DeltaNet.
`x = x + mixer(norm0(x)); x = x + moe(norm0(x))`. Untied head, no bias.

**Gated attention** (`H` query heads, `KVH` key / value heads of `D`):
`wq: E -> H * 2D`, a head's q and its gate; `wk, wv: E -> KVH * D`;
`q = norm0(q; q_norm)`, `k = norm0(k; k_norm)` over `D`; rotary (`rope_theta`,
rotate-half) on the first `partial_rotary_factor * D` dimensions, the others
pass; causal softmax attention at `D^-1/2`, grouped-query; `wo(attn *
sigmoid(gate))`.

**Gated DeltaNet** (`HK` key heads and `HV` value heads of `DK`, `DV`):
`in_qkvz: E -> 2 HK DK + 2 HV DV`, `in_ba: E -> 2 HV`. `u = concat(q, k, v)`;
`u = silu(conv(u))`, a causal depthwise convolution over the last
`linear_conv_kernel_dim` positions (zeros before position 0), no bias.
`beta = sigmoid(b)`; `g = -exp(A_log) * softplus(a + dt_bias)`, a value head.
q and k repeated `HV / HK` times to `HV` heads; `q = l2norm(q) / sqrt(DK)`,
`k = l2norm(k)` (`x * rsqrt(sum x^2 + 1e-6)`). With `S [DK, DV]` a value head,
from zeros, POSITION BY POSITION: `S = S * exp(g)`; `d = (v - S^T k) * beta`;
`S = S + k (x) d`; `o = S^T q`. Then `o = w_n * rmsnorm(o) * silu(z)` over a
value head (plain weight) and `out_proj: HV DV -> E`.

**Expert layer** (every layer): `p = softmax(x Wr)` over ALL `router_num_experts`;
the `num_experts_per_tok` largest; `w = p_taken / sum(p_taken)`
(`norm_topk_prob`); `routed = sum_e w_e down_e(silu(gate_e x) * up_e x)`;
`shared = sigmoid(x . w_sg) * down_s(silu(gate_s x) * up_s x)`; `moe = routed +
shared`. **The expert share**, as in the program: the configuration's file
says how many experts are held here (`num_experts`), how wide the router is
(`router_num_experts`) and which share this is (`expert_share_index`: global
ids `index * num_experts ..`). The sum runs over the taken experts that are
HELD, under the weights of the whole taken set; what the absent ones would
add is left out, and that partial result goes on to the next layer. Every
share computes the shared expert alike. An expert's weights are seeded by its
GLOBAL id, so the shares of one seed are the parts of one model
(tests/unit/test_qwen3_next.py adds them up).

**Routes.** Every layer has a router; the **selection score** is `p`, the
softmax probability over all experts. With `routes` the forward takes, for
each sequence, the expert sets it is GIVEN (`[rows, routed layers, k]` global
ids, held or not) and weights them by the same rule over the given set; it
returns what it would have taken itself and the trail: its own k-th `p` minus
the lowest `p` of the given set.

**Departures.** The published checkpoint's multi-token-prediction module is no
key of `config.json` and is not served. The checkpoint interleaves heads in
`in_proj_qkvz` / `in_proj_ba`; the seeded weights use the program's layout
(q, k, v, z and b, a each contiguous; `wq` = all q then all gates), a
loader's matter. Assumed (the configuration's `assumed`): the state is
float32 as the family's modeling file computes it, and the served program
keeps the convolution's last inputs in bf16; `A_log`, `dt_bias` are float32.

**Seeded weights that exercise the recurrence**: normal, std 0.02; norm
weights at their identity; `A_log` uniform in log(0.002) .. log(0.4) and
`dt_bias` uniform in -1 .. 1, so that a step's decay `exp(g)` lies in about
0.5 to 0.999. With the modeling file's initialiser (`A` uniform in 0 .. 16) an
untrained state forgets in one step, and a state carried wrongly would pass
every comparison.

**What a hybrid family brings beside this module**: the program's state
pools (`ModelFamily.recurrent_state`), counts of its own (below; perf/costs.py
counts keys and values in every layer and every expert a step), a reader kind
that takes them (`perf/reader_kinds/hybrid_decode.py`: the step's share of the
HBM peak, the state kernel's and the held grouped products' rooflines) and an
AOT guard of its own
(`tests/perf/test_aot_qwen3_next.py`).

Float32, matmul precision "highest", no kernel, no cache, one sequence at a
time through a mixer (`lax.map`: a loop), one expert at a time through the
mixture, one layer of weights at a time. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import mistral as base
from perf.reference.mistral import _Frozen, _f32, _normal, matmul

# Leaves of `served_params` whose last axis is the experts the router scores:
# what the planted fault `--break-path route` permutes.
ROUTER_LEAVES = ("router",)
DISPATCH_BLOCK = 1024  # rows of one expert a call: one shape, whatever the load
L2_EPS = 1e-6
A_RANGE = (0.002, 0.4)  # of exp(A_log), drawn uniformly in the logarithm
BF16, F32 = 2, 4


def sizes(hf: dict) -> dict:
    interval = hf.get("full_attention_interval", 4)
    layers = hf["num_hidden_layers"]
    if layers % interval:
        raise ValueError(f"{layers} layers are no whole periods of {interval}")
    held = hf["num_experts"]
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    return {
        "E": hf["hidden_size"], "V": hf["vocab_size"], "NL": layers,
        "interval": interval, "periods": layers // interval,
        "H": hf["num_attention_heads"], "KVH": hf["num_key_value_heads"],
        "D": hf["head_dim"],
        "rot": int(hf["head_dim"] * hf.get("partial_rotary_factor", 0.25)),
        "HK": hk, "HV": hv, "DK": dk, "DV": dv, "K": hf["linear_conv_kernel_dim"],
        "key_dim": hk * dk, "value_dim": hv * dv, "conv_dim": 2 * hk * dk + hv * dv,
        "M": hf["moe_intermediate_size"],
        "Ms": hf["shared_expert_intermediate_size"],
        "X": held, "XR": hf.get("router_num_experts", held),
        "first": hf.get("expert_share_index", 0) * held,
        "k": hf["num_experts_per_tok"],
    }


def is_attention(hf: dict, layer: int) -> bool:
    return (layer + 1) % hf.get("full_attention_interval", 4) == 0


def _flat(hf: dict) -> _Frozen:
    """The configuration as a static argument: nested groups left out."""
    return _Frozen({k: v for k, v in hf.items() if not isinstance(v, (dict, list))})


# ---- the seeded weights -----------------------------------------------------


def gdn_weights(hf: dict, seed_key, layer) -> dict:
    s = sizes(hf)
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 3), 6)
    lo, hi = (math.log(a) for a in A_RANGE)
    return {
        "input_norm": jnp.zeros((s["E"],), jnp.bfloat16),
        "in_qkvz": _normal(k[0], (s["E"], s["conv_dim"] + s["value_dim"])),
        "in_ba": _normal(k[1], (s["E"], 2 * s["HV"])),
        "conv_w": _normal(k[2], (s["K"], s["conv_dim"])),
        "A_log": jax.random.uniform(k[3], (s["HV"],), jnp.float32, lo, hi),
        "dt_bias": jax.random.uniform(k[4], (s["HV"],), jnp.float32, -1.0, 1.0),
        "norm": jnp.ones((s["DV"],), jnp.bfloat16),
        "out_proj": _normal(k[5], (s["value_dim"], s["E"])),
    }


def attn_weights(hf: dict, seed_key, layer) -> dict:
    s = sizes(hf)
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 4), 4)
    return {
        "input_norm": jnp.zeros((s["E"],), jnp.bfloat16),
        "wq": _normal(k[0], (s["E"], 2 * s["H"] * s["D"])),
        "wk": _normal(k[1], (s["E"], s["KVH"] * s["D"])),
        "wv": _normal(k[2], (s["E"], s["KVH"] * s["D"])),
        "wo": _normal(k[3], (s["H"] * s["D"], s["E"])),
        "q_norm": jnp.zeros((s["D"],), jnp.bfloat16),
        "k_norm": jnp.zeros((s["D"],), jnp.bfloat16),
    }


def moe_weights(hf: dict, seed_key, layer) -> dict:
    """Norm, router and shared expert of one layer's mixture."""
    s = sizes(hf)
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 5), 5)
    return {
        "post_norm": jnp.zeros((s["E"],), jnp.bfloat16),
        "router": _normal(k[0], (s["E"], s["XR"])),
        "shared_gate": _normal(k[1], (s["E"], s["Ms"])),
        "shared_up": _normal(k[2], (s["E"], s["Ms"])),
        "shared_down": _normal(k[3], (s["Ms"], s["E"])),
        "shared_router": _normal(k[4], (s["E"],)),
    }


def expert_weights(hf: dict, seed_key, layer, expert) -> dict:
    """One expert by its GLOBAL id."""
    s = sizes(hf)
    k = jax.random.split(
        jax.random.fold_in(base.layer_key(seed_key, layer), 100 + expert), 3)
    return {
        "w_gate": _normal(k[0], (s["E"], s["M"])),
        "w_up": _normal(k[1], (s["E"], s["M"])),
        "w_down": _normal(k[2], (s["M"], s["E"])),
    }


def top_weights(hf: dict, seed_key) -> dict:
    s = sizes(hf)
    k = jax.random.split(jax.random.fold_in(seed_key, 1), 2)
    return {
        "embed": _normal(k[0], (s["V"], s["E"])),
        "final_norm": jnp.zeros((s["E"],), jnp.bfloat16),
        "lm_head": _normal(k[1], (s["V"], s["E"])),
    }


def served_params(hf: dict, seed_key) -> dict:
    """The whole model in the program's layout: the DeltaNet layers stacked
    in their order `[state layers, ...]`, the attention layers `[periods,
    ...]`, norm, router and shared expert `[layers, ...]`, the held experts
    `[layers, held, ...]`."""
    s = sizes(hf)
    every = jnp.arange(s["NL"], dtype=jnp.int32)
    mixers = every.reshape(s["periods"], s["interval"])
    held = s["first"] + jnp.arange(s["X"], dtype=jnp.int32)

    def over(fn, layers):
        return jax.lax.map(lambda l: fn(hf, seed_key, l), layers)

    return {
        **top_weights(hf, seed_key),
        "layers": {
            "gdn": over(gdn_weights, mixers[:, :-1].reshape(-1)),
            "attn": over(attn_weights, mixers[:, -1]),
            "moe": over(moe_weights, every),
            "experts": jax.lax.map(
                lambda l: jax.lax.map(
                    lambda x: expert_weights(hf, seed_key, l, x), held),
                every),
        },
    }


# ---- the layers -------------------------------------------------------------


def norm0(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + weight)


def partial_rope(x, rot, theta):
    """x [T, heads, D] at positions 0..T-1: rotate-half on the first `rot`
    dimensions."""
    return jnp.concatenate([base.rope(x[..., :rot], theta), x[..., rot:]], axis=-1)


def attention_block(hf, x, w, quant=None):
    """x [T, E] -> x + gated attention(norm0(x))."""
    s, eps = sizes(hf), hf["rms_norm_eps"]
    T, H, KVH, D = x.shape[0], s["H"], s["KVH"], s["D"]
    h = norm0(x, w["input_norm"], eps)
    qg = matmul(h, w["wq"], quant)
    q, gate = qg[:, : H * D].reshape(T, H, D), qg[:, H * D:]
    k = matmul(h, w["wk"], quant).reshape(T, KVH, D)
    v = matmul(h, w["wv"], quant).reshape(T, KVH, D)
    q = partial_rope(norm0(q, w["q_norm"], eps), s["rot"], hf["rope_theta"])
    k = partial_rope(norm0(k, w["k_norm"], eps), s["rot"], hf["rope_theta"])
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * D)
    return x + matmul(attn * jax.nn.sigmoid(gate), w["wo"], quant)


def gdn_inputs(hf, x, w, quant=None):
    """x [T, E] -> (q, k [T, HV, DK], v [T, HV, DV], g, beta [T, HV], z [T,
    HV, DV], u [T, conv_dim]: the convolution's inputs)."""
    s, eps = sizes(hf), hf["rms_norm_eps"]
    T, K = x.shape[0], s["K"]
    h = norm0(x, w["input_norm"], eps)
    qkvz = matmul(h, w["in_qkvz"], quant)
    ba = matmul(h, w["in_ba"], quant)
    u, z = qkvz[:, : s["conv_dim"]], qkvz[:, s["conv_dim"]:]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(padded[j: j + T] * w["conv_w"][j] for j in range(K)))
    q = y[:, : s["key_dim"]].reshape(T, s["HK"], s["DK"])
    k = y[:, s["key_dim"]: 2 * s["key_dim"]].reshape(T, s["HK"], s["DK"])
    v = y[:, 2 * s["key_dim"]:].reshape(T, s["HV"], s["DV"])

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    rep = s["HV"] // s["HK"]
    q = jnp.repeat(l2(q) / jnp.sqrt(jnp.float32(s["DK"])), rep, axis=1)
    k = jnp.repeat(l2(k), rep, axis=1)
    beta = jax.nn.sigmoid(ba[:, : s["HV"]])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, s["HV"]:] + w["dt_bias"])
    return q, k, v, g, beta, z.reshape(T, s["HV"], s["DV"]), u


def delta_rule(q, k, v, g, beta):
    """Position by position from an empty state: o [T, HV, DV] and the state
    [HV, DK, DV] after the last position."""
    def one(S, at):
        qt, kt, vt, gt, bt = at
        S = S * jnp.exp(gt)[:, None, None]
        d = (vt - jnp.einsum("hkv,hk->hv", S, kt)) * bt[:, None]
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    S, o = jax.lax.scan(one, S0, (q, k, v, g, beta))
    return o, S


def gdn_block(hf, x, w, quant=None):
    """x [T, E] -> x + Gated DeltaNet(norm0(x))."""
    s, eps = sizes(hf), hf["rms_norm_eps"]
    q, k, v, g, beta, z, _ = gdn_inputs(hf, x, w, quant)
    o, _ = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = w["norm"] * o * jax.nn.silu(z)
    return x + matmul(o.reshape(x.shape[0], s["value_dim"]), w["out_proj"], quant)


def route(hf, x, w, given, follow, quant=None):
    """Rows x [R, E] after the mixer. `given` [R, k] are the sets to take
    where `follow` [R]; every other row takes its own. Returns the normed
    rows, the sets taken, their weights (p over the taken, renormalised),
    the reference's own sets (best first) and the trail [R]: the own k-th p
    minus the lowest p of the taken set."""
    h = norm0(x, w["post_norm"], hf["rms_norm_eps"])
    p = jax.nn.softmax(matmul(h, w["router"], quant), axis=-1)
    ownv, own = jax.lax.top_k(p, hf["num_experts_per_tok"])
    sets = jnp.where(follow[:, None], given, own)
    taken = jnp.take_along_axis(p, sets, axis=-1)
    return (h, sets, taken / jnp.sum(taken, axis=-1, keepdims=True), own,
            ownv[:, -1] - taken.min(axis=-1))


def shared_expert(hf, x, h, w, quant=None):
    """x + the shared expert's gated output on the normed rows h."""
    mid = jax.nn.silu(matmul(h, w["shared_gate"], quant)) * matmul(
        h, w["shared_up"], quant)
    gate = jax.nn.sigmoid(matmul(h, w["shared_router"][:, None], quant))
    return x + gate * matmul(mid, w["shared_down"], quant)


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
    return matmul(norm0(x[rows], final_norm, hf["rms_norm_eps"]), lm_head.T, quant)


_make_top = jax.jit(lambda hf, key: _f32(top_weights(hf, key)), static_argnums=0)
_make_gdn = jax.jit(lambda hf, key, l: _f32(gdn_weights(hf, key, l)), static_argnums=0)
_make_attn = jax.jit(lambda hf, key, l: _f32(attn_weights(hf, key, l)), static_argnums=0)
_make_moe = jax.jit(lambda hf, key, l: _f32(moe_weights(hf, key, l)), static_argnums=0)
_gdn = jax.jit(
    lambda hf, xs, w, quant: jax.lax.map(lambda x: gdn_block(hf, x, w, quant), xs),
    static_argnums=(0, 3))
_attention = jax.jit(
    lambda hf, xs, w, quant: jax.lax.map(
        lambda x: attention_block(hf, x, w, quant), xs),
    static_argnums=(0, 3))
_route = jax.jit(route, static_argnums=(0, 5))
_shared = jax.jit(shared_expert, static_argnums=(0, 4))
_dispatch = jax.jit(dispatch, static_argnums=(0, 10))
_head = jax.jit(head, static_argnums=(0, 5))


def experts_apply(hf, seed_key, layer, x, given, follow, live, quant=None):
    """The mixture of one layer on rows x [R, E] (`live` [R]: padding rows
    take no routed expert): the shared expert on every row, then the held
    experts one at a time, each on the rows whose taken set names it.
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
    given = np.zeros((n, pad_to, s["NL"], k), np.int32)
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
            if is_attention(hf, layer):
                xs = _attention(f, xs, _make_attn(f, seed_key, layer), quant)
            else:
                xs = _gdn(f, xs, _make_gdn(f, seed_key, layer), quant)
            flat, o, t = experts_apply(
                hf, seed_key, layer, xs.reshape(n * pad_to, -1),
                given[:, :, layer].reshape(n * pad_to, k), follow.reshape(-1),
                live, quant)
            xs = flat.reshape(n, pad_to, -1)
            own.append(o.reshape(n, pad_to, k))
            trail.append(t.reshape(n, pad_to))
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


def _mixer_params(hf: dict) -> tuple[int, int]:
    """(a DeltaNet layer's, an attention layer's) parameters outside the
    mixture, norms included."""
    s = sizes(hf)
    gdn = (s["E"] * (s["conv_dim"] + s["value_dim"]) + s["E"] * 2 * s["HV"]
           + s["K"] * s["conv_dim"] + s["value_dim"] * s["E"] + s["E"] + s["DV"])
    attn = (s["E"] * 2 * s["H"] * s["D"] + 2 * s["E"] * s["KVH"] * s["D"]
            + s["H"] * s["D"] * s["E"] + s["E"] + 2 * s["D"])
    return gdn, attn


def _outside_experts_bytes(hf: dict) -> int:
    """Every byte of weights a decode step reads whatever it routes: the
    mixers, norm, router and shared expert of every layer, the final norm
    and the head (one embedding row a token is left out)."""
    s = sizes(hf)
    gdn, attn = _mixer_params(hf)
    moe = s["E"] * s["XR"] + 3 * s["E"] * s["Ms"] + 2 * s["E"]
    n_attn = s["periods"]
    return (BF16 * ((s["NL"] - n_attn) * gdn + n_attn * attn + s["NL"] * moe
                    + s["E"] + s["V"] * s["E"])
            + (s["NL"] - n_attn) * 2 * s["HV"] * (F32 - BF16))


def expert_bytes(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["E"] * s["M"] * BF16


def weight_bytes(hf: dict) -> int:
    """Every parameter held on the chip: the held experts of every layer,
    everything outside them, and the embedding."""
    s = sizes(hf)
    return (_outside_experts_bytes(hf) + s["NL"] * s["X"] * expert_bytes(hf)
            + s["V"] * s["E"] * BF16)


def kv_bytes_per_token(hf: dict) -> int:
    """Keys and values a token leaves: in the attention layers only."""
    s = sizes(hf)
    return 2 * s["periods"] * s["KVH"] * s["D"] * BF16


def state_bytes_per_slot(hf: dict) -> int:
    """What a slot owns whatever its length: a DeltaNet layer's recurrent
    state (float32) and its convolution's last inputs (bf16)."""
    s = sizes(hf)
    a_layer = s["HV"] * s["DK"] * s["DV"] * F32 + (s["K"] - 1) * s["conv_dim"] * BF16
    return (s["NL"] - s["periods"]) * a_layer


def gdn_update_bytes(hf: dict, slots: float) -> float:
    """Least HBM traffic of one decode step's state updates: every
    recurrent state of `slots` slots read once and written once."""
    s = sizes(hf)
    return 2.0 * slots * (s["NL"] - s["periods"]) * s["HV"] * s["DK"] * s["DV"] * F32


def moe_experts_bytes(hf: dict, touched: float) -> float:
    """Weight bytes of one layer's grouped products: the held experts that
    hold a row."""
    return touched * expert_bytes(hf)


def moe_experts_flops(hf: dict, rows: float) -> float:
    """FLOPs of one layer's grouped products on `rows` rows: of the
    `num_experts_per_tok` assignments a row, the held share at even routing."""
    s = sizes(hf)
    return 2.0 * 3 * s["E"] * s["M"] * rows * s["k"] * s["X"] / s["XR"]


def hybrid_decode_bytes(hf: dict, resident_tokens: float, touched: float,
                        slots: float) -> float:
    """Least HBM traffic of one decode step: the held experts its rows touch
    in each layer (`touched`, a layer), the other weights once, the state of
    `slots` slots read and written, the resident keys and values once."""
    s = sizes(hf)
    return (s["NL"] * moe_experts_bytes(hf, touched) + _outside_experts_bytes(hf)
            + 2.0 * slots * state_bytes_per_slot(hf)
            + resident_tokens * kv_bytes_per_token(hf))


def prefill_flops_per_token(hf: dict, context: float = 0.0) -> float:
    """Useful FLOPs to prefill one prompt token on this chip: 2 per matrix
    parameter it meets (the mixers, router, shared expert, the held share of
    its `num_experts_per_tok` experts), attention against `context` earlier
    tokens in the attention layers (4 H D each) and the delta rule's state
    work in the others (6 HV DK DV: decay-and-read, write, read)."""
    s = sizes(hf)
    gdn, attn = _mixer_params(hf)
    moe = s["E"] * s["XR"] + 3 * s["E"] * s["Ms"] + s["E"] + (
        3 * s["E"] * s["M"] * s["k"] * s["X"] / s["XR"])
    n_attn = s["periods"]
    return (2.0 * ((s["NL"] - n_attn) * gdn + n_attn * attn + s["NL"] * moe)
            + n_attn * 4 * s["H"] * s["D"] * context
            + (s["NL"] - n_attn) * 6 * s["HV"] * s["DK"] * s["DV"])
