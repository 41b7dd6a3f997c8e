"""Plain reference for the Kimi Linear architecture (KimiLinearForCausalLM,
`model_type` `kimi_linear`, e.g. Kimi-Linear-48B-A3B-Instruct): Kimi Delta
Attention (KDA) layers and latent attention (MLA) layers, three to one; a
leading dense layer, then routed layers whose router scores by a sigmoid and
selects on the score plus a bias, with one shared expert.

`E` = hidden size, eps = `rms_norm_eps`, `rms(x; w) = x * rsqrt(mean(x^2) +
eps) * w`. Block `l`: `h = x + Mixer_l(rms(x))`, `y = h + FFN_l(rms(h))`. Final
`rms`, untied head, no bias anywhere. Layer `l` (1-based) is MLA where
`linear_attn_config.full_attn_layers` names it, else KDA (the file keeps both
published lists whole; the first `num_hidden_layers` layers are run).

**KDA** (`H` = `linear_attn_config.num_heads` heads of `D` = its `head_dim`,
`K` = `short_conv_kernel_size`). `u = [W_q x, W_k x, W_v x]` (each `H D` wide);
`[q, k, v] = silu(conv(u))`, a causal depthwise convolution over the last `K`
positions (zeros before position 0), no bias; `q = l2(q) / sqrt(D)`, `k =
l2(k)` a head (`x * rsqrt(sum x^2 + 1e-6)`). The gate, a KEY CHANNEL: `g = -exp(
A_log[h]) * softplus(W_fb W_fa x + dt_bias)`, `[H, D]` a position (`W_fa: E ->
D`, `W_fb: D -> H D`); `beta = sigmoid(W_b x)`, one a head. With `S [D, D]` (key
by value) a head, from zeros, POSITION BY POSITION: `S = Diag(exp(g)) S`; `d =
(v - S^T k) * beta`; `S = S + k (x) d`; `o = S^T q` (which is `S_t = (I - beta k
k^T) Diag(alpha) S_{t-1} + beta k v^T`). Then `W_o(w_n * rms(o) * sigmoid(W_gb
W_ga x))`, the norm over a head (`W_ga: E -> D`, `W_gb: D -> H D`).

**MLA** (`H` heads; `mla_use_nope`: NO rotary anywhere, the `qk_rope_head_dim`
dimensions stay as a width). `q = W_q x` (`H` x (`nope` + `rope`));
`[c, kpe] = W_kva x` (`kv_lora_rank` + `rope`); `c = rms(c; w_kv)`; `k_nope,h =
W_kb,h c`, `v_h = W_vb,h c` (the two halves of the published `kv_b_proj`);
head h's key is `[k_nope,h, kpe]` (`kpe` shared by the heads); causal softmax
attention at `(nope + rope)^-1/2`; `W_o` (`H v_head_dim -> E`).

**FFN.** Layers below `first_k_dense_replace`: `W_down(silu(W_gate x) * W_up
x)` at `intermediate_size`. Every other layer is routed: `s = sigmoid(W_r x)`
over ALL `router_num_experts` (float32); the `num_experts_per_token` taken `T`
are the largest of `s + b` (`b` the selection bias `e_score_correction_bias`,
float32; with `num_expert_group` = `topk_group` = 1 the group step is the
identity); weights `w_i = routed_scaling_factor * s_i / sum_{j in T} s_j`
(`moe_renormalize`); `FFN(x) = sum_{i in T} w_i E_i(x) + E_shared(x)`, every `E`
a SwiGLU of `moe_intermediate_size`, the shared one ungated. **The expert
share**, as in the program: the file says how many experts are held here
(`num_experts`), how wide the router is (`router_num_experts`) and which share
this is (`expert_share_index`: global ids `index * num_experts ..`). The sum
runs over the taken experts that are HELD, under the weights of the whole
taken set; what the absent ones would add is left out, and that partial
result goes on to the next layer. Every share computes the shared expert and
the dense layer alike. An expert's weights are seeded by its GLOBAL id, so the
shares of one seed are the parts of one model (tests/unit/test_kimi_linear.py
adds them up).

**Routes.** Routed layer `j` is layer `j + first_k_dense_replace`; the
**selection score** is `s + b`. With `routes` the forward takes, for each
sequence, the expert sets it is GIVEN (`[rows, routed layers, k]` global ids,
held or not) and weights them by the same rule over the given set; it returns
what it would have taken itself and the trail: its own k-th `s + b` minus the
lowest `s + b` of the given set.

**Departures and what is assumed** (the configuration's `assumed`): the
pre-norm residual and the q / k l2-norm's 1e-6; the state float32 and the
served convolution tail bf16; the selection bias seeded uniform in +-0.05, as
K-EXAONE's; `A_log` uniform in log(0.05) .. log(1) a head and `dt_bias` uniform
in -5 .. 2 a channel, so that a step's decay `exp(g)` runs from about 0.2 to
0.999 ACROSS the channels of one head: some forget within a few positions,
some barely, and a program that gave a head one decay (its channels' mean)
would show. Weights in the program's layout (`W_q, W_k, W_v` side by side in
`in_qkv`; `W_fa, W_ga, W_b` in `in_fgb`; `kv_b_proj` as its two halves a head,
`w_kb [H, nope, rank]` and `w_vb [H, rank, v]`, as the absorbed decode
multiplies them), a loader's matter.

**What a latent hybrid brings beside this module**: the program's latent pool
(`ModelFamily.latent_pages`) and state pools, counts of its own (below: a
token's latent row once a latent layer, a slot's state read and written a KDA
layer, held experts by the count a step's rows touch), a reader kind for the
latent decode kernel (`perf/reader_kinds/latent_decode.py`) and an AOT guard of
its own (`tests/perf/test_aot_kimi_linear.py`).

Float32, matmul precision "highest", no kernel, no cache, one sequence at a
time through a mixer (`lax.map`: a loop), attention in blocks of `QUERY_BLOCK`
query rows, one expert at a time through the mixture, one layer of weights at
a time. Imports nothing of the program.
"""

from __future__ import annotations

import math

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
L2_EPS = 1e-6
A_RANGE = (0.05, 1.0)  # of exp(A_log), drawn uniformly in the logarithm, a head
DT_BIAS_RANGE = (-5.0, 2.0)  # a channel
LANES = 128
BF16, F32 = 2, 4


def sizes(hf: dict) -> dict:
    lin, layers, held = hf["linear_attn_config"], hf["num_hidden_layers"], hf["num_experts"]
    latent = sum(l <= layers for l in lin["full_attn_layers"])
    dense = hf.get("first_k_dense_replace", 0)
    return {
        "E": hf["hidden_size"], "V": hf["vocab_size"], "NL": layers,
        "H": lin["num_heads"], "D": lin["head_dim"], "K": lin["short_conv_kernel_size"],
        "HD": lin["num_heads"] * lin["head_dim"],
        "rank": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"], "DV": hf["v_head_dim"],
        "AH": hf["num_attention_heads"],
        "latent": latent, "kda": layers - latent,
        "periods": latent,  # as `hybrid_decode` counts the layers without state
        "dense": dense, "routed": layers - dense,
        "Md": hf["intermediate_size"], "M": hf["moe_intermediate_size"],
        "Ms": hf["moe_intermediate_size"] * hf.get("num_shared_experts", 1),
        "X": held, "XR": hf.get("router_num_experts", held),
        "first": hf.get("expert_share_index", 0) * held,
        "k": hf["num_experts_per_token"],
        "scale": hf.get("routed_scaling_factor", 1.0),
    }


def is_latent(hf: dict, layer: int) -> bool:
    """Whether 0-based `layer` is an MLA layer."""
    return layer + 1 in hf["linear_attn_config"]["full_attn_layers"]


def _flat(hf: dict) -> _Frozen:
    """The configuration as a static argument: the nested group's numbers
    brought up, its lists left out."""
    lin = hf["linear_attn_config"]
    flat = {k: v for k, v in hf.items() if not isinstance(v, (dict, list))}
    return _Frozen({**flat, "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
                    "conv_kernel": lin["short_conv_kernel_size"]})


def _dims(f) -> tuple:
    """(E, H, D, K) of a flat configuration's KDA layers."""
    return f["hidden_size"], f["kda_heads"], f["kda_head_dim"], f["conv_kernel"]


# ---- the seeded weights -----------------------------------------------------


def kda_weights(f, seed_key, layer) -> dict:
    """`f` is the flat configuration (`_flat`), as every maker below takes."""
    E, H, D, K = _dims(f)
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 3), 8)
    return {
        "input_norm": jnp.ones((E,), jnp.bfloat16),
        "in_qkv": _normal(k[0], (E, 3 * H * D)),
        "in_fgb": _normal(k[1], (E, 2 * D + H)),
        "conv_w": _normal(k[2], (K, 3 * H * D)),
        "f_b": _normal(k[3], (D, H * D)),
        "g_b": _normal(k[4], (D, H * D)),
        "A_log": jax.random.uniform(
            k[5], (H,), jnp.float32, math.log(A_RANGE[0]), math.log(A_RANGE[1])),
        "dt_bias": jax.random.uniform(k[6], (H * D,), jnp.float32, *DT_BIAS_RANGE),
        "o_norm": jnp.ones((D,), jnp.bfloat16),
        "wo": _normal(k[7], (H * D, E)),
    }


def mla_weights(hf, seed_key, layer) -> dict:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    rank, nope, rope, dv = (hf[k] for k in (
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 4), 5)
    return {
        "input_norm": jnp.ones((E,), jnp.bfloat16),
        "wq": _normal(k[0], (E, H * (nope + rope))),
        "w_kva": _normal(k[1], (E, rank + rope)),
        "kv_norm": jnp.ones((rank,), jnp.bfloat16),
        "w_kb": _normal(k[2], (H, nope, rank)),
        "w_vb": _normal(k[3], (H, rank, dv)),
        "wo": _normal(k[4], (H * dv, E)),
    }


def dense_weights(hf, seed_key, layer) -> dict:
    E, M = hf["hidden_size"], hf["intermediate_size"]
    k = jax.random.split(jax.random.fold_in(base.layer_key(seed_key, layer), 7), 3)
    return {
        "post_norm": jnp.ones((E,), jnp.bfloat16),
        "w_gate": _normal(k[0], (E, M)),
        "w_up": _normal(k[1], (E, M)),
        "w_down": _normal(k[2], (M, E)),
    }


def moe_weights(hf, seed_key, layer) -> dict:
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


def expert_weights(hf, seed_key, layer, expert) -> dict:
    """One expert by its GLOBAL id."""
    E, M = hf["hidden_size"], hf["moe_intermediate_size"]
    k = jax.random.split(
        jax.random.fold_in(base.layer_key(seed_key, layer), 100 + expert), 3)
    return {
        "w_gate": _normal(k[0], (E, M)),
        "w_up": _normal(k[1], (E, M)),
        "w_down": _normal(k[2], (M, E)),
    }


def top_weights(hf, seed_key) -> dict:
    E, V = hf["hidden_size"], hf["vocab_size"]
    k = jax.random.split(jax.random.fold_in(seed_key, 1), 2)
    return {
        "embed": _normal(k[0], (V, E)),
        "final_norm": jnp.ones((E,), jnp.bfloat16),
        "lm_head": _normal(k[1], (V, E)),
    }


def served_params(hf: dict, seed_key) -> dict:
    """The whole model in the program's layout: the KDA layers stacked in
    their order `[KDA layers, ...]`, the MLA layers `[latent layers, ...]`,
    the leading dense layers' FFN `[dense layers, ...]`, norm, router, bias
    and shared expert `[routed layers, ...]`, the held experts `[routed
    layers, held, ...]`."""
    s, f = sizes(hf), _flat(hf)
    every = np.arange(s["NL"], dtype=np.int32)
    latent = np.array([is_latent(hf, int(l)) for l in every])
    held = s["first"] + jnp.arange(s["X"], dtype=jnp.int32)

    def over(fn, layers):
        return jax.lax.map(lambda l: fn(f, seed_key, l), jnp.asarray(layers))

    return {
        **top_weights(hf, seed_key),
        "layers": {
            "kda": over(kda_weights, every[~latent]),
            "mla": over(mla_weights, every[latent]),
            "dense": over(dense_weights, every[: s["dense"]]),
            "moe": over(moe_weights, every[s["dense"]:]),
            "experts": jax.lax.map(
                lambda l: jax.lax.map(
                    lambda x: expert_weights(hf, seed_key, l, x), held),
                jnp.asarray(every[s["dense"]:])),
        },
    }


# ---- the layers -------------------------------------------------------------


def kda_inputs(f, x, w, quant=None):
    """x [T, E] -> (q, k, v [T, H, D], the log-decay g [T, H, D], beta [T,
    H], the output gate [T, H, D], u [T, 3 H D]: the convolution's inputs)."""
    _, H, D, K = _dims(f)
    T = x.shape[0]
    h = rms_norm(x, w["input_norm"], f["rms_norm_eps"])
    u = matmul(h, w["in_qkv"], quant)
    fgb = matmul(h, w["in_fgb"], quant)
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(padded[j: j + T] * w["conv_w"][j] for j in range(K)))
    q, k, v = (a.reshape(T, H, D) for a in jnp.split(y, 3, axis=-1))

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    lift = matmul(fgb[:, :D], w["f_b"], quant) + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(lift).reshape(T, H, D)
    gate = jax.nn.sigmoid(matmul(fgb[:, D:2 * D], w["g_b"], quant)).reshape(T, H, D)
    return (l2(q) / jnp.sqrt(jnp.float32(D)), l2(k), v, g,
            jax.nn.sigmoid(fgb[:, 2 * D:]), gate, u)


def delta_rule(q, k, v, g, beta):
    """Position by position from an empty state, the decay a key channel: o
    [T, H, D] and the state [H, D, D] after the last position."""
    def one(S, at):
        qt, kt, vt, gt, bt = at
        S = S * jnp.exp(gt)[:, :, None]
        d = (vt - jnp.einsum("hkv,hk->hv", S, kt)) * bt[:, None]
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    S, o = jax.lax.scan(one, S0, (q, k, v, g, beta))
    return o, S


def kda_block(f, x, w, quant=None):
    """x [T, E] -> x + KDA(rms(x))."""
    q, k, v, g, beta, gate, _ = kda_inputs(f, x, w, quant)
    o, _ = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, w["o_norm"], f["rms_norm_eps"]) * gate
    return x + matmul(o.reshape(x.shape[0], -1), w["wo"], quant)


def mla_block(f, x, w, quant=None, shared_key=True):
    """x [T, E] -> x + latent attention(rms(x)), in the expanded form, a
    block of `QUERY_BLOCK` query rows at a time. `shared_key` False drops the
    `qk_rope_head_dim` dimensions every head's key shares (a planted fault)."""
    H, rank, nope, dv = (f[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "v_head_dim"))
    T, eps = x.shape[0], f["rms_norm_eps"]
    h = rms_norm(x, w["input_norm"], eps)
    q = matmul(h, w["wq"], quant).reshape(T, H, -1)
    ckv = matmul(h, w["w_kva"], quant)
    c = rms_norm(ckv[:, :rank], w["kv_norm"], eps)
    kpe = ckv[:, rank:] if shared_key else jnp.zeros_like(ckv[:, rank:])
    # The two halves of kv_b_proj as [rank, H * d] matrices.
    w_kb = jnp.moveaxis(w["w_kb"], 2, 0).reshape(rank, H * nope)
    w_vb = jnp.moveaxis(w["w_vb"], 1, 0).reshape(rank, H * dv)
    k = jnp.concatenate([
        matmul(c, w_kb, quant).reshape(T, H, nope),
        jnp.broadcast_to(kpe[:, None], (T, H, kpe.shape[-1]))], axis=-1)
    v = matmul(c, w_vb, quant).reshape(T, H, dv)
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} positions are no whole blocks of {block}")
    cols = jnp.arange(T)[None, :]

    def rows(at):
        qb = jax.lax.dynamic_slice_in_dim(q, at, block, axis=0)
        seen = cols <= (at + jnp.arange(block))[:, None]
        scores = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(block, H * dv)

    attn = jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, H * dv)
    return x + matmul(attn, w["wo"], quant)


def dense_block(f, x, w, quant=None):
    """x [R, E] -> x + the dense SwiGLU on rms(x)."""
    h = rms_norm(x, w["post_norm"], f["rms_norm_eps"])
    mid = jax.nn.silu(matmul(h, w["w_gate"], quant)) * matmul(h, w["w_up"], quant)
    return x + matmul(mid, w["w_down"], quant)


def route(f, x, w, given, follow, quant=None):
    """Rows x [R, E] after the mixer. `given` [R, k] are the sets to take
    where `follow` [R]; every other row takes its own. Returns the normed
    rows, the sets taken, their weights (the sigmoid scores of the taken,
    renormalised and scaled), the reference's own sets (best first) and the
    trail [R]: the own k-th selection score minus the lowest selection score
    of the taken set."""
    h = rms_norm(x, w["post_norm"], f["rms_norm_eps"])
    s = jax.nn.sigmoid(matmul(h, w["router"], quant))
    select = s + w["router_bias"]
    ownv, own = jax.lax.top_k(select, f["num_experts_per_token"])
    sets = jnp.where(follow[:, None], given, own)
    taken = jnp.take_along_axis(s, sets, axis=-1)
    weights = f.get("routed_scaling_factor", 1.0) * taken / jnp.sum(
        taken, axis=-1, keepdims=True)
    return (h, sets, weights, own,
            ownv[:, -1] - jnp.take_along_axis(select, sets, axis=-1).min(axis=-1))


def shared_expert(f, x, h, w, quant=None):
    """x + the shared expert's output on the normed rows h, ungated."""
    mid = jax.nn.silu(matmul(h, w["shared_gate"], quant)) * matmul(
        h, w["shared_up"], quant)
    return x + matmul(mid, w["shared_down"], quant)


def dispatch(f, seed_key, layer, x, h, sets, probs, expert, idx, live, quant=None):
    """x + one expert's weighted output on the rows `idx` (padded: a padded
    entry is not `live` and adds nothing). The expert's weights are made
    from the seed here, upcast, used on these rows and dropped."""
    w = _f32(expert_weights(f, seed_key, layer, expert))
    weight = jnp.sum(jnp.where(sets[idx] == expert, probs[idx], 0.0), -1) * live
    hi = h[idx]
    out = matmul(jax.nn.silu(matmul(hi, w["w_gate"], quant))
                 * matmul(hi, w["w_up"], quant), w["w_down"], quant)
    return x.at[idx].add(out * weight[:, None])


def head(f, x, rows, final_norm, lm_head, quant=None):
    """Logits [len(rows), V] at positions `rows` of x [T, E]."""
    return matmul(rms_norm(x[rows], final_norm, f["rms_norm_eps"]), lm_head.T, quant)


_make_top = jax.jit(lambda f, key: _f32(top_weights(f, key)), static_argnums=0)
_make_kda = jax.jit(lambda f, key, l: _f32(kda_weights(f, key, l)), static_argnums=0)
_make_mla = jax.jit(lambda f, key, l: _f32(mla_weights(f, key, l)), static_argnums=0)
_make_dense = jax.jit(lambda f, key, l: _f32(dense_weights(f, key, l)), static_argnums=0)
_make_moe = jax.jit(lambda f, key, l: _f32(moe_weights(f, key, l)), static_argnums=0)
_kda = jax.jit(
    lambda f, xs, w, quant: jax.lax.map(lambda x: kda_block(f, x, w, quant), xs),
    static_argnums=(0, 3))
_mla = jax.jit(
    lambda f, xs, w, quant: jax.lax.map(lambda x: mla_block(f, x, w, quant), xs),
    static_argnums=(0, 3))
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
            if is_latent(hf, layer):
                xs = _mla(f, xs, _make_mla(f, seed_key, layer), quant)
            else:
                xs = _kda(f, xs, _make_kda(f, seed_key, layer), quant)
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


def _kda_params(hf: dict) -> int:
    """A KDA layer's bf16 parameters, norms included (`A_log` and `dt_bias`
    are float32 and counted beside)."""
    s = sizes(hf)
    return (s["E"] * 3 * s["HD"] + s["E"] * (2 * s["D"] + s["H"]) + s["K"] * 3 * s["HD"]
            + 2 * s["D"] * s["HD"] + s["HD"] * s["E"] + s["E"] + s["D"])


def _mla_params(hf: dict) -> int:
    s = sizes(hf)
    return (s["E"] * s["AH"] * (s["nope"] + s["rope"]) + s["E"] * (s["rank"] + s["rope"])
            + s["rank"] * s["AH"] * (s["nope"] + s["DV"]) + s["AH"] * s["DV"] * s["E"]
            + s["E"] + s["rank"])


def _dense_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["E"] * s["Md"] + s["E"]


def _moe_params(hf: dict) -> int:
    """A routed layer's bf16 parameters outside its routed experts: norm,
    router, shared expert (the bias is float32 and counted beside)."""
    s = sizes(hf)
    return s["E"] * s["XR"] + 3 * s["E"] * s["Ms"] + s["E"]


def _outside_experts_bytes(hf: dict) -> int:
    """Every byte of weights a decode step reads whatever it routes: the
    mixers, the dense layers' FFN, norm, router, bias and shared expert of
    every routed layer, the final norm and the head (one embedding row a
    token is left out)."""
    s = sizes(hf)
    return (BF16 * (s["kda"] * _kda_params(hf) + s["latent"] * _mla_params(hf)
                    + s["dense"] * _dense_params(hf) + s["routed"] * _moe_params(hf)
                    + s["E"] + s["V"] * s["E"])
            + F32 * (s["kda"] * (s["H"] + s["HD"]) + s["routed"] * s["XR"]))


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


def latent_bytes_per_token(hf: dict) -> int:
    """What a token's row in ONE latent layer needs: the compressed
    key-value and the shared key part, once (the pool holds it padded to
    whole lanes, `latent_row_bytes`; the least the work needs is this)."""
    s = sizes(hf)
    return (s["rank"] + s["rope"]) * BF16


def latent_row_bytes(hf: dict) -> int:
    """What the pool holds a token a latent layer: the row up to whole lanes."""
    s = sizes(hf)
    return -(-(s["rank"] + s["rope"]) // LANES) * LANES * BF16


def kv_bytes_per_token(hf: dict) -> int:
    """What a token leaves for as long as its sequence lives: one latent row
    in each latent layer, nothing in a KDA layer."""
    return sizes(hf)["latent"] * latent_bytes_per_token(hf)


def state_bytes_per_slot(hf: dict) -> int:
    """What a slot owns whatever its length: a KDA layer's recurrent state
    (float32) and its convolution's last inputs (bf16)."""
    s = sizes(hf)
    return s["kda"] * (s["H"] * s["D"] * s["D"] * F32 + (s["K"] - 1) * 3 * s["HD"] * BF16)


def kda_update_bytes(hf: dict, slots: float) -> float:
    """Least HBM traffic of one decode step's state updates: every
    recurrent state of `slots` slots read once and written once."""
    s = sizes(hf)
    return 2.0 * slots * s["kda"] * s["H"] * s["D"] * s["D"] * F32


gdn_update_bytes = kda_update_bytes  # the name `hybrid_decode`'s `state_roofline` asks


def mla_decode_bytes(hf: dict, tokens: float) -> float:
    """Least HBM traffic of ONE latent layer's decode attention over
    `tokens` resident tokens: each token's row once."""
    return tokens * latent_bytes_per_token(hf)


def mla_decode_flops(hf: dict, tokens: float) -> float:
    """FLOPs of ONE latent layer's decode attention in the absorbed form over
    `tokens` resident tokens: every head's score over the row's `rank + rope`
    numbers and its weighted sum over the first `rank`."""
    s = sizes(hf)
    return 2.0 * s["AH"] * (2 * s["rank"] + s["rope"]) * tokens


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
    `num_experts_per_token` assignments a row, the held share at even routing."""
    s = sizes(hf)
    return 2.0 * 3 * s["E"] * s["M"] * rows * s["k"] * s["X"] / s["XR"]


def hybrid_decode_bytes(hf: dict, resident_tokens: float, touched: float,
                        slots: float) -> float:
    """Least HBM traffic of one decode step: the held experts its rows touch
    in each routed layer (`touched`, a layer), the other weights once, the
    state of `slots` slots read and written, each resident token's latent
    rows once."""
    s = sizes(hf)
    return (s["routed"] * moe_experts_bytes(hf, touched) + _outside_experts_bytes(hf)
            + 2.0 * slots * state_bytes_per_slot(hf)
            + resident_tokens * kv_bytes_per_token(hf))


def decode_step_bytes_per_chip(hf: dict, resident_tokens: float, chips: int) -> float:
    """Least HBM traffic of one decode step with the configuration's slots
    live, the whole step's: `hybrid_decode_bytes` at the held experts those
    slots' rows are expected to touch at even routing."""
    slots = hf["engine"]["num_slots"]
    return hybrid_decode_bytes(
        hf, resident_tokens, experts_touched(hf, slots), slots) / chips


def prefill_flops_per_token(hf: dict, context: float = 0.0) -> float:
    """Useful FLOPs to prefill one prompt token on this chip: 2 per matrix
    parameter it meets (the mixers, the dense FFN, router, shared expert, the
    held share of its `num_experts_per_token` experts), attention against
    `context` earlier tokens in the latent layers in the expanded form (2 AH
    (nope + rope + v) each) and the delta rule's state work in the others (6
    H D D: decay-and-read, write, read)."""
    s = sizes(hf)
    moe = _moe_params(hf) + 3 * s["E"] * s["M"] * s["k"] * s["X"] / s["XR"]
    return (2.0 * (s["kda"] * _kda_params(hf) + s["latent"] * _mla_params(hf)
                   + s["dense"] * _dense_params(hf) + s["routed"] * moe)
            + s["latent"] * 2 * s["AH"] * (s["nope"] + s["rope"] + s["DV"]) * context
            + s["kda"] * 6 * s["H"] * s["D"] * s["D"])
