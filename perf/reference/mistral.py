"""Plain reference for the Mistral architecture (MistralForCausalLM), and
the seeded weights both it and the served program are given.

Straight `jax.numpy` in float32 with matmul precision "highest": no kernel,
no cache, no batching, one sequence at a time, one layer at a time (so one
layer of float32 weights is all it holds). It follows the published model:
pre-norm decoder blocks, RMSNorm, grouped-query attention with rotary
embeddings (rotate-half convention, theta from the config), SwiGLU MLP, no
biases, untied output head. Departure: none; `sliding_window` is null in the
published config and is not modelled.

It imports nothing of the program. The weights come from `--seed` through
`layer_weights` / `top_weights` below; `served_params` stacks the same values
in the layout the program's loaders produce (`[layers, in, out]`), which is
the program's input format, as a checkpoint would be.

`quant` turns the reference into the *control* of `correct`: the same
forward with every matmul operand rounded to a lower precision ("fp8":
float8_e4m3 with a per-row scale; "int8": weights only, per output channel).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02  # initializer_range of the published config
ROW_BLOCK = 256  # default padding of a sequence; a cell pads to its longest request


def sizes(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return {
        "E": hf["hidden_size"],
        "H": heads,
        "KVH": hf.get("num_key_value_heads", heads),
        "D": hf.get("head_dim") or hf["hidden_size"] // heads,
        "M": hf["intermediate_size"],
        "V": hf["vocab_size"],
        "NL": hf["num_hidden_layers"],
    }


def _normal(key, shape):
    return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(
        jnp.bfloat16
    )


def layer_key(seed_key, layer):
    return jax.random.fold_in(seed_key, 1000 + layer)


def attn_weights(hf: dict, lkey) -> dict:
    s = sizes(hf)
    E, H, KVH, D = s["E"], s["H"], s["KVH"], s["D"]
    k = jax.random.split(lkey, 4)
    return {
        "input_norm": jnp.ones((E,), jnp.bfloat16),
        "wq": _normal(k[0], (E, H * D)),
        "wk": _normal(k[1], (E, KVH * D)),
        "wv": _normal(k[2], (E, KVH * D)),
        "wo": _normal(k[3], (H * D, E)),
        "post_attn_norm": jnp.ones((E,), jnp.bfloat16),
    }


def layer_weights(hf: dict, seed_key, layer) -> dict:
    """One decoder block's weights, bf16, `[in, out]`."""
    s = sizes(hf)
    lkey = layer_key(seed_key, layer)
    k = jax.random.split(jax.random.fold_in(lkey, 7), 3)
    w = attn_weights(hf, lkey)
    w["w_gate"] = _normal(k[0], (s["E"], s["M"]))
    w["w_up"] = _normal(k[1], (s["E"], s["M"]))
    w["w_down"] = _normal(k[2], (s["M"], s["E"]))
    return w


def top_weights(hf: dict, seed_key) -> dict:
    s = sizes(hf)
    k = jax.random.split(jax.random.fold_in(seed_key, 1), 2)
    return {
        "embed": _normal(k[0], (s["V"], s["E"])),
        "final_norm": jnp.ones((s["E"],), jnp.bfloat16),
        "lm_head": _normal(k[1], (s["V"], s["E"])),
    }


def served_params(hf: dict, seed_key, layer_fn=None) -> dict:
    """The whole model in the program's parameter layout: the per-layer
    values above, stacked on a leading layer axis. Jit it with the
    program's parameter shardings as `out_shardings`, so every leaf is
    born on its device in its served type."""
    layer_fn = layer_fn or layer_weights
    layers = jax.lax.map(
        lambda l: layer_fn(hf, seed_key, l),
        jnp.arange(sizes(hf)["NL"], dtype=jnp.int32),
    )
    return {**top_weights(hf, seed_key), "layers": layers}


# ---- lower precisions, for the control -------------------------------------


def _fp8(x):
    """Round to float8_e4m3 with one scale per row (last axis)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _int8_weight(w):
    """Symmetric int8 per output channel (axis -1 of `[in, out]`)."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def matmul(x, w, quant=None):
    """x [..., in] @ w [in, out] in float32; `quant` rounds the operands."""
    if quant == "fp8":
        x, w = _fp8(x), jnp.swapaxes(_fp8(jnp.swapaxes(w, -1, -2)), -1, -2)
    elif quant == "int8":
        w = _int8_weight(w)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w)


# ---- the forward pass -------------------------------------------------------


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, theta):
    """x [T, heads, D]; rotate-half convention, positions 0..T-1."""
    T, _, D = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_block(hf: dict, x, w, quant=None):
    """x [T, E] -> x + attention(x): causal, grouped-query."""
    s = sizes(hf)
    H, KVH, D = s["H"], s["KVH"], s["D"]
    T = x.shape[0]
    h = rms_norm(x, w["input_norm"], hf["rms_norm_eps"])
    q = rope(matmul(h, w["wq"], quant).reshape(T, H, D), hf["rope_theta"])
    k = rope(matmul(h, w["wk"], quant).reshape(T, KVH, D), hf["rope_theta"])
    v = matmul(h, w["wv"], quant).reshape(T, KVH, D)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v).reshape(T, H * D)
    return x + matmul(attn, w["wo"], quant)


def mlp_block(hf: dict, x, w, quant=None):
    h = rms_norm(x, w["post_attn_norm"], hf["rms_norm_eps"])
    gate = jax.nn.silu(matmul(h, w["w_gate"], quant))
    return x + matmul(gate * matmul(h, w["w_up"], quant), w["w_down"], quant)


def block(hf: dict, x, w, quant=None):
    return mlp_block(hf, attention_block(hf, x, w, quant), w, quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def layer_apply(hf, seed_key, layer, xs, quant=None):
    """One decoder block on every sequence of `xs` (each [T, E]): the
    layer's weights are made from the seed once, upcast, used and dropped."""
    w = _f32(_make_layer(_Frozen(hf), seed_key, layer))
    return [_block(_Frozen(hf), x, w, quant) for x in xs]


def embed_tokens(hf, embed, tokens):
    return embed[tokens]


def head(hf, x, rows, final_norm, lm_head, quant=None):
    """Logits [len(rows), V] at positions `rows` of x [T, E]."""
    h = rms_norm(x[rows], final_norm, hf["rms_norm_eps"])
    return matmul(h, lm_head.T, quant)


def forward(hf: dict, seed_key, seqs, quant=None, layer_apply=layer_apply,
            pad_to=ROW_BLOCK, rows_pad=128):
    """For each (tokens, rows) of `seqs`, the logits [len(rows), V]
    (float32) at positions `rows`. Layer by layer, all sequences through a
    layer before the next layer's weights exist. Every sequence is
    right-padded to `pad_to` tokens (causal: padding changes no earlier
    position) and its rows to `rows_pad`, so that each stage compiles for
    one shape however long the requests of a run happen to be."""
    f = _Frozen(hf)
    with jax.default_matmul_precision("highest"):
        top = _f32(_make_top(f, seed_key))
        xs = [_embed(f, top["embed"], _padded(tokens, pad_to))
              for tokens, _ in seqs]
        for layer in range(sizes(hf)["NL"]):
            xs = layer_apply(hf, seed_key, layer, xs, quant)
        return [
            _head(f, x, _padded(rows, rows_pad), top["final_norm"],
                  top["lm_head"], quant)[: len(rows)]
            for x, (_, rows) in zip(xs, seqs)
        ]


def _padded(values, n: int):
    if len(values) > n:
        raise ValueError(f"{len(values)} values do not fit the padding {n}")
    return jnp.asarray(list(values) + [0] * (n - len(values)), jnp.int32)


class _Frozen(dict):
    """A config dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


_make_top = jax.jit(top_weights, static_argnums=0)
_make_layer = jax.jit(layer_weights, static_argnums=0)
_block = jax.jit(block, static_argnums=(0, 3))
_embed = jax.jit(embed_tokens, static_argnums=0)
_head = jax.jit(head, static_argnums=(0, 5))
attention_jit = jax.jit(attention_block, static_argnums=(0, 3))
