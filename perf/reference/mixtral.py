"""Plain reference for the Mixtral architecture (MixtralForCausalLM).

The Mistral block (perf/reference/mistral.py, the benchmark's own file) with
the MLP replaced by the published sparse mixture: a linear router over
`num_local_experts`, the `num_experts_per_tok` largest logits selected, a
softmax over the SELECTED logits only (renormalised, as published), and the
sum of the selected experts' SwiGLU outputs weighted by it. Float32,
"highest", one expert's weights at a time; no expert is run on a token it was
not routed for in any way that changes the result (unselected experts get
weight 0).

Imports nothing of the program. Weights come from the seed, per layer and
per expert, and `served_params` stacks them as the program's loader does:
`w_gate`/`w_up` `[layers, experts, E, M]`, `w_down` `[layers, experts, M, E]`,
`router` `[layers, E, experts]`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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


def route(hf: dict, h, router, quant=None):
    """Dense [T, experts] weights: softmax over the top-k logits, 0 elsewhere."""
    logits = matmul(h, router, quant)
    topv, topi = jax.lax.top_k(logits, hf["num_experts_per_tok"])
    probs = jax.nn.softmax(topv, axis=-1)
    onehot = jax.nn.one_hot(topi, hf["num_local_experts"], dtype=jnp.float32)
    return jnp.einsum("tk,tkx->tx", probs, onehot)


def expert_apply(hf: dict, h, w, quant=None):
    gate = jax.nn.silu(matmul(h, w["w_gate"], quant))
    return matmul(gate * matmul(h, w["w_up"], quant), w["w_down"], quant)


_make_shared = jax.jit(shared_weights, static_argnums=0)
_make_expert = jax.jit(
    lambda hf, seed_key, layer, x: expert_weights(
        hf, base.layer_key(seed_key, layer), x
    ),
    static_argnums=0,
)
_route = jax.jit(route, static_argnums=(0, 3))
_expert = jax.jit(expert_apply, static_argnums=(0, 3))


def layer_apply(hf, seed_key, layer, xs, quant=None):
    f = _Frozen(hf)
    w = _f32(_make_shared(f, seed_key, layer))
    xs = [base.attention_jit(f, x, w, quant) for x in xs]
    hs = [rms_norm(x, w["post_attn_norm"], hf["rms_norm_eps"]) for x in xs]
    weights = [_route(f, h, w["router"], quant) for h in hs]
    for expert in range(hf["num_local_experts"]):
        we = _f32(_make_expert(f, seed_key, layer, expert))
        xs = [x + wt[:, expert:expert + 1] * _expert(f, h, we, quant)
              for x, h, wt in zip(xs, hs, weights)]
    return xs


def forward(hf: dict, seed_key, seqs, quant=None, **padding):
    return base.forward(hf, seed_key, seqs, quant, layer_apply, **padding)


sizes = base.sizes
