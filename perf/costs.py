"""What the algorithm needs: bytes and floating-point operations, from shapes.

Counted once and only what is useful: weights are read once per decode step
per chip, resident KV once, padding does no useful work, a mixture of
experts counts the experts a token is routed to and not all of them. A
tier-1 test pins each count against a hand-worked case. `hf` is a
configuration file's dict (the published `config.json` keys).

The counts below are of the llama and mixtral shapes: heads of one width, a
cache of keys and values. An architecture they do not fit (heads of two
widths, a latent cache, layers of several kinds) brings its own counts as
functions of the same names in its reference module, and a reader asks for a
count through `of(reference, name)`.
"""

from __future__ import annotations

BF16 = 2


def of(reference, name: str):
    """The cost function `name`: the configuration's reference module's own
    where it has one, this file's otherwise."""
    return getattr(reference, name, None) or globals()[name]


def _dims(hf):
    heads = hf["num_attention_heads"]
    kvh = hf.get("num_key_value_heads", heads)
    d = hf.get("head_dim") or hf["hidden_size"] // heads
    return hf["hidden_size"], heads, kvh, d, hf["intermediate_size"]


def layer_params(hf: dict, active_only: bool = False) -> int:
    """Matrix parameters of one decoder block (norms left out: 2E)."""
    E, H, KVH, D, M = _dims(hf)
    attn = E * H * D + 2 * E * KVH * D + H * D * E
    experts = hf.get("num_local_experts", 0)
    if experts:
        used = hf["num_experts_per_tok"] if active_only else experts
        return attn + E * experts + used * 3 * E * M
    return attn + 3 * E * M


def weight_bytes(hf: dict) -> int:
    """Every parameter a decode step reads: all blocks (a dense-dispatch
    step reads every expert), final norm, output head, one embedding row
    per token (left out: 8 KB)."""
    E = hf["hidden_size"]
    return BF16 * (
        hf["num_hidden_layers"] * (layer_params(hf) + 2 * E)
        + E + hf["vocab_size"] * E
    )


def kv_bytes_per_token(hf: dict) -> int:
    _, _, KVH, D, _ = _dims(hf)
    return 2 * hf["num_hidden_layers"] * KVH * D * BF16


def decode_step_bytes_per_chip(hf: dict, resident_tokens: float, chips: int) -> float:
    """Least HBM traffic of one decode step on one chip: its share of the
    weights once, its share of the resident keys and values once."""
    return (weight_bytes(hf) + resident_tokens * kv_bytes_per_token(hf)) / chips


def prefill_flops_per_token(hf: dict, context: float = 0.0) -> float:
    """Useful FLOPs to prefill one prompt token: 2 per matrix parameter of
    the experts it is routed to, plus attention against `context` earlier
    tokens (QK^T and PV: 4 * H * D each). The output head runs once per
    prompt, not per token, and is left out."""
    _, H, _, D, _ = _dims(hf)
    per_layer = 2 * layer_params(hf, active_only=True) + 4 * H * D * context
    return hf["num_hidden_layers"] * per_layer
