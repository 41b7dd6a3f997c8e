"""Mixtral-family decoder: Llama attention + sparse-MoE FFN.

Expert parallelism, TPU-style: expert weights are stacked
[num_experts, ...] with the EXPERT axis sharded over the tp mesh axis
(see kubeai_tpu.parallel.sharding EXPERT rule — experts reuse the tensor
axis on one physical mesh). Routing is computed densely: every expert's
FFN runs as one batched einsum over the expert axis and the top-k router
weights zero out non-selected experts. This keeps shapes static and the
MXU busy — the standard serving trade (dense dispatch) until capacity-
based sorting is worth it; XLA shards the expert einsums so each device
computes only its local experts and psums the combine.

Parity: the reference serves Mixtral via vLLM catalog presets; here it is
the in-tree MoE path, and the `ep` axis promised in SURVEY.md §2 exists
as real sharded compute.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeai_tpu.models.llama import _prefill_attention
from kubeai_tpu.models.registry import (
    ModelFamily,
    register_model_family,
    route_dtype,
)
from kubeai_tpu.ops.attention import decode_attention
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies
from kubeai_tpu.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    num_experts_per_tok: int = 2
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 32768
    dtype: Any = jnp.bfloat16

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def routed_layers(self) -> int:
        """Layers that have a router: every one here. A model with leading
        dense layers has fewer, and its forwards hand over that many."""
        return self.num_layers

    @staticmethod
    def from_hf_dict(d: dict) -> "MixtralConfig":
        return MixtralConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", 8),
            num_experts=d.get("num_local_experts", 8),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            rope_theta=d.get("rope_theta", 1e6),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 32768),
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "MixtralConfig":
        return MixtralConfig(
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=96,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            num_experts=4,
            num_experts_per_tok=2,
            rope_theta=10000.0,
        )


def param_specs(cfg: MixtralConfig) -> dict:
    L = None
    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": {
            "input_norm": (L, sh.EMBED),
            "wq": (L, sh.EMBED, sh.HEADS),
            "wk": (L, sh.EMBED, sh.KV_HEADS),
            "wv": (L, sh.EMBED, sh.KV_HEADS),
            "wo": (L, sh.HEADS, sh.EMBED),
            "post_attn_norm": (L, sh.EMBED),
            "router": (L, sh.EMBED, None),
            # Expert axis sharded over the mesh (EP = tp axis reuse).
            "w_gate": (L, sh.EXPERT, sh.EMBED, None),
            "w_up": (L, sh.EXPERT, sh.EMBED, None),
            "w_down": (L, sh.EXPERT, None, sh.EMBED),
        },
        "final_norm": (sh.EMBED,),
        "lm_head": (sh.VOCAB, sh.EMBED),
    }


def init_params(cfg: MixtralConfig, key: jax.Array | None = None) -> dict:
    if key is None:
        key = jax.random.PRNGKey(0)
    E, H, KVH, D, M, V, NL, X = (
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_size,
        cfg.intermediate_size,
        cfg.vocab_size,
        cfg.num_layers,
        cfg.num_experts,
    )
    ks = jax.random.split(key, 10)
    dt = cfg.dtype

    def rnd(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    return {
        "embed": rnd(ks[0], (V, E)),
        "layers": {
            "input_norm": jnp.ones((NL, E), dt),
            "wq": rnd(ks[1], (NL, E, H * D)),
            "wk": rnd(ks[2], (NL, E, KVH * D)),
            "wv": rnd(ks[3], (NL, E, KVH * D)),
            "wo": rnd(ks[4], (NL, H * D, E)),
            "post_attn_norm": jnp.ones((NL, E), dt),
            "router": rnd(ks[5], (NL, E, X)),
            "w_gate": rnd(ks[6], (NL, X, E, M)),
            "w_up": rnd(ks[7], (NL, X, E, M)),
            "w_down": rnd(ks[8], (NL, X, M, E)),
        },
        "final_norm": jnp.ones((E,), dt),
        "lm_head": rnd(ks[9], (V, E)),
    }


@jax.named_scope("moe_ffn")
def _moe_ffn(x, lp, cfg):
    """x: [B, S, E] (or [B, E] for decode via S=1 squeeze by caller).

    Dense top-k MoE: softmax over the selected experts' router logits,
    all experts computed batched over the (sharded) expert axis, combine
    weighted by the routing probabilities.

    Returns (y [B, S, E], topi [B, S, k]): the output and the global ids
    of the experts each token took, in the router's order (best first).
    `topi` is the tensor the weight map is scattered from, not a second
    `top_k`, so what is handed over is what was computed with.
    """
    with jax.named_scope("moe_router"):
        router_logits = jnp.einsum(
            "bse,ex->bsx", x, lp["router"]
        ).astype(jnp.float32)  # [B, S, X]
        topv, topi = jax.lax.top_k(router_logits, cfg.num_experts_per_tok)
        probs = jax.nn.softmax(topv, axis=-1)  # normalize over selected only
        # Scatter the top-k probabilities back to a dense [B, S, X] weight
        # map.
        weights = jnp.zeros_like(router_logits)
        b_idx = jnp.arange(router_logits.shape[0])[:, None, None]
        s_idx = jnp.arange(router_logits.shape[1])[None, :, None]
        weights = weights.at[b_idx, s_idx, topi].set(probs)

    # All experts, batched einsum over the expert axis (sharded -> each
    # device computes its local experts; XLA psums the combine).
    g = jax.nn.silu(jnp.einsum("bse,xem->bsxm", x, lp["w_gate"]))
    u = jnp.einsum("bse,xem->bsxm", x, lp["w_up"])
    y = jnp.einsum("bsxm,xme->bsxe", g * u, lp["w_down"])
    return jnp.einsum(
        "bsxe,bsx->bse", y, weights.astype(y.dtype)
    ), topi


def _stack_routes(topi_all, cfg):
    """The layer scan's stacked `topi` [routed layers, *rows, k] as the
    forwards hand it over: [*rows, routed layers, k], in the smallest
    unsigned integer type that holds an expert id."""
    return jnp.moveaxis(topi_all, 0, -2).astype(route_dtype(cfg.num_experts))


def prefill(params, cfg, tokens, lengths, lora=None, lora_idx=None, *,
            routes=False):
    """Whole-prompt prefill. `routes=True` appends the expert sets taken,
    [B, S, routed layers, k] (see `_stack_routes`); the engine always asks
    for them, a caller that does not gets the three outputs it always
    got. The same holds for the other three forwards below."""
    B, S = tokens.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    x = params["embed"][tokens]

    def layer(x, lp):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("bse,eh->bsh", h, lp["wq"]).reshape(B, S, H, D)
        k = jnp.einsum("bse,eh->bsh", h, lp["wk"]).reshape(B, S, KVH, D)
        v = jnp.einsum("bse,eh->bsh", h, lp["wv"]).reshape(B, S, KVH, D)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        attn = _prefill_attention(q, k, v)
        x = x + jnp.einsum("bsh,he->bse", attn.reshape(B, S, H * D), lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        y, topi = _moe_ffn(h2, lp, cfg)
        return x + y, (k, v, topi)

    x, (k_all, v_all, topi_all) = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    if routes:
        return logits, k_all, v_all, _stack_routes(topi_all, cfg)
    return logits, k_all, v_all


def decode_step(params, cfg, tokens, positions, k_cache, v_cache,
                lora=None, lora_idx=None, *, routes=False):
    B = tokens.shape[0]
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    x = params["embed"][tokens]
    pos1 = positions[:, None]
    lengths = positions + 1
    slot_idx = jnp.arange(B)

    def layer(carry, scanned):
        x = carry
        lp, kc, vc = scanned["p"], scanned["kc"], scanned["vc"]
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("be,eh->bh", h, lp["wq"]).reshape(B, 1, H, D)
        k = jnp.einsum("be,eh->bh", h, lp["wk"]).reshape(B, 1, KVH, D)
        v = jnp.einsum("be,eh->bh", h, lp["wv"]).reshape(B, 1, KVH, D)
        q = apply_rope(q, pos1, inv_freq)[:, 0]
        k = apply_rope(k, pos1, inv_freq)[:, 0]
        v = v[:, 0]
        kc = kc.at[slot_idx, positions].set(k.astype(kc.dtype))
        vc = vc.at[slot_idx, positions].set(v.astype(vc.dtype))
        attn = decode_attention(q, kc, vc, lengths)
        x = x + jnp.einsum("bh,he->be", attn.reshape(B, H * D), lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        y, topi = _moe_ffn(h2[:, None], lp, cfg)
        return x + y[:, 0], (kc, vc, topi[:, 0])

    x, (k_cache, v_cache, topi_all) = jax.lax.scan(
        layer, x, {"p": params["layers"], "kc": k_cache, "vc": v_cache}
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "be,ve->bv", x, params["lm_head"], preferred_element_type=jnp.float32
    )
    if routes:
        return logits, k_cache, v_cache, _stack_routes(topi_all, cfg)
    return logits, k_cache, v_cache


def decode_step_paged(params, cfg, tokens, positions, k_pages, v_pages,
                      block_tables, lora=None, lora_idx=None, *,
                      attn_kernel=None, routes=False):
    """Paged decode (block tables). The attention layout follows the
    pool as in llama.decode_step_paged: a bf16 pool stays stacked outside
    the layer scan, is read in place by the layer-indexed kernel and
    written by one batched scatter after it ("fused"); a quantized pool
    takes scatter-then-attend inside the scan ("per_layer"). `attn_kernel`
    names one explicitly. MoE FFN unchanged; `routes=True` appends the
    expert sets of the B rows, [B, routed layers, k]."""
    from kubeai_tpu.ops.paged_attention import (
        batched_scatter_sequence,
        paged_decode_attention,
        paged_decode_attention_fused,
        resolve_decode_kernel,
        scatter_decode_token,
        token_page_coords,
    )

    from kubeai_tpu.ops.kv_quant import is_quantized_kv, kv_pages_shape

    attn_kernel = resolve_decode_kernel(
        attn_kernel, quantized=is_quantized_kv(k_pages)
    )
    B = tokens.shape[0]
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    page_size = kv_pages_shape(k_pages)[2]
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    x = params["embed"][tokens]
    pos1 = positions[:, None]
    page_ids, offsets = token_page_coords(block_tables, positions, page_size)
    lengths = positions + 1

    @jax.named_scope("qkv")
    def layer_qkv(x, lp):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("be,eh->bh", h, lp["wq"]).reshape(B, 1, H, D)
        k = jnp.einsum("be,eh->bh", h, lp["wk"]).reshape(B, 1, KVH, D)
        v = jnp.einsum("be,eh->bh", h, lp["wv"]).reshape(B, 1, KVH, D)
        q = apply_rope(q, pos1, inv_freq)[:, 0]
        k = apply_rope(k, pos1, inv_freq)[:, 0]
        return q, k, v[:, 0]

    @jax.named_scope("layer_finish")
    def layer_finish(x, attn, lp):
        x = x + jnp.einsum("bh,he->be", attn.reshape(B, H * D), lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        y, topi = _moe_ffn(h2[:, None], lp, cfg)
        return x + y[:, 0], topi[:, 0]

    if attn_kernel == "per_layer":

        def layer_pl(carry, scanned):
            x, lp = carry, scanned["p"]
            kp, vp = scanned["kp"], scanned["vp"]
            q, k, v = layer_qkv(x, lp)
            with jax.named_scope("kv_page_write"):
                kp, vp = scatter_decode_token(kp, vp, k, v, page_ids, offsets)
            with jax.named_scope("paged_attention"):
                attn = paged_decode_attention(
                    q, kp, vp, block_tables, lengths
                )
            x, topi = layer_finish(x, attn, lp)
            return x, (kp, vp, topi)

        x, (k_pages, v_pages, topi_all) = jax.lax.scan(
            layer_pl, x,
            {"p": params["layers"], "kp": k_pages, "vp": v_pages},
        )
    else:

        def layer(carry, scanned):
            x, lp = carry, scanned["p"]
            q, k, v = layer_qkv(x, lp)
            with jax.named_scope("paged_attention"):
                attn = paged_decode_attention_fused(
                    q, k_pages, v_pages, k, v, block_tables, positions,
                    scanned["li"],
                )
            x, topi = layer_finish(x, attn, lp)
            return x, (k, v, topi)

        x, (k_all, v_all, topi_all) = jax.lax.scan(
            layer, x,
            {
                "p": params["layers"],
                "li": jnp.arange(cfg.num_layers, dtype=jnp.int32),
            },
        )
        with jax.named_scope("kv_page_write"):
            k_pages, v_pages = batched_scatter_sequence(
                k_pages, v_pages, k_all[:, :, None], v_all[:, :, None],
                page_ids[:, None], offsets[:, None],
            )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "be,ve->bv", x, params["lm_head"],
            preferred_element_type=jnp.float32,
        )
    if routes:
        return logits, k_pages, v_pages, _stack_routes(topi_all, cfg)
    return logits, k_pages, v_pages


def prefill_chunk(
    params,
    cfg: MixtralConfig,
    tokens: jnp.ndarray,  # [1, C] one chunk (right-padded on the last chunk)
    start: jnp.ndarray,  # scalar int32: absolute position of tokens[:, 0]
    length: jnp.ndarray,  # scalar int32: true total prompt length
    k_slot: jnp.ndarray,  # [NL, L, KVH, D] this slot's cache
    v_slot: jnp.ndarray,
    want_logits: bool = False,
    lora=None,  # accepted for signature parity; mixtral carries no LoRA
    lora_idx=None,
    *,
    routes: bool = False,
):
    """Chunked incremental prefill for Mixtral (llama-pattern attention
    chunk + the dense top-k MoE FFN, which is shape-generic over the
    chunk's [1, C, E]). Enables chunked admission and the prefix cache
    for the MoE family; equivalence vs whole-prompt prefill is
    test-enforced. `routes=True` appends the chunk's expert sets,
    [C, routed layers, k], padding rows of a last chunk included."""
    from kubeai_tpu.ops.attention import chunked_prefill_attention

    B, C = tokens.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    positions = start + jnp.arange(C)[None, :]
    x = params["embed"][tokens]

    def layer(x, scanned):
        lp = scanned["p"]
        kc, vc = scanned["kc"], scanned["vc"]  # [L, KVH, D]
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("bse,eh->bsh", h, lp["wq"]).reshape(B, C, H, D)
        k = jnp.einsum("bse,eh->bsh", h, lp["wk"]).reshape(B, C, KVH, D)
        v = jnp.einsum("bse,eh->bsh", h, lp["wv"]).reshape(B, C, KVH, D)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        kc = jax.lax.dynamic_update_slice(
            kc, k[0].astype(kc.dtype), (start, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            vc, v[0].astype(vc.dtype), (start, 0, 0)
        )
        attn = chunked_prefill_attention(q, kc[None], vc[None], start[None])
        x = x + jnp.einsum("bsh,he->bse", attn.reshape(B, C, H * D), lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        y, topi = _moe_ffn(h2, lp, cfg)
        return x + y, {"kc": kc, "vc": vc, "topi": topi[0]}

    x, outs = jax.lax.scan(
        layer, x, {"p": params["layers"], "kc": k_slot, "vc": v_slot}
    )
    k_slot, v_slot = outs["kc"], outs["vc"]
    tail = (_stack_routes(outs["topi"], cfg),) if routes else ()
    if not want_logits:
        return (None, k_slot, v_slot, *tail)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(length - 1 - start, 0, C - 1)
    last = jax.lax.dynamic_slice(x, (0, idx, 0), (1, 1, x.shape[-1]))[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    return (logits, k_slot, v_slot, *tail)


register_model_family(
    ModelFamily(
        "mixtral",
        config_from_hf=MixtralConfig.from_hf_dict,
        tiny_config=MixtralConfig.tiny,
        init_params=init_params,
        param_specs=param_specs,
        prefill=prefill,
        decode_step=decode_step,
        decode_step_paged=decode_step_paged,
        prefill_chunk=prefill_chunk,
        hf_architectures=("MixtralForCausalLM",),
        route_dims=lambda cfg: (
            cfg.num_experts, cfg.num_experts_per_tok, cfg.routed_layers
        ),
    )
)
