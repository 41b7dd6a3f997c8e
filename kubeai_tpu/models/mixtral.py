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

A second family of the same routed code, `sdar_moe` (SDAR-30B-A3B: the
Qwen3-MoE layer, generated by diffusion over blocks), differs from Mixtral
in three things the config states: `head_dim` is its own key, q and k are
RMS-normed a head before RoPE (`qk_norm`), and the experts, 128 of which a
row takes 8, are computed SPARSELY (`sparse_experts`: rows sorted by expert,
one grouped product over the experts that hold rows, added back weighted;
no row dropped). It generates a block of `block_length` positions at a time
(`block_forward_paged`, `block_commit`, docs/concepts/block-diffusion.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeai_tpu.models.registry import ModelFamily, register_model_family
from kubeai_tpu.ops.attention import decode_attention, prefill_attention
from kubeai_tpu.ops.experts import EXPERT_LEAVES, moe_sparse, stack_routes
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.projections import split_heads
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
    # 0: hidden_size // num_heads. SDAR's heads are 128 wide on a hidden
    # size of 2048 with 32 of them.
    head_dim: int = 0
    # RMSNorm over a head of q and of k (one weight of head_size each, all
    # heads share it), before RoPE.
    qk_norm: bool = False
    # Dense dispatch computes every expert on every row: num_experts /
    # num_experts_per_tok times the useful work (4x at Mixtral's 2 of 8,
    # whose experts GSPMD splits over tp; 16x at 8 of 128). Sparse dispatch
    # computes an expert on the rows routed to it.
    sparse_experts: bool = False
    # Generation by diffusion over blocks (0 = one token a forward, left to
    # right): a block of `block_length` positions starts as `mask_token_id`
    # and is filled in at most `denoising_steps` forwards, a row committed
    # once its top token's probability passes `confidence_threshold` or is
    # the block's highest; one more forward writes the block's K and V.
    block_length: int = 0
    denoising_steps: int = 0
    confidence_threshold: float = 1.0
    mask_token_id: int = -1

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

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
    def from_sdar_dict(d: dict) -> "MixtralConfig":
        """`sdar_moe` (config.json of SDAR-30B-A3B-Chat). How it generates
        is no key of config.json: block length, denoising steps, threshold
        and mask id are read from the file's `assumed` group (a benchmark
        configuration's) or its top level, else they are the family's
        published defaults."""
        if d.get("mlp_only_layers") or d.get("decoder_sparse_step", 1) != 1:
            raise ValueError("sdar_moe: every layer is routed here")
        if not d.get("norm_topk_prob", True):
            raise ValueError("sdar_moe: norm_topk_prob false is not served")
        how = {**d, **(d.get("assumed") or {})}
        return MixtralConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["moe_intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", 4),
            num_experts=d["num_experts"],
            num_experts_per_tok=d["num_experts_per_tok"],
            rope_theta=d.get("rope_theta", 1e6),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            max_position_embeddings=d.get("max_position_embeddings", 32768),
            head_dim=d.get("head_dim") or 0,
            qk_norm=True,
            sparse_experts=True,
            block_length=int(how.get("block_length", 4)),
            denoising_steps=int(how.get("denoising_steps", 4)),
            confidence_threshold=float(how.get("confidence_threshold", 0.9)),
            mask_token_id=int(how.get("mask_token_id", 151669)),
        )

    @staticmethod
    def tiny_sdar(vocab_size: int = 512) -> "MixtralConfig":
        return dataclasses.replace(
            MixtralConfig.tiny(vocab_size), num_experts=8, head_dim=32,
            qk_norm=True, sparse_experts=True, block_length=4,
            denoising_steps=4, confidence_threshold=0.9,
            mask_token_id=vocab_size - 1, rms_norm_eps=1e-6,
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
            **({"q_norm": (L, None), "k_norm": (L, None)} if cfg.qk_norm else {}),
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
            **(
                {"q_norm": jnp.ones((NL, D), dt), "k_norm": jnp.ones((NL, D), dt)}
                if cfg.qk_norm else {}
            ),
            "router": rnd(ks[5], (NL, E, X)),
            "w_gate": rnd(ks[6], (NL, X, E, M)),
            "w_up": rnd(ks[7], (NL, X, E, M)),
            "w_down": rnd(ks[8], (NL, X, M, E)),
        },
        "final_norm": jnp.ones((E,), dt),
        "lm_head": rnd(ks[9], (V, E)),
    }


def _layer_xs(params, cfg):
    """(what the layer scan slices a layer at a time, what it does not).
    A sparse family's expert weights stay whole outside the scan: the
    grouped product is a kernel, and a kernel's sliced operand would be
    copied out of the stack, 1.2 GB a layer at SDAR's sizes. It takes the
    stack and the layer's number instead (`ops.experts.moe_sparse`)."""
    layers = params["layers"]
    if not cfg.sparse_experts:
        return layers, None
    return (
        {k: v for k, v in layers.items() if k not in EXPERT_LEAVES},
        {k: layers[k] for k in EXPERT_LEAVES},
    )


def _qkv(h, lp, cfg, positions, inv_freq):
    """h [B, S, E] at `positions` [B, S] -> q [B, S, H, D], k and v
    [B, S, KVH, D], q and k rotated; with `qk_norm`, RMS-normed a head
    before the rotation. One body for a prompt's rows and a decode step's:
    ops.projections.split_heads reads S and holds the few-row forwards'
    projections before their head reshape (it says why)."""
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    q, k, v = split_heads(
        jnp.einsum("bse,eh->bsh", h, lp["wq"]),
        jnp.einsum("bse,eh->bsh", h, lp["wk"]),
        jnp.einsum("bse,eh->bsh", h, lp["wv"]),
        H, KVH, D,
    )
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return (
        apply_rope(q, positions, inv_freq),
        apply_rope(k, positions, inv_freq),
        v,
    )


@jax.named_scope("moe_ffn")
def _moe_ffn(x, lp, cfg, experts=None, layer=None):
    """x: [B, S, E] (or [B, E] for decode via S=1 squeeze by caller).

    Top-k MoE: softmax over the selected experts' router logits; then
    either all experts computed batched over the (sharded) expert axis and
    combined weighted by the routing probabilities (dense dispatch), or,
    for a `sparse_experts` family, `moe_sparse` on the stack `experts`
    at `layer`.

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
    if cfg.sparse_experts:
        if experts is None:
            raise ValueError(
                "this forward computes experts densely only: a sparse "
                "family's layers take the expert stack and their number"
            )
        B, S, E = x.shape
        y = moe_sparse(
            x.reshape(B * S, E), experts, layer,
            topi.reshape(B * S, -1), probs.reshape(B * S, -1),
        )
        return y.reshape(B, S, E), topi
    with jax.named_scope("moe_router"):
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


def prefill(params, cfg, tokens, lengths, lora=None, lora_idx=None, *,
            routes=False):
    """Whole-prompt prefill. `routes=True` appends the expert sets taken,
    [B, S, routed layers, k] (see `ops.experts.stack_routes`); the engine
    always asks for them, a caller that does not gets the three outputs it
    always got. The same holds for the other three forwards below."""
    B, S = tokens.shape
    H, D = cfg.num_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    x = params["embed"][tokens]
    scanned, experts = _layer_xs(params, cfg)

    def layer(x, xs):
        lp = xs["p"]
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, lp, cfg, positions, inv_freq)
        # A block-diffusion family's prompt is causal between blocks and
        # full inside one.
        attn = prefill_attention(q, k, v, mask_block=max(1, cfg.block_length))
        x = x + jnp.einsum("bsh,he->bse", attn.reshape(B, S, H * D), lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        y, topi = _moe_ffn(h2, lp, cfg, experts, xs["li"])
        return x + y, (k, v, topi)

    x, (k_all, v_all, topi_all) = jax.lax.scan(
        layer, x,
        {"p": scanned, "li": jnp.arange(cfg.num_layers, dtype=jnp.int32)},
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    if routes:
        return logits, k_all, v_all, stack_routes(topi_all, cfg.num_experts)
    return logits, k_all, v_all


def decode_step(params, cfg, tokens, positions, k_cache, v_cache,
                lora=None, lora_idx=None, *, routes=False):
    B = tokens.shape[0]
    H, D = cfg.num_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    x = params["embed"][tokens]
    pos1 = positions[:, None]
    lengths = positions + 1
    slot_idx = jnp.arange(B)

    def layer(carry, scanned):
        x = carry
        lp, kc, vc = scanned["p"], scanned["kc"], scanned["vc"]
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = (a[:, 0] for a in _qkv(h[:, None], lp, cfg, pos1, inv_freq))
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
        return logits, k_cache, v_cache, stack_routes(topi_all, cfg.num_experts)
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
        decode_layout,
        paged_decode_attention,
        paged_decode_attention_fused,
        scatter_decode_token,
        token_page_coords,
    )

    from kubeai_tpu.ops.kv_quant import is_quantized_kv, kv_pages_shape

    attn_kernel = attn_kernel or decode_layout(
        quantized=is_quantized_kv(k_pages)
    )
    B = tokens.shape[0]
    H, D = cfg.num_heads, cfg.head_size
    page_size = kv_pages_shape(k_pages)[2]
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    x = params["embed"][tokens]
    pos1 = positions[:, None]
    page_ids, offsets = token_page_coords(block_tables, positions, page_size)
    lengths = positions + 1

    @jax.named_scope("qkv")
    def layer_qkv(x, lp):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h[:, None], lp, cfg, pos1, inv_freq)
        return q[:, 0], k[:, 0], v[:, 0]

    scanned_p, experts = _layer_xs(params, cfg)
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    @jax.named_scope("layer_finish")
    def layer_finish(x, attn, lp, li):
        x = x + jnp.einsum("bh,he->be", attn.reshape(B, H * D), lp["wo"])
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        y, topi = _moe_ffn(h2[:, None], lp, cfg, experts, li)
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
            x, topi = layer_finish(x, attn, lp, scanned["li"])
            return x, (kp, vp, topi)

        x, (k_pages, v_pages, topi_all) = jax.lax.scan(
            layer_pl, x,
            {"p": scanned_p, "li": layer_ids, "kp": k_pages, "vp": v_pages},
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
            x, topi = layer_finish(x, attn, lp, scanned["li"])
            return x, (k, v, topi)

        x, (k_all, v_all, topi_all) = jax.lax.scan(
            layer, x, {"p": scanned_p, "li": layer_ids},
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
        return logits, k_pages, v_pages, stack_routes(topi_all, cfg.num_experts)
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
    H, D = cfg.num_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    positions = start + jnp.arange(C)[None, :]
    x = params["embed"][tokens]

    def layer(x, scanned):
        lp = scanned["p"]
        kc, vc = scanned["kc"], scanned["vc"]  # [L, KVH, D]
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, lp, cfg, positions, inv_freq)
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
    tail = (stack_routes(outs["topi"], cfg.num_experts),) if routes else ()
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


def block_forward_paged(params, cfg, tokens, positions, k_pages, v_pages,
                        block_tables, *, routes=False):
    """One forward of a block-diffusion family: `tokens` [B, R] are each
    slot's block (R = cfg.block_length positions from `positions` [B], the
    block's first, which is also the number of tokens the slot's pages
    hold), run against the slot's pages and the block's own R new K/V
    rows, full inside the block. The pool is read in place and NOT
    written: the caller writes a block's rows once the block is finished
    (`batched_scatter_sequence` of the returned K and V).

    Returns (logits [B, R, V] float32: row r predicts the token AT
    position r, no shift; k_new, v_new [NL, B, R, KVH, D]) and, with
    `routes`, the rows' expert sets [B, R, routed layers, k]: every
    forward routes every row anew."""
    from kubeai_tpu.ops.paged_attention import paged_block_attention_fused

    B, R = tokens.shape
    H, D = cfg.num_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    pos = positions[:, None] + jnp.arange(R)[None, :]
    x = params["embed"][tokens]
    scanned_p, experts = _layer_xs(params, cfg)

    def layer(x, xs):
        lp = xs["p"]
        with jax.named_scope("qkv"):
            h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(h, lp, cfg, pos, inv_freq)
        with jax.named_scope("paged_attention"):
            attn = paged_block_attention_fused(
                q, k_pages, v_pages, k, v, block_tables, positions, xs["li"]
            )
        with jax.named_scope("layer_finish"):
            x = x + jnp.einsum(
                "bsh,he->bse", attn.reshape(B, R, H * D), lp["wo"]
            )
            h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
            y, topi = _moe_ffn(h2, lp, cfg, experts, xs["li"])
        return x + y, (k, v, topi)

    x, (k_new, v_new, topi_all) = jax.lax.scan(
        layer, x,
        {"p": scanned_p, "li": jnp.arange(cfg.num_layers, dtype=jnp.int32)},
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "bre,ve->brv", x, params["lm_head"],
            preferred_element_type=jnp.float32,
        )
    if routes:
        return logits, k_new, v_new, stack_routes(topi_all, cfg.num_experts)
    return logits, k_new, v_new


@jax.named_scope("block_commit")
def block_commit(cfg, logits, tokens, chosen=None, temperature=None):
    """The family's rule for one denoising forward (`low_confidence_dynamic`
    of the published `block_diffusion_generate`): of the rows of `tokens`
    [B, R] that still hold `mask_token_id`, commit every one whose chosen
    token's probability is over `confidence_threshold`, and the most
    confident one (the first of equals) whatever its probability. `chosen`
    [B, R] are sampled tokens (None: the top token; `temperature` [B] then
    scales the logits the probability is read from, 0 meaning 1).

    `mask_token_id` is never a candidate: its logit is taken out before the
    softmax and the argmax (the published code leaves open what a row
    committed to the mask would mean; the reference does the same).

    Returns (tokens after the commits, commit [B, R] bool, confidence
    [B, R] float32)."""
    masked = tokens == cfg.mask_token_id
    ids = jnp.arange(logits.shape[-1])
    logits = jnp.where(ids == cfg.mask_token_id, -jnp.inf, logits)
    if temperature is not None:
        logits = logits / jnp.where(temperature > 0, temperature, 1.0)[:, None, None]
    top = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
    chosen = top if chosen is None else chosen
    picked = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    conf = jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))
    among = jnp.where(masked, conf, -jnp.inf)
    best = jnp.arange(tokens.shape[1])[None, :] == jnp.argmax(among, axis=-1)[:, None]
    commit = masked & ((conf > cfg.confidence_threshold) | best)
    return jnp.where(commit, chosen, tokens), commit, conf


register_model_family(
    ModelFamily(
        "sdar_moe",
        config_from_hf=MixtralConfig.from_sdar_dict,
        tiny_config=MixtralConfig.tiny_sdar,
        init_params=init_params,
        param_specs=param_specs,
        prefill=prefill,
        decode_step=None,  # no forward of this family is one token wide
        block_forward_paged=block_forward_paged,
        block_commit=block_commit,
        block_generation=lambda cfg: {
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "confidence_threshold": cfg.confidence_threshold,
            "mask_token_id": cfg.mask_token_id,
        },
        hf_architectures=("SDARMoeForCausalLM",),
        route_dims=lambda cfg: (
            cfg.num_experts, cfg.num_experts_per_tok, cfg.routed_layers
        ),
    )
)

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
