"""Gemma-family decoder (Gemma 1/2).

Covers the reference's Gemma catalog entries (e.g. `gemma-2b-it-tpu`,
reference: charts/models/values.yaml:80-87) natively. Architectural deltas
from Llama, all config-driven:

  - embeddings scaled by sqrt(hidden_size)
  - RMSNorm uses (1 + weight) (zero-centred weights)
  - GeGLU MLP (gelu(tanh) gate instead of silu)
  - separate head_dim (not hidden/heads)
  - Gemma-2: pre+post norms around attention AND MLP (sandwich), logit
    softcapping, optional query pre-scaling

Same engine contract as llama: param_specs/init_params/prefill/decode_step
with stacked layers + lax.scan, slot KV cache, LoRA-free for now (adapters
target the llama family first).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeai_tpu.models.registry import ModelFamily, register_model_family
from kubeai_tpu.ops.attention import (
    causal_prefill_attention,
    chunked_prefill_attention,
    decode_attention,
    prefill_attention,
)
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.projections import split_heads
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies
from kubeai_tpu.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class GemmaConfig:
    vocab_size: int = 256000
    hidden_size: int = 2048
    intermediate_size: int = 16384
    num_layers: int = 18
    num_heads: int = 8
    num_kv_heads: int = 1
    head_dim: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 8192
    # Gemma-2 extras
    sandwich_norms: bool = False  # pre+post norms (gemma2)
    final_logit_softcapping: float | None = None
    attn_logit_softcapping: float | None = None
    query_pre_attn_scalar: float | None = None
    # Gemma-2 alternates sliding-window (even layers) and global (odd
    # layers) attention; None = all-global (Gemma 1). `layer_types`
    # (serialized by HF as "sliding_attention"/"full_attention" per layer)
    # overrides the default alternating pattern when a checkpoint carries
    # a custom mapping.
    sliding_window: int | None = None
    layer_types: tuple[str, ...] | None = None
    dtype: Any = jnp.bfloat16

    def layer_windows(self) -> jnp.ndarray:
        """Per-layer effective window, 0 = global (scanned through the
        layer loop so one compiled graph serves both layer kinds)."""
        if self.sliding_window is None:
            return jnp.zeros((self.num_layers,), jnp.int32)
        if self.layer_types is not None:
            sliding = [t == "sliding_attention" for t in self.layer_types]
        else:
            sliding = [i % 2 == 0 for i in range(self.num_layers)]
        return jnp.asarray(
            [self.sliding_window if s else 0 for s in sliding], jnp.int32
        )

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def num_kv_heads_(self) -> int:
        return self.num_kv_heads

    @staticmethod
    def from_hf_dict(d: dict) -> "GemmaConfig":
        is_g2 = d.get("model_type") == "gemma2" or "Gemma2" in str(
            d.get("architectures")
        )
        return GemmaConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", 1),
            head_dim=d.get("head_dim", 256),
            rope_theta=d.get("rope_theta", 10000.0),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            sandwich_norms=is_g2,
            final_logit_softcapping=d.get("final_logit_softcapping"),
            attn_logit_softcapping=d.get("attn_logit_softcapping"),
            query_pre_attn_scalar=d.get("query_pre_attn_scalar"),
            sliding_window=d.get("sliding_window") if is_g2 else None,
            layer_types=tuple(d["layer_types"]) if d.get("layer_types") else None,
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "GemmaConfig":
        return GemmaConfig(
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
        )


def param_specs(cfg: GemmaConfig) -> dict:
    L = None
    layers = {
        "input_norm": (L, sh.EMBED),
        "wq": (L, sh.EMBED, sh.HEADS),
        "wk": (L, sh.EMBED, sh.KV_HEADS),
        "wv": (L, sh.EMBED, sh.KV_HEADS),
        "wo": (L, sh.HEADS, sh.EMBED),
        "pre_mlp_norm": (L, sh.EMBED),
        "w_gate": (L, sh.EMBED, sh.MLP),
        "w_up": (L, sh.EMBED, sh.MLP),
        "w_down": (L, sh.MLP, sh.EMBED),
    }
    if cfg.sandwich_norms:
        layers["post_attn_norm"] = (L, sh.EMBED)
        layers["post_mlp_norm"] = (L, sh.EMBED)
    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": layers,
        "final_norm": (sh.EMBED,),
    }


def init_params(cfg: GemmaConfig, key: jax.Array | None = None) -> dict:
    if key is None:
        key = jax.random.PRNGKey(0)
    E, H, KVH, D, M, V, NL = (
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_size,
        cfg.intermediate_size,
        cfg.vocab_size,
        cfg.num_layers,
    )
    ks = jax.random.split(key, 9)
    dt = cfg.dtype

    def rnd(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    layers = {
        "input_norm": jnp.zeros((NL, E), dt),  # (1+w) convention
        "wq": rnd(ks[1], (NL, E, H * D)),
        "wk": rnd(ks[2], (NL, E, KVH * D)),
        "wv": rnd(ks[3], (NL, E, KVH * D)),
        "wo": rnd(ks[4], (NL, H * D, E)),
        "pre_mlp_norm": jnp.zeros((NL, E), dt),
        "w_gate": rnd(ks[5], (NL, E, M)),
        "w_up": rnd(ks[6], (NL, E, M)),
        "w_down": rnd(ks[7], (NL, M, E)),
    }
    if cfg.sandwich_norms:
        layers["post_attn_norm"] = jnp.zeros((NL, E), dt)
        layers["post_mlp_norm"] = jnp.zeros((NL, E), dt)
    return {
        "embed": rnd(ks[0], (V, E)),
        "layers": layers,
        "final_norm": jnp.zeros((E,), dt),
    }


def _norm(x, w, eps):
    # Gemma stores zero-centred norm weights: scale = 1 + w.
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _softcap(x, cap):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def _mlp(x, gate, up, down):
    g = jax.nn.gelu(jnp.einsum("bse,em->bsm", x, gate), approximate=True)
    return jnp.einsum(
        "bsm,me->bse", g * jnp.einsum("bse,em->bsm", x, up), down
    )


def _q_scale(cfg: GemmaConfig) -> float:
    if cfg.query_pre_attn_scalar is not None:
        return cfg.query_pre_attn_scalar ** -0.5
    return cfg.head_size ** -0.5


def prefill(params, cfg, tokens, lengths, lora=None, lora_idx=None):
    B, S = tokens.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    x = params["embed"][tokens].astype(jnp.float32)
    x = (x * (cfg.hidden_size ** 0.5)).astype(params["embed"].dtype)

    def layer(x, scanned):
        lp, win = scanned["p"], scanned["win"]
        h = _norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("bse,eh->bsh", h, lp["wq"]).reshape(B, S, H, D)
        k = jnp.einsum("bse,eh->bsh", h, lp["wk"]).reshape(B, S, KVH, D)
        v = jnp.einsum("bse,eh->bsh", h, lp["wv"]).reshape(B, S, KVH, D)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        qs = q * (_q_scale(cfg) * D ** 0.5)
        if cfg.attn_logit_softcapping is not None or cfg.sliding_window:
            # Softcap / sliding window need the raw-logit path (the flash
            # kernel carries neither mask).
            attn = causal_prefill_attention(
                qs, k, v,
                logit_softcap=cfg.attn_logit_softcapping,
                window=win if cfg.sliding_window else None,
            )
        else:
            attn = prefill_attention(qs, k, v)
        a_out = jnp.einsum(
            "bsh,he->bse", attn.reshape(B, S, H * D), lp["wo"]
        )
        if cfg.sandwich_norms:
            a_out = _norm(a_out, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + a_out
        h2 = _norm(x, lp["pre_mlp_norm"], cfg.rms_norm_eps)
        m_out = _mlp(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        if cfg.sandwich_norms:
            m_out = _norm(m_out, lp["post_mlp_norm"], cfg.rms_norm_eps)
        x = x + m_out
        return x, (k, v)

    x, (k_all, v_all) = jax.lax.scan(
        layer, x, {"p": params["layers"], "win": cfg.layer_windows()}
    )
    x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["embed"],
        preferred_element_type=jnp.float32,
    )
    logits = _softcap(logits, cfg.final_logit_softcapping)
    return logits, k_all, v_all


def decode_step(params, cfg, tokens, positions, k_cache, v_cache,
                lora=None, lora_idx=None):
    B = tokens.shape[0]
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    x = params["embed"][tokens].astype(jnp.float32)
    x = (x * (cfg.hidden_size ** 0.5)).astype(params["embed"].dtype)
    pos1 = positions[:, None]
    lengths = positions + 1
    slot_idx = jnp.arange(B)

    def layer(carry, scanned):
        x = carry
        lp, kc, vc = scanned["p"], scanned["kc"], scanned["vc"]
        h = _norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = split_heads(
            jnp.einsum("be,eh->bh", h, lp["wq"]),
            jnp.einsum("be,eh->bh", h, lp["wk"]),
            jnp.einsum("be,eh->bh", h, lp["wv"]),
            H, KVH, D,
        )
        q = apply_rope(q[:, None], pos1, inv_freq)[:, 0]
        k = apply_rope(k[:, None], pos1, inv_freq)[:, 0]
        kc = kc.at[slot_idx, positions].set(k.astype(kc.dtype))
        vc = vc.at[slot_idx, positions].set(v.astype(vc.dtype))
        attn = decode_attention(
            q * (_q_scale(cfg) * D ** 0.5), kc, vc, lengths,
            logit_softcap=cfg.attn_logit_softcapping,
            window=scanned["win"] if cfg.sliding_window else None,
        )
        a_out = jnp.einsum("bh,he->be", attn.reshape(B, H * D), lp["wo"])
        if cfg.sandwich_norms:
            a_out = _norm(a_out, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + a_out
        h2 = _norm(x, lp["pre_mlp_norm"], cfg.rms_norm_eps)
        m_out = _mlp(h2[:, None], lp["w_gate"], lp["w_up"], lp["w_down"])[:, 0]
        if cfg.sandwich_norms:
            m_out = _norm(m_out, lp["post_mlp_norm"], cfg.rms_norm_eps)
        x = x + m_out
        return x, (kc, vc)

    x, (k_cache, v_cache) = jax.lax.scan(
        layer, x,
        {
            "p": params["layers"], "kc": k_cache, "vc": v_cache,
            "win": cfg.layer_windows(),
        },
    )
    x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "be,ve->bv", x, params["embed"], preferred_element_type=jnp.float32
    )
    logits = _softcap(logits, cfg.final_logit_softcapping)
    return logits, k_cache, v_cache


def decode_step_paged(params, cfg, tokens, positions, k_pages, v_pages,
                      block_tables, lora=None, lora_idx=None, *,
                      attn_kernel=None):
    """Paged decode (block tables). The attention layout follows the
    pool as in llama.decode_step_paged: a bf16 pool stays stacked outside
    the layer scan, is read in place by the layer-indexed kernel and
    written by one batched scatter after it ("fused"); a quantized pool
    takes scatter-then-attend inside the scan ("per_layer"). `attn_kernel`
    names one explicitly. The per-layer sliding window rides the scan,
    so Gemma-2's alternating local/global layers share one compiled
    graph."""
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
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    page_size = kv_pages_shape(k_pages)[2]
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    x = params["embed"][tokens].astype(jnp.float32)
    x = (x * (cfg.hidden_size ** 0.5)).astype(params["embed"].dtype)
    pos1 = positions[:, None]
    page_ids, offsets = token_page_coords(block_tables, positions, page_size)
    lengths = positions + 1

    def layer_qkv(x, lp):
        h = _norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = split_heads(
            jnp.einsum("be,eh->bh", h, lp["wq"]),
            jnp.einsum("be,eh->bh", h, lp["wk"]),
            jnp.einsum("be,eh->bh", h, lp["wv"]),
            H, KVH, D,
        )
        q = apply_rope(q[:, None], pos1, inv_freq)[:, 0]
        k = apply_rope(k[:, None], pos1, inv_freq)[:, 0]
        return q * (_q_scale(cfg) * D ** 0.5), k, v

    def layer_finish(x, attn, lp):
        a_out = jnp.einsum("bh,he->be", attn.reshape(B, H * D), lp["wo"])
        if cfg.sandwich_norms:
            a_out = _norm(a_out, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + a_out
        h2 = _norm(x, lp["pre_mlp_norm"], cfg.rms_norm_eps)
        m_out = _mlp(h2[:, None], lp["w_gate"], lp["w_up"], lp["w_down"])[:, 0]
        if cfg.sandwich_norms:
            m_out = _norm(m_out, lp["post_mlp_norm"], cfg.rms_norm_eps)
        return x + m_out

    if attn_kernel == "per_layer":

        def layer_pl(carry, scanned):
            x, lp = carry, scanned["p"]
            kp, vp = scanned["kp"], scanned["vp"]
            q, k, v = layer_qkv(x, lp)
            kp, vp = scatter_decode_token(kp, vp, k, v, page_ids, offsets)
            attn = paged_decode_attention(
                q, kp, vp, block_tables, lengths,
                logit_softcap=cfg.attn_logit_softcapping,
                window=scanned["win"] if cfg.sliding_window else None,
            )
            return layer_finish(x, attn, lp), (kp, vp)

        x, (k_pages, v_pages) = jax.lax.scan(
            layer_pl, x,
            {
                "p": params["layers"], "win": cfg.layer_windows(),
                "kp": k_pages, "vp": v_pages,
            },
        )
    else:

        def layer(carry, scanned):
            x, lp = carry, scanned["p"]
            q, k, v = layer_qkv(x, lp)
            attn = paged_decode_attention_fused(
                q, k_pages, v_pages, k, v,
                block_tables, positions, scanned["li"],
                logit_softcap=cfg.attn_logit_softcapping,
                window=scanned["win"] if cfg.sliding_window else None,
            )
            return layer_finish(x, attn, lp), (k, v)

        x, (k_all, v_all) = jax.lax.scan(
            layer, x,
            {
                "p": params["layers"],
                "win": cfg.layer_windows(),
                "li": jnp.arange(cfg.num_layers, dtype=jnp.int32),
            },
        )
        k_pages, v_pages = batched_scatter_sequence(
            k_pages, v_pages, k_all[:, :, None], v_all[:, :, None],
            page_ids[:, None], offsets[:, None],
        )
    x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "be,ve->bv", x, params["embed"], preferred_element_type=jnp.float32
    )
    logits = _softcap(logits, cfg.final_logit_softcapping)
    return logits, k_pages, v_pages


def prefill_chunk(
    params,
    cfg: GemmaConfig,
    tokens: jnp.ndarray,  # [1, C] one chunk (right-padded on the last chunk)
    start: jnp.ndarray,  # scalar int32: absolute position of tokens[:, 0]
    length: jnp.ndarray,  # scalar int32: true total prompt length
    k_slot: jnp.ndarray,  # [NL, L, KVH, D] this slot's cache
    v_slot: jnp.ndarray,
    want_logits: bool = False,
    lora=None,  # accepted for signature parity; gemma carries no LoRA
    lora_idx=None,
):
    """Chunked incremental prefill for Gemma 1/2 (same contract as
    llama.prefill_chunk): one [1, C] graph per chunk against the slot
    cache, causal-frontier masking by absolute position — plus Gemma's
    specifics (embed normalizer, query scale, logit softcaps, sandwich
    norms, per-layer sliding-window alternation). Enables the engine's
    chunked admission and prefix cache for the gemma family; equivalence
    vs whole-prompt prefill is test-enforced."""
    B, C = tokens.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(D, cfg.rope_theta))
    positions = start + jnp.arange(C)[None, :]
    x = params["embed"][tokens].astype(jnp.float32)
    x = (x * (cfg.hidden_size ** 0.5)).astype(params["embed"].dtype)

    def layer(x, scanned):
        lp, win = scanned["p"], scanned["win"]
        kc, vc = scanned["kc"], scanned["vc"]  # [L, KVH, D]
        h = _norm(x, lp["input_norm"], cfg.rms_norm_eps)
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
        attn = chunked_prefill_attention(
            q * (_q_scale(cfg) * D ** 0.5), kc[None], vc[None], start[None],
            logit_softcap=cfg.attn_logit_softcapping,
            window=win if cfg.sliding_window else None,
        )
        a_out = jnp.einsum(
            "bsh,he->bse", attn.reshape(B, C, H * D), lp["wo"]
        )
        if cfg.sandwich_norms:
            a_out = _norm(a_out, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + a_out
        h2 = _norm(x, lp["pre_mlp_norm"], cfg.rms_norm_eps)
        m_out = _mlp(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        if cfg.sandwich_norms:
            m_out = _norm(m_out, lp["post_mlp_norm"], cfg.rms_norm_eps)
        x = x + m_out
        return x, {"kc": kc, "vc": vc}

    x, caches = jax.lax.scan(
        layer, x,
        {
            "p": params["layers"], "win": cfg.layer_windows(),
            "kc": k_slot, "vc": v_slot,
        },
    )
    k_slot, v_slot = caches["kc"], caches["vc"]
    if not want_logits:
        return None, k_slot, v_slot
    x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(length - 1 - start, 0, C - 1)
    last = jax.lax.dynamic_slice(x, (0, idx, 0), (1, 1, x.shape[-1]))[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["embed"],
        preferred_element_type=jnp.float32,
    )
    logits = _softcap(logits, cfg.final_logit_softcapping)
    return logits, k_slot, v_slot


register_model_family(
    ModelFamily(
        "gemma",
        config_from_hf=GemmaConfig.from_hf_dict,
        tiny_config=GemmaConfig.tiny,
        init_params=init_params,
        param_specs=param_specs,
        prefill=prefill,
        decode_step=decode_step,
        decode_step_paged=decode_step_paged,
        prefill_chunk=prefill_chunk,
        hf_architectures=("GemmaForCausalLM", "Gemma2ForCausalLM"),
    )
)
