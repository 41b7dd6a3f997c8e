"""Llama-family decoder (Llama 2/3/3.1) — the flagship text-generation model.

TPU-first choices:
  - Layers are *stacked* ([num_layers, ...] leading axis) and iterated with
    `lax.scan`: compile time is O(1) in depth (matters for 70B/80-layer),
    and XLA pipelines the per-layer HBM streaming.
  - Pure functional: params are a flat dict pytree; every leaf has a logical
    sharding spec (see `param_specs`) consumed by kubeai_tpu.parallel.
  - bfloat16 params/activations, float32 softmax/norm accumulations — MXU
    native precision.
  - GQA: q reshaped to [kv_heads, group] (see ops.attention), never repeated.

Capability parity: this replaces the Llama presets the reference serves via
vLLM images, e.g. `llama-3.1-8b-instruct-tpu` with --tensor-parallel-size=4
on google-tpu-v5e-2x2 (reference: charts/models/values.yaml:119-131). Here
TP is the `tp` mesh axis and XLA's collectives, not an engine flag.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.projections import split_heads
from kubeai_tpu.ops.rope import (
    apply_rope,
    rope_attention_scaling,
    rope_frequencies,
)
from kubeai_tpu.ops.attention import decode_attention, prefill_attention
from kubeai_tpu.engine.quantization import dequantize as _w
from kubeai_tpu.parallel import sharding as sh


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None
    rope_theta: float = 500000.0
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style q/k/v biases
    dtype: Any = jnp.bfloat16

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @staticmethod
    def from_hf_dict(d: dict) -> "LlamaConfig":
        """Build from a HuggingFace config.json dict (architectures Llama*)."""
        return LlamaConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
            head_dim=d.get("head_dim"),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=d.get("rope_scaling"),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            # Qwen2 always uses qkv biases; HF exposes attention_bias on
            # both configs (Qwen2 defaults true, Llama false).
            attention_bias=d.get(
                "attention_bias",
                d.get("model_type") == "qwen2",
            ),
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """A test-sized config (runs in ms on CPU)."""
        return LlamaConfig(
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            rope_theta=10000.0,
            max_position_embeddings=1024,
        )


def param_specs(cfg: LlamaConfig) -> dict:
    """Logical sharding axes per parameter (leading axis = stacked layers,
    sharded over the pp mesh axis when it exists — replicated otherwise)."""
    L = sh.LAYERS
    layers = {
        "input_norm": (L, sh.EMBED),
        "wq": (L, sh.EMBED, sh.HEADS),
        "wk": (L, sh.EMBED, sh.KV_HEADS),
        "wv": (L, sh.EMBED, sh.KV_HEADS),
        "wo": (L, sh.HEADS, sh.EMBED),
        "post_attn_norm": (L, sh.EMBED),
        "w_gate": (L, sh.EMBED, sh.MLP),
        "w_up": (L, sh.EMBED, sh.MLP),
        "w_down": (L, sh.MLP, sh.EMBED),
    }
    if cfg.attention_bias:
        layers["bq"] = (L, sh.HEADS)
        layers["bk"] = (L, sh.KV_HEADS)
        layers["bv"] = (L, sh.KV_HEADS)
    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": layers,
        "final_norm": (sh.EMBED,),
        "lm_head": (sh.VOCAB, sh.EMBED),
    }


def init_params(cfg: LlamaConfig, key: jax.Array | None = None) -> dict:
    """Random init (for tests and benchmarks; real weights come from
    kubeai_tpu.engine.weights loaders)."""
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
    ks = jax.random.split(key, 10)
    scale = 0.02
    dt = cfg.dtype

    def rnd(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    layers = {
        "input_norm": jnp.ones((NL, E), dt),
        "wq": rnd(ks[1], (NL, E, H * D)),
        "wk": rnd(ks[2], (NL, E, KVH * D)),
        "wv": rnd(ks[3], (NL, E, KVH * D)),
        "wo": rnd(ks[4], (NL, H * D, E)),
        "post_attn_norm": jnp.ones((NL, E), dt),
        "w_gate": rnd(ks[5], (NL, E, M)),
        "w_up": rnd(ks[6], (NL, E, M)),
        "w_down": rnd(ks[7], (NL, M, E)),
    }
    if cfg.attention_bias:
        layers["bq"] = rnd(ks[9], (NL, H * D))
        layers["bk"] = jnp.zeros((NL, KVH * D), dt)
        layers["bv"] = jnp.zeros((NL, KVH * D), dt)
    params = {
        "embed": rnd(ks[0], (V, E)),
        "layers": layers,
        "final_norm": jnp.ones((E,), dt),
        "lm_head": rnd(ks[8], (V, E)),
    }
    if cfg.tie_word_embeddings:
        params["lm_head"] = params["embed"]
    return params


@jax.named_scope("mlp")
def _mlp(x, gate, up, down):
    return jnp.einsum(
        "bsm,me->bse", jax.nn.silu(jnp.einsum("bse,em->bsm", x, _w(gate)))
        * jnp.einsum("bse,em->bsm", x, _w(up)),
        _w(down),
    )


# ---- LoRA (hot-swappable, batched) ------------------------------------------
#
# Adapter weights live in fixed-shape stacked buffers so loading/unloading an
# adapter is a buffer update, never a recompile (the hot-swap requirement the
# reference meets through vLLM's dynamic LoRA API —
# reference: internal/vllmclient/client.go:30-73, adapters.go:24-118):
#
#   A[target]: [n_adapters, NL, E, r_max]    B[target]: [n_adapters, NL, r_max, out]
#
# Adapter index 0 is reserved as all-zeros ("no adapter"); per-request
# adapter selection is a gather over the adapter axis, so one batched decode
# serves a mix of adapters (punica-style batching, MXU-friendly).

LORA_TARGETS = ("wq", "wk", "wv", "wo")


def init_lora_buffers(
    cfg: LlamaConfig, n_adapters: int, max_rank: int, dtype=None
) -> dict:
    dtype = dtype or cfg.dtype
    E, H, KVH, D = (
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_size,
    )
    NL = cfg.num_layers
    out_dims = {"wq": H * D, "wk": KVH * D, "wv": KVH * D, "wo": E}
    in_dims = {"wq": E, "wk": E, "wv": E, "wo": H * D}
    bufs = {}
    for t in LORA_TARGETS:
        bufs[t] = {
            "A": jnp.zeros((n_adapters, NL, in_dims[t], max_rank), dtype),
            "B": jnp.zeros((n_adapters, NL, max_rank, out_dims[t]), dtype),
        }
    return bufs


def _lora_delta(x, A, B, idx):
    """x: [B, S, in] (or [B, in]); A: [n, in, r], B: [n, r, out] for ONE
    layer; idx: [B] adapter index per row. Returns the low-rank delta."""
    Ag = A[idx]  # [B, in, r]
    Bg = B[idx]  # [B, r, out]
    if x.ndim == 2:
        xa = jnp.einsum("be,ber->br", x, Ag)
        return jnp.einsum("br,bro->bo", xa, Bg)
    xa = jnp.einsum("bse,ber->bsr", x, Ag)
    return jnp.einsum("bsr,bro->bso", xa, Bg)


def _scan_xs(params: dict, lora: dict | None):
    """Build scan inputs: per-layer params plus (optionally) per-layer LoRA
    slices. Adapter axis moves behind the layer axis so lax.scan slices
    layers: [n, NL, ...] -> [NL, n, ...]."""
    if lora is None:
        return {"p": params["layers"]}
    return {
        "p": params["layers"],
        "l": {
            t: {
                "A": jnp.moveaxis(lora[t]["A"], 1, 0),
                "B": jnp.moveaxis(lora[t]["B"], 1, 0),
            }
            for t in LORA_TARGETS
        },
    }


def prefill(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, S] int32, right-padded
    lengths: jnp.ndarray,  # [B] true prompt lengths
    lora: dict | None = None,  # stacked adapter buffers (init_lora_buffers)
    lora_idx: jnp.ndarray | None = None,  # [B] adapter index (0 = none)
    mesh=None,  # Mesh with an sp axis > 1 → ring-attention prefill
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-prompt forward. Returns (last_token_logits [B, V],
    k_all [NL, B, S, KVH, D], v_all [NL, B, S, KVH, D]).

    The caller scatters the returned KV into the slot's pages
    (kubeai_tpu.ops.paged_attention.batched_scatter_sequence).

    Long-context serving: when `mesh` carries an sp axis of size > 1 (and
    the padded length divides by it), prefill attention runs as RING
    ATTENTION with the sequence sharded over sp — each device holds S/sp
    of the prompt and K/V rotate over ICI (parallel/ring_attention.py).
    The engine passes its mesh automatically, making sp a serving-path
    knob rather than a demo.
    """
    B, S = tokens.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    use_ring = sp > 1 and S % sp == 0 and (S // sp) >= 1
    if use_ring:
        from kubeai_tpu.parallel.ring_attention import ring_attention_sharded

        def attend(q, k, v):
            return ring_attention_sharded(q, k, v, mesh)
    else:
        attend = prefill_attention
    inv_freq = jnp.asarray(
        rope_frequencies(
            D, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        )
    )
    msc = rope_attention_scaling(cfg.rope_scaling)
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    x = params["embed"][tokens]  # gather: [B, S, E]

    def layer(x, scanned):
        lp = scanned["p"]
        lor = scanned.get("l")

        def proj(h, w, target, bias=None):
            out = jnp.einsum("bse,eh->bsh", h, _w(w))
            if bias is not None:
                out = out + bias
            if lor is not None:
                out = out + _lora_delta(
                    h, lor[target]["A"], lor[target]["B"], lora_idx
                )
            return out

        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = proj(h, lp["wq"], "wq", lp.get("bq")).reshape(B, S, H, D)
        k = proj(h, lp["wk"], "wk", lp.get("bk")).reshape(B, S, KVH, D)
        v = proj(h, lp["wv"], "wv", lp.get("bv")).reshape(B, S, KVH, D)
        q = apply_rope(q, positions, inv_freq, msc)
        k = apply_rope(k, positions, inv_freq, msc)
        attn = attend(q, k, v)
        x = x + proj(attn.reshape(B, S, H * D), lp["wo"], "wo")
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + _mlp(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, (k, v)

    x, (k_all, v_all) = jax.lax.scan(layer, x, _scan_xs(params, lora))
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # Logits only for each sequence's final real token.
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]  # [B, E]
    # bf16 matmul, fp32 accumulation: MXU-native, no fp32 weight copy.
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "be,ve->bv", last, params["lm_head"],
            preferred_element_type=jnp.float32,
        )
    return logits, k_all, v_all


def decode_step(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B] one token per slot
    positions: jnp.ndarray,  # [B] absolute position of each token
    k_cache: jnp.ndarray,  # [NL, B, L, KVH, D]
    v_cache: jnp.ndarray,  # [NL, B, L, KVH, D]
    lora: dict | None = None,  # stacked adapter buffers
    lora_idx: jnp.ndarray | None = None,  # [B] adapter index per slot
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step for every active slot. Writes the new token's KV into
    the cache (functional update) and returns (logits [B, V], k_cache, v_cache).
    """
    B = tokens.shape[0]
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(
        rope_frequencies(
            D, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        )
    )
    msc = rope_attention_scaling(cfg.rope_scaling)
    x = params["embed"][tokens]  # [B, E]
    pos1 = positions[:, None]  # [B, 1]
    lengths = positions + 1  # cache valid length incl. this token
    slot_idx = jnp.arange(B)

    def layer(carry, scanned):
        x = carry
        lp = scanned["p"]
        lor = scanned.get("l")
        kc, vc = scanned["kc"], scanned["vc"]

        def proj(h, w, target, bias=None):
            out = jnp.einsum("be,eh->bh", h, _w(w))
            if bias is not None:
                out = out + bias
            if lor is not None:
                out = out + _lora_delta(
                    h, lor[target]["A"], lor[target]["B"], lora_idx
                )
            return out

        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = split_heads(
            proj(h, lp["wq"], "wq", lp.get("bq")),
            proj(h, lp["wk"], "wk", lp.get("bk")),
            proj(h, lp["wv"], "wv", lp.get("bv")),
            H, KVH, D,
        )
        q = apply_rope(q[:, None], pos1, inv_freq, msc)[:, 0]  # [B, H, D]
        k = apply_rope(k[:, None], pos1, inv_freq, msc)[:, 0]  # [B, KVH, D]
        # Scatter the new token's K/V into each slot at its position.
        kc = kc.at[slot_idx, positions].set(k.astype(kc.dtype))
        vc = vc.at[slot_idx, positions].set(v.astype(vc.dtype))
        attn = decode_attention(q, kc, vc, lengths)  # [B, H, D]
        x = x + proj(attn.reshape(B, H * D), lp["wo"], "wo")
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + _mlp(h2[:, None], lp["w_gate"], lp["w_up"], lp["w_down"])[:, 0]
        return x, (kc, vc)

    xs = _scan_xs(params, lora)
    xs["kc"] = k_cache
    xs["vc"] = v_cache
    x, (k_cache, v_cache) = jax.lax.scan(layer, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "be,ve->bv", x, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    return logits, k_cache, v_cache


@jax.named_scope("qkv")
def _decode_layer_qkv(x, lp, lor, cfg, inv_freq, msc, pos1, lora_idx):
    """Shared decode-layer front half: norm, QKV projection (+bias/LoRA),
    rope. Returns (q [B,H,D], k [B,KVH,D], v [B,KVH,D], proj) where proj
    is reused for the output projection. One body for every paged decode
    layout — decode_step_paged's fused AND per_layer branches, and the
    pipeline path (_paged_decode_layer) — so the projection/LoRA math
    cannot drift between them. The three projections go to heads through
    ops.projections.split_heads, which says why."""
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size

    def proj(h, w, target, bias=None):
        out = jnp.einsum("be,eh->bh", h, _w(w))
        if bias is not None:
            out = out + bias
        if lor is not None:
            out = out + _lora_delta(
                h, lor[target]["A"], lor[target]["B"], lora_idx
            )
        return out

    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q, k, v = split_heads(
        proj(h, lp["wq"], "wq", lp.get("bq")),
        proj(h, lp["wk"], "wk", lp.get("bk")),
        proj(h, lp["wv"], "wv", lp.get("bv")),
        H, KVH, D,
    )
    q = apply_rope(q[:, None], pos1, inv_freq, msc)[:, 0]  # [B, H, D]
    k = apply_rope(k[:, None], pos1, inv_freq, msc)[:, 0]  # [B, KVH, D]
    return q, k, v, proj


@jax.named_scope("layer_finish")
def _decode_layer_finish(x, attn, lp, proj, cfg):
    """Shared decode-layer back half: output projection, residual, MLP."""
    B = x.shape[0]
    H, D = cfg.num_heads, cfg.head_size
    x = x + proj(attn.reshape(B, H * D), lp["wo"], "wo")
    h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
    x = x + _mlp(h2[:, None], lp["w_gate"], lp["w_up"], lp["w_down"])[:, 0]
    return x


def _paged_decode_layer(
    x, scanned, cfg, inv_freq, msc, positions, lengths,
    page_ids, offsets, block_tables, lora_idx,
):
    """One decode layer against per-layer page pools: project, rope,
    scatter the new token's K/V through the block tables, attend over
    resident pages, MLP. Used by decode_step_paged's "per_layer" layout
    (pools ride the layer scan as xs/ys) and by decode_step_paged_pp
    (stage-local scan inside the GPipe shard_map, pools are stage-local
    scan carries); the fused layout shares the projection/MLP halves via
    _decode_layer_qkv/_decode_layer_finish but attends through the fused
    kernel with a deferred scatter."""
    from kubeai_tpu.ops.paged_attention import (
        paged_decode_attention,
        scatter_decode_token,
    )

    lp = scanned["p"]
    lor = scanned.get("l")
    kp, vp = scanned["kp"], scanned["vp"]
    q, k, v, proj = _decode_layer_qkv(
        x, lp, lor, cfg, inv_freq, msc, positions[:, None], lora_idx
    )
    with jax.named_scope("kv_page_write"):
        kp, vp = scatter_decode_token(kp, vp, k, v, page_ids, offsets)
    with jax.named_scope("paged_attention"):
        attn = paged_decode_attention(q, kp, vp, block_tables, lengths)
    x = _decode_layer_finish(x, attn, lp, proj, cfg)
    return x, (kp, vp)


def decode_step_paged(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B] one token per slot
    positions: jnp.ndarray,  # [B] absolute position of each token
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D] page pools
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP] page ids per slot (-1 = free)
    lora: dict | None = None,
    lora_idx: jnp.ndarray | None = None,
    *,
    attn_kernel: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decode step against the PAGED cache. The layout follows the pool
    (ops.paged_attention.decode_layout; `attn_kernel` names one
    explicitly, as a test's reference: no engine option does):

    "fused", every bf16 pool — the stacked [NL, ...] page pools stay
    OUTSIDE the layer scan and are never sliced, copied or re-stacked:
    the Pallas kernel reads a layer's resident pages straight from HBM
    through a scalar-prefetched layer index, the new token's K/V is
    folded in as an extra attention column (it is NOT in the pool yet),
    and all layers' new rows are written in place by ONE batched scatter
    after the scan. Per-step cache writes are O(NL * B) tokens and reads
    only each slot's resident pages.

    "per_layer", a quantized pool — scatter-then-attend inside the layer
    scan: the pools ride the scan as xs/ys (XLA slices a layer's pool out
    of the stack and writes it back every layer, and copies the pool
    once a step: PERF.md section 6, PR 25) and each layer attends through
    paged_decode_attention, which has the only int8 path.

    Both layouts share _decode_layer_qkv/_decode_layer_finish, so the
    projection/LoRA/MLP math cannot drift between them."""
    from kubeai_tpu.ops.kv_quant import is_quantized_kv, kv_pages_shape
    from kubeai_tpu.ops.paged_attention import (
        batched_scatter_sequence,
        decode_layout,
        paged_decode_attention_fused,
        token_page_coords,
    )

    attn_kernel = attn_kernel or decode_layout(
        quantized=is_quantized_kv(k_pages)
    )
    inv_freq = jnp.asarray(
        rope_frequencies(
            cfg.head_size, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        )
    )
    msc = rope_attention_scaling(cfg.rope_scaling)
    page_size = kv_pages_shape(k_pages)[2]
    x = params["embed"][tokens]  # [B, E]
    page_ids, offsets = token_page_coords(block_tables, positions, page_size)
    pos1 = positions[:, None]
    xs = _scan_xs(params, lora)

    if attn_kernel == "per_layer":
        lengths = positions + 1

        def layer_pl(carry, scanned):
            return _paged_decode_layer(
                carry, scanned, cfg, inv_freq, msc, positions, lengths,
                page_ids, offsets, block_tables, lora_idx,
            )

        xs["kp"] = k_pages
        xs["vp"] = v_pages
        x, (k_pages, v_pages) = jax.lax.scan(layer_pl, x, xs)
    else:

        def layer(carry, scanned):
            x = carry
            lp = scanned["p"]
            lor = scanned.get("l")
            q, k, v, proj = _decode_layer_qkv(
                x, lp, lor, cfg, inv_freq, msc, pos1, lora_idx
            )
            with jax.named_scope("paged_attention"):
                attn = paged_decode_attention_fused(
                    q, k_pages, v_pages, k, v, block_tables, positions,
                    scanned["li"],
                )
            x = _decode_layer_finish(x, attn, lp, proj, cfg)
            return x, (k, v)

        xs["li"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        x, (k_all, v_all) = jax.lax.scan(layer, x, xs)
        # One batched write for every layer's new token ([NL, B, KVH, D]).
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
    return logits, k_pages, v_pages


def decode_step_paged_pp(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B] one token per slot
    positions: jnp.ndarray,  # [B]
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D], layer axis sharded on pp
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    lora: dict | None = None,
    lora_idx: jnp.ndarray | None = None,
    *,
    mesh,
    microbatches: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel paged decode: GPipe microbatching over the pp
    mesh axis with STAGE-LOCAL KV. Stage s owns layers [s*NL/P, (s+1)*NL/P)
    — both their weights and their page pools (the [NL, ...] leading axis
    of params["layers"] and the pools shards over pp, see param_specs /
    Engine pool_sharding) — so cache reads/writes never cross stages;
    only [mb, E] activations hop stage-to-stage via ppermute.

    Numerics are identical to decode_step_paged (tested): same per-layer
    math, same scatter-before-attend ordering per microbatch; off-schedule
    ticks compute on clamped duplicate microbatches and their cache writes
    are redirected to reserved scratch page 0 (the same sink
    token_page_coords uses for unallocated entries).

    The reference has no PP anywhere (engines are single-Pod opaque,
    internal/modelcontroller/pod_plan.go:28-156); SURVEY §2's
    TPU-equivalents list makes PP for >8B this repo's obligation.
    """
    from jax.sharding import PartitionSpec as P

    from kubeai_tpu.ops.paged_attention import token_page_coords
    from kubeai_tpu.parallel.mesh import AXIS_PIPELINE

    B = tokens.shape[0]
    M = microbatches
    if M < 1 or B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    n_stages = mesh.shape[AXIS_PIPELINE]
    NL = k_pages.shape[0]
    if NL % n_stages:
        raise ValueError(f"{NL} layers not divisible by {n_stages} pp stages")
    page_size = k_pages.shape[2]
    inv_freq = jnp.asarray(
        rope_frequencies(
            cfg.head_size, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        )
    )
    msc = rope_attention_scaling(cfg.rope_scaling)
    lengths = positions + 1
    page_ids, offsets = token_page_coords(block_tables, positions, page_size)
    if lora_idx is None:
        lora_idx = jnp.zeros((B,), jnp.int32)

    mb = B // M

    def mbt(a):
        return a.reshape(M, mb, *a.shape[1:])

    x_mb = mbt(params["embed"][tokens])  # [M, mb, E]
    pos_mb, len_mb = mbt(positions), mbt(lengths)
    pid_mb, off_mb = mbt(page_ids), mbt(offsets)
    bt_mb, lidx_mb = mbt(block_tables), mbt(lora_idx)

    xs = _scan_xs(params, lora)
    xs_specs = jax.tree_util.tree_map(lambda _: P(AXIS_PIPELINE), xs)
    rep = P()

    # tp > 1 composes via PARTIAL-manual shard_map: manual collectives
    # over pp only, while tp (Megatron-sharded projections and KV heads)
    # stays under GSPMD, which keeps inserting its own collectives inside
    # the stage body — this is what lets pp compose with tp (the
    # 70B-on-v5e-8 plan: pp=2 × tp=4) without hand-writing the
    # tensor-parallel psums. With tp == 1 the shard_map stays FULLY
    # manual (the pre-composition behavior): partial-manual changes XLA's
    # fusion choices inside the body, which reorders bf16 rounding enough
    # to flip near-tie samples vs the single-device engine — keep pure-pp
    # deployments bit-stable.
    # NOTE: no jax.lax.psum over pp in the body — psum over the manual
    # axis of a partial-manual shard_map crashes XLA's partitioners (both
    # Shardy and GSPMD, jax 0.9: "Invalid binary instruction opcode
    # copy"); the stage outputs are stacked via out_specs instead and the
    # last stage selected outside.
    tp_size = mesh.shape.get("tp", 1)
    manual_kw = (
        {"axis_names": {AXIS_PIPELINE}, "check_vma": True}
        if tp_size > 1 else {"check_vma": False}
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            xs_specs, P(AXIS_PIPELINE), P(AXIS_PIPELINE),
            rep, rep, rep, rep, rep, rep, rep,
        ),
        out_specs=(
            P(AXIS_PIPELINE), P(AXIS_PIPELINE), P(AXIS_PIPELINE),
        ),
        **manual_kw,
    )
    def run(xs, kp, vp, x_mb, pos_mb, len_mb, pid_mb, off_mb, bt_mb, lidx_mb):
        stage = jax.lax.axis_index(AXIS_PIPELINE)
        last = n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def local_layers(h, kp, vp, pos, lens, pid, off, bt, lidx):
            """One pass through this stage's layer slice; returns updated
            local pools. Same per-layer body as decode_step_paged
            (_paged_decode_layer), so the paths cannot drift."""

            def layer(carry, scanned):
                return _paged_decode_layer(
                    carry, scanned, cfg, inv_freq, msc, pos, lens,
                    pid, off, bt, lidx,
                )

            xs_l = dict(xs)
            xs_l["kp"] = kp
            xs_l["vp"] = vp
            y, (kp, vp) = jax.lax.scan(layer, h, xs_l)
            return y, kp, vp

        ticks = M + n_stages - 1

        def tick(carry, t):
            buf, kp, vp, out = carry
            idx = jnp.clip(t - stage, 0, M - 1)
            active = (t - stage >= 0) & (t - stage < M)
            h = jnp.where(stage == 0, x_mb[jnp.clip(t, 0, M - 1)], buf)
            # Off-schedule ticks recompute a clamped duplicate microbatch;
            # their K/V writes sink into reserved scratch page 0.
            pid = jnp.where(active, pid_mb[idx], 0)
            off = jnp.where(active, off_mb[idx], 0)
            y, kp, vp = local_layers(
                h, kp, vp, pos_mb[idx], len_mb[idx], pid, off,
                bt_mb[idx], lidx_mb[idx],
            )
            mb_out = t - last
            store = (stage == last) & (mb_out >= 0)
            out = jnp.where(
                store, out.at[jnp.clip(mb_out, 0, M - 1)].set(y), out
            )
            buf = jax.lax.ppermute(y, AXIS_PIPELINE, fwd)
            return (buf, kp, vp, out), None

        # The activation buffer and output accumulator START identical on
        # every stage but become stage-varying inside the scan (ppermute /
        # stage-gated writes): mark them varying over pp up front so the
        # scan carry types are stable under vma tracking.
        zero = jax.lax.pcast(
            jnp.zeros_like(x_mb[0]), AXIS_PIPELINE, to="varying"
        )
        out0 = jax.lax.pcast(
            jnp.zeros_like(x_mb), AXIS_PIPELINE, to="varying"
        )
        (_, kp, vp, out), _ = jax.lax.scan(
            tick, (zero, kp, vp, out0), jnp.arange(ticks)
        )
        return out[None], kp, vp  # [1, M, mb, E] per stage

    hidden, k_pages, v_pages = run(
        xs, k_pages, v_pages, x_mb, pos_mb, len_mb, pid_mb, off_mb,
        bt_mb, lidx_mb,
    )
    # hidden is [n_stages, M, mb, E]; only the LAST stage stored real
    # microbatch outputs (the other stages' accumulators are zeros).
    x = hidden[-1].reshape(B, -1)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "be,ve->bv", x, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    return logits, k_pages, v_pages


def trunk_layer(x: jnp.ndarray, lp: dict, cfg: LlamaConfig) -> jnp.ndarray:
    """One trunk layer [B, S, E] -> [B, S, E] (per-layer params `lp`).
    Module-level (not a closure) so pipeline parallelism can stage it
    (parallel/pipeline.py shards the stacked layer axis over pp)."""
    B, S, _ = x.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(
        D, cfg.rope_theta, cfg.rope_scaling, cfg.max_position_embeddings,
    ))
    msc = rope_attention_scaling(cfg.rope_scaling)
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q = jnp.einsum("bse,eh->bsh", h, _w(lp["wq"]))
    if "bq" in lp:
        q = q + lp["bq"]
    k = jnp.einsum("bse,eh->bsh", h, _w(lp["wk"]))
    if "bk" in lp:
        k = k + lp["bk"]
    v = jnp.einsum("bse,eh->bsh", h, _w(lp["wv"]))
    if "bv" in lp:
        v = v + lp["bv"]
    q = apply_rope(q.reshape(B, S, H, D), positions, inv_freq, msc)
    k = apply_rope(k.reshape(B, S, KVH, D), positions, inv_freq, msc)
    attn = prefill_attention(q, k, v.reshape(B, S, KVH, D))
    x = x + jnp.einsum("bsh,he->bse", attn.reshape(B, S, H * D), _w(lp["wo"]))
    h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
    return x + _mlp(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


def _verify_page_coords(block_tables, positions, K, page_size):
    """Page coords for all K window positions per slot: ([B, K], [B, K])."""
    from kubeai_tpu.ops.paged_attention import token_page_coords

    ids_list, offs_list = [], []
    for k_i in range(K):
        ids, offs = token_page_coords(
            block_tables, positions + k_i, page_size
        )
        ids_list.append(ids)
        offs_list.append(offs)
    return jnp.stack(ids_list, axis=1), jnp.stack(offs_list, axis=1)


def _paged_verify_layer(
    carry, scanned, cfg, inv_freq, msc, pos_k, page_ids, offsets,
    block_tables, positions, lora_idx,
):
    """One verify layer over a [B, K, E] window against the paged cache.
    Shared by decode_verify_paged (layer scan over the full stack) and
    decode_verify_paged_pp (stage-local layer scans) so the speculative
    math cannot drift between the single-mesh and pipeline paths — the
    same anti-drift guarantee _paged_decode_layer gives vanilla decode."""
    from kubeai_tpu.ops.paged_attention import paged_verify_attention

    x = carry
    B, K, _ = x.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    lp = scanned["p"]
    lor = scanned.get("l")
    kp, vp = scanned["kp"], scanned["vp"]

    def proj(h, w, target, bias=None):
        out = jnp.einsum("bke,eh->bkh", h, _w(w))
        if bias is not None:
            out = out + bias
        if lor is not None:
            out = out + _lora_delta(
                h, lor[target]["A"], lor[target]["B"], lora_idx
            )
        return out

    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q, k, v = split_heads(
        proj(h, lp["wq"], "wq", lp.get("bq")),
        proj(h, lp["wk"], "wk", lp.get("bk")),
        proj(h, lp["wv"], "wv", lp.get("bv")),
        H, KVH, D,
    )
    q = apply_rope(q, pos_k, inv_freq, msc)
    k = apply_rope(k, pos_k, inv_freq, msc)
    kp = kp.at[page_ids, offsets].set(k.astype(kp.dtype))
    vp = vp.at[page_ids, offsets].set(v.astype(vp.dtype))
    attn = paged_verify_attention(q, kp, vp, block_tables, positions)
    x = x + proj(attn.reshape(B, K, H * D), lp["wo"], "wo")
    h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
    x = x + _mlp(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, (kp, vp)


def decode_verify_paged(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, K] — last emitted token + K-1 proposals
    positions: jnp.ndarray,  # [B] absolute position of tokens[:, 0]
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    lora: dict | None = None,
    lora_idx: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SPECULATIVE VERIFY: one forward over a K-token window per slot
    against the paged cache. Writes the window's KV through the block
    tables (rejected tail positions hold garbage that the per-slot
    position pointer masks and later steps overwrite) and returns logits
    for EVERY window position [B, K, V] so the engine can accept the
    longest matching proposal prefix (engine.py speculative mode).
    Attention dispatches to the multi-query paged Pallas kernel on TPU,
    gather reference elsewhere (ops/paged_attention.py)."""
    B, K = tokens.shape
    page_size = k_pages.shape[2]
    inv_freq = jnp.asarray(
        rope_frequencies(
            cfg.head_size, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        )
    )
    msc = rope_attention_scaling(cfg.rope_scaling)
    pos_k = positions[:, None] + jnp.arange(K)[None, :]  # [B, K]
    x = params["embed"][tokens]  # [B, K, E]
    page_ids, offsets = _verify_page_coords(
        block_tables, positions, K, page_size
    )

    def layer(carry, scanned):
        return _paged_verify_layer(
            carry, scanned, cfg, inv_freq, msc, pos_k, page_ids, offsets,
            block_tables, positions, lora_idx,
        )

    xs = _scan_xs(params, lora)
    xs["kp"] = k_pages
    xs["vp"] = v_pages
    x, (k_pages, v_pages) = jax.lax.scan(layer, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "bke,ve->bkv", x, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    return logits, k_pages, v_pages


def decode_verify_paged_pp(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, K] — last emitted token + K-1 proposals
    positions: jnp.ndarray,  # [B]
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D], layer axis sharded on pp
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    lora: dict | None = None,
    lora_idx: jnp.ndarray | None = None,
    *,
    mesh,
    microbatches: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Speculative verify under pipeline parallelism: the same GPipe
    schedule as decode_step_paged_pp (stage-local layers + stage-local KV,
    [mb, K, E] activations hopping via ppermute), with the per-layer math
    shared through _paged_verify_layer — so a pp engine speculates with
    the identical accept/reject semantics the single-mesh engine has.
    Off-schedule ticks recompute clamped duplicate microbatches; their
    cache writes sink into reserved scratch page 0.

    Reference analog: none (the reference has neither PP nor speculation —
    vLLM flags ride Model.spec.args, api/k8s/v1/model_types.go:85-90);
    SURVEY §2's TPU-equivalents list makes both this repo's obligation.
    """
    from jax.sharding import PartitionSpec as P

    from kubeai_tpu.parallel.mesh import AXIS_PIPELINE

    B, K = tokens.shape
    M = microbatches
    if M < 1 or B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    n_stages = mesh.shape[AXIS_PIPELINE]
    NL = k_pages.shape[0]
    if NL % n_stages:
        raise ValueError(f"{NL} layers not divisible by {n_stages} pp stages")
    page_size = k_pages.shape[2]
    inv_freq = jnp.asarray(
        rope_frequencies(
            cfg.head_size, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        )
    )
    msc = rope_attention_scaling(cfg.rope_scaling)
    pos_k = positions[:, None] + jnp.arange(K)[None, :]  # [B, K]
    page_ids, offsets = _verify_page_coords(
        block_tables, positions, K, page_size
    )
    if lora_idx is None:
        lora_idx = jnp.zeros((B,), jnp.int32)

    mb = B // M

    def mbt(a):
        return a.reshape(M, mb, *a.shape[1:])

    x_mb = mbt(params["embed"][tokens])  # [M, mb, K, E]
    pos_mb, posk_mb = mbt(positions), mbt(pos_k)
    pid_mb, off_mb = mbt(page_ids), mbt(offsets)
    bt_mb, lidx_mb = mbt(block_tables), mbt(lora_idx)

    xs = _scan_xs(params, lora)
    xs_specs = jax.tree_util.tree_map(lambda _: P(AXIS_PIPELINE), xs)
    rep = P()

    # Same partial-manual vs fully-manual split as decode_step_paged_pp
    # (and the same XLA landmines documented there).
    tp_size = mesh.shape.get("tp", 1)
    manual_kw = (
        {"axis_names": {AXIS_PIPELINE}, "check_vma": True}
        if tp_size > 1 else {"check_vma": False}
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            xs_specs, P(AXIS_PIPELINE), P(AXIS_PIPELINE),
            rep, rep, rep, rep, rep, rep, rep,
        ),
        out_specs=(
            P(AXIS_PIPELINE), P(AXIS_PIPELINE), P(AXIS_PIPELINE),
        ),
        **manual_kw,
    )
    def run(xs, kp, vp, x_mb, pos_mb, posk_mb, pid_mb, off_mb, bt_mb, lidx_mb):
        stage = jax.lax.axis_index(AXIS_PIPELINE)
        last = n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def local_layers(h, kp, vp, pos, posk, pid, off, bt, lidx):
            def layer(carry, scanned):
                return _paged_verify_layer(
                    carry, scanned, cfg, inv_freq, msc, posk, pid, off,
                    bt, pos, lidx,
                )

            xs_l = dict(xs)
            xs_l["kp"] = kp
            xs_l["vp"] = vp
            y, (kp, vp) = jax.lax.scan(layer, h, xs_l)
            return y, kp, vp

        ticks = M + n_stages - 1

        def tick(carry, t):
            buf, kp, vp, out = carry
            idx = jnp.clip(t - stage, 0, M - 1)
            active = (t - stage >= 0) & (t - stage < M)
            h = jnp.where(stage == 0, x_mb[jnp.clip(t, 0, M - 1)], buf)
            pid = jnp.where(active, pid_mb[idx], 0)
            off = jnp.where(active, off_mb[idx], 0)
            y, kp, vp = local_layers(
                h, kp, vp, pos_mb[idx], posk_mb[idx], pid, off,
                bt_mb[idx], lidx_mb[idx],
            )
            mb_out = t - last
            store = (stage == last) & (mb_out >= 0)
            out = jnp.where(
                store, out.at[jnp.clip(mb_out, 0, M - 1)].set(y), out
            )
            buf = jax.lax.ppermute(y, AXIS_PIPELINE, fwd)
            return (buf, kp, vp, out), None

        zero = jax.lax.pcast(
            jnp.zeros_like(x_mb[0]), AXIS_PIPELINE, to="varying"
        )
        out0 = jax.lax.pcast(
            jnp.zeros_like(x_mb), AXIS_PIPELINE, to="varying"
        )
        (_, kp, vp, out), _ = jax.lax.scan(
            tick, (zero, kp, vp, out0), jnp.arange(ticks)
        )
        return out[None], kp, vp  # [1, M, mb, K, E] per stage

    hidden, k_pages, v_pages = run(
        xs, k_pages, v_pages, x_mb, pos_mb, posk_mb, pid_mb, off_mb,
        bt_mb, lidx_mb,
    )
    # hidden is [n_stages, M, mb, K, E]; only the LAST stage stored real
    # outputs.
    x = hidden[-1].reshape(B, K, -1)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum(
        "bke,ve->bkv", x, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    return logits, k_pages, v_pages


def _trunk(params: dict, cfg: LlamaConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """Transformer trunk: [B, S] tokens -> [B, S, E] final hidden states."""
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(
        lambda h, lp: (trunk_layer(h, lp, cfg), None), x, params["layers"]
    )
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)


def hidden_states(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, S] right-padded
    lengths: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """Mean-pooled, L2-normalized embeddings [B, E] — the TextEmbedding
    feature (the reference delegates embeddings to Infinity Pods,
    reference: internal/modelcontroller/engine_infinity.go; here any causal
    model doubles as an embedder)."""
    x = _trunk(params, cfg, tokens)  # [B, S, E]
    S = tokens.shape[1]
    mask = (jnp.arange(S)[None, :] < lengths[:, None]).astype(jnp.float32)
    summed = jnp.einsum("bse,bs->be", x.astype(jnp.float32), mask)
    pooled = summed / jnp.maximum(lengths[:, None].astype(jnp.float32), 1.0)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
    )


def prefill_chunk(
    params: dict,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [1, C] one chunk (right-padded on the last chunk)
    start: jnp.ndarray,  # scalar int32: absolute position of tokens[:, 0]
    length: jnp.ndarray,  # scalar int32: true total prompt length
    k_slot: jnp.ndarray,  # [NL, L, KVH, D] one slot's dense buffer
    v_slot: jnp.ndarray,
    want_logits: bool = False,
    lora: dict | None = None,
    lora_idx: jnp.ndarray | None = None,
):
    """One chunk of incremental prefill against one slot's dense
    [NL, L, KVH, D] buffer (the engine's staging buffer, or the draft
    model's cache row).

    The same compiled graph serves every chunk of every prompt length
    (static [1, C] shape) — unlike whole-prompt prefill, which compiles per
    power-of-two bucket — and activation memory stays O(C * L) instead of
    O(S^2). Stale cache contents beyond the causal frontier are masked by
    position. Returns (logits_or_None, k_slot, v_slot).
    """
    B, C = tokens.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    inv_freq = jnp.asarray(rope_frequencies(
            D, cfg.rope_theta, cfg.rope_scaling,
            cfg.max_position_embeddings,
        ))
    msc = rope_attention_scaling(cfg.rope_scaling)
    positions = start + jnp.arange(C)[None, :]
    x = params["embed"][tokens]

    def layer(x, scanned):
        lp = scanned["p"]
        lor = scanned.get("l")
        kc, vc = scanned["kc"], scanned["vc"]  # [L, KVH, D]

        def proj(h, w, target, bias=None):
            out = jnp.einsum("bse,eh->bsh", h, _w(w))
            if bias is not None:
                out = out + bias
            if lor is not None:
                out = out + _lora_delta(
                    h, lor[target]["A"], lor[target]["B"], lora_idx
                )
            return out

        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q = proj(h, lp["wq"], "wq", lp.get("bq")).reshape(B, C, H, D)
        k = proj(h, lp["wk"], "wk", lp.get("bk")).reshape(B, C, KVH, D)
        v = proj(h, lp["wv"], "wv", lp.get("bv")).reshape(B, C, KVH, D)
        q = apply_rope(q, positions, inv_freq, msc)
        k = apply_rope(k, positions, inv_freq, msc)
        kc = jax.lax.dynamic_update_slice(
            kc, k[0].astype(kc.dtype), (start, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            vc, v[0].astype(vc.dtype), (start, 0, 0)
        )
        from kubeai_tpu.ops.attention import chunked_prefill_attention

        attn = chunked_prefill_attention(
            q, kc[None], vc[None], start[None]
        )
        x = x + proj(attn.reshape(B, C, H * D), lp["wo"], "wo")
        h2 = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        x = x + _mlp(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, {"kc": kc, "vc": vc}

    xs = _scan_xs(params, lora)
    xs["kc"] = k_slot
    xs["vc"] = v_slot
    x, caches = jax.lax.scan(layer, x, xs)
    k_slot, v_slot = caches["kc"], caches["vc"]
    if not want_logits:
        return None, k_slot, v_slot
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(length - 1 - start, 0, C - 1)
    last = jax.lax.dynamic_slice(x, (0, idx, 0), (1, 1, x.shape[-1]))[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    return logits, k_slot, v_slot
