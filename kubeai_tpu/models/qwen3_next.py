"""Qwen3-Next (Qwen3NextForCausalLM): a hybrid of Gated DeltaNet layers and
gated softmax attention, every layer followed by a mixture of experts with a
shared expert.

Layer `i` is full attention where `(i + 1) % full_attention_interval == 0`,
else Gated DeltaNet; `x = x + mixer(norm0(x)); x = x + moe(norm0(x))`, with
`norm0(x; w) = rms_norm(x) * (1 + w)` (the weight is zero-centred). The
layers are run as ONE scan over periods of `full_attention_interval` layers
(three DeltaNet, one attention), so the compiled program does not grow with
depth; perf/reference/qwen3_next.py has every equation.

**Two kinds of state.** An attention layer owns pages (`num_kv_heads` of
`head_dim`), a DeltaNet layer owns no keys and values but, a slot, a
recurrent state `[value heads, 128, 128]` float32 and the last
`linear_conv_kernel_dim - 1` inputs of its causal convolution. The family
says so through `ModelFamily.recurrent_state`; the engine then stacks its
page pool over the attention layers only and keeps the two state pools
beside it (docs/concepts/hybrid-state.md). `prefill(..., state=True)`
returns the rows an admission writes (the state after the prompt's true
length, pad positions neither decaying nor writing); `decode_step_paged(...,
state=pools)` updates the pools in place (`ops/gated_delta.py`).

**An expert share.** `num_experts` is what this chip HOLDS,
`router_experts` what the router scores (512) and `expert_share_index` which
share this is: global ids `first_expert .. first_expert + num_experts - 1`.
The router scores all, takes `num_experts_per_tok` on a softmax over all,
renormalised over the taken; the rows routed to held experts go through the
grouped product (`ops/experts.py:moe_sparse`), the others add nothing
here, and this partial sum plus the shared expert (which every chip computes
alike) goes on to the next layer. No code stands in for the absent chips.
The hand-over carries all the global ids a row took.

Weights are in the repo's own layout (no checkpoint loader yet: the
published `in_proj_qkvz` / `in_proj_ba` interleave heads, a loader's matter).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from kubeai_tpu.models.registry import ModelFamily, register_model_family
from kubeai_tpu.ops.attention import prefill_attention
from kubeai_tpu.ops.experts import (
    EXPERT_LEAVES,
    at,
    moe_sparse,
    shared_expert,
    stack_routes,
)
from kubeai_tpu.ops.gated_delta import (
    conv_prefill,
    conv_step as _conv_step,
    gdn_chunk_scan,
    gdn_update,
)
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.projections import split_heads
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies
from kubeai_tpu.parallel import sharding as sh

L2_EPS = 1e-6  # of the q / k normalisation inside the delta rule


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    # Experts held here, of `router_experts` that the router scores: share
    # `expert_share_index` of router_experts / num_experts.
    num_experts: int = 512
    router_experts: int = 512
    expert_share_index: int = 0
    num_experts_per_tok: int = 10
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of "
                f"{self.full_attention_interval}"
            )
        if self.router_experts % self.num_experts or not (
            0 <= self.expert_share_index
            < self.router_experts // self.num_experts
        ):
            raise ValueError(
                f"share {self.expert_share_index} of {self.num_experts} "
                f"experts does not lie in a router {self.router_experts} wide"
            )

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def page_layers(self) -> int:
        """Layers that own pages: one a period."""
        return self.periods

    @property
    def state_layers(self) -> int:
        return self.num_layers - self.periods

    @property
    def routed_layers(self) -> int:
        return self.num_layers

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def first_expert(self) -> int:
        return self.expert_share_index * self.num_experts

    @staticmethod
    def from_hf_dict(d: dict) -> "Qwen3NextConfig":
        """config.json of Qwen3-Next. The expert share is three keys of a
        benchmark configuration's file: `num_experts` (held here),
        `router_num_experts` (absent: all are held) and
        `expert_share_index`."""
        if d.get("mlp_only_layers") or d.get("decoder_sparse_step", 1) != 1:
            raise ValueError("qwen3_next: every layer is routed here")
        if not d.get("norm_topk_prob", True):
            raise ValueError("qwen3_next: norm_topk_prob false is not served")
        return Qwen3NextConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d["head_dim"],
            partial_rotary_factor=d.get("partial_rotary_factor", 0.25),
            rope_theta=d.get("rope_theta", 1e7),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            full_attention_interval=d.get("full_attention_interval", 4),
            linear_num_key_heads=d["linear_num_key_heads"],
            linear_num_value_heads=d["linear_num_value_heads"],
            linear_key_head_dim=d["linear_key_head_dim"],
            linear_value_head_dim=d["linear_value_head_dim"],
            linear_conv_kernel_dim=d.get("linear_conv_kernel_dim", 4),
            moe_intermediate_size=d["moe_intermediate_size"],
            shared_expert_intermediate_size=d["shared_expert_intermediate_size"],
            num_experts=d["num_experts"],
            router_experts=d.get("router_num_experts", d["num_experts"]),
            expert_share_index=d.get("expert_share_index", 0),
            num_experts_per_tok=d["num_experts_per_tok"],
            max_position_embeddings=d.get("max_position_embeddings", 262144),
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "Qwen3NextConfig":
        """Two periods; 4 of 16 experts held (share 1 of 4), 3 a token."""
        return Qwen3NextConfig(
            vocab_size=vocab_size, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=32, rope_theta=10000.0,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_experts=4, router_experts=16, expert_share_index=1,
            num_experts_per_tok=3, max_position_embeddings=2048,
        )


def recurrent_state(cfg: Qwen3NextConfig) -> dict:
    """What a slot owns beside its pages (`ModelFamily.recurrent_state`)."""
    return {
        "state_layers": cfg.state_layers,
        "page_layers": cfg.page_layers,
        "pools": {
            "recurrent": (
                (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim),
                jnp.float32,
            ),
            # The last inputs of the convolution, oldest first, flat.
            "conv": (
                ((cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim,), cfg.dtype,
            ),
        },
    }


def param_specs(cfg: Qwen3NextConfig) -> dict:
    def whole(rank):
        return (None,) * rank

    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": {
            "gdn": {
                "input_norm": whole(2), "in_qkvz": whole(3), "in_ba": whole(3),
                "conv_w": whole(3), "A_log": whole(2), "dt_bias": whole(2),
                "norm": whole(2), "out_proj": whole(3),
            },
            "attn": {
                "input_norm": whole(2), "wq": whole(3), "wk": whole(3),
                "wv": whole(3), "wo": whole(3), "q_norm": whole(2),
                "k_norm": whole(2),
            },
            "moe": {
                "post_norm": whole(2), "router": whole(3),
                "shared_gate": whole(3), "shared_up": whole(3),
                "shared_down": whole(3), "shared_router": whole(2),
            },
            "experts": {name: whole(4) for name in EXPERT_LEAVES},
        },
        "final_norm": (sh.EMBED,),
        "lm_head": (sh.VOCAB, sh.EMBED),
    }


def init_params(cfg: Qwen3NextConfig, key: jax.Array | None = None) -> dict:
    """Seeded weights: normal, std 0.02; norm weights at their identity (0
    where zero-centred, 1 for the DeltaNet output norm); `A_log` and
    `dt_bias` drawn so that a step's decay lies in about 0.5 to 0.999 (an
    untrained state that forgets in one step would hide a state carried
    wrongly)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    P, NL, NS = cfg.periods, cfg.num_layers, cfg.state_layers
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    HV, DV = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    M, Ms = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    X, K = cfg.num_experts, cfg.linear_conv_kernel_dim
    ks = iter(jax.random.split(key, 24))

    def rnd(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32) * 0.02).astype(dt)

    return {
        "embed": rnd((V, E)),
        "layers": {
            "gdn": {
                "input_norm": jnp.zeros((NS, E), dt),
                "in_qkvz": rnd((NS, E, cfg.conv_dim + cfg.value_dim)),
                "in_ba": rnd((NS, E, 2 * HV)),
                "conv_w": rnd((NS, K, cfg.conv_dim)),
                "A_log": jax.random.uniform(
                    next(ks), (NS, HV), jnp.float32,
                    math.log(0.002), math.log(0.4)),
                "dt_bias": jax.random.uniform(
                    next(ks), (NS, HV), jnp.float32, -1.0, 1.0),
                "norm": jnp.ones((NS, DV), dt),
                "out_proj": rnd((NS, cfg.value_dim, E)),
            },
            "attn": {
                "input_norm": jnp.zeros((P, E), dt),
                "wq": rnd((P, E, 2 * H * D)),
                "wk": rnd((P, E, KVH * D)),
                "wv": rnd((P, E, KVH * D)),
                "wo": rnd((P, H * D, E)),
                "q_norm": jnp.zeros((P, D), dt),
                "k_norm": jnp.zeros((P, D), dt),
            },
            "moe": {
                "post_norm": jnp.zeros((NL, E), dt),
                "router": rnd((NL, E, cfg.router_experts)),
                "shared_gate": rnd((NL, E, Ms)),
                "shared_up": rnd((NL, E, Ms)),
                "shared_down": rnd((NL, Ms, E)),
                "shared_router": rnd((NL, E)),
            },
            "experts": {
                "w_gate": rnd((NL, X, E, M)),
                "w_up": rnd((NL, X, E, M)),
                "w_down": rnd((NL, X, M, E)),
            },
        },
        "final_norm": jnp.zeros((E,), dt),
        "lm_head": rnd((V, E)),
    }


def _norm0(x, w, eps):
    """RMSNorm under a zero-centred weight."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _partial_rope(x, positions, cfg):
    """Rotary (rotate-half) on the first `partial_rotary_factor` of a head's
    dimensions; the others pass."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    inv_freq = jnp.asarray(rope_frequencies(rot, cfg.rope_theta))
    return jnp.concatenate(
        [apply_rope(x[..., :rot], positions, inv_freq), x[..., rot:]], axis=-1
    )


def _moe_parts(x, mp, experts, layer, cfg):
    """Rows x [N, E] (already normed) through the expert layer of `layer`:
    (this share's part of the routed sum, the shared expert's gated output,
    both float32, and topi [N, k]: the global ids taken, best first)."""
    with jax.named_scope("moe_router"):
        logits = jnp.einsum(
            "ne,ex->nx", x, mp["router"], preferred_element_type=jnp.float32
        )
        topv, topi = jax.lax.top_k(
            jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok
        )
        probs = topv / jnp.sum(topv, axis=-1, keepdims=True)
    with jax.named_scope("moe_shared"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "ne,e->n", x, mp["shared_router"],
            preferred_element_type=jnp.float32,
        ))
        shared = shared_expert(x, mp) * gate[:, None]
    routed = moe_sparse(
        x, experts, layer, topi, probs, first=cfg.first_expert
    )
    return routed.astype(jnp.float32), shared, topi


@jax.named_scope("moe_ffn")
def _moe(x, mp, experts, layer, cfg):
    """(y [N, E]: the routed part plus the shared expert, topi [N, k])."""
    routed, shared, topi = _moe_parts(x, mp, experts, layer, cfg)
    return (routed + shared).astype(x.dtype), topi


def _gdn_project(h, lp, cfg):
    """h [..., E] -> u [..., conv_dim] (q, k, v before the convolution), z
    [..., value_dim], beta and g [..., HV] float32."""
    HV = cfg.linear_num_value_heads
    with jax.named_scope("gdn_proj"):
        qkvz = h @ lp["in_qkvz"]
        ba = (h @ lp["in_ba"]).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :HV])
        g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[..., HV:] + lp["dt_bias"])
    return qkvz[..., : cfg.conv_dim], qkvz[..., cfg.conv_dim:], beta, g


def _gdn_heads(y, cfg):
    """The convolved channels y [..., conv_dim] float32 -> q, k [..., HV,
    DK] (normalised, q scaled, each key head repeated for its value heads)
    and v [..., HV, DV]."""
    HK, HV = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    DK, DV = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    lead = y.shape[:-1]
    q = y[..., : cfg.key_dim].reshape(*lead, HK, DK)
    k = y[..., cfg.key_dim : 2 * cfg.key_dim].reshape(*lead, HK, DK)
    v = y[..., 2 * cfg.key_dim :].reshape(*lead, HV, DV)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q = jnp.repeat(l2(q) * DK ** -0.5, HV // HK, axis=-2)
    return q, jnp.repeat(l2(k), HV // HK, axis=-2), v


def _gdn_out(o, z, lp, cfg):
    """o [..., HV, DV] float32, z [..., value_dim] -> [..., E]."""
    with jax.named_scope("gdn_out"):
        lead = o.shape[:-2]
        z = z.reshape(*lead, cfg.linear_num_value_heads, -1).astype(jnp.float32)
        o = rms_norm(o, lp["norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
        return o.reshape(*lead, cfg.value_dim).astype(cfg.dtype) @ lp["out_proj"]


def _attn_project(h, lp, cfg, positions):
    """h [B, S, E] -> q [B, S, H, D], k, v [B, S, KVH, D], gate [B, S, H*D]."""
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qg = jnp.einsum("bse,eh->bsh", h, lp["wq"])
    q, k, v = split_heads(
        qg[..., : H * D],
        jnp.einsum("bse,eh->bsh", h, lp["wk"]),
        jnp.einsum("bse,eh->bsh", h, lp["wv"]),
        H, KVH, D,
    )
    q = _norm0(q, lp["q_norm"], cfg.rms_norm_eps)
    k = _norm0(k, lp["k_norm"], cfg.rms_norm_eps)
    return (
        _partial_rope(q, positions, cfg), _partial_rope(k, positions, cfg),
        v, qg[..., H * D :],
    )


def _period_xs(params, cfg):
    """What the scan over periods slices a period at a time: the attention
    layer's weights and the period's number. The DeltaNet layers' and the
    mixtures' weights stay whole outside it and are read at their layer's
    number (`ops.experts.at`): sliced a period at a time, the three DeltaNet
    layers of a period came out of the stack as one copy (150 MB of `in_qkvz`
    a period a decode step in the compiled chunk), where a layer read at its
    own number is read by the product that uses it."""
    return {
        "attn": params["layers"]["attn"],
        "pi": jnp.arange(cfg.periods, dtype=jnp.int32),
    }


def prefill(params, cfg, tokens, lengths, lora=None, lora_idx=None, *,
            routes=False, state=False):
    """Whole-prompt prefill of [A, S] prompts. Returns (logits at
    `lengths - 1`, k_all, v_all [page layers, A, S, KVH, D]) and then, with
    `state`, the rows an admission writes into the state pools
    ({"recurrent": [state layers, A, HV, DK, DV], "conv": [state layers, A,
    (K - 1) * conv_dim]}: the state after position `lengths - 1`, the
    convolution's last K - 1 real inputs) and, with `routes`, the expert
    sets [A, S, routed layers, k]."""
    A, S = tokens.shape
    G = cfg.full_attention_interval - 1
    positions = jnp.arange(S)[None, :].repeat(A, axis=0)
    real = positions < lengths[:, None]  # [A, S]
    layers = params["layers"]
    experts = layers["experts"]
    x = params["embed"][tokens]

    def moe(x, layer):
        mp = at(layers["moe"], layer)
        h = _norm0(x, mp["post_norm"], cfg.rms_norm_eps)
        y, topi = _moe(h.reshape(A * S, -1), mp, experts, layer, cfg)
        return x + y.reshape(A, S, -1), topi.reshape(A, S, -1)

    def gdn(x, lp):
        h = _norm0(x, lp["input_norm"], cfg.rms_norm_eps)
        u, z, beta, g = _gdn_project(h, lp, cfg)
        with jax.named_scope("gdn_conv"):
            y, tail = conv_prefill(u, lp["conv_w"], lengths)
            q, k, v = _gdn_heads(jax.nn.silu(y), cfg)
        with jax.named_scope("gdn_scan"):
            # A pad position neither decays nor writes.
            o, s = gdn_chunk_scan(
                q, k, v, jnp.where(real[..., None], g, 0.0),
                jnp.where(real[..., None], beta, 0.0),
            )
        return x + _gdn_out(o, z, lp, cfg), s, tail

    def attention(x, lp):
        with jax.named_scope("gated_attention"):
            h = _norm0(x, lp["input_norm"], cfg.rms_norm_eps)
            q, k, v, gate = _attn_project(h, lp, cfg, positions)
            attn = prefill_attention(q, k, v).reshape(A, S, -1)
            attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)
            return x + jnp.einsum("bsh,he->bse", attn, lp["wo"]), k, v

    def period(x, xs):
        first = xs["pi"] * (G + 1)
        rec, conv, topis = [], [], []
        for j in range(G):
            x, s, tail = gdn(x, at(layers["gdn"], xs["pi"] * G + j))
            x, topi = moe(x, first + j)
            rec.append(s), conv.append(tail), topis.append(topi)
        x, k, v = attention(x, xs["attn"])
        x, topi = moe(x, first + G)
        topis.append(topi)
        return x, (k, v, jnp.stack(rec), jnp.stack(conv), jnp.stack(topis))

    x, (k_all, v_all, rec, conv, topi_all) = jax.lax.scan(
        period, x, _period_xs(params, cfg)
    )
    x = _norm0(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    out = [logits, k_all, v_all]
    if state:
        out.append({
            "recurrent": rec.reshape(cfg.state_layers, *rec.shape[2:]),
            "conv": conv.reshape(cfg.state_layers, *conv.shape[2:]),
        })
    if routes:
        # [periods, layers a period, *rows, k] -> layers first.
        topi_all = topi_all.reshape(cfg.num_layers, *topi_all.shape[2:])
        out.append(stack_routes(topi_all, cfg.router_experts))
    return tuple(out)


def decode_step_paged(params, cfg, tokens, positions, k_pages, v_pages,
                      block_tables, lora=None, lora_idx=None, *,
                      attn_kernel=None, routes=False, state=None):
    """One token a slot. The page pool (stacked over the attention layers)
    is read in place by the layer-indexed kernel and written by one batched
    scatter after the scan; the state pools `state` ({"recurrent": [state
    layers, B, HV, DK, DV] float32, "conv": [state layers, B, (K - 1) *
    conv_dim]}) ride the scan and are updated in place, a layer at a time.
    Returns (logits, k_pages, v_pages, state) and, with `routes`, the B
    rows' expert sets [B, routed layers, k]."""
    from kubeai_tpu.ops.paged_attention import (
        batched_scatter_sequence,
        paged_decode_attention_fused,
        token_page_coords,
    )

    if attn_kernel not in (None, "", "fused"):
        raise ValueError(f"qwen3_next decodes with the fused layout, not {attn_kernel!r}")
    if state is None:
        raise ValueError("qwen3_next decodes against its state pools")
    B = tokens.shape[0]
    G = cfg.full_attention_interval - 1
    page_size = k_pages.shape[2]
    pos1 = positions[:, None]
    page_ids, offsets = token_page_coords(block_tables, positions, page_size)
    layers = params["layers"]
    experts = layers["experts"]
    x = params["embed"][tokens]

    def moe(x, layer):
        mp = at(layers["moe"], layer)
        h = _norm0(x, mp["post_norm"], cfg.rms_norm_eps)
        y, topi = _moe(h, mp, experts, layer, cfg)
        return x + y, topi

    def gdn(x, rec, conv, li):
        lp = at(layers["gdn"], li)
        h = _norm0(x, lp["input_norm"], cfg.rms_norm_eps)
        u, z, beta, g = _gdn_project(h, lp, cfg)
        with jax.named_scope("gdn_conv"):
            y, conv = _conv_step(conv, li, u, lp["conv_w"])
            q, k, v = _gdn_heads(jax.nn.silu(y), cfg)
        with jax.named_scope("gdn_update"):
            rec, o = gdn_update(rec, li, q, k, v, jnp.exp(g), beta)
        return x + _gdn_out(o, z, lp, cfg), rec, conv

    def attention(x, lp, li):
        with jax.named_scope("gated_attention"):
            h = _norm0(x, lp["input_norm"], cfg.rms_norm_eps)
            q, k, v, gate = _attn_project(h[:, None], lp, cfg, pos1)
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            with jax.named_scope("paged_attention"):
                attn = paged_decode_attention_fused(
                    q, k_pages, v_pages, k, v, block_tables, positions, li
                ).reshape(B, -1)
            attn = attn * jax.nn.sigmoid(
                gate[:, 0].astype(jnp.float32)).astype(attn.dtype)
            return x + jnp.einsum("bh,he->be", attn, lp["wo"]), k, v

    def period(carry, xs):
        x, rec, conv = carry
        first = xs["pi"] * (G + 1)
        topis = []
        for j in range(G):
            x, rec, conv = gdn(x, rec, conv, xs["pi"] * G + j)
            x, topi = moe(x, first + j)
            topis.append(topi)
        x, k, v = attention(x, xs["attn"], xs["pi"])
        x, topi = moe(x, first + G)
        topis.append(topi)
        return (x, rec, conv), (k, v, jnp.stack(topis))

    (x, rec, conv), (k_all, v_all, topi_all) = jax.lax.scan(
        period, (x, state["recurrent"], state["conv"]), _period_xs(params, cfg)
    )
    with jax.named_scope("kv_page_write"):
        k_pages, v_pages = batched_scatter_sequence(
            k_pages, v_pages, k_all[:, :, None], v_all[:, :, None],
            page_ids[:, None], offsets[:, None],
        )
    x = _norm0(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "be,ve->bv", x, params["lm_head"],
            preferred_element_type=jnp.float32,
        )
    state = {"recurrent": rec, "conv": conv}
    if routes:
        topi_all = topi_all.reshape(cfg.num_layers, *topi_all.shape[2:])
        return logits, k_pages, v_pages, state, stack_routes(
            topi_all, cfg.router_experts)
    return logits, k_pages, v_pages, state


register_model_family(
    ModelFamily(
        "qwen3_next",
        config_from_hf=Qwen3NextConfig.from_hf_dict,
        tiny_config=Qwen3NextConfig.tiny,
        init_params=init_params,
        param_specs=param_specs,
        prefill=prefill,
        decode_step=None,  # the page pool and the state pools are the cache
        decode_step_paged=decode_step_paged,
        hf_architectures=("Qwen3NextForCausalLM",),
        route_dims=lambda cfg: (
            cfg.router_experts, cfg.num_experts_per_tok, cfg.routed_layers
        ),
        held_experts=lambda cfg: (
            cfg.first_expert, cfg.first_expert + cfg.num_experts
        ),
        recurrent_state=recurrent_state,
    )
)
