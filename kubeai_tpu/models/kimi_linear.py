"""Kimi Linear (KimiLinearForCausalLM, `model_type` `kimi_linear`): Kimi Delta
Attention (KDA) layers and latent attention (MLA, no rotary) layers, three to
one, behind a leading dense layer a mixture of experts whose router scores by
a sigmoid and selects on the score plus a bias.

Block `l`: `x = x + mixer(rms(x)); x = x + ffn(rms(x))`. Layer `l` (1-based) is
latent attention where `linear_attn_config.full_attn_layers` names it, else
KDA. perf/reference/kimi_linear.py has every equation and what is assumed.

**KDA**: the gated delta rule (`ops/gated_delta.py`) under a decay a KEY
CHANNEL: q, k, v through a short causal convolution and a SiLU, q and k
normalised a head, `g = -exp(A_log) * softplus(W_fb W_fa x + dt_bias)` a vector
of `head_dim` a head, `beta = sigmoid(W_b x)` one a head, the output
RMS-normed a head and gated by `sigmoid(W_gb W_ga x)`. A slot owns, a KDA
layer, a recurrent state `[heads, 128, 128]` float32 and the convolution's
last inputs (`ModelFamily.recurrent_state`).

**MLA**: a token leaves ONE row in the cache, `[rms(c), kpe]` with `c` the
compressed key-value (`kv_lora_rank`) and `kpe` the key part every head
shares (`qk_rope_head_dim`; `mla_use_nope`: nothing is rotated), padded to
whole lanes. The family says so (`ModelFamily.latent_pages`); the engine then
holds ONE pool over the latent layers, `[latent layers, pages, page, row]`, in
`cache.k_pages`, no second one (`cache.v_pages` is None), beside the state
pools (docs/concepts/latent-cache.md). Prefill attends in the expanded form
(keys of 192, values of 128); decode in the absorbed form against the pool
(`ops/latent_attention.py`): the two agree, `tests/unit/test_kimi_linear.py`.

The layers are run as ONE scan over periods of three KDA layers and one MLA
layer; the one slot that may be dense (period 0's first) is a conditional on
the period's number (`ops/experts.py:ffn_behind_dense`, which the family
shares with `models/exaone_moe.py`, as it shares the convolution and the
state update with `models/qwen3_next.py` through `ops/gated_delta.py`).

**An expert share**, as there: `num_experts` is what this chip HOLDS,
`router_experts` what the router scores, `expert_share_index` which share
this is. Weights are in the repo's own layout (no checkpoint loader yet).
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
    ffn_behind_dense,
    stack_routes,
)
from kubeai_tpu.ops.gated_delta import (
    conv_prefill,
    conv_step,
    gdn_update,
    kda_chunk_scan,
)
from kubeai_tpu.ops.latent_attention import (
    latent_decode_attention,
    latent_row_width,
    write_latent_rows,
)
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.parallel import sharding as sh

L2_EPS = 1e-6  # of the q / k normalisation inside the delta rule


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_layers: int = 27
    # Whole periods of `period - 1` KDA layers and one MLA layer, in that
    # order: layer `l` (0-based) attends where `(l + 1) % period == 0`.
    period: int = 4
    num_heads: int = 32  # of both mixers
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    first_k_dense: int = 1
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    num_experts: int = 256  # held here
    router_experts: int = 256
    expert_share_index: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_layers % self.period:
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of "
                f"{self.period - 1} KDA layers and one MLA layer"
            )
        if self.first_k_dense > self.period:
            raise ValueError("the leading dense layers lie in the first period")
        if self.router_experts % self.num_experts or not (
            0 <= self.expert_share_index
            < self.router_experts // self.num_experts
        ):
            raise ValueError(
                f"share {self.expert_share_index} of {self.num_experts} "
                f"experts does not lie in a router {self.router_experts} wide"
            )

    # What the engine asks of any configuration: the latent layers keep one
    # shared row a token, no heads of keys and values.
    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def head_size(self) -> int:
        return self.latent_row

    @property
    def latent_row(self) -> int:
        """A token's row in the latent pool: 512 + 64 in 640."""
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def periods(self) -> int:
        return self.num_layers // self.period

    @property
    def page_layers(self) -> int:
        return self.periods

    @property
    def state_layers(self) -> int:
        return self.num_layers - self.periods

    @property
    def routed_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def kda_dim(self) -> int:
        """q, k and v of a KDA layer, each."""
        return self.num_heads * self.kda_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        return self.moe_intermediate_size * self.num_shared_experts

    @property
    def first_expert(self) -> int:
        return self.expert_share_index * self.num_experts

    @staticmethod
    def from_hf_dict(d: dict) -> "KimiLinearConfig":
        """config.json of Kimi Linear. The expert share is three keys of a
        benchmark configuration's file: `num_experts` (held here),
        `router_num_experts` (absent: all are held) and `expert_share_index`."""
        lin = d["linear_attn_config"]
        layers = d["num_hidden_layers"]
        full = [l for l in lin["full_attn_layers"] if l <= layers]  # 1-based
        period = full[0]
        if full != list(range(period, layers + 1, period)) or layers % period:
            raise ValueError(
                f"kimi_linear: {layers} layers with latent attention at {full} "
                f"are no whole periods of {period - 1} KDA layers and one MLA "
                "layer (the published 27 end in a period of 2 + 1, a tail the "
                "scan over periods does not run yet)"
            )
        for key, want in (
            ("q_lora_rank", None), ("mla_use_nope", True),
            ("moe_router_activation_func", "sigmoid"), ("moe_renormalize", True),
            ("num_expert_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
        ):
            if d.get(key, want) != want:
                raise ValueError(f"kimi_linear: {key}={d[key]!r} is not served")
        if lin["num_heads"] != d["num_attention_heads"]:
            raise ValueError("kimi_linear: the two mixers have as many heads")
        return KimiLinearConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            num_layers=layers,
            period=period,
            num_heads=lin["num_heads"],
            kda_head_dim=lin["head_dim"],
            conv_kernel=lin.get("short_conv_kernel_size", 4),
            kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=d["qk_nope_head_dim"],
            qk_rope_head_dim=d["qk_rope_head_dim"],
            v_head_dim=d["v_head_dim"],
            intermediate_size=d["intermediate_size"],
            first_k_dense=d.get("first_k_dense_replace", 1),
            moe_intermediate_size=d["moe_intermediate_size"],
            num_shared_experts=d.get("num_shared_experts", 1),
            num_experts=d["num_experts"],
            router_experts=d.get("router_num_experts", d["num_experts"]),
            expert_share_index=d.get("expert_share_index", 0),
            num_experts_per_tok=d["num_experts_per_token"],
            routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("model_max_length", 1048576),
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "KimiLinearConfig":
        """Two periods behind a dense layer; 4 of 16 experts held (share 1 of
        4), 3 a token; a latent row of 32 + 16 in 128."""
        return KimiLinearConfig(
            vocab_size=vocab_size, hidden_size=64, num_layers=8, num_heads=4,
            kda_head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, num_experts=4, router_experts=16,
            expert_share_index=1, num_experts_per_tok=3,
            routed_scaling_factor=2.5, max_position_embeddings=2048,
        )


def recurrent_state(cfg: KimiLinearConfig) -> dict:
    """What a slot owns beside its pages (`ModelFamily.recurrent_state`)."""
    return {
        "state_layers": cfg.state_layers,
        "page_layers": cfg.page_layers,
        "pools": {
            "recurrent": (
                (cfg.num_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32,
            ),
            # The last inputs of the convolution (q, k and v side by side),
            # oldest first, flat.
            "conv": (((cfg.conv_kernel - 1) * 3 * cfg.kda_dim,), cfg.dtype),
        },
    }


def latent_pages(cfg: KimiLinearConfig) -> dict:
    """What a token leaves in a page layer (`ModelFamily.latent_pages`)."""
    return {"row": (cfg.latent_row,), "dtype": cfg.dtype}


def param_specs(cfg: KimiLinearConfig) -> dict:
    def whole(rank):
        return (None,) * rank

    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": {
            "kda": {
                "input_norm": whole(2), "in_qkv": whole(3), "in_fgb": whole(3),
                "conv_w": whole(3), "f_b": whole(3), "g_b": whole(3),
                "A_log": whole(2), "dt_bias": whole(2), "o_norm": whole(2),
                "wo": whole(3),
            },
            "mla": {
                "input_norm": whole(2), "wq": whole(3), "w_kva": whole(3),
                "kv_norm": whole(2), "w_kb": whole(4), "w_vb": whole(4),
                "wo": whole(3),
            },
            "dense": {
                "post_norm": whole(2), "w_gate": whole(3), "w_up": whole(3),
                "w_down": whole(3),
            },
            "moe": {
                "post_norm": whole(2), "router": whole(3),
                "router_bias": whole(2), "shared_gate": whole(3),
                "shared_up": whole(3), "shared_down": whole(3),
            },
            "experts": {name: whole(4) for name in EXPERT_LEAVES},
        },
        "final_norm": (sh.EMBED,),
        "lm_head": (sh.VOCAB, sh.EMBED),
    }


def init_params(cfg: KimiLinearConfig, key: jax.Array | None = None) -> dict:
    """Seeded weights: normal, std 0.02; norm weights 1; `A_log` and `dt_bias`
    drawn so that the channels of a head forget apart, some within a few
    positions and some barely (an untrained state that forgets everything in
    one step would hide a state carried wrongly, one that forgets nothing a
    gate applied wrongly); the selection bias uniform in +-0.05."""
    if key is None:
        key = jax.random.PRNGKey(0)
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    P, NS, NR = cfg.periods, cfg.state_layers, cfg.routed_layers
    # The two low-rank gates (W_fa, W_ga) are as wide as a head.
    H, HD, R = cfg.num_heads, cfg.kda_dim, cfg.kda_head_dim
    M, Ms, X = cfg.moe_intermediate_size, cfg.shared_intermediate_size, cfg.num_experts
    ks = iter(jax.random.split(key, 32))

    def rnd(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32) * 0.02).astype(dt)

    return {
        "embed": rnd((V, E)),
        "layers": {
            "kda": {
                "input_norm": jnp.ones((NS, E), dt),
                "in_qkv": rnd((NS, E, 3 * HD)),
                "in_fgb": rnd((NS, E, 2 * R + H)),
                "conv_w": rnd((NS, cfg.conv_kernel, 3 * HD)),
                "f_b": rnd((NS, R, HD)),
                "g_b": rnd((NS, R, HD)),
                "A_log": jax.random.uniform(
                    next(ks), (NS, H), jnp.float32, math.log(0.05), math.log(1.0)),
                "dt_bias": jax.random.uniform(
                    next(ks), (NS, HD), jnp.float32, -5.0, 2.0),
                "o_norm": jnp.ones((NS, cfg.kda_head_dim), dt),
                "wo": rnd((NS, HD, E)),
            },
            "mla": {
                "input_norm": jnp.ones((P, E), dt),
                "wq": rnd((P, E, H * cfg.qk_head_dim)),
                "w_kva": rnd((P, E, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
                "kv_norm": jnp.ones((P, cfg.kv_lora_rank), dt),
                "w_kb": rnd((P, H, cfg.qk_nope_head_dim, cfg.kv_lora_rank)),
                "w_vb": rnd((P, H, cfg.kv_lora_rank, cfg.v_head_dim)),
                "wo": rnd((P, H * cfg.v_head_dim, E)),
            },
            "dense": {
                "post_norm": jnp.ones((cfg.first_k_dense, E), dt),
                "w_gate": rnd((cfg.first_k_dense, E, cfg.intermediate_size)),
                "w_up": rnd((cfg.first_k_dense, E, cfg.intermediate_size)),
                "w_down": rnd((cfg.first_k_dense, cfg.intermediate_size, E)),
            },
            "moe": {
                "post_norm": jnp.ones((NR, E), dt),
                "router": rnd((NR, E, cfg.router_experts)),
                "router_bias": jax.random.uniform(
                    next(ks), (NR, cfg.router_experts), jnp.float32, -0.05, 0.05),
                "shared_gate": rnd((NR, E, Ms)),
                "shared_up": rnd((NR, E, Ms)),
                "shared_down": rnd((NR, Ms, E)),
            },
            "experts": {
                "w_gate": rnd((NR, X, E, M)),
                "w_up": rnd((NR, X, E, M)),
                "w_down": rnd((NR, X, M, E)),
            },
        },
        "final_norm": jnp.ones((E,), dt),
        "lm_head": rnd((V, E)),
    }


# ---- KDA ---------------------------------------------------------------------


def _kda_project(h, lp, cfg):
    """h [..., E] -> u [..., 3 * kda_dim] (q, k, v before the convolution),
    the log-decay g [..., H, D] (<= 0, a key channel), beta [..., H] and the
    output gate [..., H, D], the last three float32."""
    H, D = cfg.num_heads, cfg.kda_head_dim
    R = D  # the low-rank gates' width
    with jax.named_scope("kda_proj"):
        u = h @ lp["in_qkv"]
        fgb = h @ lp["in_fgb"]
        f = (fgb[..., :R] @ lp["f_b"]).astype(jnp.float32)
        gate = (fgb[..., R:2 * R] @ lp["g_b"]).astype(jnp.float32)
        beta = jax.nn.sigmoid(fgb[..., 2 * R:].astype(jnp.float32))
        lead = f.shape[:-1]
        g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
            f + lp["dt_bias"]).reshape(*lead, H, D)
    return u, g, beta, jax.nn.sigmoid(gate).reshape(*lead, H, D)


def _kda_heads(y, cfg):
    """The convolved channels y [..., 3 * kda_dim] float32, after their SiLU
    -> q, k (normalised a head, q scaled) and v, each [..., H, D]."""
    H, D = cfg.num_heads, cfg.kda_head_dim
    q, k, v = (
        x.reshape(*x.shape[:-1], H, D) for x in jnp.split(jax.nn.silu(y), 3, -1)
    )

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    return l2(q) * D ** -0.5, l2(k), v


def _kda_out(o, gate, lp, cfg):
    """o, gate [..., H, D] float32 -> [..., E]."""
    with jax.named_scope("kda_out"):
        o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps) * gate
        return o.reshape(*o.shape[:-2], cfg.kda_dim).astype(cfg.dtype) @ lp["wo"]


# ---- MLA ---------------------------------------------------------------------


def _mla_project(h, lp, cfg):
    """h [..., E] -> q [..., H, nope + rope] and the token's latent row
    [..., row]: `[rms(c), kpe]`, pad lanes zero."""
    with jax.named_scope("mla_proj"):
        # Held as values before the head reshape (`ops/projections.py` says
        # why): folded into the dot, the reshape had the compiled decode
        # chunk copy the whole stacked `wq` transposed every step (85 MB;
        # AOT, PR 50).
        q, ckv = jax.lax.optimization_barrier((h @ lp["wq"], h @ lp["w_kva"]))
        q = q.reshape(*q.shape[:-1], cfg.num_heads, cfg.qk_head_dim)
        c = rms_norm(ckv[..., : cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_norm_eps)
        row = jnp.concatenate([c, ckv[..., cfg.kv_lora_rank:]], axis=-1)
        pad = cfg.latent_row - row.shape[-1]
        return q, jnp.pad(row, ((0, 0),) * (row.ndim - 1) + ((0, pad),))


# ---- the two forwards ----------------------------------------------------------


def _period_xs(params, cfg):
    """What the scan over periods slices a period at a time: the MLA layer's
    weights and the period's number. The KDA layers' and the FFNs' weights
    stay whole outside it and are read at their layer's number
    (`models/qwen3_next.py:_period_xs` says why)."""
    return {
        "mla": params["layers"]["mla"],
        "pi": jnp.arange(cfg.periods, dtype=jnp.int32),
    }


def _head(params, cfg, x):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum(
            "be,ve->bv", x, params["lm_head"], preferred_element_type=jnp.float32
        )


def _routes_out(topi_all, cfg):
    """[periods, layers a period, *rows, k] -> the hand-over's form; the
    leading dense layers have no row."""
    topi_all = topi_all.reshape(cfg.num_layers, *topi_all.shape[2:])
    return stack_routes(topi_all[cfg.first_k_dense:], cfg.router_experts)


def prefill(params, cfg, tokens, lengths, lora=None, lora_idx=None, *,
            routes=False, state=False):
    """Whole-prompt prefill of [A, S] prompts. Returns (logits at `lengths -
    1`, the latent rows [page layers, A, S, row], None: no second pool) and
    then, with `state`, the rows an admission writes into the state pools
    ({"recurrent": [state layers, A, H, D, D], "conv": [state layers, A, (K -
    1) * 3 * kda_dim]}: the state after position `lengths - 1`, the
    convolution's last K - 1 real inputs) and, with `routes`, the expert sets
    [A, S, routed layers, k]."""
    A, S = tokens.shape
    G = cfg.period - 1
    real = (jnp.arange(S)[None, :] < lengths[:, None])[..., None]  # [A, S, 1]
    layers = params["layers"]
    x = params["embed"][tokens]

    def ffn(x, layer, slot):
        flat, topi = ffn_behind_dense(x.reshape(A * S, -1), layers, layer, slot, cfg)
        return flat.reshape(A, S, -1), topi.reshape(A, S, -1)

    def kda(x, lp):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        u, g, beta, gate = _kda_project(h, lp, cfg)
        with jax.named_scope("kda_conv"):
            y, tail = conv_prefill(u, lp["conv_w"], lengths)
            q, k, v = _kda_heads(y, cfg)
        with jax.named_scope("kda_scan"):
            # A pad position neither decays nor writes.
            o, s = kda_chunk_scan(
                q, k, v, jnp.where(real[..., None], g, 0.0),
                jnp.where(real, beta, 0.0),
            )
        return x + _kda_out(o, gate, lp, cfg), s, tail

    def mla(x, lp):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, row = _mla_project(h, lp, cfg)
        with jax.named_scope("mla_prefill"):
            c, kpe = row[..., : cfg.kv_lora_rank], row[
                ..., cfg.kv_lora_rank : cfg.kv_lora_rank + cfg.qk_rope_head_dim]
            k_nope = jnp.einsum("bsr,hdr->bshd", c, lp["w_kb"])
            v = jnp.einsum("bsr,hrd->bshd", c, lp["w_vb"])
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                kpe[:, :, None], (A, S, cfg.num_heads, kpe.shape[-1]))], axis=-1)
            attn = prefill_attention(q, k, v).reshape(A, S, -1)
            return x + jnp.einsum("bsh,he->bse", attn, lp["wo"]), row

    def period(x, xs):
        first = xs["pi"] * cfg.period
        rec, conv, topis = [], [], []
        for j in range(G):
            x, s, tail = kda(x, at(layers["kda"], xs["pi"] * G + j))
            x, topi = ffn(x, first + j, j)
            rec.append(s), conv.append(tail), topis.append(topi)
        x, row = mla(x, xs["mla"])
        x, topi = ffn(x, first + G, G)
        topis.append(topi)
        return x, (row, jnp.stack(rec), jnp.stack(conv), jnp.stack(topis))

    x, (rows, rec, conv, topi_all) = jax.lax.scan(
        period, x, _period_xs(params, cfg)
    )
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    out = [_head(params, cfg, last), rows, None]
    if state:
        out.append({
            "recurrent": rec.reshape(cfg.state_layers, *rec.shape[2:]),
            "conv": conv.reshape(cfg.state_layers, *conv.shape[2:]),
        })
    if routes:
        out.append(_routes_out(topi_all, cfg))
    return tuple(out)


def decode_step_paged(params, cfg, tokens, positions, k_pages, v_pages,
                      block_tables, lora=None, lora_idx=None, *,
                      attn_kernel=None, routes=False, state=None):
    """One token a slot. `k_pages` is the latent pool [page layers, pages,
    page, row] (`v_pages` is None and comes back None), read in place a layer
    at a time and written by one scatter after the scan; the state pools
    `state` ({"recurrent": [state layers, B, H, D, D] float32, "conv": [state
    layers, B, (K - 1) * 3 * kda_dim]}) ride the scan and are updated in
    place. Returns (logits, k_pages, None, state) and, with `routes`, the B
    rows' expert sets [B, routed layers, k]."""
    from kubeai_tpu.ops.paged_attention import token_page_coords

    if attn_kernel not in (None, "", "fused"):
        raise ValueError(f"kimi_linear decodes with the fused layout, not {attn_kernel!r}")
    if state is None or v_pages is not None:
        raise ValueError(
            "kimi_linear decodes against one latent pool and its state pools")
    G = cfg.period - 1
    page_ids, offsets = token_page_coords(
        block_tables, positions, k_pages.shape[2])
    layers = params["layers"]
    x = params["embed"][tokens]
    scale = cfg.qk_head_dim ** -0.5

    def kda(x, rec, conv, li):
        lp = at(layers["kda"], li)
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        u, g, beta, gate = _kda_project(h, lp, cfg)
        with jax.named_scope("kda_conv"):
            y, conv = conv_step(conv, li, u, lp["conv_w"])
            q, k, v = _kda_heads(y, cfg)
        with jax.named_scope("kda_update"):
            rec, o = gdn_update(rec, li, q, k, v, jnp.exp(g), beta)
        return x + _kda_out(o, gate, lp, cfg), rec, conv

    def mla(x, lp, li):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, row = _mla_project(h, lp, cfg)
        with jax.named_scope("mla_decode"):
            # Absorbed: the query through the key half of W_kvb, so that
            # every head attends the shared rows as they lie; the value half
            # after the weighted sum of their first `rank` numbers.
            q_c = jnp.einsum(
                "bhd,hdr->bhr", q[..., : cfg.qk_nope_head_dim], lp["w_kb"])
            q_row = jnp.concatenate([q_c, q[..., cfg.qk_nope_head_dim:]], axis=-1)
            q_row = jnp.pad(
                q_row, ((0, 0), (0, 0), (0, cfg.latent_row - q_row.shape[-1])))
            o_c = latent_decode_attention(
                q_row, k_pages, row, block_tables, positions, li,
                scale=scale, rank=cfg.kv_lora_rank)
            attn = jnp.einsum("bhr,hrd->bhd", o_c, lp["w_vb"])
        return x + attn.reshape(attn.shape[0], -1) @ lp["wo"], row

    def period(carry, xs):
        x, rec, conv = carry
        first = xs["pi"] * cfg.period
        topis = []
        for j in range(G):
            x, rec, conv = kda(x, rec, conv, xs["pi"] * G + j)
            x, topi = ffn_behind_dense(x, layers, first + j, j, cfg)
            topis.append(topi)
        x, row = mla(x, xs["mla"], xs["pi"])
        x, topi = ffn_behind_dense(x, layers, first + G, G, cfg)
        topis.append(topi)
        return (x, rec, conv), (row, jnp.stack(topis))

    (x, rec, conv), (rows, topi_all) = jax.lax.scan(
        period, (x, state["recurrent"], state["conv"]), _period_xs(params, cfg)
    )
    with jax.named_scope("latent_page_write"):
        k_pages = write_latent_rows(k_pages, rows, page_ids, offsets)
    state = {"recurrent": rec, "conv": conv}
    out = (_head(params, cfg, x), k_pages, None, state)
    return (*out, _routes_out(topi_all, cfg)) if routes else out


register_model_family(
    ModelFamily(
        "kimi_linear",
        config_from_hf=KimiLinearConfig.from_hf_dict,
        tiny_config=KimiLinearConfig.tiny,
        init_params=init_params,
        param_specs=param_specs,
        prefill=prefill,
        decode_step=None,  # the latent pool and the state pools are the cache
        decode_step_paged=decode_step_paged,
        hf_architectures=("KimiLinearForCausalLM",),
        route_dims=lambda cfg: (
            cfg.router_experts, cfg.num_experts_per_tok, cfg.routed_layers
        ),
        held_experts=lambda cfg: (
            cfg.first_expert, cfg.first_expert + cfg.num_experts
        ),
        recurrent_state=recurrent_state,
        latent_pages=latent_pages,
    )
)
