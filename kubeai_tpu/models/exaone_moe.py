"""EXAONE-MoE (ExaoneMoeForCausalLM, `model_type` `exaone_moe`): window and
global attention layers mixed, a leading dense layer, and routed layers whose
router scores by a sigmoid and selects on the score plus a bias.

Block `l`: `x = x + attn(rms(x)); x = x + ffn(rms(x))`. Attention is
grouped-query with q and k RMS-normed a head; a WINDOW layer (`layer_types[l]`
`sliding_attention`) rotates q and k and sees the last `sliding_window`
positions, a GLOBAL layer (`full_attention`) rotates nothing and sees every
earlier position. Layers below `first_k_dense_replace` have a dense SwiGLU;
every other layer routes: `s = sigmoid(x Wr)` over all `router_experts`, the
`num_experts_per_tok` largest of `s + bias` are taken, weighted `scale * s /
sum(s taken)`, and one ungated shared expert is added.
perf/reference/exaone_moe.py has every equation and what is assumed.

The layers are run as ONE scan over periods of the layer pattern (three
window layers and one global), so the compiled program does not grow with
depth; the one slot of a period that may be dense (period 0's) is a
conditional on the period's number.

**Two kinds of KV layer.** The family says which layers keep a window
(`ModelFamily.kv_layers`); the engine then stacks its page pool over the
global layers only and keeps a WINDOW pool beside it in which a slot owns a
fixed ring of `ring_pages(window, page)` pages however long its sequence
(docs/concepts/window-cache.md). `prefill(..., state=True)` returns the
window layers' keys and values beside the global ones (the admission writes
the ring's positions only); `decode_step_paged(..., state=pools)` attends
each layer against its own pool through the stacked kernel, a window layer
through the ring's wrapped block table with the window as the kernel's
argument, and writes the new token's keys and values to ring page
`(position // page) % ring`.

**An expert share**, as in `models/qwen3_next.py`: `num_experts` is what this
chip HOLDS, `router_experts` what the router scores, `expert_share_index`
which share this is. Rows routed to absent experts add nothing here; the
shared expert and the dense layer are computed on every chip alike. The
hand-over carries all the global ids a row took, one row a ROUTED layer (a
leading dense layer has none).

Weights are in the repo's own layout (no checkpoint loader yet).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from kubeai_tpu.models.registry import ModelFamily, register_model_family
from kubeai_tpu.ops.attention import prefill_attention
from kubeai_tpu.ops.experts import (
    EXPERT_LEAVES,
    at,
    ffn_behind_dense as _ffn,
    stack_routes,
)
from kubeai_tpu.ops.norms import rms_norm
from kubeai_tpu.ops.rope import apply_rope, rope_frequencies
from kubeai_tpu.parallel import sharding as sh

GLOBAL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 128
    # The kinds of the layers of ONE period of the pattern, in order.
    period_types: tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL)
    first_k_dense: int = 1
    # Experts held here, of `router_experts` that the router scores: share
    # `expert_share_index` of router_experts / num_experts.
    num_experts: int = 128
    router_experts: int = 128
    expert_share_index: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        P = len(self.period_types)
        if self.num_layers % P:
            raise ValueError(
                f"{self.num_layers} layers are no whole periods of {P}"
            )
        if set(self.period_types) - {GLOBAL, WINDOW} or not (
            GLOBAL in self.period_types and WINDOW in self.period_types
        ):
            raise ValueError(
                f"a period needs window and global layers, got {self.period_types}"
            )
        if not 0 <= self.first_k_dense <= P:
            raise ValueError(
                f"{self.first_k_dense} leading dense layers do not lie in the "
                f"first period of {P}"
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
        return self.num_layers // len(self.period_types)

    @property
    def global_layers(self) -> int:
        """Layers that own pages by the sequence's length."""
        return self.periods * self.period_types.count(GLOBAL)

    @property
    def window_layers(self) -> int:
        """Layers that own a ring of fixed size a slot."""
        return self.num_layers - self.global_layers

    @property
    def routed_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def shared_intermediate_size(self) -> int:
        return self.moe_intermediate_size * self.num_shared_experts

    @property
    def first_expert(self) -> int:
        return self.expert_share_index * self.num_experts

    @staticmethod
    def from_hf_dict(d: dict) -> "ExaoneMoeConfig":
        """config.json of the family. `layer_types` may be longer than
        `num_hidden_layers` (a benchmark configuration cut in depth keeps the
        published list whole): its first `num_hidden_layers` entries are run
        and must be whole repeats of one period. The expert share is three
        keys of a benchmark configuration's file: `num_experts` (held here),
        `router_num_experts` (absent: all are held) and `expert_share_index`."""
        if d.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError("exaone_moe: the router scores by a sigmoid")
        if d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
            raise ValueError("exaone_moe: expert groups are not served")
        if not d.get("norm_topk_prob", True):
            raise ValueError("exaone_moe: norm_topk_prob false is not served")
        layers = d["num_hidden_layers"]
        kinds = tuple(d["layer_types"][:layers])
        if len(kinds) != layers:
            raise ValueError(
                f"layer_types names {len(kinds)} of {layers} layers"
            )
        period = next(
            p for p in range(1, layers + 1)
            if layers % p == 0 and kinds == kinds[:p] * (layers // p)
            and GLOBAL in kinds[:p]
        )
        rope = d.get("rope_parameters") or {}
        return ExaoneMoeConfig(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            moe_intermediate_size=d["moe_intermediate_size"],
            num_layers=layers,
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d["head_dim"],
            sliding_window=d["sliding_window"],
            period_types=kinds[:period],
            first_k_dense=d.get("first_k_dense_replace", 0),
            num_experts=d["num_experts"],
            router_experts=d.get("router_num_experts", d["num_experts"]),
            expert_share_index=d.get("expert_share_index", 0),
            num_experts_per_tok=d["num_experts_per_tok"],
            num_shared_experts=d.get("num_shared_experts", 1),
            routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
            rope_theta=rope.get("rope_theta", d.get("rope_theta", 1e6)),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 262144),
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "ExaoneMoeConfig":
        """Two periods; a dense first layer; 8 of 32 experts held (share 1 of
        4), 4 a token; a window of 16 positions."""
        return ExaoneMoeConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, sliding_window=16, first_k_dense=1,
            num_experts=8, router_experts=32, expert_share_index=1,
            num_experts_per_tok=4, rope_theta=10000.0,
            max_position_embeddings=2048,
        )


def kv_layers(cfg: ExaoneMoeConfig) -> dict:
    """Which layers own what (`ModelFamily.kv_layers`)."""
    return {
        "global_layers": cfg.global_layers,
        "window_layers": cfg.window_layers,
        "window": cfg.sliding_window,
    }


def param_specs(cfg: ExaoneMoeConfig) -> dict:
    def whole(rank):
        return (None,) * rank

    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": {
            "attn": {
                "input_norm": whole(2), "wq": whole(3), "wk": whole(3),
                "wv": whole(3), "wo": whole(3), "q_norm": whole(2),
                "k_norm": whole(2),
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


def init_params(cfg: ExaoneMoeConfig, key: jax.Array | None = None) -> dict:
    """Seeded weights: normal, std 0.02; norm weights 1; the selection bias
    uniform in +-0.05 (float32)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    NL, ND, NR = cfg.num_layers, cfg.first_k_dense, cfg.routed_layers
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Md, M, Ms = (cfg.intermediate_size, cfg.moe_intermediate_size,
                 cfg.shared_intermediate_size)
    X = cfg.num_experts
    ks = iter(jax.random.split(key, 20))

    def rnd(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32) * 0.02).astype(dt)

    return {
        "embed": rnd((V, E)),
        "layers": {
            "attn": {
                "input_norm": jnp.ones((NL, E), dt),
                "wq": rnd((NL, E, H * D)),
                "wk": rnd((NL, E, KVH * D)),
                "wv": rnd((NL, E, KVH * D)),
                "wo": rnd((NL, H * D, E)),
                "q_norm": jnp.ones((NL, D), dt),
                "k_norm": jnp.ones((NL, D), dt),
            },
            "dense": {
                "post_norm": jnp.ones((ND, E), dt),
                "w_gate": rnd((ND, E, Md)),
                "w_up": rnd((ND, E, Md)),
                "w_down": rnd((ND, Md, E)),
            },
            "moe": {
                "post_norm": jnp.ones((NR, E), dt),
                "router": rnd((NR, E, cfg.router_experts)),
                "router_bias": jax.random.uniform(
                    next(ks), (NR, cfg.router_experts), jnp.float32,
                    -0.05, 0.05),
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


def _qkv(h, lp, cfg, positions, rotate: bool):
    """h [B, S, E] at `positions` [B, S] -> q [B, S, H, D], k and v [B, S,
    KVH, D]; q and k RMS-normed a head, and rotated in a window layer.

    The flat projections are held as values before the head reshape at
    every number of rows, a prompt's too (`ops/projections.py` says what the
    compiler does when it can fold the reshape into the dot: here, with the
    layers read at their number inside the scan, the compiled 2 x 2048
    admission copied the WHOLE stacked `wq`, `wk` and `wv` transposed in its
    entry, 0.95 GiB of temporaries beside 13.4 GiB of arguments; AOT, PR
    46)."""
    q, k, v = jax.lax.optimization_barrier((
        jnp.einsum("bse,eh->bsh", h, lp["wq"]),
        jnp.einsum("bse,eh->bsh", h, lp["wk"]),
        jnp.einsum("bse,eh->bsh", h, lp["wv"]),
    ))
    lead = q.shape[:-1]
    q = q.reshape(*lead, cfg.num_heads, cfg.head_dim)
    k = k.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if rotate:
        inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta))
        q, k = apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq)
    return q, k, v


def _stack_kind(per_period):
    """A list (one entry a layer of that kind in a period) of scan outputs
    [periods, ...] -> [layers of that kind, ...] in layer order."""
    a = jnp.stack(per_period, axis=1)  # [periods, layers a period, ...]
    return a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])


def prefill(params, cfg, tokens, lengths, lora=None, lora_idx=None, *,
            routes=False, state=False):
    """Whole-prompt prefill of [A, S] prompts. Returns (logits at `lengths -
    1`, k_all, v_all [global layers, A, S, KVH, D]) and then, with `state`,
    the window layers' keys and values ({"k_window", "v_window"}: [window
    layers, A, S, KVH, D]; the admission writes the ring's positions) and,
    with `routes`, the expert sets [A, S, routed layers, k]."""
    A, S = tokens.shape
    P = len(cfg.period_types)
    positions = jnp.arange(S)[None, :].repeat(A, axis=0)
    layers = params["layers"]
    x = params["embed"][tokens]

    def attention(x, lp, kind):
        with jax.named_scope("attn_window" if kind == WINDOW else "attn_global"):
            h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(h, lp, cfg, positions, rotate=kind == WINDOW)
            attn = prefill_attention(
                q, k, v, window=cfg.sliding_window if kind == WINDOW else 0
            ).reshape(A, S, -1)
            return x + jnp.einsum("bsh,he->bse", attn, lp["wo"]), k, v

    def period(x, pi):
        kv = {GLOBAL: ([], []), WINDOW: ([], [])}
        topis = []
        for slot, kind in enumerate(cfg.period_types):
            layer = pi * P + slot
            x, k, v = attention(x, at(layers["attn"], layer), kind)
            kv[kind][0].append(k), kv[kind][1].append(v)
            flat, topi = _ffn(x.reshape(A * S, -1), layers, layer, slot, cfg)
            x = flat.reshape(A, S, -1)
            topis.append(topi.reshape(A, S, -1))
        return x, (kv[GLOBAL], kv[WINDOW], jnp.stack(topis))

    x, ((kg, vg), (kw, vw), topi_all) = jax.lax.scan(
        period, x, jnp.arange(cfg.periods, dtype=jnp.int32)
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = jnp.einsum(
        "be,ve->bv", last, params["lm_head"],
        preferred_element_type=jnp.float32,
    )
    out = [logits, _stack_kind(kg), _stack_kind(vg)]
    if state:
        out.append({"k_window": _stack_kind(kw), "v_window": _stack_kind(vw)})
    if routes:
        # [periods, layers a period, *rows, k] -> layers first; the leading
        # dense layers have no row.
        topi_all = topi_all.reshape(cfg.num_layers, *topi_all.shape[2:])
        out.append(stack_routes(
            topi_all[cfg.first_k_dense:], cfg.router_experts))
    return tuple(out)


def decode_step_paged(params, cfg, tokens, positions, k_pages, v_pages,
                      block_tables, lora=None, lora_idx=None, *,
                      attn_kernel=None, routes=False, state=None):
    """One token a slot. Both pools are read in place by the layer-indexed
    kernel, a global layer through the slot's page list, a window layer
    through its ring (the wrapped block table, `sliding_window` as the
    kernel's window, so it reads at most a ring of pages), and written by one
    batched scatter a pool after the scan. `state` is {"k_window", "v_window"}:
    [window layers, ring pool pages, page, KVH, D]. Returns (logits, k_pages,
    v_pages, state) and, with `routes`, the B rows' expert sets [B, routed
    layers, k]."""
    from kubeai_tpu.ops.paged_attention import (
        batched_scatter_sequence,
        paged_decode_attention_fused,
        ring_block_tables,
        ring_pages,
        token_page_coords,
    )

    if attn_kernel not in (None, "", "fused"):
        raise ValueError(f"exaone_moe decodes with the fused layout, not {attn_kernel!r}")
    if state is None:
        raise ValueError("exaone_moe decodes against its window pool")
    B = tokens.shape[0]
    P = len(cfg.period_types)
    per = {kind: cfg.period_types.count(kind) for kind in (GLOBAL, WINDOW)}
    page = k_pages.shape[2]
    tables = {
        GLOBAL: block_tables,
        WINDOW: ring_block_tables(
            block_tables, ring_pages(cfg.sliding_window, page)
        ),
    }
    pools = {
        GLOBAL: (k_pages, v_pages),
        WINDOW: (state["k_window"], state["v_window"]),
    }
    pos1 = positions[:, None]
    layers = params["layers"]
    x = params["embed"][tokens]

    def attention(x, lp, kind, li):
        """`li`: the layer's number among the layers of its kind."""
        with jax.named_scope("attn_window" if kind == WINDOW else "attn_global"):
            h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(h[:, None], lp, cfg, pos1, rotate=kind == WINDOW)
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            with jax.named_scope("paged_attention"):
                attn = paged_decode_attention_fused(
                    q, *pools[kind], k, v, tables[kind], positions, li,
                    window=cfg.sliding_window if kind == WINDOW else None,
                ).reshape(B, -1)
            return x + jnp.einsum("bh,he->be", attn, lp["wo"]), k, v

    def period(x, pi):
        kv = {GLOBAL: ([], []), WINDOW: ([], [])}
        topis = []
        for slot, kind in enumerate(cfg.period_types):
            layer = pi * P + slot
            li = pi * per[kind] + len(kv[kind][0])
            x, k, v = attention(x, at(layers["attn"], layer), kind, li)
            kv[kind][0].append(k), kv[kind][1].append(v)
            x, topi = _ffn(x, layers, layer, slot, cfg)
            topis.append(topi)
        return x, (kv[GLOBAL], kv[WINDOW], jnp.stack(topis))

    x, (new_global, new_window, topi_all) = jax.lax.scan(
        period, x, jnp.arange(cfg.periods, dtype=jnp.int32)
    )
    with jax.named_scope("kv_page_write"):
        for kind, (k_new, v_new) in ((GLOBAL, new_global), (WINDOW, new_window)):
            page_ids, offsets = token_page_coords(tables[kind], positions, page)
            pools[kind] = batched_scatter_sequence(
                *pools[kind], _stack_kind(k_new)[:, :, None],
                _stack_kind(v_new)[:, :, None], page_ids[:, None],
                offsets[:, None],
            )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "be,ve->bv", x, params["lm_head"],
            preferred_element_type=jnp.float32,
        )
    state = dict(zip(("k_window", "v_window"), pools[WINDOW]))
    if routes:
        topi_all = topi_all.reshape(cfg.num_layers, *topi_all.shape[2:])
        return logits, *pools[GLOBAL], state, stack_routes(
            topi_all[cfg.first_k_dense:], cfg.router_experts)
    return logits, *pools[GLOBAL], state


register_model_family(
    ModelFamily(
        "exaone_moe",
        config_from_hf=ExaoneMoeConfig.from_hf_dict,
        tiny_config=ExaoneMoeConfig.tiny,
        init_params=init_params,
        param_specs=param_specs,
        prefill=prefill,
        decode_step=None,  # the two page pools are the cache
        decode_step_paged=decode_step_paged,
        hf_architectures=("ExaoneMoeForCausalLM",),
        route_dims=lambda cfg: (
            cfg.router_experts, cfg.num_experts_per_tok, cfg.routed_layers
        ),
        held_experts=lambda cfg: (
            cfg.first_expert, cfg.first_expert + cfg.num_experts
        ),
        kv_layers=kv_layers,
    )
)
