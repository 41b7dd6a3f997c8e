"""The routed expert layer, sparsely computed: what every family whose rows
take a few of many experts shares.

`moe_sparse` takes rows and the expert sets a family's own router chose for
them (the router is the family's: softmax or sigmoid, biased or not) and
returns the routed sum: the rows' assignments put in the order of their
experts (`dispatch`), one grouped product over the experts that hold any
(`ops/grouped_matmul.py`), each row's results added back under its weights
(`combine`). A layer may hold a SHARE of the router's experts (`first`).
Beside it, what its callers each need around it: the shared expert every
row also takes (`shared_expert`), a layer of stacked weights read at its
number (`at`), and the form in which a forward hands its expert sets over
(`route_dtype`, `stack_routes`). `tests/unit/test_moe_experts_compiled.py`
counts the compiled operations under the three scopes `moe_dispatch`,
`moe_experts` and `moe_combine`. At the end, the one router two families
share whole: sigmoid scores selected under a bias, behind leading dense layers
(`route_sigmoid_bias`, `moe_parts_sigmoid_bias`, `dense_ffn`,
`ffn_behind_dense`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubeai_tpu.ops.grouped_matmul import grouped_matmul, tile_plan
from kubeai_tpu.ops.norms import rms_norm

# The leaves of a sparse family's expert stack, each [layers, experts, ...].
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")

# Most assignments (rows x k) whose order `dispatch` finds by comparison;
# above it they are sorted. The comparison is [N * k, N * k] and the two
# one-hot products [N * k, N] x [N, E], so their cost grows with the square
# where a sort and a gather grow with N * k. Read on a v5e (my chip run, PR
# 48: us a layer of `dispatch` + `combine` at bf16, the wall clock of a scan
# of 64 layers whose products are one elementwise pass, less the same scan
# without the books; median of 30): by comparison / sorted with the inverse
# scattered / sorted with the inverse as a second `argsort`:
#     512 (64 x 8, 16 held of 128, E 6,144)     11.0 /  57.7 /  60.3
#     640 (64 x 10, 64 held of 512, E 2,048)     5.7 /  44.1 /  42.6
#   1,024 (128 x 8, 128 held, E 2,048)          16.2 /  56.0 /  54.9
#   1,280 (128 x 10, 64 held of 512)            14.8 /  71.1 /  70.2
#   2,048 (256 x 8, 128 held)                   79.1 /  84.7 /  80.5
#   5,120 (512 x 10, 64 held of 512)           195.3 / 217.9 / 201.9
#  16,384 (2,048 x 8, 128 held)                    - / 489.8 / 418.8
# Four to eight times cheaper up to 1,280, level from 2,048 on, where the
# products' square would soon lead (4 GFLOP at 2,048, 275 at 16,384): the
# largest size read at which the comparison clearly wins. The second
# `argsort` beats the scatter at every size read above it.
RANK_BY_COMPARISON_MAX = 1280


def _exact(dtype):
    """The precision at which a product adds up its operands' products as
    they are: bf16 operands are exact in one pass into a float32 sum,
    float32 operands (the tests') need every pass."""
    return None if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST


def dispatch(x, flat, k, X):
    """Rows `x` [N, E] and their assignments `flat` [N * k] (the local expert
    of assignment n * k + j, X for one that is not held) -> (xs [N * k, E]:
    the rows in the order of their experts, an expert's in row order;
    counts [X]: assignments an expert; dest [N, k]: where in `xs` each
    assignment's row went, the inverse of the stable `argsort(flat)`).

    Few assignments (a decode step, a block) are put in order where they
    lie: `dest` is ONE comparison of every assignment's key with every
    other's, summed, and `xs` a product with the [N * k, N] matrix of zeros
    and ones that says which row goes where (one term a row, so exact).
    Nothing is sorted, gathered or scattered; on a v5e the `argsort`, the
    row gather and `bincount`'s scatter-add cost 4.5, 9.7 and 6.8 us a layer
    at 640 assignments (PERF.md section 5). Many (an admission) are sorted."""
    (NK,) = flat.shape
    counts = (flat[:, None] == jnp.arange(X, dtype=flat.dtype)).sum(
        0, dtype=jnp.int32)
    if NK > RANK_BY_COMPARISON_MAX:
        order = jnp.argsort(flat)  # stable
        return x[order // k], counts, jnp.argsort(order).reshape(-1, k)
    key = flat * NK + jnp.arange(NK, dtype=flat.dtype)  # distinct; ordered as the stable sort orders
    dest = (key[None, :] < key[:, None]).sum(1, dtype=jnp.int32).reshape(-1, k)
    goes = (dest[None] == jnp.arange(NK, dtype=jnp.int32)[:, None, None]).any(-1)
    xs = jnp.dot(goes.astype(x.dtype), x, precision=_exact(x.dtype),
                 preferred_element_type=x.dtype)
    return xs, counts, dest


def combine(out, dest, weights):
    """out [N * k, E] in `dispatch`'s order -> [N, E] float32: row n's k
    results `out[dest[n, j]]` under `weights` [N, k] (of `out`'s dtype),
    summed in float32. Few assignments: a product with the [N, N * k] matrix
    that holds each weight at its assignment's place (at most one term an
    entry, so exact), the same products summed in another order than the
    gather-then-sum that many assignments keep."""
    N, k = dest.shape
    if N * k > RANK_BY_COMPARISON_MAX:
        return jnp.einsum("nke,nk->ne", out[dest], weights,
                          preferred_element_type=jnp.float32)
    place = dest[:, :, None] == jnp.arange(N * k, dtype=jnp.int32)  # [N, k, N * k]
    return jnp.dot(
        jnp.where(place, weights[:, :, None], 0).sum(1), out,
        precision=_exact(out.dtype), preferred_element_type=jnp.float32)


def moe_sparse(x, experts, layer, topi, probs, first=None):
    """x [N, E] through the experts each row took (`topi` [N, k], weights
    `probs` [N, k] float32): the N * k assignments in the order of their
    experts (`dispatch`), one grouped product over the experts that hold
    any, and each row's k results added back weighted (`combine`). No
    assignment is dropped, whatever the load of an expert; one that gets no
    row costs nothing.

    `experts` holds every layer's weights, [NL, X, ...]; the grouped
    product reads them as NL * X groups and is told which X are `layer`'s,
    so no layer is sliced out of the stack.

    `first` (None: every expert the router scores is held, `topi` counts
    from 0): the layer holds a SHARE of the router's experts, global ids
    `first .. first + X - 1`. An assignment to an expert that lives on
    another chip is ordered behind the held ones, belongs to no group of
    the product and adds nothing: what comes back is this share's part of
    the routed sum, under the weights of the whole set."""
    N, k = topi.shape
    NL, X = experts["w_gate"].shape[:2]
    with jax.named_scope("moe_dispatch"):
        flat = topi.reshape(-1)
        if first is not None:
            flat = flat - first
            flat = jnp.where((flat >= 0) & (flat < X), flat, X)
        xs, counts, dest = dispatch(x, flat, k, X)
    with jax.named_scope("moe_experts"):
        # One tile map for the three products, over this layer's X groups;
        # only the weights are NL * X groups wide.
        product = functools.partial(
            grouped_matmul, sizes=counts, layer=layer,
            plan=tile_plan(counts, N * k))
        stacked = {
            name: w.reshape(NL * X, *w.shape[2:]) for name, w in experts.items()
        }
        g = product(xs, stacked["w_gate"])
        u = product(xs, stacked["w_up"])
        out = product(jax.nn.silu(g) * u, stacked["w_down"])
    with jax.named_scope("moe_combine"):
        if first is not None:
            # Rows behind the last group are the product's to leave unwritten
            # (a zero weight does not do: 0 x NaN is NaN).
            held = jnp.arange(N * k) < counts.sum()
            out = jnp.where(held[:, None], out, 0)
        return combine(out, dest, probs.astype(out.dtype)).astype(x.dtype)


def shared_expert(x, mp):
    """Rows x [N, E] through the expert every row takes (`shared_gate`,
    `shared_up`, `shared_down` of `mp`): its SwiGLU, summed into float32.
    A family that gates it applies its gate to what comes back."""
    mid = jax.nn.silu(x @ mp["shared_gate"]) * (x @ mp["shared_up"])
    return jnp.einsum(
        "nm,me->ne", mid, mp["shared_down"],
        preferred_element_type=jnp.float32,
    )


def at(tree, i):
    """Layer `i` (traced) of weights stacked over layers."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
    )


def route_dtype(num_experts: int) -> str:
    """The smallest unsigned integer type that holds a global expert id:
    what a routed family's forwards hand their expert sets over in."""
    return "uint8" if num_experts <= 256 else (
        "uint16" if num_experts <= 65536 else "uint32"
    )


def stack_routes(topi, num_experts: int):
    """Expert sets stacked layers first, [routed layers, *rows, k] (a layer
    scan's output; a caller whose scan runs over periods, or has leading
    dense layers, reshapes or slices first) as the forwards hand them over:
    [*rows, routed layers, k], in the smallest unsigned integer type that
    holds one of the router's `num_experts` ids."""
    return jnp.moveaxis(topi, 0, -2).astype(route_dtype(num_experts))

# ---- a sigmoid router behind leading dense layers ------------------------------
#
# What the families share whose router scores by a sigmoid, selects on the
# score plus a bias and weights by the score alone, with one ungated shared
# expert and `first_k_dense` leading layers that have a dense SwiGLU instead
# (models/exaone_moe.py, models/kimi_linear.py). `cfg` is the family's own
# configuration; read of it: `num_experts_per_tok`, `routed_scaling_factor`,
# `first_expert`, `first_k_dense`, `rms_norm_eps`.


@jax.named_scope("dense_ffn")
def dense_ffn(x, dp):
    """Rows x [N, E] (already normed) through a leading dense layer's SwiGLU."""
    mid = jax.nn.silu(x @ dp["w_gate"]) * (x @ dp["w_up"])
    return mid @ dp["w_down"]


def route_sigmoid_bias(x, mp, cfg):
    """(topi [N, k]: the global ids taken, best first; their weights [N, k]
    float32). Selected on `sigmoid + bias`, weighted by the sigmoid alone,
    renormalised over the taken and scaled."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.einsum(
            "ne,ex->nx", x, mp["router"], preferred_element_type=jnp.float32
        ))
        _, topi = jax.lax.top_k(s + mp["router_bias"], cfg.num_experts_per_tok)
        taken = jnp.take_along_axis(s, topi, axis=-1)
        probs = cfg.routed_scaling_factor * taken / jnp.sum(
            taken, axis=-1, keepdims=True
        )
    return topi, probs


def moe_parts_sigmoid_bias(x, mp, experts, layer, cfg):
    """Rows x [N, E] (already normed) through routed layer `layer`: (this
    share's part of the routed sum, the shared expert's output, both
    float32, and topi [N, k])."""
    topi, probs = route_sigmoid_bias(x, mp, cfg)
    with jax.named_scope("moe_shared"):
        shared = shared_expert(x, mp)
    routed = moe_sparse(
        x, experts, layer, topi, probs, first=cfg.first_expert
    )
    return routed.astype(jnp.float32), shared, topi


def ffn_behind_dense(x, layers, layer, slot, cfg):
    """x [N, E] after attention through the FFN of `layer` (traced), which
    stands at `slot` (static) of its period: x + ffn(rms(x)) and the expert
    sets topi [N, k] it took (zeros for a dense layer, which has no row in
    the hand-over). Only period 0's first `first_k_dense` slots can be dense."""
    k = cfg.num_experts_per_tok

    def dense(x):
        dp = at(layers["dense"], jnp.minimum(layer, cfg.first_k_dense - 1))
        h = rms_norm(x, dp["post_norm"], cfg.rms_norm_eps)
        return x + dense_ffn(h, dp), jnp.zeros((x.shape[0], k), jnp.int32)

    @jax.named_scope("moe_ffn")
    def moe(x):
        r = jnp.maximum(layer - cfg.first_k_dense, 0)
        mp = at(layers["moe"], r)
        h = rms_norm(x, mp["post_norm"], cfg.rms_norm_eps)
        routed, shared, topi = moe_parts_sigmoid_bias(
            h, mp, layers["experts"], r, cfg)
        return x + (routed + shared).astype(x.dtype), topi

    if slot >= cfg.first_k_dense:
        return moe(x)
    return jax.lax.cond(layer < cfg.first_k_dense, dense, moe, x)
