"""The gated delta rule (Gated DeltaNet's recurrence), in the two forms a
serving engine runs it.

A value head keeps a state `S [DK, DV]` (float32). One position, with the
head's `q`, `k` `[DK]`, `v` `[DV]`, a decay `exp(g)` in (0, 1] and a write
strength `beta` in (0, 1):

    S = S * exp(g);  d = (v - S^T k) * beta;  S = S + k (x) d;  o = S^T q

**Decode** (`gdn_update`): one position a slot against the engine's state
pool `[layers, slots, HV, DK, DV]`, updated in place. Where kernels run
(`ops/dispatch.py`) it is ONE pass: a Pallas kernel whose grid walks (slot,
block of heads), each step bringing that block's states into VMEM, doing
the four lines above there and writing the block back to where it came from
(`input_output_aliases`): every state element is read once and written once
a step. The pool stays stacked over layers; the layer is a prefetched
scalar in the block's index, so no layer is sliced out of the stack (the
slice would be a copy of 134 MB a layer at 64 slots of 32 heads of 128 x
128). On the `jnp` path it is the same four lines on the sliced layer.

**Prefill** (`gdn_chunk_scan`): a prompt's positions in chunks of `chunk`,
the chunk's positions against one another by matrix products and the state
carried from chunk to chunk (the WY form of "Gated Delta Networks", Yang et
al. 2024, as the family's modeling file computes it). A position whose
`beta` is 0 and whose `g` is 0 neither writes nor decays: that is how a
prompt padded into its bucket leaves the state of its true length.
`gdn_positions` is the rule position by position, what the chunked form is
tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import dispatch

HEADS_A_BLOCK = 16  # 16 states of 128 x 128 float32: 1 MiB in, 1 MiB out
_HIGHEST = jax.lax.Precision.HIGHEST


def _gdn_update_kernel(layer_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref,
                       s_ref, o_ref, s_out_ref, *, heads: int):
    """One (slot, block of `heads` value heads). q and k come with DK on the
    sublanes and a head a lane ([DK, heads]), so that a head's column
    broadcasts over the state's DV lanes; v, decay, beta and o are rows
    [heads, DV] (decay and beta constant along a row)."""
    del layer_ref  # it chose the block
    for h in range(heads):
        kc = k_ref[0, 0, :, h:h + 1]  # [DK, 1]
        s = s_ref[0, 0, h] * decay_ref[0, h:h + 1, :]
        kv = jnp.sum(s * kc, axis=0, keepdims=True)  # [1, DV]
        d = (v_ref[0, h:h + 1, :] - kv) * beta_ref[0, h:h + 1, :]
        s = s + kc * d
        o_ref[0, h:h + 1, :] = jnp.sum(
            s * q_ref[0, 0, :, h:h + 1], axis=0, keepdims=True)
        s_out_ref[0, 0, h] = s


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=0)
def _gdn_update_pallas(state, layer, q, k, v, decay, beta, *, interpret=False):
    # Imported where it is used: the registry loads every family's module,
    # and Pallas costs a second of every process's start (PERF.md, setup_s).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, slots, hv, dk, dv = state.shape
    hb = HEADS_A_BLOCK if hv % HEADS_A_BLOCK == 0 else hv
    nb = hv // hb

    def columns(x):  # [B, HV, DK] -> [B, blocks, DK, heads a block]
        return x.reshape(slots, nb, hb, dk).swapaxes(2, 3)

    def rows(x):  # [B, HV] -> [B, HV, DV]
        return jnp.broadcast_to(x[..., None], (slots, hv, dv))

    col = pl.BlockSpec((1, 1, dk, hb), lambda b, j, layer: (b, j, 0, 0))
    row = pl.BlockSpec((1, hb, dv), lambda b, j, layer: (b, j, 0))
    blk = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda b, j, layer: (layer[0], b, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_gdn_update_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, nb),
            in_specs=[col, col, row, row, row, blk],
            out_specs=[row, blk],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, hv, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # Operand 6 (after the prefetched layer) is the pool: the blocks
        # the grid does not visit, every other layer's, stay what they are.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(layer.reshape(1), columns(q), columns(k), v, rows(decay), rows(beta),
      state)
    return state, o


def ref_gdn_update(state, layer, q, k, v, decay, beta):
    """The four lines on layer `layer` of the pool, in `jnp`."""
    s = jax.lax.dynamic_index_in_dim(state, layer, axis=0, keepdims=False)
    s = s * decay[..., None, None]
    kv = jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HIGHEST)
    d = (v - kv) * beta[..., None]
    s = s + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HIGHEST)
    return jax.lax.dynamic_update_index_in_dim(state, s, layer, axis=0), o


def gdn_update(state, layer, q, k, v, decay, beta):
    """One position a slot. `state` [layers, B, HV, DK, DV] float32 (the
    whole pool), `layer` its row; q, k [B, HV, DK], v [B, HV, DV], decay
    (= exp(g)) and beta [B, HV], all float32. Returns (the pool with that
    layer's states updated, o [B, HV, DV])."""
    layer = jnp.asarray(layer, jnp.int32)
    mode = dispatch.kernel_mode()
    if mode == "reference":
        return ref_gdn_update(state, layer, q, k, v, decay, beta)
    call = dispatch.on_every_device(
        functools.partial(_gdn_update_pallas, interpret=mode == "interpret"),
        n_in=7, n_out=2,
    )
    return call(state, layer, q, k, v, decay, beta)


def gdn_positions(q, k, v, g, beta, state=None):
    """The rule position by position. q, k [T, HV, DK], v [T, HV, DV], g and
    beta [T, HV]; `state` [HV, DK, DV] or None (zeros). Returns (o [T, HV,
    DV], the state after the last position)."""
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)

    def one(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        kv = jnp.einsum("hk,hkv->hv", kt, s, precision=_HIGHEST)
        d = (vt - kv) * bt[:, None]
        s = s + kt[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=_HIGHEST)

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    return o, state


def gdn_chunk_scan(q, k, v, g, beta, chunk: int = 64):
    """A batch of prompts from an empty state. q, k [A, S, HV, DK], v [A, S,
    HV, DV], g (log decay, <= 0) and beta [A, S, HV], float32; S a multiple
    of `chunk` or shorter than it. Returns (o [A, S, HV, DV], the state
    after position S - 1 [A, HV, DK, DV])."""
    A, S, H, DK = q.shape
    DV = v.shape[-1]
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"{S} positions are no multiple of the chunk {C}")
    n = S // C
    ein = functools.partial(jnp.einsum, precision=_HIGHEST)

    def chunks(x):  # [A, S, H, ...] -> [n, A, H, C, ...]
        x = x.reshape(A, n, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g), chunks(beta)  # [n, A, H, C]
    G = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    # exp(G_i - G_j) for i >= j: each factor is at most 1.
    decay = jnp.where(lower, jnp.exp(
        jnp.where(lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    k_beta = k * beta[..., None]
    # (I + N)^-1 with N strictly lower (N^C = 0), by products:
    # (I - N)(I + N^2)(I + N^4)... over the powers below C.
    N = jnp.where(strict, ein("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
    eye = jnp.eye(C, dtype=jnp.float32)
    T, P = eye - N, ein("...ij,...jk->...ik", N, N)
    span = 2
    while span < C:
        T = T + ein("...ij,...jk->...ik", T, P)
        P = ein("...ij,...jk->...ik", P, P)
        span *= 2
    u = ein("...ij,...jv->...iv", T, v * beta[..., None])  # [n, A, H, C, DV]
    w = ein("...ij,...jk->...ik", T, k_beta * jnp.exp(G)[..., None])
    within = jnp.where(lower, ein("...ik,...jk->...ij", q, k) * decay, 0.0)
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])  # [n, A, H]

    def one(s, x):
        u_c, w_c, within_c, q_c, k_c, last_c = x
        v_new = u_c - ein("ahck,ahkv->ahcv", w_c, s)
        o = ein("ahck,ahkv->ahcv", q_c, s) + ein(
            "ahij,ahjv->ahiv", within_c, v_new)
        s = s * last_c[..., None, None] + ein("ahck,ahcv->ahkv", k_c, v_new)
        return s, o

    state, o = jax.lax.scan(
        one, jnp.zeros((A, H, DK, DV), jnp.float32),
        (u, w, within, q_in, k_out, last),
    )
    # [n, A, H, C, DV] -> [A, S, H, DV]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(A, S, H, DV)
    return o, state
