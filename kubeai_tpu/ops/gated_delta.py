"""The gated delta rule (Gated DeltaNet's recurrence), in the two forms a
serving engine runs it.

A value head keeps a state `S [DK, DV]` (float32). One position, with the
head's `q`, `k` `[DK]`, `v` `[DV]`, a decay `exp(g)` in (0, 1] and a write
strength `beta` in (0, 1):

    S = S * exp(g);  d = (v - S^T k) * beta;  S = S + k (x) d;  o = S^T q

The decay is one number a head (Gated DeltaNet: every row of `S` forgets
alike) or one a key channel (Kimi Delta Attention: `g [DK]`, row `c` of `S`
forgets by `exp(g[c])`, which is `S = Diag(exp(g)) S` above and the same
three lines after it).

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
`gdn_positions` is the rule position by position, what the chunked forms are
tested against. `kda_chunk_scan` is the chunked form under a decay a channel:
there the decay between two positions of a chunk is a vector, `exp(G_i - G_j)`
with `G` the chunk's cumulative log-decay, and it cannot be split into
`exp(G_i) * exp(-G_j)` (the second overflows float32 for a channel that
forgets within a few positions). So a chunk is cut into blocks of `sub`
positions: inside a block the decays are taken pair by pair, each at most 1;
between a block and the positions before it they are split at the block's
start, `exp(G_i - R) * exp(R - G_j)` with `G_i <= R <= G_j`, each factor at
most 1 again, and the products go to the matrix unit. No gate is clamped.
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
    broadcasts over the state's DV lanes; v, beta and o are rows [heads, DV]
    (beta constant along a row). The decay comes as what it is: one number a
    head as such a row, one a key channel as such a column (the block's rank
    says which; nothing is branched on when the kernel runs)."""
    del layer_ref  # it chose the block
    a_channel = len(decay_ref.shape) == 4
    for h in range(heads):
        kc = k_ref[0, 0, :, h:h + 1]  # [DK, 1]
        s = s_ref[0, 0, h] * (
            decay_ref[0, 0, :, h:h + 1] if a_channel else decay_ref[0, h:h + 1, :]
        )
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
            in_specs=[col, col, row, row if decay.ndim == 2 else col, row, blk],
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
    )(layer.reshape(1), columns(q), columns(k), v,
      rows(decay) if decay.ndim == 2 else columns(decay), rows(beta), state)
    return state, o


def _over_state(decay, state):
    """A decay [..., HV] (a head) or [..., HV, DK] (a key channel) as it
    multiplies the states [..., HV, DK, DV]."""
    a_channel = decay.ndim == state.ndim - 1
    return decay[..., None] if a_channel else decay[..., None, None]


def ref_gdn_update(state, layer, q, k, v, decay, beta):
    """The four lines on layer `layer` of the pool, in `jnp`."""
    s = jax.lax.dynamic_index_in_dim(state, layer, axis=0, keepdims=False)
    s = s * _over_state(decay, s)
    kv = jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HIGHEST)
    d = (v - kv) * beta[..., None]
    s = s + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HIGHEST)
    return jax.lax.dynamic_update_index_in_dim(state, s, layer, axis=0), o


def gdn_update(state, layer, q, k, v, decay, beta):
    """One position a slot. `state` [layers, B, HV, DK, DV] float32 (the
    whole pool), `layer` its row; q, k [B, HV, DK], v [B, HV, DV], beta [B,
    HV] and decay (= exp(g)) [B, HV], or [B, HV, DK] where the key channels
    of a head forget apart, all float32. Returns (the pool with that layer's
    states updated, o [B, HV, DV])."""
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
    """The rule position by position. q, k [T, HV, DK], v [T, HV, DV], beta
    [T, HV] and g [T, HV] or, a key channel, [T, HV, DK]; `state` [HV, DK,
    DV] or None (zeros). Returns (o [T, HV, DV], the state after the last
    position)."""
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)

    def one(s, x):
        qt, kt, vt, gt, bt = x
        s = s * _over_state(jnp.exp(gt), s)
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
    A, S = q.shape[:2]
    C = min(chunk, S)
    if S % C:
        raise ValueError(f"{S} positions are no multiple of the chunk {C}")
    n = S // C
    ein = functools.partial(jnp.einsum, precision=_HIGHEST)
    q, k, v = _chunks(q, n, C), _chunks(k, n, C), _chunks(v, n, C)
    g, beta = _chunks(g, n, C), _chunks(beta, n, C)  # [n, A, H, C]
    G = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    # exp(G_i - G_j) for i >= j: each factor is at most 1.
    decay = jnp.where(lower, jnp.exp(
        jnp.where(lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    k_beta = k * beta[..., None]
    N = jnp.where(strict, ein("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
    within = jnp.where(lower, ein("...ik,...jk->...ij", q, k) * decay, 0.0)
    return _carry_chunks(
        N, within, v * beta[..., None], k_beta * jnp.exp(G)[..., None],
        q * jnp.exp(G)[..., None], k * jnp.exp(G[..., -1:] - G)[..., None],
        jnp.exp(G[..., -1]), (A, S),
    )


def _carry_chunks(N, within, v_beta, k_in, q_in, k_out, last, shape):
    """What the two chunked forms share once a chunk's positions are
    weighed against one another: N [n, A, H, C, C] (strictly lower: `beta_i`
    times key i against key j under the decay between them), `within` (lower:
    query i against key j under it), and, decayed from the chunk's start or
    to its end, `k_in`, `q_in`, `k_out` [n, A, H, C, DK]; `last` the whole
    chunk's decay, [n, A, H] or, a key channel, [n, A, H, DK]. Returns (o [A,
    S, H, DV], the state after position S - 1)."""
    A, S = shape
    C, DK, DV = N.shape[-1], k_in.shape[-1], v_beta.shape[-1]
    H = N.shape[2]
    ein = functools.partial(jnp.einsum, precision=_HIGHEST)
    # (I + N)^-1 with N strictly lower (N^C = 0), by products:
    # (I - N)(I + N^2)(I + N^4)... over the powers below C.
    eye = jnp.eye(C, dtype=jnp.float32)
    T, P = eye - N, ein("...ij,...jk->...ik", N, N)
    span = 2
    while span < C:
        T = T + ein("...ij,...jk->...ik", T, P)
        P = ein("...ij,...jk->...ik", P, P)
        span *= 2
    u = ein("...ij,...jv->...iv", T, v_beta)  # [n, A, H, C, DV]
    w = ein("...ij,...jk->...ik", T, k_in)

    def one(s, x):
        u_c, w_c, within_c, q_c, k_c, last_c = x
        v_new = u_c - ein("ahck,ahkv->ahcv", w_c, s)
        o = ein("ahck,ahkv->ahcv", q_c, s) + ein(
            "ahij,ahjv->ahiv", within_c, v_new)
        s = s * _over_state(last_c, s) + ein("ahck,ahcv->ahkv", k_c, v_new)
        return s, o

    state, o = jax.lax.scan(
        one, jnp.zeros((A, H, DK, DV), jnp.float32),
        (u, w, within, q_in, k_out, last),
    )
    # [n, A, H, C, DV] -> [A, S, H, DV]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(A, S, H, DV)
    return o, state


def _chunks(x, n, C):
    """[A, S, H, ...] -> [n, A, H, C, ...]."""
    x = x.reshape(x.shape[0], n, C, *x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def kda_chunk_scan(q, k, v, g, beta, chunk: int = 64, sub: int = 16):
    """`gdn_chunk_scan` under a decay a key channel: g [A, S, H, DK] (log
    decay, <= 0, of any size), everything else as there. Exact for any gate
    (the module's docstring says how no factor passes 1)."""
    A, S, H, DK = q.shape
    C = min(chunk, S)
    B = min(sub, C)
    if S % C or C % B:
        raise ValueError(
            f"{S} positions are no whole chunks of {C} in blocks of {B}")
    n = S // C
    ein = functools.partial(jnp.einsum, precision=_HIGHEST)
    q, k, v, g, beta = (_chunks(x, n, C) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)  # [n, A, H, C, DK]
    tri = jnp.tril(jnp.ones((B, B), bool))[..., None]
    kk, qk = [], []  # a block of rows each: [n, A, H, B, C]
    for lo in range(0, C, B):
        Gb, kb, qb = (x[..., lo:lo + B, :] for x in (G, k, q))
        # Inside the block, pair by pair: exp(G_i - G_j) for i >= j.
        pair = jnp.where(tri, jnp.exp(jnp.where(
            tri, Gb[..., :, None, :] - Gb[..., None, :, :], 0.0)), 0.0)
        rows = [[], []]
        if lo:
            # Against the positions before the block, split at its start.
            R = G[..., lo - 1:lo, :]
            before = k[..., :lo, :] * jnp.exp(R - G[..., :lo, :])
            for part, x in zip(rows, (kb, qb)):
                part.append(ein(
                    "...ic,...jc->...ij", x * jnp.exp(Gb - R), before))
        for part, x in zip(rows, (kb, qb)):
            part.append(jnp.sum(
                x[..., :, None, :] * kb[..., None, :, :] * pair, axis=-1))
            part.append(jnp.zeros((*x.shape[:-2], B, C - lo - B), jnp.float32))
        kk.append(jnp.concatenate(rows[0], axis=-1))
        qk.append(jnp.concatenate(rows[1], axis=-1))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    N = jnp.where(
        strict, jnp.concatenate(kk, axis=-2) * beta[..., None], 0.0)
    to_end = jnp.exp(G[..., -1:, :] - G)
    return _carry_chunks(
        N, jnp.concatenate(qk, axis=-2), v * beta[..., None],
        k * beta[..., None] * jnp.exp(G), q * jnp.exp(G), k * to_end,
        jnp.exp(G[..., -1, :]), (A, S),
    )


# ---- the short causal convolution in front of the rule ------------------------
#
# Both families that run the rule convolve q, k and v over the last K
# positions, a channel at a time (depthwise, no bias), before it. A slot keeps
# its last K - 1 inputs beside its recurrent state.


def conv_prefill(u, w, lengths):
    """A batch of prompts from nothing: inputs `u` [A, S, C], taps `w` [K, C]
    (the last tap is the position's own), true lengths [A] -> (y [A, S, C]
    float32, the K - 1 inputs a slot keeps [A, (K - 1) * C]: those at
    `lengths - K + 1 .. lengths - 1`, the oldest first, zeros before position
    0)."""
    A, S, _ = u.shape
    K = w.shape[0]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(
        padded[:, j : j + S].astype(jnp.float32) * w[j].astype(jnp.float32)
        for j in range(K)
    )
    idx = lengths[:, None] + jnp.arange(K - 1)[None, :]  # into `padded`
    tail = jnp.take_along_axis(padded, idx[:, :, None], axis=1)
    return y, tail.reshape(A, -1)


def conv_step(conv, li, u, w):
    """One position of layer `li`'s causal convolution, every slot: the pool
    `conv` [state layers, B, (K - 1) * C] (a slot's last K - 1 inputs, the
    oldest first), the position's input `u` [B, C] and the taps `w` [K, C]
    -> (y [B, C] float32, the pool with the layer's rows moved on by one).

    Tap t of every slot is read where it lies: C is a multiple of the 128
    lanes, so `[li, :, t * C:(t + 1) * C]` is a slice in the tiling the pool
    has, and the K products are summed as they are read. As a `[B, K, C]`
    window the row was re-laid-out three times a layer to be read once
    (PERF.md section 5: 11.6-11.9 us each on a v5e, beside a 27.5 us sum
    over a 4-row sublane axis)."""
    B, C = u.shape
    K = w.shape[0]
    u = u.astype(conv.dtype)
    taps = [
        jax.lax.dynamic_slice(conv, (li, 0, t * C), (1, B, C))[0]
        for t in range(K - 1)
    ] + [u]
    y = sum(
        tap.astype(jnp.float32) * w[t].astype(jnp.float32)
        for t, tap in enumerate(taps)
    )
    return y, jax.lax.dynamic_update_index_in_dim(
        conv, jnp.concatenate(taps[1:], axis=-1), li, 0)
