"""The `sdar_moe` family (SDAR-30B-A3B's shape at a tiny size: QK-norm, a
`head_dim` that is not hidden_size / heads, 2 of 8 experts computed sparsely,
blocks of 4 filled over up to 4 denoising forwards and one that writes the
cache), on the CPU with seeded weights, against the benchmark's plain
reference `perf/reference/sdar_moe.py`, which imports nothing of the program.

Each tolerance stands between two readings, written beside it: the largest
the sound program gives and the smallest a control gives (the reference's
own float8 forward, or the program with one part of the mathematics left
out)."""

import dataclasses
import functools
import http.client
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.routes import decode_forwards, encode_forwards
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama, mixtral
from kubeai_tpu.models.registry import get_model_family
from kubeai_tpu.ops import dispatch
from kubeai_tpu.ops import experts as experts_ops
from kubeai_tpu.ops.attention import causal_prefill_attention, prefill_attention
from kubeai_tpu.ops.paged_attention import (
    batched_scatter_sequence,
    batched_sequence_page_coords,
    paged_block_attention_fused,
    ref_paged_block_attention_fused,
    ref_paged_decode_attention_fused,
)
from perf.reference import sdar_moe as reference

HF = {
    "architectures": ["SDARMoeForCausalLM"], "head_dim": 32, "hidden_size": 64,
    "intermediate_size": 192, "moe_intermediate_size": 96, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "vocab_size": 512,
    "assumed": {"block_length": 4, "denoising_steps": 4,
                "confidence_threshold": 0.9, "mask_token_id": 511},
}
KEY = jax.random.PRNGKey(36)
PAGE, SLOT_PAGES = 16, 8
MASK, R = 511, 4


@pytest.fixture(scope="module")
def family():
    return get_model_family("SDARMoeForCausalLM")


@pytest.fixture(scope="module")
def served(family):
    """The family's config from the benchmark's keys, and the reference's
    seeded weights in the program's layout (bf16, as served)."""
    cfg = family.config_from_hf(HF)
    assert (cfg.head_size, cfg.qk_norm, cfg.sparse_experts) == (32, True, True)
    assert cfg.head_size != cfg.hidden_size // cfg.num_heads
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (4, 4, 511)
    return cfg, jax.jit(lambda k: reference.served_params(HF, k))(KEY)


# ---- the model through the paged cache, against the reference -----------------


@functools.lru_cache(maxsize=None)
def _jitted(family, cfg):
    """The family's two forwards, compiled once a config."""
    return (
        jax.jit(lambda params, toks, n: mixtral.prefill(
            params, cfg, toks, n, routes=True)),
        jax.jit(lambda params, state, start, kp, vp, bt: family.block_forward_paged(
            params, cfg, state, start, kp, vp, bt, routes=True)),
    )


def serve(family, cfg, params, prompt, n_blocks=4, fault=None):
    """The family's forwards as the engine chains them, for one request:
    prefill of the prompt's whole blocks into pages, then blocks, each denoised
    until no mask is left and closed by the forward that writes its K and V.
    Returns the served tokens, the forwards `(start, rows, commit, tokens)`
    in the order they ran, and the program's logits row at each (position)
    where a forward committed.

    `fault` leaves one part out: "qk_norm", "block_mask" (the prompt under
    the causal mask), ("expert", e) (the output of expert e in every layer),
    "commit_kv" (a finished block's K and V are not written)."""
    if fault == "qk_norm":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    if isinstance(fault, tuple):
        layers = dict(params["layers"])
        layers["w_down"] = layers["w_down"].at[:, fault[1]].set(0)
        params = {**params, "layers": layers}
    n_pages = 1 + SLOT_PAGES
    pool = jnp.zeros((cfg.num_layers, n_pages, PAGE, cfg.num_kv_heads,
                      cfg.head_size), cfg.dtype)
    kp, vp = pool, pool
    bt = jnp.arange(1, n_pages, dtype=jnp.int32)[None]
    whole = len(prompt) // R * R
    wire, logits_at, tokens_out = [], {}, []
    if whole:
        bucket = -(-whole // 16) * 16
        toks = jnp.asarray([prompt[:whole] + [0] * (bucket - whole)], jnp.int32)
        mcfg = dataclasses.replace(cfg, block_length=1) if fault == "block_mask" else cfg
        _, k_all, v_all, routes = _jitted(family, mcfg)[0](
            params, toks, jnp.asarray([whole]))
        page_ids, offsets = batched_sequence_page_coords(
            bt, jnp.asarray([whole]), bucket, PAGE)
        kp, vp = batched_scatter_sequence(kp, vp, k_all, v_all, page_ids, offsets)
        wire.append((0, np.asarray(routes[0, :whole]), (), ()))
    state = prompt[whole:] + [MASK] * (R - len(prompt) + whole)
    for b in range(n_blocks):
        start = whole + b * R
        while True:
            logits, k_new, v_new, routes = _jitted(family, cfg)[1](
                params, jnp.asarray([state], jnp.int32),
                jnp.asarray([start], jnp.int32), kp, vp, bt)
            if MASK not in state:
                break
            filled, commit, _ = family.block_commit(
                cfg, logits, jnp.asarray([state], jnp.int32))
            rows = np.flatnonzero(np.asarray(commit[0])).tolist()
            for r in rows:
                state[r] = int(filled[0, r])
                logits_at[start + r] = np.asarray(logits[0, r])
            wire.append(
                (start, np.asarray(routes[0]), rows, [state[r] for r in rows]))
        wire.append((start, np.asarray(routes[0]), (), ()))
        if fault != "commit_kv":
            pos = start + np.arange(R)
            kp, vp = batched_scatter_sequence(
                kp, vp, k_new, v_new,
                jnp.asarray(bt[0, pos // PAGE])[None], jnp.asarray(pos % PAGE)[None])
        tokens_out += state
        state = [MASK] * R
    return tokens_out[len(prompt) - whole:], wire, logits_at


def packed(forwards, a_block=3):
    """The forwards on the wire: packed blocks of `a_block` forwards, as the
    chunks of a stream carry them."""
    forwards = list(forwards or ())
    return [encode_forwards(forwards[at:at + a_block])
            for at in range(0, len(forwards), a_block)]


def against_reference(prompt, served_tokens, wire, logits_at):
    """The reference replays the hand-over; the widest distance between its
    logits and the program's at the rows that chose the served tokens."""
    (got,) = reference.replay(
        HF, KEY, [{"prompt": prompt, "served": served_tokens,
                   "handover": {"kubeai_forwards": packed(wire)}}],
        state=None, pad_to=64, rows_pad=32)
    assert got is not None, "the hand-over breaks the family's rule"
    ours = np.stack([logits_at[len(prompt) + i] for i in range(len(served_tokens))])
    ours[:, MASK] = -np.inf
    keep = np.isfinite(got["logits"])  # the mask id's column is -inf
    return float(np.abs(np.where(keep, got["logits"] - np.where(keep, ours, 0), 0)).max()), got


# Seeded weights of std 0.02 at width 64 give logits up to 0.64. The sound
# program (bf16 weights and activations against the float32 reference) reads
# at most 5.2e-3 over the four prompts below; the weakest control (the expert
# most rows take, left out) reads 3.8e-2, the float8 control 5.6e-2.
LOGIT_TOL = 1e-2
PROMPTS = [list(range(3, 13)), list(range(40, 47)), [9, 8, 7], list(range(100, 116))]


@pytest.mark.parametrize("prompt", PROMPTS, ids=lambda p: f"P{len(p)}")
def test_prefill_then_blocks_match_the_reference_at_every_committing_forward(
        family, served, prompt):
    """P = 10, 7, 3, 16: left-over prompt tokens open the first block, a
    prompt shorter than a block has no forward of its own, a prompt of whole
    blocks starts on a block of masks."""
    cfg, params = served
    tokens, wire, logits_at = serve(family, cfg, params, prompt)
    n = 4 * R - len(prompt) % R
    assert len(tokens) == n and MASK not in tokens
    worst, got = against_reference(prompt, tokens, wire, logits_at)
    assert worst < LOGIT_TOL, worst
    # Decisions taken in bf16 that float32 would not take are few at this
    # size, and trail by rounding.
    assert got["routes"]["differs"].mean() < 0.05
    assert got["routes"]["trail"].max() < 5e-3
    assert got["order"]["trail"].max() < 1e-4
    # The reference's own first choice is the served token almost everywhere.
    first = got["logits"].argmax(-1)
    assert (first == np.asarray(tokens)).mean() > 0.8


@pytest.mark.parametrize("fault", ["qk_norm", "block_mask", "expert", "commit_kv"])
def test_one_part_of_the_mathematics_left_out_lands_over_the_tolerance(
        family, served, fault):
    """QK-norm off reads 0.36; the prompt under the causal mask 0.15; a
    finished block's K and V not written 0.43; the output of the expert that
    most rows take zeroed 0.038: all over LOGIT_TOL."""
    cfg, params = served
    prompt = PROMPTS[0]
    if fault == "expert":
        _, wire, _ = serve(family, cfg, params, prompt)
        taken = np.concatenate([f[1].reshape(-1) for f in wire])
        fault = ("expert", int(np.bincount(taken).argmax()))
    tokens, wire, logits_at = serve(family, cfg, params, prompt, fault=fault)
    worst, _ = against_reference(prompt, tokens, wire, logits_at)
    assert worst > 1.5 * LOGIT_TOL, (fault, worst)


def test_the_float8_control_lands_over_the_tolerance(family, served):
    """The reference itself in float8 (every matmul operand rounded), taking
    its own decisions, against the float32 reference that follows them."""
    cfg, params = served
    prompt = PROMPTS[0]
    tokens, wire, _ = serve(family, cfg, params, prompt)
    request = [{"prompt": prompt, "served": tokens,
                "handover": {"kubeai_forwards": packed(wire)}}]
    (low,) = reference.replay(HF, KEY, request, state=None, quant="fp8",
                              follow="own", pad_to=64, rows_pad=32)
    (full,) = reference.replay(HF, KEY, request, state=None,
                               follow=[low["own"]], pad_to=64, rows_pad=32)
    keep = np.isfinite(full["logits"])  # the mask id's column is -inf in both
    assert np.abs(low["logits"][keep] - full["logits"][keep]).max() > 1.5 * LOGIT_TOL


def test_a_hand_over_that_breaks_the_rule_is_not_replayed(family, served):
    cfg, params = served
    prompt = PROMPTS[0]
    tokens, wire, _ = serve(family, cfg, params, prompt, 2)
    ask = lambda blocks, toks=tokens: reference.replay(  # noqa: E731
        HF, KEY, [{"prompt": prompt, "served": toks,
                   "handover": {"kubeai_forwards": packed(blocks)}}],
        state=None, pad_to=64, rows_pad=32)[0]
    assert ask(wire) is not None
    assert ask(wire[:-1]) is None  # the last block's closing forward missing
    assert ask(wire[1:]) is None  # the prompt's forward missing
    assert ask(wire[:2] + wire[1:]) is None  # a forward twice
    assert ask(wire + wire[-1:]) is None  # one left over
    assert ask([]) is None and ask(None) is None
    other = list(wire)
    other[1] = (*wire[1][:3], [MASK])
    assert ask(other) is None  # committed to the mask
    cut = packed(wire)
    cut[0] = {**cut[0], "data": cut[0]["data"][:-8]}
    (got,) = reference.replay(
        HF, KEY, [{"prompt": prompt, "served": tokens,
                   "handover": {"kubeai_forwards": cut}}],
        state=None, pad_to=64, rows_pad=32)
    assert got is None  # bytes that are not what the header says
    # Another token served than the forward chose is replayed, and is a gap.
    got = ask(wire, tokens[:-1] + [(tokens[-1] + 1) % 500])
    assert got["logits"][-1].max() - got["logits"][-1][(tokens[-1] + 1) % 500] > 0.05


# ---- the expert layer, sparse against dense ------------------------------------


def dense_moe(x, lp, topi, probs):
    """Every expert on every row, weighted by the routing (0 where a row did
    not take the expert): the sum the sparse layer has to equal."""
    out = jnp.zeros_like(x)
    for e in range(lp["w_gate"].shape[0]):
        y = (jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])) @ lp["w_down"][e]
        out = out + y * jnp.sum(jnp.where(topi == e, probs, 0.0), -1)[:, None]
    return out


@pytest.mark.parametrize("routing", ["even", "uneven", "one_expert_idle"])
def test_the_sparse_expert_layer_equals_the_dense_sum(routing):
    """float32, so the tolerance is the order of summation's: 2e-6 read,
    1e-5 allowed; dropping one assignment reads 1e-2."""
    cfg = dataclasses.replace(mixtral.MixtralConfig.tiny_sdar(), dtype=jnp.float32)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(1))
    layers = {k: v.astype(jnp.float32) * 8.0 for k, v in params["layers"].items()}
    rng = np.random.default_rng(2)
    n, X, k = 37, cfg.num_experts, cfg.num_experts_per_tok
    x = jnp.asarray(rng.standard_normal((n, cfg.hidden_size)), jnp.float32)
    if routing == "even":
        topi = np.stack([np.arange(n) % X, (np.arange(n) + 3) % X], 1)
    elif routing == "uneven":  # expert 0 takes every row, 5 the second of most
        topi = np.stack([np.zeros(n, int), np.where(np.arange(n) % 7, 5, 2)], 1)
    else:  # experts 1 and 6 get no row
        topi = np.stack([np.arange(n) % 3 * 2, np.arange(n) % 2 * 2 + 3], 1)
        assert not np.isin([1, 6], topi).any()
    probs = jnp.asarray(rng.dirichlet(np.ones(k), n), jnp.float32)
    topi = jnp.asarray(topi, jnp.int32)
    experts = {name: layers[name] for name in experts_ops.EXPERT_LEAVES}
    for layer in range(cfg.num_layers):
        got = experts_ops.moe_sparse(x, experts, jnp.int32(layer), topi, probs)
        lp = {name: w[layer] for name, w in experts.items()}
        want = dense_moe(x, lp, topi, probs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
        dropped = dense_moe(x, lp, topi.at[0, 1].set(topi[0, 0]), probs)
        assert float(jnp.abs(dropped - want).max()) > 1e-3


def sorted_moe(x, experts, layer, topi, probs, first=None):
    """The expert layer as it kept its books until PR 48, the reference of
    the test below: the assignments sorted, the rows gathered, `bincount`,
    the inverse permutation scattered, `out[back]` summed under the weights.
    Returns (xs, counts, back, out, y)."""
    from kubeai_tpu.ops.grouped_matmul import grouped_matmul

    N, k = topi.shape
    NL, X = experts["w_gate"].shape[:2]
    flat = topi.reshape(-1)
    if first is not None:
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < X), flat, X)
    order = jnp.argsort(flat)
    xs = x[order // k]
    counts = jnp.bincount(flat, length=X).astype(jnp.int32)
    stacked = {n: w.reshape(NL * X, *w.shape[2:]) for n, w in experts.items()}
    product = functools.partial(grouped_matmul, sizes=counts, layer=layer)
    g, u = product(xs, stacked["w_gate"]), product(xs, stacked["w_up"])
    out = product(jax.nn.silu(g) * u, stacked["w_down"])
    out = jnp.where((flat[order] < X)[:, None], out, 0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(N * k))
    y = jnp.einsum(
        "nke,nk->ne", out[back].reshape(N, k, -1), probs.astype(out.dtype),
        preferred_element_type=jnp.float32)
    return xs, counts, back, out, y


# rows, k, experts held, experts the router scores, `first`, how rows are
# routed. The first three are the cells' decode and block shapes; the last is
# over `RANK_BY_COMPARISON_MAX`, where the assignments are still sorted.
BOOKS = {
    "qwen3-next-640": (64, 10, 64, 512, 0, "top_k"),
    "sdar-1024": (128, 8, 128, 128, None, "top_k"),
    "k-exaone-512": (64, 8, 16, 128, 0, "top_k"),
    "every-row-to-one-expert": (24, 4, 8, 8, None, "one"),
    "no-assignment-held": (24, 4, 8, 32, 24, "none_held"),
    "a-share-in-the-middle": (40, 6, 16, 64, 32, "top_k"),
    "over-the-crossover": (192, 8, 16, 32, 16, "top_k"),
}


@pytest.mark.parametrize("mode", ["reference", "interpret"])
@pytest.mark.parametrize("case", list(BOOKS))
def test_the_books_kept_by_comparison_are_the_sorted_books(monkeypatch, mode, case):
    """`dispatch` and `combine` against the sort, gather and scatter they
    replace: `dest` is the inverse of the stable `argsort`, `counts` is
    `bincount`, the rows handed to the products are bit for bit the gathered
    ones, the combined rows are the same products summed in another order
    (one float32 rounding of the terms' size) and the layer's output is the
    same bf16 but where that rounding straddles a bf16 step."""
    N, k, X, R, first, routing = BOOKS[case]
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", mode == "interpret")
    assert dispatch.kernel_mode() == mode
    assert (N * k > experts_ops.RANK_BY_COMPARISON_MAX) == (case == "over-the-crossover")
    E, M, NL = 128, 64, 2
    rng = np.random.default_rng(N * k)
    x = jnp.asarray(rng.standard_normal((N, E)), jnp.bfloat16)
    scores = rng.standard_normal((N, R)).astype(np.float32)
    topi = np.argsort(-scores, -1)[:, :k]
    if routing == "one":
        topi = np.full((N, k), 5)
    elif routing == "none_held":
        topi = topi % first  # every id under the share's first
    probs = jnp.asarray(rng.dirichlet(np.ones(k), N), jnp.float32)
    topi = jnp.asarray(topi, jnp.int32)
    experts = {
        name: jnp.asarray(rng.standard_normal((NL, X, *shape)) * 0.2, jnp.bfloat16)
        for name, shape in (("w_gate", (E, M)), ("w_up", (E, M)), ("w_down", (M, E)))}
    layer = jnp.int32(1)
    xs_s, counts_s, back, out, y_s = sorted_moe(x, experts, layer, topi, probs, first)

    flat = topi.reshape(-1)
    if first is not None:
        flat = jnp.where((flat >= first) & (flat < first + X), flat - first, X)
    xs, counts, dest = experts_ops.dispatch(x, flat, k, X)
    np.testing.assert_array_equal(np.asarray(dest).reshape(-1), np.asarray(back))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_s))
    assert xs.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(xs, np.float32), np.asarray(xs_s, np.float32))
    if routing == "none_held":
        assert int(counts.sum()) == 0
    elif first is not None:
        assert 0 < int(counts.sum()) < N * k

    weights = probs.astype(out.dtype)
    y = experts_ops.combine(out, dest, weights)
    assert y.dtype == jnp.float32
    terms = jnp.abs(out[back].reshape(N, k, -1).astype(jnp.float32)
                    * weights.astype(jnp.float32)[:, :, None]).sum(1)
    assert np.all(np.abs(np.asarray(y) - np.asarray(y_s))
                  <= 2.0 ** -23 * np.asarray(terms))

    got = experts_ops.moe_sparse(x, experts, layer, topi, probs, first)
    want = y_s.astype(x.dtype)
    differ = np.asarray(got, np.float32) != np.asarray(want, np.float32)
    assert differ.mean() <= 1e-3, differ.mean()
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2.0 ** -7)
    if routing != "none_held":
        assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.05


# rows of x, groups of w (NL * X), the layer's X counts, the layer, k, n: what
# the product is called with. "stack": sizes over all of w's groups, as the
# function is called without a layer. "middle-layer": only the groups of
# layer 1 of 3 hold rows. "share": 21 rows lie behind the last group.
# "k-steps": two steps along k, the second over a remainder (948 of 1152
# columns, masked; in float32 five of 512, the last over 52). "even-k-steps",
# "even-n-passes": a width of 18 strips in two tiles of 9, along k (nothing
# to mask) and along n. At a narrow n the whole of k is one tile.
GROUPED = {
    "stack": (74, 24, [0] * 8 + [20, 0, 1, 9, 0, 30, 11, 3] + [0] * 8, None, 64, 96),
    "middle-layer": (74, 24, [20, 0, 1, 9, 0, 30, 11, 3], 1, 64, 96),
    "share": (95, 24, [20, 0, 1, 9, 0, 30, 11, 3], 2, 64, 96),
    "two-row-tiles": (300, 12, [0, 130, 126, 44], 1, 64, 96),
    "share-nothing-held": (40, 8, [0, 0, 0, 0], 1, 64, 96),
    "k-steps": (150, 6, [100, 0, 30], 1, 2100, 2048),
    "even-k-steps": (150, 6, [100, 0, 30], 1, 2304, 2048),
    "even-n-passes": (150, 6, [100, 0, 30], 1, 64, 2304),
}


@pytest.mark.parametrize("case", list(GROUPED))
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.0)],
                         ids=["f32", "bf16"])
def test_the_grouped_matmul_kernel_equals_the_grouped_product(
        interpreted, dtype, tol, case):
    """The Pallas grouped matmul (interpreted) against `jax.lax.ragged_dot`
    within the tolerance, and BIT FOR BIT against the installed library's
    `gmm` (interpreted) over every group of the stack: rows that are no
    multiple of the 128-row tile, empty groups, one layer of a stack told by
    its number. With one k step bf16 is exact over a few columns (over
    2,304 a dozen sums round the other way); with two, the sum is taken in
    another order than the plain product's."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm as library_gmm

    from kubeai_tpu.ops.grouped_matmul import (
        TILE_ROWS, grouped_matmul, tile_plan, weight_tile)

    rows, G, counts, layer, k, n = GROUPED[case]
    if k > 64 or n > 96:
        tol = 1e-3 if dtype == jnp.float32 else 0.05
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    w = jnp.asarray(rng.standard_normal((G, k, n)) * 0.1, dtype)
    counts = jnp.asarray(counts, jnp.int32)
    X = counts.shape[0]
    held = int(counts.sum())
    whole = np.zeros(G, np.int32)  # the sizes the library is called with
    whole[(layer or 0) * X:][:X] = counts
    if layer is None:
        got = grouped_matmul(x, w, counts)
    else:
        got = grouped_matmul(
            x, w, counts, layer=jnp.int32(layer), plan=tile_plan(counts, rows))
    assert got.shape == (rows, n) and got.dtype == dtype
    want = jax.lax.ragged_dot(x, w, jnp.asarray(whole))
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held], np.float32),
        atol=tol)
    with jax.default_matmul_precision(
            "default" if dtype == jnp.bfloat16 else "highest"):
        library = library_gmm(
            jnp.pad(x, ((0, -rows % TILE_ROWS), (0, 0))), w, jnp.asarray(whole),
            preferred_element_type=dtype,
            tiling=(TILE_ROWS, *weight_tile(k, n, w.dtype.itemsize)),
            interpret=True)
    # Rows behind the last group are written by nobody, in either.
    np.testing.assert_array_equal(
        np.asarray(got[:held], np.float32), np.asarray(library[:held], np.float32))
    if held:
        moved = jax.lax.ragged_dot(x, w, jnp.asarray(np.roll(whole, 1)))
        gap = jnp.abs(moved.astype(jnp.float32) - want.astype(jnp.float32))
        assert float(gap[:held].max()) > 0.1


def test_a_sparse_family_refuses_a_forward_that_computes_experts_densely():
    cfg = mixtral.MixtralConfig.tiny_sdar()
    params = mixtral.init_params(cfg)
    lp = {k: v[0] for k, v in params["layers"].items()}
    with pytest.raises(ValueError, match="sparse"):
        mixtral._moe_ffn(jnp.zeros((1, 2, cfg.hidden_size), cfg.dtype), lp, cfg)


# ---- the widened attention kernel (interpreted) ---------------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    assert dispatch.kernel_mode() == "interpret"


def _block_inputs(rows, kvh, g, d, old_lengths, *, page=8, mp=4, dead=(), seed=0):
    """Random stacked pools with every page filled (what lies past a length
    or in nobody's page must not matter); slots in `dead` hold no page."""
    rng = np.random.default_rng(seed)
    b = len(old_lengths)
    n_pages = 1 + b * mp
    rand = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    bt = np.full((b, mp), -1, np.int32)
    for s, ln in enumerate(old_lengths):
        if s not in dead:
            n = min(-(-(ln + rows) // page), mp)
            bt[s, :n] = 1 + s * mp + np.arange(n)
    return (rand(b, rows, kvh * g, d), rand(2, n_pages, page, kvh, d),
            rand(2, n_pages, page, kvh, d), rand(b, rows, kvh, d),
            rand(b, rows, kvh, d), jnp.asarray(bt),
            jnp.asarray(old_lengths, jnp.int32))


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("kvh, g, d", [(4, 8, 128), (2, 2, 32)],
                         ids=["sdar", "tiny"])
def test_the_widened_kernel_matches_the_reference(interpreted, rows, kvh, g, d):
    """R = 1 is the one-token kernel (and equals the block reference at one
    row); R = 4 folds a block's rows into the query group. Slots at lengths
    that end inside a page, on a page's edge and at 0, and one that holds no
    page. float32 pools: 1e-4 is the interpreter's order of summation (2e-5
    read); a block that did not see its own later rows reads 0.2."""
    q, kp, vp, kn, vn, bt, pos = _block_inputs(
        rows, kvh, g, d, [5, 16, 0, 27, 9], dead=(4,), seed=rows + kvh)
    got = paged_block_attention_fused(q, kp, vp, kn, vn, bt, pos, 1)
    want = ref_paged_block_attention_fused(q, kp, vp, kn, vn, bt, pos, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    if rows == 1:
        one = ref_paged_decode_attention_fused(
            q[:, 0], kp, vp, kn[:, 0], vn[:, 0], bt, pos, jnp.int32(1))
        np.testing.assert_allclose(
            np.asarray(got[:, 0]), np.asarray(one), atol=1e-4, rtol=1e-4)
    else:
        causal = jnp.stack([
            ref_paged_block_attention_fused(
                q[:, r:r + 1], kp, vp, kn[:, :r + 1][:, -1:], vn[:, :r + 1][:, -1:],
                bt, pos, jnp.int32(1))[:, 0] for r in range(rows)], 1)
        assert float(jnp.abs(causal - want).max()) > 0.05


# ---- the block mask in prefill ---------------------------------------------------


def _dense_block_attention(q, k, v, block):
    s, d = q.shape[1], q.shape[-1]
    g = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(d)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = jnp.where((j // block <= i // block)[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vv)


@pytest.mark.parametrize("path", ["jnp", "flash"])
def test_prefill_attention_under_the_block_mask(monkeypatch, path):
    """Causal between blocks of 4, full inside one: the jnp path at an
    unaligned length, the flash kernel (interpreted) at 256. 2e-5 read in
    float32; the causal mask in its place reads 0.5."""
    rng = np.random.default_rng(5)
    s = 256 if path == "flash" else 44
    q = jnp.asarray(rng.standard_normal((2, s, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, 2, 32)), jnp.float32)
    if path == "flash":
        monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    got = prefill_attention(q, k, v, mask_block=4)
    want = _dense_block_attention(q, k, v, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)
    causal = prefill_attention(q, k, v)
    assert float(jnp.abs(causal - want).max()) > 0.05
    np.testing.assert_allclose(
        np.asarray(causal), np.asarray(causal_prefill_attention(q, k, v)),
        atol=2e-4, rtol=2e-4)


# ---- the engine and the server ----------------------------------------------------


def _engine(family, served, **kw):
    cfg, params = served
    kw = {"num_slots": 4, "max_seq_len": 128, "page_size": 16, **kw}
    return Engine(family, cfg, params, cfg=EngineConfig(**kw), eos_token_ids=())


def _run(eng, prompts, max_tokens, forwards=True):
    rids = [eng.add_request(p, SamplingParams(temperature=0.0, max_tokens=n),
                            forwards=forwards)
            for p, n in zip(prompts, max_tokens)]
    toks = {r: [] for r in rids}
    handed = {r: [] for r in rids}
    while eng.has_work():
        for ev in eng.step():
            toks[ev.rid].append(ev.token)
            if ev.forwards is not None:
                handed[ev.rid].extend(ev.forwards)
    return [(toks[r], handed[r]) for r in rids]


def test_the_engine_serves_what_the_chained_forwards_serve_and_replay_follows_it(
        family, served):
    """Four requests share the slots, the chunks and the admissions; each is
    served the tokens its own chain of forwards gives, cut at max_tokens, and
    its hand-over replays: a request's numbers do not depend on its
    neighbours."""
    cfg, params = served
    eng = _engine(family, served)
    max_tokens = [13, 9, 5, 8]
    out = _run(eng, PROMPTS, max_tokens)
    requests = []
    for prompt, n, (tokens, wire) in zip(PROMPTS, max_tokens, out):
        alone, wire_alone, _ = serve(family, cfg, params, prompt)
        assert tokens == alone[:n]
        n_forwards = sum(1 for f in wire_alone if f[0] < len(prompt) + n)
        assert [tuple(f[2]) for f in wire] == [
            tuple(f[2]) for f in wire_alone[:n_forwards]]
        requests.append({"prompt": prompt, "served": tokens,
                         "handover": {"kubeai_forwards": packed(wire)}})
    got = reference.replay(HF, KEY, requests, state=None, pad_to=64, rows_pad=32)
    assert all(g is not None for g in got)
    for g, (tokens, _) in zip(got, out):
        best = g["logits"].max(-1)
        served_logit = g["logits"][np.arange(len(tokens)), tokens]
        assert float((best - served_logit).max()) < LOGIT_TOL
    stats = eng.block_stats
    assert stats["requests"] == 4 and stats["forwards_sent"] == sum(
        len(w) for _, w in out)
    assert stats["tokens"] == stats["denoise"]  # one row a forward: no confidence near 0.9
    assert stats["commit"] * R >= sum(max_tokens)


def test_nobody_asks_and_nothing_is_handed_over(family, served):
    eng = _engine(family, served)
    asked = _run(_engine(family, served), PROMPTS[:2], [9, 9])
    quiet = _run(eng, PROMPTS[:2], [9, 9], forwards=False)
    assert [t for t, _ in quiet] == [t for t, _ in asked]
    assert all(w == [] for _, w in quiet)
    assert eng.block_stats["forwards_sent"] == 0 and eng.block_stats["denoise"] > 0


def test_a_sampled_request_commits_its_sampled_tokens_and_repeats_with_its_seed(
        family, served):
    eng = _engine(family, served)
    sampled = SamplingParams(temperature=0.8, top_k=20, seed=3, max_tokens=9)
    first = eng.generate([PROMPTS[1]], sampled)
    assert first == eng.generate([PROMPTS[1]], sampled)
    # What the block chunk says its sampler ran (PR 44): the candidate pool
    # while a sampled request lives, the argmax alone for a greedy one.
    drawn = dict(eng.sampler_chunks)
    assert drawn["pool"] >= 2 and drawn["argmax"] == 0
    assert first != eng.generate(
        [PROMPTS[1]], SamplingParams(temperature=0.0, max_tokens=9))
    assert eng.sampler_chunks["pool"] == drawn["pool"]
    assert eng.sampler_chunks["argmax"] >= 1
    assert len(first[0]) == 9 and MASK not in first[0]


def test_a_block_family_refuses_what_it_is_not_served_with(family, served):
    cfg, params = served
    for kw, word in (({"prefill_chunk": 32}, "prefill_chunk"),
                     ({"speculate": 2}, "speculate"),
                     ({"kv_dtype": "int8"}, "int8")):
        with pytest.raises(ValueError, match=word):
            _engine(family, served, **kw)
    eng = _engine(family, served)
    with pytest.raises(ValueError, match="kubeai_forwards"):
        eng.add_request([1, 2, 3], SamplingParams(max_tokens=2), routes=True)


def _post(srv, body):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, resp.read().decode()


def _get(srv, path):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request("GET", path)
    return conn.getresponse().read().decode()


def test_the_server_hands_the_forwards_over_on_the_stream_and_counts_them(
        family, served):
    srv = EngineServer(_engine(family, served), ByteTokenizer(), "tiny",
                       host="127.0.0.1", port=0)
    srv.start()
    try:
        state = json.loads(_get(srv, "/v1/state"))
        assert state["generation"] == {
            "block_length": 4, "denoising_steps": 4,
            "confidence_threshold": 0.9, "mask_token_id": 511}
        assert state["moe"]["routes"] is False  # rows are routed anew a forward
        body = {"prompt": "ten tokens", "max_tokens": 10, "temperature": 0,
                "stream": True}
        status, raw = _post(srv, {**body, "kubeai_forwards": True})
        assert status == 200
        chunks = [json.loads(line[6:]) for line in raw.splitlines()
                  if line.startswith("data: {")]
        assert all("kubeai_forwards" in c for c in chunks)
        wire = [b for c in chunks for b in c["kubeai_forwards"]]
        tokens = [t for c in chunks for t in c.get("token_ids", [])]
        assert len(tokens) == 10
        assert all(set(b) == {"forwards", "shape", "dtype", "data"} for b in wire)
        assert all(len(c["kubeai_forwards"]) <= 1 for c in chunks)  # one a chunk
        forwards = [f for b in wire for f in decode_forwards(b)]
        # The prompt's forward, then 4 + 1 or fewer a block over 3 blocks.
        assert forwards[0][0] == 0 and forwards[0][1].shape == (8, 2, 2)
        assert sum(len(f[2]) for f in forwards) == 12 - 10 % 4
        prompt = list(b"ten tokens")
        (got,) = reference.replay(
            HF, KEY, [{"prompt": prompt, "served": tokens,
                       "handover": {"kubeai_forwards": wire}}],
            state=state, pad_to=64, rows_pad=32)
        assert got is not None and got["logits"].shape == (10, 512)
        # The same request, not streamed; one that does not ask; one that
        # asks for routes, which this family does not hand over.
        status, raw = _post(srv, {**body, "stream": False, "kubeai_forwards": True})
        choice = json.loads(raw)["choices"][0]
        assert choice["token_ids"] == tokens
        again = [f for b in choice["kubeai_forwards"] for f in decode_forwards(b)]
        assert len(again) == len(forwards) and all(
            a[0] == f[0] and np.array_equal(a[1], f[1]) and a[2:] == f[2:]
            for a, f in zip(again, forwards))
        status, raw = _post(srv, body)
        assert status == 200 and "kubeai_forwards" not in raw
        status, raw = _post(srv, {**body, "kubeai_routes": True})
        assert status == 400 and "kubeai_forwards" in raw
        status, raw = _post(srv, {**body, "kubeai_forwards": "yes"})
        assert status == 400
        metrics = _get(srv, "/metrics")
        for line in ('kubeai_engine_block_forwards_total{kind="denoise"}',
                     'kubeai_engine_block_forwards_total{kind="commit"}',
                     "kubeai_engine_block_tokens_total",
                     "kubeai_engine_block_program_forwards_total",
                     "kubeai_engine_block_chunks_total",
                     "kubeai_engine_forwards_sent_total",
                     "kubeai_engine_forward_requests_total 2"):
            assert line in metrics, line
    finally:
        srv.stop()


def test_a_family_that_generates_a_token_a_forward_is_served_and_told_so():
    cfg = llama.LlamaConfig.tiny()
    eng = Engine("llama", cfg, llama.init_params(cfg),
                 cfg=EngineConfig(num_slots=2, max_seq_len=64, page_size=16))
    srv = EngineServer(eng, ByteTokenizer(), "tiny", host="127.0.0.1", port=0)
    srv.start()
    try:
        assert "generation" not in json.loads(_get(srv, "/v1/state"))
        status, raw = _post(srv, {"prompt": "hi", "max_tokens": 3, "temperature": 0,
                                  "kubeai_forwards": True})
        choice = json.loads(raw)["choices"][0]
        assert status == 200 and choice["kubeai_forwards"] is None
        assert len(choice["token_ids"]) == 3
    finally:
        srv.stop()
