"""The `kimi_linear` family (Kimi-Linear-48B-A3B's shape at a tiny size: three
KDA layers to one latent-attention layer, a leading dense layer, a
sigmoid-plus-bias router, 4 of 16 experts held as share 1 of 4, 3 a token), on
the CPU with seeded weights, against the benchmark's plain reference
`perf/reference/kimi_linear.py`, which imports nothing of the program.

Each tolerance stands between two readings, written beside it: the largest
the sound program gives and the smallest a planted fault or a lower precision
gives."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.paged_cache import PagedKVCache
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import kimi_linear as kl
from kubeai_tpu.models.registry import get_model_family
from kubeai_tpu.ops import dispatch, experts
from kubeai_tpu.ops import gated_delta as gd
from kubeai_tpu.ops import latent_attention as la
from kubeai_tpu.ops.experts import at
from kubeai_tpu.ops.paged_attention import batched_sequence_page_coords
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh
from perf.reference import kimi_linear as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "perf", "configs", "tiny-kimi-linear.json")) as f:
    HF = json.load(f)
KEY = jax.random.PRNGKey(50)
PAGE, SLOTS, SLOT, MAX_LEN = 16, 4, 2, 128
PROMPT, STEPS, BUCKET = 37, 13, 64
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (PROMPT + STEPS,), 0, 500))
RANK, SHARED = HF["kv_lora_rank"], HF["qk_rope_head_dim"]


@pytest.fixture(scope="module")
def family():
    return get_model_family("KimiLinearForCausalLM")


def served(dtype, hf=HF):
    """(config, the reference's seeded weights in the program's layout)."""
    cfg = dataclasses.replace(kl.KimiLinearConfig.from_hf_dict(hf), dtype=dtype)
    params = jax.jit(lambda k: reference.served_params(hf, k))(KEY)
    return cfg, jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16
                             else a, params)


def through_the_cache(cfg, params, fault=None, bucket=BUCKET):
    """Prefill PROMPT tokens padded into `bucket`, write the latent rows and
    the state into slot SLOT of fresh pools, then STEPS decode steps
    teacher-forced on TOKENS. Returns (logits [STEPS + 1, V] at positions
    PROMPT - 1 .., the expert sets [PROMPT + STEPS, routed layers, k] the
    program took, the state pools). `fault(step, pool, state) -> (pool,
    state)` plants one before a decode step."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :PROMPT] = TOKENS[:PROMPT]
    lengths = jnp.array([PROMPT])
    logits0, rows_all, none, rows, routes0 = jax.jit(
        lambda p, t, l: kl.prefill(p, cfg, t, l, routes=True, state=True)
    )(params, jnp.asarray(tokens), lengths)
    assert none is None and rows_all.shape == (cfg.page_layers, 1, bucket, cfg.latent_row)
    cache = PagedKVCache.create(
        cfg.page_layers, 1 + SLOTS * MAX_LEN // PAGE, PAGE, SLOTS, MAX_LEN,
        cfg.num_kv_heads, cfg.head_size, state=kl.recurrent_state(cfg),
        latent=kl.latent_pages(cfg))
    assert cache.v_pages is None and cache.k_pages.shape[2:] == (PAGE, cfg.latent_row)
    bt = np.full((SLOTS, MAX_LEN // PAGE), -1, np.int32)
    bt[SLOT, :4] = [5, 9, 3, 7]
    pid, off = batched_sequence_page_coords(
        jnp.asarray(bt[SLOT:SLOT + 1]), lengths, bucket, PAGE)
    pool = la.write_latent_rows(cache.k_pages, rows_all, pid, off)
    state = {n: p.at[:, SLOT].set(rows[n][:, 0].astype(p.dtype))
             for n, p in cache.state.items()}
    step = jax.jit(lambda p, t, pos, pool, bt, st: kl.decode_step_paged(
        p, cfg, t, pos, pool, None, bt, routes=True, state=st))
    got, routes = [np.asarray(logits0[0])], [np.asarray(routes0[0, :PROMPT])]
    for i in range(STEPS):
        t, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        t[SLOT], pos[SLOT] = TOKENS[PROMPT + i], PROMPT + i
        if fault is not None:
            pool, state = fault(i, pool, state)
        lg, pool, none, state, r = step(
            params, jnp.asarray(t), jnp.asarray(pos), pool, jnp.asarray(bt), state)
        assert none is None
        got.append(np.asarray(lg[SLOT]))
        routes.append(np.asarray(r[SLOT])[None])
    return np.stack(got), np.concatenate(routes).astype(np.int64), state


def against_the_reference(got, given, quant=None):
    """max |logit difference| to the reference's full forward over the same
    tokens, following the program's expert sets."""
    seq = [int(t) for t in TOKENS]
    rows = list(range(PROMPT - 1, PROMPT + STEPS))
    logits, own, trail = reference.forward(
        HF, KEY, [(seq, rows)], quant=quant, routes=[given], pad_to=64, rows_pad=16)
    return float(np.abs(np.asarray(logits[0]) - got).max()), own[0], trail[0]


# The logits' standard deviation is 0.16. In float32 the program, through
# its latent pool (prefill expanded, decode absorbed) and its state pools,
# reads 3.1e-7 off the reference's full forward; the planted faults below
# read 0.37 (the state zeroed before one decode step), 0.38 (the convolution
# tail shifted by one), 0.31 (a head's channels given their mean gate) and,
# the smallest, 2.7e-3 (the shared key dimensions dropped from the pool).
F32_TOL = 2e-5
# Served in bfloat16 (the configuration's precision) it reads 6.3e-3 off; the
# reference's own int8 forward (weights only) reads 3.5e-2 off the float32
# one, its float8 forward 5.4e-2.
BF16_TOL = 1.2e-2


@pytest.fixture(scope="module")
def sound():
    cfg, params = served(jnp.float32)
    return cfg, params, through_the_cache(cfg, params)


def test_prefill_then_decode_through_the_cache_is_the_references_full_forward(sound):
    _, _, (got, given, _) = sound
    gap, own, trail = against_the_reference(got, given)
    assert gap < F32_TOL
    # In float32 the program's expert sets are the reference's own.
    assert (np.sort(given, -1) == np.sort(own, -1)).all() and trail.max() == 0
    # All three global ids a row of each of the 7 routed layers (the leading
    # dense layer has no row), of a router 16 wide, held here (4-7) or not.
    assert given.shape == (PROMPT + STEPS, 7, 3) and given.max() > 7


def test_served_in_bfloat16_it_stays_under_what_float8_lands_over():
    cfg, params = served(jnp.bfloat16)
    got, given, _ = through_the_cache(cfg, params)
    gap, _, _ = against_the_reference(got, given)
    assert gap < BF16_TOL
    seq, rows = [int(t) for t in TOKENS], list(range(PROMPT - 1, PROMPT + STEPS))
    full = reference.forward(HF, KEY, [(seq, rows)], pad_to=64, rows_pad=16)[0]
    low = reference.forward(HF, KEY, [(seq, rows)], quant="fp8", pad_to=64,
                            rows_pad=16)[0]
    assert float(np.abs(np.asarray(full) - np.asarray(low)).max()) > BF16_TOL


@pytest.mark.parametrize("fault", ["state_zeroed", "tail_shifted", "shared_key_dropped"])
def test_a_planted_cache_fault_moves_the_logits_past_the_tolerance(sound, fault):
    cfg, params, _ = sound

    def plant(step, pool, state):
        if step != 4:
            return pool, state
        if fault == "state_zeroed":
            return pool, dict(
                state, recurrent=state["recurrent"].at[:, SLOT].set(0.0))
        if fault == "shared_key_dropped":
            # The 16 numbers of every resident row that all heads' keys share.
            return pool.at[..., RANK:RANK + SHARED].set(0.0), state
        # The convolution's last inputs, one position late.
        tail = state["conv"][:, SLOT].reshape(cfg.state_layers, 3, 3 * cfg.kda_dim)
        return pool, dict(state, conv=state["conv"].at[:, SLOT].set(
            jnp.roll(tail, 1, axis=1).reshape(cfg.state_layers, -1)))

    got, given, _ = through_the_cache(cfg, params, fault=plant)
    gap, _, _ = against_the_reference(got, given)
    assert gap > 30 * F32_TOL


def test_a_head_given_its_channels_mean_gate_is_seen(sound, monkeypatch):
    """What tells KDA from a delta rule gated a head: every channel of a head
    decaying by the head's mean log-decay, in prefill and decode alike."""
    cfg, params, _ = sound
    project = kl._kda_project

    def mean_gate(h, lp, cfg):
        u, g, beta, gate = project(h, lp, cfg)
        return u, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta, gate

    monkeypatch.setattr(kl, "_kda_project", mean_gate)
    got, given, _ = through_the_cache(cfg, params)
    gap, _, _ = against_the_reference(got, given)
    assert gap > 30 * F32_TOL


def test_a_prompt_padded_into_a_larger_bucket_leaves_the_state_of_the_unpadded_one(sound):
    cfg, params, (got, _, state) = sound
    wide, _, wide_state = through_the_cache(cfg, params, bucket=128)
    # Pad positions neither decay nor write; the tail is the last three REAL
    # inputs: 1e-6 is float32 rounding in another order of chunks.
    assert np.abs(wide - got).max() < 1e-5
    assert np.abs(np.asarray(wide_state["recurrent"][:, SLOT])
                  - np.asarray(state["recurrent"][:, SLOT])).max() < 1e-6
    assert np.array_equal(np.asarray(wide_state["conv"][:, SLOT]),
                          np.asarray(state["conv"][:, SLOT]))


# ---- the two new pieces of `ops/` alone ----------------------------------------


def _rule_inputs(length, H=4, DK=16, DV=8, strongest=40.0):
    ks = jax.random.split(jax.random.PRNGKey(length), 5)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(jax.random.normal(ks[0], (length, H, DK))) / np.sqrt(DK)
    k = l2(jax.random.normal(ks[1], (length, H, DK)))
    v = jax.random.normal(ks[2], (length, H, DV))
    # A channel's log-decay a position from -1e-4 (barely) to -40 (gone in
    # one): over a chunk of 64 a channel adds up to -200 and more, and
    # float32 ends at exp(88.7).
    g = -jnp.exp(jax.random.uniform(
        ks[3], (length, H, DK), minval=np.log(1e-4), maxval=np.log(strongest)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (length, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("length,padded", [(150, 192), (64, 64), (37, 64), (9, 16)])
def test_the_channel_scan_is_the_rule_position_by_position_at_gates_that_overflow(
        length, padded):
    q, k, v, g, beta = _rule_inputs(length)
    want_o, want_s = gd.gdn_positions(q, k, v, g, beta)

    def pad(x):  # a pad position: g = 0, beta = 0
        return jnp.pad(x, ((0, padded - length),) + ((0, 0),) * (x.ndim - 1))[None]

    o, s = gd.kda_chunk_scan(pad(q), pad(k), pad(v), pad(g), pad(beta))
    # Float32 in another order: 6e-6 read; the outputs reach 1.2.
    assert float(jnp.abs(o[0, :length] - want_o).max()) < 5e-5
    assert float(jnp.abs(s[0] - want_s).max()) < 5e-5
    if length >= 64:
        # exp(-G) of the first chunk's cumulative log-decay is no float32.
        G = np.cumsum(np.asarray(g[:64], np.float64), axis=0)
        with np.errstate(over="ignore"):
            assert G.min() < -150 and not np.isfinite(np.exp(np.float32(-G.min())))


def test_a_gate_a_head_is_the_broadcast_case_of_a_gate_a_channel():
    q, k, v, g, beta = _rule_inputs(64, strongest=0.7)
    a_head = g.mean(-1)
    want_o, want_s = gd.gdn_positions(q, k, v, a_head, beta)
    o, s = gd.kda_chunk_scan(
        q[None], k[None], v[None],
        jnp.broadcast_to(a_head[..., None], g.shape)[None], beta[None])
    assert float(jnp.abs(o[0] - want_o).max()) < 5e-6
    assert float(jnp.abs(s[0] - want_s).max()) < 5e-6
    # And the channels' own gates give another state: the mean is no stand-in.
    _, own = gd.gdn_positions(q, k, v, g, beta)
    assert float(jnp.abs(own - want_s).max()) > 1e-2


@pytest.mark.parametrize("a_channel", [False, True])
def test_the_pallas_update_interpreted_is_the_jnp_update(monkeypatch, a_channel):
    L, B, H, DK, DV = 3, 5, 4, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    state = jax.random.normal(ks[0], (L, B, H, DK, DV))
    q, k = (jax.random.normal(x, (B, H, DK)) for x in ks[1:3])
    v = jax.random.normal(ks[3], (B, H, DV))
    decay = jnp.exp(-jax.random.uniform(
        ks[4], (B, H, DK) if a_channel else (B, H), maxval=5.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    want_s, want_o = gd.ref_gdn_update(state, jnp.int32(1), q, k, v, decay, beta)
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    got_s, got_o = gd.gdn_update(state + 0, 1, q, k, v, decay, beta)
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5
    # The other layers' states are what they were.
    assert np.array_equal(np.asarray(got_s[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(got_s[2]), np.asarray(state[2]))
    if a_channel:
        # One decay a head (the channels' mean) is another update.
        mean_s, _ = gd.ref_gdn_update(
            state, jnp.int32(1), q, k, v, decay.mean(-1), beta)
        assert float(jnp.abs(mean_s - want_s).max()) > 1e-2


def _latent_case(dtype, seed=1, dead=(4,), past=None):
    """9 slots of 4 heads against rows of 32 + 16 in 128, pages of 32 rows.
    With G the pages the kernel attends as one block, the slots hold 0 pages
    (a new sequence), 1, G - 1, G (whole pages: the block is full to its last
    row), none (slot 4, freed between two live ones: its position still says
    5), G + 1, 2G + 1 whole, 2G + 1 with ONE row in the last, and one page
    with one row. The slots in `dead` have no block-table row; `past` is
    written to every pool row past its slot's length, pages nobody holds
    included."""
    H, W, page, NL = 4, 128, 32, 2
    G = la.block_pages(page, W, jnp.dtype(dtype).itemsize, 1 << 20)
    MP = 2 * G + 1
    assert la.block_pages(page, W, jnp.dtype(dtype).itemsize, MP) == G > 1
    pos = np.array([
        0, 17, (G - 1) * page - 3, G * page, 5, (G + 1) * page - 7,
        (2 * G + 1) * page, 2 * G * page + 1, 1], np.int32)
    B, P = len(pos), 1 + int(sum(-(-int(n) // page) for n in pos))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    live = jnp.arange(W) < RANK + SHARED  # pad lanes are zero

    def rows(key, shape):
        return (jax.random.normal(key, shape) * live).astype(dtype)

    q, pool, new = rows(ks[0], (B, H, W)), rows(ks[1], (NL, P, page, W)), rows(ks[2], (B, W))
    bt = np.full((B, MP), -1, np.int32)
    held = np.zeros((P, page), bool)  # rows that hold a slot's old token
    ids = iter(range(1, P))
    for b in range(B):
        for i in range(-(-int(pos[b]) // page)):
            page_id = next(ids)
            held[page_id, : int(pos[b]) - i * page] = True
            if b not in dead:
                bt[b, i] = page_id
    if past is not None:
        pool = jnp.where(jnp.asarray(held)[None, :, :, None], pool, jnp.asarray(past, dtype))
    return q, pool, new, jnp.asarray(bt), jnp.asarray(pos)


@pytest.mark.parametrize("past", [None, 1e30], ids=["", "past-the-length-1e30"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_the_latent_kernel_interpreted_is_the_jnp_attention(monkeypatch, dtype, tol, past):
    """Page counts on both sides of a block's edge; with `past`, a block that
    attended a row it should have masked reads 1e30 at once (the reference
    is given the pool as drawn: it must not matter what lies past a length)."""
    args = _latent_case(dtype, past=past)
    drawn = _latent_case(dtype)
    want = la.ref_latent_decode_attention(
        *drawn, jnp.int32(1), scale=0.2, rank=RANK).astype(jnp.float32)
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    got = la.latent_decode_attention(*args, 1, scale=0.2, rank=RANK)
    assert got.shape == (9, 4, RANK) and got.dtype == dtype
    # float32: sums in another order (6e-7 read, the outputs reach 2.9); in
    # bfloat16 both round their result to 8 bits of it.
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < tol
    # A slot with no page (new, or freed) attends its own new row alone.
    new = args[2].astype(jnp.float32)
    for b in (0, 4):
        np.testing.assert_array_equal(
            np.asarray(got[b].astype(jnp.float32)),
            np.broadcast_to(np.asarray(new[b, :RANK]), (4, RANK)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_live_slots_output_is_the_same_to_the_bit_beside_dead_slots(monkeypatch, dtype):
    """Slots 2 and 6 freed as well (their positions still say G - 1 and 2G +
    1 pages): the walk steps over them and the ring's entries fall to other
    blocks, and no live slot's output moves by a bit."""
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    full = la.latent_decode_attention(*_latent_case(dtype), 1, scale=0.2, rank=RANK)
    args = _latent_case(dtype, dead=(2, 4, 6))
    got = la.latent_decode_attention(*args, 1, scale=0.2, rank=RANK)
    for b in (0, 1, 3, 5, 7, 8):
        np.testing.assert_array_equal(np.asarray(got[b].astype(jnp.float32)),
                                      np.asarray(full[b].astype(jnp.float32)))
    for b in (2, 6):
        np.testing.assert_array_equal(
            np.asarray(got[b].astype(jnp.float32)),
            np.broadcast_to(np.asarray(args[2][b, :RANK].astype(jnp.float32)), (4, RANK)))
        assert float(jnp.abs(got[b].astype(jnp.float32) - full[b].astype(jnp.float32)).max()) > 1e-2


def test_the_absorbed_decode_is_the_expanded_attention():
    """One MLA layer of the tiny model in float32: queries folded through the
    key half of W_kvb against the latent rows, values unfolded afterwards,
    against keys and values expanded a head, at one query position."""
    cfg, params = served(jnp.float32)
    lp = at(params["layers"]["mla"], 1)
    T = 29
    h = jax.random.normal(jax.random.PRNGKey(4), (1, T, cfg.hidden_size))
    q, row = kl._mla_project(h, lp, cfg)
    c, kpe = row[..., :RANK], row[..., RANK:RANK + SHARED]
    k = jnp.concatenate([
        jnp.einsum("bsr,hdr->bshd", c, lp["w_kb"]),
        jnp.broadcast_to(kpe[:, :, None], (1, T, cfg.num_heads, SHARED))], -1)
    v = jnp.einsum("bsr,hrd->bshd", c, lp["w_vb"])
    scale = cfg.qk_head_dim ** -0.5
    probs = jax.nn.softmax(jnp.einsum("hd,shd->hs", q[0, -1], k[0]) * scale, -1)
    expanded = jnp.einsum("hs,shd->hd", probs, v[0])
    # Absorbed, through the pool: rows 0 .. T-2 resident, row T-1 the new one.
    pool = jnp.zeros((2, 4, 16, cfg.latent_row)).at[1, 1:3].set(
        jnp.pad(row[0, :-1], ((0, 32 - (T - 1)), (0, 0))).reshape(2, 16, -1))
    q_c = jnp.einsum("hd,hdr->hr", q[0, -1, :, :cfg.qk_nope_head_dim], lp["w_kb"])
    q_row = jnp.pad(jnp.concatenate([q_c, q[0, -1, :, cfg.qk_nope_head_dim:]], -1),
                    ((0, 0), (0, cfg.latent_row - RANK - SHARED)))
    o_c = la.ref_latent_decode_attention(
        q_row[None], pool, row[:, -1], jnp.array([[1, 2]]), jnp.array([T - 1]),
        jnp.int32(1), scale=scale, rank=RANK)
    absorbed = jnp.einsum("hr,hrd->hd", o_c[0], lp["w_vb"])
    # 2e-8 read; the attention's output reaches 0.01.
    assert float(jnp.abs(absorbed - expanded).max()) < 1e-6
    assert float(jnp.abs(expanded).max()) > 3e-3


def test_prefill_attention_takes_values_narrower_than_keys(monkeypatch):
    from kubeai_tpu.ops.attention import causal_prefill_attention, prefill_attention

    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k = (jax.random.normal(x, (1, 256, 2, 48)) for x in ks[:2])
    v = jax.random.normal(ks[2], (1, 256, 2, 16))
    want = causal_prefill_attention(q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, 32),)))[..., :16]
    assert prefill_attention(q, k, v).shape == (1, 256, 2, 16)
    np.testing.assert_array_equal(np.asarray(prefill_attention(q, k, v)), np.asarray(want))
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    flash = prefill_attention(q, k, v)
    assert flash.shape == (1, 256, 2, 16)
    assert float(jnp.abs(flash - want).max()) < 2e-5


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's share test: four chips of 4 experts each route over all
    16, each computes its own experts' part under the weights of the whole
    taken set, every one computes the shared expert alike; the four parts and
    the shared expert counted once are the uncut reference layer."""
    layer, rows = 5, 24
    x = jax.random.normal(jax.random.PRNGKey(11), (rows, HF["hidden_size"]))
    uncut = {**HF, "num_experts": 16, "router_num_experts": 16, "expert_share_index": 0}
    none = np.zeros((rows, 3), np.int32)
    want, own, _ = reference.experts_apply(
        uncut, KEY, layer, x, none, np.zeros(rows, bool), np.ones(rows, bool))
    w = reference._make_moe(reference._flat(HF), KEY, layer)
    h = reference.rms_norm(x, w["post_norm"], HF["rms_norm_eps"])
    total = jnp.zeros_like(x)
    for share in range(4):
        cfg, params = served(jnp.float32, hf={**HF, "expert_share_index": share})
        layers = params["layers"]
        routed, shared, topi = experts.moe_parts_sigmoid_bias(
            h, at(layers["moe"], layer - 1), layers["experts"], layer - 1, cfg)
        assert np.array_equal(np.sort(np.asarray(topi), -1), np.sort(own, -1))
        total = total + routed
    # Float32 sums in another order: 3e-8 read, the layer's output reaches 0.05.
    assert float(jnp.abs(x + total + shared - want).max()) < 1e-6
    # One share alone is NOT the layer: what the absent experts add is left out.
    assert float(jnp.abs(x + routed + shared - want).max()) > 1e-4


def test_the_published_depth_is_refused_for_its_tail_and_the_cut_is_whole_periods():
    published = {**HF, "num_hidden_layers": 27, "linear_attn_config": {
        **HF["linear_attn_config"], "full_attn_layers": [4, 8, 12, 16, 20, 24, 27]}}
    with pytest.raises(ValueError, match="whole periods of 3 KDA layers"):
        kl.KimiLinearConfig.from_hf_dict(published)
    cfg = kl.KimiLinearConfig.from_hf_dict({**published, "num_hidden_layers": 12})
    assert (cfg.periods, cfg.state_layers, cfg.page_layers, cfg.routed_layers) == (
        3, 9, 3, 11)
    for key, value in (("mla_use_nope", False), ("q_lora_rank", 1536)):
        with pytest.raises(ValueError, match=key):
            kl.KimiLinearConfig.from_hf_dict({**HF, key: value})


# ---- through the engine -------------------------------------------------------


def make_engine(**kw):
    cfg, params = served(jnp.float32)
    slots = kw.pop("num_slots", 2)
    return Engine("kimi_linear", cfg, params, cfg=EngineConfig(
        num_slots=slots, max_seq_len=MAX_LEN, page_size=PAGE, **kw))


PROMPTS = [[int(t) for t in TOKENS[:n]] for n in (40, 9, 21)]
GREEDY = SamplingParams(temperature=0.0, max_tokens=20)


@pytest.fixture(scope="module")
def fresh_streams():
    return [make_engine().generate([p], GREEDY)[0] for p in PROMPTS]


def test_the_engine_serves_what_the_reference_puts_first(fresh_streams):
    """Greedy serving in float32: every served token is the reference's
    first at its position, through admission, the decode chunk, the latent
    pool and the two kinds of state."""
    for prompt, out in zip(PROMPTS, fresh_streams):
        seq = prompt + out[:-1]
        rows = list(range(len(prompt) - 1, len(seq)))
        logits = np.asarray(reference.forward(
            HF, KEY, [(seq, rows)], pad_to=64, rows_pad=32)[0])
        assert logits.argmax(-1).tolist() == out


def test_a_slot_reused_after_a_longer_request_serves_what_a_fresh_engine_serves(
        fresh_streams):
    engine = make_engine(num_slots=1)
    got = [engine.generate([p], GREEDY)[0] for p in PROMPTS]
    assert got == fresh_streams
    assert engine.state_stats["admissions"] == 3


def test_a_request_preempted_and_recomputed_serves_the_same_stream():
    """Preemption by recompute needs no snapshot: the re-admission rebuilds
    the latent rows and the state from position 0."""
    prompts = [[int(t) for t in TOKENS[i:i + 20]] for i in (0, 7, 19)]
    sp = SamplingParams(temperature=0.0, max_tokens=40)
    want = make_engine(num_slots=4).generate(prompts, sp)
    tight = make_engine(num_slots=4, num_pages=1 + 9)
    preempted = []
    tight.on_preempt = lambda rid, client: preempted.append(rid)
    assert tight.generate(prompts, sp) == want
    assert preempted


def test_the_family_refuses_what_a_latent_pool_and_its_state_are_not_served_with(
        family, devices8):
    cfg, params = served(jnp.float32)
    kept = "kimi_linear keeps recurrent state and a latent pool beside its pages"

    def build(mesh=None, draft=None, **kw):
        return Engine(family, cfg, params, mesh=mesh, draft=draft, cfg=EngineConfig(
            num_slots=2, max_seq_len=MAX_LEN, page_size=PAGE, **kw))

    for name, kw in (
        ("prefix_cache", dict(prefix_cache=True, prefill_chunk=32)),
        ("prefill_chunk", dict(prefill_chunk=32)),
        ("speculate", dict(speculate=3)),
        ("speculate", dict(draft=(cfg, params))),
        ("kv_dtype int8", dict(kv_dtype="int8")),
        ("max_adapters", dict(max_adapters=2)),
        ("a pp mesh axis", dict(mesh=build_mesh(MeshConfig(pp=2), devices=devices8[:2]))),
        ("a tp mesh axis", dict(mesh=build_mesh(MeshConfig(tp=2), devices=devices8[:2]))),
    ):
        with pytest.raises(ValueError, match=f"{kept}.*{name}"):
            build(**kw)
    engine = build()
    for call in (
        lambda: engine.export_handoff([1, 2, 3]),
        lambda: engine.import_handoff(None),
        lambda: engine.export_prefix_pages([]),
        lambda: engine.import_prefix_pages(None),
        lambda: engine.enable_kv_spill(object()),
    ):
        with pytest.raises(ValueError, match=kept):
            call()
    for name, kw in (
        ("prefill role", dict(role="prefill")),
        ("decode role", dict(role="decode")),
        ("kv_sharing", dict(kv_sharing=True)),
        ("a KV spill store", dict(kv_spill_store=object())),
    ):
        with pytest.raises(ValueError, match=f"{kept}.*{name}"):
            EngineServer(engine, ByteTokenizer(), "tiny", port=0, **kw)


def test_a_latent_pool_alone_is_refused_by_the_same_table(family, monkeypatch):
    """The refusals are the pool kind's, not the state's: a family that said
    a latent pool and no recurrent state would be refused alike."""
    cfg, params = served(jnp.float32)
    engine = Engine(family, cfg, params, cfg=EngineConfig(
        num_slots=2, max_seq_len=MAX_LEN, page_size=PAGE))
    monkeypatch.setattr(type(engine), "_beside_pages", property(lambda self: None))
    assert engine._kept_apart == "a latent pool"
    with pytest.raises(ValueError, match="kimi_linear keeps a latent pool and is not"):
        engine.refuse_state_snapshot("a KV spill store")
    engine.cfg = dataclasses.replace(engine.cfg, prefill_chunk=32)
    with pytest.raises(ValueError, match="keeps a latent pool.*prefill_chunk"):
        engine._check_family_engine(None)


def test_the_pools_on_v1_state_and_the_counters():
    engine = make_engine()
    server = EngineServer(engine, ByteTokenizer(), "tiny", host="127.0.0.1", port=0)
    server.start()
    try:
        import http.client

        def get(path):
            conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
            conn.request("GET", path)
            body = conn.getresponse().read().decode()
            conn.close()
            return body

        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
        conn.request("POST", "/v1/completions", json.dumps({
            "model": "tiny", "prompt": "hello latent hybrid", "max_tokens": 9,
            "temperature": 0, "kubeai_routes": True}),
            {"Content-Type": "application/json"})
        reply = json.loads(conn.getresponse().read())
        conn.close()
        block = reply["choices"][0]["kubeai_routes"][0]
        assert block["shape"] == [7, 3] and block["start"] == 0
        state = json.loads(get("/v1/state"))
        assert state["moe"] == {"experts": 16, "k": 3, "routed_layers": 7,
                                "routes": True, "held": [4, 8]}
        assert state["state"]["state_layers"] == 6
        assert state["state"]["page_layers"] == state["kv_cache"]["page_layers"] == 2
        # [4 heads, 16, 16] float32 and 3 x 192 channels of float32 (this
        # engine serves in float32), six layers of each.
        assert state["state"]["bytes_per_slot"] == {
            "recurrent": 6 * 4 * 16 * 16 * 4, "conv": 6 * 3 * 192 * 4}
        # One pool: 2 latent layers x (1 + 2 x 8) pages x 16 rows of 128.
        latent_bytes = 2 * 17 * 16 * 128 * 4
        (pool,) = state["kv_pools"]
        assert pool == {"kind": "latent", "layers": 2, "pages": 16,
                        "pages_per_slot": 8, "pages_used": 0,
                        "bytes": latent_bytes, "row": 128}
        assert state["kv_cache"]["pool_bytes"] == latent_bytes
        assert state["state"]["pool_bytes"]["latent"] == latent_bytes
        metrics = get("/metrics")

        def value(line_start):
            return float(next(l for l in metrics.splitlines()
                              if l.startswith(line_start)).rsplit(" ", 1)[1])

        assert value('kubeai_engine_state_pool_bytes{kind="recurrent"}') == (
            2 * 6 * 4 * 16 * 16 * 4)
        assert value('kubeai_engine_state_pool_bytes{kind="latent"}') == latent_bytes
        pages = value('kubeai_engine_decode_live_pages_total{pool="latent"}')
        # The blocks the decode kernel attended them in: G pages a block at
        # most, and a block holds one live page at least.
        blocks = value('kubeai_engine_decode_page_blocks_total{pool="latent"}')
        assert engine._latent_block == la.block_pages(16, 128, 4, 8) > 1
        assert 0 < pages / engine._latent_block <= blocks <= pages
        assert not any(l.startswith("kubeai_engine_decode_live_pages_total ")
                       for l in metrics.splitlines())
        assert value('kubeai_engine_kv_pool_pages{pool="latent",state="free"}') == 16
        assert value("kubeai_engine_state_admissions_total") == 1
        held = value('kubeai_engine_moe_assignments_total{held="true"}')
        absent = value('kubeai_engine_moe_assignments_total{held="false"}')
        rows = value('kubeai_engine_route_rows_total{kind="prefill"}') + value(
            'kubeai_engine_route_rows_total{kind="decode"}')
        assert held + absent == rows * 7 * 3 and 0 < held < absent
    finally:
        server.stop()
