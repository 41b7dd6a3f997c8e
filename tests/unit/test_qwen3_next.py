"""The `qwen3_next` family (Qwen3-Next-80B-A3B's shape at a tiny size: three
Gated DeltaNet layers to one gated attention layer, partial rotary, a shared
expert, 4 of 16 experts held as share 1 of 4, 3 a token), on the CPU with
seeded weights, against the benchmark's plain reference
`perf/reference/qwen3_next.py`, which imports nothing of the program.

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
from kubeai_tpu.models import qwen3_next as qn
from kubeai_tpu.models.registry import get_model_family
from kubeai_tpu.ops import dispatch
from kubeai_tpu.ops import gated_delta as gd
from kubeai_tpu.ops.experts import at, route_dtype, stack_routes
from kubeai_tpu.ops.paged_attention import (
    batched_scatter_sequence,
    batched_sequence_page_coords,
)
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh
from perf.reference import qwen3_next as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "perf", "configs", "tiny-qwen3-next.json")) as f:
    HF = json.load(f)
KEY = jax.random.PRNGKey(43)
PAGE, SLOTS, SLOT, MAX_LEN = 16, 4, 2, 128
PROMPT, STEPS, BUCKET = 37, 13, 64
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (PROMPT + STEPS,), 0, 500))


@pytest.fixture(scope="module")
def family():
    return get_model_family("Qwen3NextForCausalLM")


def served(dtype):
    """(config, the reference's seeded weights in the program's layout)."""
    cfg = dataclasses.replace(qn.Qwen3NextConfig.from_hf_dict(HF), dtype=dtype)
    params = jax.jit(lambda k: reference.served_params(HF, k))(KEY)
    return cfg, jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16
                             else a, params)


def through_the_cache(cfg, params, fault=None, bucket=BUCKET):
    """Prefill PROMPT tokens padded into `bucket`, write pages and state into
    slot SLOT of fresh pools, then STEPS decode steps teacher-forced on
    TOKENS. Returns (logits [STEPS + 1, V] at positions PROMPT - 1 ..,
    the expert sets [PROMPT + STEPS, layers, k] the program took).
    `fault(step, state) -> state` plants one before a decode step."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :PROMPT] = TOKENS[:PROMPT]
    lengths = jnp.array([PROMPT])
    logits0, k_all, v_all, rows, routes0 = jax.jit(
        lambda p, t, l: qn.prefill(p, cfg, t, l, routes=True, state=True)
    )(params, jnp.asarray(tokens), lengths)
    cache = PagedKVCache.create(
        cfg.page_layers, 1 + SLOTS * MAX_LEN // PAGE, PAGE, SLOTS, MAX_LEN,
        cfg.num_kv_heads, cfg.head_dim, dtype=cfg.dtype,
        state=qn.recurrent_state(cfg))
    bt = np.full((SLOTS, MAX_LEN // PAGE), -1, np.int32)
    bt[SLOT, :4] = [5, 9, 3, 7]
    pid, off = batched_sequence_page_coords(
        jnp.asarray(bt[SLOT:SLOT + 1]), lengths, bucket, PAGE)
    kp, vp = batched_scatter_sequence(
        cache.k_pages, cache.v_pages, k_all, v_all, pid, off)
    state = {n: pool.at[:, SLOT].set(rows[n][:, 0].astype(pool.dtype))
             for n, pool in cache.state.items()}
    step = jax.jit(lambda p, t, pos, kp, vp, bt, st: qn.decode_step_paged(
        p, cfg, t, pos, kp, vp, bt, routes=True, state=st))
    got, routes = [np.asarray(logits0[0])], [np.asarray(routes0[0, :PROMPT])]
    for i in range(STEPS):
        t, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        t[SLOT], pos[SLOT] = TOKENS[PROMPT + i], PROMPT + i
        if fault is not None:
            state = fault(i, state)
        lg, kp, vp, state, r = step(
            params, jnp.asarray(t), jnp.asarray(pos), kp, vp, jnp.asarray(bt), state)
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


# The logits' standard deviation is 0.16. In float32 the program, through its
# pages and its state pools, reads 2.4e-7 off the reference's full forward;
# a state zeroed before one decode step reads 0.23 off, a convolution tail
# shifted by one position 0.32.
F32_TOL = 2e-5
# Served in bfloat16 (the configuration's precision) it reads 5.8e-3 off; the
# reference's own float8 forward reads 4.1e-2 off the float32 one (its int8
# forward, weights only, 9.8e-3: at this size it does not separate).
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
    # All three global ids a row, of a router 16 wide, held here (4-7) or not.
    assert given.shape == (PROMPT + STEPS, 8, 3) and given.max() > 7


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


@pytest.mark.parametrize("fault", ["state_zeroed", "tail_shifted"])
def test_a_planted_state_fault_moves_the_logits_past_the_tolerance(sound, fault):
    cfg, params, _ = sound

    def plant(step, state):
        if step != 4:
            return state
        if fault == "state_zeroed":
            return dict(state, recurrent=state["recurrent"].at[:, SLOT].set(0.0))
        # The convolution's last inputs, one position late.
        tail = state["conv"][:, SLOT].reshape(cfg.state_layers, 3, cfg.conv_dim)
        return dict(state, conv=state["conv"].at[:, SLOT].set(
            jnp.roll(tail, 1, axis=1).reshape(cfg.state_layers, -1)))

    got, given, _ = through_the_cache(cfg, params, fault=plant)
    gap, _, _ = against_the_reference(got, given)
    assert gap > 50 * F32_TOL


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


def window_step(conv, li, u, w):
    """A position of the convolution as `decode_step_paged` took it until PR
    48: the row as a `[B, K, C]` window, summed over the taps' axis."""
    B, C = u.shape
    K = w.shape[0]
    tail = jax.lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
    window = jnp.concatenate(
        [tail.reshape(B, K - 1, C), u[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1)
    return y, jax.lax.dynamic_update_index_in_dim(
        conv, window[:, 1:].reshape(B, -1), li, 0)


def test_the_decode_convolution_reads_its_taps_where_they_lie():
    """`_conv_step` against the three lines it replaces, over K - 1
    consecutive positions from an admission's row (after which no input of
    the prompt is left in it): the new row bit for bit, the sum within one
    float32 rounding of its terms (the same four products, in tap order)."""
    cfg, params = served(jnp.bfloat16)
    K, C, NS = cfg.linear_conv_kernel_dim, cfg.conv_dim, cfg.state_layers
    tokens = np.zeros((1, BUCKET), np.int32)
    tokens[0, :PROMPT] = TOKENS[:PROMPT]
    rows = jax.jit(lambda p, t, l: qn.prefill(p, cfg, t, l, state=True)[3])(
        params, jnp.asarray(tokens), jnp.array([PROMPT]))["conv"]
    assert rows.shape == (NS, 1, (K - 1) * C)
    rng = np.random.default_rng(48)
    pool = jnp.asarray(rng.standard_normal((NS, SLOTS, (K - 1) * C)), cfg.dtype)
    pool = pool.at[:, SLOT].set(rows[:, 0].astype(cfg.dtype))
    new, old = jax.jit(qn._conv_step), jax.jit(window_step)
    w = params["layers"]["gdn"]["conv_w"]
    assert w.shape == (NS, K, C)
    theirs = pool
    for step in range(K - 1):
        for li in range(NS):
            u = jnp.asarray(rng.standard_normal((SLOTS, C)), jnp.float32)
            y, pool = new(pool, jnp.int32(li), u, w[li])
            want, theirs = old(theirs, jnp.int32(li), u, w[li])
            np.testing.assert_array_equal(
                np.asarray(pool, np.float32), np.asarray(theirs, np.float32))
            terms = np.abs(np.asarray(w[li], np.float32)).max() * (
                np.abs(np.asarray(theirs[li], np.float32)).max() + 1) * K
            assert y.dtype == jnp.float32 and y.shape == (SLOTS, C)
            assert np.abs(np.asarray(y) - np.asarray(want)).max() <= 2.0 ** -23 * terms
            assert np.abs(np.asarray(want)).max() > 1e-3
    # The newest input is the row's last.
    np.testing.assert_array_equal(
        np.asarray(pool[NS - 1, :, -C:], np.float32),
        np.asarray(u.astype(cfg.dtype), np.float32))


@pytest.mark.parametrize("length,padded", [(150, 192), (64, 64), (37, 64), (9, 16)])
def test_the_chunked_scan_is_the_rule_position_by_position(length, padded):
    H, DK, DV = 4, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(length), 5)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(jax.random.normal(ks[0], (length, H, DK))) / np.sqrt(DK)
    k = l2(jax.random.normal(ks[1], (length, H, DK)))
    v = jax.random.normal(ks[2], (length, H, DV))
    g = -jax.random.uniform(ks[3], (length, H), minval=0.001, maxval=0.7)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (length, H)))
    want_o, want_s = gd.gdn_positions(q, k, v, g, beta)

    def pad(x):  # a pad position: g = 0, beta = 0
        return jnp.pad(x, ((0, padded - length),) + ((0, 0),) * (x.ndim - 1))[None]

    o, s = gd.gdn_chunk_scan(pad(q), pad(k), pad(v), pad(g), pad(beta))
    # Float32 in another order: 3e-7 read; the state's entries reach 0.8.
    assert float(jnp.abs(o[0, :length] - want_o).max()) < 5e-6
    assert float(jnp.abs(s[0] - want_s).max()) < 5e-6


def test_the_pallas_update_interpreted_is_the_jnp_update(monkeypatch):
    L, B, H, DK, DV = 3, 5, 4, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    state = jax.random.normal(ks[0], (L, B, H, DK, DV))
    q, k = (jax.random.normal(x, (B, H, DK)) for x in ks[1:3])
    v = jax.random.normal(ks[3], (B, H, DV))
    decay = jnp.exp(-jax.random.uniform(ks[4], (B, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    want_s, want_o = gd.ref_gdn_update(state, jnp.int32(1), q, k, v, decay, beta)
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    got_s, got_o = gd.gdn_update(state + 0, 1, q, k, v, decay, beta)
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5
    # The other layers' states are what they were.
    assert np.array_equal(np.asarray(got_s[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(got_s[2]), np.asarray(state[2]))


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
    h = reference.norm0(x, w["post_norm"], HF["rms_norm_eps"])
    total = jnp.zeros_like(x)
    for share in range(4):
        hf = {**HF, "expert_share_index": share}
        cfg = dataclasses.replace(qn.Qwen3NextConfig.from_hf_dict(hf), dtype=jnp.float32)
        params = jax.jit(lambda k, hf=hf: reference.served_params(hf, k))(KEY)
        layers = jax.tree.map(lambda a: a.astype(jnp.float32), params["layers"])
        routed, shared, topi = qn._moe_parts(
            h, at(layers["moe"], layer), layers["experts"], layer, cfg)
        assert np.array_equal(np.sort(np.asarray(topi), -1), np.sort(own, -1))
        total = total + routed
    # Float32 sums in another order: 2e-8 read, the layer's output reaches 0.02.
    assert float(jnp.abs(x + total + shared - want).max()) < 1e-6
    # One share alone is NOT the layer: what the absent experts add is left out.
    assert float(jnp.abs(x + routed + shared - want).max()) > 1e-4


def test_route_ids_past_255_are_handed_over_in_sixteen_bits():
    assert route_dtype(512) == "uint16" and route_dtype(256) == "uint8"
    cfg = dataclasses.replace(qn.Qwen3NextConfig.tiny(), router_experts=512,
                              num_experts=64, expert_share_index=7)
    assert cfg.first_expert == 448
    routes = stack_routes(jnp.full((8, 5, 3), 511), cfg.router_experts)
    assert routes.dtype == jnp.uint16 and routes.shape == (5, 8, 3)
    assert int(routes.max()) == 511


# ---- through the engine -------------------------------------------------------


def make_engine(**kw):
    cfg, params = served(jnp.float32)
    slots = kw.pop("num_slots", 2)
    return Engine("qwen3_next", cfg, params, cfg=EngineConfig(
        num_slots=slots, max_seq_len=MAX_LEN, page_size=PAGE, **kw))


PROMPTS = [[int(t) for t in TOKENS[:n]] for n in (40, 9, 21)]
GREEDY = SamplingParams(temperature=0.0, max_tokens=20)


@pytest.fixture(scope="module")
def fresh_streams():
    return [make_engine().generate([p], GREEDY)[0] for p in PROMPTS]


def test_the_engine_serves_what_the_reference_puts_first(fresh_streams):
    """Greedy serving in float32: every served token is the reference's
    first at its position, through admission, the decode chunk and the two
    kinds of state."""
    for prompt, out in zip(PROMPTS, fresh_streams):
        seq = prompt + out[:-1]
        rows = list(range(len(prompt) - 1, len(seq)))
        logits = np.asarray(reference.forward(
            HF, KEY, [(seq, rows)], pad_to=64, rows_pad=32)[0])
        assert logits.argmax(-1).tolist() == out


def test_a_slot_reused_after_a_longer_request_serves_what_a_fresh_engine_serves(
        fresh_streams):
    """An admission overwrites the slot's state whole: nothing of the longer
    request that held the slot before is added to."""
    engine = make_engine(num_slots=1)
    got = [engine.generate([p], GREEDY)[0] for p in PROMPTS]
    assert got == fresh_streams
    assert engine.state_stats["admissions"] == 3


def test_a_request_preempted_and_recomputed_serves_the_same_stream():
    """Preemption by recompute needs no snapshot: the re-admission rebuilds
    the state from position 0 over the prompt and what was served."""
    prompts = [[int(t) for t in TOKENS[i:i + 20]] for i in (0, 7, 19)]
    sp = SamplingParams(temperature=0.0, max_tokens=40)
    want = make_engine(num_slots=4).generate(prompts, sp)
    tight = make_engine(num_slots=4, num_pages=1 + 9)
    preempted = []
    tight.on_preempt = lambda rid, client: preempted.append(rid)
    assert tight.generate(prompts, sp) == want
    assert preempted


def test_the_family_refuses_what_needs_a_snapshot_of_state(family, devices8):
    cfg, params = served(jnp.float32)

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
        with pytest.raises(ValueError, match=f"qwen3_next keeps recurrent state.*{name}"):
            build(**kw)
    engine = build()
    for call in (
        lambda: engine.export_handoff([1, 2, 3]),
        lambda: engine.import_handoff(None),
        lambda: engine.export_prefix_pages([]),
        lambda: engine.import_prefix_pages(None),
        lambda: engine.enable_kv_spill(object()),
    ):
        with pytest.raises(ValueError, match="qwen3_next keeps recurrent state"):
            call()
    for name, kw in (
        ("prefill role", dict(role="prefill")),
        ("decode role", dict(role="decode")),
        ("kv_sharing", dict(kv_sharing=True)),
        ("a KV spill store", dict(kv_spill_store=object())),
    ):
        with pytest.raises(ValueError, match=f"qwen3_next keeps recurrent state.*{name}"):
            EngineServer(engine, ByteTokenizer(), "tiny", port=0, **kw)


def test_state_and_share_on_v1_state_and_the_counters():
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
            "model": "tiny", "prompt": "hello hybrid", "max_tokens": 9,
            "temperature": 0, "kubeai_routes": True}),
            {"Content-Type": "application/json"})
        reply = json.loads(conn.getresponse().read())
        conn.close()
        block = reply["choices"][0]["kubeai_routes"][0]
        assert block["shape"] == [8, 3] and block["start"] == 0
        state = json.loads(get("/v1/state"))
        assert state["moe"] == {"experts": 16, "k": 3, "routed_layers": 8,
                                "routes": True, "held": [4, 8]}
        assert state["state"]["state_layers"] == 6
        assert state["state"]["page_layers"] == state["kv_cache"]["page_layers"] == 2
        # [4 value heads, 16, 16] float32 and 3 x 128 channels of float32
        # (this engine serves in float32), six layers of each.
        assert state["state"]["bytes_per_slot"] == {
            "recurrent": 6 * 4 * 16 * 16 * 4, "conv": 6 * 3 * 128 * 4}
        metrics = get("/metrics")

        def value(line_start):
            return float(next(l for l in metrics.splitlines()
                              if l.startswith(line_start)).rsplit(" ", 1)[1])

        assert value('kubeai_engine_state_pool_bytes{kind="recurrent"}') == (
            2 * 6 * 4 * 16 * 16 * 4)
        assert value("kubeai_engine_state_admissions_total") == 1
        held = value('kubeai_engine_moe_assignments_total{held="true"}')
        absent = value('kubeai_engine_moe_assignments_total{held="false"}')
        rows = value('kubeai_engine_route_rows_total{kind="prefill"}') + value(
            'kubeai_engine_route_rows_total{kind="decode"}')
        assert held + absent == rows * 8 * 3 and 0 < held < absent
        # Touched counts held experts only: at most 4 a (pass, layer).
        assert value('kubeai_engine_moe_experts_touched_total{kind="decode"}') <= (
            4 * value('kubeai_engine_moe_passes_total{kind="decode"}'))
    finally:
        server.stop()
