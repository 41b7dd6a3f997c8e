"""The `exaone_moe` family (K-EXAONE-236B-A23B's shape at a tiny size: three
window layers to one global, a leading dense layer, a sigmoid-plus-bias router
over 32 experts of which 8 are held as share 1 of 4, 4 a token, an ungated
shared expert), on the CPU with seeded weights, against the benchmark's plain
reference `perf/reference/exaone_moe.py`, which imports nothing of the
program. The window is 16 positions and a page 8, so a slot's ring is 3 pages
(24 positions) and the sequences here pass it three to five times.

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
from kubeai_tpu.models import exaone_moe as em
from kubeai_tpu.models.registry import get_model_family
from kubeai_tpu.ops import paged_attention as pa
from kubeai_tpu.ops import experts
from kubeai_tpu.ops.experts import at
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh
from perf.reference import exaone_moe as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "perf", "configs", "tiny-exaone-moe.json")) as f:
    HF = json.load(f)
KEY = jax.random.PRNGKey(46)
PAGE, SLOTS, SLOT, MAX_LEN = 8, 4, 2, 128
RING = 3  # pages of a slot's ring: a window of 16 touches at most 3 pages of 8
PROMPT, STEPS, BUCKET = 77, 30, 128  # 3.2 rings of prompt, 4.5 in all
TOKENS = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (PROMPT + STEPS,), 0, 500))


@pytest.fixture(scope="module")
def family():
    return get_model_family("ExaoneMoeForCausalLM")


def served(dtype, hf=HF, **changed):
    """(config, the reference's seeded weights in the program's layout)."""
    cfg = dataclasses.replace(
        em.ExaoneMoeConfig.from_hf_dict(hf), dtype=dtype, **changed)
    params = jax.jit(lambda k: reference.served_params(hf, k))(KEY)
    return cfg, jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16
                             else a, params)


def through_the_cache(cfg, params, ring=RING, bucket=BUCKET):
    """Prefill PROMPT tokens padded into `bucket`, write the global layers'
    pages and the window layers' rings for slot SLOT of fresh pools, then
    STEPS decode steps teacher-forced on TOKENS. Returns (logits [STEPS + 1,
    V] at positions PROMPT - 1 .., the expert sets [PROMPT + STEPS, routed
    layers, k] the program took, the cache). `ring` other than the family's
    own builds and addresses a ring of that many pages (a planted fault)."""
    saved = pa.ring_pages
    pa.ring_pages = lambda window, page: ring
    try:
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :PROMPT] = TOKENS[:PROMPT]
        lengths = jnp.array([PROMPT])
        logits0, k_all, v_all, rows, routes0 = jax.jit(
            lambda p, t, l: em.prefill(p, cfg, t, l, routes=True, state=True)
        )(params, jnp.asarray(tokens), lengths)
        assert k_all.shape[0] == cfg.global_layers
        assert rows["k_window"].shape[0] == cfg.window_layers
        cache = PagedKVCache.create(
            cfg.global_layers, 1 + SLOTS * MAX_LEN // PAGE, PAGE, SLOTS, MAX_LEN,
            cfg.num_kv_heads, cfg.head_dim, dtype=cfg.dtype,
            window={**em.kv_layers(cfg), "ring": ring})
        bt = np.full((SLOTS, MAX_LEN // PAGE), -1, np.int32)
        bt[SLOT, :14] = [5, 9, 3, 7, 21, 22, 30, 12, 40, 41, 2, 17, 18, 19]
        pid, off = pa.batched_sequence_page_coords(
            jnp.asarray(bt[SLOT:SLOT + 1]), lengths, bucket, PAGE)
        kp, vp = pa.batched_scatter_sequence(
            cache.k_pages, cache.v_pages, k_all, v_all, pid, off)
        kw, vw = pa.ring_scatter_sequence(
            cache.state["k_window"], cache.state["v_window"], rows["k_window"],
            rows["v_window"], jnp.array([SLOT]), lengths, ring)
        state = {"k_window": kw, "v_window": vw}
        step = jax.jit(lambda p, t, pos, kp, vp, bt, st: em.decode_step_paged(
            p, cfg, t, pos, kp, vp, bt, routes=True, state=st))
        got, routes = [np.asarray(logits0[0])], [np.asarray(routes0[0, :PROMPT])]
        for i in range(STEPS):
            t, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
            t[SLOT], pos[SLOT] = TOKENS[PROMPT + i], PROMPT + i
            lg, kp, vp, state, r = step(
                params, jnp.asarray(t), jnp.asarray(pos), kp, vp,
                jnp.asarray(bt), state)
            got.append(np.asarray(lg[SLOT]))
            routes.append(np.asarray(r[SLOT])[None])
    finally:
        pa.ring_pages = saved
    return np.stack(got), np.concatenate(routes).astype(np.int64), (kp, vp, state)


def against_the_reference(got, given, quant=None):
    """max |logit difference| to the reference's full forward over the same
    tokens, following the program's expert sets."""
    seq = [int(t) for t in TOKENS]
    rows = list(range(PROMPT - 1, PROMPT + STEPS))
    logits, own, trail = reference.forward(
        HF, KEY, [(seq, rows)], quant=quant, routes=[given], pad_to=128, rows_pad=32)
    return float(np.abs(np.asarray(logits[0]) - got).max()), own[0], trail[0]


# The logits' standard deviation is 0.16. In float32 the program, through both
# pools, reads 2.4e-7 off the reference's full forward; a ring one page short
# reads 8.0e-2 off, a window one position too wide 0.11, one too narrow 0.15.
F32_TOL = 2e-5
# Served in bfloat16 (the configuration's precision) it reads 5.6e-3 off; the
# reference's own float8 forward reads 4.8e-2 off the float32 one (its int8
# forward, weights only, 1.6e-2).
BF16_TOL = 1.5e-2


@pytest.fixture(scope="module")
def sound():
    cfg, params = served(jnp.float32)
    return cfg, params, through_the_cache(cfg, params)


def test_prefill_then_decode_through_both_pools_is_the_references_full_forward(sound):
    cfg, _, (got, given, _) = sound
    gap, own, trail = against_the_reference(got, given)
    assert gap < F32_TOL
    # In float32 the program's expert sets are the reference's own.
    assert (np.sort(given, -1) == np.sort(own, -1)).all() and trail.max() == 0
    # All four global ids a row, of a router 32 wide, held here (8-15) or not;
    # seven routed layers: the leading dense layer has no row.
    assert given.shape == (PROMPT + STEPS, 7, 4) and given.max() > 15
    assert (PROMPT + STEPS) / (RING * PAGE) > 4  # the ring was passed 4 times


def test_served_in_bfloat16_it_stays_under_what_float8_lands_over():
    cfg, params = served(jnp.bfloat16)
    got, given, _ = through_the_cache(cfg, params)
    gap, _, _ = against_the_reference(got, given)
    assert gap < BF16_TOL
    seq, rows = [int(t) for t in TOKENS], list(range(PROMPT - 1, PROMPT + STEPS))
    full = reference.forward(HF, KEY, [(seq, rows)], pad_to=128, rows_pad=32)[0]
    low = reference.forward(HF, KEY, [(seq, rows)], quant="fp8", pad_to=128,
                            rows_pad=32)[0]
    assert float(np.abs(np.asarray(full) - np.asarray(low)).max()) > BF16_TOL


@pytest.mark.parametrize("fault", ["ring_one_page_short", "window_one_too_wide",
                                   "window_one_too_narrow"])
def test_a_planted_cache_fault_moves_the_logits_past_the_tolerance(sound, fault):
    """A ring of 2 pages overwrites keys a later query still sees; a mask
    off by one sees one key more, or one fewer, than the reference."""
    _, params, _ = sound
    if fault == "ring_one_page_short":
        cfg, _ = served(jnp.float32)
        got, given, _ = through_the_cache(cfg, params, ring=RING - 1)
    else:
        wide = fault == "window_one_too_wide"
        cfg, _ = served(jnp.float32, sliding_window=17 if wide else 15)
        got, given, _ = through_the_cache(cfg, params)
    gap, _, _ = against_the_reference(got, given)
    assert gap > 100 * F32_TOL


def test_a_prompt_padded_into_a_larger_bucket_leaves_the_rings_of_the_unpadded_one():
    cfg, params = served(jnp.float32)
    got, _, (_, _, state) = through_the_cache(cfg, params, bucket=80)
    wide, _, (_, _, wide_state) = through_the_cache(cfg, params, bucket=128)
    assert np.abs(wide - got).max() < 1e-5
    # The slot's three ring pages hold the same keys; nobody else's page and
    # no pad position was written (page 0 is scratch).
    ring = slice(1 + SLOT * RING, 1 + (SLOT + 1) * RING)
    assert np.abs(np.asarray(wide_state["k_window"][:, ring])
                  - np.asarray(state["k_window"][:, ring])).max() < 1e-5
    others = np.delete(np.asarray(wide_state["k_window"]),
                       [0, *range(ring.start, ring.stop)], axis=1)
    assert not others.any()


def test_the_ring_holds_the_last_window_of_positions_wherever_the_prompt_ends():
    """For every prompt length around the page and ring boundaries, the
    positions a next query sees (the last `window - 1`) lie in the slot's
    ring at page `(p // page) % ring`, offset `p % page`; each live target is
    written once."""
    layers, kvh, d, bucket = 2, 1, 4, 64
    seq = jnp.arange(bucket, dtype=jnp.float32)[None, None, :, None, None] + jnp.zeros(
        (layers, 1, bucket, kvh, d))
    for length in (1, 7, 8, 9, 16, 23, 24, 25, 40, 47, 48, 49, 63, 64):
        empty = jnp.full((layers, 1 + SLOTS * RING, PAGE, kvh, d), -1.0)
        kw, _ = pa.ring_scatter_sequence(
            empty, empty, seq, seq, jnp.array([SLOT]), jnp.array([length]), RING)
        kw = np.asarray(kw)
        for p in range(max(0, length - 16), length):
            page = 1 + SLOT * RING + (p // PAGE) % RING
            assert (kw[:, page, p % PAGE] == p).all(), (length, p)
        mine = kw[:, 1 + SLOT * RING: 1 + (SLOT + 1) * RING]
        assert mine[mine >= 0].max() == length - 1  # no pad position written
        assert (np.delete(kw, range(1 + SLOT * RING, 1 + (SLOT + 1) * RING),
                          axis=1)[:, 1:] == -1).all()  # nobody else's page
    assert pa.ring_pages(16, 8) == 3 and pa.ring_pages(128, 64) == 3
    assert pa.ring_pages(128, 16) == 9 and pa.ring_pages(100, 64) == 3
    bt = jnp.array([[4, 5, -1, -1, -1], [-1, -1, -1, -1, -1]])
    assert np.asarray(pa.ring_block_tables(bt, 3)).tolist() == [
        [1, 2, 3, 1, 2], [-1, -1, -1, -1, -1]]


def test_the_flash_prefill_with_a_window_interpreted_is_the_jnp_prefill(monkeypatch):
    from kubeai_tpu.ops import dispatch
    from kubeai_tpu.ops.attention import causal_prefill_attention
    from kubeai_tpu.ops.pallas_attention import flash_causal_prefill

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 384, 4, 128))
    k, v = (jax.random.normal(x, (1, 384, 2, 128)) for x in ks[1:])
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    for window in (128, 100, 300):
        want = causal_prefill_attention(q, k, v, window=window)
        got = flash_causal_prefill(q, k, v, window=window)
        assert float(jnp.abs(got - want).max()) < 2e-5, window
    # No window: the kernel's loop and mask are what they were.
    assert float(jnp.abs(flash_causal_prefill(q, k, v)
                         - causal_prefill_attention(q, k, v)).max()) < 2e-5


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's share test: four chips of 8 experts each route over all
    32, each computes its own experts' part under the weights of the whole
    taken set, every one computes the shared expert alike; the four parts and
    the shared expert counted once are the uncut reference layer."""
    layer, rows = 5, 24
    x = jax.random.normal(jax.random.PRNGKey(11), (rows, HF["hidden_size"]))
    uncut = {**HF, "num_experts": 32, "router_num_experts": 32, "expert_share_index": 0}
    none = np.zeros((rows, 4), np.int32)
    want, own, _ = reference.experts_apply(
        uncut, KEY, layer, x, none, np.zeros(rows, bool), np.ones(rows, bool))
    w = reference._make_moe(reference._flat(HF), KEY, layer)
    h = reference.rms_norm(x, w["post_norm"], HF["rms_norm_eps"])
    total = jnp.zeros_like(x)
    for share in range(4):
        hf = {**HF, "expert_share_index": share}
        cfg, params = served(jnp.float32, hf=hf)
        layers = params["layers"]
        routed, shared, topi = experts.moe_parts_sigmoid_bias(
            h, at(layers["moe"], layer - 1), layers["experts"], layer - 1, cfg)
        assert np.array_equal(np.sort(np.asarray(topi), -1), np.sort(own, -1))
        total = total + routed
    # Float32 sums in another order: 3e-8 read, the layer's output reaches 0.05.
    assert float(jnp.abs(x + total + shared - want).max()) < 1e-6
    # One share alone is NOT the layer: what the absent experts add is left out.
    assert float(jnp.abs(x + routed + shared - want).max()) > 1e-4


def test_the_bias_moves_a_selection_and_never_a_weight():
    cfg, params = served(jnp.float32)
    mp = at(params["layers"]["moe"], 2)
    x = jax.random.normal(jax.random.PRNGKey(12), (64, cfg.hidden_size))
    topi, probs = experts.route_sigmoid_bias(x, mp, cfg)
    s = jax.nn.sigmoid(x @ mp["router"])
    # The weights are the sigmoid scores of the taken, renormalised and scaled
    # by 2.5: the bias is no part of them.
    taken = jnp.take_along_axis(s, topi, axis=-1)
    assert float(jnp.abs(probs - 2.5 * taken / taken.sum(-1, keepdims=True)).max()) < 1e-6
    assert float(jnp.abs(probs.sum(-1) - 2.5).max()) < 1e-5
    # Without the bias other experts are taken in some rows (uniform in +-0.05
    # against scores 0.01 apart), and the rows that take the same set weigh
    # it the same.
    bare_i, bare_p = experts.route_sigmoid_bias(x, dict(mp, router_bias=jnp.zeros_like(mp["router_bias"])), cfg)
    same = (np.sort(np.asarray(topi), -1) == np.sort(np.asarray(bare_i), -1)).all(-1)
    assert 0 < same.sum() < len(same)
    order = np.argsort(np.asarray(topi), -1), np.argsort(np.asarray(bare_i), -1)
    a = np.take_along_axis(np.asarray(probs), order[0], -1)[same]
    b = np.take_along_axis(np.asarray(bare_p), order[1], -1)[same]
    assert np.abs(a - b).max() < 1e-6


def test_the_published_shape_reads_its_period_and_its_share_from_the_file():
    with open(os.path.join(ROOT, "perf", "configs", "k-exaone-236b-a23b-v5e1.json")) as f:
        cfg = em.ExaoneMoeConfig.from_hf_dict(json.load(f))
    assert cfg.period_types == (em.WINDOW,) * 3 + (em.GLOBAL,)
    assert (cfg.num_layers, cfg.periods, cfg.global_layers, cfg.window_layers) == (8, 2, 2, 6)
    assert (cfg.first_k_dense, cfg.routed_layers) == (1, 7)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (16, 128, 0)
    assert em.kv_layers(cfg) == {"global_layers": 2, "window_layers": 6, "window": 128}
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(cfg, num_layers=6)
    with pytest.raises(ValueError, match="router 128 wide"):
        dataclasses.replace(cfg, expert_share_index=8)


# ---- through the engine -------------------------------------------------------


def make_engine(**kw):
    cfg, params = served(jnp.float32)
    slots = kw.pop("num_slots", 2)
    return Engine("exaone_moe", cfg, params, cfg=EngineConfig(
        num_slots=slots, max_seq_len=MAX_LEN, page_size=PAGE, **kw))


PROMPTS = [[int(t) for t in TOKENS[:n]] for n in (77, 9, 40)]
GREEDY = SamplingParams(temperature=0.0, max_tokens=30)


@pytest.fixture(scope="module")
def fresh_streams():
    return [make_engine().generate([p], GREEDY)[0] for p in PROMPTS]


def test_the_engine_serves_what_the_reference_puts_first(fresh_streams):
    """Greedy serving in float32: every served token is the reference's
    first at its position, through admission, the decode chunk and both
    pools."""
    for prompt, out in zip(PROMPTS, fresh_streams):
        seq = prompt + out[:-1]
        rows = list(range(len(prompt) - 1, len(seq)))
        logits = np.asarray(reference.forward(
            HF, KEY, [(seq, rows)], pad_to=128, rows_pad=32)[0])
        assert logits.argmax(-1).tolist() == out


def test_a_slot_reused_after_a_longer_request_serves_what_a_fresh_engine_serves(
        fresh_streams):
    """A ring holds what its new owner wrote and what the last one left; the
    kernel's position mask keeps the latter out."""
    engine = make_engine(num_slots=1)
    assert [engine.generate([p], GREEDY)[0] for p in PROMPTS] == fresh_streams


def test_a_request_preempted_and_recomputed_serves_the_same_stream():
    """Preemption by recompute: the re-admission rebuilds both pools from
    position 0 over the prompt and what was served."""
    prompts = [[int(t) for t in TOKENS[i:i + 20]] for i in (0, 7, 19)]
    sp = SamplingParams(temperature=0.0, max_tokens=60)
    want = make_engine(num_slots=4).generate(prompts, sp)
    tight = make_engine(num_slots=4, num_pages=1 + 18)
    preempted = []
    tight.on_preempt = lambda rid, client: preempted.append(rid)
    assert tight.generate(prompts, sp) == want
    assert preempted


def test_a_window_slots_pages_do_not_grow_with_length_and_global_pages_do():
    engine = make_engine(num_slots=2)
    window, pool = engine.kv_pools()[1], engine.cache.state["k_window"]
    assert pool.shape == (6, 1 + 2 * RING, PAGE, 2, 16)
    assert engine.cache.k_pages.shape == (2, 1 + 2 * MAX_LEN // PAGE, PAGE, 2, 16)
    assert (window["pages"], window["pages_per_slot"], window["window"]) == (6, 3, 16)
    engine.add_request(PROMPTS[2], SamplingParams(temperature=0.0, max_tokens=80))
    seen = []
    while engine.has_work():
        engine.step()
        glob, win = engine.kv_pools()
        if engine.num_active:
            seen.append((glob["pages_used"], win["pages_used"],
                         engine.live_kv["pages"], engine.live_window["pages"]))
    # Global pages follow the length (40 -> 120 tokens: 5 or 6 -> 15 pages);
    # the ring stays 3 pages, of which a step reads 2 or 3.
    assert seen[0][0] <= 7 and seen[-1][0] >= 15
    assert {s[1] for s in seen} == {RING}
    assert seen[-1][2] >= 12 and {s[3] for s in seen} <= {2, 3}
    # Release returns both: the allocator's pages, and the ring with the slot.
    glob, win = engine.kv_pools()
    assert glob["pages_used"] == 0 and win["pages_used"] == 0
    assert engine.kv_utilization() == 0.0
    # The window pool is the same size whatever was served.
    assert engine.cache.state["k_window"].shape == pool.shape


def test_a_one_kind_family_builds_one_pool_and_takes_todays_arguments():
    from kubeai_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    engine = Engine("llama", cfg, llama.init_params(cfg), cfg=EngineConfig(
        num_slots=2, max_seq_len=64, page_size=16))
    assert engine.cache.state == {} and engine._state_pools() == ()
    assert engine._window is None and engine.kv_pools() is None
    assert engine.cache.k_pages.shape[0] == cfg.num_layers
    cache = engine.cache
    lowered = engine._decode_jit.lower(
        engine.params, cache.k_pages, cache.v_pages, cache.block_tables,
        engine._state, None)
    assert len(lowered.args_info[0]) == 6  # params, kp, vp, bt, state, lora


def test_the_family_refuses_what_needs_a_rule_for_a_forgotten_ring(family, devices8):
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
        with pytest.raises(ValueError, match=f"exaone_moe keeps a window ring.*{name}"):
            build(**kw)
    engine = build()
    for call in (
        lambda: engine.export_handoff([1, 2, 3]),
        lambda: engine.import_handoff(None),
        lambda: engine.export_prefix_pages([]),
        lambda: engine.import_prefix_pages(None),
        lambda: engine.enable_kv_spill(object()),
    ):
        with pytest.raises(ValueError, match="exaone_moe keeps a window ring"):
            call()
    for name, kw in (
        ("prefill role", dict(role="prefill")),
        ("decode role", dict(role="decode")),
        ("kv_sharing", dict(kv_sharing=True)),
        ("a KV spill store", dict(kv_spill_store=object())),
    ):
        with pytest.raises(ValueError, match=f"exaone_moe keeps a window ring.*{name}"):
            EngineServer(engine, ByteTokenizer(), "tiny", port=0, **kw)


def test_the_pools_and_the_share_on_v1_state_and_the_counters():
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
            "model": "tiny", "prompt": "hello ring " * 4, "max_tokens": 40,
            "temperature": 0, "kubeai_routes": True}),
            {"Content-Type": "application/json"})
        reply = json.loads(conn.getresponse().read())
        conn.close()
        block = reply["choices"][0]["kubeai_routes"][0]
        assert block["shape"] == [7, 4] and block["start"] == 0
        state = json.loads(get("/v1/state"))
        assert state["moe"] == {"experts": 32, "k": 4, "routed_layers": 7,
                                "routes": True, "held": [8, 16]}
        glob, win = state["kv_pools"]
        page_bytes = 2 * PAGE * 2 * 16 * 2  # k and v; the pools are bf16
        assert glob == {"kind": "global", "layers": 2, "pages": 2 * MAX_LEN // PAGE,
                        "pages_per_slot": MAX_LEN // PAGE, "pages_used": 0,
                        "bytes": 2 * (1 + 2 * MAX_LEN // PAGE) * page_bytes}
        assert win == {"kind": "window", "layers": 6, "pages": 2 * RING,
                       "pages_per_slot": RING, "pages_used": 0, "window": 16,
                       "bytes": 6 * (1 + 2 * RING) * page_bytes}
        assert state["kv_cache"]["page_layers"] == 2 and "state" not in state
        metrics = get("/metrics")

        def value(line_start):
            return float(next(l for l in metrics.splitlines()
                              if l.startswith(line_start)).rsplit(" ", 1)[1])

        assert value('kubeai_engine_kv_pool_pages{pool="window",state="free"}') == 6
        assert value('kubeai_engine_kv_pool_pages{pool="global",state="free"}') == 32
        # 44 prompt tokens and 40 served: a chunk's global layer read 6 to 11
        # pages, its window layer 2 or 3, whatever the length.
        read_global = value('kubeai_engine_decode_live_pages_total{pool="global"}')
        read_window = value('kubeai_engine_decode_live_pages_total{pool="window"}')
        assert 0 < read_window < read_global / 2
        held = value('kubeai_engine_moe_assignments_total{held="true"}')
        absent = value('kubeai_engine_moe_assignments_total{held="false"}')
        rows = value('kubeai_engine_route_rows_total{kind="prefill"}') + value(
            'kubeai_engine_route_rows_total{kind="decode"}')
        assert held + absent == rows * 7 * 4 and 0 < held < absent
        assert value('kubeai_engine_moe_experts_touched_total{kind="decode"}') <= (
            8 * value('kubeai_engine_moe_passes_total{kind="decode"}'))
    finally:
        server.stop()
