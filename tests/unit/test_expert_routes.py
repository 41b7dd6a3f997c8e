"""The router's expert choices, handed over: a routed family's forwards
return the sets they took, the engine brings them to the host in the
readbacks it already does, a request that asks gets its own rows, and the
counters say how the experts are loaded. Tiny Mixtral, on the CPU.

Row p of a request is the sets taken when the program computed position p
of prompt plus served tokens: P + N - 1 rows for P prompt and N served
tokens (docs/concepts/expert-routes.md)."""

import base64
import dataclasses
import http.client
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.routes import decode_block, encode_block, join_blocks
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import gemma, llama, mixtral
from kubeai_tpu.models.registry import get_model_family
from kubeai_tpu.ops.experts import route_dtype
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.unit.test_host_timeline import Recorder

GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


@pytest.fixture(scope="module")
def tiny():
    """float32, so that no choice of the router hangs on bf16's rounding."""
    cfg = dataclasses.replace(mixtral.MixtralConfig.tiny(), dtype=jnp.float32)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(7))
    # Seeded weights of std 0.02 leave every layer's output far below the
    # residual; the experts have to matter for a swapped pair to show.
    layers = dict(params["layers"])
    for name in ("w_gate", "w_up", "w_down", "router"):
        layers[name] = layers[name] * 8.0
    return cfg, {**params, "layers": layers}


def _engine(tiny, mesh=None, **kw):
    cfg, params = tiny
    kw = {"num_slots": 4, "max_seq_len": 128, "page_size": 16,
          "decode_chunk": 4, "cache_dtype": jnp.float32, **kw}
    return Engine("mixtral", cfg, params, mesh=mesh, cfg=EngineConfig(**kw))


def _run(eng, prompts, ask=True, params=GREEDY, **kw):
    """Serve the prompts together; per request its tokens and its blocks in
    the order the events carried them."""
    asks = ask if isinstance(ask, (list, tuple)) else [ask] * len(prompts)
    rids = [eng.add_request(p, params, routes=a, **kw)
            for p, a in zip(prompts, asks)]
    toks = {r: [] for r in rids}
    blocks = {r: [] for r in rids}
    while eng.has_work():
        for ev in eng.step():
            toks[ev.rid].append(ev.token)
            if ev.routes is not None:
                blocks[ev.rid].extend(ev.routes)
    return [(toks[r], blocks[r]) for r in rids]


def _rows(blocks):
    """The blocks laid out by position, a later block over an earlier one;
    asserts there is no gap."""
    by_pos = {}
    for start, rows in blocks:
        for j, row in enumerate(rows):
            by_pos[start + j] = row
    first = min(by_pos)
    assert sorted(by_pos) == list(range(first, first + len(by_pos)))
    return first, np.stack([by_pos[p] for p in sorted(by_pos)])


def _exactly_once(blocks, first, n):
    """Positions first .. first + n - 1, each in one block, in order."""
    seen = [s + j for s, rows in blocks for j in range(len(rows))]
    assert seen == list(range(first, first + n)), seen


# ---- the forwards return what the router took ---------------------------------


def test_the_family_says_whether_it_routes():
    fam = get_model_family("mixtral")
    cfg = mixtral.MixtralConfig.tiny()
    assert fam.routes and fam.route_dims(cfg) == (4, 2, 2)
    assert cfg.routed_layers == cfg.num_layers
    for name in ("llama", "qwen", "gemma"):
        assert not get_model_family(name).routes
    assert [route_dtype(n) for n in (8, 256, 257, 65536, 65537)] == [
        "uint8", "uint8", "uint16", "uint16", "uint32"]


def test_moe_ffn_returns_the_topi_its_weights_are_built_from(tiny):
    cfg, params = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.hidden_size))
    y, topi = mixtral._moe_ffn(x, lp, cfg)
    assert y.shape == x.shape and topi.shape == (2, 5, cfg.num_experts_per_tok)
    logits = jnp.einsum("bse,ex->bsx", x, lp["router"])
    assert np.array_equal(topi, jax.lax.top_k(logits, 2)[1])


@pytest.mark.parametrize("forward", ["prefill", "decode_step",
                                     "decode_step_paged", "prefill_chunk"])
def test_each_forward_hands_over_rows_by_routed_layers_by_k(tiny, forward):
    cfg, params = tiny
    NL, KVH, D, K = (cfg.num_layers, cfg.num_kv_heads, cfg.head_size,
                     cfg.num_experts_per_tok)
    toks = jnp.arange(1, 13).reshape(2, 6)
    if forward == "prefill":
        args, rows = (toks, jnp.array([6, 4])), (2, 6)
    elif forward == "decode_step":
        cache = jnp.zeros((NL, 2, 16, KVH, D), jnp.float32)
        args, rows = (toks[:, 0], jnp.array([3, 5]), cache, cache), (2,)
    elif forward == "decode_step_paged":
        pool = jnp.zeros((NL, 5, 8, KVH, D), jnp.float32)
        bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
        args, rows = (toks[:, 0], jnp.array([3, 5]), pool, pool, bt), (2,)
    else:
        slot = jnp.zeros((NL, 16, KVH, D), jnp.float32)
        args = (toks[:1], jnp.int32(0), jnp.int32(6), slot, slot)
        rows = (6,)
    fn = getattr(mixtral, forward)
    plain = fn(params, cfg, *args)
    routed = fn(params, cfg, *args, routes=True)
    assert len(plain) == 3 and len(routed) == 4
    assert routed[3].shape == (*rows, NL, K) and routed[3].dtype == jnp.uint8
    assert int(routed[3].max()) < cfg.num_experts
    # The two experts of a set differ, best first, and asking changes nothing.
    assert bool((routed[3][..., 0] != routed[3][..., 1]).all())
    if plain[0] is not None:
        assert np.array_equal(plain[0], routed[0])


def test_prefill_and_decode_hand_over_the_same_sets_for_the_same_position(tiny):
    """Position p computed by prefill, by a chunk and by a decode step
    through the page pool: one router, one answer."""
    eng = _engine(tiny)
    prompt = list(range(1, 12))
    (toks, blocks), = _run(eng, [prompt], params=SamplingParams(
        temperature=0.0, max_tokens=9))
    _, rows = _rows(blocks)
    cfg, params = tiny
    seq = prompt + toks[:-1]
    whole = mixtral.prefill(params, cfg, jnp.asarray([seq]),
                            jnp.asarray([len(seq)]), routes=True)[3][0]
    assert np.array_equal(rows, whole)


# ---- (a) which rows a request gets ------------------------------------------------


@pytest.mark.parametrize("max_tokens", [1, 2, 5, 6, 9])
def test_one_shot_prefill_and_a_chunk_that_overruns_max_tokens(tiny, max_tokens):
    """decode_chunk is 4: 2, 5 and 6 stop inside a chunk, whose surplus steps
    are dropped with their tokens; 1 ends on the first token."""
    eng = _engine(tiny)
    prompt = list(range(3, 14))
    (toks, blocks), = _run(eng, [prompt], params=SamplingParams(
        temperature=0.0, max_tokens=max_tokens))
    assert len(toks) == max_tokens
    _exactly_once(blocks, 0, len(prompt) + max_tokens - 1)
    assert blocks[0][0] == 0 and len(blocks[0][1]) == len(prompt)
    assert all(len(rows) == 1 for _, rows in blocks[1:])
    assert eng._requests == {}


def test_two_buckets_admitted_in_one_step(tiny):
    eng = _engine(tiny)
    rec = Recorder()
    eng.profiler._annotate = rec
    prompts = [list(range(1, 6)), list(range(2, 9)), list(range(40, 60))]
    out = _run(eng, prompts)
    kinds = [a["attrs"] for a in rec.named("step.admit") if "kind" in a["attrs"]]
    assert [(a["bucket"], a["batch"]) for a in kinds] == [(16, 2), (32, 1)]
    for prompt, (toks, blocks) in zip(prompts, out):
        _exactly_once(blocks, 0, len(prompt) + len(toks) - 1)
    # Served alone, each request gets the rows it got in the batch.
    for prompt, (toks, blocks) in zip(prompts, out):
        (alone_toks, alone), = _run(_engine(tiny), [prompt])
        assert alone_toks == toks
        assert np.array_equal(_rows(alone)[1], _rows(blocks)[1])


def test_chunked_prefill_and_a_reused_prefix(tiny):
    eng = _engine(tiny, prefill_chunk=16, prefix_cache=True)
    shared = list(range(1, 41))  # 40 tokens: two full pages to share
    first = shared + [50]        # 41 > chunk: three chunks, the last back-aligned
    (toks, blocks), = _run(eng, [first])
    _exactly_once(blocks, 0, len(first) + len(toks) - 1)
    (plain_toks, plain), = _run(_engine(tiny), [first])
    assert plain_toks == toks
    assert np.array_equal(_rows(plain)[1], _rows(blocks)[1])
    # The same prefix again: the first block starts at the first computed
    # position, and holds what a full prefill computes there.
    second = shared + [51, 52]
    (toks2, blocks2), = _run(eng, [second])
    assert eng.prefix_stats["hit_tokens"] == 32
    _exactly_once(blocks2, 32, len(second) + len(toks2) - 1 - 32)
    (_, full), = _run(_engine(tiny), [second])
    assert np.array_equal(_rows(full)[1][32:], _rows(blocks2)[1])


def test_a_resume_prefix_is_recomputed_and_sent_before_the_next_row(tiny):
    prompt = list(range(5, 17))
    (toks, blocks), = _run(_engine(tiny), [prompt], params=SamplingParams(
        temperature=0.0, max_tokens=8))
    eng = _engine(tiny)
    (rest, resumed), = _run(eng, [prompt], resume_tokens=toks[:3],
                            params=SamplingParams(temperature=0.0, max_tokens=8))
    assert rest == toks[3:]
    # prompt + served[:2] is the context; the third token is forced.
    assert resumed[0][0] == 0 and len(resumed[0][1]) == len(prompt) + 2
    _exactly_once(resumed, 0, len(prompt) + 8 - 1)
    assert np.array_equal(_rows(resumed)[1], _rows(blocks)[1])


def test_after_a_preemption_the_recomputed_rows_come_again_under_their_positions(tiny):
    """A pool too small for both: the younger request is evicted, and its
    re-admission recomputes prompt and served tokens. The later block is the
    one the cache now holds; laid out by position nothing is missing."""
    eng = _engine(tiny, num_slots=2, max_seq_len=64, num_pages=6)
    preempted = []
    eng.on_preempt = lambda rid, client: preempted.append(rid)
    prompts = [list(range(1, 31)), list(range(31, 61))]
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    out = _run(eng, prompts, params=sp)
    assert preempted
    for prompt, (toks, blocks) in zip(prompts, out):
        first, rows = _rows(blocks)
        assert first == 0 and len(rows) == len(prompt) + len(toks) - 1
        (alone_toks, alone), = _run(_engine(tiny, max_seq_len=64), [prompt], params=sp)
        assert alone_toks == toks and np.array_equal(_rows(alone)[1], rows)
    starts = [s for s, _ in out[1][1]]
    assert starts.count(0) == 2  # the victim's prompt rows, sent twice


# ---- (b) the rows are the ones the program used -----------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def plain_forward(params, cfg, seq, sets=None):
    """A Mixtral forward in plain jax.numpy float32 over one whole sequence:
    no cache, no batch, one token's experts computed one by one. `sets`
    [T, layers, k] are the experts to take; None takes the router's own.
    Returns (logits [T, V], the sets taken)."""
    T = len(seq)
    H, KVH, D, K = (cfg.num_heads, cfg.num_kv_heads, cfg.head_size,
                    cfg.num_experts_per_tok)
    pos = jnp.arange(T, dtype=jnp.float32)
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    cos, sin = jnp.cos(pos[:, None] * inv)[:, None], jnp.sin(pos[:, None] * inv)[:, None]

    def rope(x):
        a, b = x[..., : D // 2], x[..., D // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    x = params["embed"][jnp.asarray(seq)]
    taken = []
    for layer in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        h = _rms(x, lp["input_norm"], cfg.rms_norm_eps)
        q = rope((h @ lp["wq"]).reshape(T, H, D))
        k = rope((h @ lp["wk"]).reshape(T, KVH, D))
        v = (h @ lp["wv"]).reshape(T, KVH, D)
        k, v = jnp.repeat(k, H // KVH, 1), jnp.repeat(v, H // KVH, 1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(T, H * D) @ lp["wo"]
        h2 = _rms(x, lp["post_attn_norm"], cfg.rms_norm_eps)
        router = h2 @ lp["router"]
        ids = (jax.lax.top_k(router, K)[1] if sets is None
               else jnp.asarray(sets[:, layer], jnp.int32))
        taken.append(ids)
        weight = jax.nn.softmax(jnp.take_along_axis(router, ids, -1), -1)
        y = jnp.zeros_like(x)
        for j in range(K):
            e = ids[:, j]
            g = jax.nn.silu(jnp.einsum("te,tem->tm", h2, lp["w_gate"][e]))
            u = jnp.einsum("te,tem->tm", h2, lp["w_up"][e])
            y = y + weight[:, j, None] * jnp.einsum("tm,tme->te", g * u, lp["w_down"][e])
        x = x + y
    x = _rms(x, params["final_norm"], cfg.rms_norm_eps)
    return x @ params["lm_head"].T, np.stack([np.asarray(t) for t in taken], 1)


def _greedy(logits, n_prompt):
    return np.asarray(jnp.argmax(logits[n_prompt - 1:], -1)).tolist()


PROMPT = [17, 400, 3, 91, 256, 8, 8, 120, 77, 301, 45]
LONG = SamplingParams(temperature=0.0, max_tokens=14)


def test_a_plain_forward_that_follows_the_handed_over_sets_reproduces_the_tokens(tiny):
    cfg, params = tiny
    (toks, blocks), = _run(_engine(tiny), [PROMPT], params=LONG)
    first, rows = _rows(blocks)
    assert first == 0 and len(rows) == len(PROMPT) + len(toks) - 1
    seq = PROMPT + toks[:-1]
    logits, _ = plain_forward(params, cfg, seq, rows)
    assert _greedy(logits, len(PROMPT)) == toks
    # Shifted by one position, or by one layer, they are other sets.
    for wrong in (np.roll(rows, 1, axis=0), rows[:, ::-1]):
        assert not np.array_equal(wrong, rows)
        logits, _ = plain_forward(params, cfg, seq, wrong)
        assert _greedy(logits, len(PROMPT)) != toks


def test_with_two_experts_swapped_it_does_not(tiny):
    cfg, params = tiny
    (toks, blocks), = _run(_engine(tiny), [PROMPT], params=LONG)
    rows = _rows(blocks)[1]
    swap = np.array([1, 0, 2, 3], rows.dtype)[rows]
    assert (swap != rows).any()
    logits, _ = plain_forward(params, cfg, PROMPT + toks[:-1], swap)
    assert _greedy(logits, len(PROMPT)) != toks


def test_a_permuted_router_in_the_engines_weights_shows_in_the_hand_over(tiny):
    """The engine's copy of the weights has its router's columns permuted:
    what is handed over is taken where the program uses it, so it is the
    permuted router's choice, and a forward that follows the unpermuted
    router neither makes those choices nor reproduces the tokens."""
    cfg, params = tiny
    perm = np.array([2, 0, 3, 1])
    layers = dict(params["layers"])
    layers["router"] = layers["router"][:, :, perm]
    permuted = {**params, "layers": layers}
    (toks, blocks), = _run(_engine((cfg, permuted)), [PROMPT], params=LONG)
    rows = _rows(blocks)[1]
    seq = PROMPT + toks[:-1]
    logits, own = plain_forward(permuted, cfg, seq)
    assert np.array_equal(own, rows) and _greedy(logits, len(PROMPT)) == toks
    logits, unpermuted = plain_forward(params, cfg, seq)
    assert not np.array_equal(unpermuted, rows)
    assert _greedy(logits, len(PROMPT)) != toks
    # The sound engine serves other tokens along other routes.
    (sound_toks, sound), = _run(_engine(tiny), [PROMPT], params=LONG)
    assert sound_toks != toks and not np.array_equal(_rows(sound)[1], rows)


# ---- (c) nobody asked -----------------------------------------------------------------


def test_nobody_asked_nothing_is_kept_and_the_counters_still_move(tiny):
    eng = _engine(tiny, prefill_chunk=16)
    seen = []
    real = eng.add_request

    def spy(*a, **kw):
        rid = real(*a, **kw)
        seen.append(eng._requests[rid])
        return rid

    eng.add_request = spy
    prompts = [list(range(1, 8)), list(range(10, 50)), list(range(60, 63))]
    out = _run(eng, prompts, ask=False)
    assert all(blocks == [] for _, blocks in out)
    assert len(seen) == 3 and all(r.route_backlog is None for r in seen)
    st = eng.route_stats
    served = sum(len(toks) for toks, _ in out)
    assert st["requests"] == 0 and st["rows_sent"] == 0
    assert st["rows_prefill"] == sum(map(len, prompts))
    assert st["rows_decode"] == served - len(prompts)  # the first comes from prefill
    moe = eng.moe
    assert st["expert_tokens"].sum() == (
        (st["rows_prefill"] + st["rows_decode"]) * moe["routed_layers"] * moe["k"])
    ratios = [v for kind, v, *_ in eng.drain_timing() if kind == "moe_imbalance"]
    # One reading per forward and routed layer: 1.0 is even, experts / k
    # (2.0 here) every row on one set.
    assert ratios and all(1.0 <= r <= moe["experts"] / moe["k"] for r in ratios)


def test_the_sum_rule_holds_over_a_window_of_the_servers_counters(tiny):
    srv = _serve(_engine(tiny))
    try:
        def counters():
            text = _get(srv, "/metrics")[1]
            out = {}
            for line in text.splitlines():
                if line.startswith("kubeai_engine_") and "_bucket" not in line:
                    name, value = line.rsplit(" ", 1)
                    out[name] = float(value)
            return out

        _post(srv, {"prompt": "warm the counters", "max_tokens": 3, "temperature": 0})
        before = counters()
        _post(srv, {"prompt": "a window of its own", "max_tokens": 7,
                    "temperature": 0, "kubeai_routes": True})
        _post(srv, {"prompt": "and one that does not ask", "max_tokens": 5,
                    "temperature": 0})
        after = counters()
        delta = {k: v - before.get(k, 0.0) for k, v in after.items()}
        experts = sum(v for k, v in delta.items()
                      if k.startswith("kubeai_engine_moe_expert_tokens_total{"))
        rows = sum(v for k, v in delta.items()
                   if k.startswith("kubeai_engine_route_rows_total{"))
        assert rows == 19 + 6 + 25 + 4 and experts == rows * 2 * 2
        assert delta["kubeai_engine_route_requests_total"] == 1
        assert delta["kubeai_engine_route_rows_sent_total"] == 19 + 7 - 1
        assert delta["kubeai_engine_moe_imbalance_ratio_count"] > 0
        assert delta['kubeai_engine_step_phase_seconds_count{phase="routes"}'] > 0
    finally:
        srv.stop()


# ---- (d) a dense family, and the engines that refuse -----------------------------------


def _dense(name):
    mod, cfg = ((llama, llama.LlamaConfig.tiny()) if name == "llama"
                else (gemma, gemma.GemmaConfig.tiny()))
    return name, cfg, mod.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["llama", "gemma"])
def dense(request):
    name, cfg, params = _dense(request.param)
    return Engine(name, cfg, params, cfg=EngineConfig(
        num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4))


def _lowered_outputs(eng, jitted, *args):
    with jax.set_mesh(eng.mesh):
        return jitted.lower(*args).out_info


def test_a_dense_familys_programs_have_the_outputs_they_had(dense):
    eng = dense
    assert eng.moe is None and not eng._routes and eng.routes_unsupported == ""
    B, chunk = eng.cfg.num_slots, eng.cfg.decode_chunk
    pool = eng.cache.k_pages
    out = _lowered_outputs(
        eng, eng._decode_jit, eng.params, pool, eng.cache.v_pages,
        eng.cache.block_tables, eng._state, None)
    # Since PR 44 a chunk's last output is the scalar its sampler branched
    # on (did the candidate pool run), whatever the family.
    toks, kp, vp, state, pooled = out
    assert (toks.shape, toks.dtype) == ((chunk, B), jnp.int32)
    assert (pooled.shape, pooled.dtype) == ((), jnp.bool_)
    assert kp.shape == vp.shape == pool.shape
    assert jax.tree.structure(state) == jax.tree.structure(eng._state)
    assert len(jax.tree.leaves(out)) == 4 + len(eng._state)
    mp = eng._bt_host.shape[1]
    out = _lowered_outputs(
        eng, eng._prefill_admit_jit, eng.params, jnp.zeros((2, 16), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 2), jnp.float32),
        jnp.zeros((2, mp), jnp.int32), pool, eng.cache.v_pages,
        eng.cache.block_tables, eng._state, None)
    toks, kp, vp, bt, state = out
    assert (toks.shape, toks.dtype) == ((2,), jnp.int32)
    assert bt.shape == (B, mp) and kp.shape == pool.shape
    assert len(jax.tree.leaves(out)) == 4 + len(eng._state)
    assert not any(leaf.dtype == jnp.uint8 for leaf in jax.tree.leaves(out))


def test_a_routed_familys_programs_gain_one_small_output_each(tiny):
    eng = _engine(tiny)
    assert eng._routes and eng.moe["routes"]
    pool, mp = eng.cache.k_pages, eng._bt_host.shape[1]
    out = _lowered_outputs(
        eng, eng._decode_jit, eng.params, pool, eng.cache.v_pages,
        eng.cache.block_tables, eng._state, None)
    (toks, routes), *_, pooled = out
    assert (toks.shape, routes.shape, routes.dtype) == (
        (4, 4), (4, 4, 2, 2), jnp.uint8)  # [chunk, slots, routed layers, k]
    assert (pooled.shape, pooled.dtype) == ((), jnp.bool_)
    assert len(jax.tree.leaves(out)) == 5 + len(eng._state)
    out = _lowered_outputs(
        eng, eng._prefill_admit_jit, eng.params, jnp.zeros((2, 16), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 2), jnp.float32),
        jnp.zeros((2, mp), jnp.int32), pool, eng.cache.v_pages,
        eng.cache.block_tables, eng._state, None)
    (toks, routes), *_ = out
    assert (toks.shape, routes.shape, routes.dtype) == (
        (2,), (2, 16, 2, 2), jnp.uint8)


def test_a_dense_family_reads_back_no_expert_sets_a_reap(dense, monkeypatch):
    """One transfer a reap: the tokens, no expert sets, and the scalar that
    says what the chunk's sampler ran (PR 44)."""
    eng = dense
    got = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: got.append(x) or real(x))
    rec = Recorder()
    monkeypatch.setattr(eng.profiler, "_annotate", rec)
    (toks, blocks), = _run(eng, [[1, 2, 3]], ask=True)
    assert len(toks) == 6 and blocks == []
    reaps = [r for r in rec.named("step.reap") if r["attrs"]["rows"]]
    assert len(got) == len(reaps)
    for tokens, routes, pooled in got:
        assert tokens.shape == (eng.cfg.decode_chunk, eng.cfg.num_slots)
        assert routes is None and pooled.shape == ()
    assert not rec.named("step.routes")
    assert "routes" not in {p for p, _ in eng.profiler.drain()}


def test_a_dense_family_serves_a_request_that_asks_and_says_it_has_no_router(dense):
    srv = _serve(dense)
    try:
        assert "moe" not in json.loads(_get(srv, "/v1/state")[1])
        body = {"prompt": "hello", "max_tokens": 4, "temperature": 0,
                "kubeai_routes": True}
        status, raw = _post(srv, body)
        choice = json.loads(raw)["choices"][0]
        assert status == 200 and choice["kubeai_routes"] is None
        assert len(choice["token_ids"]) == 4
        status, raw = _post(srv, {**body, "stream": True})
        chunks = _sse(raw)
        assert status == 200 and chunks
        assert all(c["kubeai_routes"] is None for c in chunks)
        assert sum((c.get("token_ids", []) for c in chunks), []) == choice["token_ids"]
        status, raw = _post(srv, {**body, "kubeai_routes": "yes"})
        assert status == 400 and "boolean" in raw
        # Not asking leaves the response as it was.
        status, raw = _post(srv, {"prompt": "hello", "max_tokens": 4, "temperature": 0})
        assert "kubeai_routes" not in raw and "token_ids" not in raw
    finally:
        srv.stop()


def _refusing(kind, devices8, tiny):
    name, cfg, params = _dense("llama")
    ecfg = EngineConfig(num_slots=4, max_seq_len=96, page_size=16, decode_chunk=4)
    if kind == "pp":
        mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
        return Engine(name, cfg, params, mesh=mesh, cfg=ecfg), "unified"
    if kind == "speculation":
        return Engine(name, cfg, params, cfg=dataclasses.replace(
            ecfg, speculate=2)), "unified"
    return _engine(tiny), kind  # one half of a disaggregated pair


@pytest.mark.parametrize("kind", ["pp", "speculation", "prefill", "decode"])
def test_engines_that_hand_no_routes_over_say_so_and_answer_400(kind, devices8, tiny):
    eng, role = _refusing(kind, devices8, tiny)
    if role == "unified":
        assert eng.routes_unsupported and not eng._routes
        with pytest.raises(ValueError, match="expert routes are not available"):
            eng.add_request([1, 2, 3], GREEDY, routes=True)
    srv = _serve(eng, role=role)
    try:
        state = json.loads(_get(srv, "/v1/state")[1])
        if eng.moe is None:
            assert "moe" not in state
        else:
            assert state["moe"] == {"experts": 4, "k": 2, "routed_layers": 2,
                                    "routes": False}
        for path, body in (
            ("/v1/completions", {"prompt": "hi"}),
            ("/v1/chat/completions",
             {"messages": [{"role": "user", "content": "hi"}]}),
        ):
            status, raw = _post(srv, {**body, "max_tokens": 2,
                                      "kubeai_routes": True}, path)
            assert status == 400
            assert "kubeai_routes is not available" in raw
        assert eng.num_pending == 0 and eng.num_active == 0
    finally:
        srv.stop()


def test_a_request_admitted_from_a_kv_handoff_is_refused(tiny):
    srv = _serve(_engine(tiny))
    try:
        status, raw = _post(srv, {"prompt": "hi", "kubeai_routes": True},
                            headers={"X-Disagg-Handoff": "abc"})
        assert status == 400 and "KV handoff" in raw
    finally:
        srv.stop()


# ---- (e) over HTTP ---------------------------------------------------------------------


def _serve(eng, role="unified"):
    srv = EngineServer(eng, ByteTokenizer(), "tiny", host="127.0.0.1", port=0,
                       role=role)
    srv.start()
    return srv


def _post(srv, body, path="/v1/completions", headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json", **(headers or {})})
    resp = conn.getresponse()
    return resp.status, resp.read().decode()


def _get(srv, path):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    return resp.status, resp.read().decode()


def _sse(raw):
    return [json.loads(line[6:]) for line in raw.splitlines()
            if line.startswith("data: {")]


def _by_hand(block):
    """The documented format, decoded without this repo's decoder."""
    raw = base64.b64decode(block["data"])
    dtype = {"uint8": "<u1", "uint16": "<u2", "uint32": "<u4"}[block["dtype"]]
    rows = np.frombuffer(raw, dtype).reshape(block["rows"], *block["shape"])
    return block["start"], rows


def test_the_sse_chunks_decode_to_the_rows_the_engine_emitted(tiny):
    eng = _engine(tiny)
    emitted: dict[int, list] = {}
    real = eng.step

    both_queued = threading.Event()

    def spy():
        both_queued.wait(30)  # both are queued before either is admitted
        events = real()
        for ev in events:
            emitted.setdefault(ev.rid, []).append(ev.routes)
        return events

    eng.step = spy
    srv = _serve(eng)
    try:
        asks = {"prompt": "the one that asks", "max_tokens": 9, "temperature": 0,
                "stream": True, "kubeai_routes": True}
        quiet = {"prompt": "its neighbour in the batch", "max_tokens": 9,
                 "temperature": 0, "stream": True}
        out = {}
        threads = [threading.Thread(
            target=lambda k=k, b=b: out.__setitem__(k, _post(srv, b)))
            for k, b in (("asks", asks), ("quiet", quiet))]
        for t in threads:
            t.start()
        for _ in range(400):
            if eng.num_pending == 2:
                break
            threading.Event().wait(0.05)
        both_queued.set()
        for t in threads:
            t.join()
        assert out["asks"][0] == out["quiet"][0] == 200
        # One admission call took both: they shared every decode step.
        assert eng.admit_stats["calls"] == 1
        chunks = _sse(out["asks"][1])
        P = len(asks["prompt"])
        wire = []
        for chunk in chunks:
            assert "kubeai_routes" in chunk
            for block in chunk["kubeai_routes"]:
                assert set(block) == {"start", "rows", "shape", "dtype", "data"}
                assert block["shape"] == [2, 2] and block["dtype"] == "uint8"
                start, rows = _by_hand(block)
                again = decode_block(block)
                assert again[0] == start and np.array_equal(again[1], rows)
                wire.append((start, rows))
        tokens = sum((c.get("token_ids", []) for c in chunks), [])
        assert len(tokens) == 9
        _exactly_once(wire, 0, P + 9 - 1)
        assert wire[0][0] == 0 and len(wire[0][1]) >= P  # with the first token
        asked_rid = next(r for r, evs in emitted.items() if evs[0] is not None)
        engine_blocks = [b for routes in emitted[asked_rid] for b in routes]
        assert np.array_equal(_rows(wire)[1], _rows(engine_blocks)[1])
        # The neighbour got tokens and nothing else.
        other = next(r for r in emitted if r != asked_rid)
        assert all(routes is None for routes in emitted[other])
        quiet_chunks = _sse(out["quiet"][1])
        assert quiet_chunks and not any("kubeai_routes" in c for c in quiet_chunks)
        # The non-streamed body carries the same rows as one list.
        status, raw = _post(srv, {**asks, "stream": False})
        choice = json.loads(raw)["choices"][0]
        assert status == 200 and choice["token_ids"] == tokens
        unary = [decode_block(b) for b in choice["kubeai_routes"]]
        assert len(unary) == 1 and unary[0][0] == 0
        assert np.array_equal(unary[0][1], _rows(wire)[1])
        # And through the chat endpoint, whose template lengthens the prompt.
        status, raw = _post(srv, {
            "messages": [{"role": "user", "content": "hi"}], "max_tokens": 3,
            "temperature": 0, "kubeai_routes": True}, "/v1/chat/completions")
        body = json.loads(raw)
        (start, rows), = [decode_block(b) for b in body["choices"][0]["kubeai_routes"]]
        assert start == 0 and len(rows) == body["usage"]["prompt_tokens"] + 3 - 1
    finally:
        srv.stop()


def test_a_stop_string_ends_the_rows_with_the_tokens_consumed(tiny):
    """The handler cuts the text at the stop string; the rows sent are those
    of the tokens it consumed, P + N - 1 for usage's N."""
    srv = _serve(_engine(tiny))
    try:
        base = {"prompt": "stop me", "max_tokens": 12, "temperature": 0,
                "kubeai_routes": True}
        whole = json.loads(_post(srv, base)[1])["choices"][0]
        text = whole["text"]
        if len(text) < 2:
            pytest.skip("the tiny model served no text to stop on")
        status, raw = _post(srv, {**base, "stop": [text[1]]})
        body = json.loads(raw)
        choice, n = body["choices"][0], body["usage"]["completion_tokens"]
        assert choice["finish_reason"] == "stop" and 0 < n < 12
        assert choice["token_ids"] == whole["token_ids"][:n]
        (start, rows), = [decode_block(b) for b in choice["kubeai_routes"]]
        assert start == 0 and len(rows) == len(base["prompt"]) + n - 1
    finally:
        srv.stop()


def test_blocks_on_the_wire_round_trip_and_join_where_they_touch():
    rows = np.arange(3 * 16 * 2, dtype=np.uint16).reshape(3, 16, 2) * 300
    block = encode_block(37, rows)
    assert block["start"] == 37 and block["rows"] == 3 and block["shape"] == [16, 2]
    assert len(base64.b64decode(block["data"])) == 3 * 16 * 2 * 2
    start, again = decode_block(block)
    assert start == 37 and again.dtype == np.uint16 and np.array_equal(again, rows)
    assert np.array_equal(_by_hand(block)[1], rows)
    with pytest.raises(ValueError):
        decode_block({**block, "rows": 4})
    with pytest.raises(ValueError):
        encode_block(0, rows.astype(np.int32))
    joined = join_blocks([
        (0, rows[:2]), (2, rows[2:]),         # touch: one block
        (0, rows[:1]), (1, rows[1:2]),        # a step back: a new one
        (5, rows[:1]),                        # a gap: a new one
    ])
    assert [(s, len(r)) for s, r in joined] == [(0, 3), (0, 2), (5, 1)]
    assert np.array_equal(joined[0][1], rows)


# ---- (f) tp > 1 ----------------------------------------------------------------------------


@pytest.mark.parametrize("tp", [2, 4])
def test_tensor_parallel_hands_over_the_rows_of_one_device(tiny, devices8, tp):
    """Experts split over the tp axis (two, one a device): ids stay global."""
    prompts = [list(range(1, 12)), list(range(20, 45))]
    single = _run(_engine(tiny, prefill_chunk=16), prompts)
    mesh = build_mesh(MeshConfig(dp=1, sp=1, tp=tp), devices=devices8[:tp])
    sharded = _run(_engine(tiny, mesh=mesh, prefill_chunk=16), prompts)
    for (toks, blocks), (toks1, blocks1) in zip(sharded, single):
        assert toks == toks1
        assert np.array_equal(_rows(blocks)[1], _rows(blocks1)[1])
        assert {int(e) for e in np.unique(_rows(blocks)[1])} == {0, 1, 2, 3}


# ---- (g) the span and the phase -------------------------------------------------------------


def test_step_routes_is_a_span_under_reaps_and_admissions_and_a_step_phase(tiny):
    eng = _engine(tiny, prefill_chunk=32, prefix_cache=True)
    rec = Recorder()
    eng.profiler._annotate = rec
    shared = list(range(1, 41))
    _run(eng, [[5, 6, 7], [8, 9, 10, 11], list(range(60, 80))], ask=[True, False, False])
    _run(eng, [shared + [50]], ask=False)
    _run(eng, [shared + [51, 52]])
    spans = rec.named("step.routes")
    assert {s["parent"] for s in spans} == {"step.reap", "step.admit"}
    for s in spans:
        at = s["attrs"]
        assert set(at) == {"rows", "layers", "k", "bytes", "asked"}
        assert (at["layers"], at["k"]) == (2, 2) and at["bytes"] > 0
    admits = [s["attrs"] for s in spans if s["parent"] == "step.admit"]
    assert [a["rows"] for a in admits] == [7, 20, 41, 10]  # the hit computed 42 - 32
    assert [a["asked"] for a in admits] == [1, 0, 0, 1]
    assert [a["bytes"] for a in admits] == [
        2 * 16 * 4, 32 * 4, (32 + 32) * 4, 32 * 4]  # whole buffers, unsliced
    reaps = [s["attrs"] for s in spans if s["parent"] == "step.reap"]
    assert all(a["bytes"] == 4 * 4 * 2 * 2 for a in reaps)  # chunk x slots x 2 x 2
    assert sum(a["rows"] for a in reaps) == eng.route_stats["rows_decode"]
    assert max(a["asked"] for a in reaps) == 1
    # A phase of the step, and the admission invariant still holds.
    drained = eng.profiler.drain()
    totals = {}
    for phase, seconds in drained:
        totals[phase] = totals.get(phase, 0.0) + seconds
    assert totals["routes"] > 0
    timing = eng.drain_timing()
    host = sum(s for kind, s, *_ in timing if kind == "admit_host")
    wait = sum(s for kind, s, *_ in timing if kind == "admit_wait")
    assert 0 < host + wait <= totals["prefill"]
    assert {s["parent"] for s in rec.named("admit.wait")} == {"step.admit"}
