"""Quantized (int8) paged-KV cache suite (ops/kv_quant, engine int8
mode, handoff wire negotiation): quantization math and edge cases,
greedy token identity vs a bf16 pool (in-process and over real HTTP),
byte-identical quantized wire round trips across handoff / peer fetch /
spill, typed dtype-mismatch refusal at every boundary, the fused
spec-verify host transfer, and the CRD/renderer surface."""

import json
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from testutil import http_get, http_post

from kubeai_tpu.crd.model import (
    KVCacheSpec,
    Model,
    ModelSpec,
    ValidationError,
)
from kubeai_tpu.disagg.handoff import (
    HandoffError,
    KVHandoff,
    KVPageExport,
    deserialize,
    deserialize_pages,
    serialize,
    serialize_pages,
)
from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.quantization import (
    dequantize,
    is_quantized,
    quantize_params,
    quantize_tensor,
    quantized_specs,
)
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama
from kubeai_tpu.objstore import KVSpillStore
from kubeai_tpu.ops.kv_quant import (
    SCALE_FLOOR,
    dequantize_kv,
    kv_capacity_factor,
    quantize_kv,
    resolve_kv_dtype,
)
from kubeai_tpu.routing.prefixchain import ChainComputer

pytestmark = pytest.mark.kvquant

TOK = ByteTokenizer()
PAGE = 16
PROMPT = "the quick brown fox jumps over the lazy dog"


# ---- ops/kv_quant: quantization math ----------------------------------------


def test_kv_quantize_roundtrip_error_bound():
    """Symmetric per-row int8: reconstruction error is at most half a
    quantization step (scale/2) per element."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 5, 4, 32)), jnp.float32)
    q8, scale = quantize_kv(x)
    assert q8.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert q8.shape == x.shape and scale.shape == x.shape[:-1]
    deq = dequantize_kv(q8, scale, jnp.float32)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    bound = np.asarray(scale)[..., None] * (0.5 + 1e-3)
    assert (err <= bound).all()


def test_kv_quantize_zero_rows_are_exact():
    """A zero-variance row (scratch page) clamps to SCALE_FLOOR and
    round-trips to EXACT zeros — not floor-sized noise."""
    x = jnp.zeros((2, 4, 8), jnp.bfloat16)
    q8, scale = quantize_kv(x)
    assert not np.asarray(q8).any()
    assert (np.asarray(scale) == SCALE_FLOOR).all()
    assert not np.asarray(dequantize_kv(q8, scale)).any()


def test_resolve_kv_dtype():
    assert resolve_kv_dtype("") == "bfloat16"
    assert resolve_kv_dtype("bfloat16") == "bfloat16"
    assert resolve_kv_dtype(" INT8 ") == "int8"
    with pytest.raises(ValueError, match="fp8"):
        resolve_kv_dtype("fp8")


def test_kv_capacity_factor_values():
    # 2D/(D+4): the ~2x headline holds at real head dims, not tiny ones.
    assert kv_capacity_factor(128) == pytest.approx(256 / 132)
    assert kv_capacity_factor(128) > 1.9
    assert kv_capacity_factor(16) == pytest.approx(1.6)


# ---- wire format: quantized blobs and tampered headers ----------------------


def _mk_q8_handoff(page_size=8, plen=13, nl=2, kvh=2, d=4, **kw):
    n_pages = -(-plen // page_size)
    rng = np.random.default_rng(plen * page_size + 1)
    shape = (nl, n_pages, page_size, kvh, d)
    fields = dict(
        token_ids=list(range(1, plen + 1)),
        first_token=7,
        first_finish="",
        page_size=page_size,
        dtype="int8",
        k_pages=rng.integers(-127, 128, shape).astype(np.int8),
        v_pages=rng.integers(-127, 128, shape).astype(np.int8),
        seed=42,
        temperature=0.0,
        top_k=0,
        top_p=1.0,
        max_tokens=8,
        k_scales=rng.random(shape[:-1]).astype(np.float32) + 0.01,
        v_scales=rng.random(shape[:-1]).astype(np.float32) + 0.01,
    )
    fields.update(kw)
    return KVHandoff(**fields)


def test_quantized_handoff_roundtrip_byte_identical():
    h = _mk_q8_handoff()
    blob = serialize(h)
    h2 = deserialize(blob)
    assert h2.quantized and h2.dtype == "int8"
    assert h2.k_pages.dtype == np.int8
    assert h2.k_scales.dtype == np.float32
    assert h2.k_pages.tobytes() == h.k_pages.tobytes()
    assert h2.v_pages.tobytes() == h.v_pages.tobytes()
    assert h2.k_scales.tobytes() == h.k_scales.tobytes()
    assert h2.v_scales.tobytes() == h.v_scales.tobytes()
    assert serialize(h2) == blob
    ks, vs = h2.contiguous_scales()
    assert ks.shape == (2, h.plen, 2) and vs.shape == ks.shape


def test_serialize_refuses_scale_dtype_mismatch():
    with pytest.raises(HandoffError, match="requires k_scales"):
        serialize(_mk_q8_handoff(k_scales=None, v_scales=None))
    rng = np.random.default_rng(3)
    with pytest.raises(HandoffError, match="non-quantized dtype"):
        serialize(
            _mk_q8_handoff(
                dtype="float32",
                k_pages=rng.random((2, 2, 8, 2, 4)).astype(np.float32),
                v_pages=rng.random((2, 2, 8, 2, 4)).astype(np.float32),
            )
        )
    h = _mk_q8_handoff()
    with pytest.raises(HandoffError, match="scale shape"):
        serialize(_mk_q8_handoff(k_scales=h.k_scales[:, :1]))


def _retag(blob: bytes, mutate) -> bytes:
    """Rewrite a blob's JSON header in place (body untouched)."""
    (hdr_len,) = struct.unpack("<I", blob[4:8])
    header = json.loads(blob[8 : 8 + hdr_len])
    mutate(header)
    hdr = json.dumps(header).encode()
    return blob[:4] + struct.pack("<I", len(hdr)) + hdr + blob[8 + hdr_len :]


def test_deserialize_refuses_tampered_quant_headers():
    blob = serialize(_mk_q8_handoff())
    with pytest.raises(HandoffError, match="quant scheme"):
        deserialize(
            _retag(blob, lambda h: h["kv_quant"].update(scheme="int4-page"))
        )
    with pytest.raises(HandoffError, match="scale dtype"):
        deserialize(
            _retag(
                blob, lambda h: h["kv_quant"].update(scale_dtype="float16")
            )
        )
    with pytest.raises(HandoffError, match="missing its kv_quant"):
        deserialize(_retag(blob, lambda h: h.pop("kv_quant")))
    # A kv_quant block on a non-int8 blob is refused too.
    rng = np.random.default_rng(4)
    f32 = serialize(
        _mk_q8_handoff(
            dtype="float32",
            k_pages=rng.random((2, 2, 8, 2, 4)).astype(np.float32),
            v_pages=rng.random((2, 2, 8, 2, 4)).astype(np.float32),
            k_scales=None,
            v_scales=None,
        )
    )
    with pytest.raises(HandoffError, match="non-int8"):
        deserialize(
            _retag(
                f32,
                lambda h: h.update(
                    kv_quant={"scheme": "int8-token-head"}
                ),
            )
        )


def _mk_q8_export(n_pages=2, nl=2, kvh=2, d=4, page_size=PAGE):
    rng = np.random.default_rng(n_pages * 7)
    shape = (nl, n_pages, page_size, kvh, d)
    return KVPageExport(
        prefix_hashes=tuple(f"{i:02x}" * 16 for i in range(n_pages)),
        page_size=page_size,
        dtype="int8",
        k_pages=rng.integers(-127, 128, shape).astype(np.int8),
        v_pages=rng.integers(-127, 128, shape).astype(np.int8),
        k_scales=rng.random(shape[:-1]).astype(np.float32) + 0.01,
        v_scales=rng.random(shape[:-1]).astype(np.float32) + 0.01,
    )


def test_quantized_page_export_roundtrip_byte_identical():
    e = _mk_q8_export()
    blob = serialize_pages(e)
    e2 = deserialize_pages(blob)
    assert e2.quantized and e2.dtype == "int8" and e2.n_pages == 2
    assert e2.k_pages.tobytes() == e.k_pages.tobytes()
    assert e2.k_scales.tobytes() == e.k_scales.tobytes()
    assert e2.v_scales.tobytes() == e.v_scales.tobytes()
    assert serialize_pages(e2) == blob


def test_quantized_spill_store_roundtrip():
    """The objstore spill leg ships the same KVP1 blobs: a quantized
    single-page spill fills back byte-identically."""
    e = _mk_q8_export(n_pages=1)
    blob = serialize_pages(e)
    store = KVSpillStore()
    store.put(e.prefix_hashes[0], blob)
    got = store.get(e.prefix_hashes[0])
    assert got == blob
    filled = deserialize_pages(got)
    assert filled.quantized
    assert filled.k_pages.tobytes() == e.k_pages.tobytes()
    assert filled.k_scales.tobytes() == e.k_scales.tobytes()


# ---- weight quantization edge cases (engine/quantization) -------------------


def test_weight_quant_zero_variance_channel_uses_scale_floor():
    rng = np.random.default_rng(11)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    w[:, 2] = 0.0  # a dead output channel must not divide by zero
    q = quantize_tensor(jnp.asarray(w))
    assert is_quantized(q)
    scale = np.asarray(q["scale"])  # [1, out]
    assert scale[0, 2] == pytest.approx(1e-8)
    deq = np.asarray(dequantize(q), np.float32)
    assert not deq[:, 2].any()  # exact zeros, not floor-sized noise
    # int8 step plus the bf16 dequant's ~2^-8 relative rounding.
    assert (np.abs(deq - w) <= scale * 0.5 + np.abs(w) * 0.01).all()


def test_weight_quant_negative_only_channel():
    w = -np.abs(np.random.default_rng(12).standard_normal((8, 3))).astype(
        np.float32
    ) - 0.1
    q = quantize_tensor(jnp.asarray(w))
    w8 = np.asarray(q["w8"])
    assert w8.min() >= -127 and w8.max() <= 0
    deq = np.asarray(dequantize(q), np.float32)
    assert (deq <= 0).all()  # sign survives symmetric quantization
    err = np.abs(deq - w)
    # bf16 dequant adds ~2^-8 relative rounding on top of the int8 step.
    assert (err <= np.asarray(q["scale"]) * 0.5 + np.abs(w) * 0.01).all()


def test_quantized_specs_mirror_tp_sharding():
    """quantized_specs keeps the weight's axes on w8 and replicates the
    scale's singleton input axis while sharding its output axis — the
    invariant that makes int8 weights transparent under tp."""
    rng = np.random.default_rng(13)
    params = {
        "embed": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
        "layers": {
            "wq": jnp.asarray(
                rng.standard_normal((2, 8, 4)), jnp.float32
            ),
            "norm": jnp.ones((2, 8), jnp.float32),
        },
    }
    qp = quantize_params(params)
    leaf = qp["layers"]["wq"]
    assert is_quantized(leaf)
    assert leaf["w8"].shape == (2, 8, 4) and leaf["w8"].dtype == jnp.int8
    assert leaf["scale"].shape == (2, 1, 4)
    assert leaf["scale"].dtype == jnp.float32
    # Non-target leaves pass through untouched.
    assert not is_quantized(qp["layers"]["norm"])
    specs = {
        "embed": (None, "tp"),
        "layers": {"wq": ("layers", "fsdp", "tp"), "norm": ("layers", None)},
    }
    qs = quantized_specs(specs, qp["layers"])
    assert qs["layers"]["wq"] == {
        "w8": ("layers", "fsdp", "tp"),
        "scale": ("layers", None, "tp"),
    }
    assert qs["layers"]["norm"] == ("layers", None)
    assert qs["embed"] == (None, "tp")


# ---- engine: int8 mode, refusals, token identity ----------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(vocab_size=TOK.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def raw(tiny):
    """One bf16 and one int8 engine over the SAME weights — the pair
    every identity and refusal check below compares across."""
    cfg, params = tiny

    def mk(**kw):
        return Engine(
            "llama", cfg, params,
            cfg=EngineConfig(
                num_slots=4, max_seq_len=128, page_size=PAGE,
                decode_chunk=4, **kw,
            ),
            eos_token_ids=TOK.eos_token_ids,
        )

    return {"bf16": mk(), "int8": mk(kv_dtype="int8")}


@pytest.mark.parametrize(
    "kw,msg", [(dict(speculate=2), "speculative")], ids=["speculation"],
)
def test_int8_engine_config_refusals(tiny, kw, msg):
    cfg, params = tiny
    with pytest.raises(ValueError, match=msg):
        Engine(
            "llama", cfg, params,
            cfg=EngineConfig(
                num_slots=2, max_seq_len=64, kv_dtype="int8", **kw
            ),
            eos_token_ids=TOK.eos_token_ids,
        )


@pytest.mark.parametrize(
    "kv_dtype,layout",
    [("", "fused"), ("bfloat16", "fused"), ("int8", "per_layer")],
    ids=["unset", "bfloat16", "int8"],
)
def test_the_pool_decides_the_decode_layout(tiny, kv_dtype, layout):
    """The only input is the pool's kind: the Pallas kernels read bf16
    pages, so an int8 pool takes scatter-then-attend and every other the
    stacked pool in place."""
    cfg, params = tiny
    eng = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64, kv_dtype=kv_dtype),
        eos_token_ids=TOK.eos_token_ids,
    )
    assert eng.decode_kernel == layout
    assert eng.kv_layout == {"fused": "stacked"}.get(layout, layout)


def _greedy(eng, prompts, max_tokens=8):
    outs, rids = {}, []
    for p in prompts:
        rid = eng.add_request(
            TOK.encode(p),
            SamplingParams(temperature=0.0, max_tokens=max_tokens, seed=7),
        )
        rids.append(rid)
        outs[rid] = []
    while eng.has_work():
        for ev in eng.step():
            outs[ev.rid].append(ev.token)
    return [outs[r] for r in rids]


def test_greedy_decode_token_identical_in_process(raw):
    """The tentpole acceptance bar, in-process: int8 KV changes HBM
    bytes, not tokens — greedy streams match bf16 exactly."""
    prompts = [PROMPT, "pack my box with five dozen jugs", "a" * 40]
    ref = _greedy(raw["bf16"], prompts)
    got = _greedy(raw["int8"], prompts)
    assert got == ref
    assert all(len(t) == 8 for t in ref)


def test_kv_cache_info_reports_quantization(raw):
    bf = raw["bf16"].kv_cache_info()
    q8 = raw["int8"].kv_cache_info()
    assert bf["dtype"] == "bfloat16" and not bf["quantized"]
    assert q8["dtype"] == "int8" and q8["quantized"]
    assert bf["capacity_factor"] == 1.0
    assert q8["capacity_factor"] == pytest.approx(kv_capacity_factor(16))
    # Same page geometry, strictly smaller resident pool.
    assert q8["num_pages"] == bf["num_pages"]
    assert q8["pool_bytes"] < bf["pool_bytes"]
    d = 16  # tiny llama head_size
    assert q8["pool_bytes"] / bf["pool_bytes"] == pytest.approx(
        (d + 4) / (2 * d)
    )


def test_in_process_handoff_dtype_mismatch_refused(raw):
    """bf16 and int8 pools refuse each other's handoffs with a typed
    error — never a silent astype."""
    ids = TOK.encode(PROMPT)
    sp = SamplingParams(temperature=0.0, max_tokens=6, seed=1)
    h_bf = raw["bf16"].export_handoff(ids, sp)
    h_q8 = raw["int8"].export_handoff(ids, sp)
    assert h_bf.dtype == "bfloat16" and not h_bf.quantized
    assert h_q8.dtype == "int8" and h_q8.quantized
    with pytest.raises(HandoffError, match="dtype"):
        raw["int8"].import_handoff(h_bf)
    with pytest.raises(HandoffError, match="dtype"):
        raw["bf16"].import_handoff(h_q8)


def test_in_process_quantized_handoff_wire_identity(raw):
    """An engine-exported int8 handoff survives the wire byte-for-byte:
    pages AND scales, and re-serialization is stable."""
    h = raw["int8"].export_handoff(
        TOK.encode(PROMPT), SamplingParams(temperature=0.0, max_tokens=6)
    )
    blob = serialize(h)
    h2 = deserialize(blob)
    assert h2.quantized
    assert h2.k_pages.tobytes() == np.asarray(h.k_pages).tobytes()
    assert h2.v_pages.tobytes() == np.asarray(h.v_pages).tobytes()
    assert h2.k_scales.tobytes() == np.asarray(h.k_scales).tobytes()
    assert h2.v_scales.tobytes() == np.asarray(h.v_scales).tobytes()
    assert serialize(h2) == blob


def test_page_export_dtype_mismatch_refused(qfleet):
    """The peer-fetch import path refuses cross-dtype page exports the
    same way (tiny llama geometry: 2L, 2KVH, 16D). Runs on the fleet's
    prefix-cache-enabled engines — the only pools that import pages."""
    import ml_dtypes

    shape = (2, 1, PAGE, 2, 16)
    q8 = KVPageExport(
        prefix_hashes=("aa" * 16,), page_size=PAGE, dtype="int8",
        k_pages=np.ones(shape, np.int8), v_pages=np.ones(shape, np.int8),
        k_scales=np.ones(shape[:-1], np.float32),
        v_scales=np.ones(shape[:-1], np.float32),
    )
    bf = KVPageExport(
        prefix_hashes=("aa" * 16,), page_size=PAGE, dtype="bfloat16",
        k_pages=np.ones(shape, ml_dtypes.bfloat16),
        v_pages=np.ones(shape, ml_dtypes.bfloat16),
    )
    with pytest.raises(HandoffError, match="dtype"):
        _inner(qfleet["bf16"]).import_prefix_pages(q8)
    with pytest.raises(HandoffError, match="dtype"):
        _inner(qfleet["a8"]).import_prefix_pages(bf)


# ---- satellite: fused spec-verify host transfer -----------------------------


def test_spec_verify_fuses_host_transfer(tiny, monkeypatch):
    """_process_spec must fetch choices AND n_emit in ONE device_get (two
    sequential transfers would double per-verify-step readback), and
    charge readback exactly once per invocation through the profiler."""
    cfg, params = tiny
    eng = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(
            num_slots=2, max_seq_len=128, page_size=PAGE,
            speculate=2, spec_adaptive=False,
        ),
        eos_token_ids=TOK.eos_token_ids,
    )
    calls = {"invocations": 0, "gets": 0, "syncs": 0, "depth": 0}
    orig_get = jax.device_get
    orig_spec = Engine._process_spec
    orig_span = eng.profiler.span

    def counting_get(x):
        if calls["depth"]:
            calls["gets"] += 1
        return orig_get(x)

    def counting_spec(self, choices, n_emit, chunk_slots):
        calls["invocations"] += 1
        calls["depth"] += 1
        try:
            return orig_spec(self, choices, n_emit, chunk_slots)
        finally:
            calls["depth"] -= 1

    def counting_span(name, **attrs):
        if calls["depth"] and name == "step.readback":
            calls["syncs"] += 1
        return orig_span(name, **attrs)

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(Engine, "_process_spec", counting_spec)
    monkeypatch.setattr(eng.profiler, "span", counting_span)
    # Repetitive prompt: prompt-lookup proposals get real acceptances.
    eng.add_request(
        TOK.encode("ab ab ab ab ab ab ab ab"),
        SamplingParams(temperature=0.0, max_tokens=12, seed=0),
    )
    while eng.has_work():
        eng.step()
    assert calls["invocations"] >= 1
    assert calls["gets"] == calls["invocations"]  # ONE fused transfer
    assert calls["syncs"] == calls["invocations"]  # charged exactly once
    # The phase reached the profiler's step records.
    specced = [
        r for r in eng.profiler.recent() if "readback" in r["phases_s"]
    ]
    assert specced


# ---- CRD + renderer surface -------------------------------------------------


def _mk_model(**spec_kw):
    spec_kw.setdefault("url", "hf://org/m")
    spec = ModelSpec(autoscaling_disabled=True, replicas=1, **spec_kw)
    m = Model(name="m", spec=spec)
    m.validate()
    return m


def test_crd_kv_cache_validation():
    with pytest.raises(ValidationError, match="kvCache.dtype"):
        _mk_model(kv_cache=KVCacheSpec(dtype="fp8"))
    with pytest.raises(ValidationError, match="speculativeTokens"):
        _mk_model(
            kv_cache=KVCacheSpec(dtype="int8"), speculative_tokens=2
        )
    with pytest.raises(ValidationError, match="KubeAITPU"):
        _mk_model(
            url="ollama://gemma2:2b", engine="OLlama",
            kv_cache=KVCacheSpec(dtype="int8"),
        )
    m = _mk_model(kv_cache=KVCacheSpec(dtype="int8"))
    assert m.spec.kv_cache.enabled()


def test_renderer_emits_kv_dtype_flag():
    from kubeai_tpu.config import System
    from kubeai_tpu.operator.engines import render_pod, resolve_model_config

    cfg = System().default_and_validate()
    m = _mk_model(kv_cache=KVCacheSpec(dtype="int8"))
    pod = render_pod(m, cfg, resolve_model_config(m, cfg), "x")
    args = pod["spec"]["containers"][0]["args"]
    assert args[args.index("--kv-dtype") + 1] == "int8"
    plain = _mk_model()
    pod = render_pod(plain, cfg, resolve_model_config(plain, cfg), "x")
    assert "--kv-dtype" not in pod["spec"]["containers"][0]["args"]


# ---- real-HTTP fleet: identity, two-hop, peer fetch, refusals ---------------


@pytest.fixture(scope="module")
def qfleet(tiny):
    """Five EngineServers over ONE tiny llama: a bf16 sharing replica, two
    int8 sharing replicas, and an int8 prefill/decode pair — every
    KV-byte tier (handoff, peer fetch, spill) exercised over real
    sockets in both dtypes."""
    cfg, params = tiny

    def ecfg(**kw):
        return EngineConfig(
            num_slots=4, max_seq_len=128, page_size=PAGE,
            prefill_chunk=32, decode_chunk=4, prefix_cache=True, **kw,
        )

    plans = {
        "bf16": (ecfg(), dict(kv_sharing=True, kv_spill_store=KVSpillStore())),
        "a8": (
            ecfg(kv_dtype="int8"),
            dict(kv_sharing=True, kv_spill_store=KVSpillStore()),
        ),
        "b8": (ecfg(kv_dtype="int8"), dict(kv_sharing=True)),
        "p8": (ecfg(kv_dtype="int8"), dict(role="prefill")),
        "d8": (ecfg(kv_dtype="int8"), dict(role="decode")),
    }
    servers = {}
    for name, (ec, kw) in plans.items():
        eng = Engine(
            "llama", cfg, params, cfg=ec, eos_token_ids=TOK.eos_token_ids
        )
        srv = EngineServer(eng, TOK, "tiny", host="127.0.0.1", port=0, **kw)
        srv.start()
        servers[name] = srv
    yield servers
    for srv in servers.values():
        srv.stop()


def _addr(srv):
    return f"127.0.0.1:{srv.port}"


def _gen(srv, req, headers=None):
    st, body = http_post(_addr(srv), "/v1/completions", req, headers=headers)
    assert st == 200, body
    return json.loads(body)["choices"][0]


def _inner(srv):
    return getattr(srv.engine, "inner", srv.engine)


def _post_blob(addr, path, blob, headers=None):
    import http.client

    host, _, port = addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    hdrs = {"Content-Length": str(len(blob))}
    hdrs.update(headers or {})
    conn.request("POST", path, body=blob, headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_http_greedy_identical_bf16_vs_int8(qfleet):
    req = {"model": "tiny", "prompt": PROMPT, "max_tokens": 12,
           "temperature": 0, "seed": 11}
    ref = _gen(qfleet["bf16"], req)
    got = _gen(qfleet["a8"], req)
    assert got["text"] == ref["text"]
    assert got["finish_reason"] == ref["finish_reason"]


def test_http_state_and_metrics_expose_quantization(qfleet):
    st, body = http_get(_addr(qfleet["a8"]), "/v1/state")
    state = json.loads(body)
    kv = state["kv_cache"]
    assert kv["dtype"] == "int8" and kv["quantized"]
    assert kv["capacity_factor"] == pytest.approx(kv_capacity_factor(16))
    # Which decode layout was compiled follows the pool's kind.
    assert kv["kv_layout"] == "per_layer"
    st, body = http_get(_addr(qfleet["bf16"]), "/v1/state")
    assert json.loads(body)["kv_cache"]["kv_layout"] == "stacked"
    st, body = http_get(_addr(qfleet["a8"]), "/metrics")
    text = body.decode()
    assert "kubeai_engine_kv_quant_enabled 1" in text
    assert "kubeai_engine_kv_quant_capacity_factor 1.6" in text
    assert "kubeai_engine_kv_cache_bytes" in text
    st, body = http_get(_addr(qfleet["bf16"]), "/metrics")
    assert "kubeai_engine_kv_quant_enabled 0" in body.decode()


@pytest.mark.parametrize(
    "sampling",
    [
        {"temperature": 0, "seed": 17},
        {"temperature": 0.8, "top_k": 8, "seed": 17},
    ],
    ids=["greedy", "seeded-sampling"],
)
def test_http_int8_two_hop_token_identical_to_unified(qfleet, sampling):
    """Disagg over quantized pools: the int8 prefill->decode pair streams
    token-identically to an int8 unified replica — the wire carried the
    pages+scales verbatim, so the decode pool is byte-equal."""
    prompt = f"two hop t={sampling['temperature']} {PROMPT}"
    req = {"model": "tiny", "prompt": prompt, "max_tokens": 16, **sampling}
    ref = _gen(qfleet["a8"], req)
    st, body = http_post(
        _addr(qfleet["p8"]), "/v1/completions", req,
        headers={"X-Disagg-Transfer": _addr(qfleet["d8"])},
    )
    assert st == 200, body
    receipt = json.loads(body)
    assert receipt["object"] == "kv.handoff"
    st, body = http_post(
        _addr(qfleet["d8"]), "/v1/completions", req,
        headers={"X-Disagg-Handoff": receipt["handoff_id"]},
    )
    assert st == 200, body
    got = json.loads(body)["choices"][0]
    assert got["text"] == ref["text"]
    assert got["finish_reason"] == ref["finish_reason"]


def test_http_import_refuses_bf16_blob_on_int8_decode(qfleet, raw):
    """A bf16 handoff blob POSTed to an int8 decode pool is refused with
    a typed 400 — at import or at admission, never a silent cast."""
    h = raw["bf16"].export_handoff(
        TOK.encode("mismatch handoff prompt"),
        SamplingParams(temperature=0.0, max_tokens=6, seed=2),
    )
    st, body = _post_blob(_addr(qfleet["d8"]), "/v1/kv/import", serialize(h))
    if st == 200:
        receipt = json.loads(body)
        st, body = http_post(
            _addr(qfleet["d8"]), "/v1/completions",
            {"model": "tiny", "prompt": "mismatch handoff prompt",
             "max_tokens": 6, "temperature": 0},
            headers={"X-Disagg-Handoff": receipt["handoff_id"]},
        )
    assert st == 400
    assert b"dtype" in body


def test_http_import_refuses_tampered_quant_blob(qfleet, raw):
    blob = serialize(
        raw["int8"].export_handoff(
            TOK.encode("tampered scheme prompt"),
            SamplingParams(temperature=0.0, max_tokens=6, seed=3),
        )
    )
    bad = _retag(blob, lambda h: h["kv_quant"].update(scheme="int4-page"))
    st, body = _post_blob(_addr(qfleet["d8"]), "/v1/kv/import", bad)
    assert st == 400
    assert b"quant scheme" in body


def test_http_int8_peer_fetch_identity_and_byte_equality(qfleet):
    """Peer prefix fetch between two int8 replicas: token-identical to
    the bf16 reference, and the fetched pages + scales are byte-equal to
    the holder's."""
    prompt = f"peer fetch story {PROMPT}"
    req = {"model": "tiny", "prompt": prompt, "max_tokens": 12,
           "temperature": 0, "seed": 5}
    ref = _gen(qfleet["bf16"], req)
    _gen(qfleet["a8"], req)  # warm the holder
    st, body = http_get(_addr(qfleet["a8"]), "/v1/state")
    state = json.loads(body)
    chain = ChainComputer(PAGE).chain_for_request(req, chat=False)
    assert chain and set(chain) <= set(state["kv_holdings"])

    before = _inner(qfleet["b8"]).kv_share_stats["imported_pages"]
    got = _gen(
        qfleet["b8"], req, headers={"X-KV-Source": _addr(qfleet["a8"])}
    )
    assert got["text"] == ref["text"]
    assert _inner(qfleet["b8"]).kv_share_stats["imported_pages"] > before
    assert qfleet["b8"].metrics.kv_fetch_bytes.get() > 0

    a_exp = _inner(qfleet["a8"]).export_prefix_pages(chain)
    b_exp = _inner(qfleet["b8"]).export_prefix_pages(chain)
    assert a_exp.quantized and b_exp.quantized
    assert a_exp.dtype == b_exp.dtype == "int8"
    assert np.array_equal(
        np.asarray(a_exp.k_pages), np.asarray(b_exp.k_pages)
    )
    assert np.array_equal(
        np.asarray(a_exp.v_pages), np.asarray(b_exp.v_pages)
    )
    assert np.array_equal(
        np.asarray(a_exp.k_scales), np.asarray(b_exp.k_scales)
    )
    assert np.array_equal(
        np.asarray(a_exp.v_scales), np.asarray(b_exp.v_scales)
    )


def test_http_cross_dtype_fetch_degrades_to_recompute(qfleet):
    """A bf16 replica pointed at an int8 holder: the fetch is refused
    (HandoffError), the failure counter rises, nothing is imported, and
    the request recomputes with the correct answer — degradation, not
    corruption, not failure."""
    prompt = "a wholly distinct saga of dtype disagreement"
    req = {"model": "tiny", "prompt": prompt, "max_tokens": 10,
           "temperature": 0, "seed": 9}
    _gen(qfleet["a8"], req)  # int8 holder warms and advertises
    ref = _gen(qfleet["b8"], req)  # int8 self-reference (greedy)
    bf = qfleet["bf16"]
    fails = bf.metrics.kv_fetch_failures.get(source="peer")
    imported = _inner(bf).kv_share_stats["imported_pages"]
    got = _gen(bf, req, headers={"X-KV-Source": _addr(qfleet["a8"])})
    assert got["text"] == ref["text"]
    assert bf.metrics.kv_fetch_failures.get(source="peer") > fails
    assert _inner(bf).kv_share_stats["imported_pages"] == imported
