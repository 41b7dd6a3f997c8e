"""Serverless-grade cold start: snapshot publish/restore round-trips on
a real file:// bucket, greedy-decode token identity between a snapshot-
restored engine and its full-load twin — in process AND over real HTTP —
plus the orbax round-trip satellites (plain, `like=`, 8-device sharded
layout)."""

import contextlib
import json

import jax
import numpy as np
import pytest

from testutil import http_get, http_post

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.coldstart import ColdStartManager
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama
from kubeai_tpu.parallel.mesh import single_device_mesh

pytestmark = pytest.mark.coldstart

ECFG = dict(num_slots=4, max_seq_len=128, decode_chunk=4)


def _reset_compilation_cache():
    with contextlib.suppress(Exception):
        jax.config.update("jax_compilation_cache_dir", None)


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    """Two boots of the same tiny model against one file:// snapshot
    bucket: the first full-loads and publishes, the second restores.
    Yields (full_mgr, full_params, restored_mgr, restored_params)."""
    root = tmp_path_factory.mktemp("snap-bucket")
    url = "file://" + str(root / "snaps")
    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    mesh = single_device_mesh()

    mgr1 = ColdStartManager(
        url, "snap-llama", ECFG, mesh,
        work_dir=str(root / "boot1"),
    )
    params1 = mgr1.acquire_params(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(7))
    )
    assert mgr1.tracker.restored is False
    assert mgr1.maybe_publish(params1) is True

    # Second boot, same fingerprint: must restore. The full-load
    # fallback initializes from a DIFFERENT key, so a silent fallback
    # would break token identity rather than mask it.
    mgr2 = ColdStartManager(
        url, "snap-llama", ECFG, mesh,
        work_dir=str(root / "boot2"),
    )
    template = llama.init_params(cfg, jax.random.PRNGKey(0))
    params2 = mgr2.acquire_params(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(1)),
        like=template,
    )
    assert mgr2.tracker.restored is True
    assert "restored" in mgr2.tracker.events
    yield tok, cfg, mgr1, params1, mgr2, params2
    _reset_compilation_cache()


def _engine(cfg, params, tok):
    return Engine(
        "llama", cfg, params, cfg=EngineConfig(**ECFG),
        eos_token_ids=tok.eos_token_ids,
    )


def test_publish_then_restore_round_trip(boots):
    _tok, _cfg, mgr1, params1, mgr2, params2 = boots
    assert mgr1.fingerprint == mgr2.fingerprint
    assert "published" in mgr1.tracker.events
    # The restored tree is bit-identical to the published one.
    for a, b in zip(jax.tree.leaves(params1), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Both boots phase-timed for the forecaster: load on the full path,
    # fetch+restore on the snapshot path.
    assert "load" in mgr1.tracker.phases
    assert "fetch" in mgr2.tracker.phases and "restore" in mgr2.tracker.phases
    assert "load" not in mgr2.tracker.phases


def test_greedy_decode_token_identity_in_process(boots):
    tok, cfg, _mgr1, params1, _mgr2, params2 = boots
    prompt = tok.encode("The cold start was")
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    full = _engine(cfg, params1, tok).generate([prompt], sp)[0]
    restored = _engine(cfg, params2, tok).generate([prompt], sp)[0]
    assert full == restored
    assert len(full) > 0


@pytest.fixture(scope="module")
def servers(boots):
    """The same two engines behind real HTTP sockets, each carrying its
    boot's cold_start record."""
    tok, cfg, mgr1, params1, mgr2, params2 = boots
    out = []
    for mgr, params in ((mgr1, params1), (mgr2, params2)):
        srv = EngineServer(
            _engine(cfg, params, tok), tok, "snap-llama",
            host="127.0.0.1", port=0,
            cold_start=mgr.tracker.snapshot(),
        )
        srv.start()
        out.append(srv)
    yield out
    for srv in out:
        srv.stop()


def test_greedy_decode_token_identity_over_http(servers):
    full_srv, restored_srv = servers
    payload = {
        "model": "snap-llama",
        "prompt": "Hello, snapshots!",
        "max_tokens": 12,
        "temperature": 0,
    }
    texts = []
    for srv in (full_srv, restored_srv):
        status, body = http_post(
            f"127.0.0.1:{srv.port}", "/v1/completions", payload
        )
        assert status == 200, body
        texts.append(json.loads(body)["choices"][0]["text"])
    assert texts[0] == texts[1]
    assert texts[0]


def test_state_and_metrics_expose_boot_path(servers):
    full_srv, restored_srv = servers
    for srv, restored in ((full_srv, False), (restored_srv, True)):
        status, body = http_get(f"127.0.0.1:{srv.port}", "/v1/state")
        assert status == 200
        cs = json.loads(body)["cold_start"]
        assert cs["restored"] is restored
        assert cs["fingerprint"]
        status, body = http_get(f"127.0.0.1:{srv.port}", "/metrics")
        assert status == 200
        text = body.decode()
        assert f"kubeai_coldstart_restored {1 if restored else 0}" in text
        assert "kubeai_coldstart_phase_seconds" in text


def test_snapshot_carries_the_cache_dir_in_effect(tmp_path):
    """The manager owns no cache directory: publish bundles whatever
    directory JAX's persistent cache is set to, and restore copies the
    bundle's entries into the directory in effect on THAT boot — so a
    cache placed from outside (JAX_COMPILATION_CACHE_DIR) is the one a
    snapshot fills."""
    url = "file://" + str(tmp_path / "snaps")
    mesh = single_device_mesh()
    cache_a, cache_b = tmp_path / "cache_a", tmp_path / "cache_b"
    cache_a.mkdir()
    cache_b.mkdir()
    (cache_a / "jit_step-abc-cache").write_bytes(b"compiled")
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    try:
        jax.config.update("jax_compilation_cache_dir", str(cache_a))
        mgr1 = ColdStartManager(
            url, "m", ECFG, mesh, work_dir=str(tmp_path / "boot1")
        )
        assert not hasattr(mgr1, "cache_dir")
        assert mgr1.maybe_publish(mgr1.acquire_params(lambda: tree)) is True

        jax.config.update("jax_compilation_cache_dir", str(cache_b))
        mgr2 = ColdStartManager(
            url, "m", ECFG, mesh, work_dir=str(tmp_path / "boot2")
        )
        restored = mgr2.acquire_params(lambda: 1 / 0)
        assert mgr2.tracker.restored is True
        np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
        assert (cache_b / "jit_step-abc-cache").read_bytes() == b"compiled"
    finally:
        _reset_compilation_cache()


# ---- orbax round-trip satellites ---------------------------------------------


def test_orbax_roundtrip_plain_and_like(tmp_path):
    from kubeai_tpu.engine.weights import (
        load_native_checkpoint,
        save_native_checkpoint,
    )

    tree = {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "layers": {"b": np.ones((5,), dtype=np.int32)},
    }
    path = str(tmp_path / "ckpt")
    save_native_checkpoint(path, tree)
    plain = load_native_checkpoint(path)
    np.testing.assert_array_equal(np.asarray(plain["w"]), tree["w"])
    np.testing.assert_array_equal(
        np.asarray(plain["layers"]["b"]), tree["layers"]["b"]
    )
    # `like=` pins the tree structure and dtypes to the target template.
    like = jax.tree.map(jax.numpy.zeros_like, tree)
    typed = load_native_checkpoint(path, like=like)
    assert typed["layers"]["b"].dtype == np.int32
    np.testing.assert_array_equal(np.asarray(typed["w"]), tree["w"])


def test_orbax_roundtrip_sharded_layout(tmp_path, devices8):
    """A tree sharded over the 8-device virtual mesh survives the
    save/restore cycle with values AND layout intact — the property the
    snapshot fingerprint's mesh signature protects."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from kubeai_tpu.engine.weights import (
        load_native_checkpoint,
        save_native_checkpoint,
    )

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("data", "model"))
    sharding = NamedSharding(mesh, PartitionSpec(None, "model"))
    host = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
    arr = jax.device_put(host, sharding)
    path = str(tmp_path / "sharded")
    save_native_checkpoint(path, {"w": arr})
    like = {"w": jax.device_put(np.zeros_like(host), sharding)}
    restored = load_native_checkpoint(path, like=like)
    np.testing.assert_array_equal(np.asarray(restored["w"]), host)
    assert restored["w"].sharding.is_equivalent_to(sharding, arr.ndim)
