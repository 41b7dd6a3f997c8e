"""The routed comparison's parts, each alone: what the load generator asks
and keeps, the row rule, a reference that follows given expert sets, the
readings taken from them, and the planted router fault."""

import base64
import http.server
import importlib
import json
import os
import threading

import numpy as np
import pytest

from perf import check, loadgen, readers, traffic
from perf.tokenizer import text_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOE = {"experts": 4, "k": 2, "routed_layers": 2, "routes": True}


def block(start, rows, dtype="uint8", shape=None, data=None):
    rows = np.asarray(rows)
    return {"start": start, "rows": len(rows),
            "shape": shape or list(rows.shape[1:]), "dtype": dtype,
            "data": data or base64.b64encode(
                rows.astype(check.ROUTE_DTYPES[dtype]).tobytes()).decode()}


def some_rows(n, seed=0):
    """n rows [2 layers, 2 distinct experts of 4]."""
    rng = np.random.default_rng(seed)
    return np.stack([[rng.permutation(4)[:2] for _ in range(2)] for _ in range(n)])


# ---- what the load generator sends and keeps -------------------------------


def test_a_request_body_is_todays_unless_routes_are_asked():
    req = {"index": 3, "prompt_len": 9, "max_tokens": 5}
    today = json.dumps({
        "model": "m", "prompt": text_of(traffic.prompt_tokens(11, 3, 9, 512)),
        "max_tokens": 5, "temperature": 0.0, "stream": True})
    assert loadgen.request_body("m", req, 512, 11) == today
    asked = json.loads(loadgen.request_body("m", req, 512, 11, routes=True))
    assert asked == {**json.loads(today), "kubeai_routes": True}


class SSE(http.server.BaseHTTPRequestHandler):
    """A server that streams three chunks: the first token with the
    prompt's block, an empty delta that carries a token and its row, and
    the finish chunk with `[]`."""

    bodies: list = []
    rows = some_rows(4)

    def do_POST(self):
        SSE.bodies.append(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        chunks = [
            {"token_ids": [7], "kubeai_routes": [block(0, self.rows[:3])]},
            {"token_ids": [8], "choices": [{"text": ""}],
             "kubeai_routes": [block(3, self.rows[3:])]},
            {"token_ids": [], "kubeai_routes": []},
        ]
        for c in chunks:
            self.wfile.write(b"data: " + json.dumps(c).encode() + b"\n\n")
        self.wfile.write(b"data: [DONE]\n\n")

    def log_message(self, *a):
        pass


@pytest.fixture()
def sse_port():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), SSE)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    SSE.bodies.clear()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()


@pytest.mark.parametrize("routes", [False, True])
def test_the_load_generator_keeps_route_blocks_only_where_it_asked(sse_port, routes):
    req = {"index": 0, "prompt_len": 3, "max_tokens": 2}
    clock = loadgen.Clock(0.0)
    rec = loadgen.one_request("127.0.0.1", sse_port, "m", req, 512, 5, clock,
                              10.0, routes=routes)
    assert rec["ok"] and rec["token_ids"] == [7, 8]
    assert [n for _, n in rec["events"]] == [1, 1]
    assert ("kubeai_routes" in json.loads(SSE.bodies[0])) is routes
    if not routes:
        # Byte for byte what a dense cell always sent and kept.
        assert SSE.bodies[0].decode() == loadgen.request_body("m", req, 512, 5)
        assert set(rec) == {"index", "due", "prompt_len", "max_tokens", "ok",
                            "events", "token_ids", "sent", "status", "end"}
        return
    assert [b["start"] for b in rec["routes"]] == [0, 3]  # arrival order
    rows = check.assemble_routes(rec["routes"], 3 + 2 - 1, MOE)
    np.testing.assert_array_equal(rows, SSE.rows)


# ---- the row rule -----------------------------------------------------------

ROWS = some_rows(7, seed=1)  # P = 5, N = 3: positions 0..6


def _bad_id():
    rows = ROWS.copy()
    rows[2, 1] = [1, 4]
    return [block(0, rows)]


def _twice_in_a_set():
    rows = ROWS.copy()
    rows[4, 0] = [3, 3]
    return [block(0, rows)]


GOOD = {
    "one block": [block(0, ROWS)],
    "prefill then a row a token": [block(0, ROWS[:5]), block(5, ROWS[5:6]), block(6, ROWS[6:])],
    "uint16 ids": [block(0, ROWS, "uint16")],
    "a re-admission after a preemption sends everything held again": [
        block(0, some_rows(5, seed=9)), block(5, some_rows(1, seed=8)),
        block(0, ROWS[:6]), block(6, ROWS[6:])],
}
BAD = {
    "a missing row": [block(0, ROWS[:5]), block(6, ROWS[6:])],
    "a missing last row": [block(0, ROWS[:6])],
    "a surplus row": [block(0, ROWS), block(7, ROWS[:1])],
    "a twice-sent row": [block(0, ROWS[:6]), block(5, ROWS[5:6]), block(6, ROWS[6:])],
    "a twice-sent last row": [block(0, ROWS), block(6, ROWS[6:])],
    "rows that do not start at 0": [block(1, ROWS[1:])],
    "a step back that leaves rows it does not recompute": [
        block(0, ROWS[:6]), block(0, ROWS[:3]), block(6, ROWS[6:])],
    "another shape than /v1/state says": [block(0, ROWS[:, :1])],
    "a header that lies about its bytes": [block(0, ROWS, shape=[2, 3])],
    "bytes that are not base64": [block(0, ROWS, data="@@@")],
    "an unknown dtype": [{**block(0, ROWS), "dtype": "int64"}],
    "a block without a key": [{k: v for k, v in block(0, ROWS).items() if k != "start"}],
    "an expert id the model has not": _bad_id(),
    "one expert twice in a set": _twice_in_a_set(),
    "no block at all": [],
    "no routes on the record": None,
}


@pytest.mark.parametrize("name", sorted(GOOD))
def test_the_row_rule_takes(name):
    rows = check.assemble_routes(GOOD[name], 7, MOE)
    np.testing.assert_array_equal(rows, ROWS)


@pytest.mark.parametrize("name", sorted(BAD))
def test_the_row_rule_refuses(name):
    assert check.assemble_routes(BAD[name], 7, MOE) is None


# ---- a reference that follows ----------------------------------------------


@pytest.fixture(scope="module")
def mixtral():
    import jax

    with open(os.path.join(ROOT, "perf", "configs", "tiny-mixtral.json")) as f:
        cfg = json.load(f)
    ref = importlib.import_module("perf.reference.mixtral")
    tokens = np.random.default_rng(2).integers(0, cfg["vocab_size"], 40).tolist()
    seqs = [(tokens, [38, 39]), (tokens[:25], [24])]
    return cfg, ref, jax.random.PRNGKey(8), seqs, {"pad_to": 64, "rows_pad": 8}


def test_only_a_routed_reference_takes_routes(mixtral):
    assert check.takes_routes(mixtral[1])
    assert not check.takes_routes(importlib.import_module("perf.reference.mistral"))


def test_following_its_own_sets_changes_nothing_and_trails_by_nothing(mixtral):
    cfg, ref, key, seqs, padding = mixtral
    plain = ref.forward(cfg, key, seqs, **padding)
    logits, own, trail = ref.forward(cfg, key, seqs, routes=[None, None], **padding)
    assert [o.shape for o in own] == [(40, 2, 2), (25, 2, 2)]
    assert [t.shape for t in trail] == [(40, 2), (25, 2)]
    again, own2, trail2 = ref.forward(cfg, key, seqs, routes=own, **padding)
    for a, b, c in zip(plain, logits, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert all((t == 0).all() for t in trail + trail2)
    # The order inside a set is the router's, and does not matter to a
    # follower: the weights are the softmax over the set.
    flipped, own3, trail3 = ref.forward(
        cfg, key, seqs, routes=[o[..., ::-1] for o in own], **padding)
    np.testing.assert_allclose(np.asarray(flipped[0]), np.asarray(plain[0]), atol=1e-6)
    assert all((t == 0).all() for t in trail3)
    np.testing.assert_array_equal(own3[0], own[0])


def test_a_given_set_that_is_not_its_own_is_computed_and_trails(mixtral):
    cfg, ref, key, seqs, padding = mixtral
    plain, own, _ = ref.forward(cfg, key, seqs, routes=[None, None], **padding)
    given = [o.copy() for o in own]
    best, second = own[0][10, 0]
    other = next(e for e in range(cfg["num_local_experts"]) if e not in (best, second))
    given[0][10, 0] = [best, other]  # position 10, layer 0: the k-th swapped
    logits, own2, trail = ref.forward(cfg, key, seqs, routes=given, **padding)
    assert trail[0][10, 0] > 0 and (trail[1] == 0).all()
    assert np.count_nonzero(trail[0][:, 0]) == 1  # layer 0: that decision alone
    np.testing.assert_array_equal(own2[0][:, 0], own[0][:, 0])
    assert np.abs(np.asarray(logits[0]) - np.asarray(plain[0])).max() > 1e-6
    # Causal: the other sequence, and nothing before position 10, moved.
    np.testing.assert_array_equal(np.asarray(logits[1]), np.asarray(plain[1]))
    r, decisions = check._route_readings(np, given, own2, trail)
    assert decisions == (40 + 25) * 2
    assert r["followed_share"] >= 1 / decisions
    assert r["route_trail_max"] == trail[0].max()


def test_rows_beyond_the_sequence_are_refused(mixtral):
    cfg, ref, key, seqs, padding = mixtral
    _, own, _ = ref.forward(cfg, key, seqs, routes=[None, None], **padding)
    with pytest.raises(ValueError):
        ref.forward(cfg, key, seqs, routes=[own[0], own[0]], **padding)


def test_the_lower_precisions_take_other_sets(mixtral):
    cfg, ref, key, seqs, padding = mixtral
    _, own, _ = ref.forward(cfg, key, seqs, routes=[None, None], **padding)
    _, low, _ = ref.forward(cfg, key, seqs, quant="fp8", routes=[None, None], **padding)
    _, own_f, trail = ref.forward(cfg, key, seqs, routes=low, **padding)
    r, _ = check._route_readings(np, low, own_f, trail)
    assert 0 < r["followed_share"] < 0.5 and r["route_trail_max"] > 0


def test_route_readings_skip_what_was_not_followed():
    own = [some_rows(5, 1), some_rows(6, 2)]
    given = [None, own[1][..., ::-1].copy()]
    given[1][0, 0] = [e for e in range(4) if e not in own[1][0, 0]]
    trail = [np.ones((5, 2)), np.zeros((6, 2))]
    trail[1][0, 0] = 0.25
    r, decisions = check._route_readings(np, given, own, trail)
    assert decisions == 12 and r["followed_share"] == 1 / 12
    assert r["route_trail_max"] == 0.25 and r["route_trail"] < 0.25
    assert check._route_readings(np, [None], own[:1], trail[:1]) == (
        {"followed_share": None, "route_trail": None, "route_trail_max": None}, 0)


# ---- the planted fault, and the reader's filter ------------------------------


def test_the_router_fault_rolls_the_router_columns_and_nothing_else(mixtral):
    import jax

    cfg, ref, key, _, _ = mixtral
    params = jax.jit(lambda k: ref.served_params(cfg, k))(key)
    broken = check.break_router(params, ref)
    np.testing.assert_array_equal(
        np.asarray(broken["layers"]["router"], np.float32),
        np.roll(np.asarray(params["layers"]["router"], np.float32), 1, axis=-1))
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, broken)
    same["layers"].pop("router")
    assert all(jax.tree.leaves(same))
    with pytest.raises(SystemExit):
        check.break_router(params, importlib.import_module("perf.reference.mistral"))


def test_a_histogram_per_step_can_keep_one_label():
    text = lambda a, b: readers.parse_prometheus(  # noqa: E731
        f'kubeai_engine_step_phase_seconds_sum{{phase="routes"}} {a}\n'
        f'kubeai_engine_step_phase_seconds_sum{{phase="decode"}} {b}\n')
    obs = {"metrics0": text(1.0, 10.0), "metrics1": text(1.5, 30.0),
           "steps0": 0, "steps1": 100}
    with open(os.path.join(ROOT, "perf", "layer_metrics", "routes_ms_per_step.json")) as f:
        spec = json.load(f)
    assert readers.read(spec, obs) == pytest.approx(5.0)
    del spec["where"]
    assert readers.read(spec, obs) == pytest.approx(205.0)
