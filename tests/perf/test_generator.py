"""`correct` for a generator that is not one token a forward, left to right.

A family's reference module that has `HANDOVER` and `replay` says what to ask
the program for and replays it; the harness keeps what a stream carried as it
came, and keeps the sample, the gaps and the limits in its own hands. Proved
on a stub that has the real shape of the problem (tests/perf/stub_blockdiff.py:
blocks of 4 denoised in up to 4 forwards plus one that writes the cache,
committed by confidence, 2 of 8 experts routed anew at every forward), added
to a copy of the tree as files only; and on the real engine, server and load
generator with the routed wire as the one hand-over flag."""

import copy
import http.server
import json
import os
import shutil
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

import test_benchmark_json as static  # its `check`, `load`, `load_file`, `HARNESS`
from perf import check as perf_check
from perf import loadgen, traffic
from perf.tokenizer import text_of

ROOT = static.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("routes", "order")
# Limits of the stub, from seeds 70-93 (24 seeds, bf16 served, on the CPU):
# sound largest 0.0345, 6.2e-4, 0.0090, 0, 0.033, 0.0072; the float8
# control's smallest 0.83, 0.031, 0.119, 0.445, 0.227, 0.134.
CORRECT = {"max_gap": 0.15, "mean_gap": 0.004, "short": 0, "decisions_bad": 0,
           "routes_followed_share": 0.04, "routes_trail": 0.05,
           "order_followed_share": 0.1, "order_trail": 0.04}
CFG = {"architectures": ["StubBlockDiffusionForCausalLM"], "hidden_size": 32,
       "num_hidden_layers": 3, "vocab_size": 96, "prompt_vocab_size": 90,
       "mask_token_id": 95, "confidence_threshold": 0.5,
       "reference": "stub_blockdiff", "reduced": {}, "chips": 1, "mesh": {"tp": 1},
       "engine": {"num_slots": 4, "max_seq_len": 64}, "correct": CORRECT}
MIX = {"prompt_tokens": {"dist": "uniform", "low": 6, "high": 13},
       "output_tokens": {"dist": "uniform", "low": 8, "high": 16}}
FLAG = "stub_forwards"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the stub family added as files: its
    reference, a configuration with the eight limits, a cell. The copy's own
    `check.py` is what the tests below drive."""
    root = tmp_path_factory.mktemp("generator") / "repo"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (root / "perf" / p).read_bytes() for p in static.HARNESS}
    shutil.copy(os.path.join(HERE, "stub_blockdiff.py"),
                root / "perf" / "reference" / "stub_blockdiff.py")
    (root / "perf" / "configs" / "stub-blockdiff.json").write_text(json.dumps(CFG))
    b = static.load()
    b["configs"].append({**b["configs"][0], "name": "stub-blockdiff", "reduced": [],
                         "file": "perf/configs/stub-blockdiff.json"})
    b["workloads"].append({"name": "stub-blockdiff.decode-sat", "chips": 1,
                           "config": "stub-blockdiff", "traffic": "decode-sat",
                           "why": "blocks of 4 denoised under the saturated closed loop"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("out_tok_s", "step_mean_ms"):
            m["workloads"].append("stub-blockdiff.decode-sat")
    for kind in KINDS:  # what the check reads of each kind, as a metric
        name = f"{kind}_followed_share"
        (root / "perf" / "layer_metrics" / f"{name}.json").write_text(
            json.dumps({"reader": "observed", "key": name}))
        b["per_layer"].append({
            "name": name, "unit": "%", "better": "lower", "source": "program_counter",
            "layer": "Router", "moves": "out_tok_s",
            "workloads": ["stub-blockdiff.decode-sat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return types.SimpleNamespace(
        root=root, before=before,
        check=static.load_file(root / "perf" / "check.py", "perf_check_generator"),
        reference=static.load_file(
            root / "perf" / "reference" / "stub_blockdiff.py", "stub_blockdiff_copy"))


def serve(reference, key, seed, quant, n_requests=8):
    """Records as the load generator keeps them, from the stand-in program:
    the stub's own `generate` in `quant`, its blocks through JSON as a
    stream would carry them."""
    records = []
    for index in range(n_requests):
        plen, n_out = 6 + index, 8 + 4 * (index % 3)
        prompt = traffic.prompt_tokens(seed, index, plen, CFG["prompt_vocab_size"])
        served, blocks = reference.generate(CFG, key, prompt, n_out, quant)
        records.append({"index": index, "ok": True, "prompt_len": plen,
                        "max_tokens": n_out, "token_ids": served,
                        "handover": {FLAG: json.loads(json.dumps(blocks))}})
    return records


def verdict(tree, records, seed, key, logs=None, **kw):
    return tree.check.served_against_reference(
        tree.reference, CFG, key, MIX, records, seed, CFG["prompt_vocab_size"],
        log=(logs if logs is not None else []).append, state={"moe": {"experts": 8}},
        **kw)


def over(v):
    return {name for name, (value, limit) in v["compared"].items()
            if value is None or value > limit}


def test_the_family_arrives_as_files_and_the_harness_is_what_it_was(tree):
    assert static.check(str(tree.root)) == []
    assert tree.check.replays(tree.reference)
    assert tree.check.compared_names(tree.reference) == set(CORRECT)
    # A configuration that leaves a compared name out is refused by tier-1's
    # rule (tests/perf/test_benchmark_json.py applies it to every file).
    assert tree.check.compared_names(tree.reference) != set(CORRECT) - {"order_trail"}
    for p, content in tree.before.items():
        assert (tree.root / "perf" / p).read_bytes() == content
    # Each kind's followed share is an `observed` reading, found by its name.
    v = verdict(tree, serve(tree.reference, 1078, 78, "bf16"), 78, 1078)
    assert set(v["observed"]) == {f"{k}_followed_share" for k in KINDS}
    readers = static.load_file(tree.root / "perf" / "readers.py", "perf_readers_generator")
    for name, value in v["observed"].items():
        with open(tree.root / "perf" / "layer_metrics" / f"{name}.json") as f:
            assert readers.read(json.load(f), dict(v["observed"])) == value
        assert value == 100.0 * v[name]


@pytest.mark.parametrize("seed", [78, 85])
def test_served_by_itself_in_float32_nothing_differs_and_nothing_trails(tree, seed):
    v = verdict(tree, serve(tree.reference, 1000 + seed, seed, None), seed, 1000 + seed)
    assert v["correct"] is True and set(v["compared"]) == set(CORRECT)
    assert v["max_gap"] == 0.0 and v["mean_gap"] == 0.0 and v["flip_share"] == 0.0
    assert v["decisions_bad"] == 0 and v["short"] == 0 and v["tokens"] == 92
    for kind in KINDS:
        assert v[f"{kind}_followed_share"] == 0.0
        assert v[f"{kind}_trail"] == 0.0 and v[f"{kind}_trail_max"] == 0.0


@pytest.mark.parametrize("seed", [71, 78, 90])
def test_served_in_bfloat16_it_stays_under_limits_the_float8_control_lands_over(tree, seed):
    logs = []
    v = verdict(tree, serve(tree.reference, 1000 + seed, seed, "bf16"), seed,
                1000 + seed, logs, controls=["fp8"])
    assert v["correct"] is True, v["compared"]
    assert v["decisions_bad"] == 0 and v["routes_followed_share"] > 0
    fp8 = v["control"]["fp8"]
    for name in ("max_gap", "mean_gap", "routes_followed_share", "routes_trail",
                 "order_followed_share", "order_trail"):
        assert fp8[name] > CORRECT[name] >= v[name], name
    landed = next(l for l in logs if l.startswith("control fp8 lands over: "))
    assert set(landed.split("over: ")[1].split(", ")) == set(CORRECT) - {
        "short", "decisions_bad"}
    assert any(l.startswith("correct: routes, order followed over ") for l in logs)
    # Printed, not compared: the share of flips and each kind's largest trail.
    for name in ("flip_share", "routes_trail_max", "order_trail_max"):
        assert any(l.startswith(f"correct: {name} = ") and "not compared" in l for l in logs)


def another_committing_forward(records):
    """Every block's first two denoising forwards change commits: still a
    whole generation by the rule, but not the one that ran."""
    for r in records:
        denoising = {}
        for b in r["handover"][FLAG]:
            if b["commit"]:
                denoising.setdefault(b["start"], []).append(b)
        for first, second, *_ in (d for d in denoising.values() if len(d) > 1):
            first["commit"], second["commit"] = second["commit"], first["commit"]


def a_forward_missing(records):
    blocks = records[2]["handover"][FLAG]
    del blocks[next(i for i, b in enumerate(blocks) if b["commit"])]


def the_commit_forward_missing(records):
    del records[1]["handover"][FLAG][-1]


def a_forward_twice(records):
    blocks = records[3]["handover"][FLAG]
    i = next(i for i, b in enumerate(blocks) if b["commit"])
    blocks.insert(i, copy.deepcopy(blocks[i]))


def forwards_out_of_order(records):
    blocks = records[4]["handover"][FLAG]
    blocks[-1], blocks[-2] = blocks[-2], blocks[-1]


def no_handover_at_all(records):
    del records[0]["handover"]


def an_expert_the_model_has_not(records):
    records[5]["handover"][FLAG][0]["routes"][0][0] = [1, 8]


def every_fifth_token_altered(records):
    """What `--break-path token` plants where the engine hands tokens out."""
    seen = 0
    for r in records:
        for i in range(len(r["token_ids"])):
            seen += 1
            if seen % 5 == 0:
                r["token_ids"][i] = (r["token_ids"][i] + 1) % CFG["prompt_vocab_size"]


FAULTS = {
    "another committing forward": (
        another_committing_forward, {"order_followed_share", "order_trail", "mean_gap"}, 0),
    "a forward missing": (a_forward_missing, {"decisions_bad"}, 1),
    "the forward that writes the cache missing": (
        the_commit_forward_missing, {"decisions_bad"}, 1),
    "a forward twice": (a_forward_twice, {"decisions_bad"}, 1),
    "forwards out of order": (forwards_out_of_order, {"decisions_bad"}, 1),
    "no hand-over on the record": (no_handover_at_all, {"decisions_bad"}, 1),
    "an expert id the model has not": (an_expert_the_model_has_not, {"decisions_bad"}, 1),
    "every fifth token altered": (every_fifth_token_altered, {"max_gap", "mean_gap"}, 0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_not_correct_by_a_named_number(tree, fault):
    plant, named, bad = FAULTS[fault]
    records = serve(tree.reference, 1078, 78, "bf16")
    sound = verdict(tree, copy.deepcopy(records), 78, 1078)
    assert sound["correct"] is True
    plant(records)
    logs = []
    v = verdict(tree, records, 78, 1078, logs)
    assert v["correct"] is False and v["decisions_bad"] == bad
    assert named <= over(v), (over(v), v["compared"])
    for name in named:
        assert any(l.startswith(f"correct: {name} = ") and l.endswith("OVER") for l in logs)
    if bad:
        # The request that broke the rule is left out, never cut or padded:
        # the others read what they read.
        assert over(v) == {"decisions_bad"}
        assert 0 < v["tokens"] < sound["tokens"]


def test_state_reaches_the_replay_whole(tree):
    """`/v1/state` is the family's to read: here the stub takes the number
    of experts from it, and a state that says 4 refuses sets that name 5."""
    records = serve(tree.reference, 1078, 78, "bf16")
    v = tree.check.served_against_reference(
        tree.reference, CFG, 1078, MIX, records, 78, CFG["prompt_vocab_size"],
        log=lambda _: None, state={"moe": {"experts": 4}, "anything": {"else": 1}})
    assert v["decisions_bad"] == len(perf_check.sample(records, 78))
    assert v["correct"] is False and v["max_gap"] is None and v["tokens"] == 0
    assert v["compared"]["routes_trail"] == [None, CORRECT["routes_trail"]]


def test_a_reference_with_neither_name_is_called_exactly_as_it_was():
    """A dense family's module has no `HANDOVER` and no `replay`: `forward`
    gets the arguments it always got, whatever else a record carries."""
    calls = []

    def forward(cfg, key, seqs, quant=None, pad_to=0, rows_pad=0):
        calls.append((seqs, quant, pad_to, rows_pad))
        return [np.eye(96, dtype=np.float32)[np.asarray(tokens)[np.asarray(rows)] + 1]
                for tokens, rows in seqs]

    dense = types.SimpleNamespace(forward=forward)
    assert not perf_check.replays(dense) and not perf_check.takes_routes(dense)
    assert perf_check.compared_names(dense) == {"max_gap", "mean_gap", "short"}
    prompt = traffic.prompt_tokens(3, 0, 5, 90)
    served = [prompt[-1] + 1, prompt[-1] + 2, prompt[-1] + 3]
    records = [{"index": 0, "ok": True, "prompt_len": 5, "max_tokens": 3,
                "token_ids": served, "handover": {FLAG: [{"start": 0}]}}]
    cfg = {"correct": {"max_gap": 0.5, "mean_gap": 0.5, "short": 0}}
    v = perf_check.served_against_reference(
        dense, cfg, 0, MIX, records, 3, 90, log=lambda _: None, state={"x": 1})
    assert calls == [([(prompt + served[:-1], [4, 5, 6])], None, 256, 128)]
    assert v["correct"] is True and set(v["compared"]) == set(cfg["correct"])
    assert v["observed"] == {} and "decisions_bad" not in v


# ---- what the load generator asks and keeps ---------------------------------


class Forwards(http.server.BaseHTTPRequestHandler):
    """Streams a request of the stub as a server of such a family would: the
    prompt's forward with the first event, then a block's forwards with the
    event that carries the block's tokens, a chunk without the key, an empty
    list, and the last forward with the finish chunk."""

    bodies: list = []
    blocks = [{"start": 0, "rows": 3, "commit": [], "routes": [[1, 2]]},
              {"start": 3, "rows": 4, "commit": [2], "routes": [[0, 5]]},
              {"start": 3, "rows": 4, "commit": [0, 1, 3], "routes": [[3, 4]]},
              {"start": 3, "rows": 4, "commit": [], "routes": [[6, 7]]}]

    def do_POST(self):
        Forwards.bodies.append(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        chunks = [
            {"token_ids": [], FLAG: self.blocks[:1], "other_flag": ["not asked"]},
            {"token_ids": [7, 8], FLAG: self.blocks[1:3]},
            {"token_ids": [9, 10]},
            {"token_ids": [], FLAG: []},
            {"token_ids": [], FLAG: self.blocks[3:]},
        ]
        for c in chunks:
            self.wfile.write(b"data: " + json.dumps(c).encode() + b"\n\n")
        self.wfile.write(b"data: [DONE]\n\n")

    def log_message(self, *a):
        pass


@pytest.fixture()
def forwards_port():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Forwards)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    Forwards.bodies.clear()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()


@pytest.mark.parametrize("handover", [(), (FLAG,), (FLAG, "kubeai_routes")])
def test_the_load_generator_keeps_what_each_flag_carried_in_arrival_order(
        forwards_port, handover):
    req = {"index": 0, "prompt_len": 3, "max_tokens": 4}
    today = json.dumps({
        "model": "m", "prompt": text_of(traffic.prompt_tokens(5, 0, 3, 90)),
        "max_tokens": 4, "temperature": 0.0, "stream": True})
    rec = loadgen.one_request("127.0.0.1", forwards_port, "m", req, 90, 5,
                              loadgen.Clock(0.0), 10.0, handover=handover)
    assert rec["ok"] and rec["token_ids"] == [7, 8, 9, 10]
    assert [n for _, n in rec["events"]] == [2, 2]
    sent = json.loads(Forwards.bodies[0])
    assert sent == {**json.loads(today), **{flag: True for flag in handover}}
    if not handover:
        # Byte for byte what a cell without `HANDOVER` always sent and kept.
        assert Forwards.bodies[0].decode() == today == loadgen.request_body("m", req, 90, 5)
        assert set(rec) == {"index", "due", "prompt_len", "max_tokens", "ok",
                            "events", "token_ids", "sent", "status", "end"}
        return
    assert list(rec["handover"]) == list(handover)
    assert rec["handover"][FLAG] == Forwards.blocks  # as they came, in order
    assert "routes" not in rec and "other_flag" not in rec["handover"]
    if len(handover) > 1:
        assert rec["handover"]["kubeai_routes"] == []  # asked, never carried


# ---- through run.py: the real engine, server and load generator -------------

REPLAY_MIXTRAL = '''"""The routed wire as a hand-over flag: Mixtral's reference, asked through
`HANDOVER` and answering through `replay` (one token a forward, so the rows
are the dense comparison's; the decisions are the router's)."""
import numpy as np

from perf import check
from perf.reference import mixtral
from perf.reference.mixtral import served_params  # noqa: F401

HANDOVER = ("kubeai_routes",)
DECISIONS = ("routes",)
ROUTER_LEAVES = mixtral.ROUTER_LEAVES
STATES = []  # what `replay` was handed, for the test to read back


def replay(cfg, key, requests, *, state, quant=None, follow=None, pad_to, rows_pad):
    STATES.append(state)
    seqs = [(r["prompt"] + r["served"][:-1],
             list(range(len(r["prompt"]) - 1, len(r["prompt"]) - 1 + len(r["served"]))))
            for r in requests]
    given = follow
    if follow is None:
        given = [check.assemble_routes(r["handover"].get("kubeai_routes"), len(s), state["moe"])
                 for r, (s, _) in zip(requests, seqs)]
    elif follow == "own":
        given = [None] * len(seqs)
    logits, own, trail = mixtral.forward(cfg, key, seqs, quant=quant, routes=given,
                                         pad_to=pad_to, rows_pad=rows_pad)
    out = []
    for lg, g, o, t in zip(logits, given, own, trail):
        if follow is None and g is None:
            out.append(None)
            continue
        took = o if g is None else g
        out.append({"logits": np.asarray(lg), "own": o, "routes": {
            "differs": (np.sort(took, -1) != np.sort(o, -1)).any(-1), "trail": t}})
    return out
'''


@pytest.mark.parametrize("fault,correct", [("", True), ("route", False)])
def test_run_py_asks_keeps_and_replays_through_the_real_server(tmp_path, fault, correct):
    """A copy of the tree where tiny Mixtral's reference asks through
    `HANDOVER` and answers through `replay`: the copy's own `run.py` puts the
    flag into every request of the timed run, the real server streams its
    blocks, the load generator keeps them under `handover`, `/v1/state`
    reaches the replay whole, and the line compares the generator's names."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", "perf_out"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    (root / "perf" / "reference" / "replay_mixtral.py").write_text(REPLAY_MIXTRAL)
    with open(root / "perf" / "configs" / "tiny-mixtral.json") as f:
        cfg = json.load(f)
    old = cfg["correct"]
    cfg.update(reference="replay_mixtral", correct={
        "max_gap": old["max_gap"], "mean_gap": old["mean_gap"], "short": 0,
        "decisions_bad": 0, "routes_followed_share": old["followed_share"],
        "routes_trail": old["route_trail"]})
    (root / "perf" / "configs" / "tiny-replay.json").write_text(json.dumps(cfg))
    (root / "perf" / "rehearse.d").mkdir(exist_ok=True)
    (root / "perf" / "rehearse.d" / "replay.json").write_text(json.dumps({
        "configs": [{"name": "tiny-replay", "file": "perf/configs/tiny-replay.json"}],
        "workloads": [{"name": "tiny-replay.closed", "config": "tiny-replay",
                       "traffic": "tiny-closed", "chips": 4,
                       "as": "mixtral-8x7b.decode-sat"}]}))
    records = tmp_path / "records.json"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH=ROOT, PERF_KEEP_RECORDS=str(records))
    done = subprocess.run(
        [sys.executable, str(root / "perf" / "run.py"), "--rehearse", "--workload",
         "tiny-replay.closed", "--seed", "34", "--seconds", "2", "--trace", "1",
         *(["--break-path", fault] if fault else [])],
        capture_output=True, text=True, cwd=root, env=env, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is correct and line["failed"] == 0
    assert set(line["compared"]) == set(cfg["correct"]) | {"failed"}
    assert "perf: handed over by every request: ['kubeai_routes']" in lines
    assert not any("routes asked of every request" in l for l in lines)
    assert any(l.startswith("perf: correct: routes followed over ") for l in lines)
    assert line["compared"]["decisions_bad"] == [0, 0]
    kept = [r for r in json.loads(records.read_text())["records"] if r["ok"]]
    assert kept and all(list(r["handover"]) == ["kubeai_routes"] for r in kept)
    assert "routes" not in kept[0] and kept[0]["handover"]["kubeai_routes"][0]["start"] == 0
    share = line["compared"]["routes_followed_share"][0]
    if correct:
        assert 0 <= share <= cfg["correct"]["routes_followed_share"]
        # The traced line has no metric of this name in BENCHMARK.json, so the
        # reading is observed and simply not reported; Mixtral's own is.
        assert "route_followed_share" not in line["metrics"]
    else:
        assert share > 0.5 and "OVER" in next(
            l for l in lines if l.startswith("perf: correct: routes_followed_share"))
