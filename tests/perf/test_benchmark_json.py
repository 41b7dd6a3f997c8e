"""BENCHMARK.json and the data files it names: the contract's static rules,
and that a later PR can add a configuration, a mix, a cell and a per-layer
metric, and a whole architecture (reference, reader kind, cost function, a
cache of its own shape, a rehearsal cell), with new files and entries only."""

import base64
import glob
import importlib
import importlib.util
import json
import os
import re
import shutil
import types

import pytest

from perf import check as perf_check
from perf import costs, readers, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def check(root) -> list[str]:
    """Every rule this file can check statically; returns what is wrong."""
    b, bad = load(root), []
    if set(b) != {"command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"}:
        bad.append(f"top-level keys {sorted(b)}")
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    e2e_names = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
        if not NAME.match(c["name"]) or not all(NAME.match(k) for k in c["reduced"]):
            bad.append(f"config name {c['name']!r}")
        path = os.path.join(root, c["file"])
        if not any(c["file"].startswith(p + "/") for p in b["paths"]):
            bad.append(f"{c['file']} is outside paths")
        try:
            with open(path) as f:
                body = json.load(f)
            if "reference" not in body or not os.path.exists(os.path.join(
                    root, "perf", "reference", body["reference"] + ".py")):
                bad.append(f"{c['file']} names no reference")
            if set(c["reduced"]) != set(body.get("reduced", {})):
                bad.append(f"{c['name']}: reduced differs from its file's")
        except (OSError, ValueError) as e:
            bad.append(f"{c['file']}: {e}")
    for w in b["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            if not NAME.match(w[key]):
                bad.append(f"workload {key} {w[key]!r}")
        if w["config"] not in cfgs:
            bad.append(f"{w['name']}: unknown config")
        if w["chips"] not in (1, 4) or not 1 <= len(w["why"]) <= 200:
            bad.append(f"{w['name']}: chips or why")
        try:
            traffic.load_mix(w["traffic"], os.path.join(root, "perf"))
        except (OSError, ValueError) as e:
            bad.append(f"{w['name']}: mix {e}")
    if sum(1 for w in b["workloads"] if w["chips"] == 4) > max(1, len(cells) // 4):
        bad.append("too many four-chip cells")
    if {c["name"] for c in b["configs"]} != {w["config"] for w in b["workloads"]}:
        bad.append("a configuration no cell uses")
    for m in b["end_to_end"] + b["per_layer"]:
        if not NAME.match(m["name"]) or not UNIT.match(m["unit"]):
            bad.append(f"metric {m['name']!r} unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
            bad.append(f"{m['name']}: better/source")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: unknown cell {w}")
    for m in b["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad.append(f"{m['name']}: keys")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end source")
        if not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    if "setup_s" not in e2e_names:
        bad.append("no setup_s")

    def reports(cell):
        return {m["name"] for m in b["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}

    for m in b["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            bad.append(f"{m['name']}: keys")
        if m["moves"] not in e2e_names:
            bad.append(f"{m['name']}: moves {m['moves']}")
        for cell in m.get("workloads", cells):
            if m["moves"] not in reports(cell):
                bad.append(f"{m['name']}: {cell} does not report {m['moves']}")
        path = os.path.join(root, "perf", "layer_metrics", m["name"] + ".json")
        try:
            with open(path) as f:
                if readers.kind(json.load(f)["reader"],
                                os.path.join(root, "perf")) is None:
                    bad.append(f"{m['name']}: unknown reader kind")
        except (OSError, ValueError, KeyError) as e:
            bad.append(f"{m['name']}: {e}")
    for cell in cells:
        if len(reports(cell)) < 2:
            bad.append(f"{cell}: reports no end-to-end metric besides setup_s")
        if not any("workloads" not in m or cell in m["workloads"]
                   for m in b["per_layer"]):
            bad.append(f"{cell}: reports no per-layer metric")
    return bad


def test_the_benchmark_as_committed_meets_the_static_rules():
    assert check(ROOT) == []
    b = load()
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in b["paths"])


def test_command_names_no_file_outside_paths():
    b = load()
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in b["paths"])


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "perf", "configs"))))
def test_configuration_files_state_their_engine_and_limits(name):
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert cfg["chips"] in (1, 4)
    assert {"num_slots", "max_seq_len"} <= set(cfg["engine"])
    # What a configuration has to state is learned from its reference module:
    # the dense three; the router's three beside them where `forward` follows
    # routes; where it has `replay` (a generator of its own), `decisions_bad`
    # and a share and a trail for each kind in its `DECISIONS`.
    reference = importlib.import_module("perf.reference." + cfg["reference"])
    assert set(cfg["correct"]) == perf_check.compared_names(reference)
    for name, limit in cfg["correct"].items():
        if name in ("short", "route_rows_bad", "decisions_bad"):
            assert limit == 0, name
        elif name.endswith("followed_share"):
            assert 0 < limit < 1, name
        else:
            assert limit > 0, name
    assert cfg["mesh"]["tp"] == cfg["chips"]
    traffic.prompt_vocab(cfg)  # raises unless it is in 1..vocab_size


def named_configuration_files(root=ROOT) -> set[str]:
    """The files that an entry of BENCHMARK.json or of a rehearsal file names."""
    named = {c["file"] for c in load(root)["configs"]}
    perf = os.path.join(root, "perf")
    for path in [os.path.join(perf, "rehearse.json"), *sorted(
            glob.glob(os.path.join(perf, "rehearse.d", "*.json")))]:
        with open(path) as f:
            named |= {c["file"] for c in json.load(f)["configs"]}
    return named


@pytest.mark.parametrize("name", sorted(
    os.listdir(os.path.join(ROOT, "perf", "configs"))))
def test_every_configuration_file_is_named_by_an_entry(name):
    """The guard against `config_not_added`: a `model_config` PR that brings
    the file and not the entry under `configs` (and a workload that runs it)
    sees red here before the driver refuses it. A rehearsal preset is named
    by its rehearsal file."""
    assert f"perf/configs/{name}" in named_configuration_files(), (
        f"perf/configs/{name} is named by no entry under `configs` of "
        "BENCHMARK.json and by no rehearsal file: add the entry and a workload "
        "that runs it (perf/README.md, 'Adding things')")


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(ROOT, "perf", "reference"))
    if f.endswith(".py") and f != "__init__.py"))
def test_every_reference_module_is_named_by_a_configuration_file(name):
    families = set()
    for f in os.listdir(os.path.join(ROOT, "perf", "configs")):
        with open(os.path.join(ROOT, "perf", "configs", f)) as fh:
            families.add(json.load(fh)["reference"] + ".py")
    assert name in families, (
        f"perf/reference/{name} is the reference of no file under perf/configs/")


def test_a_configuration_brought_without_its_entry_is_seen(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = named_configuration_files(str(root))
    assert before == {"perf/configs/" + f for f in os.listdir(root / "perf" / "configs")}
    shutil.copy(root / "perf" / "configs" / "tiny-mistral.json",
                root / "perf" / "configs" / "brought-alone.json")
    assert "perf/configs/brought-alone.json" not in named_configuration_files(str(root))


PUBLISHED = {  # the models' public config.json, widths and all
    "mistral-7b-v5e1": dict(
        hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
        num_key_value_heads=8, vocab_size=32768, rope_theta=1e6,
        rms_norm_eps=1e-5, max_position_embeddings=32768),
    "mixtral-8x7b-v5e4": dict(
        hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
        num_key_value_heads=8, vocab_size=32000, rope_theta=1e6,
        rms_norm_eps=1e-5, max_position_embeddings=32768,
        num_local_experts=8, num_experts_per_tok=2),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_only_depth_is_reduced_from_the_published_config(name):
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        cfg = json.load(f)
    for key, value in PUBLISHED[name].items():
        assert cfg[key] == value, key
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["from"], cut["to"]) == (32, cfg["num_hidden_layers"])


HARNESS = ("run.py", "readers.py", "check.py", "costs.py", "traffic.py", "loadgen.py",
           "tokenizer.py")

STUB_REFERENCE = '''"""A stub architecture: a latent cache of 576 numbers a token a layer."""
from perf.reference.mistral import forward, served_params  # noqa: F401


def kv_bytes_per_token(hf):
    return hf["num_hidden_layers"] * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * 2


def decode_step_bytes_per_chip(hf, resident_tokens, chips):
    return (1000 + resident_tokens * kv_bytes_per_token(hf)) / chips
'''

STUB_KIND = '''"""A reader kind of the stub's own: latent bytes resident, in MiB."""
from perf import costs


def read(spec, obs):
    used = obs["polled"].get("kv_tokens") or []
    if not used:
        return None
    per_token = costs.of(obs.get("reference"), "kv_bytes_per_token")(obs["hf"])
    return sum(used) / len(used) * per_token / 2**20 * spec.get("scale", 1.0)
'''


def load_file(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_later_pr_adds_a_config_mix_cell_and_metric_as_files_only(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = load()
    before = {p: (root / "perf" / p).read_bytes() for p in HARNESS}
    with open(root / "perf" / "configs" / "mistral-7b-v5e1.json") as f:
        cfg = json.load(f)
    cfg["engine"]["num_slots"] = 16
    (root / "perf" / "configs" / "mistral-7b-16slot.json").write_text(json.dumps(cfg))
    (root / "perf" / "traffic" / "chat-fast.json").write_text(
        json.dumps({"extends": "chat", "rate_rps": 9.5}))
    (root / "perf" / "layer_metrics" / "itl_mean_ms.json").write_text(json.dumps(
        {"reader": "histogram_mean", "scale": 1000.0,
         "metric": "kubeai_engine_inter_token_latency_seconds"}))
    b["configs"].append({**b["configs"][0], "name": "mistral-7b-16slot",
                         "file": "perf/configs/mistral-7b-16slot.json"})
    b["workloads"].append({"name": "mistral-7b-16slot.chat-fast", "chips": 1,
                           "config": "mistral-7b-16slot", "traffic": "chat-fast",
                           "why": "the same chat lengths at a higher rate"})
    for m in b["end_to_end"]:
        if m["name"] == "tpot_mean_ms":
            m["workloads"].append("mistral-7b-16slot.chat-fast")
    b["per_layer"].append({
        "name": "itl_mean_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "Decode step",
        "moves": "tpot_mean_ms", "workloads": ["mistral-7b-16slot.chat-fast"]})

    # A whole architecture, as files: its reference (with cost functions of
    # its own), a reader kind, a configuration, a cell that reports a metric
    # of that kind, and a rehearsal cell.
    (root / "perf" / "reference" / "stub_latent.py").write_text(STUB_REFERENCE)
    (root / "perf" / "reader_kinds").mkdir(exist_ok=True)
    (root / "perf" / "reader_kinds" / "latent_resident_mib.py").write_text(STUB_KIND)
    stub = {**cfg, "reference": "stub_latent", "kv_lora_rank": 512, "qk_rope_head_dim": 64}
    (root / "perf" / "configs" / "stub-latent.json").write_text(json.dumps(stub))
    (root / "perf" / "layer_metrics" / "latent_resident_mib.json").write_text(
        json.dumps({"reader": "latent_resident_mib"}))
    (root / "perf" / "rehearse.d").mkdir(exist_ok=True)
    (root / "perf" / "rehearse.d" / "stub.json").write_text(json.dumps({
        "configs": [{"name": "stub-latent", "file": "perf/configs/stub-latent.json"}],
        "workloads": [{"name": "stub-latent.closed", "config": "stub-latent",
                       "traffic": "tiny-closed", "chips": 1,
                       "as": "stub-latent.decode-sat"}]}))
    b["configs"].append({**b["configs"][0], "name": "stub-latent",
                         "file": "perf/configs/stub-latent.json"})
    b["workloads"].append({"name": "stub-latent.decode-sat", "chips": 1,
                           "config": "stub-latent", "traffic": "decode-sat",
                           "why": "a latent cache under the saturated closed loop"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("out_tok_s", "decode_hbm_share"):
            m["workloads"].append("stub-latent.decode-sat")
    b["per_layer"].append({
        "name": "latent_resident_mib", "unit": "MiB", "better": "higher",
        "source": "program_counter", "layer": "KV pool",
        "moves": "out_tok_s", "workloads": ["stub-latent.decode-sat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert check(str(root)) == []

    mix = traffic.load_mix("chat-fast", str(root / "perf"))
    assert mix["rate_rps"] == 9.5 and mix["loop"] == "open"
    assert len(traffic.open_schedule(mix, 1, 10.0)) == round(9.5 * (10 + mix["preroll_s"]))
    # The new metric reads through the existing reader kind.
    edge = lambda s, c: readers.parse_prometheus(  # noqa: E731
        f"kubeai_engine_inter_token_latency_seconds_sum {s}\n"
        f"kubeai_engine_inter_token_latency_seconds_count {c}\n")
    with open(root / "perf" / "layer_metrics" / "itl_mean_ms.json") as f:
        spec = json.load(f)
    assert readers.read(spec, {"metrics0": edge(1.0, 10), "metrics1": edge(3.0, 50)}) \
        == pytest.approx(50.0)

    # The stub's reader kind is found by its name, asks the stub's reference
    # for its cost first, and `decode_hbm_share` does too; a cost the stub
    # does not bring is perf/costs.py's.
    reference = load_file(root / "perf" / "reference" / "stub_latent.py", "stub_latent")
    obs = {"polled": {"kv_tokens": [1000.0, 3000.0]}, "hf": stub, "chips": 1,
           "reference": reference, "engine": {"decode_chunk": 8},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"modules": {"jit__decode_chunk(1)": {"count": 2, "total_s": 0.016}}}}
    per_token = 16 * 576 * 2
    assert readers.read({"reader": "latent_resident_mib"}, obs, str(root / "perf")) \
        == pytest.approx(2000 * per_token / 2**20)
    assert readers.kind("latent_resident_mib") is None  # not a kind of this tree
    assert readers.kind("histogram_mean", str(root / "perf")) is readers.histogram_mean
    assert costs.of(reference, "decode_step_bytes_per_chip")(stub, 2000, 1) \
        == 1000 + 2000 * per_token
    assert costs.of(reference, "prefill_flops_per_token") is costs.prefill_flops_per_token
    share = readers.read({"reader": "decode_hbm_share", "module": "^jit__decode_chunk"},
                         obs, str(root / "perf"))
    assert share == pytest.approx(100.0 * (1000 + 2000 * per_token) / 819e9 / 0.001)

    # Its rehearsal cell is found in rehearse.d/ by the copy's own run.py.
    run = load_file(root / "perf" / "run.py", "perf_run_copy")
    _, cell, body = run.load_cell("stub-latent.closed", True)
    assert cell["as"] == "stub-latent.decode-sat" and body["reference"] == "stub_latent"
    assert [m["name"] for m in run.metrics_for(b, "per_layer", cell)
            if m["name"].startswith("latent")] == ["latent_resident_mib"]
    assert run.load_cell("tiny-mistral.closed", True)[1]["config"] == "tiny-mistral"

    for p, content in before.items():
        assert (root / "perf" / p).read_bytes() == content


STUB_ROUTED_REFERENCE = '''"""A stub routed architecture, numpy only: one leading dense layer, then
layers with 32 experts of which a token takes 4.

Routed layers are layers 1.. (routed layer j is layer j + 1). The selection
score is `sigmoid(router logit) + expert_bias`: the 4 largest are taken; the
weights are the sigmoids (without the bias) of the taken set, renormalised."""
import numpy as np

ROUTER_LEAVES = ("router",)
X, K = 32, 4


def _weights(hf, key):
    rng = np.random.default_rng(int(key))
    E, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    n = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    return {"embed": n(V, E), "head": n(E, V) / np.sqrt(E), "dense": n(E, E) / np.sqrt(E),
            "router": n(L - 1, E, X) * 2.0 / np.sqrt(E), "bias": n(L - 1, X) * 0.05,
            "experts": n(L - 1, X, E, E) / np.sqrt(E)}


def _low(x, quant):
    """A lower precision: fewer mantissa bits in every activation."""
    if quant is None:
        return x
    bits = {"bf16": 8, "fp8": 3, "int8": 5}[quant]
    m, e = np.frexp(x)
    return np.ldexp(np.round(m * 2**bits) / 2**bits, e).astype(np.float32)


def forward(hf, key, seqs, quant=None, routes=None, pad_to=0, rows_pad=0):
    w = _weights(hf, key)
    logits, own, trail = [], [], []
    for i, (tokens, rows) in enumerate(seqs):
        x = _low(w["embed"][np.asarray(tokens)], quant)
        # No attention: a running mean makes every position see its past.
        x = np.cumsum(x, 0) / np.arange(1, len(tokens) + 1)[:, None]
        x = x + _low(np.tanh(x @ w["dense"]), quant)
        given = None if routes is None else routes[i]
        o_seq, t_seq = [], []
        for j in range(hf["num_hidden_layers"] - 1):
            h = _low(x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6), quant)
            score = 1.0 / (1.0 + np.exp(-_low(h @ w["router"][j], quant)))
            select = score + w["bias"][j]
            mine = np.argsort(-select, axis=-1, kind="stable")[:, :K]
            sets = mine if given is None else np.asarray(given)[:, j]
            taken = np.take_along_axis(select, sets, -1)
            t_seq.append(np.take_along_axis(select, mine, -1)[:, -1] - taken.min(-1))
            o_seq.append(mine)
            wt = np.take_along_axis(score, sets, -1)
            wt = wt / wt.sum(-1, keepdims=True)
            out = np.einsum("te,tkef->tkf", h, w["experts"][j][sets])
            x = x + _low(np.einsum("tk,tkf->tf", wt, np.tanh(out)), quant)
        logits.append(_low(x[np.asarray(rows)], quant) @ w["head"])
        own.append(np.stack(o_seq, 1))
        trail.append(np.stack(t_seq, 1))
    return logits if routes is None else (logits, own, trail)
'''


def serve_with(reference, cfg, key, seed, quant, n_requests=8, roll=0):
    """Records as the load generator would keep them, from a stand-in
    program: the stub in `quant`, decoding greedily and handing over the
    sets it took on the wire's terms (uint8 ids, the prompt's block with the
    first token, then a row a token). `roll` plants the router fault."""
    import numpy as np

    records = []
    for index in range(n_requests):
        plen, n_out = 6 + index, 5 + index % 3
        seq = traffic.prompt_tokens(seed, index, plen, cfg["vocab_size"])
        served, blocks = [], []
        for step in range(n_out):
            (lg,), (own,), _ = reference.forward(
                cfg, key, [(seq + served, [plen - 1 + step])], quant=quant,
                routes=[None])
            took = ((own + roll) % 32).astype(np.uint8)
            start = 0 if step == 0 else plen + step - 1
            blocks.append({
                "start": start, "rows": len(took) - start, "shape": [3, 4],
                "dtype": "uint8",
                "data": base64.b64encode(took[start:].tobytes()).decode()})
            served.append(int(lg[0].argmax()))
        records.append({"index": index, "ok": True, "prompt_len": plen,
                        "max_tokens": n_out, "token_ids": served,
                        "routes": blocks})
    return records


def test_a_later_pr_adds_a_routed_architecture_as_files_only(tmp_path):
    """What the next `model_config` PR does for an architecture with a
    router: a reference with a `routes` parameter (k = 4 of 32 experts, a
    leading dense layer, a sigmoid-plus-bias selection score), a
    configuration with the six limits, a cell and the router's per-layer
    metrics, in a copy of the tree, with the harness byte for byte what it
    is. The copy's own check then follows the routes a stand-in program
    hands over, passes it, and fails its router fault and its control."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (root / "perf" / p).read_bytes() for p in HARNESS}
    (root / "perf" / "reference" / "stub_routed.py").write_text(STUB_ROUTED_REFERENCE)
    cfg = {"architectures": ["StubRoutedForCausalLM"], "hidden_size": 32,
           "num_hidden_layers": 4, "vocab_size": 96, "reference": "stub_routed",
           "reduced": {}, "chips": 1, "mesh": {"tp": 1},
           "engine": {"num_slots": 4, "max_seq_len": 64},
           "correct": {"max_gap": 0.02, "mean_gap": 0.0005, "short": 0,
                       "route_rows_bad": 0, "followed_share": 0.08,
                       "route_trail": 0.01}}
    (root / "perf" / "configs" / "stub-routed.json").write_text(json.dumps(cfg))
    b = load()
    b["configs"].append({**b["configs"][0], "name": "stub-routed", "reduced": [],
                         "file": "perf/configs/stub-routed.json"})
    b["workloads"].append({"name": "stub-routed.decode-sat", "chips": 1,
                           "config": "stub-routed", "traffic": "decode-sat",
                           "why": "4 of 32 experts under the saturated closed loop"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("out_tok_s", "routes_ms_per_step", "moe_imbalance",
                         "route_followed_share"):
            m["workloads"].append("stub-routed.decode-sat")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert check(str(root)) == []

    chk = load_file(root / "perf" / "check.py", "perf_check_copy")
    reference = load_file(root / "perf" / "reference" / "stub_routed.py", "stub_routed")
    assert chk.takes_routes(reference)
    moe = {"experts": 32, "k": 4, "routed_layers": 3, "routes": True}
    mix = {"prompt_tokens": {"dist": "uniform", "low": 6, "high": 13},
           "output_tokens": {"dist": "uniform", "low": 5, "high": 7}}
    seed, key, logs = 78, 1234, []

    def verdict(records, **kw):
        return chk.served_against_reference(
            reference, cfg, key, mix, records, seed, cfg["vocab_size"],
            log=logs.append, moe=moe, **kw)

    sound = verdict(serve_with(reference, cfg, key, seed, "bf16"), controls=["fp8"])
    assert set(sound["compared"]) == set(cfg["correct"])
    assert sound["correct"] is True, sound["compared"]
    assert sound["route_rows_bad"] == 0
    assert 0 < sound["followed_share"] <= cfg["correct"]["followed_share"]
    # The control took its own sets in float8 and the reference followed them.
    assert sound["control"]["fp8"]["followed_share"] > cfg["correct"]["followed_share"]
    assert any(l.startswith("control fp8 lands over: ") and "NO LIMIT" not in l
               for l in logs)
    # Not followed, the same sound records read a flip's gap and not rounding:
    # this is what a routed family was compared on before.
    blind = chk.served_against_reference(
        reference, cfg, key, mix, serve_with(reference, cfg, key, seed, "bf16"),
        seed, cfg["vocab_size"], log=logs.append, moe=None)
    assert blind["mean_gap"] > sound["mean_gap"] and blind["max_gap"] > cfg["correct"]["max_gap"]
    assert blind["compared"]["followed_share"] == [None, cfg["correct"]["followed_share"]]
    assert blind["correct"] is False
    # The router fault: other experts taken and handed over.
    broken = verdict(serve_with(reference, cfg, key, seed, "bf16", roll=1))
    assert broken["correct"] is False and broken["route_rows_bad"] == 0
    assert broken["followed_share"] > 0.9
    assert broken["route_trail"] > cfg["correct"]["route_trail"]
    # A request that lost a row is not followed, and is counted.
    records = serve_with(reference, cfg, key, seed, "bf16")
    del records[2]["routes"][-1]
    lost = verdict(records)
    assert lost["route_rows_bad"] == 1 and lost["correct"] is False

    for p, content in before.items():
        assert (root / "perf" / p).read_bytes() == content


class OnePoolCache:
    """A cache that is neither `k_pages` nor `v_pages`: one latent pool, a
    state beside it, and the small block tables."""

    def __init__(self, jnp):
        self.latent_pages = jnp.ones((4, 300, 16, 64), jnp.float32)  # 4.7 MiB
        self.state = {"conv": jnp.ones((512, 1024), jnp.float32)}  # 2 MiB
        self.block_tables = jnp.full((4, 8), -1, jnp.int32)
        self.page_size = 16


def test_a_cache_of_another_shape_is_freed_and_remade_by_walking_it():
    import jax
    import jax.numpy as jnp

    from perf import run

    engine = types.SimpleNamespace(
        params={"w": jnp.ones((8, 8))}, cache=OnePoolCache(jnp),
        _state={"tokens": jnp.zeros((4,), jnp.int32)}, has_work=lambda: False)
    bench = run.Bench.__new__(run.Bench)
    bench.jax, bench.engine, bench._pools_like = jax, engine, {}
    bench._make_params = lambda key: {"w": jnp.full((8, 8), 2.0)}
    tables = engine.cache.block_tables
    bench.release()
    assert engine.cache.latent_pages.is_deleted()
    assert engine.cache.state["conv"].is_deleted() and engine.params["w"].is_deleted()
    assert not tables.is_deleted() and not engine._state["tokens"].is_deleted()
    bench.reseed(5)
    assert engine.cache.latent_pages.shape == (4, 300, 16, 64)
    assert float(engine.cache.latent_pages.sum()) == 0.0  # an idle pool holds nothing
    assert engine.cache.state["conv"].shape == (512, 1024)
    assert float(engine.params["w"][0, 0]) == 2.0 and engine.cache.block_tables is tables
    assert bench._pools_like == {}
    # Before the reference runs, everything goes, whatever it is called.
    held = perf_check.device_arrays(engine, engine.cache)
    assert len(held) == 5
    perf_check.free_engine(engine)
    assert all(a.is_deleted() for a in held) and tables.is_deleted()
