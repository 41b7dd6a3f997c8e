"""The configuration `k-exaone-236b-a23b-v5e1`, its cell and its tiny rehearsal:
the three things a `model_config` PR must leave in `BENCHMARK.json`
(perf/README.md, "Adding things"), the catalog's keys as published with the
three cuts and the expert share as explicit keys, the reference's counts
hand-worked, the family's reader kind on a worked trace, and `run.py
--rehearse` driving both KV pools, the expert share behind a leading dense
layer and the routed `correct` end to end on the CPU, sound and with each
planted fault.

`test_qwen3_next_cell.py:55` holds PR 43's entries to be the LAST of `configs`
and `workloads`, which any later configuration ends; that file is the
benchmark's and only a `benchmark` PR may edit it (PERF.md section 7 item 18),
so tests/conftest.py marks that one test as expected to fail.
`test_the_pinned_test_loses_its_place_at_the_lists_end_and_nothing_else` runs
that test's own body here on the two lists as PR 43 left them, so every other
assertion of it still holds PR 43's entries, and holds the marker to that one
clause."""

import json
import os
import re

import pytest

from test_rehearsal import KEYS, load_benchmark, load_config, run  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, CELL = "k-exaone-236b-a23b-v5e1", "k-exaone-236b-a23b.gen-sat"
TINY = "tiny-exaone-moe.closed"
SOURCE = "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json"
LLLG = ["sliding_attention"] * 3 + ["full_attention"]
# The catalog entry's `config`, key for key (lists of 48 by their period).
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432, "layer_types": LLLG * 12,
    "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47, "model_type": "exaone_moe",
    "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"],
    "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_pattern": "LLLG", "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600,
}
REDUCED = {"num_hidden_layers": (48, 8), "num_experts": (128, 16),
           "vocab_size": (153600, 19200)}
LIMITS = {"max_gap", "mean_gap", "short", "route_rows_bad", "followed_share",
          "route_trail"}
NEW_METRICS = {"kexaone_experts_roofline", "window_pages_share", "moe_held_share",
               "moe_experts_touched"}
DEVICE_QUEUE = {"device_starved_ms_per_step", "starved_before_prefill_share",
                "dispatch_drained_share"}


def spec(name):
    with open(os.path.join(ROOT, "perf", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def reported(b, cell):
    return {m["name"]: m for m in b["per_layer"] if cell in m.get("workloads", ())}


def test_benchmark_json_holds_the_configuration_the_cell_and_its_metrics():
    b = load_benchmark()
    (entry,) = [c for c in b["configs"] if c["name"] == CONFIG]
    assert entry["source"] == SOURCE and entry["reduced"] == list(REDUCED)
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "gen-sat", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # Appended: behind every entry that was there (PR 43's are the ones before).
    names = [c["name"] for c in b["configs"]]
    assert names.index(CONFIG) == names.index("qwen3-next-80b-a3b-v5e1") + 1
    cells = [w["name"] for w in b["workloads"]]
    assert cells.index(CELL) == cells.index("qwen3-next-80b-a3b.decode-sat") + 1
    before = cells[:cells.index(CELL)]
    for m in [*b["end_to_end"], *b["per_layer"]]:
        if CELL in m.get("workloads", ()):
            at = m["workloads"].index(CELL)
            assert set(m["workloads"][:at]) <= set(before), m["name"]
            assert not set(m["workloads"][at + 1:]) & set(before), m["name"]
    names = set(reported(b, CELL))
    # The whole step's share of the HBM roofline, through the entry that
    # exists, with the family's own count of a step's bytes.
    assert "decode_hbm_share" in names
    from perf.reference import exaone_moe

    assert callable(exaone_moe.decode_step_bytes_per_chip)
    mix = json.load(open(os.path.join(ROOT, "perf", "traffic", "gen-sat.json")))
    assert (mix["loop"], mix["clients"]) == ("closed", "num_slots")
    assert mix["prompt_tokens"] == {"dist": "uniform", "low": 512, "high": 2048}
    assert mix["output_tokens"] == {"dist": "uniform", "low": 768, "high": 1920}
    assert 6 <= mix["preroll_s"] <= 15 and mix["drain_s"] == 5
    # The family's own metrics are data files and a reader kind, read in PR
    # 46's chip runs through a copy of BENCHMARK.json that names them, and NOT
    # entries (PERF.md section 7 item 18): an entry a `benchmark` PR later
    # adds reads here alone, or here and in PR 43's cell.
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert CELL in m["workloads"] and m["unit"] in ("%", "count")


def test_the_pinned_test_loses_its_place_at_the_lists_end_and_nothing_else(monkeypatch):
    """tests/conftest.py marks `test_qwen3_next_cell.py`'s static test `xfail`
    for one clause, line 55's `b["configs"][-1] is entry and b["workloads"][-1]
    is cell`. Its whole body runs here against `BENCHMARK.json` with what was
    appended BEHIND PR 43's two entries taken off those two lists: PR 43's
    `source`, `reduced`, `file`, the cell's configuration, traffic and chips,
    the `why` lengths, its end-to-end and per-layer metrics and the check that
    the family's five metrics are no entries all stay held. Unchanged, the body
    fails at that clause and at no other; once a `benchmark` PR drops the
    clause it fails nowhere, this test says so, and the marker goes."""
    import traceback

    import test_qwen3_next_cell as pinned

    body = pinned.test_benchmark_json_holds_the_configuration_the_cell_and_its_metrics
    with pytest.raises(AssertionError) as failed:
        body()
    at = traceback.extract_tb(failed.value.__traceback__)[-1]
    assert at.line.startswith(
        'assert b["configs"][-1] is entry and b["workloads"][-1] is cell'), at.line
    b = load_benchmark()
    for key, last in (("configs", pinned.CONFIG), ("workloads", pinned.CELL)):
        names = [e["name"] for e in b[key]]
        b[key] = b[key][:names.index(last) + 1]
    monkeypatch.setattr(pinned, "load_benchmark", lambda: b)
    body()


@pytest.mark.parametrize("cell,also", [
    ("qwen3-next-80b-a3b.decode-sat", set()),
    (CELL, {"decode_hbm_share"}),
])
def test_a_routed_share_cell_reports_what_its_kind_reports(cell, also):
    """A closed-loop cell of a routed family that holds a share: judged on
    `out_tok_s`, every per-layer metric of it moves that, each names a reader
    that exists, and PR 40's six device-queue entries keep PR 40's cells."""
    from perf import readers

    b = load_benchmark()
    end = {m["name"] for m in b["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    assert end == {"out_tok_s", "setup_s"}
    layer = reported(b, cell)
    assert all(m["moves"] == "out_tok_s" for m in layer.values())
    assert {"decode_device_ms", "paged_attn_ms", "step_mean_ms", "device_idle_share",
            "moe_imbalance", "route_followed_share", "routes_ms_per_step",
            "kv_used_share", "compiles_in_window", "batch_mean", "prefill_step_share",
            "step_host_share", "tpot_p95_ms.sat", "admit_host_ms_per_step",
            "admit_wait_mean_ms", "admit_calls", "prefill_useful_tokens",
            "prefill_pad_tokens", "loop_gap_ms_per_step", "emit_busy_ms_per_step",
            "emit_lag_mean_ms"} | also <= set(layer)
    assert not DEVICE_QUEUE & set(layer)
    assert ("decode_hbm_share" in layer) == bool(also)
    for name in layer:
        assert readers.kind(spec(name)["reader"]) is not None, name
    for name in NEW_METRICS:
        assert readers.kind(spec(name)["reader"]) is not None, name


def test_every_width_is_the_catalogs_and_the_three_cuts_are_stated():
    cfg = load_config(CONFIG)
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert list(cfg["reduced"]) == list(REDUCED)
    for key, (published, here) in REDUCED.items():
        cut = cfg["reduced"][key]
        assert (cut["from"], cut["to"], cfg[key]) == (published, here, here), key
        assert len(cut["why"]) > 100
    # The share as explicit keys: the router's width, which share this is.
    assert (cfg["router_num_experts"], cfg["expert_share_index"]) == (128, 0)
    assert {"residual", "qk_norm", "rope_layers", "router_bias", "router_num_experts",
            "expert_share_index", "shared_expert", "weights", "mtp", "tokenizer"} <= set(
                cfg["assumed"])
    assert "8 chips share each layer" in cfg["deployment"]
    assert "WHOLE" in cfg["deployment"] and "48 chips" in cfg["deployment"]
    assert "prompt_vocab_size" not in cfg  # the slice holds no reserved id
    # ISSUE 46's engine block, at the engine's default of 8 steps a chunk: 16
    # was tried against the spread of `out_tok_s` and taken out again (it does
    # not repair the host's lateness, and a traced slice then holds too few
    # chunk programs for `decode_device_ms`); the file says that the spread is
    # unresolved (PERF.md section 6, PR 46; ROADMAP A4).
    assert cfg["engine"] == {"num_slots": 64, "max_seq_len": 4096, "max_admit_batch": 2}
    assert "NOT resolved" in cfg["engine_note"]
    assert (cfg["source"], cfg["reference"], cfg["chips"]) == (SOURCE, "exaone_moe", 1)
    assert set(cfg["correct"]) == LIMITS == set(load_config("tiny-exaone-moe")["correct"])
    # The program reads the same cuts and the same share from the same keys.
    from kubeai_tpu.models.registry import get_model_family

    family = get_model_family(cfg["architectures"][0])
    mcfg = family.config_from_hf(cfg)
    assert family.route_dims(mcfg) == (128, 8, 7)
    assert family.held_experts(mcfg) == (0, 16)
    assert family.kv_layers(mcfg) == {
        "global_layers": 2, "window_layers": 6, "window": 128}
    assert family.recurrent_state is None


def test_the_references_counts_at_the_published_widths():
    """Hand-worked (ISSUE 46): attention 113.25M a layer (q 50.33M, k and v
    6.29M each, o 50.33M), an expert 37.75M, router 0.79M, the dense MLP
    339.74M; a chip's expert layer 755.8M, its dense layer 453.0M, an eighth
    of the vocabulary 235.9M; 5.98B parameters = 11.96 GB; 4,096 B of keys and
    values a token a layer."""
    from perf.reference import exaone_moe as ref

    cfg = load_config(CONFIG)
    q, kv = 6144 * 8192, 6144 * 1024
    attn, dense, expert, router = 2 * q + 2 * kv, 3 * 6144 * 18432, 3 * 6144 * 2048, 6144 * 128
    assert [round(n / 1e6, 2) for n in (q, kv, attn, expert, router, dense)] == [
        50.33, 6.29, 113.25, 37.75, 0.79, 339.74]
    assert round((attn + router + 17 * expert) / 1e6, 1) == 755.8
    assert round((attn + dense) / 1e6, 1) == 453.0
    assert round(2 * 19200 * 6144 / 1e6, 1) == 235.9
    norms = 6144 + 2 * 128
    assert ref._attn_params(cfg) == attn + norms
    assert ref._dense_params(cfg) == dense + 6144
    assert ref._moe_params(cfg) == router + expert + 6144
    assert ref.expert_bytes(cfg) == 2 * expert == 75_497_472
    outside = 2 * (8 * (attn + norms) + dense + 6144 + 7 * (router + expert + 6144)
                   + 6144 + 19200 * 6144) + 7 * 128 * 4
    assert ref._outside_experts_bytes(cfg) == outside
    assert ref.weight_bytes(cfg) == outside + 7 * 16 * 2 * expert + 2 * 19200 * 6144
    assert round(ref.weight_bytes(cfg) / 1e9, 2) == 11.96
    assert ref.routed_layers(cfg) == 7
    assert ref.kv_bytes_per_token(cfg) == 2 * 4096 == 8192  # the 2 global layers
    assert ref.window_bytes_per_slot(cfg) == 6 * 4096 * 128
    assert ref.window_bytes_per_slot(cfg, 50) == 6 * 4096 * 50
    # 64 rows of 8 among 128: an expert is missed by a row with 15/16.
    touched = 16 * (1 - (15 / 16) ** 64)
    assert ref.experts_touched(cfg, 64) == pytest.approx(touched) and 15.7 < touched < 15.8
    assert ref.moe_experts_bytes(cfg, 12) == 12 * 2 * expert
    assert ref.moe_experts_flops(cfg, 64) == 2 * expert * 64 * 8 / 8
    # A step of the cell at 64 slots of 2,700 resident tokens: 13.2 GB, of
    # which the touched experts 63%, the global pages 11%, the rings 1.5%.
    step = ref.decode_step_bytes_per_chip(cfg, 64 * 2700, 1)
    assert step == pytest.approx(
        outside + 7 * touched * 2 * expert + 64 * 2700 * 8192 + 64 * 6 * 4096 * 128)
    assert 13.1 < step / 1e9 < 13.3
    assert round(100 * 7 * touched * 2 * expert / step) == 63
    assert round(100 * 64 * 2700 * 8192 / step) == 11
    # 3.56 GFLOP a prompt token before attention (ISSUE 46 reckoned 3.8), and attention
    # against 1,000 earlier tokens: all of them in the 2 global layers, 128
    # in the 6 window layers.
    flat = 2.0 * (8 * (attn + norms) + dense + 6144 + 7 * (router + expert + 6144 + expert))
    assert ref.prefill_flops_per_token(cfg, 0) == flat and 3.5e9 < flat < 3.6e9
    assert ref.prefill_flops_per_token(cfg, 1000) == flat + 4 * 64 * 128 * (
        2 * 1000 + 6 * 128)


def test_the_familys_reader_kind_on_a_worked_trace():
    """18 whole chunks of 8 steps in the slice: the grouped products ran 18 x
    8 x 7 x 3 times over 512 sorted rows (64 rows of 8) in 2.0 s; the counters
    say 15.5 held experts a (pass, layer), an eighth of the assignments held
    and 2.5 ring pages of 45 read a slot."""
    from perf import readers
    from perf.reference import exaone_moe as ref

    cfg = load_config(CONFIG)

    def counters(touched, passes, held, absent, glob, ring):
        return {
            "kubeai_engine_moe_experts_touched_total": [
                ({"kind": "decode"}, touched), ({"kind": "prefill"}, 7 * touched)],
            "kubeai_engine_moe_passes_total": [
                ({"kind": "decode"}, passes), ({"kind": "prefill"}, passes)],
            "kubeai_engine_moe_assignments_total": [
                ({"held": "true"}, held), ({"held": "false"}, absent)],
            "kubeai_engine_decode_live_pages_total": [
                ({"pool": "global"}, glob), ({"pool": "window"}, ring)],
        }

    n = 18 * 8 * 7
    chunk_ops = {
        "gmm.3 bf16[512,2048]": {"count": n, "total_s": 0.65},
        "gmm.4 bf16[512,2048]": {"count": n, "total_s": 0.65},
        "gmm.5 bf16[512,6144]": {"count": n, "total_s": 0.70},
        "fusion.1 f32[64,19200]": {"count": 144, "total_s": 0.05}}
    obs = {
        "metrics0": counters(155, 10, 1000, 7000, 500, 30),
        "metrics1": counters(155 + 155 * 700, 7010, 1000 + 50000, 7000 + 350000,
                             500 + 42500, 30 + 2500),
        "polled": {"kv_tokens": [170000.0]},
        "trace": {"window_s": 3.0, "busy_s": 2.9,
                  "modules": {"jit__decode_chunk": {"count": 19, "total_s": 3.8},
                              "jit__prefill_admit": {"count": 3, "total_s": 0.3}},
                  "ops": {},
                  "ops_in": {
                      "jit__decode_chunk": {"count": 18, "total_s": 3.6,
                                            "ops": chunk_ops},
                      "jit__prefill_admit": {"count": 3, "total_s": 0.3, "ops": {
                          "gmm.9 bf16[16384,2048]": {"count": 21, "total_s": 0.1}}}}},
        "hf": cfg, "engine": {"num_slots": 64, "decode_chunk": 8}, "reference": ref,
        "chips": 1,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }
    # 144 forwards x 7 routed layers x 15.5 experts' bytes over the products'
    # 2.0 s inside the chunk (the admission's products are left out).
    assert readers.read(spec("kexaone_experts_roofline"), obs) == pytest.approx(
        100 * 144 * 7 * (15.5 * 75_497_472 / 819e9) / 2.0)
    assert readers.read(spec("window_pages_share"), obs) == pytest.approx(
        100 * 2500 / 45000)
    assert readers.read(spec("moe_held_share"), obs) == pytest.approx(12.5)
    assert readers.read(spec("moe_experts_touched"), obs) == pytest.approx(15.5)
    assert 0 < readers.read(spec("kexaone_experts_roofline"), obs) < 100
    # The whole step through the entry that exists: the family's own bytes
    # at the pool's mean resident tokens over a step of 3.8 / 19 / 8 s.
    assert readers.read(spec("decode_hbm_share"), obs) == pytest.approx(
        100 * ref.decode_step_bytes_per_chip(cfg, 170000.0, 1) / 819e9 / (3.8 / 19 / 8))
    assert 0 < readers.read(spec("decode_hbm_share"), obs) < 100
    # A program without the counters or the products (the parent), a
    # reference of another family, or a run without a trace: nothing is read
    # and nothing raises.
    bare = {**obs["trace"], "ops_in": {"jit__decode_chunk": {
        "count": 18, "total_s": 3.6, "ops": {}}}}
    from perf.reference import mixtral

    for broken, still_read in (
            ({**obs, "metrics0": {}, "metrics1": {}}, set()),
            ({**obs, "trace": None},
             {"moe_held_share", "moe_experts_touched", "window_pages_share"}),
            ({**obs, "trace": bare},
             {"moe_held_share", "moe_experts_touched", "window_pages_share"}),
            ({**obs, "reference": mixtral},
             {"moe_held_share", "moe_experts_touched", "window_pages_share"})):
        for name in NEW_METRICS - still_read:
            assert readers.read(spec(name), broken) is None, name


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_drives_both_pools_the_share_and_the_routed_check(
        tmp_path, trace):
    rc, lines, err = run(tmp_path, "--workload", TINY, "--seed", str(2**31 + 11),
                         "--seconds", "2", "--trace", str(trace),
                         *(() if trace else ("--control", "fp8")))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # never a chip result
    asked = next(l for l in lines if "routes asked of every request" in l)
    assert "'experts': 32" in asked and "'held': [8, 16]" in asked
    assert "'routed_layers': 7" in asked  # the leading dense layer has no row
    limits = load_config("tiny-exaone-moe")["correct"]
    assert set(line["compared"]) == set(limits) | {"failed"}
    for name, (value, limit) in line["compared"].items():
        assert limit == limits.get(name, 0) and value <= limit
    followed = next(l for l in lines if "followed over" in l)
    assert int(re.search(r"over (\d+) decisions", followed).group(1)) > 500
    b = load_benchmark()
    allowed = {m["name"] for m in b["per_layer" if trace else "end_to_end"]
               if "workloads" not in m or CELL in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    if trace:
        assert {"moe_imbalance", "route_followed_share", "routes_ms_per_step",
                "step_mean_ms", "kv_used_share", "batch_mean"} <= set(line["metrics"])
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert line["metrics"]["route_followed_share"]["value"] == (
            100.0 * line["compared"]["followed_share"][0])
        # Sequences of 21 to 84 tokens against rings of 24: the global pool
        # is what `kv_used_share` reads, a few per cent of 4 x 128 tokens.
        assert 0 < line["metrics"]["kv_used_share"]["value"] < 60
        # Trace readers find no TPU plane on the CPU and are left out.
        assert not {"decode_device_ms", "paged_attn_ms", "decode_hbm_share"} & set(
            line["metrics"])
    else:
        assert {"setup_s", "out_tok_s"} == set(line["metrics"])
        # The float8 reference in the program's place takes its own sets and
        # lands over the limits; the program itself is sound.
        over = next(l for l in lines if l.startswith("perf: control fp8 lands over: "))
        assert {"max_gap", "followed_share", "route_trail"} <= set(
            over.split("over: ")[1].split(", "))


@pytest.mark.parametrize("fault,over", [
    ("token", {"max_gap"}),
    ("route", {"followed_share", "route_trail"}),
])
def test_a_planted_fault_comes_out_not_correct_by_a_named_number(tmp_path, fault, over):
    rc, lines, err = run(tmp_path, "--workload", TINY, "--seed", "5",
                         "--seconds", "2", "--trace", "0", "--break-path", fault)
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    found = {name for name, (value, limit) in line["compared"].items()
             if value is None or value > limit}
    assert over <= found, line["compared"]
    # The hand-over itself is whole: the program handed over what it did.
    assert line["compared"]["route_rows_bad"] == [0, 0]
