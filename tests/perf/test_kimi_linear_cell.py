"""The configuration `kimi-linear-48b-a3b-v5e1`, its cell and its tiny
rehearsal: the three things a `model_config` PR must leave in `BENCHMARK.json`
(perf/README.md, "Adding things"), the catalog's keys as published with the two
cuts and the expert share as explicit keys, the reference's counts hand-worked,
the family's metric files and reader kind on a worked trace, and `run.py
--rehearse` driving the latent pool beside the state pools, the expert share
behind a leading dense layer and the routed `correct` end to end on the CPU,
sound and with each planted fault.

The entries are held BEHIND those that were there (PR 46's are the ones
before), never to a list's end: the next configuration appends behind these."""

import json
import os
import re

import pytest

from test_rehearsal import KEYS, load_benchmark, load_config, run  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, CELL = "kimi-linear-48b-a3b-v5e1", "kimi-linear-48b-a3b.gen-sat"
BEFORE = ("k-exaone-236b-a23b-v5e1", "k-exaone-236b-a23b.gen-sat")
TINY = "tiny-kimi-linear.closed"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
# The catalog entry's `config`, key for key.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                       22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}
REDUCED = {"num_hidden_layers": (27, 12), "num_experts": (256, 32)}
LIMITS = {"max_gap", "mean_gap", "short", "route_rows_bad", "followed_share",
          "route_trail"}
NEW_METRICS = {"kda_update_roofline", "mla_decode_roofline", "latent_pages_share"}
WANTED_WHEN_W7_LANDS = {"moe_held_share", "moe_experts_touched"}
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
    # Appended: right behind the entries that were there.
    names = [c["name"] for c in b["configs"]]
    assert names.index(CONFIG) == names.index(BEFORE[0]) + 1
    cells = [w["name"] for w in b["workloads"]]
    assert cells.index(CELL) == cells.index(BEFORE[1]) + 1
    before = cells[:cells.index(CELL)]
    for m in [*b["end_to_end"], *b["per_layer"]]:
        if CELL in m.get("workloads", ()):
            at = m["workloads"].index(CELL)
            assert set(m["workloads"][:at]) <= set(before), m["name"]
            assert not set(m["workloads"][at + 1:]) & set(before), m["name"]
    # Every list the gen-sat cell before it is in, but the page-pool kernel's
    # (`paged_attn_ms` reads `^_paged_pallas`: this family's decode attention
    # is `_latent_decode_pallas`, over rows without heads).
    there, here = reported(b, BEFORE[1]), reported(b, CELL)
    assert set(there) - set(here) == {"paged_attn_ms"} and set(here) <= set(there)
    # The whole step's share of the HBM roofline, through the entry that
    # exists, with the family's own count of a step's bytes.
    assert "decode_hbm_share" in here
    from perf.reference import kimi_linear

    assert callable(kimi_linear.decode_step_bytes_per_chip)
    # The mix as it stands: no new traffic file.
    mix = json.load(open(os.path.join(ROOT, "perf", "traffic", "gen-sat.json")))
    assert (mix["loop"], mix["clients"]) == ("closed", "num_slots")
    assert mix["prompt_tokens"] == {"dist": "uniform", "low": 512, "high": 2048}
    assert mix["output_tokens"] == {"dist": "uniform", "low": 768, "high": 1920}
    # The family's own metrics are data files and a reader kind, read in PR
    # 50's chip runs through a copy of BENCHMARK.json that names them, and NOT
    # entries (PERF.md section 7 item 18): an entry a `benchmark` PR later
    # adds reads here.
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS | WANTED_WHEN_W7_LANDS:
            assert CELL in m["workloads"] and m["unit"] in ("%", "count")


def test_the_cell_reports_what_a_routed_share_cell_reports():
    from perf import readers

    b = load_benchmark()
    end = {m["name"] for m in b["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert end == {"out_tok_s", "setup_s"}
    layer = reported(b, CELL)
    assert all(m["moves"] == "out_tok_s" for m in layer.values())
    assert {"decode_device_ms", "decode_hbm_share", "step_mean_ms",
            "device_idle_share", "moe_imbalance", "route_followed_share",
            "routes_ms_per_step", "kv_used_share", "compiles_in_window",
            "batch_mean", "prefill_step_share", "step_host_share",
            "tpot_p95_ms.sat", "admit_host_ms_per_step", "admit_wait_mean_ms",
            "admit_calls", "prefill_useful_tokens", "prefill_pad_tokens",
            "loop_gap_ms_per_step", "emit_busy_ms_per_step",
            "emit_lag_mean_ms"} <= set(layer)
    assert not DEVICE_QUEUE & set(layer)
    for name in set(layer) | NEW_METRICS | WANTED_WHEN_W7_LANDS:
        assert readers.kind(spec(name)["reader"]) is not None, name


def test_every_width_is_the_catalogs_and_the_two_cuts_are_stated():
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
    assert (cfg["router_num_experts"], cfg["expert_share_index"]) == (256, 0)
    assert {"residual", "kda_gates", "kda_norms", "A_log_dt_bias",
            "recurrent_state_dtype", "conv_tail_dtype", "mla", "latent_row",
            "router_bias", "router_num_experts", "expert_share_index",
            "shared_expert", "weights", "prompt_vocab_size"} <= set(cfg["assumed"])
    assert "8 chips share each layer" in cfg["deployment"]
    assert "WHOLE" in cfg["deployment"] and "16 chips" in cfg["deployment"]
    # The vocabulary is whole; prompts keep off the tokenizer's reserved ids.
    assert cfg["prompt_vocab_size"] == 163584 < cfg["vocab_size"]
    assert cfg["engine"] == {
        "num_slots": 128, "max_seq_len": 4096, "max_admit_batch": 2,
        # Two buckets between the powers of two (PERF.md section 6, PR 50,
        # the refusal round); 768 and 1536 do not compile.
        "prefill_buckets": [512, 1024, 1280, 1792, 2048]}
    # An admission of 1,536 tokens in all does not compile for a v5e (the
    # expert layer's gather of 12,288 rows: AOT, PR 50): no shape has them.
    assert all(a * b != 1536 for b in cfg["engine"]["prefill_buckets"] for a in (1, 2))
    assert all(b % 128 == 0 for b in cfg["engine"]["prefill_buckets"])
    assert (cfg["source"], cfg["reference"], cfg["chips"]) == (SOURCE, "kimi_linear", 1)
    assert set(cfg["correct"]) == LIMITS == set(load_config("tiny-kimi-linear")["correct"])
    # The program reads the same cuts and the same share from the same keys.
    from kubeai_tpu.models.registry import get_model_family

    family = get_model_family(cfg["architectures"][0])
    mcfg = family.config_from_hf(cfg)
    assert family.route_dims(mcfg) == (256, 8, 11)
    assert family.held_experts(mcfg) == (0, 32)
    rec = family.recurrent_state(mcfg)
    assert (rec["state_layers"], rec["page_layers"]) == (9, 3)
    assert rec["pools"]["recurrent"][0] == (32, 128, 128)
    assert rec["pools"]["conv"][0] == (3 * 12288,)
    latent = family.latent_pages(mcfg)
    assert latent["row"] == (640,)  # 512 + 64 in whole lanes
    assert family.kv_layers is None


def test_the_references_counts_at_the_published_widths():
    """Hand-worked (ISSUE 50): a KDA layer 39.51M (q, k, v 28.31M, o 9.44M,
    the low-rank gates and beta 1.71M, the convolution 0.05M), an MLA layer
    29.12M (q 14.16M, kv_a 1.33M, kv_b 4.19M, o 9.44M), the dense FFN 63.70M,
    an expert 7.078M, router + shared expert 7.67M; 3.84B parameters = 7.68
    GB; 1,152 B a token a latent layer, 19.54 MB of state a slot."""
    from perf.reference import kimi_linear as ref

    cfg = load_config(CONFIG)
    E, HD = 2304, 4096
    qkv, o, gates, conv = 3 * E * HD, HD * E, E * 288 + 2 * 128 * HD, 4 * 3 * HD
    kda = qkv + o + gates + conv
    q, kva, kvb = E * 32 * 192, E * 576, 512 * 32 * 256
    mla = q + kva + kvb + o
    dense, expert, router = 3 * E * 9216, 3 * E * 1024, E * 256
    assert [round(n / 1e6, 2) for n in (qkv, o, gates, conv, kda)] == [
        28.31, 9.44, 1.71, 0.05, 39.51]
    assert [round(n / 1e6, 2) for n in (q, kva, kvb, mla, dense, expert)] == [
        14.16, 1.33, 4.19, 29.11, 63.7, 7.08]
    assert ref._kda_params(cfg) == kda + E + 128
    assert ref._mla_params(cfg) == mla + E + 512
    assert ref._dense_params(cfg) == dense + E
    assert ref._moe_params(cfg) == router + expert + E
    assert ref.expert_bytes(cfg) == 2 * expert == 14_155_776
    outside = (2 * (9 * (kda + E + 128) + 3 * (mla + E + 512) + dense + E
                    + 11 * (router + expert + E) + E + 163840 * E)
               + 4 * (9 * (32 + HD) + 11 * 256))
    assert ref._outside_experts_bytes(cfg) == outside
    assert ref.weight_bytes(cfg) == outside + 11 * 32 * 2 * expert + 2 * 163840 * E
    assert round(ref.weight_bytes(cfg) / 1e9, 2) == 7.68
    assert ref.routed_layers(cfg) == 11
    # A token: 576 numbers in each of the 3 latent layers; the pool holds 640.
    assert ref.latent_bytes_per_token(cfg) == 1152 and ref.latent_row_bytes(cfg) == 1280
    assert ref.kv_bytes_per_token(cfg) == 3 * 1152 == 3456
    assert ref.mla_decode_bytes(cfg, 1000) == 1000 * 1152
    assert ref.mla_decode_flops(cfg, 1000) == 2 * 32 * (512 + 64 + 512) * 1000
    # A slot: [32, 128, 128] float32 and 3 x 12,288 bf16, in 9 layers.
    assert ref.state_bytes_per_slot(cfg) == 9 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert round(ref.state_bytes_per_slot(cfg) / 1e6, 2) == 19.54
    assert ref.kda_update_bytes(cfg, 128) == 2 * 128 * 9 * 32 * 128 * 128 * 4
    assert ref.gdn_update_bytes is ref.kda_update_bytes
    # 128 rows of 8 among 256: an expert is missed by a row with 31/32.
    touched = 32 * (1 - (31 / 32) ** 128)
    assert ref.experts_touched(cfg, 128) == pytest.approx(touched) and 31.4 < touched < 31.5
    assert ref.moe_experts_bytes(cfg, 12) == 12 * 2 * expert
    assert ref.moe_experts_flops(cfg, 128) == 2 * expert * 128 * 8 / 8
    # A step of the cell at 128 slots of 2,700 resident tokens: 13.0 GB, of
    # which the state read and written 38%, the touched experts 38%, the
    # latent rows 9%: 15.9 ms at 819 GB/s, so about 8.0k tokens/s the ceiling.
    step = ref.decode_step_bytes_per_chip(cfg, 128 * 2700, 1)
    state = 2 * 128 * ref.state_bytes_per_slot(cfg)
    assert step == pytest.approx(
        outside + 11 * touched * 2 * expert + state + 128 * 2700 * 3456)
    assert step == ref.hybrid_decode_bytes(cfg, 128 * 2700, touched, 128)
    assert 12.9 < step / 1e9 < 13.1
    assert round(100 * state / step) == 38
    assert round(100 * 11 * touched * 2 * expert / step) == 38
    assert round(100 * 128 * 2700 * 3456 / step) == 9
    # 1.37 GFLOP a prompt token before attention; against 1,000 earlier
    # tokens the 3 latent layers add 2 x 32 x (192 + 128) x 1,000 each.
    flat = 2.0 * (9 * (kda + E + 128) + 3 * (mla + E + 512) + dense + E
                  + 11 * (router + expert + E + expert)) + 9 * 6 * 32 * 128 * 128
    assert ref.prefill_flops_per_token(cfg, 0) == flat and 1.36e9 < flat < 1.37e9
    assert ref.prefill_flops_per_token(cfg, 1000) == flat + 3 * 2 * 32 * 320 * 1000


def test_the_familys_metric_files_on_a_worked_trace():
    """18 whole chunks of 8 steps in the slice: the state kernel ran 18 x 8 x
    9 times over 128 slots in 0.9 s, the latent kernel 18 x 8 x 3 times over
    128 slots in 0.25 s; the counters say 5,500 live latent pages a dispatched
    chunk (43 a slot: 2.7k tokens)."""
    from perf import readers
    from perf.reference import kimi_linear as ref

    cfg = load_config(CONFIG)

    def counters(pages, chunks):
        return {
            "kubeai_engine_decode_live_pages_total": [({"pool": "latent"}, pages)],
            "kubeai_engine_dispatches_total": [
                ({"before": "decode", "queue": "busy"}, chunks),
                ({"before": "decode", "queue": "empty"}, 1.0),
                ({"before": "prefill", "queue": "busy"}, chunks / 4)],
        }

    chunk_ops = {
        "_gdn_update_pallas.1 f32[128,32,128]": {"count": 18 * 8 * 9, "total_s": 0.9},
        "_latent_decode_pallas.1 bf16[128,32,512]": {"count": 18 * 8 * 3, "total_s": 0.25},
        "gmm.3 bf16[1024,1024]": {"count": 18 * 8 * 11, "total_s": 0.4},
        "fusion.1 f32[128,163840]": {"count": 144, "total_s": 0.05}}
    obs = {
        "metrics0": counters(11000.0, 2.0),
        "metrics1": counters(11000.0 + 5500.0 * 700, 702.0),
        "polled": {"kv_tokens": [345600.0]},
        "trace": {"window_s": 3.0, "busy_s": 2.9,
                  "modules": {"jit__decode_chunk": {"count": 19, "total_s": 2.85},
                              "jit__prefill_admit": {"count": 3, "total_s": 0.1}},
                  "ops": {},
                  "ops_in": {
                      "jit__decode_chunk": {"count": 18, "total_s": 2.7,
                                            "ops": chunk_ops},
                      "jit__prefill_admit": {"count": 3, "total_s": 0.1, "ops": {
                          "gmm.9 bf16[32768,1024]": {"count": 33, "total_s": 0.05}}}}},
        "hf": cfg, "engine": {"num_slots": 128, "decode_chunk": 8, "page_size": 64},
        "reference": ref, "chips": 1,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }
    # 144 steps of 128 slots' states read and written over the kernel's 0.9 s.
    assert readers.read(spec("kda_update_roofline"), obs) == pytest.approx(
        100 * 144 * (2 * 128 * 9 * 32 * 128 * 128 * 4 / 819e9) / 0.9)
    # 432 runs, each the rows of (5,500 - 128) pages of 64 tokens once, which
    # takes longer to stream than to multiply (1,152 B against 69.6 kFLOP a
    # token: 1.41 ns against 0.35 ns).
    tokens = (5500 - 128) * 64
    assert readers.read(spec("mla_decode_roofline"), obs) == pytest.approx(
        100 * 432 * (tokens * 1152 / 819e9) / 0.25)
    assert tokens * 1152 / 819e9 > ref.mla_decode_flops(cfg, tokens) / 197e12
    assert readers.read(spec("latent_pages_share"), obs) == pytest.approx(100.0)
    for name in NEW_METRICS:
        assert 0 < readers.read(spec(name), obs) <= 100
    # The whole step through the entry that exists: the family's own bytes
    # at the pool's mean resident tokens over a step of 2.85 / 19 / 8 s.
    assert readers.read(spec("decode_hbm_share"), obs) == pytest.approx(
        100 * ref.decode_step_bytes_per_chip(cfg, 345600.0, 1) / 819e9 / (2.85 / 19 / 8))
    assert 0 < readers.read(spec("decode_hbm_share"), obs) < 100
    # A program without the counters or the kernels (the parent), a reference
    # of another family, or a run without a trace: nothing is read and
    # nothing raises.
    bare = {**obs["trace"], "ops_in": {"jit__decode_chunk": {
        "count": 18, "total_s": 2.7, "ops": {}}}}
    from perf.reference import mixtral

    for broken, still_read in (
            ({**obs, "metrics0": {}, "metrics1": {}}, {"kda_update_roofline"}),
            ({**obs, "trace": None}, {"latent_pages_share"}),
            ({**obs, "trace": bare}, {"latent_pages_share"}),
            ({**obs, "reference": mixtral}, {"latent_pages_share"})):
        for name in NEW_METRICS - still_read:
            assert readers.read(spec(name), broken) is None, name


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_drives_the_latent_pool_the_state_and_the_routed_check(
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
    assert "'experts': 16" in asked and "'held': [4, 8]" in asked
    assert "'routed_layers': 7" in asked  # the leading dense layer has no row
    limits = load_config("tiny-kimi-linear")["correct"]
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
        # Sequences of 21 to 84 tokens in a pool of 4 x 128: the latent pool
        # is what `kv_used_share` reads.
        assert 0 < line["metrics"]["kv_used_share"]["value"] < 70
        # Trace readers find no TPU plane on the CPU and are left out.
        assert not {"decode_device_ms", "decode_hbm_share"} & set(line["metrics"])
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
