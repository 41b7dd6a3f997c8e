"""The configuration `qwen3-next-80b-a3b-v5e1`, its cell and its tiny rehearsal:
the three things a `model_config` PR must leave in `BENCHMARK.json`
(perf/README.md, "Adding things"), the catalog's keys as published with the
expert share as explicit keys, the reference's counts hand-worked, the family's
reader kind on a worked trace, and `run.py --rehearse` driving the state
pools, the expert share and the routed `correct` end to end on the CPU, sound
and with each planted fault."""

import json
import os
import re

import pytest

from test_rehearsal import KEYS, load_benchmark, load_config, run  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, CELL = "qwen3-next-80b-a3b-v5e1", "qwen3-next-80b-a3b.decode-sat"
TINY = "tiny-qwen3-next.closed"
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
# The catalog entry's `config`, key for key.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": (48, 16), "num_experts": (512, 64)}
LIMITS = {"max_gap", "mean_gap", "short", "route_rows_bad", "followed_share",
          "route_trail"}
NEW_METRICS = {"hybrid_decode_hbm_share", "gdn_update_roofline", "moe_held_roofline",
               "moe_held_share", "moe_experts_touched"}


def spec(name):
    with open(os.path.join(ROOT, "perf", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_benchmark_json_holds_the_configuration_the_cell_and_its_metrics():
    b = load_benchmark()
    (entry,) = [c for c in b["configs"] if c["name"] == CONFIG]
    assert entry["source"] == SOURCE and entry["reduced"] == list(REDUCED)
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "decode-sat", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert b["configs"][-1] is entry and b["workloads"][-1] is cell  # appended
    end = {m["name"] for m in b["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert end == {"out_tok_s", "setup_s"}
    layer = [m for m in b["per_layer"] if CELL in m.get("workloads", ())]
    assert all(m["moves"] == "out_tok_s" for m in layer)
    names = {m["name"] for m in layer}
    assert {"decode_device_ms", "paged_attn_ms", "step_mean_ms", "device_idle_share",
            "moe_imbalance", "route_followed_share", "routes_ms_per_step",
            "kv_used_share", "compiles_in_window", "batch_mean"} <= names
    # A dense model's count, and a metric that reads nothing since PR 42.
    assert not {"decode_hbm_share", "starved_before_prefill_share"} & names
    # The family's own five metrics are data files and a reader kind, read in
    # PR 43's chip runs through a scratch copy of BENCHMARK.json that names
    # them, and NOT entries: the driver takes new entries only at the
    # list's end, and tests/perf/test_device_queue_metrics.py holds PR 40's six
    # to be the LAST six with exactly PR 40's cells, which also keeps this cell
    # out of the two device-queue lists. Only a `benchmark` PR may edit that
    # test (PERF.md section 7 item 18); an entry it then adds reads here alone.
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["unit"] in ("%", "count")
    assert not {"device_starved_ms_per_step", "dispatch_drained_share"} & names
    from perf import readers

    for name in names | NEW_METRICS:  # each names a reader that exists
        assert readers.kind(spec(name)["reader"]) is not None, name


def test_every_width_is_the_catalogs_and_depth_and_the_experts_held_are_reduced():
    cfg = load_config(CONFIG)
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert list(cfg["reduced"]) == list(REDUCED)
    for key, (published, here) in REDUCED.items():
        cut = cfg["reduced"][key]
        assert (cut["from"], cut["to"], cfg[key]) == (published, here, here), key
    # The share as explicit keys: the router's width, which share this is.
    assert (cfg["router_num_experts"], cfg["expert_share_index"]) == (512, 0)
    assert {"router_num_experts", "expert_share_index", "recurrent_state_dtype",
            "conv_tail_dtype", "A_log_dt_bias", "weights", "mtp", "tokenizer"} <= set(
                cfg["assumed"])
    assert "8 chips share each layer" in cfg["deployment"]
    assert "WHOLE" in cfg["deployment"]  # the mixers and the head, over their share
    assert (cfg["vocab_size"], cfg["prompt_vocab_size"]) == (151936, 151643)
    assert cfg["engine"]["max_seq_len"] == 2048 and cfg["engine"]["num_slots"] in (32, 64)
    assert (cfg["source"], cfg["reference"], cfg["chips"]) == (SOURCE, "qwen3_next", 1)
    assert set(cfg["correct"]) == LIMITS == set(load_config("tiny-qwen3-next")["correct"])
    # The program reads the same share from the same keys.
    from kubeai_tpu.models.registry import get_model_family

    family = get_model_family(cfg["architectures"][0])
    mcfg = family.config_from_hf(cfg)
    assert family.route_dims(mcfg) == (512, 10, 16)
    assert family.held_experts(mcfg) == (0, 64)
    assert family.recurrent_state(mcfg)["state_layers"] == 12
    assert family.recurrent_state(mcfg)["page_layers"] == 4


def test_the_references_counts_at_the_published_widths():
    """Hand-worked (ISSUE 43): outside the experts a DeltaNet layer is 33.72M
    parameters and an attention layer 27.26M, router + shared expert + gate
    4.20M a layer, an expert 3.146M; a slot's state 2.146 MB a DeltaNet layer;
    2,048 B of keys and values a token a layer that has them."""
    from perf.reference import qwen3_next as ref

    cfg = load_config(CONFIG)
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048
    expert = 3 * 2048 * 512
    assert [round(n / 1e6, 2) for n in (gdn, attn, moe)] == [33.72, 27.26, 4.20]
    assert round(expert / 1e6, 3) == 3.146
    assert ref._mixer_params(cfg) == (gdn + 2048 + 128, attn + 2048 + 512)
    assert ref.expert_bytes(cfg) == 2 * expert
    outside = 2 * (12 * (gdn + 2048 + 128) + 4 * (attn + 2048 + 512)
                   + 16 * (moe + 2048) + 2048 + 151936 * 2048) + 12 * 64 * 2
    assert ref._outside_experts_bytes(cfg) == outside
    assert ref.weight_bytes(cfg) == outside + 16 * 64 * 2 * expert + 2 * 151936 * 2048
    assert round(ref.weight_bytes(cfg) / 1e9, 2) == 8.85
    assert ref.kv_bytes_per_token(cfg) == 4 * 2048 == 8192
    slot = 12 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert ref.state_bytes_per_slot(cfg) == slot and round(slot / 12 / 1e6, 3) == 2.146
    assert ref.gdn_update_bytes(cfg, 64) == 2 * 64 * 12 * 32 * 128 * 128 * 4
    assert ref.moe_experts_bytes(cfg, 46) == 46 * 2 * expert
    assert ref.moe_experts_flops(cfg, 64) == 2 * expert * 64 * 10 / 8
    # ISSUE 43's step: 46 held experts a layer, 64 slots, 30,000 resident
    # tokens: 9.9 GB, of which the experts 47% and the state 33%.
    step = ref.hybrid_decode_bytes(cfg, 30_000, 46, 64)
    assert step == 16 * 46 * 2 * expert + outside + 2 * 64 * slot + 30_000 * 8192
    assert 9.9 < step / 1e9 < 10.0
    assert round(100 * 16 * 46 * 2 * expert / step) == 47
    assert round(100 * 2 * 64 * slot / step) == 33
    assert ref.prefill_flops_per_token(cfg, 100) == (
        2.0 * (12 * (gdn + 2048 + 128) + 4 * (attn + 2048 + 512)
               + 16 * (moe + expert * 10 / 8))
        + 4 * 4 * 16 * 256 * 100 + 12 * 6 * 32 * 128 * 128)


def test_the_familys_reader_kind_on_a_worked_trace():
    """18 whole chunks of 8 steps in the slice at 160 ms a chunk: a step is
    20 ms. The state kernel ran 18 x 8 x 12 times over 64 slots in 0.9 s; the
    grouped products 18 x 8 x 16 x 3 times over 640 sorted rows in 1.2 s; the
    counters say 46 held experts a (pass, layer) and an eighth of the
    assignments held."""
    from perf import readers
    from perf.reference import qwen3_next as ref

    cfg = load_config(CONFIG)

    def counters(touched, passes, held, absent):
        return {
            "kubeai_engine_moe_experts_touched_total": [
                ({"kind": "decode"}, touched), ({"kind": "prefill"}, 7 * touched)],
            "kubeai_engine_moe_passes_total": [
                ({"kind": "decode"}, passes), ({"kind": "prefill"}, passes)],
            "kubeai_engine_moe_assignments_total": [
                ({"held": "true"}, held), ({"held": "false"}, absent)],
        }

    chunk_ops = {
        "_gdn_update_pallas.1 f32[64,32,128]": {"count": 18 * 8 * 12, "total_s": 0.9},
        "gmm.3 bf16[640,512]": {"count": 18 * 8 * 16, "total_s": 0.4},
        "gmm.4 bf16[640,512]": {"count": 18 * 8 * 16, "total_s": 0.4},
        "gmm.5 bf16[640,2048]": {"count": 18 * 8 * 16, "total_s": 0.4},
        "fusion.1 f32[64,151936]": {"count": 144, "total_s": 0.1}}
    obs = {
        "metrics0": counters(460, 10, 1000, 7000),
        "metrics1": counters(460 + 46 * 16000, 16010, 1000 + 80000, 7000 + 560000),
        "polled": {"kv_tokens": [30000.0]},
        "trace": {"window_s": 3.0, "busy_s": 2.95,
                  "modules": {"jit__decode_chunk": {"count": 19, "total_s": 3.04},
                              "jit__prefill_admit": {"count": 3, "total_s": 0.1}},
                  "ops": {},
                  "ops_in": {
                      "jit__decode_chunk": {"count": 18, "total_s": 2.88,
                                            "ops": chunk_ops},
                      "jit__prefill_admit": {"count": 3, "total_s": 0.1, "ops": {
                          "gmm.9 bf16[20480,512]": {"count": 48, "total_s": 0.05}}}}},
        "hf": cfg, "engine": {"num_slots": 64, "decode_chunk": 8}, "reference": ref,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }
    step_s = 3.04 / 19 / 8
    need = ref.hybrid_decode_bytes(cfg, 30000.0, 46.0, 64.0)
    assert readers.read(spec("hybrid_decode_hbm_share"), obs) == pytest.approx(
        100 * need / 819e9 / step_s)
    # 144 steps x 12 layers of states read and written, over the kernel's 0.9 s.
    assert readers.read(spec("gdn_update_roofline"), obs) == pytest.approx(
        100 * 144 * ref.gdn_update_bytes(cfg, 64) / 819e9 / 0.9)
    # 144 forwards x 16 layers x 46 experts' bytes over the products' 1.2 s
    # inside the chunk (the admission's products are left out).
    assert readers.read(spec("moe_held_roofline"), obs) == pytest.approx(
        100 * 144 * 16 * (46 * 2 * 3 * 2048 * 512 / 819e9) / 1.2)
    assert readers.read(spec("moe_held_share"), obs) == pytest.approx(12.5)
    assert readers.read(spec("moe_experts_touched"), obs) == pytest.approx(46.0)
    for name in ("hybrid_decode_hbm_share", "gdn_update_roofline", "moe_held_roofline"):
        assert 0 < readers.read(spec(name), obs) < 100
    # A program without the counters or the kernel (the parent), or a run
    # without a trace: nothing is read and nothing raises. (The state kernel's
    # share needs the trace alone, the two counter ratios the counters alone.)
    bare = {**obs["trace"], "ops_in": {"jit__decode_chunk": {
        "count": 18, "total_s": 2.88, "ops": {}}}}
    for broken, still_read in (
            ({**obs, "metrics0": {}, "metrics1": {}}, {"gdn_update_roofline"}),
            ({**obs, "trace": None}, {"moe_held_share", "moe_experts_touched"}),
            ({**obs, "trace": bare}, {"moe_held_share", "moe_experts_touched"})):
        for name in NEW_METRICS - still_read:
            assert readers.read(spec(name), broken) is None, name


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_drives_the_state_pools_the_share_and_the_routed_check(
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
    limits = load_config("tiny-qwen3-next")["correct"]
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
        # Trace readers find no TPU plane on the CPU and are left out.
        assert not {"decode_device_ms", "paged_attn_ms"} & set(line["metrics"])
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
