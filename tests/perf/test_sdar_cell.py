"""The configuration `sdar-30b-a3b-v5e1`, its cell and its tiny rehearsal:
the three things a `model_config` PR must leave in `BENCHMARK.json`
(perf/README.md, "Adding things"), the catalog's keys as published, and
`run.py --rehearse` driving the block step, the hand-over and `replay` end to
end on the CPU, sound and with each planted fault."""

import json
import os
import re

import pytest

from test_rehearsal import KEYS, load_benchmark, load_config, run  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, CELL, TINY = "sdar-30b-a3b-v5e1", "sdar-30b-a3b.decode-sat", "tiny-sdar-moe.closed"
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
# The catalog entry's `config`, key for key.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
LIMITS = {"max_gap", "mean_gap", "short", "decisions_bad", "routes_followed_share",
          "routes_trail", "order_followed_share", "order_trail"}


def test_benchmark_json_holds_the_configuration_the_cell_and_its_metrics():
    b = load_benchmark()
    (entry,) = [c for c in b["configs"] if c["name"] == CONFIG]
    assert entry["source"] == SOURCE and entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "decode-sat", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    end = {m["name"] for m in b["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert end == {"out_tok_s", "setup_s"}
    layer = [m for m in b["per_layer"] if CELL in m.get("workloads", ())]
    assert all(m["moves"] == "out_tok_s" for m in layer)
    assert {"block_forward_ms", "block_forward_hbm_share", "forwards_per_token",
            "moe_experts_roofline", "routes_followed_share", "order_followed_share",
            "step_mean_ms", "device_idle_share", "moe_imbalance"} <= {
                m["name"] for m in layer}
    from perf import readers

    for m in layer:  # each names a reader that exists
        with open(os.path.join(ROOT, "perf", "layer_metrics", m["name"] + ".json")) as f:
            assert readers.kind(json.load(f)["reader"]) is not None, m["name"]


def test_every_width_is_the_catalogs_and_only_depth_is_reduced():
    cfg = load_config(CONFIG)
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["from"], cut["to"], cfg["num_hidden_layers"]) == (48, 7, 7)
    assert {"block_length", "denoising_steps", "remasking_strategy",
            "confidence_threshold", "mask_token_id", "qk_norm", "architectures",
            "weights", "tokenizer"} <= set(cfg["assumed"])
    assert (cfg["assumed"]["block_length"], cfg["assumed"]["denoising_steps"],
            cfg["assumed"]["confidence_threshold"], cfg["assumed"]["mask_token_id"]) == (
                4, 4, 0.9, 151669)
    assert (cfg["vocab_size"], cfg["prompt_vocab_size"]) == (151936, 151643)
    assert cfg["prompt_vocab_size"] <= cfg["assumed"]["mask_token_id"] < cfg["vocab_size"]
    assert cfg["engine"] == {"num_slots": 32, "max_seq_len": 2048}
    assert (cfg["source"], cfg["reference"], cfg["chips"]) == (SOURCE, "sdar_moe", 1)
    assert set(cfg["correct"]) == LIMITS == set(load_config("tiny-sdar-moe")["correct"])


def test_the_references_counts_at_the_published_widths():
    """Hand-worked: attention 18.87M, router 0.26M, an expert 4.72M, a layer
    623.1M parameters; 7 layers and the head are 9.35 GB a forward that
    touches every expert, 14,336 B of K and V a token."""
    from perf.reference import sdar_moe as ref

    cfg = load_config(CONFIG)
    attn = 2048 * 4096 * 2 + 2 * 2048 * 512
    expert = 3 * 2048 * 768
    assert (attn, expert) == (18_874_368, 4_718_592)
    layer = attn + 2048 * 128 + 128 * expert + 2 * 2048 + 2 * 128
    assert ref.layer_params(cfg, 128) == layer and round(layer / 1e6, 1) == 623.1
    assert ref.weight_bytes(cfg) == 2 * (7 * layer + 2048 + 151936 * 2048)
    assert round(ref.weight_bytes(cfg) / 1e9, 2) == 9.35
    assert ref.kv_bytes_per_token(cfg) == 14336
    # 128 rows of 8 leave an expert idle once in 3,900 forwards a layer.
    assert 127.9 < ref.experts_touched(cfg, 128) < 128
    assert ref.experts_touched(cfg, 1) == pytest.approx(8)
    assert ref.block_forward_bytes(cfg, 0, 128) == ref.weight_bytes(cfg)
    assert ref.block_forward_bytes(cfg, 1000, 100) == (
        ref.weight_bytes(cfg) + 1000 * 14336 - 7 * 28 * 2 * expert)
    assert ref.moe_experts_flops(cfg, 128) == 2 * 128 * 8 * expert
    assert ref.moe_experts_bytes(cfg, 100) == 2 * 100 * expert
    assert ref.prefill_flops_per_token(cfg, 100) == 7 * (
        2 * (attn + 2048 * 128 + 8 * expert) + 4 * 32 * 128 * 100)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_drives_the_block_step_the_hand_over_and_replay(tmp_path, trace):
    rc, lines, err = run(tmp_path, "--workload", TINY, "--seed", str(2**31 + 11),
                         "--seconds", "2", "--trace", str(trace))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # never a chip result
    assert any("handed over by every request: ['kubeai_forwards']" in l for l in lines)
    assert not any("routes asked of every request" in l for l in lines)
    limits = load_config("tiny-sdar-moe")["correct"]
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
        assert {"forwards_per_token", "routes_followed_share", "order_followed_share",
                "moe_imbalance", "routes_ms_per_step", "step_mean_ms"} <= set(
                    line["metrics"])
        # First and last blocks of a request are not whole: a little over 1.25.
        assert 1.25 <= line["metrics"]["forwards_per_token"]["value"] < 1.4
        assert line["metrics"]["routes_followed_share"]["value"] == (
            100.0 * line["compared"]["routes_followed_share"][0])
        # Trace readers find no TPU plane on the CPU and are left out.
        assert not {"block_forward_ms", "block_forward_hbm_share",
                    "moe_experts_roofline"} & set(line["metrics"])
    else:
        assert {"setup_s", "out_tok_s"} == set(line["metrics"])


@pytest.mark.parametrize("fault,over", [
    ("token", {"max_gap"}),
    ("route", {"routes_followed_share", "routes_trail"}),
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
    assert line["compared"]["decisions_bad"] == [0, 0]


def test_the_float8_control_takes_its_own_decisions_and_fails_the_limits(tmp_path):
    rc, lines, err = run(tmp_path, "--workload", TINY, "--seed", "6",
                         "--seconds", "2", "--trace", "0", "--control", "fp8")
    assert rc == 0, err[-2000:]
    over = next(l for l in lines if l.startswith("perf: control fp8 lands over: "))
    assert {"max_gap", "routes_followed_share", "routes_trail"} <= set(
        over.split("over: ")[1].split(", "))
    assert json.loads(lines[-1])["correct"] is True  # the program itself is sound


def test_the_block_readers_on_a_worked_trace():
    """20 chunks of 144 ms in the slice, 10 forwards a chunk by the counters,
    100 experts touched a layer: a forward is 14.4 ms; it has to stream
    9.35 GB less 7 x 28 idle experts = 7.50 GB + 10,000 resident tokens; the
    21 grouped products of a forward took 10 ms of it."""
    from perf import readers
    from perf.reference import sdar_moe as ref

    cfg = load_config(CONFIG)

    def spec(name):
        with open(os.path.join(ROOT, "perf", "layer_metrics", name + ".json")) as f:
            return json.load(f)

    def counters(forwards, chunks, touched, passes, denoise, commit, tokens):
        return {
            "kubeai_engine_block_program_forwards_total": [({}, forwards)],
            "kubeai_engine_block_chunks_total": [({}, chunks)],
            "kubeai_engine_moe_experts_touched_total": [
                ({"kind": "decode"}, touched), ({"kind": "prefill"}, 5 * touched)],
            "kubeai_engine_moe_passes_total": [
                ({"kind": "decode"}, passes), ({"kind": "prefill"}, passes)],
            "kubeai_engine_block_forwards_total": [
                ({"kind": "denoise"}, denoise), ({"kind": "commit"}, commit)],
            "kubeai_engine_block_tokens_total": [({}, tokens)],
        }

    obs = {
        "metrics0": counters(50, 5, 700, 7, 40, 10, 40),
        "metrics1": counters(2050, 205, 1400700, 14007, 32040, 8010, 32040),
        "polled": {"kv_tokens": [10000.0]},
        "trace": {"window_s": 3.0, "busy_s": 2.9,
                  "modules": {"jit__block_chunk": {"count": 20, "total_s": 2.88},
                              "jit__block_admit": {"count": 12, "total_s": 0.09}},
                  "ops": {"gmm.23 bf16[1024,768]": 0.7, "gmm.24 bf16[1024,768]": 0.7,
                          "gmm.25 bf16[1024,2048]": 0.6,
                          "gmm.21 bf16[2048,768]": 0.5, "fusion.1 f32[32,4]": 0.2},
                  "ops_in": {
                      "jit__block_chunk": {"count": 20, "total_s": 2.88, "ops": {
                          "gmm.23 bf16[1024,768]": {"count": 1400, "total_s": 0.7},
                          "gmm.24 bf16[1024,768]": {"count": 1400, "total_s": 0.7},
                          "gmm.25 bf16[1024,2048]": {"count": 1400, "total_s": 0.6},
                          "fusion.1 f32[32,4]": {"count": 200, "total_s": 0.2}}},
                      "jit__block_admit": {"count": 12, "total_s": 0.09, "ops": {
                          "gmm.21 bf16[2048,768]": {"count": 84, "total_s": 0.5}}}}},
        "hf": cfg, "engine": {"num_slots": 32}, "reference": ref,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    }
    assert readers.read(spec("block_forward_ms"), obs) == pytest.approx(14.4)
    need = ref.block_forward_bytes(cfg, 10000.0, 100.0)
    assert need == ref.weight_bytes(cfg) - 7 * 28 * 2 * 3 * 2048 * 768 + 10000 * 14336
    assert readers.read(spec("block_forward_hbm_share"), obs) == pytest.approx(
        100 * need / 819e9 / 0.0144)
    assert readers.read(spec("forwards_per_token"), obs) == pytest.approx(1.25)
    # 200 forwards x 7 layers x (100 experts x 9.44 MB / 819 GB/s) over the
    # 2.0 s of grouped products inside the chunk (the admission's are left out).
    assert readers.read(spec("moe_experts_roofline"), obs) == pytest.approx(
        100 * 200 * 7 * (100 * 2 * 3 * 2048 * 768 / 819e9) / 2.0)
    assert readers.read(spec("moe_experts_roofline"), obs) < 100
    # A program without the counters (the parent), or a run without a trace:
    # nothing is read and nothing raises.
    for broken in ({**obs, "metrics0": {}, "metrics1": {}}, {**obs, "trace": None}):
        for name in ("block_forward_ms", "block_forward_hbm_share",
                     "moe_experts_roofline"):
            assert readers.read(spec(name), broken) is None
    assert readers.read(spec("forwards_per_token"),
                        {**obs, "metrics0": {}, "metrics1": {}}) is None


def block_obs(chunk_ops, admit_ops, flops_per_s=197e12):
    """What a traced run of the cell observes, from device events as
    `trace_reduce.extract` gives them: 2 chunk programs of 100 ms and one
    admission of 30 ms, each with the operations `[name, seconds]` handed in
    laid end to end from its start, between two chunks that the slice's
    edges cut (20 and 30 ms of them are seen, with a product each); 10
    forwards a chunk and 100 experts touched a layer by the counters."""
    from perf import readers, trace_reduce
    from perf.reference import sdar_moe as ref

    cut = [["gmm.23 bf16[1024,768]", 0.01]]
    modules = [["jit__block_chunk(11)", 0.95, 0.02], ["jit__block_chunk(11)", 1.0, 0.1],
               ["jit__block_admit(12)", 1.2, 0.03], ["jit__block_chunk(11)", 1.3, 0.1],
               ["jit__block_chunk(11)", 1.45, 0.03]]
    ops = []
    for (_, at, _), mine in zip(modules, (cut, chunk_ops, admit_ops, chunk_ops, cut)):
        for name, seconds in mine:
            ops.append([name, at, seconds])
            at += seconds

    def counters(f, c, t, p):
        return readers.parse_prometheus(
            f"kubeai_engine_block_program_forwards_total {f}\n"
            f"kubeai_engine_block_chunks_total {c}\n"
            f'kubeai_engine_moe_experts_touched_total{{kind="decode"}} {t}\n'
            f'kubeai_engine_moe_passes_total{{kind="decode"}} {p}\n')

    return {
        "metrics0": counters(0, 0, 0, 0), "metrics1": counters(100, 10, 70000, 700),
        "polled": {}, "hf": load_config(CONFIG), "engine": {"num_slots": 32},
        "reference": ref,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": flops_per_s},
        "trace": trace_reduce.summarize(
            {"devices": [{"name": "/device:TPU:0", "modules": modules, "ops": ops}]}),
    }


def roofline(obs):
    from perf import readers

    with open(os.path.join(ROOT, "perf", "layer_metrics",
                           "moe_experts_roofline.json")) as f:
        return readers.read(json.load(f), obs)


DECODE = [["gmm.23 bf16[1024,768]", 0.03], ["gmm.24 bf16[1024,768]", 0.03],
          ["gmm.25 bf16[1024,2048]", 0.03], ["fusion.341 f32[32,4]", 0.005]]
# 20 forwards x 7 layers x 100 experts x 9.44 MB / 819 GB/s over 0.18 s.
BYTES_BOUND = 100 * 20 * 7 * (100 * 2 * 3 * 2048 * 768 / 819e9) / 0.18


@pytest.mark.parametrize("admit_ops", [
    [],
    [["gmm.21 bf16[16384,768]", 0.01]],
    # One prompt in the 128 bucket: 1 x 128 x 8 = 1,024 assignments, the
    # decode forward's own count, in a program of its own.
    [["gmm.21 bf16[1024,768]", 0.01], ["gmm.22 bf16[1024,2048]", 0.02]],
], ids=["no-admission", "admission-16384-rows", "admission-with-decode-rows"])
def test_products_outside_the_chunk_do_not_move_the_roofline(admit_ops):
    assert roofline(block_obs(DECODE, admit_ops)) == pytest.approx(BYTES_BOUND)
    assert BYTES_BOUND < 100


@pytest.mark.parametrize("assignments", [1024, 2048, 4096])
def test_products_inside_the_chunk_count_whatever_their_rows(assignments):
    """A forward of 8 rows a slot has 2,048 assignments where one of 4 has
    1,024: found all the same, and the FLOPs follow the trace's own shape
    (seen with an MXU slow enough for the FLOPs to bound the product)."""
    ops = [[f"gmm.3{i} bf16[{assignments},{n}]", 0.03]
           for i, n in enumerate((768, 768, 2048))]
    assert roofline(block_obs(ops, [])) == pytest.approx(BYTES_BOUND)
    flops = 2.0 * assignments * 3 * 2048 * 768  # a layer's three products
    assert roofline(block_obs(ops, [], flops_per_s=1e12)) == pytest.approx(
        100 * 20 * 7 * (flops / 1e12) / 0.18)


def test_products_of_two_row_counts_in_one_chunk_take_their_mean():
    ops = [[f"gmm.{i} bf16[{assignments},768]", seconds]
           for i, (assignments, seconds) in enumerate(
               [(1024, 0.01)] * 3 + [(2048, 0.02)] * 3)]
    mean = 2.0 * 1536 * 3 * 2048 * 768
    assert roofline(block_obs(ops, [], flops_per_s=1e12)) == pytest.approx(
        100 * 20 * 7 * (mean / 1e12) / 0.18)


@pytest.mark.parametrize("chunk_ops", [
    [], [["fusion.341 f32[32,4]", 0.05]],
], ids=["empty-chunk", "no-product-in-the-chunk"])
def test_a_chunk_without_grouped_products_reads_nothing(chunk_ops):
    assert roofline(block_obs(chunk_ops, [["gmm.21 bf16[1024,768]", 0.01]])) is None
