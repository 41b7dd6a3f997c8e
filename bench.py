"""Benchmark: steady-state decode throughput of the TPU serving engine.

Prints ONE JSON line naming what it measured and where:
  {"metric": "...", "value": N, "unit": "tok/s",
   "platform": "tpu", "device_kind": "...", "devices": N}

The measurement runs in this process, the only one on the chip. Off a TPU
it exits non-zero without a number, unless `--cpu` was typed — and then the
line says platform "cpu", which is not a device metric. ROADMAP A1 replaces
this file with the real instrument; until then it is a quick closed-loop
probe.

Methodology: random-init Llama-3.2-1B-class weights (no checkpoint
downloads; throughput is weight-value-independent), all decode slots kept
full (continuous batching steady state), timed after compile warm-up.
`--smoke` runs a tiny config for quick sanity.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def llama_1b_cfg():
    from kubeai_tpu.models import llama

    # Llama-3.2-1B architecture (hidden 2048, 16 layers, GQA 32/8 heads).
    return llama.LlamaConfig(
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=500000.0,
        max_position_embeddings=4096,
    )


def llama_8b_cfg():
    from kubeai_tpu.models import llama

    # Llama-3-8B architecture (hidden 4096, 32 layers, GQA 32/8 heads).
    # int8 weights ≈ 8 GB — fits one v5e chip's 16 GB HBM with KV room.
    return llama.LlamaConfig(
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=4096,
    )


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny model, quick run")
    ap.add_argument(
        "--model", default="1b", choices=["1b", "8b"],
        help="model shape: 1b = Llama-3.2-1B-class proxy, 8b = Llama-3-8B "
        "class (the BASELINE.md north-star shape; pair with int8 on one chip)",
    )
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--decode-steps", type=int, default=96)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument(
        "--measure-seconds", type=float, default=45.0,
        help="wall-clock measurement window: after warm-up, decode until "
        "this much time has passed or the batch starts draining (<=0 "
        "restores the fixed --decode-steps loop)",
    )
    ap.add_argument(
        "--cpu", action="store_true",
        help="run on the host CPU on purpose; without it the bench refuses "
        "to run anywhere but a TPU",
    )
    ap.add_argument(
        "--uniform-prompts", action="store_true",
        help="all prompts exactly --prompt-len (default: mixed lengths in "
        "[prompt-len/4, prompt-len], the serving-realistic case where "
        "paging wins)",
    )
    ap.add_argument(
        "--measure", default="decode",
        choices=["decode", "prefill", "coldstart", "step-overlap"],
        help="what to measure: 'decode' = steady-state decode tok/s (the "
        "headline); 'prefill' = admission throughput in prompt tok/s over "
        "shared-prefix traffic — pair with/without --prefix-cache for the "
        "on-chip APC A/B (requests share a prompt-len-sized system "
        "prefix with small unique tails); 'coldstart' = boot-to-first-"
        "tokens with snapshot restore vs full load (two boots against a "
        "file:// snapshot store; reports the restore speedup and checks "
        "greedy token identity between the two engines); 'step-overlap' = "
        "the same steady-state decode A/B'd on the synchronous loop vs the "
        "overlapped one every engine off a pp mesh runs "
        "(reports the speedup, both arms' tok/s and per-phase step "
        "breakdown, and checks greedy token identity)",
    )
    ap.add_argument(
        "--prefix-cache", action="store_true",
        help="enable automatic prefix caching (implies a prefill chunk "
        "of max(32, min(512, max-seq-len/4)) when --prefill-chunk unset)",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunked prefill size (0 = whole-prompt bucketed prefill, "
        "unless --prefix-cache implies one)",
    )
    ap.add_argument(
        "--requests", type=int, default=0,
        help="(--measure prefill) admissions to time; default 4x slots",
    )
    ap.add_argument(
        "--page-size", type=int, default=64,
        help="KV page size (full pages are the prefix-cache sharing "
        "unit: a shared prefix shorter than one page can never hit)",
    )
    ap.add_argument(
        "--speculate", type=int, default=0,
        help="prompt-lookup speculative decoding window (0 = off)",
    )
    ap.add_argument(
        "--spec-adaptive", choices=["on", "off"], default="on",
        help="with --speculate: 'on' measures both modes and runs the "
        "faster (production default); 'off' benchmarks PURE speculation",
    )
    ap.add_argument(
        "--quantization", default="", choices=["", "int8"],
        help="weight-only quantization",
    )
    ap.add_argument(
        "--kv-dtype", default="", choices=["", "bfloat16", "int8"],
        help="paged KV cache storage dtype (int8 = quantized pages: "
        "~2x slot capacity at equal HBM; requires no --speculate)",
    )
    ap.add_argument(
        "--decode-chunk", type=int, default=32,
        help="decode steps fused into one device call (amortizes host "
        "dispatch)",
    )
    return ap.parse_args(argv)


DEVICE: dict = {}  # platform / device_kind / devices, set once in main()


def _emit(line: dict) -> None:
    """The one JSON line: every result names the device it ran on."""
    print(json.dumps({**line, **DEVICE}), flush=True)


def main(argv=None) -> int:
    args = _parse_args(argv)
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from kubeai_tpu.engine.coldstart import enable_compilation_cache

    enable_compilation_cache()
    device = jax.devices()[0]
    DEVICE.update(
        platform=device.platform,
        device_kind=device.device_kind,
        devices=len(jax.devices()),
    )
    if device.platform != "tpu" and not args.cpu:
        print(
            f"bench: no TPU (JAX found platform {device.platform!r}); a "
            "benchmark measures the chip or nothing. Pass --cpu to run on "
            "the host on purpose.",
            file=sys.stderr,
        )
        return 1

    import numpy as np

    from kubeai_tpu.engine import Engine, EngineConfig
    from kubeai_tpu.engine.sampling import SamplingParams
    from kubeai_tpu.models import llama

    if args.smoke:
        cfg = llama.LlamaConfig.tiny()
        args.slots, args.prompt_len, args.decode_steps = 4, 16, 20
        args.max_seq_len = 64
        # Two warm-up steps at a large chunk would consume smoke's whole
        # 48-token budget before the timed loop runs (0 tok/s).
        args.decode_chunk = min(args.decode_chunk, 4)
        # Full pages are the prefix-cache sharing unit: the default
        # 64-token page exceeds smoke's whole 16-token prefix, which
        # would make a prefix_cache=on smoke line structurally unable
        # to hit while still claiming to measure the cache.
        args.page_size = min(args.page_size, 8)
        if args.prefill_chunk > 0:
            args.prefill_chunk = min(args.prefill_chunk, 8)
        model_name = "llama-tiny"
    elif args.model == "8b":
        cfg = llama_8b_cfg()
        model_name = "llama-8b-class"
    else:
        cfg = llama_1b_cfg()
        model_name = "llama-1b-class"

    if args.measure == "coldstart":
        return _measure_coldstart(args, cfg, model_name)
    if args.measure == "step-overlap":
        return _measure_step_overlap(args, cfg, model_name)

    prefill_chunk = args.prefill_chunk
    if prefill_chunk <= 0 and (
        args.prefix_cache or args.measure == "prefill"
    ):
        # Chunk BOTH arms of a prefill A/B identically — the cache-off
        # arm on whole-prompt prefill would conflate chunking overhead
        # with cache benefit.
        prefill_chunk = max(32, min(512, args.max_seq_len // 4))
        if args.smoke:
            prefill_chunk = 8
    args.prefill_chunk = prefill_chunk
    params = llama.init_params(cfg)
    eng = Engine(
        "llama",
        cfg,
        params,
        cfg=EngineConfig(
            num_slots=args.slots,
            max_seq_len=args.max_seq_len,
            speculate=args.speculate,
            spec_adaptive=args.spec_adaptive == "on",
            quantization=args.quantization,
            kv_dtype=args.kv_dtype,
            decode_chunk=max(1, args.decode_chunk),
            prefill_chunk=prefill_chunk,
            prefix_cache=args.prefix_cache,
            page_size=args.page_size,
        ),
    )

    if args.measure == "prefill":
        return _measure_prefill(args, eng, cfg, model_name)

    rng = np.random.default_rng(0)
    gen_budget = args.max_seq_len - args.prompt_len
    sp = SamplingParams(temperature=0.0, max_tokens=gen_budget)

    # Fill every slot, warm up prefill+decode compiles. Mixed lengths by
    # default: decode cost under paging tracks RESIDENT tokens, which is
    # what serving traffic looks like (uniform max-length is the slot
    # cache's best case, not the common case).
    for i in range(args.slots):
        if args.uniform_prompts:
            plen = args.prompt_len
        else:
            lo = min(max(4, args.prompt_len // 4), args.prompt_len)
            plen = int(rng.integers(lo, args.prompt_len + 1))
        eng.add_request(
            rng.integers(0, cfg.vocab_size, plen).tolist(), sp
        )
    # Warm-up: run until every request is admitted (each prompt bucket
    # shape compiles its own prefill) plus one extra decode chunk, so the
    # timed window below measures steady-state decode only.
    eng.step()
    while eng.num_pending and eng.has_work():
        eng.step()
    eng.step()

    # Timed steady-state decode: until the wall window closes or the batch
    # starts draining.
    t0 = time.perf_counter()
    tokens = 0
    steps = 0
    dt = 0.0
    full_batch = eng.num_active
    steady = None  # (tokens, dt) at the last still-full-batch step
    while eng.has_work():
        tokens += len(eng.step())
        steps += 1
        dt = time.perf_counter() - t0
        if eng.num_active < full_batch:
            # Batch is draining (sequences exhausted their generation
            # budget): averaging shrinking-batch steps in would deflate
            # the reported steady state below what "continuous batching,
            # bs=N" claims. Report up to the last full-batch step; only
            # if the very first timed step already drained (nothing
            # better exists) does the shrunken sample stand.
            if steady is not None:
                tokens, dt = steady
            break
        steady = (tokens, dt)
        if args.measure_seconds > 0:
            if dt >= args.measure_seconds:
                break
        elif steps >= args.decode_steps:
            break
    _emit(_result_line(args, eng, model_name, tokens / dt if dt > 0 else 0.0))
    return 0


def _measure_prefill(args, eng, cfg, model_name) -> int:
    """Admission throughput over shared-prefix traffic: every request is
    an args.prompt_len system prefix plus a small unique tail — the
    serving shape CHWBL routes at a replica. With --prefix-cache the
    engine prefills only the tails after the first admission; without it
    every prompt pays the full prefill."""
    import numpy as np

    from kubeai_tpu.engine.sampling import SamplingParams

    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()
    tail = 8
    n_requests = args.requests or args.slots * 4
    sp = SamplingParams(temperature=0.0, max_tokens=1)

    # Warm-up: compile the prefill/chunk graphs, then (cache on) a
    # SECOND request that registers-then-HITS the shared prefix so the
    # hit-admission path (gather + suffix chunks) also compiles outside
    # the timed region — the cache-on arm must not pay its compile
    # inside the very number the A/B showcases.
    warmups = 2 if args.prefix_cache else 1
    for _ in range(warmups):
        eng.add_request(
            system + rng.integers(0, cfg.vocab_size, tail).tolist(), sp
        )
        while eng.has_work():
            eng.step()
    hit0 = eng.prefix_stats["hit_tokens"]
    prompt0 = eng.prefix_stats["prompt_tokens"]

    t0 = time.perf_counter()
    done_tokens = 0
    submitted = 0
    while submitted < n_requests:
        wave = min(args.slots, n_requests - submitted)
        for _ in range(wave):
            eng.add_request(
                system + rng.integers(0, cfg.vocab_size, tail).tolist(), sp
            )
        submitted += wave
        while eng.has_work():
            eng.step()
        done_tokens += wave * (args.prompt_len + tail)
    dt = time.perf_counter() - t0
    line = {
        "metric": f"{model_name} prefill admission throughput, "
        f"shared {args.prompt_len}-token prefix + {tail}-token tails, "
        f"prefix_cache={'on' if args.prefix_cache else 'off'}, "
        f"bs={args.slots}, paged kv cache, "
        f"chunk={args.prefill_chunk}, page={args.page_size}"
        + (" (smoke)" if args.smoke else ""),
        "value": round(done_tokens / dt if dt > 0 else 0.0, 2),
        "unit": "prompt tok/s",
    }
    if args.prefix_cache:
        # Timed-region deltas (the cumulative engine stats include the
        # untimed warm-up admissions).
        line["hit_tokens"] = eng.prefix_stats["hit_tokens"] - hit0
        line["prompt_tokens"] = eng.prefix_stats["prompt_tokens"] - prompt0
    _emit(line)
    return 0


def _measure_coldstart(args, cfg, model_name) -> int:
    """Boot-to-first-tokens, twice against one file:// snapshot store:
    boot A full-loads (param init stands in for HF conversion on this
    zero-egress image), warms up, and publishes its snapshot; boot B
    restores from it. Reports the restore speedup and checks greedy
    token identity between the two engines — a fast boot that decodes
    different tokens is a bug, not a win."""
    import shutil
    import tempfile

    from kubeai_tpu.engine import Engine, EngineConfig
    from kubeai_tpu.engine.coldstart import ColdStartManager
    from kubeai_tpu.engine.sampling import SamplingParams
    from kubeai_tpu.models import llama
    from kubeai_tpu.parallel.mesh import single_device_mesh

    root = tempfile.mkdtemp(prefix="bench-coldstart-")
    snap_url = "file://" + os.path.join(root, "snaps")
    ecfg = EngineConfig(
        num_slots=args.slots,
        max_seq_len=args.max_seq_len,
        decode_chunk=max(1, args.decode_chunk),
    )
    mesh = single_device_mesh()
    prompt = list(range(1, 1 + min(16, args.prompt_len)))
    sp = SamplingParams(temperature=0.0, max_tokens=8)

    def boot(label: str):
        t0 = time.perf_counter()
        mgr = ColdStartManager(
            snap_url, model_name, ecfg, mesh,
            work_dir=os.path.join(root, label),
        )
        params = mgr.acquire_params(lambda: llama.init_params(cfg))
        eng = Engine("llama", cfg, params, cfg=ecfg)
        toks = eng.generate([prompt], sp)[0]
        mgr.maybe_publish(params)
        mgr.tracker.finish()
        return mgr, toks, time.perf_counter() - t0

    try:
        _m1, toks_full, t_full = boot("full")
        m2, toks_restore, t_restore = boot("restore")
    finally:
        shutil.rmtree(root, ignore_errors=True)

    identical = toks_full == toks_restore
    speedup = t_full / t_restore if t_restore > 0 else 0.0
    ok = m2.tracker.restored and identical
    _emit({
        "metric": f"{model_name} engine cold start, snapshot restore vs "
        f"full load, bs={args.slots}"
        + (" (smoke)" if args.smoke else ""),
        "value": round(speedup, 2),
        "unit": "x faster boot",
        "full_load_s": round(t_full, 3),
        "restore_s": round(t_restore, 3),
        "restored": bool(m2.tracker.restored),
        "tokens_identical": identical,
    })
    # A restore that didn't happen, or decoded different tokens, is a
    # failed measurement — not a speedup.
    return 0 if ok else 1


def _measure_step_overlap(args, cfg, model_name) -> int:
    """A/B the SAME steady-state decode with the overlapped dispatch/reap
    pipeline off vs on, against identical seeded traffic. Reports the
    speedup plus both arms' per-phase step breakdown — under overlap the
    win shows up as overlap_idle (the block_until_ready wait) shrinking
    while schedule/sample/readback hide behind device compute. Greedy
    token identity is checked first: a faster pipeline that decodes
    different tokens is a bug, not a win."""
    import numpy as np

    from kubeai_tpu.engine import Engine, EngineConfig
    from kubeai_tpu.engine.sampling import SamplingParams
    from kubeai_tpu.fleet.profiler import phase_totals
    from kubeai_tpu.models import llama

    params = llama.init_params(cfg)

    def build(overlap: bool) -> Engine:
        engine = Engine(
            "llama", cfg, params,
            cfg=EngineConfig(
                num_slots=args.slots,
                max_seq_len=args.max_seq_len,
                quantization=args.quantization,
                kv_dtype=args.kv_dtype,
                decode_chunk=max(1, args.decode_chunk),
                prefill_chunk=max(0, args.prefill_chunk),
                page_size=args.page_size,
            ),
        )
        # No option names the loop: the synchronous arm is put there the way
        # lockstep puts its engines (engine/multihost.py), before any step.
        if not overlap:
            engine._overlap = False
        return engine

    engines = {"sync": build(False), "overlap": build(True)}

    # Identity smoke — doubles as the prefill/decode warm-up compile for
    # both arms, so the timed windows below measure steady state only.
    ident_prompts = [list(range(1, 1 + min(16, args.prompt_len))), [7, 8, 9]]
    sp_ident = SamplingParams(temperature=0.0, max_tokens=16)
    streams = [e.generate(ident_prompts, sp_ident) for e in engines.values()]
    identical = streams[0] == streams[1]

    gen_budget = args.max_seq_len - args.prompt_len
    sp = SamplingParams(temperature=0.0, max_tokens=gen_budget)
    arms: dict[str, dict] = {}
    for name, eng in engines.items():
        rng = np.random.default_rng(0)  # identical traffic per arm
        for _ in range(args.slots):
            if args.uniform_prompts:
                plen = args.prompt_len
            else:
                lo = min(max(4, args.prompt_len // 4), args.prompt_len)
                plen = int(rng.integers(lo, args.prompt_len + 1))
            eng.add_request(
                rng.integers(0, cfg.vocab_size, plen).tolist(), sp
            )
        eng.step()
        while eng.num_pending and eng.has_work():
            eng.step()
        eng.step()
        mark = len(eng.profiler.recent())
        t0 = time.perf_counter()
        tokens = steps = 0
        dt = 0.0
        full_batch = eng.num_active
        steady = None
        while eng.has_work():
            tokens += len(eng.step())
            steps += 1
            dt = time.perf_counter() - t0
            if eng.num_active < full_batch:
                if steady is not None:
                    tokens, dt = steady
                break
            steady = (tokens, dt)
            if steps >= args.decode_steps:
                break
        phases = phase_totals(eng.profiler.recent()[mark:])
        arms[name] = {
            "toks_per_s": round(tokens / dt, 2) if dt > 0 else 0.0,
            "phases_s": {k: round(v, 4) for k, v in sorted(phases.items())},
        }

    sync_tps = arms["sync"]["toks_per_s"]
    over_tps = arms["overlap"]["toks_per_s"]
    speedup = over_tps / sync_tps if sync_tps > 0 else 0.0
    _emit({
        "metric": f"{model_name} overlapped step pipeline vs sync decode, "
        f"bs={args.slots}, paged kv cache, "
        f"chunk={max(1, args.decode_chunk)}"
        + (" (smoke)" if args.smoke else ""),
        "value": round(speedup, 3),
        "unit": "x decode speedup",
        "sync": arms["sync"],
        "overlap": arms["overlap"],
        "tokens_identical": identical,
    })
    # An overlap arm that decoded different tokens is a failed
    # measurement — not a speedup.
    return 0 if identical else 1


def _result_line(args, eng, model_name, toks_per_s):
    return {
        "metric": f"{model_name} decode throughput, continuous batching, "
        f"bs={args.slots}, paged kv cache"
        + f" ({eng.kv_layout} layout)"
        + ", "
        + ("uniform" if args.uniform_prompts else "mixed")
        + " prompts"
        # Label with what actually RAN (the engine downgrades silently
        # when speculation preconditions fail).
        + (
            f", speculate={eng._spec}"
            + ("/adaptive" if eng.cfg.spec_adaptive else "")
            if eng._spec else ""
        )
        + (f", {args.quantization}" if args.quantization else "")
        + (f", kv={args.kv_dtype}" if args.kv_dtype else "")
        + f", chunk={eng.cfg.decode_chunk}"
        + (" (smoke)" if args.smoke else ""),
        "value": round(toks_per_s, 2),
        "unit": "tok/s",
    }


if __name__ == "__main__":
    sys.exit(main())
