"""The benchmark's one command.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chips. It makes the weights on the device from the
seed, builds the program's `Engine` and `EngineServer` in-process, warms the
closed set of shapes the cell's traffic can meet, and then lets a child
process that never imports JAX offer the load over HTTP/SSE. After the window
it reduces the client's records (and, with `--trace 1`, counters, polled
series and a profiler slice) to metrics, frees the program's state, and
holds a seeded sample of what was served against the plain reference.

The last line of stdout is one JSON object with exactly the keys `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), and last of all `compared`: each number `correct` was decided
on, beside its limit (also the last lines of stderr). Off a TPU it prints no
result and exits non-zero; `--rehearse` runs the tiny CPU presets of
perf/rehearse.json and perf/rehearse.d/*.json, and its line says platform
`cpu`, which nothing may record as a chip result.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perf import check, e2e, readers, trace_reduce, traffic  # noqa: E402
from perf.tokenizer import BenchTokenizer  # noqa: E402

WINDOW_LEAD_S = 1.5  # the child's start-up, before the pre-roll begins
TRACE_SLICE_S = 3.0  # the profiler's slice, in the middle of the window
POLL_HZ = 5.0
POOL_MIN_BYTES = 1 << 20  # a cache's array of this size or more is a pool


def log(msg: str) -> None:
    print(f"perf: {msg}", flush=True)


def load_cell(name: str, rehearse: bool) -> tuple[dict, dict, dict]:
    """(benchmark, cell, configuration file's dict)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells, configs = bench["workloads"], bench["configs"]
    if rehearse:
        cells, configs = [], []
        for path in [os.path.join(HERE, "rehearse.json"), *sorted(
                glob.glob(os.path.join(HERE, "rehearse.d", "*.json")))]:
            with open(path) as f:
                extra = json.load(f)
            cells += extra["workloads"]
            configs += extra["configs"]
    cell = next((w for w in cells if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"perf: no workload {name!r}; known: "
                         f"{[w['name'] for w in cells]}")
    entry = next(c for c in configs if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        return bench, cell, json.load(f)


def metrics_for(bench: dict, section: str, cell: dict) -> list[dict]:
    """The metrics of a section that this cell reports. A rehearsal cell
    reports what the cell it stands in for (`as`) reports."""
    name = cell.get("as", cell["name"])
    return [m for m in bench[section]
            if "workloads" not in m or name in m["workloads"]]


def seed_key(jax, seed: int):
    """Any whole number up to a little over 2**31."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def warm_shapes(engine, mix: dict) -> list[tuple[int, int]]:
    """(prefill bucket, admit batch) pairs the mix can meet: every bucket
    its prompt range touches, by every power-of-two admit batch."""
    lo, hi = traffic.prompt_length_range(mix)
    buckets = [b for b in engine.cfg.buckets()
               if engine._bucket(lo) <= b <= engine._bucket(hi)]
    batches, a = [], 1
    while a <= min(engine.cfg.max_admit_batch, engine.cfg.num_slots):
        batches.append(a)
        a *= 2
    return [(b, a) for b in buckets for a in batches]


def warm_up(engine, mix: dict, vocab: int) -> int:
    """Drive every shape once through the engine's own entry, before the
    serve loop starts, so that the admit batch is chosen and not left to
    timing. Returns the number of requests it took."""
    from kubeai_tpu.engine.sampling import SamplingParams

    lo, hi = traffic.prompt_length_range(mix)
    buckets = engine.cfg.buckets()
    sent = 0

    def drain():
        while engine.has_work():
            engine.step()

    for bucket, batch in warm_shapes(engine, mix):
        below = max([b for b in buckets if b < bucket], default=0)
        plen = min(hi, max(lo, below + 1))
        for i in range(batch):
            engine.add_request(
                traffic.prompt_tokens(1, sent + i, plen, vocab),
                SamplingParams(temperature=0.0, max_tokens=1))
        sent += batch
        drain()
    # The decode chunk, every slot live: one program whatever the count of
    # live rows (a reap launches none of its own).
    for i in range(engine.cfg.num_slots):
        engine.add_request(
            traffic.prompt_tokens(1, sent + i, lo, vocab),
            SamplingParams(temperature=0.0, max_tokens=2 + engine.cfg.decode_chunk))
    sent += engine.cfg.num_slots
    drain()
    return sent


class CompileCounter:
    """Programs compiled (or loaded from the cache) by this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.count += 1


class Poller(threading.Thread):
    """Samples in-process gauges at POLL_HZ inside the window (traced runs
    only: the timed run carries no poller)."""

    def __init__(self, engine, clock_now, seconds):
        super().__init__(daemon=True)
        self.engine, self.now, self.seconds = engine, clock_now, seconds
        self.series: dict[str, list[float]] = {
            "kv_utilization": [], "kv_tokens": [], "batch": []}
        self._seen_step = 0

    def run(self):
        page = self.engine.cfg.page_size
        pages = self.engine.cfg.effective_num_pages() - 1
        while self.now() < self.seconds:
            if self.now() >= 0:
                used = self.engine.kv_utilization()
                self.series["kv_utilization"].append(used)
                self.series["kv_tokens"].append(used * pages * page)
                for rec in self.engine.profiler.recent():
                    if rec["step"] > self._seen_step:
                        self._seen_step = rec["step"]
                        self.series["batch"].append(
                            rec["tokens"] / self.engine.cfg.decode_chunk)
            time.sleep(1.0 / POLL_HZ)


def scrape(port: int) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        return readers.parse_prometheus(resp.read().decode())


class Bench:
    """One process's set-up: the chips, the engine and its server."""

    def __init__(self, args, bench, cell, cfg, mix):
        import jax

        from kubeai_tpu.engine import Engine, EngineConfig
        from kubeai_tpu.engine.coldstart import enable_compilation_cache
        from kubeai_tpu.engine.server import EngineServer
        from kubeai_tpu.models.registry import get_model_family
        from kubeai_tpu.parallel import sharding as psh
        from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

        self.jax, self.bench = jax, bench
        self._pools_like: dict = {}  # what `release` took, for `reseed`
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.chips = int(cell["chips"])
        self.cache_dir = enable_compilation_cache()
        devices = jax.devices()
        self.platform = devices[0].platform
        if not args.rehearse and self.platform != "tpu":
            raise SystemExit(
                f"perf: no TPU (JAX found platform {self.platform!r}); the "
                "benchmark measures the chip or nothing")
        if len(devices) < self.chips:
            raise SystemExit(
                f"perf: cell {cell['name']} needs {self.chips} chips, JAX "
                f"found {len(devices)}")
        self.devices = devices[: self.chips]
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        self.kind = self.devices[0].device_kind
        if not args.rehearse and self.kind not in peaks:
            raise SystemExit(
                f"perf: device kind {self.kind!r} is not in perf/peaks.json")
        self.peaks = peaks.get(self.kind, {})
        self.compiles = CompileCounter(jax)
        log(f"{self.chips} x {self.kind} after {time.time() - T_START:.1f} s")

        self.reference = importlib.import_module(
            "perf.reference." + cfg["reference"])
        family = get_model_family(cfg["architectures"][0])
        model_cfg = family.config_from_hf(cfg)
        mesh = build_mesh(MeshConfig(**cfg["mesh"]), devices=self.devices)
        self._make_params = jax.jit(
            lambda k: self.reference.served_params(cfg, k),
            out_shardings=psh.param_shardings(
                family.param_specs(model_cfg), mesh))
        # The tokenizer and the logits keep the whole vocabulary; prompts
        # (the warm-up's, the load generator's, the check's) keep off the
        # ids a configuration reserves.
        self.vocab = cfg["vocab_size"]
        self.prompt_vocab = traffic.prompt_vocab(cfg)
        if self.prompt_vocab != self.vocab:
            log(f"prompts drawn from the first {self.prompt_vocab} of "
                f"{self.vocab} ids")
        self.key = seed_key(jax, args.seed)
        t = time.time()
        params = jax.block_until_ready(self._make_params(self.key))
        log(f"weights on the device in {time.time() - t:.1f} s")
        if args.break_path == "route":
            params = check.break_router(params, self.reference)
        t = time.time()
        self.engine = Engine(family, model_cfg, params, mesh=mesh,
                             cfg=EngineConfig(**cfg["engine"]),
                             eos_token_ids=())
        del params
        self.engine_cfg = {f: getattr(self.engine.cfg, f) for f in (
            "num_slots", "max_seq_len", "page_size", "decode_chunk",
            "max_admit_batch")}
        n_warm = warm_up(self.engine, mix, self.prompt_vocab)
        log(f"engine built and {len(warm_shapes(self.engine, mix))} prefill "
            f"shapes and the decode chunk warmed with {n_warm} requests in "
            f"{time.time() - t:.1f} s; {self.compiles.count} programs "
            f"compiled or loaded; cache {self.cache_dir}")
        if args.break_path == "token":
            check.break_tokens(self.engine)
        self.server = EngineServer(
            self.engine, BenchTokenizer(self.vocab), cell["config"],
            host="127.0.0.1", port=0)
        self.server.start()
        # A router whose routes this engine hands over: the timed run asks
        # for them and `correct` follows them. Any other engine has None.
        # A reference with `HANDOVER` (a generator of its own) names the
        # flags itself and is handed `/v1/state` whole.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.server.port}/v1/state", timeout=30) as resp:
            self.state = json.load(resp)
        self.handover = list(getattr(self.reference, "HANDOVER", ()))
        moe = self.state.get("moe")
        self.moe = moe if moe and moe.get("routes") and not self.handover else None
        if self.moe:
            log(f"routes asked of every request: {self.moe}")
        if self.handover:
            log(f"handed over by every request: {self.handover}")

    def reseed(self, seed: int) -> None:
        """Study mode: new weights from another seed, in place, and the
        cache's pools again if `release` took them."""
        import jax.numpy as jnp

        jax, engine = self.jax, self.engine
        for leaf in jax.tree.leaves(engine.params):
            if not leaf.is_deleted():
                leaf.delete()
        self.key = seed_key(jax, seed)
        engine.params = jax.block_until_ready(self._make_params(self.key))
        for name, tree in self._pools_like.items():
            setattr(engine.cache, name, jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype, device=a.sharding), tree))
        self._pools_like = {}

    def release(self) -> None:
        """Study mode: once the engine is idle, give the weights and the
        cache's pools back, so the reference has room beside what stays. A
        pool is any attribute of the cache that holds device arrays over
        `POOL_MIN_BYTES`, whatever its name: two page pools, one latent
        pool, state beside pages. An idle engine's pools hold nothing that
        is read again, so `reseed` re-makes them as zeros; the small tables
        beside them (block tables) stay."""
        deadline = time.time() + 60.0
        while self.engine.has_work() and time.time() < deadline:
            time.sleep(0.05)
        time.sleep(0.5)  # let the serve loop finish the step it is in
        jax, cache = self.jax, self.engine.cache
        self._pools_like = {}
        for name, value in vars(cache).items():
            leaves = jax.tree.leaves(value)
            if leaves and all(isinstance(a, jax.Array) for a in leaves) and (
                    sum(a.nbytes for a in leaves) >= POOL_MIN_BYTES):
                self._pools_like[name] = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding), value)
        for leaf in jax.tree.leaves(
                [self.engine.params]
                + [getattr(cache, name) for name in self._pools_like]):
            leaf.delete()

    def window(self, mix: dict, seed: int, seconds: float, trace: bool) -> dict:
        """Offer the mix for `seconds` and return what was observed."""
        jax, engine, port = self.jax, self.engine, self.server.port
        preroll = float(mix.get("preroll_s", 0.0))
        drain_s = float(mix.get("drain_s", 30.0))
        t0_wall = time.time() + WINDOW_LEAD_S + preroll
        clock = lambda: time.time() - t0_wall  # noqa: E731
        spec = {
            "mix": mix, "seed": seed, "seconds": seconds, "t0_wall": t0_wall,
            "host": "127.0.0.1", "port": port, "model": self.cell["config"],
            "vocab": self.prompt_vocab,
            "clients": traffic.num_clients(mix, self.engine_cfg),
            **({"routes": True} if self.moe else {}),
            **({"handover": self.handover} if self.handover else {}),
        }
        out_dir = os.path.join(ROOT, "perf_out")
        os.makedirs(out_dir, exist_ok=True)
        spec_path = os.path.join(out_dir, f"loadgen_{os.getpid()}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        child_env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path],
            stdout=subprocess.PIPE, env=child_env)
        obs: dict = {"polled": {}, "trace": None,
                     "setup_s": t0_wall - T_START}
        trace_dir = os.path.join(out_dir, f"trace_{os.getpid()}")
        poller = None
        try:
            time.sleep(max(0.0, -clock()))
            compiles0 = self.compiles.count
            obs["metrics0"], obs["steps0"] = (
                scrape(port), engine.profiler.steps_completed)
            if trace:
                poller = Poller(engine, clock, seconds)
                poller.start()
                slice_s = min(TRACE_SLICE_S, seconds / 2)
                time.sleep(max(0.0, (seconds - slice_s) / 2 - clock()))
                jax.profiler.start_trace(trace_dir)
                time.sleep(slice_s)
                jax.profiler.stop_trace()
            time.sleep(max(0.0, seconds / 2 - clock()))
            obs["pending_mid"] = engine.num_pending
            time.sleep(max(0.0, seconds - clock()))
            obs["pending_end"] = engine.num_pending
            obs["metrics1"], obs["steps1"] = (
                scrape(port), engine.profiler.steps_completed)
            obs["compiles_in_window"] = self.compiles.count - compiles0
            out, _ = child.communicate(timeout=drain_s + 60.0)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            os.unlink(spec_path)
        if child.returncode != 0:
            raise SystemExit(
                f"perf: the load generator exited {child.returncode}")
        if poller is not None:
            poller.join(timeout=5)
            obs["polled"] = poller.series
        if trace:
            events = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
            obs["trace"] = trace_reduce.summarize(events)
            if os.environ.get("PERF_KEEP_TRACE_EVENTS"):
                with open(os.environ["PERF_KEEP_TRACE_EVENTS"], "w") as f:
                    json.dump(events, f)
        if os.environ.get("PERF_KEEP_RECORDS"):  # for a study of the spread
            with open(os.environ["PERF_KEEP_RECORDS"], "wb") as f:
                f.write(out)
        obs.update(records=json.loads(out)["records"], loop=mix["loop"],
                   seconds=seconds, drain_s=drain_s, hf=self.cfg,
                   engine=self.engine_cfg, chips=self.chips, peaks=self.peaks,
                   reference=self.reference)
        return obs

    def end_to_end(self, obs: dict) -> dict:
        metrics = {}
        for m in metrics_for(self.bench, "end_to_end", self.cell):
            value = (obs["setup_s"] if m["name"] == "setup_s" else
                     e2e.END_TO_END[m["name"]](
                         obs["loop"], obs["records"], obs["seconds"],
                         obs["drain_s"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return metrics

    def per_layer(self, obs: dict) -> dict:
        metrics = {}
        for m in metrics_for(self.bench, "per_layer", self.cell):
            with open(os.path.join(
                    HERE, "layer_metrics", m["name"] + ".json")) as f:
                value = readers.read(json.load(f), obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return metrics

    def verdict(self, obs: dict, seed: int, controls=()) -> dict:
        t = time.time()
        v = check.served_against_reference(
            self.reference, self.cfg, self.key, self.mix, obs["records"], seed,
            self.prompt_vocab, controls=controls, log=log, moe=self.moe,
            state=self.state)
        log(f"reference check took {time.time() - t:.1f} s")
        return v


def study(b: Bench, args) -> int:
    """Not a run: several short windows after one set-up. `--sweep` offers
    the open-loop mix at each rate to find the knee; `--study-seeds` reads
    `correct` and its controls on fresh weights and traffic per seed."""
    controls = [c for c in args.control.split(",") if c]
    if args.sweep:
        for rate in (float(r) for r in args.sweep.split(",")):
            mix = {**b.mix, "rate_rps": rate}
            obs = b.window(mix, args.seed, args.seconds, False)
            rec, s = obs["records"], args.seconds
            due = sum(1 for r in rec if 0 <= r["due"] < s)
            done = sum(1 for r in rec if r.get("ok") and 0 <= r.get("end", -1) < s)
            log("sweep " + json.dumps({
                "rate_rps": rate, "due_per_s": due / s, "done_per_s": done / s,
                "pending_mid": obs["pending_mid"], "pending_end": obs["pending_end"],
                "ttft_p95_ms": e2e.ttft_p95_ms("open", rec, s, obs["drain_s"]),
                "tpot_p95_ms": e2e.tpot_p95_ms("open", rec, s),
                "gap_p99_ms": e2e.gap_p99_ms("open", rec, s),
                "out_tok_s": e2e.out_tok_s("open", rec, s),
                "late_p95_ms": e2e.late_p95_ms(rec, s)}))
    for seed in (int(x) for x in args.study_seeds.split(",") if x):
        b.reseed(seed)
        obs = b.window(b.mix, seed, args.seconds, False)
        b.release()
        v = b.verdict(obs, seed, controls)
        attempted, failed = e2e.counts(obs["loop"], obs["records"], args.seconds)
        log("study " + json.dumps({
            "seed": seed, "attempted": attempted, "failed": failed,
            **{k: v[k] for k in v if k != "correct"},
            "compiles_in_window": obs["compiles_in_window"],
            **{k: m["value"] for k, m in b.end_to_end(obs).items()}}))
    b.server.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny presets on the CPU; never a chip result")
    ap.add_argument("--control", default="",
                    help="study: also read the control of `correct` in these "
                    "lower precisions (comma-separated: fp8,int8)")
    ap.add_argument("--study-seeds", default="",
                    help="study: after one set-up, a window and a check per seed")
    ap.add_argument("--sweep", default="",
                    help="study: after one set-up, a window per offered rate")
    ap.add_argument("--break-path", default="", choices=("", "token", "route"),
                    help="plant a fault to show `correct` come out false: "
                    "`token` alters served tokens where they are produced, "
                    "`route` permutes the router's expert columns in the "
                    "served weights")
    args = ap.parse_args(argv)

    bench, cell, cfg = load_cell(args.workload, args.rehearse)
    mix = traffic.load_mix(cell["traffic"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(cell['chips'])}")
    b = Bench(args, bench, cell, cfg, mix)
    if args.sweep or args.study_seeds:
        return study(b, args)

    obs = b.window(mix, args.seed, args.seconds, bool(args.trace))
    b.server.stop()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in b.devices)
    attempted, failed = e2e.counts(mix["loop"], obs["records"], args.seconds)
    # The program's state goes before the reference runs: the peak stays the
    # program's, and the reference has the chip to itself.
    check.free_engine(b.engine)
    v = b.verdict(obs, args.seed, [c for c in args.control.split(",") if c])
    correct = bool(v["correct"] and failed == 0 and attempted > 0)
    # What the check read of the decisions it followed (a router's, a
    # generator's) is an observation like any other: a per-layer metric of
    # kind `observed` shows a drift before it fails.
    obs.update(v["observed"])
    metrics = b.per_layer(obs) if args.trace else b.end_to_end(obs)

    device = {"platform": b.platform, "kind": b.kind, "count": b.chips,
              "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if args.trace and obs["trace"]:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        line["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                             "idle_gaps": obs["trace"]["idle_gaps"]}
    log(f"setup_s {obs['setup_s']:.3f}; compiles in the window "
        f"{obs['compiles_in_window']}; requests recorded "
        f"{len(obs['records'])}; whole run {time.time() - T_START:.1f} s")
    # Each number `correct` was decided on, beside its limit: last in the
    # line, and the last lines of stderr.
    line["compared"] = {**v["compared"], "failed": [failed, 0]}
    for name, (value, limit) in line["compared"].items():
        print(f"perf: compared: {name} = {value}  limit {limit}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
