"""chip_smoke.py — does the serving path start, compile and answer on the TPU?

Drives HTTP request -> EngineServer -> RequestScheduler -> Engine.step ->
Pallas/XLA once, through `python -m kubeai_tpu.engine.server`, at the full
widths of `mistral-7b-instruct-tpu` (catalog/models.yaml): hidden 4096,
32 query / 8 KV heads of 128, FFN 14336, vocabulary 32768, rope theta 1e6,
bf16. Four chips serve all 32 layers at tp=4 (`--tpu-topology 2x2`); one
chip serves the same widths at 16 layers, the depth that fits 16 GB beside
a page pool. Weights are seeded and written here as an HF safetensors
directory, so the server loads them like any `--model-url`.

Three legs, each the only process on the chip while it runs; this parent
never imports JAX:

  1. device   — platform, device_kind, count, versions. Not a TPU: fail.
  2. kernels  — paged decode, multi-query verify and flash prefill, compiled
                (never interpreted) at this model's head shapes, against
                their jnp references.
  3. server   — boot, /health, /v1/state, then requests over HTTP.

The last line of stdout is one JSON object, `{"ok": true, "device":
{"platform", "kind", "count"}}` and no other key; the `summary:` line before
it carries what was served (mesh, depth, boot-to-Ready, cache entries). The
exit code is 0 only when every leg passed, and a failed run prints no result
object. Times printed here are smoke observations, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CKPT_DIR = os.path.join(REPO, "chip_smoke_ckpt")
OUT_DIR = os.path.join(REPO, "chip_smoke_out")
MODEL_NAME = "mistral-7b-instruct-tpu"
SEED = 0
# The whole run must end inside the driver's 1200 s, compilation included.
DEADLINE_S = 1150.0

# Mistral-7B-Instruct-v0.3 as published; only num_hidden_layers is cut.
MISTRAL_7B = {
    "architectures": ["MistralForCausalLM"],
    "model_type": "mistral",
    "vocab_size": 32768,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-5,
    "max_position_embeddings": 32768,
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}
PAGE = 64  # EngineConfig.page_size default

# Serving sizes per chip count, from shapes. KV costs 2 * 8 * 128 * 2 B =
# 4 KiB per token per layer.
#   1 chip : 16 layers = 7.5 GB of weights; 8 slots x 2048 tokens x 64 KiB
#            = 1 GiB of pages.
#   4 chips: 32 layers = 14.5 GB of weights (3.6 GB per chip); 16 slots x
#            8192 tokens x 128 KiB = 16 GiB of pages (4 GiB per chip). These
#            are the sizes catalog/models.yaml lists for the model.
SIZES = {
    1: {"depth": 16, "num_slots": 8, "max_seq_len": 2048, "topology": ""},
    4: {"depth": 32, "num_slots": 16, "max_seq_len": 8192, "topology": "2x2"},
}


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---- checkpoint writer (numpy only) -----------------------------------------


def _bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 bit patterns (uint16), round to nearest even."""
    u = x.astype(np.float32).view(np.uint32)
    return ((u + 0x7FFF + ((u >> 16) & 1)) >> 16).astype(np.uint16)


# Seeded values come from one pool of N(0, 0.02) draws; each tensor reads the
# pool cyclically from its own seeded offset. Drawing 7e9 fresh normals would
# cost minutes of host time inside the run's limit and test nothing more. The
# length is prime, so no matrix row lines up with the period.
POOL_LEN = 16_777_259


def _write_cyclic(f, pool: memoryview, start_elt: int, n_elts: int) -> None:
    start, need = start_elt * 2, n_elts * 2
    while need:
        chunk = pool[start:start + need]
        f.write(chunk)
        need -= len(chunk)
        start = 0


def write_checkpoint(
    out_dir: str, hf_cfg: dict, seed: int = SEED, pool_len: int = POOL_LEN
) -> int:
    """Write `hf_cfg` + seeded bf16 weights as an HF-layout safetensors
    directory (one file per layer, one for the embeddings). Norm weights
    are ones. `lm_head` rows past the first 256 are zero: with no
    tokenizer files the server falls back to the ByteTokenizer, and this
    keeps greedy decoding inside its alphabet (and off its EOS, id 256).
    The text is still mostly U+FFFD, because bytes >= 0x80 rarely form
    valid UTF-8, so the smoke compares token ids where it compares greedy
    output. Returns the bytes written."""
    os.makedirs(out_dir, exist_ok=True)
    for name in os.listdir(out_dir):
        if name.endswith(".safetensors"):
            os.remove(os.path.join(out_dir, name))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=1)

    rng = np.random.default_rng(seed)
    pool = memoryview(
        _bf16_bits(rng.standard_normal(pool_len, np.float32) * 0.02).tobytes()
    )
    one = _bf16_bits(np.ones(1, np.float32)).tobytes()
    E = hf_cfg["hidden_size"]
    M = hf_cfg["intermediate_size"]
    V = hf_cfg["vocab_size"]
    H, KVH = hf_cfg["num_attention_heads"], hf_cfg["num_key_value_heads"]
    D = hf_cfg.get("head_dim") or E // H
    NL = hf_cfg["num_hidden_layers"]
    byte_rows = min(256, V)

    def shard(index: int, tensors: list[tuple[str, tuple, str]]) -> int:
        """tensors: (name, shape, kind) with kind in normal|ones|lm_head."""
        header, offset = {}, 0
        for name, shape, _ in tensors:
            n = int(np.prod(shape)) * 2
            header[name] = {
                "dtype": "BF16", "shape": list(shape),
                "data_offsets": [offset, offset + n],
            }
            offset += n
        blob = json.dumps(header, separators=(",", ":")).encode()
        blob += b" " * (-len(blob) % 8)
        path = os.path.join(
            out_dir, f"model-{index:05d}-of-{NL + 1:05d}.safetensors"
        )
        with open(path, "wb") as f:
            f.write(struct.pack("<Q", len(blob)))
            f.write(blob)
            for _, shape, kind in tensors:
                n = int(np.prod(shape))
                if kind == "ones":
                    f.write(one * n)
                    continue
                if kind == "lm_head":
                    n = byte_rows * shape[1]
                _write_cyclic(f, pool, int(rng.integers(pool_len)), n)
                if kind == "lm_head":
                    f.write(bytes((int(np.prod(shape)) - n) * 2))
        return 8 + len(blob) + offset

    total = shard(0, [
        ("model.embed_tokens.weight", (V, E), "normal"),
        ("model.norm.weight", (E,), "ones"),
        ("lm_head.weight", (V, E), "lm_head"),
    ])
    for i in range(NL):
        p = f"model.layers.{i}."
        total += shard(i + 1, [
            (p + "input_layernorm.weight", (E,), "ones"),
            (p + "self_attn.q_proj.weight", (H * D, E), "normal"),
            (p + "self_attn.k_proj.weight", (KVH * D, E), "normal"),
            (p + "self_attn.v_proj.weight", (KVH * D, E), "normal"),
            (p + "self_attn.o_proj.weight", (E, H * D), "normal"),
            (p + "post_attention_layernorm.weight", (E,), "ones"),
            (p + "mlp.gate_proj.weight", (M, E), "normal"),
            (p + "mlp.up_proj.weight", (M, E), "normal"),
            (p + "mlp.down_proj.weight", (E, M), "normal"),
        ])
    return total


# ---- leg 1: device (child) ---------------------------------------------------


def leg_device() -> int:
    import jax
    import jaxlib

    from kubeai_tpu.engine.coldstart import enable_compilation_cache

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — the version string is informational
        libtpu = "unknown"
    cache_dir = enable_compilation_cache()
    d = jax.devices()[0]
    print(json.dumps({
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "cache_dir": cache_dir,
    }), flush=True)
    return 0  # the parent judges the platform, and names it when it fails


# ---- leg 2: kernels (child) --------------------------------------------------

# Kernel output (bf16) against the f32 reference, both from the same bf16
# inputs. Outputs are weighted means of N(0, 1) values; a short row returns
# nearly v itself, so |out| reaches 2 to 4, where one bf16 ulp is 2^-6 =
# 1.6e-2. Ulps are what separates the two: the kernel rounds its result to
# bf16, and the MXU takes the f32 operands q * scale and the softmax
# weights in bf16 passes at default precision, which can move the result
# across a rounding boundary. The largest error the chip showed is exactly
# one ulp, 1.56e-2 (TPU v5 lite, PR 21); the tolerance allows a few. A
# wrong page, mask or head mapping gives errors of order 0.1 to 1.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2


def leg_kernels(max_seq_len: int) -> int:
    import jax
    import jax.numpy as jnp

    from kubeai_tpu.engine.coldstart import enable_compilation_cache
    from kubeai_tpu.ops import dispatch
    from kubeai_tpu.ops import paged_attention as pa
    from kubeai_tpu.ops.attention import causal_prefill_attention
    from kubeai_tpu.ops.pallas_attention import flash_causal_prefill

    enable_compilation_cache()
    # "compiled" is what the dispatches answer on a TPU and nowhere else:
    # the Pallas kernel through Mosaic, never the interpreter or a reference.
    if dispatch.kernel_mode() != "compiled":
        log(
            f"kernels leg needs a TPU, found {jax.default_backend()} "
            f"(kernel mode {dispatch.kernel_mode()!r})"
        )
        return 1
    rng = np.random.default_rng(SEED)
    D = MISTRAL_7B["head_dim"]
    failures = 0

    def bf16(shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)

    def highest(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def check(name, got, want):
        nonlocal failures
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(
            np.all(np.isfinite(got))
            and np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL)
        )
        failures += not ok
        log(f"  {'ok  ' if ok else 'FAIL'} {name}: max|err| {err:.2e}")

    def attempt(name, fn):
        """A kernel the compiler refuses fails the leg with its message."""
        nonlocal failures
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report and count, then go on
            failures += 1
            log(f"  FAIL {name}: {type(e).__name__}: {str(e)[:2000]}")

    # Ragged lengths round the page boundaries, a one-token slot, a full
    # one; every slot's unused block-table tail is -1 (absent pages).
    lengths = [1, 63, 64, 65, 130, 700, max_seq_len // 2 + 1, max_seq_len]
    B, mp, spec_k = len(lengths), max_seq_len // PAGE, 4
    n_pages = 1 + sum(-(-n // PAGE) for n in lengths)
    ids = rng.permutation(np.arange(1, n_pages))
    bt = np.full((B, mp), -1, np.int32)
    at = 0
    for s, n in enumerate(lengths):
        k = -(-n // PAGE)
        bt[s, :k] = ids[at:at + k]
        at += k
    bt = jnp.asarray(bt)
    lens = jnp.asarray(lengths, jnp.int32)
    positions = jnp.maximum(lens - spec_k, 0)

    for H, KVH in ((32, 8), (8, 2)):  # unsharded; one tp=4 shard
        tag = f"H{H}/KVH{KVH}"
        kp, vp = bf16((n_pages, PAGE, KVH, D)), bf16((n_pages, PAGE, KVH, D))

        def decode():
            q = bf16((B, H, D))
            got = jax.jit(pa.paged_decode_attention)(q, kp, vp, bt, lens)
            want = highest(pa.ref_paged_decode_attention, q, kp, vp, bt, lens)
            check(f"paged decode {tag}", got, want)

        def verify():
            q = bf16((B, spec_k, H, D))
            got = jax.jit(pa.paged_verify_attention)(q, kp, vp, bt, positions)
            want = highest(
                pa.ref_paged_verify_attention, q, kp, vp, bt, positions
            )
            check(f"paged verify {tag} K{spec_k}", got, want)

        attempt(f"paged decode {tag}", decode)
        attempt(f"paged verify {tag}", verify)

        S = 256
        while S <= max_seq_len:
            def flash(S=S):
                b = 2 if S <= 1024 else 1
                q, k, v = bf16((b, S, H, D)), bf16((b, S, KVH, D)), bf16(
                    (b, S, KVH, D)
                )
                got = jax.jit(flash_causal_prefill)(q, k, v)
                # One KV head at a time: the reference's [G, S, S] f32
                # logits for all heads at once do not fit beside the rest.
                g = H // KVH
                want = jnp.concatenate([
                    highest(
                        causal_prefill_attention,
                        q[:, :, j * g:(j + 1) * g], k[:, :, j:j + 1],
                        v[:, :, j:j + 1],
                    )
                    for j in range(KVH)
                ], axis=2)
                check(f"flash prefill {tag} S{S}", got, want)

            attempt(f"flash prefill {tag} S{S}", flash)
            S *= 2
    print(json.dumps({"failures": failures}), flush=True)
    return 1 if failures else 0


# ---- leg 3: server (driven from the parent) ----------------------------------


def _http(method: str, url: str, body: dict | None = None, timeout=600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _get_json(url: str, timeout=30.0) -> dict:
    return json.loads(_http("GET", url, timeout=timeout)[1])


def _chat(base: str, text: str, max_tokens: int, stream: bool = False):
    body = {
        "model": MODEL_NAME,
        "messages": [{"role": "user", "content": text}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        "stream": stream,
    }
    status, raw = _http("POST", base + "/v1/chat/completions", body)
    if status != 200:
        raise RuntimeError(f"chat completion answered {status}: {raw[:300]!r}")
    return raw


def _metric(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _prompt(n_bytes: int, salt: int) -> str:
    words = ("page", "slot", "mesh", "chip", "token", "shard", "bucket")
    out, i = [], salt
    while sum(len(w) + 1 for w in out) < n_bytes:
        out.append(words[i % len(words)] + str(i % 97))
        i += 3
    return " ".join(out)[:n_bytes]


class Failed(Exception):
    pass


def need(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def drive_server(base: str, device: dict, sizes: dict, proc) -> dict:
    """The requests of leg 3. Raises Failed on the first wrong answer."""
    seen: dict = {}
    state = _get_json(base + "/v1/state")
    dev = state.get("device") or {}
    want_count = 4 if sizes["topology"] else 1
    need(
        dev.get("platform") == "tpu"
        and dev.get("device_kind") == device["kind"]
        and dev.get("count") == want_count,
        f"server process is not on the expected device: /v1/state says {dev}, "
        f"expected tpu / {device['kind']} / {want_count}",
    )
    log(f"server sits on {dev}")
    # The server's own boot record (load / compile / warmup seconds).
    seen["boot_phases_s"] = {
        k: round(v, 1)
        for k, v in (state.get("cold_start") or {}).get("phases", {}).items()
    }
    metrics0 = _http("GET", base + "/metrics")[1].decode()

    # 1. short, not streamed.
    first_prompt = "Say hello to the chip."
    r = json.loads(_chat(base, first_prompt, 16))
    need(r["usage"]["completion_tokens"] == 16, f"short chat usage: {r['usage']}")
    first_text = r["choices"][0]["message"]["content"]
    log(f"short chat ok: {first_text!r}")

    # 2. streamed. Every content chunk carries the ids of its tokens.
    def streamed(text: str, max_tokens: int) -> list[int]:
        raw = _chat(base, text, max_tokens, stream=True).decode()
        events = [l[6:] for l in raw.splitlines() if l.startswith("data: ")]
        need(
            events and events[-1] == "[DONE]",
            "SSE stream did not end in [DONE]",
        )
        chunks = [json.loads(e) for e in events[:-1]]
        ids = [int(t) for c in chunks for t in c.get("token_ids", ())]
        need(
            len(ids) == max_tokens,
            f"streamed chunks carried {len(ids)} token ids, asked {max_tokens}",
        )
        need(
            chunks[-1]["choices"][0]["finish_reason"] == "length",
            f"stream finish_reason: {chunks[-1]['choices'][0]}",
        )
        return ids

    first_ids = streamed(first_prompt, 16)
    need(
        bytes(first_ids).decode("utf-8", errors="replace") == first_text,
        f"streamed token ids {first_ids} do not decode to the text the same "
        f"greedy request returned unstreamed, {first_text!r}",
    )
    log(f"streamed chat ok: [DONE] seen, token ids {first_ids}")

    # 3. concurrent: prompts of 300-1500 bytes land in the 512, 1024 and
    # 2048 buckets (flash prefill, batched admission); 96 new tokens cross
    # a 64-token page boundary in the paged decode kernel.
    plens = [300, 420, 700, 1100, 1500, 1450]
    results: list = [None] * len(plens)

    def one(i):
        try:
            results[i] = json.loads(_chat(base, _prompt(plens[i], i), 96))
        except Exception as e:  # noqa: BLE001 — surfaced by the check below
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(plens))]
    for t in threads:
        t.start()
    max_batch = 0
    while any(t.is_alive() for t in threads):
        need(proc.poll() is None, "server exited during the concurrent leg")
        step = _get_json(base + "/v1/state").get("last_step") or {}
        max_batch = max(max_batch, int(step.get("batch_size", 0)))
        time.sleep(0.05)
    for i, r in enumerate(results):
        need(not isinstance(r, Exception), f"concurrent request {i}: {r!r}")
        need(
            r["usage"]["completion_tokens"] == 96
            and r["usage"]["prompt_tokens"] >= plens[i],
            f"concurrent request {i} usage: {r['usage']}",
        )
    need(max_batch >= 4, f"/v1/state last_step never showed a batch: max {max_batch}")
    seen["max_batch"] = max_batch
    log(f"{len(plens)} concurrent completions ok, batch of {max_batch} seen")

    # 4. a bucket nothing has compiled yet (256): the compile happens inside
    # step() with work active, and must not trip the step watchdog.
    t_cold = time.monotonic()
    r = json.loads(_chat(base, _prompt(200, 11), 8))
    seen["cold_bucket_request_s"] = round(time.monotonic() - t_cold, 1)
    need(r["usage"]["completion_tokens"] == 8, f"cold bucket usage: {r['usage']}")
    need(
        _http("GET", base + "/health")[0] == 200,
        "server unhealthy after a compile inside step()",
    )
    log(
        f"cold-bucket request ok in {seen['cold_bucket_request_s']}s, compile "
        "included (smoke observation); server still healthy"
    )

    # 5. the first request again, after the batch and the cold bucket have
    # been through the pool: greedy output must repeat. Token ids decide
    # it; the decoded text is mostly U+FFFD and different streams could
    # compare equal.
    again_ids = streamed(first_prompt, 16)
    need(
        again_ids == first_ids,
        f"greedy token ids changed: {first_ids} -> {again_ids}",
    )
    r = json.loads(_chat(base, first_prompt, 16))
    again = r["choices"][0]["message"]["content"]
    need(again == first_text, f"greedy text changed: {first_text!r} -> {again!r}")
    log("greedy repeat ok: same 16 token ids, same text")

    metrics1 = _http("GET", base + "/metrics")[1].decode()
    asked = 16 + 16 + 96 * len(plens) + 8 + 16 + 16
    gen = _metric(metrics1, "kubeai_engine_generated_tokens_total") - _metric(
        metrics0, "kubeai_engine_generated_tokens_total"
    )
    prm = _metric(metrics1, "kubeai_engine_prompt_tokens_total") - _metric(
        metrics0, "kubeai_engine_prompt_tokens_total"
    )
    need(gen == asked, f"generated_tokens moved by {gen}, asked for {asked}")
    need(prm >= sum(plens), f"prompt_tokens moved by {prm}")
    need(
        _metric(metrics1, "kubeai_engine_watchdog_stalls_total") == 0,
        "the step watchdog fired",
    )
    seen["generated_tokens"] = int(gen)
    return seen


def leg_server(device: dict, sizes: dict, deadline: float) -> dict:
    os.makedirs(OUT_DIR, exist_ok=True)
    cfg = dict(MISTRAL_7B, num_hidden_layers=sizes["depth"])
    t0 = time.monotonic()
    nbytes = write_checkpoint(CKPT_DIR, cfg)
    log(
        f"wrote {nbytes / 1e9:.2f} GB checkpoint ({sizes['depth']} layers) to "
        f"{CKPT_DIR} in {time.monotonic() - t0:.0f}s"
    )
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [
        sys.executable, "-m", "kubeai_tpu.engine.server",
        "--model-url", CKPT_DIR,
        "--served-model-name", MODEL_NAME,
        "--host", "127.0.0.1", "--port", str(port),
        "--num-slots", str(sizes["num_slots"]),
        "--max-seq-len", str(sizes["max_seq_len"]),
    ]
    if sizes["topology"]:
        cmd += ["--tpu-topology", sizes["topology"]]
    log_path = os.path.join(OUT_DIR, "server.log")
    base = f"http://127.0.0.1:{port}"
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    log("starting: " + " ".join(cmd))
    with open(log_path, "wb") as logf:
        t_boot = time.monotonic()
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=logf, stderr=subprocess.STDOUT)
        try:
            while True:
                need(
                    proc.poll() is None,
                    f"server exited with {proc.returncode} before Ready",
                )
                need(time.monotonic() < deadline, "server not Ready in time")
                try:
                    if _http("GET", base + "/health", timeout=2)[0] == 200:
                        break
                except (urllib.error.URLError, OSError):
                    pass
                time.sleep(0.5)
            boot_s = time.monotonic() - t_boot
            # What the kernels leg and the boot left in the cache. Their
            # graphs are the same every run; after Ready, how concurrent
            # arrivals group into admission batches is a matter of timing,
            # and a run may meet a batch shape the last one did not.
            at_ready = cache_entries(device["cache_dir"])
            log(
                f"server Ready after {boot_s:.1f}s (smoke observation), "
                f"{at_ready} compile-cache entries"
            )
            seen = drive_server(base, device, sizes, proc)
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=max(5.0, deadline - time.monotonic()))
            need(rc == 0, f"server exited with {rc} after SIGTERM")
            log("SIGTERM: server drained and exited 0")
            return {
                "boot_to_ready_s": round(boot_s, 1),
                "cache_entries_at_ready": at_ready,
                **seen,
            }
        except BaseException:
            logf.flush()
            with open(log_path, "rb") as lf:
                tail = lf.read()[-6000:].decode(errors="replace")
            print("---- server log tail ----\n" + tail, flush=True)
            raise
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


# ---- parent -------------------------------------------------------------------


def run_leg(args: list[str], deadline: float) -> dict:
    """Run one leg as a child; returns the JSON object on its last line."""
    assert "jax" not in sys.modules, "the parent must stay off JAX"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), *args],
        cwd=REPO, stdout=subprocess.PIPE, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise Failed(f"leg {args} ran out of time")
    sys.stdout.write(out)
    sys.stdout.flush()
    last = {}
    for line in out.splitlines():
        if line.startswith("{"):
            last = json.loads(line)
    need(proc.returncode == 0, f"leg {args} exited with {proc.returncode}: {last}")
    return last


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--leg", choices=["device", "kernels"])
    ap.add_argument("--max-seq-len", type=int, default=2048)
    args = ap.parse_args()
    if args.leg == "device":
        return leg_device()
    if args.leg == "kernels":
        return leg_kernels(args.max_seq_len)

    t_start = time.monotonic()
    deadline = t_start + DEADLINE_S
    try:
        device = run_leg(["--leg", "device"], deadline)
        need(
            device.get("platform") == "tpu",
            f"no TPU: JAX found platform {device.get('platform')!r}",
        )
        log(f"device: {device}")
        sizes = SIZES[4 if device["count"] == 4 else 1]
        before = cache_entries(device["cache_dir"])
        run_leg(
            ["--leg", "kernels", "--max-seq-len", str(sizes["max_seq_len"])],
            deadline,
        )
        served = leg_server(device, sizes, deadline)
    except Failed as e:
        log(f"FAILED: {e}")
        return 1
    result = {
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": device["count"],
        },
    }
    # What was served goes on its own labelled line: the driver reads the
    # last line, which holds the keys "ok" and "device" and no other.
    log("summary: " + json.dumps({
        **result,
        "mesh": sizes["topology"] or "1",
        "depth": sizes["depth"],
        "num_slots": sizes["num_slots"],
        "max_seq_len": sizes["max_seq_len"],
        **served,
        "cache_dir": device["cache_dir"],
        "cache_entries_before": before,
        "cache_entries_after": cache_entries(device["cache_dir"]),
        "elapsed_s": round(time.monotonic() - t_start, 1),
        "claim": None,
    }))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
