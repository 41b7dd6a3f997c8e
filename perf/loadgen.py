"""The load generator: a child process that never imports JAX and speaks
HTTP/SSE to the server like any client.

    python perf/loadgen.py <spec.json>   ->  one JSON object on stdout

The spec (written by perf/run.py) names the mix, the seed, the window and the
wall-clock time at which the measured window starts. Every time in the
result is in seconds relative to that start. Open loop: each request is sent
at its due time whatever happened to earlier ones, and `sent - due` is how
late the generator ran; a stream that outlives the window is cut at its first
event after the window's end (its first token, if that is still to come). Closed loop: each client sends its next request when
its last one has ended, from `-preroll_s` until the window closes; what is
still streaming then is cut.

Where the spec says `routes` (the server's `/v1/state` reported a router
whose routes it hands over), every request carries `"kubeai_routes": true`
and its record keeps, under `routes`, the blocks of every chunk's top-level
`kubeai_routes` in arrival order, as they came: `{start, rows, shape, dtype,
data}` with `data` in base64 (docs/concepts/expert-routes.md; perf/check.py
holds them to the row rule). Where the spec names `handover` flags (the
configuration's reference module has `HANDOVER`: a generator of its own, see
perf/check.py), every request carries each flag as `true` and its record
keeps, under `handover[<flag>]`, the blocks of every chunk's top-level key of
that name, in arrival order, as they came: nothing is decoded here, the
family's reference says what they mean. With neither, the request body and
the records are what they always were.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perf import traffic  # noqa: E402
from perf.tokenizer import text_of  # noqa: E402


class Clock:
    """Seconds since the window's start, on the monotonic clock."""

    def __init__(self, t0_wall: float, wall=time.time, mono=time.monotonic):
        self._mono = mono
        self._origin = mono() + (t0_wall - wall())

    def now(self) -> float:
        return self._mono() - self._origin

    def sleep_until(self, t: float, sleep=time.sleep) -> None:
        while True:
            left = t - self.now()
            if left <= 0:
                return
            sleep(min(left, 0.5))


def request_body(model, req, vocab, seed, routes=False, handover=()) -> str:
    body = {
        "model": model,
        "prompt": text_of(
            traffic.prompt_tokens(seed, req["index"], req["prompt_len"], vocab)
        ),
        "max_tokens": req["max_tokens"], "temperature": 0.0, "stream": True,
    }
    if routes:
        body["kubeai_routes"] = True
    for flag in handover:
        body[flag] = True
    return json.dumps(body)


def one_request(host, port, model, req, vocab, seed, clock, timeout,
                stop_at=None, routes=False, handover=()) -> dict:
    """Send one streamed completion and record every token-bearing event.
    Past `stop_at` the stream is cut (the server cancels a request whose
    client went away) and the record says so."""
    rec = {"index": req["index"], "due": req.get("due"),
           "prompt_len": req["prompt_len"], "max_tokens": req["max_tokens"],
           "ok": False, "events": [], "token_ids": []}
    if routes:
        rec["routes"] = []
    if handover:
        rec["handover"] = {flag: [] for flag in handover}
    body = request_body(model, req, vocab, seed, routes, handover)
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        rec["sent"] = clock.now()
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json",
                      "Connection": "close"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(300).decode("utf-8", "replace")
            return rec
        lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            if line.startswith(b"data: "):
                if line.startswith(b"data: [DONE]"):
                    rec["ok"] = True
                    break
                lines.append((clock.now(), line[6:]))
                if stop_at is not None and lines[-1][0] >= stop_at:
                    rec["cut"] = True
                    break
        # Parsing waits until the stream has ended, so it costs the timed
        # path nothing.
        for t, raw in lines:
            chunk = json.loads(raw)
            ids = chunk.get("token_ids")
            if ids:
                rec["events"].append([t, len(ids)])
                rec["token_ids"].extend(ids)
            if routes:
                rec["routes"].extend(chunk.get("kubeai_routes") or ())
            for flag in handover:
                rec["handover"][flag].extend(chunk.get(flag) or ())
        if rec["ok"]:
            rec["end"] = clock.now()
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def run_open(spec, clock, send) -> list[dict]:
    schedule = traffic.open_schedule(spec["mix"], spec["seed"], spec["seconds"])
    records: list[dict] = []
    lock = threading.Lock()

    def fire(req):
        clock.sleep_until(req["due"])
        # Past the window's end only a first token is still wanted: the
        # stream is cut at the first event that arrives after it.
        rec = send(req, stop_at=spec["seconds"])
        with lock:
            records.append(rec)

    # One thread per request, started ahead of its due time: a pool would
    # queue a due request behind a slow one, which is what an open loop
    # must not do.
    threads = []
    for req in schedule:
        clock.sleep_until(req["due"] - 0.25)
        th = threading.Thread(target=fire, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    deadline = spec["seconds"] + spec["mix"].get("drain_s", 30.0)
    for th in threads:
        th.join(timeout=max(0.0, deadline - clock.now()))
    with lock:
        done = {r["index"] for r in records}
        out = list(records)
    for req in schedule:
        if req["index"] not in done:
            out.append({"index": req["index"], "due": req["due"],
                        "prompt_len": req["prompt_len"],
                        "max_tokens": req["max_tokens"], "ok": False,
                        "events": [], "token_ids": [],
                        "error": "not finished by the drain limit"})
    return out


def run_closed(spec, clock, send) -> list[dict]:
    seqs = traffic.closed_sequences(spec["mix"], spec["seed"], spec["clients"])
    records: list[dict] = []
    lock = threading.Lock()
    start = -float(spec["mix"].get("preroll_s", 0.0))

    def client(cycle):
        clock.sleep_until(start)
        i = 0
        while clock.now() < spec["seconds"]:
            rec = send(cycle[i % len(cycle)], stop_at=spec["seconds"])
            rec["lap"] = i // len(cycle)
            with lock:
                records.append(rec)
            i += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in seqs]
    for th in threads:
        th.start()
    deadline = spec["seconds"] + spec["mix"].get("drain_s", 30.0)
    for th in threads:
        th.join(timeout=max(0.0, deadline - clock.now()))
    with lock:
        return list(records)


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    clock = Clock(spec["t0_wall"])
    timeout = spec["seconds"] + spec["mix"].get("drain_s", 30.0) + 30.0

    def send(req, stop_at=None):
        return one_request(spec["host"], spec["port"], spec["model"], req,
                           spec["vocab"], spec["seed"], clock, timeout, stop_at,
                           routes=bool(spec.get("routes")),
                           handover=tuple(spec.get("handover") or ()))

    run = run_open if spec["mix"]["loop"] == "open" else run_closed
    records = run(spec, clock, send)
    json.dump({"loop": spec["mix"]["loop"], "records": records}, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
