"""Shared test fakes: scripted engine backends, metrics servers, a fake
kubelet — the httptest.Server / markAllModelPodsReady equivalents
(reference: test/integration/utils_test.go)."""

from __future__ import annotations

import json
import subprocess
import tempfile
import threading
import time
from contextlib import contextmanager
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


class FakeEngine:
    """Scripted engine backend. `behavior(path, body) -> (status, payload)`
    — or `(status, payload, headers)` — overrides the default echo
    response."""

    def __init__(self, behavior=None):
        fake = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                req_body = self.rfile.read(n)
                fake.requests.append((self.path, req_body))
                fake.request_headers.append(
                    {k.lower(): v for k, v in self.headers.items()}
                )
                result = (fake.behavior or fake.default)(
                    self.path, req_body
                )
                status, payload = result[0], result[1]
                extra_headers = result[2] if len(result) > 2 else {}
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in extra_headers.items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

        self.requests: list = []
        self.request_headers: list = []
        self.behavior = behavior
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def default(self, path, body):
        try:
            model = json.loads(body).get("model", "?")
        except json.JSONDecodeError:
            model = "?"
        return 200, {
            "object": "chat.completion",
            "model": model,
            "echo": model,
            "backend": self.port,
        }

    @property
    def port(self):
        return self.httpd.server_address[1]

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class FakeTelemetryEngine:
    """Scripted serving-endpoint telemetry: GET /metrics serves Prom
    text, GET /v1/state serves a JSON snapshot — what the fleet
    aggregator sweeps. `metrics_text`/`state` are mutable; set `dead`
    to make every request drop the connection (a dead endpoint the
    aggregator must flag stale, not merge)."""

    def __init__(self, metrics_text: str = "", state: dict | None = None):
        srv = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                if srv.dead:
                    self.connection.close()
                    return
                if self.path == "/metrics":
                    body = srv.metrics_text.encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path == "/v1/state":
                    body = json.dumps(srv.state).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.metrics_text = metrics_text
        self.state = state or {}
        self.dead = False
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    @property
    def port(self):
        return self.httpd.server_address[1]

    @property
    def addr(self):
        h, p = self.httpd.server_address[:2]
        return f"{h}:{p}"

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class FakeMetricsServer:
    """Static Prom-text server (reference: hack/vllm-mock-metrics/main.go)."""

    def __init__(self, text: str):
        srv = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                body = srv.text.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.text = text
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    @property
    def addr(self):
        h, p = self.httpd.server_address[:2]
        return f"{h}:{p}"

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def http_post(address: str, path: str, payload: dict, timeout=30, headers=None):
    """POST JSON to host:port; returns (status, body_bytes)."""
    import http.client

    host, _, port = address.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    body = json.dumps(payload).encode()
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", path, body=body, headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def http_get(address: str, path: str, timeout=10, headers=None):
    import http.client

    host, _, port = address.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    conn.request("GET", path, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def ready_pod_manifest(model: str, index: int, port: int, ip="127.0.0.1") -> dict:
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": f"model-{model}-{index}",
            "namespace": "default",
            "labels": {"model": model},
            "annotations": {
                "model-pod-ip": ip,
                "model-pod-port": str(port),
            },
        },
        "status": {
            "conditions": [{"type": "Ready", "status": "True"}],
            "podIP": ip,
        },
    }


def mark_model_pods_ready(store, name: str | None = None):
    """Write Pod status by hand — no kubelet runs in these tests
    (reference: utils_test.go:118-132)."""
    selector = {"model": name} if name else None
    for pod in store.list("Pod", "default", selector):
        if "model" not in (pod["metadata"].get("labels") or {}):
            continue
        status = pod.setdefault("status", {})
        if any(
            c.get("type") == "Ready" and c.get("status") == "True"
            for c in status.get("conditions", [])
        ):
            continue
        status["conditions"] = [
            {"type": "Ready", "status": "True"},
            {"type": "PodScheduled", "status": "True"},
        ]
        status["podIP"] = "10.0.0.9"
        try:
            store.update(pod)
        except Exception:
            pass


@contextmanager
def fake_kubelet(store, name: str | None = None, interval: float = 0.05):
    """Background thread continuously marking model pods ready."""
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            mark_model_pods_ready(store, name)
            time.sleep(interval)

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join(timeout=2)


def eventually(fn, timeout=10, interval=0.05, msg="condition"):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            result = fn()
            if result:
                return result
        except Exception as e:
            last = e
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg} (last error: {last})")


def popen_logged(cmd, **kw) -> subprocess.Popen:
    """Popen with stdout and stderr in a temp file; `proc.output()` reads
    what was written so far. Not a pipe: a pipe nobody drains blocks the
    child once 64 KiB sit in it, and XLA:CPU alone writes 2 KB for every
    hit in the persistent compilation cache — a server boot on a warm
    cache then stalls mid-step and reads as a hang (or dies by its own
    step watchdog)."""
    logf = tempfile.TemporaryFile()
    proc = subprocess.Popen(cmd, stdout=logf, stderr=subprocess.STDOUT, **kw)

    def output() -> str:
        size = logf.seek(0, 2)
        logf.seek(0)
        return logf.read(size).decode(errors="replace")

    proc.output = output
    return proc


def synchronous(engine):
    """`engine` on the synchronous step loop, the way lockstep puts it
    there (`engine/multihost.py`: `_overlap = False` before the first
    step). No option selects the loop: an engine overlaps unless its mesh
    has a pp axis, so a case that wants the other loop says so here."""
    assert engine._inflight is None, "set before the first step"
    engine._overlap = False
    return engine


def per_layer_forward(family):
    """`family.decode_step_paged` held to the per-layer layout through the
    forwards' own `attn_kernel=` keyword: the reference of the stacked
    layout, named at the forward since no engine option names a layout."""
    forward = family.decode_step_paged

    def held(*args, **kw):
        return forward(*args, **{**kw, "attn_kernel": "per_layer"})

    return held
