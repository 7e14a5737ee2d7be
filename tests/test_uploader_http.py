"""TraceUploader over a REAL HTTP peer (loopback http.server).

Round-4 review: the upload path had wire-format tests but never
faced a real socket peer. Zero egress makes a remote `/api/traces`
unreachable, so the peer is a loopback HTTP server speaking the same
contract — real sockets, real POST bodies, real status codes
(traceCollectorService.ts:797-899 `_uploadTraces`)."""

import http.server
import json
import threading
import time

import pytest

from senweaver_ide_tpu.traces.collector import TraceCollector
from senweaver_ide_tpu.traces.uploader import (TraceUploader,
                                               http_trace_transport)


class _TracesHandler(http.server.BaseHTTPRequestHandler):
    received = []          # class-level: one server per fixture
    fail_next = 0
    fail_code = 500
    retry_after = None     # sent as a Retry-After header on failures
    requests = 0

    def do_POST(self):
        _TracesHandler.requests += 1
        body = self.rfile.read(int(self.headers["Content-Length"]))
        if self.path != "/api/traces":
            self.send_response(404)
            self.end_headers()
            return
        if _TracesHandler.fail_next > 0:
            _TracesHandler.fail_next -= 1
            self.send_response(_TracesHandler.fail_code)
            if _TracesHandler.retry_after is not None:
                self.send_header("Retry-After",
                                 str(_TracesHandler.retry_after))
            self.end_headers()
            return
        payload = json.loads(body)
        _TracesHandler.received.append(payload)
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.end_headers()
        self.wfile.write(b'{"ok": true}')

    def log_message(self, *a):      # keep pytest output clean
        pass


@pytest.fixture()
def traces_server():
    _TracesHandler.received = []
    _TracesHandler.fail_next = 0
    _TracesHandler.fail_code = 500
    _TracesHandler.retry_after = None
    _TracesHandler.requests = 0
    srv = http.server.HTTPServer(("127.0.0.1", 0), _TracesHandler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_port}/api/traces"
    srv.shutdown()


def _ended_traces(n: int, collector=None):
    collector = collector or TraceCollector()
    out = []
    for i in range(n):
        tid = collector.start_trace(f"t{i}")
        collector.record_user_message(f"t{i}", 0, f"msg {i}")
        collector.end_trace(tid)
        out.append(collector.get_trace(tid))
    return out


def test_upload_over_real_socket(traces_server, tmp_path):
    traces = _ended_traces(3)
    up = TraceUploader(http_trace_transport(traces_server),
                       uploaded_ids_path=str(tmp_path / "ids.json"))
    assert up.upload(traces) == 3
    assert len(_TracesHandler.received) == 1          # one batch
    sent = _TracesHandler.received[0]["traces"]
    assert len(sent) == 3
    assert {t["id"] for t in sent} == {t.id for t in traces}
    # dedup: a second cycle re-sends nothing
    assert up.upload(traces) == 0
    assert len(_TracesHandler.received) == 1


def test_upload_survives_restart_without_resend(traces_server, tmp_path):
    traces = _ended_traces(2)
    path = str(tmp_path / "ids.json")
    TraceUploader(http_trace_transport(traces_server),
                  uploaded_ids_path=path).upload(traces)
    # fresh process posture: new uploader, same WAL file
    up2 = TraceUploader(http_trace_transport(traces_server),
                        uploaded_ids_path=path)
    assert up2.upload(traces) == 0
    assert len(_TracesHandler.received) == 1


def test_transient_5xx_retried_in_call(traces_server, tmp_path):
    """A 5xx is transient: the transport retries in-call with backoff
    and the batch lands without waiting for the next upload cycle."""
    traces = _ended_traces(2)
    sleeps = []
    up = TraceUploader(
        http_trace_transport(traces_server, sleep=sleeps.append),
        uploaded_ids_path=str(tmp_path / "ids.json"))
    _TracesHandler.fail_next = 1
    assert up.upload(traces) == 2          # 500 → in-call retry → 200
    assert _TracesHandler.requests == 2
    assert len(_TracesHandler.received) == 1
    # one backoff slept: base 0.5s scaled by the 0.5–1.5x jitter
    assert len(sleeps) == 1
    assert 0.25 <= sleeps[0] <= 0.75


def test_retry_after_header_is_a_backoff_floor(traces_server, tmp_path):
    """A 503 naming its own backpressure interval is honored: the
    retry sleeps at least Retry-After seconds, never the (smaller)
    jittered exponential."""
    traces = _ended_traces(1)
    sleeps = []
    up = TraceUploader(
        http_trace_transport(traces_server, sleep=sleeps.append),
        uploaded_ids_path=str(tmp_path / "ids.json"))
    _TracesHandler.fail_next = 1
    _TracesHandler.fail_code = 503
    _TracesHandler.retry_after = 2
    assert up.upload(traces) == 1
    assert len(sleeps) == 1
    assert sleeps[0] >= 2.0                # floor, not the 0.25–0.75 base


def test_429_is_transient_and_retried(traces_server, tmp_path):
    """Throttling (429) is backpressure, not batch rejection — it
    retries like a 5xx instead of failing fast like other 4xx."""
    traces = _ended_traces(1)
    sleeps = []
    up = TraceUploader(
        http_trace_transport(traces_server, sleep=sleeps.append),
        uploaded_ids_path=str(tmp_path / "ids.json"))
    _TracesHandler.fail_next = 1
    _TracesHandler.fail_code = 429
    assert up.upload(traces) == 1          # 429 → retry → 200
    assert _TracesHandler.requests == 2
    assert len(sleeps) == 1


def test_exhausted_retries_defer_to_next_cycle(traces_server, tmp_path):
    traces = _ended_traces(2)
    up = TraceUploader(
        http_trace_transport(traces_server, max_retries=1,
                             sleep=lambda s: None),
        uploaded_ids_path=str(tmp_path / "ids.json"))
    _TracesHandler.fail_next = 3
    assert up.upload(traces) == 0          # 2 attempts, both 500 → give up
    assert _TracesHandler.requests == 2
    # nothing was marked: the next cycle re-sends (one more 500, then 200)
    assert up.upload(traces) == 2
    assert _TracesHandler.requests == 4
    assert len(_TracesHandler.received) == 1


def test_4xx_fails_fast_without_retry(traces_server, tmp_path):
    """Client errors are permanent — the batch itself is rejected, so
    retrying would only hammer the ingest endpoint."""
    traces = _ended_traces(1)
    sleeps = []
    up = TraceUploader(
        http_trace_transport(traces_server, sleep=sleeps.append),
        uploaded_ids_path=str(tmp_path / "ids.json"))
    _TracesHandler.fail_next = 1
    _TracesHandler.fail_code = 422
    assert up.upload(traces) == 0
    assert _TracesHandler.requests == 1    # exactly one attempt
    assert sleeps == []
    # the uploader contract still holds: nothing marked, next cycle works
    assert up.upload(traces) == 1
    assert len(_TracesHandler.received) == 1


def test_unreachable_peer_is_a_clean_false(tmp_path):
    traces = _ended_traces(1)
    sleeps = []
    up = TraceUploader(
        http_trace_transport("http://127.0.0.1:9/api/traces",  # closed
                             max_retries=1, sleep=sleeps.append),
        uploaded_ids_path=str(tmp_path / "ids.json"))
    t0 = time.monotonic()
    assert up.upload(traces) == 0
    assert len(sleeps) == 1                # transient → retried once
    assert time.monotonic() - t0 < 10      # fails fast, no hang
