"""The load generator and the client-side reduction, against a fake
server that speaks the program's SSE framing."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import clientstats
import loadgen


class FakeHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    short = False          # end every stream one token early
    shed_every = 0         # answer 429 to every n-th request
    count = 0
    lock = threading.Lock()

    def log_message(self, *a):
        pass

    def _send(self, obj):
        data = b"data: " + (obj if isinstance(obj, bytes)
                            else json.dumps(obj).encode()) + b"\n\n"
        self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
        self.wfile.flush()

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        cls = type(self)
        with cls.lock:
            cls.count += 1
            n = cls.count
        if cls.shed_every and n % cls.shed_every == 0:
            msg = b'{"error": "over capacity"}'
            self.send_response(429)
            self.send_header("Content-Length", str(len(msg)))
            self.end_headers()
            self.wfile.write(msg)
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        n_out = body["max_tokens"] - (1 if cls.short else 0)
        for i in range(n_out):
            time.sleep(0.002)
            text = f"w{i}" if i == 0 else f" w{i}"
            self._send({"choices": [{"index": 0, "text": text,
                                     "finish_reason": None}]})
        self._send({"choices": [{"index": 0, "text": "",
                                 "finish_reason": "length"}]})
        self._send(b"[DONE]")
        self.wfile.write(b"0\r\n\r\n")
        self.close_connection = True


@pytest.fixture
def fake_server():
    handler = type("H", (FakeHandler,), {"count": 0})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv, handler
    srv.shutdown()
    srv.server_close()


def _plan(srv, loop, reqs, seconds, concurrency=0):
    return {"host": "127.0.0.1", "port": srv.server_address[1], "model": "m",
            "loop": loop, "concurrency": concurrency, "seconds": seconds,
            "drain_timeout_s": 20, "requests": reqs}


def test_open_loop_counts_every_token_and_times_from_due(fake_server):
    srv, _ = fake_server
    reqs = [{"due_s": 0.05 * i, "prompt": "w1 w2", "max_tokens": 8 + i % 3}
            for i in range(40)]
    res = loadgen.run(_plan(srv, "open", reqs, 2.0))
    st = clientstats.reduce(res)
    assert st["attempted"] == 40 and st["failed"] == 0, st["failures"]
    assert st["tokens"] == sum(r["max_tokens"] for r in reqs)
    assert st["samples"] == {"ttft": 40, "itl": st["tokens"] - 40}
    assert 0 < st["ttft_p50_ms"] < 500
    assert st["ttft_p95_ms"] is None            # 40 * 5% < 10 samples beyond
    assert st["itl_p95_ms"] is not None
    assert all(r["sent_s"] >= r["due_s"] for r in res["requests"])


def test_closed_loop_keeps_concurrency_and_stops_at_the_window(fake_server):
    srv, handler = fake_server
    reqs = [{"due_s": 0.0, "prompt": "w1", "max_tokens": 20}
            for _ in range(500)]
    res = loadgen.run(_plan(srv, "closed", reqs, 1.0, concurrency=4))
    st = clientstats.reduce(res)
    assert 4 <= st["attempted"] < 500 and st["failed"] == 0
    assert res["not_sent"] == 500 - st["attempted"]
    assert max(r["sent_s"] for r in res["requests"]) < 1.0
    assert st["out_tok_s"] > 0
    assert handler.count == st["attempted"]


def test_a_short_stream_and_a_shed_are_failures(fake_server):
    srv, handler = fake_server
    handler.short = True
    reqs = [{"due_s": 0.0, "prompt": "w1", "max_tokens": 4}]
    st = clientstats.reduce(loadgen.run(_plan(srv, "open", reqs, 0.5)))
    assert st["failed"] == 1 and "3 chunks" in st["failures"][0]
    handler.short = False
    handler.shed_every = 2
    reqs = [{"due_s": 0.01 * i, "prompt": "w1", "max_tokens": 4}
            for i in range(6)]
    st = clientstats.reduce(loadgen.run(_plan(srv, "open", reqs, 0.5)))
    assert st["attempted"] == 6 and st["failed"] == 3
    assert all("HTTP 429" in f for f in st["failures"])


def test_a_dead_server_fails_every_request():
    reqs = [{"due_s": 0.0, "prompt": "w1", "max_tokens": 4}]
    plan = {"host": "127.0.0.1", "port": 1, "model": "m", "loop": "open",
            "concurrency": 0, "seconds": 0.2, "drain_timeout_s": 2,
            "requests": reqs}
    st = clientstats.reduce(loadgen.run(plan))
    assert st["attempted"] == 1 and st["failed"] == 1


@pytest.mark.parametrize("n,q,want", [
    (100, 95, None), (200, 95, "value"), (5, 50, "value"), (0, 50, None)])
def test_a_tail_needs_ten_samples_beyond_it(n, q, want):
    got = clientstats.percentile(list(range(n)), q)
    assert (got is None) == (want is None)


def test_percentile_interpolates():
    assert clientstats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
