#!/usr/bin/env python3
"""The load generator: one JAX-free process, one thread, raw sockets.

Reads a plan (JSON: where the server is, the requests with their due
times, open or closed loop), sends every request as a streamed
``/v1/completions`` and writes what the client saw (JSON): for each
request when it was due, when it was sent, the arrival time of every
SSE chunk that carried text, whether a finish chunk and ``[DONE]``
came, and the HTTP status.  It parses HTTP and SSE by hand from one
``selectors`` loop, so there is no thread to fight over an interpreter
lock and a timestamp is taken the moment bytes are readable.

Open loop: a request is sent when it is due, whatever the server is
doing, and timed from when it was due.  Closed loop: ``concurrency``
clients each send their next request when the last one ended, until
the window is over.  Either way every request sent is then drained
(bounded by ``drain_timeout_s``), so attempted and failed are exact.
"""

import errno
import json
import selectors
import socket
import sys
import time


class Conn:
    def __init__(self, idx: int, req: dict, sock, payload: bytes, now: float):
        self.idx, self.req, self.sock = idx, req, sock
        self.out = payload
        self.buf = bytearray()
        self.status = 0
        self.sent_s = now
        self.chunk_s = []        # arrival of each chunk that carried text
        self.words = 0
        self.finish = None       # finish_reason of the finish chunk
        self.done = False        # [DONE] seen
        self.error = ""


def _payload(plan: dict, req: dict) -> bytes:
    body = json.dumps({
        "model": plan["model"], "prompt": req["prompt"],
        "max_tokens": req["max_tokens"], "temperature": 0,
        "ignore_eos": True, "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {plan['host']}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    return head.encode() + body


def _parse(c: Conn, now: float) -> None:
    """Consume whole SSE events from the buffer.  The stream is chunked
    transfer encoding; the server writes one event per chunk, so the
    hex size lines fall between events and are skipped with them."""
    if not c.status:
        end = c.buf.find(b"\r\n\r\n")
        if end < 0:
            return
        c.status = int(c.buf[:end].split(b"\r\n", 1)[0].split()[1])
        del c.buf[:end + 4]
    if c.status != 200:
        return
    while True:
        start = c.buf.find(b"data: ")
        end = c.buf.find(b"\n\n", start) if start >= 0 else -1
        if end < 0:
            return
        event = bytes(c.buf[start + 6:end])
        del c.buf[:end + 2]
        if event == b"[DONE]":
            c.done = True
            return
        choice = json.loads(event)["choices"][0]
        text = choice.get("text", "")
        if text:
            c.chunk_s.append(now)
            c.words += len(text.split())
        if choice.get("finish_reason"):
            c.finish = choice["finish_reason"]


def run(plan: dict) -> dict:
    reqs = plan["requests"]
    seconds = float(plan["seconds"])
    closed = plan["loop"] == "closed"
    sel = selectors.DefaultSelector()
    results = [None] * len(reqs)
    live = 0
    nxt = 0
    t0 = time.monotonic()
    t0_unix = time.time()

    def start(idx: int, now: float) -> None:
        nonlocal live
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        rc = s.connect_ex((plan["host"], plan["port"]))
        c = Conn(idx, reqs[idx], s, _payload(plan, reqs[idx]), now)
        if rc not in (0, errno.EINPROGRESS):
            c.error = f"connect: {errno.errorcode.get(rc, rc)}"
            s.close()
            results[idx] = c
            return
        sel.register(s, selectors.EVENT_WRITE, c)
        live += 1

    def finish(c: Conn, error: str = "") -> None:
        nonlocal live
        c.error = c.error or error
        try:
            sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()
        results[c.idx] = c
        live -= 1

    deadline = seconds + float(plan.get("drain_timeout_s", 120.0))
    while True:
        now = time.monotonic() - t0
        if closed:
            while (nxt < len(reqs) and now < seconds
                   and live < plan["concurrency"]):
                reqs[nxt]["due_s"] = now
                start(nxt, now)
                nxt += 1
        else:
            while nxt < len(reqs) and reqs[nxt]["due_s"] <= now:
                start(nxt, now)
                nxt += 1
        sending_over = now >= seconds if closed else nxt >= len(reqs)
        if sending_over and live == 0:
            break
        if now > deadline:
            for key in list(sel.get_map().values()):
                finish(key.data, "timeout: not drained")
            break
        wait = 0.05
        if not closed and nxt < len(reqs):
            wait = max(0.0, min(wait, reqs[nxt]["due_s"] - now))
        for key, mask in sel.select(wait):
            c = key.data
            now = time.monotonic() - t0
            try:
                if mask & selectors.EVENT_WRITE:
                    err = c.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
                    if err:
                        finish(c, f"connect: {errno.errorcode.get(err, err)}")
                        continue
                    n = c.sock.send(c.out)
                    c.out = c.out[n:]
                    if not c.out:
                        sel.modify(c.sock, selectors.EVENT_READ, c)
                    continue
                data = c.sock.recv(1 << 16)
            except (BlockingIOError, InterruptedError):
                continue
            except OSError as e:
                finish(c, f"socket: {e}")
                continue
            if not data:
                finish(c, "" if c.done or c.status not in (0, 200)
                       else "closed before [DONE]")
                continue
            c.buf += data
            try:
                _parse(c, now)
            except (ValueError, KeyError, IndexError) as e:
                finish(c, f"unparsable stream: {e}")
                continue
            if c.done:
                finish(c)
    sent = [c for c in results if c is not None]
    return {
        "seconds": seconds, "t0_unix": t0_unix,
        "requests": [{
            "idx": c.idx, "due_s": c.req["due_s"], "sent_s": c.sent_s,
            "max_tokens": c.req["max_tokens"], "status": c.status,
            "chunk_s": c.chunk_s, "words": c.words, "finish": c.finish,
            "done": c.done, "error": c.error} for c in sent],
        "not_sent": len(reqs) - len(sent),
    }


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    out = run(plan)
    with open(sys.argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
