"""The load generator: a process of its own that never imports JAX.

    python3 benchmarks/harness/loadgen.py  < plan.json  > events.jsonl

It reads one JSON plan on stdin (see `schedule.py` and `serve_cell.py`),
sends the fill requests, waits until each has its first token, opens the
window, and then either sends the window's requests at their due times
(open loop: never waiting for a completion, clocking each from its DUE time)
or keeps `clients` requests outstanding (closed loop). It prints
`{"event": "open", "t_open": ...}` when the window opens and, after it
closes, `{"event": "done", "records": [...]}`. Times are `time.monotonic()`,
which on Linux is one clock for every process of the machine, so the
server's spans and these stamps can be set side by side.

Every request is a streaming POST to /v1/completions; a text delta of k
whitespace-separated ids is k tokens stamped with the frame's arrival time.
"""

from __future__ import annotations

import http.client
import json
import random
import socket
import sys
import threading
import time
from urllib.parse import urlparse


class Request:
    def __init__(self, rid: int, prompt_len: int, max_tokens: int, fill: bool,
                 due: float | None):
        self.rid, self.prompt_len, self.max_tokens = rid, prompt_len, max_tokens
        self.fill, self.due = fill, due
        self.sent: float | None = None
        self.tokens: list[float] = []
        self.done: float | None = None
        self.error: str | None = None
        self.first = threading.Event()
        self.finished = threading.Event()
        self.sock: socket.socket | None = None

    def record(self) -> dict:
        return {"id": self.rid, "fill": self.fill, "due": self.due,
                "sent": self.sent, "prompt_len": self.prompt_len,
                "max_tokens": self.max_tokens, "tokens": self.tokens,
                "done": self.done, "error": self.error}


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        u = urlparse(plan["url"])
        self.host, self.port, self.path = u.hostname, u.port, u.path
        self.vocab = int(plan["vocab"])
        self.seed = int(plan["seed"])
        self.requests: list[Request] = []
        self.closing = threading.Event()
        self.lock = threading.Lock()
        self.next_id = int(plan.get("first_id", 0))

    def new_request(self, item: dict, fill: bool, due: float | None) -> Request:
        with self.lock:
            r = Request(self.next_id, item["prompt_len"], item["max_tokens"],
                        fill, due)
            self.next_id += 1
            self.requests.append(r)
        return r

    def body(self, r: Request) -> bytes:
        # the first token is the request's id: every prompt differs from its
        # first token, so no block of it is found in the prefix cache, and the
        # server's spans can be matched to this record
        rng = random.Random(self.seed * 1000003 + r.rid)
        ids = [r.rid % self.vocab] + [rng.randrange(self.vocab)
                                      for _ in range(r.prompt_len - 1)]
        return json.dumps({"prompt": " ".join(map(str, ids)),
                           "max_tokens": r.max_tokens,
                           "stream": True}).encode()

    def send(self, r: Request) -> None:
        """Runs in the request's own thread until its stream ends."""
        body = self.body(r)
        try:
            conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
            conn.connect()
            r.sock = conn.sock
            r.sent = time.monotonic()
            conn.request("POST", self.path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}")
            for raw in resp:
                now = time.monotonic()
                if not raw.startswith(b"data:"):
                    continue
                data = raw[5:].strip()
                if data == b"[DONE]":
                    r.done = now
                    break
                frame = json.loads(data)
                if "error" in frame:
                    raise RuntimeError(str(frame["error"])[:200])
                k = len(frame["choices"][0].get("text", "").split())
                if k:
                    r.tokens.extend([now] * k)
                    r.first.set()
            else:
                if not self.closing.is_set():
                    raise RuntimeError("stream ended without [DONE]")
            conn.close()
        except Exception as e:  # noqa: BLE001 - recorded, counted as failed
            if not self.closing.is_set():
                r.error = f"{type(e).__name__}: {e}"[:200]
        finally:
            r.first.set()
            r.finished.set()

    def start(self, r: Request) -> threading.Thread:
        t = threading.Thread(target=self.send, args=(r,), daemon=True)
        t.start()
        return t

    def client(self, first: Request, sequence: list, cursor: list) -> None:
        """Closed loop: one client, its next request after the last ends."""
        r = first
        while True:
            self.send(r)
            if self.closing.is_set() or r.error:
                return
            with self.lock:
                if cursor[0] >= len(sequence):
                    return
                item = sequence[cursor[0]]
                cursor[0] += 1
            r = self.new_request(item, fill=False, due=None)

    def run(self) -> dict:
        plan = self.plan
        seconds = float(plan["seconds"])
        fill = [self.new_request(it, True, None) for it in plan["fill"]]
        if plan["mode"] == "closed":
            cursor = [0]
            threads = [threading.Thread(target=self.client,
                                        args=(r, plan["sequence"], cursor),
                                        daemon=True) for r in fill]
            for t in threads:
                t.start()
        else:
            threads = [self.start(r) for r in fill]
        for r in fill:
            if not r.first.wait(float(plan.get("fill_timeout_s", 300))):
                r.error = "no first token during the fill"
        t_open = time.monotonic() + 0.25
        print(json.dumps({"event": "open", "t_open": t_open,
                          "fill_errors": sum(1 for r in fill if r.error)}),
              flush=True)
        t_close = t_open + seconds
        if plan["mode"] == "open":
            for item in plan["window"]:
                due = t_open + item["due_s"]
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                threads.append(self.start(self.new_request(item, False, due)))
        delay = t_close - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        self.closing.set()
        with self.lock:
            live = [r for r in self.requests if not r.finished.is_set()]
        for r in live:  # wake the readers: the window is over
            try:
                if r.sock is not None:
                    r.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        for t in threads:
            t.join(10)
        with self.lock:
            records = [r.record() for r in self.requests]
        return {"event": "done", "t_open": t_open, "t_close": t_close,
                "records": records}


def main() -> int:
    plan = json.load(sys.stdin)
    out = Generator(plan).run()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
