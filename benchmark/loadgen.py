"""Closed-loop load generator: one process, a few client threads.

Started by `run.py` as a child of its own, so that the clients' Python does
not share the server's interpreter lock. Imports nothing of the program and
nothing of the benchmark: plain `http.client` keep-alive connections and
`time.monotonic` (CLOCK_MONOTONIC, one clock for every process of the
machine, so the parent's window edges mean the same here).

Protocol, pickles with a length in front on stdin / stdout (only this
benchmark's own processes write them):
  parent -> {"port", "path", "headers", "threads", "bodies": [(index, bytes)]}
  parent -> {"begin": t, "end": t, "active": n, "each": m}
                                           one phase; repeated. The first
                                           `active` threads take part, each
                                           sends at most `each` requests
                                           (both optional: all, no cap)
  child  -> [(index, sent, received, status, reply bytes), ...]
  parent -> None                           leave

Each client thread walks its own share of the bodies in order: it sends one,
waits for the whole reply, records it, and sends the next while the clock is
before `end`. A request in flight at `end` is waited for and recorded; one
that fails on the wire is recorded with status -1 and the error's text.
"""

from __future__ import annotations

import http.client
import pickle
import struct
import sys
import threading
import time

REPLY_TIMEOUT_S = 120


def read_msg(f):
    head = f.read(8)
    if len(head) < 8:
        return None
    (n,) = struct.unpack("<Q", head)
    return pickle.loads(f.read(n))


def write_msg(f, obj):
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    f.write(struct.pack("<Q", len(data)))
    f.write(data)
    f.flush()


class Client(threading.Thread):
    def __init__(self, cfg, bodies):
        super().__init__(daemon=True)
        self.cfg = cfg
        self.bodies = bodies        # [(index, bytes)], this thread's share
        self.at = 0                 # next body; wraps when the share is spent
        self.conn = None
        self.phase = None
        self.go = threading.Event()
        self.done = threading.Event()
        self.records = []

    def _post(self, body):
        if self.conn is None:
            self.conn = http.client.HTTPConnection(
                "127.0.0.1", self.cfg["port"], timeout=REPLY_TIMEOUT_S)
        self.conn.request("POST", self.cfg["path"], body, self.cfg["headers"])
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def run(self):
        while True:
            self.go.wait()
            self.go.clear()
            if self.phase is None:
                if self.conn is not None:
                    self.conn.close()
                return
            begin, end, each = self.phase
            self.records = []
            delay = begin - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            while self.bodies and (each is None or len(self.records) < each):
                index, body = self.bodies[self.at % len(self.bodies)]
                sent = time.monotonic()
                if sent >= end:
                    break
                self.at += 1
                try:
                    status, reply = self._post(body)
                except (OSError, http.client.HTTPException) as e:
                    status, reply = -1, f"{e.__class__.__name__}: {e}".encode()
                    if self.conn is not None:
                        self.conn.close()
                    self.conn = None
                self.records.append(
                    (index, sent, time.monotonic(), status, reply))
            self.done.set()


def main() -> int:
    fin, fout = sys.stdin.buffer, sys.stdout.buffer
    cfg = read_msg(fin)
    n = cfg["threads"]
    clients = [Client(cfg, cfg["bodies"][j::n]) for j in range(n)]
    for c in clients:
        c.start()
    while True:
        phase = read_msg(fin)
        if phase is None:
            for c in clients:
                c.phase = None
                c.go.set()
            for c in clients:
                c.join(10)
            return 0
        active = clients[:phase.get("active", n)]
        for c in active:
            c.phase = (phase["begin"], phase["end"], phase.get("each"))
            c.done.clear()
            c.go.set()
        records = []
        for c in active:
            c.done.wait()
            records.extend(c.records)
        write_msg(fout, records)


if __name__ == "__main__":
    sys.exit(main())
