"""Live-query fan-out soak over real sockets: the harness behind the
socket tests of `tests/test_live_fanout.py`.

`live_soak` starts a server on a memory datastore, subscribes real
WebSocket sessions to one table, streams CREATEs from writer threads
and returns counts: commits returned, notifications delivered, order
violations, overflows, subscriptions left after every session hung up,
and what shows whether a consumer that never reads held a writer up
(where each notification was written to its socket, how deep the frozen
sessions' queues grew, whether every notification routed to them is
accounted for). It returns no time and no rate: a CPU speed proves
nothing about the fan-out (ROADMAP: speed is measured on the chip).
"""

from __future__ import annotations

import json
import selectors
import socket
import struct
import threading
import time


class _SoakWs:
    """Minimal RFC6455 json client for the soak: blocking handshake +
    rpc calls; notification collection happens externally through a
    shared selector loop reading `sock` via `feed()`."""

    def __init__(self, port, rcvbuf=None):
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf:
            self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                 rcvbuf)
        self.sock.settimeout(30)
        self.sock.connect(("127.0.0.1", port))
        key = "c29ha3Nlc3Npb25rZXk93d=="
        self.sock.sendall(
            (f"GET /rpc HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\n"
             f"Sec-WebSocket-Version: 13\r\n\r\n").encode())
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = self.sock.recv(4096)
            if not chunk:
                raise ConnectionError("handshake failed")
            resp += chunk
        self.buf = bytearray(resp.split(b"\r\n\r\n", 1)[1])
        self._id = 0

    def call(self, method, params):
        self._id += 1
        payload = json.dumps({"id": self._id, "method": method,
                              "params": params}).encode()
        mask = b"\x11\x22\x33\x44"
        masked = bytes(c ^ mask[i % 4] for i, c in enumerate(payload))
        n = len(payload)
        if n < 126:
            hdr = b"\x81" + bytes([0x80 | n])
        else:
            hdr = b"\x81" + struct.pack("!BH", 0x80 | 126, n)
        self.sock.sendall(hdr + mask + masked)
        while True:
            msg = self._read_msg()
            if msg.get("id") == self._id:
                return msg

    def _read_msg(self):
        while True:
            msgs = _soak_parse(self.buf)
            if msgs:
                if msgs[0] is None:  # server close frame
                    raise ConnectionError("closed by server")
                return msgs[0]
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("closed")
            self.buf += chunk

    def feed(self) -> list:
        """Non-blocking drain for the collector: recv once, return the
        complete messages parsed out of the buffer."""
        try:
            chunk = self.sock.recv(262144)
        except (BlockingIOError, InterruptedError):
            return []
        except OSError:
            return [None]  # connection gone
        if not chunk:
            return [None]
        self.buf += chunk
        return _soak_parse(self.buf, limit=0)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


def _soak_parse(buf: bytearray, limit: int = 1) -> list:
    """Parse complete server frames out of `buf` in place; returns
    decoded json messages (close frames decode to None)."""
    out = []
    while buf and (limit == 0 or len(out) < limit):
        if len(buf) < 2:
            break
        b1, b2 = buf[0], buf[1]
        n = b2 & 0x7F
        off = 2
        if n == 126:
            if len(buf) < 4:
                break
            n = struct.unpack_from("!H", buf, 2)[0]
            off = 4
        elif n == 127:
            if len(buf) < 10:
                break
            n = struct.unpack_from("!Q", buf, 2)[0]
            off = 10
        if len(buf) < off + n:
            break
        data = bytes(buf[off:off + n])
        del buf[:off + n]
        opcode = b1 & 0x0F
        if opcode == 0x8:
            out.append(None)
            break
        if opcode not in (0x1, 0x2):
            continue
        try:
            out.append(json.loads(data.decode()))
        except ValueError:
            continue
    return out


class _SendWatch:
    """Stands in an outbox's `send_batch`: counts the notifications
    handed to the socket and the batches handed over on a thread that
    commits writes. The spine's promise is that the second stays 0: a
    commit publishes and returns, and only the session's own writer
    thread ever waits for its consumer's TCP window."""

    def __init__(self, outbox, writer_threads: set):
        self.outbox = outbox
        self.send = outbox.send_batch
        self.writer_threads = writer_threads
        self.handed = 0
        self.on_writer_thread = 0
        outbox.send_batch = self

    def __call__(self, batch):
        self.handed += len(batch)
        if threading.get_ident() in self.writer_threads:
            self.on_writer_thread += 1
        return self.send(batch)

    def unaccounted(self, routed: int) -> int:
        """Notifications routed to this outbox that are neither handed
        to its socket, nor counted as dropped by a typed overflow, nor
        still queued (notify policy: each overflow queues one OVERFLOW
        note a bound live id, and those are routed notes too)."""
        ob = self.outbox
        with ob.lock:
            owed = routed + ob.overflows * len(ob.lids)
            return owed - (self.handed + ob.dropped + len(ob.q))


def live_soak(sessions=64, frozen=2, writers=4, writes=400,
              depth=None, reconnects=0, payload_pad=0, settle_s=8.0,
              commit_deadline_s=120.0):
    """The live-fanout soak: `sessions` real WebSocket sessions each
    holding one LIVE SELECT on a shared table, `writers` threads
    streaming CREATEs through the datastore, `frozen` sessions that
    never read their socket (tiny SO_RCVBUF so TCP backpressure bites),
    and an optional mid-stream reconnect storm. One collector thread
    drains every live socket through a selector (scales to thousands
    of sessions without a thread per client).

    A writer blocked by a frozen consumer stays blocked for good, so
    the writers are joined against `commit_deadline_s` and `commits`
    counts the CREATEs that returned: less than `writes` means a stall
    (or a failed write), whatever the load on the machine."""
    from surrealdb_tpu import Datastore, cnf
    from surrealdb_tpu.server import make_server

    old_depth = cnf.LIVE_QUEUE_DEPTH
    if depth is not None:
        cnf.LIVE_QUEUE_DEPTH = depth
    table = "soak"
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True,
                      max_inflight=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    pad = "x" * payload_pad if payload_pad else ""
    per_writer = writes // writers
    res: dict = {}
    try:
        ds.execute(f"DEFINE TABLE {table}", ns="s", db="s")

        # -- subscribe the fleet ----------------------------------------
        live, cold = [], []
        writer_threads: set = set()
        watches, frozen_watches = [], []
        for i in range(sessions):
            is_frozen = i < frozen
            c = _SoakWs(port, rcvbuf=4096 if is_frozen else None)
            c.call("use", ["s", "s"])
            out = c.call("live", [table])
            c.lid = out.get("result")
            c.si = i
            (cold if is_frozen else live).append(c)
            w = _SendWatch(ds.fanout._routes[str(c.lid)], writer_threads)
            watches.append(w)
            if is_frozen:
                frozen_watches.append(w)

        # per-phase base keeps `s` globally unique AND monotonic per
        # (phase, writer) stream: the order detector keys on
        # s // 1_000_000, so a later phase restarting at j=0 must not
        # compare against an earlier phase's high-water mark
        phase = [0]
        tally = {"commits": 0, "attempted": 0, "frozen_queue_max": 0}
        tally_lock = threading.Lock()

        def run_writes(tag, count):
            phase[0] += 1
            base = phase[0] * 100_000_000

            def w(wi):
                writer_threads.add(threading.get_ident())
                ok = deepest = 0
                for j in range(count // writers):
                    out = ds.execute(
                        f"CREATE {table}:{tag}{wi}x{j} SET s = $s, p = $p",
                        ns="s", db="s",
                        vars={"s": base + wi * 1_000_000 + j, "p": pad},
                    )
                    ok += out[-1].error is None
                    for fw in frozen_watches:
                        deepest = max(deepest, len(fw.outbox.q))
                with tally_lock:
                    tally["commits"] += ok
                    tally["frozen_queue_max"] = max(
                        tally["frozen_queue_max"], deepest)

            ts = [threading.Thread(target=w, args=(i,), daemon=True)
                  for i in range(writers)]
            tally["attempted"] += (count // writers) * writers
            end = time.monotonic() + commit_deadline_s
            for t in ts:
                t.start()
            for t in ts:
                t.join(max(end - time.monotonic(), 0.0))
            return sum(t.is_alive() for t in ts)

        stats = {"delivered": 0, "overflow": 0, "order_violations": 0,
                 "per_session": {}}
        stop = threading.Event()

        def collect():
            sel = selectors.DefaultSelector()
            for c in live:
                c.sock.setblocking(False)
                sel.register(c.sock, selectors.EVENT_READ, c)
            last_seq: dict = {}
            while not stop.is_set():
                for key, _ev in sel.select(timeout=0.2):
                    c = key.data
                    for msg in c.feed():
                        if msg is None:
                            try:
                                sel.unregister(c.sock)
                            except KeyError:
                                pass
                            break
                        if msg.get("id") is not None:
                            continue
                        note = msg.get("result") or {}
                        act = note.get("action")
                        if act == "OVERFLOW":
                            stats["overflow"] += 1
                            continue
                        if act == "ERROR":
                            continue
                        row = note.get("result") or {}
                        s = row.get("s")
                        prev = last_seq.get((c.si, s is not None
                                             and s // 1_000_000))
                        if prev is not None and s is not None \
                                and s <= prev:
                            stats["order_violations"] += 1
                        if s is not None:
                            last_seq[(c.si, s // 1_000_000)] = s
                        stats["delivered"] += 1
                        ps = stats["per_session"]
                        ps[c.si] = ps.get(c.si, 0) + 1

        col = threading.Thread(target=collect, daemon=True)
        col.start()

        # -- fan-out run: writes streaming into the subscribed fleet ----
        stalled = run_writes("f", writes)
        if reconnects and not stalled:
            # reconnect storm mid-stream: drop + resubscribe
            storm = live[:reconnects]
            for c in storm:
                c.close()
            stalled = run_writes("g", max(writes // 2, writers))
            for c in storm:
                nc = _SoakWs(port)
                nc.call("use", ["s", "s"])
                nc.call("live", [table])
                nc.close()
        # let deliveries settle, then stop collecting
        target = len(live) * per_writer * writers
        end = time.monotonic() + settle_s
        while time.monotonic() < end \
                and stats["delivered"] < target:
            time.sleep(0.05)
        stop.set()
        col.join(timeout=5)

        # every notification routed to a frozen session is on its way
        # to the socket, counted as dropped by a typed overflow, or
        # still queued. A batch the session's writer has popped and not
        # yet handed over is in neither for a moment, so poll. (Every
        # commit is routed: the tests' writes stay far under
        # LIVE_DISPATCH_BACKLOG, whose overflow would drop whole groups
        # before they reach an outbox.)
        unaccounted = 0
        if not stalled and ds.fanout.flush(10.0):
            end = time.monotonic() + 5.0
            while True:
                unaccounted = sum(abs(fw.unaccounted(tally["commits"]))
                                  for fw in frozen_watches)
                if not unaccounted or time.monotonic() >= end:
                    break
                time.sleep(0.02)
        elif frozen_watches:
            unaccounted = -1  # dispatch never drained: nothing to add up

        # disconnect-GC at scale: closing every session without KILL
        # must empty the subscription registry (the leak satellite)
        for c in live + cold:
            c.close()
        gc_end = time.monotonic() + 10.0
        while len(ds.live_queries) and time.monotonic() < gc_end:
            time.sleep(0.05)
        tel = ds.telemetry
        res = {
            "sessions": sessions,
            "frozen": frozen,
            "writes": tally["attempted"],
            "commits": tally["commits"],
            "writers_stalled": stalled,
            "sends_on_writer_threads": sum(
                w.on_writer_thread for w in watches),
            "frozen_queue_max": tally["frozen_queue_max"],
            "frozen_unaccounted": unaccounted,
            "delivered": stats["delivered"],
            "order_violations": stats["order_violations"],
            "overflow_notes": stats["overflow"],
            "overflows": tel.get("live_overflows"),
            "overflow_disconnects": tel.get("live_overflow_disconnects"),
            "notifications_dropped": tel.get("notifications_dropped"),
            "live_sessions_end": len(ds.live_queries),
            "per_session_complete": sum(
                1 for v in stats["per_session"].values()
                if v >= per_writer * writers
            ),
            "reconnects": reconnects,
        }
    finally:
        cnf.LIVE_QUEUE_DEPTH = old_depth
        srv.shutdown()
        ds.close()
    return res
