"""The HTTP request edge: a gated request runs on its connection's own
thread, ONE shared thread watches for clients that went away
(server/hangup.py), and a reply leaves in one `sendall`."""

import json
import re
import socket
import statistics
import subprocess
import threading
import time
from http.server import BaseHTTPRequestHandler

import pytest

from surrealdb_tpu import Datastore, inflight, wire
from surrealdb_tpu.server import make_server
from surrealdb_tpu.telemetry import Telemetry


@pytest.fixture()
def server():
    """2 slots and no queue: the third concurrent request sheds."""
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True,
                      max_inflight=2, queue_depth=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield ds, srv, srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def _request(port, path, body, headers=()):
    head = [f"POST {path} HTTP/1.1", f"Host: 127.0.0.1:{port}",
            "surreal-ns: t", "surreal-db: t",
            f"Content-Length: {len(body)}", *headers]
    return "\r\n".join(head).encode() + b"\r\n\r\n" + body


def _sql(port, sql, headers=()):
    return _request(port, "/sql", sql.encode(), headers)


def _read_reply(f):
    """(status, head bytes, body bytes) of one reply from a socket file."""
    head = b""
    while not head.endswith(b"\r\n\r\n"):
        line = f.readline()
        assert line, f"connection closed inside a reply head: {head!r}"
        head += line
    n = int(re.search(rb"Content-Length: (\d+)", head).group(1))
    return int(head.split()[1]), head, f.read(n)


def _until(cond, seconds=5.0):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.0005)
    return cond()


def _sleeping(ds):
    """The handle of the SLEEP now in flight, once it has registered."""
    found = []

    def look():
        with ds.inflight.lock:
            found[:] = [h for h in ds.inflight.queries.values()
                        if "SLEEP" in h.sql_head]
        return bool(found)

    assert _until(look), "the SLEEP never registered"
    return found[0]


def _idle(ds, srv):
    """No query registered, no slot held, nothing under the watch."""
    return (ds.inflight.count() == 0 and srv.admission.active == 0
            and not srv.hangups._running)


# -- one thread a request ----------------------------------------------------

def test_keepalive_requests_start_no_thread(server, monkeypatch):
    ds, srv, port = server
    started = []
    start = threading.Thread.start
    monkeypatch.setattr(
        threading.Thread, "start",
        lambda t: (started.append(t.name), start(t))[1])
    before = threading.active_count()
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s, \
            s.makefile("rb") as f:
        for i in range(40):
            s.sendall(_sql(port, f"RETURN {i}"))
            status, _head, body = _read_reply(f)
            assert status == 200 and json.loads(body)[0]["result"] == i
        # the connection's own thread, and nothing a request
        assert threading.active_count() == before + 1
    assert len(started) == 1 and "process_request_thread" in started[0]
    assert "surreal-query-worker" not in started
    assert _until(lambda: threading.active_count() == before)
    assert ds.telemetry.get("disconnect_cancels") == 0


# -- one write a reply -------------------------------------------------------

_DATE = rb"Date: \w{3}, \d{2} \w{3} \d{4} \d{2}:\d{2}:\d{2} GMT"


def _parent_head(status_line, *headers):
    """The head as send_response / send_header / end_headers write it:
    status line, Server, Date, then `headers` in order."""
    h = BaseHTTPRequestHandler
    server = f"Server: {h.server_version} {h.sys_version}".encode()
    lines = [status_line, server, _DATE, *headers]
    return b"\r\n".join(
        ln if ln is _DATE else re.escape(ln) for ln in lines
    ) + rb"\r\n\r\n"


_RPC = {"id": 1, "method": "query", "params": ["RETURN 1"]}
_JSON = b"Content-Type: application/json"
_REPLIES = {
    "sql": (lambda p: _sql(p, "RETURN 1"), b"HTTP/1.1 200 OK", [_JSON]),
    "rpc-json": (
        lambda p: _request(p, "/rpc", json.dumps(_RPC).encode()),
        b"HTTP/1.1 200 OK", [_JSON]),
    "rpc-cbor": (
        lambda p: _request(p, "/rpc", wire.encode(_RPC),
                           ["Content-Type: application/cbor"]),
        b"HTTP/1.1 200 OK", [b"Content-Type: application/cbor"]),
    # no body: one that a failed request leaves unread is parsed as the
    # next request line, and answered by the stdlib's own 400 page
    "400": (lambda p: _sql(p, "", ["X-Surreal-Timeout: soon"]),
            b"HTTP/1.1 400 Bad Request", [_JSON]),
    "404": (lambda p: _request(p, "/nowhere", b""),
            b"HTTP/1.1 404 Not Found", [_JSON]),
}


def _server_writes(monkeypatch, port):
    """Every `sendall` made on a server-side socket of `port`."""
    writes = []
    sendall = socket.socket.sendall

    def counted(sock, data, *flags):
        if sock.getsockname()[1] == port:
            writes.append(bytes(data))
        return sendall(sock, data, *flags)

    monkeypatch.setattr(socket.socket, "sendall", counted)
    return writes


@pytest.mark.parametrize("case", sorted(_REPLIES))
def test_reply_is_one_sendall_with_the_parents_head(server, monkeypatch,
                                                    case):
    _ds, _srv, port = server
    request, status_line, headers = _REPLIES[case]
    writes = _server_writes(monkeypatch, port)
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(request(port))
        _status, head, body = _read_reply(s.makefile("rb"))
    assert writes == [head + body], f"{len(writes)} writes for one reply"
    want = _parent_head(status_line, *headers,
                        b"Content-Length: %d" % len(body))
    assert re.fullmatch(want, head), head


def test_shed_reply_is_one_sendall_with_retry_after(server, monkeypatch):
    ds, srv, port = server
    holders = []
    for _ in range(2):  # both slots, no queue
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(_sql(port, "SLEEP 20s"))
        holders.append(s)
    assert _until(lambda: srv.admission.active == 2)
    writes = _server_writes(monkeypatch, port)
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(_sql(port, ""))  # no body, as the 400 above
        status, head, body = _read_reply(s.makefile("rb"))
    assert status == 503 and writes == [head + body]
    want = _parent_head(
        b"HTTP/1.1 503 Service Unavailable", _JSON, b"Retry-After: 1",
        b"Content-Length: %d" % len(body))
    assert re.fullmatch(want, head), head
    for s in holders:
        s.close()
    assert _until(lambda: _idle(ds, srv))


# -- the hang-up watch -------------------------------------------------------

def test_hangup_sets_the_cancel_flag_within_milliseconds(server):
    """The 50 ms poll is gone: the watcher wakes when the peer leaves."""
    ds, srv, port = server
    took = []
    for _ in range(5):
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(_sql(port, "SLEEP 20s"))
        handle = _sleeping(ds)
        t0 = time.monotonic()
        s.close()
        assert _until(handle.cancel.is_set, 2.0)
        took.append(time.monotonic() - t0)
        # SLEEP looks at the flag every 50 ms
        assert _until(lambda: _idle(ds, srv), 2.0)
    # a poll every 50 ms reads 25 ms here
    assert statistics.median(took) < 0.010, took
    assert ds.telemetry.get("disconnect_cancels") == 5
    assert "surreal_disconnect_cancels_total 5" in ds.telemetry.prometheus(ds)
    assert ds.telemetry.get("queries_killed") == 5


def test_half_close_cancels(server):
    ds, srv, port = server
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(_sql(port, "SLEEP 20s"))
        handle = _sleeping(ds)
        s.shutdown(socket.SHUT_WR)
        assert _until(handle.cancel.is_set, 2.0)
        status, _head, body = _read_reply(s.makefile("rb"))
    assert status == 200 and "cancelled" in json.loads(body)[0]["result"]
    assert ds.telemetry.get("disconnect_cancels") == 1
    assert _until(lambda: _idle(ds, srv))


def test_pipelined_request_neither_cancels_nor_is_lost(server):
    ds, _srv, port = server
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(_sql(port, "SLEEP 150ms; RETURN 1")
                  + _sql(port, "RETURN 2"))
        with s.makefile("rb") as f:
            first = json.loads(_read_reply(f)[2])
            second = json.loads(_read_reply(f)[2])
    assert [r["status"] for r in first] == ["OK", "OK"]
    assert first[1]["result"] == 1 and second[0]["result"] == 2
    assert ds.telemetry.get("disconnect_cancels") == 0
    assert ds.telemetry.get("queries_killed") == 0


def test_requests_buffered_behind_a_hangup_are_cancelled_too(server):
    """The peer left while request 1 ran; request 2 is already in the
    server's buffer and must not run to its end for nobody."""
    ds, srv, port = server
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(_sql(port, "SLEEP 20s") + _sql(port, "SLEEP 19s"))
    _sleeping(ds)
    s.close()
    assert _until(lambda: ds.telemetry.get("disconnect_cancels") == 2)
    assert _until(lambda: _idle(ds, srv))


def test_cancelled_connections_number_serves_a_stranger(server,
                                                        monkeypatch):
    """After a cancelled request its descriptor has left the watch: a
    new connection that gets the same number runs a query to its end."""
    ds, srv, port = server
    joined = []
    join = srv.hangups.join
    monkeypatch.setattr(srv.hangups, "join",
                        lambda fd: (joined.append(fd), join(fd))[1])
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(_sql(port, "SLEEP 20s"))
    _sleeping(ds)
    s.close()
    before = threading.active_count()
    assert _until(lambda: _idle(ds, srv)
                  and threading.active_count() < before)
    assert not srv.hangups._gone
    for _ in range(3):  # the lowest free number is the next one given
        with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
            s.sendall(_sql(port, "SLEEP 120ms; RETURN 7"))
            rows = json.loads(_read_reply(s.makefile("rb"))[2])
        assert rows[1] == {"status": "OK", "result": 7,
                           "time": rows[1]["time"]}
        time.sleep(0.02)
    assert joined[0] in joined[1:], joined
    assert ds.telemetry.get("disconnect_cancels") == 1


def test_tls_connection_joins_the_watch(tmp_path):
    import ssl

    crt, key = str(tmp_path / "c.pem"), str(tmp_path / "k.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", crt, "-days", "1", "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True,
                      tls_cert=crt, tls_key=key)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        sctx = ssl.create_default_context()
        sctx.check_hostname = False
        sctx.verify_mode = ssl.CERT_NONE
        raw = socket.create_connection(("127.0.0.1", port), timeout=5)
        s = sctx.wrap_socket(raw)
        s.sendall(_sql(port, "RETURN 3"))
        assert json.loads(_read_reply(s.makefile("rb"))[2])[0]["result"] == 3
        s.sendall(_sql(port, "SLEEP 20s"))
        handle = _sleeping(ds)
        s.close()
        assert _until(handle.cancel.is_set, 2.0)
        assert _until(lambda: _idle(ds, srv))
        assert ds.telemetry.get("disconnect_cancels") == 1
    finally:
        srv.shutdown()
        srv.server_close()


# -- a reused thread keeps nothing of a request ------------------------------

def test_raising_handler_leaves_nothing_on_the_thread(server, monkeypatch):
    ds, srv, port = server
    seen = []
    handler = srv.RequestHandlerClass
    gated = handler._dispatch_gated

    def recorded(self, fn):
        seen.append((threading.get_ident(), inflight.current(),
                     list(getattr(ds.telemetry._local, "stack", ()))))
        return gated(self, fn)

    monkeypatch.setattr(handler, "_dispatch_gated", recorded)
    execute = ds.execute

    def failing(sql, *a, **kw):
        if "boom" in sql:
            raise RuntimeError("boom")
        return execute(sql, *a, **kw)

    monkeypatch.setattr(ds, "execute", failing)
    monkeypatch.setattr(srv, "handle_error", lambda *a: None)
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s, \
            s.makefile("rb") as f:
        # raised inside the handler, mapped to a 401: the thread lives on
        s.sendall(_sql(port, "", ["Authorization: Bearer x.y.z"]))
        assert _read_reply(f)[0] == 401
        assert _idle(ds, srv)
        s.sendall(_sql(port, "RETURN 2"))
        assert json.loads(_read_reply(f)[2])[0]["result"] == 2
        # one nothing maps: it ends the connection, and frees the rest
        s.sendall(_sql(port, "RETURN 'boom'"))
        assert f.readline() == b""
    assert _until(lambda: _idle(ds, srv))
    assert len({ident for ident, _h, _s in seen}) == 1
    assert [(h, stack) for _i, h, stack in seen] == [(None, [])] * 3


def test_ending_a_span_drops_the_children_left_open():
    """A connection thread outlives its requests: a span that was never
    ended must not stay under every later query of that thread."""
    tel = Telemetry()
    root = tel.start("query")
    tel.start("statement")  # never ended
    tel.end(root)
    assert tel._local.stack == []
    assert [s.name for s in tel.traces] == ["query"]


def test_server_close_stops_the_watcher_once():
    def watchers():
        return {t for t in threading.enumerate()
                if t.name == "surreal-hangup-watch"}

    # by identity, not by count: the watcher of the test before may
    # still be on its way out while this server starts its own
    before = watchers()
    srv = make_server(Datastore("memory"), "127.0.0.1", 0,
                      unauthenticated=True)
    mine = watchers() - before
    assert len(mine) == 1
    srv.server_close()
    srv.server_close()  # the stop descriptor is written once
    assert _until(lambda: not mine & watchers())
