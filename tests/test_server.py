"""Server surface tests: HTTP routes, REST /key CRUD, RPC over HTTP and
WebSocket (raw-socket RFC6455 client), live-query push, export/import,
GraphQL (reference test tiers 4-5: api_integration + http/ws black-box)."""

import base64
import hashlib
import json
import os
import re
import socket
import struct
import threading
import time
import urllib.request

import pytest

from surrealdb_tpu import Datastore
from surrealdb_tpu.server import make_server
from surrealdb_tpu.telemetry import stage_snapshot


@pytest.fixture(scope="module")
def server():
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield ds, f"http://127.0.0.1:{port}", port
    srv.shutdown()


@pytest.fixture(scope="module")
def secure_server():
    ds = Datastore("memory")
    ds.execute("DEFINE USER root ON ROOT PASSWORD 'r00t' ROLES OWNER")
    srv = make_server(ds, "127.0.0.1", 0)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield ds, f"http://127.0.0.1:{port}", port
    srv.shutdown()


def _req(url, method="GET", body=None, headers=None):
    req = urllib.request.Request(url, method=method, data=body)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, r.read()


def test_health_version(server):
    _ds, base, _port = server
    assert _req(base + "/health")[0] == 200
    assert b"surrealdb-tpu" in _req(base + "/version")[1]


def test_sql_route(server):
    _ds, base, _port = server
    hdrs = {"surreal-ns": "t", "surreal-db": "t"}
    status, body = _req(base + "/sql", "POST", b"CREATE srv:1 SET x = 1; SELECT * FROM srv", hdrs)
    assert status == 200
    out = json.loads(body)
    assert out[0]["status"] == "OK"
    assert out[1]["result"][0]["x"] == 1


def test_key_rest(server):
    _ds, base, _port = server
    hdrs = {"surreal-ns": "t", "surreal-db": "t", "Content-Type": "application/json"}
    s, b = _req(base + "/key/widget/a", "POST", json.dumps({"n": 5}).encode(), hdrs)
    assert s == 200 and json.loads(b)[0]["result"][0]["n"] == 5
    s, b = _req(base + "/key/widget/a", "PATCH", json.dumps({"m": 6}).encode(), hdrs)
    assert json.loads(b)[0]["result"][0]["m"] == 6
    s, b = _req(base + "/key/widget", "GET", None, hdrs)
    assert len(json.loads(b)[0]["result"]) == 1
    s, b = _req(base + "/key/widget/a", "DELETE", None, hdrs)
    assert json.loads(b)[0]["result"][0]["n"] == 5
    s, b = _req(base + "/key/widget", "GET", None, hdrs)
    assert json.loads(b)[0]["result"] == []


def test_http_rpc(server):
    _ds, base, _port = server
    body = json.dumps({"id": 1, "method": "query",
                       "params": ["RETURN 40 + 2"]}).encode()
    s, b = _req(base + "/rpc", "POST", body,
                {"surreal-ns": "t", "surreal-db": "t"})
    out = json.loads(b)
    assert out["result"][0]["result"] == 42


def _stage(name):
    st = stage_snapshot().get(name) or {}
    return st.get("count", 0), st.get("total_ms", 0.0)


def _stages_after(before, requests):
    """What `requests` replies added to `request` and `reply_encode`
    (`request` closes a moment after its client has the reply)."""
    end = time.monotonic() + 5
    while (_stage("request")[0] < before["request"][0] + requests
           and time.monotonic() < end):
        time.sleep(0.005)
    return {k: tuple(a - b for a, b in zip(_stage(k), v))
            for k, v in before.items()}


_IDS_SQL = ("SELECT VALUE ->follows->account->follows->account "
            "FROM type::record('account', $i)")
_IDS_BODY = ('[{"status": "OK", "result": '
             '[["account:3", "account:`a b`", "account:3"]], "time": "T"}]')


@pytest.mark.parametrize("route", ["rpc", "sql", "ws"])
def test_reply_of_record_ids_is_encoded_once_inside_request(server, route):
    ds, base, port = server
    ns = f"enc_{route}"
    hdrs = {"surreal-ns": ns, "surreal-db": ns}
    ds.execute(
        "DEFINE TABLE follows TYPE RELATION; "
        "CREATE account:1, account:2, account:3, account:`a b`; "
        "RELATE account:1->follows:1->account:2; "
        "RELATE account:1->follows:2->account:`a b`; "
        "RELATE account:2->follows:3->account:3; "
        "RELATE account:2->follows:4->account:`a b`; "
        "RELATE account:`a b`->follows:5->account:3",
        ns=ns, db=ns)
    before = {k: _stage(k) for k in ("request", "reply_encode")}
    requests = 1
    if route == "rpc":
        body = json.dumps({"id": 7, "method": "query",
                           "params": [_IDS_SQL, {"i": 1}]}).encode()
        _s, raw = _req(base + "/rpc", "POST", body, hdrs)
        want = '{"id": 7, "result": ' + _IDS_BODY + '}'
    elif route == "sql":
        _s, raw = _req(base + "/sql", "POST",
                       _IDS_SQL.replace("$i", "1").encode(), hdrs)
        want = _IDS_BODY
    else:
        ws = WsClient(port)
        try:
            ws.call("use", [ns, ns])               # a request of its own
            raw = json.dumps(
                ws.call("query", [_IDS_SQL, {"i": 1}])).encode()
        finally:
            ws.close()
        requests = 2
        want = '{"id": 2, "result": ' + _IDS_BODY + '}'
    # the reply's bytes are what they were before the ids kept their text
    assert re.sub(r'"time": "[0-9.]+ms"', '"time": "T"', raw.decode()) == want
    added = _stages_after(before, requests)
    count, wall_ms = added["reply_encode"]
    assert count == requests and wall_ms > 0
    req_count, req_wall_ms = added["request"]
    assert req_count == requests and wall_ms <= req_wall_ms


class WsClient:
    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        key = base64.b64encode(os.urandom(16)).decode()
        self.sock.sendall(
            (f"GET /rpc HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\nSec-WebSocket-Version: 13\r\n\r\n"
             ).encode())
        resp = b""
        while b"\r\n\r\n" not in resp:
            resp += self.sock.recv(4096)
        assert b"101" in resp.split(b"\r\n")[0]
        self._id = 0

    def send(self, method, params):
        self._id += 1
        payload = json.dumps({"id": self._id, "method": method,
                              "params": params}).encode()
        mask = os.urandom(4)
        masked = bytes(c ^ mask[i % 4] for i, c in enumerate(payload))
        n = len(payload)
        if n < 126:
            hdr = b"\x81" + struct.pack("!B", 0x80 | n)
        else:
            hdr = b"\x81" + struct.pack("!BH", 0x80 | 126, n)
        self.sock.sendall(hdr + mask + masked)
        return self._id

    def recv(self):
        def read(n):
            out = b""
            while len(out) < n:
                chunk = self.sock.recv(n - len(out))
                if not chunk:
                    raise ConnectionError("closed")
                out += chunk
            return out

        b1, b2 = read(2)
        n = b2 & 0x7F
        if n == 126:
            n = struct.unpack("!H", read(2))[0]
        elif n == 127:
            n = struct.unpack("!Q", read(8))[0]
        data = read(n)
        return json.loads(data.decode())

    def call(self, method, params):
        rid = self.send(method, params)
        while True:
            msg = self.recv()
            if msg.get("id") == rid:
                return msg

    def close(self):
        self.sock.close()


def test_ws_rpc_and_live(server):
    _ds, _base, port = server
    ws = WsClient(port)
    try:
        assert ws.call("use", ["t", "t"]).get("error") is None
        out = ws.call("query", ["CREATE wst:1 SET v = 7; SELECT * FROM wst:1"])
        assert out["result"][1]["result"][0]["v"] == 7
        assert ws.call("select", ["wst:1"])["result"][0]["v"] == 7
        assert ws.call("create", ["wst:2", {"v": 9}])["result"][0]["v"] == 9
        assert ws.call("merge", ["wst:2", {"w": 1}])["result"][0]["w"] == 1
        assert ws.call("delete", ["wst:2"])["result"][0]["v"] == 9
        # live query: notification pushed over the same socket
        live = ws.call("live", ["wst"])
        lid = live["result"]
        ws.send("query", ["CREATE wst:3 SET v = 3"])
        got_note = None
        for _ in range(10):
            msg = ws.recv()
            if "result" in msg and isinstance(msg["result"], dict) and \
                    msg["result"].get("action"):
                got_note = msg["result"]
                break
        assert got_note is not None
        assert got_note["action"] == "CREATE"
        assert got_note["id"] == lid if "id" in got_note else True
        assert got_note["result"]["v"] == 3
    finally:
        ws.close()


def test_export_import(server):
    ds, base, _port = server
    hdrs = {"surreal-ns": "exp", "surreal-db": "exp"}
    _req(base + "/sql", "POST",
         b"DEFINE TABLE item SCHEMALESS; CREATE item:1 SET n = 1; CREATE item:2 SET n = 2",
         hdrs)
    s, text = _req(base + "/export", "GET", None, hdrs)
    assert s == 200
    assert b"DEFINE TABLE item" in text and b"INSERT [" in text
    # import into a fresh db
    hdrs2 = {"surreal-ns": "exp2", "surreal-db": "exp2"}
    s, b = _req(base + "/import", "POST", text, hdrs2)
    assert s == 200
    s, b = _req(base + "/sql", "POST", b"SELECT count() FROM item GROUP ALL", hdrs2)
    assert json.loads(b)[0]["result"][0]["count"] == 2


def test_signin_root_user(server):
    ds, base, _port = server
    ds.execute("DEFINE USER admin ON ROOT PASSWORD 'secret' ROLES OWNER")
    body = json.dumps({"user": "admin", "pass": "secret"}).encode()
    s, b = _req(base + "/signin", "POST", body)
    assert s == 200
    token = json.loads(b)["token"]
    assert token.count(".") == 2
    # bad password
    body = json.dumps({"user": "admin", "pass": "wrong"}).encode()
    try:
        s, b = _req(base + "/signin", "POST", body)
        assert False, "expected 401"
    except urllib.error.HTTPError as e:
        assert e.code == 401


def test_graphql(server):
    _ds, base, _port = server
    hdrs = {"surreal-ns": "t", "surreal-db": "t"}
    _req(base + "/sql", "POST", b"CREATE gq:1 SET name = 'x', n = 1", hdrs)
    body = json.dumps({"query": "{ gq { name n } }"}).encode()
    s, b = _req(base + "/graphql", "POST", body, hdrs)
    out = json.loads(b)
    assert out["data"]["gq"][0]["name"] == "x"


def test_secure_anonymous_denied(secure_server):
    """Anonymous sessions on a secured server get no grants (ADVICE:
    unauthenticated clients must not default to owner)."""
    _ds, base, _port = secure_server
    hdrs = {"surreal-ns": "t", "surreal-db": "t"}
    s, b = _req(base + "/sql", "POST", b"CREATE locked:1 SET x = 1", hdrs)
    out = json.loads(b)
    assert out[0]["status"] == "ERR"
    # nothing was written
    out = _ds.execute("SELECT * FROM locked", ns="t", db="t")[0]
    # nothing was written — the table was never created
    assert out.result in ([], None) or (
        out.error is not None and "does not exist" in out.error
    )


def test_secure_token_and_basic_auth(secure_server):
    _ds, base, _port = secure_server
    hdrs = {"surreal-ns": "t", "surreal-db": "t"}
    # signin → bearer token works
    body = json.dumps({"user": "root", "pass": "r00t"}).encode()
    s, b = _req(base + "/signin", "POST", body)
    token = json.loads(b)["token"]
    auth_hdrs = dict(hdrs, Authorization=f"Bearer {token}")
    s, b = _req(base + "/sql", "POST", b"CREATE sec:1 SET x = 2", auth_hdrs)
    assert json.loads(b)[0]["status"] == "OK"
    # basic auth works too
    import base64 as b64
    basic = b64.b64encode(b"root:r00t").decode()
    basic_hdrs = dict(hdrs, Authorization=f"Basic {basic}")
    s, b = _req(base + "/sql", "POST", b"SELECT * FROM sec", basic_hdrs)
    out = json.loads(b)
    assert out[0]["status"] == "OK" and out[0]["result"][0]["x"] == 2
    # wrong basic credentials get nothing
    bad = b64.b64encode(b"root:nope").decode()
    bad_hdrs = dict(hdrs, Authorization=f"Basic {bad}")
    s, b = _req(base + "/sql", "POST", b"SELECT * FROM sec", bad_hdrs)
    out = json.loads(b)[0]
    # failed basic auth falls back to an anonymous session: rows are
    # permission-filtered away (reference returns empty, not an error)
    assert out["result"] in ([], None) or out["status"] == "ERR"


def test_key_route_injection_blocked(server):
    """Path segments are bound as parameters, not spliced into SurrealQL."""
    from urllib.parse import quote

    _ds, base, _port = server
    hdrs = {"surreal-ns": "t", "surreal-db": "t",
            "Content-Type": "application/json"}
    s, b = _req(base + "/key/safekey/one", "POST",
                json.dumps({"v": 1}).encode(), hdrs)
    assert s == 200 and json.loads(b)[0]["status"] == "OK"
    # a crafted "table" segment must not execute as extra statements
    evil = quote("safekey; REMOVE TABLE safekey", safe="")
    s, b = _req(base + f"/key/{evil}", "GET", None, hdrs)
    assert s == 200
    s, b = _req(base + "/key/safekey", "GET", None, hdrs)
    assert json.loads(b)[0]["result"][0]["v"] == 1


def test_define_api_served(server):
    """DEFINE API endpoints are served at /api/:ns/:db/<path>."""
    _ds, base, _port = server
    hdrs = {"surreal-ns": "t", "surreal-db": "t"}
    _req(base + "/sql", "POST",
         b'DEFINE API "/hello" FOR get THEN { RETURN { status: 200, body: { msg: "hi" } } };'
         b'DEFINE API "/item/:id" FOR get THEN { RETURN { body: $request.params.id } };',
         hdrs)
    s, b = _req(base + "/api/t/t/hello", "GET", None, hdrs)
    assert s == 200 and json.loads(b)["msg"] == "hi"
    s, b = _req(base + "/api/t/t/item/42", "GET", None, hdrs)
    # string bodies are written raw as text/plain (serialized bodies come
    # from api::res::body middleware)
    assert s == 200 and b == b"42"


def test_tls_server(tmp_path):
    """HTTPS serving via --web-crt/--web-key equivalents (reference ntw
    rustls config)."""
    import ssl
    import subprocess
    import threading
    import urllib.request

    crt, key = str(tmp_path / "c.pem"), str(tmp_path / "k.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", crt, "-days", "1", "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )
    from surrealdb_tpu import Datastore
    from surrealdb_tpu.server import make_server

    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 18441, unauthenticated=True,
                      tls_cert=crt, tls_key=key)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        sctx = ssl.create_default_context()
        sctx.check_hostname = False
        sctx.verify_mode = ssl.CERT_NONE
        body = urllib.request.urlopen(
            "https://127.0.0.1:18441/version", context=sctx
        ).read()
        assert b"surrealdb-tpu" in body
    finally:
        srv.shutdown()
