"""JSON request bodies whose float arrays are parsed natively
(native/jsonnum.py, native/jsonnum.cpp): the decoded value is what
`json.loads` returns, type for type and bit for bit; malformed bodies
raise what it raises; counters `rpc_json_native_arrays` and
`rpc_json_native_fallbacks` and stage `body_decode` say what the server
did; a parser that cannot be built leaves `json.loads` serving and the
native memtable loaded."""

import base64
import importlib.util
import json
import os
import socket
import struct
import threading
import urllib.request

import numpy as np
import pytest

from surrealdb_tpu import Datastore, native
from surrealdb_tpu.kvs.native_mem import NativeMemBackend
from surrealdb_tpu.native import jsonnum
from surrealdb_tpu.server import make_server
from surrealdb_tpu.telemetry import stage_snapshot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 2**31 + 977


def _module(*path):
    spec = importlib.util.spec_from_file_location(
        "t_jsonnum_" + path[-1][:-3], os.path.join(BENCH, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bits(a):
    """`a` with every float replaced by its IEEE-754 bytes and every value
    tagged with its type, so that == compares type for type, bit for bit."""
    t = type(a)
    if t is float:
        return (t, struct.pack("<d", a))
    if t is list:
        if a and all(type(x) is float for x in a):
            return (t, struct.pack(f"<{len(a)}d", *a))
        return (t, [_bits(x) for x in a])
    if t is dict:
        return (t, [(k, _bits(v)) for k, v in a.items()])
    return (t, a)


def _same_as_json_loads(body: bytes):
    want = json.loads(body)
    got = jsonnum.loads(body)
    assert _bits(got) == _bits(want)
    return got


def _counters():
    return jsonnum.rpc_json_native_arrays(), jsonnum.rpc_json_native_fallbacks()


@pytest.fixture(autouse=True)
def _parser():
    assert jsonnum.load() is not None, "the native parser did not build"


# -- the seeded bodies of the benchmark's kinds, at rehearsal sizes ----------


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearsal"]}


def _knn_bodies():
    knn = _module("kinds", "knn.py")
    sz = _config("ann768")
    xs, rng = knn.clustered_rows(sz["rows"], sz["dim"], SEED)
    near = rng.integers(0, sz["rows"], 128)
    qs = knn.queries_near(xs, near, rng)
    return knn.Deployment(sz, xs, qs, near, None, {}).bodies()


def _scan_bodies():
    scan = _module("kinds", "scan.py")
    sz = _config("scan768")
    xs, rng = scan.clustered_rows(sz["rows"], sz["dim"], SEED)
    near = rng.integers(0, sz["rows"], 128)
    qs = scan.queries_near(xs, near, rng)
    return scan.Deployment(sz, xs, qs, near, None, {}).bodies()


def _knn_rw_bodies():
    rw = _module("kinds", "knn_rw.py")
    sz = _config("exact128rw")
    n, pool = sz["rows"], sz["pool"]
    writes = int(pool * sz["write_share"])
    xs, rng = rw.knn.clustered_rows(n + writes, sz["dim"], SEED)
    insert_at, _on_row = rw.lay_out_pool(pool, sz["clients"], writes, rng)
    ops = np.zeros(pool, np.int8)
    ops[insert_at] = rw.INSERT
    rows = rng.integers(0, n, pool)
    rows[insert_at] = n + np.arange(writes)
    pool_q = rw.knn.queries_near(xs, rows, rng)
    pool_q[insert_at] = xs[n:]
    return rw.Deployment(sz, xs, ops, rows, pool_q, {}).bodies()


@pytest.mark.parametrize("bodies", [_knn_bodies, _scan_bodies, _knn_rw_bodies],
                         ids=["knn", "scan", "knn_rw"])
def test_seeded_bodies_decode_bit_for_bit(bodies):
    bodies = bodies()
    arrays0, fallbacks0 = _counters()
    for body in bodies:
        _same_as_json_loads(body)
    # every body carries one vector, $q or an INSERT's $v
    assert _counters() == (arrays0 + len(bodies), fallbacks0)


@pytest.mark.parametrize("width", ["f32", "f64"])
def test_random_vectors_decode_bit_for_bit(width):
    rng = np.random.default_rng(SEED)
    arrays0, _f = _counters()
    for _ in range(48):
        n = int(rng.integers(16, 4097))
        v = rng.standard_normal(n) * 10.0 ** rng.integers(-300, 301, n)
        if width == "f32":
            with np.errstate(over="ignore"):
                v = v.astype(np.float32).astype(np.float64)
            v[~np.isfinite(v)] = 0.5
        body = json.dumps({"id": 1, "method": "query",
                           "params": ["RETURN $q", {"q": v.tolist()}]})
        _same_as_json_loads(body.encode())
    assert _counters()[0] == arrays0 + 48


def _array(lit: str, n: int = 16) -> bytes:
    return ("[" + ",".join([lit] * n) + "]").encode()


@pytest.mark.parametrize("lit,native", [
    ("-0.0", True), ("1E5", True), ("5e-324", True), ("1.5E+05", True),
    ("2.4703282292062328e-324", True), ("1.7976931348623157e308", True),
    ("0.1000000000000000055511151231257827021181583404541015625", True),
    ("1e400", False), ("-1e400", False), ("1e-400", False),
    ("1.7976931348623159e308", False), ("2.4703282292062327e-324", False),
])
def test_edge_literals(lit, native):
    """Out of range (inf, or a value that rounds to zero) takes the
    fallback: the array is refused and json.loads decodes it."""
    arrays0, fallbacks0 = _counters()
    _same_as_json_loads(_array(lit))
    if native:
        assert _counters() == (arrays0 + 1, fallbacks0)
    else:
        assert _counters() == (arrays0, fallbacks0 + 1)


F16 = ",".join(f"{i}.5" for i in range(16))
F15 = ",".join(f"{i}.5" for i in range(15))


@pytest.mark.parametrize("body,arrays", [
    ('{"q": [%s]}' % F16, 1),
    ('[%s]' % F16, 1),                                   # the whole body
    ('{"q": [%s, 3]}' % F16, 0),                         # an int: stays int
    ('{"q": [3, %s]}' % F16, 0),
    ('{"q": [%s, -0]}' % F16, 0),
    ('{"q": [%s, "1.5"]}' % F16, 0),
    ('{"q": [%s, null]}' % F16, 0),
    ('{"q": [[%s], [%s], 1]}' % (F16, F16), 2),          # nested
    ('{"q": [[[%s]]], "r": [[%s]]}' % (F16, F16), 2),
    ('{"q": [%s]}' % F15, 0),                            # 15 elements
    ('{"q": [], "r": [[]]}', 0),
    ('{"q": [%s]}' % F16.replace(",", " ,\n\t"), 1),     # whitespace
    ('{"q": [ \r\n%s\n ] }' % F16, 1),
    ('{"q": [%s]}' % ",".join(["NaN"] + F15.split(",")), 0),
    ('{"q": [%s]}' % ",".join(["Infinity"] + F15.split(",")), 0),
    ('{"q": [%s]}' % ",".join(["-Infinity"] + F15.split(",")), 0),
    ('{"s": "[%s]", "q": [%s]}' % (F16, F16), 1),        # inside a string
    ('{"s": "a \\" [%s] \\\\", "q": [%s]}' % (F16, F16), 1),
    ('{"s": "\\"[%s]\\"", "q": 1}' % F16, 0),
    ('{"s": "\\u0000sdbf0", "q": [%s]}' % F16, 0),       # the placeholder
    ('{"s": "\\u0000sdbf0"}', 0),
    ('{"s": "é ü 日本 \\ud83d\\ude00", "q": [%s], "t": "ß"}' % F16, 1),
    ('{"q": [%s], "q": 2}' % F16, 0),                    # the key twice
    ('{"q": 2, "q": [%s]}' % F16, 1),
    ('{"a": [%s], "b": {"c": [%s], "d": [1, 2]}}' % (F16, F16), 2),
])
def test_bodies_decode_as_json_loads_does(body, arrays):
    arrays0, _f = _counters()
    _same_as_json_loads(body.encode())
    assert _counters()[0] == arrays0 + arrays


@pytest.mark.parametrize("body", [
    '{"q": [%s]' % F16,                                  # no closing brace
    '{"q": [%s],}' % F16,
    '{"q": [%s,]}' % F16,                                # trailing comma
    '{[%s]: 1}' % F16,                                   # an array as a key
    '{"q": [%s]} x' % F16,
    '{"q": [%s] [%s]}' % (F16, F16),
    '{"q": [01.5, %s]}' % F16,
    '{"q": [1., %s]}' % F16,
    '{"q": [.5, %s]}' % F16,
    '{"q": [%s]' % F16 + ', "s": "\\u0000"',
    '',
    '{"q": "[%s]}' % F16,                                # an open string
])
def test_malformed_bodies_raise_what_json_loads_raises(body):
    raw = body.encode()
    with pytest.raises(json.JSONDecodeError) as want:
        json.loads(raw)
    with pytest.raises(json.JSONDecodeError) as got:
        jsonnum.loads(raw)
    assert (got.value.msg, got.value.pos) == (want.value.msg, want.value.pos)


def _outcome(loads, body):
    try:
        return "value", _bits(loads(body))
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        return type(e).__name__, str(e)


def test_mutated_bodies_decode_or_fail_as_json_loads_does():
    """Bytes flipped, dropped and inserted at random in a body with float
    arrays, strings with escapes and nested lists: the same value, or
    the same error at the same position."""
    rng = np.random.default_rng(SEED)
    floats = json.dumps(rng.uniform(-1e3, 1e3, 20).tolist())
    base = ('{"id": 1, "s": "a\\"b[1.5]\\\\", "q": %s, "n": [[%s], 1, "x"], '
            '"e": 1e5}' % (floats, floats)).encode()
    alphabet = b'[]{}",:\\ 0123456789.eE+-untrlfaNIy\n\x00\xc3\xa9'
    arrays0, _f = _counters()
    for _ in range(4000):
        body = bytearray(base)
        for _k in range(int(rng.integers(1, 5))):
            i = int(rng.integers(len(body)))
            op = rng.random()
            if op < 0.4:
                body[i] = alphabet[int(rng.integers(len(alphabet)))]
            elif op < 0.7:
                del body[i]
            else:
                body.insert(i, alphabet[int(rng.integers(len(alphabet)))])
        body = bytes(body)
        assert _outcome(jsonnum.loads, body) == _outcome(json.loads, body), body
    assert _counters()[0] > arrays0          # the native path was taken


def test_body_larger_than_a_threads_kept_buffers():
    v = np.random.default_rng(SEED).standard_normal(30_000)
    body = json.dumps({"q": v.tolist(), "r": v[:16].tolist()}).encode()
    assert jsonnum._KEEP_BODY < len(body) < jsonnum.MAX_BODY
    arrays0, fallbacks0 = _counters()
    _same_as_json_loads(body)
    assert _counters() == (arrays0 + 2, fallbacks0)


def test_body_above_the_cap_skips_the_pass():
    big = ('{"q": [%s], "pad": "%s"}'
           % (F16, "x" * jsonnum.MAX_BODY)).encode()
    arrays0, fallbacks0 = _counters()
    _same_as_json_loads(big)
    assert _counters() == (arrays0, fallbacks0 + 1)


# -- through the server -------------------------------------------------------


@pytest.fixture()
def server():
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield ds, srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def _vector_body(rid: int, n: int = 768) -> bytes:
    q = np.random.default_rng(SEED + rid).standard_normal(n, np.float32)
    return json.dumps({"id": rid, "method": "query",
                       "params": ["RETURN math::sum($q)",
                                  {"q": q.tolist()}]}).encode()


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method="POST", data=body,
        headers={"surreal-ns": "t", "surreal-db": "t"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def _stage_count(name):
    return (stage_snapshot().get(name) or {}).get("count", 0)


def _metric(ds, name):
    lines = [ln for ln in ds.telemetry.prometheus(ds).splitlines()
             if ln.startswith(f"surreal_{name}_total ")]
    assert len(lines) == 1, lines
    return int(lines[0].split()[1])


def test_one_native_array_per_768_float_rpc_request(server):
    ds, port = server
    arrays0 = ds.telemetry.get("rpc_json_native_arrays")
    fallbacks0 = ds.telemetry.get("rpc_json_native_fallbacks")
    decodes0 = _stage_count("body_decode")
    for rid in range(5):
        body = _vector_body(rid)
        want = sum(json.loads(body)["params"][1]["q"])
        out = _post(port, "/rpc", body)
        assert out["id"] == rid
        assert out["result"][0]["result"] == pytest.approx(want, rel=1e-12)
    assert ds.telemetry.get("rpc_json_native_arrays") == arrays0 + 5
    assert ds.telemetry.get("rpc_json_native_fallbacks") == fallbacks0
    assert _stage_count("body_decode") == decodes0 + 5
    assert _metric(ds, "rpc_json_native_arrays") == arrays0 + 5
    assert _metric(ds, "rpc_json_native_fallbacks") == fallbacks0
    # a bound integer bypasses the parser: no array, no fallback
    out = _post(port, "/rpc", json.dumps({
        "id": 9, "method": "query", "params": ["RETURN $i", {"i": 7}]}
    ).encode())
    assert out["result"][0]["result"] == 7
    assert _counters() == (arrays0 + 5, fallbacks0)


def _ws_frame(payload: bytes) -> bytes:
    mask = os.urandom(4)
    masked = bytes(c ^ mask[i % 4] for i, c in enumerate(payload))
    return b"\x81" + struct.pack("!BH", 0x80 | 126, len(payload)) \
        + mask + masked


def _ws_reply(f) -> dict:
    _b1, b2 = f.read(2)
    n = b2 & 0x7F
    if n == 126:
        n = struct.unpack("!H", f.read(2))[0]
    elif n == 127:
        n = struct.unpack("!Q", f.read(8))[0]
    return json.loads(f.read(n))


def test_websocket_frames_decode_natively(server):
    ds, port = server
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        key = base64.b64encode(os.urandom(16)).decode()
        s.sendall((f"GET /rpc HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
                   f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
                   f"Sec-WebSocket-Key: {key}\r\n"
                   f"Sec-WebSocket-Version: 13\r\n\r\n").encode())
        f = s.makefile("rb")
        while f.readline() not in (b"\r\n", b""):
            pass
        s.sendall(_ws_frame(json.dumps(
            {"id": 1, "method": "use", "params": ["t", "t"]}).encode()))
        assert _ws_reply(f)["id"] == 1
        arrays0, fallbacks0 = _counters()
        body = _vector_body(2, 128)
        s.sendall(_ws_frame(body))
        out = _ws_reply(f)
        assert out["id"] == 2 and out["result"][0]["result"] == pytest.approx(
            sum(json.loads(body)["params"][1]["q"]), rel=1e-12)
        assert _counters() == (arrays0 + 1, fallbacks0)
        s.sendall(_ws_frame(b'{"id": 3, "method": "query", "params": [1.5'))
        assert _ws_reply(f)["error"]["code"] == -32700


def test_a_parser_that_cannot_be_built_leaves_json_loads_and_the_memtable(
        server, monkeypatch, tmp_path, capfd):
    monkeypatch.setattr(jsonnum, "_SO", str(tmp_path / "_jsonnum.so"))
    monkeypatch.setattr(jsonnum, "_CXX", ("g++", "-fno-such-flag"))
    monkeypatch.setattr(jsonnum, "_fn", None)
    monkeypatch.setattr(jsonnum, "_tried", False)
    capfd.readouterr()
    assert jsonnum.load() is None
    assert jsonnum.load() is None
    err = capfd.readouterr().err
    assert err.count("native JSON float parser build failed") == 1
    assert "json.loads decodes request bodies alone" in err
    assert not os.path.exists(jsonnum._SO)
    # the memtable's library is another, and still loads and serves
    assert native.load() is not None
    assert isinstance(Datastore("memory").backend, NativeMemBackend)
    ds, port = server
    arrays0, fallbacks0 = _counters()
    body = _vector_body(11)
    out = _post(port, "/rpc", body)
    assert out["result"][0]["result"] == pytest.approx(
        sum(json.loads(body)["params"][1]["q"]), rel=1e-12)
    _same_as_json_loads(body)
    assert _counters() == (arrays0, fallbacks0)


# -- the benchmark's reader ---------------------------------------------------


def test_body_decode_reader():
    reader = _module("layers", "body_decode_us.py")
    window = {"requests": 1000, "stages": {
        "request": {"count": 1000, "total_us": 9e6},
        "body_decode": {"count": 1000, "total_us": 45000.0}}}
    assert reader.read(window) == pytest.approx(45.0)
    parent = {"requests": 1000, "stages": {
        "request": {"count": 1000, "total_us": 9e6}}}
    assert reader.read(parent) is None
    assert reader.read(dict(window, requests=0)) is None
