"""CBOR wire format + storage encoding round-trips (reference
core/src/rpc/format/cbor tag dialect; VERDICT round-2 item 8)."""

from decimal import Decimal

import pytest

from surrealdb_tpu import wire
from surrealdb_tpu.val import (
    NONE, Datetime, Duration, File, Geometry, Range, RecordId, SSet,
    Table, Uuid,
)


def _rt(v):
    return wire.decode(wire.encode(v))


def test_scalars_roundtrip():
    for v in (NONE, None, True, False, 0, 42, -7, 2**40, 1.5, float("inf"),
              "hello", "", b"\x00\xff", Decimal("1.25")):
        got = _rt(v)
        assert type(got) is type(v) or v is NONE
        assert got == v or (v is NONE and got is NONE)


def test_value_types_roundtrip():
    vals = [
        Datetime.parse("2025-01-02T03:04:05.123456789Z"),
        Duration.parse("1w2d3h4m5s6ms7ns"),
        Uuid("018e7a26-5b30-7b3b-8000-000000000000"),
        RecordId("person", "tobie"),
        RecordId("t", 42),
        RecordId("t", ["a", 1]),
        Table("person"),
        File("bucket", "/a.txt"),
        SSet([1, 2, 3]),
        Range(1, 10, True, False),
        Geometry("Point", (1.0, 2.0)),
        Geometry("Polygon", (((0.0, 0.0), (1.0, 0.0), (1.0, 1.0),
                              (0.0, 0.0)),)),
        Geometry("GeometryCollection", [Geometry("Point", (3.0, 4.0))]),
    ]
    for v in vals:
        assert _rt(v) == v, v


def test_nested_roundtrip():
    v = {"a": [1, {"b": RecordId("x", 1), "c": NONE}],
         "d": Duration.parse("5m"), "e": [True, None, 1.5]}
    got = _rt(v)
    assert got["a"][1]["b"] == RecordId("x", 1)
    assert got["a"][1]["c"] is NONE
    assert got["d"] == Duration.parse("5m")


def test_storage_encoding_no_pickle_for_values():
    """Stored records use the self-describing CBOR encoding (header 0x01),
    not pickle."""
    from surrealdb_tpu.kvs.api import deserialize, serialize

    doc = {"id": RecordId("t", 1), "n": 1, "s": "x",
           "when": Datetime.parse("2025-01-01T00:00:00Z")}
    raw = serialize(doc)
    assert raw[:1] == b"\x01"
    assert deserialize(raw) == doc
    # legacy headerless pickle still reads
    import pickle

    assert deserialize(pickle.dumps({"k": 1})) == {"k": 1}


def test_http_rpc_cbor():
    import threading
    import urllib.request

    from surrealdb_tpu import Datastore
    from surrealdb_tpu.server import make_server

    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        body = wire.encode({"id": 1, "method": "query",
                            "params": ["RETURN 40 + 2"]})
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/rpc", method="POST", data=body,
            headers={"Content-Type": "application/cbor",
                     "Accept": "application/cbor"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers.get("Content-Type") == "application/cbor"
            out = wire.decode(r.read())
        assert out["result"][0]["result"] == 42
    finally:
        srv.shutdown()


# -- a stored vector decodes as one run of f64s (PR 31) ----------------------


def _slow_decode(data: bytes):
    """The item-by-item decoder, with the run's fast path switched off."""
    from surrealdb_tpu import wire

    old = wire._F64_RUN_MIN
    wire._F64_RUN_MIN = 1 << 62
    try:
        return wire.decode(data)
    finally:
        wire._F64_RUN_MIN = old


@pytest.mark.parametrize("value", [
    [0.5] * 7,                                   # under the run's floor
    [0.5] * 8,
    [float(i) / 3 for i in range(768)],          # an embedding
    [1.5, float("inf"), float("-inf"), -0.0, 5e-324, 1.7976931348623157e308,
     0.1, 0.2, 0.3],
    [0.5] * 8 + [2],                             # an int ends the run: no run
    [0.5] * 8 + [None],
    list(range(12)),
    [[0.25] * 9, [0.75] * 9, "x"],               # runs inside an array
    {"id": 7, "emb": [0.125] * 16, "tags": ["a", "b"], "n": {"v": [2.5] * 8}},
])
def test_f64_run_decodes_as_item_by_item(value):
    from surrealdb_tpu import wire

    data = wire.encode(value)
    fast, slow = wire.decode(data), _slow_decode(data)
    assert repr(fast) == repr(slow) == repr(value)   # repr: -0.0 and types


def test_f64_run_keeps_nan_and_refuses_truncation():
    import math

    from surrealdb_tpu import wire
    from surrealdb_tpu.err import SdbError

    data = wire.encode([float("nan")] * 4 + [1.0] * 6)
    out = wire.decode(data)
    assert all(math.isnan(x) for x in out[:4]) and out[4:] == [1.0] * 6
    for cut in (3, 20, len(data) - 1):
        with pytest.raises(SdbError, match="truncated"):
            wire.decode(data[:cut])


# -- a decode outside the decode cache (PR 32) -------------------------------


def _framed(framing: str) -> bytes:
    import pickle

    from surrealdb_tpu.catalog import TableDef
    from surrealdb_tpu.kvs.api import serialize

    if framing == "wire":
        raw = serialize({"id": RecordId("t", 32), "emb": [0.25] * 16,
                         "tags": ["a", {"n": NONE}]})
        assert raw[:1] == b"\x01"
    elif framing == "pickle":
        raw = serialize(TableDef(name="t32"))     # carries no wire encoding
        assert raw[:1] == b"\x00"
    else:
        raw = pickle.dumps({"k": [1, 2.5, "x"]})  # legacy: no header byte
        assert raw[:1] == b"\x80"
    return raw


@pytest.mark.parametrize("framing", ["wire", "pickle", "headerless"])
def test_deserialize_fresh_equals_deserialize_and_shares_nothing(framing):
    from surrealdb_tpu.kvs import api

    raw = _framed(framing)
    api.deserialize(raw)            # a wire-framed value is in the cache now
    assert (raw in api._dec_cache) == (framing == "wire")
    cached, charged = dict(api._dec_cache), api._dec_cache_bytes
    a, b = api.deserialize_fresh(raw), api.deserialize_fresh(raw)
    assert a == b == api.deserialize(raw)
    assert repr(a) == repr(api.deserialize(raw))
    assert a is not b
    if framing == "wire":
        pristine = api._dec_cache[raw]
        assert a is not pristine and a["emb"] is not pristine["emb"]
        assert a["tags"][1] is not pristine["tags"][1]
        a["emb"].append("mine")     # the caller's to mutate
        a["tags"][1]["n"] = 1
        assert api.deserialize(raw) == b == api.deserialize_fresh(raw)
    assert api._dec_cache_bytes == charged
    assert api._dec_cache.keys() == cached.keys()
    assert all(api._dec_cache[k] is v for k, v in cached.items())


def test_deserialize_fresh_never_fills_the_cache():
    from surrealdb_tpu.kvs import api
    from surrealdb_tpu.kvs.api import serialize

    raw = serialize({"never": "seen", "emb": [0.5] * 32})
    cached, charged = len(api._dec_cache), api._dec_cache_bytes
    assert api.deserialize_fresh(raw) == {"never": "seen", "emb": [0.5] * 32}
    assert raw not in api._dec_cache
    assert (len(api._dec_cache), api._dec_cache_bytes) == (cached, charged)


@pytest.mark.parametrize("header", [b"\x00", b""])
def test_deserialize_fresh_refuses_a_disallowed_global(header):
    import pickle

    from surrealdb_tpu.kvs import api

    raw = header + pickle.dumps(pickle.PickleBuffer)   # a global by name
    for decode in (api.deserialize, api.deserialize_fresh):
        with pytest.raises(pickle.UnpicklingError, match="disallowed type"):
            decode(raw)
