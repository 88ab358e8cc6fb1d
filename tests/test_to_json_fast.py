"""`val.to_json` answers the exact types of a reply before its `isinstance`
ladder. Held here to the ladder as it stood before that (a copy, with the
record id's text built anew): equal values of the same types for every type
the ladder names, their nestings and subclasses, and the same JSON bytes."""

import base64
import datetime
import enum
import json
import math
import uuid
from decimal import Decimal

import pytest

from surrealdb_tpu import val
from surrealdb_tpu.val import (
    NONE, Closure, Datetime, Duration, File, Geometry, Range, RecordId,
    Regex, SSet, Table, Uuid, to_json,
)


def ladder(v):
    """`to_json` before the exact-type dispatch."""
    if v is NONE:
        return None
    if v is None:
        return None
    if isinstance(v, (bool, int, str)):
        return v
    if isinstance(v, float):
        return v
    if isinstance(v, Decimal):
        return str(v)
    if isinstance(v, Duration):
        return v.render()
    if isinstance(v, Datetime):
        return v.render()
    if isinstance(v, Uuid):
        return str(v.u)
    if isinstance(v, list):
        return [ladder(x) for x in v]
    if isinstance(v, SSet):
        return [ladder(x) for x in v.items]
    if isinstance(v, dict):
        return {k: ladder(x) for k, x in v.items()}
    if isinstance(v, Geometry):
        return ladder(v.to_object())
    if isinstance(v, (bytes, bytearray)):
        return base64.b64encode(bytes(v)).decode()
    if isinstance(v, RecordId):
        return (f"{val.escape_rid_table(v.tb)}:"
                f"{val.render_record_id_key(v.id)}")
    if isinstance(v, Table):
        return v.name
    if isinstance(v, (Range, Regex, File)):
        return v.render()
    if isinstance(v, Closure):
        return None
    raise TypeError(f"cannot jsonify {type(v)!r}")


def same(a, b) -> bool:
    """Equal, of the same types all the way down; NaN equals NaN."""
    if type(a) is not type(b):
        return False
    if isinstance(a, list):
        return len(a) == len(b) and all(map(same, a, b))
    if isinstance(a, dict):
        return (list(a) == list(b)
                and all(same(a[k], b[k]) for k in a))
    if isinstance(a, float) and math.isnan(a):
        return math.isnan(b)
    return a == b


class Colour(enum.IntEnum):
    RED = 1
    GREEN = 2


class Row(dict):
    pass


class Rows(list):
    pass


class Name(str):
    pass


class Real(float):
    pass


_UUID = uuid.UUID("018f5a3e-7c1b-7a2e-9c1d-0123456789ab")
_WHEN = datetime.datetime(2024, 5, 6, 7, 8, 9, 123456,
                          tzinfo=datetime.timezone.utc)

SCALARS = {
    "NONE": NONE,
    "null": None,
    "true": True,
    "false": False,
    "int": 7,
    "int_negative": -3,
    "int_big": 2 ** 70,
    "str": "plain",
    "str_empty": "",
    "str_unicode": "naïve ✓",
    "float": 1.5,
    "float_whole": 2.0,
    "float_nan": float("nan"),
    "float_inf": float("inf"),
    "float_neg_inf": float("-inf"),
    "decimal": Decimal("12.340"),
    "duration": Duration(90_000_000_000),
    "datetime": Datetime(_WHEN),
    "uuid": Uuid(_UUID),
    "bytes": b"\x00\x01binary",
    "bytearray": bytearray(b"abc"),
    "rid_int": RecordId("person", 42),
    "rid_str": RecordId("person", "tobie"),
    "rid_escaped": RecordId("my table", "needs-ticks"),
    "rid_list": RecordId("temp", ["London", 5]),
    "rid_dict": RecordId("temp", {"city": "London"}),
    "rid_uuid": RecordId("temp", Uuid(_UUID)),
    "rid_range": RecordId("temp", Range(1, 5)),
    "rid_bool": RecordId("temp", True),
    "table": Table("person"),
    "range": Range(1, 10),
    "range_inclusive": Range(1, 10, end_incl=True),
    "range_open": Range(NONE, 3),
    "regex": Regex("a+b"),
    "file": File("bucket", "/some/key.txt"),
    "closure": Closure([("a", None)], None),
    "point": Geometry("Point", (1.0, 2.0)),
    "line": Geometry("LineString", ((1.0, 2.0), (3.0, 4.0))),
    "collection": Geometry("GeometryCollection",
                           [Geometry("Point", (1.0, 2.0))]),
    "sset": SSet([3, 1, 2]),
    "sset_of_rids": SSet([RecordId("a", 2), RecordId("a", 1)]),
    "int_enum": Colour.GREEN,
    "str_subclass": Name("sub"),
    "float_subclass": Real(2.5),
}

NESTED = {
    "list_empty": [],
    "dict_empty": {},
    "list_of_rids": [RecordId("person", i) for i in range(5)],
    "list_of_list_of_rids": [[RecordId("person", i) for i in range(3)]],
    "list_mixed_rids": [RecordId("a", 1), RecordId("a", [1]), "a:1", 1,
                        RecordId("a", "x y"), None, NONE],
    "list_of_bools": [True, False, 1, 0, 1.0],
    "list_of_floats": [0.5, float("nan"), float("inf"), -0.0],
    "list_of_scalars": ["a", 1, 2.5, None, NONE, Decimal("1.0")],
    "knn_rows": [{"id": RecordId("vec128", i), "d": 0.25 * i}
                 for i in range(10)],
    "document": {"id": RecordId("user", "u1"), "name": "x", "age": 3,
                 "ok": True, "score": 1.5, "none": NONE, "null": None,
                 "tags": ["a", "b", 3], "addr": {"city": "y", "zip": 1},
                 "when": Datetime(_WHEN), "took": Duration(5),
                 "friends": [RecordId("user", "u2"), RecordId("user", 3)],
                 "blob": b"\xff", "where": Geometry("Point", (0.0, 1.0))},
    "statements": [{"status": "OK", "time": "0.100ms",
                    "result": [[RecordId("person", 1),
                                RecordId("person", 1)]]},
                   {"status": "ERR", "time": "0.001ms",
                    "result": "An error occurred"}],
    "dict_subclass": Row(id=RecordId("a", 1), n=Colour.RED),
    "list_subclass": Rows([RecordId("a", 1), True, [Rows([1])]]),
    "subclasses_inside": [Row(a=1), Rows([2]), Colour.RED, Name("n"),
                          {"k": Rows([Row(b=Real(1.0))])}],
    "sset_nested": {"s": SSet([SSet([1]), [RecordId("a", 1)]])},
    "tuple_coords_via_geometry": [Geometry(
        "Polygon", (((0.0, 0.0), (1.0, 0.0), (1.0, 1.0), (0.0, 0.0)),))],
    "deep": [[[[[{"a": [{"b": [RecordId("deep", 1)]}]}]]]]],
}

CASES = {**SCALARS, **NESTED}


@pytest.mark.parametrize("name", sorted(CASES))
def test_to_json_equals_the_ladder(name):
    value = CASES[name]
    got, want = to_json(value), ladder(value)
    assert same(got, want), (got, want)
    # a second conversion serves the kept texts: still the ladder's answer
    assert same(to_json(value), want)
    assert json.dumps(got) == json.dumps(want)


@pytest.mark.parametrize("name", sorted(SCALARS))
def test_to_json_inside_a_list_and_a_dict_equals_the_ladder(name):
    value = SCALARS[name]
    for wrapped in ([value], {"k": value}, [[value, {"k": [value]}]]):
        assert same(to_json(wrapped), ladder(wrapped))


@pytest.mark.parametrize("ids", [
    list(range(1000)),
    [f"u{i}" for i in range(1000)],
    [i * 7919 % 250_000 for i in range(1000)],   # repeats: a bag of path ends
], ids=["int", "str", "bag"])
def test_a_thousand_id_reply_has_the_ladders_bytes(ids):
    pool = {i: RecordId("person", i) for i in set(ids)}
    reply = {"id": 0, "result": [{"status": "OK", "time": "1.000ms",
                                  "result": [[pool[i] for i in ids]]}]}
    want = json.dumps(ladder(reply))
    assert json.dumps(to_json(reply)) == want
    assert json.dumps(to_json(reply)) == want      # from the kept texts
    assert len(want) > 9000


@pytest.mark.parametrize("value", [
    object(), (1, 2), {1, 2}, 1j, [object()], {"k": (1,)},
    [RecordId("a", 1), object()],
], ids=["object", "tuple", "set", "complex", "in_list", "in_dict",
        "after_a_rid"])
def test_unknown_type_still_raises(value):
    with pytest.raises(TypeError, match="cannot jsonify"):
        to_json(value)
    with pytest.raises(TypeError, match="cannot jsonify"):
        ladder(value)


def test_bool_stays_bool_and_enum_stays_enum():
    out = to_json([True, Colour.RED, {"b": False}])
    assert out[0] is True and out[2]["b"] is False
    assert type(out[1]) is Colour
    assert json.dumps(out) == '[true, 1, {"b": false}]'
