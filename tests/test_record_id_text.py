"""`RecordId.render()` keeps its text where the id is exactly an `int` or a
`str`, and only there: the text equals the expression that built it before,
an id that can change under it is rendered on every call, and however an
object is made (`copy`, `pickle`, the wire codecs) it renders, compares and
hashes as it did. Counter `rid_renders` counts the texts built."""

import copy
import json
import pickle

import pytest

from surrealdb_tpu import Datastore, fb, wire
from surrealdb_tpu.kvs.api import deserialize, deserialize_fresh, serialize
from surrealdb_tpu.val import (
    NONE, Range, RecordId, Uuid, copy_value, escape_rid_table, render,
    render_record_id_key, rid_renders, to_json,
)


def built_anew(rid) -> str:
    """What `render()` returned before it kept anything."""
    return f"{escape_rid_table(rid.tb)}:{render_record_id_key(rid.id)}"


KEPT = {
    "int": ("person", 7, "person:7"),
    "int_zero": ("person", 0, "person:0"),
    "int_negative": ("person", -12, "person:-12"),
    "int_large": ("person", 2 ** 63 - 1, "person:9223372036854775807"),
    "int_beyond_64_bits": ("person", 10 ** 30, "person:" + "1" + "0" * 30),
    "str_bare": ("person", "tobie", "person:tobie"),
    "str_digits_only": ("person", "123", "person:`123`"),
    "str_ulid_like": ("person", "01HZX3K9Q8R7V6T5S4P3N2M1A0",
                      "person:01HZX3K9Q8R7V6T5S4P3N2M1A0"),
    "str_leading_digit": ("person", "8abc", "person:8abc"),
    "str_needs_ticks": ("person", "needs-ticks", "person:`needs-ticks`"),
    "str_with_space": ("person", "a b", "person:`a b`"),
    "str_with_tick": ("person", "a`b", "person:`a\\`b`"),
    "str_with_backslash": ("person", "a\\b", "person:`a\\\\b`"),
    "str_empty": ("person", "", "person:``"),
    "str_unicode": ("person", "zoë", "person:`zoë`"),
    "table_escaped": ("my table", 1, "`my table`:1"),
    "table_with_tick": ("a`b", "x", "`a\\`b`:x"),
    "table_keyword_stays_bare": ("select", 1, "select:1"),
    "table_leading_digit": ("9lives", "x", "`9lives`:x"),
}

_UUID = "018f5a3e-7c1b-7a2e-9c1d-0123456789ab"

NOT_KEPT = {
    "list": lambda: RecordId("temp", ["London", 5]),
    "dict": lambda: RecordId("temp", {"city": "London"}),
    "range": lambda: RecordId("temp", Range(1, 5)),
    "uuid": lambda: RecordId("temp", Uuid(_UUID)),
    "bool_true": lambda: RecordId("temp", True),
    "bool_false": lambda: RecordId("temp", False),
    "float": lambda: RecordId("temp", 1.5),
    "none": lambda: RecordId("temp", NONE),
    "str_subclass": lambda: RecordId("temp", type("S", (str,), {})("x")),
    "int_subclass": lambda: RecordId("temp", type("I", (int,), {})(3)),
}


@pytest.mark.parametrize("name", sorted(KEPT))
def test_kept_text_is_the_text_built_anew(name):
    tb, key, want = KEPT[name]
    rid = RecordId(tb, key)
    assert rid._text is None
    assert rid.render() == want == built_anew(rid)
    assert rid._text == want
    assert rid.render() is rid._text          # served, not built again
    assert render(rid) == want and to_json(rid) == want
    assert repr(rid) == f"RecordId({want})"


@pytest.mark.parametrize("name", sorted(NOT_KEPT))
def test_other_ids_keep_no_text(name):
    rid = NOT_KEPT[name]()
    first = rid.render()
    assert first == built_anew(rid)
    assert rid._text is None
    assert rid.render() == first and rid._text is None
    assert to_json([rid]) == [first] and rid._text is None


def test_a_list_id_changed_in_place_shows_in_the_next_render():
    rid = RecordId("temp", ["London", 5])
    assert rid.render() == "temp:['London', 5]"
    rid.id.append(6)
    assert rid.render() == "temp:['London', 5, 6]"
    assert to_json([rid]) == ["temp:['London', 5, 6]"]
    nested = RecordId("temp", {"k": [1]})
    assert nested.render() == "temp:{ k: [1] }"
    nested.id["k"].append(2)
    assert nested.render() == "temp:{ k: [1, 2] }"


def _pickled(rid):
    return pickle.loads(pickle.dumps(rid, protocol=5))


def _pickled_before_the_slot(rid):
    """The bytes a tree without `_text` stored: the state names `tb` and
    `id` alone (an unset slot is left out of it)."""
    old = RecordId(rid.tb, rid.id)
    del old._text
    data = pickle.dumps(old, protocol=5)
    assert b"_text" not in data
    return pickle.loads(data)


def _stored(rid):
    return deserialize(serialize(rid))


def _stored_fresh(rid):
    return deserialize_fresh(serialize(rid))


def _pickle_framed(rid):
    return deserialize(b"\x00" + pickle.dumps(rid, protocol=5))


def _in_a_document(rid):
    return copy_value({"id": rid, "l": [rid]})["l"][0]


MAKERS = {
    "copy": copy.copy,
    "deepcopy": copy.deepcopy,
    "copy_value": _in_a_document,
    "pickle": _pickled,
    "pickle_before_the_slot": _pickled_before_the_slot,
    "pickle_framed_store": _pickle_framed,
    "serialize": _stored,
    "serialize_fresh": _stored_fresh,
    "wire": lambda rid: wire.decode(wire.encode(rid)),
    "fb": lambda rid: fb.decode(fb.encode(rid)),
}

MADE_FROM = {
    "int": lambda: RecordId("person", 7),
    "str": lambda: RecordId("person", "needs-ticks"),
    "list": lambda: RecordId("temp", ["London", 5]),
}


@pytest.mark.parametrize("rendered_first", [False, True],
                         ids=["fresh", "rendered"])
@pytest.mark.parametrize("kind", sorted(MADE_FROM))
@pytest.mark.parametrize("maker", sorted(MAKERS))
def test_however_it_is_made_it_renders_and_compares_equal(maker, kind,
                                                          rendered_first):
    rid = MADE_FROM[kind]()
    want = built_anew(rid)
    if rendered_first:
        assert rid.render() == want
    made = MAKERS[maker](rid)
    assert type(made) is RecordId
    assert made.render() == want and made.render() == want
    assert to_json([made, rid]) == [want, want]
    assert made == rid and hash(made) == hash(rid)
    assert {rid: 1}[made] == 1


def test_a_deep_copy_of_a_list_id_is_its_own():
    rid = RecordId("temp", ["London", 5])
    twin = copy.deepcopy(rid)
    twin.id.append(6)
    assert rid.render() == "temp:['London', 5]"
    assert twin.render() == "temp:['London', 5, 6]"
    assert rid != twin


def test_eq_and_hash_never_read_the_text():
    a, b = RecordId("person", 7), RecordId("person", 7)
    a.render()
    assert a == b and hash(a) == hash(b) and b._text is None
    assert RecordId("person", 7) != RecordId("person", "7")
    assert RecordId("person", 7) != RecordId("people", 7)
    assert RecordId("person", 1) != RecordId("person", True)
    # a text put there by hand changes neither
    a._text = "elsewhere:1"
    assert a == b and hash(a) == hash(b)


def test_rid_renders_counts_texts_built_not_texts_served():
    ids = [i * 7 % 500 for i in range(1000)]          # 500 distinct nodes
    pool = {i: RecordId("person", i) for i in set(ids)}
    reply = [{"status": "OK", "result": [[pool[i] for i in ids]]}]
    n0 = rid_renders()
    first = json.dumps(to_json(reply))
    assert rid_renders() - n0 == len(pool) == 500
    assert json.dumps(to_json(reply)) == first
    assert rid_renders() - n0 == 500                  # its repeat: none
    # an id that keeps no text is built, and counted, every time
    rid = RecordId("temp", [1])
    n1 = rid_renders()
    rid.render(), rid.render(), to_json([rid])
    assert rid_renders() - n1 == 3


def test_the_datastore_reports_rid_renders():
    ds = Datastore("memory")
    try:
        n0 = ds.telemetry.get("rid_renders")
        assert n0 == rid_renders()
        RecordId("person", 1).render()
        assert ds.telemetry.get("rid_renders") == n0 + 1
        line = [ln for ln in ds.telemetry.prometheus(ds).splitlines()
                if ln.startswith("surreal_rid_renders_total ")]
        assert line == [f"surreal_rid_renders_total {n0 + 1}"]
    finally:
        ds.close()
