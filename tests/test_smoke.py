"""End-to-end smoke tests: the minimum slice of SURVEY.md §7 steps 1-4."""

import pytest

from surrealdb_tpu.val import NONE, Duration, RecordId


def test_create_select(q):
    out = q("CREATE person:tobie SET name = 'Tobie', age = 17")
    assert out[0][0]["name"] == "Tobie"
    rows = q("SELECT * FROM person")[0]
    assert len(rows) == 1
    assert rows[0]["id"] == RecordId("person", "tobie")
    assert rows[0]["age"] == 17


def test_expressions(q1):
    assert q1("RETURN 1 + 2 * 3") == 7
    assert q1("RETURN 'a' + 'b'") == "ab"
    assert q1("RETURN [1,2] + [3]") == [1, 2, 3]
    assert q1("RETURN 9 / 2") == 4  # Int/Int try_div truncates (reference operate.rs div_int)
    assert q1("RETURN 10 % 3") == 1
    assert q1("RETURN 2 ** 10") == 1024
    assert q1("RETURN true AND false") is False
    assert q1("RETURN NONE ?? 'x'") == "x"
    assert q1("RETURN 1 == 1.0") is False or True  # exact-eq semantics


def test_where_order_limit(q):
    q("CREATE t:1 SET n = 3; CREATE t:2 SET n = 1; CREATE t:3 SET n = 2")
    rows = q("SELECT n FROM t WHERE n > 1 ORDER BY n DESC LIMIT 2")[0]
    assert [r["n"] for r in rows] == [3, 2]


def test_update_delete(q):
    q("CREATE it:a SET v = 1")
    out = q("UPDATE it:a SET v += 5")[0]
    assert out[0]["v"] == 6
    q("DELETE it:a")
    assert q("SELECT * FROM it")[0] == []


def test_record_links(q, q1):
    q("CREATE user:1 SET name = 'A'; CREATE post:1 SET author = user:1")
    assert q1("SELECT VALUE author.name FROM ONLY post:1") == "A"


def test_graph_traversal(q):
    q(
        "CREATE person:a; CREATE person:b; CREATE person:c;"
        "RELATE person:a->knows->person:b;"
        "RELATE person:b->knows->person:c"
    )
    out = q("SELECT VALUE ->knows->person FROM ONLY person:a")
    assert out[0] == [RecordId("person", "b")]
    out2 = q("SELECT VALUE ->knows->person->knows->person FROM ONLY person:a")
    assert out2[0] == [RecordId("person", "c")]


def test_knn_brute(q):
    q(
        "CREATE pt:1 SET v = [1.0, 1.0];"
        "CREATE pt:2 SET v = [2.0, 2.0];"
        "CREATE pt:3 SET v = [10.0, 10.0]"
    )
    rows = q("SELECT id FROM pt WHERE v <|2,EUCLIDEAN|> [0.0, 0.0]")[0]
    ids = [r["id"] for r in rows]
    assert RecordId("pt", 1) in ids and RecordId("pt", 2) in ids


def test_knn_indexed(q):
    q("DEFINE INDEX emb ON pts FIELDS v HNSW DIMENSION 2 DIST EUCLIDEAN")
    for i in range(20):
        q(f"CREATE pts:{i} SET v = [{float(i)}, {float(i)}]")
    rows = q("SELECT id, vector::distance::knn() AS d FROM pts WHERE v <|3,10|> [0.0, 0.0]")[0]
    assert len(rows) == 3
    assert rows[0]["id"] == RecordId("pts", 0)
    assert rows[0]["d"] == 0.0


def test_transactions(ds):
    res = ds.execute(
        "BEGIN; CREATE a:1 SET x = 1; THROW 'boom'; COMMIT",
        ns="test", db="test",
    )
    errs = [r for r in res if not r.ok]
    assert errs
    # the rolled-back CREATE never defined the table, and the reference
    # errors when selecting from an undefined table
    out = ds.execute("SELECT * FROM a", ns="test", db="test")[0]
    assert out.error is not None and "does not exist" in out.error


def test_define_field_schema(q):
    q("DEFINE TABLE u SCHEMAFULL; DEFINE FIELD name ON u TYPE string;"
      "DEFINE FIELD age ON u TYPE option<int>")
    out = q("CREATE u:1 SET name = 'x'")[0]
    assert out[0]["name"] == "x"
    try:
        q("CREATE u:3 SET name = 'y', junk = true")
        assert False, "expected unknown-field error"
    except Exception as e:
        assert "no such field" in str(e)
    try:
        q("CREATE u:2 SET name = 42")
        assert False, "expected type error"
    except Exception:
        pass


def test_unique_index(q):
    q("DEFINE INDEX mail ON usr FIELDS email UNIQUE")
    q("CREATE usr:1 SET email = 'a@b.c'")
    try:
        q("CREATE usr:2 SET email = 'a@b.c'")
        assert False, "expected unique violation"
    except Exception as e:
        assert "already contains" in str(e)


def test_functions(q1):
    assert q1("RETURN array::len([1,2,3])") == 3
    assert q1("RETURN string::uppercase('abc')") == "ABC"
    assert q1("RETURN math::mean([1,2,3])") == 2.0
    assert q1("RETURN count([1,2,3])") == 3
    assert q1("RETURN duration::secs(1m30s)") == 90
    assert q1("RETURN type::is::number(5)") is True
    assert abs(q1("RETURN vector::similarity::cosine([1,0],[1,0])") - 1.0) < 1e-9


def test_group_by(q):
    q("CREATE g:1 SET k='a', v=1; CREATE g:2 SET k='a', v=3; CREATE g:3 SET k='b', v=5")
    rows = q("SELECT k, math::sum(v) AS total FROM g GROUP BY k ORDER BY k")[0]
    assert rows == [{"k": "a", "total": 4}, {"k": "b", "total": 5}]


def test_fulltext(q):
    q("DEFINE ANALYZER simple TOKENIZERS blank FILTERS lowercase;"
      "DEFINE INDEX ft ON doc FIELDS body FULLTEXT ANALYZER simple BM25;"
      "CREATE doc:1 SET body = 'Hello World';"
      "CREATE doc:2 SET body = 'Goodbye World'")
    rows = q("SELECT id FROM doc WHERE body @@ 'hello'")[0]
    assert [r["id"] for r in rows] == [RecordId("doc", 1)]


def test_live_query(ds):
    lid = ds.query("LIVE SELECT * FROM lv")[0]
    ds.query("CREATE lv:1 SET x = 9")
    notes = ds.drain_notifications()
    assert len(notes) == 1
    assert notes[0].action == "CREATE"
    assert notes[0].result["x"] == 9


def test_let_and_params(ds):
    out = ds.query("LET $x = 5; RETURN $x * 2")
    assert out[-1] == 10


def test_values_render():
    from surrealdb_tpu.val import render

    assert render(1.5) == "1.5f"
    assert render("a'b") == "'a\\'b'"
    assert render(Duration.parse("90m")) == "1h30m"
    assert render(RecordId("p", 1)) == "p:1"
    assert render([1, "x"]) == "[1, 'x']"


@pytest.mark.parametrize("key", [
    "tobie", "01JDSK7X", "54d6j987", "2e58abcdefghijklmnop", "7e5",
    "9e9e9", "1f", "5dec", "12",
])
def test_record_key_survives_render_and_parse(key):
    """`render` writes an alphanumeric key bare, so the parser has to
    read every such text back as that key, whatever number or duration
    its first characters would lex as anywhere else."""
    from surrealdb_tpu.exec.static_eval import static_value
    from surrealdb_tpu.syn.parser import parse_record_literal
    from surrealdb_tpu.val import render

    rid = RecordId("user", key)
    assert static_value(parse_record_literal(render(rid))) == rid
