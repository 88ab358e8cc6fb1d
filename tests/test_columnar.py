"""Columnar/scalar equivalence property suite (PR 14).

Every statement runs through BOTH executors — the columnar push
executor (vectorized predicates, hash aggregation, column store) and
the row-at-a-time interpreter (planner_strategy=compute-only with
SURREAL_COLUMNAR=off) — and the rendered answers must be identical:
null/NONE handling, mixed-type columns, exotic values (NaN, >2^53
ints, Decimals, nested objects), and the scalar-fallback boundary
included. Randomized statements come from a seeded grammar so failures
reproduce."""

from __future__ import annotations

import random

import numpy as np
import pytest

from surrealdb_tpu import Datastore, cnf
from surrealdb_tpu.kvs.ds import Session
from surrealdb_tpu.val import render


@pytest.fixture(autouse=True)
def _restore_columnar():
    prev = cnf.COLUMNAR
    yield
    cnf.COLUMNAR = prev


def _both(ds, sql, vars=None):
    """(columnar_rendered, interpreter_rendered) for one statement —
    errors render as `error:<text>` so error parity is asserted too."""

    def _run():
        r = ds.execute(sql, ns="t", db="t", vars=vars or {})[-1]
        return f"error:{r.error}" if r.error is not None \
            else render(r.result)

    def _run_interp():
        sess = Session(ns="t", db="t", auth_level="owner")
        sess.planner_strategy = "compute-only"
        r = ds.execute(sql, session=sess, vars=vars or {})[-1]
        return f"error:{r.error}" if r.error is not None \
            else render(r.result)

    cnf.COLUMNAR = "auto"
    col = _run()
    cnf.COLUMNAR = "off"
    try:
        interp = _run_interp()
    finally:
        cnf.COLUMNAR = "auto"
    return col, interp


def _assert_same(ds, sql, vars=None):
    a, b = _both(ds, sql, vars)
    assert a == b, f"columnar diverged on {sql!r}:\n  col:    {a}\n  interp: {b}"
    return a


@pytest.fixture(scope="module")
def ds():
    d = Datastore("memory")
    d.query("DEFINE TABLE rows", ns="t", db="t")
    rng = random.Random(1405)
    stmts = []
    cats = ["a", "b", "c", "d", ""]
    for i in range(400):
        sets = [f"i = {rng.randint(-50, 50)}"]
        if rng.random() < 0.9:
            sets.append(f"f = {round(rng.uniform(-10, 10), 4)}")
        if rng.random() < 0.8:
            sets.append(f's = "{rng.choice(cats)}"')
        if rng.random() < 0.5:
            sets.append(f"b = {str(rng.random() < 0.5).lower()}")
        # mixed-type column: int / float / string / bool / NULL / array
        r = rng.random()
        if r < 0.2:
            sets.append(f"m = {rng.randint(0, 5)}")
        elif r < 0.4:
            sets.append(f"m = {round(rng.uniform(0, 5), 2)}")
        elif r < 0.55:
            sets.append(f'm = "x{rng.randint(0, 3)}"')
        elif r < 0.65:
            sets.append("m = NULL")
        elif r < 0.75:
            sets.append("m = [1, 2]")
        # exotic values that must route through the scalar fallback
        if rng.random() < 0.05:
            sets.append(f"big = {2**60 + i}")
        if rng.random() < 0.05:
            sets.append("d = 3.14dec")
        if rng.random() < 0.3:
            sets.append(f"o = {{ x: {rng.randint(0, 9)} }}")
        stmts.append(f"CREATE rows:{i} SET " + ", ".join(sets))
    d.query("; ".join(stmts), ns="t", db="t")
    return d


# ---------------------------------------------------------------------------
# randomized statement grammar
# ---------------------------------------------------------------------------

_FIELDS = ["i", "f", "s", "b", "m", "big", "o.x"]
_NUM_CONSTS = ["0", "7", "-3", "2.5", "-0.5", "100"]
_STR_CONSTS = ['"a"', '"c"', '""', '"zz"']


def _rand_pred(rng, depth=0):
    r = rng.random()
    if depth < 2 and r < 0.25:
        op = rng.choice(["AND", "OR"])
        return (f"({_rand_pred(rng, depth + 1)} {op} "
                f"{_rand_pred(rng, depth + 1)})")
    if r < 0.35:
        return f"{rng.choice(_FIELDS)} IN [1, 2.5, \"a\", true]"
    lhs = rng.choice(_FIELDS)
    op = rng.choice(["<", "<=", ">", ">=", "=", "!="])
    if rng.random() < 0.5:
        rhs = rng.choice(_NUM_CONSTS + _STR_CONSTS + ["NONE", "NULL",
                                                      "true"])
    else:
        rhs = rng.choice(_FIELDS)
    return f"{lhs} {op} {rhs}"


def _rand_select(rng):
    parts = []
    if rng.random() < 0.5:
        group = rng.sample(["i", "s", "b", "m"], rng.randint(1, 2))
        aggs = rng.sample([
            "count() AS c", "math::sum(i) AS si", "math::sum(f) AS sf",
            "math::mean(f) AS mf", "count(f > 0) AS cp",
        ], rng.randint(1, 3))
        parts.append("SELECT " + ", ".join(group + aggs) + " FROM rows")
        where = f" WHERE {_rand_pred(rng)}" if rng.random() < 0.7 else ""
        parts.append(where)
        parts.append(" GROUP BY " + ", ".join(group))
        if rng.random() < 0.4:
            parts.append(f" ORDER BY {group[0]} "
                         + rng.choice(["ASC", "DESC"]))
            if rng.random() < 0.6:
                parts.append(f" LIMIT {rng.randint(1, 6)}")
                if rng.random() < 0.4:
                    parts.append(f" START {rng.randint(0, 3)}")
    else:
        proj = rng.choice([
            "*", "i, f", "i, i * 2 AS d", "s, i + f AS x",
            "i, i > 0 AS pos",
        ])
        parts.append(f"SELECT {proj} FROM rows")
        if rng.random() < 0.8:
            parts.append(f" WHERE {_rand_pred(rng)}")
        if rng.random() < 0.5:
            key = rng.choice(["i", "f", "s", "id"])
            parts.append(f" ORDER BY {key} "
                         + rng.choice(["ASC", "DESC"]))
            if rng.random() < 0.7:
                parts.append(f" LIMIT {rng.randint(1, 20)}")
                if rng.random() < 0.4:
                    parts.append(f" START {rng.randint(0, 5)}")
    return "".join(parts)


def test_randomized_equivalence(ds):
    rng = random.Random(77)
    for _ in range(120):
        sql = _rand_select(rng)
        _assert_same(ds, sql)


def test_null_none_handling(ds):
    for sql in [
        "SELECT i FROM rows WHERE m = NULL",
        "SELECT i FROM rows WHERE m = NONE",
        "SELECT i FROM rows WHERE m != NONE ORDER BY i LIMIT 7",
        "SELECT i FROM rows WHERE f < 0 OR f = NONE",
        "SELECT m, count() AS c FROM rows GROUP BY m",
        "SELECT b, count() AS c FROM rows GROUP BY b",
    ]:
        _assert_same(ds, sql)


def test_mixed_type_and_exotic_columns(ds):
    # m mixes int/float/str/bool/NULL/arrays; big exceeds 2^53;
    # d is a Decimal — every comparison must agree with the scalar path
    for sql in [
        "SELECT i, m FROM rows WHERE m > 1",
        "SELECT i FROM rows WHERE m < \"x1\"",
        "SELECT i FROM rows WHERE big > 0",
        "SELECT i FROM rows WHERE d = 3.14dec",
        "SELECT m, count() AS c FROM rows WHERE m != NONE GROUP BY m",
    ]:
        _assert_same(ds, sql)


def test_aggregate_coverage(ds):
    for sql in [
        "SELECT s, math::min(i) AS mn, math::max(i) AS mx FROM rows "
        "WHERE i != NONE GROUP BY s",
        "SELECT s, math::sum(i * 2) AS si FROM rows GROUP BY s",
        "SELECT s, f FROM rows WHERE f > 0 GROUP BY s, f LIMIT 10",
        "SELECT VALUE count() FROM rows GROUP BY s",
        "SELECT s, array::group(i) AS gi FROM rows WHERE i > 40 "
        "GROUP BY s",
        # implicit collect of a non-aggregate projection
        "SELECT s, i FROM rows WHERE i > 45 GROUP BY s",
        # a float mean is the left-to-right sum over the count on both
        # executors: the builtin `sum` compensates since Python 3.12 and
        # differs from that fold in the last place on these rows
        "SELECT s, count() AS c, math::mean(f) AS mf, count(f > 0) AS cp "
        "FROM rows GROUP BY s",
        # the shapes the conformance gate's analytics smoke diffed:
        # filtered sum + mean, min/max under ORDER BY .. LIMIT, and a
        # product summed over two keys behind an IN filter
        "SELECT s, count() AS c, math::sum(i) AS units, math::mean(f) "
        "AS avg FROM rows WHERE f < 5 AND i > -20 GROUP BY s",
        "SELECT s, count() AS c, math::min(i) AS lo, math::max(i) AS hi "
        "FROM rows WHERE i != NONE GROUP BY s ORDER BY c DESC LIMIT 3",
        "SELECT s, b, math::sum(f * i) AS rev FROM rows "
        "WHERE s IN ['a', 'b'] GROUP BY s, b",
    ]:
        _assert_same(ds, sql)


def test_min_max_error_parity(ds):
    # math::min over a column with missing values errors identically
    sql = "SELECT s, math::min(f) AS mn FROM rows GROUP BY s"
    cnf.COLUMNAR = "auto"
    r_col = ds.execute(sql, ns="t", db="t")[-1]
    sess = Session(ns="t", db="t", auth_level="owner")
    sess.planner_strategy = "compute-only"
    cnf.COLUMNAR = "off"
    try:
        r_interp = ds.execute(sql, session=sess)[-1]
    finally:
        cnf.COLUMNAR = "auto"
    assert (r_col.error is None) == (r_interp.error is None)
    if r_col.error is not None:
        assert r_col.error == r_interp.error


def test_scalar_fallback_boundary(ds):
    """Statements the kernels cannot serve end-to-end must still answer
    identically (per-row / per-expression fallback)."""
    for sql in [
        # regex comparison: compile-time rejection
        "SELECT i FROM rows WHERE s = /a/",
        # string concat arithmetic: exotic rows
        "SELECT i FROM rows WHERE i + 1 > 2 AND m != NONE",
        # division corner cases incl. int/int and by-zero
        "SELECT i FROM rows WHERE f / i > 0.1",
        "SELECT i FROM rows WHERE i / 0 = NONE",
        # nested-object path
        "SELECT i FROM rows WHERE o.x >= 5",
        # NOT + negation
        "SELECT i FROM rows WHERE !(i > 0) AND -i < 20",
    ]:
        _assert_same(ds, sql)


def test_columnar_off_is_pure_scalar(ds):
    """SURREAL_COLUMNAR=off must force the scalar path through the
    STREAMING executor too (fallback-correctness gate shape)."""
    from surrealdb_tpu.exec.batch import counters

    COUNTERS = counters(ds)
    sql = "SELECT i FROM rows WHERE i > 10 ORDER BY i LIMIT 5"
    cnf.COLUMNAR = "off"
    before = COUNTERS["rows_vectorized"]
    off = render(ds.query_one(sql, ns="t", db="t"))
    assert COUNTERS["rows_vectorized"] == before
    cnf.COLUMNAR = "auto"
    on = render(ds.query_one(sql, ns="t", db="t"))
    assert off == on


def test_order_rand_seeded_and_complete(ds):
    """ORDER BY RAND uses the datastore-scoped RNG: the row SET is
    stable and no global-random state is consumed."""
    state = random.getstate()
    out = ds.query_one(
        "SELECT i FROM rows WHERE i > 30 ORDER BY RAND()", ns="t", db="t"
    )
    assert random.getstate() == state  # global RNG untouched
    base = ds.query_one(
        "SELECT i FROM rows WHERE i > 30 ORDER BY i", ns="t", db="t"
    )
    assert sorted(render(r) for r in out) == \
        sorted(render(r) for r in base)


def test_topk_order_stability(ds):
    """The bounded top-k heap must keep full-sort tie order (stable)."""
    for sql in [
        "SELECT i, id FROM rows ORDER BY s ASC LIMIT 12",
        "SELECT i, id FROM rows ORDER BY s DESC LIMIT 12 START 3",
        "SELECT s, count() AS c FROM rows GROUP BY s ORDER BY c DESC "
        "LIMIT 2",
    ]:
        _assert_same(ds, sql)


def test_colstore_eviction_rebuilds_identically(ds):
    from surrealdb_tpu.exec.batch import store_evict

    sql = ("SELECT s, count() AS c, math::sum(i) AS si FROM rows "
           "GROUP BY s")
    a = _assert_same(ds, sql)
    store_evict(ds)  # accountant eviction path
    assert not ds._table_columns
    b = _assert_same(ds, sql)
    assert a == b
    assert ds._table_columns  # rebuilt on touch


def test_colstore_respects_txn_overlay(ds):
    """Uncommitted writes in the SAME transaction must be visible —
    the column store (committed state only) must stand aside."""
    out = ds.query(
        "BEGIN; CREATE rows:9001 SET s = \"zz9\", i = 1; "
        "SELECT s, count() AS c FROM rows WHERE s = \"zz9\" GROUP BY s; "
        "COMMIT;",
        ns="t", db="t",
    )
    assert out[2] == [{"s": "zz9", "c": 1}]
    ds.query("DELETE rows:9001", ns="t", db="t")


def test_partial_decoder_roundtrip():
    from surrealdb_tpu import wire
    from surrealdb_tpu.kvs.api import deserialize_fields, serialize
    from surrealdb_tpu.val import NONE, RecordId

    doc = {
        "id": RecordId("t", 1), "a": 1, "b": [1, {"c": 2}],
        "s": "héllo", "n": None, "x": NONE, "f": 2.5,
        "big": 2 ** 62, "neg": -7,
    }
    raw = serialize(doc)
    out = deserialize_fields(raw, {"a", "s", "x", "f", "neg"})
    assert out["a"] == 1 and out["s"] == "héllo" and out["f"] == 2.5
    assert out["x"] is NONE and out["neg"] == -7
    assert "b" not in out and "big" not in out
    # non-map top level falls back to None/shared decode
    assert wire.decode_fields(wire.encode([1, 2]), {"a"}) is None


def test_index_pushdown_prunes_and_matches(ds):
    from surrealdb_tpu.exec.batch import counters

    d2 = Datastore("memory")
    COUNTERS = counters(d2)
    d2.query("DEFINE TABLE p; DEFINE INDEX ix ON p FIELDS a, b",
             ns="t", db="t")
    stmts = [
        f"CREATE p:{i} SET a = {i % 4}, b = {i}, c = {i * 2}"
        for i in range(64)
    ]
    d2.query("; ".join(stmts), ns="t", db="t")
    before = COUNTERS["pushdown_rows_pruned"]
    sql = "SELECT id FROM p WHERE a = 1 AND b > 40 AND b < 60"
    got = render(d2.query_one(sql, ns="t", db="t"))
    sess = Session(ns="t", db="t", auth_level="owner")
    sess.planner_strategy = "compute-only"
    want = render(d2.execute(sql, session=sess)[-1].unwrap())
    assert got == want
    assert COUNTERS["pushdown_rows_pruned"] > before  # rows were pruned
    # EXPLAIN still shows the index access path
    ex = d2.query_one("EXPLAIN " + sql, ns="t", db="t")
    assert any("Iterate Index" in str(e.get("operation", ""))
               for e in (ex if isinstance(ex, list) else [ex]))


def test_fused_filtered_knn_equivalence():
    d2 = Datastore("memory")
    d2.query("DEFINE TABLE v", ns="t", db="t")
    rng = np.random.default_rng(5)
    stmts = []
    for i in range(300):
        vec = rng.normal(size=8).round(4).tolist()
        stmts.append(
            f"CREATE v:{i} SET emb = {vec}, cat = {i % 7}, "
            f"score = {round(float(rng.uniform(0, 1)), 4)}"
        )
    d2.query("; ".join(stmts), ns="t", db="t")
    q = rng.normal(size=8).round(4).tolist()
    sql = ("SELECT id, vector::distance::knn() AS d FROM v "
           "WHERE cat = 3 AND score > 0.25 AND emb <|4|> $q")
    from surrealdb_tpu.exec.batch import counters

    COUNTERS = counters(d2)
    before = COUNTERS["fused_knn_queries"]
    cnf.COLUMNAR = "auto"
    fused = render(d2.query_one(sql, ns="t", db="t", vars={"q": q}))
    assert COUNTERS["fused_knn_queries"] > before
    cnf.COLUMNAR = "off"
    try:
        scalar = render(d2.query_one(sql, ns="t", db="t",
                                     vars={"q": q}))
    finally:
        cnf.COLUMNAR = "auto"
    assert fused == scalar


def test_review_regressions(ds):
    """Pinned repros from the PR-14 review pass."""
    # 1: array-typed column inside a composite index must not prefilter
    # whole-array predicates against its unnested per-element entries
    d2 = Datastore("memory")
    d2.query("DEFINE TABLE t; DEFINE FIELD tags ON t TYPE array; "
             "DEFINE INDEX ix ON t FIELDS cat, x, tags", ns="t", db="t")
    d2.query("CREATE t:1 SET cat=1, x=9, tags=[1,2]", ns="t", db="t")
    a = d2.query_one("SELECT id FROM t WHERE cat=1 AND tags=[1,2]",
                     ns="t", db="t")
    b = d2.query_one(
        "SELECT id FROM t WITH NOINDEX WHERE cat=1 AND tags=[1,2]",
        ns="t", db="t")
    assert render(a) == render(b) and len(a) == 1
    # 2: &&/|| VALUE semantics (deciding operand, not a bool) must not
    # vectorize as comparison operands
    _assert_same(ds, "SELECT id FROM rows WHERE (b && i) = 3 LIMIT 3")
    _assert_same(ds, "SELECT id FROM rows WHERE (i || f) > 2 LIMIT 3")
    # 3: Decimal constants keep Decimal arithmetic (value AND type)
    _assert_same(ds, "SELECT i + 0.5dec AS x FROM rows LIMIT 3")


def test_explain_analyze_reports_vectorized_rows(ds):
    sess = Session(ns="t", db="t", auth_level="owner")
    sess.planner_strategy = "all-ro"
    txt = [r.unwrap() for r in ds.execute(
        "EXPLAIN ANALYZE SELECT i FROM rows WHERE i > 0", session=sess
    )][0]
    assert "vectorized: " in txt and "fallback: " in txt


def test_info_for_system_columnar_section(ds):
    info = ds.query_one("INFO FOR SYSTEM", ns="t", db="t")
    col = info["columnar"]
    assert col["rows_vectorized"] > 0
    assert "colstore_bytes" in col and "colstore_builds" in col


def test_memory_accountant_covers_colstore(ds):
    from surrealdb_tpu import resource

    ds.query_one(
        "SELECT s, count() AS c FROM rows GROUP BY s", ns="t", db="t"
    )
    snap = resource.get_accountant().snapshot()
    assert snap["by_kind"].get("col", 0) > 0


# ---------------------------------------------------------------------------
# colstore-backed ORDER BY (PR 15): lexsort vs the scalar key extractor
# ---------------------------------------------------------------------------


def test_order_by_lexsort_dual_execution(ds):
    """ORDER BY over clean scalar columns rides np.lexsort; the answer
    (including tie order, LIMIT/START bounds, DESC, multi-key, NONE and
    mixed-rank rows) must render identically to the scalar comparator —
    and exotic key columns (arrays, >2^53 ints, Decimals) must bail to
    the scalar path rather than guess."""
    queries = [
        "SELECT i, f FROM rows ORDER BY i",
        "SELECT i, f FROM rows ORDER BY i DESC, f ASC",
        "SELECT i, s FROM rows ORDER BY s, i DESC LIMIT 25",
        "SELECT f, b FROM rows ORDER BY b DESC, f LIMIT 11 START 4",
        "SELECT i AS rank, f FROM rows ORDER BY rank DESC LIMIT 9",
        # mixed-rank key column (int/float/str/bool/NULL/array rows):
        # array rows are exotic → whole sort falls back, still identical
        "SELECT m, i FROM rows ORDER BY m, i LIMIT 30",
        # exotic keys: >2^53 ints and Decimals route scalar
        "SELECT big, i FROM rows ORDER BY big DESC, i LIMIT 15",
        "SELECT s, i FROM rows WHERE i > 0 ORDER BY s DESC, i",
    ]
    for sql in queries:
        _assert_same(ds, sql)


def test_order_by_lexsort_counter_and_fallback(ds):
    from surrealdb_tpu.exec.batch import counters

    before = counters(ds)["order_lexsort"]
    cnf.COLUMNAR = "auto"
    ds.query_one("SELECT i, f FROM rows ORDER BY i DESC LIMIT 20",
                 ns="t", db="t")
    assert counters(ds)["order_lexsort"] == before + 1
    # an exotic key column must NOT count (scalar fallback served it)
    ds.query_one("SELECT m, i FROM rows ORDER BY m LIMIT 20",
                 ns="t", db="t")
    assert counters(ds)["order_lexsort"] == before + 1
