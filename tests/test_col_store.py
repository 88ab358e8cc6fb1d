"""Columnar vector scan path: the native extraction kernel, the
version-keyed column store (col.py), and the VecTopKScan streaming fast
path (reference role: exec/operators/knn_topk.rs + compiled scan
decode)."""

import math

import numpy as np
import pytest

from surrealdb_tpu import Datastore, cnf
from surrealdb_tpu.exec.batch import counters
from surrealdb_tpu.kvs.ds import Session
from surrealdb_tpu.val import RecordId, render


def _seed(ds, n=300, dim=8, tb="v"):
    rng = np.random.default_rng(7)
    xs = rng.normal(size=(n, dim)).astype(np.float64)
    ds.query(f"DEFINE TABLE {tb}", ns="t", db="t")
    txn = ds.transaction(write=True)
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize

    try:
        for i in range(n):
            txn.set(
                K.record("t", "t", tb, i),
                serialize({"id": RecordId(tb, i), "emb": xs[i].tolist()}),
            )
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    return xs


def _ground_truth_cos(xs, q, k):
    sims = (xs @ q) / (np.linalg.norm(xs, axis=1) * np.linalg.norm(q))
    return [int(i) for i in np.argsort(-sims)[:k]], sims


def test_vec_topk_matches_ground_truth():
    ds = Datastore("memory")
    xs = _seed(ds)
    q = np.random.default_rng(8).normal(size=(8,))
    rows = ds.query_one(
        "SELECT id, vector::similarity::cosine(emb, $q) AS s FROM v "
        "ORDER BY s DESC LIMIT 7",
        ns="t", db="t", vars={"q": q.tolist()},
    )
    top, sims = _ground_truth_cos(xs, q, 7)
    assert [r["id"].id for r in rows] == top
    # projected scores are exact f64, recomputed per winning row
    assert abs(rows[0]["s"] - sims[top[0]]) < 1e-12


def test_vec_topk_invalidation_and_ragged_fallback():
    ds = Datastore("memory")
    xs = _seed(ds)
    q = np.random.default_rng(9).normal(size=(8,))
    sql = ("SELECT id, vector::distance::euclidean(emb, $q) AS d FROM v "
           "ORDER BY d ASC LIMIT 3")
    rows = ds.query_one(sql, ns="t", db="t", vars={"q": q.tolist()})
    d = np.linalg.norm(xs - q[None, :], axis=1)
    assert [r["id"].id for r in rows] == [int(i) for i in np.argsort(d)[:3]]
    # a committed write invalidates the cached column
    ds.query_one("CREATE v:9999 SET emb = $e", ns="t", db="t",
                 vars={"e": q.tolist()})
    rows = ds.query_one(sql, ns="t", db="t", vars={"q": q.tolist()})
    assert rows[0]["id"].id == 9999
    # a ragged row disables the columnar path; the row-at-a-time engine
    # then raises its usual dimension error — identical behavior with
    # and without the fast path
    ds.query_one("CREATE v:bad SET emb = [1.0, 2.0]", ns="t", db="t")
    from surrealdb_tpu.err import SdbError

    with pytest.raises(SdbError, match="same dimension"):
        ds.query_one(sql, ns="t", db="t", vars={"q": q.tolist()})


def test_column_store_uncommitted_writes_bypass():
    # rows written inside the SAME transaction must be visible — the
    # column cache (committed state) must not serve that query
    ds = Datastore("memory")
    _seed(ds, n=50)
    q = [1.0] * 8
    out = ds.execute(
        "BEGIN; CREATE v:777 SET emb = $e; "
        "SELECT id, vector::similarity::cosine(emb, $e) AS s FROM v "
        "ORDER BY s DESC LIMIT 1; COMMIT;",
        ns="t", db="t", vars={"e": q},
    )
    sel = [r for r in out if r.ok and isinstance(r.result, list)][-1]
    assert sel.result[0]["id"].id == 777


def test_native_extract_kernel_direct():
    from surrealdb_tpu.native import available

    if not available():
        pytest.skip("native memtable unavailable")
    import surrealdb_tpu.wire as W
    from surrealdb_tpu.native import NativeMemtable

    mt = NativeMemtable()
    snap0 = mt.snapshot()
    batch = []
    for i in range(64):
        doc = {"id": i, "emb": [float(i), i + 1, i + 2.5], "pad": "x" * i}
        batch.append((b"p*%03d" % i, b"\x01" + W.encode(doc)))
    batch.append((b"p*zz1", b"\x01" + W.encode({"emb": [1.0]})))
    batch.append((b"p*zz2", b"\x01" + W.encode({"other": 1})))
    assert mt.commit_batch(snap0, batch)
    snap = mt.snapshot()
    mat, keys, bad = mt.scan_extract_f32(
        b"p*", b"p+", snap, b"emb", 3, 2, 8
    )
    assert mat.shape == (64, 3)
    assert keys[0] == b"%03d" % 0 and len(keys) == 64
    assert sorted(bad) == [b"zz1", b"zz2"]
    assert np.allclose(mat[10], [10.0, 11.0, 12.5])


# -- the top-k scan owns the rows it yields (PR 32) --------------------------

_SCANS = {"cos_sim": ("vector::similarity::cosine", "DESC"),
          "eucl": ("vector::distance::euclidean", "ASC"),
          "dot": ("vector::dot", "DESC"),
          "manh": ("vector::distance::manhattan", "ASC")}
# shape -> (table, projection, tail); each yields the 10 rows of LIMIT 10
_SHAPES = {"id_and_score": ("v", "id, {f} AS s", ""),
           "star_and_score": ("v", "*, {f} AS s", ""),
           "computed_field": ("vc", "*, {f} AS s", ""),
           "start_3": ("v", "id, {f} AS s", " START 3")}


@pytest.fixture(scope="module")
def owned_ds():
    ds = Datastore("memory")
    _seed(ds)
    _seed(ds, tb="vc")
    ds.query("DEFINE FIELD len ON vc COMPUTED vector::magnitude(emb)",
             ns="t", db="t")
    return ds


def _row_at_a_time(ds, sql, vars):
    """The same statement on the interpreter: no streaming plan, no
    columnar kernels (tests/test_columnar.py `_both`)."""
    sess = Session(ns="t", db="t", auth_level="owner")
    sess.planner_strategy = "compute-only"
    prev, cnf.COLUMNAR = cnf.COLUMNAR, "off"
    try:
        return ds.execute(sql, session=sess, vars=vars)[-1].unwrap()
    finally:
        cnf.COLUMNAR = prev


def _scan_statement(kind, shape):
    fn, direction = _SCANS[kind]
    tb, proj, tail = _SHAPES[shape]
    return (f"SELECT {proj.format(f=fn + '(emb, $q)')} FROM {tb} "
            f"ORDER BY s {direction} LIMIT 10{tail}")


def _bits(rows):
    """Rows with every float as its hex: equality is bit for bit."""
    def h(v):
        if isinstance(v, float):
            return v.hex()
        if isinstance(v, list):
            return [h(x) for x in v]
        if isinstance(v, dict):
            return [(k, h(x)) for k, x in v.items()]
        return v
    return [h(r) for r in rows]


@pytest.mark.parametrize("shape", list(_SHAPES))
@pytest.mark.parametrize("kind", list(_SCANS))
def test_owned_rows_equal_the_shared_fetch_and_the_interpreter(
        owned_ds, monkeypatch, kind, shape):
    """Winners decoded fresh from their bytes give, bit for bit and in
    order, the rows the same plan gives through `fetch_record` (the
    decode cache and its copy), and the row-at-a-time engine's rows:
    ids, order, keys, stored and computed values exactly, the score to
    the last ulps (the streaming projection sums in numpy, the
    interpreter in Python: 1-2 ulp apart before this change too)."""
    from surrealdb_tpu.exec import eval as ev

    ds = owned_ds
    sql, yielded = _scan_statement(kind, shape), 10
    vars = {"q": np.random.default_rng(31).normal(size=(8,)).tolist()}
    before = counters(ds)["scan_rows_owned"]
    got = ds.query_one(sql, ns="t", db="t", vars=vars)
    # the streaming plan served it, through the owned fetch
    assert counters(ds)["scan_rows_owned"] - before == len(got) == yielded
    with monkeypatch.context() as m:
        m.setattr(ev, "fetch_record_owned", ev.fetch_record)
        shared = ds.query_one(sql, ns="t", db="t", vars=vars)
    assert _bits(got) == _bits(shared)
    want = _row_at_a_time(ds, sql, vars)
    assert counters(ds)["scan_rows_owned"] - before == 2 * yielded
    assert [list(r) for r in got] == [list(r) for r in want]  # key order
    for g, w in zip(got, want):
        assert abs(g["s"] - w["s"]) <= 4 * math.ulp(w["s"])
        g, w = dict(g, s=None), dict(w, s=None)
        assert _bits([g]) == _bits([w])
    if shape == "computed_field":
        assert all(isinstance(g["len"], float) for g in got)


def test_owned_rows_bypass_the_decode_cache_and_stay_pristine(owned_ds):
    """The scan leaves the decode cache as it found it, counts the rows
    it yielded, and a caller that mutates a row cannot reach the next
    answer."""
    from surrealdb_tpu.kvs import api

    ds = owned_ds
    sql, yielded = _scan_statement("cos_sim", "star_and_score"), 10
    vars = {"q": np.random.default_rng(32).normal(size=(8,)).tolist()}
    cached, charged = len(api._dec_cache), api._dec_cache_bytes
    before = counters(ds)["scan_rows_owned"]
    first = ds.query_one(sql, ns="t", db="t", vars=vars)
    assert len(api._dec_cache) == cached
    assert api._dec_cache_bytes == charged
    assert counters(ds)["scan_rows_owned"] - before == len(first) == yielded
    pristine = render(first)
    for row in first:
        row["emb"][0] = 1e9
        row["emb"].append("spoiled")
        row["extra"] = True
    again = ds.query_one(sql, ns="t", db="t", vars=vars)
    assert render(again) == pristine
    assert len(api._dec_cache) == cached


def test_indexed_knn_yields_no_owned_rows():
    """An indexed `<|k|>` query builds no `VecTopKScanOp`: its winners
    come through `fetch_record`, and the counter stays where it was."""
    ds = Datastore("memory")
    _seed(ds, n=80)
    ds.query("DEFINE INDEX ix ON v FIELDS emb HNSW DIMENSION 8",
             ns="t", db="t")
    rows = ds.query_one(
        "SELECT id, vector::distance::knn() AS d FROM v "
        "WHERE emb <|5|> $q", ns="t", db="t", vars={"q": [0.5] * 8})
    assert len(rows) == 5
    assert counters(ds)["scan_rows_owned"] == 0
    assert ds.telemetry.get("columnar_scan_rows_owned") == 0
    assert "surreal_columnar_scan_rows_owned_total 0" \
        in ds.telemetry.prometheus(ds)
