"""Segmented LSM-style ANN (idx/segments.py): seal/build/merge
lifecycle, exact fan-out, tombstone density, snapshot consistency."""

import threading

import numpy as np
import pytest

from surrealdb_tpu import cnf
from surrealdb_tpu.idx import segments
from surrealdb_tpu.idx.vector import TpuVectorIndex
from surrealdb_tpu.val import RecordId

DIM = 12


def _mk_engine():
    ix = TpuVectorIndex("b", "b", "t", "ix", {
        "dimension": DIM, "distance": "euclidean", "vector_type": "f32",
    })
    ix.version = 0
    return ix


def _apply(ix, entries, maintain=True):
    """Apply op-log entries the way sync's log applier does, then run
    the post-sync maintenance hook."""
    with ix.lock, ix.rw.write():
        ix._apply_entries(entries)
    if maintain:
        ix._maybe_maintain()


def _sets(ix, vecs, start_id):
    return [
        ("set", start_id + i, np.asarray(v, np.float32).tobytes())
        for i, v in enumerate(vecs)
    ]


def _brute(ix, qs, k):
    """Oracle: the engine's own exact path with segments disabled."""
    old = cnf.KNN_SEG_MODE
    cnf.KNN_SEG_MODE = "off"
    try:
        return ix.knn_batch(qs, k)
    finally:
        cnf.KNN_SEG_MODE = old


def _pairs(res):
    return [[(r.id, d) for r, d in row] for row in res]


@pytest.fixture()
def seg_cnf(monkeypatch):
    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 256)
    monkeypatch.setattr(cnf, "KNN_SEG_FANOUT", 2)
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    # byte-identity assertions compare the exact f64 host ladder on
    # both sides (the conftest default routes brute scoring through
    # the inline device kernel, which ranks/reports in f32)
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    # counter assertions are per-test: the module counters are global
    # and other suites' legacy-path tests legitimately bump them
    segments.reset_counters()
    yield


# ---------------------------------------------------------------------------
# exact fan-out: byte-identical to the brute oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_exact_fanout_byte_identical_property(seg_cnf, monkeypatch,
                                              seed):
    """Property: with graphs NOT yet built (every sealed span served by
    its exact scan), the segment fan-out + merge_topk answer is
    byte-identical to the unsegmented brute oracle — across random
    seal points, random deletes, and a random mutable tail."""
    rng = np.random.default_rng(seed)
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS",
                        int(rng.integers(64, 400)))
    ix = _mk_engine()
    nid = 0
    for _ in range(int(rng.integers(2, 6))):
        vs = rng.normal(size=(int(rng.integers(80, 500)), DIM))
        _apply(ix, _sets(ix, vs, nid), maintain=False)
        nid += len(vs)
        # seal WITHOUT building: exact per-segment serving
        with ix._segments().lock:
            ix._segments()._seal_locked()
        if nid > 10:
            dels = rng.integers(0, nid, int(rng.integers(0, 30)))
            _apply(ix, [("del", int(d), None) for d in dels],
                   maintain=False)
    st = ix._segments().status()
    assert st["segments"] >= 1
    assert st["ready"] == 0  # nothing built: pure exact fan-out
    qs = rng.normal(size=(6, DIM)).astype(np.float32)
    for k in (1, 7, 23):
        got = _pairs(ix.knn_batch(qs, k))
        want = _pairs(_brute(ix, qs, k))
        assert got == want, f"k={k} diverged from brute oracle"


# ---------------------------------------------------------------------------
# delete-heavy segments
# ---------------------------------------------------------------------------


def test_tombstone_95pct_segment_still_fills_k(seg_cnf):
    """A segment at 95% tombstone density must still return exactly k
    results, identical to brute — the per-segment oversampling (and
    the exact underfill guard) generalize the PR-7 fix."""
    rng = np.random.default_rng(11)
    ix = _mk_engine()
    vs = rng.normal(size=(1200, DIM))
    _apply(ix, _sets(ix, vs, 0))
    assert ix.ensure_ann()
    st = ix._segments().status()
    lo, hi = st["spans"][0]["lo"], st["spans"][0]["hi"]
    live = [ix.rids[r].id for r in range(lo, hi) if ix.valid[r]]
    kill = live[: int(len(live) * 0.95)]
    _apply(ix, [("del", i, None) for i in kill])
    qs = rng.normal(size=(5, DIM)).astype(np.float32)
    k = 10
    got = ix.knn_batch(qs, k)
    want = _brute(ix, qs, k)
    assert all(len(g) == k for g in got)
    assert _pairs(got) == _pairs(want)
    # staleness then schedules a bounded SEGMENT rebuild that compacts
    # the dead rows out of the graph — never a whole-index rebuild
    assert ix.ensure_ann()
    spans = ix._segments().status()["spans"]
    total_graph = sum(s.get("graph_rows", 0) for s in spans)
    n_live = int(ix.valid.sum())
    assert total_graph <= n_live + int(cnf.KNN_SEG_ROWS)
    assert segments.counters()["ann_full_rebuilds"] == 0
    assert _pairs(ix.knn_batch(qs, k)) == _pairs(_brute(ix, qs, k))


def test_merge_compacts_tombstones(seg_cnf, monkeypatch):
    """A tier merge builds ONE graph over the run's span and its
    row_map excludes rows already tombstoned — delete reclamation
    happens at merge time, not via a global repack."""
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 128)
    rng = np.random.default_rng(7)
    ix = _mk_engine()
    nid = 0
    for _ in range(4):
        vs = rng.normal(size=(128, DIM))
        _apply(ix, _sets(ix, vs, nid), maintain=False)
        nid += 128
        with ix._segments().lock:
            ix._segments()._seal_locked()
    dels = list(range(0, nid, 3))
    _apply(ix, [("del", d, None) for d in dels], maintain=False)
    assert ix.ensure_ann()
    st = ix._segments().status()
    assert segments.counters()["seg_merges"] >= 1
    total_graph = sum(s.get("graph_rows", 0) for s in st["spans"])
    assert total_graph == int(ix.valid.sum())  # dead rows compacted out


# ---------------------------------------------------------------------------
# seal / merge during queries: snapshot consistency
# ---------------------------------------------------------------------------


def test_seal_merge_during_query_snapshot_consistency(seg_cnf,
                                                      monkeypatch):
    """Queries racing the whole maintenance lifecycle (seal → build →
    merge → splice) must answer exactly at every point: an in-flight
    query holds its captured segment list, so a merge swapping the
    table under it can never tear an answer."""
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 100)
    rng = np.random.default_rng(23)
    ix = _mk_engine()
    vs = rng.normal(size=(900, DIM))
    _apply(ix, _sets(ix, vs, 0), maintain=False)
    qs = rng.normal(size=(4, DIM)).astype(np.float32)
    want = _pairs(_brute(ix, qs, 8))
    errs = []
    stop = threading.Event()

    def query_loop():
        try:
            while not stop.is_set():
                got = _pairs(ix.knn_batch(qs, 8))
                if got != want:
                    errs.append(got)
                    return
        except Exception as e:  # surface, never swallow
            errs.append(repr(e))

    t = threading.Thread(target=query_loop, daemon=True)
    t.start()
    try:
        # run the full lifecycle synchronously while queries hammer
        assert ix.ensure_ann()
        for _ in range(3):
            ix._segments().drain()
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errs, f"racing query diverged: {errs[:1]}"
    assert _pairs(ix.knn_batch(qs, 8)) == want


# ---------------------------------------------------------------------------
# lifecycle details
# ---------------------------------------------------------------------------


def test_adopts_legacy_graph_without_rebuild(monkeypatch):
    """An engine that grew past the segmentation floor with a legacy
    whole-store graph keeps serving it: the graph becomes the first
    sealed segment, appended rows become the mutable tail — no build
    runs, no serving gap opens."""
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "off")
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    rng = np.random.default_rng(5)
    ix = _mk_engine()
    _apply(ix, _sets(ix, rng.normal(size=(500, DIM)), 0))
    assert ix.ensure_ann()
    legacy = ix._ann
    assert legacy is not None
    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 256)
    _apply(ix, _sets(ix, rng.normal(size=(40, DIM)), 500))
    st = ix._segments().status()
    assert st["segments"] >= 1
    assert st["spans"][0]["hi"] == 500
    assert ix._segments().segs[0].graph[0] is legacy  # adopted, not rebuilt
    assert ix._ann is None  # single accounting owner
    qs = rng.normal(size=(3, DIM)).astype(np.float32)
    assert _pairs(ix.knn_batch(qs, 5)) == _pairs(_brute(ix, qs, 5))


def test_overwrite_in_sealed_segment_exact_immediately(seg_cnf):
    """A row overwritten after its segment's graph snapshot goes dirty
    and brute-merges: the stale graph copy can never serve its old
    distance (the legacy dirty-row discipline, per segment)."""
    rng = np.random.default_rng(9)
    ix = _mk_engine()
    _apply(ix, _sets(ix, rng.normal(size=(600, DIM)), 0))
    assert ix.ensure_ann()
    q = rng.normal(size=DIM).astype(np.float32)
    _apply(ix, _sets(ix, [q], 77))  # overwrite row 77 to the query
    res = ix.knn_batch(q[None, :], 3)[0]
    assert res[0][0].id == 77
    assert res[0][1] == 0.0


def test_full_rebuild_counter_counts_legacy_treadmill(monkeypatch):
    """The legacy path counts its whole-index rebuild when drift passes
    the tail fraction; the segmented path never increments it."""
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "off")
    segments.reset_counters()
    rng = np.random.default_rng(3)
    ix = _mk_engine()
    _apply(ix, _sets(ix, rng.normal(size=(400, DIM)), 0))
    assert ix.ensure_ann()
    assert segments.counters()["ann_full_rebuilds"] == 0
    # push drift past KNN_ANN_TAIL_FRAC: the next build is a treadmill
    # turn and must be counted
    _apply(ix, _sets(ix, rng.normal(size=(200, DIM)), 400))
    assert ix.ensure_ann()
    assert segments.counters()["ann_full_rebuilds"] >= 1


@pytest.mark.parametrize("via", ["engine", "sql"])
def test_churn_zero_full_rebuilds_segmented(seg_cnf, monkeypatch, request,
                                            via):
    """Sustained mixed insert/delete churn on a segmented engine, fed
    through the op log ("engine") or by SQL on a Datastore ("sql": the
    write and query path a server takes, what the conformance gate's
    churn smoke drove): every commit is searchable on the very next
    query, recall stays exact-grade, seals/builds happen, and the
    whole-index rebuild counter stays at 0."""
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 200)
    segments.reset_counters()
    rng = np.random.default_rng(17)
    if via == "sql":
        ds = request.getfixturevalue("ds")
        ds.query(
            f"DEFINE TABLE t; DEFINE INDEX ix ON t FIELDS v HNSW "
            f"DIMENSION {DIM} DIST EUCLIDEAN TYPE F32"
        )
        ix = None

        def add(vs, start):
            ds.query("".join(
                f"CREATE t:{start + i} SET v = {v.tolist()};"
                for i, v in enumerate(vs)
            ))

        def delete(ids):
            ds.query("".join(f"DELETE t:{int(d)};" for d in ids))

        def nearest(q):
            rows = ds.query("SELECT id FROM t WHERE v <|1|> $q",
                            vars={"q": q.tolist()})[0]
            return rows[0]["id"].id
    else:
        ix = _mk_engine()

        def add(vs, start):
            _apply(ix, _sets(ix, vs, start))

        def delete(ids):
            _apply(ix, [("del", int(d), None) for d in ids])

        def nearest(q):
            return ix.knn_batch(q[None, :], 1)[0][0][0].id

    nid = 0
    for _ in range(10):
        vs = np.round(rng.normal(size=(150, DIM)), 4).astype(np.float32)
        add(vs, nid)
        nid += 150
        # ingest-to-searchable is one sync: the row committed a moment
        # ago is the very next query's answer, no build in the way
        assert nearest(vs[-1]) == nid - 1
        delete(rng.integers(0, nid, 25))
        if ix is None:
            ix = next(iter(ds.vector_indexes.values()))
        ix._segments().drain()
    c = segments.counters()
    assert c["seg_seals"] >= 2 and c["seg_builds"] >= 2
    assert c["ann_full_rebuilds"] == 0 and ix.ann_full_rebuilds == 0
    qs = rng.normal(size=(6, DIM)).astype(np.float32)
    got = _pairs(ix.knn_batch(qs, 10))
    want = _pairs(_brute(ix, qs, 10))
    hits = sum(
        len({i for i, _ in g} & {i for i, _ in w})
        for g, w in zip(got, want)
    )
    assert hits / (10 * len(qs)) >= 0.95


def test_repack_resets_segments(seg_cnf):
    """A full repack (row remap) voids the segment table; maintenance
    re-seals from the new numbering and answers stay exact."""
    rng = np.random.default_rng(31)
    ix = _mk_engine()
    _apply(ix, _sets(ix, rng.normal(size=(700, DIM)), 0))
    assert ix.ensure_ann()
    old_gen = ix._segments().gen
    rids = list(ix.rids)
    rows = [ix.vecs[i].copy() for i in range(len(rids))]
    index = {ix.row_index[k]: None for k in ()} or dict(ix.row_index)
    with ix.lock, ix.rw.write():
        ix._install_rows(rids, rows, index)
    assert ix._segments().gen > old_gen
    assert ix._segments().status()["segments"] == 0
    ix._maybe_maintain()
    assert ix.ensure_ann()
    qs = rng.normal(size=(3, DIM)).astype(np.float32)
    assert _pairs(ix.knn_batch(qs, 5)) == _pairs(_brute(ix, qs, 5))


def test_graph_eviction_degrades_to_exact_and_rebuilds(seg_cnf):
    """Accountant eviction of one segment's graph degrades that span
    to exact scans (answers unchanged) and the next maintenance pass
    rebuilds it."""
    rng = np.random.default_rng(41)
    ix = _mk_engine()
    _apply(ix, _sets(ix, rng.normal(size=(600, DIM)), 0))
    assert ix.ensure_ann()
    seg = ix._segments().segs[0]
    qs = rng.normal(size=(3, DIM)).astype(np.float32)
    want = _pairs(_brute(ix, qs, 7))
    seg.acct.evict()
    assert seg.graph is None and seg.state == "pending"
    assert _pairs(ix.knn_batch(qs, 7)) == want
    assert ix.ensure_ann()
    assert seg.state == "ready" and seg.graph is not None
    assert _pairs(ix.knn_batch(qs, 7)) == want


def test_seg_snapshot_persist_reload(seg_cnf, tmp_path, monkeypatch):
    """Per-segment artifacts (SKVANN01 frames keyed by content hash)
    reload instead of rebuilding; an overwritten row changes the span's
    bytes and misses the artifact (stale graphs never load)."""
    from surrealdb_tpu.idx import cagra

    rng = np.random.default_rng(13)
    ix = _mk_engine()
    ix.snapshot_dir = str(tmp_path)
    vs = rng.normal(size=(500, DIM))
    builds = []
    real_build = cagra.build_index

    def counting_build(*a, **kw):
        builds.append(1)
        return real_build(*a, **kw)

    # counted BEFORE the rows land: applying them already kicks the
    # background seal + build, which may finish first
    monkeypatch.setattr(cagra, "build_index", counting_build)
    _apply(ix, _sets(ix, vs, 0))
    assert ix.ensure_ann()
    n_first = len(builds)
    assert n_first >= 1
    assert list(tmp_path.glob("*.annsnap"))
    # same rows, fresh engine: the artifact must serve the build
    ix2 = _mk_engine()
    ix2.snapshot_dir = str(tmp_path)
    _apply(ix2, _sets(ix2, vs, 0))
    assert ix2.ensure_ann()
    assert len(builds) == n_first  # loaded, not rebuilt
    # an overwrite invalidates by content: a third engine with one
    # changed row must rebuild
    vs2 = vs.copy()
    vs2[3] += 1.0
    ix3 = _mk_engine()
    ix3.snapshot_dir = str(tmp_path)
    _apply(ix3, _sets(ix3, vs2, 0))
    assert ix3.ensure_ann()
    assert len(builds) > n_first


def test_explain_surfaces_segmented(seg_cnf, ds):
    """EXPLAIN names the segmented route and its fan-out shape."""
    import json

    rng = np.random.default_rng(19)
    ds.query(
        f"DEFINE TABLE t; DEFINE INDEX ix ON t FIELDS v HNSW "
        f"DIMENSION {DIM} DIST EUCLIDEAN TYPE F32"
    )
    ds.query("".join(
        f"CREATE t:{i} SET v = [{', '.join(f'{x:.4f}' for x in v)}];"
        for i, v in enumerate(rng.normal(size=(320, DIM)))
    ))
    q = rng.normal(size=DIM)
    vals = ", ".join(f"{x:.4f}" for x in q)
    sql = f"SELECT id FROM t WHERE v <|5,10|> [{vals}]"
    ds.query(sql)  # engage + seal
    ix = next(iter(ds.vector_indexes.values()))
    assert ix.ensure_ann()
    rows = ds.query(f"EXPLAIN {sql}")[0]
    blob = json.dumps(rows, default=str)
    assert "segmented" in blob, blob
