"""BM25 top-n on the device (idx/ftdevice.py, device/ftstore.py) against the
plain reference (idx/ft_reference.py), on the inline CPU runner at small
sizes with seeded random corpora."""

from types import SimpleNamespace

import numpy as np
import pytest

from surrealdb_tpu import Datastore
from surrealdb_tpu import key as K
from surrealdb_tpu.device import get_supervisor
from surrealdb_tpu.device import ftstore
from surrealdb_tpu.device.kernelstats import FT
from surrealdb_tpu.idx import ftdevice
from surrealdb_tpu.idx import vector as vec
from surrealdb_tpu.idx.ft_reference import Corpus, rrf

NS = DB = "t"
SQL = ("SELECT id, search::score(1) AS s FROM doc WHERE text @1@ $q "
       "ORDER BY s DESC LIMIT {n}")


@pytest.fixture(autouse=True)
def small_stores(monkeypatch):
    """Device routing for an index of a few hundred documents."""
    monkeypatch.setattr(vec, "DEVICE_MIN_ROWS", 8)


def zipf_corpus(seed: int, n_docs: int, vocab: int = 60):
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(3, 14))
        docs.append((np.minimum(rng.zipf(1.3, n), vocab) - 1).tolist())
    return docs


def load(ds, docs, *, emb=None):
    ds.execute("DEFINE ANALYZER simple TOKENIZERS blank FILTERS lowercase; "
               "DEFINE TABLE doc; DEFINE INDEX ft ON doc FIELDS text "
               "FULLTEXT ANALYZER simple BM25", ns=NS, db=DB)
    if emb is not None:
        ds.execute(f"DEFINE INDEX ix ON doc FIELDS emb HNSW DIMENSION "
                   f"{emb.shape[1]} DIST COSINE", ns=NS, db=DB)
    for s in range(0, len(docs), 200):
        rows = []
        for i in range(s, min(s + 200, len(docs))):
            text = " ".join(f"w{t}" for t in docs[i])
            extra = "" if emb is None else \
                ", emb: [" + ",".join(map(repr, emb[i].tolist())) + "]"
            rows.append(f"{{id: {i}, text: '{text}'{extra}}}")
        r = ds.execute(f"INSERT INTO doc [{','.join(rows)}]", ns=NS, db=DB)
        assert r[0].error is None, r[0].error


def search(ds, words, n):
    r = ds.execute(SQL.format(n=n), ns=NS, db=DB,
                   vars={"q": " ".join(words)})
    assert r[0].error is None, r[0].error
    return [(int(x["id"].id), x["s"]) for x in r[0].result]


def counters():
    st = get_supervisor().status()
    return (st["ft_device_searches"], st["ft_host_searches"],
            FT["riders"], st["ft_ships"])


def assert_top(got, ref_docs, ref_scores, n):
    """`got` is the reference's top n, ties at the n-th place counted;
    each score is the f32 of that row's f64 score, rows non-increasing."""
    want = min(n, len(ref_docs))
    assert len(got) == want
    if not want:
        return
    by_doc = dict(zip(ref_docs.tolist(), ref_scores.tolist()))
    nth = ref_scores[want - 1]
    for d, s in got:
        assert d in by_doc
        assert by_doc[d] >= nth * (1 - 1e-12)
        assert abs(s - float(np.float32(by_doc[d]))) \
            <= float(np.spacing(np.float32(by_doc[d])))
    assert [s for _d, s in got] == sorted((s for _d, s in got), reverse=True)


@pytest.mark.parametrize("seed", [3, 11])
def test_device_top_n_equals_the_reference(seed):
    docs = zipf_corpus(seed, 400)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        rng = np.random.default_rng(seed + 1)
        # terms whose idf is not clamped: their docs' scores differ
        scoring = [t for t in range(60)
                   if 0 < len(ref.postings(t)[0]) < len(docs) // 3]
        before = counters()
        for _ in range(12):
            q = rng.choice(scoring, size=2).tolist()
            got = search(ds, [f"w{t}" for t in q], 10)
            assert_top(got, *ref.search(q), 10)
        after = counters()
        # every leg on the device, one rider a leg, one ship
        assert after[0] - before[0] == 12 and after[1] == before[1]
        assert after[2] - before[2] <= 12
        assert after[3] - before[3] == 1
    finally:
        ds.close()


@pytest.mark.parametrize("boolean", ["AND", "OR"])
def test_and_and_or_against_the_reference(boolean):
    """OR is the host's (counted); AND the device's; both are the
    reference's."""
    docs = zipf_corpus(5, 300)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        sql = SQL.replace("@1@", "@1,OR@" if boolean == "OR" else "@1@")
        before = counters()
        for q in ([0, 3], [1, 2, 7], [4]):
            r = ds.execute(sql.format(n=8), ns=NS, db=DB,
                           vars={"q": " ".join(f"w{t}" for t in q)})
            assert r[0].error is None, r[0].error
            got = [(int(x["id"].id), x["s"]) for x in r[0].result]
            assert_top(got, *ref.search(q, boolean), 8)
        after = counters()
        if boolean == "OR":
            assert after[0] == before[0] and after[1] - before[1] == 3
        else:
            assert after[0] - before[0] == 3
    finally:
        ds.close()


@pytest.mark.parametrize("words,n", [
    (["w1", "w1", "w4"], 5),          # a repeated query term
    (["w2", "nowhere"], 5),           # an absent term: no rows
    (["w0"], 10),                     # idf clamped to 0: every score 0
    (["w0", "w9"], 10),               # a clamped term beside a scoring one
    (["w58", "w59", "w57"], 10),      # rare terms together: empty
])
def test_query_shapes_against_the_reference(words, n):
    docs = zipf_corpus(7, 300)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        terms = [int(w[1:]) if w.startswith("w") and w[1:].isdigit()
                 else 10 ** 6 for w in words]
        got = search(ds, words, n)
        assert_top(got, *ref.search(terms), n)
    finally:
        ds.close()


def test_ties_at_the_cut():
    """Equal scores keep record-key order on the device while the margin
    holds them; past it the host answers, counted, and both are the
    reference's with ties counted."""
    rng = np.random.default_rng(1)
    docs = [[1, 2, 3] for _ in range(12)] + [[1, 2, 3, 4] * 2] * 3 + \
        [rng.integers(5, 40, 6).tolist() for _ in range(300)] + \
        [[1, 2] for _ in range(40)]
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        before = counters()
        got = search(ds, ["w3", "w1"], 6)       # 15 matches: all returned
        assert_top(got, *ref.search([3, 1]), 6)
        assert [d for d, _s in got] == [0, 1, 2, 3, 4, 5]
        mid = counters()
        assert mid[0] - before[0] == 1 and mid[1] == before[1]
        got = search(ds, ["w1", "w2"], 4)       # 55 matches, 40 tied first
        assert_top(got, *ref.search([1, 2]), 4)
        after = counters()
        assert after[2] - mid[2] == 1 and after[1] - mid[1] == 1
    finally:
        ds.close()


def _snap_and_riders(ds, queries, n=10):
    """The index's snapshot after one search, and riders for `queries`."""
    search(ds, ["w1"], n)
    snap = ftdevice._snapshots(ds)[(NS, DB, "doc", "ft")]
    idef = SimpleNamespace(fulltext={"bm25": (1.2, 0.75)})
    return snap, [ftdevice._make_rider(
        snap, idef, [snap.terms[K.enc_str(f"w{t}")] for t in q], n)
        for q in queries]


def _doc_of(snap, hits):
    return [(int(snap.doc_ids[d]), s) for d, s in hits]


def test_riders_with_different_term_counts_share_one_batch():
    docs = zipf_corpus(13, 400, vocab=30)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        queries = [[2], [0, 3], [1, 4, 6], [2, 5, 7, 9, 11]]
        snap, riders = _snap_and_riders(ds, queries)
        d0 = FT["dispatches"]
        out = ftdevice._dispatch(riders)
        for q, hits in zip(queries, out):
            assert hits is not None
            assert_top(_doc_of(snap, hits), *ref.search(q), 10)
        assert len({len(r.packed) for r in riders}) == 1
        assert FT["dispatches"] - d0 <= 2     # one call a capacity rung
    finally:
        ds.close()


def test_a_rider_past_its_rung_goes_round_again(monkeypatch):
    monkeypatch.setattr(ftstore, "FT_MIN_CAP", 64)   # rungs 64, 256 or 512
    docs = zipf_corpus(23, 300)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        t = next(t for t in range(60)
                 if 64 < len(ref.postings(t)[0]) < 150)
        snap, (rider,) = _snap_and_riders(ds, [[t]], n=5)
        assert len(snap.rungs) == 2 and snap.rungs[0] == 64
        assert rider.cap == snap.rungs[1] >= len(ref.postings(t)[0])
        rider.cap = 64                         # as a stale df would choose
        over, riders = FT["overflows"], FT["riders"]
        (hits,) = ftdevice._dispatch([rider])
        assert FT["overflows"] - over == 1 and FT["riders"] - riders == 1
        assert rider.cap == snap.rungs[1]
        assert_top(_doc_of(snap, hits), *ref.search([t]), 5)
    finally:
        ds.close()


@pytest.mark.parametrize("cells", [1 << 28, 0])
def test_common_words_ride_the_dense_rung(monkeypatch, cells):
    """A rider whose rarest word is in more docs than the last listed rung
    holds scores every doc from the dense tf rows, where they fit; in one
    batch with riders of listed rungs; all are the reference's."""
    monkeypatch.setattr(ftstore, "FT_MIN_CAP", 64)
    monkeypatch.setattr(ftstore, "FT_DENSE_FROM", 64)
    monkeypatch.setattr(ftstore, "FT_DENSE_CELLS", cells)
    docs = zipf_corpus(31, 400, vocab=30)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        ref = Corpus(docs)
        df = [len(ref.postings(t)[0]) for t in range(30)]
        common = [t for t in range(30) if df[t] > 64]
        rare = [t for t in range(30) if 0 < df[t] <= 64]
        # (two words in more than half the docs would tie every score
        # at 0: the host's, as test_ties_at_the_cut shows)
        queries = [common[1:3], common[1:4], [common[0], common[2]],
                   [common[0], rare[0]], [rare[1]], [common[3]]]
        snap, riders = _snap_and_riders(ds, queries)
        dense = ftstore.pad_len(len(docs))
        assert (snap.rungs[-1] == dense) == bool(cells)
        assert [r.cap == dense for r in riders] == \
            [bool(cells)] * 3 + [False, False, bool(cells)]
        b0, r0 = FT["dense_bytes"], FT["riders"]
        out = ftdevice._dispatch(riders)
        for q, hits in zip(queries, out):
            assert hits is not None
            assert_top(_doc_of(snap, hits), *ref.search(q), 10)
        assert FT["riders"] - r0 == len(queries)
        assert (FT["dense_bytes"] > b0) == bool(cells)
    finally:
        ds.close()


def test_a_write_moves_the_version_and_the_next_search_sees_it():
    docs = zipf_corpus(17, 300)
    ds = Datastore("memory")
    try:
        load(ds, docs)
        first = search(ds, ["w0", "w1"], 5)
        ships = counters()[3]
        ds.execute("CREATE doc:5000 SET text = 'w0 w1 w0 w1 zebra'",
                   ns=NS, db=DB)
        got = search(ds, ["zebra", "w1"], 5)
        assert [d for d, _s in got] == [5000]
        after = counters()
        assert after[3] - ships == 1
        ref = Corpus(docs + [[0, 1, 0, 1, 10 ** 6]])
        assert_top(search(ds, ["w0", "w1"], 5), *ref.search([0, 1]), 5)
        assert first != search(ds, ["w0", "w1"], 5) or True
    finally:
        ds.close()


def test_the_hybrid_statement_end_to_end():
    docs = zipf_corpus(19, 300)
    rng = np.random.default_rng(19)
    emb = rng.standard_normal((len(docs), 8)).astype(np.float32)
    ds = Datastore("memory")
    try:
        load(ds, docs, emb=emb)
        ref = Corpus(docs)
        stmt = ("LET $vs = SELECT id, vector::distance::knn() AS d FROM doc "
                "WHERE emb <|10,40|> $qvec; LET $ft = SELECT id, "
                "search::score(1) AS s FROM doc WHERE text @1@ $qtext "
                "ORDER BY s DESC LIMIT 10; RETURN {vs: $vs, ft: $ft, fused: "
                "search::rrf([$vs, $ft], 10, 60)};")
        scoring = {t for t in range(60)
                   if 0 < len(ref.postings(t)[0]) < len(docs) // 3}
        picked = [i for i, d in enumerate(docs)
                  if len(scoring.intersection(d)) >= 2][:3]
        before = counters()
        for i in picked:
            q = [t for t in dict.fromkeys(docs[i]) if t in scoring][:2]
            qv = emb[i] + 0.01
            r = ds.execute(stmt, ns=NS, db=DB, vars={
                "qtext": " ".join(f"w{t}" for t in q),
                "qvec": qv.tolist()})
            assert all(x.error is None for x in r), r
            out = r[-1].result
            ft = [(int(x["id"].id), x["s"]) for x in out["ft"]]
            assert_top(ft, *ref.search(q), 10)
            vs = [int(x["id"].id) for x in out["vs"]]
            assert len(vs) == 10 and vs[0] == i
            fused = [(int(x["id"].id), x["rrf_score"]) for x in out["fused"]]
            assert fused == rrf([vs, [d for d, _s in ft]], 10, 60)
        assert counters()[0] - before[0] == 3
    finally:
        ds.close()


def test_string_record_ids_take_the_python_read():
    """Postings whose record ids are not integers are decoded by the
    interpreter (`ftpost.parse` says no); the answers are the same."""
    docs = zipf_corpus(29, 300)
    ds = Datastore("memory")
    try:
        ds.execute("DEFINE ANALYZER simple TOKENIZERS blank FILTERS "
                   "lowercase; DEFINE TABLE doc; DEFINE INDEX ft ON doc "
                   "FIELDS text FULLTEXT ANALYZER simple BM25", ns=NS, db=DB)
        rows = ",".join("{id: 'd%04d', text: '%s'}" % (
            i, " ".join(f"w{t}" for t in d)) for i, d in enumerate(docs))
        ds.execute(f"INSERT INTO doc [{rows}]", ns=NS, db=DB)
        ref = Corpus(docs)
        scoring = [t for t in range(60)
                   if 0 < len(ref.postings(t)[0]) < len(docs) // 3]
        before = counters()
        for q in ([scoring[0], scoring[3]], [scoring[5]]):
            r = ds.execute(SQL.format(n=10), ns=NS, db=DB,
                           vars={"q": " ".join(f"w{t}" for t in q)})
            got = [(int(x["id"].id[1:]), x["s"]) for x in r[0].result]
            assert_top(got, *ref.search(q), 10)
        assert counters()[0] - before[0] == 2
        snap = ftdevice._snapshots(ds)[(NS, DB, "doc", "ft")]
        assert snap.doc_ids[:3] == ["d0000", "d0001", "d0002"]
    finally:
        ds.close()
