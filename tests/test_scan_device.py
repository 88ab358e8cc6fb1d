"""The no-index vector scan on the device: `SELECT ..., vector::...(emb, $q)
AS s FROM t ORDER BY s LIMIT k` rides the scans' batcher into `vec_knn` on
the table's resident exact f32 column block (col.py, device/vecstore.py) and
comes back equal, id for id and in order, to the host `VecTopKScanOp` and to
a plain f64 reference. CPU only, the runner inline (as tests/conftest.py sets
it): answers and counts, never a time."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from surrealdb_tpu import Datastore, cnf
from surrealdb_tpu import col as colmod
from surrealdb_tpu import key as K
from surrealdb_tpu.device import get_supervisor, kernelstats, set_supervisor
from surrealdb_tpu.device.supervisor import DeviceSupervisor
from surrealdb_tpu.exec.batch import counters
from surrealdb_tpu.kvs.api import serialize
from surrealdb_tpu.val import RecordId

NS = DB = "s"
N, DIM = 600, 12
FN = {"cos_sim": "vector::similarity::cosine",
      "eucl": "vector::distance::euclidean",
      "dot": "vector::dot",
      "manh": "vector::distance::manhattan"}
SERVED = [("cos_sim", "DESC"), ("eucl", "ASC"), ("dot", "DESC")]


@pytest.fixture(autouse=True)
def small_tables_ride(monkeypatch):
    """A table of a few hundred rows is big enough for the device."""
    monkeypatch.setattr(cnf, "KNN_DEVICE_MIN_ROWS", 64)


def rows_of(seed: int, n: int = N, dim: int = DIM):
    return np.random.default_rng(seed).normal(size=(n, dim)) \
        .astype(np.float32)


def new_table(xs, tb: str = "v") -> Datastore:
    """Rows by the KV route, the f32 values as the f64 a document holds."""
    ds = Datastore("memory")
    ds.query(f"DEFINE TABLE {tb}", ns=NS, db=DB)
    txn = ds.transaction(write=True)
    for i, x in enumerate(xs):
        txn.set(K.record(NS, DB, tb, i), serialize(
            {"id": RecordId(tb, i), "emb": x.astype(np.float64).tolist()}))
    txn.commit()
    return ds


def scan_sql(kind: str, direction: str, limit: int, start: int = 0,
             tb: str = "v") -> str:
    tail = f" START {start}" if start else ""
    return (f"SELECT id, {FN[kind]}(emb, $q) AS s FROM {tb} "
            f"ORDER BY s {direction} LIMIT {limit}{tail}")


def ask(ds, text: str, q):
    rows = ds.query_one(text, ns=NS, db=DB,
                        vars={"q": np.asarray(q, np.float64).tolist()})
    return [r["id"].id for r in rows], [r["s"] for r in rows]


def reference(xs, ids, q, kind: str, direction: str, limit: int,
              start: int = 0):
    """Plain f64 numpy over the rows as the documents hold them."""
    x, q = np.asarray(xs, np.float64), np.asarray(q, np.float64)
    if kind == "cos_sim":
        s = (x @ q) / (np.linalg.norm(x, axis=1) * np.linalg.norm(q))
    elif kind == "eucl":
        s = np.linalg.norm(x - q[None, :], axis=1)
    elif kind == "manh":
        s = np.abs(x - q[None, :]).sum(axis=1)
    else:
        s = x @ q
    order = np.argsort(-s if direction == "DESC" else s, kind="stable")
    order = order[start:start + limit]
    return [ids[int(i)] for i in order], s[order].tolist()


def scan_counts() -> dict:
    return dict(kernelstats.SCAN)


def sup_counts() -> dict:
    st = get_supervisor().status()
    return {k: st[k] for k in ("host_routed", "fallbacks", "col_ships")}


def on_host(monkeypatch):
    """The host `VecTopKScanOp`: the routing policy says host."""
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")


@pytest.mark.parametrize("shape", ["plain", "start", "k_above_rows"])
@pytest.mark.parametrize("kind,direction", SERVED)
def test_device_scan_equals_host_scan_and_f64(monkeypatch, kind, direction,
                                              shape):
    xs = rows_of(11)
    ds = new_table(xs)
    q = rows_of(12, 1)[0]
    limit, start = {"plain": (10, 0), "start": (7, 5),
                    "k_above_rows": (N + 50, 0)}[shape]
    text = scan_sql(kind, direction, limit, start)
    before, routed = scan_counts(), sup_counts()
    got_ids, got_s = ask(ds, text, q)
    after = scan_counts()
    assert after["riders"] - before["riders"] == 1
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["rows_scored"] - before["rows_scored"] == N
    assert sup_counts()["host_routed"] == routed["host_routed"]
    want_ids, want_s = reference(xs, list(range(N)), q, kind, direction,
                                 limit, start)
    assert got_ids == want_ids
    assert np.allclose(got_s, want_s, rtol=0, atol=1e-12)
    # the winners came by the owned fetch, on either route
    assert counters(ds)["scan_rows_owned"] == len(got_ids)
    with monkeypatch.context() as m:
        on_host(m)
        host_ids, host_s = ask(ds, text, q)
    assert scan_counts() == after          # the host answered that one
    assert host_ids == got_ids and host_s == got_s
    assert counters(ds)["scan_rows_owned"] == 2 * len(got_ids)


def test_riders_of_different_limit_share_one_dispatch(monkeypatch):
    """Scans that arrive while a dispatch is in flight ride the next one
    together, k = the largest LIMIT among them, and each gets its own
    first rows."""
    xs = rows_of(21)
    ds = new_table(xs)
    qs = rows_of(22, 6)
    limits = [3, 10, 1, 25, 10, 7]
    ask(ds, scan_sql("cos_sim", "DESC", 10), qs[0])    # ship, compile
    real = colmod.device_topk
    first_in, release = threading.Event(), threading.Event()
    seen = []

    def held(col, metric, batch, k, *a):
        seen.append((len(batch), k))
        if len(seen) == 1:
            first_in.set()
            assert release.wait(30)
        return real(col, metric, batch, k, *a)

    monkeypatch.setattr(colmod, "device_topk", held)
    answers = [None] * len(limits)

    def one(j):
        answers[j] = ask(ds, scan_sql("cos_sim", "DESC", limits[j]),
                         qs[j])[0]

    threads = [threading.Thread(target=one, args=(j,))
               for j in range(len(limits))]
    threads[0].start()
    assert first_in.wait(30)
    for t in threads[1:]:
        t.start()
    from surrealdb_tpu.exec import stream

    batcher = stream._scan_batcher()
    for _ in range(3000):
        with batcher.cond:
            if len(batcher.queue) == len(limits) - 1:
                break
        threading.Event().wait(0.01)
    release.set()
    for t in threads:
        t.join(30)
    assert seen == [(1, limits[0]), (len(limits) - 1, max(limits[1:]))]
    for j, limit in enumerate(limits):
        assert answers[j] == reference(xs, list(range(N)), qs[j],
                                       "cos_sim", "DESC", limit)[0]


@pytest.mark.parametrize("write", ["INSERT", "UPDATE", "DELETE"])
def test_a_committed_write_is_seen_and_reships_once(write):
    xs = rows_of(31)
    ds = new_table(xs)
    q = xs[17] * 1.5 + 0.01 * rows_of(32, 1)[0]   # nearest: row 17, no tie
    text = scan_sql("cos_sim", "DESC", 5)
    sup = get_supervisor()
    ids, _s = ask(ds, text, q)
    assert ids[0] == 17
    base = sup_counts()["col_ships"]
    ask(ds, text, q)
    assert sup_counts()["col_ships"] == base        # resident: no ship
    column = next(iter(ds._vector_columns.values()))
    key = column.device_key("cosine")
    assert sup.inline_store(key).vecs.shape == (N, DIM)
    emb = (q.astype(np.float64) * 2).tolist()
    x2, live = xs.astype(np.float64), list(range(N))
    if write == "INSERT":
        ds.query("INSERT INTO v {id: 9001, emb: $e}", ns=NS, db=DB,
                 vars={"e": emb})
        x2, live = np.vstack([x2, emb]), live + [9001]
    elif write == "UPDATE":
        ds.query("UPDATE v:3 SET emb = $e", ns=NS, db=DB, vars={"e": emb})
        x2[3] = emb
    else:
        ds.query("DELETE v:17", ns=NS, db=DB)
        x2, live = np.delete(x2, 17, axis=0), live[:17] + live[18:]
    drops = sup.runner_status()["ops"].get("vec_drop", 0)
    for _ in range(3):
        got, _s = ask(ds, text, q)
        assert got == reference(x2, live, q, "cos_sim", "DESC", 5)[0]
    assert sup_counts()["col_ships"] == base + 1    # exactly one re-ship
    assert sup.runner_status()["ops"]["vec_drop"] == drops + 1
    assert column.superseded
    newer = next(iter(ds._vector_columns.values()))
    assert newer is not column and newer.version > column.version
    host = sup._inline_host
    assert host.vec[key][0] == [newer.version]      # the one block left
    assert host.vec[key][1].vecs.shape[0] == len(live)
    base = f"vec/col/{column.dev_base}/"
    assert [k for k in host.vec if k.startswith(base)] == [key]


@pytest.mark.parametrize("case", ["manh", "cos_asc", "ragged", "dirty_txn",
                                  "unhealthy"])
def test_the_host_answers_by_rule_and_it_is_counted(case):
    xs = rows_of(41)
    ds = new_table(xs)
    q = rows_of(42, 1)[0]
    kind, direction = {"manh": ("manh", "ASC"),
                       "cos_asc": ("cos_sim", "ASC")}.get(
                           case, ("cos_sim", "DESC"))
    text = scan_sql(kind, direction, 6)
    before, counts = scan_counts(), sup_counts()
    counter, old = "host_routed", None
    try:
        if case == "ragged":
            ds.query("CREATE v:odd SET emb = [1.0, 2.0]", ns=NS, db=DB)
            with pytest.raises(Exception, match="same dimension"):
                ask(ds, text, q)
        elif case == "dirty_txn":
            out = ds.execute(
                "BEGIN; CREATE v:9002 SET emb = $q; " + text + "; COMMIT;",
                ns=NS, db=DB,
                vars={"q": np.asarray(q, np.float64).tolist()})
            sel = [r for r in out if r.ok and isinstance(r.result, list)
                   and r.result and "s" in r.result[0]][-1]
            assert sel.result[0]["id"].id == 9002
        else:
            if case == "unhealthy":
                sick = DeviceSupervisor(mode="auto")
                sick.state = "degraded"
                old = set_supervisor(sick)
                counts, counter = sup_counts(), "fallbacks"
            got, _s = ask(ds, text, q)
            assert got == reference(xs, list(range(N)), q, kind, direction,
                                    6)[0]
        assert sup_counts()[counter] == counts[counter] + 1
        assert scan_counts() == before
    finally:
        if old is not None:
            set_supervisor(old)


@pytest.fixture()
def one_device(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "device_count", lambda: 1)


STORE_CFG = {"hbm_budget": 1 << 40, "score_budget": 1 << 26,
             "query_chunk": 64, "int8_oversample": 4, "block_rows": 1 << 20}


@pytest.mark.parametrize("metric", ["cosine", "euclidean", "dot"])
def test_an_exact_store_keeps_f32_rows_alone(one_device, metric):
    from surrealdb_tpu.device.vecstore import VecStore

    xs = rows_of(51, 300, 24)
    cfg = dict(STORE_CFG, exact=True)
    st = VecStore(f"t/exact/{metric}", xs, np.ones(300, bool), metric, 3.0,
                  cfg)
    st.ensure()
    assert st.device_rank is None and st.device_full is None
    assert st.rank_mode is None and st.device_vecs.dtype == np.float32
    assert st.device_nbytes() == VecStore.estimate_device_bytes(
        300, 24, 4, metric, cfg, 1) == 300 * 24 * 4 + 300
    # without the flag the same rows are a bf16 rank + f32 rescore store,
    # allocated for the capacity it grows in (768 rows at 300)
    assert VecStore.estimate_device_bytes(300, 24, 4, metric, STORE_CFG, 1) \
        == 6 * 768 * 24 + 9 * 768
    meta, (dists, ids) = st.knn(xs[:3], 5)
    assert meta == {"mode": "pairs", "rank_mode": None}
    assert ids[:, 0].tolist() == [0, 1, 2] or metric == "dot"


def clustered(n: int, dim: int, seed: int, std: float):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((max(n // 100, 8), dim), dtype=np.float32)
    xs = rng.standard_normal((n, dim), dtype=np.float32) * std \
        + centers[rng.integers(0, len(centers), n)]
    return xs.astype(np.float32), rng


def test_on_tight_clusters_the_exact_store_is_right_and_bf16_is_not(
        one_device):
    """The reason the flag exists: 4,096 x 64 in clusters of ~100 rows
    0.05 wide. The f32 store's top 10 is f64's on every query; a bf16
    rank of 26 candidates loses members of it."""
    from surrealdb_tpu.device.vecstore import VecStore

    xs, rng = clustered(4096, 64, 5, 0.05)
    qs = (xs[rng.integers(0, 4096, 64)]
          + 0.05 * rng.standard_normal((64, 64), dtype=np.float32)) \
        .astype(np.float32)
    x64, q64 = xs.astype(np.float64), qs.astype(np.float64)
    sims = (q64 @ x64.T) / (np.linalg.norm(q64, axis=1)[:, None]
                            * np.linalg.norm(x64, axis=1)[None, :])
    want = np.argsort(-sims, axis=1, kind="stable")[:, :10]
    got = {}
    for name, cfg in (("exact", dict(STORE_CFG, exact=True)),
                      ("bf16", STORE_CFG)):
        st = VecStore("t/clustered/" + name, xs, np.ones(4096, bool),
                      "cosine", 3.0, cfg)
        st.ensure()
        got[name] = st.knn(qs, 10)[1][1]
    assert np.array_equal(got["exact"], want)
    held = np.mean([len(set(a) & set(b)) for a, b in zip(got["bf16"], want)])
    assert held < 9.5, held


def knn_sql(where: str = "") -> str:
    return ("SELECT id, vector::distance::knn() AS d FROM v WHERE "
            + where + "emb <|5,COSINE|> $q")


@pytest.mark.parametrize("masked", [False, True])
def test_the_fused_no_index_knn_reads_the_resident_block(masked):
    """`<|k,COSINE|>` with no index: a whole-column group searches the
    column's resident block and ships nothing with the call; a group
    behind a predicate still ships its surviving rows (`brute_knn`)."""
    xs = rows_of(61)
    ds = Datastore("memory")
    ds.query("DEFINE TABLE v", ns=NS, db=DB)
    txn = ds.transaction(write=True)
    for i, x in enumerate(xs):
        txn.set(K.record(NS, DB, "v", i), serialize(
            {"id": RecordId("v", i), "g": i % 2,
             "emb": x.astype(np.float64).tolist()}))
    txn.commit()
    sup = get_supervisor()
    ops0 = dict(sup.runner_status()["ops"]) if sup._inline_host else {}
    ships0 = sup_counts()["col_ships"]
    text = knn_sql("g = 1 AND " if masked else "")
    for seed in (62, 63):
        q = rows_of(seed, 1)[0]
        rows = ds.query_one(text, ns=NS, db=DB,
                            vars={"q": q.astype(np.float64).tolist()})
        x64, q64 = xs.astype(np.float64), q.astype(np.float64)
        dist = 1.0 - (x64 @ q64) / (np.linalg.norm(x64, axis=1)
                                    * np.linalg.norm(q64))
        if masked:
            dist[0::2] = np.inf
        want = np.argsort(dist, kind="stable")[:5]
        assert [r["id"].id for r in rows] == want.tolist()
        assert np.allclose([r["d"] for r in rows], dist[want], atol=1e-5)
    ops1 = sup.runner_status()["ops"]
    moved = {k: ops1.get(k, 0) - ops0.get(k, 0)
             for k in ("brute_knn", "vec_knn", "vec_load")}
    if masked:
        assert moved == {"brute_knn": 2, "vec_knn": 0, "vec_load": 0}
        assert sup_counts()["col_ships"] == ships0
    else:
        assert moved == {"brute_knn": 0, "vec_knn": 2, "vec_load": 1}
        assert sup_counts()["col_ships"] == ships0 + 1
