"""A vector store that grows while it serves: the chip-resident block of
the bf16 ranking store is allocated at a capacity, written in place by
`vec_append` (device/vecstore.py, device/handlers.py), and the index engine
sends deltas instead of the whole block (idx/vector.py, device/supervisor.py).
CPU only, the runner inline, small sizes: answers, counts and object
identities, never a time."""

from __future__ import annotations

import sys
import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import Datastore
from surrealdb_tpu.device import get_supervisor, kernelstats, set_supervisor
from surrealdb_tpu.device.supervisor import DeviceSupervisor
from surrealdb_tpu.device.vecstore import VecStore, capacity_for
from surrealdb_tpu.idx import vector as V

NS = DB = "t"
DIM = 16
CFG = {"hbm_budget": 1 << 40, "score_budget": 1 << 26, "query_chunk": 8,
       "int8_oversample": 4, "block_rows": 1 << 20}
ARRAYS = ("device_full", "device_rank", "device_x2", "device_norms",
          "device_valid")


@pytest.fixture(autouse=True)
def one_device_and_small_stores(monkeypatch):
    """The served single-chip path (the suite's 8 virtual devices would
    send `ensure` down the mesh branches), a supervisor of this test's
    own, and stores of a few hundred rows big enough for the device."""
    import jax

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(V, "DEVICE_MIN_ROWS", 32)
    old = set_supervisor(DeviceSupervisor(mode="inline"))
    yield
    set_supervisor(old)


def rows_of(seed: int, n: int, dim: int = DIM):
    return np.random.default_rng(seed).normal(size=(n, dim)) \
        .astype(np.float32)


# -- (a) the store: appended == loaded fresh, bit for bit --------------------

# case -> (rows to append at the end, overwritten rows, tombstoned rows,
#          appended rows tombstoned in the same batch)
CASES = {
    "append_one_row": (1, (), (), ()),
    "append_across_a_ladder_step": (5, (), (), ()),
    "overwrite_a_row": (0, (7,), (), ()),
    "tombstone_a_row": (0, (), (11,), ()),
    "append_then_tombstone_in_one_batch": (3, (), (), (301,)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("metric", ["euclidean", "cosine"])
def test_appended_store_equals_the_store_loaded_fresh(metric, case):
    grow, over, dead, dead_new = CASES[case]
    n = 300
    xs = rows_of(3, n + grow)
    new_rows = rows_of(4, len(over))
    st = VecStore("a", xs[:n].copy(), np.ones(n, bool), metric, 3.0, CFG)
    st.ensure()
    assert st.growable and st.capacity == capacity_for(n) == 768
    final = xs.copy()
    valid = np.ones(n + grow, bool)
    for j, r in enumerate(over):
        final[r] = new_rows[j]
    valid[list(dead) + list(dead_new)] = False
    touched = np.array(list(over) + list(dead) + list(range(n, n + grow)),
                       np.int32)
    assert st.append(final[touched], touched, valid[touched])
    assert st.shape == (n + grow, DIM) and st.capacity == 768
    fresh = VecStore("b", final, valid, metric, 3.0, CFG)
    fresh.ensure()
    assert fresh.capacity == st.capacity
    for name in ARRAYS:
        got, want = getattr(st, name), getattr(fresh, name)
        assert (got is None) == (want is None), name
        if got is not None:
            # the valid region, and what lies past it is masked out
            assert np.array_equal(
                np.asarray(got)[:n + grow].view(np.uint8),
                np.asarray(want)[:n + grow].view(np.uint8)), name
    assert not np.asarray(st.device_valid)[n + grow:].any()
    near = list(over) + list(dead) + list(dead_new) \
        + list(range(n, n + grow)) + [0, 150]
    qs = final[near] + rows_of(5, len(near)) * 0.01
    meta, bufs = st.knn(qs, 10)
    want_meta, want = fresh.knn(qs, 10)
    assert meta == want_meta == {"mode": "pairs", "rank_mode": "bf16"}
    for got, ref in zip(bufs, want):
        assert np.array_equal(got.view(np.int32), ref.view(np.int32))
    ids = bufs[1]
    assert not set(ids.ravel().tolist()) & set(dead + dead_new)
    for j, r in enumerate(near):
        assert (ids[j, 0] == r) == bool(valid[r])


def test_capacity_is_a_function_of_the_rows_with_a_step_of_headroom():
    last = 0
    for n in (1, 31, 64, 300, 1024, 5000, 98_976, 100_000, 106_495,
              106_496, 1_000_000, 10_000_000):
        cap = capacity_for(n)
        step = max(256, (1 << (n - 1).bit_length()) // 32)
        assert cap % step == 0 and step <= cap - n <= 2 * step
        assert cap >= last
        last = cap
    assert capacity_for(98_976) == capacity_for(100_000) == 106_496
    # budgeted, and reported, at what is allocated
    st = VecStore("c", rows_of(1, 300), np.ones(300, bool), "euclidean",
                  3.0, CFG)
    est = VecStore.estimate_device_bytes(300, DIM, 4, "euclidean", CFG, 1)
    assert est == 6 * 768 * DIM + 9 * 768 == st.device_nbytes()
    st.ensure()
    assert st.device_nbytes() == est and st.nbytes() == 300 * DIM * 4


def test_a_store_that_cannot_grow_says_so():
    xs = rows_of(2, 300)
    ones = np.ones(300, bool)
    exact = VecStore("e", xs, ones, "euclidean", 3.0, dict(CFG, exact=True))
    int8 = VecStore("i", xs, ones, "cosine", 3.0, dict(CFG, hbm_budget=1))
    for st in (exact, int8):
        st.ensure()
        assert not st.growable and st.capacity == 300
        assert not st.append(xs[:1], np.array([300], np.int32), ones[:1])
    grows = VecStore("g", xs, ones, "euclidean", 3.0, CFG)
    grows.ensure()
    assert not grows.append(xs[:1], np.array([768], np.int32), ones[:1])
    assert not grows.append(xs[:1], np.array([-1], np.int32), ones[:1])
    assert grows.shape == (300, DIM)
    assert grows.append(xs[:1], np.array([767], np.int32), ones[:1])
    assert grows.shape == (768, DIM)


# -- the op and the supervisor's hand-over -----------------------------------


def loader_of(xs, valid, metric="euclidean"):
    def loader():
        return "vec_load", {"metric": metric, "mink_p": 3.0, "cfg": CFG}, \
            [xs, valid.astype(np.uint8)]
    return loader


def knn_ids(sup, key, tag, q, k=5):
    t, _meta, bufs = sup.call("vec_knn", {"key": key, "tag": tag, "k": k},
                              [np.asarray(q, np.float32)[None, :]])
    assert t == "ok"
    return bufs[1][0].tolist()


def vec_counts(sup) -> dict:
    st = sup.status()
    return {k: st[k] for k in ("vec_appends", "vec_append_rows",
                               "vec_append_bytes", "vec_full_ships")}


def test_a_delta_moves_the_tag_and_a_stranger_is_stale():
    sup = get_supervisor()
    xs = rows_of(7, 310)
    ones = np.ones(310, bool)
    sup.ensure_loaded("vec/k", [1, 0], loader_of(xs[:300], ones[:300]))
    assert vec_counts(sup)["vec_full_ships"] == 1
    before = dict(kernelstats.APPEND)

    def delta():
        idx = np.arange(300, 310, dtype=np.int32)
        return [xs[idx], idx, ones[idx].astype(np.uint8)]

    sup.ensure_loaded("vec/k", [2, 0], None, delta=([1, 0], delta))
    assert vec_counts(sup) == {
        "vec_appends": 1, "vec_append_rows": 10,
        "vec_append_bytes": 10 * DIM * 4 + 10 * 4 + 10,
        "vec_full_ships": 1}
    assert {k: kernelstats.APPEND[k] - before[k] for k in before} == {
        "appends": 1, "rows": 10, "bytes": 10 * DIM * 4 + 10 * 4 + 10}
    assert knn_ids(sup, "vec/k", [2, 0], xs[305])[0] == 305
    assert sup.runner_status()["vec"]["vec/k"] == {
        "rows": 310, "capacity": 768}
    # the old tag is gone with the delta; a delta from it is `stale`
    t, _m, _b = sup.call("vec_knn", {"key": "vec/k", "tag": [1, 0], "k": 5},
                         [xs[:1]])
    assert t == "stale"
    t, _m, _b = sup.call(
        "vec_append", {"key": "vec/k", "tag_from": [1, 0], "tag": [3, 0]},
        delta())
    assert t == "stale"
    t, _m, _b = sup.call(
        "vec_append", {"key": "vec/nobody", "tag_from": [1, 0],
                       "tag": [3, 0]}, delta())
    assert t == "stale"
    from surrealdb_tpu.telemetry import stage_snapshot

    assert stage_snapshot()["vec_append"]["count"] >= 1


def test_a_delta_the_runner_cannot_take_becomes_one_whole_load():
    """`full`: the rows pass the capacity. `stale`: the runner lost the
    block (a restart holds nothing). Either way the supervisor forgets
    the key and the loader's whole block follows, at the capacity of the
    rows it now has."""
    sup = get_supervisor()
    xs = rows_of(8, 900)
    ones = np.ones(900, bool)
    sup.ensure_loaded("vec/f", [1, 0], loader_of(xs[:300], ones[:300]))

    def delta(lo, hi):
        def make():
            idx = np.arange(lo, hi, dtype=np.int32)
            return [xs[idx], idx, ones[idx].astype(np.uint8)]
        return make

    # 300 -> 800 rows passes the 768 the block was allocated for
    sup.ensure_loaded("vec/f", [2, 0], loader_of(xs[:800], ones[:800]),
                      delta=([1, 0], delta(300, 800)))
    assert vec_counts(sup)["vec_full_ships"] == 2
    assert vec_counts(sup)["vec_appends"] == 0
    assert sup.runner_status()["vec"]["vec/f"] == {
        "rows": 800, "capacity": capacity_for(800)}
    assert knn_ids(sup, "vec/f", [2, 0], xs[799])[0] == 799
    # the runner restarted: it holds nothing, the supervisor's record of
    # what it holds went with it
    sup._inline_host.vec.clear()
    sup.forget("vec/f")
    sup.ensure_loaded("vec/f", [3, 0], loader_of(xs[:810], ones[:810]),
                      delta=([2, 0], delta(800, 810)))
    assert vec_counts(sup)["vec_full_ships"] == 3
    # ... or only the runner knows it did (an eviction): `stale`
    sup._inline_host.vec.clear()
    sup.ensure_loaded("vec/f", [4, 0], loader_of(xs[:820], ones[:820]),
                      delta=([3, 0], delta(810, 820)))
    assert vec_counts(sup)["vec_full_ships"] == 4
    assert vec_counts(sup)["vec_appends"] == 0
    assert knn_ids(sup, "vec/f", [4, 0], xs[815])[0] == 815


def test_prewarm_compiles_the_append_ladder_with_the_search_programs():
    sup = get_supervisor()
    xs = rows_of(9, 1100)  # a capacity no other test of this file has
    sup.ensure_loaded("vec/w", [1, 0], loader_of(xs, np.ones(1100, bool)))
    seen = set(kernelstats._SEEN)
    t, meta, _b = sup.call("vec_prewarm", {"key": "vec/w", "tag": [1, 0],
                                           "buckets": [8], "k": 10})
    assert t == "ok" and meta["warmed"] == [8]
    new = {k for k in kernelstats._SEEN - seen if k[0] == "vec_append"}
    assert sorted(k[1][1] for k in new) == [1, 2, 4, 8]
    # warming wrote nothing
    assert sup.runner_status()["vec"]["vec/w"] == {
        "rows": 1100, "capacity": 1536}
    assert knn_ids(sup, "vec/w", [1, 0], xs[7])[0] == 7


# -- the index engine --------------------------------------------------------


def new_index(n: int, metric: str = "EUCLIDEAN", seed: int = 20):
    ds = Datastore("memory")
    ds.query(f"DEFINE TABLE pts; DEFINE INDEX ix ON pts FIELDS emb HNSW "
             f"DIMENSION {DIM} DIST {metric} TYPE F32", ns=NS, db=DB)
    xs = rows_of(seed, n)
    for s in range(0, n, 100):
        ds.query("INSERT INTO pts $rows", ns=NS, db=DB, vars={"rows": [
            {"id": i, "emb": xs[i].tolist()}
            for i in range(s, min(s + 100, n))]})
    return ds, xs


def knn(ds, q, k=10):
    rows = ds.query(
        f"SELECT id, vector::distance::knn() AS d FROM pts "
        f"WHERE emb <|{k}|> $q", ns=NS, db=DB,
        vars={"q": np.asarray(q, np.float64).tolist()})[0]
    return [r["id"].id for r in rows], [r["d"] for r in rows]


def insert(ds, i, vec):
    return ds.query("INSERT INTO pts {id: $id, emb: $v}", ns=NS, db=DB,
                    vars={"id": int(i), "v": vec.tolist()})[0]


def engine(ds):
    return ds.vector_indexes[(NS, DB, "pts", "ix")]


def brute(xs, ids, q, k=10):
    d = np.linalg.norm(np.asarray(xs, np.float64)
                       - np.asarray(q, np.float64)[None, :], axis=1)
    order = np.argsort(d, kind="stable")[:k]
    return [ids[int(i)] for i in order], d[order]


@pytest.mark.parametrize("metric", ["EUCLIDEAN", "COSINE"])
def test_writes_reach_the_chip_as_deltas_and_are_found(metric):
    ds, xs = new_index(300, metric)
    more = rows_of(21, 40)
    sup = get_supervisor()
    assert knn(ds, xs[5])[0][0] == 5
    ix = engine(ds)
    assert vec_counts(sup)["vec_full_ships"] == 1
    assert ix._dev_capacity == capacity_for(300)
    # an insert, an overwrite, a tombstone: one delta each, at the next
    # search, never a whole block
    assert insert(ds, 300, more[0])[0]["id"].id == 300
    assert knn(ds, more[0])[0][0] == 300
    ds.query("UPDATE pts:7 SET emb = $v", ns=NS, db=DB,
             vars={"v": more[1].tolist()})
    ids, dists = knn(ds, more[1])
    assert ids[0] == 7 and dists[0] < 1e-6
    ds.query("DELETE pts:9", ns=NS, db=DB)
    assert 9 not in knn(ds, xs[9])[0]
    # several writes between two searches are folded into one delta
    for j in range(2, 12):
        insert(ds, 299 + j, more[j])
    assert knn(ds, more[11])[0][0] == 310
    counts = vec_counts(sup)
    assert counts["vec_full_ships"] == 1 and counts["vec_appends"] == 4
    assert counts["vec_append_rows"] == 1 + 1 + 1 + 10
    assert sup.runner_status()["vec"][ix._dev_key] == {
        "rows": 311, "capacity": capacity_for(300)}
    # the answers are the host path's
    final = np.concatenate([xs, more[:1], more[2:12]])
    final[7] = more[1]
    for q in (final[3], final[7], final[305]):
        got, got_d = knn(ds, q)
        with ix.rw.read():
            host = ix._host_knn_single(np.asarray(q, np.float32), 10)
        assert got == [r.id for r, _d in host]
        assert np.allclose(got_d, [d for _r, d in host], rtol=1e-5,
                           atol=1e-6)
    assert sup.status()["fallbacks"] == 0


def test_rows_past_the_capacity_cost_one_whole_load_at_the_next():
    ds, xs = new_index(700)
    sup = get_supervisor()
    more = rows_of(22, 400)
    assert knn(ds, xs[0])[0][0] == 0
    ix = engine(ds)
    assert ix._dev_capacity == capacity_for(700) == 1024
    before = [knn(ds, q)[0] for q in xs[:8]]
    for s in range(0, 324, 108):        # 1,024 rows: the block is full
        ds.query("INSERT INTO pts $rows", ns=NS, db=DB, vars={"rows": [
            {"id": 700 + j, "emb": more[j].tolist()}
            for j in range(s, s + 108)]})
        assert knn(ds, more[s + 107])[0][0] == 700 + s + 107
    assert vec_counts(sup)["vec_full_ships"] == 1
    insert(ds, 1024, more[324])         # one more than it holds
    assert knn(ds, more[324])[0][0] == 1024
    counts = vec_counts(sup)
    assert counts["vec_full_ships"] == 2 and counts["vec_appends"] == 3
    assert ix._dev_capacity == capacity_for(1025) > 1025
    # from there deltas again, and the old answers where no new row is
    # nearer
    insert(ds, 1025, more[325])
    assert knn(ds, more[325])[0][0] == 1025
    assert vec_counts(sup)["vec_full_ships"] == 2
    assert vec_counts(sup)["vec_appends"] == 4
    final = np.concatenate([xs, more[:326]])
    for q, old in zip(xs[:8], before):
        want = brute(final, list(range(1026)), q)[0]
        assert knn(ds, q)[0] == want
        assert [i for i in want if i < 700] == [i for i in old if i in want]


def test_a_runner_that_lost_the_block_gets_the_whole_of_it():
    ds, xs = new_index(300)
    sup = get_supervisor()
    more = rows_of(23, 4)
    assert knn(ds, xs[0])[0][0] == 0
    insert(ds, 300, more[0])
    assert knn(ds, more[0])[0][0] == 300
    assert vec_counts(sup) == {
        "vec_appends": 1, "vec_append_rows": 1,
        "vec_append_bytes": DIM * 4 + 4 + 1, "vec_full_ships": 1}
    # a restart: the runner holds nothing and the supervisor knows it
    sup._inline_host.vec.clear()
    sup.forget(engine(ds)._dev_key)
    insert(ds, 301, more[1])
    assert knn(ds, more[1])[0][0] == 301
    assert vec_counts(sup)["vec_full_ships"] == 2
    assert vec_counts(sup)["vec_appends"] == 1
    # an eviction the supervisor has not heard of: the delta is `stale`,
    # then the search itself is, and the whole block goes once
    sup._inline_host.vec.clear()
    assert knn(ds, more[0])[0][0] == 300
    assert vec_counts(sup)["vec_full_ships"] == 3
    insert(ds, 302, more[2])
    sup._inline_host.vec.clear()
    assert knn(ds, more[2])[0][0] == 302
    assert vec_counts(sup)["vec_full_ships"] == 4
    assert sup.status()["fallbacks"] == 0


def test_a_gap_too_wide_for_a_delta_ships_the_whole_block(monkeypatch):
    monkeypatch.setattr(V, "DELTA_MAX_ROWS", 8)
    ds, xs = new_index(300)
    sup = get_supervisor()
    more = rows_of(24, 20)
    assert knn(ds, xs[0])[0][0] == 0
    for j in range(9):
        insert(ds, 300 + j, more[j])
    assert knn(ds, more[8])[0][0] == 308
    assert vec_counts(sup)["vec_full_ships"] == 2
    assert vec_counts(sup)["vec_appends"] == 0


def test_the_host_arrays_grow_amortised():
    """1,000 single-row syncs reallocate the host matrix a handful of
    times, not 1,000: `vecs` / `valid` are views of buffers with room."""
    ds, xs = new_index(300)
    more = rows_of(25, 1000)
    assert knn(ds, xs[0])[0][0] == 0
    ix = engine(ds)
    places = set()  # where the matrix lay after each sync
    for j in range(1000):
        insert(ds, 300 + j, more[j])
        ix.sync(ctx_of(ds))
        assert ix.vecs.base is ix._vec_buf and len(ix.vecs) == 301 + j
        assert ix.valid.base is ix._valid_buf and ix.live == 301 + j
        places.add(ix.vecs.ctypes.data)
    assert len(places) <= 8  # a quarter more each time: 301 -> 1,300
    assert np.array_equal(ix.vecs[:300], xs)
    assert np.array_equal(ix.vecs[300:], more)
    assert ix.valid.all() and len(ix.rids) == 1300


class ctx_of:
    """What `TpuVectorIndex.sync` needs of a context: a transaction
    and the datastore."""

    def __init__(self, ds):
        self.ds = ds
        self.txn = ds.transaction(write=False)

    def __del__(self):
        self.txn.cancel()


def test_a_view_taken_before_an_append_keeps_its_rows():
    ds, xs = new_index(300)
    assert knn(ds, xs[0])[0][0] == 0
    ix = engine(ds)
    insert(ds, 300, xs[0] + 1)
    knn(ds, xs[0])
    taken = ix.vecs
    for j in range(200):
        insert(ds, 301 + j, xs[j] + 2)
    knn(ds, xs[0])
    assert len(taken) == 301 and len(ix.vecs) == 501
    assert np.array_equal(taken[:300], xs)


# -- versions: burnt, and never backwards ------------------------------------


def count_rebuilds(monkeypatch):
    calls = []
    real = V.TpuVectorIndex._rebuild

    def rebuild(self, ctx):
        calls.append(self.version)
        return real(self, ctx)

    monkeypatch.setattr(V.TpuVectorIndex, "_rebuild", rebuild)
    return calls


def test_a_cancelled_writer_burns_a_version_and_costs_no_rebuild(
        monkeypatch):
    ds, xs = new_index(300)
    assert knn(ds, xs[0])[0][0] == 0
    rebuilds = count_rebuilds(monkeypatch)
    more = rows_of(26, 3)
    out = ds.execute("BEGIN; INSERT INTO pts {id: 900, emb: $v}; CANCEL",
                     ns=NS, db=DB, vars={"v": more[0].tolist()})
    assert out[-1].error is None
    insert(ds, 901, more[1])            # the version after the burnt one
    assert knn(ds, more[1])[0][0] == 901
    assert 900 not in knn(ds, more[0])[0]
    assert rebuilds == []
    assert vec_counts(get_supervisor())["vec_full_ships"] == 1
    # a gap nobody burnt (here: a log entry that went missing) is still
    # resolved by the rebuild
    insert(ds, 902, more[2])
    ix = engine(ds)
    from surrealdb_tpu import key as K

    txn = ds.transaction(write=True)
    txn.delete(K.ix_state(NS, DB, "pts", "ix", b"hl",
                          K.enc_u64(ix.version + 1)))
    txn.commit()
    insert(ds, 903, more[0])
    assert knn(ds, more[0])[0][0] == 903
    assert len(rebuilds) == 1


def test_an_older_snapshot_does_not_take_rows_from_the_cache(monkeypatch):
    """A search whose transaction began before a write committed comes
    to sync after a search that began later: the cache is ahead of its
    snapshot and stays there. Stepping back would hide the row from the
    riders that already synced to it."""
    ds, xs = new_index(300)
    assert knn(ds, xs[0])[0][0] == 0
    rebuilds = count_rebuilds(monkeypatch)
    ix = engine(ds)
    old = ctx_of(ds)                    # snapshot before the write
    row = rows_of(27, 1)[0]
    insert(ds, 300, row)
    assert knn(ds, row)[0][0] == 300    # a later search syncs forward
    ver = ix.version
    ix.sync(old)                        # the older one arrives
    assert ix.version == ver and rebuilds == []
    assert knn(ds, row)[0][0] == 300
    # a cache ahead of what is COMMITTED (a writer searched inside its
    # transaction, then cancelled) is still rebuilt
    out = ds.execute(
        "BEGIN; INSERT INTO pts {id: 301, emb: $v}; "
        "SELECT id FROM pts WHERE emb <|3|> $v; CANCEL",
        ns=NS, db=DB, vars={"v": (row + 1).tolist()})
    assert out[-1].error is None and ix.version == ver + 1
    assert 301 not in knn(ds, row + 1)[0]
    assert len(rebuilds) == 1 and ix.version == ver


def test_concurrent_inserts_are_retried_not_refused():
    """Writers of one indexed table conflict on its version key; an
    auto-commit statement that loses is run again (exec/executor.py
    CONFLICT_RETRIES), so a pool of writers sees its rows created."""
    ds, xs = new_index(64)
    more = rows_of(28, 160)
    errors = []

    def writer(w):
        for j in range(w, 160, 4):
            out = ds.execute("INSERT INTO pts {id: $id, emb: $v}", ns=NS,
                             db=DB, vars={"id": 1000 + j,
                                          "v": more[j].tolist()})
            if out[0].error is not None:
                errors.append(out[0].error)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(4)]
    # the writers have to overlap for their commits to conflict: since the
    # native memtable's short calls keep the interpreter lock (PR 36) a
    # statement gives it away nowhere, so the test switches threads as
    # often as those calls used to (~120 retries, as at the parent)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(interval)
    # what was refused after every retry was refused as a conflict
    assert all("can be retried" in e for e in errors), errors[:2]
    assert len(errors) <= 8
    n = ds.query("SELECT count() FROM pts GROUP ALL", ns=NS, db=DB)[0]
    assert n[0]["count"] == 64 + 160 - len(errors)
    # counted, and timed: `stmt_envelope` holds a statement's attempts
    from surrealdb_tpu.telemetry import stage_snapshot

    assert ds.telemetry.get("index_commit_retries") \
        == stage_snapshot()["commit_retry"]["count"] >= len(errors)
    assert ds.telemetry.get("index_commit_retries_exhausted") == len(errors)


def test_only_a_vector_indexs_conflict_is_run_again():
    """The retry is for the conflict a vector index makes by design. A
    statement that loses its commit over anything else (here: two
    writers of one record in a table without one) is refused at once,
    as ever, and the floor is never taken."""
    from surrealdb_tpu.err import TxConflict
    from surrealdb_tpu.exec import executor as X
    from surrealdb_tpu.telemetry import stage_snapshot

    ds = Datastore("memory")
    ds.query("DEFINE TABLE plain; CREATE plain:1 SET n = 0", ns=NS, db=DB)
    tries = []
    real = X.Executor._commit_and_publish

    def commit_after_a_rival(self, cur):
        # another writer of the same record commits first
        tries.append(1)
        rival = ds.transaction(write=True)
        for k in list(cur.btx.writes):
            rival.btx.set(k, b"\x00rival")
        rival.commit()
        return real(self, cur)

    before = stage_snapshot().get("commit_retry", {}).get("count", 0)
    X.Executor._commit_and_publish = commit_after_a_rival
    try:
        out = ds.execute("UPDATE plain:1 SET n = 1", ns=NS, db=DB)
    finally:
        X.Executor._commit_and_publish = real
    assert "can be retried" in out[0].error and len(tries) == 1
    assert ds.telemetry.get("index_commit_retries") == 0
    assert stage_snapshot().get("commit_retry", {}).get("count", 0) == before
    assert not ds.retry_floor.locked()
    # the stores say it with a type, not only with a message
    a, b = ds.transaction(write=True), ds.transaction(write=True)
    a.set_val(b"k", 1)
    b.set_val(b"k", 2)
    a.commit()
    with pytest.raises(TxConflict, match="can be retried"):
        b.commit()
    b.cancel()


# -- (d) writers and readers together, against a plain brute force -----------


def test_writers_and_readers_see_what_the_visibility_rule_allows(
        monkeypatch):
    """4 writers and 8 readers on one index. For a search sent at s and
    answered at r, and a row whose INSERT was sent at b and acknowledged
    at a: it must be found if a < s, may be if b <= r, never otherwise.
    Ids against a plain f64 brute force over must-see rows and the
    may-see rows the answer itself holds; every distance the f64
    distance of its row; no rebuild, no answer from the host."""
    n0, per = 1024, 48
    ds, xs = new_index(n0, seed=30)
    new = rows_of(31, 4 * per) * 0.5 + xs[:4 * per]
    assert knn(ds, xs[0])[0][0] == 0
    rebuilds = count_rebuilds(monkeypatch)
    sup = get_supervisor()
    ships0 = vec_counts(sup)["vec_full_ships"]
    sent, acked = {}, {}
    searches = []
    done = threading.Event()
    failures = []

    def writer(w):
        for j in range(w * per, (w + 1) * per):
            rid = n0 + j
            while True:
                sent.setdefault(rid, time.monotonic())
                out = ds.execute("INSERT INTO pts {id: $id, emb: $v}",
                                 ns=NS, db=DB,
                                 vars={"id": rid, "v": new[j].tolist()})
                if out[0].error is None:
                    break
                if "can be retried" not in out[0].error:
                    failures.append(out[0].error)
                    return
            acked[rid] = time.monotonic()
            time.sleep(0.01)

    def reader(seed):
        rng = np.random.default_rng(seed)
        while not done.is_set():
            known = list(acked)
            if known and rng.random() < 0.7:
                rid = known[int(rng.integers(len(known)))]
                q = new[rid - n0] + rows_of(int(rng.integers(1 << 30)),
                                            1)[0] * 0.01
            else:
                q = xs[int(rng.integers(n0))] + 0.01
            s = time.monotonic()
            try:
                got = knn(ds, q)
            except Exception as e:  # the reader's thread: kept for the end
                failures.append(repr(e))
                return
            searches.append((s, time.monotonic(), q, got))

    readers = [threading.Thread(target=reader, args=(40 + r,))
               for r in range(8)]
    writers = [threading.Thread(target=writer, args=(w,)) for w in range(4)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    # the first searches wait for their programs to compile: read on
    # until enough of them came after a write
    t_end = time.monotonic() + 60
    while len(searches) < 150 and time.monotonic() < t_end:
        time.sleep(0.05)
    done.set()
    for t in readers:
        t.join()
    assert not failures, failures[:3]
    assert len(acked) == 4 * per
    assert len(searches) >= 50
    everything = np.concatenate([xs, new])
    ids_all = list(range(n0 + 4 * per))
    readback = 0
    for s, r, q, (ids, dists) in searches:
        must = [i for i in ids_all
                if i < n0 or (i in acked and acked[i] < s)]
        may = {i for i in ids_all if i >= n0 and i not in must
               and sent.get(i, np.inf) <= r}
        assert all(i in may or i < n0 or acked[i] < s for i in ids), \
            ("a row seen before it was sent", ids)
        pool = must + sorted(may & set(ids))
        want, want_d = brute(everything[pool], pool, q)
        kth = want_d[-1]
        row_d = np.linalg.norm(
            everything[ids].astype(np.float64)
            - np.asarray(q, np.float64)[None, :], axis=1)
        assert np.allclose(dists, row_d, rtol=1e-5, atol=1e-6)
        # a must-see row the search sits on is the nearest by far
        if want_d[0] < 0.2 and want[0] >= n0:
            readback += 1
            assert ids[0] == want[0], (ids, want)
        assert sum(1 for i, d in zip(ids, row_d)
                   if i in want or d <= kth * (1 + 1e-9)) >= 9
    assert readback >= 10
    assert rebuilds == []
    assert sup.status()["fallbacks"] == 0
    counts = vec_counts(sup)
    assert counts["vec_appends"] >= 1
    assert 4 * per >= counts["vec_append_rows"] >= 1
    # 1,024 rows are allocated 1,536: the 192 new ones fit
    assert counts["vec_full_ships"] == ships0


# -- a dispatch lends its read lock while it waits for the runner ------------


def test_rwlock_lends_a_read_hold_to_a_waiting_writer():
    from surrealdb_tpu.utils.rwlock import RWLock

    rw = RWLock()
    order = []
    wrote = threading.Event()

    def writer():
        with rw.write():
            order.append("write")
        wrote.set()

    with rw.read():
        t = threading.Thread(target=writer)
        t.start()
        assert not wrote.wait(0.1)          # a reader is in the way
        assert rw.lend_read() is True
        assert rw.lend_read() is False      # one hold, lent once
        assert wrote.wait(5)                # lent: the writer passes
        rw.reclaim_read()
        order.append("read again")
    t.join()
    assert order == ["write", "read again"]
    with rw.write():
        # the write hold is the holder's read permission: nothing to lend
        assert rw.lend_read() is False
        with rw.read():
            pass
    # an outer read hold would keep the writer out all the same, and no
    # hold is nothing to lend: refused, nothing given up
    assert rw.lend_read() is False
    with rw.read(), rw.read():
        assert rw.lend_read() is False
        assert rw._readers == 2
    with rw.read():                          # balanced: free for both again
        assert rw._readers == 1
    with rw.write():
        pass


class WaitingRunner:
    """A supervisor whose runner takes its time: `call` runs `sent` once
    the request is queued, as a live one does, then waits to be let go."""

    def __init__(self, capacity):
        self.capacity = capacity
        self.queued = threading.Event()
        self.go = threading.Event()
        self.lent = None

    def ensure_loaded(self, key, tag, loader, delta=None):
        pass

    def call(self, op, meta, bufs=(), timeout_s=None, sent=None):
        if sent is not None:
            sent()
        self.lent = sent is not None
        self.queued.set()
        assert self.go.wait(10)
        b = len(bufs[0])
        return "ok", {"rank_mode": "bf16", "capacity": self.capacity}, [
            np.tile(np.array([0.5, 1.5], np.float32), (b, 1)),
            np.tile(np.array([3, 298], np.int32), (b, 1))]


def test_a_sync_does_not_wait_out_a_dispatchs_round_trip():
    ds, xs = new_index(300)
    assert knn(ds, xs[0])[0][0] == 0
    ix = engine(ds)
    real = get_supervisor()
    for grows, waits in ((True, False), (False, True)):
        fake = WaitingRunner(ix._dev_capacity)
        ix._dev_capacity = fake.capacity if grows else None
        set_supervisor(fake)
        out = []

        def dispatch():
            with ix.rw.read():
                out.append(ix._device_knn_batch(xs[:2], 2))

        t = threading.Thread(target=dispatch)
        t.start()
        assert fake.queued.wait(10) and fake.lent is grows
        # a write arrives while the search is with the runner
        synced = threading.Event()

        def sync():
            with ix.lock, ix.rw.write():
                ix.rids.append("a row that came meanwhile")
            synced.set()

        w = threading.Thread(target=sync)
        w.start()
        # a block that grows in place lends its lock; any other store
        # (an int8 answer is rescored from the host rows) keeps it
        assert synced.wait(0.3) is not waits
        fake.go.set()
        t.join()
        w.join()
        assert synced.is_set()
        # mapped by the rows as they were when the search was queued
        assert [[r.id for r, _d in row] for row in out[0]] == [[3, 298]] * 2
        ix.rids.pop()
        set_supervisor(real)
    assert knn(ds, xs[5])[0][0] == 5


class LateReplies:
    """The test's supervisor with a reply on its way: the runner has
    served a `vec_knn` (in order, as a live one does), `sent` has run
    (the caller's lock is lent), and the reply is held back until
    `go`. Everything else goes straight through."""

    def __init__(self, real):
        self.real = real
        self.hold = threading.Event()   # set: hold the next reply back
        self.queued = threading.Event()
        self.go = threading.Event()

    def __getattr__(self, name):
        return getattr(self.real, name)

    def call(self, op, meta, bufs=(), timeout_s=None, sent=None):
        reply = self.real.call(op, meta, bufs, timeout_s=timeout_s)
        if op == "vec_knn" and sent is not None and self.hold.is_set():
            self.hold.clear()
            sent()
            self.queued.set()
            assert self.go.wait(10)
        return reply


@pytest.mark.parametrize("redispatched", [False, True],
                         ids=["the_copy_dropped", "the_next_block_shipped"])
def test_a_reply_that_outlived_its_block_brings_nothing_back(
        redispatched, monkeypatch):
    """A search whose lock is lent comes back after a sync has passed
    the capacity and dropped the device copy (or after the next search
    has shipped the next block): the old block's capacity stays gone,
    the next dispatch ships or appends, and every search answers."""
    from surrealdb_tpu import cnf

    # a second dispatch may launch beside the one in flight
    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE_MIN", 1)
    ds, xs = new_index(700)
    assert knn(ds, xs[0])[0][0] == 0
    ix = engine(ds)
    assert ix._dev_capacity == 1024
    sup = LateReplies(get_supervisor())
    set_supervisor(sup)
    more = rows_of(28, 400)
    sup.hold.set()
    late = []
    t = threading.Thread(target=lambda: late.append(knn(ds, xs[3])))
    t.start()
    assert sup.queued.wait(10)
    # 1,030 rows while that search is with the runner: past the block
    ds.query("INSERT INTO pts $rows", ns=NS, db=DB, vars={"rows": [
        {"id": 700 + j, "emb": more[j].tolist()} for j in range(330)]})
    if redispatched:
        assert knn(ds, more[329])[0][0] == 1029
        assert ix._dev_capacity == capacity_for(1030) == 1536
    else:
        ix.sync(ctx_of(ds))             # a search that has yet to ride
        assert ix._dev_capacity is None and ix._dev_tag is None
    ships = vec_counts(sup)["vec_full_ships"]
    sup.go.set()
    t.join()
    assert late[0][0][0] == 3           # mapped by the rows it was sent on
    if redispatched:
        assert ix._dev_capacity == 1536 and ix._dev_tag is not None
    else:
        assert ix._dev_capacity is None and ix._dev_tag is None
        assert knn(ds, more[329])[0][0] == 1029     # no TypeError: a ship
        ships += 1
    assert vec_counts(sup)["vec_full_ships"] == ships
    # and from there deltas, as after any step
    appends = vec_counts(sup)["vec_appends"]
    insert(ds, 1030, more[330])
    assert knn(ds, more[330])[0][0] == 1030
    assert vec_counts(sup) == dict(
        vec_counts(sup), vec_full_ships=ships, vec_appends=appends + 1)
    assert ix._dev_capacity == 1536
    assert sup.status()["fallbacks"] == 0


def test_a_delta_from_no_tag_is_the_whole_ship():
    """`ensure_loaded` handed a delta that starts from nothing takes the
    loader's path (it used to raise on `list(None)`)."""
    sup = get_supervisor()
    xs = rows_of(29, 310)
    ones = np.ones(310, bool)
    sup.ensure_loaded("vec/n", [1, 0], loader_of(xs[:300], ones[:300]))
    sup.ensure_loaded("vec/n", [2, 1], loader_of(xs, ones),
                      delta=(None, lambda: 1 / 0))
    assert vec_counts(sup)["vec_full_ships"] == 2
    assert knn_ids(sup, "vec/n", [2, 1], xs[305])[0] == 305
