"""Shard-partitioned vector serving (idx/shardvec.py): boundary
correctness, failure policy, split behavior, and the persisted-ANN
artifact cycle.

The property test mirrors PR-3's boundary-scan property: scatter-gather
KNN over random range splits must be byte-identical to the unsharded
engine — distances AND order. The failure tests hold the robustness
contract: typed error naming the shard, flagged partial answers,
bounded hedged dispatch, recovery to full answers after heal.
"""

from __future__ import annotations

import contextlib
import os
import random
import time

import numpy as np
import pytest

from surrealdb_tpu import Datastore, cnf
from surrealdb_tpu import key as K
from surrealdb_tpu.err import KnnShardUnavailable  # noqa: F401  (typed API)


NS = DB = "a"


def _hek(i, tb="t", ix="ix"):
    return K.ix_state(NS, DB, tb, ix, b"he", K.enc_value(i))


def _bulk(ds, xs, tb="t", ix="ix", chunk=256):
    """Fast ingest through the KV layer (records + index state), in
    chunks so sharded commits stay reasonably sized."""
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    n = xs.shape[0]
    for s in range(0, n, chunk):
        txn = ds.transaction(write=True)
        try:
            for i in range(s, min(s + chunk, n)):
                txn.set(K.record(NS, DB, tb, i),
                        serialize({"id": RecordId(tb, i)}))
                txn.set_val(_hek(i, tb, ix), xs[i].tobytes())
            txn.set_val(K.ix_state(NS, DB, tb, ix, b"vn"),
                        min(s + chunk, n))
            txn.commit()
        except BaseException:
            txn.cancel()
            raise


def _define(ds, dim, tb="t", ix="ix"):
    ds.query(
        f"DEFINE TABLE {tb}; DEFINE INDEX {ix} ON {tb} FIELDS emb "
        f"HNSW DIMENSION {dim} DIST EUCLIDEAN TYPE F32",
        ns=NS, db=DB,
    )


def _knn(ds, q, k=7, tb="t"):
    return ds.execute(
        f"SELECT id, vector::distance::knn() AS d FROM {tb} "
        f"WHERE emb <|{k}|> $q",
        ns=NS, db=DB, vars={"q": q.tolist()},
    )[-1]


def _pairs(res):
    return [(str(r["id"]), r["d"]) for r in (res.result or [])]


def test_merge_topk_unit():
    from surrealdb_tpu.idx.shardvec import merge_topk

    class _Ctx:
        def check_deadline(self):
            pass

    a = [("a1", 0.1), ("a2", 0.5), ("a3", 0.9)]
    b = [("b1", 0.2), ("b2", 0.3)]
    c = []
    out = merge_topk(_Ctx(), [a, b, c], 4)
    assert out == [("a1", 0.1), ("b1", 0.2), ("b2", 0.3), ("a2", 0.5)]
    # ties keep shard order (stable merge)
    out = merge_topk(_Ctx(), [[("x", 0.5)], [("y", 0.5)]], 2)
    assert out == [("x", 0.5), ("y", 0.5)]


def test_scatter_gather_matches_unsharded_property():
    """Property: scatter-gather KNN over random range splits is
    byte-identical to the unsharded engine — same ids, same distances,
    same order — for splits cutting anywhere inside the element
    keyspace (mirrors PR-3's boundary-scan property test)."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from shard_harness import sharded_cluster

    rng = np.random.default_rng(0x5EED)
    pr = random.Random(0x5EED)
    n, dim = 240, 12
    xs = rng.normal(size=(n, dim)).astype(np.float32)

    ref = Datastore("pymem")
    _define(ref, dim)
    _bulk(ref, xs)
    qs = rng.normal(size=(6, dim)).astype(np.float32)
    want = [_pairs(_knn(ref, q)) for q in qs]
    assert all(len(w) == 7 for w in want)

    for _round in range(2):
        cuts = sorted(pr.sample(range(5, n - 5), 2))
        with sharded_cluster([_hek(cuts[0]), _hek(cuts[1])]) as (_g, meta):
            ds = Datastore(f"shard://{meta}")
            try:
                _define(ds, dim)
                _bulk(ds, xs)
                for q, w in zip(qs, want):
                    res = _knn(ds, q)
                    assert res.error is None
                    assert res.partial is None
                    assert _pairs(res) == w, (cuts, q[:3])
                eng = ds.vector_indexes[(NS, DB, "t", "ix")]
                from surrealdb_tpu.idx.shardvec import (
                    ShardedVectorIndex,
                )

                assert isinstance(eng, ShardedVectorIndex)
                assert len(eng.parts) == 3
                assert sum(len(p.engine.rids) for p in eng.parts) == n
                # residency + fan-out observability
                info = ds.query("INFO FOR SYSTEM", ns=NS, db=DB)[0]
                shards = info["knn"][0]["shards"]
                assert len(shards) == 3
                assert sum(s["rows"] for s in shards) == n
                assert ds.telemetry.get("knn_shard_fanout") >= 3
                assert ds.telemetry.gauges["knn_index_shards"]() == 3
            finally:
                ds.close()


def _three_group_cluster():
    """3 single-member groups with the middle group serving an upper
    element slice BEHIND a FaultProxy (so tests can black-hole exactly
    one index shard), cuts: [he(60), hl) — the op log + version keys
    live on the healthy third group."""
    from surrealdb_tpu.kvs.faults import FaultProxy
    from surrealdb_tpu.kvs.remote import serve_kv
    from surrealdb_tpu.kvs.shard import init_topology

    srvs = [serve_kv("127.0.0.1", 0, block=False) for _ in range(3)]
    addrs = [f"127.0.0.1:{s.server_address[1]}" for s in srvs]
    proxy = FaultProxy(("127.0.0.1", srvs[1].server_address[1])).start()
    init_topology(
        [[addrs[0]], [proxy.addr], [addrs[2]]],
        [_hek(60), K.ix_state(NS, DB, "t", "ix", b"hl")],
    )
    return srvs, addrs, proxy


def test_partial_policy_hedging_and_heal(monkeypatch):
    """Black-hole the shard serving the upper element slice: a FRESH
    serving node (whose part must rebuild from that shard) fails typed
    in error mode — naming the shard — answers flagged-partial from
    the healthy slice in partial mode (hedged once), and returns
    byte-identical full answers after heal."""
    monkeypatch.setattr(cnf, "KNN_SHARD_TIMEOUT_S", 0.5)
    monkeypatch.setenv("SURREAL_KV_OP_TIMEOUT_S", "0.5")
    rng = np.random.default_rng(3)
    n, dim = 120, 8
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    q = rng.normal(size=dim).astype(np.float32)
    srvs, addrs, proxy = _three_group_cluster()
    try:
        from surrealdb_tpu.kvs.remote import RetryPolicy
        from surrealdb_tpu.kvs.shard import ShardedBackend

        def _ds():
            be = ShardedBackend(
                addrs[0], op_timeout=0.5, connect_timeout=0.5,
                policy=RetryPolicy(deadline_s=1.0, base_ms=10,
                                   max_ms=50),
            )
            return Datastore(backend=be)

        ds = _ds()
        _define(ds, dim)
        _bulk(ds, xs)
        full = _pairs(_knn(ds, q, k=5))
        assert len(full) == 5
        proxy.partition()
        ds2 = _ds()  # fresh node: catalog reads hit the healthy meta
        # error mode (the default): typed, names the shard
        res = _knn(ds2, q, k=5)
        assert res.error is not None
        assert "knn shard" in res.error and "@" in res.error
        assert ds2.telemetry.get("knn_hedged_dispatches") >= 1
        # partial mode: flagged answer from the healthy slice only
        monkeypatch.setattr(cnf, "KNN_PARTIAL", "partial")
        res = _knn(ds2, q, k=5)
        assert res.error is None
        assert res.partial and len(res.partial["missing_shards"]) == 1
        assert "@" in res.partial["missing_shards"][0]
        assert all(int(i.split(":")[1].rstrip(")")) <= 60
                   for i, _d in _pairs(res))
        assert ds2.telemetry.get("knn_partial_results") >= 1
        # heal: full answers resume, byte-identical
        proxy.heal()
        deadline = time.monotonic() + 15
        res = None
        while time.monotonic() < deadline:
            res = _knn(ds2, q, k=5)
            if res.error is None and res.partial is None:
                break
            time.sleep(0.2)
        assert res is not None and res.error is None \
            and res.partial is None
        assert _pairs(res) == full
        ds.close()
        ds2.close()
    finally:
        proxy.stop()
        for s in srvs:
            with contextlib.suppress(Exception):
                s.shutdown()
                s.server_close()


def test_split_mid_serving_stays_exact():
    """An online shard split through the element keyspace re-cuts the
    partition behind the epoch fence: the very next query re-partitions,
    the moved slice rebuilds from KV truth, and answers stay
    byte-identical throughout."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from shard_harness import sharded_cluster
    from surrealdb_tpu.kvs.remote import serve_kv
    from surrealdb_tpu.kvs.shard import split_shard

    rng = np.random.default_rng(11)
    n, dim = 200, 10
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    qs = rng.normal(size=(4, dim)).astype(np.float32)
    spare = serve_kv("127.0.0.1", 0, block=False)
    spare_addr = f"127.0.0.1:{spare.server_address[1]}"
    try:
        with sharded_cluster([_hek(100)]) as (_g, meta):
            ds = Datastore(f"shard://{meta}")
            try:
                _define(ds, dim)
                _bulk(ds, xs)
                want = [_pairs(_knn(ds, q)) for q in qs]
                eng = ds.vector_indexes[(NS, DB, "t", "ix")]
                assert len(eng.parts) == 2
                epoch0 = eng.map_epoch
                # split the UPPER element slice at he(150)
                split_shard(meta, _hek(150), [spare_addr])
                for q, w in zip(qs, want):
                    res = _knn(ds, q)
                    assert res.error is None and res.partial is None
                    assert _pairs(res) == w
                assert eng.map_epoch > epoch0
                assert len(eng.parts) == 3
                rows = [len(p.engine.rids) for p in eng.parts]
                assert sum(rows) == n and all(r > 0 for r in rows)
            finally:
                ds.close()
    finally:
        with contextlib.suppress(Exception):
            spare.shutdown()
            spare.server_close()


def test_write_syncs_through_log_and_partial_error_is_retryable():
    """Writes racing queries sync through the shared op log (no
    rebuild), and the typed error is RetryableKvError-adjacent in
    message shape (names shard + reason)."""
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    from shard_harness import sharded_cluster

    rng = np.random.default_rng(2)
    dim = 8
    xs = rng.normal(size=(64, dim)).astype(np.float32)
    with sharded_cluster([_hek(32)]) as (_g, meta):
        ds = Datastore(f"shard://{meta}")
        try:
            _define(ds, dim)
            _bulk(ds, xs)
            q = rng.normal(size=dim).astype(np.float32)
            assert _knn(ds, q).error is None
            # SQL-path write lands in BOTH slices via the op log
            for rid, scale in ((7, 0.0), (40, 0.001)):
                v = (q * (1 + scale)).astype(np.float32)
                r = ds.execute(
                    f"UPDATE t:{rid} SET emb = $v", ns=NS, db=DB,
                    vars={"v": v.tolist()},
                )[-1]
                assert r.error is None
            res = _knn(ds, q, k=2)
            got = [i for i, _d in _pairs(res)]
            assert got == ["RecordId(t:7)", "RecordId(t:40)"]
        finally:
            ds.close()


def test_router_trims_consumed_op_log(monkeypatch):
    """The shared op log is bounded on sharded stores: part engines
    never trim (the router owns the shared log), and once every part
    has consumed a burst of entries a write-capable query buffers the
    range delete. A later fresh engine still answers correctly (gap ->
    range rebuild)."""
    import sys

    from surrealdb_tpu.idx import shardvec

    sys.path.insert(0, os.path.dirname(__file__))
    from shard_harness import sharded_cluster

    monkeypatch.setattr(shardvec, "TRIM_LOG_ENTRIES", 8)
    rng = np.random.default_rng(6)
    dim = 8
    xs = rng.normal(size=(40, dim)).astype(np.float32)
    hl_beg = K.ix_state(NS, DB, "t", "ix", b"hl")
    hl_end = hl_beg + b"\xff" * 8
    with sharded_cluster([_hek(20)]) as (_g, meta):
        ds = Datastore(f"shard://{meta}")
        try:
            _define(ds, dim)
            # SQL-path writes populate the log (unlike the bulk loader)
            for i in range(40):
                r = ds.execute(
                    f"CREATE t:{i} SET emb = $v", ns=NS, db=DB,
                    vars={"v": xs[i].tolist()},
                )[-1]
                assert r.error is None
            txn = ds.transaction(False)
            n_log = sum(1 for _ in txn.scan(hl_beg, hl_end))
            txn.cancel()
            assert n_log == 40
            q = rng.normal(size=dim).astype(np.float32)
            res = _knn(ds, q, k=3)
            assert res.error is None and res.partial is None
            txn = ds.transaction(False)
            n_log = sum(1 for _ in txn.scan(hl_beg, hl_end))
            txn.cancel()
            assert n_log == 0, "consumed log was not trimmed"
            # fresh engine: gap in the log => range rebuild, same rows
            ds2 = Datastore(f"shard://{meta}")
            res2 = _knn(ds2, q, k=3)
            assert res2.error is None and res2.partial is None
            assert _pairs(res2) == _pairs(res)
            ds2.close()
        finally:
            ds.close()


@pytest.mark.parametrize("corrupt", [False, True])
def test_ann_snapshot_persist_reload(tmp_path, monkeypatch, corrupt):
    """Persisted CAGRA artifacts: a restart reloads the build keyed by
    mutation stamp instead of rebuilding; a corrupt snapshot is
    rejected (CRC) with a warning and rebuilt — never served."""
    from surrealdb_tpu.idx import cagra

    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    rng = np.random.default_rng(5)
    n, dim = 1200, 16
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    path = str(tmp_path / "db")

    ds = Datastore(f"lsm://{path}")
    _define(ds, dim)
    _bulk(ds, xs)
    q = xs[3]
    first = _pairs(_knn(ds, q, k=5))
    eng = ds.vector_indexes[(NS, DB, "t", "ix")]
    assert eng.ensure_ann()
    graph0 = eng._ann.graph.copy()
    snapdir = eng.snapshot_dir
    # `ensure_ann` returns once a graph serves: a background build that
    # the first query started may have installed it and still be
    # writing the artifact (tmp + rename), so wait for the rename
    files, end = [], time.monotonic() + 10.0
    while not files and time.monotonic() < end:
        files = [f for f in os.listdir(snapdir) if f.endswith(".annsnap")]
        time.sleep(0.0 if files else 0.02)
    assert len(files) == 1
    ds.close()

    if corrupt:
        snap = os.path.join(snapdir, files[0])
        with open(snap, "r+b") as f:
            f.seek(os.path.getsize(snap) // 2)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0xFF]))

    builds = []
    orig = cagra.build_index
    monkeypatch.setattr(
        cagra, "build_index",
        lambda *a, **k: (builds.append(1), orig(*a, **k))[1],
    )
    ds2 = Datastore(f"lsm://{path}")
    _knn(ds2, q, k=5)
    eng2 = ds2.vector_indexes[(NS, DB, "t", "ix")]
    assert eng2.ensure_ann()
    if corrupt:
        assert len(builds) == 1  # rejected + rebuilt, never served
    else:
        assert len(builds) == 0  # loaded in place of the rebuild
        assert np.array_equal(eng2._ann.graph, graph0)
    # either way: answers equal the pre-restart exact results
    assert _pairs(_knn(ds2, q, k=5)) == first
    ds2.close()
