"""TPU CSR graph engine: device multi-hop parity with the host `~`-key path."""

import numpy as np

from surrealdb_tpu.val import RecordId


def _build_graph(ds, n_nodes=40, seed=0):
    rng = np.random.default_rng(seed)
    stmts = [f"CREATE n:{i};" for i in range(n_nodes)]
    edges = set()
    for i in range(n_nodes):
        for j in rng.integers(0, n_nodes, size=3):
            if i != j:
                edges.add((i, int(j)))
    for a, b in sorted(edges):
        stmts.append(f"RELATE n:{a}->e->n:{b};")
    ds.execute("".join(stmts), ns="t", db="t")
    return sorted(edges)


def test_csr_single_hop_parity(ds):
    edges = _build_graph(ds)
    from surrealdb_tpu.exec.context import Ctx
    from surrealdb_tpu.graph.csr import get_csr
    from surrealdb_tpu.kvs.ds import Session

    txn = ds.transaction(write=False)
    ctx = Ctx(ds, Session(ns="t", db="t"), txn)
    csr = get_csr(ds, ctx, "n", "e", "out")
    # parity vs the host scan for every node
    host = {}
    for a, b in edges:
        host.setdefault(a, set()).add(b)
    for a in range(40):
        got = set(csr.multi_hop([a], 1))
        assert got == host.get(a, set()), f"node {a}"
    txn.cancel()


def test_csr_multi_hop_union(ds):
    ds.execute(
        "CREATE m:1; CREATE m:2; CREATE m:3; CREATE m:4;"
        "RELATE m:1->me->m:2; RELATE m:2->me->m:3; RELATE m:3->me->m:4;",
        ns="t", db="t",
    )
    from surrealdb_tpu.exec.context import Ctx
    from surrealdb_tpu.graph.csr import get_csr
    from surrealdb_tpu.kvs.ds import Session

    txn = ds.transaction(write=False)
    ctx = Ctx(ds, Session(ns="t", db="t"), txn)
    csr = get_csr(ds, ctx, "m", "me", "out")
    assert set(csr.multi_hop([1], 2)) == {3}
    assert set(csr.multi_hop([1], 2, "union")) == {2, 3}
    assert set(csr.multi_hop([1], 3)) == {4}
    txn.cancel()


def test_csr_rebuild_on_write(ds):
    ds.execute("CREATE r:1; CREATE r:2; RELATE r:1->re->r:2", ns="t", db="t")
    from surrealdb_tpu.exec.context import Ctx
    from surrealdb_tpu.graph.csr import get_csr
    from surrealdb_tpu.kvs.ds import Session

    txn = ds.transaction(write=False)
    ctx = Ctx(ds, Session(ns="t", db="t"), txn)
    csr = get_csr(ds, ctx, "r", "re", "out")
    assert set(csr.multi_hop([1], 1)) == {2}
    txn.cancel()
    ds.execute("CREATE r:3; RELATE r:1->re->r:3", ns="t", db="t")
    txn = ds.transaction(write=False)
    ctx = Ctx(ds, Session(ns="t", db="t"), txn)
    csr = get_csr(ds, ctx, "r", "re", "out")
    assert set(csr.multi_hop([1], 1)) == {2, 3}
    txn.cancel()


def test_recursion_csr_fast_path_matches_host(ds):
    """+collect recursion expands a level over the threshold as one CSR
    device hop (declared RELATION tables only); results must match the
    host walk (both are visited-set deduplicated)."""
    import surrealdb_tpu.graph as G
    from surrealdb_tpu.device import get_supervisor

    ds.execute("DEFINE TABLE e TYPE RELATION", ns="t", db="t")
    _build_graph(ds, n_nodes=30, seed=2)
    old = G.TPU_FRONTIER_THRESHOLD
    try:
        q = "RETURN array::sort(n:0.{..+collect}(->e->n))"
        host = ds.query(q, ns="t", db="t")[0]
        hops0 = get_supervisor().runner_status()["ops"].get("csr_hop", 0)
        G.TPU_FRONTIER_THRESHOLD = 2
        dev = ds.query(q, ns="t", db="t")[0]
        assert sorted(r.render() for r in host) == sorted(
            r.render() for r in dev
        )
        assert len(host) > 3
        # the device hop really ran (it was dead code once)
        assert get_supervisor().runner_status()["ops"]["csr_hop"] > hops0
    finally:
        G.TPU_FRONTIER_THRESHOLD = old


def test_vector_incremental_sync(ds):
    """Writes after the first search apply via the op log, not a rebuild."""
    ds.query("DEFINE INDEX ve ON vt FIELDS v HNSW DIMENSION 2")
    for i in range(8):
        ds.query(f"CREATE vt:{i} SET v = [{float(i)}, 0.0]")
    rows = ds.query("SELECT id FROM vt WHERE v <|2,5|> [0.0, 0.0]")[0]
    assert rows[0]["id"] == RecordId("vt", 0)
    eng = next(iter(ds.vector_indexes.values()))
    ver0 = eng.version
    rebuilt = {"n": 0}
    orig = eng._rebuild

    def counting(ctx):
        rebuilt["n"] += 1
        return orig(ctx)

    eng._rebuild = counting
    ds.query("CREATE vt:100 SET v = [-1.0, 0.0]")
    ds.query("DELETE vt:1")
    rows = ds.query("SELECT id FROM vt WHERE v <|3,5|> [-1.0, 0.0]")[0]
    ids = [r["id"] for r in rows]
    assert ids[0] == RecordId("vt", 100)
    assert RecordId("vt", 1) not in ids
    assert rebuilt["n"] == 0, "expected incremental log apply, got rebuild"
    assert eng.version > ver0


def test_csr_fast_path_in_txn_and_post_commit():
    """Regression: the shared CSR cache tracks COMMITTED state — an
    uncommitted RELATE must fall back to `~`-key scans in its own txn and
    invalidate the cache only on commit."""
    import numpy as np

    from surrealdb_tpu import Datastore
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    ds = Datastore("memory")
    ds.query("DEFINE TABLE person; DEFINE TABLE knows TYPE RELATION",
             ns="b", db="b")
    rng = np.random.default_rng(5)
    txn = ds.transaction(write=True)
    for i in range(300):
        txn.set(K.record("b", "b", "person", i),
                serialize({"id": RecordId("person", i)}))
    e = 0
    for s_ in range(100):
        for d_ in rng.integers(0, 300, size=3):
            txn.set(K.record("b", "b", "knows", e), serialize({
                "id": RecordId("knows", e),
                "in": RecordId("person", int(s_)),
                "out": RecordId("person", int(d_)),
            }))
            txn.set(K.graph("b", "b", "person", int(s_), K.DIR_OUT,
                            "knows", e), b"")
            txn.set(K.graph("b", "b", "knows", e, K.DIR_IN, "person",
                            int(s_)), b"")
            txn.set(K.graph("b", "b", "knows", e, K.DIR_OUT, "person",
                            int(d_)), b"")
            txn.set(K.graph("b", "b", "person", int(d_), K.DIR_IN,
                            "knows", e), b"")
            e += 1
    txn.commit()
    sql = "SELECT VALUE ->knows->person->knows->person FROM person:0"
    base = len(ds.query_one(sql, ns="b", db="b")[0])
    ds.query_one(sql, ns="b", db="b")  # warm the CSR cache
    res = ds.execute(
        f"BEGIN; RELATE person:0->knows->person:1; {sql}; COMMIT",
        ns="b", db="b",
    )
    assert res[2].error is None
    intx = len(res[2].result[0])
    after = len(ds.query_one(sql, ns="b", db="b")[0])
    # the new person:0->1 edge adds person:1's fanout to the result
    assert intx > base and after == intx


def test_csr_fast_path_matches_slow_path():
    """Bag semantics + ordering of the CSR pair hop equal the per-record
    scan path exactly."""
    import numpy as np

    import surrealdb_tpu.exec.eval as E
    from surrealdb_tpu import Datastore

    ds = Datastore("memory")
    q = lambda s: ds.query(s, ns="b", db="b")
    q("DEFINE TABLE person; DEFINE TABLE knows TYPE RELATION")
    rng = np.random.default_rng(3)
    for i in range(40):
        q(f"CREATE person:{i}")
    for _ in range(300):
        a, b = rng.integers(0, 40, size=2)
        q(f"RELATE person:{int(a)}->knows->person:{int(b)}")
    sql = "SELECT VALUE ->knows->person->knows->person FROM person:0"
    fast = q(sql)[0]
    orig = E._csr_bag_pair_hop
    E._csr_bag_pair_hop = lambda *a, **k: None  # force per-record scans
    try:
        slow = q(sql)[0]
    finally:
        E._csr_bag_pair_hop = orig
    assert fast == slow


def test_incremental_replay_after_relate():
    """A committed RELATE on a warm CSR replays from the edge op-log —
    no full edge-table rescan (VERDICT r4 item 5)."""
    import numpy as np

    from surrealdb_tpu import Datastore
    from surrealdb_tpu import key as K
    from surrealdb_tpu.graph import csr as csrmod
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    ds = Datastore("memory")
    ds.query("DEFINE TABLE person; DEFINE TABLE knows TYPE RELATION",
             ns="g", db="g")
    n, e = 500, 3000
    rng = np.random.default_rng(3)
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    txn = ds.transaction(write=True)
    try:
        for i in range(n):
            txn.set(K.record("g", "g", "person", i),
                    serialize({"id": RecordId("person", i)}))
        for j in range(e):
            s, d = int(src[j]), int(dst[j])
            txn.set(K.record("g", "g", "knows", j), serialize({
                "id": RecordId("knows", j), "in": RecordId("person", s),
                "out": RecordId("person", d)}))
            txn.set(K.graph("g", "g", "person", s, K.DIR_OUT, "knows", j),
                    b"")
            txn.set(K.graph("g", "g", "knows", j, K.DIR_IN, "person", s),
                    b"")
            txn.set(K.graph("g", "g", "knows", j, K.DIR_OUT, "person", d),
                    b"")
            txn.set(K.graph("g", "g", "person", d, K.DIR_IN, "knows", j),
                    b"")
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    sql = ("SELECT VALUE ->knows->person->knows->person->knows->person "
           "FROM person:0")
    out1 = ds.query_one(sql, ns="g", db="g")  # builds the CSR

    builds = []
    orig_build = csrmod.CsrGraph.build

    def counting_build(self, ctx):
        builds.append(self.key)
        return orig_build(self, ctx)

    csrmod.CsrGraph.build = counting_build
    try:
        ds.query_one("RELATE person:0->knows->person:1", ns="g", db="g")
        out2 = ds.query_one(sql, ns="g", db="g")
        assert builds == [], f"full rebuild ran: {builds}"
    finally:
        csrmod.CsrGraph.build = orig_build
    # the new edge participates in the traversal
    flat2 = out2[0] if out2 and isinstance(out2[0], list) else out2
    flat1 = out1[0] if out1 and isinstance(out1[0], list) else out1
    assert len(flat2) > len(flat1)
    # a DELETE is not replayable: the op-log entry poisons the window,
    # so the CSR never serves stale adjacency — small-frontier queries
    # fall back to authoritative per-record scans until a big query pays
    # the rebuild
    ds.query_one("DELETE knows:0", ns="g", db="g")
    from surrealdb_tpu.graph.csr import oplog_slice

    gk = ("g", "g", "knows")
    ver = ds.graph_versions[gk]
    assert oplog_slice(ds, gk, ver - 1, ver) is None
    out3 = ds.query_one(sql, ns="g", db="g")
    flat3 = out3[0] if out3 and isinstance(out3[0], list) else out3
    assert len(flat3) <= len(flat2)
