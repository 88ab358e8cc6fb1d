"""The device bag hop: a folded `->edge->node` chain over a declared
RELATION rides the graph's batcher into `csr_bag_hop` and comes back equal,
element for element, to the host CSR walk, to the per-record `~`-key scans
and to a plain walk over Python lists. CPU only, the runner inline (as
tests/conftest.py sets it): answers and counts, never a time."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import Datastore, telemetry
from surrealdb_tpu import key as K
from surrealdb_tpu.device import csrstore, get_supervisor, kernelstats
from surrealdb_tpu.exec import eval as ev
from surrealdb_tpu.kvs.api import serialize
from surrealdb_tpu.val import RecordId

NS = DB = "g"
CHAIN = "->knows->person"


def sql(hops: int, start: str) -> str:
    return f"SELECT VALUE {CHAIN * hops} FROM {start}"


def bulk(ds, n_nodes: int, src, dst, first_edge: int = 0):
    """Nodes and edges by the KV route (chip_smoke.py bulk_graph's keys):
    edge ids are integers, so ascending edge id is the `~` scan order."""
    txn = ds.transaction(write=True)
    for i in range(n_nodes):
        txn.set(K.record(NS, DB, "person", i),
                serialize({"id": RecordId("person", i)}))
    for e, (s, d) in enumerate(zip(src, dst), first_edge):
        s, d = int(s), int(d)
        txn.set(K.record(NS, DB, "knows", e), serialize({
            "id": RecordId("knows", e), "in": RecordId("person", s),
            "out": RecordId("person", d)}))
        txn.set(K.graph(NS, DB, "person", s, K.DIR_OUT, "knows", e), b"")
        txn.set(K.graph(NS, DB, "knows", e, K.DIR_IN, "person", s), b"")
        txn.set(K.graph(NS, DB, "knows", e, K.DIR_OUT, "person", d), b"")
        txn.set(K.graph(NS, DB, "person", d, K.DIR_IN, "knows", e), b"")
    txn.commit()


def new_store(n_nodes: int, src, dst) -> Datastore:
    ds = Datastore("memory")
    ds.query("DEFINE TABLE person; DEFINE TABLE knows TYPE RELATION",
             ns=NS, db=DB)
    bulk(ds, n_nodes, src, dst)
    return ds


def plain_walk(src, dst, start: int, hops: int) -> list:
    """The reference: adjacency as Python lists in ascending edge id,
    walked level by level by plain loops."""
    adj = {}
    for s, d in zip(src, dst):
        adj.setdefault(int(s), []).append(int(d))
    level = [start]
    for _ in range(hops):
        level = [d for v in level for d in adj.get(v, [])]
    return [RecordId("person", v) for v in level]


def random_graph(seed: int, n_nodes: int = 90, n_edges: int = 700):
    """Sources only among the first two thirds (the rest have no
    out-edge), self-loops and parallel edges planted."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes * 2 // 3, n_edges)
    dst = rng.integers(0, n_nodes, n_edges)
    src[:5] = dst[:5]                   # self-loops
    src[5:10], dst[5:10] = src[10:15], dst[10:15]   # parallel edges
    return n_nodes, src, dst


def ops(name: str) -> int:
    return get_supervisor().runner_status()["ops"].get(name, 0)


def csr_counts() -> dict:
    return get_supervisor().runner_status()["csr"]


def query(ds, text: str, **vars):
    return ds.query(text, ns=NS, db=DB, vars=vars or None)[0]


def warm(ds):
    """The first traversal from a busy node: its third pair meets a
    frontier of 64 or more and builds the CSR (the cold-cache rule);
    from then on every folded chain finds the cache valid."""
    for start in range(30):
        query(ds, sql(3, f"person:{start}"))
        if ds.graph_engine:
            return next(iter(ds.graph_engine.values()))
    raise AssertionError("no start node built the CSR")


@pytest.fixture(scope="module", params=[11, 12, 13])
def graph(request):
    n, src, dst = random_graph(request.param)
    ds = new_store(n, src, dst)
    csr = warm(ds)
    yield ds, csr, n, src, dst
    ds.close()


# -- (a) answers -------------------------------------------------------------


@pytest.mark.parametrize("hops", [1, 2, 3])
def test_served_chain_equals_every_other_walk(graph, hops, monkeypatch):
    ds, csr, n, src, dst = graph
    # a busy node, a node with no out-edge, a self-loop's node
    starts = [int(src[20]), n - 1, int(src[0])]
    before = ops("csr_bag_hop")
    served = [query(ds, sql(hops, f"person:{s}")) for s in starts]
    # PR 21's vacuous pass must not repeat: the op really ran
    assert ops("csr_bag_hop") - before == len(starts)
    for s, got in zip(starts, served):
        assert got == [plain_walk(src, dst, s, hops)]
        assert got == [csr.materialize_rids(csr.hop_bag_idx([s], hops),
                                            "person")]
    assert served[1] == [[]]
    # the per-record `~`-key scans, the seam taken out
    monkeypatch.setattr(ev, "_csr_bag_pair_hop", lambda *a, **k: None)
    before = ops("csr_bag_hop")
    assert [query(ds, sql(hops, f"person:{s}")) for s in starts] == served
    assert ops("csr_bag_hop") == before


def test_a_start_id_that_is_no_node(graph):
    ds, _csr, n, _src, _dst = graph
    before = ops("csr_bag_hop")
    # SELECT finds no such record and returns no row; the idiom on the
    # id itself reaches the seam, which knows no such node
    assert query(ds, sql(2, f"person:{n + 1000}")) == []
    assert query(ds, f"RETURN person:{n + 1000}{CHAIN * 2}") == []
    assert query(ds, f"RETURN type::record('person', $i){CHAIN * 3}",
                 i=n + 7) == []
    assert ops("csr_bag_hop") == before


def test_a_bound_start_and_a_list_of_starts(graph):
    ds, _csr, _n, src, dst = graph
    a, b = int(src[30]), int(src[31])
    before = ops("csr_bag_hop")
    assert query(ds, sql(3, "type::record('person', $i)"), i=a) \
        == [plain_walk(src, dst, a, 3)]
    # an array start maps each element through the chain on its own
    assert query(ds, f"RETURN [person:{a}, person:{b}]{CHAIN * 2}") \
        == [plain_walk(src, dst, a, 2), plain_walk(src, dst, b, 2)]
    assert ops("csr_bag_hop") - before == 3
    # several start records in ONE frontier (what a chain that fell
    # back to the scans half-way hands over): sources in frontier
    # order, a repeated source twice
    assert _csr.hop_bag_served([a, b, a], 2, "person") \
        == plain_walk(src, dst, a, 2) + plain_walk(src, dst, b, 2) \
        + plain_walk(src, dst, a, 2)


def test_riders_of_different_hop_counts_share_one_batch(graph):
    """One dispatch is held inside its RPC while six more riders of
    1, 2 and 3 hops queue: they leave together, one `csr_bag_hop` a
    (start slots, capacities) group, and every answer is its own."""
    ds, csr, _n, src, dst = graph
    sup = get_supervisor()
    real, gate, inside = sup.call, threading.Event(), threading.Event()
    held = []

    def call(op, meta, bufs=(), timeout_s=None):
        if op == "csr_bag_hop" and not held:
            held.append(1)
            inside.set()
            assert gate.wait(10)
        return real(op, meta, bufs, timeout_s)

    starts = [int(s) for s in src[40:47]]
    hops = [3, 1, 2, 3, 1, 2, 3]
    out = {}

    def go(j):
        out[j] = query(ds, sql(hops[j], f"person:{starts[j]}"))

    sup.call = call
    try:
        threads = [threading.Thread(target=go, args=(j,), daemon=True)
                   for j in range(7)]
        before = ops("csr_bag_hop")
        threads[0].start()
        assert inside.wait(10)
        for t in threads[1:]:
            t.start()
        end = time.monotonic() + 10
        while len(csr._batcher.queue) < 6 and time.monotonic() < end:
            time.sleep(0.002)
        assert len(csr._batcher.queue) == 6
        gate.set()
        for t in threads:
            t.join(20)
    finally:
        sup.call = real
    for j in range(7):
        assert out[j] == [plain_walk(src, dst, starts[j], hops[j])]
    # the held dispatch, then one RPC a hop count for the six behind it
    assert ops("csr_bag_hop") - before == 4


# -- the wire -----------------------------------------------------------------


def test_the_rpc_carries_indexes_not_masks(graph):
    """Out: a count and the start indexes a rider; back: every level's
    total and the last level's ids — nothing of the graph's size."""
    ds, csr, n, src, dst = graph
    sup = get_supervisor()
    real, seen = sup.call, []

    def call(op, meta, bufs=(), timeout_s=None):
        reply = real(op, meta, bufs, timeout_s)
        if op == "csr_bag_hop":
            seen.append((meta, list(bufs), reply))
        return reply

    sup.call = call
    try:
        s = int(src[50])
        got = query(ds, sql(3, f"person:{s}"))
    finally:
        sup.call = real
    (meta, bufs, (tag, _rmeta, rbufs)), = seen
    assert tag == "ok" and len(bufs) == 1
    assert bufs[0].dtype == np.int32 and bufs[0].shape == (1, 2)
    assert bufs[0].tolist() == [[1, csr.node_index[K.enc_value(s)]]]
    totals, flat = rbufs
    assert totals.shape == (1, 3) and totals.dtype == np.int32
    assert flat.dtype == np.int32 and len(flat) == len(got[0]) \
        == totals[0, 2]
    top = csrstore.bag_caps(n, len(src), 1, 3)[-1][-1]
    assert flat.nbytes <= 4 * top and tuple(meta["caps"]) \
        == csrstore.bag_caps(n, len(src), 1, 3)[0]


def test_the_kernel_builds_nothing_of_the_graphs_size():
    """Shapes of the jitted program at the source's scale: inputs are
    the resident CSR and [B, 1 + C0]; the output [B, hops + cap]."""
    import jax
    import jax.numpy as jnp

    n, e, b = 1_000_000, 10_000_000, 32
    caps = csrstore.bag_caps(n, e, 1, 3)[0]
    assert caps == (64, 512, 4096)
    out = jax.eval_shape(
        lambda ip, c, p: csrstore._bag_hop_impl(ip, c, p, caps),
        jax.ShapeDtypeStruct((csrstore.pad_len(n + 1),), jnp.int32),
        jax.ShapeDtypeStruct((csrstore.pad_len(e),), jnp.int32),
        jax.ShapeDtypeStruct((b, 2), jnp.int32))
    assert out.shape == (b, 3 + 4096) and out.dtype == jnp.int32
    ladder = csrstore.bag_caps(n, e, 1, 3)
    assert [r[-1] for r in ladder] == [4096, 16384, 65536, 262144]
    assert ladder[-1][-1] == csrstore.BAG_MAX_CAP


@pytest.mark.parametrize("x", [1, 1023, 1025, 250_001, 2_498_976,
                               2_500_000, 10_000_000])
def test_padded_lengths_keep_a_growing_graphs_programs(x):
    p = csrstore.pad_len(x)
    assert x <= p < x + max(1024, x // 7 + 2)
    # 1,024 more edges (the benchmark's SQL tail) land in the same shape
    if x == 2_498_976:
        assert csrstore.pad_len(x + 1024) == p


# -- (b) overflow -------------------------------------------------------------


@pytest.fixture(scope="module")
def hub():
    """A sparse graph (mean out-degree 2) with one hub of 300 edges."""
    rng = np.random.default_rng(5)
    n = 400
    src = np.concatenate([rng.integers(1, n, 500), np.zeros(300, np.int64)])
    dst = np.concatenate([rng.integers(1, n, 500), rng.integers(1, n, 300)])
    ds = new_store(n, src, dst)
    # the hub's 300 neighbours are the frontier that builds the CSR
    query(ds, sql(2, "person:0"))
    yield ds, next(iter(ds.graph_engine.values())), n, src, dst
    ds.close()


def test_a_hub_rides_the_next_rung_and_is_counted(hub):
    ds, _csr, n, src, dst = hub
    ladder = csrstore.bag_caps(n, len(src), 1, 2)
    assert ladder[0][0] == 64 and ladder[1][0] == 256 \
        and ladder[2][0] == 1024
    c0, calls0 = csr_counts(), ops("csr_bag_hop")
    routed0 = get_supervisor().status()["host_routed"]
    got = query(ds, sql(2, "person:0"))
    assert got == [plain_walk(src, dst, 0, 2)] and len(got[0]) > 300
    c1 = csr_counts()
    # 300 paths at level 1: past 64, past 256, inside 1,024: one
    # overflow, one more dispatch, answered on the device
    assert c1["overflows"] - c0["overflows"] == 1
    assert c1["bag_riders"] - c0["bag_riders"] == 1
    assert c1["paths_out"] - c0["paths_out"] == len(got[0])
    assert ops("csr_bag_hop") - calls0 == 2
    assert get_supervisor().status()["host_routed"] == routed0


@pytest.fixture()
def short_ladder(monkeypatch):
    """Two rungs only; the ladder is cached by its arguments."""
    monkeypatch.setattr(csrstore, "BAG_RUNGS", 2)
    csrstore.bag_caps.cache_clear()
    yield
    monkeypatch.undo()
    csrstore.bag_caps.cache_clear()


def test_past_the_ladders_top_the_host_walks_and_is_counted(
        hub, short_ladder, monkeypatch):
    ds, csr, _n, src, dst = hub    # top: 256 at level 1
    sup = get_supervisor()
    routed0, c0 = sup.status()["host_routed"], csr_counts()
    got = query(ds, sql(2, "person:0"))
    assert got == [plain_walk(src, dst, 0, 2)]
    assert sup.status()["host_routed"] - routed0 == 1
    c1 = csr_counts()
    # 300 paths are known after the first rung: the second (256) is
    # not tried, the rider goes straight to the host
    assert c1["overflows"] - c0["overflows"] == 1
    assert c1["bag_riders"] == c0["bag_riders"]
    # a start list longer than the widest start slot: the host's too
    monkeypatch.setattr(csrstore, "BAG_MAX_START", 32)
    calls0 = ops("csr_bag_hop")
    assert csr.hop_bag_served(list(range(1, 70)), 1, "person") is None
    assert ops("csr_bag_hop") == calls0
    assert sup.status()["host_routed"] - routed0 == 2


def test_a_store_on_a_mesh_refuses_and_the_host_walks(monkeypatch):
    """A four-chip host may place the CSR on a mesh (`_place_csr`): the
    mesh store has no bag kernel, the op answers `refused`, the seam
    takes the host walk and the supervisor counts it."""
    from surrealdb_tpu.device.handlers import DeviceHost

    monkeypatch.setattr(DeviceHost, "_place_csr", lambda self, e: 2)
    n, src, dst = random_graph(21)
    ds = new_store(n, src, dst)
    try:
        sup = get_supervisor()
        csr = warm(ds)
        routed0, calls0 = sup.status()["host_routed"], ops("csr_bag_hop")
        s = int(src[20])
        assert query(ds, sql(3, f"person:{s}")) \
            == [plain_walk(src, dst, s, 3)]
        assert type(sup.inline_store(csr._dev_key)).__name__ \
            == "MeshCsrStore"
        assert sup.status()["host_routed"] - routed0 == 1
        assert ops("csr_bag_hop") == calls0   # `refused` is no `ok`
    finally:
        ds.close()


# -- (c) writes ---------------------------------------------------------------


def test_a_relate_is_traversed_by_the_next_statement():
    n, src, dst = random_graph(31)
    ds = new_store(n, src, dst)
    try:
        warm(ds)
        s = int(src[20])
        first = query(ds, sql(3, f"person:{s}"))
        assert first == [plain_walk(src, dst, s, 3)]
        # a new edge out of the start node, to a node with out-edges
        t = int(src[21])
        loads0, calls0 = ops("csr_load"), ops("csr_bag_hop")
        query(ds, f"RELATE person:{s}->knows:900000->person:{t}")
        src2, dst2 = np.append(src, s), np.append(dst, t)
        want = plain_walk(src2, dst2, s, 3)
        assert len(want) > len(first[0])
        # the version moved: the cache is cold for a lone source, so
        # the per-record scans answer the first two pairs (and see the
        # edge); their frontier of 64 or more replays the op log, and
        # the third pair rides the device from a re-shipped block ...
        assert query(ds, sql(3, f"person:{s}")) == [want]
        assert ops("csr_load") - loads0 == 1
        assert ops("csr_bag_hop") - calls0 == 1
        # ... as does the next whole chain
        assert query(ds, sql(3, f"person:{s}")) == [want]
        assert ops("csr_load") - loads0 == 1
        assert ops("csr_bag_hop") - calls0 == 2
    finally:
        ds.close()


def test_uncommitted_edge_writes_keep_the_seam_back():
    n, src, dst = random_graph(32)
    ds = new_store(n, src, dst)
    try:
        warm(ds)
        s, t = int(src[22]), int(src[23])
        assert query(ds, sql(2, f"person:{s}")) \
            == [plain_walk(src, dst, s, 2)]
        calls0 = ops("csr_bag_hop")
        res = ds.execute(
            f"BEGIN; RELATE person:{s}->knows:800000->person:{t}; "
            f"{sql(2, f'person:{s}')}; COMMIT", ns=NS, db=DB)
        assert all(r.ok for r in res), [r.error for r in res]
        want = plain_walk(np.append(src, s), np.append(dst, t), s, 2)
        assert res[-2].result == [want]
        # the statement saw its own transaction's edge, from the scans
        assert ops("csr_bag_hop") == calls0
    finally:
        ds.close()


# -- (d) tracing --------------------------------------------------------------


def test_stages_phases_and_counters_move_by_what_was_served(graph):
    ds, _csr, _n, src, dst = graph

    def stage(name):
        st = telemetry._STAGES.get(name)
        return (st.count, st.total_ns) if st else (0, 0)

    names = ("graph_hop", "hop_post", "batch_wait", "batch_ride",
             "batch_dispatch", "device_rpc")
    p0, c0 = dict(kernelstats.PHASES), csr_counts()
    s0 = {k: stage(k) for k in names}
    starts = [int(v) for v in src[60:63]]
    want = [plain_walk(src, dst, s, 3) for s in starts]
    for s, w in zip(starts, want):
        assert query(ds, sql(3, f"person:{s}")) == [w]
    for k in names:
        assert stage(k)[0] - s0[k][0] == 3, k
    # graph_hop holds the ride, the ride the dispatch, the dispatch
    # the RPC and hop_post
    gh = stage("graph_hop")[1] - s0["graph_hop"][1]
    ride = stage("batch_ride")[1] - s0["batch_ride"][1]
    disp = stage("batch_dispatch")[1] - s0["batch_dispatch"][1]
    rpc = stage("device_rpc")[1] - s0["device_rpc"][1]
    post = stage("hop_post")[1] - s0["hop_post"][1]
    assert gh >= ride >= disp >= rpc + post > 0
    for ph in ("h2d", "device", "d2h"):
        assert kernelstats.PHASES[ph] > p0.get(ph, 0), ph
    c1 = csr_counts()
    assert c1["bag_riders"] - c0["bag_riders"] == 3
    assert c1["paths_out"] - c0["paths_out"] == sum(map(len, want))
    levels = sum(len(plain_walk(src, dst, s, h))
                 for s in starts for h in (1, 2, 3))
    assert c1["edges_gathered"] - c0["edges_gathered"] == levels
    assert c1["overflows"] == c0["overflows"]
