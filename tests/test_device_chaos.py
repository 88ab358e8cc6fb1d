"""Device-runner chaos: SIGKILL and SIGSTOP the supervised DeviceRunner
under concurrent KNN + multi-hop graph load. The serving contract:

- zero query errors — every in-flight and subsequent query completes
  via the host paths, with results identical to a host-only run;
- typed telemetry: device_restarts / device_dispatch_timeouts counters
  and the device_degraded gauge observe the incident;
- the supervisor re-promotes the device within one probe interval of
  the runner coming back healthy (hysteresis=1 here);
- a deadline-bounded query waiting on a wedged dispatch unwinds within
  its budget, not the dispatch timeout.
"""

from __future__ import annotations

import os
import re
import signal
import threading
import time

import numpy as np
import pytest

from surrealdb_tpu.device import DeviceSupervisor, set_supervisor

DIM = 8
N_VECS = 300
N_NODES = 40
N_CLIENTS = 32


@pytest.fixture()
def sub_sup():
    """A real subprocess supervisor with chaos-friendly timings,
    installed as the process singleton for the test's duration."""
    sup = DeviceSupervisor(
        mode="auto", dispatch_timeout_s=1.0, load_timeout_s=10.0,
        init_timeout_s=120.0, probe_interval_s=0.2, promote_successes=1,
    )
    old = set_supervisor(sup)
    try:
        yield sup
    finally:
        set_supervisor(old)
        sup.shutdown()


@pytest.fixture()
def chaos_ds(monkeypatch):
    import surrealdb_tpu.idx.vector as V
    from surrealdb_tpu import Datastore

    monkeypatch.setattr(V, "DEVICE_MIN_ROWS", 32)
    ds = Datastore("memory")
    rng = np.random.default_rng(71)
    ds.query(
        f"DEFINE TABLE p; DEFINE INDEX ix ON p FIELDS v HNSW "
        f"DIMENSION {DIM} DIST EUCLIDEAN TYPE F32"
    )
    vecs = rng.normal(size=(N_VECS, DIM)).astype(np.float32)
    stmts = []
    for i, v in enumerate(vecs):
        vals = ", ".join(f"{x:.6f}" for x in v)
        stmts.append(f"CREATE p:{i} SET v = [{vals}];")
    edges = set()
    for i in range(N_NODES):
        for j in rng.integers(0, N_NODES, size=3):
            if i != int(j):
                edges.add((i, int(j)))
    stmts.extend(f"CREATE n:{i};" for i in range(N_NODES))
    stmts.extend(f"RELATE n:{a}->e->n:{b};" for a, b in sorted(edges))
    ds.query("".join(stmts))
    yield ds, vecs
    ds.close()


def _knn_sql(qv) -> str:
    vals = ", ".join(f"{x:.6f}" for x in qv)
    return f"SELECT id FROM p WHERE v <|5,20|> [{vals}]"


def _csr(ds):
    from surrealdb_tpu.exec.context import Ctx
    from surrealdb_tpu.graph.csr import get_csr
    from surrealdb_tpu.kvs.ds import Session

    txn = ds.transaction(write=False)
    ctx = Ctx(ds, Session(ns="test", db="test"), txn)
    g = get_csr(ds, ctx, "n", "e", "out")
    txn.cancel()
    return g


def _host_truth(ds, vecs, queries):
    """Expected results with the device OFF — the host-only baseline
    the degraded path must match exactly."""
    off = DeviceSupervisor(mode="off")
    prev = set_supervisor(off)
    try:
        knn = [
            [r["id"] for r in ds.query(_knn_sql(q))[0]] for q in queries
        ]
        g = _csr(ds)
        hops = sorted(g.multi_hop(list(range(8)), 3))
    finally:
        set_supervisor(prev)
    return knn, hops


def _warm_device(sup, ds, queries):
    assert sup.wait_ready(120), f"runner never came up: {sup.status()}"
    ds.query(_knn_sql(queries[0]))  # compile + ship the vec store
    g = _csr(ds)
    g.multi_hop(list(range(8)), 3)  # compile + ship the CSR store
    assert sup.state == "ready"
    return g


def _run_clients(ds, g, queries, expect_knn, expect_hops, stop_at,
                 errors, mismatches):
    def client(ci):
        qi = ci % len(queries)
        while time.monotonic() < stop_at:
            try:
                got = [r["id"] for r in ds.query(_knn_sql(queries[qi]))[0]]
                if got != expect_knn[qi]:
                    mismatches.append((ci, "knn", got))
                hops = sorted(g.multi_hop(list(range(8)), 3))
                if hops != expect_hops:
                    mismatches.append((ci, "graph", hops))
            except Exception as e:  # noqa: BLE001 — the assertion IS "no errors"
                errors.append((ci, repr(e)))
                return

    threads = [
        threading.Thread(target=client, args=(ci,), daemon=True)
        for ci in range(N_CLIENTS)
    ]
    for t in threads:
        t.start()
    return threads


def _gauge(ds, name) -> float:
    text = ds.telemetry.prometheus()
    m = re.search(rf"^surreal_{name} ([0-9.]+)$", text, re.M)
    assert m, f"gauge {name} missing from /metrics"
    return float(m.group(1))


def _wait_state(sup, state, timeout):
    deadline = time.monotonic() + timeout
    while sup.state != state and time.monotonic() < deadline:
        time.sleep(0.02)
    return sup.state == state


def test_sigkill_runner_under_load(sub_sup, chaos_ds):
    ds, vecs = chaos_ds
    queries = vecs[:8]
    expect_knn, expect_hops = _host_truth(ds, vecs, queries)
    g = _warm_device(sub_sup, ds, queries)
    # sanity: the DEVICE results already match the host baseline
    assert [r["id"] for r in ds.query(_knn_sql(queries[0]))[0]] \
        == expect_knn[0]

    errors, mismatches = [], []
    stop_at = time.monotonic() + 4.0
    threads = _run_clients(ds, g, queries, expect_knn, expect_hops,
                           stop_at, errors, mismatches)
    time.sleep(0.3)
    pid = sub_sup.runner_pid()
    assert pid is not None
    os.kill(pid, signal.SIGKILL)  # crash the runner mid-load
    assert _wait_state(sub_sup, "degraded", 5.0) or \
        sub_sup.state == "ready"  # may already have re-promoted
    for t in threads:
        t.join(timeout=30)
    assert not errors, f"queries errored during runner crash: {errors[:5]}"
    assert not mismatches, f"host fallback diverged: {mismatches[:5]}"
    # recovery: re-promoted without a server restart, telemetry typed
    assert _wait_state(sub_sup, "ready", 30.0), sub_sup.status()
    assert sub_sup.counters["device_restarts"] >= 1
    assert _gauge(ds, "device_restarts") >= 1
    assert _gauge(ds, "device_degraded") == 0
    # and the device path serves again, still matching
    assert [r["id"] for r in ds.query(_knn_sql(queries[1]))[0]] \
        == expect_knn[1]


def test_sigstop_wedge_under_load(sub_sup, chaos_ds):
    ds, vecs = chaos_ds
    queries = vecs[:8]
    expect_knn, expect_hops = _host_truth(ds, vecs, queries)
    g = _warm_device(sub_sup, ds, queries)

    errors, mismatches = [], []
    stop_at = time.monotonic() + 4.0
    threads = _run_clients(ds, g, queries, expect_knn, expect_hops,
                           stop_at, errors, mismatches)
    time.sleep(0.3)
    pid = sub_sup.runner_pid()
    os.kill(pid, signal.SIGSTOP)  # wedge, don't kill: the nastier mode
    # the full dispatch window elapsing classifies the runner as wedged:
    # it is SIGKILLed, the circuit opens, clients continue on host
    assert _wait_state(sub_sup, "degraded", 10.0) or \
        sub_sup.state == "ready"
    for t in threads:
        t.join(timeout=30)
    assert not errors, f"queries errored during wedge: {errors[:5]}"
    assert not mismatches, f"host fallback diverged: {mismatches[:5]}"
    assert sub_sup.counters["device_dispatch_timeouts"] >= 1
    assert _gauge(ds, "device_dispatch_timeouts") >= 1
    # a fresh runner replaces the wedged (stopped) one
    assert _wait_state(sub_sup, "ready", 30.0), sub_sup.status()
    assert sub_sup.counters["device_restarts"] >= 1
    assert [r["id"] for r in ds.query(_knn_sql(queries[2]))[0]] \
        == expect_knn[2]


def test_query_budget_bounds_wedged_dispatch(sub_sup, chaos_ds):
    """A deadline-bounded query that reaches a wedged device must unwind
    within ITS budget — the dispatch wait is min(op timeout, remaining
    query budget), and the host fallback serves the answer."""
    from surrealdb_tpu import inflight

    ds, vecs = chaos_ds
    queries = vecs[:2]
    expect_knn, _hops = _host_truth(ds, vecs, queries)
    _warm_device(sub_sup, ds, queries)
    sub_sup.dispatch_timeout_s = 30.0  # only the QUERY budget may bound
    pid = sub_sup.runner_pid()
    os.kill(pid, signal.SIGSTOP)
    try:
        handle = ds.inflight.open("test", "test", "chaos",
                                  time.monotonic() + 0.5)
        t0 = time.monotonic()
        with inflight.activate(handle):
            res = ds.execute(_knn_sql(queries[0]), ns="test", db="test")
        elapsed = time.monotonic() - t0
        ds.inflight.close(handle)
        assert elapsed < 2.0, (
            f"query waited {elapsed:.2f}s on a wedged dispatch with a "
            f"0.5s budget"
        )
        # the short budget orphaned the dispatch and served from host
        if res[0].ok:
            assert [r["id"] for r in res[0].result] == expect_knn[0]
    finally:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def test_require_refuses_a_runner_that_is_not_a_tpu():
    """`require` means the chip: a runner on any other platform is an
    init error unless JAX_PLATFORMS named that platform."""
    from surrealdb_tpu.device.supervisor import require_refusal

    assert "needs a TPU" in require_refusal("require", "cpu", "")
    assert "needs a TPU" in require_refusal("require", "cpu", "tpu")
    assert require_refusal("require", "cpu", "cpu") is None
    assert require_refusal("require", "cpu", "tpu,cpu") is None
    assert require_refusal("require", "tpu", "") is None
    assert require_refusal("auto", "cpu", "") is None


def test_require_mode_surfaces_device_loss(chaos_ds, tmp_path,
                                           monkeypatch):
    """SURREAL_DEVICE=require: a degraded device is a query ERROR (the
    flagship-path posture), never a silent host fallback. And a compile
    is not a wedge: from an EMPTY compile cache the first query's XLA
    compile outlasts the 0.2 s dispatch window many times over, yet the
    runner — which announced it — is neither timed out nor killed."""
    ds, vecs = chaos_ds
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    sup = DeviceSupervisor(
        mode="require", dispatch_timeout_s=0.2, init_timeout_s=120.0,
        probe_interval_s=30.0, promote_successes=1,
    )
    old = set_supervisor(sup)
    try:
        assert sup.wait_ready(120)
        pid = sup.runner_pid()
        ok = ds.query(_knn_sql(vecs[0]))[0]
        assert len(ok) == 5
        assert sup.runner_pid() == pid
        assert sup.counters["device_dispatch_timeouts"] == 0
        assert sup.counters["device_restarts"] == 0
        assert sup.runner_status()["compile"]["persistent_misses"] >= 1
        os.kill(sup.runner_pid(), signal.SIGKILL)
        time.sleep(0.2)
        res = ds.execute(_knn_sql(vecs[0]), ns="test", db="test")
        assert not res[0].ok
        assert "device required" in (res[0].error or "")
    finally:
        set_supervisor(old)
        sup.shutdown()


def test_ann_reship_after_sigkill_midload(sub_sup, chaos_ds, monkeypatch):
    """Quantized-ANN crash/reship: the CAGRA blocks (graph + int8 rows)
    ship via the same (key, tag) protocol as the vector store, so a
    runner SIGKILL — including one landing MID-multipart-load — must
    (a) never error a query (the numpy descent mirror serves), and
    (b) reship from host truth on recovery with IDENTICAL results:
    same build epoch => same top-k, byte-stable across the cycle."""
    from surrealdb_tpu import cnf as _cnf

    ds, vecs = chaos_ds
    monkeypatch.setattr(_cnf, "KNN_ANN_MODE", "force")
    # a candidate set of 100/300 makes the device (int8 query) and the
    # numpy-mirror (f32 query) descents agree on the exact top-5 with
    # margin: the invariant under test is the reship cycle, not the
    # quantization edge
    monkeypatch.setattr(_cnf, "KNN_ANN_OVERSAMPLE", 20)
    sql = _knn_sql(vecs[0])
    ds.query(sql)  # instantiate the index engine
    ix = next(iter(ds.vector_indexes.values()))
    assert ix.ensure_ann()  # host-side graph build (device-independent)
    assert sub_sup.wait_ready(120), sub_sup.status()
    # every ANN ship streams as many small parts: the crash window below
    # reliably lands inside the part stream
    monkeypatch.setattr(sub_sup, "LOAD_PART_BYTES", 2048, raising=False)

    expect = [r["id"] for r in ds.query(sql)[0]]  # ships + searches
    assert len(expect) == 5
    assert [r["id"] for r in ds.query(sql)[0]] == expect  # deterministic

    # arm: the next ANN part stream loses its runner mid-ship
    orig_call = sub_sup.call
    kills = []

    def chaos_call(op, meta, bufs=(), **kw):
        if op == "ann_load_part" and not kills:
            kills.append(sub_sup.runner_pid())
            os.kill(kills[0], signal.SIGKILL)
        return orig_call(op, meta, bufs, **kw)

    monkeypatch.setattr(sub_sup, "call", chaos_call)
    os.kill(sub_sup.runner_pid(), signal.SIGKILL)  # drop the loaded blocks
    # every query during the outage serves from the numpy descent — and
    # the exact re-rank makes the answer identical either way
    deadline = time.monotonic() + 30.0
    while not kills and time.monotonic() < deadline:
        assert [r["id"] for r in ds.query(sql)[0]] == expect
        time.sleep(0.05)
    assert kills, "reship never re-attempted while armed"

    # disarm; the next recovery completes the ship and serves on-device
    monkeypatch.setattr(sub_sup, "call", orig_call)
    assert _wait_state(sub_sup, "ready", 30.0), sub_sup.status()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        assert [r["id"] for r in ds.query(sql)[0]] == expect
        if sub_sup.status().get("ann_blocks"):
            break
        time.sleep(0.05)
    assert [r["id"] for r in ds.query(sql)[0]] == expect
    assert sub_sup.counters["device_restarts"] >= 1
