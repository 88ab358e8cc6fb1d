"""The PR-6 cross-query scoring batcher (device/batcher.py) and its
serving integration: batch size must grow with client concurrency,
batched results must be byte-identical to the sequential path, expired
riders must withdraw from queued batches, a poisoned rider must never
fail its batchmates, CSR hop expansion must coalesce, and the
persistent compile cache must survive a runner restart."""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from surrealdb_tpu import cnf
from surrealdb_tpu.device.batcher import BatchStats, DeviceBatcher
from surrealdb_tpu.val import RecordId


def _mk_index(n=512, dim=16, metric="cosine", seed=5):
    from surrealdb_tpu.idx.vector import TpuVectorIndex

    rng = np.random.default_rng(seed)
    ix = TpuVectorIndex("t", "t", "pts", "ix", {
        "dimension": dim, "distance": metric, "vector_type": "f32",
    })
    ix.vecs = rng.normal(size=(n, dim)).astype(np.float32)
    ix.valid = np.ones(n, dtype=bool)
    ix.rids = [RecordId("pts", i) for i in range(n)]
    ix.version = 0
    return ix, rng


# -- batch growth + byte identity -------------------------------------------

def test_batch_grows_with_concurrency_and_results_bit_identical(
    monkeypatch,
):
    """Concurrent riders coalesce into larger dispatches, and every
    rider's (rid, dist) list is byte-identical to what a sequential
    one-query-at-a-time run returns (host BLAS path: gemm prefix
    columns are bitwise stable, single queries pad to 2 columns)."""
    import surrealdb_tpu.idx.vector as V

    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    # strict one-batch-at-a-time coalescing: this test asserts MAXIMAL
    # batch growth, which overlapped (pipelined) dispatch trades away
    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE", 1)
    monkeypatch.setattr(V, "DEVICE_MIN_ROWS", 16)
    ix, rng = _mk_index(n=4096, dim=32)
    qs = rng.normal(size=(64, 32)).astype(np.float32)

    sequential = [ix._raw_knn(q, 10) for q in qs]

    sizes = []
    orig = ix.coalescer.dispatch  # bound at batcher construction

    def spy(payloads):
        sizes.append(len(payloads))
        return orig(payloads)

    ix.coalescer.dispatch = spy

    # gate the FIRST dispatch so the rest of the clients pile up behind
    # it and must share one (or a few) coalesced follow-up dispatches
    gate = threading.Event()
    first = threading.Event()
    orig_multi = ix._host_knn_multi

    def gated_multi(qvs, k):
        if not first.is_set():
            first.set()
            assert gate.wait(10)
        return orig_multi(qvs, k)

    ix._host_knn_multi = gated_multi
    out = {}

    def go(i):
        out[i] = ix._raw_knn(qs[i], 10)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(64)]
    threads[0].start()
    assert_deadline = time.monotonic() + 10
    while not first.is_set() and time.monotonic() < assert_deadline:
        time.sleep(0.002)
    for t in threads[1:]:
        t.start()
    time.sleep(0.2)  # let the riders enqueue behind the gated dispatch
    gate.set()
    for t in threads:
        t.join(timeout=10)
    assert len(out) == 64
    assert max(sizes) >= 32, f"riders did not coalesce: {sizes}"
    for i in range(64):
        got = out[i]
        want = sequential[i]
        assert [r.id for r, _ in got] == [r.id for r, _ in want]
        # BYTE identity: the float distances match exactly
        assert [d for _r, d in got] == [d for _r, d in want], \
            f"rider {i}: batched distances differ from sequential"


def test_host_single_equals_host_multi_row(monkeypatch):
    """The 1-query path pads to a 2-column gemm: bit-identical to the
    same query inside a larger batch."""
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    import surrealdb_tpu.idx.vector as V

    monkeypatch.setattr(V, "DEVICE_MIN_ROWS", 16)
    for metric in ("cosine", "euclidean", "dot"):
        ix, rng = _mk_index(n=4096, dim=24, metric=metric, seed=7)
        qs = rng.normal(size=(16, 24)).astype(np.float32)
        multi = ix._host_knn_multi(qs, 8)
        for b in range(16):
            single = ix._host_knn_single(qs[b], 8)
            assert [(r.id, d) for r, d in single] == \
                [(r.id, d) for r, d in multi[b]], metric


# -- deadline withdrawal ------------------------------------------------------

def test_expired_rider_withdraws_from_queued_batch():
    """A rider whose query budget expires while parked behind an
    in-flight dispatch raises QueryTimeout promptly and withdraws its
    queue entry (it must not ride — or hold up — the next batch)."""
    from surrealdb_tpu import inflight
    from surrealdb_tpu.err import QueryTimeout

    gate = threading.Event()
    started = threading.Event()

    def dispatch(payloads):
        started.set()
        assert gate.wait(10)
        return [p * 2 for p in payloads]

    b = DeviceBatcher(dispatch=dispatch, stats=BatchStats())
    res = {}
    t1 = threading.Thread(target=lambda: res.setdefault("a", b.submit(1)),
                          daemon=True)
    t1.start()
    assert started.wait(5)

    reg = inflight.InflightRegistry()
    h = reg.open("t", "t", "knn", deadline=time.monotonic() + 0.15)
    err = {}

    def rider():
        with inflight.activate(h):
            try:
                b.submit(2)
            except QueryTimeout as e:
                err["e"] = e

    t2 = threading.Thread(target=rider, daemon=True)
    t0 = time.monotonic()
    t2.start()
    t2.join(timeout=3)
    assert not t2.is_alive(), "expired rider still parked"
    assert "e" in err and time.monotonic() - t0 < 1.0
    assert h.timed_out
    with b.cond:
        assert not b.queue, "timed-out rider left its queue entry"
    gate.set()
    t1.join(timeout=5)
    assert res["a"] == 2
    reg.close(h)


# -- per-rider degradation isolation -----------------------------------------

def test_per_rider_isolation_through_degrade_ladder():
    """Batch kernel fails retryably, the batched fallback fails too:
    every rider is answered INDIVIDUALLY — the poisoned rider gets its
    own error, its batchmates all succeed."""

    class Boom(Exception):
        pass

    def dispatch(payloads):
        raise Boom("device down")

    def fallback_batch(payloads):
        raise RuntimeError("host batch kernel exploded")

    def fallback_one(p):
        if p == "poison":
            raise ValueError("bad rider")
        return f"ok-{p}"

    b = DeviceBatcher(dispatch=dispatch, fallback_batch=fallback_batch,
                      fallback=fallback_one, retryable=(Boom,),
                      stats=BatchStats())
    # force one coalesced batch: gate the first dispatch via a plain
    # submit on a thread, then pile the rest behind it
    results = {}
    errors = {}

    def go(p):
        try:
            results[p] = b.submit(p)
        except Exception as e:
            errors[p] = e

    ts = [threading.Thread(target=go, args=(p,))
          for p in ("a", "poison", "b", "c")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=5)
    assert results == {"a": "ok-a", "b": "ok-b", "c": "ok-c"}
    assert isinstance(errors["poison"], ValueError)


def test_batched_host_fallback_serves_whole_batch():
    """Device failure degrades to ONE batched host kernel call (the
    fallback paths batch too), not per-rider singles."""

    class Down(Exception):
        pass

    calls = []

    def dispatch(payloads):
        raise Down()

    def fallback_batch(payloads):
        calls.append(len(payloads))
        return [p + 100 for p in payloads]

    b = DeviceBatcher(dispatch=dispatch, fallback_batch=fallback_batch,
                      retryable=(Down,), stats=BatchStats())
    assert b.submit(1) == 101
    assert calls == [1]


# -- CSR hop batching ---------------------------------------------------------

def test_csrstore_batched_hops_match_single(monkeypatch):
    """[B, n] stacked-mask hop expansion == per-mask loop (the device
    kernel the graph batcher dispatches)."""
    from surrealdb_tpu.device.csrstore import CsrStore

    rng = np.random.default_rng(2)
    n, e = 50, 200
    rows = rng.integers(0, n, size=e).astype(np.int32)
    cols = rng.integers(0, n, size=e).astype(np.int32)
    st = CsrStore("k", rows, cols, n)
    masks = np.zeros((3, n), np.uint8)
    masks[0, 0] = masks[1, 7] = masks[2, 13] = 1
    for hops in (1, 2, 3):
        for union in (False, True):
            batched = st.multi_hop(masks, hops, union)
            for b in range(3):
                single = st.multi_hop(masks[b], hops, union)
                assert np.array_equal(batched[b], single), \
                    (hops, union, b)


def test_graph_multi_hop_coalesces(monkeypatch):
    """Concurrent CsrGraph.multi_hop riders share one stacked device
    call, with results identical to sequential calls."""
    from surrealdb_tpu.graph.csr import CsrGraph

    g = CsrGraph("t", "t", "n", "e", "out")
    rng = np.random.default_rng(4)
    nn, ne = 40, 120
    g.node_ids = list(range(nn))
    g.node_index = {}
    from surrealdb_tpu import key as K

    for i in range(nn):
        g.node_index[K.enc_value(i)] = i
    g.rows = rng.integers(0, nn, size=ne).astype(np.int32)
    g.cols = rng.integers(0, nn, size=ne).astype(np.int32)
    g._built = True

    sequential = {s: sorted(g.multi_hop([s], 2)) for s in range(8)}

    sizes = []
    orig = g._batcher.dispatch  # bound at lazy batcher construction
    gate = threading.Event()
    first = threading.Event()

    # gate via the dispatch path: block the first device dispatch so
    # riders coalesce behind it
    def gated_spy(payloads):
        sizes.append(len(payloads))
        if not first.is_set():
            first.set()
            assert gate.wait(10)
        return orig(payloads)

    g._batcher.dispatch = gated_spy
    out = {}

    def go(s):
        out[s] = sorted(g.multi_hop([s], 2))

    ts = [threading.Thread(target=go, args=(s,)) for s in range(8)]
    ts[0].start()
    deadline = time.monotonic() + 10
    while not first.is_set() and time.monotonic() < deadline:
        time.sleep(0.002)
    for t in ts[1:]:
        t.start()
    time.sleep(0.2)
    gate.set()
    for t in ts:
        t.join(timeout=10)
    assert out == sequential
    assert max(sizes) >= 4, f"hop riders did not coalesce: {sizes}"


# -- pipelined dispatch -------------------------------------------------------

def test_pipelined_second_dispatch_overlaps(monkeypatch):
    """With pipeline depth 2, a second batch launches while the first
    is still inside its kernel once PIPELINE_MIN riders are queued."""
    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE", 2)
    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE_MIN", 4)
    gate = threading.Event()
    in_flight = []
    overlap = threading.Event()

    def dispatch(payloads):
        in_flight.append(len(payloads))
        if len(in_flight) == 1:
            assert gate.wait(10)
        else:
            overlap.set()
        return list(payloads)

    b = DeviceBatcher(dispatch=dispatch, stats=BatchStats())
    ts = [threading.Thread(target=b.submit, args=(i,), daemon=True)
          for i in range(8)]
    ts[0].start()
    deadline = time.monotonic() + 5
    while not in_flight and time.monotonic() < deadline:
        time.sleep(0.002)
    for t in ts[1:]:
        t.start()
    # the overlapped dispatch must start WHILE the first is gated
    assert overlap.wait(5), "second dispatch never overlapped the first"
    gate.set()
    for t in ts:
        t.join(timeout=5)


# -- compile cache ------------------------------------------------------------

def test_compile_cache_dir_from_environment(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the cache: the runner reports
    it and sets no directory of its own (jax reads the variable
    natively). The subprocess rehearsal in test_chip_smoke.py shows
    entries landing there."""
    import jax

    from surrealdb_tpu.device import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    calls = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (calls.append(name), real(name, val)))
    old_dir = jax.config.jax_compilation_cache_dir
    compile_cache.reset_for_tests()
    try:
        info = compile_cache.initialize()
        assert info["dir"] == str(tmp_path / "jc") and info["from_env"]
        assert "jax_compilation_cache_dir" not in calls
        assert jax.config.jax_compilation_cache_dir == old_dir
    finally:
        compile_cache.reset_for_tests()


def test_compile_cache_dir_fixed_in_checkout(tmp_path, monkeypatch):
    """Without the variable: one fixed path in the checkout, whatever
    the working directory, home or datastore."""
    import jax

    from surrealdb_tpu import Datastore
    from surrealdb_tpu.device import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.chdir(tmp_path)
    Datastore(f"file://{tmp_path}/store.skv").close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.cache_dir() == want
    old_dir = jax.config.jax_compilation_cache_dir
    compile_cache.reset_for_tests()
    try:
        info = compile_cache.initialize()
        assert info["dir"] == want and not info["from_env"]
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        compile_cache.reset_for_tests()
        jax.config.update("jax_compilation_cache_dir", old_dir)


def test_prewarm_op_compiles_bucket_ladder():
    from surrealdb_tpu.device.handlers import DeviceHost

    host = DeviceHost()
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(128, 8)).astype(np.float32)
    host.handle("vec_load", {
        "key": "p", "tag": [1, 0], "metric": "cosine",
        "mink_p": 3.0, "cfg": {
            "hbm_budget": 1 << 30, "score_budget": 1 << 20,
            "query_chunk": 64, "int8_oversample": 8,
            "block_rows": 1 << 20,
        },
    }, [vecs, np.ones(128, np.uint8)])
    t, meta, _b = host.handle(
        "vec_prewarm", {"key": "p", "tag": [1, 0], "buckets": [1, 4, 8]},
        [],
    )
    assert t == "ok"
    assert meta["warmed"] == [1, 4, 8]
    # stale tag answers stale, not an error
    t2, _m2, _b2 = host.handle(
        "vec_prewarm", {"key": "p", "tag": [9, 9], "buckets": [1]}, [],
    )
    assert t2 == "stale"


# -- batching telemetry -------------------------------------------------------

def test_batch_stats_recorded():
    stats = BatchStats()

    def dispatch(payloads):
        return list(payloads)

    b = DeviceBatcher(dispatch=dispatch, stats=stats)
    b.submit(1)
    b.submit(2)
    d = stats.to_dict()
    assert d["dispatches"] == 2 and d["riders"] == 2
    assert d["last"] == 1 and d["max"] >= 1
