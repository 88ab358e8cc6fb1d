"""What the served KNN path does before its RPC leaves (idx/vector.py,
idx/segments.py): the batch of query vectors is built from the riders'
buffers without ever letting the interpreter lock go, and the live-row
counts are kept where the mask is written instead of being reduced from
it by every query."""

import statistics
import threading
import time

import numpy as np
import pytest

from surrealdb_tpu import cnf
from surrealdb_tpu.idx.vector import TpuVectorIndex, _Coalescer
from surrealdb_tpu.val import RecordId


class _Capture:
    """An index double that keeps the batch `_dispatch` hands it."""

    def __init__(self, dtype=np.float32):
        self.lock = threading.RLock()
        self.dtype = dtype
        self.got = None

    def knn_batch(self, qvs, kmax):
        self.got = qvs
        return [[(i, 0.0)] * kmax for i in range(len(qvs))]

    _host_knn_multi = knn_batch


# -- (i) the batch ------------------------------------------------------------


@pytest.mark.parametrize("dim", [128, 768])
@pytest.mark.parametrize("b", [1, 2, 17])
@pytest.mark.parametrize("entry", ["_dispatch", "_fallback_batch"])
def test_batch_is_the_stack_of_the_riders(dim, b, entry):
    rng = np.random.default_rng(dim + b)
    riders = [rng.normal(size=dim).astype(np.float32) for _ in range(b)]
    ix = _Capture()
    res = getattr(_Coalescer(ix), entry)(
        [(q, 1 + i % 3) for i, q in enumerate(riders)]
    )
    qvs = ix.got
    assert qvs.shape == (b, dim) and qvs.dtype == np.float32
    assert qvs.flags.c_contiguous
    assert np.array_equal(qvs, np.stack(riders))
    # every rider gets its own row's answer, cut to its own k
    assert [r[0][0] for r in res] == list(range(b))
    assert [len(r) for r in res] == [1 + i % 3 for i in range(b)]


@pytest.mark.parametrize("odd", ["strided", "float64", "list"])
def test_batch_converts_a_payload_off_the_fast_path(odd):
    rng = np.random.default_rng(5)
    dim = 768
    plain = rng.normal(size=dim).astype(np.float32)
    if odd == "strided":
        other = rng.normal(size=2 * dim).astype(np.float32)[::2]
        assert not other.flags.c_contiguous
    elif odd == "float64":
        other = rng.normal(size=dim)
    else:
        other = [float(x) for x in rng.normal(size=dim)]
    ix = _Capture()
    _Coalescer(ix)._dispatch([(plain, 1), (other, 1), (plain, 1)])
    want = np.stack([plain, np.asarray(other, np.float32), plain])
    assert ix.got.dtype == np.float32 and ix.got.flags.c_contiguous
    assert np.array_equal(ix.got, want)


def test_batch_keeps_the_dtype_of_a_float64_index():
    riders = [np.arange(4.0) + i for i in range(3)]
    ix = _Capture(dtype=np.float64)
    _Coalescer(ix)._dispatch([(q, 1) for q in riders])
    assert ix.got.dtype == np.float64
    assert np.array_equal(ix.got, np.stack(riders))


def test_sealed_segment_descent_reads_the_batch_itself(monkeypatch):
    """On a sealed-segment engine the f32 queries the descent gets (the
    buffer `_ann_device_search` puts on the wire, and the host mirror's)
    are the dispatcher's batch, not a second copy of it."""
    from surrealdb_tpu.device import DeviceUnavailable
    from surrealdb_tpu.idx import cagra

    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 256)
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    rng = np.random.default_rng(9)
    n, dim = 300, 768
    ix = TpuVectorIndex("t", "t", "pts", "ix", {
        "dimension": dim, "distance": "cosine", "vector_type": "f32"})
    ix.vecs = rng.normal(size=(n, dim)).astype(np.float32)
    ix.valid = np.ones(n, dtype=bool)
    ix.rids = [RecordId("pts", i) for i in range(n)]
    ix.version = 0
    assert ix.ensure_ann()
    assert ix.ann_plan(10)["ann"] == "segmented"

    seen = {}
    real_batch = ix.knn_batch
    real_score = cagra.int8_score_fn

    def spy_batch(qvs, k):
        seen["batch"] = qvs
        return real_batch(qvs, k)

    def spy_device(ann, qs32, kc, dev_key=None, tag=None):
        seen["wire"] = qs32
        raise DeviceUnavailable("the test has no runner")

    def spy_score(ann, qs32):
        seen["mirror"] = qs32
        return real_score(ann, qs32)

    monkeypatch.setattr(ix, "knn_batch", spy_batch)
    monkeypatch.setattr(ix, "_use_device", lambda: True)
    monkeypatch.setattr(ix, "_ann_device_search", spy_device)
    monkeypatch.setattr(cagra, "int8_score_fn", spy_score)
    riders = [ix.vecs[i].copy() for i in (3, 77, 201)]
    res = ix.coalescer._dispatch([(q, 10) for q in riders])
    assert [r[0][0].id for r in res] == [3, 77, 201]
    batch = seen["batch"]
    assert batch.dtype == np.float32 and batch.flags.c_contiguous
    for name in ("wire", "mirror"):
        assert seen[name].flags.c_contiguous
        assert np.shares_memory(seen[name], batch), name


# -- (ii) the mechanism -------------------------------------------------------


def test_batch_assembly_never_lets_the_interpreter_lock_go():
    """numpy gives the interpreter lock up around every copy of more
    than 500 elements; with other threads wanting the lock the copying
    thread then waits a switch interval or more, once a rider, to get
    it back (`np.stack` below: the contrast, printed and not asserted
    on). A join of the riders' buffers holds the lock from start to
    end, so its time stays what it is alone: tens of microseconds."""
    b, dim = 16, 768
    rng = np.random.default_rng(1)
    payloads = [(rng.normal(size=dim).astype(np.float32), 10)
                for _ in range(b)]
    co = _Coalescer(_Capture())
    end = time.monotonic() + 20.0  # the test's own time limit
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set() and time.monotonic() < end + 5.0:
            for _ in range(2000):
                x += 1

    spinners = [threading.Thread(target=spin, daemon=True)
                for _ in range(8)]
    for t in spinners:
        t.start()

    def timed(fn, tries):
        out = []
        for _ in range(tries):
            if time.monotonic() > end:
                break
            time.sleep(0.001)  # start each try with a fresh time slice
            t0 = time.perf_counter()
            fn()
            out.append(time.perf_counter() - t0)
        return out

    try:
        new = timed(lambda: co._stack(payloads), 50)
        old = timed(lambda: np.stack([q for q, _k in payloads]), 3)
    finally:
        stop.set()
        for t in spinners:
            t.join(10.0)
    assert len(new) >= 10, "the spinning threads starved the test"
    if old:
        print(f"join {statistics.median(new) * 1e3:.3f} ms, "
              f"np.stack {statistics.median(old) * 1e3:.1f} ms")
    assert statistics.median(new) < 1e-3


# -- (iii) the kept counts ----------------------------------------------------


def _check_counts(ix, want_live):
    assert ix.live == int(ix.valid.sum()) == want_live
    segs = ix._segs
    spans = list(segs.segs) if segs is not None else []
    for s in spans:
        assert s.live == int(np.count_nonzero(ix.valid[s.lo:s.hi])), \
            (s.lo, s.hi)
    return len(spans)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_kept_counts_follow_the_mask(monkeypatch, seed):
    """A seeded random sequence of insert / overwrite / delete /
    re-insert / rebuild / seal through SQL and the index's own sync:
    after every step the store's `live` is the mask's sum and every
    sealed span's `live` the count of its flags."""
    from surrealdb_tpu import Datastore

    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 32)
    monkeypatch.setattr(cnf, "KNN_SEG_FANOUT", 2)
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    rng = np.random.default_rng(seed)
    dim = 8
    ds = Datastore("memory")

    def vec():
        return "[" + ", ".join(f"{x:.4f}" for x in rng.normal(size=dim)) + "]"

    ds.query("DEFINE TABLE t; DEFINE INDEX ix ON t FIELDS v HNSW "
             f"DIMENSION {dim} DIST EUCLIDEAN TYPE F32")
    search = f"SELECT id FROM t WHERE v <|3,10|> {vec()}"
    live: set = set()
    dead: set = set()
    next_id = 0

    def insert():
        nonlocal next_id
        ids = range(next_id, next_id + int(rng.integers(5, 40)))
        next_id = ids.stop
        live.update(ids)
        return "".join(f"CREATE t:{i} SET v = {vec()};" for i in ids)

    def some(pool, most):
        pool = sorted(pool)
        take = min(len(pool), int(rng.integers(1, most)))
        return [int(i) for i in rng.choice(pool, take, replace=False)] \
            if take else []

    def overwrite():
        return "".join(f"UPDATE t:{i} SET v = {vec()};"
                       for i in some(live, 12))

    def delete():
        ids = some(live, 20)
        live.difference_update(ids)
        dead.update(ids)
        return "".join(f"DELETE t:{i};" for i in ids)

    def reinsert():
        ids = some(dead, 10)
        dead.difference_update(ids)
        live.update(ids)
        return "".join(f"CREATE t:{i} SET v = {vec()};" for i in ids)

    writes = [insert, overwrite, delete, reinsert]
    ds.query(insert())
    ds.query(search)  # the first sync builds the engine
    ix = next(iter(ds.vector_indexes.values()))
    _check_counts(ix, len(live))
    sealed = rebuilt = 0
    for step in range(30):
        op = int(rng.integers(0, 6))
        if op == 4:
            # what memory pressure does: the host arrays go, the next
            # sync rebuilds them from the KV rows (dead rows drop out)
            ix._mem_evict_vec()
            _check_counts(ix, 0)
            rebuilt += 1
        elif op == 5:
            ix.ensure_ann()  # seal, build and merge to quiescence
        else:
            # one or two writes between two syncs, so that a row can
            # be appended and tombstoned inside one batch of the log
            sql = writes[op]() + (
                writes[int(rng.integers(0, 4))]() if step % 3 == 0 else ""
            )
            if sql:
                ds.query(sql)
        ds.query(search)
        sealed = max(sealed, _check_counts(ix, len(live)))
    assert sealed >= 1, "no span was sealed: the test lost its subject"
    # and the spans' counts are what the graph-served fan-out reads:
    # its answers are the unsegmented exact scan's
    ix.ensure_ann()
    qs = rng.normal(size=(4, dim)).astype(np.float32)
    with ix.rw.read():
        got = ix.knn_batch(qs, 5)
        monkeypatch.setattr(cnf, "KNN_SEG_MODE", "off")
        want = ix.knn_batch(qs, 5)
    assert [[(r.id, d) for r, d in row] for row in got] \
        == [[(r.id, d) for r, d in row] for row in want]
    ds.close()


def test_kept_counts_hold_against_a_concurrent_sealer(monkeypatch):
    """The writer flips flags under the engine's write lock while another
    thread seals, builds and merges spans under the table lock only: a
    span counted at its seal while one of its flags is changing must end
    with the count of its flags all the same (a flag counted twice, or
    not at all, would show here)."""
    import sys

    monkeypatch.setattr(cnf, "KNN_SEG_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_SEG_ROWS", 16)
    monkeypatch.setattr(cnf, "KNN_SEG_FANOUT", 2)
    monkeypatch.setattr(cnf, "KNN_ANN_MODE", "force")
    monkeypatch.setattr(cnf, "KNN_HOST_BATCH", "host")
    dim = 8
    ix = TpuVectorIndex("t", "t", "pts", "ix", {
        "dimension": dim, "distance": "euclidean", "vector_type": "f32"})
    ix.version = 0
    rng = np.random.default_rng(3)
    segs = ix._segments()
    stop = threading.Event()
    failed = []

    def sealer():
        try:
            turn = 0
            while not stop.is_set():
                turn += 1
                with segs.lock:
                    segs._seal_locked()
                if turn % 50 == 0:
                    segs.drain(timeout_s=2.0)
        except Exception as e:  # noqa: BLE001
            failed.append(e)

    def raw():
        return rng.normal(size=dim).astype(np.float32).tobytes()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    th = threading.Thread(target=sealer, daemon=True)
    th.start()
    try:
        n_ids = 0
        end = time.monotonic() + 2.0
        while time.monotonic() < end and n_ids < 4000:
            entries = [("set", n_ids + i, raw()) for i in range(8)]
            n_ids += 8
            for i in rng.integers(0, n_ids, 6):
                entries.append(("del", int(i), None))
            for i in rng.integers(0, n_ids, 4):
                entries.append(("set", int(i), raw()))
            # `_ann_lock` too: the log applier writes the dirty-row map
            # without it while a merge rebuilds the map under it (as
            # the parent does; not this test's subject)
            with ix.lock, ix.rw.write(), ix._ann_lock:
                ix._apply_entries(entries)
    finally:
        stop.set()
        th.join(30.0)
        sys.setswitchinterval(old)
    assert not th.is_alive() and not failed, failed
    assert _check_counts(ix, int(ix.valid.sum())) >= 2
