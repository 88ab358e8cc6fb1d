"""Benchmarks: the five BASELINE.md configs driven through the DATABASE
(parser → planner → TpuVectorIndex / graph engine), not raw kernels.

Prints ONE JSON line (the primary metric) to stdout; `--all` prints one
line per config. vs_baseline compares against a single-host CPU
comparator measured on the same data: a numpy HNSW-style greedy-graph
search for the KNN configs (the reference's own comparator class — its
CPU HNSW), and a numpy adjacency walk for the graph config.

Configs (BASELINE.md + the north-star 10M config):
  1. hnsw100k  DEFINE INDEX ... HNSW DIMENSION 128 + SELECT <|10|>  (100k)
  2. knn1m     1M x 768 cosine SELECT <|10,40|>                     (1M)
  3. knn10m    10M x 768 cosine SELECT <|10|> — int8 rank store,
               exact host rescore, recall vs exact ground truth (DEFAULT)
  4. ann10m    10M x 768 cosine through the quantized CAGRA graph index
               (int8 descent + exact re-rank); 250k on CPU containers
  5. brute     vector::similarity::cosine scan, no index
  6. graph3hop SELECT ->knows->person 3-hop over a RELATE graph
  7. hybrid    BM25 @@ + HNSW rerank (search::rrf)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_PLATFORM = None


def _probe_backend() -> str:
    """Bring up the device path BEFORE any expensive ingest, or fail.

    The probe IS the serving architecture: spawn the supervised
    DeviceRunner under its init watchdog in `require` mode — a runner
    that comes up on anything but a TPU is an init error, device
    trouble during a config surfaces as a query error, and nothing
    answers from the host unnoticed. The warmed supervisor is
    installed as the process singleton, so the benched SQL queries
    dispatch to the very runner the probe validated.

    An explicit `JAX_PLATFORMS=cpu` is the one way to run without a
    chip: device ops then run inline (offloading numpy-speed kernels
    to a subprocess would only measure IPC) and every line says
    `platform: cpu`. Returns the platform name."""
    global _PLATFORM
    if _PLATFORM is not None:
        return _PLATFORM
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        os.environ.setdefault("SURREAL_DEVICE", "inline")
        _PLATFORM = "cpu"
        return _PLATFORM
    from surrealdb_tpu import cnf
    from surrealdb_tpu.device import DeviceSupervisor, set_supervisor

    timeout_s = cnf.BACKEND_INIT_TIMEOUT_S
    sup = DeviceSupervisor(mode="require", init_timeout_s=timeout_s)
    if not sup.wait_ready(timeout_s + 10):
        err = (sup.last_error or "backend init failed")[-500:]
        sup.shutdown()
        raise SystemExit(
            f"bench: no accelerator, no measurement: {err} "
            f"(JAX_PLATFORMS=cpu runs the CPU configs deliberately)"
        )
    _PLATFORM = sup.platform
    set_supervisor(sup)
    print(f"bench: backend ready: {_PLATFORM} ({sup.device_kind}) x"
          f"{sup.device_count} (supervised runner pid "
          f"{sup.runner_pid()})", file=sys.stderr, flush=True)
    return _PLATFORM


def _bulk_vectors(ds, ns, db, tb, ix_name, xs, dim, metric="euclidean",
                  inline_emb=False):
    """Fast ingest: records + vector-index state through the KV layer (the
    SQL INSERT path is not the thing under test here). `inline_emb` also
    stores the vector in the document (needed only by the brute scan)."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    txn = ds.transaction(write=True)
    try:
        n = xs.shape[0]
        ver = 0
        for i in range(n):
            rid = RecordId(tb, i)
            doc = {"id": rid}
            if inline_emb:
                doc["emb"] = xs[i].tolist()
            txn.set(K.record(ns, db, tb, i), serialize(doc))
            txn.set_val(
                K.ix_state(ns, db, tb, ix_name, b"he", K.enc_value(i)),
                xs[i].tobytes(),
            )
            ver += 1
        txn.set_val(K.ix_state(ns, db, tb, ix_name, b"vn"), ver)
        txn.commit()
    except BaseException:
        txn.cancel()
        raise


def _setup_knn(ds, n, dim, metric):
    rng = np.random.default_rng(7)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    ds.query(
        f"DEFINE TABLE tbl; DEFINE INDEX ix ON tbl FIELDS emb HNSW "
        f"DIMENSION {dim} DIST {metric.upper()} TYPE F32",
        ns="b", db="b",
    )
    _bulk_vectors(ds, "b", "b", "tbl", "ix", xs, dim)
    return xs


def _run_queries(ds, sql_tmpl, qs, iters, threads=1):
    """Drive `iters` SQL KNN queries; with threads>1 they run as concurrent
    clients, so the index's cross-query coalescer batches device work (the
    production access pattern for a threaded server)."""
    qlists = [q.tolist() for q in qs]

    def one(i):
        rows = ds.query_one(
            sql_tmpl, ns="b", db="b", vars={"q": qlists[i % len(qlists)]}
        )
        assert rows, "no results"

    if threads <= 1:
        t0 = time.perf_counter()
        for i in range(iters):
            one(i)
        return iters / (time.perf_counter() - t0)
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(threads) as ex:
        t0 = time.perf_counter()
        list(ex.map(one, range(iters)))
        return iters / (time.perf_counter() - t0)


def _recall_at_10(ds, tb, xs, qs, sql_tmpl, metric="cosine", nq=16):
    """Exact ground truth (numpy f64 brute) vs the SQL results."""
    if metric == "cosine":
        xn = xs / np.maximum(
            np.linalg.norm(xs, axis=1, keepdims=True), 1e-30
        )
    hits = 0
    for i in range(nq):
        q = qs[i]
        if metric == "cosine":
            qn = q / max(np.linalg.norm(q), 1e-30)
            d = 1.0 - xn @ qn
        else:
            d = ((xs - q) ** 2).sum(axis=1)
        truth = set(np.argsort(d, kind="stable")[:10].tolist())
        rows = ds.query_one(
            sql_tmpl, ns="b", db="b", vars={"q": q.tolist()}
        )
        got = {r["id"].id for r in rows}
        hits += len(truth & got)
    return hits / (10 * nq)


def _index_engine_qps(ix, qs, repeat, k=10):
    """Raw index-engine ceiling on the same box: one big batch through
    `ix.knn_batch` — the EXACT entry the serving path's cross-query
    batcher dispatches (device on accelerators, batched BLAS host on
    cpu). sql_knn_qps vs this number is pure serving-stack tax; the
    conformance perf-smoke keeps the ratio from regressing."""
    big = np.repeat(qs, repeat, axis=0)
    ix.knn_batch(big, k)  # warm: compile + stat caches
    t0 = time.perf_counter()
    ix.knn_batch(big, k)
    return len(big) / (time.perf_counter() - t0)


class _HostHnsw:
    """A compact CPU HNSW (numpy distances, greedy beam search) standing in
    for the reference's CPU comparator (surrealdb/benches/index_hnsw.rs)."""

    def __init__(self, xs, m=16, efc=100, seed=5):
        self.xs = xs.astype(np.float32)
        n = xs.shape[0]
        rng = np.random.default_rng(seed)
        self.neighbors = [[] for _ in range(n)]
        self.entry = 0
        order = rng.permutation(n)
        for count, i in enumerate(order):
            if count == 0:
                self.entry = int(i)
                continue
            cand = self.search(self.xs[i], k=m, ef=efc, _building=count)
            self.neighbors[i] = [c for c, _d in cand[:m]]
            for c, _d in cand[:m]:
                nb = self.neighbors[c]
                nb.append(int(i))
                if len(nb) > m * 2:
                    d = np.linalg.norm(self.xs[nb] - self.xs[c], axis=1)
                    keep = np.argsort(d)[: m * 2]
                    self.neighbors[c] = [nb[int(j)] for j in keep]

    def search(self, q, k=10, ef=80, _building=None):
        import heapq

        visited = {self.entry}
        d0 = float(np.linalg.norm(self.xs[self.entry] - q))
        cands = [(d0, self.entry)]
        best = [(-d0, self.entry)]
        while cands:
            d, node = heapq.heappop(cands)
            if -best[0][0] < d and len(best) >= ef:
                break
            nbrs = [x for x in self.neighbors[node] if x not in visited]
            if not nbrs:
                continue
            visited.update(nbrs)
            ds_ = np.linalg.norm(self.xs[nbrs] - q, axis=1)
            for nb, dd in zip(nbrs, ds_):
                dd = float(dd)
                if len(best) < ef or dd < -best[0][0]:
                    heapq.heappush(cands, (dd, int(nb)))
                    heapq.heappush(best, (-dd, int(nb)))
                    if len(best) > ef:
                        heapq.heappop(best)
        out = sorted(((-nd, i) for nd, i in best))
        return [(i, d) for d, i in out[:k]]


def bench_hnsw100k(quick=False):
    from surrealdb_tpu import Datastore
    from surrealdb_tpu.idx import vector as V

    n = 10_000 if quick else 100_000
    dim = 128
    ds = Datastore("memory")
    xs = _setup_knn(ds, n, dim, "euclidean")
    rng = np.random.default_rng(11)
    qs = rng.normal(size=(64, dim)).astype(np.float32)
    sql = "SELECT id FROM tbl WHERE emb <|10|> $q"
    _run_queries(ds, sql, qs, 3)  # warm: sync + compile
    _run_queries(ds, sql, qs, 64, threads=64)  # warm batched kernel shapes
    qps = _run_queries(ds, sql, qs, 256 if quick else 2048, threads=64)
    recall = _recall_at_10(ds, "tbl", xs, qs, sql, metric="euclidean")
    ix = ds.vector_indexes[("b", "b", "tbl", "ix")]
    kernel_qps = _index_engine_qps(ix, qs, 16 if quick else 64)

    # CPU HNSW comparator on a subsample (build cost bounds the size)
    bn = min(n, 20_000)
    hnsw = _HostHnsw(xs[:bn])
    t0 = time.perf_counter()
    for i in range(32):
        hnsw.search(qs[i % len(qs)], k=10, ef=80)
    base_qps = 32 / (time.perf_counter() - t0)
    return {
        "metric": f"sql_knn_qps_hnsw_{n//1000}k_{dim}d",
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": round(qps / base_qps, 2),
        "recall_at_10": round(recall, 4),
        "cpu_hnsw_qps": round(base_qps, 2),
        "cpu_hnsw_n": bn,
        "index_engine_qps": round(kernel_qps, 2),
        "clients": 64,
    }


def bench_knn1m(quick=False):
    from surrealdb_tpu import Datastore

    n = 50_000 if quick else 1_000_000
    dim = 128 if quick else 768
    ds = Datastore("memory")
    xs = _setup_knn(ds, n, dim, "cosine")
    rng = np.random.default_rng(13)
    qs = rng.normal(size=(64, dim)).astype(np.float32)
    sql = "SELECT id FROM tbl WHERE emb <|10,40|> $q"
    _run_queries(ds, sql, qs, 3)
    _run_queries(ds, sql, qs, 128, threads=128)  # warm batched shapes
    qps = _run_queries(ds, sql, qs, 256 if quick else 2048, threads=128)
    recall = _recall_at_10(ds, "tbl", xs, qs, sql, metric="cosine",
                           nq=4 if quick else 16)

    # raw index-engine throughput (same TpuVectorIndex the SQL used),
    # large query batches per dispatch — the engine-side ceiling
    ix = ds.vector_indexes[("b", "b", "tbl", "ix")]
    kernel_qps = _index_engine_qps(ix, qs, 64 if quick else 128)

    # honest CPU comparator: HNSW-class greedy-graph search (numpy) on a
    # subsample — the reference's own comparator class (benches/index_hnsw.rs)
    bn = min(n, 20_000)
    hnsw = _HostHnsw(xs[:bn])
    t0 = time.perf_counter()
    for i in range(32):
        hnsw.search(qs[i % len(qs)], k=10, ef=80)
    base_qps = 32 / (time.perf_counter() - t0)
    return {
        "metric": f"sql_knn_qps_{n//1000}k_{dim}d_cosine",
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": round(qps / base_qps, 2),
        "recall_at_10": round(recall, 4),
        "cpu_hnsw_qps": round(base_qps, 2),
        "cpu_hnsw_n": bn,
        "index_engine_qps": round(kernel_qps, 2),
        "index_engine_vs_baseline": round(kernel_qps / base_qps, 2),
        "clients": 128,
    }


def _churn_ops(ds, ns, db, tb, ix_name, ver, adds, dels, live):
    """Commit one mixed insert/delete batch through the KV layer the
    way the write path does it (he state + hl op log + vn version), so
    the serving engine consumes it through its incremental log
    applier — the exact continuous-ingest shape under test."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    txn = ds.transaction(write=True)
    try:
        for i, v in adds:
            txn.set(K.record(ns, db, tb, i),
                    serialize({"id": RecordId(tb, i)}))
            txn.set_val(
                K.ix_state(ns, db, tb, ix_name, b"he", K.enc_value(i)),
                v.tobytes(),
            )
            ver += 1
            txn.set_val(
                K.ix_state(ns, db, tb, ix_name, b"hl", K.enc_u64(ver)),
                ("set", i, v.tobytes()),
            )
            live[i] = v
        for i in dels:
            txn.delete(K.record(ns, db, tb, i))
            txn.delete(
                K.ix_state(ns, db, tb, ix_name, b"he", K.enc_value(i))
            )
            ver += 1
            txn.set_val(
                K.ix_state(ns, db, tb, ix_name, b"hl", K.enc_u64(ver)),
                ("del", i, None),
            )
            live.pop(i, None)
        txn.set_val(K.ix_state(ns, db, tb, ix_name, b"vn"), ver)
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    return ver


def _churn_run(n0, dim, rounds, add, dele, nq, seed=15):
    """One sustained insert/delete/query churn run against a fresh
    datastore under the CURRENT cnf knobs. Returns per-round query
    latencies, ingest-to-searchable latencies (commit → the new row
    answering a query), and recall@10 checks vs the f64 brute oracle
    over the live rows."""
    from surrealdb_tpu import Datastore

    ds = Datastore("memory")
    try:
        rng = np.random.default_rng(seed)
        # embedding-shaped (clustered) data, like the ann smoke: real
        # vector workloads have low intrinsic dimension — unclustered
        # uniform gaussians are the known-pathological case for ANY
        # graph-ANN index (neighbors near-equidistant) and would bench
        # the data, not the index
        nc = max(n0 // 200, 64)
        centers = rng.normal(size=(nc, dim)).astype(np.float32)

        def mkvecs(count):
            return (centers[rng.integers(0, nc, count)]
                    + 0.15 * rng.normal(size=(count, dim))
                    ).astype(np.float32)

        ds.query(
            f"DEFINE TABLE tbl; DEFINE INDEX ix ON tbl FIELDS emb "
            f"HNSW DIMENSION {dim} DIST EUCLIDEAN TYPE F32",
            ns="b", db="b",
        )
        live: dict = {}
        ver = _churn_ops(ds, "b", "b", "tbl", "ix", 0,
                         list(enumerate(mkvecs(n0))), [], live)
        sql = "SELECT id FROM tbl WHERE emb <|10|> $q"

        def q_ids(qv, k=10):
            rows = ds.query_one(
                sql if k == 10
                else f"SELECT id FROM tbl WHERE emb <|{k}|> $q",
                ns="b", db="b", vars={"q": qv.tolist()},
            )
            return [r["id"].id for r in rows]

        q_ids(mkvecs(1)[0])  # engage/sync
        # both modes start from a BUILT index (the steady-state churn
        # comparison, not the cold-build race): segmented drains its
        # first seal, legacy lands its whole-store graph
        ds.vector_indexes[("b", "b", "tbl", "ix")].ensure_ann()
        nid = n0
        lat_ms, ingest_ms, recalls = [], [], []
        for r in range(rounds):
            adds = [(nid + j, v) for j, v in enumerate(mkvecs(add))]
            nid += add
            pool = np.asarray(sorted(live))
            dels = [int(i) for i in rng.choice(
                pool, size=min(dele, len(pool) - 1), replace=False
            )]
            ver = _churn_ops(ds, "b", "b", "tbl", "ix", ver, adds,
                             dels, live)
            probe_id, probe_vec = adds[-1]
            t0 = time.perf_counter()
            got = q_ids(probe_vec, 1)
            ingest_ms.append((time.perf_counter() - t0) * 1e3)
            assert got == [probe_id], (
                f"round {r}: committed row not searchable ({got})"
            )
            round_lat = []
            for qv in mkvecs(nq):
                t0 = time.perf_counter()
                q_ids(qv)
                round_lat.append((time.perf_counter() - t0) * 1e3)
            lat_ms.append(round_lat)
            if r % 4 == 3 or r == rounds - 1:
                ids = np.asarray(sorted(live))
                mat = np.stack([live[i] for i in ids]).astype(
                    np.float64
                )
                hits = tot = 0
                for qv in mkvecs(8):
                    d = ((mat - qv.astype(np.float64)) ** 2).sum(axis=1)
                    truth = set(
                        ids[np.argsort(d, kind="stable")[:10]].tolist()
                    )
                    hits += len(truth & set(q_ids(qv)))
                    tot += 10
                recalls.append(hits / tot)
        eng = ds.vector_indexes[("b", "b", "tbl", "ix")]
        seg_status = seg_stats = None
        if getattr(eng, "_segs", None) is not None \
                and eng._segs.active():
            eng._segs.drain()  # settle in-flight background builds
            st = eng._segs.status()
            seg_status = {k: st[k] for k in
                          ("segments", "ready", "tail_rows")}
            seg_stats = {k: v for k, v in st["stats"].items() if v}
        return {
            "lat_ms": lat_ms, "ingest_ms": ingest_ms,
            "recalls": recalls, "rows_end": len(live),
            "seg_status": seg_status, "seg_stats": seg_stats,
            "full_rebuilds": eng.ann_full_rebuilds,
        }
    finally:
        ds.close()


def _pct(vals, p):
    vals = sorted(vals)
    return vals[min(int(p * (len(vals) - 1)), len(vals) - 1)]


def bench_knn_churn(quick=False):
    """Sustained mixed insert/delete/query churn (ROADMAP item 3 gate):
    the segmented LSM-style index must hold recall@10 >= 0.95 with a
    FLAT query p99 across the run and bounded ingest-to-searchable
    latency, while the pre-PR single-graph path — run on the same
    churn at the same scale — pays the rebuild treadmill (counted via
    ann_full_rebuilds) and a growing brute-merged tail."""
    from surrealdb_tpu import cnf

    if quick:
        n0, dim, rounds, add, dele, nq = 90_000, 48, 12, 4096, 1024, 12
        seal = 16_384
    else:
        n0, dim, rounds, add, dele, nq = 1_000_000, 768, 8, 32_768, \
            8_192, 12
        seal = 131_072
    saved = (cnf.KNN_SEG_MODE, cnf.KNN_SEG_ROWS, cnf.KNN_ANN_MODE)
    try:
        # segmented run (counters read ENGINE-scoped from the run)
        cnf.KNN_SEG_MODE, cnf.KNN_SEG_ROWS = "force", seal
        cnf.KNN_ANN_MODE = "force"
        seg = _churn_run(n0, dim, rounds, add, dele, nq)
        # pre-PR contrast: the whole-store graph with the drift
        # threshold, same churn (quick scale keeps the bench bounded)
        cnf.KNN_SEG_MODE = "off"
        ln0, ldim = (n0, dim) if quick else (90_000, 48)
        lrounds = rounds if quick else 12
        legacy = _churn_run(ln0, ldim, lrounds,
                            add if quick else 4096,
                            dele if quick else 1024, nq)
        legacy_rebuilds = legacy["full_rebuilds"]
    finally:
        cnf.KNN_SEG_MODE, cnf.KNN_SEG_ROWS, cnf.KNN_ANN_MODE = saved

    def phase(lats, frac0, frac1):
        flat = [x for rl in lats[int(len(lats) * frac0):
                                 max(int(len(lats) * frac1), 1)]
                for x in rl]
        return flat or [0.0]

    first = phase(seg["lat_ms"], 0.0, 1 / 3)
    last = phase(seg["lat_ms"], 2 / 3, 1.0)
    lfirst = phase(legacy["lat_ms"], 0.0, 1 / 3)
    llast = phase(legacy["lat_ms"], 2 / 3, 1.0)
    all_lat = [x for rl in seg["lat_ms"] for x in rl]
    return {
        "metric": f"knn_churn_{n0 // 1000}k_{dim}d",
        "value": round(1000.0 / max(_pct(all_lat, 0.5), 1e-9), 2),
        "unit": "qps",
        "recall_at_10_min": round(min(seg["recalls"]), 4),
        "p50_ms": round(_pct(all_lat, 0.5), 2),
        "p99_ms": round(_pct(all_lat, 0.99), 2),
        "p99_ms_first_third": round(_pct(first, 0.99), 2),
        "p99_ms_last_third": round(_pct(last, 0.99), 2),
        "ingest_to_searchable_ms_p95": round(
            _pct(seg["ingest_ms"], 0.95), 2),
        "ingest_to_searchable_ms_max": round(max(seg["ingest_ms"]), 2),
        "rows_end": seg["rows_end"],
        "segments": seg["seg_status"],
        "seg_counters": seg["seg_stats"],
        "ann_full_rebuilds": seg["full_rebuilds"],
        "legacy_contrast": {
            "scale": f"{ln0 // 1000}k_{ldim}d",
            "ann_full_rebuilds": legacy_rebuilds,
            "recall_at_10_min": round(min(legacy["recalls"]), 4),
            "p99_ms_first_third": round(_pct(lfirst, 0.99), 2),
            "p99_ms_last_third": round(_pct(llast, 0.99), 2),
            "ingest_to_searchable_ms_p95": round(
                _pct(legacy["ingest_ms"], 0.95), 2),
        },
    }


def bench_knn10m(quick=False):
    """North-star config (BASELINE.md): 10M×768 cosine KNN, k=10, SQL
    search path, recall@10 vs exact f64 ground truth. At this scale the
    index auto-selects the int8 ranking store + exact host rescore
    (idx/vector.py: 6 B/elem for bf16+f32 ≈ 46 GB > HBM). Records live in
    KV (the SELECT projects them); the 30 GB vector block feeds the index
    store directly — the `he`-key ingest path is exercised by the other
    configs and would only double host RAM here."""
    from surrealdb_tpu import Datastore
    from surrealdb_tpu import key as K
    from surrealdb_tpu.idx.vector import TpuVectorIndex
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    n = 100_000 if quick else 10_000_000
    dim = 768
    ds = Datastore("memory")
    ds.query(
        f"DEFINE TABLE tbl; DEFINE INDEX ix ON tbl FIELDS emb HNSW "
        f"DIMENSION {dim} DIST COSINE TYPE F32",
        ns="b", db="b",
    )
    rng = np.random.default_rng(31)
    t0 = time.perf_counter()
    xs = np.empty((n, dim), np.float32)
    step = 1_000_000
    for s in range(0, n, step):
        e = min(s + step, n)
        xs[s:e] = rng.normal(size=(e - s, dim)).astype(np.float32)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    txn = ds.transaction(write=True)
    try:
        for i in range(n):
            txn.set(K.record("b", "b", "tbl", i),
                    serialize({"id": RecordId("tbl", i)}))
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    ingest_s = time.perf_counter() - t0

    # seed the index store (device upload happens on first search)
    ix = TpuVectorIndex("b", "b", "tbl", "ix",
                        {"dimension": dim, "distance": "cosine",
                         "vector_type": "f32"})
    ix.vecs = xs
    ix.valid = np.ones(n, dtype=bool)
    ix.rids = [RecordId("tbl", i) for i in range(n)]
    ix.version = 0
    ds.vector_indexes[("b", "b", "tbl", "ix")] = ix

    qs = rng.normal(size=(64, dim)).astype(np.float32)
    sql = "SELECT id FROM tbl WHERE emb <|10|> $q"
    t0 = time.perf_counter()
    _run_queries(ds, sql, qs, 2)  # device build + compile
    build_s = time.perf_counter() - t0
    # 128 concurrent clients: the cross-query batcher converts client
    # concurrency into device/BLAS batch size — the production shape
    _run_queries(ds, sql, qs, 128, threads=128)  # warm batched shapes
    qps = _run_queries(ds, sql, qs, 256 if quick else 1024, threads=128)

    # raw index-engine ceiling through the same routed entry the
    # serving path dispatches (acceptance: sql_knn >= index_engine)
    kernel_qps = _index_engine_qps(ix, qs, 8 if quick else 64)

    # recall vs exact ground truth: ONE pass over the store (chunk-outer,
    # all queries batched per chunk; norms computed once per chunk)
    nq = 4 if quick else 8
    qn_mat = (qs[:nq] / np.maximum(
        np.linalg.norm(qs[:nq], axis=1, keepdims=True), 1e-30
    )).astype(np.float32)  # [nq, D]
    best_d = np.full((nq, 10), np.inf)
    best_i = np.zeros((nq, 10), np.int64)
    for s in range(0, n, step):
        blk = xs[s:s + step]
        norms = np.maximum(np.linalg.norm(blk, axis=1), 1e-30)
        d = 1.0 - (blk @ qn_mat.T).T / norms[None, :]  # [nq, chunk]
        for qi in range(nq):
            idx = np.argpartition(d[qi], 10)[:10]
            cd = np.concatenate([best_d[qi], d[qi][idx]])
            ci = np.concatenate([best_i[qi], idx + s])
            keep = np.argpartition(cd, 10)[:10]
            best_d[qi], best_i[qi] = cd[keep], ci[keep]
    hits = 0
    for qi in range(nq):
        truth = set(best_i[qi].tolist())
        rows = ds.query_one(sql, ns="b", db="b",
                            vars={"q": qs[qi].tolist()})
        got = {r["id"].id for r in rows}
        hits += len(truth & got)
    recall = hits / (10 * nq)

    # CPU HNSW comparator (subsample — graph build cost bounds size)
    bn = min(n, 20_000)
    hnsw = _HostHnsw(xs[:bn])
    t0 = time.perf_counter()
    for i in range(32):
        hnsw.search(qs[i % len(qs)], k=10, ef=80)
    base_qps = 32 / (time.perf_counter() - t0)
    size = f"{n // 1_000_000}m" if n >= 1_000_000 else f"{n // 1000}k"
    return {
        "metric": f"sql_knn_qps_{size}_{dim}d_cosine",
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": round(qps / base_qps, 2),
        "recall_at_10": round(recall, 4),
        "cpu_hnsw_qps": round(base_qps, 2),
        "cpu_hnsw_n": bn,
        "index_engine_qps": round(kernel_qps, 2),
        "index_engine_vs_baseline": round(kernel_qps / base_qps, 2),
        "rank_mode": ix.rank_mode,
        "gen_s": round(gen_s, 1),
        "ingest_s": round(ingest_s, 1),
        "device_build_s": round(build_s, 1),
        "clients": 128,
    }


def _clustered_rows(n, dim, nc, std, seed, chunk=1_000_000):
    """Embedding-shaped data: `nc` gaussian clusters, generated in
    chunks (a 10M×768 block is 30 GB — the generator must not double
    it). Pure i.i.d. gaussian at high dim is adversarial for every
    graph-ANN (distance concentration) and resembles no real embedding
    distribution; the ANN configs bench on data with the low intrinsic
    dimension real embeddings have."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(nc, dim)).astype(np.float32)
    xs = np.empty((n, dim), np.float32)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        xs[s:e] = centers[rng.integers(0, nc, e - s)]
        xs[s:e] += std * rng.normal(size=(e - s, dim)).astype(np.float32)
    return xs, rng


def bench_ann10m(quick=False):
    """Quantized graph-ANN north-star (ROADMAP item 2): CAGRA-style
    fixed-degree graph + int8 rows + exact f32 re-rank, cosine, k=10.
    Full config is 10M×768 (int8 store ~7.4 GB + graph ~1.2 GB vs
    30 GB f32 — the config that doesn't fit HBM uncompressed); quick
    runs 250k×768 on CPU containers. Emits recall@10 vs exact ground
    truth, the graph build time, and the ann-vs-brute engine ratio the
    acceptance gate reads (≥10× at 1M-scale; measured 18× at 250k on
    one CPU core)."""
    from surrealdb_tpu import Datastore, cnf
    from surrealdb_tpu import key as K
    from surrealdb_tpu.idx.vector import TpuVectorIndex
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    reduced = quick or _PLATFORM == "cpu"
    n = 250_000 if reduced else 10_000_000
    dim = 768
    nc = max(n // 100, 100)
    ds = Datastore("memory")
    ds.query(
        f"DEFINE TABLE tbl; DEFINE INDEX ix ON tbl FIELDS emb HNSW "
        f"DIMENSION {dim} DIST COSINE TYPE F32",
        ns="b", db="b",
    )
    t0 = time.perf_counter()
    xs, rng = _clustered_rows(n, dim, nc, 0.15, 31)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    txn = ds.transaction(write=True)
    try:
        for i in range(n):
            txn.set(K.record("b", "b", "tbl", i),
                    serialize({"id": RecordId("tbl", i)}))
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    ingest_s = time.perf_counter() - t0

    ix = TpuVectorIndex("b", "b", "tbl", "ix",
                        {"dimension": dim, "distance": "cosine",
                         "vector_type": "f32"})
    ix.vecs = xs
    ix.valid = np.ones(n, dtype=bool)
    ix.rids = [RecordId("tbl", i) for i in range(n)]
    ix.version = 0
    ds.vector_indexes[("b", "b", "tbl", "ix")] = ix

    qi = rng.integers(0, n, 64)
    qs = xs[qi] + 0.075 * rng.normal(size=(64, dim)).astype(np.float32)

    # brute engine ceiling FIRST (the comparator the ratio gates on),
    # while no graph exists: the exact path the store served pre-ANN
    old_mode = cnf.KNN_ANN_MODE
    cnf.KNN_ANN_MODE = "off"
    try:
        brep = 4 if quick else 1
        brute_big = np.repeat(qs, brep, axis=0)
        ix.knn_batch(brute_big[:2], 10)  # warm: ship + compile
        t0 = time.perf_counter()
        ix.knn_batch(brute_big, 10)
        brute_qps = len(brute_big) / (time.perf_counter() - t0)
    finally:
        cnf.KNN_ANN_MODE = old_mode

    # graph build (auto mode crosses KNN_ANN_MIN_ROWS at both sizes;
    # ensure_ann makes it synchronous so build_s is honest)
    t0 = time.perf_counter()
    assert ix.ensure_ann(), "ann build did not land"
    ann_build_s = time.perf_counter() - t0

    sql = "SELECT id FROM tbl WHERE emb <|10|> $q"
    _run_queries(ds, sql, qs, 3)  # warm: sync + ship + compile
    _run_queries(ds, sql, qs, 128, threads=128)
    qps = _run_queries(ds, sql, qs, 512 if quick else 1024, threads=128)

    kernel_qps = _index_engine_qps(ix, qs, 8 if quick else 16)

    # recall vs exact ground truth: one chunked pass over the store
    nq = 16 if quick else 8
    qn_mat = (qs[:nq] / np.maximum(
        np.linalg.norm(qs[:nq], axis=1, keepdims=True), 1e-30
    )).astype(np.float32)
    step = 1_000_000
    best_d = np.full((nq, 10), np.inf)
    best_i = np.zeros((nq, 10), np.int64)
    for s in range(0, n, step):
        blk = xs[s:s + step]
        norms = np.maximum(np.linalg.norm(blk, axis=1), 1e-30)
        d = 1.0 - (blk @ qn_mat.T).T / norms[None, :]
        for q_ix in range(nq):
            idx = np.argpartition(d[q_ix], 10)[:10]
            cd = np.concatenate([best_d[q_ix], d[q_ix][idx]])
            ci = np.concatenate([best_i[q_ix], idx + s])
            keep = np.argpartition(cd, 10)[:10]
            best_d[q_ix], best_i[q_ix] = cd[keep], ci[keep]
    hits = 0
    for q_ix in range(nq):
        truth = set(best_i[q_ix].tolist())
        rows = ds.query_one(sql, ns="b", db="b",
                            vars={"q": qs[q_ix].tolist()})
        got = {r["id"].id for r in rows}
        hits += len(truth & got)
    recall = hits / (10 * nq)

    ann = ix._ann
    size = f"{n // 1_000_000}m" if n >= 1_000_000 else f"{n // 1000}k"
    res = {
        "metric": f"sql_knn_ann_qps_{size}_{dim}d_cosine",
        "value": round(qps, 2),
        "unit": "qps",
        "recall_at_10": round(recall, 4),
        "index_engine_qps": round(kernel_qps, 2),
        "brute_engine_qps": round(brute_qps, 2),
        "ann_vs_brute": round(kernel_qps / max(brute_qps, 1e-9), 2),
        "ann_build_s": round(ann_build_s, 1),
        "ann_bytes": ann.nbytes(),
        "f32_bytes": int(xs.nbytes),
        "ann_degree": ann.d_out,
        "gen_s": round(gen_s, 1),
        "ingest_s": round(ingest_s, 1),
        "clients": 128,
    }
    if reduced and not quick:
        # a 10M one-core CPU build is an hours-long workload: an
        # explicit CPU run takes the reduced config and says so
        res["reduced"] = "cpu platform: 250k rows, not 10M"
    return res


def _brute_ceiling_ratio(n, dim, seed=29, iters=24):
    """(sql_qps, ceiling_qps) at a scale of the caller's choosing: the
    SAME cosine scoring + top-k over the column-store matrix with
    precomputed row norms (the SQL path caches them per version, so
    the raw comparator gets them precomputed too)."""
    from surrealdb_tpu import Datastore
    from surrealdb_tpu.col import get_vector_column
    from surrealdb_tpu.exec.context import Ctx
    from surrealdb_tpu.kvs.ds import Session

    ds = Datastore("memory")
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    ds.query("DEFINE TABLE tbl", ns="b", db="b")
    _bulk_vectors(ds, "b", "b", "tbl", "__noix", xs, dim, inline_emb=True)
    q = rng.normal(size=(dim,)).astype(np.float32)
    sql = ("SELECT id, vector::similarity::cosine(emb, $q) AS s FROM tbl "
           "ORDER BY s DESC LIMIT 10")
    for _ in range(2):
        ds.query_one(sql, ns="b", db="b", vars={"q": q.tolist()})
    t0 = time.perf_counter()
    for _ in range(iters):
        ds.query_one(sql, ns="b", db="b", vars={"q": q.tolist()})
    sql_qps = iters / (time.perf_counter() - t0)
    txn = ds.transaction(write=False)
    try:
        col = get_vector_column(
            Ctx(ds, Session(ns="b", db="b", auth_level="owner"), txn),
            "tbl", "emb", dim,
        )
    finally:
        txn.cancel()
    m = col.mat
    row_norms = np.linalg.norm(m, axis=1)

    def _once():
        dots = m @ q
        scores = dots / (row_norms * np.linalg.norm(q))
        part = np.argpartition(-scores, 9)[:10]
        return part[np.argsort(-scores[part], kind="stable")]

    _once()
    t0 = time.perf_counter()
    for _ in range(iters * 2):
        _once()
    return sql_qps, (iters * 2) / (time.perf_counter() - t0)


def bench_brute(quick=False):
    from surrealdb_tpu import Datastore

    n = 5_000 if quick else 20_000
    dim = 128
    ds = Datastore("memory")
    rng = np.random.default_rng(17)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    ds.query("DEFINE TABLE tbl", ns="b", db="b")
    _bulk_vectors(ds, "b", "b", "tbl", "__noix", xs, dim, inline_emb=True)
    q = rng.normal(size=(dim,)).astype(np.float32)
    sql = ("SELECT id, vector::similarity::cosine(emb, $q) AS s FROM tbl "
           "ORDER BY s DESC LIMIT 10")
    iters = 3
    ds.query_one(sql, ns="b", db="b", vars={"q": q.tolist()})  # warm caches
    t0 = time.perf_counter()
    for _ in range(iters):
        rows = ds.query_one(sql, ns="b", db="b", vars={"q": q.tolist()})
        assert len(rows) == 10
    qps = iters / (time.perf_counter() - t0)
    # raw engine ceiling: the SAME scoring math (cosine + top-k) over
    # the column-store matrix, no SQL stack — acceptance wants the SQL
    # path within 2x of this
    from surrealdb_tpu.col import get_vector_column
    from surrealdb_tpu.exec.context import Ctx
    from surrealdb_tpu.kvs.ds import Session

    sess0 = Session(ns="b", db="b", auth_level="owner")
    txn0 = ds.transaction(write=False)
    try:
        col = get_vector_column(Ctx(ds, sess0, txn0), "tbl", "emb", dim)
    finally:
        txn0.cancel()
    m = col.mat
    # honest ceiling: the SQL path caches per-version row norms
    # (col.norms()), so the raw comparator gets them precomputed too
    row_norms = np.linalg.norm(m, axis=1)

    def _ceiling_once():
        dots = m @ q
        scores = dots / (row_norms * np.linalg.norm(q))
        part = np.argpartition(-scores, 9)[:10]
        return part[np.argsort(-scores[part], kind="stable")]

    _ceiling_once()
    t0 = time.perf_counter()
    for _ in range(iters * 3):
        _ceiling_once()
    engine_qps = (iters * 3) / (time.perf_counter() - t0)
    # baseline: the row-at-a-time legacy engine on the same query (the
    # streaming batched executor is the thing under test here)
    sess = Session(ns="b", db="b", auth_level="owner")
    sess.planner_strategy = "compute-only"
    t0 = time.perf_counter()
    for _ in range(iters):
        res = ds.execute(sql, session=sess, vars={"q": q.tolist()})
        assert len(res[-1].unwrap()) == 10
    legacy_qps = iters / (time.perf_counter() - t0)
    out = {
        "metric": f"sql_brute_scan_qps_{n//1000}k_{dim}d",
        "value": round(qps, 3),
        "unit": "qps",
        "vs_baseline": round(qps / legacy_qps, 2),
        "legacy_engine_qps": round(legacy_qps, 3),
        "engine_ceiling_qps": round(engine_qps, 3),
        # honesty note: at this small N the scoring kernel is ~0.7ms
        # while a full SQL roundtrip (parse-cache hit, txn, plan,
        # winner fetch, projection, envelope) carries ~2ms of fixed
        # cost — the ratio here is overhead physics, not kernel tax.
        # The ceiling-tracking acceptance number is the 100k config
        # below, where the engine does real work per query.
        "vs_engine_ceiling": round(qps / engine_qps, 3),
    }
    if not quick:
        s100, c100 = _brute_ceiling_ratio(100_000, dim)
        out["sql_qps_100k"] = round(s100, 3)
        out["engine_ceiling_qps_100k"] = round(c100, 3)
        out["vs_engine_ceiling_100k"] = round(s100 / c100, 3)
    return out


def _bulk_analytics_rows(ds, ns, db, tb, n, seed=23):
    """Fast ingest of analytics-shaped rows (scalar columns) through the
    KV layer — the SQL INSERT path is not the thing under test."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    rng = np.random.default_rng(seed)
    cats = rng.integers(0, 24, size=n)
    prices = np.round(rng.uniform(0.0, 1000.0, size=n), 2)
    qty = rng.integers(1, 50, size=n)
    regions = np.array(["eu", "us", "apac", "latam"])[
        rng.integers(0, 4, size=n)
    ]
    txn = ds.transaction(write=True)
    try:
        for i in range(n):
            doc = {
                "id": RecordId(tb, i),
                "cat": int(cats[i]),
                "price": float(prices[i]),
                "qty": int(qty[i]),
                "region": str(regions[i]),
            }
            txn.set(K.record(ns, db, tb, i), serialize(doc))
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    return n


def bench_analytics(quick=False):
    """ROADMAP item 1 gate: filtered aggregation + GROUP BY over ≥1M
    rows through the columnar push executor vs the row-at-a-time
    interpreter (planner_strategy=compute-only + SURREAL_COLUMNAR=off).
    The interpreter baseline is measured on a row subsample and scaled
    (it is minutes-per-query at 1M), the columnar number is measured
    directly."""
    from surrealdb_tpu import Datastore, cnf
    from surrealdb_tpu.kvs.ds import Session
    from surrealdb_tpu.val import render

    n = 60_000 if quick else 1_000_000
    ds = Datastore("memory")
    ds.query("DEFINE TABLE sales", ns="b", db="b")
    t0 = time.perf_counter()
    _bulk_analytics_rows(ds, "b", "b", "sales", n)
    ingest_s = time.perf_counter() - t0
    queries = [
        ("filtered_agg",
         "SELECT cat, count() AS orders, math::sum(qty) AS units, "
         "math::mean(price) AS avg_price FROM sales "
         "WHERE price < 250 AND qty > 10 GROUP BY cat"),
        ("group_by",
         "SELECT region, count() AS c, math::sum(price) AS rev "
         "FROM sales GROUP BY region"),
        ("topk_order",
         "SELECT cat, math::max(price) AS mx FROM sales GROUP BY cat "
         "ORDER BY mx DESC LIMIT 5"),
    ]

    def run_columnar(sql, iters):
        ds.query_one(sql, ns="b", db="b")  # warm: column-store build
        t0 = time.perf_counter()
        for _ in range(iters):
            out = ds.query_one(sql, ns="b", db="b")
        return iters / (time.perf_counter() - t0), out

    def run_interp(sql, iters):
        sess = Session(ns="b", db="b", auth_level="owner")
        sess.planner_strategy = "compute-only"
        prev, cnf.COLUMNAR = cnf.COLUMNAR, "off"
        try:
            t0 = time.perf_counter()
            for _ in range(iters):
                out = ds.execute(sql, session=sess)[-1].unwrap()
            return iters / (time.perf_counter() - t0), out
        finally:
            cnf.COLUMNAR = prev

    per_query = {}
    ratios = []
    for name, sql in queries:
        col_qps, col_out = run_columnar(sql, 8 if quick else 5)
        # interpreter: full run on quick; one full run at 1M would be
        # minutes — measure one iteration (it IS the slow side)
        interp_qps, interp_out = run_interp(sql, 2 if quick else 1)
        identical = render(col_out) == render(interp_out)
        ratio = col_qps / max(interp_qps, 1e-9)
        ratios.append(ratio)
        per_query[name] = {
            "columnar_qps": round(col_qps, 3),
            "interpreter_qps": round(interp_qps, 4),
            "speedup": round(ratio, 1),
            "identical": identical,
        }
    from surrealdb_tpu.exec.batch import counters

    COUNTERS = counters(ds)
    worst = min(ratios)
    return {
        "metric": f"sql_analytics_speedup_{n // 1000}k",
        "value": round(worst, 1),  # WORST-case speedup is the gate
        "unit": "x_vs_interpreter",
        "rows": n,
        "ingest_s": round(ingest_s, 1),
        "queries": per_query,
        "columnar_counters": {
            k: COUNTERS[k] for k in (
                "colstore_builds", "colstore_hits", "agg_columnar",
                "agg_streamed", "rows_fallback",
            )
        },
        "all_identical": all(
            q["identical"] for q in per_query.values()
        ),
    }


def bench_graph3hop(quick=False):
    from surrealdb_tpu import Datastore
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    # BASELINE config 4: 1M nodes / 10M edges (quick: 1/50 scale)
    n_nodes = 20_000 if quick else 1_000_000
    n_edges = 200_000 if quick else 10_000_000
    ds = Datastore("memory")
    ds.query("DEFINE TABLE person; DEFINE TABLE knows TYPE RELATION",
             ns="b", db="b")
    rng = np.random.default_rng(19)
    src = rng.integers(0, n_nodes, size=n_edges)
    dst = rng.integers(0, n_nodes, size=n_edges)
    txn = ds.transaction(write=True)
    try:
        for i in range(n_nodes):
            txn.set(K.record("b", "b", "person", i),
                    serialize({"id": RecordId("person", i)}))
        for e in range(n_edges):
            s, d = int(src[e]), int(dst[e])
            erid = RecordId("knows", e)
            txn.set(K.record("b", "b", "knows", e), serialize({
                "id": erid, "in": RecordId("person", s),
                "out": RecordId("person", d),
            }))
            # the four graph keys, like doc/edges writes them
            txn.set(K.graph("b", "b", "person", s, K.DIR_OUT, "knows", e),
                    b"")
            txn.set(K.graph("b", "b", "knows", e, K.DIR_IN, "person", s),
                    b"")
            txn.set(K.graph("b", "b", "knows", e, K.DIR_OUT, "person", d),
                    b"")
            txn.set(K.graph("b", "b", "person", d, K.DIR_IN, "knows", e),
                    b"")
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    sql = "SELECT VALUE ->knows->person->knows->person->knows->person FROM person:0"
    t0 = time.perf_counter()
    out = ds.query_one(sql, ns="b", db="b")
    first_ms = (time.perf_counter() - t0) * 1000
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        out = ds.query_one(sql, ns="b", db="b")
    ms = (time.perf_counter() - t0) / iters * 1000

    # honest CPU comparator: scipy-free numpy CSR adjacency + 3 sparse
    # frontier expansions — the classic single-host way to run this
    # traversal (the reference walks per-record KV range scans; a numpy
    # CSR is the STRONGER baseline to beat)
    order = np.argsort(src, kind="stable")
    ss, dd = src[order], dst[order]
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.add.at(indptr, ss + 1, 1)
    indptr = np.cumsum(indptr)

    def csr_3hop(start: int):
        frontier = np.array([start], dtype=np.int64)
        for _hop in range(3):
            if not len(frontier):
                break
            parts = [
                dd[indptr[v]:indptr[v + 1]] for v in frontier
            ]
            frontier = np.concatenate(parts) if parts else frontier[:0]
        return frontier

    csr_3hop(0)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        ref = csr_3hop(0)
    base_ms = (time.perf_counter() - t0) / iters * 1000
    reached = (
        len(out[0]) if isinstance(out, list) and out
        and isinstance(out[0], list) else
        (len(out) if isinstance(out, list) else 1)
    )
    size = (f"{n_nodes // 1_000_000}m" if n_nodes >= 1_000_000
            else f"{n_nodes // 1000}k")
    esize = (f"{n_edges // 1_000_000}m" if n_edges >= 1_000_000
             else f"{n_edges // 1000}k")
    return {
        "metric": f"sql_graph_3hop_ms_{size}_nodes_{esize}_edges",
        "value": round(ms, 2),
        "unit": "ms",
        # ratio > 1 means the SQL path beats the numpy CSR walk
        "vs_baseline": round(base_ms / ms, 3) if ms else 0.0,
        "cpu_csr_ms": round(base_ms, 2),
        "first_ms": round(first_ms, 2),
        "reached": reached,
        "csr_reached": int(len(ref)),
    }


def bench_hybrid(quick=False):
    from surrealdb_tpu import Datastore

    n = 500 if quick else 5_000
    dim = 64
    ds = Datastore("memory")
    ds.query(
        "DEFINE ANALYZER simple TOKENIZERS class FILTERS lowercase;"
        "DEFINE INDEX ft ON doc FIELDS text FULLTEXT ANALYZER simple BM25;"
        f"DEFINE INDEX hx ON doc FIELDS emb HNSW DIMENSION {dim} DIST COSINE TYPE F32",
        ns="b", db="b",
    )
    rng = np.random.default_rng(23)
    words = ["graph", "vector", "index", "query", "search", "database",
             "tensor", "shard", "batch", "kernel"]
    texts = []
    embs = np.empty((n, dim), np.float32)
    for i in range(n):
        text = " ".join(rng.choice(words, size=8))
        texts.append(text)
        emb = rng.normal(size=dim).astype(np.float32)
        embs[i] = emb
        ds.query(
            "CREATE doc CONTENT { text: $t, emb: $e }",
            ns="b", db="b", vars={"t": text, "e": emb.tolist()},
        )
    q = rng.normal(size=dim).astype(np.float32).tolist()
    sql = (
        "LET $vs = SELECT id, vector::distance::knn() AS distance FROM doc "
        "WHERE emb <|10,40|> $q;"
        "LET $ft = SELECT id, search::score(1) AS ft_score FROM doc "
        "WHERE text @1@ 'graph' ORDER BY ft_score DESC LIMIT 10;"
        "RETURN search::rrf([$vs, $ft], 10, 60);"
    )
    ds.execute(sql, ns="b", db="b", vars={"q": q})  # warm
    iters = 8
    t0 = time.perf_counter()
    for _ in range(iters):
        res = ds.execute(sql, ns="b", db="b", vars={"q": q})
        fused = res[-1].unwrap()
        assert fused
    qps = iters / (time.perf_counter() - t0)

    # CPU comparator: the same hybrid retrieval as one numpy program —
    # BM25 over a term-doc matrix + exact cosine top-10 + RRF fusion
    qv = np.asarray(q, np.float32)
    qn = qv / max(np.linalg.norm(qv), 1e-30)
    en = embs / np.maximum(
        np.linalg.norm(embs, axis=1, keepdims=True), 1e-30
    )
    vocab = {w: j for j, w in enumerate(words)}
    tf = np.zeros((n, len(words)), np.float32)
    for i, t in enumerate(texts):
        for w in t.split():
            tf[i, vocab[w]] += 1
    dl = tf.sum(axis=1)
    avgdl = dl.mean()
    dfreq = (tf > 0).sum(axis=0)
    idf = np.log(1 + (n - dfreq + 0.5) / (dfreq + 0.5))
    k1, b_ = 1.2, 0.75

    def host_hybrid():
        j = vocab["graph"]
        bm = idf[j] * tf[:, j] * (k1 + 1) / (
            tf[:, j] + k1 * (1 - b_ + b_ * dl / avgdl)
        )
        ft_top = np.argsort(-bm, kind="stable")[:10]
        d = 1.0 - en @ qn
        vs_top = np.argsort(d, kind="stable")[:10]
        scores: dict = {}
        for rank, i in enumerate(vs_top):
            scores[i] = scores.get(i, 0.0) + 1.0 / (60 + rank + 1)
        for rank, i in enumerate(ft_top):
            scores[i] = scores.get(i, 0.0) + 1.0 / (60 + rank + 1)
        return sorted(scores, key=scores.get, reverse=True)[:10]

    host_hybrid()  # warm
    base_iters = 200  # sub-ms fn: enough samples to beat timer jitter
    t0 = time.perf_counter()
    for _ in range(base_iters):
        host_hybrid()
    base_qps = base_iters / (time.perf_counter() - t0)
    return {
        "metric": f"sql_hybrid_rrf_qps_{n}docs",
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": round(qps / base_qps, 3) if base_qps else 0.0,
        "cpu_hybrid_qps": round(base_qps, 2),
    }


# ---------------------------------------------------------------------------
# live-query fan-out soak (real sockets; the push-traffic load story)
# ---------------------------------------------------------------------------


class _SoakWs:
    """Minimal RFC6455 json client for the soak: blocking handshake +
    rpc calls; notification collection happens externally through a
    shared selector loop reading `sock` via `feed()`."""

    def __init__(self, port, rcvbuf=None):
        import socket as S

        self.sock = S.socket(S.AF_INET, S.SOCK_STREAM)
        if rcvbuf:
            self.sock.setsockopt(S.SOL_SOCKET, S.SO_RCVBUF, rcvbuf)
        self.sock.settimeout(30)
        self.sock.connect(("127.0.0.1", port))
        key = "c29ha3Nlc3Npb25rZXk93d=="
        self.sock.sendall(
            (f"GET /rpc HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\n"
             f"Sec-WebSocket-Version: 13\r\n\r\n").encode())
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = self.sock.recv(4096)
            if not chunk:
                raise ConnectionError("handshake failed")
            resp += chunk
        self.buf = bytearray(resp.split(b"\r\n\r\n", 1)[1])
        self._id = 0

    def call(self, method, params):
        self._id += 1
        payload = json.dumps({"id": self._id, "method": method,
                              "params": params}).encode()
        mask = b"\x11\x22\x33\x44"
        masked = bytes(c ^ mask[i % 4] for i, c in enumerate(payload))
        n = len(payload)
        if n < 126:
            hdr = b"\x81" + bytes([0x80 | n])
        else:
            import struct as st

            hdr = b"\x81" + st.pack("!BH", 0x80 | 126, n)
        self.sock.sendall(hdr + mask + masked)
        while True:
            msg = self._read_msg()
            if msg.get("id") == self._id:
                return msg

    def _read_msg(self):
        while True:
            msgs = _soak_parse(self.buf)
            if msgs:
                if msgs[0] is None:  # server close frame
                    raise ConnectionError("closed by server")
                return msgs[0]
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("closed")
            self.buf += chunk

    def feed(self) -> list:
        """Non-blocking drain for the collector: recv once, return the
        complete messages parsed out of the buffer."""
        try:
            chunk = self.sock.recv(262144)
        except (BlockingIOError, InterruptedError):
            return []
        except OSError:
            return [None]  # connection gone
        if not chunk:
            return [None]
        self.buf += chunk
        return _soak_parse(self.buf, limit=0)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


def _soak_parse(buf: bytearray, limit: int = 1) -> list:
    """Parse complete server frames out of `buf` in place; returns
    decoded json messages (close frames decode to None)."""
    import struct as st

    out = []
    while buf and (limit == 0 or len(out) < limit):
        if len(buf) < 2:
            break
        b1, b2 = buf[0], buf[1]
        n = b2 & 0x7F
        off = 2
        if n == 126:
            if len(buf) < 4:
                break
            n = st.unpack_from("!H", buf, 2)[0]
            off = 4
        elif n == 127:
            if len(buf) < 10:
                break
            n = st.unpack_from("!Q", buf, 2)[0]
            off = 10
        if len(buf) < off + n:
            break
        data = bytes(buf[off:off + n])
        del buf[:off + n]
        opcode = b1 & 0x0F
        if opcode == 0x8:
            out.append(None)
            break
        if opcode not in (0x1, 0x2):
            continue
        try:
            out.append(json.loads(data.decode()))
        except ValueError:
            continue
    return out


def live_soak(sessions=64, frozen=2, writers=4, writes=400,
              depth=None, policy=None, reconnects=0, payload_pad=0,
              table="soak", settle_s=8.0):
    """The live-fanout soak: `sessions` real WebSocket sessions each
    holding one LIVE SELECT on a shared table, `writers` threads
    streaming CREATEs through the datastore, `frozen` sessions that
    never read their socket (tiny SO_RCVBUF so TCP backpressure bites),
    and an optional mid-stream reconnect storm. One collector thread
    drains every live socket through a selector (scales to thousands
    of sessions without a thread per client).

    Returns the metrics dict the `live_fanout` BENCH family and the
    conformance-gate smoke both consume."""
    import selectors
    import threading

    from surrealdb_tpu import Datastore, cnf
    from surrealdb_tpu.server import make_server

    old_depth, old_policy = cnf.LIVE_QUEUE_DEPTH, cnf.LIVE_OVERFLOW_POLICY
    if depth is not None:
        cnf.LIVE_QUEUE_DEPTH = depth
    if policy is not None:
        cnf.LIVE_OVERFLOW_POLICY = policy
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True,
                      max_inflight=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    pad = "x" * payload_pad if payload_pad else ""
    res: dict = {}
    try:
        ds.execute(f"DEFINE TABLE {table}", ns="s", db="s")

        # -- baseline write qps: zero subscribers ------------------------
        # per-phase base keeps `s` globally unique AND monotonic per
        # (phase, writer) stream: the order detector keys on
        # s // 1_000_000, so a later phase restarting at j=0 must not
        # compare against an earlier phase's high-water mark
        phase = [0]

        def run_writes(tag, count):
            phase[0] += 1
            base = phase[0] * 100_000_000
            done = []

            def w(wi):
                for j in range(count // writers):
                    ds.execute(
                        f"CREATE {table}:{tag}{wi}x{j} SET ts = $ts, "
                        f"s = $s, p = $p",
                        ns="s", db="s",
                        vars={"ts": time.time(),
                              "s": base + wi * 1_000_000 + j, "p": pad},
                    )
                done.append(wi)

            ts = [threading.Thread(target=w, args=(i,), daemon=True)
                  for i in range(writers)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            return (count // writers) * writers / dt

        base_qps = run_writes("b", writes)

        # -- subscribe the fleet ----------------------------------------
        live, cold = [], []
        for i in range(sessions):
            is_frozen = i < frozen
            c = _SoakWs(port, rcvbuf=4096 if is_frozen else None)
            c.call("use", ["s", "s"])
            out = c.call("live", [table])
            c.lid = out.get("result")
            c.si = i
            (cold if is_frozen else live).append(c)
        stats = {"delivered": 0, "overflow": 0, "error": 0,
                 "order_violations": 0, "lat": [], "closed": 0,
                 "per_session": {}}
        stop = threading.Event()

        def collect():
            sel = selectors.DefaultSelector()
            for c in live:
                c.sock.setblocking(False)
                sel.register(c.sock, selectors.EVENT_READ, c)
            last_seq: dict = {}
            while not stop.is_set():
                for key, _ev in sel.select(timeout=0.2):
                    c = key.data
                    for msg in c.feed():
                        if msg is None:
                            try:
                                sel.unregister(c.sock)
                            except KeyError:
                                pass
                            stats["closed"] += 1
                            break
                        if msg.get("id") is not None:
                            continue
                        note = msg.get("result") or {}
                        act = note.get("action")
                        if act == "OVERFLOW":
                            stats["overflow"] += 1
                            continue
                        if act == "ERROR":
                            stats["error"] += 1
                            continue
                        row = note.get("result") or {}
                        ts = row.get("ts")
                        if isinstance(ts, (int, float)):
                            stats["lat"].append(time.time() - ts)
                        s = row.get("s")
                        prev = last_seq.get((c.si, s is not None
                                             and s // 1_000_000))
                        if prev is not None and s is not None \
                                and s <= prev:
                            stats["order_violations"] += 1
                        if s is not None:
                            last_seq[(c.si, s // 1_000_000)] = s
                        stats["delivered"] += 1
                        ps = stats["per_session"]
                        ps[c.si] = ps.get(c.si, 0) + 1

        col = threading.Thread(target=collect, daemon=True)
        col.start()

        # -- fan-out run: writes streaming into the subscribed fleet ----
        t0 = time.perf_counter()
        fan_qps = run_writes("f", writes)
        if reconnects:
            # reconnect storm mid-stream: drop + resubscribe
            storm = live[:reconnects]
            for c in storm:
                c.close()
            run_writes("g", max(writes // 2, writers))
            for c in storm:
                nc = _SoakWs(port)
                nc.call("use", ["s", "s"])
                nc.call("live", [table])
                nc.close()
        # let deliveries settle, then stop collecting
        target = len(live) * (writes // writers) * writers
        end = time.monotonic() + settle_s
        while time.monotonic() < end \
                and stats["delivered"] < target:
            time.sleep(0.05)
        wall = time.perf_counter() - t0
        stop.set()
        col.join(timeout=5)

        lats = sorted(stats["lat"])

        def pct(p):
            return round(
                lats[min(int(len(lats) * p), len(lats) - 1)] * 1000, 2
            ) if lats else None

        # disconnect-GC at scale: closing every session without KILL
        # must empty the subscription registry (the leak satellite)
        for c in live + cold:
            c.close()
        gc_end = time.monotonic() + 10.0
        while len(ds.live_queries) and time.monotonic() < gc_end:
            time.sleep(0.05)
        tel = ds.telemetry
        res = {
            "config": "live_fanout",
            "metric": f"live_fanout_qps_{sessions}sessions",
            "value": round(stats["delivered"] / wall, 1),
            "unit": "notifications/s",
            "sessions": sessions,
            "frozen": frozen,
            "writes": (writes // writers) * writers,
            "delivered": stats["delivered"],
            "delivery_p50_ms": pct(0.50),
            "delivery_p99_ms": pct(0.99),
            "write_qps_base": round(base_qps, 1),
            "write_qps_fanout": round(fan_qps, 1),
            "decoupling_ratio": round(fan_qps / base_qps, 3)
            if base_qps else 0.0,
            "order_violations": stats["order_violations"],
            "overflow_notes": stats["overflow"],
            "overflows": tel.get("live_overflows"),
            "overflow_disconnects": tel.get("live_overflow_disconnects"),
            "notifications_dropped": tel.get("notifications_dropped"),
            "live_sessions_end": len(ds.live_queries),
            "per_session_complete": sum(
                1 for v in stats["per_session"].values()
                if v >= (writes // writers) * writers
            ),
            "reconnects": reconnects,
        }
    finally:
        cnf.LIVE_QUEUE_DEPTH, cnf.LIVE_OVERFLOW_POLICY = \
            old_depth, old_policy
        srv.shutdown()
        ds.close()
    return res


def bench_live_fanout(quick=False):
    """BENCH family `live_fanout`: fan-out qps + delivery p50/p99 +
    overflow/shed counts at production shape — thousands of WS sessions
    full-size, with frozen consumers and a reconnect storm."""
    if quick:
        return live_soak(sessions=64, frozen=2, writers=4, writes=400,
                         payload_pad=256)
    sessions = int(os.environ.get("SURREAL_BENCH_LIVE_SESSIONS", "1000"))
    return live_soak(sessions=sessions, frozen=max(sessions // 50, 2),
                     writers=8,
                     writes=max(240, 200_000 // max(sessions, 1)),
                     payload_pad=256,
                     reconnects=max(sessions // 10, 4), settle_s=20.0)


def _spawn_kv_proc(port, role, peers, data_dir,
                   failover_timeout=1.0, lease_ttl=0.8):
    """One replica-set member as a real subprocess — SIGKILL mid-run is
    a genuine hard death, not a simulated one."""
    import socket as _socket
    import subprocess

    p = subprocess.Popen(
        [sys.executable, "-m", "surrealdb_tpu", "kv",
         "--bind", f"127.0.0.1:{port}", "--role", role,
         "--peers", ",".join(peers),
         "--failover-timeout", str(failover_timeout),
         "--lease-ttl", str(lease_ttl),
         "--data-dir", data_dir, "--no-fsync"],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "SURREAL_DEVICE": "off"},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    for _ in range(150):
        try:
            _socket.create_connection(("127.0.0.1", port),
                                      timeout=0.2).close()
            return p
        except OSError:
            time.sleep(0.1)
    p.kill()
    raise RuntimeError(f"kv {role} on :{port} did not come up")


def _free_port():
    import socket as _socket

    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _bulk_vectors_sharded(ds, ns, db, tb, ix_name, xs, chunk=512):
    """Chunked ingest through the ROUTING client (records + index
    state + version bumps); chunks keep per-commit writesets sane on a
    sharded store (cross-shard chunks run real 2PC)."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    n = xs.shape[0]
    for s in range(0, n, chunk):
        txn = ds.transaction(write=True)
        try:
            for i in range(s, min(s + chunk, n)):
                txn.set(K.record(ns, db, tb, i),
                        serialize({"id": RecordId(tb, i)}))
                txn.set_val(
                    K.ix_state(ns, db, tb, ix_name, b"he",
                               K.enc_value(i)),
                    xs[i].tobytes(),
                )
            txn.set_val(K.ix_state(ns, db, tb, ix_name, b"vn"),
                        min(s + chunk, n))
            txn.commit()
        except BaseException:
            txn.cancel()
            raise


def bench_mem_pressure(quick=False):
    """BENCH family `mem_pressure`: the churn workload
    (tools/mem_churn.py — vector writes/deletes, KNN + FT queries,
    background CAGRA builds, a live subscription) run twice in fresh
    subprocesses: unconstrained, then under SURREAL_MEM_BUDGET_MB
    clamped to ~half the unconstrained accounted peak. Emits both
    runs' qps/RSS/eviction counters plus `answers_identical` — the
    trajectory catches two regressions at once: unbounded growth
    (accounted/peak RSS trend) and pressure-induced wrongness
    (answers_identical must stay true with evictions > 0)."""
    import subprocess

    rows, ops = (6000, 220) if quick else (12000, 400)

    def run(budget_mb):
        env = dict(os.environ)
        env.update({
            "SURREAL_DEVICE": "off",
            "SURREAL_KNN_ANN": "force",
            # builds run (and evict) but serving stays exact, so the
            # answers digest is deterministic by construction
            "SURREAL_KNN_ANN_MAX_K": "0",
        })
        env.pop("SURREAL_MEM_BUDGET_MB", None)
        if budget_mb:
            env["SURREAL_MEM_BUDGET_MB"] = str(budget_mb)
        p = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "mem_churn.py"),
             "--rows", str(rows), "--ops", str(ops)],
            capture_output=True, text=True, timeout=3600, env=env,
        )
        if p.returncode != 0:
            raise RuntimeError(
                f"mem churn died (budget={budget_mb}MB): "
                f"{p.stderr[-400:]}"
            )
        return json.loads(p.stdout.strip().splitlines()[-1])

    base = run(0)
    budget = max(1, int(base["accounted_peak_mb"] / 2))
    press = run(budget)
    return {
        "config": "mem_pressure",
        "rows": rows,
        "ops": ops,
        "budget_mb": budget,
        "qps_unpressured": base["qps"],
        "qps_pressured": press["qps"],
        "peak_rss_mb_unpressured": base["peak_rss_mb"],
        "peak_rss_mb_pressured": press["peak_rss_mb"],
        "accounted_peak_mb_unpressured": base["accounted_peak_mb"],
        "accounted_peak_mb_pressured": press["accounted_peak_mb"],
        "evictions": press["evictions"],
        "ft_cache_evictions": press["ft_cache_evictions"],
        "answers_identical": (press["answers_digest"]
                              == base["answers_digest"]),
        "oom": press["oom"] or base["oom"],
    }


def bench_knn_sharded(quick=False, groups=2):
    """BENCH family `knn_sharded`: scatter-gather KNN over a REAL
    multi-group sharded cluster — every group a primary+replica pair of
    subprocess KV servers, the element keyspace cut so each group owns
    a slice of the index rows (idx/shardvec.py). Clustered data.

    Emits: aggregate + per-shard fan-out qps, merge recall@10 vs the
    single-node oracle, p50/p99 latency, and the failover story —
    one element-shard primary SIGKILLed mid-run must yield ZERO wrong
    answers (only typed partial/retried ones, SURREAL_KNN_PARTIAL=
    partial) with recovery to full answers after the replica promotes.
    Baseline: the SAME data served by one single-node remote KV (the
    PR-1 deployment sharding replaces); gate aggregate_qps >= 1x it."""
    import shutil
    import signal
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from surrealdb_tpu import Datastore, cnf
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.shard import init_topology

    n = 20_000 if quick else 60_000
    dim = 64
    k = 10
    nq = 16
    q_phase = 240 if quick else 600
    threads = 8
    xs, rng = _clustered_rows(n, dim, 64, 0.15, 31)
    qs = xs[rng.integers(0, n, nq)] + 0.05 * rng.normal(
        size=(nq, dim)
    ).astype(np.float32)
    # exact ground truth (the single-node oracle's answers)
    xn = xs.astype(np.float64)
    truth = []
    for q in qs:
        d = np.linalg.norm(xn - q.astype(np.float64)[None, :], axis=1)
        truth.append([int(i) for i in np.argsort(d, kind="stable")[:k]])
    hek = lambda i: K.ix_state("b", "b", "tbl", "ix", b"he",  # noqa: E731
                               K.enc_value(i))
    cuts = [hek(n * g // groups) for g in range(1, groups)]
    tmp = tempfile.mkdtemp(prefix="bench-knnsh-")
    procs = []
    group_addrs = []
    sql = f"SELECT id FROM tbl WHERE emb <|{k}|> $q"

    def _define(ds):
        ds.query(
            f"DEFINE TABLE tbl; DEFINE INDEX ix ON tbl FIELDS emb "
            f"HNSW DIMENSION {dim} DIST EUCLIDEAN TYPE F32",
            ns="b", db="b",
        )

    def _drive(ds, n_queries, lats=None, outcomes=None):
        def one(i):
            t0 = time.perf_counter()
            r = ds.execute(sql, ns="b", db="b",
                           vars={"q": qs[i % nq].tolist()})[-1]
            dt = time.perf_counter() - t0
            if lats is not None:
                lats.append(dt)
            if outcomes is None:
                return
            if r.error is not None:
                outcomes.append(("error", i % nq))
            elif r.partial:
                outcomes.append(("partial", i % nq))
            else:
                got = [row["id"].id for row in r.result]
                outcomes.append((
                    "full" if got == truth[i % nq] else "wrong",
                    i % nq,
                ))

        with ThreadPoolExecutor(threads) as ex:
            t0 = time.perf_counter()
            list(ex.map(one, range(n_queries)))
            return n_queries / (time.perf_counter() - t0)

    saved_partial = cnf.KNN_PARTIAL
    saved_budget = cnf.KNN_SHARD_TIMEOUT_S
    try:
        # ---- boot the cluster: `groups` primary+replica pairs -------
        for g in range(groups):
            ports = [_free_port(), _free_port()]
            addrs = [f"127.0.0.1:{p}" for p in ports]
            procs.append(_spawn_kv_proc(
                ports[0], "primary", addrs, f"{tmp}/g{g}p"))
            procs.append(_spawn_kv_proc(
                ports[1], "replica", addrs, f"{tmp}/g{g}r"))
            group_addrs.append(addrs)
        init_topology(group_addrs, cuts)
        ds = Datastore(f"shard://{','.join(group_addrs[0])}")
        _define(ds)
        t0 = time.perf_counter()
        _bulk_vectors_sharded(ds, "b", "b", "tbl", "ix", xs)
        ingest_s = time.perf_counter() - t0
        # ---- steady state: fan-out qps + recall ---------------------
        cnf.KNN_PARTIAL = "partial"
        cnf.KNN_SHARD_TIMEOUT_S = 2.0
        _drive(ds, threads * 2)  # warm: sync parts, pin pools
        fan0 = ds.telemetry.get("knn_shard_fanout")
        lats: list = []
        outcomes: list = []
        qps = _drive(ds, q_phase, lats, outcomes)
        fanout_qps = (ds.telemetry.get("knn_shard_fanout") - fan0) \
            * qps / max(q_phase, 1)
        assert all(o == "full" for o, _ in outcomes), \
            "steady state must answer fully"
        hits = sum(
            len(set(truth[iq]) & set(
                row["id"].id for row in ds.execute(
                    sql, ns="b", db="b", vars={"q": qs[iq].tolist()}
                )[-1].result
            )) for iq in range(nq)
        )
        recall = hits / (k * nq)
        # ---- SIGKILL one element-shard primary mid-run --------------
        victim = procs[2]  # group 1's primary (an element-range group)
        kill_lats: list = []
        kill_outcomes: list = []

        def killer():
            time.sleep(0.4)
            victim.send_signal(signal.SIGKILL)
            victim.wait()

        import threading as _threading

        kt = _threading.Thread(target=killer)
        kt.start()
        _drive(ds, q_phase, kill_lats, kill_outcomes)
        kt.join()
        wrong = sum(1 for o, _ in kill_outcomes if o == "wrong")
        partials = sum(1 for o, _ in kill_outcomes if o == "partial")
        errs = sum(1 for o, _ in kill_outcomes if o == "error")
        # ---- recovery: full answers must resume post-failover -------
        recovered = False
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            r = ds.execute(sql, ns="b", db="b",
                           vars={"q": qs[0].tolist()})[-1]
            if r.error is None and not r.partial \
                    and [row["id"].id for row in r.result] == truth[0]:
                recovered = True
                break
            time.sleep(0.3)
        shard_info = ds.query("INFO FOR SYSTEM",
                              ns="b", db="b")[0].get("knn")
        hedged = ds.telemetry.get("knn_hedged_dispatches")
        n_partial_res = ds.telemetry.get("knn_partial_results")
        ds.close()
        # ---- single-node oracle: ONE remote KV group, same stack ----
        port = _free_port()
        procs.append(_spawn_kv_proc(
            port, "primary", [f"127.0.0.1:{port}"], f"{tmp}/single"))
        ds1 = Datastore(f"remote://127.0.0.1:{port}")
        _define(ds1)
        _bulk_vectors_sharded(ds1, "b", "b", "tbl", "ix", xs)
        _drive(ds1, threads * 2)
        single_qps = _drive(ds1, q_phase)
        ds1.close()
        lat_ms = sorted(x * 1000 for x in lats)
        klat_ms = sorted(x * 1000 for x in kill_lats)

        def _pct(a, p):
            return round(a[min(int(len(a) * p), len(a) - 1)], 2) \
                if a else None

        return {
            "metric": f"knn_sharded_{groups}g_{n//1000}k_{dim}d",
            "shard_groups": groups,
            "rows": n,
            # 1-core honesty: each query pays one extra sub-txn
            # lifecycle per additional shard its reads touch, and the
            # halved per-part gemms land on the SAME core — parity
            # with single-node needs >= 2 cores (the per-part searches
            # and KV servers then genuinely parallelize)
            "cores": os.cpu_count() or 1,
            "qps": round(qps, 2),
            "fanout_qps": round(fanout_qps, 2),
            "single_node_qps": round(single_qps, 2),
            "vs_single_node": round(qps / max(single_qps, 1e-9), 3),
            "recall_at_10": round(recall, 4),
            "p50_ms": _pct(lat_ms, 0.50),
            "p99_ms": _pct(lat_ms, 0.99),
            "kill_p50_ms": _pct(klat_ms, 0.50),
            "kill_p99_ms": _pct(klat_ms, 0.99),
            "kill_wrong_answers": wrong,
            "kill_partial_answers": partials,
            "kill_error_answers": errs,
            "knn_partial_results": n_partial_res,
            "knn_hedged_dispatches": hedged,
            "recovered_full_answers": recovered,
            "index_shards": (len(shard_info[0]["shards"])
                             if shard_info else None),
            "ingest_s": round(ingest_s, 1),
            "clients": threads,
            "queries": q_phase * 2,
        }
    finally:
        cnf.KNN_PARTIAL = saved_partial
        cnf.KNN_SHARD_TIMEOUT_S = saved_budget
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=5)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def bench_follower_reads(quick=False):
    """BENCH family `follower_reads`: closed-timestamp bounded-staleness
    read serving on replicas (kvs/remote.py) over a REAL 3-member
    replica group of subprocess KV servers.

    Measures read qps primary-only (the PR-5 baseline: every read on
    one node) vs follower-enabled (READ AT semantics: replicas prove
    the bound and serve), the per-node serve distribution, and the
    correctness gate: every answer for the write-once keyset must be
    exact — zero stale answers. On a 1-core container the CLIENT
    process is the GIL-bound side, so the honest number here is the
    measured fan-out (reads actually leaving the primary) plus the qps
    delta; the >=1.8x/replica scaling gate needs cores for the three
    server processes + client threads to run in parallel (same caveat
    as PR 9's sharded numbers)."""
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from surrealdb_tpu.kvs.remote import (
        RemoteBackend, RetryPolicy, _status_of,
    )

    n_keys = 2000
    n_queries = 3000 if quick else 12000
    threads = 8
    gets_per_query = 4
    tmp = tempfile.mkdtemp(prefix="bench-follower-")
    ports = [_free_port() for _ in range(3)]
    peers = [f"127.0.0.1:{p}" for p in ports]
    procs = []
    be = None
    try:
        for i, port in enumerate(ports):
            procs.append(_spawn_kv_proc(
                port, "primary" if i == 0 else "replica", peers,
                os.path.join(tmp, f"m{i}"),
                failover_timeout=5.0, lease_ttl=4.0,
            ))
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            st = _status_of(("127.0.0.1", ports[0]), None)
            if st and st.get("attached_replicas") == 2:
                break
            time.sleep(0.2)
        be = RemoteBackend(",".join(peers),
                           policy=RetryPolicy(deadline_s=20.0))
        expect = {}
        for base in range(0, n_keys, 256):
            tx = be.transaction(True)
            for i in range(base, min(base + 256, n_keys)):
                k = f"/k/{i:06d}".encode()
                expect[k] = f"v{i}".encode()
                tx.set(k, expect[k])
            tx.commit()
        keys = sorted(expect)
        wrong = [0]

        def drive(staleness, count=None):
            count = n_queries if count is None else count

            def one(q):
                tx = be.transaction(False, max_staleness=staleness)
                for j in range(gets_per_query):
                    k = keys[(q * 7 + j * 131) % n_keys]
                    if tx.get(k) != expect[k]:
                        wrong[0] += 1
                tx.commit()

            with ThreadPoolExecutor(threads) as ex:
                t0 = time.perf_counter()
                list(ex.map(one, range(count)))
                return count / (time.perf_counter() - t0)

        def served_counters():
            out = {}
            for port in ports:
                st = _status_of(("127.0.0.1", port), None) or {}
                out[f"127.0.0.1:{port}"] = (
                    st.get("counters", {}).get(
                        "follower_reads_served", 0
                    ),
                    st.get("role"),
                )
            return out

        # warmup OUTSIDE the measurement (connections, page cache) so
        # the baseline is not cold-start-inflated in the follower
        # path's favor, then the primary-only baseline (exact reads)
        drive(None, count=max(n_queries // 8, 200))
        drive(30.0, count=max(n_queries // 8, 200))
        drive_exact_qps = drive(None)
        base_counters = served_counters()
        follower_qps = drive(30.0)
        after_counters = served_counters()
        per_node = {
            a: after_counters[a][0] - base_counters[a][0]
            for a in after_counters
        }
        replica_serves = sum(
            v for a, v in per_node.items()
            if after_counters[a][1] == "replica"
        )
        total_reads = n_queries
        return {
            "metric": "kv_follower_read_qps_3node",
            "value": round(follower_qps, 1),
            "unit": "qps",
            "primary_only_qps": round(drive_exact_qps, 1),
            "scaling_x": round(follower_qps / max(drive_exact_qps,
                                                  1e-9), 2),
            "replica_served_frac": round(
                replica_serves / max(total_reads, 1), 3
            ),
            "per_node_served": {a: v for a, v in per_node.items()},
            "stale_answers": wrong[0],
            "cores": os.cpu_count(),
            "clients": threads,
            "keys": n_keys,
            "queries": n_queries,
            "note": (
                "client process is GIL-bound on few-core hosts; the "
                "fan-out fraction is the honest scaling signal there "
                "(servers are separate processes)"
            ),
        }
    finally:
        if be is not None:
            be.close()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=5)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def bench_knn_mesh(quick=False):
    """BENCH family `knn_mesh`: the DeviceRunner mesh execution layer
    (device/mesh.py) across virtual device counts 1/2/4/8 — the same
    clustered store and queries served by a FRESH supervised runner per
    count. The runner subprocess inherits XLA_FLAGS, so every count is
    a real n-device jax process (virtual CPU devices — the mesh
    collectives compiled are the TPU deployment's);
    SURREAL_DEVICE_MESH=force row-shards the store across the full
    mesh, so count 1 is the legacy single-device kernel baseline.

    Emits per count: vec_knn qps, recall@10 vs f64 ground truth, merge
    overhead vs the 1-device run, and the runner-REPORTED mesh width
    (`mesh_ndev` — sharded_kernel_ran is only true when a reply said
    so, never inferred). tools/bench_report.py --multichip rolls this
    line into MULTICHIP_r0N.json."""
    import re

    from surrealdb_tpu import cnf
    from surrealdb_tpu.device.supervisor import DeviceSupervisor

    n = 20_000 if quick else 60_000
    dim = 64
    k = 10
    nq = 16
    dispatches = 40 if quick else 160
    xs, rng = _clustered_rows(n, dim, 64, 0.15, 31)
    qs = xs[rng.integers(0, n, nq)] + 0.05 * rng.normal(
        size=(nq, dim)
    ).astype(np.float32)
    xn = xs.astype(np.float64)
    truth = []
    for q in qs:
        d = np.linalg.norm(xn - q.astype(np.float64)[None, :], axis=1)
        truth.append(set(
            int(i) for i in np.argsort(d, kind="stable")[:k]
        ))
    valid = np.ones(n, np.uint8)
    cfg = {
        "hbm_budget": cnf.KNN_HBM_BUDGET_BYTES,
        "score_budget": cnf.KNN_SCORE_BUDGET_ELEMS,
        "query_chunk": cnf.KNN_QUERY_CHUNK,
        "int8_oversample": cnf.KNN_INT8_OVERSAMPLE,
        "block_rows": 1 << 20,
    }

    def loader():
        return "vec_load", {
            "metric": "euclidean", "mink_p": 3.0, "cfg": dict(cfg),
        }, [xs, valid]

    def run_count(nd):
        saved = {key: os.environ.get(key) for key in
                 ("XLA_FLAGS", "SURREAL_DEVICE_MESH", "JAX_PLATFORMS")}
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            os.environ.get("XLA_FLAGS", ""),
        ).strip()
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={nd}"
        ).strip()
        os.environ["SURREAL_DEVICE_MESH"] = "force"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sup = DeviceSupervisor(mode="require")
        try:
            if not sup.wait_ready(300):
                raise RuntimeError(
                    f"knn_mesh: {nd}-device runner never became ready: "
                    f"{sup.last_error}")
            sup.ensure_loaded("vec/knn-mesh", [1, 0], loader)
            meta = None

            def query():
                t, m, bufs = sup.call(
                    "vec_knn",
                    {"key": "vec/knn-mesh", "tag": [1, 0], "k": k},
                    [qs],
                )
                assert t == "ok", m.get("error")
                return m, bufs

            meta, bufs = query()  # warm: pays the mesh kernel compile
            t0 = time.perf_counter()
            for _ in range(dispatches):
                meta, bufs = query()
            dt = time.perf_counter() - t0
            if meta.get("mode") == "cand":
                # int8 candidates: exact host rescore, the serving path
                cand = bufs[0]
                got = []
                for b in range(nq):
                    ids_b = cand[b][(cand[b] >= 0) & (cand[b] < n)]
                    d = np.linalg.norm(
                        xn[ids_b] - qs[b].astype(np.float64)[None, :],
                        axis=1,
                    )
                    sel = np.argsort(d, kind="stable")[:k]
                    got.append(set(int(i) for i in ids_b[sel]))
            else:
                got = [set(int(i) for i in row) for row in bufs[1]]
            hits = sum(len(g & t) for g, t in zip(got, truth))
            return {
                "device_count": nd,
                "mesh_ndev": int(meta.get("mesh_ndev", 1) or 1),
                "rank_mode": meta.get("rank_mode"),
                "sharded_kernel_ran":
                    int(meta.get("mesh_ndev", 1) or 1) >= 2,
                "qps": round(dispatches * nq / dt, 1),
                "recall_at_10": round(hits / (k * nq), 4),
            }
        finally:
            sup.shutdown()
            for key, v in saved.items():
                if v is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = v

    counts = []
    for nd in (1, 2, 4, 8):
        counts.append(run_count(nd))
    base = next((c.get("qps") for c in counts
                 if c.get("device_count") == 1 and c.get("qps")), None)
    for c in counts:
        if base and c.get("qps"):
            # virtual devices timeshare the same cores, so this is the
            # mesh partition/merge TAX (positive), not a speedup claim
            c["merge_overhead"] = round(base / c["qps"] - 1.0, 4)
    sharded = [c for c in counts if c.get("sharded_kernel_ran")]
    return {
        "metric": "knn_mesh",
        "n": n, "dim": dim, "k": k, "queries_per_dispatch": nq,
        "counts": counts,
        "sharded_kernel_ran": bool(sharded),
        "n_devices_used": max(
            (c["mesh_ndev"] for c in sharded), default=1),
        "mesh_shape": [max((c["mesh_ndev"] for c in sharded),
                           default=1)],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run all six configs (one JSON line each)")
    ap.add_argument("--config", default=None,
                    choices=["hnsw100k", "knn1m", "knn10m", "ann10m",
                             "brute", "graph3hop", "hybrid",
                             "live_fanout", "knn_sharded",
                             "mem_pressure", "follower_reads",
                             "analytics", "knn_churn", "knn_mesh"])
    ap.add_argument("--groups", type=int, default=2,
                    help="shard groups for --config knn_sharded (2/4)")
    args = ap.parse_args()

    def emit(res):
        res.setdefault("platform", _PLATFORM or "unprobed")
        # resource-governance trajectory: every line carries the
        # process high-water RSS, the accountant's view of derived
        # state, and any eviction counters that moved — a future
        # unbounded-growth regression shows up as a peak_rss_mb /
        # accounted_mb trend long before it OOMs a real node
        import resource as _rusage

        from surrealdb_tpu import resource as _resource

        res.setdefault("peak_rss_mb", round(
            _rusage.getrusage(_rusage.RUSAGE_SELF).ru_maxrss
            / 1024.0, 1))
        snap = _resource.get_accountant().snapshot()
        res.setdefault("accounted_mb", round(
            snap["accounted_bytes"] / (1 << 20), 3))
        evs = {k: v for k, v in snap["counters"].items() if v}
        if evs:
            res.setdefault("mem_counters", evs)
        # device-supervisor health snapshot: the benched queries ran
        # through the supervised runner in `require` mode, so a
        # degraded device already failed the config; the counters say
        # what the run dispatched and compiled
        from surrealdb_tpu.device import get_supervisor

        st = get_supervisor().status()
        res.setdefault("backend_state", st["state"])
        res.setdefault("device_kind", st.get("device_kind"))
        if st.get("fallbacks"):
            res.setdefault("device_fallbacks", st["fallbacks"])
        if st.get("host_routed"):
            res.setdefault("device_host_routed", st["host_routed"])
        # batching efficiency + compile-cache behavior of the run
        # (the PR-6 serving-tax instrumentation)
        b = st.get("batching") or {}
        if b.get("dispatches"):
            res.setdefault("device_batch_avg", b["avg"])
            res.setdefault("device_batch_max", b["max"])
        cc = st.get("compile_cache") or {}
        if cc.get("hits") or cc.get("misses"):
            res.setdefault("compile_cache_hits", cc["hits"])
            res.setdefault("compile_cache_misses", cc["misses"])
        print(json.dumps(res), flush=True)

    fns = {
        "hnsw100k": bench_hnsw100k,
        "knn1m": bench_knn1m,
        "knn10m": bench_knn10m,
        "ann10m": bench_ann10m,
        "brute": bench_brute,
        "graph3hop": bench_graph3hop,
        "hybrid": bench_hybrid,
        "live_fanout": bench_live_fanout,
        "knn_sharded": bench_knn_sharded,
        "mem_pressure": bench_mem_pressure,
        "follower_reads": bench_follower_reads,
        "analytics": bench_analytics,
        "knn_churn": bench_knn_churn,
        "knn_mesh": bench_knn_mesh,
    }
    _probe_backend()
    if args.all:
        for name, fn in fns.items():
            if name == "knn_sharded":
                emit(fn(quick=args.quick, groups=2))
                emit(fn(quick=args.quick, groups=4))
            else:
                emit(fn(quick=args.quick))
        return 0
    if args.config == "knn_sharded":
        emit(bench_knn_sharded(quick=args.quick, groups=args.groups))
        return 0
    if args.config:
        emit(fns[args.config](quick=args.quick))
        return 0
    # Default: the BASELINE north-star — 10M×768 KNN through the SQL
    # path. Any config that raises ends the run non-zero: a line that
    # was not measured is not printed.
    if args.quick:
        emit(bench_knn10m(quick=True))
        emit(bench_ann10m(quick=True))
        emit(bench_live_fanout(quick=True))
        emit(bench_analytics(quick=True))
        emit(bench_knn_sharded(quick=True, groups=2))
        emit(bench_mem_pressure(quick=True))
        return 0
    if _PLATFORM == "cpu":
        # an explicit CPU run (JAX_PLATFORMS=cpu): the 10M×768 ingest
        # is a TPU-scale workload, so the 1M config stands in and the
        # line says so; the ANN config self-reduces and labels itself
        res = bench_knn1m(quick=False)
        res["reduced"] = "cpu platform: knn1m stands in for knn10m"
        emit(res)
        emit(bench_ann10m(quick=False))
        emit(bench_live_fanout(quick=False))
        for g in (2, 4):
            emit(bench_knn_sharded(quick=False, groups=g))
        emit(bench_mem_pressure(quick=False))
        emit(bench_analytics(quick=False))
        return 0
    # a --quick-sized smoke runs FIRST so a broken search path fails in
    # about a minute, not after a 30 GB ingest
    smoke = bench_knn1m(quick=True)
    print(f"bench: smoke ok: {json.dumps(smoke)}", file=sys.stderr,
          flush=True)
    emit(bench_knn10m(quick=False))
    emit(bench_ann10m(quick=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
