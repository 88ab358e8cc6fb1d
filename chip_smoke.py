#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that surrealdb-tpu starts on the chip.

One process serves SurrealQL over HTTP (`make_server` on a thread, a
memory datastore, `SURREAL_DEVICE=require`); the supervised DeviceRunner
subprocess owns the accelerator. This script never imports jax. Every
query goes through `POST /sql`; every answer is checked against a plain
reference written here (f64 numpy brute force, a numpy BFS). Stages:

  exact  BASELINE config 1: 100,000 x 128 f32, HNSW DIST EUCLIDEAN,
         `<|10|>` — vec_load + vec_knn (bf16 rank, f32 rescore). Most
         rows load through the KV bulk route, the last batch through
         SQL INSERT (write -> `he` key -> op log -> re-ship), and the
         inserted rows must come back in the answers. Every reported
         distance is held to f32 accuracy against f64; the ids are
         held to what `rank_mode: bf16` promises on a TPU, where
         `lax.approx_max_k` is approximate: recall@10 >= 0.985 with
         every returned row inside the reference's top 16 (EXACT_*).
  ann    BASELINE config 2: 1,000,000 x 768 f32 cosine, `<|10,40|>` —
         sealed-segment CAGRA graphs built on the host, shipped, int8
         descent on the device, exact re-rank. recall@10 >= 0.95.
  graph  BASELINE config 4 cut 10x: 100,000 nodes / 1,000,000 edges,
         `person:0.{..5+collect}(->knows->person)` — levels of >= 512
         nodes expand as CSR hops on the device. Reached set exact.
  ml     one `ml::` call on a tiny ONNX model, then one more KNN: the
         serving process must not have taken the chip.

It exits non-zero, and prints nothing on stdout, unless the runner came
up on a TPU, every stage passed its check, vec_knn / ann_search / csr_hop
were each dispatched, and the supervisor counted no fallback, host
route, restart, dispatch timeout, dispatch error or budget refusal.

On success stdout holds two JSON lines. The first is the report: sizes,
set-up seconds, compile cache, runner evidence, supervisor counters. The
last is the verdict, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as the runner's jax reports it.

`--rehearsal` (needs JAX_PLATFORMS=cpu) walks the same code at tiny
sizes on the CPU for the test suite; its report says `"rehearsal": true`.
`--break-check STAGE` corrupts that stage's reference, to prove that a
failed check fails the run.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time
import traceback
import urllib.request

import numpy as np

NS = DB = "smoke"
K_NN = 10
# the whole run, compilation included, must end inside the driver's
# 1200 s; a hang becomes a failure with clean-up, not a kill
DEADLINE_S = 1150

# The bf16 rank stage keeps kc = 26 candidates per query with
# `lax.approx_max_k(recall_target=0.95)`. On a TPU that is a
# PartialReduce over L bins, (kc-1)/(1-0.95) = 500 <= L < 1000, and the
# j-th nearest row is lost when a nearer one shares its bin: j-1 in L.
# Over the top 10 that is 45/L per query — 0.45% to 0.9% of ids (8 of
# 1280 measured on a v5e, L = 782). The row that takes a lost one's
# place is the next nearest, so an answer never reaches past the top
# 10 + REACH. On the CPU backend approx_max_k is exact and recall is 1.
EXACT_MIN_RECALL = 0.985
EXACT_REACH = 6

FULL = {
    # 136 queries (1360 ids): P(recall < 0.985 | 0.9% loss) < 1e-3
    "exact": {"rows": 100_000, "dim": 128, "sql_rows": 1024, "queries": 128,
              "burst": 8},
    # 128 queries: the strided routing probe misses a 100-row cluster
    # with p ~ e^-(100/24) = 1.5% (cnf.KNN_ANN_PROBE_FRAC), a miss costs
    # a whole query, and 16 queries would fail a sound index one run
    # in five
    "ann": {"rows": 1_000_000, "dim": 768, "queries": 128},
    "graph": {"nodes": 100_000, "edges": 1_000_000, "depth": 5},
}
REHEARSAL = {
    "exact": {"rows": 1024, "dim": 128, "sql_rows": 64, "queries": 4,
              "burst": 4},
    "ann": {"rows": 2048, "dim": 768, "queries": 4},
    "graph": {"nodes": 400, "edges": 2000, "depth": 4},
}
# what the full sizes cut from their source, BASELINE.json's configs
REDUCED = {
    "graph": "BASELINE config 4 is 1M nodes / 10M edges; cut 10x for "
             "host ingest time inside the 1200 s limit",
}
# thresholds a rehearsal shrinks so tiny stores walk the same paths:
# exact stays under both ANN floors, ann lands on sealed segments
REHEARSAL_ENV = {
    "SURREAL_KNN_HOST_BATCH": "device",
    "SURREAL_KNN_DEVICE_MIN_ROWS": "64",
    "SURREAL_KNN_ANN_MIN_ROWS": "1536",
    "SURREAL_KNN_SEG_MIN_ROWS": "2048",
    "SURREAL_KNN_SEG_ROWS": "1024",
    # the NN-descent refine round is most of a tiny build's seconds
    "SURREAL_KNN_ANN_REFINE": "0",
}


class CheckFailed(Exception):
    pass


def log(msg: str):
    print(f"[chip_smoke +{time.monotonic() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.monotonic()


# -- data from the seed ------------------------------------------------------


def clustered_rows(n: int, dim: int, seed: int, std: float = 0.15):
    """Embedding-shaped rows: n // 100 gaussian clusters (i.i.d.
    gaussian at high dimension resembles no deployment). Filled in
    place, chunk by chunk."""
    rng = np.random.default_rng(seed)
    nc = max(n // 100, 8)
    centers = rng.standard_normal((nc, dim), dtype=np.float32)
    xs = np.empty((n, dim), np.float32)
    step = 1 << 16
    for s in range(0, n, step):
        blk = xs[s:s + step]
        rng.standard_normal(out=blk, dtype=np.float32)
        blk *= std
        blk += centers[rng.integers(0, nc, len(blk))]
    return xs, rng


def queries_near(xs, rows, rng):
    q = xs[rows] + 0.05 * rng.standard_normal(
        (len(rows), xs.shape[1]), dtype=np.float32)
    return q.astype(np.float32)


# -- plain references --------------------------------------------------------


def brute_force(xs, qs, metric: str, keep: int):
    """f64 numpy brute force: per query the `keep` nearest (ids, dists)
    in ascending order, plus a function giving any row's distance."""
    q64 = qs.astype(np.float64)
    nq = len(qs)
    best_d = np.full((nq, 0), np.inf)
    best_i = np.zeros((nq, 0), np.int64)
    qn = np.linalg.norm(q64, axis=1)
    step = 1 << 16
    for s in range(0, len(xs), step):
        blk = xs[s:s + step].astype(np.float64)
        if metric == "euclidean":
            d = np.sqrt(np.maximum(
                (blk * blk).sum(1)[None, :] + (q64 * q64).sum(1)[:, None]
                - 2.0 * q64 @ blk.T, 0.0))
        else:  # cosine distance
            d = 1.0 - (q64 @ blk.T) / np.maximum(
                qn[:, None] * np.linalg.norm(blk, axis=1)[None, :], 1e-300)
        ids = np.arange(s, s + len(blk))[None, :].repeat(nq, 0)
        best_d = np.concatenate([best_d, d], axis=1)
        best_i = np.concatenate([best_i, ids], axis=1)
        order = np.argsort(best_d, axis=1, kind="stable")[:, :keep]
        best_d = np.take_along_axis(best_d, order, 1)
        best_i = np.take_along_axis(best_i, order, 1)

    def dist_of(qi: int, row: int) -> float:
        x = xs[row].astype(np.float64)
        if metric == "euclidean":
            return float(np.linalg.norm(x - q64[qi]))
        return float(1.0 - (x @ q64[qi]) / max(
            np.linalg.norm(x) * qn[qi], 1e-300))

    return best_i, best_d, dist_of


def bfs_collect(src, dst, n_nodes: int, start: int, depth: int) -> set:
    """numpy BFS with `+collect` semantics: union of the levels 1..depth
    under a visited set that does not hold the start node (a cycle may
    rediscover and collect it)."""
    order = np.argsort(src, kind="stable")
    cols = dst[order]
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    visited = np.zeros(n_nodes, bool)
    frontier = np.array([start], np.int64)
    for _ in range(depth):
        if not len(frontier):
            break
        parts = [cols[indptr[v]:indptr[v + 1]] for v in frontier]
        nxt = np.unique(np.concatenate(parts)) if parts else frontier[:0]
        nxt = nxt[~visited[nxt]]
        visited[nxt] = True
        frontier = nxt
    return set(np.flatnonzero(visited).tolist())


# -- the served path ---------------------------------------------------------


class Client:
    """HTTP `/sql` client: what a user's SDK sends."""

    def __init__(self, port: int):
        self.url = f"http://127.0.0.1:{port}"
        self.headers = {"surreal-ns": NS, "surreal-db": DB,
                        "Accept": "application/json"}

    def post(self, path: str, body: bytes):
        req = urllib.request.Request(self.url + path, data=body,
                                     headers=self.headers, method="POST")
        with urllib.request.urlopen(req, timeout=DEADLINE_S) as r:
            return json.loads(r.read())

    def sql(self, text: str):
        """Results of every statement; an ERR statement raises."""
        out = self.post("/sql", text.encode())
        for st in out:
            if st["status"] != "OK":
                raise RuntimeError(f"statement failed: {st['result']}")
        return [st["result"] for st in out]

    def knn(self, table: str, q, ef=None):
        """[(row id, distance)] of `<|10[,ef]|>` in answer order."""
        op = f"<|{K_NN},{ef}|>" if ef else f"<|{K_NN}|>"
        vec = "[" + ",".join(repr(float(v)) for v in q) + "]"
        rows = self.sql(
            f"SELECT id, vector::distance::knn() AS d FROM {table} "
            f"WHERE emb {op} {vec}")[0]
        return [(int(str(r["id"]).split(":", 1)[1]), float(r["d"]))
                for r in rows]


def bulk_vectors(ds, table: str, ix: str, xs, chunk: int = 50_000):
    """The KV bulk route: records + `he`
    index state + the `vn` version, no op log — the first search
    rebuilds from the `he` keys."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    n = len(xs)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        txn = ds.transaction(write=True)
        try:
            for i in range(s, e):
                txn.set(K.record(NS, DB, table, i),
                        serialize({"id": RecordId(table, i)}))
                txn.set_val(
                    K.ix_state(NS, DB, table, ix, b"he", K.enc_value(i)),
                    xs[i].tobytes())
            txn.set_val(K.ix_state(NS, DB, table, ix, b"vn"), e)
            txn.commit()
        except BaseException:
            txn.cancel()
            raise


def bulk_graph(ds, n_nodes: int, src, dst, chunk: int = 100_000):
    """The KV bulk route for a RELATE graph:
    node records, edge records and the four `~` graph keys per edge."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    txn = ds.transaction(write=True)
    try:
        for i in range(n_nodes):
            txn.set(K.record(NS, DB, "person", i),
                    serialize({"id": RecordId("person", i)}))
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    for lo in range(0, len(src), chunk):
        txn = ds.transaction(write=True)
        try:
            for e in range(lo, min(lo + chunk, len(src))):
                s, d = int(src[e]), int(dst[e])
                txn.set(K.record(NS, DB, "knows", e), serialize({
                    "id": RecordId("knows", e),
                    "in": RecordId("person", s),
                    "out": RecordId("person", d)}))
                txn.set(K.graph(NS, DB, "person", s, K.DIR_OUT, "knows", e),
                        b"")
                txn.set(K.graph(NS, DB, "knows", e, K.DIR_IN, "person", s),
                        b"")
                txn.set(K.graph(NS, DB, "knows", e, K.DIR_OUT, "person", d),
                        b"")
                txn.set(K.graph(NS, DB, "person", d, K.DIR_IN, "knows", e),
                        b"")
            txn.commit()
        except BaseException:
            txn.cancel()
            raise


def check_knn(got, ref_i, ref_d, dist_of, qi: int, rtol: float, atol: float,
              what: str):
    """One answer against the reference: k distinct rows in ascending
    order, every reported distance equal to the f64 distance of THAT
    row within tolerance. Returns how many rows belong to the
    reference top-k, counting ties at its last place (for recall)."""
    if len({row for row, _d in got}) != K_NN:
        raise CheckFailed(
            f"{what} q{qi}: {len(got)} rows, want {K_NN} distinct")
    ds_ = [d for _i, d in got]
    if any(not np.isfinite(d) for d in ds_) or ds_ != sorted(ds_):
        raise CheckFailed(f"{what} q{qi}: distances not finite/ascending")
    for row, d in got:
        want = dist_of(qi, row)
        if abs(d - want) > atol + rtol * abs(want):
            raise CheckFailed(
                f"{what} q{qi}: row {row} reported {d!r}, f64 reference "
                f"{want!r} (rtol {rtol}, atol {atol})")
    truth = set(ref_i[qi, :K_NN].tolist())
    kth = ref_d[qi, K_NN - 1]
    overlap = 0
    for row, _d in got:
        if row in truth or dist_of(qi, row) <= kth + atol + rtol * kth:
            overlap += 1  # in the top-k, or tied with its last place
    return overlap


def onnx_linear(w, b) -> bytes:
    """Hand-encoded ONNX ModelProto `y = x @ w + b` (tests/test_ml.py)."""
    def varint(n):
        out = b""
        while True:
            byte = n & 0x7F
            n >>= 7
            if n:
                out += bytes([byte | 0x80])
            else:
                return out + bytes([byte])

    def field(fno, wt, payload):
        return varint((fno << 3) | wt) + (
            varint(len(payload)) + payload if wt == 2 else payload)

    def tensor(name, arr):
        msg = b"".join(field(1, 0, varint(d)) for d in arr.shape)
        msg += field(2, 0, varint(1))  # float32
        msg += field(8, 2, name.encode())
        return msg + field(9, 2, arr.astype("<f4").tobytes())

    def node(op, ins, outs):
        msg = b"".join(field(1, 2, i.encode()) for i in ins)
        msg += b"".join(field(2, 2, o.encode()) for o in outs)
        return msg + field(4, 2, op.encode())

    graph = field(1, 2, node("MatMul", ["x", "w"], ["xw"]))
    graph += field(1, 2, node("Add", ["xw", "b"], ["y"]))
    graph += field(5, 2, tensor("w", w)) + field(5, 2, tensor("b", b))
    graph += field(11, 2, field(1, 2, b"x"))
    graph += field(12, 2, field(1, 2, b"y"))
    return field(7, 2, graph)


# -- stages ------------------------------------------------------------------


def stage_exact(ds, cl, sup, size, seed, broken, timing):
    n, dim, n_sql = size["rows"], size["dim"], size["sql_rows"]
    xs, rng = clustered_rows(n, dim, seed)
    n_bulk = n - n_sql
    cl.sql(f"DEFINE TABLE vec128; DEFINE INDEX ix ON vec128 FIELDS emb "
           f"HNSW DIMENSION {dim} DIST EUCLIDEAN TYPE F32")
    t = time.monotonic()
    bulk_vectors(ds, "vec128", "ix", xs[:n_bulk])
    timing["ingest_s"] += time.monotonic() - t
    # first search: rebuild from the `he` keys, ship, compile
    cl.knn("vec128", xs[0])
    # the last rows arrive as a client would send them
    t = time.monotonic()
    for s in range(n_bulk, n, 256):
        rows = ",".join(
            "{id:%d,emb:[%s]}" % (
                i, ",".join(repr(float(v)) for v in xs[i]))
            for i in range(s, min(s + 256, n)))
        cl.sql(f"INSERT INTO vec128 [{rows}]")
    timing["ingest_s"] += time.monotonic() - t
    nq = size["queries"] + size["burst"]
    # half the queries sit next to rows the INSERT acknowledged
    near = np.concatenate([
        rng.integers(n_bulk, n, nq // 2), rng.integers(0, n_bulk,
                                                       nq - nq // 2)])
    qs = queries_near(xs, near, rng)
    ref_i, ref_d, dist_of = brute_force(xs, qs, "euclidean",
                                        K_NN + EXACT_REACH)
    if broken:
        ref_i, ref_d = (ref_i + 1) % n, ref_d * 0.5
    answers = [cl.knn("vec128", qs[i]) for i in range(size["queries"])]
    # a concurrent burst rides the cross-query batcher as one dispatch
    burst = [None] * size["burst"]

    def one(j):
        burst[j] = cl.knn("vec128", qs[size["queries"] + j])

    ts = [threading.Thread(target=one, args=(j,)) for j in range(len(burst))]
    for th in ts:
        th.start()
    for th in ts:
        th.join(DEADLINE_S)
    if any(b is None for b in burst):
        raise CheckFailed("exact: a burst query did not answer")
    answers += burst
    # f32 rescore of 128 terms: the sum's rounding is ~1e-6 relative;
    # one bf16 pass (4e-3) would miss this by three orders of magnitude
    rtol, atol = 1e-5, 1e-6
    overlap = sum(check_knn(a, ref_i, ref_d, dist_of, i, rtol, atol, "exact")
                  for i, a in enumerate(answers))
    for i, a in enumerate(answers):
        reach = ref_d[i, -1]
        far = [row for row, _d in a
               if dist_of(i, row) > reach + atol + rtol * reach]
        if far:
            raise CheckFailed(f"exact q{i}: rows {far} lie beyond the "
                              f"reference's top {K_NN + EXACT_REACH}")
    recall = overlap / (nq * K_NN)
    if recall < EXACT_MIN_RECALL:
        raise CheckFailed(f"exact: {overlap} of {nq * K_NN} ids match the "
                          f"f64 reference, recall@10 {recall:.4f} < "
                          f"{EXACT_MIN_RECALL}")
    # an acknowledged write is read back: each query placed next to an
    # inserted row must return that row
    missing = [int(near[i]) for i in range(nq // 2)
               if int(near[i]) not in {row for row, _d in answers[i]}]
    if missing:
        raise CheckFailed(f"exact: SQL-inserted rows {missing[:5]} did not "
                          f"come back from the query placed on them")
    return {"rows": n, "dim": dim, "metric": "euclidean", "k": K_NN,
            "sql_inserted_rows": n_sql, "queries": nq,
            "ids_matching": overlap, "ids_expected": nq * K_NN,
            "recall_at_10": round(recall, 4),
            "min_recall": EXACT_MIN_RECALL,
            "inserted_rows_read_back": nq // 2,
            "distance_rtol": rtol, "distance_atol": atol}


def stage_ann(ds, cl, sup, size, seed, broken, timing):
    n, dim = size["rows"], size["dim"]
    xs, rng = clustered_rows(n, dim, seed + 1)
    cl.sql(f"DEFINE TABLE vec768; DEFINE INDEX ix ON vec768 FIELDS emb "
           f"HNSW DIMENSION {dim} DIST COSINE TYPE F32")
    t = time.monotonic()
    bulk_vectors(ds, "vec768", "ix", xs)
    timing["ingest_s"] += time.monotonic() - t
    # the first search syncs the engine and seals the bulk load into a
    # segment; until its graph is built the span is scanned exactly ON
    # THE HOST (idx/segments.py `_exact_span`), so this answer is not
    # part of the check
    t = time.monotonic()
    cl.knn("vec768", xs[0], ef=40)
    timing["sync_s"] += time.monotonic() - t
    log(f"ann: {n} rows ingested and synced")
    ix = ds.vector_indexes[(NS, DB, "vec768", "ix")]
    t = time.monotonic()
    if not ix.ensure_ann():
        raise CheckFailed("ann: segment graphs did not build")
    timing["graph_build_s"] += time.monotonic() - t
    log(f"ann: graphs built in {time.monotonic() - t:.1f}s")
    plan = ix.ann_plan(K_NN)
    if not plan or plan.get("ann") != "segmented" \
            or plan["ready"] != plan["segments"] or plan["tail_rows"]:
        raise CheckFailed(f"ann: not served from sealed graphs: {plan}")
    nq = size["queries"]
    qs = queries_near(xs, rng.integers(0, n, nq), rng)
    ref_i, ref_d, dist_of = brute_force(xs, qs, "cosine", K_NN + 6)
    if broken:
        ref_i = (ref_i + 1) % n
    before = sup.runner_status()["ops"].get("ann_search", 0)
    answers = [cl.knn("vec768", qs[i], ef=40) for i in range(nq)]
    searched = sup.runner_status()["ops"].get("ann_search", 0) - before
    if searched < nq * plan["segments"]:
        raise CheckFailed(
            f"ann: {searched} device descents for {nq} queries over "
            f"{plan['segments']} segment(s); now served as "
            f"{ix.ann_plan(K_NN)}, supervisor {sup.status()}")
    hits = 0
    for i, a in enumerate(answers):
        # the re-rank is the host's f32-product/f64-combine cosine
        # ladder: ~1e-6 absolute at these norms
        check_knn(a, ref_i, ref_d, dist_of, i, 1e-5, 1e-5, "ann")
        hits += len({row for row, _d in a} & set(ref_i[i, :K_NN].tolist()))
    recall = hits / (nq * K_NN)
    if recall < 0.95:
        raise CheckFailed(f"ann: recall@10 {recall:.4f} < 0.95")
    return {"rows": n, "dim": dim, "metric": "cosine", "k": K_NN, "ef": 40,
            "queries": nq, "recall_at_10": round(recall, 4),
            "segments": plan["segments"], "device_descents": searched}


def stage_graph(ds, cl, sup, size, seed, broken, timing):
    n_nodes, n_edges, depth = size["nodes"], size["edges"], size["depth"]
    rng = np.random.default_rng(seed + 2)
    src = rng.integers(0, n_nodes, n_edges)
    dst = rng.integers(0, n_nodes, n_edges)
    cl.sql("DEFINE TABLE person; DEFINE TABLE knows TYPE RELATION")
    t = time.monotonic()
    bulk_graph(ds, n_nodes, src, dst)
    timing["ingest_s"] += time.monotonic() - t
    want = bfs_collect(src, dst, n_nodes, 0, depth)
    if broken:
        want = want | {n_nodes}
    before = sup.runner_status()["ops"].get("csr_hop", 0)
    rows = cl.sql(
        f"RETURN person:0.{{..{depth}+collect}}(->knows->person)")[0]
    hops = sup.runner_status()["ops"].get("csr_hop", 0) - before
    got = [int(str(r).split(":", 1)[1]) for r in rows]
    if len(got) != len(set(got)) or set(got) != want:
        raise CheckFailed(
            f"graph: reached {len(set(got))} nodes, reference {len(want)}; "
            f"{len(set(got) ^ want)} differ")
    return {"nodes": n_nodes, "edges": n_edges, "depth": depth,
            "reached": len(got), "device_hops": hops}


def stage_ml(ds, cl, sup, xs_query):
    """One ml:: call, then prove the serving process left the chip to
    the runner: its jax (loaded by the ONNX executor) is pinned to the
    CPU backend, it holds no accelerator device file, and the same
    runner pid answers the next KNN."""
    ds.capabilities.allow_experimental.names.add("ml")
    pid = sup.runner_pid()
    cl.post("/ml/import", onnx_linear(
        np.array([[1.0, 2.0], [3.0, 4.0]], np.float32),
        np.array([0.5, -0.5], np.float32)))
    out = cl.sql("RETURN ml::model<0.0.0>([1, 1])")[0]
    if [round(float(v), 4) for v in out] != [4.5, 5.5]:
        raise CheckFailed(f"ml: got {out}, want [4.5, 5.5]")
    jax = sys.modules.get("jax")
    if jax is None or jax.config.jax_platforms != "cpu":
        raise CheckFailed("ml: the serving process's jax is not pinned "
                          "to the CPU backend")
    held = [t for t in (_fd_target(f) for f in os.listdir("/proc/self/fd"))
            if t.startswith(("/dev/accel", "/dev/vfio"))]
    if held:
        raise CheckFailed(f"ml: serving process holds {held}")
    before = sup.runner_status()["ops"].get("vec_knn", 0)
    if len(cl.knn("vec128", xs_query)) != K_NN:
        raise CheckFailed("ml: the KNN after the ml:: call came back short")
    if sup.runner_pid() != pid \
            or sup.runner_status()["ops"].get("vec_knn", 0) <= before:
        raise CheckFailed("ml: the KNN after the ml:: call was not served "
                          "by the same runner")
    return {"serving_jax_platforms": "cpu", "serving_accel_fds": 0,
            "runner_pid_unchanged": True}


COUNTERS = ("fallbacks", "host_routed", "restarts", "dispatch_timeouts",
            "dispatch_errors", "oom_refusals")


def assert_device_served(sup, after: str) -> dict:
    """Fail as soon as anything answered from the host or the runner
    was disturbed — checked after every stage, not only at the end."""
    st = sup.status()
    bad = {k: st[k] for k in COUNTERS if st[k]}
    if bad or st["state"] != "ready":
        raise CheckFailed(f"after {after}: supervisor state "
                          f"{st['state']}, {bad}, last error "
                          f"{st['last_error']}")
    return st


def _fd_target(fd: str) -> str:
    try:
        return os.readlink(f"/proc/self/fd/{fd}")
    except OSError:
        return ""


# -- entry -------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU (needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--break-check", choices=["exact", "ann", "graph"],
                    help="corrupt this stage's reference: the run must fail")
    args = ap.parse_args()
    sizes = REHEARSAL if args.rehearsal else FULL
    reduced = {} if args.rehearsal else REDUCED
    if args.rehearsal:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            print("chip_smoke: --rehearsal needs JAX_PLATFORMS=cpu",
                  file=sys.stderr)
            return 2
        os.environ.update(REHEARSAL_ENV)
    os.environ["SURREAL_DEVICE"] = "require"

    def on_alarm(_sig, _frm):
        raise TimeoutError(f"chip_smoke: not done after {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)

    from surrealdb_tpu import Datastore
    from surrealdb_tpu.device import get_supervisor, reset_supervisor
    from surrealdb_tpu.device.compile_cache import cache_dir, entry_count
    from surrealdb_tpu.server import make_server

    if args.rehearsal:
        import surrealdb_tpu.graph as graph

        graph.TPU_FRONTIER_THRESHOLD = 8
    cache = cache_dir()
    entries_before = entry_count(cache)
    srv = None
    ds = Datastore("memory")
    try:
        sup = get_supervisor()
        if not sup.wait_ready(sup.init_timeout_s + 10):
            print(f"chip_smoke: no device runner: {sup.last_error}",
                  file=sys.stderr)
            return 1
        if sup.platform != "tpu" and not args.rehearsal:
            print(f"chip_smoke: the runner is on {sup.platform!r}, not a "
                  f"TPU; nothing to prove here", file=sys.stderr)
            return 1
        log(f"runner pid {sup.runner_pid()} on {sup.platform} "
            f"{sup.device_kind} x{sup.device_count}")
        srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        cl = Client(srv.server_address[1])
        timing = {"ingest_s": 0.0, "sync_s": 0.0, "graph_build_s": 0.0}
        stages = {}
        for name, fn in (("exact", stage_exact), ("ann", stage_ann),
                         ("graph", stage_graph)):
            t = time.monotonic()
            stages[name] = fn(ds, cl, sup, sizes[name], args.seed,
                              args.break_check == name, timing)
            assert_device_served(sup, name)
            stages[name]["checked"] = True
            stages[name]["seconds"] = round(time.monotonic() - t, 1)
            log(f"{name} ok: {stages[name]}")
        stages["ml"] = stage_ml(ds, cl, sup, np.zeros(
            sizes["exact"]["dim"], np.float32))
        stages["ml"]["checked"] = True
        st = assert_device_served(sup, "ml")
        rs = sup.runner_status()
        ops = {k: rs["ops"].get(k, 0)
               for k in ("vec_knn", "ann_search", "csr_hop")}
        if not all(ops.values()):
            raise CheckFailed(f"an op never reached the device: {ops}")
        compile_s = rs["compile"]["backend_compile_s"]
        verdict = {
            "ok": True,
            "device": {"platform": str(rs["platform"]),
                       "kind": str(rs["device_kind"]),
                       "count": int(rs["device_count"])},
        }
        report = {
            "rehearsal": bool(args.rehearsal),
            "versions": st["versions"],
            "seed": args.seed,
            "stages": stages,
            "reduced": reduced,
            "setup_s": {
                "ingest": round(timing["ingest_s"], 1),
                "index_sync": round(timing["sync_s"], 1),
                "graph_build": round(timing["graph_build_s"], 1),
                "ship": st["ship_s"],
                "compile": round(sum(compile_s.values()), 2),
            },
            "compile_s_by_kernel": {
                k: round(v, 2) for k, v in sorted(
                    compile_s.items(), key=lambda kv: -kv[1])[:12]},
            "compile_cache": {
                "dir": cache, "entries_before": entries_before,
                "entries_after": entry_count(cache),
                "loaded": rs["compile"]["persistent_hits"],
                "compiled": rs["compile"]["persistent_misses"]},
            "memtable": "native" if type(ds.backend).__name__
            == "NativeMemBackend" else "python",
            "runner": {
                "pid": sup.runner_pid(), "dispatches": ops,
                "blocks": {k: rs[f"{k}_blocks"]
                           for k in ("vec", "ann", "csr")},
                "rank_modes": rs["rank_modes"],
                "mesh_ndev": rs["mesh"]["n_devices"],
                "sharded": {k: rs["mesh"][f"sharded_{k}"]
                            for k in ("vec", "ann", "csr")},
                "device_bytes_in_use": [d["bytes_in_use"]
                                        for d in rs["devices"]],
                "device_peak_bytes": [d["peak_bytes_in_use"]
                                      for d in rs["devices"]],
                "device_bytes_limit": [d["bytes_limit"]
                                       for d in rs["devices"]],
            },
            "supervisor": {k: st[k] for k in COUNTERS},
            "total_s": round(time.monotonic() - T0, 1),
        }
    except Exception as e:
        # the boundary: whatever a stage raised fails the run, loudly
        if not isinstance(e, CheckFailed):
            traceback.print_exc()
        print(f"chip_smoke: FAILED: {e.__class__.__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        reset_supervisor()  # stops the runner subprocess
        ds.close()
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
