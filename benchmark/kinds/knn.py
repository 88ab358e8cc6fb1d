"""Kind `knn`: a vector table behind a declared vector index, searched by
`WHERE emb <|k[,ef]|> $q` with the query vector as a bound variable, over
`POST /rpc` (method `query`), as the source's benchmarks and the SDKs send it.

Everything a KNN deployment needs besides its sizes (`configs/<name>.json`)
lives here: rows and queries from the seed, ingest, the plain reference
(f64 numpy brute force), the comparison that decides `correct`, and the
lower-precision control. `clustered_rows`, `queries_near`, `bulk_vectors`
and the per-answer conditions are copied from `chip_smoke.py` (PR 21), so
that a later change to the smoke cannot move the yardstick.

The reference imports nothing of the program. Only `ingest` touches
`surrealdb_tpu`: it is the deployment's load path, not its measure.
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

NS = DB = "bench"
HEADERS = {"surreal-ns": NS, "surreal-db": DB, "Accept": "application/json",
           "Content-Type": "application/json"}
PATH = "/rpc"       # where the window's requests go
COUNTERS = ("fallbacks", "host_routed", "restarts", "dispatch_timeouts",
            "dispatch_errors", "oom_refusals")


class SetupFailed(Exception):
    pass


# -- data from the seed ------------------------------------------------------


def clustered_rows(n: int, dim: int, seed: int, std: float = 0.15):
    """Embedding-shaped rows: n // 100 gaussian clusters. Filled in
    place, chunk by chunk. Returns (rows f32, the generator)."""
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


def sizes(cfg: dict, rehearsal: bool) -> dict:
    """The configuration as it is run: the file's sizes, or its
    `rehearsal` block laid over them for the CPU tests."""
    out = {k: v for k, v in cfg.items() if k != "rehearsal"}
    if rehearsal:
        out.update(cfg.get("rehearsal", {}))
    return out


# -- the deployment's load path ----------------------------------------------


def bulk_vectors(ds, table: str, ix: str, xs, chunk: int = 50_000):
    """The KV bulk route: records + `he` index state + the `vn`
    version, no op log — the first search rebuilds from the `he` keys."""
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


def vec_literal(v) -> str:
    return "[" + ",".join(map(repr, v.tolist())) + "]"


def knn_op(sz: dict) -> str:
    return f"<|{sz['k']},{sz['ef']}|>" if sz.get("ef") else f"<|{sz['k']}|>"


def knn_sql(sz: dict) -> str:
    """The statement every request carries; the vector is bound as `$q`."""
    return (f"SELECT id, vector::distance::knn() AS d FROM {sz['table']} "
            f"WHERE emb {knn_op(sz)} $q")


def rpc_body(sz: dict, index: int, q) -> bytes:
    """One JSON RPC `query` request: the statement and `{"q": [floats]}`."""
    return json.dumps({"id": index, "method": "query",
                       "params": [knn_sql(sz), {"q": q.tolist()}]}).encode()


class Deployment:
    """One loaded store and its seeded query pool."""

    def __init__(self, sz, xs, pool_q, pool_rows, on_sql, timing):
        self.sz = sz
        self.xs = xs
        self.pool_q = pool_q          # [P, D] f32, query i of the pool
        self.pool_rows = pool_rows    # the row each query was placed on
        self.on_sql = on_sql          # [P] bool: that row came by SQL INSERT
        self.timing = timing
        self.op = sz["runner_op"]

    def bodies(self):
        """The pool as request bodies, in pool order."""
        return [rpc_body(self.sz, i, q) for i, q in enumerate(self.pool_q)]

    def judge(self, records, before, after, limits, seed, say) -> dict:
        """The verdict on one window. `records` are the generators':
        (pool index, sent, received, status, reply). Every reply is parsed
        (`ok` says which are answers at all); the comparison with the
        reference takes all of them, or `compare_max` drawn from the seed."""
        k = self.sz["k"]
        parsed = [parse_answer(r[3], r[4], k) for r in records]
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        compared = compare(self, [(records[j][0], parsed[j]) for j in chosen],
                           limits, say)
        compared["answers_compared"] = num(len(chosen), 1, ">=")
        compared.update(device_served(before, after, self.op, say))
        return {"ok": [not isinstance(a, str) for a in parsed],
                "compared": compared,
                "metrics": {"recall_at_10": compared["recall_at_10"]["value"]}}

    def judge_control(self, records, limits, seed) -> dict:
        """The control's numbers on the queries that `judge` compared: the
        reference in bfloat16, put in the program's place."""
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        idx = [records[j][0] for j in chosen]
        answers = control_answers(self.xs, self.pool_q[idx], self.sz["metric"],
                                  self.sz["k"])
        words = []
        out = compare(self, list(zip(idx, answers)), limits, words.append)
        out["correct"] = all(c["ok"] for c in out.values())
        out["first_failures"] = words
        return out


def explained(sz: dict, plan) -> bool:
    """An EXPLAIN reply that says the planner took the declared index for
    this operator (a statement that names no ef is shown with the
    planner's own)."""
    try:
        first = plan[0]
        op = first["detail"]["plan"]["operator"]
        return first["operation"] == "Iterate Index" \
            and first["detail"]["plan"]["index"] == "ix" \
            and (op == knn_op(sz) or (not sz.get("ef") and op.startswith(
                knn_op(sz)[:-2] + ",")))
    except (KeyError, IndexError, TypeError):
        return False


def setup(cfg: dict, seed: int, ds, http, rehearsal: bool,
          log) -> Deployment:
    """Rows from the seed, DEFINE, ingest, first search, the SQL-inserted
    tail, the ANN build where the deployment has one, and the query pool.
    `http.sql(text)` posts to the served `/sql`, `http.query(text, vars)`
    to `/rpc`; both return the statements' results. Every write is
    acknowledged before this returns."""
    sql = http.sql
    sz = sizes(cfg, rehearsal)
    n, dim, tb = sz["rows"], sz["dim"], sz["table"]
    n_sql = int(sz.get("sql_rows", 0))
    n_bulk = n - n_sql
    timing = {}
    t = time.monotonic()
    xs, rng = clustered_rows(n, dim, seed)
    timing["data_s"] = time.monotonic() - t
    sql(f"DEFINE TABLE {tb}; DEFINE INDEX ix ON {tb} FIELDS emb "
        f"{sz['index']} DIMENSION {dim} DIST {sz['metric'].upper()} TYPE F32")
    t = time.monotonic()
    bulk_vectors(ds, tb, "ix", xs[:n_bulk])
    timing["ingest_s"] = time.monotonic() - t
    log(f"{n_bulk} rows by the bulk route in {timing['ingest_s']:.1f}s")
    # first search: rebuild from the `he` keys, ship, compile
    t = time.monotonic()
    http.query(knn_sql(sz), {"q": xs[0].tolist()})
    timing["first_search_s"] = time.monotonic() - t
    log(f"first search (index sync, ship) {timing['first_search_s']:.1f}s")
    if sz.get("explain_in_setup"):
        plan = http.query(knn_sql(sz) + " EXPLAIN", {"q": xs[0].tolist()})[0]
        if not explained(sz, plan):
            raise SetupFailed(f"EXPLAIN does not name the index: {plan}")
    if n_sql:
        # the last rows arrive as a client would send them
        t = time.monotonic()
        for s in range(n_bulk, n, 256):
            rows = ",".join("{id:%d,emb:%s}" % (i, vec_literal(xs[i]))
                            for i in range(s, min(s + 256, n)))
            sql(f"INSERT INTO {tb} [{rows}]")
        timing["sql_insert_s"] = time.monotonic() - t
    if sz.get("ann"):
        ix = ds.vector_indexes[(NS, DB, tb, "ix")]
        t = time.monotonic()
        if not ix.ensure_ann():
            raise SetupFailed("the segment graphs did not build")
        timing["graph_build_s"] = time.monotonic() - t
        plan = ix.ann_plan(sz["k"])
        if not plan or plan.get("ann") != sz["ann"] \
                or plan.get("ready") != plan.get("segments") \
                or plan.get("tail_rows"):
            raise SetupFailed(f"not served from sealed graphs: {plan}")
        log(f"graphs built in {timing['graph_build_s']:.1f}s: {plan}")
    # the pool: a share of it on rows the INSERT acknowledged, shuffled so
    # that any stretch of it holds both kinds
    t = time.monotonic()
    pool = sz["pool"]
    n_on_sql = int(pool * sz.get("queries_on_sql_rows", 0.0)) if n_sql else 0
    near = np.concatenate([rng.integers(n_bulk, n, n_on_sql),
                           rng.integers(0, n_bulk, pool - n_on_sql)])
    qs = queries_near(xs, near, rng)
    order = rng.permutation(pool)
    timing["pool_s"] = time.monotonic() - t
    return Deployment(sz, xs, qs[order], near[order], order < n_on_sql,
                      timing)


# -- the plain reference -----------------------------------------------------


def _distances(blk64, q64, qn, metric):
    if metric == "euclidean":
        return np.sqrt(np.maximum(
            (blk64 * blk64).sum(1)[None, :] + (q64 * q64).sum(1)[:, None]
            - 2.0 * q64 @ blk64.T, 0.0))
    return 1.0 - (q64 @ blk64.T) / np.maximum(
        qn[:, None] * np.linalg.norm(blk64, axis=1)[None, :], 1e-300)


def brute_force(xs, qs, metric: str, keep: int, threads: int = 4):
    """f64 numpy brute force: per query the `keep` nearest (ids, dists),
    ascending. Blocks of queries go to a few threads (BLAS and the
    partition release the GIL); rows go by in blocks of 65,536."""
    nq = len(qs)
    qblock = min(256, max(32, -(-nq // threads)))
    out_i = np.zeros((nq, keep), np.int64)
    out_d = np.zeros((nq, keep), np.float64)
    step = 1 << 16
    blocks64 = [xs[s:s + step].astype(np.float64)
                for s in range(0, len(xs), step)] \
        if len(xs) * xs.shape[1] <= (1 << 26) else None

    def one(lo):
        q64 = qs[lo:lo + qblock].astype(np.float64)
        qn = np.linalg.norm(q64, axis=1)
        best_d = np.full((len(q64), 0), np.inf)
        best_i = np.zeros((len(q64), 0), np.int64)
        for b, s in enumerate(range(0, len(xs), step)):
            blk = blocks64[b] if blocks64 is not None \
                else xs[s:s + step].astype(np.float64)
            d = _distances(blk, q64, qn, metric)
            kk = min(keep, d.shape[1])
            part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
            best_d = np.concatenate(
                [best_d, np.take_along_axis(d, part, 1)], axis=1)
            best_i = np.concatenate([best_i, part + s], axis=1)
        order = np.argsort(best_d, axis=1, kind="stable")[:, :keep]
        out_d[lo:lo + qblock] = np.take_along_axis(best_d, order, 1)
        out_i[lo:lo + qblock] = np.take_along_axis(best_i, order, 1)

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, range(0, nq, qblock)))
    return out_i, out_d


def row_distances(xs, qs, rows, metric: str):
    """f64 distance of each query to each of ITS rows: rows is [nq, m]."""
    x = xs[rows].astype(np.float64)            # [nq, m, D]
    q = qs.astype(np.float64)[:, None, :]
    if metric == "euclidean":
        return np.linalg.norm(x - q, axis=2)
    return 1.0 - (x * q).sum(2) / np.maximum(
        np.linalg.norm(x, axis=2) * np.linalg.norm(q, axis=2), 1e-300)


# -- the control: the reference in the next precision down --------------------


def to_bf16(a):
    """f32 -> the nearest bfloat16 (ties to even), kept in f32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def control_answers(xs, qs, metric: str, k: int):
    """What the served path would return if the pass that decides its
    answers ran in bfloat16 (one MXU pass: bf16 inputs, f32 sums) and
    nothing re-scored them: ids ranked and distances reported from that
    arithmetic. [(rows, dists)] per query."""
    xb, qb = to_bf16(xs), to_bf16(qs)
    out = []
    step = 256
    xn2 = (xb * xb).sum(1)
    xn = np.sqrt(xn2)
    for lo in range(0, len(qb), step):
        q = qb[lo:lo + step]
        dots = q @ xb.T
        if metric == "euclidean":
            d = np.sqrt(np.maximum(
                xn2[None, :] + (q * q).sum(1)[:, None] - 2.0 * dots, 0.0))
        else:
            d = 1.0 - dots / np.maximum(
                np.linalg.norm(q, axis=1)[:, None] * xn[None, :], 1e-30)
        part = np.argpartition(d, k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d, part, 1)
        order = np.argsort(pd, axis=1, kind="stable")
        ids = np.take_along_axis(part, order, 1)
        ds_ = np.take_along_axis(pd, order, 1)
        out.extend((ids[i].tolist(), [float(v) for v in ds_[i]])
                   for i in range(len(q)))
    return out


# -- the comparison ----------------------------------------------------------


def num(value, limit, sense: str) -> dict:
    """One number that decides `correct`, beside its limit."""
    ok = value <= limit if sense == "<=" else value >= limit
    return {"value": value, "limit": limit, "sense": sense, "ok": bool(ok)}


def parse_answer(status: int, body: bytes, k: int):
    """(rows, dists) of one reply, or a string saying what is wrong
    with it: k distinct rows, finite distances in ascending order."""
    if status != 200:
        return f"status {status}: {body[:200]!r}"
    try:
        out = json.loads(body)
        if "error" in out:
            return f"rpc error: {str(out['error'])[:200]}"
        st = out["result"][0]
        if st["status"] != "OK":
            return f"statement failed: {str(st.get('result'))[:200]}"
        rows = [int(str(r["id"]).split(":", 1)[1]) for r in st["result"]]
        dists = [float(r["d"]) for r in st["result"]]
    except (ValueError, KeyError, IndexError, TypeError) as e:
        return f"unreadable reply: {e.__class__.__name__}: {e}"
    if len(rows) != k or len(set(rows)) != k:
        return f"{len(rows)} rows ({len(set(rows))} distinct), want {k}"
    if not all(np.isfinite(dists)) or dists != sorted(dists):
        return "distances not finite and ascending"
    return rows, dists


def pick(latencies, limit: int, seed: int):
    """Which of the window's answers are compared: all of them, or a
    sample drawn from the seed with the slowest request in it."""
    n = len(latencies)
    if n <= limit:
        return list(range(n))
    rng = np.random.default_rng([seed, 0xC0FFEE])
    chosen = set(rng.choice(n, limit - 1, replace=False).tolist())
    chosen.add(int(np.argmax(latencies)))
    return sorted(chosen)


def compare(dep: Deployment, answers, limits: dict, say) -> dict:
    """`answers` is [(pool index, (rows, dists) or an error string)] for
    the replies compared. Returns {name: num}: the numbers that decide
    `correct`. `say(text)` gets the first failing comparison of each
    kind, in words."""
    sz = dep.sz
    k, metric, n = sz["k"], sz["metric"], len(dep.xs)
    bad = [(i, a) for i, a in answers if isinstance(a, str)]
    good = [(i, a) for i, a in answers if not isinstance(a, str)]
    for i, a in good:
        if min(a[0]) < 0 or max(a[0]) >= n:
            bad.append((i, f"a row id outside the table: {a[0]}"))
    if bad:
        say(f"{len(bad)} of {len(answers)} answers are no answers; query "
            f"{bad[0][0]} of the pool: {bad[0][1]}")
    out = {"bad_answers": num(len(bad), 0, "<=")}
    outside = {i for i, _a in bad}
    good = [(i, a) for i, a in good if i not in outside]
    if not good:
        out["recall_at_10"] = num(0.0, limits["recall_at_10_min"], ">=")
        return out
    idx = np.array([i for i, _a in good])
    got_i = np.array([a[0] for _i, a in good], np.int64)
    got_d = np.array([a[1] for _i, a in good], np.float64)
    qs = dep.pool_q[idx]
    # every reported distance against the f64 distance of THAT row
    want = row_distances(dep.xs, qs, got_i, metric)
    err = np.abs(got_d - want) / (np.abs(want) + limits["dist_floor"])
    worst = np.unravel_index(int(np.argmax(err)), err.shape)
    out["dist_err_max"] = num(float(err[worst]), limits["dist_err_max"], "<=")
    if not out["dist_err_max"]["ok"]:
        say(f"query {idx[worst[0]]}: row {got_i[worst]} reported "
            f"{got_d[worst]!r}, its f64 distance is {want[worst]!r}: error "
            f"{err[worst]:.3g} of (distance + {limits['dist_floor']}), "
            f"limit {limits['dist_err_max']}")
    # ids against the f64 top k, ties at its last place counted
    ref_i, ref_d = brute_force(dep.xs, qs, metric, k)
    kth = ref_d[:, k - 1:k]
    hit = (got_i[:, :, None] == ref_i[:, None, :]).any(2) \
        | (want <= kth * (1 + 1e-9) + 1e-12)
    recall = float(hit.sum()) / hit.size
    out["recall_at_10"] = num(recall, limits["recall_at_10_min"], ">=")
    if not out["recall_at_10"]["ok"]:
        j = int(np.argmin(hit.sum(1)))
        say(f"recall@{k} {recall:.5f} < {limits['recall_at_10_min']}: "
            f"{int(hit.sum())} of {hit.size} ids belong to the f64 top "
            f"{k}; worst is query {idx[j]}: got {got_i[j].tolist()}, "
            f"reference {ref_i[j].tolist()}")
    if sz.get("sql_rows"):
        # an acknowledged write is read back: a query placed on an
        # inserted row returns that row
        on = dep.on_sql[idx]
        placed = dep.pool_rows[idx]
        lost = on & ~(got_i == placed[:, None]).any(1)
        if lost.any():
            j = int(np.argmax(lost))
            say(f"query {idx[j]} sits on SQL-inserted row {placed[j]}, "
                f"which did not come back: {got_i[j].tolist()}")
        out["readback_missing"] = num(int(lost.sum()), 0, "<=")
        out["readback_queries"] = num(int(on.sum()), 1, ">=")
    return out


def device_served(before: dict, after: dict, op: str, say) -> dict:
    """A window in which the device did not serve is a failed run: none
    of the supervisor's six counters may move, its state stays `ready`,
    and the cell's runner op has to advance."""
    sb, sa = before["supervisor"], after["supervisor"]
    moved = {c: sa[c] - sb[c] for c in COUNTERS if sa[c] != sb[c]}
    events = sum(abs(v) for v in moved.values()) + (sa["state"] != "ready")
    ops = after["runner"]["ops"].get(op, 0) \
        - before["runner"]["ops"].get(op, 0)
    if events:
        say(f"the device did not serve the whole window: supervisor "
            f"{sa['state']}, counters moved {moved}, last error "
            f"{sa.get('last_error')}")
    if ops < 1:
        say(f"runner op {op} did not advance in the window")
    return {"host_served_events": num(events, 0, "<="),
            "device_dispatches": num(ops, 1, ">=")}
