"""Kind `scan`: a vector table with NO index, ranked by a vector function in
the projection over `POST /rpc` (method `query`), the query vector bound as
`$q`: upstream's documented brute-force search and BASELINE config 3,

    SELECT id, vector::similarity::cosine(emb, $q) AS s FROM <tb>
        ORDER BY s DESC LIMIT 10

An answer is the exact top 10 by cosine similarity over every committed row,
each `s` the f64 cosine of the row it is reported for, in non-increasing `s`.

Everything a scan deployment needs besides its sizes (`configs/<name>.json`)
lives here: ingest (documents that hold the vector, by the KV bulk route),
the plain reference (f64 numpy cosine similarity over all rows in blocks),
the comparison that decides `correct`, and the control (the reference in
bfloat16). Rows, queries, `pick` and `num` are `kinds/knn.py`'s, imported and
not copied.

The reference imports nothing of the program. Only the load path
(`bulk_documents`, `runner_scans`) touches `surrealdb_tpu`.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_kind_knn_for_scan",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "knn.py"))
knn = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(knn)

NS, DB, HEADERS, PATH, COUNTERS = \
    knn.NS, knn.DB, knn.HEADERS, knn.PATH, knn.COUNTERS
SetupFailed = knn.SetupFailed
clustered_rows, queries_near, pick, num, sizes, vec_literal = \
    knn.clustered_rows, knn.queries_near, knn.pick, knn.num, knn.sizes, \
    knn.vec_literal


# -- the deployment's load path ----------------------------------------------


def runner_scans(sup) -> dict:
    """The runner's scan counters, or SetupFailed for a program whose
    runner keeps none: it has no exact column block, and would answer a
    whole window by scanning the table on the host a query. Fails here,
    at once, before any data is made."""
    scan = sup.runner_status().get("scan")
    if not isinstance(scan, dict) or "riders" not in scan:
        raise SetupFailed("the program's device runner reports no `scan` "
                          "counters: it has no exact column block")
    return scan


def bulk_documents(ds, table: str, xs, chunk: int = 20_000):
    """The KV bulk route for documents that hold their vector:
    `{id, emb: [dim floats]}`, row i under the integer id i. A record
    is put together from encoded pieces (the floats of a chunk turned
    into the wire's big-endian doubles by numpy at once), checked here
    against the program's own `serialize`."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu import wire
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    n, dim = xs.shape

    def doc(i, row=None):
        return serialize({"id": RecordId(table, i), "emb": xs[
            i if row is None else row].astype(np.float64).tolist()})

    def bodies(lo, hi):
        out = np.empty((hi - lo, dim, 9), np.uint8)
        out[:, :, 0] = 0xFB
        out[:, :, 1:] = xs[lo:hi].astype(">f8").view(np.uint8) \
            .reshape(hi - lo, dim, 8)
        return out.reshape(hi - lo, dim * 9)

    # the record's constant pieces, cut out of a real one (an id whose
    # encoding is five bytes that occur nowhere else in it)
    head, rest = doc(70001, 0).split(wire.encode(70001), 1)
    mid = rest[:len(rest) - dim * 9]
    rec = K.record_prefix(NS, DB, table)
    for i in (0, n // 2, n - 1):
        if head + wire.encode(i) + mid + bodies(i, i + 1)[0].tobytes() \
                != doc(i) or rec + K.enc_value(i) != K.record(
                    NS, DB, table, i):
            raise SetupFailed("the bulk route's records are not the "
                              "program's")
    enc_id, wire_id = K.enc_value, wire.encode
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        body = bodies(lo, hi)
        txn = ds.transaction(write=True)
        put = txn.set
        try:
            for i in range(lo, hi):
                put(rec + enc_id(i),
                    head + wire_id(i) + mid + body[i - lo].tobytes())
            txn.commit()
        except BaseException:
            txn.cancel()
            raise


def scan_sql(sz: dict) -> str:
    """The statement every request carries; the vector is bound as `$q`."""
    return sz["statement"]


def rpc_body(sz: dict, index: int, q) -> bytes:
    return json.dumps({"id": index, "method": "query",
                       "params": [scan_sql(sz), {"q": q.tolist()}]}).encode()


# -- the plain reference -----------------------------------------------------


def top_similar(xs, qs, keep: int, threads: int = 4):
    """f64 numpy cosine similarity of every query to every row, rows
    going by in blocks of 65,536: per query the `keep` most similar
    (row numbers, similarities), descending."""
    nq = len(qs)
    qblock = min(256, max(32, -(-nq // threads)))
    out_i = np.zeros((nq, keep), np.int64)
    out_s = np.zeros((nq, keep), np.float64)
    step = 1 << 16

    def one(lo):
        q64 = qs[lo:lo + qblock].astype(np.float64)
        q64 /= np.maximum(np.linalg.norm(q64, axis=1), 1e-300)[:, None]
        best_s = np.full((len(q64), 0), -np.inf)
        best_i = np.zeros((len(q64), 0), np.int64)
        for s in range(0, len(xs), step):
            blk = xs[s:s + step].astype(np.float64)
            sim = (q64 @ blk.T) / np.maximum(
                np.linalg.norm(blk, axis=1), 1e-300)[None, :]
            kk = min(keep, sim.shape[1])
            part = np.argpartition(-sim, kk - 1, axis=1)[:, :kk]
            best_s = np.concatenate(
                [best_s, np.take_along_axis(sim, part, 1)], axis=1)
            best_i = np.concatenate([best_i, part + s], axis=1)
        order = np.argsort(-best_s, axis=1, kind="stable")[:, :keep]
        out_s[lo:lo + qblock] = np.take_along_axis(best_s, order, 1)
        out_i[lo:lo + qblock] = np.take_along_axis(best_i, order, 1)

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, range(0, nq, qblock)))
    return out_i, out_s


def row_similarities(xs, qs, rows):
    """f64 cosine similarity of each query to each of ITS rows:
    rows is [nq, m]."""
    x = xs[rows].astype(np.float64)            # [nq, m, D]
    q = qs.astype(np.float64)[:, None, :]
    return (x * q).sum(2) / np.maximum(
        np.linalg.norm(x, axis=2) * np.linalg.norm(q, axis=2), 1e-300)


# -- the control: the reference in the next precision down --------------------


def control_answers(xs, qs, k: int):
    """What the served path would return if the pass that decides its
    answers ran in bfloat16 (one MXU pass: bf16 inputs, f32 sums) and
    nothing re-scored them: ids ranked and `s` reported from that
    arithmetic. [(rows, sims)] per query."""
    qb = knn.to_bf16(qs)
    qn = np.maximum(np.linalg.norm(qs, axis=1), 1e-30)[:, None]
    best_s = np.full((len(qb), 0), -np.inf, np.float32)
    best_i = np.zeros((len(qb), 0), np.int64)
    step = 1 << 16
    for s in range(0, len(xs), step):
        blk = xs[s:s + step]
        xb = knn.to_bf16(blk / np.maximum(
            np.linalg.norm(blk, axis=1), 1e-30)[:, None])
        sim = (qb @ xb.T) / qn
        kk = min(k, sim.shape[1])
        part = np.argpartition(-sim, kk - 1, axis=1)[:, :kk]
        best_s = np.concatenate(
            [best_s, np.take_along_axis(sim, part, 1)], axis=1)
        best_i = np.concatenate([best_i, part + s], axis=1)
    order = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
    ids = np.take_along_axis(best_i, order, 1)
    sims = np.take_along_axis(best_s, order, 1)
    return [(ids[i].tolist(), [float(v) for v in sims[i]])
            for i in range(len(qb))]


# -- the deployment -----------------------------------------------------------


class Deployment:
    """One loaded table and its seeded query pool."""

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
        (pool index, sent, received, status, reply). Every reply is
        parsed (`ok` says which are answers at all); the comparison with
        the reference takes all of them, or `compare_max` drawn from the
        seed."""
        k = self.sz["k"]
        parsed = [parse_answer(r[3], r[4], k) for r in records]
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        compared = compare(self, [(records[j][0], parsed[j]) for j in chosen],
                           limits, say)
        compared["answers_compared"] = num(len(chosen), 1, ">=")
        compared.update(device_served(before, after, self.op, len(records),
                                      say))
        return {"ok": [not isinstance(a, str) for a in parsed],
                "compared": compared,
                "metrics": {"recall_at_10": compared["recall_at_10"]["value"]}}

    def judge_control(self, records, limits, seed) -> dict:
        """The control's numbers on the queries that `judge` compared:
        the reference in bfloat16, put in the program's place."""
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        idx = [records[j][0] for j in chosen]
        answers = control_answers(self.xs, self.pool_q[idx], self.sz["k"])
        words = []
        out = compare(self, list(zip(idx, answers)), limits, words.append)
        out["correct"] = all(c["ok"] for c in out.values())
        out["first_failures"] = words
        return out


def setup(cfg: dict, seed: int, ds, http, rehearsal: bool,
          log) -> Deployment:
    """The runner probe, rows from the seed, DEFINE (no index), bulk
    ingest, the first scan (column build, ship, compile), the
    SQL-inserted tail and the scan that makes the program take it in
    (one version bump: one rebuild, one re-ship), and the query pool.
    `http.sql(text)` posts to the served `/sql`, `http.query(text, vars)`
    to `/rpc`. Every write is acknowledged before this returns, and the
    table's device block is as the window will find it."""
    from surrealdb_tpu.device import get_supervisor

    sz = sizes(cfg, rehearsal)
    n, dim, tb, k = sz["rows"], sz["dim"], sz["table"], sz["k"]
    n_sql = int(sz["sql_rows"])
    n_bulk = n - n_sql
    op = sz["runner_op"]
    timing = {}
    sup = get_supervisor()
    runner_scans(sup)

    def ask(q):
        rows = http.query(scan_sql(sz), {"q": q.tolist()})[0]
        return [int(str(r["id"]).split(":", 1)[1]) for r in rows]

    def state():
        st, rs = sup.status(), sup.runner_status()
        return {"ships": st.get("col_ships", 0),
                "riders": rs["scan"]["riders"],
                "ops": rs["ops"].get(op, 0),
                "routed": st["host_routed"] + st["fallbacks"]}

    t = time.monotonic()
    xs, rng = clustered_rows(n, dim, seed)
    timing["data_s"] = time.monotonic() - t
    http.sql(f"DEFINE TABLE {tb}")
    t = time.monotonic()
    bulk_documents(ds, tb, xs[:n_bulk])
    timing["ingest_s"] = time.monotonic() - t
    log(f"{n_bulk} documents by the bulk route in {timing['ingest_s']:.1f}s")
    # first scan: the column from the documents, its ship, the compile
    t = time.monotonic()
    s0 = state()
    first = ask(xs[0])
    s1 = state()
    if len(first) != k or first[0] != 0:
        raise SetupFailed(f"the first scan, placed on row 0, returned {first}")
    if s1["riders"] - s0["riders"] != 1 or s1["ops"] <= s0["ops"] \
            or s1["ships"] - s0["ships"] != 1 or s1["routed"] != s0["routed"]:
        raise SetupFailed(f"the first scan was not served from a shipped "
                          f"column block: {s0} -> {s1}")
    timing["first_scan_s"] = time.monotonic() - t
    log(f"first scan (column build, ship, compile) "
        f"{timing['first_scan_s']:.1f}s")
    # the last rows arrive as a client would send them
    t = time.monotonic()
    for s in range(n_bulk, n, 64):
        rows = ",".join("{id:%d,emb:%s}" % (i, vec_literal(xs[i]))
                        for i in range(s, min(s + 64, n)))
        http.sql(f"INSERT INTO {tb} [{rows}]")
    timing["sql_insert_s"] = time.monotonic() - t
    # the next scan takes them in: one rebuild, one re-ship; placed on
    # the last inserted row, it has to return that row first
    t = time.monotonic()
    if n_sql:
        again = ask(xs[n - 1])
        s2 = state()
        if not again or again[0] != n - 1:
            raise SetupFailed(f"the scan placed on SQL-inserted row {n - 1} "
                              f"returned {again}")
        if s2["ships"] - s1["ships"] != 1 or s2["routed"] != s1["routed"] \
                or s2["riders"] - s1["riders"] != 1:
            raise SetupFailed(f"after the INSERTs the scan was not served "
                              f"from one re-shipped block: {s1} -> {s2}")
    timing["reship_s"] = time.monotonic() - t
    # the ship's prewarm ladder runs behind the scans: wait until the
    # runner has compiled or loaded all of it
    t = time.monotonic()
    quiet, last = 0, None
    while quiet < 3:
        rs = sup.runner_status()
        now = (rs["cc"]["misses"], rs["ops"].get("vec_prewarm", 0))
        quiet = quiet + 1 if now == last else 0
        last = now
        time.sleep(0.3)
    timing["prewarm_wait_s"] = time.monotonic() - t
    log(f"{n_sql} SQL rows in {timing['sql_insert_s']:.1f}s, taken in in "
        f"{timing['reship_s']:.1f}s, prewarm quiet after "
        f"{timing['prewarm_wait_s']:.1f}s ({last[0]} first-shape "
        f"dispatches so far)")
    # the pool: a share of it on rows the INSERT acknowledged, shuffled so
    # that any stretch of it holds both kinds
    t = time.monotonic()
    pool = sz["pool"]
    n_on_sql = int(pool * sz["queries_on_sql_rows"]) if n_sql else 0
    near = np.concatenate([rng.integers(n_bulk, n, n_on_sql),
                           rng.integers(0, n_bulk, pool - n_on_sql)])
    qs = queries_near(xs, near, rng)
    order = rng.permutation(pool)
    timing["pool_s"] = time.monotonic() - t
    return Deployment(sz, xs, qs[order], near[order], order < n_on_sql,
                      timing)


# -- the comparison ----------------------------------------------------------


def parse_answer(status: int, body: bytes, k: int):
    """(rows, sims) of one reply, or a string saying what is wrong with
    it: k distinct rows with finite similarities."""
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
        sims = [float(r["s"]) for r in st["result"]]
    except (ValueError, KeyError, IndexError, TypeError) as e:
        return f"unreadable reply: {e.__class__.__name__}: {e}"
    if len(rows) != k or len(set(rows)) != k:
        return f"{len(rows)} rows ({len(set(rows))} distinct), want {k}"
    if not all(np.isfinite(sims)):
        return "similarities not finite"
    return rows, sims


def compare(dep: Deployment, answers, limits: dict, say) -> dict:
    """`answers` is [(pool index, (rows, sims) or an error string)] for
    the replies compared. Returns {name: num}: the numbers that decide
    `correct`. `say(text)` gets the first failing comparison of each
    kind, in words."""
    sz = dep.sz
    k, n = sz["k"], len(dep.xs)
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
    got_s = np.array([a[1] for _i, a in good], np.float64)
    qs = dep.pool_q[idx]
    # (b) every reported similarity against the f64 cosine of THAT row
    want = row_similarities(dep.xs, qs, got_i)
    err = np.abs(got_s - want)
    worst = np.unravel_index(int(np.argmax(err)), err.shape)
    out["score_err_max"] = num(float(err[worst]), limits["score_err_max"],
                               "<=")
    if not out["score_err_max"]["ok"]:
        say(f"query {idx[worst[0]]}: row {got_i[worst]} reported "
            f"{got_s[worst]!r}, its f64 cosine similarity is "
            f"{want[worst]!r}: error {err[worst]:.3g}, limit "
            f"{limits['score_err_max']}")
    # (c) rows in non-increasing similarity: the largest step upwards
    rise = np.diff(got_s, axis=1)
    at = np.unravel_index(int(np.argmax(rise)), rise.shape)
    out["order_rise_max"] = num(float(max(rise[at], 0.0)),
                                limits["order_slack"], "<=")
    if not out["order_rise_max"]["ok"]:
        say(f"query {idx[at[0]]}: place {at[1] + 1} reports "
            f"{got_s[at[0], at[1] + 1]!r} after {got_s[at]!r}")
    # (a) ids against the f64 top k, ties at its last place counted
    ref_i, ref_s = top_similar(dep.xs, qs, k)
    kth = ref_s[:, k - 1:k]
    hit = (got_i[:, :, None] == ref_i[:, None, :]).any(2) \
        | (want >= kth - 1e-12)
    recall = float(hit.sum()) / hit.size
    out["recall_at_10"] = num(recall, limits["recall_at_10_min"], ">=")
    if not out["recall_at_10"]["ok"]:
        j = int(np.argmin(hit.sum(1)))
        say(f"recall@{k} {recall:.5f} < {limits['recall_at_10_min']}: "
            f"{int(hit.sum())} of {hit.size} ids belong to the f64 top "
            f"{k}; worst is query {idx[j]}: got {got_i[j].tolist()}, "
            f"reference {ref_i[j].tolist()}")
    if sz["sql_rows"]:
        # (d) an acknowledged write is read back: a query placed on an
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


def device_served(before: dict, after: dict, op: str, requests: int,
                  say) -> dict:
    """A window in which the device did not serve is a failed run: none
    of the supervisor's six counters may move (`host_routed` is what a
    scan answered on the host counts as), its state stays `ready`, the
    cell's runner op has to advance, and the runner's scan riders are
    the window's requests, one for one."""
    out = knn.device_served(before, after, op, say)
    riders = (after["runner"].get("scan") or {}).get("riders", 0) \
        - (before["runner"].get("scan") or {}).get("riders", 0)
    if riders != requests:
        say(f"the runner's scans served {riders} riders in a window of "
            f"{requests} requests")
    out["scan_riders_off"] = num(abs(riders - requests), 0, "<=")
    return out
