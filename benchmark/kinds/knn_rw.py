"""Kind `knn_rw`: kind `knn`'s vector table and `<|k|>` statement under a
read-mostly write mix. Most of the pool's bodies are `knn`'s bound search,

    SELECT id, vector::distance::knn() AS d FROM <tb> WHERE emb <|10|> $q

and a share of them (`write_share`, YCSB B's 5 %) are one-row inserts of new
rows, `INSERT INTO <tb> {id: $id, emb: $v}` with both bound, each id once.
The store grows while it serves: set-up ends at the configuration's `rows`,
and warm-up and the window add to them.

What an answer may hold then depends on when it was asked, and the rule is
stated from the clients' own timestamps (one CLOCK_MONOTONIC for every
process of the machine). For a search sent at s and answered at r, and a row
whose INSERT was sent at b and acknowledged at a:

    must-see   a < s (acknowledged before the search was sent), or the
               INSERT was sent in warm-up (warm-up waits for every reply)
    may-see    b <= r and not must-see
    never      b > r, or the INSERT was never sent, or it failed

The reference (f64 numpy brute force, `kinds/knn.py`'s, imported and not
copied) runs over set-up rows + must-see rows + the may-see rows the answer
itself returned. Warm-up's requests are not in the window's records; which
INSERTs it sent follows from the generators' fixed walk: client c (thread
c // 4 of process c % 4, `loadgen.py`) sends the pool positions = c mod
`clients` in order from the start and never skips, so every position of its
share before its first window record was sent, and acknowledged, in warm-up.

The reference imports nothing of the program. Only set-up (`bulk_vectors`,
the probe) touches `surrealdb_tpu`.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_kind_knn_for_knn_rw",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "knn.py"))
knn = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(knn)

NS, DB, HEADERS, PATH = knn.NS, knn.DB, knn.HEADERS, knn.PATH
SetupFailed = knn.SetupFailed
num, sizes, knn_sql = knn.num, knn.sizes, knn.knn_sql

SEARCH, INSERT = 0, 1


def insert_sql(sz: dict) -> str:
    """The write every INSERT body carries; id and vector are bound."""
    return f"INSERT INTO {sz['table']} {{id: $id, emb: $v}}"


def rpc_body(index: int, text: str, variables: dict) -> bytes:
    return json.dumps({"id": index, "method": "query",
                       "params": [text, variables]}).encode()


# -- the pool ----------------------------------------------------------------


def lay_out_pool(pool: int, clients: int, writes: int, rng):
    """Which pool positions are INSERTs and which searches sit on their
    rows. Returns (insert positions ascending, {position: insert number}
    of the searches placed on an inserted row).

    An INSERT at position i is followed at i + clients, the same caller's
    next request, by a search placed on its row, and at a later position of
    another caller by one more. Positions are drawn from the seed, so any
    stretch of the pool holds the configuration's share of writes."""
    kind = np.zeros(pool, np.int8)          # 0 free, 1 INSERT, 2 read-back
    chosen = []
    for i in rng.permutation(pool - clients).tolist():
        if len(chosen) == writes:
            break
        if kind[i] or kind[i + clients]:
            continue
        kind[i], kind[i + clients] = 1, 2
        chosen.append(i)
    if len(chosen) < writes:
        raise SetupFailed(f"a pool of {pool} has no room for {writes} "
                          f"INSERTs with their read-backs")
    chosen.sort()
    on_row = {}
    for w, i in enumerate(chosen):
        on_row[i + clients] = w
        for _try in range(64):
            j = i + clients + 1 + int(rng.integers(16 * clients))
            if j < pool and not kind[j] and (j - i) % clients:
                kind[j] = 2
                on_row[j] = w
                break
    return chosen, on_row


class Deployment:
    """One loaded store, the rows the pool will insert, and the pool."""

    def __init__(self, sz, xs, ops, rows, pool_q, timing):
        self.sz = sz
        self.xs = xs                  # set-up rows, then the pool's inserts
        self.n0 = sz["rows"]          # rows acknowledged by set-up
        self.ops = ops                # [P] SEARCH or INSERT
        self.rows = rows              # [P] the row inserted / searched near
        self.pool_q = pool_q          # [P, D] f32 (an INSERT's: its row)
        self.timing = timing
        self.op = sz["runner_op"]
        self.clients = sz["clients"]

    def bodies(self):
        """The pool as request bodies, in pool order."""
        search, write = knn_sql(self.sz), insert_sql(self.sz)
        return [rpc_body(i, write, {"id": int(self.rows[i]),
                                    "v": self.pool_q[i].tolist()})
                if self.ops[i] == INSERT
                else rpc_body(i, search, {"q": self.pool_q[i].tolist()})
                for i in range(len(self.ops))]

    # -- the verdict ---------------------------------------------------------

    def parse(self, record):
        """(rows, dists) of a search's reply, True for an INSERT that
        created its row, or a string saying what is wrong."""
        index, _s, _r, status, body = record
        if self.ops[index] == SEARCH:
            return knn.parse_answer(status, body, self.sz["k"])
        return parse_insert(status, body, int(self.rows[index]))

    def chosen(self, records, limits, seed):
        """(limits as run, every reply parsed, the visibility of every
        INSERT, the records of the searches that are compared)."""
        limits = {**limits, **self.sz.get("limits_rehearsal", {})}
        parsed = [self.parse(r) for r in records]
        searches = [j for j, r in enumerate(records)
                    if self.ops[r[0]] == SEARCH]
        picked = knn.pick([records[j][2] - records[j][1] for j in searches],
                          limits["compare_max"], seed)
        return limits, parsed, Visibility(self, records, parsed), \
            [searches[j] for j in picked]

    def judge(self, records, before, after, limits, seed, say) -> dict:
        """The verdict on one window. `records` are the generators':
        (pool index, sent, received, status, reply). Every reply is parsed;
        every INSERT's is judged, and of the searches `compare_max` drawn
        from the seed (the slowest among them) are compared with the
        reference under the visibility rule."""
        limits, parsed, vis, chosen = self.chosen(records, limits, seed)
        compared = compare(self, vis, [(records[j], parsed[j])
                                       for j in chosen], limits, say)
        compared["answers_compared"] = num(len(chosen), 1, ">=")
        failed = [(records[j][0], a) for j, a in enumerate(parsed)
                  if self.ops[records[j][0]] == INSERT and a is not True]
        if failed:
            say(f"{len(failed)} INSERTs were not acknowledged with their "
                f"row; pool position {failed[0][0]}: {failed[0][1]}")
        compared["insert_failed"] = num(len(failed), 0, "<=")
        compared["inserts_acknowledged"] = num(len(vis.window_acks), 1, ">=")
        if vis.wrapped:
            say(f"{vis.wrapped} requests repeat or step back in their "
                f"caller's walk of the pool: it is too small for the run")
        compared["pool_wrapped"] = num(vis.wrapped, 0, "<=")
        compared.update(knn.device_served(before, after, self.op, say))
        compared.update(grew_in_place(self, vis, before, after, say))
        return {"ok": [not isinstance(a, str) for a in parsed],
                "compared": compared,
                "metrics": {"recall_at_10": compared["recall_at_10"]["value"]}}

    def judge_control(self, records, limits, seed) -> dict:
        """The control's numbers on the searches that `judge` compared:
        the reference in bfloat16 (`kinds/knn.py control_answers`' pass)
        over set-up and must-see rows, put in the program's place."""
        limits, _parsed, vis, chosen = self.chosen(records, limits, seed)
        answers = control_answers(self, vis, [records[j] for j in chosen])
        words = []
        out = compare(self, vis, list(zip([records[j] for j in chosen],
                                          answers)), limits, words.append)
        out["correct"] = all(c["ok"] for c in out.values())
        out["first_failures"] = words
        return out


def parse_insert(status: int, body: bytes, row: int):
    """True for a reply that acknowledges the created record with its id."""
    if status != 200:
        return f"status {status}: {body[:200]!r}"
    try:
        out = json.loads(body)
        if "error" in out:
            return f"rpc error: {str(out['error'])[:200]}"
        st = out["result"][0]
        if st["status"] != "OK":
            return f"statement failed: {str(st.get('result'))[:200]}"
        made = [int(str(r["id"]).split(":", 1)[1]) for r in st["result"]]
    except (ValueError, KeyError, IndexError, TypeError) as e:
        return f"unreadable reply: {e.__class__.__name__}: {e}"
    if made != [row]:
        return f"created {made}, want [{row}]"
    return True


# -- the visibility rule -----------------------------------------------------


class Visibility:
    """When each of the pool's INSERTs was sent and acknowledged, from the
    window's records and the generators' walk."""

    def __init__(self, dep: Deployment, records, parsed):
        clients = dep.clients
        n_w = len(dep.xs) - dep.n0
        self.n0 = dep.n0
        # per inserted row (by insert number): sent, acknowledged;
        # -inf = in warm-up, +inf = never (or refused)
        self.sent = np.full(n_w, np.inf)
        self.acked = np.full(n_w, np.inf)
        first = {}                      # client -> its first window position
        last = {}
        seen = set()
        # requests that repeat a position of the window, or step back in
        # their caller's walk (a share spent in warm-up and begun again)
        self.wrapped = 0
        for index, _s, _r, _st, _b in sorted(records, key=lambda r: r[1]):
            c = index % clients
            if index in seen or index < last.get(c, -1):
                self.wrapped += 1
            seen.add(index)
            last[c] = index
            first.setdefault(c, index)
        insert_at = np.flatnonzero(dep.ops == INSERT)
        number = {int(p): w for w, p in enumerate(insert_at)}
        self.warmup = 0
        for p in insert_at.tolist():
            if p < first.get(p % clients, 0):
                self.sent[number[p]] = self.acked[number[p]] = -np.inf
                self.warmup += 1
        self.window_acks = []
        for (index, sent, received, _st, _b), a in zip(records, parsed):
            if dep.ops[index] == INSERT and a is True:
                w = number[index]
                if self.sent[w] == np.inf:      # a wrapped repeat: the first
                    self.sent[w], self.acked[w] = sent, received
                    self.window_acks.append(received)

    def classes(self, sent: float, received: float):
        """(must-see, may-see) masks over the inserted rows for a search
        sent and answered then."""
        must = self.acked < sent
        return must, (self.sent <= received) & ~must


def to_row(record, a):
    """A compared search: (pool index, sent, received, rows, dists)."""
    return record[0], record[1], record[2], a[0], a[1]


def compare(dep: Deployment, vis: Visibility, answers, limits: dict,
            say) -> dict:
    """`answers` is [(record, (rows, dists) or an error string)] for the
    searches compared. Returns {name: num}: the numbers that decide
    `correct`; `say(text)` gets the first failing comparison of each kind."""
    sz = dep.sz
    k, metric, n0, n_all = sz["k"], sz["metric"], dep.n0, len(dep.xs)
    bad = [(r[0], a) for r, a in answers if isinstance(a, str)]
    good = [to_row(r, a) for r, a in answers if not isinstance(a, str)]
    if bad:
        say(f"{len(bad)} of {len(answers)} answers are no answers; query "
            f"{bad[0][0]} of the pool: {bad[0][1]}")
    out = {"bad_answers": num(len(bad), 0, "<=")}
    if not good:
        out["recall_at_10"] = num(0.0, limits["recall_at_10_min"], ">=")
        return out
    idx = np.array([g[0] for g in good])
    got_i = np.array([g[3] for g in good], np.int64)
    got_d = np.array([g[4] for g in good], np.float64)
    qs = dep.pool_q[idx]
    # a row no search may hold: outside the table, or inserted and neither
    # must-see nor may-see for THAT search
    phantom = (got_i < 0) | (got_i >= n_all)
    must_of, may_of = [], []
    for j, g in enumerate(good):
        must, may = vis.classes(g[1], g[2])
        must_of.append(must)
        may_of.append(may)
        w = got_i[j] - n0
        new = (w >= 0) & (w < len(must))
        phantom[j] |= new & ~(must | may)[np.clip(w, 0, len(must) - 1)]
    if phantom.any():
        j = int(np.argmax(phantom.any(1)))
        say(f"query {idx[j]} sent {good[j][1]:.4f} answered "
            f"{good[j][2]:.4f} holds rows nobody had sent by then, or "
            f"nobody inserted: {got_i[j][phantom[j]].tolist()}")
    out["phantom_rows"] = num(int(phantom.sum()), 0, "<=")
    safe_i = np.where(phantom, 0, got_i)
    # every reported distance against the f64 distance of THAT row
    want = knn.row_distances(dep.xs, qs, safe_i, metric)
    err = np.where(phantom, 0.0, np.abs(got_d - want)
                   / (np.abs(want) + limits["dist_floor"]))
    worst = np.unravel_index(int(np.argmax(err)), err.shape)
    out["dist_err_max"] = num(float(err[worst]), limits["dist_err_max"], "<=")
    if not out["dist_err_max"]["ok"]:
        say(f"query {idx[worst[0]]}: row {got_i[worst]} reported "
            f"{got_d[worst]!r}, its f64 distance is {want[worst]!r}: error "
            f"{err[worst]:.3g} of (distance + {limits['dist_floor']}), "
            f"limit {limits['dist_err_max']}")
    # ids against the f64 top k over set-up rows, must-see rows and the
    # may-see rows the answer itself holds; ties at its last place count
    base_i, base_d = knn.brute_force(dep.xs[:n0], qs, metric, k)
    new_d = knn._distances(dep.xs[n0:].astype(np.float64),
                           qs.astype(np.float64),
                           np.linalg.norm(qs.astype(np.float64), axis=1),
                           metric) if n_all > n0 \
        else np.zeros((len(qs), 0))
    hits = total = 0
    lost = readbacks = 0
    worst_q = None
    for j in range(len(good)):
        returned = np.zeros(n_all - n0, bool)
        w = got_i[j] - n0
        returned[w[(w >= 0) & (w < n_all - n0)]] = True
        seen = np.flatnonzero(must_of[j] | (may_of[j] & returned))
        cand_i = np.concatenate([base_i[j], seen + n0])
        cand_d = np.concatenate([base_d[j], new_d[j, seen]])
        order = np.argsort(cand_d, kind="stable")[:k]
        ref_i, kth = cand_i[order], cand_d[order[-1]]
        hit = np.isin(got_i[j], ref_i) \
            | ((want[j] <= kth * (1 + 1e-9) + 1e-12) & ~phantom[j])
        hits += int(hit.sum())
        total += k
        if worst_q is None or hit.sum() < worst_q[0]:
            worst_q = (int(hit.sum()), idx[j], got_i[j].tolist(),
                       ref_i.tolist())
        # an acknowledged write is read back: a search placed on a
        # must-see inserted row returns it
        placed = int(dep.rows[idx[j]]) - n0
        if placed >= 0 and must_of[j][placed]:
            readbacks += 1
            if placed + n0 not in got_i[j]:
                lost += 1
                if lost == 1:
                    say(f"query {idx[j]} sent {good[j][1]:.4f} sits on "
                        f"row {placed + n0}, whose INSERT was acknowledged "
                        f"{vis.acked[placed]:.4f}; it did not come back: "
                        f"{got_i[j].tolist()}")
    recall = hits / total
    out["recall_at_10"] = num(recall, limits["recall_at_10_min"], ">=")
    if not out["recall_at_10"]["ok"]:
        say(f"recall@{k} {recall:.5f} < {limits['recall_at_10_min']}: {hits} "
            f"of {total} ids belong to the f64 top {k} of what their search "
            f"could see; worst is query {worst_q[1]}: got {worst_q[2]}, "
            f"reference {worst_q[3]}")
    out["readback_missing"] = num(lost, 0, "<=")
    out["readback_queries"] = num(readbacks, limits["readback_queries_min"],
                                  ">=")
    return out


def grew_in_place(dep, vis, before, after, say) -> dict:
    """The store grew in the window without a re-ship: the supervisor's
    `vec_append_rows` moved, by no more than the INSERTs acknowledged; no
    whole `vec_load`; and the rows the chip held at the window's start are
    what the walk says warm-up inserted (the last of them may still wait
    for the next search: at most one a caller)."""
    sb, sa = before["supervisor"], after["supervisor"]
    appended = sa["vec_append_rows"] - sb["vec_append_rows"]
    ships = sa["vec_full_ships"] - sb["vec_full_ships"]
    blocks = (before["runner"] or {}).get("vec") or {}
    held = max((b["rows"] for b in blocks.values()), default=0)
    want = dep.n0 + vis.warmup
    off = max(0, held - want) + max(0, want - dep.clients - held)
    waiting = max(0, want - held)
    over = max(0, appended - len(vis.window_acks) - waiting)
    if off:
        say(f"the chip held {held} rows at the window's start; the walk "
            f"says warm-up left {want} (set-up {dep.n0} + {vis.warmup})")
    if ships:
        say(f"{ships} whole vec_load(s) inside the window: the block was "
            f"shipped again")
    if appended < 1 or over:
        say(f"vec_append wrote {appended} rows in the window; "
            f"{len(vis.window_acks)} INSERTs were acknowledged in it and "
            f"{waiting} waited from warm-up")
    return {"appended_rows": num(appended, 1, ">="),
            "appended_rows_over": num(over, 0, "<="),
            "full_ships": num(ships, 0, "<="),
            "warmup_rows_off": num(off, 0, "<=")}


# -- the control: the reference in the next precision down --------------------


def control_answers(dep: Deployment, vis: Visibility, records):
    """What the served path would return if the pass that decides its
    answers ran in bfloat16 and nothing re-scored them, over set-up rows
    and the must-see rows of each search. [(rows, dists)] a search."""
    sz = dep.sz
    k, metric, n0 = sz["k"], sz["metric"], dep.n0
    idx = [r[0] for r in records]
    qs = dep.pool_q[idx]
    base = knn.control_answers(dep.xs[:n0], qs, metric, k)
    xb, qb = knn.to_bf16(dep.xs[n0:]), knn.to_bf16(qs)
    dots = qb @ xb.T
    if metric == "euclidean":
        d = np.sqrt(np.maximum((xb * xb).sum(1)[None, :]
                               + (qb * qb).sum(1)[:, None] - 2.0 * dots, 0.0))
    else:
        d = 1.0 - dots / np.maximum(
            np.linalg.norm(qb, axis=1)[:, None]
            * np.linalg.norm(xb, axis=1)[None, :], 1e-30)
    out = []
    for j, r in enumerate(records):
        must, _may = vis.classes(r[1], r[2])
        seen = np.flatnonzero(must)
        cand_i = np.concatenate([np.asarray(base[j][0], np.int64), seen + n0])
        cand_d = np.concatenate([np.asarray(base[j][1], np.float64),
                                 d[j, seen].astype(np.float64)])
        order = np.argsort(cand_d, kind="stable")[:k]
        out.append((cand_i[order].tolist(),
                    [float(v) for v in cand_d[order]]))
    return out


# -- set-up ------------------------------------------------------------------


def setup(cfg: dict, seed: int, ds, http, rehearsal: bool,
          log) -> Deployment:
    """The probe, then `knn`'s set-up at this configuration's sizes (rows
    from the seed, DEFINE, bulk ingest, first search, the SQL-inserted
    tail and the search that takes it in), then the pool. Every write is
    acknowledged before this returns."""
    from surrealdb_tpu.device import get_supervisor

    sup = get_supervisor()
    if "vec_full_ships" not in sup.status():
        # a program whose resident block cannot grow ships the whole store
        # again and compiles a new program for every INSERT: it does not
        # serve this mix. Fails here, at once, before any data is made.
        raise SetupFailed("the program's supervisor reports no "
                          "`vec_full_ships`: its resident vector block does "
                          "not grow in place (no vec_append)")
    sz = sizes(cfg, rehearsal)
    n, dim, tb = sz["rows"], sz["dim"], sz["table"]
    n_sql = int(sz["sql_rows"])
    n_bulk = n - n_sql
    pool, clients = sz["pool"], sz["clients"]
    writes = int(pool * sz["write_share"])
    timing = {}
    t = time.monotonic()
    xs, rng = knn.clustered_rows(n + writes, dim, seed)
    timing["data_s"] = time.monotonic() - t
    http.sql(f"DEFINE TABLE {tb}; DEFINE INDEX ix ON {tb} FIELDS emb "
             f"{sz['index']} DIMENSION {dim} DIST {sz['metric'].upper()} "
             f"TYPE F32")
    t = time.monotonic()
    knn.bulk_vectors(ds, tb, "ix", xs[:n_bulk])
    timing["ingest_s"] = time.monotonic() - t
    log(f"{n_bulk} rows by the bulk route in {timing['ingest_s']:.1f}s")

    def ask(q):
        rows = http.query(knn_sql(sz), {"q": q.tolist()})[0]
        return [int(str(r["id"]).split(":", 1)[1]) for r in rows]

    # first search: rebuild from the `he` keys, ship, compile
    t = time.monotonic()
    ask(xs[0])
    timing["first_search_s"] = time.monotonic() - t
    log(f"first search (index sync, ship) {timing['first_search_s']:.1f}s")
    # the last rows arrive as a client would send them
    t = time.monotonic()
    for s in range(n_bulk, n, 256):
        rows = ",".join("{id:%d,emb:%s}" % (i, knn.vec_literal(xs[i]))
                        for i in range(s, min(s + 256, n)))
        http.sql(f"INSERT INTO {tb} [{rows}]")
    timing["sql_insert_s"] = time.monotonic() - t
    # the next search takes them in, by a delta: the block stays
    t = time.monotonic()
    ships = sup.status()["vec_full_ships"]
    again = ask(xs[n - 1])
    if again[0] != n - 1:
        raise SetupFailed(f"the search placed on SQL-inserted row {n - 1} "
                          f"returned {again}")
    if sup.status()["vec_full_ships"] != ships:
        raise SetupFailed("the SQL-inserted rows cost a whole re-ship: the "
                          "resident block did not grow in place")
    timing["take_in_s"] = time.monotonic() - t
    # the pool: the INSERTs with their read-backs, then `knn`'s searches,
    # half on the SQL-inserted rows and half on bulk rows
    t = time.monotonic()
    insert_at, on_row = lay_out_pool(pool, clients, writes, rng)
    ops = np.zeros(pool, np.int8)
    ops[insert_at] = INSERT
    rows = np.where(rng.random(pool) < sz["queries_on_sql_rows"],
                    rng.integers(n_bulk, n, pool),
                    rng.integers(0, n_bulk, pool))
    rows[insert_at] = n + np.arange(writes)
    for j, w in on_row.items():
        rows[j] = n + w
    pool_q = knn.queries_near(xs, rows, rng)
    pool_q[insert_at] = xs[n:]
    timing["pool_s"] = time.monotonic() - t
    return Deployment(sz, xs, ops, rows, pool_q, timing)
