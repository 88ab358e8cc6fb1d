"""Kind `graph`: a RELATE graph (`person` nodes, `knows TYPE RELATION` edges)
traversed by a folded `->knows->person` chain from one bound start record,
over `POST /rpc` (method `query`), as BASELINE config 4 and upstream's
`->edge->node` idiom spell it:

    SELECT VALUE ->knows->person->knows->person->knows->person
        FROM type::record('person', $i)

(JSON RPC carries no record id, so the start is built from the bound `$i`;
the statement's text is the same for every request and the AST cache serves
it.) An answer is a BAG: one id a path, duplicates kept, in scan order.

Everything a graph deployment needs besides its sizes (`configs/<name>.json`)
lives here: edges from the seed, ingest, the plain reference (adjacency as
Python lists, walked by plain loops), the comparison that decides `correct`,
and the control (the SET answer, what a dense-mask kernel would return).
`bulk_graph` writes `chip_smoke.py bulk_graph`'s keys (PR 21), copied so that
a later change to the smoke cannot move the yardstick.

The reference imports nothing of the program. Only the load path
(`bulk_graph`, `runner_knows`) touches `surrealdb_tpu`.
"""

from __future__ import annotations

import json
import time
from collections import Counter

import numpy as np

NS = DB = "bench"
HEADERS = {"surreal-ns": NS, "surreal-db": DB, "Accept": "application/json",
           "Content-Type": "application/json"}
PATH = "/rpc"       # where the window's requests go
COUNTERS = ("fallbacks", "host_routed", "restarts", "dispatch_timeouts",
            "dispatch_errors", "oom_refusals")
NODE_TB, EDGE_TB = "person", "knows"
SCAN_CHECKS = 8     # start nodes whose `~`-scan order set-up compares


class SetupFailed(Exception):
    pass


def sizes(cfg: dict, rehearsal: bool) -> dict:
    """The configuration as it is run: the file's sizes, or its
    `rehearsal` block laid over them for the CPU tests."""
    out = {k: v for k, v in cfg.items() if k != "rehearsal"}
    if rehearsal:
        out.update(cfg.get("rehearsal", {}))
    return out


# -- data from the seed ------------------------------------------------------


def edges_from(seed: int, nodes: int, edges: int):
    """PR 21's generator: both ends uniform over the nodes, so
    out-degrees are Poisson around edges / nodes and some nodes have
    none; self-loops and parallel edges happen and stay."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nodes, edges)
    dst = rng.integers(0, nodes, edges)
    return src, dst, rng


# -- the deployment's load path ----------------------------------------------


def runner_knows(op: str) -> bool:
    """Whether the program's device runner has the cell's op at all: a
    program from before the op fails the cell here, at once, instead of
    answering a whole window from its host walk."""
    from surrealdb_tpu.device import get_supervisor

    try:
        get_supervisor().call(op, {"key": "bench/none", "tag": [0]})
    except Exception as e:  # the supervisor's error types differ by mode
        if "unknown device op" in str(e):
            return False
        raise
    return True


def bulk_graph(ds, n_nodes: int, src, dst, chunk: int = 100_000):
    """The KV bulk route for a RELATE graph (`chip_smoke.py bulk_graph`):
    node records, edge records and the four `~` graph keys per edge, edge
    e with the integer id e. The keys and the edge record are put
    together from encoded pieces (a node's id encoded once, not ten
    times), checked here against the program's own `key.graph` and
    `serialize`."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu import wire
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    def doc(e, s, d):
        return serialize({"id": RecordId(EDGE_TB, e),
                          "in": RecordId(NODE_TB, s),
                          "out": RecordId(NODE_TB, d)})

    # node ids are used ten times each: encoded once; an edge id once
    enc = [K.enc_value(i) for i in range(n_nodes)]
    wir = [wire.encode(i) for i in range(n_nodes)]
    enc_id, wire_id = K.enc_value, wire.encode
    node_pre, edge_pre = (K.graph_tb_prefix(NS, DB, tb)
                          for tb in (NODE_TB, EDGE_TB))
    out_e, in_e = (d + K.enc_str(EDGE_TB) for d in (K.DIR_OUT, K.DIR_IN))
    out_n, in_n = (d + K.enc_str(NODE_TB) for d in (K.DIR_OUT, K.DIR_IN))
    rec_n, rec_e = (K.record_prefix(NS, DB, tb) for tb in (NODE_TB, EDGE_TB))
    # the edge record's constant pieces, cut out of a real one
    # (ids whose encodings are five bytes that occur nowhere else in it)
    a, b, c = 70001, 70002, 70003
    head, rest = doc(a, b, c).split(wire_id(a), 1)
    mid1, rest = rest.split(wire_id(b), 1)
    mid2, tail = rest.split(wire_id(c), 1)
    for e, s, d in ((0, 1, 2), (len(src), n_nodes - 1, n_nodes // 3)):
        if head + wire_id(e) + mid1 + wir[s] + mid2 + wir[d] + tail \
                != doc(e, s, d) or tail \
                or node_pre + enc[s] + out_e + enc_id(e) != K.graph(
                    NS, DB, NODE_TB, s, K.DIR_OUT, EDGE_TB, e) \
                or edge_pre + enc_id(e) + in_n + enc[s] != K.graph(
                    NS, DB, EDGE_TB, e, K.DIR_IN, NODE_TB, s) \
                or rec_e + enc_id(e) != K.record(NS, DB, EDGE_TB, e):
            raise SetupFailed("the bulk route's keys are not the program's")
    txn = ds.transaction(write=True)
    try:
        for i in range(n_nodes):
            txn.set(rec_n + enc[i], serialize({"id": RecordId(NODE_TB, i)}))
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    sl, dl = src.tolist(), dst.tolist()
    for lo in range(0, len(sl), chunk):
        txn = ds.transaction(write=True)
        put = txn.set
        try:
            for e in range(lo, min(lo + chunk, len(sl))):
                s, d = sl[e], dl[e]
                ee, es, ed = enc_id(e), enc[s], enc[d]
                put(rec_e + ee,
                    head + wire_id(e) + mid1 + wir[s] + mid2 + wir[d])
                put(node_pre + es + out_e + ee, b"")
                put(edge_pre + ee + in_n + es, b"")
                put(edge_pre + ee + out_n + ed, b"")
                put(node_pre + ed + in_e + ee, b"")
            txn.commit()
        except BaseException:
            txn.cancel()
            raise


def hop_sql(sz: dict, hops=None) -> str:
    """The statement every request carries; the start is bound as `$i`."""
    chain = f"->{EDGE_TB}->{NODE_TB}" * (sz["hops"] if hops is None else hops)
    return f"SELECT VALUE {chain} FROM type::record('{NODE_TB}', $i)"


def rpc_body(sz: dict, index: int, start: int) -> bytes:
    return json.dumps({"id": index, "method": "query",
                       "params": [hop_sql(sz), {"i": int(start)}]}).encode()


# -- the plain reference -----------------------------------------------------


class Reference:
    """The graph as the clients were told it is: per source the
    destinations of its bulk edges in ascending edge id (their `~` scan
    order, checked in set-up), then those of the acknowledged SQL edges
    (their ids are the server's, so their place in the order is not
    known here: only the bag is)."""

    def __init__(self, n_nodes: int, src, dst):
        adj = [[] for _ in range(n_nodes)]
        for s, d in zip(src.tolist(), dst.tolist()):
            adj[s].append(d)
        self.adj = adj
        self.sql_adj = {}             # source -> destinations by SQL edges

    def add_sql(self, edges):
        for s, d in edges:
            self.adj[s].append(d)
            self.sql_adj.setdefault(s, []).append(d)

    def walk(self, start: int, hops: int):
        """(ids of the last level, one a path; whether every edge on
        the paths is a bulk edge, so that the order is known too)."""
        level, ordered = [start], True
        for _ in range(hops):
            nxt = []
            for v in level:
                if v in self.sql_adj:
                    ordered = False
                nxt.extend(self.adj[v])
            level = nxt
        return level, ordered


def control_answer(ref_ids):
    """The SET answer: the reached nodes, each once, ascending: what a
    dense-mask hop returns and what a bag must not be mistaken for."""
    return sorted(set(ref_ids))


# -- the deployment -----------------------------------------------------------


class Deployment:
    """One loaded graph and its seeded pool of start nodes."""

    def __init__(self, sz, ref, pool, timing):
        self.sz = sz
        self.ref = ref                # the plain reference, SQL edges in
        self.pool = pool              # [P] start node of query i
        self.timing = timing
        self.op = sz["runner_op"]

    def bodies(self):
        """The pool as request bodies, in pool order."""
        return [rpc_body(self.sz, i, s) for i, s in enumerate(self.pool)]

    def judge(self, records, before, after, limits, seed, say) -> dict:
        """The verdict on one window. `records` are the generators':
        (pool index, sent, received, status, reply). Every reply is
        parsed (`ok` says which are answers at all); the comparison with
        the reference takes all of them, or `compare_max` drawn from the
        seed."""
        parsed = [parse_answer(r[3], r[4]) for r in records]
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
        """The control's numbers on the queries that `judge` compared:
        each answer deduplicated, put in the program's place."""
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        idx = [records[j][0] for j in chosen]
        answers = [control_answer(self.ref.walk(
            int(self.pool[i]), self.sz["hops"])[0]) for i in idx]
        words = []
        out = compare(self, list(zip(idx, answers)), limits, words.append)
        out["correct"] = all(c["ok"] for c in out.values())
        out["first_failures"] = words
        return out


def busy_start(src, dst, n_nodes: int, taken) -> int:
    """A start node whose second level holds 64 nodes or more: the
    frontier at which the program builds (or replays) its CSR."""
    deg = np.bincount(src, minlength=n_nodes)
    reach = np.bincount(src, weights=deg[dst], minlength=n_nodes)
    for v in np.flatnonzero(reach >= 64):
        if int(v) not in taken:
            return int(v)
    raise SetupFailed("no start node reaches 64 nodes in two hops")


def setup(cfg: dict, seed: int, ds, http, rehearsal: bool,
          log) -> Deployment:
    """Edges from the seed, DEFINE, bulk ingest, the scan-order check, the
    first traversal (CSR build, ship, compile), the SQL-related tail and
    the traversal that makes the program take it in, and the pool.
    `http.sql(text)` posts to the served `/sql`, `http.query(text, vars)`
    to `/rpc`. Every write is acknowledged before this returns, and the
    graph's device block is as the window will find it."""
    from surrealdb_tpu.device import get_supervisor

    sz = sizes(cfg, rehearsal)
    n, n_edges, hops = sz["nodes"], sz["edges"], sz["hops"]
    n_sql = int(sz["sql_edges"])
    n_bulk = n_edges - n_sql
    op = sz["runner_op"]
    timing = {}
    if not runner_knows(op):
        raise SetupFailed(f"the program's device runner has no op {op!r}")
    sup = get_supervisor()
    t = time.monotonic()
    src, dst, rng = edges_from(seed, n, n_edges)
    ref = Reference(n, src[:n_bulk], dst[:n_bulk])
    timing["data_s"] = time.monotonic() - t
    http.sql(f"DEFINE TABLE {NODE_TB}; DEFINE TABLE {EDGE_TB} TYPE RELATION")
    t = time.monotonic()
    bulk_graph(ds, n, src[:n_bulk], dst[:n_bulk])
    timing["ingest_s"] = time.monotonic() - t
    log(f"{n} nodes and {n_bulk} edges by the bulk route in "
        f"{timing['ingest_s']:.1f}s")

    def ask(start: int, n_hops: int):
        rows = http.query(hop_sql(sz, n_hops), {"i": int(start)})[0]
        return [int(r.split(":", 1)[1]) for r in rows[0]] if rows else None

    def served() -> int:
        return sup.runner_status()["ops"].get(op, 0)

    # ascending edge id IS the `~`-key scan order: two-hop chains from
    # lone sources find the CSR cold and are answered by the per-record
    # scans (the program's rule: no build for fewer than 64 sources)
    t = time.monotonic()
    before = served()
    for s in rng.integers(0, n, SCAN_CHECKS).tolist():
        got, want = ask(s, 2), ref.walk(s, 2)[0]
        if got != want:
            raise SetupFailed(
                f"the `~`-key scans do not return a source's edges in "
                f"ascending edge id: 2 hops from {s} gave {got[:8]}..., "
                f"the reference {want[:8]}...")
    if served() != before:
        raise SetupFailed("the scan-order check was not answered by the "
                          "per-record scans: the device op advanced")
    timing["scan_check_s"] = time.monotonic() - t
    # first traversal: its third pair meets 64 sources or more, builds
    # the CSR from the `~` keys, ships it and compiles
    t = time.monotonic()
    first = busy_start(src[:n_bulk], dst[:n_bulk], n, ())
    if ask(first, hops) != ref.walk(first, hops)[0]:
        raise SetupFailed(f"the first traversal (from {first}) is wrong")
    if ask(first, hops) != ref.walk(first, hops)[0] or served() <= before:
        raise SetupFailed(f"the second traversal (from {first}) is wrong, "
                          f"or {op} did not serve it")
    timing["first_traversal_s"] = time.monotonic() - t
    log(f"first traversal (CSR build, ship, compile) "
        f"{timing['first_traversal_s']:.1f}s")
    # the last edges arrive as a client would send them
    t = time.monotonic()
    tail = list(zip(src[n_bulk:].tolist(), dst[n_bulk:].tolist()))
    for lo in range(0, n_sql, 64):
        http.sql(";".join(
            f"RELATE {NODE_TB}:{a}->{EDGE_TB}->{NODE_TB}:{b}"
            for a, b in tail[lo:lo + 64]))
    timing["sql_relate_s"] = time.monotonic() - t
    # the program takes them in at the next frontier of 64: replay,
    # re-ship. Then a chain from an SQL edge's source has to hold every
    # path through that edge, from the device
    t = time.monotonic()
    ref.add_sql(tail)
    again = busy_start(src, dst, n, {first})
    before = served()
    for s in (again, tail[0][0], tail[-1][0]):
        got, want = ask(s, hops), ref.walk(s, hops)[0]
        if Counter(got) != Counter(want):
            raise SetupFailed(
                f"after the RELATEs a traversal from {s} returns "
                f"{len(got)} ids, the reference {len(want)}")
    if served() - before < 3:
        raise SetupFailed(f"after the RELATEs {op} served "
                          f"{served() - before} of 3 traversals")
    timing["reship_s"] = time.monotonic() - t
    # the ship's prewarm ladder runs behind the traversals: wait until
    # the runner has compiled or loaded all of it
    t = time.monotonic()
    quiet, last = 0, None
    while quiet < 3:
        rs = sup.runner_status()
        now = (rs["cc"]["misses"], rs["ops"].get("csr_prewarm", 0))
        quiet = quiet + 1 if now == last else 0
        last = now
        time.sleep(0.3)
    timing["prewarm_wait_s"] = time.monotonic() - t
    log(f"{n_sql} SQL edges in {timing['sql_relate_s']:.1f}s, taken in "
        f"in {timing['reship_s']:.1f}s, prewarm quiet after "
        f"{timing['prewarm_wait_s']:.1f}s ({last[0]} first-shape "
        f"dispatches so far)")
    # the pool: a share of it starts at an SQL edge's source (drawn from
    # those sources, so they repeat), the rest at distinct other nodes;
    # shuffled so that any stretch of it holds both kinds
    t = time.monotonic()
    pool = sz["pool"]
    n_on = int(pool * sz["queries_on_sql_edges"]) if n_sql else 0
    sources = np.asarray([a for a, _b in tail])
    starts = np.concatenate([
        sources[rng.integers(0, len(sources), n_on)] if n_on
        else np.zeros(0, np.int64),
        rng.choice(n, pool - n_on, replace=pool - n_on > n)])
    order = rng.permutation(pool)
    timing["pool_s"] = time.monotonic() - t
    return Deployment(sz, ref, starts[order], timing)


# -- the comparison ----------------------------------------------------------


def num(value, limit, sense: str) -> dict:
    """One number that decides `correct`, beside its limit."""
    ok = value <= limit if sense == "<=" else value >= limit
    return {"value": value, "limit": limit, "sense": sense, "ok": bool(ok)}


def parse_answer(status: int, body: bytes):
    """The node ids of one reply, in its order, or a string saying what
    is wrong with it: one row, a list of `person:<int>` ids."""
    if status != 200:
        return f"status {status}: {body[:200]!r}"
    try:
        out = json.loads(body)
        if "error" in out:
            return f"rpc error: {str(out['error'])[:200]}"
        st = out["result"][0]
        if st["status"] != "OK":
            return f"statement failed: {str(st.get('result'))[:200]}"
        (row,) = st["result"]
        pre = NODE_TB + ":"
        if not all(r.startswith(pre) for r in row):
            return "an id of another table"
        return [int(r[len(pre):]) for r in row]
    except (ValueError, KeyError, IndexError, TypeError,
            AttributeError) as e:
        return f"unreadable reply: {e.__class__.__name__}: {e}"


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
    """`answers` is [(pool index, ids or an error string)] for the
    replies compared. Returns {name: num}: the numbers that decide
    `correct`. `say(text)` gets the first failing comparison of each
    kind, in words."""
    ref, hops, n = dep.ref, dep.sz["hops"], dep.sz["nodes"]
    bad = [(i, a) for i, a in answers if isinstance(a, str)]
    good = [(i, a) for i, a in answers if not isinstance(a, str)]
    for i, a in good:
        if a and (min(a) < 0 or max(a) >= n):
            bad.append((i, f"an id outside the table: {max(a)}"))
    if bad:
        say(f"{len(bad)} of {len(answers)} answers are no answers; query "
            f"{bad[0][0]} of the pool: {bad[0][1]}")
    outside = {i for i, _a in bad}
    bag_bad = order_bad = ordered_seen = held = wanted = 0
    lost = on_sql = 0
    for i, got in good:
        if i in outside:
            continue
        start = int(dep.pool[i])
        want, ordered = ref.walk(start, hops)
        have, need = Counter(got), Counter(want)
        held += sum((have & need).values())
        wanted += len(want)
        if have != need:
            if not bag_bad:
                say(f"query {i} (from {start}): {len(got)} ids, the "
                    f"reference has {len(want)}; "
                    f"{sum((need - have).values())} missing, "
                    f"{sum((have - need).values())} too many")
            bag_bad += 1
        if ordered:
            ordered_seen += 1
            if got != want:
                if not order_bad:
                    at = next((j for j, (g, w) in enumerate(zip(got, want))
                               if g != w), min(len(got), len(want)))
                    say(f"query {i} (from {start}): differs from the "
                        f"reference's sequence at place {at}")
                order_bad += 1
        if start in ref.sql_adj:
            # an acknowledged RELATE is traversed: every path through
            # each SQL edge out of the start node is in the answer
            on_sql += 1
            through = Counter()
            for b in ref.sql_adj[start]:
                through.update(ref.walk(b, hops - 1)[0])
            if through - have:
                if not lost:
                    say(f"query {i} starts at {start}, the source of an "
                        f"SQL edge: {sum((through - have).values())} "
                        f"paths through it did not come back")
                lost += 1
    out = {"bad_answers": num(len(bad), 0, "<="),
           "bag_mismatch": num(bag_bad, limits["bag_mismatch"], "<="),
           "order_mismatch": num(order_bad, limits["order_mismatch"], "<="),
           "ordered_answers": num(ordered_seen, 1, ">="),
           "recall_at_10": num(held / wanted if wanted else 0.0,
                               limits["recall_at_10_min"], ">=")}
    if dep.sz["sql_edges"]:
        out["readback_missing"] = num(lost, 0, "<=")
        out["readback_queries"] = num(on_sql, 1, ">=")
    return out


def device_served(before: dict, after: dict, op: str, say) -> dict:
    """A window in which the device did not serve is a failed run: none
    of the supervisor's six counters may move, its state stays `ready`,
    and the cell's runner op has to advance. (`knn.py device_served`,
    copied.)"""
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
