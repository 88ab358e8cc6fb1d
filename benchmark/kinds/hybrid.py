"""Kind `hybrid`: BASELINE config 5, upstream SurrealDB's documented hybrid
search. One table holds each passage's text under a `FULLTEXT ... BM25`
index and its embedding under a vector index; a request is ONE `/rpc`
`query` with `$qtext` and `$qvec` bound that runs both legs and fuses them by
reciprocal rank fusion:

    LET $vs = SELECT id, vector::distance::knn() AS d FROM passage
        WHERE emb <|10,40|> $qvec;
    LET $ft = SELECT id, search::score(1) AS s FROM passage
        WHERE text @1@ $qtext ORDER BY s DESC LIMIT 10;
    RETURN {vs: $vs, ft: $ft, fused: search::rrf([$vs, $ft], 10, 60)};

The last statement returns both legs beside the fusion, so each is judged.

Everything the deployment needs besides its sizes (`configs/<name>.json`)
lives here: passages and queries from the seed, ingest by the KV bulk route
(records, the vector index's `he` rows, the full-text index's own `bf` /
`bl` / `bs` / `bv` keys in the program's format, checked against what the
program itself writes for a few passages), the plain reference (f64 numpy
BM25 over the generated token ids, kind `knn`'s f64 brute-force cosine, and
RRF), the comparison that decides `correct`, and the control (the BM25 leg
scored in bfloat16, no rescore). Vectors, `pick`, `num` and the vector
leg's conditions are kind `knn`'s, imported and not copied.

The reference imports nothing of the program. Only the load path touches
`surrealdb_tpu`.
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_kind_knn_for_hybrid",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "knn.py"))
knn = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(knn)

NS, DB, HEADERS, PATH = knn.NS, knn.DB, knn.HEADERS, knn.PATH
SetupFailed = knn.SetupFailed
num, pick, sizes = knn.num, knn.pick, knn.sizes
FT_IX, VEC_IX = "ft", "ix"
WORD_LETTERS = 5       # 26**5 > 2**20 ranks: every word fits
# `check_format` compares the terms whose values are under this size
PROBE_BYTES = 1 << 20


# -- data from the seed ------------------------------------------------------


def spell(ranks: np.ndarray):
    """Word rank (0 first) -> its lowercase letters, bijective base 26
    ("a" .. "z", "aa", ...): ([n, WORD_LETTERS] uint8 left-aligned,
    lengths)."""
    x = ranks.astype(np.int64) + 1
    cols = []
    while (x > 0).any():
        cols.append(np.where(x > 0, (x - 1) % 26 + 97, 0))
        x = np.where(x > 0, (x - 1) // 26, 0)
    cols = np.stack(cols[::-1], 1).astype(np.uint8)
    width = (cols > 0).sum(1)
    out = np.zeros((len(ranks), WORD_LETTERS), np.uint8)
    at = np.arange(cols.shape[1])[None, :] - (cols.shape[1] - width)[:, None]
    ok = at >= 0
    out[np.nonzero(ok)[0], at[ok]] = cols[ok]
    return out, width


class Passages:
    """n passages of Zipf-distributed words (s = 1 over `vocab` ranks),
    lengths Poisson(mean) with a floor: the token ranks end to end, each
    token's passage and its character span in the passage's text."""

    def __init__(self, n: int, vocab: int, mean_len: float, min_len: int,
                 seed: int):
        rng = np.random.default_rng([seed, 5])
        self.n = n
        self.lens = np.maximum(rng.poisson(mean_len, n), min_len)
        harmonic = np.cumsum(1.0 / np.arange(1, vocab + 1))
        total = int(self.lens.sum())
        self.ranks = np.minimum(np.searchsorted(
            harmonic, rng.random(total) * harmonic[-1]), vocab - 1)
        self.starts = np.concatenate([[0], np.cumsum(self.lens)[:-1]])
        self.doc = np.repeat(np.arange(n), self.lens)
        self.letters, wlen = spell(np.arange(vocab))
        self.wlen = wlen
        tl = wlen[self.ranks]
        ends = np.cumsum(tl + 1)
        cstart = ends - (tl + 1)
        base = cstart[self.starts]
        self.a = cstart - base[self.doc]
        self.b = self.a + tl
        self._cstart, self._base = cstart, base
        self.rng = rng

    def texts(self) -> list:
        """Each passage's text: its words joined by single spaces."""
        tl = self.wlen[self.ranks]
        buf = np.full(int(self._cstart[-1] + tl[-1]), 32, np.uint8)
        mask = np.arange(WORD_LETTERS)[None, :] < tl[:, None]
        at = self._cstart[:, None] + np.arange(WORD_LETTERS)[None, :]
        buf[at[mask]] = self.letters[self.ranks][mask]
        last = np.append(self.starts[1:], len(self.ranks)) - 1
        ends = self._base + self.b[last]
        raw = buf.tobytes()
        return [raw[s:e].decode() for s, e in
                zip(self._base.tolist(), ends.tolist())]

    def words_of(self, p: int) -> np.ndarray:
        """The distinct ranks of passage p, in order of first occurrence."""
        r = self.ranks[self.starts[p]:self.starts[p] + self.lens[p]]
        _u, first = np.unique(r, return_index=True)
        return r[np.sort(first)]


# -- the deployment's load path ----------------------------------------------


def runner_ft(sup) -> dict:
    """The runner's BM25 counters, or SetupFailed for a program whose
    runner keeps none: it would score every query's postings on the host,
    about a second of interpreter a query at this size. Fails here, at
    once, before any data is made."""
    ft = sup.runner_status().get("ft")
    if not isinstance(ft, dict) or "riders" not in ft:
        raise SetupFailed("the program's device runner reports no `ft` "
                          "counters: it has no BM25 op over a resident "
                          "full-text index")
    return ft


def _records(n: int, fields: list) -> np.ndarray:
    """[n, width] uint8 rows of packed fields: (dtype, values) pairs in
    order, each filled a column at a time."""
    dt = np.dtype([(f"f{i}", d) for i, (d, _v) in enumerate(fields)])
    rec = np.zeros(n, dt)
    for i, (_d, v) in enumerate(fields):
        rec[f"f{i}"] = v
    return rec.view(np.uint8).reshape(n, dt.itemsize)


def _place(buf: np.ndarray, at: np.ndarray, rows: np.ndarray,
           chunk: int = 1 << 20):
    """buf[at[i] : at[i] + width] = rows[i], a chunk of rows at a time."""
    ar = np.arange(rows.shape[1])
    for s in range(0, len(at), chunk):
        buf[at[s:s + chunk, None] + ar] = rows[s:s + chunk]


def posting_values(ps: Passages, ridk: np.ndarray):
    """Each term's `bf` value as the program stores it: the store's pickle
    framing byte, then a pickle of {record key: (tf, [(0, a, b, a, b) per
    occurrence], id)} with the passages in id order, put together by numpy
    (fixed-width opcodes, no memo: `pickle.loads` reads it as it reads
    `pickle.dumps`'s). Returns (term ranks, values)."""
    order = np.argsort(ps.ranks, kind="stable")
    r, d = ps.ranks[order], ps.doc[order]
    m = len(r)
    newp = np.ones(m, bool)
    newp[1:] = (r[1:] != r[:-1]) | (d[1:] != d[:-1])
    pstart = np.nonzero(newp)[0]
    tf = np.diff(np.append(pstart, m))
    prank, pdoc = r[pstart], d[pstart]
    newt = np.ones(len(pstart), bool)
    newt[1:] = prank[1:] != prank[:-1]
    tstart = np.nonzero(newt)[0]
    tidx = np.cumsum(newt) - 1
    # a value: framing, PROTO 5, EMPTY_DICT, MARK (5 bytes), its postings,
    # SETITEMS, STOP (2); a posting: 26 bytes, 27 an occurrence, 7
    psize = 33 + 27 * tf
    poff = np.cumsum(psize) - psize + 5 * (tidx + 1) + 2 * tidx
    tlast = np.append(tstart[1:], len(pstart)) - 1
    te = poff[tlast] + psize[tlast]
    ts = poff[tstart] - 5
    buf = np.zeros(int(te[-1]) + 2, np.uint8)
    u1, i4 = np.uint8, "<i4"
    _place(buf, ts, np.tile(np.frombuffer(b"\x00\x80\x05}(", u1),
                            (len(ts), 1)))
    _place(buf, te, np.tile(np.frombuffer(b"u.", u1), (len(te), 1)))
    # SHORT_BINBYTES key, BININT tf, EMPTY_LIST, MARK
    _place(buf, poff, _records(len(pdoc), [
        (u1, ord("C")), (u1, ridk.shape[1]), ((u1, ridk.shape[1]),
                                              ridk[pdoc]),
        (u1, ord("J")), (i4, tf), (u1, ord("]")), (u1, ord("("))]))
    # APPENDS, BININT id, TUPLE3
    _place(buf, poff + 26 + 27 * tf, _records(len(pdoc), [
        (u1, ord("e")), (u1, ord("J")), (i4, pdoc), (u1, 0x87)]))
    # an occurrence: MARK, five BININTs (0, a, b, a, b), TUPLE
    pidx = np.cumsum(newp) - 1
    a, b = ps.a[order], ps.b[order]
    fields = [(u1, ord("("))]
    for v in (0, a, b, a, b):
        fields += [(u1, ord("J")), (i4, v)]
    _place(buf, poff[pidx] + 26 + 27 * (np.arange(m) - pstart[pidx]),
           _records(m, fields + [(u1, ord("t"))]))
    raw = buf.tobytes()
    return prank[tstart], [raw[s:e] for s, e in
                           zip(ts.tolist(), (te + 2).tolist())]


def bulk_hybrid(ds, tb: str, xs, ps: Passages, texts: list, log,
                chunk: int = 50_000) -> dict:
    """The KV bulk route: records `{id, text}`, the vector index's `he`
    rows and `vn`, and the full-text index's `bf`, `bl`, `bs`, `bv` keys.
    Returns the seconds of each part."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import serialize
    from surrealdb_tpu.val import RecordId

    n = len(xs)
    timing = {}
    t = time.monotonic()
    # a key is its kind's prefix and the record's (or word's) suffix, as
    # `K.record` and `K.ix_state` put them together
    rids = [K.enc_value(i) for i in range(n)]
    rec, he = K.record_prefix(NS, DB, tb), K.ix_state(NS, DB, tb, VEC_IX,
                                                      b"he")
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        txn = ds.transaction(write=True)
        try:
            for i in range(s, e):
                txn.set(rec + rids[i], serialize(
                    {"id": RecordId(tb, i), "text": texts[i]}))
                txn.set_val(he + rids[i], xs[i].tobytes())
            txn.set_val(K.ix_state(NS, DB, tb, VEC_IX, b"vn"), e)
            txn.commit()
        except BaseException:
            txn.cancel()
            raise
    timing["records_s"] = time.monotonic() - t
    t = time.monotonic()
    ridk = np.frombuffer(b"".join(rids), np.uint8).reshape(n, -1)
    ranks, values = posting_values(ps, ridk)
    timing["postings_s"] = time.monotonic() - t
    t = time.monotonic()
    bf = K.ix_state(NS, DB, tb, FT_IX, b"bf")
    keys = [bf + K.enc_str(word) for word in words_of_ranks(ps, ranks)]
    for s in range(0, len(keys), 4 * chunk):
        txn = ds.transaction(write=True)
        try:
            for i in range(s, min(s + 4 * chunk, len(keys))):
                txn.set(keys[i], values[i])
            txn.commit()
        except BaseException:
            txn.cancel()
            raise
    bl = K.ix_state(NS, DB, tb, FT_IX, b"bl")
    txn = ds.transaction(write=True)
    try:
        for i, dl in enumerate(ps.lens.tolist()):
            txn.set_val(bl + rids[i], dl)
        txn.set_val(K.ix_state(NS, DB, tb, FT_IX, b"bs"),
                    {"docs": n, "total_len": int(ps.lens.sum())})
        txn.set_val(K.ix_state(NS, DB, tb, FT_IX, b"bv"), time.time_ns())
        txn.commit()
    except BaseException:
        txn.cancel()
        raise
    timing["ft_keys_s"] = time.monotonic() - t
    log(f"{n} passages by the bulk route: records + he "
        f"{timing['records_s']:.1f}s, {len(values)} posting values built "
        f"in {timing['postings_s']:.1f}s"
        f", full-text keys {timing['ft_keys_s']:.1f}s")
    return timing


def words_of_ranks(ps: Passages, ranks) -> list:
    raw = ps.letters[ranks]
    return [bytes(row[:w]).decode() for row, w in
            zip(raw, ps.wlen[ranks].tolist())]


def check_format(cfg_defines: str, ds_main, tb: str, ps: Passages,
                 texts: list, probe: list):
    """The bulk route writes what the program writes: the passages
    `probe` are indexed by the program itself in a scratch store, and
    each one's `bl` value and its entry in every one of its terms' `bf`
    values must equal the bulk route's."""
    from surrealdb_tpu import Datastore
    from surrealdb_tpu import key as K
    from surrealdb_tpu.kvs.api import deserialize

    scratch = Datastore("memory")
    try:
        scratch.query(cfg_defines, ns=NS, db=DB)
        for p in probe:
            scratch.query(f"CREATE {tb}:{p} SET text = $t", ns=NS, db=DB,
                          vars={"t": texts[p]})
        mine, theirs = ds_main.transaction(write=False), \
            scratch.transaction(write=False)
        try:
            for p in probe:
                ridk = K.enc_value(p)
                bl = K.ix_state(NS, DB, tb, FT_IX, b"bl", ridk)
                if deserialize(mine.get(bl)) != deserialize(theirs.get(bl)):
                    raise SetupFailed(f"passage {p}: `bl` differs from "
                                      f"the program's")
                for w in set(texts[p].split()):
                    bf = K.ix_state(NS, DB, tb, FT_IX, b"bf", K.enc_str(w))
                    raw = mine.get(bf)
                    if len(raw) > PROBE_BYTES:
                        continue   # a common word: seconds to decode
                    got = deserialize(raw)
                    want = deserialize(theirs.get(bf))
                    if got.get(ridk) != want.get(ridk):
                        raise SetupFailed(
                            f"passage {p}, word {w!r}: the bulk route's "
                            f"posting {got.get(ridk)} is not the program's "
                            f"{want.get(ridk)}")
        finally:
            mine.cancel()
            theirs.cancel()
    finally:
        scratch.close()


def leg_sql(sz: dict, leg: int) -> str:
    """The statement of one leg, `LET $<name> = ` taken off: 0 the
    vector leg, 1 the BM25 leg."""
    text = sz["statement"].split(";")[leg].strip()
    return text[text.index("=") + 1:].strip()


def rpc_body(sz: dict, index: int, text: str, q) -> bytes:
    return json.dumps({"id": index, "method": "query", "params": [
        sz["statement"], {"qtext": text, "qvec": q.tolist()}]}).encode()


def setup(cfg: dict, seed: int, ds, http, rehearsal: bool, log):
    """The runner probe, passages and vectors from the seed, DEFINE, bulk
    ingest (checked against the program's own writes), the first vector
    search and the segment's graph, the first BM25 search (the index read
    from KV, shipped), EXPLAIN of the BM25 leg, the prewarm, the pool.
    Every write is acknowledged before this returns."""
    from surrealdb_tpu.device import get_supervisor

    sup = get_supervisor()
    runner_ft(sup)
    sz = sizes(cfg, rehearsal)
    n, dim, tb = sz["rows"], sz["dim"], sz["table"]
    timing = {}
    t = time.monotonic()
    xs, rng = knn.clustered_rows(n, dim, seed)
    ps = Passages(n, sz["vocab"], sz["mean_words"], sz["min_words"], seed)
    texts = ps.texts()
    timing["data_s"] = time.monotonic() - t
    defines = (f"DEFINE ANALYZER simple TOKENIZERS blank FILTERS lowercase; "
               f"DEFINE TABLE {tb}; DEFINE INDEX {FT_IX} ON {tb} FIELDS text "
               f"FULLTEXT ANALYZER simple BM25")
    http.sql(defines + f"; DEFINE INDEX {VEC_IX} ON {tb} FIELDS emb "
             f"{sz['index']} DIMENSION {dim} DIST {sz['metric'].upper()} "
             f"TYPE F32")
    timing.update(bulk_hybrid(ds, tb, xs, ps, texts, log))
    t = time.monotonic()
    check_format(defines, ds, tb, ps, texts, [0, n // 2, n - 1])
    timing["format_check_s"] = time.monotonic() - t
    # the BM25 leg first: its index read, ship and the prewarm's compiles
    # (in the runner) overlap the vector leg's host work
    s0 = sup.status()
    t = time.monotonic()
    text0 = " ".join(words_of_ranks(ps, ps.words_of(0)[:sz["words"]]))
    rows = http.query(leg_sql(sz, 1), {"qtext": text0})[0]
    timing["first_text_search_s"] = time.monotonic() - t
    s1 = sup.status()
    moved = {k: s1[k] - s0[k] for k in
             ("ft_ships", "ft_device_searches", "ft_host_searches")}
    if not any(str(r["id"]) == f"{tb}:0" for r in rows) or moved != {
            "ft_ships": 1, "ft_device_searches": 1, "ft_host_searches": 0}:
        raise SetupFailed(
            f"the first BM25 search, on passage 0's words, was not served "
            f"from one shipped index: {len(rows)} rows, counters {moved}")
    plan = http.query(leg_sql(sz, 1) + " EXPLAIN", {"qtext": text0})[0]
    try:
        named = plan[0]["operation"] == "Iterate Index" \
            and plan[0]["detail"]["plan"]["index"] == FT_IX
    except (KeyError, IndexError, TypeError):
        named = False
    if not named:
        raise SetupFailed(f"EXPLAIN of the BM25 leg does not name the "
                          f"full-text index: {plan}")
    log(f"first BM25 search (index read, ship) "
        f"{timing['first_text_search_s']:.1f}s")
    # the vector leg as kind `knn` sets it up: first search, graph build
    t = time.monotonic()
    http.query(leg_sql(sz, 0), {"qvec": xs[0].tolist()})
    timing["first_vector_search_s"] = time.monotonic() - t
    ix = ds.vector_indexes[(NS, DB, tb, VEC_IX)]
    t = time.monotonic()
    if not ix.ensure_ann():
        raise SetupFailed("the segment graphs did not build")
    timing["graph_build_s"] = time.monotonic() - t
    plan = ix.ann_plan(sz["k"])
    if not plan or plan.get("ann") != sz["ann"] \
            or plan.get("ready") != plan.get("segments") \
            or plan.get("tail_rows"):
        raise SetupFailed(f"not served from sealed graphs: {plan}")
    log(f"first vector search {timing['first_vector_search_s']:.1f}s, "
        f"graphs built in {timing['graph_build_s']:.1f}s: {plan}")
    # the ship's prewarm compiles behind the searches: wait for its
    # thread, then for quiet
    t = time.monotonic()
    while any(th.name == "ft-prewarm" for th in threading.enumerate()) \
            and time.monotonic() - t < 300:
        time.sleep(0.3)
    quiet, last = 0, None
    while quiet < 3:
        rs = sup.runner_status()
        now = (rs["cc"]["misses"], rs["ops"].get("ft_prewarm", 0))
        quiet = quiet + 1 if now == last else 0
        last = now
        time.sleep(0.3)
    timing["prewarm_wait_s"] = time.monotonic() - t
    # the pool: a passage, up to `words` of its distinct words, its vector
    t = time.monotonic()
    pool = sz["pool"]
    target = rng.integers(0, n, pool)
    qs = knn.queries_near(xs, target, rng)
    words = []
    for p in target.tolist():
        w = ps.words_of(p)
        words.append(rng.permutation(w)[:sz["words"]] if len(w) > sz["words"]
                     else rng.permutation(w))
    timing["pool_s"] = time.monotonic() - t
    return Deployment(sz, xs, ps, target, words, qs, timing)


# -- the plain reference -----------------------------------------------------


class Reference:
    """BM25 in f64 numpy over the passages' token ranks (the semantics
    of `surrealdb_tpu/idx/ft_reference.py`, written again here): a
    passage's length is its token count, idf ln((N - df + .5) / (df + .5))
    clamped at 0, tf' = 1 + ln tf, k1 and b as f32 values, AND over the
    distinct query terms, equal scores in passage order."""

    def __init__(self, ps: Passages, k1: float, b: float):
        key = ps.ranks.astype(np.int64) * ps.n + ps.doc
        uk, tf = np.unique(key, return_counts=True)
        self.term, self.docs, self.tf = uk // ps.n, uk % ps.n, tf
        self.dl = ps.lens
        self.n = ps.n
        self.avg = float(ps.lens.sum()) / ps.n
        self.k1, self.b = float(np.float32(k1)), float(np.float32(b))

    def postings(self, t: int):
        lo, hi = np.searchsorted(self.term, [t, t + 1])
        return self.docs[lo:hi], self.tf[lo:hi]

    def parts(self, terms):
        """(matching docs, [(idf, tf of each doc)] per term)."""
        post = [self.postings(int(t)) for t in terms]
        docs = post[0][0]
        for d, _tf in post[1:]:
            docs = np.intersect1d(docs, d)
        out = []
        for d, tf in post:
            idf = max(np.log((self.n - len(d) + 0.5) / (len(d) + 0.5)), 0.0)
            out.append((idf, tf[np.searchsorted(d, docs)]))
        return docs, out

    def scores(self, terms, docs=None):
        """f64 scores of `docs` (default: every match), and the docs."""
        match, parts = self.parts(terms)
        if docs is None:
            docs = match
        docs = np.asarray(docs, np.int64)
        at = np.searchsorted(match, docs)
        ok = (at < len(match)) & (match[np.minimum(at, len(match) - 1)]
                                  == docs)
        norm = (1 - self.b) + (self.b / self.avg) * self.dl[docs]
        s = np.zeros(len(docs))
        for idf, tf in parts:
            tfd = np.where(ok, tf[np.minimum(at, len(tf) - 1)]
                           if len(tf) else 0, 1)
            tfp = 1.0 + np.log(tfd)
            s = s + (idf * (self.k1 + 1) * tfp / (tfp + self.k1 * norm)
                     if idf > 0 else 0.0)
        return docs, np.where(ok, s, -np.inf)

    def top(self, terms, k: int):
        docs, s = self.scores(terms)
        order = np.lexsort((docs, -s))[:k]
        return docs[order], s[order], len(docs)


def rrf(lists: list, limit: int, k: float):
    """[(id, fused score)]: each id scores the sum of 1 / (k + rank + 1)
    over the lists it is in, best first, equal scores in order of first
    appearance (upstream's search::rrf)."""
    scores: dict = {}
    for lst in lists:
        for rank, i in enumerate(lst):
            scores[i] = scores.get(i, 0.0) + 1.0 / (k + rank + 1)
    return [(i, scores[i]) for i in
            sorted(scores, key=lambda i: -scores[i])[:limit]]


# -- the control: the BM25 leg in the next precision down --------------------


def control_ft(ref: Reference, terms, k: int):
    """What the BM25 leg would return if it were scored in bfloat16 (each
    term's weight, tf', length norm and k1 rounded to bf16, f32 sums) and
    nothing rescored it: ids ranked and `s` reported from that arithmetic."""
    docs, parts = ref.parts(terms)
    bf = knn.to_bf16
    norm = bf(((1 - ref.b) + (ref.b / ref.avg) * ref.dl[docs])
              .astype(np.float32))
    k1 = bf(np.float32(ref.k1))
    s = np.zeros(len(docs), np.float32)
    for idf, tf in parts:
        w = bf(np.float32(idf * (ref.k1 + 1)))
        tfp = bf((1.0 + np.log(tf)).astype(np.float32))
        s = s + w * tfp / (tfp + k1 * norm)
    order = np.lexsort((docs, -s))[:k]
    return docs[order].tolist(), [float(v) for v in s[order]]


# -- the comparison ----------------------------------------------------------


def parse_answer(status: int, body: bytes, tb: str):
    """{vs: [(id, d)], ft: [(id, s)], fused: [(id, rrf_score)]} of one
    reply, or a string saying what is wrong with it."""
    if status != 200:
        return f"status {status}: {body[:200]!r}"
    try:
        out = json.loads(body)
        if "error" in out:
            return f"rpc error: {str(out['error'])[:200]}"
        res = out["result"]
        if len(res) != 3 or any(st["status"] != "OK" for st in res):
            return f"statements: {str(res)[:300]}"
        last = res[2]["result"]

        def rows(name, field):
            got = []
            for r in last[name]:
                t, i = str(r["id"]).split(":", 1)
                if t != tb:
                    raise ValueError(f"id {r['id']} is not a {tb}")
                got.append((int(i), float(r[field])))
            return got

        ans = {"vs": rows("vs", "d"), "ft": rows("ft", "s"),
               "fused": rows("fused", "rrf_score")}
    except (ValueError, KeyError, IndexError, TypeError) as e:
        return f"unreadable reply: {e.__class__.__name__}: {e}"
    for name, cap in (("vs", 10), ("ft", 10), ("fused", 10)):
        ids = [i for i, _v in ans[name]]
        if len(ids) > cap or len(set(ids)) != len(ids):
            return f"{name}: {len(ids)} rows ({len(set(ids))} distinct)"
        if not all(np.isfinite([v for _i, v in ans[name]])):
            return f"{name}: values not finite"
    if len(ans["vs"]) != 10:
        return f"vs: {len(ans['vs'])} rows, want 10"
    return ans


class Deployment:
    """One loaded table and its seeded pool of (words, vector) pairs."""

    def __init__(self, sz, xs, ps, target, words, qs, timing):
        self.sz = sz
        self.xs = xs
        self.ps = ps
        self.target = target      # [P] the passage each pair came from
        self.words = words        # [P] its query's word ranks
        self.qs = qs              # [P, D] f32
        self.timing = timing
        self.ref = Reference(ps, *sz["bm25"])

    def text(self, i: int) -> str:
        return " ".join(words_of_ranks(self.ps, self.words[i]))

    def bodies(self):
        return [rpc_body(self.sz, i, self.text(i), q)
                for i, q in enumerate(self.qs)]

    def judge(self, records, before, after, limits, seed, say) -> dict:
        parsed = [parse_answer(r[3], r[4], self.sz["table"])
                  for r in records]
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        compared = compare(self, [(records[j][0], parsed[j])
                                  for j in chosen], limits, say)
        compared["answers_compared"] = num(len(chosen), 1, ">=")
        compared.update(device_served(before, after, len(records), say))
        return {"ok": [not isinstance(a, str) for a in parsed],
                "compared": compared,
                "metrics": {"recall_at_10": compared["recall_at_10"]["value"]}}

    def judge_control(self, records, limits, seed) -> dict:
        """The control's numbers on the pairs `judge` compared: the exact
        vector leg, the BM25 leg from `control_ft`, their fusion."""
        chosen = pick([r[2] - r[1] for r in records], limits["compare_max"],
                      seed)
        idx = [records[j][0] for j in chosen]
        ref_i, ref_d = knn.brute_force(self.xs, self.qs[idx],
                                       self.sz["metric"], 10)
        answers = []
        for j, i in enumerate(idx):
            vs = list(zip(ref_i[j].tolist(), ref_d[j].tolist()))
            ids, s = control_ft(self.ref, self.words[i], 10)
            ft = list(zip(ids, s))
            fused = rrf([[v for v, _d in vs], ids], 10, self.sz["rrf_k"])
            answers.append((i, {"vs": vs, "ft": ft, "fused": fused}))
        words = []
        out = compare(self, answers, limits, words.append)
        out["correct"] = all(c["ok"] for c in out.values())
        out["first_failures"] = words
        return out


def compare(dep: Deployment, answers, limits: dict, say) -> dict:
    """`answers` is [(pool index, parsed answer or an error string)].
    Returns {name: num}, the numbers that decide `correct`."""
    sz, ref = dep.sz, dep.ref
    n = len(dep.xs)
    bad = [(i, a) for i, a in answers if isinstance(a, str)]
    good = []
    for i, a in answers:
        if isinstance(a, str):
            continue
        ids = [x for name in ("vs", "ft", "fused") for x, _v in a[name]]
        if ids and (min(ids) < 0 or max(ids) >= n):
            bad.append((i, f"an id outside the table: {ids}"))
            continue
        good.append((i, a))
    # the BM25 leg: as many rows as the reference has, up to 10, the
    # reference's top 10 with ties at the last place, each `s` the f32 of
    # THAT row's f64 score
    mismatch, err_max, err_at = 0, 0.0, None
    ft_ref = {}
    kept = []
    for i, a in good:
        top_d, top_s, matches = ref.top(dep.words[i], 10)
        ft_ref[i] = top_d.tolist()
        got = [d for d, _s in a["ft"]]
        if len(got) != len(top_d):
            bad.append((i, f"ft: {len(got)} rows, the reference has "
                           f"{len(top_d)} of {matches} matches"))
            continue
        kept.append((i, a))
        if not got:
            continue
        _d, want = ref.scores(dep.words[i], got)
        if (want < top_s[-1] - 1e-12 * abs(top_s[-1])).any():
            mismatch += 1
            if mismatch == 1:
                say(f"pair {i}: ft ids {got}, reference top "
                    f"{top_d.tolist()} (scores {top_s.tolist()})")
        s = np.asarray([v for _d, v in a["ft"]])
        w32 = np.where(np.isfinite(want), want, np.inf).astype(np.float32) \
            .astype(np.float64)
        with np.errstate(invalid="ignore"):
            e = np.abs(s - w32) / np.maximum(np.abs(w32), 1e-30)
        e = np.where(np.isfinite(e), e, np.inf)
        if e.max() > err_max:
            err_max, err_at = float(e.max()), (i, got[int(e.argmax())],
                                               float(s[int(e.argmax())]),
                                               float(want[int(e.argmax())]))
    good = kept
    if bad:
        say(f"{len(bad)} of {len(answers)} answers are no answers; pair "
            f"{bad[0][0]}: {bad[0][1]}")
    out = {"bad_answers": num(len(bad), 0, "<="),
           "ft_mismatch": num(mismatch, 0, "<="),
           "ft_score_err_max": num(err_max, limits["ft_score_err_max"],
                                   "<=")}
    if not out["ft_score_err_max"]["ok"]:
        i, d, s, want = err_at
        say(f"pair {i}: passage {d} reported s {s!r}, its f64 BM25 is "
            f"{want!r}: relative error {err_max:.3g}, limit "
            f"{limits['ft_score_err_max']}")
    if not good:
        out["recall_at_10"] = num(0.0, limits["recall_at_10_min"], ">=")
        return out
    idx = np.asarray([i for i, _a in good])
    qs = dep.qs[idx]
    # the vector leg: kind knn's conditions
    got_i = np.asarray([[v for v, _d in a["vs"]] for _i, a in good])
    got_d = np.asarray([[d for _v, d in a["vs"]] for _i, a in good])
    want = knn.row_distances(dep.xs, qs, got_i, sz["metric"])
    err = np.abs(got_d - want) / (np.abs(want) + limits["dist_floor"])
    out["dist_err_max"] = num(float(err.max()), limits["dist_err_max"], "<=")
    ref_i, ref_d = knn.brute_force(dep.xs, qs, sz["metric"], 10)
    hit = (got_i[:, :, None] == ref_i[:, None, :]).any(2) \
        | (want <= ref_d[:, 9:10] * (1 + 1e-9) + 1e-12)
    out["vs_recall_at_10"] = num(float(hit.mean()),
                                 limits["vs_recall_at_10_min"], ">=")
    if not out["vs_recall_at_10"]["ok"]:
        say(f"vector leg recall@10 {hit.mean():.5f} < "
            f"{limits['vs_recall_at_10_min']}")
    # the fusion: search::rrf of the reply's own two legs, and against
    # the fusion of the exact legs
    fused_bad, share = 0, []
    for j, (i, a) in enumerate(good):
        mine = rrf([[v for v, _d in a["vs"]], [d for d, _s in a["ft"]]],
                   10, sz["rrf_k"])
        if [v for v, _s in mine] != [v for v, _s in a["fused"]] or any(
                abs(x - y) > 1e-12 for (_a, x), (_b, y) in
                zip(mine, a["fused"])):
            fused_bad += 1
            if fused_bad == 1:
                say(f"pair {i}: fused {a['fused']} is not search::rrf of "
                    f"its legs {mine}")
        exact = {v for v, _s in rrf([ref_i[j].tolist(), ft_ref[i]], 10,
                                    sz["rrf_k"])}
        share.append(sum(v in exact for v, _s in a["fused"])
                     / max(len(exact), 1))
    out["fused_mismatch"] = num(fused_bad, 0, "<=")
    recall = float(np.mean(share))
    out["recall_at_10"] = num(recall, limits["recall_at_10_min"], ">=")
    if not out["recall_at_10"]["ok"]:
        say(f"fused recall@10 {recall:.5f} < {limits['recall_at_10_min']}")
    return out


def device_served(before: dict, after: dict, requests: int, say) -> dict:
    """Both legs of every request on the device: kind knn's six counters
    still, `ann_search` and `ft_bm25` advanced, no BM25 leg answered by
    the host in the window, and the runner's BM25 riders are the
    window's requests, one for one."""
    out = knn.device_served(before, after, "ann_search", say)
    ops = after["runner"]["ops"].get("ft_bm25", 0) \
        - before["runner"]["ops"].get("ft_bm25", 0)
    if ops < 1:
        say("runner op ft_bm25 did not advance in the window")
    out["ft_device_dispatches"] = num(ops, 1, ">=")
    host = after["supervisor"].get("ft_host_searches", 0) \
        - before["supervisor"].get("ft_host_searches", 0)
    if host:
        say(f"{host} BM25 legs answered by the host in the window")
    out["ft_host_searches"] = num(host, 0, "<=")
    riders = (after["runner"].get("ft") or {}).get("riders", 0) \
        - (before["runner"].get("ft") or {}).get("riders", 0)
    if riders != requests:
        say(f"the runner's BM25 op served {riders} riders in a window of "
            f"{requests} requests")
    out["ft_riders_off"] = num(abs(riders - requests), 0, "<=")
    return out
