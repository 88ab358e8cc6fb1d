"""BM25 top-n on the device: the serving half of `device/ftstore.py`.

One shape rides it: a single `field @ref@ query` MATCHES (AND), a projection
of `id` and `search::score` only, and `ORDER BY search::score(ref) DESC
LIMIT n` with no START (`fulltext._ft_order_limit`). For it the planner asks
`device_leg` before the host search; every other MATCHES shape is the
host's (`fulltext._ft_search_impl`), counted in `ft_host_searches`.

- **Snapshot.** The first search of an index version reads its `bf` / `bl`
  / `bs` keys from KV truth into CSR arrays (`_read_index`, the values'
  pickles decoded natively by `native/ftpost.py` where it can) and keeps on
  the host the term -> term-number map, each term's df, the doc ids, the
  corpus stats and the arrays; the first dispatch that needs them ships the
  postings (`ensure_loaded`, key `ft/<ns>/<db>/<tb>/<ix>`, tag `[bv]`), then
  the runner compiles the programs ahead (`_prewarm`); stage `ft_build`
  holds the read and the ship. A write moves `bv`, so the next search
  builds and ships anew; a search whose own transaction wrote the index is
  the host's.
- **Rider.** The query is analysed as `_ft_search_impl` analyses it, its
  distinct terms sorted rarest first, each with its weight `idf * (k1 + 1)`
  (the same clamped idf) as f32; an absent term answers no rows. Riders go
  through this module's `DeviceBatcher` to the runner op `ft_bm25` in ONE
  call a dispatch, grouped by capacity rung; one a rung past its capacity
  comes back and goes round again on the rung that holds it.
- **Rescore.** Each candidate the device returned (its `k` = n + margin
  best by f32) is scored again in f64 from the integers it came back with
  (tf per term, dl), with `_ft_search_impl`'s arithmetic in its order, and
  rounded to f32 as it reports; the candidates are sorted by the f64 score
  (equal scores: the lower doc number, i.e. record-key order, first) and
  the first n kept. Stage `ft_post`, per dispatch.
- **Exactness.** Where the AND matches outnumber `k`, every doc the device
  did not return scored at most the k-th returned f32 score `L`, so at most
  `L + err` in f64 (`_err_bound`). The rider is exact when that is below its
  n-th f64 score; else (ties or near-ties beyond the margin) it is answered
  by the host search and counted as one.

Stage `ft_search` spans one leg, analysis to rows, either path.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from surrealdb_tpu import key as K
from surrealdb_tpu.telemetry import stage_record

# k = the smallest power of two >= n + FT_MARGIN places a rider brings back
# (32 for LIMIT 10), or its rung's capacity where that is smaller (then
# every match comes back). What makes the top n exact is the check in `_rescore`,
# not this number: the margin only has to outnumber the docs whose f64
# scores lie within 2 * err of the n-th (exact ties among them are docs
# with the same tf vector and length), so that the check passes and the
# host is not asked. err (`_err_bound`) is (16 + T) units of f32 rounding
# (2**-24) of the sum of the rider's weights: each term's f32 score (weight
# rounded, log, one division, three products) is within 16 units of its
# f64 value relative to that weight, and a sum of T terms adds T more.
FT_MARGIN = 16
# the largest LIMIT the device serves (k up to 512 places a rider)
FT_MAX_N = 256
# relative f32 error of one rider's score, per unit of its weights' sum
_ULP = 2.0 ** -24


def _err_bound(n_terms: int, weights) -> float:
    return (16 + n_terms) * _ULP * float(sum(weights))


def _places(n: int, cap: int) -> int:
    """k of a rider of LIMIT n on the rung of capacity `cap`."""
    from surrealdb_tpu.device.csrstore import pow2_at_least

    return min(pow2_at_least(n + FT_MARGIN), cap)


class Snapshot:
    """One index version as shipped: what the host needs to make riders
    and rescore them."""

    __slots__ = ("key", "tag", "terms", "df", "doc_ids", "n_docs",
                 "avg_len", "rungs", "arrays")

    def __init__(self, key, tag, terms, df, doc_ids, n_docs, avg_len,
                 rungs, arrays):
        self.key = key
        self.tag = tag
        self.terms = terms          # K.enc_str(term) -> term number
        self.df = df                # [T] int32
        self.doc_ids = doc_ids      # doc number -> record id value
        self.n_docs = n_docs        # max(stats docs, 1), as the host path
        self.avg_len = avg_len
        self.rungs = rungs
        self.arrays = arrays        # what `ft_load` ships: indptr, docs,
        #                             tf, dl (kept for a re-ship)


class _Rider:
    __slots__ = ("snap", "packed", "cap", "k", "n", "slots", "idf",
                 "consts", "weights", "k1", "b")

    def __init__(self, snap, packed, cap, k, n, slots, idf, consts, weights,
                 k1, b):
        self.snap = snap
        self.packed = packed        # [1 + 2T] int32
        self.cap = cap
        self.k = k
        self.n = n
        self.slots = slots          # query-order term -> device slot
        self.idf = idf              # query-order idf (f64)
        self.consts = consts
        self.weights = weights
        self.k1 = k1
        self.b = b


_lock = threading.Lock()
_BATCHER = None


def _snapshots(ds) -> dict:
    c = getattr(ds, "_ft_device", None)
    if c is None:
        c = ds._ft_device = {}
    return c


def device_leg(idef, mt, query: str, ctx, n: int):
    """The FtResult of the top-n leg on the device, or None where the
    host answers (its search is counted there)."""
    from surrealdb_tpu.device import get_supervisor
    from surrealdb_tpu.idx import fulltext as ft
    from surrealdb_tpu.idx.vector import DEVICE_MIN_ROWS, _route

    if mt.boolean != "AND" or not 1 <= n <= FT_MAX_N:
        return None
    sup = get_supervisor()
    if _route(sup) != "device":
        return None
    t0 = time.perf_counter_ns()
    ns, db = ctx.need_ns_db()
    tb, ix = idef.tb, idef.name
    if getattr(ctx.txn, "write", False) \
            and ft._txn_wrote(ctx.txn, ft._ver_key(ns, db, tb, ix)):
        return None
    stats = ctx.txn.get_val(ft._stats_key(ns, db, tb, ix)) or {"docs": 0}
    if stats["docs"] < DEVICE_MIN_ROWS:
        return None
    az = ft.get_analyzer(idef.fulltext.get("analyzer"), ctx)
    terms = list(dict.fromkeys(
        tok[0] for tok in ft.analyze(az, query, stage="query") if tok[0]))
    if not terms or len(terms) > 64:
        return None
    snap = _snapshot(idef, ctx, ns, db)
    nums = [snap.terms.get(K.enc_str(t)) for t in terms]
    if any(x is None for x in nums):
        # AND over a term no document holds: no rows
        sup.note_ft_search(on_device=True)
        res = ft.FtResult([], {})
        stage_record("ft_search", time.perf_counter_ns() - t0)
        return res
    hits = _batcher().submit(_make_rider(snap, idef, nums, n))
    if hits is None:
        return None
    sup.note_ft_search(on_device=True)
    res = ft.FtResult([(_rid(tb, snap.doc_ids[d]), s) for d, s in hits], {})
    stage_record("ft_search", time.perf_counter_ns() - t0)
    return res


def _rid(tb, rid_id):
    from surrealdb_tpu.val import RecordId

    return RecordId(tb, rid_id)


def _idf(n_docs: int, df: int) -> float:
    """`_ft_search_impl`'s idf: clamped at 0, so a term in more than
    half the documents still has to match and adds nothing."""
    return max(math.log((n_docs - df + 0.5) / (df + 0.5)), 0.0)


def _make_rider(snap: Snapshot, idef, nums: list, n: int) -> _Rider:
    """The packed row: live terms, term numbers rarest first, weights."""
    from surrealdb_tpu.device.ftstore import rung_for, term_bucket

    k1, b = idef.fulltext.get("bm25", (1.2, 0.75))
    k1, b = float(np.float32(k1)), float(np.float32(b))
    dfs = [int(snap.df[x]) for x in nums]
    idf = [_idf(snap.n_docs, df) for df in dfs]
    order = sorted(range(len(nums)), key=lambda j: (dfs[j], j))
    slots = [0] * len(nums)
    for s, j in enumerate(order):
        slots[j] = s
    t = term_bucket(len(nums))
    packed = np.zeros(1 + 2 * t, np.int32)
    packed[0] = len(nums)
    packed[1:1 + len(nums)] = [nums[j] for j in order]
    weights = np.asarray([idf[j] * (k1 + 1) for j in order], np.float32)
    packed[1 + t:1 + t + len(nums)] = weights.view(np.int32)
    cap = rung_for(dfs[order[0]], snap.rungs)
    k = _places(n, cap)
    consts = (float(np.float32(k1)), float(np.float32(1 - b)),
              float(np.float32(b / max(snap.avg_len, 1e-9))))
    return _Rider(snap, packed, cap, k, n, slots, idf, consts,
                  weights.astype(np.float64), k1, b)


# -- the snapshot ----------------------------------------------------------


def _snapshot(idef, ctx, ns, db) -> Snapshot:
    """The snapshot of the version this transaction reads, built from KV
    truth first where there is none (stage `ft_build`; its ship is the
    first dispatch's, `_call_loaded`)."""
    from surrealdb_tpu.device.ftstore import rungs_for
    from surrealdb_tpu.idx import fulltext as ft

    tb, ix = idef.tb, idef.name
    ver = ctx.txn.get_val(ft._ver_key(ns, db, tb, ix)) or 0
    cache = _snapshots(ctx.ds)
    ck = (ns, db, tb, ix)
    snap = cache.get(ck)
    if snap is not None and snap.tag == [ver]:
        return snap
    with _lock:
        snap = cache.get(ck)
        if snap is not None and snap.tag == [ver]:
            return snap
        t0 = time.perf_counter_ns()
        stats = ctx.txn.get_val(ft._stats_key(ns, db, tb, ix)) or {
            "docs": 0, "total_len": 0}
        n_docs = max(stats["docs"], 1)
        terms, indptr, docs, tf, dl, doc_ids = _read_index(
            ctx.txn, ns, db, tb, ix)
        df = np.diff(indptr).astype(np.int32)
        snap = Snapshot(f"ft/{ns}/{db}/{tb}/{ix}", [ver], terms, df,
                        doc_ids, n_docs, stats["total_len"] / n_docs,
                        rungs_for(df, len(dl)),
                        (indptr, docs, tf, dl))
        cache[ck] = snap
        stage_record("ft_build", time.perf_counter_ns() - t0)
        return snap


def _prewarm(sup, snap: Snapshot, terms: int, k: int):
    """After a ship, the runner compiles every rung's programs for
    `terms` slots and `k` places (`ftstore.prewarm_shapes`), one
    `ft_prewarm` call a program, on a thread of its own: the dispatch
    that shipped is not held and searches are served between the
    compiles. Best effort, as every prewarm
    (`DeviceSupervisor._prewarm_async`); none inline."""
    from surrealdb_tpu.device.ftstore import prewarm_shapes

    if sup.mode == "inline":
        return
    meta = {"key": snap.key, "tag": list(snap.tag), "terms": terms, "k": k}

    def warm():
        for cap, riders in prewarm_shapes(snap.rungs):
            try:
                t, _m, _b = sup.call("ft_prewarm",
                                     dict(meta, cap=cap, riders=riders),
                                     timeout_s=sup.load_timeout_s)
            except Exception:
                return
            if t != "ok":
                return

    threading.Thread(target=warm, daemon=True, name="ft-prewarm").start()


def _read_index(txn, ns, db, tb, ix):
    """The index's postings as CSR from KV truth: (term key -> number,
    indptr, docs, tf, dl, doc ids). A term is known by its key's suffix
    (`K.enc_str(term)`); doc numbers follow record-key order (the `bl`
    keys'), and a term's docs ascend."""
    from surrealdb_tpu.kvs.api import deserialize
    from surrealdb_tpu.native import ftpost

    pre = K.ix_state(ns, db, tb, ix, b"bl")
    plen = len(pre)
    doc_keys, dl_raw = [], []
    for k, raw in txn.btx.scan(*K.prefix_range(pre)):
        doc_keys.append(bytes(k[plen:]))
        dl_raw.append(raw)
    lens = {raw: deserialize(raw) or 0 for raw in set(dl_raw)}
    dl = np.asarray([lens[raw] for raw in dl_raw], np.int32)
    pre = K.ix_state(ns, db, tb, ix, b"bf")
    plen = len(pre)
    names, raws = [], []
    for k, raw in txn.btx.scan(*K.prefix_range(pre)):
        names.append(bytes(k[plen:]))
        raws.append(raw)
    counts, rids, post_tf = ftpost.parse(raws)
    ids = _int_ids(doc_keys) if rids is not None else None
    if ids is not None:
        # the ids ascend, each once: consecutive ids number the docs
        # themselves, from the first
        if len(ids) and ids[-1] - ids[0] == len(ids) - 1:
            post_doc = rids - ids[0]
        else:
            post_doc = np.searchsorted(ids, rids)
        doc_ids = ids.tolist()
    else:
        docno = {k: i for i, k in enumerate(doc_keys)}
        doc_ids = [None] * len(doc_keys)
        post_doc, post_tf, counts = [], [], []
        for raw in raws:
            post = deserialize(raw) or {}
            counts.append(len(post))
            for ridk, (tf, _offs, rid_id) in post.items():
                d = docno[ridk]
                doc_ids[d] = rid_id
                post_doc.append(d)
                post_tf.append(tf)
        post_doc = np.asarray(post_doc, np.int64)
        counts = np.asarray(counts, np.int64)
    indptr = np.zeros(len(names) + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    post_doc = np.asarray(post_doc, np.int32)
    post_tf = np.asarray(post_tf, np.int32)
    # a term's postings are in the order its documents were indexed:
    # sort only where some term's are not ascending already
    step = np.diff(post_doc) <= 0
    step[indptr[1:-1] - 1] = False
    if step.any():
        term_of = np.repeat(np.arange(len(names), dtype=np.int64), counts)
        order = np.lexsort((post_doc, term_of))
        post_doc, post_tf = post_doc[order], post_tf[order]
    terms = {t: i for i, t in enumerate(names)}
    return terms, indptr.astype(np.int32), post_doc, post_tf, dl, doc_ids


def _int_ids(doc_keys: list):
    """The record ids of `bl` key suffixes that all encode integers under
    2**53 (`K.enc_value`: the number tag, the f64 order-preserving, a zero
    tie-break), ascending as the keys are; else None."""
    n = len(doc_keys)
    raw = b"".join(doc_keys)
    if len(raw) != 17 * n:
        return None
    rows = np.frombuffer(raw, np.uint8).reshape(n, 17)
    if n and ((rows[:, 0] != K.TAG_NUMBER).any()
              or (rows[:, 9:] != np.frombuffer(K.enc_i64(0), np.uint8))
              .any()):
        return None
    u = rows[:, 1:9].copy().view(">u8")[:, 0]
    top = np.uint64(1 << 63)
    bits = np.where(u & top, u ^ top, ~u)
    f = bits.astype(np.uint64).view(np.float64)
    ids = f.astype(np.int64)
    if (ids != f).any() or (np.abs(f) >= 2.0 ** 53).any():
        return None
    return ids


# -- the batcher -----------------------------------------------------------


def _batcher():
    global _BATCHER
    if _BATCHER is None:
        from surrealdb_tpu.device import DeviceOpError, DeviceUnavailable
        from surrealdb_tpu.device.batcher import DeviceBatcher

        with _lock:
            if _BATCHER is None:
                _BATCHER = DeviceBatcher(
                    dispatch=_dispatch, fallback=_fallback,
                    retryable=(DeviceUnavailable, DeviceOpError))
    return _BATCHER


def _fallback(rider):
    """Per-rider degrade after device trouble: the host answers."""
    from surrealdb_tpu.device import get_supervisor

    get_supervisor().note_fallback()
    return None


def _dispatch(riders: list) -> list:
    """One batch: riders of one shipped version and scoring constants
    share ONE `ft_bm25` call, grouped by (capacity, k); a rider that
    overflowed its rung goes round again. Returns each rider's hits
    [(doc number, f32 score)], or None where the host has to answer."""
    from surrealdb_tpu.device import get_supervisor
    from surrealdb_tpu.device.ftstore import rung_for

    sup = get_supervisor()
    if not sup.fast_path():
        raise sup.unavailable(f"device {sup.state}")
    out = [None] * len(riders)
    stores: dict = {}
    for i, r in enumerate(riders):
        stores.setdefault((id(r.snap), r.consts), []).append(i)
    post_ns = 0
    for (_snap, consts), pending in stores.items():
        snap = riders[pending[0]].snap
        while pending:
            groups: dict = {}
            for i in pending:
                r = riders[i]
                groups.setdefault((r.cap, r.k, len(r.packed)), []).append(i)
            shapes = list(groups)
            bufs = [np.stack([riders[i].packed for i in groups[s]])
                    for s in shapes]
            reply = _call_loaded(sup, snap, {
                "groups": [[cap, k] for cap, k, _w in shapes],
                "consts": list(consts)}, bufs)
            t0 = time.monotonic_ns()
            pending = []
            for s, rows in zip(shapes, reply):
                for i, row in zip(groups[s], rows):
                    r = riders[i]
                    if row[0] < 0:
                        r.cap = rung_for(int(-1 - row[0]), r.snap.rungs)
                        r.k = _places(r.n, r.cap)
                        pending.append(i)
                    else:
                        out[i] = _rescore(r, row)
            post_ns += time.monotonic_ns() - t0
    stage_record("ft_post", post_ns)
    return out


def _call_loaded(sup, snap: Snapshot, meta: dict, bufs: list):
    """`ft_bm25` on the snapshot's version: shipped first where the
    runner lacks it (stage `ft_build`, counted in `ft_ships`, then the
    prewarm for this call's first shape), once more where it answers
    `stale` (a restarted or evicting runner)."""
    for _attempt in (0, 1):
        t0 = time.perf_counter_ns()
        shipped = []

        def loader():
            shipped.append(True)
            sup.note_ft_ship()
            return "ft_load", {}, list(snap.arrays)

        sup.ensure_loaded(snap.key, snap.tag, loader)
        if shipped:
            stage_record("ft_build", time.perf_counter_ns() - t0)
            _prewarm(sup, snap, (bufs[0].shape[1] - 1) // 2,
                     meta["groups"][0][1])
        t, _m, out = sup.call("ft_bm25", dict(
            meta, key=snap.key, tag=list(snap.tag)), bufs)
        if t != "stale":
            return out
        sup.forget(snap.key)
    raise sup.unavailable("full-text block thrashing")


def _rescore(r: _Rider, row: np.ndarray):
    """[(doc number, f32 score)] of the rider's top n, or None where the
    returned places cannot prove them (module docstring)."""
    t = (len(r.packed) - 1) // 2
    k, n_terms = r.k, int(r.packed[0])
    count = int(row[0])
    got = min(count, k)
    ids = row[1:1 + got]
    f32 = row[1 + k:1 + k + got].view(np.float32)
    dls = row[1 + 2 * k:1 + 2 * k + got]
    tf = row[1 + 3 * k:1 + 3 * k + t * k].reshape(t, k)[:, :got]
    k1, b = r.k1, r.b
    avg = max(r.snap.avg_len, 1e-9)
    scores = []
    for p in range(got):
        dl = int(dls[p])
        length_norm = (1 - b) + (b / avg) * dl
        sc = 0.0
        for j, s in enumerate(r.slots):
            tfj = int(tf[s, p])
            idf = r.idf[j]
            if idf == 0.0 or tfj <= 0:
                part = 0.0
            else:
                tf_prime = 1.0 + math.log(tfj)
                part = idf * (k1 + 1) * tf_prime / (
                    tf_prime + k1 * length_norm)
            sc = sc + part
        scores.append(sc)
    order = sorted(range(got), key=lambda p: (-scores[p], int(ids[p])))
    keep = order[:r.n]
    if count > k and keep and float(f32[got - 1]) + _err_bound(
            n_terms, r.weights) >= scores[keep[-1]]:
        return None
    return [(int(ids[p]), float(np.float32(scores[p]))) for p in keep]
