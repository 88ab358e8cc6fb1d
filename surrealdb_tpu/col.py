"""Persistent per-table vector column store.

Reference role: the compiled scan/decode path of the reference executor
(core/src/exec/operators/scan) — brute-force vector scoring over a table
should not deserialize every document in the host language per query.
This module keeps an (ids, float32 matrix) column extracted from a
table's records, built by the native C++ kernel
(native/memtable.cpp sdb_scan_extract_f32) when the datastore runs on
the native memtable, or by a Python scan otherwise. Columns are cached
on the Datastore keyed by the table's write version (the same
post-commit counter the graph CSR cache rides), so repeat queries skip
extraction entirely and any committed write invalidates the cache.

A column also owns a device block: `device_topk` ships `mat` to the
supervised runner once a table version (and metric) as an EXACT f32
store (`cfg["exact"]`, device/vecstore.py), and every no-index scan
of the table (`VecTopKScanOp`, `exec/vops.py _fused_dispatch`) is a
`vec_knn` on that resident block: rows never travel with a query.
"""

from __future__ import annotations

import threading
import uuid

import numpy as np

from surrealdb_tpu import key as K

_BUILD_LOCK = threading.Lock()

class VectorColumn:
    __slots__ = ("version", "ids", "mat", "bad_ids", "ids_enc",
                 "_norms", "_no_zero_row", "dev_base", "superseded")

    def __init__(self, version, ids, mat, bad_ids, ids_enc=None):
        self.version = version
        self.ids = ids          # decoded record-id keys, row-aligned
        self.mat = mat          # (n, dim) float32
        self.bad_ids = bad_ids  # record ids whose field didn't conform
        # encoded id key suffixes (key order) — the row-alignment token
        # shared with exec/batch.py TableColumns for fused filtered KNN
        self.ids_enc = ids_enc
        self._norms = None
        self._no_zero_row = None
        # what the device block's key is made of (`device_key`): one
        # random name a (datastore, table, field, dim), the same for
        # every version, so that a ship replaces the version before;
        # set by `get_vector_column`
        self.dev_base = None
        # a newer version of this column has been built: riders that
        # still hold this one score on the host instead of shipping a
        # block that is already replaced
        self.superseded = False

    def norms(self):
        """Per-row L2 norms, computed once per version — the cosine
        scoring path's dominant recompute (bit-identical: the cached
        array IS np.linalg.norm(mat, axis=1))."""
        if self._norms is None:
            self._norms = np.linalg.norm(self.mat, axis=1)
        return self._norms

    def device_key(self, metric: str, p: float = 3.0) -> str:
        which = f"{metric}:{p}" if metric == "minkowski" else metric
        return f"vec/col/{self.dev_base}/{which}"

    def servable(self, metric) -> bool:
        """Whether the device's exact block can answer for this column
        under `metric` (None: an order no metric gives): enough rows to
        be worth a dispatch, the current version, and for cosine no
        zero row (the host path ranks those as NaN, the kernel as
        similarity 0)."""
        from surrealdb_tpu import cnf

        if (metric is None or self.superseded
                or self.dev_base is None
                or self.mat.shape[0] < cnf.KNN_DEVICE_MIN_ROWS):
            return False
        if metric == "cosine" and self._no_zero_row is None:
            # squared norms without an [n, dim] temporary
            self._no_zero_row = bool(
                (np.einsum("ij,ij->i", self.mat, self.mat) > 0).all())
        return metric != "cosine" or self._no_zero_row


def query_batch(queries: list) -> np.ndarray:
    """[B, D] f32 from each rider's query as f32 bytes: one join and a
    view, not `np.stack` (PERF.md section 6, PR 26)."""
    return np.frombuffer(b"".join(queries), np.float32).reshape(
        len(queries), -1)


def device_topk(col: VectorColumn, metric: str, qs: np.ndarray, k: int,
                p: float = 3.0):
    """[B, D] f32 queries against the column's resident exact block:
    (dists f32 [B, k], row numbers i32 [B, k]), ascending distance
    (cosine distance, euclidean distance, minus the dot product), every
    row scored in f32. Ships the block first if the runner lacks this
    version, once more if it answers `stale`; the block of the version
    before is dropped ahead of the ship, so two versions of a table are
    never resident together. Raises as `DeviceSupervisor.call` does."""
    from surrealdb_tpu.device import get_supervisor
    from surrealdb_tpu.idx.vector import device_cfg

    sup = get_supervisor()
    key = col.device_key(metric, p)
    tag = [int(col.version)]

    def loader():
        sup.note_col_ship(col.mat.nbytes)
        sup.call("vec_drop", {"key": key})
        return "vec_load", {
            "metric": metric, "mink_p": float(p),
            "cfg": dict(device_cfg(), exact=True),
        }, [
            np.ascontiguousarray(col.mat),
            np.ones(col.mat.shape[0], np.uint8),
        ]

    meta = {"key": key, "tag": tag, "k": int(min(k, col.mat.shape[0]))}
    for _attempt in (0, 1):
        sup.ensure_loaded(key, tag, loader)
        t, _meta, bufs = sup.call("vec_knn", meta, [qs])
        if t != "stale":
            return bufs[0], bufs[1]
        # runner evicted or restarted between load and query
        sup.forget(key)
    raise sup.unavailable("column block thrashing")


def _cache(ds) -> dict:
    c = getattr(ds, "_vector_columns", None)
    if c is None:
        c = ds._vector_columns = {}
    return c


def _block_name(ds, ck) -> str:
    """The random name of a column's device block, kept beside the
    cache and not in it: evicting the columns (`exec/batch.py
    store_evict`) must not orphan their blocks under names nobody
    knows."""
    names = getattr(ds, "_vector_block_names", None)
    if names is None:
        names = ds._vector_block_names = {}
    return names.setdefault(ck, uuid.uuid4().hex[:16])


def get_vector_column(ctx, tb: str, field: str, dim: int):
    """The (ids, matrix, bad_ids) column for `tb.field`, or None when the
    shape can't be served (dirty txn overlay, nested field, no backend
    support). Commit-consistent: keyed by the table write version."""
    ns, db = ctx.need_ns_db()
    gk = (ns, db, tb)
    # uncommitted writes to this table in the current txn would be
    # invisible to the committed-state column; fail CLOSED on write
    # buffers we cannot see (ShardTx per-shard subs, unknown engines)
    if gk in getattr(ctx.txn, "_graph_dirty", ()):
        return None
    pre = K.record_prefix(ns, db, tb)
    beg, end = K.prefix_range(pre)
    from surrealdb_tpu.exec.batch import txn_range_clean

    if not txn_range_clean(ctx.txn, beg, end):
        return None
    # version is read BEFORE the build's fresh transaction opens: the
    # built state can only be newer than the stamp, so a concurrent
    # commit in between costs one rebuild next query — never staleness
    version = ctx.ds.graph_versions.get(gk, 0)
    ck = (ns, db, tb, field, dim)
    cache = _cache(ctx.ds)
    hit = cache.get(ck)
    if hit is not None and hit.version == version:
        return hit
    # one build a version: callers that arrive together after a write
    # wait for the first one's column instead of each extracting (and
    # then shipping) the whole table
    with _BUILD_LOCK:
        version = ctx.ds.graph_versions.get(gk, 0)
        hit = cache.get(ck)
        if hit is not None and hit.version == version:
            return hit
        # build from a FRESH transaction (committed state only) — the
        # caller's snapshot may predate commits already counted in
        # `version` (same pattern as graph/csr.py build())
        txn = ctx.ds.transaction(write=False)
        try:
            col = _build(ctx, txn, tb, field, dim, beg, end, pre)
        finally:
            txn.cancel()
        if col is None:
            return None
        col.version = version
        col.dev_base = _block_name(ctx.ds, ck)
        if hit is not None:
            hit.superseded = True
        cache[ck] = col
    return col


def _build(ctx, txn, tb, field, dim, beg, end, pre):
    btx = getattr(txn, "btx", None)
    table = getattr(getattr(btx, "store", None), "table", None)
    snap = getattr(btx, "snap", None)
    if table is not None and snap is not None and hasattr(
        table, "scan_extract_f32"
    ):
        est = table.count_range_at(beg, end, snap)
        mat, key_sfx, bad_sfx = table.scan_extract_f32(
            beg, end, snap, field.encode(), dim, len(pre), est
        )
        ids = [K.dec_value(s)[0] for s in key_sfx]
        bad = [K.dec_value(s)[0] for s in bad_sfx]
        return VectorColumn(0, ids, mat, bad, ids_enc=list(key_sfx))
    # portable fallback: Python scan + decode (still cached by version)
    from surrealdb_tpu.kvs.api import deserialize

    ids, rows, bad, ids_enc = [], [], [], []
    for k, raw in txn.scan(beg, end):
        doc = deserialize(raw)
        v = doc.get(field) if isinstance(doc, dict) else None
        ok = isinstance(v, list) and len(v) == dim
        if ok:
            try:
                arr = np.asarray(v, np.float32)
            except (TypeError, ValueError):
                ok = False
        if ok and arr.ndim == 1 and arr.dtype.kind in ("i", "f"):
            ids.append(K.dec_value(k[len(pre):])[0])
            ids_enc.append(k[len(pre):])
            rows.append(arr)
        else:
            bad.append(K.dec_value(k[len(pre):])[0])
    mat = (
        np.stack(rows).astype(np.float32)
        if rows else np.empty((0, dim), np.float32)
    )
    return VectorColumn(0, ids, mat, bad, ids_enc=ids_enc)
