"""TPU-resident vector index.

Replaces the reference's HNSW graph walk (idx/trees/hnsw/, hot loop
layer.rs:184-223: per-neighbor async KV fetch + scalar distance) with a
device-resident flat store: batched distance (`einsum` on the MXU) +
`jax.lax.top_k`, blockwise for big stores, mesh-sharded for multi-chip
(SURVEY.md §7 step 4). Exact search ⇒ recall@10 = 1.0 ≥ the 0.95 target.

Consistency model mirrors hnsw/index.rs's two-phase design: the KV `he` keys
(rid→vector) written inside the caller's transaction are the source of
truth; the device block cache is an overlay rebuilt/extended when a search
observes a newer KV version — "device blocks are a cache rebuilt from KV"
(SURVEY.md §5 checkpoint/resume).

Fault isolation: this module NEVER imports jax. Device execution goes
through the supervised DeviceRunner subprocess (surrealdb_tpu.device):
the search path ships raw row blocks + query batches over the
supervisor's RPC, and degrades to the exact numpy host path whenever
the device is cold, degraded, or out of budget — a wedged TPU can stall
the runner process, never a query worker thread.
"""

from __future__ import annotations

import threading
import time
import uuid

import numpy as np

from surrealdb_tpu import key as K
from surrealdb_tpu import resource
from surrealdb_tpu.device.batcher import DeviceBatcher
from surrealdb_tpu.err import SdbError
from surrealdb_tpu.telemetry import stage_record
from surrealdb_tpu.utils.rwlock import RWLock
from surrealdb_tpu.val import NONE, RecordId, is_truthy

from surrealdb_tpu import cnf

# device-search threshold: below this, numpy on host beats dispatch overhead
DEVICE_MIN_ROWS = cnf.KNN_DEVICE_MIN_ROWS
# blockwise scan threshold (rows) to bound [B, N] materialization
BLOCK_ROWS = cnf.KNN_BLOCK_ROWS
# rows one delta to a resident block may carry (`vec_append`); a sync
# gap that touched more ships the whole block, as every write did once
DELTA_MAX_ROWS = 4096


def _vec_dtype(params) -> type:
    # the index vector type governs storage precision; the reference's
    # parser defaults to F32 (syn define.rs:1107 VectorType::F32)
    vt = (params or {}).get("vector_type", "f32")
    return np.float32 if str(vt).lower() in ("f32", "i16", "i32") else np.float64


def _as_vector(v, dim, what, dtype=np.float64):
    if not isinstance(v, (list, tuple)):
        raise SdbError(f"Incorrect vector value for {what}")
    try:
        arr = np.asarray(v, dtype=dtype)
    except (TypeError, ValueError):
        raise SdbError(f"Incorrect vector value for {what}")
    if arr.ndim != 1 or arr.shape[0] != dim:
        raise SdbError(
            f"Incorrect vector dimension ({arr.shape[0] if arr.ndim == 1 else '?'}). Expected a vector of {dim} dimension."
        )
    return arr


def vector_index_update(idef, rid: RecordId, before, after, ctx):
    """Write-side maintenance: persist rid→vector under `he` state keys
    (reference hnsw/elements.rs) inside the caller's transaction."""
    ns, db = ctx.need_ns_db()
    dim = idef.hnsw["dimension"]
    col = idef.cols[0]
    from surrealdb_tpu.exec.eval import evaluate

    dtype = _vec_dtype(idef.hnsw)
    key = K.ix_state(ns, db, rid.tb, idef.name, b"he", K.enc_value(rid.id))
    vkey = K.ix_state(ns, db, rid.tb, idef.name, b"vn")
    old_vec = None
    new_vec = None
    if isinstance(before, dict):
        v = evaluate(col, ctx.with_doc(before, rid))
        if v is not NONE and v is not None:
            old_vec = v
    if isinstance(after, dict):
        v = evaluate(col, ctx.with_doc(after, rid))
        if v is not NONE and v is not None:
            new_vec = _as_vector(v, dim, f"index {idef.name}", dtype)
    if new_vec is None and old_vec is None:
        return
    # version allocation is process-atomic (ds.lock): concurrent writers
    # can't collide on a log slot; a cancelled txn burns a version. The
    # datastore remembers which (`_note_version`): sync() steps over a
    # gap it knows to be burnt, and resolves any other with a rebuild.
    # The KV read happens BEFORE the lock — on a sharded store it is a
    # remote round trip, and ds.lock must never be held across one.
    stored = ctx.txn.get_val(vkey) or 0
    with ctx.ds.lock:
        counters = getattr(ctx.ds, "_ix_versions", None)
        if counters is None:
            counters = {}
            ctx.ds._ix_versions = counters
        ckey = (ns, db, rid.tb, idef.name)
        ver = max(counters.get(ckey, 0), stored) + 1
        counters[ckey] = ver
        _note_version(ctx.ds, ctx.txn, ckey, ver)
    log_key = K.ix_state(ns, db, rid.tb, idef.name, b"hl", K.enc_u64(ver))
    if new_vec is not None:
        ctx.txn.set_val(key, new_vec.tobytes())
        ctx.txn.set_val(log_key, ("set", rid.id, new_vec.tobytes()))
    else:
        ctx.txn.delete(key)
        ctx.txn.set_val(log_key, ("del", rid.id, None))
    ctx.txn.set_val(vkey, ver)


# burnt versions remembered an index, at most (a store that nobody
# searches never reads them back; past this they are forgotten, and the
# gap they leave is resolved by a rebuild, as every gap once was)
BURNT_MAX = 4096


def _note_version(ds, txn, ckey, ver: int):
    """Book-keeping for the op log's gaps (caller holds `ds.lock`).
    Writers of one index conflict on its version key, so of the
    transactions that overlap one commits and the rest are cancelled:
    a version that is allocated and not committed when a higher one is
    (`open`), or whose transaction was cancelled (`burnt`), will never
    be in the log. `_read_log` steps over exactly those; a gap of any
    other origin (a log trimmed by another node's rebuild, a statement
    rolled back inside a transaction that then committed) still forces
    the rebuild."""
    book = ds.__dict__.setdefault("_ix_gaps", {}).setdefault(
        ckey, {"open": set(), "burnt": set()})
    book["open"].add(ver)
    mine = txn.__dict__.get("_ix_allocs")
    if mine is not None:
        mine.append((ckey, ver))
        return
    mine = txn._ix_allocs = [(ckey, ver)]

    def settle(burnt: bool):
        with ds.lock:
            for key, v in mine:
                b = ds._ix_gaps[key]
                b["open"].discard(v)
                if burnt:
                    if len(b["burnt"]) >= BURNT_MAX:
                        b["burnt"].clear()
                    b["burnt"].add(v)

    txn.on_commit(lambda: settle(False))
    txn.on_cancel(lambda: settle(True))


def _exact_mxu_distances(metric: str, xs, q):
    """Exact f64 distances for the device-rankable metrics, shared by the
    single-query host path and the batched rescore. `xs` is [..., D] and
    `q` broadcasts against it; reduction is over the last axis. The
    reference computes distances in f64 regardless of stored type
    (trees/vector.rs)."""
    if metric == "euclidean":
        return np.linalg.norm(xs - q, axis=-1)
    if metric == "cosine":
        dots = (xs * q).sum(axis=-1)
        denom = np.maximum(
            np.linalg.norm(xs, axis=-1) * np.linalg.norm(q, axis=-1), 1e-300
        )
        return 1.0 - dots / denom
    if metric == "dot":
        return -(xs * q).sum(axis=-1)
    raise SdbError(f"unsupported device metric {metric}")


class _Coalescer(DeviceBatcher):
    """Self-clocking cross-query dynamic batcher over one vector index.

    The first searcher dispatches immediately (no added latency when
    idle); searches arriving while a device call is in flight queue up
    and ride the NEXT dispatch as one batched kernel call — so device
    batch size grows with client concurrency, inference-server style.
    This is how concurrent `SELECT … <|k|>` statements (e.g. from the
    threaded HTTP/WS server) share MXU work instead of serializing
    per-query dispatches. Reference contrast: hnsw/index.rs walks the
    graph per query under an RwLock; here concurrency *increases*
    device efficiency.

    The batching mechanics (pipelined dispatch, deadline withdrawal,
    per-rider attribution) live in `device/batcher.py`; this class
    binds them to one index's engine entry: batch kernel =
    `index.knn_batch` (device or batched host, routed by platform),
    first fallback = the SAME batched host kernel, last-resort
    fallback = per-rider host single search (one poisoned rider can
    never fail its batchmates)."""

    def __init__(self, index):
        from surrealdb_tpu.device import DeviceOpError, DeviceUnavailable

        self.index = index
        super().__init__(
            dispatch=self._dispatch,
            fallback_batch=self._fallback_batch,
            fallback=self._fallback_one,
            retryable=(DeviceUnavailable, DeviceOpError),
        )

    def search(self, qv: np.ndarray, k: int):
        return self.submit((qv, k))

    def _read_lock(self):
        # TpuVectorIndex carries a reader-writer lock so pipelined
        # dispatches can score concurrently while cache sync stays
        # exclusive; test doubles may only have the legacy RLock
        rw = getattr(self.index, "rw", None)
        if rw is not None:
            return rw.read()
        return self.index.lock

    def _stack(self, payloads) -> np.ndarray:
        """The riders' vectors as one C-contiguous [B, D] array of the
        index's dtype, built from their buffers: one `bytes.join`, one
        `frombuffer`. numpy gives the interpreter lock up around every
        copy of more than 500 elements, and among the request threads
        the dispatcher then queues to get it back once a rider
        (`np.stack`, or a row assignment into a preallocated buffer);
        a join of buffers never lets go. `_as_vector` hands every rider
        a contiguous 1-D array of that dtype already, so the conversion
        copies nothing there. The result is read-only."""
        dtype = getattr(self.index, "dtype", None)
        if dtype is None:  # a test double: the riders' own
            dtype = payloads[0][0].dtype
        rows = [np.ascontiguousarray(q, dtype) for q, _k in payloads]
        return np.frombuffer(b"".join(rows), dtype).reshape(
            len(rows), rows[0].size
        )

    def _dispatch(self, payloads):
        kmax = max(k for _q, k in payloads)
        qvs = self._stack(payloads)
        # the routed engine entry when the index has one; test doubles
        # expose only the raw device kernel
        fn = getattr(self.index, "knn_batch", None) \
            or self.index._device_knn_batch
        with self._read_lock():
            results = fn(qvs, kmax)
        return [pairs[:k] for (_q, k), pairs in zip(payloads, results)]

    def _fallback_batch(self, payloads):
        # the device couldn't serve this batch: answer the WHOLE batch
        # from one batched exact host kernel (a [B, N] BLAS pass still
        # beats B single passes — the degraded path batches too)
        from surrealdb_tpu.device import get_supervisor

        get_supervisor().note_fallback()
        kmax = max(k for _q, k in payloads)
        qvs = self._stack(payloads)
        with self._read_lock():
            results = self.index._host_knn_multi(qvs, kmax)
        return [pairs[:k] for (_q, k), pairs in zip(payloads, results)]

    def _fallback_one(self, payload):
        q, k = payload
        with self._read_lock():
            return self.index._host_knn_single(q, k)


def device_routed() -> bool:
    """Routing policy for the scoring engine (SURREAL_KNN_HOST_BATCH),
    shared by the index engine and the no-index column scan (col.py):
    dispatch to the device runner on real accelerators; when the
    "device" IS this host's CPU, the batched BLAS host path wins —
    offloading numpy-speed kernels through jax only adds dispatch
    overhead. `device` forces the old always-dispatch behavior,
    `host` forces host scoring."""
    from surrealdb_tpu.device import get_supervisor

    mode = cnf.KNN_HOST_BATCH
    if mode == "host":
        return False
    sup = get_supervisor()
    if not sup.fast_path():
        if sup.mode != "off":
            # device wanted but cold/degraded/disabled: host serves
            sup.note_fallback()
        return False
    if mode == "device":
        return True
    if sup.platform == "cpu":
        # the "accelerator" is this host's own CPU (inline debug
        # mode or a CPU-platform runner): one BLAS pass here beats
        # shipping numpy-speed work through jax/IPC
        sup.note_host_routed()
        return False
    return True


def device_cfg() -> dict:
    """Kernel budgets shipped per dispatch (read at call time so the
    serving process's configuration governs the runner)."""
    return {
        "hbm_budget": cnf.KNN_HBM_BUDGET_BYTES,
        "score_budget": cnf.KNN_SCORE_BUDGET_ELEMS,
        "query_chunk": cnf.KNN_QUERY_CHUNK,
        "int8_oversample": cnf.KNN_INT8_OVERSAMPLE,
        "block_rows": BLOCK_ROWS,
    }


class TpuVectorIndex:
    """Per-(ns,db,tb,ix) device block cache + search engine."""

    def __init__(self, ns, db, tb, ix, params: dict, key_range=None,
                 label: str = ""):
        self.key = (ns, db, tb, ix)
        self.params = params
        self.dim = params["dimension"]
        # optional [lo, hi) clamp over the `he` element keyspace: a
        # shard-partitioned index (idx/shardvec.py) builds one engine
        # per shard range, each covering only its slice of the rows
        self.key_range = (
            None if key_range is None
            else (bytes(key_range[0]), bytes(key_range[1]))
        )
        self.label = label  # display name for residency/partial reports
        # directory for persisted CAGRA build artifacts (set by
        # get_vector_index from the datastore; None = never persist)
        self.snapshot_dir = None
        from surrealdb_tpu.ops.metrics import normalize_metric

        self.metric, self.mink_p = normalize_metric(
            params.get("distance", "euclidean")
        )
        self.dtype = _vec_dtype(params)
        self.lock = threading.RLock()
        # reader-writer lock over the host arrays: pipelined dispatches
        # score concurrently under read; cache sync mutates under write
        self.rw = RWLock()
        self.version = -1
        self.rids: list = []  # row -> RecordId
        self.row_index: dict = {}  # enc(id) -> row
        self.vecs = np.zeros((0, self.dim), dtype=self.dtype)
        self.valid = np.zeros(0, dtype=bool)  # tombstone mask
        # device blocks live in the supervised DeviceRunner, addressed
        # by (cache key, [version, epoch]); a runner restart or an epoch
        # bump re-ships them from the host arrays (KV truth)
        self._dev_key = f"vec/{uuid.uuid4().hex[:16]}"
        self._dev_epoch = 0
        # the host arrays grow amortised: `vecs` / `valid` are views of
        # the first rows of these buffers (None: the arrays are whole,
        # as a rebuild or a caller's assignment leaves them)
        self._vec_buf = None
        self._valid_buf = None
        # a resident block that grows in place (device/vecstore.py) is
        # written by deltas: the tag this engine last brought the
        # runner to, the rows it held then and the capacity it said it
        # has (known only while the tag is), and the rows below that
        # count overwritten or flipped since. `_drop_device` forgets
        # them: then the whole block goes
        self._dev_tag = None
        self._dev_rows = 0
        self._dev_capacity = None
        # lint: mem-account(at most DELTA_MAX_ROWS row numbers: past that the whole block ships and the set is dropped)
        self._dev_dirty: set = set()
        self._dev_ship_lock = threading.Lock()
        self.rank_mode = None  # last runner-reported ranking mode
        # widest mesh the runner reported serving this engine's blocks
        # on (device/mesh.py; 1 or 0 = legacy single-device stores)
        self._dev_mesh = 0
        self._dev_mesh_ann = 0
        # per-epoch host scoring stats (row norms / squared norms) for
        # the batched BLAS host path; rebuilt lazily after cache sync
        self._host_stats = None
        # quantized graph-ANN overlay (idx/cagra.py): built from a host
        # snapshot for stores past cnf.KNN_ANN_MIN_ROWS, searched by
        # int8 greedy descent + exact re-rank. The flat graph + int8
        # arrays ship to the runner under their own (key, tag) blocks.
        self._ann = None           # built cagra.AnnIndex
        self._ann_state = "idle"   # idle | building | ready
        # rows overwritten since the graph snapshot, stamped with the
        # mutation counter at overwrite time: a build only un-dirties
        # rows whose stamp predates its snapshot (a row overwritten
        # AGAIN mid-build keeps brute-merging)
        self._ann_dirty: dict = {}
        self._ann_mut = 0          # overwrite stamp counter
        # tombstones since the snapshot: deletions poison graph slots
        # (the re-rank filters them), so they count toward staleness
        # like appends/overwrites do
        self._ann_dead = 0
        self._ann_dead_base = 0
        self._ann_gen = 0          # bumped on full repack (row remap)
        self._ann_seq = 0          # device block tag for shipped builds
        self._ann_lock = threading.Lock()
        self._ann_dev_key = f"ann/{uuid.uuid4().hex[:16]}"
        # segmented LSM-style serving (idx/segments.py): lazily created
        # once the store crosses the segmentation floor; None until
        # then (small stores keep the legacy single-graph overlay)
        self._segs = None
        # whole-index ANN rebuilds THIS engine scheduled (the legacy
        # drift treadmill); engine-scoped so churn gates can assert 0
        # without cross-datastore pollution (a module-level aggregate
        # lives in idx/segments.py)
        self.ann_full_rebuilds = 0
        self.coalescer = _Coalescer(self)
        # queries in flight on this engine (between sync and the end of
        # their scoring pass): a pinned engine's host arrays are not
        # evictable — freeing state out from under an active search
        # would silently change its answer, the one degradation the
        # governance layer must never produce
        self._pins = 0
        # the pin count has a lock of its own: a search pins and unpins
        # once each, and must not queue for that behind a sync that
        # holds `self.lock` while it waits for the write lock
        self._pin_lock = threading.Lock()
        # resource governance: every byte this engine derives from KV
        # truth is a tracked, evictable account — the host rows
        # (rebuild = one range scan on the next sync), the CAGRA
        # build (rebuild in the background / reload from a persisted
        # artifact; brute force serves meanwhile), and the per-epoch
        # rank stats (a trivial recompute). Bound methods: the
        # accountant holds them weakly, so a discarded engine is
        # pruned, never pinned.
        acct_label = f"{tb}.{ix}" + (f"[{label}]" if label else "")
        # shard-part engines (key_range set) are TRACKED but their host
        # rows are not byte-evictable: the scatter router syncs and
        # searches a part in separate steps, and a background eviction
        # between them could merge a silently short answer — the one
        # wrongness this layer forbids. Their ann/rank-stats overlays
        # (safe to drop mid-flight) stay evictable; the unsharded
        # engine keeps full evictability behind the pin guard.
        self._mem_vec = resource.register(
            "vec", acct_label, self._vec_mem_bytes,
            evict=self._mem_evict_vec if key_range is None else None,
            owner=self,
        )
        self._mem_ann = resource.register(
            "ann", acct_label, self._ann_mem_bytes,
            evict=self._mem_evict_ann, owner=self,
        )
        self._mem_stats = resource.register(
            "rank_stats", acct_label, self._stats_mem_bytes,
            evict=self._mem_evict_stats, owner=self,
        )

    # -- the tombstone mask and its live-row count --------------------------

    @property
    def valid(self) -> np.ndarray:
        return self._valid

    @valid.setter
    def valid(self, mask: np.ndarray):
        # a whole new mask (empty store, eviction, append, repack):
        # `live` is counted here, once a write, so that no query has to
        # reduce the mask (a reduction over more than 500 flags gives
        # the interpreter lock up, and every request thread then queues
        # to get it back). Single flags change through `_flip`.
        self.live = int(np.count_nonzero(mask))
        self._valid = mask

    def _flip(self, row: int, flag: bool):
        """Tombstone or revive one row whose flag differs from `flag`
        (caller holds the write lock): the mask, the store's live count
        and the count of the row's sealed span move together."""
        segs = self._segs
        if segs is None:
            self._valid[row] = flag
        else:
            segs.flip(row, flag)
        self.live += 1 if flag else -1

    # -- resource accounting ------------------------------------------------

    def _vec_mem_bytes(self) -> int:
        buf = self._vec_buf
        if buf is not None and self.vecs.base is buf:
            return int(buf.nbytes) + int(self._valid_buf.nbytes)
        return int(self.vecs.nbytes) + int(self.valid.nbytes)

    def _ann_mem_bytes(self) -> int:
        ann = self._ann
        return int(ann.nbytes()) if ann is not None else 0

    def _stats_mem_bytes(self) -> int:
        st = self._host_stats
        if st is None:
            return 0
        return sum(int(a.nbytes) for a in st
                   if a is not None and hasattr(a, "nbytes"))

    def _mem_evict_stats(self):
        # per-epoch scoring stats: recomputed lazily by the next BLAS
        # ranking pass — the cheapest possible degrade
        self._host_stats = None

    def _mem_evict_ann(self):
        # drop the built graph; brute force serves (exactly) until the
        # background build — possibly a fast artifact reload — returns.
        # The dirty-row map survives: an in-flight query that captured
        # the old AnnIndex still needs it for its exact tail merge, and
        # row numbers stay valid until a repack.
        with self._ann_lock:
            self._ann = None
            self._ann_gen += 1  # voids a build racing this eviction
            if self._ann_state == "ready":
                self._ann_state = "idle"

    def _mem_evict_vec(self):
        # degrade the host arrays to rebuild-on-touch: version -1 makes
        # the next sync() re-scan this engine's KV range (the exact
        # PR-9 fresh-node discipline); the ANN snapshot's row numbering
        # dies with the arrays. PINNED engines are skipped: a query
        # between its sync() and its read-locked scoring pass must
        # never observe the arrays vanish — eviction degrades speed,
        # NEVER answers. Called only from checkpoint sites that hold
        # none of this engine's locks.
        if self._pins > 0:
            return  # actively serving: not evictable right now
        with self.lock, self.rw.write():
            with self._pin_lock:
                if self._pins > 0:
                    return  # pinned while this waited for the locks
                # under the pin lock: a search that pins from here on
                # finds the version gone and syncs behind this eviction
                self.version = -1
            self.rids = []
            self.row_index = {}
            self.vecs = np.zeros((0, self.dim), dtype=self.dtype)
            self.valid = np.zeros(0, dtype=bool)
            self._vec_buf = self._valid_buf = None
            self._drop_device()
            with self._ann_lock:
                self._ann = None
                self._ann_dirty = {}
                self._ann_dead = 0
                self._ann_dead_base = 0
                self._ann_gen += 1
                if self._ann_state == "ready":
                    self._ann_state = "idle"
            if self._segs is not None:
                self._segs.reset()

    # -- cache sync ---------------------------------------------------------
    def sync(self, ctx):
        """Bring the device block cache up to the KV truth: small gaps apply
        the op log incrementally (append + tombstone); big gaps or heavy
        fragmentation trigger a full repack (the reference's two-phase
        pending/compaction design, hnsw/index.rs). A store that crossed
        the ANN threshold (or whose graph went stale) kicks a background
        graph build afterwards — brute force serves until it lands."""
        # pressure checkpoint BEFORE taking any index lock: past the
        # soft watermark this may evict cold accounts (possibly this
        # engine's own — the rebuild below then runs from KV truth)
        self._mem_vec.touch()
        resource.checkpoint()
        ver0 = self.version
        t0 = time.perf_counter_ns()
        try:
            self._sync_impl(ctx)
        finally:
            if self.version != ver0:
                # stage `index_sync`: this searcher found the version
                # moved and read the log, applied it or waited for the
                # thread that did (inside its `index_knn`)
                stage_record("index_sync", time.perf_counter_ns() - t0)
                # the sync grew state (log apply / rebuild): settle
                # with a fresh poll, same step-jump rationale as the
                # ANN install
                resource.checkpoint(fresh=True)
            self._maybe_maintain()

    def _sync_impl(self, ctx):
        ns, db, tb, ix = self.key
        vkey = K.ix_state(ns, db, tb, ix, b"vn")
        ver = ctx.txn.get_val(vkey) or 0
        have = self.version
        if ver == have:
            return
        behind = 0 <= ver < have
        if behind and self._committed_version(ctx, vkey) >= have:
            # this transaction's snapshot is older than what the cache
            # holds, and what it holds is committed: serve that.
            # Rebuilding back to the snapshot would take the rows away
            # from the searches that already synced to them and wait to
            # ride, acknowledged writes among them. Decided without the
            # engine's locks (the caller is pinned: the arrays stay),
            # so a search that began before a write does not queue
            # behind the syncs of those that began after it
            return
        # `self.lock` makes the syncs take turns; the write lock, which
        # has to wait for the dispatches in flight, is taken only to
        # change the arrays: the searches that queued behind this sync
        # find the version theirs and leave without it, and the log is
        # read before it
        with self.lock:
            if ver == self.version:
                return
            if not behind and 0 <= ver < self.version:
                # another search's sync took the cache past this
                # snapshot while this one waited its turn: as above
                # (no store is asked under the lock)
                return
            gap = ver - self.version
            entries = None
            if self.version >= 0 and 0 < gap <= max(4096,
                                                    len(self.rids) // 4):
                entries = self._read_log(ctx, self.version, ver)
            with self.rw.write():
                if entries is not None:
                    self._apply_entries(entries)
                    self.version = ver
                    frag = (
                        1.0 - self.live / len(self.valid)
                        if len(self.valid)
                        else 0.0
                    )
                    if frag <= 0.25:
                        return
                self._rebuild(ctx)
                self.version = ver

    @staticmethod
    def _committed_version(ctx, vkey) -> int:
        """The index version as committed now, by a read transaction
        of its own (the caller's may hold an older snapshot)."""
        txn = ctx.ds.transaction(write=False)
        try:
            return txn.get_val(vkey) or 0
        finally:
            txn.cancel()

    def _read_log(self, ctx, from_ver, to_ver):
        """The op-log entries of (from_ver, to_ver] for `_apply_entries`,
        or None where the log is incomplete (e.g. trimmed): rebuild
        instead."""
        ns, db, tb, ix = self.key
        beg = K.ix_state(ns, db, tb, ix, b"hl", K.enc_u64(from_ver + 1))
        end = K.ix_state(ns, db, tb, ix, b"hl", K.enc_u64(to_ver)) + b"\x00"
        entries = list(ctx.txn.scan_vals(beg, end))
        if len(entries) != to_ver - from_ver \
                and not self._gaps_are_burnt(ctx, entries, from_ver, to_ver):
            return None
        return [e for _k, e in entries]

    def _gaps_are_burnt(self, ctx, entries, from_ver, to_ver) -> bool:
        """Whether every version in (from_ver, to_ver] that the log
        lacks belongs to a transaction of this process that was
        cancelled, or is still open below a committed version and so
        will be (`_note_version`). The burnt ones passed are forgotten."""
        ds = ctx.ds
        book = getattr(ds, "_ix_gaps", {}).get(self.key)
        if book is None:
            return False
        have = {int.from_bytes(k[-8:], "big") for k, _e in entries}
        with ds.lock:
            gone = book["burnt"] | book["open"]
            ok = all(v in have or v in gone
                     for v in range(from_ver + 1, to_ver + 1))
            if ok:
                book["burnt"] = {v for v in book["burnt"] if v > to_ver}
        return ok

    def _apply_entries(self, entries):
        """Apply pre-fetched op-log entries [(op, idv, raw), ...] to the
        host arrays. Pure in-memory — the caller holds the index locks
        and has already fetched the log slice (the shard router fetches
        ONCE and fans the ops out to its parts by key range)."""
        tb = self.key[2]
        add_rows = []
        add_rids = []
        add_valid = []
        touched = []  # rows below the old count, overwritten or flipped
        for op, idv, raw in entries:
            h = K.enc_value(idv)
            row = self.row_index.get(h)
            if op == "del":
                if row is None:
                    continue
                if row < len(self.valid):
                    if self.valid[row]:
                        self._ann_dead += 1
                        self._flip(row, False)
                        touched.append(row)
                else:
                    # the row was appended EARLIER IN THIS BATCH and is
                    # still in the pending buffers — dropping the
                    # tombstone here would resurrect it forever
                    ai = row - len(self.rids)
                    if 0 <= ai < len(add_valid):
                        add_valid[ai] = False
                continue
            vec = np.frombuffer(raw, dtype=self.dtype)
            if row is not None and row < len(self.vecs):
                self.vecs[row] = vec
                touched.append(row)
                if not self.valid[row]:
                    self._flip(row, True)
                # the ANN graph/int8 snapshot no longer matches this
                # row: brute-merge it at query time until a rebuild
                self._ann_mut += 1
                self._ann_dirty[row] = self._ann_mut
            elif row is not None:
                # overwrite of a same-batch append: update the pending
                # buffer in place (a second append would leave a stale
                # duplicate row permanently valid)
                ai = row - len(self.rids)
                add_rows[ai] = vec
                add_valid[ai] = True
            else:
                self.row_index[h] = len(self.rids) + len(add_rids)
                add_rids.append(RecordId(tb, idv))
                add_rows.append(vec)
                add_valid.append(True)
        if add_rows:
            self._grow_host(add_rows, add_valid)
            self.rids.extend(add_rids)
        if self._dev_capacity is None:
            self._drop_device()
            return
        # the runner holds a block that grows in place: keep what
        # changed since the tag it holds, for the next dispatch to send
        # as one delta (folded across syncs until then)
        self._host_stats = None
        self._dev_dirty.update(touched)
        n = len(self.rids)
        if n > self._dev_capacity or len(self._dev_dirty) \
                + n - self._dev_rows > DELTA_MAX_ROWS:
            self._drop_device()

    def _grow_host(self, add_rows, add_valid):
        """Appends rows to the host arrays without copying what is
        there: `vecs` / `valid` are views of buffers with room to
        spare, reallocated (a quarter larger) only when they are full
        or when the arrays were assigned from outside. A view taken
        before keeps its length (`_build_ann` relies on that)."""
        n, m = len(self.vecs), len(add_rows)
        need = n + m
        buf, vbuf = self._vec_buf, self._valid_buf
        if buf is None or self.vecs.base is not buf \
                or self._valid.base is not vbuf or need > len(buf):
            room = need + max(need // 4, 64)
            buf = np.empty((room, self.dim), self.dtype)
            buf[:n] = self.vecs
            vbuf = np.zeros(room, bool)
            vbuf[:n] = self._valid
            self._vec_buf, self._valid_buf = buf, vbuf
        for j, vec in enumerate(add_rows, n):
            buf[j] = vec
        vbuf[n:need] = add_valid
        self.vecs = buf[:need]
        # not through the setter: it would count the whole mask again
        self._valid = vbuf[:need]
        self.live += sum(add_valid)

    def _drop_device(self):
        """Invalidate the device-resident cache (host arrays are truth):
        bumping the epoch makes the runner's copy stale, so the next
        dispatch re-ships the blocks. The host scoring stats are derived
        from the same arrays and invalidate with it."""
        self._dev_epoch += 1
        self.rank_mode = None
        self._host_stats = None
        self._dev_tag = None
        self._dev_capacity = None
        self._dev_dirty = set()

    def _ensure_device(self, sup, tag, loader):
        """The runner holds this engine's block at `tag` when this
        returns: already, by one delta (`vec_append`) where it holds
        the tag the kept changes start from, else by the whole ship.
        Callers hold the read lock, so the arrays and the changes stand
        still; dispatches that arrive together send one delta."""
        if self._dev_tag == tag:
            sup.ensure_loaded(self._dev_key, tag, loader)
            return
        with self._dev_ship_lock:
            delta = None
            if self._dev_capacity is not None and self._dev_tag is not None \
                    and self._dev_tag != tag:
                delta = (self._dev_tag, self._delta_bufs)
            # lint: lock-held(the ship lock serialises exactly this call, so that dispatches arriving together send one delta; bounded by the supervisor's load timeout and degrade circuit)
            sup.ensure_loaded(self._dev_key, tag, loader, delta=delta)
            self._dev_tag = list(tag)
            self._dev_rows = len(self.rids)
            self._dev_dirty = set()

    def _delta_bufs(self):
        """[rows, row numbers, mask bits] of what changed since
        `_dev_tag`: the rows overwritten or flipped, then the rows
        appended, as they are now."""
        idx = np.asarray(
            sorted(r for r in self._dev_dirty if r < self._dev_rows)
            + list(range(self._dev_rows, len(self.rids))), np.int32)
        return [np.ascontiguousarray(self.vecs[idx]), idx,
                self.valid[idx].astype(np.uint8)]

    def _he_range(self) -> tuple[bytes, bytes, bytes]:
        """(prefix, begin, end) of this engine's element keyspace —
        clamped to `key_range` for a shard part."""
        ns, db, tb, ix = self.key
        pre = K.ix_state(ns, db, tb, ix, b"he")
        beg, end = K.prefix_range(pre)
        if self.key_range is not None:
            beg = max(beg, self.key_range[0])
            end = min(end, self.key_range[1])
        return pre, beg, end

    def _scan_rows(self, ctx):
        """Read this engine's rows from KV truth (range-clamped). Pure
        I/O — takes NO index locks, so the scatter paths can park on a
        remote scan without wedging concurrent searchers; the caller
        installs the snapshot afterwards under the write lock."""
        pre, beg, end = self._he_range()
        tb = self.key[2]
        rids = []
        rows = []
        index = {}
        plen = len(pre)
        from surrealdb_tpu.kvs.api import deserialize

        for k, raw in ctx.txn.scan(beg, end):
            idv, _pos = K.dec_value(k, plen)
            index[K.enc_value(idv)] = len(rids)
            rids.append(RecordId(tb, idv))
            rows.append(np.frombuffer(deserialize(raw), dtype=self.dtype))
            if len(rids) % 65536 == 0:
                # chunk-boundary pause point: a rebuild under memory
                # pressure evicts colder state before allocating more
                resource.throttle("index_rebuild")
        return rids, rows, index

    def _install_rows(self, rids, rows, index):
        """Install a freshly scanned snapshot (caller holds the locks)."""
        self.rids = rids
        self.row_index = index
        self.vecs = (
            np.stack(rows) if rows else np.zeros((0, self.dim), self.dtype)
        )
        self.valid = np.ones(len(rids), dtype=bool)
        self._vec_buf = self._valid_buf = None
        self._drop_device()
        # a repack remaps row ids: the ANN snapshot (graph ids, dirty
        # rows, any build in flight) is void — discard and re-trigger;
        # the segment table (spans of the old numbering) dies with it
        with self._ann_lock:
            self._ann = None
            self._ann_dirty = {}
            self._ann_dead = 0
            self._ann_dead_base = 0
            self._ann_gen += 1
            if self._ann_state == "ready":
                self._ann_state = "idle"
        if self._segs is not None:
            self._segs.reset()

    def _rebuild(self, ctx):
        ns, db, tb, ix = self.key
        self._install_rows(*self._scan_rows(ctx))
        # trim the consumed op log when we can write (bounds log growth);
        # shard parts never trim — the router owns the shared log
        if self.key_range is None and getattr(ctx.txn, "write", False):
            ver = ctx.txn.get_val(K.ix_state(ns, db, tb, ix, b"vn")) or 0
            beg = K.ix_state(ns, db, tb, ix, b"hl", K.enc_u64(0))
            end = K.ix_state(ns, db, tb, ix, b"hl", K.enc_u64(ver)) + b"\x00"
            ctx.txn.delete_range(beg, end)

    # -- shard-part serving (driven by idx/shardvec.py) ---------------------

    def part_sync(self, ctx, ver: int, entries):
        """Bring ONE shard part up to global mutation version `ver`.

        The router read `vn` once and fetched the shared op log once;
        `entries` is this part's share — ascending `(gver, op, idv,
        raw)` tuples — or None when the log cannot cover the gap (full
        range rebuild). Lock discipline differs from the unsharded
        `sync`: all KV I/O (the rebuild scan) runs OUTSIDE the index
        locks, so a scatter attempt parked on a sick shard's scan never
        wedges searchers of the healthy parts; installs re-check the
        version under the lock, so two racing syncs converge instead of
        regressing."""
        if ver <= self.version:
            return
        if entries is not None and self.version >= 0:
            frag = 0.0
            with self.lock, self.rw.write():
                if ver > self.version:
                    self._apply_entries([
                        (op, idv, raw) for g, op, idv, raw in entries
                        if g > self.version
                    ])
                    self.version = ver
                if len(self.valid):
                    frag = 1.0 - self.live / len(self.valid)
            if frag <= 0.25:
                self._maybe_maintain()
                return
        rids, rows, index = self._scan_rows(ctx)  # KV I/O: no locks held
        with self.lock, self.rw.write():
            if ver >= self.version:
                self._install_rows(rids, rows, index)
                self.version = ver
        self._maybe_maintain()

    def search_topk(self, qv: np.ndarray, k: int):
        """Per-part scatter entry: top-k over this part's (already
        synced) rows — exact, or CAGRA descent + exact re-rank when the
        part grew past the ANN floor. Pure compute: by the lock
        discipline above it can never block on a remote shard.

        Routing: device-bound parts ride the cross-query coalescer
        (concurrent queries share one batched kernel per part block);
        host-routed parts call the batched engine entry directly —
        paying the coalescer's condition dance per part per query
        measurably loses to one BLAS pass on CPU-routed stores."""
        with self._pin_lock:
            self._pins += 1  # pin: eviction must not race this search
        try:
            n = self.live
            if n == 0:
                return []
            k = min(k, n)
            if len(self.rids) < DEVICE_MIN_ROWS:
                # tiny part: the exact host ladder, bit-for-bit the
                # unsharded small-store path
                with self.rw.read():
                    return self._host_knn_single(qv, k)
            if self._use_device():
                return self.coalescer.search(qv, k)
            # lint: lock-held(read-side hold is the array-swap guard vs sync's rw.write; a device dispatch inside is bounded by the supervisor call timeout + degrade circuit, and eviction is already pin-gated)
            with self.rw.read():
                return self.knn_batch(np.asarray(qv)[None, :], k)[0]
        finally:
            with self._pin_lock:
                self._pins -= 1

    def residency(self) -> dict:
        """Index-serving residency for INFO FOR SYSTEM / /metrics."""
        out = {
            "rows": self.live,
            "bytes": int(self.vecs.nbytes),
            "version": int(self.version),
            "ann": self._ann_state,
        }
        ann = self._ann
        if ann is not None:
            out["ann_bytes"] = ann.nbytes()
        mesh_nd = max(int(self._dev_mesh), int(self._dev_mesh_ann))
        if mesh_nd > 1:
            # devices this engine's runner blocks actually served on
            # (device/mesh.py row-sharding); absent = single-device
            out["device_sharded"] = mesh_nd
        segs = self._segs
        if segs is not None and segs.active():
            st = segs.status()
            out["ann"] = "segmented"
            out["segments"] = st["segments"]
            out["segments_ready"] = st["ready"]
            out["tail_rows"] = st["tail_rows"]
        if self.label:
            out["range"] = self.label
        return out

    # -- segmented LSM-style serving (idx/segments.py) ----------------------

    def _segments(self):
        """The segment coordinator, created on first touch."""
        if self._segs is None:
            from surrealdb_tpu.idx.segments import SegmentedAnn

            with self.lock:
                if self._segs is None:
                    self._segs = SegmentedAnn(self)
        return self._segs

    def _seg_engaged(self) -> bool:
        """True when segmented serving governs this engine (mode +
        metric + size gates, idx/segments.py policy)."""
        segs = self._segs
        if segs is not None:
            return segs.engaged()
        from surrealdb_tpu import cnf as _cnf

        if str(_cnf.KNN_SEG_MODE).lower() == "off":
            return False
        return self._segments().engaged()

    def _maybe_maintain(self):
        """Post-sync index maintenance: segmented engines seal / build
        / merge in the background (idx/segments.py); everything else
        keeps the legacy whole-store graph schedule."""
        if self._seg_engaged():
            self._segments().maybe_maintain()
            return
        self._maybe_build_ann()

    # -- quantized graph-ANN overlay (idx/cagra.py) -------------------------

    def _ann_floor(self):
        """Row floor above which a graph build is scheduled, or None
        when the ANN path is disabled for this index (mode off, or a
        metric the MXU scoring recipe doesn't cover)."""
        mode = cnf.KNN_ANN_MODE
        if mode == "off" or self.metric not in (
            "euclidean", "cosine", "dot"
        ):
            return None
        if mode == "force":
            return 256
        return cnf.KNN_ANN_MIN_ROWS

    def _ann_stale(self, ann, n) -> bool:
        """Appended-tail + overwritten-row fraction past which the
        graph is rebuilt. Until the rebuild lands those rows are
        brute-ranked and merged per query, so results stay exact-
        re-ranked either way — staleness is a throughput concern."""
        drift = (n - ann.built_n) + len(self._ann_dirty) \
            + max(self._ann_dead - self._ann_dead_base, 0)
        return drift / max(n, 1) > cnf.KNN_ANN_TAIL_FRAC

    def _maybe_build_ann(self):
        floor = self._ann_floor()
        if floor is None:
            return
        n = len(self.rids)
        if n < floor:
            return
        ann = self._ann
        if ann is not None and not self._ann_stale(ann, n):
            return
        with self._ann_lock:
            if self._ann_state == "building":
                return
            self._ann_state = "building"
        if ann is not None:
            # drift past KNN_ANN_TAIL_FRAC is re-deriving the WHOLE
            # graph — the rebuild treadmill the segmented path
            # (idx/segments.py) exists to eliminate; counted so the
            # knn_churn gate can assert it never happens there
            from surrealdb_tpu.idx import segments as _segments

            self.ann_full_rebuilds += 1
            _segments.count("ann_full_rebuilds")
        threading.Thread(target=self._build_ann, daemon=True,
                         name="ann-build").start()

    def ensure_ann(self) -> bool:
        """Synchronous build entry (bench/tests): returns True when a
        ready, non-stale graph (or, on a segmented engine, a fully
        built segment set) serves searches of this store."""
        import time as _time

        if self._seg_engaged():
            return self._segments().drain()
        floor = self._ann_floor()
        n = len(self.rids)
        if floor is None or n < floor:
            return False
        while True:
            ann = self._ann
            if ann is not None and not self._ann_stale(ann, n):
                return True
            with self._ann_lock:
                if self._ann_state != "building":
                    if ann is not None:
                        from surrealdb_tpu.idx import segments as _sg

                        self.ann_full_rebuilds += 1
                        _sg.count("ann_full_rebuilds")
                    self._ann_state = "building"
                    break
            _time.sleep(0.05)  # a background build is running: wait
        self._build_ann()
        ann = self._ann
        # honest answer: a failed rebuild leaves the old (stale) graph
        # serving, which is NOT the fresh build this entry promises
        return ann is not None and not self._ann_stale(ann, len(self.rids))

    def _build_ann(self):
        """Build the CAGRA graph + int8 arrays from a host snapshot.
        Runs WITHOUT the index lock held through the build: the host
        arrays are append-stable (the log applier grows them by
        reallocation, so a captured reference keeps its length), and a
        concurrent in-place overwrite lands in `_ann_dirty`, whose rows
        are brute-merged at query time — a torn snapshot can never
        surface a wrong distance, only a slightly worse candidate set.
        A full repack bumps `_ann_gen`; a build that raced one is
        discarded.

        With a `snapshot_dir`, a persisted artifact whose mutation
        stamp (the `vn` version) AND row-identity digest match the
        current snapshot loads in seconds instead of redoing the build;
        a fresh build persists on the way out (idx/cagra.py
        save_index/load_index, SKVCRC01 frame idiom)."""
        from surrealdb_tpu.idx import cagra

        with self.rw.read():
            gen = self._ann_gen
            xs = self.vecs
            rids = self.rids
            version, epoch = self.version, self._dev_epoch
            mut_cut = self._ann_mut
            dead0 = self._ann_dead
        ann = self._load_ann_snapshot(xs, rids, version)
        loaded = ann is not None
        if ann is None:
            try:
                ann = cagra.build_index(xs, self.metric, version, epoch)
            except Exception:
                with self._ann_lock:
                    self._ann_state = "idle"
                return
        installed = False
        with self._ann_lock:
            if self._ann_gen != gen:
                self._ann_state = "idle"  # repack raced: discard
                return
            installed = True
            self._ann = ann
            self._ann_seq += 1
            # rows dirtied BEFORE the snapshot hold their new values in
            # xs (writers exclude the capture via the rw lock, so the
            # build covered them); rows stamped after — overwritten
            # DURING the build, possibly half-captured — stay dirty and
            # keep brute-merging
            self._ann_dirty = {
                r: g for r, g in self._ann_dirty.items() if g > mut_cut
            }
            # deletions known at snapshot time are as absorbed as an
            # ANN rebuild can make them (the rows leave the arrays only
            # at the next full repack) — stop counting them as drift
            self._ann_dead_base = dead0
            self._ann_state = "ready"
        if installed:
            self._mem_ann.touch()
            # the install just grew accounted bytes by a step: settle
            # pressure NOW with a fresh poll — the gated hot-path
            # checkpoint could reuse a stale low reading
            resource.checkpoint(fresh=True)
        if installed and not loaded:
            self._save_ann_snapshot(ann, xs, rids)

    # -- persisted build artifacts ------------------------------------------

    def _ann_snap_path(self):
        if not self.snapshot_dir:
            return None
        import hashlib
        import os

        ns, db, tb, ix = self.key
        # filename: readable stem + a collision-proof tag (names may
        # contain bytes a filesystem rejects; parts add their range)
        ident = repr((ns, db, tb, ix, self.label))
        tag = hashlib.sha256(ident.encode()).hexdigest()[:16]
        stem = "".join(
            c if c.isalnum() else "_" for c in f"{ns}.{db}.{tb}.{ix}"
        )[:48]
        return os.path.join(self.snapshot_dir, f"{stem}-{tag}.annsnap")

    @staticmethod
    def _row_digest(rids, n: int) -> str:
        """Row-identity digest over the first `n` rows IN ORDER: graph
        node ids are row numbers, so a reloaded artifact is only valid
        when the numbering — not just the row set — matches."""
        import hashlib

        h = hashlib.sha256()
        for r in rids[:n]:
            h.update(K.enc_value(r.id))
            h.update(b";")
        return h.hexdigest()

    def _load_ann_snapshot(self, xs, rids, version):
        path = self._ann_snap_path()
        if path is None or not len(xs):
            return None
        import os
        import sys

        from surrealdb_tpu.idx import cagra

        try:
            ann, meta = cagra.load_index(path)
        except OSError:
            return None  # no snapshot (or unreadable dir): just build
        except Exception as e:
            # corrupt/torn snapshot: warn + rebuild, NEVER serve it
            print(
                f"[surrealdb-tpu] ann snapshot {path} rejected "
                f"({e}); rebuilding from rows",
                file=sys.stderr, flush=True,
            )
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        if (ann.metric != self.metric
                or ann.built_n != len(xs)
                or ann.built_version != int(version)
                or meta.get("dim") != int(xs.shape[1])
                or meta.get("rows") != self._row_digest(rids, len(xs))):
            return None  # stale stamp: rows changed since the save
        return ann

    def _save_ann_snapshot(self, ann, xs, rids):
        path = self._ann_snap_path()
        if path is None:
            return
        import os
        import sys

        from surrealdb_tpu.idx import cagra

        try:
            os.makedirs(self.snapshot_dir, exist_ok=True)
            cagra.save_index(ann, path, extra={
                "dim": int(xs.shape[1]),
                "rows": self._row_digest(rids, ann.built_n),
            })
        except OSError as e:
            print(
                f"[surrealdb-tpu] ann snapshot save failed ({path}): "
                f"{e}", file=sys.stderr, flush=True,
            )

    def _ann_route(self, k: int):
        """The ready AnnIndex when a k-NN search of `k` should ride the
        graph path, else None (brute force — bit-for-bit the legacy
        results). A stale-but-built graph keeps serving while its
        replacement builds; the tail merge keeps results exact."""
        if cnf.KNN_ANN_MODE == "off" or k > cnf.KNN_ANN_MAX_K:
            return None
        return self._ann

    def _seg_route(self, k: int):
        """The segment coordinator when a k-NN search of `k` should fan
        over sealed segments, else None. Same k gate as the graph
        route; exact-only segment sets still fan out (each span scans
        exactly — the merge stays byte-identical to brute)."""
        if k > cnf.KNN_ANN_MAX_K:
            return None
        segs = self._segs
        if segs is not None and segs.active():
            return segs
        return None

    def ann_plan(self, k: int):
        """EXPLAIN surface: how a k-NN of `k` over this engine is
        served — None (brute scan), {"ann": "graph"} (legacy
        whole-store graph), or {"ann": "segmented", ...} with the
        segment fan-out shape."""
        segs = self._seg_route(k)
        if segs is not None:
            st = segs.status()
            return {
                "ann": "segmented",
                "segments": st["segments"],
                "ready": st["ready"],
                "tail_rows": st["tail_rows"],
            }
        if self._ann_route(k) is not None:
            return {"ann": "graph"}
        return None

    def _ann_search_cfg(self) -> dict:
        w = max(int(cnf.KNN_ANN_SEARCH_WIDTH), 1)
        width = 1
        while width < w:
            width *= 2  # pow2: descent kernel shapes stay a ladder
        return {
            "width": width,
            "iters": max(int(cnf.KNN_ANN_ITERS), 1),
            "expand": max(int(cnf.KNN_ANN_EXPAND), 1),
        }

    def _ann_device_search(self, ann, qs32: np.ndarray, kc: int,
                           dev_key=None, tag=None):
        """Descent candidates from the runner's AnnStore blocks; ships
        the build snapshot on first use / after a runner restart via
        the same (key, tag) protocol as the vector blocks — PR-4
        crash/reship and the post-ship prewarm apply unchanged.
        Segmented engines pass a per-SEGMENT `dev_key`/`tag`
        (idx/segments.py), making every sealed segment an independently
        shippable/evictable runner block."""
        from surrealdb_tpu.device import get_supervisor

        sup = get_supervisor()
        if dev_key is None:
            dev_key = self._ann_dev_key
        if tag is None:
            tag = [int(self._ann_seq), int(ann.built_version),
                   int(ann.built_epoch)]

        def loader():
            return "ann_load", {
                "metric": ann.metric,
                "cfg": self._ann_search_cfg(),
            }, [
                np.ascontiguousarray(ann.graph),
                np.ascontiguousarray(ann.x8),
                np.ascontiguousarray(ann.arow),
                np.ascontiguousarray(ann.x2),
            ]

        for _attempt in (0, 1):
            sup.ensure_loaded(dev_key, tag, loader)
            t, meta, bufs = sup.call(
                "ann_search",
                {"key": dev_key, "tag": tag, "kc": int(kc)},
                [qs32],
            )
            if t == "stale":
                sup.forget(dev_key)
                continue
            break
        else:
            raise sup.unavailable("ann cache thrashing")
        nd = int(meta.get("mesh_ndev", 1) or 1)
        if nd > self._dev_mesh_ann:
            self._dev_mesh_ann = nd
        return bufs[0]

    def _ann_extra_topk(self, ann, qvs, k: int, n: int):
        """Per-query top-k ids over rows the graph snapshot can't see
        (appended tail + overwritten rows), exact-scored; None when the
        snapshot covers the store. Bounded by KNN_ANN_TAIL_FRAC — past
        it `_ann_stale` schedules a rebuild."""
        dirty = [r for r in list(self._ann_dirty) if r < ann.built_n]
        if n <= ann.built_n and not dirty:
            return None
        extra = np.arange(ann.built_n, n, dtype=np.int64)
        if dirty:
            extra = np.concatenate(
                [np.asarray(sorted(dirty), np.int64), extra]
            )
        # tombstoned rows must not crowd valid ones out of the top-k
        # (the final re-rank would drop them, silently shrinking the
        # exact tail coverage)
        extra = extra[self.valid[extra]]
        if not len(extra):
            return None
        rows = self.vecs[extra]
        k_eff = min(k, len(extra))
        out = []
        for qv in qvs:
            d = self._host_distances(qv, xs=rows)
            if k_eff < len(extra):
                sel = np.argpartition(d, k_eff - 1)[:k_eff]
            else:
                sel = np.arange(len(extra))
            out.append(extra[sel])
        return out

    def _ann_knn_batch(self, ann, qvs: np.ndarray, k: int):
        """Graph-ANN search: int8 greedy descent (the runner's jax
        kernel, or its numpy mirror when the device is cold/degraded/
        host-routed) proposes an oversampled candidate set per query;
        rows outside the build snapshot are brute-ranked and merged;
        the final top-k comes from the exact `_host_distances` ladder
        over the union — every reported distance is exact, and the
        quantized descent only decides which kc candidates get
        considered (the AQR-style multi-stage re-rank)."""
        from surrealdb_tpu.device import DeviceOpError, DeviceUnavailable
        from surrealdb_tpu.idx import cagra

        n = len(self.rids)
        b = len(qvs)
        kc = min(ann.built_n, max(cnf.KNN_ANN_OVERSAMPLE * k, 32))
        qs32 = np.ascontiguousarray(np.asarray(qvs, np.float32))
        cand = None
        if self._use_device():
            try:
                cand = self._ann_device_search(ann, qs32, kc)
            except (DeviceUnavailable, DeviceOpError):
                # degrade to the numpy descent below — counted, so a
                # run that meant to measure the device can tell
                from surrealdb_tpu.device import get_supervisor

                get_supervisor().note_fallback()
                cand = None
        if cand is None:
            cfg = self._ann_search_cfg()
            width = min(max(cfg["width"], kc), ann.built_n)
            fn, probe_fn = cagra.int8_score_fn(ann, qs32)
            cand = cagra.descend(
                ann.graph, ann.built_n, fn, b, width, cfg["iters"],
                min(cfg["expand"], width), kc, probe_fn=probe_fn,
            )
        # stage `knn_post`: this dispatch's host work once the
        # candidates are back — the unseen-rows merge and the exact
        # re-rank of every rider
        t_post = time.perf_counter_ns()
        extra_top = self._ann_extra_topk(ann, qvs, k, n)
        out = []
        for i in range(b):
            ids_b = cand[i].astype(np.int64)
            ids_b = ids_b[(ids_b >= 0) & (ids_b < n)]
            if extra_top is not None:
                ids_b = np.concatenate([ids_b, extra_top[i]])
            ids_b = np.unique(ids_b)
            d = self._host_distances(qvs[i], xs=self.vecs[ids_b])
            d = np.where(self.valid[ids_b], d, np.inf)
            k_eff = min(k, len(ids_b))
            if k_eff == 0:
                out.append([])
                continue
            sel = np.argpartition(d, k_eff - 1)[:k_eff]
            sel = sel[np.argsort(d[sel], kind="stable")]
            res_i = [
                (self.rids[int(ids_b[j])], float(d[j]))
                for j in sel
                if np.isfinite(d[j])
            ]
            if len(res_i) < k:
                # tombstone-dense neighborhood (e.g. a fully deleted
                # cluster): graph candidates can underfill k while the
                # store still holds enough valid rows — answer that
                # query exactly rather than short (rare path; the
                # staleness counter is already scheduling a rebuild
                # when deletions accumulate)
                if len(res_i) < min(k, self.live):
                    res_i = self._host_knn_single(qvs[i], k)
            out.append(res_i)
        stage_record("knn_post", time.perf_counter_ns() - t_post)
        return out

    # -- search -------------------------------------------------------------
    def knn(self, q, k: int, ctx, ef=None, cond=None, cond_ctx=None):
        """Top-k nearest records. `cond`: optional per-record predicate —
        handled by oversample + host truthiness check + refill
        (SURVEY.md hard-parts: cond-filtered KNN)."""
        t0 = time.perf_counter_ns()
        with self._pin_lock:
            self._pins += 1  # pin: eviction must not race this query
        try:
            return self._knn(q, k, ctx, ef=ef, cond=cond,
                             cond_ctx=cond_ctx)
        finally:
            with self._pin_lock:
                self._pins -= 1
            # wall time inside the index: the cache sync check, then
            # the batcher's `batch_wait` + `batch_ride` of this rider;
            # its batch's `batch_dispatch` (⊃ `device_rpc`, `knn_post`)
            # is recorded once by the thread that dispatched it
            stage_record("index_knn", time.perf_counter_ns() - t0)

    def _knn(self, q, k: int, ctx, ef=None, cond=None, cond_ctx=None):
        self.sync(ctx)
        n = self.live
        if n == 0:
            return []
        qv = _as_vector(q, self.dim, "knn query", self.dtype)
        if cond is None:
            pairs = self._raw_knn(qv, min(k, n))
            return pairs[:k]
        # predicate pushdown: oversample and refill
        want = k
        fetch = min(max(4 * k, 64), n)
        checked: set = set()
        out = []
        while True:
            pairs = self._raw_knn(qv, min(fetch, n))
            for rid, dist in pairs:
                hkey = K.enc_value(rid.id)
                if hkey in checked:
                    continue
                checked.add(hkey)
                if self._check_cond(rid, cond, cond_ctx):
                    out.append((rid, dist))
                    if len(out) >= want:
                        return out
            if fetch >= n:
                return out
            fetch = min(fetch * 4, n)

    def _check_cond(self, rid, cond, ctx):
        from surrealdb_tpu.exec.eval import evaluate, fetch_record

        doc = fetch_record(ctx, rid)
        if doc is NONE:
            return False
        c = ctx.with_doc(doc, rid)
        return is_truthy(evaluate(cond, c))

    def _raw_knn(self, qv: np.ndarray, k: int):
        n = len(self.rids)
        if n < DEVICE_MIN_ROWS:
            # tiny store: a single exact pass beats any batching overhead
            return self._host_knn_single(qv, k)
        # Everything else rides the cross-query batcher — including the
        # degraded/CPU-only paths, which coalesce into one batched host
        # kernel instead of N single passes (PR 6: the batcher must win
        # on CPU-only boxes too).
        return self.coalescer.search(qv, k)

    def _use_device(self) -> bool:
        """This index's searches go to the device (`device_routed`)."""
        return device_routed()

    def knn_batch(self, qvs: np.ndarray, k: int):
        """The raw batched engine entry: [B, D] queries -> per-query
        (rid, dist) lists. A store with a built CAGRA graph routes
        through int8 descent + exact re-rank (`_ann_knn_batch`);
        everything else goes to the device runner or the batched exact
        host kernel by `_use_device`. This is the path the cross-query
        batcher dispatches.
        Device trouble raises DeviceUnavailable/DeviceOpError for the
        batcher's per-rider degrade ladder (the ANN path degrades
        internally to its numpy descent instead — falling back to a
        brute scan would forfeit the graph's 10× at the worst moment)."""
        segs = self._seg_route(k)
        if segs is not None:
            return segs.knn_batch(qvs, k)
        ann = self._ann_route(k)
        if ann is not None:
            return self._ann_knn_batch(ann, qvs, k)
        if self._use_device():
            return self._device_knn_batch(qvs, k)
        return self._host_knn_multi(qvs, k)

    def _host_knn_single(self, qv: np.ndarray, k: int):
        """Exact numpy top-k over the host arrays — the degraded path
        and the small-store fast path (identical results to device).
        Delegates to the batched kernel so sequential and batched
        results are byte-identical by construction."""
        return self._host_knn_multi(
            np.asarray(qv)[None, :], k
        )[0]

    def _host_knn_multi(self, qvs: np.ndarray, k: int):
        """Batched exact host KNN: [B, D] queries -> per-query
        (rid, dist) lists. Large stores with MXU metrics run the same
        two-stage discipline as the device kernels — ONE gemm ranking
        pass over the whole store in store precision, then an exact
        distance-ladder rescore of the oversampled candidates — so the
        [B, N] block is touched once, in f32, and every reported
        distance comes from the same per-metric ladder the legacy host
        path used. Small stores and exotic metrics keep the legacy
        per-query ladder bit-for-bit (the conformance oracle's path)."""
        n = len(self.rids)
        if n == 0:
            return [[] for _ in range(len(qvs))]
        if n < DEVICE_MIN_ROWS or self.metric not in (
            "euclidean", "cosine", "dot"
        ):
            return self._host_knn_multi_exact(qvs, k)
        return self._host_knn_multi_blas(qvs, k)

    def _host_knn_multi_exact(self, qvs: np.ndarray, k: int):
        """Legacy full-ladder search, one query at a time — byte-
        identical to the pre-batcher `_host_knn_single`."""
        n = len(self.rids)
        k_eff = min(k, n)
        out = []
        for qv in qvs:
            d = self._host_distances(qv)
            d = np.where(self.valid, d, np.inf)
            idx = np.argpartition(d, k_eff - 1)[:k_eff]
            idx = idx[np.argsort(d[idx], kind="stable")]
            out.append([
                (self.rids[i], float(d[i]))
                for i in idx
                if np.isfinite(d[i])
            ])
        return out

    def _host_stats_cached(self):
        """Per-epoch ranking stats for the BLAS path: f32 squared row
        norms (euclidean scores), f32 inverse row norms (cosine
        scores), and the invalid-row index list (None when the store
        has no tombstones — the common case skips the mask pass).
        Computed blockwise; never materializes an [N, D] copy."""
        st = self._host_stats
        if st is not None:
            return st
        xs = self.vecs
        n = xs.shape[0]
        x2 = np.empty(n, np.float64)
        step = max(1, (64 << 20) // max(xs.shape[1] * 8, 1))
        for s in range(0, n, step):
            blk = xs[s:s + step].astype(np.float64)
            x2[s:s + step] = (blk * blk).sum(axis=1)
        inv_norms = (
            1.0 / np.maximum(np.sqrt(x2), 1e-300)
        ).astype(np.float32)
        invalid = None
        if not self.valid.all():
            invalid = np.nonzero(~self.valid)[0]
        st = (x2.astype(np.float32), inv_norms, invalid)
        self._host_stats = st
        return st

    def _host_knn_multi_blas(self, qvs: np.ndarray, k: int):
        """Stage 1: rank every query against the whole store with one
        gemm per chunk (store precision; per-row results are bitwise
        stable across batch sizes >= 2, single queries pad to 2 rows —
        so batched and sequential searches return identical bytes).
        Stage 2: exact rescore of the kc oversampled candidates through
        `_host_distances` — the reported distances use the SAME ladder
        (and the same f32-cosine specialization) as the legacy path."""
        xs = self.vecs
        n = xs.shape[0]
        m = self.metric
        x2_32, inv_norms32, invalid = self._host_stats_cached()
        k_eff = min(k, n)
        kc = min(n, max(2 * k, k + 16))
        # bound the [chunk, N] f32 score block
        step = max(1, (cnf.KNN_SCORE_BUDGET_ELEMS // 2) // max(n, 1))
        out = []
        for s in range(0, len(qvs), step):
            qc = qvs[s:s + step]
            qb = np.ascontiguousarray(np.asarray(qc, dtype=xs.dtype))
            pad1 = qb.shape[0] == 1
            if pad1:
                # gemv and gemm round differently; a 2-row gemm keeps
                # single-query results bit-identical to batched ones
                qb = np.concatenate([qb, qb], axis=0)
            dots = qb @ xs.T  # [B, N] store precision
            if pad1:
                dots = dots[:1]
            if m == "euclidean":
                score = x2_32[None, :] - 2.0 * dots
            elif m == "cosine":
                score = dots * inv_norms32[None, :]
                np.negative(score, out=score)
            else:  # dot
                score = -dots
            if invalid is not None and len(invalid):
                score[:, invalid] = np.inf
            cand = np.argpartition(score, kc - 1, axis=1)[:, :kc]
            for b in range(cand.shape[0]):
                ids_b = cand[b]
                rows = xs[ids_b]
                d = self._host_distances(qc[b], xs=rows)
                d = np.where(self.valid[ids_b], d, np.inf)
                sel = np.argpartition(d, min(k_eff, kc) - 1)[:k_eff]
                sel = sel[np.argsort(d[sel], kind="stable")]
                out.append([
                    (self.rids[int(ids_b[j])], float(d[j]))
                    for j in sel
                    if np.isfinite(d[j])
                ])
        return out

    def _device_knn_batch(self, qvs: np.ndarray, k: int):
        """Batched search through the device supervisor: [B, D] queries
        -> per-query (rid, dist) lists. The runner ranks (bf16/int8/
        sharded) and rescores where it holds f32 rows; the int8 path
        returns candidates that are EXACTLY rescored here from the
        full-precision host rows. Raises DeviceUnavailable for the
        coalescer to degrade to the host path."""
        from surrealdb_tpu.device import DeviceUnavailable, get_supervisor

        sup = get_supervisor()

        def loader():
            return "vec_load", {
                "metric": self.metric,
                "mink_p": self.mink_p,
                "cfg": device_cfg(),
            }, [
                np.ascontiguousarray(self.vecs),
                np.ascontiguousarray(self.valid.astype(np.uint8)),
            ]

        qs32 = np.ascontiguousarray(qvs, dtype=np.float32)
        meta = bufs = None
        for _attempt in (0, 1):
            # what the answer is mapped by: the rows as the runner will
            # have them when it serves this search
            rids = self.rids
            n = len(rids)
            tag = [int(self.version), int(self._dev_epoch)]
            self._ensure_device(sup, tag, loader)
            # Once the search is in the runner's queue nothing below
            # reads the host arrays of a block that grows in place
            # (ids come back final, and are mapped by `rids` as
            # captured: rows are only ever appended to that list): the
            # read lock is lent until the reply is here, so that a
            # write's sync does not wait out the round trip with every
            # new search queued behind it. What a later dispatch sends
            # is served after this search.
            lent = []
            try:
                t, meta, bufs = sup.call(
                    "vec_knn",
                    {"key": self._dev_key, "tag": tag, "k": int(k)},
                    [qs32],
                    sent=(lambda: lent.append(self.rw.lend_read()))
                    if self._dev_capacity is not None else None,
                )
            finally:
                if lent and lent[0]:
                    self.rw.reclaim_read()
            if t == "stale":
                # runner evicted/restarted between load and query
                sup.forget(self._dev_key)
                continue
            break
        else:
            # sup.unavailable: SdbError in require mode (the query must
            # fail loudly), DeviceUnavailable (degrade to host) in auto
            raise sup.unavailable("vec cache thrashing")
        # stage `knn_post`: the host's share of this dispatch after the
        # RPC — the exact rescore of int8 candidates, ids -> record ids
        t_post = time.perf_counter_ns()
        if tag[1] == self._dev_epoch:
            # what the reply says of the block holds only while the
            # runner has that block: a search whose lock was lent may
            # come back after a sync dropped the device copy (a
            # capacity step, a rebuild), and must not bring the old
            # block's capacity back beside a tag that is gone
            self.rank_mode = meta.get("rank_mode")
            # a block that grows in place says so: deltas from here on
            self._dev_capacity = meta.get("capacity")
        nd = int(meta.get("mesh_ndev", 1) or 1)
        if nd > self._dev_mesh:
            self._dev_mesh = nd
        if meta.get("mode") == "cand":
            # int8 ranking candidates: exact host rescore from the
            # full-precision rows (kc rows per query — tiny next to the
            # store); per-query loop bounds the gather to [kc, D]
            cand = bufs[0]
            out = []
            for b in range(cand.shape[0]):
                ids_b = cand[b]
                ids_b = ids_b[(ids_b >= 0) & (ids_b < n)]
                rows = self.vecs[ids_b]
                d = self._host_distances(qvs[b], xs=rows)
                d = np.where(self.valid[ids_b], d, np.inf)
                k_eff = min(k, len(ids_b))
                if k_eff == 0:
                    out.append([])
                    continue
                sel = np.argpartition(d, k_eff - 1)[:k_eff]
                sel = sel[np.argsort(d[sel], kind="stable")]
                out.append([
                    (self.rids[int(ids_b[j])], float(d[j]))
                    for j in sel
                    if np.isfinite(d[j])
                ])
        else:
            dists, ids = bufs
            out = [
                [
                    (rids[int(i)], float(d))
                    for d, i in zip(drow, irow)
                    if 0 <= i < n and np.isfinite(d)
                ]
                for drow, irow in zip(dists, ids)
            ]
        stage_record("knn_post", time.perf_counter_ns() - t_post)
        return out

    def _host_distances(self, qv, xs=None):
        # the reference accumulates in f64 for most metrics regardless of
        # stored type (trees/vector.rs generic impls use to_float), but
        # cosine has an F32 specialization (cosine_distance_f32): f32
        # dot/norm sums combined in f64 — match it for TYPE F32 stores
        raw = self.vecs if xs is None else xs
        m = self.metric
        if m == "cosine" and raw.dtype == np.float32:
            x32 = raw
            q32 = np.asarray(qv, dtype=np.float32)
            dots = (x32 * q32[None, :]).sum(axis=1).astype(np.float64)
            na = np.sqrt((x32 * x32).sum(axis=1).astype(np.float64))
            nb = np.sqrt(np.float64((q32 * q32).sum()))
            return 1.0 - dots / np.maximum(na * nb, 1e-300)
        xs = raw.astype(np.float64)
        qv = np.asarray(qv, dtype=np.float64)
        if m in ("euclidean", "cosine", "dot"):
            return _exact_mxu_distances(m, xs, qv[None, :])
        if m == "manhattan":
            return np.abs(xs - qv[None, :]).sum(axis=1)
        if m == "chebyshev":
            return np.abs(xs - qv[None, :]).max(axis=1) if xs.size else np.zeros(0)
        if m == "hamming":
            return (xs != qv[None, :]).sum(axis=1).astype(np.float64)
        if m == "minkowski":
            return np.power(
                np.power(np.abs(xs - qv[None, :]), self.mink_p).sum(axis=1),
                1.0 / self.mink_p,
            )
        if m == "pearson":
            xc = xs - xs.mean(axis=1, keepdims=True)
            qc = qv - qv.mean()
            xn = xc / np.maximum(np.linalg.norm(xc, axis=1, keepdims=True), 1e-30)
            qn = qc / max(np.linalg.norm(qc), 1e-30)
            return 1.0 - xn @ qn
        if m == "jaccard":
            mn = np.minimum(xs, qv[None, :]).sum(axis=1)
            mx = np.maximum(xs, qv[None, :]).sum(axis=1)
            return 1.0 - mn / np.maximum(mx, 1e-30)
        raise SdbError(f"unsupported metric {m}")


def get_vector_index(idef, ctx):
    """The serving engine for one vector index: a node-local
    TpuVectorIndex, or — on a range-sharded store — the scatter-gather
    router (idx/shardvec.py) that partitions the index along the shard
    map and merges per-shard top-k."""
    ns, db = ctx.need_ns_db()
    key = (ns, db, idef.tb, idef.name)
    eng = ctx.ds.vector_indexes.get(key)
    if eng is None:
        from surrealdb_tpu.kvs.shard import ShardedBackend

        if isinstance(ctx.ds.backend, ShardedBackend):
            from surrealdb_tpu.idx.shardvec import ShardedVectorIndex

            eng = ShardedVectorIndex(ns, db, idef.tb, idef.name,
                                     idef.hnsw, ctx.ds.backend,
                                     telemetry=ctx.ds.telemetry)
        else:
            eng = TpuVectorIndex(ns, db, idef.tb, idef.name, idef.hnsw)
        eng.snapshot_dir = getattr(ctx.ds, "ann_snapshot_dir", None)
        ctx.ds.vector_indexes[key] = eng
    return eng
