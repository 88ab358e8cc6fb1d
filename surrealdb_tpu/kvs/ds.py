"""Datastore facade (reference: core/src/kvs/ds.rs `Datastore`).

Owns the storage backend, the catalog/index caches, the live-query broker,
and the TPU engine handles; `execute()` parses SurrealQL and runs the
statement loop.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Optional

from surrealdb_tpu import cnf
from surrealdb_tpu.err import SdbError
from surrealdb_tpu.kvs.api import Transaction
from surrealdb_tpu.telemetry import stage_record


class Session:
    """Per-connection session (reference: dbs/session.rs)."""

    def __init__(self, ns=None, db=None, auth_level="none", rid=None, ac=None):
        self.ns = ns
        self.db = db
        self.auth_level = auth_level  # owner | editor | viewer | record | none
        self.rid = rid  # record-auth identity (RecordId)
        self.ac = ac  # access method name
        self.token = None  # verified JWT claims ($token / $session.tk)
        # the base the authenticated principal is scoped to: root | ns |
        # db. DDL at a broader base than this fails the IAM check
        # (reference Options auth level / auth_limit)
        self.auth_base = "root"
        self.planner_strategy = None  # None | "all-ro" | "compute-only"
        # EXPLAIN ANALYZE: omit volatile attrs (batches/elapsed) so output
        # is deterministic — the language-test harness sets this
        # (reference dbs/session.rs:44)
        self.redact_volatile_explain_attrs = False
        self.import_mode = False  # OPTION IMPORT: DEFINEs overwrite
        # session-level follower-read default (seconds): SELECTs without
        # an explicit READ AT bound inherit it; None = exact reads
        self.max_staleness: Optional[float] = None
        self.variables: dict[str, Any] = {}

    @property
    def is_owner(self):
        return self.auth_level == "owner"


class QueryResult:
    """One statement's outcome."""

    __slots__ = ("result", "error", "time_ns", "partial")

    def __init__(self, result=None, error: Optional[str] = None, time_ns: int = 0):
        self.result = result
        self.error = error
        self.time_ns = time_ns
        # typed partial-result marker (SURREAL_KNN_PARTIAL=partial): a
        # scatter-gather KNN answered without one or more index shards.
        # None = complete; else {"missing_shards": [names]} — a partial
        # answer is always FLAGGED, never silently short (idx/shardvec)
        self.partial = None

    @property
    def ok(self):
        return self.error is None

    def unwrap(self):
        if self.error is not None:
            raise SdbError(self.error)
        return self.result

    def __repr__(self):
        if self.error is not None:
            return f"QueryResult(error={self.error!r})"
        return f"QueryResult({self.result!r})"


class Notification:
    """A live-query notification (CREATE/UPDATE/DELETE action on a record)."""

    __slots__ = ("live_id", "action", "record", "result")

    def __init__(self, live_id, action, record, result):
        self.live_id = live_id
        self.action = action  # CREATE | UPDATE | DELETE
        self.record = record  # RecordId
        self.result = result  # value payload

    def __repr__(self):
        return f"Notification({self.action} {self.record} -> {self.result!r})"


class Datastore:
    def __init__(self, path: str = "memory", strict: bool = False,
                 capabilities=None, check_version: bool = True,
                 backend=None):
        from surrealdb_tpu.capabilities import Capabilities

        from surrealdb_tpu.telemetry import Telemetry

        self.path = path
        self.strict = strict
        self.capabilities = capabilities or Capabilities.from_env()
        # created before the backend: the remote engine records its
        # retry/failover counters here
        self.telemetry = Telemetry()
        # directory for persisted CAGRA artifacts (disk stores set it;
        # idx/vector.py reload-or-rebuild keys off the mutation stamp)
        self.ann_snapshot_dir = None
        if backend is not None:
            # pre-built backend injection: the deterministic simulator
            # mounts a real Datastore on a ShardedBackend whose
            # transport/clock are the sim seams (sim/harness.py)
            self.backend = backend
        elif path in ("memory", "mem://", "mem"):
            # the C++ memtable engine when the toolchain built it, else the
            # pure-Python sorted map (same Transactable semantics)
            from surrealdb_tpu.native import available

            if available():
                from surrealdb_tpu.kvs.native_mem import NativeMemBackend

                self.backend = NativeMemBackend()
            else:
                from surrealdb_tpu.kvs.mem import MemBackend

                self.backend = MemBackend()
        elif path in ("pymem", "pymem://"):
            from surrealdb_tpu.kvs.mem import MemBackend

            self.backend = MemBackend()
        elif path.startswith("lsm://"):
            from surrealdb_tpu.kvs.lsm import LsmBackend

            self.backend = LsmBackend(path[len("lsm://"):])
            self._register_ann_cache_dir(path[len("lsm://"):])
        elif path.startswith("file://") or path.startswith("skv://"):
            from surrealdb_tpu.kvs.file import FileBackend

            self.backend = FileBackend(path.split("://", 1)[1])
            self._register_ann_cache_dir(path.split("://", 1)[1])
        elif path.startswith("remote://"):
            # distributed mode: stateless database node over a shared
            # transactional KV service (reference kvs/tikv/mod.rs:32);
            # a comma-separated address list names a replica set — the
            # client follows primary failovers automatically
            from surrealdb_tpu.kvs.remote import RemoteBackend

            self.backend = RemoteBackend(path.split("://", 1)[1],
                                         telemetry=self.telemetry)
        elif path.startswith("shard://"):
            # range-sharded distributed mode: the address list names the
            # META group (shard 0); the shard map is read from there and
            # reads/commits route by key range (kvs/shard.py)
            from surrealdb_tpu.kvs.shard import ShardedBackend

            self.backend = ShardedBackend(path.split("://", 1)[1],
                                          telemetry=self.telemetry)
        else:
            raise SdbError(f"unknown datastore path: {path!r}")
        # cross-transaction caches / engines
        self.lock = threading.RLock()
        self.vector_indexes: dict = {}  # (ns,db,tb,ix) -> TpuVectorIndex
        # held, for a bounded time, by an auto-commit statement that lost
        # its commit to another writer while it runs again
        # (exec/executor.py CONFLICT_RETRIES)
        self.retry_floor = threading.Lock()
        self.index_builds: dict = {}  # (ns,db,tb,ix) -> building status
        self.ft_indexes: dict = {}  # (ns,db,tb,ix) -> FullTextIndex
        # live subscriptions, indexed by (ns,db,tb) — the write path
        # gates on count_for() instead of scanning every subscription
        from surrealdb_tpu.server.fanout import FanoutHub, \
            SubscriptionRegistry

        self.live_queries = SubscriptionRegistry()
        self.notifications: list[Notification] = []  # in-proc, bounded
        self.notification_handlers: list = []  # callables(Notification)
        # the notification fan-out spine: post-commit dispatch workers +
        # per-session bounded outboxes (threads spawn lazily on first
        # publish — embedded datastores that never LIVE pay nothing)
        self.fanout = FanoutHub(self)
        # full-text result cache: bounded LRU (entry + byte caps) — a
        # hot mixed read/write table must not grow one dead entry per
        # write-version forever. Registered with the memory accountant
        # below; evictions surface as ft_cache_evictions.
        from surrealdb_tpu.resource import BudgetedLRU

        self._ft_cache = BudgetedLRU(cnf.FT_CACHE_ENTRIES,
                                     cnf.FT_CACHE_BYTES)
        self.ml_cache: dict = {}  # (ns,db,name,version,hash) -> SurmlFile
        self.module_cache: dict = {}  # (ns,db,name) -> (hash, wasm Instance)
        self.sequences: dict = {}
        self._hlc_wall = 0  # HLC: last physical millis issued
        self._hlc_count = 0  # HLC: logical counter within the millisecond
        self.graph_engine = None  # (ns,db,node_tb,edge_tb,dir) -> CsrGraph
        self.graph_versions = {}  # (ns,db,tb) -> write counter
        # observability (reference: kvs::Metrics gauges + kvs/slowlog.rs)
        import os as _os

        self.metrics = {
            "transactions": 0, "commits": 0, "cancels": 0,
            "statements": 0, "statement_errors": 0, "slow_queries": 0,
        }
        try:
            self.slow_log_threshold_ms = float(
                _os.environ.get("SURREAL_SLOW_QUERY_THRESHOLD_MS", "0") or 0
            )
        except ValueError:
            self.slow_log_threshold_ms = 0.0
        self.slow_log: list = []  # (ms, sql-ish label) ring
        # parsed-statement cache: repeated query texts (the common client
        # pattern — same SQL, different $vars) skip the parser entirely.
        # ASTs are execution-state-free, so cached statement lists are
        # shared across concurrent executors.
        self._ast_cache: dict = {}
        self._ast_cache_cap = cnf.AST_CACHE_SIZE
        # cluster identity (reference dbs/node.rs); background loops start
        # only for served/clustered instances via start_node_tasks()
        from surrealdb_tpu.node import make_node_id

        self.node_id = make_node_id()
        self.node_tasks = None
        # in-flight (non-LIVE) query registry: KILL <query-id>, INFO FOR
        # SYSTEM exposure, drain-time cancellation (inflight.py)
        from surrealdb_tpu.inflight import InflightRegistry

        self.inflight = InflightRegistry(self.telemetry)
        # device supervisor health gauges (device_degraded,
        # device_restarts, ...) — the supervisor itself is process-wide
        # and lazy; registering gauges spawns nothing
        from surrealdb_tpu.device import attach_telemetry

        attach_telemetry(self.telemetry)
        # node-wide memory governance: register this datastore's
        # derived-state accounts (vector engines register their own as
        # they are created) and surface the accountant through this
        # hub's gauges/counters. Accounts hold the datastore weakly —
        # a closed/discarded ds is pruned, never pinned.
        from surrealdb_tpu import resource as _resource

        _resource.attach_telemetry(self.telemetry)
        self._mem_ft = _resource.register(
            "ft", "ft-cache", self._ft_cache_bytes,
            evict=self._ft_cache_evict, owner=self,
        )
        self._mem_csr = _resource.register(
            "csr", "csr-blocks", self._csr_mem_bytes,
            evict=self._csr_mem_evict, owner=self,
        )
        # columnar executor state: the version-keyed scalar column store
        # (exec/batch.py) plus the brute-scan vector columns (col.py) —
        # both pure caches over the record keyspace, eviction = drop +
        # rebuild-on-touch
        self._table_columns: dict = {}
        self._vector_columns: dict = {}
        self._mem_col = _resource.register(
            "col", "column-store", self._col_mem_bytes,
            evict=self._col_mem_evict, owner=self,
        )
        # statement-scoped RNG (ORDER BY RAND): seeded via
        # SURREAL_RAND_SEED for reproducible sim/bench runs
        import random as _rnd

        self.rng = _rnd.Random(cnf.RAND_SEED or None)
        from surrealdb_tpu.exec.batch import counters as _col_counters

        self._columnar_counters = _col_counters(self)
        for _ck in ("rows_vectorized", "rows_fallback", "scan_rows_owned",
                    "colstore_hits", "colstore_builds", "fused_knn_queries",
                    "pushdown_rows_pruned"):
            self.telemetry.register_counter(
                f"columnar_{_ck}",
                lambda k=_ck: self._columnar_counters.get(k, 0)
            )
        self.telemetry.register_counter(
            "ft_cache_evictions", lambda: self._ft_cache.evictions
        )
        # record-id texts built, not served from the object's slot
        # (val.RecordId.render): process-wide, like the stage table
        from surrealdb_tpu.val import rid_renders as _rid_renders

        self.telemetry.register_counter("rid_renders", _rid_renders)
        # native memtable calls made keeping the interpreter lock, and
        # try-lock calls that found the store's mutex held and fell back
        # to the releasing binding (native/__init__.py): process-wide too
        from surrealdb_tpu.native import kv_native_busy, kv_native_kept

        self.telemetry.register_counter("kv_native_kept", kv_native_kept)
        self.telemetry.register_counter("kv_native_busy", kv_native_busy)
        # arrays of a JSON request body parsed natively, and bodies whose
        # native pass was skipped or refused (native/jsonnum.py)
        from surrealdb_tpu.native import jsonnum as _jsonnum

        self.telemetry.register_counter(
            "rpc_json_native_arrays", _jsonnum.rpc_json_native_arrays)
        self.telemetry.register_counter(
            "rpc_json_native_fallbacks", _jsonnum.rpc_json_native_fallbacks)
        # index-serving shard count across all sharded vector indexes
        # (0 on unsharded stores; pairs with the knn_shard_fanout /
        # knn_partial_results / knn_hedged_dispatches counters)
        self.telemetry.register_gauge(
            "knn_index_shards",
            lambda: sum(
                len(getattr(eng, "parts", ()) or ())
                for eng in list(self.vector_indexes.values())
            ),
        )
        # shared decoded-catalog cache (version, dict); local backends
        # only — a remote keyspace can change under us without a local
        # commit, so remote datastores skip it
        self._catalog_ver = 0
        self._catalog_shared = (0, {})
        from surrealdb_tpu.kvs.remote import RemoteBackend as _RB
        from surrealdb_tpu.kvs.shard import ShardedBackend as _SB

        self._local_catalog_cache = not isinstance(self.backend, (_RB, _SB))
        if not self._local_catalog_cache:
            # follower-read observability: worst observed closed-ts lag
            # across replica-set members (-1 until a follower read runs)
            self.telemetry.register_gauge(
                "repl_closed_ts_lag_s",
                lambda: round(self.backend.replication_lag_s(), 3),
            )
        # TSO window state (sharded stores lease versionstamp windows
        # from the meta shard instead of running a local HLC); windows
        # expire so an idle node can't stamp far in the logical past
        self._tso_next = 0
        self._tso_end = 0
        self._tso_expiry = 0.0
        self._stamp_storage_version(check_version)

    # -- resource accounting (resource.py) -----------------------------------

    def _ft_cache_bytes(self) -> int:
        return int(self._ft_cache.nbytes)

    def _ft_cache_evict(self):
        # drop the coldest half: the next identical search re-runs the
        # posting walk (pure cache, KV truth untouched)
        self._ft_cache.shrink(0.5)

    def _csr_mem_bytes(self) -> int:
        ge = self.graph_engine
        total = 0
        if ge:
            for g in list(ge.values()):
                nb = getattr(g, "nbytes", None)
                if nb is not None:
                    total += int(nb())
        totals = getattr(self, "_edge_oplog_totals", None)
        if totals:
            # ~3 small objects per logged edge op
            total += sum(totals.values()) * 96
        return total

    def _col_mem_bytes(self) -> int:
        from surrealdb_tpu.exec.batch import store_nbytes

        total = store_nbytes(self)
        for col in list(getattr(self, "_vector_columns", {}).values()):
            mat = getattr(col, "mat", None)
            if mat is not None:
                total += int(mat.nbytes)
            norms = getattr(col, "_norms", None)
            if norms is not None:
                total += int(norms.nbytes)
        return total

    def _col_mem_evict(self):
        from surrealdb_tpu.exec.batch import store_evict

        store_evict(self)

    def _csr_mem_evict(self):
        # CSR adjacency + the edge op log are caches over the `~` graph
        # keys: dropping them degrades the next traversal to a rebuild
        # scan (get_csr), exactly like a version bump would
        self.graph_engine = {} if self.graph_engine is not None else None
        self._edge_oplog = {}
        self._edge_oplog_totals = {}

    def _register_ann_cache_dir(self, store_path: str):
        """Disk-backed stores anchor the persisted-ANN artifact dir
        (idx/cagra.py save_index) next to the data: a restart reloads
        a 1M-row graph build in seconds."""
        import os as _os

        base = store_path if _os.path.isdir(store_path) \
            else _os.path.dirname(_os.path.abspath(store_path))
        self.ann_snapshot_dir = _os.path.join(base, ".ann-cache")

    def start_node_tasks(self, interval_s: float = 10.0,
                         stale_s: float = 30.0):
        """Start heartbeat + membership-check loops (reference
        engine/tasks.rs:48-56). Idempotent."""
        from surrealdb_tpu.node import NodeTasks

        if self.node_tasks is None:
            self.node_tasks = NodeTasks(self, interval_s, stale_s)
            self.node_tasks.start()
        return self.node_tasks


    # -- transactions -------------------------------------------------------
    def transaction(self, write: bool = True,
                    max_staleness: Optional[float] = None) -> Transaction:
        """Open a transaction. `max_staleness` (seconds, read-only
        transactions only) opts into closed-timestamp follower reads on
        replicated backends: the read may be served by a replica that
        can PROVE it is at most that stale. Local backends serve latest
        — trivially within any bound — and never see the parameter.
        The default (None) is byte-identical to the exact path."""
        self.metrics["transactions"] += 1
        if max_staleness is not None and not write \
                and getattr(self.backend, "supports_staleness", False):
            return Transaction(
                self.backend.transaction(write,
                                         max_staleness=max_staleness),
                write,
            )
        if self._local_catalog_cache:
            # stage `txn_lock_ds`: the wait for this process-wide
            # mutex alone (the store's own is `txn_lock_store`, taken
            # inside it); recorded once it is given back
            t0 = time.monotonic_ns()
            self.lock.acquire()
            t1 = time.monotonic_ns()
            try:
                t = Transaction(self.backend.transaction(write), write)
                t._ds = self
                t._shared_cat = self._catalog_shared
            finally:
                self.lock.release()
            stage_record("txn_lock_ds", t1 - t0, end_ns=t1)
            return t
        return Transaction(self.backend.transaction(write), write)

    def record_statement(self, ok: bool, time_ns: int, label: str = ""):
        self.metrics["statements"] += 1
        if not ok:
            self.metrics["statement_errors"] += 1
        ms = time_ns / 1e6
        if self.slow_log_threshold_ms and ms >= self.slow_log_threshold_ms:
            self.metrics["slow_queries"] += 1
            self.slow_log.append((round(ms, 3), label[:200]))
            if len(self.slow_log) > 1000:
                del self.slow_log[:500]

    # -- execution ----------------------------------------------------------
    def execute(
        self,
        sql: str,
        ns: Optional[str] = None,
        db: Optional[str] = None,
        vars: Optional[dict] = None,
        session: Optional[Session] = None,
        deadline: Optional[float] = None,
        handle=None,
    ) -> list[QueryResult]:
        """Parse and run a SurrealQL query; one QueryResult per statement.

        `deadline` is an absolute `time.monotonic()` point seeding every
        statement's ExecContext (the edge X-Surreal-Timeout budget);
        `handle` is a pre-opened `QueryHandle` when the caller needs to
        cancel from outside (server disconnect watch). A nested execute
        on the same thread (api::invoke, surrealism host sql) inherits
        the enclosing query's handle instead of registering a new one."""
        from surrealdb_tpu.exec.executor import Executor
        from surrealdb_tpu.syn import parse

        from surrealdb_tpu import inflight as _inflight
        from surrealdb_tpu.err import ParseError

        # embedded convenience path: a caller holding the Datastore object
        # has root access by construction (like the reference's local engine)
        sess = session or Session(ns=ns, db=db, auth_level="owner")
        if ns is not None:
            sess.ns = ns
        if db is not None:
            sess.db = db
        stmts = self._ast_cache.get(sql)
        if stmts is None:
            t_parse = time.perf_counter_ns()
            try:
                stmts = parse(sql, capabilities=self.capabilities)
                stage_record("parse", time.perf_counter_ns() - t_parse)
            except ParseError as e:
                # a parse error fails the whole query (reference behaviour)
                return [QueryResult(error=str(e))]
            from surrealdb_tpu import cnf as _cnf

            if len(stmts) > _cnf.MAX_STATEMENTS_PER_QUERY:
                return [QueryResult(
                    error="The query contains too many statements"
                )]
            with self.lock:
                if len(self._ast_cache) >= self._ast_cache_cap:
                    self._ast_cache.clear()
                self._ast_cache[sql] = stmts
        own = None
        if handle is None:
            cur = _inflight.current()
            if cur is not None:
                handle = cur  # nested execute: ride the enclosing query
                if cur.edge:
                    cur.refine(sess.ns, sess.db, sql)
            else:
                own = handle = self.inflight.open(
                    sess.ns, sess.db, sql, deadline
                )
        elif deadline is not None and handle.deadline is None:
            handle.deadline = deadline
        try:
            with _inflight.activate(handle):
                ex = Executor(self, sess)
                return ex.execute(stmts, vars or {})
        finally:
            if own is not None:
                self.inflight.close(own)

    def query(self, sql: str, ns="test", db="test", vars=None):
        """Convenience: execute and unwrap every statement's result."""
        return [r.unwrap() for r in self.execute(sql, ns=ns, db=db, vars=vars)]

    def query_one(self, sql: str, ns="test", db="test", vars=None):
        out = self.query(sql, ns=ns, db=db, vars=vars)
        return out[-1] if out else None

    # -- notifications ------------------------------------------------------
    def notify(self, notification: Notification):
        """Enqueue-only delivery: the fan-out hub appends to the bounded
        in-process buffer, invokes embedded handlers (errors counted,
        never swallowed silently), and routes to the bound session
        outbox. No socket I/O, no unbounded growth, and nothing here
        runs on a committing writer's thread — the doc pipeline captures
        events and the post-commit dispatch workers call this."""
        self.fanout.deliver(notification)

    def drain_notifications(self) -> list[Notification]:
        # barrier: anything already committed must be matched and
        # routed before the drain returns (the embedded consumer's
        # read-your-own-writes contract survives async dispatch)
        self.fanout.flush()
        with self.lock:
            out = self.notifications
            self.notifications = []
        return out

    def gc_session_lives(self, lids) -> int:
        """Drop a dead session's live queries: registry entries, outbox
        routes, and the persisted `!lq` catalog rows (the reference GCs
        these from engine/tasks.rs:49-51; without it a session that died
        without KILL pays match cost on every write forever)."""
        lids = [str(x) for x in lids]
        subs = []
        for lid in lids:
            self.fanout.unbind(lid)
            sub = self.live_queries.pop(lid, None)
            if sub is not None:
                subs.append((lid, sub))
        if not subs:
            return 0
        from surrealdb_tpu import key as K

        try:
            txn = self.transaction(write=True)
        except SdbError:
            # KV unavailable: the registry is clean, rows sweep later
            self.telemetry.inc("live_gc_collected", len(subs))
            return len(subs)
        committed = False
        try:
            for lid, sub in subs:
                txn.delete(K.lq_def(sub.ns, sub.db, sub.tb, lid))
            txn.commit()
            committed = True
        except SdbError:
            pass  # rows survive until the next sweep
        finally:
            # ANY non-commit exit must release the write transaction —
            # the periodic sweep swallows errors, so a leaked handle
            # would recur every interval
            if not committed:
                try:
                    txn.cancel()
                except SdbError:
                    pass
        self.telemetry.inc("live_gc_collected", len(subs))
        return len(subs)

    STORAGE_VERSION = 1  # on-disk format version (reference kvs/version/)

    def _stamp_storage_version(self, check: bool = True):
        """Stamp new stores; refuse to open any OTHER format version
        (reference version markers: `surreal upgrade` migrates forward,
        a plain open never does, and a FUTURE format never opens)."""
        from surrealdb_tpu import key as K

        txn = self.transaction(write=True)
        try:
            cur = txn.get(K.storage_version())
            if cur is None:
                txn.set(K.storage_version(),
                        str(self.STORAGE_VERSION).encode())
                txn.commit()
                return
            txn.cancel()
            if not check:
                return  # the upgrade/fix CLI opens old stores to migrate
            have = int(cur.decode() or 1)
            if have > self.STORAGE_VERSION:
                raise SdbError(
                    f"The storage version {have} is newer than this build "
                    f"supports ({self.STORAGE_VERSION}); run a newer "
                    f"release or `surreal fix`"
                )
            if have < self.STORAGE_VERSION:
                raise SdbError(
                    f"The storage version {have} is older than this build "
                    f"({self.STORAGE_VERSION}); run `surreal upgrade` to "
                    f"migrate the data"
                )
        except SdbError:
            raise
        except BaseException:
            txn.cancel()
            raise

    def next_versionstamp(self) -> int:
        """Hybrid logical clock versionstamp (reference kvs/clock.rs
        HlcTimeStamp): [44-bit wall millis | 20-bit logical counter].
        Monotonic even when the wall clock stalls or steps backwards —
        the logical counter advances within a millisecond, and the
        physical part never regresses below the last issued stamp.

        Sharded stores instead draw from a sequence window leased from
        the meta shard (PD-style TSO, kvs/shard.py): per-node HLCs
        could interleave inconsistently across shards, but windows off
        one counter keep `SHOW CHANGES` ordering globally consistent.
        Window starts embed wall millis in the same [44|20] layout, so
        stamps stay comparable to datetime-derived bounds."""
        tso = getattr(self.backend, "tso_window", None)
        if tso is not None:
            now = time.monotonic()
            with self.lock:
                if self._tso_next < self._tso_end \
                        and now < self._tso_expiry:
                    v = self._tso_next
                    self._tso_next += 1
                    return v
                # an expired window is abandoned, not drained: a
                # changefeed cursor may already have advanced past it,
                # and stamps issued behind the cursor would be silently
                # skipped by SHOW CHANGES consumers — staleness is
                # bounded by the window TTL
                self._tso_end = 0
            # refill outside ds.lock: one meta round-trip per window
            start, end = tso(cnf.KV_TSO_WINDOW)
            with self.lock:
                if self._tso_next >= self._tso_end:
                    # windows are disjoint and strictly increasing, so
                    # adopting a fresh one never regresses; a racing
                    # refill that lost simply wastes its window
                    self._tso_next, self._tso_end = start, end
                    self._tso_expiry = (time.monotonic()
                                        + cnf.KV_TSO_WINDOW_TTL_S)
                v = self._tso_next
                self._tso_next += 1
                return v
        with self.lock:
            wall = int(time.time() * 1000)
            if wall > self._hlc_wall:
                self._hlc_wall = wall
                self._hlc_count = 0
            else:
                self._hlc_count += 1
                if self._hlc_count >= (1 << 20):
                    # logical overflow within one ms: borrow a millisecond
                    self._hlc_wall += 1
                    self._hlc_count = 0
            return (self._hlc_wall << 20) | self._hlc_count

    def close(self):
        if self.node_tasks is not None:
            self.node_tasks.stop()
        self.fanout.close_all()
        self._mem_ft.close()
        self._mem_csr.close()
        self.backend.close()
