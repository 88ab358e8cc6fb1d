"""Native in-memory engine: the C++ MVCC memtable behind the Transactable
contract (reference role: kvs/mem's native MVCC btree). Transactions pin a
snapshot version at start (repeatable reads), keep a Python-side buffered
writeset, and commit through the native batch op which validates
write-write conflicts against versions committed after the snapshot — the
same optimistic model as kvs/mem.MemTx."""

from __future__ import annotations

import time
from typing import Optional

from surrealdb_tpu.err import SdbError, TxConflict
from surrealdb_tpu.kvs.api import Backend, BackendTx
from surrealdb_tpu.kvs.mem import CONFLICT_MSG
from surrealdb_tpu.native import NativeMemtable
from surrealdb_tpu.telemetry import stage_record


class NativeMemTx(BackendTx):
    def __init__(self, store: "NativeMemBackend", write: bool):
        self.store = store
        self.write = write
        # stage `txn_lock_store`, as kvs/mem.py `snapshot` records it:
        # this store's mutex lies inside the library, so the reading is
        # the whole call: `sdb_snapshot_try` keeping the interpreter, and
        # where the mutex was held, the blocking `sdb_snapshot` after it,
        # which hands the interpreter away and has to get it back
        t0 = time.monotonic_ns()
        self.snap = store.table.snapshot()
        stage_record("txn_lock_store", time.monotonic_ns() - t0)
        self.writes: dict[bytes, Optional[bytes]] = {}
        self.savepoints: list[dict] = []
        self.done = False

    def _check(self):
        if self.done:
            raise SdbError("transaction is finished")

    def _release(self):
        if self.snap is not None:
            self.store.table.release(self.snap)
            self.snap = None

    def __del__(self):
        try:
            self._release()
        except Exception:
            pass

    def get(self, key: bytes) -> Optional[bytes]:
        self._check()
        if key in self.writes:
            return self.writes[key]
        return self.store.table.get_at(key, self.snap)

    def set(self, key: bytes, val: bytes) -> None:
        self._check()
        if not self.write:
            raise SdbError("transaction is read-only")
        self.writes[key] = bytes(val)

    def delete(self, key: bytes) -> None:
        self._check()
        if not self.write:
            raise SdbError("transaction is read-only")
        self.writes[key] = None

    def scan(self, beg, end, limit=None, reverse=False):
        self._check()
        if not self.writes:
            yield from self.store.table.scan_at(beg, end, self.snap, limit,
                                                reverse)
            return
        # merge the snapshot scan with the overlay
        base = dict(self.store.table.scan_at(beg, end, self.snap))
        for k, v in self.writes.items():
            if beg <= k < end:
                if v is None:
                    base.pop(k, None)
                else:
                    base[k] = v
        keys = sorted(base, reverse=reverse)
        n = 0
        for k in keys:
            yield k, base[k]
            n += 1
            if limit is not None and n >= limit:
                return

    def count(self, beg, end):
        self._check()
        if not self.writes:
            return self.store.table.count_range_at(beg, end, self.snap)
        return sum(1 for _ in self.scan(beg, end))

    def new_save_point(self):
        self.savepoints.append(dict(self.writes))

    def rollback_to_save_point(self):
        if self.savepoints:
            self.writes = self.savepoints.pop()

    def release_last_save_point(self):
        if self.savepoints:
            self.savepoints.pop()

    def commit(self):
        self._check()
        self.done = True
        snap, self.snap = self.snap, None
        # commit_batch validates conflicts and releases the snapshot under
        # one mutex hold on the C++ side (see sdb_commit_batch)
        ver = self.store.table.commit_batch(snap, self.writes.items())
        if not ver:
            raise TxConflict(CONFLICT_MSG)

    def cancel(self):
        self.done = True
        self.writes.clear()
        self._release()


class NativeMemBackend(Backend):
    def __init__(self):
        self.table = NativeMemtable()

    def transaction(self, write: bool) -> NativeMemTx:
        return NativeMemTx(self, write)
