"""KV store: the wait for the store's own mutex where a transaction takes its
snapshot, per request: stage `txn_lock_store` (`kvs/mem.py snapshot`: the
wait to acquire `VersionedStore.lock`; `kvs/native_mem.py`: the whole
`sdb_snapshot` call, because the native memtable's mutex lies inside the
library, and the call hands the interpreter away and has to get it back).
Taken while `Datastore.lock` is held; inside `txn_open_us`. A program without
the stage reads nothing."""


def read(window):
    st = window["stages"].get("txn_lock_store")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
