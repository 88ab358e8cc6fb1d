"""KV store: the wait to acquire `Datastore.lock` where a transaction opens
(`kvs/ds.py transaction`), per request: stage `txn_lock_ds`. The first of the
two process-wide mutexes inside `txn_open_us`; what `txn_open_us` holds
beyond the two is the holder's own work and its waits for the interpreter.
Every transaction records it, the statement's own among them. A program
without the stage reads nothing."""


def read(window):
    st = window["stages"].get("txn_lock_ds")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
