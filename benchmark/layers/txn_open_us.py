"""KV store: wall time to open the statement's transaction
(`exec/executor.py`: `ds.transaction(write=True)` even for a SELECT), per
request. Under concurrent clients this is mostly waiting."""


def read(window):
    st = window["stages"].get("txn_open")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
