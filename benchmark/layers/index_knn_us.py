"""Index engine: wall time inside `TpuVectorIndex.knn` (cache sync, batcher
wait, device RPC, re-rank), per request."""


def read(window):
    st = window["stages"].get("index_knn")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
