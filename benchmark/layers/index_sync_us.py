"""Index engine: wall time a request spends bringing the index's cache up to
the version its transaction reads, per request of the window: stage
`index_sync` (`idx/vector.py TpuVectorIndex.sync`, recorded only when the
version moved: the op log read, `_apply_entries` under the engine's write
lock, or the wait for the thread that did both; inside `index_knn`). In a
read-only window nothing records it. A program without the stage reads
nothing."""


def read(window):
    st = window["stages"].get("index_sync")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
