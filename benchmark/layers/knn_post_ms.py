"""Index engine: what the dispatcher thread does on the host once the RPC has
returned, per dispatch: stage `knn_post` (`idx/vector.py _device_knn_batch`
and `_ann_knn_batch`, `idx/segments.py _graph_span` a span and `knn_batch`
for their merge): the exact re-rank of every rider's candidates, ids to
record ids, the merge of the spans."""


def read(window):
    st, b = window["stages"].get("knn_post"), window["batching"]
    if not st or not b.get("dispatches"):
        return None
    return st["total_us"] / b["dispatches"] / 1e3
