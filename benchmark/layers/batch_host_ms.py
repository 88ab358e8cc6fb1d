"""Index engine: the host's share of one dispatch: stage `batch_dispatch`
(wall time of `DeviceBatcher._run`) less `device_rpc`, per dispatch. Before
the RPC: stacking the riders, the read lock, `ensure_loaded`; after it:
`knn_post_ms`, and handing out the results."""


def read(window):
    stages, b = window["stages"], window["batching"]
    st = stages.get("batch_dispatch")
    if not st or not b.get("dispatches"):
        return None
    rpc = stages.get("device_rpc", {}).get("total_us", 0.0)
    return (st["total_us"] - rpc) / b["dispatches"] / 1e3
