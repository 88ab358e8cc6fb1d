"""Executor: wall time a scan spends fetching its winners, per request: stage
`scan_fetch` (`exec/stream.py VecTopKScanOp`): `fetch_record` of the rows the
device ranked, in rank order (each a 768-float document decoded), yielded to
`ProjectOp`, which recomputes `s` in f64 from them. A program without the
stage reads nothing."""


def read(window):
    st = window["stages"].get("scan_fetch")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
