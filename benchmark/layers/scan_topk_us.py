"""Executor: wall time of a scan's device leg, per request: stage `vec_scan`
(`exec/stream.py VecTopKScanOp`), from the submit to the scans' batcher until
the row numbers are back. It holds the batcher's wait and ride
(`batch_wait_us`, `batch_ride_us`), and in the ride the RPC and the program
`exact_scan`. A program without the stage reads nothing."""


def read(window):
    st = window["stages"].get("vec_scan")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
