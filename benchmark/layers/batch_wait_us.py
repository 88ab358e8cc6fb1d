"""Batcher: how long a rider sat in the queue before a dispatcher took it,
per request: stage `batch_wait` (`device/batcher.py submit`: enqueue until the
grab). It is the time queued behind the dispatch in flight; a lone caller
dispatches at once and reads next to nothing."""


def read(window):
    st = window["stages"].get("batch_wait")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
