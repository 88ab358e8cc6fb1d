"""Batcher: from the moment a dispatcher took a rider's batch until the rider
held its answer, per request: stage `batch_ride` (`device/batcher.py submit`):
its batch's whole dispatch (`batch_dispatch`) plus the rider's own wake-up.
With `batch_wait_us` it makes up `index_knn_us` but for the sync check."""


def read(window):
    st = window["stages"].get("batch_ride")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
