"""Supervisor / IPC: wall time of one device RPC (frame out, the runner's
kernel, frame back), mean over the window's dispatches."""


def read(window):
    st = window["stages"].get("device_rpc")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
