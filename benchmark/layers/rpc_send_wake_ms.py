"""Supervisor / IPC: the first hand-off of a device RPC, mean over the
window's calls: stage `rpc_send_wake` (`device/supervisor.py
_record_rpc_parts`): from `_call_live`'s entry until the send thread holds
the item (`_lock`, the queue's `put`, the send thread's wake). The first of
`rpc_out_ms`'s three parts: the three sum to it. A thread that wants the
interpreter back waits here for it; a program without the stage reads
nothing."""


def read(window):
    st = window["stages"].get("rpc_send_wake")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
