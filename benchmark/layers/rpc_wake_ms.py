"""Supervisor / IPC: the last hand-off of a device RPC, mean over the
window's calls: stage `rpc_wake` (`device/supervisor.py
_record_rpc_parts`): from the recv thread holding the reply until the
waiting caller runs again: the pending lookup under `_lock`, `Event.set`,
the waiter's wake. The second of `rpc_back_ms`'s two parts. A program
without the stage reads nothing."""


def read(window):
    st = window["stages"].get("rpc_wake")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
