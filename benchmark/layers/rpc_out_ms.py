"""Supervisor / IPC: the way out of one device RPC, mean over the window's
calls: stage `rpc_out` (`device/supervisor.py _record_rpc_parts`): from
`_call_live`'s entry until the runner had the request read and decoded: the
queue to the send thread, the encoding, the socket, the runner's read. Both
ends stamp CLOCK_MONOTONIC. A call that finds the runner busy with another
waits here."""


def read(window):
    st = window["stages"].get("rpc_out")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
