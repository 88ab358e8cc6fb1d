"""Supervisor / IPC: the way back of one device RPC, mean over the window's
calls: stage `rpc_back` (`device/supervisor.py _record_rpc_parts`): from the
runner's stamp just before it sends the reply until the waiting thread runs
again: the encoding, the socket, the supervisor's recv thread, the event,
and the wait for the interpreter lock."""


def read(window):
    st = window["stages"].get("rpc_back")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
