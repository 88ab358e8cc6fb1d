"""Supervisor / IPC: a sent request until the runner has it, mean over the
window's calls: stage `rpc_wire_out` (`device/supervisor.py
_record_rpc_parts`): from `send_msg`'s return until the runner's `recv`
stamp: the socket, the runner finishing the op before this one, its read and
decode. The third of `rpc_out_ms`'s three parts, and the one that grows when
the runner is busy, not the serving process. A program without the stage
reads nothing."""


def read(window):
    st = window["stages"].get("rpc_wire_out")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
