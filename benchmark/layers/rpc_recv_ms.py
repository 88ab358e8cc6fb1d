"""Supervisor / IPC: a ready reply until the recv thread holds it, mean over
the window's calls: stage `rpc_recv` (`device/supervisor.py
_record_rpc_parts`): from the runner's `ready` stamp until `recv_msg` has
returned the decoded reply in `_recv_loop`: the runner's encode and send,
the socket, the recv thread's wake, its read and decode. The first of
`rpc_back_ms`'s two parts. A program without the stage reads nothing."""


def read(window):
    st = window["stages"].get("rpc_recv")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
