"""Supervisor / IPC: the send thread's own work for one device RPC, mean
over the window's calls: stage `rpc_send` (`device/supervisor.py
_record_rpc_parts`): from the send thread holding the item until
`proto.send_msg` has returned, or until the runner had the request where
that came first (the thread may lose the interpreter between `sendall` and
its stamp): the header's encoding and `sendall`. The second of
`rpc_out_ms`'s three parts. A program without the stage reads nothing."""


def read(window):
    st = window["stages"].get("rpc_send")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
