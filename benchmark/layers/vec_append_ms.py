"""Supervisor / IPC: one delta to the resident block, call to reply, mean over
the window's deltas: stage `vec_append` (`device/supervisor.py _append`:
the changed rows gathered, ONE `vec_append` RPC, the runner's donating
program launched; the call's own `device_rpc` and its parts are recorded
inside it, as any call's). The dispatch that sends it waits for it before its
`vec_knn`. A program without the stage reads nothing."""


def read(window):
    st = window["stages"].get("vec_append")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
