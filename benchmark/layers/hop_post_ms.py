"""Graph engine: what the dispatcher thread does on the host once the RPC
has returned, per dispatch: stage `hop_post` (`graph/csr.py _bag_dispatch`):
the reply's totals to Python, every rider's slice of the returned ids, and
the riders that passed a capacity put on the next rung (their second RPC
lies inside the stage's dispatch, not inside the stage)."""


def read(window):
    st, b = window["stages"].get("hop_post"), window["batching"]
    if not st or not b.get("dispatches"):
        return None
    return st["total_us"] / b["dispatches"] / 1e3
