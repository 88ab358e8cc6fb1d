"""Runner: riders of the window whose paths at some level passed the
capacity they rode, so that they were dispatched again on a higher rung of
the ladder (or, past its top, walked on the host): what
`runner_status()["csr"]["overflows"]` (`device/kernelstats.py CSR`, counted
in `device/csrstore.py bag_hop`) gained between the window's two snapshots.
The deployment's ladder is sized so that this reads 0."""


def read(window):
    before = (window["before"].get("runner") or {}).get("csr")
    after = (window["after"].get("runner") or {}).get("csr")
    if not before or not after:
        return None
    return after["overflows"] - before["overflows"]
