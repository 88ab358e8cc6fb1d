"""Supervisor / IPC: whole vector blocks shipped to the runner inside the
window: what the supervisor's `vec_full_ships` (`device/supervisor.py
ensure_loaded`, every `vec_load`) gained between the window's two snapshots.
A store that grows in place takes its writes as deltas; a whole ship comes
once a capacity step of rows, with new programs behind it: 0 in a sound
window. A program without the counter reads nothing."""


def read(window):
    before = (window["before"].get("supervisor") or {}).get("vec_full_ships")
    after = (window["after"].get("supervisor") or {}).get("vec_full_ships")
    if before is None or after is None:
        return None
    return after - before
