"""Col store: column blocks shipped to the runner inside the window: what
the supervisor's `col_ships` (`device/supervisor.py note_col_ship`, counted by
`col.py device_topk`'s loader) gained between the window's two snapshots. A
block is shipped once a table version and never with a query, and the window
is read-only: this reads 0. A program without the counter reads nothing."""


def read(window):
    before = (window["before"].get("supervisor") or {}).get("col_ships")
    after = (window["after"].get("supervisor") or {}).get("col_ships")
    if before is None or after is None:
        return None
    return after - before
