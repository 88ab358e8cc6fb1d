"""Runner: bytes resident on the fullest chip after the window."""


def read(window):
    used = [d["bytes_in_use"] for d in window["after"]["runner"]["devices"]
            if d["bytes_in_use"] is not None]
    return max(used) if used else None
