"""Server: the silent milliseconds of the window: stage `request_stall`
(`server/stallwatch.py`: queries open and none finished for 0.5 s or more,
recorded with the whole silent span when one finishes again). 0.0 in a sound
window; a number whenever the watch ran (it records `gil_wake` every tick),
nothing from a program without it."""


def read(window):
    if not window["stages"].get("gil_wake"):
        return None
    st = window["stages"].get("request_stall")
    return st["total_us"] / 1e3 if st else 0.0
