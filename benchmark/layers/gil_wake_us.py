"""Server: what a wake costs, mean over the stall watch's ticks in the
window: stage `gil_wake` (`server/stallwatch.py`: one thread sleeps 0.1 s at
a time and records how much later than asked it ran again). At an idle
server this is the kernel's timer slack; under load it is the queue for the
interpreter that every hand-off of a request pays. A program without the
watch reads nothing."""


def read(window):
    st = window["stages"].get("gil_wake")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"]
