"""Device: the share of the traced window in which no operation ran on the
chip: 1 - (union of the device plane's operation intervals) / window."""


def read(window):
    trace = window["trace"]
    if not trace or not trace.get("window_s") or trace.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
