"""Server: time requests waited for an admission slot, per request."""


def read(window):
    st = window["stages"].get("admission_wait")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
