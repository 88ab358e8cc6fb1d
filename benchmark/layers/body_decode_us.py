"""Server: the request body's decode, per request: stage `body_decode`
(`server/__init__.py _decode_body`: a `/rpc` body's bytes to the request's
value, JSON float arrays parsed natively by `native/jsonnum.py`, wall
time). Inside `request_edge_us`. A program without the stage reads
nothing."""


def read(window):
    st = window["stages"].get("body_decode")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
