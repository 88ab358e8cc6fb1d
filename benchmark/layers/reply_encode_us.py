"""Server: the reply's encoding, per request: stage `reply_encode`
(`server/__init__.py _encode_reply`: the program's values to JSON or CBOR
bytes, once a reply, wall time). Inside `request_edge_us`. A program without
the stage reads nothing."""


def read(window):
    st = window["stages"].get("reply_encode")
    if not st or not window["requests"]:
        return None
    return st["total_us"] / window["requests"]
