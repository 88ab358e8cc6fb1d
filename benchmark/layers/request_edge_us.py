"""Server: what a request costs at the edge, per request: the `request`
stage (`server/__init__.py _dispatch_gated`: from before admission until the
reply is written) less the stages inside it that belong to other layers
(`admission_wait`, `parse`, `stmt_envelope`, `stmt_eval`). What is left is
the worker thread's start, the HTTP body read, the JSON decode of the
vector, the session, the reply's encoding and its write."""

INNER = ("admission_wait", "parse", "stmt_envelope", "stmt_eval")


def read(window):
    stages = window["stages"]
    st = stages.get("request")
    if not st or not window["requests"]:
        return None
    inner = sum(stages.get(name, {}).get("total_us", 0.0) for name in INNER)
    return (st["total_us"] - inner) / window["requests"]
