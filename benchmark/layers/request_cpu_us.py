"""Server: CPU time of the thread that serves a request, per request: what
`cpu_ms` of the `request` stage (`time.thread_time_ns` at its two ends)
gained between the window's two snapshots. Times `qps` it is the number of
cores the request threads keep busy: near 1 the serving process is bound by
its interpreter, well under 1 its threads are waiting. The dispatcher's
share of a batch is in it (a rider dispatches on its own request thread);
the HTTP handler thread that only watches the socket, the send and recv
threads of the supervisor and the runner process are not."""


def read(window):
    after = (window["after"].get("stages") or {}).get("request") or {}
    before = (window["before"].get("stages") or {}).get("request") or {}
    if after.get("cpu_ms") is None or not window["requests"]:
        return None
    return (after["cpu_ms"] - before.get("cpu_ms", 0.0)) * 1e3 \
        / window["requests"]
