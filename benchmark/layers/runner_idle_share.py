"""Runner: the share of the window in which the chip's process had nothing
to do: what `loop.idle_ns` (blocked in `recv_msg`) gained between the two
`runner_status()` snapshots over what `idle_ns` and `busy_ns` gained together
(`device/kernelstats.py LOOP`, counted by `device/runner.py serve`). With
`device_idle_share` it splits the chip's idle time: up to this share nothing
had been sent to the runner, the rest is the runner's own host work around
the kernels.

A traced run's `stop_trace` (`trace["stop_trace_s"]`, 3-10 s on a v5e with
the hook's Python tracer on) is called by the benchmark's hook on a thread of
its own inside the runner. The serve loop goes on meanwhile, 20-25 % slower,
and stands still for about 1.5 s of it inside an op (one `runner_device` of
1.56 s in `exact128.knn-c1`, PERF.md section 5): those seconds count as `busy`
here, so a traced run's share reads a few points low.
"""


def read(window):
    before = (window["before"].get("runner") or {}).get("loop")
    after = (window["after"].get("runner") or {}).get("loop")
    if not before or not after:
        return None
    idle = after["idle_ns"] - before["idle_ns"]
    busy = after["busy_ns"] - before["busy_ns"]
    if idle + busy <= 0:
        return None
    return 100.0 * idle / (idle + busy)
