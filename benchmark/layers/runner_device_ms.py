"""Runner: from the first launch of an op until its first output is on the
host, on the runner's host clock, mean over the window's calls: stage
`runner_device` (`kernelstats.phase("device")` in `device/vecstore.py knn`
and `device/annstore.py search`: the eager `jit__pad` / `jit_reshape`
programs, the kernel, and the `np.asarray` that waits for it and brings the
first output back). Against the trace's device seconds of one run of the
cell's program it gives the launch latency, the small eager programs beside
it and one copy back; it cannot be smaller than those device seconds."""


def read(window):
    st = window["stages"].get("runner_device")
    if not st or not st["count"]:
        return None
    return st["total_us"] / st["count"] / 1e3
