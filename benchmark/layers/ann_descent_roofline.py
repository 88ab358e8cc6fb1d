"""Kernels: the graph descent's share of its roofline on the chip.

From the device trace: the runs of the jitted program `jit__descent_impl`
inside the profiler window and the device seconds they took. Against them
the least time one run can take, by `moved` below: the bytes a search has to
read over the HBM bandwidth. The descent is a `fori_loop` of a fixed number
of iterations over static shapes, so the rows it scores are known on the
host: the runner counts them (`kernelstats.ANN`, in `runner_status()["ann"]`:
`rows_scored` = riders x iters x expand x out-degree, plus the probe rows
once a search), for the riders that asked and not the power of two they were
padded to. The mean over the window's searches stands for a traced run.

Bytes only: the int8 dot products are some 50 times below the bytes here (2
operations a byte read, against 393 TOP/s over 819 GB/s = 480), and
`peaks.json` has no int8 peak. A row scored costs its `dim` int8 values, its
f32 scale and the int32 id that named it; the queries, the frontier kept
between iterations and the answer are left out, so the count is under what
the kernel moves and the share cannot pass 100 %.
"""


def moved(rows_scored: float, dim: int) -> float:
    """Bytes one search has to read to score `rows_scored` rows."""
    return rows_scored * (dim + 4 + 4)


def read(window):
    trace, peaks, cfg = window["trace"], window["peaks"], window["config"]
    prog = (trace or {}).get("programs", {}).get("jit__descent_impl")
    before = (window["before"].get("runner") or {}).get("ann")
    after = (window["after"].get("runner") or {}).get("ann")
    if not prog or not prog["runs"] or not prog["seconds"] or not peaks \
            or not before or not after:
        return None
    searches = after["searches"] - before["searches"]
    if searches <= 0:
        return None
    rows = (after["rows_scored"] - before["rows_scored"]) / searches
    least = moved(rows, cfg["dim"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * prog["runs"] * least / prog["seconds"]
