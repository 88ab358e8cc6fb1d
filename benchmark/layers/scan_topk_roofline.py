"""Kernels: the exact scan's share of its roofline on the chip.

From the device trace: the runs of the jitted program `jit_exact_scan`
(`ops/topk.py exact_scan`, the exact store's one program) inside the profiler
window and the device seconds they took, start of the program to its end.
Against them the least time one run can take on this chip, by `costs` below:
the larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth. The riders of a run are the window's mean per scan dispatch,
unpadded, from the runner's own count (`runner_status()["scan"]`,
`kernelstats.SCAN`). What is counted is what the answer needs, once: the
f32 rows read once, one multiply-add a rider, row and dimension (not the six
bf16 passes `Precision.HIGHEST` makes of it, nor the norms), so the share
cannot pass 100 %. Expect the bytes to set the least time: at 32 riders a
row's 3 KB are read for 49 k operations, 16 operations a byte against the
chip's 240.
"""


def costs(rows: int, dim: int, k: int, riders: float):
    """(operations, bytes) one run has to do for `riders` queries: every
    f32 row read once and scored once a rider, the batch read, the packed
    reply (a distance and an id a place) written."""
    ops = 2.0 * riders * rows * dim
    moved = rows * dim * 4 + riders * dim * 4 + riders * k * 8
    return ops, moved


def read(window):
    trace, peaks, cfg = window["trace"], window["peaks"], window["config"]
    prog = (trace or {}).get("programs", {}).get("jit_exact_scan")
    before = (window["before"].get("runner") or {}).get("scan")
    after = (window["after"].get("runner") or {}).get("scan")
    if not prog or not prog["runs"] or not prog["seconds"] or not peaks \
            or not before or not after:
        return None
    dispatches = after["dispatches"] - before["dispatches"]
    if dispatches <= 0:
        return None
    ops, moved = costs(cfg["rows"], cfg["dim"], cfg["k"],
                       (after["riders"] - before["riders"]) / dispatches)
    least = max(ops / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    return 100.0 * prog["runs"] * least / prog["seconds"]
