"""Kernels: `knn_rank_rescore`'s share of its roofline on the chip.

From the device trace: the runs of the jitted program `jit_knn_rank_rescore`
inside the profiler window and the device seconds they took, start of the
program to its end. Against them the least time one run can take on this
chip, by `costs` below: the larger of its operations over the bf16 peak and
its bytes over the HBM bandwidth. The riders of a run are the window's mean
per dispatch, unpadded, and the store is counted at its rows, not at the
capacity it is padded to: what is counted is what the answer needs, so the
share cannot pass 100 %.
"""


def costs(rows: int, dim: int, k: int, riders: float):
    """(operations, bytes) one run has to do for `riders` queries: the bf16
    ranking pass over the whole store (2*B*N*D; the bf16 copy, the f32
    squared norms and the validity mask read once), then the f32 rescore of
    kc = max(2k, k+16) gathered candidates per query."""
    kc = max(2 * k, k + 16)
    ops = 2.0 * riders * rows * dim + 2.0 * riders * kc * dim
    moved = rows * dim * 2 + rows * 4 + rows + riders * kc * dim * 4 \
        + riders * dim * 4 + riders * k * 8
    return ops, moved


def read(window):
    trace, peaks, cfg = window["trace"], window["peaks"], window["config"]
    b = window["batching"]
    prog = (trace or {}).get("programs", {}).get("jit_knn_rank_rescore")
    if not prog or not prog["runs"] or not prog["seconds"] or not peaks \
            or not b["dispatches"]:
        return None
    ops, moved = costs(cfg["rows"], cfg["dim"], cfg["k"],
                       b["riders"] / b["dispatches"])
    least = max(ops / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    return 100.0 * prog["runs"] * least / prog["seconds"]
