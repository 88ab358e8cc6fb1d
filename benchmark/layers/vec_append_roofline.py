"""Kernels: `vec_append`'s share of its roofline on the chip.

From the device trace: the runs of the jitted program `jit_vec_append`
(`device/vecstore.py _append_program`, one donating scatter into the resident
block) inside the profiler window and the device seconds they took, start of
the program to its end. Against them the least time one run can take on this
chip, by `costs` below: its bytes over the HBM bandwidth (it computes next to
nothing). The rows of a run are the window's mean per delta, unpadded, from
the supervisor's `vec_append_rows` / `vec_appends`. What is counted is what
the write needs, once, so the share cannot pass 100 %. Expect a fraction of a
percent: a row is 781 bytes and the program is bound by its launch, not by the
memory; the number is here so that a later change of the program is seen.
"""


def costs(rows: float, dim: int):
    """(operations, bytes) one run has to do for `rows` rows: the f32 row
    read and written, its bf16 copy written, its stat (4 B) and its mask
    bit (1 B) written, its row number (4 B) read."""
    ops = rows * dim
    moved = rows * (dim * 4 + dim * (4 + 2) + 4 + 1 + 4)
    return ops, moved


def read(window):
    trace, peaks, cfg = window["trace"], window["peaks"], window["config"]
    prog = (trace or {}).get("programs", {}).get("jit_vec_append")
    before = window["before"].get("supervisor") or {}
    after = window["after"].get("supervisor") or {}
    if not prog or not prog["runs"] or not prog["seconds"] or not peaks \
            or "vec_appends" not in before or "vec_appends" not in after:
        return None
    deltas = after["vec_appends"] - before["vec_appends"]
    if deltas <= 0:
        return None
    ops, moved = costs(
        (after["vec_append_rows"] - before["vec_append_rows"]) / deltas,
        cfg["dim"])
    least = max(ops / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    return 100.0 * prog["runs"] * least / prog["seconds"]
