"""Kernels: the bag hop's share of its roofline on the chip.

From the device trace: the runs of the jitted program `jit__bag_hop_impl`
inside the profiler window and the device seconds they took. Against them
the least time one run can take, by `moved` below: the bytes the answers
need over the HBM bandwidth. What the riders asked for is counted by the
runner (`kernelstats.CSR`, in `runner_status()["csr"]`): `edges_gathered`,
the paths of every level, and `paths_out`, those of the last, for the riders
a dispatch answered and not for the power of two or the capacity they were
padded to. The mean over the window's dispatches stands for a traced run.

Bytes only: the op does no arithmetic to speak of. A frontier entry costs
two reads of `indptr` (its slice's two ends), a path one read of the
destination column and one write of the id. The frontier entries are the
start nodes (one a rider at least) and every level's paths but the last's.
The compare-and-sum that finds each path's source, and the padding, are
left out, so the count is under what the kernel moves and the share cannot
pass 100 %. Expect it far under 1 %: launch latency and the capacity-sized
compare bound the op, not the bandwidth.
"""


def moved(riders: float, edges_gathered: float, paths_out: float) -> float:
    """Bytes the bag hops of `riders` chains have to move."""
    frontier = riders + edges_gathered - paths_out
    return 4.0 * (2.0 * frontier + 2.0 * edges_gathered)


def read(window):
    trace, peaks = window["trace"], window["peaks"]
    prog = (trace or {}).get("programs", {}).get("jit__bag_hop_impl")
    before = (window["before"].get("runner") or {}).get("csr")
    after = (window["after"].get("runner") or {}).get("csr")
    b = window["batching"]
    if not prog or not prog["runs"] or not prog["seconds"] or not peaks \
            or not before or not after or not b.get("dispatches"):
        return None
    riders = after["bag_riders"] - before["bag_riders"]
    if riders <= 0:
        return None
    per_run = moved(riders,
                    after["edges_gathered"] - before["edges_gathered"],
                    after["paths_out"] - before["paths_out"]) \
        / b["dispatches"]
    least = per_run / peaks["hbm_bytes_per_s"]
    return 100.0 * prog["runs"] * least / prog["seconds"]
