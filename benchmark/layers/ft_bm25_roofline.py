"""Kernels: the BM25 top-n's share of its roofline on the chip.

From the device trace: the runs of the jitted program `jit__bm25_impl`
(`device/ftstore.py _bm25_impl`) inside the profiler window and the device
seconds they took. Against them the least time one run can take, by `moved`
below: the bytes the answers need over the HBM bandwidth. What the riders
needed is counted by the runner (`kernelstats.FT`, in
`runner_status()["ft"]`), unpadded: `candidates`, the postings of each
rider's rarest term, each read with its tf and its passage's length;
`postings_read`, those doc numbers and every probe of a binary search in the
other terms' slices; `dense_bytes`, the dense tf rows and doc lengths read
by riders on the dense rung, where the runner counts them; and for the reply, each rider's count and its `k`
places of doc, score, length and the tfs of the query's words. `k` and the
words are the cell's (`config`): the least the program can send. The mean
over the window's program calls stands for a traced run.

Bytes only: the op's arithmetic (a log and a division a candidate and term)
is nothing beside its gathers. Padding (the capacity rung, the power-of-two
riders, the term slots) is left out, so the share cannot pass 100 %. Expect
it far under 1 %: launch latency and the dependent gathers of the binary
search bound the op, not the bandwidth.
"""

from math import ceil, log2


def moved(riders: float, candidates: float, postings_read: float,
          k: int, words: int, dense_bytes: float = 0.0) -> float:
    """Bytes the BM25 top-n of `riders` queries has to move."""
    places = 2 ** ceil(log2(k + 16))
    return 4.0 * postings_read + 8.0 * candidates + dense_bytes \
        + 4.0 * riders * (1 + places * (3 + words))


def read(window):
    trace, peaks, cfg = window["trace"], window["peaks"], window["config"]
    prog = (trace or {}).get("programs", {}).get("jit__bm25_impl")
    before = (window["before"].get("runner") or {}).get("ft")
    after = (window["after"].get("runner") or {}).get("ft")
    if not prog or not prog["runs"] or not prog["seconds"] or not peaks \
            or not before or not after:
        return None
    calls = after["dispatches"] - before["dispatches"]
    if calls <= 0:
        return None
    per_run = moved(after["riders"] - before["riders"],
                    after["candidates"] - before["candidates"],
                    after["postings_read"] - before["postings_read"],
                    cfg["k"], cfg["words"], after.get("dense_bytes", 0)
                    - before.get("dense_bytes", 0)) / calls
    least = per_run / peaks["hbm_bytes_per_s"]
    return 100.0 * prog["runs"] * least / prog["seconds"]
