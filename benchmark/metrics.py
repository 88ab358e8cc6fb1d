"""End-to-end arithmetic on a window's request log.

A record is (pool index, sent, received, HTTP status, ok) on the machine's
monotonic clock. The window is [begin, end): every request sent in it
counts, whenever its reply came.
"""

from __future__ import annotations


def percentile(sorted_values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    n = len(sorted_values)
    if not n:
        raise ValueError("no values")
    pos = q * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    return sorted_values[lo] + (sorted_values[hi] - sorted_values[lo]) * (pos - lo)


def end_to_end(records, begin: float, end: float) -> dict:
    """qps: answers with a good status received inside the window over
    the window's seconds. Latencies: send to full reply, over ALL of the
    window's requests, so a stall moves the tail and the rate alike."""
    lat = sorted((r[2] - r[1]) * 1e3 for r in records)
    done = sum(1 for r in records if r[4] and r[2] <= end)
    return {
        "qps": done / (end - begin),
        "latency_p50_ms": percentile(lat, 0.50),
        "latency_p95_ms": percentile(lat, 0.95),
    }
