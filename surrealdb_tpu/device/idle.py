"""What the serving process was doing while the runner held no op.

Pure interval arithmetic over one window's stage timeline
(telemetry.stage_record while a `DeviceSupervisor.profile` window is
open: `(stage, thread id, start_ns, end_ns)` on CLOCK_MONOTONIC, the
clock the runner stamps `recv` and `ready` with). No jax, no clock
read, no state: the serving process calls it once a window, a test
calls it on hand-made intervals.

The runner holds an op from a reply's `recv` to its `ready`: the end of
that call's `rpc_out` and the start of its `rpc_back`, which one thread
records one after the other (supervisor._record_rpc_parts). Every
nanosecond of the window outside those is a gap, and each gap is cut by
the first of these that covers it:

    request_out    a request on its way: `rpc_send_wake`, `rpc_send`,
                   `rpc_wire_out` of some call
    reply_back     a reply on its way: `rpc_recv`, `rpc_wake`
    dispatch_host  a dispatcher's host work: inside a `batch_dispatch`
                   and outside every `device_rpc`
    riders_queued  riders queued and no call in flight: a `batch_wait`
                   open
    no_rider       none of them: nothing had been asked of the device
"""

from __future__ import annotations

REQUEST_OUT = ("rpc_send_wake", "rpc_send", "rpc_wire_out")
REPLY_BACK = ("rpc_recv", "rpc_wake")


def union(spans) -> list:
    """Sorted, disjoint, non-empty intervals covering what `spans` do."""
    out = []
    for s, e in sorted(sp for sp in spans if sp[1] > sp[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def subtract(a: list, b: list) -> list:
    """`a` less `b`; both as `union` returns them."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def total(a: list) -> int:
    return sum(e - s for s, e in a)


def runner_busy(timeline) -> list:
    """The `[recv, ready]` of every call whose reply the timeline holds."""
    out_end, spans = {}, []
    for stage, tid, start, end in timeline:
        if stage == "rpc_out":
            out_end[tid] = end
        elif stage == "rpc_back" and tid in out_end:
            spans.append((out_end.pop(tid), start))
    return union(spans)


def runner_idle_by(timeline, w0: int, w1: int) -> dict:
    """{busy_s, idle_s, by: {cause: seconds}} of the window `[w0, w1]`
    (ns); `by`'s values sum to `idle_s`, and `busy_s` + `idle_s` is the
    window."""
    named = {}
    for stage, _tid, start, end in timeline:
        named.setdefault(stage, []).append((start, end))

    def cover(*stages):
        return union(sp for st in stages for sp in named.get(st, ()))

    busy = runner_busy(timeline)
    left = subtract([[w0, w1]], busy) if w1 > w0 else []
    idle_ns = total(left)
    by = {}
    for cause, spans in (
            ("request_out", cover(*REQUEST_OUT)),
            ("reply_back", cover(*REPLY_BACK)),
            ("dispatch_host", subtract(cover("batch_dispatch"),
                                       cover("device_rpc"))),
            ("riders_queued", cover("batch_wait"))):
        rest = subtract(left, spans)
        by[cause] = (total(left) - total(rest)) / 1e9
        left = rest
    by["no_rider"] = total(left) / 1e9
    return {"busy_s": (max(w1 - w0, 0) - idle_ns) / 1e9,
            "idle_s": idle_ns / 1e9, "by": by}
