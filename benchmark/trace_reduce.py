"""From the profiler's trace of the runner to busy seconds and a breakdown.

Run as a child of `run.py` once the runner is gone (`JAX_PLATFORMS=cpu`:
reading a trace needs jax's reader, never a chip):

    python trace_reduce.py <trace dir> <platform>

Prints one JSON object: per device plane the union of the intervals in
which an operation ran (`busy_s`, averaged over the planes), the ten
operations that took most device time, each jitted program's runs and
device seconds on the first chip, and the idle time summed by the
pair of programs on either side of each gap, ten largest (the program
writes no host spans into the trace yet, so a gap cannot be named by what
the host was doing).

On a TPU the device planes are `/device:TPU:<n>` and their `XLA Ops` line
holds one event per operation. The CPU backend of a rehearsal has no device
plane: there the host threads that run XLA's CPU executables stand in, so
that the tests walk this code; a rehearsal's numbers are never device
numbers.
"""

from __future__ import annotations

import glob
import json
import os
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# lines of a device plane that restate the ops above them
SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code")


def union_seconds(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(events, top: int = 10):
    """Idle seconds between consecutive operations, summed by the pair
    of operations on either side: [(name, seconds)], largest first."""
    out, end, last = {}, None, None
    for s, e, name in sorted(events):
        if end is not None and s > end:
            key = f"{last} -> {name}"
            out[key] = out.get(key, 0.0) + (s - end) / 1e9
        if end is None or e > end:
            end, last = e, name
    return sorted(out.items(), key=lambda g: -g[1])[:top]


def short(name: str) -> str:
    """`%fusion.6 = (f32[8,896]{...}) fusion(...)` -> `fusion.6`;
    `jit_knn_rank_rescore(1234567)` -> `jit_knn_rank_rescore`."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.endswith(")") and "(" in name:
        head, _, tail = name.rpartition("(")
        if tail[:-1].isdigit():
            name = head
    return name[:80]


def module_events(profile):
    """The first device plane's jitted programs, one event per run."""
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            return [(ev.start_ns, ev.start_ns + ev.duration_ns, short(ev.name))
                    for ln in plane.lines if ln.name == MODULES_LINE
                    for ev in ln.events if ev.duration_ns > 0]
    return []


def device_lines(profile, platform: str):
    """[(plane name, [(start ns, end ns, op name)])] of the planes on
    which the device's operations are recorded."""
    planes = []
    for plane in profile.planes:
        if platform == "tpu":
            if not plane.name.startswith("/device:TPU:"):
                continue
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or \
                [ln for ln in lines if ln.name not in SUMMARY_LINES]
        else:
            if not plane.name.startswith("/host:"):
                continue
            ops = [ln for ln in plane.lines if "xla" in ln.name.lower()
                   or "eigen" in ln.name.lower()]
        events = [(ev.start_ns, ev.start_ns + ev.duration_ns, short(ev.name))
                  for ln in ops for ev in ln.events if ev.duration_ns > 0]
        if events:
            planes.append((plane.name, events))
    return planes


def reduce_trace(trace_dir: str, platform: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    profile = ProfileData.from_file(paths[-1])
    seen = [(p.name, [ln.name for ln in p.lines]) for p in profile.planes]
    planes = device_lines(profile, platform)
    if not planes:
        return {"error": "no operation on any device plane", "planes": seen}
    busy = [union_seconds([(s, e) for s, e, _n in evs]) for _p, evs in planes]
    # programs first (their names are the jitted functions'), then the
    # operations inside them; gaps are named by the programs around them
    modules = module_events(profile)
    by_op = {}
    for prefix, evs in [("program ", modules)] + [("", e) for _p, e in planes]:
        for s, e, name in evs:
            by_op[prefix + name] = by_op.get(prefix + name, 0.0) + (e - s) / 1e9
    programs = {}
    for s, e, name in modules:
        p = programs.setdefault(name, {"runs": 0, "seconds": 0.0})
        p["runs"] += 1
        p["seconds"] += (e - s) / 1e9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])
    top = [kv for kv in top if kv[0].startswith("program ")][:4] \
        + [kv for kv in top if not kv[0].startswith("program ")][:6]
    return {
        "busy_s": sum(busy) / len(busy),
        "events": sum(len(evs) for _p, evs in planes),
        "device_ops": [[n, s / len(planes)] for n, s in top],
        "idle_gaps": [[n, s] for n, s in gaps(modules or planes[0][1])],
        # the first device plane's jitted programs: runs and device seconds
        "programs": programs,
        "planes": seen,
    }


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1], sys.argv[2])))
