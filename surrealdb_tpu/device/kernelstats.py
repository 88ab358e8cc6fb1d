"""Kernel compile-shape accounting for the device runner, and the
runner's own clock: what one op spent in each phase, and how the serve
loop's time divides into waiting for work and doing it.

A "miss" is a dispatch that had to compile a new (kernel, shape)
combination in this process; a "hit" reuses an already-compiled
executable. With the persistent compilation cache warm
(device/compile_cache.py), a miss costs a disk load instead of a full
XLA compile — the counters say how well the power-of-two bucket ladder
is bounding the compiled-shape set, and whether serving traffic is
paying compiles mid-query. Surfaced as `device_compile_cache_hits` /
`device_compile_cache_misses` through the supervisor's telemetry and
`INFO FOR SYSTEM`.

Lock-free on purpose: a lost increment under a thread race skews a
gauge by one sample (same discipline as telemetry.StageStat).
"""

from __future__ import annotations

import time
from contextlib import contextmanager

COUNTS = {"hits": 0, "misses": 0, "sharded": 0}
_SEEN: set = set()
# widest mesh any sharded dispatch actually ran on in this process:
# `cc.mesh_ndev` in `runner_status()`
MESH_LAST = {"ndev": 0}


# jax's own compile accounting (jax.monitoring listeners, installed by
# the runner): seconds inside XLA backend compile per jitted function
# — a persistent-cache hit spends only its load time there — and the
# persistent cache's hit/miss counts. What `chip_smoke.py` reports as
# cold/warm compile time per kernel.
# lint: mem-account(one float per jitted function name in the tree, plus two counters)
COMPILE = {"backend_compile_s": {}, "persistent_hits": 0,
           "persistent_misses": 0}
# called with the kernel name right before a first-shape dispatch
# compiles: the runner tells the supervisor, whose dispatch window then
# covers a compile instead of reading it as a wedge
ON_COMPILE = None


# nanoseconds the op being served has spent in each phase so far
# (`h2d`, `device`, `d2h`): the runner clears it before an op and sends
# it in the reply's `t`, where the supervisor turns it into the
# `runner_*` stages
# lint: mem-account(one int per phase name, three names in the tree)
PHASES: dict = {}
# the serve loop's time since the runner announced `ready`: blocked in
# recv_msg (`idle_ns`) or anything else (`busy_ns`); `op_status` reports
# it as `loop`
# lint: mem-account(fixed-key int counters, not derived state)
LOOP = {"idle_ns": 0, "busy_ns": 0}
_op_t0 = None  # monotonic_ns when the op in hand was received
# graph-ANN descents (device/annstore.py search): `rows_scored` is what
# the answers needed, unpadded riders x iters*expand*d_out plus the
# probe rows once a search; `op_status` reports it as `ann`
# lint: mem-account(fixed-key int counters, not derived state)
ANN = {"searches": 0, "rows_scored": 0}
# bag hops over resident CSR blocks (device/csrstore.py bag_hop), for the
# riders a capacity rung answered: `edges_gathered` is their paths summed
# over every level (one column read each), `paths_out` the last level's
# (the ids returned); a rider that passed its rung's capacity counts in
# `overflows` alone. `op_status` reports it as `csr`
# lint: mem-account(fixed-key int counters, not derived state)
CSR = {"bag_riders": 0, "paths_out": 0, "edges_gathered": 0,
       "overflows": 0}
# `vec_knn` on exact stores (device/handlers.py; on one device the
# program `exact_scan`): every one scores every row of its store, so
# `rows_scored` is unpadded riders x the store's rows; `op_status`
# reports it as `scan`
# lint: mem-account(fixed-key int counters, not derived state)
SCAN = {"riders": 0, "dispatches": 0, "rows_scored": 0}
# `vec_append` on the stores that grow in place (device/handlers.py;
# the program `vec_append`): deltas written, their rows (unpadded) and
# the bytes of their buffers; `op_status` reports it as `append`
# lint: mem-account(fixed-key int counters, not derived state)
APPEND = {"appends": 0, "rows": 0, "bytes": 0}


@contextmanager
def phase(name: str):
    """Times one phase of the op in hand into PHASES and writes it into
    the profiler's trace as `runner:<name>` (free unless a trace is
    being taken), so the runner's host spans and the device's
    operations lie on one clock."""
    from jax.profiler import TraceAnnotation

    t0 = time.monotonic_ns()
    with TraceAnnotation("runner:" + name):
        try:
            yield
        finally:
            PHASES[name] = PHASES.get(name, 0) + time.monotonic_ns() - t0


def loop_received(t_mark: int) -> int:
    """The serve loop has an op in hand: everything since `t_mark` was
    waiting for it. Returns now (the reply's `t.recv`)."""
    global _op_t0
    now = _op_t0 = time.monotonic_ns()
    LOOP["idle_ns"] += now - t_mark
    PHASES.clear()
    return now


def loop_replied() -> int:
    """The op in hand is answered: everything since it was received was
    work. Returns now (the next wait's `t_mark`)."""
    global _op_t0
    now = time.monotonic_ns()
    LOOP["busy_ns"] += now - _op_t0
    _op_t0 = None
    return now


def loop_snapshot() -> dict:
    """LOOP as of now: the op in hand (the `status` that asks) counts
    as busy up to this moment, so two snapshots differ by exactly the
    wall time between them."""
    out = dict(LOOP)
    if _op_t0 is not None:
        out["busy_ns"] += time.monotonic_ns() - _op_t0
    return out


def note_compile(kernel: str):
    COUNTS["misses"] += 1
    if ON_COMPILE is not None:
        ON_COMPILE(kernel)


def install_jax_listeners():
    """Feed COMPILE from jax.monitoring (call once, jax already up)."""
    import jax.monitoring as mon

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            by_fn = COMPILE["backend_compile_s"]
            name = str(kw.get("fun_name", "?"))
            by_fn[name] = by_fn.get(name, 0.0) + float(secs)

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILE["persistent_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILE["persistent_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


def note_hit(kernel: str):
    COUNTS["hits"] += 1


def note_sharded(kernel: str, ndev: int):
    """Record a mesh dispatch (device/mesh.py kernels) of width
    `ndev`; width-1 meshes don't count as sharded execution."""
    if ndev > 1:
        COUNTS["sharded"] += 1
        if ndev > MESH_LAST["ndev"]:
            MESH_LAST["ndev"] = ndev


# store shapes change every sync epoch under write load, so the seen-set
# must be bounded in a long-running server; overflow clears it (the next
# dispatches re-count as misses — a blip in a gauge, not a leak)
_SEEN_MAX = 4096


def note_shape(kernel: str, shape_key) -> bool:
    """Record a dispatch against (kernel, shape_key); returns True when
    this shape was already compiled in this process (a hit)."""
    key = (kernel, shape_key)
    if key in _SEEN:
        COUNTS["hits"] += 1
        return True
    if len(_SEEN) >= _SEEN_MAX:
        _SEEN.clear()
    _SEEN.add(key)
    note_compile(kernel)
    return False


def snapshot() -> dict:
    out = dict(COUNTS)
    out["mesh_ndev"] = MESH_LAST["ndev"]
    return out


def reset():
    COUNTS["hits"] = 0
    COUNTS["misses"] = 0
    COUNTS["sharded"] = 0
    MESH_LAST["ndev"] = 0
    _SEEN.clear()
