"""DeviceSupervisor: health-checked dispatch to the DeviceRunner.

State machine (doc/operations.md "Device supervision"):

    off ──(mode=off)───────────────────────────────► stays off
    cold ──first use──► probing ──ready frame──► ready
    ready ──crash / dispatch timeout──► degraded
    degraded ──probe streak ≥ promote threshold──► ready

While degraded (or still cold/probing) every dispatch raises
`DeviceUnavailable` and the callers serve from the host paths (numpy
KNN, host CSR) — the circuit breaker. A background probe thread
respawns and pings the runner every `SURREAL_DEVICE_PROBE_INTERVAL_S`;
promotion back to ready requires `SURREAL_DEVICE_PROMOTE_SUCCESSES`
consecutive healthy probes (hysteresis — one lucky ping after a crash
loop must not flap traffic back onto a sick device).

Deadlines ("The Tail at Scale"): every dispatch waits at most
min(op timeout, calling query's remaining budget) — the inflight
thread-local from PR 2 — so a wedged device can never hold a query past
its deadline. A wait that exhausts the FULL op timeout is a wedge: the
runner is SIGKILLed and the state degrades; a wait cut short by a small
query budget merely orphans that one request (the runner may be healthy
and mid-kernel — killing it would thrash under tight deadlines).

A compile is not a wedge. The runner announces every first-shape
dispatch with a `compiling` frame before it enters XLA; from then until
that dispatch replies, no waiter — the compiling one or those queued
behind the single-threaded runner — is timed out by the dispatch
window, only by the load window (`SURREAL_DEVICE_LOAD_TIMEOUT_S`, the
bound on one compile) or its own query budget. When the reply lands,
queued waiters get a fresh dispatch window.

Modes (`SURREAL_DEVICE`): `off` (host paths only), `auto` (default:
supervised subprocess, degrade-and-recover), `require` (the device path
IS the contract: failures surface as query errors instead of degrading,
and a runner that comes up on anything but a TPU is an init error
unless `JAX_PLATFORMS` names that platform — chip_smoke.py and the
benchmark's cells run here), `inline` (no subprocess; ops run in-process —
debug/tests only, forfeits isolation).
"""

from __future__ import annotations

import os
import queue
import socket
import subprocess
import sys
import threading
import time
from typing import Optional

from surrealdb_tpu import cnf
from surrealdb_tpu.err import SdbError

_STATES = ("off", "cold", "probing", "ready", "degraded")


class DeviceUnavailable(Exception):
    """Internal degrade signal: the device can't serve this dispatch —
    fall back to the host path. Never surfaces to a client."""


class DeviceOpError(Exception):
    """The runner rejected ONE op (bad input, kernel error). Not a
    health event: callers degrade that query to host without tripping
    the circuit breaker."""


class DeviceOutOfMemory(DeviceUnavailable):
    """The runner REFUSED a store ship that cannot fit its device
    byte budget (SURREAL_DEVICE_MEM_BUDGET_MB) even after evicting
    every other store. Subclass of DeviceUnavailable so every existing
    degrade ladder already answers from the host paths; the supervisor
    additionally remembers the (key, tag) so later dispatches for that
    store fail fast to host instead of re-shipping gigabytes at the
    runner just to be refused again. The runner stays healthy for
    every other store — a refusal is never a circuit-breaker event."""


class DeviceSupervisor:
    def __init__(self, mode: Optional[str] = None,
                 dispatch_timeout_s: Optional[float] = None,
                 load_timeout_s: Optional[float] = None,
                 init_timeout_s: Optional[float] = None,
                 probe_interval_s: Optional[float] = None,
                 promote_successes: Optional[int] = None):
        # env is re-read at construction (not import) so tests and
        # embedded servers can configure per-instance
        self.mode = (mode or os.environ.get("SURREAL_DEVICE", "")
                     or cnf.DEVICE_MODE).lower()
        if self.mode not in ("off", "auto", "require", "inline"):
            raise SdbError(f"SURREAL_DEVICE must be off|auto|require|"
                           f"inline, got {self.mode!r}")
        self.dispatch_timeout_s = (
            cnf.env_float("SURREAL_DEVICE_DISPATCH_TIMEOUT_S",
                          cnf.DEVICE_DISPATCH_TIMEOUT_S)
            if dispatch_timeout_s is None else dispatch_timeout_s)
        self.load_timeout_s = (
            cnf.env_float("SURREAL_DEVICE_LOAD_TIMEOUT_S",
                          cnf.DEVICE_LOAD_TIMEOUT_S)
            if load_timeout_s is None else load_timeout_s)
        # init watchdog: SURREAL_DEVICE_INIT_TIMEOUT_S, else
        # SURREAL_BACKEND_INIT_TIMEOUT_S (two names, one window)
        self.init_timeout_s = (
            cnf.env_float("SURREAL_DEVICE_INIT_TIMEOUT_S",
                          cnf.BACKEND_INIT_TIMEOUT_S)
            if init_timeout_s is None else init_timeout_s)
        self.probe_interval_s = (
            cnf.env_float("SURREAL_DEVICE_PROBE_INTERVAL_S",
                          cnf.DEVICE_PROBE_INTERVAL_S)
            if probe_interval_s is None else probe_interval_s)
        self.promote_successes = (
            cnf.env_int("SURREAL_DEVICE_PROMOTE_SUCCESSES",
                        cnf.DEVICE_PROMOTE_SUCCESSES)
            if promote_successes is None else promote_successes)
        self.state = "off" if self.mode == "off" else "cold"
        self.platform: Optional[str] = None
        self.device_kind: Optional[str] = None
        self.device_count = 0
        self.versions: Optional[dict] = None  # jax/jaxlib/libtpu (runner)
        self.last_error: Optional[str] = None
        self.counters = {
            "device_spawns": 0, "device_restarts": 0,
            "device_dispatch_timeouts": 0, "device_dispatch_errors": 0,
            "device_fallbacks": 0, "device_host_routed": 0,
            "device_oom_refusals": 0,
            "device_col_ships": 0, "device_col_ship_bytes": 0,
            # writes to resident vector blocks: deltas sent as
            # `vec_append` (their rows and bytes), and whole `vec_load`s
            "device_vec_appends": 0, "device_vec_append_rows": 0,
            "device_vec_append_bytes": 0, "device_vec_full_ships": 0,
        }
        # wall seconds spent shipping block caches to the runner
        self.ship_s = 0.0
        # compile-aware dispatch window (see module docstring): the seq
        # the runner announced as compiling, and the monotonic time
        # before which no dispatch may be declared timed out
        self._compiling_seq = None
        self._no_wedge_before = 0.0
        # stores the runner refused under its byte budget: key -> tag.
        # ensure_loaded fails these fast (typed DeviceOutOfMemory →
        # host paths) until the store's tag changes (a rebuilt, smaller
        # store deserves a fresh attempt).
        self._oom_keys: dict = {}
        # last-known runner-side kernel compile counters (piggybacked on
        # every reply) + the runner's persistent-compile-cache info
        self.compile_counts = {"hits": 0, "misses": 0}
        self.compile_cache_info: Optional[dict] = None
        # mesh topology from the runner's ready frame (device/mesh.py
        # describe()); inline mode derives it lazily in status()
        self.mesh_info: Optional[dict] = None
        self._lock = threading.RLock()
        self._ready = threading.Event()
        self._gen = 0
        self._proc: Optional[subprocess.Popen] = None
        self._sock: Optional[socket.socket] = None
        self._send_q: Optional[queue.Queue] = None
        # seq -> [Event, reply|None, t_got, t_sent, t_in]: the three
        # stamps of the threads that hand the call on (`_send_loop`,
        # `_recv_loop`; time.monotonic_ns, None until stamped)
        self._pending: dict = {}
        self._seq = 0
        self._loaded: dict = {}  # cache key -> tag (current runner gen)
        self._probe_thread: Optional[threading.Thread] = None
        self._spawn_thread: Optional[threading.Thread] = None
        # (proc, sock) of a runner still in its init handshake — tracked
        # so shutdown() can kill a MID-INIT runner (it may hold the
        # exclusive accelerator for up to init_timeout_s otherwise)
        self._spawning: Optional[tuple] = None
        self._stop = threading.Event()
        self._inline_host = None
        if self.mode == "inline":
            self.state = "ready"
            self._ready.set()

    # -- public surface ------------------------------------------------------

    def fast_path(self) -> bool:
        """True when callers should route this dispatch to the device.
        A cold supervisor kicks off the async spawn and answers False —
        the first queries serve from host while the runner initializes
        (degrade-and-recover, never block a query on jax init)."""
        if self.mode == "off" or self._stop.is_set():
            return False
        if self.mode in ("inline", "require"):
            return True
        if self.state == "ready":
            return True
        if self.state == "cold":
            self.ensure_started()
        return False

    def unavailable(self, reason: str):
        """The exception a CALLER should raise when it gives up on the
        device (cache thrashing, repeated stale replies): SdbError in
        require mode — the query must fail loudly, not silently serve
        host results — else the internal degrade signal."""
        if self.mode == "require":
            return SdbError(
                "device required (SURREAL_DEVICE=require) but "
                f"unavailable: {reason}"
            )
        return DeviceUnavailable(reason)

    def note_fallback(self):
        """A caller served from the host path because the device was
        unavailable (counted once per degraded dispatch)."""
        if self.mode != "off":
            self.counters["device_fallbacks"] += 1

    def note_host_routed(self):
        """A caller with a serving device answered from the host by
        rule, not by trouble (counted once per query so answered)."""
        self.counters["device_host_routed"] += 1

    def note_col_ship(self, nbytes: int):
        """A table's column block (col.py) is about to be shipped:
        once a table version a metric, never with a query."""
        self.counters["device_col_ships"] += 1
        self.counters["device_col_ship_bytes"] += int(nbytes)

    def ensure_started(self):
        """Kick the async first spawn (idempotent, never blocks)."""
        if self.mode in ("off", "inline") or self._stop.is_set():
            return
        with self._lock:
            if self.state != "cold" or self._spawn_thread is not None:
                return
            self.state = "probing"
            stop = self._stop
            t = threading.Thread(target=self._first_spawn, args=(stop,),
                                 daemon=True, name="device-spawn")
            self._spawn_thread = t
        t.start()

    def wait_ready(self, timeout_s: float) -> bool:
        """Block until the runner is serving (bench/boot prewarm).
        Returns False EARLY when init fails (state degraded) — a
        fast-erroring backend must fail fast and loud, not eat the
        whole watchdog window while the probe loop respawns it."""
        if self.mode == "off":
            return False
        self.ensure_started()
        end = time.monotonic() + timeout_s
        while True:
            left = end - time.monotonic()
            if left <= 0:
                return self._ready.is_set()
            if self._ready.wait(min(left, 0.05)):
                return True
            if self.state == "degraded":
                return False

    def call(self, op: str, meta: dict, bufs=(),
             timeout_s: Optional[float] = None, sent=None):
        """One dispatch -> (tag, meta, bufs). Raises DeviceUnavailable
        (degrade to host), DeviceOpError (this op failed), or SdbError
        (mode=require and the device can't serve). Wall time lands in
        the `device_rpc` stage stat, and a live runner's reply cuts it
        into six more and the two hand-offs among them into five
        (`_record_rpc_parts`). `sent()`, if given, runs
        once the request is in the runner's queue, which the runner
        serves in order: whatever is sent after it finds this op done.
        An inline host runs the op in the caller's thread and never
        calls it."""
        from surrealdb_tpu.telemetry import stage_record

        if self.mode == "off" or self._stop.is_set():
            raise DeviceUnavailable("device disabled")
        if self.mode == "inline":
            t0 = time.perf_counter_ns()
            try:
                return self._call_inline(op, meta, bufs)
            finally:
                stage_record("device_rpc",
                             time.perf_counter_ns() - t0)
        base = self.dispatch_timeout_s if timeout_s is None else timeout_s
        if not self._ready.is_set():
            self.ensure_started()
            if self.mode == "require":
                # hard-SLA posture: wait at most one dispatch window
                # (capped by the query budget) for readiness, then FAIL
                # the query — warm with wait_ready() at boot instead.
                # Deliberately the DISPATCH window even for loads: this
                # is a health gate, not an op.
                budget = _query_remaining()
                wait = self.dispatch_timeout_s if budget is None \
                    else min(self.dispatch_timeout_s, max(budget, 0.0))
                if not self._ready.wait(wait):
                    raise SdbError(
                        "device required (SURREAL_DEVICE=require) but "
                        f"unavailable: state={self.state}, "
                        f"last error: {self.last_error}"
                    )
            else:
                raise DeviceUnavailable(f"device {self.state}")
        try:
            t0 = time.perf_counter_ns()
            try:
                return self._call_live(op, meta, bufs, base, sent=sent)
            finally:
                stage_record("device_rpc",
                             time.perf_counter_ns() - t0)
        except DeviceUnavailable:
            if self.mode == "require":
                raise SdbError(
                    "device required (SURREAL_DEVICE=require) but "
                    f"dispatch failed: {self.last_error}"
                )
            raise
        except DeviceOpError as e:
            if self.mode == "require":
                # an op failure must surface too: require means the
                # device path IS the contract, not a fast path
                raise SdbError(f"device op failed "
                               f"(SURREAL_DEVICE=require): {e}")
            raise

    # -- cache bookkeeping ---------------------------------------------------

    # single-frame ship cap: bigger stores go begin/part.../end so no
    # frame (and no transient copy) has to hold the whole store
    LOAD_PART_BYTES = 256 << 20

    def ensure_loaded(self, key: str, tag, loader, delta=None):
        """Ship a block cache unless (key, tag) is already resident on
        the CURRENT runner. `loader() -> (op, meta, bufs)` materializes
        the payload only when a ship is actually needed. `delta`, for a
        vector block that grows in place, is `(tag_from, make)`: where
        the runner holds `tag_from`, `make() -> [rows, row numbers,
        mask bits]` goes as ONE `vec_append` and the block moves to
        `tag` where it lies; where it does not (`stale`: a restart, an
        eviction) or the rows pass its capacity (`full`), the loader's
        whole ship follows, as ever."""
        tag = list(tag)
        with self._lock:
            if self._loaded.get(key) == tag:
                return
            held = self._loaded.get(key)
        if delta is not None and held is not None \
                and delta[0] is not None and held == list(delta[0]) \
                and self._append(key, held, tag, delta[1]):
            return
        with self._lock:
            if self._oom_keys.get(key) == tag:
                # the runner already refused this exact store under its
                # byte budget: fail fast instead of re-shipping it just
                # to be refused again — to the host paths in auto mode,
                # as a loud typed error under require
                if self.mode == "require":
                    raise SdbError(
                        f"device required (SURREAL_DEVICE=require) but "
                        f"store {key} exceeds the device byte budget"
                    )
                raise DeviceOutOfMemory(
                    f"store {key} over device budget (cached refusal)"
                )
        op, meta, bufs = loader()
        meta = dict(meta)
        meta["key"] = key
        meta["tag"] = tag
        # refusal bookkeeping (counter + the per-(key, tag) fail-fast
        # cache) happens in _call_live/_call_inline where the oom reply
        # is DETECTED — require mode rewraps the exception as SdbError
        # before it would reach a handler here, and the recording must
        # survive that
        t0 = time.monotonic()
        if op == "vec_load":
            self.counters["device_vec_full_ships"] += 1
        if (op == "vec_load"
                and bufs[0].nbytes > self.LOAD_PART_BYTES):
            self._multipart_vec_load(key, tag, meta, bufs[0], bufs[1])
        elif (op == "ann_load"
                and sum(b.nbytes for b in bufs) > self.LOAD_PART_BYTES):
            self._multipart_ann_load(key, tag, meta, bufs)
        else:
            self.call(op, meta, bufs, timeout_s=self.load_timeout_s)
        with self._lock:
            self.ship_s += time.monotonic() - t0
            self._loaded[key] = tag
            self._oom_keys.pop(key, None)
        if self.mode != "inline":
            kind = {"vec_load": "vec", "ann_load": "ann",
                    "csr_load": "csr"}.get(op)
            if kind is not None:
                self._prewarm_async(key, tag, kind)

    def _append(self, key: str, tag_from, tag, make) -> bool:
        """One `vec_append` from `tag_from` to `tag`; stage
        `vec_append`, call to reply (inside it the call's `device_rpc`
        and its parts, as any call's). False, and the key forgotten,
        where the runner said `stale` or `full`."""
        from surrealdb_tpu.telemetry import stage_record

        bufs = make()
        t0 = time.perf_counter_ns()
        t, _m, _b = self.call(
            "vec_append", {"key": key, "tag_from": tag_from, "tag": tag},
            bufs, timeout_s=self.load_timeout_s,
        )
        stage_record("vec_append", time.perf_counter_ns() - t0)
        if t != "ok":
            self.forget(key)
            return False
        with self._lock:
            self.ship_s += (time.perf_counter_ns() - t0) / 1e9
            self._loaded[key] = tag
            self.counters["device_vec_appends"] += 1
            self.counters["device_vec_append_rows"] += len(bufs[1])
            self.counters["device_vec_append_bytes"] += sum(
                int(b.nbytes) for b in bufs)
        return True

    def _multipart_vec_load(self, key, tag, meta, vecs, valid):
        begin = dict(meta)
        begin["shape"] = list(vecs.shape)
        begin["dtype"] = vecs.dtype.str
        self.call("vec_load_begin", begin, [valid],
                  timeout_s=self.load_timeout_s)
        row_bytes = max(1, vecs.shape[1] * vecs.dtype.itemsize)
        step = max(1, self.LOAD_PART_BYTES // row_bytes)
        for off in range(0, vecs.shape[0], step):
            t, _m, _b = self.call(
                "vec_load_part", {"key": key, "off": off},
                [vecs[off:off + step]], timeout_s=self.load_timeout_s,
            )
            if t == "stale":  # runner restarted mid-ship
                raise self.unavailable("runner lost mid-load")
        t, _m, _b = self.call("vec_load_end", {"key": key, "tag": tag},
                              timeout_s=self.load_timeout_s)
        if t == "stale":
            raise self.unavailable("runner lost mid-load")

    def _multipart_ann_load(self, key, tag, meta, bufs):
        """Chunked ship of a quantized ANN index: begin carries the
        small per-row arrays + shapes, the graph and the int8 rows
        stream as named row-chunked parts (a 10M×768 index is ~9 GB —
        no single frame, and no transient copy, holds it whole)."""
        graph, x8, arow, x2q = bufs
        begin = dict(meta)
        begin["d_out"] = int(graph.shape[1])
        begin["dim"] = int(x8.shape[1])
        self.call("ann_load_begin", begin, [arow, x2q],
                  timeout_s=self.load_timeout_s)
        for name, arr in (("graph", graph), ("x8", x8)):
            row_bytes = max(1, arr.shape[1] * arr.dtype.itemsize)
            step = max(1, self.LOAD_PART_BYTES // row_bytes)
            for off in range(0, arr.shape[0], step):
                t, _m, _b = self.call(
                    "ann_load_part",
                    {"key": key, "buf": name, "off": off},
                    [arr[off:off + step]],
                    timeout_s=self.load_timeout_s,
                )
                if t == "stale":  # runner restarted mid-ship
                    raise self.unavailable("runner lost mid-load")
        t, _m, _b = self.call("ann_load_end", {"key": key, "tag": tag},
                              timeout_s=self.load_timeout_s)
        if t == "stale":
            raise self.unavailable("runner lost mid-load")

    def _prewarm_async(self, key: str, tag, kind: str = "vec"):
        """Fire-and-forget compile of the kernel ladder for a freshly
        shipped store: the power-of-two query-bucket ladder for vector
        and ANN blocks (SURREAL_DEVICE_PREWARM_BUCKETS), the hop-depth
        ladder for CSR graphs (SURREAL_DEVICE_PREWARM_HOPS). Runs on a
        daemon thread so the shipping query isn't held; with the
        persistent compile cache warm it's near-free. Best-effort by
        contract — any failure only costs warmth."""
        if kind == "csr":
            op, field = "csr_prewarm", "hops"
            raw = cnf.env_str("SURREAL_DEVICE_PREWARM_HOPS",
                              cnf.DEVICE_PREWARM_HOPS)
        else:
            op = "ann_prewarm" if kind == "ann" else "vec_prewarm"
            field = "buckets"
            raw = cnf.env_str("SURREAL_DEVICE_PREWARM_BUCKETS",
                              cnf.DEVICE_PREWARM_BUCKETS)
        try:
            steps = [int(x) for x in raw.split(",") if x.strip()]
        except ValueError:
            steps = []
        if not steps:
            return

        def warm():
            # one shape per dispatch, smallest first: each call stays
            # well inside the load window, so a slow compile can never
            # be misclassified as a wedged runner
            for b in sorted(set(steps)):
                try:
                    t, _m, _b = self.call(
                        op,
                        {"key": key, "tag": list(tag), field: [b]},
                        timeout_s=self.load_timeout_s,
                    )
                except Exception:
                    return
                if t != "ok":
                    return

        threading.Thread(target=warm, daemon=True,
                         name="device-prewarm").start()

    def forget(self, key: str):
        with self._lock:
            self._loaded.pop(key, None)
            self._oom_keys.pop(key, None)

    # -- introspection -------------------------------------------------------

    def status(self) -> dict:
        if self.mode == "inline" and self.platform is None \
                and "jax" in sys.modules:
            # no new import (inline forfeits isolation anyway): mirror
            # an already-initialized in-process jax for INFO/metrics
            try:
                devs = sys.modules["jax"].devices()
                self.platform = devs[0].platform if devs else "none"
                self.device_count = len(devs)
            except Exception:
                pass
        if self.mesh_info is None and self.mode == "inline" \
                and "jax" in sys.modules:
            try:
                from surrealdb_tpu.device import mesh as devmesh

                self.mesh_info = devmesh.describe()
            except Exception:
                pass
        with self._lock:
            loaded = list(self._loaded)
        out = {
            "state": self.state,
            "mode": self.mode,
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
            "versions": self.versions,
            "ship_s": round(self.ship_s, 3),
            "restarts": self.counters["device_restarts"],
            "dispatch_timeouts": self.counters["device_dispatch_timeouts"],
            "dispatch_errors": self.counters["device_dispatch_errors"],
            "fallbacks": self.counters["device_fallbacks"],
            "host_routed": self.counters.get("device_host_routed", 0),
            "oom_refusals": self.counters.get("device_oom_refusals", 0),
            "col_ships": self.counters["device_col_ships"],
            "col_ship_bytes": self.counters["device_col_ship_bytes"],
            "vec_appends": self.counters["device_vec_appends"],
            "vec_append_rows": self.counters["device_vec_append_rows"],
            "vec_append_bytes": self.counters["device_vec_append_bytes"],
            "vec_full_ships": self.counters["device_vec_full_ships"],
            "last_error": self.last_error,
            "vec_blocks": sum(1 for k in loaded if k.startswith("vec/")),
            "csr_blocks": sum(1 for k in loaded if k.startswith("csr/")),
            "ann_blocks": sum(1 for k in loaded if k.startswith("ann/")),
            "compile_cache": self.compile_counts_now(),
        }
        if self.compile_cache_info is not None:
            out["compile_cache_dir"] = self.compile_cache_info
        if self.mesh_info is not None:
            out["mesh"] = dict(self.mesh_info)
        from surrealdb_tpu.device.batcher import BATCH_STATS

        out["batching"] = BATCH_STATS.to_dict()
        if self.mode == "inline" and self._inline_host is not None:
            out["vec_blocks"] = len(self._inline_host.vec)
            out["csr_blocks"] = len(self._inline_host.csr)
            out["ann_blocks"] = len(self._inline_host.ann)
        return out

    def compile_counts_now(self) -> dict:
        """Kernel compile hit/miss counters: in-process (inline mode)
        or the last runner-piggybacked snapshot (subprocess)."""
        if self.mode == "inline":
            from surrealdb_tpu.device import kernelstats

            return kernelstats.snapshot()
        return dict(self.compile_counts)

    def pending_calls(self) -> int:
        """Dispatches sent (or queued to send) and not yet answered.
        Takes no lock, so the stall watch can ask while some thread
        holds `_lock` for good."""
        return len(self._pending)

    def runner_pid(self) -> Optional[int]:
        p = self._proc
        return p.pid if p is not None else None

    def runner_status(self) -> dict:
        """The runner's own account (device/handlers.py op_status):
        per-op dispatch counts, resident blocks, rank modes, mesh
        placement, per-device memory, compile seconds per kernel and
        persistent-cache hits. One RPC; raises like `call`."""
        _t, meta, _b = self.call("status", {})
        return meta

    # `stop_trace` took 3-8 s on a v5e (PERF.md), and the first
    # `start_trace` of a process sets the profiler up: far past a
    # dispatch window, and no wedge
    PROFILE_TIMEOUT_S = 120.0

    def profile(self, dir: str, seconds: float) -> dict:
        """Take a profiler trace of the runner for `seconds` while it
        serves: jax's `.xplane.pb` under `dir`/plugins/profile/, with
        the device's operations and the runner's own `runner:<op>`,
        `runner:h2d|device|d2h` and `runner:idle` spans on one clock.
        Blocks the caller for the window plus the time `stop_trace`
        takes to write it; dispatches queued behind the start or the
        stop wait for it (they are not timed out as a wedge) and see
        its seconds in their `rpc_out`.

        The same window of THIS process: while it is open every stage
        record keeps its interval (telemetry.timeline_arm), written as
        `dir`/host_stages.json (`{clock, window_ns, stages: [[stage,
        thread id, start_ns, end_ns], ...]}`) on CLOCK_MONOTONIC, the
        clock of each `runner:<op>` span's `t_recv` in the trace.
        Returns {dir, window_s, stop_s, host_stages, runner_busy_s,
        runner_idle_s, runner_idle_by}: the seconds of the window in
        which the runner held no op, by what this process was doing
        then (device/idle.py)."""
        import json

        from surrealdb_tpu import telemetry
        from surrealdb_tpu.device.idle import runner_idle_by

        # armed before the start and disarmed after the stop: a call
        # records when its waiter runs again, which may be after the
        # window's edge
        telemetry.timeline_arm()
        started = stopped = None
        try:
            started = self._profile_call({"action": "start", "dir": dir})
            time.sleep(max(float(seconds), 0.0))
            stopped = self._profile_call({"action": "stop"})
        finally:
            timeline = telemetry.timeline_disarm()
            if started is not None and stopped is None:
                # interrupted, or the stop itself failed: a trace left
                # open would make every later `start_trace` raise
                try:
                    self._profile_call({"action": "stop"})
                except Exception:
                    pass  # runner gone or restarting: no trace left
        w0 = int(started["started"] * 1e9)
        w1 = int(stopped["stopping"] * 1e9)
        idle = runner_idle_by(timeline, w0, w1)
        path = os.path.join(dir, "host_stages.json")
        os.makedirs(dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"clock": "CLOCK_MONOTONIC", "window_ns": [w0, w1],
                       "stages": timeline}, f)
        return {"dir": dir,
                "window_s": stopped["stopping"] - started["started"],
                "stop_s": stopped["stopped"] - stopped["stopping"],
                "host_stages": path,
                "runner_busy_s": idle["busy_s"],
                "runner_idle_s": idle["idle_s"],
                "runner_idle_by": idle["by"]}

    def _profile_call(self, meta: dict) -> dict:
        hold = self.PROFILE_TIMEOUT_S
        with self._lock:
            # like a declared compile: whoever queues behind this call
            # keeps waiting instead of killing the runner
            self._no_wedge_before = max(self._no_wedge_before,
                                        time.monotonic() + hold)
        try:
            _t, out, _b = self.call("profile", meta, timeout_s=hold)
        finally:
            with self._lock:
                self._no_wedge_before = \
                    time.monotonic() + self.dispatch_timeout_s
        return out

    def shutdown(self):
        """Stop the runner and every background thread (server drain).
        The supervisor itself returns to `cold`: a later dispatch may
        legitimately respawn (embedded/test processes share the
        singleton across server lifecycles)."""
        with self._lock:
            self._stop.set()
            # background threads captured the OLD stop event; a fresh
            # one re-arms the supervisor for future use
            self._stop = threading.Event()
            proc, self._proc = self._proc, None
            sock, self._sock = self._sock, None
            spawning, self._spawning = self._spawning, None
            # stale threads exit on their captured token; dropping the
            # refs lets a later degradation start fresh ones
            self._probe_thread = None
            self._spawn_thread = None
            self._ready.clear()
            self._send_q = None
            self._gen += 1  # orphan any surviving send/recv loops
            if self.state != "off":
                self.state = "cold"
            self._fail_pending("device supervisor shut down")
            self._loaded.clear()
            self._oom_keys.clear()
            self._inline_host = None
        _close_sock(sock)
        if spawning is not None:
            # a runner still in its init handshake holds the (exclusive)
            # accelerator: kill it too, and close its socket so the
            # spawn thread's handshake recv unwinds immediately
            _reap(spawning[0])
            _close_sock(spawning[1])
        if proc is not None:
            proc.kill()
            try:
                proc.wait(timeout=5)
            except (subprocess.TimeoutExpired, OSError):
                pass

    # -- inline mode ---------------------------------------------------------

    def _call_inline(self, op, meta, bufs):
        from surrealdb_tpu.device.handlers import DeviceHost

        with self._lock:
            if self._inline_host is None:
                self._inline_host = DeviceHost()
            host = self._inline_host
        try:
            tag, out_meta, out_bufs = host.handle(op, dict(meta),
                                                 list(bufs))
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            from surrealdb_tpu.device.handlers import DeviceBudgetError

            if isinstance(e, DeviceBudgetError):
                self._note_oom(meta)
                raise DeviceOutOfMemory(str(e)) from e
            self.counters["device_dispatch_errors"] += 1
            raise DeviceOpError(f"{e.__class__.__name__}: {e}") from e
        if self.platform is None and op != "status":
            # lazily mirror platform info for status()/INFO
            try:
                _t, st, _b = host.handle("status", {}, [])
                self.platform = st.get("platform")
                self.device_kind = st.get("device_kind")
                self.device_count = st.get("device_count", 0)
            except BaseException:
                pass
        return tag, out_meta, out_bufs

    def inline_store(self, key: str):
        """Test/debug hook: the in-process VecStore/CsrStore behind a
        cache key (inline mode only; None when absent)."""
        host = self._inline_host
        if host is None:
            return None
        ent = host.vec.get(key) or host.csr.get(key)
        return ent[1] if ent is not None else None

    # -- subprocess lifecycle ------------------------------------------------

    def _spawn_runner(self, stop) -> bool:
        """Spawn + handshake one runner under the init watchdog.
        Returns True when the runner answered ready. `stop` is the
        lifecycle token captured by the calling thread — a shutdown
        re-arms the supervisor with a fresh token, so a stale spawn
        must abort instead of registering a zombie runner."""
        import surrealdb_tpu

        pkg_root = os.path.dirname(
            os.path.dirname(os.path.abspath(surrealdb_tpu.__file__))
        )
        parent, child = socket.socketpair()
        code = (
            "import sys; sys.path.insert(0, sys.argv[2]); "
            "from surrealdb_tpu.device.runner import main; "
            "main(int(sys.argv[1]))"
        )
        try:
            proc = subprocess.Popen(
                [sys.executable, "-c", code, str(child.fileno()),
                 pkg_root],
                pass_fds=(child.fileno(),),
            )
        except OSError as e:
            _close_sock(parent)
            child.close()
            self.last_error = f"spawn failed: {e}"
            return False
        # plain close (no shutdown): the child inherited this fd — a
        # SHUT_RDWR here would sever ITS end of the shared socket
        child.close()
        self.counters["device_spawns"] += 1
        with self._lock:
            if stop.is_set() or stop is not self._stop:
                _reap(proc)
                _close_sock(parent)
                return False
            self._spawning = (proc, parent)
        from surrealdb_tpu.device import proto

        parent.settimeout(self.init_timeout_s)
        try:
            tag, meta, _bufs = proto.recv_msg(parent)
        except socket.timeout:
            self.last_error = (
                f"init watchdog: backend init exceeded "
                f"{self.init_timeout_s:.0f}s"
            )
            self._abort_spawn(proc, parent)
            return False
        except (ConnectionError, OSError) as e:
            self.last_error = f"runner died during init: {e}"
            self._abort_spawn(proc, parent)
            return False
        if tag != "ready":
            self.last_error = (
                f"backend init failed: {meta.get('error', tag)}"
            )
            self._abort_spawn(proc, parent)
            return False
        platform = meta.get("platform")
        refusal = require_refusal(self.mode, platform,
                                  os.environ.get("JAX_PLATFORMS", ""))
        if refusal is not None:
            self.last_error = refusal
            self._abort_spawn(proc, parent)
            return False
        parent.settimeout(None)
        with self._lock:
            self._spawning = None
            if stop.is_set() or stop is not self._stop:
                _reap(proc)
                _close_sock(parent)
                return False
            self._gen += 1
            gen = self._gen
            self._proc = proc
            self._sock = parent
            self._loaded.clear()
            self.platform = platform
            self.device_kind = meta.get("device_kind")
            self.device_count = int(meta.get("device_count", 0))
            self.versions = meta.get("versions")
            self._compiling_seq = None
            self._no_wedge_before = 0.0
            if meta.get("compile_cache") is not None:
                self.compile_cache_info = meta["compile_cache"]
            if meta.get("mesh") is not None:
                self.mesh_info = meta["mesh"]
            self._send_q = queue.Queue()
        threading.Thread(target=self._send_loop, args=(parent, gen),
                         daemon=True, name="device-send").start()
        threading.Thread(target=self._recv_loop, args=(parent, gen),
                         daemon=True, name="device-recv").start()
        return True

    def _abort_spawn(self, proc, sock):
        with self._lock:
            self._spawning = None
        _reap(proc)
        _close_sock(sock)

    def _first_spawn(self, stop):
        ok = self._spawn_runner(stop)
        with self._lock:
            if self._spawn_thread is threading.current_thread():
                self._spawn_thread = None
            if stop.is_set() or stop is not self._stop:
                return
            if ok:
                self.state = "ready"
                self._ready.set()
                return
        self._mark_degraded(self.last_error or "init failed",
                            kill=False)

    def _mark_degraded(self, reason: str, kill: bool = True):
        """Circuit-break: kill the runner (crash-only restart discipline
        — its cache is rebuilt from KV truth on re-ship), fail every
        in-flight dispatch, and start the background re-probe."""
        with self._lock:
            if self._stop.is_set() or self.state == "off":
                return
            if self.state != "degraded":
                # only the TRANSITION records the cause: the socket
                # teardown that follows a wedge-kill must not overwrite
                # the wedge as "runner died"
                self.last_error = reason
            was_ready = self.state == "ready"
            self.state = "degraded"
            self._ready.clear()
            proc, self._proc = self._proc, None
            sock, self._sock = self._sock, None
            self._send_q = None
            self._loaded.clear()
            self._fail_pending(reason)
            start_probe = self._probe_thread is None
            if start_probe:
                self._probe_thread = threading.Thread(
                    target=self._probe_loop, args=(self._stop,),
                    daemon=True, name="device-probe",
                )
        _ = was_ready
        _close_sock(sock)
        if kill:
            _reap(proc)
        if start_probe:
            self._probe_thread.start()

    def _fail_pending(self, reason: str):
        # caller holds the lock
        for slot in self._pending.values():
            slot[1] = ("err", {"error": reason, "_unavail": True}, [])
            slot[0].set()
        self._pending.clear()

    def _probe_loop(self, stop):
        """Background re-probe with hysteresis: a recovered device is
        re-promoted without a server restart."""
        streak = 0
        while not stop.wait(self.probe_interval_s):
            with self._lock:
                if self.state != "degraded" or stop is not self._stop:
                    break
                have_runner = self._proc is not None
            try:
                if not have_runner:
                    if not self._spawn_runner(stop):
                        streak = 0
                        continue
                    self.counters["device_restarts"] += 1
                t, _m, _b = self._call_live("ping", {}, (),
                                            self.dispatch_timeout_s,
                                            health_check=True)
                if t != "ok":
                    raise DeviceUnavailable(str(_m))
                streak += 1
            except (DeviceUnavailable, DeviceOpError) as e:
                streak = 0
                with self._lock:
                    proc, self._proc = self._proc, None
                    sock, self._sock = self._sock, None
                    self._send_q = None
                    self._loaded.clear()
                # keep last_error = the original degradation cause (or
                # the spawn failure _spawn_runner just recorded)
                _close_sock(sock)
                _reap(proc)
                continue
            if streak >= max(1, self.promote_successes):
                with self._lock:
                    if self.state == "degraded":
                        self.state = "ready"
                        self._ready.set()
                break
        with self._lock:
            if self._probe_thread is threading.current_thread():
                self._probe_thread = None
            # re-arm if we raced a fresh degradation
            if (self.state == "degraded" and stop is self._stop
                    and not stop.is_set()
                    and self._probe_thread is None):
                self._probe_thread = threading.Thread(
                    target=self._probe_loop, args=(stop,),
                    daemon=True, name="device-probe",
                )
                self._probe_thread.start()

    # -- live dispatch -------------------------------------------------------

    def _call_live(self, op, meta, bufs, base_timeout,
                   health_check=False, sent=None):
        t_call = time.monotonic_ns()
        budget = None if health_check else _query_remaining()
        eff = base_timeout if budget is None \
            else min(base_timeout, max(budget, 0.0))
        if eff <= 0:
            raise DeviceUnavailable("query budget exhausted")
        with self._lock:
            if not health_check and self.state != "ready":
                raise DeviceUnavailable(f"device {self.state}")
            sock = self._sock
            sq = self._send_q
            if sock is None or sq is None:
                raise DeviceUnavailable("no runner")
            self._seq += 1
            seq = self._seq
            ev = threading.Event()
            slot = [ev, None, None, None, None]
            self._pending[seq] = slot
        meta = dict(meta)
        meta["seq"] = seq
        sq.put((op, meta, bufs, slot))
        if sent is not None:
            sent()
        start = time.monotonic()
        end = start + eff
        cancelled = False
        while not ev.is_set():
            # a declared compile (ours or one we queue behind) holds
            # the wedge clock; the query's own budget still applies
            limit = max(end, self._no_wedge_before)
            if budget is not None:
                limit = min(limit, start + max(budget, 0.0))
            left = limit - time.monotonic()
            if left <= 0:
                break
            ev.wait(min(left, 0.05))
            if not health_check and _query_cancelled():
                cancelled = True
                break
        if not ev.is_set():
            with self._lock:
                self._pending.pop(seq, None)
            if cancelled:
                raise DeviceUnavailable("query cancelled mid-dispatch")
            self.counters["device_dispatch_timeouts"] += 1
            if eff >= base_timeout - 1e-9:
                # the FULL op window elapsed: wedged runner — kill and
                # degrade (a short-budget query merely orphans its call)
                self._mark_degraded(
                    f"dispatch timeout: {op} exceeded {base_timeout}s "
                    f"(runner wedged)"
                )
            raise DeviceUnavailable(f"dispatch timed out ({op})")
        t_wake = time.monotonic_ns()
        tag, rmeta, rbufs = slot[1]
        if not health_check:
            _record_rpc_parts(rmeta.get("t"), t_call, slot[2], slot[3],
                              slot[4], t_wake)
        if tag == "err":
            if rmeta.get("_unavail"):
                raise DeviceUnavailable(rmeta.get("error", "runner died"))
            if rmeta.get("oom"):
                # typed budget refusal from the runner: degrade this
                # store to host, never the circuit breaker
                self._note_oom(meta)
                raise DeviceOutOfMemory(
                    rmeta.get("error", "device store over budget")
                )
            self.counters["device_dispatch_errors"] += 1
            raise DeviceOpError(rmeta.get("error", "device op failed"))
        return tag, rmeta, rbufs

    def _note_oom(self, meta: dict):
        """Record a budget refusal for the store named in `meta` —
        counter + the per-(key, tag) fail-fast cache ensure_loaded
        consults, recorded HERE so it happens in every mode (require
        rewraps the exception before callers could record it)."""
        self.counters["device_oom_refusals"] += 1
        key, tag = meta.get("key"), meta.get("tag")
        if key and tag is not None:
            with self._lock:
                self._oom_keys[key] = list(tag)

    def _send_loop(self, sock, gen):
        from surrealdb_tpu.device import proto

        while True:
            with self._lock:
                sq = self._send_q if gen == self._gen else None
            if sq is None:
                return
            try:
                op, meta, bufs, slot = sq.get(timeout=0.25)
            except queue.Empty:
                continue
            slot[2] = time.monotonic_ns()
            try:
                proto.send_msg(sock, op, meta, bufs)
            except (OSError, ValueError) as e:
                if self._is_current(gen):
                    self._mark_degraded(f"runner link lost (send): {e}")
                return
            slot[3] = time.monotonic_ns()

    def _recv_loop(self, sock, gen):
        from surrealdb_tpu.device import proto

        while True:
            try:
                tag, meta, bufs = proto.recv_msg(sock)
            except (ConnectionError, OSError) as e:
                if self._is_current(gen):
                    self._mark_degraded(f"runner died: {e}")
                return
            t_in = time.monotonic_ns()
            if tag == "compiling":
                with self._lock:
                    self._compiling_seq = meta.get("seq")
                    self._no_wedge_before = \
                        time.monotonic() + self.load_timeout_s
                continue
            cc = meta.get("cc")
            if isinstance(cc, dict):
                self.compile_counts = cc
            seq = meta.get("seq")
            with self._lock:
                slot = self._pending.pop(seq, None)
                if seq == self._compiling_seq:
                    # compile over: dispatches queued behind it get a
                    # fresh window from here
                    self._compiling_seq = None
                    self._no_wedge_before = \
                        time.monotonic() + self.dispatch_timeout_s
            if slot is not None:
                slot[1] = (tag, meta, bufs)
                slot[4] = t_in
                slot[0].set()

    def _is_current(self, gen) -> bool:
        with self._lock:
            return gen == self._gen and not self._stop.is_set() \
                and self.state in ("ready", "degraded", "probing")


def _record_rpc_parts(t, t_call: int, t_got, t_sent, t_in,
                      t_wake: int):
    """One RPC cut in six stages on one clock (time.monotonic_ns is
    CLOCK_MONOTONIC in both processes), from the reply's `t`
    (proto.REPLY_T): `rpc_out` from `_call_live`'s entry until the
    runner had read and decoded the request, `runner_h2d`,
    `runner_device`, `runner_d2h` as the op timed them
    (kernelstats.phase; 0 for an op that times none), `runner_other`
    the rest between the runner's `recv` and `ready` stamps, `rpc_back`
    from `ready` until the waiter ran again. They partition the call,
    so they sum to its `device_rpc`.

    The two hand-offs are cut again at the stamps of the threads that
    carry the call (`t_got`, `t_sent`: `_send_loop` holds the item,
    `send_msg` has returned; `t_in`: `_recv_loop` holds the decoded
    reply), to the nanosecond: `rpc_out` = `rpc_send_wake` (entry to
    `t_got`: `_lock`, the queue, the send thread's wake) + `rpc_send`
    (to min(`t_sent`, `recv`): encode and `sendall`; the min, because
    the send thread may lose the interpreter between `sendall` and its
    stamp, or not have stamped yet) + `rpc_wire_out` (to `recv`: the
    socket, the runner finishing the op before, its read and decode);
    `rpc_back` = `rpc_recv` (`ready` to `t_in`: the runner's encode and
    send, the socket, the recv thread's wake, read and decode) +
    `rpc_wake` (to the waiter running: the pending lookup under
    `_lock`, `Event.set`, the waiter's wake).

    All eleven or none, so their counts agree: a reply without `t` (an
    inline host, an error, an older runner), a stamp missing or a
    negative difference records nothing. Each is recorded with its own
    end stamp, for an open window's timeline (telemetry.stage_record);
    the runner's four have sums and no stamps, and are laid end to end
    from `recv` to `ready` there (`runner_other` first)."""
    from surrealdb_tpu.device.proto import REPLY_T
    from surrealdb_tpu.telemetry import stage_record

    if not isinstance(t, bytes) or len(t) != REPLY_T.size \
            or t_got is None or t_in is None:
        return
    recv, ready, h2d, dev, d2h = REPLY_T.unpack(t)
    sent = recv if t_sent is None else min(t_sent, recv)
    out = recv - t_call
    send_wake = t_got - t_call
    send = sent - t_got
    other = ready - recv - h2d - dev - d2h
    back = t_wake - ready
    back_recv = t_in - ready
    wake = t_wake - t_in
    if min(out, send_wake, send, h2d, dev, d2h, other, back, back_recv,
           wake) < 0:
        return
    stage_record("rpc_out", out, end_ns=recv)
    stage_record("rpc_send_wake", send_wake, end_ns=t_got)
    stage_record("rpc_send", send, end_ns=sent)
    stage_record("rpc_wire_out", recv - sent, end_ns=recv)
    end = recv
    for name, ns in (("runner_other", other), ("runner_h2d", h2d),
                     ("runner_device", dev), ("runner_d2h", d2h)):
        end += ns
        stage_record(name, ns, end_ns=end)
    stage_record("rpc_back", back, end_ns=t_wake)
    stage_record("rpc_recv", back_recv, end_ns=t_in)
    stage_record("rpc_wake", wake, end_ns=t_wake)


def require_refusal(mode: str, platform, jax_platforms: str):
    """Why a runner that came up on `platform` is an init error, or
    None. `require` means the chip: jax quietly choosing another
    backend must not pass for it. Naming the platform in JAX_PLATFORMS
    is the one deliberate way to run elsewhere (tests, rehearsals)."""
    if mode != "require" or platform == "tpu" \
            or platform in jax_platforms.lower().split(","):
        return None
    return (
        f"SURREAL_DEVICE=require needs a TPU but the runner came up on "
        f"{platform!r} (set JAX_PLATFORMS={platform} to run there "
        f"deliberately)"
    )


def _query_remaining():
    from surrealdb_tpu.inflight import remaining

    return remaining()


def _query_cancelled() -> bool:
    from surrealdb_tpu.inflight import cancelled

    return cancelled()


def _reap(proc):
    """SIGKILL + reap a runner without blocking the caller (a zombie
    per restart would accumulate in long-lived serving processes)."""
    if proc is None:
        return
    try:
        proc.kill()
    except OSError:
        pass
    threading.Thread(target=proc.wait, daemon=True,
                     name="device-reap").start()


def _close_sock(sock):
    if sock is None:
        return
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


# -- process-wide singleton --------------------------------------------------
# Device HBM is a process-wide resource: every Datastore in the process
# shares ONE supervised runner. Tests swap instances via set_supervisor.

_SUP: Optional[DeviceSupervisor] = None
_SUP_LOCK = threading.Lock()


def get_supervisor() -> DeviceSupervisor:
    global _SUP
    with _SUP_LOCK:
        if _SUP is None:
            _SUP = DeviceSupervisor()
        return _SUP


def set_supervisor(sup: Optional[DeviceSupervisor]):
    """Install a supervisor instance; returns the previous one (tests
    restore it). Does NOT shut the old one down."""
    global _SUP
    with _SUP_LOCK:
        old, _SUP = _SUP, sup
        return old


def reset_supervisor():
    """Shut down and drop the singleton (next get_ re-reads env)."""
    global _SUP
    with _SUP_LOCK:
        old, _SUP = _SUP, None
    if old is not None:
        old.shutdown()


def attach_telemetry(telemetry):
    """Register the device gauges on a datastore's telemetry hub. The
    closures read the CURRENT singleton so a swapped supervisor keeps
    reporting."""
    telemetry.register_gauge(
        "device_degraded",
        lambda: 1 if get_supervisor().state == "degraded" else 0,
    )
    for name in ("device_restarts", "device_dispatch_timeouts",
                 "device_fallbacks", "device_host_routed",
                 "device_oom_refusals", "device_col_ships",
                 "device_col_ship_bytes", "device_vec_appends",
                 "device_vec_append_rows", "device_vec_append_bytes",
                 "device_vec_full_ships"):
        telemetry.register_gauge(
            name, lambda n=name: get_supervisor().counters.get(n, 0)
        )
    # cross-query batching efficiency (device/batcher.py): dispatch-size
    # last/avg/max say whether concurrency is actually coalescing
    from surrealdb_tpu.device.batcher import BATCH_STATS

    telemetry.register_gauge(
        "device_batch_size_last", lambda: BATCH_STATS.last
    )
    telemetry.register_gauge(
        "device_batch_size_max", lambda: BATCH_STATS.max
    )
    telemetry.register_gauge(
        "device_batch_size_avg",
        lambda: round(BATCH_STATS.riders / max(BATCH_STATS.dispatches, 1),
                      2),
    )
    telemetry.register_gauge(
        "device_batch_dispatches", lambda: BATCH_STATS.dispatches
    )
    # kernel compile-shape accounting: misses = compiles paid in this
    # process (cheap disk loads when the persistent cache is warm)
    telemetry.register_gauge(
        "device_compile_cache_hits",
        lambda: get_supervisor().compile_counts_now()["hits"],
    )
    telemetry.register_gauge(
        "device_compile_cache_misses",
        lambda: get_supervisor().compile_counts_now()["misses"],
    )
