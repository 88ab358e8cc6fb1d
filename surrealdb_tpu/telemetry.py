"""Telemetry: spans, Prometheus metrics, trace ring.

Reference: server/src/telemetry/mod.rs:1-40 — tracing-subscriber +
OpenTelemetry OTLP export of traces/metrics/logs, with datastore gauges
from kvs::Metrics (ds.rs:150-167). This build has no network egress, so
the same data is surfaced as pull endpoints instead of OTLP push:

- `/metrics` (server): Prometheus text format — datastore counters,
  query-duration histogram, HTTP/WS/RPC counters.
- `/telemetry/traces` (server): recent per-query span trees as JSON.
- `/telemetry/stalls` (server): the stall watch's last dumps.

Spans are thread-local and cheap: `span(name)` context managers nest;
each query's root span lands in a bounded ring buffer.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

_BUCKETS_MS = (0.1, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000,
               2500, 5000, 10000)


class StageStat:
    """One query stage's accumulated timing. Updates are deliberately
    lock-free: under the GIL a lost increment during a race skews a
    metric by one sample, which is acceptable for observability — a
    per-stage lock would put two atomic ops on every query's hot path
    for data nobody reads at that granularity.

    `cpu_ns` stays None unless the stage is recorded with the thread's
    CPU time (only `request` is: server/__init__.py), and `to_dict`
    then carries `cpu_ms` beside the wall time."""

    __slots__ = ("count", "total_ns", "max_ns", "cpu_ns")

    def __init__(self):
        self.count = 0
        self.total_ns = 0
        self.max_ns = 0
        self.cpu_ns = None

    def add(self, ns: int, cpu_ns=None):
        self.count += 1
        self.total_ns += ns
        if ns > self.max_ns:
            self.max_ns = ns
        if cpu_ns is not None:
            self.cpu_ns = (self.cpu_ns or 0) + cpu_ns

    def to_dict(self) -> dict:
        c = self.count
        d = {
            "count": c,
            "total_ms": round(self.total_ns / 1e6, 3),
            "avg_us": round(self.total_ns / max(c, 1) / 1e3, 1),
            "max_us": round(self.max_ns / 1e3, 1),
        }
        if self.cpu_ns is not None:
            d["cpu_ms"] = round(self.cpu_ns / 1e6, 3)
        return d


# Per-stage query timing (the PR-6 overhead strip's measurement hook):
# process-wide so the serving edge (request, admission), the datastore
# (parse, txn open), the executor (envelope, eval) and the device layer
# (batcher wait/ride/dispatch, the supervisor's RPC and its six parts)
# all land in ONE table regardless of which Datastore/Telemetry
# instance they hang off. Stages surface in /metrics and `INFO FOR
# SYSTEM`, and the benchmark's `--trace 1` readers take them from
# `stage_snapshot()`; doc/operations.md lists them with what contains
# what.
_STAGES: dict[str, StageStat] = {}


# While a `DeviceSupervisor.profile` window is open, every record also
# lands here as `(stage, thread id, start_ns, end_ns)` on
# CLOCK_MONOTONIC (time.monotonic_ns; perf_counter_ns is the same clock
# on Linux, and the runner stamps with it too), so the window's
# `host_stages.json` says where each interval lay and not only what it
# summed to. None while no window is open: a record then costs one
# global read more. Bounded: a window left open cannot grow it past
# TIMELINE_MAX entries (~50 MB; half a minute of 32 busy callers).
TIMELINE_MAX = 1 << 18
_TIMELINE = None


def stage_record(name: str, ns: int, cpu_ns=None, end_ns=None):
    """Record `ns` nanoseconds of wall time (and, where the caller
    measured it, `cpu_ns` of its thread's CPU time) spent in query
    stage `name`. `end_ns` is the stage's end on time.monotonic_ns
    where the caller records after the fact (a wait written down once
    the ride is over, a part of an RPC that ended at another thread's
    stamp); left out, the stage ended now. It places the interval in
    an open window's timeline and changes no sum."""
    st = _STAGES.get(name)
    if st is None:
        # dict set is atomic under the GIL; a racing first-record for
        # the same stage leaves one winner and loses one sample
        st = _STAGES.setdefault(name, StageStat())
    st.add(ns, cpu_ns)
    tl = _TIMELINE
    if tl is not None and len(tl) < TIMELINE_MAX:
        if end_ns is None:
            end_ns = time.monotonic_ns()
        tl.append((name, threading.get_ident(), end_ns - ns, end_ns))


def timeline_arm():
    """Start keeping the stages' intervals (`DeviceSupervisor.profile`
    opens its window with this). A window already open keeps its
    list."""
    global _TIMELINE
    if _TIMELINE is None:
        _TIMELINE = []


def timeline_disarm() -> list:
    """Stop keeping them; the intervals kept since `timeline_arm`."""
    global _TIMELINE
    tl, _TIMELINE = _TIMELINE, None
    return tl or []


def stage_snapshot() -> dict:
    """{stage: {count, total_ms, avg_us, max_us[, cpu_ms]}} sorted by
    total time descending."""
    items = sorted(_STAGES.items(), key=lambda kv: -kv[1].total_ns)
    return {k: v.to_dict() for k, v in items}


# this module is imported with the datastore, so for a server these are
# the process's start
_WALL0 = time.monotonic()
_CPU0 = time.process_time()


def cpu_usage() -> float:
    """CPU seconds this process has used over the wall seconds it has
    run (both since this module was imported): the cores it has kept
    busy on average. `INFO FOR SYSTEM` reports it."""
    return round((time.process_time() - _CPU0)
                 / max(time.monotonic() - _WALL0, 1e-3), 4)


class Span:
    __slots__ = ("name", "start_ns", "dur_ns", "attrs", "children")

    def __init__(self, name: str):
        self.name = name
        self.start_ns = time.time_ns()
        self.dur_ns = 0
        self.attrs: dict = {}
        self.children: list[Span] = []

    def to_dict(self):
        d = {
            "name": self.name,
            "start_ns": self.start_ns,
            "dur_us": round(self.dur_ns / 1000, 1),
        }
        if self.attrs:
            d["attrs"] = self.attrs
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


class Telemetry:
    """Per-datastore telemetry hub (counters + histogram + trace ring)."""

    def __init__(self, ring_size: int = 256):
        self.lock = threading.Lock()
        self.ring_size = ring_size
        self.traces: list[Span] = []  # rendered lazily by recent_traces
        # the stall watch's last dumps (server/stallwatch.py), newest
        # last; served at /telemetry/stalls
        self.stalls: list[dict] = []
        self.counters: dict[str, int] = {}
        # query duration histogram (cumulative bucket counts, Prometheus
        # `le` semantics) + sum/count
        self.hist = [0] * (len(_BUCKETS_MS) + 1)
        self.hist_sum_ms = 0.0
        self.hist_count = 0
        self._local = threading.local()
        # gauges: name -> zero-arg callable sampled at scrape time (the
        # admission controller and in-flight registry register theirs)
        self.gauges: dict = {}
        # counter providers: like gauges but rendered as counters
        self.counter_providers: dict = {}

    def register_gauge(self, name: str, fn):
        with self.lock:
            self.gauges[name] = fn

    def register_counter(self, name: str, fn):
        """A monotonically increasing counter whose value lives with its
        owner (sampled at scrape, rendered as `surreal_<name>_total`).
        Lets hot paths count under a lock they already hold instead of
        taking the telemetry lock per event."""
        with self.lock:
            self.counter_providers[name] = fn

    def unregister_gauge(self, name: str):
        """Drop a gauge provider (a closed sharded backend must not
        leave a dangling closure behind for the next scrape)."""
        with self.lock:
            self.gauges.pop(name, None)

    # -- counters -----------------------------------------------------------
    # The remote-KV client records its resilience counters here:
    # kv_retries (transport retries), kv_failovers (primary changes
    # observed), kv_txn_failovers (read-only txns transparently
    # re-pinned), kv_deadline_exhausted (ops that ran out their retry
    # deadline). The shard router adds kv_shard_map_refreshes (stale-map
    # recoveries), kv_2pc_commits / kv_2pc_aborts (cross-shard
    # transaction outcomes), kv_2pc_decide_deferred (phase-2 deliveries
    # left to a participant's resolver), plus gauges kv_shards /
    # kv_shard_map_epoch. All surface through `prometheus()` as
    # surreal_<name>_total (counters) / surreal_<name> (gauges).
    def inc(self, name: str, by: int = 1):
        with self.lock:
            self.counters[name] = self.counters.get(name, 0) + by

    def get(self, name: str) -> int:
        with self.lock:
            v = self.counters.get(name, 0)
            fn = self.counter_providers.get(name)
        if fn is not None:
            try:
                v += fn()
            except Exception:
                pass
        return v

    # -- spans --------------------------------------------------------------
    def start(self, name: str, **attrs) -> Span:
        """Open a span nested under the thread's current span."""
        s = Span(name)
        s.attrs.update(attrs)
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        if stack:
            stack[-1].children.append(s)
        stack.append(s)
        s.dur_ns = -time.perf_counter_ns()  # closed in end()
        return s

    def end(self, s: Span):
        s.dur_ns += time.perf_counter_ns()
        stack = getattr(self._local, "stack", None)
        if stack and stack[-1] is s:
            stack.pop()
        elif stack and s in stack:
            # children left open (an exception between a `start` and
            # its `try`): a thread that serves request after request
            # must not keep them under every later query
            del stack[stack.index(s):]
        if not stack:
            self._finish_trace(s)

    @contextmanager
    def span(self, name: str, **attrs):
        """Nested span context; completing the outermost span records the
        trace into the ring."""
        s = self.start(name, **attrs)
        try:
            yield s
        finally:
            self.end(s)

    def _finish_trace(self, s: Span):
        ms = s.dur_ns / 1e6
        with self.lock:
            self.hist_count += 1
            self.hist_sum_ms += ms
            for i, edge in enumerate(_BUCKETS_MS):
                if ms <= edge:
                    self.hist[i] += 1
                    break
            else:
                self.hist[-1] += 1
            # ring holds the finished Span OBJECTS; the dict/json render
            # happens lazily at read time (recent_traces) — serializing
            # every query's span tree was measurable dict churn on the
            # serving hot path and the ring overwrites most of them
            # unread anyway
            self.traces.append(s)
            if len(self.traces) > self.ring_size:
                del self.traces[: self.ring_size // 2]

    def recent_traces(self, limit: int = 64):
        with self.lock:
            spans = list(self.traces[-limit:])
        return [s.to_dict() for s in spans]

    STALL_RING = 4

    def add_stall(self, dump: dict):
        with self.lock:
            self.stalls.append(dump)
            del self.stalls[:-self.STALL_RING]

    def recent_stalls(self) -> list[dict]:
        with self.lock:
            return list(self.stalls)

    # -- prometheus ---------------------------------------------------------
    def prometheus(self, ds=None) -> str:
        """Render Prometheus text-format metrics (server /metrics)."""
        lines = []

        def counter(name, value, help_=None):
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} counter")
            lines.append(f"{name} {value}")

        with self.lock:
            counters = dict(self.counters)
            hist = list(self.hist)
            hsum, hcount = self.hist_sum_ms, self.hist_count
            gauges = dict(self.gauges)
            cprov = dict(self.counter_providers)
        for k, fn in sorted(cprov.items()):
            try:
                counters.setdefault(k, 0)
                counters[k] += fn()
            except Exception:
                continue
        if ds is not None:
            for k, v in ds.metrics.items():
                counter(f"surreal_ds_{k}_total", v,
                        "datastore counter (kvs::Metrics analog)")
            lines.append("# TYPE surreal_live_queries gauge")
            lines.append(f"surreal_live_queries {len(ds.live_queries)}")
            lines.append("# TYPE surreal_vector_indexes gauge")
            lines.append(f"surreal_vector_indexes {len(ds.vector_indexes)}")
        for k in sorted(counters):
            counter(f"surreal_{k}_total", counters[k])
        for k in sorted(gauges):
            try:
                v = gauges[k]()
            except Exception:
                continue  # a dying provider must not poison the scrape
            lines.append(f"# TYPE surreal_{k} gauge")
            lines.append(f"surreal_{k} {v}")
        lines.append("# TYPE surreal_query_stage_us summary")
        for sname, st in stage_snapshot().items():
            lines.append(
                f'surreal_query_stage_us{{stage="{sname}",stat="avg"}} '
                f'{st["avg_us"]}'
            )
            lines.append(
                f'surreal_query_stage_us{{stage="{sname}",stat="max"}} '
                f'{st["max_us"]}'
            )
            lines.append(
                f'surreal_query_stage_count{{stage="{sname}"}} '
                f'{st["count"]}'
            )
        lines.append("# TYPE surreal_query_duration_ms histogram")
        acc = 0
        for i, edge in enumerate(_BUCKETS_MS):
            acc += hist[i]
            lines.append(
                f'surreal_query_duration_ms_bucket{{le="{edge}"}} {acc}'
            )
        lines.append(
            f'surreal_query_duration_ms_bucket{{le="+Inf"}} {hcount}'
        )
        lines.append(f"surreal_query_duration_ms_sum {round(hsum, 3)}")
        lines.append(f"surreal_query_duration_ms_count {hcount}")
        return "\n".join(lines) + "\n"
