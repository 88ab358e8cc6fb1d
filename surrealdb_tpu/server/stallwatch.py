"""Stall watch: ONE thread a server reads what a wake costs and catches
the moments when nothing finishes.

It sleeps `TICK_S` at a time. Every tick it records stage `gil_wake`:
how much later than asked it ran again. That is what any thread pays
that wants the interpreter back after a wait: the kernel's timer slack
at an idle server (the base; doc/operations.md gives the chip host's),
and on top of it the queue for the interpreter under load.

Then it reads the in-flight registry (inflight.py): `count()`, the
queries open, and `finished`, the queries closed so far. Queries open
and none finished for `STALL_S` is a stall: it takes ONE dump (every
thread's name and its top `FRAMES` frames from `sys._current_frames()`,
the device supervisor's state and pending calls, its own lateness and
the process's CPU time over those ticks) into the ring of the last few
on `Datastore.telemetry` (`GET /telemetry/stalls`), and one line to
stderr. Lateness and CPU time tell the kinds apart: threads parked on
one mutex, or waiting for the runner, leave the watch on time; a thread
that kept the interpreter makes the watch late and uses the CPU all the
while; a process that was not run at all (stopped from outside, every
thread in the kernel) makes it late and uses none. When a query
finishes again it records stage `request_stall` with the whole silent
span, to the tick. A watch kept from running sees nothing until it is
over: a tick `STALL_S` late or more at a server whose queries finished
meanwhile is dumped after the fact (`kind` `late_tick`; the frames are
then of the moment after) and its lateness recorded as the span. A
lone slow query is silent too and counts: the watch cannot know that
one `SLEEP 2s` is meant.

What it costs: ten wake-ups a second, each two lock acquisitions, a
read of the process's CPU clock and a stage record. An embedded
datastore starts none; `make_server` does, and `server_close` ends it.
"""

from __future__ import annotations

import sys
import threading
import time

from surrealdb_tpu.telemetry import stage_record

TICK_S = 0.1
STALL_S = 0.5
FRAMES = 8


class StallWatch:
    """The watch of one server; `close` it with the server. `clock`
    (ns, monotonic) and `start=False` are the tests' seams: they drive
    `tick` themselves."""

    def __init__(self, ds, clock=time.monotonic_ns, start: bool = True):
        self._ds = ds
        self._clock = clock
        self._stop = threading.Event()
        self._seen = ds.inflight.finished  # `finished` at the last tick
        self._quiet_ns = None  # since when queries are open, none done
        # over the ticks since then: how many, their lateness (sum,
        # largest), the CPU time the process used
        self._ticks = self._late_sum = self._late_max = self._cpu_sum = 0
        self._dumped = False
        if start:
            threading.Thread(target=self._run, daemon=True,
                             name="surreal-stall-watch").start()

    def close(self):
        """Stop the thread (the server is closing)."""
        self._stop.set()

    def _run(self):
        tick_ns = int(TICK_S * 1e9)
        cpu = time.process_time_ns()
        while True:
            asked = self._clock() + tick_ns
            if self._stop.wait(TICK_S):
                return
            now, cpu0, cpu = self._clock(), cpu, time.process_time_ns()
            self.tick(now, max(now - asked, 0), cpu - cpu0)

    def tick(self, now: int, late_ns: int, cpu_ns: int = 0):
        """One reading at `now`, `late_ns` after the tick was due; the
        process used `cpu_ns` of CPU time since the tick before."""
        stage_record("gil_wake", late_ns, end_ns=now)
        reg = self._ds.inflight
        done, n_open = reg.finished, reg.count()
        moved, self._seen = done != self._seen, done
        if moved or not n_open:
            # a query finished, or none is open: a silence ends here
            if moved and not self._dumped and late_ns >= STALL_S * 1e9:
                # one no tick saw: the watch itself could not run, and
                # whatever was open has finished since
                self._note(now, late_ns, cpu_ns)
                self._dump("late_tick", now, n_open, done)
            if self._dumped:
                stage_record("request_stall", now - self._quiet_ns,
                             end_ns=now)
            self._dumped = False
            self._quiet_ns = None
            if n_open:
                self._note(now, 0, 0)
            return
        self._note(now, late_ns, cpu_ns)
        if not self._dumped and now - self._quiet_ns >= STALL_S * 1e9:
            self._dump("silent", now, n_open, done)

    def _note(self, now: int, late_ns: int, cpu_ns: int):
        """One more tick of a silence; the first one starts it, where
        this tick was due: what it came late belongs to it."""
        if self._quiet_ns is None:
            self._quiet_ns = now - late_ns
            self._ticks = self._late_sum = self._late_max = 0
            self._cpu_sum = 0
        self._ticks += 1
        self._late_sum += late_ns
        self._late_max = max(self._late_max, late_ns)
        self._cpu_sum += cpu_ns

    def _dump(self, kind: str, now: int, n_open: int, done: int):
        from surrealdb_tpu.device import get_supervisor

        self._dumped = True
        sup = get_supervisor()
        names = {t.ident: t.name for t in threading.enumerate()}
        threads = []
        for ident, frame in sys._current_frames().items():
            frames = []
            while frame is not None and len(frames) < FRAMES:
                code = frame.f_code
                frames.append(f"{code.co_filename}:{frame.f_lineno} "
                              f"{code.co_name}")
                frame = frame.f_back
            threads.append({"id": ident, "name": names.get(ident, "?"),
                            "frames": frames})
        dump = {
            "at": time.time(),
            "kind": kind,
            "silent_s": (now - self._quiet_ns) / 1e9,
            "open": n_open,
            "finished": done,
            "ticks": self._ticks,
            "late_ms": {"sum": self._late_sum / 1e6,
                        "max": self._late_max / 1e6},
            "cpu_ms": self._cpu_sum / 1e6,
            "device": {"state": sup.state,
                       "pending_calls": sup.pending_calls()},
            "threads": threads,
        }
        self._ds.telemetry.add_stall(dump)
        print(f"[surrealdb-tpu] stall ({kind}): {n_open} queries open and "
              f"none finished for {dump['silent_s']:.2f}s; the watch ran "
              f"{dump['late_ms']['sum']:.1f} ms late over {self._ticks} "
              f"ticks and the process used {dump['cpu_ms']:.1f} ms of "
              f"CPU; device {sup.state}, "
              f"{dump['device']['pending_calls']} calls pending; "
              f"{len(threads)} threads' frames at /telemetry/stalls",
              file=sys.stderr, flush=True)
