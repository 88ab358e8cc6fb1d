"""Profiler window for the device runner, from outside the program.

Nothing under `surrealdb_tpu/` calls `jax.profiler`, and only the runner
subprocess holds the chip, so only it can trace it. The runner inherits the
serving process's environment; with this directory on PYTHONPATH the
interpreter imports this module at start-up. It does nothing unless
`BENCH_TRACE_DIR` is set AND the process is the runner. There it starts one
thread that waits for the serving process to drop `<dir>/start`, calls
`jax.profiler.start_trace(<dir>)`, waits for `<dir>/stop`, calls
`stop_trace()` and writes `<dir>/done`. A profiler window inside
`device/runner.py` is the next `tracing` issue's; then this file goes.
"""

import os
import sys


def _cmdline_has_runner() -> bool:
    try:
        with open("/proc/self/cmdline", "rb") as f:
            return b"surrealdb_tpu.device.runner" in f.read()
    except OSError:
        return False


def _watch(trace_dir: str):
    import time

    def wait_for(name):
        path = os.path.join(trace_dir, name)
        while not os.path.exists(path):
            time.sleep(0.02)

    wait_for("start")
    import jax

    note = {}
    try:
        jax.profiler.start_trace(trace_dir)
        note["started"] = time.monotonic()
        wait_for("stop")
        note["stopping"] = time.monotonic()
        jax.profiler.stop_trace()
        note["stopped"] = time.monotonic()
    except Exception as e:  # the runner must keep serving: report, not raise
        note["error"] = f"{e.__class__.__name__}: {e}"
    import json

    with open(os.path.join(trace_dir, "done.tmp"), "w") as f:
        json.dump(note, f)
    os.replace(os.path.join(trace_dir, "done.tmp"),
               os.path.join(trace_dir, "done"))


def _install():
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if not trace_dir or not _cmdline_has_runner():
        return
    import threading

    threading.Thread(target=_watch, args=(trace_dir,), daemon=True,
                     name="bench-trace").start()


_install()
