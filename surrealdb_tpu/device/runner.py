"""DeviceRunner subprocess entry point.

Spawned by the DeviceSupervisor with one end of a socketpair. Owns ALL
JAX state: backend init happens HERE (never on a serving thread), so a
stalled accelerator init stalls this process while the supervisor's
init watchdog times out and the serving path degrades to host
execution. One process for each chip: nothing else in the program may
open the accelerator.

Protocol (device/proto.py frames):
  runner -> supervisor on boot:  ("ready", {platform, device_kind,
                                            device_count, versions,
                                            compile_cache, mesh})
  supervisor -> runner:          (op, {seq, ...}, bufs)
  runner -> supervisor:          ("compiling", {seq, kernel}) — zero or
                                 more, before a first-shape dispatch
                                 enters XLA: the supervisor's dispatch
                                 window then covers the compile
  runner -> supervisor:          ("ok"|"stale"|"err", {seq, ...}, bufs)

Every "ok"/"stale" reply's meta carries `cc` (compile-shape counters)
and `t` (proto.REPLY_T), the op's timeline on CLOCK_MONOTONIC
(time.monotonic_ns, the clock the supervisor stamps with too): `recv`
when the request was read and decoded, `ready` just before the reply
is sent, and the nanoseconds spent in the phases the op timed (`h2d`,
`device`, `d2h`; kernelstats.phase). The same spans go into the
profiler's trace as `runner:<op>`, `runner:<phase>` and `runner:idle`
(blocked in recv); `runner:<op>` carries `seq` and `t_recv`, that
`recv` stamp, so whoever reads the trace can lay the profiler's clock
over CLOCK_MONOTONIC and the device plane beside the serving process's
`host_stages.json` (DeviceSupervisor.profile).

The loop is deliberately single-threaded and crash-only: any internal
corruption is allowed to kill the process — the supervisor restarts it
and the serving side re-ships block caches from KV truth."""

from __future__ import annotations

import os
import signal
import socket
import sys
import time
import traceback


def serve(sock) -> None:
    """Init jax, announce readiness, serve ops until EOF/shutdown."""
    from surrealdb_tpu.device import proto

    try:
        # persistent compilation cache FIRST: a respawned runner (the
        # supervisor's crash/degrade/restart cycle) must reload its
        # compiled kernels from disk instead of paying cold XLA
        # compiles before serving at full speed
        from surrealdb_tpu.device.compile_cache import initialize

        cache_info = initialize()
        import jax
        import jaxlib

        devs = jax.devices()
        platform = devs[0].platform if devs else "none"
        ndev = len(devs)
        from surrealdb_tpu.device import mesh as devmesh

        mesh_info = devmesh.describe()
    except BaseException as e:  # init failed: report, then die
        try:
            proto.send_msg(sock, "init_error", {"error": str(e)[:500]})
        except OSError:
            pass
        raise
    from importlib import metadata

    from surrealdb_tpu.device import kernelstats
    from surrealdb_tpu.device.handlers import DeviceBudgetError, DeviceHost

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    kernelstats.install_jax_listeners()
    host = DeviceHost()
    current = {"seq": None}

    def announce_compile(kernel):
        # sent from inside host.handle, before the jitted call enters
        # XLA; a broken link surfaces on the reply send below
        try:
            proto.send_msg(sock, "compiling",
                           {"seq": current["seq"], "kernel": kernel})
        except OSError:
            pass

    kernelstats.ON_COMPILE = announce_compile
    proto.send_msg(sock, "ready", {
        "platform": platform,
        "device_kind": devs[0].device_kind if devs else None,
        "device_count": ndev,
        "versions": {"jax": jax.__version__,
                     "jaxlib": jaxlib.__version__, "libtpu": libtpu},
        "compile_cache": cache_info, "mesh": mesh_info,
    })
    from jax.profiler import TraceAnnotation

    t_mark = time.monotonic_ns()
    while True:
        try:
            with TraceAnnotation("runner:idle"):
                op, meta, bufs = proto.recv_msg(sock)
        except ConnectionError:
            return  # supervisor went away: die with it
        t_recv = kernelstats.loop_received(t_mark)
        if op == "shutdown":
            try:
                proto.send_msg(sock, "ok", {"seq": meta.get("seq")})
            except OSError:
                pass
            return
        seq = current["seq"] = meta.get("seq")
        try:
            with TraceAnnotation("runner:" + op, seq=seq, t_recv=t_recv):
                tag, out_meta, out_bufs = host.handle(op, meta, bufs)
            out_meta = dict(out_meta)
            out_meta["seq"] = seq
            # compile-shape counters piggyback on every reply so the
            # supervisor's gauges track the subprocess without a
            # dedicated RPC per scrape
            out_meta["cc"] = kernelstats.snapshot()
            ph = kernelstats.PHASES
            out_meta["t"] = proto.REPLY_T.pack(
                t_recv, time.monotonic_ns(), ph.get("h2d", 0),
                ph.get("device", 0), ph.get("d2h", 0))
            proto.send_msg(sock, tag, out_meta, out_bufs)
        except ConnectionError:
            return
        except BaseException as e:
            err = f"{e.__class__.__name__}: {e}"
            tb = traceback.format_exc(limit=6)
            reply = {"seq": seq, "error": err[:500], "trace": tb[-2000:]}
            if isinstance(e, DeviceBudgetError):
                # typed refusal, not a health event: the supervisor
                # raises DeviceOutOfMemory and degrades THIS store to
                # host paths; the runner keeps serving everything else
                reply["oom"] = True
            try:
                proto.send_msg(sock, "err", reply)
            except OSError:
                return
        t_mark = kernelstats.loop_replied()


def main(fd: int) -> None:
    # the supervisor owns this process's lifetime; a Ctrl-C aimed at the
    # server must not race the supervisor's orderly shutdown
    try:
        signal.signal(signal.SIGINT, signal.SIG_IGN)
    except (ValueError, OSError):
        pass
    sock = socket.socket(fileno=fd)
    try:
        serve(sock)
    finally:
        try:
            sock.close()
        except OSError:
            pass


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    main(int(sys.argv[1]))
