#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served path.

    python3 benchmark/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

One run is one new process tree, as `chip_smoke.py` builds it. This process
serves: `SURREAL_DEVICE=require`, a memory datastore, `make_server` on a
thread, the supervised runner subprocess holding the chip. It never imports
jax. The load comes from child processes (`loadgen.py`), so the clients'
Python does not share the server's interpreter lock.

Set-up (counted in `setup_s`, process start to window start): runner up and
on a TPU, the deployment loaded and every write acknowledged (the cell's
kind, `kinds/<kind>.py`, from `configs/<config>.json` and `--seed`), then a
warm-up that drives the window's own traffic for a few seconds so that every
query bucket the window forms is compiled or loaded from the cache. Then
the window: `--seconds` of the traffic in `traffic/<traffic>.json`, read
only. Then: snapshots, the device's peak memory, the runner and the server
stopped, and only then the plain reference over the answers that the clients
received inside the window, which decides `correct`.

The last line of stdout is the result: `correct`, `attempted`, `failed`,
`metrics` (`--trace 0`: the cell's end-to-end metrics; `--trace 1`: its
per-layer metrics, each from its reader `layers/<metric>.py`), `device`,
with `--trace 1` `breakdown`, and last `compared`: each number that decided
`correct` beside its limit. The same numbers are the last lines of stderr.

No cell's name, size or limit is in this file. A new KNN deployment or
traffic mix is a JSON file and an entry in BENCHMARK.json; a new kind or
per-layer metric is one new file.

`--rehearsal` (needs JAX_PLATFORMS=cpu) runs the same code at the config's
`rehearsal` sizes on the CPU for the tests; the result says so and its
numbers are no device numbers. `--control` also judges the reference in the
next precision down, put in the program's place: it has to fail.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
import urllib.request  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import loadgen  # noqa: E402
import metrics as e2e  # noqa: E402

# a hang becomes a failure with clean-up, not a kill: the driver allows a
# compiling first run 1200 s
DEADLINE_S = 1150
# warm-up and trace: the same for every cell until one needs another value
WARMUP_BURSTS = 3         # times each burst size is driven
WARMUP_SECONDS = 2.5      # one steady phase
WARMUP_MAX_PHASES = 8
TRACE_SECONDS = 3.0       # the profiler's share of the window
# thresholds a rehearsal shrinks so that tiny stores walk the same paths
# (chip_smoke.py REHEARSAL_ENV)
REHEARSAL_ENV = {
    "SURREAL_KNN_HOST_BATCH": "device",
    "SURREAL_KNN_DEVICE_MIN_ROWS": "64",
    "SURREAL_KNN_ANN_MIN_ROWS": "1536",
    "SURREAL_KNN_SEG_MIN_ROWS": "2048",
    "SURREAL_KNN_SEG_ROWS": "1024",
    "SURREAL_KNN_ANN_REFINE": "0",
}


class RunFailed(Exception):
    pass


def log(msg: str):
    print(f"[bench +{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise RunFailed(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find_cell(root: str, workload: str):
    """(benchmark, cell, config entry, config, traffic) by the names in
    BENCHMARK.json."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise RunFailed(f"BENCHMARK.json has no workload {workload!r}")
    cell = cells[0]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    return bench, cell, entry, config, traffic


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Http:
    """Set-up traffic: `POST /sql` with the statement as the body, and
    `POST /rpc` method `query` with bound variables."""

    def __init__(self, port: int, headers: dict):
        self.base = f"http://127.0.0.1:{port}"
        self.headers = headers

    def _post(self, path: str, body: bytes):
        req = urllib.request.Request(self.base + path, data=body,
                                     headers=self.headers, method="POST")
        with urllib.request.urlopen(req, timeout=DEADLINE_S) as r:
            return json.loads(r.read())

    @staticmethod
    def _results(statements):
        for st in statements:
            if st["status"] != "OK":
                raise RunFailed(f"statement failed: {st['result']}")
        return [st["result"] for st in statements]

    def sql(self, text: str):
        return self._results(self._post("/sql", text.encode()))

    def query(self, text: str, variables: dict):
        out = self._post("/rpc", json.dumps(
            {"id": 0, "method": "query", "params": [text, variables]}).encode())
        if "error" in out:
            raise RunFailed(f"rpc failed: {out['error']}")
        return self._results(out["result"])


class Generators:
    """The load generators: child processes, started once, driven phase
    by phase, stopped and waited for in `close`."""

    def __init__(self, traffic: dict, port: int, path: str, headers: dict,
                 bodies):
        self.procs = []
        self.threads = traffic["threads_per_process"]
        n = traffic["processes"]
        env = {k: v for k, v in os.environ.items()
               if k not in ("BENCH_TRACE_DIR", "PYTHONPATH")}
        indexed = list(enumerate(bodies))
        for j in range(n):
            p = subprocess.Popen(
                [sys.executable, os.path.join(BENCH, "loadgen.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
            self.procs.append(p)
            loadgen.write_msg(p.stdin, {
                "port": port, "path": path, "headers": headers,
                "threads": traffic["threads_per_process"],
                "bodies": indexed[j::n]})

    def phase(self, begin: float, end: float, during=None, clients=None,
              each=None):
        """Every client (or the first `clients` of them) sends from
        `begin` until `end`, at most `each` requests; all records."""
        per = self.threads
        left = len(self.procs) * per if clients is None else clients
        busy = []
        for p in self.procs:
            active = min(per, left)
            left -= active
            if active:
                busy.append(p)
                loadgen.write_msg(p.stdin, {"begin": begin, "end": end,
                                            "active": active, "each": each})
        if during is not None:
            during()
        records = []
        for p in busy:
            got = loadgen.read_msg(p.stdout)
            if got is None:
                raise RunFailed("a load generator died")
            records.extend(got)
        return records

    def close(self):
        for p in self.procs:
            try:
                loadgen.write_msg(p.stdin, None)
                p.stdin.close()
            except (OSError, ValueError):
                pass
        for p in self.procs:
            try:
                p.wait(15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            p.stdout.close()


def snapshot(sup, first: bool) -> dict:
    """The program's counters at a window's edge. `runner_status` is
    itself one device RPC, so it stays outside the stage readings."""
    from surrealdb_tpu.telemetry import stage_snapshot

    runner = sup.runner_status() if first else None
    stages = stage_snapshot()
    status = sup.status()
    if not first:
        runner = sup.runner_status()
    return {"stages": stages, "supervisor": status,
            "batching": status["batching"], "runner": runner}


def deltas(before: dict, after: dict) -> tuple:
    """What the window added: per stage {count, total_us}, and the
    batcher's {dispatches, riders}."""
    stages = {}
    for name, a in after["stages"].items():
        b = before["stages"].get(name, {"count": 0, "total_ms": 0.0})
        if a["count"] > b["count"]:
            stages[name] = {
                "count": a["count"] - b["count"],
                "total_us": (a["total_ms"] - b["total_ms"]) * 1e3}
    batching = {k: after["batching"][k] - before["batching"][k]
                for k in ("dispatches", "riders")}
    return stages, batching


def warm_up(gens: Generators, sup, traffic: dict) -> int:
    """Drives the window's own clients until the runner has every shape
    the window will form. The batcher pads a dispatch's riders to a power
    of two, and a dispatch that finds riders queued takes them all: so
    first bursts of 2, 3, 5, 9, ... clients at an idle server (one rider
    goes alone, the rest ride together), a few times each, then the
    steady traffic in phases of `WARMUP_SECONDS` until a whole phase adds
    no first-shape dispatch (`cc.misses`: compiled, or loaded from the
    persistent cache). Returns the requests sent."""
    clients = traffic["processes"] * traffic["threads_per_process"]
    sent = 0

    def drive(**kw):
        nonlocal sent
        now = time.monotonic()
        recs = gens.phase(now + 0.05, now + 0.05 + kw.pop("seconds"), **kw)
        bad = [r for r in recs if r[3] != 200]
        if bad:
            raise RunFailed(f"{len(bad)} of {len(recs)} warm-up requests "
                            f"failed, first: {bad[0][3]} {bad[0][4][:300]!r}")
        sent += len(recs)

    bursts, b = {1, clients}, 1
    while b + 1 < clients:
        bursts.add(b + 1)
        b *= 2
    for b in sorted(bursts):
        for _ in range(WARMUP_BURSTS):
            drive(seconds=60.0, clients=b, each=1)
    misses = sup.runner_status()["cc"]["misses"]
    for phase in range(WARMUP_MAX_PHASES):
        drive(seconds=WARMUP_SECONDS)
        now_misses = sup.runner_status()["cc"]["misses"]
        if now_misses == misses:
            log(f"warm-up: quiet after {phase + 1} steady phase(s), "
                f"{misses} first-shape dispatches in all")
            return sent
        misses = now_misses
    raise RunFailed(f"warm-up: still new shapes after "
                    f"{WARMUP_MAX_PHASES} phases")


class GcWatch:
    """Times this process's garbage collections (`gc.callbacks`): a
    full collection over a loaded store's heap holds the interpreter
    lock, and so every request, for as long as it takes."""

    def __init__(self):
        self.pauses = []          # (generation, start, seconds)
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            self.pauses.append((info["generation"], self._t0,
                                time.monotonic() - self._t0))

    def within(self, begin: float, end: float) -> dict:
        inside = [p for p in self.pauses if begin <= p[1] < end]
        return {"collections": len(inside),
                "full_collections": sum(1 for p in inside if p[0] == 2),
                "total_s": sum(p[2] for p in inside),
                "longest_s": max((p[2] for p in inside), default=0.0)}


def window_notes(records, begin, end, watch, before, after, stages,
                 batching):
    """What a reader of a far-off run wants to see (stderr only): the
    answers of each second, the slowest request, garbage collection in
    the serving process, programs first met, re-ships, the stages."""
    by_second = [0] * (int(end - begin) + 1)
    for r in records:
        by_second[min(max(int(r[2] - begin), 0), len(by_second) - 1)] += 1
    rb, ra = before["runner"], after["runner"]
    yield (f"answers per second of the window: {by_second}; slowest request "
           f"{max(r[2] - r[1] for r in records) * 1e3:.0f} ms; serving "
           f"process gc in the window: {watch.within(begin, end)}")
    yield (f"first-shape dispatches in the window: "
           f"{ra['cc']['misses'] - rb['cc']['misses']}, programs loaded "
           f"{ra['compile']['persistent_hits'] - rb['compile']['persistent_hits']}"
           f", compiled "
           f"{ra['compile']['persistent_misses'] - rb['compile']['persistent_misses']}"
           f"; shipped "
           f"{after['supervisor']['ship_s'] - before['supervisor']['ship_s']:.3f}"
           f"s; largest batch so far {after['batching']['max']}")
    yield ("stages in the window, ms per request: " + ", ".join(
        f"{k} {v['total_us'] / 1e3 / max(len(records), 1):.2f}"
        for k, v in stages.items()) + f"; batcher {batching}")


def sleep_until(t: float):
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def touch(path: str):
    with open(path, "w"):
        pass


def traced(trace_dir: str, begin: float, seconds: float):
    """Drops the start and the stop file for the runner's profiler
    window (hooks/sitecustomize.py) while the generators run."""
    def during():
        sleep_until(begin + 0.5)
        touch(os.path.join(trace_dir, "start"))
        time.sleep(seconds)
        touch(os.path.join(trace_dir, "stop"))
    return during


def profiler_note(trace_dir: str) -> dict:
    """Waits for the runner's `done`: the profiler window has closed and
    the trace is written."""
    done = os.path.join(trace_dir, "done")
    t_end = time.monotonic() + 120
    while not os.path.exists(done):
        if time.monotonic() > t_end:
            raise RunFailed("the runner's profiler window did not close")
        time.sleep(0.05)
    note = load_json(done)
    if "error" in note:
        raise RunFailed(f"profiler: {note['error']}")
    return note


def reduce_trace(trace_dir: str, platform: str, note: dict) -> dict:
    """Reduces the trace in a child: reading it needs jax, which this
    process never imports."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_TRACE_DIR", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_reduce.py"), trace_dir,
         platform], env=env, capture_output=True, text=True, timeout=200)
    if out.returncode != 0:
        raise RunFailed(f"trace_reduce failed: {out.stderr[-2000:]}")
    trace = json.loads(out.stdout.strip().splitlines()[-1])
    if "error" in trace:
        raise RunFailed(f"trace: {trace['error']}: {trace.get('planes')}")
    trace["window_s"] = note["stopping"] - note["started"]
    trace["stop_trace_s"] = note["stopped"] - note["stopping"]
    return trace


def run(args) -> int:
    root = ROOT
    if not os.path.isdir(os.path.join(root, "surrealdb_tpu")):
        print(f"benchmark: no program beside the benchmark in {root}",
              file=sys.stderr)
        return 3
    bench, cell, entry, config, traffic = find_cell(root, args.workload)
    if traffic["loop"] != "closed":
        raise RunFailed(f"the generator drives closed loops only, not "
                        f"{traffic['loop']!r}")
    kind = load_module(os.path.join(BENCH, "kinds", config["kind"] + ".py"),
                       "bench_kind_" + config["kind"])
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if args.rehearsal:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            print("benchmark: --rehearsal needs JAX_PLATFORMS=cpu",
                  file=sys.stderr)
            return 2
        os.environ.update(REHEARSAL_ENV)
    os.environ["SURREAL_DEVICE"] = "require"
    trace_dir = None
    if args.trace:
        # a fixed place inside the checkout, emptied before and after
        trace_dir = os.path.join(root, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        os.environ["BENCH_TRACE_DIR"] = trace_dir
        os.environ["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(BENCH, "hooks")]
            + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
               if p])
    sys.path.insert(0, root)

    from surrealdb_tpu import Datastore
    from surrealdb_tpu.device import get_supervisor, reset_supervisor
    from surrealdb_tpu.server import make_server

    def on_alarm(_sig, _frm):
        raise TimeoutError(f"not done after {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)
    srv = gens = None
    watch = GcWatch()
    gc.callbacks.append(watch)
    ds = Datastore("memory")
    try:
        sup = get_supervisor()
        if not sup.wait_ready(sup.init_timeout_s + 10):
            raise RunFailed(f"no device runner: {sup.last_error}")
        if sup.platform != "tpu" and not args.rehearsal:
            raise RunFailed(f"the runner is on {sup.platform!r}, not a TPU: "
                            f"nothing to measure here")
        if (sup.device_count or 0) < cell["chips"] and not args.rehearsal:
            raise RunFailed(f"the cell needs {cell['chips']} chip(s), the "
                            f"runner sees {sup.device_count}")
        log(f"{'REHEARSAL: ' if args.rehearsal else ''}runner pid "
            f"{sup.runner_pid()} on {sup.platform} {sup.device_kind} "
            f"x{sup.device_count}")
        if sup.platform == "tpu" and sup.device_kind not in peaks:
            raise RunFailed(f"peaks.json has no device {sup.device_kind!r}")
        srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        port = srv.server_address[1]
        headers = dict(kind.HEADERS)
        dep = kind.setup(config, args.seed, ds, Http(port, headers),
                         args.rehearsal, log)
        gens = Generators(traffic, port, kind.PATH, headers, dep.bodies())
        warm = warm_up(gens, sup, traffic)
        before = snapshot(sup, first=True)
        begin = time.monotonic() + 0.2
        end = begin + args.seconds
        setup_s = begin - T_START
        trace_s = min(TRACE_SECONDS, args.seconds - 1.0)
        log(f"set-up {setup_s:.1f}s ({warm} warm-up requests); window "
            f"of {args.seconds}s")
        records = gens.phase(
            begin, end, traced(trace_dir, begin, trace_s) if trace_dir
            else None)
        after = snapshot(sup, first=False)
        note = profiler_note(trace_dir) if trace_dir else None
        rs = after["runner"]
        device = {
            "platform": str(rs["platform"]), "kind": str(rs["device_kind"]),
            "count": int(rs["device_count"]),
            "memory_peak_bytes": max(
                (d["peak_bytes_in_use"] or 0) for d in rs["devices"]),
        }
    except Exception as e:
        # the boundary: whatever set-up or the window raised fails the run
        if not isinstance(e, (RunFailed, kind.SetupFailed)):
            traceback.print_exc()
        print(f"benchmark: FAILED: {e.__class__.__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        gc.callbacks.remove(watch)
        if gens is not None:
            gens.close()
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        reset_supervisor()  # stops the runner: the chip is free
        ds.close()

    # the window has closed, the peak is read, the program is gone: judge
    says = []
    t_judge = time.monotonic()
    verdict = dep.judge(records, before, after, config["limits"], args.seed,
                        says.append)
    compared = verdict["compared"]
    ok = verdict["ok"]
    rows = [(r[0], r[1], r[2], r[3], ok[i]) for i, r in enumerate(records)]
    numbers = e2e.end_to_end(rows, begin, end)
    numbers.update(verdict["metrics"])
    numbers["setup_s"] = setup_s
    failed = sum(1 for good in ok if not good)
    compared["failed_requests"] = {
        "value": failed, "limit": 0, "sense": "<=", "ok": failed == 0}
    repeats = len(records) - len({r[0] for r in records})
    log(f"window: {len(records)} requests, {failed} failed, {repeats} "
        f"repeated a pool query; judged in "
        f"{time.monotonic() - t_judge:.1f}s; " + ", ".join(
            f"{k} {v:.6g}" for k, v in numbers.items()))
    stages, batching = deltas(before, after)
    for line in window_notes(records, begin, end, watch, before, after,
                             stages, batching):
        log(line)
    log("set-up split: " + ", ".join(
        f"{k} {v:.1f}" for k, v in dep.timing.items()))
    result = {"correct": all(c["ok"] for c in compared.values()),
              "attempted": len(records), "failed": failed}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if args.trace:
        trace = reduce_trace(trace_dir, device["platform"], note)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {trace['events']} device operations, busy "
            f"{trace['busy_s']:.4f}s of {trace['window_s']:.3f}s, "
            f"stop_trace took {trace['stop_trace_s']:.1f}s; planes "
            f"{[p for p, _lines in trace['planes']]}")
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        window = {
            "before": before, "after": after, "stages": stages,
            "batching": batching, "requests": len(records),
            "answers": len(records) - failed, "seconds": end - begin,
            "config": dep.sz, "trace": trace, "device": device,
            "peaks": peaks.get(device["kind"]),
        }
        out = {}
        for m in bench["per_layer"]:
            if not applies(m, args.workload):
                continue
            reader = load_module(
                os.path.join(BENCH, "layers", m["name"] + ".py"),
                "bench_layer_" + m["name"].replace(".", "_").replace("-", "_"))
            value = reader.read(window)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": units[m["name"]]}
        result["metrics"] = out
        result["device"] = device
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        result["metrics"] = {
            m["name"]: {"value": numbers[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, args.workload)}
        result["device"] = device
    if args.rehearsal:
        result["rehearsal"] = True
    if args.control:
        result["control"] = dep.judge_control(
            records, config["limits"], args.seed)
        print("control (has to fail): " + json.dumps(result["control"]),
              file=sys.stderr)
    result["compared"] = compared
    for text in says:
        print(f"not correct: {text}", file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['sense']} "
              f"{c['limit']!r} {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU (needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--control", action="store_true",
                    help="also judge the lower-precision control")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except RunFailed as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
