"""One dispatch's timeline from inside the program: the stages the server
edge (`request`), the batcher (`batch_wait`, `batch_ride`,
`batch_dispatch`), the index engine (`knn_post`), the supervisor
(`rpc_out`, `runner_*`, `rpc_back` and the five hand-offs inside the two
ways) and the KV store (`txn_lock_*`) record, the runner's `loop` and
`ann` counters, and the window the program owns in both processes: the
profiler's trace, `host_stages.json` and `runner_idle_by`
(device/idle.py). CPU only: the batcher tests run no device, the RPC
tests a CPU runner subprocess."""

from __future__ import annotations

import glob
import json
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from surrealdb_tpu import cnf, telemetry
from surrealdb_tpu.device import DeviceSupervisor, proto
from surrealdb_tpu.device.batcher import BatchStats, DeviceBatcher

RPC_PARTS = ("rpc_out", "runner_h2d", "runner_device", "runner_d2h",
             "runner_other", "rpc_back")
# `rpc_out` = the first three, `rpc_back` = the last two
HANDOFFS = ("rpc_send_wake", "rpc_send", "rpc_wire_out", "rpc_recv",
            "rpc_wake")


def totals() -> dict:
    """{stage: (count, total ns)} of the process-wide table, unrounded."""
    return {k: (v.count, v.total_ns)
            for k, v in list(telemetry._STAGES.items())}


def added(before: dict, name: str) -> tuple:
    """(count, ns) that stage `name` gained since `before`."""
    c0, n0 = before.get(name, (0, 0))
    c1, n1 = totals().get(name, (0, 0))
    return c1 - c0, n1 - n0


# -- batcher ------------------------------------------------------------------


class Gated:
    """A batch kernel whose first call waits for `gate`."""

    def __init__(self):
        self.gate = threading.Event()
        self.started = threading.Event()
        self.sizes = []

    def __call__(self, payloads):
        self.sizes.append(len(payloads))
        if len(self.sizes) == 1:
            self.started.set()
            assert self.gate.wait(10)
        return [p * 2 for p in payloads]


def queue_behind(batcher, kernel, riders: int, delay: float):
    """One rider dispatches and is held; `riders` more queue behind it
    for `delay` seconds; then everything finishes. Returns the answers."""
    out = {}

    def go(i):
        out[i] = batcher.submit(i)

    threads = [threading.Thread(target=go, args=(i,), daemon=True)
               for i in range(riders + 1)]
    threads[0].start()
    assert kernel.started.wait(5)
    for t in threads[1:]:
        t.start()
    end = time.monotonic() + 5
    while len(batcher.queue) < riders and time.monotonic() < end:
        time.sleep(0.001)
    assert len(batcher.queue) == riders
    time.sleep(delay)
    kernel.gate.set()
    for t in threads:
        t.join(5)
    return out


def test_a_queued_rider_records_its_wait_and_the_dispatcher_none(
        monkeypatch):
    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE", 1)
    # alone: the rider is its own dispatcher, nothing to wait for
    b = DeviceBatcher(dispatch=lambda ps: list(ps), stats=BatchStats())
    t0 = totals()
    assert b.submit(7) == 7
    assert added(t0, "batch_wait")[0] == 1
    assert added(t0, "batch_wait")[1] < 20e6
    assert added(t0, "batch_ride")[0] == 1
    # queued behind a dispatch held for 0.15 s
    kernel = Gated()
    b = DeviceBatcher(dispatch=kernel, stats=BatchStats())
    t0 = totals()
    out = queue_behind(b, kernel, riders=1, delay=0.15)
    assert out == {0: 0, 1: 2}
    count, ns = added(t0, "batch_wait")
    assert count == 2
    # the rider waited at least the delay, the dispatcher next to nothing
    assert 0.15e9 <= ns < 0.15e9 + 0.1e9
    # each rode its own dispatch: the first for at least the delay
    count, ns = added(t0, "batch_ride")
    assert count == 2 and ns >= 0.15e9
    # what a rider spends in `submit` is its wait plus its ride
    assert added(t0, "batch_dispatch")[1] <= ns


def test_batch_dispatch_counts_dispatches_not_riders(monkeypatch):
    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE", 1)
    kernel = Gated()
    b = DeviceBatcher(dispatch=kernel, stats=BatchStats())
    t0 = totals()
    out = queue_behind(b, kernel, riders=3, delay=0.02)
    assert out == {0: 0, 1: 2, 2: 4, 3: 6}
    assert kernel.sizes == [1, 3]
    assert added(t0, "batch_dispatch")[0] == 2
    assert added(t0, "batch_wait")[0] == 4
    assert added(t0, "batch_ride")[0] == 4


def test_a_failed_dispatch_is_timed_with_its_degrade_tiers():
    def dispatch(_payloads):
        raise OSError("device down")

    def fallback(p):
        time.sleep(0.03)
        return -p

    b = DeviceBatcher(dispatch=dispatch, fallback=fallback,
                      retryable=(OSError,), stats=BatchStats())
    t0 = totals()
    assert b.submit(4) == -4
    count, ns = added(t0, "batch_dispatch")
    assert count == 1 and ns >= 0.03e9


def test_a_withdrawn_rider_records_no_wait(monkeypatch):
    from surrealdb_tpu import inflight
    from surrealdb_tpu.err import QueryTimeout

    monkeypatch.setattr(cnf, "DEVICE_BATCH_PIPELINE", 1)
    kernel = Gated()
    b = DeviceBatcher(dispatch=kernel, stats=BatchStats())
    first = threading.Thread(target=b.submit, args=(1,), daemon=True)
    first.start()
    assert kernel.started.wait(5)
    t0 = totals()
    reg = inflight.InflightRegistry()
    h = reg.open("t", "t", "knn", deadline=time.monotonic() + 0.1)
    with inflight.activate(h), pytest.raises(QueryTimeout):
        b.submit(2)
    reg.close(h)
    assert added(t0, "batch_wait")[0] == 0
    assert added(t0, "batch_ride")[0] == 0
    kernel.gate.set()
    first.join(5)
    # the held rider finished and recorded its own
    assert added(t0, "batch_wait")[0] == 1


# -- index engine -------------------------------------------------------------


def test_knn_post_is_recorded_once_a_dispatch(monkeypatch):
    """Two riders in one dispatch through the inline device path: one
    `knn_post`, inside that dispatch's `batch_dispatch`."""
    import surrealdb_tpu.idx.vector as V
    from surrealdb_tpu.val import RecordId

    monkeypatch.setattr(V, "DEVICE_MIN_ROWS", 16)
    rng = np.random.default_rng(3)
    ix = V.TpuVectorIndex("t", "t", "pts", "ix", {
        "dimension": 8, "distance": "euclidean", "vector_type": "f32"})
    ix.vecs = rng.normal(size=(256, 8)).astype(np.float32)
    ix.valid = np.ones(256, dtype=bool)
    ix.rids = [RecordId("pts", i) for i in range(256)]
    ix.version = 0
    t0 = totals()
    res = ix.coalescer._dispatch([(ix.vecs[0], 3), (ix.vecs[1], 3)])
    assert [r[0][0].id for r in res] == [0, 1]
    assert added(t0, "knn_post")[0] == 1
    assert added(t0, "device_rpc")[0] >= 1
    # the inline host's reply has no `t`: no part of an RPC is recorded
    assert all(added(t0, p)[0] == 0 for p in RPC_PARTS + HANDOFFS)


# -- one RPC cut in six, over a live CPU runner ---------------------------------


@pytest.fixture(scope="module")
def live():
    """A CPU runner subprocess on ONE device (so `VecStore.knn` takes the
    single-chip path the v5e takes), a small store loaded. No prewarm
    and no other caller: every RPC in these tests is the test's own."""
    mp = pytest.MonkeyPatch()
    mp.setenv("XLA_FLAGS", "")
    sup = DeviceSupervisor(mode="auto", dispatch_timeout_s=20.0,
                           load_timeout_s=60.0, init_timeout_s=120.0)
    try:
        assert sup.wait_ready(120), sup.last_error
        rng = np.random.default_rng(0)
        vecs = rng.normal(size=(2048, 16)).astype(np.float32)
        cfg = {"hbm_budget": 1 << 40, "score_budget": 1 << 26,
               "query_chunk": 256, "int8_oversample": 4,
               "block_rows": 1 << 20}
        sup.call("vec_load", {"key": "vec/t", "tag": [1, 0],
                              "metric": "euclidean", "mink_p": 2.0,
                              "cfg": cfg},
                 [vecs, np.ones(2048, np.uint8)], timeout_s=60)

        def knn(riders=3):
            return sup.call("vec_knn", {"key": "vec/t", "tag": [1, 0],
                                        "k": 5}, [vecs[:riders]])

        knn()  # compile
        yield sup, knn
    finally:
        sup.shutdown()
        mp.undo()


def test_one_vec_knn_is_cut_in_six_parts_inside_its_device_rpc(live):
    _sup, knn = live
    t0 = totals()
    t_before = time.monotonic_ns()
    tag, meta, bufs = knn()
    t_after = time.monotonic_ns()
    assert tag == "ok" and bufs[1][:, 0].tolist() == [0, 1, 2]
    recv, ready, h2d, device, d2h = proto.REPLY_T.unpack(meta["t"])
    # the runner's stamps are on this process's clock, inside the call
    assert t_before <= recv <= ready <= t_after
    assert min(h2d, device, d2h) >= 0
    assert h2d + device + d2h <= ready - recv
    parts = {p: added(t0, p) for p in RPC_PARTS}
    assert all(c == 1 and ns >= 0 for c, ns in parts.values()), parts
    assert parts["runner_device"][1] == device > 0 and h2d == d2h == 0
    count, rpc_ns = added(t0, "device_rpc")
    assert count == 1
    total = sum(ns for _c, ns in parts.values())
    # they partition `_call_live`; `device_rpc` has only its entry and
    # return beyond that
    assert total <= rpc_ns and rpc_ns - total < 2e6


def test_the_hand_offs_partition_the_way_out_and_the_way_back(live):
    _sup, knn = live
    t0 = totals()
    for _ in range(3):
        assert knn()[0] == "ok"
    got = {p: added(t0, p) for p in RPC_PARTS + HANDOFFS}
    assert all(c == 3 and ns >= 0 for c, ns in got.values()), got
    # to the nanosecond: the stamps between are shared
    assert got["rpc_out"][1] == sum(
        got[p][1] for p in ("rpc_send_wake", "rpc_send", "rpc_wire_out"))
    assert got["rpc_back"][1] == got["rpc_recv"][1] + got["rpc_wake"][1]
    # a thread woke on either way: neither hand-off is free
    assert got["rpc_send_wake"][1] > 0 and got["rpc_wake"][1] > 0


def test_a_reply_without_t_or_with_a_negative_part_records_nothing():
    from surrealdb_tpu.device.supervisor import _record_rpc_parts

    pack = proto.REPLY_T.pack  # recv, ready, h2d, device, d2h
    every = RPC_PARTS + HANDOFFS
    # (t, t_call, t_got, t_sent, t_in, t_wake)
    t0 = totals()
    _record_rpc_parts(None, 100, 120, 150, 700, 900)
    _record_rpc_parts(pack(200, 300, 0, 60, 0)[:16], 100, 120, 150, 700,
                      900)
    # the runner's clock behind the caller's: no stage, not a negative one
    _record_rpc_parts(pack(50, 300, 0, 0, 0), 100, 120, 150, 700, 900)
    _record_rpc_parts(pack(200, 300, 0, 500, 0), 100, 120, 150, 700, 900)
    # the recv thread's stamp before the runner's `ready`, or after the
    # waiter's wake; a hand-off never stamped: all or none
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, 120, 150, 250, 900)
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, 120, 150, 950, 900)
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, None, 150, 700, 900)
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, 120, 150, None, 900)
    assert all(added(t0, p)[0] == 0 for p in every)
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, 120, 150, 700, 900)
    assert {p: added(t0, p)[1] for p in every} == {
        "rpc_out": 100, "runner_h2d": 0, "runner_device": 60,
        "runner_d2h": 0, "runner_other": 40, "rpc_back": 600,
        "rpc_send_wake": 20, "rpc_send": 30, "rpc_wire_out": 50,
        "rpc_recv": 400, "rpc_wake": 200}
    # the send thread stamped late (it lost the interpreter after
    # `sendall`), or had not stamped yet: its part ends at the runner's
    # `recv` at the latest
    t0 = totals()
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, 120, 260, 700, 900)
    _record_rpc_parts(pack(200, 300, 0, 60, 0), 100, 120, None, 700, 900)
    assert {p: added(t0, p)[1] for p in HANDOFFS[:3]} == {
        "rpc_send_wake": 40, "rpc_send": 160, "rpc_wire_out": 0}
    assert added(t0, "rpc_out") == (2, 200)


def test_the_runners_loop_counters_track_wall_time(live):
    sup, knn = live
    first = sup.runner_status()
    for _ in range(4):
        knn()
        time.sleep(0.1)
    last = sup.runner_status()
    a, b = first["loop"], last["loop"]
    idle, busy = b["idle_ns"] - a["idle_ns"], b["busy_ns"] - a["busy_ns"]
    assert idle > 0.3e9 and busy > 0
    # on the runner's own clock: from one status's arrival to the next's
    wall = proto.REPLY_T.unpack(last["t"])[0] \
        - proto.REPLY_T.unpack(first["t"])[0]
    assert abs(idle + busy - wall) <= 0.05 * wall


def test_ann_search_counts_the_rows_it_scores():
    from surrealdb_tpu.device import kernelstats
    from surrealdb_tpu.device.annstore import AnnStore
    from surrealdb_tpu.idx import cagra

    rng = np.random.default_rng(2)
    n, dim, d_out = 512, 8, 4
    x = rng.normal(size=(n, dim)).astype(np.float32)
    graph = rng.integers(0, n, size=(n, d_out)).astype(np.int32)
    arow = np.maximum(np.abs(x).max(axis=1), 1e-30) / 127.0
    x8 = np.rint(x / arow[:, None]).astype(np.int8)
    cfg = {"width": 16, "iters": 3, "expand": 2}
    store = AnnStore("ann/t", graph, x8, arow.astype(np.float32),
                     (x ** 2).sum(axis=1).astype(np.float32),
                     "euclidean", cfg)
    probe = cagra.probe_count(n, 16)
    before = dict(kernelstats.ANN)
    assert store.search(x[:3], 8).shape == (3, 8)   # padded to 4 riders
    assert store.search(x[:1], 8).shape == (1, 8)
    got = {k: kernelstats.ANN[k] - before[k] for k in before}
    assert got == {"searches": 2,
                   "rows_scored": 4 * 3 * 2 * d_out + 2 * probe}


# -- the profiler window the program owns ----------------------------------------


def test_profile_puts_the_runners_spans_beside_the_devices_operations(
        live, tmp_path):
    from jax.profiler import ProfileData

    sup, knn = live
    note, held = {}, []
    window = threading.Thread(
        target=lambda: note.update(sup.profile(str(tmp_path), 1.0)))
    window.start()
    time.sleep(0.3)
    for _ in range(4):
        recv, ready = proto.REPLY_T.unpack(knn()[1]["t"])[:2]
        held.append((recv, ready))
        time.sleep(0.05)
    window.join(60)
    assert note["window_s"] >= 1.0 and note["stop_s"] >= 0
    paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(paths) == 1
    spans, xla, t_recvs = {}, [], []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith("runner:"):
                    spans.setdefault(ev.name, []).append(span)
                    if ev.name == "runner:vec_knn":
                        t_recvs.append(next(
                            int(v) for k, v in ev.stats if k == "t_recv"))
                elif "xla" in line.name.lower() and ev.duration_ns > 0:
                    xla.append(span)
    assert len(spans["runner:vec_knn"]) == 4
    assert len(spans["runner:device"]) == 4 and spans["runner:idle"]
    # the host's wait for the device encloses the device's own work
    for s, e in spans["runner:device"]:
        assert any(s <= xs and xe <= e for xs, xe in xla), (s, e)
    # the phase lies inside its op, and it is the op's only one: on one
    # device `vec_knn` is one launch that carries its transfer in and
    # its one copy out (tests/test_vec_knn_single_launch.py)
    ops = spans["runner:vec_knn"]
    assert all(any(s <= ps and pe <= e for s, e in ops)
               for ps, pe in spans["runner:device"])
    assert "runner:h2d" not in spans and "runner:d2h" not in spans
    # each op's span carries the `recv` stamp its reply carried: the
    # trace's clock laid over CLOCK_MONOTONIC
    assert sorted(t_recvs) == [recv for recv, _ready in held]
    # the same window of this process: every stage's interval, on that
    # clock, beside the trace
    assert telemetry._TIMELINE is None
    doc = json.load(open(note["host_stages"]))
    assert note["host_stages"] == str(tmp_path / "host_stages.json")
    w0, w1 = doc["window_ns"]
    assert (w1 - w0) / 1e9 == pytest.approx(note["window_s"], abs=1e-6)
    rows = doc["stages"]
    assert all(start <= end for _st, _tid, start, end in rows)
    # (the window's own start and stop calls are in it too)
    assert {r[3] for r in rows if r[0] == "rpc_out"} >= {
        recv for recv, _ready in held}
    assert {r[2] for r in rows if r[0] == "rpc_back"} >= {
        ready for _recv, ready in held}
    for name in HANDOFFS + ("device_rpc",):
        assert sum(1 for r in rows if r[0] == name) >= 4, name
    # the runner held an op from each `recv` to its `ready` (and for a
    # moment at either edge: the window's own start and stop calls);
    # the rest is idle, and every idle second has one cause
    busy = sum(ready - recv for recv, ready in held) / 1e9
    assert busy <= note["runner_busy_s"] < busy + 0.005
    assert note["runner_busy_s"] + note["runner_idle_s"] == pytest.approx(
        note["window_s"], abs=1e-6)
    by = note["runner_idle_by"]
    assert list(by) == ["request_out", "reply_back", "dispatch_host",
                        "riders_queued", "no_rider"]
    assert sum(by.values()) == pytest.approx(note["runner_idle_s"],
                                             abs=1e-6)
    # one caller that waits between its calls: nearly all of it is
    # nobody asking, the hand-offs are the rest, and no batcher ran
    assert by["no_rider"] > 0.8 * note["window_s"]
    assert by["request_out"] > 0 and by["reply_back"] > 0
    assert by["dispatch_host"] == by["riders_queued"] == 0
    # and the runner still serves
    tag, _meta, bufs = knn()
    assert tag == "ok" and bufs[1][:, 0].tolist() == [0, 1, 2]


def test_an_interrupted_profile_leaves_no_trace_open(live, tmp_path,
                                                     monkeypatch):
    import surrealdb_tpu.device.supervisor as S

    sup, knn = live

    me, sleep = threading.get_ident(), time.sleep

    def interrupted(s):
        if threading.get_ident() != me:
            return sleep(s)  # the supervisor's own threads
        assert telemetry._TIMELINE is not None  # the window is open
        raise KeyboardInterrupt

    monkeypatch.setattr(S.time, "sleep", interrupted)
    with pytest.raises(KeyboardInterrupt):
        sup.profile(str(tmp_path / "a"), 1.0)
    monkeypatch.undo()
    # the window was closed on the way out, in both processes: stages
    # are no longer kept, and a second one can open
    assert telemetry._TIMELINE is None
    t0 = totals()
    assert knn()[0] == "ok" and added(t0, "rpc_out")[0] == 1
    assert telemetry._TIMELINE is None
    note = sup.profile(str(tmp_path / "b"), 0.05)
    assert note["window_s"] >= 0.05
    assert knn()[0] == "ok"


# -- the idle seconds' causes, on hand-made intervals ------------------------------


def rpc(tid, t_call, t_got, sent, recv, ready, t_in, t_wake):
    """The timeline rows one call leaves (supervisor._record_rpc_parts
    and `call`), in the order they are recorded."""
    return [("rpc_out", tid, t_call, recv),
            ("rpc_send_wake", tid, t_call, t_got),
            ("rpc_send", tid, t_got, sent),
            ("rpc_wire_out", tid, sent, recv),
            ("rpc_back", tid, ready, t_wake),
            ("rpc_recv", tid, ready, t_in),
            ("rpc_wake", tid, t_in, t_wake),
            ("device_rpc", tid, t_call, t_wake)]


def test_interval_arithmetic():
    from surrealdb_tpu.device.idle import subtract, total, union

    assert union([(5, 9), (1, 3), (2, 4), (9, 9), (8, 12)]) == [
        [1, 4], [5, 12]]
    assert union([]) == []
    a = [[0, 10], [20, 30]]
    assert subtract(a, []) == a
    assert subtract(a, [[0, 30]]) == []
    assert subtract(a, [[2, 3], [5, 22], [29, 40]]) == [
        [0, 2], [3, 5], [22, 29]]
    assert subtract(a, [[10, 20]]) == a
    assert total(a) == 20


def test_runner_idle_by_names_each_gap_by_its_first_cause():
    from surrealdb_tpu.device.idle import runner_busy, runner_idle_by

    s = 10 ** 9  # the rows in seconds, the arithmetic in ns
    rows = []
    # a dispatcher (thread 1) works 1 s before its call and 0.5 after;
    # the runner holds the op from 3.0 to 4.0
    rows += rpc(1, 2 * s, 2.2 * s, 2.5 * s, 3 * s, 4 * s, 4.6 * s, 5 * s)
    rows.append(("batch_dispatch", 1, 1 * s, 5.5 * s))
    # a second call (thread 2) overlaps it: sent while the runner is
    # busy, held from 4.0 to 6.0; its way out past 4.0 is no gap
    rows += rpc(2, 3.5 * s, 3.6 * s, 3.7 * s, 4 * s, 6 * s, 6.1 * s,
                6.3 * s)
    # two riders queued: one while all that went on, one after it with
    # no call in flight
    rows.append(("batch_wait", 3, 2.5 * s, 5.2 * s))
    rows.append(("batch_wait", 4, 7 * s, 8 * s))
    rows = [(st, tid, int(a), int(b)) for st, tid, a, b in rows]
    assert runner_busy(rows) == [[3 * s, 6 * s]]
    out = runner_idle_by(rows, 0, 10 * s)
    assert out["busy_s"] == 3.0 and out["idle_s"] == 7.0
    assert out["by"] == {
        "request_out": 1.0,     # 2.0-3.0, though a dispatch is open too
        "reply_back": pytest.approx(0.3),    # 6.0-6.3
        "dispatch_host": 1.0,   # 1.0-2.0; 5.0-5.5 lies under the busy
        "riders_queued": 1.0,   # 7.0-8.0; 2.5-5.2 was named before
        "no_rider": pytest.approx(3.7),      # 0-1, 6.3-7, 8-10
    }
    assert sum(out["by"].values()) == pytest.approx(out["idle_s"])
    # a window clips what lies outside it, and a gap with nothing open
    # is nobody's
    out = runner_idle_by(rows, int(8.5 * s), int(9.5 * s))
    assert out["busy_s"] == 0.0 and out["idle_s"] == 1.0
    assert out["by"]["no_rider"] == 1.0
    assert sum(out["by"].values()) == 1.0
    out = runner_idle_by(rows, int(3.2 * s), int(3.8 * s))
    assert out["busy_s"] == pytest.approx(0.6) and out["idle_s"] == 0.0
    assert not any(out["by"].values())
    # an empty timeline, an empty window
    assert runner_idle_by([], 0, s)["by"]["no_rider"] == 1.0
    assert runner_idle_by(rows, s, s) == {
        "busy_s": 0.0, "idle_s": 0.0,
        "by": dict.fromkeys(out["by"], 0.0)}


def test_a_stage_keeps_its_interval_only_while_a_window_is_open():
    assert telemetry._TIMELINE is None
    t0 = totals()
    telemetry.stage_record("t_closed", 5)
    telemetry.timeline_arm()
    try:
        before = time.monotonic_ns()
        telemetry.stage_record("t_now", 7)
        after = time.monotonic_ns()
        telemetry.stage_record("t_then", 30, end_ns=1000)
    finally:
        rows = telemetry.timeline_disarm()
    telemetry.stage_record("t_closed", 5)
    assert telemetry._TIMELINE is None and telemetry.timeline_disarm() == []
    me = threading.get_ident()
    assert [r[:2] for r in rows] == [("t_now", me), ("t_then", me)]
    assert rows[1][2:] == (970, 1000)
    assert rows[0][3] - rows[0][2] == 7 and before <= rows[0][3] <= after
    # the sums are what they would be with no window
    assert added(t0, "t_then") == (1, 30) and added(t0, "t_now") == (1, 7)
    assert added(t0, "t_closed") == (2, 10)


# -- `txn_open` by lock ------------------------------------------------------------


@pytest.mark.parametrize("stage, other", [
    ("txn_lock_ds", "txn_lock_store"),
    ("txn_lock_store", "txn_lock_ds"),
])
def test_a_transactions_wait_for_each_mutex_is_its_own_stage(
        monkeypatch, stage, other):
    """Another thread holds the mutex and, once the opener has read
    its clock and gone for the lock, moves that clock 5 ms on and lets
    go: the stage of that mutex reads the 5 ms, the other's nothing."""
    import surrealdb_tpu.kvs.ds as D
    import surrealdb_tpu.kvs.mem as M
    from surrealdb_tpu import Datastore

    ds = Datastore("pymem")
    lock = ds.lock if stage == "txn_lock_ds" else ds.backend.vs.lock
    now, opener_read = [10 ** 12], threading.Event()

    def clock():
        if threading.current_thread().name == "opener":
            opener_read.set()
        return now[0]

    fake = types.SimpleNamespace(**vars(time))
    fake.monotonic_ns = clock
    monkeypatch.setattr(D, "time", fake)
    monkeypatch.setattr(M, "time", fake)
    # nobody holds either: no wait
    t0 = totals()
    ds.transaction(write=False).cancel()
    assert added(t0, stage) == (1, 0) and added(t0, other) == (1, 0)
    t0 = totals()
    opened = []
    opener = threading.Thread(
        target=lambda: opened.append(ds.transaction(write=False)),
        name="opener", daemon=True)
    with lock:
        opener.start()
        assert opener_read.wait(10)
        now[0] += 5_000_000
    opener.join(10)
    assert opened and not opener.is_alive()
    opened[0].cancel()
    assert added(t0, stage) == (1, 5_000_000)
    assert added(t0, other) == (1, 0)


def test_the_native_store_times_its_snapshot_call():
    from surrealdb_tpu import Datastore
    from surrealdb_tpu.native import available

    if not available():
        pytest.skip("no native memtable here")
    ds = Datastore("memory")
    t0 = totals()
    ds.transaction(write=False).cancel()
    (count, ns), (ds_count, _ns) = added(t0, "txn_lock_store"), \
        added(t0, "txn_lock_ds")
    assert count == ds_count == 1 and 0 < ns < 1e9


# -- the server edge --------------------------------------------------------------


def test_request_carries_cpu_time_and_no_other_stage_does(ds):
    from surrealdb_tpu.server import make_server

    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        t0 = totals()
        cpu0 = (telemetry.stage_snapshot().get("request") or {}).get(
            "cpu_ms", 0.0)
        for _ in range(3):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.server_address[1]}/sql",
                data=b"RETURN 1 + 1", method="POST",
                headers={"surreal-ns": "t", "surreal-db": "t",
                         "Accept": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                assert json.loads(r.read())[0]["result"] == 2
        # the stage closes after the reply is written: the last record
        # may land a moment after its client has read the answer
        end = time.monotonic() + 5
        while added(t0, "request")[0] < 3 and time.monotonic() < end:
            time.sleep(0.005)
        snap = telemetry.stage_snapshot()
        count, ns = added(t0, "request")
        assert count == 3
        # `request` contains the admission wait and the statement
        inner = sum(added(t0, p)[1] for p in
                    ("admission_wait", "parse", "stmt_envelope",
                     "stmt_eval"))
        assert 0 < inner <= ns
        cpu_ms = snap["request"]["cpu_ms"] - cpu0
        assert 0 < cpu_ms <= ns / 1e6 + 3 * 0.01  # clock resolution
        # ... and the reply's encoding, once a reply (wall time only: a
        # read of the thread's CPU clock is a system call)
        enc_count, enc_ns = added(t0, "reply_encode")
        assert enc_count == 3 and 0 < enc_ns <= ns
        assert [k for k, v in snap.items() if "cpu_ms" in v] == ["request"]
        assert all("last_us" not in v for v in snap.values())
        # /metrics and INFO FOR SYSTEM read the same table
        info = ds.query("INFO FOR SYSTEM", ns="t", db="t")[0]
        assert info["stages"]["request"]["count"] >= 3
        assert 0 < info["cpu_usage"] < (info["available_parallelism"] + 1)
    finally:
        srv.shutdown()
        srv.server_close()
