"""The stall watch (server/stallwatch.py): what a wake costs, stage
`gil_wake`, and the moments when queries are open and none finishes: one
dump a stall at `/telemetry/stalls`, stage `request_stall` with the
silent span. The tests drive `tick` with their own clock; only the last
one lets the thread run."""

import json
import sys
import threading
import time
import urllib.request

import pytest

from surrealdb_tpu import Datastore, telemetry
from surrealdb_tpu.server import make_server
from surrealdb_tpu.server.stallwatch import STALL_S, TICK_S, StallWatch

TICK = int(TICK_S * 1e9)
STALL_TICKS = round(STALL_S / TICK_S)


def totals() -> dict:
    return {k: (v.count, v.total_ns)
            for k, v in list(telemetry._STAGES.items())}


def added(before: dict, name: str) -> tuple:
    c0, n0 = before.get(name, (0, 0))
    c1, n1 = totals().get(name, (0, 0))
    return c1 - c0, n1 - n0


def _until(cond, seconds=10.0):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.001)
    return cond()


class Driven:
    """A watch nobody started, and the clock its ticks are given."""

    def __init__(self, ds):
        self.ds = ds
        self.now = 10 ** 12
        self.watch = StallWatch(ds, start=False)

    def tick(self, ticks: int = 1, late_ns: int = 0, cpu_ns: int = 0):
        for _ in range(ticks):
            self.now += TICK + late_ns
            self.watch.tick(self.now, late_ns, cpu_ns)

    def stalls(self) -> list:
        return self.ds.telemetry.recent_stalls()


def test_no_dump_while_queries_finish():
    ds = Datastore("memory")
    d = Driven(ds)
    t0 = totals()
    h = ds.inflight.open("t", "t", "SELECT 1")
    for _ in range(3 * STALL_TICKS):
        d.tick()
        # between any two ticks one query ends and the next is open
        ds.inflight.close(h)
        h = ds.inflight.open("t", "t", "SELECT 1")
    assert d.stalls() == [] and added(t0, "request_stall") == (0, 0)
    assert added(t0, "gil_wake")[0] >= 3 * STALL_TICKS


@pytest.mark.parametrize("n_open", [1, 3])
def test_one_dump_a_stall_and_the_span_once_a_query_finishes(n_open,
                                                            capfd):
    """`n_open` 1: a lone slow query is silent too, and counts."""
    ds = Datastore("memory")
    d = Driven(ds)
    handles = [ds.inflight.open("t", "t", f"SELECT {i}")
               for i in range(n_open)]
    t0 = totals()
    d.tick()                            # the silence is first seen here
    d.tick(STALL_TICKS - 1, late_ns=2_000_000, cpu_ns=1_000_000)
    assert d.stalls() == []
    d.tick(late_ns=3_000_000)           # STALL_S of it: the one dump
    d.tick(2 * STALL_TICKS)             # and no second one
    (dump,) = d.stalls()
    late = (STALL_TICKS - 1) * 2_000_000 + 3_000_000
    assert dump["silent_s"] == pytest.approx(STALL_S + late / 1e9)
    assert dump["open"] == n_open and dump["finished"] == 0
    assert dump["kind"] == "silent" and dump["ticks"] == STALL_TICKS + 1
    assert dump["late_ms"] == {"sum": late / 1e6, "max": 3.0}
    assert dump["cpu_ms"] == STALL_TICKS - 1.0
    assert dump["device"] == {"state": dump["device"]["state"],
                              "pending_calls": 0}
    me = next(t for t in dump["threads"]
              if t["id"] == threading.get_ident())
    assert me["name"] == threading.current_thread().name
    assert 1 <= len(me["frames"]) <= 8
    assert any("test_stall_watch.py" in f and " tick" in f
               for f in me["frames"])
    err = capfd.readouterr().err
    assert err.count("[surrealdb-tpu] stall (silent):") == 1
    assert f"{n_open} queries open and none finished" in err
    assert added(t0, "request_stall") == (0, 0)
    ds.inflight.close(handles.pop())
    d.tick()
    ticks = 1 + 3 * STALL_TICKS
    assert added(t0, "request_stall") == (1, ticks * TICK + late)
    # what is still open starts a new silence from here
    d.tick(STALL_TICKS - 1)
    assert len(d.stalls()) == 1
    d.tick()
    assert len(d.stalls()) == (2 if handles else 1)
    assert added(t0, "request_stall")[0] == 1


def test_an_idle_server_never_stalls():
    ds = Datastore("memory")
    d = Driven(ds)
    t0 = totals()
    d.tick(4 * STALL_TICKS, late_ns=60_000)
    # queries that come and go between two ticks are no silence either
    ds.execute("RETURN 1", ns="t", db="t")
    d.tick(4 * STALL_TICKS, late_ns=60_000)
    assert d.stalls() == [] and added(t0, "request_stall") == (0, 0)
    # every tick reads what its wake cost (the table is the process's:
    # a server some other test left running ticks into it too)
    count, ns = added(t0, "gil_wake")
    assert count >= 8 * STALL_TICKS and ns >= 8 * STALL_TICKS * 60_000


def test_a_tick_the_watch_could_not_make_is_a_stall_after_the_fact():
    """The whole process stood still (or one thread kept the
    interpreter): no tick ran to see it, and by the next one the query
    has finished. Its lateness is the silence."""
    ds = Datastore("memory")
    d = Driven(ds)
    h = ds.inflight.open("t", "t", "SELECT 1")
    d.tick()
    t0 = totals()
    ds.inflight.close(h)
    h = ds.inflight.open("t", "t", "SELECT 2")
    d.tick(late_ns=2_900_000_000, cpu_ns=40_000_000)
    (dump,) = d.stalls()
    assert dump["kind"] == "late_tick"
    assert dump["silent_s"] == pytest.approx(2.9 + TICK_S)
    assert dump["late_ms"]["max"] == 2900.0 and dump["cpu_ms"] == 40.0
    assert added(t0, "request_stall") == (1, 2_900_000_000 + TICK)
    # less than a stall late, or nobody there to wait: nothing
    ds.inflight.close(h)
    d.tick(late_ns=400_000_000)
    d.tick(3, late_ns=2_900_000_000)
    assert len(d.stalls()) == 1 and added(t0, "request_stall")[0] == 1
    # queries open all through it: the late tick is inside their silence
    h = ds.inflight.open("t", "t", "SELECT 3")
    d.tick(late_ns=2_900_000_000)
    assert [s["kind"] for s in d.stalls()] == ["late_tick", "silent"]
    assert d.stalls()[1]["silent_s"] == pytest.approx(2.9)
    ds.inflight.close(h)
    d.tick()
    assert added(t0, "request_stall")[0] == 2


def test_the_ring_keeps_the_last_four_dumps():
    ds = Datastore("memory")
    d = Driven(ds)
    for i in range(6):
        h = ds.inflight.open("t", "t", f"SELECT {i}")
        d.tick(STALL_TICKS + 1)
        ds.inflight.close(h)
        d.tick()
    assert [s["finished"] for s in d.stalls()] == [2, 3, 4, 5]


def test_a_planted_stall_is_dumped_with_the_holders_frame():
    """Open queries parked behind a held `Datastore.lock`: the dump at
    `/telemetry/stalls` names the holder's frame and the parked
    threads', and `request_stall` holds the span once they finish."""
    ds = Datastore("memory")
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
    srv.stalls.close()  # this test's own watch, on its own clock
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    d = Driven(ds)
    holding, release = threading.Event(), threading.Event()

    def hold_the_datastore_lock():
        with ds.lock:
            holding.set()
            release.wait(30)

    def ask(out):
        req = urllib.request.Request(
            base + "/sql", data=b"RETURN 7", method="POST",
            headers={"surreal-ns": "t", "surreal-db": "t",
                     "Accept": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out.append(json.loads(r.read())[0]["result"])

    answers = []
    holder = threading.Thread(target=hold_the_datastore_lock,
                              name="the-holder", daemon=True)
    clients = [threading.Thread(target=ask, args=(answers,), daemon=True)
               for _ in range(3)]
    ask(answers)  # the namespace's first use writes: once, and alone
    assert answers.pop() == 7
    try:
        holder.start()
        assert holding.wait(10)
        for c in clients:
            c.start()
        # registered, and at the datastore's door
        assert _until(lambda: ds.inflight.count() == 3 and sum(
            f.f_code.co_filename.endswith("surrealdb_tpu/kvs/ds.py")
            for f in sys._current_frames().values()) == 3)
        t0 = totals()
        d.tick(STALL_TICKS + 1)
        # the endpoint answers while every query is parked
        with urllib.request.urlopen(base + "/telemetry/stalls",
                                    timeout=10) as r:
            (dump,) = json.loads(r.read())
        assert dump["open"] == 3 and dump["silent_s"] == STALL_S
        by_name = {t["name"]: t["frames"] for t in dump["threads"]}
        assert any("hold_the_datastore_lock" in f
                   for f in by_name["the-holder"])
        # the parked ones' top frame: the datastore, asking for its lock
        parked = [fr for fr in by_name.values()
                  if "surrealdb_tpu/kvs/ds.py" in fr[0]]
        assert len(parked) == 3
        assert answers == [] and added(t0, "request_stall") == (0, 0)
    finally:
        release.set()
    for t in [holder] + clients:
        t.join(10)
    assert answers == [7, 7, 7] and _until(
        lambda: ds.inflight.count() == 0)
    d.tick()
    assert added(t0, "request_stall") == (1, (STALL_TICKS + 1) * TICK)
    srv.shutdown()
    srv.server_close()


def test_the_watch_starts_and_ends_with_the_server():
    def watchers():
        return {t for t in threading.enumerate()
                if t.name == "surreal-stall-watch"}

    # by identity, not by count: another test's watch may still be on
    # its way out
    before = watchers()
    ds = Datastore("memory")  # an embedded datastore starts no thread
    ds.execute("RETURN 1", ns="t", db="t")
    assert watchers() == before
    t0 = totals()
    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True)
    mine = watchers() - before
    assert len(mine) == 1 and all(t.daemon for t in mine)
    # it ticks on its own: a wake's lateness, never negative
    assert _until(lambda: added(t0, "gil_wake")[0] >= 2)
    assert added(t0, "gil_wake")[1] >= 0
    srv.server_close()
    srv.server_close()
    assert _until(lambda: not mine & watchers())
