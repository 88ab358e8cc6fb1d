"""Live-query fan-out spine tests (server/fanout.py).

The push-path robustness contract: commit latency decoupled from
consumer speed by construction (bounded per-session outboxes drained by
dedicated writers), slow-consumer policy (typed OVERFLOW or forced
disconnect), post-commit dispatch with exactly-once commit-order
delivery, eval-error poisoning that never fails the write, disconnect
GC of leaked subscriptions, drain flush, and the deterministic
simulator's delivery invariant with its bug-finding seeds pinned.
"""

import os
import threading
import time

import pytest
from live_soak import _SoakWs, live_soak

from surrealdb_tpu import cnf


def _flush(ds, timeout=5.0):
    assert ds.fanout.flush(timeout), "dispatch backlog failed to drain"


def _wait(pred, timeout=5.0, every=0.01):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(every)
    return pred()


def _live(ds, sql, ns="test", db="test"):
    out = ds.execute(sql, ns=ns, db=db)
    assert out[-1].error is None, out[-1].error
    return str(out[-1].result.u)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_subscription_registry_index():
    from surrealdb_tpu.catalog import SubscriptionDef
    from surrealdb_tpu.server.fanout import SubscriptionRegistry

    reg = SubscriptionRegistry()
    a = SubscriptionDef(id="a", ns="n", db="d", tb="t1")
    b = SubscriptionDef(id="b", ns="n", db="d", tb="t1")
    c = SubscriptionDef(id="c", ns="n", db="d", tb="t2")
    reg["a"], reg["b"], reg["c"] = a, b, c
    assert len(reg) == 3 and "a" in reg and reg.get("c") is c
    assert reg.count_for("n", "d", "t1") == 2
    assert reg.count_for("n", "d", "t2") == 1
    assert reg.count_for("n", "d", "zz") == 0
    assert {s.id for s in reg.for_table("n", "d", "t1")} == {"a", "b"}
    assert reg.pop("a") is a and reg.pop("a") is None
    assert reg.count_for("n", "d", "t1") == 1
    # registration stamps the watermark (no history replay)
    assert b._fanout_seq > 0
    reg.clear()
    assert len(reg) == 0 and reg.count_for("n", "d", "t2") == 0


# ---------------------------------------------------------------------------
# embedded delivery semantics (post-commit dispatch)
# ---------------------------------------------------------------------------


def test_commit_order_exactly_once(ds):
    got = []
    ds.notification_handlers.append(got.append)
    lid = _live(ds, "LIVE SELECT * FROM ord")
    for i in range(25):
        ds.query(f"CREATE ord:{i} SET v = {i}")
    _flush(ds)
    notes = [n for n in got if n.live_id == lid]
    assert [n.result["v"] for n in notes] == list(range(25))
    assert all(n.action == "CREATE" for n in notes)


def test_sub_registered_mid_transaction_receives_commit(ds):
    """The watermark is stamped at COMMIT, not capture: a subscription
    registered while the writing transaction is still open receives the
    event — it committed after the registration existed. (Capture still
    gates on the registry at WRITE time, like the reference's
    write-time matching, so a pre-existing subscription covers the
    table here.)"""
    got = []
    ds.notification_handlers.append(got.append)
    pre = _live(ds, "LIVE SELECT * FROM mid")
    out = ds.execute(
        "BEGIN; CREATE mid:1 SET v = 1; LIVE SELECT * FROM mid; COMMIT;",
        ns="test", db="test",
    )
    assert all(r.error is None for r in out), [r.error for r in out]
    mid = str(out[2].result.u)
    _flush(ds)
    assert _wait(lambda: len(got) == 2), (
        f"commit after mid-txn subscription was silently skipped: "
        f"{[(n.live_id == pre, n.action) for n in got]}"
    )
    assert {n.live_id for n in got} == {pre, mid}
    assert all(n.action == "CREATE" and n.result["v"] == 1 for n in got)


def test_live_binds_outbox_atomically(ds):
    """Routing binds inside the LIVE statement itself (via
    session.live_outbox) — binding later at the rpc layer would leave a
    window where dispatch matches the sub but finds no route."""
    from surrealdb_tpu.kvs.ds import Session

    ob = ds.fanout.register_session(lambda notes: None)
    sess = Session(ns="test", db="test", auth_level="owner")
    sess.live_outbox = ob
    out = ds.execute("LIVE SELECT * FROM ab", session=sess)
    lid = str(out[-1].result.u)
    assert lid in ob.lids
    assert ds.fanout._routes.get(lid) is ob
    ds.fanout.close_all()


def test_cancelled_and_failed_txns_never_notify(ds):
    got = []
    ds.notification_handlers.append(got.append)
    _live(ds, "LIVE SELECT * FROM ctx")
    ds.execute("BEGIN; CREATE ctx:a SET v = 1; CANCEL;",
               ns="test", db="test")
    ds.execute("BEGIN; CREATE ctx:b SET v = 2; THROW 'boom'; COMMIT;",
               ns="test", db="test")
    ds.query("CREATE ctx:c SET v = 3")
    _flush(ds)
    assert [n.result["v"] for n in got] == [3], (
        "uncommitted mutations leaked to subscribers"
    )


def test_kill_stops_delivery_fast(ds):
    got = []
    ds.notification_handlers.append(got.append)
    lid = _live(ds, "LIVE SELECT * FROM klt")
    ds.query("CREATE klt:1 SET v = 1")
    _flush(ds)
    assert _wait(lambda: len(got) == 1)
    t0 = time.monotonic()
    out = ds.execute("KILL $id", ns="test", db="test", vars={"id": lid})
    kill_ms = (time.monotonic() - t0) * 1000
    assert out[-1].error is None
    assert kill_ms < 250, f"KILL took {kill_ms:.0f}ms"
    ds.query("CREATE klt:2 SET v = 2")
    _flush(ds)
    time.sleep(0.05)
    assert len(got) == 1, "killed live query still delivered"
    assert lid not in ds.live_queries


def test_eval_error_poisons_only_that_subscription(ds):
    got = []
    ds.notification_handlers.append(got.append)
    good = _live(ds, "LIVE SELECT * FROM psn")
    bad = _live(ds, "LIVE SELECT * FROM psn WHERE string::len(v) > 0")
    out = ds.execute("CREATE psn:1 SET v = 7", ns="test", db="test")
    assert out[-1].error is None, "eval error must NEVER fail the write"
    _flush(ds)
    assert _wait(lambda: len(got) >= 2)
    by_lid = {}
    for n in got:
        by_lid.setdefault(n.live_id, []).append(n)
    assert [n.action for n in by_lid[good]] == ["CREATE"]
    assert [n.action for n in by_lid[bad]] == ["ERROR"]
    assert "string::len" in str(by_lid[bad][0].result)
    assert ds.telemetry.get("live_eval_errors") == 1
    assert bad not in ds.live_queries and good in ds.live_queries
    # the healthy subscription keeps flowing
    ds.query("CREATE psn:2 SET v = 8")
    _flush(ds)
    assert _wait(lambda: len(by_lid[good]) == 2 or
                 sum(1 for n in got if n.live_id == good) == 2)


def test_notifications_buffer_bounded(ds, monkeypatch):
    monkeypatch.setattr(cnf, "NOTIFY_BUFFER_CAP", 5)
    _live(ds, "LIVE SELECT * FROM cap")
    for i in range(20):
        ds.query(f"CREATE cap:{i}")
    _flush(ds)
    assert len(ds.notifications) <= 5
    assert ds.telemetry.get("notifications_dropped") >= 15
    # draining resets the window
    ds.drain_notifications()
    ds.query("CREATE cap:zz")
    _flush(ds)
    assert len(ds.notifications) == 1


# ---------------------------------------------------------------------------
# outbox overflow policy (hub level)
# ---------------------------------------------------------------------------


def _frozen_session(ds, depth, policy=None, close_conn=None):
    got, gate = [], threading.Event()

    def send(notes):
        gate.wait(10)
        got.extend(notes)

    ob = ds.fanout.register_session(send, depth=depth, policy=policy,
                                    close_conn=close_conn)
    return ob, got, gate


def test_overflow_notify_policy(ds):
    ob, got, gate = _frozen_session(ds, depth=4)
    lid = _live(ds, "LIVE SELECT * FROM ovn")
    ds.fanout.bind(lid, ob)
    for i in range(30):
        ds.query(f"CREATE ovn:{i} SET v = {i}")
    _flush(ds)
    assert ds.telemetry.get("live_overflows") >= 1
    assert ob.dropped > 0 and not ob.closed
    gate.set()
    assert _wait(lambda: ob.queue_len() == 0)
    actions = [n.action for n in got]
    assert "OVERFLOW" in actions
    over = next(n for n in got if n.action == "OVERFLOW")
    assert over.live_id == lid and over.result["dropped"] > 0
    # the laggard recovered: fresh writes flow again
    n0 = len(got)
    ds.query("CREATE ovn:zz SET v = 99")
    _flush(ds)
    assert _wait(lambda: len(got) > n0)
    assert got[-1].action == "CREATE" and got[-1].result["v"] == 99


def test_overflow_disconnect_policy(ds):
    kicked = threading.Event()
    ob, _got, gate = _frozen_session(
        ds, depth=4, policy="disconnect", close_conn=kicked.set
    )
    lid = _live(ds, "LIVE SELECT * FROM ovd")
    ds.fanout.bind(lid, ob)
    for i in range(30):
        ds.query(f"CREATE ovd:{i}")
    _flush(ds)
    assert kicked.wait(5), "laggard was never kicked"
    assert ob.closed
    assert ds.telemetry.get("live_overflow_disconnects") >= 1
    gate.set()


def test_error_tombstone_survives_overflow(ds):
    """A poisoned subscription's typed ERROR must not vanish into a
    later queue reset (found by run_live_sim seed 7)."""
    ob, got, gate = _frozen_session(ds, depth=4)
    bad = _live(ds, "LIVE SELECT * FROM tmb WHERE string::len(v) > 0")
    good = _live(ds, "LIVE SELECT * FROM tmb")
    ds.fanout.bind(bad, ob)
    ds.fanout.bind(good, ob)
    for i in range(30):
        ds.query(f"CREATE tmb:{i} SET v = {i}")
    _flush(ds)
    gate.set()
    assert _wait(lambda: ob.queue_len() == 0)
    assert any(n.action == "ERROR" and n.live_id == bad for n in got), (
        "poison tombstone was dropped by the overflow reset"
    )


def test_drain_flushes_pending_deliveries(ds):
    got = []

    def slow_send(notes):
        time.sleep(0.01)
        got.extend(notes)

    ob = ds.fanout.register_session(slow_send, depth=512)
    lid = _live(ds, "LIVE SELECT * FROM drn")
    ds.fanout.bind(lid, ob)
    for i in range(40):
        ds.query(f"CREATE drn:{i} SET v = {i}")
    assert ds.fanout.drain(timeout=10)
    assert _wait(lambda: len(got) == 40), (
        f"drain lost queued notifications ({len(got)}/40)"
    )
    assert ob.closed
    ob.join()
    ds.fanout.close_all()


# ---------------------------------------------------------------------------
# real sockets: decoupling, overflow, disconnect GC
# ---------------------------------------------------------------------------


def test_frozen_consumer_does_not_stall_writers():
    """The acceptance criterion: a WS consumer whose socket is frozen
    mid-stream never holds a writer up. Pre-spine, the first full TCP
    buffer stalled every write transaction on the node forever. Shown
    by counts that no load on the machine moves: every commit returned;
    no notification was written to a socket on a committing thread;
    the frozen session's queue stayed within its depth, and every
    notification routed to it was sent, is still queued, or was
    dropped by a typed overflow."""
    r = live_soak(sessions=1, frozen=1, writers=2, writes=600,
                  depth=64, payload_pad=8192, settle_s=0.5)
    assert r["writers_stalled"] == 0 and r["commits"] == r["writes"] == 600, (
        f"writes stalled behind a frozen consumer: {r}"
    )
    assert r["sends_on_writer_threads"] == 0, (
        f"a commit wrote notifications to a consumer's socket: {r}"
    )
    assert r["frozen_queue_max"] <= 64, r
    assert r["frozen_unaccounted"] == 0, (
        f"notifications lost without a typed overflow: {r}"
    )


@pytest.mark.parametrize("shape", [
    dict(sessions=4, frozen=0, writers=4, writes=200, settle_s=10.0),
    # the conformance gate's live smoke: seven readers beside one
    # frozen session at depth 64
    dict(sessions=8, frozen=1, writers=2, writes=200, depth=64,
         settle_s=12.0),
], ids=["4-live", "7-live-1-frozen"])
def test_ws_exactly_once_commit_order(shape):
    r = live_soak(**shape)
    assert r["commits"] == r["writes"], r
    assert r["per_session_complete"] == r["sessions"] - r["frozen"], r
    assert r["order_violations"] == 0, r
    assert r["live_sessions_end"] == 0, "disconnect GC leaked subs"


def test_ws_frozen_socket_overflow_resolves():
    """A genuinely frozen socket (tiny receive buffer, consumer never
    reads) must resolve per policy once kernel buffers fill: typed
    overflow + bounded queue, writers untouched."""
    r = live_soak(sessions=2, frozen=1, writers=2, writes=900,
                  depth=16, payload_pad=8192, settle_s=10.0)
    assert r["overflows"] >= 1, (
        f"frozen socket never tripped the overflow policy: {r}"
    )
    # at depth 16 with 8KB payloads even the live reader may take an
    # honest overflow notice — what may NOT happen is reordering,
    # silent loss (sent + dropped + queued accounts for every note), or
    # a stalled writer
    assert r["order_violations"] == 0
    assert r["delivered"] > 0
    assert r["frozen_queue_max"] <= 16 and r["frozen_unaccounted"] == 0, r
    assert r["writers_stalled"] == 0 and r["commits"] == r["writes"], r
    assert r["sends_on_writer_threads"] == 0, r


def test_disconnect_gc_and_sweep(ds):
    """A WS session dying without KILL leaves no live queries behind:
    the session-close path GCs immediately; the periodic sweep is the
    backstop for an outbox that closed without its session unwinding."""
    from surrealdb_tpu import key as K
    from surrealdb_tpu.server import make_server

    srv = make_server(ds, "127.0.0.1", 0, unauthenticated=True,
                      max_inflight=0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        c = _SoakWs(port)
        c.call("use", ["test", "test"])
        c.call("live", ["gone"])
        assert len(ds.live_queries) == 1
        c.close()  # dies without KILL
        assert _wait(lambda: len(ds.live_queries) == 0), (
            "session close leaked its live query"
        )
        txn = ds.transaction(write=False)
        rows = list(txn.scan(*K.prefix_range(
            K.lq_prefix("test", "test", "gone"))))
        txn.cancel()
        assert rows == [], "persisted !lq row leaked"
    finally:
        srv.shutdown()
    # the sweep backstop: a bound outbox that closed non-gracefully
    got = []
    ob = ds.fanout.register_session(got.extend)
    lid = _live(ds, "LIVE SELECT * FROM swp")
    ds.fanout.bind(lid, ob)
    ob.cancel.set()  # simulate a hard death (no unregister ran)
    assert ds.fanout.sweep_dead_sessions() == 1
    assert lid not in ds.live_queries


def test_sweep_tick_returns_none(ds):
    """Runtime.every treats a NUMERIC tick return as the next delay:
    a tick that leaks its count would spin the sweep loop hot at
    delay=0 (regression: this starved the sim kernel suite-wide)."""
    captured = {}

    class FakeRuntime:
        def every(self, interval, tick, name="t", immediate=False):
            captured["tick"] = tick

            class H:
                def cancel(self):
                    pass
            return H()

    ds.fanout._runtime = FakeRuntime()
    ds.fanout.register_session(lambda notes: None)
    assert captured["tick"]() is None
    ds.fanout.close_all()


# ---------------------------------------------------------------------------
# changefeed GC scheduling
# ---------------------------------------------------------------------------


def test_changefeed_gc_purges_and_counts(ds):
    from surrealdb_tpu import key as K
    from surrealdb_tpu.cf import run_changefeed_gc

    ds.query("DEFINE TABLE cft CHANGEFEED 1s")
    for i in range(5):
        ds.query(f"CREATE cft:{i} SET v = {i}")
    beg, end = K.prefix_range(K.changefeed_prefix("test", "test"))
    txn = ds.transaction(write=False)
    n0 = len(list(txn.scan(beg, end)))
    txn.cancel()
    assert n0 >= 5
    assert run_changefeed_gc(ds) == 0  # nothing old enough yet
    time.sleep(1.2)
    purged = run_changefeed_gc(ds)
    assert purged >= 5
    assert ds.telemetry.get("changefeed_gc_purged") == purged
    txn = ds.transaction(write=False)
    n1 = len(list(txn.scan(beg, end)))
    txn.cancel()
    assert n1 == n0 - purged


def test_changefeed_gc_tick_rides_task_lease(ds):
    from surrealdb_tpu.cf import changefeed_gc_tick

    ds.query("DEFINE TABLE cfl CHANGEFEED 1s")
    ds.query("CREATE cfl:1")
    time.sleep(1.1)
    assert changefeed_gc_tick(ds) >= 1  # this node wins the lease
    # immediately again: lease held by us, so it still runs (renewal)
    assert changefeed_gc_tick(ds) == 0  # nothing left to purge


# ---------------------------------------------------------------------------
# deterministic simulation: the delivery invariant
# ---------------------------------------------------------------------------

# seeds that found real protocol bugs during development, pinned:
# 1, 2 — a subscription registered between an event's commit and its
#        async dispatch received history (fixed: registration/capture
#        watermark); 7 — a poisoned subscription's typed ERROR was
#        dropped by a later queue-overflow reset (fixed: tombstones
#        survive the reset); 5 — poison sub with an empty event window
#        (checker soundness).
LIVE_SIM_SEEDS = [1, 2, 5, 7, 11, 23, 42]


@pytest.mark.parametrize("seed", LIVE_SIM_SEEDS)
def test_live_sim_seed(seed):
    from surrealdb_tpu.sim.harness import run_live_sim

    r = run_live_sim(seed)
    assert r.ok, f"{r.summary()}\n" + "\n".join(
        r.violations[:5] + r.errors[:5]
    )
    assert r.stats["commits"] > 0 and r.stats["delivered"] > 0


def test_live_sim_reproducible():
    from surrealdb_tpu.sim.harness import run_live_sim

    a, b = run_live_sim(3), run_live_sim(3)
    assert a.trace_digest == b.trace_digest
    assert a.store_digest == b.store_digest


@pytest.mark.slow
def test_live_sim_sweep():
    from surrealdb_tpu.sim.harness import run_live_sim

    for seed in range(100, 160):
        r = run_live_sim(seed)
        assert r.ok, f"{r.summary()}\n" + "\n".join(r.violations[:5])


# ---------------------------------------------------------------------------
# static rule 7 (check_robustness)
# ---------------------------------------------------------------------------


def _load_checker():
    import importlib.util

    root = os.path.join(os.path.dirname(__file__), "..")
    spec = importlib.util.spec_from_file_location(
        "check_robustness", os.path.join(root, "tools",
                                         "check_robustness.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rule7_clean_on_repo():
    mod = _load_checker()
    root = os.path.join(os.path.dirname(__file__), "..")
    assert mod.scan(root) == []


def test_rule7_fires_on_violations(tmp_path):
    mod = _load_checker()
    bad = tmp_path / "ds.py"
    bad.write_text(
        "class Datastore:\n"
        "    def notify(self, n):\n"
        "        with self.lock:\n"
        "            for h in self.handlers:\n"
        "                h(n)\n"
        "            self.sock.sendall(b'x')\n"
    )
    findings = mod.check_file(str(bad), "surrealdb_tpu/kvs/ds.py")
    assert any("sendall" in f for f in findings)
    assert any("under a lock" in f for f in findings)
    # a rename must not silently retire the rule
    gone = tmp_path / "empty.py"
    gone.write_text("x = 1\n")
    findings = mod.check_file(str(gone), "surrealdb_tpu/kvs/ds.py")
    assert any("not found" in f for f in findings)
