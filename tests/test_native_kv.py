"""Native C++ memtable engine: contract parity with the Python engine."""

import ctypes
import os
import subprocess
import threading
import time

import pytest

from surrealdb_tpu.native import available


pytestmark = pytest.mark.skipif(not available(), reason="no g++ toolchain")


def test_native_available():
    assert available()


def test_basic_ops():
    from surrealdb_tpu.kvs.native_mem import NativeMemBackend

    b = NativeMemBackend()
    tx = b.transaction(write=True)
    tx.set(b"a", b"1")
    tx.set(b"b", b"2")
    tx.set(b"c", b"3")
    tx.delete(b"b")
    assert tx.get(b"a") == b"1"
    assert tx.get(b"b") is None
    tx.commit()
    tx = b.transaction(write=False)
    assert [k for k, _ in tx.scan(b"a", b"z")] == [b"a", b"c"]
    assert [k for k, _ in tx.scan(b"a", b"z", reverse=True)] == [b"c", b"a"]
    assert tx.count(b"a", b"z") == 2
    tx.cancel()


def test_rollback_and_savepoints():
    from surrealdb_tpu.kvs.native_mem import NativeMemBackend

    b = NativeMemBackend()
    tx = b.transaction(write=True)
    tx.set(b"x", b"1")
    tx.new_save_point()
    tx.set(b"y", b"2")
    tx.rollback_to_save_point()
    tx.commit()
    tx = b.transaction(write=False)
    assert tx.get(b"x") == b"1"
    assert tx.get(b"y") is None
    tx.cancel()
    # cancelled txns leave no trace
    tx = b.transaction(write=True)
    tx.set(b"z", b"9")
    tx.cancel()
    tx = b.transaction(write=False)
    assert tx.get(b"z") is None
    tx.cancel()


def test_engine_parity_through_sql():
    """Same SQL workload on both engines produces identical results."""
    from surrealdb_tpu import Datastore

    work = (
        "DEFINE INDEX i ON t FIELDS n;"
        "CREATE t:1 SET n = 3; CREATE t:2 SET n = 1; CREATE t:3 SET n = 2;"
        "RELATE t:1->e->t:2;"
        "UPDATE t:2 SET n = 10;"
        "DELETE t:3;"
    )
    q = (
        "SELECT * FROM t ORDER BY n;"
        "SELECT id FROM t WHERE n = 10;"
        "RETURN t:1->e->t;"
        "SELECT count() FROM t GROUP ALL"
    )
    outs = []
    for path in ("memory", "pymem"):
        ds = Datastore(path)
        ds.execute(work, ns="p", db="p")
        outs.append([r.result for r in ds.execute(q, ns="p", db="p")])
    from surrealdb_tpu.val import render

    assert render(outs[0]) == render(outs[1])


def test_datastore_uses_native_by_default():
    from surrealdb_tpu import Datastore
    from surrealdb_tpu.kvs.native_mem import NativeMemBackend

    ds = Datastore("memory")
    assert isinstance(ds.backend, NativeMemBackend)


# -- the keeping binding and its try-lock entry points ----------------------
#
# `_try` twins run under the interpreter lock (ctypes.PyDLL) and take the
# store's mutex only when it is free; busy, they touch nothing and the
# blocking twin runs through the releasing binding (native/__init__.py).

def _commit(t, snap, items, entry):
    """Raw commit through `entry` (a bound ctypes function), releasing the
    snapshot as the transaction path does."""
    n = len(items)
    keys = (ctypes.c_char_p * n)(*[k for k, _v in items])
    klens = (ctypes.c_int64 * n)(*[len(k) for k, _v in items])
    vals = (ctypes.c_char_p * n)(*[v or b"" for _k, v in items])
    vlens = (ctypes.c_int64 * n)(
        *[-1 if v is None else len(v) for _k, v in items])
    return entry(t.h, snap, n, keys, klens, vals, vlens, 1)


def _get(t, key, snap, entry):
    out = ctypes.c_void_p()
    n = ctypes.c_int64()
    rc = entry(t.h, key, len(key), snap, ctypes.byref(out), ctypes.byref(n))
    if rc != 1:
        return rc, None
    val = ctypes.string_at(out.value, n.value)
    t.keep.sdb_buf_free(out)
    return rc, val


def _seeded():
    """A store with live keys, a tombstone and an older snapshot held."""
    from surrealdb_tpu.native import NativeMemtable

    t = NativeMemtable()
    t.commit_batch(t.snapshot(), [(b"a", b"1"), (b"b", b"2"), (b"c", b"")])
    old = t.snapshot()
    t.commit_batch(t.snapshot(), [(b"b", None), (b"a", b"1b")])
    return t, old


def _state(t):
    snap = t.snapshot()
    try:
        return list(t.scan_at(b"", b"\xff", snap))
    finally:
        t.release(snap)


_PARITY = {
    "snapshot": lambda t, old, e: e(t.h),
    "release": lambda t, old, e: (
        e(t.h, old), t.commit_batch(t.snapshot(), [(b"a", b"1c")]),
        _get(t, b"a", old, t.lib.sdb_get_at)),
    "get_present": lambda t, old, e: _get(t, b"a", t.snapshot(), e),
    "get_empty_value": lambda t, old, e: _get(t, b"c", t.snapshot(), e),
    "get_absent": lambda t, old, e: _get(t, b"zz", t.snapshot(), e),
    "get_tombstone": lambda t, old, e: _get(t, b"b", t.snapshot(), e),
    "get_at_old_snapshot": lambda t, old, e: _get(t, b"b", old, e),
    "commit_0": lambda t, old, e: _commit(t, t.snapshot(), [], e),
    "commit_1": lambda t, old, e: _commit(t, t.snapshot(), [(b"d", b"4")], e),
    "commit_64": lambda t, old, e: _commit(
        t, t.snapshot(),
        [(b"k%03d" % i, None if i % 7 == 0 else b"v%d" % i)
         for i in range(64)], e),
    "commit_conflict": lambda t, old, e: _commit(t, old, [(b"a", b"x")], e),
}
_TWINS = {
    "snapshot": "sdb_snapshot", "release": "sdb_snapshot_release",
    "get": "sdb_get_at", "commit": "sdb_commit_batch",
}


@pytest.mark.parametrize("case", sorted(_PARITY))
def test_try_entry_point_answers_as_its_blocking_twin(case):
    """Two stores in one state: the `_try` twin through the keeping binding
    on one, the blocking call through the releasing one on the other, give
    the same answer and leave the same state."""
    blocking = _TWINS[case.split("_")[0]]
    op = _PARITY[case]
    t1, old1 = _seeded()
    t2, old2 = _seeded()
    got = op(t1, old1, getattr(t1.keep, blocking + "_try"))
    want = op(t2, old2, getattr(t2.lib, blocking))
    if case == "release":  # the twins answer nothing / 0 = released
        assert got[0] == 0 and want[0] is None
        got, want = got[1:], want[1:]
    assert got == want
    assert _state(t1) == _state(t2)


def test_commits_past_64_keys_release_the_interpreter():
    """Up to KEEP_COMMIT_MAX keys a commit keeps the interpreter; one key
    more is a bulk commit and goes through the releasing binding."""
    from surrealdb_tpu.native import KEEP_COMMIT_MAX, NativeMemtable

    assert KEEP_COMMIT_MAX == 64
    t = NativeMemtable()
    called = []

    class Spy:
        def __init__(self, lib, tag):
            self._lib, self._tag = lib, tag

        def __getattr__(self, name):
            fn = getattr(self._lib, name)

            def call(*a):
                called.append((self._tag, name))
                return fn(*a)
            return call

    t.keep, t.lib = Spy(t.keep, "keep"), Spy(t.lib, "release")
    for n in (64, 65):
        snap = t.snapshot()
        called.clear()
        assert t.commit_batch(snap, [(b"n%d-%d" % (n, i), b"v")
                                     for i in range(n)])
        assert called == ([("keep", "sdb_commit_batch_try")] if n == 64
                          else [("release", "sdb_commit_batch")])
    assert len(_state(t)) == 129


def test_a_held_mutex_falls_back_to_the_blocking_call():
    """A thread holds the store's mutex again and again through a releasing
    `count_range_at` over 200,000 keys. Meanwhile every bounded call still
    answers right (the try finds the mutex held, the blocking twin waits for
    it with the interpreter given away), `kv_native_busy` rises, and no
    thread deadlocks: both are joined with a timeout of their own."""
    from surrealdb_tpu.native import NativeMemtable, kv_native_busy

    t = NativeMemtable()
    rows = 200_000
    for lo in range(0, rows, 50_000):
        t.commit_batch(t.snapshot(),
                       [(b"r%07d" % i, b"v%d" % i)
                        for i in range(lo, lo + 50_000)])
    stop = threading.Event()
    counted = []
    checked = []
    errors = []
    busy0 = kv_native_busy()

    def hold():
        try:
            while not stop.is_set():
                snap = t.snapshot()
                counted.append(t.count_range_at(b"r", b"s", snap))
                t.release(snap)
        except BaseException as e:  # reported by the test, not the thread
            errors.append(e)

    def bounded():
        deadline = time.monotonic() + 20
        i = 0
        try:
            while time.monotonic() < deadline and (
                    kv_native_busy() - busy0 < 20 or i < 200):
                snap = t.snapshot()
                key = b"r%07d" % (i * 7919 % rows)
                assert t.get_at(key, snap) == b"v%d" % (i * 7919 % rows)
                assert t.get_at(b"absent", snap) is None
                assert t.commit_batch(snap, [(b"w%d" % i, b"x")]) > 0
                checked.append(i)
                i += 1
        except BaseException as e:
            errors.append(e)

    holder = threading.Thread(target=hold, daemon=True)
    caller = threading.Thread(target=bounded, daemon=True)
    holder.start()
    caller.start()
    caller.join(timeout=60)
    stop.set()
    holder.join(timeout=60)
    assert not caller.is_alive() and not holder.is_alive()
    assert errors == []
    assert kv_native_busy() - busy0 >= 20
    assert len(checked) >= 200
    assert counted and set(counted) == {rows}
    assert t.count_range_at(b"w", b"x", t.snapshot()) == len(checked)


def test_a_library_without_the_try_entry_points_is_rebuilt(
        tmp_path, monkeypatch):
    """A prebuilt `_memtable.so` newer than its source but from before the
    try-lock ABI fails the symbol probe: `load()` closes it, rebuilds it
    from the source and binds the new one, both ways."""
    import surrealdb_tpu.native as native

    src = tmp_path / "memtable.cpp"
    src.write_text(open(native._SRC).read())
    stale = tmp_path / "stale.cpp"
    stale.write_text('extern "C" long sdb_scan_extract_f32() { return 0; }\n')
    so = tmp_path / "_memtable.so"
    subprocess.run(["g++", "-shared", "-fPIC", str(stale), "-o", str(so)],
                   check=True, capture_output=True, timeout=120)
    os.utime(src, (1, 1))  # the stale library is the newer file
    for name, value in (("_SRC", str(src)), ("_SO", str(so)), ("_lib", None),
                        ("_keep", None), ("_tried", False)):
        monkeypatch.setattr(native, name, value)
    lib = native.load()
    assert lib is not None and native._keep is not None
    assert hasattr(lib, "sdb_get_at_try") and hasattr(native._keep,
                                                       "sdb_get_at_try")
    t = native.NativeMemtable()
    assert t.commit_batch(t.snapshot(), [(b"k", b"v")])
    assert t.get_at(b"k", t.snapshot()) == b"v"


def test_the_datastore_reports_kv_native_calls():
    """`kv_native_kept` grows by the bounded calls of one known statement,
    read through the datastore's telemetry and its `/metrics` text, and
    `kv_native_busy` stays 0 on a store nothing else is calling."""
    from surrealdb_tpu import Datastore

    ds = Datastore("memory")
    try:
        ds.execute("CREATE t:1 SET n = 1", ns="p", db="p")
        ds.execute("SELECT * FROM t:1", ns="p", db="p")  # warm
        kept0 = ds.telemetry.get("kv_native_kept")
        busy0 = ds.telemetry.get("kv_native_busy")
        ds.execute("SELECT * FROM t:1", ns="p", db="p")
        # a snapshot (1); three reads that find their key, namespace,
        # database and the record (2 each: the read and its buffer's
        # free); one range scan that finds nothing (its batch and its
        # free; its start goes through the releasing binding); the
        # release (1)
        assert ds.telemetry.get("kv_native_kept") == kept0 + 10
        assert ds.telemetry.get("kv_native_busy") == busy0
        lines = {ln.split()[0]: int(ln.split()[1])
                 for ln in ds.telemetry.prometheus(ds).splitlines()
                 if ln.startswith("surreal_kv_native_")}
        assert lines == {"surreal_kv_native_kept_total": kept0 + 10,
                         "surreal_kv_native_busy_total": busy0}
    finally:
        ds.close()
