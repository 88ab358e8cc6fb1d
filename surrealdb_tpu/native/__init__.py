"""ctypes binding + lazy build of the native C++ MVCC memtable.

The shared library is compiled once (g++ -O2, from memtable.cpp and
nothing else) into the package directory and cached. Without a working
toolchain `load()` returns None and the pure-Python engine serves — but
never silently: the failed build is reported once on stderr with the
compiler's own output.

Values read out of the store are copied into malloc'd buffers on the C++
side under the store mutex and freed here via sdb_buf_free — so a
concurrent commit can never invalidate a buffer while Python copies it.

The library is bound twice. `ctypes.CDLL` gives the interpreter lock
away for the call and takes it back after, which under many threads costs
a wake and a queue for the lock each time; `ctypes.PyDLL` keeps it. A
call whose work is bounded goes through the keeping binding: the ones
that take no store mutex (`sdb_buf_free`, `sdb_scan_batch`, the free of
a drained iterator) directly, and the ones that do (a snapshot, its
release, one key's read, a commit of up to `KEEP_COMMIT_MAX` keys) as
their `_try` twin, which takes the mutex only if it is free and else
answers busy, so the blocking call runs through the releasing binding.
A keeping call therefore never waits for the mutex while it holds the
interpreter. Work that grows with the store (a range's scan or count, a
column extract, `sdb_len`, a bulk commit) always releases. Counters
`kv_native_kept` (calls made keeping the interpreter) and
`kv_native_busy` (`_try` calls that found the mutex held) are
process-wide, lock-free like `telemetry.StageStat` (a race loses one
count); the datastore registers both (kvs/ds.py)."""

from __future__ import annotations

import _ctypes
import ctypes
import os
import subprocess
import sys
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "memtable.cpp")
_SO = os.path.join(_HERE, "_memtable.so")
_lock = threading.Lock()
_lib = None
_keep = None
_tried = False
_reported = False
# symbols the current ABI has and an older build lacks: a prebuilt library
# without one of them is rebuilt
_PROBES = ("sdb_scan_extract_f32", "sdb_get_at_try")
# a `_try` entry point's answer when the store's mutex was held (the ones
# that return an int answer -1)
_BUSY = (1 << 64) - 1
# the most keys a commit hands to the keeping binding: under the mutex a
# key costs one map lookup to validate and one append
KEEP_COMMIT_MAX = 64
_kept = 0
_busy = 0


def kv_native_kept() -> int:
    return _kept


def kv_native_busy() -> int:
    return _busy


def _report(what: str, e: BaseException):
    """Say ONCE that the pure-Python memtable serves, and why."""
    global _reported
    if _reported:
        return
    _reported = True
    stderr = getattr(e, "stderr", None) or b""
    print(
        f"[surrealdb-tpu] native memtable {what} "
        f"({e.__class__.__name__}: {e}); the pure-Python memtable "
        f"serves instead\n{stderr.decode(errors='replace')[-2000:]}",
        file=sys.stderr, flush=True,
    )


def _build() -> bool:
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", _SRC, "-o", _SO],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return True
    except (OSError, subprocess.SubprocessError) as e:
        _report("build failed", e)
        return False


def _open():
    """The library at `_SO` as the releasing binding, or None when it lacks
    a symbol of the current ABI. A stale handle is closed again, so that a
    rebuilt file at the same path is mapped anew and not served from the
    loader's list of open libraries."""
    lib = ctypes.CDLL(_SO)
    try:
        for name in _PROBES:
            getattr(lib, name)
    except AttributeError:
        _ctypes.dlclose(lib._handle)
        return None
    return lib


def _declare(lib):
    c_char_pp = ctypes.POINTER(ctypes.c_char_p)
    vp = ctypes.c_void_p
    i64 = ctypes.c_int64
    i64p = ctypes.POINTER(i64)
    u64 = ctypes.c_uint64
    get_args = [vp, ctypes.c_char_p, i64, u64, ctypes.POINTER(vp), i64p]
    commit_args = [vp, u64, i64, c_char_pp, i64p, c_char_pp, i64p,
                   ctypes.c_int]
    for name, restype, argtypes in (
        ("sdb_memtable_new", vp, []),
        ("sdb_memtable_free", None, [vp]),
        ("sdb_buf_free", None, [vp]),
        ("sdb_snapshot", u64, [vp]),
        ("sdb_snapshot_try", u64, [vp]),
        ("sdb_snapshot_release", None, [vp, u64]),
        ("sdb_snapshot_release_try", ctypes.c_int, [vp, u64]),
        ("sdb_get_at", ctypes.c_int, get_args),
        ("sdb_get_at_try", ctypes.c_int, get_args),
        ("sdb_len", i64, [vp]),
        ("sdb_commit_batch", u64, commit_args),
        ("sdb_commit_batch_try", u64, commit_args),
        ("sdb_scan_new_at", vp,
         [vp, ctypes.c_char_p, i64, ctypes.c_char_p, i64, u64, i64,
          ctypes.c_int]),
        ("sdb_scan_free", None, [vp]),
        ("sdb_scan_batch", i64, [vp, ctypes.c_char_p, i64, i64, i64p]),
        ("sdb_count_range_at", i64,
         [vp, ctypes.c_char_p, i64, ctypes.c_char_p, i64, u64]),
        ("sdb_scan_extract_f32", i64,
         [vp, ctypes.c_char_p, i64, ctypes.c_char_p, i64, u64,
          ctypes.c_char_p, i64, i64, i64,
          ctypes.POINTER(ctypes.c_float), i64,
          ctypes.c_char_p, i64, i64p,
          ctypes.c_char_p, i64, i64p, i64p]),
    ):
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes


def load():
    """The releasing binding of the library, or None when unavailable
    (`_keep` then holds the keeping one)."""
    global _lib, _keep, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SO) or os.path.getmtime(_SO) < os.path.getmtime(_SRC):
            if not _build():
                return None
        try:
            lib = _open()
        except OSError as e:
            _report("did not load", e)
            return None
        if lib is None:
            # an old library without the current ABI: rebuild once, else
            # fall back to the pure-Python memtable
            if not _build():
                return None
            try:
                lib = _open()
            except OSError as e:
                _report("did not load after a rebuild", e)
                return None
            if lib is None:
                _report("did not load after a rebuild",
                        AttributeError(f"a symbol of {_PROBES} is missing"))
                return None
        keep = ctypes.PyDLL(_SO)
        _declare(lib)
        _declare(keep)
        _keep = keep
        _lib = lib
        return _lib


class NativeMemtable:
    """Thin OO wrapper over the C ABI (MVCC: snapshot reads + optimistic
    batch commit)."""

    def __init__(self):
        self.lib = load()
        if self.lib is None:
            raise RuntimeError("native memtable unavailable")
        self.keep = _keep
        self.h = self.lib.sdb_memtable_new()

    def __del__(self):
        try:
            if getattr(self, "h", None):
                self.lib.sdb_memtable_free(self.h)
                self.h = None
        except Exception:
            pass

    # -- snapshots ----------------------------------------------------------
    def snapshot(self) -> int:
        global _kept, _busy
        snap = self.keep.sdb_snapshot_try(self.h)
        if snap != _BUSY:
            _kept += 1
            return snap
        _busy += 1
        return self.lib.sdb_snapshot(self.h)

    def release(self, snap: int) -> None:
        global _kept, _busy
        if self.keep.sdb_snapshot_release_try(self.h, snap) == 0:
            _kept += 1
            return
        _busy += 1
        self.lib.sdb_snapshot_release(self.h, snap)

    # -- reads --------------------------------------------------------------
    def get_at(self, key: bytes, snap: int):
        global _kept, _busy
        out = ctypes.c_void_p()
        n = ctypes.c_int64()
        found = self.keep.sdb_get_at_try(self.h, key, len(key), snap,
                                         ctypes.byref(out), ctypes.byref(n))
        if found < 0:
            _busy += 1
            found = self.lib.sdb_get_at(self.h, key, len(key), snap,
                                        ctypes.byref(out), ctypes.byref(n))
        else:
            _kept += 1
        if found:
            try:
                return ctypes.string_at(out.value, n.value)
            finally:
                self.keep.sdb_buf_free(out)
                _kept += 1
        return None

    def __len__(self):
        return self.lib.sdb_len(self.h)

    def scan_at(self, beg: bytes, end: bytes, snap: int, limit=None,
                reverse=False):
        global _kept
        it = self.lib.sdb_scan_new_at(
            self.h, beg, len(beg), end, len(end), snap,
            -1 if limit is None else int(limit), 1 if reverse else 0,
        )
        # the batches free what they pack, so only an iterator the caller
        # left early still holds rows to free, and that free releases
        drained = False
        try:
            # batched drain: one FFI crossing per ~512 rows; frames are
            # [u32 klen][u32 vlen][key][val] unpacked with memoryview
            # slicing (a call a row cost more in ctypes marshalling than
            # the C++ side spent scanning)
            cap = 1 << 16
            buf = ctypes.create_string_buffer(cap)
            used = ctypes.c_int64()
            from_u32 = int.from_bytes
            while True:
                n = self.keep.sdb_scan_batch(
                    it, buf, cap, 512, ctypes.byref(used)
                )
                _kept += 1
                if n == -1:  # one item larger than the buffer: grow
                    cap *= 4
                    buf = ctypes.create_string_buffer(cap)
                    continue
                if n <= 0:
                    drained = True
                    return
                # copy only the used bytes (buf.raw would materialize the
                # whole cap-sized buffer first)
                mv = ctypes.string_at(buf, used.value)
                off = 0
                for _ in range(n):
                    kl = from_u32(mv[off:off + 4], "little")
                    vl = from_u32(mv[off + 4:off + 8], "little")
                    off += 8
                    k = mv[off:off + kl]
                    off += kl
                    v = mv[off:off + vl]
                    off += vl
                    yield k, v
        finally:
            if drained:
                self.keep.sdb_scan_free(it)
                _kept += 1
            else:
                self.lib.sdb_scan_free(it)

    def count_range_at(self, beg: bytes, end: bytes, snap: int) -> int:
        return self.lib.sdb_count_range_at(self.h, beg, len(beg), end,
                                           len(end), snap)

    def scan_extract_f32(self, beg: bytes, end: bytes, snap: int,
                         fname: bytes, dim: int, skip_prefix: int,
                         est_rows: int):
        """Columnar scan: extract `fname` as an (n, dim) float32 matrix +
        key suffixes; rows that don't conform come back as raw suffixes.
        Returns (matrix, [key_suffix bytes], [bad_key_suffix bytes])."""
        import numpy as _np

        max_rows = max(est_rows, 1024)
        keycap = max_rows * 40 + 1024
        badcap = keycap
        while True:
            mat = _np.empty((max_rows, dim), _np.float32)
            keybuf = ctypes.create_string_buffer(keycap)
            badbuf = ctypes.create_string_buffer(badcap)
            keyused = ctypes.c_int64()
            badused = ctypes.c_int64()
            badcount = ctypes.c_int64()
            n = self.lib.sdb_scan_extract_f32(
                self.h, beg, len(beg), end, len(end), snap,
                fname, len(fname), dim, skip_prefix,
                mat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                max_rows,
                keybuf, keycap, ctypes.byref(keyused),
                badbuf, badcap, ctypes.byref(badused),
                ctypes.byref(badcount),
            )
            if n == -1:
                keycap *= 4
                badcap *= 4
                continue
            if n == -2:
                # matrix full mid-scan: size to the true row count
                max_rows = self.count_range_at(beg, end, snap) + 1024
                keycap = max(keycap, max_rows * 40 + 1024)
                badcap = keycap
                continue
            break

        def _frames(raw: bytes):
            out = []
            off = 0
            total = len(raw)
            while off < total:
                ln = int.from_bytes(raw[off:off + 4], "little")
                off += 4
                out.append(raw[off:off + ln])
                off += ln
            return out

        keys = _frames(ctypes.string_at(keybuf, keyused.value))
        bad = _frames(ctypes.string_at(badbuf, badused.value))
        return mat[:n], keys, bad

    # -- writes -------------------------------------------------------------
    def commit_batch(self, snap: int, items, release_snap: bool = True) -> int:
        """items: iterable of (key, val|None). Returns the new version, or
        0 when a write-write conflict was detected (retryable). With
        `release_snap` the committer's snapshot is released atomically with
        the validation (single mutex hold on the C++ side). Up to
        `KEEP_COMMIT_MAX` keys keep the interpreter (module docstring)."""
        global _kept, _busy
        items = list(items)
        n = len(items)
        if not n:
            if release_snap:
                self.release(snap)
            return 1  # empty commit: nothing to validate or apply
        keys = (ctypes.c_char_p * n)(*[k for k, _v in items])
        klens = (ctypes.c_int64 * n)(*[len(k) for k, _v in items])
        vals = (ctypes.c_char_p * n)(
            *[(v if v is not None else b"") for _k, v in items]
        )
        vlens = (ctypes.c_int64 * n)(
            *[(len(v) if v is not None else -1) for _k, v in items]
        )
        args = (self.h, snap, n, keys, klens, vals, vlens,
                1 if release_snap else 0)
        if n <= KEEP_COMMIT_MAX:
            ver = self.keep.sdb_commit_batch_try(*args)
            if ver != _BUSY:
                _kept += 1
                return ver
            _busy += 1
        return self.lib.sdb_commit_batch(*args)


def available() -> bool:
    return load() is not None
