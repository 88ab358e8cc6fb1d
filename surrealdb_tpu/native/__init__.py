"""ctypes binding + lazy build of the native C++ MVCC memtable.

The shared library is compiled once (g++ -O2, from memtable.cpp and
nothing else) into the package directory and cached. Without a working
toolchain `load()` returns None and the pure-Python engine serves — but
never silently: the failed build is reported once on stderr with the
compiler's own output.

Values read out of the store are copied into malloc'd buffers on the C++
side under the store mutex and freed here via sdb_buf_free — so a
concurrent commit can never invalidate a buffer while Python copies it."""

from __future__ import annotations

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
_tried = False
_reported = False


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


def load():
    """The bound library, or None when unavailable."""
    global _lib, _tried
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
            lib = ctypes.CDLL(_SO)
            lib.sdb_scan_extract_f32  # symbol probe: stale prebuilt .so?
        except OSError as e:
            _report("did not load", e)
            return None
        except AttributeError:
            # an old library without the current ABI: rebuild once, else
            # fall back to the pure-Python memtable
            if not _build():
                return None
            try:
                lib = ctypes.CDLL(_SO)
                lib.sdb_scan_extract_f32
            except (OSError, AttributeError) as e:
                _report("did not load after a rebuild", e)
                return None
        c_char_pp = ctypes.POINTER(ctypes.c_char_p)
        i64 = ctypes.c_int64
        i64p = ctypes.POINTER(i64)
        u64 = ctypes.c_uint64
        lib.sdb_memtable_new.restype = ctypes.c_void_p
        lib.sdb_memtable_free.argtypes = [ctypes.c_void_p]
        lib.sdb_buf_free.argtypes = [ctypes.c_void_p]
        lib.sdb_snapshot.restype = u64
        lib.sdb_snapshot.argtypes = [ctypes.c_void_p]
        lib.sdb_snapshot_release.argtypes = [ctypes.c_void_p, u64]
        lib.sdb_get_at.restype = ctypes.c_int
        lib.sdb_get_at.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i64, u64,
            ctypes.POINTER(ctypes.c_void_p), i64p,
        ]
        lib.sdb_len.restype = i64
        lib.sdb_len.argtypes = [ctypes.c_void_p]
        lib.sdb_commit_batch.restype = u64
        lib.sdb_commit_batch.argtypes = [
            ctypes.c_void_p, u64, i64, c_char_pp, i64p, c_char_pp, i64p,
            ctypes.c_int,
        ]
        lib.sdb_scan_new_at.restype = ctypes.c_void_p
        lib.sdb_scan_new_at.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i64, ctypes.c_char_p, i64,
            u64, i64, ctypes.c_int,
        ]
        lib.sdb_scan_next.restype = ctypes.c_int
        lib.sdb_scan_next.argtypes = [ctypes.c_void_p, c_char_pp, i64p,
                                      c_char_pp, i64p]
        lib.sdb_scan_free.argtypes = [ctypes.c_void_p]
        lib.sdb_scan_batch.restype = i64
        lib.sdb_scan_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i64, i64, i64p,
        ]
        lib.sdb_count_range_at.restype = i64
        lib.sdb_count_range_at.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i64, ctypes.c_char_p, i64,
            u64,
        ]
        lib.sdb_scan_extract_f32.restype = i64
        lib.sdb_scan_extract_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i64, ctypes.c_char_p,
            i64, u64, ctypes.c_char_p, i64, i64, i64,
            ctypes.POINTER(ctypes.c_float), i64,
            ctypes.c_char_p, i64, i64p,
            ctypes.c_char_p, i64, i64p, i64p,
        ]
        _lib = lib
        return _lib


class NativeMemtable:
    """Thin OO wrapper over the C ABI (MVCC: snapshot reads + optimistic
    batch commit)."""

    def __init__(self):
        self.lib = load()
        if self.lib is None:
            raise RuntimeError("native memtable unavailable")
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
        return self.lib.sdb_snapshot(self.h)

    def release(self, snap: int) -> None:
        self.lib.sdb_snapshot_release(self.h, snap)

    # -- reads --------------------------------------------------------------
    def get_at(self, key: bytes, snap: int):
        out = ctypes.c_void_p()
        n = ctypes.c_int64()
        if self.lib.sdb_get_at(self.h, key, len(key), snap,
                               ctypes.byref(out), ctypes.byref(n)):
            try:
                return ctypes.string_at(out.value, n.value)
            finally:
                self.lib.sdb_buf_free(out)
        return None

    def __len__(self):
        return self.lib.sdb_len(self.h)

    def scan_at(self, beg: bytes, end: bytes, snap: int, limit=None,
                reverse=False):
        it = self.lib.sdb_scan_new_at(
            self.h, beg, len(beg), end, len(end), snap,
            -1 if limit is None else int(limit), 1 if reverse else 0,
        )
        try:
            # batched drain: one FFI crossing per ~512 rows; frames are
            # [u32 klen][u32 vlen][key][val] unpacked with memoryview
            # slicing (the per-row sdb_scan_next path cost more in ctypes
            # marshalling than the C++ side spent scanning)
            cap = 1 << 16
            buf = ctypes.create_string_buffer(cap)
            used = ctypes.c_int64()
            from_u32 = int.from_bytes
            while True:
                n = self.lib.sdb_scan_batch(
                    it, buf, cap, 512, ctypes.byref(used)
                )
                if n == -1:  # one item larger than the buffer: grow
                    cap *= 4
                    buf = ctypes.create_string_buffer(cap)
                    continue
                if n <= 0:
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
        the validation (single mutex hold on the C++ side)."""
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
        return self.lib.sdb_commit_batch(self.h, snap, n, keys, klens,
                                         vals, vlens,
                                         1 if release_snap else 0)


def available() -> bool:
    return load() is not None
