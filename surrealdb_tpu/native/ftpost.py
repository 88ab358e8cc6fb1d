"""A FULLTEXT index's posting values read natively, for the device store's
build (`idx/ftdevice.py _read_index`).

`parse(raws)` takes the raw `bf` values of an index (each the store's
pickle of `{record key: (tf, offsets, record id)}`) and returns, without
building the dicts or their offset lists, each value's posting count and
every posting's record id and tf: `ftpost.cpp` walks the pickle opcodes in
one call over all the values, with the interpreter lock released (the call
is bounded by the values, up to a few seconds for an index of millions of
postings). Where the library cannot be built or loaded (said once on
stderr), or a value is not that shape with integer record ids, it returns
`(None, None, None)` and the caller decodes every value in Python.

The library is its own, built lazily (g++ -O3, from ftpost.cpp alone)
beside the others, as `native/jsonnum.py` builds its own."""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ftpost.cpp")
_SO = os.path.join(_HERE, "_ftpost.so")
_CXX = ("g++", "-O3", "-std=c++17", "-shared", "-fPIC")
# the fewest bytes one posting takes in a value: an empty key (2), the tf
# (2), the empty list (1), the id (2), the tuple (1)
_MIN_POSTING = 8

_lock = threading.Lock()
_fn = None
_tried = False


def _report(what: str, e: BaseException):
    stderr = getattr(e, "stderr", None) or b""
    print(
        f"[surrealdb-tpu] native full-text posting reader {what} "
        f"({e.__class__.__name__}: {e}); postings are decoded in Python"
        f"\n{stderr.decode(errors='replace')[-2000:]}",
        file=sys.stderr, flush=True,
    )


def _build() -> bool:
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run([*_CXX, _SRC, "-o", tmp], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        _report("build failed", e)
        return False


def load():
    """The reader's entry point, or None when the library is unavailable
    (said once, on stderr)."""
    global _fn, _tried
    if _fn is not None or _tried:
        return _fn
    with _lock:
        if _fn is not None or _tried:
            return _fn
        _tried = True
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            if not _build():
                return None
        try:
            fn = ctypes.CDLL(_SO).sdb_ft_postings
        except (OSError, AttributeError) as e:
            _report("did not load", e)
            return None
        i64, ptr = ctypes.c_int64, ctypes.c_void_p
        fn.restype = i64
        fn.argtypes = [ctypes.c_char_p, ptr, i64, ptr, ptr, ptr, i64]
        _fn = fn
        return _fn


def parse(raws: list):
    """(counts [V] int64, record ids [P] int64, tfs [P] int32) of the
    values `raws`, or (None, None, None) (module docstring)."""
    fn = load()
    if fn is None or not raws:
        return None, None, None
    data = b"".join(raws)
    offs = np.zeros(len(raws) + 1, np.int64)
    np.cumsum([len(r) for r in raws], out=offs[1:])
    cap = len(data) // _MIN_POSTING + 1
    counts = np.empty(len(raws), np.int64)
    rids = np.empty(cap, np.int64)
    tfs = np.empty(cap, np.int32)
    got = fn(data, offs.ctypes.data, len(raws), counts.ctypes.data,
             rids.ctypes.data, tfs.ctypes.data, cap)
    if got < 0:
        return None, None, None
    return counts, rids[:got].copy(), tfs[:got].copy()
