"""JSON request bodies with their float arrays read natively.

`loads(raw)` returns what `json.loads(raw)` returns, equal type for type
and bit for bit, but an array of at least `MIN_FLOATS` JSON numbers that
all have a fraction or an exponent (a bound query vector) is parsed by
`jsonnum.cpp` (`std::from_chars`, which rounds correctly, as `float()`
does) instead of one `float()` call an element under the interpreter
lock: 17–28 us for 768 numbers where `json.loads` takes ~255 us, on
one x86-64 core.

The pass is one scan of the body's bytes. Each array it takes is cut
out of the text and replaced by a placeholder string, `json.loads`
decodes the shorter text, and each placeholder is swapped for its
array's values. Everything else, integer literals included, is decoded
by `json.loads` as before. A body that holds the placeholder's escaped
form (`\\u0000`) or passes `MAX_BODY` bytes skips the pass; a malformed
body raises what `json.loads(raw)` raises.

The library is its own, built lazily (g++ -O3, from jsonnum.cpp alone)
beside the memtable's and loaded apart from it: without a toolchain
`load()` returns None, the failure is said once on stderr, and
`json.loads` decodes every body. The call is bounded by the body, so it
keeps the interpreter lock (`ctypes.PyDLL`, as the memtable's bounded
calls do). Counters `rpc_json_native_arrays` (arrays decoded natively)
and `rpc_json_native_fallbacks` (bodies whose pass was skipped, or
whose arrays were refused or could not all be placed, although the
library is loaded) are process-wide and lock-free, like
`telemetry.StageStat` (a race loses one count); the datastore registers
both (kvs/ds.py)."""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "jsonnum.cpp")
_SO = os.path.join(_HERE, "_jsonnum.so")
_CXX = ("g++", "-O3", "-std=c++17", "-shared", "-fPIC")
# the fewest elements of an array worth a native parse
MIN_FLOATS = 16
# bodies above this are decoded by json.loads alone
MAX_BODY = 1 << 20
# the shortest body that can hold a taken array: 16 elements of 3 bytes
# ("1.0"), 15 commas, two brackets
_MIN_BODY = MIN_FLOATS * 3 + (MIN_FLOATS - 1) + 2
# a placeholder as json.loads returns it: the body's text for it begins
# with the escape `\u0000`, and a body that holds that escape is not cut
_MARK = "\x00sdbf"

_lock = threading.Lock()
_fn = None
_tried = False
_arrays = 0
_fallbacks = 0


def rpc_json_native_arrays() -> int:
    return _arrays


def rpc_json_native_fallbacks() -> int:
    return _fallbacks


def _report(what: str, e: BaseException):
    stderr = getattr(e, "stderr", None) or b""
    print(
        f"[surrealdb-tpu] native JSON float parser {what} "
        f"({e.__class__.__name__}: {e}); json.loads decodes request "
        f"bodies alone\n{stderr.decode(errors='replace')[-2000:]}",
        file=sys.stderr, flush=True,
    )


def _build() -> bool:
    # into a file of this process's own, renamed into place, so that a
    # process never maps another's half-written library
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
    """The parser's entry point, or None when the library is unavailable
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
            fn = ctypes.PyDLL(_SO).sdb_json_float_arrays
        except (OSError, AttributeError) as e:
            _report("did not load", e)
            return None
        i64 = ctypes.c_int64
        fn.restype = i64
        fn.argtypes = [ctypes.c_char_p, i64, i64, ctypes.c_void_p, i64,
                       ctypes.c_void_p, i64, ctypes.POINTER(i64)]
        _fn = fn
        return _fn


def _place(doc, lists: dict) -> None:
    """Swap each placeholder string among `doc`'s values for its list,
    until `lists` is empty (a placeholder in a key's place stays)."""
    stack = [doc]
    while stack and lists:
        node = stack.pop()
        items = node.items() if type(node) is dict else enumerate(node)
        for k, v in items:
            tv = type(v)
            if tv is str:
                if v in lists:
                    node[k] = lists.pop(v)
            elif tv is dict or tv is list:
                stack.append(v)


class _Out:
    """Output buffers for a body of up to `size` bytes: a taken array
    spans at least _MIN_BODY bytes, an element at least 4 with its
    comma."""

    def __init__(self, size: int):
        self.size = size
        self.spans = np.empty(3 * (size // _MIN_BODY + 1), np.int64)
        self.vals = np.empty(size // 4 + 1, np.float64)
        self.args = (self.spans.ctypes.data, len(self.spans) // 3,
                     self.vals.ctypes.data, len(self.vals))
        self.refused = ctypes.c_int64()
        self.refused_ref = ctypes.byref(self.refused)


# each thread keeps the buffers of the largest body it decoded, up to
# _KEEP_BODY bytes (the values are read out after the call, and another
# thread may run in between); a larger body gets buffers of its own
_KEEP_BODY = 1 << 18
_local = threading.local()


def _buffers(n: int) -> _Out:
    out = getattr(_local, "out", None)
    if out is not None and out.size >= n:
        return out
    out = _Out(max(n, 1 << 14))
    if n <= _KEEP_BODY:
        _local.out = out
    return out


def loads(raw: bytes):
    """`json.loads(raw)`, with the float arrays parsed natively (module
    docstring)."""
    global _arrays, _fallbacks
    fn = load()
    n = len(raw)
    if fn is None or n < _MIN_BODY:
        return json.loads(raw)
    if n > MAX_BODY:
        _fallbacks += 1
        return json.loads(raw)
    out = _buffers(n)
    sp, max_spans, vp, max_vals = out.args
    found = fn(raw, n, MIN_FLOATS, sp, max_spans, vp, max_vals,
               out.refused_ref)
    refused = out.refused.value
    if found <= 0:
        if found < 0 or refused:
            _fallbacks += 1
        return json.loads(raw)
    vals = out.vals
    cut = out.spans[:3 * found].tolist()
    parts = []
    lists = {}
    prev = 0
    off = 0
    for i in range(found):
        a, end, count = cut[3 * i:3 * i + 3]
        parts.append(raw[prev:a])
        parts.append(b'"\\u0000sdbf%d"' % i)
        lists[f"{_MARK}{i}"] = vals[off:off + count].tolist()
        prev = end
        off += count
    parts.append(raw[prev:])
    try:
        doc = json.loads(b"".join(parts))
    except ValueError:
        return json.loads(raw)  # raises what the body alone raises
    if type(doc) is str:
        doc = lists.pop(doc, doc)
    elif type(doc) is dict or type(doc) is list:
        _place(doc, lists)
    if lists or refused:
        _fallbacks += 1
    if lists:
        # a placeholder in a key's place, or under a key given twice
        return json.loads(raw)
    _arrays += found
    return doc
