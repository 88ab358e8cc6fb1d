"""Device RPC framing: the kvs/remote.py length-prefixed frame idiom,
extended with raw buffer shipping.

One message =

    u32 total_len | u32 header_len | header | buf0 | buf1 | ...

`header` is the project wire codec (CBOR) encoding
`[tag, meta, descs]` where `descs` lists `[dtype_str, shape]` per
buffer. Buffers are the raw little-endian bytes of C-contiguous numpy
arrays — f32/int32 query/result tensors never pay a CBOR round-trip,
which is the whole point of the socketpair (the 10M-row int8 store is
~7.6 GB; encoding it as CBOR arrays would double memory and burn
minutes).

Mesh execution (device/mesh.py) rides the same frames — ships stay
FULL arrays (the runner row-shards at install, so crash/reship needs
no shard bookkeeping on the serving side). It only adds meta fields:
the ready frame carries `mesh` (topology describe()), load/search
replies carry `mesh_ndev` (devices actually serving that store; 1 =
legacy single-device). Unknown meta keys are ignored by older peers,
so no frame-format version bump is needed.
"""

from __future__ import annotations

import struct

import numpy as np

_HDR = struct.Struct(">I")
# a reply's `t`, the op's timeline on the runner: monotonic_ns when the
# request was read and decoded (`recv`) and just before the reply is
# sent (`ready`), then the nanoseconds the op spent in its `h2d`,
# `device` and `d2h` phases. Packed, not a map: the codec is Python and
# sits on every RPC's path (a five-key map cost 8 us more to encode and
# decode than these 40 bytes)
REPLY_T = struct.Struct("<5q")
# device frames carry whole block caches (a sharded store re-ship after
# a runner restart), so the cap is far above the KV wire's 256 MB
MAX_FRAME = 16 << 30


def _encode(msg) -> bytes:
    from surrealdb_tpu import wire

    return wire.encode(msg)


def _decode(b: bytes):
    from surrealdb_tpu import wire

    return wire.decode(b)


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(n - len(buf), 4 << 20))
        if not chunk:
            raise ConnectionError("device peer closed")
        buf.extend(chunk)
    return bytes(buf)


def send_msg(sock, tag: str, meta: dict, bufs=()) -> None:
    """Ship one (tag, meta, buffers) message. Buffers are numpy arrays;
    non-contiguous input is copied, dtype/shape ride the header."""
    arrs = [np.ascontiguousarray(b) for b in bufs]
    descs = [[a.dtype.str, list(a.shape)] for a in arrs]
    header = _encode([tag, meta, descs])
    total = 4 + len(header) + sum(a.nbytes for a in arrs)
    if total > MAX_FRAME:
        raise ValueError(f"device frame too large: {total}")
    sock.sendall(_HDR.pack(total) + _HDR.pack(len(header)) + header)
    for a in arrs:
        sock.sendall(a.tobytes() if a.nbytes else b"")


def recv_msg(sock):
    """Receive one message -> (tag, meta, [numpy arrays])."""
    (total,) = _HDR.unpack(_recv_exact(sock, 4))
    if total > MAX_FRAME:
        raise ConnectionError(f"device frame too large: {total}")
    (hlen,) = _HDR.unpack(_recv_exact(sock, 4))
    if hlen > total - 4:
        raise ConnectionError("device frame header overruns frame")
    tag, meta, descs = _decode(_recv_exact(sock, hlen))
    bufs = []
    for dtype_str, shape in descs:
        dt = np.dtype(dtype_str)
        n = int(np.prod(shape)) if shape else 1
        raw = _recv_exact(sock, n * dt.itemsize)
        bufs.append(np.frombuffer(raw, dtype=dt).reshape(shape))
    return tag, meta, bufs
