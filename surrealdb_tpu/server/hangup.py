"""Hang-up watch: ONE thread a server learns which clients went away.

A gated HTTP request runs on its connection's own thread, so nothing
reads that socket while the query runs. This thread blocks in `epoll`
over the sockets of the connections that have sent a gated request and
wakes only when a peer really left (FIN or reset: close, kill, TCP
half-close). The request then in flight on that connection has its
cooperative cancel flag set, exactly as `KILL` sets it, and its
admission slot is free within one check_deadline interval
(doc/operations.md "In-flight query registry, KILL, disconnect").

What a request pays is two dict operations under `_lock` (`begin`,
`end`) and no system call: a descriptor joins the epoll set once a
connection (`join`) and leaves it before its socket can close
(`leave`). It asks for hang-up only (`EPOLLRDHUP`), one-shot, so a
pipelined next request's bytes neither cancel nor wake anything and a
peer that left is reported once; `_gone` remembers it for the requests
that connection has still buffered. TLS sockets join like any other:
the TCP hang-up shows on the descriptor.
"""

from __future__ import annotations

import os
import select
import threading

_HANGUP = select.POLLRDHUP | select.POLLHUP | select.POLLERR


def _peer_gone(fd: int) -> bool:
    """Whether the peer of the socket that is `fd` NOW has hung up (a
    closed descriptor reads POLLNVAL: no)."""
    p = select.poll()
    p.register(fd, select.POLLRDHUP)
    return any(ev & _HANGUP for _fd, ev in p.poll(0))


class HangupWatch:
    """The watch of one server; `close` it with the server."""

    def __init__(self, telemetry):
        self._telemetry = telemetry
        self._lock = threading.Lock()
        self._running: dict = {}  # fd -> handle of the request in flight
        self._gone: set = set()  # fds whose peer left, until `leave`
        self._ep = select.epoll()
        self._stop = os.eventfd(0)  # `close` writes it; the thread owns it
        self._ep.register(self._stop, select.EPOLLIN)
        threading.Thread(target=self._run, args=(self._stop,), daemon=True,
                         name="surreal-hangup-watch").start()

    def join(self, fd: int):
        """A connection's first gated request: watch its descriptor."""
        with self._lock:
            self._gone.discard(fd)  # a closed stranger's number, reused
        self._ep.register(fd, select.EPOLLRDHUP | select.EPOLLONESHOT)

    def leave(self, fd: int):
        """The connection ends; its socket is still open."""
        with self._lock:
            self._gone.discard(fd)
            self._running.pop(fd, None)
        self._ep.unregister(fd)

    def begin(self, fd: int, handle):
        """`handle` runs on the connection `fd` from here to `end`."""
        with self._lock:
            gone = fd in self._gone
            if gone:
                handle.cancel.set()
            else:
                self._running[fd] = handle
        if gone:
            self._telemetry.inc("disconnect_cancels")

    def end(self, fd: int):
        """Before the reply is written (the client may close once it
        has it) and before the handle is closed: a `cancel.set()` made
        under `_lock` can then never land on a recycled handle."""
        with self._lock:
            self._running.pop(fd, None)

    def close(self):
        """Stop the thread (the server is closing); once."""
        with self._lock:
            stop, self._stop = self._stop, None
        if stop is not None:
            os.eventfd_write(stop, 1)

    def _run(self, stop: int):
        while True:
            for fd, _ev in self._ep.poll():
                if fd == stop:
                    os.close(stop)
                    return
                # The event names a NUMBER. Between the poll and here
                # its connection may have ended and a new one taken the
                # number, so ask the descriptor itself, under the lock
                # that `join` / `begin` / `leave` take: a stranger's
                # query is never cancelled.
                with self._lock:
                    handle = None
                    if _peer_gone(fd):
                        self._gone.add(fd)
                        handle = self._running.pop(fd, None)
                        if handle is not None:
                            handle.cancel.set()
                if handle is not None:
                    self._telemetry.inc("disconnect_cancels")
