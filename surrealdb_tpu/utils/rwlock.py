"""A small reader-writer lock for the scoring hot path.

The pipelined cross-query batcher (device/batcher.py) may run two
scoring kernels concurrently; both only READ the index's host arrays,
while cache sync (which mutates them, sometimes in place) must be
exclusive. A plain RLock would serialize the kernels and defeat the
pipeline. Writer-preference: a waiting writer blocks NEW readers, so a
steady query stream cannot starve cache sync forever.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager


class RWLock:
    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = None  # owning thread while write-held
        self._writer_depth = 0
        self._writers_waiting = 0
        # read holds of the calling thread (`lend_read` gives up one,
        # and only where it is the thread's only one)
        self._mine = threading.local()

    @contextmanager
    def read(self):
        me = threading.current_thread()
        mine = self._mine
        with self._cond:
            if self._writer is me:
                # write lock implies read permission (sync paths call
                # back into readers)
                self._writer_depth += 1
                reentrant_write = True
            else:
                reentrant_write = False
                while self._writer is not None or self._writers_waiting:
                    self._cond.wait()
                self._readers += 1
                mine.n = getattr(mine, "n", 0) + 1
        try:
            yield
        finally:
            with self._cond:
                if reentrant_write:
                    self._writer_depth -= 1
                else:
                    mine.n -= 1
                    self._readers -= 1
                    if self._readers == 0:
                        self._cond.notify_all()

    def lend_read(self) -> bool:
        """Gives up one read hold of the calling thread until
        `reclaim_read`, so that a waiting writer can pass while the
        holder waits for something that reads nothing (a dispatch that
        has handed its request to the device runner). False, and
        nothing given up, where the caller holds the write lock (its
        read permission is the write hold itself) or any number of
        read holds but one: an outer hold would keep the writer out,
        and no hold is nothing to lend."""
        with self._cond:
            if self._writer is threading.current_thread() \
                    or getattr(self._mine, "n", 0) != 1:
                return False
            self._mine.n = 0
            self._readers -= 1
            if self._readers == 0:
                self._cond.notify_all()
        return True

    def reclaim_read(self):
        """Takes back the read hold `lend_read` gave up; waits behind
        a writer like any new reader."""
        with self._cond:
            while self._writer is not None or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
            self._mine.n = 1

    @contextmanager
    def write(self):
        me = threading.current_thread()
        with self._cond:
            if self._writer is me:  # reentrant
                self._writer_depth += 1
            else:
                self._writers_waiting += 1
                try:
                    while self._writer is not None or self._readers:
                        self._cond.wait()
                finally:
                    self._writers_waiting -= 1
                self._writer = me
                self._writer_depth = 1
        try:
            yield
        finally:
            with self._cond:
                self._writer_depth -= 1
                if self._writer_depth == 0:
                    self._writer = None
                    self._cond.notify_all()
