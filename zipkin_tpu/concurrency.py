"""Concurrency primitives for host-side store coordination.

The reference leans on ArrayBlockingQueue / synchronized / immutable
data for thread safety (SURVEY.md §5 "race detection"). Our device
store has one extra hazard the JVM design doesn't: ``ingest_step``
donates the previous state's device buffers (buffer donation is how the
ring update stays allocation-free), so a query that is still reading a
snapshot of the old state can see its buffers deleted mid-kernel.

``RWLock`` makes the swap safe: a query holds a read lock while it
takes its snapshot of the state and dispatches its kernels, and waits
for their results outside it (a launch already dispatched reads its
inputs before any later donating launch reuses them: the device runs
its queue in order); ingest takes the write lock to run the donating
step and swap the state pointer. Writer-preference keeps the hot
ingest path from starving behind a stream of queries.
"""

from __future__ import annotations

import contextlib
import threading


class RWLock:
    """Writer-preference readers/writer lock (non-reentrant)."""

    def __init__(self) -> None:
        self._cond = threading.Condition()  # lock-order: 42 rwlock-internal
        self._readers = 0  # guarded-by: _cond
        self._writers_waiting = 0  # guarded-by: _cond
        self._writer = False  # guarded-by: _cond

    @contextlib.contextmanager
    def read(self):
        with self._cond:
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
        try:
            yield
        finally:
            with self._cond:
                self._readers -= 1
                if self._readers == 0:
                    self._cond.notify_all()

    @contextlib.contextmanager
    def write(self, wait=None):
        """``wait``, a context manager, is entered round the acquisition
        alone (a span that times the wait for the lock)."""
        with wait or contextlib.nullcontext(), self._cond:
            self._writers_waiting += 1
            while self._writer or self._readers:
                self._cond.wait()
            self._writers_waiting -= 1
            self._writer = True
        try:
            yield
        finally:
            with self._cond:
                self._writer = False
                self._cond.notify_all()
