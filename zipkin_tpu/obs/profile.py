"""On-demand ``jax.profiler`` capture (the ostrich /pprof role).

One capture at a time, process-wide: the jax profiler is a global
singleton, so a second concurrent start would abort the first trace.
The API exposes this as ``POST /debug/profile?seconds=N`` — the caller
blocks for the window (ThreadingHTTPServer gives it its own thread) and
gets back the trace directory, viewable with TensorBoard / Perfetto.

The Python tracer is OFF (``python_tracer_level = 0``): hooking every
Python call stalled the daemon for seconds at the capture's start and
stop (one ack waited 6.1 s in a traced benchmark run), which bent the
very window the trace was taken to measure. The write path's stages
are in the trace by name instead (``obs.stage``), on host lines of the
same ``.xplane.pb`` as the device plane.
"""

from __future__ import annotations

import tempfile
import threading
import time
from typing import Optional

MAX_SECONDS = 120.0

_capture_lock = threading.Lock()  # lock-order: 86 profiler


class ProfilerBusy(RuntimeError):
    """A capture is already running."""


def capture(seconds: float, out_dir: Optional[str] = None
            ) -> "tuple[str, float]":
    """Trace device + host activity for ``seconds`` (clamped to
    [0.01, MAX_SECONDS] — the one clamp site); returns (trace
    directory, effective seconds). Raises ProfilerBusy when a capture
    is in flight, and propagates whatever ``jax.profiler`` raises when
    the backend can't trace (callers map that to a 503)."""
    seconds = min(max(float(seconds), 0.01), MAX_SECONDS)
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture is already running")
    try:
        import jax

        out_dir = out_dir or tempfile.mkdtemp(prefix="zipkin-tpu-profile-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return out_dir, seconds
    finally:
        _capture_lock.release()
