"""One span per stage of the write path, with two outputs.

``with stage("store.encode", unit=seq):`` opens a
``jax.profiler.TraceAnnotation`` (a span in the profiler's OWN trace,
so on the same clock as the device plane of a ``POST /debug/profile``
capture; a flag test when no capture runs) and on exit observes the
elapsed ``perf_counter`` seconds into ``sketch`` or, with none given,
into the child ``stage="encode"`` (the span's name after its dot) of
the one labelled family ``zipkin_ingest_stage_seconds`` in the
process-wide registry. Spans nest on a thread, which is their
parentage; what crosses threads carries ``unit=<wal_seq>``.

The stage observes however the block ends (a stage that raised still
took its time). jax is imported at the first span, not with this
module: ``wal/`` and ``ingest/`` import without it.
"""

from __future__ import annotations

import time
from typing import Optional

from zipkin_tpu.obs.registry import LatencySketch, default_registry

FAMILY = "zipkin_ingest_stage_seconds"
QUERY_FAMILY = "zipkin_query_stage_seconds"

_HELP = {
    FAMILY: ("Seconds per stage of the write path as the daemon sees it "
             "(one child per span of obs.stage that names no sketch of "
             "its own; docs/OBSERVABILITY.md has the table)"),
    QUERY_FAMILY: ("Seconds per stage of the read path (obs.query_stage: "
                   "compile, rw_wait, device, decode; docs/OBSERVABILITY.md "
                   "has the table)"),
}

_annotation = None  # jax.profiler.TraceAnnotation, bound at the first span


def stage_family(name: str = FAMILY) -> LatencySketch:
    """The ``{name}{stage}`` family of the process-wide registry
    (registered at first use): ``zipkin_ingest_stage_seconds`` for the
    write path, ``zipkin_query_stage_seconds`` for the read path."""
    reg = default_registry()
    fam = reg.get(name)
    if fam is None:
        fam = reg.register(LatencySketch(name, _HELP[name],
                                         labelnames=("stage",)))
    return fam


def query_stage(name: str, **ids) -> "stage":
    """A span of the read path: as ``stage``, observed into
    ``zipkin_query_stage_seconds{stage=<name after its dot>}``."""
    return stage(name, stage_family(QUERY_FAMILY).labels(
        stage=name.partition(".")[2] or name), **ids)


class stage:
    """See the module docstring. ``less`` is seconds to leave out of
    the observation (a wait timed by a span of its own); ``seconds``
    is what was observed, for a caller that keeps a second sketch."""

    __slots__ = ("name", "sketch", "less", "seconds", "_ann", "_t0")

    def __init__(self, name: str, sketch=None, **ids):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self.name = name
        self.sketch = sketch
        self.less = 0.0
        self.seconds: Optional[float] = None
        self._ann = _annotation(
            name, **{k: v for k, v in ids.items() if v is not None})

    def __enter__(self) -> "stage":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def tag(self, **ids) -> None:
        """Identifiers known only inside the span (a sequence the
        append assigns)."""
        self._ann.set_metadata(**ids)

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0 - self.less

    def done(self) -> None:
        """End the span here, before the ``with`` block does (the
        block may go on under a lock this span waited for)."""
        if self.seconds is None:
            self.seconds = max(self.elapsed(), 0.0)
            self._ann.__exit__(None, None, None)
            sketch = self.sketch
            if sketch is None:
                sketch = stage_family().labels(
                    stage=self.name.partition(".")[2] or self.name)
            sketch.observe(self.seconds)

    def __exit__(self, *exc) -> None:
        self.done()
