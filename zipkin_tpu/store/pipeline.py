"""Pipelined ingest + asynchronous eviction sealing (host side).

The fused ingest step is device work, but r5/1B profiling showed the
*host* half of every batch — thrift decode, columnar encode,
``should_index``, name-lc interning, ``make_device_batch`` padding and
the implicit H2D copy — running serially on one thread inside the
writer critical section, and PR 3's eviction capture stalling the
write path entirely (D2H pull + deflate seal inline) on every ring
lap. This module overlaps all of it, in the staging-buffer spirit of
DrJAX's MapReduce overlap and Ragged Paged Attention's paged staging
discipline (PAPERS.md):

``IngestPipeline`` — a three-stage software pipeline over the store's
write path (see docs/INGEST_PIPELINE.md):

1. **produce** (caller threads, under the store's encode lock):
   encode + index-policy bits + padding — everything that needs
   the dictionaries but not the device — feeding a bounded prefetch
   queue whose depth is the ONLY backpressure on writers;
2. **stage** (one thread): ``jax.device_put`` of the padded chunk
   into device memory while the previous fused step is still
   executing under JAX async dispatch; the stage→commit queue is
   bounded at 2 (double buffering);
3. **commit** (one thread): the eviction-capture trigger, then the
   donating state swap under ``store._rw.write()`` — the write lock
   is held for dispatch only, never for encode or H2D.

Batches flow through the queues strictly FIFO and the pads are the
same pad buckets the serial path uses, so a pipelined drive lands a
final device state BITWISE IDENTICAL to the serial path's (gated in
tests/test_pipeline.py and bench_smoke's pipeline phase) and hits the
same jit cache entries (zero steady-state recompiles,
``device.compile_count``).

``EvictionSealer`` — takes eviction capture off the critical path.
The write path still issues the read-only ``capture_eviction_rows``
launch synchronously (the captured-before-overwrite ordering
invariant lives there), but the resulting DEVICE arrays are handed to
this background thread for the D2H fetch, deflate compression, and
``ArchiveDirectory.append``. The bounded in-flight queue is the only
thing that can stall ingest (surfaced as the capture-backlog gauge +
stall counter); cold reads run behind ``TpuSpanStore.seal_barrier``
so a segment is never invisible to the query that needs it.

Error semantics match the serial write path's per-batch failures: a
worker failure parks the error, the failed item is dropped (counted
done, so blocked producers always unblock), and the parked error
re-raises ONCE on the next feed/submit/drain — failing that caller's
apply() exactly as an inline failure would — after which the stage
keeps processing. A transient fault (full disk during a seal, a
suspect store during a commit) therefore costs the batches that hit
it, never a permanently wedged store; the collector's queue counts
the surfaced failures like any other write error.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import NamedTuple, Optional

import jax

from zipkin_tpu.obs.stages import stage
from zipkin_tpu.store import device as dev

_STOP = object()


class IngestUnit(NamedTuple):
    """One committed launch's worth of work: a padded DeviceBatch
    (stacked along a leading axis when ``chained``) plus the host
    bookkeeping the commit stage needs. ``n_parts`` is the number of
    chunker parts inside (the sweep-cadence increment). ``wal_seq``
    is the unit's write-ahead-log sequence (None when no WAL is
    attached); the commit stage advances the store's applied frontier
    to it inside the same write-lock hold as the donating swap, so a
    checkpoint cut is always consistent with its manifest sequence.
    ``sketch`` is the unit's host sketch-mirror delta (store/mirror):
    computed in stage 1 from the same columns the device scatters,
    folded into the mirror inside the commit's write-lock hold —
    the query engine's zero-dispatch tier is never behind the
    committed frontier."""

    db: object
    n_spans: int
    n_anns: int
    n_banns: int
    n_parts: int
    chained: bool
    wal_seq: Optional[int] = None
    sketch: Optional[object] = None
    # Sharded units only (parallel/shard.ShardedSpanStore): max spans
    # any shard's part carries, precomputed HOST-side in stage 1 —
    # ShardedStore.ingest requires it so the commit hold never syncs.
    incoming: Optional[int] = None
    # Paged layout only (store/paged.PagePlanner): (lo, hi) gid ranges
    # of the pages this unit reclaims — the commit stage pulls them
    # through the eviction sink BEFORE the launch (per-page
    # captured-before-overwrite). Empty for ring units.
    reclaims: tuple = ()


class _StageBase:
    """Shared fed/done accounting: every item fed is eventually counted
    done exactly once (processed or dropped-on-error), so ``drain``
    and blocked producers always terminate."""

    def __init__(self):
        self._cond = threading.Condition()  # lock-order: 65 stage
        self._fed = 0  # guarded-by: _cond
        self._done = 0  # guarded-by: _cond
        self._error: Optional[BaseException] = None  # guarded-by: _cond
        self._closed = False  # guarded-by: _cond
        # Monotonic timestamp of the last forward progress (an item
        # completing, or work arriving at an idle stage) — the stall
        # watchdog's probe reads the age (obs.fleet).
        self._last_progress = time.monotonic()  # guarded-by: _cond

    @property
    def error(self) -> Optional[BaseException]:
        """Peek at the parked worker error without clearing it
        (TpuSpanStore.stop_pipeline re-raises it after stop)."""
        with self._cond:
            return self._error

    def take_error(self) -> Optional[BaseException]:
        """Pop the parked worker error (if any). Surfacing CLEARS it —
        one failed batch fails one caller, then the stage keeps
        working, mirroring the serial path's per-batch failures."""
        with self._cond:
            err, self._error = self._error, None
            return err

    def _check_feedable(self) -> None:
        err = self.take_error()
        if err is not None:
            raise err
        with self._cond:
            if self._closed:
                raise RuntimeError("pipeline stage is stopped")
            if self._done == self._fed:
                # Idle → busy: the stall clock starts at arrival, not
                # at the last completion before the idle gap.
                self._last_progress = time.monotonic()
            self._fed += 1

    def _mark_done(self) -> None:
        with self._cond:
            self._done += 1
            self._last_progress = time.monotonic()
            self._cond.notify_all()

    def progress_age_s(self) -> float:
        """Seconds since this stage last made forward progress while
        holding queued work; 0.0 when idle. The watchdog's pipeline/
        sealer stall signal — a large age with a non-empty queue means
        a wedged worker, not backpressure."""
        with self._cond:
            if self._done >= self._fed:
                return 0.0
            return max(0.0, time.monotonic() - self._last_progress)

    def _park_error(self, exc: BaseException) -> None:
        with self._cond:
            if self._error is None:
                self._error = exc

    def _wait_idle(self) -> None:
        with self._cond:
            while self._done < self._fed:
                self._cond.wait(timeout=0.5)

    def drain(self) -> None:
        """Block until everything fed BEFORE this call is processed;
        re-raises (and clears) a parked worker error — the item that
        errored was dropped, not silently retried. Draining to a
        snapshot target, not to empty, keeps drain() terminating under
        sustained concurrent feeding (a checkpoint save must not chase
        live writers forever)."""
        with self._cond:
            target = self._fed
            while self._done < target:
                self._cond.wait(timeout=0.5)
        err = self.take_error()
        if err is not None:
            raise err

    def _unregister(self, registry, metrics) -> None:
        for m in metrics:
            if registry.get(m.name) is m:
                registry.unregister(m.name)


class IngestPipeline(_StageBase):
    """Three-stage ingest pipeline over one TpuSpanStore (see module
    docstring). Created by ``TpuSpanStore.start_pipeline``; writers
    call ``feed`` (stage 1's tail), readers are untouched — they
    snapshot ``store.state`` under the read lock exactly as before."""

    def __init__(self, store, depth: int = 8, registry=None,
                 stage_buffers: int = 2):
        from zipkin_tpu import obs

        super().__init__()
        self._store = store
        self.depth = max(1, int(depth))
        self._prefetch: "queue.Queue" = queue.Queue(maxsize=self.depth)
        # Staged (device-resident) units in flight: 2 = classic double
        # buffering (one committing, one staging). Batch-escalated
        # deployments (StoreConfig.batch_spans, r12) may raise it so a
        # long device step never starves the H2D stage, at the cost of
        # stage_buffers x batch_spans of staged device memory.
        self.stage_buffers = max(1, int(stage_buffers))
        self._staged: "queue.Queue" = queue.Queue(
            maxsize=self.stage_buffers)
        # Stage-2 H2D hook: a sharded store places units over its mesh
        # (ShardedSpanStore.stage_unit); the single-device store keeps
        # the plain transfer.
        self._stage = getattr(store, "stage_unit", None) or dev.stage_batch
        reg = registry or obs.default_registry()
        self._registry = reg
        self.h_encode = reg.register(obs.LatencySketch(
            "zipkin_store_pipeline_encode_seconds",
            "Stage 1 per apply/write_thrift call: columnar encode + "
            "index bits + padding (outside the write lock)"))
        self.h_stage = reg.register(obs.LatencySketch(
            "zipkin_store_pipeline_stage_seconds",
            "Stage 2 per unit: H2D device_put of the padded batch"))
        self.h_commit = reg.register(obs.LatencySketch(
            "zipkin_store_pipeline_commit_seconds",
            "Stage 3 per unit: capture trigger + donating dispatch "
            "under the write lock"))
        self.g_depth = reg.register(obs.Gauge(
            "zipkin_store_pipeline_prefetch_depth",
            "Padded units waiting in the ingest prefetch queue",
            fn=lambda: float(self._prefetch.qsize())))
        self.c_stall = reg.register(obs.Counter(
            "zipkin_store_pipeline_stall_seconds_total",
            "Seconds writers blocked on a full prefetch queue "
            "(pipeline backpressure)"))
        self.c_units = reg.register(obs.Counter(
            "zipkin_store_pipeline_units_total",
            "Launch units fed through the ingest pipeline"))
        self._stager = threading.Thread(
            target=self._stage_loop, name="zipkin-ingest-stage",
            daemon=True)
        self._committer = threading.Thread(
            target=self._commit_loop, name="zipkin-ingest-commit",
            daemon=True)
        self._stager.start()
        self._committer.start()

    # -- stage 1 tail (caller threads) ----------------------------------

    def feed(self, unit: IngestUnit) -> float:
        """Enqueue one padded unit; blocks when the prefetch queue is
        full (the designed writer backpressure). Returns the seconds
        spent blocked so stage-1 timing can exclude them."""
        self._check_feedable()
        # Only a put against an already-full queue is backpressure;
        # elapsed time on a non-full put is just lock contention and
        # must not read as a stall on a loaded machine.
        stall = 0.0
        if self._prefetch.full():
            with stage("pipeline.feed_stall", unit=unit.wal_seq) as wait:
                self._prefetch.put(unit)
            stall = wait.seconds
        else:
            self._prefetch.put(unit)
        if stall > 1e-4:
            self.c_stall.inc(stall)
        self.c_units.inc()
        return stall

    # -- stage 2: H2D staging -------------------------------------------

    def _stage_loop(self) -> None:
        while True:
            item = self._prefetch.get()
            if item is _STOP:
                self._staged.put(_STOP)
                return
            try:
                with stage("pipeline.h2d", self.h_stage,
                           unit=item.wal_seq):
                    item = item._replace(db=self._stage(item.db))
            except BaseException as e:  # noqa: BLE001 — parked, re-raised
                self._park_error(e)
                self._mark_done()  # drop this unit; keep flowing
                continue
            self._staged.put(item)

    # -- stage 3: commit ------------------------------------------------

    def _commit_loop(self) -> None:
        store = self._store
        while True:
            item = self._staged.get()
            if item is _STOP:
                return
            try:
                with stage("pipeline.commit", self.h_commit,
                           unit=item.wal_seq):
                    store._commit_unit(item)
            except BaseException as e:  # noqa: BLE001 — parked, re-raised
                # This unit's spans are dropped (mirrors untouched, so
                # ring invariants hold and a failed capture pull is
                # retried by the next unit's trigger) — the same cost a
                # serial per-batch failure has.
                self._park_error(e)
            finally:
                self._mark_done()

    # -- lifecycle ------------------------------------------------------

    def stop(self) -> None:
        """Drain (best effort), stop both workers, unregister gauges.
        Never raises — callers that care about a parked error read
        ``.error`` (TpuSpanStore.stop_pipeline re-raises it)."""
        with self._cond:
            self._closed = True
        self._wait_idle()
        self._prefetch.put(_STOP)
        self._stager.join(timeout=30.0)
        self._committer.join(timeout=30.0)
        self._unregister(self._registry, (
            self.h_encode, self.h_stage, self.h_commit, self.g_depth,
            self.c_stall, self.c_units,
        ))

    def queued(self) -> int:
        return self._prefetch.qsize() + self._staged.qsize()


class EvictionSealer(_StageBase):
    """Background seal stage for eviction capture: D2H fetch + deflate
    + directory append off the write path. The capture PULL stays
    synchronous in ``TpuSpanStore._capture_window`` (ordering
    invariant); this thread only ever touches capture OUTPUT arrays,
    which no ingest step donates — so it needs no store lock."""

    def __init__(self, store, backlog: int = 4, registry=None):
        from zipkin_tpu import obs

        super().__init__()
        self._store = store
        self.backlog = max(1, int(backlog))
        self._q: "queue.Queue" = queue.Queue(maxsize=self.backlog)
        reg = registry or obs.default_registry()
        self._registry = reg
        self.g_backlog = reg.register(obs.Gauge(
            "zipkin_store_capture_backlog",
            "Pulled-but-unsealed eviction capture windows in flight",
            fn=lambda: float(self._q.qsize())))
        self.c_stall = reg.register(obs.Counter(
            "zipkin_store_capture_stall_seconds_total",
            "Seconds the write path blocked on a full capture-seal "
            "backlog (sealer backpressure)"))
        self.c_sealed = reg.register(obs.Counter(
            "zipkin_store_capture_windows_sealed_total",
            "Capture windows sealed into cold segments"))
        self.c_errors = reg.register(obs.Counter(
            "zipkin_store_capture_seal_errors_total",
            "Capture windows whose async seal failed (window lost "
            "from the cold tier; error re-raised on the write path)"))
        self._worker = threading.Thread(
            target=self._loop, name="zipkin-capture-seal", daemon=True)
        self._worker.start()

    def submit(self, n_s: int, n_a: int, n_b: int,
               s_m, a_m, b_m, lo: int, hi: int,
               pull_s: float) -> None:
        """Hand one pulled window (device-resident row matrices) to
        the sealer. Blocks when ``backlog`` windows are in flight —
        the ONLY way capture can stall ingest. Raises a parked error
        from an earlier failed seal (matching the inline path, where a
        sink failure surfaced on the write path that triggered it)."""
        self._check_feedable()
        full = self._q.full()  # see IngestPipeline.feed: full-at-entry
        t0 = time.perf_counter()
        self._q.put((n_s, n_a, n_b, s_m, a_m, b_m, lo, hi, pull_s))
        stall = time.perf_counter() - t0
        if full and stall > 1e-4:
            self.c_stall.inc(stall)

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is _STOP:
                return
            try:
                self._seal(item)
                self.c_sealed.inc()
            except BaseException as e:  # noqa: BLE001 — parked, re-raised
                # The window is LOST from the cold tier (its rows may
                # already be overwritten in the rings) — counted, and
                # the error fails the next write/barrier ONCE; later
                # windows still seal. _sealed_upto is not advanced, so
                # a checkpoint cut never claims the hole.
                self.c_errors.inc()
                self._park_error(e)
            finally:
                self._mark_done()

    def _seal(self, item) -> None:
        from zipkin_tpu.store.tpu import mats_to_batch

        n_s, n_a, n_b, s_m, a_m, b_m, lo, hi, pull_s = item
        t0 = time.perf_counter()
        host = jax.device_get((s_m, a_m, b_m))
        batch, gids = mats_to_batch(n_s, n_a, n_b, *host)
        sink = self._store.eviction_sink
        if sink is None:
            # Sink detached with windows still in flight: no segment
            # was written, so the frontier must NOT advance — leaving
            # the hole visible keeps a later checkpoint cut from
            # claiming a window the cold tier never got.
            return
        from zipkin_tpu.testing.crash import kill_point

        kill_point("mid-seal")
        sink(batch, gids, lo, hi,
             pull_s + (time.perf_counter() - t0))
        self._store._note_sealed(lo, hi)

    def stop(self) -> None:
        """Seal everything in flight, then stop. Never raises."""
        with self._cond:
            self._closed = True
        self._wait_idle()
        self._q.put(_STOP)
        self._worker.join(timeout=30.0)
        self._unregister(self._registry, (
            self.g_backlog, self.c_stall, self.c_sealed, self.c_errors,
        ))

    def queued(self) -> int:
        return self._q.qsize()

    def at_capacity(self) -> bool:
        """True when the in-flight window queue is full — the next
        capture submit will stall the write path (the watchdog's
        sealer-backlog signal)."""
        return self._q.qsize() >= self.backlog
