"""shard_map-ed ingest: N store shards, one collective summary.

Mesh layout: one axis ``shard`` = data-parallel ingest shards (the
analogue of the reference's horizontally scaled collector fleet,
ScribeSpanReceiver.scala:42-56). Store state is stacked with a leading
[n_shards] dim sharded over the axis; batches likewise. The fused
per-shard ingest is exactly store/device.ingest_step; the summary that
the sampler/query layer needs crosses shards via ICI collectives only.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from zipkin_tpu.ops import moments as M
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.base import service_scan_only


def compat_shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """shard_map across jax versions: the promoted ``jax.shard_map``
    (with its ``check_vma`` flag) when present, else the
    ``jax.experimental.shard_map`` this environment ships (same
    semantics; the flag was named ``check_rep`` there)."""
    if hasattr(jax, "shard_map"):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)
    from jax.experimental.shard_map import shard_map as _sm

    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
               check_rep=check_vma)


def _stack_states(config: dev.StoreConfig, n: int):
    one = dev.init_state(config)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), one)


DEP_SUMMARY_K = 1 << 14  # the single-chip deps-read compaction bound


def _pmax64(x, axis: str):
    """``lax.pmax`` for 64-bit values. The TPU lowers only SUM
    all-reduces over 64-bit types ("Supported lowering only of Sum all
    reduce" — refused at compile time, found by the chipless four-chip
    compile in PR 22), so gather the shard values and reduce locally:
    same result, n tiny rows of traffic."""
    return jnp.max(jax.lax.all_gather(x, axis), axis=0)


def _pmin64(x, axis: str):
    """``lax.pmin`` twin of :func:`_pmax64`."""
    return jnp.min(jax.lax.all_gather(x, axis), axis=0)


def _summarize(state: dev.StoreState, axis: str,
               dep_k: int = DEP_SUMMARY_K) -> Dict[str, jnp.ndarray]:
    """Cross-shard global aggregates, computed inside shard_map."""
    # Counters and additive sketches ride a psum.
    spans_seen = jax.lax.psum(state.counters["spans_seen"], axis)
    svc_counts = jax.lax.psum(state.svc_span_counts, axis)
    svc_hist = jax.lax.psum(state.svc_hist, axis)
    cms_counts = jax.lax.psum(state.cms_trace_spans, axis)
    ann_svc_counts = jax.lax.psum(state.ann_svc_counts, axis)
    # HLL merge is an elementwise max.
    hll_regs = jax.lax.pmax(state.hll_traces, axis)
    # Moments combine is associative+commutative but not "+", so the
    # bank can't ride a psum — but its COUNT column can, and the count
    # decides which cells are live. Instead of all-gathering the full
    # [S*S, 5] bank per shard (~20 MB/shard at S=1024, EVERY ingest
    # step — VERDICT r4 weak #7), psum the counts (one column), pick
    # the global top-k live cells (identical on every shard: computed
    # from replicated input), and all-gather only those k rows — the
    # same compaction the single-chip deps read uses. When more than k
    # cells are live the compacted bank would silently drop links, so
    # a lax.cond falls back to the full gather (pred is replicated;
    # both branches produce the dense bank, selected cells combine
    # through the same Chan/Pébay tree as before).
    bank = dev.total_dep_moments(state)  # [S*S, 5]
    cells = bank.shape[0]
    if dep_k is None or dep_k >= cells:
        banks = jax.lax.all_gather(bank, axis)  # [n, S*S, 5]
        dep_moments = M.reduce_moments(banks, axis=0)
    else:
        cnt = jax.lax.psum(bank[:, 0], axis)
        nz = (cnt > 0).sum()

        def compact(b):
            _, idx = jax.lax.top_k(cnt, dep_k)
            gathered = jax.lax.all_gather(b[idx], axis)  # [n, k, 5]
            top = M.reduce_moments(gathered, axis=0)
            return jnp.zeros_like(b).at[idx].set(top)

        def full(b):
            return M.reduce_moments(jax.lax.all_gather(b, axis), axis=0)

        dep_moments = jax.lax.cond(nz > dep_k, full, compact, bank)
    return {
        "spans_seen": spans_seen,
        "svc_span_counts": svc_counts,
        "svc_hist": svc_hist,
        "cms_trace_spans": cms_counts,
        "ann_svc_counts": ann_svc_counts,
        "hll_traces": hll_regs,
        "dep_moments": dep_moments,
        "ts_min": _pmin64(state.ts_min, axis),
        "ts_max": _pmax64(state.ts_max, axis),
    }


def make_sharded_archive(mesh: Mesh, axis: str = "shard"):
    """Per-shard dependency bucket close (dev.dep_close_bucket): sweeps
    the pending ring and rotates the window bank, per shard, so the
    sharded deployment keeps the same time-windowed banks as the
    single-store path. Writes route whole traces to one shard, so the
    streaming join is shard-local."""

    def fn(state, incoming):
        del incoming  # cadence is the caller's policy; kept for compat
        state = jax.tree.map(lambda x: x[0], state)
        new_state = dev.dep_close_bucket.__wrapped__(state)
        return jax.tree.map(lambda x: x[None], new_state)

    mapped = compat_shard_map(
        fn, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,))


def make_sharded_sweep(mesh: Mesh, axis: str = "shard"):
    """Per-shard pending sweep (dev.dep_sweep) — run before dependency
    reads so cross-batch late parents are linked on every shard."""

    def fn(state):
        state = jax.tree.map(lambda x: x[0], state)
        new_state = dev.dep_sweep.__wrapped__(state)
        return jax.tree.map(lambda x: x[None], new_state)

    mapped = compat_shard_map(
        fn, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,))


def make_sharded_ingest(mesh: Mesh, axis: str = "shard"):
    """Build the jitted sharded step:

    (stacked_states [n,...], stacked_batches [n,...]) →
        (stacked_states, global summary replicated)
    """

    def shard_fn(state, batch):
        # shard_map hands us blocks with the leading shard dim of size 1.
        state = jax.tree.map(lambda x: x[0], state)
        batch = jax.tree.map(lambda x: x[0], batch)
        new_state = dev.ingest_step.__wrapped__(state, batch)
        summary = _summarize(new_state, axis)
        new_state = jax.tree.map(lambda x: x[None], new_state)
        return new_state, summary

    mapped = compat_shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,))


def stacked_incoming(device_batches) -> int:
    """Max spans any shard's batch carries, read off the stacked
    pytree. SYNCS when the stack is device-resident — call it OUTSIDE
    store locks and pass the result to ``ShardedStore.ingest``."""
    return int(np.max(np.asarray(device_batches.n_spans)))


class ShardedStore:
    """Host handle for an n-shard device store.

    Round-robins host batches across shards (callers feeding from
    multiple ingest processes would instead target their local shard).
    """

    def __init__(self, mesh: Mesh, config: dev.StoreConfig, axis: str = "shard"):
        if config.paged_enabled:
            # The page planner is per-store HOST state; the stacked
            # per-shard states have no per-shard planner yet (the
            # daemon rejects --layout paged with --shards too).
            raise ValueError(
                "layout='paged' is single-device only; the sharded "
                "store has no per-shard page planner yet")
        self.mesh = mesh
        self.axis = axis
        self.config = config
        self.n = mesh.shape[axis]
        sharding = NamedSharding(mesh, P(axis))
        self.states = jax.device_put(_stack_states(config, self.n), sharding)
        self.step = make_sharded_ingest(mesh, axis)
        self.archive_step = make_sharded_archive(mesh, axis)
        self.sweep_step = make_sharded_sweep(mesh, axis)
        self.last_summary = None
        # Host upper bound of any shard's write_pos / lower bound of any
        # shard's last bucket close — paces rotation without device
        # syncs (mirrors TpuSpanStore._maybe_archive).
        self._wp_upper = 0
        self._archived_lower = 0
        self._batches_since_sweep = 0

    # Same cadence as TpuSpanStore.SWEEP_EVERY: bounds how long a
    # cross-batch child waits for its link in per-ingest summaries.
    SWEEP_EVERY = 64

    def ingest(self, device_batches,
               incoming: Optional[int] = None) -> Dict[str, np.ndarray]:
        """device_batches: pytree stacked [n_shards, ...].

        ``incoming`` is the max spans any shard's batch carries —
        compute it HOST-SIDE (or via ``stacked_incoming`` outside any
        store lock) and pass it in. It is required: reading it off the
        device-resident stack here would put a host sync inside every
        caller's lock hold (ShardedSpanStore._apply_locked commits
        under the write lock — graftlint sync-under-lock, the r10
        group-commit stall class)."""
        if incoming is None:
            raise TypeError(
                "ShardedStore.ingest requires incoming= (max spans "
                "per shard batch); use stacked_incoming(batches) "
                "OUTSIDE store locks")
        incoming = int(incoming)
        self._maybe_archive(incoming)
        self._batches_since_sweep += 1
        if self._batches_since_sweep >= self.SWEEP_EVERY:
            self.sweep()
        self.states, summary = self.step(self.states, device_batches)
        self._wp_upper += incoming
        self.last_summary = summary
        return summary

    def sweep(self) -> None:
        """Resolve pending (late-parent) children on every shard."""
        self.states = self.sweep_step(self.states)
        self._batches_since_sweep = 0

    def _maybe_archive(self, incoming: int) -> None:
        cap = self.config.capacity
        if self._wp_upper + incoming - self._archived_lower <= cap:
            return
        self.states = self.archive_step(self.states, jnp.int64(incoming))
        self._batches_since_sweep = 0
        self._archived_lower = min(
            self._wp_upper,
            max(self._wp_upper + incoming - cap, self._wp_upper - cap // 2),
        )


def global_summary(states, mesh: Mesh, axis: str = "shard",
                   dep_k: int = DEP_SUMMARY_K):
    """One-off collective summary over stacked states (no ingest).
    ``dep_k`` bounds the dependency-bank collective (None = full
    gather; see _summarize)."""

    def fn(state):
        state = jax.tree.map(lambda x: x[0], state)
        return _summarize(state, axis, dep_k)

    mapped = compat_shard_map(
        fn, mesh=mesh, in_specs=(P(axis),), out_specs=P(), check_vma=False
    )
    return jax.jit(mapped)(states)


def stack_batches(batches) -> Tuple:
    """Host: list of n DeviceBatch → stacked pytree [n, ...]."""
    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


# ---------------------------------------------------------------------------
# ShardedSpanStore — the full SpanStore SPI over the mesh
# ---------------------------------------------------------------------------


from zipkin_tpu.store.analytics import WindowedAnalytics
from zipkin_tpu.store.base import SuspectGuard


class ShardedSpanStore(WindowedAnalytics, SuspectGuard):
    """SpanStore SPI over an n-shard device mesh.

    Writes route whole traces to shards by trace-id hash (the role of
    Cassandra's key-range sharding / BucketedColumnFamily hot-row
    buckets, CassieSpanStore.scala:49,108-116), so every trace is
    resident on exactly one shard and trace-local reads stay local.
    Reads run the single-store query kernels per shard under shard_map
    and merge across shards: elementwise collectives in-graph where the
    merge is min/max/sum (durations, presence, sketches), or a host
    merge of the per-shard top-k candidates for index queries — the
    batched-cluster-read role of CassieSpanStore.scala:253-270.

    Implements the same surface the conformance suite drives against the
    in-memory and single-device stores (SpanStoreValidator.scala:27).
    """

    def __init__(self, mesh: Mesh, config: dev.StoreConfig,
                 axis: str = "shard", codec=None, registry=None,
                 dispatch_window_s: float = 0.0):
        import threading

        from zipkin_tpu import obs
        from zipkin_tpu.columnar.encode import SpanCodec
        from zipkin_tpu.concurrency import RWLock
        from zipkin_tpu.parallel.dispatch import CrossShardDispatcher
        from zipkin_tpu.store.base import PinBank
        from zipkin_tpu.store.mirror import FleetMirror, SketchMirror

        self.mesh = mesh
        self.axis = axis
        self.config = config
        self.inner = ShardedStore(mesh, config, axis)
        self.n = mesh.shape[axis]
        self.codec = codec or SpanCodec()
        self.ttls: Dict[int, float] = {}
        self.pins = PinBank()
        self._name_lc: Dict[int, int] = {}
        self._kernels: Dict = {}  # guarded-by: _kernels_lock
        # Same discipline as TpuSpanStore: _lock serializes writers and
        # host dicts; the RWLock guards the states swap (sharded ingest
        # donates the previous stacked states) against in-flight reads.
        # _kernels_lock is a dedicated LEAF for the mapped-kernel
        # compile cache: query threads build kernels while HOLDING the
        # read lock, so guarding the dict with _lock would invert the
        # encode(10) -> commit(40) order (a writer holding _lock and
        # waiting on the write lock deadlocks against a reader waiting
        # on _lock — graftlint lock-order forbids the shortcut).
        self._lock = threading.Lock()  # lock-order: 10 encode
        self._rw = RWLock()  # lock-order: 40 commit
        self._kernels_lock = threading.Lock()  # lock-order: 75 kernel-cache
        # Collective-launch serializer (the r14-noted deadlock): every
        # mapped read kernel is a shard_map program whose collectives
        # rendezvous ALL mesh devices inside one launch. The XLA CPU
        # backend runs concurrent launches on a shared device pool, so
        # two collective programs in flight can each seize a subset of
        # the per-device rendezvous slots and wait forever for the
        # rest (N API threads x psum catalogs under the SHARED read
        # lock — the read lock never excluded reader/reader). One
        # launch at a time makes the rendezvous trivially complete.
        # Dedicated LEAF below the read-lock hold (40 -> 45); never
        # held across anything that blocks on another launch.
        self._coll_lock = threading.Lock()  # lock-order: 45 collective-launch
        # Monotonic collective-launch count (one per _coll_lock hold):
        # the dispatcher-batching counter-proof reads deltas of this.
        self._coll_launches = 0  # guarded-by: _coll_lock
        # Host commit frontier: _step_seq advances inside every
        # donating write-lock hold; _read_epoch covers host-only
        # visibility changes (pin/TTL mutations) — together the query
        # engine's result-cache key (write_frontier()).
        self._step_seq = 0
        self._read_epoch = 0
        # Per-shard sketch-mirror twins (store/mirror.py), fed deltas
        # on the commit path, merged lazily into the fleet view the
        # engine sketch tier and the windowed-analytics mixin read.
        self._mirrors = [SketchMirror(config, dicts=self.codec.dicts)
                         for _ in range(self.n)]
        self._fleet_mirror = FleetMirror(config, self._mirrors,
                                         lambda: self._step_seq)
        # Durable write-ahead log (wal/sharded.ShardedWal) + pipelined
        # ingest (store/pipeline) — both optional, attached/started by
        # the deployment wiring (main/example.py --wal-dir/--pipeline).
        self.wal = None
        self._wal_marks = None  # guarded-by: _lock
        self._wal_applied = 0
        self._pipeline = None  # guarded-by: _lock
        self._registry = reg = registry or obs.default_registry()
        # Per-shard occupancy/lap gauges: hash-partition imbalance is
        # invisible in the summed counters() totals.
        self._occ_family = reg.register(obs.CallbackFamily(
            "zipkin_shard_occupancy",
            "Per-shard span ring occupancy (hash-partition skew view)",
            "shard", self._occupancy_by_shard))
        self._laps_family = reg.register(obs.CallbackFamily(
            "zipkin_shard_ring_laps",
            "Per-shard span ring laps (eviction-pressure skew view)",
            "shard", self._laps_by_shard))
        # Cross-shard query dispatcher: concurrent API reads coalesce
        # into one collective launch per micro-window instead of
        # queueing singly behind _coll_lock.
        self._dispatcher = CrossShardDispatcher(
            self, window_s=dispatch_window_s, registry=reg)

    @property
    def dicts(self):
        return self.codec.dicts

    @property
    def states(self):
        return self.inner.states

    @property
    def dispatcher(self):
        return self._dispatcher

    def collective_launches(self) -> int:
        """Monotonic count of collective query launches (each one a
        _coll_lock hold). The dispatcher-batching acceptance test
        proves N concurrent reads land in ≤2 launches by differencing
        this around the burst."""
        with self._coll_lock:
            return self._coll_launches

    def close(self) -> None:
        """Ordered shutdown: stop the dispatcher (queued reads finish;
        later ones execute inline), drain+stop the pipeline, force the
        WAL durable, and unregister the per-shard gauge families. The
        WAL object itself stays open (its owner closes it, after any
        final checkpoint truncation)."""
        d = self.__dict__.get("_dispatcher")
        if d is not None:
            d.close()
        self.stop_pipeline(raise_errors=False)
        if self.wal is not None:
            self.wal.sync()
        for fam in (self.__dict__.get("_occ_family"),
                    self.__dict__.get("_laps_family")):
            if fam is not None and self._registry.get(fam.name) is fam:
                self._registry.unregister(fam.name)

    # -- resident query engines (query/engine.py; the duck-typed twin
    # of ReadSpanStore's registry, so Collector.flush/close and
    # checkpoint.save can join the executor thread's lifecycle) ------

    def register_query_engine(self, engine) -> None:
        self.__dict__.setdefault("_query_engines", []).append(engine)

    def query_engines(self):
        return list(self.__dict__.get("_query_engines", ()))

    # -- writes ---------------------------------------------------------

    def _shard_of(self, trace_id: int) -> int:
        # Shared with the multi-host routing tier (parallel/multihost
        # partition_for_trace): one hash, no drift between the producer
        # partitioner and the store's placement.
        from zipkin_tpu.parallel.multihost import shard_of

        return shard_of(trace_id, self.n)

    def apply(self, spans) -> None:
        from zipkin_tpu.columnar.encode import to_signed64

        from zipkin_tpu.store.base import prune_ttls
        from zipkin_tpu.store.tpu import TpuSpanStore

        if not spans:
            return
        with self._lock:
            # Donating sharded ingest must not race an orphaned
            # checkpoint reader (see store.base.SuspectGuard).
            self.ensure_writable()
            for s in spans:
                self.ttls.setdefault(to_signed64(s.trace_id), 1.0)
            prune_ttls(self.ttls, TpuSpanStore.MAX_TTL_ENTRIES)
            if self.pins:
                # Pin-bank arrivals change read answers before the
                # commit bumps the frontier — invalidate cached reads.
                self._bump_read_epoch()
            self.pins.note_write(to_signed64, spans)
            self._apply_locked(list(spans))

    def _apply_locked(self, spans) -> None:  # called-under: _lock
        from zipkin_tpu.store.base import should_index
        from zipkin_tpu.store.tpu import _next_pow2, name_lc_ids

        groups = [[] for _ in range(self.n)]
        for s in spans:
            groups[self._shard_of(s.trace_id)].append(s)
        # One launch per shard must fit every ring (span AND annotation):
        # colliding slot scatters within a launch are implementation-
        # defined (see TpuSpanStore._chunk_columnar). Split-and-retry;
        # a single span fatter than an annotation ring gets truncated.
        c = self.config
        # A launch's unresolved children must also fit the pending ring
        # without self-collision (the same bound TpuSpanStore applies in
        # _max_chunk_spans): pslot = (pend_pos + rank) % pending_slots
        # would scatter colliding slots within one launch otherwise.
        cap = max(1, min(c.capacity // 2, c.pending_slots))

        def oversized(g):
            return (len(g) > cap
                    or sum(len(s.annotations) for s in g) > c.ann_capacity
                    or sum(len(s.binary_annotations) for s in g)
                    > c.bann_capacity)

        if any(oversized(g) for g in groups):
            if len(spans) > 1:
                mid = len(spans) // 2
                self._apply_locked(spans[:mid])
                self._apply_locked(spans[mid:])
                return
            import dataclasses

            s = spans[0]
            spans = [dataclasses.replace(
                s,
                annotations=tuple(s.annotations[:c.ann_capacity]),
                binary_annotations=tuple(
                    s.binary_annotations[:c.bann_capacity]
                ),
            )]
            groups = [[] for _ in range(self.n)]
            groups[self._shard_of(s.trace_id)] = spans
        batches = [self.codec.encode(g) for g in groups]
        parts = []
        for g, batch in zip(groups, batches):
            indexable = np.fromiter(
                (should_index(s) for s in g), bool, len(g)
            )
            lc = name_lc_ids(batch, self.dicts, self._name_lc)
            parts.append((batch, lc, indexable))
        unit = self._build_unit(parts)
        if self.wal is not None:
            # Journal BEFORE the donating commit (ack-after-append,
            # docs/DURABILITY.md) and under self._lock, so append
            # order == encode order == commit order — the property
            # the dictionary-delta replay chain depends on.
            unit = unit._replace(wal_seq=self._journal_unit(parts))
        if self._pipeline is not None:
            # Pipelined sharded ingest: stage 2 device_puts via
            # stage_unit, stage 3 runs _commit_unit — all shards'
            # commits ride one fused mesh launch per unit.
            self._pipeline.feed(unit)
            return
        unit = unit._replace(db=self.stage_unit(unit.db))
        self._commit_unit(unit)

    def _build_unit(self, parts):
        """Host stage-1 body shared by the serial writer, the ingest
        pipeline, and WAL replay: pad every shard's encoded part to
        fleet-wide buckets (the single store's rule: ``_next_pow2``
        spans, ``_pad_rows`` annotation and binary rows), stack
        host-side, and compute each
        shard's sketch-mirror delta from the PRE-PAD columns. ``parts``
        is one (SpanBatch, name_lc, indexable) triple per shard, in
        shard order. Journaled parts replayed through this same body
        re-cut bitwise-identical launches (wal/recovery)."""
        from zipkin_tpu.aggregate import windows as win_mod
        from zipkin_tpu.store.pipeline import IngestUnit
        from zipkin_tpu.store.tpu import _next_pow2, _pad_rows

        batches = [b for b, _, _ in parts]
        pad_s = _next_pow2(max(b.n_spans for b in batches))
        pad_a = _pad_rows(max(b.n_annotations for b in batches))
        pad_b = _pad_rows(max(b.n_binary for b in batches))
        if self.config.window_enabled:
            ea, eb = win_mod.error_ids(self.dicts)
            err_of = lambda b: win_mod.span_error_flags(b, ea, eb)  # noqa: E731
        else:
            err_of = lambda b: None  # noqa: E731 — flag lowers out
        dbs = [
            dev.make_device_batch(
                b, lc, ix,
                pad_spans=pad_s, pad_anns=pad_a, pad_banns=pad_b,
                error_flag=err_of(b),
            )
            for b, lc, ix in parts
        ]
        sketch = tuple(
            m.delta_of([part])
            for m, part in zip(self._mirrors, parts)
        )
        return IngestUnit(
            stack_batches(dbs),
            sum(b.n_spans for b in batches),
            sum(b.n_annotations for b in batches),
            sum(b.n_binary for b in batches),
            self.n, False, sketch=sketch,
            # incoming from the HOST batches: reading it off the
            # stacked device pytree inside the write-lock hold was a
            # device sync stalling every reader behind the commit
            # (graftlint sync-under-lock, the r10 group-commit stall
            # class).
            incoming=max(b.n_spans for b in batches),
        )

    def stage_unit(self, db):
        """Stage-2 H2D: place the host-stacked batch pytree over the
        mesh. The pipeline's stage thread calls this hook (see
        IngestPipeline); the serial path runs it inline."""
        return jax.device_put(db, NamedSharding(self.mesh, P(self.axis)))

    def _commit_unit(self, unit) -> None:
        """Stage 3 — the ONE donating commit body behind the serial
        writer, the pipeline's commit thread, and WAL replay (the
        TpuSpanStore._commit_unit contract over the mesh). The sharded
        ingest launch (and its in-graph psum/pmax summary) runs under
        the WRITE lock, which excludes every reader — so ingest
        collectives can never overlap a query collective and need no
        _coll_lock. Mirror deltas fold inside the same hold, BEFORE
        the frontier bump, so a sketch-tier read at frontier F already
        includes commit F."""
        self.ensure_writable()
        with self._rw.write():
            self.inner.ingest(unit.db, incoming=unit.incoming)
            if unit.sketch is not None:
                for m, d in zip(self._mirrors, unit.sketch):
                    m.apply(d)
            self._step_seq += 1
            if unit.wal_seq is not None:
                self._wal_applied = unit.wal_seq

    # -- durable write-ahead log (zipkin_tpu.wal.sharded) ----------------

    def attach_wal(self, wal) -> None:
        """Journal every subsequent launch unit into ``wal`` (a
        ShardedWal: one segment log per shard + the group-commit epoch
        log) before its donating commit. Attach before live writes —
        units committed earlier are only covered by checkpoints. The
        store does not own the log's lifecycle."""
        from zipkin_tpu.wal.record import dict_sizes

        with self._lock:
            self.wal = wal
            self._wal_marks = dict_sizes(self.dicts)

    def _journal_unit(self, parts) -> int:  # called-under: _lock
        """Append one sharded launch unit — every shard's part plus
        the dictionary entries its encode step added — as one
        group-commit epoch; returns the epoch sequence. Runs on the
        encoding thread under self._lock."""
        from zipkin_tpu.wal.record import dict_sizes, dump_dict_deltas

        sizes, deltas = dump_dict_deltas(self.dicts, self._wal_marks)
        seq = self.wal.append_unit(parts, self._wal_marks, deltas)
        self._wal_marks = sizes
        return seq

    def wal_sync(self) -> None:
        """Force the attached WAL durable; no-op without one."""
        if self.wal is not None:
            self.wal.sync()

    # -- pipelined ingest lifecycle (store/pipeline) ---------------------

    PIPELINE_DEPTH = 8
    STAGE_BUFFERS = 2

    def start_pipeline(self, depth: Optional[int] = None,
                       stage_buffers: Optional[int] = None):
        """Switch the write path to the three-stage ingest pipeline:
        apply() becomes stage 1 (encode + partition + pad + host
        stack, outside the device critical section), a stage thread
        places units over the mesh (stage_unit), and a commit thread
        holds the write lock only for the fused all-shard donating
        swap — the PR 4 pipeline driving every shard's commit body
        concurrently. Same quiesce rules as TpuSpanStore."""
        from zipkin_tpu.store.pipeline import IngestPipeline

        with self._lock:
            if self._pipeline is not None:
                raise RuntimeError("ingest pipeline already running")
            self._pipeline = IngestPipeline(
                self, depth or self.PIPELINE_DEPTH,
                registry=self._registry,
                stage_buffers=stage_buffers or self.STAGE_BUFFERS)
            return self._pipeline

    def drain_pipeline(self) -> None:
        """Block until every accepted batch is committed on every
        shard (no-op when no pipeline runs); re-raises a parked
        pipeline error."""
        with self._lock:
            p = self._pipeline
        if p is not None:
            p.drain()

    def stop_pipeline(self, raise_errors: bool = True) -> None:
        """Drain, stop the pipeline threads, and return to the serial
        write path — quiesced UNDER the encode lock with the pipeline
        still published (two concurrent device writers would break the
        ring-scatter contract; see TpuSpanStore.stop_pipeline)."""
        with self._lock:
            p = self._pipeline
            if p is None:
                return
            p.stop()
            self._pipeline = None
        err = p.take_error()
        if raise_errors and err is not None:
            raise err

    @contextlib.contextmanager
    def pipelined(self, depth: Optional[int] = None):
        """Scoped pipelined ingest: drains and stops on exit."""
        pipe = self.start_pipeline(depth)
        try:
            yield pipe
        finally:
            self.stop_pipeline()

    # -- query-engine hooks (query/engine.py) ----------------------------

    def write_frontier(self) -> Tuple[int, int]:
        """Monotonic host-mirrored commit frontier — the result-cache
        key component (same contract as TpuSpanStore.write_frontier).
        No device traffic."""
        return (self._step_seq, self._read_epoch)

    def _bump_read_epoch(self) -> None:
        self._read_epoch += 1

    def ensure_sketch_mirror(self):
        """The fleet sketch mirror (FleetMirror over the per-shard
        twins), resynced from the device aggregates if a state swap
        left any shard cold (checkpoint restore) — one batched D2H of
        the stacked arrays (a plain sharded device_get, NOT a
        collective program, so no _coll_lock), after which incremental
        per-commit deltas keep every shard warm with zero device
        traffic."""
        fm = self._fleet_mirror
        if not fm.warm:
            with self._rw.read():
                st = self.states
                host = jax.device_get((
                    st.svc_hist, st.ann_svc_counts, st.name_presence,
                    st.ann_value_counts, st.bann_key_counts,
                    st.hll_traces, st.win_epoch, st.win_counts,
                    st.win_sums, st.win_mm,
                ))
                for i, m in enumerate(self._mirrors):
                    if not m.warm:
                        m.adopt(*(np.asarray(h)[i] for h in host))
        return fm

    DEFAULT_TTL_S = 1.0

    def set_time_to_live(self, trace_id: int, ttl_seconds: float) -> None:
        from zipkin_tpu.columnar.encode import to_signed64
        from zipkin_tpu.store.base import fill_pin

        tid = to_signed64(trace_id)
        with self._lock:
            self.ttls[tid] = ttl_seconds
            pin = ttl_seconds > self.DEFAULT_TTL_S
            if not pin:
                self.pins.unpin(tid)
            # Pin/unpin changes read answers without a commit — the
            # result cache must not serve the stale frontier.
            self._bump_read_epoch()
        if pin:
            fill_pin(self.pins, self._lock, tid, lambda: (
                self.get_spans_by_trace_ids([trace_id]) or [[]])[0])
            with self._lock:
                self._bump_read_epoch()

    def get_time_to_live(self, trace_id: int) -> float:
        from zipkin_tpu.columnar.encode import to_signed64

        with self._lock:
            return self.ttls[to_signed64(trace_id)]

    # -- mapped query kernels (cached per static shape) ------------------

    def _kernel(self, key, build):
        # The cache dict is shared by every API handler thread
        # (graftlint guarded-by caught the old unlocked check-then-
        # set). build() traces OUTSIDE the hold: tracing can take
        # seconds and needs no cache state — a duplicate build for a
        # racing key is cheap, a lock held across jax tracing is not.
        with self._kernels_lock:
            fn = self._kernels.get(key)
        if fn is None:
            fn = build()
            with self._kernels_lock:
                fn = self._kernels.setdefault(key, fn)
        return fn

    def _collect(self, kernel, *args):
        """Launch one mapped collective kernel and fetch its result,
        serialized behind the collective-launch leaf lock: concurrent
        shard_map programs deadlock the XLA CPU collective rendezvous
        (see _coll_lock). Callers hold the read lock; the launch AND
        the device_get complete inside the hold, so no second
        collective can be in flight."""
        with self._coll_lock:
            self._coll_launches += 1
            return jax.device_get(kernel(*args))

    def _unstack(self, state):
        return jax.tree.map(lambda x: x[0], state)

    def _q_by_service(self, limit: int):
        def build():
            def fn(state, svc, name_lc, end_ts):
                st = self._unstack(state)
                mat = dev.query_trace_ids_by_service(
                    st, svc, name_lc, end_ts, limit
                )
                return mat[None]

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(self.axis), P(), P(), P()),
                out_specs=P(self.axis), check_vma=False,
            ))

        return self._kernel(("svc", limit), build)

    def _iq_by_service(self, limit: int, named: bool):
        """Index fast-path kernel: per-shard O(depth) bucket read +
        completeness flag (see dev.iquery_trace_ids_by_service). The
        named/unnamed branch is host state, so it keys the kernel
        cache, not a traced conditional."""
        c = self.config

        def build():
            def fn(state, svc, name_lc, end_ts):
                st = self._unstack(state)
                lay, _, _ = c.cand_layout
                if named:
                    fam = lay[dev.StoreConfig.CAND_NAME]
                    mat, complete, wm = dev._iq_verify_impl(
                        st.cand_idx, st.cand_pos, st.cand_wm,
                        st.row_gid, st.indexable, st.trace_id, st.ts_last,
                        c.capacity, fam, min(limit, fam[3]),
                        (svc.astype(jnp.int32), name_lc.astype(jnp.int32)),
                        end_ts, st.key_tab, st.key_wm, st.write_pos,
                        st.counters["key_claim_drops"],
                    )
                else:
                    fam = lay[dev.StoreConfig.CAND_SVC]
                    mat, complete, wm = dev._iq_service_impl(
                        st.cand_idx, st.cand_pos, st.cand_wm,
                        st.row_gid, st.indexable, st.trace_id,
                        st.ts_last, c.capacity, fam,
                        min(limit, fam[3]), svc, end_ts,
                    )
                return mat[None], complete[None], wm[None]

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(self.axis), P(), P(), P()),
                out_specs=(P(self.axis),) * 3, check_vma=False,
            ))

        return self._kernel(("isvc", limit, named), build)

    def _iq_by_annotation(self, limit: int, mode: str):
        """mode: 'ann' (user annotation value), 'bkey' (binary key
        only), or 'bval' (binary key + 1-2 value forms)."""
        c = self.config

        def build():
            def fn(state, svc, ann, bkey, bval, bval2, end_ts):
                st = self._unstack(state)
                lay, _, _ = c.cand_layout
                svc32 = svc.astype(jnp.int32)
                if mode == "ann":
                    fam = lay[dev.StoreConfig.CAND_ANN]
                    mat, complete, wm = dev._iq_verify_impl(
                        st.cand_idx, st.cand_pos, st.cand_wm,
                        st.row_gid, st.indexable, st.trace_id, st.ts_last,
                        c.capacity, fam, min(limit, fam[3]),
                        (svc32, ann.astype(jnp.int32)), end_ts,
                        st.key_tab, st.key_wm, st.write_pos,
                        st.counters["key_claim_drops"],
                        st.ann_poison,
                    )
                elif mode == "bkey":
                    fam = lay[dev.StoreConfig.CAND_BANN]
                    mat, complete, wm = dev._iq_verify_impl(
                        st.cand_idx, st.cand_pos, st.cand_wm,
                        st.row_gid, st.indexable, st.trace_id, st.ts_last,
                        c.capacity, fam, min(limit, fam[3]),
                        (svc32, bkey.astype(jnp.int32), jnp.int32(-1)),
                        end_ts, st.key_tab, st.key_wm, st.write_pos,
                        st.counters["key_claim_drops"],
                        st.ann_poison,
                    )
                else:
                    fam = lay[dev.StoreConfig.CAND_BANN]
                    # 2-bucket window: clamp to 2*depth, not depth (see
                    # dev.iquery_trace_ids_by_annotation).
                    mat, complete, wm = dev._iq_verify2_impl(
                        st.cand_idx, st.cand_pos, st.cand_wm,
                        st.row_gid, st.indexable, st.trace_id, st.ts_last,
                        c.capacity, fam, min(limit, 2 * fam[3]),
                        (svc32, bkey.astype(jnp.int32),
                         bval.astype(jnp.int32)),
                        (svc32, bkey.astype(jnp.int32),
                         bval2.astype(jnp.int32)),
                        end_ts, st.key_tab, st.key_wm, st.write_pos,
                        st.counters["key_claim_drops"],
                        st.ann_poison,
                    )
                return mat[None], complete[None], wm[None]

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(self.axis),) + (P(),) * 6,
                out_specs=(P(self.axis),) * 3, check_vma=False,
            ))

        return self._kernel(("iann", limit, mode), build)

    def _q_by_annotation(self, limit: int):
        def build():
            def fn(state, svc, ann, bkey, bval, bval2, end_ts):
                st = self._unstack(state)
                mat = dev.query_trace_ids_by_annotation(
                    st, svc, ann, bkey, bval, bval2, end_ts, limit
                )
                return mat[None]

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(self.axis),) + (P(),) * 6,
                out_specs=P(self.axis), check_vma=False,
            ))

        return self._kernel(("ann", limit), build)

    def _q_durations(self):
        def build():
            def fn(state, qids):
                st = self._unstack(state)
                mat = dev.query_durations(st, qids)
                return jnp.stack([
                    _pmax64(mat[0], self.axis),
                    _pmax64(mat[1], self.axis),
                    _pmin64(mat[2], self.axis),
                    _pmax64(mat[3], self.axis),
                ])

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=P(), check_vma=False,
            ))

        return self._kernel(("durations",), build)

    def _iq_durations(self):
        """Trace-membership fast path (dev.iquery_durations) with the
        cross-shard min/max merge; ``exact`` requires every shard's
        queried buckets to pass the displaced-gid gate."""

        def build():
            def fn(state, qids):
                st = self._unstack(state)
                mat, exact = dev.iquery_durations(st, qids)
                merged = jnp.stack([
                    _pmax64(mat[0], self.axis),
                    _pmax64(mat[1], self.axis),
                    _pmin64(mat[2], self.axis),
                    _pmax64(mat[3], self.axis),
                ])
                all_exact = jax.lax.pmin(
                    exact.astype(jnp.int32), self.axis
                )
                return merged, all_exact

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=(P(), P()), check_vma=False,
            ))

        return self._kernel(("idurations",), build)

    def _durations_mat(self, qids):
        with self._rw.read():
            if self.config.use_index:
                mat, exact = self._collect(
                    self._iq_durations(), self.states, qids)
                if exact:
                    return mat
            return self._collect(self._q_durations(), self.states, qids)

    def _iq_gather(self, k_s: int, k_a: int, k_b: int):
        """Per-shard trace-membership gather (dev.iquery_gather_trace_rows)
        + a cross-shard AND of the exactness gates."""

        def build():
            def fn(state, qids):
                st = self._unstack(state)
                counts, s, a, b, exact = dev.iquery_gather_trace_rows(
                    st, qids, k_s, k_a, k_b
                )
                all_exact = jax.lax.pmin(
                    exact.astype(jnp.int32), self.axis
                )
                return counts[None], s[None], a[None], b[None], all_exact

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=(P(self.axis),) * 4 + (P(),), check_vma=False,
            ))

        return self._kernel(("igather", k_s, k_a, k_b), build)

    def _gather_via_index(self, qids):
        """Sharded analogue of TpuSpanStore._gather_via_index: returns
        the per-shard gather payload, or None when any shard's queried
        bucket fails its gate (caller scans)."""
        from zipkin_tpu.store.base import index_gather_with_escalation

        def fetch(k_s, k_a, k_b):
            counts, s_m, a_m, b_m, exact = self._collect(
                self._iq_gather(k_s, k_a, k_b), self.states, qids)
            return (bool(exact), int(counts[:, 0].max()),
                    int(counts[:, 1].max()), int(counts[:, 2].max()),
                    (counts, s_m, a_m, b_m))

        return index_gather_with_escalation(self.config, len(qids), fetch)

    def _q_gather(self, k_s: int, k_a: int, k_b: int):
        def build():
            def fn(state, qids):
                st = self._unstack(state)
                counts, s, a, b = dev.gather_trace_rows(
                    st, qids, k_s, k_a, k_b
                )
                return counts[None], s[None], a[None], b[None]

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=P(self.axis), check_vma=False,
            ))

        return self._kernel(("gather", k_s, k_a, k_b), build)

    def _cat_kernel(self, key: str):
        """One small collective per catalog key — all-reducing the whole
        catalog to read one scalar/row would waste device time on hot
        paths like the sampler's stored_span_count tick."""

        def build():
            def fn(state):
                st = self._unstack(state)
                if key == "hll_traces":
                    return jax.lax.pmax(st.hll_traces, self.axis)
                if key == "spans_seen":
                    return jax.lax.psum(st.counters["spans_seen"],
                                        self.axis)
                return jax.lax.psum(getattr(st, key), self.axis)

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis),),
                out_specs=P(), check_vma=False,
            ))

        return self._kernel(("cat", key), build)

    # -- id lookups ------------------------------------------------------

    def _svc_id(self, service_name: str):
        return self.dicts.services.get(service_name.lower())

    @staticmethod
    def _shard_candidates(mats: np.ndarray, k: int):
        """Flatten per-shard candidate matrices [n, 3, kk]; truncated if
        ANY shard filled its window. The window bound is the kernel's
        ACTUAL slot count (kk = mats.shape[-1]), which may be clamped
        below the requested k by bucket geometry — comparing against
        the requested k would let a full clamped window read as
        untruncated."""
        kk = min(k, mats.shape[-1])
        cands, truncated = [], False
        for sh in range(mats.shape[0]):
            n_valid = 0
            for t, ts, v in zip(*mats[sh]):
                if v:
                    cands.append((int(t), int(ts)))
                    n_valid += 1
            truncated |= n_valid >= kk
        return cands, truncated

    def get_trace_ids_by_name(self, service_name, span_name, end_ts,
                              limit):
        """Top-k trace ids by (service[, span name]) via the
        cross-shard dispatcher: concurrent index reads ride ONE
        multi-probe mesh launch (get_trace_ids_multi) instead of
        queueing singly behind _coll_lock."""
        return self._dispatcher.ids(
            ("name", service_name, span_name, end_ts, limit))

    def _get_trace_ids_by_name_direct(self, service_name, span_name,
                                      end_ts, limit):
        from zipkin_tpu.store.base import topk_ids_with_escalation

        svc = self._svc_id(service_name)
        if svc is None or limit <= 0:
            return []
        if span_name is not None:
            name_lc = self.dicts.span_names.get(span_name.lower())
            if name_lc is None:
                return []
        else:
            name_lc = -1

        def fetch(k):
            with self._rw.read():
                mats = self._collect(
                    self._q_by_service(k), self.states, jnp.int32(svc),
                    jnp.int32(name_lc), jnp.int64(end_ts),
                )
            return self._shard_candidates(mats, k)

        def index_fetch(k):
            with self._rw.read():
                mats, complete, wm = self._collect(
                    self._iq_by_service(k, name_lc >= 0), self.states,
                    jnp.int32(svc), jnp.int32(name_lc),
                    jnp.int64(end_ts),
                )
            cands, truncated = self._shard_candidates(mats, k)
            # window > len(cands) ⇔ no shard's window truncated: only
            # then may the underfull-equals-complete claim fire.
            window = len(cands) if truncated else len(cands) + 1
            return cands, bool(np.all(complete)), int(np.max(wm)), window

        from zipkin_tpu.store.base import (index_first_topk,
                                           service_scan_only)

        if self.config.use_index and not service_scan_only(
                svc, self.config):
            return index_first_topk(
                limit, self.config.ann_capacity, index_fetch, fetch
            )
        return topk_ids_with_escalation(
            limit, self.config.ann_capacity, fetch
        )

    def get_trace_ids_by_annotation(self, service_name, annotation,
                                    value, end_ts, limit):
        """Top-k trace ids by annotation via the cross-shard
        dispatcher (see get_trace_ids_by_name)."""
        return self._dispatcher.ids(
            ("annotation", service_name, annotation, value, end_ts,
             limit))

    def _get_trace_ids_by_annotation_direct(self, service_name,
                                            annotation, value, end_ts,
                                            limit):
        from zipkin_tpu.models.constants import CORE_ANNOTATIONS
        from zipkin_tpu.store.base import resolve_annotation_query

        if annotation in CORE_ANNOTATIONS or limit <= 0:
            return []
        svc = self._svc_id(service_name)
        if svc is None:
            return []
        from zipkin_tpu.store.base import topk_ids_with_escalation

        resolved = resolve_annotation_query(self.dicts, annotation, value)
        if resolved is None:
            return []
        ann_value, bann_key, bann_value, bann_value2 = resolved

        def fetch(k):
            with self._rw.read():
                mats = self._collect(
                    self._q_by_annotation(k), self.states,
                    jnp.int32(svc), jnp.int32(ann_value),
                    jnp.int32(bann_key), jnp.int32(bann_value),
                    jnp.int32(bann_value2), jnp.int64(end_ts),
                )
            return self._shard_candidates(mats, k)

        if ann_value >= 0:
            mode = "ann"
        elif bann_value < 0 and bann_value2 < 0:
            mode = "bkey"
        else:
            mode = "bval"
        bv1 = bann_value if bann_value >= 0 else bann_value2
        bv2 = bann_value2 if bann_value2 >= 0 else bv1
        # Mixed user-annotation + binary-key names OR across families:
        # only the scan sees both sides.
        mixed = ann_value >= 0 and bann_key >= 0

        def index_fetch(k):
            with self._rw.read():
                mats, complete, wm = self._collect(
                    self._iq_by_annotation(k, mode), self.states,
                    jnp.int32(svc), jnp.int32(ann_value),
                    jnp.int32(bann_key), jnp.int32(bv1),
                    jnp.int32(bv2), jnp.int64(end_ts),
                )
            cands, truncated = self._shard_candidates(mats, k)
            window = len(cands) if truncated else len(cands) + 1
            return cands, bool(np.all(complete)), int(np.max(wm)), window

        from zipkin_tpu.store.base import (index_first_topk,
                                           service_scan_only)

        c = self.config
        if c.use_index and not mixed and not service_scan_only(svc, c):
            return index_first_topk(
                limit, c.ann_capacity + c.bann_capacity, index_fetch,
                fetch,
            )
        return topk_ids_with_escalation(
            limit, c.ann_capacity + c.bann_capacity, fetch
        )

    def _iq_multi(self, n: int, k: int):
        """Batched multi-probe index kernel over the mesh: every probe
        reads its bucket on EVERY shard in one launch (dev._iq_multi_impl
        under shard_map); the host merges per-shard candidates."""
        c = self.config

        def build():
            k_max = max(fam[3] for fam in c.cand_layout[0])

            def fn(state, b_base, s_base, n_b, depth, key1, key2, key3,
                   three, is_svc, end_ts, poison_on):
                st = self._unstack(state)
                mat, complete, wm = dev._iq_multi_impl(
                    st.cand_idx, st.cand_pos, st.cand_wm, st.row_gid,
                    st.indexable, st.trace_id, st.ts_last,
                    c.capacity, k, k_max,
                    b_base, s_base, n_b, depth, key1, key2, key3,
                    three, is_svc, end_ts, poison_on,
                    st.ann_poison, st.write_pos, st.key_tab, st.key_wm,
                    st.counters["key_claim_drops"],
                )
                return mat[None], complete[None], wm[None]

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh,
                in_specs=(P(self.axis),) + (P(),) * 11,
                out_specs=(P(self.axis),) * 3, check_vma=False,
            ))

        return self._kernel(("imulti", n, k), build)

    def get_trace_ids_multi(self, queries):
        """Batched index read over the mesh: all queries' probes ride
        one launch; distrusted buckets fall back to the singular sharded
        paths. Same trust policy as TpuSpanStore.get_trace_ids_multi
        (shared resolve/gate helpers), with per-shard saturation folded
        into each probe's flag."""
        from zipkin_tpu.store.base import ReadSpanStore
        from zipkin_tpu.store.tpu import (
            build_probe_arrays,
            gate_multi_probes,
            resolve_multi_probes,
        )

        c = self.config
        if not c.use_index or not queries:
            return ReadSpanStore.get_trace_ids_multi(self, queries)
        results, probes, limits, fallback = resolve_multi_probes(
            c, self.dicts, queries
        )
        if probes:
            # Unlike the single-device path, the mesh kernel takes the
            # clamped k directly (k_eff); the raw request k is unused.
            arrs, _, k_eff = build_probe_arrays(c, probes, limits)
            order = ("b_base", "s_base", "n_b", "depth", "key1", "key2",
                     "key3", "three", "is_svc", "end_ts", "poison_on")
            with self._rw.read():
                mats, completes, wms = self._collect(
                    self._iq_multi(len(arrs["key1"]), k_eff),
                    self.states,
                    *(jnp.asarray(arrs[name]) for name in order),
                )
            per_probe = []
            for pi, p in enumerate(probes):
                window_pi = min(k_eff, p[1][3])
                cands = []
                saturated = False
                for sh in range(mats.shape[0]):
                    mat = mats[sh, pi]
                    shard_cands = [
                        (int(t), int(ts))
                        for t, ts, v in zip(mat[0], mat[1], mat[2]) if v
                    ]
                    saturated |= len(shard_cands) >= window_pi
                    cands.extend(shard_cands)
                per_probe.append((
                    cands, bool(np.all(completes[:, pi])),
                    int(np.max(wms[:, pi])), saturated,
                ))
            gated = gate_multi_probes(probes, limits, per_probe)
            for qi, ids in gated.items():
                if ids is None:
                    fallback.append(qi)
                else:
                    results[qi] = ids
        for qi in fallback:
            q = queries[qi]
            if q[0] == "name":
                results[qi] = self.get_trace_ids_by_name(*q[1:])
            else:
                results[qi] = self.get_trace_ids_by_annotation(*q[1:])
        return [r if r is not None else [] for r in results]

    # -- trace reads -----------------------------------------------------

    def _sorted_qids(self, trace_ids) -> np.ndarray:
        from zipkin_tpu.columnar.encode import to_signed64

        # Unique for the same reason as TpuSpanStore._sorted_qids.
        return np.unique(
            np.asarray([to_signed64(t) for t in trace_ids], np.int64)
        )

    def traces_exist(self, trace_ids):
        from zipkin_tpu.columnar.encode import to_signed64

        if not trace_ids:
            return set()
        canon = {to_signed64(t): t for t in trace_ids}
        qids = self._sorted_qids(trace_ids)
        from zipkin_tpu.store.base import exist_from_duration_mat

        mat = self._durations_mat(qids)
        return exist_from_duration_mat(canon, qids, mat[0], self.pins,
                                       self._lock)

    def get_traces_duration(self, trace_ids):
        from zipkin_tpu.columnar.encode import to_signed64
        from zipkin_tpu.store.base import durations_from_mat

        if not trace_ids:
            return []
        canon = {to_signed64(t): t for t in trace_ids}
        qids = self._sorted_qids(trace_ids)
        mat = self._durations_mat(qids)
        return durations_from_mat(trace_ids, canon, qids, mat, self.pins,
                                  self._lock)

    def get_spans_by_trace_ids(self, trace_ids):
        from zipkin_tpu.columnar.encode import to_signed64
        from zipkin_tpu.store.tpu import decode_gathered

        if not trace_ids:
            return []
        from zipkin_tpu.store.base import (
            apply_pin_merges,
            gather_with_escalation,
        )

        qids = self._sorted_qids(trace_ids)
        with self._rw.read():
            payload = None
            if self.config.use_index:
                payload = self._gather_via_index(qids)
            if payload is None:
                def fetch(k_s, k_a, k_b):
                    counts, s_m, a_m, b_m = self._collect(
                        self._q_gather(k_s, k_a, k_b), self.states,
                        qids)
                    return (int(counts[:, 0].max()),
                            int(counts[:, 1].max()),
                            int(counts[:, 2].max()),
                            (counts, s_m, a_m, b_m))

                payload = gather_with_escalation(self.config, fetch)
            counts, s_m, a_m, b_m = payload
        spans = []
        for sh in range(self.n):
            spans.extend(decode_gathered(
                self.codec, int(counts[sh, 0]), int(counts[sh, 1]),
                int(counts[sh, 2]), s_m[sh], a_m[sh], b_m[sh],
            ))
        by_tid: Dict[int, list] = {}
        for span in spans:
            by_tid.setdefault(span.trace_id, []).append(span)
        with self._lock:
            apply_pin_merges(self.pins, by_tid, trace_ids, to_signed64)
        return [
            by_tid[to_signed64(tid)]
            for tid in trace_ids
            if to_signed64(tid) in by_tid
        ]

    def get_spans_by_trace_id(self, trace_id: int):
        found = self.get_spans_by_trace_ids([trace_id])
        return found[0] if found else []

    # -- name catalogs / analytics --------------------------------------

    # Catalog keys the fused bundle kernel serves — everything the
    # dispatcher may merge into ONE launch. Keys outside this set
    # (none today) would fall back to their singular kernels.
    CAT_BUNDLE_KEYS = frozenset((
        "svc_hist", "ann_svc_counts", "name_presence",
        "ann_value_counts", "bann_key_counts", "spans_seen",
        "hll_traces",
    ))

    def _cat_bundle_kernel(self):
        """ONE collective program all-reducing every catalog array the
        dispatcher can serve: ≥2 concurrent catalog reads sharing a
        micro-window cost one launch total instead of one launch each
        behind _coll_lock."""

        def build():
            def fn(state):
                st = self._unstack(state)
                out = {k: jax.lax.psum(getattr(st, k), self.axis)
                       for k in ("svc_hist", "ann_svc_counts",
                                 "name_presence", "ann_value_counts",
                                 "bann_key_counts")}
                out["spans_seen"] = jax.lax.psum(
                    st.counters["spans_seen"], self.axis)
                out["hll_traces"] = jax.lax.pmax(st.hll_traces,
                                                 self.axis)
                return out

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis),),
                out_specs=P(), check_vma=False,
            ))

        return self._kernel(("cat_bundle",), build)

    def _fetch_cat_bundle(self):
        """Every dispatcher-servable catalog entry: one launch, one
        D2H (the dispatcher's fused path)."""
        with self._rw.read():
            with self._coll_lock:
                self._coll_launches += 1
                return jax.device_get(
                    self._cat_bundle_kernel()(self.states))

    def _cat_direct(self, key):
        """Read-locked fetch of ONE collective catalog entry — the
        cheap singular kernel, for a read with nothing to share a
        launch with."""
        with self._rw.read():
            with self._coll_lock:
                self._coll_launches += 1
                return jax.device_get(self._cat_kernel(key)(self.states))

    def _cat(self, key, row=None):
        """One catalog entry (optionally one row of it), via the
        cross-shard dispatcher: concurrent catalog reads coalesce into
        one fused bundle launch (parallel/dispatch)."""
        return self._dispatcher.cat(key, row)

    def get_all_service_names(self):
        present = self._cat("ann_svc_counts") > 0
        d = self.dicts.services
        out = {
            d.decode(i) for i in np.flatnonzero(present)
            if i < len(d) and d.decode(i)
        }
        # Dictionary-overflow services can't mark the presence array —
        # list the ones any shard's rings still hold as hosts (see
        # TpuSpanStore.get_all_service_names; OR across shards rides
        # a psum of the per-shard presence).
        S = self.config.max_services
        n_over = len(d) - S
        if n_over > 0:
            pad = 1 << max(0, (n_over - 1)).bit_length()

            def build():
                def fn(state):
                    st = self._unstack(state)
                    pres = dev.overflow_service_presence(st, pad)
                    return jax.lax.psum(
                        pres.astype(jnp.int32), self.axis) > 0

                return jax.jit(compat_shard_map(
                    fn, mesh=self.mesh, in_specs=(P(self.axis),),
                    out_specs=P(), check_vma=False,
                ))

            with self._rw.read():
                pres = self._collect(
                    self._kernel(("overflow_presence", pad), build),
                    self.states)
            out.update(
                name for i in np.flatnonzero(pres[:n_over])
                if (name := d.decode(S + int(i)))
            )
        return out

    def _scan_cat_kernel(self):
        """Overflow-service catalog reads: per-shard ring scans
        (dev.svc_scan_catalog) psum-ed across the mesh — the
        [max_services]-sized catalog arrays cannot represent services
        past the dictionary cap, and a clamped row read would serve
        service max_services-1's data under the wrong name."""
        def build():
            def fn(state, svc):
                st = self._unstack(state)
                rows = dev.svc_scan_catalog(st, svc)
                return tuple(jax.lax.psum(r, self.axis) for r in rows)

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=(P(),) * 4, check_vma=False,
            ))

        return self._kernel(("scan_catalog",), build)

    def _svc_catalog_scan(self, svc: int):
        # One-entry memo keyed on (svc, write position): the kernel
        # returns all four catalog rows per launch — see
        # TpuSpanStore._svc_catalog_scan.
        key = (svc, self.inner._wp_upper)
        cached = getattr(self, "_svc_scan_memo", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        with self._rw.read():
            rows = self._collect(self._scan_cat_kernel(), self.states,
                                 jnp.int32(svc))
        self._svc_scan_memo = (key, rows)
        return rows

    def get_span_names(self, service: str):
        svc = self._svc_id(service)
        if svc is None:
            return set()
        if service_scan_only(svc, self.config):
            row = self._svc_catalog_scan(svc)[0] > 0
        else:
            row = self._cat("name_presence", svc) > 0
        d = self.dicts.span_names
        return {
            d.decode(i) for i in np.flatnonzero(row)
            if i < len(d) and d.decode(i)
        }

    def _summary_kernel(self):
        def build():
            def fn(state):
                return _summarize(self._unstack(state), self.axis)

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis),),
                out_specs=P(), check_vma=False,
            ))

        return self._kernel(("summary",), build)

    def _deps_range_kernel(self):
        def build():
            def fn(state, start_ts, end_ts):
                st = self._unstack(state)
                bank = dev.dep_moments_in_range(st, start_ts, end_ts)
                banks = jax.lax.all_gather(bank, self.axis)
                # ts range rides the same launch — running the full
                # summary kernel just to clip two scalars would
                # all-reduce every catalog array per windowed query.
                ts_min = jnp.maximum(_pmin64(st.ts_min, self.axis),
                                     start_ts)
                ts_max = jnp.minimum(_pmax64(st.ts_max, self.axis),
                                     end_ts)
                return M.reduce_moments(banks, axis=0), ts_min, ts_max

            return jax.jit(compat_shard_map(
                fn, mesh=self.mesh, in_specs=(P(self.axis), P(), P()),
                out_specs=(P(), P(), P()), check_vma=False,
            ))

        return self._kernel(("deps_range",), build)

    def get_dependencies(self, start_ts=None, end_ts=None):
        from zipkin_tpu.aggregate.job import dependencies_from_bank

        # Sweep first — but only when something was written since the
        # last sweep, so read-only dependency polling stays a pure read
        # (same contract as TpuSpanStore.get_dependencies).
        if self.inner._batches_since_sweep:
            with self._lock:
                if self.inner._batches_since_sweep:
                    # The sweep step donates state buffers — same
                    # suspect gate as every other donating path.
                    self.ensure_writable()
                    with self._rw.write():
                        self.inner.sweep()
        with self._rw.read():
            if start_ts is None and end_ts is None:
                with self._coll_lock:
                    self._coll_launches += 1
                    summary = self._summary_kernel()(self.states)
                    bank, ts_min, ts_max = jax.device_get(
                        (summary["dep_moments"], summary["ts_min"],
                         summary["ts_max"])
                    )
            else:
                s = dev.I64_MIN if start_ts is None else int(start_ts)
                e = dev.I64_MAX if end_ts is None else int(end_ts)
                bank, ts_min, ts_max = self._collect(
                    self._deps_range_kernel(), self.states,
                    jnp.int64(s), jnp.int64(e)
                )
        return dependencies_from_bank(
            bank, self.dicts.services, self.config.max_services,
            float(ts_min), float(ts_max),
        )

    def service_duration_quantiles(self, service: str, qs):
        from zipkin_tpu.ops import quantile as Q

        svc = self._svc_id(service)
        if svc is None:
            return None
        c = self.config
        gamma = (1.0 + c.quantile_alpha) / (1.0 - c.quantile_alpha)
        if service_scan_only(svc, c):
            counts = self._svc_catalog_scan(svc)[1]
        else:
            counts = self._cat("svc_hist", svc)
        return Q.quantiles_host(counts, gamma, 1.0, qs)

    def top_annotations(self, service: str, k: int = 10):
        svc = self._svc_id(service)
        if svc is None:
            return []
        if service_scan_only(svc, self.config):
            row = self._svc_catalog_scan(svc)[2]
        else:
            row = self._cat("ann_value_counts", svc)
        order = np.argsort(-row)[:k]
        d = self.dicts.annotations
        return [
            (d.decode(int(i)), int(row[i])) for i in order
            if row[i] > 0 and i < len(d)
        ]

    def top_binary_keys(self, service: str, k: int = 10):
        svc = self._svc_id(service)
        if svc is None:
            return []
        if service_scan_only(svc, self.config):
            row = self._svc_catalog_scan(svc)[3]
        else:
            row = self._cat("bann_key_counts", svc)
        order = np.argsort(-row)[:k]
        d = self.dicts.binary_keys
        return [
            (d.decode(int(i)), int(row[i])) for i in order
            if row[i] > 0 and i < len(d)
        ]

    def estimated_unique_traces(self) -> float:
        from zipkin_tpu.ops import hll

        regs = self._cat("hll_traces")
        return float(hll.estimate(hll.HyperLogLog(regs)))

    def stored_span_count(self) -> float:
        """psum-ed spans_seen across every shard — the sharded flow
        source for the adaptive controller (the ZK group-sum role,
        AdaptiveSampler.scala:204-237)."""
        return float(self._cat("spans_seen"))

    def _counter_blocks(self):
        """(totals dict, per-shard [n, F] block matrix), memoized on
        the host-side write clocks — same fetched-once-per-ingest-step
        contract as TpuSpanStore.counter_block, so scrapes between
        writes cost no device traffic. The per-shard matrix is a plain
        vmap over the stacked states (no collective program, so no
        _coll_lock)."""
        key = (self.inner._wp_upper, self.inner._batches_since_sweep,
               self.inner._archived_lower)
        memo = getattr(self, "_cblock_memo", None)
        if memo is not None and memo[0] == key:
            return dict(memo[1]), memo[2]
        with self._rw.read():
            blocks = np.asarray(jax.device_get(jax.vmap(
                dev.counter_block.__wrapped__
            )(self.inner.states)))
        out: Dict[str, float] = {}
        for i, name in enumerate(dev.COUNTER_BLOCK_FIELDS):
            col = blocks[:, i]
            if name == "ts_min":
                out[name] = float(col.min())
            elif name == "ts_max":
                out[name] = float(col.max())
            else:
                out[name] = float(col.sum())
        out["shards"] = float(self.n)
        self._cblock_memo = (key, dict(out), blocks)
        return dict(out), blocks

    def counters(self) -> Dict[str, float]:
        """Store-stage counters for /metrics: per-shard device counter
        blocks summed across the mesh (occupancy/laps are per-shard
        quantities, so sums read as mesh totals; ts_min/ts_max reduce
        by min/max). Per-shard SKEW — which the sums erase — is
        surfaced separately by shard_counters() and the
        zipkin_shard_occupancy{shard=}/zipkin_shard_ring_laps{shard=}
        gauge families."""
        totals, _ = self._counter_blocks()
        return totals

    def shard_counters(self):
        """One counter dict PER SHARD, in shard order — the
        hash-partition imbalance view counters()'s mesh totals sum
        away."""
        _, blocks = self._counter_blocks()
        return [
            {name: float(blocks[sh, i])
             for i, name in enumerate(dev.COUNTER_BLOCK_FIELDS)}
            for sh in range(blocks.shape[0])
        ]

    def _shard_column(self, field: str) -> Dict[str, float]:
        i = dev.COUNTER_BLOCK_FIELDS.index(field)
        _, blocks = self._counter_blocks()
        return {str(sh): float(blocks[sh, i])
                for sh in range(blocks.shape[0])}

    def _occupancy_by_shard(self) -> Dict[str, float]:
        return self._shard_column("ring_occupancy")

    def _laps_by_shard(self) -> Dict[str, float]:
        return self._shard_column("ring_laps")
