"""TpuSpanStore — the SpanStore SPI backed by the device columnar store.

The host side owns the dictionaries (strings never reach the device),
computes index policy bits (store.base.should_index, lowercased
span-name ids), pads batches, and decodes query results back into span
objects; everything between upload and the k winning rows runs on device
(store/device.py).

Plays the role of CassieSpanStore (the production backend,
zipkin-cassandra/.../CassieSpanStore.scala:55) and passes the same
conformance suite as the in-memory reference store.

Beyond the SPI it exposes the analytics the reference computes offline
(dependencies, percentiles, top annotations, cardinality) straight from
the streaming sketch state — see the ``analytics``-section methods.
"""

from __future__ import annotations

import contextlib
import threading
import time as _time
from collections import deque
from typing import (
    TYPE_CHECKING,
    Deque,
    Dict,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from zipkin_tpu.columnar.dictionary import DictionarySet
from zipkin_tpu.columnar.encode import SpanCodec
from zipkin_tpu.columnar.schema import SpanBatch
from zipkin_tpu.models.constants import CORE_ANNOTATIONS
from zipkin_tpu.models.dependencies import Dependencies, DependencyLink, Moments
from zipkin_tpu.models.span import Span
from zipkin_tpu.obs.stages import stage
from zipkin_tpu.ops import hll
from zipkin_tpu.ops import quantile as Q
from zipkin_tpu.store import device as dev

if TYPE_CHECKING:  # typing only — also feeds graftlint's call resolver
    from zipkin_tpu.wal.log import WriteAheadLog
from zipkin_tpu.store.pipeline import (
    EvictionSealer,
    IngestPipeline,
    IngestUnit,
)
from zipkin_tpu.columnar.encode import to_signed64
from zipkin_tpu.concurrency import RWLock
from zipkin_tpu.store.analytics import WindowedAnalytics
from zipkin_tpu.store.mirror import SketchMirror
from zipkin_tpu.store.paged import PagePlanner
from zipkin_tpu.testing.crash import kill_point
from zipkin_tpu.store.base import (
    MAX_TTL_ENTRIES,
    IndexedTraceId,
    PinBank,
    SpanStore,
    TraceIdDuration,
    apply_pin_merges,
    durations_from_mat,
    exist_from_duration_mat,
    fill_pin,
    gather_with_escalation,
    index_first_topk,
    index_topk_or_none,
    prune_ttls,
    resolve_annotation_query,
    service_scan_only,
    should_index,
    topk_ids_with_escalation,
)

_BATCH_MIN = 64


def resolve_multi_probes(config, dicts, queries):
    """Turn a ``get_trace_ids_multi`` query list into index-bucket probe
    rows (shared by the single-device and sharded stores).

    Returns (results, probes, limits, fallback):
    - ``results``: per-query list, pre-filled with [] for queries that
      resolve to nothing (unknown service/name/value), None otherwise;
    - ``probes``: (query_idx, fam_row, key1, key2, key3, three, is_svc,
      poison_on, end_ts) tuples — fam_row is a config.cand_layout row;
    - ``limits``: per-query limit;
    - ``fallback``: query indices that must use the singular path
      (mixed user-annotation + binary-key names: OR-across-families is
      scan-only semantics).
    """
    lay, _, _ = config.cand_layout
    results = [None] * len(queries)
    fallback: List[int] = []
    probes: List[tuple] = []
    limits = [0] * len(queries)
    for qi, q in enumerate(queries):
        if q[0] == "name":
            _, service, span_name, end_ts, limit = q
            limits[qi] = limit
            svc = dicts.services.get(service.lower())
            if svc is None or limit <= 0:
                results[qi] = []
                continue
            if service_scan_only(svc, config):
                fallback.append(qi)  # overflow service: scan-only
                continue
            if span_name is not None:
                name_lc = dicts.span_names.get(span_name.lower())
                if name_lc is None:
                    results[qi] = []
                    continue
                probes.append((qi, lay[dev.StoreConfig.CAND_NAME],
                               svc, name_lc, -1, False, False, False,
                               end_ts))
            else:
                probes.append((qi, lay[dev.StoreConfig.CAND_SVC],
                               svc, -1, -1, False, True, False, end_ts))
        else:
            _, service, annotation, value, end_ts, limit = q
            limits[qi] = limit
            if annotation in CORE_ANNOTATIONS or limit <= 0:
                results[qi] = []
                continue
            svc = dicts.services.get(service.lower())
            if svc is None:
                results[qi] = []
                continue
            if service_scan_only(svc, config):
                fallback.append(qi)  # overflow service: scan-only
                continue
            resolved = resolve_annotation_query(dicts, annotation, value)
            if resolved is None:
                results[qi] = []
                continue
            ann_value, bann_key, bann_value, bann_value2 = resolved
            if ann_value >= 0 and bann_key >= 0:
                fallback.append(qi)  # mixed: scan-only semantics
                continue
            if ann_value >= 0:
                probes.append((qi, lay[dev.StoreConfig.CAND_ANN],
                               svc, ann_value, -1, False, False, True,
                               end_ts))
                continue
            fam = lay[dev.StoreConfig.CAND_BANN]
            if bann_value < 0 and bann_value2 < 0:
                probes.append((qi, fam, svc, bann_key, -1, True, False,
                               True, end_ts))
                continue
            v1 = bann_value if bann_value >= 0 else bann_value2
            v2 = bann_value2 if bann_value2 >= 0 else bann_value
            probes.append((qi, fam, svc, bann_key, v1, True, False,
                           True, end_ts))
            if v2 != v1:
                probes.append((qi, fam, svc, bann_key, v2, True, False,
                               True, end_ts))
    return results, probes, limits, fallback


def build_probe_arrays(config, probes, limits):
    """Pack probe rows into the dtype-final numpy arrays
    dev._iq_multi_impl consumes, padded to a power-of-two probe count
    (bounds the compile cache). Padding probes are harmless service
    probes with end_ts=-1 (match nothing). Returns (arrays, k, k_eff):
    ``k`` the requested per-probe candidate count, ``k_eff`` the
    kernel's actual clamp (widest family depth)."""
    lay, _, _ = config.cand_layout
    k = max(1, max(limits[p[0]] for p in probes)) * 8
    n = _next_pow2(len(probes))
    pad_fam = lay[dev.StoreConfig.CAND_SVC]
    pad_row = (None, pad_fam, 0, -1, -1, False, True, False, -1)
    rows = probes + [pad_row] * (n - len(probes))
    arrs = {
        "b_base": np.asarray([r[1][0] for r in rows], np.int64),
        "s_base": np.asarray([r[1][1] for r in rows], np.int64),
        "n_b": np.asarray([r[1][2] for r in rows], np.int64),
        "depth": np.asarray([r[1][3] for r in rows], np.int64),
        "key1": np.asarray([r[2] for r in rows], np.int32),
        "key2": np.asarray([r[3] for r in rows], np.int32),
        "key3": np.asarray([r[4] for r in rows], np.int32),
        "three": np.asarray([r[5] for r in rows], bool),
        "is_svc": np.asarray([r[6] for r in rows], bool),
        "poison_on": np.asarray([r[7] for r in rows], bool),
        "end_ts": np.asarray([r[8] for r in rows], np.int64),
    }
    k_eff = min(k, max(fam[3] for fam in lay))
    return arrs, k, k_eff


def gate_multi_probes(probes, limits, per_probe):
    """Shared trust gating for batched index probes. ``per_probe`` is
    aligned with ``probes``: (candidates, complete, watermark,
    saturated) — saturated meaning the probe's effective window filled
    (its candidates may be truncated). Returns {query_idx: ids-or-None}
    where None = the query must fall back to its singular path."""
    by_q: Dict[int, list] = {}
    for pi, p in enumerate(probes):
        by_q.setdefault(p[0], []).append(pi)
    out = {}
    for qi, pis in by_q.items():
        cands = []
        complete = True
        wm = -(1 << 62)
        saturated = False
        win_total = 0
        for pi in pis:
            c_, comp_, wm_, sat_ = per_probe[pi]
            cands.extend(c_)
            complete = complete and comp_
            wm = max(wm, wm_)
            saturated |= sat_
            # window > len ⇔ unsaturated: the underfull-equals-complete
            # claim may only fire when NO probe truncated its window.
            win_total += len(c_) + (0 if sat_ else 1)
        if len(pis) > 1 and saturated:
            # Per-probe windows truncated independently: a trace cut
            # from one probe's top-k can outrank the other probe's
            # survivors, so no union-level claim is sound — unlike the
            # singular verify2 kernel, which top-k's over the
            # CONCATENATED buckets.
            out[qi] = None
        else:
            out[qi] = index_topk_or_none(
                limits[qi], win_total, cands, complete, wm
            )
    return out


@jax.jit
def _launch_marker(write_pos):
    """A scalar that is ready once the launch that produced
    ``write_pos`` has run, in a buffer no later launch donates."""
    return write_pos + 0


def device_memory(device) -> Dict[str, float]:
    """{kind: bytes} of one device's ``memory_stats()``."""
    stats = device.memory_stats() or {}
    return {kind: float(stats[key])
            for kind, key in (("in_use", "bytes_in_use"),
                              ("peak", "peak_bytes_in_use"))
            if key in stats}


def _next_pow2(n: int) -> int:
    p = _BATCH_MIN
    while p < n:
        p <<= 1
    return p


# From this many rows up a launch's annotation and binary pads take the
# half-octave rung 3 * 2^(k-1) between each pair of powers of two.
_LADDER_MIN = 4096


def _pad_rows(n: int) -> int:
    """The pad of a launch's annotation or binary-annotation dimension:
    the smallest rung >= n of ..., 2048, 4096, 6144, 8192, 12288,
    16384, 24576, ... (``_next_pow2`` below ``_LADDER_MIN``). The index
    write launches each of these dimensions five times over, and a
    padded row costs what a valid one does, so the rung between two
    powers of two takes up to a quarter of that work off a launch
    (12,288 annotation rows launch 12,288 and not 16,384) for at most
    one more compiled shape an octave. A pure function of the row
    count, never over ``_next_pow2(n)``: WAL replay re-cuts the same
    launches and the chunkers' ring-capacity guards hold as they
    did (docs/PERFORMANCE.md, "The pad ladder")."""
    p = _next_pow2(n)
    rung = p // 4 * 3
    return rung if n <= rung and rung >= _LADDER_MIN else p


def name_lc_ids(batch: SpanBatch, dicts: DictionarySet,
                cache: Dict[int, int]) -> np.ndarray:
    """Lowercased span-name dictionary id per span (-1 for empty names),
    maintained incrementally through ``cache``."""
    out = np.empty(batch.n_spans, np.int32)
    for i, nid in enumerate(batch.name_id):
        nid = int(nid)
        lc = cache.get(nid)
        if lc is None:
            name = dicts.span_names.decode(nid)
            lc = -1 if name == "" else dicts.span_names.encode(name.lower())
            cache[nid] = lc
        out[i] = lc
    return out


def mats_to_batch(
    n_s: int, n_a: int, n_b: int,
    span_mat: np.ndarray, ann_mat: np.ndarray, bann_mat: np.ndarray,
) -> Tuple[SpanBatch, np.ndarray]:
    """(SpanBatch, per-row gids) from the stacked i64 matrices the
    gather/capture kernels produce (already compacted, spans in
    insertion order). Shared by the query decode paths and the
    cold-tier eviction capture (which seals the batch into a segment
    instead of decoding spans)."""
    batch = SpanBatch.empty(n_s, n_a, n_b)
    for i, col in enumerate(dev.SPAN_MAT_COLS[:-1]):  # row_gid is last
        tgt = getattr(batch, col)
        setattr(batch, col, span_mat[i, :n_s].astype(tgt.dtype))
    gids = span_mat[len(dev.SPAN_MAT_COLS) - 1, :n_s].astype(np.int64)
    gid_to_local = {int(g): i for i, g in enumerate(gids)}
    if n_a:
        a = {name: ann_mat[i, :n_a]
             for i, name in enumerate(dev.ANN_MAT_COLS)}
        batch.ann_span_idx = np.array(
            [gid_to_local.get(int(g), 0) for g in a["ann_gid"]], np.int32
        )
        batch.ann_ts = a["ann_ts"]
        batch.ann_value_id = a["ann_value_id"].astype(np.int32)
        batch.ann_service_id = a["ann_service_id"].astype(np.int32)
        batch.ann_endpoint_id = a["ann_endpoint_id"].astype(np.int32)
    if n_b:
        b = {name: bann_mat[i, :n_b]
             for i, name in enumerate(dev.BANN_MAT_COLS)}
        batch.bann_span_idx = np.array(
            [gid_to_local.get(int(g), 0) for g in b["bann_gid"]], np.int32
        )
        batch.bann_key_id = b["bann_key_id"].astype(np.int32)
        batch.bann_value_id = b["bann_value_id"].astype(np.int32)
        batch.bann_type = b["bann_type"].astype(np.uint8)
        batch.bann_service_id = b["bann_service_id"].astype(np.int32)
        batch.bann_endpoint_id = b["bann_endpoint_id"].astype(np.int32)
    return batch, gids


def decode_gathered(
    codec: SpanCodec, n_s: int, n_a: int, n_b: int,
    span_mat: np.ndarray, ann_mat: np.ndarray, bann_mat: np.ndarray,
) -> List[Span]:
    """Decode the stacked i64 matrices dev.gather_trace_rows produced
    into Span objects. Shared by the single-store and sharded read
    paths."""
    if n_s == 0:
        return []
    batch, _ = mats_to_batch(n_s, n_a, n_b, span_mat, ann_mat, bann_mat)
    return codec.decode(batch)


_SPAN_COLS = ("trace_id", "span_id", "parent_id", "name_id", "service_id",
              "ts_cs", "ts_cr", "ts_sr", "ts_ss", "ts_first", "ts_last",
              "duration", "flags")
_ANN_COLS = ("ann_ts", "ann_value_id", "ann_service_id", "ann_endpoint_id")
_BANN_COLS = ("bann_key_id", "bann_value_id", "bann_type",
              "bann_service_id", "bann_endpoint_id")




class TpuSpanStore(WindowedAnalytics, SpanStore):
    def __init__(self, config: Optional[dev.StoreConfig] = None,
                 codec: Optional[SpanCodec] = None,
                 registry=None):
        self.config = config or dev.StoreConfig()
        if self.config.layout not in ("ring", "paged"):
            raise ValueError(
                f"unknown layout {self.config.layout!r} "
                "(expected 'ring' or 'paged')")
        self.codec = codec or SpanCodec()
        self.state = dev.init_state(self.config)
        # Paged layout (ISSUE 19): the host page allocator. Slot/gid
        # assignment moves from the device's write_pos arithmetic to
        # the planner's per-unit claim plan (stage 1 of the pipeline);
        # the device kernels stay layout-blind because paged gids keep
        # the ring invariant slot == gid % capacity (epoch-encoded).
        self._planner = (PagePlanner(self.config)
                         if self.config.paged_enabled else None)
        # Serializes writers against each other (queue workers).
        self._lock = threading.Lock()  # lock-order: 10 encode
        # Guards the state swap: ingest_step donates the old state's
        # device buffers, so queries snapshot self.state under a read
        # lock and hold it across their kernels + host gathers, while
        # the donating step runs under the write lock.
        self._rw = RWLock()  # lock-order: 40 commit
        # Host mirrors of write_pos / last-bucket-close position, pacing
        # the dependency bucket rotation without a device sync per batch.
        self._wp = 0
        self._archived = 0
        # Eviction capture (cold tier, store/archive): when a sink is
        # attached, the write path pulls every ring row to the host
        # BEFORE any of the three rings can overwrite it. The mirrors
        # track each ring's write cursor (host-side, no device sync)
        # and the per-ring capture high-water marks; a capture window
        # is always [_cap_upto, _wp) with EXACTLY _awp - _cap_a
        # annotation rows (each batch's side rows belong to its own
        # spans), so the pull needs no count escalation in steady
        # state. sink(batch, gids, gid_lo, gid_hi, pull_seconds).
        self.eviction_sink = None
        self._awp = 0
        self._bwp = 0
        self._cap_upto = 0  # guarded-by: _cap_lock
        self._cap_a = 0  # guarded-by: _cap_lock
        self._cap_b = 0  # guarded-by: _cap_lock
        # Async eviction sealing (store/pipeline.EvictionSealer): with
        # capture_backlog > 0 the write path only PULLS a capture
        # window (read-only launch, ordering invariant intact) and a
        # background thread does the D2H + deflate + directory append.
        # _sealed_upto trails _cap_upto by exactly the in-flight
        # windows; checkpoint manifests cut at the SEALED frontier.
        # _cap_lock serializes window capture between the serial write
        # path (under _lock) and the pipeline's commit thread.
        self.capture_backlog = self.CAPTURE_BACKLOG
        self._sealer: Optional[EvictionSealer] = None
        self._sealed_upto = 0  # guarded-by: _cap_lock
        self._cap_lock = threading.Lock()  # lock-order: 30 capture
        # Pipelined ingest (store/pipeline.IngestPipeline), opt-in via
        # start_pipeline(): apply/write_thrift become stage 1 (encode +
        # pad under _lock) and the commit thread owns the device write
        # path (the _wp/_awp/_bwp mirrors, capture/archive triggers,
        # sweep cadence).
        self._pipeline: Optional[IngestPipeline] = None
        # Durable write-ahead log (zipkin_tpu.wal): when attached, every
        # planned launch group is journaled (stage-1 output + dictionary
        # delta) BEFORE its donating commit; _wal_applied tracks the
        # highest sequence whose unit has committed to the device
        # (advanced inside the commit's write-lock hold, so checkpoint
        # cuts read a sequence exactly consistent with the state), and
        # _wal_marks the dictionary high-water sizes of the last
        # journaled record (the next record's delta base).
        self.wal: Optional[WriteAheadLog] = None
        self._wal_applied = 0
        self._wal_marks = None
        # Batch lineage tracker (obs.fleet.LineageTracker): when
        # attached, _journal_group stamps each record's meta with a
        # commit timestamp (+ a sampled B3 context) and reports the
        # append so the unit's WAL append → fsync → ship → follower
        # apply shows up as one causally-linked self-trace.
        self.lineage = None
        # Host sketch mirror (store/mirror.SketchMirror): numpy twins
        # of the device's lifetime aggregate arrays AND the windowed
        # Moments-sketch arena, updated by each commit's delta inside
        # the write-lock hold — the query engine's zero-dispatch
        # sketch tier (docs/QUERY_ENGINE.md). The dictionary set
        # resolves the "error" convention ids for the window cells'
        # error counts.
        self.sketch_mirror = SketchMirror(self.config,
                                          dicts=self.codec.dicts)
        # Read-visibility epoch: bumped by host-side state that changes
        # query answers WITHOUT a device commit (pin/TTL mutations,
        # pin-bank arrivals). write_frontier() = (_step_seq, epoch) is
        # the result-cache key component.
        self._read_epoch = 0
        # Pending-sweep pacing: sweep every SWEEP_EVERY batches on the
        # write path (bounds how long a cross-batch child waits for its
        # link) and lazily before dependency reads — but only when
        # something was written since the last sweep, so read-only
        # dependency polling stays a pure read.
        self._batches_since_sweep = 0
        # Keyed by to_signed64(trace_id) — ids >= 2^63 arrive unsigned
        # on some write paths and signed on others.
        self.ttls: Dict[int, float] = {}
        # Eviction-exempt spans of pinned traces (see PinBank).
        self.pins = PinBank()
        # Annotation rows dropped because a single span carried more than
        # a ring's capacity (the maxTraceCols-style guard).
        self.anns_truncated = 0
        self.banns_truncated = 0
        # Index read-path outcome counters (surfaced via counters() →
        # /metrics): how often the fast path answered vs degraded to the
        # O(ring) scan kernels — the observable for the sparse-key
        # aliasing rate the per-key cursor table exists to shrink.
        self.index_hits = 0
        self.index_fallbacks = 0
        # name_id -> lowercased-name id, maintained incrementally.
        self._name_lc: Dict[int, int] = {}
        # Telemetry (zipkin_tpu.obs): the device counter block is
        # fetched at most ONCE per ingest progress — _step_seq bumps on
        # every state mutation and keys the memo, so metric scrapes
        # between ingest steps reuse the cached block instead of
        # launching a D2H each.
        self._step_seq = 0
        self._cblock_memo: Optional[tuple] = None
        from zipkin_tpu import obs

        reg = registry or obs.default_registry()
        self._registry = reg
        # Launch dispatch is ASYNC under JAX, so a per-step wall clock
        # only measures host dispatch. Every launch leaves a marker
        # scalar behind and waits for the marker RUN_AHEAD launches
        # back (see _observe_ingest); the dispatch sketch keeps the
        # always-on host-side number.
        self._h_ingest = reg.register(obs.LatencySketch(
            "zipkin_store_ingest_step_seconds",
            "A launch's dispatch until the device had run it, queue "
            "and all (observed RUN_AHEAD launches later, where the "
            "host waits for it: that wait is the write path's "
            "back-pressure on the device queue)"))
        self._h_dispatch = reg.register(obs.LatencySketch(
            "zipkin_store_ingest_dispatch_seconds",
            "Host dispatch time per fused step/chain (async: excludes "
            "device compute — see zipkin_store_ingest_step_seconds)"))
        self._c_launches = reg.register(obs.Counter(
            "zipkin_store_ingest_launches_total",
            "Device ingest launches (chained chunks count as one)"))
        # What the launches were made of, by dimension: the rows their
        # shapes carried (the pads, times the parts of a chained unit)
        # and those of them that were padding. Every label set exists
        # from here on, so a share of 0 reads 0 and not "no sample".
        rows = reg.register(obs.Counter(
            "zipkin_store_launch_rows_total",
            "Rows the ingest launches carried, padding included "
            "(the launch shape's pad x the chunks of a chained unit)",
            labelnames=("dim",)))
        pad_rows = reg.register(obs.Counter(
            "zipkin_store_launch_pad_rows_total",
            "Rows of zipkin_store_launch_rows_total that were padding "
            "(launched less valid)", labelnames=("dim",)))
        self._c_launch_rows = {
            dim: (rows.labels(dim=dim), pad_rows.labels(dim=dim))
            for dim in ("span", "annotation", "binary")}
        # (marker, dispatch time) of the launches the device may not
        # have run yet, oldest first; the committing thread's alone.
        self._in_flight: Deque[Tuple[jax.Array, float]] = deque()
        reg.register(obs.Counter(
            "zipkin_store_jit_compiles_total",
            "Compiled variants across the ingest/staging/capture jits "
            "(dev.compile_count; steady-state pipelined ingest adds 0)",
            fn=lambda: float(dev.compile_count())))
        # The allocator's own numbers for the device the state lives
        # on, read at scrape and never on the write path.
        device = next(iter(self.state.write_pos.devices()))
        reg.register(obs.CallbackFamily(
            "zipkin_device_memory_bytes",
            "Memory of the store's device as its allocator reports it "
            "(memory_stats(); no samples where the backend reports "
            "none, as the CPU's does)",
            "kind", lambda: device_memory(device)))
        # Windowed Moments-sketch arena families (zipkin_window_*,
        # docs/OBSERVABILITY.md): fold counters are process-monotonic
        # mirror totals (never regress on ring self-clears or resync);
        # the cell gauge reads live occupancy.
        mirror = self.sketch_mirror
        reg.register(obs.Counter(
            "zipkin_window_spans_total",
            "Spans folded into the windowed (service × time-bucket) "
            "Moments-sketch cells since process start",
            fn=lambda: float(mirror.win_spans_total)))
        reg.register(obs.Counter(
            "zipkin_window_errors_total",
            "Error-flagged spans ('error' annotation value or binary "
            "key) folded into the windowed cells since process start",
            fn=lambda: float(mirror.win_errors_total)))
        reg.register(obs.Gauge(
            "zipkin_window_cells_active",
            "Occupied (service, time-bucket) cells in the windowed "
            "arena ring",
            fn=lambda: float(mirror.window_live_cells())))
        reg.register(obs.Gauge(
            "zipkin_window_retention_seconds",
            "Windowed-analytics retention: window_seconds × "
            "window_buckets (0 = arena disabled)",
            fn=lambda: float(
                self.config.window_seconds * self.config.window_buckets
                if self.config.window_enabled else 0.0)))
        # Paged-layout allocator occupancy (gauges read the planner's
        # host mirrors under its own lock — zero device traffic).
        if self._planner is not None:
            planner = self._planner
            reg.register(obs.Gauge(
                "zipkin_store_pages_active",
                "Device pages holding live spans (paged layout)",
                fn=lambda: float(planner.stats()["pages_active"])))
            reg.register(obs.Gauge(
                "zipkin_store_pages_free",
                "Device pages on the allocator free list (paged "
                "layout)",
                fn=lambda: float(planner.stats()["pages_free"])))
            reg.register(obs.Counter(
                "zipkin_store_page_reclaims_total",
                "Pages captured + recycled through the free list "
                "since process start (paged layout)",
                fn=lambda: float(planner.stats()["page_reclaims"])))
        # The zipkin_store_counter family is registered by ApiServer
        # from the generic counters() hook (one registration site for
        # every backend), not here.

    @property
    def dicts(self) -> DictionarySet:
        return self.codec.dicts

    # -- writes ---------------------------------------------------------

    def _name_lc_ids(self, batch: SpanBatch) -> np.ndarray:
        return name_lc_ids(batch, self.dicts, self._name_lc)

    # ItemQueue-aligned chunk bound: keeps jit shapes bounded and batches
    # well under any ring capacity.
    MAX_CHUNK = 4096
    # TTL-map bound (store/base.MAX_TTL_ENTRIES — shared with the
    # sharded and replica stores; kept as a class attr for callers).
    MAX_TTL_ENTRIES = MAX_TTL_ENTRIES
    # How many launches the host may lead the device by: launch i
    # waits until launch i - RUN_AHEAD has run. Enough queued work
    # (4 x 36 ms at the benchmark's geometry) that a host hiccup does
    # not idle the device; few enough that no wait is long (0: every
    # launch waits for itself, as tests do to see one).
    RUN_AHEAD = 4
    # Default prefetch depth for start_pipeline(None).
    PIPELINE_DEPTH = 8
    # Default staged-unit (H2D double-buffer) slots for start_pipeline.
    STAGE_BUFFERS = 2
    # Default async-seal backlog: 0 = seal inline on the write path
    # (bitwise-deterministic timing, the library default); deployments
    # that want capture off the critical path set capture_backlog > 0
    # (the daemon's --capture-backlog does).
    CAPTURE_BACKLOG = 0

    def apply(self, spans: Sequence[Span]) -> None:
        if not spans:
            return
        with stage("store.lock_wait") as wait, self._lock:
            wait.done()
            for span in spans:
                self.ttls.setdefault(to_signed64(span.trace_id), 1.0)
            if self.pins:
                # Pin-bank arrivals change read answers before the
                # commit bumps the frontier — invalidate cached reads.
                self._bump_read_epoch()
            self.pins.note_write(to_signed64, spans)
            self._prune_ttls()
            # Chunking keeps jit shapes bounded and batches under ring
            # capacity (a single launch must not scatter colliding
            # slots); trace grouping just keeps each trace's rows
            # adjacent in the ring. _chunk_columnar additionally guards
            # the annotation rings (one fat span's rows get truncated,
            # not the whole batch dropped). Multiple chunks chain into
            # one launch (_write_parts) to amortize the per-dispatch
            # floor.
            # Buffer at most one chain group (+ one trace chunk's worth)
            # of encoded columnar parts — a bulk apply() must not hold
            # the whole call's columnar copy in host memory at once.
            if self._pipeline is not None:
                self._apply_pipelined(spans)
                return
            parts = []
            for part in self._chunk_by_trace(spans):
                with stage("store.encode"):
                    parts.extend(self._encode_part(part))
                if self.CHAIN_SIZES and len(parts) >= self.CHAIN_SIZES[0]:
                    self._write_parts(parts)
                    parts = []
            if parts:
                self._write_parts(parts)

    def _encode_part(self, part: Sequence[Span]) -> list:
        """One trace chunk → columnar parts with index bits (the body
        of stage 1 on the span-object path, serial or pipelined)."""
        batch = self.codec.encode(part)
        indexable = np.fromiter(
            (should_index(s) for s in part), bool, len(part)
        )
        return list(self._chunk_columnar(
            batch, self._name_lc_ids(batch), indexable))

    def _apply_pipelined(self, spans: Sequence[Span]) -> None:
        """Stage 1 of the ingest pipeline (caller thread, under the
        encode lock): encode + index bits + padding, feeding the
        prefetch queue. The chunk flush boundary, the CHAIN_SIZES
        grouping, and the pad buckets are IDENTICAL to the serial
        path's, so both modes cut the same launch units — the basis of
        the pipelined-equals-serial bitwise guarantee
        (tests/test_pipeline.py)."""
        pipe = self._pipeline
        self.ensure_writable()  # fail fast; the commit thread re-checks
        with stage("store.encode") as encode:
            parts = []
            for part in self._chunk_by_trace(spans):
                parts.extend(self._encode_part(part))
                if self.CHAIN_SIZES and len(parts) >= self.CHAIN_SIZES[0]:
                    encode.less += self._feed_units(pipe, parts)
                    parts = []
            if parts:
                encode.less += self._feed_units(pipe, parts)
        pipe.h_encode.observe(encode.seconds)

    def _feed_units(self, pipe: IngestPipeline, parts) -> float:
        """Pad + enqueue one flushed part list as launch units; returns
        seconds spent blocked on pipeline backpressure (excluded from
        the encode sketch). With a WAL attached each group is journaled
        HERE — on the stage-1 caller thread, under the encode lock, so
        append order equals feed order equals (FIFO) commit order."""
        stalled = 0.0
        for group in self._plan_units(parts):
            # Journal BEFORE padding: _pad_unit's page planning (paged
            # layout) keys its claim plan to the unit's WAL sequence
            # ATOMICALLY under the planner lock, so a checkpoint's
            # planner snapshot can never see a plan without its seq
            # (the replay memo's integrity). Dictionaries grew in the
            # encode stage, so the journaled delta is pad-independent.
            seq = (self._journal_group(group)
                   if self.wal is not None else None)
            unit = self._pad_unit(group, wal_seq=seq)
            if seq is not None:
                unit = unit._replace(wal_seq=seq)
                kill_point("after-append")
            stalled += pipe.feed(unit)
        return stalled

    def _chunk_by_trace(self, spans: Sequence[Span]):
        chunk_size = self._max_chunk_spans()
        by_trace: Dict[int, List[Span]] = {}
        for s in spans:
            by_trace.setdefault(s.trace_id, []).append(s)
        batch: List[Span] = []
        for trace_spans in by_trace.values():
            if batch and len(batch) + len(trace_spans) > chunk_size:
                yield batch
                batch = []
            batch.extend(trace_spans)
            # A single trace larger than the chunk is split; its links
            # still join via the resident-ring archive path.
            while len(batch) > chunk_size:
                yield batch[:chunk_size]
                batch = batch[chunk_size:]
        if batch:
            yield batch

    def _max_chunk_spans(self) -> int:
        """One-launch span bound: the span ring (colliding-slot scatter
        guard) AND the pending ring (a launch's unresolved children must
        fit without self-collision) both cap it. ``config.batch_spans``
        (the r12 batch-escalation knob) replaces the legacy MAX_CHUNK
        ceiling when set — bigger launches amortize the per-launch
        index-write entry costs; the ring guards still clamp."""
        c = self.config
        # <= 0 means "default" (and a negative knob value must never
        # reach the chunkers: a non-positive chunk size turns
        # _chunk_by_trace's split loop into an infinite empty-yield).
        limit = c.batch_spans if c.batch_spans > 0 else self.MAX_CHUNK
        # Paged layout: one launch's page demand is bounded by its span
        # count (<= ~4·spans/page_rows + 1 open pages), and every page
        # claimed inside a unit is reclaim-exempt for that unit (the
        # capture-before-reuse pull runs before the launch). capacity//8
        # keeps the worst-case demand under half the pool, so the
        # allocator always finds an untouched victim.
        span_cap = (max(1, c.capacity // 8) if c.paged_enabled
                    else c.capacity // 2 or 1)
        return max(1, min(limit, span_cap, c.pending_slots))

    def _prune_ttls(self) -> None:
        prune_ttls(self.ttls, self.MAX_TTL_ENTRIES)

    def write_thrift(self, payload: bytes,
                     sample_threshold: int = 0) -> Tuple[int, int, int]:
        """Native fast path: raw thrift Span sequence → device, bypassing
        python span objects entirely. Returns
        (written, dropped, written_debug).

        ``sample_threshold`` applies the sampler's trace-id test on the
        numeric columns BEFORE string interning (Sampler.scala:39-48
        semantics incl. the debug override, SpanSamplerFilter.scala:40-47)
        so the fast path neither bypasses sampling nor pollutes the
        dictionaries with sampled-out names; 0 keeps everything.
        ``written_debug`` counts kept debug spans (the slow path never
        runs those through the sampler's counters).

        Raises zipkin_tpu.native.NativeUnavailable when g++ is absent —
        callers fall back to wire.thrift + apply(); ParseCapacityError
        propagates for callers to chunk."""
        from zipkin_tpu import native

        with stage("store.lock_wait") as wait, self._lock:
            wait.done()
            # Stage 1: the whole body (parse + index bits + chunking,
            # and on the pipelined path padding and the journal too),
            # less the feed stall, which is a span of its own. On the
            # serial path the pad is timed in _commit_group.
            with stage("store.encode") as encode:
                batch, name_lc, dropped, kept_debug = (
                    native.parse_spans_columnar_sampled(
                        payload, self.dicts, sample_threshold,
                        max_spans=self.MAX_CHUNK,
                    )
                )
                if batch.n_spans == 0:
                    return 0, dropped, 0
                for tid in np.unique(batch.trace_id):
                    self.ttls.setdefault(int(tid), 1.0)
                if self.pins:
                    # Fast-path arrivals for pinned traces must reach
                    # the eviction-exempt bank too: decode just those
                    # rows.
                    keep = np.isin(
                        batch.trace_id,
                        np.fromiter(self.pins.tids(), np.int64,
                                    len(self.pins.tids())),
                    )
                    if keep.any():
                        pinned_part = self._select_batch(batch, keep)
                        self._bump_read_epoch()
                        self.pins.note_write(
                            to_signed64, self.codec.decode(pinned_part)
                        )
                self._prune_ttls()
                indexable = native.indexable_from_batch(batch, self.dicts)
                parts = list(self._chunk_columnar(batch, name_lc, indexable))
                pipe = self._pipeline
                if pipe is not None:
                    self.ensure_writable()
                    encode.less = self._feed_units(pipe, parts)
            if pipe is not None:
                pipe.h_encode.observe(encode.seconds)
            else:
                self._write_parts(parts)
            return batch.n_spans, dropped, kept_debug

    def _chunk_columnar(self, batch: SpanBatch, name_lc: np.ndarray,
                        indexable: np.ndarray):
        """Split a parsed columnar batch so every chunk fits the ring
        capacities (a single launch must never scatter colliding slots —
        see write_batch). The common case (batch fits) costs nothing."""
        c = self.config
        max_spans = self._max_chunk_spans()
        if (batch.n_spans <= max_spans
                and batch.n_annotations <= c.ann_capacity
                and batch.n_binary <= c.bann_capacity):
            yield batch, name_lc, indexable
            return
        start = 0
        while start < batch.n_spans:
            stop = min(start + max_spans, batch.n_spans)
            # Shrink until the chunk's annotation rows fit their rings.
            while stop > start + 1:
                a_n = int(np.count_nonzero(
                    (batch.ann_span_idx >= start) & (batch.ann_span_idx < stop)
                ))
                b_n = int(np.count_nonzero(
                    (batch.bann_span_idx >= start)
                    & (batch.bann_span_idx < stop)
                ))
                if a_n <= c.ann_capacity and b_n <= c.bann_capacity:
                    break
                stop = start + (stop - start) // 2
            part = self._slice_batch(batch, start, stop)
            # A single span can carry more annotations than a ring holds;
            # yielding it as-is would wrap the ring and scatter colliding
            # slots nondeterministically in one launch. Truncate its
            # annotation rows instead (counted, like maxTraceCols drops).
            if part.n_annotations > c.ann_capacity:
                self.anns_truncated += part.n_annotations - c.ann_capacity
                part = self._truncate_anns(part, c.ann_capacity, binary=False)
            if part.n_binary > c.bann_capacity:
                self.banns_truncated += part.n_binary - c.bann_capacity
                part = self._truncate_anns(part, c.bann_capacity, binary=True)
            yield part, name_lc[start:stop], indexable[start:stop]
            start = stop

    @staticmethod
    def _truncate_anns(batch: SpanBatch, cap: int, binary: bool) -> SpanBatch:
        """Keep only the first ``cap`` (binary) annotation rows."""
        import dataclasses

        cols = SpanBatch.BANN_COLUMNS if binary else SpanBatch.ANN_COLUMNS
        return dataclasses.replace(
            batch, **{c: getattr(batch, c)[:cap] for c in cols}
        )

    @staticmethod
    def _select_batch(batch: SpanBatch, keep: np.ndarray) -> SpanBatch:
        """Columnar selection of arbitrary span rows (bool mask) with
        their annotation rows, span indices rebased."""
        idx = np.flatnonzero(keep)
        remap = np.full(batch.n_spans, -1, np.int32)
        remap[idx] = np.arange(idx.size, dtype=np.int32)
        a_sel = keep[batch.ann_span_idx] if batch.n_annotations else (
            np.zeros(0, bool)
        )
        b_sel = keep[batch.bann_span_idx] if batch.n_binary else (
            np.zeros(0, bool)
        )
        out = SpanBatch.empty(idx.size, int(a_sel.sum()), int(b_sel.sum()))
        for col in _SPAN_COLS:
            setattr(out, col, getattr(batch, col)[idx])
        out.ann_span_idx = remap[batch.ann_span_idx[a_sel]]
        for col in _ANN_COLS:
            setattr(out, col, getattr(batch, col)[a_sel])
        out.bann_span_idx = remap[batch.bann_span_idx[b_sel]]
        for col in _BANN_COLS:
            setattr(out, col, getattr(batch, col)[b_sel])
        return out

    @staticmethod
    def _slice_batch(batch: SpanBatch, start: int, stop: int) -> SpanBatch:
        """Columnar slice of span rows [start, stop) with their
        annotation/binary rows, span indices rebased."""
        a_sel = (batch.ann_span_idx >= start) & (batch.ann_span_idx < stop)
        b_sel = (batch.bann_span_idx >= start) & (batch.bann_span_idx < stop)
        out = SpanBatch.empty(
            stop - start, int(a_sel.sum()), int(b_sel.sum())
        )
        for col in _SPAN_COLS:
            setattr(out, col, getattr(batch, col)[start:stop])
        out.ann_span_idx = batch.ann_span_idx[a_sel] - start
        for col in _ANN_COLS:
            setattr(out, col, getattr(batch, col)[a_sel])
        out.bann_span_idx = batch.bann_span_idx[b_sel] - start
        for col in _BANN_COLS:
            setattr(out, col, getattr(batch, col)[b_sel])
        return out

    def write_batch(self, batch: SpanBatch, indexable: np.ndarray) -> None:
        """Upload one columnar batch and run the fused ingest step.

        A batch larger than a ring would scatter colliding slot indices in
        one launch (result order implementation-defined on TPU) — callers
        must chunk; ``apply`` does.
        """
        if self._pipeline is not None:
            # Committing on the caller thread while the pipeline's
            # commit thread is live would make two concurrent device
            # writers (racing the mirror bumps and capture clocks) —
            # the ring-scatter contract forbids it.
            raise RuntimeError(
                "write_batch commits inline and cannot run while an "
                "ingest pipeline is active; use apply()/write_thrift "
                "or stop_pipeline() first"
            )
        c = self.config
        if (batch.n_spans > min(c.capacity, c.pending_slots)
                or batch.n_annotations > c.ann_capacity
                or batch.n_binary > c.bann_capacity):
            raise ValueError(
                f"batch ({batch.n_spans} spans / {batch.n_annotations} anns "
                f"/ {batch.n_binary} banns) exceeds ring capacity "
                f"({min(c.capacity, c.pending_slots)}/{c.ann_capacity}/"
                f"{c.bann_capacity}); split into smaller batches"
            )
        self._write_device(batch, self._name_lc_ids(batch), indexable)

    # Chained-launch grouping: chunks per ingest_steps launch. Powers of
    # two only ({4, 8, 16}) so the scan length doesn't fragment the
    # compile cache; leftovers run singly.
    CHAIN_SIZES = (16, 8, 4)

    def _write_parts(self, parts) -> None:
        """Write a list of (batch, name_lc, indexable) chunks, chaining
        groups of equal-padded chunks into single ``dev.ingest_steps``
        launches — one dispatch per GROUP instead of per chunk (the
        ItemQueue batch-drain role, ItemQueue.scala:39)."""
        for group in self._plan_units(parts):
            self._commit_group(group)

    def _commit_group(self, group) -> None:
        """Journal (when a WAL is attached) then commit one planned
        launch group — the serial write path's ack-after-append point:
        by the time the donating swap runs, the group's record is in
        the log, so a crash between append and commit REPLAYS the
        group instead of losing it."""
        seq = None
        if self.wal is not None:
            kill_point("before-append")
            seq = self._journal_group(group)
        # Journal-before-pad: see _feed_units — the paged planner's
        # claim plan is keyed to ``seq`` inside _pad_unit. The pad is
        # stage-1 work, so it is the serial path's second observation
        # of store.encode (the journal lies between the two).
        with stage("store.encode"):
            unit = self._pad_unit(group, wal_seq=seq)
        if seq is not None:
            unit = unit._replace(wal_seq=seq)
            kill_point("after-append")
        self._commit_unit(unit)
        kill_point("after-commit")

    def _plan_units(self, parts):
        """CHAIN_SIZES greedy grouping of chunker parts into launch
        units — ONE policy shared by the serial writer and the ingest
        pipeline's stage 1 (identical grouping is a precondition of
        the pipelined-equals-serial bitwise guarantee). Spans are
        bounded by capacity//2 so the archive cadence (one
        dependency-bucket close per half ring) can never be outrun
        inside one launch; annotation/binary rows are bounded by their
        FULL ring capacities — a group exceeding one would overwrite
        its own side rows mid-launch, where no capture hook can run
        (the pre-launch capture trigger already protects every OLDER
        uncaptured row up to exactly this bound). Yields part lists;
        singletons dispatch via ingest_step, larger groups chain
        through ingest_steps."""
        span_budget = (max(1, self.config.capacity // 8)
                       if self.config.paged_enabled
                       else max(1, self.config.capacity // 2))
        ann_budget = max(1, self.config.ann_capacity)
        bann_budget = max(1, self.config.bann_capacity)
        i = 0
        n = len(parts)
        while i < n:
            took = 1
            for size in self.CHAIN_SIZES:
                if i + size > n:
                    continue
                group = parts[i:i + size]
                if (sum(p[0].n_spans for p in group) <= span_budget
                        and sum(p[0].n_annotations for p in group)
                        <= ann_budget
                        and sum(p[0].n_binary for p in group)
                        <= bann_budget):
                    yield group
                    took = size
                    break
            else:
                yield parts[i:i + 1]
            i += took

    def _pad_unit(self, group, wal_seq: Optional[int] = None
                  ) -> IngestUnit:
        """Pad one planned group to its buckets (host numpy — the
        H2D copy is the pipeline's stage 2, or implicit at dispatch on
        the serial path): spans to a power of two, annotation and
        binary rows to ``_pad_rows``'s half-octave ladder. Chained
        groups pad every chunk to the group max and stack along a
        leading scan axis. Bucketing bounds the jit compile cache, so
        a warmed steady state pads into already-compiled shapes only
        (dev.compile_count gates this).

        The per-span error bit (the window cells' error counts) is a
        pure function of (batch, dictionary state) — WAL replay
        rebuilds the dictionaries in append order, so a replayed unit
        recomputes identical flags (aggregate.windows)."""
        from zipkin_tpu.aggregate import windows as win_mod

        sketch = self.sketch_mirror.delta_of(group)
        # Paged layout: slot/gid claims are planned HERE — on the
        # stage-1 caller thread, under the encode lock — so claim
        # order equals feed order equals journal order (the planner's
        # determinism contract). ``wal_seq`` is only passed by WAL
        # replay, which re-reads recorded plans for already-planned
        # sequences instead of re-deriving them.
        plan = None
        if self._planner is not None:
            plan = self._planner.plan_unit(
                [np.asarray(b.trace_id) for b, _, _ in group],
                wal_seq=wal_seq)
        if self.config.window_enabled:
            ea, eb = win_mod.error_ids(self.dicts)
            err_of = lambda b: win_mod.span_error_flags(b, ea, eb)  # noqa: E731
        else:
            err_of = lambda b: None  # noqa: E731 — flag lowers out
        pad_rc = 1
        if plan is not None:
            pad_rc = _next_pow2(max(
                [1] + [len(c.reclaim_pages) for c in plan.chunks]))
        if len(group) == 1:
            b, lc, ix = group[0]
            cp = plan.chunks[0] if plan is not None else None
            db = dev.make_device_batch(
                b, name_lc_id=lc, indexable=ix,
                pad_spans=_next_pow2(b.n_spans),
                pad_anns=_pad_rows(b.n_annotations),
                pad_banns=_pad_rows(b.n_binary),
                error_flag=err_of(b),
                span_slot=None if cp is None else cp.span_slot,
                span_gid=None if cp is None else cp.span_gid,
                reclaim_pages=None if cp is None else cp.reclaim_pages,
                pad_reclaims=pad_rc,
            )
            return IngestUnit(db, b.n_spans, b.n_annotations,
                              b.n_binary, 1, False, sketch=sketch,
                              reclaims=plan.reclaims if plan else ())
        pad_s = _next_pow2(max(b.n_spans for b, _, _ in group))
        pad_a = _pad_rows(max(b.n_annotations for b, _, _ in group))
        pad_b = _pad_rows(max(b.n_binary for b, _, _ in group))
        dbs = [
            dev.make_device_batch(
                b, name_lc_id=lc, indexable=ix,
                pad_spans=pad_s, pad_anns=pad_a, pad_banns=pad_b,
                error_flag=err_of(b),
                span_slot=None if plan is None
                else plan.chunks[ci].span_slot,
                span_gid=None if plan is None
                else plan.chunks[ci].span_gid,
                reclaim_pages=None if plan is None
                else plan.chunks[ci].reclaim_pages,
                pad_reclaims=pad_rc,
            )
            for ci, (b, lc, ix) in enumerate(group)
        ]
        return IngestUnit(
            dev.stack_device_batches(dbs),
            sum(b.n_spans for b, _, _ in group),
            sum(b.n_annotations for b, _, _ in group),
            sum(b.n_binary for b, _, _ in group),
            len(group), True, sketch=sketch,
            reclaims=plan.reclaims if plan else (),
        )

    def _commit_unit(self, unit: IngestUnit) -> None:
        """Stage 3 — the ONE device-commit body behind both write
        modes: eviction-capture trigger, bucket-rotation trigger, the
        donating state swap under the write lock, host mirror bumps,
        and the sweep cadence. Serial writers run it inline under
        self._lock; the pipeline's commit thread runs it alone (it is
        the only device writer while a pipeline is active)."""
        self.ensure_writable()
        unit_id = unit.wal_seq
        with stage("store.commit", unit=unit_id), \
                stage("store.dispatch", self._h_dispatch,
                      unit=unit_id) as dispatch:
            if self._planner is not None:
                # Paged capture is at page granularity: the unit's plan
                # names exactly the pages it reclaims, and their rows
                # are pulled BEFORE the launch whose invalidation
                # scatter erases them (the per-page captured-before-
                # overwrite invariant). The ring-window trigger stays
                # dormant — its [cap_upto, wp) arithmetic is FIFO-gid
                # arithmetic.
                if unit.reclaims:
                    self._capture_pages(unit.reclaims)
            else:
                self._maybe_capture(unit.n_spans, unit.n_anns,
                                    unit.n_banns)
            self._maybe_archive(unit.n_spans)
            step = dev.ingest_steps if unit.chained else dev.ingest_step
            # The host mirrors, the WAL applied frontier, and the
            # cadence sweep all advance INSIDE the write-lock hold: a
            # checkpoint's state gather (under the read lock) then
            # always pairs the device cut with exactly-matching clocks
            # — the invariant deterministic replay (wal/recovery)
            # rebuilds launches from.
            with self._rw.write():
                self.state = step(self.state, unit.db)
                # Mirror BEFORE the frontier bump: a sketch-tier read
                # at frontier F must already include commit F's delta.
                if unit.sketch is not None:
                    self.sketch_mirror.apply(unit.sketch)
                self._wp += unit.n_spans
                self._awp += unit.n_anns
                self._bwp += unit.n_banns
                self._step_seq += 1
                if unit.wal_seq is not None:
                    self._wal_applied = unit.wal_seq
                # Dispatch accounting stops HERE: the cadence sweep
                # below is its own launch, and folding it into the
                # per-batch dispatch sketch would plant a 1-in-64
                # outlier that reads as an ingest regression.
                dispatch.done()
                self._batches_since_sweep += unit.n_parts
                if self._batches_since_sweep >= self.SWEEP_EVERY:
                    self.state = dev.dep_sweep(self.state)
                    self._step_seq += 1
                    self._batches_since_sweep = 0
            db = unit.db
            for dim, pad, valid in (
                    ("span", db.trace_id.shape[-1], unit.n_spans),
                    ("annotation", db.ann_ts.shape[-1], unit.n_anns),
                    ("binary", db.bann_key_id.shape[-1], unit.n_banns)):
                launched, padding = self._c_launch_rows[dim]
                launched.inc(pad * unit.n_parts)
                padding.inc(pad * unit.n_parts - valid)
            self._observe_ingest()

    def _write_device_many(self, group) -> None:
        """One chained launch over ≥2 chunks: pad every chunk to the
        group's max shapes, stack, and scan (dev.ingest_steps). Each
        chunk individually satisfies the ring-capacity guards, and scan
        steps run sequentially, so per-launch invariants match the
        single-chunk path's."""
        self._commit_group(group)

    def _write_device(self, batch: SpanBatch, name_lc: np.ndarray,
                      indexable: np.ndarray) -> None:
        """Pad, upload, and run the fused ingest step for one chunk that
        already fits the ring capacities."""
        self._commit_group([(batch, name_lc, indexable)])

    def _observe_ingest(self) -> None:
        """Launch accounting past the always-on dispatch sketch (the
        ``store.dispatch`` span of _commit_unit), and the write path's
        back-pressure on the device queue. Dispatch is ASYNC and the
        device runs its queue in order, so the launch leaves a marker
        behind (a scalar of its own computed from the new write_pos:
        the state's leaves are donated to the next launch, a marker
        is not) and the host then waits for the marker RUN_AHEAD
        launches back: it leads the device by that many launches and
        no more, and every wait is short (a drain of the whole queue
        is a quarter of a second with the store's lock held once the
        device sets the rate, and the acks come in bursts).
        ``store.device_sync_wait`` times the wait alone; the sketch,
        dispatch until done."""
        self._c_launches.inc()
        # Under the read lock: a reader-triggered pending sweep
        # (get_dependencies) is a DONATING step, and reading a state
        # the sweep just consumed would hit deleted buffers.
        with self._rw.read():
            marker = _launch_marker(self.state.write_pos)
        self._in_flight.append((marker, _time.perf_counter()))
        if len(self._in_flight) > self.RUN_AHEAD:
            marker, dispatched = self._in_flight.popleft()
            with stage("store.device_sync_wait"):
                jax.block_until_ready(marker)
            self._h_ingest.observe(_time.perf_counter() - dispatched)

    # Write-path sweep cadence (batches). Each sweep is one small launch
    # over the pending ring; 64 bounds a cross-batch child's link
    # latency to ~64 ItemQueue batches without taxing every write.
    SWEEP_EVERY = 64

    def _sweep_pending(self) -> None:
        """Resolve pending (late-parent) children now; see dev.dep_sweep.
        Clock reset rides the write-lock hold (checkpoint-cut
        consistency, see _commit_unit)."""
        self.ensure_writable()
        with self._rw.write():
            self.state = dev.dep_sweep(self.state)
            self._step_seq += 1
            self._batches_since_sweep = 0

    def _maybe_archive(self, incoming: int) -> None:
        """Close the current dependency time bucket on a span-volume
        cadence (one bucket per half ring capacity — the
        hourly-aggregation-timer role). Unlike the r2 watermark archive
        this is pure windowing policy: links resolve at ingest through
        the streaming hash join and never depend on ring residency."""
        cap = self.config.capacity
        if self._wp + incoming - self._archived <= cap:
            return
        self.ensure_writable()
        with self._rw.write():
            self.state = dev.dep_close_bucket(self.state)
            self._step_seq += 1
            self._batches_since_sweep = 0
            self._archived = min(
                self._wp,
                max(self._wp + incoming - cap, self._wp - cap // 2),
            )

    def _maybe_capture(self, n_s: int, n_a: int, n_b: int) -> None:
        """Eviction capture trigger, called BEFORE every device write
        with the incoming row counts: if the write would overwrite any
        uncaptured row in ANY of the three rings (the annotation rings
        lap faster than the span ring whenever spans average more side
        rows than the capacity ratio), pull the whole uncaptured window
        [_cap_upto, _wp) to the host and hand it to the sink. Riding
        the write path keeps the invariant simple — every captured row
        is still fully resident — and adds ZERO ops to the fused ingest
        step (the pull is its own read-only launch)."""
        sink = self.eviction_sink
        if sink is None:
            return
        c = self.config
        # Threshold check UNDER the capture lock: the clocks it reads
        # are _cap_lock-guarded, and the committing thread is the only
        # writer, so the uncontended acquire costs nothing while
        # keeping the read inside the lock's ownership (graftlint
        # guarded-by; the old lock-free early-out raced capture_now).
        with self._cap_lock:
            if (self._wp + n_s - self._cap_upto <= c.capacity
                    and self._awp + n_a - self._cap_a <= c.ann_capacity
                    and self._bwp + n_b - self._cap_b
                    <= c.bann_capacity):
                return
            self._capture_window()

    def _capture_window(self) -> None:  # called-under: _cap_lock
        """Pull the whole uncaptured window [cap_upto, wp) — the ONE
        capture body behind the write-path trigger and capture_now,
        serialized by _cap_lock (the serial writer holds self._lock
        too; the pipeline's commit thread holds only _cap_lock, and
        capture_now drains the pipeline before taking it).

        The PULL is synchronous — the captured-before-overwrite
        ordering invariant requires the read-only launch to complete
        before the overwriting step dispatches — but with
        capture_backlog > 0 the captured rows stay DEVICE-resident and
        the D2H + deflate + directory append move to the background
        sealer (store/pipeline.EvictionSealer), whose bounded queue is
        the only thing that can stall ingest. Capture outputs are
        fresh arrays no ingest step ever donates, so the sealer needs
        no store lock."""
        lo, hi = self._cap_upto, self._wp
        cap_anns = self._awp - self._cap_a
        cap_banns = self._bwp - self._cap_b
        if hi <= lo:
            self._cap_upto, self._cap_a, self._cap_b = (
                self._wp, self._awp, self._bwp)
            return
        t0 = _time.perf_counter()
        n_s, n_a, n_b, s_m, a_m, b_m = self._pull_evicted_rows(
            lo, hi, cap_anns, cap_banns)
        pull_s = _time.perf_counter() - t0
        if self.capture_backlog and self.capture_backlog > 0:
            if self._sealer is None:
                self._sealer = EvictionSealer(
                    self, backlog=self.capture_backlog,
                    registry=self._registry)
            self._sealer.submit(n_s, n_a, n_b, s_m, a_m, b_m,
                                lo, hi, pull_s)
        else:
            batch, gids = mats_to_batch(
                n_s, n_a, n_b, *jax.device_get((s_m, a_m, b_m)))
            kill_point("mid-seal")
            self.eviction_sink(batch, gids, lo, hi,
                               _time.perf_counter() - t0)
            self._note_sealed_locked(lo, hi)
        # Clocks advance only AFTER the pull succeeds: a transient
        # device error mid-pull leaves the window uncaptured-but-
        # resident, and the next write retries it — stamping first
        # would silently skip it forever. (An ASYNC seal failure after
        # a successful pull is counted + re-raised on the write path,
        # but its window cannot be retried — the rows may already be
        # overwritten; checkpoint cuts at the SEALED frontier so a
        # snapshot never claims an unsealed window.)
        self._cap_upto, self._cap_a, self._cap_b = (
            self._wp, self._awp, self._bwp)

    def _capture_pages(self, reclaims) -> None:
        """Paged-layout eviction capture: pull each reclaimed page's
        rows (one [lo, hi) = one page's gid range, hi - lo ==
        page_rows) through the same pull/seal machinery as the ring
        window, BEFORE the claiming unit's launch. Called on the
        committing thread only (serial writer under self._lock, or the
        pipeline's commit thread) — the same ordering position as
        _maybe_capture.

        The sealed frontier stays CONTIGUITY-gated: least-recently-
        written reclaim hands back pages out of gid order, so the
        frontier lags the newest sealed page until the older live
        pages below it are themselves reclaimed — conservative by
        design (a checkpoint cut never claims a live page's gids as
        cold-durable; the saved ring state still holds those rows)."""
        sink = self.eviction_sink
        if sink is None:
            return
        c = self.config
        with self._cap_lock:
            for lo, hi in reclaims:
                t0 = _time.perf_counter()
                n_s, n_a, n_b, s_m, a_m, b_m = self._pull_evicted_rows(
                    lo, hi, c.page_rows * 2, c.page_rows)
                pull_s = _time.perf_counter() - t0
                if self.capture_backlog and self.capture_backlog > 0:
                    if self._sealer is None:
                        self._sealer = EvictionSealer(
                            self, backlog=self.capture_backlog,
                            registry=self._registry)
                    self._sealer.submit(n_s, n_a, n_b, s_m, a_m, b_m,
                                        lo, hi, pull_s)
                else:
                    batch, gids = mats_to_batch(
                        n_s, n_a, n_b,
                        *jax.device_get((s_m, a_m, b_m)))
                    kill_point("mid-seal")
                    self.eviction_sink(batch, gids, lo, hi,
                                       _time.perf_counter() - t0)
                    self._note_sealed_locked(lo, hi)

    def _note_sealed(self, lo: int, hi: int) -> None:
        """Advance the sealed frontier — every gid below it is durable
        in the cold tier (called by the SEALER THREAD; the inline seal
        path, already under _cap_lock, uses the _locked twin).
        CONTIGUITY-GATED: if an earlier window's seal failed (a hole —
        its rows are lost from the cold tier), the frontier stays
        below the hole even as later windows seal, so a checkpoint cut
        never claims the hole and a restore can re-capture whatever of
        it the saved rings still held.

        The _cap_lock hold is load-bearing: the sealer thread races
        the commit thread's capture trigger and checkpoint's frontier
        cut, and an unlocked read-modify-write here could publish a
        torn frontier (graftlint guarded-by caught the old unlocked
        version)."""
        with self._cap_lock:
            self._note_sealed_locked(lo, hi)

    def _note_sealed_locked(self, lo: int, hi: int) -> None:  # called-under: _cap_lock
        if lo <= self._sealed_upto:
            self._sealed_upto = max(self._sealed_upto, hi)

    def sealed_frontier(self) -> int:
        """Cold-tier durability frontier (gid): every span below it is
        sealed into a cold segment. The sanctioned read for callers
        holding NO store lock (operator tooling, tests). NOT for code
        already under ``_rw`` — taking ``_cap_lock`` inside a read/
        write hold inverts the canonical capture(30) → commit(40)
        order; checkpoint's save path documents its deliberately
        unlocked reads for exactly that reason."""
        with self._cap_lock:
            return self._sealed_upto

    def seal_barrier(self) -> None:
        """Wait until every pulled capture window is sealed (no-op
        without an async sealer). Cold-tier reads and checkpoint cuts
        run behind this so a captured row is never invisible."""
        s = self._sealer
        if s is not None:
            s.drain()

    def capture_now(self) -> None:
        """Flush the uncaptured window [cap_upto, write_pos) through
        the eviction sink and wait for the seal — checkpoint restore
        uses this to re-align the capture clocks (the ann/bann mirrors
        don't survive a restart), and operators can call it to make
        the cold tier current before a planned shutdown."""
        with self._lock:
            if self.eviction_sink is None:
                return
            if self._planner is not None:
                # Paged stores capture at reclaim time only: every
                # page handed back to the free list was sealed before
                # reuse, and LIVE pages are never flushed early (their
                # rows are still fully resident and queryable — there
                # is no pending window to make current).
                self.drain_pipeline()
                self.seal_barrier()
                return
            self.drain_pipeline()
            with self._cap_lock:
                self._capture_window()
            self.seal_barrier()

    def _pull_evicted_rows(self, lo: int, hi: int, n_anns: int,
                           n_banns: int):
        """One capture window as (n_s, n_a, n_b, span_mat, ann_mat,
        bann_mat) with the row matrices still DEVICE-resident — only
        the [3] count vector syncs, so the write path never waits on
        the bulk D2H. The host mirrors predict the side-row counts
        exactly; the escalation loop is a belt-and-braces guard, not
        the steady state."""
        from zipkin_tpu.store.base import escalate_cap

        c = self.config
        k_s = min(_next_pow2(hi - lo), c.capacity)
        k_a = min(_next_pow2(max(n_anns, 1)), c.ann_capacity)
        k_b = min(_next_pow2(max(n_banns, 1)), c.bann_capacity)
        while True:
            with self._rw.read():
                counts, s_m, a_m, b_m = dev.capture_eviction_rows(
                    self.state, lo, hi, k_s, k_a, k_b)
                n_s, n_a, n_b = (
                    int(x) for x in jax.device_get(counts))
            if n_s <= k_s and n_a <= k_a and n_b <= k_b:
                return n_s, n_a, n_b, s_m, a_m, b_m
            k_s = escalate_cap(n_s, k_s, c.capacity)
            k_a = escalate_cap(n_a, k_a, c.ann_capacity)
            k_b = escalate_cap(n_b, k_b, c.bann_capacity)

    def adopt_state(self, state, spans_written: int,
                    archived: Optional[int] = None) -> None:
        """Adopt a device state produced OUTSIDE the store's write path
        (e.g. a benchmark streaming dev.ingest_step directly) and re-seed
        every host-side clock that paces sweeps and bucket rotation:

        - ``spans_written``: total spans ever written into the adopted
          state (its write_pos) — seeds the archive cadence.
        - ``archived``: span watermark of the last dependency-bucket
          close; defaults to ``spans_written`` ("just rotated").

        The sweep clock is marked dirty: the adopted state may carry
        unresolved pending children, so the first dependency read must
        run a pending sweep (the streaming-join contract) even though no
        store-mediated batch was ever written."""
        self.drain_pipeline()
        self.seal_barrier()
        self.ensure_writable()
        with self._rw.write():
            self.state = state
        self._step_seq += 1
        self._wp = int(spans_written)
        self._archived = self._wp if archived is None else int(archived)
        self._batches_since_sweep = 1
        # The adopted state's history predates the sink: re-seed the
        # capture clocks so only post-adoption evictions are captured.
        # The sealed frontier follows (nothing is pending: the barrier
        # above drained the sealer; the lock still owns the clocks).
        self._awp = self._bwp = 0
        with self._cap_lock:
            self._cap_upto = self._wp
            self._cap_a = self._cap_b = 0
            self._sealed_upto = self._cap_upto
        # The adopted state's aggregates were built outside the write
        # path: resync the sketch mirror lazily from the device.
        self.sketch_mirror.mark_cold()
        # Paged: the page table is a pure function of the resident
        # rows — rebuild it from the adopted columns (partial pages
        # stay closed; see PagePlanner.rebuild).
        if self._planner is not None:
            row_gid, trace_col = jax.device_get(
                (self.state.row_gid, self.state.trace_id))
            self._planner.rebuild(row_gid, trace_col,
                                  wal_applied=self._wal_applied)

    # -- durable write-ahead log (zipkin_tpu.wal) -----------------------

    def attach_wal(self, wal) -> None:
        """Journal every subsequent launch group into ``wal`` before
        its donating commit (the ack-after-append contract,
        docs/DURABILITY.md). Attach before live writes — groups
        committed earlier are only covered by checkpoints. The store
        does not own the log's lifecycle: callers close() it after the
        store is closed."""
        from zipkin_tpu.wal.record import dict_sizes

        with self._lock:
            self.wal = wal
            self._wal_marks = dict_sizes(self.dicts)
            if self.lineage is not None:
                wal.set_on_durable(self.lineage.on_durable)

    def attach_lineage(self, tracker) -> None:
        """Stamp every journaled launch group with lineage meta
        (obs.fleet.LineageTracker) and report its append/fsync
        progress to the tracker. Host-side only: stamps ride the WAL
        record's json header, which replay ignores — the device write
        path and step census are untouched. Order-independent with
        ``attach_wal``."""
        with self._lock:
            self.lineage = tracker
            if self.wal is not None:
                self.wal.set_on_durable(tracker.on_durable)

    def _journal_group(self, group) -> int:
        """Append one planned launch group (+ the dictionary entries
        its encode step added) to the WAL; returns the record's
        sequence. Runs on the encoding thread under self._lock, so
        append order == encode order == commit order — the property
        replay's dictionary-delta chain depends on.

        With a lineage tracker attached the record meta gains the
        commit timestamp (+ sampled B3 context) and the append is
        reported. With fsync=off/batch the WAL's on_durable callback
        fires synchronously in ``wal.append`` while THIS thread holds
        the store's encode lock; the tracker only buffers there (its
        sink, ``store.apply``, runs on a thread of its own)."""
        from zipkin_tpu.wal.record import dump_dict_deltas, encode_unit

        sizes, deltas = dump_dict_deltas(self.dicts, self._wal_marks)
        lin = self.lineage
        if lin is not None:
            extra = lin.stamp()
            seq = self.wal.append(encode_unit(
                group, self._wal_marks, deltas, extra=extra))
            lin.note_append(seq, extra)
        else:
            seq = self.wal.append(encode_unit(group, self._wal_marks,
                                              deltas))
        self._wal_marks = sizes
        return seq

    def wal_sync(self) -> None:
        """Force the attached WAL's durable frontier to the append
        frontier (fsync); no-op without a WAL. Part of the shutdown
        ordering: drain-pipeline → seal-barrier → wal_sync →
        checkpoint."""
        if self.wal is not None:
            self.wal.sync()

    # -- pipelined ingest lifecycle (store/pipeline) --------------------

    def start_pipeline(self, depth: Optional[int] = None,
                       stage_buffers: Optional[int] = None
                       ) -> IngestPipeline:
        """Switch the write path to the three-stage ingest pipeline:
        apply/write_thrift become stage 1 (encode + pad, outside
        the device critical section), a stage thread device_puts into
        double-buffered staging slots, and a commit thread holds the
        write lock only for the donating swap. ``depth`` bounds the
        prefetch queue (the writer backpressure); ``stage_buffers``
        sizes the staged-unit queue (default STAGE_BUFFERS = 2, the
        classic double buffer — see IngestPipeline). Reads are
        untouched; they see a consistent, possibly a-few-batches-stale
        state until drain_pipeline(). See docs/INGEST_PIPELINE.md."""
        with self._lock:
            if self._pipeline is not None:
                raise RuntimeError("ingest pipeline already running")
            self._pipeline = IngestPipeline(
                self, depth or self.PIPELINE_DEPTH,
                registry=self._registry,
                stage_buffers=stage_buffers or self.STAGE_BUFFERS)
            return self._pipeline

    def drain_pipeline(self) -> None:
        """Block until every accepted batch is committed to the device
        (no-op when no pipeline is running); re-raises a parked
        pipeline error. After it returns, reads see everything
        apply() accepted before the call."""
        p = self._pipeline
        if p is not None:
            p.drain()

    def stop_pipeline(self, raise_errors: bool = True) -> None:
        """Drain, stop the pipeline threads, and return the store to
        the serial write path. The quiesce runs UNDER the encode lock
        with the pipeline still published: unpublishing first would
        let a writer blocked on _lock fall through to the serial path
        and commit concurrently with the commit thread's remaining
        queued units — two device writers, which the ring-scatter
        contract forbids. Writers block on _lock until the commit
        thread has fully stopped (it never takes _lock, so this cannot
        deadlock)."""
        with self._lock:
            p = self._pipeline
            if p is None:
                return
            p.stop()
            self._pipeline = None
        err = p.take_error()
        if raise_errors and err is not None:
            raise err

    def ingest_pipeline(self) -> Optional[IngestPipeline]:
        """The running ingest pipeline, or None on the serial path —
        the stall watchdog's probe handle (obs.fleet)."""
        return self._pipeline

    def eviction_sealer(self):
        """The async capture sealer, or None when sealing is inline —
        the backlog watchdog's probe handle (obs.fleet)."""
        return self._sealer

    @contextlib.contextmanager
    def pipelined(self, depth: Optional[int] = None):
        """Scoped pipelined ingest: ``with store.pipelined(8): ...`` —
        drains and stops on exit (re-raising any parked error)."""
        pipe = self.start_pipeline(depth)
        try:
            yield pipe
        finally:
            self.stop_pipeline()

    def close(self) -> None:
        """Stop the pipeline (draining accepted batches) and the
        capture sealer (sealing pulled windows), then force the WAL
        durable — nothing accepted or captured is dropped on an
        orderly shutdown. The WAL object itself stays open (its owner
        closes it, after any final checkpoint truncation)."""
        self.stop_pipeline(raise_errors=False)
        s, self._sealer = self._sealer, None
        if s is not None:
            s.stop()
        self.wal_sync()

    # TTLs above the per-write default mark a trace pinned: its spans are
    # materialized to the host pin bank so ring eviction can't drop them.
    DEFAULT_TTL_S = 1.0

    def set_time_to_live(self, trace_id: int, ttl_seconds: float) -> None:
        tid = to_signed64(trace_id)
        with self._lock:
            self.ttls[tid] = ttl_seconds
            self._bump_read_epoch()
            pin = ttl_seconds > self.DEFAULT_TTL_S
            if not pin:
                self.pins.unpin(tid)
        if pin:
            fill_pin(self.pins, self._lock, tid, lambda: (
                self.get_spans_by_trace_ids([trace_id]) or [[]])[0])
            with self._lock:
                self._bump_read_epoch()  # bank filled: reads widened

    def get_time_to_live(self, trace_id: int) -> float:
        with self._lock:
            return self.ttls[to_signed64(trace_id)]

    # -- query-engine hooks (query/engine.py) ---------------------------

    def write_frontier(self) -> Tuple[int, int]:
        """Monotonic host-mirrored commit frontier — the result-cache
        key component. (_step_seq advances inside every donating
        write-lock hold: ingest commits, sweeps, bucket closes, state
        adoption — so ring eviction is a frontier advance too;
        _read_epoch covers host-only visibility changes: pin/TTL
        mutations and pin-bank arrivals.) No device traffic."""
        return (self._step_seq, self._read_epoch)

    def _bump_read_epoch(self) -> None:
        self._read_epoch += 1

    def ensure_sketch_mirror(self) -> SketchMirror:
        """The sketch mirror, resynced from the device aggregates if a
        state swap left it cold (checkpoint restore, adopt_state) —
        ONE batched D2H, after which incremental deltas keep it warm
        with zero device traffic. Lock order: _rw.read THEN the
        mirror's lock (the commit path takes _rw.write then the
        mirror's lock — same order, no inversion)."""
        m = self.sketch_mirror
        if not m.warm:
            with self._rw.read():
                st = self.state
                host = jax.device_get((
                    st.svc_hist, st.ann_svc_counts, st.name_presence,
                    st.ann_value_counts, st.bann_key_counts,
                    st.hll_traces, st.win_epoch, st.win_counts,
                    st.win_sums, st.win_mm,
                ))
                m.adopt(*host)
        return m

    # -- windowed analytics (aggregate/windows.py) ----------------------
    # windowed_quantiles / slo_burn / latency_heatmap come from the
    # WindowedAnalytics mixin (store/analytics.py): host-only reads
    # over the sketch mirror, shared verbatim with the device-free
    # ReplicaSpanStore (store/replica.py).

    # -- id lookups -----------------------------------------------------

    def _svc_id(self, service_name: str) -> Optional[int]:
        return self.dicts.services.get(service_name.lower())

    def get_trace_ids_by_name(
        self, service_name: str, span_name: Optional[str],
        end_ts: int, limit: int,
    ) -> List[IndexedTraceId]:
        svc = self._svc_id(service_name)
        if svc is None or limit <= 0:
            return []
        scan_only = service_scan_only(svc, self.config)
        if span_name is not None:
            name_lc = self.dicts.span_names.get(span_name.lower())
            if name_lc is None:
                return []
        else:
            name_lc = -1

        def fetch(k):
            with self._rw.read():
                mat = jax.device_get(dev.query_trace_ids_by_service(
                    self.state, svc, name_lc, end_ts, k
                ))
            cands = [(int(t), int(ts))
                     for t, ts, v in zip(mat[0], mat[1], mat[2]) if v]
            return cands, len(cands) >= k

        def index_fetch(k):
            with self._rw.read():
                mat, complete, wm = jax.device_get(
                    dev.iquery_trace_ids_by_service(
                        self.state, svc, name_lc, end_ts, k
                    )
                )
            cands = [(int(t), int(ts))
                     for t, ts, v in zip(mat[0], mat[1], mat[2]) if v]
            return cands, bool(complete), int(wm), mat.shape[1]

        # Paged layout: the index read gates (wm < write_pos -
        # capacity trust checks) are FIFO-gid arithmetic, unsound
        # against epoch-encoded gids — id lookups take the exact
        # O(ring) scan (index WRITES still run, keeping the lowering
        # within one census table of the ring step).
        if (self.config.use_index and not scan_only
                and self._planner is None):
            return self._index_first(
                limit, self.config.ann_capacity, index_fetch, fetch
            )
        return topk_ids_with_escalation(
            limit, self.config.ann_capacity, fetch
        )

    def _index_first(self, limit, k_max, index_fetch, scan_fetch):
        """index_first_topk with hit/fallback accounting (→ /metrics)."""
        return index_first_topk(limit, k_max, index_fetch, scan_fetch,
                                stats=self)

    def get_trace_ids_by_annotation(
        self, service_name: str, annotation: str, value: Optional[bytes],
        end_ts: int, limit: int,
    ) -> List[IndexedTraceId]:
        if annotation in CORE_ANNOTATIONS or limit <= 0:
            return []
        svc = self._svc_id(service_name)
        if svc is None:
            return []
        scan_only = service_scan_only(svc, self.config)
        resolved = resolve_annotation_query(self.dicts, annotation, value)
        if resolved is None:
            return []
        ann_value, bann_key, bann_value, bann_value2 = resolved

        def fetch(k):
            with self._rw.read():
                mat = jax.device_get(dev.query_trace_ids_by_annotation(
                    self.state, svc, ann_value, bann_key, bann_value,
                    bann_value2, end_ts, k,
                ))
            cands = [(int(t), int(ts))
                     for t, ts, v in zip(mat[0], mat[1], mat[2]) if v]
            return cands, len(cands) >= k

        def index_fetch(k):
            with self._rw.read():
                mat, complete, wm = jax.device_get(
                    dev.iquery_trace_ids_by_annotation(
                        self.state, svc, ann_value, bann_key, bann_value,
                        bann_value2, end_ts, k,
                    )
                )
            cands = [(int(t), int(ts))
                     for t, ts, v in zip(mat[0], mat[1], mat[2]) if v]
            return cands, bool(complete), int(wm), mat.shape[1]

        c = self.config
        # A name present BOTH as a user-annotation value and as a
        # binary key matches through either side in the scan (OR
        # semantics); the index families are per-side, so the rare
        # mixed case takes the scan.
        mixed = ann_value >= 0 and bann_key >= 0
        if (c.use_index and not mixed and not scan_only
                and self._planner is None):
            return self._index_first(
                limit, c.ann_capacity + c.bann_capacity, index_fetch,
                fetch,
            )
        return topk_ids_with_escalation(
            limit, c.ann_capacity + c.bann_capacity, fetch
        )

    def get_trace_ids_multi(self, queries) -> List[List[IndexedTraceId]]:
        """Batched index read: every query's bucket probe rides ONE
        kernel launch (dev._iq_multi_impl) instead of one ~100ms
        dispatch each; only unresolvable dictionary keys, mixed
        ann/binary names, and distrusted buckets drop to the singular
        paths. See SpanStore.get_trace_ids_multi for the query format."""
        c = self.config
        if not c.use_index or self._planner is not None or not queries:
            return super().get_trace_ids_multi(queries)
        results, probes, limits, fallback = resolve_multi_probes(
            c, self.dicts, queries
        )
        if probes:
            arrs, k, k_eff = build_probe_arrays(c, probes, limits)
            with self._rw.read():
                mats, completes, wms = jax.device_get(
                    dev.iquery_trace_ids_multi(self.state, arrs, k)
                )
            per_probe = []
            for pi, p in enumerate(probes):
                mat = mats[pi]
                cands = [
                    (int(t), int(ts))
                    for t, ts, v in zip(mat[0], mat[1], mat[2]) if v
                ]
                window_pi = min(k_eff, p[1][3])
                per_probe.append((
                    cands, bool(completes[pi]), int(wms[pi]),
                    len(cands) >= window_pi,
                ))
            gated = gate_multi_probes(probes, limits, per_probe)
            for qi, ids in gated.items():
                if ids is None:
                    fallback.append(qi)
                else:
                    self.index_hits += 1
                    results[qi] = ids
        for qi in fallback:
            q = queries[qi]
            if q[0] == "name":
                results[qi] = self.get_trace_ids_by_name(*q[1:])
            else:
                results[qi] = self.get_trace_ids_by_annotation(*q[1:])
        return [r if r is not None else [] for r in results]

    # -- trace reads ----------------------------------------------------

    @staticmethod
    def _canon_ids(trace_ids: Sequence[int]) -> Dict[int, int]:
        """signed-canonical id → caller's original id (ids ≥ 2^63 arrive
        unsigned on the wire but are stored signed)."""
        return {to_signed64(t): t for t in trace_ids}

    def _sorted_qids(self, trace_ids: Sequence[int]) -> np.ndarray:
        # Unique: duplicated request ids would double-count bucket
        # candidates on the index fast path (result duplication, and the
        # cap-escalation loop can never converge); downstream decode is
        # keyed by trace id, so duplicates reconstruct per request id.
        return np.unique(
            np.asarray([to_signed64(t) for t in trace_ids], np.int64)
        )

    def _durations_mat(self, qids: np.ndarray) -> np.ndarray:
        """[4, nq] duration matrix: trace-membership fast path when its
        exactness gate holds, the full-ring scan otherwise."""
        with self._rw.read():
            if self.config.use_index and self._planner is None:
                mat, exact = jax.device_get(
                    dev.iquery_durations(self.state, qids)
                )
                if exact:
                    return mat
            return jax.device_get(dev.query_durations(self.state, qids))

    def traces_exist(self, trace_ids: Sequence[int]) -> Set[int]:
        if not trace_ids:
            return set()
        canon = self._canon_ids(trace_ids)
        qids = self._sorted_qids(trace_ids)
        mat = self._durations_mat(qids)
        return exist_from_duration_mat(canon, qids, mat[0], self.pins,
                                       self._lock)

    def _gather_trace_mats(self, trace_ids: Sequence[int]):
        """Shared ring gather for whole-trace reads: (n_s, n_a, n_b,
        span_mat, ann_mat, bann_mat)."""
        qids = self._sorted_qids(trace_ids)
        with self._rw.read():
            st = self.state
            payload = None
            if self.config.use_index and self._planner is None:
                payload = self._gather_via_index(st, qids)
            if self._planner is not None:
                payload = self._gather_via_pages(st, qids)
            if payload is None:
                def fetch(k_s, k_a, k_b):
                    counts, s_m, a_m, b_m = jax.device_get(
                        dev.gather_trace_rows(st, qids, k_s, k_a, k_b)
                    )
                    n_s, n_a, n_b = (int(x) for x in counts)
                    return n_s, n_a, n_b, (n_s, n_a, n_b, s_m, a_m, b_m)

                payload = gather_with_escalation(self.config, fetch)
        return payload

    def get_trace_rows(self, trace_ids: Sequence[int]
                       ) -> List[Tuple[int, Span]]:
        """Ring rows of the requested traces as (row gid, Span) pairs
        in insertion order, WITHOUT pin-bank merging — the hot-tier
        read the TieredSpanStore dedupes against cold segments by gid
        (a row captured before eviction exists identically in both
        tiers while it stays resident)."""
        if not trace_ids:
            return []
        n_s, n_a, n_b, span_mat, ann_mat, bann_mat = (
            self._gather_trace_mats(trace_ids))
        if n_s == 0:
            return []
        batch, gids = mats_to_batch(
            n_s, n_a, n_b, span_mat, ann_mat, bann_mat)
        return [
            (int(g), s) for g, s in zip(gids, self.codec.decode(batch))
        ]

    def get_spans_by_trace_ids(self, trace_ids: Sequence[int]
                               ) -> List[List[Span]]:
        if not trace_ids:
            return []
        n_s, n_a, n_b, span_mat, ann_mat, bann_mat = (
            self._gather_trace_mats(trace_ids))
        spans = self._decode_gathered(
            n_s, n_a, n_b, span_mat, ann_mat, bann_mat
        )
        by_tid: Dict[int, List[Span]] = {}
        for span in spans:
            by_tid.setdefault(span.trace_id, []).append(span)
        # Pinned traces read through the eviction-exempt bank.
        with self._lock:
            apply_pin_merges(self.pins, by_tid, trace_ids, to_signed64)
        # One result per query id, duplicates included — matching the
        # in-memory reference store's behavior.
        return [
            by_tid[to_signed64(tid)]
            for tid in trace_ids
            if to_signed64(tid) in by_tid
        ]

    def _decode_gathered(
        self, n_s: int, n_a: int, n_b: int,
        span_mat: np.ndarray, ann_mat: np.ndarray, bann_mat: np.ndarray,
    ) -> List[Span]:
        return decode_gathered(
            self.codec, n_s, n_a, n_b, span_mat, ann_mat, bann_mat
        )

    def _gather_via_pages(self, st, qids: np.ndarray):
        """Whole-trace gather over the queried traces' PAGE CHAINS —
        the paged layout's answer to the index gather: the kernel
        touches K·page_rows candidate rows
        (dev.gather_paged_trace_rows) instead of scanning the full
        arena. Returns None when a chain overflowed
        page_max_chain — those reads stay exact via the ring scan."""
        chains = self._planner.chains_for(qids)
        if chains is None:
            return None
        pages, epochs = chains
        # Pad the page list to a pow2 bucket (hole pages = -1 produce
        # zero rows) so steady-state reads hit compiled shapes only.
        k = _next_pow2(max(1, len(pages)))
        pg = np.full(k, -1, np.int32)
        ep = np.zeros(k, np.int64)
        pg[:len(pages)] = pages
        ep[:len(epochs)] = epochs

        def fetch(k_s, k_a, k_b):
            counts, s_m, a_m, b_m = jax.device_get(
                dev.gather_paged_trace_rows(st, qids, pg, ep,
                                            k_s, k_a, k_b)
            )
            n_s, n_a, n_b = (int(x) for x in counts)
            return n_s, n_a, n_b, (n_s, n_a, n_b, s_m, a_m, b_m)

        return gather_with_escalation(self.config, fetch)

    def _gather_via_index(self, st, qids: np.ndarray):
        """Whole-trace gather through the trace-membership buckets (see
        dev.iquery_gather_trace_rows). Returns the gather payload, or
        None when any queried bucket fails its exactness gate — the
        caller then runs the full-ring scan gather."""
        from zipkin_tpu.store.base import index_gather_with_escalation

        def fetch(k_s, k_a, k_b):
            counts, s_m, a_m, b_m, exact = jax.device_get(
                dev.iquery_gather_trace_rows(st, qids, k_s, k_a, k_b)
            )
            n_s, n_a, n_b = (int(x) for x in counts)
            return (bool(exact), n_s, n_a, n_b,
                    (n_s, n_a, n_b, s_m, a_m, b_m))

        return index_gather_with_escalation(self.config, len(qids), fetch)

    def get_traces_duration(
        self, trace_ids: Sequence[int]
    ) -> List[TraceIdDuration]:
        if not trace_ids:
            return []
        canon = self._canon_ids(trace_ids)
        qids = self._sorted_qids(trace_ids)
        mat = self._durations_mat(qids)
        return durations_from_mat(trace_ids, canon, qids, mat, self.pins,
                                  self._lock)

    # -- name catalogs --------------------------------------------------

    def get_all_service_names(self) -> Set[str]:
        with self._rw.read():
            present = jax.device_get(self.state.ann_svc_counts) > 0
        d = self.dicts.services
        out = {
            d.decode(i) for i in np.flatnonzero(present)
            if i < len(d) and d.decode(i)
        }
        # Dictionary-overflow services (id >= max_services) cannot mark
        # the presence array — list the ones the rings still hold as
        # annotation/binary hosts (the only data that exists for them;
        # ring-window semantics vs the indexed services' lifetime
        # counter, documented in dev.overflow_service_presence).
        S = self.config.max_services
        n_over = len(d) - S
        if n_over > 0:
            pad = 1 << max(0, (n_over - 1)).bit_length()
            with self._rw.read():
                pres = jax.device_get(
                    dev.overflow_service_presence(self.state, pad)
                )
            out.update(
                name for i in np.flatnonzero(pres[:n_over])
                if (name := d.decode(S + int(i)))
            )
        return out

    def _svc_catalog_scan(self, svc: int):
        """One-launch ring-scan catalog rows for an overflow service
        (see dev.svc_scan_catalog): (names, dur_hist, ann_values,
        bann_keys). The [max_services]-sized catalog arrays cannot
        represent these services, and a clamped gather would serve
        service max_services-1's data under the wrong name.

        The kernel computes all four rows per launch, so a one-entry
        memo keyed on (svc, write position) lets a UI service page that
        calls all four endpoints pay ONE O(ring) scan + D2H instead of
        four."""
        key = (svc, self._wp)
        cached = getattr(self, "_svc_scan_memo", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        with self._rw.read():
            rows = jax.device_get(dev.svc_scan_catalog(self.state, svc))
        self._svc_scan_memo = (key, rows)
        return rows

    def get_span_names(self, service: str) -> Set[str]:
        svc = self._svc_id(service)
        if svc is None:
            return set()
        if service_scan_only(svc, self.config):
            row = self._svc_catalog_scan(svc)[0] > 0
        else:
            with self._rw.read():
                row = jax.device_get(self.state.name_presence[svc]) > 0
        d = self.dicts.span_names
        return {
            d.decode(i) for i in np.flatnonzero(row)
            if i < len(d) and d.decode(i)
        }

    # -- analytics (the reference's offline aggregates, served live) ----

    def get_dependencies(self, start_ts: Optional[int] = None,
                         end_ts: Optional[int] = None) -> Dependencies:
        """DependencyLinks from the time-tagged banks + the accumulating
        window — Aggregates.getDependencies(startDate, endDate)
        (Aggregates.scala:26-31). Without a window, the all-time total;
        with one, only banks whose children overlap it (bucket-granular).
        A pending sweep runs first so children whose parent arrived in a
        later batch are linked before the read."""
        from zipkin_tpu.aggregate.job import dependencies_from_bank

        if self._batches_since_sweep:
            with self._lock:
                if self._batches_since_sweep:
                    self._sweep_pending()
        S = self.config.max_services
        k = min(S * S, 1 << 14)
        with self._rw.read():
            st = self.state
            # Device-side compaction: ship the k densest link cells
            # (~400 KB) instead of the full [S*S, 5] bank (~20 MB —
            # the D2H was the whole dependencies p99). If more
            # than k links are live, transfer the full bank instead:
            # compaction never drops a link.
            if start_ts is None and end_ts is None:
                nz, idx, rows, ts_min, ts_max = jax.device_get((
                    *dev.total_dep_moments_compact(
                        st.dep_moments, st.dep_banks, st.dep_window, k
                    ),
                    st.ts_min, st.ts_max,
                ))
                if int(nz) > k:
                    rows = None
                    bank = jax.device_get(dev.total_dep_moments(st))
            else:
                s = dev.I64_MIN if start_ts is None else int(start_ts)
                e = dev.I64_MAX if end_ts is None else int(end_ts)
                nz, idx, rows, ts_min, ts_max = jax.device_get((
                    *dev.dep_in_range_compact(
                        st.dep_moments, st.dep_banks, st.dep_bank_ts,
                        st.dep_overflow_ts, st.dep_window,
                        st.dep_window_ts, jnp.int64(s), jnp.int64(e), k,
                    ),
                    jnp.maximum(st.ts_min, jnp.int64(s)),
                    jnp.minimum(st.ts_max, jnp.int64(e)),
                ))
                if int(nz) > k:
                    rows = None
                    bank = jax.device_get(dev.dep_moments_in_range(
                        st, jnp.int64(s), jnp.int64(e)
                    ))
        if rows is not None:
            bank = np.zeros((S * S, rows.shape[1]), np.float32)
            bank[idx] = rows
        return dependencies_from_bank(
            bank, self.dicts.services, self.config.max_services,
            float(ts_min), float(ts_max),
        )

    def archive_now(self) -> None:
        """Close the current dependency time bucket immediately: sweep
        pending children, rotate the window into a time-tagged bank (the
        hourly-aggregation-timer role of zipkin-deployment-web's
        AnormAggregator schedule)."""
        with self._lock:
            self.drain_pipeline()
            self.ensure_writable()
            with self._rw.write():
                self.state = dev.dep_close_bucket(self.state)
            self._step_seq += 1
            self._archived = self._wp
            self._batches_since_sweep = 0

    def service_duration_quantiles(
        self, service: str, qs: Sequence[float]
    ) -> Optional[List[float]]:
        svc = self._svc_id(service)
        if svc is None:
            return None
        if service_scan_only(svc, self.config):
            counts = self._svc_catalog_scan(svc)[1]
            c = self.config
            gamma = (1.0 + c.quantile_alpha) / (1.0 - c.quantile_alpha)
            return Q.quantiles_host(counts, gamma, 1.0, qs)
        with self._rw.read():
            hist = dev.svc_histogram(self.state)
            counts = jax.device_get(hist.counts[svc])
        return Q.quantiles_host(counts, hist.gamma, hist.min_value, qs)

    def top_annotations(self, service: str, k: int = 10) -> List[Tuple[str, int]]:
        svc = self._svc_id(service)
        if svc is None:
            return []
        if service_scan_only(svc, self.config):
            row = self._svc_catalog_scan(svc)[2]
        else:
            with self._rw.read():
                row = jax.device_get(self.state.ann_value_counts[svc])
        order = np.argsort(-row)[:k]
        d = self.dicts.annotations
        return [
            (d.decode(int(i)), int(row[i]))
            for i in order
            if row[i] > 0 and i < len(d)
        ]

    def top_binary_keys(self, service: str, k: int = 10) -> List[Tuple[str, int]]:
        svc = self._svc_id(service)
        if svc is None:
            return []
        if service_scan_only(svc, self.config):
            row = self._svc_catalog_scan(svc)[3]
        else:
            with self._rw.read():
                row = jax.device_get(self.state.bann_key_counts[svc])
        order = np.argsort(-row)[:k]
        d = self.dicts.binary_keys
        return [
            (d.decode(int(i)), int(row[i])) for i in order
            if row[i] > 0 and i < len(d)
        ]

    def estimated_unique_traces(self) -> float:
        with self._rw.read():
            regs = jax.device_get(self.state.hll_traces)
        return float(hll.estimate(hll.HyperLogLog(regs)))

    def counter_block(self) -> Dict[str, int]:
        """The device counter block (dev.COUNTER_BLOCK_FIELDS): ring
        occupancy/laps, queue depths, poison census, and the ingest
        counters — ONE fused read-only launch + ONE scalar-vector D2H,
        memoized per ingest step (_step_seq), so any number of metric
        scrapes between steps costs zero device traffic. Maintaining
        the block adds no ops to the ingest step itself — the derived
        values are computed at fetch time from cursors the step already
        keeps (bench_smoke's census gate holds with telemetry on)."""
        key = self._step_seq
        memo = self._cblock_memo
        if memo is not None and memo[0] == key:
            return memo[1]
        with self._rw.read():
            vec = jax.device_get(dev.counter_block(self.state))
        blk = {
            name: int(v)
            for name, v in zip(dev.COUNTER_BLOCK_FIELDS, vec)
        }
        self._cblock_memo = (key, blk)
        return blk

    def step_census(self, n_spans: int = 256, n_anns: int = 512,
                    n_banns: int = 256) -> Dict[str, int]:
        """Scatter/gather/sort census of the fused ingest step's
        StableHLO lowering at the given pad shapes — the portable proxy
        for per-batch launch cost (gated in tier-1 at
        ``census.LOWERING_TABLE``). Memoized per shape; computed only when
        asked (a trace, not a compile) — metric scrapes never pay it."""
        key = (n_spans, n_anns, n_banns)
        memo = getattr(self, "_census_memo", None)
        if memo is not None and memo[0] == key:
            return memo[1]
        from zipkin_tpu.columnar.schema import SpanBatch

        batch = SpanBatch.empty(0, 0, 0)
        # Paged configs lower with planner-assigned slot/gid columns
        # (shape [P]); synthesize empty ones so the traced shapes
        # match what _pad_unit feeds the compiled step.
        paged_cols = (
            dict(span_slot=np.zeros(0, np.int32),
                 span_gid=np.zeros(0, np.int64),
                 reclaim_pages=np.zeros(0, np.int32))
            if self.config.paged_enabled else {})
        db = dev.make_device_batch(
            batch, name_lc_id=np.zeros(0, np.int32),
            indexable=np.zeros(0, bool),
            pad_spans=n_spans, pad_anns=n_anns, pad_banns=n_banns,
            **paged_cols,
        )
        with self._rw.read():
            text = dev.ingest_step.lower(self.state, db).as_text()
        census = dev.stablehlo_op_census(text)
        self._census_memo = (key, census)
        return census

    def counters(self) -> Dict[str, float]:
        out = {k: float(v) for k, v in self.counter_block().items()}
        # Host-side guards surface through the same hook (the API's
        # /metrics reads counters() generically).
        out["anns_truncated"] = float(self.anns_truncated)
        out["banns_truncated"] = float(self.banns_truncated)
        out["index_hits"] = float(self.index_hits)
        out["index_scan_fallbacks"] = float(self.index_fallbacks)
        # jit cache-miss tracking for the ingest/staging jits: a warmed
        # pipelined steady state must hold this flat (bench_smoke's
        # pipeline phase gates the delta at zero).
        out["jit_compiles"] = float(dev.compile_count())
        # The resident query programs' twin counter: flat in steady
        # state (every dispatch hits a compiled variant) — the query
        # engine's "zero steady-state recompiles" observable.
        out["query_jit_compiles"] = float(dev.query_compile_count())
        p = self._pipeline
        if p is not None:
            out["pipeline_prefetch_depth"] = float(p.queued())
        s = self._sealer
        if s is not None:
            out["capture_backlog"] = float(s.queued())
        # Active ingest kernel paths (r12): which rank implementations
        # this config's compiled steps took, so every /metrics scrape
        # says which kernel produced its numbers (dev.active_paths —
        # trace-time records).
        paths = dev.active_paths(self.config)
        out["rank_path_counting"] = float(
            "counting" in paths.get("rank", ()))
        # Rings (span, ann, bann, pend) every compiled step wrote as
        # windows, and rings some step scattered into (the paged
        # layout's span ring; a pad past a tiny ring).
        forms = [f.split(":") for f in paths.get("ring_write", ())]
        scattered = {ring for ring, form in forms if form == "scatter"}
        out["ring_write_scatter"] = float(len(scattered))
        out["ring_write_window"] = float(
            len({ring for ring, _ in forms} - scattered))
        out["batch_spans_limit"] = float(self._max_chunk_spans())
        # Paged-layout allocator occupancy (host mirrors — the same
        # numbers the zipkin_store_pages_* gauges export).
        if self._planner is not None:
            pstats = self._planner.stats()
            out["pages_active"] = float(pstats["pages_active"])
            out["pages_free"] = float(pstats["pages_free"])
            out["page_reclaims_total"] = float(pstats["page_reclaims"])
        # Windowed-arena fold accounting (host-monotonic mirror
        # counters — zero device traffic, like every read above).
        out["window_spans"] = float(self.sketch_mirror.win_spans_total)
        out["window_errors"] = float(
            self.sketch_mirror.win_errors_total)
        return out

    def stored_span_count(self) -> float:
        """The DEVICE spans_seen counter — the adaptive controller's
        flow source reads the sketch state itself, not a host mirror.
        Served from the per-step counter block (at most one D2H per
        ingest step, shared with every other telemetry read)."""
        return float(self.counter_block()["spans_seen"])
