"""Device-resident columnar span store: state pytree + fused kernels.

The TPU replacement for the reference's scatter-indexes-into-a-DB design
(CassieSpanStore.scala:283-321 writes one batch per column family per
span batch; 5 index ops per span). Here a span batch is uploaded once as
padded columnar arrays and **one jitted ``ingest_step`` launch** updates:

- the span/annotation/binary-annotation ring buffers (the store, TTL by
  eviction — the analogue of Cassandra's span TTL, CassieSpanStore:47),
- the streaming dependency hash join (span table + pending ring +
  window bank — the ZipkinAggregateJob resolved at ingest time),
- the device index column families (service / span-name / annotation /
  binary / trace-membership bucket rings — the Cassandra index CFs),
- per-service latency histograms (p50/p95/p99 queries),
- per-service span counts, span-name presence, top-annotation counters
  (ServiceNames/SpanNames/TopAnnotations column families),
- a HyperLogLog of distinct trace ids and a count-min of spans/trace,
- ingest counters feeding the adaptive sampler.

Queries are separate jitted kernels: index reads touch O(bucket depth)
rows and carry exactness gates (never-wrapped cursor, overwrite
watermark, displaced-gid gate); the O(ring) scan kernels remain the
always-exact fallback. The host only receives the k winners.

State carries 64-bit ids/timestamps (x64 mode); all sketch state is
32-bit. Static configuration (capacities) is pytree aux data so jit
retraces only when shapes actually change.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from zipkin_tpu.columnar.schema import SpanBatch
from zipkin_tpu.models.constants import FIRST_USER_ANNOTATION_ID
from zipkin_tpu.ops import cms, hll, join
from zipkin_tpu.ops import moments as M
from zipkin_tpu.ops import quantile as Q
from zipkin_tpu.ops.hashing import dev_split64

I64_MAX = np.int64(2**63 - 1)
I64_MIN = np.int64(-(2**63))
I32_MIN = np.int32(-(2**31))
NO_TS = -1


# -- read programs, compiled before the store's read lock is taken -------

_compiling = threading.local()


def compiling() -> bool:
    """Whether this thread is inside ``compile_only()``."""
    return getattr(_compiling, "on", False)


@contextlib.contextmanager
def compile_only():
    """Inside, a ReadProgram compiles for its arguments' shapes and
    returns its results' shapes (jax.ShapeDtypeStruct), running nothing.
    TpuSpanStore._fetch passes ``shapes_of(state)`` through a read's
    launch this way before it takes the read lock, so a read that meets
    a program or a k it has not run yet compiles it off the lock: the
    commit never waits behind a compile."""
    _compiling.on = True
    try:
        yield
    finally:
        _compiling.on = False


def shapes_of(tree):
    """``tree``'s arrays as jit keys its cache on them (a committed
    array keeps its sharding). Reads only the arrays' metadata, so a
    leaf that a donating launch has since taken is fine."""
    def one(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    return jax.tree_util.tree_map(one, tree)


def _arg_key(arg):
    """What jit's cache tells a dynamic argument by: structure, shapes,
    dtypes, weak types; a Python scalar's type and not its value."""
    leaves, tree = jax.tree_util.tree_flatten(arg)
    return tree, tuple(
        (x.shape, str(x.dtype), getattr(x, "weak_type", False))
        if hasattr(x, "dtype") else type(x) for x in leaves)


class ReadProgram:
    """``jax.jit(fn, static_argnums)`` that compiles ahead inside
    ``compile_only()``. Two readers that need one new program at once
    compile it once: the second waits for the first."""

    def __init__(self, fn, static_argnums=()):
        self._jit = jax.jit(fn, static_argnums=static_argnums)
        self._static = frozenset(static_argnums)
        self._shapes: dict = {}  # guarded-by: _lock
        self._pending: dict = {}  # guarded-by: _lock
        self._lock = threading.Lock()  # lock-order: 92 read-program (leaf)
        functools.update_wrapper(self, fn)

    def __getattr__(self, name):  # lower, trace, _cache_size, ...
        return getattr(self._jit, name)

    def __call__(self, *args):
        if not compiling():
            return self._jit(*args)
        key = tuple(a if i in self._static else _arg_key(a)
                    for i, a in enumerate(args))
        while True:
            with self._lock:
                if key in self._shapes:
                    return self._shapes[key]
                waiting = self._pending.get(key)
                if waiting is None:
                    done = self._pending[key] = threading.Event()
                    break
            waiting.wait()  # another reader compiles it; it may fail
        try:
            out = self._jit.lower(*args).compile().out_info
            with self._lock:
                self._shapes[key] = out
            return out
        finally:
            with self._lock:
                del self._pending[key]
            done.set()


def read_program(static_argnums=()):
    """Decorator: the function as a ReadProgram."""
    return lambda fn: ReadProgram(fn, static_argnums)


class StoreConfig(NamedTuple):
    """Static store geometry (hashable → usable as a jit static arg)."""

    capacity: int = 1 << 16  # span ring rows
    ann_capacity: int = 1 << 18
    bann_capacity: int = 1 << 17
    max_services: int = 256
    max_span_names: int = 2048
    max_annotation_values: int = 4096
    max_binary_keys: int = 1024
    cms_depth: int = 4
    cms_width: int = 1 << 16
    hll_p: int = 14
    # 2048 buckets at alpha=0.01 cover ~1 µs .. ~10^17 µs; fewer buckets
    # silently clip long durations into the top bucket.
    quantile_buckets: int = 2048
    quantile_alpha: float = 0.01
    # Ring of time-tagged dependency-link banks: closing a time bucket
    # (dep_close_bucket) rotates the accumulating window bank into its
    # own [S*S, 5] slot stamped with the resolved children's ts range,
    # so get_dependencies(start, end) can answer a window
    # (Aggregates.getDependencies(startDate, endDate),
    # Aggregates.scala:26-31). Banks older than the ring merge into a
    # tail bank (all-time totals never regress).
    dep_buckets: int = 16
    # Streaming-join state sizes (0 = derived from capacity). The span
    # hash table resolves child → parent service at INGEST time (the
    # device replacement for the Scalding parent×child shuffle join,
    # ZipkinAggregateJob.scala:26-33); the pending ring holds children
    # whose parent hasn't arrived yet, re-probed by dep_sweep.
    span_tab_slots: int = 0  # open-addressing slots; default 2*capacity
    pend_slots: int = 0  # pending-children ring; default capacity//4
    # Device index column families (the ServiceNameIndex /
    # ServiceSpanNameIndex / AnnotationsIndex roles,
    # cassandra-schema.txt:1-22): per-key FIFO bucket rings written by
    # batch scatters at ingest, so index queries read O(bucket depth)
    # rows instead of scanning the rings — on this device class every
    # HLO op costs ~25-100ms at ring size (round 3), which made
    # O(ring) index queries ~1s each. 0 = derived from capacity.
    use_index: bool = True
    idx_service_depth: int = 0
    idx_name_buckets: int = 0
    idx_name_depth: int = 0
    idx_ann_buckets: int = 0
    idx_ann_depth: int = 0
    idx_bann_buckets: int = 0
    idx_bann_depth: int = 0
    # Trace-membership gid index (whole-trace fetch + durations).
    # buckets * depth >= 2 * ring capacity keeps the exactness gate
    # (everything a bucket displaced is already evicted) true in steady
    # state — see the trace-segment gate in _index_write.
    idx_trace_buckets: int = 0
    # Per-key cursor table slots (0 = 2x total candidate buckets). See
    # StoreState.key_tab.
    idx_key_slots: int = 0
    # Host-side per-launch span bound (the ingest batch-escalation knob,
    # r12): 0 keeps the store's legacy MAX_CHUNK default (4096); larger
    # values let one launch carry more spans, amortizing the per-launch
    # scatter entry costs (scripts/step_time.py times a step on the
    # chip). The ring-capacity guards (capacity//2, pending_slots,
    # ann/bann rings) still clamp it per launch.
    batch_spans: int = 0
    # FIFO-rank computation for the unified index write (_index_write):
    # "argsort" = the r6 stable rank sort; "counting" = the r12
    # segmented counting rank (one scatter-add + cumsum + one gather —
    # no stablehlo.sort); "auto" picks counting on the TPU backend
    # whenever the coarse watermark regime is active (wm_shift > 0)
    # and the counting scratch fits its budget, argsort otherwise
    # (incl. everywhere on CPU, where the comparator sort is the
    # faster implementation — see rank_mode). Both paths are
    # BITWISE-identical (tests/test_rank_paths.py fuzzes this), so the
    # choice is pure perf policy and may vary per launch shape.
    rank_path: str = "auto"
    # Windowed Moments-sketch analytics arena (r13,
    # aggregate/windows.py): a dense [S, W, k] grid of INTEGER
    # Moments-sketch cells keyed by (service, time bucket) — per cell
    # the (total, error, duration) count triple, the power sums
    # Σx..Σx⁴ of the quantized log-duration x, and (min, max) of x.
    # Time buckets are ring-indexed with per-slot epoch stamps, so any
    # ad-hoc window is a cell-sum and stale slots self-clear on reuse.
    # window_seconds is the bucket width; window_buckets is the ring
    # length W, giving window_seconds * window_buckets of windowed
    # retention. OPT-IN at the library layer (default 0 = the arena's
    # step update lowers out entirely and the state arrays shrink to a
    # [S, 1, k] stub so the checkpoint schema stays uniform) — the
    # daemon enables it by default via --window-seconds (example.py),
    # and the census bump it spends inside the fused step is gated in
    # store/census.py (BASE vs BASE + WINDOW_BUMP lowerings).
    window_seconds: int = 0
    window_buckets: int = 64
    # Paged span storage (r19, the Ragged-Paged-Attention layout): the
    # span ring is carved into capacity/page_rows fixed-size pages
    # allocated from a host free-list (store/paged.PagePlanner) and
    # chained per trace, so wildly skewed trace sizes share one slot
    # pool without over-provisioning. gids stay epoch-encoded
    # (gid = page_epoch * capacity + slot), which keeps the
    # slot == gid % capacity liveness invariant — every ring-scan query
    # kernel works unchanged on a paged store. "ring" (default) is the
    # historical FIFO layout; its fused-step lowering is byte-identical
    # with these fields present (static branch, store/census.py BASE).
    layout: str = "ring"
    # Rows per device page. Power of two >= 8.
    page_rows: int = 256
    # Host page-table chain bound per trace: a trace spanning more
    # pages than this stops being page-addressable and its reads fall
    # back to the exact ring-scan gather (bounded host memory; the
    # maxTraceCols-style guard at page granularity).
    page_max_chain: int = 64

    @property
    def paged_enabled(self) -> bool:
        return self.layout == "paged"

    @property
    def n_pages(self) -> int:
        return self.capacity // max(1, self.page_rows)

    @property
    def tab_slots(self) -> int:
        # Power of two: _tab_slots masks with n-1 and relies on an odd
        # double-hash step being coprime to the table size.
        return _next_pow2_int(self.span_tab_slots or 2 * self.capacity)

    @property
    def pending_slots(self) -> int:
        # Never smaller than a max-size ingest chunk: one launch's
        # unresolved children must fit without self-collision
        # (TpuSpanStore.write_batch validates this).
        return _next_pow2_int(self.pend_slots or max(1 << 16,
                                                     self.capacity // 4))

    def _derived(self, explicit: int, scale: int, lo: int,
                 hi: int) -> int:
        """Derived index geometry: total entries stay O(ring capacity)
        (the families mirror the rings they index; outsized arrays cost
        a full copy per step on backends without buffer donation)."""
        return _next_pow2_int(
            explicit or max(lo, min(hi, self.capacity // scale))
        )

    @property
    def svc_depth(self) -> int:
        return self._derived(self.idx_service_depth, 64, 64, 4096)

    @property
    def name_buckets(self) -> int:
        return self._derived(self.idx_name_buckets, 32, 256, 8192)

    @property
    def name_depth(self) -> int:
        return self._derived(self.idx_name_depth, 512, 64, 512)

    @property
    def ann_buckets(self) -> int:
        return self._derived(self.idx_ann_buckets, 16, 256, 16384)

    @property
    def ann_depth(self) -> int:
        return self._derived(self.idx_ann_depth, 512, 64, 512)

    @property
    def bann_buckets(self) -> int:
        return self._derived(self.idx_bann_buckets, 32, 256, 8192)

    @property
    def bann_depth(self) -> int:
        return self._derived(self.idx_bann_depth, 1024, 32, 256)

    # Trace-membership family: depths are fixed small constants (a
    # trace's rows per family), buckets scale so buckets*depth covers
    # 4x the corresponding ring (see the clumping note below).
    # Trace-membership rows cluster: one trace puts ALL its rows in one
    # bucket, so per-lap bucket traffic is Poisson over ~2 traces — far
    # lumpier than the per-row families. 2x-ring coverage left 13-30%
    # of buckets wrapping faster than a ring lap (gates closed, measured
    # round 4); 4x coverage via doubled depths buys the variance
    # headroom while bucket count (and the write path's rank-sort
    # geometry) stays put.
    TRACE_SPAN_DEPTH = 64
    TRACE_ANN_DEPTH = 128
    TRACE_BANN_DEPTH = 64

    @property
    def trace_buckets(self) -> int:
        return _next_pow2_int(
            self.idx_trace_buckets
            or max(256, 4 * self.capacity // self.TRACE_SPAN_DEPTH)
        )

    # -- unified index layouts -------------------------------------------
    # ALL index families — the four candidate families AND the three
    # trace-membership sub-families — live in ONE flat [slots, 3] entry
    # arena (and one cursor array + one watermark array), written by ONE
    # combined rank-sort + scatter pass per ingest step: per-family
    # writes cost ~33 fused kernels each on a backend where per-kernel
    # overhead dominates, and the r5 ablation put the
    # two separate write blocks at 380 ms of the 586 ms step. Layout per
    # family: (bucket_base, slot_base, n_buckets, depth). The candidate
    # families are the arena PREFIX, so probe-side consumers of
    # ``cand_layout`` see unchanged bases; the trace families follow
    # (their rows spend the verify/ts columns on a trace-mix word and
    # the row ts — the arena-tripling cost round 5 priced in).

    @property
    def idx_layout(self):
        B = self.trace_buckets
        return _pack_layout((
            (self.max_services, self.svc_depth),
            (self.name_buckets, self.name_depth),
            (self.ann_buckets, self.ann_depth),
            (self.bann_buckets, self.bann_depth),
            (B, self.TRACE_SPAN_DEPTH),
            (B, self.TRACE_ANN_DEPTH),
            (B, self.TRACE_BANN_DEPTH),
        ))

    CAND_SVC, CAND_NAME, CAND_ANN, CAND_BANN = range(4)
    N_CAND_FAMILIES = 4

    @property
    def cand_layout(self):
        """The candidate-family prefix of the unified arena, in the
        historical (rows, total_buckets, total_slots) shape — totals
        count the CANDIDATE families only (key-table sizing and probe
        padding depend on them, not on the trace suffix)."""
        rows, _, _ = self.idx_layout
        cand = rows[: self.N_CAND_FAMILIES]
        b_base, s_base, n_b, depth = cand[-1]
        return cand, b_base + n_b, s_base + n_b * depth

    @property
    def key_slots(self) -> int:
        return _next_pow2_int(
            self.idx_key_slots or 2 * self.cand_layout[1]
        )

    @property
    def trace_layout(self):
        """Trace-membership rows of the unified arena: bases are GLOBAL
        (into cand_idx/cand_pos/cand_wm); totals are the unified
        totals."""
        rows, total_b, total_s = self.idx_layout
        return rows[self.N_CAND_FAMILIES:], total_b, total_s

    TR_SPAN, TR_ANN, TR_BANN = range(3)

    # -- windowed analytics arena geometry --------------------------------

    @property
    def window_us(self) -> int:
        return int(self.window_seconds) * 1_000_000

    @property
    def window_enabled(self) -> bool:
        return self.window_seconds > 0 and self.window_buckets > 0

    @property
    def win_slots(self) -> int:
        """Allocated ring length: the configured ring when the arena
        is enabled, a 1-slot stub otherwise (a disabled arena keeps a
        well-formed state schema without paying [S, W, k] memory)."""
        return max(1, self.window_buckets) if self.window_enabled else 1

    @property
    def win_x_shift(self) -> int:
        """Quantization shift: fine histogram bucket index >> shift
        keeps x < 2^MAX_X_BITS, bounding the int64 Σx⁴ cell sums.
        Delegates to the ONE definition site (aggregate.windows, the
        mirror's twin) so device and mirror can never disagree."""
        from zipkin_tpu.aggregate.windows import win_x_shift

        return win_x_shift(self.quantile_buckets)


def config_from_dict(d: dict) -> StoreConfig:
    """The StoreConfig a stored or shipped dict describes (a snapshot's
    meta.json, the replication handshake): keys this build does not
    know are dropped, keys the dict lacks take the defaults, so an
    option can be added or deleted without stranding what was written
    before."""
    return StoreConfig(**{
        k: v for k, v in d.items() if k in StoreConfig._fields})


def _next_pow2_int(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _pack_layout(fams):
    """((n_buckets, depth), ...) → (per-family (bucket_base, slot_base,
    n_buckets, depth), total_buckets, total_slots) — the shared packing
    of the unified index arrays."""
    out = []
    b_base = s_base = 0
    for n_b, depth in fams:
        out.append((b_base, s_base, n_b, depth))
        b_base += n_b
        s_base += n_b * depth
    return tuple(out), b_base, s_base


# -- fast scatter primitives -------------------------------------------------
#
# Measured on the real chip (round 4; PERF.md 6, "r4/r5 records"): any
# 64-bit scatter (set/add/min/max) on this backend serializes at
# ~100-125 ns/row — a 917k-row index write costs ~100 ms — while 1-D
# int32 scatter-set with unique indices vectorizes at ~4.5 ns/row, and
# 2-D scatters are slow in EVERY dtype. Sorts and elementwise i64 math
# are cheap. So the hot ingest writes route through these helpers:
# bitcast i64 arrays to two i32 bit-planes and issue two strided 1-D
# unique scatters (10.4 ms vs 116 ms for 917k rows into 8M, measured).
# Callers must guarantee uniqueness among the surviving (ok) indices;
# dropped rows are remapped to DISTINCT out-of-bounds slots so the
# promise holds for the whole index vector.
#
# Rule: a state leaf that a step scatters into lives in PLANE form
# (see "the index arena's plane form" below), and only HASHED slots are
# scattered: a launch's CONSECUTIVE ring slots go in as a window
# (_ring_write below), on the leaf as it is.


def _p32(x):
    """i64[...] -> i32[..., 2] bit-planes (free bitcast)."""
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _p64(p):
    """i32[..., 2] bit-planes -> i64[...] (free bitcast)."""
    return jax.lax.bitcast_convert_type(p, jnp.int64)


def _oob_unique(idx, ok, n_rows: int):
    """Remap ~ok rows to distinct OOB indices (>= n_rows) so a dropping
    scatter may honestly claim unique_indices."""
    n = idx.shape[0]
    return jnp.where(
        ok, idx.astype(jnp.int32),
        jnp.int32(n_rows) + jnp.arange(n, dtype=jnp.int32),
    )


def _uset(arr, idx, vals, ok):
    """arr.at[idx[ok]].set(vals[ok]) for a 1-D arr of any dtype; indices
    must be unique among ok rows. i64 goes via two i32 plane scatters
    (the only fast 64-bit scatter on this backend); other dtypes scatter
    directly with the uniqueness promise."""
    safe = _oob_unique(idx, ok, arr.shape[0])
    if arr.dtype == jnp.int64:
        p = _p32(arr)
        v = _p32(jnp.asarray(vals, jnp.int64))
        lo = p[:, 0].at[safe].set(v[:, 0], mode="drop",
                                  unique_indices=True)
        hi = p[:, 1].at[safe].set(v[:, 1], mode="drop",
                                  unique_indices=True)
        return _p64(jnp.stack([lo, hi], axis=-1))
    return arr.at[safe].set(jnp.asarray(vals, arr.dtype), mode="drop",
                            unique_indices=True)


def _ring_windowed(cap: int, pad: int) -> bool:
    """Whether a ``pad``-row launch writes a ``cap``-row ring as windows
    (``_ring_write``): from shapes alone, at trace time. A pad past the
    ring (tiny-ring tests) has no window to land in and scatters."""
    return pad <= cap


def _ring_write(arr, start, vals, n):
    """arr[(start + k) % cap] = vals[k] for every k < n: the write of
    ``n`` CONSECUTIVE ring slots from ``start`` (< cap), bit for bit
    what ``_uset(arr, (start + arange(P)) % cap, vals, arange(P) < n)``
    leaves, without a scatter: two P-row windows, each a slice read, a
    select and a ``dynamic_update_slice`` on the donated leaf in its own
    dtype (an i64 column is never bitcast to planes: that bitcast of the
    whole column and the stack back were a quarter of the step at the
    2^22 ring, PERF.md 6, PR 30). Window A starts at
    min(start, cap - P) and holds every slot up to the ring's end;
    window B starts at 0 and holds what lapped. Slot s0 + j of a window
    is batch row k = (s0 + j - start) mod cap and is written where
    k < n, so a window no row of the batch falls in writes back what it
    read (P rows, nothing of the ring's size) and a lap needs no
    ``lax.cond``. The batch rows come by slicing ``vals`` doubled, not
    by a gather. n <= P; a pad past the ring falls back to ``_uset``."""
    cap, pad = arr.shape[0], vals.shape[0]
    j = jnp.arange(pad, dtype=jnp.int32)
    start, n = start.astype(jnp.int32), n.astype(jnp.int32)
    if not _ring_windowed(cap, pad):
        return _uset(arr, (start + j) % cap, vals, j < n)
    twice = jnp.tile(jnp.asarray(vals, arr.dtype), 2)

    def window(a, s0):
        r = (s0 - start) % cap  # the window's first slot is batch row r
        t = cap - r             # and from row t of the window on, j - t
        head = j < t
        k = jnp.where(head, r + j, j - t)
        v = jnp.where(
            head,
            jax.lax.dynamic_slice(twice, (jnp.minimum(r, pad),), (pad,)),
            jax.lax.dynamic_slice(twice, (pad - jnp.minimum(t, pad),),
                                  (pad,)))
        old = jax.lax.dynamic_slice(a, (s0,), (pad,))
        return jax.lax.dynamic_update_slice(
            a, jnp.where(k < n, v, old), (s0,))

    return window(window(arr, jnp.minimum(start, cap - pad)), jnp.int32(0))


def _uset_p(arr2, idx, vals, ok):
    """``arr2`` is an [M, 2] i32 PLANE-PAIR array (the bit-planes of a
    logical i64 vector, kept in plane form so every load is an 8-byte
    i32 row gather instead of an i64 gather — i64 gathers are the
    dominant cost class on this backend). Scatter-set of
    logical i64 ``vals`` at unique ``idx`` among ok rows."""
    v = _p32(jnp.asarray(vals, jnp.int64))
    safe = _oob_unique(idx, ok, arr2.shape[0])
    lo = arr2[:, 0].at[safe].set(v[:, 0], mode="drop",
                                 unique_indices=True)
    hi = arr2[:, 1].at[safe].set(v[:, 1], mode="drop",
                                 unique_indices=True)
    return jnp.stack([lo, hi], axis=-1)


# -- the index arena's plane form ---------------------------------------------
#
# The index arena's logical [total_slots, 3] i64 rows of (gid, verify,
# ts) are held as six [total_slots] i32 leaves — plane 2c is the low
# word of column c, plane 2c + 1 its high word, the bits ``_p32`` would
# give — so the step's entry write is six 1-D unique i32 scatters into
# donated leaves, in place, and costs the batch. Slicing a plane out of
# an i64 or stacked leaf and stacking it back is a pass over the whole
# leaf every step: 60 % of the step at a 1.99 GB arena (PERF.md 6,
# PR 26). Readers gather 4-byte words at the slots they probe and
# combine lo and hi for the gathered rows only.
ARENA_COLS = 3
ARENA_PLANES = 2 * ARENA_COLS


def _arena_init(total_slots: int):
    """Every logical entry (-1, -1, -1): both words of i64 -1 are -1."""
    return tuple(jnp.full(total_slots, -1, jnp.int32)
                 for _ in range(ARENA_PLANES))


def _arena_col(planes, idx, col: int):
    """Logical i64 column ``col`` (0 gid, 1 verify, 2 ts) of the arena
    rows ``idx`` (any shape): two word gathers."""
    return _p64(jnp.stack(
        [planes[2 * col][idx], planes[2 * col + 1][idx]], axis=-1))


def _arena_window(planes, start, depth: int):
    """``depth`` consecutive logical rows from ``start`` -> [depth, 3]
    i64: one contiguous slice per plane (a bucket's FIFO window)."""
    w = [jax.lax.dynamic_slice(p, (start,), (depth,)) for p in planes]
    return jnp.stack(
        [_p64(jnp.stack(w[2 * c:2 * c + 2], axis=-1))
         for c in range(ARENA_COLS)], axis=-1)


def _arena_set(planes, idx, vals, ok):
    """Row scatter ``arena[idx[ok]] = vals[ok]`` of logical [N, 3] i64
    rows: one 1-D unique i32 scatter per plane (2-D scatters are slow
    in every dtype on this backend; 1-D unique i32 is ~4.5 ns/row)."""
    v = _p32(jnp.asarray(vals, jnp.int64))  # [N, 3, 2]
    safe = _oob_unique(idx, ok, planes[0].shape[0])
    return tuple(
        p.at[safe].set(v[:, j // 2, j % 2], mode="drop",
                       unique_indices=True)
        for j, p in enumerate(planes))


def arena_rows64(planes):
    """Host view: numpy planes ([..., M] each) -> the logical
    [..., M, 3] i64 arena (tests, checkpoint migration checks)."""
    p = np.stack([np.asarray(x) for x in planes], axis=-1)
    return np.ascontiguousarray(p).view(np.int64)


def arena_planes(rows64):
    """Host inverse of ``arena_rows64``: a logical [..., M, 3] i64
    arena (the pre-revision-19 checkpoint leaf) -> six contiguous
    [..., M] i32 planes."""
    p = np.ascontiguousarray(np.asarray(rows64, np.int64)).view(np.int32)
    return tuple(np.ascontiguousarray(p[..., j])
                 for j in range(ARENA_PLANES))


# Per-key record table: i32 fingerprints (claims ride the vectorized
# duplicate-index i32 scatter-min; see _index_write). 0x7FFFFFFF is the
# empty sentinel — it loses every min-war and _fp31 never produces it.
# INT32_MIN is the restore tombstone: unclaimable (wins every min-war)
# and outside _fp31's range, so it matches no lookup (poison_ann_trust,
# checkpoint rev<9 migration).
_FP_EMPTY = jnp.int32(0x7FFFFFFF)
_FP_TOMB = jnp.int32(-0x80000000)
# Claim failure scales ~load^PROBES (slots only fill, so a key that
# fails all probes fails forever and its queries lose the per-key fast
# path). 3 probes at the bench's 0.25 load keeps misses under ~2% for
# one extra i32 gather+war round per ingest step.
_KEY_PROBES = 3


def _fp31(k48):
    """48-bit key -> 31-bit non-negative fingerprint (never _FP_EMPTY).
    Takes bits 17..47; _tab_slots consumes the low log2(T) bits for the
    probe sequence, so at T = 2^23 slots the two overlap by ~6 bits and
    same-slot keys already agree on that much of the fingerprint: the
    same-slot collision odds are ~2^-(31 - max(0, log2(T) - 17)), about
    2^-25 at bench geometry — NOT the full 2^-31. A collision only
    makes two keys share a record and merge watermarks (conservative:
    extra scan fallbacks, never a wrong answer), so the margin is spent
    on fallback rate, not correctness. (Kept as a plain shift rather
    than a mixed hash: the fingerprints live in checkpoints, and
    changing the function would tombstone every restored key table.)"""
    f = (k48 >> jnp.uint64(17)).astype(jnp.int32) & jnp.int32(0x7FFFFFFF)
    return jnp.minimum(f, jnp.int32(0x7FFFFFFE))


def _seg_reduce_sorted(segid, vals, op, identity):
    """Running segmented reduce over rows SORTED by segid (log-doubling:
    ~20 shifted elementwise steps, all vectorized — i64 elementwise is
    fine on this backend, only i64 SCATTER is serialized). Returns the
    running reduction; row i holds op over its segment's rows <= i, so
    each segment's LAST row holds the full segment reduction."""
    n = vals.shape[0]
    d = 1
    while d < n:
        shifted = jnp.concatenate(
            [jnp.full(d, identity, vals.dtype), vals[:-d]])
        same = jnp.concatenate(
            [jnp.zeros(d, bool), segid[d:] == segid[:-d]])
        vals = jnp.where(same, op(vals, shifted), vals)
        d *= 2
    return vals


def _unsort_i32(order, svals, fill=0):
    """Scatter sorted-space i32 values back to original row order (the
    permutation is unique by construction)."""
    n = order.shape[0]
    return jnp.full(n, fill, jnp.int32).at[order].set(
        svals, unique_indices=True)


def _slot_war(slot, packed, active, n_slots: int):
    """Explicit arbitration replacing a read-back scatter-min war: among
    ``active`` rows contending for the same slot, the numerically
    smallest ``packed`` wins — bitwise the same outcome as the old
    ``.at[slot].min(packed)`` + re-read, but built from sorts and
    elementwise ops (i64 scatters serialize at ~100 ns/row on this
    backend; sorts are nearly free — PERF.md 6, "r4/r5 records").

    Returns (seg_min, write_row), both in ORIGINAL row order:
    ``seg_min`` is the minimum packed offered at the row's slot this
    round (I64_MAX for inactive rows), ``write_row`` marks exactly one
    row per contended slot (safe for a unique scatter)."""
    n = packed.shape[0]
    s = jnp.where(active, slot.astype(jnp.int32), jnp.int32(n_slots))
    # Lexicographic (slot, packed) via two stable argsorts.
    ord1 = jnp.argsort(packed, stable=True)
    ord2 = jnp.argsort(s[ord1], stable=True)
    order = ord1[ord2]
    ss = s[order]
    sp = jnp.where(active[order], packed[order], I64_MAX)
    # Sorted ascending by packed within each slot run, so the running
    # segmented min broadcasts the winner's word to every row of a run.
    seg_min_sorted = _seg_reduce_sorted(ss, sp, jnp.minimum, I64_MAX)
    first = jnp.concatenate([jnp.ones(1, bool), ss[1:] != ss[:-1]])
    write_sorted = first & active[order]
    inv = jnp.argsort(order)  # unsort permutation
    seg_min = seg_min_sorted[inv]
    write_row = _unsort_i32(order, write_sorted.astype(jnp.int32)) > 0
    return seg_min, write_row


_LO_FLIP = jnp.int32(-0x80000000)  # sign-flip: u32 order as i32 order

# Coarse gid-watermark granularity divisor: overstatement is bounded by
# capacity / 2^_WM_COARSE_FRAC_BITS (see _coarse_gid32 and the
# wm_shift derivation in ingest_step).
_WM_COARSE_FRAC_BITS = 8


def _war_max64(arr, idx, vals, ok):
    """``arr.at[idx[ok]].max(vals[ok])`` for an i64 WATERMARK array —
    EXACT — via a two-phase i32 plane war (duplicate indices allowed;
    i32 scatter-max vectorizes at ~9 ns/row on this backend while i64
    serializes at ~100 ns/row):

    1. hi planes war (signed i32 compare == i64 order on high words);
    2. one i32 gather reads each row's SETTLED hi;
    3. lo planes war, entered only by rows whose hi equals the settled
       hi, against a base that keeps the slot's old lo only where its
       hi survived (both conditions computable elementwise).

    The lo plane is sign-flipped so unsigned 32-bit order matches i32
    compare; I64_MIN's planes are (INT32_MIN, INT32_MIN) under the
    flip, losing every war — the empty sentinel round-trips bit-exact.
    Earlier conservative variants (independent plane maxes) overstated
    by up to a plane boundary and systematically closed the bucket
    gates in the round-4 bench — a watermark's VALUE is the product."""
    neg = jnp.int32(-0x80000000)
    p = _p32(arr)
    lo_arr = p[:, 0] ^ _LO_FLIP
    hi_arr = p[:, 1]
    v = _p32(jnp.asarray(vals, jnp.int64))
    safe = jnp.where(ok, idx.astype(jnp.int32), arr.shape[0])
    hi_off = jnp.where(ok, v[:, 1], neg)
    hi_after = hi_arr.at[safe].max(hi_off, mode="drop")
    settled = hi_after[jnp.where(ok, idx.astype(jnp.int32), 0)]
    lo_base = jnp.where(hi_after == hi_arr, lo_arr, neg)
    lo_off = jnp.where(ok & (v[:, 1] == settled),
                       v[:, 0] ^ _LO_FLIP, neg)
    lo_after = lo_base.at[safe].max(lo_off, mode="drop")
    return _p64(jnp.stack([lo_after ^ _LO_FLIP, hi_after], axis=-1))


def _war_min64(arr, idx, vals, ok):
    """Exact ``arr.at[idx[ok]].min(vals[ok])`` — bitwise NOT reverses
    i64 order without overflow, so a min-war is a max-war in the
    complemented domain (an I64_MAX empty sentinel complements to
    _war_max64's I64_MIN one)."""
    return ~_war_max64(~arr, idx, ~jnp.asarray(vals, jnp.int64), ok)


def _coarse_gid32(gids, ok, shift: int):
    """Per-row i32 contribution of a GID to the SHARED coarse watermark
    scatter (_index_write's unified war — one vectorized i32
    duplicate-index scatter-max instead of _war_max64's two plane wars
    + settled gather per family): ceil to the next 2^shift boundary, so
    the stored watermark OVERSTATES the true max displaced gid by
    < 2^shift — against trust margins of >= ring capacity (displaced
    entries are ring-laps old in steady state), callers pick shift so
    the overstatement is a sub-percent slice of the margin. Overstating
    a watermark costs scan fallbacks, never a wrong answer. ~ok rows
    contribute 0 (the zeroed scratch's no-op), so untouched slots keep
    their exact i64 value on fold-back — empty I64_MIN sentinels, and
    underfull-bucket trust before the first wrap, survive bit-exact.
    gids are non-negative; the coarse domain holds to 2^(31 + shift)
    spans of lifetime (2^45+ at bench shapes), and gids past it
    SATURATE to the domain ceiling — the watermark pins high and the
    gates stay conservatively closed, never silently re-open (an
    unclamped int32 cast would wrap negative and freeze the watermark
    instead). Callers must route shift == 0 through the exact
    _war_max64 path instead: the un-shifted domain saturates at ~2.1B
    lifetime spans, an unrecoverable cliff for long-lived small stores
    — _index_write's exact_gid_wars branch does."""
    v = jnp.minimum(
        (jnp.asarray(gids, jnp.int64) >> shift) + 1,
        jnp.int64(0x7FFFFFFF),
    ).astype(jnp.int32)
    return jnp.where(ok & (jnp.asarray(gids, jnp.int64) >= 0), v, 0)


# Coarse-ts watermark granularity: candidate-family overwrite
# watermarks war in 2^_WM_TS_SHIFT-µs units (~1.05 s). The trust gate
# compares a query's limit-th candidate ts against the watermark;
# displaced entries are ring-laps (minutes+) older than any trusted
# candidate in steady state, so a <= 1.05 s ceil overstatement costs at
# most a rare extra scan fallback, never a wrong answer. Contributions
# past the coarse ceiling (ts >= 2^(31+shift) µs, ~year 2041) take the
# EXACT plane-war fallback below instead of saturating — saturation
# would close the bucket forever.
_WM_TS_SHIFT = 20


def _coarse_ts32(ts, ok, shift: int):
    """Per-row i32 contribution of a displaced TS to the shared coarse
    watermark scatter: ceil in 2^shift-µs units. Negative ts (the
    I64_MIN / NO_TS sentinels) contribute nothing — a displaced entry
    without a timestamp can never match a query (the kernels require
    ts >= 0), so omitting it cannot un-protect an answer. Rows at or
    past the coarse ceiling ALSO contribute nothing here; the caller
    MUST route exactly those rows (the overflow mask) through the
    exact war (_index_write's cond). The ceiling is
    (2^31 - 1) << shift, NOT 2^(31+shift): a ts in the last coarse
    unit below 2^(31+shift) would ceil to exactly 2^31, whose i32 cast
    wraps NEGATIVE — losing the scatter-max and silently UNDERSTATING
    the watermark, the one failure direction the gates can't absorb."""
    t = jnp.asarray(ts, jnp.int64)
    lim = jnp.int64((1 << 31) - 1) << shift
    in_dom = ok & (t >= 0) & (t < lim)
    v = ((t >> shift) + 1).astype(jnp.int32)
    return jnp.where(in_dom, v, 0), ok & (t >= lim)


def _ring(n, dtype, fill=0):
    return jnp.full((n,), fill, dtype)


@jax.tree_util.register_pytree_node_class
@dataclass
class StoreState:
    """The carried state pytree. All arrays; config is static aux."""

    config: StoreConfig

    # -- span ring ------------------------------------------------------
    trace_id: jnp.ndarray
    span_id: jnp.ndarray
    parent_id: jnp.ndarray
    name_id: jnp.ndarray  # original-case span-name dictionary id
    name_lc_id: jnp.ndarray  # lowercased id for matching; -1 = empty name
    service_id: jnp.ndarray  # owning service (server-preferred); -1 none
    ts_cs: jnp.ndarray
    ts_cr: jnp.ndarray
    ts_sr: jnp.ndarray
    ts_ss: jnp.ndarray
    ts_first: jnp.ndarray
    ts_last: jnp.ndarray
    duration: jnp.ndarray
    flags: jnp.ndarray
    indexable: jnp.ndarray  # bool: should_index() computed on host
    row_gid: jnp.ndarray  # global row id occupying each slot; -1 empty
    write_pos: jnp.ndarray  # scalar i64: total spans ever written

    # -- annotation ring ------------------------------------------------
    ann_gid: jnp.ndarray  # global span row the annotation belongs to; -1
    ann_ts: jnp.ndarray
    ann_value_id: jnp.ndarray
    ann_service_id: jnp.ndarray
    ann_endpoint_id: jnp.ndarray
    ann_write_pos: jnp.ndarray

    # -- binary-annotation ring -----------------------------------------
    bann_gid: jnp.ndarray
    bann_key_id: jnp.ndarray
    bann_value_id: jnp.ndarray
    bann_type: jnp.ndarray
    bann_service_id: jnp.ndarray
    bann_endpoint_id: jnp.ndarray
    bann_write_pos: jnp.ndarray

    # -- streaming aggregate state (never evicted) ----------------------
    # Dependency links resolve at INGEST time through a streaming hash
    # join: every span is inserted into ``span_tab`` (open addressing,
    # key = mix48(trace_id, span_id), payload = service); every child
    # batch row probes the table for its parent and, when found, its
    # duration folds into the accumulating window bank ``dep_window``
    # via the exact segmented-Moments reduction. Children whose parent
    # hasn't arrived yet wait in the pending ring and are re-probed by
    # ``dep_sweep``. This replaces the r2 eviction-watermark ring join,
    # whose O(ring) sort cost every read and archive pass paid —
    # measured 8.8s per get_dependencies at a 2^22 ring (round 3).
    # ``dep_close_bucket`` rotates the window into a time-tagged slot of
    # ``dep_banks`` (the hourly-Dependencies-rows role,
    # Dependencies.scala:59-67); displaced slots merge into the all-time
    # tail ``dep_moments``. All parts are disjoint:
    # total = combine(tail, banks, window).
    dep_moments: jnp.ndarray  # [S*S, 5] f32 — tail (pre-ring) link moments
    dep_banks: jnp.ndarray  # [K, S*S, 5] f32 — time-tagged bucket ring
    dep_bank_ts: jnp.ndarray  # [K, 2] i64 — (min first_ts, max last_ts)
    dep_overflow_ts: jnp.ndarray  # [2] i64 — ts range of the tail bank
    dep_bank_seq: jnp.ndarray  # scalar i64 — next bucket slot
    dep_window: jnp.ndarray  # [S*S, 5] f32 — accumulating current bucket
    dep_window_ts: jnp.ndarray  # [2] i64 — ts range folded into window
    # Dep-join hash table, stored as the [H, 2] i32 BIT-PLANES of the
    # logical packed word (mix48 << 16)|(svc+1 << 1)|1 (_TAB_EMPTY when
    # free): every probe round's load is then an 8-byte i32 row gather
    # instead of an i64 gather — the dominant cost class on this
    # backend — and every store a pair of vectorized
    # i32 plane scatters. Bitcast-identical to the old i64 column
    # (checkpoint revision 11 migrates by view, losslessly).
    span_tab: jnp.ndarray  # [H, 2] i32 — planes of the packed word
    pend_key: jnp.ndarray  # [Q] i64 — (mix48(tid,parent) << 16)|(csvc+1<<1)|1
    pend_dur: jnp.ndarray  # [Q] i64 — pending child duration
    pend_tsf: jnp.ndarray  # [Q] i64 — pending child first_ts
    pend_tsl: jnp.ndarray  # [Q] i64 — pending child last_ts
    pend_pos: jnp.ndarray  # scalar i64 — pending ring cursor

    # -- index column families -------------------------------------------
    # ALL seven index families — the four candidate families (service /
    # service+name / service+ann-value / service+binary) AND the three
    # trace-membership sub-families — share ONE flat arena of logical
    # [total_slots, 3] i64 (gid, verify, ts) rows, held as six
    # [total_slots] i32 bit-plane leaves (see "the index arena's plane
    # form"), one [total_buckets] i64 cursor array, and one watermark
    # array, laid out per
    # StoreConfig.idx_layout (candidate families are the prefix; the
    # probe-side ``cand_layout`` view is unchanged). One combined
    # rank-sort + scatter pass serves every family (_index_write). A
    # bucket's FIFO ring never wrapping (cursor <= depth) means it
    # holds EVERY entry ever written for its key → an index read is
    # complete; a wrapped CANDIDATE bucket is still exact when the
    # query's last candidate ranks >= its ts watermark, and a wrapped
    # TRACE bucket when everything it displaced is already evicted
    # (gid watermark < write_pos - capacity) — the exactness gate for
    # whole-trace fetch and durations. The watermark array carries ts
    # values on the candidate prefix and gids on the trace suffix;
    # every query slices by family, never across the boundary.
    cand_idx: Tuple[jnp.ndarray, ...]  # 6 x [total_slots] i32 planes
    cand_pos: jnp.ndarray
    cand_wm: jnp.ndarray
    # Middle-host trust: annotation/binary index entries are written
    # under a span's (min, max) annotation-host pair, so a span whose
    # annotations span 3+ DISTINCT host services is never indexed under
    # its middle hosts. ann_poison[s] is the max span gid that had
    # service s as a middle host; annotation-family fast paths for s are
    # trusted only once that span is evicted (gid < write_pos -
    # capacity) — the same displaced-gid gate as tr_wm, self-healing as
    # the ring turns over.
    ann_poison: jnp.ndarray  # [S] i64, I64_MIN = never poisoned
    # Per-key record table (the device rendition of Cassandra's per-key
    # index rows, cassandra-schema.txt:4-8): open addressing keyed by a
    # 31-bit FINGERPRINT of the candidate families' verify word (i32 —
    # duplicate-index i32 scatter-min vectorizes on this backend where
    # the exact i64 word war serialized at ~100 ns/row). key_wm[slot] is
    # the max span gid attributed to an entry ever DISPLACED from a
    # recorded key's bucket window; a query whose key record shows
    # key_wm < write_pos - capacity holds every RESIDENT entry of that
    # key in the bucket window — complete even when bucket-mates wrapped
    # the bucket (the sparse-key aliasing fallback).
    # Claim-on-empty ONLY, never stolen. Distinct keys may share a
    # (slot, fingerprint) — they then share a record and their
    # watermarks merge, which only OVERSTATES (extra fallbacks, never a
    # wrong answer); an absent record (congestion) degrades to the
    # per-bucket gates the same way.
    key_tab: jnp.ndarray  # [T] i32 — fp31(key48); _FP_EMPTY empty
    key_wm: jnp.ndarray  # [T] i64 — max displaced gid; I64_MIN none
    svc_hist: jnp.ndarray  # [S, B] f32 — per-service duration log-histogram
    svc_span_counts: jnp.ndarray  # [S] f32
    ann_svc_counts: jnp.ndarray  # [S] f32 — services seen on any annotation
    name_presence: jnp.ndarray  # [S, N] f32 — (ann-service, span-name)
    ann_value_counts: jnp.ndarray  # [S, A] f32 — top annotations per service
    bann_key_counts: jnp.ndarray  # [S, K] f32 — top binary keys per service
    hll_traces: jnp.ndarray  # [2^p] i32 — distinct trace ids
    cms_trace_spans: jnp.ndarray  # [depth, width] i32 — spans per trace
    ts_min: jnp.ndarray  # scalar i64 — earliest ts seen (ingest wall)
    ts_max: jnp.ndarray  # scalar i64
    # Windowed Moments-sketch arena (aggregate/windows.py): dense
    # (service × ring-indexed time bucket) integer cells updated inside
    # the fused step. win_epoch[w] stamps the ABSOLUTE time bucket a
    # slot currently holds (-1 = never used); a newer bucket landing
    # on the slot zeroes every service's cell row first (stale cells
    # self-clear, no sweep). All fields are integers accumulated by
    # scatter-add/-max so the host mirror twins match BITWISE.
    win_epoch: jnp.ndarray  # [W] i64 — absolute bucket per slot; -1 empty
    win_counts: jnp.ndarray  # [S, W, 3] i32 — (total, err, n_duration)
    win_sums: jnp.ndarray  # [S, W, 4] i64 — Σx, Σx², Σx³, Σx⁴
    win_mm: jnp.ndarray  # [S, W, 2] i32 — (max(-x), max(x)); I32_MIN empty
    counters: Dict[str, jnp.ndarray] = field(default_factory=dict)

    _FIELDS = (
        "trace_id", "span_id", "parent_id", "name_id", "name_lc_id",
        "service_id", "ts_cs", "ts_cr", "ts_sr", "ts_ss", "ts_first",
        "ts_last", "duration", "flags", "indexable", "row_gid", "write_pos",
        "ann_gid", "ann_ts", "ann_value_id", "ann_service_id",
        "ann_endpoint_id", "ann_write_pos",
        "bann_gid", "bann_key_id", "bann_value_id", "bann_type",
        "bann_service_id", "bann_endpoint_id", "bann_write_pos",
        "dep_moments", "dep_banks", "dep_bank_ts", "dep_overflow_ts",
        "dep_bank_seq", "dep_window", "dep_window_ts", "span_tab",
        "pend_key", "pend_dur", "pend_tsf", "pend_tsl", "pend_pos",
        "cand_idx", "cand_pos", "cand_wm",
        "ann_poison", "key_tab", "key_wm",
        "svc_hist", "svc_span_counts", "ann_svc_counts",
        "name_presence", "ann_value_counts", "bann_key_counts",
        "hll_traces", "cms_trace_spans", "ts_min", "ts_max",
        "win_epoch", "win_counts", "win_sums", "win_mm", "counters",
    )

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self._FIELDS), self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        return cls(config, *children)

    def replace(self, **kw) -> "StoreState":
        return replace(self, **kw)


def init_state(config: StoreConfig = StoreConfig()) -> StoreState:
    c = config
    S = c.max_services
    return StoreState(
        config=c,
        trace_id=_ring(c.capacity, jnp.int64),
        span_id=_ring(c.capacity, jnp.int64),
        parent_id=_ring(c.capacity, jnp.int64),
        name_id=_ring(c.capacity, jnp.int32),
        name_lc_id=_ring(c.capacity, jnp.int32, -1),
        service_id=_ring(c.capacity, jnp.int32, -1),
        ts_cs=_ring(c.capacity, jnp.int64, NO_TS),
        ts_cr=_ring(c.capacity, jnp.int64, NO_TS),
        ts_sr=_ring(c.capacity, jnp.int64, NO_TS),
        ts_ss=_ring(c.capacity, jnp.int64, NO_TS),
        ts_first=_ring(c.capacity, jnp.int64, NO_TS),
        ts_last=_ring(c.capacity, jnp.int64, NO_TS),
        duration=_ring(c.capacity, jnp.int64, NO_TS),
        flags=_ring(c.capacity, jnp.int32),
        indexable=_ring(c.capacity, jnp.bool_, False),
        row_gid=_ring(c.capacity, jnp.int64, -1),
        write_pos=jnp.int64(0),
        ann_gid=_ring(c.ann_capacity, jnp.int64, -1),
        ann_ts=_ring(c.ann_capacity, jnp.int64, NO_TS),
        ann_value_id=_ring(c.ann_capacity, jnp.int32, -1),
        ann_service_id=_ring(c.ann_capacity, jnp.int32, -1),
        ann_endpoint_id=_ring(c.ann_capacity, jnp.int32, -1),
        ann_write_pos=jnp.int64(0),
        bann_gid=_ring(c.bann_capacity, jnp.int64, -1),
        bann_key_id=_ring(c.bann_capacity, jnp.int32, -1),
        bann_value_id=_ring(c.bann_capacity, jnp.int32, -1),
        bann_type=_ring(c.bann_capacity, jnp.int32),
        bann_service_id=_ring(c.bann_capacity, jnp.int32, -1),
        bann_endpoint_id=_ring(c.bann_capacity, jnp.int32, -1),
        bann_write_pos=jnp.int64(0),
        # Counting state is int32: float32 scatter-adds of 1.0 silently
        # freeze at 2^24 (~16.7M), far below the 1B-span target. int32 is
        # exact to 2.1e9 and psum-able. Only the Moments bank stays f32
        # (its combine adds batch-sized increments, not +1s).
        dep_moments=jnp.zeros((S * S, M.N_FIELDS), jnp.float32),
        dep_banks=jnp.zeros((c.dep_buckets, S * S, M.N_FIELDS), jnp.float32),
        dep_bank_ts=jnp.tile(
            jnp.array([[I64_MAX, I64_MIN]], jnp.int64), (c.dep_buckets, 1)
        ),
        dep_overflow_ts=jnp.array([I64_MAX, I64_MIN], jnp.int64),
        dep_bank_seq=jnp.int64(0),
        dep_window=jnp.zeros((S * S, M.N_FIELDS), jnp.float32),
        dep_window_ts=jnp.array([I64_MAX, I64_MIN], jnp.int64),
        span_tab=_p32(jnp.full(c.tab_slots, _TAB_EMPTY, jnp.int64)),
        pend_key=jnp.zeros(c.pending_slots, jnp.int64),
        pend_dur=jnp.zeros(c.pending_slots, jnp.int64),
        pend_tsf=jnp.zeros(c.pending_slots, jnp.int64),
        pend_tsl=jnp.zeros(c.pending_slots, jnp.int64),
        pend_pos=jnp.int64(0),
        # LOAD-BEARING init values: _index_write derives
        # slot occupancy from cursors (pos + rank >= depth), which
        # over-claims when an in-batch bucket overflow (cnt > depth)
        # skipped slots this cursor lap — such "occupied" slots still
        # hold these INIT entries, and the displacement path feeds them
        # into the watermark wars and the fp-key lookup. That is
        # harmless precisely because gid/ts = -1 / I64_MIN lose every
        # max-war and verify = -1 hashes to a fingerprint that matches
        # no claimed key. Changing these fills requires re-deriving that
        # argument (or adding an explicit old-entry validity check).
        cand_idx=_arena_init(c.idx_layout[2]),
        cand_pos=jnp.zeros(c.idx_layout[1], jnp.int64),
        cand_wm=jnp.full(c.idx_layout[1], I64_MIN, jnp.int64),
        ann_poison=jnp.full(S, I64_MIN, jnp.int64),
        key_tab=jnp.full(c.key_slots, _FP_EMPTY, jnp.int32),
        key_wm=jnp.full(c.key_slots, I64_MIN, jnp.int64),
        svc_hist=Q.init(
            shape=(S,), n_buckets=c.quantile_buckets, alpha=c.quantile_alpha,
            dtype=jnp.int32,
        ).counts,
        svc_span_counts=jnp.zeros(S, jnp.int32),
        ann_svc_counts=jnp.zeros(S, jnp.int32),
        name_presence=jnp.zeros((S, c.max_span_names), jnp.int32),
        ann_value_counts=jnp.zeros((S, c.max_annotation_values), jnp.int32),
        bann_key_counts=jnp.zeros((S, c.max_binary_keys), jnp.int32),
        hll_traces=hll.init(c.hll_p).registers,
        cms_trace_spans=cms.init(c.cms_depth, c.cms_width).counts,
        ts_min=jnp.int64(I64_MAX),
        ts_max=jnp.int64(I64_MIN),
        # Windowed Moments-sketch arena: integer cells (see the field
        # comments above). min/max planes start at I32_MIN (the
        # scatter-max empty sentinel — a zero fill would pin min_x at
        # 0 because min rides max(-x)); consumers ignore them while
        # the cell's duration count is 0.
        win_epoch=_ring(c.win_slots, jnp.int64, -1),
        win_counts=jnp.zeros((S, c.win_slots, 3), jnp.int32),
        win_sums=jnp.zeros((S, c.win_slots, 4), jnp.int64),
        win_mm=jnp.full((S, c.win_slots, 2), I32_MIN, jnp.int32),
        counters={
            "spans_seen": jnp.int64(0),
            "anns_seen": jnp.int64(0),
            "banns_seen": jnp.int64(0),
            "batches": jnp.int64(0),
            # Keyed index rows whose per-key claim exhausted its probes
            # (table congestion). While 0, an absent key record PROVES
            # the key was never indexed — the negative-lookup gate.
            "key_claim_drops": jnp.int64(0),
            # Pending-sweep count: the only mutation that moves no
            # write cursor, counted so checkpoint._state_generation
            # can detect it (staged-leaf reuse safety).
            "sweeps": jnp.int64(0),
        },
    )


def _scatter_add(counts, idx, weights):
    """``counts.reshape(-1)[idx] += weights`` with idx < 0 dropped —
    the one primitive behind every ingest counter/presence/sketch
    update (the reference's 5-index-writes-per-span hot loop,
    processor/IndexService.scala:30-38)."""
    flat = counts.reshape(-1)
    m = flat.shape[0]
    safe = jnp.where(idx >= 0, idx, m)
    out = jnp.concatenate([flat, jnp.zeros(1, flat.dtype)])
    out = out.at[safe].add(weights.astype(flat.dtype))
    return out[:m].reshape(counts.shape)


def svc_histogram(state: StoreState) -> Q.LogHistogram:
    c = state.config
    gamma = (1.0 + c.quantile_alpha) / (1.0 - c.quantile_alpha)
    return Q.LogHistogram(state.svc_hist, gamma, 1.0)


@read_program(static_argnums=(0,))
def _svc_scan_catalog_impl(dims, service_id_col, duration, row_gid,
                           ann_gid, ann_service_id, ann_value_id,
                           name_id_col, name_lc_col, indexable,
                           bann_gid, bann_service_id, bann_key_id, svc):
    cap, n_names, n_q, n_av, n_bk, gamma = dims

    def hadd(n, idx, ok):
        # -1-masked rows must go through the scratch-slot remap
        # (_scatter_add): a raw ``.at[-1].add`` WRAPS to the last
        # bucket (NumPy negative indexing), silently inflating it.
        ones = jnp.ones(idx.shape, jnp.int32)
        return _scatter_add(jnp.zeros(n, jnp.int32),
                            jnp.where(ok, idx, -1), ones, False)

    # Span-ring rows of this service: duration log-histogram.
    m_sp = (row_gid >= 0) & (service_id_col == svc) & (duration >= 0)
    hist = Q.LogHistogram(jnp.zeros(n_q, jnp.int32), gamma, 1.0)
    bidx = Q.bucket_index(hist, duration.astype(jnp.float32))
    dur_row = hadd(n_q, bidx, m_sp)
    # Annotation-ring rows hosted by this service.
    m_a = (ann_gid >= 0) & (ann_service_id == svc)
    slot, live = _span_slot(ann_gid, row_gid, cap)
    nm = name_id_col[slot]
    nm_ok = (
        m_a & live & indexable[slot] & (name_lc_col[slot] >= 0)
        & (nm >= 0) & (nm < n_names)
    )
    name_row = hadd(n_names, nm, nm_ok)
    av_ok = (
        m_a & (ann_value_id >= FIRST_USER_ANNOTATION_ID)
        & (ann_value_id < n_av)
    )
    ann_row = hadd(n_av, ann_value_id, av_ok)
    # Binary-annotation-ring rows hosted by this service.
    bk_ok = (
        (bann_gid >= 0) & (bann_service_id == svc)
        & (bann_key_id >= 0) & (bann_key_id < n_bk)
    )
    bkey_row = hadd(n_bk, bann_key_id, bk_ok)
    return name_row, dur_row, ann_row, bkey_row


@read_program(static_argnums=(0, 1))
def _overflow_presence_impl(base, n_over, ann_gid, ann_service_id,
                            bann_gid, bann_service_id):
    pres = jnp.zeros(n_over, jnp.int32)
    for gid, svc in ((ann_gid, ann_service_id),
                     (bann_gid, bann_service_id)):
        ok = (gid >= 0) & (svc >= base)
        pres = _scatter_add(
            pres, jnp.where(ok, svc - base, -1),
            jnp.ones(svc.shape, jnp.int32), False,
        )
    return pres > 0


def overflow_service_presence(state: StoreState, n_over: int):
    """Which dictionary-overflow service ids (>= max_services) are
    present as annotation/binary-annotation hosts in the RINGS — the
    service-listing criterion for services no presence array can
    represent. Ring-resident (window) semantics, vs the lifetime
    ann_svc_counts of indexed services: the only data that exists for
    an overflow service lives in the raw ring columns. ``n_over`` is a
    static pad (next pow2 of the dictionary overflow count) so dict
    growth doesn't recompile per service."""
    return _overflow_presence_impl(
        state.config.max_services, n_over,
        state.ann_gid, state.ann_service_id,
        state.bann_gid, state.bann_service_id,
    )


def svc_scan_catalog(state: StoreState, svc_id: int):
    """Ring-scan catalog aggregates for ONE service id — the query path
    for dictionary-overflow services (id >= max_services), which no
    [max_services]-sized catalog array (name_presence, svc_hist,
    ann_value_counts, bann_key_counts) can represent: a clamped gather
    there would silently serve service max_services-1's data under the
    wrong name. Returns (span-name presence row, duration log-histogram
    row, annotation-value counts row, binary-key counts row), computed
    from ring-RESIDENT rows only — the indexed counterparts are
    lifetime counters, so the overflow path is window-bounded: slower
    and shorter-memoried, never wrong-service. All four aggregates ride
    one launch (i32 1-D scatter-adds, the vectorized class on this
    backend). Reference role: the per-service catalogs of
    CassieSpanStore.scala (ServiceNames/SpanNames column families)."""
    c = state.config
    gamma = (1.0 + c.quantile_alpha) / (1.0 - c.quantile_alpha)
    return _svc_scan_catalog_impl(
        (c.capacity, c.max_span_names, c.quantile_buckets,
         c.max_annotation_values, c.max_binary_keys, gamma),
        state.service_id, state.duration, state.row_gid,
        state.ann_gid, state.ann_service_id, state.ann_value_id,
        state.name_id, state.name_lc_id, state.indexable,
        state.bann_gid, state.bann_service_id, state.bann_key_id,
        jnp.int32(svc_id),
    )


# ---------------------------------------------------------------------------
# Device batch (padded, fixed shape)
# ---------------------------------------------------------------------------


class DeviceBatch(NamedTuple):
    """A SpanBatch padded to static shape + host-computed index columns."""

    trace_id: jnp.ndarray
    span_id: jnp.ndarray
    parent_id: jnp.ndarray
    name_id: jnp.ndarray
    name_lc_id: jnp.ndarray
    service_id: jnp.ndarray
    ts_cs: jnp.ndarray
    ts_cr: jnp.ndarray
    ts_sr: jnp.ndarray
    ts_ss: jnp.ndarray
    ts_first: jnp.ndarray
    ts_last: jnp.ndarray
    duration: jnp.ndarray
    flags: jnp.ndarray
    has_parent: jnp.ndarray
    indexable: jnp.ndarray
    n_spans: jnp.ndarray

    ann_span_idx: jnp.ndarray
    ann_ts: jnp.ndarray
    ann_value_id: jnp.ndarray
    ann_service_id: jnp.ndarray
    ann_endpoint_id: jnp.ndarray
    n_anns: jnp.ndarray

    bann_span_idx: jnp.ndarray
    bann_key_id: jnp.ndarray
    bann_value_id: jnp.ndarray
    bann_type: jnp.ndarray
    bann_service_id: jnp.ndarray
    bann_endpoint_id: jnp.ndarray
    n_banns: jnp.ndarray

    # Per-span error flag ("error" annotation value / binary key),
    # computed on the HOST in stage 1 (aggregate.windows
    # span_error_flags — the dictionary lookup the device can't do) and
    # consumed by the windowed-arena error counts. Defaults to all
    # False for direct-device callers that don't track errors.
    error_flag: jnp.ndarray

    # Paged layout (r19) stage-1 page claims, planned on the HOST by
    # store/paged.PagePlanner (deterministic from the unit stream, so
    # WAL replay re-derives bitwise-identical claims). Ring batches
    # carry shape-(1,) placeholders; the ring lowering never touches
    # them (static branch → DCE, same discipline as error_flag before
    # the window arena existed).
    span_slot: jnp.ndarray      # i32 [P]  destination slot per span
    span_gid: jnp.ndarray       # i64 [P]  epoch-encoded gid per span
    reclaim_page: jnp.ndarray   # i32 [RC] page ids invalidated first (-1 pad)


def _pad(a: np.ndarray, n: int, fill=0, dtype=None) -> np.ndarray:
    dtype = dtype or a.dtype
    out = np.full(n, fill, dtype)
    out[: len(a)] = a
    return out


def make_device_batch(
    batch: SpanBatch,
    name_lc_id: np.ndarray,
    indexable: np.ndarray,
    pad_spans: int,
    pad_anns: int,
    pad_banns: int,
    error_flag: np.ndarray = None,
    span_slot: np.ndarray = None,
    span_gid: np.ndarray = None,
    reclaim_pages: np.ndarray = None,
    pad_reclaims: int = 1,
) -> DeviceBatch:
    """Host: pad a SpanBatch (+ index columns) to static shapes.

    ``name_lc_id`` is the lowercased span-name dictionary id (-1 for empty
    names); ``indexable`` is store.base.should_index computed per span;
    ``error_flag`` is the per-span error bit (windows.span_error_flags),
    all-False when the caller doesn't track errors.
    """
    from zipkin_tpu.columnar.schema import FLAG_HAS_PARENT

    if batch.n_spans > pad_spans or batch.n_annotations > pad_anns:
        raise ValueError("batch larger than device batch padding")
    if batch.n_binary > pad_banns:
        raise ValueError("batch larger than device batch padding")
    f = batch.flags.astype(np.int32)
    return DeviceBatch(
        trace_id=_pad(batch.trace_id, pad_spans),
        span_id=_pad(batch.span_id, pad_spans),
        parent_id=_pad(batch.parent_id, pad_spans),
        name_id=_pad(batch.name_id, pad_spans),
        name_lc_id=_pad(np.asarray(name_lc_id, np.int32), pad_spans, -1),
        service_id=_pad(batch.service_id, pad_spans, -1),
        ts_cs=_pad(batch.ts_cs, pad_spans, NO_TS),
        ts_cr=_pad(batch.ts_cr, pad_spans, NO_TS),
        ts_sr=_pad(batch.ts_sr, pad_spans, NO_TS),
        ts_ss=_pad(batch.ts_ss, pad_spans, NO_TS),
        ts_first=_pad(batch.ts_first, pad_spans, NO_TS),
        ts_last=_pad(batch.ts_last, pad_spans, NO_TS),
        duration=_pad(batch.duration, pad_spans, NO_TS),
        flags=_pad(f, pad_spans),
        has_parent=_pad(
            (f & int(FLAG_HAS_PARENT)).astype(bool), pad_spans, False
        ),
        indexable=_pad(np.asarray(indexable, bool), pad_spans, False),
        n_spans=np.int32(batch.n_spans),
        ann_span_idx=_pad(batch.ann_span_idx, pad_anns),
        ann_ts=_pad(batch.ann_ts, pad_anns, NO_TS),
        ann_value_id=_pad(batch.ann_value_id, pad_anns, -1),
        ann_service_id=_pad(batch.ann_service_id, pad_anns, -1),
        ann_endpoint_id=_pad(batch.ann_endpoint_id, pad_anns, -1),
        n_anns=np.int32(batch.n_annotations),
        bann_span_idx=_pad(batch.bann_span_idx, pad_banns),
        bann_key_id=_pad(batch.bann_key_id, pad_banns, -1),
        bann_value_id=_pad(batch.bann_value_id, pad_banns, -1),
        bann_type=_pad(batch.bann_type.astype(np.int32), pad_banns),
        bann_service_id=_pad(batch.bann_service_id, pad_banns, -1),
        bann_endpoint_id=_pad(batch.bann_endpoint_id, pad_banns, -1),
        n_banns=np.int32(batch.n_binary),
        error_flag=_pad(
            np.zeros(batch.n_spans, bool) if error_flag is None
            else np.asarray(error_flag, bool),
            pad_spans, False,
        ),
        # Ring batches keep shape-(1,) placeholders so every ring unit
        # shares one jit cache entry; paged batches pad the planner's
        # claims to the unit's static shapes.
        span_slot=(
            np.zeros(1, np.int32) if span_slot is None
            else _pad(np.asarray(span_slot, np.int32), pad_spans)
        ),
        span_gid=(
            np.zeros(1, np.int64) if span_gid is None
            else _pad(np.asarray(span_gid, np.int64), pad_spans, -1)
        ),
        reclaim_page=(
            np.full(1, -1, np.int32) if reclaim_pages is None
            else _pad(
                np.asarray(reclaim_pages, np.int32), pad_reclaims, -1
            )
        ),
    )


# ---------------------------------------------------------------------------
# Dependency-link kernel (shared by ingest_step and offline recompute)
# ---------------------------------------------------------------------------


def dep_link_moments(
    trace_id, span_id, parent_id, service_id, duration,
    build_valid, probe_valid, n_services: int,
):
    """[S*S, 5] Moments of child durations per (parent_svc, child_svc).

    The device-native ZipkinAggregateJob.scala:26-38: a sort-merge join
    of (trace_id, parent_id) against (trace_id, span_id) followed by a
    segmented moments reduction — no shuffles, one launch.
    """
    S = n_services
    found, parent_svc = join.lookup(
        (trace_id, span_id), build_valid, service_id,
        (trace_id, parent_id), probe_valid,
    )
    link_ok = (
        found
        & (parent_svc >= 0) & (service_id >= 0)
        & (parent_svc < S) & (service_id < S)
        & (duration >= 0)
    )
    link_id = jnp.where(link_ok, parent_svc.astype(jnp.int32) * S + service_id, 0)
    return M.segment_moments(
        duration.astype(jnp.float32), link_id, S * S, valid=link_ok
    )


@jax.jit
def recompute_dep_moments(state: "StoreState"):
    """Offline recompute over the live span ring (the rerunnable-batch-job
    analogue; parity check for the streaming archive+live path)."""
    from zipkin_tpu.columnar.schema import FLAG_HAS_PARENT

    live = state.row_gid >= 0
    has_parent = (state.flags & jnp.int32(int(FLAG_HAS_PARENT))) != 0
    return dep_link_moments(
        state.trace_id, state.span_id, state.parent_id, state.service_id,
        state.duration, live, live & has_parent, state.config.max_services,
    )


# -- streaming hash join ----------------------------------------------------
#
# The span hash table + pending ring resolve parent/child links at
# ingest time. Per-op cost on this class of device grows with operand
# ROWS (measured ~25-100ms per HLO op at 8M rows, round 3), so the
# r2 design — an O(ring) sort-join per archive pass and per
# get_dependencies — paid seconds per call; probing a hash table costs
# a handful of ops on BATCH-sized arrays instead.

_TAB_PROBES = 4
_SVC_MASK = 0x7FFF  # 15-bit service payload (svc + 1; 0 = missing)


def _mix48(a, b):
    """48-bit mixed key of two i64 columns (uint64 result < 2^48)."""
    from zipkin_tpu.ops.hashing import mix_keys64

    return mix_keys64([a, b]) >> jnp.uint64(16)


# Empty span-table sentinel: I64_MAX, so a plain scatter-MIN both fills
# empty slots and arbitrates every in-batch race deterministically (see
# _tab_insert). A packed word can never equal it: svc is clipped below
# the full 15-bit mask, so the low 16 bits are never all-ones.
_TAB_EMPTY = (1 << 63) - 1


def _tab_pack(key48, svc):
    """(key48, service) → occupied table word (never _TAB_EMPTY)."""
    s = (jnp.clip(svc, -1, _SVC_MASK - 2) + 1).astype(jnp.uint64)
    return ((key48 << jnp.uint64(16)) | (s << jnp.uint64(1))
            | jnp.uint64(1)).astype(jnp.int64)


def _tab_slots(key48, n_slots: int):
    """The probe sequence: double hashing over a power-of-two table."""
    h0 = key48 & jnp.uint64(n_slots - 1)
    step = ((key48 >> jnp.uint64(20)) << jnp.uint64(1)) | jnp.uint64(1)
    return [
        ((h0 + jnp.uint64(j) * step) & jnp.uint64(n_slots - 1)).astype(
            jnp.int32
        )
        for j in range(_TAB_PROBES)
    ]


def _tab_lookup(tab, key48):
    """(found, svc) per probe key — svc is -1 when absent/serviceless.
    ``tab`` is the [H, 2] i32 plane-pair table (StoreState.span_tab):
    each probe load is an 8-byte i32 row gather, bitcast locally back
    to the logical packed word."""
    found = jnp.zeros(key48.shape, bool)
    svc = jnp.full(key48.shape, -1, jnp.int32)
    for slot in _tab_slots(key48, tab.shape[0]):
        cur = _p64(tab[slot]).astype(jnp.uint64)
        hit = (cur != jnp.uint64(_TAB_EMPTY)) & (
            (cur >> jnp.uint64(16)) == key48)
        first = hit & ~found
        svc = jnp.where(
            first,
            ((cur >> jnp.uint64(1)) & jnp.uint64(_SVC_MASK)).astype(
                jnp.int32
            ) - 1,
            svc,
        )
        found |= hit
    return found, svc


def _tab_insert(tab, key48, svc, valid):
    """Insert (key48 → svc) rows. Each probe round is ONE scatter-MIN:
    the empty sentinel (_TAB_EMPTY = I64_MAX) loses to every packed
    word, and rows racing for one slot resolve to the numerically
    smallest word — so the client and server halves of an RPC, which
    share (trace_id, span_id), deterministically keep the LOWEST
    service id regardless of arrival order, in-batch or across batches.
    (The reference merges the halves before joining and picks one
    serviceName, ZipkinAggregateJob.scala mergeSpan; min-service-id is
    this store's deterministic analogue — divergence noted in
    COVERAGE.md row 3.) A different-key loser fails the read-back
    verify and retries its next probe; a key is only ever lost when all
    probes land on slots occupied by foreign keys — then the last slot
    is stolen (random-replacement eviction; the table outlives ring
    retention, bounded like the reference's index TTL,
    CassieSpanStore.scala:48)."""
    oob = tab.shape[0]
    packed = _tab_pack(key48, svc)
    placed = ~jnp.asarray(valid, bool)
    slots = _tab_slots(key48, tab.shape[0])
    # Each round's min-war is arbitrated EXPLICITLY (_slot_war sorts the
    # contenders) instead of by an i64 scatter-min + re-read — bitwise
    # the same winner (numerically smallest packed word), but built
    # from sorts and one unique plane scatter. The table itself lives
    # in i32 plane form (StoreState.span_tab): probe loads are i32 row
    # gathers, writes i32 plane scatters — i64 gathers/scatters are the
    # serialized class on this backend (PERF.md 6, "r4/r5 records").
    for slot in slots:
        cur = _p64(tab[slot])
        curu = cur.astype(jnp.uint64)
        open_ = (curu == jnp.uint64(_TAB_EMPTY)) | (
            (curu >> jnp.uint64(16)) == key48
        )
        attempt = ~placed & open_
        seg_min, write_row = _slot_war(slot, packed, attempt, oob)
        after = jnp.minimum(cur, seg_min)  # inactive rows: seg_min=MAX
        tab = _uset_p(tab, slot, after, write_row)
        placed |= attempt & (
            (after.astype(jnp.uint64) >> jnp.uint64(16)) == key48)
    # Last-resort steal: the old state is discarded, so the winner is
    # simply the smallest packed word among same-slot stealers.
    seg_min, write_row = _slot_war(slots[-1], packed, ~placed, oob)
    return _uset_p(tab, slots[-1], seg_min, write_row)


# -- index column families ---------------------------------------------------
#
# Each family is a flat [B*K, 2] i64 array of (span gid, verify) entries
# in per-bucket FIFO rings plus a [B] i32 cursor — the device rendition
# of the reference's index column families (ServiceNameIndex /
# ServiceSpanNameIndex / AnnotationsIndex, CassieSpanStore.scala:168-251,
# cassandra-schema.txt). Written by batch-sized scatters inside
# ingest_step; read by O(depth) bucket slices. Entry liveness is checked
# against the span ring at query time (gid round-trip), so eviction
# needs no index maintenance.


def _fifo_ranks(bucket, valid, n_buckets: int):
    """Arrival-order rank of each row within its bucket. One stable
    single-key sort (bucket in the high bits, row index in the low bits)
    + a cummax segment-start fill — deterministic, so two ingests of the
    same batch produce bitwise-identical index state.

    The shift is derived from the (static) row count, so an
    annotation-heavy launch past 2^21 concatenated rows widens the key
    instead of tripping an assert; the static bucket-count bound keeps
    the sentinel (one past every real bucket id, 2^62 after shifting)
    from wrapping sign."""
    n = bucket.shape[0]
    shift = max((n - 1).bit_length(), 1)
    assert n_buckets < (1 << (62 - shift)), (
        f"rank key space exhausted: {n} rows x {n_buckets} buckets")
    key = jnp.where(valid, bucket.astype(jnp.int64),
                    jnp.int64(1) << (62 - shift))
    skey = (key << shift) | jnp.arange(n, dtype=jnp.int64)
    order = jnp.argsort(skey)
    sk = key[order]
    first = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
    idxs = jnp.arange(n, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(first, idxs, jnp.int32(-1)))
    rank = jnp.zeros(n, jnp.int32).at[order].set(idxs - start,
                                                 unique_indices=True)
    return rank


# -- segmented counting-sort ranks (r12) -------------------------------------
#
# The r12 alternative to _fifo_ranks' stable argsort: the within-bucket
# arrival rank decomposes as (same-bucket rows in EARLIER row blocks) +
# (same-bucket earlier rows in MY block). The first term is a counting
# sort — the per-(bucket, block) occupancy histogram is ONE i32
# duplicate-index scatter-add (the same vectorized class as the bucket
# count the write pass already pays) turned into prefixes by a cumsum
# along the block axis, read back by ONE gather; the second term is
# block-1 shifted elementwise equality tests. Net census vs the argsort
# path: -1 stablehlo.sort, ±0 scatters, ±0 gathers (the argsort path
# spends 1 scatter + 1 gather on its unsort), and the O(N log N)
# comparator sort disappears from the compile.
#
# The scratch is the dense [(n_buckets+1) x ceil(N/block)] histogram —
# it scales with buckets x rows, so huge-arena geometries (the 2^22
# bench rings, whose trace families alone carry ~800k buckets) blow any
# block size past the budget and statically keep the argsort path;
# rank_block_for is the feasibility oracle and docs/PERFORMANCE.md
# carries the arithmetic. Both paths are BITWISE-identical for every
# row (including the ~valid sentinel-bucket rows), fuzz-gated by
# tests/test_rank_paths.py.

# Block sizes tried smallest-first (each must be a power of two: block
# membership tests mask with block-1). Bigger blocks shrink the scratch
# but pay (block-1) shifted compares; past 64 the elementwise tail
# would dominate the sort it replaces.
_RANK_BLOCKS = (8, 16, 32, 64)
# Scratch budget in i32 elements (128 MiB transient): generous for
# smoke/test geometries and wide enough that MID-size bench rings
# (cap 2^16 at ~57k-row launches, block 64) still engage counting so
# the on-chip matrix arms can measure the sort-vs-counting delta; the
# 2^22 cert geometry (~800k buckets x ~2M rows) is out of reach for
# ANY block size — docs/PERFORMANCE.md carries the arithmetic — and
# statically keeps argsort.
_RANK_SCRATCH_ELEMS = 1 << 25


def rank_block_for(n_rows: int, n_buckets: int) -> int:
    """Smallest feasible counting-rank block size for a launch shape
    (0 = no block fits the scratch budget; take the argsort path)."""
    for blk in _RANK_BLOCKS:
        groups = -(-n_rows // blk)
        if (n_buckets + 1) * groups <= _RANK_SCRATCH_ELEMS:
            return blk
    return 0


def rank_mode(rank_path: str, n_rows: int, n_buckets: int,
              wm_shift: int):
    """Static rank-path decision for one launch shape: ("argsort", 0)
    or ("counting", block). The wm_shift == 0 small-store regime stays
    on argsort even when counting is requested — tiny rings mean tiny
    batches, where the counting pass's fixed overhead (scratch zeroing
    + shifted compares) buys nothing, and keeping one static policy per
    regime keeps the compile-cache story simple (mirrors the exact
    gid-war fallback in _index_write).

    "auto" is BACKEND-aware: the counting sort exists to delete a TPU
    sort bottleneck; on the CPU backend XLA's sort is fast and the
    counting scratch traffic measurably LOSES (~+11% on device-heavy
    tier-1 modules, r12 measurement), so auto picks counting only on
    TPU. An explicit "counting" is honored on every backend — that is
    what the CI equivalence/census gates pin the path with. The choice
    is always bitwise-neutral, so a checkpoint moving between backends
    never diverges."""
    if rank_path not in ("auto", "argsort", "counting"):
        raise ValueError(f"unknown rank_path {rank_path!r}")
    if rank_path == "argsort" or wm_shift == 0:
        return "argsort", 0
    if rank_path == "auto" and jax.default_backend() != "tpu":
        return "argsort", 0
    blk = rank_block_for(n_rows, n_buckets)
    if blk == 0:
        # Scratch infeasible at this geometry: "counting" degrades to
        # argsort rather than OOMing the device (recorded in the
        # active-paths registry so counters()/bench say what ran).
        return "argsort", 0
    return "counting", blk


def _fifo_ranks_counting(bucket, valid, n_buckets: int, block: int):
    """Counting-sort twin of _fifo_ranks: bitwise-identical rank vector
    (valid rows rank among same-bucket valid rows, ~valid rows among
    themselves via the sentinel bucket — exactly the argsort path's
    sentinel-key semantics), built from one duplicate-index i32
    scatter-add, one cumsum, one gather, and block-1 shifted compares.
    ``block`` must be a power of two (see _RANK_BLOCKS); valid rows
    must carry bucket in [0, n_buckets) — the same contract the argsort
    path's callers already honor (_index_write's seg() clips)."""
    n = bucket.shape[0]
    groups = -(-n // block)
    b_eff = jnp.where(
        valid, jnp.clip(bucket, 0, n_buckets - 1).astype(jnp.int32),
        jnp.int32(n_buckets),
    )
    rows = jnp.arange(n, dtype=jnp.int32)
    g = rows // jnp.int32(block)
    sidx = b_eff * jnp.int32(groups) + g
    # Per-(bucket, block) occupancy — duplicate-index i32 scatter-add,
    # the vectorized class (PERF.md 6, "r4/r5 records"); indices are
    # in-range by construction, mode="drop" is belt-and-braces.
    cnt = jnp.zeros((n_buckets + 1) * groups, jnp.int32).at[sidx].add(
        1, mode="drop")
    cnt2 = cnt.reshape(n_buckets + 1, groups)
    # Exclusive prefix along the block axis: same-bucket rows in
    # earlier blocks.
    prefix = (jnp.cumsum(cnt2, axis=1) - cnt2).reshape(-1)
    pre = prefix[sidx]
    # Same-bucket earlier rows within my block: block-1 shifted
    # equality tests, masked to block membership (blocks are aligned —
    # row i and i-d share a block iff i % block >= d).
    in_block = rows & jnp.int32(block - 1)
    w = jnp.zeros(n, jnp.int32)
    for d in range(1, min(block, n)):
        same = jnp.concatenate(
            [jnp.zeros(d, bool), b_eff[d:] == b_eff[:-d]])
        w = w + (same & (in_block >= d)).astype(jnp.int32)
    return pre + w


# Active-path registry: which rank and ring-write implementations each
# StoreConfig's compiled steps actually took (trace-time records — one
# entry per compile, so steady state writes nothing). Surfaced through
# TpuSpanStore.counters() -> /metrics and the bench JSON, so every
# recorded spans/s figure says which kernels produced it. The lock
# guards reads against a concurrent first-compile on another thread (a
# /metrics scrape during a pipelined store's new-shape trace must not
# see a set mid-mutation). Entries live as long as the process, keyed
# by config — the SAME lifecycle and sharing as the jit caches whose
# path choices they record: a new store reusing a config also reuses
# those compiled steps, so the inherited record is accurate for it.
_ACTIVE_PATHS: Dict[StoreConfig, Dict[str, set]] = {}
_ACTIVE_PATHS_LOCK = threading.Lock()  # lock-order: 85 trace-registry


def _note_path(config: StoreConfig, kind: str, value: str) -> None:
    with _ACTIVE_PATHS_LOCK:
        _ACTIVE_PATHS.setdefault(config, {}).setdefault(
            kind, set()).add(value)


def active_paths(config: StoreConfig) -> Dict[str, Tuple[str, ...]]:
    """{"rank": ("counting", ...),
    "ring_write": ("ann:window", "bann:window", "pend:window",
    "span:window")} — every implementation this config's compiled
    ingest steps used (may hold both when different launch shapes
    picked different modes). ``ring_write`` names, ring by ring, the
    form its write took: ``window`` (consecutive slots as slice
    updates, ``_ring_write``) or ``scatter`` (``_uset``: the paged
    layout's span ring, or a pad past a tiny ring)."""
    with _ACTIVE_PATHS_LOCK:
        return {
            k: tuple(sorted(v))
            for k, v in _ACTIVE_PATHS.get(config, {}).items()
        }


def _index_write(entries, pos, wm, key_tab, key_wm, ann_poison,
                 gbucket, slot0, depth, gid, verify, ts, valid,
                 keyed_from: int, n_cand_rows: int, n_cand_buckets: int,
                 poison_bucket=None, poison_gid=None, poison_ok=None,
                 wm_shift: int = 0, ts_shift: int = _WM_TS_SHIFT,
                 rank_sel=("argsort", 0)):
    """ONE combined append of (gid, verify, ts) rows into the UNIFIED
    index arena — candidate families and trace-membership families
    alike: ``gbucket`` is the global bucket id (addressing pos/wm),
    ``slot0`` the bucket's first entry row, and ``depth`` its FIFO
    depth — all per-row vectors, constant per concatenated family
    segment, so every family rides the same rank sort, count scatter,
    displaced-row gather, entry scatter, and cursor update (per-kernel
    overhead dominates on this backend; the r5 split
    cand/trace write blocks cost two of everything).

    Row sections (static slices of the concatenation):
    - ``[0:n_cand_rows)``  candidate-family rows. Their buckets' ``wm``
      is the overwrite TS watermark: the max ts ever displaced (by
      wraparound, or by in-batch overflow where one launch writes more
      than ``depth`` rows to a bucket and keeps the newest). Queries on
      a wrapped bucket are exact iff their last returned candidate
      still ranks >= the watermark. The war runs COARSE — one shared
      vectorized i32 duplicate-index scatter-max in 2^ts_shift-µs ceil
      units (see _WM_TS_SHIFT) — with an EXACT plane-war fallback,
      entered under lax.cond only when some contribution lies past the
      coarse domain (costs nothing on real traffic).
    - ``[n_cand_rows:)``  trace-membership rows. Their buckets' ``wm``
      is the max DISPLACED GID (ring overwrite order is oldest-first,
      so wm < write_pos - capacity proves the bucket holds every
      resident row of its traces). The war rides the SAME shared
      scatter in 2^wm_shift-gid units (except wm_shift == 0: exact —
      see _war_max_gid_coarse's small-store rationale).

    ``key_tab``/``key_wm`` is the per-key cursor table (see
    StoreState.key_tab); rows in ``[keyed_from:n_cand_rows)`` (the
    keyed families are a contiguous MIDDLE slice — the service family,
    whose bucket IS the key, leads, and the trace families trail) claim
    a record for their verify word, and every displaced or
    in-batch-dropped keyed entry maxes its span gid into its key's
    displaced watermark — through the same shared scatter. So do the
    middle-host ``ann_poison`` contributions (``poison_*``, per
    annotation row). Also returns the number of keyed rows whose claim
    found no slot (table congestion): while that count is ZERO over the
    store's lifetime, an ABSENT record proves its key was never indexed
    — the negative-lookup gate (see iquery wrappers)."""
    n_b = pos.shape[0]
    rank_kind, rank_blk = rank_sel
    if rank_kind == "counting":
        rank = _fifo_ranks_counting(gbucket, valid, n_b, rank_blk)
    else:
        rank = _fifo_ranks(gbucket, valid, n_b)
    b_c = jnp.clip(gbucket, 0, n_b - 1)
    oob_b = jnp.where(valid, b_c, n_b)
    cnt = jnp.zeros(n_b + 1, jnp.int32).at[oob_b].add(
        1, mode="drop")[:n_b]
    keep = valid & (rank >= cnt[b_c] - depth)
    # Cursor math runs in the i32 low plane: depths are powers of two
    # (StoreConfig._derived), so (pos + rank) % depth only needs the low
    # 32 bits, and the occupancy test only needs pos itself, which stays
    # far below 2^31 per bucket (total entries ever / n_buckets).
    pos_lo = _p32(pos)[:, 0]
    pos_b = pos_lo[b_c]
    slot = slot0.astype(jnp.int32) + ((pos_b + rank) % depth)
    # A kept write DISPLACES a previous entry iff its bucket has already
    # wrapped past this slot — pos + rank >= depth. NOT identical to the
    # old per-slot occupancy gather (gid >= 0): when an earlier batch
    # overflowed a bucket (cnt > depth), its dropped rows never wrote
    # their slots, so a cursor-"occupied" slot may still hold the INIT
    # entry — whose values are chosen to be inert here (they lose every
    # watermark war and match no key fingerprint; see init_state).
    occupied = keep & (pos_b + rank >= depth)
    gidx = jnp.where(keep, slot, 0)
    # The displaced entries, for ALL families, as word gathers of the
    # planes each section needs (lo and hi combined for the gathered
    # rows only): ts for the candidate prefix, gid from the keyed
    # slice on (keyed + trace rows are contiguous), verify for the
    # keyed slice.
    cand = slice(0, n_cand_rows)
    trc = slice(n_cand_rows, None)
    sfx = slice(keyed_from, n_cand_rows)
    old_ts_c = jnp.where(
        occupied[cand], _arena_col(entries, gidx[cand], 2), I64_MIN)
    old_gid = _arena_col(entries, gidx[keyed_from:], 0)
    # Old entry identity is consumed by the keyed-slice machinery below.
    old_gid_s = old_gid[:n_cand_rows - keyed_from]
    old_verify_s = _arena_col(entries, gidx[sfx], 1)
    dropped_ts = jnp.where(
        valid[cand] & ~keep[cand],
        jnp.asarray(ts, jnp.int64)[cand], I64_MIN,
    )
    disp_ts = jnp.maximum(old_ts_c, dropped_ts)
    # Trace rows: the watermark needs the TRUE displaced gid (from the
    # shared old-row gather) — under continuous displacement the
    # displaced entry is ~2 window-laps old and already ring-evicted,
    # which is exactly what keeps the gate passing in steady state;
    # substituting the current row's (always-recent) gid would hold
    # every busy bucket's gate closed forever. In-batch dropped rows
    # carry their own gid.
    gid = jnp.asarray(gid, jnp.int64)
    tr_wmv = jnp.where(
        occupied[trc], old_gid[n_cand_rows - keyed_from:], gid[trc])
    tr_ok = occupied[trc] | (valid[trc] & ~keep[trc])
    verify = jnp.asarray(verify, jnp.int64)
    vals = jnp.stack([gid, verify, jnp.asarray(ts, jnp.int64)], axis=-1)
    entries = _arena_set(entries, slot, vals, keep)
    pos = pos + cnt.astype(pos.dtype)

    # -- per-key fingerprint records (suffix rows only) ----------------
    # 1. Claim records for this batch's keys: empty slots only, i32
    #    fingerprint min-war arbitration (duplicate-index i32 scatters
    #    vectorize; the old exact-word i64 war serialized at ~100 ns/row
    #    and dominated the whole ingest step). Records are NEVER stolen
    #    and never seeded on occupied-by-foreign probes. Two distinct
    #    keys may share (slot, fingerprint) — then they SHARE a record
    #    and their displaced watermarks merge, which can only overstate
    #    a watermark: extra fallbacks, never a wrong answer. The
    #    negative-lookup gate stays sound: an indexed key either placed
    #    a record its probes will find (fp match) or counted a drop.
    #
    #    All three probe slots are read in ONE stacked gather and the
    #    claim goes to the first EMPTY probe; rows that lose the
    #    in-batch min-war at their chosen slot retry (next empty probe
    #    under the updated table) in a lax.cond round that costs
    #    nothing once the key population is resident — the round-4
    #    3-sequential-probe loop paid 3 gather+scatter+gather rounds
    #    on EVERY step forever. Probe-exhaustion semantics (and the
    #    drop count) are identical: initial + 2 retries = 3 attempts.
    T = key_tab.shape[0]
    v_s = valid[sfx]
    verify_s = verify[sfx]
    k48n = verify_s.astype(jnp.uint64) >> jnp.uint64(16)
    fp = _fp31(k48n)
    slots3 = jnp.stack(_tab_slots(k48n, T)[:_KEY_PROBES])  # [3, M]

    def claim_round(key_tab, placed):
        cur = key_tab[slots3]                 # one gather, 3M rows
        already = (cur == fp[None, :]).any(0)
        empty = cur == _FP_EMPTY
        choose = jnp.full(fp.shape, T, jnp.int32)
        for i in range(_KEY_PROBES - 1, -1, -1):
            choose = jnp.where(empty[i], slots3[i], choose)
        attempt = v_s & ~placed & ~already & (choose < T)
        key_tab = key_tab.at[jnp.where(attempt, choose, T)].min(
            jnp.where(attempt, fp, _FP_EMPTY), mode="drop"
        )
        after = key_tab[jnp.where(attempt, choose, 0)]
        placed = placed | already | (attempt & (after == fp))
        # Lost the same-batch min-war at a still-open table: retryable.
        unresolved = attempt & ~placed
        return key_tab, placed, unresolved

    placed = jnp.zeros(fp.shape, bool)
    key_tab, placed, unresolved = claim_round(key_tab, placed)
    for _ in range(_KEY_PROBES - 1):
        key_tab, placed, unresolved = jax.lax.cond(
            unresolved.any(),
            claim_round,
            lambda kt, pl: (kt, pl, jnp.zeros_like(pl)),
            key_tab, placed,
        )
    # 2. Record displacements: bucket-wrap victims carry their OLD
    #    entry's (verify, gid); in-batch overflow drops carry their own.
    #    The displaced gid must be the TRUE old gid (not the current
    #    row's): a busy key's displaced entries are ~2 window-laps old
    #    and already evicted, which is exactly what keeps its record's
    #    eviction gate passing in steady state.
    keep_s = keep[sfx]
    disp_ok = (keep_s & occupied[sfx]) | (v_s & ~keep_s)
    disp_key = jnp.where(keep_s, old_verify_s, verify_s)
    disp_gid = jnp.where(keep_s, old_gid_s, gid[sfx])
    k48d = disp_key.astype(jnp.uint64) >> jnp.uint64(16)
    fpd = _fp31(k48d)
    dslots3 = jnp.stack(_tab_slots(k48d, T)[:_KEY_PROBES])
    dhit = key_tab[dslots3] == fpd[None, :]   # one gather, 3M rows
    dslot = jnp.full(k48d.shape, T, jnp.int32)
    for i in range(_KEY_PROBES - 1, -1, -1):
        dslot = jnp.where(dhit[i], dslots3[i], dslot)
    key_hit = disp_ok & dhit.any(0)

    # -- the SHARED watermark war --------------------------------------
    # Every watermark family — candidate ts watermarks, trace-family
    # displaced-gid watermarks, per-key displaced-gid watermarks, and
    # the middle-host ann_poison stamps — folds through ONE vectorized
    # i32 duplicate-index scatter-max over a partitioned scratch, each
    # contribution pre-encoded in its own family's coarse unit (the
    # buckets are disjoint, so mixed units can share a scatter). The r5
    # step paid one war per family (the "+ bucket wm war off" 73 ms
    # ablation slice plus three coarse gid scatters); this is one.
    valid_c = valid[cand]
    S_p = ann_poison.shape[0]
    n_scr = n_b + T + S_p + 1
    val_c, over_c = _coarse_ts32(disp_ts, valid_c, ts_shift)
    idx_c = jnp.where(valid_c, b_c[cand], n_scr - 1)
    parts_idx = [idx_c]
    parts_val = [val_c]
    exact_gid_wars = wm_shift == 0  # small-store satellite: no cliff
    if not exact_gid_wars:
        parts_idx.append(jnp.where(tr_ok, b_c[trc], n_scr - 1))
        parts_val.append(_coarse_gid32(tr_wmv, tr_ok, wm_shift))
        parts_idx.append(jnp.where(key_hit, n_b + dslot, n_scr - 1))
        parts_val.append(_coarse_gid32(disp_gid, key_hit, wm_shift))
        if poison_bucket is not None:
            parts_idx.append(jnp.where(
                poison_ok,
                n_b + T + jnp.clip(poison_bucket, 0, S_p - 1),
                n_scr - 1,
            ))
            parts_val.append(
                _coarse_gid32(poison_gid, poison_ok, wm_shift))
    scr = jnp.zeros(n_scr, jnp.int32).at[
        jnp.concatenate(parts_idx)
    ].max(jnp.concatenate(parts_val), mode="drop")
    # Fold back per segment: only slots the war actually raised touch
    # their exact i64 state (empty I64_MIN sentinels survive bit-exact).
    scr_b = scr[:n_b]
    ts_upd = jnp.where(scr_b > 0, scr_b.astype(jnp.int64) << ts_shift,
                       I64_MIN)
    if exact_gid_wars:
        wm = jnp.maximum(
            wm,
            jnp.where(jnp.arange(n_b) < n_cand_buckets, ts_upd, I64_MIN),
        )
        wm = _war_max64(wm, b_c[trc], tr_wmv, tr_ok)
        key_wm = _war_max64(key_wm, dslot, disp_gid, key_hit)
        if poison_bucket is not None:
            ann_poison = _war_max64(
                ann_poison, jnp.clip(poison_bucket, 0, S_p - 1),
                jnp.asarray(poison_gid, jnp.int64), poison_ok,
            )
    else:
        gid_upd = jnp.where(
            scr_b > 0, scr_b.astype(jnp.int64) << wm_shift, I64_MIN)
        wm = jnp.maximum(
            wm,
            jnp.where(jnp.arange(n_b) < n_cand_buckets, ts_upd, gid_upd),
        )
        scr_k = scr[n_b:n_b + T]
        key_wm = jnp.maximum(key_wm, jnp.where(
            scr_k > 0, scr_k.astype(jnp.int64) << wm_shift, I64_MIN))
        if poison_bucket is not None:
            scr_p = scr[n_b + T:n_b + T + S_p]
            ann_poison = jnp.maximum(ann_poison, jnp.where(
                scr_p > 0, scr_p.astype(jnp.int64) << wm_shift,
                I64_MIN))
    # Exact overflow fallback for the ts war: contributions past the
    # coarse ceiling run the exact plane war instead of saturating (a
    # saturated ts watermark would close its bucket forever). lax.cond
    # executes one branch at runtime, so real traffic (no overflow)
    # pays a scalar reduction, not the war.
    wm = jax.lax.cond(
        over_c.any(),
        lambda w: _war_max64(w, b_c[cand], disp_ts, over_c),
        lambda w: w,
        wm,
    )
    n_drops = (v_s & ~placed).sum().astype(jnp.int64)
    return entries, pos, wm, key_tab, key_wm, ann_poison, n_drops


def _span_host_range(ann_svc, ann_span_idx, valid_a, n_spans: int):
    """Per span: (min, max) service over its annotation hosts — the
    span's host SET for spans with at most two distinct hosts (the
    cs/cr-client + sr/ss-server shape of real traffic). Spans with more
    distinct hosts index under min/max only (counted nowhere: the scan
    fallback still finds them when a bucket is incomplete)."""
    big = jnp.int32(1 << 30)
    seg = jnp.where(valid_a, ann_span_idx, n_spans)
    mn = jnp.full(n_spans + 1, big, jnp.int32).at[seg].min(
        jnp.where(valid_a, ann_svc, big), mode="drop"
    )[:n_spans]
    mx = jnp.full(n_spans + 1, -1, jnp.int32).at[seg].max(
        jnp.where(valid_a, ann_svc, -1), mode="drop"
    )[:n_spans]
    return mn, mx


def _mixb(keys):
    from zipkin_tpu.ops.hashing import mix_keys64

    return mix_keys64([jnp.asarray(k, jnp.int64) for k in keys])


def _bucket_of(mixed, n_buckets: int):
    return (mixed & jnp.uint64(n_buckets - 1)).astype(jnp.int32)


def _verify_of(mixed):
    return mixed.astype(jnp.int64)


def _window_fold(window, window_ts, durations, link_id, ok, tsf, tsl, S):
    """Fold resolved links into the accumulating window bank (exact
    segmented Moments — same Chan/Pébay arithmetic as the host monoid,
    ZipkinAggregateJob.scala:36-46)."""
    bank = M.segment_moments(
        durations.astype(jnp.float32), link_id, S * S, valid=ok
    )
    new_window = M.combine(window, bank)
    any_ok = ok.any()
    ts_f = jnp.where(ok & (tsf >= 0), tsf, I64_MAX).min()
    ts_l = jnp.where(ok & (tsl >= 0), tsl, I64_MIN).max()
    new_ts = jnp.stack([
        jnp.minimum(window_ts[0], ts_f), jnp.maximum(window_ts[1], ts_l)
    ])
    return new_window, jnp.where(any_ok, new_ts, window_ts)


def _resolve_links(tab, trace_id, span_id, parent_id, svc, child_svc,
                   duration, build_ok, probe_ok, S):
    """Resolve each child's parent service: FIRST an exact within-batch
    sort-join (batch-sized, so same-batch parent/child pairs — the
    overwhelmingly common case — never depend on hash-table occupancy),
    THEN a span-table probe for parents from earlier batches. Returns
    (resolved, link_id, pending, ckey) — pending children found no
    parent anywhere and wait in the pending ring."""
    in_batch, psvc_b = join.lookup(
        (trace_id, span_id), build_ok, svc,
        (trace_id, parent_id), probe_ok,
    )
    ckey = _mix48(trace_id, parent_id)
    in_tab, psvc_t = _tab_lookup(tab, ckey)
    found = in_batch | in_tab
    psvc = jnp.where(in_batch, psvc_b, psvc_t)
    resolved = (
        probe_ok & found & (psvc >= 0) & (child_svc >= 0)
        & (child_svc < S) & (psvc < S) & (duration >= 0)
    )
    link_id = jnp.where(
        resolved, psvc * jnp.int32(S) + child_svc, 0
    )
    # A found parent without a service can never produce a link: drop
    # (matches the r2 join's link_ok gate), don't queue. Children whose
    # own service can't address a bank cell never queue either.
    pending = (probe_ok & ~found & (child_svc >= 0) & (child_svc < S)
               & (duration >= 0))
    return resolved, link_id, pending, ckey


def _sweep_core(state: "StoreState"):
    """Re-probe the pending ring; resolved children fold into the
    window. Returns the updated (window, window_ts, pend_key)."""
    S = state.config.max_services
    u = state.pend_key.astype(jnp.uint64)
    occupied = (u & jnp.uint64(1)) == 1
    ckey = u >> jnp.uint64(16)
    csvc = ((u >> jnp.uint64(1)) & jnp.uint64(_SVC_MASK)).astype(
        jnp.int32
    ) - 1
    found, psvc = _tab_lookup(state.span_tab, ckey)
    resolved = (occupied & found & (psvc >= 0) & (psvc < S)
                & (csvc >= 0) & (csvc < S))
    link_id = jnp.where(resolved, psvc * jnp.int32(S) + csvc, 0)
    window, window_ts = _window_fold(
        state.dep_window, state.dep_window_ts, state.pend_dur, link_id,
        resolved, state.pend_tsf, state.pend_tsl, S,
    )
    # Children whose parent arrived without a service — or whose own
    # service id can't address a bank cell — can never link: free their
    # slots too.
    drop = occupied & found & (
        (psvc < 0) | (psvc >= S) | (csvc < 0) | (csvc >= S)
    )
    cleared = jnp.where(resolved | drop, jnp.int64(0), state.pend_key)
    return window, window_ts, cleared


@partial(jax.jit, donate_argnums=(0,))
def dep_sweep(state: "StoreState") -> "StoreState":
    """Resolve pending children against the span table (the late-parent
    half of the streaming join). Cheap relative to ring size — all ops
    are pending-ring-sized. Called by the bucket close, before
    dependency reads, and on the collector's timer."""
    window, window_ts, cleared = _sweep_core(state)
    return state.replace(
        dep_window=window, dep_window_ts=window_ts, pend_key=cleared,
        # The sweep mutates state without moving any write cursor, so
        # it must bump a counter: checkpoint._state_generation decides
        # staged-leaf reuse from counters + cursors alone, and a sweep
        # between two save attempts would otherwise silently mix two
        # inconsistent cuts.
        counters={**state.counters,
                  "sweeps": state.counters["sweeps"] + 1},
    )


@partial(jax.jit, donate_argnums=(0,))
def dep_close_bucket(state: "StoreState") -> "StoreState":
    """Sweep, then rotate the window bank into a time-tagged slot of
    ``dep_banks`` — closing the current dependency time bucket (the
    hourly-aggregation-timer role of the reference's AnormAggregator
    schedule). The displaced slot merges into the all-time tail. An
    empty window only sweeps: rotating would displace one real
    time-tagged bank per idle tick and erode the windowing."""
    window, window_ts, cleared = _sweep_core(state)
    rotate = window[:, 0].sum() > 0
    K = state.config.dep_buckets
    slot = (state.dep_bank_seq % K).astype(jnp.int32)
    displaced = state.dep_banks[slot]
    displaced_ts = state.dep_bank_ts[slot]
    empty_ts = jnp.array([I64_MAX, I64_MIN], jnp.int64)
    return state.replace(
        dep_moments=jnp.where(
            rotate, M.combine(state.dep_moments, displaced),
            state.dep_moments,
        ),
        dep_overflow_ts=jnp.where(rotate, jnp.stack([
            jnp.minimum(state.dep_overflow_ts[0], displaced_ts[0]),
            jnp.maximum(state.dep_overflow_ts[1], displaced_ts[1]),
        ]), state.dep_overflow_ts),
        dep_banks=jnp.where(
            rotate, state.dep_banks.at[slot].set(window), state.dep_banks
        ),
        dep_bank_ts=jnp.where(
            rotate, state.dep_bank_ts.at[slot].set(window_ts),
            state.dep_bank_ts,
        ),
        dep_bank_seq=state.dep_bank_seq + rotate.astype(jnp.int64),
        dep_window=jnp.where(rotate, jnp.zeros_like(window), window),
        dep_window_ts=jnp.where(rotate, empty_ts, window_ts),
        pend_key=cleared,
        # An un-rotated close still sweeps — see dep_sweep's counter.
        counters={**state.counters,
                  "sweeps": state.counters["sweeps"] + 1},
    )


def poison_index_trust(state: "StoreState") -> "StoreState":
    """Mark every index bucket permanently untrusted (cursor past depth,
    watermark at +inf), forcing all reads through the exact scan
    kernels. Used when restoring snapshots that predate the index
    families: empty buckets with zero cursors would otherwise claim
    completeness and silently hide every restored span from the fast
    paths. New writes still append (cursors keep counting), but trust
    never returns for a poisoned bucket — the scan fallback serves the
    store's remaining lifetime, which is exactly the pre-index behavior
    the snapshot was taken under."""
    big = jnp.int64(1) << 60
    # One unified cursor/watermark pair covers every family now
    # (candidate prefix + trace suffix of the shared arena). Explicit
    # i64 (a legacy snapshot may restore other dtypes).
    return state.replace(
        cand_pos=jnp.full(state.cand_pos.shape, big, jnp.int64),
        cand_wm=jnp.full(state.cand_wm.shape, I64_MAX, jnp.int64),
    )


def poison_ann_trust(state: "StoreState") -> "StoreState":
    """Trust reset for snapshots predating revision 7, covering both
    rev-7 additions. Works on single and stacked sharded states alike.

    - ``ann_poison`` didn't exist: any restored resident span might
      have 3+ distinct annotation hosts, so stamp every service with
      the current write_pos — the annotation-family fast paths distrust
      their buckets until the ring has fully turned over, then
      self-heal.
    - ``key_tab`` didn't exist: the claim-with-clean-watermark
      invariant ("a fresh claim is the key's first record ever") does
      NOT hold across the restore boundary — pre-restore displacement
      history is lost, so a post-restore claim could certify a window
      missing displaced-but-resident restored spans. Permanently
      disable the table with a tombstone fingerprint (INT32_MIN: the
      i32 min-war can never overwrite it and _fp31 never produces it,
      so claims always fail → absent records → bucket gates serve,
      exactly the pre-upgrade behavior); key_wm is pinned at I64_MAX
      so even a fingerprint collision with the tombstone pattern reads
      as untrusted."""
    wp = jnp.asarray(state.write_pos, jnp.int64)
    counters = dict(state.counters)
    # A tombstoned table must also kill the NEGATIVE gate (absent record
    # ⇒ never indexed): pre-restore claims are lost, so absence proves
    # nothing. A nonzero drop counter disables it permanently.
    counters["key_claim_drops"] = jnp.maximum(
        jnp.asarray(counters.get("key_claim_drops", 0), jnp.int64),
        jnp.ones_like(wp),
    )
    return state.replace(
        ann_poison=jnp.broadcast_to(
            wp[..., None], state.ann_poison.shape
        ).astype(jnp.int64),
        key_tab=jnp.full(state.key_tab.shape, _FP_TOMB, jnp.int32),
        key_wm=jnp.full(state.key_wm.shape, I64_MAX, jnp.int64),
        counters=counters,
    )


@partial(jax.jit, donate_argnums=(0,))
def rebuild_span_tab(state: "StoreState") -> "StoreState":
    """(Re)insert every live resident span into the hash table. Used
    when restoring pre-revision-4 snapshots (whose schema had no table),
    so children arriving after the restore still find checkpointed
    parents — the case the retired resident-ring join covered."""
    live = state.row_gid >= 0
    key = _mix48(state.trace_id, state.span_id)
    return state.replace(
        span_tab=_tab_insert(state.span_tab, key, state.service_id, live)
    )


def dep_archive_step(state: "StoreState", w_new=None) -> "StoreState":
    """Compatibility alias from the r2 watermark-archive API: closing a
    bucket is the streaming join's analogue of an archive pass. The
    watermark argument is vestigial (links no longer depend on ring
    residency). NOTE: unlike the r2 original this DONATES ``state`` —
    reassign the result, don't keep using the argument."""
    del w_new
    return dep_close_bucket(state)


def dep_archive_auto(state: "StoreState", incoming=None) -> "StoreState":
    """Compatibility alias (see dep_archive_step; donates ``state``)."""
    del incoming
    return dep_close_bucket(state)


def stablehlo_op_census(stablehlo_text: str,
                        ops=("scatter", "gather", "sort")) -> dict:
    """Scatter/gather/sort census of a StableHLO lowering — the ONE
    counter behind the tier-1 census ceilings (store/census.py),
    TpuSpanStore.step_census, and the counter-block purity gate; keep a
    single definition so the gate and the runtime observable can never
    drift. Backend-independent: counts ops the program ISSUES, not what
    a backend fuses away."""
    import re

    return {
        op: len(re.findall(rf'"stablehlo\.{op}"', stablehlo_text))
        for op in ops
    }


# Telemetry counter block: every scalar the obs layer wants, packed
# into ONE [N] i64 vector so a metrics scrape costs one fused read-only
# launch + one D2H instead of a dict of tiny transfers. Derived values
# (occupancy, laps, poison census) are computed HERE at fetch time from
# cursors the ingest step already maintains — the block adds ZERO ops
# to the ingest step itself (scripts/bench_smoke.py asserts the step's
# scatter/sort census is unchanged and that this fetch lowers with no
# scatter/sort at all).
COUNTER_BLOCK_FIELDS = (
    "write_pos", "ann_write_pos", "bann_write_pos", "pend_pos",
    "dep_bank_seq", "ring_occupancy", "ring_laps", "ann_ring_occupancy",
    "bann_ring_occupancy", "pend_depth", "poisoned_services",
    "spans_seen", "anns_seen", "banns_seen", "batches",
    "key_claim_drops", "sweeps", "ts_min", "ts_max",
)


@jax.jit
def counter_block(state: StoreState) -> jnp.ndarray:
    """[len(COUNTER_BLOCK_FIELDS)] i64 — see COUNTER_BLOCK_FIELDS."""
    c = state.config
    wp = state.write_pos
    poisoned = jnp.sum(
        (state.ann_poison >= wp - c.capacity)
        & (state.ann_poison > I64_MIN)
    ).astype(jnp.int64)
    vals = {
        "write_pos": wp,
        "ann_write_pos": state.ann_write_pos,
        "bann_write_pos": state.bann_write_pos,
        "pend_pos": state.pend_pos,
        "dep_bank_seq": state.dep_bank_seq,
        "ring_occupancy": jnp.minimum(wp, c.capacity),
        "ring_laps": wp // c.capacity,
        "ann_ring_occupancy": jnp.minimum(state.ann_write_pos,
                                          c.ann_capacity),
        "bann_ring_occupancy": jnp.minimum(state.bann_write_pos,
                                           c.bann_capacity),
        "pend_depth": jnp.minimum(state.pend_pos, c.pending_slots),
        "poisoned_services": poisoned,
        "ts_min": state.ts_min,
        "ts_max": state.ts_max,
        **{k: state.counters[k] for k in (
            "spans_seen", "anns_seen", "banns_seen", "batches",
            "key_claim_drops", "sweeps",
        )},
    }
    return jnp.stack([
        jnp.asarray(vals[f], jnp.int64) for f in COUNTER_BLOCK_FIELDS
    ])


@read_program()
def _total_dep_impl(dep_moments, dep_banks, dep_window):
    banks = M.reduce_moments(dep_banks, axis=0)
    return M.combine(M.combine(dep_moments, banks), dep_window)


def total_dep_moments(state: "StoreState"):
    """Tail + time-tagged banks + accumulating window: the complete link
    Moments bank. Callers wanting pending (late-parent) children
    included run dep_sweep first — TpuSpanStore.get_dependencies does."""
    return _total_dep_impl(
        state.dep_moments, state.dep_banks, state.dep_window
    )


@read_program()
def _dep_in_range_impl(dep_moments, dep_banks, dep_bank_ts,
                       dep_overflow_ts, dep_window, dep_window_ts,
                       start_ts, end_ts):
    start_ts = jnp.asarray(start_ts, jnp.int64)
    end_ts = jnp.asarray(end_ts, jnp.int64)
    bmin = dep_bank_ts[:, 0]
    bmax = dep_bank_ts[:, 1]
    sel = (bmin <= end_ts) & (bmax >= start_ts)
    banks = jnp.where(sel[:, None, None], dep_banks, 0.0)
    total = M.reduce_moments(banks, axis=0)
    ov = (dep_overflow_ts[0] <= end_ts) & (dep_overflow_ts[1] >= start_ts)
    total = M.combine(total, jnp.where(ov, dep_moments, 0.0))
    w_ok = (dep_window_ts[0] <= end_ts) & (dep_window_ts[1] >= start_ts)
    return M.combine(total, jnp.where(w_ok, dep_window, 0.0))


def _compact_bank(bank, k: int):
    """(n_nonzero, row ids [k], rows [k, 5]) — top-k-by-count compaction
    of a [S*S, 5] Moments bank. Real deployments have O(S) live links,
    so shipping the k densest rows instead of the whole bank cuts the
    host transfer from ~20 MB to ~400 KB (the D2H was the entire
    dependencies-query p99). The caller must verify n_nonzero <= k and
    fall back to the full bank otherwise — compaction never silently
    drops a link."""
    counts = bank[:, 0]
    nz = (counts > 0).sum(dtype=jnp.int32)
    _, idx = jax.lax.top_k(counts, k)
    return nz, idx.astype(jnp.int32), bank[idx]


@read_program(static_argnums=(3,))
def total_dep_moments_compact(dep_moments, dep_banks, dep_window,
                              k: int):
    """total_dep_moments fused with _compact_bank in one launch."""
    return _compact_bank(
        _total_dep_impl.__wrapped__(dep_moments, dep_banks, dep_window),
        k,
    )


@read_program(static_argnums=(8,))
def dep_in_range_compact(dep_moments, dep_banks, dep_bank_ts,
                         dep_overflow_ts, dep_window, dep_window_ts,
                         start_ts, end_ts, k: int):
    """dep_moments_in_range fused with _compact_bank in one launch."""
    return _compact_bank(
        _dep_in_range_impl.__wrapped__(
            dep_moments, dep_banks, dep_bank_ts, dep_overflow_ts,
            dep_window, dep_window_ts, start_ts, end_ts,
        ),
        k,
    )


def dep_moments_in_range(state: "StoreState", start_ts, end_ts):
    """Link Moments restricted to banks (and the open window) whose
    children's ts range overlaps [start_ts, end_ts] — the device answer
    to Aggregates.getDependencies(startDate, endDate)
    (Aggregates.scala:26-31). Bucket-granular: a bank overlapping the
    window contributes whole (the reference's hourly Dependencies rows
    are equally coarse, Dependencies.scala:59-67)."""
    return _dep_in_range_impl(
        state.dep_moments, state.dep_banks, state.dep_bank_ts,
        state.dep_overflow_ts, state.dep_window, state.dep_window_ts,
        start_ts, end_ts,
    )


# ---------------------------------------------------------------------------
# ingest_step — ONE fused launch per batch
# ---------------------------------------------------------------------------


# The span ring's columns that a launch writes from the batch's column
# of the same name (row_gid, the sixteenth, is the step's own).
_SPAN_RING_COLS = (
    "trace_id", "span_id", "parent_id", "name_id", "name_lc_id",
    "service_id", "ts_cs", "ts_cr", "ts_sr", "ts_ss", "ts_first",
    "ts_last", "duration", "flags", "indexable",
)


@partial(jax.jit, donate_argnums=(0,))
def ingest_step(state: StoreState, b: DeviceBatch) -> StoreState:
    c = state.config
    S = c.max_services
    P = b.trace_id.shape[0]
    PA = b.ann_ts.shape[0]
    PB = b.bann_key_id.shape[0]

    # A launch writes every ring at CONSECUTIVE slots from its cursor,
    # so the writes are windows (_ring_write: slice, select, slice
    # update, on the donated leaf) and not scatters; only the paged
    # layout's span rows, whose slots are the planner's, scatter
    # (_uset, which asserts unique_indices to XLA: duplicate slots
    # would be silent state corruption, not just nondeterminism).
    # Either way the valid rows must fit the ring — n_spans <= capacity,
    # n_anns <= ann_capacity, n_banns <= bann_capacity, pending count
    # <= pending_slots — which are dynamic values the host chunker
    # enforces per batch (TpuSpanStore.write_batch raises on violation,
    # store/tpu.py). The pad P itself may exceed the ring (tiny-ring
    # tests pad well past capacity): such a ring scatters too, its
    # padded rows remapped to DISTINCT out-of-bounds slots by _uset.
    def ring_write(ring, cap, pos, n, cols):
        """``cols`` ({leaf: batch column}) at ``n`` consecutive slots
        of one ring from its cursor ``pos``; notes the form it took."""
        for name, vals in cols.items():
            upd[name] = _ring_write(getattr(state, name), pos % cap, vals, n)
        pad = next(iter(cols.values())).shape[0]
        _note_path(c, "ring_write", ring + (
            ":window" if _ring_windowed(cap, pad) else ":scatter"))

    upd = {}
    mask = jnp.arange(P) < b.n_spans
    mask_a = jnp.arange(PA) < b.n_anns
    mask_b = jnp.arange(PB) < b.n_banns

    # -- span ring/page writes -----------------------------------------
    # Ring: consecutive slots mod capacity are unique within a batch
    # (P <= capacity, enforced by the host chunkers). Paged (r19): the
    # host PagePlanner pre-assigned each span a (slot, epoch-encoded
    # gid) pair with gid = page_epoch * capacity + slot — slots are
    # unique among valid rows by construction (pages fill
    # monotonically, pages are distinct), and slot == gid % capacity
    # still holds, so every liveness check downstream is layout-blind.
    # The ring's consecutive slots are written as windows; the paged
    # layout's column writes ride the fast unique plane scatter (_uset).
    with jax.named_scope("ingest.ring_write"):
        if c.paged_enabled:
            R = c.page_rows
            RC = b.reclaim_page.shape[0]
            # Invalidate every row of the pages this unit reclaims BEFORE
            # the batch writes land (the functional update chain fixes the
            # order): the planner spliced these pages out of their owners'
            # chains, and a stale row_gid would keep the old spans visible
            # to the ring-scan kernels but not the page gather. The
            # reclaimed rows were captured host-side before this launch
            # (TpuSpanStore._capture_pages), so the captured-before-
            # overwrite invariant holds per page.
            with jax.named_scope("ingest.evict_pages"):
                r_slots = (
                    b.reclaim_page[:, None] * R
                    + jnp.arange(R, dtype=jnp.int32)[None, :]
                ).reshape(-1)
                r_ok = jnp.repeat(b.reclaim_page >= 0, R)
                row_gid0 = _uset(
                    state.row_gid, r_slots, jnp.full(RC * R, -1, jnp.int64),
                    r_ok,
                )
            gids = b.span_gid
            for col in _SPAN_RING_COLS:
                upd[col] = _uset(getattr(state, col), b.span_slot,
                                 getattr(b, col), mask)
            upd["row_gid"] = _uset(row_gid0, b.span_slot, gids, mask)
            _note_path(c, "ring_write", "span:scatter")
        else:
            gids = state.write_pos + jnp.arange(P, dtype=jnp.int64)
            ring_write(
                "span", c.capacity, state.write_pos, b.n_spans,
                {**{col: getattr(b, col) for col in _SPAN_RING_COLS},
                 "row_gid": gids})
        upd["write_pos"] = state.write_pos + b.n_spans.astype(jnp.int64)

    # -- annotation ring writes ----------------------------------------
    # Annotation/binary rings stay FIFO under BOTH layouts (ann rows
    # have no pages; their liveness rides the owning span's gid via
    # _span_slot), so ring-age ordering and the _iq freshness gates
    # keep working unchanged in paged mode.
    with jax.named_scope("ingest.annotation_ring_write"):
        a_gids = state.ann_write_pos + jnp.arange(PA, dtype=jnp.int64)
        if c.paged_enabled:
            span_gid_of_ann = gids[b.ann_span_idx]
        else:
            span_gid_of_ann = state.write_pos + b.ann_span_idx.astype(jnp.int64)
        ring_write(
            "ann", c.ann_capacity, state.ann_write_pos, b.n_anns,
            {"ann_gid": span_gid_of_ann,
             **{col: getattr(b, col) for col in ANN_MAT_COLS[1:]}})
        upd["ann_write_pos"] = state.ann_write_pos + b.n_anns.astype(jnp.int64)

        bb_gids = state.bann_write_pos + jnp.arange(PB, dtype=jnp.int64)
        if c.paged_enabled:
            span_gid_of_bann = gids[b.bann_span_idx]
        else:
            span_gid_of_bann = state.write_pos + b.bann_span_idx.astype(jnp.int64)
        ring_write(
            "bann", c.bann_capacity, state.bann_write_pos, b.n_banns,
            {"bann_gid": span_gid_of_bann,
             **{col: getattr(b, col) for col in BANN_MAT_COLS[1:]}})
        upd["bann_write_pos"] = state.bann_write_pos + b.n_banns.astype(jnp.int64)

    # -- streaming dependency join -------------------------------------
    # Insert this batch's spans into the hash table FIRST so same-batch
    # parents resolve immediately, then probe each child for its parent
    # (ZipkinAggregateJob.scala:26-38 as a streaming hash join; r2's
    # O(ring) sort-join cost seconds per pass at scale).
    with jax.named_scope("ingest.span_table_insert"):
        skey = _mix48(b.trace_id, b.span_id)
        tab = _tab_insert(state.span_tab, skey, b.service_id, mask)
        upd["span_tab"] = tab
    with jax.named_scope("ingest.dependency_join"):
        resolved, link_id, pending, ckey = _resolve_links(
            tab, b.trace_id, b.span_id, b.parent_id, b.service_id,
            b.service_id, b.duration, mask, mask & b.has_parent, S,
        )
        upd["dep_window"], upd["dep_window_ts"] = _window_fold(
            state.dep_window, state.dep_window_ts, b.duration, link_id,
            resolved, b.ts_first, b.ts_last, S,
        )
        # Children whose parent hasn't arrived yet wait in the pending ring
        # (re-probed by dep_sweep); the ring overwrites oldest-first, the
        # bounded-wait analogue of the reference's index TTL.
        # The pending rows take consecutive slots too, once packed to the
        # front of a P-row buffer by their running count (a scatter of
        # the batch's size).
        rank = jnp.cumsum(pending.astype(jnp.int32)) - 1
        n_pend = pending.sum(dtype=jnp.int64)
        front = jnp.zeros(P, jnp.int64)
        ring_write(
            "pend", c.pending_slots, state.pend_pos, n_pend,
            {name: _uset(front, rank, vals, pending)
             for name, vals in (
                 ("pend_key", _tab_pack(ckey, b.service_id)),
                 ("pend_dur", b.duration), ("pend_tsf", b.ts_first),
                 ("pend_tsl", b.ts_last))})
        upd["pend_pos"] = state.pend_pos + n_pend

    # -- index column families -----------------------------------------
    # (written before the counter block; the ann-derived columns below
    # are shared with the presence/top-annotation updates further down)
    n_key_drops = jnp.int64(0)
    if c.use_index:
        with jax.named_scope("ingest.index_segments"):
            lay, _, _ = c.idx_layout
            # Coarse-war granularity for ALL the gid watermarks in this
            # step (ann_poison, key_wm, the trace-segment wm): overstate by at most
            # capacity / 2^_WM_COARSE_FRAC_BITS — a sub-percent slice of
            # each gate's >= 1-ring trust margin (gates trust iff
            # wm < write_pos - capacity, and displaced entries are
            # ring-laps old whenever a gate is consulted in steady state).
            wm_shift = max(0, c.capacity.bit_length() - 1
                           - _WM_COARSE_FRAC_BITS)
            a_host = b.ann_service_id
            a_idx_ok = mask_a & (a_host >= 0) & (a_host < S)
            gid_a = jnp.where(a_idx_ok, span_gid_of_ann, -1)
            ts_a = b.ts_last[b.ann_span_idx]

            def seg(fam, local_bucket, gid, verify, ts, ok):
                """One concatenation segment of the combined write: global
                bucket, first-slot row, depth vectors + the entry payload.
                The service family is not per-key-tracked (its bucket IS the
                key — no aliasing — and its verify words are raw service ids
                whose key48 would all collide); it MUST stay the first
                segment — _index_write takes the keyed families as the
                suffix from ``keyed_from``."""
                b_base, s_base, n_b, depth = lay[fam]
                lb = jnp.clip(local_bucket, 0, n_b - 1)
                n = lb.shape[0]
                return fam, (
                    lb.astype(jnp.int32) + jnp.int32(b_base),
                    lb.astype(jnp.int64) * depth + jnp.int64(s_base),
                    jnp.full(n, depth, jnp.int32),
                    jnp.asarray(gid, jnp.int64),
                    jnp.asarray(verify, jnp.int64),
                    jnp.asarray(ts, jnp.int64),
                    ok,
                )

            segments = []
            # Service family: bucket = the annotation's own host service —
            # exactly the rows the scan kernel matches for a service query.
            segments.append(seg(
                StoreConfig.CAND_SVC, a_host, gid_a, a_host, ts_a, a_idx_ok
            ))
            # (service, span name) family.
            ann_name_lc_i = b.name_lc_id[b.ann_span_idx]
            nm_ok = a_idx_ok & (ann_name_lc_i >= 0)
            nm_mix = _mixb([a_host, ann_name_lc_i])
            segments.append(seg(
                StoreConfig.CAND_NAME, _bucket_of(nm_mix, c.name_buckets),
                gid_a, _verify_of(nm_mix), ts_a, nm_ok,
            ))
            # (service, annotation value) family: a span's value can match a
            # query under ANY of its hosts (per-slot semantics of the scan /
            # the in-memory oracle), so entries are written under the span's
            # host-set (min, max) pair. Core annotations are never queryable
            # (SpanStore.scala:199) and are skipped.
            hmin, hmax = _span_host_range(a_host, b.ann_span_idx, a_idx_ok, P)
            h1 = hmin[b.ann_span_idx]
            h2 = hmax[b.ann_span_idx]
            # A 3+-distinct-host span is indexed under (min, max) only: its
            # MIDDLE hosts' annotation-family buckets could claim complete
            # answers that silently omit it. Record the span's gid against
            # each middle host; queries for that service distrust the
            # annotation fast paths until the span is evicted (see
            # StoreState.ann_poison).
            mid = a_idx_ok & (a_host != h1) & (a_host != h2)
            v_ok = (
                mask_a & (b.ann_value_id >= FIRST_USER_ANNOTATION_ID)
                & (b.ann_value_id < jnp.int32(1 << 30))
            )
            for h, extra in ((h1, None), (h2, h2 != h1)):
                ok = v_ok & (h >= 0) & (h < S)
                if extra is not None:
                    ok &= extra
                mix = _mixb([h, b.ann_value_id])
                segments.append(seg(
                    StoreConfig.CAND_ANN, _bucket_of(mix, c.ann_buckets),
                    jnp.where(ok, span_gid_of_ann, -1), _verify_of(mix),
                    ts_a, ok,
                ))
            # (service, binary key[, value]) family: two bucket keyings per
            # host — with the value (valued queries) and with a -1 sentinel
            # (key-only queries) — under the span's host-set pair.
            bh1 = hmin[b.bann_span_idx]
            bh2 = hmax[b.bann_span_idx]
            bk_idx_ok = mask_b & (b.bann_key_id >= 0)
            ts_b = b.ts_last[b.bann_span_idx]
            no_val = jnp.full(PB, -1, jnp.int32)
            for h, val, extra in (
                (bh1, b.bann_value_id, None), (bh2, b.bann_value_id, bh2 != bh1),
                (bh1, no_val, None), (bh2, no_val, bh2 != bh1),
            ):
                ok = bk_idx_ok & (h >= 0) & (h < S)
                if extra is not None:
                    ok &= extra
                mix = _mixb([h, b.bann_key_id, val])
                segments.append(seg(
                    StoreConfig.CAND_BANN, _bucket_of(mix, c.bann_buckets),
                    jnp.where(ok, span_gid_of_bann, -1), _verify_of(mix),
                    ts_b, ok,
                ))
            # keyed_from depends on the un-keyed SVC family being the SINGLE
            # leading segment; a reorder would silently poison the key table
            # (service verify words all collide in key48 space) — assert the
            # invariant structurally, at trace time.
            fams = [f for f, _ in segments]
            assert (fams[0] == StoreConfig.CAND_SVC
                    and StoreConfig.CAND_SVC not in fams[1:]), fams
            n_cand_rows = sum(p[0].shape[0] for _, p in segments)
            # Trace-membership families trail the candidate segments in the
            # SAME unified concatenation: row gids bucketed by trace-id
            # hash, one sub-family per ring (whole-trace fetch + durations).
            # Verify carries the trace mix, ts the row's last_ts — the
            # arena rows are uniform (gid, verify, ts) triples.
            tb = _bucket_of(_mixb([b.trace_id]), c.trace_buckets)
            tmix = _verify_of(_mixb([b.trace_id]))
            NC = StoreConfig.N_CAND_FAMILIES
            segments.append(seg(
                NC + StoreConfig.TR_SPAN, tb, gids, tmix, b.ts_last, mask
            ))
            segments.append(seg(
                NC + StoreConfig.TR_ANN, tb[b.ann_span_idx], a_gids,
                tmix[b.ann_span_idx], ts_a, mask_a,
            ))
            segments.append(seg(
                NC + StoreConfig.TR_BANN, tb[b.bann_span_idx], bb_gids,
                tmix[b.bann_span_idx], b.ts_last[b.bann_span_idx], mask_b,
            ))
            cat = [jnp.concatenate(parts)
                   for parts in zip(*(p for _, p in segments))]
        # Static per-shape path decisions (r12), recorded at trace time
        # so counters()/bench can report which kernels a config's
        # compiled steps actually used. Both rank paths are bitwise-
        # identical, so a mixed-shape store (different pad buckets
        # picking different modes) still lands one deterministic state.
        rank_sel = rank_mode(
            c.rank_path, cat[0].shape[0], c.idx_layout[1], wm_shift)
        _note_path(c, "rank", rank_sel[0])
        with jax.named_scope("ingest.index_write"):
            (upd["cand_idx"], upd["cand_pos"], upd["cand_wm"],
             upd["key_tab"], upd["key_wm"], upd["ann_poison"],
             n_key_drops) = _index_write(
                state.cand_idx, state.cand_pos, state.cand_wm,
                state.key_tab, state.key_wm, state.ann_poison, *cat,
                keyed_from=segments[0][1][0].shape[0],
                n_cand_rows=n_cand_rows,
                n_cand_buckets=c.cand_layout[1],
                poison_bucket=a_host, poison_gid=span_gid_of_ann,
                poison_ok=mid,
                wm_shift=wm_shift,
                rank_sel=rank_sel,
            )

    # -- per-service latency histogram ---------------------------------
    with jax.named_scope("ingest.sketch_update"):
        hist = svc_histogram(state)
        svc_ok = mask & (b.service_id >= 0) & (b.service_id < S) & (b.duration >= 0)
        bidx = Q.bucket_index(hist, b.duration.astype(jnp.float32))
        g = jnp.clip(b.service_id, 0, S - 1)
        ones_p = jnp.ones(P, jnp.int32)
        ones_a = jnp.ones(PA, jnp.int32)
        upd["svc_hist"] = _scatter_add(
            state.svc_hist,
            jnp.where(svc_ok, g * c.quantile_buckets + bidx, -1),
            ones_p,
        )

        # -- counters / presence matrices ----------------------------------
        svc_cnt_ok = mask & (b.service_id >= 0) & (b.service_id < S)
        upd["svc_span_counts"] = _scatter_add(
            state.svc_span_counts, jnp.where(svc_cnt_ok, b.service_id, -1),
            ones_p,
        )
        a_svc = b.ann_service_id
        a_svc_ok = mask_a & (a_svc >= 0) & (a_svc < S)
        upd["ann_svc_counts"] = _scatter_add(
            state.ann_svc_counts, jnp.where(a_svc_ok, a_svc, -1),
            ones_a,
        )

        # span-name presence keyed by annotation-host service (the semantics
        # of getSpanNames: names of indexed spans for a service).
        ann_name = b.name_id[b.ann_span_idx]  # batch-local gather
        ann_name_lc = b.name_lc_id[b.ann_span_idx]
        ann_indexable = b.indexable[b.ann_span_idx]
        np_ok = (
            a_svc_ok & ann_indexable
            & (ann_name_lc >= 0) & (ann_name >= 0) & (ann_name < c.max_span_names)
        )
        upd["name_presence"] = _scatter_add(
            state.name_presence,
            jnp.where(np_ok, a_svc * c.max_span_names + ann_name, -1),
            ones_a,
        )

        # top annotations per service (user annotations only).
        av_ok = (
            a_svc_ok
            & (b.ann_value_id >= FIRST_USER_ANNOTATION_ID)
            & (b.ann_value_id < c.max_annotation_values)
        )
        upd["ann_value_counts"] = _scatter_add(
            state.ann_value_counts,
            jnp.where(av_ok, a_svc * c.max_annotation_values + b.ann_value_id, -1),
            ones_a,
        )

        bk_svc = b.bann_service_id
        bk_ok = (
            mask_b & (bk_svc >= 0) & (bk_svc < S)
            & (b.bann_key_id >= 0) & (b.bann_key_id < c.max_binary_keys)
        )
        upd["bann_key_counts"] = _scatter_add(
            state.bann_key_counts,
            jnp.where(bk_ok, bk_svc * c.max_binary_keys + b.bann_key_id, -1),
            jnp.ones(PB, jnp.int32),
        )

        # -- probabilistic state -------------------------------------------
        t_hi, t_lo = dev_split64(b.trace_id)
        upd["hll_traces"] = hll.update(
            hll.HyperLogLog(state.hll_traces), t_hi, t_lo, valid=mask
        ).registers
        cms_sketch = cms.CountMin(state.cms_trace_spans)
        cms_idx = cms._indices(cms_sketch, t_hi, t_lo)  # [depth, P]
        cms_flat = cms_idx + (
            jnp.arange(c.cms_depth, dtype=jnp.int32) * c.cms_width
        )[:, None]
        cms_flat = jnp.where(mask[None, :], cms_flat, -1).reshape(-1)
        upd["cms_trace_spans"] = _scatter_add(
            state.cms_trace_spans, cms_flat,
            jnp.ones(c.cms_depth * P, jnp.int32),
        )

    # -- windowed Moments-sketch arena ---------------------------------
    # (service × ring-indexed time bucket) integer cells; the host
    # mirror folds the SAME rows in numpy (aggregate.windows
    # apply_window_update) — every op here is an integer add/max so the
    # two agree bitwise regardless of accumulation order. Budget: +5
    # scatters (+1 of them the serialized i64 class, 4P rows), +2
    # gathers, 0 sorts — the store/census.py r13 bump.
    if c.window_enabled:
        with jax.named_scope("ingest.window_arena"):
            Wn = c.win_slots
            w_ok = mask & (b.service_id >= 0) & (b.service_id < S) \
                & (b.ts_first >= 0)
            a_bkt = jnp.where(w_ok, b.ts_first, 0) // jnp.int64(c.window_us)
            slot = (a_bkt % Wn).astype(jnp.int32)
            slot = jnp.where(w_ok, slot, 0)
            # Epoch war: each touched slot advances to the max absolute
            # bucket offered this step; rows older than the winner (stale
            # lates, or the losers of an in-batch ring wrap) are dropped.
            new_epoch = _war_max64(state.win_epoch, slot, a_bkt, w_ok)
            upd["win_epoch"] = new_epoch
            stale = (new_epoch != state.win_epoch)[None, :, None]
            counts_w = jnp.where(stale, jnp.int32(0), state.win_counts)
            sums_w = jnp.where(stale, jnp.int64(0), state.win_sums)
            mm_w = jnp.where(stale, I32_MIN, state.win_mm)
            live = w_ok & (a_bkt == new_epoch[slot])
            cid = g * Wn + slot  # g = clip(service_id) — valid where live
            d_ok = live & (b.duration >= 0)
            x = (bidx >> c.win_x_shift).astype(jnp.int32)
            base3 = cid * 3
            idx_c = jnp.concatenate([
                jnp.where(live, base3, -1),
                jnp.where(live & b.error_flag, base3 + 1, -1),
                jnp.where(d_ok, base3 + 2, -1),
            ])
            upd["win_counts"] = _scatter_add(
                counts_w, idx_c, jnp.ones(3 * P, jnp.int32)
            )
            flat_s = sums_w.reshape(-1)
            xi = x.astype(jnp.int64)
            base4 = cid * 4
            idx_s = jnp.concatenate([base4, base4 + 1, base4 + 2,
                                     base4 + 3])
            safe_s = jnp.where(jnp.tile(d_ok, 4), idx_s, flat_s.shape[0])
            vals_s = jnp.concatenate([xi, xi * xi, xi * xi * xi,
                                      xi * xi * xi * xi])
            upd["win_sums"] = flat_s.at[safe_s].add(
                vals_s, mode="drop").reshape(sums_w.shape)
            flat_m = mm_w.reshape(-1)
            base2 = cid * 2
            idx_m = jnp.concatenate([base2, base2 + 1])
            safe_m = jnp.where(jnp.tile(d_ok, 2), idx_m, flat_m.shape[0])
            vals_m = jnp.concatenate([-x, x])
            upd["win_mm"] = flat_m.at[safe_m].max(
                vals_m, mode="drop").reshape(mm_w.shape)

    # -- time range + counters -----------------------------------------
    with jax.named_scope("ingest.counters"):
        firsts = jnp.where(mask & (b.ts_first >= 0), b.ts_first, I64_MAX)
        lasts = jnp.where(mask & (b.ts_last >= 0), b.ts_last, I64_MIN)
        upd["ts_min"] = jnp.minimum(state.ts_min, firsts.min())
        upd["ts_max"] = jnp.maximum(state.ts_max, lasts.max())
        # Spread-then-update: counters the step doesn't touch (sweeps)
        # must carry through, not silently reset to absent.
        upd["counters"] = {
            **state.counters,
            "spans_seen": state.counters["spans_seen"] + b.n_spans,
            "anns_seen": state.counters["anns_seen"] + b.n_anns,
            "banns_seen": state.counters["banns_seen"] + b.n_banns,
            "batches": state.counters["batches"] + 1,
            "key_claim_drops": state.counters["key_claim_drops"]
            + n_key_drops,
        }

    return state.replace(**upd)


@partial(jax.jit, donate_argnums=(0,))
def ingest_steps(state: StoreState, stacked: DeviceBatch) -> StoreState:
    """Chained ingest: run one fused step per leading-axis slice of
    ``stacked`` (a DeviceBatch whose every array carries a [k, ...]
    batch axis) inside a single jitted launch.

    On this backend one jitted CALL costs ~90-110 ms of dispatch
    regardless of work, while a ``lax.scan`` iteration costs ~5-7 ms
    (round 3, not re-measured) — so landing k batches per launch divides the
    per-batch dispatch floor by ~k. This is the device analogue of the
    reference collector draining several ItemQueue items per worker
    wake-up (ItemQueue.scala:39): amortize the fixed per-dispatch cost
    over many queued batches. Chunk boundaries, ring-capacity guards,
    and the sweep cadence are the CALLER's job, exactly as for
    ingest_step; every slice must satisfy the same capacity bounds."""
    state, _ = jax.lax.scan(
        lambda st, db: (ingest_step.__wrapped__(st, db), None),
        state, stacked,
    )
    return state


def stack_device_batches(dbs) -> DeviceBatch:
    """Stack equal-shape DeviceBatches along a new leading axis for
    ingest_steps (host-side; numpy arrays in, one stacked batch out)."""
    import numpy as np

    return DeviceBatch(*(
        np.stack([np.asarray(getattr(db, f)) for db in dbs])
        for f in DeviceBatch._fields
    ))


# ---------------------------------------------------------------------------
# Query kernels
# ---------------------------------------------------------------------------


def _span_slot(gid, row_gid, capacity: int):
    """Per annotation/binary ring row: (owning span's ring slot,
    row-still-live mask). Liveness = the span row at the slot still
    carries the gid this annotation was written under."""
    slot = jnp.clip((gid % capacity).astype(jnp.int32), 0, capacity - 1)
    return slot, (gid >= 0) & (row_gid[slot] == gid)


def _topk_candidates(tid, ts, valid, k: int):
    """Top-``k`` candidate rows by ts desc (validity folded into the
    key; valid rows have ts >= 0 by construction). Returns ONE stacked
    [3, min(k, rows)] i64 array (tid, ts, ok).

    Callers dedup candidates by trace id on the host
    (store.base.dedup_rank_limit) and re-query with a bigger ``k`` when
    the window may have truncated a hot trace's spans — the top-k
    primitive compiles in seconds where a full multi-key ring sort
    compiles for minutes at 2^23 rows on TPU, and executes in ~1ms.
    The escalation is exact: every trace missing from the candidate set
    has its best span below ALL k candidates, so any ``limit`` distinct
    traces found rank strictly above every excluded trace.
    """
    key = jnp.where(valid, ts, jnp.int64(-1))
    vals, idx = jax.lax.top_k(key, min(k, key.shape[0]))
    return jnp.stack([tid[idx], ts[idx], (vals >= 0).astype(jnp.int64)])


def _owned(gid, hit, row_gid, capacity: int):
    """Per span ring slot: whether the span there owns a side row that
    ``hit`` marks. One scatter over the side rows, no gather: a gid is
    its lap (gid // capacity; the page epoch in the paged layout) and
    its slot, and the gids written at a slot only grow, so the newest
    lap among the slot's hit rows is the span's own lap iff one of them
    is the span's. The per-row gathers of span columns this replaces
    took 20-30 ns a row on the chip (PERF.md 5); XLA lowers the scatter
    as one sort of the (slot, lap) pairs. i32 max, not a bool scatter:
    bool scatters serialize on this backend."""
    slot = jnp.clip((gid % capacity).astype(jnp.int32), 0, capacity - 1)
    lap = (gid // capacity).astype(jnp.int32) + 1
    laps = jnp.zeros(capacity + 1, jnp.int32).at[
        jnp.where(hit & (gid >= 0), slot, capacity)
    ].max(lap, mode="drop")[:-1]
    return (row_gid >= 0) & (laps == (row_gid // capacity).astype(
        jnp.int32) + 1)


@read_program(static_argnums=(7, 8))
def _q_by_service_impl(
    ann_gid, ann_service_id, row_gid, indexable, name_lc_col, trace_id,
    ts_last, capacity: int, k: int, svc_id, name_lc_id, end_ts,
):
    """One candidate per span: a span with a live annotation hosted by
    ``svc_id`` (any side row it owns counts once)."""
    ok = _owned(ann_gid, ann_service_id == svc_id, row_gid, capacity)
    ok &= indexable
    ok &= (name_lc_id < 0) | (name_lc_col == name_lc_id)
    ok &= (ts_last >= 0) & (ts_last <= end_ts)
    return _topk_candidates(trace_id, ts_last, ok, k)


def query_trace_ids_by_service(
    state: StoreState, svc_id, name_lc_id, end_ts, k: int
):
    """Candidate spans of a service (any annotation host), optional
    span-name match, last_ts <= end_ts, top ``k`` by last_ts desc (one
    candidate a span; never more than the span ring holds).

    Reference semantics: getTraceIdsByName (SpanStore.scala /
    CassieSpanStore.scala:366) with index ts = span last timestamp.
    Returns ONE stacked [3, k] i64 candidate array (see
    _topk_candidates). The jitted impl takes ONLY the seven columns it
    reads — every argument buffer costs dispatch time,
    and passing the whole 40-leaf state pytree made every index query
    pay ~0.8s of pure argument overhead.
    """
    return _q_by_service_impl(
        state.ann_gid, state.ann_service_id, state.row_gid,
        state.indexable, state.name_lc_id, state.trace_id, state.ts_last,
        state.config.capacity, k, svc_id, name_lc_id, end_ts,
    )


@read_program(static_argnums=(10, 11))
def _q_by_annotation_impl(
    ann_gid, ann_service_id, ann_value_col, row_gid, indexable, ts_last,
    trace_id, bann_gid, bann_key_col, bann_value_col,
    capacity: int, k: int,
    svc_id, ann_value_id, bann_key_id, bann_value_id, bann_value_id2,
    end_ts,
):
    """One candidate per span: a span with a live annotation hosted by
    ``svc_id`` and a live annotation of the value or binary annotation
    of the key (and value) asked for."""
    hosted = _owned(ann_gid, ann_service_id == svc_id, row_gid, capacity)
    a_hit = _owned(
        ann_gid, (ann_value_col == ann_value_id) & (ann_value_id >= 0),
        row_gid, capacity)
    value_free = (bann_value_id < 0) & (bann_value_id2 < 0)
    value_hit = (
        ((bann_value_id >= 0) & (bann_value_col == bann_value_id))
        | ((bann_value_id2 >= 0) & (bann_value_col == bann_value_id2))
    )
    b_hit = _owned(
        bann_gid, (bann_key_col == bann_key_id) & (bann_key_id >= 0)
        & (value_free | value_hit), row_gid, capacity)
    ok = hosted & (a_hit | b_hit) & indexable
    ok &= (ts_last >= 0) & (ts_last <= end_ts)
    return _topk_candidates(trace_id, ts_last, ok, k)


def query_trace_ids_by_annotation(
    state: StoreState, svc_id, ann_value_id, bann_key_id, bann_value_id,
    bann_value_id2, end_ts, k: int,
):
    """Annotation-index query (CassieSpanStore AnnotationsIndex semantics).

    Matches spans of ``svc_id`` that carry the user annotation
    ``ann_value_id``, OR a binary annotation with ``bann_key_id``
    (and one of ``bann_value_id``/``bann_value_id2`` if >= 0 — two slots
    because the host dictionary may hold a value in both str and bytes
    form). Pass -1 to disable either side. The jitted impl takes only
    the ten columns it reads (see query_trace_ids_by_service).
    """
    return _q_by_annotation_impl(
        state.ann_gid, state.ann_service_id, state.ann_value_id,
        state.row_gid, state.indexable, state.ts_last, state.trace_id,
        state.bann_gid, state.bann_key_id, state.bann_value_id,
        state.config.capacity, k,
        svc_id, ann_value_id, bann_key_id, bann_value_id, bann_value_id2,
        end_ts,
    )


# -- index fast-path query kernels ------------------------------------------


def _iq_finish(entries, cnt, wm, row_gid, indexable, ts_last, trace_id,
               extra_ok, capacity: int, depth: int, k: int, end_ts):
    """Shared tail: entry liveness via the gid round-trip, span-level
    filters from the ring, top-k by ts. ``complete`` is True when no
    probed bucket ever wrapped — then the candidate set provably holds
    every matching span still resident, and the host can skip the
    O(ring) scan fallback. For wrapped buckets the returned watermark
    lets the host decide trust per query (store.base.index_first_topk)."""
    gid = entries[:, 0]
    slot = jnp.clip((gid % capacity).astype(jnp.int32), 0, capacity - 1)
    live = (gid >= 0) & (row_gid[slot] == gid)
    ok = live & indexable[slot] & extra_ok
    ts = ts_last[slot]
    ok &= (ts >= 0) & (ts <= end_ts)
    mat = _topk_candidates(trace_id[slot], ts, ok, k)
    return mat, cnt <= depth, wm


@read_program(static_argnums=(7, 8, 9))
def _iq_service_impl(entries, pos, wm, row_gid, indexable, trace_id,
                     ts_last, capacity: int, layout, k: int,
                     svc, end_ts):
    # Span-name-filtered lookups route through the (service, name)
    # family (_iq_verify_impl), never through this bucket.
    b_base, s_base, n_b, depth = layout
    svc_i = jnp.clip(jnp.asarray(svc, jnp.int32), 0, n_b - 1)
    row = _arena_window(
        entries, jnp.int32(s_base) + svc_i * depth, depth)
    gb = jnp.int32(b_base) + svc_i
    ok = jnp.ones(depth, bool)
    return _iq_finish(row, pos[gb], wm[gb], row_gid, indexable, ts_last,
                      trace_id, ok, capacity, depth, k, end_ts)


def _key_lookup_wm(key_tab, key_wm, mixed):
    """Per-key record lookup (see StoreState.key_tab): (record found,
    max displaced gid) for the query key's verify word. Works on scalar
    or [N]-vector ``mixed``. Fingerprint matches may alias a different
    key's record — then the returned watermark is the shared (merged)
    one, which can only be LARGER than the key's true watermark:
    conservative for the completeness gate, and still sound for the
    negative gate (an indexed key's probes always find its fp record,
    or a drop was counted)."""
    T = key_tab.shape[0]
    k48 = mixed >> jnp.uint64(16)
    fp = _fp31(k48)
    found = jnp.zeros(jnp.shape(k48), bool)
    wmv = jnp.full(jnp.shape(k48), I64_MIN, jnp.int64)
    for slot in _tab_slots(k48, T)[:_KEY_PROBES]:
        hit = key_tab[slot] == fp
        wmv = jnp.where(hit & ~found, key_wm[slot], wmv)
        found |= hit
    return found, wmv


@read_program(static_argnums=(7, 8, 9))
def _iq_verify_impl(entries, pos, wm, row_gid, indexable, trace_id,
                    ts_last, capacity: int, layout, k: int,
                    key_parts, end_ts, key_tab, key_wm, write_pos,
                    key_drops, poison=None):
    b_base, s_base, n_b, depth = layout
    mixed = _mixb(list(key_parts))
    lb = _bucket_of(mixed, n_b)
    row = _arena_window(entries, jnp.int32(s_base) + lb * depth, depth)
    gb = jnp.int32(b_base) + lb
    ver_ok = row[:, 1] == _verify_of(mixed)
    cnt, bwm = pos[gb], wm[gb]
    # Per-key completeness: every entry this key ever LOST from its
    # bucket is already evicted from the ring, so the verify-matched
    # window rows are the key's full resident entry set — exact even
    # when bucket-mates wrapped the bucket. Negative twin: while no
    # claim was ever dropped, an ABSENT record proves the key was never
    # indexed at all — the (empty) result is the true answer, the
    # reference's instant empty-row read.
    kfound, kwmv = _key_lookup_wm(key_tab, key_wm, mixed)
    key_complete = (kfound & (kwmv < write_pos - capacity)) | (
        ~kfound & (key_drops == 0)
    )
    if poison is not None:
        # Middle-host distrust (see StoreState.ann_poison): while a
        # 3+-distinct-host span with key_parts[0] as a middle host is
        # still resident, no completeness claim may be trusted — its
        # middle-host entries (and their key claims) were never
        # written, so even the absence proof doesn't hold.
        svc = jnp.clip(key_parts[0], 0, poison.shape[0] - 1)
        bad = poison[svc] >= write_pos - capacity
        cnt = jnp.where(bad, jnp.int64(depth + 1), cnt)
        bwm = jnp.where(bad, jnp.int64(I64_MAX), bwm)
        key_complete &= ~bad
    mat, complete, out_wm = _iq_finish(
        row, cnt, bwm, row_gid, indexable, ts_last, trace_id, ver_ok,
        capacity, depth, k, end_ts,
    )
    return mat, complete | key_complete, out_wm


@read_program(static_argnums=(7, 8, 9))
def _iq_verify2_impl(entries, pos, wm, row_gid, indexable, trace_id,
                     ts_last, capacity: int, layout, k: int,
                     key_parts1, key_parts2, end_ts,
                     key_tab, key_wm, write_pos, key_drops,
                     poison=None):
    b_base, s_base, n_b, depth = layout
    m1 = _mixb(list(key_parts1))
    m2 = _mixb(list(key_parts2))
    lb1 = _bucket_of(m1, n_b)
    lb2 = _bucket_of(m2, n_b)
    r1 = _arena_window(entries, jnp.int32(s_base) + lb1 * depth, depth)
    r2 = _arena_window(entries, jnp.int32(s_base) + lb2 * depth, depth)
    row = jnp.concatenate([r1, r2])
    gb1 = jnp.int32(b_base) + lb1
    gb2 = jnp.int32(b_base) + lb2
    cnt = jnp.maximum(pos[gb1], pos[gb2])
    bwm = jnp.maximum(wm[gb1], wm[gb2])
    # Candidates span BOTH buckets, so per-key completeness needs both
    # keys' records to pass the displaced-gid gate.
    kf1, kw1 = _key_lookup_wm(key_tab, key_wm, m1)
    kf2, kw2 = _key_lookup_wm(key_tab, key_wm, m2)
    horizon = write_pos - capacity
    key_complete = (kf1 & kf2 & (kw1 < horizon) & (kw2 < horizon)) | (
        ~kf1 & ~kf2 & (key_drops == 0)
    )
    if poison is not None:
        svc = jnp.clip(key_parts1[0], 0, poison.shape[0] - 1)
        bad = poison[svc] >= horizon
        cnt = jnp.where(bad, jnp.int64(depth + 1), cnt)
        bwm = jnp.where(bad, jnp.int64(I64_MAX), bwm)
        key_complete &= ~bad
    ver_ok = (row[:, 1] == _verify_of(m1)) | (row[:, 1] == _verify_of(m2))
    mat, complete, out_wm = _iq_finish(
        row, cnt, bwm, row_gid, indexable, ts_last, trace_id, ver_ok,
        capacity, depth, k, end_ts,
    )
    return mat, complete | key_complete, out_wm


@read_program(static_argnums=(7, 8, 9))
def _iq_multi_impl(entries, pos, wm, row_gid, indexable, trace_id,
                   ts_last, capacity: int, k: int, k_max: int,
                   b_base, s_base, n_b, depth,
                   key1, key2, key3, three, is_svc,
                   end_ts, poison_on, poison, write_pos,
                   key_tab, key_wm, key_drops):
    """N independent index-bucket probes in ONE launch.

    Every probe carries its own family geometry (b_base/s_base/n_b/
    depth, rows of config.cand_layout) and key parts as DATA, so one
    compiled kernel serves any mix of service / (service, span-name) /
    (service, annotation-value) / (service, binary-key[, value]) probes.
    A jitted call cost ~90-110 ms flat when measured (round 3);
    the reference pays one index read per slice of a query
    (ThriftQueryService.scala:166-196) — this folds all slices (and all
    queries of a batch) into a single dispatch. Returns ([N, 3, k]
    candidates, [N] complete, [N] watermark) with the same trust
    contract as _iq_verify_impl; ``k_max`` is the widest family depth
    (static pad for the per-probe bucket windows).

    - ``three``: probe keys are (key1, key2, key3) instead of (key1,
      key2) — the binary families mix three parts.
    - ``is_svc``: service-family probe; the bucket is key1 itself and
      entry verify words equal the host service id.
    - ``poison_on``: apply the middle-host ann_poison gate (see
      StoreState.ann_poison) with key1 as the service id.
    """
    m2 = _mixb([key1, key2])
    m3 = _mixb([key1, key2, key3])
    mixed = jnp.where(three, m3, m2)
    nb64 = n_b.astype(jnp.int64)
    lb = (mixed & (nb64 - 1).astype(jnp.uint64)).astype(jnp.int64)
    lb = jnp.where(is_svc, jnp.clip(key1.astype(jnp.int64), 0, nb64 - 1),
                   lb)
    gb = b_base + lb
    slot0 = s_base + lb * depth.astype(jnp.int64)
    rows = jnp.arange(k_max, dtype=jnp.int64)[None, :]
    valid_row = rows < depth[:, None]
    idx = jnp.clip(slot0[:, None] + rows, 0, entries[0].shape[0] - 1)
    exp_ver = jnp.where(is_svc, key1.astype(jnp.int64), _verify_of(mixed))
    ver_ok = valid_row & (
        _arena_col(entries, idx, 1) == exp_ver[:, None])   # [N, Kmax]
    gid = _arena_col(entries, idx, 0)
    slot = jnp.clip((gid % capacity).astype(jnp.int32), 0, capacity - 1)
    live = (gid >= 0) & (row_gid[slot] == gid)
    ok = live & indexable[slot] & ver_ok
    ts = ts_last[slot]
    ok &= (ts >= 0) & (ts <= end_ts[:, None])
    mat = jax.vmap(
        lambda t, s, o: _topk_candidates(t, s, o, k)
    )(trace_id[slot], ts, ok)
    cnt = pos[jnp.clip(gb, 0, pos.shape[0] - 1)]
    wmv = wm[jnp.clip(gb, 0, wm.shape[0] - 1)]
    horizon = write_pos - capacity
    bad = poison_on & (
        poison[jnp.clip(key1, 0, poison.shape[0] - 1)] >= horizon
    )
    cnt = jnp.where(bad, depth.astype(jnp.int64) + 1, cnt)
    wmv = jnp.where(bad, jnp.int64(I64_MAX), wmv)
    kfound, kwmv = _key_lookup_wm(key_tab, key_wm, mixed)
    key_complete = ~is_svc & ~bad & (
        (kfound & (kwmv < horizon))
        | (~kfound & (key_drops == 0))
    )
    return mat, (cnt <= depth) | key_complete, wmv


def iquery_trace_ids_multi(state: StoreState, probes, k: int):
    """Host wrapper for _iq_multi_impl: ``probes`` is a dict of equal-
    length numpy arrays (keys matching the kernel's probe operands).
    Returns device results ([N, 3, k], [N] complete, [N] wm)."""
    c = state.config
    k_max = max(fam[3] for fam in c.cand_layout[0])
    k = min(k, k_max)
    return _iq_multi_impl(
        state.cand_idx, state.cand_pos, state.cand_wm, state.row_gid,
        state.indexable, state.trace_id, state.ts_last,
        c.capacity, k, k_max,
        jnp.asarray(probes["b_base"], jnp.int64),
        jnp.asarray(probes["s_base"], jnp.int64),
        jnp.asarray(probes["n_b"], jnp.int64),
        jnp.asarray(probes["depth"], jnp.int64),
        jnp.asarray(probes["key1"], jnp.int32),
        jnp.asarray(probes["key2"], jnp.int32),
        jnp.asarray(probes["key3"], jnp.int32),
        jnp.asarray(probes["three"], bool),
        jnp.asarray(probes["is_svc"], bool),
        jnp.asarray(probes["end_ts"], jnp.int64),
        jnp.asarray(probes["poison_on"], bool),
        state.ann_poison, state.write_pos,
        state.key_tab, state.key_wm,
        state.counters["key_claim_drops"],
    )


def iquery_trace_ids_by_service(state: StoreState, svc_id, name_lc_id,
                                end_ts, k: int):
    """Index fast path for getTraceIdsByName: an O(depth) bucket read
    (service family, or the (service, span-name) family when a name is
    given) instead of the O(ring) scan. Returns (candidates [3, k],
    complete, entry_count); the host falls back to the scan kernel when
    the bucket wrapped and the result underfills (store.base gating)."""
    c = state.config
    lay, _, _ = c.cand_layout
    if name_lc_id is not None and name_lc_id >= 0:
        fam = lay[StoreConfig.CAND_NAME]
        return _iq_verify_impl(
            state.cand_idx, state.cand_pos, state.cand_wm,
            state.row_gid, state.indexable, state.trace_id, state.ts_last,
            c.capacity, fam, min(k, fam[3]),
            (jnp.int32(svc_id), jnp.int32(name_lc_id)), end_ts,
            state.key_tab, state.key_wm, state.write_pos,
            state.counters["key_claim_drops"],
        )
    fam = lay[StoreConfig.CAND_SVC]
    return _iq_service_impl(
        state.cand_idx, state.cand_pos, state.cand_wm,
        state.row_gid, state.indexable, state.trace_id, state.ts_last,
        c.capacity, fam, min(k, fam[3]), svc_id, end_ts,
    )


def iquery_trace_ids_by_annotation(state: StoreState, svc_id,
                                   ann_value_id, bann_key_id,
                                   bann_value_id, bann_value_id2,
                                   end_ts, k: int):
    """Index fast path for the annotation query (AnnotationsIndex role).
    Same contract as iquery_trace_ids_by_service."""
    c = state.config
    lay, _, _ = c.cand_layout
    if ann_value_id is not None and ann_value_id >= 0:
        fam = lay[StoreConfig.CAND_ANN]
        return _iq_verify_impl(
            state.cand_idx, state.cand_pos, state.cand_wm,
            state.row_gid, state.indexable, state.trace_id, state.ts_last,
            c.capacity, fam, min(k, fam[3]),
            (jnp.int32(svc_id), jnp.int32(ann_value_id)), end_ts,
            state.key_tab, state.key_wm, state.write_pos,
            state.counters["key_claim_drops"], state.ann_poison,
        )
    if bann_value_id is None or bann_value_id < 0:
        bann_value_id = -1
    if bann_value_id2 is None or bann_value_id2 < 0:
        bann_value_id2 = -1
    # A value may be dictionary-keyed in only one of its str/bytes
    # forms: any non-negative id makes this a VALUED query.
    if bann_value_id < 0 and bann_value_id2 >= 0:
        bann_value_id = bann_value_id2
    if bann_value_id >= 0 and bann_value_id2 < 0:
        bann_value_id2 = bann_value_id
    fam = lay[StoreConfig.CAND_BANN]
    if bann_value_id < 0:
        # Key-only query: the sentinel-keyed buckets.
        return _iq_verify_impl(
            state.cand_idx, state.cand_pos, state.cand_wm,
            state.row_gid, state.indexable, state.trace_id, state.ts_last,
            c.capacity, fam, min(k, fam[3]),
            (jnp.int32(svc_id), jnp.int32(bann_key_id), jnp.int32(-1)),
            end_ts, state.key_tab, state.key_wm, state.write_pos,
            state.counters["key_claim_drops"], state.ann_poison,
        )
    # The two-bucket probe's candidate window is 2*depth rows; clamping
    # k to depth would truncate valid candidates of never-wrapped
    # buckets and let the host's underfull-equals-complete gate trust a
    # silently cut window (caught by the 3-store oracle parity drive).
    return _iq_verify2_impl(
        state.cand_idx, state.cand_pos, state.cand_wm,
        state.row_gid, state.indexable, state.trace_id, state.ts_last,
        c.capacity, fam, min(k, 2 * fam[3]),
        (jnp.int32(svc_id), jnp.int32(bann_key_id),
         jnp.int32(bann_value_id)),
        (jnp.int32(svc_id), jnp.int32(bann_key_id),
         jnp.int32(bann_value_id2)),
        end_ts, state.key_tab, state.key_wm, state.write_pos,
        state.counters["key_claim_drops"], state.ann_poison,
    )


@read_program(static_argnums=(8, 9))
def _iq_durations_impl(entries, pos, wm, trace_id, row_gid, ts_first,
                       ts_last, write_pos, capacity: int, layout,
                       sorted_qids):
    b_base, s_base, n_b, depth = layout
    nq = sorted_qids.shape[0]
    lb = _bucket_of(_mixb([sorted_qids]), n_b)
    qb = jnp.int32(b_base) + lb
    rows = (jnp.int32(s_base) + lb[:, None] * depth
            + jnp.arange(depth, dtype=jnp.int32)[None, :])
    # Unified arena rows are (gid, verify, ts) triples; only the gid
    # column's two word planes are read.
    gid = _arena_col(entries, rows, 0)
    slot = jnp.clip((gid % capacity).astype(jnp.int32), 0, capacity - 1)
    live = (gid >= 0) & (row_gid[slot] == gid)
    match = live & (trace_id[slot] == sorted_qids[:, None])
    tf = ts_first[slot]
    tl = ts_last[slot]
    has_ts = match & (tf >= 0)
    firsts = jnp.where(has_ts, tf, I64_MAX).min(axis=1)
    lasts = jnp.where(match & (tl >= 0), tl, I64_MIN).max(axis=1)
    gate = (pos[qb] <= depth) | (wm[qb] < write_pos - capacity)
    mat = jnp.stack([
        match.any(axis=1).astype(jnp.int64),
        has_ts.any(axis=1).astype(jnp.int64),
        firsts, lasts,
    ])
    return mat, gate.all()


def iquery_durations(state: StoreState, sorted_qids):
    """Trace-membership fast path for getTracesDuration/tracesExist:
    candidate rows come from the queried traces' gid buckets (nq*depth
    rows) instead of a 4-scatter pass over the full span ring. Returns
    (mat [4, nq] — same layout as query_durations — , exact) where
    ``exact`` requires every queried bucket to pass the displaced-gid
    gate; the host falls back to the scan kernel otherwise."""
    c = state.config
    tlay, _, _ = c.trace_layout
    return _iq_durations_impl(
        state.cand_idx, state.cand_pos, state.cand_wm,
        state.trace_id, state.row_gid, state.ts_first, state.ts_last,
        state.write_pos, c.capacity, tlay[StoreConfig.TR_SPAN],
        sorted_qids,
    )


@read_program(static_argnums=(10,))
def _iq_gather_impl(
    tr_entries, tr_pos, tr_wm,
    span_cols, ann_cols, bann_cols, sorted_qids,
    write_pos, ann_write_pos, bann_write_pos,
    statics,
):
    (capacity, ann_capacity, bann_capacity, lay_s, lay_a, lay_b,
     k_spans, k_anns, k_banns) = statics
    trace_id = span_cols[0]
    row_gid = span_cols[-1]
    ann_gid = ann_cols[0]
    bann_gid = bann_cols[0]
    nq = sorted_qids.shape[0]
    lb = _bucket_of(_mixb([sorted_qids]), lay_s[2])

    def family(layout, ring_wp, ring_cap):
        b_base, s_base, _, depth = layout
        qb = jnp.int32(b_base) + lb
        rows = (jnp.int32(s_base) + lb[:, None] * depth
                + jnp.arange(depth, dtype=jnp.int32)[None, :])
        gid = _arena_col(tr_entries, rows, 0)
        gate = (tr_pos[qb] <= depth) | (tr_wm[qb] < ring_wp - ring_cap)
        return gid, gate.all()

    # Span rows: direct liveness + trace match.
    s_gid, gate_s = family(lay_s, write_pos, capacity)
    s_slot = jnp.clip((s_gid % capacity).astype(jnp.int32), 0,
                      capacity - 1)
    s_ok = ((s_gid >= 0) & (row_gid[s_slot] == s_gid)
            & (trace_id[s_slot] == sorted_qids[:, None]))
    count_s = s_ok.sum(dtype=jnp.int64)
    key_s = jnp.where(s_ok, I64_MAX - s_gid, jnp.int64(-1)).reshape(-1)
    vals_s, sel_s = jax.lax.top_k(key_s, k_spans)  # oldest gid first
    sslot = s_slot.reshape(-1)[sel_s]
    span_mat = jnp.stack([c[sslot].astype(jnp.int64) for c in span_cols])
    span_mat = jnp.where((vals_s >= 0)[None, :], span_mat, -1)

    def ragged(layout, ring_wp, ring_cap, owner_col, cols, k):
        """Annotation/binary rows: entry validity = the ring slot still
        holds this position (overwrite order) + owning span live and in
        the queried set."""
        gid, gate = family(layout, ring_wp, ring_cap)
        slot = jnp.clip((gid % ring_cap).astype(jnp.int32), 0,
                        ring_cap - 1)
        fresh = (gid >= 0) & (gid >= ring_wp - ring_cap)
        owner = owner_col[slot]
        oslot = jnp.clip((owner % capacity).astype(jnp.int32), 0,
                         capacity - 1)
        ok = (fresh & (owner >= 0) & (row_gid[oslot] == owner)
              & (trace_id[oslot] == sorted_qids[:, None]))
        count = ok.sum(dtype=jnp.int64)
        key = jnp.where(ok, I64_MAX - gid, jnp.int64(-1)).reshape(-1)
        vals, sel = jax.lax.top_k(key, k)
        rslot = slot.reshape(-1)[sel]
        mat = jnp.stack([c[rslot].astype(jnp.int64) for c in cols])
        return count, jnp.where((vals >= 0)[None, :], mat, -1), gate

    count_a, ann_mat, gate_a = ragged(
        lay_a, ann_write_pos, ann_capacity, ann_gid, ann_cols, k_anns,
    )
    count_b, bann_mat, gate_b = ragged(
        lay_b, bann_write_pos, bann_capacity, bann_gid, bann_cols,
        k_banns,
    )
    counts = jnp.stack([count_s, count_a, count_b])
    return counts, span_mat, ann_mat, bann_mat, gate_s & gate_a & gate_b


def iquery_gather_trace_rows(
    state: StoreState, sorted_qids, k_spans: int, k_anns: int,
    k_banns: int,
):
    """Trace-membership fast path for whole-trace materialization: the
    same four-array contract as gather_trace_rows plus an ``exact``
    flag; candidates come from the queried traces' gid buckets instead
    of full-ring scans. The host falls back to gather_trace_rows when
    any queried bucket fails the displaced-gid gate (hot traces beyond
    the per-family depths, or shuffled arrival near the gate)."""
    c = state.config
    tlay, _, _ = c.trace_layout
    statics = (c.capacity, c.ann_capacity, c.bann_capacity,
               tlay[StoreConfig.TR_SPAN], tlay[StoreConfig.TR_ANN],
               tlay[StoreConfig.TR_BANN], k_spans, k_anns, k_banns)
    return _iq_gather_impl(
        state.cand_idx, state.cand_pos, state.cand_wm,
        tuple(getattr(state, col) for col in SPAN_MAT_COLS),
        tuple(getattr(state, col) for col in ANN_MAT_COLS),
        tuple(getattr(state, col) for col in BANN_MAT_COLS),
        sorted_qids,
        state.write_pos, state.ann_write_pos, state.bann_write_pos,
        statics,
    )


# A launch's rows as the host recorded them (TpuSpanStore's launch map):
# span gids [wp0, next launch's wp0), annotation ring positions [a0, a1),
# binary ring positions [b0, b1), and whether each side's rows were
# written in span order (owner gids ascending over the positions).
LAUNCH_COLS = ("wp0", "a0", "a1", "b0", "b1", "ordered")


@read_program(static_argnums=(12,))
def _iq_launch_gather_impl(
    tr_entries, tr_pos, tr_wm,
    span_cols, ann_cols, bann_cols, sorted_qids,
    write_pos, ann_write_pos, bann_write_pos,
    launches, floor,
    statics,
):
    """Whole traces through the trace-membership SPAN family alone: the
    bucket gives a queried trace's span rows (exact under its
    displaced-gid gate), and each span's annotation and binary rows are
    found where the launch that wrote the span put them. A launch
    writes its spans' side rows as one run of ring positions, in span
    order, so within [a0, a1) the owner gids ascend: a binary search
    finds the first row of a span, and its rows follow. No side-row
    bucket is read, so a trace too large for one (a 21-span trace
    fills the 128-row annotation bucket) stays on the index.

    With ``scan`` (a static) the span rows come from a scan of the
    span ring instead of the bucket, for a trace whose span bucket
    fails its gate: elementwise over the span ring, where the
    whole-ring gather (_gather_impl) reads every side row's owner.

    Returns (counts [3], span_mat, ann_mat, bann_mat, flags [4]):
    flags = (span buckets exact, every found span known to the launch
    map and its launch ordered, no span with more annotation rows than
    ``per_a``, none with more binary rows than ``per_b``)."""
    (capacity, ann_capacity, bann_capacity, lay_s, k_spans, k_anns,
     k_banns, per_a, per_b, steps_a, steps_b, scan) = statics
    trace_id = span_cols[0]
    row_gid = span_cols[-1]
    if scan:
        nq = sorted_qids.shape[0]
        at = jnp.clip(jnp.searchsorted(sorted_qids, trace_id), 0, nq - 1)
        hit = (row_gid >= 0) & (sorted_qids[at] == trace_id)
        count_s = hit.sum(dtype=jnp.int64)
        vals_s, sslot = jax.lax.top_k(
            jnp.where(hit, I64_MAX - row_gid, jnp.int64(-1)), k_spans)
        s_ok = vals_s >= 0
        s_gid = jnp.where(s_ok, row_gid[sslot], -1)
        gate_s = jnp.bool_(True)
    else:
        b_base, s_base, _, depth = lay_s
        lb = _bucket_of(_mixb([sorted_qids]), lay_s[2])
        qb = jnp.int32(b_base) + lb
        rows = (jnp.int32(s_base) + lb[:, None] * depth
                + jnp.arange(depth, dtype=jnp.int32)[None, :])
        s_gid = _arena_col(tr_entries, rows, 0)
        gate_s = ((tr_pos[qb] <= depth)
                  | (tr_wm[qb] < write_pos - capacity)).all()
        s_slot = jnp.clip((s_gid % capacity).astype(jnp.int32), 0,
                          capacity - 1)
        s_ok = ((s_gid >= 0) & (row_gid[s_slot] == s_gid)
                & (trace_id[s_slot] == sorted_qids[:, None]))
        count_s = s_ok.sum(dtype=jnp.int64)
        key_s = jnp.where(s_ok, I64_MAX - s_gid, jnp.int64(-1)).reshape(-1)
        vals_s, sel_s = jax.lax.top_k(key_s, k_spans)  # oldest gid first
        sslot = s_slot.reshape(-1)[sel_s]
    span_mat = jnp.stack([c[sslot].astype(jnp.int64) for c in span_cols])
    span_mat = jnp.where((vals_s >= 0)[None, :], span_mat, -1)

    wp0, a0, a1, b0, b1, ordered = launches
    z = jnp.searchsorted(wp0, s_gid, side="right") - 1
    zc = jnp.clip(z, 0, wp0.shape[0] - 1)
    # A span before the first launch the map keeps, but at or after
    # its floor, has no side rows left in the rings (the map drops a
    # launch only once both side rings have overwritten its rows).
    in_map = s_ok & (z >= 0)
    known = ~s_ok | (s_gid >= floor)
    known &= ~in_map | ordered[zc]

    def runs(lo0, hi0, ring_wp, ring_cap, cols, per, steps, k):
        owner = cols[0]

        def owner_at(pos):
            return owner[jnp.clip((pos % ring_cap).astype(jnp.int32), 0,
                                  ring_cap - 1)]

        # the launch's rows still resident: the ring may have lapped
        # into the front of the run, never into its middle
        lo = jnp.where(in_map, jnp.maximum(lo0[zc], ring_wp - ring_cap), 0)
        hi = jnp.where(in_map, jnp.maximum(hi0[zc], lo), 0)

        def halve(_, lh):
            lo_, hi_ = lh
            mid = (lo_ + hi_) // 2
            right = (mid < hi_) & (owner_at(mid) < s_gid)
            return (jnp.where(right, mid + 1, lo_),
                    jnp.where(right, hi_, mid))

        first, _ = jax.lax.fori_loop(0, steps, halve, (lo, hi))
        pos = first[..., None] + jnp.arange(per, dtype=jnp.int64)
        ok = (in_map[..., None] & (pos < hi[..., None])
              & (owner_at(pos) == s_gid[..., None]))
        after = first + per
        over = (in_map & (after < hi) & (owner_at(after) == s_gid)).any()
        count = ok.sum(dtype=jnp.int64)
        key = jnp.where(ok, I64_MAX - pos, jnp.int64(-1)).reshape(-1)
        vals, sel = jax.lax.top_k(key, min(k, key.shape[0]))
        rslot = jnp.clip((pos.reshape(-1)[sel] % ring_cap).astype(
            jnp.int32), 0, ring_cap - 1)
        mat = jnp.stack([c[rslot].astype(jnp.int64) for c in cols])
        return count, jnp.where((vals >= 0)[None, :], mat, -1), over

    count_a, ann_mat, over_a = runs(a0, a1, ann_write_pos, ann_capacity,
                                    ann_cols, per_a, steps_a, k_anns)
    count_b, bann_mat, over_b = runs(b0, b1, bann_write_pos,
                                     bann_capacity, bann_cols, per_b,
                                     steps_b, k_banns)
    counts = jnp.stack([count_s, count_a, count_b])
    flags = jnp.stack([gate_s, known.all(), ~over_a, ~over_b])
    return counts, span_mat, ann_mat, bann_mat, flags


def iquery_launch_gather_rows(state: StoreState, sorted_qids, launches,
                              floor: int, k_spans: int, k_anns: int,
                              k_banns: int, per_a: int, per_b: int,
                              scan: bool = False):
    """Host wrapper for _iq_launch_gather_impl. ``launches`` is the
    tuple of LAUNCH_COLS numpy arrays (wp0 ascending, padded with
    I64_MAX rows), ``floor`` the first span gid the map covers. The
    binary searches take as many halvings as a run as long as the ring
    needs (a run is never longer), so no launch recompiles them."""
    c = state.config
    tlay, _, _ = c.trace_layout
    statics = (c.capacity, c.ann_capacity, c.bann_capacity,
               tlay[StoreConfig.TR_SPAN], k_spans, k_anns, k_banns,
               per_a, per_b, c.ann_capacity.bit_length(),
               c.bann_capacity.bit_length(), scan)
    return _iq_launch_gather_impl(
        state.cand_idx, state.cand_pos, state.cand_wm,
        tuple(getattr(state, col) for col in SPAN_MAT_COLS),
        tuple(getattr(state, col) for col in ANN_MAT_COLS),
        tuple(getattr(state, col) for col in BANN_MAT_COLS),
        sorted_qids,
        state.write_pos, state.ann_write_pos, state.bann_write_pos,
        tuple(jnp.asarray(a) for a in launches), jnp.int64(floor),
        statics,
    )


@read_program()
def _q_durations_impl(trace_id, row_gid, ts_first, ts_last, sorted_qids):
    nq = sorted_qids.shape[0]
    live = row_gid >= 0
    pos = jnp.searchsorted(sorted_qids, trace_id)
    pos_c = jnp.clip(pos, 0, nq - 1)
    match = live & (sorted_qids[pos_c] == trace_id)
    seg = jnp.where(match, pos_c, nq)
    has_ts = match & (ts_first >= 0)
    # Ring-sized i64/bool scatter-reductions serialize on this backend
    # (~100 ns/row — 4.2M rows cost ~420 ms EACH; this kernel was the
    # whole q_durations p99); the exact plane wars and i32 maxes
    # vectorize.
    min_first = _war_min64(
        jnp.full(nq + 1, I64_MAX, jnp.int64), seg, ts_first, has_ts
    )[:nq]
    max_last = _war_max64(
        jnp.full(nq + 1, I64_MIN, jnp.int64), seg, ts_last, has_ts
    )[:nq]
    found = jnp.zeros(nq + 1, jnp.int32).at[seg].max(
        has_ts.astype(jnp.int32), mode="drop")[:nq] > 0
    present = jnp.zeros(nq + 1, jnp.int32).at[seg].max(
        match.astype(jnp.int32), mode="drop")[:nq] > 0
    return jnp.stack([
        present.astype(jnp.int64), found.astype(jnp.int64), min_first, max_last
    ])


def query_durations(state: StoreState, sorted_qids):
    """Per queried trace id, ONE stacked [4, nq] i64 array:
    (present, found, min first_ts, max last_ts).

    ``present`` = any live row carries the id (traces_exist semantics);
    ``found`` additionally requires a timestamp (getTracesDuration,
    Index.scala:26: duration = max(last) - min(first)). ``sorted_qids``
    must be ascending (host sorts). The jitted impl takes only the four
    columns it reads (see query_trace_ids_by_service).
    """
    return _q_durations_impl(
        state.trace_id, state.row_gid, state.ts_first, state.ts_last,
        sorted_qids,
    )


# Column order of the stacked matrices gather_trace_rows returns; the
# host decodes by these names (row_gid last in SPAN_MAT_COLS).
SPAN_MAT_COLS = (
    "trace_id", "span_id", "parent_id", "name_id", "service_id",
    "ts_cs", "ts_cr", "ts_sr", "ts_ss", "ts_first", "ts_last",
    "duration", "flags", "row_gid",
)
ANN_MAT_COLS = ("ann_gid", "ann_ts", "ann_value_id", "ann_service_id",
                "ann_endpoint_id")
BANN_MAT_COLS = ("bann_gid", "bann_key_id", "bann_value_id", "bann_type",
                 "bann_service_id", "bann_endpoint_id")


@read_program(static_argnums=(7, 8, 9, 10, 11, 12, 13))
def _gather_impl(
    span_cols, ann_cols, bann_cols, sorted_qids,
    write_pos, ann_write_pos, bann_write_pos,
    capacity: int, ann_capacity: int, bann_capacity: int,
    k_spans: int, k_anns: int, k_banns: int,
    paged: bool = False,
):
    trace_id = span_cols[0]
    row_gid = span_cols[-1]
    ann_gid = ann_cols[0]
    bann_gid = bann_cols[0]

    nq = sorted_qids.shape[0]
    live = row_gid >= 0
    pos = jnp.clip(jnp.searchsorted(sorted_qids, trace_id), 0, nq - 1)
    span_in = live & (sorted_qids[pos] == trace_id)

    a_slot, a_live = _span_slot(ann_gid, row_gid, capacity)
    ann_in = a_live & span_in[a_slot]
    b_slot, b_live = _span_slot(bann_gid, row_gid, capacity)
    bann_in = b_live & span_in[b_slot]

    def oldest_k(mask, wp, cap, k):
        """Indices of the k oldest matching ring slots (insertion
        order). top_k on an i32 freshness key — a full i64 ring argsort
        compiles for ~a minute per shape at 2^22 on TPU; top_k is
        seconds, and k rows are all a trace read needs."""
        head = (wp % cap).astype(jnp.int32)
        slots = jnp.arange(cap, dtype=jnp.int32)
        age = (slots - head) % jnp.int32(cap)
        key = jnp.where(mask, jnp.int32(cap) - age, 0)
        _, sel = jax.lax.top_k(key, k)
        return sel

    if paged:
        # Paged layout: slot position is a page assignment, not an
        # arrival rank — insertion order lives in the epoch-encoded
        # gid, so span rows sort by the i64 gid key directly (the
        # _iq_gather_impl idiom).
        skey = jnp.where(span_in, I64_MAX - row_gid, jnp.int64(-1))
        _, sel = jax.lax.top_k(skey, k_spans)
    else:
        sel = oldest_k(span_in, write_pos, capacity, k_spans)
    span_mat = jnp.stack([c[sel].astype(jnp.int64) for c in span_cols])

    a_sel = oldest_k(ann_in, ann_write_pos, ann_capacity, k_anns)
    ann_mat = jnp.stack([c[a_sel].astype(jnp.int64) for c in ann_cols])
    # Mask stale selections (when fewer than k_anns match).
    ann_mat = jnp.where(ann_in[a_sel][None, :], ann_mat, -1)

    b_sel = oldest_k(bann_in, bann_write_pos, bann_capacity, k_banns)
    bann_mat = jnp.stack([c[b_sel].astype(jnp.int64) for c in bann_cols])
    bann_mat = jnp.where(bann_in[b_sel][None, :], bann_mat, -1)

    counts = jnp.stack([
        span_in.sum(dtype=jnp.int64),
        ann_in.sum(dtype=jnp.int64),
        bann_in.sum(dtype=jnp.int64),
    ])
    return counts, span_mat, ann_mat, bann_mat


@partial(jax.jit, static_argnums=(8, 9, 10, 11, 12, 13, 14))
def _capture_impl(
    span_cols, ann_cols, bann_cols, lo, hi,
    write_pos, ann_write_pos, bann_write_pos,
    capacity: int, ann_capacity: int, bann_capacity: int,
    k_spans: int, k_anns: int, k_banns: int,
    paged: bool = False,
):
    row_gid = span_cols[-1]
    ann_gid = ann_cols[0]
    bann_gid = bann_cols[0]
    span_in = (row_gid >= lo) & (row_gid < hi)
    ann_in = (ann_gid >= lo) & (ann_gid < hi)
    bann_in = (bann_gid >= lo) & (bann_gid < hi)

    def oldest_k(mask, wp, cap, k):
        head = (wp % cap).astype(jnp.int32)
        slots = jnp.arange(cap, dtype=jnp.int32)
        age = (slots - head) % jnp.int32(cap)
        key = jnp.where(mask, jnp.int32(cap) - age, 0)
        _, sel = jax.lax.top_k(key, k)
        return sel

    if paged:
        # Page-granular capture: order the page's spans by gid (their
        # insertion order) so the sealed segment is bitwise-stable
        # regardless of slot placement inside the page.
        skey = jnp.where(span_in, I64_MAX - row_gid, jnp.int64(-1))
        _, sel = jax.lax.top_k(skey, k_spans)
    else:
        sel = oldest_k(span_in, write_pos, capacity, k_spans)
    span_mat = jnp.stack([c[sel].astype(jnp.int64) for c in span_cols])
    a_sel = oldest_k(ann_in, ann_write_pos, ann_capacity, k_anns)
    ann_mat = jnp.stack([c[a_sel].astype(jnp.int64) for c in ann_cols])
    ann_mat = jnp.where(ann_in[a_sel][None, :], ann_mat, -1)
    b_sel = oldest_k(bann_in, bann_write_pos, bann_capacity, k_banns)
    bann_mat = jnp.stack([c[b_sel].astype(jnp.int64) for c in bann_cols])
    bann_mat = jnp.where(bann_in[b_sel][None, :], bann_mat, -1)
    counts = jnp.stack([
        span_in.sum(dtype=jnp.int64),
        ann_in.sum(dtype=jnp.int64),
        bann_in.sum(dtype=jnp.int64),
    ])
    return counts, span_mat, ann_mat, bann_mat


def capture_eviction_rows(
    state: StoreState, lo: int, hi: int,
    k_spans: int, k_anns: int, k_banns: int,
):
    """Eviction capture: pull every ring row (span + annotation +
    binary) whose SPAN gid falls in [lo, hi), compacted to the front in
    insertion order — the cold tier's batched host pull. Same stacked
    matrix shape as gather_trace_rows so the host decode path is
    shared. A PURE READ: the fused ingest step's lowering is untouched
    (bench_smoke's census gate holds with capture wired); the
    cold tier pays one extra read-only launch + one D2H per capture
    window on the existing archive cadence.

    The caller triggers the pull BEFORE any of the three rings can
    overwrite a row in the window (TpuSpanStore._maybe_capture tracks
    all three write cursors), so every captured span is complete —
    including side-table rows a faster-lapping annotation ring would
    have dropped first."""
    c = state.config
    return _capture_impl(
        tuple(getattr(state, col) for col in SPAN_MAT_COLS),
        tuple(getattr(state, col) for col in ANN_MAT_COLS),
        tuple(getattr(state, col) for col in BANN_MAT_COLS),
        jnp.int64(lo), jnp.int64(hi),
        state.write_pos, state.ann_write_pos, state.bann_write_pos,
        c.capacity, c.ann_capacity, c.bann_capacity,
        k_spans, k_anns, k_banns, c.paged_enabled,
    )


def gather_trace_rows(
    state: StoreState, sorted_qids, k_spans: int, k_anns: int, k_banns: int,
):
    """Device-side gather of every ring row belonging to ``sorted_qids``,
    compacted to the front in insertion order, returned as THREE stacked
    i64 matrices plus a [3] count vector — four arrays total, because
    host transfers pay a large per-array latency and the naive path
    (pull whole ring columns, mask on host) moves the entire store
    to the host per trace read.

    Span rows sort by global row id (insertion order); annotation rows
    by ring age so per-span annotation insert order survives. Rows
    beyond the static ``k_*`` caps are dropped — counts tell the caller
    to escalate caps and retry (the maxTraceCols-style guard,
    CassieSpanStore.scala:50). The jitted impl takes only the columns
    it gathers (per-argument dispatch overhead).
    """
    c = state.config
    return _gather_impl(
        tuple(getattr(state, col) for col in SPAN_MAT_COLS),
        tuple(getattr(state, col) for col in ANN_MAT_COLS),
        tuple(getattr(state, col) for col in BANN_MAT_COLS),
        sorted_qids,
        state.write_pos, state.ann_write_pos, state.bann_write_pos,
        c.capacity, c.ann_capacity, c.bann_capacity,
        k_spans, k_anns, k_banns, c.paged_enabled,
    )


@read_program(static_argnums=(8, 9, 10, 11, 12, 13, 14))
def _paged_gather_impl(
    span_cols, ann_cols, bann_cols, sorted_qids, pages, epochs,
    ann_write_pos, bann_write_pos,
    capacity: int, page_rows: int, ann_capacity: int, bann_capacity: int,
    k_spans: int, k_anns: int, k_banns: int,
):
    """Paged trace assembly (r19): gather span rows from an explicit
    page list instead of scanning the whole ring.

    ``pages`` [K] i32 / ``epochs`` [K] i64 come from the host page
    table (store/paged.PagePlanner.chains_for) — every page any queried
    trace has rows in, -1-padded. Validity is per ROW, not per page:
    the expected gid of slot (p, j) is epoch*capacity + p*R + j, and a
    gathered row counts only when its live row_gid equals that AND its
    trace_id is one of ``sorted_qids`` (pages are shared by small
    traces, so a page may carry rows of non-queried traces). The
    output span_mat is masked to -1 on dead rows.

    Annotation/binary rows stay on their FIFO rings (no pages), so
    their membership is the _gather_impl scan unchanged.
    """
    trace_col = span_cols[0]
    row_gid = span_cols[-1]
    ann_gid = ann_cols[0]
    bann_gid = bann_cols[0]
    nq = sorted_qids.shape[0]
    R = page_rows
    n_pages = capacity // R
    pg = jnp.clip(pages, 0, n_pages - 1)
    offs = jnp.arange(R, dtype=jnp.int32)[None, :]
    page_slots = pg[:, None] * R + offs                      # [K, R]
    expected = jnp.where(
        pages[:, None] >= 0,
        epochs[:, None] * jnp.int64(capacity)
        + page_slots.astype(jnp.int64),
        jnp.int64(-1),
    ).reshape(-1)                                            # [K*R]
    slot = page_slots.reshape(-1)
    rows = jnp.stack([col[slot].astype(jnp.int64) for col in span_cols])
    g_tid = rows[0]
    g_gid = rows[-1]
    g_live = (expected >= 0) & (g_gid == expected)
    g_pos = jnp.clip(jnp.searchsorted(sorted_qids, g_tid), 0, nq - 1)
    ok = g_live & (sorted_qids[g_pos] == g_tid)
    skey = jnp.where(ok, I64_MAX - expected, jnp.int64(-1))
    _, sel = jax.lax.top_k(skey, k_spans)
    span_mat = jnp.where(ok[sel][None, :], rows[:, sel], -1)

    # Ann/bann membership: owning-span liveness over the slot array,
    # exactly _gather_impl's scan (annotation rows are ringed, not
    # paged; ring age IS their insertion order in both layouts).
    live_r = row_gid >= 0
    pos_r = jnp.clip(jnp.searchsorted(sorted_qids, trace_col), 0, nq - 1)
    span_in = live_r & (sorted_qids[pos_r] == trace_col)
    a_slot, a_live = _span_slot(ann_gid, row_gid, capacity)
    ann_in = a_live & span_in[a_slot]
    b_slot, b_live = _span_slot(bann_gid, row_gid, capacity)
    bann_in = b_live & span_in[b_slot]

    def oldest_k(mask, wp, cap, k):
        head = (wp % cap).astype(jnp.int32)
        slots = jnp.arange(cap, dtype=jnp.int32)
        age = (slots - head) % jnp.int32(cap)
        key = jnp.where(mask, jnp.int32(cap) - age, 0)
        _, sel = jax.lax.top_k(key, k)
        return sel

    a_sel = oldest_k(ann_in, ann_write_pos, ann_capacity, k_anns)
    ann_mat = jnp.stack([c[a_sel].astype(jnp.int64) for c in ann_cols])
    ann_mat = jnp.where(ann_in[a_sel][None, :], ann_mat, -1)
    b_sel = oldest_k(bann_in, bann_write_pos, bann_capacity, k_banns)
    bann_mat = jnp.stack([c[b_sel].astype(jnp.int64) for c in bann_cols])
    bann_mat = jnp.where(bann_in[b_sel][None, :], bann_mat, -1)
    counts = jnp.stack([
        ok.sum(dtype=jnp.int64),
        ann_in.sum(dtype=jnp.int64),
        bann_in.sum(dtype=jnp.int64),
    ])
    return counts, span_mat, ann_mat, bann_mat


def gather_paged_trace_rows(
    state: StoreState, sorted_qids, pages, epochs,
    k_spans: int, k_anns: int, k_banns: int,
):
    """Paged twin of gather_trace_rows: span rows come from the page
    list, annotation rows from the ring scan. Same four-array contract,
    so the host decode and escalation paths are shared."""
    c = state.config
    return _paged_gather_impl(
        tuple(getattr(state, col) for col in SPAN_MAT_COLS),
        tuple(getattr(state, col) for col in ANN_MAT_COLS),
        tuple(getattr(state, col) for col in BANN_MAT_COLS),
        sorted_qids,
        jnp.asarray(pages, jnp.int32), jnp.asarray(epochs, jnp.int64),
        state.ann_write_pos, state.bann_write_pos,
        c.capacity, c.page_rows, c.ann_capacity, c.bann_capacity,
        k_spans, k_anns, k_banns,
    )



# ---------------------------------------------------------------------------
# Pipelined-ingest staging + jit-compile accounting
# ---------------------------------------------------------------------------


def stage_batch(db: DeviceBatch) -> DeviceBatch:
    """H2D staging of one padded batch: ``jax.device_put`` of the whole
    pytree, returned immediately (the transfer proceeds asynchronously)
    so the pipeline's stage thread can overlap the copy with the
    previous fused step's device compute. Placement is left uncommitted
    on the default device. NOTE: staged (device-resident) arguments
    key DIFFERENT jit cache rows than host numpy arguments on this jax
    version, so the first pipelined drive at a given pad bucket
    compiles its own entry even if the serial path warmed that shape —
    thereafter steady state is zero recompiles (gated via
    ``compile_count`` in bench_smoke's pipeline phase, warmed through
    the pipeline)."""
    return jax.device_put(db)


# The write-path jits whose compile-cache growth the ingest pipeline
# gates on: steady-state pipelined ingest must hit only pow2 pad
# buckets that warmup already compiled (zero recompiles). Query jits
# are deliberately excluded — their cache is keyed by request shapes
# the write path does not control.
_INGEST_JITS = (
    ingest_step, ingest_steps, dep_sweep, dep_close_bucket,
    rebuild_span_tab, _capture_impl,
)

# The resident query programs (query/engine.py's index tier): the
# batched multi-probe kernel plus every read kernel the engine's
# cached paths dispatch. A warmed steady state must hold their cache
# sizes flat — bench_smoke's query phase gates query_compile_count()
# deltas at ZERO.
_QUERY_JITS = (
    _iq_multi_impl, _iq_service_impl, _iq_verify_impl,
    _iq_verify2_impl, _iq_durations_impl, _iq_gather_impl,
    _iq_launch_gather_impl, _q_by_service_impl, _q_by_annotation_impl,
    _q_durations_impl,
    _gather_impl, _paged_gather_impl, counter_block,
)


def compile_count() -> int:
    """Total compiled variants (jit cache entries) across the ingest /
    staging / capture jits — a process-wide monotone recompile counter.
    Surfaced through ``TpuSpanStore.counters()`` -> /metrics as
    ``jit_compiles``; bench_smoke's pipeline phase asserts its delta is
    ZERO across a warmed pipelined drive."""
    total = 0
    for fn in _INGEST_JITS:
        try:
            total += fn._cache_size()
        except Exception:  # pragma: no cover; graftlint: disable=swallowed-exception
            pass  # best-effort probe of a private jax API
    return total


def query_compile_count() -> int:
    """Compiled variants across the resident query kernels
    (_QUERY_JITS) — the query-path twin of ``compile_count``. A
    resident executor serving steady traffic must hold this flat:
    every dispatch hits an already-compiled program (pow2 probe
    padding bounds the shape space). Surfaced through
    ``TpuSpanStore.counters()`` → /metrics as ``query_jit_compiles``."""
    total = 0
    for fn in _QUERY_JITS:
        try:
            total += fn._cache_size()
        except Exception:  # pragma: no cover; graftlint: disable=swallowed-exception
            pass  # best-effort probe of a private jax API
    return total
