"""Checkpoint/restore for the device store (durability).

The reference's durability IS its storage backend (Cassandra TTLs,
CassieSpanStore.scala:47-48); the TPU store's state lives in HBM, so
durability is an explicit snapshot: device state pytree → host npz +
dictionaries/TTL map → json. Restore rebuilds an equivalent
TpuSpanStore (SURVEY.md §5 checkpoint/resume).

Snapshots are atomic (write to a temp dir, rename) so a crash mid-save
leaves the previous checkpoint intact.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Optional

import jax
import numpy as np

from zipkin_tpu.columnar.dictionary import DictionarySet
from zipkin_tpu.columnar.encode import SpanCodec
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.testing.crash import kill_point

_STATE_FILE = "state.npz"
_META_FILE = "meta.json"
_PINS_FILE = "pins.pkl"
# Bump when the StoreState schema changes in a way load() must adapt to.
# 7: span_tab empty sentinel 0 → _TAB_EMPTY (deterministic min-insert);
#    ann_poison middle-host trust array added.
# 8: key_claim_drops counter added — the negative-lookup gate's proof
#    obligation. Snapshots predating it never counted drops, so their
#    restores must keep the gate OFF (drops forced >= 1).
# 9: key_tab stores i32 fingerprints instead of exact i64 key words
#    (the i64 claim war serialized on TPU; see device._index_write).
#    Older tables are tombstoned on restore and the drop floor above
#    extends to revision 8 snapshots.
# 10: trace-membership depths doubled (32/64/32 -> 64/128/64, 4x-ring
#    coverage — 2x measurably let Poisson trace-clumping wrap 13-30% of
#    buckets per lap). Older snapshots carry half-size tr_idx arrays,
#    so their trace families restore poisoned (scan serves) instead of
#    silently misaligned.
# 11: the trace-membership families merged into the candidate arena
#    (one [slots, 3] entry array + one cursor/watermark pair for all
#    seven families — tr_idx/tr_pos/tr_wm no longer exist), candidate
#    ts watermarks war coarsely (stored values round UP to 2^20 µs —
#    still upper bounds, so old exact values restore compatibly), and
#    span_tab became [H, 2] i32 bit-planes (bitcast-identical; migrated
#    losslessly below). Pre-11 cand_*/tr_* arrays are dropped: the
#    candidate segment restores permanently untrusted (scan serves, the
#    pre-index treatment) while the trace segment seeds wm = write_pos
#    and self-heals after one ring lap.
# 12: cold-tier archive (store/archive): saving a TieredSpanStore adds
#    meta["archive"] (sketch params + captured-gid watermark + segment
#    manifest) and one immutable blob per segment under ``segments/``;
#    load() rebuilds the TieredSpanStore around the restored device
#    store and re-aligns the capture clocks with one capture_now()
#    flush. Snapshots without the key restore plain stores unchanged,
#    and pre-12 loaders simply ignore the extra files.
# 13: durability (zipkin_tpu.wal): single-device snapshots add
#    meta["clocks"] — the host pacing mirrors (write/capture/sweep/
#    archive clocks, sealed frontier) plus the last-applied WAL
#    sequence — making restore EXACT instead of re-seeded ("just
#    rotated" / capture_now flush), which is what lets WAL replay
#    land a bitwise-identical state; and meta["slab_crc32"] — a CRC32
#    per state leaf, verified on restore (CorruptSlabError) so a
#    rotted slab fails fast instead of feeding garbage into
#    device_put. Pre-13 snapshots restore exactly as before (clocks
#    re-seeded, no CRC check); pre-13 loaders ignore both keys.
# 14: windowed Moments-sketch arena (aggregate/windows.py): four new
#    state leaves — win_epoch / win_counts / win_sums / win_mm, the
#    (service × ring-indexed time bucket) integer cell grid — ride the
#    generic leaf save/restore. Pre-14 snapshots simply lack the keys,
#    so they restore with an EMPTY arena at init defaults (windowed
#    answers cover post-restore ingest only — correct, since the ring
#    retains at most window_seconds × window_buckets anyway); the
#    sketch-mirror cold resync below already re-adopts the window
#    twins with the other aggregates. Pre-14 loaders drop the unknown
#    leaves via the `known` filter.
# 18: paged span layout (store/paged): snapshots of a paged store add
#    meta["paged"] — the host page allocator + per-trace page-table
#    snapshot, including the recent claim-plan memo keyed by WAL seq
#    (the pipelined-save window: units planned ahead of the gathered
#    device frontier replay from recorded claims, not re-planning).
#    The StoreState leaf schema is UNCHANGED — the paged layout reuses
#    the ring arenas with epoch-encoded gids — so pre-18 ring
#    snapshots restore exactly as before (StoreConfig defaults fill
#    layout="ring"), and a paged store restoring a snapshot WITHOUT
#    the key rebuilds its page table from the resident row_gid /
#    trace_id columns (PagePlanner.rebuild; partial pages stay
#    closed). Revisions 15-17 were consumed by the replication /
#    sharded-serving line (sharded clocks, fleet WAL shipping); their
#    snapshots restore through the same revision-tolerant key checks.
# 19: the index arena is saved as the store now holds it: six i32
#    bit-plane leaves ``cand_idx.0`` .. ``cand_idx.5`` ([slots] each,
#    [n_shards, slots] sharded; plane 2c the low word of column c,
#    2c + 1 its high word — device._arena_set) instead of one
#    ``cand_idx`` [slots, 3] i64 leaf. Same bits, other shape: a
#    revision 11-18 snapshot's i64 leaf restores BIT FOR BIT through a
#    host-side numpy view (device.arena_planes), gated on the stored
#    key, not the revision. A pre-19 loader drops the unknown keys
#    but restores cand_pos over an empty arena: do not downgrade
#    across this revision (docs/MIGRATION.md).
_REVISION = 19
_SEGMENTS_DIR = "segments"


class CorruptSlabError(RuntimeError):
    """A checkpoint state slab failed its manifest CRC32 — the
    snapshot is damaged (torn copy, disk rot, or mixed cuts). Restore
    refuses to feed the corrupt leaf to the device; recover from the
    ``.old`` snapshot or an earlier checkpoint plus the WAL."""


def _slab_crc(arr) -> int:
    """CRC32 over a leaf's raw C-order bytes (dtype/shape are pinned
    by the npy header, so content bytes are the integrity surface)."""
    import zlib

    a = np.ascontiguousarray(arr)
    return zlib.crc32(memoryview(a).cast("B"))


def _host_clocks(store) -> Optional[dict]:
    """The single-device store's host pacing clocks, captured under
    the same read lock as the state gather (the mirrors advance inside
    the commit's write-lock hold, so this pair is exact)."""
    if not hasattr(store, "_cap_upto"):
        return None
    # The capture clocks are _cap_lock-guarded, but taking _cap_lock
    # HERE (under the gather's read lock) would invert the canonical
    # _cap_lock(30) -> _rw(40) order — the capture pull holds the
    # capture lock while acquiring the read lock, and a reader-
    # triggered pending sweep is a WRITER, so the inversion is a real
    # deadlock triangle (graftlint lock-order). Instead save() relies
    # on its quiesce protocol: the pipeline is drained, the seal
    # barrier ran under this same read-lock hold, GIL-atomic int reads
    # can't tear, and restore's min(cap_upto, sealed_upto) tolerates
    # the one benign race left (a serial writer stamping clocks before
    # it reaches the write lock).
    return {
        "wp": int(store._wp),
        "awp": int(store._awp),
        "bwp": int(store._bwp),
        "archived": int(store._archived),
        "batches_since_sweep": int(store._batches_since_sweep),
        "cap_upto": int(store._cap_upto),  # graftlint: disable=guarded-by
        "cap_a": int(store._cap_a),  # graftlint: disable=guarded-by
        "cap_b": int(store._cap_b),  # graftlint: disable=guarded-by
        "sealed_upto": int(store._sealed_upto),  # graftlint: disable=guarded-by
        "wal_applied": int(getattr(store, "_wal_applied", 0)),
    }


def _sharded_clocks(store) -> Optional[dict]:
    """The sharded store's host pacing clocks, captured under the same
    read lock as the stacked-state gather. The top-level
    ``wal_applied`` key keeps save()'s WAL-truncation coordination
    identical across store kinds (a ShardedWal truncates by epoch
    sequence exactly as a WriteAheadLog does by record sequence)."""
    inner = getattr(store, "inner", None)
    if inner is None or not hasattr(inner, "_wp_upper"):
        return None
    return {
        "sharded": 1,
        "wp_upper": int(inner._wp_upper),
        "archived_lower": int(inner._archived_lower),
        "batches_since_sweep": int(inner._batches_since_sweep),
        "step_seq": int(getattr(store, "_step_seq", 0)),
        "wal_applied": int(getattr(store, "_wal_applied", 0)),
    }


def _dict_dump(d) -> list:
    # One entry codec shared with the WAL's dictionary deltas
    # (wal/record.py): replay equality-verifies restored entries
    # against delta values, so the two must never diverge.
    from zipkin_tpu.wal.record import dump_value

    return [dump_value(v) for v in d.values()]


def _dict_load(dictionary, values: list) -> None:
    from zipkin_tpu.wal.record import load_value

    for item in values:
        dictionary.encode(load_value(item))


def _leaf_items(name: str, value) -> list:
    """(npz key, leaf) pairs of one StoreState field: a plain array
    under its own name; the ``counters`` dict and the ``cand_idx``
    plane tuple as ``name.<key>`` / ``name.<plane>``."""
    if isinstance(value, dict):
        return [(f"{name}.{k}", v) for k, v in value.items()]
    if isinstance(value, tuple):
        return [(f"{name}.{j}", v) for j, v in enumerate(value)]
    return [(name, value)]


def _savez_fast(path: str, leaves: dict) -> None:
    """npz-compatible writer at deflate level 1. np.savez_compressed is
    hardwired to zlib level 6 on one core — measured 177 s for a 412 MB
    snapshot of a 2^22-ring store; level 1 compresses the same state
    ~5x faster within a few percent of the size, and np.load reads any
    deflate-compressed zip member unchanged."""
    import zipfile

    with zipfile.ZipFile(path, "w", compression=zipfile.ZIP_DEFLATED,
                         compresslevel=1, allowZip64=True) as zf:
        for name, arr in leaves.items():
            with zf.open(name + ".npy", "w", force_zip64=True) as f:
                np.lib.format.write_array(
                    f, np.asanyarray(arr), allow_pickle=False
                )


_SLAB_BYTES = 64 << 20  # transfer granularity for big leaves
_GEN_FILE = "generation.json"


def _bounded_get(x, deadline_s: Optional[float]):
    """jax.device_get with a deadline. A blocked device transfer is
    uninterruptible from Python, so the fetch runs on an abandonable
    daemon thread; on timeout the thread is orphaned and TimeoutError
    raised — the caller retries or gives up, but never loses work
    already staged to disk."""
    if deadline_s is None:
        return jax.device_get(x)
    import threading

    box = {}

    def run():
        try:
            box["v"] = jax.device_get(x)
        except Exception as e:  # noqa: BLE001 — re-raised below
            box["e"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        err = TimeoutError(
            f"device_get exceeded {deadline_s:.0f}s")
        # The abandoned thread may keep READING state buffers after the
        # caller's locks release; carry it so save() can stamp the store
        # suspect (store.base.SuspectGuard) and later joins can clear it.
        err.orphan = t
        raise err
    if "e" in box:
        raise box["e"]
    return box["v"]


def _fetch_leaf(arr, deadline_s, retries: int, stats: Optional[dict]):
    """Fetch one device leaf as slabs of <= _SLAB_BYTES (sliced on
    device along the leading axis), each slab under its own deadline.

    FAIL-FAST: the first slab timeout raises immediately. The old
    per-slab retry+backoff ran while save() held the writer-blocking
    read lock, and a retry enqueues BEHIND the blocked transfer — it
    could never succeed until that one cleared, so every retry only
    extended the lock hold (and the ingest stall) by another deadline
    + backoff. The save now fails
    on the first timeout, the store is stamped suspect by the caller,
    and recovery is the staged resume: a retry of save() skips every
    leaf already on disk. ``retries`` is accepted for call-site
    compatibility and deliberately ignored."""
    import time

    del retries  # fail-fast: no in-lock retry, see docstring
    nbytes = arr.size * getattr(arr, "dtype", np.dtype(np.int64)).itemsize
    shape = getattr(arr, "shape", ())
    if deadline_s is None or not shape or nbytes <= _SLAB_BYTES:
        slabs = [arr]
    else:
        rows = shape[0]
        row_bytes = max(1, nbytes // max(rows, 1))
        step = max(1, _SLAB_BYTES // row_bytes)
        slabs = [arr[i:i + step] for i in range(0, rows, step)]
    out = []
    for slab in slabs:
        t0 = time.perf_counter()
        try:
            h = _bounded_get(slab, deadline_s)
        except TimeoutError:
            if stats is not None:
                stats["slab_timeouts"] = stats.get("slab_timeouts",
                                                   0) + 1
            raise
        dt = time.perf_counter() - t0
        h = np.asarray(h)
        if stats is not None:
            stats["slabs"] = stats.get("slabs", 0) + 1
            stats["bytes"] = stats.get("bytes", 0) + h.nbytes
            stats["slab_s"] = stats.get("slab_s", 0.0) + dt
            mbps = h.nbytes / 1e6 / max(dt, 1e-9)
            stats["mb_per_s_min"] = round(min(
                stats.get("mb_per_s_min", mbps), mbps), 2)
            stats["mb_per_s_max"] = round(max(
                stats.get("mb_per_s_max", mbps), mbps), 2)
        out.append(h)
    return out[0] if len(out) == 1 else np.concatenate(out, axis=0)


def _state_generation(store, n_shards, deadline_s) -> list:
    """A cheap scalar fingerprint of the device state's write history:
    equal generations mean no ingest/sweep/archive touched the state
    between two save attempts, so staged leaves from the earlier
    attempt are still a consistent cut and may be reused."""
    state = store.states if n_shards else store.state
    gen = {
        "write_pos": state.write_pos,
        "ann_write_pos": state.ann_write_pos,
        "bann_write_pos": state.bann_write_pos,
        "pend_pos": state.pend_pos,
        "dep_bank_seq": state.dep_bank_seq,
        "ts_max": state.ts_max,
        **{f"counters.{k}": v for k, v in state.counters.items()},
    }
    host = _bounded_get(gen, deadline_s)
    # Lists, not tuples: the fingerprint round-trips through JSON and
    # must compare equal to its own deserialization.
    return sorted(
        [k, np.asarray(v).reshape(-1).tolist()] for k, v in host.items()
    )


def _seal_barrier(store) -> None:
    """Wait for the store's async capture sealer (if any) to finish
    every pulled window — see the call sites in save() for why this
    must run under the state read lock."""
    barrier = getattr(store, "seal_barrier", None)
    if barrier is not None:
        barrier()


def save(store, path: str, chunk_deadline_s: Optional[float] = None,
         slab_retries: int = 1) -> dict:
    """Snapshot a TpuSpanStore OR a ShardedSpanStore to ``path`` (a
    directory), atomically. Sharded stores save their stacked
    [n_shards, ...] state; load() re-shards it over a mesh.

    With ``chunk_deadline_s`` set, the device→host gather is CHUNKED
    and RESUMABLE: each leaf transfers in <= 64 MB slabs, each under
    its own deadline (+ ``slab_retries`` re-requests), and completed
    leaves persist in a ``<path>.staging`` directory — if a transfer
    blocks past its deadline, the failed save raises but a retry skips
    everything already staged (guarded by a state-generation
    fingerprint so a write between attempts discards the stage rather
    than mixing two cuts). Returns transfer stats (slab count/bytes/
    bandwidth, resumed leaf count)."""
    # Resident-query-executor quiesce (query/engine.py): wait for any
    # in-flight coalesced query launch to finish before the gather
    # begins, so the snapshot's device cut never interleaves with a
    # standing executor's batch mid-dispatch (the ordered-shutdown
    # contract: drain-queries → drain-pipeline → seal → gather).
    for eng in getattr(store, "query_engines", lambda: ())():
        eng.drain()
    # Same quiesce for the sharded store's cross-shard dispatcher — a
    # fused catalog/index launch mid-dispatch must finish before the
    # gather's cut.
    dispatcher = getattr(store, "dispatcher", None)
    if dispatcher is not None:
        dispatcher.drain()
    # A TieredSpanStore (store/archive) snapshots as its hot device
    # store plus the segment manifest; the segments themselves are
    # immutable host blobs, so they add host IO only — never device
    # transfer time under the read lock.
    tiered = (store if getattr(store, "archive", None) is not None
              and hasattr(store, "hot") else None)
    if tiered is not None:
        store = tiered.hot
    n_shards = getattr(store, "n", None) if hasattr(store, "states") else None
    # A PRIOR save's timeout may have left an orphaned transfer thread
    # still reading the state; a fresh consistent cut must not race it.
    # Give the orphan a short grace to finish, else refuse
    # (StoreSuspectError) — the same gate the donating write paths use.
    ensure = getattr(store, "ensure_writable", None)
    if ensure is not None and getattr(store, "suspect", False):
        ensure(wait_s=5.0)
    # Pipelined-ingest quiesce: batches accepted by apply() but still
    # in the prefetch/staging queues must land in this cut, or a
    # restore would silently drop them (the collector already counted
    # them stored). No-op for serial stores and shard stores.
    drain = getattr(store, "drain_pipeline", None)
    if drain is not None:
        drain()
    stats: dict = {"resumed_leaves": 0, "chunked": chunk_deadline_s
                   is not None}
    staging = os.path.abspath(path) + ".staging"
    leaves = {}
    if chunk_deadline_s is None:
        # Fast path (the default, e.g. the daemon's SIGTERM save): ONE
        # batched device_get of the whole pytree under the read lock —
        # per-leaf transfers and a staged double-write would be a pure
        # latency/IO regression for callers that never asked for
        # resumability. Ingest donates the previous state's buffers, so
        # the lock must cover the gather.
        with store._rw.read():
            # Capture-backlog quiesce, UNDER the read lock: any window
            # pulled before this point seals now; a window pulled
            # after cannot lose rows from this cut (its overwriting
            # write blocks on the write lock until the gather is done,
            # so the rows are still resident in the gathered state).
            _seal_barrier(store)
            # Host clocks under the SAME read lock as the gather: the
            # mirrors advance inside the commit's write-lock hold, so
            # (state, clocks, applied WAL seq) is one consistent cut —
            # the anchor deterministic replay resumes from.
            clocks = (_sharded_clocks(store) if n_shards
                      else _host_clocks(store))
            state = store.states if n_shards else store.state
            host_state = jax.device_get(state)
        for name in dev.StoreState._FIELDS:
            for key, leaf in _leaf_items(name, getattr(host_state, name)):
                leaves[key] = np.asarray(leaf)
    else:
        # Chunked+resumable path. The read lock covers the whole
        # gather (consistent cut; writers block). On timeout the
        # orphaned transfer thread may still be reading state buffers
        # after the lock releases, so the store is STAMPED SUSPECT
        # below: donating ingest and the next save refuse
        # to run (StoreSuspectError) until the orphan is joined —
        # nothing relies on callers reading a docstring anymore.
        try:
            with store._rw.read():
                _seal_barrier(store)  # same argument as the fast path
                clocks = (_sharded_clocks(store) if n_shards
                          else _host_clocks(store))
                gen = _state_generation(store, n_shards,
                                        chunk_deadline_s)
                if os.path.isdir(staging):
                    try:
                        with open(os.path.join(staging, _GEN_FILE)) as f:
                            prior = json.load(f)
                    except (OSError, ValueError):
                        prior = None
                    if prior != gen:
                        shutil.rmtree(staging, ignore_errors=True)
                os.makedirs(staging, exist_ok=True)
                with open(os.path.join(staging, _GEN_FILE), "w") as f:
                    json.dump(gen, f)
                state = store.states if n_shards else store.state
                for name in dev.StoreState._FIELDS:
                    for key, leaf in _leaf_items(name,
                                                 getattr(state, name)):
                        dest = os.path.join(staging, key + ".npy")
                        if os.path.exists(dest):
                            stats["resumed_leaves"] += 1
                            continue
                        host = _fetch_leaf(leaf, chunk_deadline_s,
                                           slab_retries, stats)
                        tmp_leaf = dest + ".tmp"
                        with open(tmp_leaf, "wb") as f:
                            np.save(f, host, allow_pickle=False)
                        os.replace(tmp_leaf, dest)
        except TimeoutError as e:
            mark = getattr(store, "mark_suspect", None)
            if mark is not None:
                mark(getattr(e, "orphan", None))
            raise
        if stats.get("slab_s"):
            stats["mb_per_s_avg"] = round(
                stats["bytes"] / 1e6 / stats["slab_s"], 2)
        for fname in os.listdir(staging):
            if fname.endswith(".npy"):
                # mmap: the finalize zip streams straight from the
                # staged files instead of doubling the snapshot in RAM.
                leaves[fname[:-4]] = np.load(
                    os.path.join(staging, fname), mmap_mode="r",
                    allow_pickle=False)
    archive_meta = None
    seg_blobs = []
    # Paged layout (revision 18): snapshot the page allocator + page
    # table. plan_unit keys each claim plan to its WAL seq atomically
    # under the planner lock, so this cut is self-consistent at ANY
    # boundary: plans at seq <= the snapshot's last_seq replay from
    # the recorded memo; later ones re-derive deterministically.
    planner = getattr(store, "_planner", None)
    paged_meta = planner.snapshot() if planner is not None else None
    with store._lock:
        # Pinned traces' eviction-exempt banks must survive restarts —
        # the TTL alone restoring while the spans vanish would break the
        # retention contract pinning exists for (SpanStore.scala:66).
        # Pickled (not wire-encoded): both the JSON and thrift codecs
        # normalize bytes-vs-str values, and the bank must restore the
        # exact objects reads were returning before the restart.
        pins_snapshot = {
            tid: list(bank) for tid, bank in store.pins.items()
        }
        ttls_snapshot = {str(k): v for k, v in store.ttls.items()}
        if tiered is not None:
            # The manifest cuts at the SEALED frontier, not the pull
            # clock: with an async sealer, _cap_upto can run ahead of
            # the last appended segment, and claiming an unsealed
            # window would lose it on restore (restore re-captures
            # only [captured_upto, wp) from the restored rings).
            # Inline sealing keeps the two equal. ORDER MATTERS: the
            # clock reads come BEFORE the segment snapshot — segments
            # only grow, so every window sealed before the clock read
            # has its segment in the (later) snapshot; a pipelined
            # store's commit thread doesn't hold store._lock, and the
            # reverse order could claim a window sealed between the
            # two reads without shipping its segment. The segment
            # list may then cover gids PAST captured_upto — a harmless
            # superset (gid dedup), never a loss. Windows pulled after
            # save's under-lock seal barrier can't lose rows from this
            # cut either way: their overwriting writes blocked on the
            # write lock until the state gather finished, so the rows
            # are resident in the gathered ring state.
            # Unlocked clock reads, same justification (and same
            # lock-order constraint) as _host_clocks above: the min()
            # makes the cut safe against the one benign race.
            captured_upto = int(min(
                store._cap_upto,  # graftlint: disable=guarded-by
                getattr(store, "_sealed_upto",
                        store._cap_upto)))  # graftlint: disable=guarded-by
            segs = tiered.archive.snapshot()
            archive_meta = {
                "params": tiered.params._asdict(),
                "captured_upto": captured_upto,
                "segments": [
                    {"seg_id": s.seg_id, "gid_lo": s.gid_lo,
                     "gid_hi": s.gid_hi, "n_spans": s.n_spans,
                     "file": f"seg-{s.seg_id:08d}.bin"}
                    for s in segs
                ],
            }
            seg_blobs = [(f"seg-{s.seg_id:08d}.bin", s) for s in segs]
    meta = {
        "revision": _REVISION,
        "config": store.config._asdict(),
        "shards": n_shards,
        # Per-slab integrity: verified on restore (CorruptSlabError).
        # For staged leaves this re-reads the .npy files (host IO only,
        # never device time under a lock).
        "slab_crc32": {k: _slab_crc(v) for k, v in leaves.items()},
        "ttls": ttls_snapshot,
        "name_lc": {str(k): v for k, v in store._name_lc.items()},
        "dicts": {
            "services": _dict_dump(store.dicts.services),
            "span_names": _dict_dump(store.dicts.span_names),
            "annotations": _dict_dump(store.dicts.annotations),
            "binary_keys": _dict_dump(store.dicts.binary_keys),
            "binary_values": _dict_dump(store.dicts.binary_values),
            "endpoints": _dict_dump(store.dicts.endpoints),
        },
    }
    if archive_meta is not None:
        meta["archive"] = archive_meta
    if clocks is not None:
        meta["clocks"] = clocks
    if paged_meta is not None:
        meta["paged"] = paged_meta
    parent = os.path.dirname(os.path.abspath(path)) or "."
    tmp = tempfile.mkdtemp(prefix=".ckpt-", dir=parent)
    old = path + ".old"
    try:
        _savez_fast(os.path.join(tmp, _STATE_FILE), leaves)
        with open(os.path.join(tmp, _META_FILE), "w") as f:
            json.dump(meta, f)
        if seg_blobs:
            # Segments are immutable, so a blob already present in the
            # live snapshot CAN be hard-linked (or copied) instead of
            # re-serialized — per-save archive cost O(new segments),
            # not O(history). Reuse is gated on the blob's own header
            # matching the live segment (id + gid range + row count +
            # size), not the filename alone: a restored-older-copy
            # lineage can re-mint a seg id, and filename-only reuse
            # would silently link the WRONG bytes (the state leaves'
            # generation fingerprint guards the same staleness class).
            seg_dir = os.path.join(tmp, _SEGMENTS_DIR)
            os.makedirs(seg_dir)
            prev_dir = os.path.join(path, _SEGMENTS_DIR)
            for fname, seg in seg_blobs:
                dest = os.path.join(seg_dir, fname)
                prev = os.path.join(prev_dir, fname)
                if _segment_blob_matches(prev, seg):
                    try:
                        os.link(prev, dest)
                        stats["reused_segments"] = stats.get(
                            "reused_segments", 0) + 1
                        continue
                    except OSError:
                        try:
                            shutil.copyfile(prev, dest)
                            stats["reused_segments"] = stats.get(
                                "reused_segments", 0) + 1
                            continue
                        except OSError:
                            pass
                with open(dest, "wb") as f:
                    f.write(seg.to_bytes())
        if pins_snapshot:
            import pickle

            with open(os.path.join(tmp, _PINS_FILE), "wb") as f:
                pickle.dump(pins_snapshot, f)
        # Keep the previous checkpoint alive until the new one is in
        # place: path → path.old, tmp → path, then drop path.old. A crash
        # at any point leaves either path or path.old restorable (load()
        # falls back to path.old).
        shutil.rmtree(old, ignore_errors=True)
        if os.path.isdir(path):
            os.replace(path, old)
        # Crash-harness injection site (testing/crash.py): dying HERE
        # is the worst mid-swap moment — only ``path.old`` (or nothing,
        # on the first save) is restorable, and the WAL was not yet
        # truncated, so recovery must fall back + replay.
        kill_point("mid-checkpoint")
        os.replace(tmp, path)
        shutil.rmtree(old, ignore_errors=True)
        # The staged cut is fully inside the finalized snapshot now.
        del leaves
        shutil.rmtree(staging, ignore_errors=True)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    # Checkpoint-coordinated WAL truncation: the finalized snapshot
    # (which includes the sealed cold-tier frontier — the seal barrier
    # ran under the gather's read lock) covers every record up to its
    # applied sequence, so those segments can go. Runs ONLY after the
    # rename landed — a failed save never shrinks the log.
    wal = getattr(store, "wal", None)
    if wal is not None and clocks is not None:
        stats["wal_truncated_segments"] = wal.truncate(
            int(clocks["wal_applied"]))
    return stats


def _segment_blob_matches(blob_path: str, seg) -> bool:
    """True iff the blob at ``blob_path`` has the SAME identity header
    as the live segment — a header-only read (~1 KB), never the full
    blob. See the reuse note in save()."""
    import struct

    try:
        with open(blob_path, "rb") as f:
            head = f.read(9)
            if head[:5] != b"ZSEG1":
                return False
            (hlen,) = struct.unpack(">I", head[5:9])
            if hlen > 1 << 22:
                return False
            header = json.loads(f.read(hlen).decode("utf-8"))
    except (OSError, ValueError, struct.error):
        return False
    return (
        header.get("seg_id") == seg.seg_id
        and header.get("gid_lo") == seg.gid_lo
        and header.get("gid_hi") == seg.gid_hi
        and header.get("n_spans") == seg.n_spans
        and header.get("comp_bytes") == seg.comp_bytes
    )


def exists(path) -> bool:
    """True when ``load(path)`` has a snapshot to restore — the
    directory itself, or the ``.old`` fallback a crash mid-swap leaves
    behind. The ONE restorability predicate (example.py's boot and
    wal/recovery.recover share it): a boot path that only checked
    ``path`` would build a FRESH store after a mid-swap crash and
    replay the WAL tail against empty dictionaries."""
    return bool(path) and (os.path.isdir(path)
                           or os.path.isdir(path + ".old"))


def load(path: str, mesh=None, config_defaults=None):
    """Restore a store from a snapshot directory (falling back to the
    ``.old`` snapshot if a save crashed mid-swap).

    Single-device snapshots restore a TpuSpanStore. Sharded snapshots
    (saved from a ShardedSpanStore) restore a ShardedSpanStore over
    ``mesh`` — or a mesh built from the first n visible devices when
    not given; the shard count must match the snapshot's.

    ``config_defaults`` fills config keys the snapshot's meta does NOT
    carry (a knob newer than the snapshot's revision) — keys present
    in the meta always win, since the saved leaves were shaped by
    them. The daemon passes its --window-seconds/--window-buckets here
    so a pre-rev-14 snapshot restores with an EMPTY window arena at
    the flag geometry instead of silently disabling the feature."""
    if not os.path.isdir(path) and os.path.isdir(path + ".old"):
        path = path + ".old"
    with open(os.path.join(path, _META_FILE)) as f:
        meta = json.load(f)
    cfg_map = dict(meta["config"])
    for k, v in (config_defaults or {}).items():
        cfg_map.setdefault(k, v)
    config = dev.config_from_dict(cfg_map)

    dicts = DictionarySet.__new__(DictionarySet)
    from zipkin_tpu.columnar.dictionary import Dictionary
    from zipkin_tpu.models.constants import (
        CORE_ANNOTATION_IDS,
        FIRST_USER_ANNOTATION_ID,
    )

    dicts.services = Dictionary()
    dicts.span_names = Dictionary()
    dicts.annotations = Dictionary(reserved=dict(CORE_ANNOTATION_IDS))
    dicts.binary_keys = Dictionary()
    dicts.binary_values = Dictionary()
    dicts.endpoints = Dictionary()
    d = meta["dicts"]
    # Annotation dict dump includes the reserved entries; replay in order.
    for name in ("services", "span_names", "binary_keys",
                 "binary_values", "endpoints"):
        _dict_load(getattr(dicts, name), d[name])
    ann = Dictionary()
    _dict_load(ann, d["annotations"])
    dicts.annotations = ann

    n_shards = meta.get("shards")
    if n_shards:
        from jax.sharding import Mesh

        from zipkin_tpu.parallel.shard import ShardedSpanStore

        if mesh is None:
            devices = jax.devices()
            if len(devices) < n_shards:
                raise ValueError(
                    f"snapshot has {n_shards} shards but only "
                    f"{len(devices)} devices are visible"
                )
            mesh = Mesh(np.array(devices[:n_shards]),
                        axis_names=("shard",))
        if "shard" not in mesh.shape:
            raise ValueError(
                f"mesh must have a 'shard' axis (ShardedSpanStore's "
                f"axis); got axes {tuple(mesh.shape)}"
            )
        if mesh.shape["shard"] != n_shards:
            raise ValueError(
                f"snapshot has {n_shards} shards; mesh has "
                f"{mesh.shape['shard']}"
            )
        store = ShardedSpanStore(mesh, config, codec=SpanCodec(dicts))
    else:
        store = TpuSpanStore(config, codec=SpanCodec(dicts))
    store.ttls = {int(k): v for k, v in meta["ttls"].items()}
    store._name_lc = {int(k): v for k, v in meta["name_lc"].items()}
    pins_path = os.path.join(path, _PINS_FILE)
    if os.path.exists(pins_path):
        import pickle

        with open(pins_path, "rb") as f:
            for tid, bank in pickle.load(f).items():
                store.pins.pin(int(tid), bank)

    data = np.load(os.path.join(path, _STATE_FILE))
    # Slab integrity (revision 13): every leaf checks against its
    # manifest CRC32 BEFORE anything reaches device_put — a rotted
    # slab is a named, immediate failure, not device garbage. Pre-13
    # snapshots carry no CRCs and skip the check.
    crcs = meta.get("slab_crc32") or {}

    def _leaf(key):
        arr = np.asarray(data[key])
        want = crcs.get(key)
        if want is not None and _slab_crc(arr) != int(want):
            raise CorruptSlabError(
                f"checkpoint slab '{key}' fails its manifest CRC32 — "
                f"snapshot at {path} is damaged; restore from the "
                f".old snapshot or an earlier checkpoint + WAL replay"
            )
        return arr

    upd = {}
    # Counters the snapshot predates keep their init defaults — the
    # schema may grow counters (e.g. key_claim_drops) and ingest
    # addresses them by name.
    base_state = store.inner.states if n_shards else store.state
    counters = dict(base_state.counters)
    planes = {}
    for key in data.files:
        if key.startswith("counters."):
            counters[key.split(".", 1)[1]] = jax.numpy.asarray(
                _leaf(key))
        elif key.startswith("cand_idx."):
            planes[int(key.split(".", 1)[1])] = _leaf(key)
        elif key == "cand_idx":
            # Revisions 11-18 saved the arena as one [slots, 3] i64
            # leaf: the same bits as today's six i32 planes, split by
            # a host-side view (pre-11 arenas are dropped below).
            planes = dict(enumerate(dev.arena_planes(_leaf(key))))
        else:
            upd[key] = jax.numpy.asarray(_leaf(key))
    if planes:
        upd["cand_idx"] = tuple(
            jax.numpy.asarray(planes[j])
            for j in range(dev.ARENA_PLANES))
    # Drop snapshot counters the current schema no longer carries.
    counters = {
        k: v for k, v in counters.items() if k in base_state.counters
    }
    if meta.get("revision", 1) < 9:
        # Pre-rev-8 stores never counted key-claim drops, and rev-8
        # tables stored exact key words that the rev-9 fingerprint
        # schema tombstones on restore (see below): either way a key
        # may have bucket entries but no record, which the negative-
        # lookup gate would misread as "never indexed". Force the gate
        # off for the restored store's lifetime.
        counters["key_claim_drops"] = jax.numpy.maximum(
            jax.numpy.asarray(counters["key_claim_drops"],
                              jax.numpy.int64),
            jax.numpy.int64(1),
        )
    upd["counters"] = counters
    # Leaves the current schema no longer carries (e.g. the r2 watermark
    # dep_archived_gid, retired with the streaming hash join) are
    # dropped; leaves the snapshot predates (span_tab, pending ring,
    # dep_window) keep their init_state defaults — the table rebuilds as
    # new spans arrive, and any SAVED state's links were already folded
    # into dep_moments/dep_banks by the pre-upgrade archive policy.
    known = set(dev.StoreState._FIELDS)
    revision = meta.get("revision", 1)
    legacy = revision < 4
    if revision < 11:
        # Revision 11 merged every index family into ONE arena: a
        # pre-11 cand_idx/cand_pos/cand_wm (candidate families only)
        # or tr_idx/tr_pos/tr_wm (gone from the schema) would misalign
        # against the unified slot math while its cursors still claimed
        # exactness. Drop the stale arrays and poison trust per
        # segment:
        # - candidate prefix: cursor past depth + wm at +inf — the
        #   ts-watermark gate has no eviction-horizon analogue to heal
        #   through, so restored candidate queries scan for the store's
        #   remaining lifetime (the pre-index snapshot treatment);
        # - trace suffix: wm seeds at the restore-time write_pos, NOT
        #   +inf — wm = wp claims "any restored-era gid may have been
        #   displaced", which the displaced-gid gate (wm < write_pos -
        #   capacity) re-opens after one full ring lap, once every
        #   restored span is evicted and the fresh entries are
        #   authoritative (ann_poison's self-healing pattern).
        for k in ("tr_idx", "tr_pos", "tr_wm",
                  "cand_idx", "cand_pos", "cand_wm"):
            upd.pop(k, None)
        n_total = config.idx_layout[1]
        n_cand = config.cand_layout[1]
        shape = (n_total,)
        if n_shards:
            shape = (n_shards,) + shape  # stacked sharded state
        big = jax.numpy.int64(1) << 60
        upd["cand_pos"] = jax.numpy.full(shape, big, jax.numpy.int64)
        is_cand = jax.numpy.arange(n_total) < n_cand
        wp = upd.get("write_pos")
        if wp is None:
            tr_seed = jax.numpy.full(shape, dev.I64_MAX,
                                     jax.numpy.int64)
        else:
            wp = jax.numpy.asarray(wp, jax.numpy.int64)
            if n_shards:
                wp = wp.reshape((-1, 1))  # [n_shards] -> broadcastable
            tr_seed = jax.numpy.broadcast_to(wp, shape)
        upd["cand_wm"] = jax.numpy.where(
            is_cand, jax.numpy.int64(dev.I64_MAX), tr_seed
        )
    if revision < 9 and "key_tab" in upd:
        # Revisions < 9 stored exact 64-bit key words; the table is now
        # 31-bit fingerprints (i32). The packed words are recoverable
        # (fp31 of the stored key48), but the claim-is-first-record
        # invariant can't be re-certified across the schema change, so
        # tombstone the table (INT32_MIN: unclaimable, matches no
        # fingerprint) and let load()'s pre-rev-8 drop-counter floor
        # keep the negative gate off; bucket gates serve as before.
        upd["key_tab"] = jax.numpy.full(
            np.asarray(upd["key_tab"]).shape, dev._FP_TOMB,
            jax.numpy.int32,
        )
        if "key_wm" in upd:
            upd["key_wm"] = jax.numpy.full(
                np.asarray(upd["key_wm"]).shape, dev.I64_MAX,
                jax.numpy.int64,
            )
    # Snapshots predating (parts of) the index families — or carrying
    # the pre-unification per-family layout — would restore empty
    # buckets whose zero cursors claim completeness, hiding every
    # restored span from the fast paths. Poison index trust so the
    # exact scan kernels serve instead (load() applies below).
    pre_index = revision < 6
    # Revision < 7: the span table used 0 as its empty sentinel (now
    # _TAB_EMPTY, for deterministic min-insert), and ann_poison didn't
    # exist — any restored span might be a 3+-distinct-host span whose
    # middle hosts were never indexed, so stamp every service poisoned
    # until the ring turns over (dev.poison_ann_trust below).
    pre_poison = revision < 7
    upd = {k: v for k, v in upd.items() if k in known}
    if "span_tab" in upd and np.asarray(upd["span_tab"]).dtype == np.int64:
        # Pre-rev-11 snapshots store the dep-join table as packed i64
        # words; rev 11 keeps [H, 2] i32 bit-planes — a pure
        # representation change, so the migration is a lossless bitcast
        # (little-endian: word 0 is the low plane, matching
        # lax.bitcast_convert_type). Gated on the stored DTYPE, not the
        # revision, so a snapshot that already carries planes (however
        # its meta is labeled) passes through untouched.
        tab = np.asarray(upd["span_tab"])
        if pre_poison:
            # Rev < 7 used 0 as the empty sentinel (now _TAB_EMPTY).
            tab = np.where(tab == 0, dev._TAB_EMPTY, tab)
        tab = np.ascontiguousarray(tab)
        upd["span_tab"] = jax.numpy.asarray(
            tab.view(np.int32).reshape(tab.shape + (2,))
        )
    if legacy:
        _migrate_legacy_live_links(data, upd, config, n_shards)
    if "dep_banks" not in upd:
        # Pre-revision-3 snapshot (single archive bank, no time tags):
        # the saved dep_moments becomes the all-time tail. Its ts range
        # is unknown, so mark the tail as covering every window (a zero
        # bank contributes nothing either way); the bucket ring starts
        # empty at the init_state defaults.
        if float(np.asarray(data["dep_moments"])[:, 0].sum()) > 0:
            upd["dep_overflow_ts"] = jax.numpy.asarray(
                np.array([dev.I64_MIN, dev.I64_MAX], np.int64)
            )
    if n_shards:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P("shard"))

        def place(x):
            return jax.device_put(jax.numpy.asarray(x), sharding)

        upd = {k: jax.tree_util.tree_map(place, v)
               for k, v in upd.items()}
        with store._rw.write():
            store.inner.states = store.inner.states.replace(**upd)
            if pre_index:
                store.inner.states = dev.poison_index_trust(
                    store.inner.states
                )
            if pre_poison:
                store.inner.states = dev.poison_ann_trust(
                    store.inner.states
                )
            if legacy:
                store.inner.states = _sharded_rebuild_tab(
                    mesh, store.inner.states
                )
        wps = np.asarray(jax.device_get(store.inner.states.write_pos))
        store.inner._wp_upper = int(wps.max())
        # Links resolve at ingest now; the mirror only paces time-bucket
        # rotation, so resume with the cadence clock at "just rotated".
        store.inner._archived_lower = store.inner._wp_upper
        # The restored aggregates were never deltas on this process's
        # per-shard mirror twins: resync lazily on the first
        # sketch-tier read (FleetMirror.mark_cold cascades).
        fm = getattr(store, "_fleet_mirror", None)
        if fm is not None:
            fm.mark_cold()
        clocks = meta.get("clocks")
        if clocks and clocks.get("sharded"):
            # Revision-16 sharded snapshots carry the fleet pacing
            # clocks: restore them EXACTLY so a ShardedWal tail replay
            # re-cuts the uncrashed fleet's launches bitwise — the
            # same contract as the single-device clocks below.
            store.inner._wp_upper = int(clocks["wp_upper"])
            store.inner._archived_lower = int(clocks["archived_lower"])
            store.inner._batches_since_sweep = int(
                clocks["batches_since_sweep"])
            # The store is load-local (not yet published to any
            # reader/writer thread), so the bare clock store is
            # race-free.
            store._step_seq = int(  # graftlint: disable=guarded-by
                clocks.get("step_seq", 0))
            store._wal_applied = int(clocks.get("wal_applied", 0))
        return store
    with store._rw.write():
        store.state = store.state.replace(**upd)
        if pre_index:
            store.state = dev.poison_index_trust(store.state)
        if pre_poison:
            store.state = dev.poison_ann_trust(store.state)
        if legacy:
            # The pre-rev-4 schema had no span table: re-insert resident
            # spans so post-restore children still find their parents.
            store.state = dev.rebuild_span_tab(store.state)
    # Re-seed the host mirrors that pace dependency bucket rotation —
    # or, for revision-13 snapshots, restore them EXACTLY: the saved
    # clocks were captured under the gather's read lock, so sweep and
    # bucket-rotation cadence resume mid-stride and a WAL replay
    # re-cuts the uncrashed drive's launches bitwise (wal/recovery).
    store._wp = int(store.state.write_pos)
    store._archived = store._wp
    # The restored aggregates were never deltas on this process's
    # sketch mirror: resync lazily on first sketch-tier read.
    if hasattr(store, "sketch_mirror"):
        store.sketch_mirror.mark_cold()
    clocks = meta.get("clocks")
    if clocks:
        store._archived = int(clocks["archived"])
        store._batches_since_sweep = int(clocks["batches_since_sweep"])
        store._awp = int(clocks["awp"])
        store._bwp = int(clocks["bwp"])
        with store._cap_lock:
            store._cap_upto = int(clocks["cap_upto"])
            store._cap_a = int(clocks["cap_a"])
            store._cap_b = int(clocks["cap_b"])
            store._sealed_upto = int(clocks["sealed_upto"])
        store._wal_applied = int(clocks.get("wal_applied", 0))
    # Paged layout (revision 18): restore the page allocator + page
    # table — or, for a paged config pointed at a snapshot saved
    # without it (pre-18, or a ring store's), rebuild the table from
    # the resident device columns.
    if getattr(store, "_planner", None) is not None:
        pmeta = meta.get("paged")
        if pmeta:
            store._planner.restore(pmeta)
        else:
            row_gid, trace_col = jax.device_get(
                (store.state.row_gid, store.state.trace_id))
            store._planner.rebuild(row_gid, trace_col,
                                   wal_applied=store._wal_applied)
    arch = meta.get("archive")
    if arch:
        return _restore_tiered(path, store, arch,
                               exact_clocks=bool(clocks))
    return store


def _restore_tiered(path: str, store, arch: dict,
                    exact_clocks: bool = False):
    """Rebuild the TieredSpanStore around a restored device store:
    segments load from their immutable blobs, the captured-gid
    watermark restores from the manifest, and one capture_now() flush
    re-aligns the side-ring capture clocks (the host annotation/binary
    mirrors don't survive a restart — flushing the resident uncaptured
    window to a fresh segment makes every clock zero-delta again; the
    row overlap with the ring is the tiers' normal state and gid-level
    dedupe absorbs it).

    ``exact_clocks`` (revision-13 snapshots): the capture clocks were
    saved exactly, so the reseed + flush is SKIPPED — capture resumes
    mid-stride, which keeps a WAL replay's capture windows (and hence
    its cold segments) identical to the uncrashed drive's."""
    from zipkin_tpu.store.archive import (
        ArchiveParams,
        Segment,
        SegmentDirectory,
        TieredSpanStore,
    )

    params = ArchiveParams(**arch["params"])
    directory = SegmentDirectory(params, store.codec)
    segs = []
    for ent in arch["segments"]:
        with open(os.path.join(path, _SEGMENTS_DIR, ent["file"]),
                  "rb") as f:
            segs.append(Segment.from_bytes(f.read()))
    for seg in segs:
        # Dictionary-delta validation: every id a segment references
        # lies below its seal-time high-water marks; the restored
        # dictionaries (saved in the same snapshot) must cover them.
        sizes = (len(store.dicts.services), len(store.dicts.span_names),
                 len(store.dicts.annotations),
                 len(store.dicts.binary_keys),
                 len(store.dicts.binary_values),
                 len(store.dicts.endpoints))
        if any(have < need for have, need in zip(sizes,
                                                 seg.dict_sizes)):
            raise ValueError(
                f"segment {seg.seg_id} references dictionary ids past "
                f"the restored dictionaries ({sizes} < "
                f"{seg.dict_sizes}); snapshot is inconsistent"
            )
    directory.restore(
        segs, max((s.seg_id for s in segs), default=-1) + 1)
    tiered = TieredSpanStore(store, params=params, directory=directory)
    if exact_clocks:
        return tiered
    # The save-time manifest may ship a segment sealed just past its
    # captured_upto clock read (harmless superset, see save()); adopt
    # the segments' CONTIGUOUS frontier so the capture_now flush below
    # starts exactly where sealed coverage ends — keeping cold
    # coverage contiguous and overlap-free. Walking contiguity (not
    # max(gid_hi)) matters when a failed async seal left a hole: the
    # frontier must stop below the hole so the flush re-captures
    # whatever of it the restored rings still hold.
    frontier = int(arch.get("captured_upto", 0))
    for s in sorted(segs, key=lambda s: s.gid_lo):
        if s.gid_lo <= frontier:
            frontier = max(frontier, s.gid_hi)
    with store._cap_lock:
        store._cap_upto = min(frontier, store._wp)
        store._sealed_upto = store._cap_upto
        store._cap_a = store._cap_b = 0
    store._awp = store._bwp = 0
    tiered.capture_now()
    return tiered


def _sharded_rebuild_tab(mesh, states):
    """Per-shard rebuild_span_tab for legacy sharded snapshots."""
    from jax.sharding import PartitionSpec as P

    from zipkin_tpu.parallel.shard import compat_shard_map

    def fn(state):
        state = jax.tree.map(lambda x: x[0], state)
        new_state = dev.rebuild_span_tab.__wrapped__(state)
        return jax.tree.map(lambda x: x[None], new_state)

    mapped = compat_shard_map(
        fn, mesh=mesh, in_specs=(P("shard"),), out_specs=P("shard"),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,))(states)


def _migrate_legacy_live_links(data, upd, config, n_shards) -> None:
    """Pre-revision-4 snapshots carry links only in dep_moments/dep_banks
    plus an eviction watermark (dep_archived_gid): links of UNARCHIVED
    resident children existed only implicitly, computed on demand by the
    retired ring join. Reconstruct exactly those links here (host numpy,
    same segmented-Moments arithmetic) and seed the new streaming-join
    window bank with them — and queue children whose parent was NOT
    resident into the pending ring (packed with the bit-identical host
    mixer, hashing.np_mix_keys64), so a parent arriving after the
    upgrade still links. An upgrade loses nothing."""
    from zipkin_tpu.columnar.schema import FLAG_HAS_PARENT
    from zipkin_tpu.ops.hashing import np_mix_keys64
    from zipkin_tpu.store.device import _SVC_MASK

    S = config.max_services
    Q = config.pending_slots

    def one(slice_of):
        gid = slice_of("row_gid")
        live = gid >= 0
        flags = slice_of("flags")
        has_parent = (flags & int(FLAG_HAS_PARENT)) != 0
        archived = np.int64(slice_of("dep_archived_gid"))
        tid = slice_of("trace_id")
        sid = slice_of("span_id")
        pid = slice_of("parent_id")
        svc = slice_of("service_id")
        dur = slice_of("duration")
        tsf = slice_of("ts_first")
        tsl = slice_of("ts_last")
        probe = live & has_parent & (gid >= archived)
        window = np.zeros((S * S, 5), np.float32)
        wts = np.array([dev.I64_MAX, dev.I64_MIN], np.int64)
        pend = {
            "pend_key": np.zeros(Q, np.int64),
            "pend_dur": np.zeros(Q, np.int64),
            "pend_tsf": np.zeros(Q, np.int64),
            "pend_tsl": np.zeros(Q, np.int64),
            "pend_pos": np.int64(0),
        }
        if not probe.any():
            return window, wts, pend
        order = np.lexsort((sid[live], tid[live]))
        b_tid, b_sid = tid[live][order], sid[live][order]
        b_svc = svc[live][order]
        q_tid, q_pid = tid[probe], pid[probe]
        # Two-key search: positions where (tid, sid) == (q_tid, q_pid).
        bk = np.rec.fromarrays([b_tid, b_sid])
        qk = np.rec.fromarrays([q_tid, q_pid])
        pos = np.searchsorted(bk, qk)
        pos_c = np.clip(pos, 0, len(bk) - 1)
        found = (len(bk) > 0) & (bk[pos_c] == qk)
        psvc = np.where(found, b_svc[pos_c], -1)
        csvc = svc[probe]
        d = dur[probe]
        ok = found & (psvc >= 0) & (csvc >= 0) & (psvc < S) \
            & (csvc < S) & (d >= 0)
        # Children with no resident parent: queue them (newest Q) so a
        # parent arriving after the upgrade still links via dep_sweep —
        # the same gate the device ingest uses for its pending pushes.
        pend_mask = ~found & (csvc >= 0) & (csvc < S) & (d >= 0)
        if pend_mask.any():
            sel = np.flatnonzero(pend_mask)[-Q:]
            nq = sel.size
            key48 = np_mix_keys64(
                [q_tid[sel], q_pid[sel]]
            ) >> np.uint64(16)
            svc_part = (np.clip(csvc[sel], -1, _SVC_MASK - 2)
                        .astype(np.uint64) + np.uint64(1))
            packed = ((key48 << np.uint64(16))
                      | (svc_part << np.uint64(1))
                      | np.uint64(1)).view(np.int64)
            pend["pend_key"][:nq] = packed
            pend["pend_dur"][:nq] = d[sel]
            pend["pend_tsf"][:nq] = tsf[probe][sel]
            pend["pend_tsl"][:nq] = tsl[probe][sel]
            pend["pend_pos"] = np.int64(nq)
        if not ok.any():
            return window, wts, pend
        link = (psvc.astype(np.int64) * S + csvc)[ok]
        dv = d[ok].astype(np.float64)
        n = np.bincount(link, minlength=S * S).astype(np.float64)
        sx = np.bincount(link, weights=dv, minlength=S * S)
        mean = np.divide(sx, n, out=np.zeros_like(sx), where=n > 0)
        c = dv - mean[link]
        m2 = np.bincount(link, weights=c * c, minlength=S * S)
        m3 = np.bincount(link, weights=c * c * c, minlength=S * S)
        m4 = np.bincount(link, weights=c * c * c * c, minlength=S * S)
        window = np.stack([n, mean, m2, m3, m4], axis=-1).astype(
            np.float32
        )
        ptsf, ptsl = tsf[probe][ok], tsl[probe][ok]
        lo = ptsf[ptsf >= 0]
        hi = ptsl[ptsl >= 0]
        if lo.size:
            wts[0] = lo.min()
        if hi.size:
            wts[1] = hi.max()
        return window, wts, pend

    def col(name):
        if name in data.files:
            return np.asarray(data[name])
        if name == "dep_archived_gid":
            # Revision-1 layout: no watermark leaf, but its dep_moments
            # bank was the complete link state — treat the ring as fully
            # archived or every resident link would double-count.
            return np.asarray(data["write_pos"])
        return np.int64(0)

    if n_shards:
        windows, tss = [], []
        pends = {k: [] for k in ("pend_key", "pend_dur", "pend_tsf",
                                 "pend_tsl", "pend_pos")}
        for sh in range(n_shards):
            def slice_of(name, sh=sh):
                v = col(name)
                return v[sh] if getattr(v, "ndim", 0) > 0 else v
            w, t, p = one(slice_of)
            windows.append(w)
            tss.append(t)
            for k in pends:
                pends[k].append(p[k])
        upd["dep_window"] = jax.numpy.asarray(np.stack(windows))
        upd["dep_window_ts"] = jax.numpy.asarray(np.stack(tss))
        for k, vs in pends.items():
            upd[k] = jax.numpy.asarray(np.stack(vs))
    else:
        w, t, p = one(col)
        upd["dep_window"] = jax.numpy.asarray(w)
        upd["dep_window_ts"] = jax.numpy.asarray(t)
        for k, v in p.items():
            upd[k] = jax.numpy.asarray(v)
