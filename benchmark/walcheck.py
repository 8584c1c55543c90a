"""The durability guarantee, held against the disk: after the daemon has
exited, its write-ahead log directory is read here and every ``Log``
call acked ``OK`` has to be in it, whole, and to have been fsynced
before its ack reached the client.

Two things are read, neither through the program's code:

- the log's segment files, in the format ``zipkin_tpu/wal/log.py`` and
  ``wal/record.py`` document (``ZWAL1`` header; records of ``u32 len |
  u8 flags | u32 crc32 | payload``, payload deflated when flag 1 is set;
  a payload is ``u32 meta_len | meta json | column blobs`` and the meta
  lists every column's name, dtype and length in order). Only the CRC-
  valid prefix counts, as at recovery. A directory of ``wal-*.seg`` is
  one log. A directory with ``epoch/`` and ``shard-NNN/`` is the tree
  that ``wal/sharded.py`` documents, each member a log of its own: a
  launch unit is one record in every member under one sequence number
  (a segment's header json gives ``base_seq``; a record's sequence is
  that plus its index in the segment), the shard logs hold its spans,
  one part a shard, and the epoch log's record is its group commit. A
  unit is committed only if its sequence lies in the valid prefix of
  every member (what the program's open-time alignment keeps: it cuts
  every member back to the shortest; a unit whose record a checkpoint's
  truncation has freed in one member counts as freed in all), and is
  durable only once all its n + 1 records are;
- the fsync journal that ``daemon_entry.py`` keeps round ``os.fsync``:
  one line ``monotonic seconds, inode, file size`` for every fsync that
  returned. ``time.monotonic`` is CLOCK_MONOTONIC, one clock for every
  process of the machine, so those seconds and the client's ack times
  compare.

Numbers (exact, limit 0 each):

- ``acked_spans_not_in_wal``: acked spans that the log does not hold
  with their ids and all their annotation and binary-annotation rows;
- ``acks_before_durable``: acked calls of which some record was not
  covered, when the ack arrived, by an fsync that had returned: in a
  tree, any of the unit's n + 1 records, whichever member holds it.
"""

from __future__ import annotations

import glob
import json
import os
import struct
import zlib

import numpy as np

_MAGIC = b"ZWAL1"
_REC = struct.Struct(">IBI")
FLAG_DEFLATE = 0x01


def read_segment(path: str):
    """Yields (end offset, meta, payload, offset of the blobs) for the
    segment's CRC-valid prefix."""
    with open(path, "rb") as f:
        data = f.read()
    if data[:5] != _MAGIC:
        return
    (hlen,) = struct.unpack_from(">I", data, 5)
    p = 9 + hlen
    while p + _REC.size <= len(data):
        n, flags, crc = _REC.unpack_from(data, p)
        body = data[p + _REC.size:p + _REC.size + n]
        if len(body) < n or zlib.crc32(body) != crc:
            return
        p += _REC.size + n
        payload = zlib.decompress(body) if flags & FLAG_DEFLATE else body
        (mlen,) = struct.unpack_from(">I", payload, 0)
        yield p, json.loads(payload[4:4 + mlen]), payload, 4 + mlen


def cat(arrays: list) -> np.ndarray:
    return np.concatenate(arrays) if arrays else np.zeros(0, np.int64)


def record_parts(meta: dict, payload: bytes, off: int):
    """Yields, for each part of a unit record, its spans' (trace ids,
    span ids, rows of annotations, rows of binary annotations)."""
    for cols in meta["parts"]:
        arr = {}
        for col, dtype, length in cols:
            dt = np.dtype(dtype)
            if col in ("trace_id", "span_id", "ann_span_idx",
                       "bann_span_idx"):
                arr[col] = np.frombuffer(payload, dt, length, off)
            off += dt.itemsize * length
        n = len(arr["trace_id"])
        yield (arr["trace_id"].astype(np.int64),
               arr["span_id"].astype(np.int64),
               np.bincount(arr["ann_span_idx"], minlength=n),
               np.bincount(arr["bann_span_idx"], minlength=n))


def read_wal(directory: str) -> dict:
    """Columns over every journaled span: ids, rows of annotations and
    binary annotations, and where its record ends (inode, offset)."""
    tid, sid, n_ann, n_bann, ino, end = [], [], [], [], [], []
    n_records = n_bytes = 0
    for path in sorted(glob.glob(os.path.join(directory, "wal-*.seg"))):
        inode = os.stat(path).st_ino
        n_bytes += os.path.getsize(path)
        for end_off, meta, payload, off in read_segment(path):
            n_records += 1
            for part in record_parts(meta, payload, off):
                for column, values in zip((tid, sid, n_ann, n_bann), part):
                    column.append(values)
                ino.append(np.full(len(part[0]), inode, np.int64))
                end.append(np.full(len(part[0]), end_off, np.int64))
    return {"trace_id": cat(tid), "span_id": cat(sid), "n_ann": cat(n_ann),
            "n_bann": cat(n_bann), "inode": cat(ino), "end": cat(end),
            "records": n_records, "bytes": n_bytes}


def segment_header(path: str):
    """(base_seq, where the records start), or None where the header
    cannot be read."""
    with open(path, "rb") as f:
        head = f.read(9)
        if len(head) < 9 or head[:5] != _MAGIC:
            return None
        (hlen,) = struct.unpack(">I", head[5:])
        try:
            return int(json.loads(f.read(hlen))["base_seq"]), 9 + hlen
        except (ValueError, KeyError):
            return None


def read_member(directory: str):
    """Yields (sequence, inode, end offset, meta, payload, offset of the
    blobs) over one member log's valid prefix, as the program's
    open-time scan finds it: segments in order, up to the first whose
    header cannot be read or whose ``base_seq`` leaves a hole, through
    the first that ends in a torn record."""
    expect = None
    for path in sorted(glob.glob(os.path.join(directory, "wal-*.seg"))):
        head = segment_header(path)
        if head is None or (expect is not None and head[0] != expect):
            return
        expect, valid = head
        inode = os.stat(path).st_ino
        for valid, meta, payload, off in read_segment(path):
            yield expect, inode, valid, meta, payload, off
            expect += 1
        if valid < os.path.getsize(path):
            return


def read_tree(directory: str, fsyncs: dict) -> dict:
    """read_wal's columns over every span of a COMMITTED unit of a
    sharded log tree and, in place of where its record ends, when its
    unit became durable: the latest of the first covering fsyncs of the
    unit's records, one in every member."""
    members = sorted(glob.glob(os.path.join(directory, "shard-[0-9]*"))) + [
        os.path.join(directory, "epoch")]
    tid, sid, n_ann, n_bann, seq = [], [], [], [], []
    logs, n_bytes = [], 0
    for member in members:
        rec = []
        for s, inode, end_off, meta, payload, off in read_member(member):
            rec.append((s, inode, end_off))
            for part in record_parts(meta, payload, off):
                for column, values in zip((tid, sid, n_ann, n_bann), part):
                    column.append(values)
                seq.append(np.full(len(part[0]), s, np.int64))
        logs.append(np.asarray(rec, np.int64).reshape(-1, 3))
        n_bytes += sum(os.path.getsize(p) for p in glob.glob(
            os.path.join(member, "wal-*.seg")))
    # Committed: the sequences that every member holds. Each member's
    # are without a hole, so they run from the latest first record (a
    # checkpoint's truncation may have freed older segments in some
    # members and not yet in others) to the earliest last one.
    frontier = min(int(r[-1, 0]) if len(r) else 0 for r in logs)
    first = max(int(r[0, 0]) if len(r) else 1 for r in logs)
    unit_at = np.zeros(frontier + 1)  # by sequence; 0 is no unit
    for rec in logs:
        rec = rec[(rec[:, 0] >= first) & (rec[:, 0] <= frontier)]
        unit_at[rec[:, 0]] = np.maximum(
            unit_at[rec[:, 0]], covered_at(fsyncs, rec[:, 1], rec[:, 2]))
    seq = cat(seq)
    kept = (seq >= first) & (seq <= frontier)
    return {"trace_id": cat(tid)[kept], "span_id": cat(sid)[kept],
            "n_ann": cat(n_ann)[kept], "n_bann": cat(n_bann)[kept],
            "durable_at": unit_at[seq[kept]],
            "records": max(0, frontier - first + 1),
            "bytes": n_bytes, "members": len(members),
            "cut": sum(int(((r[:, 0] > frontier) | (r[:, 0] < first)).sum())
                       for r in logs)}


def read_fsyncs(path: str) -> dict:
    """{inode: (times, sizes)} in the order the fsyncs returned."""
    out = {}
    try:
        with open(path) as f:
            rows = [ln.split() for ln in f if ln.strip()]
    except OSError:
        rows = []
    for t, inode, size in rows:
        ts, sz = out.setdefault(int(inode), ([], []))
        ts.append(float(t))
        sz.append(int(size))
    return {k: (np.asarray(t), np.maximum.accumulate(np.asarray(s)))
            for k, (t, s) in out.items()}


def covered_at(fsyncs: dict, inode, end) -> np.ndarray:
    """When each record (its segment's inode, where it ends) became
    durable: the first fsync of its segment that returned with the file
    at least that long; inf where none did."""
    at = np.full(len(inode), np.inf)
    for ino, (returned, sizes) in fsyncs.items():
        sel = inode == ino
        j = np.searchsorted(sizes, end[sel], side="left")
        at[sel] = np.append(returned, np.inf)[j]
    return at


def check(wal_dir: str, fsync_path: str, ref, ack_time: dict,
          annotations_per_span: int, binary_per_span: int, say) -> dict:
    """``ack_time``: {frame number: monotonic seconds its OK arrived}."""
    fsyncs = read_fsyncs(fsync_path)
    tree = os.path.isdir(os.path.join(wal_dir, "epoch"))
    wal = read_tree(wal_dir, fsyncs) if tree else read_wal(wal_dir)
    want_tid, want_sid, frame = ref.span_keys()
    if not len(wal["span_id"]):  # nothing journaled: one row that matches none
        wal.update({k: np.full(1, -1, np.int64) for k in (
            "trace_id", "span_id", "n_ann", "n_bann", "inode", "end",
            "durable_at")})
    order = np.argsort(wal["span_id"], kind="stable")
    sids = wal["span_id"][order]
    at = np.minimum(np.searchsorted(sids, want_sid), len(sids) - 1)
    row = order[at]
    whole = (sids[at] == want_sid) & (wal["trace_id"][row] == want_tid) \
        & (wal["n_ann"][row] == annotations_per_span) \
        & (wal["n_bann"][row] == binary_per_span)
    missing = int((~whole).sum())

    durable_at = np.where(
        whole, wal["durable_at"][row] if tree else covered_at(
            fsyncs, wal["inode"][row], wal["end"][row]), np.inf)
    acked = np.full(int(frame.max()) + 1 if len(frame) else 0, -np.inf)
    acked[list(ack_time)] = list(ack_time.values())
    late = whole & (durable_at > acked[frame])
    early = np.unique(frame[late])
    if tree:
        say(f"wal: a tree of {wal['members']} logs, {wal['records']} "
            f"units committed in all of them, {wal['cut']} records "
            "outside them")
    say(f"wal: {wal['bytes']} bytes, {wal['records']} records, "
        f"{len(wal['span_id'])} spans "
        f"journaled, {sum(len(t) for t, _ in fsyncs.values())} fsyncs; "
        f"{len(want_sid)} acked spans held against it")
    if missing:
        say(f"WRONG wal: {missing} acked spans not journaled whole, first "
            f"in call {int(frame[~whole][0])}")
    if len(early):
        lead = (durable_at - acked[frame])[late]
        say(f"WRONG wal: {len(early)} calls acked before their fsync had "
            f"returned, e.g. call {int(early[0])}; by up to "
            f"{float(np.max(lead)) * 1e3:.1f} ms")
    return {"acked_spans_not_in_wal": missing,
            "acks_before_durable": int(len(early))}
