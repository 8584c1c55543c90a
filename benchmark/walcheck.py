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
  valid prefix counts, as at recovery;
- the fsync journal that ``daemon_entry.py`` keeps round ``os.fsync``:
  one line ``monotonic seconds, inode, file size`` for every fsync that
  returned. ``time.monotonic`` is CLOCK_MONOTONIC, one clock for every
  process of the machine, so those seconds and the client's ack times
  compare.

Numbers (exact, limit 0 each):

- ``acked_spans_not_in_wal``: acked spans that the log does not hold
  with their ids and all their annotation and binary-annotation rows;
- ``acks_before_durable``: acked calls of which some record was not
  covered, when the ack arrived, by an fsync that had returned.
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
            for cols in meta["parts"]:
                arr = {}
                for col, dtype, length in cols:
                    dt = np.dtype(dtype)
                    if col in ("trace_id", "span_id", "ann_span_idx",
                               "bann_span_idx"):
                        arr[col] = np.frombuffer(payload, dt, length, off)
                    off += dt.itemsize * length
                n = len(arr["trace_id"])
                tid.append(arr["trace_id"].astype(np.int64))
                sid.append(arr["span_id"].astype(np.int64))
                n_ann.append(np.bincount(arr["ann_span_idx"], minlength=n))
                n_bann.append(np.bincount(arr["bann_span_idx"], minlength=n))
                ino.append(np.full(n, inode, np.int64))
                end.append(np.full(n, end_off, np.int64))
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0, np.int64))
    return {"trace_id": cat(tid), "span_id": cat(sid), "n_ann": cat(n_ann),
            "n_bann": cat(n_bann), "inode": cat(ino), "end": cat(end),
            "records": n_records, "bytes": n_bytes}


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


def check(wal_dir: str, fsync_path: str, ref, ack_time: dict,
          annotations_per_span: int, binary_per_span: int, say) -> dict:
    """``ack_time``: {frame number: monotonic seconds its OK arrived}."""
    wal = read_wal(wal_dir)
    want_tid, want_sid, frame = ref.span_keys()
    if not len(wal["span_id"]):  # nothing journaled: one row that matches none
        wal.update({k: np.full(1, -1, np.int64) for k in (
            "trace_id", "span_id", "n_ann", "n_bann", "inode", "end")})
    order = np.argsort(wal["span_id"], kind="stable")
    sids = wal["span_id"][order]
    at = np.minimum(np.searchsorted(sids, want_sid), len(sids) - 1)
    row = order[at]
    whole = (sids[at] == want_sid) & (wal["trace_id"][row] == want_tid) \
        & (wal["n_ann"][row] == annotations_per_span) \
        & (wal["n_bann"][row] == binary_per_span)
    missing = int((~whole).sum())

    # when each journaled span's record became durable: the first fsync
    # of its segment that returned with the file at least that long
    fsyncs = read_fsyncs(fsync_path)
    durable_at = np.full(len(want_sid), np.inf)
    for inode, (returned, sizes) in fsyncs.items():
        sel = whole & (wal["inode"][row] == inode)
        j = np.searchsorted(sizes, wal["end"][row[sel]], side="left")
        durable_at[sel] = np.append(returned, np.inf)[j]
    acked = np.full(int(frame.max()) + 1 if len(frame) else 0, -np.inf)
    acked[list(ack_time)] = list(ack_time.values())
    late = whole & (durable_at > acked[frame])
    early = np.unique(frame[late])
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
