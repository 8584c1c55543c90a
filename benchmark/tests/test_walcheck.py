"""walcheck reads the log that the program's own writer makes, finds
every journaled span with its rows, and tells an ack that came before
its fsync from one that came after."""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import walcheck  # noqa: E402


class FakeRef:
    def __init__(self, tids, sids, frames):
        self.keys = (np.asarray(tids), np.asarray(sids), np.asarray(frames))

    def span_keys(self):
        return self.keys


def batch(first_id: int, n: int):
    from zipkin_tpu.columnar.schema import SpanBatch

    cols = {}
    for c in SpanBatch.SPAN_COLUMNS:
        cols[c] = np.zeros(n, np.int64)
    cols["trace_id"] = np.arange(first_id, first_id + n) * 7
    cols["span_id"] = np.arange(first_id, first_id + n)
    for c in SpanBatch.ANN_COLUMNS + SpanBatch.BANN_COLUMNS:
        per = 6 if c in SpanBatch.ANN_COLUMNS else 2
        cols[c] = (np.repeat(np.arange(n, dtype=np.int32), per)
                   if c.endswith("span_idx") else np.zeros(n * per, np.int32))
    return SpanBatch(**cols)


def test_the_log_is_held_against_the_acks(tmp_path):
    from zipkin_tpu.wal.log import WriteAheadLog
    from zipkin_tpu.wal.record import encode_unit

    wal_dir, journal = str(tmp_path / "wal"), str(tmp_path / "fsyncs.txt")
    wal = WriteAheadLog(wal_dir, fsync="off", segment_bytes=4096)
    ends = []
    for f in range(3):  # three calls of 100 spans; the small segments roll
        part = (batch(1000 * (f + 1), 100), np.zeros(100, np.int32),
                np.ones(100, bool))
        wal.append(encode_unit([part], [0] * 6, {}))
        seg = wal._segments[-1]
        ends.append((os.stat(seg.path).st_ino, seg.nbytes))
    wal.close()
    t = time.monotonic()
    # calls 0 and 1 fsynced at t+1 and t+2; call 2 never
    with open(journal, "w") as out:
        for f in (0, 1):
            out.write(f"{t + 1 + f:.6f} {ends[f][0]} {ends[f][1]}\n")

    got = walcheck.read_wal(wal_dir)
    assert got["records"] == 3 and len(got["span_id"]) == 300
    assert (got["n_ann"] == 6).all() and (got["n_bann"] == 2).all()

    sids = np.concatenate([np.arange(1000 * (f + 1), 1000 * (f + 1) + 100)
                           for f in range(3)])
    ref = FakeRef(sids * 7, sids, np.repeat(np.arange(3), 100))
    lines = []
    late = {0: t + 5, 1: t + 5, 2: t + 5}
    r = walcheck.check(wal_dir, journal, ref, late, 6, 2, lines.append)
    assert r == {"acked_spans_not_in_wal": 0, "acks_before_durable": 1}
    early = {0: t + 1.5, 1: t + 1.5, 2: t + 5}  # call 1 acked before t+2
    r = walcheck.check(wal_dir, journal, ref, early, 6, 2, lines.append)
    assert r["acks_before_durable"] == 2
    # a span the log does not hold, and one held with a row short
    ref = FakeRef(np.append(sids * 7, 5), np.append(sids, 5),
                  np.append(np.repeat(np.arange(3), 100), 2))
    r = walcheck.check(wal_dir, journal, ref, late, 6, 2, lines.append)
    assert r["acked_spans_not_in_wal"] == 1
    r = walcheck.check(wal_dir, journal, ref, late, 5, 2, lines.append)
    assert r["acked_spans_not_in_wal"] == 301


def test_an_empty_log_holds_nothing(tmp_path):
    ref = FakeRef([7, 14], [1, 2], [0, 0])
    r = walcheck.check(str(tmp_path), str(tmp_path / "none.txt"), ref,
                       {0: 1.0}, 6, 2, lambda m: None)
    assert r == {"acked_spans_not_in_wal": 2, "acks_before_durable": 0}
