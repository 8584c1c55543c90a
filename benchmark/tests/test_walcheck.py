"""walcheck reads the log that the program's own writer makes, finds
every journaled span with its rows, and tells an ack that came before
its fsync from one that came after."""

import os
import sys
import time

import numpy as np
import pytest

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


# -- the sharded log tree (wal/sharded.py) --------------------------------

UNITS, SHARDS, PER_PART = 3, 2, 100


def first_id(unit: int, shard: int) -> int:
    return 10_000 * (unit + 1) + 1_000 * shard


def write_tree(wal_dir: str):
    """Three launch units over two shards, written by the program's own
    ShardedWal into segments so small that they roll. Returns, for each
    unit, where its record ends in every member (inode, offset), the
    shard logs first and the epoch log last."""
    from zipkin_tpu.wal.sharded import ShardedWal

    wal = ShardedWal(wal_dir, SHARDS, fsync="off", segment_bytes=4096,
                     compress=False)  # a part record a segment
    ends = []
    for u in range(UNITS):
        parts = [(batch(first_id(u, s), PER_PART),
                  np.zeros(PER_PART, np.int32), np.ones(PER_PART, bool))
                 for s in range(SHARDS)]
        assert wal.append_unit(parts, [0] * 6, {}) == u + 1
        segs = [log._segments[-1] for log in wal.shards + [wal.epoch]]
        ends.append([(os.stat(g.path).st_ino, g.nbytes) for g in segs])
    assert len(wal.shards[0]._segments) > 1  # they rolled
    wal.close()
    return ends


def tree_ref():
    sids = np.concatenate([
        np.arange(first_id(u, s), first_id(u, s) + PER_PART)
        for u in range(UNITS) for s in range(SHARDS)])
    return FakeRef(sids * 7, sids,
                   np.repeat(np.arange(UNITS), SHARDS * PER_PART))


def write_journal(path: str, ends, t: float, late=()):
    """Every record fsynced at t + 1, but (unit, member) ``late`` at
    t + 9."""
    with open(path, "w") as out:
        for u, members in enumerate(ends):
            for m, (inode, size) in enumerate(members):
                at = t + (9 if (u, m) in late else 1)
                out.write(f"{at:.6f} {inode} {size}\n")


def test_a_tree_all_journaled_and_durable_reads_nought(tmp_path):
    wal_dir, journal = str(tmp_path / "wal"), str(tmp_path / "fsyncs.txt")
    ends = write_tree(wal_dir)
    t = time.monotonic()
    write_journal(journal, ends, t)
    acks = {u: t + 5 for u in range(UNITS)}
    lines = []
    r = walcheck.check(wal_dir, journal, tree_ref(), acks, 6, 2, lines.append)
    assert r == {"acked_spans_not_in_wal": 0, "acks_before_durable": 0}
    assert "3 units committed" in lines[0]
    # an ack that beat the fsyncs of its unit is seen
    acks[1] = t + 0.5
    r = walcheck.check(wal_dir, journal, tree_ref(), acks, 6, 2, lines.append)
    assert r == {"acked_spans_not_in_wal": 0, "acks_before_durable": 1}
    # a row short of what was sent is not whole
    r = walcheck.check(wal_dir, journal, tree_ref(), acks, 5, 2, lines.append)
    assert r["acked_spans_not_in_wal"] == UNITS * SHARDS * PER_PART


@pytest.mark.parametrize("member", [0, 1, SHARDS])
def test_an_fsync_of_any_member_after_the_ack_is_seen(tmp_path, member):
    """The ack has to wait for the fsyncs of all n + 1 logs: shard 0's,
    shard 1's and the epoch log's, whichever comes last."""
    wal_dir, journal = str(tmp_path / "wal"), str(tmp_path / "fsyncs.txt")
    ends = write_tree(wal_dir)
    t = time.monotonic()
    write_journal(journal, ends, t, late={(1, member)})
    acks = {u: t + 5 for u in range(UNITS)}
    r = walcheck.check(wal_dir, journal, tree_ref(), acks, 6, 2,
                       lambda m: None)
    assert r == {"acked_spans_not_in_wal": 0, "acks_before_durable": 1}


@pytest.mark.parametrize("member", ["epoch", "shard-001"])
def test_a_unit_short_of_one_record_is_not_committed(tmp_path, member):
    """An epoch record cut off the tail, or the part record of one
    shard: the unit is in no committed prefix, and the program's own
    open-time alignment cuts it from every member."""
    from zipkin_tpu.wal.sharded import ShardedWal

    wal_dir, journal = str(tmp_path / "wal"), str(tmp_path / "fsyncs.txt")
    ends = write_tree(wal_dir)
    t = time.monotonic()
    write_journal(journal, ends, t)
    last = sorted(os.listdir(os.path.join(wal_dir, member)))[-1]
    path = os.path.join(wal_dir, member, last)
    os.truncate(path, os.path.getsize(path) - 1)  # a torn last record
    acks = {u: t + 5 for u in range(UNITS)}
    lines = []
    r = walcheck.check(wal_dir, journal, tree_ref(), acks, 6, 2, lines.append)
    assert r == {"acked_spans_not_in_wal": SHARDS * PER_PART,
                 "acks_before_durable": 0}
    assert "2 units committed" in lines[0]
    assert "first in call 2" in "".join(lines)
    reopened = ShardedWal(wal_dir, SHARDS, fsync="off")
    assert reopened.last_seq == 2  # the program cuts the same unit
    reopened.close()


def test_a_unit_freed_from_the_front_of_one_member_is_not_in_the_log(tmp_path):
    """A checkpoint's truncation frees whole segments from the front,
    member by member: a unit that one member no longer holds is in no
    committed range, so its spans read as not journaled (as a flat log
    that lost its first segment reads), never as acked too early."""
    wal_dir, journal = str(tmp_path / "wal"), str(tmp_path / "fsyncs.txt")
    ends = write_tree(wal_dir)
    t = time.monotonic()
    write_journal(journal, ends, t)
    shard = os.path.join(wal_dir, "shard-000")
    os.remove(os.path.join(shard, sorted(os.listdir(shard))[0]))  # unit 1
    acks = {u: t + 5 for u in range(UNITS)}
    lines = []
    r = walcheck.check(wal_dir, journal, tree_ref(), acks, 6, 2, lines.append)
    assert r == {"acked_spans_not_in_wal": SHARDS * PER_PART,
                 "acks_before_durable": 0}
    assert "2 units committed" in lines[0]
    assert "first in call 0" in "".join(lines)
