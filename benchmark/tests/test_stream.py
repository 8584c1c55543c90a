"""The stream: a re-keyed frame decodes (by the program's own thrift
reader) to the same span with the new ids and the shifted times, and
the reference says of that span what was sent."""

import base64
import os
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gen  # noqa: E402
from reference import Reference  # noqa: E402


def frame_messages(frame: bytes) -> list:
    """The base64 messages of a Log frame, parsed here by hand."""
    (n,) = struct.unpack_from(">i", frame, 0)
    assert n == len(frame) - 4
    p = 4 + 4 + 4 + 3 + 4 + 3  # version, len, "Log", seqid, list field
    etype, count = struct.unpack_from(">bi", frame, p)
    p += 5
    out = []
    for _ in range(count):
        p += 3
        (ln,) = struct.unpack_from(">i", frame, p)
        assert frame[p + 4:p + 4 + ln] == b"zipkin"
        p += 4 + ln + 3
        (ln,) = struct.unpack_from(">i", frame, p)
        out.append(frame[p + 4:p + 4 + ln])
        p += 4 + ln + 1
    assert frame[p:p + 1] == b"\x00" and p + 1 == len(frame)
    return out


def test_rekeyed_frame_decodes_to_the_same_span_with_new_ids():
    from zipkin_tpu.wire.thrift import span_from_bytes

    s = gen.Stream(2**31 + 7, 512, 64, 8, 10_000_000, ahead=8)
    frames = {f: s.frame(f) for f in range(32)}  # four passes, made ahead
    s.close()
    assert len(s.salts) >= 4 and s.salts[0] == 0
    assert len(set(s.salts)) == len(s.salts)
    assert len(s.frames) <= 16  # taken frames are dropped
    pool = s.pool
    for f in (0, 9, 31):
        msgs = frame_messages(frames[f])
        assert len(msgs) == 64
        for j in (0, 17, 63):
            pos = f * 64 + j
            k, i = divmod(pos, pool.n)
            span, end = span_from_bytes(base64.b64decode(msgs[j]))
            salt = s.salts[k]
            assert span.trace_id == int(pool.trace_id[i]) ^ salt
            assert span.id == int(pool.span_id[i]) ^ salt
            want_parent = (int(pool.parent_id[i]) ^ salt
                           if pool.has_parent[i] else None)
            assert span.parent_id == want_parent
            assert span.name == pool.span_name(i)
            got = [(a.timestamp, a.value,
                    (a.host.ipv4, a.host.port, a.host.service_name))
                   for a in span.annotations]
            want = [(ts + k * s.pass_shift_us, v, e)
                    for ts, v, e in pool.annotations(i)]
            assert got == want
            assert [(b.key, b.value) for b in span.binary_annotations] == [
                (key, v) for key, v, _ in pool.binary_annotations(i)]


def test_same_seed_same_stream_and_trees_keep_their_shape():
    a = gen.Stream(5, 512, 64, 8, 10_000_000)
    b = gen.Stream(5, 512, 64, 8, 10_000_000)
    c = gen.Stream(6, 512, 64, 8, 10_000_000)
    fa, fb, fc = ([x.frame(n) for n in range(16)] for x in (a, b, c))
    for x in (a, b, c):
        x.close()
    assert fa == fb and fa != fc
    p = a.pool
    # a parent comes before its child, in the same trace
    kids = p.parent_pos >= 0
    assert (p.parent_pos[kids] < kids.nonzero()[0]).all()
    assert (p.trace_idx[p.parent_pos[kids]] == p.trace_idx[kids]).all()


def test_reference_reads_back_what_was_acked():
    s = gen.Stream(11, 512, 64, 8, 10_000_000)
    s.close()
    n_frames = 16
    ref = Reference(s, range(n_frames))
    assert ref.n_spans() == 1024
    tid = ref.longest_trace()
    spans = ref.trace(tid)
    assert len(spans) >= 2 and all(x["traceId"] == f"{tid:x}" for x in spans)
    # with half of the calls never acked, their spans are not expected
    half = Reference(s, range(0, n_frames, 2))
    assert half.n_spans() == 512
    total = sum(ref.dependency_calls().values())
    assert total == int((s.pool.parent_pos >= 0).sum()) * 2
    assert sum(half.dependency_calls().values()) < total
    svc = ref.services()[0]
    ranked = ref.ranked_traces(svc)
    assert ranked == sorted(ranked, key=lambda r: -r[0])
    ids = [f"{t:x}" for _, t in ranked[:10]]
    assert ref.check_query(svc, 10, ids) is None
    assert ref.check_query(svc, 10, ids[1:]) is not None


def test_only_the_newest_spans_are_held_to_be_whole():
    s = gen.Stream(11, 512, 64, 8, 10_000_000)
    s.close()
    ref = Reference(s, range(16), retained=300)  # whole calls: 256 spans
    assert ref.first_retained == 1024 - 256
    # what is kept for all time still counts every acked span
    assert ref.n_spans() == 1024
    assert sum(ref.dependency_calls().values()) == \
        int((s.pool.parent_pos >= 0).sum()) * 2
    # the longest trace is looked for among the newest spans only
    tid = ref.longest_trace()
    newest = {ref.trace_id_of(n) for n in range(ref.first_retained, 1024)}
    assert tid in newest
    tids, sids, frame = ref.span_keys()
    assert len(tids) == 1024 and frame[0] == 0 and frame[-1] == 15
    assert tids[-1] == ref.trace_id_of(1023)
