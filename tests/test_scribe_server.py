"""Raw-TCP framed-thrift scribe endpoint: a real client socket → spans
land in the store (reference: ScribeSpanReceiver.scala:69-141)."""

import base64

import pytest

from zipkin_tpu.ingest.collector import Collector
from zipkin_tpu.ingest.receiver import ResultCode, ScribeReceiver
from zipkin_tpu.ingest.scribe_server import (
    ScribeClient,
    ScribeServer,
    decode_log_reply,
    encode_log_call,
    handle_call,
)
from zipkin_tpu.models.span import Annotation, Endpoint, Span
from zipkin_tpu.store.memory import InMemorySpanStore
from zipkin_tpu.wire.thrift import ThriftError, span_to_bytes

EP = Endpoint(0x0A000001, 80, "svc")


def make_span(tid, sid):
    return Span(trace_id=tid, name="op", id=sid,
                annotations=(Annotation(10, "sr", EP),
                             Annotation(20, "ss", EP)))


def entry_for(span):
    return ("zipkin", base64.b64encode(span_to_bytes(span)).decode())


class TestFrameCodec:
    def test_roundtrip_call_reply(self):
        store = InMemorySpanStore()
        collector = Collector(store, max_queue=10, concurrency=1)
        rx = ScribeReceiver(collector.accept)
        frame = encode_log_call([entry_for(make_span(1, 1))], seqid=7)
        reply = handle_call(rx, frame[4:])  # strip length prefix
        assert decode_log_reply(reply) == ResultCode.OK
        collector.flush()
        assert store.get_spans_by_trace_ids([1])
        collector.close()

    def test_unknown_method_gets_exception(self):
        rx = ScribeReceiver(lambda spans: None)
        frame = encode_log_call([], seqid=1)
        # Rewrite method name "Log" -> "Nop" (same length).
        bad = frame[4:].replace(b"Log", b"Nop", 1)
        reply = handle_call(rx, bad)
        with pytest.raises(ThriftError):
            decode_log_reply(reply)


class TestTcpEndToEnd:
    def test_client_to_store_over_socket(self):
        store = InMemorySpanStore()
        collector = Collector(store, max_queue=100, concurrency=2)
        rx = ScribeReceiver(collector.accept)
        server = ScribeServer(rx, host="127.0.0.1", port=0)
        server.serve_in_thread()
        host, port = server.server_address
        client = ScribeClient(host, port)
        try:
            spans = [make_span(i, 1) for i in range(1, 6)]
            code = client.log([entry_for(s) for s in spans])
            assert code == ResultCode.OK
            collector.flush()
            for s in spans:
                got = store.get_spans_by_trace_ids([s.trace_id])
                assert got and got[0][0].trace_id == s.trace_id
            assert rx.stats["received"] == 5
        finally:
            client.close()
            server.shutdown()
            collector.close()

    def test_pushback_try_later(self):
        import threading

        store = InMemorySpanStore()
        gate = threading.Event()
        collector = Collector(store, max_queue=1, concurrency=1)
        orig_apply = store.apply
        store.apply = lambda spans: (gate.wait(5), orig_apply(spans))[1]
        rx = ScribeReceiver(collector.accept)
        server = ScribeServer(rx, host="127.0.0.1", port=0)
        server.serve_in_thread()
        host, port = server.server_address
        client = ScribeClient(host, port)
        try:
            codes = set()
            for i in range(20):
                codes.add(client.log([entry_for(make_span(100 + i, 1))]))
            assert ResultCode.TRY_LATER in codes  # queue filled -> pushback
            gate.set()
        finally:
            client.close()
            server.shutdown()
            gate.set()
            collector.close()


# -- the native frame decode against the python one ----------------------
#
# handle_call decodes a Log frame in one native call (off the GIL) where
# the receiver keeps payloads as raw thrift and the library loads;
# _parse_log_args + ScribeReceiver.decode stay the definition. Each case
# below is one frame given to both: same payload bytes, same entry
# boundaries, same received / ignored / bad, same reply or ThriftError.

import importlib.util  # noqa: E402
import os  # noqa: E402
import struct  # noqa: E402

from zipkin_tpu import native  # noqa: E402
from zipkin_tpu.wire.thrift import (  # noqa: E402
    T_I32, T_I64, T_LIST, T_MAP, T_STRING, T_STRUCT,
)

HEADER = encode_log_call([], seqid=3)[4:4 + 4 + 4 + 3 + 4]
OLD_HEADER = struct.pack(">i", 3) + b"Log" + b"\x01" + struct.pack(">i", 3)


def _fh(ftype, fid):
    return struct.pack(">bh", ftype, fid)


def _s(b):
    return struct.pack(">i", len(b)) + b


def _entry(category=b"zipkin", message=b"", extra=b""):
    out = b""
    if category is not None:
        out += _fh(T_STRING, 1) + _s(category)
    out += extra
    if message is not None:
        out += _fh(T_STRING, 2) + _s(message)
    return out + b"\x00"


def _args(entries, before=b"", after=b"", etype=T_STRUCT, count=None):
    n = len(entries) if count is None else count
    return (before + _fh(T_LIST, 1) + struct.pack(">bi", etype, n)
            + b"".join(entries) + after + b"\x00")


def _b64(i, n=40):
    return base64.b64encode(bytes((i * 7 + k) % 256 for k in range(n)))


def _benchmark_frame():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "gen.py")
    spec = importlib.util.spec_from_file_location("benchmark_gen", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    stream = gen.Stream(2147485301, 2048, 2048, 64, 10_000_000, ahead=1)
    try:
        return stream.frame(0)[4:]
    finally:
        stream.close()


GOOD = [_entry(message=_b64(i, 30 + i)) for i in range(7)]
NESTED = _fh(T_LIST, 9) + struct.pack(">bi", T_MAP, 1) + struct.pack(
    ">bbi", T_I32, T_STRING, 1) + struct.pack(">i", 5) + _s(b"v")
DEEP = (_fh(T_STRUCT, 7) * 70) + b"\x00" * 70

# name -> (frame after the length prefix, sent back to python's base64)
FRAMES = {
    "benchmark-shaped": (_benchmark_frame, 0),
    "mixed-categories": (HEADER + _args([
        _entry(b"zipkin", _b64(1)), _entry(b"other", _b64(2)),
        _entry(b"ZipKin", _b64(3)), _entry(b"ZIPKIN", _b64(4)),
        _entry(b"zipkin2", _b64(5)), _entry(b"", _b64(6)),
        _entry(None, _b64(7)), _entry(b"zipki", _b64(8))]), 0),
    "unknown-field-in-struct": (HEADER + _args(
        GOOD, before=_fh(T_I64, 5) + b"\x00" * 8 + NESTED,
        after=_fh(T_STRING, 2) + _s(b"trailer")), 0),
    "unknown-field-in-entry": (HEADER + _args([
        _entry(message=_b64(1), extra=_fh(T_I32, 3) + b"\x00\x00\x00\x07"),
        _entry(message=_b64(2), extra=NESTED),
        # field 1 and 2 of another type are unknown fields too
        _entry(message=_b64(3), extra=_fh(T_I32, 2) + b"\x00\x00\x00\x01"),
        _entry(None, _b64(4), extra=_fh(T_I64, 1) + b"\x00" * 8)]), 0),
    "empty-list": (HEADER + _args([]), 0),
    "no-list-at-all": (HEADER + b"\x00", 0),
    "two-lists": (HEADER + _args(GOOD[:3])[:-1] + _args(GOOD[3:]), 0),
    "repeated-fields-last-wins": (HEADER + _args([
        _entry(b"other", _b64(1), extra=_fh(T_STRING, 1) + _s(b"zipkin")
               + _fh(T_STRING, 2) + _s(b"!!"))]), 0),
    "empty-and-absent-message": (HEADER + _args([
        _entry(message=b""), _entry(message=None),
        _entry(message=_b64(1))]), 0),
    "whitespace": (HEADER + _args([
        _entry(message=_b64(1)[:20] + b"\n" + _b64(1)[20:]),
        _entry(message=_b64(2, 41) + b"\r\n"),
        _entry(message=b" " + _b64(3)), GOOD[0]]), 3),
    "missing-padding": (HEADER + _args([
        _entry(message=_b64(1, 40).rstrip(b"=")),       # 54 chars: bad
        _entry(message=_b64(2, 41).rstrip(b"=")),       # 55 chars: bad
        _entry(message=_b64(3, 40)[:-1]), GOOD[1],
        _entry(message=b"A")]), 4),
    "wrong-padding": (HEADER + _args([
        _entry(message=_b64(1, 42) + b"="), _entry(message=_b64(2, 42) + b"=="),
        _entry(message=_b64(3, 40) + b"===="), _entry(message=b"===="),
        _entry(message=b"QQ=="), _entry(message=b"QQ=Q"),
        _entry(message=b"Q==="), _entry(message=b"QUJD=QUJD"),
        _entry(message=b"QUI=QUJD"), GOOD[2]]), 8),
    "non-alphabet-byte": (HEADER + _args([
        _entry(message=_b64(1)[:9] + b"!" + _b64(1)[9:]),
        _entry(message=_b64(2)[:8] + b"-_*." + _b64(2)[12:]),
        _entry(message=b"\x00\x00\x00\x00"), GOOD[3]]), 3),
    "non-ascii-byte": (HEADER + _args([
        GOOD[4], _entry(message=_b64(1)[:8] + b"\xc3\xa9" + _b64(1)[8:]),
        _entry(message=b"\xff" + _b64(2)[1:]), GOOD[5]]), 2),
    # a category the walk will not judge: the whole frame is python's
    "non-utf8-category": (HEADER + _args([
        _entry(b"zipkin\xff", _b64(1)), GOOD[6]]), None),
    "non-ascii-category": (HEADER + _args([
        _entry("ZİPKİN".encode(), _b64(1)), GOOD[0]]), None),
    "old-style-header": (OLD_HEADER + _args(GOOD), 0),
    "trailing-bytes": (HEADER + _args(GOOD) + b"\x0bgarbage", 0),
    # malformed: python raises, and so the native walk must not answer
    "bad-element-type": (HEADER + _args(GOOD, etype=T_STRING), None),
    "negative-count": (HEADER + _args([], count=-1), None),
    "nesting-too-deep": (HEADER + _args(GOOD, before=DEEP), None),
    "unknown-type": (HEADER + _args(GOOD, before=_fh(1, 4)), None),
    "negative-string-length": (HEADER + _args([
        _fh(T_STRING, 1) + struct.pack(">i", -2) + b"\x00"]), None),
}
MALFORMED = {"bad-element-type", "negative-count", "nesting-too-deep",
             "unknown-type", "negative-string-length"}
_WHOLE = HEADER + _args([
    _entry(b"zipkin", _b64(1), extra=_fh(T_I32, 3) + b"\x00\x00\x00\x07")],
    before=_fh(T_I64, 5) + b"\x00" * 8)
_LIST_AT = len(HEADER) + 11
# cut inside: the struct's unknown i64, the list's header, the entry's
# category header / length / bytes, its unknown i32, its message length /
# bytes, before the entry's stop, before the struct's stop
for _name, _cut in [("unknown-i64", len(HEADER) + 6), ("list-field", _LIST_AT + 2),
                    ("list-header", _LIST_AT + 6), ("category-header", _LIST_AT + 9),
                    ("category-length", _LIST_AT + 13),
                    ("category-bytes", _LIST_AT + 18), ("unknown-i32", _LIST_AT + 26),
                    ("message-length", _LIST_AT + 33),
                    ("message-bytes", _LIST_AT + 50), ("entry-stop", len(_WHOLE) - 2),
                    ("struct-stop", len(_WHOLE) - 1), ("count-overstated", None)]:
    FRAMES["truncated-" + _name] = (
        _WHOLE[:_cut] if _cut else HEADER + _args(GOOD, count=len(GOOD) + 1),
        None)


def _decode_both(frame, monkeypatch):
    """handle_call on one frame, natively and with the library patched
    away: (reply or error, payloads by entry, joined, stats, frames)."""
    out = []
    for lib in (True, False):
        got = []
        rx = ScribeReceiver(lambda spans: None, categories=("Zipkin",),
                            process_thrift=got.append)
        with monkeypatch.context() as m:
            if not lib:
                m.setattr(native, "available", lambda: False)
            try:
                reply = handle_call(rx, frame)
            except ThriftError as e:
                reply = ("ThriftError", str(e))
        payload = got[0] if got else []
        joined = (payload.joined() if isinstance(payload, native.LogSegments)
                  else b"".join(payload))
        out.append((reply, list(payload), joined, rx.stats, rx.frames))
    return out


@pytest.mark.parametrize("name", list(FRAMES))
def test_native_frame_decode_matches_python(name, monkeypatch):
    frame, sent_back = FRAMES[name]
    if callable(frame):
        frame = frame()
    (reply, entries, joined, stats, frames), python = _decode_both(
        frame, monkeypatch)
    assert (reply, entries, joined, stats) == python[:4]
    assert joined == b"".join(entries)
    assert isinstance(reply, tuple) == (
        name.startswith("truncated-") or name in MALFORMED)
    assert python[4] == {"native": 0, "sent_back": 0,
                         "python": 0 if isinstance(reply, tuple) else 1}
    if sent_back is None:  # the native walk gave the frame back whole
        assert frames == python[4]
    else:
        assert frames == {"native": 1, "python": 0, "sent_back": sent_back}
        assert stats["received"] > 0 or name in ("empty-list", "no-list-at-all")


def test_truncated_cases_cover_every_cut():
    """Every prefix of a frame with each field kind in it: python raises
    for each but the whole, and the native walk answers none of them."""
    rx = ScribeReceiver(lambda spans: None, process_thrift=lambda p: None)
    pos = len(HEADER)
    for cut in range(pos, len(_WHOLE)):
        assert native.decode_log(_WHOLE[:cut], pos, rx.categories) is None
        with pytest.raises(ThriftError):
            handle_call(rx, _WHOLE[:cut])
    assert rx.frames == {"native": 0, "python": 0, "sent_back": 0}
    assert handle_call(rx, _WHOLE) and rx.frames["native"] == 1


def test_python_door_keeps_the_python_decode():
    """Without a raw-thrift sink the payloads are Span objects: the
    python decode, whatever the library."""
    got = []
    rx = ScribeReceiver(got.append)
    frame = encode_log_call([entry_for(make_span(5, 1)), ("zipkin", "!!")])
    assert decode_log_reply(handle_call(rx, frame[4:])) == ResultCode.OK
    assert [s.trace_id for s in got[0]] == [5]
    assert rx.stats["bad"] == 1
    assert rx.frames == {"native": 0, "python": 1, "sent_back": 0}


# -- the TCP door into a device store, natively and without the library --


def _served(tmp_path, monkeypatch, lib, spans, extra=()):
    """One Log call of ``spans`` (+ raw ``extra`` entries) through the
    TCP door of the daemon's own wiring; what the store holds and what
    /metrics says of the door."""
    from zipkin_tpu.testing.scribe_rig import ScribeRig, log_entries

    if not lib:
        monkeypatch.setattr(native, "available", lambda: False)
    rig = ScribeRig(str(tmp_path / ("wal-native" if lib else "wal-python")))
    try:
        rig.receiver.export_stats(rig.registry, "tcp")
        entries = log_entries(spans)
        entries[1:1] = extra
        assert rig.client.log(entries) == ResultCode.OK
        stored = rig.store.get_spans_by_trace_ids(
            [s.trace_id for s in spans])
        text = rig.registry.render_text()
        bad = rig.collector.bad_payloads
    finally:
        rig.close()
    door = {line.rsplit(" ", 1)[0]: line.rsplit(" ", 1)[1]
            for line in text.splitlines() if line.startswith("zipkin_scribe_")}
    return stored, door, bad


@pytest.mark.parametrize("lib", [True, False], ids=["native", "python"])
def test_served_round_trip(tmp_path, monkeypatch, lib):
    spans = [make_span(i, 1) for i in range(1, 9)]
    stored, door, bad = _served(
        tmp_path, monkeypatch, lib, spans,
        extra=[("other", "aWdub3JlZA=="), ("zipkin", "Zm9vA")])
    assert [t[0] for t in stored] == spans and bad == 0
    tcp = 'zipkin_scribe_entries{transport="tcp",result="%s"}'
    assert [door[tcp % k] for k in ("received", "ignored", "bad")] == [
        "10", "1", "1"]
    assert door['zipkin_scribe_frames{transport="tcp",decode="native"}'] \
        == ("1" if lib else "0")
    assert door['zipkin_scribe_frames{transport="tcp",decode="python"}'] \
        == ("0" if lib else "1")
    assert door['zipkin_scribe_entries_sent_back{transport="tcp"}'] \
        == ("1" if lib else "0")


def test_thrift_corrupt_entry_costs_only_itself(tmp_path, monkeypatch):
    """Good base64 of corrupt thrift among good entries: the joined
    parse fails, and the segments' entry boundaries let the collector
    decode entry by entry (``_decode_segments_slow``)."""
    spans = [make_span(i, 1) for i in range(1, 6)]
    corrupt = ("zipkin", base64.b64encode(b"\xff\xfecorrupt").decode())
    stored, door, bad = _served(tmp_path, monkeypatch, True, spans,
                                extra=[corrupt])
    assert [t[0] for t in stored] == spans and bad == 1
    assert door['zipkin_scribe_entries{transport="tcp",result="bad"}'] == "0"
    assert door['zipkin_scribe_frames{transport="tcp",decode="native"}'] == "1"
    assert door['zipkin_scribe_entries_sent_back{transport="tcp"}'] == "0"
